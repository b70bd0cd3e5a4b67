"""`make serving-smoke`: the continuous-batching acceptance loop on the CPU
mesh.

32 mixed-length, mixed-budget requests through a tiny Llama, twice:

- **static** — gang-scheduled batches of ``N_SLOTS`` through ``generate()``
  (left-padded to the batch max prompt, every row running the batch max
  budget) — today's default serving story;
- **serving** — the same request set through :class:`ServingEngine`
  (dense slot cache, chunked prefill, continuous admission).

Asserts: every request completes; per-request continuations are BIT-EQUAL
between the two paths; the engine's decode steady state is ONE executable
with zero post-warmup recompiles; and the engine's aggregate tokens/s is
strictly higher than the static baseline's.
"""

import json
import sys
import time

import numpy as np

N_REQUESTS = 32
N_SLOTS = 8


def main():
    print(json.dumps({"row": "start", "requests": N_REQUESTS}), flush=True)

    import jax
    import jax.numpy as jnp

    from accelerate_tpu import Model, ServingConfig, ServingEngine, generate
    from accelerate_tpu import generation as G
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.utils import set_seed

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    probe = rng.integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), probe)

    # Mixed traffic: short and long prompts, chatty and terse budgets — the
    # shape of real mixed-user load, and the worst case for gang scheduling
    # (every batch row pays the batch max).
    lengths = rng.integers(3, 48, N_REQUESTS)
    budgets = np.where(
        rng.random(N_REQUESTS) < 0.5,
        rng.integers(4, 8, N_REQUESTS),
        rng.integers(40, 64, N_REQUESTS),
    ).astype(int)
    prompts = [
        rng.integers(1, cfg.vocab_size, (int(n),), dtype=np.int32) for n in lengths
    ]
    useful_tokens = int(budgets.sum())

    # --- Phase 1: static-batch generate() ---------------------------------
    G.clear_generation_cache()
    t0 = time.perf_counter()
    static_rows = {}
    for i0 in range(0, N_REQUESTS, N_SLOTS):
        batch = list(range(i0, min(i0 + N_SLOTS, N_REQUESTS)))
        smax = max(len(prompts[i]) for i in batch)
        bmax = int(max(budgets[i] for i in batch))
        ids = np.zeros((len(batch), smax), np.int32)
        mask = np.zeros((len(batch), smax), np.int32)
        for r, i in enumerate(batch):
            p = prompts[i]
            ids[r, smax - len(p):] = p
            mask[r, smax - len(p):] = 1
        out = np.asarray(
            generate(model, ids, max_new_tokens=bmax, attention_mask=mask)
        )
        for r, i in enumerate(batch):
            static_rows[i] = out[r, smax:smax + int(budgets[i])]
    static_s = time.perf_counter() - t0
    static_execs = sum(
        int(fn._cache_size()) for fn in G._GEN_LOOP_CACHE.values()
        if callable(getattr(fn, "_cache_size", None))
    )
    static_tps = useful_tokens / static_s
    print(json.dumps({
        "row": "static", "seconds": round(static_s, 3),
        "useful_tokens": useful_tokens, "tokens_per_s": round(static_tps, 2),
        "compiled_executables": static_execs,
    }), flush=True)

    # --- Phase 2: ServingEngine -------------------------------------------
    engine = ServingEngine(
        model,
        ServingConfig(n_slots=N_SLOTS, max_len=128, prefill_chunks=[8, 16, 32]),
    )
    t0 = time.perf_counter()
    outs = engine.run(prompts, max_new_tokens=[int(b) for b in budgets])
    serve_s = time.perf_counter() - t0
    stats = engine.stats()
    serve_tps = useful_tokens / serve_s
    print(json.dumps({
        "row": "serving", "seconds": round(serve_s, 3),
        "useful_tokens": useful_tokens, "tokens_per_s": round(serve_tps, 2),
        "ttft_p50_s": round(stats["ttft_p50_s"], 4),
        "ttft_p95_s": round(stats["ttft_p95_s"], 4),
        "decode_executables": stats["decode_executables"],
        "prefill_executables": stats["prefill_executables"],
        "steady_recompiles": stats["steady_recompiles"],
        "mean_occupancy": stats["mean_occupancy"],
        "slot_reuses": stats["slot_reuses"],
    }), flush=True)

    # --- Acceptance ---------------------------------------------------------
    assert stats["requests_completed"] == N_REQUESTS, (
        f"only {stats['requests_completed']}/{N_REQUESTS} requests completed"
    )
    mismatched = [
        i for i in range(N_REQUESTS)
        if not np.array_equal(
            outs[i][len(prompts[i]):len(prompts[i]) + int(budgets[i])],
            static_rows[i],
        )
    ]
    assert not mismatched, f"engine != generate() for requests {mismatched}"
    assert stats["decode_executables"] == 1, (
        f"decode compiled {stats['decode_executables']} executables, want 1"
    )
    assert stats["steady_recompiles"] == 0, (
        f"{stats['steady_recompiles']} steady-state recompiles, want 0"
    )
    assert serve_tps > static_tps, (
        f"serving {serve_tps:.2f} tok/s did not beat static {static_tps:.2f}"
    )
    print(json.dumps({
        "row": "ok",
        "speedup": round(serve_tps / static_tps, 2),
        "outputs_bit_equal": True,
    }), flush=True)


if __name__ == "__main__":
    sys.exit(main())
