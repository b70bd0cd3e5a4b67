"""Benchmark: Llama FSDP training throughput on the chip, in ONE process.

    python bench.py

Runs on a TPU or not at all: with no TPU backend it exits nonzero before
measuring anything, it never substitutes a smaller model, and a device whose
peak is not in ``PEAKS`` is an error, not a default. Every phase is fatal —
an exception in any of them is a nonzero exit, not a row with an error field.
Every row names the device it ran on (``platform``, ``device_kind``,
``device_count``).

Phases, one JSON row each (flushed as it completes), then the final row:

1. seq 2048: the ~1.06B Llama (hidden 2048, inter 5632, 18 layers, 16 x 128
   heads, vocab 32000), bf16 compute, bf16 params + bf16 Adam moments (what
   fits 16 GB of HBM beside the gradients), FSDP over all local chips, the
   Pallas flash kernel under the "dots" remat policy (keep matmul outputs,
   recompute elementwise).
2. seq 8192: same model under the leaner "flash" policy.
3. decode: bf16 vs int8 weight-only ``generate()`` on one chip's worth of
   the same model.

The batch is per chip, so the global batch always divides the dp axes. Timed
windows start after two warm-up steps and end in ``block_until_ready``.
``chip_smoke.py`` builds its trainer through :func:`llama_1b` and
:func:`build_trainer` too, so the smoke and the bench run the same program.
"""

import json
import sys
import time

import numpy as np

METRIC = "llama_fsdp_train_tokens_per_sec_per_chip"
MFU_TARGET = 0.45  # BASELINE.md contract: >=45% MFU

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``. A kind that is
# not here is an error: add it with its source, never assume one.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

# (per-chip batch, remat policy) per sequence length: what fits one 16 GB
# v5e beside bf16 params, bf16 Adam moments and gradients (PERF.md, Bring-up).
TRAIN_SHAPES = {2048: (2, "dots"), 8192: (1, "flash")}


def require_tpu() -> None:
    """A nonzero exit naming what was found, unless the backend is a TPU."""
    import jax

    from accelerate_tpu.utils import is_tpu_available

    if not is_tpu_available():
        sys.exit(
            f"no TPU: JAX's default backend is {jax.default_backend()!r} "
            f"({jax.devices()[0].device_kind}). This program measures the "
            "chip and does not fall back to another backend."
        )


def device_fields() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": jax.device_count()}


def peak_flops(device_kind: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak recorded for device_kind {device_kind!r}; add it to "
            "bench.PEAKS with its source"
        )
    return PEAKS[device_kind]["bf16_flops"]


def llama_1b(seq: int, remat_policy: str):
    """The ~1.06B-param Llama both ``bench.py`` and ``chip_smoke.py`` run.
    Width is fixed; only batch and remat policy move to make it fit."""
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=5632,
        num_hidden_layers=18,
        num_attention_heads=16,
        num_key_value_heads=16,
        max_position_embeddings=seq,
        dtype=jnp.bfloat16,
        remat=True,
        remat_policy=remat_policy,
        attention_impl="flash",
    )


def build_trainer(cfg, per_chip_batch: int, seq: int, *, parallelism_config=None,
                  kwargs_handlers=None):
    """The normal training path for ``cfg``: ``Accelerator`` (bf16, FSDP) ->
    ``Model.from_flax`` -> ``prepare`` -> ``prepare_train_step``, on one fixed
    seeded batch of ``per_chip_batch`` sequences per chip.

    Params and Adam moments are bf16: 1B of fp32 masters + fp32 moments +
    gradients does not fit a 16 GB chip. Returns ``(acc, model, step,
    batch)``; the state is ``acc.train_state``."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import LlamaForCausalLM, cross_entropy_loss
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import FullyShardedDataParallelPlugin, set_seed

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    set_seed(0)

    module = LlamaForCausalLM(cfg)
    batch = per_chip_batch * jax.device_count()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32)

    acc = Accelerator(
        mixed_precision="bf16",
        fsdp_plugin=FullyShardedDataParallelPlugin(),
        parallelism_config=parallelism_config,
        kwargs_handlers=kwargs_handlers,
    )
    model = Model.from_flax(module, jax.random.key(0), ids[:, :-1])
    model.params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), model.params)
    tx = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    model, _ = acc.prepare(model, tx)

    def loss_fn(params, b):
        logits = module.apply({"params": params}, b["x"])
        return cross_entropy_loss(logits, b["y"])

    step = acc.prepare_train_step(loss_fn, max_grad_norm=1.0)
    sharding = NamedSharding(acc.mesh, PartitionSpec(("dp_replicate", "dp_shard")))
    b = {
        "x": jax.device_put(ids[:, :-1], sharding),
        "y": jax.device_put(ids[:, 1:], sharding),
    }
    return acc, model, step, b


def measure_train(seq: int, iters: int) -> dict:
    import tempfile

    import jax

    from accelerate_tpu.utils import TelemetryKwargs

    per_chip_batch, policy = TRAIN_SHAPES[seq]
    cfg = llama_1b(seq, policy)
    # Telemetry rides along without per-step device syncs, so its step
    # times are enqueue times and stay out of the row; its recompile count,
    # executable census and peak HBM go in.
    acc, model, step, b = build_trainer(
        cfg, per_chip_batch, seq,
        kwargs_handlers=[TelemetryKwargs(
            straggler_probe_every=0, log_every=0,
            output_dir=tempfile.mkdtemp(prefix="bench_telemetry_"),
            profile={"capture_cost": False},
        )],
    )
    n_params = model.num_parameters()
    state = acc.train_state

    t_w = time.perf_counter()
    for _ in range(2):
        state, metrics = step(state, b)
        jax.block_until_ready(metrics["loss"])
    warmup_s = time.perf_counter() - t_w

    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, b)
    loss = float(jax.block_until_ready(metrics["loss"]))
    dt = (time.perf_counter() - t0) / iters
    if not np.isfinite(loss):
        raise FloatingPointError(f"non-finite loss {loss} at seq {seq}")

    dev = device_fields()
    tok_s_chip = per_chip_batch * seq / dt
    # Model FLOPs per token, fwd+bwd: 6 per parameter, plus causal attention
    # (QK^T and PV over the lower triangle: 6*L*H*S; remat's recompute is
    # not counted).
    flops_per_token = 6 * n_params + 6 * cfg.num_hidden_layers * cfg.hidden_size * seq
    tel = acc.telemetry.summary() if acc.telemetry is not None else {}
    return {
        "seq": seq,
        "tok_s_chip": round(tok_s_chip, 1),
        "mfu": round(tok_s_chip * flops_per_token / peak_flops(dev["device_kind"]), 4),
        "params_b": round(n_params / 1e9, 3),
        "per_chip_batch": per_chip_batch,
        "remat_policy": policy,
        "precision": "bf16-params+opt",
        "warmup_s": round(warmup_s, 2),
        "telemetry": {k: tel.get(k) for k in (
            "steps", "recompiles", "peak_hbm_bytes", "executables")},
    }


def measure_decode(new_tokens: int = 32) -> dict:
    """bf16 vs int8 weight-only greedy decode of the same 1B model, tokens/s
    on the default device."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import Model, generate
    from accelerate_tpu.generation import clear_generation_cache
    from accelerate_tpu.models import LlamaForCausalLM
    from accelerate_tpu.utils.quantization import quantize_model_for_decode

    cfg = llama_1b(2048, "dots")
    module = LlamaForCausalLM(cfg)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, 64), dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), prompt)
    model.params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), model.params)
    rows = {}
    # The int8 copy is built after the bf16 row so two 1B copies never share
    # HBM with a compile.
    for name, make in (("bf16", lambda: model),
                       ("int8", lambda: quantize_model_for_decode(model))):
        m = make()
        clear_generation_cache()
        jax.block_until_ready(generate(m, prompt, max_new_tokens=new_tokens))
        t0 = time.perf_counter()
        jax.block_until_ready(generate(m, prompt, max_new_tokens=new_tokens))
        rows[name] = new_tokens / (time.perf_counter() - t0)
    return {
        "decode_tok_s_bf16": round(rows["bf16"], 1),
        "decode_tok_s_int8": round(rows["int8"], 1),
        "int8_decode_speedup": round(rows["int8"] / rows["bf16"], 3),
    }


def main() -> int:
    from accelerate_tpu.compile_manager import place_compile_cache

    cache_dir = place_compile_cache()
    require_tpu()
    dev = device_fields()
    print(json.dumps({"phase": "start", "compile_cache": cache_dir, **dev}), flush=True)

    r2k = measure_train(2048, 30)
    print(json.dumps({"phase": "seq2048", **r2k, **dev}), flush=True)
    r8k = measure_train(8192, 15)
    print(json.dumps({"phase": "seq8192", **r8k, **dev}), flush=True)
    dec = measure_decode()
    print(json.dumps({"phase": "decode", **dec, **dev}), flush=True)

    print(json.dumps({
        "metric": METRIC,
        "value": r2k["tok_s_chip"],
        "unit": (f"tokens/s/chip (bf16 compute, {r2k['precision']}, "
                 f"{r2k['params_b']:.2f}B params, seq 2048, per-chip batch "
                 f"{r2k['per_chip_batch']}, flash+{r2k['remat_policy']}-remat)"),
        "vs_baseline": round(r2k["mfu"] / MFU_TARGET, 3),
        "mfu_2048": r2k["mfu"],
        "tok_s_8192": r8k["tok_s_chip"],
        "mfu_8192": r8k["mfu"],
        "warmup_s_2048": r2k["warmup_s"],
        "warmup_s_8192": r8k["warmup_s"],
        **dec,
        **dev,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
