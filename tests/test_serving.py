"""Continuous-batching serving engine (serving.py): chunk-ladder math, slot
alloc/free/reuse, per-slot EOS retirement, chunked-prefill == one-shot cache
equivalence, decode parity with generate(), occupancy accounting, the
single-executable steady state, and the off-by-default contract. All
CPU-only, tier-1 fast."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import Model, ServingConfig, ServingEngine, generate
from accelerate_tpu.generation import _llama_forward_cached, init_cache, init_slot_cache
from accelerate_tpu.serving import default_prefill_ladder, plan_chunks
from accelerate_tpu.utils import set_seed


@pytest.fixture(scope="module")
def llama():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), probe)
    return cfg, model


def _prompts(cfg, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32) for n in lengths]


# ---------------------------------------------------------------------------
# Pure ladder math
# ---------------------------------------------------------------------------


def test_default_prefill_ladder():
    assert default_prefill_ladder(256, 16, 256) == [16, 32, 64, 128, 256]
    assert default_prefill_ladder(100, 16, 256) == [16, 32, 64, 100]
    assert default_prefill_ladder(8, 16, 256) == [8]  # capacity below min chunk


def test_plan_chunks_greedy_cover():
    ladder = [4, 8, 16]
    assert plan_chunks(16, ladder) == [(16, 16)]
    assert plan_chunks(21, ladder) == [(16, 16), (4, 4), (4, 1)]
    assert plan_chunks(3, ladder) == [(4, 3)]  # short prompt pads the min rung
    # valid counts always cover the prompt exactly
    for p in range(1, 40):
        chunks = plan_chunks(p, ladder)
        assert sum(v for _, v in chunks) == p
        assert all(v <= c and c in ladder for c, v in chunks)


def test_plan_chunks_rejects_empty():
    with pytest.raises(ValueError):
        plan_chunks(0, [8])
    with pytest.raises(ValueError):
        plan_chunks(5, [])


def test_init_slot_cache_per_slot_lengths(llama):
    cfg, _ = llama
    cache = init_slot_cache(cfg, 5, 32)
    assert cache.length.shape == (5,)
    assert cache.k.shape[1] == 5 and cache.k.shape[2] == 32


# ---------------------------------------------------------------------------
# Engine behavior
# ---------------------------------------------------------------------------


def test_engine_greedy_parity_with_generate(llama):
    """The acceptance bar: per-request engine output bit-equal to a batch-1
    generate() for the same prompt/budget, under mixed lengths, chunked
    prefill, and mid-flight slot reuse."""
    cfg, model = llama
    # 8 requests over 4 distinct (length, budget) combos: different token
    # CONTENT per request (mixed retirement order) while the reference
    # generate() calls reuse 4 compiled shapes instead of 8.
    prompts = _prompts(cfg, [3, 7, 12, 20, 3, 7, 12, 20])
    budgets = [6, 4, 8, 3, 6, 4, 8, 3]
    engine = ServingEngine(
        model, ServingConfig(n_slots=3, max_len=64, prefill_chunks=[4, 8])
    )
    outs = engine.run(prompts, max_new_tokens=budgets)
    for prompt, budget, got in zip(prompts, budgets, outs):
        want = np.asarray(generate(model, prompt[None], max_new_tokens=budget))[0]
        np.testing.assert_array_equal(got, want)
    stats = engine.stats()
    assert stats["requests_completed"] == len(prompts)
    assert stats["slot_reuses"] >= len(prompts) - 3  # slots recycled mid-flight


def test_per_slot_eos_retirement(llama):
    """Rows retire at their own EOS; the returned row pads with the pad id
    exactly like generate()."""
    cfg, model = llama
    prompts = _prompts(cfg, [5, 9, 5, 9], seed=9)
    # Use whatever greedy emits first for prompt 0 as the engine-wide EOS:
    # some requests hit it quickly, others run to budget.
    eos = int(np.asarray(generate(model, prompts[0][None], max_new_tokens=1))[0, -1])
    budget = 8
    engine = ServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8],
                      eos_token_id=eos),
    )
    outs = engine.run(prompts, max_new_tokens=budget)
    lengths = []
    for prompt, got in zip(prompts, outs):
        want = np.asarray(
            generate(model, prompt[None], max_new_tokens=budget, eos_token_id=eos)
        )[0]
        np.testing.assert_array_equal(got, want)
        new = got[len(prompt):]
        if eos in new:
            idx = int(np.argmax(new == eos))
            assert (new[idx:] == eos).all()  # post-EOS slots are pad(=eos)
            lengths.append(idx + 1)
        else:
            lengths.append(budget)
    assert len(set(lengths)) > 1  # rows really retired at different times


def test_chunked_prefill_matches_oneshot_prefill(llama):
    """Writing a prompt chunk-by-chunk into a slot must leave the same cache
    contents and next-token logits as one whole-prompt prefill."""
    cfg, model = llama
    prompt = _prompts(cfg, [13], seed=5)[0]
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8])
    )
    engine.submit(prompt, max_new_tokens=1)
    # Drive prefill only: with a budget of one token the request is done with
    # its first, which is settled a tick after the final chunk's dispatch.
    while engine.pending:
        engine.tick()
    slot_cache = engine._cache
    slot = 0  # first alloc takes slot 0
    one = init_cache(cfg, 1, 32)
    logits, one = _llama_forward_cached(cfg, model.params, prompt[None], one)
    p = len(prompt)
    np.testing.assert_allclose(
        np.asarray(slot_cache.k[:, slot, :p]), np.asarray(one.k[:, 0, :p]),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(slot_cache.v[:, slot, :p]), np.asarray(one.v[:, 0, :p]),
        rtol=1e-5, atol=1e-5,
    )
    assert int(slot_cache.length[slot]) == p
    # The first sampled token came from the same logits row.
    want_tok = int(np.argmax(np.asarray(logits)[0]))
    res = engine.poll()
    assert len(res) == 1 and int(res[0]["tokens"][p]) == want_tok


def test_single_decode_executable_steady_state(llama):
    """Zero steady-state recompiles: ONE decode executable and at most
    len(ladder) prefill executables, no matter how requests churn."""
    cfg, model = llama
    engine = ServingEngine(
        model, ServingConfig(n_slots=3, max_len=64, prefill_chunks=[4, 8])
    )
    engine.run(_prompts(cfg, [3, 17, 6, 11, 9, 5]), max_new_tokens=5)
    # Second wave after a drain — still the same executables.
    engine.run(_prompts(cfg, [2, 13, 8], seed=11), max_new_tokens=7)
    stats = engine.stats()
    assert stats["decode_executables"] == 1
    assert stats["prefill_executables"] <= 2
    assert stats["decode_chunk_executables"] <= 2
    assert stats["steady_recompiles"] == 0


def test_prefill_executables_flat_with_mesh_placed_params(llama):
    """Params prepared by an Accelerator carry a NamedSharding over its mesh
    even on one device. The cache must start in that same form, or the first
    prefill returns it changed and the rung that ran first compiles twice
    (first seen on the v5e: 6 prefill executables for a 5-rung ladder)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    cfg, model = llama
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp_shard",))
    placed = Model(module=model.module, params=jax.device_put(
        model.params, NamedSharding(mesh, PartitionSpec())))
    engine = ServingEngine(
        placed, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8])
    )
    engine.warmup()  # walks rung 8 first, then rung 4, each riding a decode step
    engine.run(_prompts(cfg, [8, 12]), max_new_tokens=3)  # rung 8 again
    assert engine.stats()["decode_chunk_executables"] == 2


def test_occupancy_and_token_accounting(llama):
    cfg, model = llama
    budgets = [3, 6, 4, 5, 7, 2]
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[8])
    )
    engine.run(_prompts(cfg, [4, 9, 5, 7, 3, 6], seed=2), max_new_tokens=budgets)
    stats = engine.stats()
    assert stats["requests_submitted"] == stats["requests_completed"] == 6
    assert stats["tokens_out"] == sum(budgets)  # no EOS configured
    assert stats["slot_allocs"] == 6 and stats["slot_reuses"] == 4
    assert 0 < stats["mean_occupancy"] <= 2
    assert stats["peak_occupancy"] <= 2
    assert stats["tokens_per_s"] and stats["tokens_per_s"] > 0
    assert stats["ttft_p50_s"] is not None and stats["ttft_p95_s"] >= stats["ttft_p50_s"]


@pytest.mark.parametrize("lengths,budgets,rows_summed", [
    # one request: its first token comes from the prompt's last chunk, then 5
    # decode steps over 5 + 1 .. 5 + 5 rows
    ([5], [6], sum(5 + j for j in range(1, 6))),
    # two at once: every request's decode steps, whichever tick they fell in
    ([4, 9], [3, 6], sum(4 + j for j in range(1, 3)) + sum(9 + j for j in range(1, 6))),
], ids=["one_request", "two_slots"])
def test_cache_counters_say_what_the_decode_steps_had_to_read(llama, lengths, budgets,
                                                               rows_summed):
    """``stats()["cache"]``: the buffer's planes and a token's bytes from the
    cache's owner, and the mean over decode steps of the rows the decoding
    slots held, from lengths the host already has; ``stats()["passes"]``."""
    from accelerate_tpu.kv_cache import cache_spec, kv_bytes_per_token

    cfg, model = llama
    engine = ServingEngine(model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[8]))
    engine.run(_prompts(cfg, lengths, seed=2), max_new_tokens=budgets)
    stats = engine.stats()
    assert stats["passes"] == 1
    assert stats["cache"]["planes"] == cache_spec(cfg).layers == cfg.num_hidden_layers
    assert stats["cache"]["bytes_per_token"] == kv_bytes_per_token(cfg)
    assert stats["cache"]["live_rows_mean"] == pytest.approx(
        rows_summed / stats["decode_steps"], abs=1e-3)
    engine.reset_metrics()   # the window opens: the sum restarts with the steps
    assert engine.stats()["cache"]["live_rows_mean"] is None
    assert engine.stats()["cache"]["planes"] == cfg.num_hidden_layers


@pytest.mark.parametrize("forced", [False, True], ids=["dots_over_the_slice", "kernel_forced"])
def test_cache_counters_say_what_the_decode_steps_did_read(llama, monkeypatch, forced):
    """``read_rows_mean`` beside ``live_rows_mean``: off the chip a decode
    step's attention reads the whole buffer, ``n_slots x max_len`` rows; where
    the decode kernel runs (forced here, under the interpreter, in blocks of 4
    rows) it reads each decoding slot's rows rounded up to a block and nothing
    of the slot that is free."""
    from accelerate_tpu.ops import decode_attention

    if forced:
        monkeypatch.setattr(decode_attention, "INTERPRET", True)
        monkeypatch.setattr(decode_attention, "block_rows", lambda t_max, *_: 4)
    cfg, model = llama
    engine = ServingEngine(model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[8]))
    engine.run(_prompts(cfg, [5], seed=2), max_new_tokens=[6])
    cache = engine.stats()["cache"]
    assert engine.stats()["decode_steps"] == 5
    assert cache["live_rows_mean"] == pytest.approx(sum(5 + j for j in range(1, 6)) / 5)
    # 6, 7, 8, 9 and 10 rows, in blocks of 4: 8 + 8 + 8 + 12 + 12
    assert cache["read_rows_mean"] == pytest.approx(48 / 5 if forced else 2 * 64)
    engine.reset_metrics()
    assert engine.stats()["cache"]["read_rows_mean"] is None


def test_incremental_submit_poll(llama):
    """The front-end contract: submissions land mid-flight, poll() delivers
    each result exactly once."""
    cfg, model = llama
    prompts = _prompts(cfg, [6, 4, 6, 4], seed=7)
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8])
    )
    first = [engine.submit(p, max_new_tokens=4) for p in prompts[:2]]
    for _ in range(3):
        engine.tick()
    late = [engine.submit(p, max_new_tokens=4) for p in prompts[2:]]
    seen = {}
    for _ in range(200):
        engine.tick()
        for res in engine.poll():
            assert res["id"] not in seen
            seen[res["id"]] = res
        if not engine.pending:
            break
    assert set(seen) == set(first + late)
    for rid, prompt in zip(first + late, prompts):
        want = np.asarray(generate(model, prompt[None], max_new_tokens=4))[0]
        np.testing.assert_array_equal(seen[rid]["tokens"], want)


def test_sampled_decoding_deterministic_per_request(llama):
    """temperature>0: one PRNG stream per request — identical keys replay
    identical outputs, and distinct keys may diverge."""
    cfg, model = llama
    prompts = _prompts(cfg, [5, 8], seed=13)
    keys = [jax.random.key(i) for i in (1, 2)]

    def run():
        engine = ServingEngine(
            model,
            ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8],
                          temperature=0.8, top_k=20),
        )
        return engine.run(prompts, max_new_tokens=6, rngs=keys)

    a, b = run(), run()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_submit_validation(llama):
    cfg, model = llama
    engine = ServingEngine(model, ServingConfig(n_slots=2, max_len=16))
    with pytest.raises(ValueError, match="empty"):
        engine.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="capacity|max_len"):
        engine.submit(np.ones((12,), np.int32), max_new_tokens=8)
    with pytest.raises(ValueError, match=">= 1"):
        engine.submit(np.ones((4,), np.int32), max_new_tokens=0)


def test_encdec_rejected(llama):
    from accelerate_tpu.utils.dataclasses import ServingConfig as SC

    class FakeT5:
        pass

    FakeT5.__name__ = "T5ForConditionalGeneration"

    class FakeModel:
        module = FakeT5()
        params = {}

    with pytest.raises(ValueError, match="causal"):
        ServingEngine(FakeModel(), SC(n_slots=1, max_len=8))


def test_serving_config_validation():
    with pytest.raises(ValueError):
        ServingConfig(n_slots=0)
    with pytest.raises(ValueError):
        ServingConfig(prefill_chunks_per_tick=0)
    with pytest.raises(ValueError):
        ServingConfig(min_prefill_chunk=32, max_prefill_chunk=16)


# ---------------------------------------------------------------------------
# Integration: accelerator wiring, telemetry block, compile manager
# ---------------------------------------------------------------------------


def _accelerator(tmp_path, handlers):
    import optax  # noqa: F401

    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    set_seed(0)
    return Accelerator(project_dir=str(tmp_path), kwargs_handlers=handlers)


def test_serving_off_by_default(tmp_path, llama):
    """No ServingConfig handler -> no serving config, and building an engine
    is an explicit error; the training path never constructs one."""
    cfg, model = llama
    acc = _accelerator(tmp_path, [])
    assert acc.serving_config is None
    with pytest.raises(ValueError, match="serving is off"):
        acc.build_serving_engine(model)


def test_accelerator_builds_wired_engine(tmp_path, llama):
    """ServingConfig in kwargs_handlers + CompileKwargs: the engine sources
    its prefill ladder from the compile manager's fixed seq buckets and
    pushes its summary into the telemetry recorder."""
    import json
    import os

    from accelerate_tpu.utils import CompileKwargs, TelemetryKwargs

    cfg, model = llama
    sc = ServingConfig(n_slots=2, max_len=64)
    acc = _accelerator(
        tmp_path,
        [sc, CompileKwargs(buckets="fixed", seq_buckets=[4, 8], warmup="off"),
         TelemetryKwargs(straggler_probe_every=0, log_every=0)],
    )
    assert acc.serving_config is sc
    engine = acc.build_serving_engine(model)
    assert engine.ladder == [4, 8]
    engine.run(_prompts(cfg, [5, 3, 9], seed=4), max_new_tokens=3)
    summary = acc.telemetry.summary()
    assert summary["serving"]["requests_completed"] == 3
    assert summary["serving"]["steady_recompiles"] == 0
    acc.telemetry.close()
    report = os.path.join(str(tmp_path), "telemetry", "rank_0.jsonl")
    events = [json.loads(l) for l in open(report)]
    kinds = {e["event"] for e in events}
    assert "serving_request_done" in kinds and "serving_summary" in kinds


def test_serving_summary_acceptance_rate_ema(tmp_path):
    """record_serving keeps a cross-push EMA of the speculation acceptance
    rate: first push seeds it, later pushes blend 0.9/0.1, pushes with no
    rate (speculation off / nothing drafted yet) leave it untouched."""
    from accelerate_tpu.utils import TelemetryKwargs

    acc = _accelerator(
        tmp_path, [TelemetryKwargs(straggler_probe_every=0, log_every=0)])
    tele = acc.telemetry
    spec = lambda rate: {"speculation": {  # noqa: E731
        "k": 4, "ngram": 16, "drafted": 100, "accepted": 50,
        "acceptance_rate": rate, "tokens_per_tick": 1.0, "verify_time_s": 0.1}}
    tele.record_serving(spec(None))
    assert tele.summary()["serving"]["speculation"]["acceptance_rate_ema"] is None
    tele.record_serving(spec(0.5))
    assert tele.summary()["serving"]["speculation"]["acceptance_rate_ema"] == 0.5
    tele.record_serving(spec(1.0))
    got = tele.summary()["serving"]["speculation"]["acceptance_rate_ema"]
    assert got == pytest.approx(0.9 * 0.5 + 0.1 * 1.0)
    tele.record_serving(spec(None))  # no new rate: EMA survives unchanged
    assert (tele.summary()["serving"]["speculation"]["acceptance_rate_ema"]
            == pytest.approx(0.55))
    tele.close()


def test_generation_signatures_reach_manifest_and_warm(tmp_path, llama):
    """generate(compile_manager=...) buckets the prompt up the seq ladder,
    records the signature, and warmup_generation() replays it into the
    compiled-loop cache on a fresh process (simulated by clearing it)."""
    from accelerate_tpu import generation as G
    from accelerate_tpu.utils import CompileKwargs

    cfg, model = llama
    acc = _accelerator(
        tmp_path,
        [CompileKwargs(buckets="fixed", seq_buckets=[8, 16], warmup="off")],
    )
    cm = acc.compile_manager
    prompts = _prompts(cfg, [5, 7, 3], seed=6)
    plain = [
        np.asarray(generate(model, p[None], max_new_tokens=4))[0] for p in prompts
    ]
    G.clear_generation_cache()
    outs = [
        np.asarray(
            generate(model, p[None], max_new_tokens=4, compile_manager=cm)
        )[0]
        for p in prompts
    ]
    # Bucketing preserves outputs bit-for-bit (left pads are masked out)...
    for got, want in zip(outs, plain):
        np.testing.assert_array_equal(got, want)
    # ...and all three lengths shared ONE bucketed signature.
    gen_entries = [
        e for e in cm.manifest.entries
        if (e.get("spec") or {}).get("kind") == "generation"
    ]
    assert len(gen_entries) == 1
    assert gen_entries[0]["spec"]["prompt_len"] == 8
    # Restart: a cold loop cache warms from the manifest before any request.
    G.clear_generation_cache()
    assert cm.warmup_generation(model) == 1
    assert len(G._GEN_LOOP_CACHE) == 1
    # Train-step warmup must ignore generation entries (they need a model).
    pending_specs = [e["spec"].get("kind") for e in cm.manifest.entries]
    assert "generation" in pending_specs  # present in the manifest...
    from accelerate_tpu.compile_manager import spec_array_dims

    dims = {"batch": set(), "seq": set()}
    for e in cm.manifest.entries:
        spec_array_dims(e["spec"], dims)
    assert dims == {"batch": set(), "seq": set()}  # ...but never warms a step


# ---------------------------------------------------------------------------
# Robustness surface (the full fault matrix lives in tests/test_chaos.py)
# ---------------------------------------------------------------------------


def test_poll_rows_carry_explicit_status(llama):
    """Every poll() row now names its terminal state; the fault-free path is
    all `ok` and the faults stats block stays zeroed."""
    from accelerate_tpu.serving import REQUEST_STATUSES

    cfg, model = llama
    eng = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8])
    )
    ids = [eng.submit(p, max_new_tokens=3) for p in _prompts(cfg, [5, 9])]
    rows = {}
    while eng.pending:
        eng.tick()
        for r in eng.poll():
            rows[r["id"]] = r
    assert set(rows) == set(ids)
    for r in rows.values():
        assert r["status"] == "ok"
        assert r["status"] in REQUEST_STATUSES
    f = eng.stats()["faults"]
    assert f["injected"] == 0 and f["sheds"] == 0 and f["timeouts"] == 0


def test_submit_deadline_validation(llama):
    cfg, model = llama
    eng = ServingEngine(
        model, ServingConfig(n_slots=1, max_len=64, prefill_chunks=[4, 8])
    )
    with pytest.raises(ValueError):
        eng.submit(_prompts(cfg, [5])[0], max_new_tokens=2, deadline_s=0.0)
    with pytest.raises(ValueError):
        eng.submit(_prompts(cfg, [5])[0], max_new_tokens=2, deadline_s=-1.0)


def test_serving_config_robustness_defaults():
    """The robustness knobs are off by default — no queue cap, no deadline,
    reject-on-overload (inert without a cap), bounded retries."""
    c = ServingConfig()
    assert c.max_queue_depth is None
    assert c.deadline_s is None
    assert c.overload_policy == "reject"
    assert c.max_retries == 2
    assert c.max_idle_ticks == 100
