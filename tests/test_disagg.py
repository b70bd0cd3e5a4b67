"""Disaggregated serving (disagg.py + planner slice sizing): planner split
math, KV-page handoff bit-equality, router parity with the colocated engine
and with generate(), the one-executable decode steady state across slot AND
lane reuse, the sharded-decode opt-in's flat census, handoff byte/latency
accounting, warmup/reset_metrics, and the Accelerator wiring (off by
default). All CPU-only on the forced 8-device host platform, tier-1 fast."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import (
    DisaggConfig,
    DisaggServingEngine,
    Model,
    ServingConfig,
    ServingEngine,
    generate,
    replay_trace,
)
from accelerate_tpu.generation import _llama_forward_cached
from accelerate_tpu.planner import (
    BandwidthTable,
    PlannerError,
    kv_bytes_per_token,
    plan_disagg_slices,
)
from accelerate_tpu.utils import set_seed


@pytest.fixture(scope="module")
def llama():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8),
                                              dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), probe)
    return cfg, model


def _prompts(cfg, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32)
            for n in lengths]


# ---------------------------------------------------------------------------
# Planner slice sizing (pure math)
# ---------------------------------------------------------------------------


def test_plan_disagg_slices_balances_flop_ratio():
    # ratio 1 on 8 devices: 4/4 is optimal (makespan 0.25 both sides).
    plan = plan_disagg_slices(8, prefill_decode_flop_ratio=1.0)
    assert (plan.n_prefill, plan.n_decode) == (4, 4)
    assert plan.bottleneck == "balanced"
    # Prefill-heavy traffic pulls devices into the prefill slice.
    heavy = plan_disagg_slices(8, prefill_decode_flop_ratio=3.0)
    assert heavy.n_prefill == 6
    # Decode-heavy traffic keeps the prefill slice minimal.
    light = plan_disagg_slices(8, prefill_decode_flop_ratio=1.0 / 7.0)
    assert light.n_prefill == 1 and light.n_decode == 7
    assert light.bottleneck == "balanced"  # 1/7 vs 1/7 exactly


def test_plan_disagg_slices_ties_prefer_decode():
    # On 2 devices every ratio splits 1/1; on 4 with ratio 1, 2/2 wins, but a
    # ratio where p=2 and p=3 tie must keep the SMALLER prefill slice.
    plan = plan_disagg_slices(4, prefill_decode_flop_ratio=1.0)
    assert (plan.n_prefill, plan.n_decode) == (2, 2)
    tie = plan_disagg_slices(3, prefill_decode_flop_ratio=0.5)
    assert tie.n_prefill == 1  # makespan(1)=0.5 == makespan(2)=0.5 -> p=1


def test_plan_disagg_slices_pin_and_errors():
    plan = plan_disagg_slices(8, prefill_decode_flop_ratio=1.0, n_prefill=6)
    assert (plan.n_prefill, plan.n_decode) == (6, 2)
    # The pin is clamped into [1, n-1].
    assert plan_disagg_slices(4, prefill_decode_flop_ratio=1.0,
                              n_prefill=99).n_prefill == 3
    with pytest.raises(PlannerError):
        plan_disagg_slices(1, prefill_decode_flop_ratio=1.0)
    with pytest.raises(PlannerError):
        plan_disagg_slices(8, prefill_decode_flop_ratio=0.0)


def test_plan_disagg_prices_handoff(llama):
    cfg, _ = llama
    kvb = kv_bytes_per_token(cfg, dtype=np.float32)
    # 2 (K and V) * layers * kv_heads * head_dim * itemsize.
    from accelerate_tpu.kv_cache import cache_spec

    spec = cache_spec(cfg)
    assert kvb == 2 * spec.layers * spec.kv_heads * spec.head_dim * 4
    bw = BandwidthTable()
    plan = plan_disagg_slices(8, prefill_decode_flop_ratio=2.0, bw=bw,
                              kv_bytes_per_token=kvb)
    assert plan.handoff_gbps == pytest.approx(bw.handoff_gbps(8), rel=1e-6)
    assert plan.handoff_s_per_ktoken == pytest.approx(
        1000.0 * kvb / (bw.handoff_gbps(8) * 1e9), rel=1e-4)
    d = plan.to_dict()
    assert list(d) == sorted(d)  # deterministic artifact ordering


def test_plan_disagg_prices_int8_pages_at_half_bf16(llama):
    # Quantized KV pages move ~half the bytes of bf16 pages (int8 payload
    # plus one f32 absmax scale per page), and the slice plan's handoff
    # seconds must reprice accordingly. The method on BandwidthTable and
    # the module function are the same pricing.
    cfg, _ = llama
    bw = BandwidthTable()
    kvb_bf16 = bw.kv_bytes_per_token(cfg, dtype=np.dtype("bfloat16"))
    kvb_int8 = bw.kv_bytes_per_token(cfg, dtype=np.int8)
    assert kvb_bf16 == kv_bytes_per_token(cfg, dtype=np.dtype("bfloat16"))
    assert kvb_int8 == kv_bytes_per_token(cfg, dtype=np.int8)
    # "~half": exactly (head_dim + 4) / (2 * head_dim) — the +4-byte f32
    # absmax scale per page keeps it just over 0.5.
    from accelerate_tpu.kv_cache import cache_spec

    head_dim = cache_spec(cfg).head_dim
    assert kvb_int8 / kvb_bf16 == (head_dim + 4) / (2 * head_dim)
    assert kvb_int8 / kvb_bf16 == pytest.approx(0.5, rel=0.15)
    assert kvb_int8 < kvb_bf16 < kv_bytes_per_token(cfg, dtype=np.float32)
    p16 = plan_disagg_slices(8, prefill_decode_flop_ratio=2.0, bw=bw,
                             kv_bytes_per_token=kvb_bf16)
    p8 = plan_disagg_slices(8, prefill_decode_flop_ratio=2.0, bw=bw,
                            kv_bytes_per_token=kvb_int8)
    assert p8.handoff_s_per_ktoken == pytest.approx(
        0.5 * p16.handoff_s_per_ktoken, rel=0.15)
    # No dtype override: the config's own dtype prices the link.
    assert bw.kv_bytes_per_token(cfg) == kv_bytes_per_token(cfg)


def test_disagg_config_validation():
    with pytest.raises(ValueError):
        DisaggConfig(n_prefill_lanes=0)
    with pytest.raises(ValueError):
        DisaggConfig(handoff_depth=0)
    with pytest.raises(ValueError):
        DisaggConfig(prefill_decode_flop_ratio=-1.0)
    with pytest.raises(ValueError):
        DisaggConfig(expected_prompt_tokens=0)
    with pytest.raises(ValueError):
        DisaggConfig(n_prefill_devices=0)
    with pytest.raises(ValueError):
        DisaggConfig(handoff_sample_every=0)


# ---------------------------------------------------------------------------
# Router correctness: bit-equality across the handoff
# ---------------------------------------------------------------------------


def _engines(model, **disagg_kw):
    sc = ServingConfig(n_slots=3, max_len=64, prefill_chunks=[4, 8])
    colo = ServingEngine(model, sc)
    dis = DisaggServingEngine(model, sc, disagg=DisaggConfig(**disagg_kw))
    return colo, dis


def _two_programs(cfg, params, ids, cache, return_all=False, attn_bound=None):
    return _llama_forward_cached(cfg, params, ids, cache, return_all=return_all,
                                 attn_bound=attn_bound)


def test_transferred_pages_bit_equal_to_in_place(llama):
    """The core handoff invariant: after prefilling the same prompt, the
    decode-side cache slot holds byte-identical K/V pages to the colocated
    engine's in-place prefill — pad tail and all committed lengths
    included."""
    cfg, model = llama
    colo, dis = _engines(model, n_prefill_lanes=1)
    # the prefill program in place: a forward without ``chunk`` keeps it (the
    # engine's own ticks run the chunk inside the decode step, one program,
    # whose float32 sums are the same to ~1e-6, not to the bit)
    colo = ServingEngine(model, colo.config, forward_cached=_two_programs)
    (prompt,) = _prompts(cfg, [13], seed=5)
    colo.run([prompt], max_new_tokens=1)
    dis.run([prompt], max_new_tokens=1)
    ck, dk = np.asarray(colo._cache.k), np.asarray(dis._cache.k)
    cv, dv = np.asarray(colo._cache.v), np.asarray(dis._cache.v)
    # Both engines granted slot ids from the same policy; compare the whole
    # committed region of the request's slot (slot allocation is LIFO from
    # the same free list, so the single request took the same slot).
    np.testing.assert_array_equal(
        np.asarray(colo._cache.length), np.asarray(dis._cache.length))
    n = int(np.asarray(colo._cache.length).max())
    slot = int(np.argmax(np.asarray(colo._cache.length)))
    np.testing.assert_array_equal(ck[:, slot, :n], dk[:, slot, :n])
    np.testing.assert_array_equal(cv[:, slot, :n], dv[:, slot, :n])


def test_router_bit_equal_greedy_two_waves(llama):
    """Router output == colocated engine == batch-1 generate(), across two
    request waves through the same engines (slot AND lane reuse, donated
    buffers recycled mid-flight)."""
    cfg, model = llama
    colo, dis = _engines(model, n_prefill_lanes=2)
    for seed in (3, 11):  # second wave reuses every slot and lane
        prompts = _prompts(cfg, [3, 7, 12, 20, 5, 9], seed=seed)
        budgets = [6, 4, 8, 3, 5, 7]
        got_c = colo.run(prompts, max_new_tokens=budgets)
        got_d = dis.run(prompts, max_new_tokens=budgets)
        for prompt, budget, c, d in zip(prompts, budgets, got_c, got_d):
            np.testing.assert_array_equal(c, d)
            want = np.asarray(
                generate(model, prompt[None], max_new_tokens=budget))[0]
            np.testing.assert_array_equal(d, want)


def test_router_bit_equal_sampled(llama):
    """Sampled decoding: per-request PRNG streams survive the two-mesh split
    (the rng carry crosses with the final page's arm payload)."""
    cfg, model = llama
    sc = ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8],
                       temperature=0.8, top_k=20)
    colo = ServingEngine(model, sc)
    dis = DisaggServingEngine(model, sc, disagg=DisaggConfig(n_prefill_lanes=2))
    prompts = _prompts(cfg, [5, 11, 3, 17], seed=8)
    keys = [jax.random.key(40 + i) for i in range(4)]
    got_c = colo.run(prompts, max_new_tokens=6, rngs=keys)
    got_d = dis.run(prompts, max_new_tokens=6, rngs=keys)
    for c, d in zip(got_c, got_d):
        np.testing.assert_array_equal(c, d)


def test_decode_steady_state_one_executable(llama):
    """The zero-recompile invariant survives the split: the decode program's
    dispatch census stays at exactly 1 across waves on the default (fixed
    single-device) decode placement."""
    cfg, model = llama
    _, dis = _engines(model, n_prefill_lanes=2)
    for seed in (3, 11):
        dis.run(_prompts(cfg, [3, 12, 7, 20], seed=seed), max_new_tokens=5)
    s = dis.stats()
    assert s["decode_executables"] == 1
    assert s["steady_recompiles"] == 0
    execs = dis.executable_counts()
    # Data-plane programs are rung/placement-bounded, never per-request.
    assert execs["handoff_extract"] <= len(dis.ladder) * len(
        {l.device for l in dis._lanes})
    assert execs["slot_arm"] == 1


def test_shard_decode_slots_optin_flat_census(llama):
    """The opt-in slot-sharded decode placement keeps a FLAT dispatch census
    (pre-warmed at init — jax 0.9.0 holds two dispatch entries for one
    compiled typed-key program under a multi-device NamedSharding) and zero
    steady recompiles; outputs stay bit-equal to the colocated engine."""
    cfg, model = llama
    sc = ServingConfig(n_slots=4, max_len=64, prefill_chunks=[4, 8])
    colo = ServingEngine(model, sc)
    dis = DisaggServingEngine(
        model, sc,
        disagg=DisaggConfig(n_prefill_lanes=2, n_prefill_devices=4,
                            shard_decode_slots=True),
    )
    assert dis._decode_mesh is not None  # 4 slots over 4 decode devices
    prompts = _prompts(cfg, [3, 9, 14, 6], seed=4)
    got_c = colo.run(prompts, max_new_tokens=4)
    got_d = dis.run(prompts, max_new_tokens=4)
    for c, d in zip(got_c, got_d):
        np.testing.assert_array_equal(c, d)
    assert dis.stats()["steady_recompiles"] == 0


def test_shard_decode_slots_indivisible_falls_back(llama):
    cfg, model = llama
    sc = ServingConfig(n_slots=3, max_len=64, prefill_chunks=[4, 8])
    dis = DisaggServingEngine(
        model, sc,
        disagg=DisaggConfig(n_prefill_devices=4, shard_decode_slots=True),
    )
    assert dis._decode_mesh is None  # 3 slots % 4 devices -> single-device
    outs = dis.run(_prompts(cfg, [5, 8], seed=2), max_new_tokens=3)
    assert len(outs) == 2


def test_single_device_rejected(llama):
    cfg, model = llama
    with pytest.raises(ValueError, match="needs >= 2 devices"):
        DisaggServingEngine(model, ServingConfig(n_slots=2, max_len=32),
                            devices=[jax.devices()[0]])


# ---------------------------------------------------------------------------
# Handoff accounting + stats/telemetry
# ---------------------------------------------------------------------------


def test_handoff_byte_accounting(llama):
    """handoff_bytes is exactly the K+V page bytes the chunks committed:
    per chunk 2 * layers * chunk_size * kv_heads * head_dim * itemsize."""
    cfg, model = llama
    _, dis = _engines(model, n_prefill_lanes=1)
    prompts = _prompts(cfg, [13, 4], seed=6)  # chunks: [8,4,4(pad)] + [4]
    dis.run(prompts, max_new_tokens=2)
    d = dis.stats()["disagg"]
    kvb = kv_bytes_per_token(cfg, dtype=np.float32)
    from accelerate_tpu.serving import plan_chunks

    chunk_tokens = sum(
        size for p in prompts for size, _ in plan_chunks(len(p), dis.ladder))
    assert d["handoff_bytes"] == chunk_tokens * kvb
    assert d["handoff_transfers"] == sum(
        len(plan_chunks(len(p), dis.ladder)) for p in prompts)
    assert d["handoff_inserts"] == d["handoff_transfers"]
    assert d["handoff_final_flushes"] == len(prompts)


def test_disagg_stats_block(llama):
    cfg, model = llama
    _, dis = _engines(model, n_prefill_lanes=2, handoff_sample_every=2)
    dis.run(_prompts(cfg, [9, 13, 5], seed=7), max_new_tokens=4)
    s = dis.stats()
    d = s["disagg"]
    assert d["n_prefill_devices"] + d["n_decode_devices"] == len(jax.devices())
    assert d["slice_plan"]["n_prefill"] == d["n_prefill_devices"]
    assert d["handoff_lat_sampled"] >= 1
    assert d["handoff_lat_mean_s"] > 0
    assert d["measured_flop_ratio"] == pytest.approx(
        s["prompt_tokens_in"] / s["tokens_out"], rel=1e-5)


def test_warmup_and_reset_metrics(llama):
    """warmup() compiles every lane's full ladder and resets the counters:
    a measured run starts at zero with all programs already compiled."""
    cfg, model = llama
    _, dis = _engines(model, n_prefill_lanes=2)
    dis.warmup()
    s = dis.stats()
    assert s["requests_completed"] == 0 and s["ticks"] == 0
    assert s["disagg"]["handoff_transfers"] == 0
    lane_devs = {l.device for l in dis._lanes}
    assert dis.executable_counts()["prefill"] == len(dis.ladder) * len(lane_devs)
    # A post-warmup run never grows the decode census.
    dis.run(_prompts(cfg, [6, 10], seed=9), max_new_tokens=3)
    assert dis.stats()["steady_recompiles"] == 0
    assert dis.stats()["decode_executables"] == 1


def test_replay_trace_open_loop(llama):
    """replay_trace submits on the arrival clock and returns rows in input
    order — and the same trace is bit-stable across engines."""
    cfg, model = llama
    colo, dis = _engines(model, n_prefill_lanes=2)
    prompts = _prompts(cfg, [7, 3, 12], seed=10)
    arrivals = [0.0, 0.0, 0.005]
    rows_c, _ = replay_trace(colo, prompts, arrivals=arrivals,
                             max_new_tokens=4)
    rows_d, _ = replay_trace(dis, prompts, arrivals=arrivals,
                             max_new_tokens=4)
    for c, d in zip(rows_c, rows_d):
        np.testing.assert_array_equal(c, d)
    with pytest.raises(ValueError, match="arrivals"):
        replay_trace(colo, prompts, arrivals=[0.0], max_new_tokens=2)


# ---------------------------------------------------------------------------
# Accelerator wiring (off by default)
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


def test_accelerator_disagg_off_by_default(tmp_path, llama):
    cfg, model = llama
    sc = ServingConfig(n_slots=2, max_len=64)
    acc = _accelerator(tmp_path, [sc])
    assert acc.disagg_config is None
    engine = acc.build_serving_engine(model)
    assert not isinstance(engine, DisaggServingEngine)


def test_accelerator_builds_disagg_engine(tmp_path, llama):
    """DisaggConfig in kwargs_handlers upgrades build_serving_engine to the
    two-mesh router and streams the `disagg` block through telemetry."""
    import json
    import os

    from accelerate_tpu.utils import TelemetryKwargs

    cfg, model = llama
    sc = ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8])
    dc = DisaggConfig(n_prefill_lanes=1)
    acc = _accelerator(
        tmp_path,
        [sc, dc, TelemetryKwargs(straggler_probe_every=0, log_every=0)],
    )
    assert acc.disagg_config is dc
    engine = acc.build_serving_engine(model)
    assert isinstance(engine, DisaggServingEngine)
    engine.run(_prompts(cfg, [5, 9], seed=4), max_new_tokens=3)
    summary = acc.telemetry.summary()
    assert summary["serving"]["requests_completed"] == 2
    assert summary["disagg"]["handoff_transfers"] > 0
    acc.telemetry.close()
    report = os.path.join(str(tmp_path), "telemetry", "rank_0.jsonl")
    events = [json.loads(line) for line in open(report)]
    kinds = {e["event"] for e in events}
    assert "disagg_summary" in kinds


def test_accelerator_disagg_disabled_handler(tmp_path, llama):
    """enabled=False keeps the colocated engine even with the handler
    present — the one-flag rollback path."""
    cfg, model = llama
    sc = ServingConfig(n_slots=2, max_len=64)
    acc = _accelerator(tmp_path, [sc, DisaggConfig(enabled=False)])
    engine = acc.build_serving_engine(model)
    assert not isinstance(engine, DisaggServingEngine)


# ---------------------------------------------------------------------------
# Robustness surface (the full fault matrix lives in tests/test_chaos.py)
# ---------------------------------------------------------------------------


def test_lane_quarantine_survives_on_remaining_lane(llama):
    """Killing ONE of two prefill lanes quarantines it without degrading:
    the survivor carries the whole trace, rows stay bit-equal to generate(),
    and the decode census stays 1."""
    from accelerate_tpu import FaultInjector, generate

    cfg, model = llama
    chaos = FaultInjector(
        seed=3,
        schedule=[{"point": "lane_health", "kind": "dead_lane", "unit": 0}],
    )
    eng = DisaggServingEngine(
        model,
        ServingConfig(n_slots=4, max_len=64, prefill_chunks=[4, 8]),
        disagg=DisaggConfig(n_prefill_lanes=2),
        chaos=chaos,
    )
    prompts = _prompts(cfg, [3, 7, 12, 20, 5, 9])
    budgets = [6, 4, 8, 3, 5, 6]
    outs = eng.run(prompts, max_new_tokens=budgets)
    for p, b, got in zip(prompts, budgets, outs):
        want = np.asarray(generate(model, p[None], max_new_tokens=b))[0]
        np.testing.assert_array_equal(got, want)
    s = eng.stats()
    assert s["faults"]["lane_quarantines"] == 1
    assert s["disagg"]["quarantined_lanes"] == [0]
    assert s["disagg"]["healthy_lanes"] == 1
    assert s["disagg"]["degraded"] is False
    assert s["decode_executables"] == 1
    assert s["steady_recompiles"] == 0


def test_router_ships_every_plane_of_a_looped_model_and_prices_them():
    """A stack run three times over two layers keeps six cache planes a
    token: the handoff ships all of them (the router's tokens are the
    colocated engine's) and the slice plan prices the link with the cache's
    own ``kv_bytes_per_token``, passes included. (Last in the file: the handoff
    programs are jitted once a process, and an earlier test reads their census.)"""
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native", total_ut_steps=3,
                           sandwich_norm=True, early_exit_gate=True)
    model = Model.from_flax(LlamaForCausalLM(cfg), jax.random.key(0),
                            np.ones((1, 8), np.int32))
    colo, dis = _engines(model, n_prefill_lanes=1)
    assert dis._cache.n_layers == 3 * cfg.num_hidden_layers
    assert dis.slice_plan.kv_bytes_per_token == (
        2 * 4 * 3 * cfg.num_hidden_layers * cfg.num_key_value_heads * cfg.head_dim)
    prompts = _prompts(cfg, [13, 5, 9], seed=5)
    budgets = [5, 7, 4]
    for c, d in zip(colo.run(prompts, max_new_tokens=budgets),
                    dis.run(prompts, max_new_tokens=budgets)):
        np.testing.assert_array_equal(c, d)
