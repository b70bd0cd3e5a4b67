"""Serving under fire (chaos.py + the robustness layer in serving.py /
disagg.py): deterministic fault schedules, explicit terminal statuses for
every fault kind, bit-equal survivors, slot/lane quarantine with the decode
census pinned at 1, degraded colocated fallback, admission control +
deadlines, the hang guard, and the preemption drain. All CPU-only on the
forced 8-device host platform, tier-1 fast."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import (
    DisaggConfig,
    DisaggServingEngine,
    FaultInjector,
    InjectedFaultError,
    Model,
    ServingConfig,
    ServingEngine,
    ServingStalledError,
    generate,
)
from accelerate_tpu.chaos import INJECTION_POINTS, deterministic_jitter
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


def _drain(engine, ids, guard=5000):
    """Tick until every submitted id has a result; return {id: result}."""
    results = {}
    ticks = 0
    while engine.pending:
        engine.tick()
        for r in engine.poll():
            results[r["id"]] = r
        ticks += 1
        assert ticks < guard, "drain guard tripped"
    assert set(ids) <= set(results), "a request vanished without a status"
    return results


# ---------------------------------------------------------------------------
# FaultInjector (pure host logic)
# ---------------------------------------------------------------------------


def test_injector_deterministic_and_seed_sensitive():
    spec = dict(rates={"handoff_device_put": {"transfer_error": 0.3,
                                              "delay": 0.2}})
    a, b = FaultInjector(seed=9, **spec), FaultInjector(seed=9, **spec)
    c = FaultInjector(seed=10, **spec)
    grid = [(t, u) for t in range(50) for u in range(3)]
    draws_a = [a.draw("handoff_device_put", t, u) for t, u in grid]
    draws_b = [b.draw("handoff_device_put", t, u) for t, u in grid]
    draws_c = [c.draw("handoff_device_put", t, u) for t, u in grid]
    assert draws_a == draws_b
    assert a.injected == b.injected and len(a.injected) > 0
    assert draws_a != draws_c  # a different seed must move the schedule
    # Call ORDER must not matter: the draw is a pure function of its inputs.
    d = FaultInjector(seed=9, **spec)
    draws_d = [d.draw("handoff_device_put", t, u) for t, u in reversed(grid)]
    assert list(reversed(draws_d)) == draws_a
    # Faults carry the residual uniform for sub-decisions.
    for f in draws_a:
        if f is not None:
            assert f.kind in ("transfer_error", "delay")
            assert 0.0 <= f.u < 1.0
    s = a.summary()
    assert s["injected"] == len(a.injected)
    assert sum(s["by_site"].values()) == s["injected"]


def test_injector_schedule_entries():
    chaos = FaultInjector(seed=0, schedule=[
        {"point": "lane_health", "kind": "dead_lane", "unit": 1},
        {"point": "decode_tick", "kind": "poison", "tick": 5, "count": 2},
    ])
    # Unit-pinned entry fires on the first matching unit only, once.
    assert chaos.draw("lane_health", 0, unit=0) is None
    f = chaos.draw("lane_health", 0, unit=1)
    assert f is not None and f.kind == "dead_lane"
    assert chaos.draw("lane_health", 1, unit=1) is None  # consumed
    # Tick-pinned entry with count=2 fires exactly twice at that tick.
    assert chaos.draw("decode_tick", 4) is None
    assert chaos.draw("decode_tick", 5).kind == "poison"
    assert chaos.draw("decode_tick", 5).kind == "poison"
    assert chaos.draw("decode_tick", 5) is None


def test_injector_validation():
    with pytest.raises(ValueError):
        FaultInjector(rates={"nope": 0.1})
    with pytest.raises(ValueError):
        FaultInjector(rates={"decode_tick": {"dead_lane": 0.1}})  # illegal kind
    with pytest.raises(ValueError):
        FaultInjector(rates={"handoff_device_put": {"transfer_error": 1.5}})
    with pytest.raises(ValueError):
        FaultInjector(rates={"handoff_device_put": {"transfer_error": 0.6,
                                                    "delay": 0.6}})  # sum > 1
    with pytest.raises(ValueError):
        FaultInjector(schedule=[{"point": "lane_health", "kind": "poison"}])
    with pytest.raises(ValueError):
        FaultInjector(delay_ticks=0)
    # Scalar rate shorthand takes the point's first legal kind.
    chaos = FaultInjector(seed=1, rates={"prefill_dispatch": 1.0})
    assert chaos.draw("prefill_dispatch", 0).kind == "transfer_error"
    assert set(INJECTION_POINTS) == {
        # serving
        "prefill_dispatch", "decode_tick", "handoff_device_put", "lane_health",
        # training
        "train_step", "collective_op", "checkpoint_save", "dataloader_batch",
        "host_heartbeat",
        # weight publication
        "publish_manifest", "publish_transfer", "canary_window",
        # autoscaling
        "autoscale_decide", "resize_transfer", "load_spike",
        # crash durability
        "journal_append", "journal_compact", "engine_crash",
        # fleet routing
        "cell_crash", "cell_partition", "router_heartbeat",
        # speculative decoding + quantized KV pages
        "draft_mismatch", "page_dequant",
    }


def test_training_points_and_extras():
    """Training-side points: kind legality, schedule pass-through fields on
    Fault.extra, slow_step_s validation, and the point-name-keyed hash —
    adding the training points must not have moved any serving schedule."""
    with pytest.raises(ValueError):
        FaultInjector(rates={"train_step": {"torn_write": 0.1}})  # wrong point
    with pytest.raises(ValueError):
        FaultInjector(slow_step_s=-1.0)
    chaos = FaultInjector(seed=4, schedule=[
        {"point": "train_step", "kind": "slow_step", "tick": 2, "seconds": 0.5},
        {"point": "host_heartbeat", "kind": "dead_host", "tick": 3, "unit": 1,
         "exit_code": 77},
        {"point": "checkpoint_save", "kind": "torn_write", "tick": 0},
    ])
    f = chaos.draw("train_step", 2)
    assert f.kind == "slow_step" and f.extra == {"seconds": 0.5}
    assert chaos.draw("host_heartbeat", 3, unit=0) is None  # wrong rank
    f = chaos.draw("host_heartbeat", 3, unit=1)
    assert f.kind == "dead_host" and f.extra == {"exit_code": 77}
    f = chaos.draw("checkpoint_save", 0, unit=0)
    assert f.kind == "torn_write" and f.extra is None
    # Rate-driven training faults carry no extra.
    rated = FaultInjector(seed=4, rates={"train_step": 1.0})
    f = rated.draw("train_step", 0)
    assert f.kind == "nonfinite_grad" and f.extra is None  # first legal kind
    # Point-name keying: a serving-point draw grid is identical whether or
    # not training rates exist on the same injector.
    a = FaultInjector(seed=9, rates={"decode_tick": {"poison": 0.3}})
    b = FaultInjector(seed=9, rates={"decode_tick": {"poison": 0.3},
                                     "train_step": {"slow_step": 0.5}})
    grid = [(t, u) for t in range(40) for u in range(2)]
    assert [a.draw("decode_tick", t, u) for t, u in grid] == \
           [b.draw("decode_tick", t, u) for t, u in grid]


def test_deterministic_jitter():
    vals = [deterministic_jitter(3, t, a) for t in range(20) for a in range(3)]
    assert all(0.5 <= v < 1.0 for v in vals)
    assert vals == [deterministic_jitter(3, t, a)
                    for t in range(20) for a in range(3)]
    assert len(set(vals)) > 10  # actually jitters


def test_injected_fault_error_carries_fault():
    f = FaultInjector(seed=1, rates={"prefill_dispatch": 1.0}).draw(
        "prefill_dispatch", 7, unit=2)
    err = InjectedFaultError(f)
    assert err.fault is f and isinstance(err, RuntimeError)
    assert "prefill_dispatch" in str(err) and "tick 7" in str(err)


# ---------------------------------------------------------------------------
# Engine-level fault handling (colocated)
# ---------------------------------------------------------------------------


def test_poison_quarantines_slot_and_replays_bit_equal(llama):
    """A poisoned KV page mid-decode: the sentinel catches it, the slot is
    quarantined, the request replays idempotently, and EVERY output —
    including the replayed one — stays bit-equal to generate()."""
    cfg, model = llama
    prompts = _prompts(cfg, [3, 7, 12, 20, 5, 9])
    budgets = [6, 4, 8, 3, 5, 6]

    def run(seed):
        chaos = FaultInjector(seed=seed, schedule=[
            {"point": "decode_tick", "kind": "poison", "tick": 8}])
        eng = ServingEngine(
            model, ServingConfig(n_slots=3, max_len=64, prefill_chunks=[4, 8]),
            chaos=chaos)
        ids = [eng.submit(p, max_new_tokens=b)
               for p, b in zip(prompts, budgets)]
        res = _drain(eng, ids)
        return [res[i] for i in ids], eng.stats(), chaos

    res, stats, chaos = run(7)
    assert [r["status"] for r in res] == ["ok"] * len(prompts)
    assert stats["faults"]["slot_quarantines"] == 1
    assert stats["faults"]["retries"] == 1
    assert stats["faults"]["quarantined_slots"] == 1
    assert stats["decode_executables"] == 1  # census survives quarantine
    for p, b, r in zip(prompts, budgets, res):
        want = np.asarray(generate(model, p[None], max_new_tokens=b))[0]
        np.testing.assert_array_equal(r["tokens"], want)
    # Same seed => identical fault schedule, statuses, and rows.
    res2, stats2, chaos2 = run(7)
    assert chaos.injected == chaos2.injected
    assert stats2["faults"] == stats["faults"]
    for a, b_ in zip(res, res2):
        assert a["status"] == b_["status"]
        np.testing.assert_array_equal(a["tokens"], b_["tokens"])


def test_prefill_transfer_error_retries_then_fails(llama):
    """Every injected transfer error at prefill dispatch burns one retry;
    with the budget exhausted the request terminates `failed` — explicitly,
    never silently."""
    cfg, model = llama
    prompts = _prompts(cfg, [5, 9])
    chaos = FaultInjector(seed=2, rates={"prefill_dispatch": 1.0})  # always
    eng = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8],
                             max_retries=2),
        chaos=chaos)
    ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    res = _drain(eng, ids)
    assert [res[i]["status"] for i in ids] == ["failed", "failed"]
    assert eng.stats()["faults"]["failed"] == 2
    assert eng.stats()["faults"]["retries"] == 4  # 2 per request
    assert eng.stats()["requests_completed"] == 0


def test_hang_guard_raises_stalled(llama):
    """Once every slot is quarantined nothing can ever progress — the idle
    guard must raise ServingStalledError naming the stuck request instead of
    spinning forever (the failure mode this PR exists to kill)."""
    cfg, model = llama
    chaos = FaultInjector(seed=3, rates={"decode_tick": {"poison": 1.0}})
    eng = ServingEngine(
        model, ServingConfig(n_slots=1, max_len=64, prefill_chunks=[4, 8],
                             max_retries=50, max_idle_ticks=10),
        chaos=chaos)
    rid = eng.submit(_prompts(cfg, [5])[0], max_new_tokens=4)
    with pytest.raises(ServingStalledError, match=f"{rid}:queued"):
        for _ in range(500):
            eng.tick()
    assert eng.stats()["faults"]["quarantined_slots"] == 1


def test_deadline_timeout_frees_slot(llama):
    """A request that misses its deadline terminates `timeout` and frees its
    slot the same tick — the next request reuses it and completes ok."""
    cfg, model = llama
    prompts = _prompts(cfg, [5, 7])
    eng = ServingEngine(
        model, ServingConfig(n_slots=1, max_len=64, prefill_chunks=[4, 8]))
    import time as _time

    doomed = eng.submit(prompts[0], max_new_tokens=30, deadline_s=1e-4)
    eng.tick()
    _time.sleep(0.001)
    healthy = eng.submit(prompts[1], max_new_tokens=3)
    res = _drain(eng, [doomed, healthy])
    assert res[doomed]["status"] == "timeout"
    assert res[healthy]["status"] == "ok"
    want = np.asarray(generate(model, prompts[1][None], max_new_tokens=3))[0]
    np.testing.assert_array_equal(res[healthy]["tokens"], want)
    assert eng.stats()["faults"]["timeouts"] == 1
    # The timed-out partial row is still returned, padded to prompt+budget.
    assert res[doomed]["tokens"].shape == (len(prompts[0]) + 30,)


def test_admission_reject_and_shed_oldest(llama):
    cfg, model = llama
    prompts = _prompts(cfg, [5, 6, 7, 8, 9, 10])
    sc = dict(n_slots=1, max_len=64, prefill_chunks=[4, 8],
              max_queue_depth=2)
    # reject: the NEW request is shed.
    eng = ServingEngine(model, ServingConfig(**sc, overload_policy="reject"))
    ids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    res = _drain(eng, ids)
    statuses = [res[i]["status"] for i in ids]
    assert statuses.count("shed") >= 1 and statuses.count("ok") >= 1
    assert res[ids[-1]]["status"] == "shed"  # last in, rejected
    assert eng.stats()["faults"]["sheds"] == statuses.count("shed")
    # shed_oldest: the OLDEST queued request is shed, the new one queues.
    eng2 = ServingEngine(model,
                         ServingConfig(**sc, overload_policy="shed_oldest"))
    ids2 = [eng2.submit(p, max_new_tokens=3) for p in prompts]
    res2 = _drain(eng2, ids2)
    assert res2[ids2[-1]]["status"] == "ok"  # newest survived
    assert [res2[i]["status"] for i in ids2].count("shed") >= 1


def test_admission_block_applies_backpressure(llama):
    cfg, model = llama
    prompts = _prompts(cfg, [5, 6, 7, 8])
    eng = ServingEngine(model, ServingConfig(
        n_slots=1, max_len=64, prefill_chunks=[4, 8],
        max_queue_depth=1, overload_policy="block"))
    ids = [eng.submit(p, max_new_tokens=3) for p in prompts]  # blocks inline
    res = _drain(eng, ids)
    assert [res[i]["status"] for i in ids] == ["ok"] * 4  # nobody shed
    assert eng.stats()["faults"]["sheds"] == 0


def test_preemption_drain(llama):
    """SIGTERM mid-serving (modeled by the manager's latch): in-flight
    requests finish ok, queued ones are shed, nothing new admits, and the
    engine reports the resumable exit code 75."""
    cfg, model = llama

    class _FakeFT:
        preempted = False

    ft = _FakeFT()
    prompts = _prompts(cfg, [5, 6, 7, 8])
    eng = ServingEngine(
        model, ServingConfig(n_slots=1, max_len=64, prefill_chunks=[4, 8]),
        fault_tolerance=ft)
    ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(3):  # let request 0 reach decode
        eng.tick()
    ft.preempted = True
    res = _drain(eng, ids)
    assert res[ids[0]]["status"] == "ok"  # in flight: drained to completion
    assert all(res[i]["status"] == "shed" for i in ids[1:])  # queued: shed
    late = eng.submit(prompts[0], max_new_tokens=2)
    assert {r["id"]: r for r in eng.poll()}[late]["status"] == "shed"
    assert eng.preempted is True
    assert eng.preemption_exit_code == 75
    assert eng.stats()["faults"]["preempted"] is True


# ---------------------------------------------------------------------------
# Disagg: lane faults, handoff faults, degraded fallback
# ---------------------------------------------------------------------------


def test_dead_lanes_degrade_to_colocated_bit_equal(llama):
    """Killing EVERY prefill lane mid-flight flips the engine degraded: it
    falls back to colocated prefill on the decode mesh, keeps serving, stays
    bit-equal to generate(), and the decode census stays 1."""
    cfg, model = llama
    prompts = _prompts(cfg, [3, 7, 12, 20, 5, 9])
    budgets = [6, 4, 8, 3, 5, 6]
    chaos = FaultInjector(seed=1, schedule=[
        {"point": "lane_health", "kind": "dead_lane", "unit": 0},
        {"point": "lane_health", "kind": "dead_lane", "unit": 1},
    ])
    eng = DisaggServingEngine(
        model, ServingConfig(n_slots=4, max_len=64, prefill_chunks=[4, 8]),
        disagg=DisaggConfig(n_prefill_lanes=2), chaos=chaos)
    outs = eng.run(prompts, max_new_tokens=budgets)
    for p, b, got in zip(prompts, budgets, outs):
        want = np.asarray(generate(model, p[None], max_new_tokens=b))[0]
        np.testing.assert_array_equal(got, want)
    s = eng.stats()
    assert s["disagg"]["degraded"] is True
    assert s["disagg"]["healthy_lanes"] == 0
    assert s["faults"]["lane_quarantines"] == 2
    assert s["faults"]["degraded"] is True
    assert s["decode_executables"] == 1
    assert s["steady_recompiles"] == 0


def test_handoff_transfer_error_transient_vs_persistent(llama):
    """An injected handoff transfer error with residual u < 0.75 is
    transient (one failed attempt, the retry lands); u >= 0.75 is persistent
    (every retry fails, the lane is quarantined, the request re-queues and
    replays bit-equal on another lane)."""
    cfg, model = llama
    prompts = _prompts(cfg, [3, 7, 12, 20, 5, 9])
    budgets = [6, 4, 8, 3, 5, 6]
    chaos = FaultInjector(
        seed=5, rates={"handoff_device_put": {"transfer_error": 0.25}})
    eng = DisaggServingEngine(
        model, ServingConfig(n_slots=4, max_len=64, prefill_chunks=[4, 8],
                             max_retries=4),
        disagg=DisaggConfig(n_prefill_lanes=2, handoff_retries=1),
        chaos=chaos)
    ids = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    res = _drain(eng, ids)
    f = eng.stats()["faults"]
    kinds = {(e["point"], e["kind"]) for e in chaos.injected}
    assert ("handoff_device_put", "transfer_error") in kinds
    assert f["handoff_retries"] >= 1  # at least one transient retry happened
    for p, b, i in zip(prompts, budgets, ids):
        if res[i]["status"] == "ok":
            want = np.asarray(generate(model, p[None], max_new_tokens=b))[0]
            np.testing.assert_array_equal(res[i]["tokens"], want)
    assert eng.stats()["decode_executables"] == 1


def test_handoff_delay_and_poison(llama):
    """A straggler handoff defers the background insert but never corrupts
    output; a poisoned handoff page is caught by the decode sentinel after
    the slot arms, and the request replays bit-equal."""
    cfg, model = llama
    prompts = _prompts(cfg, [3, 7, 12, 20, 5, 9])
    budgets = [6, 4, 8, 3, 5, 6]
    chaos = FaultInjector(
        seed=13,
        rates={"handoff_device_put": {"delay": 0.15, "poison": 0.08}},
        delay_ticks=4)
    eng = DisaggServingEngine(
        model, ServingConfig(n_slots=4, max_len=64, prefill_chunks=[4, 8],
                             max_retries=4),
        disagg=DisaggConfig(n_prefill_lanes=2), chaos=chaos)
    ids = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    res = _drain(eng, ids)
    f = eng.stats()["faults"]
    kinds = {(e["point"], e["kind"]) for e in chaos.injected}
    assert ("handoff_device_put", "delay") in kinds
    assert f["handoff_delays"] >= 1
    for p, b, i in zip(prompts, budgets, ids):
        if res[i]["status"] == "ok":
            want = np.asarray(generate(model, p[None], max_new_tokens=b))[0]
            np.testing.assert_array_equal(res[i]["tokens"], want)
    if ("handoff_device_put", "poison") in kinds:
        assert f["slot_quarantines"] >= 1  # the sentinel caught it
    assert eng.stats()["decode_executables"] == 1


# ---------------------------------------------------------------------------
# Off-by-default contract
# ---------------------------------------------------------------------------


def test_off_by_default_no_chaos_no_faults(llama):
    """Without an injector or robustness config the engine behaves exactly
    as before: ok statuses, zero fault counters, unchanged result keys."""
    cfg, model = llama
    prompts = _prompts(cfg, [5, 9])
    eng = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8]))
    ids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    res = _drain(eng, ids)
    for i in ids:
        assert res[i]["status"] == "ok"
        assert set(res[i]) == {"id", "status", "tokens", "new_tokens",
                               "ttft_s", "tpot_s", "weights_version",
                               "attempt", "recovered", "drafted", "accepted",
                               "queue_wait_s", "prefill_blocked_s",
                               "prefill_own_s", "token_times_s"}
        assert res[i]["attempt"] == 1 and res[i]["recovered"] is False
    f = eng.stats()["faults"]
    assert f["injected"] == 0 and f["degraded"] is False
    assert all(v in (0, False) for v in f.values())


def test_serving_config_robustness_validation():
    with pytest.raises(ValueError):
        ServingConfig(overload_policy="drop_everything")
    with pytest.raises(ValueError):
        ServingConfig(max_queue_depth=0)
    with pytest.raises(ValueError):
        ServingConfig(deadline_s=0.0)
    with pytest.raises(ValueError):
        ServingConfig(max_retries=-1)
    with pytest.raises(ValueError):
        ServingConfig(max_idle_ticks=0)
    with pytest.raises(ValueError):
        DisaggConfig(handoff_retries=-1)
    with pytest.raises(ValueError):
        DisaggConfig(handoff_backoff_s=0.2, handoff_backoff_cap_s=0.1)
    c = ServingConfig()
    assert c.max_queue_depth is None and c.deadline_s is None
    assert c.overload_policy == "reject"
    assert c.max_retries == 2 and c.max_idle_ticks == 100
