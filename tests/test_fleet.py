"""Fleet router (fleet.py): cell registry/health, session-affinity routing
with spillover and shed, exactly-once cross-cell drain of a dead cell's
journal, and cell-granular publish/scale lifecycle.

All CPU-only, tier-1 fast. The full game day (hard-kill mid-trace, ok rows
bit-equal to an uninterrupted reference, executable census per survivor,
second seeded round bit-identical) lives in `make fleet-smoke`
(test_utils/scripts/fleet_smoke.py); here cells are in-process engines and
a "crash" is the deterministic `cell_crash` chaos point or an engine
abandoned by the router.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import (
    FaultInjector,
    FleetConfig,
    FleetDegradedError,
    FleetRouter,
    Model,
    ServingConfig,
    ServingEngine,
)
from accelerate_tpu.fleet import CELL_STATES, _affinity_hash
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


def _mk_cell(model, wal, **kw):
    cfg = ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8],
                        journal_dir=str(wal), **kw)
    return ServingEngine(model, cfg)


def _fleet(model, tmp_path, n=2, config=None, chaos=None):
    cells = {f"c{i}": _mk_cell(model, tmp_path / f"wal{i}") for i in range(n)}
    return FleetRouter(cells, config, chaos=chaos)


def _drain_fleet(router, guard=5000):
    rows = {}
    ticks = 0
    while router.pending:
        router.tick()
        for r in router.poll():
            rows[r["id"]] = r
        ticks += 1
        assert ticks < guard, "fleet drain guard tripped"
    for r in router.poll():
        rows[r["id"]] = r
    return rows


def _session_for(cell_index, n_cells, prefix="s"):
    """A session key whose affinity hash lands on cell `cell_index` of an
    all-healthy n-cell fleet (routable order is sorted names c0..cN)."""
    for i in range(1000):
        key = f"{prefix}{i}"
        if _affinity_hash(key) % n_cells == cell_index:
            return key
    raise AssertionError("no session key found")


# ---------------------------------------------------------------------------
# registry + routing
# ---------------------------------------------------------------------------


def test_registry_requires_journal_and_unique_names(llama, tmp_path):
    cfg, model = llama
    bare = ServingEngine(model, ServingConfig(
        n_slots=2, max_len=32, prefill_chunks=[4, 8]))
    with pytest.raises(ValueError, match="no journal"):
        FleetRouter({"c0": bare})
    bare.close()
    with pytest.raises(ValueError, match="at least one cell"):
        FleetRouter({})
    router = _fleet(model, tmp_path, n=2)
    assert router.cell_states() == {"c0": "healthy", "c1": "healthy"}
    assert set(router.cell_states().values()) <= set(CELL_STATES)
    with pytest.raises(ValueError, match="already registered"):
        router.scale_up("c0", engine=router._cells["c0"].engine)
    router.close()


def test_affinity_routing_is_deterministic(llama, tmp_path):
    cfg, model = llama
    router = _fleet(model, tmp_path, n=2)
    prompts = _prompts(cfg, [5, 6, 7, 8])
    placed = {}
    for i, p in enumerate(prompts):
        rid = router.submit(p, max_new_tokens=3, rng=jax.random.key(i),
                            client_request_id=f"r{i}", session_id=f"sess{i}")
        placed[rid] = router._requests[rid]["cell"]
    # Pure function of the session key: matches the hash, and repeats.
    for rid, cell in placed.items():
        key = router._requests[rid]["session"]
        want = f"c{_affinity_hash(key) % 2}"
        assert cell == want
    rows = _drain_fleet(router)
    assert len(rows) == 4
    for rid, row in rows.items():
        assert row["cell"] == placed[rid]
        assert row["spilled"] is False and row["drained_from"] is None
        assert row["status"] == "ok"
    s = router.stats()
    assert s["routed_affinity"] == 4 and s["routed_spilled"] == 0
    assert s["completed"] == 4 and s["ok"] == 4
    router.close()


def test_spillover_when_affinity_target_breaches(llama, tmp_path):
    cfg, model = llama
    # Band of 1.0: the affinity target breaches once its rolling
    # queue-depth p95 exceeds one pending request.
    router = _fleet(model, tmp_path, n=2,
                    config=FleetConfig(queue_depth_band=1.0))
    hot = _session_for(0, 2)
    prompts = _prompts(cfg, [5, 6, 7, 8, 5])
    # Pile work on c0 (no ticks yet: p95 window is empty, nothing spills).
    for i, p in enumerate(prompts[:4]):
        router.submit(p, max_new_tokens=6, rng=jax.random.key(i),
                      session_id=hot)
    assert router.stats()["routed_spilled"] == 0
    router.tick()  # c0's window now samples queue depth > band
    rid = router.submit(prompts[4], max_new_tokens=3,
                        rng=jax.random.key(9), session_id=hot)
    rec = router._requests[rid]
    assert rec["spilled"] is True and rec["cell"] == "c1"
    rows = _drain_fleet(router)
    assert rows[rid]["spilled"] is True and rows[rid]["cell"] == "c1"
    assert router.stats()["routed_spilled"] == 1
    router.close()


def test_shed_only_when_all_cells_breach(llama, tmp_path):
    cfg, model = llama
    router = _fleet(model, tmp_path, n=1,
                    config=FleetConfig(queue_depth_band=1.0))
    prompts = _prompts(cfg, [5, 6, 7, 8, 5])
    for i, p in enumerate(prompts[:4]):
        router.submit(p, max_new_tokens=6, rng=jax.random.key(i),
                      session_id="s")
    router.tick()
    rid = router.submit(prompts[4], max_new_tokens=4,
                        rng=jax.random.key(9), session_id="s")
    row = router._rows[rid]
    assert row["status"] == "shed" and row["cell"] is None
    # The shed row carries the FULL fleet poll schema — engine keys plus
    # provenance — and pads the prompt to budget like an engine shed.
    assert set(row) == {
        "id", "status", "tokens", "new_tokens", "ttft_s", "tpot_s",
        "weights_version", "attempt", "recovered", "drafted", "accepted",
        "queue_wait_s", "prefill_blocked_s", "prefill_own_s", "token_times_s",
        "cell", "spilled", "drained_from",
    }
    assert row["tokens"].shape == (len(prompts[4]) + 4,)
    rows = _drain_fleet(router)
    assert rows[rid]["status"] == "shed"
    s = router.stats()
    assert s["shed"] == 1 and s["completed"] == 5
    assert s["ok"] == 4
    router.close()


def test_fleetwide_cid_dedupe(llama, tmp_path):
    cfg, model = llama
    router = _fleet(model, tmp_path, n=2)
    (p,) = _prompts(cfg, [5])
    rid = router.submit(p, max_new_tokens=3, rng=jax.random.key(0),
                        client_request_id="dup")
    assert router.submit(p, max_new_tokens=3,
                         client_request_id="dup") == rid
    rows = _drain_fleet(router)
    assert set(rows) == {rid}
    # A duplicate AFTER completion re-emits the finished row.
    assert router.submit(p, max_new_tokens=3,
                         client_request_id="dup") == rid
    (again,) = router.poll()
    assert again["id"] == rid
    assert np.array_equal(again["tokens"], rows[rid]["tokens"])
    s = router.stats()
    assert s["submitted"] == 1 and s["deduped"] == 2
    router.close()


# ---------------------------------------------------------------------------
# health + cross-cell drain
# ---------------------------------------------------------------------------


def test_cell_crash_drains_exactly_once_and_bit_equal(llama, tmp_path):
    cfg, model = llama
    prompts = _prompts(cfg, [5, 6, 7, 8, 5, 6])

    def run(root, chaos):
        router = FleetRouter(
            {f"c{i}": _mk_cell(model, root / f"wal{i}") for i in range(2)},
            chaos=chaos)
        rids = {}
        for i, p in enumerate(prompts):
            rids[f"r{i}"] = router.submit(
                p, max_new_tokens=6, rng=jax.random.key(i),
                client_request_id=f"r{i}", session_id=f"sess{i}")
        rows = _drain_fleet(router)
        by_cid = {cid: rows[rid] for cid, rid in rids.items()}
        stats = router.stats()
        return router, by_cid, stats

    ref_router, ref, _ = run(tmp_path / "ref", None)
    ref_router.close()

    chaos = FaultInjector(seed=29, schedule=[
        {"point": "cell_crash", "kind": "crash", "tick": 1, "unit": 0}])
    router, got, s = run(tmp_path / "chaos", chaos)
    assert router.cell_states()["c0"] == "dead"
    assert s["dead"] == 1 and s["drains"] == 1
    assert s["drained_cached"] + s["drained_resubmitted"] >= 1
    assert s["drain_last_s"] is not None
    # Exactly-once: every request resolves exactly once, bit-equal to the
    # uninterrupted reference under equal weights.
    assert set(got) == set(ref)
    for cid in ref:
        assert got[cid]["status"] == "ok" == ref[cid]["status"]
        assert np.array_equal(got[cid]["tokens"], ref[cid]["tokens"])
    # Provenance: c0's requests carry drained_from and recovered.
    moved = [r for r in got.values() if r["drained_from"] == "c0"]
    assert moved and all(r["recovered"] and r["cell"] != "c0"
                         for r in moved)
    # The survivor kept the zero-recompile invariant through the drain.
    surv = router._cells["c1"].engine
    assert surv.executable_counts()["decode"] == 1
    assert surv._stats["steady_recompiles"] == 0
    # Exactly-once on-device: the survivor EXECUTED only what was not
    # already journaled terminal on the dead cell.
    assert surv._stats["completed"] == len(prompts) - s["drained_cached"]
    # Dedupe survives the cell's death: resubmitting a drained cid
    # re-emits its row instead of re-executing.
    before = router.stats()["completed"]
    rid = router.submit(prompts[0], max_new_tokens=6,
                        client_request_id="r0")
    (row,) = router.poll()
    assert row["id"] == rid
    assert np.array_equal(row["tokens"], got["r0"]["tokens"])
    assert router.stats()["completed"] == before
    assert router.stats()["deduped"] == 1
    router.close()


def test_cell_crash_drain_replays_speculative_cells_bit_equal(llama, tmp_path):
    """Cross-cell drain with speculation on in every cell: the survivor
    re-executes the dead cell's in-flight requests through its own
    speculative decode path and every row stays bit-equal to an
    uninterrupted speculative fleet AND to a non-speculative one (exact
    verification composes with the drain's rng/idempotency replay)."""
    cfg, model = llama
    prompts = _prompts(cfg, [5, 6, 7, 8])
    spec = dict(speculate_k=2, speculate_ngram=8)

    def run(root, chaos, **kw):
        router = FleetRouter(
            {f"c{i}": _mk_cell(model, root / f"wal{i}", **kw)
             for i in range(2)},
            chaos=chaos)
        rids = {}
        for i, p in enumerate(prompts):
            rids[f"r{i}"] = router.submit(
                p, max_new_tokens=6, rng=jax.random.key(i),
                client_request_id=f"r{i}", session_id=f"sess{i}")
        rows = _drain_fleet(router)
        by_cid = {cid: rows[rid] for cid, rid in rids.items()}
        return router, by_cid

    plain_router, plain = run(tmp_path / "plain", None)
    plain_router.close()
    ref_router, ref = run(tmp_path / "ref", None, **spec)
    ref_router.close()

    chaos = FaultInjector(seed=29, schedule=[
        {"point": "cell_crash", "kind": "crash", "tick": 1, "unit": 0}])
    router, got = run(tmp_path / "chaos", chaos, **spec)
    assert router.cell_states()["c0"] == "dead"
    assert set(got) == set(ref) == set(plain)
    for cid in ref:
        assert got[cid]["status"] == "ok"
        # Speculation never changes greedy output: chaos == spec ref ==
        # non-speculative fleet, token for token.
        assert np.array_equal(got[cid]["tokens"], ref[cid]["tokens"])
        assert np.array_equal(got[cid]["tokens"], plain[cid]["tokens"])
    # Requests the survivor re-executed drafted through its own engine.
    resub = [r for r in got.values()
             if r["drained_from"] == "c0" or r["cell"] == "c1"]
    assert any(r["drafted"] > 0 for r in resub)
    surv = router._cells["c1"].engine
    assert surv.executable_counts()["decode"] == 1
    assert surv._stats["steady_recompiles"] == 0
    assert surv.stats()["speculation"]["drafted"] > 0
    router.close()


def test_idle_cell_is_declared_dead_and_drained(llama, tmp_path):
    cfg, model = llama
    router = _fleet(model, tmp_path, n=2,
                    config=FleetConfig(max_idle_ticks=3))
    hot = _session_for(0, 2)
    (p,) = _prompts(cfg, [5])
    rid = router.submit(p, max_new_tokens=4, rng=jax.random.key(0),
                        client_request_id="stuck", session_id=hot)
    assert router._requests[rid]["cell"] == "c0"
    # Wedge c0: it heartbeats but never makes progress.
    router._cells["c0"].engine.tick = lambda: None
    ticks = 0
    while router.cell_states()["c0"] != "dead":
        router.tick()
        ticks += 1
        assert ticks < 20, "idle-death detection never fired"
    assert router._cells["c0"].death_class == "cell-dead"
    rows = _drain_fleet(router)
    assert rows[rid]["status"] == "ok"
    assert rows[rid]["cell"] == "c1" and rows[rid]["drained_from"] == "c0"
    assert router.stats()["drained_resubmitted"] == 1
    router.close()


def test_partition_degrades_then_heals(llama, tmp_path):
    cfg, model = llama
    chaos = FaultInjector(seed=7, schedule=[
        {"point": "cell_partition", "kind": "delay", "tick": 0, "unit": 1,
         "delay_ticks": 3}])
    router = _fleet(model, tmp_path, n=2, chaos=chaos)
    router.tick()
    assert router.cell_states()["c1"] == "degraded"
    # Degraded = unreachable for NEW admissions; routing redirects to c0.
    cold = _session_for(1, 2)
    (p,) = _prompts(cfg, [5])
    rid = router.submit(p, max_new_tokens=3, rng=jax.random.key(0),
                        session_id=cold)
    assert router._requests[rid]["cell"] == "c0"
    while router.cell_states()["c1"] != "healthy":
        router.tick()
    assert router.stats()["degraded"] == 0
    rows = _drain_fleet(router)
    assert rows[rid]["status"] == "ok"
    router.close()


def test_router_heartbeat_chaos_skips_health_pass(llama, tmp_path):
    cfg, model = llama
    chaos = FaultInjector(seed=11, schedule=[
        {"point": "router_heartbeat", "kind": "delay", "tick": 0}])
    router = _fleet(model, tmp_path, n=1, chaos=chaos)
    router.tick()
    assert router.stats()["heartbeat_skips"] == 1
    router.tick()
    assert router.stats()["heartbeat_skips"] == 1
    router.close()


def test_no_healthy_cell_raises_fleet_degraded(llama, tmp_path):
    cfg, model = llama
    from accelerate_tpu.utils.constants import FLEET_DEGRADED_EXIT_CODE

    router = _fleet(model, tmp_path, n=1)
    router._kill_cell(router._cells["c0"], "cell-dead", reason="test")
    (p,) = _prompts(cfg, [5])
    with pytest.raises(FleetDegradedError) as ei:
        router.submit(p, max_new_tokens=3)
    assert ei.value.exit_code == FLEET_DEGRADED_EXIT_CODE
    router.close()


# ---------------------------------------------------------------------------
# cell-granular lifecycle
# ---------------------------------------------------------------------------


def _pump(router, cfg, session, n, budget=3, seed=100, cid_prefix="p",
          deadline_s=None):
    rids = []
    prompts = _prompts(cfg, [5] * n, seed=seed)
    for i, p in enumerate(prompts):
        rids.append(router.submit(
            p, max_new_tokens=budget, rng=jax.random.key(seed + i),
            client_request_id=f"{cid_prefix}{i}", session_id=session,
            deadline_s=deadline_s))
    return rids


def test_publish_canaries_one_cell_then_promotes_fleetwide(llama, tmp_path):
    cfg, model = llama
    router = _fleet(model, tmp_path, n=2,
                    config=FleetConfig(canary_ticks=1, min_canary_cohort=2))
    c0, c1 = _session_for(0, 2), _session_for(1, 2)
    # Baseline traffic on the non-canary cell.
    _pump(router, cfg, c1, 2, cid_prefix="b")
    _drain_fleet(router)
    params = model.params
    out = router.publish(params, weights_version=7)
    assert out == {"version": 7, "canary_cell": "c0"}
    with pytest.raises(ValueError, match="already in flight"):
        router.publish(params, weights_version=8)
    # Canary-cell admissions bind the candidate at fraction=1.0.
    _pump(router, cfg, c0, 3, cid_prefix="c")
    rows = _drain_fleet(router)
    canary_rows = [r for r in rows.values() if r["cell"] == "c0"]
    assert canary_rows and all(
        r["weights_version"] == 7 for r in canary_rows)
    s = router.stats()
    assert s["publishes"] == 1 and s["promoted"] == 1
    assert s["rolled_back"] == 0 and s["quarantined_versions"] == []
    # Promote-all: every live cell now serves version 7.
    for name in ("c0", "c1"):
        assert router._cells[name].engine.weights_version == 7
    router.close()


def test_publish_rollback_quarantines_the_version(llama, tmp_path):
    cfg, model = llama
    router = _fleet(model, tmp_path, n=2,
                    config=FleetConfig(canary_ticks=1, min_canary_cohort=2,
                                       slo_tolerance=0.05))
    c1 = _session_for(1, 2)
    # Healthy baseline on c1.
    _pump(router, cfg, c1, 3, cid_prefix="b")
    _drain_fleet(router)
    params = model.params
    router.publish(params, weights_version=9)
    # A candidate that blows the SLO: the canary cohort's terminal events
    # are all timeouts (seeded into the engine's real cohort store — the
    # engine-side accounting itself is test_publish.py's subject), so the
    # canary ok-ratio is 0 against a baseline of 1.
    router._cells["c0"].engine._cohorts[9]["events"].extend(
        {"status": "timeout", "ttft_s": None, "tpot_s": None}
        for _ in range(3))
    for _ in range(3):
        router.tick()
    s = router.stats()
    assert s["rolled_back"] == 1 and s["promoted"] == 0
    assert s["quarantined_versions"] == [9]
    assert router._cells["c1"].engine.weights_version == 0
    with pytest.raises(ValueError, match="quarantined"):
        router.publish(params, weights_version=9)
    # A fresh version is still publishable after the quarantine.
    router.publish(params, weights_version=10)
    router.close()


def test_scale_up_and_drain_down(llama, tmp_path):
    cfg, model = llama
    router = _fleet(model, tmp_path, n=1)
    router.scale_up("c1", engine=_mk_cell(model, tmp_path / "walN"))
    assert router.stats()["cells"] == 2
    assert router.cell_states()["c1"] == "healthy"
    # Requests on the draining cell finish; then it closes + deregisters.
    hot = _session_for(0, 2)
    rids = _pump(router, cfg, hot, 2)
    router.scale_down("c0")
    assert router.cell_states()["c0"] == "draining"
    (p,) = _prompts(cfg, [6], seed=9)
    moved = router.submit(p, max_new_tokens=3, rng=jax.random.key(5),
                          session_id=hot)
    assert router._requests[moved]["cell"] == "c1"
    rows = _drain_fleet(router)
    assert all(rows[r]["status"] == "ok" for r in rids + [moved])
    s = router.stats()
    assert s["cells"] == 1 and s["scale_ups"] == 1 and s["scale_downs"] == 1
    assert "c0" not in router.cell_states()
    with pytest.raises(ValueError, match="no live cell"):
        router.scale_down("c0")
    router.close()


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="max_idle_ticks"):
        FleetConfig(max_idle_ticks=0)
    with pytest.raises(ValueError, match="queue_depth_band"):
        FleetConfig(queue_depth_band=0.0)
    with pytest.raises(ValueError, match="canary_ticks"):
        FleetConfig(canary_ticks=0)
    with pytest.raises(ValueError, match="min_canary_cohort"):
        FleetConfig(min_canary_cohort=0)
    with pytest.raises(ValueError, match="slo_tolerance"):
        FleetConfig(slo_tolerance=1.0)
