"""Unit tests for profiler.py: the exactly-summing attribution identity,
the one-step lag, flight-ring eviction and per-exit-class dumps, and the
MetricsHub registration/collision/alias/SLO contracts. All host-side —
no devices, no Accelerator; tier-1 fast."""

import json
import os

import pytest

from accelerate_tpu.profiler import (
    COMM_AXES,
    STEP_TERMS,
    TICK_TERMS,
    DeviceTimeProfiler,
    FlightRecorder,
    MetricsHub,
    ProfilerConfig,
    dump_flight,
    exit_class_name,
    find_flight_bundles,
)
from accelerate_tpu.utils.constants import (
    EXIT_CODE_TABLE,
    FLIGHT_DIR_ENV,
    SDC_EXIT_CODE,
    SERVING_CRASH_EXIT_CODE,
)

# A plan artifact shaped like planner.ParallelPlan.to_json_dict() — enough
# for note_plan to price comm terms and bandwidth residuals.
PLAN = {
    "layout": {"dp_shard": 8},
    "n_devices": 8,
    "predicted_step_s": 0.010,
    "breakdown": {
        "compute_s": 0.006,
        "fsdp_comm_s": 0.003,
        "dp_comm_s": 0.0,
        "tp_comm_s": 0.0,
        "cp_comm_s": 0.0,
        "pp_comm_s": 0.0,
        "fsdp_bytes": 1 << 20,
        "step_s": 0.010,
    },
    "bandwidths": {
        "ici_gbps": 100.0,
        "dcn_gbps": 25.0,
        "flops_per_chip": 1e12,
        "mfu": 0.4,
        "collective_efficiency": 0.8,
        "ici_domain": 64,
        "dp_overlap": 0.8,
    },
}


def _profiler(**cfg):
    cfg.setdefault("capture_cost", False)
    return DeviceTimeProfiler(ProfilerConfig(**cfg))


def _sum_terms(rec):
    return sum(rec["terms"].values())


# ---------------------------------------------------------------------------
# attribution identity + lag
# ---------------------------------------------------------------------------


def test_step_terms_sum_exactly_with_plan():
    prof = _profiler()
    prof.note_plan(PLAN)
    prof.note_straggler(0.001)
    for i in range(5):
        prof.on_step(i, wall_s=0.02, data_wait_s=0.002)
    prof.flush()
    recs = prof.records()
    assert len(recs) == 5
    for rec in recs:
        assert rec["kind"] == "step"
        assert set(rec["terms"]) == set(STEP_TERMS)
        assert abs(_sum_terms(rec) - rec["wall_s"]) < 1e-8
        assert rec["terms"]["data_wait_s"] == pytest.approx(0.002)
        assert rec["terms"]["straggler_skew_s"] == pytest.approx(0.001)
    # fsdp is the only active axis: the comm split and bandwidth samples
    # name it and nothing else.
    assert set(recs[0]["comm_axes_s"]) == {"fsdp"}
    assert set(recs[0]["bandwidth"]) == {"fsdp"}
    assert recs[0]["overlap_ratio"] is not None
    summary = prof.summary()
    assert summary["steps"] == 5
    assert set(summary["bandwidth_residuals"]) == {"fsdp"}
    assert summary["bandwidth_residuals"]["fsdp"]["residual_mean"] > 0
    assert summary["overlap_ratio_mean"] is not None


def test_step_terms_without_plan_degrade_to_residual():
    """No plan, no cost: the decomposition keeps the identity with the
    dispatch residual carrying the unattributed wall, and the overlap
    ratio is withheld rather than invented."""
    prof = _profiler()
    prof.on_step(0, wall_s=0.02, data_wait_s=0.0)
    prof.flush()
    (rec,) = prof.records()
    assert abs(_sum_terms(rec) - rec["wall_s"]) < 1e-8
    assert rec["terms"]["device_compute_s"] == 0.0
    assert rec["terms"]["comm_exposed_s"] == 0.0
    assert rec["overlap_ratio"] is None
    assert rec["bandwidth"] is None
    assert prof.summary()["overlap_ratio_mean"] is None


def test_straggler_skew_capped_to_budget_fraction():
    prof = _profiler(max_skew_fraction=0.5)
    prof.note_straggler(10.0)  # a stale spike far beyond the step wall
    prof.on_step(0, wall_s=0.02, data_wait_s=0.0)
    prof.flush()
    (rec,) = prof.records()
    assert rec["terms"]["straggler_skew_s"] == pytest.approx(0.01)
    assert abs(_sum_terms(rec) - rec["wall_s"]) < 1e-8


def test_lagged_fetch_one_step_behind():
    """on_step(N) finalizes N-1; the pending record only lands at flush."""
    prof = _profiler()
    prof.on_step(0, wall_s=0.01, data_wait_s=0.0)
    assert prof.records() == []
    prof.on_step(1, wall_s=0.01, data_wait_s=0.0)
    assert [r["step"] for r in prof.records()] == [0]
    prof.flush()
    assert [r["step"] for r in prof.records()] == [0, 1]
    prof.flush()  # idempotent: nothing pending
    assert len(prof.records()) == 2


def test_tick_terms_sum_with_bookkeeping_residual():
    prof = _profiler()
    sections = {"admit_s": 0.001, "prefill_s": 0.002, "decode_s": 0.003,
                "host_fetch_s": 0.001, "bookkeeping_s": 0.0005}
    for i in range(3):
        prof.on_tick(i, wall_s=0.010, sections=sections)
    prof.flush()
    recs = prof.records()
    assert len(recs) == 3
    for rec in recs:
        assert rec["kind"] == "tick"
        assert set(rec["terms"]) == set(TICK_TERMS)
        assert abs(_sum_terms(rec) - rec["wall_s"]) < 1e-8
        # residual absorbed the unmeasured 2.5ms on top of its section
        assert rec["terms"]["bookkeeping_s"] == pytest.approx(0.003)
    assert prof.summary()["ticks"] == 3


def test_reset_keeps_pricing_drops_records():
    prof = _profiler()
    prof.note_plan(PLAN)
    prof.on_step(0, wall_s=0.02, data_wait_s=0.0)
    prof.flush()
    assert prof.records()
    prof.reset()
    assert prof.records() == []
    assert prof.summary()["steps"] == 0
    prof.on_step(1, wall_s=0.02, data_wait_s=0.0)
    prof.flush()
    (rec,) = prof.records()
    assert rec["comm_axes_s"], "plan pricing must survive reset()"


# ---------------------------------------------------------------------------
# flight ring
# ---------------------------------------------------------------------------


def test_ring_eviction_keeps_newest():
    prof = _profiler(ring_size=4)
    for i in range(10):
        prof.on_step(i, wall_s=0.01, data_wait_s=0.0)
    prof.flush()
    recs = prof.records()
    assert len(recs) == 4
    assert [r["step"] for r in recs] == [6, 7, 8, 9]
    assert prof.summary()["ring"] == {"capacity": 4, "len": 4}
    assert prof.summary()["steps"] == 10  # aggregates ignore eviction


@pytest.mark.parametrize("code,klass", [
    (SERVING_CRASH_EXIT_CODE, "serving-crash"),
    (SDC_EXIT_CODE, "sdc"),
])
def test_flight_dump_per_exit_class(tmp_path, code, klass):
    prof = DeviceTimeProfiler(ProfilerConfig(capture_cost=False),
                              out_dir=str(tmp_path))
    prof.on_step(7, wall_s=0.01, data_wait_s=0.0)
    prof.note_gauge("journal_lsn", 42)
    path = dump_flight(prof, code, reason="test")
    assert path == str(tmp_path / f"flight_{klass}.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["exit_class"] == klass
    assert doc["reason"] == "test"
    assert doc["gauges"]["journal_lsn"] == 42
    # dump_flight flushed the lagged record: the ring identifies step 7.
    assert doc["entries"][-1]["step"] == 7
    assert prof.summary()["flight_dumps"] == 1


def test_exit_class_name_covers_table():
    for row in EXIT_CODE_TABLE:
        assert exit_class_name(row["code"]) == row["classification"]
    assert exit_class_name(1) == "1"


def test_flight_dir_env_overrides_out_dir(tmp_path, monkeypatch):
    env_dir = tmp_path / "supervisor"
    monkeypatch.setenv(FLIGHT_DIR_ENV, str(env_dir))
    fr = FlightRecorder(out_dir=str(tmp_path / "out"))
    fr.record("step", step=1)
    path = fr.dump("oom")
    assert path == str(env_dir / "flight_oom.json")
    assert find_flight_bundles()[0] == os.path.abspath(path)


def test_dump_flight_respects_flight_off():
    prof = DeviceTimeProfiler(ProfilerConfig(capture_cost=False,
                                             flight=False))
    prof.on_step(0, wall_s=0.01, data_wait_s=0.0)
    assert dump_flight(prof, SERVING_CRASH_EXIT_CODE) is None
    assert dump_flight(None, SERVING_CRASH_EXIT_CODE) is None


# ---------------------------------------------------------------------------
# MetricsHub
# ---------------------------------------------------------------------------


def test_hub_cross_kind_collision_rejected():
    hub = MetricsHub()
    hub.counter("serving_requests_total")
    with pytest.raises(ValueError, match="cross-kind"):
        hub.gauge("serving_requests_total")
    # same-kind re-registration returns the same instrument
    c = hub.counter("serving_requests_total")
    c.inc(3)
    assert "accelerate_tpu_serving_requests_total 3.0" in hub.render()


def test_hub_rejects_malformed_names():
    hub = MetricsHub()
    for bad in ("Caps", "1leading", "dash-ed", ""):
        with pytest.raises(ValueError):
            hub.counter(bad)
        with pytest.raises(ValueError):
            hub.register_provider(bad, dict)


def test_hub_provider_collision_and_replace():
    hub = MetricsHub()
    a = lambda: {"x": 1}  # noqa: E731
    b = lambda: {"x": 2}  # noqa: E731
    hub.register_provider("sub", a)
    hub.register_provider("sub", a)  # same callable: idempotent
    with pytest.raises(ValueError, match="replace=True"):
        hub.register_provider("sub", b)
    hub.register_provider("sub", b, replace=True)
    assert "accelerate_tpu_sub_x 2" in hub.render()


def test_hub_provider_walk_skips_non_numeric():
    hub = MetricsHub()
    hub.register_provider("j", lambda: {
        "appends": 5, "dir": "/tmp/x", "nested": {"ok": True},
        "none": None, "ratio": float("nan")})
    names = hub.metric_names()
    assert names == {"accelerate_tpu_j_appends", "accelerate_tpu_j_nested_ok"}


def test_hub_slo_burn_rate():
    hub = MetricsHub()
    with pytest.raises(ValueError):
        hub.register_slo("bad", 1.5)
    hub.register_slo("avail", 0.9, window=100)
    for _ in range(18):
        hub.observe_slo("avail", True)
    for _ in range(2):
        hub.observe_slo("avail", False)
    rec = hub.burn_rates()["avail"]
    assert rec["events"] == 20
    assert rec["error_rate"] == pytest.approx(0.1)
    assert rec["burn_rate"] == pytest.approx(1.0, abs=1e-6)
    assert rec["alert"] is False  # at budget, not over it
    hub.observe_slo("avail", False)
    assert hub.burn_rates()["avail"]["alert"] is True
    names = hub.metric_names()
    assert "accelerate_tpu_slo_avail_burn_rate" in names
    assert "accelerate_tpu_slo_avail_error_rate" in names


def test_profiler_summary_renders_under_profile_subsystem():
    hub = MetricsHub()
    prof = _profiler()
    hub.register_provider("profile", prof.summary)
    prof.on_step(0, wall_s=0.01, data_wait_s=0.0)
    prof.flush()
    names = hub.metric_names()
    assert "accelerate_tpu_profile_steps" in names
    assert "accelerate_tpu_profile_ring_capacity" in names


def test_comm_axes_cover_planner_axes():
    from accelerate_tpu.planner import CostBreakdown

    bd = CostBreakdown()
    for axis in COMM_AXES:
        assert hasattr(bd, f"{axis}_comm_s")
