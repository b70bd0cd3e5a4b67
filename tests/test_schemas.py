"""Public observability schema pins: the exact key sets of ``poll()`` rows,
``stats()`` (including the faults/window/disagg/autoscale blocks), and
``summary()`` top-level blocks. These dicts are consumed by bench rows,
smokes, dashboards, and the autoscaler — a silently renamed or dropped key
breaks them downstream, so additions/removals must update these pins
deliberately. All CPU-only, tier-1 fast."""

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


POLL_ROW_KEYS = {
    "id", "status", "tokens", "new_tokens", "ttft_s", "tpot_s",
    "weights_version", "attempt", "recovered", "drafted", "accepted",
    # serving.timing_row_keys: TTFT's three terms and each token's time
    "queue_wait_s", "prefill_blocked_s", "prefill_own_s", "token_times_s",
}

SERVING_STATS_KEYS = {
    "requests_submitted", "requests_completed", "tokens_out",
    "prompt_tokens_in", "elapsed_s", "tokens_per_s",
    "ttft_p50_s", "ttft_p95_s", "ttft_queue_wait_mean_s",
    "ttft_prefill_mean_s", "tpot_mean_s",
    "ttft_terms", "token_gap", "tick_phases",
    "ticks", "decode_steps", "steps_overlapped", "prefill_chunks", "prefill_chunks_fused",
    "prefill_pad_tokens",
    "prefill_ladder", "n_slots", "mean_occupancy", "peak_occupancy",
    "cache", "passes", "layout", "mean_queue_depth", "slot_allocs", "slot_reuses", "steady_recompiles",
    "prefill_steady_recompiles", "decode_executables", "prefill_executables",
    "decode_chunk_executables", "weights_version",
    "canary", "window", "faults", "journal", "sdc", "speculation",
}

# stats()["speculation"] (ServingEngine.speculation_stats): live whether or
# not speculate_k is set — zeros/None when off, so dashboards key off one
# shape. Feeds the hub's accelerate_tpu_spec_* series and the
# serving_speculative bench row.
SPECULATION_KEYS = {
    "k", "ngram", "drafted", "accepted", "acceptance_rate",
    "tokens_per_tick", "verify_time_s",
}

# The engine ``stats()["sdc"]`` block (DecodeCanary.summary; None when no
# canary is attached) and the telemetry ``summary()["sdc"]`` block
# (SDCSentinel.summary).
SDC_CANARY_KEYS = {
    "every", "armed", "golden_digest", "probes", "mismatches",
    "quarantines", "suppressed_rows",
}

SDC_SUMMARY_KEYS = {
    "vote_every", "repair", "digests", "votes", "mismatches", "probes",
    "probes_failed", "repairs", "quarantines", "quarantined_hosts",
    "peer_quarantined",
}

JOURNAL_KEYS = {
    "dir", "fsync", "appends", "bytes_written", "syncs", "rotations",
    "compactions", "compact_aborts", "records_retired", "torn_writes",
    "torn_tails", "corrupt_skipped", "pending", "retired",
    "recovered_inflight", "recovered_terminal", "deduped",
}

WINDOW_KEYS = {
    "requests", "capacity", "ok", "ttft_p50_s", "ttft_p95_s",
    "tpot_p50_s", "tpot_p95_s", "shed_rate", "timeout_rate", "failed_rate",
    "queue_depth_p95", "prompt_decode_ratio",
}

FAULTS_KEYS = {
    "sheds", "timeouts", "failed", "retries", "slot_quarantines",
    "lane_quarantines", "handoff_retries", "handoff_delays",
    "promoted", "rolled_back",
    "injected", "quarantined_slots", "degraded", "preempted",
}

DISAGG_KEYS = {
    "slice_plan", "n_prefill_devices", "n_decode_devices",
    "decode_slot_sharded", "n_prefill_lanes", "handoff_depth",
    "handoff_transfers", "handoff_inserts", "handoff_bytes",
    "handoff_final_flushes", "handoff_lat_sampled", "handoff_lat_mean_s",
    "handoff_lat_p95_s", "quarantined_lanes", "healthy_lanes", "degraded",
    "measured_flop_ratio", "resize",
}

AUTOSCALE_KEYS = {
    "samples", "decisions", "holds", "grows", "shrinks", "resplits",
    "dead_device_shrinks", "resizes", "aborts", "flap_damped", "spikes",
    "planner_refusals", "active_devices", "pool_devices", "dead_devices",
    "cooldown_until_tick", "breach_over", "breach_under", "last_action",
}

# Fleet-router poll rows are the engine row plus routing provenance; the
# stats() block feeds the MetricsHub ``accelerate_tpu_fleet_*`` series and
# the serving_fleet bench row.
FLEET_POLL_ROW_KEYS = POLL_ROW_KEYS | {"cell", "spilled", "drained_from"}

FLEET_STATS_KEYS = {
    "cells", "healthy", "degraded", "draining", "dead", "ticks",
    "submitted", "deduped", "routed_affinity", "routed_spilled", "shed",
    "completed", "ok", "heartbeat_skips",
    "drains", "drained_cached", "drained_resubmitted", "drain_last_s",
    "publishes", "promoted", "rolled_back", "quarantined_versions",
    "scale_ups", "scale_downs", "per_cell",
}

FLEET_PER_CELL_KEYS = {
    "state", "pending", "weights_version", "queue_depth_p95",
    "requests_completed", "decode_executables", "steady_recompiles",
}

TRACING_STATS_KEYS = {
    "spans", "dropped_spans", "by_kind", "requests", "open_spans", "flows",
}

# Blocks summary() may legally contain; anything else is an unpinned leak.
SUMMARY_ALWAYS = {
    "steps", "recompiles", "peak_hbm_bytes", "collectives",
    "checkpoint_events", "checkpoint",
}
SUMMARY_OPTIONAL = {
    "faults", "watchdog", "serving", "reshard", "disagg", "publish",
    "autoscale", "plan", "tracing", "executables", "compile", "sdc",
    "profile",
    "step_time_mean_s", "step_time_p50_s", "step_time_p90_s",
    "data_wait_mean_s", "ema_samples_per_s", "ema_tokens_per_s",
}

# The summary()["profile"] block (profiler.DeviceTimeProfiler.summary).
PROFILE_SUMMARY_KEYS = {
    "steps", "ticks", "cost_captured", "overlap_ratio_mean",
    "terms_mean_s", "tick_terms_mean_s", "bandwidth_residuals", "ring",
    "flight_dumps",
}

# Prometheus series a fresh profiled+traced telemetry recorder renders from
# the ONE MetricsHub renderer — the pinned accelerate_tpu_<subsystem>_<name>
# scheme. Activity (spans, steps, SLO windows) only ADDS names; this is the
# floor that must never drift.
HUB_BASE_METRIC_NAMES = {
    "accelerate_tpu_telemetry_steps",
    "accelerate_tpu_telemetry_recompiles",
    "accelerate_tpu_telemetry_peak_hbm_bytes",
    "accelerate_tpu_telemetry_checkpoint_events",
    "accelerate_tpu_profile_steps",
    "accelerate_tpu_profile_ticks",
    "accelerate_tpu_profile_cost_captured",
    "accelerate_tpu_profile_ring_capacity",
    "accelerate_tpu_profile_ring_len",
    "accelerate_tpu_profile_flight_dumps",
    "accelerate_tpu_tracing_spans",
    "accelerate_tpu_tracing_dropped_spans",
    "accelerate_tpu_tracing_requests",
    "accelerate_tpu_tracing_open_spans",
    "accelerate_tpu_tracing_flows",
}


def test_poll_row_schema(llama):
    cfg, model = llama
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8]))
    for p in _prompts(cfg, [5, 9]):
        engine.submit(p, max_new_tokens=2)
    while engine.pending:
        engine.tick()
    rows = engine.poll()
    assert len(rows) == 2
    for row in rows:
        assert set(row) == POLL_ROW_KEYS
        assert row["status"] == "ok"


def test_serving_stats_schema(llama):
    cfg, model = llama
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8]))
    engine.run(_prompts(cfg, [5, 9]), max_new_tokens=2)
    stats = engine.stats()
    assert set(stats) == SERVING_STATS_KEYS
    assert set(stats["window"]) == WINDOW_KEYS
    assert set(stats["faults"]) == FAULTS_KEYS
    assert stats["journal"] is None  # journaling is off by default
    assert set(stats["speculation"]) == SPECULATION_KEYS
    assert stats["speculation"]["k"] == 0  # speculation is off by default
    assert stats["speculation"]["acceptance_rate"] is None
    assert stats["layout"] == {"qkv_fused": True}


def test_speculation_stats_and_hub_series(llama):
    """With speculate_k set: the speculation block populates (same pinned
    shape), poll rows carry real drafted/accepted counts, and a hub wired
    via telemetry renders the accelerate_tpu_spec_* series floor."""
    from types import SimpleNamespace

    from accelerate_tpu import MetricsHub

    cfg, model = llama
    hub = MetricsHub()
    engine = ServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=48, prefill_chunks=[4, 8],
                      speculate_k=2, speculate_ngram=8),
        telemetry=SimpleNamespace(hub=hub, record_event=lambda *a, **k: None,
                                  record_serving=lambda *a, **k: None),
    )
    for p in _prompts(cfg, [5, 9]):
        engine.submit(p, max_new_tokens=8)
    rows = []
    while engine.pending:
        engine.tick()
        rows.extend(engine.poll())
    stats = engine.stats()
    assert set(stats) == SERVING_STATS_KEYS
    spec = stats["speculation"]
    assert set(spec) == SPECULATION_KEYS
    assert spec["k"] == 2 and spec["drafted"] > 0
    assert spec["acceptance_rate"] is not None
    assert len(rows) == 2
    for row in rows:
        assert set(row) == POLL_ROW_KEYS
        assert row["drafted"] >= row["accepted"] >= 0
    assert sum(r["drafted"] for r in rows) == spec["drafted"]
    names = hub.metric_names()
    assert {
        "accelerate_tpu_spec_k",
        "accelerate_tpu_spec_drafted",
        "accelerate_tpu_spec_accepted",
        "accelerate_tpu_spec_acceptance_rate",
        "accelerate_tpu_spec_tokens_per_tick",
        "accelerate_tpu_spec_verify_time_s",
    } <= names, f"missing spec series in {sorted(names)}"


def test_journal_stats_schema(llama, tmp_path):
    cfg, model = llama
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8],
                             journal_dir=str(tmp_path / "wal")))
    engine.run(_prompts(cfg, [5, 9]), max_new_tokens=2)
    stats = engine.stats()
    assert set(stats) == SERVING_STATS_KEYS
    assert set(stats["journal"]) == JOURNAL_KEYS


def test_disagg_stats_schema(llama):
    cfg, model = llama
    engine = DisaggServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8]),
        disagg=DisaggConfig(n_prefill_lanes=2),
    )
    engine.run(_prompts(cfg, [5, 9]), max_new_tokens=2)
    stats = engine.stats()
    assert set(stats) == SERVING_STATS_KEYS | {"disagg"}
    assert set(stats["disagg"]) == DISAGG_KEYS
    assert stats["layout"] == {"qkv_fused": False}  # the decode mesh keeps the model's layout


def test_autoscale_stats_schema(llama):
    from accelerate_tpu import AutoscaleConfig, AutoscaleController

    cfg, model = llama
    engine = DisaggServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8]),
        disagg=DisaggConfig(n_prefill_lanes=1),
    )
    ctl = AutoscaleController(engine, AutoscaleConfig())
    assert set(ctl.stats()) == AUTOSCALE_KEYS


def test_fleet_stats_and_poll_row_schema(llama, tmp_path):
    """The fleet.py observability surface: stats() block keys, per-cell
    sub-block keys, poll rows = engine schema + provenance, and the
    MetricsHub ``accelerate_tpu_fleet_*`` series floor."""
    from types import SimpleNamespace

    from accelerate_tpu import FleetRouter, MetricsHub

    cfg, model = llama
    hub = MetricsHub()
    telemetry = SimpleNamespace(hub=hub, record_event=lambda *a, **k: None)
    cells = {
        f"c{i}": ServingEngine(model, ServingConfig(
            n_slots=2, max_len=32, prefill_chunks=[4, 8],
            journal_dir=str(tmp_path / f"wal{i}")))
        for i in range(2)
    }
    router = FleetRouter(cells, telemetry=telemetry)
    for i, p in enumerate(_prompts(cfg, [5, 9])):
        router.submit(p, max_new_tokens=2, client_request_id=f"r{i}")
    rows = []
    while router.pending:
        router.tick()
        rows.extend(router.poll())
    assert len(rows) == 2
    for row in rows:
        assert set(row) == FLEET_POLL_ROW_KEYS
        assert row["status"] == "ok"
    stats = router.stats()
    assert set(stats) == FLEET_STATS_KEYS
    for name, block in stats["per_cell"].items():
        assert name in cells
        assert set(block) == FLEET_PER_CELL_KEYS
    names = hub.metric_names()
    fleet_names = {n for n in names if n.startswith("accelerate_tpu_fleet_")}
    assert {
        "accelerate_tpu_fleet_cells",
        "accelerate_tpu_fleet_healthy",
        "accelerate_tpu_fleet_submitted",
        "accelerate_tpu_fleet_completed",
        "accelerate_tpu_fleet_drains",
    } <= fleet_names, f"missing fleet series in {sorted(fleet_names)}"
    router.close()


def test_summary_block_schema(tmp_path):
    from accelerate_tpu import Accelerator, TraceRecorder
    from accelerate_tpu.utils import TelemetryKwargs

    acc = Accelerator(
        project_dir=str(tmp_path),
        kwargs_handlers=[TelemetryKwargs(tracing=True, log_every=0)],
    )
    out = acc.telemetry.summary()
    keys = set(out)
    assert SUMMARY_ALWAYS <= keys
    assert keys <= SUMMARY_ALWAYS | SUMMARY_OPTIONAL, (
        f"unpinned summary blocks: {keys - SUMMARY_ALWAYS - SUMMARY_OPTIONAL}")
    assert isinstance(acc.telemetry.tracing, TraceRecorder)
    assert set(out["tracing"]) == TRACING_STATS_KEYS


def test_profile_block_schema_and_hub_metric_names(tmp_path):
    """TelemetryKwargs(profile=True): summary() grows the pinned profile
    block and the MetricsHub renders the pinned base name set (telemetry +
    profile + tracing providers)."""
    from accelerate_tpu import Accelerator, DeviceTimeProfiler
    from accelerate_tpu.utils import TelemetryKwargs

    acc = Accelerator(
        project_dir=str(tmp_path),
        kwargs_handlers=[TelemetryKwargs(tracing=True, profile=True,
                                         log_every=0)],
    )
    assert isinstance(acc.telemetry.profiler, DeviceTimeProfiler)
    out = acc.telemetry.summary()
    assert set(out["profile"]) == PROFILE_SUMMARY_KEYS
    names = acc.telemetry.hub.metric_names()
    assert HUB_BASE_METRIC_NAMES <= names, (
        f"missing pinned series: {HUB_BASE_METRIC_NAMES - names}")
    for name in names:
        assert name.startswith("accelerate_tpu_"), (
            f"series {name} violates the pinned naming scheme")
    # One renderer: the legacy exporter surface is a pure delegation.
    assert acc.telemetry.tracing.metrics_text() == acc.telemetry.hub.render()


def test_profile_off_by_default(tmp_path):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils import TelemetryKwargs

    acc = Accelerator(
        project_dir=str(tmp_path),
        kwargs_handlers=[TelemetryKwargs(log_every=0)],
    )
    assert acc.telemetry.profiler is None
    assert "profile" not in acc.telemetry.summary()


def test_sdc_block_schemas(tmp_path):
    """The two sdc.py observability blocks, pinned — and off by default:
    ``stats()["sdc"]`` is None until a DecodeCanary is attached, and
    ``summary()`` grows an ``sdc`` block only when the sentinel is armed."""
    from accelerate_tpu.sdc import DecodeCanary, SDCConfig, SDCSentinel

    class _Eng:  # the canary only touches these at construction time
        def attach_sdc_canary(self, canary):
            self.canary = canary

    canary = DecodeCanary(_Eng(), every=4)
    assert set(canary.summary()) == SDC_CANARY_KEYS
    assert canary.summary()["armed"] is False

    class _Acc:
        project_dir = str(tmp_path)

    class _Mgr:
        accelerator = _Acc()

    sentinel = SDCSentinel(_Mgr(), SDCConfig())
    assert set(sentinel.summary()) == SDC_SUMMARY_KEYS
    assert sentinel.summary()["quarantined_hosts"] == []
