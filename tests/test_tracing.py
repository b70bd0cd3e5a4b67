"""Request-scoped distributed tracing (tracing.py): the explain() telescoping
identity (terms sum to measured TTFT — the pinned acceptance bar), Chrome
trace export validity with cross-lane flow events, seeded tick-domain
determinism under chaos, Prometheus text parity, chaos span annotation, the
TelemetryKwargs wiring, and the off-by-default zero-cost contract. All
CPU-only, tier-1 fast."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import (
    DisaggConfig,
    DisaggServingEngine,
    FaultInjector,
    Model,
    ServingConfig,
    ServingEngine,
    TraceConfig,
    TraceRecorder,
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
# TraceConfig plumbing
# ---------------------------------------------------------------------------


def test_trace_config_from_value():
    assert TraceConfig.from_value(None) is None
    assert TraceConfig.from_value(False) is None
    cfg = TraceConfig.from_value(True)
    assert cfg is not None and cfg.enabled
    cfg = TraceConfig.from_value({"max_spans": 17, "wall_clock": False})
    assert cfg.max_spans == 17 and cfg.wall_clock is False
    same = TraceConfig(max_spans=5)
    assert TraceConfig.from_value(same) is same
    with pytest.raises(TypeError):
        TraceConfig.from_value("yes")


def test_tracing_off_by_default(llama):
    cfg, model = llama
    engine = ServingEngine(
        model, ServingConfig(n_slots=2, max_len=32, prefill_chunks=[4, 8]))
    assert engine.tracing is None
    outs = engine.run(_prompts(cfg, [5, 9]), max_new_tokens=3)
    assert len(outs) == 2  # hooks are inert None-checks when off


# ---------------------------------------------------------------------------
# Consumer 1: explain() — the telescoping identity (pinned acceptance bar)
# ---------------------------------------------------------------------------


def test_explain_terms_sum_to_measured_ttft(llama):
    cfg, model = llama
    tr = TraceRecorder(TraceConfig())
    engine = ServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8]),
        tracing=tr,
    )
    prompts = _prompts(cfg, [3, 7, 12, 20, 5], seed=7)
    engine.run(prompts, max_new_tokens=4)
    assert len(tr.request_ids()) == len(prompts)
    for rid in tr.request_ids():
        rep = tr.explain(rid)
        assert rep["status"] == "ok"
        terms = rep["terms"]
        assert set(terms) == {"queue_wait_s", "prefill_s", "handoff_s",
                              "backoff_s", "stall_s"}
        # The pinned identity: disjoint sub-intervals telescope to the
        # measured TTFT exactly (float-add tolerance only).
        assert sum(terms.values()) == pytest.approx(rep["ttft_s"],
                                                    abs=1e-9, rel=1e-9)
        assert rep["dominant"] in terms
        assert terms[rep["dominant"]] == max(terms.values())
        # Colocated engine: no handoff, no chaos backoff.
        assert terms["handoff_s"] == 0.0 and terms["backoff_s"] == 0.0
        assert rep["total_s"] >= rep["ttft_s"]
        assert rep["decode_s"] == pytest.approx(
            rep["total_s"] - rep["ttft_s"], abs=1e-9)
        assert rep["n_spans"] > 0 and rep["decode_ticks"] > 0


def test_explain_untraced_request_raises():
    tr = TraceRecorder(TraceConfig())
    with pytest.raises(KeyError):
        tr.explain(12345)


def test_explain_disagg_includes_handoff_terms(llama):
    cfg, model = llama
    tr = TraceRecorder(TraceConfig())
    engine = DisaggServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8]),
        disagg=DisaggConfig(n_prefill_lanes=2),
        tracing=tr,
    )
    engine.run(_prompts(cfg, [6, 11, 17], seed=5), max_new_tokens=3)
    saw_handoff = False
    for rid in tr.request_ids():
        rep = tr.explain(rid)
        terms = rep["terms"]
        assert sum(terms.values()) == pytest.approx(rep["ttft_s"],
                                                    abs=1e-9, rel=1e-9)
        assert rep["lanes"], "disagg request must record its prefill lane"
        saw_handoff = saw_handoff or terms["handoff_s"] > 0
    assert saw_handoff  # final flushes are measured walls, not zeros


# ---------------------------------------------------------------------------
# Consumer 2: Chrome trace export (Perfetto)
# ---------------------------------------------------------------------------


def test_chrome_trace_exports_valid_json_with_flows(llama, tmp_path):
    cfg, model = llama
    tr = TraceRecorder(TraceConfig())
    engine = DisaggServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8]),
        disagg=DisaggConfig(n_prefill_lanes=2),
        tracing=tr,
    )
    engine.run(_prompts(cfg, [6, 11, 17, 9], seed=5), max_new_tokens=3)
    path = str(tmp_path / "trace.json")
    tr.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    phases = {e["ph"] for e in events}
    assert {"M", "X"} <= phases
    # Process metadata names every subsystem that emitted spans.
    names = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"serving", "prefill", "handoff", "decode"} <= names
    # Flow events stitch the KV handoff from prefill lane to decode slot:
    # each "s" (on the handoff span) pairs with an "f" (on the kv_insert
    # span) through a shared flow id, across different tids.
    starts = {e["id"]: e for e in events if e["ph"] == "s"}
    finishes = {e["id"]: e for e in events if e["ph"] == "f"}
    assert starts and finishes
    paired = set(starts) & set(finishes)
    assert paired, "at least one handoff must stitch end-to-end"
    for fid in paired:
        assert starts[fid]["ts"] <= finishes[fid]["ts"]
    # X events carry non-negative microsecond walls.
    for e in events:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0


# ---------------------------------------------------------------------------
# Tick-domain determinism under seeded chaos
# ---------------------------------------------------------------------------


def _chaos_run(llama, seed):
    cfg, model = llama
    tr = TraceRecorder(TraceConfig())
    chaos = FaultInjector(
        seed=seed,
        rates={"handoff_device_put": {"transfer_error": 0.25, "delay": 0.2}},
    )
    engine = DisaggServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8]),
        disagg=DisaggConfig(n_prefill_lanes=2),
        chaos=chaos,
        tracing=tr,
    )
    engine.run(_prompts(cfg, [6, 11, 17, 9, 5], seed=5), max_new_tokens=3)
    return tr


def test_tick_trace_bit_identical_across_seeded_runs(llama):
    a = _chaos_run(llama, seed=1234)
    b = _chaos_run(llama, seed=1234)
    ja = json.dumps(a.tick_trace(), sort_keys=True)
    jb = json.dumps(b.tick_trace(), sort_keys=True)
    assert ja == jb  # the deterministic tick-domain projection replays
    c = _chaos_run(llama, seed=99)
    assert json.dumps(c.tick_trace(), sort_keys=True) != ja


def test_chaos_injections_annotate_spans(llama):
    tr = _chaos_run(llama, seed=1234)
    chaos_spans = [s for s in tr.spans() if s.subsystem == "chaos"]
    assert chaos_spans, "seeded rates must inject at least one fault"
    for s in chaos_spans:
        assert s.attrs.get("injected") is True
        assert "point" in s.attrs and "kind" in s.attrs
        assert s.attrs.get("seed") == 1234


# ---------------------------------------------------------------------------
# Consumer 3: Prometheus text exposition
# ---------------------------------------------------------------------------


def test_metrics_text_matches_stats(llama):
    cfg, model = llama
    tr = TraceRecorder(TraceConfig())
    engine = ServingEngine(
        model,
        ServingConfig(n_slots=2, max_len=64, prefill_chunks=[4, 8]),
        tracing=tr,
    )
    engine.run(_prompts(cfg, [5, 9], seed=2), max_new_tokens=3)
    text = tr.metrics_text()
    stats = engine.stats()
    lines = dict(
        line.rsplit(" ", 1) for line in text.splitlines()
        if line and not line.startswith("#") and "{" not in line
    )
    assert float(lines["accelerate_tpu_serving_requests_completed"]) == (
        stats["requests_completed"])
    assert float(lines["accelerate_tpu_serving_tokens_out"]) == (
        stats["tokens_out"])
    # window_stats parity rides through the nested "window" block.
    assert float(lines["accelerate_tpu_serving_window_requests"]) == (
        stats["window"]["requests"])
    assert "accelerate_tpu_tracing_spans_total" in text
    assert float(lines["accelerate_tpu_tracing_requests"]) == 2
    assert "accelerate_tpu_trace_" not in text   # the pre-hub names are gone


# ---------------------------------------------------------------------------
# Telemetry wiring (TelemetryKwargs(tracing=...)) + bounded buffers
# ---------------------------------------------------------------------------


def test_telemetry_kwargs_builds_recorder(tmp_path):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils import TelemetryKwargs

    acc = Accelerator(
        project_dir=str(tmp_path),
        kwargs_handlers=[TelemetryKwargs(tracing=True, log_every=0)],
    )
    assert isinstance(acc.telemetry.tracing, TraceRecorder)
    assert acc.telemetry.summary()["tracing"]["spans"] == 0


def test_telemetry_kwargs_tracing_off(tmp_path):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils import TelemetryKwargs

    acc = Accelerator(
        project_dir=str(tmp_path),
        kwargs_handlers=[TelemetryKwargs(log_every=0)],
    )
    assert acc.telemetry.tracing is None
    assert "tracing" not in acc.telemetry.summary()


def test_span_buffer_bounded():
    tr = TraceRecorder(TraceConfig(max_spans=10))
    for i in range(25):
        tr.instant("serving", "tickle", i)
    assert tr.stats()["spans"] == 10
    assert tr.stats()["dropped_spans"] == 15
