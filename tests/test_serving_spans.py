"""The serving tick measured from inside (serving.py ``_phase``): the phases
add up to the tick, TTFT's three terms add up to ``ttft_s``, every fetched
token has its time, the recorder's phase spans hang under their tick, and the
compile watch sees a prefill rung the warm-up never built. CPU, tiny model:
what is checked is the accounting, never a speed."""

import json

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
    TraceRecorder,
)
from accelerate_tpu.generation import _llama_forward_cached
from accelerate_tpu.serving import TICK, TICK_PHASES, TTFT_TERMS
from accelerate_tpu.utils import set_seed


@pytest.fixture(scope="module")
def llama():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8),
                                              dtype=np.int32)
    return cfg, Model.from_flax(module, jax.random.key(0), probe)


def _prompts(cfg, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32)
            for n in lengths]


def _two_programs(cfg, params, ids, cache, return_all=False, attn_bound=None):
    """The Llama plan without ``chunk``: an engine given it runs a prompt chunk
    in a program of its own, and fetches a final chunk's token alone."""
    return _llama_forward_cached(cfg, params, ids, cache, return_all=return_all,
                                 attn_bound=attn_bound)


def _engine(model, cls=ServingEngine, **kw):
    config = dict(n_slots=2, max_len=64, prefill_chunks=[4, 8])
    config.update(kw.pop("config", {}))
    return cls(model, ServingConfig(**config), **kw)


def _drain(engine):
    rows = {}
    while engine.pending:
        engine.tick()
        rows.update((r["id"], r) for r in engine.poll())
    return rows


def _assert_phases_add_up(block, ticks):
    assert set(block) == {"ticks", "wall_s", "phases_s", "device_wait_s", "host_s"}
    assert tuple(block["phases_s"]) == TICK_PHASES
    assert block["ticks"] == ticks and block["wall_s"] > 0
    assert sum(block["phases_s"].values()) == pytest.approx(block["wall_s"], rel=1e-9)
    assert block["device_wait_s"] == pytest.approx(
        block["phases_s"]["serving.first_token_fetch"]
        + block["phases_s"]["serving.decode_fetch"], rel=1e-12)
    assert block["host_s"] + block["device_wait_s"] == pytest.approx(
        block["wall_s"], rel=1e-9)


@pytest.mark.parametrize("cls,kw", [
    (ServingEngine, {}),
    (ServingEngine, {"forward_cached": _two_programs}),
    (DisaggServingEngine, {"disagg": DisaggConfig(n_prefill_lanes=2)}),
], ids=["colocated", "colocated_two_programs", "disagg"])
def test_the_phases_of_a_tick_add_up_to_its_wall_time(llama, cls, kw):
    cfg, model = llama
    engine = _engine(model, cls, **kw)
    for p in _prompts(cfg, [5, 19, 9]):
        engine.submit(p, max_new_tokens=4)
    rows = _drain(engine)
    assert all(r["status"] == "ok" for r in rows.values())
    stats = engine.stats()
    _assert_phases_add_up(stats["tick_phases"], stats["ticks"])
    # a run with prefill and decode spends time in every phase; where the
    # chunks ride the decode step, their first tokens come in its one fetch
    phases = stats["tick_phases"]["phases_s"]
    fused = stats["prefill_chunks_fused"]
    assert fused == (stats["prefill_chunks"] if cls is ServingEngine and not kw else 0)
    assert (phases.pop("serving.first_token_fetch") == 0) == bool(fused)
    assert all(v > 0 for v in phases.values())


def test_ttft_terms_add_up_per_row_and_have_their_tails(llama):
    cfg, model = llama
    engine = _engine(model)
    for p in _prompts(cfg, [5, 19, 9, 12]):   # four requests on two slots: some queue
        engine.submit(p, max_new_tokens=3)
    rows = _drain(engine)
    for row in rows.values():
        terms = [row[k] for k in TTFT_TERMS]
        assert all(t >= 0 for t in terms)
        assert sum(terms) == pytest.approx(row["ttft_s"], rel=1e-9)
    block = engine.stats()["ttft_terms"]
    assert block["n"] == 4
    assert set(block) == {"n"} | {f"{k[:-2]}_p{q}_s" for k in TTFT_TERMS for q in (50, 95)}
    waits = sorted(r["queue_wait_s"] for r in rows.values())
    assert waits[0] <= block["queue_wait_p50_s"] <= block["queue_wait_p95_s"] <= waits[-1]
    # the third and fourth waited for a slot, the first two did not
    assert min(rows[2]["queue_wait_s"], rows[3]["queue_wait_s"]) > 10 * max(
        rows[0]["queue_wait_s"], rows[1]["queue_wait_s"])
    # the means that were there are means of the same terms
    stats = engine.stats()
    assert stats["ttft_queue_wait_mean_s"] == pytest.approx(np.mean(waits), rel=1e-9)
    assert stats["ttft_prefill_mean_s"] == pytest.approx(np.mean(
        [r["prefill_blocked_s"] + r["prefill_own_s"] for r in rows.values()]), rel=1e-9)


def test_a_prompt_behind_another_s_chunks_is_blocked_for_as_long_as_they_take(llama):
    """Two long prompts, both granted a slot in the first tick: the engine
    advances the head of the line one chunk a tick, so the second's first
    dispatch waits for all the first's chunks."""
    cfg, model = llama
    engine = _engine(model, config={"max_len": 96})
    a, b = (engine.submit(p, max_new_tokens=2) for p in _prompts(cfg, [40, 40]))
    rows = _drain(engine)
    first, second = rows[a], rows[b]
    assert first["queue_wait_s"] < 0.5 * first["prefill_own_s"]
    assert second["queue_wait_s"] < 0.5 * first["prefill_own_s"]
    # the first is dispatched within the tick that granted it
    assert first["prefill_blocked_s"] < 0.25 * first["prefill_own_s"]
    # the second waits out the first's five chunks of 8: all but the first's
    # last tick, which ends after the first token
    assert second["prefill_blocked_s"] > 0.5 * first["prefill_own_s"]
    assert second["prefill_blocked_s"] == pytest.approx(
        second["ttft_s"] - second["queue_wait_s"] - second["prefill_own_s"], rel=1e-9)


@pytest.mark.parametrize("speculate_k", [0, 2])
def test_every_new_token_has_its_time(llama, speculate_k):
    cfg, model = llama
    engine = _engine(model, config={"speculate_k": speculate_k})
    for p in _prompts(cfg, [5, 9]):
        engine.submit(p, max_new_tokens=8)
    rows = _drain(engine)
    steps = engine.stats()["decode_steps"]
    for row in rows.values():
        times = row["token_times_s"]
        assert len(times) == row["new_tokens"] == 8
        assert times[0] == row["ttft_s"]
        assert all(b >= a for a, b in zip(times, times[1:]))
        if not speculate_k:   # one token a fetch, each fetch later than the last
            assert len(set(times)) == len(times)
    gap = engine.stats()["token_gap"]
    # every token but a request's first waited for the one before it
    assert gap["n"] == sum(r["new_tokens"] - 1 for r in rows.values())
    assert 0 <= gap["p50_s"] <= gap["p95_s"] <= gap["max_s"]
    longest = max(b - a for r in rows.values()
                  for a, b in zip(r["token_times_s"], r["token_times_s"][1:]))
    assert gap["max_s"] == pytest.approx(longest, rel=1e-6)
    if speculate_k and steps < 14:   # a draft was accepted: tokens of one fetch share a time
        assert gap["p50_s"] == 0.0 or any(
            len(set(r["token_times_s"])) < 8 for r in rows.values())


def test_a_row_without_a_first_token_has_no_terms_and_no_token_times(llama):
    cfg, model = llama
    engine = _engine(model, config={"max_queue_depth": 1, "overload_policy": "reject"})
    ids = [engine.submit(p, max_new_tokens=2) for p in _prompts(cfg, [5, 6, 7, 8])]
    rows = _drain(engine)
    shed = [rows[i] for i in ids if rows[i]["status"] == "shed"]
    assert shed
    for row in shed:
        assert [row[k] for k in TTFT_TERMS] == [None, None, None]
        assert row["token_times_s"] == [] and row["ttft_s"] is None


def test_reset_metrics_zeroes_the_three_blocks(llama):
    cfg, model = llama
    engine = _engine(model)
    engine.run(_prompts(cfg, [5, 9]), max_new_tokens=3)
    before = engine.stats()
    assert before["tick_phases"]["wall_s"] > 0 and before["ttft_terms"]["n"] == 2
    assert before["token_gap"]["n"] == 4
    engine.reset_metrics()
    after = engine.stats()
    assert after["tick_phases"] == {
        "ticks": 0, "wall_s": 0.0, "phases_s": dict.fromkeys(TICK_PHASES, 0.0),
        "device_wait_s": 0.0, "host_s": 0.0}
    assert after["ttft_terms"]["n"] == 0 and after["token_gap"]["n"] == 0
    assert all(v is None for k, v in after["ttft_terms"].items() if k != "n")
    assert all(v is None for k, v in after["token_gap"].items() if k != "n")
    assert after["ttft_queue_wait_mean_s"] is None


def _traced_run(llama, **kw):
    cfg, model = llama
    tr = TraceRecorder()
    engine = _engine(model, tracing=tr, **kw)
    rows = engine.run(_prompts(cfg, [6, 19, 9], seed=5), max_new_tokens=3)
    return tr, engine, rows


def test_with_a_recorder_every_phase_span_hangs_under_its_tick(llama):
    tr, engine, _ = _traced_run(llama)
    spans = {s.seq: s for s in tr.spans()}
    ticks = [s for s in spans.values() if s.name == TICK]
    phases = [s for s in spans.values() if s.kind == "tick_phase" and s.name != TICK]
    assert len(ticks) == engine.stats()["ticks"] and all(t.parent is None for t in ticks)
    # every chunk rode a decode step: no final chunk's token was fetched alone
    assert {s.name for s in phases} == set(TICK_PHASES) - {"serving.first_token_fetch"}
    for s in phases:
        tick = spans[s.parent]
        assert tick.name == TICK
        assert tick.start_tick == s.start_tick == s.end_tick == tick.end_tick
        assert tick.t0 <= s.t0 <= s.t1 <= tick.t1
    # a prefill phase says whose chunk it was
    prefill = [s for s in phases if s.name == "serving.prefill"]
    assert {s.attrs["request_id"] for s in prefill} == {0, 1, 2}
    assert sum(s.attrs["final"] for s in prefill) == 3
    assert sum(s.attrs["size"] for s in prefill) == sum(
        s.attrs["size"] for s in spans.values() if s.kind == "prefill_chunk")
    # the spans are read off the counters' clock: the ticks' seconds are the
    # same, and a phase's counter also holds the host time between it and the
    # phase before (a span, like the profiler's annotation, does not)
    block = engine.stats()["tick_phases"]
    assert sum(t.t1 - t.t0 for t in ticks) == pytest.approx(block["wall_s"], rel=1e-9)
    fetch = sum(s.t1 - s.t0 for s in phases if s.name == "serving.decode_fetch")
    assert 0 < fetch <= block["phases_s"]["serving.decode_fetch"]


def test_a_request_s_spans_hang_under_its_queued_span(llama):
    tr, engine, _ = _traced_run(llama)
    for rid in tr.request_ids():
        spans = tr.spans(rid)
        queued = [s for s in spans if s.kind == "queued"]
        assert len(queued) == 1 and queued[0].parent is None
        rest = [s for s in spans if s.kind != "queued"]
        assert {s.kind for s in rest} == {"prefill_chunk", "finish"}
        assert all(s.parent == queued[0].seq for s in rest)
    # what a tick records for no one request is the tick's child
    ticks = {s.seq for s in tr.spans() if s.name == TICK}
    assert all(s.parent in ticks for s in tr.spans() if s.kind == "decode_tick")
    # both projections carry the parent
    assert all("parent" in v for v in tr.tick_trace())
    events = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert sum("parent" in e["args"] for e in events) == sum(
        s.parent is not None for s in tr.spans())


def test_the_recorder_is_given_the_engine_s_own_instants(llama):
    cfg, model = llama
    tr = TraceRecorder()
    engine = _engine(model, tracing=tr)
    ids = [engine.submit(p, max_new_tokens=3) for p in _prompts(cfg, [5, 19, 9, 12])]
    rows = _drain(engine)
    for rid in ids:
        ex = tr.explain(rid)
        assert ex["terms"]["queue_wait_s"] == rows[rid]["queue_wait_s"]
        assert ex["ttft_s"] == rows[rid]["ttft_s"]


def test_the_tick_domain_trace_with_phase_spans_still_replays_bit_identically(llama):
    a, _, rows_a = _traced_run(llama)
    b, _, rows_b = _traced_run(llama)
    ja = json.dumps(a.tick_trace(), sort_keys=True)
    assert ja == json.dumps(b.tick_trace(), sort_keys=True)
    assert '"serving.decode_fetch"' in ja
    # and greedy output does not depend on who is watching
    cfg, model = llama
    plain = _engine(model).run(_prompts(cfg, [6, 19, 9], seed=5), max_new_tokens=3)
    for x, y, z in zip(rows_a, rows_b, plain):
        assert np.array_equal(x, y) and np.array_equal(x, z)


def test_a_prefill_rung_the_warm_up_never_saw_counts_once(llama, caplog):
    from accelerate_tpu.state import PartialState

    PartialState()   # the repo's logger says nothing without it
    cfg, model = llama
    engine = _engine(model)
    engine.warmup()
    stats = engine.stats()
    # the warm-up's chunks rode the decode step: that program holds the rungs
    assert stats["prefill_steady_recompiles"] == 0 and stats["decode_chunk_executables"] == 2
    assert stats["prefill_executables"] == 0
    engine.run(_prompts(cfg, [5, 19]), max_new_tokens=2)
    assert engine.stats()["prefill_steady_recompiles"] == 0
    engine.ladder = [4, 8, 16]   # a rung that warmup() never built
    with caplog.at_level("WARNING"):
        engine.run(_prompts(cfg, [33, 33]), max_new_tokens=2)
    stats = engine.stats()
    assert stats["prefill_steady_recompiles"] == 1 and stats["decode_chunk_executables"] == 3
    assert stats["steady_recompiles"] == 0    # decode's watch keeps its meaning
    assert sum("prefill compiled mid-flight" in r.getMessage() for r in caplog.records) == 1
    # an engine that was never warmed compiles on demand, and nothing is counted
    cold = _engine(model)
    cold.run(_prompts(cfg, [5, 19]), max_new_tokens=2)
    assert cold.stats()["prefill_steady_recompiles"] == 0
