"""The reduction from a profiler trace to numbers, on intervals worked by hand
and on a fifth of a second of a trace recorded on the v5e during PR 24
(``mistral_serve_steady``, 24 slots; operation texts cut to what the reduction
reads)."""

import os

import pytest

from chipbench import trace_reduce as T

TRACE = os.path.join(os.path.dirname(__file__), "data", "steady_trace.json")


def test_union_gaps_and_uncovered_length_by_hand():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert T.union_length(iv) == 30
    assert T.union_length([]) == 0
    assert T.gaps_of(iv, 0, 50) == [(20, 30), (40, 50)]
    assert T.gaps_of(iv, 8, 35) == [(20, 30)]
    # a collective from 0 to 20 with compute from 5 to 12: 13 of it are exposed
    assert T.subtract_length([(0, 20)], [(5, 12)]) == 13
    assert T.subtract_length([(0, 20)], []) == 20


@pytest.mark.parametrize("text,short,opcode", [
    ("%fusion.3 = bf16[24,14336]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[16,4096]{1,0} %p), kind=kLoop",
     "%fusion.3 fusion bf16[24,14336]", "fusion"),
    ("%while.4 = (s32[]{:T(128)}, bf16[24,1,4096]{2,0,1}) while((s32[]{:T(128)}) %tuple.64), "
     "condition=%c, body=%b", "%while.4 while (tuple)", "while"),
    ("%all-gather.7 = bf16[4096,14336]{1,0} all-gather(bf16[1024,14336]{1,0} %x), dimensions={0}",
     "%all-gather.7 all-gather bf16[4096,14336]", "all-gather"),
    ("$core.py:123 step", "$core.py:123 step", ""),
])
def test_an_operation_s_text_is_cut_to_a_line(text, short, opcode):
    assert T.short_op(text) == (short, opcode)
    assert T.short_op(T.compact_op(text)) == (short, opcode)   # what a checked-in trace keeps


def _planes(ops_by_device, modules=(), host=()):
    return {"devices": {f"/device:TPU:{i}": {"modules": list(modules), "ops": list(ops)}
                        for i, ops in enumerate(ops_by_device)},
            "host": list(host)}


def test_a_synthetic_trace_by_hand():
    # one chip, 100 ns from its first event to its last: a program of two operations, a gap of 30 ns
    # while the host ticks, then an all-gather that a fusion overlaps by half
    ops = [("%a = f32[8]{0} fusion(f32[8]{0} %x)", 0, 20),
           ("%b = f32[8]{0} copy(f32[8]{0} %a)", 20, 30),
           ("%all-gather.1 = f32[8]{0} all-gather(f32[2]{0} %b)", 60, 100),
           ("%c = f32[8]{0} fusion(f32[8]{0} %x)", 80, 100),
           ("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%body", 0, 30)]
    r = T.reduce_planes(_planes([ops], modules=[("jit_step(123)", 0, 30), ("jit_step(123)", 60, 100)],
                                host=[("chipbench.tick", 25, 70)]))
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(70e-9)
    assert r["collective_exposed_s"] == pytest.approx(20e-9)
    assert r["programs"] == {"jit_step": {"count": 2.0, "total_s": pytest.approx(70e-9)}}
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert names[0] == "%all-gather.1 all-gather f32[8]" and not any("while" in n for n in names)
    assert r["breakdown"]["idle_gaps"] == [["chipbench.tick", pytest.approx(30e-9)]]


def test_device_numbers_are_means_over_the_chips():
    busy = [("%a = f32[8]{0} fusion(f32[8]{0} %x)", 0, 50)]
    half = [("%a = f32[8]{0} fusion(f32[8]{0} %x)", 0, 25)]
    r = T.reduce_planes(_planes([busy, half], modules=[("jit_step(1)", 0, 50)]))
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(37.5e-9)
    assert r["programs"]["jit_step"]["count"] == 1.0   # every chip runs the program


def test_a_trace_in_which_nothing_ran_on_the_device_is_an_error():
    with pytest.raises(ValueError, match="nothing ran"):
        T.reduce_planes(_planes([[]]))


def test_the_recorded_trace_reads_as_it_did_on_the_chip():
    r = T.reduce_planes(T.load_trimmed(TRACE))
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.2, rel=1e-3)
    idle = r["window_s"] - r["busy_s"]   # every idle stretch is under one of the named gaps
    assert sum(t for _, t in r["breakdown"]["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    # two decode steps of 78.8 ms each and two prefill chunks inside the fifth of a second
    assert r["programs"]["jit_decode"]["count"] == 2.0
    assert r["programs"]["jit_decode"]["total_s"] / 2 == pytest.approx(0.0788, rel=0.01)
    assert r["programs"]["jit_prefill"]["count"] == 2.0
    assert 0.18 < r["busy_s"] < r["window_s"]
    # whole-buffer GQA: the keys and values of all 2048 rows are repeated four times
    assert r["breakdown"]["device_ops"][0][0].startswith("%broadcast_in_dim")
    assert r["breakdown"]["device_ops"][0][0].endswith("bf16[24,2048,8,4,128]")
    assert r["breakdown"]["idle_gaps"][0][0] == "chipbench.tick"
    assert len(r["breakdown"]["device_ops"]) <= 10 and len(r["breakdown"]["idle_gaps"]) <= 10


def test_a_program_is_named_without_its_fingerprint():
    assert T.program_name("jit_decode(1270189828952432798)") == "jit_decode"
    assert T.program_name("jit_prefill") == "jit_prefill"


def _metric_context(trace):
    from chipbench import spec
    from chipbench.metrics._context import MetricContext
    from chipbench.stats import RequestRecord

    done = RequestRecord(index=0, phase="window", prompt_len=400, budget=100, due_s=1.0,
                         submit_s=1.0, status="ok", new_tokens=100, first_token_s=2.0,
                         done_s=12.0)
    result = {"records": [done], "window_s": 40.0, "summary": {},
              "counters": {"mean_occupancy": 12.0, "n_slots": 24, "decode_steps": 2}}
    return MetricContext(cell=spec.load_cell("mistral_serve_steady"),
                         peaks=spec.load_peaks("TPU v5 lite"), result=result, trace=trace)


@pytest.mark.parametrize("name,low,high", [
    ("decode_step_ms", 78.0, 79.6),            # as the trace's own jit_decode events
    ("prefill_chunk_ms", 1.0, 40.0),
    ("prefill_share_pct", 1.0, 30.0),
    ("device_idle_pct.serve", 0.1, 10.0),
    # 7.25 GB of matmul weights + 12 slots x 450 live rows x 64 KiB a row = 7.60 GB: 9.28 ms
    # at 819 GB/s, over a 78.8 ms step
    ("decode_hbm_roofline", 11.6, 12.0),
    ("slot_occupancy_pct", 50.0, 50.0),
])
def test_each_reader_on_the_recorded_trace(name, low, high):
    from chipbench import spec

    value = spec.load_reader(name)(_metric_context(T.reduce_planes(T.load_trimmed(TRACE))))
    assert low <= value <= high, value


@pytest.mark.parametrize("name", ["decode_step_ms", "prefill_chunk_ms", "prefill_share_pct",
                                  "device_idle_pct.serve", "decode_hbm_roofline"])
def test_a_reader_with_no_trace_to_read_says_nothing(name):
    from chipbench import spec

    assert spec.load_reader(name)(_metric_context(None)) is None


def test_a_busy_slice_without_a_prompt_chunk_reads_a_prefill_share_of_nought():
    from chipbench import spec

    trace = T.reduce_planes(T.load_trimmed(TRACE))
    trace["programs"].pop("jit_prefill")
    ctx = _metric_context(trace)
    assert spec.load_reader("prefill_share_pct")(ctx) == 0.0
    assert spec.load_reader("prefill_chunk_ms")(ctx) is None   # a mean over no chunks is none
