"""The four readers of the engine's own phase spans and counters
(``tick_host_ms``, ``queue_wait_p95_ms``, ``prefill_blocked_p95_ms``,
``token_gap_p95_ms``): each on a hand-written ``counters`` dict, silent where
the program has no such block (the parent commit's has none), and found by
name in a rehearsal of the cells that list them. Nothing here is a measurement."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import spec
from chipbench.metrics._context import MetricContext

COUNTERS = {
    "tick_phases": {"ticks": 400, "wall_s": 36.0, "device_wait_s": 35.2, "host_s": 0.8,
                    "phases_s": {}},
    "ttft_terms": {"n": 52, "queue_wait_p50_s": 0.0001, "queue_wait_p95_s": 0.09,
                   "prefill_blocked_p50_s": 0.05, "prefill_blocked_p95_s": 0.7,
                   "prefill_own_p50_s": 0.2, "prefill_own_p95_s": 0.6},
    "token_gap": {"n": 5000, "p50_s": 0.081, "p95_s": 0.0995, "max_s": 0.17},
}
EXPECTED = {"tick_host_ms": 2.0, "queue_wait_p95_ms": 90.0,
            "prefill_blocked_p95_ms": 700.0, "token_gap_p95_ms": 99.5}
EMPTY = {"tick_phases": {"ticks": 0, "wall_s": 0.0, "device_wait_s": 0.0, "host_s": 0.0,
                         "phases_s": {}},
         "ttft_terms": {"n": 0, "queue_wait_p95_s": None, "prefill_blocked_p95_s": None},
         "token_gap": {"n": 0, "p50_s": None, "p95_s": None, "max_s": None}}


def _read(name, counters):
    ctx = MetricContext(cell=None, peaks={}, result={"counters": counters}, trace=None)
    return spec.load_reader(name)(ctx)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_on_a_hand_written_counters_dict(name):
    assert _read(name, COUNTERS) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("counters", [{}, {"ticks": 12, "decode_steps": 12}, EMPTY],
                         ids=["no_counters", "a_program_without_the_blocks", "nothing_counted"])
def test_a_reader_finds_nothing_and_says_nothing(name, counters):
    assert _read(name, counters) is None


def test_the_manifest_lists_each_reader_in_the_cells_that_report_what_it_moves():
    per_layer = {m["name"]: m for m in spec.load_manifest()["per_layer"]}
    both = ["mistral_serve_steady", "mixtral_serve_decode"]
    assert per_layer["tick_host_ms"]["workloads"] == both
    assert per_layer["token_gap_p95_ms"]["workloads"] == both
    for name in ("queue_wait_p95_ms", "prefill_blocked_p95_ms"):   # TTFT is steady's alone
        assert per_layer[name]["workloads"] == ["mistral_serve_steady"]
        assert per_layer[name]["moves"] == "ttft_p95_ms"
    assert all(per_layer[n]["layer"] == "scheduler" for n in EXPECTED)


@pytest.mark.parametrize("cell", ["mistral_serve_steady", "mixtral_serve_decode"])
def test_a_cell_s_rehearsal_names_the_new_readers_among_those_with_a_value(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed",
                        "2500000007", "--seconds", "3", "--trace", "1", "--rehearse"],
                       cwd=spec.ROOT, env=env, text=True, capture_output=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    listed = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert listed & set(EXPECTED) <= set(line["readers_with_a_value"])
    assert len(listed & set(EXPECTED)) == (4 if cell == "mistral_serve_steady" else 2)
