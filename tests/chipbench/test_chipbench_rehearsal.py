"""The command end to end, each run a process of its own: off the chip it
refuses; at the rehearsal's tiny size on the CPU the sound program comes out
correct and a program that alters its tokens does not; and a cell, a configuration and a metric
dropped into a copy of the directories run with no edit to a file that is there.
Nothing here is a measurement."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ["mistral_serve_steady", "mixtral_serve_decode"]


def _run(args, cwd=spec.ROOT, script=("-m", "chipbench.run"), path=spec.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path, BENCH_RUN="7")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, *script, *args], cwd=cwd, env=env, text=True,
                       capture_output=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


def _rehearse(cell, seed, *extra, trace=0, **kw):
    rc, line, err = _run(["--workload", cell, "--seed", str(seed), "--seconds", "3",
                          "--trace", str(trace), "--rehearse", *extra], **kw)
    assert rc == 0 and line is not None, err[-2000:]
    assert line["rehearsal"] is True and line["metrics"] == {}   # counts only, no metric
    assert all("platform=cpu rehearsal" in ln for ln in err.splitlines() if ln.startswith("["))
    return line, err


def test_off_the_chip_the_command_exits_nonzero_and_prints_no_result():
    rc, line, err = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0 and line is None
    assert "'cpu'" in err and "Nothing is measured on another backend" in err


def test_with_only_the_benchmark_s_own_files_it_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    rc, line, _ = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, path=str(tmp_path))
    assert rc != 0 and line is None


def test_a_name_the_manifest_lacks_is_an_error_before_jax_starts():
    rc, line, err = _run(["--workload", "no_such_cell", "--seed", "1", "--seconds", "1",
                          "--trace", "0"])
    assert rc != 0 and line is None and "no workload 'no_such_cell'" in err


def test_a_sound_run_is_correct_and_prints_each_number_beside_its_limit_last():
    """The open loop drains every request, so the sample does not hang on the
    machine's speed. (The control at this size: ``test_chipbench_control.py``.)"""
    line, err = _rehearse(CELLS[0], 4)
    assert line["correct"] is True, err[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "compared"   # each number beside its limit, last in the line
    assert set(line["compared"]) == {"gap_max", "gap_mean", "bad_rows", "failed",
                                     "compiles_in_window"}
    last = [ln for ln in err.splitlines() if ln.startswith("[")][-6:]
    assert all("compared " in ln or "correct:" in ln for ln in last), last


@pytest.mark.parametrize("cell", CELLS)
def test_a_token_altered_where_it_is_produced_comes_out_as_not_correct(cell):
    line, _ = _rehearse(cell, 4, script=(os.path.join(HERE, "faulty_run.py"), "altered_token"))
    assert line["correct"] is False
    c = line["compared"]
    assert c["gap_mean"]["value"] > 10 * c["gap_mean"]["limit"]
    assert c["bad_rows"]["value"] == 0 and c["failed"]["value"] == 0   # only the gaps tell


def test_a_cell_a_configuration_and_a_metric_dropped_in_run_with_no_edit(tmp_path):
    """What a later PR does: new files and new entries of BENCHMARK.json, no
    change to a file that is there."""
    bench = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    manifest = spec.load_manifest()
    config = json.loads((bench / "configs" / "mistral-7b-v0.3-l16.json").read_text())
    config["num_hidden_layers"] = 8
    (bench / "configs" / "mistral-7b-v0.3-l8.json").write_text(json.dumps(config))
    cell = json.loads((bench / "workloads" / "mistral_serve_steady.json").read_text())
    cell.update(config="mistral-7b-v0.3-l8", traffic="bursty_chat")
    cell["traffic_params"]["arrivals"].update(cv=3.0)
    (bench / "workloads" / "mistral_serve_burst.json").write_text(json.dumps(cell))
    (bench / "metrics" / "ticks_per_request.py").write_text(
        "def read(ctx):\n"
        "    c = ctx.result['counters']\n"
        "    return c['ticks'] / max(1, c['requests_completed'])\n")
    manifest["configs"].append(dict(manifest["configs"][0], name="mistral-7b-v0.3-l8",
                                    file="chipbench/configs/mistral-7b-v0.3-l8.json"))
    manifest["workloads"].append({"name": "mistral_serve_burst", "config": "mistral-7b-v0.3-l8",
                                  "traffic": "bursty_chat", "chips": 1, "why": "bursts"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "mistral_serve_steady" in m.get("workloads", []):
            m["workloads"].append("mistral_serve_burst")
    manifest["per_layer"].append({"name": "ticks_per_request", "unit": "ticks", "better": "lower",
                                  "source": "program_counter", "layer": "scheduler",
                                  "moves": "ttft_p95_ms", "workloads": ["mistral_serve_burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    path = os.pathsep.join([str(tmp_path), spec.ROOT])   # the copy first; the program from the repo
    line, err = _rehearse("mistral_serve_burst", 5, trace=1, cwd=tmp_path, path=path)
    assert line["correct"] is True and line["attempted"] > 0, err[-2000:]
    # the new reader was found by its name and read the engine's counters
    assert {"ticks_per_request", "slot_occupancy_pct"} <= set(line["readers_with_a_value"])
    assert "configuration mistral-7b-v0.3-l8" in err
    assert all(p.read_bytes() == data for p, data in before.items())
