"""BENCHMARK.json is consistent with itself and with the files it names."""

import json
import os
import re

import pytest

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"((?<!vocab)_size$|_dim$|_rank$|expand|experts_per_tok|window)")


@pytest.fixture(scope="module")
def manifest():
    return spec.load_manifest()


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        if m["name"].endswith("_roofline") or "mfu" in re.split(r"[_.]", m["name"]):
            assert m["unit"] == "%"


def test_configurations_are_files_with_every_reduced_key_and_no_cut_width(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and c["source"].startswith("https://")
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert cfg[key] != cfg["published"][key]


def test_cells_metrics_and_files_agree(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = [w["name"] for w in manifest["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        cell = spec.load_cell(w["name"])   # every named file exists and agrees
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, f"{w['name']} reports no per-layer metric"
        for m in cell.per_layer:   # it reports the end-to-end metric each one moves
            assert m["moves"] in reported, (w["name"], m["name"], m["moves"])
            assert m["name"] in cell.readers
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for name in m.get("workloads", []):
            assert name in cells


def test_a_name_that_is_not_there_is_an_error_not_a_skip(tmp_path, manifest):
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("no_such_cell")
    with pytest.raises(spec.SpecError, match="no file"):
        spec.load_reader("no_such_metric")
    with pytest.raises(spec.SpecError, match="no peak recorded"):
        spec.load_peaks("TPU v9 imaginary")
