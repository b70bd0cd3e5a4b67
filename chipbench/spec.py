"""Finds what BENCHMARK.json names: the cell's file, its configuration, the
family's module and the per-layer metrics' readers. Something named there and
absent from its directory is an error at start, never a skip."""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Callable

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    pass


def _load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"), "the manifest")


def load_peaks(device_kind: str, bench_dir: str = HERE) -> dict:
    peaks = _load_json(os.path.join(bench_dir, "peaks.json"), "the table of peaks")
    if device_kind not in peaks:
        raise SpecError(f"no peak recorded for device_kind {device_kind!r}: add it to "
                        "chipbench/peaks.json with its source")
    return peaks[device_kind]


def load_reader(name: str, bench_dir: str = HERE) -> Callable:
    """``metrics/<name>.py``'s ``read(ctx)``. Metric names may hold dots, so
    the file is loaded by its path."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"per-layer metric {name!r}: no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"per-layer metric {name!r}: {path} defines no read(ctx)")
    return module.read


def _metrics_of(manifest: dict, group: str, cell: str) -> list[dict]:
    """A metric without a ``workloads`` key belongs to every cell."""
    return [m for m in manifest[group] if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: dict            # workloads/<cell>.json
    config_name: str
    config: dict              # configs/<configuration>.json
    family: Any               # families/<family>.py, imported
    driver: Any               # drivers/<driver>.py, imported
    end_to_end: list[dict]    # this cell's entries of the manifest
    per_layer: list[dict]
    readers: dict             # per-layer metric name -> read(ctx)


def load_cell(name: str, root: str = ROOT, bench_dir: str = HERE) -> Cell:
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SpecError(f"BENCHMARK.json has no workload {name!r} (it has: {known})")
    workload = _load_json(os.path.join(bench_dir, "workloads", name + ".json"),
                          f"workload {name!r}")
    for key in ("config", "traffic", "chips"):
        if workload.get(key) != entry[key]:
            raise SpecError(f"workload {name!r}: {key} is {workload.get(key)!r} in its file "
                            f"and {entry[key]!r} in BENCHMARK.json")
    cfg_entry = next((c for c in manifest["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {name!r} names configuration {entry['config']!r}, "
                        "which BENCHMARK.json does not list")
    config = _load_json(os.path.join(root, cfg_entry["file"]),
                        f"configuration {entry['config']!r}")
    try:
        family = importlib.import_module(f"chipbench.families.{config['family']}")
        driver = importlib.import_module(f"chipbench.drivers.{workload['driver']}")
    except ModuleNotFoundError as e:
        raise SpecError(f"workload {name!r}: {e}") from e
    per_layer = _metrics_of(manifest, "per_layer", name)
    return Cell(
        name=name, chips=int(entry["chips"]), workload=workload,
        config_name=entry["config"], config=config, family=family, driver=driver,
        end_to_end=_metrics_of(manifest, "end_to_end", name), per_layer=per_layer,
        readers={m["name"]: load_reader(m["name"], bench_dir) for m in per_layer},
    )
