"""Shrinks a cell for ``--rehearse`` and for the tests: the same files, code
paths and control flow at widths a CPU runs in seconds. Nothing a rehearsal
prints is a metric."""

from __future__ import annotations

import copy
import dataclasses

_TINY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "vocab_size": 512, "initializer_range": 0.06}
_SHRINK = 8  # lengths, slots and rates of the traffic are divided by this


def _shrink_len(spec: dict) -> dict:
    spec = dict(spec)
    for key in ("median", "min", "max", "value"):
        if key in spec:
            spec[key] = max(2, int(spec[key]) // _SHRINK)
    return spec


def shrink(cell):
    config = dict(cell.config, **_TINY)
    if "num_local_experts" in config:
        config["num_local_experts"] = 4
    w = copy.deepcopy(cell.workload)
    if "engine" in w:
        w["engine"]["n_slots"] = max(2, w["engine"]["n_slots"] // _SHRINK)
        w["engine"]["max_len"] = w["engine"]["max_len"] // _SHRINK
    t = w.get("traffic_params")
    if t:
        t["prompt_len"] = _shrink_len(t["prompt_len"])
        t["output_len"] = _shrink_len(t["output_len"])
        if t["arrivals"]["kind"] == "closed":
            t["arrivals"]["clients"] = max(2, t["arrivals"]["clients"] // _SHRINK)
            t["arrivals"]["pool"] = 64
    # limits are set from readings at the size they are for: the tiny size has its own
    w["correct"] = {**w["correct"], **w["correct"].get("rehearsal", {})}
    for key in ("ramp_s", "trace_s", "drain_limit_s"):
        if key in w:
            w[key] = min(float(w[key]), 1.0)
    if "ramp_ticks" in w:
        w["ramp_ticks"] = min(int(w["ramp_ticks"]), 8)
    return dataclasses.replace(cell, config=config, workload=w)
