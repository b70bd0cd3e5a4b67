"""Input-pipeline benchmark: does the loader keep up with the training step?

Answers two questions with numbers (VERDICT r4 'what's weak' #5):

1. **Overlap** — with native C++ collation (native/host_runtime.cpp) + the
   prefetch thread, what fraction of a bench-shaped step time does the loader
   steal? The reference's MpDeviceLoader (data_loader.py:669-719) exists for
   exactly this; here the claim is measured: added wall-clock per step vs a
   pure-compute loop, at the 1B@2048 target step time (~80 ms) and a tighter
   ~25 ms decode-shaped step.

2. **Dispatch-mode cost** — DataLoaderDispatcher pays a per-batch
   ``broadcast_object_list`` (rank 0 reads + pickles the full batch). How
   many ms/batch vs shard mode, same data? (reference: data_loader.py:804-944)

Host-side only — runs anywhere, no TPU needed. Emits one JSON line per
measurement. The dispatch measurement self-launches a 2-process CPU gang.
"""

import json
import os
import sys
import time

import numpy as np

# Host-side benchmark: it measures the input pipeline, so it never takes an
# accelerator (set before jax is imported, here and in the launched gang).
os.environ["JAX_PLATFORMS"] = "cpu"

SEQ = 2048
BATCH = 8
N_BATCHES = 60


def _dataset(n_samples: int):
    rng = np.random.default_rng(0)
    return [
        {"input_ids": rng.integers(0, 32000, SEQ).astype(np.int32),
         "labels": rng.integers(0, 32000, SEQ).astype(np.int32)}
        for _ in range(n_samples)
    ]


def _collate(samples):
    from accelerate_tpu.native import stack_items

    return {
        k: stack_items([s[k] for s in samples]) for k in samples[0]
    }


def _loader(prefetch_size: int, force_python: bool):
    import torch.utils.data as tud

    from accelerate_tpu.data_loader import prepare_data_loader

    if force_python:
        os.environ["ACCELERATE_DISABLE_NATIVE"] = "1"
    else:
        os.environ.pop("ACCELERATE_DISABLE_NATIVE", None)
    ds = _dataset(BATCH * N_BATCHES)
    dl = tud.DataLoader(ds, batch_size=BATCH, collate_fn=_collate, shuffle=False)
    return prepare_data_loader(dl, put_on_device=False, prefetch_size=prefetch_size)


def bench_overlap(step_ms: float, prefetch_size: int, force_python: bool) -> dict:
    """Walk the loader with a simulated device-bound step (time.sleep releases
    the GIL exactly like a dispatched device computation) and report the
    loader's added wall-clock per step."""
    dl = _loader(prefetch_size, force_python)
    it = iter(dl)
    next(it)  # warm: thread started, first batch buffered
    t0 = time.perf_counter()
    n = 0
    for _ in it:
        time.sleep(step_ms / 1e3)
        n += 1
    wall = time.perf_counter() - t0
    per_step_ms = wall / n * 1e3
    idle_ms = per_step_ms - step_ms
    return {
        "metric": "input_pipeline_overlap",
        "step_ms": step_ms,
        "prefetch": prefetch_size,
        "native_collation": not force_python,
        "per_step_ms": round(per_step_ms, 3),
        "loader_added_ms": round(idle_ms, 3),
        "loader_idle_frac": round(max(0.0, idle_ms) / step_ms, 4),
        "n": n,
    }


def bench_dispatch_vs_shard() -> None:
    """2-process gang: ms/batch for dispatch mode (per-batch object
    broadcast) vs shard mode (each rank reads its own shard)."""
    import subprocess

    from accelerate_tpu.test_utils import get_launch_command

    cmd = get_launch_command(num_processes=2, virtual_devices=2) + [
        __file__, "--gang-child"
    ]
    r = subprocess.run(
        cmd, env={**os.environ, "PYTHONPATH": os.getcwd()},
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        print(json.dumps({"metric": "dispatch_vs_shard", "error": r.stderr[-1500:]}))
        return
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            print(line)


def _gang_child() -> None:
    import torch.utils.data as tud

    from accelerate_tpu.data_loader import prepare_data_loader
    from accelerate_tpu.state import PartialState

    state = PartialState()
    ds = _dataset(BATCH * N_BATCHES)
    rows = {}
    for mode, group in (("shard", 1), ("dispatch_g1", 1), ("dispatch_g8", 8)):
        dl = prepare_data_loader(
            tud.DataLoader(ds, batch_size=BATCH, collate_fn=_collate, shuffle=False),
            put_on_device=False,
            dispatch_batches=mode.startswith("dispatch"),
            dispatch_group_size=group,
        )
        it = iter(dl)
        next(it)
        t0 = time.perf_counter()
        n = sum(1 for _ in it)
        rows[mode] = (time.perf_counter() - t0) / n * 1e3
    if state.is_main_process:
        print(json.dumps({
            "metric": "dispatch_vs_shard",
            "shard_ms_per_batch": round(rows["shard"], 3),
            "dispatch_group1_ms_per_batch": round(rows["dispatch_g1"], 3),
            "dispatch_group8_ms_per_batch": round(rows["dispatch_g8"], 3),
            "group8_overhead_ms": round(rows["dispatch_g8"] - rows["shard"], 3),
            "batch_bytes": int(BATCH * SEQ * 4 * 2),
        }), flush=True)


def main() -> None:
    if "--gang-child" in sys.argv:
        _gang_child()
        return
    for step_ms in (80.0, 25.0):
        for prefetch, force_py in ((2, False), (2, True), (0, False)):
            print(json.dumps(bench_overlap(step_ms, prefetch, force_py)), flush=True)
    bench_dispatch_vs_shard()


if __name__ == "__main__":
    main()
