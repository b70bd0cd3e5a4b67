"""Finds the knee of an open-loop serving cell, once, when the cell is defined:
the highest arrival rate at which the backlog does not grow. One process, one
engine; each rate gets a ramp, a window and a full drain.

    python3 -m chipbench.sweep --workload <cell> --seed <n> --seconds <s> --rates 3,4,5,6

Prints one JSON line per rate. Not part of a benchmark run: the cell's file
holds the resulting rate as a number.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import sys

from . import spec, stats, weights
from .drivers import serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    from accelerate_tpu.compile_manager import place_compile_cache

    place_compile_cache()
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"chipbench.sweep: no TPU (default backend {jax.default_backend()!r})")
    flat = weights.make_weights(cell.family.weight_specs(cell.config),
                                cell.config["initializer_range"], args.seed)
    engine = serve.build_engine(cell, weights.nest(flat),
                                cell.workload["traffic_params"]["output_len"]["max"])
    engine.warmup()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        w = copy.deepcopy(cell.workload)
        w["traffic_params"]["arrivals"]["rate_per_s"] = rate
        loop = serve.Loop(engine, jax.profiler.TraceAnnotation)
        marks = {}

        def on_open():
            engine.poll()
            engine.reset_metrics()
            marks["open_pending"] = engine.pending

        def on_close():
            marks["close_pending"] = engine.pending
            marks["counters"] = engine.stats()

        times = serve.drive(loop, dataclasses.replace(cell, workload=w), args.seed + i,
                            args.seconds, on_open, on_close, lambda now: None)
        while engine.pending:
            loop.step()
        s = stats.serve_summary(loop.records, times["close_s"], times["end_s"])
        c = marks["counters"]
        print(json.dumps({
            "platform": d.platform, "rate_per_s": rate, "window_s": times["close_s"],
            "pending_at_open": marks["open_pending"], "pending_at_close": marks["close_pending"],
            "drain_s": times["end_s"] - times["close_s"],
            "mean_occupancy": c["mean_occupancy"], "mean_queue_depth": c["mean_queue_depth"],
            "ticks": c["ticks"], "prefill_chunks": c["prefill_chunks"],
            **{k: s.get(k) for k in ("attempted", "failed", "serve_tok_s", "ttft_p50_ms",
                                     "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
                                     "gen_late_p95_ms")},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
