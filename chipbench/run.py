"""Runs one cell once.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

On a TPU with as many chips as the cell asks for, or not at all: any other
backend is a nonzero exit that names it, within seconds, and prints no result.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``compared`` last); with ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

``--rehearse`` is for debugging the command where there is no chip: the same
control flow at a tiny size on whatever backend JAX finds. Every line it
prints says ``platform=<backend> rehearsal`` and its result holds counts only,
no metric. ``--control int8`` also reads the control of ``correct`` (the
reference in the lower precision, put in the program's place); benchmark runs
never pass it.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from . import spec  # noqa: E402

_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCensus:
    """Executables built, or loaded from the persistent cache, so far."""

    def __init__(self):
        import jax.monitoring

        self.executables = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        self.executables += event == _BACKEND_EVENT

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


class Context:
    """What a driver gets from this file."""

    def __init__(self, cell, args, devices):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.control = args.control
        self.rehearse = bool(args.rehearse)
        self.devices = devices
        self.tag = f"platform={devices[0].platform}" + (" rehearsal" if self.rehearse else "")
        self.compiles = CompileCensus()
        self.weights = None       # flat, as the reference reads them
        self.weights_tree = None  # nested, as the program holds them
        self.trace_dir = os.path.join(spec.ROOT, ".chipbench_trace")
        self._tracing = False
        self._trace_span = None
        self.trace_reduction = None

    def say(self, msg: str) -> None:
        # each line says how long after the process's start: set-up is read off them
        print(f"[{self.tag} +{self.since_start():.1f}s] {msg}", file=sys.stderr, flush=True)

    def since_start(self) -> float:
        return time.perf_counter() - _T0

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def gaps_file(self) -> str | None:
        """Where a ``--control`` run leaves every compared token's gaps."""
        if not self.control:
            return None
        os.makedirs(os.path.join(spec.ROOT, ".chipbench_gaps"), exist_ok=True)
        return os.path.join(spec.ROOT, ".chipbench_gaps", f"{self.cell.name}_{self.seed}.npz")

    def memory_peak_bytes(self) -> int | None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    # -- the profile: the last seconds of the window ---------------------------
    # It is stopped when the window has closed (``finish_trace``): stopping
    # takes the host a second or two, which would otherwise fall into the window
    # and read as the load generator running late.

    def tracer(self, seconds: float, trace_s: float):
        if not self.trace:
            return lambda now: None
        import jax

        start = max(0.0, seconds - trace_s)

        def step(now: float) -> None:
            if not self._tracing and self._trace_span is None and now >= start:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                t = time.perf_counter()
                jax.profiler.start_trace(self.trace_dir, profiler_options=options)
                self._tracing = True
                self._trace_span = [time.perf_counter(), None, time.perf_counter() - t]

        return step

    def finish_trace(self) -> None:
        """Stops the profile; a driver calls it as the window closes. The
        reduction waits until the run's numbers are taken."""
        if not self._tracing:
            return
        import jax

        self._trace_span[1] = time.perf_counter()
        jax.profiler.stop_trace()
        self._tracing = False
        self._trace_span.append(time.perf_counter() - self._trace_span[1])

    def reduce_trace(self) -> None:
        """After the window: from the trace's file to the numbers."""
        self.finish_trace()
        if self._trace_span is None:
            return
        from . import trace_reduce

        t = time.perf_counter()
        size = trace_reduce.dir_bytes(self.trace_dir)
        try:
            self.trace_reduction = trace_reduce.reduce_dir(self.trace_dir)
        except ValueError:
            if not self.rehearse:   # a rehearsal's backend has no device plane
                raise
        self.say(f"trace: start {self._trace_span[2]:.2f} s, stop {self._trace_span[3]:.2f} s, "
                 f"reduce {time.perf_counter() - t:.2f} s, {size / 2**20:.1f} MiB")
        if not os.environ.get("CHIPBENCH_KEEP_TRACE"):
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def _parse(argv):
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.run", description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8",), default=None,
                    help="also read the control of `correct` (not part of a benchmark run)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever backend JAX finds; prints no metric")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    cell = spec.load_cell(args.workload)  # a missing file is an error here, before JAX

    from accelerate_tpu.compile_manager import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    # Every program of a run goes to the persistent cache, the small ones too:
    # the second run of a cell in a checkout then compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    if args.rehearse:
        from . import rehearsal

        cell = rehearsal.shrink(cell)
    elif devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.exit(f"chipbench: cell {cell.name!r} needs {cell.chips} TPU chip(s); JAX's default "
                 f"backend is {jax.default_backend()!r} with {len(devices)} device(s) "
                 f"({devices[0].device_kind}). Nothing is measured on another backend.")
    devices = devices[: cell.chips]
    peaks = None if args.rehearse else spec.load_peaks(devices[0].device_kind)

    ctx = Context(cell, args, devices)
    ctx.say(f"cell {cell.name}: configuration {cell.config_name} (family "
            f"{cell.config['family']}), {cell.chips} chip(s) of {devices[0].device_kind}, "
            f"seed {ctx.seed}, window {ctx.seconds:g} s, trace {int(ctx.trace)}; "
            f"compile cache {cache_dir}")

    from . import weights

    t = time.perf_counter()
    specs = cell.family.weight_specs(cell.config)
    ctx.weights = weights.make_weights(specs, cell.config["initializer_range"], ctx.seed)
    jax.block_until_ready(ctx.weights)
    ctx.weights_tree = weights.nest(ctx.weights)
    ctx.say(f"weights: {weights.n_params(specs) / 1e9:.3f}B parameters in bfloat16 from the "
            f"seed, {time.perf_counter() - t:.2f} s")

    result = cell.driver.run(ctx)

    from . import check

    correct, compared = check.judge(result["numbers"], result["limits"])
    metrics, readers_with_a_value = {}, []
    if ctx.trace:
        from .metrics import _context

        mctx = _context.MetricContext(cell=cell, peaks=peaks, result=result,
                                      trace=ctx.trace_reduction)
        for m in cell.per_layer:
            value = cell.readers[m["name"]](mctx)
            if value is None:       # a reader that found nothing says nothing
                continue
            readers_with_a_value.append(m["name"])
            if not args.rehearse:   # a rehearsal names the readers that ran and no number
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    elif not args.rehearse:
        values = dict(result["end_to_end"], setup_s=result["setup_s"])
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if ctx.trace and ctx.trace_reduction is not None and not args.rehearse:
        device["busy_s"] = ctx.trace_reduction["busy_s"]
        device["window_s"] = ctx.trace_reduction["window_s"]
        line["breakdown"] = ctx.trace_reduction["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
        line["readers_with_a_value"] = readers_with_a_value
    if ctx.control:
        line["control"] = {k: v for k, v in result["numbers"].items() if k.startswith("control_")}
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in compared}

    for c in compared:
        ctx.say(f"compared {c['name']}: {c['value']} (limit {c['limit']}) "
                f"{'ok' if c['ok'] else 'OVER'}")
    ctx.say(f"correct: {correct}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
