"""Driver ``serve``: one ``ServingEngine`` on one chip, driven through
``submit`` / ``tick`` / ``poll`` from this one thread, as ``replay_trace``
drives it. Open loop (arrivals on a schedule fixed by the cell; the window's
requests are drained afterwards so that each has its latencies, and the drain
counts toward no rate) or closed loop (each of n clients sends its next
request when the last returns; the window closes on the clock).

The harness's clock: a request's time to first token starts when it was due,
not when ``submit`` was reached, so the generator's lateness is inside it.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from .. import check, stats, traffic
from ..stats import RequestRecord


def build_engine(cell, weights_tree, max_new_tokens: int):
    import jax.numpy as jnp

    from accelerate_tpu import Model
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.utils import ServingConfig

    eng = cell.workload["engine"]
    module = cell.family.program_module(cell.config, eng["max_len"])
    model = Model(module=module, params=weights_tree)
    return ServingEngine(model, ServingConfig(
        n_slots=int(eng["n_slots"]), max_len=int(eng["max_len"]),
        max_new_tokens=int(max_new_tokens), cache_dtype=jnp.bfloat16,
        temperature=0.0, eos_token_id=None, speculate_k=0))


class Loop:
    """Submits, ticks and polls; keeps one record per request."""

    def __init__(self, engine, annotate):
        self.engine = engine
        self.annotate = annotate
        self.records: list[RequestRecord] = []
        self.inflight: dict[int, RequestRecord] = {}
        self.t0 = 0.0  # the window's opening on time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, req: traffic.Request, phase: str, due_s: float | None) -> None:
        with self.annotate("chipbench.submit"):
            t = self.now()
            rid = self.engine.submit(req.prompt, max_new_tokens=req.budget)
        rec = RequestRecord(index=req.index, phase=phase, prompt_len=int(req.prompt.size),
                            budget=req.budget, due_s=t if due_s is None else due_s,
                            submit_s=t)
        self.records.append(rec)
        self.inflight[rid] = rec

    def step(self) -> None:
        with self.annotate("chipbench.tick"):
            self.engine.tick()
        with self.annotate("chipbench.poll"):
            rows = self.engine.poll()
        t = self.now()
        for row in rows:
            rec = self.inflight.pop(row["id"])
            rec.status = row["status"]
            rec.new_tokens = int(row["new_tokens"])
            rec.tokens = row["tokens"]
            rec.done_s = t   # our clock, when the row reached the client
            if row["ttft_s"] is not None:
                # The engine streams nothing, so the instant of the first token
                # is the one it noted, counted from submit(): shift onto ours.
                rec.first_token_s = min(rec.submit_s + row["ttft_s"], t)

    def open_window_now(self) -> None:
        """Moves the clock's zero to this instant: after a ramp that is counted
        in ticks, the window opens here, and what the ramp recorded moves with it."""
        shift = self.now()
        self.t0 += shift
        for rec in self.records:
            rec.shift(-shift)

    def idle(self, seconds: float) -> None:
        with self.annotate("chipbench.idle"):
            time.sleep(min(0.002, max(0.0, seconds)))


def drive(loop: Loop, cell, seed: int, seconds: float, on_open, on_close, tracer) -> dict:
    """Ramp, window and drain. ``on_open`` / ``on_close`` run at the window's
    edges (counters, compile census, the profile's end); ``tracer`` is given
    the loop's clock after every step and starts the profile."""
    w = cell.workload
    arr = w["traffic_params"]["arrivals"]
    vocab = cell.config["vocab_size"]
    # A closed loop's ramp is counted in ticks (``ramp_ticks``), so that every
    # run opens its window on the same state of the engine; an open loop's is
    # in seconds, as its arrivals are.
    ramp_ticks = int(w.get("ramp_ticks", 0))
    ramp_s = 0.0 if ramp_ticks else float(w.get("ramp_s", 0.0))
    engine = loop.engine
    loop.t0 = time.perf_counter() + ramp_s

    if arr["kind"] == "open":
        ramp = traffic.requests_for_phase(w["traffic_params"], vocab, seed, ramp_s, salt=1) \
            if ramp_s > 0 else []
        window = traffic.requests_for_phase(w["traffic_params"], vocab, seed, seconds)
        todo = collections.deque(
            [("ramp", r, r.due_s - ramp_s) for r in ramp if r.due_s < ramp_s]
            + [("window", r, r.due_s) for r in window if r.due_s < seconds])
        clients = None
    else:
        pool = traffic.requests_for_phase(w["traffic_params"], vocab, seed, seconds)
        todo, clients = None, int(arr["clients"])
        sent = 0

    opened, steps = False, 0
    while True:
        now = loop.now()
        if not opened and (steps >= ramp_ticks if ramp_ticks else now >= 0.0):
            if ramp_ticks:
                loop.open_window_now()
                now = 0.0
            on_open()
            opened = True
        if opened and now >= seconds:
            break
        if clients is None:
            while todo and todo[0][2] <= now:
                phase, req, due = todo.popleft()
                loop.submit(req, phase, due)
        else:
            while len(loop.inflight) < clients:
                loop.submit(pool[sent % len(pool)], "window" if opened else "ramp", None)
                sent += 1
        if engine.pending:
            loop.step()
            steps += 1
        else:
            loop.idle(todo[0][2] - now if todo else seconds - now)
        if opened:
            tracer(loop.now())
    close_s = loop.now()
    on_close()
    # The drain: nothing more is sent. An open loop waits for every request
    # due in the window, so that each has its latencies; a closed loop for
    # those that were running at the close, so that what they produced inside
    # the window is counted (the engine shows a row only when it is finished).
    # The drain counts toward no rate.
    limit = close_s + float(w["drain_limit_s"])
    while loop.now() < limit and any(clients is not None or r.phase == "window"
                                     for r in loop.inflight.values()):
        loop.step()
    return {"close_s": close_s, "end_s": loop.now()}


def run(ctx) -> dict:
    """One run of a serving cell. ``ctx`` is ``run.py``'s: the cell, seed,
    seconds, weights, clocks, compile census and trace switch."""
    import jax

    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    out_spec = cell.workload["traffic_params"]["output_len"]
    max_new = int(out_spec.get("max", out_spec.get("value", 0)))
    engine = build_engine(cell, ctx.weights_tree, max_new)
    ctx.say(f"engine: {engine.n_slots} slots x {engine.t_max} rows, prefill ladder "
            f"{engine.ladder}")
    t = time.perf_counter()
    with ctx.annotate("chipbench.warmup"):
        engine.warmup()
    ctx.say(f"warm-up (every prefill rung and the decode program): "
            f"{time.perf_counter() - t:.2f} s, executables built {ctx.compiles.executables}, "
            f"of them from the cache {ctx.compiles.cache_hits}")

    loop = Loop(engine, ctx.annotate)
    marks = {}

    def on_open():
        engine.poll()
        engine.reset_metrics()
        marks["compiles_open"] = ctx.compiles.executables
        marks["setup_s"] = ctx.since_start()

    def on_close():
        marks["counters"] = engine.stats()
        ctx.finish_trace()

    times = drive(loop, cell, seed, seconds, on_open, on_close,
                  ctx.tracer(seconds, float(cell.workload.get("trace_s", 4.0))))
    ctx.reduce_trace()
    compiles_in_window = ctx.compiles.executables - marks["compiles_open"]
    window_s = times["close_s"]          # the clock passes `seconds` by part of a tick
    summary = stats.serve_summary(loop.records, window_s, times["end_s"])
    if cell.workload["traffic_params"]["arrivals"]["kind"] == "open":
        # after the drain, a request of the window that never finished has failed
        summary["failed"] += summary["unfinished"]
    memory_peak = ctx.memory_peak_bytes()

    # free the program's state before the reference runs
    counters = marks["counters"]
    engine.close()
    loop.engine = None
    del engine
    gc.collect()

    t = time.perf_counter()
    sample = check.pick_sample(loop.records, seed, int(cell.workload["correct"]["sample_requests"]))
    numbers = check.served_gaps(cell.family, cell.config, ctx.weights,
                                sample, int(cell.workload["engine"]["max_len"]),
                                float(cell.workload["correct"].get("router_margin_min", 0.0)),
                                control=ctx.control, say=ctx.say, dump=ctx.gaps_file())
    ctx.say(f"compared {numbers['tokens']} served tokens of {numbers['requests']} requests, "
            f"{numbers.get('tokens_left_out', 0)} left out by the routing margin; the reference took "
            f"{time.perf_counter() - t:.2f} s")
    numbers["bad_rows"] = check.row_faults(loop.records, cell.config["vocab_size"])
    numbers["failed"] = summary["failed"]
    numbers["compiles_in_window"] = compiles_in_window
    limits = dict(cell.workload["correct"]["limits"], bad_rows=0, failed=0,
                  compiles_in_window=0)

    ctx.say(f"window {window_s:.3f} s: due {summary['attempted']}, failed {summary['failed']}, "
            f"finished inside {summary['finished_in_window']} "
            f"({summary['tokens_out_in_window']} tokens); samples: ttft {summary['n_ttft']}, "
            f"tpot {summary['n_tpot']}; generator late p95 over the whole window "
            f"{summary.get('gen_late_p95_ms', 0.0):.0f} ms; drained until {times['end_s']:.2f} s, "
            f"{len(loop.inflight)} still in flight then")
    return {
        "setup_s": marks["setup_s"], "window_s": window_s, "summary": summary,
        "records": loop.records, "counters": counters, "memory_peak_bytes": memory_peak,
        "numbers": numbers, "limits": limits,
        "attempted": summary["attempted"], "failed": summary["failed"],
        "end_to_end": {k: summary[k] for k in ("serve_tok_s", "ttft_p95_ms", "tpot_p95_ms")
                       if k in summary},
    }
