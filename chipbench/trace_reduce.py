"""From a profiler trace (``.xplane.pb``) to busy and idle time, device time by
program and by operation, exposed collective time, and the longest idle gaps
with what the host was doing in them. Reads the file with
``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per run of a
compiled program (``jit_decode(<fingerprint>)``) and whose line ``XLA Ops`` has
one event per operation inside it; ``/host:CPU`` has one line per host thread,
where ``jax.profiler.TraceAnnotation`` spans (``chipbench.tick``) appear under
their own names; all on one clock, nanoseconds.

    python3 -m chipbench.trace_reduce <dir or file> [--dump] [--trim <out> <seconds>]
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                         r"collective-permute|collective-broadcast)")   # of an opcode
_OPCODE = re.compile(r"[\s)]([a-z][a-z0-9\-]*)\(")
_CONTAINERS = ("while", "conditional", "call")   # their bodies' operations are events too
_HOST_SPAN = "chipbench."
_TOP = 10


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f)) for d, _, fs in os.walk(path) for f in fs)


def program_name(event_name: str) -> str:
    """``jit_decode(12345)`` -> ``jit_decode``."""
    return event_name.split("(", 1)[0]


def short_op(event_name: str) -> tuple[str, str]:
    """``%fusion.3 = bf16[24,14336]{...} fusion(...)`` -> (``%fusion.3 fusion
    bf16[24,14336]``, ``fusion``): a line's worth of an XLA operation's text,
    and its opcode."""
    lhs, _, rhs = event_name.partition(" = ")
    if not rhs:
        return event_name[:120], ""
    m = _OPCODE.search(rhs)
    opcode = m.group(1) if m else ""
    result = rhs.split("{", 1)[0].split(" ", 1)[0] if not rhs.startswith("(") else "(tuple)"
    return f"{lhs} {opcode} {result}"[:120], opcode


def compact_op(event_name: str) -> str:
    """The operation's text cut down to what ``short_op`` reads, for a trace
    that is checked in."""
    lhs, _, rhs = event_name.partition(" = ")
    if not rhs:
        return event_name[:120]
    short, opcode = short_op(event_name)
    return f"{lhs} = {short.rsplit(' ', 1)[-1]} {opcode}()"


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals, lo: float, hi: float):
    """The idle ``(start, end)`` stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def subtract_length(intervals, cover) -> float:
    """Length of ``intervals`` that ``cover`` does not overlap."""
    both = union_length(list(intervals) + list(cover))
    return both - union_length(cover)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def read_planes(path: str) -> dict:
    """The trace as plain lists: ``{"devices": {plane: {"modules": [...],
    "ops": [...]}}, "host": [...]}`` with events as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines or MODULES_LINE in lines:
                devices[plane.name] = {
                    "modules": _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
                    "ops": _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                }
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [ev for ev in _events(ln) if ev[0].startswith(_HOST_SPAN)]
    return {"devices": devices, "host": host}


def reduce_planes(planes: dict) -> dict:
    """The numbers the metrics read. Seconds throughout; device numbers are
    means over the chips that ran something."""
    devices = {n: d for n, d in planes["devices"].items() if d["ops"] or d["modules"]}
    if not devices:
        raise ValueError("the trace holds no device plane with operations: nothing ran "
                         "on the device in the traced slice")
    all_ev = [ev for d in devices.values() for ev in (d["ops"] or d["modules"])]
    # The traced window runs from the first device event to the last. The
    # profiler's own span is a quarter of a second longer (looked at by hand, PR
    # 24): the host is starting and stopping the profile there and drives nothing,
    # which would read as six points of idle that a run without a profile has not.
    lo, hi = min(e[1] for e in all_ev), max(e[2] for e in all_ev)
    window_ns = hi - lo
    programs, ops = {}, {}
    busy, exposed = [], []
    for d in devices.values():
        work = d["ops"] or d["modules"]
        busy.append(union_length([(s, e) for _, s, e in work]))
        kinds = [(short_op(n)[1], s, e) for n, s, e in d["ops"]]
        coll = [(s, e) for k, s, e in kinds if _COLLECTIVE.match(k)]
        comp = [(s, e) for k, s, e in kinds
                if not _COLLECTIVE.match(k) and k not in _CONTAINERS]
        exposed.append(subtract_length(coll, comp) if coll else 0.0)
        for n, s, e in d["modules"]:
            p = programs.setdefault(program_name(n), {"count": 0, "total_s": 0.0})
            p["count"] += 1
            p["total_s"] += (e - s) * 1e-9
        for n, s, e in d["ops"]:
            short, opcode = short_op(n)
            if opcode not in _CONTAINERS:
                ops[short] = ops.get(short, 0.0) + (e - s) * 1e-9
    n_dev = len(devices)
    for p in programs.values():          # per chip: every chip runs the program
        p["count"] = p["count"] / n_dev
        p["total_s"] = p["total_s"] / n_dev
    first = next(iter(devices.values()))
    work = first["ops"] or first["modules"]
    idle = {}   # seconds the first chip sat idle, by what the host was doing
    for s, e in gaps_of([(s, e) for _, s, e in work], lo, hi):
        doing = _host_doing(planes["host"], s, e)
        idle[doing] = idle.get(doing, 0.0) + (e - s) * 1e-9
    return {
        "devices": n_dev,
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "collective_exposed_s": sum(exposed) / n_dev * 1e-9,
        "programs": programs,
        "breakdown": {
            "device_ops": [[n, t / n_dev] for n, t in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:_TOP]],
            "idle_gaps": [[n, t] for n, t in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:_TOP]],
        },
    }


def _host_doing(host, s: float, e: float) -> str:
    """The harness's span that covers most of the gap [s, e], or ``host``."""
    best, best_len = "host (no harness span)", 0.0
    for name, hs, he in host:
        overlap = min(e, he) - max(s, hs)
        if overlap > best_len:
            best, best_len = name, overlap
    return best


def reduce_dir(path: str) -> dict:
    return reduce_planes(read_planes(path))


def _dump(path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print("PLANE", plane.name, dict(list(plane.stats)[:8]))
        for ln in plane.lines:
            evs = list(ln.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0.0) + float(e.duration_ns)
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {ln.name!r}: {len(evs)} events, {len(names)} names")
            for n, t in top:
                print(f"      {t * 1e-6:10.3f} ms  {n[:110]}")
            for e in evs[:3]:
                print("      first:", e.name[:80], e.start_ns, e.duration_ns,
                      [(k, str(v)[:40]) for k, v in list(e.stats)[:6]])


def trim(path: str, out: str, seconds: float) -> None:
    """The first ``seconds`` of a trace as JSON (``read_planes``' form): small
    enough to check in, and what the tests reduce."""
    planes = load_trimmed(path) if path.endswith(".json") else read_planes(path)
    all_ev = [ev for d in planes["devices"].values() for ev in d["ops"] + d["modules"]]
    lo = min(e[1] for e in all_ev)
    hi = lo + seconds * 1e9

    def cut(evs, name=lambda n: n):
        return [[name(n), s - lo, e - lo] for n, s, e in evs if s >= lo and e <= hi]

    small = {
        "devices": {n: {"modules": cut(d["modules"]), "ops": cut(d["ops"], compact_op)}
                    for n, d in planes["devices"].items()},
        "host": cut(planes["host"]),
    }
    with open(out, "w") as f:
        json.dump(small, f, separators=(",", ":"))


def load_trimmed(path: str) -> dict:
    with open(path) as f:
        small = json.load(f)
    return {
        "devices": {n: {k: [tuple(e) for e in v] for k, v in d.items()}
                    for n, d in small["devices"].items()},
        "host": [tuple(e) for e in small["host"]],
    }


if __name__ == "__main__":
    if "--dump" in sys.argv:
        _dump(sys.argv[1])
    elif "--trim" in sys.argv:
        i = sys.argv.index("--trim")
        trim(sys.argv[1], sys.argv[i + 1], float(sys.argv[i + 2]))
    else:
        print(json.dumps(reduce_dir(sys.argv[1]), indent=1))
