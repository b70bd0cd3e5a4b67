"""Fleet router — cell-granular failover above the serving engines.

One engine on one mesh is a CELL, not a fleet: every robustness guarantee
below this layer (admission SLOs, deterministic chaos, the request
journal's exactly-once ``recover()``, SDC quarantine, autoscale resize)
stops at the boundary of a single :class:`~accelerate_tpu.serving.
ServingEngine` — a whole-cell loss loses every in-flight request in it.
The :class:`FleetRouter` treats whole engines as schedulable units the way
arXiv:2412.14374 treats per-stage programs as independently schedulable /
restartable units, in four legs:

1. **Cell registry + health.** Each cell is a JOURNALED engine with its own
   WAL directory, weights version, and rolling ``window_stats()``. The
   router heartbeats cells every tick and classifies them
   ``healthy | degraded | draining | dead`` — a cell that stops making
   progress with work pending for ``FleetConfig.max_idle_ticks`` ticks (the
   engine-level hang guard's definition, fleet-scoped) or whose process
   exits per ``EXIT_CODE_TABLE`` is dead. Every routing decision is a pure
   function of (tick, registry state, request key), so seeded runs replay
   bit-identically — the same counter-based determinism discipline as
   chaos.py.

2. **Routing + spillover.** ``submit()`` picks a cell by session-affinity
   hash (the seam prefix-affinity routing will plug into), spilling to the
   least-loaded cell when the affinity target's queue-depth p95 breaches
   ``FleetConfig.queue_depth_band``. The router sheds only when ALL cells
   breach — and SLO aggregates stay per-cell (unweighted across cells), so
   one sick cell can't hide behind a big healthy one's volume.

3. **Exactly-once cross-cell drain.** When a cell dies mid-trace the
   router ADOPTS the dead cell's journal directory (journal.py's sentinel
   arbitrates against a restarting cell supervisor — double adoption is
   double execution) and replays it: journaled terminals re-emit their
   cached rows, never re-executed; in-flight requests resubmit by
   ``client_request_id`` onto surviving cells — a recovery, so they never
   spend ``max_retries`` — and their deadlines re-anchor charging
   pre-crash runtime but not the outage (the journal's monotonic
   ``t_mono`` stamps). Under equal weights the replayed rows are bit-equal
   to an uninterrupted run: zero lost, zero double-executed.

4. **Cell-granular lifecycle.** ``publish()`` canaries a whole CELL (the
   canary cell binds the candidate at ``fraction=1.0`` via the engine's
   existing canary machinery — the same seam ``WeightPublisher`` drives);
   after ``canary_ticks`` the fleet-level SLO comparison decides
   promote-all (``swap_params`` on every other live cell) or rollback +
   quarantine-the-version (``publish()`` refuses it thereafter).
   ``scale_up()/scale_down()`` spin an ENTIRE cell up or down through the
   existing planner-validated engine construction path rather than
   resizing one mesh.

Deterministic chaos points (chaos.py): ``cell_crash`` hard-kills a cell
mid-trace (the drain path's game day), ``cell_partition`` makes a cell
unreachable for ``extra["delay_ticks"]`` ticks (it keeps ticking; its rows
surface on heal), ``router_heartbeat`` skips one health pass.

Off by default: nothing constructs a router unless you do —
``Accelerator.build_fleet_router`` or this module directly. ``make
fleet-smoke`` is the game-day gate.

Usage::

    from accelerate_tpu import FleetConfig, FleetRouter

    router = FleetRouter({"cell0": engine0, "cell1": engine1},
                         FleetConfig(), chaos=injector)
    rid = router.submit(prompt, client_request_id="req-0",
                        session_id="sess-7")
    while router.pending:
        router.tick()
        for row in router.poll():
            ...   # row["cell"], row["spilled"], row["drained_from"]
"""

from __future__ import annotations

import time
import zlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .journal import RequestJournal
from .logging import get_logger
from .serving import timing_row_keys
from .utils.constants import (
    CELL_DEAD_EXIT_CODE,
    FLEET_DEGRADED_EXIT_CODE,
)

logger = get_logger(__name__)

__all__ = ["FleetConfig", "FleetRouter", "FleetDegradedError", "CELL_STATES"]

#: Legal cell health classifications, healthiest first.
CELL_STATES = ("healthy", "degraded", "draining", "dead")

# Default partition length (router ticks) when a cell_partition schedule
# entry carries no ``delay_ticks``.
_DEFAULT_PARTITION_TICKS = 2


def _log_ok() -> bool:
    from .state import PartialState

    return bool(PartialState._shared_state)


class FleetDegradedError(RuntimeError):
    """No healthy cell remains to route or drain onto. Front-ends exit
    ``FLEET_DEGRADED_EXIT_CODE`` (81): more capacity — not a faster
    restart — is the fix, so the supervisor relaunches WITH backoff."""

    exit_code = FLEET_DEGRADED_EXIT_CODE


_MASK = (1 << 64) - 1


def _affinity_hash(key: str) -> int:
    """Session-affinity hash: crc32 -> splitmix64 finalizer. Deterministic
    across processes and platforms (never Python's randomized ``hash``),
    so a seeded run routes identically on replay."""
    x = (zlib.crc32(str(key).encode("utf-8")) + 0x9E3779B97F4A7C15) & _MASK
    z = x
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK
    return (z ^ (z >> 31)) & _MASK


@dataclass
class FleetConfig:
    """Fleet-router knobs.

    - ``max_idle_ticks`` — a cell with work pending whose progress marker
      holds still this many consecutive router ticks is classified dead
      (and drained).
    - ``queue_depth_band`` — a cell whose rolling queue-depth p95 exceeds
      this spills new admissions to the least-loaded in-band cell; when
      EVERY cell breaches, the router sheds.
    - ``canary_ticks`` — minimum router ticks a cell-granular publish
      canary window lasts before the promote/rollback decision.
    - ``min_canary_cohort`` — minimum terminal events the canary cell's
      cohort needs before the decision (first-dispatch noise must not
      decide a rollback).
    - ``slo_tolerance`` — the canary cell's ok-ratio may trail the fleet
      baseline by this much and still promote.
    """

    max_idle_ticks: int = 8
    queue_depth_band: float = 16.0
    canary_ticks: int = 8
    min_canary_cohort: int = 4
    slo_tolerance: float = 0.05

    def __post_init__(self):
        if int(self.max_idle_ticks) < 1:
            raise ValueError(
                f"max_idle_ticks must be >= 1, got {self.max_idle_ticks}")
        if float(self.queue_depth_band) <= 0.0:
            raise ValueError(
                f"queue_depth_band must be > 0, got {self.queue_depth_band}")
        if int(self.canary_ticks) < 1:
            raise ValueError(
                f"canary_ticks must be >= 1, got {self.canary_ticks}")
        if int(self.min_canary_cohort) < 1:
            raise ValueError(
                f"min_canary_cohort must be >= 1, got {self.min_canary_cohort}")
        if not 0.0 <= float(self.slo_tolerance) < 1.0:
            raise ValueError(
                f"slo_tolerance must be in [0, 1), got {self.slo_tolerance}")


class _Cell:
    """One registered engine plus the router's health bookkeeping for it."""

    __slots__ = ("name", "index", "engine", "journal_dir", "draining",
                 "dead", "death_class", "died_tick", "partitioned_until",
                 "last_marker", "idle_ticks", "pad_token_id")

    def __init__(self, name: str, index: int, engine):
        self.name = name
        self.index = index
        self.engine = engine
        self.journal_dir = engine.journal.dir
        self.draining = False
        self.dead = False
        self.death_class: Optional[str] = None
        self.died_tick: Optional[int] = None
        self.partitioned_until = -1
        self.last_marker = None
        self.idle_ticks = 0
        self.pad_token_id = int(engine.pad_token_id)

    def state(self, tick: int) -> str:
        if self.dead:
            return "dead"
        if self.draining:
            return "draining"
        if self.partitioned_until > tick:
            return "degraded"
        return "healthy"


class FleetRouter:
    """Session-affinity router + health/failover control plane over a
    registry of journaled serving cells. See the module docstring for the
    four legs; every public method is host-side bookkeeping — the router
    never touches device state, so the per-cell zero-recompile invariant
    (one decode executable, zero steady recompiles) is untouched.

    ``cells`` is a ``{name: engine}`` mapping or a list of engines
    (auto-named ``cell0..cellN``); every engine must have a journal
    attached — a cell without a WAL cannot be drained, which defeats the
    point of a fleet."""

    def __init__(self, cells, config: Optional[FleetConfig] = None, *,
                 chaos=None, telemetry=None, tracing=None):
        self.config = config if config is not None else FleetConfig()
        self.chaos = chaos
        self.telemetry = telemetry
        self.tracing = tracing
        if not isinstance(cells, dict):
            cells = {f"cell{i}": eng for i, eng in enumerate(cells)}
        if not cells:
            raise ValueError("a fleet needs at least one cell")
        self._cells: dict[str, _Cell] = {}
        for name, engine in cells.items():
            self._register(str(name), engine)
        self._ticks = 0
        self._next_rid = 0
        # Router-level request book: rid -> routing record; cid -> rid for
        # idempotency; (cell, engine rid) -> rid for poll translation.
        self._requests: dict[int, dict] = {}
        self._cids: dict[str, int] = {}
        self._by_cell: dict[tuple[str, int], int] = {}
        self._rows: dict[int, dict] = {}
        self._finished: list[dict] = []
        # Journals this router adopted from dead cells. Held until close():
        # a relaunched cell supervisor must start a FRESH journal dir — its
        # old requests already live on the survivors.
        self._adopted: list[RequestJournal] = []
        self._publish: Optional[dict] = None
        self._quarantined: set[int] = set()
        self._c = {
            "submitted": 0, "deduped": 0, "routed_affinity": 0,
            "routed_spilled": 0, "shed": 0, "completed": 0, "ok": 0,
            "drains": 0, "drained_cached": 0, "drained_resubmitted": 0,
            "publishes": 0, "promoted": 0, "rolled_back": 0,
            "scale_ups": 0, "scale_downs": 0, "heartbeat_skips": 0,
        }
        self._drain_last_s: Optional[float] = None
        if self.tracing is not None:
            self.tracing.register_gauges("fleet", self.stats)
        self._hub = (getattr(self.tracing, "hub", None)
                     or getattr(self.telemetry, "hub", None))
        if self._hub is not None:
            if self.tracing is None:
                self._hub.register_provider("fleet", self.stats,
                                            replace=True)
            self._hub.register_slo("fleet_availability", 0.99)

    def _register(self, name: str, engine) -> None:
        if name in self._cells:
            raise ValueError(f"cell {name!r} is already registered")
        if engine.journal is None:
            raise ValueError(
                f"cell {name!r} has no journal attached — set "
                "ServingConfig.journal_dir (one directory per cell); an "
                "unjournaled cell cannot be drained after a crash"
            )
        self._cells[name] = _Cell(name, len(self._cells), engine)

    # -- registry views ----------------------------------------------------

    def cell_states(self) -> dict[str, str]:
        """``{name: healthy|degraded|draining|dead}`` right now."""
        return {n: c.state(self._ticks) for n, c in sorted(self._cells.items())}

    def _routable(self) -> list[_Cell]:
        """Cells eligible for NEW admissions, in deterministic name order:
        healthy only — degraded (partitioned) cells are unreachable,
        draining cells are on their way out, dead cells are gone."""
        return [c for _, c in sorted(self._cells.items())
                if c.state(self._ticks) == "healthy"]

    def _alive(self) -> list[_Cell]:
        return [c for _, c in sorted(self._cells.items()) if not c.dead]

    @property
    def pending(self) -> int:
        """Router-level requests not yet terminally resolved."""
        return sum(1 for rid in self._requests if rid not in self._rows)

    # -- leg 2: routing + spillover ---------------------------------------

    def _breaches(self, cell: _Cell) -> bool:
        qd = cell.engine.window_stats()["queue_depth_p95"]
        return qd is not None and qd > float(self.config.queue_depth_band)

    def _route(self, key: str) -> tuple[Optional[_Cell], bool]:
        """The tick-deterministic routing decision: (cell, spilled) — or
        ``(None, False)`` when every routable cell breaches its band (the
        caller sheds). Affinity first; spillover to the least-loaded
        in-band cell only when the affinity target breaches."""
        routable = self._routable()
        if not routable:
            raise FleetDegradedError(
                "no healthy cell to route onto — "
                f"states: {self.cell_states()}"
            )
        target = routable[_affinity_hash(key) % len(routable)]
        if not self._breaches(target):
            return target, False
        in_band = [c for c in routable if c is not target
                   and not self._breaches(c)]
        if not in_band:
            return None, False
        # Least-loaded by the same deterministic signal the breach test
        # reads (queue-depth p95 is integer per-tick samples, never a
        # wall-clock latency), name-tiebroken.
        spill = min(in_band, key=lambda c: (
            c.engine.window_stats()["queue_depth_p95"] or 0.0, c.name))
        return spill, True

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               rng: Optional[jax.Array] = None,
               deadline_s: Optional[float] = None,
               client_request_id: Optional[str] = None,
               session_id: Optional[str] = None) -> int:
        """Route one request onto a cell; returns a ROUTER-level id whose
        row lands in :meth:`poll` with ``cell``/``spilled``/
        ``drained_from`` provenance on top of the engine row. ``session_id``
        pins the affinity hash (defaults to ``client_request_id``, then the
        router id — so anonymous requests still spread deterministically).
        Duplicate ``client_request_id`` submits dedupe fleet-wide, even
        when the original landed on a cell that has since died."""
        cid = (str(client_request_id)
               if client_request_id is not None else None)
        if cid is not None and cid in self._cids:
            self._c["deduped"] += 1
            rid = self._cids[cid]
            row = self._rows.get(rid)
            if row is not None:
                self._finished.append(dict(row))
            return rid
        rid = self._next_rid
        self._next_rid += 1
        # The engine-level idempotency key: ALWAYS set, so a dead cell's
        # journal can name its in-flight requests for cross-cell resubmit.
        eng_cid = cid if cid is not None else f"fleet-{rid}"
        key = session_id if session_id is not None else eng_cid
        cell, spilled = self._route(str(key))
        self._c["submitted"] += 1
        if cell is None:
            # Every cell breaches: shed at the router, poll-row shaped like
            # an engine shed (prompt + pad to budget) so callers see ONE
            # row schema.
            self._c["shed"] += 1
            prompt_arr = np.asarray(prompt, np.int32).reshape(-1)
            budget = int(max_new_tokens) if max_new_tokens is not None else 0
            pad = self._alive()[0].pad_token_id if self._alive() else 0
            row = {
                "id": rid, "status": "shed",
                "tokens": np.concatenate([
                    prompt_arr,
                    np.full((budget,), pad, np.int32)]),
                "new_tokens": 0, "ttft_s": None, "tpot_s": None,
                "weights_version": None, "attempt": 1, "recovered": False,
                "drafted": 0, "accepted": 0, **timing_row_keys(),
                "cell": None, "spilled": False, "drained_from": None,
            }
            self._requests[rid] = {"cid": eng_cid, "cell": None,
                                   "eng_rid": None, "spilled": False,
                                   "drained_from": None, "session": str(key)}
            if cid is not None:
                self._cids[cid] = rid
            self._rows[rid] = row
            self._finished.append(dict(row))
            self._c["completed"] += 1
            return rid
        eng_rid = cell.engine.submit(
            prompt, max_new_tokens=max_new_tokens, rng=rng,
            deadline_s=deadline_s, client_request_id=eng_cid)
        self._c["routed_spilled" if spilled else "routed_affinity"] += 1
        self._requests[rid] = {"cid": eng_cid, "cell": cell.name,
                               "eng_rid": eng_rid, "spilled": spilled,
                               "drained_from": None, "session": str(key)}
        self._cids[eng_cid] = rid
        self._by_cell[(cell.name, eng_rid)] = rid
        return rid

    # -- the tick loop -----------------------------------------------------

    def tick(self) -> None:
        """One router heartbeat: draw chaos, tick every live cell, collect
        reachable cells' rows, reclassify health (idle-death detection,
        partition healing, drain retirement), and poll any open publish
        window. Deterministic: every decision is a function of the tick
        counter and journaled/windowed state, never wall-clock."""
        t = self._ticks
        ch = self.chaos
        heartbeat_skip = False
        if ch is not None:
            f = ch.draw("router_heartbeat", t)
            if f is not None:
                heartbeat_skip = True
                self._c["heartbeat_skips"] += 1
        for cell in self._alive():
            if ch is None:
                continue
            f = ch.draw("cell_partition", t, unit=cell.index)
            if f is not None:
                ticks = int((f.extra or {}).get(
                    "delay_ticks", _DEFAULT_PARTITION_TICKS))
                cell.partitioned_until = max(cell.partitioned_until,
                                             t + ticks)
                self._event("fleet_cell_partition", cell=cell.name,
                            tick=t, heal_tick=cell.partitioned_until)
            f = ch.draw("cell_crash", t, unit=cell.index)
            if f is not None:
                self._kill_cell(cell, "cell-dead",
                                reason="injected cell_crash")
        for cell in self._alive():
            try:
                cell.engine.tick()
            except Exception as e:  # a cell death must not kill the fleet
                self._kill_cell(cell, "cell-dead",
                                reason=f"engine tick raised: {e}")
        for cell in self._alive():
            if cell.partitioned_until > t:
                continue  # unreachable: its rows surface on heal
            self._collect(cell)
        if not heartbeat_skip:
            self._health_pass(t)
        self._publish_poll()
        self._ticks += 1

    def _collect(self, cell: _Cell) -> None:
        for row in cell.engine.poll():
            rid = self._by_cell.get((cell.name, row["id"]))
            if rid is None:
                continue  # not routed through this router
            rec = self._requests[rid]
            out = dict(row)
            out["id"] = rid
            out["cell"] = cell.name
            out["spilled"] = rec["spilled"]
            out["drained_from"] = rec["drained_from"]
            if rec["drained_from"] is not None:
                out["recovered"] = True
            self._rows[rid] = out
            self._finished.append(dict(out))
            self._c["completed"] += 1
            if out["status"] == "ok":
                self._c["ok"] += 1
            if self._hub is not None:
                self._hub.observe_slo("fleet_availability",
                                      out["status"] == "ok")

    def poll(self) -> list[dict]:
        """Finished rows since the last call — the engine poll-row schema
        plus ``cell`` (where it executed), ``spilled`` (routed off its
        affinity target), ``drained_from`` (the dead cell it was drained
        from, else None)."""
        out = self._finished
        self._finished = []
        return out

    # -- leg 1: health ----------------------------------------------------

    def _health_pass(self, t: int) -> None:
        for cell in self._alive():
            if cell.partitioned_until == t:
                self._event("fleet_cell_healed", cell=cell.name, tick=t)
            marker = cell.engine._progress_marker()
            if cell.engine.pending > 0 and marker == cell.last_marker:
                cell.idle_ticks += 1
            else:
                cell.idle_ticks = 0
            cell.last_marker = marker
            if cell.idle_ticks >= int(self.config.max_idle_ticks):
                self._kill_cell(
                    cell, "cell-dead",
                    reason=f"no progress for {cell.idle_ticks} ticks "
                           f"with {cell.engine.pending} pending")
                continue
            if cell.draining and cell.engine.pending == 0:
                self._retire(cell)

    def _event(self, event: str, **fields) -> None:
        if self.telemetry is not None:
            try:
                self.telemetry.record_event(event, **fields)
            except Exception as e:  # observability must never kill routing
                logger.warning_once(f"fleet: telemetry event failed: {e}")

    # -- leg 3: exactly-once cross-cell drain ------------------------------

    def _kill_cell(self, cell: _Cell, death_class: str, *,
                   reason: str) -> None:
        """Declare a cell dead (``EXIT_CODE_TABLE`` class ``cell-dead``,
        exit code ``CELL_DEAD_EXIT_CODE``) and drain its journal onto the
        survivors. The engine object is ABANDONED, not closed — exactly
        what a process death leaves behind: an unsealed ``.open`` segment
        the journal's replay reads anyway."""
        if cell.dead:
            return
        cell.dead = True
        cell.death_class = death_class
        cell.died_tick = self._ticks
        engine, cell.engine = cell.engine, None
        del engine  # abandoned: no close(), no seal — a crash leaves both
        if _log_ok():
            logger.warning(
                "fleet: cell %r is dead at tick %d (%s, exit class %r "
                "code %d) — draining its journal onto survivors",
                cell.name, self._ticks, reason, death_class,
                CELL_DEAD_EXIT_CODE,
            )
        self._event("fleet_cell_dead", cell=cell.name, tick=self._ticks,
                    reason=reason, exit_code=CELL_DEAD_EXIT_CODE)
        self._drain_dead_cell(cell)

    def _drain_dead_cell(self, cell: _Cell) -> None:
        """Replay the dead cell's journal exactly-once onto the survivors:
        terminals -> cached rows (never re-executed), in-flight -> fresh
        submits by ``client_request_id`` on a surviving cell (a recovery,
        not a retry), deadlines re-anchored to charge pre-crash runtime
        but not the outage."""
        t0 = time.perf_counter()
        tr = self.tracing
        span = (tr.begin("fleet", "drain", self._ticks, cell=cell.name)
                if tr is not None else None)
        try:
            jr = RequestJournal.adopt(
                cell.journal_dir,
                f"fleet-router:tick={self._ticks}:cell={cell.name}")
        except Exception:
            if span is not None:
                tr.end(span, self._ticks, error="adoption refused")
            raise
        try:
            records, scan = jr.replay()
        except Exception:
            jr.release_adoption()
            raise
        self._adopted.append(jr)
        admits: dict[int, dict] = {}
        terminals: dict[int, dict] = {}
        last_mono = None
        for rec in records:
            tm = rec.get("t_mono")
            if tm is not None:
                last_mono = tm if last_mono is None else max(last_mono, tm)
            erid = rec.get("rid")
            if erid is None:
                continue
            erid = int(erid)
            if rec.get("t") == "admit":
                admits[erid] = rec
            elif rec.get("t") == "terminal":
                terminals[erid] = rec
        now = time.perf_counter()
        n_cached = n_resubmitted = 0
        # Union, not just admits: the cell's compactor retires the admit of a
        # finished request (its terminal row is self-contained), so a cached
        # reply can survive on disk with no admit record left.
        for erid in sorted(set(admits) | set(terminals)):
            a = admits.get(erid)
            trec = terminals.get(erid)
            cid = a.get("cid") if a is not None else trec.get("cid")
            rid = self._cids.get(str(cid)) if cid is not None else None
            if rid is None:
                continue  # not routed through this router (e.g. warmup)
            if rid in self._rows:
                continue  # already resolved fleet-side
            rec = self._requests[rid]
            if trec is not None:
                # Journaled terminal: re-emit the cached row, provenance'd.
                row = {
                    "id": rid, "status": trec.get("status"),
                    "tokens": np.asarray(trec.get("row", []), np.int32),
                    "new_tokens": int(trec.get("new_tokens", 0)),
                    "ttft_s": trec.get("ttft_s"),
                    "tpot_s": trec.get("tpot_s"),
                    "weights_version": trec.get("weights_version"),
                    "attempt": int(trec.get("attempt", 1)),
                    "recovered": True,
                    "drafted": int(trec.get("drafted", 0)),
                    "accepted": int(trec.get("accepted", 0)),
                    **timing_row_keys(),
                    "cell": cell.name, "spilled": rec["spilled"],
                    "drained_from": cell.name,
                }
                rec["drained_from"] = cell.name
                self._rows[rid] = row
                self._finished.append(dict(row))
                self._c["completed"] += 1
                if row["status"] == "ok":
                    self._c["ok"] += 1
                n_cached += 1
                continue
            # In-flight: resubmit on a surviving cell — same prompt, same
            # per-request rng, same idempotency key, so the replay is
            # bit-equal under equal weights.
            targets = self._routable()
            if not targets:
                for j in self._adopted:
                    j.release_adoption()
                raise FleetDegradedError(
                    f"cell {cell.name!r} died with requests in flight and "
                    "no healthy cell remains to drain onto — states: "
                    f"{self.cell_states()}"
                )
            target = targets[_affinity_hash(rec["session"]) % len(targets)]
            try:
                rng = jax.random.wrap_key_data(
                    jnp.asarray(a["rng"], jnp.uint32))
            except Exception:
                rng = jax.random.key(0)
            dl = a.get("deadline_s")
            remaining = None
            if dl is not None:
                elapsed = 0.0
                if last_mono is not None and a.get("t_mono") is not None:
                    # Pre-crash runtime in the DEAD cell's own monotonic
                    # epoch: charge what it actually ran, not the outage.
                    elapsed = max(0.0, float(last_mono) - float(a["t_mono"]))
                remaining = max(0.0, float(dl) - elapsed)
            new_erid = target.engine.submit(
                np.asarray(a["tokens"], np.int32),
                max_new_tokens=int(a["budget"]), rng=rng,
                deadline_s=remaining, client_request_id=str(cid))
            rec["cell"] = target.name
            rec["eng_rid"] = new_erid
            rec["drained_from"] = cell.name
            self._by_cell[(target.name, new_erid)] = rid
            n_resubmitted += 1
        self._drain_last_s = time.perf_counter() - t0
        self._c["drains"] += 1
        self._c["drained_cached"] += n_cached
        self._c["drained_resubmitted"] += n_resubmitted
        if _log_ok():
            logger.warning(
                "fleet: drained cell %r in %.3fs — %d terminals re-emitted "
                "from cache, %d in-flight resubmitted (%d journal records, "
                "%d segments)", cell.name, self._drain_last_s, n_cached,
                n_resubmitted, scan["records"], scan["segments"],
            )
        self._event("fleet_cell_drained", cell=cell.name,
                    seconds=self._drain_last_s, cached=n_cached,
                    resubmitted=n_resubmitted)
        if span is not None:
            tr.end(span, self._ticks, cached=n_cached,
                   resubmitted=n_resubmitted)

    # -- leg 4: cell-granular lifecycle ------------------------------------

    def publish(self, params, *, weights_version: int) -> dict:
        """Start a CELL-granular canary: the (deterministically chosen)
        canary cell binds every one of its new admissions to the candidate
        (``fraction=1.0`` through the engine's own canary machinery — the
        same seam ``WeightPublisher`` drives for request-granular canaries)
        while the rest of the fleet serves the old version. The decision
        lands in :meth:`tick` after ``canary_ticks``: promote-all, or
        rollback + quarantine the version. A quarantined version is
        refused here ever after."""
        v = int(weights_version)
        if v in self._quarantined:
            raise ValueError(
                f"weights_version {v} is quarantined — a cell canary "
                "rolled it back; publish a new version instead")
        if self._publish is not None:
            raise ValueError(
                f"a fleet publish (version {self._publish['version']}) is "
                "already in flight")
        routable = self._routable()
        if not routable:
            raise FleetDegradedError(
                f"no healthy cell to canary on — states: {self.cell_states()}")
        canary = routable[0]  # deterministic: lowest name
        canary.engine.begin_canary(params, weights_version=v, fraction=1.0)
        self._publish = {"version": v, "cell": canary.name, "params": params,
                         "started_tick": self._ticks}
        self._c["publishes"] += 1
        self._event("fleet_publish_begin", version=v, cell=canary.name,
                    tick=self._ticks)
        return {"version": v, "canary_cell": canary.name}

    def _fleet_baseline_ok(self, exclude: str) -> Optional[float]:
        """Fleet SLO baseline: the UNWEIGHTED mean of per-cell ok-ratios
        over the other live cells' rolling windows — per-cell on purpose,
        so one sick cell counts as one cell instead of hiding under a big
        healthy cell's request volume."""
        ratios = []
        for cell in self._alive():
            if cell.name == exclude:
                continue
            w = cell.engine.window_stats()
            if w["requests"]:
                ratios.append(w["ok"] / w["requests"])
        return sum(ratios) / len(ratios) if ratios else None

    def _publish_poll(self) -> None:
        p = self._publish
        if p is None:
            return
        cell = self._cells.get(p["cell"])
        if cell is None or cell.dead:
            # The canary cell died mid-window: the candidate was never
            # fleet-visible, so just end the window (no quarantine — the
            # VERSION was not convicted, the cell was).
            self._publish = None
            self._c["rolled_back"] += 1
            self._event("fleet_publish_aborted", version=p["version"],
                        cell=p["cell"], tick=self._ticks)
            return
        if self._ticks - p["started_tick"] < int(self.config.canary_ticks):
            return
        co = cell.engine.cohort_stats(p["version"])
        if co is None or co["completed"] < int(self.config.min_canary_cohort):
            return  # keep the window open until the cohort is decidable
        canary_ok = co["ok"] / co["completed"]
        baseline = self._fleet_baseline_ok(exclude=cell.name)
        promote = (baseline is None
                   or canary_ok + float(self.config.slo_tolerance)
                   >= baseline)
        if promote:
            cell.engine.promote_canary()
            for other in self._alive():
                if other.name != cell.name:
                    other.engine.swap_params(
                        p["params"], weights_version=p["version"])
            self._c["promoted"] += 1
            self._event("fleet_publish_promoted", version=p["version"],
                        canary_ok=round(canary_ok, 4),
                        baseline=(round(baseline, 4)
                                  if baseline is not None else None))
        else:
            cell.engine.rollback_canary()
            self._quarantined.add(p["version"])
            self._c["rolled_back"] += 1
            if _log_ok():
                logger.warning(
                    "fleet: version %d rolled back on canary cell %r "
                    "(ok %.3f vs fleet baseline %.3f) — version "
                    "QUARANTINED fleet-wide", p["version"], cell.name,
                    canary_ok, baseline,
                )
            self._event("fleet_publish_rolled_back", version=p["version"],
                        canary_ok=round(canary_ok, 4),
                        baseline=round(baseline, 4))
        self._publish = None

    def scale_up(self, name: str, engine=None, *, factory=None) -> None:
        """Register a whole new cell. Pass a constructed (journaled,
        ideally warmed) engine, or a zero-arg ``factory`` so construction
        — which runs the existing planner-validated
        ``build_serving_engine`` path — happens inside the router's
        accounting."""
        if engine is None:
            if factory is None:
                raise ValueError("scale_up needs an engine or a factory")
            engine = factory()
        self._register(str(name), engine)
        self._c["scale_ups"] += 1
        self._event("fleet_scale_up", cell=str(name), tick=self._ticks)

    def scale_down(self, name: str) -> None:
        """Drain a whole cell out: no new admissions from now on; once its
        in-flight work finishes the engine is closed and deregistered at
        the end of a tick."""
        cell = self._cells.get(str(name))
        if cell is None or cell.dead:
            raise ValueError(f"no live cell named {name!r}")
        cell.draining = True
        self._event("fleet_scale_down", cell=str(name), tick=self._ticks)

    def _retire(self, cell: _Cell) -> None:
        self._collect(cell)  # anything its last tick finished
        cell.engine.close()
        del self._cells[cell.name]
        self._c["scale_downs"] += 1
        self._event("fleet_cell_retired", cell=cell.name, tick=self._ticks)

    # -- reporting / lifecycle --------------------------------------------

    def stats(self) -> dict:
        """The ``fleet`` telemetry block (pinned by tests/test_schemas.py;
        the MetricsHub renders it under ``accelerate_tpu_fleet_*``)."""
        states = self.cell_states()
        per_cell = {}
        for name, cell in sorted(self._cells.items()):
            if cell.dead:
                per_cell[name] = {
                    "state": "dead", "pending": None,
                    "weights_version": None, "queue_depth_p95": None,
                    "requests_completed": None, "decode_executables": None,
                    "steady_recompiles": None,
                }
                continue
            eng = cell.engine
            per_cell[name] = {
                "state": states[name],
                "pending": eng.pending,
                "weights_version": eng.weights_version,
                "queue_depth_p95": eng.window_stats()["queue_depth_p95"],
                "requests_completed": eng._stats["completed"],
                "decode_executables": eng.executable_counts()["decode"],
                "steady_recompiles": eng._stats["steady_recompiles"],
            }
        return {
            "cells": len(self._cells),
            "healthy": sum(1 for s in states.values() if s == "healthy"),
            "degraded": sum(1 for s in states.values() if s == "degraded"),
            "draining": sum(1 for s in states.values() if s == "draining"),
            "dead": sum(1 for s in states.values() if s == "dead"),
            "ticks": self._ticks,
            "submitted": self._c["submitted"],
            "deduped": self._c["deduped"],
            "routed_affinity": self._c["routed_affinity"],
            "routed_spilled": self._c["routed_spilled"],
            "shed": self._c["shed"],
            "completed": self._c["completed"],
            "ok": self._c["ok"],
            "heartbeat_skips": self._c["heartbeat_skips"],
            "drains": self._c["drains"],
            "drained_cached": self._c["drained_cached"],
            "drained_resubmitted": self._c["drained_resubmitted"],
            "drain_last_s": (round(self._drain_last_s, 6)
                             if self._drain_last_s is not None else None),
            "publishes": self._c["publishes"],
            "promoted": self._c["promoted"],
            "rolled_back": self._c["rolled_back"],
            "quarantined_versions": sorted(self._quarantined),
            "scale_ups": self._c["scale_ups"],
            "scale_downs": self._c["scale_downs"],
            "per_cell": per_cell,
        }

    def close(self) -> None:
        """Close every live cell's engine and release the dead cells'
        adopted journals (a relaunching supervisor may take them over
        from here — the drained requests dedupe by their journaled
        ``client_request_id`` terminal rows)."""
        for cell in self._alive():
            cell.engine.close()
        for jr in self._adopted:
            jr.release_adoption()
        self._adopted.clear()
