"""Silent-data-corruption (SDC) sentinel: cross-replica integrity voting,
redundant-compute probes, and device quarantine with shrink-and-resume.

Every defense in fault_tolerance.py / serving.py / journal.py triggers on
*loud* failures — nonfinite grads, dead hosts, torn writes, hung ranks. The
failure class that actually poisons fleet-scale runs is silent: a chip that
computes finite-but-WRONG values, invisible to NaN sentinels, watchdogs,
and checksums-of-bytes-at-rest alike. This module closes it with the
redundancy the stack already carries:

- **Cross-replica integrity voting** (:class:`SDCSentinel`). Every prepared
  train step fingerprints its new params + grad norm with a cheap fused
  reduction (:func:`integrity_digest`) that rides the step's existing
  metrics fetch, observed ONE STEP LAGGED like the divergence sentinel so
  the host never stalls dispatch. In a multi-process gang each process
  fetches the digest from its own local silicon — dp replication makes the
  value redundantly computed per host — so every ``vote_every`` steps the
  digests are allgathered (``PartialState.allgather_host_floats``) and
  majority-voted bit-wise (:func:`vote`). A disagreeing replica is finite
  and therefore invisible to the PR 3 sentinel; the vote still names it.
- **Redundant-compute probe**. On a vote mismatch the gang re-runs the
  SAME jitted step on a golden ``(state, batch)`` snapshot captured (and
  pre-compiled) at warmup, comparing each rank's digest bit-wise to its
  stored golden value. The jitted step carries gang collectives, so the
  probe is collective too — every rank probes together (which is also the
  2-replica no-majority fallback: with no majority to trust, everyone
  proves its own silicon). A clean probe classifies the episode
  *transient* (a flipped bit in flight — repair in place: roll back to the
  newest verified checkpoint via PR 3's machinery, or broadcast params
  from a majority replica; the resumed run replays bit-equal to
  fault-free). A probe that REPRODUCES the corruption on known-good inputs
  convicts the silicon — *sticky*: the host is quarantined on disk
  (``sdc_quarantine.json``, persisted across restarts) and the process
  exits :data:`~accelerate_tpu.utils.constants.SDC_EXIT_CODE` (79);
  ``classify_exit`` maps it and the :class:`GangSupervisor` relaunches
  SHRUNK through the existing ``shrink_world_size`` path with zero
  backoff, excluding the convicted host.
- **Serving-side decode canary** (:class:`DecodeCanary`). A periodic
  known-prompt probe request rides the engine's own slot machinery, its
  output tokens compared bit-wise against a golden row captured at canary
  warmup. The probe is suppressed from the journal and from ``poll()``
  exactly like ``warmup()``'s synthetic request. A mismatch quarantines
  the decode device through the autoscaler's existing ``mark_device_dead``
  correctness-shrink.
- **Chaos closes the loop**: the ``bit_flip`` kind (chaos.py) at
  ``train_step`` / ``decode_tick`` injects finite host-side corruption —
  ``Fault.extra`` picks ``mode`` (``"transient"`` | ``"sticky"``), the
  mantissa ``bit``, and the target rank rides the schedule entry's
  ``unit``. Point-name-keyed draws mean existing seeds' schedules never
  move, and ``make sdc-smoke`` replays detect→classify→repair and
  detect→quarantine→shrink-relaunch bit-identically, twice.

Off by default: nothing here runs unless ``FaultToleranceKwargs(sdc=...)``
arms the sentinel or a :class:`DecodeCanary` is attached to an engine;
every hook in the hot paths is a single ``is None`` check.

Usage (training)::

    accelerator = Accelerator(
        project_config=ProjectConfiguration(project_dir="runs/exp1",
                                            automatic_checkpoint_naming=True),
        kwargs_handlers=[FaultToleranceKwargs(
            sdc=dict(vote_every=8, repair="rollback"))],
    )

Usage (serving)::

    canary = DecodeCanary(engine, every=64, autoscaler=controller)
    canary.warmup()            # capture the golden row (after engine.warmup())
    # ... engine.tick() drives probes automatically; engine.stats()["sdc"]
"""

from __future__ import annotations

import json
import logging
import os
import platform
import time
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import numpy as np

from .utils.constants import SDC_EXIT_CODE, SDC_QUARANTINE_FILE

logger = logging.getLogger(__name__)

__all__ = [
    "SDCConfig",
    "SDCError",
    "SDCSentinel",
    "DecodeCanary",
    "integrity_digest",
    "vote",
    "flip_float32",
    "load_quarantine",
    "record_quarantine",
]


class SDCError(RuntimeError):
    """Raised when SDC handling cannot proceed (e.g. a transient repair
    found no verified checkpoint to restore). Exits
    :data:`~accelerate_tpu.utils.constants.SDC_EXIT_CODE` under a
    supervised launch."""

    exit_code = SDC_EXIT_CODE


@dataclass
class SDCConfig:
    """Knobs for the silent-data-corruption sentinel. Accepted by
    ``FaultToleranceKwargs(sdc=...)`` as an instance or a plain dict of
    these fields.

    - ``vote_every``: steps between cross-replica digest votes (every step
      still computes the digest — it rides the fetch — but the allgather
      only runs on vote steps). Voting needs >= 2 processes; single-process
      runs keep the digest plumbing live and skip the vote.
    - ``repair``: what a *transient* verdict does — ``"rollback"`` restores
      the newest verified checkpoint (PR 3 machinery; the replay is
      bit-equal to fault-free), ``"broadcast"`` re-syncs params from the
      lowest majority replica in place (falls back to rollback when the
      vote had no majority to trust).
    - ``max_repairs``: transient repairs before the NEXT flag on this rank
      escalates to a sticky conviction — a rank that keeps flagging is
      suspect hardware even if each probe comes back clean.
    - ``probe``: ``"golden"`` captures a golden (state, batch) snapshot at
      the first prepared step and pre-compiles the probe (host-memory cost:
      one state copy); ``"off"`` skips the snapshot — vote mismatches then
      classify as transient without a probe (no conviction possible).
    - ``bit``: which float32 mantissa bit the chaos ``bit_flip`` flips by
      default (< 23 keeps the digest finite — the whole point of SDC; the
      vote transport is float32 precision, so the flip lives there too).
    """

    vote_every: int = 8
    repair: str = "rollback"
    max_repairs: int = 2
    probe: str = "golden"
    bit: int = 5

    def __post_init__(self):
        self.vote_every = int(self.vote_every)
        if self.vote_every < 1:
            raise ValueError(f"vote_every must be >= 1, got {self.vote_every}")
        if self.repair not in ("rollback", "broadcast"):
            raise ValueError(
                f"repair must be 'rollback' or 'broadcast', got {self.repair!r}")
        if self.probe not in ("golden", "off"):
            raise ValueError(f"probe must be 'golden' or 'off', got {self.probe!r}")
        self.max_repairs = int(self.max_repairs)
        if self.max_repairs < 0:
            raise ValueError(f"max_repairs must be >= 0, got {self.max_repairs}")
        self.bit = int(self.bit)
        if not 0 <= self.bit < 23:
            raise ValueError(
                f"bit must be a float32 mantissa bit (0..22), got {self.bit}")


# ----------------------------------------------------------------------
# Pure pieces: digest, vote, bit flip — unit-testable without a mesh.
# ----------------------------------------------------------------------


def integrity_digest(params, grad_norm):
    """One cheap fused fingerprint of the step's outputs, built INSIDE the
    jitted step so it folds into the existing metrics fetch: a per-leaf
    abs-sum, each weighted by a small leaf-index-dependent factor (so two
    leaves swapping values cannot cancel), plus the grad norm. Replicated
    execution computes it redundantly per host — the redundancy the vote
    compares."""
    import jax
    import jax.numpy as jnp

    acc = jnp.asarray(0.0, jnp.float32)
    for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
        if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            continue
        w = jnp.asarray(float((i % 31) + 1), jnp.float32)
        acc = acc + w * jnp.sum(jnp.abs(leaf)).astype(jnp.float32)
    return acc + jnp.asarray(grad_norm, jnp.float32)


def vote(digests) -> dict:
    """Majority-vote a table of per-replica digests, compared BIT-wise
    (float64 byte patterns — silent corruption is exact or it isn't there).

    Returns ``{"agree", "has_majority", "majority_ranks", "outliers"}``:

    - all equal → ``agree=True``, no outliers;
    - a strict majority (> n/2) agrees → the disagreeing ranks are the
      outliers;
    - NO strict majority (the 2-replica split, or a 3-way tie) → every rank
      is an outlier: nobody can be trusted by counting, so the caller falls
      back to the redundant-compute probe on all of them.
    """
    vals = [np.float64(v) for v in digests]
    n = len(vals)
    groups: dict[bytes, list[int]] = {}
    for i, v in enumerate(vals):
        groups.setdefault(v.tobytes(), []).append(i)
    if len(groups) == 1:
        return {"agree": True, "has_majority": True,
                "majority_ranks": list(range(n)), "outliers": []}
    best = max(groups.values(), key=lambda g: (len(g), -g[0]))
    if 2 * len(best) > n:
        return {"agree": False, "has_majority": True,
                "majority_ranks": list(best),
                "outliers": sorted(set(range(n)) - set(best))}
    return {"agree": False, "has_majority": False,
            "majority_ranks": [], "outliers": list(range(n))}


def flip_float32(value: float, bit: int = 5) -> float:
    """Flip one mantissa bit of ``value``'s float32 representation — the
    canonical silent corruption: finite (bit < 23 never touches the
    exponent/sign), wrong, and invisible to every nonfinite check. Float32
    space on purpose: the digest comes out of the jitted step as float32
    and the allgather transport carries float32 precision, so a float64-ulp
    flip would be silently rounded away in flight."""
    a = np.array(np.float32(value))
    a.view(np.int32)[...] ^= np.int32(1) << np.int32(int(bit))
    return float(a)


# ----------------------------------------------------------------------
# Quarantine persistence: a tiny JSON record next to the checkpoints, so
# the exclusion survives the shrink-relaunch and every restart after it.
# ----------------------------------------------------------------------


def _quarantine_path(project_dir: str) -> str:
    return os.path.join(project_dir, SDC_QUARANTINE_FILE)


def load_quarantine(project_dir: Optional[str]) -> dict:
    """Read the quarantine record (``{"hosts": [...]}``); empty when none
    or unreadable — a torn record must never block a relaunch."""
    if not project_dir:
        return {"hosts": []}
    try:
        with open(_quarantine_path(project_dir)) as f:
            rec = json.load(f)
        if isinstance(rec, dict) and isinstance(rec.get("hosts"), list):
            return rec
    except (OSError, ValueError):
        pass
    return {"hosts": []}


def record_quarantine(project_dir: str, entry: dict) -> dict:
    """Append one conviction to the quarantine record, atomically
    (tmp + rename — the same torn-write discipline as the checkpoints)."""
    rec = load_quarantine(project_dir)
    rec["hosts"].append(entry)
    os.makedirs(project_dir, exist_ok=True)
    path = _quarantine_path(project_dir)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=2)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return rec


# ----------------------------------------------------------------------
# Golden snapshot plumbing: host copies of a (possibly multi-process
# sharded) pytree plus the recipe to rebuild bit-identical global arrays
# with the SAME sharding — so the probe reuses the step's executable.
# ----------------------------------------------------------------------


class _Snap(NamedTuple):
    shape: tuple
    dtype: Any
    sharding: Any
    shards: list  # [(device, np.ndarray), ...] — this process's shards


def _snapshot(tree):
    import jax

    def snap(x):
        if not hasattr(x, "addressable_shards"):
            return x  # python scalar / None-like leaf: keep verbatim
        shards = [(s.device, np.asarray(s.data)) for s in x.addressable_shards]
        return _Snap(tuple(x.shape), x.dtype, x.sharding, shards)

    return jax.tree.map(snap, tree)


def _restore(snapped):
    import jax

    def rest(s):
        if not isinstance(s, _Snap):
            return s
        bufs = [jax.device_put(data, dev) for dev, data in s.shards]
        return jax.make_array_from_single_device_arrays(s.shape, s.sharding, bufs)

    return jax.tree.map(rest, snapped,
                        is_leaf=lambda x: isinstance(x, _Snap))


# ----------------------------------------------------------------------
# Training-side sentinel
# ----------------------------------------------------------------------


class SDCSentinel:
    """Owned by the :class:`FaultToleranceManager` when
    ``FaultToleranceKwargs(sdc=...)`` arms it. The manager feeds it the
    lagged step metrics (``observe``); it owns the vote/probe/verdict
    protocol and hands control back for the side effects it cannot take
    alone (the collective rollback repair runs through the manager's PR 3
    machinery)."""

    def __init__(self, manager, config: SDCConfig):
        self.manager = manager
        self.config = config
        self._pending = None  # (digest_arr, tick, slot, flip_fault)
        self._flip = None  # next bit_flip to fold into the observed digest
        self._sticky = False  # injected "bad silicon": probes re-corrupt too
        self._golden = None  # {"step_fn", "state", "batch", "digest"}
        self.repairs_done = 0
        self.peer_quarantined = False  # a PEER was convicted; gang is dying
        self._stats = {
            "digests": 0, "votes": 0, "mismatches": 0,
            "probes": 0, "probes_failed": 0, "repairs": 0, "quarantines": 0,
        }
        hub = getattr(getattr(manager.accelerator, "telemetry", None),
                      "hub", None)
        if hub is not None:
            # The sentinel's tallies on the unified metrics registry
            # (accelerate_tpu_sdc_* gauges — profiler.py MetricsHub).
            hub.register_provider("sdc", self.summary, replace=True)
        # Quarantine record from previous incarnations of this run: the
        # supervisor already shrank past the convicted hosts, this is the
        # persisted audit trail (and what the smoke pins across relaunch).
        self.quarantined_hosts = list(
            load_quarantine(getattr(manager.accelerator, "project_dir", None))
            .get("hosts", []))
        if self.quarantined_hosts:
            logger.warning(
                "sdc: %d host(s) quarantined from earlier incarnations of "
                "this run: %s", len(self.quarantined_hosts),
                [h.get("host") for h in self.quarantined_hosts],
            )

    # -- golden snapshot (warmup) -----------------------------------------

    @property
    def needs_golden(self) -> bool:
        return self.config.probe == "golden" and self._golden is None

    def capture_golden(self, step_fn, state, batch) -> None:
        """Called by the prepared-step wrapper once, before the first real
        step: snapshot (state, batch) to host, then run the probe once —
        recording the golden digest AND pre-compiling the step so steady
        state never recompiles. The probe runs on restored COPIES, so
        buffer donation never touches the live state."""
        self._golden = {
            "step_fn": step_fn,
            "state": _snapshot(state),
            "batch": _snapshot(batch),
            "digest": None,
        }
        self._golden["digest"] = self._run_golden_step()
        logger.info("sdc: golden probe captured (digest=%r)",
                    self._golden["digest"])

    def _run_golden_step(self) -> float:
        g = self._golden
        _, metrics = g["step_fn"](_restore(g["state"]), _restore(g["batch"]))
        return float(np.asarray(metrics["sdc_digest"]))

    # -- chaos hook --------------------------------------------------------

    def note_bit_flip(self, fault) -> None:
        """A ``train_step``/``bit_flip`` draw landed on this rank: corrupt
        the NEXT observed digest (the fault is drawn at the step it
        corrupts; the digest is observed one step lagged). ``sticky`` also
        latches the injected bad-silicon flag so the probe reproduces it."""
        self._flip = fault
        if str((fault.extra or {}).get("mode", "transient")) == "sticky":
            self._sticky = True

    # -- the lagged observe + vote + probe protocol ------------------------

    def observe(self, metrics: Optional[dict], tick: int, slot: int) -> Optional[str]:
        """Called by the manager every step with the just-dispatched step's
        metrics. Swaps the one-step lag, and on vote ticks runs the
        cross-replica protocol. Returns ``"repair"`` when a transient
        corruption needs the manager's repair path; convicts and exits
        (``SDC_EXIT_CODE``) on sticky; ``None`` otherwise."""
        pending, self._pending = self._pending, None
        if metrics is not None and "sdc_digest" in metrics:
            self._pending = (metrics["sdc_digest"], tick, slot, self._flip)
            self._flip = None
        if pending is None:
            return None
        digest_arr, p_tick, p_slot, flip = pending
        try:
            digest = float(np.asarray(digest_arr))
        except Exception:  # an undigestable metric must never kill training
            return None
        self._stats["digests"] += 1
        if flip is not None:
            bit = int((flip.extra or {}).get("bit", self.config.bit))
            digest = flip_float32(digest, bit=bit)
        state = self.manager.accelerator.state
        if state.num_processes < 2:
            return None  # no replicas to vote across
        if p_tick % self.config.vote_every:
            return None
        # Collective: every rank reaches this at the same tick (same loop,
        # same monotonic tick counter — the watchdog heartbeat's argument).
        table = state.allgather_host_floats([digest])
        self._stats["votes"] += 1
        verdict = vote(table[:, 0])
        if verdict["agree"]:
            return None
        self._stats["mismatches"] += 1
        rank = state.process_index
        flagged = rank in verdict["outliers"]
        self.manager._event(
            "sdc_vote_mismatch", tick=p_tick, rank=rank, flagged=flagged,
            has_majority=verdict["has_majority"], outliers=verdict["outliers"],
            digests=[float(v) for v in table[:, 0]],
        )
        logger.warning(
            "sdc: cross-replica digest mismatch at tick %d (outliers %s, "
            "majority=%s) — running the redundant-compute probe.",
            p_tick, verdict["outliers"], verdict["has_majority"],
        )
        # The probe re-runs the jitted step, which carries gang collectives
        # — so EVERY rank probes together (also the no-majority fallback:
        # with nothing to trust by counting, each rank proves its own
        # silicon against its own golden digest).
        failed = self._run_probe()
        if flagged and not failed and self.repairs_done >= self.config.max_repairs:
            # A rank that keeps flagging past the repair budget is suspect
            # hardware even when each individual probe comes back clean.
            failed = True
            logger.error(
                "sdc: rank %d flagged again after %d repair(s) — escalating "
                "to a sticky conviction.", rank, self.repairs_done)
        verdicts = state.allgather_host_floats(
            [1.0 if flagged else 0.0, 1.0 if failed else 0.0])
        sticky_ranks = [i for i in range(verdicts.shape[0])
                        if verdicts[i, 1] > 0.5]
        if sticky_ranks:
            if rank in sticky_ranks:
                self._convict(p_tick)  # never returns
            self.peer_quarantined = True
            self.manager._event(
                "sdc_peer_quarantined", tick=p_tick, ranks=sticky_ranks)
            logger.error(
                "sdc: peer rank(s) %s convicted of sticky corruption — the "
                "supervisor will relaunch the gang shrunk; exit the loop "
                "(ft.sdc.peer_quarantined is set).", sticky_ranks)
            return None
        return "repair"

    def _run_probe(self) -> bool:
        """Re-run the pre-compiled golden step and compare bit-wise to the
        stored golden digest. Returns True when the probe FAILED (the
        corruption reproduces on known-good inputs → sticky silicon)."""
        if self._golden is None or self._golden.get("digest") is None:
            return False  # probe off / not yet captured: cannot convict
        self._stats["probes"] += 1
        d = self._run_golden_step()
        if self._sticky:
            # The injected "bad silicon" corrupts every pass through the
            # chip — exactly what a real sticky fault does to the probe.
            d = flip_float32(d, bit=self.config.bit)
        ok = np.float64(d).tobytes() == np.float64(self._golden["digest"]).tobytes()
        if not ok:
            self._stats["probes_failed"] += 1
            logger.error(
                "sdc: redundant-compute probe FAILED (golden=%r got=%r) — "
                "the corruption reproduces on known-good inputs.",
                self._golden["digest"], d)
        return not ok

    def note_repair(self, mode: str) -> None:
        self.repairs_done += 1
        self._stats["repairs"] += 1
        logger.warning("sdc: transient corruption repaired via %s (%d/%d "
                       "repairs used).", mode, self.repairs_done,
                       self.config.max_repairs)

    def broadcast_params(self, slot: int, majority_ranks: Optional[list] = None):
        """``repair="broadcast"``: re-sync params in place from the lowest
        majority replica (dp replication makes every healthy replica's copy
        identical, so any majority member is a valid source). Returns the
        repaired TrainState, or None when there is no majority to trust
        (caller falls back to rollback)."""
        import jax
        from jax.experimental import multihost_utils

        acc = self.manager.accelerator
        state = acc._train_states[slot]
        src = min(majority_ranks) if majority_ranks else 0
        snapped = _snapshot(state.params)
        host = jax.tree.map(
            lambda s: s.shards[0][1] if isinstance(s, _Snap) else s, snapped,
            is_leaf=lambda x: isinstance(x, _Snap))
        synced = multihost_utils.broadcast_one_to_all(
            host, is_source=acc.process_index == src)
        rebuilt = jax.tree.map(
            lambda s, h: (s._replace(shards=[(d, np.asarray(h)) for d, _ in s.shards])
                          if isinstance(s, _Snap) else h),
            snapped, synced, is_leaf=lambda x: isinstance(x, _Snap))
        new_state = state.replace(params=_restore(rebuilt))
        acc._train_states[slot] = new_state
        return new_state

    # -- conviction --------------------------------------------------------

    def _convict(self, tick: int) -> None:
        """Sticky verdict on THIS rank: quarantine the host on disk, flush
        the post-mortem (telemetry + the injector's fault log), and exit
        ``SDC_EXIT_CODE`` so the supervisor relaunches the gang shrunk."""
        from .chaos import flush_injected_log

        acc = self.manager.accelerator
        self._stats["quarantines"] += 1
        entry = {
            "process_index": int(acc.process_index),
            "host": platform.node(),
            "step": int(np.asarray(acc.step)),
            "tick": int(tick),
            "reason": "redundant-compute probe reproduced the corruption",
            "time": time.time(),
        }
        project_dir = getattr(acc, "project_dir", None)
        if project_dir:
            record_quarantine(project_dir, entry)
        logger.error(
            "sdc: STICKY corruption on rank %d (%s) — quarantined; exiting "
            "%d for a shrunk relaunch.", entry["process_index"],
            entry["host"], SDC_EXIT_CODE)
        self.manager._event("sdc_quarantine", **entry)
        # os._exit skips every atexit/finally: the flight ring, the
        # injector's schedule, and the telemetry summary must reach disk
        # here or the post-mortem loses them (same discipline as
        # dead_host / engine_crash).
        from .profiler import dump_flight

        flush_injected_log(
            self.manager.chaos, getattr(acc, "telemetry", None))
        dump_flight(getattr(acc, "telemetry", None), SDC_EXIT_CODE,
                    reason=f"sticky SDC conviction on rank "
                           f"{entry['process_index']} at step "
                           f"{entry['step']}")
        os._exit(SDC_EXIT_CODE)

    # -- reporting ---------------------------------------------------------

    def summary(self) -> dict:
        """The ``sdc`` telemetry block (pinned in tests/test_schemas.py)."""
        return {
            "vote_every": self.config.vote_every,
            "repair": self.config.repair,
            "digests": self._stats["digests"],
            "votes": self._stats["votes"],
            "mismatches": self._stats["mismatches"],
            "probes": self._stats["probes"],
            "probes_failed": self._stats["probes_failed"],
            "repairs": self._stats["repairs"],
            "quarantines": self._stats["quarantines"],
            "quarantined_hosts": [h.get("host") for h in self.quarantined_hosts],
            "peer_quarantined": self.peer_quarantined,
        }


# ----------------------------------------------------------------------
# Serving-side decode canary
# ----------------------------------------------------------------------


class DecodeCanary:
    """A periodic known-prompt probe through the live engine's own slot
    machinery. ``warmup()`` runs one probe to completion and stores its
    row as the golden; afterwards the engine's tick drives a probe every
    ``every`` ticks, pops its row from the finished queue BEFORE ``poll()``
    can see it (the ``warmup()`` suppression idiom), and compares the
    output tokens bit-wise. A mismatch is silent decode corruption:
    counted, reported through telemetry, and — with an autoscaler attached
    — answered by quarantining the decode device through the existing
    ``mark_device_dead`` correctness-shrink.

    The probe request is journal-suppressed at submit (a journaled probe
    would replay as a phantom request after a crash) and rides a fixed rng
    key, so its tokens are deterministic for fixed weights."""

    _RNG_SEED = 0x5DC  # fixed sampling stream: probe rows must be replayable

    def __init__(self, engine, *, every: int = 64, prompt=None,
                 max_new_tokens: int = 4, autoscaler=None, telemetry=None):
        self.engine = engine
        self.every = max(1, int(every))
        self.max_new_tokens = int(max_new_tokens)
        self.prompt = (np.asarray(prompt, np.int32) if prompt is not None
                       else np.arange(1, 7, dtype=np.int32))
        if self.prompt.ndim != 1 or self.prompt.size == 0:
            raise ValueError("canary prompt must be a non-empty 1-D token row")
        self.autoscaler = autoscaler
        self.telemetry = telemetry
        self._golden: Optional[list] = None
        self._inflight: Optional[int] = None
        self.probe_rids: list[int] = []  # every probe ever submitted (audit)
        self._stats = {"probes": 0, "mismatches": 0, "quarantines": 0,
                       "suppressed_rows": 0}
        engine.attach_sdc_canary(self)

    # -- lifecycle ---------------------------------------------------------

    def warmup(self) -> None:
        """Run one probe to completion and store its row as the golden.
        Call after ``engine.warmup()`` (the ladder must already be
        compiled) and before real traffic."""
        rid = self._submit()
        for _ in range(10_000):
            if self._inflight is None:
                break
            self.engine.tick()  # on_tick() collects the row for us
        if self._inflight is not None:
            self._inflight = None
            raise SDCError(f"canary warmup probe {rid} never completed")
        golden = self._last_row_tokens
        if golden is None:
            raise SDCError(f"canary warmup probe {rid} finished without a row")
        self._golden = golden
        # Warmup rows must not pollute the measured probe counters.
        self._stats["probes"] = 0
        self._stats["suppressed_rows"] = 0
        logger.info("sdc: decode canary armed (golden digest %08x, %d tokens)",
                    self.golden_digest or 0, len(golden))

    @property
    def armed(self) -> bool:
        return self._golden is not None

    @property
    def golden_digest(self) -> Optional[int]:
        if self._golden is None:
            return None
        import zlib

        return zlib.crc32(np.asarray(self._golden, np.int64).tobytes())

    # -- the per-tick hook (called by the engine at the end of its tick) ---

    def on_tick(self) -> None:
        self._last_row_tokens = None
        if self._inflight is not None:
            row = self._pop_row(self._inflight)
            if row is not None:
                self._inflight = None
                self._last_row_tokens = [int(t) for t in
                                         np.asarray(row["tokens"]).ravel()]
                self._stats["probes"] += 1
                if self._golden is not None:
                    self._check(row, self._last_row_tokens)
        if (self._golden is not None and self._inflight is None
                and self.engine._stats["ticks"] % self.every == 0):
            self._submit()

    _last_row_tokens: Optional[list] = None

    def _submit(self) -> int:
        import jax

        eng = self.engine
        # The warmup() idiom: the synthetic probe must reach neither the
        # WAL (phantom replay at recover()) nor poll() (a phantom row).
        jr, eng._journal = eng._journal, None
        try:
            self._inflight = eng.submit(
                self.prompt.copy(), max_new_tokens=self.max_new_tokens,
                rng=jax.random.key(self._RNG_SEED))
        finally:
            eng._journal = jr
        self.probe_rids.append(self._inflight)
        return self._inflight

    def _pop_row(self, rid: int) -> Optional[dict]:
        for row in self.engine._finished:
            if row["id"] == rid:
                self.engine._finished.remove(row)
                self._stats["suppressed_rows"] += 1
                return row
        return None

    def _check(self, row: dict, toks: list) -> None:
        if row["status"] == "ok" and toks == self._golden:
            return
        self._stats["mismatches"] += 1
        import zlib

        got = zlib.crc32(np.asarray(toks, np.int64).tobytes())
        logger.error(
            "sdc: decode canary mismatch (status=%s golden=%08x got=%08x) — "
            "silent decode corruption.", row["status"],
            self.golden_digest or 0, got)
        if self.telemetry is not None:
            try:
                self.telemetry.record_event(
                    "sdc_canary_mismatch", tick=self.engine._stats["ticks"],
                    status=row["status"], golden_digest=self.golden_digest,
                    got_digest=got)
            except Exception:  # observability must never kill serving
                pass
        self._quarantine_decode_device()

    def _quarantine_decode_device(self) -> None:
        if self.autoscaler is None:
            return
        devs = getattr(self.engine, "decode_devices", None)
        if not devs:
            logger.warning(
                "sdc: canary mismatch but the engine exposes no decode "
                "device list — nothing to quarantine.")
            return
        # Without finer attribution the canary convicts the decode slice's
        # lead device; the resize rebuilds the slice without it (and a
        # re-probe on the new layout re-convicts if the bad chip survived).
        dev = devs[0]
        try:
            self.autoscaler.mark_device_dead(dev)
            self._stats["quarantines"] += 1
            logger.error("sdc: decode device %s quarantined via "
                         "mark_device_dead.", dev)
        except Exception as e:
            logger.warning(f"sdc: mark_device_dead({dev}) failed: {e}")

    # -- reporting ---------------------------------------------------------

    def reset_counters(self) -> None:
        """Engine ``reset_metrics()`` hook: zero the probe counters without
        disarming the golden row."""
        for k in self._stats:
            self._stats[k] = 0
        self._inflight = None

    def summary(self) -> dict:
        """The engine ``stats()["sdc"]`` block (pinned in
        tests/test_schemas.py)."""
        return {
            "every": self.every,
            "armed": self.armed,
            "golden_digest": self.golden_digest,
            "probes": self._stats["probes"],
            "mismatches": self._stats["mismatches"],
            "quarantines": self._stats["quarantines"],
            "suppressed_rows": self._stats["suppressed_rows"],
        }
