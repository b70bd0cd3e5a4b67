"""Zero-downtime weight publication: train-to-serve hot swap (layer L8).

The repo has a fault-tolerant training gang (fault_tolerance.py) and a
chaos-hardened serving stack (serving.py / disagg.py); this module is the
path BETWEEN them — continuous deployment of freshly trained weights into a
live engine without dropping a request. The portable-redistribution idea of
arXiv:2112.01075 (PAPERS.md): a checkpoint written under one topology is
republished under another through a planned minimal transfer schedule, not
ad-hoc gathers.

The :class:`WeightPublisher` watches a training run's checkpoint directory
and drives the rollout:

1. **Trust boundary** — only COMMITTED, hash-verified checkpoints are
   publishable: :func:`~accelerate_tpu.fault_tolerance.verify_checkpoint`
   must pass on the fault-tolerance manifest (a torn ``.tmp`` staging dir or
   a legacy dir with no manifest is refused), and the manifest's monotonic
   ``weights_version`` (the train step) must exceed the engine's — stale or
   duplicate versions are refused with ``warning_once``, not re-published.
2. **Topology-gap redistribution** — the checkpoint's safetensors leaves
   are replanned onto the SERVING placement via the elastic-resharding
   planner (:meth:`~accelerate_tpu.resharding.ReshardExecutor.plan_tree` /
   ``put_tree`` — no new collective code), with the moved bytes priced
   against the :class:`~accelerate_tpu.planner.BandwidthTable` exactly like
   the disagg KV handoff.
3. **Double-buffered hot swap** — the engine binds the new tree as a new
   version: in-flight requests finish on the version they bound at grant,
   new admissions bind the new one, and decode stays ONE executable with
   zero recompiles (params are a non-donated argument; the executable
   census pins it). Every ``poll()`` row carries its ``weights_version``.
4. **Canary + SLO auto-rollback** — a configurable fraction of new
   admissions routes to the candidate (error-diffusion — exact and
   deterministic); once both cohorts have enough warmup-excluded terminal
   events, ok-only TTFT/TPOT ratios and timeout/failed/nonfinite-sentinel
   rates decide: promote, or roll back bit-equal to never having published
   (a rolled-back version is quarantined for the publisher's lifetime — the
   still-newest-on-disk bad checkpoint is never republished).
   ``stats()["faults"]`` counts ``promoted`` / ``rolled_back``; telemetry
   gets a ``weights_published`` event per decision.

Every failure path is deterministically injectable
(:class:`~accelerate_tpu.chaos.FaultInjector` points ``publish_manifest`` /
``publish_transfer`` / ``canary_window``) and flows through the same
recovery code as the real fault: a torn manifest skips the checkpoint (old
version keeps serving), a transfer error retries with capped deterministic
backoff then aborts the publish, an injected SLO regression rolls back.
``make publish-smoke`` replays the whole train→publish→canary→rollback run
bit-identically under one seed.

Off by default everywhere: nothing constructs a publisher unless you do
(directly or via ``Accelerator.build_weight_publisher``).

Usage::

    from accelerate_tpu import PublishConfig, WeightPublisher

    pub = WeightPublisher(engine, PublishConfig(
        checkpoint_dir="out/checkpoints", canary_fraction=0.25,
    ))
    while serving:
        engine.tick()
        pub.poll()   # scan -> verify -> redistribute -> canary -> decide
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from .chaos import InjectedFaultError, deterministic_jitter
from .fault_tolerance import checkpoint_index, verify_checkpoint
from .logging import get_logger

logger = get_logger(__name__)

__all__ = ["PublishConfig", "WeightPublisher"]


def _log_ok() -> bool:
    """The repo logger needs accelerate state; the publisher must also work
    standalone (no Accelerator), where these logs are just skipped."""
    from .state import PartialState

    return bool(PartialState._shared_state)


@dataclass
class PublishConfig:
    """Weight-publication policy.

    - ``checkpoint_dir``: the training run's checkpoint root (the directory
      holding committed ``checkpoint_N`` dirs — what
      ``ProjectConfiguration(automatic_checkpoint_naming=True)`` writes).
    - ``weights_name``: the model-weights file inside a checkpoint.
    - ``check_hashes``: full sha256 verification against the
      fault-tolerance manifest (the trust boundary); size-only when False.
    - ``canary_fraction``: fraction of new admissions routed to the
      candidate during the canary window. ``1.0`` publishes as a full
      cutover (no canary window, no SLO decision).
    - ``canary_warmup``: per-cohort terminal events excluded from the SLO
      comparison (first-dispatch noise must not decide a rollback).
    - ``min_cohort``: post-warmup terminal events BOTH cohorts need before
      the promote/rollback decision fires.
    - ``max_ttft_ratio`` / ``max_tpot_ratio``: candidate-vs-primary ok-only
      latency ratios above which the canary reads as an SLO regression.
    - ``max_rate_increase``: allowed absolute increase of the candidate's
      timeout/failed rates over the primary's.
    - ``transfer_retries``: redistribution retries before the publish is
      aborted (the old version keeps serving).
    - ``backoff_s`` / ``backoff_cap_s``: capped exponential retry backoff,
      jittered deterministically so a chaos replay backs off identically.
    - ``staging_budget_bytes``: reshard-executor device staging budget.
    - ``bandwidths``: :class:`~accelerate_tpu.planner.BandwidthTable`
      overrides for pricing the redistribution bytes.
    """

    checkpoint_dir: str = ""
    weights_name: str = "model.safetensors"
    check_hashes: bool = True
    canary_fraction: float = 0.1
    canary_warmup: int = 2
    min_cohort: int = 4
    max_ttft_ratio: float = 1.5
    max_tpot_ratio: float = 1.5
    max_rate_increase: float = 0.0
    transfer_retries: int = 2
    backoff_s: float = 0.05
    backoff_cap_s: float = 1.0
    staging_budget_bytes: int = 256 * 1024 * 1024
    bandwidths: Optional[dict] = field(default=None)

    def __post_init__(self):
        if not 0.0 < float(self.canary_fraction) <= 1.0:
            raise ValueError(
                f"canary_fraction must be in (0, 1], got {self.canary_fraction}"
            )
        if self.canary_warmup < 0 or self.min_cohort < 1:
            raise ValueError(
                "need canary_warmup >= 0 and min_cohort >= 1, got "
                f"{self.canary_warmup}/{self.min_cohort}"
            )
        if self.max_ttft_ratio <= 0 or self.max_tpot_ratio <= 0:
            raise ValueError("latency ratios must be > 0")
        if self.transfer_retries < 0:
            raise ValueError("transfer_retries must be >= 0")


class WeightPublisher:
    """Watches a verified-checkpoint stream and hot-swaps a live engine.

    ``engine`` is a :class:`~accelerate_tpu.serving.ServingEngine` or
    :class:`~accelerate_tpu.disagg.DisaggServingEngine`; ``chaos`` arms the
    publication injection points; ``telemetry`` receives
    ``weights_published`` events and the publish summary block.
    """

    def __init__(self, engine, config: Optional[PublishConfig] = None, *,
                 chaos=None, telemetry=None, tracing=None):
        self.engine = engine
        self.config = config if config is not None else PublishConfig()
        self.chaos = chaos
        self.telemetry = telemetry
        # Trace recorder (tracing.py): explicit, else the telemetry
        # recorder's, else whatever the engine is already tracing with —
        # publish phase spans then land in the same request timeline.
        self.tracing = tracing
        if self.tracing is None:
            self.tracing = getattr(telemetry, "tracing", None)
        if self.tracing is None:
            self.tracing = getattr(engine, "tracing", None)
        if self.tracing is not None and chaos is not None:
            self.tracing.attach_chaos(chaos)
        if self.tracing is not None:
            self.tracing.register_gauges("publish", self.stats)
        self._executor = None           # lazy — built on first publish
        self._publish_seq = 0           # chaos tick for publish_* draws
        self._candidate: Optional[dict] = None
        self._last_refused: Optional[int] = None
        # Versions that rolled back: quarantined for the publisher's
        # lifetime so the still-newest-on-disk bad checkpoint is never
        # republished — recovery is a NEWER committed step, not a retry.
        self._vetoed: set[int] = set()
        self._stats = {
            "scans": 0, "published": 0, "promoted": 0, "rolled_back": 0,
            "aborted": 0, "skipped_unverified": 0, "skipped_stale": 0,
            "skipped_vetoed": 0,
            "bytes_planned": 0, "bytes_moved": 0,
            "predicted_transfer_s": 0.0, "transfer_wall_s": 0.0,
            "swap_wall_s": 0.0,
        }
        self.history: list[dict] = []   # one record per publish decision

    def _tick(self) -> int:
        """The engine's tick clock — publish spans share the serving
        timeline so a trace shows which decode ticks a publish overlapped."""
        try:
            return int(self.engine._stats["ticks"])
        except (AttributeError, KeyError, TypeError):
            return 0

    # -- the watch loop ----------------------------------------------------

    def poll(self) -> Optional[dict]:
        """One publisher round, called between engine ticks: while a canary
        window is open, try to decide it; otherwise scan for a newer
        verified checkpoint and publish it. Returns the action record
        (``{"action": "published" | "promoted" | "rolled_back" |
        "aborted", ...}``) or None when nothing happened."""
        if self._candidate is not None:
            return self.maybe_decide()
        found = self.scan()
        if found is None:
            return None
        return self.publish(*found)

    # -- checkpoint discovery (the trust boundary) -------------------------

    def scan(self) -> Optional[tuple[str, int]]:
        """Newest publishable checkpoint: committed ``checkpoint_N`` dirs
        only (a ``.tmp`` staging dir never parses), manifest-verified, with
        a ``weights_version`` strictly newer than the engine's. Returns
        ``(path, version)`` or None."""
        self._stats["scans"] += 1
        root = self.config.checkpoint_dir
        if not root or not os.path.isdir(root):
            return None
        dirs = []
        for name in os.listdir(root):
            idx = checkpoint_index(name)
            if idx is not None and os.path.isdir(os.path.join(root, name)):
                dirs.append((idx, os.path.join(root, name)))
        for idx, path in sorted(dirs, reverse=True):
            ok, reason = verify_checkpoint(
                path, check_hashes=self.config.check_hashes)
            if not ok:
                self._stats["skipped_unverified"] += 1
                if _log_ok():
                    logger.warning_once(
                        f"publish: refusing {path!r} — {reason}; only "
                        "committed, manifest-verified checkpoints are "
                        "publishable"
                    )
                continue
            version = self._manifest_version(path, idx)
            if version in self._vetoed:
                self._stats["skipped_vetoed"] += 1
                if _log_ok():
                    logger.warning_once(
                        f"publish: refusing {path!r} — weights_version "
                        f"{version} rolled back earlier and is quarantined; "
                        "commit a newer step to recover"
                    )
                continue
            if self.tracing is not None and version > int(
                    self.engine.weights_version) and version not in self._vetoed:
                self.tracing.instant("publish", "scan", self._tick(),
                                     version=version)
            if version <= int(self.engine.weights_version):
                if self._last_refused != version:
                    self._last_refused = version
                    if _log_ok():
                        logger.warning_once(
                            f"publish: refusing {path!r} — weights_version "
                            f"{version} is not newer than the serving "
                            f"primary {self.engine.weights_version} (stale "
                            "or duplicate)"
                        )
                self._stats["skipped_stale"] += 1
                return None  # newest committed version is already serving
            return path, version
        return None

    @staticmethod
    def _manifest_version(ckpt_dir: str, idx: int) -> int:
        """The monotonic version guard: the fault-tolerance manifest's
        ``weights_version`` (the train step), falling back to ``step`` and
        finally to the directory index for older manifests."""
        import json

        from .utils.constants import CHECKPOINT_MANIFEST_NAME

        try:
            with open(os.path.join(ckpt_dir, CHECKPOINT_MANIFEST_NAME)) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return int(idx)
        for key in ("weights_version", "step"):
            v = manifest.get(key)
            if v is not None:
                return int(v)
        return int(idx)

    # -- the publish pipeline ----------------------------------------------

    def publish(self, ckpt_dir: str, weights_version: Optional[int] = None
                ) -> Optional[dict]:
        """Publish one verified checkpoint into the engine: load its
        weights, redistribute them across the train→serve topology gap
        through the reshard executor's planned schedule, and bind them —
        as a canary candidate (``canary_fraction < 1``) or a full cutover.
        Returns the publish record, or None when the checkpoint was
        refused / the transfer aborted (the old version keeps serving
        either way)."""
        cfg = self.config
        seq = self._publish_seq
        self._publish_seq += 1
        if weights_version is None:
            weights_version = self._manifest_version(
                ckpt_dir, checkpoint_index(os.path.basename(ckpt_dir)) or 0)
        version = int(weights_version)
        if version in self._vetoed:
            self._stats["skipped_vetoed"] += 1
            if _log_ok():
                logger.warning(
                    "publish: refusing %r — weights_version %d rolled back "
                    "earlier and is quarantined", ckpt_dir, version,
                )
            return None

        tr = self.tracing
        h_pub = None
        if tr is not None:
            h_pub = tr.begin("publish", f"publish[v{version}]", self._tick(),
                             seq=seq, version=version)

        # Chaos gate 1: the manifest trust boundary. An injected torn_write
        # reads as a torn manifest, version_mismatch as a stale version —
        # both refuse the checkpoint through the same code path as the real
        # condition, and the old version keeps serving.
        h_verify = None
        if tr is not None:
            h_verify = tr.begin("publish", "verify", self._tick())
        fault = None
        if self.chaos is not None:
            fault = self.chaos.draw("publish_manifest", seq, unit=version)
        if fault is not None and fault.kind == "torn_write":
            self._stats["skipped_unverified"] += 1
            if _log_ok():
                logger.warning(
                    "publish: refusing %r — manifest verification failed "
                    "(injected torn write); old version %d keeps serving",
                    ckpt_dir, self.engine.weights_version,
                )
            if tr is not None:
                tr.end(h_verify, self._tick(), ok=False)
                tr.end(h_pub, self._tick(), ok=False, reason="torn_manifest")
            return None
        if fault is not None and fault.kind == "version_mismatch":
            self._stats["skipped_stale"] += 1
            if _log_ok():
                logger.warning(
                    "publish: refusing %r — weights_version %d read as "
                    "stale (injected version mismatch); old version %d "
                    "keeps serving",
                    ckpt_dir, version, self.engine.weights_version,
                )
            if tr is not None:
                tr.end(h_verify, self._tick(), ok=False)
                tr.end(h_pub, self._tick(), ok=False,
                       reason="version_mismatch")
            return None
        ok, reason = verify_checkpoint(ckpt_dir,
                                       check_hashes=cfg.check_hashes)
        if not ok:
            self._stats["skipped_unverified"] += 1
            if _log_ok():
                logger.warning("publish: refusing %r — %s", ckpt_dir, reason)
            if tr is not None:
                tr.end(h_verify, self._tick(), ok=False)
                tr.end(h_pub, self._tick(), ok=False, reason="unverified")
            return None
        if tr is not None:
            tr.end(h_verify, self._tick(), ok=True)

        h_redist = None
        if tr is not None:
            h_redist = tr.begin("publish", "redistribute", self._tick())
        host_tree, prefix = self._load_weights(ckpt_dir)
        schedule, predicted_s, n_devices = self._plan(host_tree, ckpt_dir,
                                                      prefix)
        moved_bytes = sum(
            t.nbytes for t in schedule.transfers
            if t.op != "noop" or t.host_staged
        )
        self._stats["bytes_planned"] += int(moved_bytes)
        self._stats["predicted_transfer_s"] += float(predicted_s)

        new_params = self._transfer(host_tree, prefix, seq, version)
        if new_params is None:
            if tr is not None:
                tr.end(h_redist, self._tick(), ok=False)
                tr.end(h_pub, self._tick(), ok=False,
                       reason="transfer_aborted")
            return None  # aborted — retries exhausted
        if tr is not None:
            tr.end(h_redist, self._tick(), ok=True,
                   bytes=int(moved_bytes))

        t0 = time.perf_counter()
        if float(cfg.canary_fraction) >= 1.0:
            self.engine.swap_params(new_params, weights_version=version)
            mode = "cutover"
        else:
            self.engine.begin_canary(
                new_params, weights_version=version,
                fraction=float(cfg.canary_fraction),
            )
            self._candidate = {
                "version": version, "primary": int(self.engine.weights_version),
                "seq": seq, "ckpt_dir": ckpt_dir,
            }
            mode = "canary"
        swap_s = time.perf_counter() - t0
        self._stats["swap_wall_s"] += swap_s
        self._stats["published"] += 1
        if tr is not None:
            if mode == "canary":
                # The canary window outlives this call — a detached span
                # closed by maybe_decide() when the cohort verdict lands.
                self._candidate["trace_span"] = tr.begin(
                    "publish", f"canary_window[v{version}]", self._tick(),
                    detached=True, version=version,
                    fraction=float(cfg.canary_fraction))
            tr.end(h_pub, self._tick(), ok=True, mode=mode,
                   swap_s=round(swap_s, 6))
        record = {
            "action": "published", "mode": mode, "version": version,
            "ckpt_dir": ckpt_dir, "bytes": int(moved_bytes),
            "predicted_transfer_s": float(predicted_s),
            "swap_s": round(swap_s, 6), "n_devices": n_devices,
        }
        self.history.append(record)
        self._event("weights_published", outcome=mode, version=version,
                    bytes=int(moved_bytes),
                    predicted_transfer_s=float(predicted_s))
        if _log_ok():
            logger.info(
                "publish: version %d bound (%s, %d leaf bytes planned, "
                "predicted transfer %.3gs, swap %.3gs)",
                version, mode, moved_bytes, predicted_s, swap_s,
            )
        return record

    def _load_weights(self, ckpt_dir: str) -> tuple[Any, str]:
        """Checkpoint safetensors -> a host tree with the ENGINE's treedef
        (leaf order matched by flattened name, so structure mismatches are
        impossible by construction and missing leaves fail loudly), plus the
        plan-manifest key prefix for this tree (probed by suffix — the
        manifest keys leaves per TrainState slot, e.g. ``slot0/params/...``,
        while the engine tree is the bare params subtree)."""
        import jax

        from .parallel.sharding import _path_to_name
        from .utils.other import load_sharded_safetensors

        loaded = load_sharded_safetensors(
            ckpt_dir, weights_name=self.config.weights_name)
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.engine._params_template)
        names = [_path_to_name(p) for p, _ in flat]
        missing = [n for n in names if n not in loaded]
        if missing:
            raise ValueError(
                f"publish: checkpoint {ckpt_dir!r} is missing "
                f"{len(missing)}/{len(names)} serving leaves (first: "
                f"{missing[0]!r}) — was it written by a different model "
                "config?"
            )
        host_tree = jax.tree_util.tree_unflatten(
            treedef, [np.asarray(loaded[n]) for n in names])

        from .resharding import read_plan_manifest

        prefix = ""
        manifest = read_plan_manifest(ckpt_dir)
        if manifest and names:
            probe = "/" + names[0]
            for key in manifest.get("leaves", {}):
                if key.endswith(probe):
                    prefix = key[: -len(probe)]
                    break
        return host_tree, prefix

    def _dst_shardings_and_mesh(self):
        """The serving placement to redistribute onto, and a mesh for the
        executor: the first NamedSharding leaf's mesh when the serving tree
        is mesh-sharded, else a trivial one-device mesh (its axis names
        never match a train-side spec, so every moved leaf takes the safe
        host-staged ingest path)."""
        import jax
        from jax.sharding import Mesh, NamedSharding

        dst = jax.tree.map(lambda leaf: leaf.sharding, self.engine._params_template)
        mesh = None
        for s in jax.tree_util.tree_leaves(
                dst, is_leaf=lambda x: hasattr(x, "device_set")):
            if isinstance(s, NamedSharding):
                mesh = s.mesh
                break
        if mesh is None:
            leaves = jax.tree_util.tree_leaves(self.engine._params_template)
            dev = next(iter(leaves[0].sharding.device_set))
            mesh = Mesh(np.asarray([dev]), ("publish",))
        return dst, mesh

    def _plan(self, host_tree, ckpt_dir: str, prefix: str):
        """Build (or refresh) the reshard executor against this checkpoint's
        plan manifest and price the redistribution like the disagg handoff:
        planned schedule bytes against the BandwidthTable."""
        from .planner import BandwidthTable
        from .resharding import ReshardExecutor, predict_transfer_s, read_plan_manifest

        dst, mesh = self._dst_shardings_and_mesh()
        self._executor = ReshardExecutor(
            mesh, manifest=read_plan_manifest(ckpt_dir),
            staging_budget_bytes=self.config.staging_budget_bytes,
        )
        self._dst = dst
        schedule = self._executor.plan_tree(host_tree, dst, prefix=prefix)
        n_devices = len(mesh.devices.reshape(-1))
        predicted_s = predict_transfer_s(
            schedule, BandwidthTable.from_dict(self.config.bandwidths),
            n_devices)
        return schedule, predicted_s, n_devices

    def _transfer(self, host_tree, prefix: str, seq: int, version: int):
        """The guarded redistribution: one chaos draw at
        ``publish_transfer``, then ``put_tree`` with capped
        deterministic-jitter backoff retries. A transient injected error
        (``u < 0.75``) fails exactly one attempt; a persistent one (or a
        real failure surviving every retry) ABORTS the publish — the old
        version keeps serving, nothing is half-bound."""
        cfg = self.config
        fault = None
        if self.chaos is not None:
            fault = self.chaos.draw("publish_transfer", seq, unit=version)
        attempts = int(cfg.transfer_retries) + 1
        t0 = time.perf_counter()
        for attempt in range(attempts):
            try:
                if (fault is not None and fault.kind == "transfer_error"
                        and (attempt == 0 or fault.u >= 0.75)):
                    raise InjectedFaultError(fault)
                out = self._executor.put_tree(host_tree, self._dst,
                                              prefix=prefix)
                self._stats["transfer_wall_s"] += time.perf_counter() - t0
                # The executor is rebuilt per publish, so its accumulator
                # holds only this publish's bytes.
                self._stats["bytes_moved"] += self._executor.stats()[
                    "bytes_transferred"]
                return out
            except RuntimeError as e:
                if attempt == attempts - 1:
                    self._stats["aborted"] += 1
                    self.history.append({
                        "action": "aborted", "version": version,
                        "reason": str(e), "attempts": attempts,
                    })
                    self._event("weights_published", outcome="aborted",
                                version=version, reason=str(e))
                    if _log_ok():
                        logger.warning(
                            "publish: transfer for version %d failed %dx "
                            "(%s) — publish aborted, version %d keeps "
                            "serving",
                            version, attempts, e,
                            self.engine.weights_version,
                        )
                    return None
                backoff = min(
                    float(cfg.backoff_s) * (2 ** attempt),
                    float(cfg.backoff_cap_s),
                ) * deterministic_jitter(
                    self.chaos.seed if self.chaos is not None else 0,
                    seq, attempt,
                )
                if backoff > 0:
                    time.sleep(backoff)

    # -- the canary decision -----------------------------------------------

    def maybe_decide(self) -> Optional[dict]:
        """Promote or roll back the open canary window once BOTH cohorts
        have ``min_cohort`` post-warmup terminal events; None while the
        window is still filling. The decision compares ok-only TTFT/TPOT
        ratios and timeout/failed/nonfinite-sentinel rates, and draws the
        ``canary_window`` chaos point exactly once — an injected
        ``slo_regression`` forces the rollback path."""
        cand = self._candidate
        if cand is None:
            return None
        cfg = self.config
        prim_stats = self.engine.cohort_stats(cand["primary"],
                                              warmup=cfg.canary_warmup)
        cand_stats = self.engine.cohort_stats(cand["version"],
                                              warmup=cfg.canary_warmup)
        if (prim_stats is None or cand_stats is None
                or prim_stats["completed"] < cfg.min_cohort
                or cand_stats["completed"] < cfg.min_cohort):
            return None

        reasons = []
        if self.chaos is not None:
            fault = self.chaos.draw("canary_window", cand["seq"],
                                    unit=cand["version"])
            if fault is not None and fault.kind == "slo_regression":
                reasons.append("injected slo_regression")

        def ratio(kind, limit):
            a, b = cand_stats[kind], prim_stats[kind]
            if a is not None and b is not None and b > 0 and a / b > limit:
                reasons.append(
                    f"{kind.replace('ok_', '').replace('_mean_s', '')} "
                    f"ratio {a / b:.2f} > {limit}"
                )

        ratio("ok_ttft_mean_s", cfg.max_ttft_ratio)
        ratio("ok_tpot_mean_s", cfg.max_tpot_ratio)
        for key in ("timeout_rate", "failed_rate"):
            if cand_stats[key] > prim_stats[key] + cfg.max_rate_increase:
                reasons.append(
                    f"{key} {cand_stats[key]:.3f} > "
                    f"{prim_stats[key]:.3f} + {cfg.max_rate_increase}"
                )
        if cand_stats["poisoned"] > prim_stats["poisoned"]:
            reasons.append(
                f"nonfinite sentinels {cand_stats['poisoned']} > "
                f"{prim_stats['poisoned']}"
            )

        self._candidate = None
        if reasons:
            window = self.engine.rollback_canary()
            self._stats["rolled_back"] += 1
            self._vetoed.add(cand["version"])
            action = "rolled_back"
        else:
            window = self.engine.promote_canary()
            self._stats["promoted"] += 1
            action = "promoted"
        if self.tracing is not None:
            h_win = cand.get("trace_span")
            if h_win is not None:
                self.tracing.end(h_win, self._tick(), action=action,
                                 n_reasons=len(reasons))
            self.tracing.instant(
                "publish", "decide", self._tick(), action=action,
                version=cand["version"],
                reason=(reasons[0] if reasons else ""))
        record = {
            "action": action, "version": cand["version"],
            "reasons": reasons,
            "cohorts": {"primary": prim_stats, "candidate": cand_stats},
            "routed": {"candidate": window["routed_candidate"],
                       "primary": window["routed_primary"]},
        }
        self.history.append(record)
        self._event("weights_published", outcome=action,
                    version=cand["version"], reasons="; ".join(reasons),
                    candidate_completed=cand_stats["completed"],
                    primary_completed=prim_stats["completed"])
        return record

    # -- reporting ---------------------------------------------------------

    def stats(self) -> dict:
        """The publish telemetry block: publication counters, priced/moved
        bytes, reshard-executor accumulators, and the serving version."""
        out = dict(self._stats)
        out["predicted_transfer_s"] = round(out["predicted_transfer_s"], 6)
        out["transfer_wall_s"] = round(out["transfer_wall_s"], 6)
        out["swap_wall_s"] = round(out["swap_wall_s"], 6)
        out["weights_version"] = int(self.engine.weights_version)
        out["canary"] = self.engine.canary_status()
        out["reshard"] = self._executor.stats() if self._executor else None
        return out

    def _event(self, name: str, **fields) -> None:
        if self.telemetry is not None:
            try:
                self.telemetry.record_event(name, **fields)
            except Exception as e:  # observability must never kill a publish
                if _log_ok():
                    logger.warning_once(
                        f"publish: telemetry event failed: {e}")
