"""Step-level training telemetry (layer L10 — observability).

The profiler (`utils/profiling.py`) answers "where did THIS step's time go"
on demand; the trackers (`tracking.py`) record whatever scalars the user
hands them. Neither watches the loop itself, so the regressions that
actually eat production throughput — silent jit recompiles, input
starvation, straggler ranks, HBM creep — stay invisible until a bench run
tanks. :class:`TelemetryRecorder` closes that gap: it rides inside every
prepared train step and records, per step,

- wall time (dispatch wall by default; exact device wall with
  ``sync_timing=True``), dataloader-wait time, and samples/s + tokens/s
  with EMA smoothing;
- a **recompile watchdog**: the jitted step function's executable-cache
  size is sampled every call; any growth past the first compile logs a
  warning carrying the offending batch's shape/dtype digest (the usual
  culprit — see docs/troubleshooting.md "recompile storms");
- device-memory gauges (``bytes_in_use`` and a peak-HBM high-water mark)
  via :func:`~accelerate_tpu.utils.memory.get_device_memory_stats`;
- cumulative collective-op counters (count + payload bytes) fed by
  ``utils/operations.py``'s control-plane collectives;
- a periodic cross-rank straggler probe: every N steps the ranks allgather
  their last step time and the max/min skew is recorded (and warned about
  past a threshold).

Records stream to a per-rank JSONL file under ``<project_dir>/telemetry/``
(crash-safe: line-buffered, one self-contained JSON object per line) and a
smoothed summary is forwarded into the tracker stack via
``Accelerator.log()`` on the main process every ``log_every`` steps.

Enable by passing ``TelemetryKwargs`` (utils/dataclasses.py) to
``Accelerator(kwargs_handlers=[...])``. Off by default; when off, the only
cost anywhere in the hot path is a ``None`` attribute check.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Optional

import jax
import numpy as np

from .logging import get_logger
from .profiler import DeviceTimeProfiler, MetricsHub, ProfilerConfig
from .tracing import TraceConfig, TraceRecorder
from .utils.memory import get_device_memory_stats, live_bytes_on_device
from .utils.operations import collective_counters, gather

logger = get_logger(__name__)

# JSONL record schema, by "event" field:
#   step            — one prepared-train-step record (the common row)
#   optimizer_step  — imperative path: backward()-accumulated + apply timing
#   straggler_probe — cross-rank step-time skew sample
#   checkpoint_save / checkpoint_load — duration of a (re)store
#   summary         — final aggregate written by close()
STEP_RECORD_KEYS = (
    "event",
    "step",
    "time",
    "wall_s",
    "data_wait_s",
    "samples",
    "samples_per_s",
    "tokens_per_s",
    "ema_samples_per_s",
    "ema_tokens_per_s",
    "collectives",
    "hbm_bytes_in_use",
    "hbm_peak_bytes",
    "recompiles",
)


def _batch_digest(batch) -> str:
    """Stable shape/dtype fingerprint of a batch pytree — the watchdog's
    "what changed" evidence when a recompile fires."""
    parts = []
    try:
        leaves = jax.tree_util.tree_leaves_with_path(batch)
    except Exception:
        return f"<undigestable {type(batch).__name__}>"
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path) or "leaf"
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None:
            parts.append(f"{name}:{type(leaf).__name__}")
        else:
            parts.append(f"{name}:{dtype}{list(shape)}")
    return "|".join(parts) or "<empty>"


def _batch_counts(batch) -> tuple[Optional[int], Optional[int]]:
    """(samples, tokens) from a global batch: samples = leading dim of the
    first array leaf; tokens = B*S of the first rank>=2 leaf (the sequence
    convention every model in models/ follows)."""
    samples = tokens = None
    try:
        leaves = jax.tree_util.tree_leaves(batch)
    except Exception:
        return None, None
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if not shape:
            continue
        if samples is None:
            samples = int(shape[0])
        if tokens is None and len(shape) >= 2:
            tokens = int(shape[0]) * int(shape[1])
        if samples is not None and tokens is not None:
            break
    return samples, tokens


class TelemetryRecorder:
    """Per-process training-loop observer. One instance per Accelerator,
    created when a :class:`~accelerate_tpu.utils.TelemetryKwargs` handler is
    passed; all hooks no-op through a ``None`` check when absent."""

    def __init__(self, accelerator, handler):
        self.accelerator = accelerator
        self.handler = handler
        self.process_index = accelerator.process_index
        self.num_processes = accelerator.num_processes
        base = handler.output_dir or os.path.join(
            accelerator.project_dir or ".", "telemetry"
        )
        self.output_dir = base
        self.path = os.path.join(base, f"rank_{self.process_index}.jsonl")
        self._fh = None  # opened lazily: a run that never steps writes nothing
        self.step = 0
        self._ema_samples = None
        self._ema_tokens = None
        self._peak_hbm: Optional[int] = None
        self._step_times: list[float] = []
        self._data_waits: list[float] = []
        self._pending_data_wait = 0.0
        self._pending_backward = 0.0
        self._last_wall: Optional[float] = None
        # Recompile watchdog state, keyed per watched callable.
        self._watch: dict[int, dict] = {}
        self.recompiles = 0
        self._checkpoint_events = 0
        # Checkpoint-cost/robustness tally (fed by record_event; surfaced as
        # the summary's "checkpoint" block so bench rows can track
        # checkpoint-cost regressions and recovery actions across rounds).
        self._ckpt = {
            "saves": 0,
            "loads": 0,
            "save_s": 0.0,
            "load_s": 0.0,
            "verify_s": 0.0,
            "retries": 0,
            "torn_skipped": 0,
            "preemption_saves": 0,
            "rollbacks": 0,
            "fallback_saves": 0,
            "async_errors": 0,
        }
        # Injected-fault + watchdog tallies (fed by record_event; surfaced
        # as the summary's "faults"/"watchdog" blocks so bench training rows
        # grade robustness runs — the training twin of the serving engines'
        # faults block).
        self._faults = {"injected": 0, "by_site": {}}
        self._watchdog = {
            "warnings": 0,
            "stalls": 0,
            "last_straggler": None,
            "last_ages_s": None,
        }
        # Serving block (serving.py): per-request TTFT/TPOT events stream as
        # they retire; the engine pushes its aggregate summary via
        # record_serving and it rides the summary as the "serving" block.
        self._serving_summary: Optional[dict] = None
        self._serving_requests = 0
        # Speculative-decoding acceptance-rate EMA across summary pushes.
        self._spec_accept_ema: Optional[float] = None
        # Elastic reshard block (resharding.py): cumulative leaves/bytes/
        # depth/wall time across restores and live migrations this run.
        self._reshard_summary: Optional[dict] = None
        # Disaggregated-serving block (disagg.py): slice plan, handoff
        # bytes/latency, measured prefill:decode FLOP ratio.
        self._disagg_summary: Optional[dict] = None
        # Weight-publication block (publish.py): publish/promote/rollback
        # counts, redistribution bytes, swap latency.
        self._publish_summary: Optional[dict] = None
        # Autoscale block (autoscale.py): decision/resize counters and the
        # controller's live state (cooldown, breach streaks, device census).
        self._autoscale_summary: Optional[dict] = None
        # Auto-parallelism plan (planner.py): note_plan installs the active
        # plan; after _plan_calibrate_after steps the measured step time +
        # peak HBM are written back into the plan artifact (the calibration
        # loop) and the summary carries a "plan" block.
        self._plan: Optional[dict] = None
        self._plan_path: Optional[str] = None
        self._plan_calibrate_after = 0
        self._plan_calibration: Optional[dict] = None
        # Request-scoped tracing (tracing.py): built from the handler's
        # ``tracing`` knob; serving engines constructed through the
        # accelerator pick it up from here, and summary() grows a
        # "tracing" block. None when off — same zero-cost contract as
        # every other hook in this file.
        # The unified metrics registry (profiler.py MetricsHub): tracing,
        # serving, autoscale, publish, journal, and the SDC sentinel all
        # register providers here; one renderer, one naming scheme.
        self.hub = MetricsHub()
        self.hub.register_provider("telemetry", self._hub_stats)
        self.tracing = None
        tr_cfg = TraceConfig.from_value(getattr(handler, "tracing", None))
        if tr_cfg is not None:
            self.tracing = TraceRecorder(tr_cfg, hub=self.hub)
        # Device-time attribution (profiler.py): built from the handler's
        # ``profile`` knob; lagged one step — zero extra device syncs.
        # summary() grows a "profile" block and abnormal exits dump the
        # profiler's flight ring. Same zero-cost None contract when off.
        self.profiler = None
        pf_cfg = ProfilerConfig.from_value(getattr(handler, "profile", None))
        if pf_cfg is not None:
            self.profiler = DeviceTimeProfiler(
                pf_cfg, out_dir=accelerator.project_dir or ".")
            self.hub.register_provider("profile", self.profiler.summary)
            if self.tracing is not None:
                self.profiler.flight.attach_tracing(self.tracing)
        # JSONL rotation state (handler.max_log_bytes): one warning on the
        # first rotation, then silent.
        self._rotated_once = False
        # Counters are process-global (utils/operations.py); a new recorder
        # means a new run's tally.
        collective_counters.reset()
        collective_counters.enabled = True

    # -- hot-path hooks ----------------------------------------------------

    def on_train_step(self, step_fn, batch, wall_s: float, metrics=None):
        """Called by the prepared step wrapper after every step."""
        self.step += 1
        self._last_wall = wall_s
        self._step_times.append(wall_s)
        data_wait, self._pending_data_wait = self._pending_data_wait, 0.0
        self._data_waits.append(data_wait)
        self._watch_recompiles(step_fn, batch, manifest=True)
        samples, tokens = _batch_counts(batch)
        samples_per_s = samples / wall_s if samples and wall_s > 0 else None
        tokens_per_s = tokens / wall_s if tokens and wall_s > 0 else None
        alpha = self.handler.ema_alpha
        if samples_per_s is not None:
            self._ema_samples = (
                samples_per_s
                if self._ema_samples is None
                else alpha * samples_per_s + (1 - alpha) * self._ema_samples
            )
        if tokens_per_s is not None:
            self._ema_tokens = (
                tokens_per_s
                if self._ema_tokens is None
                else alpha * tokens_per_s + (1 - alpha) * self._ema_tokens
            )
        record = {
            "event": "step",
            "step": self.step,
            "time": time.time(),
            "wall_s": wall_s,
            "data_wait_s": data_wait,
            "samples": samples,
            "samples_per_s": samples_per_s,
            "tokens_per_s": tokens_per_s,
            "ema_samples_per_s": self._ema_samples,
            "ema_tokens_per_s": self._ema_tokens,
            "collectives": collective_counters.snapshot(),
            "recompiles": self.recompiles,
        }
        record.update(self._memory_gauges())
        if self.profiler is not None:
            # Lagged attribution: this call finalizes step N-1's record and
            # stashes step N — host arithmetic only, zero device syncs.
            self.profiler.on_step(self.step, wall_s, data_wait)
            self.profiler.note_gauge("hbm_peak_bytes", self._peak_hbm)
            self.profiler.note_gauge("recompiles", self.recompiles)
        if metrics is not None and self.handler.sync_timing:
            # Only in sync mode: fetching the loss would otherwise force the
            # very host sync non-blocking timing exists to avoid.
            loss = metrics.get("loss") if isinstance(metrics, dict) else None
            if loss is not None:
                try:
                    record["loss"] = float(np.asarray(loss))
                except Exception:
                    pass
        self._write(record)
        every = self.handler.straggler_probe_every
        if every and self.step % every == 0:
            self._straggler_probe(wall_s)
        self._maybe_calibrate_plan()
        self._forward_to_trackers(record)

    def on_backward(self, grad_fn, batch, wall_s: float):
        """Imperative path: accumulate backward wall time; the record is
        emitted at the apply boundary (on_apply_gradients)."""
        self._pending_backward += wall_s
        self._watch_recompiles(grad_fn, batch)

    def on_apply_gradients(self, wall_s: float):
        self.step += 1
        backward_s, self._pending_backward = self._pending_backward, 0.0
        data_wait, self._pending_data_wait = self._pending_data_wait, 0.0
        total = backward_s + wall_s
        self._step_times.append(total)
        self._data_waits.append(data_wait)
        record = {
            "event": "optimizer_step",
            "step": self.step,
            "time": time.time(),
            "wall_s": total,
            "backward_s": backward_s,
            "apply_s": wall_s,
            "data_wait_s": data_wait,
            "collectives": collective_counters.snapshot(),
            "recompiles": self.recompiles,
        }
        record.update(self._memory_gauges())
        self._write(record)
        every = self.handler.straggler_probe_every
        if every and self.step % every == 0:
            self._straggler_probe(total)
        self._forward_to_trackers(record)

    def add_data_wait(self, seconds: float):
        """Fed by the prepared dataloaders: host time blocked waiting for the
        next batch (collation + read not hidden by prefetch)."""
        self._pending_data_wait += seconds

    # -- recompile watchdog ------------------------------------------------

    def _record_manifest_signature(self, batch, digest: str):
        """Watchdog → shapes-manifest bridge: every NEW step-batch signature
        is persisted (one JSONL line) so the compile manager's AOT warmup can
        consume it across runs — including runs where only telemetry was on
        (compile_manager.record_watchdog_signature writes a standalone
        manifest under the project dir in that case)."""
        try:
            from .compile_manager import record_watchdog_signature

            record_watchdog_signature(self.accelerator, batch, digest)
        except Exception as e:  # a bridge failure must never kill training
            logger.warning_once(f"telemetry: shapes-manifest bridge failed: {e}")

    def _watch_recompiles(self, fn, batch, manifest: bool = False):
        entry = self._watch.setdefault(
            id(fn), {"cache_size": None, "digests": set(), "layout_recompiled": False}
        )
        cache_size_fn = getattr(fn, "_cache_size", None)
        if callable(cache_size_fn):
            try:
                size = int(cache_size_fn())
            except Exception:
                size = None
            if size is not None:
                prev = entry["cache_size"]
                entry["cache_size"] = size
                digest = _batch_digest(batch)
                new_digest = digest not in entry["digests"]
                entry["digests"].add(digest)
                if new_digest and manifest:
                    self._record_manifest_signature(batch, digest)
                extra = max(0, size - prev) if prev is not None else 0
                if extra > 0:
                    self.recompiles += extra
                    if not new_digest and not entry["layout_recompiled"]:
                        # The one expected same-shape recompile: the step
                        # leaves its output shardings to GSPMD, which on a
                        # mesh may return state leaves in another sharding
                        # than prepare() gave them, so the second call sees
                        # new input shardings (chip_smoke.py warms up twice for
                        # this). Counted and recorded, not warning-worthy.
                        entry["layout_recompiled"] = True
                        reason = "state shardings settle (expected once)"
                    else:
                        reason = (
                            "batch shape/dtype change" if new_digest
                            else "unchanged batch shapes — a non-batch argument "
                                 "is varying"
                        )
                        logger.warning(
                            "telemetry: jitted step recompiled (executable "
                            "cache %d -> %d, %d recompile(s) total; %s) — "
                            "offending batch digest: %s. Recompiles retrace "
                            "and re-lower the whole step; pad to fixed shapes "
                            "(see docs/troubleshooting.md).",
                            prev, size, self.recompiles, reason, digest,
                            main_process_only=False,
                        )
                    self._write(
                        {
                            "event": "recompile",
                            "step": self.step,
                            "time": time.time(),
                            "recompiles": self.recompiles,
                            "reason": reason,
                            "batch_digest": digest,
                        }
                    )
                return
        # Fallback (no cache-size API): infer from batch-digest novelty.
        digest = _batch_digest(batch)
        if digest not in entry["digests"]:
            first = not entry["digests"]
            entry["digests"].add(digest)
            if manifest:
                self._record_manifest_signature(batch, digest)
            if not first:
                self.recompiles += 1
                logger.warning(
                    "telemetry: batch shape/dtype changed (recompile likely, "
                    "%d total) — digest: %s",
                    self.recompiles, digest,
                    main_process_only=False,
                )
                self._write(
                    {
                        "event": "recompile",
                        "step": self.step,
                        "time": time.time(),
                        "recompiles": self.recompiles,
                        "reason": "batch shape/dtype change",
                        "batch_digest": digest,
                    }
                )

    # -- probes & gauges ---------------------------------------------------

    def _memory_gauges(self) -> dict:
        every = max(1, self.handler.memory_every)
        if self.step % every != 0:
            return {"hbm_bytes_in_use": None, "hbm_peak_bytes": self._peak_hbm}
        stats = get_device_memory_stats()
        in_use = stats.get("bytes_in_use")
        if in_use is None:
            # Backends without memory_stats (the virtual CPU mesh): gauge the
            # live-array census instead so peak-HBM tracking — and the
            # planner's predicted-vs-measured calibration — still works.
            in_use = live_bytes_on_device()
        peak = stats.get("peak_bytes_in_use", in_use)
        if peak is not None:
            peak = int(peak)
            self._peak_hbm = peak if self._peak_hbm is None else max(self._peak_hbm, peak)
        return {
            "hbm_bytes_in_use": int(in_use) if in_use is not None else None,
            "hbm_peak_bytes": self._peak_hbm,
        }

    def _straggler_probe(self, wall_s: float):
        """Allgather the last step time across ranks and record the skew.
        The probe's own collective must not pollute the counters it reports."""
        was_enabled, collective_counters.enabled = collective_counters.enabled, False
        try:
            times = np.asarray(gather(np.asarray([wall_s], np.float64)), np.float64)
        except Exception as e:  # a failed probe must never kill training
            # warning_once keyed by the message: a wedged rank fails every
            # probe tick identically, and a long stall must not flood the log.
            logger.warning_once(f"telemetry: straggler probe failed: {e}")
            return
        finally:
            collective_counters.enabled = was_enabled
        t_max, t_min = float(times.max()), float(times.min())
        mean = float(times.mean()) or 1e-12
        skew = (t_max - t_min) / mean
        if self.profiler is not None:
            # Absolute skew seconds land on the NEXT finalized step's
            # attribution record (the probe runs after the step it sampled).
            self.profiler.note_straggler(t_max - t_min)
        self._write(
            {
                "event": "straggler_probe",
                "step": self.step,
                "time": time.time(),
                "step_time_max_s": t_max,
                "step_time_min_s": t_min,
                "skew": skew,
                "rank_times_s": [float(t) for t in times.ravel()],
            }
        )
        if skew > self.handler.straggler_warn_skew and self.num_processes > 1:
            slowest = int(np.argmax(times.ravel()))
            logger.warning(
                "telemetry: straggler skew %.1f%% at step %d (max %.4fs rank %d, "
                "min %.4fs) — one rank is consistently behind; check its input "
                "pipeline and host load (docs/troubleshooting.md).",
                100 * skew, self.step, t_max, slowest, t_min,
            )

    def record_event(self, event: str, **fields):
        """Out-of-band durations (checkpoint save/load, fault-tolerance
        actions, user phases)."""
        if event in ("checkpoint_save", "checkpoint_load"):
            self._checkpoint_events += 1
        ck = self._ckpt
        if event == "checkpoint_save":
            ck["saves"] += 1
            ck["save_s"] += float(fields.get("seconds") or 0.0)
        elif event == "checkpoint_load":
            ck["loads"] += 1
            ck["load_s"] += float(fields.get("seconds") or 0.0)
        elif event == "checkpoint_verify":
            ck["verify_s"] += float(fields.get("seconds") or 0.0)
        elif event == "checkpoint_save_retry":
            ck["retries"] += 1
        elif event == "checkpoint_torn_skipped":
            ck["torn_skipped"] += 1
        elif event == "preemption_save":
            ck["preemption_saves"] += 1
        elif event == "rollback":
            ck["rollbacks"] += 1
        elif event == "checkpoint_fallback_save":
            ck["fallback_saves"] += 1
        elif event == "checkpoint_async_error":
            ck["async_errors"] += 1
        elif event == "serving_request_done":
            self._serving_requests += 1
        elif event == "weights_published":
            # Publication lifecycle tally (publish.py): one event per
            # outcome — canary/cutover on publish, then promoted /
            # rolled_back / aborted as the canary window resolves.
            pub = self._publish_summary
            if pub is None:
                pub = self._publish_summary = {"by_outcome": {}}
            by = pub["by_outcome"]
            outcome = str(fields.get("outcome"))
            by[outcome] = by.get(outcome, 0) + 1
            if "version" in fields:
                pub["last_version"] = fields.get("version")
        elif event == "fault_injected":
            self._faults["injected"] += 1
            site = f"{fields.get('point')}:{fields.get('kind')}"
            by = self._faults["by_site"]
            by[site] = by.get(site, 0) + 1
        elif event == "training_stalled":
            wd = self._watchdog
            if fields.get("level") == "stall":
                wd["stalls"] += 1
            else:
                wd["warnings"] += 1
            wd["last_straggler"] = fields.get("straggler")
            wd["last_ages_s"] = fields.get("ages_s")
        if self.tracing is not None:
            # Checkpoint save/restore and watchdog stalls get trace spans
            # through this one forwarding point — checkpointing.py and
            # fault_tolerance.py already report here.
            try:
                self.tracing.on_event(event, fields, self.step)
            except Exception:
                logger.warning_once(f"telemetry: trace forwarding failed "
                                    f"for {event!r}")
        record = {"event": event, "step": self.step, "time": time.time()}
        record.update(fields)
        self._write(record)

    def note_plan(self, plan: dict, path: Optional[str],
                  calibrate_after: int = 10) -> None:
        """Install the resolved auto-parallelism plan (planner.py). The
        summary gains a ``plan`` block (predicted vs measured step time /
        peak HBM) and, when ``path`` is set, measurements are written back
        into the artifact after ``calibrate_after`` steps."""
        self._plan = dict(plan)
        self._plan_path = path
        self._plan_calibrate_after = int(calibrate_after)
        if self.profiler is not None:
            # The plan's CostBreakdown + BandwidthTable price the
            # profiler's per-axis comm terms and bandwidth residuals.
            self.profiler.note_plan(self._plan)
        self._write({
            "event": "plan",
            "step": self.step,
            "time": time.time(),
            "layout": self._plan.get("layout"),
            "predicted_step_s": self._plan.get("predicted_step_s"),
            "predicted_hbm_gib": self._plan.get("predicted_hbm_gib"),
            "path": path,
        })

    def record_reshard(self, block: dict) -> None:
        """Record a completed elastic reshard (resharding.py): leaves moved,
        bytes transferred, schedule depth, wall time, staging budget. The
        summary gains a ``reshard`` block; repeated reshards (restore then a
        live migration) accumulate the counters and keep the last kind."""
        prev = self._reshard_summary or {}
        merged = dict(block)
        for k in ("leaves", "moved_leaves", "bytes", "bytes_transferred",
                  "host_staged", "depth"):
            merged[k] = int(prev.get(k, 0)) + int(block.get(k, 0) or 0)
        merged["wall_s"] = round(
            float(prev.get("wall_s", 0.0)) + float(block.get("wall_s", 0.0) or 0.0), 6
        )
        merged["peak_batch_bytes"] = max(
            int(prev.get("peak_batch_bytes", 0)), int(block.get("peak_batch_bytes", 0) or 0)
        )
        merged["count"] = int(prev.get("count", 0)) + 1
        self._reshard_summary = merged
        self.record_event("reshard", **{
            k: v for k, v in block.items() if not isinstance(v, dict) or k == "ops"
        })

    def _plan_measurements(self) -> tuple[Optional[float], Optional[float]]:
        """(measured p50 step seconds, measured peak HBM GiB) so far."""
        step_s = None
        if self._step_times:
            step_s = float(np.percentile(np.asarray(self._step_times), 50))
        peak_gib = self._peak_hbm / (1024 ** 3) if self._peak_hbm else None
        return step_s, peak_gib

    def _maybe_calibrate_plan(self, final: bool = False) -> None:
        if (
            self._plan is None
            or self._plan_path is None
            or self._plan_calibration is not None
            or not self._plan_calibrate_after
        ):
            return
        if not final and self.step < self._plan_calibrate_after:
            return
        if not self._step_times:
            return
        step_s, peak_gib = self._plan_measurements()
        try:
            from .planner import record_calibration

            cal = record_calibration(
                self._plan_path,
                measured_step_s=step_s,
                measured_peak_hbm_gib=peak_gib,
                steps=len(self._step_times),
            )
        except Exception as e:  # calibration must never kill training
            logger.warning_once(f"telemetry: plan calibration failed: {e}")
            return
        if cal is not None:
            self._plan_calibration = cal
            self._write({
                "event": "plan_calibration",
                "step": self.step,
                "time": time.time(),
                **{k: cal.get(k) for k in (
                    "runs", "measured_step_s", "measured_peak_hbm_gib",
                    "step_time_ratio", "hbm_ratio", "mfu_effective",
                )},
            })

    def plan_block(self) -> Optional[dict]:
        """The summary's ``plan`` block: predicted vs measured, calibration
        deltas."""
        if self._plan is None:
            return None
        step_s, peak_gib = self._plan_measurements()
        predicted_s = self._plan.get("predicted_step_s")
        predicted_gib = self._plan.get("predicted_hbm_gib")
        block = {
            "layout": self._plan.get("layout"),
            "predicted_step_s": predicted_s,
            "predicted_hbm_gib": predicted_gib,
            "measured_step_p50_s": step_s,
            "measured_peak_hbm_gib": peak_gib,
            "calibrated": self._plan_calibration is not None,
        }
        if step_s and predicted_s:
            block["step_time_ratio"] = step_s / predicted_s
        if peak_gib and predicted_gib:
            block["hbm_ratio"] = peak_gib / predicted_gib
        if self._plan_calibration:
            block["calibration_runs"] = self._plan_calibration.get("runs")
            block["mfu_effective"] = self._plan_calibration.get("mfu_effective")
        return block

    def record_serving(self, block: dict) -> None:
        """Serving-engine aggregate (serving.py ``engine.stats()``): written
        as a JSONL record and embedded as the summary's ``serving`` block —
        TTFT/TPOT percentiles, queue depth, slot occupancy, tokens/s,
        steady-state recompile census. Last push wins."""
        self._serving_summary = dict(block)
        spec = self._serving_summary.get("speculation")
        if isinstance(spec, dict):
            rate = spec.get("acceptance_rate")
            if rate is not None:
                # Cross-push EMA: single stats() pushes are noisy on short
                # windows; the EMA is the number the autoscaler / perf
                # trajectory should trend on.
                prev = self._spec_accept_ema
                self._spec_accept_ema = (
                    float(rate) if prev is None
                    else 0.9 * prev + 0.1 * float(rate)
                )
            spec = dict(spec)
            spec["acceptance_rate_ema"] = (
                round(self._spec_accept_ema, 6)
                if self._spec_accept_ema is not None else None
            )
            self._serving_summary["speculation"] = spec
        self._write({
            "event": "serving_summary", "step": self.step, "time": time.time(),
            **self._serving_summary,
        })

    def record_disagg(self, block: dict) -> None:
        """Disaggregated-serving aggregate (disagg.py ``stats()["disagg"]``):
        the planner slice plan, per-phase device counts, KV-page handoff
        bytes + sampled latency, and the measured prefill:decode FLOP ratio
        (the number to feed back into ``DisaggConfig`` — the serving twin of
        the plan-calibration loop). Written as a JSONL record and embedded
        as the summary's ``disagg`` block; last push wins."""
        self._disagg_summary = dict(block)
        self._write({
            "event": "disagg_summary", "step": self.step, "time": time.time(),
            **self._disagg_summary,
        })

    def record_autoscale(self, block: dict) -> None:
        """Autoscaling aggregate (autoscale.py ``stats()``): samples,
        decisions split by action (holds/grows/shrinks/resplits), resize vs
        abort counts, flap-damped decisions, and the device census. Written
        as a JSONL record and embedded as the summary's ``autoscale`` block;
        last push wins."""
        self._autoscale_summary = dict(block)
        self._write({
            "event": "autoscale_summary", "step": self.step,
            "time": time.time(), **self._autoscale_summary,
        })

    def record_publish(self, block: dict) -> None:
        """Weight-publication aggregate (publish.py ``stats()``): scans,
        publishes, promotions/rollbacks, BandwidthTable-priced
        redistribution bytes and swap latency. Written as a JSONL record
        and embedded as the summary's ``publish`` block; the outcome tally
        accumulated from ``weights_published`` events is preserved under
        ``by_outcome``. Last push wins."""
        prev = self._publish_summary or {}
        merged = dict(block)
        if "by_outcome" in prev:
            merged["by_outcome"] = dict(prev["by_outcome"])
        if "last_version" in prev and "last_version" not in merged:
            merged["last_version"] = prev["last_version"]
        self._publish_summary = merged
        self.record_event("publish_summary", **{
            k: v for k, v in block.items() if not isinstance(v, (dict, list))
        })

    # -- output ------------------------------------------------------------

    def _write(self, record: dict):
        if self._fh is None:
            os.makedirs(self.output_dir, exist_ok=True)
            # Line-buffered: each record is durable on its newline, so a
            # preempted run keeps every completed step's row.
            self._fh = open(self.path, "a", buffering=1)
        # Clock hygiene: every record carries a monotonic timestamp next to
        # its wall "time". Durations must be computed from t_mono deltas —
        # an NTP step can move time.time() backwards mid-run, and a
        # negative "step time" from subtracted wall clocks has burned real
        # postmortems. (The step/straggler walls in this file are already
        # perf_counter deltas measured by the callers.)
        record.setdefault("t_mono", time.perf_counter())
        self._fh.write(json.dumps(record) + "\n")
        self._maybe_rotate()

    def _maybe_rotate(self):
        """Size-triggered JSONL rotation: a long serving run must not grow
        the per-rank file without bound. One rotation generation
        (``rank_N.jsonl.1``) is kept — crash-safe via os.replace."""
        limit = getattr(self.handler, "max_log_bytes", None)
        if not limit or self._fh is None:
            return
        try:
            if self._fh.tell() < int(limit):
                return
            self._fh.close()
            os.replace(self.path, self.path + ".1")
            self._fh = open(self.path, "a", buffering=1)
            if not self._rotated_once:
                self._rotated_once = True
                logger.warning_once(
                    f"telemetry: {self.path} crossed max_log_bytes="
                    f"{int(limit)} and was rotated to {self.path}.1 — "
                    "raise TelemetryKwargs.max_log_bytes to keep more."
                )
        except OSError as e:
            logger.warning_once(f"telemetry: log rotation failed: {e}")

    def _forward_to_trackers(self, record: dict):
        every = self.handler.log_every
        if not every or self.step % every != 0:
            return
        acc = self.accelerator
        if not getattr(acc, "trackers", None):
            return
        values = {
            "telemetry/step_time_s": record.get("wall_s"),
            "telemetry/data_wait_s": record.get("data_wait_s"),
            "telemetry/recompiles": record.get("recompiles"),
        }
        if record.get("ema_samples_per_s") is not None:
            values["telemetry/samples_per_s"] = record["ema_samples_per_s"]
        if record.get("ema_tokens_per_s") is not None:
            values["telemetry/tokens_per_s"] = record["ema_tokens_per_s"]
        if record.get("hbm_peak_bytes") is not None:
            values["telemetry/hbm_peak_bytes"] = record["hbm_peak_bytes"]
        acc.log({k: v for k, v in values.items() if v is not None}, step=self.step)

    def summary(self) -> dict:
        """Aggregate of everything recorded so far — embedded in bench
        output and written as the final JSONL record by close()."""
        times = np.asarray(self._step_times, np.float64)
        waits = np.asarray(self._data_waits, np.float64)
        out = {
            "steps": int(times.size),
            "recompiles": self.recompiles,
            "peak_hbm_bytes": self._peak_hbm,
            "collectives": collective_counters.snapshot(),
            "checkpoint_events": self._checkpoint_events,
            # Checkpoint cost + fault-tolerance actions (save_s/verify_s/
            # retries land in bench rows so checkpoint-cost regressions show
            # up in the perf trajectory).
            "checkpoint": {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in self._ckpt.items()
            },
        }
        ft = getattr(self.accelerator, "fault_tolerance", None)
        if ft is not None and ft.chaos is not None:
            # Injected-fault census straight from the injector — the
            # authoritative ordered log (chaos.py), not just the events this
            # recorder happened to see.
            out["faults"] = ft.chaos.summary()
        elif self._faults["injected"]:
            out["faults"] = {
                "injected": self._faults["injected"],
                "by_site": dict(sorted(self._faults["by_site"].items())),
            }
        if ft is not None and getattr(ft, "sdc", None) is not None:
            # SDC-sentinel block (sdc.py): digest/vote/probe/repair/
            # quarantine tallies; bench rows embed it next to "faults".
            out["sdc"] = ft.sdc.summary()
        if ft is not None and ft.watchdog is not None:
            # Stall-detection ladder counts + last per-rank ages
            # (fault_tolerance.py StepWatchdog).
            out["watchdog"] = ft.watchdog.summary()
        elif self._watchdog["warnings"] or self._watchdog["stalls"]:
            out["watchdog"] = dict(self._watchdog)
        if self._serving_summary is not None:
            # Serving block (TTFT/TPOT/occupancy/tokens-per-s — serving.py):
            # bench rows embed it like the checkpoint/compile blocks.
            out["serving"] = dict(self._serving_summary)
        if self._reshard_summary is not None:
            # Elastic reshard block (resharding.py): leaves moved, bytes
            # transferred, schedule depth, wall time, staging budget.
            out["reshard"] = dict(self._reshard_summary)
        if self._disagg_summary is not None:
            # Disaggregated-serving block (disagg.py): slice plan + KV-page
            # handoff bytes/latency; bench rows embed it alongside "serving".
            out["disagg"] = dict(self._disagg_summary)
        if self._publish_summary is not None:
            # Weight-publication block (publish.py): publish outcomes,
            # redistribution bytes, swap latency; rides next to "serving".
            out["publish"] = dict(self._publish_summary)
        if self._autoscale_summary is not None:
            # Autoscale block (autoscale.py): decisions, resizes, aborts,
            # flap-damped holds, device census; rides next to "serving".
            out["autoscale"] = dict(self._autoscale_summary)
        plan_block = self.plan_block()
        if plan_block is not None:
            # Auto-parallelism plan block (planner.py): predicted vs
            # measured step time / peak HBM + calibration state.
            out["plan"] = plan_block
        if self.tracing is not None:
            # Tracing block (tracing.py): span/request/flow census — the
            # aggregate face of the per-request span machinery.
            out["tracing"] = self.tracing.stats()
        if self.profiler is not None:
            # Device-time attribution block (profiler.py): term means,
            # measured comm/compute overlap ratio, per-axis bandwidth
            # residuals against the BandwidthTable, flight-ring census.
            out["profile"] = self.profiler.summary()
        # Executable census: total dispatch-cache size across the watched
        # jitted fns — the number shape bucketing caps at len(buckets).
        sizes = [e["cache_size"] for e in self._watch.values() if e["cache_size"]]
        if sizes:
            out["executables"] = int(sum(sizes))
        cm = getattr(self.accelerator, "compile_manager", None)
        if cm is not None:
            # Bucket/warmup/persistent-cache stats (hit-miss counters live
            # under "persistent_cache") from the compile manager.
            out["compile"] = cm.summary()
        if times.size:
            out.update(
                step_time_mean_s=float(times.mean()),
                step_time_p50_s=float(np.percentile(times, 50)),
                step_time_p90_s=float(np.percentile(times, 90)),
                data_wait_mean_s=float(waits.mean()) if waits.size else 0.0,
                ema_samples_per_s=self._ema_samples,
                ema_tokens_per_s=self._ema_tokens,
            )
        return out

    def _hub_stats(self) -> dict:
        """The cheap scalar face of this recorder for the MetricsHub's
        Prometheus rendering (``accelerate_tpu_telemetry_*``) — deliberately
        NOT summary(), which walks percentiles on every call."""
        return {
            "steps": self.step,
            "recompiles": self.recompiles,
            "peak_hbm_bytes": self._peak_hbm or 0,
            "checkpoint_events": self._checkpoint_events,
        }

    def close(self):
        # A short run that never reached calibrate_after still calibrates on
        # the way out — partial measurements beat none for the next launch.
        self._maybe_calibrate_plan(final=True)
        if self.profiler is not None:
            # Finalize the lagged attribution records so the summary (and
            # any flight dump after this point) covers the last step/tick.
            self.profiler.flush()
        if self._fh is not None:
            self._write({"event": "summary", "time": time.time(), **self.summary()})
            self._fh.close()
            self._fh = None
        collective_counters.enabled = False
