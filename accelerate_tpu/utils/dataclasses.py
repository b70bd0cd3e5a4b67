"""Plugin dataclasses & kwargs handlers (layer L2).

Re-design of the reference's ``utils/dataclasses.py`` (3226 LoC of torch
plugin plumbing, reference: src/accelerate/utils/dataclasses.py). The torch
backend zoo (DDP kwargs, FSDP plugin, DeepSpeed plugin, Megatron plugin)
collapses on TPU into *sharding and precision choices* consumed by the
Accelerator when it builds mesh + PartitionSpecs + the jitted step. We keep
the reference's config surface (field names, env-var decode) so launch
configs translate, but each plugin's payload is a JAX-native policy.
"""

from __future__ import annotations

import copy
import dataclasses
import enum
import functools
import os
import warnings
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Optional

import jax.numpy as jnp

from .environment import parse_choice_from_env, parse_flag_from_env, str_to_bool


class KwargsHandler:
    """Base: ``to_kwargs()`` returns the diff vs default values
    (reference: utils/dataclasses.py:70-89)."""

    def to_dict(self):
        return copy.deepcopy(self.__dict__)

    def to_kwargs(self):
        default_dict = self.__class__().to_dict()
        this_dict = self.to_dict()
        return {k: v for k, v in this_dict.items() if default_dict[k] != v}


class EnumWithContains(enum.EnumMeta):
    def __contains__(cls, item):
        try:
            cls(item)
        except ValueError:
            return False
        return True


class BaseEnum(str, enum.Enum, metaclass=EnumWithContains):
    def __str__(self):
        return self.value

    @classmethod
    def list(cls):
        return list(map(str, cls))


class PrecisionType(BaseEnum):
    NO = "no"
    BF16 = "bf16"
    FP16 = "fp16"
    FP8 = "fp8"


class ComputeEnvironment(BaseEnum):
    LOCAL_MACHINE = "LOCAL_MACHINE"
    TPU_POD = "TPU_POD"


class LoggerType(BaseEnum):
    """Tracker identifiers accepted by ``Accelerator(log_with=...)``
    (reference: utils/dataclasses.py LoggerType). Plain strings work too —
    ``filter_trackers`` (tracking.py) resolves either."""

    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    MLFLOW = "mlflow"
    COMETML = "comet_ml"
    AIM = "aim"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"
    SWANLAB = "swanlab"
    TRACKIO = "trackio"


class SaveFormat(BaseEnum):
    SAFETENSORS = "safetensors"
    ORBAX = "orbax"
    MSGPACK = "msgpack"


DTYPE_MAP = {
    "no": jnp.float32,
    "fp32": jnp.float32,
    "bf16": jnp.bfloat16,
    "fp16": jnp.float16,
    "fp8": jnp.float8_e4m3fn,
}


@dataclass
class MixedPrecisionPolicy(KwargsHandler):
    """What dtype each tensor class uses inside the jitted step.

    TPU-native replacement for torch autocast + GradScaler + FSDP
    MixedPrecisionPolicy (reference: accelerator.py:561-612,
    utils/fsdp_utils.py:861-870). Params and optimizer state stay fp32 master
    copies; compute and activations run in ``compute_dtype``; gradients are
    reduced in ``reduce_dtype``.
    """

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    reduce_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32

    @classmethod
    def from_mixed_precision(cls, mixed_precision: str) -> "MixedPrecisionPolicy":
        if mixed_precision in (None, "no"):
            return cls(compute_dtype=jnp.float32)
        if mixed_precision == "bf16":
            return cls(compute_dtype=jnp.bfloat16)
        if mixed_precision == "fp16":
            # fp16 on TPU still reduces in fp32; dynamic loss scaling is
            # handled by the step builder when fp16 is requested.
            return cls(compute_dtype=jnp.float16)
        if mixed_precision == "fp8":
            return cls(compute_dtype=jnp.bfloat16)  # fp8 applies per-matmul via recipe
        raise ValueError(f"Unknown mixed precision {mixed_precision}")

    def cast_for_compute(self, tree):
        import jax

        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """(reference: utils/dataclasses.py:1120-1160)"""

    num_steps: int = None
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False


@dataclass
class GradScalerKwargs(KwargsHandler):
    """Dynamic loss-scaling config for fp16 (reference:
    utils/dataclasses.py:242-270). On TPU bf16 needs no scaling; this exists
    for fp16 parity and is implemented in pure JAX inside the step."""

    init_scale: float = 65536.0
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    enabled: bool = True


@dataclass
class InitProcessGroupKwargs(KwargsHandler):
    """(reference: utils/dataclasses.py:272-310) — maps to
    jax.distributed.initialize timeouts."""

    backend: Optional[str] = "xla"
    init_method: Optional[str] = None
    timeout: Optional[timedelta] = None


@dataclass
class DistributedDataParallelKwargs(KwargsHandler):
    """Reference: utils/dataclasses.py:157-241. Under GSPMD there is no DDP
    reducer to configure — gradient mean is a single psum the compiler
    schedules — so the bucketing knobs are advisory no-ops. ``comm_hook``
    IS live: it routes the step through a ``shard_map``-controlled gradient
    sync (parallel/comm_hooks.py) replacing the psum with fp16/bf16 wire
    compression or PowerSGD rank-``powersgd_rank`` low-rank reduction with
    error feedback — for DCN-spanning data-parallel meshes where the grad
    all-reduce can't hide behind compute. DDP (replicated-param) meshes
    only; pass via ``Accelerator(kwargs_handlers=[...])``."""

    bucket_cap_mb: int = 25
    find_unused_parameters: bool = False
    gradient_as_bucket_view: bool = False
    static_graph: bool = False
    comm_hook: str = "no"  # no | fp16 | bf16 | powersgd
    powersgd_rank: int = 8  # reference: matrix_approximation_rank state option


@dataclass
class AutocastKwargs(KwargsHandler):
    """(reference: utils/dataclasses.py:311-340)"""

    enabled: bool = True
    cache_enabled: bool = None


class FP8Format(BaseEnum):
    E4M3 = "E4M3"
    E5M2 = "E5M2"
    HYBRID = "HYBRID"


@dataclass
class FP8RecipeKwargs(KwargsHandler):
    """fp8 matmul recipe (reference: TERecipeKwargs/AORecipeKwargs,
    utils/dataclasses.py:312-484). On TPU this selects XLA float8 dots:
    activations/weights quantized per-tensor with delayed or current scaling,
    master weights bf16/fp32.

    ``backend`` mirrors the reference's AO→TE→MSAMP auto-pick
    (reference: accelerator.py:478-503): "TE" and "AO" both map to the
    native float8-operand dot path (ops/fp8.py ``_f8_dot`` — TE's HYBRID
    GEMM recipe and torchao's dynamic-scaling Float8Linear are the same
    computation under XLA), "QDQ" forces the quantize-dequantize
    formulation, and "AUTO" lets the platform decide. "MSAMP" raises:
    MS-AMP is deprecated upstream and deliberately dropped here (see
    COVERAGE.md, deliberate drops)."""

    fp8_format: str = "HYBRID"  # E4M3 fwd / E5M2 bwd when HYBRID
    backend: str = "AUTO"       # AUTO | TE | AO | QDQ (MSAMP: rejected)
    amax_history_len: int = 16
    amax_compute_algo: str = "max"
    margin: int = 0
    use_during_eval: bool = False

    def __post_init__(self):
        self.fp8_format = self.fp8_format.upper()
        if self.fp8_format not in FP8Format.list():
            raise ValueError(f"fp8_format must be one of {FP8Format.list()}")
        self.backend = self.backend.upper()
        from ..ops.fp8 import backend_to_native

        backend_to_native(self.backend)  # validates (MSAMP rejected here)

    @property
    def native_dots(self) -> "bool | None":
        """None = platform default (ACCELERATE_FP8_NATIVE env)."""
        from ..ops.fp8 import backend_to_native

        return backend_to_native(self.backend)


@dataclass
class ProfileKwargs(KwargsHandler):
    """jax.profiler configuration (reference: utils/dataclasses.py:486-601
    wraps torch.profiler)."""

    activities: Optional[list] = None
    schedule_option: Optional[dict] = None
    on_trace_ready: Optional[Callable] = None
    record_shapes: bool = False
    profile_memory: bool = False
    with_stack: bool = False
    with_flops: bool = False
    output_trace_dir: Optional[str] = None


@dataclass
class TelemetryKwargs(KwargsHandler):
    """Step-level telemetry config (telemetry.py). Passing this handler to
    ``Accelerator(kwargs_handlers=[...])`` turns the subsystem on; without it
    no recorder exists and every hook is a single ``None`` check.

    - ``sync_timing``: block on the step's metrics before stopping the step
      timer. Exact per-step device wall time, but it defeats async dispatch —
      leave False (dispatch wall; converges to the true step time once the
      device queue applies backpressure) for production loops.
    - ``log_every``: forward the smoothed summary into the tracker stack via
      ``Accelerator.log()`` every N steps (main process; 0 disables).
    - ``straggler_probe_every``: allgather step times across ranks every N
      steps and record max/min skew (0 disables).
    - ``memory_every``: sample device-memory stats every N steps (some
      backends make ``memory_stats()`` a sync point).
    - ``output_dir``: JSONL destination; default ``<project_dir>/telemetry``.
    - ``max_log_bytes``: size-triggered rotation bound for the per-rank
      JSONL — when the live file crosses it, it is renamed to
      ``<name>.jsonl.1`` (replacing any previous rotation) and a fresh
      file starts, with a one-time warning. Generous but finite by
      default; ``None``/0 disables rotation.
    - ``tracing``: request-scoped tracing (tracing.py). ``True`` (default
      recorder), a dict of :class:`~accelerate_tpu.tracing.TraceConfig`
      field overrides, or a ``TraceConfig``. The recorder lands on
      ``telemetry.tracing``, serving engines built through the
      accelerator inherit it, and ``summary()`` gains a ``"tracing"``
      block. Off (None) means zero cost: every hook is one ``is None``
      check.
    - ``profile``: device-time attribution (profiler.py). ``True``
      (default :class:`~accelerate_tpu.profiler.ProfilerConfig`), a dict
      of field overrides, or a ``ProfilerConfig``. The profiler lands on
      ``telemetry.profiler``, ``summary()`` gains a ``"profile"`` block
      (exactly-summing per-step terms, comm/compute overlap ratio,
      BandwidthTable residuals), and abnormal exits dump its flight ring
      as ``flight_<exit_class>.json``. Attribution is lagged one step —
      zero extra device syncs; off (None) is the same zero-cost contract
      as ``tracing``.
    """

    enabled: bool = True
    sync_timing: bool = False
    log_every: int = 10
    straggler_probe_every: int = 50
    straggler_warn_skew: float = 0.2
    ema_alpha: float = 0.1
    memory_every: int = 1
    output_dir: Optional[str] = None
    max_log_bytes: Optional[int] = 256 * 1024 * 1024
    tracing: Any = None
    profile: Any = None


@dataclass
class FaultToleranceKwargs(KwargsHandler):
    """Fault-tolerance config (fault_tolerance.py). Passing this handler to
    ``Accelerator(kwargs_handlers=[...])`` turns the subsystem on; without it
    ``accelerator.fault_tolerance`` is ``None``, every hook site is a single
    ``None`` check, and the checkpoint byte layout is unchanged.

    Four pillars (docs/usage_guides/fault_tolerance.md):

    - **Atomic verified checkpoints**: every save writes into a
      ``checkpoint_N.tmp`` staging dir, fsyncs, emits a ``manifest.json``
      (per-file sizes + checksums + world size + step) and renames to
      ``checkpoint_N`` as the commit point. ``load_state()`` walks
      newest→oldest and restores the newest checkpoint whose manifest
      verifies, skipping torn ones. ``total_limit`` pruning runs *after* the
      commit, so a failed save can never destroy the only good checkpoint.
      ``checksum``: ``"sha256"`` hashes every byte; ``"size"`` checks
      existence + size only (for multi-TB checkpoints where hashing
      dominates save time).
    - **Preemption-aware auto-save**: SIGTERM/SIGUSR1 handlers installed at
      ``prepare()`` set a flag the training loop observes via
      ``accelerator.should_checkpoint()`` (local, free) or
      ``accelerator.check_preemption()`` (collective — rank-coherent on
      multi-host meshes). After the final save, exit with
      ``utils.constants.PREEMPTION_EXIT_CODE`` — the launch gang loop treats
      it as resumable and relaunches with ``ACCELERATE_RESTART_ATTEMPT`` set
      so elastic auto-resume continues the run.
    - **Save retry**: transient storage errors (OSError / TensorStore
      failures) retry ``save_retries`` times with jittered exponential
      backoff (``retry_backoff_s`` doubling up to ``retry_backoff_max_s``)
      before falling back to ``fallback_dir`` when configured.
    - **Divergence sentinel**: watches the step metrics (loss + grad norm,
      fetched one step lagged so the watch never stalls async dispatch) for
      ``sentinel_window`` consecutive nonfinite or exploding
      (> ``sentinel_explode_factor`` × EMA) steps. Policy ``"warn"`` logs +
      records the episode, ``"halt"`` raises :class:`DivergenceError`,
      ``"rollback"`` restores the newest *verified* checkpoint (at most
      ``max_rollbacks`` times) and re-primes RNG/dataloader state so the run
      resumes deterministically. ``"off"`` disables the watch entirely.

    Two more pillars ride on the same manager (default off):

    - **Chaos injection** (``chaos``): a
      :class:`~accelerate_tpu.chaos.FaultInjector` (or its constructor
      kwargs as a dict) drives deterministic training-side faults —
      ``train_step``/``nonfinite_grad``/``slow_step``,
      ``checkpoint_save``/``torn_write``, ``dataloader_batch``/
      ``corrupt_batch``, ``host_heartbeat``/``dead_host`` — through the
      SAME recovery paths real failures take (sentinel → rollback, save
      retry → fallback, exit → gang relaunch). ``None`` (default) keeps
      every hook a single ``None`` check.
    - **SDC sentinel** (``sdc``): an
      :class:`~accelerate_tpu.sdc.SDCConfig` (or its constructor kwargs as
      a dict) arms the silent-data-corruption defenses — every step
      fingerprints the new params + grad norm inside the jitted step (one
      fused reduction riding the existing metrics fetch, one step lagged),
      every ``vote_every`` steps the dp replicas allgather and
      majority-vote the digests bit-wise, and a mismatch triggers the
      redundant-compute probe on a golden batch to classify *transient*
      (repair in place: rollback or majority broadcast) vs *sticky* (bad
      silicon: quarantine the host on disk, exit
      ``utils.constants.SDC_EXIT_CODE`` so the supervisor relaunches the
      gang SHRUNK without it). Independent of the divergence ``sentinel``
      policy — SDC is finite-but-wrong, invisible to nonfinite checks.
      ``None`` (default) keeps every hook a single ``None`` check.
    - **Step watchdog** (``watchdog``): a host-side thread + lagged
      per-step notes detecting a progress-free or straggling gang. A step
      older than ``watchdog_warn_s`` emits a ``training_stalled`` telemetry
      event (per-rank last-step ages, straggler named); past
      ``watchdog_stall_s`` the policy escalates — ``"warn"`` keeps logging,
      ``"error"`` raises :class:`~accelerate_tpu.fault_tolerance.
      TrainingStalledError` at the next completed step, ``"preempt"``
      self-preempts (SIGTERM → preemption save if the loop is alive, then
      hard-exits ``TRAINING_STALLED_EXIT_CODE`` after a grace period so the
      supervisor relaunches from the newest verified checkpoint). With
      ``watchdog_heartbeat_every`` > 0 and a multi-process gang, every N
      steps the ranks allgather (step, age) over the ``agree_any``-style
      channel so a stalled PEER is detected and named too.

    All events (save retries, torn checkpoints skipped, preemption saves,
    rollbacks, injected faults, stall warnings) flow into the telemetry
    JSONL when a :class:`TelemetryKwargs` handler is also present.
    """

    enabled: bool = True
    atomic_checkpoints: bool = True
    verify_on_load: bool = True
    checksum: str = "sha256"  # sha256 | size
    save_retries: int = 3
    retry_backoff_s: float = 0.5
    retry_backoff_max_s: float = 8.0
    fallback_dir: Optional[str] = None
    install_signal_handlers: bool = True
    preemption_signals: tuple = ("SIGTERM", "SIGUSR1")
    sentinel: str = "warn"  # off | warn | halt | rollback
    sentinel_window: int = 3
    sentinel_explode_factor: float = 10.0
    sentinel_ema_alpha: float = 0.1
    max_rollbacks: int = 2
    chaos: Optional[object] = None  # FaultInjector | dict of its kwargs
    sdc: Optional[object] = None  # sdc.SDCConfig | dict of its kwargs
    watchdog: str = "off"  # off | warn | error | preempt
    watchdog_warn_s: float = 60.0
    watchdog_stall_s: float = 300.0
    watchdog_poll_s: float = 1.0
    watchdog_heartbeat_every: int = 0  # steps between gang heartbeats (0 off)
    watchdog_grace_s: float = 30.0  # preempt policy: SIGTERM → hard-exit gap

    def __post_init__(self):
        if self.checksum not in ("sha256", "size"):
            raise ValueError("checksum must be sha256|size")
        if self.sentinel not in ("off", "warn", "halt", "rollback"):
            raise ValueError("sentinel must be off|warn|halt|rollback")
        if self.sentinel_window < 1:
            raise ValueError("sentinel_window must be >= 1")
        if self.watchdog not in ("off", "warn", "error", "preempt"):
            raise ValueError("watchdog must be off|warn|error|preempt")
        if self.watchdog_warn_s <= 0 or self.watchdog_stall_s <= 0:
            raise ValueError("watchdog_warn_s/watchdog_stall_s must be > 0")
        if self.watchdog_stall_s < self.watchdog_warn_s:
            raise ValueError(
                "watchdog_stall_s must be >= watchdog_warn_s (warn first, "
                "then escalate)"
            )
        if self.watchdog_poll_s <= 0:
            raise ValueError("watchdog_poll_s must be > 0")
        if self.watchdog_heartbeat_every < 0:
            raise ValueError("watchdog_heartbeat_every must be >= 0")
        if self.sdc is not None and not isinstance(self.sdc, dict):
            # Lazy check (sdc.py imports jax at digest time): accept an
            # SDCConfig instance or a dict of its kwargs.
            if type(self.sdc).__name__ != "SDCConfig":
                raise ValueError(
                    "sdc must be an accelerate_tpu.sdc.SDCConfig or a dict "
                    f"of its kwargs, got {type(self.sdc).__name__}"
                )


@dataclass
class ElasticKwargs(KwargsHandler):
    """Elastic-resharding config (resharding.py). Passing this handler to
    ``Accelerator(kwargs_handlers=[...])`` turns the subsystem on; without it
    ``accelerator.elastic`` is ``None``, every hook site is a single ``None``
    check, and a topology-mismatched restore raises
    :class:`~accelerate_tpu.resharding.TopologyMismatchError` instead of
    resharding.

    - **Elastic restore** (``elastic_restore``): a checkpoint written on N
      devices restores on M≠N through a planned redistribution schedule —
      each leaf ingested under its *source* sharding spec (projected onto
      the new mesh) and redistributed on-device, batched so per-device bytes
      in flight never exceed ``staging_budget_mb``. Leaves that cannot fit
      even alone fall back to host-staged chunked ingest when
      ``host_stage_oversize`` is on.
    - **Live migration**: :meth:`Accelerator.migrate_plan` reshards the
      prepared ``TrainState`` (donated buffers; RNG, dataloader cursor and
      grad-accum state carried over) onto a new plan/layout mid-run and
      invalidates + optionally re-warms (``warm_after_migrate``) the
      compile-manager executables for the new shapes.
    - **Resize policy** (``resize_policy``): what an elastic relaunch
      (``ACCELERATE_RESTART_ATTEMPT`` > 0) does when it comes back on a
      different device count. ``"replan"`` re-runs the planner search under
      the new topology — pinning the model-parallel axes the calibration
      data says are winning when ``pin_winning_axes`` is on; ``"keep"``
      keeps the checkpoint's layout scaled to the new count; ``"fail"``
      refuses (same error as elastic off).
    """

    enabled: bool = True
    elastic_restore: bool = True
    staging_budget_mb: float = 256.0
    host_stage_oversize: bool = True
    resize_policy: str = "replan"  # replan | keep | fail
    pin_winning_axes: bool = True
    warm_after_migrate: bool = True

    def __post_init__(self):
        if self.resize_policy not in ("replan", "keep", "fail"):
            raise ValueError("resize_policy must be replan|keep|fail")
        if self.staging_budget_mb <= 0:
            raise ValueError("staging_budget_mb must be > 0")


@dataclass
class CompileKwargs(KwargsHandler):
    """Compile-manager config (compile_manager.py). Passing this handler to
    ``Accelerator(kwargs_handlers=[...])`` turns the subsystem on; without it
    ``accelerator.compile_manager`` is ``None`` and every hook site is a
    single ``None`` check (behavior byte-identical to the unmanaged path).

    - ``buckets``: shape-bucket policy applied at the device boundary.
      ``"pow2"`` rounds ragged dims up the power-of-two ladder, ``"fixed"``
      uses the explicit ``batch_buckets``/``seq_buckets`` ladders, ``"auto"``
      builds the ladder from the shapes manifest (previously observed shapes;
      falls back to pow2 for unseen sizes), ``None`` disables bucketing but
      keeps warmup + cache control.
    - ``bucket_batch`` / ``bucket_seq``: which dims get bucketed (axis 0 of
      every array leaf; axis 1 of rank>=2 leaves). The batch dim of a loader
      batch is padded to the loader's OWN batch size first, so the ragged
      final ``drop_last=False`` batch stops costing a one-off recompile each
      epoch.
    - ``min_bucket`` / ``max_bucket``: pow2-ladder floor and cap. A dim past
      ``max_bucket`` (or off a fixed ladder) falls through with a one-time
      warning and ships its true shape.
    - ``batch_pad_mode``: ``"repeat"`` cycles real samples (the semantics
      ``even_batches`` already gives the final batch; duplicates are trimmed
      by ``gather_for_metrics``) or ``"zero"``. Sequence padding always
      zero-fills with ``seq_pad_value``.
    - ``emit_mask``: on dict batches, ALWAYS add a ``mask_key`` leaf
      (1.0 = real element) so masked losses can ignore padding without the
      batch structure — and the compiled signature — ever changing.
    - ``warmup``: ``"execute"`` (default) runs the real jitted step on a
      copy of the train state per manifest signature (the only mode that
      populates jit's dispatch cache — zero recompiles after warmup);
      ``"aot"`` does ``lower(abstract).compile()`` (primes the persistent
      cache only); ``"off"`` disables. ``warmup_calls`` executions per
      signature absorb the second-call recompile a mesh can cost (default 2).
    - ``manifest_path``: shapes-manifest override; default
      ``<project_dir>/compile_cache/shapes_manifest.jsonl``.
    - ``cache_budget_bytes``: LRU prune budget for the persistent executable
      cache (falls back to ``JitConfig.persistent_cache_budget_bytes``).
    """

    enabled: bool = True
    buckets: Optional[str] = "pow2"  # pow2 | fixed | auto | None
    bucket_batch: bool = True
    bucket_seq: bool = True
    batch_buckets: Optional[list] = None
    seq_buckets: Optional[list] = None
    min_bucket: int = 8
    max_bucket: Optional[int] = None
    batch_pad_mode: str = "repeat"  # repeat | zero
    seq_pad_value: int = 0
    emit_mask: bool = False
    mask_key: str = "pad_mask"
    warmup: str = "execute"  # execute | aot | off
    warmup_calls: int = 2
    manifest_path: Optional[str] = None
    cache_budget_bytes: Optional[int] = None

    def __post_init__(self):
        if self.buckets not in (None, "pow2", "fixed", "auto"):
            raise ValueError("buckets must be one of pow2|fixed|auto|None")
        if self.batch_pad_mode not in ("repeat", "zero"):
            raise ValueError("batch_pad_mode must be repeat|zero")
        if self.warmup not in ("execute", "aot", "off"):
            raise ValueError("warmup must be execute|aot|off")


@dataclass
class AutoPlanKwargs(KwargsHandler):
    """Auto-parallelism planner config (planner.py). Passing this handler to
    ``Accelerator(kwargs_handlers=[...])`` — or passing
    ``Accelerator(parallelism_config="auto")`` — turns the subsystem on: the
    first ``prepare()`` call resolves a :class:`~accelerate_tpu.planner.ParallelPlan`
    for the prepared model (cached under ``<project_dir>/plans/``), installs
    its layout as the ``ParallelismConfig``, applies its remat policy, and —
    when a :class:`TelemetryKwargs` handler is also present — writes measured
    step time / peak HBM back into the plan artifact after
    ``calibrate_after`` steps so repeated runs tighten the cost model.
    Without the handler (and without ``"auto"``) nothing changes: no planner
    code runs and ``Accelerator`` behavior is byte-identical.

    - ``hbm_gib``: per-chip HBM budget the plan must fit (v5e: 16).
    - ``seq`` / ``per_chip_batch``: the training shape the plan is priced
      for. ``per_chip_batch`` is samples per chip at pure data parallelism —
      the global batch is ``per_chip_batch × device count`` for every
      candidate layout, so predicted step times compare.
    - ``axes``: mesh axes the search may raise above 1. Defaults to
      ``(dp_replicate, dp_shard, tp)`` — cp/pp/ep layouts need model/loss
      support the auto path cannot verify; enable them explicitly (the
      ``accelerate-tpu plan`` CLI searches all axes by default).
    - ``pinned``: axis → degree overrides the search must honor
      (``{"tp": 2}``); the rejection log shows what pinning cost.
    - ``bandwidths``: dict overriding :class:`~accelerate_tpu.planner.BandwidthTable`
      fields (ici_gbps, dcn_gbps, flops_per_chip, mfu, ...).
    - ``plans_dir``: artifact directory; default ``<project_dir>/plans``.
    - ``use_cache``: load a cached plan for identical inputs instead of
      re-searching (the cache key hashes every search input).
    - ``calibrate_after``: telemetry writes measured-vs-predicted step time
      and peak HBM into the plan after this many steps (0 disables).
    - ``apply_remat`` / ``apply_microbatches``: let the resolved plan flip
      ``config.remat`` on the prepared module / set gradient accumulation to
      the plan's microbatch count. Disable to treat the plan as advisory.
    """

    enabled: bool = True
    hbm_gib: float = 16.0
    seq: int = 2048
    per_chip_batch: int = 1
    optimizer: str = "adamw"
    axes: tuple = ("dp_replicate", "dp_shard", "tp")
    pinned: Optional[dict] = None
    bandwidths: Optional[dict] = None
    plans_dir: Optional[str] = None
    use_cache: bool = True
    calibrate_after: int = 10
    apply_remat: bool = True
    apply_microbatches: bool = True

    def __post_init__(self):
        if self.hbm_gib <= 0:
            raise ValueError(f"hbm_gib must be > 0, got {self.hbm_gib}")
        if self.seq < 1 or self.per_chip_batch < 1:
            raise ValueError("seq and per_chip_batch must be >= 1")
        from ..planner import ALL_SEARCH_AXES

        bad = set(self.axes) - set(ALL_SEARCH_AXES)
        if bad:
            raise ValueError(
                f"unknown search axes {sorted(bad)}; valid: {list(ALL_SEARCH_AXES)}"
            )


@dataclass
class ServingConfig(KwargsHandler):
    """Continuous-batching serving engine config (serving.py). OFF by
    default everywhere: nothing constructs a
    :class:`~accelerate_tpu.serving.ServingEngine` unless you do — the
    training path and plain ``generate()`` callers never touch serving
    code. Passing this handler to ``Accelerator(kwargs_handlers=[...])``
    only stores it (``accelerator.serving_config``) so
    ``accelerator.build_serving_engine(model)`` can construct an engine
    wired to the compile manager and telemetry recorder.

    - ``n_slots``: concurrent sequences — the KV cache is one dense buffer
      pair holding ``max_len`` private rows for each slot (no pages, no
      sharing between slots); one decode tick advances every live slot. Size it to the HBM left after params: bigger = higher
      aggregate tokens/s, until the decode step goes compute-bound.
    - ``max_len``: per-slot capacity (prompt + continuation); default
      ``min(max_position_embeddings, 4096)``. ``submit`` rejects requests
      that cannot fit.
    - ``prefill_chunks``: explicit chunk-size ladder for chunked prefill;
      default: the compile manager's seq buckets when one is wired,
      else pow2 ``min_prefill_chunk..max_prefill_chunk``. Every possible
      prompt length compiles at most ``len(ladder)`` prefill executables.
    - ``prefill_chunks_per_tick``: prompt chunks interleaved per decode
      tick — raise to admit long prompts faster at some decode-latency
      cost (head-of-line control knob).
    - ``temperature`` / ``top_k`` / ``top_p`` / ``eos_token_id`` /
      ``pad_token_id``: sampling settings, engine-wide (the compiled decode
      step bakes them in). ``max_new_tokens`` is the default per-request
      budget; ``submit``/``run`` override it per request.
    - ``cache_dtype``: KV-cache dtype override (default: model dtype).
      ``jnp.int8`` switches the slot cache to quantized KV pages
      (``kv_cache.QuantPages``: int8 data + an absmax scale per row and head) —
      attention dequantizes in-kernel and disagg handoff moves ~4x fewer
      bytes; see docs/usage_guides/serving.md "Quantized KV pages".
    - ``seed``: seeds the idle slots' PRNG pool; each request's stream is
      the ``rng`` passed at ``submit`` (default ``jax.random.key(0)``).
    - ``speculate_k``: speculative decoding — self-draft ``k`` tokens per
      slot per tick from an n-gram history match and verify all ``k+1``
      positions in ONE batched forward inside the same single jitted
      decode program (static ``(n_slots, k+1)`` shapes, so the
      zero-recompile invariant holds). ``0`` (default) keeps the plain
      one-token tick. Greedy output is bit-equal to non-speculative
      decode; sampled output draws through exact-distribution rejection
      sampling. See docs/usage_guides/serving.md "Speculative decoding".
    - ``speculate_ngram``: per-slot token-history window the self-draft
      matches against (the draft "model" capacity; >= 2).

    Admission control + SLOs (every request terminates with an explicit
    ``status`` in ``poll()`` results — ``ok | timeout | shed | failed``;
    see docs/usage_guides/serving.md "Serving under faults"):

    - ``max_queue_depth``: bound on the admission queue; ``None`` (default)
      keeps the unbounded pre-SLO behavior. When the bound is hit,
      ``overload_policy`` decides: ``"reject"`` sheds the NEW request
      immediately (status ``shed``), ``"shed_oldest"`` drops the oldest
      queued request to make room, ``"block"`` ticks the engine inside
      ``submit()`` until a queue slot frees (the hang guard still bounds a
      wedged engine).
    - ``deadline_s``: default per-request deadline, measured from
      ``submit()`` (override per request). Deadline checks run every tick;
      a timed-out request frees its slot immediately and finishes with
      status ``timeout``.
    - ``max_retries``: per-request recovery budget — how many times a
      request may be re-queued after a fault (poisoned slot, failed
      handoff, dead lane) before it finishes with status ``failed``.
      Resubmission is idempotent: the prompt + rng payload make the retry
      bit-equal to a fresh submit.
    - ``max_idle_ticks``: hang guard — after this many consecutive ticks
      with pending requests but no admission, prefill progress, live
      decode, or retirement, the engine raises
      :class:`~accelerate_tpu.serving.ServingStalledError` naming the stuck
      requests instead of spinning forever.
    - ``window_requests``: size of the rolling SLO window behind
      ``stats()["window"]`` (last N terminal requests + N per-tick
      queue-depth samples). Lifetime percentiles average the whole run, so
      a long healthy prefix masks a current breach; the autoscaler
      (autoscale.py) and canary gates read this window instead.

    Crash durability (journal.py — see docs/usage_guides/serving.md
    "Surviving engine crashes"):

    - ``journal_dir``: directory for the write-ahead request journal;
      ``None`` (default) keeps journaling fully off. With it set, every
      admission / progress batch / terminal status is durably logged and
      ``ServingEngine.recover()`` rebuilds the queue after a process death:
      completed requests return their cached rows (exactly-once — never
      re-executed), in-flight requests replay bit-equal from the journaled
      prompt + rng.
    - ``journal_fsync``: durability policy — ``"every_record"`` (fsync per
      append), ``"every_tick"`` (one fsync per engine tick; the default),
      or ``"os"`` (flush to the page cache only — survives a process crash,
      not host power loss).
    - ``journal_segment_records``: appends per WAL segment before rotation
      (seal + compaction of the sealed set).
    """

    enabled: bool = True
    n_slots: int = 8
    max_len: Optional[int] = None
    max_new_tokens: int = 32
    prefill_chunks: Optional[list] = None
    min_prefill_chunk: int = 16
    max_prefill_chunk: int = 256
    prefill_chunks_per_tick: int = 1
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    pad_token_id: Optional[int] = None
    cache_dtype: Any = None
    seed: int = 0
    speculate_k: int = 0
    speculate_ngram: int = 16
    max_queue_depth: Optional[int] = None
    overload_policy: str = "reject"
    deadline_s: Optional[float] = None
    max_retries: int = 2
    max_idle_ticks: int = 100
    window_requests: int = 128
    journal_dir: Optional[str] = None
    journal_fsync: str = "every_tick"
    journal_segment_records: int = 512

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.prefill_chunks_per_tick < 1:
            raise ValueError("prefill_chunks_per_tick must be >= 1")
        if self.min_prefill_chunk < 1 or self.max_prefill_chunk < self.min_prefill_chunk:
            raise ValueError(
                "need 1 <= min_prefill_chunk <= max_prefill_chunk, got "
                f"{self.min_prefill_chunk}..{self.max_prefill_chunk}"
            )
        if self.overload_policy not in ("reject", "shed_oldest", "block"):
            raise ValueError(
                "overload_policy must be 'reject', 'shed_oldest', or "
                f"'block', got {self.overload_policy!r}"
            )
        if self.max_queue_depth is not None and self.max_queue_depth < 1:
            raise ValueError("max_queue_depth must be >= 1 (or None)")
        if self.deadline_s is not None and not self.deadline_s > 0:
            raise ValueError("deadline_s must be > 0 (or None)")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.max_idle_ticks < 1:
            raise ValueError("max_idle_ticks must be >= 1")
        if self.window_requests < 1:
            raise ValueError("window_requests must be >= 1")
        if self.journal_fsync not in ("every_record", "every_tick", "os"):
            raise ValueError(
                "journal_fsync must be 'every_record', 'every_tick', or "
                f"'os', got {self.journal_fsync!r}"
            )
        if self.journal_segment_records < 1:
            raise ValueError("journal_segment_records must be >= 1")
        if self.speculate_k < 0:
            raise ValueError("speculate_k must be >= 0")
        if self.speculate_ngram < 2:
            raise ValueError("speculate_ngram must be >= 2")


@dataclass
class DisaggConfig(KwargsHandler):
    """Disaggregated-serving config (disagg.py). OFF by default everywhere:
    nothing splits the device set unless you construct a
    :class:`~accelerate_tpu.disagg.DisaggServingEngine` — directly, or by
    passing this handler to ``Accelerator(kwargs_handlers=[...])`` so
    ``accelerator.build_serving_engine(model)`` upgrades the colocated
    engine to the two-mesh router. Training and the colocated serving path
    never touch this.

    - ``n_prefill_devices``: pin the prefill-slice size; default ``None``
      lets :func:`~accelerate_tpu.planner.plan_disagg_slices` size it from
      the prefill:decode FLOP ratio against the planner's BandwidthTable.
    - ``prefill_decode_flop_ratio``: measured prefill:decode FLOP ratio per
      request. Default ``None`` estimates it as
      ``expected_prompt_tokens / max_new_tokens`` (both phases cost ~2·P
      FLOPs/token on a dense causal LM).
    - ``expected_prompt_tokens``: expected mean prompt length for the ratio
      estimate; default: half the serving slot capacity.
    - ``n_prefill_lanes``: concurrent prefill workspaces on the prefill
      slice — each lane owns a ``(L, 1, T_max, Hkv, D)`` cache pinned to a
      prefill device (round-robin) and prefills one request at a time.
    - ``handoff_depth``: committed KV pages a lane may keep in flight to
      the decode mesh before the router drains the oldest — depth 2 is the
      double-buffer that overlaps a chunk's transfer with the next chunk's
      prefill.
    - ``handoff_retries`` / ``handoff_backoff_s`` / ``handoff_backoff_cap_s``:
      a failed KV-page transfer retries this many times with capped,
      deterministically-jittered exponential backoff before the engine
      quarantines the lane and re-queues its in-flight request (bounded by
      ``ServingConfig.max_retries``); see docs/usage_guides/serving.md
      "Serving under faults".
    - ``handoff_sample_every``: every Nth page transfer is timed end-to-end
      (a sampled ``block_until_ready``) to feed the telemetry ``disagg``
      block's handoff latency without stalling the pipeline on every page.
    - ``bandwidths``: BandwidthTable field overrides for the slice-sizing
      cost model (same dict shape as ``AutoPlanKwargs.bandwidths``).
    - ``shard_decode_slots``: shard the decode-side slot cache across the
      decode slice (requires ``n_slots % n_decode == 0``) instead of
      hosting it on the slice's first device. Off by default: jitted
      programs taking typed PRNG-key arrays under a multi-device
      NamedSharding occupy TWO dispatch-cache entries for ONE compiled
      executable (still so on jax 0.9.0), so the sharded path reports
      ``decode_executables == 2`` even though exactly one program is ever
      compiled; the engine pre-warms both entries at init so the census
      stays flat (``steady_recompiles == 0``) either way.
    """

    enabled: bool = True
    n_prefill_devices: Optional[int] = None
    prefill_decode_flop_ratio: Optional[float] = None
    expected_prompt_tokens: Optional[float] = None
    n_prefill_lanes: int = 2
    handoff_depth: int = 2
    handoff_sample_every: int = 8
    handoff_retries: int = 2
    handoff_backoff_s: float = 0.001
    handoff_backoff_cap_s: float = 0.05
    bandwidths: Optional[dict] = None
    shard_decode_slots: bool = False

    def __post_init__(self):
        if self.n_prefill_devices is not None and self.n_prefill_devices < 1:
            raise ValueError("n_prefill_devices must be >= 1")
        if (self.prefill_decode_flop_ratio is not None
                and not self.prefill_decode_flop_ratio > 0):
            raise ValueError("prefill_decode_flop_ratio must be > 0")
        if (self.expected_prompt_tokens is not None
                and not self.expected_prompt_tokens > 0):
            raise ValueError("expected_prompt_tokens must be > 0")
        if self.n_prefill_lanes < 1:
            raise ValueError("n_prefill_lanes must be >= 1")
        if self.handoff_depth < 1:
            raise ValueError("handoff_depth must be >= 1")
        if self.handoff_sample_every < 1:
            raise ValueError("handoff_sample_every must be >= 1")
        if self.handoff_retries < 0:
            raise ValueError("handoff_retries must be >= 0")
        if self.handoff_backoff_s < 0 or self.handoff_backoff_cap_s < self.handoff_backoff_s:
            raise ValueError(
                "need 0 <= handoff_backoff_s <= handoff_backoff_cap_s, got "
                f"{self.handoff_backoff_s}..{self.handoff_backoff_cap_s}"
            )


@dataclass
class JitConfig(KwargsHandler):
    """Compilation policy — the role of the reference's TorchDynamoPlugin
    (reference: utils/dataclasses.py:1031-1118). XLA jit is always on; these
    knobs tune it. ``persistent_cache_dir`` is validated at Accelerator init
    (created; a one-time warning instead of silently handing a bad path to
    ``jax.config``) and managed — hit/size stats and LRU pruning — when a
    :class:`CompileKwargs` handler is present (compile_manager.py)."""

    donate_state: bool = True            # donate params/opt-state buffers to the step
    remat_policy: str = "none"           # none | full | dots_saveable | offload
    scan_layers: bool = True             # roll repeated blocks into lax.scan ("regional compile")
    persistent_cache_dir: Optional[str] = None
    # Only compiles slower than this hit the persistent cache (jax's own
    # knob; tiny executables cost more to deserialize than to rebuild).
    persistent_cache_min_compile_time_secs: float = 1.0
    # mtime-LRU prune budget applied at Accelerator.end_training (None = no
    # pruning; requires the compile manager).
    persistent_cache_budget_bytes: Optional[int] = None

    @classmethod
    def from_env(cls) -> "JitConfig":
        budget = os.environ.get("ACCELERATE_JIT_CACHE_BUDGET_BYTES")
        return cls(
            donate_state=parse_flag_from_env("ACCELERATE_JIT_DONATE", True),
            remat_policy=parse_choice_from_env("ACCELERATE_REMAT_POLICY", "none"),
            scan_layers=parse_flag_from_env("ACCELERATE_SCAN_LAYERS", True),
            persistent_cache_dir=os.environ.get("ACCELERATE_JIT_CACHE_DIR"),
            persistent_cache_min_compile_time_secs=float(
                os.environ.get("ACCELERATE_JIT_CACHE_MIN_COMPILE_S", "1.0") or 1.0
            ),
            persistent_cache_budget_bytes=int(budget) if budget else None,
        )


class ShardingStrategy(BaseEnum):
    """FSDP sharding strategy names kept from the reference
    (utils/dataclasses.py:1584-2190); each maps to a PartitionSpec policy."""

    FULL_SHARD = "FULL_SHARD"          # params+grads+opt state sharded (ZeRO-3)
    SHARD_GRAD_OP = "SHARD_GRAD_OP"    # grads+opt state sharded (ZeRO-2)
    NO_SHARD = "NO_SHARD"              # pure replication (DDP)
    HYBRID_SHARD = "HYBRID_SHARD"      # shard within dp_shard, replicate across dp_replicate


class StateDictType(BaseEnum):
    FULL_STATE_DICT = "FULL_STATE_DICT"
    SHARDED_STATE_DICT = "SHARDED_STATE_DICT"


@dataclass
class FullyShardedDataParallelPlugin(KwargsHandler):
    """ZeRO/FSDP policy → NamedSharding choices over the ``dp_shard`` axis.

    Keeps the reference's config surface (reference:
    utils/dataclasses.py:1584-2190, env decode :1900-1990) but the payload is
    just: which tensor classes shard over which mesh axes, the min size below
    which a param stays replicated, and state-dict format.
    """

    sharding_strategy: str = "FULL_SHARD"
    reshard_after_forward: bool = True      # FSDP2 naming (zero3 vs zero2 behavior)
    min_weight_size_to_shard: int = 2**11   # small params stay replicated (auto-wrap min_num_params analog)
    cpu_offload: bool = False               # optimizer state pinned to host memory
    # FULL_STATE_DICT: one gathered safetensors; SHARDED_STATE_DICT: 5GB-split
    # safetensors (still gathered to rank 0); DISTRIBUTED_STATE_DICT: orbax/
    # TensorStore — every process writes its own shards, no gather (pod scale).
    state_dict_type: str = "SHARDED_STATE_DICT"
    activation_checkpointing: bool = False
    mixed_precision_policy: Optional[MixedPrecisionPolicy] = None
    ignored_params: Optional[list] = None   # param-name regexes never sharded

    def __post_init__(self):
        env_prefix = "FSDP_"
        if isinstance(self.sharding_strategy, ShardingStrategy):
            self.sharding_strategy = str(self.sharding_strategy)
        self.sharding_strategy = os.environ.get(
            env_prefix + "SHARDING_STRATEGY", self.sharding_strategy
        ).upper()
        if self.sharding_strategy not in ShardingStrategy.list():
            # Accept the reference's FSDP2-style int codes 1-4.
            int_map = {"1": "FULL_SHARD", "2": "SHARD_GRAD_OP", "3": "NO_SHARD", "4": "HYBRID_SHARD"}
            self.sharding_strategy = int_map.get(self.sharding_strategy, self.sharding_strategy)
        if self.sharding_strategy not in ShardingStrategy.list():
            raise ValueError(
                f"sharding_strategy must be one of {ShardingStrategy.list()}"
            )
        self.cpu_offload = bool(
            str_to_bool(os.environ.get(env_prefix + "OFFLOAD_PARAMS", str(self.cpu_offload)))
        )
        self.state_dict_type = os.environ.get(
            env_prefix + "STATE_DICT_TYPE", self.state_dict_type
        ).upper()
        self.activation_checkpointing = bool(
            str_to_bool(
                os.environ.get(
                    env_prefix + "ACTIVATION_CHECKPOINTING", str(self.activation_checkpointing)
                )
            )
        )

    @property
    def shards_params(self) -> bool:
        return self.sharding_strategy in ("FULL_SHARD", "HYBRID_SHARD")

    @property
    def shards_grads_and_opt(self) -> bool:
        return self.sharding_strategy in ("FULL_SHARD", "HYBRID_SHARD", "SHARD_GRAD_OP")


@dataclass
class DeepSpeedPlugin(KwargsHandler):
    """ZeRO-stage compatibility shim (reference: utils/dataclasses.py:2550-3054).

    DeepSpeed does not exist on TPU; a ZeRO stage is exactly a sharding choice,
    so this plugin translates a DS config into a
    :class:`FullyShardedDataParallelPlugin`. Provided so users migrating DS
    configs keep working."""

    zero_stage: int = 2
    offload_optimizer_device: str = "none"
    offload_param_device: str = "none"
    gradient_accumulation_steps: int = 1
    gradient_clipping: Optional[float] = None
    zero3_init_flag: bool = False
    # Parsed from a ds_config's bf16/fp16 sections by from_ds_json — pass it
    # to Accelerator(mixed_precision=...) yourself; the plugin only carries it.
    mixed_precision: Optional[str] = None

    @classmethod
    def from_ds_json(
        cls, path: str, mixed_precision: "str | None" = None
    ) -> "DeepSpeedPlugin":
        """Build from a raw DeepSpeed ``ds_config.json`` — the file the
        reference's ``deepspeed_with_config_support`` example takes as
        ``--deepspeed_config_file`` (fixtures: reference
        tests/deepspeed/ds_config_zero{2,3}.json). ``"auto"`` values fall
        back to the field defaults; engine-only keys (optimizer, scheduler,
        comm backends) are ignored — the mesh owns those concerns.

        ``mixed_precision`` resolves ``bf16/fp16 {"enabled": "auto"}``
        sections, matching the reference's DeepSpeed integration where
        "auto" inherits the accelerate-level mixed-precision setting
        (reference: utils/deepspeed.py HfDeepSpeedConfig fill_match)."""
        import json

        with open(path) as f:
            cfg = json.load(f)

        def _noauto(v, default):
            return default if v in (None, "auto") else v

        # DeepSpeed semantics: NO zero_optimization section means ZeRO is
        # DISABLED (stage 0); "stage": "auto" means the engine default (2).
        z = cfg.get("zero_optimization")
        default_stage = 2 if z is not None else 0
        z = z or {}
        bf16_en = (cfg.get("bf16", {}) or {}).get("enabled")
        fp16_en = (cfg.get("fp16", {}) or {}).get("enabled")
        # "enabled": "auto" inherits the accelerate-level setting — only for
        # the matching section (an fp16 "auto" does not turn on bf16).
        if bf16_en == "auto":
            bf16_en = mixed_precision == "bf16"
        if fp16_en == "auto":
            fp16_en = mixed_precision == "fp16"
        mp = None
        if bf16_en is True:
            mp = "bf16"
        elif fp16_en is True:
            mp = "fp16"
        clip = _noauto(cfg.get("gradient_clipping"), None)
        return cls(
            zero_stage=int(_noauto(z.get("stage"), default_stage)),
            offload_optimizer_device=_noauto(
                (z.get("offload_optimizer") or {}).get("device"), "none"
            ),
            offload_param_device=_noauto(
                (z.get("offload_param") or {}).get("device"), "none"
            ),
            gradient_accumulation_steps=int(
                _noauto(cfg.get("gradient_accumulation_steps"), 1)
            ),
            gradient_clipping=None if clip is None else float(clip),
            mixed_precision=mp,
        )

    def to_fsdp_plugin(self) -> FullyShardedDataParallelPlugin:
        strategy = {0: "NO_SHARD", 1: "SHARD_GRAD_OP", 2: "SHARD_GRAD_OP", 3: "FULL_SHARD"}[
            self.zero_stage
        ]
        return FullyShardedDataParallelPlugin(
            sharding_strategy=strategy,
            cpu_offload=self.offload_optimizer_device == "cpu"
            or self.offload_param_device == "cpu",
        )


@dataclass
class TorchTensorParallelConfig(KwargsHandler):
    """TP config (reference: utils/dataclasses.py:2293-2313). The actual
    name→PartitionSpec rules live in parallel/tp.py."""

    tp_size: int = 1
    enable_async_tp: bool = False  # accepted, maps to XLA latency-hiding scheduler flags


@dataclass
class TorchContextParallelConfig(KwargsHandler):
    """CP config (reference: utils/dataclasses.py:2205-2231)."""

    cp_size: int = 1
    cp_comm_strategy: str = "alltoall"  # "allgather" gathers full KV; "alltoall" ring-rotates

    def __post_init__(self):
        if self.cp_comm_strategy not in ("allgather", "alltoall"):
            raise ValueError("cp_comm_strategy must be allgather|alltoall")


@dataclass
class SequenceParallelConfig(KwargsHandler):
    """Ulysses/ALST SP config (reference: DeepSpeedSequenceParallelConfig,
    utils/dataclasses.py:2233-2291)."""

    sp_size: int = 1
    attention_implementation: str = "native"  # native | flash (pallas)


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """(reference: utils/dataclasses.py:880-1030)"""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = True
    data_seed: Optional[int] = None
    non_blocking: bool = True
    use_stateful_dataloader: bool = False
    prefetch_size: int = 2
    # Dispatch mode: batches rank 0 ships per broadcast collective (the
    # fixed collective cost amortizer, byte-capped inside the loader).
    # 1 restores the one-collective-per-batch behavior.
    dispatch_group_size: int = 8


@dataclass
class ProjectConfiguration(KwargsHandler):
    """(reference: utils/dataclasses.py:780-878)"""

    project_dir: str = None
    logging_dir: str = None
    automatic_checkpoint_naming: bool = False
    total_limit: int = None
    iteration: int = 0
    save_on_each_node: bool = False
    # Elastic auto-resume (opt-in): on a gang restart
    # (ACCELERATE_RESTART_ATTEMPT > 0, commands/launch.py) the Accelerator
    # load_state()s the latest automatic checkpoint right after prepare(),
    # so a restarted run continues instead of silently training from scratch
    # (reference: torch elastic restarts, commands/launch.py:998-1030).
    automatic_resume: bool = False

    def set_directories(self, project_dir: str = None):
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        self.set_directories(self.project_dir)


def add_model_config_to_megatron_parser(*args, **kwargs):  # pragma: no cover
    raise NotImplementedError(
        "Megatron-LM is a GPU engine; its TP/PP/SP/EP capabilities are native "
        "here via ParallelismConfig + parallel/ modules."
    )
