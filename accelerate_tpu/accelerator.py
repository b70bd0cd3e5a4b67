"""The Accelerator façade (layer L5) — TPU-native.

Re-design of the reference's 4359-line ``accelerator.py``. The reference
rewires torch objects in place and intercepts the imperative loop
(``backward``/``step``/``zero_grad``). Here the same *user-visible flow* is
kept, but under it everything is one canonical sharded
:class:`~accelerate_tpu.train_state.TrainState` and jit-compiled functions over
a GSPMD mesh:

- ``prepare(model, tx, dataloader, schedule)`` plans NamedShardings for every
  param/optimizer leaf from ParallelismConfig + FSDP plugin + TP rules, puts
  the state on the mesh, and wraps the dataloader to emit global batch arrays.
- Imperative surface: ``backward(loss_fn, batch)`` runs a jitted
  value-and-grad (grads come out DP-mean'd by GSPMD — the reference needs a
  DDP reducer, reference: accelerator.py:1892-1896); ``optimizer.step()``
  applies them through a jitted update on accumulation boundaries.
- Fused surface (the fast path): ``prepare_train_step(loss_fn)`` returns ONE
  jitted step with grad-accum, clipping, precision policy and donation folded
  in — the idiomatic JAX shape the reference cannot express.

Gradient accumulation, ``accumulate()``, ``clip_grad_norm_``,
``gather_for_metrics``, trigger sync, checkpointing and tracking keep the
reference's semantics (reference: accelerator.py:1131-1381, 2818-2999,
3068-3140, 3584-3748).
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import time
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .data_loader import BaseDataLoader, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .model import Model
from .optimizer import AcceleratedOptimizer
from .parallelism_config import ParallelismConfig
from .parallel.sharding import (
    batch_partition_spec,
    infer_opt_state_sharding,
    plan_parameter_sharding,
    replicated,
)
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, DistributedType, GradientState, PartialState
from .tracking import GeneralTracker, filter_trackers
from .train_state import DynamicLossScale, TrainState, grads_all_finite
from .utils import (
    DataLoaderConfiguration,
    DistributedOperationException,
    FP8RecipeKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    JitConfig,
    MixedPrecisionPolicy,
    ProjectConfiguration,
    convert_bytes,
    extract_model_from_parallel,
    flatten_state_dict,
    gather,
    gather_object,
    is_tpu_available,
    pad_across_processes,
    recursively_apply,
    to_global_host,
    reduce,
    save_sharded_safetensors,
    set_seed,
)
from .utils.dataclasses import (
    AutoPlanKwargs,
    CompileKwargs,
    DisaggConfig,
    DistributedDataParallelKwargs,
    ElasticKwargs,
    FaultToleranceKwargs,
    KwargsHandler,
    ProfileKwargs,
    ServingConfig,
    TelemetryKwargs,
)

logger = get_logger(__name__)

try:
    import optax
except ImportError:  # pragma: no cover
    optax = None


def _is_optax_tx(obj) -> bool:
    return (
        hasattr(obj, "init")
        and hasattr(obj, "update")
        and not isinstance(obj, (Model, BaseDataLoader))
        and not hasattr(obj, "apply_fn")
    )


def _is_dataloader_like(obj) -> bool:
    if isinstance(obj, BaseDataLoader):
        return True
    return hasattr(obj, "dataset") or (
        hasattr(obj, "__iter__") and hasattr(obj, "batch_size")
    )


def _is_schedule(obj) -> bool:
    return callable(obj) and not _is_optax_tx(obj) and not isinstance(obj, Model) and not _is_dataloader_like(obj)


def _microbatch_split(batch, num_accum: int, what: str = "Batch"):
    """(B, ...) → (accum, B/accum, ...) without moving data across devices:
    the batch dim stays dp-sharded on the first reshaped dim (each device's
    contiguous block is a multiple of accum), the transpose is a layout
    change. Shared by the normal and comm-hook train steps — their
    accumulation semantics must never diverge."""

    def _split(x):
        b = x.shape[0]
        if b % num_accum != 0:
            raise ValueError(
                f"{what} dim {b} not divisible by gradient "
                f"accumulation steps {num_accum}."
            )
        x = x.reshape(b // num_accum, num_accum, *x.shape[1:])
        return jnp.swapaxes(x, 0, 1)

    return jax.tree.map(_split, batch)


class _HookHandle:
    """Removable registration handle (torch's RemovableHandle contract)."""

    def __init__(self, registry: list, hook):
        self._registry = registry
        self._hook = hook

    def remove(self):
        if self._hook in self._registry:
            self._registry.remove(self._hook)


class Accelerator:
    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        log_with=None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[list[KwargsHandler]] = None,
        parallelism_config: "Optional[ParallelismConfig | str]" = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        deepspeed_plugin=None,
        jit_config: Optional[JitConfig] = None,
        rng_types: Optional[list[str]] = None,
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        self._ds_gradient_clipping = None
        if deepspeed_plugin is not None:
            if fsdp_plugin is None:
                # ZeRO stages are sharding specs here (SURVEY.md §2.9).
                fsdp_plugin = deepspeed_plugin.to_fsdp_plugin()
            # A migrated ds_config's accumulation/clipping apply like the DS
            # engine applied them (from_ds_json) unless overridden here.
            if (
                gradient_accumulation_steps == 1
                and gradient_accumulation_plugin is None
                and deepspeed_plugin.gradient_accumulation_steps > 1
            ):
                gradient_accumulation_steps = deepspeed_plugin.gradient_accumulation_steps
            self._ds_gradient_clipping = deepspeed_plugin.gradient_clipping
        if fsdp_plugin is None and os.environ.get("ACCELERATE_USE_FSDP", "false").lower() == "true":
            fsdp_plugin = FullyShardedDataParallelPlugin()
        self.fsdp_plugin = fsdp_plugin

        # kwargs handlers (reference: accelerator.py:415-452)
        self.scaler_handler = None
        self.profile_handler = None
        self.fp8_recipe_handler = None
        self.ddp_handler = None
        self.telemetry_handler = None
        self.compile_handler = None
        self.fault_tolerance_handler = None
        self.auto_plan_handler = None
        self.elastic_handler = None
        # Serving config (serving.py): stored only — no serving code runs on
        # the training path; build_serving_engine constructs the engine.
        self.serving_config = None
        # Disaggregated-serving config (disagg.py): stored only; with one
        # present, build_serving_engine returns the two-mesh router.
        self.disagg_config = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler
            elif isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe_handler = handler
            elif isinstance(handler, DistributedDataParallelKwargs):
                self.ddp_handler = handler
            elif isinstance(handler, TelemetryKwargs):
                self.telemetry_handler = handler
            elif isinstance(handler, CompileKwargs):
                self.compile_handler = handler
            elif isinstance(handler, FaultToleranceKwargs):
                self.fault_tolerance_handler = handler
            elif isinstance(handler, ServingConfig):
                self.serving_config = handler
            elif isinstance(handler, DisaggConfig):
                self.disagg_config = handler
            elif isinstance(handler, AutoPlanKwargs):
                self.auto_plan_handler = handler
            elif isinstance(handler, ElasticKwargs):
                self.elastic_handler = handler

        if gradient_accumulation_plugin is None:
            ga_steps = int(
                os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS", gradient_accumulation_steps)
            )
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=ga_steps)
        self.gradient_state = GradientState(gradient_accumulation_plugin=gradient_accumulation_plugin)

        # Auto-parallelism (planner.py): parallelism_config="auto" — or an
        # AutoPlanKwargs handler — defers the layout choice to the planner at
        # prepare() time (the first call that sees a model). The mesh stays
        # unbuilt until then; an explicit ParallelismConfig is unchanged.
        if isinstance(parallelism_config, str):
            if parallelism_config != "auto":
                raise ValueError(
                    f"parallelism_config accepts a ParallelismConfig or the "
                    f"string 'auto', got {parallelism_config!r}"
                )
            parallelism_config = None
            if self.auto_plan_handler is None:
                self.auto_plan_handler = AutoPlanKwargs()
        self._auto_plan_pending = (
            self.auto_plan_handler is not None and self.auto_plan_handler.enabled
        )
        self.active_plan = None       # resolved ParallelPlan (auto mode only)
        self.active_plan_meta = None  # {"path": ..., "from_cache": ...}

        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            parallelism_config=parallelism_config,
        )
        self.jit_config = jit_config or JitConfig.from_env()
        if self.jit_config.persistent_cache_dir:
            # Validated (created; warning_once when unusable) instead of the
            # old bare passthrough of a possibly-bad path to jax.config.
            from .compile_manager import configure_persistent_cache

            self.jit_config.persistent_cache_dir = configure_persistent_cache(self.jit_config)

        self._mp_policy = MixedPrecisionPolicy.from_mixed_precision(self.state.mixed_precision)
        self.device_placement = device_placement
        self.split_batches = split_batches
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(
            split_batches=split_batches
        )
        self.rng_types = rng_types

        # Registries (reference: accelerator.py:617-622)
        self._models: list[Model] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list[BaseDataLoader] = []
        self._custom_objects: list = []
        self._save_state_pre_hooks: list[Callable] = []
        self._load_state_pre_hooks: list[Callable] = []

        # One TrainState per prepared model ("slot"); slot 0 is the primary
        # and backs the legacy single-model surface (_train_state property,
        # imperative backward, LocalSGD). Multi-model training — GANs,
        # distillation, RLHF — prepares several models and steps each through
        # prepare_train_step(loss_fn, model=...) (reference trains multiple
        # models per Accelerator natively since torch params live on modules).
        self._train_states: list[TrainState] = []
        self._slot_meta: list[dict] = []  # per-slot sharding plans
        self._state_shardings = None
        self._grad_shardings = None  # ZeRO-2 reduce-scatter constraint
        self._opt_offload = None     # (device, host) opt shardings under cpu_offload
        self._scheduler: Optional[AcceleratedScheduler] = None
        self._max_grad_norm: Optional[float] = None
        self._grad_fn_cache: dict = {}
        self._apply_jit = None
        self._gradnorm_jit = None
        self.step = 0
        self.flag_tensor = None

        # Tracking (reference: accelerator.py:3271-3408)
        self.log_with = filter_trackers(log_with, self.project_configuration.logging_dir)
        self.trackers: list[GeneralTracker] = []

        # Step-level telemetry (telemetry.py): off unless a TelemetryKwargs
        # handler was passed — every hot-path hook is then a None check.
        self.telemetry = None
        if self.telemetry_handler is not None and self.telemetry_handler.enabled:
            from .telemetry import TelemetryRecorder

            self.telemetry = TelemetryRecorder(self, self.telemetry_handler)

        # Compile manager (compile_manager.py): shape bucketing, AOT warmup
        # and persistent-cache control. Same contract as telemetry — off
        # unless a CompileKwargs handler was passed, then every hook site is
        # a None check.
        self.compile_manager = None
        if self.compile_handler is not None and self.compile_handler.enabled:
            from .compile_manager import CompileManager

            self.compile_manager = CompileManager(self, self.compile_handler)

        # Fault tolerance (fault_tolerance.py): atomic verified checkpoints,
        # preemption auto-save, save retry, divergence sentinel. Same
        # contract as telemetry — off unless a FaultToleranceKwargs handler
        # was passed, then every hook site is a None check and the
        # checkpoint byte layout is unchanged.
        self.fault_tolerance = None
        if self.fault_tolerance_handler is not None and self.fault_tolerance_handler.enabled:
            from .fault_tolerance import FaultToleranceManager

            self.fault_tolerance = FaultToleranceManager(self, self.fault_tolerance_handler)

        # Elastic resharding (resharding.py): restore a checkpoint written on
        # a different topology through a planned redistribution schedule, and
        # hot-swap layouts mid-run via migrate_plan(). Same contract as the
        # managers above — off unless an ElasticKwargs handler was passed,
        # then every hook site is a None check; without it a topology
        # mismatch raises TopologyMismatchError instead of resharding.
        self.elastic = None
        if self.elastic_handler is not None and self.elastic_handler.enabled:
            from .resharding import ElasticManager

            self.elastic = ElasticManager(self, self.elastic_handler)

    # ------------------------------------------------------------------
    # Introspection properties (reference: accelerator.py:640-780)
    # ------------------------------------------------------------------

    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def parallelism_config(self) -> Optional[ParallelismConfig]:
        return self.state.parallelism_config

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def fp8_dot_general(self):
        """Recipe-configured fp8 dot_general for custom modules (None unless
        mixed_precision="fp8"); model configs with an ``fp8`` flag wire this
        in automatically (ops/fp8.py)."""
        if self.state.mixed_precision != "fp8":
            return None
        from .ops.fp8 import fp8_dot_general

        # amax_history_len / amax_compute_algo are delayed-scaling knobs the
        # reference needs on GPU; current scaling fuses into the producer under
        # XLA, so only format and eval policy carry over (ops/fp8.py).
        recipe = self.fp8_recipe_handler
        return fp8_dot_general(
            recipe.fp8_format if recipe else "HYBRID",
            use_during_eval=recipe.use_during_eval if recipe else False,
            native=recipe.native_dots if recipe else None,
        )

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    # -- mesh-axis rank properties (reference: accelerator.py ParallelismConfig
    # rank accessors; here a rank is the device's coordinate on the mesh axis,
    # derived from process_index over the process-contiguous axis order) -----

    def _axis_rank(self, axis: str) -> int:
        mesh = self.mesh
        if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
            return 0
        # Read this process's coordinate off the mesh itself: device order may
        # be ICI-optimized (mesh_utils.create_device_mesh), so arithmetic on
        # process_index would lie on multi-host meshes.
        dev = jax.local_devices()[0]
        coords = np.argwhere(mesh.devices == dev)
        if coords.size == 0:
            return 0
        axis_idx = list(mesh.shape.keys()).index(axis)
        return int(coords[0][axis_idx])

    @property
    def data_parallel_rank(self) -> int:
        return self._axis_rank("dp_replicate")

    @property
    def data_parallel_shard_rank(self) -> int:
        return self._axis_rank("dp_shard")

    @property
    def context_parallel_rank(self) -> int:
        return self._axis_rank("cp")

    @property
    def tensor_parallel_rank(self) -> int:
        return self._axis_rank("tp")

    @property
    def pipeline_parallel_rank(self) -> int:
        return self._axis_rank("pp")

    @property
    def optimizer_step_was_skipped(self) -> bool:
        """True if the last optimizer step was skipped (fp16 overflow) —
        reference: accelerator.py GradScaler bookkeeping; here the fused step
        freezes params on non-finite grads and the wrapped optimizer records
        it."""
        return any(opt.step_was_skipped for opt in self._optimizers)

    # -- dataloader-config passthroughs (reference exposes these directly;
    # split_batches is already a ctor-set attribute) ---

    @property
    def dispatch_batches(self):
        return self.dataloader_config.dispatch_batches

    @property
    def even_batches(self) -> bool:
        return self.dataloader_config.even_batches

    @property
    def use_seedable_sampler(self) -> bool:
        return self.dataloader_config.use_seedable_sampler

    @property
    def non_blocking(self) -> bool:
        """Parity shim: device transfers are async by construction in JAX."""
        return True

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def save_iteration(self):
        return self.project_configuration.iteration

    @property
    def train_state(self) -> Optional[TrainState]:
        return self._train_state

    @train_state.setter
    def train_state(self, value: TrainState):
        self._train_state = value

    @property
    def _train_state(self) -> Optional[TrainState]:
        """Primary (slot-0) train state; None before prepare()."""
        states = getattr(self, "_train_states", None)
        return states[0] if states else None

    @_train_state.setter
    def _train_state(self, value: Optional[TrainState]):
        if value is None:
            self._train_states = []
            self._slot_meta = []
        elif getattr(self, "_train_states", None):
            self._train_states[0] = value
        else:
            self._train_states = [value]

    @property
    def state_shardings(self):
        return self._state_shardings

    # ------------------------------------------------------------------
    # Process-control passthrough (reference: accelerator.py:782-1120)
    # ------------------------------------------------------------------

    def on_main_process(self, function):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.on_local_main_process(function)

    def on_last_process(self, function):
        return self.state.on_last_process(function)

    def on_process(self, function=None, process_index=None):
        if function is None:
            return functools.partial(self.on_process, process_index=process_index)
        return self.state.on_process(function, process_index)

    def on_local_process(self, function=None, local_process_index=None):
        if function is None:
            return functools.partial(self.on_local_process, local_process_index=local_process_index)
        return self.state.on_local_process(function, local_process_index)

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.local_main_process_first():
            yield

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    # ------------------------------------------------------------------
    # prepare() — the core (reference: accelerator.py:1414-1570)
    # ------------------------------------------------------------------

    def prepare(self, *args, device_placement=None):
        """Prepare model/optimizer/dataloader/scheduler objects, returning
        them in the same order (reference: accelerator.py:1414).

        Optimizer pairing contract: each optax optimizer binds to the nearest
        model *before* it in the argument list that doesn't already have one —
        ``prepare(model, tx)``, ``prepare(gen, gen_tx, disc, disc_tx)``,
        ``prepare(student, tx, frozen_teacher)`` and
        ``prepare(frozen_teacher, student, tx)`` all do what they look like.
        An optimizer before any model, or two optimizers after the same model,
        raises. (The torch reference pairs via param references; a functional
        tx has none, so argument adjacency is the contract.)
        """
        result = []
        models = [a for a in args if isinstance(a, Model)]
        for model in models:
            if self.verify_device_map(model):
                # Same guard as the reference (accelerator.py:3744-3760): a
                # model dispatched across HBM/host/disk cannot also be
                # prepared for distributed training — its params aren't a
                # mesh-shardable tree.
                raise ValueError(
                    "You can't train a model that has been dispatched with a "
                    "multi-placement device_map (offloaded to cpu/disk). Load the "
                    "model on-device (or shard it with a ParallelismConfig mesh) "
                    "before calling prepare()."
                )

        # Models pair with optimizers by ADJACENCY in args order: each
        # optimizer binds to the nearest *preceding* model that does not have
        # one yet (the torch reference gets pairing implicitly from param
        # references; a functional optax tx has none, so argument order is
        # the contract). prepare(frozen_teacher, student, tx) therefore binds
        # tx to `student`; two optimizers after the same model is ambiguous
        # and raises. A model without a following optimizer is prepared
        # inference-only (e.g. a frozen teacher).
        pairings: list = [None] * len(models)  # models index -> tx
        tx_models: list = []  # per tx, in args order: the Model it binds to
        cur = -1  # index into `models` of the most recent model seen
        for obj in args:
            if isinstance(obj, Model):
                cur += 1
            elif _is_optax_tx(obj):
                if not models:
                    tx_models.append(None)  # lone optimizer: wrap unbound
                    continue
                if cur < 0:
                    raise ValueError(
                        "prepare() received an optimizer before any model; pass "
                        "each optimizer after the model it should train, e.g. "
                        "prepare(model, tx, dataloader)."
                    )
                if pairings[cur] is not None:
                    raise ValueError(
                        "prepare() optimizer pairing is ambiguous: two optimizers "
                        "follow the same model. Pass each optimizer immediately "
                        "after its own model, e.g. prepare(gen, gen_tx, disc, "
                        "disc_tx)."
                    )
                pairings[cur] = obj
                tx_models.append(models[cur])
        if models and self._auto_plan_pending:
            # Resolve the auto-parallelism plan from the FIRST prepared model
            # before any mesh-dependent planning happens (planner.py).
            self._resolve_auto_plan(models[0])
        for i, model in enumerate(models):
            self._prepare_state(model, pairings[i])
        tx_seen = 0

        for obj in args:
            if isinstance(obj, Model):
                result.append(self.prepare_model(obj))
            elif _is_optax_tx(obj):
                # Pairing already bound this tx to its model's slot above;
                # prepare_optimizer must only wrap, not re-bind it to some
                # other (optimizer-less) slot — e.g. a frozen teacher.
                bound = tx_models[tx_seen]
                result.append(
                    self.prepare_optimizer(
                        obj,
                        _already_bound=bound is not None,
                        _bound_slot=bound._state_slot if bound is not None else None,
                    )
                )
                tx_seen += 1
            elif isinstance(obj, AcceleratedOptimizer):
                result.append(obj)
            elif _is_dataloader_like(obj):
                result.append(self.prepare_data_loader(obj))
            elif _is_schedule(obj):
                result.append(self.prepare_scheduler(obj))
            else:
                result.append(obj)
        self._maybe_elastic_resume()
        if self.fault_tolerance is not None:
            # Rank-coherent by construction: every rank runs prepare(), and
            # the launcher signals the whole local gang (multi-host coherence
            # goes through check_preemption's collective).
            self.fault_tolerance.install_signal_handlers()
            self.fault_tolerance.start_watchdog()
        return result[0] if len(result) == 1 else tuple(result)

    def _maybe_elastic_resume(self) -> None:
        """Elastic auto-resume: when the launcher restarted the gang
        (ACCELERATE_RESTART_ATTEMPT > 0, commands/launch.py gang loop) and
        the project saves automatic checkpoints, restore the latest one
        right after prepare() — a restarted run must continue, not silently
        train from scratch. Opt-in via
        ProjectConfiguration(automatic_resume=True); reference analog:
        torch elastic restarts (launch.py:998-1030) + the script-side
        resume_from_checkpoint idiom."""
        pc = self.project_configuration
        if not (pc.automatic_resume and pc.automatic_checkpoint_naming):
            return
        if getattr(self, "_elastic_resumed", False):
            # Staged prepares: dataloaders/schedulers/custom objects
            # registered AFTER the resume still need their checkpointed
            # host-side state. Safe to re-apply only while no training has
            # happened since the resume (the rewind hazard needs steps).
            resume_dir = getattr(self, "_elastic_resume_dir", None)
            if (
                resume_dir is not None
                and self._host_state_counts() != getattr(self, "_elastic_resume_counts", None)
                and int(np.asarray(self._train_state.step))
                == getattr(self, "_elastic_resume_step", -1)
            ):
                from .checkpointing import _load_host_side_state

                _load_host_side_state(self, resume_dir)
                self._elastic_resume_counts = self._host_state_counts()
            return
        attempt = int(os.environ.get("ACCELERATE_RESTART_ATTEMPT", "0") or 0)
        if attempt <= 0:
            return
        # Wait for a prepare() that produced a *trainable* state: a staged
        # script may prepare dataloaders (no train state) or a frozen model
        # (no tx) first — resuming then would crash or skip the optimizer
        # moments, and the consumed flag would block the real resume later.
        state = self._train_state
        if state is None or state.tx is None:
            return
        # From here the decision is final for this process, including the
        # fresh-start path: a later prepare() call mid-training must never
        # rewind to a checkpoint the run itself has since written.
        self._elastic_resumed = True
        base = os.path.join(self.project_dir or ".", "checkpoints")
        from .checkpointing import _list_checkpoint_dirs

        # _list_checkpoint_dirs, not a bare startswith() scan: a restart whose
        # ONLY artifact is an interrupted checkpoint_N.tmp staging dir must
        # start fresh, not crash load_state on an empty resolver result.
        if not os.path.isdir(base) or not _list_checkpoint_dirs(base):
            logger.warning(
                "automatic_resume: restart attempt %d but no checkpoints under "
                "%s — starting fresh.", attempt, base,
            )
            return
        loaded = self.load_state()
        self._elastic_resume_dir = loaded
        self._elastic_resume_counts = self._host_state_counts()
        self._elastic_resume_step = int(np.asarray(self._train_state.step))
        logger.info(
            "automatic_resume: restart attempt %d resumed from %s (step %d)",
            attempt, loaded, self._elastic_resume_step,
            main_process_only=True,
        )

    def _host_state_counts(self) -> tuple:
        """Registration counts of everything _load_host_side_state restores
        by enumeration — the staleness key for staged elastic resume."""
        return (
            len(self._dataloaders),
            len(self._schedulers),
            len(self._custom_objects),
        )

    def _resolve_auto_plan(self, model: Model) -> None:
        """Auto-parallelism (planner.py): search — or load the cached —
        :class:`~accelerate_tpu.planner.ParallelPlan` for ``model`` on this
        process's devices, install its layout as the ParallelismConfig, and
        apply its remat/microbatch decisions. Runs at most once, from the
        first prepare() that sees a model."""
        self._auto_plan_pending = False
        handler = self.auto_plan_handler
        if self.state.parallelism_config is not None:
            logger.warning(
                "auto-plan: an explicit ParallelismConfig is already set — "
                "the planner defers to it (drop parallelism_config= to let "
                "the search choose)."
            )
            return
        if self.state._mesh is not None:
            raise RuntimeError(
                "auto-plan: the device mesh was already built (something "
                "touched accelerator.mesh before prepare()). Construct the "
                "Accelerator with parallelism_config='auto' and prepare the "
                "model before any mesh access."
            )
        module = getattr(model, "module", None)
        cfg = getattr(module, "config", None)
        if module is None or cfg is None:
            raise ValueError(
                "auto-plan needs an in-framework module carrying a config "
                "(divisibility constraints + activation model); wrap your "
                "model with Model.from_flax(module, ...) where module.config "
                "exists, or pass an explicit ParallelismConfig."
            )
        from .planner import BandwidthTable, Planner, default_tp_rules, layout_str

        # Elastic resize: a relaunch that came back on a different device
        # count re-searches under the new topology, pinning what the previous
        # run's (calibrated) plan says is winning — or, under
        # resize_policy="keep", pinning the whole scaled layout.
        pinned = handler.pinned
        if not pinned:
            pinned = self._elastic_resize_pins() or pinned
        label = f"{type(cfg).__name__}:{getattr(cfg, 'num_hidden_layers', '?')}L"
        planner = Planner(
            module,
            cfg,
            n_devices=len(self.state.devices),
            hbm_gib=handler.hbm_gib,
            seq=handler.seq,
            per_chip_batch=handler.per_chip_batch,
            optimizer=handler.optimizer,
            tp_rules=model.tp_rules or default_tp_rules(module, cfg),
            axes=tuple(handler.axes),
            pinned=pinned,
            bandwidths=BandwidthTable.from_dict(handler.bandwidths),
            label=label,
        )
        plans_dir = handler.plans_dir or os.path.join(
            self.project_dir or ".", "plans"
        )
        plan, path, from_cache = planner.resolve(
            plans_dir, use_cache=handler.use_cache
        )
        self.active_plan = plan
        self.active_plan_meta = {"path": path, "from_cache": from_cache}
        pc = plan.to_parallelism_config()
        self.state.parallelism_config = pc
        if pc.tp_size > 1 and not model.tp_rules and planner.tp_rules:
            # Train with the SAME rule table the plan was priced with —
            # otherwise a tp>1 layout would silently replicate every leaf.
            model.tp_rules = list(planner.tp_rules)
        logger.info(
            "auto-plan: %s layout %s (predicted %.4gs/step, %.3g GiB/chip%s)"
            " — artifact %s",
            "loaded cached" if from_cache else "searched",
            layout_str(plan.layout), plan.predicted_step_s,
            plan.predicted_hbm_gib,
            ", OVER BUDGET" if plan.over_budget else "",
            path,
            main_process_only=True,
        )
        if plan.over_budget:
            logger.warning(
                "auto-plan: no layout fit %.1f GiB/chip — training with the "
                "best-effort plan %s (predicted %.3g GiB). Expect OOM; see "
                "the plan's rejection log (%s) and docs/usage_guides/"
                "auto_parallelism.md.",
                plan.hbm_gib_budget, layout_str(plan.layout),
                plan.predicted_hbm_gib, path,
            )
        # Apply the remat decision the plan priced (same rebuild contract as
        # fsdp_plugin.activation_checkpointing).
        if handler.apply_remat and plan.remat and getattr(cfg, "remat", None) is False:
            import dataclasses as _dc

            new_module = type(module)(
                _dc.replace(cfg, remat=True, remat_policy=plan.remat_policy)
            )
            model.module = new_module
            model.apply_fn = new_module.apply
            logger.info(
                "auto-plan: enabled remat (policy=%s) on %s per the plan.",
                plan.remat_policy, type(module).__name__,
                main_process_only=True,
            )
        if (
            handler.apply_microbatches
            and plan.microbatches > 1
            and self.gradient_state.num_steps == 1
        ):
            self.gradient_accumulation_steps = plan.microbatches
            logger.info(
                "auto-plan: gradient_accumulation_steps=%d per the plan's "
                "microbatch ladder.", plan.microbatches,
                main_process_only=True,
            )
        if self.telemetry is not None:
            self.telemetry.note_plan(
                plan.to_json_dict(), path,
                calibrate_after=handler.calibrate_after,
            )
        if self.compile_manager is not None:
            self.compile_manager.note_plan(plan)

    def _checkpoint_plan_layout(self) -> Optional[dict]:
        """Layout recorded in the newest checkpoint's plan manifest, or None
        (no checkpoints / checkpoint predates plan manifests)."""
        base = os.path.join(self.project_dir or ".", "checkpoints")
        if not os.path.isdir(base):
            return None
        from .checkpointing import _list_checkpoint_dirs
        from .resharding import read_plan_manifest

        for name in reversed(_list_checkpoint_dirs(base)):
            manifest = read_plan_manifest(os.path.join(base, name))
            if manifest is not None:
                return manifest.get("layout") or None
        return None

    def _elastic_resize_pins(self) -> Optional[dict]:
        """Planner pins for the preemption-driven resize path: only active on
        an elastic relaunch (``ACCELERATE_RESTART_ATTEMPT`` > 0) with an
        ElasticKwargs handler and a checkpointed source layout to learn
        from. ``resize_policy="fail"`` pins nothing — the restore itself will
        raise on the mismatch."""
        elastic = self.elastic
        attempt = int(os.environ.get("ACCELERATE_RESTART_ATTEMPT", "0") or 0)
        if elastic is None or not elastic.enabled or attempt <= 0:
            return None
        if elastic.resize_policy == "fail":
            return None
        src_layout = self._checkpoint_plan_layout()
        if not src_layout:
            return None
        from .planner import layout_str, resize_pins, scaled_layout

        n_dev = len(self.state.devices)
        pins: Optional[dict] = None
        if elastic.resize_policy == "keep":
            kept = scaled_layout(src_layout, n_dev)
            if kept is not None:
                # Pin every plannable axis: the "search" then has exactly one
                # candidate — the old layout with dp rescaled — but still
                # produces a normal plan artifact + telemetry.
                pins = {
                    ax: int(kept.get(ax, 1))
                    for ax in ("dp_replicate", "dp_shard", "tp", "cp", "pp")
                }
            # Non-divisible "keep" falls through to winning-axes pinning.
        if pins is None and getattr(elastic.handler, "pin_winning_axes", True):
            pins = resize_pins(src_layout, n_dev) or None
        if pins:
            logger.info(
                "elastic resize: restart attempt %d on %d device(s) — "
                "planner re-search pinned to %s (checkpoint layout was %s).",
                attempt, n_dev, pins, layout_str(src_layout),
                main_process_only=True,
            )
        return pins

    def _apply_activation_checkpointing(self, model: Model):
        """Honor ``fsdp_plugin.activation_checkpointing`` (reference FSDP
        ``activation_checkpointing=True`` wraps blocks in
        checkpoint_wrapper): flagship modules expose ``config.remat`` — flip
        it and rebuild the module. Warn loudly when the module has no remat
        knob; a silently-ignored flag is worse than none."""
        plugin = self.fsdp_plugin
        if plugin is None or not plugin.activation_checkpointing:
            return
        module = model.module
        cfg = getattr(module, "config", None)
        if cfg is not None and getattr(cfg, "remat", None) is False:
            import dataclasses as _dc

            new_module = type(module)(_dc.replace(cfg, remat=True))
            model.module = new_module
            model.apply_fn = new_module.apply
            logger.warning(
                "activation_checkpointing: rebuilt %s with config.remat=True. "
                "Write your loss_fn against model.module / model(batch) — a "
                "loss_fn closing over the module object created before "
                "prepare() still traces the un-rematted version.",
                type(module).__name__,
            )
        elif cfg is None or not hasattr(cfg, "remat"):
            logger.warning(
                "fsdp_plugin.activation_checkpointing=True but %s has no "
                "config.remat knob — apply jax.checkpoint/nn.remat inside your "
                "module to get activation checkpointing.",
                type(module).__name__,
            )

    def _prepare_state(self, model: Model, tx):
        """Plan shardings for params + optimizer state and build the canonical
        TrainState on the mesh. This is where FSDP/ZeRO/HSDP/TP all happen
        (SURVEY.md §7: the backend zoo collapses into NamedSharding choices)."""
        self._apply_activation_checkpointing(model)
        mesh = self.mesh
        cfg = self.state.parallelism_config or ParallelismConfig()
        if (model._params if model._params is not None else model.params) is None:
            raise RuntimeError(
                "Model has no reachable params — it was prepared by a previous "
                "Accelerator whose state is gone. Rebuild it (Model.from_flax "
                "or load a checkpoint) before preparing it again."
            )
        param_shardings = plan_parameter_sharding(
            model._params if model._params is not None else model.params,
            mesh,
            fsdp_plugin=self.fsdp_plugin,
            parallelism_config=cfg,
            tp_rules=model.tp_rules,
        )
        params = jax.tree.map(
            lambda p, s: jax.device_put(jnp.asarray(p), s),
            model._params if model._params is not None else model.params,
            param_shardings,
        )
        loss_scale = None
        if self.state.mixed_precision == "fp16":
            kw = self.scaler_handler.to_kwargs() if self.scaler_handler else {}
            if kw.pop("enabled", True):
                loss_scale = DynamicLossScale.create(
                    init_scale=kw.pop("init_scale", 2.0**16),
                    **{k: v for k, v in kw.items() if k in ("growth_factor", "backoff_factor", "growth_interval")},
                )
        if tx is not None:
            opt_shardings, grad_shardings, opt_offload = self._build_opt_shardings(
                model, params, param_shardings, tx, cfg
            )
            opt_init = jax.jit(tx.init, out_shardings=opt_shardings)
            opt_state = opt_init(params)
        else:
            opt_state, opt_shardings = (), ()
            grad_shardings, opt_offload = None, None
        rep = replicated(mesh)
        extra = model.extra_state
        extra_shardings = jax.tree.map(lambda _: replicated(mesh), extra) if extra else None
        # Every leaf is COMMITTED from the start (step/loss_scale/extra too,
        # not just params/opt_state): an uncommitted scalar in the initial
        # state gives the first step call different input avals than every
        # later call (whose state is the step's committed output), costing
        # one extra executable per step fn. (What remains of that recompile
        # is GSPMD's choice of output shardings on a mesh: telemetry.py.)
        state = TrainState(
            step=jax.device_put(jnp.asarray(0, jnp.int32), rep),
            params=params,
            opt_state=opt_state,
            extra_state=jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), rep), extra)
            if extra
            else extra,
            accum_grads=None,
            loss_scale=jax.tree.map(lambda x: jax.device_put(x, rep), loss_scale)
            if loss_scale is not None
            else None,
            apply_fn=model.apply_fn,
            tx=tx,
        )
        state_shardings = TrainState(
            step=rep,
            params=param_shardings,
            opt_state=opt_shardings,
            extra_state=extra_shardings,
            accum_grads=None,
            loss_scale=jax.tree.map(lambda _: rep, state.loss_scale) if loss_scale is not None else None,
            apply_fn=model.apply_fn,
            tx=tx,
        )
        # Commit into this model's slot; the flat attrs mirror slot 0 (the
        # legacy single-model surface).
        meta = {
            "state_shardings": state_shardings,
            "param_shardings": param_shardings,
            "grad_shardings": grad_shardings,
            "opt_offload": opt_offload,
        }
        slot = getattr(model, "_state_slot", None)
        if getattr(model, "_accelerator", None) is not None and model._accelerator is not self:
            slot = None  # model was bound to a previous Accelerator; its slot is stale
        if slot is None or slot >= len(self._train_states):
            slot = len(self._train_states)
            self._train_states.append(state)
            self._slot_meta.append(meta)
        else:
            self._train_states[slot] = state
            self._slot_meta[slot] = meta
        model._state_slot = slot
        model._accelerator = self  # bind now so prepare_model won't re-prepare
        if slot == 0:
            self._state_shardings = state_shardings
            self._param_shardings = param_shardings
            self._grad_shardings = grad_shardings
            self._opt_offload = opt_offload

    def _plan_opt_shardings(self, model, param_shardings, mesh, cfg):
        """ZeRO-1/2 (SHARD_GRAD_OP) + cpu_offload planning.

        SHARD_GRAD_OP keeps params replicated but shards gradients and
        optimizer state over ``dp_shard`` (reference FSDP sharding_strategy /
        DeepSpeed stages 1-2, utils/dataclasses.py:1584-2190,
        utils/deepspeed.py:253-293). HBM per chip for N params (bf16 compute,
        fp32 Adam) on a W-way dp_shard axis:

          FULL_SHARD:    (2N params + 2N grads + 12N opt) / W
          SHARD_GRAD_OP:  2N params + (2N grads + 12N opt) / W
          NO_SHARD:       2N + 2N + 12N

        ``cpu_offload=True`` additionally pins the optimizer state to
        ``pinned_host`` memory — XLA's host-offload path streams it per update
        instead of the reference's CPUOffload module wrapper.

        Pure planner: returns (opt sharding plan tree, memory_kind or None,
        grad shardings or None — the ZeRO-2 reduce-scatter constraint for
        prepare_train_step). Callers commit the plans into the slot meta."""
        plugin = self.fsdp_plugin
        grad_shardings = None
        opt_plan = param_shardings
        if plugin is not None and plugin.shards_grads_and_opt and not plugin.shards_params:
            params_tree = model._params if model._params is not None else model.params
            opt_plan = plan_parameter_sharding(
                params_tree,
                mesh,
                fsdp_plugin=plugin,
                parallelism_config=cfg,
                tp_rules=model.tp_rules,
                shards_params_override=True,
            )
            grad_shardings = opt_plan
        mem_kind = None
        if plugin is not None and plugin.cpu_offload:
            # Host offload is a TPU-runtime feature; the CPU backend accepts
            # the memory-kind annotation but its SPMD partitioner rejects it
            # at compile time, so gate on platform rather than probing.
            if is_tpu_available():
                mem_kind = "pinned_host"
            else:
                logger.warning(
                    "fsdp_plugin.cpu_offload requested but backend %s has no "
                    "host memory space — optimizer state stays in device memory.",
                    self.device.platform,
                )
        return opt_plan, mem_kind, grad_shardings

    def _build_opt_shardings(self, model, params, param_shardings, tx, cfg):
        """Shared by _prepare_state and prepare_optimizer: plan optimizer-state
        shardings (ZeRO strategy + cpu_offload). Pure: returns
        (storage opt shardings — host-pinned under cpu_offload,
        grad shardings or None, opt_offload pair or None); callers commit
        them into the slot meta (flat attrs mirror slot 0 only)."""
        opt_plan, mem_kind, grad_shardings = self._plan_opt_shardings(
            model, param_shardings, self.mesh, cfg
        )
        opt_shapes = jax.eval_shape(tx.init, params)
        opt_shardings = infer_opt_state_sharding(
            opt_shapes, params, opt_plan, self.mesh, memory_kind=mem_kind
        )
        if mem_kind is not None:
            # Host-offloaded optimizer state: the fused step streams it to
            # device around tx.update (see prepare_train_step).
            device_shardings = infer_opt_state_sharding(opt_shapes, params, opt_plan, self.mesh)
            opt_offload = (device_shardings, opt_shardings)
        else:
            opt_offload = None
        return opt_shardings, grad_shardings, opt_offload

    def prepare_model(self, model: Model, device_placement=None, evaluation_mode: bool = False) -> Model:
        if (
            getattr(model, "_state_slot", None) is None
            or getattr(model, "_accelerator", None) is not self
        ):
            # Also re-prepare a model carrying a slot from a PREVIOUS
            # Accelerator — its stale slot index must not alias this
            # accelerator's states (and _params may need re-materializing).
            self._prepare_state(model, None)
        model._accelerator = self
        model._params = None  # canonical copy now lives in the TrainState
        model._accelerate_prepared = True
        if model not in self._models:
            self._models.append(model)
        return model

    def prepare_optimizer(
        self,
        optimizer,
        device_placement=None,
        _already_bound: bool = False,
        _bound_slot: Optional[int] = None,
    ) -> AcceleratedOptimizer:
        if isinstance(optimizer, AcceleratedOptimizer):
            return optimizer
        wrapped = AcceleratedOptimizer(
            optimizer, device_placement=device_placement or self.device_placement, accelerator=self
        )
        wrapped._state_slot = _bound_slot if _already_bound else None
        # Bind to the first prepared model still missing an optimizer (slot
        # order == order of appearance in prepare()); skipped when prepare()'s
        # model/optimizer pairing already bound this tx.
        slot = (
            None
            if _already_bound
            else next((i for i, st in enumerate(self._train_states) if st.tx is None), None)
        )
        if slot is not None:
            state = self._train_states[slot]
            model = next(
                (m for m in self._models if getattr(m, "_state_slot", None) == slot),
                self._models[-1] if self._models else None,
            )
            if slot >= len(self._slot_meta):
                # State installed directly (not via _prepare_state): keep the
                # flat-attr plans as its meta.
                self._slot_meta.extend(
                    {"state_shardings": self._state_shardings,
                     "param_shardings": self._param_shardings,
                     "grad_shardings": self._grad_shardings,
                     "opt_offload": self._opt_offload}
                    for _ in range(slot + 1 - len(self._slot_meta))
                )
            meta = self._slot_meta[slot]
            param_shardings = meta["param_shardings"]
            cfg = self.state.parallelism_config or ParallelismConfig()
            if model is not None:
                opt_shardings, grad_shardings, opt_offload = self._build_opt_shardings(
                    model, state.params, param_shardings, optimizer, cfg
                )
                meta["grad_shardings"] = grad_shardings
                meta["opt_offload"] = opt_offload
                if slot == 0:
                    self._grad_shardings = grad_shardings
                    self._opt_offload = opt_offload
            else:
                opt_shapes = jax.eval_shape(optimizer.init, state.params)
                opt_shardings = infer_opt_state_sharding(
                    opt_shapes, state.params, param_shardings, self.mesh
                )
            opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(state.params)
            self._train_states[slot] = state.replace(opt_state=opt_state, tx=optimizer)
            wrapped._state_slot = slot
            meta["state_shardings"] = meta["state_shardings"].replace(
                opt_state=opt_shardings, tx=optimizer
            )
            if slot == 0:
                self._state_shardings = meta["state_shardings"]
        self._optimizers.append(wrapped)
        return wrapped

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        if isinstance(data_loader, BaseDataLoader):
            if data_loader not in self._dataloaders:
                self._dataloaders.append(data_loader)
            data_loader._telemetry = self.telemetry
            data_loader._compile_manager = self.compile_manager
            data_loader._fault_tolerance = self.fault_tolerance
            return data_loader
        cfg = self.dataloader_config
        prepared = prepare_data_loader(
            data_loader,
            num_processes=self.num_processes,
            process_index=self.process_index,
            split_batches=cfg.split_batches,
            put_on_device=device_placement if device_placement is not None else self.device_placement,
            rng_types=self.rng_types,
            dispatch_batches=cfg.dispatch_batches,
            even_batches=cfg.even_batches,
            use_seedable_sampler=cfg.use_seedable_sampler,
            data_seed=cfg.data_seed,
            non_blocking=cfg.non_blocking,
            prefetch_size=cfg.prefetch_size,
            dispatch_group_size=cfg.dispatch_group_size,
        )
        prepared._telemetry = self.telemetry  # host-wait accounting hook
        prepared._compile_manager = self.compile_manager  # bucket padding hook
        prepared._fault_tolerance = self.fault_tolerance  # chaos corrupt_batch hook
        self._dataloaders.append(prepared)
        return prepared

    def prepare_scheduler(self, scheduler) -> AcceleratedScheduler:
        if isinstance(scheduler, AcceleratedScheduler):
            return scheduler
        wrapped = AcceleratedScheduler(
            scheduler,
            optimizers=self._optimizers or None,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.split_batches,
        )
        self._schedulers.append(wrapped)
        self._scheduler = wrapped
        return wrapped

    # ------------------------------------------------------------------
    # Gradient accumulation (reference: accelerator.py:1131-1381)
    # ------------------------------------------------------------------

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Context manager flipping ``sync_gradients`` on accumulation
        boundaries (reference: accelerator.py:1255-1297). Under GSPMD there is
        no allreduce to skip — skipping the *optimizer update* is the whole
        story — so `no_sync` semantics are free."""
        self._do_sync()
        with contextlib.nullcontext():
            yield

    def _do_sync(self):
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients(
                (self.step % self.gradient_state.num_steps) == 0
            )

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """(reference: accelerator.py:1131-1178) — a no-op under GSPMD; kept
        for API parity."""
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """Under even_batches sharding every rank always has a batch, so this
        is advisory; the ``even_batches`` override applies only inside the
        context, like the reference's (reference: accelerator.py:1299-1381)."""
        overridden = []
        if even_batches is not None:
            for dl in self._dataloaders:
                if hasattr(dl, "batch_sampler") and hasattr(dl.batch_sampler, "even_batches"):
                    overridden.append((dl.batch_sampler, dl.batch_sampler.even_batches))
                    dl.batch_sampler.even_batches = even_batches
        try:
            yield
        finally:
            for sampler, old in overridden:
                sampler.even_batches = old

    # ------------------------------------------------------------------
    # Imperative training surface (reference: accelerator.py:2818-2999)
    # ------------------------------------------------------------------

    def backward(self, loss_fn: Callable, *args, has_aux: bool = False, **kwargs):
        """Compute gradients of ``loss_fn(params, *args, **kwargs)`` w.r.t.
        the prepared params and accumulate them.

        This is the one necessary deviation from the reference's
        ``backward(loss)``: JAX differentiates *functions*, not scalars. The
        loss is divided by the accumulation step count exactly like the
        reference (accelerator.py:2840), and gradients arrive DP-averaged
        because batch + loss-mean are globally sharded.

        Returns the (unscaled) loss value, plus aux if ``has_aux``.
        """
        if self._train_state is None:
            raise RuntimeError("Call accelerator.prepare(...) before backward().")
        key = (loss_fn, has_aux)
        if key not in self._grad_fn_cache:
            policy = self._mp_policy
            num_steps_ref = self.gradient_state

            def _scaled_loss(params, scale, n_accum, *f_args, **f_kwargs):
                compute_params = policy.cast_for_compute(params)
                out = loss_fn(compute_params, *f_args, **f_kwargs)
                loss, aux = (out if has_aux else (out, None))
                scaled = loss / n_accum * scale
                return scaled.astype(jnp.float32), (loss, aux)

            grad_fn = jax.value_and_grad(_scaled_loss, has_aux=True)

            def _run(params, scale, n_accum, *f_args, **f_kwargs):
                (_, (loss, aux)), grads = grad_fn(params, scale, n_accum, *f_args, **f_kwargs)
                return loss, aux, grads

            self._grad_fn_cache[key] = jax.jit(_run)
        scale = (
            self._train_state.loss_scale.scale
            if self._train_state.loss_scale is not None
            else jnp.asarray(1.0, jnp.float32)
        )
        n_accum = jnp.asarray(float(self.gradient_state.num_steps), jnp.float32)
        tel = self.telemetry
        t0 = time.perf_counter() if tel is not None else 0.0
        loss, aux, grads = self._grad_fn_cache[key](
            self._train_state.params, scale, n_accum, *args, **kwargs
        )
        if tel is not None:
            if tel.handler.sync_timing:
                jax.block_until_ready(loss)
            tel.on_backward(self._grad_fn_cache[key], (args, kwargs), time.perf_counter() - t0)
        if self._optimizers:
            self._optimizers[0].accumulate_grads(grads)
        else:
            if self._train_state.accum_grads is None:
                self._train_state = self._train_state.replace(accum_grads=grads)
            else:
                self._train_state = self._train_state.replace(
                    accum_grads=jax.tree.map(jnp.add, self._train_state.accum_grads, grads)
                )
        return (loss, aux) if has_aux else loss

    def _apply_gradients(self, grads) -> bool:
        """Jitted optimizer update with clipping + fp16 overflow skip.
        Returns True when the step was applied."""
        if self._apply_jit is None:
            tx = self._train_state.tx

            def _apply(state: TrainState, grads, max_norm, clip_enabled):
                if state.loss_scale is not None:
                    grads = state.loss_scale.unscale(grads)
                finite = grads_all_finite(grads) if state.loss_scale is not None else jnp.asarray(True)
                if clip_enabled:
                    gnorm = optax.global_norm(grads)
                    factor = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
                    grads = jax.tree.map(lambda g: g * factor, grads)
                else:
                    gnorm = optax.global_norm(grads)
                updates, new_opt = tx.update(grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
                # fp16 overflow → keep old params/opt, still advance scale state.
                new_params = jax.tree.map(
                    lambda new, old: jnp.where(finite, new, old), new_params, state.params
                )
                new_opt = jax.tree.map(
                    lambda new, old: jnp.where(finite, new, old) if hasattr(new, "shape") else new,
                    new_opt,
                    state.opt_state,
                )
                new_scale = (
                    state.loss_scale.update(finite) if state.loss_scale is not None else None
                )
                new_state = state.replace(
                    step=state.step + jnp.where(finite, 1, 0),
                    params=new_params,
                    opt_state=new_opt,
                    loss_scale=new_scale,
                )
                return new_state, finite, gnorm

            self._apply_jit = jax.jit(
                _apply, static_argnames=("clip_enabled",), donate_argnums=(0, 1)
            )
        max_norm = jnp.asarray(self._max_grad_norm or 0.0, jnp.float32)
        tel = self.telemetry
        t0 = time.perf_counter() if tel is not None else 0.0
        new_state, finite, gnorm = self._apply_jit(
            self._train_state, grads, max_norm, self._max_grad_norm is not None
        )
        applied = bool(finite)  # host fetch — the barrier telemetry times against
        self._train_state = new_state
        self._last_grad_norm = gnorm
        if tel is not None:
            tel.on_apply_gradients(time.perf_counter() - t0)
        return applied

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: float = 2.0):
        """Arm gradient clipping for the next optimizer step and return the
        current accumulated-grad global norm (reference: accelerator.py:2946).
        ``parameters`` is accepted for signature parity and ignored — clipping
        always applies to the prepared state's grads."""
        if norm_type != 2.0:
            raise NotImplementedError("Only L2 grad-norm clipping is supported (MXU-friendly).")
        self._max_grad_norm = float(max_norm)
        grads = self._optimizers[0].grads if self._optimizers else self._train_state.accum_grads
        if grads is None:
            return None
        if self._gradnorm_jit is None:
            self._gradnorm_jit = jax.jit(optax.global_norm)
        return self._gradnorm_jit(grads)

    def clip_grad_value_(self, parameters=None, clip_value: float = 1.0):
        raise NotImplementedError(
            "clip_grad_value_ is not supported; use clip_grad_norm_ (value-clipping "
            "breaks DP-mean linearity and is rarely used on TPU)."
        )

    # ------------------------------------------------------------------
    # Fused train step — the fast path
    # ------------------------------------------------------------------

    def prepare_train_step(
        self,
        loss_fn: Callable,
        *,
        has_aux: bool = False,
        mutable_state: bool = False,
        max_grad_norm: Optional[float] = None,
        donate: Optional[bool] = None,
        model: Optional[Model] = None,
    ) -> Callable:
        """Build ONE jitted step: ``step(state, batch) -> (state, metrics)``.

        - grad accumulation folds in as a ``lax.scan`` over microbatches: when
          ``gradient_accumulation_steps > 1`` one call consumes the FULL
          optimizer batch with a leading accumulation axis. Prepared
          dataloaders add that axis automatically (host-side reshape of each
          process's local shard keeps the dp sharding layout exact).
        - precision policy: params cast to compute dtype at use; fp32 masters
          updated; fp16 loss scaling handled.
        - ``donate``: state buffers are donated so params/opt-state update in
          place in HBM (default from JitConfig).
        - ``mutable_state``: for models carrying non-param collections that
          the forward updates (flax ``batch_stats`` — BatchNorm). The loss fn
          then takes ``(params, extra_state, batch)`` and returns
          ``(loss, new_extra_state)``; the step threads the updated
          collections through ``state.extra_state``. Because the batch axis
          is dp-sharded under GSPMD, BatchNorm's batch reductions compile to
          cross-device means — sync-BN semantics with no extra machinery
          (the reference needs SyncBatchNorm conversion for this).
        """
        if not self._train_states:
            raise RuntimeError("Call accelerator.prepare(...) first.")
        if mutable_state and has_aux:
            raise ValueError("mutable_state and has_aux are mutually exclusive")
        # Multi-model: `model=` selects whose TrainState this step advances
        # (each prepared model owns a slot); default is the primary.
        slot = 0
        if model is not None:
            slot = getattr(model, "_state_slot", None)
            if slot is None or model._accelerator is not self:
                raise ValueError("model was not prepared by this Accelerator")
        if donate is None:
            donate = self.jit_config.donate_state
        policy = self._mp_policy
        tx = self._train_states[slot].tx
        num_accum = self.gradient_state.num_steps
        if max_grad_norm is None:
            # Migrated ds_config gradient_clipping applies like the DS engine
            # applied it (DeepSpeedPlugin.from_ds_json).
            max_grad_norm = self._ds_gradient_clipping
        clip_enabled = max_grad_norm is not None
        max_norm = float(max_grad_norm or 0.0)
        meta = (
            self._slot_meta[slot]
            if slot < len(self._slot_meta)
            else {"grad_shardings": self._grad_shardings, "opt_offload": self._opt_offload}
        )
        grad_shardings = meta["grad_shardings"]  # ZeRO-2: reduce-scatter grads

        def _loss_and_grads(params, extra, loss_scale, microbatch):
            def _fn(p):
                if mutable_state:
                    loss, new_extra = loss_fn(policy.cast_for_compute(p), extra, microbatch)
                    aux = None
                else:
                    out = loss_fn(policy.cast_for_compute(p), microbatch)
                    loss, aux = (out if has_aux else (out, None))
                    new_extra = extra
                scale = loss_scale.scale if loss_scale is not None else 1.0
                return (loss * scale).astype(jnp.float32), (loss, aux, new_extra)

            (_, (loss, aux, new_extra)), grads = jax.value_and_grad(_fn, has_aux=True)(params)
            if grad_shardings is not None:
                # SHARD_GRAD_OP: constrain grads to the opt-state sharding so
                # GSPMD lowers the DP grad sync as reduce-scatter (each chip
                # keeps only its 1/W slice) instead of all-reduce.
                grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
            return loss, aux, new_extra, grads

        opt_offload = meta["opt_offload"]  # (device shardings, host shardings) | None

        def _update(state: TrainState, grads):
            if state.loss_scale is not None:
                grads = state.loss_scale.unscale(grads)
                finite = grads_all_finite(grads)
            else:
                finite = jnp.asarray(True)
            gnorm = optax.global_norm(grads)
            if clip_enabled:
                factor = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * factor, grads)
            opt_state = state.opt_state
            if opt_offload is not None:
                # cpu_offload: stream host-pinned opt state onto the mesh for
                # the update, back to host after (XLA host-offload transfers).
                opt_state = jax.device_put(opt_state, opt_offload[0])
            updates, new_opt = tx.update(grads, opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_params = jax.tree.map(lambda n, o: jnp.where(finite, n, o), new_params, state.params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o) if hasattr(n, "shape") else n,
                new_opt,
                opt_state,
            )
            if opt_offload is not None:
                new_opt = jax.device_put(new_opt, opt_offload[1])
            new_scale = state.loss_scale.update(finite) if state.loss_scale is not None else None
            return state.replace(
                step=state.step + jnp.where(finite, 1, 0),
                params=new_params,
                opt_state=new_opt,
                loss_scale=new_scale,
            ), gnorm

        comm_hook = (
            getattr(self.ddp_handler, "comm_hook", "no")
            if self.ddp_handler is not None
            else "no"
        ) or "no"
        if comm_hook != "no":
            return self._comm_hook_step(
                loss_fn,
                slot=slot,
                comm_hook=comm_hook,
                policy=policy,
                num_accum=num_accum,
                update_fn=_update,
                donate=donate,
                has_aux=has_aux,
                mutable_state=mutable_state,
                grad_shardings=grad_shardings,
            )

        # SDC sentinel (sdc.py): when armed, every step's metrics carry a
        # cheap fused fingerprint of the new params + grad norm. Computed
        # INSIDE the jitted step so it folds into the one existing metrics
        # fetch, observed one step lagged like loss/grad_norm.
        _sdc_armed = (self.fault_tolerance is not None
                      and self.fault_tolerance.sdc is not None)

        def _maybe_digest(metrics, new_state, gnorm):
            if _sdc_armed:
                from .sdc import integrity_digest

                metrics["sdc_digest"] = integrity_digest(new_state.params, gnorm)
            return metrics

        if num_accum > 1:

            def step(state: TrainState, batch):
                batch = _microbatch_split(batch, num_accum)

                def body(carry, microbatch):
                    grads_acc, loss_acc, extra = carry
                    loss, _aux, new_extra, grads = _loss_and_grads(
                        state.params, extra, state.loss_scale, microbatch
                    )
                    return (
                        jax.tree.map(jnp.add, grads_acc, grads),
                        loss_acc + loss,
                        new_extra,
                    ), None

                zeros = jax.tree.map(lambda p: jnp.zeros_like(p), state.params)
                (grads, loss_sum, new_extra), _ = jax.lax.scan(
                    body, (zeros, jnp.asarray(0.0, jnp.float32), state.extra_state), batch
                )
                grads = jax.tree.map(lambda g: g / num_accum, grads)
                new_state, gnorm = _update(state, grads)
                if mutable_state:
                    new_state = new_state.replace(extra_state=new_extra)
                return new_state, _maybe_digest(
                    {"loss": loss_sum / num_accum, "grad_norm": gnorm},
                    new_state, gnorm)

        else:

            def step(state: TrainState, batch):
                loss, _aux, new_extra, grads = _loss_and_grads(
                    state.params, state.extra_state, state.loss_scale, batch
                )
                new_state, gnorm = _update(state, grads)
                if mutable_state:
                    new_state = new_state.replace(extra_state=new_extra)
                return new_state, _maybe_digest(
                    {"loss": loss, "grad_norm": gnorm}, new_state, gnorm)

        jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
        if self.compile_manager is not None:
            # Registers the underlying jitted step for executable counting
            # and AOT-warms every known manifest signature before step 0.
            self.compile_manager.register_step(jitted, slot=slot, label="train_step")

        def step_and_track(state: TrainState, batch):
            cm = self.compile_manager
            if cm is not None:
                cm.observe(batch)  # new signatures land in the shapes manifest
            if _sdc_armed:
                sdc = self.fault_tolerance.sdc
                if sdc.needs_golden:
                    # First prepared step: snapshot (state, batch) to host and
                    # pre-run the probe — it compiles the SAME executable the
                    # real steps use (identical shapes + shardings), so every
                    # later probe is recompile-free. Runs on restored copies:
                    # buffer donation never touches the live state.
                    sdc.capture_golden(jitted, state, batch)
            tel = self.telemetry
            if tel is None:
                new_state, metrics = jitted(state, batch)
                # Keep the accelerator's view current: with buffer donation
                # the previous state's arrays are dead after this call, so
                # save_state, Model.__call__ and trackers must see the new one.
                self._train_states[slot] = new_state
                return self._maybe_sentinel(new_state, metrics, slot), metrics
            if tel.profiler is not None:
                # One-time AOT cost_analysis capture (flops + bytes) BEFORE
                # the step call, while the pre-donation buffers are live —
                # the same slot sdc.capture_golden uses. Leaves the jit
                # dispatch cache untouched (flat-cache invariant).
                tel.profiler.capture_cost(jitted, state, batch)
            t0 = time.perf_counter()
            new_state, metrics = jitted(state, batch)
            if tel.handler.sync_timing:
                jax.block_until_ready(metrics)
            wall = time.perf_counter() - t0
            self._train_states[slot] = new_state
            tel.on_train_step(jitted, batch, wall, metrics=metrics)
            return self._maybe_sentinel(new_state, metrics, slot), metrics

        # The underlying jit, for callers that inspect the program itself
        # (chip_smoke.py reads the compiled HLO for the Mosaic custom call).
        step_and_track.jitted = jitted
        return step_and_track

    def _maybe_sentinel(self, new_state: TrainState, metrics, slot: int) -> TrainState:
        """Divergence-sentinel hook shared by every prepared-step wrapper:
        feeds the step metrics to fault tolerance (lagged host fetch — never
        stalls dispatch) and, when the sentinel rolled back, hands the
        RESTORED state back to the training loop in place of the diverged
        one (the loop's local ``state`` variable would otherwise keep
        training the garbage)."""
        ft = self.fault_tolerance
        if ft is None:
            return new_state
        restored = ft.observe_step(metrics, slot=slot)
        return restored if restored is not None else new_state

    def warmup_compile(self) -> Optional[dict]:
        """Compile every shapes-manifest signature against the prepared train
        steps NOW, off the training clock (compile_manager.py). Runs
        automatically inside :meth:`prepare_train_step` when a
        :class:`~accelerate_tpu.utils.CompileKwargs` handler enables warmup;
        call it again manually after the manifest grows (e.g. a fresh eval
        shape appeared). Idempotent — already-warmed signatures are skipped.
        Returns the cumulative warmup stats, or ``None`` when the compile
        manager is off."""
        if self.compile_manager is None:
            return None
        return self.compile_manager.warmup()

    def build_serving_engine(self, model, config: Optional[ServingConfig] = None,
                             disagg: Optional[DisaggConfig] = None, *,
                             chaos=None, tracing=None, journal=None):
        """Construct a :class:`~accelerate_tpu.serving.ServingEngine` over
        ``model`` (a prepared/loaded model with params on device), wired to
        this Accelerator's compile manager (prefill-chunk ladder, generation
        warmup) and telemetry recorder (serving block). ``config`` falls back
        to the :class:`~accelerate_tpu.utils.ServingConfig` handler passed at
        init; serving stays fully off — zero imports, zero hooks — without
        one.

        With a :class:`~accelerate_tpu.utils.DisaggConfig` — passed here or
        as a kwargs handler — the engine upgrades to the two-mesh
        :class:`~accelerate_tpu.disagg.DisaggServingEngine` (prefill and
        decode on planner-sized disjoint device slices, KV pages streamed
        between them). Disaggregation stays fully off without one.

        The Accelerator's fault-tolerance manager (when armed via
        :class:`~accelerate_tpu.utils.FaultToleranceKwargs`) is wired in
        too: a SIGTERM mid-serving triggers the engine's preemption drain
        (finish in-flight, shed the queue, report exit code 75).
        ``chaos`` takes a :class:`~accelerate_tpu.chaos.FaultInjector` for
        deterministic fault-injection runs. ``tracing`` takes a
        :class:`~accelerate_tpu.tracing.TraceRecorder`; it defaults to the
        recorder built from ``TelemetryKwargs(tracing=...)``, so most runs
        only set the kwarg and the engine picks it up through telemetry.
        ``journal`` takes a :class:`~accelerate_tpu.journal.RequestJournal`
        (or is built from ``ServingConfig.journal_dir``) to write-ahead-log
        every admission for exactly-once crash recovery (journal.py)."""
        cfg = config if config is not None else self.serving_config
        if cfg is None or not cfg.enabled:
            raise ValueError(
                "serving is off: pass ServingConfig(...) here or in "
                "Accelerator(kwargs_handlers=[...])."
            )
        dcfg = disagg if disagg is not None else self.disagg_config
        if dcfg is not None and dcfg.enabled:
            from .disagg import DisaggServingEngine

            return DisaggServingEngine(
                model, cfg, disagg=dcfg,
                compile_manager=self.compile_manager, telemetry=self.telemetry,
                fault_tolerance=self.fault_tolerance, chaos=chaos,
                tracing=tracing, journal=journal,
            )
        from .serving import ServingEngine

        return ServingEngine(
            model, cfg,
            compile_manager=self.compile_manager, telemetry=self.telemetry,
            fault_tolerance=self.fault_tolerance, chaos=chaos,
            tracing=tracing, journal=journal,
        )

    def build_fleet_router(self, cells, config=None, *, chaos=None,
                           tracing=None):
        """Construct a :class:`~accelerate_tpu.fleet.FleetRouter` over a
        registry of journaled serving cells (``{name: engine}`` or a list —
        each built via :meth:`build_serving_engine` with its OWN
        ``ServingConfig.journal_dir``), wired to this Accelerator's
        telemetry. The router adds the cell-granular robustness layer:
        session-affinity routing with load spillover, per-tick health
        classification, exactly-once cross-cell drain of a dead cell's
        journal, and whole-cell canary publish / scale (see
        :mod:`accelerate_tpu.fleet`). The fleet layer is OFF unless this
        router is built and ticked.

        ``config`` is a :class:`~accelerate_tpu.fleet.FleetConfig`;
        ``chaos`` takes a :class:`~accelerate_tpu.chaos.FaultInjector`
        (``cell_crash`` / ``cell_partition`` / ``router_heartbeat``
        points); ``tracing`` a
        :class:`~accelerate_tpu.tracing.TraceRecorder` for fleet spans and
        the ``accelerate_tpu_fleet_*`` gauge provider."""
        from .fleet import FleetRouter

        return FleetRouter(
            cells, config, chaos=chaos, telemetry=self.telemetry,
            tracing=tracing,
        )

    def build_weight_publisher(self, engine, config=None, *, chaos=None):
        """Construct a :class:`~accelerate_tpu.publish.WeightPublisher` that
        watches this (or another) run's checkpoint directory and hot-swaps
        verified weights into ``engine`` (a live
        :class:`~accelerate_tpu.serving.ServingEngine`) with zero downtime:
        only committed, hash-verified checkpoints are publishable, the
        train→serve topology gap is bridged through the resharding executor,
        and new versions roll out through a canary cohort with SLO
        auto-rollback (see :mod:`accelerate_tpu.publish`).

        ``config`` is a :class:`~accelerate_tpu.publish.PublishConfig`;
        ``chaos`` defaults to the engine's injector so a single seeded
        schedule covers serving and publication faults together."""
        from .publish import WeightPublisher

        if chaos is None:
            chaos = getattr(engine, "chaos", None)
        return WeightPublisher(
            engine, config, chaos=chaos, telemetry=self.telemetry,
        )

    def build_autoscale_controller(self, engine, config=None, *,
                                   device_pool=None, chaos=None):
        """Construct an
        :class:`~accelerate_tpu.autoscale.AutoscaleController` that closes
        the telemetry → planner → live-resize loop over ``engine`` (a
        :class:`~accelerate_tpu.disagg.DisaggServingEngine`): rolling-window
        SLO signals sampled every ``poll_ticks``, hysteresis + consecutive-
        breach + cooldown flap damping, a shared planner gate on every
        proposed topology, and zero-downtime grow/shrink/re-split through
        ``engine.resize`` (see :mod:`accelerate_tpu.autoscale`). Autoscaling
        is OFF unless this controller is built and polled.

        ``config`` is an :class:`~accelerate_tpu.autoscale.AutoscaleConfig`;
        ``device_pool`` is the device set the controller may scale across
        (defaults to the engine's current devices — no headroom);
        ``chaos`` defaults to the engine's injector so one seeded schedule
        covers serving, resize, and decision faults together."""
        from .autoscale import AutoscaleController

        if chaos is None:
            chaos = getattr(engine, "chaos", None)
        return AutoscaleController(
            engine, config, device_pool=device_pool, chaos=chaos,
            telemetry=self.telemetry,
        )

    def _comm_hook_step(
        self,
        loss_fn,
        *,
        slot: int,
        comm_hook: str,
        policy,
        num_accum: int,
        update_fn,
        donate: bool,
        has_aux: bool,
        mutable_state: bool,
        grad_shardings,
    ):
        """Build a train step whose DP gradient sync runs through a
        compression comm hook (``DistributedDataParallelKwargs.comm_hook``,
        reference: utils/dataclasses.py:157-241).

        GSPMD normally places the gradient ``psum`` itself, so to *replace*
        it the gradients are computed under ``shard_map`` over the DP axes
        (manual collectives) and reduced by
        :func:`parallel.comm_hooks.make_comm_hook_reducer` — fp16/bf16 wire
        compression or PowerSGD low-rank + error feedback. Hook state (the
        PowerSGD Q factors and error buffers) threads through the returned
        step in a host-side holder, one entry per prepared model slot.

        DDP semantics only: replicated params, pure data-parallel mesh.
        """
        from jax.sharding import PartitionSpec as P

        from .parallel.comm_hooks import init_powersgd_state, make_comm_hook_reducer

        if mutable_state or has_aux:
            raise NotImplementedError(
                "comm_hook is not supported together with mutable_state/has_aux"
            )
        if grad_shardings is not None:
            raise ValueError(
                "comm_hook requires replicated (DDP) gradients — it cannot "
                "compose with ZeRO-2 SHARD_GRAD_OP reduce-scatter"
            )
        mesh = self.mesh
        dp_axes = tuple(
            a for a in ("dp_replicate", "dp_shard") if mesh.shape.get(a, 1) > 1
        )
        bad = [
            a for a, s in mesh.shape.items()
            if a not in ("dp_replicate", "dp_shard") and s > 1
        ]
        if bad:
            raise ValueError(
                f"comm_hook requires a pure data-parallel mesh; axes {bad} have "
                "size > 1 (the reference's DDP comm hooks are DP-only too)"
            )
        params0 = self._train_states[slot].params
        for leaf in jax.tree.leaves(params0):
            spec = getattr(getattr(leaf, "sharding", None), "spec", None)
            if spec is not None and any(ax is not None for ax in spec):
                raise ValueError(
                    "comm_hook requires replicated (DDP) parameters; param "
                    f"sharded as {spec} — drop the FSDP plugin or the hook"
                )
        rank = int(getattr(self.ddp_handler, "powersgd_rank", 8))
        reducer = make_comm_hook_reducer(comm_hook, dp_axes, rank=rank)
        dp_total = 1
        for a in dp_axes:
            dp_total *= mesh.shape[a]
        if comm_hook == "powersgd":
            comm_state0 = init_powersgd_state(
                params0, rank, dp_size=dp_total, mesh=mesh, dp_axes=dp_axes
            )
        else:
            comm_state0 = jax.tree.map(lambda _: {}, params0)

        rep = lambda tree: jax.tree.map(  # noqa: E731 - local spec builder
            lambda x: P(*([None] * jnp.ndim(x))), tree
        )
        # Hook-state specs: Q factors are pmean'd (honestly replicated); the
        # error-feedback buffers are per-worker and SHARDED on their leading
        # dp axis (see init_powersgd_state's docstring for why a replicated
        # claim would be a silent-corruption hazard).
        _params_treedef = jax.tree_util.tree_structure(params0)
        _entries = _params_treedef.flatten_up_to(comm_state0)
        comm_specs = jax.tree_util.tree_unflatten(
            _params_treedef,
            [
                {}
                if not e
                else {
                    "q": P(None, None),
                    "e": P(dp_axes, None, None) if dp_axes else P(None, None, None),
                }
                for e in _entries
            ],
        )

        def hook_step(state: TrainState, batch, comm_state):
            loss_scale = state.loss_scale

            def local(params, batch, comm_state):
                def _fn(p, mb):
                    loss = loss_fn(policy.cast_for_compute(p), mb)
                    scale = loss_scale.scale if loss_scale is not None else 1.0
                    return (loss * scale).astype(jnp.float32), loss

                gfn = jax.value_and_grad(_fn, has_aux=True)
                if num_accum > 1:
                    micro = _microbatch_split(batch, num_accum, what="Per-device batch")

                    def body(carry, mb):
                        gacc, lacc = carry
                        (_, loss), g = gfn(params, mb)
                        return (jax.tree.map(jnp.add, gacc, g), lacc + loss), None

                    zeros = jax.tree.map(jnp.zeros_like, params)
                    (grads, loss_sum), _ = jax.lax.scan(
                        body, (zeros, jnp.asarray(0.0, jnp.float32)), micro
                    )
                    # DDP no_sync semantics: accumulate locally, reduce ONCE
                    # at the boundary — the hook fires once per optimizer
                    # step, exactly like the reference's bucket hooks.
                    grads = jax.tree.map(lambda g: g / num_accum, grads)
                    loss = loss_sum / num_accum
                else:
                    (_, loss), grads = gfn(params, batch)
                # PowerSGD reduces in TRUE gradient units: its error-feedback
                # buffers must not inherit the fp16 loss-scale factor (a
                # scale change would corrupt the carried residual by that
                # factor). fp16/bf16 wire hooks do the OPPOSITE — they
                # compress the still-scaled gradient, exactly like the
                # reference's fp16_compress_hook: the scale is what keeps
                # ~1e-6 grads above fp16's min normal on the wire.
                unscale = comm_hook == "powersgd"
                scale = loss_scale.scale if loss_scale is not None else None
                if unscale and scale is not None:
                    grads = jax.tree.map(lambda g: g / scale, grads)
                finite = grads_all_finite(grads)
                # The flag MUST agree across all DP workers: the reducer
                # pmean's P/Q, so one worker's inf grads make every worker's
                # new_comm NaN — a worker whose *local* grads were finite
                # would otherwise commit the poisoned (replicated-declared)
                # state and freeze the hook forever.
                for ax in dp_axes:
                    finite = jax.lax.pmin(finite.astype(jnp.int32), ax).astype(bool)
                grads, new_comm = reducer(grads, comm_state)
                # An overflowed step (inf grads -> NaN through qr) must not
                # poison the persistent hook state: keep the previous one.
                new_comm = jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new_comm, comm_state
                )
                if unscale and scale is not None:
                    # update_fn unscales again — hand back scaled grads so the
                    # hooked and unhooked paths share one _update.
                    grads = jax.tree.map(lambda g: g * scale, grads)
                for ax in dp_axes:
                    loss = jax.lax.pmean(loss, ax)
                return loss, grads, new_comm

            batch_specs = jax.tree.map(
                lambda x: P(dp_axes, *([None] * (jnp.ndim(x) - 1)))
                if dp_axes
                else P(*([None] * jnp.ndim(x))),
                batch,
            )
            loss, grads, new_comm = jax.shard_map(
                local,
                mesh=mesh,
                in_specs=(rep(state.params), batch_specs, comm_specs),
                out_specs=(P(), rep(state.params), comm_specs),
                check_vma=False,
            )(state.params, batch, comm_state)
            new_state, gnorm = update_fn(state, grads)
            return new_state, {"loss": loss, "grad_norm": gnorm}, new_comm

        # Donate the comm state too: the PowerSGD error buffers are
        # params-sized fp32 — updating them in place matters.
        jitted = jax.jit(hook_step, donate_argnums=(0, 2) if donate else ())
        holder = {"comm_state": comm_state0}
        if self.compile_manager is not None:
            # warmable=False: the hook step threads comm_state as a third
            # argument, which the manifest-driven warmup cannot synthesize.
            self.compile_manager.register_step(
                jitted, slot=slot, label="comm_hook_step", warmable=False
            )

        def step_and_track(state: TrainState, batch):
            cm = self.compile_manager
            if cm is not None:
                cm.observe(batch)
            tel = self.telemetry
            if tel is not None and tel.profiler is not None:
                # Same one-time cost capture as the fused path; the comm
                # hook threads its state as a third traced argument.
                tel.profiler.capture_cost(
                    jitted, state, batch, holder["comm_state"])
            t0 = time.perf_counter() if tel is not None else 0.0
            new_state, metrics, holder["comm_state"] = jitted(
                state, batch, holder["comm_state"]
            )
            self._train_states[slot] = new_state
            if tel is not None:
                if tel.handler.sync_timing:
                    jax.block_until_ready(metrics)
                tel.on_train_step(jitted, batch, time.perf_counter() - t0, metrics=metrics)
            return self._maybe_sentinel(new_state, metrics, slot), metrics

        return step_and_track

    # ------------------------------------------------------------------
    # Metrics & collectives surface (reference: accelerator.py:3000-3270)
    # ------------------------------------------------------------------

    def gather(self, tensor):
        return gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather across dp ranks and drop the duplicate tail samples that
        ``even_batches`` added on the last batch
        (reference: accelerator.py:3068-3140)."""
        try:
            recursively_apply(lambda x: x, input_data, error_on_other_type=True)
            all_tensors = True
        except TypeError:
            all_tensors = False
        if use_gather_object or not all_tensors:
            data = gather_object(input_data)
        else:
            data = self.gather(input_data)
        try:
            if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
                def _adjust(tensor):
                    return tensor[: self.gradient_state.remainder]

                if all_tensors and not use_gather_object:
                    data = recursively_apply(_adjust, data)
                else:
                    data = data[: self.gradient_state.remainder]
        except (TypeError, IndexError, KeyError) as e:
            # Un-sliceable payloads keep the reference's forgiving contract,
            # but a real trimming bug must not vanish silently (VERDICT r2).
            # Strings only: warning_once dedups on its args' reprs, and a
            # live exception instance would defeat dedup AND pin its
            # traceback (and the gathered tensors it references) forever.
            logger.warning_once(
                "gather_for_metrics could not trim the duplicate tail samples "
                f"({type(e).__name__}: {e}); returning the untrimmed gather."
            )
        return data

    def reduce(self, tensor, reduction: str = "sum", scale: float = 1.0):
        return reduce(tensor, reduction, scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        return pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        return extract_model_from_parallel(model, keep_fp32_wrapper)

    # -- preemption observation (fault_tolerance.py) ----------------------

    def should_checkpoint(self) -> bool:
        """True once this process received a preemption signal
        (SIGTERM/SIGUSR1) and a final save should happen NOW. Local and
        free — poll it every step. On multi-host meshes where only some
        hosts get the signal, use :meth:`check_preemption` (collective)
        at a coarser cadence instead so the gang saves coherently."""
        ft = self.fault_tolerance
        return ft is not None and ft.preempted

    def check_preemption(self) -> bool:
        """Collective preemption poll: True on EVERY rank as soon as ANY
        rank received a preemption signal (one tiny allreduce — call it
        every step or every N steps). After the final ``save_state()``,
        exit with :attr:`preemption_exit_code` so the launch gang loop
        relaunches the run as resumable."""
        ft = self.fault_tolerance
        if ft is None:
            return False
        if self.num_processes <= 1:
            return ft.preempted
        return self.state.agree_any(ft.preempted)

    @property
    def preemption_exit_code(self) -> int:
        """Exit code a preemption-triggered shutdown should use
        (``utils.constants.PREEMPTION_EXIT_CODE``): the ``accelerate-tpu
        launch`` gang loop treats it as resumable and relaunches with
        ``ACCELERATE_RESTART_ATTEMPT`` bumped."""
        from .utils.constants import PREEMPTION_EXIT_CODE

        return PREEMPTION_EXIT_CODE

    # -- trigger sync (reference: accelerator.py:2852-2909) ---------------

    def set_trigger(self):
        self.flag_tensor = jnp.asarray(1, jnp.int32)

    def check_trigger(self) -> bool:
        if self.flag_tensor is None:
            self.flag_tensor = jnp.asarray(0, jnp.int32)
        flag = reduce(self.flag_tensor, reduction="sum")
        if int(np.asarray(flag)) >= 1:
            self.flag_tensor = jnp.asarray(0, jnp.int32)
            return True
        return False

    # ------------------------------------------------------------------
    # Autocast / profile contexts
    # ------------------------------------------------------------------

    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """Advisory on TPU: precision is a compile-time policy applied in the
        step builders; this context exists for API parity and casts eager ops
        via jax default dtype promotion (reference: accelerator.py:3410-3437)."""
        logger.warning_once(
            "Accelerator.autocast() is a no-op on TPU: mixed precision is a "
            "compile-time policy already applied inside prepared steps "
            "(mixed_precision=%s). Remove the context or keep it for API "
            "parity — behavior is identical either way.",
            self.state.mixed_precision,
        )
        yield

    @contextlib.contextmanager
    def profile(self, profile_handler: Optional[ProfileKwargs] = None):
        """jax.profiler trace honoring :class:`ProfileKwargs`
        (reference: accelerator.py:4202-4259 wraps torch.profiler).

        - ``schedule_option`` (wait/warmup/active/repeat/skip_first, torch
          semantics): yields a session whose ``.step()`` you call once per
          train step; traces cover only the active windows
          (``<dir>/cycle_<i>``).
        - ``profile_memory``: saves a device-memory profile next to each trace.
        - ``on_trace_ready``: called with the session after each trace closes.
        - ``record_shapes``/``with_stack``/``with_flops`` are inherent to XLA
          traces (shapes, source attribution and cost analysis are always in
          the XPlane data) — accepted for API parity.
        """
        from .utils.profiling import ProfileSession

        handler = profile_handler or self.profile_handler or ProfileKwargs()
        trace_dir = handler.output_trace_dir or (self.project_dir or ".")
        if handler.output_trace_dir is None and self.project_dir is None:
            yield None
            return
        session = ProfileSession(handler, trace_dir)
        session.enter()
        try:
            yield session
        finally:
            session.exit()

    # ------------------------------------------------------------------
    # Checkpointing & model export (reference: accelerator.py:3439-3748)
    # ------------------------------------------------------------------

    def register_for_checkpointing(self, *objects):
        invalid = [obj for obj in objects if not (hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"All `objects` must include a `state_dict` and `load_state_dict` function to be stored: {invalid}"
            )
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook: Callable):
        """``hook(models, weights, output_dir)`` runs before every
        ``save_state`` write (reference: accelerator.py:3856-3890). Here the
        hook receives ``(prepared_models, train_state, output_dir)``. Returns
        a removable handle (``.remove()``)."""
        self._save_state_pre_hooks.append(hook)
        return _HookHandle(self._save_state_pre_hooks, hook)

    def register_load_state_pre_hook(self, hook: Callable):
        """``hook(models, input_dir)`` runs before every ``load_state``
        restore (reference: accelerator.py:3892-3923)."""
        self._load_state_pre_hooks.append(hook)
        return _HookHandle(self._load_state_pre_hooks, hook)

    def save_state(self, output_dir: Optional[str] = None, safe_serialization: bool = True, block: bool = True, **save_model_func_kwargs):
        """``block=False`` + ``DISTRIBUTED_STATE_DICT``: the save returns as
        soon as device→host copies finish and bytes persist in a background
        thread while training continues (orbax async — the step's donated
        buffers are safe, the snapshot is already on host). Call
        :meth:`wait_for_checkpoint` (or ``end_training``) to drain; a second
        async save waits for the first. The reference has no async tier.

        With a :class:`~accelerate_tpu.utils.FaultToleranceKwargs` handler
        the save stages into ``<dir>.tmp``, commits atomically via
        manifest+rename, and transient storage failures retry with backoff
        (falling back to ``fallback_dir`` when configured)."""
        from .checkpointing import _checkpoint_dir, save_accelerator_state

        ft = self.fault_tolerance
        if ft is None:
            if self._save_state_pre_hooks:
                # Hooks see the RESOLVED target (automatic_checkpoint_naming
                # makes the raw arg None) so sidecar writers land next to the
                # checkpoint.
                resolved = _checkpoint_dir(self, output_dir)
                for hook in self._save_state_pre_hooks:
                    hook(self._models, self._train_state, resolved)
                output_dir = resolved
            return save_accelerator_state(
                self, output_dir, safe_serialization=safe_serialization, block=block
            )

        resolved = _checkpoint_dir(self, output_dir)

        def do_save(target: str) -> str:
            if self._save_state_pre_hooks:
                from .fault_tolerance import staging_path

                # Under atomic saves the hooks write into the STAGING dir so
                # their sidecar files are covered by the manifest and ride
                # the same commit; do_save re-runs them on every retry
                # attempt (the retry loop clears the staging dir between
                # attempts).
                hook_dir = staging_path(target) if ft.atomic else target
                if ft.atomic:
                    import shutil

                    if self.is_main_process and os.path.isdir(hook_dir):
                        shutil.rmtree(hook_dir)
                    self.wait_for_everyone()
                    os.makedirs(hook_dir, exist_ok=True)
                    # Tell save_accelerator_state this staging dir is live
                    # (hook sidecar files), not a stale leftover to wipe.
                    ft.prearm_staging(hook_dir)
                for hook in self._save_state_pre_hooks:
                    hook(self._models, self._train_state, hook_dir)
            return save_accelerator_state(
                self, target, safe_serialization=safe_serialization, block=block
            )

        return ft.run_save_with_retry(do_save, resolved)

    def wait_for_checkpoint(self):
        """Block until any in-flight async checkpoint finished persisting.
        A failure in orbax's background persist thread surfaces HERE (the
        save call itself already returned): the broken checkpointer is
        dropped so the next save starts fresh, the failure lands in
        telemetry, and a
        :class:`~accelerate_tpu.fault_tolerance.CheckpointSaveError` is
        raised instead of the error being silently swallowed."""
        ckptr = getattr(self, "_async_checkpointer", None)
        if ckptr is None:
            return
        try:
            ckptr.wait_until_finished()
            check = getattr(ckptr, "check_for_errors", None)
            if callable(check):
                check()
        except Exception as e:
            try:
                ckptr.close()
            except Exception:
                pass
            self._async_checkpointer = None
            if self.telemetry is not None:
                self.telemetry.record_event(
                    "checkpoint_async_error", error=f"{type(e).__name__}: {e}"[:500]
                )
            from .fault_tolerance import CheckpointSaveError

            raise CheckpointSaveError(
                f"async (orbax) checkpoint failed to persist in the "
                f"background: {e}"
            ) from e

    def _close_async_checkpointer(self):
        ckptr = getattr(self, "_async_checkpointer", None)
        if ckptr is not None:
            ckptr.wait_until_finished()
            ckptr.close()
            self._async_checkpointer = None

    def load_state(self, input_dir: Optional[str] = None, **load_model_func_kwargs):
        from .checkpointing import _checkpoint_dir, load_accelerator_state

        if self._load_state_pre_hooks:
            resolved = _checkpoint_dir(self, input_dir, for_load=True)
            for hook in self._load_state_pre_hooks:
                hook(self._models, resolved)
            input_dir = resolved
        return load_accelerator_state(self, input_dir)

    def migrate_plan(self, plan) -> dict:
        """Hot-swap the parallel layout mid-run (resharding.py).

        Reshards every prepared ``TrainState`` in place onto the mesh the new
        plan implies — leaves move through budget-bounded, donated
        ``device_put`` batches, so peak HBM stays within the
        :class:`~accelerate_tpu.utils.ElasticKwargs` staging budget. RNG,
        dataloader cursors, grad-accum state, loss scale and the step counter
        carry over untouched (they are replicated or host-side). The
        compile-manager's executables are invalidated — the old ones were
        specialized to the previous shardings — and re-warmed for the new
        shapes when ``warm_after_migrate`` is on.

        ``plan`` is a :class:`~accelerate_tpu.planner.ParallelPlan` or a
        :class:`~accelerate_tpu.parallelism_config.ParallelismConfig`.
        Requires an ElasticKwargs handler. Step functions built by
        ``prepare_train_step`` keep working (jit retraces for the new
        shardings), except ZeRO-2 (``SHARD_GRAD_OP``) and ``cpu_offload``
        setups, whose steps captured the old sharding constraints — rebuild
        those with ``prepare_train_step`` after migrating.

        Returns the reshard stats dict (also recorded as the telemetry
        ``reshard`` block)."""
        if self.elastic is None or not self.elastic.enabled:
            raise RuntimeError(
                "migrate_plan requires an ElasticKwargs handler: "
                "Accelerator(kwargs_handlers=[ElasticKwargs()])."
            )
        if not self._train_states:
            raise RuntimeError("Nothing prepared; call accelerator.prepare(...) first.")
        new_pc = (
            plan.to_parallelism_config() if hasattr(plan, "to_parallelism_config") else plan
        )
        if not isinstance(new_pc, ParallelismConfig):
            raise TypeError(
                f"migrate_plan takes a ParallelPlan or ParallelismConfig, got {type(plan)!r}"
            )
        # Pause point: drain any async checkpoint writer and let in-flight
        # steps retire before buffers start being donated out from under them.
        if hasattr(self, "wait_for_checkpoint"):
            self.wait_for_checkpoint()
        jax.block_until_ready(
            [s for st in self._train_states for s in jax.tree_util.tree_leaves(st)]
        )

        old_pc = self.state.parallelism_config
        new_pc = new_pc.infer_missing_axis(len(self.state.devices))
        self.state.parallelism_config = new_pc
        self.state._mesh = None  # the mesh property rebuilds from new_pc
        try:
            new_mesh = self.state.mesh
            executor = self.elastic.executor(new_mesh)
            for slot, st in enumerate(self._train_states):
                model = next(
                    (m for m in self._models if getattr(m, "_state_slot", None) == slot),
                    None,
                )
                if model is None:
                    continue
                param_shardings = plan_parameter_sharding(
                    st.params,
                    new_mesh,
                    fsdp_plugin=self.fsdp_plugin,
                    parallelism_config=new_pc,
                    tp_rules=model.tp_rules,
                )
                if st.tx is not None:
                    opt_shardings, grad_shardings, opt_offload = self._build_opt_shardings(
                        model, st.params, param_shardings, st.tx, new_pc
                    )
                else:
                    opt_shardings = ()
                    grad_shardings, opt_offload = None, None
                rep = replicated(new_mesh)
                state_shardings = TrainState(
                    step=rep,
                    params=param_shardings,
                    opt_state=opt_shardings,
                    extra_state=jax.tree.map(lambda _: rep, st.extra_state)
                    if st.extra_state
                    else st.extra_state,
                    accum_grads=None,
                    loss_scale=jax.tree.map(lambda _: rep, st.loss_scale)
                    if st.loss_scale is not None
                    else None,
                    apply_fn=st.apply_fn,
                    tx=st.tx,
                )
                # In-flight accumulation windows migrate with everything else
                # (grads follow the ZeRO-2 constraint when one is active).
                migrate_shardings = state_shardings
                if st.accum_grads is not None:
                    migrate_shardings = state_shardings.replace(
                        accum_grads=grad_shardings or param_shardings
                    )
                new_state = executor.put_tree(
                    st, migrate_shardings, prefix=f"slot{slot}"
                )
                self._train_states[slot] = new_state
                self._slot_meta[slot] = {
                    "state_shardings": state_shardings,
                    "param_shardings": param_shardings,
                    "grad_shardings": grad_shardings,
                    "opt_offload": opt_offload,
                }
                if slot == 0:
                    self._state_shardings = state_shardings
                    self._param_shardings = param_shardings
                    self._grad_shardings = grad_shardings
                    self._opt_offload = opt_offload
        except Exception:
            # Roll the topology back so a failed migration leaves a
            # consistent (old) mesh behind; state leaves are untouched until
            # the executor runs, and put_tree only commits whole trees.
            self.state.parallelism_config = old_pc
            self.state._mesh = None
            raise
        # Jitted-step caches are stale: old executables were compiled for the
        # previous shardings (and donation layout).
        self._grad_fn_cache.clear()
        self._apply_jit = None
        self._gradnorm_jit = None
        if plan is not None and hasattr(plan, "to_parallelism_config"):
            self.active_plan = plan
            if self.telemetry is not None:
                self.telemetry.note_plan(plan.to_json_dict(), None)
            if self.compile_manager is not None:
                self.compile_manager.note_plan(plan)
        if self.compile_manager is not None:
            dropped = self.compile_manager.invalidate_steps()
            logger.info(
                "migrate_plan: dropped %d stale executable(s).", dropped,
                main_process_only=True,
            )
            if getattr(self.elastic.handler, "warm_after_migrate", True):
                self.compile_manager.warmup()
        stats = executor.stats()
        self.elastic.note_reshard(stats, kind="migrate")
        from .planner import _layout_dict, layout_str

        logger.info(
            "migrate_plan: %s -> %s (%d leaves, %s bytes, depth %d, %.3fs).",
            layout_str(_layout_dict(old_pc)) if old_pc is not None else "default",
            layout_str(_layout_dict(new_pc)),
            stats.get("moved_leaves", 0),
            f"{stats.get('bytes_transferred', 0):,}",
            stats.get("depth", 0),
            stats.get("wall_s", 0.0),
            main_process_only=True,
        )
        return stats

    def unscale_gradients(self, optimizer=None):
        """Parity advisory (reference: accelerator.py:2928-2944 unscales the
        GradScaler before manual grad inspection): fp16 loss-scale handling
        here is fused into the step — grads exposed via ``optimizer.grads`` /
        ``train_state.accum_grads`` are ALREADY unscaled, so there is nothing
        to do. Kept so migrating call sites run unchanged."""
        return None

    def save_model(
        self,
        model: Model,
        save_directory: str,
        max_shard_size: Union[int, str] = "5GB",
        safe_serialization: bool = True,
    ):
        """Export params as (sharded) safetensors + index
        (reference: accelerator.py:3439-3551)."""
        params = to_global_host(model.params)
        flat = flatten_state_dict(params)
        if self.is_main_process:
            save_sharded_safetensors(flat, save_directory, max_shard_size=max_shard_size)
        self.wait_for_everyone()

    def save(self, obj, f, safe_serialization: bool = False):
        from .utils.operations import save as _save

        _save(obj, f, save_on_each_node=self.project_configuration.save_on_each_node,
              safe_serialization=safe_serialization)

    def get_state_dict(self, model: Model, unwrap: bool = True):
        return flatten_state_dict(to_global_host(model.params))

    # ------------------------------------------------------------------
    # Tracking (reference: accelerator.py:3271-3408)
    # ------------------------------------------------------------------

    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: dict = {}):
        from .tracking import resolve_trackers

        self.trackers = resolve_trackers(
            self.log_with, project_name, self.logging_dir, init_kwargs
        )
        if config is not None:
            for tracker in self.trackers:
                tracker.store_init_configuration(config)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"{name} is not an available tracker stored inside the `Accelerator`.")

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: dict = {}):
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log(values, step=step, **log_kwargs.get(tracker.name, {}))

    def end_training(self):
        self._close_async_checkpointer()
        if self.fault_tolerance is not None:
            self.fault_tolerance.close()  # drain/restore signal handlers
        if self.telemetry is not None:
            self.telemetry.close()  # summary still sees the compile manager
        if self.compile_manager is not None:
            self.compile_manager.close()  # persistent-cache LRU prune
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.finish()
        self.wait_for_everyone()

    # ------------------------------------------------------------------
    # Memory / teardown (reference: accelerator.py:4260-4359)
    # ------------------------------------------------------------------

    def free_memory(self, *objects):
        from .utils.memory import release_memory

        self._close_async_checkpointer()
        if self.fault_tolerance is not None:
            self.fault_tolerance.close()
            self.fault_tolerance = None
        if self.telemetry is not None:
            self.telemetry.close()
            self.telemetry = None
        if self.compile_manager is not None:
            self.compile_manager.close()
            self.compile_manager = None
        self._train_state = None
        self._state_shardings = None
        self._grad_shardings = None
        self._param_shardings = None
        self._opt_offload = None
        self._grad_fn_cache.clear()
        self._apply_jit = None
        self._gradnorm_jit = None
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self.step = 0
        return release_memory(*objects)

    def clear(self, *objects):
        return self.free_memory(*objects)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches)

    def verify_device_map(self, model) -> bool:
        """True when ``model`` was dispatched with a multi-placement device
        map (reference: accelerator.py:3744-3760 checks for hf_device_map —
        such models must not also be prepared for distributed training)."""
        from .big_modeling import DispatchedModel

        if not isinstance(model, DispatchedModel):
            return False
        placements = {str(p) for p in model.device_map.values()}
        return len(placements) > 1

    def __deepcopy__(self, memo):
        return self
