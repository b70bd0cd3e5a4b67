"""Process/device runtime state singletons (layer L0).

TPU-native re-design of the reference's ``state.py`` (reference:
src/accelerate/state.py:123-1371). The reference's ``PartialState`` wraps
torch.distributed process groups; here the runtime is JAX's single-controller
multi-process model: ``jax.distributed.initialize`` performs the coordinator
rendezvous over DCN, after which every process sees all global devices and all
data-plane collectives are XLA ops placed by GSPMD. What remains host-side is
exactly what the reference's L0 provides: rank/world introspection, process
control (barriers, main-process gating, ``split_between_processes``) and a tiny
out-of-band object channel (see utils/operations.py).
"""

from __future__ import annotations

import enum
import logging
import os
import threading
from contextlib import contextmanager
from functools import wraps
from typing import Any, Callable, Optional

from .parallelism_config import ParallelismConfig
from .utils.environment import parse_choice_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)


class DistributedType(str, enum.Enum):
    """Launch topology. The parallelism *strategy* (FSDP/TP/CP/...) is not a
    distributed type here — unlike the reference (state.py:972-1022), strategy
    lives entirely in :class:`ParallelismConfig`; GSPMD makes the backend zoo
    collapse into sharding choices (SURVEY.md §7)."""

    NO = "NO"                      # single process, single device
    MULTI_DEVICE = "MULTI_DEVICE"  # single process, >1 local devices (one host)
    MULTI_HOST = "MULTI_HOST"      # multi-process JAX over a pod


class ThreadLocalSharedDict(threading.local):
    """Thread-local borg storage (reference: state.py:91-119 — needed there for
    TPU v2/v3 PJRT threads; kept for API parity and notebook safety)."""

    def __init__(self):
        self._storage = {}

    def __get__(self, obj, objtype=None):
        return self._storage

    def __set__(self, obj, value):
        self._storage = value


class SharedDict:
    """Descriptor holding borg shared state at class level."""

    def __init__(self):
        self._storage = {}

    def __get__(self, obj, objtype=None):
        return self._storage

    def __set__(self, obj, value):
        self._storage = value


def _maybe_init_jax_distributed():
    """Multi-host bring-up: rendezvous with the JAX coordinator over DCN.

    Replaces the reference's ``init_process_group`` + MASTER_ADDR/MASTER_PORT
    rendezvous (reference: state.py:215-285). Controlled by env the launcher
    sets (`accelerate launch`, commands/launch.py):

      ACCELERATE_COORDINATOR_ADDRESS  host:port of process 0
      ACCELERATE_NUM_PROCESSES        total process (host) count
      ACCELERATE_PROCESS_INDEX        this process's index
    """
    import jax

    coord = os.environ.get("ACCELERATE_COORDINATOR_ADDRESS")
    if coord is None:
        return
    # Idempotent across PartialState._reset_state(): the coordinator client
    # outlives the borg dicts, and re-initializing after the backend is live
    # is an error.
    if jax.distributed.is_initialized():
        return
    num = int(os.environ.get("ACCELERATE_NUM_PROCESSES", "1"))
    idx = int(os.environ.get("ACCELERATE_PROCESS_INDEX", "0"))
    if coord == "auto":
        # TPU pod: jax discovers coordinator/ranks from the TPU VM metadata
        # (the gcloud pod launch path sets this — commands/pod.py).
        try:
            jax.distributed.initialize()
        except RuntimeError as e:
            if "already initialized" not in str(e):
                raise
        return
    if num <= 1:
        return
    # Multi-process CPU gangs (--cpu / --virtual_devices) get their
    # cross-process collectives from gloo, jax's default
    # ``jax_cpu_collectives_implementation``; nothing to opt into here.
    try:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=num, process_id=idx
        )
    except RuntimeError as e:
        # Already initialized (e.g. by the launcher itself) is fine.
        if "already initialized" not in str(e):
            raise


class PartialState:
    """Borg-pattern singleton with rank/device info and process-control helpers.

    (reference: state.py:123-865)
    """

    _shared_state = SharedDict()
    _known_attrs = [
        "_cpu",
        "backend",
        "device",
        "debug",
        "distributed_type",
        "fork_launched",
        "local_process_index",
        "num_processes",
        "process_index",
    ]

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        import jax

        self._cpu = cpu
        self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
        self.fork_launched = parse_flag_from_env("FORK_LAUNCHED", False)
        if cpu:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")  # for child processes
            # jax read the environment at import: ask through its config.
            jax.config.update("jax_platforms", "cpu")
        _maybe_init_jax_distributed()

        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        self.local_process_index = int(
            os.environ.get("ACCELERATE_LOCAL_PROCESS_INDEX", self.process_index)
        )
        self._devices = jax.devices()
        self._local_devices = jax.local_devices()
        self.device = self._local_devices[0]
        self.backend = self.device.platform

        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif len(self._devices) > 1:
            self.distributed_type = DistributedType.MULTI_DEVICE
        else:
            self.distributed_type = DistributedType.NO

    def __repr__(self) -> str:
        return (
            f"Distributed environment: {self.distributed_type.value}  Backend: {self.backend}\n"
            f"Num processes: {self.num_processes}\n"
            f"Process index: {self.process_index}\n"
            f"Local process index: {self.local_process_index}\n"
            f"Device: {self.device}\n"
        )

    @staticmethod
    def _reset_state():
        """Reset for testing (reference: state.py:853-857)."""
        PartialState._shared_state.clear()

    @property
    def initialized(self) -> bool:
        return "distributed_type" in self.__dict__

    @property
    def use_distributed(self) -> bool:
        return self.num_processes > 1 or len(self._devices) > 1

    # -- device views ---------------------------------------------------

    @property
    def devices(self):
        """All global devices (every process sees the full pod)."""
        return self._devices

    @property
    def local_devices(self):
        return self._local_devices

    @property
    def num_devices(self) -> int:
        return len(self._devices)

    @property
    def local_device_count(self) -> int:
        return len(self._local_devices)

    # -- process control ------------------------------------------------

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    def wait_for_everyone(self):
        """Cross-process barrier (reference: state.py:399-414). Under JAX this
        is a sync over all global devices."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    def agree_any(self, flag: bool) -> bool:
        """Cross-rank OR of a host-side boolean: True everywhere as soon as
        ANY rank passes True. One tiny int allreduce — the rank-coherence
        primitive behind ``Accelerator.check_preemption()`` (only some hosts
        of a pod get the scheduler's SIGTERM; the whole gang must take the
        same save-and-exit decision) and ``check_trigger()``-style flags."""
        if self.num_processes <= 1:
            return bool(flag)
        import jax.numpy as jnp
        import numpy as np

        from .utils.operations import reduce

        total = reduce(jnp.asarray(1 if flag else 0, jnp.int32), reduction="sum")
        return int(np.asarray(total)) > 0

    def allgather_host_floats(self, values) -> "np.ndarray":
        """Allgather a small host-side float vector across ranks, returning
        a ``(num_processes, len(values))`` numpy array (row r = rank r's
        vector). Single-process returns the ``(1, n)`` input. The
        rank-coherence channel behind the step watchdog's gang heartbeat
        (fault_tolerance.py) — same family as :meth:`agree_any`: one tiny
        collective, every rank sees the same table and takes the same
        decision."""
        import numpy as np

        vec = np.asarray(values, np.float64).reshape(1, -1)
        if self.num_processes <= 1:
            return vec
        from .utils.operations import gather

        out = np.asarray(gather(vec), np.float64)
        return out.reshape(self.num_processes, -1)

    @contextmanager
    def main_process_first(self):
        """Main process runs the body first, others wait then run
        (reference: state.py:416-423)."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextmanager
    def local_main_process_first(self):
        if not self.is_local_main_process:
            self.wait_for_everyone()
        yield
        if self.is_local_main_process:
            self.wait_for_everyone()

    def on_main_process(self, function: Callable = None):
        """Decorator: run only on the main process (reference: state.py:425-460)."""

        @wraps(function)
        def execute_on_main_process(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return execute_on_main_process

    def on_local_main_process(self, function: Callable = None):
        @wraps(function)
        def execute_on_local_main_process(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return execute_on_local_main_process

    def on_process(self, function: Callable = None, process_index: int = None):
        @wraps(function)
        def execute_on_process(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return execute_on_process

    def on_last_process(self, function: Callable):
        return self.on_process(function, process_index=self.num_processes - 1)

    def on_local_process(self, function: Callable = None, local_process_index: int = None):
        @wraps(function)
        def execute_on_local_process(*args, **kwargs):
            if self.local_process_index == local_process_index:
                return function(*args, **kwargs)

        return execute_on_local_process

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/dict/array evenly across processes; uneven tails go to
        the first ranks; ``apply_padding`` repeats the final element so all
        ranks get equal length (reference: state.py:465-555)."""
        if self.num_processes == 1:
            yield inputs
            return
        length = len(inputs)
        num_samples_per_process, num_extras = divmod(length, self.num_processes)
        start = self.process_index * num_samples_per_process + min(self.process_index, num_extras)
        end = start + num_samples_per_process + (1 if self.process_index < num_extras else 0)

        if isinstance(inputs, dict):
            result = {k: v[start:end] for k, v in inputs.items()}
            if apply_padding:
                target = num_samples_per_process + (1 if num_extras > 0 else 0)
                for k, v in result.items():
                    while len(result[k]) < target:
                        result[k] = list(result[k]) + [inputs[k][-1]]
            yield result
            return

        result = inputs[start:end]
        if apply_padding:
            target = num_samples_per_process + (1 if num_extras > 0 else 0)
            if hasattr(result, "tolist"):
                result = list(result)
            while len(result) < target:
                result = list(result) + [inputs[-1]]
        yield result

    def print(self, *args, **kwargs):
        if self.is_local_main_process:
            print(*args, **kwargs)

    def destroy_process_group(self):
        import jax

        if self.num_processes > 1:
            try:
                jax.distributed.shutdown()
            except Exception:
                pass

    def __getattr__(self, name: str):
        if name in self._known_attrs:
            raise AttributeError(
                f"`PartialState` object has no attribute `{name}`. "
                "This happens if `PartialState._reset_state()` was called and "
                "an `Accelerator` or `PartialState` was not reinitialized."
            )
        raise AttributeError(f"'PartialState' object has no attribute '{name}'")


class AcceleratorState:
    """PartialState + mixed precision + parallelism/mesh + plugin storage.

    (reference: state.py:868-1228)
    """

    _shared_state = SharedDict()

    def __init__(
        self,
        mixed_precision: str = None,
        cpu: bool = False,
        parallelism_config: Optional[ParallelismConfig] = None,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if parallelism_config is not None and parallelism_config != self.parallelism_config:
                raise ValueError(
                    "AcceleratorState is already initialized with a different "
                    "parallelism_config; call AcceleratorState._reset_state() first."
                )
            return
        self._partial = PartialState(cpu, **kwargs)
        if mixed_precision is None:
            mixed_precision = parse_choice_from_env("ACCELERATE_MIXED_PRECISION", "no")
        mixed_precision = str(mixed_precision)
        if mixed_precision not in ("no", "bf16", "fp16", "fp8"):
            raise ValueError(
                f"mixed_precision must be one of no|bf16|fp16|fp8, got {mixed_precision}"
            )
        # bf16 is native on every TPU generation; fp16 requests are honored but
        # bf16 is the idiomatic choice (no loss scaling needed).
        self.mixed_precision = mixed_precision
        if parallelism_config is None and os.environ.get("PARALLELISM_CONFIG_DP_SHARD_SIZE"):
            parallelism_config = ParallelismConfig.from_env()
        self.parallelism_config = parallelism_config
        self._mesh = None

    @staticmethod
    def _reset_state(reset_partial_state: bool = False):
        AcceleratorState._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()

    @property
    def initialized(self) -> bool:
        return "_partial" in self.__dict__

    # Delegate PartialState surface.
    def __getattr__(self, name: str):
        partial = self.__dict__.get("_partial")
        if partial is not None and hasattr(partial, name):
            return getattr(partial, name)
        raise AttributeError(f"'AcceleratorState' object has no attribute '{name}'")

    @property
    def mesh(self):
        """The global device mesh, built lazily from parallelism_config (or a
        pure-DP mesh over all devices when no config was given)."""
        if self._mesh is None:
            cfg = self.parallelism_config
            if cfg is None:
                # Lazily inferred config must still honor env knobs that are
                # meaningful without mesh degrees (pp_virtual_stages) — else
                # the first mesh access silently overwrites the env default
                # that pipeline_apply's resolution would otherwise see.
                from .utils.environment import get_int_from_env

                cfg = ParallelismConfig(
                    pp_virtual_stages=get_int_from_env(
                        ["PARALLELISM_CONFIG_PP_VIRTUAL_STAGES"], 1
                    )
                )
            self._mesh = cfg.infer_missing_axis(len(self._partial.devices)).build_mesh(
                self._partial.devices
            )
            self.parallelism_config = cfg.infer_missing_axis(len(self._partial.devices))
        return self._mesh

    def set_mesh(self, mesh):
        self._mesh = mesh

    def destroy_process_group(self):
        self._partial.destroy_process_group()


class GradientState:
    """Singleton tracking gradient accumulation & dataloader-end state.

    (reference: state.py:1231-1371). ``sync_gradients`` flips on accumulation
    boundaries; dataloaders register themselves so the final partial window at
    the end of an epoch still syncs (reference: data_loader.py:402-414).

    Under jit the accumulation itself is folded into the train step
    (``lax.scan`` over microbatches); this host-side object exists for the
    imperative-compat API and for end-of-dataloader handling, which is
    inherently host-side control flow.
    """

    _shared_state = SharedDict()

    def __init__(self, gradient_accumulation_plugin=None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = [None]
            self.plugin_kwargs = {}
            self.step = 0
        if gradient_accumulation_plugin is not None:
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()

    @property
    def initialized(self) -> bool:
        return "sync_gradients" in self.__dict__

    @property
    def num_steps(self) -> int:
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return getattr(self.active_dataloader, "remainder", -1)

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync_gradients: bool):
        self.sync_gradients = sync_gradients

    def _add_dataloader(self, dataloader):
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader):
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)

    @property
    def active_dataloader(self):
        return self.dataloader_references[-1]

    @active_dataloader.setter
    def active_dataloader(self, value):
        if "dataloader_references" not in self.__dict__:
            self.dataloader_references = [None]
        if value is not None:
            self.dataloader_references.append(value)

    @staticmethod
    def _reset_state():
        GradientState._shared_state.clear()

    def __repr__(self):
        return (
            f"Sync Gradients: {self.sync_gradients}\n"
            f"At end of current dataloader: {self.end_of_dataloader}\n"
            f"Extra samples added: {self.remainder}\n"
            f"Gradient accumulation steps: {self.num_steps}\n"
        )


def is_initialized() -> bool:
    return AcceleratorState().initialized
