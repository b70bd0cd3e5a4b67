"""Cross-process operations on tensors and nested structures (layer L1).

Re-design of the reference's ``utils/operations.py`` (reference:
src/accelerate/utils/operations.py:85-991). Two fundamentally different
regimes exist under JAX, and this module unifies them behind the reference's
API:

1. **Inside jit** (the data plane): collectives are XLA ops — a sharded
   ``jax.Array`` is already "gathered" logically; GSPMD inserts the actual
   all-gathers/psums. Nothing here runs per-training-step.

2. **Host side / out-of-band** (the control plane): per-process numpy data
   (e.g. metric batches, python objects) crossing process boundaries uses
   ``jax.experimental.multihost_utils`` — a tiny jitted all-gather under the
   hood. This is the moral equivalent of the reference's gloo side-channel.

Single-process (1 host, N local devices) needs no inter-process traffic at
all: "gather" is just fetching the global array.
"""

from __future__ import annotations

import pickle
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np


def _partial_state():
    # Imported lazily: utils/__init__ loads before state.py finishes.
    from ..state import PartialState

    return PartialState()


class DistributedOperationException(Exception):
    """Raised when a cross-process op is called with mismatching shapes across
    ranks (reference: utils/operations.py:361-380)."""


class _CollectiveCounters:
    """Process-wide count + payload-bytes tally of the control-plane
    collectives in this module, consumed by the telemetry subsystem
    (telemetry.py). Disabled (a single bool check per call) unless a
    TelemetryRecorder is live."""

    __slots__ = ("enabled", "counts", "bytes")

    def __init__(self):
        self.enabled = False
        self.counts: dict = {}
        self.bytes: dict = {}

    def record(self, op: str, tensor) -> None:
        if not self.enabled:
            return
        nbytes = 0
        try:
            for leaf in jax.tree_util.tree_leaves(tensor):
                nbytes += int(getattr(leaf, "nbytes", 0) or 0)
        except Exception:
            pass
        self.counts[op] = self.counts.get(op, 0) + 1
        self.bytes[op] = self.bytes.get(op, 0) + nbytes

    def snapshot(self) -> dict:
        return {
            op: {"count": n, "bytes": self.bytes.get(op, 0)}
            for op, n in sorted(self.counts.items())
        }

    def reset(self) -> None:
        self.counts.clear()
        self.bytes.clear()


collective_counters = _CollectiveCounters()


# ---------------------------------------------------------------------------
# Nested-structure plumbing (pytrees make most of the reference's manual
# recursion free, but we keep the honest-recursion versions so Mapping
# subclasses and namedtuples survive round-trips like the reference's,
# utils/operations.py:85-180).
# ---------------------------------------------------------------------------

def is_tensor_information(obj) -> bool:
    return isinstance(obj, TensorInformation)


def is_namedtuple(data) -> bool:
    return isinstance(data, tuple) and hasattr(data, "_asdict") and hasattr(data, "_fields")


def honor_type(obj, generator):
    """Rebuild a sequence preserving its exact type (incl. namedtuples)."""
    if is_namedtuple(obj):
        return type(obj)(*list(generator))
    return type(obj)(generator)


def recursively_apply(
    func: Callable,
    data: Any,
    *args,
    test_type: Callable = None,
    error_on_other_type: bool = False,
    **kwargs,
):
    """Apply ``func`` to every leaf matching ``test_type`` in a nested
    list/tuple/dict structure (reference: utils/operations.py:85-130)."""
    if test_type is None:
        test_type = is_array_like
    if isinstance(data, (tuple, list)):
        return honor_type(
            data,
            (
                recursively_apply(
                    func, o, *args, test_type=test_type, error_on_other_type=error_on_other_type, **kwargs
                )
                for o in data
            ),
        )
    if isinstance(data, Mapping):
        return type(data)(
            {
                k: recursively_apply(
                    func, v, *args, test_type=test_type, error_on_other_type=error_on_other_type, **kwargs
                )
                for k, v in data.items()
            }
        )
    if test_type(data):
        return func(data, *args, **kwargs)
    if error_on_other_type:
        raise TypeError(
            f"Unsupported type {type(data)} passed — only nested "
            f"list/tuple/dict of objects satisfying {test_type.__name__} are supported."
        )
    return data


def is_array_like(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray)) or hasattr(x, "__jax_array__")


def send_to_device(tensor, device=None, non_blocking: bool = True, skip_keys=None):
    """Move a nested structure onto device(s). ``device`` may be a Device, a
    ``Sharding``, or None (default device). jax.device_put is async by nature
    so ``non_blocking`` is honored for free
    (reference: utils/operations.py:132-180)."""

    def _send(t):
        return jax.device_put(t, device)

    if skip_keys is None:
        skip_keys = []
    if isinstance(tensor, Mapping) and skip_keys:
        return type(tensor)(
            {
                k: (v if k in skip_keys else send_to_device(v, device, non_blocking))
                for k, v in tensor.items()
            }
        )
    return recursively_apply(_send, tensor)


def get_data_structure(data):
    """Nested structure of :class:`TensorInformation` describing ``data``
    (for broadcast-by-shape, reference: utils/operations.py:238-258)."""

    def _get_info(tensor):
        return TensorInformation(shape=tuple(tensor.shape), dtype=np.dtype(tensor.dtype))

    return recursively_apply(_get_info, data)


def get_shape(data):
    def _get_shape(tensor):
        return list(tensor.shape)

    return recursively_apply(_get_shape, data)


def initialize_tensors(data_structure):
    """Materialize empty tensors from a :func:`get_data_structure` skeleton."""

    def _init(info):
        return jnp.zeros(info.shape, dtype=info.dtype)

    return recursively_apply(_init, data_structure, test_type=is_tensor_information)


class TensorInformation:
    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = dtype

    def __repr__(self):
        return f"TensorInformation(shape={self.shape}, dtype={self.dtype})"

    def __eq__(self, other):
        return (
            isinstance(other, TensorInformation)
            and tuple(self.shape) == tuple(other.shape)
            and self.dtype == other.dtype
        )


def find_batch_size(data) -> int:
    """First dim of the first tensor found (reference: utils/operations.py:220-236)."""
    if isinstance(data, (tuple, list)):
        for d in data:
            try:
                return find_batch_size(d)
            except (TypeError, ValueError):
                continue
        raise ValueError("Cannot find the batch size from empty sequence.")
    if isinstance(data, Mapping):
        for v in data.values():
            try:
                return find_batch_size(v)
            except (TypeError, ValueError):
                continue
        raise ValueError("Cannot find the batch size from empty dict.")
    if not is_array_like(data):
        raise TypeError(f"Can only find the batch size of arrays but got {type(data)}.")
    if len(data.shape) == 0:
        raise ValueError("Cannot find the batch size of a 0-dim array.")
    return data.shape[0]


def iterate_over_batch(data, start: int, end: int):
    """Slice every leaf's batch dim — the reference's ``slice_tensors``
    (reference: utils/operations.py:699-720)."""

    def _slice(tensor):
        return tensor[start:end]

    return recursively_apply(_slice, data)


slice_tensors = iterate_over_batch


def concatenate(data, dim: int = 0):
    """Concatenate a list of nested structures leaf-wise
    (reference: utils/operations.py:722-744)."""
    if isinstance(data[0], (tuple, list)):
        return honor_type(data[0], (concatenate([d[i] for d in data], dim=dim) for i in range(len(data[0]))))
    if isinstance(data[0], Mapping):
        return type(data[0])({k: concatenate([d[k] for d in data], dim=dim) for k in data[0].keys()})
    if not is_array_like(data[0]):
        raise TypeError(f"Can only concatenate arrays but got {type(data[0])}")
    return jnp.concatenate([jnp.asarray(d) for d in data], axis=dim)


# ---------------------------------------------------------------------------
# Cross-process collectives (control plane).
# ---------------------------------------------------------------------------

def _world():
    state = _partial_state()
    return state.num_processes


def _process_allgather(x, tiled: bool):
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x, tiled=tiled)


def verify_operation(function):
    """Debug-mode decorator: before running a collective, gather every rank's
    leaf shapes and raise :class:`DistributedOperationException` naming the
    mismatching ranks (reference: utils/operations.py:361-422)."""
    import functools

    @functools.wraps(function)
    def wrapper(*args, **kwargs):
        state = _partial_state()
        if not getattr(state, "debug", False) or state.num_processes <= 1:
            return function(*args, **kwargs)
        tensor = kwargs.get("tensor", args[0] if args else None)
        shapes = get_shape(tensor)
        output = gather_object([shapes])
        if output[0] is not None and not all(o == output[0] for o in output):
            bad = [i for i, o in enumerate(output) if o != output[0]]
            raise DistributedOperationException(
                f"Cannot apply the desired operation due to shape mismatches. "
                f"All shapes across devices must be valid.\n\nOperation: `{function.__name__}`\n"
                f"Input shapes:\n" + "\n".join(f"  - Process {i}: {o}" for i, o in enumerate(output))
                + f"\nMismatched processes: {bad}"
            )
        return function(*args, **kwargs)

    return wrapper


@verify_operation
def gather(tensor):
    """Gather values from all processes, concatenated on dim 0.

    - A globally-sharded ``jax.Array`` is already logically global: return it
      fully replicated on host (``jax.device_get`` handles cross-process
      fetch via the runtime).
    - Per-process local numpy/host data: tiled all-gather across processes
      (reference semantics of ``_gpu_gather``, utils/operations.py:307-358).
    """
    collective_counters.record("gather", tensor)
    if _world() == 1:
        def _maybe_devget(t):
            return np.asarray(t)

        return recursively_apply(_maybe_devget, tensor)

    def _gather_one(t):
        t = np.asarray(t) if not isinstance(t, jax.Array) else t
        if isinstance(t, jax.Array) and not t.is_fully_addressable:
            # Already a global array — fetch replicated value.
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(t, tiled=True))
        return np.asarray(_process_allgather(np.asarray(t), tiled=True))

    return recursively_apply(_gather_one, tensor)


def gather_object(object: Any):
    """Gather arbitrary picklable python objects from all processes into a
    list ordered by rank (reference: utils/operations.py:424-452). Implemented
    as pickle → padded uint8 tensor → all-gather — the out-of-band channel the
    reference gets from gloo."""
    state = _partial_state()
    if state.num_processes == 1:
        return [object] if not isinstance(object, list) else object
    payload = pickle.dumps(object)
    local_len = np.array([len(payload)], dtype=np.int64)
    all_lens = _process_allgather(local_len, tiled=True)
    max_len = int(all_lens.max())
    buf = np.zeros((max_len,), dtype=np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    gathered = _process_allgather(buf, tiled=False)  # (world, max_len)
    out = []
    for i in range(state.num_processes):
        n = int(all_lens[i])
        obj = pickle.loads(gathered[i, :n].tobytes())
        if isinstance(object, list):
            out.extend(obj)
        else:
            out.append(obj)
    return out


@verify_operation
def broadcast(tensor, from_process: int = 0):
    """Broadcast a (nested) tensor from one process to all
    (reference: utils/operations.py:474-494)."""
    collective_counters.record("broadcast", tensor)
    if _world() == 1:
        return tensor
    from jax.experimental import multihost_utils

    def _bcast(t):
        arr = np.asarray(t)
        out = np.asarray(
            multihost_utils.broadcast_one_to_all(
                arr, is_source=_partial_state().process_index == from_process
            )
        )
        return out.reshape(arr.shape)  # 0-d leaves must stay 0-d

    return recursively_apply(_bcast, tensor)


# One collective costs the same for any payload up to ~1 MB (fixed dispatch
# cost dominates), so small objects ride
# a single fixed-size broadcast with the length inline — halving the fixed
# cost vs the naive length-round-then-data protocol. Larger payloads fall
# back to a second, exact-size collective; the header makes the decision
# from broadcast content, so every rank takes the same branch.
_BCAST_INLINE_BUCKET = 1 << 16


def broadcast_object_list(object_list: list, from_process: int = 0):
    """Broadcast a list of picklable objects from one process
    (reference: utils/operations.py:496-516)."""
    state = _partial_state()
    if state.num_processes == 1:
        return object_list
    from jax.experimental import multihost_utils

    is_src = state.process_index == from_process
    payload = pickle.dumps(list(object_list)) if is_src else b""
    buf = np.zeros((8 + _BCAST_INLINE_BUCKET,), dtype=np.uint8)
    if is_src:
        buf[:8] = np.frombuffer(
            np.int64(len(payload)).tobytes(), dtype=np.uint8
        )
        if len(payload) <= _BCAST_INLINE_BUCKET:
            buf[8: 8 + len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    out = np.asarray(multihost_utils.broadcast_one_to_all(buf, is_source=is_src))
    need = int(np.frombuffer(out[:8].tobytes(), dtype=np.int64)[0])
    if need <= _BCAST_INLINE_BUCKET:
        result = pickle.loads(out[8: 8 + need].tobytes())
    else:
        big = np.zeros((need,), dtype=np.uint8)
        if is_src:
            big[:] = np.frombuffer(payload, dtype=np.uint8)
        out2 = multihost_utils.broadcast_one_to_all(big, is_source=is_src)
        result = pickle.loads(np.asarray(out2).tobytes())
    for i, v in enumerate(result):
        object_list[i] = v
    return object_list


def is_global_array(t) -> bool:
    """True for a jax.Array that is already logically global over the mesh —
    reducing/gathering it per-process would double count."""
    return isinstance(t, jax.Array) and (
        not t.is_fully_addressable or getattr(t.sharding, "num_devices", 1) > 1
    )


def to_global_host(tree):
    """Fetch a pytree to host numpy, multi-host safe: leaves spanning
    non-addressable devices go through process_allgather (every process gets
    the assembled global value); fully-addressable leaves are a plain fetch.
    Used by checkpointing/save_model (reference analog: ZeRO3 16-bit gather in
    get_state_dict, accelerator.py:4002-4072)."""

    def _fetch(t):
        # np.asarray of a TPU array can expose the device's tiled layout as a
        # strided view; downstream writers (safetensors, memmap, ctypes)
        # assume C order, so normalize here at the host boundary. Reshape
        # AFTER ascontiguousarray: it promotes 0-d arrays to 1-d, which is how
        # round 1's LocalSGD corrupted scalar params to shape (1,).
        if isinstance(t, jax.Array) and not t.is_fully_addressable:
            from jax.experimental import multihost_utils

            out = np.asarray(multihost_utils.process_allgather(t, tiled=True))
            return np.ascontiguousarray(out).reshape(t.shape)
        arr = np.asarray(t)
        return np.ascontiguousarray(arr).reshape(arr.shape)

    return recursively_apply(_fetch, tree)


@verify_operation
def reduce(tensor, reduction: str = "mean", scale: float = 1.0):
    """Reduce a (nested) tensor across processes (sum or mean), applying
    ``scale`` (reference: utils/operations.py:746-788).

    Per-process host values are summed across ranks; an already-global
    jax.Array (a jit output) is by definition identical on every rank, so the
    cross-process reduce is an identity on it — only ``scale`` applies."""
    collective_counters.record("reduce", tensor)

    def _reduce_one(t):
        if is_global_array(t) and _world() > 1:
            return jnp.asarray(to_global_host(t) * scale)
        arr = np.asarray(t)
        if _world() > 1:
            stacked = _process_allgather(arr, tiled=False)
            # stack axis 0 is the process axis; summing it must restore the
            # input shape exactly (0-d leaves included — process_allgather
            # promotes scalars, see test_utils/scripts/test_ops.py).
            arr = np.sum(np.asarray(stacked).reshape((_world(),) + arr.shape), axis=0)
            if reduction == "mean":
                arr = arr / _world()
        return jnp.asarray(arr * scale)

    return recursively_apply(_reduce_one, tensor)


@verify_operation
def pad_across_processes(tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
    """Pad every process's tensor along ``dim`` to the max size across
    processes so a subsequent ``gather`` is legal
    (reference: utils/operations.py:790-840)."""
    collective_counters.record("pad_across_processes", tensor)

    def _pad_one(t):
        if is_global_array(t) and _world() > 1:
            return t  # global arrays already have one consistent shape
        t = jnp.asarray(t)
        if dim >= t.ndim:
            return t
        size = np.array([t.shape[dim]], dtype=np.int64)
        if _world() > 1:
            sizes = np.asarray(_process_allgather(size, tiled=True))
            max_size = int(sizes.max())
        else:
            max_size = int(size[0])
        if max_size == t.shape[dim]:
            return t
        pad_amount = max_size - t.shape[dim]
        pad_width = [(0, 0)] * t.ndim
        pad_width[dim] = (pad_amount, 0) if pad_first else (0, pad_amount)
        return jnp.pad(t, pad_width, constant_values=pad_index)

    return recursively_apply(_pad_one, tensor)


def pad_input_tensors(tensor, batch_size: int, num_processes: int, dim: int = 0):
    """Pad a batch so it divides evenly by ``num_processes``, repeating the
    first samples (reference: utils/operations.py:842-888)."""

    def _pad_one(t):
        t = jnp.asarray(t)
        if batch_size % num_processes == 0:
            return t
        target = int(np.ceil(batch_size / num_processes)) * num_processes
        extra = target - t.shape[dim]
        idx = jnp.arange(extra) % t.shape[dim]
        return jnp.concatenate([t, jnp.take(t, idx, axis=dim)], axis=dim)

    return recursively_apply(_pad_one, tensor)


def copy_tensor_to_devices(tensor):
    """Replicate a host tensor onto all local devices."""
    sharding = jax.sharding.NamedSharding(
        jax.sharding.Mesh(np.asarray(jax.devices()).reshape(-1), ("x",)),
        jax.sharding.PartitionSpec(),
    )
    return recursively_apply(lambda t: jax.device_put(jnp.asarray(t), sharding), tensor)


def convert_to_fp32(tensor):
    """Upcast floating leaves to fp32 (the reference wraps autocast forwards
    with this, utils/operations.py:889-949)."""

    def _convert(t):
        if jnp.issubdtype(jnp.asarray(t).dtype, jnp.floating):
            return jnp.asarray(t, dtype=jnp.float32)
        return t

    return recursively_apply(_convert, tensor)


def convert_outputs_to_fp32(model_forward):
    import functools

    @functools.wraps(model_forward)
    def forward(*args, **kwargs):
        return convert_to_fp32(model_forward(*args, **kwargs))

    return forward


def listify(data):
    """Convert arrays to plain python lists for logging
    (reference: tracking.py helper)."""

    def _listify(t):
        return np.asarray(t).tolist()

    return recursively_apply(_listify, data)


def save(obj, f, save_on_each_node: bool = False, safe_serialization: bool = True):
    """Persist ``obj`` to disk, only on main process unless
    ``save_on_each_node`` (reference: utils/other.py:384-433)."""
    from ..state import PartialState

    state = _partial_state()
    if state.is_main_process or save_on_each_node:
        if safe_serialization and _is_flat_array_dict(obj):
            from .other import save_safetensors

            save_safetensors(obj, f)
        else:
            with open(f, "wb") as fh:
                pickle.dump(obj, fh)


def _is_flat_array_dict(obj) -> bool:
    return isinstance(obj, dict) and all(is_array_like(v) for v in obj.values())
