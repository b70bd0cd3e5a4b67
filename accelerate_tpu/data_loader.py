"""Distributed data pipeline (layer L3).

Re-design of the reference's ``data_loader.py`` (1469 LoC, reference:
src/accelerate/data_loader.py). The sharding logic (who reads which sample) is
pure Python and survives almost unchanged; what changes is the device side: a
batch becomes ONE global ``jax.Array`` laid out over the mesh
(``jax.make_array_from_process_local_data``), so the "DDP each rank holds a
batch" and "TP ranks must see identical batches" rules of the reference
(data_loader.py:1127-1163) turn into the batch PartitionSpec: batch dim over
the dp axes — implicitly replicated across tp — and the sequence dim over
cp/sp.

Two feeding modes, same as the reference:
- shard mode (``DataLoaderShard``): every process reads its own slice.
- dispatch mode (``DataLoaderDispatcher``): process 0 reads the full batch and
  broadcasts (reference: data_loader.py:722-994).
"""

from __future__ import annotations

import itertools
import math
import queue
import random as _pyrandom
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import numpy as np

from .state import AcceleratorState, GradientState, PartialState
from .parallel.sharding import batch_partition_spec
from .utils.operations import (
    broadcast_object_list,
    concatenate,
    find_batch_size,
    recursively_apply,
    slice_tensors,
)
from .utils.random import next_rng_key, synchronize_rng_states

_PYTORCH_DATALOADER_KWARGS = ("batch_size", "sampler", "batch_sampler", "collate_fn", "drop_last")


class SeedableRandomSampler:
    """Deterministic, resumable shuffling sampler: reseeds ``seed + epoch``
    each epoch (reference: data_loader.py:73-108)."""

    def __init__(self, data_source_len: int, seed: int = 0, epoch: int = 0):
        self.data_source_len = data_source_len
        self.seed = seed
        self.epoch = epoch

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def __len__(self):
        return self.data_source_len

    def __iter__(self) -> Iterator[int]:
        rng = np.random.default_rng(self.seed + self.epoch)
        yield from rng.permutation(self.data_source_len).tolist()
        self.epoch += 1

    def state_dict(self):
        return {"seed": self.seed, "epoch": self.epoch}

    def load_state_dict(self, state):
        self.seed = state["seed"]
        self.epoch = state["epoch"]


class SequentialSampler:
    def __init__(self, data_source_len: int):
        self.data_source_len = data_source_len

    def __len__(self):
        return self.data_source_len

    def __iter__(self):
        return iter(range(self.data_source_len))


class BatchSampler:
    """Groups sampler indices into batches (torch-compatible semantics)."""

    def __init__(self, sampler, batch_size: int, drop_last: bool = False):
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return math.ceil(n / self.batch_size)


class BatchSamplerShard:
    """Shard an existing batch sampler across processes.

    Two modes, identical to the reference (data_loader.py:110-273):
    ``split_batches=True`` slices each yielded batch in ``num_processes``
    chunks; otherwise whole batches go round-robin. ``even_batches`` cycles
    back to the start so all shards have equal length."""

    def __init__(
        self,
        batch_sampler,
        num_processes: int = 1,
        process_index: int = 0,
        split_batches: bool = False,
        even_batches: bool = True,
    ):
        if split_batches and getattr(batch_sampler, "batch_size", 0) % num_processes != 0:
            raise ValueError(
                f"batch_size {batch_sampler.batch_size} must be divisible by "
                f"num_processes {num_processes} with split_batches=True"
            )
        self.batch_sampler = batch_sampler
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.even_batches = even_batches
        self.batch_size = getattr(batch_sampler, "batch_size", None)
        self.drop_last = getattr(batch_sampler, "drop_last", False)

    @property
    def total_length(self):
        return len(self.batch_sampler)

    def __len__(self):
        if self.split_batches:
            return len(self.batch_sampler)
        if len(self.batch_sampler) % self.num_processes == 0:
            return len(self.batch_sampler) // self.num_processes
        length = len(self.batch_sampler) // self.num_processes
        if self.drop_last:
            return length
        return length if not self.even_batches and self.process_index >= len(
            self.batch_sampler
        ) % self.num_processes else length + 1

    def __iter__(self):
        if self.split_batches:
            yield from self._iter_with_split()
        else:
            yield from self._iter_with_shard()

    def _iter_with_split(self):
        initial_data = []
        batch_length = self.batch_sampler.batch_size // self.num_processes
        last_batch = None
        for idx, batch in enumerate(self.batch_sampler):
            if idx == 0:
                initial_data = batch
            last_batch = batch
            if len(batch) == self.batch_size:
                yield batch[batch_length * self.process_index : batch_length * (self.process_index + 1)]
        if not self.drop_last and last_batch is not None and len(last_batch) < self.batch_size:
            if self.even_batches:
                while len(initial_data) < self.batch_size:
                    initial_data += initial_data
                batch = (last_batch + initial_data)[: self.batch_size]
                yield batch[batch_length * self.process_index : batch_length * (self.process_index + 1)]
            else:
                start = batch_length * self.process_index
                end = batch_length * (self.process_index + 1)
                if start < len(last_batch):
                    yield last_batch[start:end]

    def _iter_with_shard(self):
        initial_data = []
        batch_to_yield = []
        last_yielded = False
        for idx, batch in enumerate(self.batch_sampler):
            if not self.drop_last and idx < self.num_processes:
                initial_data += batch
            if idx % self.num_processes == self.process_index:
                batch_to_yield = batch
            if idx % self.num_processes == self.num_processes - 1 and (
                self.batch_size is None or len(batch) == self.batch_size
            ):
                yield batch_to_yield
                last_yielded = True
                batch_to_yield = []
            else:
                last_yielded = False
        # Tail handling.
        if self.drop_last or last_yielded and not batch_to_yield:
            return
        if not self.even_batches:
            if batch_to_yield:
                yield batch_to_yield
            return
        # even_batches: loop back to the start to equalize shard counts
        # (reference: data_loader.py:199-244). Processes that ran out of real
        # batches take *distinct* cycled chunks of initial_data (proc k-th
        # without data takes chunk k), so the final global batch still covers
        # distinct samples rather than P copies of the same chunk.
        if len(initial_data) > 0:
            target = self.batch_size or max(len(batch_to_yield), 1)
            while len(initial_data) < self.num_processes * target:
                initial_data += initial_data
            if batch_to_yield:
                yield (batch_to_yield + initial_data)[:target]
            else:
                # Rank order among the processes that lack a final batch:
                # the ones holding real batches are the first (idx % P) ranks
                # of the incomplete round.
                n_with_data = (idx + 1) % self.num_processes
                fill_rank = self.process_index - n_with_data
                start = (len(batch_to_yield or []) + fill_rank * target) % len(initial_data)
                cycle = itertools.islice(itertools.cycle(initial_data), start, start + target)
                yield list(cycle)


class IterableDatasetShard:
    """Slice of an iterable dataset per process: take windows of
    ``batch_size * num_processes`` samples and keep this rank's chunk; pad the
    final window from the window start (reference: data_loader.py:274-371)."""

    def __init__(
        self,
        dataset: Iterable,
        batch_size: int = 1,
        drop_last: bool = False,
        num_processes: int = 1,
        process_index: int = 0,
        split_batches: bool = False,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.epoch = 0

    def set_epoch(self, epoch):
        self.epoch = epoch
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    @property
    def _window(self) -> int:
        # Each rank owns one contiguous chunk of a window of this many
        # elements; split_batches means the user's batch_size already covers
        # all ranks together.
        return self.batch_size if self.split_batches else self.batch_size * self.num_processes

    @property
    def _chunk(self) -> int:
        return self._window // self.num_processes

    def _my_chunk(self, window: list) -> list:
        lo = self.process_index * self._chunk
        return window[lo: lo + self._chunk]

    def __iter__(self):
        window: list = []
        pad_source: list = []  # first full window, reused to pad the tail
        for element in self.dataset:
            window.append(element)
            if len(window) == self._window:
                yield from self._my_chunk(window)
                if not pad_source:
                    pad_source = list(window)
                window = []
        if window and not self.drop_last:
            # Ragged tail: cycle samples (from the first window if one
            # completed, else the tail itself) until every rank has a full
            # chunk — duplicates are trimmed later by gather_for_metrics.
            pad_source = pad_source or list(window)
            while len(window) < self._window:
                window.extend(pad_source[: self._window - len(window)])
            yield from self._my_chunk(window)


def default_collate(samples: list) -> Any:
    """Stack a list of samples into a batch of numpy arrays (dicts, tuples and
    scalars supported). Torch tensors are converted host-side. Large uniform
    items go through the native parallel-memcpy stacker (native/)."""
    first = samples[0]
    if hasattr(first, "numpy"):  # torch tensor
        return np.stack([np.asarray(s.numpy() if hasattr(s, "numpy") else s) for s in samples])
    if isinstance(first, dict):
        return {k: default_collate([s[k] for s in samples]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(default_collate([s[i] for s in samples]) for i in range(len(first)))
    if isinstance(first, np.ndarray) and first.ndim > 0:
        from .native import stack_items

        return stack_items(samples)
    return np.asarray(samples)


class ColumnDataset:
    """Dict-of-arrays dataset whose batches assemble in ONE native call per
    batch (``native.gather_columns``) instead of a Python loop per item —
    the torch-DataLoader-worker role (SURVEY.md §2.9) done TPU-host-native.

    ``dataset[i]`` still returns a per-item dict, so it composes with every
    sampler/shard wrapper in this module.
    """

    def __init__(self, **columns: np.ndarray):
        if not columns:
            raise ValueError("ColumnDataset needs at least one column")
        lengths = {k: len(v) for k, v in columns.items()}
        if len(set(lengths.values())) != 1:
            raise ValueError(f"Column lengths differ: {lengths}")
        self.columns = {k: np.asarray(v) for k, v in columns.items()}
        self._length = next(iter(lengths.values()))

    def __len__(self):
        return self._length

    def __getitem__(self, i):
        return {k: v[i] for k, v in self.columns.items()}

    def gather_batch(self, indices) -> dict[str, np.ndarray]:
        from .native import gather_columns

        return gather_columns(self.columns, indices)


def _to_numpy_tree(batch):
    def _conv(x):
        if hasattr(x, "detach"):  # torch tensor
            return x.detach().cpu().numpy()
        return x

    return recursively_apply(_conv, batch, test_type=lambda x: hasattr(x, "detach") or hasattr(x, "shape"))


class _PrefetchIterator:
    """Bounded background iterator: a worker thread runs the source iterator
    (dataset reads + native collation, which releases the GIL) while the main
    thread feeds the device — the reference's ``MpDeviceLoader`` prefetch
    threads (reference: data_loader.py:669-719) without torch_xla."""

    _SENTINEL = object()

    def __init__(self, source, prefetch_size: int = 2):

        self._queue = queue.Queue(maxsize=max(1, prefetch_size))
        self._stop = threading.Event()
        self._error = None

        def _fill():
            try:
                for item in source:
                    while not self._stop.is_set():
                        try:
                            self._queue.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if self._stop.is_set():
                        return
            except BaseException as e:  # re-raised on the consumer side
                self._error = e
            finally:
                while not self._stop.is_set():
                    try:
                        self._queue.put(self._SENTINEL, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        self._thread = threading.Thread(target=_fill, daemon=True, name="accel-prefetch")
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        item = self._queue.get()
        if item is self._SENTINEL:
            if self._error is not None:
                raise self._error
            raise StopIteration
        return item

    def close(self):
        self._stop.set()
        # Drain so the worker unblocks and exits.
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)


class BaseDataLoader:
    """Shared machinery: iteration with 1-batch lookahead (to flag
    ``end_of_dataloader`` for GradientState, reference: data_loader.py:582-607),
    device placement as global mesh arrays, RNG sync at epoch start."""

    def __init__(
        self,
        dataset,
        batch_sampler=None,
        collate_fn=None,
        device_placement: bool = True,
        rng_types=None,
        synchronized_generator=None,
        non_blocking: bool = True,
        use_global_device_arrays: bool = True,
        _drop_last: bool = False,
        _non_blocking: bool = True,
        **kwargs,
    ):
        self.dataset = dataset
        self.batch_sampler = batch_sampler
        self.collate_fn = collate_fn or default_collate
        self.device_placement = device_placement
        self.rng_types = rng_types
        self.use_global_device_arrays = use_global_device_arrays
        self.gradient_state = GradientState()
        self.end_of_dataloader = False
        self.remainder = -1
        self._drop_last = _drop_last
        self._iter_count = 0
        # Mid-epoch resume (reference: StatefulDataLoader state_dict surgery,
        # data_loader.py:416-508): batches handed out in the CURRENT epoch;
        # save_state records it, load_state arms ``_resume_skip`` so the next
        # __iter__ fast-forwards at the sampler level (no collation of
        # skipped batches).
        self.batches_yielded = 0
        self._resume_skip = 0
        self._pending_skip = 0
        self._sampler_snapshot = None  # sampler state at current-epoch start
        # Background host-side batch assembly (the MpDeviceLoader role,
        # reference: data_loader.py:669-719): a worker thread keeps this many
        # batches ready; native collation releases the GIL so assembly truly
        # overlaps the device step. 0 disables.
        self.prefetch_size = kwargs.get("prefetch_size", 2)
        # Set by Accelerator.prepare_data_loader when telemetry is enabled:
        # host time blocked waiting on the next batch feeds the recorder's
        # dataloader-wait accounting (telemetry.py).
        self._telemetry = None
        # Set by Accelerator.prepare_data_loader when a CompileKwargs handler
        # enables the compile manager: host batches are padded to bucket
        # shapes at the device boundary (compile_manager.bucket_pad), so a
        # ragged stream compiles at most len(buckets) executables. None =
        # ship true shapes, byte-identical to the unmanaged path.
        self._compile_manager = None
        # Set by Accelerator.prepare_data_loader when fault tolerance is on:
        # chaos `corrupt_batch` faults poison this loader's batches at the
        # device boundary (fault_tolerance.py draw_batch_fault). None (or a
        # manager with no injector armed) = batches ship untouched.
        self._fault_tolerance = None

    # -- device side -----------------------------------------------------

    def _global_sharding_for(self, arr: np.ndarray, leading_unsharded_dims: int = 0):
        state = AcceleratorState()
        mesh = state.mesh
        spec = batch_partition_spec(
            arr.ndim - leading_unsharded_dims, state.parallelism_config
        )
        if leading_unsharded_dims:
            spec = jax.sharding.PartitionSpec(
                *([None] * leading_unsharded_dims), *spec
            )
        return jax.sharding.NamedSharding(mesh, spec)

    def _pad_hint(self) -> Optional[int]:
        """This process's full local batch size — the bucket the ragged
        final ``drop_last=False`` batch pads up to. With ``even_batches=True``
        (the default) the samplers already cycle real samples so the final
        map-style batch arrives full and padding is a no-op; the hint matters
        for ``even_batches=False``, iterable datasets, and dispatch mode,
        whose true-shape tails each cost a one-off recompile every epoch."""
        total = self.total_batch_size
        if not total:
            return None
        return max(1, total // max(1, PartialState().num_processes))

    def _device_put_batch(self, batch):
        """Host numpy shard → one global jax.Array over the mesh. The fused
        train step splits microbatches for gradient accumulation *inside* jit,
        so every loader always emits plain ``(B, ...)`` global batches.

        When the compile manager is on, the batch is padded to bucket shapes
        HERE — the device boundary — so everything downstream (device_put,
        telemetry digests, the jitted step) only ever sees bucket shapes."""
        ft = self._fault_tolerance
        if ft is not None and ft.draw_batch_fault() is not None:
            # Chaos `corrupt_batch`: NaN out every float leaf. The poison is
            # real — it flows through the jitted step and produces genuinely
            # non-finite loss/grads, exercising the sentinel → rollback path
            # end to end (shapes/dtypes unchanged, so no recompile).
            batch = recursively_apply(
                lambda a: np.full_like(a, np.nan)
                if np.issubdtype(np.asarray(a).dtype, np.floating)
                else a,
                _to_numpy_tree(batch),
            )
        if not self.device_placement:
            return batch
        cm = self._compile_manager
        if cm is not None:
            batch = cm.bucket_pad(_to_numpy_tree(batch), batch_size_hint=self._pad_hint())

        def _put(arr):
            arr = np.asarray(arr)
            sharding = self._global_sharding_for(arr)
            if PartialState().num_processes > 1:
                return jax.make_array_from_process_local_data(sharding, arr)
            return jax.device_put(arr, sharding)

        return recursively_apply(_put, _to_numpy_tree(batch))

    # -- iteration protocol ----------------------------------------------

    def _raw_batches(self) -> Iterator:
        """Yield host-side batches for this process. Overridden by modes."""
        raise NotImplementedError

    def __iter__(self):
        if self.rng_types is not None:
            synchronize_rng_states(self.rng_types)
        self.begin()
        self.end_of_dataloader = False
        self._pending_skip = self._resume_skip
        self._resume_skip = 0
        self.batches_yielded = self._pending_skip
        # Snapshot the sampler state NOW: prefetch + the 1-batch lookahead may
        # run the sampler's iterator to exhaustion (auto-incrementing its
        # epoch) while the consumer is still mid-epoch; a mid-epoch save must
        # record the epoch whose permutation is actually being consumed.
        sampler = self._stateful_sampler()
        self._sampler_snapshot = sampler.state_dict() if sampler is not None else None
        tel = self._telemetry

        def _next(it):
            # Telemetry: the time this call blocks is exactly the host wait
            # the prefetch thread failed to hide — input starvation.
            if tel is None:
                return next(it)
            t0 = time.perf_counter()
            try:
                return next(it)
            finally:
                tel.add_data_wait(time.perf_counter() - t0)

        try:
            iterator = self._raw_batches()
            if self.prefetch_size and self.prefetch_size > 0:
                iterator = _PrefetchIterator(iterator, self.prefetch_size)
            try:
                current = _next(iterator)
            except StopIteration:
                self.batches_yielded = 0
                self._sampler_snapshot = None
                return
            while True:
                try:
                    nxt = _next(iterator)
                except StopIteration:
                    self.end_of_dataloader = True
                    self.batches_yielded += 1
                    yield self._device_put_batch(current)
                    # Epoch completed cleanly: next save records the live
                    # (already-advanced) sampler state with a zero offset.
                    self.batches_yielded = 0
                    self._sampler_snapshot = None
                    break
                self.batches_yielded += 1
                yield self._device_put_batch(current)
                current = nxt
        finally:
            if isinstance(iterator, _PrefetchIterator):
                iterator.close()
            self.end()

    # -- mid-epoch resume -------------------------------------------------

    def _consume_skip(self) -> int:
        """Called once by each _raw_batches implementation: number of batches
        to fast-forward past (armed by load_state_dict)."""
        n, self._pending_skip = self._pending_skip, 0
        return n

    def _stateful_sampler(self):
        obj, seen = self.batch_sampler, set()
        while obj is not None and id(obj) not in seen:
            seen.add(id(obj))
            if hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict"):
                return obj
            obj = getattr(obj, "sampler", None) or getattr(obj, "batch_sampler", None)
        return None

    def state_dict(self) -> dict:
        sd = {"batches_yielded": self.batches_yielded}
        if self._sampler_snapshot is not None:
            sd["sampler"] = self._sampler_snapshot  # mid-epoch: epoch-start state
        else:
            sampler = self._stateful_sampler()
            if sampler is not None:
                sd["sampler"] = sampler.state_dict()
        return sd

    def load_state_dict(self, state: dict):
        self._resume_skip = int(state.get("batches_yielded", 0))
        sampler = self._stateful_sampler()
        if sampler is not None and state.get("sampler") is not None:
            sampler.load_state_dict(state["sampler"])

    def begin(self):
        """Register with GradientState (reference: data_loader.py:402-408)."""
        total_bs = self.total_batch_size
        total_len = self.total_dataset_length
        # drop_last loaders never pad, so there is no duplicate tail for
        # gather_for_metrics to trim (reference guards begin() the same way,
        # data_loader.py:402-408); trimming anyway would chop real samples
        # off the final full batch.
        if total_bs and total_len is not None and not self._drop_last:
            # Duplicate-sample count on the final gathered batch, consumed by
            # gather_for_metrics (reference: accelerator.py:3068-3140).
            self.remainder = total_len % total_bs
        self.gradient_state._add_dataloader(self)

    def end(self):
        self.gradient_state._remove_dataloader(self)

    def set_epoch(self, epoch: int):
        if self.batch_sampler is not None and hasattr(self.batch_sampler, "sampler") and hasattr(
            self.batch_sampler.sampler, "set_epoch"
        ):
            self.batch_sampler.sampler.set_epoch(epoch)
        elif hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    @property
    def total_batch_size(self):
        if self.batch_sampler is None:
            return None
        if isinstance(self.batch_sampler, BatchSamplerShard):
            if self.batch_sampler.split_batches:
                return self.batch_sampler.batch_size
            return (self.batch_sampler.batch_size or 1) * self.batch_sampler.num_processes
        return getattr(self.batch_sampler, "batch_size", None)

    @property
    def total_dataset_length(self):
        try:
            return len(self.dataset)
        except TypeError:
            return None


class DataLoaderShard(BaseDataLoader):
    """Per-process loader over a sharded batch sampler
    (reference: data_loader.py:510-667)."""

    def __len__(self):
        return len(self.batch_sampler)

    def _raw_batches(self):
        fast = self.collate_fn is default_collate
        sampler_it = iter(self.batch_sampler)
        for _ in range(self._consume_skip()):  # resume: indices only, no collation
            if next(sampler_it, None) is None:
                return
        for batch_indices in sampler_it:
            # Native batch-assembly fast paths (one gather instead of a
            # Python loop per item) for array-backed datasets.
            if fast and isinstance(self.dataset, ColumnDataset):
                yield self.dataset.gather_batch(batch_indices)
                continue
            if fast and isinstance(self.dataset, np.ndarray) and self.dataset.ndim > 0:
                from .native import gather_rows

                yield gather_rows(self.dataset, batch_indices)
                continue
            samples = [self.dataset[i] for i in batch_indices]
            yield self.collate_fn(samples)


class IterableDataLoaderShard(BaseDataLoader):
    """Loader over an :class:`IterableDatasetShard`."""

    def __init__(self, dataset_shard: IterableDatasetShard, batch_size: int, **kwargs):
        super().__init__(dataset_shard, batch_sampler=None, **kwargs)
        self.batch_size = batch_size

    def _pad_hint(self) -> Optional[int]:
        # No batch sampler, so total_batch_size is None — the per-process
        # batch size is the bucket the ragged tail pads to.
        return self.batch_size

    def _raw_batches(self):
        element_it = iter(self.dataset)
        skip_elements = self._consume_skip() * self.batch_size
        _end = object()
        for _ in range(skip_elements):  # resume: drain shard elements
            if next(element_it, _end) is _end:
                return
        samples = []
        for element in element_it:
            samples.append(element)
            if len(samples) == self.batch_size:
                yield self.collate_fn(samples)
                samples = []
        if samples:
            yield self.collate_fn(samples)


class DataLoaderDispatcher(BaseDataLoader):
    """Process 0 reads the data; batch structure + content broadcast to all,
    then each process keeps its slice (reference: data_loader.py:722-994).
    Useful when the dataset lives only on one host (e.g. a stream)."""

    @property
    def total_batch_size(self):
        bs = getattr(self.batch_sampler, "batch_size", None)
        if bs is None:
            return None
        return bs if self.split_batches else bs * PartialState().num_processes

    def __init__(self, dataset, batch_sampler=None, split_batches: bool = False,
                 dispatch_group_size: int = 8, **kwargs):
        super().__init__(dataset, batch_sampler=batch_sampler, **kwargs)
        self.split_batches = split_batches
        # The per-broadcast cost is FIXED (~7 ms on a 2-proc host gang;
        # payload size barely matters below ~1 MB), so rank 0 reads ahead and ships
        # ``dispatch_group_size`` batches per collective, amortizing that
        # fixed cost to ~1 ms/batch. Same batches, same order — only the
        # collective cadence changes; every rank buffers one group.
        self.dispatch_group_size = max(1, int(dispatch_group_size))
        # Byte cap on a read-ahead group. Grouping only amortizes the
        # collective's FIXED cost, which stops mattering above ~1 MB payloads
        # (see _raw_batches) — so the cap sits AT 1 MiB: beyond it bandwidth
        # dominates and read-ahead just spikes host memory and
        # time-to-first-batch. Pinned by tests/test_data_loader.py.
        self.dispatch_group_bytes = 1 << 20
        if PartialState().num_processes > 1:
            # Dispatch mode runs broadcast collectives inside _raw_batches;
            # those must stay on the main thread, interleaved in the same
            # order on every rank — a prefetch thread would race them against
            # the step's collectives and deadlock.
            self.prefetch_size = 0

    def __len__(self):
        import math as _math

        n = len(self.batch_sampler)
        world = PartialState().num_processes
        if self.split_batches or world == 1:
            return n
        # Non-split dispatch consumes ``world`` sampler batches per yield.
        return _math.ceil(n / world)

    def _raw_batches(self):
        state = PartialState()
        world = state.num_processes
        if world == 1:
            it = iter(self.batch_sampler)
            for _ in range(self._consume_skip()):
                if next(it, None) is None:
                    return
            for batch_indices in it:
                samples = [self.dataset[i] for i in batch_indices]
                yield self.collate_fn(samples)
            return
        # Reference batch semantics (data_loader.py:804-944): in non-split
        # mode every rank receives a FULL batch_size batch, so rank 0 reads
        # ``world`` sampler batches per step and concatenates; split mode
        # slices one sampler batch into batch_size/world shards.
        per_yield = 1 if self.split_batches else world
        it = iter(self.batch_sampler)
        if state.is_main_process:
            for _ in range(self._consume_skip() * per_yield):
                if next(it, None) is None:
                    break
        else:
            self._consume_skip()
        group_size = self.dispatch_group_size
        # Grouping amortizes the collective's FIXED cost, which only pays off
        # for payloads up to ~1 MB — beyond that bandwidth dominates and the
        # read-ahead just costs host memory and time-to-first-batch. Cap the
        # group by bytes (rank 0 decides; the explicit `exhausted` flag in
        # the payload keeps every rank's termination symmetric).
        group_byte_cap = self.dispatch_group_bytes
        while True:
            if state.is_main_process:
                batches, group_bytes, exhausted = [], 0, False
                while len(batches) < group_size:
                    groups = []
                    for _ in range(per_yield):
                        try:
                            batch_indices = next(it)
                        except StopIteration:
                            break
                        samples = [self.dataset[i] for i in batch_indices]
                        groups.append(_to_numpy_tree(self.collate_fn(samples)))
                    if not groups:
                        exhausted = True
                        break
                    batch = groups[0] if len(groups) == 1 else concatenate(groups)
                    batches.append(batch)
                    group_bytes += sum(
                        getattr(leaf, "nbytes", 0)
                        for leaf in jax.tree_util.tree_leaves(batch)
                    )
                    if group_bytes >= group_byte_cap:
                        break
                payload = [batches, exhausted]
            else:
                payload = [None, None]
            broadcast_object_list(payload, from_process=0)
            batches, exhausted = payload
            for batch in batches:
                bs = find_batch_size(batch)
                if bs % world != 0:
                    # Final partial batch: repeat leading samples so every
                    # rank gets an equal, non-empty shard; gather_for_metrics
                    # trims the duplicates via `remainder` (reference:
                    # data_loader.py:804-944).
                    from .utils.operations import pad_input_tensors

                    batch = pad_input_tensors(batch, bs, world)
                    bs = find_batch_size(batch)
                shard = bs // world
                start = state.process_index * shard
                yield slice_tensors(batch, start, start + shard)
            if exhausted:
                return


def prepare_data_loader(
    dataloader,
    device=None,
    num_processes: Optional[int] = None,
    process_index: Optional[int] = None,
    split_batches: bool = False,
    put_on_device: bool = True,
    rng_types=None,
    dispatch_batches: Optional[bool] = None,
    even_batches: bool = True,
    slice_fn_for_dispatch=None,
    use_seedable_sampler: bool = True,
    data_seed: Optional[int] = None,
    non_blocking: bool = True,
    use_stateful_dataloader: bool = False,
    torch_device_mesh=None,
    prefetch_size: int = 2,
    dispatch_group_size: int = 8,
) -> BaseDataLoader:
    """Factory turning a user dataloader/dataset into a mesh-aware loader
    (reference: data_loader.py:1014-1327).

    Accepts:
      - a torch ``DataLoader`` (rebuilt with sharded samplers; batches land as
        global jax Arrays),
      - any ``(dataset, batch_size)``-style object with ``.dataset`` and
        ``.batch_size``,
      - a plain indexable dataset (then ``batch_size`` kwargs of the caller
        apply via ``DataLoaderConfiguration``),
      - an iterable dataset (no ``__len__``): wrapped in
        :class:`IterableDatasetShard`.

    Data-parallel ranks = processes along dp axes only; tp/cp/sp ranks of the
    same dp coordinate receive identical batches because batch arrays are laid
    out by PartitionSpec, not by rank arithmetic (the reference needs explicit
    mesh-aware rank remapping here, data_loader.py:1127-1163 — GSPMD gives it
    to us structurally)."""
    state = PartialState()
    if num_processes is None:
        # Only dp-axis processes read distinct data. With a single-controller
        # multi-host setup each process feeds its local addressable shard of
        # the batch arrays; make_array_from_process_local_data wants the
        # per-process slice of the *global* batch.
        num_processes = state.num_processes
    if process_index is None:
        process_index = state.process_index

    if dispatch_batches is None:
        dispatch_batches = False

    # Decompose the incoming loader.
    dataset = getattr(dataloader, "dataset", dataloader)
    batch_size = getattr(dataloader, "batch_size", None) or 1
    collate_fn = getattr(dataloader, "collate_fn", None) or default_collate
    drop_last = bool(getattr(dataloader, "drop_last", False))
    shuffle = _infer_shuffle(dataloader)
    seed = data_seed if data_seed is not None else 0

    has_len = True
    try:
        len(dataset)
    except TypeError:
        has_len = False

    if not has_len:
        shard = IterableDatasetShard(
            dataset,
            batch_size=batch_size,
            drop_last=drop_last,
            num_processes=num_processes,
            process_index=process_index,
            split_batches=split_batches,
        )
        return IterableDataLoaderShard(
            shard,
            batch_size=batch_size // num_processes if split_batches else batch_size,
            collate_fn=collate_fn,
            device_placement=put_on_device,
            rng_types=rng_types,
            prefetch_size=prefetch_size,
            _drop_last=drop_last,
        )

    if use_seedable_sampler and shuffle:
        sampler = SeedableRandomSampler(len(dataset), seed=seed)
    elif shuffle:
        # Seed must be identical on every process or ranks shuffle with
        # different permutations and the round-robin shards overlap; draw on
        # rank 0 and broadcast (the role of the reference's generator-state
        # sync, data_loader.py:576-578).
        import os as _os

        drawn = [int(_os.environ.get("ACCELERATE_SEED", _pyrandom.randint(0, 2**31)))]
        if PartialState().num_processes > 1:
            broadcast_object_list(drawn, from_process=0)
        sampler = SeedableRandomSampler(len(dataset), seed=drawn[0])
    else:
        sampler = SequentialSampler(len(dataset))

    inner = BatchSampler(sampler, batch_size=batch_size, drop_last=drop_last)
    if dispatch_batches:
        return DataLoaderDispatcher(
            dataset,
            batch_sampler=inner,
            split_batches=split_batches,
            dispatch_group_size=dispatch_group_size,
            collate_fn=collate_fn,
            device_placement=put_on_device,
            rng_types=rng_types,
            prefetch_size=prefetch_size,
            _drop_last=drop_last,
        )
    sharded = BatchSamplerShard(
        inner,
        num_processes=num_processes,
        process_index=process_index,
        split_batches=split_batches,
        even_batches=even_batches,
    )
    return DataLoaderShard(
        dataset,
        batch_sampler=sharded,
        collate_fn=collate_fn,
        device_placement=put_on_device,
        rng_types=rng_types,
        prefetch_size=prefetch_size,
        _drop_last=drop_last,
    )


def _infer_shuffle(dataloader) -> bool:
    sampler = getattr(dataloader, "sampler", None)
    if sampler is None:
        return False
    name = type(sampler).__name__
    return "Random" in name


class SkipBatchSampler:
    """Yields batches of an inner batch sampler after skipping the first
    ``skip_batches`` (reference: data_loader.py:1330-1360)."""

    def __init__(self, batch_sampler, skip_batches: int = 0):
        self.batch_sampler = batch_sampler
        self.skip_batches = skip_batches

    def __iter__(self):
        for index, samples in enumerate(self.batch_sampler):
            if index >= self.skip_batches:
                yield samples

    @property
    def total_length(self):
        return len(self.batch_sampler)

    def __len__(self):
        return max(0, len(self.batch_sampler) - self.skip_batches)


def skip_first_batches(dataloader, num_batches: int = 0):
    """Mid-epoch resume: a loader that skips the first ``num_batches``
    (reference: data_loader.py:1393-1469)."""
    if isinstance(dataloader, BaseDataLoader) and dataloader.batch_sampler is not None:
        import copy

        new_loader = copy.copy(dataloader)
        new_loader.batch_sampler = SkipBatchSampler(dataloader.batch_sampler, skip_batches=num_batches)
        return new_loader

    class _Skipper:
        def __init__(self, dl, n):
            self.dl = dl
            self.n = n

        def __iter__(self):
            for i, batch in enumerate(self.dl):
                if i >= self.n:
                    yield batch

        def __len__(self):
            return max(0, len(self.dl) - self.n)

    return _Skipper(dataloader, num_batches)
