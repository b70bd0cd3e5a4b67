"""Native host-runtime kernels (native/host_runtime.cpp) + loader wiring."""

import numpy as np
import pytest


def _lib_available():
    from accelerate_tpu import native

    return native.get_lib() is not None


# Only the kernel-vs-numpy comparisons need the compiled library; the
# fallback paths, loaders and prefetcher must stay tested on toolchain-less
# hosts (that is exactly where they run in production).
requires_lib = pytest.mark.skipif(
    not _lib_available(), reason="g++ unavailable — native kernels disabled"
)


@requires_lib
def test_gather_rows_matches_numpy():
    from accelerate_tpu import native

    rng = np.random.default_rng(0)
    src = rng.normal(size=(1000, 33)).astype(np.float32)
    idx = rng.integers(0, 1000, size=257)
    out = native.gather_rows(src, idx, force=True)
    np.testing.assert_array_equal(out, src[idx])


def test_gather_rows_noncontiguous_falls_back():
    from accelerate_tpu import native

    rng = np.random.default_rng(1)
    src = rng.normal(size=(100, 64)).astype(np.float32)[:, ::2]  # not C-contiguous
    idx = np.arange(50)
    out = native.gather_rows(src, idx, force=True)
    np.testing.assert_array_equal(out, src[idx])


@requires_lib
def test_gather_columns_matches_numpy():
    from accelerate_tpu import native

    rng = np.random.default_rng(2)
    cols = {
        "x": rng.normal(size=(500, 16)).astype(np.float32),
        "y": rng.integers(0, 9, size=(500,)).astype(np.int64),
        "z": rng.normal(size=(500, 4, 3)).astype(np.float64),
    }
    idx = rng.integers(0, 500, size=123)
    out = native.gather_columns(cols, idx, force=True)
    for k in cols:
        np.testing.assert_array_equal(out[k], cols[k][idx])


@requires_lib
def test_stack_items_matches_numpy():
    from accelerate_tpu import native

    rng = np.random.default_rng(3)
    items = [rng.normal(size=(17, 5)).astype(np.float32) for _ in range(64)]
    out = native.stack_items(items, force=True)
    np.testing.assert_array_equal(out, np.stack(items))


def test_column_dataset_loader_batches():
    """ColumnDataset assembles identical batches to per-item collation."""
    from accelerate_tpu.data_loader import ColumnDataset, prepare_data_loader

    rng = np.random.default_rng(4)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.integers(0, 2, size=(64,)).astype(np.int32)
    ds = ColumnDataset(x=x, y=y)
    assert len(ds) == 64
    assert set(ds[3]) == {"x", "y"}

    class _Spec:
        def __init__(self, dataset, batch_size):
            self.dataset = dataset
            self.batch_size = batch_size
            self.sampler = None
            self.drop_last = False

    dl = prepare_data_loader(_Spec(ds, 16), put_on_device=False, use_seedable_sampler=False)
    seen_x, seen_y = [], []
    for b in dl:
        assert b["x"].shape == (16, 8)
        seen_x.append(np.asarray(b["x"]))
        seen_y.append(np.asarray(b["y"]))
    np.testing.assert_array_equal(np.concatenate(seen_x), x)
    np.testing.assert_array_equal(np.concatenate(seen_y), y)


def test_ndarray_dataset_fast_path():
    from accelerate_tpu.data_loader import prepare_data_loader

    data = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)

    class _Spec:
        def __init__(self, dataset, batch_size):
            self.dataset = dataset
            self.batch_size = batch_size
            self.sampler = None
            self.drop_last = False

    dl = prepare_data_loader(_Spec(data, 8), put_on_device=False, use_seedable_sampler=False)
    batches = [np.asarray(b) for b in dl]
    np.testing.assert_array_equal(np.concatenate(batches), data)


def test_prefetch_iterator_order_and_errors():
    from accelerate_tpu.data_loader import _PrefetchIterator

    it = _PrefetchIterator(iter(range(100)), prefetch_size=4)
    assert list(it) == list(range(100))

    def boom():
        yield 1
        raise RuntimeError("inner failure")

    it = _PrefetchIterator(boom(), prefetch_size=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="inner failure"):
        next(it)
    it.close()


def test_prefetch_overlaps_producer_with_step():
    """The MpDeviceLoader-role claim, asserted: with the prefetch thread, a
    producer whose cost is a large fraction of the step time adds (almost)
    nothing to wall-clock; without it, the producer serializes. Margins are
    deliberately wide — this is a regression gate on the overlap mechanism,
    not a microbenchmark."""
    import time

    from accelerate_tpu.data_loader import _PrefetchIterator

    step_s, produce_s, n = 0.02, 0.012, 25

    def producer():
        for i in range(n):
            time.sleep(produce_s)  # emulates dataset read + collation
            yield i

    def walk(it):
        next(it)
        t0 = time.perf_counter()
        k = 0
        for _ in it:
            time.sleep(step_s)  # emulates a dispatched device step
            k += 1
        return (time.perf_counter() - t0) / k

    overlapped = walk(iter(_PrefetchIterator(producer(), prefetch_size=2)))
    serial = walk(iter(producer()))
    assert overlapped < step_s + 0.6 * produce_s, (overlapped, serial)
    assert serial > step_s + 0.8 * produce_s, (overlapped, serial)
    assert overlapped < serial, (overlapped, serial)


def test_prefetch_close_mid_iteration():
    from accelerate_tpu.data_loader import _PrefetchIterator

    it = _PrefetchIterator(iter(range(10_000)), prefetch_size=2)
    assert next(it) == 0
    it.close()  # must not hang


def test_gather_rows_negative_and_bad_indices():
    """Native path must match numpy semantics for negatives, raise on
    out-of-range, and honor boolean masks (review regression)."""
    from accelerate_tpu import native

    src = np.arange(40, dtype=np.float32).reshape(10, 4)
    np.testing.assert_array_equal(
        native.gather_rows(src, [-1, 0, -10], force=True), src[[-1, 0, -10]]
    )
    with pytest.raises(IndexError):
        native.gather_rows(src, [0, 10], force=True)
    mask = np.zeros(10, bool)
    mask[3] = mask[7] = True
    np.testing.assert_array_equal(native.gather_rows(src, mask, force=True), src[mask])
    cols = {"x": src}
    np.testing.assert_array_equal(
        native.gather_columns(cols, [-2, 1], force=True)["x"], src[[-2, 1]]
    )


def test_dispatcher_disables_prefetch_multiprocess():
    """Dispatch-mode collectives must stay on the main thread (single-process
    here, so prefetch stays on; the guard only fires with >1 process)."""
    from accelerate_tpu.data_loader import DataLoaderDispatcher, prepare_data_loader

    class _Spec:
        def __init__(self, dataset, batch_size):
            self.dataset = dataset
            self.batch_size = batch_size
            self.sampler = None
            self.drop_last = False

    data = np.arange(32, dtype=np.int32)
    dl = prepare_data_loader(
        _Spec(data, 8), dispatch_batches=True, put_on_device=False, prefetch_size=0
    )
    assert isinstance(dl, DataLoaderDispatcher)
    assert dl.prefetch_size == 0  # explicit opt-out plumbs through


@requires_lib
def test_load_safetensors_fast_matches_library(tmp_path):
    """Native parallel pread loader == safetensors lib, all dtypes incl bf16."""
    import ml_dtypes
    from safetensors.numpy import save_file

    from accelerate_tpu.native import load_safetensors_fast

    rng = np.random.default_rng(0)
    tensors = {
        "a/f32": rng.normal(size=(64, 128)).astype(np.float32),
        "b/bf16": rng.normal(size=(32, 16)).astype(ml_dtypes.bfloat16),
        "c/i32": rng.integers(-5, 5, size=(7,)).astype(np.int32),
        "d/scalarish": np.asarray([3.0], np.float32),
    }
    path = str(tmp_path / "m.safetensors")
    save_file(tensors, path)
    out = load_safetensors_fast(path, force=True)
    assert out is not None, "native loader must engage when forced"
    assert set(out) == set(tensors)
    for k in tensors:
        assert out[k].dtype == tensors[k].dtype
        np.testing.assert_array_equal(
            out[k].view(np.uint8), tensors[k].view(np.uint8), err_msg=k
        )


def test_load_safetensors_fast_missing_file():
    from accelerate_tpu.native import load_safetensors_fast

    assert load_safetensors_fast("/nonexistent/x.safetensors", force=True) is None


@requires_lib
def test_save_safetensors_fast_roundtrips(tmp_path):
    """Native parallel pwrite writer: the safetensors lib AND the native
    reader both load it back bit-exact, all dtypes incl bf16."""
    import ml_dtypes
    from safetensors.numpy import load_file

    from accelerate_tpu.native import load_safetensors_fast, save_safetensors_fast

    rng = np.random.default_rng(1)
    tensors = {
        "a/f32": rng.normal(size=(64, 128)).astype(np.float32),
        "b/bf16": rng.normal(size=(32, 16)).astype(ml_dtypes.bfloat16),
        "c/i64": rng.integers(-5, 5, size=(9,)).astype(np.int64),
        "d/bool": np.asarray([True, False, True]),
    }
    path = str(tmp_path / "w.safetensors")
    assert save_safetensors_fast(tensors, path, force=True)
    via_lib = load_file(path)
    via_native = load_safetensors_fast(path, force=True)
    for k in tensors:
        for out in (via_lib, via_native):
            assert out[k].dtype == tensors[k].dtype, k
            np.testing.assert_array_equal(
                out[k].view(np.uint8), tensors[k].view(np.uint8), err_msg=k
            )


@requires_lib
def test_save_safetensors_fast_rejects_object_dtype(tmp_path):
    from accelerate_tpu.native import save_safetensors_fast

    bad = {"x": np.asarray([object()], dtype=object)}
    assert save_safetensors_fast(bad, str(tmp_path / "bad.safetensors"), force=True) is False


def test_save_safetensors_unified_path_uses_writer(tmp_path):
    """utils.other.save_safetensors round-trips through whichever path the
    size gate picks."""
    from safetensors.numpy import load_file

    from accelerate_tpu.utils.other import save_safetensors

    big = {"w": np.arange(2**18, dtype=np.float32).reshape(512, 512)}
    path = str(tmp_path / "u.safetensors")
    save_safetensors(big, path)
    out = load_file(path)
    np.testing.assert_array_equal(out["w"], big["w"])
