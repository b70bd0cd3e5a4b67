"""Compile manager (compile_manager.py): bucket-policy math, ragged-stream
executable capping, shapes-manifest round-trip, AOT warmup (zero recompiles
on a warmed run, idempotence), ragged-final-batch padding, persistent-cache
validation + LRU pruning, and the off-by-default zero-overhead contract.
All CPU-only, tier-1 fast."""

import itertools
import json
import logging
import os
import time

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# Toy ragged-batch harness
# ---------------------------------------------------------------------------

N_ITEMS, DIM = 128, 4
# 8 distinct raw sequence lengths -> pow2 buckets {8, 16, 32, 64} (4 buckets).
RAGGED_LENGTHS = [5, 7, 9, 12, 17, 24, 33, 47]


def _data(seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(N_ITEMS, 64, DIM)).astype(np.float32)
    ys = rng.normal(size=(N_ITEMS, 64, 1)).astype(np.float32)
    return xs, ys


class _Dataset:
    def __init__(self, xs, ys):
        self.xs, self.ys = xs, ys

    def __len__(self):
        return len(self.xs)

    def __getitem__(self, i):
        return {"x": self.xs[i], "y": self.ys[i]}


def _ragged_collate(lengths):
    """Collate that trims each successive batch to the next raw length —
    a deterministic ragged stream through the real loader path."""
    counter = itertools.count()

    def collate(samples):
        s = lengths[next(counter) % len(lengths)]
        return {
            "x": np.stack([it["x"][:s] for it in samples]),
            "y": np.stack([it["y"][:s] for it in samples]),
        }

    return collate


class _Spec:
    def __init__(self, dataset, batch_size, collate_fn=None, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = None
        self.drop_last = drop_last
        if collate_fn is not None:
            self.collate_fn = collate_fn


def _accelerator(tmp_path, compile_kwargs=None, telemetry=True, **acc_kw):
    import optax  # noqa: F401 - ensures optax present before Accelerator

    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import CompileKwargs, TelemetryKwargs, set_seed

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    set_seed(0)
    handlers = []
    if compile_kwargs is not None:
        handlers.append(
            compile_kwargs if isinstance(compile_kwargs, CompileKwargs) else CompileKwargs(**compile_kwargs)
        )
    if telemetry:
        handlers.append(
            TelemetryKwargs(sync_timing=True, straggler_probe_every=0, log_every=0)
        )
    return Accelerator(project_dir=str(tmp_path), kwargs_handlers=handlers, **acc_kw)


def _prepare(acc, spec):
    import jax
    import optax
    import flax.linen as nn

    from accelerate_tpu import Model

    module = nn.Dense(1)
    model = Model.from_flax(module, jax.random.key(0), np.zeros((1, 8, DIM), np.float32))
    model, opt, dl = acc.prepare(model, optax.sgd(0.01), spec)

    def loss_fn(params, batch):
        pred = module.apply({"params": params}, batch["x"])
        return ((pred - batch["y"]) ** 2).mean()

    return model, dl, loss_fn


def _run_epoch(acc, dl, loss_fn, step=None):
    step = step or acc.prepare_train_step(loss_fn)
    state = acc.train_state
    for batch in dl:
        state, _ = step(state, batch)
    return step


# ---------------------------------------------------------------------------
# Bucket-policy math
# ---------------------------------------------------------------------------


def test_pow2_bucket_ladder_edges():
    from accelerate_tpu.compile_manager import ladder_bucket, pow2_bucket

    assert pow2_bucket(1, min_bucket=8) == 8
    assert pow2_bucket(8, min_bucket=8) == 8
    assert pow2_bucket(9, min_bucket=8) == 16
    assert pow2_bucket(16, min_bucket=8) == 16
    assert pow2_bucket(17, min_bucket=8) == 32
    assert pow2_bucket(1, min_bucket=1) == 1
    # Cap: past max_bucket is the oversize fall-through (None).
    assert pow2_bucket(33, min_bucket=8, max_bucket=32) is None
    assert pow2_bucket(32, min_bucket=8, max_bucket=32) == 32
    # Fixed ladders.
    assert ladder_bucket(5, [8, 16]) == 8
    assert ladder_bucket(8, [16, 8]) == 8  # unsorted input is fine
    assert ladder_bucket(9, [8, 16]) == 16
    assert ladder_bucket(17, [8, 16]) is None


def test_oversize_falls_through_with_warning(tmp_path, caplog):
    acc = _accelerator(
        tmp_path, compile_kwargs={"buckets": "pow2", "max_bucket": 16}, telemetry=False
    )
    cm = acc.compile_manager
    with caplog.at_level(logging.WARNING):
        assert cm.bucket_for(33, "seq") == 33  # true shape ships
    assert any("exceeds the largest bucket" in r.getMessage() for r in caplog.records)
    assert cm.oversize_events == 1
    assert cm.bucket_for(9, "seq") == 16  # in-range dims still bucket


def test_auto_policy_builds_ladder_from_manifest(tmp_path):
    from accelerate_tpu.compile_manager import tree_to_spec

    acc = _accelerator(tmp_path, compile_kwargs={"buckets": "auto"}, telemetry=False)
    cm = acc.compile_manager
    cm.manifest.record("d1", tree_to_spec({"x": np.zeros((16, 24, 4), np.float32)}))
    cm.manifest.record("d2", tree_to_spec({"x": np.zeros((16, 48, 4), np.float32)}))
    assert cm.bucket_for(20, "seq") == 24  # smallest observed rung >= n
    assert cm.bucket_for(30, "seq") == 48
    # Past the observed ladder: falls back to the pow2 ladder, not a crash.
    assert cm.bucket_for(50, "seq") == 64


# ---------------------------------------------------------------------------
# Bucket padding at the device boundary
# ---------------------------------------------------------------------------


def test_ragged_stream_caps_executables(tmp_path):
    """>= 8 distinct raw sequence lengths, pow2 buckets -> at most 4
    executables, and a second epoch over the same stream adds zero
    recompiles (the acceptance bar)."""
    xs, ys = _data()
    acc = _accelerator(tmp_path, compile_kwargs={"buckets": "pow2"})
    spec = _Spec(_Dataset(xs, ys), 16, collate_fn=_ragged_collate(RAGGED_LENGTHS))
    _, dl, loss_fn = _prepare(acc, spec)
    step = _run_epoch(acc, dl, loss_fn)
    assert acc.compile_manager.executable_count() <= 4
    recompiles_after_first_epoch = acc.telemetry.recompiles
    _run_epoch(acc, dl, loss_fn, step=step)  # same buckets: fully warm
    assert acc.telemetry.recompiles == recompiles_after_first_epoch
    assert acc.compile_manager.executable_count() <= 4
    # The manifest recorded one signature per bucket.
    assert len(acc.compile_manager.manifest) == 4
    acc.end_training()


def test_ragged_final_batch_padded_to_batch_bucket(tmp_path):
    """drop_last=False + even_batches=False ships a ragged 8-sample tail
    (40 % 16) without the manager; under the manager it pads to the full
    batch-size bucket, so every epoch compiles the same single shape."""
    from accelerate_tpu.utils import DataLoaderConfiguration

    xs, ys = _data()
    cfg = DataLoaderConfiguration(even_batches=False)
    acc = _accelerator(
        tmp_path, compile_kwargs={"buckets": "pow2"}, dataloader_config=cfg
    )
    spec = _Spec(_Dataset(xs[:40], ys[:40]), 16)
    _, dl, loss_fn = _prepare(acc, spec)
    shapes = [batch["x"].shape for batch in dl]
    assert len(shapes) == 3
    assert all(s[0] == 16 for s in shapes), shapes  # tail padded 8 -> 16
    acc.end_training()

    # Control: same loader without the manager ships the true ragged tail.
    acc2 = _accelerator(tmp_path, compile_kwargs=None, dataloader_config=cfg)
    _, dl2, _ = _prepare(acc2, _Spec(_Dataset(xs[:40], ys[:40]), 16))
    tail = [batch["x"].shape for batch in dl2][-1]
    assert tail[0] == 8
    acc2.end_training()


def test_emit_mask_constant_structure(tmp_path):
    """emit_mask adds the mask leaf to EVERY batch (padded or not) — a
    mask that appeared only on padded batches would change the compiled
    signature and reintroduce the recompile it exists to prevent."""
    from accelerate_tpu.utils import CompileKwargs

    acc = _accelerator(
        tmp_path,
        compile_kwargs=CompileKwargs(buckets="pow2", emit_mask=True, batch_pad_mode="zero"),
        telemetry=False,
    )
    cm = acc.compile_manager
    full = {"x": np.ones((16, 16, DIM), np.float32)}
    ragged = {"x": np.ones((10, 13, DIM), np.float32)}
    p_full = cm.bucket_pad(full, batch_size_hint=16)
    p_ragged = cm.bucket_pad(ragged, batch_size_hint=16)
    assert set(p_full) == set(p_ragged) == {"x", "pad_mask"}
    assert p_ragged["x"].shape == (16, 16, DIM)
    assert p_full["pad_mask"].shape == p_ragged["pad_mask"].shape == (16, 16)
    assert p_full["pad_mask"].all()
    assert p_ragged["pad_mask"][:10, :13].all()
    assert not p_ragged["pad_mask"][10:].any()
    assert not p_ragged["pad_mask"][:, 13:].any()
    # zero pad mode: padded region really is zeros.
    assert not p_ragged["x"][10:].any()


def test_repeat_pad_cycles_real_samples(tmp_path):
    acc = _accelerator(
        tmp_path, compile_kwargs={"buckets": "pow2", "bucket_seq": False}, telemetry=False
    )
    cm = acc.compile_manager
    arr = np.arange(3, dtype=np.float32)[:, None]
    out = cm.bucket_pad({"x": arr}, batch_size_hint=8)["x"]
    assert out.shape == (8, 1)
    np.testing.assert_array_equal(out.ravel(), [0, 1, 2, 0, 1, 2, 0, 1])


def test_seq_padding_only_touches_reference_aligned_leaves(tmp_path):
    """Axis 1 is only a 'sequence' for leaves agreeing with the batch's
    reference length (first rank>=2 leaf): a (B, 1) target or (B, 10)
    class-score leaf riding in the same dict must NOT be stretched."""
    acc = _accelerator(tmp_path, compile_kwargs={"buckets": "pow2"}, telemetry=False)
    cm = acc.compile_manager
    batch = {
        "x": np.ones((16, 13, DIM), np.float32),   # reference: seq 13 -> 16
        "pos": np.ones((16, 13), np.int32),        # aligned: padded in lockstep
        "y": np.ones((16, 1), np.float32),         # NOT a sequence: untouched
        "scores": np.ones((16, 10), np.float32),   # NOT a sequence: untouched
    }
    out = cm.bucket_pad(batch, batch_size_hint=16)
    assert out["x"].shape == (16, 16, DIM)
    assert out["pos"].shape == (16, 16)
    assert out["y"].shape == (16, 1)
    assert out["scores"].shape == (16, 10)


# ---------------------------------------------------------------------------
# Shapes manifest
# ---------------------------------------------------------------------------


def test_manifest_roundtrip(tmp_path):
    from accelerate_tpu.compile_manager import (
        ShapesManifest,
        spec_map_leaves,
        tree_to_spec,
    )

    batch = {
        "ids": np.zeros((16, 32), np.int32),
        "nested": (np.zeros((16, 32, 8), np.float32), np.zeros((16,), np.float64)),
    }
    spec = tree_to_spec(batch)
    path = str(tmp_path / "manifest.jsonl")
    m = ShapesManifest(path)
    assert m.record("digest-a", spec) is True
    assert m.record("digest-a", spec) is False  # dedup
    # Every line on disk is one self-contained JSON object.
    with open(path) as fh:
        lines = [json.loads(l) for l in fh]
    assert len(lines) == 1 and lines[0]["digest"] == "digest-a"
    # A fresh load reconstructs the same abstract batch.
    m2 = ShapesManifest(path)
    assert "digest-a" in m2 and len(m2) == 1
    rebuilt = spec_map_leaves(
        m2.entries[0]["spec"], lambda shape, dtype: np.zeros(shape, np.dtype(dtype))
    )
    assert rebuilt["ids"].shape == (16, 32) and rebuilt["ids"].dtype == np.int32
    assert isinstance(rebuilt["nested"], tuple)
    assert rebuilt["nested"][0].shape == (16, 32, 8)
    assert rebuilt["nested"][1].dtype == np.float64


def test_manifest_survives_torn_tail_line(tmp_path):
    from accelerate_tpu.compile_manager import ShapesManifest, tree_to_spec

    path = str(tmp_path / "manifest.jsonl")
    m = ShapesManifest(path)
    m.record("ok", tree_to_spec({"x": np.zeros((4, 4), np.float32)}))
    with open(path, "a") as fh:
        fh.write('{"digest": "torn", "spec"')  # preempted mid-write
    m2 = ShapesManifest(path)
    assert len(m2) == 1 and "ok" in m2


# ---------------------------------------------------------------------------
# Warmup
# ---------------------------------------------------------------------------


def test_warmup_zero_recompiles_on_restart(tmp_path, caplog):
    """Run 1 (cold) populates the manifest; run 2 warms every signature at
    prepare_train_step time, so the whole ragged epoch replays with ZERO
    recompiles and no watchdog warnings — the restart acceptance bar."""
    xs, ys = _data()
    acc = _accelerator(tmp_path, compile_kwargs={"buckets": "pow2"})
    spec = _Spec(_Dataset(xs, ys), 16, collate_fn=_ragged_collate(RAGGED_LENGTHS))
    _, dl, loss_fn = _prepare(acc, spec)
    _run_epoch(acc, dl, loss_fn)
    assert len(acc.compile_manager.manifest) == 4
    acc.end_training()

    acc2 = _accelerator(tmp_path, compile_kwargs={"buckets": "pow2"})
    spec2 = _Spec(_Dataset(xs, ys), 16, collate_fn=_ragged_collate(RAGGED_LENGTHS))
    _, dl2, loss_fn2 = _prepare(acc2, spec2)
    caplog.clear()  # drop run 1's expected cold-compile warnings
    with caplog.at_level(logging.WARNING):
        step = acc2.prepare_train_step(loss_fn2)  # warmup fires here
        warmed = dict(acc2.compile_manager.warmup_stats)
        _run_epoch(acc2, dl2, loss_fn2, step=step)
    assert warmed["signatures_compiled"] == 4
    assert warmed["seconds"] > 0
    assert acc2.telemetry.recompiles == 0
    assert acc2.compile_manager.executable_count() <= 4
    assert not any("recompiled" in r.getMessage() for r in caplog.records)
    summary = acc2.telemetry.summary()
    assert summary["executables"] <= 4
    assert summary["compile"]["warmup"]["signatures_compiled"] == 4
    acc2.end_training()


def test_warmup_idempotent(tmp_path):
    """A second warmup pass compiles nothing and leaves the executable
    count unchanged."""
    xs, ys = _data()
    acc = _accelerator(tmp_path, compile_kwargs={"buckets": "pow2"})
    spec = _Spec(_Dataset(xs, ys), 16, collate_fn=_ragged_collate(RAGGED_LENGTHS))
    _, dl, loss_fn = _prepare(acc, spec)
    _run_epoch(acc, dl, loss_fn)
    acc.end_training()

    acc2 = _accelerator(tmp_path, compile_kwargs={"buckets": "pow2"})
    spec2 = _Spec(_Dataset(xs, ys), 16, collate_fn=_ragged_collate(RAGGED_LENGTHS))
    _, _, loss_fn2 = _prepare(acc2, spec2)
    acc2.prepare_train_step(loss_fn2)
    first = acc2.compile_manager.warmup_stats["signatures_compiled"]
    count = acc2.compile_manager.executable_count()
    assert first == 4
    stats = acc2.warmup_compile()  # explicit re-warm: all signatures cached
    assert stats["signatures_compiled"] == first
    assert acc2.compile_manager.executable_count() == count
    acc2.end_training()


def test_telemetry_only_run_writes_manifest_for_future_warmup(tmp_path):
    """Satellite: the recompile watchdog's digests persist to the shapes
    manifest even when the compile manager is OFF, so a later managed run
    can warm from them."""
    xs, ys = _data()
    acc = _accelerator(tmp_path, compile_kwargs=None)
    assert acc.compile_manager is None
    spec = _Spec(_Dataset(xs, ys), 16)
    _, dl, loss_fn = _prepare(acc, spec)
    _run_epoch(acc, dl, loss_fn)
    acc.end_training()
    path = os.path.join(str(tmp_path), "compile_cache", "shapes_manifest.jsonl")
    assert os.path.exists(path)
    with open(path) as fh:
        entries = [json.loads(l) for l in fh]
    assert len(entries) == 1  # one fixed shape all epoch
    assert entries[0]["spec"]["kind"] == "dict"


# ---------------------------------------------------------------------------
# Off-by-default zero overhead
# ---------------------------------------------------------------------------


def test_disabled_by_default_no_manager_no_padding(tmp_path):
    xs, ys = _data()
    acc = _accelerator(tmp_path, compile_kwargs=None, telemetry=False)
    assert acc.compile_manager is None
    assert acc.compile_handler is None
    spec = _Spec(_Dataset(xs, ys), 16, collate_fn=_ragged_collate([13]))
    _, dl, loss_fn = _prepare(acc, spec)
    assert dl._compile_manager is None
    # Batches ship their TRUE (unbucketed) shapes.
    batch = next(iter(dl))
    assert batch["x"].shape == (16, 13, DIM)


# ---------------------------------------------------------------------------
# Persistent-cache control
# ---------------------------------------------------------------------------


def test_persistent_cache_dir_created_and_validated(tmp_path):
    import jax

    from accelerate_tpu.utils import JitConfig

    target = tmp_path / "jit_cache" / "nested"
    prev = jax.config.jax_compilation_cache_dir
    try:
        acc = _accelerator(
            tmp_path,
            compile_kwargs={"buckets": None},
            telemetry=False,
            jit_config=JitConfig(persistent_cache_dir=str(target)),
        )
        assert os.path.isdir(str(target))
        assert acc.jit_config.persistent_cache_dir == str(target)
        assert acc.compile_manager.cache is not None
        stats = acc.compile_manager.cache_stats()
        assert stats["files"] == 0 and stats["misses"] == 0
    finally:
        # The validated path lands in global jax config — restore it so later
        # tests in this process don't compile into this test's tmp dir.
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_is_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and no code sets
    another directory — neither the entry scripts' helper nor a JitConfig.
    Unset: the helper picks ``<checkout>/.jax_cache``, a fixed path."""
    import jax

    from accelerate_tpu.compile_manager import (
        configure_persistent_cache,
        place_compile_cache,
    )
    from accelerate_tpu.utils import JitConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert place_compile_cache() == "/x"
        jit_config = JitConfig(persistent_cache_dir=str(tmp_path / "mine"))
        assert configure_persistent_cache(jit_config) == "/x"
        assert jax.config.jax_compilation_cache_dir == prev
        assert not (tmp_path / "mine").exists()

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert place_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_persistent_cache_unwritable_warns_and_disables(tmp_path, caplog):
    from accelerate_tpu.utils import JitConfig

    blocker = tmp_path / "file"
    blocker.write_text("not a dir")
    bad = str(blocker / "cache")  # mkdir under a regular file must fail
    with caplog.at_level(logging.WARNING):
        acc = _accelerator(
            tmp_path,
            compile_kwargs=None,
            telemetry=False,
            jit_config=JitConfig(persistent_cache_dir=bad),
        )
    assert acc.jit_config.persistent_cache_dir is None
    assert any("persistent compilation cache DISABLED" in r.getMessage() for r in caplog.records)


def test_cache_prune_lru_respects_budget_and_hot_set(tmp_path):
    from accelerate_tpu import PartialState
    from accelerate_tpu.compile_manager import ManagedPersistentCache

    PartialState()  # the multi-process logger needs an initialized state
    cache_dir = tmp_path / "cache"
    cache_dir.mkdir()
    # Three pre-existing 100-byte entries, oldest first.
    for i, name in enumerate(["old_a", "old_b", "old_c"]):
        p = cache_dir / name
        p.write_bytes(b"x" * 100)
        t = time.time() - 1000 + i
        os.utime(p, (t, t))
    cache = ManagedPersistentCache(str(cache_dir), budget_bytes=250)
    # A file created by THIS run (after baseline) is never evicted.
    (cache_dir / "hot").write_bytes(b"x" * 100)
    removed = cache.prune()
    assert removed["removed_files"] == 2  # oldest two go; 200 bytes remain
    assert not (cache_dir / "old_a").exists()
    assert not (cache_dir / "old_b").exists()
    assert (cache_dir / "old_c").exists()
    assert (cache_dir / "hot").exists()
    stats = cache.stats(compile_events=3)
    assert stats["misses"] == 1  # the hot file appeared this run
    assert stats["estimated_hits"] == 2


def test_compile_kwargs_validation():
    from accelerate_tpu.utils import CompileKwargs

    with pytest.raises(ValueError):
        CompileKwargs(buckets="fib")
    with pytest.raises(ValueError):
        CompileKwargs(batch_pad_mode="mirror")
    with pytest.raises(ValueError):
        CompileKwargs(warmup="later")
    CompileKwargs(buckets=None, warmup="off")  # valid combos construct
