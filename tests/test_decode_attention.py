"""``ops/decode_attention.py``: one query row a slot against the rows the slot
holds, read from the whole cache stack by a plane index and a per-slot bound.
Here under the Pallas interpreter on the CPU, against ``generation._attend`` on
the layer's slice: head mapping, dtypes, planes and bounds; a slot whose bound
is 0 reads nothing; and the forwards that reach it through
``kv_cache.cache_attend`` emit what the sliced path emits, with free and
still-prefilling slots beside the decoding ones. What the chip's compiler makes
of it is in ``tests/test_generation_attend.py``; its speed is in ``PERF.md``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import Model, ServingConfig, ServingEngine, kv_cache
from accelerate_tpu.generation import KVCache, _attend, _llama_forward_cached
from accelerate_tpu.ops import decode_attention as da
from accelerate_tpu.utils import set_seed

T_MAX, BLOCK, PLANES, D = 40, 16, 3, 8   # 40 rows: the third block starts early, at row 24


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel under the interpreter wherever the program is lowered, its
    blocks ``BLOCK`` rows of the tiny shapes here."""
    monkeypatch.setattr(da, "INTERPRET", True)
    monkeypatch.setattr(da, "block_rows", lambda t_max, *_: min(BLOCK, t_max))


BOUNDS = {
    "zero": [0, 0, 0, 0],
    "one": [1, 1, 1, 1],
    "mid_block": [7, 21, 37, 7],
    "block_edge": [16, 32, 17, 33],
    "t_max": [T_MAX] * 4,
    "each_its_own": [0, 1, 29, T_MAX],
}


@pytest.mark.parametrize("bounds", list(BOUNDS))
@pytest.mark.parametrize("plane", [0, PLANES - 1], ids=["plane_0", "plane_last"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)], ids=["mha", "gqa", "mqa"])
def test_the_kernel_equals_attend_on_the_layer_s_slice(interpreted, hq, hkv, dtype, plane, bounds):
    bound = np.asarray(BOUNDS[bounds], np.int32)
    b = len(bound)
    kq, kk, kv = jax.random.split(jax.random.key(hq * 10 + hkv), 3)
    q = jax.random.normal(kq, (b, 1, hq, D), dtype)
    ck = jax.random.normal(kk, (PLANES, b, T_MAX, hkv, D), dtype)
    cv = jax.random.normal(kv, (PLANES, b, T_MAX, hkv, D), dtype)
    # a slot that does not decode is never read: were it, these would come through
    dead = bound == 0
    ck, cv = ck.at[:, dead].set(jnp.nan), cv.at[:, dead].set(jnp.nan)

    out = jax.jit(lambda *a: da.decode_attention(*a, interpret=True))(
        q, ck, cv, jnp.int32(plane), jnp.asarray(bound))

    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    assert not out[dead].any()                                  # finite zeros
    want = np.asarray(_attend(q, ck[plane], cv[plane], jnp.asarray(bound - 1)[:, None]), np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out[~dead], want[~dead], rtol=tol, atol=tol)


def test_rows_past_the_bound_are_not_scored(interpreted):
    """Rows at and past a slot's bound hold what an earlier request left: the
    output does not move when they change, in the bound's own block either."""
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(kq, (2, 1, 4, D))
    ck, cv = (jax.random.normal(k, (1, 2, T_MAX, 2, D)) for k in (kk, kv))
    bound = jnp.asarray([21, 16], jnp.int32)
    past = jnp.arange(T_MAX)[None, :, None, None] >= bound[:, None, None, None]
    run = jax.jit(lambda ck, cv: da.decode_attention(q, ck, cv, jnp.int32(0), bound,
                                                     interpret=True))
    stale = run(jnp.where(past, 7.0, ck), jnp.where(past, -3.0, cv))
    np.testing.assert_array_equal(np.asarray(run(ck, cv)), np.asarray(stale))


def test_the_kernel_s_blocks_follow_the_cache_s_shapes():
    # about 512 KB a side: Mistral's and Mixtral's 8 x 128 in bf16, Ouro's 16 x 128
    assert da.block_rows(2048, 8, 128, jnp.bfloat16) == 256
    assert da.block_rows(512, 16, 128, jnp.bfloat16) == 128
    assert da.block_rows(64, 8, 128, jnp.bfloat16) == 64         # never past a slot's rows
    assert da.block_rows(2048, 8, 128, jnp.float32) == 128
    # what does not fill whole tiles keeps the dots over the slice
    assert da.block_rows(2048, 8, 64, jnp.bfloat16) is None
    assert da.block_rows(2048, 2, 128, jnp.bfloat16) is None
    assert [da.rows_read(n, 256) for n in (0, 1, 256, 257)] == [0, 256, 256, 512]


def test_which_caches_the_kernel_takes():
    side = jnp.zeros((2, 3, 16, 8, 128), jnp.bfloat16)
    assert kv_cache.decode_block_rows(side) == 16
    assert kv_cache.decode_block_rows(kv_cache.quantize_kv_page(side)) is None     # int8 pages
    assert kv_cache.decode_block_rows(jnp.zeros((2, 3, 16, 2, 8))) is None          # tiny heads
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("slots",))
    over_two = jax.device_put(jnp.zeros((2, 4, 16, 8, 128), jnp.bfloat16),
                              jax.sharding.NamedSharding(mesh, kv_cache.slots_partition("slots")))
    assert kv_cache.decode_block_rows(over_two) is None
    seen = []
    jax.jit(lambda a: seen.append(kv_cache.decode_block_rows(a)) or a)(over_two)   # and as a tracer
    assert seen == [None]
    # off the chip a step reads the whole buffer, whatever its shapes
    cache = KVCache(side, side, jnp.zeros((3,), jnp.int32))
    assert kv_cache.decode_reads(cache) is None


# -- through the forwards -----------------------------------------------------------


@pytest.fixture(scope="module")
def llama():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    probe = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
    return cfg, Model.from_flax(module, jax.random.key(0), probe)


def _slot_cache(cfg, lengths, t_max, seed=4):
    spec = kv_cache.cache_spec(cfg)
    shape = (spec.layers, len(lengths), t_max, spec.kv_heads, spec.head_dim)
    kk, kv = jax.random.split(jax.random.key(seed))
    return KVCache(jax.random.normal(kk, shape), jax.random.normal(kv, shape),
                   jnp.asarray(lengths, jnp.int32))


def test_a_decode_step_by_bound_writes_every_row_and_reads_the_live_ones(llama, interpreted,
                                                                         monkeypatch):
    """Slots 0 and 2 decode; slot 1 is still prefilling (2 rows written, its
    next chunk due at row 2) and slot 3 is free: bound 0 for both. Their rows
    are written at their own lengths as ever, so the chunk that comes next
    overwrites the garbage row; the decoding slots' logits are the sliced
    path's."""
    cfg, model = llama
    cache = _slot_cache(cfg, [5, 2, 9, 0], T_MAX)
    ids = jnp.asarray([[3], [5], [7], [9]], jnp.int32)
    bound = jnp.asarray([6, 0, 10, 0], jnp.int32)

    got, new = _llama_forward_cached(cfg, model.params, ids, cache, attn_bound=bound)
    by_length, _ = _llama_forward_cached(cfg, model.params, ids, cache)
    monkeypatch.setattr(da, "INTERPRET", False)   # and the same step off the chip
    want, want_new = _llama_forward_cached(cfg, model.params, ids, cache, attn_bound=bound)

    live = np.asarray(bound) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    # without a bound every row reads up to its own length, the idle ones too
    np.testing.assert_allclose(np.asarray(by_length), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new.length), [6, 3, 10, 1])
    # the first layer's new rows do not depend on attention: written where they were
    np.testing.assert_array_equal(np.asarray(new.k[0]), np.asarray(want_new.k[0]))
    for side, old in ((new.k, cache.k), (new.v, cache.v)):
        changed = np.asarray((side != old).any(axis=(0, 3, 4)))          # (B, T)
        expect = np.zeros_like(changed)
        expect[np.arange(4), [5, 2, 9, 0]] = True
        np.testing.assert_array_equal(changed, expect)


def test_engine_emits_the_tokens_of_the_sliced_path(llama, monkeypatch):
    """Three requests on three slots of four: the long prompt is still being
    prefilled chunk by chunk while the short ones decode, the last slot stays
    free, and the first slot to retire is used again. Every decode step runs
    the kernel with bound 0 on the slots that do not decode; the tokens are
    those of the dots over each layer's slice."""
    cfg, model = llama
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32) for n in (5, 23, 7, 6)]

    def served():
        engine = ServingEngine(model, ServingConfig(n_slots=3, max_len=48, prefill_chunks=[4, 8]))
        rows = engine.run(prompts, max_new_tokens=[6, 5, 9, 4])
        return rows, engine.stats()

    want, stats = served()
    assert stats["cache"]["read_rows_mean"] == 3 * 48          # the whole buffer, every step
    monkeypatch.setattr(da, "INTERPRET", True)
    monkeypatch.setattr(da, "block_rows", lambda t_max, *_: min(BLOCK, t_max))
    got, stats = served()
    for g, w, prompt in zip(got, want, prompts):
        assert len(g) > len(prompt)
        np.testing.assert_array_equal(g, w)
    # each decoding slot's rows rounded up to a block of 16, nothing for the others
    cache = stats["cache"]
    assert cache["live_rows_mean"] <= cache["read_rows_mean"] < cache["live_rows_mean"] + 3 * BLOCK
    assert cache["read_rows_mean"] < 3 * 48
