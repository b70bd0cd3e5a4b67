"""The KV cache is written in place: the layer loop of every cached forward
carries the whole ``(L, B, T, Hkv, D)`` buffers and ``_cache_step`` writes only
the new rows at ``[layer, slot, row]``. Held here, bit for bit, against the
way the loop worked before: each layer lifts its slice out of the stack,
writes into that private copy, attends over it and puts the whole slice back.
The reference below does that in ``_cache_step``'s place, so every forward's
own arithmetic is shared and only the mechanism differs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import Model, ServingConfig, ServingEngine
from accelerate_tpu import generation as G
from accelerate_tpu.utils import set_seed


def _write_private_slice(ck, new, start):
    """The per-slice write as it stood: ``ck`` is one layer's (B, T, Hkv, D)."""
    if isinstance(ck, G.QuantPages):
        q = G.quantize_kv_page(new)
        return G.QuantPages(_write_private_slice(ck.data, q.data, start),
                            _write_private_slice(ck.scale, q.scale, start))
    new = new.astype(ck.dtype)
    if getattr(start, "ndim", 0) == 1:
        rows = jnp.arange(new.shape[0])[:, None]
        cols = start[:, None] + jnp.arange(new.shape[1])[None, :]
        return ck.at[rows, cols].set(new)
    return jax.lax.dynamic_update_slice(ck, new, (0, start, 0, 0))


def _cache_step_on_private_slices(ck, cv, k_new, v_new, layer, start):
    def lift(buf):
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), buf)

    def put_back(buf, piece):
        return jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_slice_in_dim(a, s[None], layer, 0), buf, piece)

    k_i = _write_private_slice(lift(ck), k_new, start)
    v_i = _write_private_slice(lift(cv), v_new, start)
    return put_back(ck, k_i), put_back(cv, v_i), k_i, v_i


def _family(name):
    from accelerate_tpu import models as M

    cfg_cls, module_cls = {
        "llama": (M.LlamaConfig, M.LlamaForCausalLM),
        "gpt2": (M.GPT2Config, M.GPT2LMHeadModel),
        "opt": (M.OPTConfig, M.OPTForCausalLM),
        "neox": (M.GPTNeoXConfig, M.GPTNeoXForCausalLM),
        "mixtral": (M.MixtralConfig, M.MixtralForCausalLM),
    }[name]
    cfg = cfg_cls.tiny(dtype=jnp.float32)
    return cfg, module_cls(cfg)


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        if name not in built:
            set_seed(0)
            cfg, module = _family(name)
            probe = np.ones((1, 4), np.int32)
            built[name] = cfg, module, Model.from_flax(module, jax.random.key(0), probe)
        return built[name]

    return get


def _filled_cache(cfg, batch, t_max, quantized, per_slot, seed):
    """A cache that already holds rows, at another length in every slot."""
    layers, kv_heads, head_dim, _ = G._cache_dims(cfg)
    kk, kv = jax.random.split(jax.random.key(seed))
    shape = (layers, batch, t_max, kv_heads, head_dim)

    def side(key):
        x = jax.random.normal(key, shape, jnp.float32)
        return G.quantize_kv_page(x) if quantized else x

    length = jnp.asarray([5, 2, 9][:batch], jnp.int32) if per_slot else jnp.asarray(5, jnp.int32)
    return G.KVCache(side(kk), side(kv), length)


@pytest.mark.parametrize("pages", ["float", "int8"])
@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("start", ["scalar", "per_slot"])
@pytest.mark.parametrize("family", ["llama", "gpt2", "opt", "neox", "mixtral"])
def test_in_place_cache_equals_private_slices(models, monkeypatch, family, start, s, pages):
    cfg, module, model = models(family)
    fwd = G.GENERATION_PLANS[type(module).__name__]
    cache = _filled_cache(cfg, 3, 16, pages == "int8", start == "per_slot", seed=s)
    ids = jnp.asarray(np.random.default_rng(s).integers(1, cfg.vocab_size, (3, s)), jnp.int32)

    logits, new = fwd(cfg, model.params, ids, cache, return_all=True)
    monkeypatch.setattr(G, "_cache_step", _cache_step_on_private_slices)
    want_logits, want = fwd(cfg, model.params, ids, cache, return_all=True)

    assert logits.shape == (3, s, cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    got_leaves, want_leaves = jax.tree.leaves(new), jax.tree.leaves(want)
    assert len(got_leaves) == len(want_leaves) == (5 if pages == "int8" else 3)
    for got_leaf, want_leaf in zip(got_leaves, want_leaves):
        assert got_leaf.dtype == want_leaf.dtype
        np.testing.assert_array_equal(np.asarray(got_leaf), np.asarray(want_leaf))
    # and the write landed where the rows were appended, in every layer
    old, written = jax.tree.leaves(cache.k)[0], jax.tree.leaves(new.k)[0]
    changed = np.asarray((old != written).any(axis=(0, 3, 4)))            # (B, T)
    at = np.broadcast_to(np.asarray(cache.length), (3,))[:, None] + np.arange(s)[None, :]
    expect = np.zeros_like(changed)
    expect[np.arange(3)[:, None], at] = True
    np.testing.assert_array_equal(changed, expect)


@pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "speculative"])
def test_engine_emits_the_tokens_of_private_slices(models, monkeypatch, speculate_k):
    """Two slots, chunked prefill, slots reused mid-flight; with speculation the
    decode program writes a window of k+1 rows a slot at per-slot offsets."""
    cfg, _, model = models("llama")
    rng = np.random.default_rng(7)
    # a repeating prompt, so that the n-gram draft has something to accept
    prompts = [np.tile(rng.integers(1, cfg.vocab_size, (3,), dtype=np.int32), 4)[:n]
               for n in (5, 11, 7)]

    def served():
        engine = ServingEngine(model, ServingConfig(
            n_slots=2, max_len=48, prefill_chunks=[4, 8], speculate_k=speculate_k))
        return engine.run(prompts, max_new_tokens=[6, 4, 8])

    got = served()
    monkeypatch.setattr(G, "_cache_step", _cache_step_on_private_slices)
    want = served()
    for g, w, prompt in zip(got, want, prompts):
        assert len(g) > len(prompt)
        np.testing.assert_array_equal(g, w)
