"""The KV cache is written in place: the layer loop of every cached forward
carries the whole ``(L, B, T, Hkv, D)`` buffers and ``kv_cache.cache_step``
writes only the new rows at ``[layer, slot, row]``. Held here, bit for bit,
against the way the loop worked before: each layer lifts its slice out of the
stack, writes into that private copy, attends over it and puts the whole slice
back. The reference below does that in ``cache_step``'s place, so every
forward's own arithmetic is shared and only the mechanism differs; that the
substitution reaches all seven plans shows there is one way to the cache.

Below that, the seam itself: ``KVCache``'s operations, which are all that the
serving engines and the planner know of the layout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import Model, ServingConfig, ServingEngine
from accelerate_tpu import generation as G
from accelerate_tpu import kv_cache as KC
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


def _step_on_private_slices(ck, cv, k_new, v_new, layer, start):
    def lift(buf):
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), buf)

    def put_back(buf, piece):
        return jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_slice_in_dim(a, s[None], layer, 0), buf, piece)

    k_i = _write_private_slice(lift(ck), k_new, start)
    v_i = _write_private_slice(lift(cv), v_new, start)
    return put_back(ck, k_i), put_back(cv, v_i), k_i, v_i


# "looped": the Llama chassis with its stack run three times over one set of
# weights (two layers, so six cache planes), sandwich norms and the exit gate
DECODER_ONLY = ["llama", "gpt2", "opt", "neox", "mixtral", "looped"]
ENCODER_DECODER = ["t5", "whisper"]


def _family(name):
    from accelerate_tpu import models as M

    cfg_cls, module_cls, kw = {
        "llama": (M.LlamaConfig, M.LlamaForCausalLM, {}),
        "gpt2": (M.GPT2Config, M.GPT2LMHeadModel, {}),
        "opt": (M.OPTConfig, M.OPTForCausalLM, {}),
        "neox": (M.GPTNeoXConfig, M.GPTNeoXForCausalLM, {}),
        "mixtral": (M.MixtralConfig, M.MixtralForCausalLM, {}),
        "looped": (M.LlamaConfig, M.LlamaForCausalLM,
                   {"total_ut_steps": 3, "sandwich_norm": True, "early_exit_gate": True}),
        "t5": (M.T5Config, M.T5ForConditionalGeneration, {"num_layers": 3}),
        "whisper": (M.WhisperConfig, M.WhisperForConditionalGeneration, {}),
    }[name]
    cfg = cfg_cls.tiny(dtype=jnp.float32, **kw)
    return cfg, module_cls(cfg)


@pytest.fixture(scope="module")
def models():
    """``get(name) -> (cfg, fwd, params)``; an encoder-decoder family's ``fwd``
    has its encoded state (batch 3) closed over and takes no mask."""
    built = {}

    def build(name):
        set_seed(0)
        cfg, module = _family(name)
        rng = np.random.default_rng(0)
        if name in DECODER_ONLY:
            model = Model.from_flax(module, jax.random.key(0), np.ones((1, 4), np.int32))
            return cfg, G.GENERATION_PLANS[type(module).__name__], model.params
        if name == "t5":
            enc_in = rng.integers(1, cfg.vocab_size, (3, 10)).astype(np.int32)
            dec_in = enc_in[:, :4]
        else:
            enc_in = rng.normal(size=(3, 24, cfg.num_mel_bins)).astype(np.float32)
            dec_in = np.zeros((3, 1), np.int32)
        params = module.init(jax.random.key(0), enc_in, dec_in)["params"]
        encode, decode = G.ENCDEC_GENERATION_PLANS[type(module).__name__]
        state = encode(cfg, params, enc_in)
        return cfg, (lambda cfg, params, ids, cache, return_all=False:
                     decode(cfg, params, ids, cache, state, return_all)), params

    def get(name):
        if name not in built:
            built[name] = build(name)
        return built[name]

    return get


def _filled_cache(cfg, batch, t_max, quantized, per_slot, seed):
    """A cache that already holds rows, at another length in every slot."""
    spec = KC.cache_spec(cfg)
    kk, kv = jax.random.split(jax.random.key(seed))
    shape = (spec.layers, batch, t_max, spec.kv_heads, spec.head_dim)

    def side(key):
        x = jax.random.normal(key, shape, jnp.float32)
        return G.quantize_kv_page(x) if quantized else x

    length = jnp.asarray([5, 2, 9][:batch], jnp.int32) if per_slot else jnp.asarray(5, jnp.int32)
    return G.KVCache(side(kk), side(kv), length)


def _forward_cases():
    for family in DECODER_ONLY:
        for start in ("scalar", "per_slot"):
            for s in (1, 4):
                for pages in ("float", "int8"):
                    yield family, start, s, pages
    # the encoder-decoder decoders append at a batch-global length
    for family in ENCODER_DECODER:
        for pages in ("float", "int8"):
            yield family, "scalar", 4, pages


@pytest.mark.parametrize("family,start,s,pages", list(_forward_cases()))
def test_in_place_cache_equals_private_slices(models, monkeypatch, family, start, s, pages):
    cfg, fwd, params = models(family)
    cache = _filled_cache(cfg, 3, 16, pages == "int8", start == "per_slot", seed=s)
    ids = jnp.asarray(np.random.default_rng(s).integers(1, cfg.vocab_size, (3, s)), jnp.int32)

    logits, new = fwd(cfg, params, ids, cache, return_all=True)
    monkeypatch.setattr(KC, "cache_step", _step_on_private_slices)
    want_logits, want = fwd(cfg, params, ids, cache, return_all=True)

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
    cfg, _, params = models("llama")
    model = Model(module=_family("llama")[1], params=params)
    rng = np.random.default_rng(7)
    # a repeating prompt, so that the n-gram draft has something to accept
    prompts = [np.tile(rng.integers(1, cfg.vocab_size, (3,), dtype=np.int32), 4)[:n]
               for n in (5, 11, 7)]

    def served():
        engine = ServingEngine(model, ServingConfig(
            n_slots=2, max_len=48, prefill_chunks=[4, 8], speculate_k=speculate_k))
        return engine.run(prompts, max_new_tokens=[6, 4, 8])

    got = served()
    monkeypatch.setattr(KC, "cache_step", _step_on_private_slices)
    want = served()
    for g, w, prompt in zip(got, want, prompts):
        assert len(g) > len(prompt)
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# The seam: what serving, disagg and the planner ask of the cache
# ---------------------------------------------------------------------------


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(params=["float", "int8"])
def slot_cache(request, models):
    """Three slots of 16 rows already holding values, each at its own length."""
    cfg = models("llama")[0]
    return _filled_cache(cfg, 3, 16, request.param == "int8", per_slot=True, seed=2)


def test_the_cache_describes_itself(slot_cache, models):
    cfg = models("llama")[0]
    spec = KC.cache_spec(cfg)
    assert slot_cache.n_layers == spec.layers and slot_cache.t_max == 16
    assert slot_cache.quantized == (slot_cache.dtype == jnp.int8)
    assert slot_cache.holds_nan == (not slot_cache.quantized)
    assert len(jax.tree.leaves(slot_cache)) == (5 if slot_cache.quantized else 3)


def test_a_slot_taken_out_and_put_back_leaves_every_other_slot_bit_equal(slot_cache):
    sub = slot_cache.take_slot(jnp.int32(1), slot_cache.length[1])
    assert sub.length.shape == (1,) and int(sub.length[0]) == 2
    for leaf, whole in zip(jax.tree.leaves((sub.k, sub.v)),
                           jax.tree.leaves((slot_cache.k, slot_cache.v))):
        np.testing.assert_array_equal(np.asarray(leaf[:, 0]), np.asarray(whole[:, 1]))
    _leaves_equal(slot_cache.put_slot(jnp.int32(1), sub), slot_cache)
    # another slot's rows put there: that slot changes, its neighbours and the lengths do not
    other = slot_cache.take_slot(jnp.int32(2), slot_cache.length[2])
    moved = slot_cache.put_slot(jnp.int32(1), other)
    np.testing.assert_array_equal(np.asarray(moved.length), np.asarray(slot_cache.length))
    for got, was in zip(jax.tree.leaves((moved.k, moved.v)),
                        jax.tree.leaves((slot_cache.k, slot_cache.v))):
        got, was = np.asarray(got), np.asarray(was)
        np.testing.assert_array_equal(got[:, [0, 2]], was[:, [0, 2]])
        np.testing.assert_array_equal(got[:, 1], was[:, 2])


def test_rows_extracted_and_inserted_at_another_slot_read_back_equal(slot_cache):
    lane = slot_cache.take_slot(jnp.int32(0), slot_cache.length[0])   # a one-slot cache
    k_rows, v_rows = lane.rows(jnp.int32(3), 4)
    assert jax.tree.leaves(k_rows)[0].shape[1:3] == (1, 4)
    got = slot_cache.insert_rows(k_rows, v_rows, jnp.int32(2), jnp.int32(6), jnp.int32(3))
    np.testing.assert_array_equal(np.asarray(got.length), [5, 2, 9])  # 6 + 3 valid rows
    for new, was in zip(jax.tree.leaves((got.k, got.v)),
                        jax.tree.leaves((slot_cache.k, slot_cache.v))):
        new, was = np.asarray(new), np.asarray(was)
        np.testing.assert_array_equal(new[:, 2, 6:10], was[:, 0, 3:7])
        np.testing.assert_array_equal(new[:, 2, :6], was[:, 2, :6])
        np.testing.assert_array_equal(new[:, 2, 10:], was[:, 2, 10:])
        np.testing.assert_array_equal(new[:, :2], was[:, :2])
    _leaves_equal(got.take_slot(jnp.int32(2), got.length[2]).rows(jnp.int32(6), 4),
                  (k_rows, v_rows))


def test_filling_a_slot_touches_that_slot_only(slot_cache):
    got = slot_cache.fill_slot(jnp.int32(1), 1)
    np.testing.assert_array_equal(np.asarray(got.length), np.asarray(slot_cache.length))
    for new, was in zip(jax.tree.leaves((got.k, got.v)),
                        jax.tree.leaves((slot_cache.k, slot_cache.v))):
        new, was = np.asarray(new), np.asarray(was)
        assert (new[:, 1] == 1).all()
        np.testing.assert_array_equal(new[:, [0, 2]], was[:, [0, 2]])


def test_the_batch_axis_tiles_and_reorders(slot_cache):
    tiled = slot_cache.take_batch(jnp.repeat(jnp.arange(3), 2))
    picked = tiled.take_batch(jnp.asarray([5, 0, 3]))
    for new, was in zip(jax.tree.leaves((picked.k, picked.v)),
                        jax.tree.leaves((slot_cache.k, slot_cache.v))):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(was)[:, [2, 0, 1]])


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16, jnp.int8], ids=["config", "bf16", "int8"])
@pytest.mark.parametrize("family", DECODER_ONLY + ENCODER_DECODER)
def test_bytes_per_token_is_what_the_cache_allocates(family, dtype):
    from accelerate_tpu.planner import kv_bytes_per_token

    cfg, _ = _family(family)
    cache = KC.init_slot_cache(cfg, 3, 8, dtype)
    allocated = sum(leaf.nbytes for leaf in jax.tree.leaves((cache.k, cache.v)))
    assert kv_bytes_per_token(cfg, dtype) * 3 * 8 == allocated


def _published_looped_config():
    """The benchmark's looped configuration as the program's config."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "ouro-2.6b.json")) as f:
        hf = json.load(f)
    from accelerate_tpu.models.hub import ouro_config_from_hf

    return ouro_config_from_hf(hf)


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_a_looped_model_s_token_takes_passes_x_layers_planes(size):
    """``2 (K, V) x 2 bytes x U x L x Hkv x D``, and the planner and the disagg
    router price a handoff with that number and no other."""
    from accelerate_tpu import disagg, planner

    cfg = _family("looped")[0] if size == "tiny" else _published_looped_config()
    spec = KC.cache_spec(cfg)
    assert spec.passes == cfg.total_ut_steps and spec.layers == spec.passes * cfg.num_hidden_layers
    want = 2 * 2 * cfg.total_ut_steps * cfg.num_hidden_layers * spec.kv_heads * spec.head_dim
    if size == "published":
        assert (spec.layers, want) == (192, 1_572_864)   # 1.5 MiB a token
    assert KC.kv_bytes_per_token(cfg, jnp.bfloat16) == want
    assert planner.kv_bytes_per_token(cfg, jnp.bfloat16) == want
    assert planner.BandwidthTable().kv_bytes_per_token(cfg, jnp.bfloat16) == want
    assert disagg.kv_bytes_per_token is planner.kv_bytes_per_token
    plan = planner.plan_disagg_slices(
        8, prefill_decode_flop_ratio=2.0, kv_bytes_per_token=want)
    assert plan.kv_bytes_per_token == want   # the number the priced handoff used
    assert plan.handoff_s_per_ktoken == pytest.approx(
        1000.0 * want / (plan.handoff_gbps * 1e9), rel=1e-4)
