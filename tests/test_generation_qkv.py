"""The serving layout of the attention input projections
(``generation.fuse_qkv_params``): q, k and v of a stack as one ``(L, H, (Hq +
2·Hkv)·D)`` kernel, read by one dot whose output is cut in three. The fused
projections equal the three contractions they replace; the helper is
idempotent, leaves int8 decode kernels and split heads alone and keeps a
version's placement; an engine built from, swapped to or canaried with a tree
in the model's layout serves what batch-1 ``generate()`` gives and compiles
nothing new, holds no reference to the model's tree, and hands a caller's own
forward the model's layout. CPU, tiny models, float32."""

import gc
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from accelerate_tpu import Model, ServingConfig, ServingEngine, generate
from accelerate_tpu import models as M
from accelerate_tpu.generation import _llama_forward_cached, _qkv_proj, fuse_qkv_params
from accelerate_tpu.models.llama import rotary_embedding
from accelerate_tpu.utils import set_seed
from accelerate_tpu.utils.quantization import DecodeQuant, quantize_model_for_decode

H, D, L = 64, 16, 3


def _attention(hq, hkv, bias, seed=0):
    """One stacked attention dict in the model's layout, float32."""
    keys = iter(jax.random.split(jax.random.key(seed), 8))
    attn = {}
    for name, heads in (("q_proj", hq), ("k_proj", hkv), ("v_proj", hkv)):
        attn[name] = {"kernel": jax.random.normal(next(keys), (L, H, heads, D)) * 0.2}
        if bias:
            attn[name]["bias"] = jax.random.normal(next(keys), (L, heads, D))
    attn["o_proj"] = {"kernel": jax.random.normal(next(keys), (L, hq, D, H))}
    return attn


@pytest.mark.parametrize("rotary_dim", [None, D // 2], ids=["full_rotary", "partial_rotary"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)], ids=["mha", "gqa", "mqa"])
def test_the_fused_projection_equals_the_three(hq, hkv, bias, rotary_dim):
    attn = _attention(hq, hkv, bias)
    tree, was_fused = fuse_qkv_params({"self_attn": attn})
    fused = tree["self_attn"]
    assert was_fused and set(fused) == {"qkv_proj", "o_proj"}
    assert fused["qkv_proj"]["kernel"].shape == (L, H, (hq + 2 * hkv) * D)
    x = jax.random.normal(jax.random.key(9), (2, 5, H))
    cos, sin = rotary_embedding(jnp.arange(10).reshape(2, 5), rotary_dim or D, 10000.0, x.dtype)
    for layer in range(L):
        one = lambda tree: jax.tree.map(lambda a: a[layer], tree)
        want = _qkv_proj(one(attn), x, cos, sin, rotary_dim=rotary_dim)
        got = _qkv_proj(one(fused), x, cos, sin, rotary_dim=rotary_dim, heads=(hq, D))
        for w, g in zip(want, got):
            assert g.shape == w.shape
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)


def test_a_fused_kernel_without_heads_is_refused():
    fused = jax.tree.map(lambda a: a[0], fuse_qkv_params({"self_attn": _attention(4, 2, False)})[0])
    x = jax.random.normal(jax.random.key(9), (2, 5, H))
    with pytest.raises(ValueError, match="heads"):
        _qkv_proj(fused["self_attn"], x, None, None)


@pytest.fixture(scope="module")
def llama():
    set_seed(0)
    cfg = M.LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    return cfg, Model.from_flax(M.LlamaForCausalLM(cfg), jax.random.key(0),
                                np.ones((1, 4), np.int32))


def _attn_of(params):
    return params["model"]["layers"]["block"]["self_attn"]


@pytest.mark.parametrize("case", ["idempotent", "other_leaves_shared", "decode_quant_kept"])
def test_the_layout_helper(llama, case):
    cfg, model = llama
    fused, was_fused = fuse_qkv_params(model.params)
    assert was_fused and "q_proj" in _attn_of(model.params)
    if case == "idempotent":
        again, fused_again = fuse_qkv_params(fused)
        assert not fused_again and again is fused
        assert all(a is b for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(fused)))
    elif case == "other_leaves_shared":
        # every leaf but q, k and v is the same array: a version costs one copy of those
        assert _attn_of(fused)["o_proj"]["kernel"] is _attn_of(model.params)["o_proj"]["kernel"]
        assert fused["lm_head"]["kernel"] is model.params["lm_head"]["kernel"]
        assert set(_attn_of(fused)) == {"qkv_proj", "o_proj"}
    else:
        quantized = quantize_model_for_decode(model).params
        kept, fused_any = fuse_qkv_params(quantized)
        assert not fused_any and kept is quantized
        assert isinstance(_attn_of(kept)["q_proj"]["kernel"], DecodeQuant)
        assert all(a is b for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(quantized)))


@pytest.mark.parametrize("placement", ["second_device", "one_device_mesh", "heads_split"])
def test_the_layout_helper_keeps_the_version_s_placement(llama, placement):
    cfg, model = llama
    devices = jax.devices()
    if placement == "second_device":
        sharding = SingleDeviceSharding(devices[1])
        params = jax.device_put(model.params, sharding)
    elif placement == "one_device_mesh":
        sharding = NamedSharding(Mesh(np.asarray(devices[:1]), ("dp",)), PartitionSpec())
        params = jax.device_put(model.params, sharding)
    else:
        mesh = Mesh(np.asarray(devices[:2]), ("tp",))
        params = jax.device_put(model.params, NamedSharding(mesh, PartitionSpec()))
        heads = NamedSharding(mesh, PartitionSpec(None, None, "tp", None))
        attn = _attn_of(params)
        for name in ("q_proj", "k_proj", "v_proj"):
            attn[name]["kernel"] = jax.device_put(attn[name]["kernel"], heads)
    fused, was_fused = fuse_qkv_params(params)
    if placement == "heads_split":
        # a kernel split over its heads keeps the three leaves, and their placement
        assert not was_fused
        assert _attn_of(fused)["q_proj"]["kernel"].sharding == heads
        return
    kernel = _attn_of(fused)["qkv_proj"]["kernel"]
    assert kernel.committed and kernel.sharding.is_equivalent_to(sharding, kernel.ndim)
    assert kernel.sharding.device_set == {devices[1] if placement == "second_device"
                                          else devices[0]}


# -- the engine: built, swapped and canaried from trees in the model's layout --------

FAMILIES = {
    "llama": (M.LlamaConfig, M.LlamaForCausalLM, {"attention_impl": "native"}),
    "mixtral": (M.MixtralConfig, M.MixtralForCausalLM, {}),
    "looped": (M.LlamaConfig, M.LlamaForCausalLM,
               {"attention_impl": "native", "total_ut_steps": 3, "sandwich_norm": True,
                "early_exit_gate": True}),
}


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            set_seed(0)
            cfg_cls, module_cls, kw = FAMILIES[name]
            cfg = cfg_cls.tiny(dtype=jnp.float32, **kw)
            cache[name] = cfg, Model.from_flax(module_cls(cfg), jax.random.key(0),
                                               np.ones((1, 4), np.int32))
        return cache[name]

    return get


def _prompts(cfg, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32) for n in lengths]


def _engine(model, **config):
    return ServingEngine(model, ServingConfig(n_slots=3, max_len=64, prefill_chunks=[4, 8],
                                              **config))


def _serve(engine, prompts, budgets, max_ticks=400):
    """Submit R0 alone and the rest once R0 decodes, so their prompt chunks ride
    its decode steps; every row, by request."""
    ids = [engine.submit(prompts[0], max_new_tokens=budgets[0])]
    rows = {}
    for _ in range(max_ticks):
        if len(ids) == 1 and engine._decoding:
            ids += [engine.submit(p, max_new_tokens=b) for p, b in zip(prompts[1:], budgets[1:])]
        if len(ids) == len(prompts) and all(i in rows for i in ids):
            break
        engine.tick()
        rows.update((r["id"], r) for r in engine.poll())
    assert all(i in rows for i in ids), "requests did not drain"
    return [rows[i] for i in ids]


def _want(model, prompt, budget):
    return np.asarray(generate(model, prompt[None], max_new_tokens=budget))[0, prompt.size:]


@pytest.mark.parametrize("speculate", [0, 2], ids=["chunk_rides", "speculation"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_engine_serves_what_generate_gives(built, family, speculate):
    cfg, model = built(family)
    engine = _engine(model, speculate_k=speculate, speculate_ngram=8)
    assert engine.stats()["layout"] == {"qkv_fused": True}
    assert "q_proj" not in _attn_of(engine._params)
    prompts, budgets = _prompts(cfg, [6, 17, 3]), [9, 6, 4]
    rows = _serve(engine, prompts, budgets)
    if speculate == 0:
        assert engine.stats()["prefill_chunks_fused"] > 0
    for row, prompt, budget in zip(rows, prompts, budgets):
        assert row["status"] == "ok"
        np.testing.assert_array_equal(np.asarray(row["tokens"])[prompt.size:],
                                      _want(model, prompt, budget))


def _scaled(params, scale):
    return jax.tree.map(lambda a: a * scale, params)


@pytest.mark.parametrize("how", ["swap", "canary"])
def test_a_version_in_the_model_s_layout_takes_effect_and_compiles_nothing(llama, how):
    """Each version is installed in the engine's layout, so every version has one
    tree shape: the programs compiled for the first serve the next, and the
    install itself reuses its one compiled copy. A canary tick runs a chunk in
    a program of its own (two versions do not share a step), so version 1 is a
    canary rolled back first and version 2 the one measured."""
    cfg, model = llama
    engine = _engine(model)
    engine.warmup()
    prompts, budgets = _prompts(cfg, [6, 17, 3]), [5, 4, 3]
    _serve(engine, prompts, budgets)
    if how == "canary":
        engine.begin_canary(_scaled(model.params, 0.5), weights_version=1, fraction=1.0)
        _serve(engine, prompts, budgets)
        engine.rollback_canary()
    before = engine.executable_counts()
    new = _scaled(model.params, 1.5)  # committed, on the serving device, the model's layout
    compiles = []

    def note(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(note)
    try:
        if how == "swap":
            engine.swap_params(new, weights_version=2)
        else:
            engine.begin_canary(new, weights_version=2, fraction=1.0)
        rows = _serve(engine, prompts, budgets)
    finally:
        jax.monitoring.unregister_event_duration_listener(note)
    assert not compiles
    assert engine.executable_counts() == before
    assert "q_proj" not in _attn_of(engine._params_by_version[2])
    want = Model(module=model.module, params=new)
    for row, prompt, budget in zip(rows, prompts, budgets):
        assert row["weights_version"] == 2
        np.testing.assert_array_equal(np.asarray(row["tokens"])[prompt.size:],
                                      _want(want, prompt, budget))


def test_the_engine_holds_no_reference_to_the_model_s_tree():
    """The engine keeps q, k and v once, fused: a caller that lets the model go
    frees the model-layout kernels."""
    set_seed(0)
    cfg = M.LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    model = Model.from_flax(M.LlamaForCausalLM(cfg), jax.random.key(0),
                            np.ones((1, 4), np.int32))
    prompts, budgets = _prompts(cfg, [6, 17, 3]), [5, 4, 3]
    want = [_want(model, p, b) for p, b in zip(prompts, budgets)]
    engine = _engine(model)
    refs = [weakref.ref(model), weakref.ref(_attn_of(model.params)["q_proj"]["kernel"])]
    del model
    gc.collect()
    assert [ref() for ref in refs] == [None, None]
    for row, prompt, w in zip(_serve(engine, prompts, budgets), prompts, want):
        np.testing.assert_array_equal(np.asarray(row["tokens"])[prompt.size:], w)


def test_a_caller_s_own_forward_is_handed_the_model_s_layout(llama):
    """A forward the caller brings may read q_proj by name: the engine installs
    the fused layout for the built-in plans alone."""
    cfg, model = llama
    read = []

    def own(cfg, params, input_ids, cache, return_all=False, pad_offset=None, kv_valid=None):
        read.append(_attn_of(params)["q_proj"]["kernel"].shape)
        return _llama_forward_cached(cfg, params, input_ids, cache, return_all=return_all,
                                     pad_offset=pad_offset, kv_valid=kv_valid)

    engine = ServingEngine(model, ServingConfig(n_slots=3, max_len=64, prefill_chunks=[4, 8]),
                           forward_cached=own)
    assert engine.stats()["layout"] == {"qkv_fused": False}
    prompts, budgets = _prompts(cfg, [6, 17, 3]), [5, 4, 3]
    rows = _serve(engine, prompts, budgets)
    assert read
    new = _scaled(model.params, 1.5)
    engine.swap_params(new, weights_version=1)
    assert "q_proj" in _attn_of(engine._params)
    for row, prompt, budget in zip(rows, prompts, budgets):
        np.testing.assert_array_equal(np.asarray(row["tokens"])[prompt.size:],
                                      _want(model, prompt, budget))
