"""Each family's arithmetic against hand-worked numbers, its leaves against the
program's module, and the program against the family's plain reference at a
tiny size."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, spec, weights
from chipbench.families import llama, mixtral


def _config(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


MISTRAL = _config("mistral-7b-v0.3-l16")
MIXTRAL = _config("mixtral-8x7b-v0.1-l3")
TINY = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32, vocab_size=256,
            rope_theta=1e6, rms_norm_eps=1e-5, hidden_act="silu", tie_word_embeddings=False,
            initializer_range=0.16, num_local_experts=4, num_experts_per_tok=2)

# attention: q 4096x4096, k and v 4096x1024 each, o 4096x4096
ATTN = 4096 * 4096 * 2 + 4096 * 1024 * 2
MLP = 3 * 4096 * 14336


def test_mistral_parameter_counts_by_hand():
    assert llama.layer_params(MISTRAL) == ATTN + MLP + 2 * 4096 == 218_112_000
    assert llama.total_params(MISTRAL) == 16 * 218_112_000 + 2 * 32768 * 4096 + 4096
    assert round(llama.total_params(MISTRAL) / 1e9, 3) == 3.758
    assert weights.n_params(llama.weight_specs(MISTRAL)) == llama.total_params(MISTRAL)


def test_mixtral_parameter_counts_by_hand():
    layer = ATTN + 4096 * 8 + 8 * MLP + 2 * 4096
    assert mixtral.layer_params(MIXTRAL) == layer == 1_451_270_144
    assert mixtral.total_params(MIXTRAL) == 3 * layer + 2 * 32000 * 4096 + 4096
    assert weights.n_params(mixtral.weight_specs(MIXTRAL)) == mixtral.total_params(MIXTRAL)
    # at the published depth of 4 in the issue's reckoning: 6.07B
    assert round((4 * layer + 2 * 32000 * 4096 + 4096) / 1e9, 2) == 6.07
    # two experts a token, not eight; every expert read by a full decode step
    assert mixtral.active_layer_matmul_params(MIXTRAL) == ATTN + 4096 * 8 + 2 * MLP
    assert mixtral.read_layer_matmul_params(MIXTRAL) == ATTN + 4096 * 8 + 8 * MLP


def test_request_flops_by_hand():
    # 100 prompt tokens, 10 outputs: 109 positions through the layers, which
    # attend 1 + 2 + ... + 109 = 5995 rows; the head for 10 positions.
    want = (4 * 16 * 32 * 128 * 5995 + 2 * 16 * (ATTN + MLP) * 109
            + 2 * 4096 * 32768 * 10)
    assert llama.request_flops(MISTRAL, 100, 10) == float(want)
    want = (4 * 3 * 32 * 128 * 5995 + 2 * 3 * (ATTN + 4096 * 8 + 2 * MLP) * 109
            + 2 * 4096 * 32000 * 10)
    assert mixtral.request_flops(MIXTRAL, 100, 10) == float(want)


def test_decode_step_bytes_by_hand():
    # a cache row: keys and values, 8 heads of 128, bf16 = 4 KiB a layer
    assert llama.decode_step_bytes(MISTRAL, 0) == 2.0 * (16 * (ATTN + MLP) + 4096 * 32768)
    assert (llama.decode_step_bytes(MISTRAL, 1000) - llama.decode_step_bytes(MISTRAL, 0)
            == 1000 * 16 * 4096)
    assert mixtral.decode_step_bytes(MIXTRAL, 10) == (
        2.0 * (3 * (ATTN + 4096 * 8 + 8 * MLP) + 4096 * 32000) + 10 * 3 * 4096)


@pytest.mark.parametrize("family", [llama, mixtral], ids=["llama", "mixtral"])
def test_leaves_are_the_module_s_and_come_from_the_seed(family):
    specs = family.weight_specs(TINY)
    w = weights.make_weights(specs, TINY["initializer_range"], 2**31 + 5)
    again = weights.make_weights(specs, TINY["initializer_range"], 2**31 + 5)
    other = weights.make_weights(specs, TINY["initializer_range"], 6)
    assert all(v.dtype == jnp.bfloat16 for v in w.values())
    assert all(np.array_equal(w[k], again[k]) for k in w)
    assert any(not np.array_equal(w[k], other[k]) for k in w)
    module = family.program_module(TINY, 64)
    want = jax.eval_shape(module.init, jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    assert jax.tree.map(lambda x: x.shape, want) == jax.tree.map(
        lambda x: x.shape, weights.nest(w))
    k = "model/layers/block/self_attn/q_proj/kernel"
    assert abs(float(np.std(np.asarray(w[k], np.float32))) - 0.16) < 0.01
    n = "model/norm/weight"
    assert abs(float(np.mean(np.asarray(w[n], np.float32))) - 1.0) < 0.05


@pytest.mark.parametrize("family", [llama, mixtral], ids=["llama", "mixtral"])
def test_program_s_cached_forward_equals_the_reference_in_float32(family):
    """The program's cached plan (what the engine's prefill and decode run) in
    float32 against the plain reference: the same mathematics, to rounding."""
    from accelerate_tpu.generation import GENERATION_PLANS, init_cache

    w = weights.make_weights(family.weight_specs(TINY), TINY["initializer_range"], 11)
    module = family.program_module(TINY, 64)
    cfg = dataclasses.replace(module.config, dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 256, size=(1, 48), dtype=np.int32)
    fwd = GENERATION_PLANS[type(module).__name__]
    with jax.default_matmul_precision("highest"):
        got, _ = fwd(cfg, weights.nest(w), ids, init_cache(cfg, 1, 64, dtype=jnp.float32),
                     return_all=True)
    want = jax.jit(lambda w, i: family.reference_logits(TINY, w, i))(w, ids[0])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("family", [llama, mixtral], ids=["llama", "mixtral"])
def test_gap_of_the_reference_s_own_greedy_token_is_nought(family):
    w = weights.make_weights(family.weight_specs(TINY), TINY["initializer_range"], 3)
    ids = np.random.default_rng(1).integers(0, 256, size=(32,), dtype=np.int32)
    ref = jax.jit(lambda w, i: family.reference_logits(TINY, w, i))
    # make every next token the reference's own first choice, position by position
    for t in range(31):
        ids[t + 1] = np.asarray(ref(w, ids))[t].argmax()
    ids2 = ids
    gap, _, low = check.build_gap_fn(family, TINY, "int8")(w, ids2)
    assert float(np.max(np.asarray(gap)[:31])) == 0.0
    assert float(np.max(np.asarray(low)[:31])) >= 0.0   # the control's choice lies at or below
