"""HF-checkpoint interop: converted weights reproduce transformers logits.

The reference wraps transformers models directly, so the switch-over story
for its users is "your checkpoints load here". Each test builds a tiny
randomly-initialized transformers model on CPU, converts its state dict with
models/hub.py, and asserts fp32 logit parity between the torch forward and
the native flax forward.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from accelerate_tpu import Model
from accelerate_tpu.models import load_pretrained, model_from_pretrained
from accelerate_tpu.models.hub import llama_params_from_hf, llama_params_to_hf


def _logits(hf_model, *args):
    hf_model.eval()
    with torch.no_grad():
        return hf_model(*[torch.from_numpy(np.asarray(a)) for a in args]).logits.numpy()


def _ids(rng, vocab, shape):
    return rng.integers(0, vocab, shape).astype(np.int32)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


def _convert(hf_model, **kw):
    return model_from_pretrained(hf_model, dtype=jnp.float32, **kw)


def test_llama_logit_parity(rng):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    ids = _ids(rng, 128, (2, 12))
    ours = _convert(hf)
    np.testing.assert_allclose(
        np.asarray(ours(ids)), _logits(hf, ids), rtol=2e-4, atol=2e-4
    )


def test_llama_roundtrip_to_hf(rng):
    """to_hf(from_hf(sd)) == sd exactly — export keeps reference-world layout."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, tie_word_embeddings=False,
    )
    torch.manual_seed(1)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    sd = {k: v.numpy() for k, v in hf.state_dict().items()}
    cfg, params, _ = load_pretrained(hf, dtype=jnp.float32)
    back = llama_params_to_hf(cfg, llama_params_from_hf(cfg, sd))
    for k, v in back.items():
        np.testing.assert_array_equal(v, sd[k], err_msg=k)


def _tiny_ouro_checkpoint(seed=0, **cfg_kw):
    """A config.json as published for the looped family and a state dict
    under its parameter names, made here: no such model is in transformers."""
    hf_cfg = dict(model_type="ouro", vocab_size=64, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
                  head_dim=8, max_position_embeddings=64, rms_norm_eps=1e-6,
                  rope_theta=1000000, rope_scaling=None, sliding_window=None,
                  use_sliding_window=False, tie_word_embeddings=False, total_ut_steps=3,
                  early_exit_threshold=1, **cfg_kw)
    rng = np.random.default_rng(seed)
    h, f, v = 32, 64, 64
    sd = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,),
          "lm_head.weight": (v, h), "model.early_exit_gate.weight": (1, h),
          "model.early_exit_gate.bias": (1,)}
    for i in range(2):
        p = f"model.layers.{i}."
        sd.update({p + f"self_attn.{n}_proj.weight": (h, h) for n in "qkvo"})
        sd.update({p + "mlp.gate_proj.weight": (f, h), p + "mlp.up_proj.weight": (f, h),
                   p + "mlp.down_proj.weight": (h, f)})
        sd.update({p + n + ".weight": (h,) for n in (
            "input_layernorm", "input_layernorm_2", "post_attention_layernorm",
            "post_attention_layernorm_2")})
    return hf_cfg, {k: rng.normal(size=shape).astype(np.float32) for k, shape in sd.items()}


def test_ouro_checkpoint_loads_roundtrips_and_runs():
    hf_cfg, sd = _tiny_ouro_checkpoint()
    cfg, params, cls = load_pretrained((hf_cfg, sd), dtype=jnp.float32)
    assert cls.__name__ == "LlamaForCausalLM"
    assert (cfg.total_ut_steps, cfg.sandwich_norm, cfg.early_exit_gate,
            cfg.early_exit_threshold) == (3, True, True, 1.0)
    block = params["model"]["layers"]["block"]
    assert block["input_layernorm_2"]["weight"].shape == (2, 32)
    assert block["post_attention_layernorm_2"]["weight"].shape == (2, 32)
    assert params["model"]["early_exit_gate"]["kernel"].shape == (32, 1)
    back = llama_params_to_hf(cfg, params)
    assert set(back) == set(sd)
    for k, v in back.items():
        np.testing.assert_array_equal(v, sd[k], err_msg=k)
    # the tree is the module's own: it runs, and all three passes count
    module = cls(cfg)
    ids = np.arange(12, dtype=np.int32)[None] % 64
    want = jax.eval_shape(module.init, jax.random.key(0), ids)["params"]
    assert jax.tree.map(lambda x: x.shape, want) == jax.tree.map(lambda x: x.shape, params)
    assert np.isfinite(np.asarray(module.apply({"params": params}, ids))).all()


@pytest.mark.parametrize("kw,error,match", [
    ({"early_exit_threshold": 0.9}, ValueError, "early_exit_threshold"),
    ({"rope_scaling": {"type": "linear", "factor": 2.0}}, NotImplementedError, "rope_scaling"),
    ({"use_sliding_window": True, "sliding_window": 4096}, NotImplementedError,
     "sliding_window"),
])
def test_ouro_config_refuses_what_is_not_computed(kw, error, match):
    hf_cfg, sd = _tiny_ouro_checkpoint()
    with pytest.raises(error, match=match):
        load_pretrained((dict(hf_cfg, **kw), sd), dtype=jnp.float32)


def test_gpt2_logit_parity(rng):
    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=64, n_layer=3, n_head=4,
    )
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(hf_cfg)
    ids = _ids(rng, 128, (2, 12))
    ours = _convert(hf)
    np.testing.assert_allclose(
        np.asarray(ours(ids)), _logits(hf, ids), rtol=2e-4, atol=2e-4
    )


def test_bert_logit_parity(rng):
    hf_cfg = transformers.BertConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
        intermediate_size=128, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, num_labels=3,
    )
    torch.manual_seed(0)
    hf = transformers.BertForSequenceClassification(hf_cfg)
    ids = _ids(rng, 128, (2, 12))
    mask = np.ones_like(ids)
    ours = _convert(hf)
    np.testing.assert_allclose(
        np.asarray(ours(ids, mask)), _logits(hf, ids, mask), rtol=2e-4, atol=2e-4
    )


def test_t5_logit_parity(rng):
    hf_cfg = transformers.T5Config(
        vocab_size=128, d_model=64, d_kv=16, d_ff=128, num_layers=2,
        num_decoder_layers=2, num_heads=4, relative_attention_num_buckets=8,
        relative_attention_max_distance=32, feed_forward_proj="relu",
        tie_word_embeddings=True, decoder_start_token_id=0,
    )
    torch.manual_seed(0)
    hf = transformers.T5ForConditionalGeneration(hf_cfg)
    ids = _ids(rng, 128, (2, 10))
    dec = _ids(rng, 128, (2, 7))
    ours = _convert(hf)
    hf.eval()
    with torch.no_grad():
        want = hf(
            input_ids=torch.from_numpy(ids.astype(np.int64)),
            decoder_input_ids=torch.from_numpy(dec.astype(np.int64)),
        ).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours(ids, dec)), want, rtol=2e-4, atol=2e-4)


def test_mixtral_logit_parity(rng):
    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, num_local_experts=4,
        num_experts_per_tok=2, max_position_embeddings=64,
    )
    torch.manual_seed(0)
    hf = transformers.MixtralForCausalLM(hf_cfg)
    ids = _ids(rng, 128, (1, 8))
    cfg, params, cls = load_pretrained(hf, dtype=jnp.float32)
    # Capacity must cover every routed token or GShard dispatch drops some and
    # parity with HF's dropless top-k breaks.
    import dataclasses

    cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_local_experts))
    ours = Model(module=cls(cfg), params=params)
    np.testing.assert_allclose(
        np.asarray(ours(ids)), _logits(hf, ids), rtol=5e-4, atol=5e-4
    )


def test_load_pretrained_from_directory(tmp_path, rng):
    """config.json + model.safetensors on disk — the checkpoint-dir path."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, tie_word_embeddings=False,
    )
    torch.manual_seed(2)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    hf.save_pretrained(tmp_path, safe_serialization=True)
    ours = model_from_pretrained(str(tmp_path), dtype=jnp.float32)
    ids = _ids(rng, 64, (2, 8))
    np.testing.assert_allclose(
        np.asarray(ours(ids)), _logits(hf, ids), rtol=2e-4, atol=2e-4
    )


def test_unknown_family_raises():
    with pytest.raises(ValueError, match="Unsupported model family"):
        load_pretrained(({"model_type": "umbrellanet"}, {}))


def test_mistral_logit_parity(rng):
    """model_type 'mistral' routes through the Llama family (GQA, no sliding
    window at these lengths)."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        sliding_window=None,
    )
    torch.manual_seed(0)
    hf = transformers.MistralForCausalLM(hf_cfg)
    ids = _ids(rng, 128, (2, 10))
    ours = _convert(hf)
    np.testing.assert_allclose(
        np.asarray(ours(ids)), _logits(hf, ids), rtol=2e-4, atol=2e-4
    )


def test_phi3_logit_parity(rng):
    """model_type 'phi3' routes through the Llama family after splitting the
    fused qkv_proj / gate_up_proj weights."""
    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        pad_token_id=0,
    )
    torch.manual_seed(0)
    hf = transformers.Phi3ForCausalLM(hf_cfg)
    ids = _ids(rng, 128, (2, 10))
    ours = _convert(hf)
    np.testing.assert_allclose(
        np.asarray(ours(ids)), _logits(hf, ids), rtol=2e-4, atol=2e-4
    )


def test_phi3_longrope_rejected(rng):
    """Phi-3-128k-style rope_scaling must fail loudly, not convert wrong."""
    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        original_max_position_embeddings=32, pad_token_id=0,
        rope_scaling={
            "type": "longrope",
            "short_factor": [1.0] * 8,
            "long_factor": [2.0] * 8,
        },
    )
    torch.manual_seed(0)
    hf = transformers.Phi3ForCausalLM(hf_cfg)
    with pytest.raises(ValueError, match="longrope"):
        _convert(hf)


def test_qwen2_logit_parity_attention_bias(rng):
    """Qwen2 = Llama architecture + q/k/v biases: conversion must carry them."""
    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = transformers.Qwen2ForCausalLM(hf_cfg)
    cfg, params, cls = __import__("accelerate_tpu.models", fromlist=["load_pretrained"]).load_pretrained(
        hf, dtype=jnp.float32
    )
    assert cfg.attention_bias, "Qwen2 conversion must enable attention_bias"
    assert "bias" in params["model"]["layers"]["block"]["self_attn"]["q_proj"]
    ids = _ids(rng, 128, (2, 10))
    got = np.asarray(Model(module=cls(cfg), params=params)(ids))
    np.testing.assert_allclose(got, _logits(hf, ids), rtol=2e-4, atol=2e-4)


def test_qwen2_generates_like_transformers(rng):
    from accelerate_tpu import generate

    hf_cfg = transformers.Qwen2Config(
        vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=False,
    )
    torch.manual_seed(1)
    hf = transformers.Qwen2ForCausalLM(hf_cfg)
    hf.eval()
    ids = rng.integers(0, 96, (1, 6)).astype(np.int64)
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(ids), max_new_tokens=5, do_sample=False, pad_token_id=0
        ).numpy()
    ours = model_from_pretrained(hf, dtype=jnp.float32)
    got = generate(ours, ids.astype(np.int32), max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))


def test_gemma_logit_parity(rng):
    """Gemma quirks: GeGLU, RMSNorm(1+w), sqrt(hidden)-scaled embeddings,
    head_dim decoupled from hidden/heads, tied head."""
    hf_cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=64,
    )
    torch.manual_seed(0)
    hf = transformers.GemmaForCausalLM(hf_cfg)
    ids = _ids(rng, 128, (2, 10))
    ours = _convert(hf)
    assert ours.module.config.rms_norm_plus_one and ours.module.config.scale_embeddings
    np.testing.assert_allclose(
        np.asarray(ours(ids)), _logits(hf, ids), rtol=3e-4, atol=3e-4
    )


def test_gemma_generates_like_transformers(rng):
    from accelerate_tpu import generate

    hf_cfg = transformers.GemmaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        max_position_embeddings=64,
    )
    torch.manual_seed(2)
    hf = transformers.GemmaForCausalLM(hf_cfg)
    hf.eval()
    ids = rng.integers(1, 96, (1, 6)).astype(np.int64)
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(ids), max_new_tokens=5, do_sample=False, pad_token_id=0
        ).numpy()
    ours = model_from_pretrained(hf, dtype=jnp.float32)
    got = generate(ours, ids.astype(np.int32), max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))
