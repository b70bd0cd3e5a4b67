"""Hugging Face checkpoint interop: load reference-world weights natively.

The reference is a wrapper around user torch modules, so "model support" means
transformers checkpoints. For a reference user to switch here, the same
checkpoints must load into the native flax families — this module owns the
name/layout mapping (reference big-model load path for comparison:
utils/modeling.py:1805-2065 ``load_checkpoint_in_model``; here the mapping is
architectural, torch ``(out, in)`` linear layout → flax ``(in, out)`` kernels,
per-head reshapes for the fused DenseGeneral projections, layer stacking for
the ``nn.scan`` layout).

Two directions per family:

- ``*_params_from_hf(cfg, state_dict)`` — HF name→tensor dict (numpy or torch)
  → our param pytree, ready for ``Model(module=..., params=...)``.
- ``*_params_to_hf(cfg, params)`` — the inverse, for exporting checkpoints a
  reference/transformers user can load back.

``load_pretrained(src)`` is the high-level entry: src is a transformers model
instance, a local checkpoint directory (config.json + *.safetensors /
pytorch_model.bin), or a (config, state_dict) pair; the family is picked from
``model_type`` and both config and weights are converted.

Logit parity with transformers is asserted in tests/test_hub.py for every
family (fp32, tiny configs).
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np


def _np(t) -> np.ndarray:
    """Accept torch tensors / np arrays / anything array-like."""
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t)


def _t(t) -> np.ndarray:
    return _np(t).T


def _set(tree: dict, path: str, value: np.ndarray) -> None:
    parts = path.split("/")
    node = tree
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value


def _get(tree: dict, path: str) -> np.ndarray:
    node = tree
    for p in path.split("/"):
        node = node[p]
    return np.asarray(node)


def _stack_layers(per_layer: list[dict]) -> dict:
    """[{path: arr} per layer] → {path: stacked arr} (the nn.scan layout)."""
    out = {}
    for key in per_layer[0]:
        out[key] = np.stack([layer[key] for layer in per_layer], axis=0)
    return out


def _place_layers(tree, stacked: dict, scan_layers: bool, scan_prefix: str,
                  unscanned_prefix_fmt: str, n_layers: int) -> None:
    if scan_layers:
        for path, arr in stacked.items():
            _set(tree, f"{scan_prefix}/{path}", arr)
    else:
        for path, arr in stacked.items():
            for i in range(n_layers):
                _set(tree, unscanned_prefix_fmt.format(i=i) + "/" + path, arr[i])


def _collect_layers(params, scan_layers: bool, scan_prefix: str,
                    unscanned_prefix_fmt: str, n_layers: int, paths: list[str]) -> list[dict]:
    """Inverse of _place_layers: per-layer dicts of {path: arr}."""
    layers = []
    for i in range(n_layers):
        layer = {}
        for path in paths:
            if scan_layers:
                layer[path] = _get(params, f"{scan_prefix}/{path}")[i]
            else:
                layer[path] = _get(params, unscanned_prefix_fmt.format(i=i) + "/" + path)
        layers.append(layer)
    return layers


# ---------------------------------------------------------------------------
# Llama
# ---------------------------------------------------------------------------

def llama_config_from_hf(hf: Any) -> "LlamaConfig":
    from .llama import LlamaConfig

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return LlamaConfig(
        vocab_size=g("vocab_size"),
        hidden_size=g("hidden_size"),
        intermediate_size=g("intermediate_size"),
        num_hidden_layers=g("num_hidden_layers"),
        num_attention_heads=g("num_attention_heads"),
        num_key_value_heads=g("num_key_value_heads") or g("num_attention_heads"),
        head_dim=g("head_dim"),
        max_position_embeddings=g("max_position_embeddings", 4096),
        rms_norm_eps=g("rms_norm_eps", 1e-5),
        rope_theta=g("rope_theta", 10000.0),
        tie_word_embeddings=bool(g("tie_word_embeddings", False)),
        # Qwen2 always carries q/k/v biases; Llama/Mistral expose the flag.
        attention_bias=bool(
            g("attention_bias", g("model_type") == "qwen2")
        ),
    )


_SANDWICH_NORMS = ("input_layernorm_2", "post_attention_layernorm_2")


def llama_params_from_hf(cfg, sd: dict) -> dict:
    h, nh, nkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    tree: dict = {"model": {}}
    _set(tree, "model/embed_tokens/embedding", _np(sd["model.embed_tokens.weight"]))
    _set(tree, "model/norm/weight", _np(sd["model.norm.weight"]))
    if not cfg.tie_word_embeddings:
        _set(tree, "lm_head/kernel", _t(sd["lm_head.weight"]))
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        layers.append({
            "self_attn/q_proj/kernel": _t(sd[p + "self_attn.q_proj.weight"]).reshape(h, nh, d),
            "self_attn/k_proj/kernel": _t(sd[p + "self_attn.k_proj.weight"]).reshape(h, nkv, d),
            "self_attn/v_proj/kernel": _t(sd[p + "self_attn.v_proj.weight"]).reshape(h, nkv, d),
            "self_attn/o_proj/kernel": _t(sd[p + "self_attn.o_proj.weight"]).reshape(nh, d, h),
            "mlp/gate_proj/kernel": _t(sd[p + "mlp.gate_proj.weight"]),
            "mlp/up_proj/kernel": _t(sd[p + "mlp.up_proj.weight"]),
            "mlp/down_proj/kernel": _t(sd[p + "mlp.down_proj.weight"]),
            "input_layernorm/weight": _np(sd[p + "input_layernorm.weight"]),
            "post_attention_layernorm/weight": _np(sd[p + "post_attention_layernorm.weight"]),
            **({
                "self_attn/q_proj/bias": _np(sd[p + "self_attn.q_proj.bias"]).reshape(nh, d),
                "self_attn/k_proj/bias": _np(sd[p + "self_attn.k_proj.bias"]).reshape(nkv, d),
                "self_attn/v_proj/bias": _np(sd[p + "self_attn.v_proj.bias"]).reshape(nkv, d),
            } if cfg.attention_bias else {}),
            **({
                f"{name}/weight": _np(sd[f"{p}{name}.weight"]) for name in _SANDWICH_NORMS
            } if cfg.sandwich_norm else {}),
        })
    _place_layers(tree, _stack_layers(layers), cfg.scan_layers,
                  "model/layers/block", "model/layers_{i}", cfg.num_hidden_layers)
    if cfg.early_exit_gate:
        _set(tree, "model/early_exit_gate/kernel", _t(sd["model.early_exit_gate.weight"]))
        _set(tree, "model/early_exit_gate/bias", _np(sd["model.early_exit_gate.bias"]))
    return tree


def llama_params_to_hf(cfg, params) -> dict:
    h, nh, nkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    sd = {
        "model.embed_tokens.weight": _get(params, "model/embed_tokens/embedding"),
        "model.norm.weight": _get(params, "model/norm/weight"),
    }
    if not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = _get(params, "lm_head/kernel").T
    paths = [
        "self_attn/q_proj/kernel", "self_attn/k_proj/kernel", "self_attn/v_proj/kernel",
        "self_attn/o_proj/kernel", "mlp/gate_proj/kernel", "mlp/up_proj/kernel",
        "mlp/down_proj/kernel", "input_layernorm/weight", "post_attention_layernorm/weight",
    ]
    if cfg.sandwich_norm:
        paths += [f"{name}/weight" for name in _SANDWICH_NORMS]
    if cfg.early_exit_gate:
        sd["model.early_exit_gate.weight"] = _get(params, "model/early_exit_gate/kernel").T
        sd["model.early_exit_gate.bias"] = _get(params, "model/early_exit_gate/bias")
    for i, layer in enumerate(_collect_layers(
        params, cfg.scan_layers, "model/layers/block", "model/layers_{i}",
        cfg.num_hidden_layers, paths,
    )):
        p = f"model.layers.{i}."
        sd[p + "self_attn.q_proj.weight"] = layer["self_attn/q_proj/kernel"].reshape(h, nh * d).T
        sd[p + "self_attn.k_proj.weight"] = layer["self_attn/k_proj/kernel"].reshape(h, nkv * d).T
        sd[p + "self_attn.v_proj.weight"] = layer["self_attn/v_proj/kernel"].reshape(h, nkv * d).T
        sd[p + "self_attn.o_proj.weight"] = layer["self_attn/o_proj/kernel"].reshape(nh * d, h).T
        sd[p + "mlp.gate_proj.weight"] = layer["mlp/gate_proj/kernel"].T
        sd[p + "mlp.up_proj.weight"] = layer["mlp/up_proj/kernel"].T
        sd[p + "mlp.down_proj.weight"] = layer["mlp/down_proj/kernel"].T
        sd[p + "input_layernorm.weight"] = layer["input_layernorm/weight"]
        sd[p + "post_attention_layernorm.weight"] = layer["post_attention_layernorm/weight"]
        if cfg.sandwich_norm:
            for name in _SANDWICH_NORMS:
                sd[f"{p}{name}.weight"] = layer[f"{name}/weight"]
    return {k: np.asarray(v) for k, v in sd.items()}


def gemma_config_from_hf(hf: Any) -> "LlamaConfig":
    """Gemma rides the Llama family with three quirks: GeGLU MLP, RMSNorm
    scales stored as (weight + 1), embeddings scaled by sqrt(hidden)."""
    import dataclasses as _dc

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    cfg = llama_config_from_hf(hf)
    return _dc.replace(
        cfg,
        head_dim=g("head_dim", 256),
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        rms_norm_plus_one=True,
        scale_embeddings=True,
    )


def ouro_config_from_hf(hf: Any) -> "LlamaConfig":
    """Ouro (looped LM) rides the Llama family: the stack run
    ``total_ut_steps`` times over one set of weights, sandwich norms
    (``input_layernorm_2``, ``post_attention_layernorm_2``) and one exit gate
    (``early_exit_gate``). ``early_exit_threshold`` under 1 is refused by
    ``LlamaConfig`` itself; a window or a rope scaling is refused here, since
    neither is computed."""
    import dataclasses as _dc

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    if g("rope_scaling") is not None or (g("use_sliding_window") and g("sliding_window")):
        raise NotImplementedError(
            "ouro: rope_scaling and sliding_window are not computed; refusing rather than "
            f"dropping them (rope_scaling={g('rope_scaling')!r}, "
            f"sliding_window={g('sliding_window')!r})")
    return _dc.replace(
        llama_config_from_hf(hf),
        total_ut_steps=int(g("total_ut_steps", 1)),
        sandwich_norm=True,
        early_exit_gate=True,
        early_exit_threshold=float(g("early_exit_threshold", 1.0)),
    )


# ---------------------------------------------------------------------------
# Mixtral (Llama attention + sparse MoE MLP)
# ---------------------------------------------------------------------------

def mixtral_config_from_hf(hf: Any) -> "MixtralConfig":
    from .moe import MixtralConfig

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return MixtralConfig(
        vocab_size=g("vocab_size"),
        hidden_size=g("hidden_size"),
        intermediate_size=g("intermediate_size"),
        num_hidden_layers=g("num_hidden_layers"),
        num_attention_heads=g("num_attention_heads"),
        num_key_value_heads=g("num_key_value_heads") or g("num_attention_heads"),
        max_position_embeddings=g("max_position_embeddings", 4096),
        rms_norm_eps=g("rms_norm_eps", 1e-5),
        rope_theta=g("rope_theta", 10000.0),
        num_local_experts=g("num_local_experts", 8),
        num_experts_per_tok=g("num_experts_per_tok", 2),
        router_aux_loss_coef=g("router_aux_loss_coef", 0.02),
    )


def mixtral_params_from_hf(cfg, sd: dict) -> dict:
    h, nh, nkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    E = cfg.num_local_experts
    tree: dict = {"model": {}}
    _set(tree, "model/embed_tokens/embedding", _np(sd["model.embed_tokens.weight"]))
    _set(tree, "model/norm/weight", _np(sd["model.norm.weight"]))
    _set(tree, "lm_head/kernel", _t(sd["lm_head.weight"]))
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        m = p + "block_sparse_moe."
        layers.append({
            "self_attn/q_proj/kernel": _t(sd[p + "self_attn.q_proj.weight"]).reshape(h, nh, d),
            "self_attn/k_proj/kernel": _t(sd[p + "self_attn.k_proj.weight"]).reshape(h, nkv, d),
            "self_attn/v_proj/kernel": _t(sd[p + "self_attn.v_proj.weight"]).reshape(h, nkv, d),
            "self_attn/o_proj/kernel": _t(sd[p + "self_attn.o_proj.weight"]).reshape(nh, d, h),
            "input_layernorm/weight": _np(sd[p + "input_layernorm.weight"]),
            "post_attention_layernorm/weight": _np(sd[p + "post_attention_layernorm.weight"]),
            "moe/router": _t(sd[m + "gate.weight"]),
            # HF experts: w1=gate (f,h), w3=up (f,h), w2=down (h,f); ours are
            # stacked (E, in, out).
            "moe/w_gate": np.stack([_t(sd[m + f"experts.{e}.w1.weight"]) for e in range(E)]),
            "moe/w_up": np.stack([_t(sd[m + f"experts.{e}.w3.weight"]) for e in range(E)]),
            "moe/w_down": np.stack([_t(sd[m + f"experts.{e}.w2.weight"]) for e in range(E)]),
        })
    _place_layers(tree, _stack_layers(layers), cfg.scan_layers,
                  "model/layers/block", "model/layers_{i}", cfg.num_hidden_layers)
    return tree


# ---------------------------------------------------------------------------
# GPT-2
# ---------------------------------------------------------------------------

def gpt2_config_from_hf(hf: Any) -> "GPT2Config":
    from .gpt2 import GPT2Config

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return GPT2Config(
        vocab_size=g("vocab_size"),
        n_positions=g("n_positions", 1024),
        n_embd=g("n_embd", 768),
        n_layer=g("n_layer", 12),
        n_head=g("n_head", 12),
        layer_norm_epsilon=g("layer_norm_epsilon", 1e-5),
    )


def gpt2_params_from_hf(cfg, sd: dict) -> dict:
    h, nh, d = cfg.n_embd, cfg.n_head, cfg.head_dim
    # transformers GPT2Model state dicts may or may not carry the
    # "transformer." prefix depending on the head class.
    pref = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
    tree: dict = {"transformer": {}}
    _set(tree, "transformer/wte/embedding", _np(sd[pref + "wte.weight"]))
    _set(tree, "transformer/wpe/embedding", _np(sd[pref + "wpe.weight"]))
    _set(tree, "transformer/ln_f/scale", _np(sd[pref + "ln_f.weight"]))
    _set(tree, "transformer/ln_f/bias", _np(sd[pref + "ln_f.bias"]))
    layers = []
    for i in range(cfg.n_layer):
        p = f"{pref}h.{i}."
        # GPT-2 Conv1D stores weights (in, out) — already the flax kernel
        # layout, no transpose.
        layers.append({
            "ln_1/scale": _np(sd[p + "ln_1.weight"]),
            "ln_1/bias": _np(sd[p + "ln_1.bias"]),
            "attn/c_attn/kernel": _np(sd[p + "attn.c_attn.weight"]).reshape(h, 3, nh, d),
            "attn/c_attn/bias": _np(sd[p + "attn.c_attn.bias"]).reshape(3, nh, d),
            "attn/c_proj/kernel": _np(sd[p + "attn.c_proj.weight"]).reshape(nh, d, h),
            "attn/c_proj/bias": _np(sd[p + "attn.c_proj.bias"]),
            "ln_2/scale": _np(sd[p + "ln_2.weight"]),
            "ln_2/bias": _np(sd[p + "ln_2.bias"]),
            "c_fc/kernel": _np(sd[p + "mlp.c_fc.weight"]),
            "c_fc/bias": _np(sd[p + "mlp.c_fc.bias"]),
            "c_proj/kernel": _np(sd[p + "mlp.c_proj.weight"]),
            "c_proj/bias": _np(sd[p + "mlp.c_proj.bias"]),
        })
    _place_layers(tree, _stack_layers(layers), cfg.scan_layers,
                  "transformer/h/block", "transformer/h_{i}", cfg.n_layer)
    return tree


# ---------------------------------------------------------------------------
# BERT
# ---------------------------------------------------------------------------

def bert_config_from_hf(hf: Any, num_labels: int = 2) -> "BertConfig":
    from .bert import BertConfig

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return BertConfig(
        vocab_size=g("vocab_size"),
        hidden_size=g("hidden_size"),
        num_hidden_layers=g("num_hidden_layers"),
        num_attention_heads=g("num_attention_heads"),
        intermediate_size=g("intermediate_size"),
        max_position_embeddings=g("max_position_embeddings", 512),
        type_vocab_size=g("type_vocab_size", 2),
        layer_norm_eps=g("layer_norm_eps", 1e-12),
        hidden_dropout_prob=g("hidden_dropout_prob", 0.1),
        num_labels=g("num_labels", num_labels),
    )


def bert_params_from_hf(cfg, sd: dict) -> dict:
    h, nh, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    pref = "bert." if any(k.startswith("bert.") for k in sd) else ""
    e = pref + "embeddings."
    tree: dict = {"bert": {}}
    _set(tree, "bert/word_embeddings/embedding", _np(sd[e + "word_embeddings.weight"]))
    _set(tree, "bert/position_embeddings/embedding", _np(sd[e + "position_embeddings.weight"]))
    _set(tree, "bert/token_type_embeddings/embedding", _np(sd[e + "token_type_embeddings.weight"]))
    _set(tree, "bert/embeddings_norm/scale", _np(sd[e + "LayerNorm.weight"]))
    _set(tree, "bert/embeddings_norm/bias", _np(sd[e + "LayerNorm.bias"]))
    if pref + "pooler.dense.weight" in sd:
        _set(tree, "bert/pooler/kernel", _t(sd[pref + "pooler.dense.weight"]))
        _set(tree, "bert/pooler/bias", _np(sd[pref + "pooler.dense.bias"]))
    if "classifier.weight" in sd:
        _set(tree, "classifier/kernel", _t(sd["classifier.weight"]))
        _set(tree, "classifier/bias", _np(sd["classifier.bias"]))
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"{pref}encoder.layer.{i}."
        layers.append({
            "attention/query/kernel": _t(sd[p + "attention.self.query.weight"]).reshape(h, nh, d),
            "attention/query/bias": _np(sd[p + "attention.self.query.bias"]).reshape(nh, d),
            "attention/key/kernel": _t(sd[p + "attention.self.key.weight"]).reshape(h, nh, d),
            "attention/key/bias": _np(sd[p + "attention.self.key.bias"]).reshape(nh, d),
            "attention/value/kernel": _t(sd[p + "attention.self.value.weight"]).reshape(h, nh, d),
            "attention/value/bias": _np(sd[p + "attention.self.value.bias"]).reshape(nh, d),
            "attention/output/kernel": _t(sd[p + "attention.output.dense.weight"]).reshape(nh, d, h),
            "attention/output/bias": _np(sd[p + "attention.output.dense.bias"]),
            "attention_norm/scale": _np(sd[p + "attention.output.LayerNorm.weight"]),
            "attention_norm/bias": _np(sd[p + "attention.output.LayerNorm.bias"]),
            "intermediate/kernel": _t(sd[p + "intermediate.dense.weight"]),
            "intermediate/bias": _np(sd[p + "intermediate.dense.bias"]),
            "output/kernel": _t(sd[p + "output.dense.weight"]),
            "output/bias": _np(sd[p + "output.dense.bias"]),
            "output_norm/scale": _np(sd[p + "output.LayerNorm.weight"]),
            "output_norm/bias": _np(sd[p + "output.LayerNorm.bias"]),
        })
    _place_layers(tree, _stack_layers(layers), cfg.scan_layers,
                  "bert/layers/block", "bert/layer_{i}", cfg.num_hidden_layers)
    return tree


# ---------------------------------------------------------------------------
# Whisper
# ---------------------------------------------------------------------------

def whisper_config_from_hf(hf: Any) -> "WhisperConfig":
    from .whisper import WhisperConfig

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return WhisperConfig(
        vocab_size=g("vocab_size"),
        num_mel_bins=g("num_mel_bins", 80),
        d_model=g("d_model"),
        encoder_layers=g("encoder_layers"),
        decoder_layers=g("decoder_layers"),
        encoder_attention_heads=g("encoder_attention_heads"),
        decoder_attention_heads=g("decoder_attention_heads"),
        encoder_ffn_dim=g("encoder_ffn_dim"),
        decoder_ffn_dim=g("decoder_ffn_dim"),
        max_source_positions=g("max_source_positions", 1500),
        max_target_positions=g("max_target_positions", 448),
    )


def _whisper_attn(sd, p, dm, nh, d) -> dict:
    out = {
        "q_proj/kernel": _t(sd[p + "q_proj.weight"]).reshape(dm, nh, d),
        "q_proj/bias": _np(sd[p + "q_proj.bias"]).reshape(nh, d),
        "k_proj/kernel": _t(sd[p + "k_proj.weight"]).reshape(dm, nh, d),  # no bias
        "v_proj/kernel": _t(sd[p + "v_proj.weight"]).reshape(dm, nh, d),
        "v_proj/bias": _np(sd[p + "v_proj.bias"]).reshape(nh, d),
        "out_proj/kernel": _t(sd[p + "out_proj.weight"]).reshape(nh, d, dm),
        "out_proj/bias": _np(sd[p + "out_proj.bias"]),
    }
    return out


def whisper_params_from_hf(cfg, sd: dict) -> dict:
    dm = cfg.d_model
    pref = "model." if any(k.startswith("model.") for k in sd) else ""
    tree: dict = {"encoder": {}, "decoder": {}}
    e = pref + "encoder."
    # torch Conv1d (out, in, k) → flax (k, in, out).
    _set(tree, "encoder/conv1/kernel", _np(sd[e + "conv1.weight"]).transpose(2, 1, 0))
    _set(tree, "encoder/conv1/bias", _np(sd[e + "conv1.bias"]))
    _set(tree, "encoder/conv2/kernel", _np(sd[e + "conv2.weight"]).transpose(2, 1, 0))
    _set(tree, "encoder/conv2/bias", _np(sd[e + "conv2.bias"]))
    _set(tree, "encoder/embed_positions", _np(sd[e + "embed_positions.weight"]))
    _set(tree, "encoder/layer_norm/scale", _np(sd[e + "layer_norm.weight"]))
    _set(tree, "encoder/layer_norm/bias", _np(sd[e + "layer_norm.bias"]))
    d_ = pref + "decoder."
    _set(tree, "decoder/embed_tokens/embedding", _np(sd[d_ + "embed_tokens.weight"]))
    _set(tree, "decoder/embed_positions/embedding", _np(sd[d_ + "embed_positions.weight"]))
    _set(tree, "decoder/layer_norm/scale", _np(sd[d_ + "layer_norm.weight"]))
    _set(tree, "decoder/layer_norm/bias", _np(sd[d_ + "layer_norm.bias"]))

    def _block(p, cross: bool) -> dict:
        # Encoder and decoder stacks may differ in head count; reshape each
        # with ITS heads (review finding: encoder dims were used for both).
        nh, d = (
            (cfg.decoder_attention_heads, cfg.decoder_head_dim)
            if cross else (cfg.encoder_attention_heads, cfg.head_dim)
        )
        layer = {}
        for k, v in _whisper_attn(sd, p + "self_attn.", dm, nh, d).items():
            layer[f"self_attn/{k}"] = v
        layer["self_attn_layer_norm/scale"] = _np(sd[p + "self_attn_layer_norm.weight"])
        layer["self_attn_layer_norm/bias"] = _np(sd[p + "self_attn_layer_norm.bias"])
        if cross:
            for k, v in _whisper_attn(sd, p + "encoder_attn.", dm, nh, d).items():
                layer[f"encoder_attn/{k}"] = v
            layer["encoder_attn_layer_norm/scale"] = _np(sd[p + "encoder_attn_layer_norm.weight"])
            layer["encoder_attn_layer_norm/bias"] = _np(sd[p + "encoder_attn_layer_norm.bias"])
        layer["fc1/kernel"] = _t(sd[p + "fc1.weight"])
        layer["fc1/bias"] = _np(sd[p + "fc1.bias"])
        layer["fc2/kernel"] = _t(sd[p + "fc2.weight"])
        layer["fc2/bias"] = _np(sd[p + "fc2.bias"])
        layer["final_layer_norm/scale"] = _np(sd[p + "final_layer_norm.weight"])
        layer["final_layer_norm/bias"] = _np(sd[p + "final_layer_norm.bias"])
        return layer

    enc_layers = [_block(f"{e}layers.{i}.", False) for i in range(cfg.encoder_layers)]
    dec_layers = [_block(f"{d_}layers.{i}.", True) for i in range(cfg.decoder_layers)]
    _place_layers(tree["encoder"], _stack_layers(enc_layers), cfg.scan_layers,
                  "layers/block", "layer_{i}", cfg.encoder_layers)
    _place_layers(tree["decoder"], _stack_layers(dec_layers), cfg.scan_layers,
                  "layers/block", "layer_{i}", cfg.decoder_layers)
    return tree


# ---------------------------------------------------------------------------
# GPT-NeoX
# ---------------------------------------------------------------------------

def neox_config_from_hf(hf: Any) -> "GPTNeoXConfig":
    from .neox import GPTNeoXConfig

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return GPTNeoXConfig(
        vocab_size=g("vocab_size"),
        hidden_size=g("hidden_size"),
        num_hidden_layers=g("num_hidden_layers"),
        num_attention_heads=g("num_attention_heads"),
        intermediate_size=g("intermediate_size"),
        rotary_pct=g("rotary_pct", 0.25),
        rotary_emb_base=g("rotary_emb_base", 10000.0),
        layer_norm_eps=g("layer_norm_eps", 1e-5),
        use_parallel_residual=bool(g("use_parallel_residual", True)),
        max_position_embeddings=g("max_position_embeddings", 2048),
    )


def neox_params_from_hf(cfg, sd: dict) -> dict:
    h, nh, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    pref = "gpt_neox." if any(k.startswith("gpt_neox.") for k in sd) else ""
    tree: dict = {"gpt_neox": {}}
    _set(tree, "gpt_neox/embed_in/embedding", _np(sd[pref + "embed_in.weight"]))
    _set(tree, "gpt_neox/final_layer_norm/scale", _np(sd[pref + "final_layer_norm.weight"]))
    _set(tree, "gpt_neox/final_layer_norm/bias", _np(sd[pref + "final_layer_norm.bias"]))
    _set(tree, "embed_out/kernel", _t(sd["embed_out.weight"]))
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"{pref}layers.{i}."
        layers.append({
            "input_layernorm/scale": _np(sd[p + "input_layernorm.weight"]),
            "input_layernorm/bias": _np(sd[p + "input_layernorm.bias"]),
            # (3H, H) with per-head [q|k|v] rows → (H, nh, 3, d).
            "attention/query_key_value/kernel": _t(sd[p + "attention.query_key_value.weight"]).reshape(h, nh, 3, d),
            "attention/query_key_value/bias": _np(sd[p + "attention.query_key_value.bias"]).reshape(nh, 3, d),
            "attention/dense/kernel": _t(sd[p + "attention.dense.weight"]).reshape(nh, d, h),
            "attention/dense/bias": _np(sd[p + "attention.dense.bias"]),
            "post_attention_layernorm/scale": _np(sd[p + "post_attention_layernorm.weight"]),
            "post_attention_layernorm/bias": _np(sd[p + "post_attention_layernorm.bias"]),
            "dense_h_to_4h/kernel": _t(sd[p + "mlp.dense_h_to_4h.weight"]),
            "dense_h_to_4h/bias": _np(sd[p + "mlp.dense_h_to_4h.bias"]),
            "dense_4h_to_h/kernel": _t(sd[p + "mlp.dense_4h_to_h.weight"]),
            "dense_4h_to_h/bias": _np(sd[p + "mlp.dense_4h_to_h.bias"]),
        })
    _place_layers(tree, _stack_layers(layers), cfg.scan_layers,
                  "gpt_neox/layers/block", "gpt_neox/layer_{i}", cfg.num_hidden_layers)
    return tree


# ---------------------------------------------------------------------------
# OPT
# ---------------------------------------------------------------------------

def opt_config_from_hf(hf: Any) -> "OPTConfig":
    from .opt import OPTConfig

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return OPTConfig(
        vocab_size=g("vocab_size"),
        hidden_size=g("hidden_size"),
        ffn_dim=g("ffn_dim"),
        num_hidden_layers=g("num_hidden_layers"),
        num_attention_heads=g("num_attention_heads"),
        max_position_embeddings=g("max_position_embeddings", 2048),
    )


def opt_params_from_hf(cfg, sd: dict) -> dict:
    h, nh, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    pref = "model.decoder." if any(k.startswith("model.decoder.") for k in sd) else "decoder."
    tree: dict = {"model": {}}
    _set(tree, "model/embed_tokens/embedding", _np(sd[pref + "embed_tokens.weight"]))
    _set(tree, "model/embed_positions/embedding", _np(sd[pref + "embed_positions.weight"]))
    _set(tree, "model/final_layer_norm/scale", _np(sd[pref + "final_layer_norm.weight"]))
    _set(tree, "model/final_layer_norm/bias", _np(sd[pref + "final_layer_norm.bias"]))
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"{pref}layers.{i}."
        layer = {}
        for name in ("q_proj", "k_proj", "v_proj"):
            layer[f"self_attn/{name}/kernel"] = _t(sd[p + f"self_attn.{name}.weight"]).reshape(h, nh, d)
            layer[f"self_attn/{name}/bias"] = _np(sd[p + f"self_attn.{name}.bias"]).reshape(nh, d)
        layer["self_attn/out_proj/kernel"] = _t(sd[p + "self_attn.out_proj.weight"]).reshape(nh, d, h)
        layer["self_attn/out_proj/bias"] = _np(sd[p + "self_attn.out_proj.bias"])
        layer["self_attn_layer_norm/scale"] = _np(sd[p + "self_attn_layer_norm.weight"])
        layer["self_attn_layer_norm/bias"] = _np(sd[p + "self_attn_layer_norm.bias"])
        layer["fc1/kernel"] = _t(sd[p + "fc1.weight"])
        layer["fc1/bias"] = _np(sd[p + "fc1.bias"])
        layer["fc2/kernel"] = _t(sd[p + "fc2.weight"])
        layer["fc2/bias"] = _np(sd[p + "fc2.bias"])
        layer["final_layer_norm/scale"] = _np(sd[p + "final_layer_norm.weight"])
        layer["final_layer_norm/bias"] = _np(sd[p + "final_layer_norm.bias"])
        layers.append(layer)
    _place_layers(tree, _stack_layers(layers), cfg.scan_layers,
                  "model/layers/block", "model/layer_{i}", cfg.num_hidden_layers)
    return tree


# ---------------------------------------------------------------------------
# ViT
# ---------------------------------------------------------------------------

def vit_config_from_hf(hf: Any) -> "ViTConfig":
    from .vit import ViTConfig

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return ViTConfig(
        image_size=g("image_size", 224),
        patch_size=g("patch_size", 16),
        num_channels=g("num_channels", 3),
        hidden_size=g("hidden_size"),
        num_hidden_layers=g("num_hidden_layers"),
        num_attention_heads=g("num_attention_heads"),
        intermediate_size=g("intermediate_size"),
        layer_norm_eps=g("layer_norm_eps", 1e-12),
        num_labels=g("num_labels", 1000),
    )


def vit_params_from_hf(cfg, sd: dict) -> dict:
    h, nh, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    pref = "vit." if any(k.startswith("vit.") for k in sd) else ""
    e = pref + "embeddings."
    tree: dict = {"vit": {}}
    _set(tree, "vit/cls_token", _np(sd[e + "cls_token"]))
    _set(tree, "vit/position_embeddings", _np(sd[e + "position_embeddings"]))
    # torch Conv2d kernel (H, C, P, P) → flax NHWC Conv kernel (P, P, C, H).
    conv = _np(sd[e + "patch_embeddings.projection.weight"]).transpose(2, 3, 1, 0)
    _set(tree, "vit/patch_embed/kernel", conv)
    _set(tree, "vit/patch_embed/bias", _np(sd[e + "patch_embeddings.projection.bias"]))
    _set(tree, "vit/ln_final/scale", _np(sd[pref + "layernorm.weight"]))
    _set(tree, "vit/ln_final/bias", _np(sd[pref + "layernorm.bias"]))
    if "classifier.weight" in sd:
        _set(tree, "classifier/kernel", _t(sd["classifier.weight"]))
        _set(tree, "classifier/bias", _np(sd["classifier.bias"]))
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"{pref}encoder.layer.{i}."
        layers.append({
            "ln_before/scale": _np(sd[p + "layernorm_before.weight"]),
            "ln_before/bias": _np(sd[p + "layernorm_before.bias"]),
            "attention/query/kernel": _t(sd[p + "attention.attention.query.weight"]).reshape(h, nh, d),
            "attention/query/bias": _np(sd[p + "attention.attention.query.bias"]).reshape(nh, d),
            "attention/key/kernel": _t(sd[p + "attention.attention.key.weight"]).reshape(h, nh, d),
            "attention/key/bias": _np(sd[p + "attention.attention.key.bias"]).reshape(nh, d),
            "attention/value/kernel": _t(sd[p + "attention.attention.value.weight"]).reshape(h, nh, d),
            "attention/value/bias": _np(sd[p + "attention.attention.value.bias"]).reshape(nh, d),
            "attention/output/kernel": _t(sd[p + "attention.output.dense.weight"]).reshape(nh, d, h),
            "attention/output/bias": _np(sd[p + "attention.output.dense.bias"]),
            "ln_after/scale": _np(sd[p + "layernorm_after.weight"]),
            "ln_after/bias": _np(sd[p + "layernorm_after.bias"]),
            "intermediate/kernel": _t(sd[p + "intermediate.dense.weight"]),
            "intermediate/bias": _np(sd[p + "intermediate.dense.bias"]),
            "output/kernel": _t(sd[p + "output.dense.weight"]),
            "output/bias": _np(sd[p + "output.dense.bias"]),
        })
    _place_layers(tree, _stack_layers(layers), cfg.scan_layers,
                  "vit/layers/block", "vit/layer_{i}", cfg.num_hidden_layers)
    return tree


# ---------------------------------------------------------------------------
# T5
# ---------------------------------------------------------------------------

def t5_config_from_hf(hf: Any) -> "T5Config":
    from .t5 import T5Config

    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    return T5Config(
        vocab_size=g("vocab_size"),
        d_model=g("d_model"),
        d_kv=g("d_kv", 64),
        d_ff=g("d_ff"),
        num_layers=g("num_layers"),
        num_decoder_layers=g("num_decoder_layers"),
        num_heads=g("num_heads"),
        relative_attention_num_buckets=g("relative_attention_num_buckets", 32),
        relative_attention_max_distance=g("relative_attention_max_distance", 128),
        layer_norm_epsilon=g("layer_norm_epsilon", 1e-6),
        decoder_start_token_id=g("decoder_start_token_id", 0),
        pad_token_id=g("pad_token_id", 0),
    )


def _t5_attn(sd, p, our, dm, nh, dk) -> dict:
    return {
        f"{our}/q/kernel": _t(sd[p + "q.weight"]).reshape(dm, nh, dk),
        f"{our}/k/kernel": _t(sd[p + "k.weight"]).reshape(dm, nh, dk),
        f"{our}/v/kernel": _t(sd[p + "v.weight"]).reshape(dm, nh, dk),
        f"{our}/o/kernel": _t(sd[p + "o.weight"]).reshape(nh, dk, dm),
    }


def t5_params_from_hf(cfg, sd: dict) -> dict:
    dm, nh, dk = cfg.d_model, cfg.num_heads, cfg.d_kv
    tree: dict = {}
    _set(tree, "shared/embedding", _np(sd["shared.weight"]))
    _set(tree, "encoder/final_ln/weight", _np(sd["encoder.final_layer_norm.weight"]))
    _set(tree, "decoder/final_ln/weight", _np(sd["decoder.final_layer_norm.weight"]))

    def enc_layer(i):
        p = f"encoder.block.{i}."
        layer = _t5_attn(sd, p + "layer.0.SelfAttention.", "self_attn", dm, nh, dk)
        layer["ln0/weight"] = _np(sd[p + "layer.0.layer_norm.weight"])
        layer["ffn/wi/kernel"] = _t(sd[p + "layer.1.DenseReluDense.wi.weight"])
        layer["ffn/wo/kernel"] = _t(sd[p + "layer.1.DenseReluDense.wo.weight"])
        layer["ln1/weight"] = _np(sd[p + "layer.1.layer_norm.weight"])
        return layer

    def dec_layer(i):
        p = f"decoder.block.{i}."
        layer = _t5_attn(sd, p + "layer.0.SelfAttention.", "self_attn", dm, nh, dk)
        layer["ln0/weight"] = _np(sd[p + "layer.0.layer_norm.weight"])
        layer.update(_t5_attn(sd, p + "layer.1.EncDecAttention.", "cross_attn", dm, nh, dk))
        layer["ln1/weight"] = _np(sd[p + "layer.1.layer_norm.weight"])
        layer["ffn/wi/kernel"] = _t(sd[p + "layer.2.DenseReluDense.wi.weight"])
        layer["ffn/wo/kernel"] = _t(sd[p + "layer.2.DenseReluDense.wo.weight"])
        layer["ln2/weight"] = _np(sd[p + "layer.2.layer_norm.weight"])
        return layer

    for stack, n, make in (("encoder", cfg.num_layers, enc_layer),
                           ("decoder", cfg.n_dec, dec_layer)):
        first = make(0)
        first["self_attn/relative_attention_bias/embedding"] = _np(
            sd[f"{stack}.block.0.layer.0.SelfAttention.relative_attention_bias.weight"]
        )
        for path, arr in first.items():
            _set(tree, f"{stack}/block_0/{path}", arr)
        rest = [make(i) for i in range(1, n)]
        if rest and cfg.scan_layers:
            for path, arr in _stack_layers(rest).items():
                _set(tree, f"{stack}/layers/block/{path}", arr)
        else:
            # unscanned names are block_1..block_{n-1}
            for i in range(1, n):
                for path, arr in rest[i - 1].items():
                    _set(tree, f"{stack}/block_{i}/{path}", arr)
    return tree


# ---------------------------------------------------------------------------
# Phi-3 (Llama architecture with fused qkv_proj / gate_up_proj)
# ---------------------------------------------------------------------------

def phi3_config_from_hf(hf: Any) -> "LlamaConfig":
    """Llama config + guards for the Phi-3 variants the plain-RoPE Llama
    family cannot represent: longrope scaling (Phi-3-mini-128k) and partial
    rotary (Phi-4-mini) would convert silently and diverge at every token."""
    g = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
        lambda k, d=None: getattr(hf, k, d)
    )
    scaling = g("rope_scaling")
    if scaling:
        raise ValueError(
            f"Phi-3 checkpoint uses rope_scaling={scaling.get('type', scaling) if isinstance(scaling, dict) else scaling!r} "
            "— longrope is not supported by the Llama family; load the base "
            "(4k) variant instead."
        )
    partial = g("partial_rotary_factor", 1.0)
    if partial not in (None, 1.0):
        raise ValueError(
            f"Phi-3 checkpoint uses partial_rotary_factor={partial} — the "
            "Llama family applies full-head RoPE only."
        )
    return llama_config_from_hf(hf)


def phi3_params_from_hf(cfg, sd: dict) -> dict:
    """Split Phi-3's fused projections into the Llama family's layout:
    qkv_proj rows are [q (Hq·d) | k (Hkv·d) | v (Hkv·d)], gate_up_proj rows
    are [gate (I) | up (I)]; everything else is byte-identical Llama."""
    q_rows = cfg.num_attention_heads * cfg.head_dim
    kv_rows = cfg.num_key_value_heads * cfg.head_dim
    split: dict = {}
    for k, v in sd.items():
        if k.endswith("self_attn.qkv_proj.weight"):
            base = k[: -len("qkv_proj.weight")]
            w = _np(v)
            split[base + "q_proj.weight"] = w[:q_rows]
            split[base + "k_proj.weight"] = w[q_rows:q_rows + kv_rows]
            split[base + "v_proj.weight"] = w[q_rows + kv_rows:]
        elif k.endswith("mlp.gate_up_proj.weight"):
            base = k[: -len("gate_up_proj.weight")]
            w = _np(v)
            split[base + "gate_proj.weight"] = w[: cfg.intermediate_size]
            split[base + "up_proj.weight"] = w[cfg.intermediate_size:]
        else:
            split[k] = v
    return llama_params_from_hf(cfg, split)


# ---------------------------------------------------------------------------
# CLIP
# ---------------------------------------------------------------------------

def clip_config_from_hf(hf: Any) -> "CLIPConfig":
    from .clip import CLIPConfig

    if isinstance(hf, dict):
        text, vision = hf.get("text_config", {}), hf.get("vision_config", {})
        tg = lambda k, d=None: text.get(k, d)  # noqa: E731
        vg = lambda k, d=None: vision.get(k, d)  # noqa: E731
        g = lambda k, d=None: hf.get(k, d)  # noqa: E731
    else:
        tg = lambda k, d=None: getattr(hf.text_config, k, d)  # noqa: E731
        vg = lambda k, d=None: getattr(hf.vision_config, k, d)  # noqa: E731
        g = lambda k, d=None: getattr(hf, k, d)  # noqa: E731
    return CLIPConfig(
        vocab_size=tg("vocab_size"),
        text_hidden_size=tg("hidden_size"),
        text_num_layers=tg("num_hidden_layers"),
        text_num_heads=tg("num_attention_heads"),
        text_intermediate_size=tg("intermediate_size"),
        max_position_embeddings=tg("max_position_embeddings", 77),
        image_size=vg("image_size", 224),
        patch_size=vg("patch_size", 32),
        num_channels=vg("num_channels", 3),
        vision_hidden_size=vg("hidden_size"),
        vision_num_layers=vg("num_hidden_layers"),
        vision_num_heads=vg("num_attention_heads"),
        vision_intermediate_size=vg("intermediate_size"),
        projection_dim=g("projection_dim", 512),
        logit_scale_init=g("logit_scale_init_value", 2.6592),
        layer_norm_eps=_clip_ln_eps(tg, vg),
        eos_token_id=tg("eos_token_id", 49407),
        hidden_act=_clip_hidden_act(tg, vg),
    )


def _clip_ln_eps(tg, vg) -> float:
    text_eps = tg("layer_norm_eps", 1e-5)
    vision_eps = vg("layer_norm_eps", 1e-5)
    if text_eps != vision_eps:
        raise ValueError(
            f"CLIP checkpoint mixes tower layer_norm_eps (text={text_eps}, "
            f"vision={vision_eps}) — not supported by the native family."
        )
    return text_eps


def _clip_hidden_act(tg, vg) -> str:
    text_act = tg("hidden_act", "quick_gelu")
    vision_act = vg("hidden_act", "quick_gelu")
    if text_act != vision_act:
        raise ValueError(
            f"CLIP checkpoint mixes tower activations (text={text_act!r}, "
            f"vision={vision_act!r}) — not supported by the native family."
        )
    return text_act


def _clip_tower_layers(sd, prefix, n, h, nh):
    d = h // nh
    layers = []
    for i in range(n):
        p = f"{prefix}.encoder.layers.{i}."
        layers.append({
            "ln1/scale": _np(sd[p + "layer_norm1.weight"]),
            "ln1/bias": _np(sd[p + "layer_norm1.bias"]),
            "self_attn/q_proj/kernel": _t(sd[p + "self_attn.q_proj.weight"]).reshape(h, nh, d),
            "self_attn/q_proj/bias": _np(sd[p + "self_attn.q_proj.bias"]).reshape(nh, d),
            "self_attn/k_proj/kernel": _t(sd[p + "self_attn.k_proj.weight"]).reshape(h, nh, d),
            "self_attn/k_proj/bias": _np(sd[p + "self_attn.k_proj.bias"]).reshape(nh, d),
            "self_attn/v_proj/kernel": _t(sd[p + "self_attn.v_proj.weight"]).reshape(h, nh, d),
            "self_attn/v_proj/bias": _np(sd[p + "self_attn.v_proj.bias"]).reshape(nh, d),
            "self_attn/out_proj/kernel": _t(sd[p + "self_attn.out_proj.weight"]).reshape(nh, d, h),
            "self_attn/out_proj/bias": _np(sd[p + "self_attn.out_proj.bias"]),
            "ln2/scale": _np(sd[p + "layer_norm2.weight"]),
            "ln2/bias": _np(sd[p + "layer_norm2.bias"]),
            "fc1/kernel": _t(sd[p + "mlp.fc1.weight"]),
            "fc1/bias": _np(sd[p + "mlp.fc1.bias"]),
            "fc2/kernel": _t(sd[p + "mlp.fc2.weight"]),
            "fc2/bias": _np(sd[p + "mlp.fc2.bias"]),
        })
    return layers


def clip_params_from_hf(cfg, sd: dict) -> dict:
    tree: dict = {"text": {}, "vision": {}}
    # Text tower
    _set(tree, "text/token_embedding", _np(sd["text_model.embeddings.token_embedding.weight"]))
    _set(tree, "text/position_embedding", _np(sd["text_model.embeddings.position_embedding.weight"]))
    _set(tree, "text/final_ln/scale", _np(sd["text_model.final_layer_norm.weight"]))
    _set(tree, "text/final_ln/bias", _np(sd["text_model.final_layer_norm.bias"]))
    _place_layers(
        tree,
        _stack_layers(_clip_tower_layers(
            sd, "text_model", cfg.text_num_layers, cfg.text_hidden_size, cfg.text_num_heads
        )),
        cfg.scan_layers, "text/layers/block", "text/layer_{i}", cfg.text_num_layers,
    )
    # Vision tower (note: HF spells it "pre_layrnorm")
    _set(tree, "vision/class_embedding", _np(sd["vision_model.embeddings.class_embedding"]))
    conv = _np(sd["vision_model.embeddings.patch_embedding.weight"]).transpose(2, 3, 1, 0)
    _set(tree, "vision/patch_embed/kernel", conv)
    _set(tree, "vision/position_embedding", _np(sd["vision_model.embeddings.position_embedding.weight"]))
    _set(tree, "vision/pre_ln/scale", _np(sd["vision_model.pre_layrnorm.weight"]))
    _set(tree, "vision/pre_ln/bias", _np(sd["vision_model.pre_layrnorm.bias"]))
    _set(tree, "vision/post_ln/scale", _np(sd["vision_model.post_layernorm.weight"]))
    _set(tree, "vision/post_ln/bias", _np(sd["vision_model.post_layernorm.bias"]))
    _place_layers(
        tree,
        _stack_layers(_clip_tower_layers(
            sd, "vision_model", cfg.vision_num_layers, cfg.vision_hidden_size,
            cfg.vision_num_heads,
        )),
        cfg.scan_layers, "vision/layers/block", "vision/layer_{i}", cfg.vision_num_layers,
    )
    _set(tree, "text_projection/kernel", _t(sd["text_projection.weight"]))
    _set(tree, "visual_projection/kernel", _t(sd["visual_projection.weight"]))
    _set(tree, "logit_scale", _np(sd["logit_scale"]))
    return tree


# ---------------------------------------------------------------------------
# High-level entry
# ---------------------------------------------------------------------------

_FAMILIES = {
    "clip": ("CLIPModel", clip_config_from_hf, clip_params_from_hf),
    "llama": ("LlamaForCausalLM", llama_config_from_hf, llama_params_from_hf),
    "mistral": ("LlamaForCausalLM", llama_config_from_hf, llama_params_from_hf),
    "qwen2": ("LlamaForCausalLM", llama_config_from_hf, llama_params_from_hf),
    "gemma": ("LlamaForCausalLM", gemma_config_from_hf, llama_params_from_hf),
    "ouro": ("LlamaForCausalLM", ouro_config_from_hf, llama_params_from_hf),
    "phi3": ("LlamaForCausalLM", phi3_config_from_hf, phi3_params_from_hf),
    "mixtral": ("MixtralForCausalLM", mixtral_config_from_hf, mixtral_params_from_hf),
    "gpt2": ("GPT2LMHeadModel", gpt2_config_from_hf, gpt2_params_from_hf),
    "bert": ("BertForSequenceClassification", bert_config_from_hf, bert_params_from_hf),
    "t5": ("T5ForConditionalGeneration", t5_config_from_hf, t5_params_from_hf),
    "vit": ("ViTForImageClassification", vit_config_from_hf, vit_params_from_hf),
    "opt": ("OPTForCausalLM", opt_config_from_hf, opt_params_from_hf),
    "gpt_neox": ("GPTNeoXForCausalLM", neox_config_from_hf, neox_params_from_hf),
    "whisper": ("WhisperForConditionalGeneration", whisper_config_from_hf, whisper_params_from_hf),
}


def _read_checkpoint_dir(path: str) -> tuple[dict, dict]:
    with open(os.path.join(path, "config.json")) as f:
        hf_cfg = json.load(f)
    sd: dict = {}
    shards = sorted(fn for fn in os.listdir(path) if fn.endswith(".safetensors"))
    if shards:
        from safetensors.numpy import load_file

        for fn in shards:
            sd.update(load_file(os.path.join(path, fn)))
    elif os.path.exists(os.path.join(path, "pytorch_model.bin")):
        import torch

        raw = torch.load(os.path.join(path, "pytorch_model.bin"),
                         map_location="cpu", weights_only=True)
        sd = {k: _np(v) for k, v in raw.items()}
    else:
        raise FileNotFoundError(f"No *.safetensors or pytorch_model.bin under {path}")
    return hf_cfg, sd


def load_pretrained(src, family: Optional[str] = None, dtype=jnp.bfloat16):
    """HF checkpoint → (our_config, params, module_class).

    ``src``: transformers ``PreTrainedModel``, a local checkpoint directory,
    or a ``(hf_config, state_dict)`` pair.
    """
    if isinstance(src, str):
        hf_cfg, sd = _read_checkpoint_dir(src)
    elif isinstance(src, tuple):
        hf_cfg, sd = src
        sd = {k: _np(v) for k, v in sd.items()}
    else:  # transformers model instance
        hf_cfg = src.config
        sd = {k: _np(v) for k, v in src.state_dict().items()}
    if family is None:
        family = (hf_cfg.get("model_type") if isinstance(hf_cfg, dict)
                  else getattr(hf_cfg, "model_type", None))
    if family not in _FAMILIES:
        # Declarative fallback: unseen architectures load via registered
        # ArchSpec rules (models/generic_hub.py) — data, not new code.
        from . import generic_hub

        spec = generic_hub.get_arch_spec(family)
        if spec is not None:
            return generic_hub.load_with_spec(spec, hf_cfg, sd, dtype)
        known = ", ".join(sorted(_FAMILIES))
        generic = ", ".join(generic_hub.known_generic_types())
        raise ValueError(
            f"Unsupported model family {family!r}; hand-written families: "
            f"{known}; generic specs: {generic}. Register new architectures "
            f"with accelerate_tpu.models.generic_hub.register_arch_spec."
        )
    cls_name, cfg_fn, params_fn = _FAMILIES[family]
    import dataclasses as _dc

    cfg = _dc.replace(cfg_fn(hf_cfg), dtype=dtype)
    params = params_fn(cfg, sd)
    import importlib

    models_pkg = importlib.import_module(__package__)
    return cfg, params, getattr(models_pkg, cls_name)


def model_from_pretrained(src, family: Optional[str] = None, dtype=jnp.bfloat16):
    """HF checkpoint → ready-to-run :class:`accelerate_tpu.Model`."""
    from ..model import Model

    cfg, params, cls = load_pretrained(src, family=family, dtype=dtype)
    return Model(module=cls(cfg), params=params)
