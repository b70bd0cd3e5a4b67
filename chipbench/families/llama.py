"""Family ``llama``: the Llama block (RMSNorm, rotary GQA attention, gated
SiLU MLP, untied head). Serves Mistral-7B. Four parts:

- the leaves the program's module holds, with their shapes (``weight_specs``),
- the program's module and parameter tree from those leaves (``to_program``),
- the plain float32 reference (``reference_logits``), which shares no code
  with ``accelerate_tpu`` and follows the published equations,
- the arithmetic of needed work: parameters, FLOPs of a request, bytes of a
  decode step. It is of the algorithm, not of the implementation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _ref_common as R
from . import _work as W

_BLOCK = "model/layers/block/"
_LAYER_LEAVES = {  # path under the block -> (shape without the layer axis, kind)
    "input_layernorm/weight": (lambda c: (c["hidden_size"],), "norm"),
    "post_attention_layernorm/weight": (lambda c: (c["hidden_size"],), "norm"),
    "self_attn/q_proj/kernel": (
        lambda c: (c["hidden_size"], c["num_attention_heads"], c["head_dim"]), "normal"),
    "self_attn/k_proj/kernel": (
        lambda c: (c["hidden_size"], c["num_key_value_heads"], c["head_dim"]), "normal"),
    "self_attn/v_proj/kernel": (
        lambda c: (c["hidden_size"], c["num_key_value_heads"], c["head_dim"]), "normal"),
    "self_attn/o_proj/kernel": (
        lambda c: (c["num_attention_heads"], c["head_dim"], c["hidden_size"]), "normal"),
    "mlp/gate_proj/kernel": (lambda c: (c["hidden_size"], c["intermediate_size"]), "normal"),
    "mlp/up_proj/kernel": (lambda c: (c["hidden_size"], c["intermediate_size"]), "normal"),
    "mlp/down_proj/kernel": (lambda c: (c["intermediate_size"], c["hidden_size"]), "normal"),
}


def outer_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model/embed_tokens/embedding": ((v, h), "normal"),
        "model/norm/weight": ((h,), "norm"),
        "lm_head/kernel": ((h, v), "normal"),
    }


def weight_specs(cfg: dict) -> dict:
    """Every leaf, in the stacked layout the program's scanned module holds:
    block leaves carry a leading layer axis."""
    specs = outer_specs(cfg)
    for path, (shape, kind) in _LAYER_LEAVES.items():
        specs[_BLOCK + path] = ((cfg["num_hidden_layers"], *shape(cfg)), kind)
    return specs


# -- the program ---------------------------------------------------------------


def _program_kwargs(cfg: dict, max_len: int, options: dict) -> dict:
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_position_embeddings=max(int(max_len), 1), rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], tie_word_embeddings=cfg["tie_word_embeddings"],
        hidden_act=cfg["hidden_act"], dtype=jnp.bfloat16, **options,
    )


def program_module(cfg: dict, max_len: int, **options):
    """The program's flax module for this configuration. ``max_len`` is the
    longest sequence the cell uses (the published context is 32768; rotary
    tables are computed from positions, so it only bounds the cache)."""
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(**_program_kwargs(cfg, max_len, options)))


# -- the plain reference ---------------------------------------------------------


def _layer_leaves(weights: dict) -> dict:
    """Block leaves by their last two path parts, still stacked by layer."""
    return {"/".join(k.split("/")[-2:]): v for k, v in weights.items()
            if k.startswith(_BLOCK)}


def reference_logits(cfg: dict, weights: dict, ids, control: str | None = None,
                     with_margin: bool = False):
    """Logits (S, V) in float32 of the whole sequence ``ids`` (S,), causal, no
    cache, no kernel. ``control`` computes the projections in the stated lower
    precision instead (see ``_ref_common.make_mm``). ``with_margin`` also
    returns how decided each position's routing is: a dense model routes
    nothing, so infinitely."""
    mm = R.make_mm(control)
    s = ids.shape[0]
    x = R.f32(weights["model/embed_tokens/embedding"][ids])
    cos, sin = R.rope_tables(s, cfg["head_dim"], cfg["rope_theta"])

    def layer(x, lw):
        x = R.attention_block(cfg, lw, x, cos, sin, mm)
        hn = R.rms_norm(x, lw["post_attention_layernorm/weight"], cfg["rms_norm_eps"])
        gate = jax.nn.silu(mm(hn, lw["gate_proj/kernel"]))
        return x + mm(gate * mm(hn, lw["up_proj/kernel"]), lw["down_proj/kernel"]), None

    x, _ = jax.lax.scan(layer, x, _layer_leaves(weights))
    x = R.rms_norm(x, weights["model/norm/weight"], cfg["rms_norm_eps"])
    logits = mm(x, weights["lm_head/kernel"])
    return (logits, jnp.full((s,), jnp.inf, jnp.float32)) if with_margin else logits


# -- needed work -------------------------------------------------------------------


def attn_matmul_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return h * d * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def layer_params(cfg: dict) -> int:
    return (attn_matmul_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]
            + 2 * cfg["hidden_size"])


def total_params(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def active_layer_matmul_params(cfg: dict) -> int:
    """Matmul weights one token passes through in one layer."""
    return attn_matmul_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def read_layer_matmul_params(cfg: dict) -> int:
    """Matmul weights one decode step of a full batch has to read in one layer."""
    return active_layer_matmul_params(cfg)


def request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    return W.request_flops(cfg, active_layer_matmul_params(cfg), prompt_len, new_tokens)


def decode_step_bytes(cfg: dict, live_rows: float) -> float:
    return W.decode_step_bytes(cfg, read_layer_matmul_params(cfg), live_rows)
