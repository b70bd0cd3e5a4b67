"""Family ``mixtral``: the Llama attention block with a routed sparse MLP
(softmax router over all experts, top-k, gates renormalised over the k, each
expert a gated SiLU MLP). Same four parts as ``llama.py``; the reference is
written from the published equations and shares no code with the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _ref_common as R
from . import _work as W
from . import llama as L

_BLOCK = "model/layers/block/"


def weight_specs(cfg: dict) -> dict:
    n, e = cfg["num_hidden_layers"], cfg["num_local_experts"]
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    specs = L.outer_specs(cfg)
    for path, (shape, kind) in L._LAYER_LEAVES.items():
        if not path.startswith("mlp/"):
            specs[_BLOCK + path] = ((n, *shape(cfg)), kind)
    specs[_BLOCK + "moe/router"] = ((n, h, e), "normal")
    specs[_BLOCK + "moe/w_gate"] = ((n, e, h, f), "normal")
    specs[_BLOCK + "moe/w_up"] = ((n, e, h, f), "normal")
    specs[_BLOCK + "moe/w_down"] = ((n, e, f, h), "normal")
    return specs


def program_module(cfg: dict, max_len: int, **options):
    from accelerate_tpu.models.moe import MixtralConfig, MixtralForCausalLM

    return MixtralForCausalLM(MixtralConfig(
        num_local_experts=cfg["num_local_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        **L._program_kwargs(cfg, max_len, options)))


def reference_logits(cfg: dict, weights: dict, ids, control: str | None = None,
                     with_margin: bool = False):
    """Logits (S, V) in float32 of the whole sequence. The router stays in
    float32 under the control too, as an int8 deployment keeps it; the experts,
    the attention projections and the head are what the control rounds.

    ``with_margin`` also returns, for each position, how decided its routing
    is: the least over the layers of (the k-th largest router probability - the
    next one). Where it is all but nought, rounding alone decides which expert
    a sound program picks, and the token it then serves says nothing about it;
    ``check.py`` leaves such positions out by a rule on this margin."""
    mm = R.make_mm(control)
    exact = R.make_mm(None)
    s = ids.shape[0]
    k = cfg["num_experts_per_tok"]
    x = R.f32(weights["model/embed_tokens/embedding"][ids])
    cos, sin = R.rope_tables(s, cfg["head_dim"], cfg["rope_theta"])

    def layer(x, lw):
        x = R.attention_block(cfg, lw, x, cos, sin, mm)
        hn = R.rms_norm(x, lw["post_attention_layernorm/weight"], cfg["rms_norm_eps"])
        probs = jax.nn.softmax(exact(hn, lw["moe/router"]), axis=-1)      # (S, E)
        top_p, top_i = jax.lax.top_k(probs, k + 1)
        margin = top_p[:, k - 1] - top_p[:, k]
        top_p, top_i = top_p[:, :k], top_i[:, :k]
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], top_i].set(top_p)

        def expert(acc, ew):                                              # one expert
            w_gate, w_up, w_down, gate_e = ew
            y = mm(jax.nn.silu(mm(hn, w_gate)) * mm(hn, w_up), w_down)
            return acc + gate_e[:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                              (lw["moe/w_gate"], lw["moe/w_up"], lw["moe/w_down"], gates.T))
        return x + out, margin

    x, margins = jax.lax.scan(layer, x, L._layer_leaves(weights))
    x = R.rms_norm(x, weights["model/norm/weight"], cfg["rms_norm_eps"])
    logits = mm(x, weights["lm_head/kernel"])
    return (logits, jnp.min(margins, axis=0)) if with_margin else logits


# -- needed work ---------------------------------------------------------------------


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: dict) -> int:
    return (L.attn_matmul_params(cfg) + cfg["hidden_size"] * cfg["num_local_experts"]
            + cfg["num_local_experts"] * expert_params(cfg) + 2 * cfg["hidden_size"])


def total_params(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def active_layer_matmul_params(cfg: dict) -> int:
    """One token: attention, the router, and the k experts it is routed to."""
    return (L.attn_matmul_params(cfg) + cfg["hidden_size"] * cfg["num_local_experts"]
            + cfg["num_experts_per_tok"] * expert_params(cfg))


def read_layer_matmul_params(cfg: dict) -> int:
    """A decode step of a full batch: its tokens between them reach every
    expert, so all of a layer's experts are read once."""
    return (L.attn_matmul_params(cfg) + cfg["hidden_size"] * cfg["num_local_experts"]
            + cfg["num_local_experts"] * expert_params(cfg))


def request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    return W.request_flops(cfg, active_layer_matmul_params(cfg), prompt_len, new_tokens)


def decode_step_bytes(cfg: dict, live_rows: float) -> float:
    return W.decode_step_bytes(cfg, read_layer_matmul_params(cfg), live_rows)
