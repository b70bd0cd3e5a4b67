"""Family ``ouro``: a looped language model on the Llama chassis (Ouro,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741). The
same ``L`` layers run ``U = total_ut_steps`` times over one set of weights;
every branch's output passes a second RMSNorm before it joins the residual
(sandwich norms); the final norm closes every pass and its output opens the
next; one exit gate, ``Linear(H, 1)``, reads every pass's output. A pass
attends over its own keys and values, so a served token keeps ``U x L`` cache
planes. Same four parts as ``llama.py``; the reference is written from the
equations in the configuration's ``assumed`` list and shares no code with the
program:

    x_0 = E[ids]
    for u = 1..U:   h = x_{u-1}
        for l = 1..L:
            h = h + RMS(Attn_l(RMS(h; g1_l)); g2_l)
            h = h + RMS(MLP_l(RMS(h; g3_l)); g4_l)
        x_u = RMS(h; g_final);   lam_u = sigmoid(w_exit . x_u + b_exit)
    logits = W_head x_U        (early_exit_threshold 1: no token leaves early)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _ref_common as R
from . import _work as W
from . import llama as L

_SANDWICH = ("input_layernorm_2", "post_attention_layernorm_2")
_GATE = "model/early_exit_gate/"


def weight_specs(cfg: dict) -> dict:
    """The Llama leaves, the two further norms of every layer (stacked by
    layer like the rest of the block) and the exit gate's kernel and bias."""
    n, h = cfg["num_hidden_layers"], cfg["hidden_size"]
    specs = L.weight_specs(cfg)
    for name in _SANDWICH:
        specs[f"{L._BLOCK}{name}/weight"] = ((n, h), "norm")
    specs[_GATE + "kernel"] = ((h, 1), "normal")
    specs[_GATE + "bias"] = ((1,), "normal")
    return specs


def program_module(cfg: dict, max_len: int, **options):
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(
        total_ut_steps=cfg["total_ut_steps"], sandwich_norm=True, early_exit_gate=True,
        early_exit_threshold=cfg["early_exit_threshold"],
        **L._program_kwargs(cfg, max_len, options)))


# -- the plain reference ---------------------------------------------------------


def _passes(cfg: dict, weights: dict, ids, mm):
    """``(x_U (S, H), lam (U, S))``: the last pass's normed output and every
    pass's exit probability, causal over the whole sequence, no cache: pass
    ``u`` projects its keys and values from its own hidden states."""
    s = ids.shape[0]
    h_, d, eps = cfg["hidden_size"], cfg["head_dim"], cfg["rms_norm_eps"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    cos, sin = R.rope_tables(s, d, cfg["rope_theta"])
    layers = L._layer_leaves(weights)
    exact = R.make_mm(None)   # the gate stays float32 under the control: it serves no token

    def layer(h, lw):
        n = R.rms_norm(h, lw["input_layernorm/weight"], eps)
        q = mm(n, lw["q_proj/kernel"].reshape(h_, nq * d)).reshape(s, nq, d)
        k = mm(n, lw["k_proj/kernel"].reshape(h_, nkv * d)).reshape(s, nkv, d)
        v = mm(n, lw["v_proj/kernel"].reshape(h_, nkv * d)).reshape(s, nkv, d)
        a = R.causal_gqa_attention(R.rope(q, cos, sin), R.rope(k, cos, sin), v)
        a = mm(a.reshape(s, nq * d), lw["o_proj/kernel"].reshape(nq * d, h_))
        h = h + R.rms_norm(a, lw["input_layernorm_2/weight"], eps)
        n = R.rms_norm(h, lw["post_attention_layernorm/weight"], eps)
        m = mm(jax.nn.silu(mm(n, lw["gate_proj/kernel"])) * mm(n, lw["up_proj/kernel"]),
               lw["down_proj/kernel"])
        return h + R.rms_norm(m, lw["post_attention_layernorm_2/weight"], eps), None

    def one_pass(x, _):
        h, _ = jax.lax.scan(layer, x, layers)
        x = R.rms_norm(h, weights["model/norm/weight"], eps)
        lam = jax.nn.sigmoid(exact(x, weights[_GATE + "kernel"])[:, 0]
                             + R.f32(weights[_GATE + "bias"])[0])
        return x, lam

    x = R.f32(weights["model/embed_tokens/embedding"][ids])
    return jax.lax.scan(one_pass, x, None, length=cfg["total_ut_steps"])


def reference_logits(cfg: dict, weights: dict, ids, control: str | None = None,
                     with_margin: bool = False):
    """Logits (S, V) in float32 of the whole sequence ``ids`` (S,), from the
    last pass: the configuration's ``early_exit_threshold`` is 1, which is
    read as "no early exit" (:func:`exit_pass`); another value is not this
    family's reference and is refused. ``control`` and ``with_margin`` as in
    ``llama.py``: a dense model routes nothing."""
    if cfg["early_exit_threshold"] != 1:
        raise ValueError(f"early_exit_threshold {cfg['early_exit_threshold']}: the reference, like "
                         "the program, computes a threshold of 1 only")
    mm = R.make_mm(control)
    x, _ = _passes(cfg, weights, ids, mm)
    logits = mm(x, weights["lm_head/kernel"])
    s = ids.shape[0]
    return (logits, jnp.full((s,), jnp.inf, jnp.float32)) if with_margin else logits


def exit_cdf(cfg: dict, weights: dict, ids):
    """(U, S): the probability that a token has left by pass ``u``. With
    ``p_u = lam_u prod_{j<u} (1 - lam_j)`` for ``u < U``, the sum over
    ``j <= u`` is ``1 - prod_{j<=u} (1 - lam_j)``; the last pass takes what
    is left, so the last row is 1."""
    _, lam = _passes(cfg, weights, ids, R.make_mm(None))
    return (1.0 - jnp.cumprod(1.0 - lam, axis=0)).at[-1].set(1.0)


def exit_pass(cdf, threshold: float):
    """(S,): the pass, counted from 1, after which each token leaves: the
    first whose cumulative exit probability reaches ``threshold``; the last
    where none does, and always under a threshold of 1 or more."""
    u = cdf.shape[0]
    if threshold >= 1:
        return jnp.full(cdf.shape[1:], u, jnp.int32)
    reached = cdf[:-1] >= threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0) + 1, u).astype(jnp.int32)


# -- needed work -------------------------------------------------------------------


def layer_params(cfg: dict) -> int:
    return L.layer_params(cfg) + len(_SANDWICH) * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
            + cfg["hidden_size"] + 1)


def _unrolled(cfg: dict) -> dict:
    """The configuration as ``_work.py`` prices it: every pass a layer. The
    layers' weights do not stay on the chip between passes (4.93 GB at the
    published size), so a step reads them once a pass; a token attends over,
    and keeps, one cache plane a pass and layer. The head runs once."""
    return dict(cfg, num_hidden_layers=cfg["total_ut_steps"] * cfg["num_hidden_layers"])


def request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    return W.request_flops(_unrolled(cfg), L.active_layer_matmul_params(cfg), prompt_len,
                           new_tokens)


def decode_step_bytes(cfg: dict, live_rows: float) -> float:
    return W.decode_step_bytes(_unrolled(cfg), L.read_layer_matmul_params(cfg), live_rows)
