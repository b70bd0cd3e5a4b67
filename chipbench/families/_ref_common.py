"""What the plain references share: float32 arithmetic at the highest matmul
precision, and the lower-precision stand-in the control computes in. Imports
nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def f32(a):
    return a.astype(jnp.float32)


def fake_int8(a, axis: int):
    """Symmetric int8 round-trip with one absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def make_mm(control: str | None):
    """``mm(x, w)`` for ``x (rows, in)`` and ``w (in, out)``. The reference
    multiplies in float32 at the highest precision. The control ``"int8"``
    first rounds each row of ``x`` and each column of ``w`` to int8 (W8A8), the
    step below the bfloat16 the configurations state."""
    if control is None:
        return lambda x, w: jnp.matmul(x, f32(w), precision=HIGHEST)
    if control == "int8":
        return lambda x, w: jnp.matmul(fake_int8(x, -1), fake_int8(f32(w), 0),
                                       precision=HIGHEST)
    raise ValueError(f"unknown control precision {control!r}")


def rms_norm(x, weight, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def rope_tables(n: int, head_dim: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """x (S, heads, D): rotate the pair (i, i + D/2), the published convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def causal_gqa_attention(q, k, v):
    """q (S, Hq, D), k and v (S, Hkv, D): full causal softmax attention, each
    group of Hq / Hkv query heads on its own key and value head."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, d)
    scores = jnp.einsum("sngd,tnd->ngst", qg, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(d))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("ngst,tnd->sngd", probs, v, precision=HIGHEST)
    return out.reshape(s, hq, d)


def attention_block(cfg, lw, x, cos, sin, mm):
    """The attention half of a Llama-family layer on x (S, H); ``lw`` holds
    this layer's leaves by their last two path parts."""
    s, h = x.shape
    nq, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    hn = rms_norm(x, lw["input_layernorm/weight"], cfg["rms_norm_eps"])
    q = mm(hn, lw["q_proj/kernel"].reshape(h, nq * d)).reshape(s, nq, d)
    k = mm(hn, lw["k_proj/kernel"].reshape(h, nkv * d)).reshape(s, nkv, d)
    v = mm(hn, lw["v_proj/kernel"].reshape(h, nkv * d)).reshape(s, nkv, d)
    out = causal_gqa_attention(rope(q, cos, sin), rope(k, cos, sin), v)
    return x + mm(out.reshape(s, nq * d), lw["o_proj/kernel"].reshape(nq * d, h))
