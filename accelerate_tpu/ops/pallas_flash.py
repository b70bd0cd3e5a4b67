"""Pallas TPU flash attention — fused forward + backward kernels.

The reference gets fused attention from SDPA/FlashAttention-2/3 through torch
(reference: src/accelerate/accelerator.py:1658-1671 and the 128k-256k sequence
claims in docs/source/concept_guides/context_parallelism.md). This is the
TPU-native equivalent: an online-softmax kernel tiled for the MXU, streaming
KV blocks through VMEM so HBM traffic is O(S) per query block and the O(S²)
score matrix never materializes.

Design notes (what makes this TPU-first rather than a port):

- Grid ``(batch*q_heads, q_blocks, k_blocks)`` with the KV dimension innermost
  and marked "arbitrary" so the accumulator/max/sum live in VMEM scratch
  across KV steps; batch×head and q-block dims are "parallel".
- GQA is free: the kernel never repeats KV heads — the BlockSpec index map
  sends query head ``h`` to KV head ``h // (Hq//Hkv)``.
- Causal masking takes *dynamic* q/k position offsets via scalar prefetch
  (SMEM), so ring attention (parallel/cp.py) can call the same kernel on
  rotated KV chunks with traced offsets. Blocks entirely above the diagonal
  are skipped with a predicated region (no MXU work at runtime).
- Backward = two kernels: dQ accumulates over KV blocks; dK/dV accumulate
  over query blocks *and* the GQA head group (group folded into the innermost
  grid dim), so dK/dV come out already group-summed at KV-head resolution.
- The forward also emits the log-sum-exp rows; the custom_vjp accepts a
  cotangent for LSE, which is what makes the chunk-merging in ring attention
  differentiable end-to-end.

Parity is tested against ``blockwise_attention`` in tests/test_attention.py;
on non-TPU platforms the kernels run under the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.imports import is_tpu_available

NEG_INF = -1e30
_LANES = 128


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal, scale, block_q, block_k, sk_actual):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = q_off + qi * block_q  # global position of q row 0 of this block
    k_start = k_off + ki * block_k

    # Entire block above the diagonal ⇒ skip (predicated out at runtime, which
    # is what recovers the ~2× causal FLOP saving even with traced offsets).
    run = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)

        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (ki * block_k + col) < sk_actual  # key-padding (static tail)
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_start + row >= k_start + col)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                      # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Multiply by the mask: if every key so far is masked m_new stays
        # NEG_INF and exp(s - m_new) would be 1, not 0.
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)            # (block_q, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # Row stats are lane-replicated ((block_q, 128) rather than
        # (block_q, 1)) to satisfy TPU tiling — same layout jax's bundled
        # flash kernel uses for l/m.
        lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l_safe), lse_ref.shape[1:])


def _fwd(q3, k3, v3, offs, *, causal, scale, block_q, block_k, sk_actual,
         hq, hkv, interpret):
    bh, sqp, dp = q3.shape
    _, skp, _ = k3.shape
    nq, nk = sqp // block_q, skp // block_k
    rep = hq // hkv

    def kv_map(b, qi, ki, offs):
        return ((b // hq) * hkv + (b % hq) // rep, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, qi, ki, offs: (b, qi, 0)),
            pl.BlockSpec((1, block_k, dp), kv_map),
            pl.BlockSpec((1, block_k, dp), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, qi, ki, offs: (b, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, qi, ki, offs: (b, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dp), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, sk_actual=sk_actual,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sqp, dp), q3.dtype),
            jax.ShapeDtypeStruct((bh, sqp, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(offs, q3, k3, v3)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, causal, scale, block_q, block_k, sk_actual):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = q_off + qi * block_q
    k_start = k_off + ki * block_k
    run = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (ki * block_k + col) < sk_actual
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_start + row >= k_start + col)
        lse = lse_ref[0][:, :1]
        p = jnp.exp(s - lse) * mask.astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0][:, :1])
        dq_acc[...] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, causal, scale, block_q, block_k, sk_actual, nq):
    ki, s_idx = pl.program_id(1), pl.program_id(2)
    n_inner = pl.num_programs(2)
    qi = s_idx % nq
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(s_idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = q_off + qi * block_q
    k_start = k_off + ki * block_k
    run = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (ki * block_k + col) < sk_actual
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_start + row >= k_start + col)
        lse = lse_ref[0][:, :1]
        p = jnp.exp(s - lse) * mask.astype(jnp.float32)
        # dV += Pᵀ @ dO
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0][:, :1])
        # dK += scale · dSᵀ @ Q
        dk_acc[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(s_idx == n_inner - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, offs, out, lse, g_out, g_lse, *, causal, scale, block_q,
         block_k, sk_actual, hq, hkv, interpret):
    bh, sqp, dp = q3.shape
    bkv, skp, _ = k3.shape
    nq, nk = sqp // block_q, skp // block_k
    rep = hq // hkv

    g_out = g_out.astype(q3.dtype)
    # δ rows fold the LSE cotangent: dS = P∘(dP − δ) with
    # δ = rowsum(dO∘O) − Σ_lanes g_lse (∂lse/∂S = P, and lse is emitted
    # lane-replicated so its cotangent sums over the lane axis).
    delta = (jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
             - jnp.sum(g_lse.astype(jnp.float32), axis=-1))  # (bh, sqp)
    delta = jnp.broadcast_to(delta[..., None], (bh, sqp, _LANES))

    def kv_map(b, qi, ki, offs):
        return ((b // hq) * hkv + (b % hq) // rep, ki, 0)

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, qi, ki, offs: (b, qi, 0)),
            pl.BlockSpec((1, block_k, dp), kv_map),
            pl.BlockSpec((1, block_k, dp), kv_map),
            pl.BlockSpec((1, block_q, dp), lambda b, qi, ki, offs: (b, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, qi, ki, offs: (b, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, qi, ki, offs: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dp), lambda b, qi, ki, offs: (b, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale, block_q=block_q,
                          block_k=block_k, sk_actual=sk_actual),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sqp, dp), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_dq",
    )(offs, q3, k3, v3, g_out, lse, delta)

    # dK/dV: grid over KV heads; innermost dim folds (GQA group g, q block qi)
    # so the accumulators sum the whole group — dK/dV come out group-summed.
    def q_map(bkv_i, ki, s_idx, offs):
        g = s_idx // nq
        qi = s_idx % nq
        return ((bkv_i // hkv) * hq + (bkv_i % hkv) * rep + g, qi, 0)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bkv, nk, nq * rep),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), q_map),
            pl.BlockSpec((1, block_k, dp), lambda b, ki, s, offs: (b, ki, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, ki, s, offs: (b, ki, 0)),
            pl.BlockSpec((1, block_q, dp), q_map),
            pl.BlockSpec((1, block_q, _LANES), q_map),
            pl.BlockSpec((1, block_q, _LANES), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, dp), lambda b, ki, s, offs: (b, ki, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, ki, s, offs: (b, ki, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dp), jnp.float32),
            pltpu.VMEM((block_k, dp), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, block_q=block_q,
                          block_k=block_k, sk_actual=sk_actual, nq=nq),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bkv, skp, dp), k3.dtype),
            jax.ShapeDtypeStruct((bkv, skp, dp), v3.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_dkv",
    )(offs, q3, k3, v3, g_out, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing (statics closed over via a cached factory)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)  # bounded: variable seq lengths each cache one closure
def _make_flash(causal, scale, block_q, block_k, sk_actual, hq, hkv, interpret):
    kw = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k,
              sk_actual=sk_actual, hq=hq, hkv=hkv, interpret=interpret)

    @jax.custom_vjp
    def flash(q3, k3, v3, offs):
        return _fwd(q3, k3, v3, offs, **kw)

    def fwd(q3, k3, v3, offs):
        out, lse = _fwd(q3, k3, v3, offs, **kw)
        # Name the residuals so a selective remat policy
        # (save_only_these_names("flash_out", "flash_lse")) keeps them: they
        # are O(S) — unlike the O(S²) score matrix — so under remat the
        # backward reuses the kernel outputs instead of re-running the
        # forward kernel.
        from jax.ad_checkpoint import checkpoint_name

        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return (out, lse), (q3, k3, v3, offs, out, lse)

    def bwd(res, g):
        q3, k3, v3, offs, out, lse = res
        g_out, g_lse = g
        dq, dk, dv = _bwd(q3, k3, v3, offs, out, lse, g_out, g_lse, **kw)
        d_offs = np.zeros(offs.shape, jax.dtypes.float0)  # int arg: zero cotangent
        return dq, dk, dv, d_offs

    flash.defvjp(fwd, bwd)
    return flash


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def pallas_flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset=0,
    k_offset=0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """Fused attention returning ``(out, lse)``.

    q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) with Hq a multiple of Hkv.
    Returns out (B, Sq, Hq, D) in q's dtype and lse (B, Hq, Sq) float32 —
    the per-row log-sum-exp that ring attention uses to merge rotated chunks
    differentiably. ``q_offset``/``k_offset`` may be traced scalars.
    """
    if interpret is None:
        # Compiled on a TPU backend, Pallas interpreter elsewhere (CPU CI).
        interpret = not is_tpu_available()
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}")

    dp = max(_LANES, _ceil_to(d, _LANES))
    block_q = min(block_q, _ceil_to(sq, _LANES))
    block_k = min(block_k, _ceil_to(sk, _LANES))
    sqp = _ceil_to(sq, block_q)
    skp = _ceil_to(sk, block_k)

    def to3(x, h, sp):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], d)
        return jnp.pad(x, ((0, 0), (0, sp - x.shape[1]), (0, dp - d)))

    q3, k3, v3 = to3(q, hq, sqp), to3(k, hkv, skp), to3(v, hkv, skp)
    offs = jnp.asarray(
        jnp.stack([jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)])
    )
    scale = 1.0 / np.sqrt(d)
    flash = _make_flash(causal, scale, block_q, block_k, sk, hq, hkv, interpret)
    out3, lse3 = flash(q3, k3, v3, offs)
    out = out3[:, :sq, :d].reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
    return out, lse3[:, :, 0].reshape(b, hq, sqp)[:, :, :sq]


def pallas_flash_attention(q, k, v, *, causal: bool = True, q_offset=0, k_offset=0,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool | None = None):
    """Fused attention: (B, Sq, Hq, D) → (B, Sq, Hq, D). See
    :func:`pallas_flash_attention_with_lse` for the variant ring attention
    uses."""
    out, _ = pallas_flash_attention_with_lse(
        q, k, v, causal=causal, q_offset=q_offset, k_offset=k_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def merge_flash_chunks(out_a, lse_a, out_b, lse_b):
    """Merge two flash outputs over disjoint key sets.

    out: (B, S, H, D); lse: (B, H, S). Because out_i = acc_i / l_i and
    exp(lse_i) = l_i·exp(m_i), the exact merged output is
    Σ_i out_i · exp(lse_i − lse) with lse = logaddexp(lse_a, lse_b).
    """
    lse = jnp.logaddexp(lse_a, lse_b)
    wa = jnp.exp(lse_a - lse).transpose(0, 2, 1)[..., None]  # (B, S, H, 1)
    wb = jnp.exp(lse_b - lse).transpose(0, 2, 1)[..., None]
    out = out_a.astype(jnp.float32) * wa + out_b.astype(jnp.float32) * wb
    return out.astype(out_a.dtype), lse
