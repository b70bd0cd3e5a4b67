"""Memory-efficient attention.

The reference delegates fused attention to SDPA/FlashAttention-2/3 via torch
(reference: SURVEY.md §2.3 CP/SP rows). Here:

- :func:`blockwise_attention` — online-softmax attention as a ``lax.scan``
  over KV blocks. Pure jnp, runs on every backend, O(S·B_k) memory instead of
  O(S²); this is what lets seq-2048×16-layer training fit a 16GB v5e chip
  without remat.
- :func:`flash_attention` — dispatcher: the Pallas TPU kernel on a TPU
  backend (ops/pallas_flash.py), the blockwise path on CPU.

Both support GQA (Hq a multiple of Hkv) and causal masking with query/key
position offsets (needed by ring attention's rotated chunks).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.environment import inside_shard_map
from ..utils.imports import is_tpu_available

logger = logging.getLogger(__name__)

NEG_INF = -1e30


def _repeat_kv(k, v, hq):
    hkv = k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset=0,
    k_offset=0,
    block_k: int = 512,
):
    """Online-softmax attention, scanning KV blocks.

    q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D). ``q_offset``/``k_offset`` are the
    global positions of element 0 of q/k — chunk-local attention inside ring
    attention passes these (they may be traced values).
    Returns (B, Sq, Hq, D).
    """
    b, sq, hq, d = q.shape
    k, v = _repeat_kv(k, v, hq)
    sk = k.shape[1]
    block_k = min(block_k, sk)
    num_blocks = (sk + block_k - 1) // block_k
    pad = num_blocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (n_blocks, B, block_k, H, D)
    kb = k.reshape(b, num_blocks, block_k, hq, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, num_blocks, block_k, hq, d).transpose(1, 0, 2, 3, 4)

    scale = 1.0 / np.sqrt(d)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        acc, m, l = carry
        blk_idx, k_blk, v_blk = xs
        k_pos = k_offset + blk_idx * block_k + jnp.arange(block_k)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
        # padding-key mask (sk = original unpadded length), then causal mask
        valid = (blk_idx * block_k + jnp.arange(block_k)) < sk
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        if causal:
            cmask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(cmask[None, None], logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(logits - m_new[..., None])
        l_corr = l * jnp.exp(m - m_new)
        l_new = l_corr + jnp.sum(p, axis=-1)
        acc = acc * jnp.exp(m - m_new)[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk
        ).astype(jnp.float32)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (jnp.arange(num_blocks), kb, vb)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, Sq, H, D)


def attention_stats(q, k, v, *, causal=True, q_offset=0, k_offset=0,
                    kv_valid_len=None):
    """One-chunk attention returning ONLINE-SOFTMAX STATS instead of the
    normalized output: (acc[B,H,Sq,D] fp32, m[B,H,Sq], l[B,H,Sq]). Ring
    attention merges these across KV rotations; cp_generation's decode uses
    ``kv_valid_len`` (traced ok) to mask unwritten tail-cache slots."""
    b, sq, hq, d = q.shape
    k, v = _repeat_kv(k, v, hq)
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(sq)
    k_pos = k_offset + jnp.arange(k.shape[1])
    if causal:
        cmask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(cmask[None, None], logits, NEG_INF)
    if kv_valid_len is not None:
        slot = jnp.arange(k.shape[1], dtype=jnp.int32)
        logits = jnp.where((slot < kv_valid_len)[None, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return acc, m, l


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0, k_offset=0,
                    block_q: int = 512, block_k: int = 512, interpret=None):
    """Fused attention kernel dispatcher. Uses the Pallas TPU kernel
    (ops/pallas_flash.py) on a TPU backend, the blockwise jnp path elsewhere
    (CPU CI; logged once). The choice is by platform only: a kernel that
    fails to compile on the chip raises, it never gives way to blockwise.

    The Pallas call is a Mosaic custom call with no GSPMD partitioning rule:
    call this either on a single device, or from inside a ``shard_map``
    (parallel/cp.py, parallel/sp.py). Model code in the *global* SPMD program
    should use :func:`auto_flash_attention`, which adds the shard_map."""
    from .pallas_flash import pallas_flash_attention

    if is_tpu_available():
        return pallas_flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, k_offset=k_offset,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    _log_cpu_path_once()
    return blockwise_attention(
        q, k, v, causal=causal, q_offset=q_offset, k_offset=k_offset, block_k=block_k
    )


def auto_flash_attention(q, k, v, *, causal: bool = True, mesh=None):
    """Model-layer fused attention: wraps :func:`flash_attention` in a
    ``shard_map`` over the (dp × tp) mesh axes when a multi-device mesh is
    active, because GSPMD cannot partition a Mosaic custom call. Degenerates
    to the plain dispatcher on one device, on CPU (blockwise partitions fine
    under GSPMD), or when already inside a manual context (pp/cp/sp).

    A batch that does not divide the dp axes cannot be split evenly by
    shard_map (e.g. a bs-1 eval forward on a four-chip host), so it runs
    ``blockwise_attention`` under GSPMD instead — with a WARNING on every
    trace, never quietly: on a training step that warning means the kernel
    is not in the program."""
    if not is_tpu_available() or inside_shard_map():
        return flash_attention(q, k, v, causal=causal)
    if mesh is None:
        from ..state import AcceleratorState

        state = AcceleratorState()
        mesh = getattr(state, "mesh", None)
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal)

    from jax.sharding import PartitionSpec as P

    dp_cap = mesh.shape.get("dp_replicate", 1) * mesh.shape.get("dp_shard", 1)
    if q.shape[0] % dp_cap != 0:
        logger.warning(
            "auto_flash_attention: batch %d does not divide the dp axes (%d) — "
            "this trace uses blockwise_attention, NOT the Pallas kernel. Pad "
            "the batch to a multiple of %d to get the kernel.",
            q.shape[0], dp_cap, dp_cap,
        )
        return blockwise_attention(q, k, v, causal=causal)

    tp = mesh.shape.get("tp", 1)
    # Heads shard over tp only when BOTH q and kv head counts divide: the
    # kernel's GQA group mapping assumes q and kv heads are split together.
    heads = "tp" if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None
    spec = P(("dp_replicate", "dp_shard"), None, heads, None)
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )(q, k, v)


@functools.lru_cache(maxsize=1)
def _log_cpu_path_once():
    logger.info(
        "flash_attention: no TPU backend — using the blockwise jnp path "
        "(memory-efficient but unfused)."
    )
