"""Context parallelism: ring attention over the ``cp`` mesh axis.

The reference reaches CP through torch's experimental DTensor
``context_parallel`` (reference: accelerator.py:1658-1671, 4110-4175;
rotation method allgather|alltoall). TPU-native design: sequences are sharded
over the ``cp`` axis by the batch PartitionSpec; attention runs under
``shard_map``, rotating KV chunks around the ring with ``ppermute`` while
accumulating online-softmax partials — compute overlaps the ICI transfer of
the next chunk, HBM stays O(S/cp) per chip. ``allgather`` mode gathers full
KV once instead (cheaper at small cp, reference's default).

Causal masking is handled by chunk offsets: query chunk i attends key chunk j
fully when j < i, causally when j == i, not at all when j > i.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.flash_attention import attention_stats, auto_flash_attention
from ..ops.pallas_flash import merge_flash_chunks, pallas_flash_attention_with_lse
from ..utils.imports import is_tpu_available


def _chunk_attention_with_lse(q_c, k_c, v_c, *, causal, q_offset, k_offset):
    """One KV-chunk attention returning (out (B,S,H,D), lse (B,H,S)).

    Pallas fused kernel on TPU (offsets ride scalar prefetch); the
    attention_stats jnp path elsewhere. Both are exact online-softmax partials
    that :func:`merge_flash_chunks` combines across ring rotations.
    """
    if is_tpu_available():
        return pallas_flash_attention_with_lse(
            q_c, k_c, v_c, causal=causal, q_offset=q_offset, k_offset=k_offset
        )
    acc, m, l = attention_stats(
        q_c, k_c, v_c, causal=causal, q_offset=q_offset, k_offset=k_offset
    )
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3)  # (B, S, H, D) f32
    return out, m + jnp.log(l_safe)


def _mesh_and_cfg():
    from ..state import AcceleratorState

    state = AcceleratorState()
    return state.mesh, state.parallelism_config


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mesh=None,
    rotate_method: Optional[str] = None,
    axis_name: str = "cp",
    batch_axes: Optional[tuple] = ("dp_replicate", "dp_shard"),
):
    """Sequence-parallel attention over the ``cp`` axis.

    q/k/v: (B, S, H, D) global arrays with S sharded over ``cp``. Falls back
    to single-chunk attention when the cp axis is trivial.
    """
    cfg = None
    if mesh is None:
        mesh, cfg = _mesh_and_cfg()
    if rotate_method is None:
        rotate_method = getattr(cfg, "cp_rotate_method", None) or "alltoall"
    cp = mesh.shape[axis_name]
    if cp == 1:
        # Global (non-manual) context: auto_flash_attention adds the
        # shard_map a Mosaic kernel needs under a multi-device mesh.
        return auto_flash_attention(q, k, v, causal=causal, mesh=mesh)

    # Manual SPMD region: batch over dp axes (or replicated — generation's
    # small batches pass batch_axes=()), seq over cp, heads over tp/sp.
    qkv_spec = P(batch_axes if batch_axes else None, axis_name, "tp", None)

    def _local(q_c, k_c, v_c):
        idx = jax.lax.axis_index(axis_name)
        s_local = q_c.shape[1]
        q_off = idx * s_local

        if rotate_method == "allgather":
            k_all = jax.lax.all_gather(k_c, axis_name, axis=1, tiled=True)
            v_all = jax.lax.all_gather(v_c, axis_name, axis=1, tiled=True)
            out, _ = _chunk_attention_with_lse(
                q_c, k_all, v_all, causal=causal, q_offset=q_off, k_offset=0
            )
            return out.astype(q_c.dtype)

        # Ring: hold q, rotate kv. After ``step`` rotations this device holds
        # the kv chunk originally owned by (idx - step) % cp. Chunk partials
        # (out, lse) merge exactly via logsumexp weights; XLA overlaps the
        # ppermute of the next chunk with the current chunk's kernel.
        def one_step(step, carry):
            out, lse, k_cur, v_cur = carry
            src = (idx - step) % cp
            o_i, lse_i = _chunk_attention_with_lse(
                q_c, k_cur, v_cur, causal=causal, q_offset=q_off,
                k_offset=src * s_local,
            )
            out, lse = merge_flash_chunks(out, lse, o_i.astype(jnp.float32), lse_i)
            perm = [(i, (i + 1) % cp) for i in range(cp)]
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            return out, lse, k_nxt, v_nxt

        b, s, h, d = q_c.shape
        carry = (
            jnp.zeros((b, s, h, d), jnp.float32),
            jnp.full((b, h, s), -1e30, jnp.float32),
            k_c,
            v_c,
        )
        for step in range(cp):  # cp is static & small: unrolled ring
            carry = one_step(step, carry)
        return carry[0].astype(q_c.dtype)

    shard = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return shard(q, k, v)
