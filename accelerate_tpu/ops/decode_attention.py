"""Pallas TPU decode attention — one query row a slot against the rows the
slot holds, read from the cache where they lie.

A decode step's attention is bound by the read of K and V. An XLA dot over a
layer's ``(B, T_max, Hkv, D)`` slice reads all ``T_max`` rows of every slot
whatever the mask says afterwards, and the slice itself is lifted out of the
``(L, B, T_max, Hkv, D)`` stack first. This kernel is handed the **whole**
stacks, the plane's index and a per-slot bound as prefetched scalars, and
copies only blocks of rows under the bound out of HBM:

- One invocation, no grid over slots or blocks: it walks the slots, for each
  loops over the ``ceil(bound / block)`` blocks of its live rows, and a slot
  whose bound is 0 (free, done, still prefilling) starts no copy and writes
  zeros. The copies are double-buffered by hand across blocks *and* slots (the
  next slot's first block flies while this slot's last is scored), since a
  grid step costs more than a short slot's whole read.
- A block arrives as the cache holds it, ``(rows, Hkv, D)``, and is scored as
  the 2-D matrix ``(rows * Hkv, D)`` it already is in memory: one
  ``(Hq, D) x (D, rows * Hkv)`` product gives every query head against every
  key-value head, and the mask keeps column ``r * Hkv + h`` for query head
  ``j`` only where ``h == j // G`` and row ``r`` lies under the bound. That is
  ``Hkv`` times the FLOPs needed and no strided load by head; the MXU has the
  room, the step is bound by the read. G = 1 (MHA) and Hkv = 1 (MQA) are the
  same code.
- Online softmax in float32 across a slot's blocks; the weights go to the
  cache's dtype for the value product, as ``generation._attend`` has them.

The cache is read only: the step's scatter of the new rows comes first
(``kv_cache.cache_attend``). Nothing here knows which axis is planes, slots
or rows beyond the operand order its caller in ``kv_cache.py`` documents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_BLOCK_BYTES = 512 * 1024  # a block of rows, one side: long enough to run at the HBM's rate

# Tests set this (monkeypatch) to run the kernel under the Pallas interpreter,
# on whatever platform the program is lowered for (and ``block_rows``, for
# their tiny shapes). The program never does.
INTERPRET = False


def block_rows(t_max: int, kv_heads: int, head_dim: int, dtype) -> int | None:
    """Rows to a block of the kernel's reads over a cache of these shapes, or
    ``None`` where the kernel does not take them: the last two axes of a
    block have to fill whole tiles of the chip's memory (``D`` a multiple of
    128 lanes, ``Hkv`` of 8 sublanes) for the block to be the 2-D matrix it is
    scored as. A power of two, about ``_BLOCK_BYTES`` a side, at most
    ``t_max``."""
    if head_dim % 128 or kv_heads % 8:
        return None
    rows = max(8, _BLOCK_BYTES // (kv_heads * head_dim * np.dtype(dtype).itemsize))
    return min(1 << (rows.bit_length() - 1), t_max)


def rows_read(bound, block: int):
    """Rows of one slot the kernel copies out of HBM for ``bound`` live rows:
    the bound rounded up to whole blocks."""
    return -(-bound // block) * block


def _kernel(layer_ref, bound_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
            *, block, t_max, scale):
    n_slots, hq, d = q_ref.shape
    hkv = kbuf.shape[2]
    cols = block * hkv
    layer = layer_ref[0]

    def first_row(kb):
        # the last block of a ``t_max`` that no block divides starts early, and
        # the mask below drops the rows it shares with the block before it
        return jnp.minimum(kb * block, t_max - block)

    def copies(b, kb, buf):
        rows = pl.ds(first_row(kb), block)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, rows], kbuf.at[buf], sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, b, rows], vbuf.at[buf], sems.at[1, buf]))

    def next_live(b):
        """The first slot at or after ``b`` that holds rows, or ``n_slots``."""
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(s < n_slots, bound_ref[jnp.minimum(s, n_slots - 1)] <= 0),
            lambda s: s + 1, b)

    # column c of a block's scores: row c // hkv of the block, key-value head c % hkv
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
    q_head = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0)
    own_head = jax.lax.rem(col, hkv) == jax.lax.div(q_head, hq // hkv)

    o_ref[...] = jnp.zeros_like(o_ref)
    first = next_live(0)

    @pl.when(first < n_slots)
    def _():
        for c in copies(first, 0, 0):
            c.start()

    def one_slot(b, step):
        bound = bound_ref[b]
        n_blocks = pl.cdiv(bound, block)
        q = q_ref[b]

        def one_block(kb, carry):
            step, m, l, acc = carry
            buf = jax.lax.rem(step, 2)
            last = kb + 1 >= n_blocks
            nb = jnp.where(last, next_live(b + 1), b)
            nkb = jnp.where(last, 0, kb + 1)

            @pl.when(nb < n_slots)
            def _():
                for c in copies(nb, nkb, 1 - buf):
                    c.start()

            for c in copies(b, kb, buf):
                c.wait()
            k = kbuf[buf].reshape(cols, d)
            v = vbuf[buf].reshape(cols, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            # rows [kb * block, bound) of the slot, as columns of this block
            start = first_row(kb)
            seen = own_head & (col >= (kb * block - start) * hkv) & (col < (bound - start) * hkv)
            s = jnp.where(seen, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return step + 1, m_new, l, acc

        init = (step, jnp.full((hq, 1), NEG_INF, jnp.float32), jnp.zeros((hq, 1), jnp.float32),
                jnp.zeros(q.shape, jnp.float32))
        step, _, l, acc = jax.lax.fori_loop(0, n_blocks, one_block, init)

        @pl.when(n_blocks > 0)
        def _():
            o_ref[b] = (acc / l).astype(o_ref.dtype)

        return step

    jax.lax.fori_loop(0, n_slots, one_slot, 0)


@jax.named_scope("attn")
def decode_attention(q, ck, cv, layer, bound, *, interpret: bool = False):
    """Softmax attention of ``q`` (B, 1, Hq, D), one new query row a slot,
    over rows ``0 .. bound[b] - 1`` of plane ``layer`` of the whole cache
    buffers ``ck`` / ``cv`` (L, B, T_max, Hkv, D); query head ``j`` reads
    key-value head ``j // (Hq // Hkv)``. ``bound`` (B,) int32 is the query's
    position + 1 for a slot that decodes, and 0 for one that does not: that
    slot reads nothing and its output row is zeros. Returns (B, 1, Hq, D) in
    ``q``'s dtype."""
    b, sq, hq, d = q.shape
    t_max, hkv = ck.shape[2:4]
    assert sq == 1 and hq % hkv == 0 and ck.shape == cv.shape
    block = block_rows(t_max, hkv, d, ck.dtype)
    kernel = functools.partial(_kernel, block=block, t_max=t_max, scale=1.0 / np.sqrt(d))
    whole = lambda i, *_: (0, 0, 0)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec((b, hq, d), whole),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((b, hq, d), whole),
            scratch_shapes=[pltpu.VMEM((2, block, hkv, d), ck.dtype),
                            pltpu.VMEM((2, block, hkv, d), cv.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), bound.astype(jnp.int32),
      q.reshape(b, hq, d), ck, cv)
    return out.reshape(b, 1, hq, d)
