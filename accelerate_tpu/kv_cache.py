"""The KV cache and everything that knows how it is laid out.

One dense buffer a side: ``k`` and ``v`` are ``(L, B, T_max, Hkv, D)`` — planes
(one per layer, or one per pass and layer where a model runs its stack more
than once: pass ``u``'s layer ``l`` is plane ``u * layers + l``), then slots
(batch rows), then each slot's ``T_max`` private rows. There are no
pages and no sharing: a slot owns its rows from 0 to ``T_max`` whether it has
written them or not. An int8 cache holds each side as a :class:`QuantPages`
pair of leaves (data and one scale per row and head) with the same leading
axes. Nothing outside this module indexes a cache leaf by axis number or asks
whether a side is one leaf or two: the forwards, the serving engines and the
planner go through :class:`KVCache`'s operations, :func:`cache_step`, for
a step of one query row a slot :func:`cache_attend`, which hands the decode
kernel (``ops/decode_attention.py``) the buffers in this order of axes, and
for a prompt chunk that rides such a step :func:`slot_step`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .ops import decode_attention as _decode


class QuantPages(NamedTuple):
    """int8 K or V with per-row absmax scales — the KV-cache twin of the
    ``QuantizedTensor`` weight pattern (utils/quantization.py). A "page" here
    is one row's head vector (``D`` values under one scale), not a block of
    rows. Rides inside ``KVCache.k``/``.v`` as a pytree subtree, so the layer
    scan, the disagg handoff and ``device_put`` all work unchanged; attention
    dequantizes adjacent to the dot (see ``generation._attend``) so the cache
    crosses HBM and the handoff link as int8 (~4x fewer bytes than bf16/fp32)."""

    data: jax.Array   # int8, same layout as the float cache it replaces
    scale: jax.Array  # f32, data.shape[:-1] + (1,) — one scale per page

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def shape(self):
        return self.data.shape

    @property
    def nbytes(self):
        return self.data.nbytes + self.scale.nbytes


def quantize_kv_page(x) -> QuantPages:
    """Symmetric int8 quantization over the trailing (head_dim) axis."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, jnp.finfo(jnp.float32).tiny) / 127.0
    data = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return QuantPages(data.astype(jnp.int8), scale)


def dequantize_kv_page(pages: QuantPages, dtype):
    return pages.data.astype(dtype) * pages.scale.astype(dtype)


def float_pages(x, dtype):
    """A layer's K or V as attention contracts it: ``QuantPages`` dequantized
    to ``dtype``, a float cache as it is."""
    return dequantize_kv_page(x, dtype) if isinstance(x, QuantPages) else x


class CacheSpec(NamedTuple):
    """What a config asks of its cache. For encoder-decoder configs these
    describe the DECODER self-attention cache."""

    layers: int  # planes of the buffer: passes x the model's layers
    kv_heads: int
    head_dim: int
    max_positions: int  # T5's relative positions are unbounded: 2**30
    passes: int = 1  # times the stack runs over its one set of weights


def cache_spec(cfg) -> CacheSpec:
    if hasattr(cfg, "n_dec"):  # T5
        return CacheSpec(cfg.n_dec, cfg.num_heads, cfg.d_kv, 2**30)
    if hasattr(cfg, "decoder_layers"):  # Whisper
        return CacheSpec(
            cfg.decoder_layers, cfg.decoder_attention_heads,
            cfg.decoder_head_dim, cfg.max_target_positions,
        )
    layers = getattr(cfg, "num_hidden_layers", None) or cfg.n_layer
    kv_heads = (
        getattr(cfg, "num_key_value_heads", None)
        or getattr(cfg, "num_attention_heads", None)
        or cfg.n_head
    )
    max_pos = getattr(cfg, "max_position_embeddings", None) or cfg.n_positions
    passes = getattr(cfg, "total_ut_steps", 1)  # each pass keeps its own K and V
    return CacheSpec(passes * layers, kv_heads, cfg.head_dim, max_pos, passes)


class KVCache(NamedTuple):
    """Every layer's K and V in one buffer each. A cached forward carries both
    whole through its layer loop and writes only the new rows, in place
    (:func:`cache_step`); a jitted caller that donates the cache gets it back
    as the same buffers. ``cache._replace(length=...)`` is the same buffers
    under another length."""

    k: jax.Array  # (L, B, T_max, Hkv, D), or QuantPages of that layout
    v: jax.Array
    # () int32 — tokens written so far (batch-global), or (B,) int32 for a
    # slot cache (serving.py) where every row advances independently.
    length: jax.Array

    @property
    def n_layers(self) -> int:
        return self.k.shape[0]

    @property
    def t_max(self) -> int:
        return self.k.shape[2]

    @property
    def dtype(self):
        """Element dtype of the stored K and V (int8 for quantized pages)."""
        return self.k.dtype

    @property
    def quantized(self) -> bool:
        return isinstance(self.k, QuantPages)

    @property
    def holds_nan(self) -> bool:
        return jnp.issubdtype(self.dtype, jnp.floating)

    def _map(self, fn, *others) -> "KVCache":
        """``fn`` over every leaf of K and of V (one leaf a side, or two) and
        of ``others``' beside them; the lengths stay."""
        return KVCache(jax.tree.map(fn, self.k, *(o.k for o in others)),
                       jax.tree.map(fn, self.v, *(o.v for o in others)), self.length)

    def take_slot(self, slot, length) -> "KVCache":
        """Slot ``slot`` alone, as a ``(L, 1, T_max, ...)`` cache whose one row
        stands at ``length``: what a forward needs to write one slot's rows."""
        sub = self._map(lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1))
        return sub._replace(length=length[None])

    def put_slot(self, slot, sub: "KVCache") -> "KVCache":
        """:meth:`take_slot`'s inverse: ``sub``'s one row back at ``slot``; no
        other slot moves. The lengths stay as they were: the caller commits
        what the slot really advanced by (``_replace(length=...)``)."""
        return self._map(
            lambda a, s: jax.lax.dynamic_update_slice_in_dim(a, s, slot, axis=1), sub)

    def rows(self, start, size: int) -> tuple:
        """Rows ``[start, start + size)`` of every slot, all layers, as a
        ``(k_rows, v_rows)`` pair: what the disagg handoff ships."""
        cut = self._map(lambda a: jax.lax.dynamic_slice_in_dim(a, start, size, axis=2))
        return cut.k, cut.v

    def insert_rows(self, k_rows, v_rows, slot, start, valid) -> "KVCache":
        """One slot's shipped rows (:meth:`rows` of a one-slot cache) written
        at ``(slot, start)``, and ``start + valid`` committed as its length."""
        zero = jnp.zeros((), jnp.int32)

        def put(a, rows):
            return jax.lax.dynamic_update_slice(a, rows, (zero, slot, start, zero, zero))

        return KVCache(jax.tree.map(put, self.k, k_rows), jax.tree.map(put, self.v, v_rows),
                       self.length.at[slot].set(start + valid))

    def fill_slot(self, slot, value) -> "KVCache":
        """Every stored element of ``slot`` set to ``value``, all layers."""
        return self._map(lambda a: a.at[:, slot].set(value))

    def take_batch(self, rows) -> "KVCache":
        """The batch axis gathered at ``rows``: beam search's tiling and reorder."""
        return self._map(lambda a: jnp.take(a, rows, axis=1))


def slots_partition(axis: str) -> PartitionSpec:
    """The spec that shards a cache side's slot axis over mesh axis ``axis``."""
    return PartitionSpec(None, axis)


def init_cache(cfg, batch: int, max_len: int, dtype=None) -> KVCache:
    spec = cache_spec(cfg)
    shape = (spec.layers, batch, max_len, spec.kv_heads, spec.head_dim)
    dtype = dtype or cfg.dtype

    def side():
        if np.dtype(dtype) == np.int8:
            # int8 data + per-page f32 scales (ones so an unwritten page
            # dequantizes to exact zeros, like the float cache).
            return QuantPages(jnp.zeros(shape, jnp.int8),
                              jnp.ones(shape[:-1] + (1,), jnp.float32))
        return jnp.zeros(shape, dtype)

    return KVCache(side(), side(), jnp.zeros((), jnp.int32))


def init_slot_cache(cfg, n_slots: int, max_len: int, dtype=None) -> KVCache:
    """Slot cache (serving.py): same dense buffers as :func:`init_cache` but
    ``length`` is a per-slot ``(n_slots,)`` vector, so every row advances
    independently — one request retiring never stalls its neighbors."""
    cache = init_cache(cfg, n_slots, max_len, dtype)
    return cache._replace(length=jnp.zeros((n_slots,), jnp.int32))


def kv_bytes_per_token(cfg, dtype=None) -> int:
    """Bytes one token's K and V occupy across every layer, scale leaves of an
    int8 cache included: read off what :func:`init_cache` allocates for one
    slot of one row."""
    one = jax.eval_shape(lambda: init_cache(cfg, 1, 1, dtype))
    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree.leaves((one.k, one.v)))


# The named scope below (like ``attn``, ``mlp``, ``moe.router``, ``moe.experts``
# and ``lm_head`` in generation.py) changes the HLO's metadata only: a device
# trace can group a step's ops by them, where the fusions' own names say shapes.


@jax.named_scope("cache_write")
def _cache_write(buf, new, layer, start, slot=None):
    """Write ``new`` (B, S, Hkv, D) into the whole cache buffer ``buf``
    (L, B, T, Hkv, D), in place, at layer ``layer`` and row offset ``start``:
    a scalar (one ``dynamic_update_slice`` at ``(layer, 0, start, 0, 0)``) or
    a per-row vector (a scatter at ``[layer, row, start[row] + s]``, the
    slot cache's path; with ``slot``, the one row of ``new`` goes to that
    slot) — ``start.ndim`` decides, at trace time. Only the new
    rows move: ``buf`` rides the layer loop's carry, so the result aliases
    it. A ``QuantPages`` cache quantizes the new pages here, writing data
    and scale leaves at the same offsets."""
    if isinstance(buf, QuantPages):
        q = quantize_kv_page(new)
        return QuantPages(_cache_write(buf.data, q.data, layer, start, slot),
                          _cache_write(buf.scale, q.scale, layer, start, slot))
    new = new.astype(buf.dtype)
    if getattr(start, "ndim", 0) == 1:
        b, s = new.shape[:2]
        rows = (jnp.arange(b, dtype=jnp.int32)[:, None] if slot is None
                else jnp.reshape(slot, (1, 1)))
        cols = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        return buf.at[layer, rows, cols].set(new)
    return jax.lax.dynamic_update_slice(buf, new[None], (layer, 0, start, 0, 0))


def cache_step(ck, cv, k_new, v_new, layer, start):
    """One layer's turn at the cache, the one way every cached forward
    reaches it: write the new K and V rows into the whole buffers
    (:func:`_cache_write`), then read that layer's (B, T, Hkv, D) slices back
    for attention. Returns ``(ck, cv, k_layer, v_layer)``. The buffers are the
    scan's carry and nothing mutates the slices, so no step copies the cache
    or stacks a layer's slice into a second one."""
    ck, cv = _cache_write(ck, k_new, layer, start), _cache_write(cv, v_new, layer, start)
    k_i, v_i = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), (ck, cv))
    return ck, cv, k_i, v_i


def slot_step(ck, cv, k_new, v_new, layer, slot, start):
    """One slot's turn at one layer for a prompt chunk that rides a decode
    step: write its (1, C, Hkv, D) rows at rows ``start ..`` of slot
    ``slot`` of plane ``layer``, in place, and read that slot's plane back,
    ``(1, T_max, Hkv, D)`` a side, for attention. Returns ``(ck, cv,
    k_slot, v_slot)``; no other slot is read or moved."""
    ck, cv = (_cache_write(a, new, layer, start[None], slot)
              for a, new in ((ck, k_new), (cv, v_new)))
    k_s, v_s = jax.tree.map(
        lambda a: jax.lax.dynamic_slice(a, (layer, slot, 0, 0, 0), (1, 1) + a.shape[2:])[0],
        (ck, cv))
    return ck, cv, k_s, v_s


def decode_block_rows(side) -> int | None:
    """Rows to a block of the decode kernel's reads over a cache side ``side``
    (``cache.k``; an array or a tracer of one), or ``None`` where a step of
    one query row a slot keeps :func:`cache_step`: int8 pages (dequantized
    whole beside the dot), shapes the kernel does not tile
    (``decode_attention.block_rows``), and a buffer laid over the devices of a
    mesh (:func:`slots_partition`; a Mosaic call has no partitioning rule)."""
    if isinstance(side, QuantPages) or not jnp.issubdtype(side.dtype, jnp.floating):
        return None
    if jax.typeof(side).sharding.mesh.size > 1:
        return None
    t_max, kv_heads, head_dim = side.shape[2:]
    return _decode.block_rows(t_max, kv_heads, head_dim, side.dtype)


def decode_reads(cache: KVCache) -> int | None:
    """For a cache where it is placed: the block of rows by which a decode
    step's attention reads it (:func:`cache_attend` with the kernel: a cache
    that :func:`decode_block_rows` takes, on a TPU), or ``None`` where such a
    step reads every row of every slot."""
    side = jax.tree.leaves(cache.k)[0]
    on_tpu = _decode.INTERPRET or next(iter(side.devices())).platform == "tpu"
    return decode_block_rows(cache.k) if on_tpu else None


def cache_attend(ck, cv, q, k_new, v_new, layer, start, bound, attend):
    """A layer's turn at a cache that :func:`decode_block_rows` takes, for a
    step of one query row a slot ``q`` (B, 1, Hq, D): returns
    ``(ck, cv, out)``. Where the program is lowered for a TPU, the new rows
    are written in place (:func:`_cache_write`) and the kernel is handed the
    whole buffers, the plane ``layer`` and ``bound`` (B,): it reads rows
    ``0 .. bound[b] - 1`` of slot ``b`` where they lie, nothing of a slot whose
    bound is 0 (whose output row is zeros), and no layer's slice is made.
    Lowered for anything else it is :func:`cache_step` and then
    ``attend(q, k_layer, v_layer)`` over the slice, ``bound`` unused. The
    choice is the lowering's, not the process's
    (``jax.lax.platform_dependent``): a program compiled for a described chip
    is the program that chip runs."""

    def kernel_over_the_stack(ck, cv):
        ck, cv = _cache_write(ck, k_new, layer, start), _cache_write(cv, v_new, layer, start)
        return ck, cv, _decode.decode_attention(q, ck, cv, layer, bound,
                                                interpret=_decode.INTERPRET)

    def dots_over_the_slice(ck, cv):
        ck, cv, k_i, v_i = cache_step(ck, cv, k_new, v_new, layer, start)
        return ck, cv, attend(q, k_i, v_i)

    if _decode.INTERPRET:
        return kernel_over_the_stack(ck, cv)
    return jax.lax.platform_dependent(ck, cv, tpu=kernel_over_the_stack,
                                      default=dots_over_the_slice)
