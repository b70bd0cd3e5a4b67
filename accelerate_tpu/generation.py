"""Autoregressive generation with a KV cache.

The reference delegates generation to transformers' ``model.generate`` over
its wrapped modules (its big-model benchmarks are generate loops —
reference: benchmarks/big_model_inference/README.md). A TPU-native framework
owns the loop: a static-shape KV cache, ONE jitted decode step reused for
every token (no per-position recompiles), and RoPE/GQA handled at the cache
level.

Design:

- The cache (kv_cache.py, which alone knows its layout) is an explicit pytree
  threaded through pure functions — no flax mutable collections, so the same
  code runs under ``jit``, ``shard_map``, and the big-model streaming path.
- One loop (:func:`_forward_cached`) carries it through a ``lax.scan`` over
  the stacked layer params and hands each layer's block one callable that
  writes the layer's new K/V rows and attends over the layer with a
  static-shape position mask; a family supplies its embedding, block and head.
- Attention math mirrors models/llama.py exactly (RMSNorm → fused QKV
  projections → RoPE at absolute positions → GQA by head repetition → SwiGLU
  MLP); parity with ``module.apply`` is pinned by tests/test_generation.py.
- Sampling: greedy, temperature, top-k, nucleus (top-p) — composable, jitted.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .models.llama import (
    apply_partial_rope,
    apply_rope,
    layer_norm,
    rms_norm,
    rotary_embedding,
    scale_residual,
)
from . import kv_cache  # cache_step is called through the module: tests substitute it
from .kv_cache import (  # noqa: F401  (re-exported: the cache's public names)
    KVCache,
    QuantPages,
    cache_spec,
    dequantize_kv_page,
    float_pages,
    init_cache,
    init_slot_cache,
    quantize_kv_page,
)
from .utils.quantization import DecodeQuant, dequantize_decode_kernel


def _row_positions(start, b: int, s: int) -> jax.Array:
    """(B, S) absolute cache positions for tokens appended at ``start`` —
    a () scalar (batch-global cache) or a (B,) per-slot vector."""
    offs = jnp.arange(s, dtype=jnp.int32)[None, :]
    if getattr(start, "ndim", 0) == 1:
        return start[:, None] + offs
    return jnp.broadcast_to(start + offs, (b, s))


# ---------------------------------------------------------------------------
# Llama block math on raw param trees (stacked nn.scan layout)
# ---------------------------------------------------------------------------


def _kernel(k, dtype):
    """A weight in compute dtype. ``DecodeQuant`` (int8 weight-only decode,
    utils/quantization.py) dequantizes HERE — adjacent to the matmul — so
    XLA fuses convert×scale into the dot and the weight rides HBM as int8
    (the bandwidth that dominates batch-1 decode)."""
    if isinstance(k, DecodeQuant):
        return dequantize_decode_kernel(k, dtype)
    return k.astype(dtype)


def _proj(x, kernel):
    # kernel (H, heads, D) — the DenseGeneral layout of models/llama.py.
    return jnp.einsum("bsh,hnd->bsnd", x, _kernel(kernel, x.dtype))


def _out_proj(x, kernel):
    # kernel (heads, D, H).
    return jnp.einsum("bsnd,ndh->bsh", x, _kernel(kernel, x.dtype))


def _dense(p, x):
    y = x @ _kernel(p["kernel"], x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


@jax.named_scope("mlp")
def _mlp(cfg, p, x):
    from .models.llama import activation_fn

    act = activation_fn(getattr(cfg, "hidden_act", "silu"))
    up = _dense(p["up_proj"], x)
    if getattr(cfg, "mlp_gated", True):
        hidden = act(_dense(p["gate_proj"], x)) * up
    else:  # plain 2-layer MLP (StarCoder2-style chassis knob)
        hidden = act(up)
    return _dense(p["down_proj"], hidden)


def _norm_w(cfg, w, like):
    """RMSNorm weight in compute dtype, honoring Gemma's (1+w) convention."""
    plus1 = 1.0 if getattr(cfg, "rms_norm_plus_one", False) else 0.0
    return (w + plus1).astype(like.dtype) if plus1 else w.astype(like.dtype)


def _chassis_norm(cfg, p, x):
    """Layer norm honoring the chassis knob: rmsnorm (default) or
    mean-centered layernorm-with-bias — same numerics as training via the
    shared functional helper (models/llama.py layer_norm)."""
    if getattr(cfg, "norm_type", "rmsnorm") == "layernorm":
        return layer_norm(x, p["weight"], p["bias"], cfg.rms_norm_eps)
    return rms_norm(x, _norm_w(cfg, p["weight"], x), cfg.rms_norm_eps)


def _embed_tokens(cfg, embed, ids):
    x = jnp.take(embed, ids, axis=0).astype(cfg.dtype)
    if getattr(cfg, "scale_embeddings", False):  # Gemma normalizer
        x = x * jnp.asarray(np.sqrt(cfg.hidden_size), cfg.dtype)
    em = getattr(cfg, "embedding_multiplier", 1.0)
    if em != 1.0:  # Granite scaling
        x = x * jnp.asarray(em, cfg.dtype)
    return x


def _qkv_proj(attn, hn, cos, sin, rotary_dim=None, heads=None):
    """q/k (roped) + v projections for one Llama-family layer; carries
    Qwen2-style attention biases when present. ``rotary_dim`` < head_dim
    rotates only the leading dims (StableLM-style partial rotary); ``cos``
    None rotates nothing (OPT). A layer in the serving layout
    (:func:`fuse_qkv_params`) holds one ``qkv_proj`` kernel
    ``(H, (Hq + 2·Hkv)·D)``: one dot, whose output is cut into q, k and v;
    ``heads`` = ``(Hq, D)`` says where, as the fused width alone does not."""
    def proj(name):
        y = _proj(hn, attn[name]["kernel"])
        if "bias" in attn[name]:
            y = y + attn[name]["bias"].astype(y.dtype)
        return y

    def rope(y):
        if cos is None:
            return y
        rd = y.shape[-1] if rotary_dim is None else rotary_dim
        return apply_partial_rope(y, cos, sin, rd)

    if "qkv_proj" in attn:
        if heads is None:
            raise ValueError("_qkv_proj: a fused qkv_proj kernel needs heads=(Hq, D) "
                             "to be cut into q, k and v")
        hq, d = heads
        y = _dense(attn["qkv_proj"], hn)
        hkv = (y.shape[-1] // d - hq) // 2
        q, k, v = (a.reshape(*y.shape[:-1], -1, d)
                   for a in jnp.split(y, [hq * d, (hq + hkv) * d], axis=-1))
        return rope(q), rope(k), v
    return rope(proj("q_proj")), rope(proj("k_proj")), proj("v_proj")


# ---------------------------------------------------------------------------
# The serving layout of the attention input projections
# ---------------------------------------------------------------------------

_QKV = ("q_proj", "k_proj", "v_proj")


def _fusable(attn) -> bool:
    """An attention dict whose q, k and v are plain stacked ``(L, H, n, D)``
    kernels of one dtype and leading shape, with a bias on all three or on
    none. ``DecodeQuant`` kernels are not: they keep the three leaves."""
    if not isinstance(attn, dict) or not all(isinstance(attn.get(n), dict) for n in _QKV):
        return False
    ps = [attn[n] for n in _QKV]
    ks = [p.get("kernel") for p in ps]
    if any(isinstance(k, DecodeQuant) or getattr(k, "ndim", None) != 4
           or set(p) - {"kernel", "bias"} for k, p in zip(ks, ps)):
        return False
    return (len({(k.shape[:2], k.dtype) for k in ks}) == 1
            and sum("bias" in p for p in ps) in (0, 3))


def _fuse_qkv(qkv):
    """q, k and v of one attention as ``{"kernel": (L, H, (Hq + 2·Hkv)·D),
    "bias": (L, (Hq + 2·Hkv)·D)}``: each reshaped, then concatenated along
    the output axis."""
    ps = [qkv[n] for n in _QKV]
    fused = {"kernel": jnp.concatenate(
        [p["kernel"].reshape(*p["kernel"].shape[:2], -1) for p in ps], axis=-1)}
    if "bias" in ps[0]:
        fused["bias"] = jnp.concatenate(
            [p["bias"].reshape(p["bias"].shape[0], -1) for p in ps], axis=-1)
    return fused


def _qkv_layout(params, fuse):
    """``params`` with ``fuse(q, k and v)`` in place of each fusable
    attention's three leaves (``fuse`` returns None: the site keeps them),
    and whether any site was fused. Every other leaf is the same array, and
    a subtree with nothing fused is the same object."""
    if _fusable(params):
        qkv = fuse({n: params[n] for n in _QKV})
        if qkv is None:
            return params, False
        return {**{n: p for n, p in params.items() if n not in _QKV}, "qkv_proj": qkv}, True
    if not isinstance(params, dict):
        return params, False
    out = {key: _qkv_layout(sub, fuse) for key, sub in params.items()}
    if not any(fused for _, fused in out.values()):
        return params, False
    return {key: tree for key, (tree, _) in out.items()}, True


def _fuse_in_place(qkv):
    """One site's fused leaves on its kernels' one placement, where they are
    whole on each device; None (the site keeps three leaves) for host
    arrays, kernels placed apart, or kernels split over devices."""
    shardings = {getattr(qkv[n]["kernel"], "sharding", None) for n in _QKV}
    if len(shardings) != 1:
        return None
    (s,) = shardings
    if s is None or not s.is_fully_replicated:
        return None
    if isinstance(s, jax.sharding.NamedSharding):  # its spec names the old rank
        s = jax.sharding.NamedSharding(s.mesh, jax.sharding.PartitionSpec(),
                                       memory_kind=s.memory_kind)
    return jax.jit(_fuse_qkv, out_shardings=s)(qkv)


def fuse_qkv_params(params):
    """The layout ``ServingEngine`` installs for a plan in
    :data:`FUSED_QKV_PLANS`: every Llama-family (or OPT) attention's
    ``q_proj``, ``k_proj`` and ``v_proj`` kernels, stacked ``(L, H, n, D)``,
    become one ``qkv_proj`` kernel ``(L, H, (Hq + 2·Hkv)·D)`` (biases
    alike), and the three leaves leave the tree. Returns the tree and
    whether any attention was fused.

    Why: ``_proj``'s ``(H, n, D)`` contraction makes XLA copy each layer's
    slice out of the stack and lay it out by head before the dot (or copy
    the whole stacks, once a step, in a looped model); a 2-D kernel's slice
    fuses into its dot, which reads it where the stack holds it, as the
    MLP's kernels are read. The mathematics is the same, column for column.

    One jitted call a site over its q, k and v leaves alone (every other
    leaf is the same array), so a version costs one copy of those at install
    and nothing in a step; the fused leaves keep the version's sharding.
    Idempotent; ``DecodeQuant`` kernels, host arrays and kernels split over
    devices keep the three leaves (:func:`_qkv_proj` reads either layout)."""
    return _qkv_layout(params, _fuse_in_place)


@jax.named_scope("attn")
def _attend(q, k, v, q_positions, kv_valid=None):
    """q (B,Sq,Hq,D) vs cached k/v (B,T,Hkv,D); causal wrt absolute cache
    slots. The causal bound kv_pos <= q_position also excludes unwritten
    cache slots (every query position is < cache length after the write).
    ``kv_valid`` (B, T) additionally masks slots holding left-padding.
    K and V are read as cached, Hkv heads wide: query head j reads KV head
    j // G, G = Hq // Hkv, by folding each group into the query rows
    (B,Hkv,G*Sq,D) — one batched matmul per KV head, no repeated copy of K
    or V, so the bytes a step reads scale with B x T x Hkv, not Hq. G == 1
    (MHA) and Hkv == 1 (MQA) are the same einsum with an axis of one.
    ``QuantPages`` k/v dequantize HERE — adjacent to the attention dots, the
    same fusion-adjacency trick as ``_kernel`` — so the cache rides HBM as
    int8 and XLA fuses convert×scale into the einsum."""
    k, v = float_pages(k, q.dtype), float_pages(v, q.dtype)
    b, sq, hq, d = q.shape
    t, hkv = k.shape[1:3]
    g = hq // hkv
    q = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4).reshape(b, hkv, g * sq, d)
    scale = 1.0 / np.sqrt(d)
    logits = (jnp.einsum("bhmd,bkhd->bhmk", q, k) * scale).reshape(b, hkv, g, sq, t)
    kv_pos = jnp.arange(t, dtype=jnp.int32)[None, :]  # (1, T)
    causal = kv_pos[None, :, :] <= q_positions[:, :, None]  # (B, Sq, T)
    if kv_valid is not None:
        causal = causal & kv_valid[:, None, :].astype(bool)
    logits = jnp.where(causal[:, None, None], logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhmk,bkhd->bhmd", probs.reshape(b, hkv, g * sq, t), v)
    return out.reshape(b, hkv, g, sq, d).transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)


class PromptChunk(NamedTuple):
    """A prompt chunk that rides a decode step (:func:`_forward_cached`'s
    ``chunk``): ``ids`` (1, C) int32 written into slot ``slot`` at rows
    ``start ..``, of which the first ``valid`` are the prompt's (all ()
    int32)."""

    ids: jax.Array
    slot: jax.Array
    start: jax.Array
    valid: jax.Array


def _forward_cached(decoder, cfg, params, input_ids, cache: KVCache, return_all=False,
                    pad_offset=None, kv_valid=None, attn_bound=None, chunk=None):
    """Run ``input_ids`` (appended at cache.length) through all layers,
    returning (logits, new_cache) — last-token logits, or every position's
    with ``return_all`` (speculative verification needs them). The one loop
    that carries the cache through the layers, whatever the family:
    ``decoder(cfg, params, input_ids, pos_ids)`` returns what is the family's
    own, ``(x, layers, block, norm, head, *xs)`` — the embedded tokens, the
    stacked layer params, ``block(p, h, attend, *xs) -> h``, the final norm
    over every position, the head, and any further per-layer inputs of the
    block. ``attend(q, k_new, v_new)`` is a layer's whole dealing with the
    cache: it writes the new K/V rows and attends over the layer
    (``cache_step`` then ``_attend``), so no block holds the buffers.

    A step of one token a row with nothing masked but the causal bound, over
    a cache the decode kernel takes (``kv_cache.decode_block_rows``: float, on
    one device, tile-shaped), goes through ``kv_cache.cache_attend`` instead:
    lowered for a TPU, attention reads rows ``0 .. bound - 1`` of each row's
    plane where the cache holds them; lowered for anything else it is the two
    calls above. ``attn_bound`` (B,) int32 is that bound: by default each
    row's position + 1, which is all the causal mask lets through; a caller
    that knows some rows are not decoding (``serving``'s free, done and
    prefilling slots) passes 0 for them, and they read nothing. It bounds
    the read only: every row's write offset is ``cache.length`` as ever.

    A model that runs its stack more than once over one set of weights
    (``cache_spec(cfg).passes``, static) gets an outer ``lax.scan`` over the
    passes around the same layer body: pass ``u`` writes and reads planes
    ``u * L .. u * L + L - 1`` of the cache, which rides the carry of both
    loops, and ``norm`` closes every pass, its output opening the next. With
    one pass the program is the layer loop alone.

    Left-padded batches (the transformers convention): ``pad_offset`` (B,)
    counts each row's leading pads — the position ids the family embeds or
    rotates by shift down by it so row content starts at position 0 — and
    ``kv_valid`` (B, T_max) masks the pad slots out of attention forever.

    ``chunk`` (a :class:`PromptChunk`) rides a step of one token a row: the
    B rows and the chunk's C tokens go through the family's embedding,
    blocks and norm as one row of B + C tokens, so every weight is read
    once for both. Only ``attend`` tells them apart: the B rows reach the
    cache as above; then the chunk's rows are written into its slot
    (``kv_cache.slot_step``), over the row of no use that the slot's own
    row of the B has just written at its length, and attend over that
    slot's plane. The head runs on the B rows and the chunk's row
    ``valid - 1``: the logits are (B + 1, V). The returned length is the B
    rows' (``cache.length + 1``); the chunk's slot's is the caller's.
    """
    if not cfg.scan_layers:
        raise ValueError("generation requires scan_layers=True (stacked blocks)")
    b, s = input_ids.shape
    start = cache.length
    positions = _row_positions(start, b, s)
    pos_ids = positions
    if pad_offset is not None:
        pos_ids = jnp.maximum(positions - pad_offset[:, None], 0)
    ids = input_ids
    if chunk is not None:
        if s != 1 or return_all or kv_valid is not None or pad_offset is not None:
            raise ValueError("a prompt chunk rides a plain step of one token a row")
        chunk_pos = chunk.start + jnp.arange(chunk.ids.shape[1], dtype=jnp.int32)
        ids = jnp.concatenate([input_ids[:, 0], chunk.ids[0]])[None]
        pos_ids = jnp.concatenate([positions[:, 0], chunk_pos])[None]
    x, stacked, block, norm, head, *xs = decoder(cfg, params, ids, pos_ids)
    by_bound = s == 1 and kv_valid is None and kv_cache.decode_block_rows(cache.k) is not None
    if by_bound and attn_bound is None:
        attn_bound = positions[:, 0] + 1

    def one_layer(carry, layer):
        h, ck, cv = carry  # hidden state, the whole (L,B,T,Hkv,D) cache
        p, i, *x_i = layer  # layer params, layer index

        def attend_rows(q, k_new, v_new):
            nonlocal ck, cv
            if by_bound:
                ck, cv, out = kv_cache.cache_attend(
                    ck, cv, q, k_new, v_new, i, start, attn_bound,
                    lambda q, k_i, v_i: _attend(q, k_i, v_i, positions))
                return out
            ck, cv, k_i, v_i = kv_cache.cache_step(ck, cv, k_new, v_new, i, start)
            return _attend(q, k_i, v_i, positions, kv_valid)

        def attend(q, k_new, v_new):
            nonlocal ck, cv
            if chunk is None:
                return attend_rows(q, k_new, v_new)
            (q, q_c), (k_new, k_c), (v_new, v_c) = (
                (a[0, :b, None], a[:, b:]) for a in (q, k_new, v_new))
            out = attend_rows(q, k_new, v_new)  # first: the chunk writes over its slot's row
            ck, cv, k_s, v_s = kv_cache.slot_step(ck, cv, k_c, v_c, i, chunk.slot, chunk.start)
            return jnp.concatenate([out[:, 0], _attend(q_c, k_s, v_s, chunk_pos[None])[0]])[None]

        return (block(p, h, attend, *x_i), ck, cv), None

    def one_pass(carry, planes):
        (h, ck, cv), _ = jax.lax.scan(one_layer, carry, (stacked, planes, *xs))
        return (norm(h), ck, cv), None

    passes = cache_spec(cfg).passes
    planes = jnp.arange(cache.n_layers, dtype=jnp.int32)
    carry = (x, cache.k, cache.v)
    if passes == 1:
        (x, new_k, new_v), _ = one_pass(carry, planes)
    else:
        (x, new_k, new_v), _ = jax.lax.scan(
            jax.named_scope("ut_pass")(one_pass), carry, planes.reshape(passes, -1))
    if chunk is not None:
        x = jnp.concatenate([x[0, :b], jax.lax.dynamic_slice_in_dim(x[0], b + chunk.valid - 1, 1)])
    elif not return_all:
        x = x[:, -1]
    return head(x).astype(jnp.float32), KVCache(new_k, new_v, start + s)


def _moe_mlp(cfg, p, h):
    """Mixtral's routed sparse MLP on raw params: softmax router, top-k, gates
    renormalised over the k; dropless (every routed (token, expert) product
    is computed, no capacity). The T tokens are contracted with the stacked
    expert weights ``(E, H, F)`` / ``(E, F, H)`` where they lie — the expert
    axis is a dimension of the dots, never an index — so under the layer
    scan the layer's slice of the stack fuses into each dot and every expert
    is read once a layer, which is the floor when a full batch reaches all
    experts. Indexing ``w[e]`` under a ``vmap`` instead is a gather that XLA
    expands into a copy of all three tensors, every layer of every step. All
    E experts are computed for every token; the products of the experts a
    token is not routed to are masked out (``where``, not a gate of zero: an
    overflow there must not reach the sum as 0 x inf)."""
    b, s = h.shape[:2]
    tokens = h.reshape(b * s, -1)
    with jax.named_scope("moe.router"):
        router_logits = tokens.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(router_logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)  # (T, k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        # (T, E): a token's gate on each expert it is routed to, zero elsewhere
        routed = topi[..., None] == jnp.arange(cfg.num_local_experts)  # (T, k, E)
        gates = jnp.sum(jnp.where(routed, topv[..., None], 0.0), axis=1)

    with jax.named_scope("moe.experts"):
        dt = tokens.dtype
        gate = jax.nn.silu(jnp.einsum("th,ehf->tef", tokens, p["w_gate"].astype(dt)))
        up = jnp.einsum("th,ehf->tef", tokens, p["w_up"].astype(dt))
        # the gate folded into the activation: one contraction over (e, f)
        # then sums the routed experts, and no (E, T, H) array exists
        act = jnp.where(routed.any(axis=1)[..., None],
                        gate * up * gates[..., None].astype(dt), 0)
        out = jnp.einsum("tef,efh->th", act, p["w_down"].astype(dt))
    return out.reshape(b, s, -1)


def _llama_decoder(cfg, params, input_ids, pos_ids):
    """Llama-family decode (mirrors models/llama.py and its chassis knobs).
    Mixtral is this block with the routed sparse MLP (``p["moe"]``) where the
    dense one (``p["mlp"]``) stands."""
    model_p = params["model"] if "model" in params else params
    embed = model_p["embed_tokens"]["embedding"]

    x = _embed_tokens(cfg, embed, input_ids)
    rd = getattr(cfg, "rotary_dim", None) or cfg.head_dim
    cos, sin = rotary_embedding(pos_ids, rd, cfg.rope_theta, x.dtype)

    heads = (cfg.num_attention_heads, cfg.head_dim)
    attn_mult = getattr(cfg, "attention_multiplier", None)
    res_mult = getattr(cfg, "residual_multiplier", 1.0)

    def block(p, h, attend):
        attn = p["self_attn"]
        hn = _chassis_norm(cfg, p["input_layernorm"], h)
        q, k_new, v_new = _qkv_proj(attn, hn, cos, sin, rotary_dim=rd, heads=heads)
        if attn_mult is not None:  # same q-folding trick as LlamaAttention
            q = q * jnp.asarray(attn_mult * np.sqrt(cfg.head_dim), q.dtype)
        out = _out_proj(attend(q, k_new, v_new), attn["o_proj"]["kernel"])
        if "bias" in attn["o_proj"]:
            out = out + attn["o_proj"]["bias"].astype(out.dtype)
        if "input_layernorm_2" in p:  # sandwich norm: the branch's output normed too
            out = _chassis_norm(cfg, p["input_layernorm_2"], out)
        h = h + scale_residual(out, res_mult)
        hn = _chassis_norm(cfg, p["post_attention_layernorm"], h)
        ffn = _moe_mlp(cfg, p["moe"], hn) if "moe" in p else _mlp(cfg, p["mlp"], hn)
        if "post_attention_layernorm_2" in p:
            ffn = _chassis_norm(cfg, p["post_attention_layernorm_2"], ffn)
        return h + scale_residual(ffn, res_mult)

    def head(h_out):
        with jax.named_scope("lm_head"):
            if cfg.tie_word_embeddings:
                logits = h_out @ embed.T.astype(cfg.dtype)
            else:
                logits = h_out @ params["lm_head"]["kernel"].astype(cfg.dtype)
        ls = getattr(cfg, "logits_scaling", 1.0)
        if ls != 1.0:  # Granite: logits / scaling
            logits = logits / jnp.asarray(ls, logits.dtype)
        return logits

    return (x, model_p["layers"]["block"], block,
            lambda x: _chassis_norm(cfg, model_p["norm"], x), head)


_llama_forward_cached = partial(_forward_cached, _llama_decoder)


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------


def _filter_logits(logits, *, temperature, top_k: Optional[int] = None,
                   top_p: Optional[float] = None):
    """The temperature/top-k/top-p filtering half of :func:`sample_logits`,
    shared bit-exactly with speculative accept/residual sampling (serving.py)
    so both draw from the identical filtered distribution."""
    logits = logits / temperature
    if top_k is not None:
        top_k = min(top_k, logits.shape[-1])  # transformers clamps too
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with cumulative mass >= top_p (always >= 1 tok).
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def sample_logits(logits, rng, *, temperature=1.0, top_k: Optional[int] = None,
                  top_p: Optional[float] = None):
    """(B, V) fp32 logits → (B,) token ids. temperature<=0 means greedy."""
    if temperature is None or temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _filter_logits(logits, temperature=temperature, top_k=top_k,
                            top_p=top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The other decoder-only families
# ---------------------------------------------------------------------------

def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _gpt2_decoder(cfg, params, input_ids, pos_ids):
    """GPT-2 decode (learned positions, fused c_attn, GELU MLP — mirrors
    models/gpt2.py)."""
    tr = params["transformer"]
    wte = tr["wte"]["embedding"]

    x = jnp.take(wte, input_ids, axis=0).astype(cfg.dtype)
    x = x + jnp.take(tr["wpe"]["embedding"], pos_ids, axis=0).astype(cfg.dtype)

    def block(p, h, attend):
        hn = _layer_norm(h, p["ln_1"], cfg.layer_norm_epsilon)
        qkv = jnp.einsum(
            "bsh,hcnd->bscnd", hn, p["attn"]["c_attn"]["kernel"].astype(hn.dtype)
        ) + p["attn"]["c_attn"]["bias"].astype(hn.dtype)
        out = attend(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        h = h + (
            jnp.einsum("bsnd,ndh->bsh", out, p["attn"]["c_proj"]["kernel"].astype(out.dtype))
            + p["attn"]["c_proj"]["bias"].astype(out.dtype)
        )
        hn = _layer_norm(h, p["ln_2"], cfg.layer_norm_epsilon)
        mid = jax.nn.gelu(_dense(p["c_fc"], hn))
        return h + mid @ p["c_proj"]["kernel"].astype(mid.dtype) + p["c_proj"]["bias"].astype(mid.dtype)

    return (x, tr["h"]["block"], block,
            lambda x: _layer_norm(x, tr["ln_f"], cfg.layer_norm_epsilon),
            lambda h_out: h_out @ wte.T.astype(cfg.dtype))


def _opt_decoder(cfg, params, input_ids, pos_ids):
    """OPT decode (learned positions with the fairseq offset of 2, pre-LN
    ReLU blocks — mirrors models/opt.py)."""
    model_p = params["model"]
    embed = model_p["embed_tokens"]["embedding"]

    x = jnp.take(embed, input_ids, axis=0).astype(cfg.dtype)
    x = x + jnp.take(
        model_p["embed_positions"]["embedding"], pos_ids + cfg.POSITION_OFFSET, axis=0
    ).astype(cfg.dtype)

    def block(p, h, attend):
        attn = p["self_attn"]
        hn = _layer_norm(h, p["self_attn_layer_norm"], cfg.layer_norm_eps)
        q, k_new, v_new = _qkv_proj(attn, hn, None, None,
                                    heads=(cfg.num_attention_heads, cfg.head_dim))
        out = attend(q, k_new, v_new)
        h = h + _out_proj(out, attn["out_proj"]["kernel"]) + attn["out_proj"]["bias"].astype(h.dtype)
        hn = _layer_norm(h, p["final_layer_norm"], cfg.layer_norm_eps)
        mid = jax.nn.relu(_dense(p["fc1"], hn))
        return h + mid @ p["fc2"]["kernel"].astype(mid.dtype) + p["fc2"]["bias"].astype(mid.dtype)

    return (x, model_p["layers"]["block"], block,
            lambda x: _layer_norm(x, model_p["final_layer_norm"], cfg.layer_norm_eps),
            lambda h_out: h_out @ embed.T.astype(cfg.dtype))


def _neox_decoder(cfg, params, input_ids, pos_ids):
    """GPT-NeoX decode: parallel residual, fused per-head [q|k|v], partial
    rotary — mirrors models/neox.py."""
    gp = params["gpt_neox"]

    x = jnp.take(gp["embed_in"]["embedding"], input_ids, axis=0).astype(cfg.dtype)
    rnd = cfg.rotary_ndims
    cos, sin = rotary_embedding(pos_ids, rnd, cfg.rotary_emb_base, x.dtype)

    def block(p, h, attend):
        attn = p["attention"]
        hn = _layer_norm(h, p["input_layernorm"], cfg.layer_norm_eps)
        qkv = jnp.einsum(
            "bsh,hncd->bsncd", hn, attn["query_key_value"]["kernel"].astype(hn.dtype)
        ) + attn["query_key_value"]["bias"].astype(hn.dtype)
        q, k_new, v_new = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        q = jnp.concatenate([apply_rope(q[..., :rnd], cos, sin), q[..., rnd:]], -1)
        k_new = jnp.concatenate([apply_rope(k_new[..., :rnd], cos, sin), k_new[..., rnd:]], -1)
        out = attend(q, k_new, v_new)
        attn_out = (
            jnp.einsum("bsnd,ndh->bsh", out, attn["dense"]["kernel"].astype(out.dtype))
            + attn["dense"]["bias"].astype(out.dtype)
        )

        def mlp(inp):
            hn2 = _layer_norm(inp, p["post_attention_layernorm"], cfg.layer_norm_eps)
            mid = jax.nn.gelu(_dense(p["dense_h_to_4h"], hn2), approximate=False)
            return _dense(p["dense_4h_to_h"], mid)

        if cfg.use_parallel_residual:
            # One residual for both sublayers; the MLP sees pre-attention h.
            return h + attn_out + mlp(h)
        h = h + attn_out
        return h + mlp(h)

    return (x, gp["layers"]["block"], block,
            lambda x: _layer_norm(x, gp["final_layer_norm"], cfg.layer_norm_eps),
            lambda h_out: h_out @ params["embed_out"]["kernel"].astype(cfg.dtype))


# ---------------------------------------------------------------------------
# Encoder-decoder plans (T5, Whisper)
# ---------------------------------------------------------------------------
#
# The reference generates with T0pp-11B in its big-model benchmark
# (reference: benchmarks/big_model_inference/README.md) via transformers'
# encoder-decoder generate. Here the split is explicit and TPU-shaped:
# ``encode`` runs ONCE (the encoder module itself + a precomputed
# cross-attention K/V stack per decoder layer — cross K/V never changes
# during decoding, so it is part of the encoded state, not the cache);
# ``decode`` keeps the causal plans' KVCache contract for decoder
# self-attention, so generate()/beam_search() reuse the same loop.


class EncDecState(NamedTuple):
    cross_k: jax.Array  # (L_dec, B, S_enc, H, D) — fixed for the whole decode
    cross_v: jax.Array
    enc_mask: Optional[jax.Array]  # (B, S_enc) key validity, or None


def _cross_attend(q, k, v, mask, scale: Optional[float]):
    """q (B,Sq,H,D) vs encoder k/v (B,Sk,H,D); no causality."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if scale is not None:
        scores = scores * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :].astype(bool), scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _t5_rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _t5_encode(cfg, params, input_ids) -> EncDecState:
    """Encoder pass + the decoder's cross K/V stack. Reuses the flax encoder
    module (models/t5.py) — its math is already parity-tested."""
    from .models.t5 import T5Stack

    input_ids = jnp.asarray(input_ids)
    mask = (input_ids != cfg.pad_token_id).astype(jnp.int32)
    x = jnp.take(params["shared"]["embedding"], input_ids, axis=0).astype(cfg.dtype)
    enc = T5Stack(cfg, is_decoder=False).apply({"params": params["encoder"]}, x, mask=mask)

    def kv(block_p):
        k = jnp.einsum("bse,ehd->bshd", enc, block_p["cross_attn"]["k"]["kernel"].astype(enc.dtype))
        v = jnp.einsum("bse,ehd->bshd", enc, block_p["cross_attn"]["v"]["kernel"].astype(enc.dtype))
        return k, v

    k0, v0 = kv(params["decoder"]["block_0"])
    stacked = params["decoder"]["layers"]["block"]
    krest = jnp.einsum(
        "bse,lehd->lbshd", enc, stacked["cross_attn"]["k"]["kernel"].astype(enc.dtype)
    )
    vrest = jnp.einsum(
        "bse,lehd->lbshd", enc, stacked["cross_attn"]["v"]["kernel"].astype(enc.dtype)
    )
    cross_k = jnp.concatenate([k0[None], krest], axis=0)
    cross_v = jnp.concatenate([v0[None], vrest], axis=0)
    return EncDecState(cross_k, cross_v, mask)


def _t5_self_bias(cfg, table, q_positions, t_max):
    """Causal relative-position bias against the full cache axis.
    table: (num_buckets, H). Returns (B, H, Sq, T_max) fp32."""
    from .models.t5 import relative_position_bucket

    kv_pos = jnp.arange(t_max, dtype=jnp.int32)  # (T,)
    rel = kv_pos[None, None, :] - q_positions[:, :, None]  # (B, Sq, T)
    buckets = relative_position_bucket(
        rel, bidirectional=False,
        num_buckets=cfg.relative_attention_num_buckets,
        max_distance=cfg.relative_attention_max_distance,
    )
    bias = jnp.take(table, buckets, axis=0)  # (B, Sq, T, H)
    return jnp.transpose(bias, (0, 3, 1, 2)).astype(jnp.float32)


def _t5_decode(cfg, params, input_ids, cache: KVCache, enc: EncDecState, return_all=False):
    """Cached T5 decoder: block_0 (bias owner) + lax.scan over the stacked
    rest — exactly the T5Stack split (models/t5.py), which is why it keeps a
    loop of its own beside :func:`_forward_cached`. No 1/sqrt(d) scaling
    (T5's initializer absorbs it); scores and softmax in fp32."""
    if not cfg.scan_layers:
        raise ValueError("generation requires scan_layers=True (stacked blocks)")
    dec = params["decoder"]
    shared = params["shared"]["embedding"]
    eps = cfg.layer_norm_epsilon

    b, s = input_ids.shape
    t_max = cache.t_max
    start = cache.length
    positions = _row_positions(start, b, s)

    y = jnp.take(shared, input_ids, axis=0).astype(cfg.dtype)
    bias_table = dec["block_0"]["self_attn"]["relative_attention_bias"]["embedding"]
    self_bias = _t5_self_bias(cfg, bias_table, positions, t_max)  # (B,H,Sq,T)

    def self_attend(q, ck, cv):
        ck, cv = float_pages(ck, q.dtype), float_pages(cv, q.dtype)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, ck).astype(jnp.float32) + self_bias
        kv_pos = jnp.arange(t_max, dtype=jnp.int32)[None, :]
        causal = kv_pos[None, :, :] <= positions[:, :, None]  # (B,Sq,T)
        scores = jnp.where(causal[:, None], scores, -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, cv)

    def block(h, ck, cv, p, i, xk, xv):
        a = p["self_attn"]
        hn = _t5_rms(h, p["ln0"]["weight"].astype(h.dtype), eps)
        q = _proj(hn, a["q"]["kernel"])
        k_new = _proj(hn, a["k"]["kernel"])
        v_new = _proj(hn, a["v"]["kernel"])
        ck, cv, k_i, v_i = kv_cache.cache_step(ck, cv, k_new, v_new, i, start)
        out = self_attend(q, k_i, v_i)
        h = h + _out_proj(out, a["o"]["kernel"])

        c = p["cross_attn"]
        hn = _t5_rms(h, p["ln1"]["weight"].astype(h.dtype), eps)
        q = _proj(hn, c["q"]["kernel"])
        out = _cross_attend(q, xk, xv, enc.enc_mask, scale=None)  # T5: no scaling
        h = h + _out_proj(out, c["o"]["kernel"])

        hn = _t5_rms(h, p["ln2"]["weight"].astype(h.dtype), eps)
        mid = jax.nn.relu(hn @ p["ffn"]["wi"]["kernel"].astype(hn.dtype))
        return h + mid @ p["ffn"]["wo"]["kernel"].astype(mid.dtype), ck, cv

    # block_0 owns cache layer 0; the scan covers layers 1..L-1.
    carry = block(y, cache.k, cache.v, dec["block_0"], 0, enc.cross_k[0], enc.cross_v[0])

    def one_layer(carry, layer):
        return block(*carry, *layer), None

    layers = jnp.arange(1, cache.n_layers, dtype=jnp.int32)
    (y, new_k, new_v), _ = jax.lax.scan(
        one_layer, carry, (dec["layers"]["block"], layers, enc.cross_k[1:], enc.cross_v[1:])
    )

    y = _t5_rms(y, dec["final_ln"]["weight"].astype(y.dtype), eps)
    h_out = y if return_all else y[:, -1]
    logits = (h_out * (cfg.d_model ** -0.5)) @ shared.T.astype(cfg.dtype)
    return logits.astype(jnp.float32), KVCache(new_k, new_v, start + s)


def _whisper_encode(cfg, params, input_features) -> EncDecState:
    """Whisper encoder (the flax module itself) + cross K/V per decoder layer."""
    from .models.whisper import WhisperEncoder

    enc = WhisperEncoder(cfg).apply(
        {"params": params["encoder"]}, jnp.asarray(input_features)
    )
    stacked = params["decoder"]["layers"]["block"]["encoder_attn"]
    k = jnp.einsum("bse,lehd->lbshd", enc, stacked["k_proj"]["kernel"].astype(enc.dtype))
    v = jnp.einsum("bse,lehd->lbshd", enc, stacked["v_proj"]["kernel"].astype(enc.dtype))
    v = v + stacked["v_proj"]["bias"][:, None, None].astype(v.dtype)
    return EncDecState(k, v, None)


def _whisper_decoder(enc: EncDecState, cfg, params, input_ids, pos_ids):
    """Whisper's decoder (mirrors models/whisper.py: pre-LN blocks, learned
    positions, biased q/v projections, no K bias, tied head); each layer's
    cross K/V ride the layer loop beside its params."""
    dec = params["decoder"]
    embed = dec["embed_tokens"]["embedding"]
    eps = cfg.layer_norm_eps
    scale = 1.0 / np.sqrt(cfg.decoder_head_dim)

    y = jnp.take(embed, input_ids, axis=0).astype(cfg.dtype)
    y = y + jnp.take(dec["embed_positions"]["embedding"], pos_ids[0], axis=0).astype(cfg.dtype)

    def proj_b(x, p):  # DenseGeneral with bias
        return _proj(x, p["kernel"]) + p["bias"].astype(x.dtype)

    def block(p, h, attend, xk, xv):
        a = p["self_attn"]
        hn = _layer_norm(h, p["self_attn_layer_norm"], eps)
        q = proj_b(hn, a["q_proj"])  # _attend applies the 1/sqrt(d) scale
        k_new = _proj(hn, a["k_proj"]["kernel"])  # Whisper: no K bias
        out = attend(q, k_new, proj_b(hn, a["v_proj"]))
        h = h + _out_proj(out, a["out_proj"]["kernel"]) + a["out_proj"]["bias"].astype(h.dtype)

        c = p["encoder_attn"]
        hn = _layer_norm(h, p["encoder_attn_layer_norm"], eps)
        q = proj_b(hn, c["q_proj"])
        out = _cross_attend(q, xk, xv, None, scale=scale)
        h = h + _out_proj(out, c["out_proj"]["kernel"]) + c["out_proj"]["bias"].astype(h.dtype)

        hn = _layer_norm(h, p["final_layer_norm"], eps)
        mid = jax.nn.gelu(_dense(p["fc1"], hn), approximate=False)
        return h + mid @ p["fc2"]["kernel"].astype(mid.dtype) + p["fc2"]["bias"].astype(mid.dtype)

    return (y, dec["layers"]["block"], block,
            lambda y: _layer_norm(y, dec["layer_norm"], eps),
            lambda h_out: h_out @ embed.T.astype(cfg.dtype),
            enc.cross_k, enc.cross_v)


def _whisper_decode(cfg, params, input_ids, cache: KVCache, enc: EncDecState, return_all=False):
    """Cached Whisper decoder, through the decoder-only families' loop."""
    return _forward_cached(partial(_whisper_decoder, enc), cfg, params, input_ids, cache, return_all)


# module class name -> (encode(cfg, params, enc_inputs) -> EncDecState,
#                       decode(cfg, params, ids, cache, enc_state))
ENCDEC_GENERATION_PLANS: dict[str, tuple] = {
    "T5ForConditionalGeneration": (_t5_encode, _t5_decode),
    "WhisperForConditionalGeneration": (_whisper_encode, _whisper_decode),
}


def register_encdec_generation_plan(module_class_name: str, encode_fn, decode_fn) -> None:
    ENCDEC_GENERATION_PLANS[module_class_name] = (encode_fn, decode_fn)


# module class name -> forward_cached(cfg, params, ids, cache)
GENERATION_PLANS: dict[str, Callable] = {
    "LlamaForCausalLM": _llama_forward_cached,
    "GPT2LMHeadModel": partial(_forward_cached, _gpt2_decoder),
    "OPTForCausalLM": partial(_forward_cached, _opt_decoder),
    "GPTNeoXForCausalLM": partial(_forward_cached, _neox_decoder),
    "MixtralForCausalLM": _llama_forward_cached,
}


def register_generation_plan(module_class_name: str, fn: Callable) -> None:
    GENERATION_PLANS[module_class_name] = fn


# The built-in plans whose decoders read q, k and v through ``_qkv_proj``,
# which takes the serving layout (:func:`fuse_qkv_params`) as well as the
# model's: ``ServingEngine`` installs that layout for these alone. A plan
# registered later, or a caller's own ``forward_cached``, is handed the
# model's layout.
FUSED_QKV_PLANS = (_llama_forward_cached, GENERATION_PLANS["OPTForCausalLM"])


@dataclasses.dataclass
class GenerationConfig:
    """Bundled sampling settings; ``generate(..., config=GenerationConfig(...))``
    uses these as defaults, explicit kwargs win."""

    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 → greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    pad_token_id: Optional[int] = None  # finished rows get this (default: eos)
    # Logit processors (transformers semantics — Whisper's transcription UX):
    suppress_tokens: Optional[tuple] = None        # never sampled
    begin_suppress_tokens: Optional[tuple] = None  # not at the FIRST new token
    forced_decoder_ids: Optional[tuple] = None     # ((position, token), ...) —
    # absolute decoder positions (0 = decoder start), like HF Whisper's
    # [(1, lang), (2, task), (3, notimestamps)]


_ENCODE_JIT_CACHE: dict = {}


def _resolve_encdec_state(model, inputs, decoder_input_ids):
    """If ``model`` is an encoder-decoder family, run its encoder (memoized
    jit per (encode_fn, cfg) — not per call) and return
    ``(decoder_ids, decode_fn, enc_state)``; else ``(None, None, None)``."""
    name = type(model.module).__name__
    plan = ENCDEC_GENERATION_PLANS.get(name)
    if plan is None:
        return None, None, None
    encode_fn, decode_fn = plan
    cfg = model.module.config
    if not getattr(cfg, "scan_layers", True):
        # Same early diagnostic as the decode fns — the encoders also slice
        # the stacked (scan) layer layout for the cross K/V.
        raise ValueError("generation requires scan_layers=True (stacked blocks)")
    key = (encode_fn, cfg)
    if key not in _ENCODE_JIT_CACHE:
        while len(_ENCODE_JIT_CACHE) >= _GEN_LOOP_CACHE_MAX:
            _ENCODE_JIT_CACHE.pop(next(iter(_ENCODE_JIT_CACHE)))
        _ENCODE_JIT_CACHE[key] = jax.jit(partial(encode_fn, cfg))
    enc_state = _ENCODE_JIT_CACHE[key](model.params, inputs)
    if decoder_input_ids is None:
        b = jnp.asarray(inputs).shape[0]
        start_id = getattr(cfg, "decoder_start_token_id", 0)
        decoder_input_ids = jnp.full((b, 1), start_id, jnp.int32)
    return jnp.asarray(decoder_input_ids), decode_fn, enc_state


def _resolve_encdec(model, inputs, decoder_input_ids, beams: int = 1):
    """Closure variant of :func:`_resolve_encdec_state` (beam search):
    returns ``(decoder_ids, fwd)`` with the encoded state closed over.

    ``beams > 1``: ``fwd`` dispatches on the batch dim — prefill sees B rows,
    decode sees B*beams — selecting the plain or beam-tiled encoded state.
    """
    dec_ids, decode_fn, enc_state = _resolve_encdec_state(model, inputs, decoder_input_ids)
    if decode_fn is None:
        return None, None
    states = {enc_state.cross_k.shape[1]: enc_state}
    if beams > 1:
        tiled = EncDecState(
            jnp.repeat(enc_state.cross_k, beams, axis=1),
            jnp.repeat(enc_state.cross_v, beams, axis=1),
            None if enc_state.enc_mask is None else jnp.repeat(enc_state.enc_mask, beams, axis=0),
        )
        states[tiled.cross_k.shape[1]] = tiled

    def fwd(cfg, params, ids, cache, return_all=False):
        return decode_fn(cfg, params, ids, cache, states[ids.shape[0]], return_all)

    return dec_ids, fwd


def generate(
    model,
    input_ids,
    max_new_tokens: Optional[int] = None,
    *,
    temperature: Optional[float] = None,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_token_id: Optional[int] = None,
    pad_token_id: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    forward_cached: Optional[Callable] = None,
    config: Optional[GenerationConfig] = None,
    decoder_input_ids=None,
    attention_mask=None,
    suppress_tokens=None,
    begin_suppress_tokens=None,
    forced_decoder_ids=None,
    seq_buckets=None,
    compile_manager=None,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations for ``input_ids`` (B, S).

    ``attention_mask`` (B, S): transformers' left-padded-batch convention —
    rows shorter than S carry leading pads marked 0. RoPE positions shift
    per row so content starts at 0 and pad slots never enter attention.

    Execution: ONE jitted program (prefill + the full decode ``lax.scan``),
    memoized per (plan, config, sampling settings) — repeated calls reuse
    the compiled loop (see :func:`_generation_loop` /
    :func:`clear_generation_cache`). Returns (B, S + max_new_tokens); after
    a row emits ``eos_token_id`` it is padded with ``pad_token_id``
    (defaulting to the EOS id, like transformers' warning-fallback).

    Encoder-decoder families (T5, Whisper): ``input_ids`` is the ENCODER
    input (token ids / mel features), the encoder runs once, and the decode
    loop starts from ``decoder_input_ids`` (default: one
    ``decoder_start_token_id`` per row — pass Whisper's forced SOT prompt
    here). Returns the decoder sequence (B, S_dec + max_new_tokens).

    ``seq_buckets`` / ``compile_manager`` (opt-in): round the prompt length
    up a bucket ladder (explicit rungs, or the compile manager's seq policy)
    by LEFT-padding — varied prompt lengths then share ≤ ``len(buckets)``
    compiled prefills instead of minting one executable per length. Output
    shape and tokens are unchanged (left-padding is masked out exactly like
    a padded batch). With a ``compile_manager``, the call's signature is also
    recorded in the shapes manifest so
    :meth:`~accelerate_tpu.compile_manager.CompileManager.warmup_generation`
    can pre-compile decode loops on the next run.
    """
    gc = config or GenerationConfig()
    max_new_tokens = gc.max_new_tokens if max_new_tokens is None else max_new_tokens
    temperature = gc.temperature if temperature is None else temperature
    top_k = top_k if top_k is not None else gc.top_k
    top_p = top_p if top_p is not None else gc.top_p
    eos_token_id = eos_token_id if eos_token_id is not None else gc.eos_token_id
    pad_token_id = pad_token_id if pad_token_id is not None else gc.pad_token_id
    if pad_token_id is None:
        pad_token_id = eos_token_id
    suppress_tokens = suppress_tokens if suppress_tokens is not None else gc.suppress_tokens
    begin_suppress_tokens = (
        begin_suppress_tokens if begin_suppress_tokens is not None
        else gc.begin_suppress_tokens
    )
    forced_decoder_ids = (
        forced_decoder_ids if forced_decoder_ids is not None else gc.forced_decoder_ids
    )
    cfg = model.module.config
    params = model.params
    # An explicit forward_cached override outranks the registries, exactly as
    # on the causal path.
    enc_state = None
    if forward_cached is not None:
        fwd = forward_cached
    else:
        dec_ids, decode_fn, enc_state = _resolve_encdec_state(
            model, input_ids, decoder_input_ids
        )
        if decode_fn is not None:
            input_ids, fwd = dec_ids, decode_fn
        else:
            fwd = GENERATION_PLANS.get(type(model.module).__name__)
    if fwd is None:
        known = ", ".join(sorted(GENERATION_PLANS) + sorted(ENCDEC_GENERATION_PLANS))
        raise ValueError(
            f"No generation plan for {type(model.module).__name__!r}; built-in: {known}"
        )
    input_ids = jnp.asarray(input_ids)
    orig_input_ids = input_ids
    b, s = input_ids.shape
    mask_np = None
    if attention_mask is not None:
        # Host-side mask arithmetic throughout: the validation below and the
        # pad_offset/kv_valid derivations used to run on device, costing a
        # blocking sync (`bool(jnp.all(...))`) on every call.
        mask_np = np.asarray(attention_mask, np.int32)

    # Opt-in prompt bucketing: round s up the ladder by LEFT-padding (masked
    # pads are invisible — same machinery as a padded batch), so a stream of
    # varied prompt lengths reuses <= len(buckets) compiled prefills.
    if (seq_buckets or compile_manager is not None) and enc_state is None:
        s_b = _bucketed_prompt_len(s, seq_buckets, compile_manager)
        if s_b > s:
            fill = pad_token_id if pad_token_id is not None else 0
            pad_block = jnp.full((b, s_b - s), fill, input_ids.dtype)
            input_ids = jnp.concatenate([pad_block, input_ids], axis=1)
            if mask_np is None:
                mask_np = np.ones((b, s), np.int32)
            mask_np = np.concatenate(
                [np.zeros((b, s_b - s), np.int32), mask_np], axis=1
            )
            s = s_b

    t_max = s + max_new_tokens
    max_pos = cache_spec(cfg).max_positions
    if t_max > max_pos:
        raise ValueError(
            f"{t_max} tokens exceeds max_position_embeddings={max_pos}"
        )
    rng = rng if rng is not None else jax.random.key(0)

    pad_offset = kv_valid = None
    if mask_np is not None:
        import inspect

        if "pad_offset" not in inspect.signature(fwd).parameters:
            raise ValueError(
                f"the generation plan for {type(model.module).__name__!r} does "
                "not take attention_mask. Encoder-decoder families derive the "
                "encoder mask from pad_token_id automatically; custom plans "
                "need pad_offset/kv_valid parameters to support padded batches."
            )
        off_np = np.argmax(mask_np, axis=1).astype(np.int32)  # leading pads per row
        # Decoder-only generation requires LEFT padding (transformers warns
        # about the same mistake): right/ragged masks would silently read the
        # next-token logits off a pad-position query.
        if not np.all(off_np + mask_np.sum(axis=1) == s):
            raise ValueError(
                "attention_mask must be left-padded (zeros then ones per row) "
                "for decoder-only generation; got a right-padded or "
                "non-contiguous mask. Re-tokenize with padding_side='left'."
            )
        pad_offset = jnp.asarray(off_np)
        kv_valid = jnp.asarray(
            np.concatenate(
                [mask_np.astype(bool), np.ones((b, t_max - s), bool)], axis=1
            )
        )

    if compile_manager is not None:
        # Generation signatures land in the shapes manifest too, so AOT
        # warmup (warmup_generation) covers decode loops across runs.
        try:
            compile_manager.record_generation_signature(
                type(model.module).__name__, b, s, max_new_tokens,
                settings={
                    "temperature": temperature, "top_k": top_k, "top_p": top_p,
                    "eos_token_id": eos_token_id, "pad_token_id": pad_token_id,
                    "masked": mask_np is not None,
                },
            )
        except Exception:  # manifest trouble must never block generation
            pass

    loop = _generation_loop(
        fwd, cfg, max_new_tokens, temperature, top_k, top_p,
        eos_token_id, pad_token_id,
        masked=mask_np is not None, encdec=enc_state is not None,
        suppress=tuple(suppress_tokens) if suppress_tokens else None,
        begin_suppress=tuple(begin_suppress_tokens) if begin_suppress_tokens else None,
        forced=tuple(tuple(f) for f in forced_decoder_ids) if forced_decoder_ids else None,
        prompt_len=s,
    )
    cache = init_cache(cfg, b, t_max)
    toks = loop(params, input_ids, cache, rng, pad_offset, kv_valid, enc_state)
    # Bucketing pads on the LEFT; the returned sequence keeps the caller's
    # original prompt columns, so the output shape never changes.
    return jnp.concatenate([orig_input_ids, toks.T.astype(orig_input_ids.dtype)], axis=1)


def _bucketed_prompt_len(s: int, seq_buckets, compile_manager) -> int:
    """Prompt length rounded up the bucket ladder: explicit ``seq_buckets``
    rungs win, else the compile manager's seq policy. Off-ladder lengths fall
    through at their true size (same contract as ``bucket_for``)."""
    if seq_buckets:
        from .compile_manager import ladder_bucket

        bucketed = ladder_bucket(s, seq_buckets)
        return int(bucketed) if bucketed is not None else s
    if compile_manager is not None:
        return int(compile_manager.bucket_for(s, "seq"))
    return s


_GEN_LOOP_CACHE: dict = {}
_GEN_LOOP_CACHE_MAX = 32  # FIFO-evicted: callers varying settings per call
                          # (fresh closures, per-request max_new_tokens)
                          # must not grow compiled programs without bound.


_PLAN_JIT_CACHE: dict = {}


def _plan_jit(fwd, cfg, static_return_all: bool = False):
    """Memoized ``jax.jit(partial(fwd, cfg))`` keyed by (fwd, cfg) — lets
    beam_search/speculative reuse compiled prefill/decode across calls
    (registry plans are stable keys; per-call enc-dec closures still
    rebuild)."""
    key = (fwd, cfg, static_return_all)
    if key not in _PLAN_JIT_CACHE:
        while len(_PLAN_JIT_CACHE) >= _GEN_LOOP_CACHE_MAX:
            _PLAN_JIT_CACHE.pop(next(iter(_PLAN_JIT_CACHE)))
        _PLAN_JIT_CACHE[key] = (
            jax.jit(partial(fwd, cfg), static_argnames=("return_all",))
            if static_return_all
            else jax.jit(partial(fwd, cfg))
        )
    return _PLAN_JIT_CACHE[key]


def clear_generation_cache() -> None:
    """Drop all memoized generation loops AND encoder/plan jits (and their
    compiled executables)."""
    _GEN_LOOP_CACHE.clear()
    _ENCODE_JIT_CACHE.clear()
    _PLAN_JIT_CACHE.clear()


def _generation_loop(fwd, cfg, max_new_tokens, temperature, top_k, top_p,
                     eos_token_id, pad_token_id, *, masked: bool, encdec: bool,
                     suppress=None, begin_suppress=None, forced=None,
                     prompt_len: int = 0):
    """ONE jitted program per (plan, config, sampling settings): prefill +
    the whole decode ``lax.scan``. Memoized — repeated ``generate`` calls
    with the same settings reuse the compiled loop instead of re-tracing it
    (closures used to defeat jit's cache, costing a full recompile per call).
    Dynamic data (params, ids, cache, rng, pad/enc state) flows as arguments.

    Logit processors (transformers semantics): ``suppress`` masks tokens at
    every step; ``begin_suppress`` only at the first generated position;
    ``forced`` is ((abs_decoder_position, token), ...) — positions before
    ``prompt_len`` are already in the prompt and ignored.
    """
    forced_key = (forced, prompt_len) if forced else None
    key = (fwd, cfg, max_new_tokens, temperature, top_k, top_p,
           eos_token_id, pad_token_id, masked, encdec,
           suppress, begin_suppress, forced_key)
    cached = _GEN_LOOP_CACHE.get(key)
    if cached is not None:
        return cached
    while len(_GEN_LOOP_CACHE) >= _GEN_LOOP_CACHE_MAX:
        _GEN_LOOP_CACHE.pop(next(iter(_GEN_LOOP_CACHE)))

    sample = partial(sample_logits, temperature=temperature, top_k=top_k, top_p=top_p)
    neg_inf = float(np.finfo(np.float32).min)
    forced_map = None
    if forced:
        fm = np.full((max_new_tokens,), -1, np.int32)
        for pos, tok in forced:
            if prompt_len <= pos < prompt_len + max_new_tokens:
                fm[pos - prompt_len] = tok
        forced_map = jnp.asarray(fm)

    def run(params, input_ids, cache, rng, pad_offset, kv_valid, enc_state):
        def call(ids, cache):
            args = (enc_state,) if encdec else ()
            kwargs = dict(pad_offset=pad_offset, kv_valid=kv_valid) if masked else {}
            return fwd(cfg, params, ids, cache, *args, **kwargs)

        logits, cache = call(input_ids, cache)
        if begin_suppress:
            # Only the FIRST sampled token sees these (transformers
            # begin_suppress_tokens) — and its logits are exactly the prefill
            # output, so mask once here instead of conditionally every step.
            logits = logits.at[:, list(begin_suppress)].set(neg_inf)

        def step(carry, t):
            cache, logits, rng, done = carry
            rng, sub = jax.random.split(rng)
            if suppress:
                logits = logits.at[:, list(suppress)].set(neg_inf)
            tok = sample(logits, sub)
            if forced_map is not None:
                f = forced_map[t]
                tok = jnp.where(f >= 0, f, tok)
            if eos_token_id is not None:
                tok = jnp.where(done, pad_token_id, tok)
                done = done | (tok == eos_token_id)
            logits, cache = call(tok[:, None], cache)
            return (cache, logits, rng, done), tok

        done0 = jnp.zeros((input_ids.shape[0],), bool)
        (_, _, _, _), toks = jax.lax.scan(
            step, (cache, logits, rng, done0), jnp.arange(max_new_tokens)
        )
        return toks

    jitted = jax.jit(run)
    _GEN_LOOP_CACHE[key] = jitted
    return jitted


def speculative_generate(
    model,
    draft_model,
    input_ids,
    max_new_tokens: int = 32,
    *,
    num_draft_tokens: int = 4,
    eos_token_id: Optional[int] = None,
) -> jax.Array:
    """Greedy speculative decoding: the draft proposes ``num_draft_tokens``
    greedily through its KV cache; ONE cached target pass over the proposal
    window (``return_all=True``) scores every slot; the longest agreeing
    prefix is accepted plus the target's correction token. The result is the
    target's greedy continuation (bit-identical to :func:`generate` in fp32;
    low-precision configs can differ where the top-2 logits sit within the
    window-shape numerics) — the draft only changes how many target passes it
    takes: best case ``ceil(N / (k+1))`` windows of k tokens instead of N
    single-token steps.

    Both caches are position-indexed, so after a rejection each cache just
    rewinds its length to the accepted prefix and the next write overwrites
    the stale slots. Batch size 1.
    """
    if num_draft_tokens < 1:
        raise ValueError(f"num_draft_tokens must be >= 1, got {num_draft_tokens}")
    cfg = model.module.config
    dcfg = draft_model.module.config
    fwd = GENERATION_PLANS.get(type(model.module).__name__)
    dfwd = GENERATION_PLANS.get(type(draft_model.module).__name__)
    if fwd is None or dfwd is None:
        raise ValueError("Both models need generation plans (see GENERATION_PLANS)")
    input_ids = jnp.asarray(input_ids)
    b, s = input_ids.shape
    if b != 1:
        raise ValueError("speculative_generate supports batch size 1")
    t_max = s + max_new_tokens + num_draft_tokens + 1
    if t_max > min(cache_spec(cfg).max_positions, cache_spec(dcfg).max_positions):
        raise ValueError("sequence would exceed max positions")

    target_step = _plan_jit(fwd, cfg, static_return_all=True)
    draft_step = _plan_jit(dfwd, dcfg)

    out = input_ids
    tcache = init_cache(cfg, b, t_max)
    dcache = init_cache(dcfg, b, t_max)
    # Prefill both caches on the prompt; carry the target's next-token logits.
    tlogits, tcache = target_step(model.params, out, tcache)
    dlogits, dcache = draft_step(draft_model.params, out, dcache)

    produced = 0
    while produced < max_new_tokens:
        k = num_draft_tokens
        # Draft proposes k tokens greedily (cached, one token at a time).
        proposals = []
        dl, dc = dlogits, dcache
        for _ in range(k):
            tok = jnp.argmax(dl, axis=-1).astype(jnp.int32)
            proposals.append(tok)
            dl, dc = draft_step(draft_model.params, tok[:, None], dc)
        prop = jnp.stack(proposals, axis=1)  # (1, k)

        # One cached target pass over the k-token window; position j's logits
        # predict the token AFTER proposal j. Combined with the carried
        # ``tlogits`` (the prediction for slot 0) every slot is scored.
        win_logits, tc = target_step(model.params, prop, tcache, return_all=True)
        preds = jnp.concatenate([tlogits[:, None], win_logits], axis=1)  # (1, k+1, V)
        pred_tok = jnp.argmax(preds.astype(jnp.float32), axis=-1).astype(jnp.int32)
        agree = np.asarray(pred_tok[0, :k] == prop[0])
        n_accept = int(np.argmin(agree)) if not agree.all() else k
        # Accepted proposals + the target's own token at the divergence (or
        # the bonus token after k agreements).
        new_toks = jnp.concatenate(
            [prop[:, :n_accept], pred_tok[:, n_accept:n_accept + 1]], axis=1
        )[:, : max_new_tokens - produced]
        out = jnp.concatenate([out, new_toks], axis=1)
        produced += new_toks.shape[1]
        if eos_token_id is not None and bool((new_toks == eos_token_id).any()):
            arr = np.array(out[0, s:])  # writable copy
            idx = int(np.argmax(arr == eos_token_id))
            arr[idx + 1:] = eos_token_id
            out = jnp.concatenate(
                [input_ids, jnp.asarray(arr)[None].astype(input_ids.dtype)], axis=1
            )
            break
        if produced >= max_new_tokens:
            break
        # Rewind both caches to the accepted prefix minus the last token and
        # re-feed it: its K/V slot rewrites (the only stale one — accepted
        # proposals' slots already hold the right K/V) and the carried logits
        # refresh.
        rewind = jnp.asarray(out.shape[1] - 1, jnp.int32)
        tlogits, tcache = target_step(model.params, out[:, -1:], tc._replace(length=rewind))
        dlogits, dcache = draft_step(draft_model.params, out[:, -1:], dc._replace(length=rewind))

    # Pad to the full length if EOS ended the loop early.
    if out.shape[1] < s + max_new_tokens:
        pad_id = eos_token_id if eos_token_id is not None else 0
        pad = jnp.full((1, s + max_new_tokens - out.shape[1]), pad_id, out.dtype)
        out = jnp.concatenate([out, pad], axis=1)
    return out[:, : s + max_new_tokens]


def beam_search(
    model,
    input_ids,
    max_new_tokens: int = 32,
    *,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    eos_token_id: Optional[int] = None,
    forward_cached: Optional[Callable] = None,
    decoder_input_ids=None,
) -> jax.Array:
    """Beam-search decoding over the same KV-cache plans as :func:`generate`.

    Standard length-normalized beam search (score = logprob_sum /
    len^length_penalty): the prompt prefills once per batch row, the cache is
    tiled to ``B×num_beams``, and every step selects the global top-K of
    ``K×V`` candidates, reordering the cache along the beam axis. Beams that
    emit ``eos_token_id`` freeze (their score stops accumulating; the eos is
    kept, later slots pad with it). Returns the single best sequence per
    batch row, shape (B, S + max_new_tokens). Encoder-decoder families
    follow :func:`generate`'s contract (``input_ids`` feeds the encoder, the
    returned sequence is the decoder's).
    """
    cfg = model.module.config
    params = model.params
    dec_ids, encdec_fwd = (
        (None, None) if forward_cached is not None
        else _resolve_encdec(model, input_ids, decoder_input_ids, beams=num_beams)
    )
    if encdec_fwd is not None:
        input_ids, fwd = dec_ids, encdec_fwd
    else:
        fwd = forward_cached or GENERATION_PLANS.get(type(model.module).__name__)
    if fwd is None:
        known = ", ".join(sorted(GENERATION_PLANS) + sorted(ENCDEC_GENERATION_PLANS))
        raise ValueError(
            f"No generation plan for {type(model.module).__name__!r}; built-in: {known}"
        )
    input_ids = jnp.asarray(input_ids)
    b, s = input_ids.shape
    k = num_beams
    t_max = s + max_new_tokens
    max_pos = cache_spec(cfg).max_positions
    if t_max > max_pos:
        raise ValueError(f"{t_max} tokens exceeds max_position_embeddings={max_pos}")

    cache = init_cache(cfg, b, t_max)
    logits, cache = _plan_jit(fwd, cfg)(params, input_ids, cache)
    logp = jax.nn.log_softmax(logits, axis=-1)  # (B, V)
    v = logp.shape[-1]

    cache = cache.take_batch(jnp.repeat(jnp.arange(b), k))  # a row's K beams side by side
    # Beam 0 carries the prompt's logp; others start dead so the first step
    # picks K distinct tokens from beam 0's distribution.
    scores = jnp.full((b, k), -jnp.inf).at[:, 0].set(0.0)
    first = jnp.broadcast_to(logp[:, None, :], (b, k, v))
    done = jnp.zeros((b, k), bool)
    lengths = jnp.zeros((b, k), jnp.int32)
    tokens = jnp.zeros((b, k, max_new_tokens), jnp.int32)

    decode = _plan_jit(fwd, cfg)
    neg_inf = jnp.asarray(-jnp.inf)

    cand_logp = first
    for t in range(max_new_tokens):
        # Candidate scores (B, K, V); frozen beams may only "continue" via
        # their 0th slot at unchanged score (one candidate, not V).
        cand = scores[..., None] + jnp.where(done[..., None], 0.0, cand_logp)
        frozen_mask = jnp.arange(v)[None, None, :] != 0
        cand = jnp.where(done[..., None] & frozen_mask, neg_inf, cand)
        flat = cand.reshape(b, k * v)
        top_scores, top_idx = jax.lax.top_k(flat, k)  # (B, K)
        beam_idx = top_idx // v
        tok = (top_idx % v).astype(jnp.int32)

        # Reorder everything along the beam axis.
        gather = lambda a: jnp.take_along_axis(a, beam_idx, axis=1)
        was_done = gather(done)
        lengths = gather(lengths)
        prev_tokens = jnp.take_along_axis(
            tokens, beam_idx[..., None], axis=1
        )
        eos = eos_token_id if eos_token_id is not None else -1
        emit = jnp.where(was_done, eos if eos_token_id is not None else 0, tok)
        tokens = prev_tokens.at[:, :, t].set(emit)
        lengths = jnp.where(was_done, lengths, lengths + 1)
        scores = top_scores
        done = was_done | (
            (emit == eos) if eos_token_id is not None else jnp.zeros_like(was_done)
        )

        flat_beam = (jnp.arange(b)[:, None] * k + beam_idx).reshape(-1)
        cache = cache.take_batch(flat_beam)
        if t + 1 < max_new_tokens:
            logits, cache = decode(params, emit.reshape(b * k, 1), cache)
            cand_logp = jax.nn.log_softmax(logits, axis=-1).reshape(b, k, v)

    final = scores / jnp.maximum(lengths, 1).astype(jnp.float32) ** length_penalty
    best = jnp.argmax(final, axis=1)  # (B,)
    best_tokens = jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0]
    prompt = jnp.broadcast_to(input_ids[:, None, :], (b, 1, s))[:, 0]
    return jnp.concatenate([prompt, best_tokens.astype(input_ids.dtype)], axis=1)
