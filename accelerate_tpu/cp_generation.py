"""Long-context generation over the ``cp`` mesh axis — flash-decoding on ICI.

The reference's context parallelism is training-only
(reference: accelerator.py:1658-1671 ``_prepare_cp``; its `.generate()` path
never shards a sequence). Here long prompts generate too:

- **Prefill** runs the prompt with sequence sharded over ``cp`` through ring
  attention (parallel/cp.py) — each chip holds S/cp of every layer's K/V, so
  a prompt ``cp×`` longer than one chip's HBM fits. The per-layer K/V chunks
  are kept, sequence-sharded, as the **prefix cache**.
- **Decode** is flash-decoding distributed over the ring: each step's query
  computes online-softmax partials (acc, m, l) against the *local* prefix
  shard; the cross-chip max/sum/weighted-value reductions are placed by
  GSPMD from the shardings — three small collectives per layer, no gathered
  cache, HBM stays O(S/cp) per chip. Newly generated tokens land in a small
  replicated **tail cache** (they are recent and tiny), merged with the
  prefix partials by the standard online-softmax combination.

Supported: the Llama plan family (Llama/Mistral/Qwen2/Gemma checkpoints).
The single-chip analog is ``generation.generate``; token-for-token greedy
parity between the two is pinned by tests/test_cp_generation.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .models.llama import rms_norm, rotary_embedding
from .ops.flash_attention import attention_stats
from .generation import (
    _embed_tokens,
    _mlp,
    _norm_w,
    _out_proj,
    _qkv_proj,
    sample_logits,
)

_CP_LOOP_CACHE: dict = {}


def clear_cp_generation_cache():
    _CP_LOOP_CACHE.clear()


def _dp_axes(mesh) -> tuple:
    return tuple(
        a for a in ("dp_replicate", "dp_shard")
        if a in mesh.shape and mesh.shape[a] > 1
    )


def _manual_cp(mesh) -> bool:
    """True when the ``cp`` axis is real and cross-shard ops must be issued
    as manual shard_map collectives. GSPMD's auto-partitioned gather/slice/
    reduce over a cp-sharded dim miscompiles on the CPU SPMD backend when the
    mesh has other nontrivial axes (partial results are re-summed over
    uninvolved axes, scaling values by that axis size), so everything that
    communicates over cp goes through an explicit shard_map body instead."""
    return mesh.shape.get("cp", 1) > 1


def _embed_sharded(cfg, embed, input_ids, mesh, batch_axes):
    """Embedding lookup with ids sequence-sharded over ``cp``: the table is
    replicated, each chip gathers its own id chunk locally."""
    b_ax = batch_axes if batch_axes else None
    return jax.shard_map(
        lambda tbl, idc: _embed_tokens(cfg, tbl, idc),
        mesh=mesh,
        in_specs=(P(None, None), P(b_ax, "cp")),
        out_specs=P(b_ax, "cp", None),
        check_vma=False,
    )(embed, input_ids)


def _gather_seq(ids, mesh, batch_axes):
    """(B, S) cp-sharded -> replicated, via a manual tiled all_gather (the
    output concat would otherwise auto-reshard over cp)."""
    b_ax = batch_axes if batch_axes else None

    def body(i_c):
        return jax.lax.all_gather(i_c, "cp", axis=1, tiled=True)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(b_ax, "cp"),),
        out_specs=P(b_ax, None), check_vma=False,
    )(ids)


def _last_position(x, mesh, batch_axes):
    """(B, S, E) with S cp-sharded -> (B, E) at the last global position,
    replicated. The final chunk lives on the last cp shard; a tiny all_gather
    of each shard's local last row keeps the extraction manual."""
    b_ax = batch_axes if batch_axes else None

    def body(x_c):
        return jax.lax.all_gather(x_c[:, -1], "cp")[-1]

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(b_ax, "cp", None),),
        out_specs=P(b_ax, None), check_vma=False,
    )(x)


def _prefix_stats_sharded(q, pk, pv, mesh, batch_axes):
    """Flash-decoding partials against the cp-sharded prefix: local stats per
    shard, then the exact online-softmax merge over cp as manual pmax/psum
    (disjoint keysets, same combination as :func:`_merge_stats`)."""
    b_ax = batch_axes if batch_axes else None

    def body(q_c, k_c, v_c):
        acc, m, l = attention_stats(q_c, k_c, v_c, causal=False)
        m_g = jax.lax.pmax(m, "cp")
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, "cp")
        acc_g = jax.lax.psum(acc * w[..., None], "cp")
        return acc_g, m_g, l_g

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(b_ax, None, None, None),
            P(b_ax, "cp", None, None),
            P(b_ax, "cp", None, None),
        ),
        out_specs=(
            P(b_ax, None, None, None),
            P(b_ax, None, None),
            P(b_ax, None, None),
        ),
        check_vma=False,
    )(q, pk, pv)


def _merge_stats(parts):
    """Exact combination of disjoint-keyset online-softmax partials."""
    m = parts[0][1]
    for _, mi, _ in parts[1:]:
        m = jnp.maximum(m, mi)
    l = sum(li * jnp.exp(mi - m) for _, mi, li in parts)
    acc = sum(ai * jnp.exp(mi - m)[..., None] for ai, mi, _ in parts)
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, H, Sq, D)
    return out.transpose(0, 2, 1, 3)  # (B, Sq, H, D)


def _unpack(cfg, params):
    model_p = params["model"] if "model" in params else params
    stacked = model_p["layers"]["block"]
    embed = model_p["embed_tokens"]["embedding"]
    final_norm = model_p["norm"]["weight"]
    head = embed.T if cfg.tie_word_embeddings else params["lm_head"]["kernel"]
    return stacked, embed, final_norm, head


def _prefill(cfg, params, input_ids, mesh, batch_axes=()):
    """Prompt forward with seq sharded over cp; ring attention per layer.
    Returns (last-token logits (B,V) fp32, prefix_k, prefix_v) with the
    prefix caches (L,B,S,Hkv,D) sequence-sharded over ``cp``."""
    from .parallel.cp import ring_attention

    stacked, embed, final_norm, head = _unpack(cfg, params)
    b, s = input_ids.shape
    if _manual_cp(mesh):
        x = _embed_sharded(cfg, embed, input_ids, mesh, batch_axes)
    else:
        x = _embed_tokens(cfg, embed, input_ids)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta, x.dtype)
    eps = cfg.rms_norm_eps

    def one_layer(h, p):
        hn = rms_norm(h, _norm_w(cfg, p["input_layernorm"]["weight"], h), eps)
        q, k_new, v_new = _qkv_proj(p["self_attn"], hn, cos, sin)
        out = ring_attention(q, k_new, v_new, causal=True, mesh=mesh, batch_axes=batch_axes)
        h = h + _out_proj(out.astype(h.dtype), p["self_attn"]["o_proj"]["kernel"])
        hn = rms_norm(h, _norm_w(cfg, p["post_attention_layernorm"]["weight"], h), eps)
        h = h + _mlp(cfg, p["mlp"], hn)
        return h, (k_new.astype(cfg.dtype), v_new.astype(cfg.dtype))

    x, (pk, pv) = jax.lax.scan(one_layer, x, stacked)
    x = rms_norm(x, _norm_w(cfg, final_norm, x), eps)
    if _manual_cp(mesh):
        last = _last_position(x, mesh, batch_axes)
    else:
        last = x[:, -1]
    logits = last @ head.astype(cfg.dtype)
    return logits.astype(jnp.float32), pk, pv


def _decode_loop(cfg, params, first_token, prefix_k, prefix_v, max_new_tokens,
                 *, rng, temperature, top_k, top_p, eos_token_id, pad_token_id,
                 prompt_len, finished0=None, mesh=None, batch_axes=()):
    """lax.scan over decode steps. Tail caches are replicated (N is small);
    the prefix stays sequence-sharded — attention merges per-chip partials."""
    stacked, embed, final_norm, head = _unpack(cfg, params)
    b = first_token.shape[0]
    n_layers, _, _, hkv, d = prefix_k.shape
    n_tail = max_new_tokens
    eps = cfg.rms_norm_eps

    tail_k = jnp.zeros((n_layers, b, n_tail, hkv, d), cfg.dtype)
    tail_v = jnp.zeros_like(tail_k)

    def forward_one(token, t, tk_all, tv_all):
        x = _embed_tokens(cfg, embed, token[:, None])
        pos = jnp.broadcast_to(
            jnp.asarray(prompt_len + t, jnp.int32)[None, None], (b, 1)
        )
        cos, sin = rotary_embedding(pos, cfg.head_dim, cfg.rope_theta, x.dtype)

        def one_layer(h, layer):
            p, pk, pv, tk, tv = layer
            hn = rms_norm(h, _norm_w(cfg, p["input_layernorm"]["weight"], h), eps)
            q, k_new, v_new = _qkv_proj(p["self_attn"], hn, cos, sin)
            tk = jax.lax.dynamic_update_slice(tk, k_new.astype(tk.dtype), (0, t, 0, 0))
            tv = jax.lax.dynamic_update_slice(tv, v_new.astype(tv.dtype), (0, t, 0, 0))
            # Flash-decoding: partials against the LOCAL prefix shard, merged
            # over cp with manual pmax/psum collectives, plus partials
            # against the replicated tail.
            if mesh is not None and _manual_cp(mesh):
                stats_prefix = _prefix_stats_sharded(q, pk, pv, mesh, batch_axes)
            else:
                stats_prefix = attention_stats(q, pk, pv, causal=False)
            stats_tail = attention_stats(q, tk, tv, causal=False, kv_valid_len=t + 1)
            out = _merge_stats([stats_prefix, stats_tail])
            h = h + _out_proj(out.astype(h.dtype), p["self_attn"]["o_proj"]["kernel"])
            hn = rms_norm(h, _norm_w(cfg, p["post_attention_layernorm"]["weight"], h), eps)
            h = h + _mlp(cfg, p["mlp"], hn)
            return h, (tk, tv)

        x, (tk_all, tv_all) = jax.lax.scan(
            one_layer, x, (stacked, prefix_k, prefix_v, tk_all, tv_all)
        )
        x = rms_norm(x, _norm_w(cfg, final_norm, x), eps)
        logits = (x[:, -1] @ head.astype(cfg.dtype)).astype(jnp.float32)
        return logits, tk_all, tv_all

    def pick(logits, key):
        if temperature is None or temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return sample_logits(
            logits, key, temperature=temperature, top_k=top_k, top_p=top_p
        )

    def step(carry, t):
        token, tk_all, tv_all, finished, key = carry
        key, sub = jax.random.split(key)
        logits, tk_all, tv_all = forward_one(token, t, tk_all, tv_all)
        nxt = pick(logits, sub)
        if eos_token_id is not None:
            nxt = jnp.where(finished, pad_token_id, nxt)
            finished = finished | (nxt == eos_token_id)
        return (nxt, tk_all, tv_all, finished, key), nxt

    finished = finished0 if finished0 is not None else jnp.zeros((b,), bool)
    key = rng if rng is not None else jax.random.key(0)
    _, toks = jax.lax.scan(
        step,
        (first_token, tail_k, tail_v, finished, key),
        jnp.arange(max_new_tokens, dtype=jnp.int32),
    )
    return toks.T  # (B, N)


def cp_generate(
    model,
    input_ids,
    max_new_tokens: int,
    *,
    temperature: Optional[float] = None,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_token_id: Optional[int] = None,
    pad_token_id: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> jax.Array:
    """Generate with the prompt sequence sharded over the ``cp`` mesh axis.

    ``input_ids`` (B, S): S must divide by the cp degree. Returns
    (B, S + max_new_tokens) like :func:`generation.generate`. Greedy output
    is token-identical to the single-chip path (pinned by tests).
    """
    from .state import AcceleratorState

    cfg = model.module.config
    params = model.params
    if mesh is None:
        mesh = AcceleratorState().mesh
    cp = mesh.shape.get("cp", 1)
    b, s = input_ids.shape
    if max_new_tokens <= 0:
        # (B, S + 0): the documented contract — matches generation.generate,
        # whose lax.scan over arange(0) appends nothing.
        return jnp.asarray(input_ids, jnp.int32)
    if s % cp != 0:
        raise ValueError(f"prompt length {s} must divide by cp={cp}")
    if not cfg.scan_layers:
        raise ValueError("cp_generate requires scan_layers=True (stacked blocks)")
    max_pos = getattr(cfg, "max_position_embeddings", None)
    if max_pos is not None and s + max_new_tokens > max_pos:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_position_embeddings ({max_pos})"
        )
    if pad_token_id is None:
        pad_token_id = eos_token_id if eos_token_id is not None else 0

    dp = _dp_axes(mesh)
    dp_total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if b % dp_total != 0:
        dp = ()  # small generation batches replicate over dp
    ids_sharding = NamedSharding(mesh, P(dp if dp else None, "cp"))
    prefix_spec = P(None, dp if dp else None, "cp", None, None)

    key = (
        id(model.module), cfg, b, s, int(max_new_tokens), temperature, top_k,
        top_p, eos_token_id, pad_token_id, mesh,
    )
    fn = _CP_LOOP_CACHE.get(key)
    if fn is None:

        def run(params, ids, rng_key):
            logits0, pk, pv = _prefill(cfg, params, ids, mesh, batch_axes=dp)
            pk = jax.lax.with_sharding_constraint(pk, NamedSharding(mesh, prefix_spec))
            pv = jax.lax.with_sharding_constraint(pv, NamedSharding(mesh, prefix_spec))
            if temperature is None or temperature <= 0:
                first = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
            else:
                rng_key, sub = jax.random.split(rng_key)
                first = sample_logits(
                    logits0, sub, temperature=temperature, top_k=top_k, top_p=top_p
                )
            finished0 = jnp.zeros((b,), bool)
            if eos_token_id is not None:
                finished0 = first == eos_token_id
            rest = _decode_loop(
                cfg, params, first, pk, pv, max_new_tokens - 1,
                rng=rng_key, temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id, pad_token_id=pad_token_id,
                prompt_len=s,  # `first` sits at position s; step t decodes s+t
                finished0=finished0, mesh=mesh, batch_axes=dp,
            ) if max_new_tokens > 1 else jnp.zeros((b, 0), jnp.int32)
            ids_full = _gather_seq(ids, mesh, dp) if _manual_cp(mesh) else ids
            out = jnp.concatenate([ids_full, first[:, None], rest], axis=1)
            return out

        fn = _CP_LOOP_CACHE[key] = jax.jit(run)
        while len(_CP_LOOP_CACHE) > 32:  # FIFO cap, same rationale as
            _CP_LOOP_CACHE.pop(next(iter(_CP_LOOP_CACHE)))  # _GEN_LOOP_CACHE

    ids = jax.device_put(jnp.asarray(input_ids, jnp.int32), ids_sharding)
    rng_key = rng if rng is not None else jax.random.key(0)
    return fn(params, ids, rng_key)
