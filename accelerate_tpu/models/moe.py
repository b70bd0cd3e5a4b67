"""Mixtral-family sparse-MoE decoder, TPU-first.

The reference reaches expert parallelism only through engine adapters
(Megatron-LM ``expert_model_parallel_size``, reference:
utils/dataclasses.py:2433,2441; DeepSpeed-MoE leaf-module marking, reference:
accelerator.py:2287) — the experts themselves live in external libraries. A
TPU-native framework owns the MoE layer, and designs it for the MXU:

- **dense GShard-style dispatch**: token→expert routing becomes three static-
  shape einsums (dispatch, batched expert matmul, combine) instead of gather/
  scatter — no dynamic shapes, everything tiles onto the MXU, and XLA turns
  the dispatch/combine contractions into all-to-alls over the ``ep`` axes
  when the expert dim is sharded (parallelism_config.ep_axes).
- **capacity-based**: each expert processes a fixed ``capacity`` of token
  slots per batch (GShard/Switch semantics); overflow tokens fall through on
  the residual path. ``capacity_factor`` trades drop rate for padding waste.
- **stacked experts**: all E experts' weights live in ONE tensor with a
  leading expert dim — a single batched einsum computes every expert, and the
  expert dim is just another sharding axis.
- **aux load-balance loss** sown to the ``"losses"`` collection; pull it with
  ``mutable=["losses"]`` (see ``moe_cross_entropy_loss``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .llama import (
    LlamaAttention,
    LlamaConfig,
    RMSNorm,
    _pin_last_dim_replicated,
    cross_entropy_loss,
    require_single_pass,
)


@dataclasses.dataclass(unsafe_hash=True)
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    capacity_factor: float = 2.0
    router_aux_loss_coef: float = 0.02

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=512, num_local_experts=4,
            num_experts_per_tok=2,
        )
        defaults.update(kw)
        return cls(**defaults)


def compute_dispatch(
    router_probs: jax.Array, num_experts_per_tok: int, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """GShard-style dispatch/combine tensors from router probabilities.

    Args:
      router_probs: (T, E) softmax outputs.
      capacity: per-expert token slots C.

    Returns:
      dispatch: (T, E, C) one-hot {0,1} — token t occupies slot c of expert e.
      combine: (T, E, C) — dispatch weighted by the (top-k renormalized)
        router weight, used to mix expert outputs back per token.
    """
    T, E = router_probs.shape
    k = num_experts_per_tok
    topk_vals, topk_idx = jax.lax.top_k(router_probs, k)  # (T, k)
    topk_vals = topk_vals / jnp.maximum(topk_vals.sum(-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(topk_idx, E, dtype=jnp.int32)  # (T, k, E)
    # Queue position per (token, slot): tokens claim expert slots in token-
    # major order, k-th choices after (k-1)-th for the same token. Flatten
    # (T, k) with slot-fastest so earlier tokens win capacity.
    flat = onehot.reshape(T * k, E)
    position = jnp.cumsum(flat, axis=0) - flat  # (T*k, E) slot index if chosen
    position = position.reshape(T, k, E)
    within_capacity = (position < capacity) & (onehot > 0)

    weights = jnp.where(within_capacity.any(-1), topk_vals, 0.0)  # (T, k)
    pos_onehot = jax.nn.one_hot(  # (T, k, E, C)
        jnp.where(within_capacity, position, capacity), capacity, dtype=router_probs.dtype
    ) * within_capacity[..., None]
    dispatch = pos_onehot.sum(1)  # (T, E, C)
    combine = (pos_onehot * weights[:, :, None, None]).sum(1)
    return dispatch, combine


def load_balance_loss(router_probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Switch-Transformer aux loss: E * Σ_e fraction_dispatched_e * mean_prob_e."""
    E = router_probs.shape[-1]
    tokens_per_expert = dispatch.sum((0, 2))  # (E,)
    frac = tokens_per_expert / jnp.maximum(dispatch.sum(), 1.0)
    mean_prob = router_probs.mean(0)
    return E * jnp.sum(frac * mean_prob.astype(jnp.float32))


class MoeLayer(nn.Module):
    """Sparse SwiGLU expert layer (Mixtral MLP shape) with stacked experts."""

    config: MixtralConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, d = x.shape
        E, k, f = cfg.num_local_experts, cfg.num_experts_per_tok, cfg.intermediate_size
        T = B * S
        capacity = int(np.ceil(k * T / E * cfg.capacity_factor))
        capacity = max(1, min(capacity, T))

        tokens = x.reshape(T, d)
        router_kernel = self.param(
            "router", nn.initializers.lecun_normal(), (d, E), jnp.float32
        )
        router_logits = (tokens.astype(jnp.float32) @ router_kernel).astype(jnp.float32)
        router_probs = jax.nn.softmax(router_logits, axis=-1)
        dispatch, combine = compute_dispatch(router_probs, k, capacity)
        self.sow(
            "losses", "router_aux_loss",
            cfg.router_aux_loss_coef * load_balance_loss(router_probs, dispatch),
        )

        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("w_gate", init, (E, d, f), jnp.float32)
        w_up = self.param("w_up", init, (E, d, f), jnp.float32)
        w_down = self.param("w_down", init, (E, f, d), jnp.float32)

        dtype = cfg.dtype
        # dispatch: (T, E, C) → expert inputs (E, C, d). Under ep sharding of
        # the E dim this contraction IS the all-to-all.
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), tokens.astype(dtype))
        h = nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dtype))
        ye = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))
        out = jnp.einsum("ecd,tec->td", ye, combine.astype(dtype))
        return out.reshape(B, S, d)


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        h = x + LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x), positions
        )
        out = h + MoeLayer(cfg, name="moe")(
            RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(h)
        )
        return out


class _ScannedMixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = MixtralBlock(self.config, name="block")(x, positions)
        return (x, positions), None


class MixtralModel(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        require_single_pass(cfg, "MixtralModel")
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32,
            name="embed_tokens",
        )(input_ids)
        positions = jnp.arange(input_ids.shape[-1])[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, input_ids.shape)
        if cfg.scan_layers:
            block = _ScannedMixtralBlock
            if cfg.remat:
                block = nn.remat(block, prevent_cse=False)
            scanned = nn.scan(
                block,
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True},
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")
            (x, _), _ = scanned((x, positions), None)
        else:
            for i in range(cfg.num_hidden_layers):
                blk = MixtralBlock
                if cfg.remat:
                    blk = nn.remat(blk, prevent_cse=False)
                x = blk(cfg, name=f"layers_{i}")(x, positions)
        return RMSNorm(cfg.rms_norm_eps, name="norm")(x)


class MixtralForCausalLM(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        x = MixtralModel(cfg, name="model")(input_ids)
        x = _pin_last_dim_replicated(x)  # FSDP propagation guard (llama.py)
        if cfg.tie_word_embeddings:
            embed = self.variables["params"]["model"]["embed_tokens"]["embedding"]
            return x @ embed.T.astype(cfg.dtype)
        return nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
            name="lm_head",
        )(x)


def mixtral_tp_rules(
    scan_layers: bool = True, ep_axes: tuple[str, ...] = ()
) -> list[tuple[str, tuple]]:
    """TP + EP rule table: attention is Megatron-TP like Llama; stacked expert
    weights shard their expert dim over ``ep_axes``
    (ParallelismConfig.ep_axes). The router stays replicated."""
    lead = (None,) if scan_layers else ()
    ep = ep_axes if len(ep_axes) != 1 else ep_axes[0]
    rules: list[tuple[str, tuple]] = [
        (r"self_attn/(q_proj|k_proj|v_proj)/kernel", lead + (None, "tp", None)),
        (r"self_attn/o_proj/kernel", lead + ("tp", None, None)),
        (r"embed_tokens/embedding", ("tp", None)),
        (r"lm_head/kernel", (None, "tp")),
    ]
    if ep_axes:
        rules += [
            (r"moe/(w_gate|w_up|w_down)", lead + (ep, None, None)),
        ]
    else:
        # Pure TP fallback: shard the ffn dim of every expert.
        rules += [
            (r"moe/(w_gate|w_up)", lead + (None, None, "tp")),
            (r"moe/w_down", lead + (None, "tp", None)),
        ]
    return [(pat, P(*spec) if isinstance(spec, tuple) else spec) for pat, spec in rules]


def moe_cross_entropy_loss(module, params, input_ids, labels, ignore_index: int = -100):
    """CE + the sown router aux losses (the loss_fn to hand to
    ``prepare_train_step`` for MoE models)."""
    logits, collections = module.apply(
        {"params": params}, input_ids, mutable=["losses"]
    )
    ce = cross_entropy_loss(logits, labels, ignore_index)
    aux = sum(
        jnp.sum(v) for v in jax.tree.leaves(collections.get("losses", {}))
    )
    return ce + aux
