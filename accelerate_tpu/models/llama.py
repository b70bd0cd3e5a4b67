"""Llama-family decoder, TPU-first.

The reference framework does not ship models (it wraps user torch modules +
transformers' ``tp_plan``, reference: accelerator.py:1580-1656); a TPU-native
framework must own the TP rule tables and the flagship architecture used by
its benchmarks (BASELINE.json: FSDP2 Llama-7B tokens/sec/chip). Design points:

- **MXU-shaped**: all projections are single large matmuls in bf16; head dim
  128 (= MXU lane width); no per-head Python loops.
- **scan over layers**: identical blocks rolled into one ``nn.scan`` — one
  trace/compile of the block instead of L (the analog of the reference's
  "regional compilation", utils/other.py:106-177, its 5-9× compile win).
- **remat**: optional ``nn.remat`` on the block to trade FLOPs for HBM.
- **TP rules**: Megatron-style column/row parallel table as name-regex →
  PartitionSpec over the ``tp`` mesh axis; composes with FSDP sharding of the
  remaining dim (parallel/sharding.py).
- **attention seam**: the inner attention call dispatches on the active mesh
  (cp → ring attention, sp → Ulysses all-to-all, else flash/native) so the
  same module serves all sequence-parallel modes.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..utils.environment import inside_shard_map


@dataclasses.dataclass(unsafe_hash=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    attention_bias: bool = False        # Qwen2-style checkpoints: bias on q/k/v
    # Decoder-variant knobs (all default off → plain Llama). These make the
    # family a configurable decoder chassis: most Llama-era architectures
    # (StarCoder2, StableLM, InternLM2, Granite, ...) are this block with
    # different constants, which is what lets models/generic_hub.py ingest
    # unseen checkpoints with declarative rules instead of new module code.
    norm_type: str = "rmsnorm"          # "layernorm": mean-centered, with bias
    mlp_gated: bool = True              # False: up_proj -> act -> down_proj
    mlp_bias: bool = False              # biases on the MLP projections
    attention_out_bias: bool = False    # bias on o_proj
    partial_rotary_factor: float = 1.0  # rotate only this fraction of head_dim
    # Granite-style scaling constants (all 1.0 → plain Llama). The attention
    # multiplier replaces the 1/sqrt(head_dim) score scale; it is folded into
    # the q projection output (q *= mult*sqrt(d)) so every attention impl —
    # the Pallas kernel included — runs unchanged.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # Gemma-family quirks (all default off → plain Llama):
    hidden_act: str = "silu"            # "gelu_tanh" for Gemma's GeGLU
    rms_norm_plus_one: bool = False     # norm scale stored as (weight + 1)
    scale_embeddings: bool = False      # multiply embeddings by sqrt(hidden)
    # A stack run several times over one set of weights (a looped, or
    # universal, transformer; all default off → plain Llama). The same
    # layers run ``total_ut_steps`` times, the final norm closes every pass
    # and its output opens the next; a pass attends over its own keys and
    # values, so the cache holds passes x layers planes (kv_cache.cache_spec).
    total_ut_steps: int = 1
    sandwich_norm: bool = False         # a second norm on each branch's output
    early_exit_gate: bool = False       # Linear(H, 1) on every pass's output
    # Exit at the first pass whose cumulative exit probability reaches this.
    # 1 runs every pass, and is all that is computed: under 1 the rows of
    # one batch would leave after different passes.
    early_exit_threshold: float = 1.0
    dtype: Any = jnp.bfloat16          # compute dtype (params stay fp32 masters)
    scan_layers: bool = True
    remat: bool = False
    # What the block remat saves (only meaningful with remat=True):
    #   flash   — keep the flash kernel's O(S) residuals, recompute the rest
    #   dots    — additionally keep every matmul output (recompute only
    #             elementwise ops; more HBM, fewer recomputed FLOPs)
    #   minimal — recompute everything, flash kernel included
    remat_policy: str = "flash"
    # flash = Pallas fused kernel on TPU (blockwise scan fallback off-TPU);
    # native = materialized O(S²) softmax, kept for parity tests.
    attention_impl: str = "flash"       # flash | native | ring | ulysses
    fp8: bool = False                   # fp8 matmuls in MLP/attention projections
    fp8_format: str = "HYBRID"          # E4M3 | E5M2 | HYBRID (e4m3 fwd / e5m2 bwd)
    fp8_backend: str = "AUTO"           # AUTO | TE | AO | QDQ (ops/fp8.py backend_to_native)

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.norm_type not in ("rmsnorm", "layernorm"):
            raise ValueError(f"norm_type must be rmsnorm|layernorm, got {self.norm_type}")
        if self.rotary_dim % 2:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of head_dim "
                f"{self.head_dim} gives odd rotary_dim {self.rotary_dim}"
            )
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps must be at least 1, got {self.total_ut_steps}")
        if self.early_exit_threshold < 1:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold} is not supported: only 1 "
                "(every one of the total_ut_steps passes runs, for every token) is computed"
            )

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def dot_general(self):
        """dot_general injected into every projection: fp8 when enabled
        (ops/fp8.py — the reference's TE/AO fp8 linear swap role), else the
        XLA default."""
        if not self.fp8:
            return None
        from ..ops.fp8 import backend_to_native, fp8_dot_general

        return fp8_dot_general(
            self.fp8_format, native=backend_to_native(self.fp8_backend)
        )

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(
            vocab_size=256, hidden_size=128, intermediate_size=384,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=512,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama_7b(cls, **kw):
        return cls(**kw)

    @classmethod
    def llama_1b(cls, **kw):
        return cls(
            hidden_size=2048, intermediate_size=5504, num_hidden_layers=16,
            num_attention_heads=16, num_key_value_heads=16, **kw,
        )


def require_single_pass(cfg, what: str) -> None:
    """Refuse, rather than run the stack once, where a walker of the layers
    other than ``LlamaModel`` and the cached forward is handed a config that
    asks for several passes."""
    if getattr(cfg, "total_ut_steps", 1) != 1:
        raise NotImplementedError(
            f"{what} runs the layer stack once; total_ut_steps={cfg.total_ut_steps} is "
            "computed by LlamaForCausalLM and the cached generation path only")


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def layer_norm(x, weight, bias, eps):
    """Functional mean-centered norm — the single source of the numerics
    shared by the LayerNorm module (training) and generation's KV-cache
    decode plan (parity depends on them staying bit-identical)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight + bias).astype(x.dtype)


def scale_residual(y, mult: float):
    """Branch residual scaling (Granite residual_multiplier) — single source
    for the training module and generation's decode plan."""
    return y if mult == 1.0 else y * jnp.asarray(mult, y.dtype)


def apply_partial_rope(x, cos, sin, rotary_dim):
    """RoPE on the leading ``rotary_dim`` dims, pass-through on the rest
    (StableLM/NeoX-style); shared by LlamaAttention and the decode plan."""
    d = x.shape[-1]
    if rotary_dim == d:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [apply_rope(x[..., :rotary_dim], cos, sin), x[..., rotary_dim:]], -1
    )


class RMSNorm(nn.Module):
    eps: float = 1e-5
    plus_one: bool = False  # Gemma stores scale as (weight + 1), init zeros

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.plus_one else nn.initializers.ones
        weight = self.param("weight", init, (x.shape[-1],), jnp.float32)
        if self.plus_one:
            weight = weight + 1.0
        return rms_norm(x, weight.astype(x.dtype), self.eps)


class LayerNorm(nn.Module):
    """Mean-centered norm with bias, params named weight/bias to match the
    torch checkpoint convention the hub mappings use (flax's nn.LayerNorm
    calls them scale/bias)."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        return layer_norm(x, weight, bias, self.eps)


def make_norm(cfg: "LlamaConfig", name: str):
    if cfg.norm_type == "layernorm":
        return LayerNorm(cfg.rms_norm_eps, name=name)
    return RMSNorm(cfg.rms_norm_eps, cfg.rms_norm_plus_one, name=name)


def activation_fn(name: str):
    table = {
        "silu": nn.silu,
        "gelu": partial(nn.gelu, approximate=False),
        "gelu_tanh": partial(nn.gelu, approximate=True),
        "gelu_new": partial(nn.gelu, approximate=True),
        "gelu_pytorch_tanh": partial(nn.gelu, approximate=True),
        "relu": nn.relu,
    }
    if name not in table:
        raise ValueError(f"Unknown hidden_act {name!r}; known: {sorted(table)}")
    return table[name]


def rotary_embedding(positions: jax.Array, head_dim: int, theta: float, dtype) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for RoPE, computed on the fly (cheap, fuses)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, D/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D) or (S, D)."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def naive_attention(q, k, v, *, causal: bool = True, segment_positions=None):
    """Reference attention in pure jnp — correct under GSPMD for dp/tp/fsdp.
    q: (B, S, Hq, D); k/v: (B, S, Hkv, D). GQA via head repetition (XLA turns
    the broadcast into an efficient layout, no materialized copy)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sk = k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None, None], logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _dispatch_attention(impl: str):
    if impl in ("native",):
        return naive_attention
    if impl == "flash":
        from ..ops.flash_attention import auto_flash_attention

        return auto_flash_attention
    if impl == "ring":
        from ..parallel.cp import ring_attention

        return ring_attention
    if impl == "ulysses":
        from ..parallel.sp import ulysses_attention

        return ulysses_attention
    raise ValueError(f"Unknown attention_impl {impl}")


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        d = cfg.head_dim
        dense = partial(
            nn.DenseGeneral, use_bias=cfg.attention_bias, dtype=cfg.dtype,
            param_dtype=jnp.float32,
            **({"dot_general": cfg.dot_general} if cfg.fp8 else {}),
        )
        q = dense(features=(cfg.num_attention_heads, d), name="q_proj")(x)
        k = dense(features=(cfg.num_key_value_heads, d), name="k_proj")(x)
        v = dense(features=(cfg.num_key_value_heads, d), name="v_proj")(x)
        if cfg.attention_multiplier is not None:
            # Exact: attn computes (q*c*sqrt(d)) . k / sqrt(d) = c * (q.k).
            q = q * jnp.asarray(
                cfg.attention_multiplier * np.sqrt(d), q.dtype
            )
        rd = cfg.rotary_dim
        cos, sin = rotary_embedding(positions, rd, cfg.rope_theta, x.dtype)
        q = apply_partial_rope(q, cos, sin, rd)
        k = apply_partial_rope(k, cos, sin, rd)
        attn_fn = _dispatch_attention(cfg.attention_impl)
        out = attn_fn(q, k, v, causal=True)
        return nn.DenseGeneral(
            features=x.shape[-1], axis=(-2, -1), use_bias=cfg.attention_out_bias,
            dtype=cfg.dtype, param_dtype=jnp.float32, name="o_proj",
            **({"dot_general": cfg.dot_general} if cfg.fp8 else {}),
        )(out)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = partial(
            nn.Dense, use_bias=cfg.mlp_bias, dtype=cfg.dtype, param_dtype=jnp.float32,
            **({"dot_general": cfg.dot_general} if cfg.fp8 else {}),
        )
        act = activation_fn(cfg.hidden_act)
        up = dense(cfg.intermediate_size, name="up_proj")(x)
        if cfg.mlp_gated:
            gate = dense(cfg.intermediate_size, name="gate_proj")(x)
            hidden = act(gate) * up
        else:  # plain 2-layer MLP (GPT/StarCoder2-style)
            hidden = act(up)
        return dense(cfg.hidden_size, name="down_proj")(hidden)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        rm = cfg.residual_multiplier
        attn = LlamaAttention(cfg, name="self_attn")(
            make_norm(cfg, "input_layernorm")(x), positions
        )
        if cfg.sandwich_norm:
            attn = make_norm(cfg, "input_layernorm_2")(attn)
        h = x + scale_residual(attn, rm)
        ffn = LlamaMLP(cfg, name="mlp")(make_norm(cfg, "post_attention_layernorm")(h))
        if cfg.sandwich_norm:
            ffn = make_norm(cfg, "post_attention_layernorm_2")(ffn)
        return h + scale_residual(ffn, rm)


class _ScannedBlock(nn.Module):
    """LlamaBlock wrapped for nn.scan: carry = hidden states."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = LlamaBlock(self.config, name="block")(x, positions)
        return (x, positions), None


class LlamaModel(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32,
            name="embed_tokens",
        )(input_ids)
        if cfg.scale_embeddings:  # Gemma normalizer
            x = x * jnp.asarray(np.sqrt(cfg.hidden_size), cfg.dtype)
        if cfg.embedding_multiplier != 1.0:  # Granite scaling
            x = x * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        positions = jnp.arange(input_ids.shape[-1])[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, input_ids.shape)
        # Selective remat: with the flash kernel the attention residuals
        # (out, lse) are O(S), so save exactly those and recompute the rest —
        # the backward reuses the kernel outputs instead of re-running the
        # forward kernel. (With native attention there is nothing cheap to
        # save; plain full-block remat applies.)
        remat_kwargs = {"prevent_cse": False}
        policy = cfg.remat_policy
        if os.environ.get("ACCELERATE_FLASH_REMAT_POLICY", "1") == "0":
            policy = "minimal"  # legacy escape hatch
        if cfg.remat and policy != "minimal":
            save_flash = jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"
            )
            if policy == "dots":
                remat_kwargs["policy"] = jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable, save_flash
                )
            elif cfg.attention_impl != "native":
                remat_kwargs["policy"] = save_flash
        if cfg.scan_layers:
            block = _ScannedBlock
            if cfg.remat:
                block = nn.remat(block, **remat_kwargs)
            scanned = nn.scan(
                block,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")

            def stack(x):
                return scanned((x, positions), None)[0][0]
        else:
            blk = nn.remat(LlamaBlock, **remat_kwargs) if cfg.remat else LlamaBlock
            blocks = [blk(cfg, name=f"layers_{i}") for i in range(cfg.num_hidden_layers)]

            def stack(x):
                for b in blocks:
                    x = b(x, positions)
                return x
        # The same modules in every pass: one set of weights. The last pass's
        # output is the model's (early_exit_threshold 1); the training
        # objective over all passes' exits (arXiv:2510.25741) is not built.
        norm = make_norm(cfg, "norm")
        gate = nn.Dense(1, dtype=cfg.dtype, param_dtype=jnp.float32,
                        name="early_exit_gate") if cfg.early_exit_gate else None
        for _ in range(cfg.total_ut_steps):
            x = norm(stack(x))
            if gate is not None:  # one (B, S) row of logits a pass, on request
                self.sow("intermediates", "exit_gate_logits", gate(x)[..., 0])
        return x


class LlamaForCausalLM(nn.Module):
    """Logits of the last pass over the stack (there is one, unless
    ``total_ut_steps`` says more). A looped model's published training
    objective, the expected loss over every pass's exit (arXiv:2510.25741),
    is not computed here: the module serves inference and the cached
    forward's parity tests; the exit gate's logits are sown under
    ``intermediates`` for whoever wants the exit distribution."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        x = LlamaModel(cfg, name="model")(input_ids)
        x = _pin_last_dim_replicated(x)  # see helper: kills FSDP param-sharding
        if cfg.tie_word_embeddings:     # propagation into the loss graph
            embed = self.variables["params"]["model"]["embed_tokens"]["embedding"]
            logits = x @ embed.T.astype(cfg.dtype)
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
                name="lm_head",
            )(x)
        if cfg.logits_scaling != 1.0:  # Granite: logits / scaling
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        return logits



# ---------------------------------------------------------------------------
# Tensor-parallel rule table (the role of transformers' tp_plan, owned
# in-framework per SURVEY.md §7 hard-part 3). Regexes match "/"-joined param
# paths; specs are dim-aligned with the param shapes. With scan_layers the
# block params gain a leading layer dim, hence the leading None.
# ---------------------------------------------------------------------------

def llama_tp_rules(scan_layers: bool = True) -> list[tuple[str, tuple]]:
    lead = (None,) if scan_layers else ()
    rules = [
        # Column-parallel: shard heads/ffn (output) dim.
        (r"self_attn/(q_proj|k_proj|v_proj)/kernel", lead + (None, "tp", None)),
        (r"mlp/(gate_proj|up_proj)/kernel", lead + (None, "tp")),
        # Row-parallel: shard input dim; XLA inserts the psum on the output.
        (r"self_attn/o_proj/kernel", lead + ("tp", None, None)),
        (r"mlp/down_proj/kernel", lead + ("tp", None)),
        # Embedding + head sharded on vocab.
        (r"embed_tokens/embedding", ("tp", None)),
        (r"lm_head/kernel", (None, "tp")),
    ]
    return [(pat, P(*spec) if isinstance(spec, tuple) else spec) for pat, spec in rules]


def fused_cross_entropy_loss(config, params, input_ids, labels,
                             ignore_index: int = -100, chunk_size: int = 256):
    """Causal-LM loss with the head matmul folded into a chunked loss.

    The naive path materializes (B, S, V) logits and log-softmaxes them in
    fp32 — for a 32k vocab at seq 2048 that's gigabytes of HBM traffic per
    step, pure bandwidth with no MXU work. Here the sequence is scanned in
    ``chunk_size`` slices: each slice's logits live only inside the scan body
    (rematerialized in the backward), and the loss needs just the slice's
    log-sum-exp and the label logit. Exactly equal to
    ``cross_entropy_loss(module.apply(...), labels)`` up to fp32 summation
    order.

    ``params`` is the full LlamaForCausalLM tree (``model`` + optional
    ``lm_head``).
    """
    cfg = config
    hidden = LlamaModel(cfg, name="model").apply({"params": params["model"]}, input_ids)
    # Same FSDP/HSDP propagation fix as LlamaForCausalLM.__call__ /
    # cross_entropy_loss: without these pins the sharded head param leaks
    # vocab/hidden sharding into the scan-local loss graph and the backward
    # pays an involuntary full rematerialization (see _pin_last_dim_replicated).
    hidden = _pin_last_dim_replicated(hidden)
    if cfg.tie_word_embeddings:
        head = params["model"]["embed_tokens"]["embedding"].T
    else:
        head = params["lm_head"]["kernel"]
    head = head.astype(cfg.dtype)  # (H, V)

    b, s, h = hidden.shape
    n_chunks = max(1, s // chunk_size)
    if s % chunk_size:
        n_chunks, chunk_size = 1, s  # odd tails: fall back to one chunk
    hc = hidden.reshape(b, n_chunks, chunk_size, h).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_loss(carry, xs):
        hx, y = xs
        logits = _pin_last_dim_replicated((hx @ head).astype(jnp.float32))
        valid = y != ignore_index
        safe = jnp.where(valid, y, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        token_loss = jnp.where(valid, lse - picked, 0.0)
        loss_sum, count = carry
        return (loss_sum + token_loss.sum(), count + valid.sum()), None

    (loss_sum, count), _ = jax.lax.scan(chunk_loss, (0.0, 0), (hc, yc))
    return loss_sum / jnp.maximum(count, 1)


def _pin_last_dim_replicated(x):
    """Constrain ``x``'s last dim to replicated; other dims stay
    UNCONSTRAINED (free for batch/seq propagation).

    Applied at the two activation boundaries around the unembed matmul
    (final hidden and logits). Under FSDP/HSDP every param — including 1-D
    norm scales and the lm_head kernel — is sharded over ``dp_shard``, and
    shardy propagates those param shardings into the activations (hidden /
    vocab dim sharded), while the label-scatter path of the CE backward
    stays batch-sharded. The mismatched cotangents meet in an ``add_any``
    that GSPMD can only reconcile by involuntary full rematerialization
    (replicate + repartition — the ``[SPMD]`` compile warning; wasted HBM +
    ICI every step). Pinning just the feature dim keeps the loss graph
    batch-sharded; sharded params are all-gathered at use like any other
    FSDP weight. (Block outputs are feature-replicated under Megatron-style
    TP too, so this is sharding-neutral for TP/CP/SP.) Passive singleton
    peek (no AcceleratorState construction) for the same reason as
    parallel/pp.py:_resolve_virtual_stages."""
    from ..state import AcceleratorState

    mesh = AcceleratorState._shared_state.get("_mesh")
    if mesh is None or getattr(x, "ndim", 0) < 2:
        return x
    if inside_shard_map():
        # Inside shard_map (manual axes) — e.g. a comm-hook step or the
        # GPipe stage body — sharding constraints don't apply (and raise);
        # the caller already controls the layout by hand.
        return x
    if mesh.shape.get("pp", 1) > 1:
        # Under GPipe the last stage computes the unembed inside shard_map
        # with its own stage-local layout; pinning the collected logits on
        # the global mesh would force a conflicting reshard in the backward
        # ppermute chain (observed as a fresh [SPMD] remat warning).
        return x
    if mesh.shape.get("tp", 1) > 1:
        # Megatron-style vocab-parallel TP (llama_tp_rules shards
        # lm_head/kernel and the embedding on tp) deliberately keeps the
        # vocab dim of logits tp-sharded; forcing replication here would
        # all-gather the full fp32 (B,S,V) logits every step.
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(*([P.UNCONSTRAINED] * (x.ndim - 1)), None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Token-level CE with masking — computed in fp32 regardless of compute
    dtype (loss reductions always fp32 on TPU to avoid bf16 accumulation
    error)."""
    logits = _pin_last_dim_replicated(logits).astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    token_loss = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    token_loss = jnp.where(valid, token_loss, 0.0)
    return token_loss.sum() / jnp.maximum(valid.sum(), 1)
