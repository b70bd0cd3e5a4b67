"""Big-model inference: load + run models larger than one chip's HBM.

TPU-native redesign of the reference's hook machinery (reference:
big_modeling.py:62-662, hooks.py:242-719). The reference intercepts every
``module.forward`` with ``AlignDevicesHook``s that fault weights in from
CPU/disk and evict them after. Python-per-module hooks would destroy XLA
fusion, so the equivalent here is *layer streaming*:

- params live where the device map put them (HBM / host numpy / disk memmap);
- the forward walks the model's layer stream plan, keeping at most two
  decoder blocks resident: while block *i* computes on the chip, block
  *i+1*'s weights ride the DMA in parallel (``jax.device_put`` is async),
  which is the role of the reference's ``AlignDevicesHook`` prefetch;
- each block reuses ONE jitted computation (identical shapes ⇒ one compile),
  the same trick as the reference's regional compilation
  (utils/other.py:106-177).

Models without a registered stream plan fall back to materialize-per-call
(exactly the reference's ``cpu_offload`` semantics, big_modeling.py:179-231).
"""

from __future__ import annotations

from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .model import Model
from .utils.modeling import (
    _DiskHandle,
    check_device_map,
    compute_abstract_params,
    default_execution_device,
    get_balanced_memory,
    get_max_memory,
    infer_auto_device_map,
    load_checkpoint_in_model,
    normalize_device_map,
    placement_for,
)
from .utils.offload import offload_state_dict
from .utils.other import flatten_state_dict, unflatten_state_dict

__all__ = [
    "init_empty_weights",
    "init_on_device",
    "cpu_offload",
    "cpu_offload_with_hook",
    "disk_offload",
    "dispatch_model",
    "load_checkpoint_and_dispatch",
    "DispatchedModel",
    "UserCpuOffloadHook",
    "register_stream_plan",
    "register_stream_spec",
]


def init_empty_weights(module, *sample_args, rng=None, **sample_kwargs):
    """Abstract-shape init — zero bytes allocated.

    The functional counterpart of the reference's meta-device context manager
    (big_modeling.py:62-178): returns a pytree of ``jax.ShapeDtypeStruct``
    describing ``module.init``'s params.
    """
    return compute_abstract_params(module, *sample_args, rng=rng, **sample_kwargs)


def init_on_device(device):
    """Context manager placing array creation (``module.init`` included) on
    ``device`` — host RAM via ``jax.local_devices(backend="cpu")[0]`` for
    models that must not touch HBM during init (reference:
    big_modeling.py:116-178 ``init_on_device``)."""
    return jax.default_device(device)


# ---------------------------------------------------------------------------
# Param resolver: faults groups in from their placement, with async prefetch
# ---------------------------------------------------------------------------


class ParamResolver:
    """Materialize param subtrees on the execution device on demand.

    ``prefetch`` enqueues the H2D copy immediately and returns; ``take``
    hands the arrays over and evicts them from the cache once consumed —
    together they give the double-buffered pipeline the reference builds
    with hook ``pre_forward``/``post_forward`` pairs (hooks.py:358-431).
    """

    def __init__(self, placed_params, device, sep: str = "/"):
        self.placed = placed_params
        self.device = device
        self.sep = sep
        self._cache: dict[str, Any] = {}
        self._cache_bytes: dict[str, int] = {}
        self.peak_cached_bytes = 0  # high-water mark of concurrently faulted params

    def _subtree(self, prefix: str):
        node = self.placed
        for part in prefix.split(self.sep):
            node = node[part]
        return node

    def _materialize(self, node, layer_index: Optional[int] = None):
        def _leaf(a):
            if isinstance(a, _DiskHandle):
                a = a.load()
            if layer_index is not None:
                a = a[layer_index]
            if isinstance(a, jax.Array) and a.devices() == {self.device}:
                return a
            return jax.device_put(np.asarray(a) if isinstance(a, np.memmap) else a, self.device)

        return jax.tree.map(_leaf, node)

    def _key(self, prefix, layer_index):
        return prefix if layer_index is None else f"{prefix}@{layer_index}"

    @staticmethod
    def _nbytes(tree) -> int:
        return sum(
            getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree)
        )

    def _insert(self, key, value):
        self._cache[key] = value
        self._cache_bytes[key] = self._nbytes(value)
        self.peak_cached_bytes = max(self.peak_cached_bytes, sum(self._cache_bytes.values()))

    def prefetch(self, prefix: str, layer_index: Optional[int] = None):
        key = self._key(prefix, layer_index)
        if key not in self._cache:
            self._insert(key, self._materialize(self._subtree(prefix), layer_index))

    def take(self, prefix: str, layer_index: Optional[int] = None):
        key = self._key(prefix, layer_index)
        if key in self._cache:
            self._cache_bytes.pop(key, None)
            return self._cache.pop(key)
        value = self._materialize(self._subtree(prefix), layer_index)
        self.peak_cached_bytes = max(
            self.peak_cached_bytes, sum(self._cache_bytes.values()) + self._nbytes(value)
        )
        return value

    def peek(self, prefix: str, layer_index: Optional[int] = None):
        """Like take but keeps resident (for groups already living on device)."""
        key = self._key(prefix, layer_index)
        if key not in self._cache:
            self._insert(key, self._materialize(self._subtree(prefix), layer_index))
        return self._cache[key]


# ---------------------------------------------------------------------------
# Generic layer-streaming engine
# ---------------------------------------------------------------------------
#
# The reference's ``AlignDevicesHook`` is architecture-agnostic because torch
# modules expose their submodule tree at runtime (hooks.py:586-719). The
# flax equivalent: every family here factors as
#   embed -> [identical blocks; scanned pytree has the per-layer split] -> head
# so a streamed forward is a *segment list* — cheap declarative specs below —
# walked by ONE engine that double-buffers the layer faults. Families without
# a spec fall back to materialize-per-call with a warning.

_STREAM_PLANS: dict[str, Callable] = {}
_STREAM_SPECS: dict[str, Callable] = {}
_JIT_CACHE: dict[Any, Callable] = {}


def register_stream_plan(module_class_name: str, fn: Callable):
    """Register ``fn(module, resolver, *args) -> output`` as the streamed
    forward for a model family (escape hatch for custom architectures; the
    built-in families use :func:`register_stream_spec`)."""
    _STREAM_PLANS[module_class_name] = fn


def register_stream_spec(module_class_name: str, builder: Callable):
    """Register ``builder(cfg) -> [Seg | LayerSeg, ...]`` for a family."""
    _STREAM_SPECS[module_class_name] = builder


def _jit_for(key, fn):
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = jax.jit(fn)
    return _JIT_CACHE[key]


class Seg:
    """One faulted group + one jitted fn: ``fn(params_tuple, *carry) -> carry``.

    ``prefixes`` are resolver groups faulted for this segment (passed to the
    fn as a tuple, in order); names in ``keep`` are ``peek``-ed so later
    segments reuse the upload (tied embeddings), the rest are ``take``-n and
    evicted once consumed.
    """

    def __init__(self, name: str, prefixes: list, fn: Callable, keep: tuple = ()):
        self.name = name
        self.prefixes = list(prefixes)
        self.fn = fn
        self.keep = set(keep)


class LayerSeg:
    """A streamed stack of identical blocks.

    The per-layer param split comes from the pytree layout itself: with
    ``scan_layers`` the stacked subtree at ``scan_prefix`` is sliced on its
    leading axis; otherwise ``unscan_fmt.format(i=i)`` names each block's own
    subtree. ``fn(block_params, *carry) -> carry`` runs per layer while the
    next layer's weights ride the DMA (double buffering).
    """

    def __init__(
        self,
        name: str,
        scan_prefix: str,
        unscan_fmt: str,
        n_layers: int,
        fn: Callable,
        offset: int = 0,
    ):
        self.name = name
        self.scan_prefix = scan_prefix
        self.unscan_fmt = unscan_fmt
        self.n_layers = n_layers
        self.fn = fn
        self.offset = offset  # unscanned name index start (T5's block_1..block_{n-1})


def _as_tuple(x):
    return x if isinstance(x, tuple) else (x,)


_warned_fallback: set = set()


def _spec_arity(segments) -> int:
    """Number of model inputs a spec's first segment consumes (its fn takes
    ``(params, *inputs)``)."""
    import inspect

    first = segments[0]
    return len(inspect.signature(first.fn).parameters) - 1


def _leaf_nbytes(leaf) -> int:
    if isinstance(leaf, _DiskHandle):
        return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return getattr(leaf, "nbytes", 0)


def _warn_materialize_fallback(cls_name, params, reason: str):
    """One warning per class: a dispatched model silently materializing
    everything on device was round-2's hidden OOM cliff."""
    if cls_name in _warned_fallback:
        return
    _warned_fallback.add(cls_name)
    total = sum(_leaf_nbytes(leaf) for leaf in jax.tree.leaves(params))
    # Plain stdlib logging: dispatch runs before/without Accelerator() init.
    import logging

    logging.getLogger(__name__).warning(
        "dispatch_model: %s cannot use layer streaming (%s) — the full param "
        "tree (%.2f GB) will be materialized on the execution device for "
        "every forward, defeating offload. register_stream_spec()/"
        "register_stream_plan() add streamed forwards for custom models.",
        cls_name or "<apply_fn model>",
        reason,
        total / 1e9,
    )


def _run_stream_spec(module, resolver: ParamResolver, segments, *inputs):
    cfg = module.config
    carry = tuple(jnp.asarray(a) for a in inputs)
    for seg in segments:
        if isinstance(seg, LayerSeg):
            if getattr(cfg, "scan_layers", False):
                keys = [(seg.scan_prefix, i) for i in range(seg.n_layers)]
            else:
                keys = [
                    (seg.unscan_fmt.format(i=i + seg.offset), None) for i in range(seg.n_layers)
                ]
            if not keys:
                continue
            fn = _jit_for((cfg, seg.name), seg.fn)
            resolver.prefetch(*keys[0])
            for i, (prefix, idx) in enumerate(keys):
                if i + 1 < len(keys):
                    resolver.prefetch(*keys[i + 1])  # DMA overlaps block i's compute
                carry = _as_tuple(fn(resolver.take(prefix, idx), *carry))
        else:
            params = tuple(
                resolver.peek(p) if p in seg.keep else resolver.take(p) for p in seg.prefixes
            )
            carry = _as_tuple(_jit_for((cfg, seg.name), seg.fn)(params, *carry))
    return carry[0]


def _positions_like(input_ids):
    return jnp.broadcast_to(
        jnp.arange(input_ids.shape[-1], dtype=jnp.int32)[None, :], input_ids.shape
    )


def _llama_like_spec(cfg, block_cls, norm_cls):
    """Llama-family decoder (also Mistral/Qwen/Gemma via config, and Mixtral
    with its MoE block): embed [+Gemma scale] -> blocks(x, pos) -> RMSNorm ->
    tied or Dense head."""
    import flax.linen as nn

    from .models.llama import require_single_pass

    require_single_pass(cfg, "layer streaming")
    embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32)
    block = block_cls(cfg)
    norm = norm_cls()
    tied = cfg.tie_word_embeddings

    def embed_fn(params, input_ids):
        x = embed.apply({"params": params[0]}, input_ids)
        if getattr(cfg, "scale_embeddings", False):
            x = x * jnp.asarray(np.sqrt(cfg.hidden_size), cfg.dtype)
        return x, _positions_like(input_ids)

    def block_fn(p, x, pos):
        return block.apply({"params": p}, x, pos), pos

    if tied:
        def head_fn(params, x, pos):
            x = norm.apply({"params": params[0]}, x)
            return x @ params[1]["embedding"].T.astype(cfg.dtype)

        head = Seg("head", ["model/norm", "model/embed_tokens"], head_fn)
    else:
        def head_fn(params, x, pos):
            x = norm.apply({"params": params[0]}, x)
            return x @ params[1]["kernel"].astype(cfg.dtype)

        head = Seg("head", ["model/norm", "lm_head"], head_fn)

    return [
        Seg("embed", ["model/embed_tokens"], embed_fn, keep=("model/embed_tokens",) if tied else ()),
        LayerSeg("block", "model/layers/block", "model/layers_{i}",
                 cfg.num_hidden_layers, block_fn),
        head,
    ]


def _llama_spec(cfg):
    from .models.llama import LlamaBlock, RMSNorm

    return _llama_like_spec(
        cfg, LlamaBlock,
        lambda: RMSNorm(cfg.rms_norm_eps, getattr(cfg, "rms_norm_plus_one", False)),
    )


def _mixtral_spec(cfg):
    from .models.llama import RMSNorm
    from .models.moe import MixtralBlock

    return _llama_like_spec(cfg, MixtralBlock, lambda: RMSNorm(cfg.rms_norm_eps))


def _opt_spec(cfg):
    """OPT — the reference's OPT-30B big-model-inference workload
    (benchmarks/big_model_inference/README.md) with ≤2 blocks in HBM."""
    import flax.linen as nn

    from .models.opt import OPTBlock

    embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32)
    pos_embed = nn.Embed(
        cfg.max_position_embeddings + cfg.POSITION_OFFSET, cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=jnp.float32,
    )
    block = OPTBlock(cfg)
    ln = nn.LayerNorm(epsilon=cfg.layer_norm_eps)

    def embed_fn(params, input_ids):
        pos = jnp.arange(input_ids.shape[-1]) + cfg.POSITION_OFFSET
        return embed.apply({"params": params[0]}, input_ids) + pos_embed.apply(
            {"params": params[1]}, pos
        )

    def head_fn(params, x):
        x = ln.apply({"params": params[0]}, x)
        return (x @ params[1]["embedding"].T.astype(cfg.dtype)).astype(jnp.float32)

    return [
        Seg("embed", ["model/embed_tokens", "model/embed_positions"], embed_fn,
            keep=("model/embed_tokens",)),
        LayerSeg("block", "model/layers/block", "model/layer_{i}",
                 cfg.num_hidden_layers, lambda p, x: block.apply({"params": p}, x)),
        Seg("head", ["model/final_layer_norm", "model/embed_tokens"], head_fn),
    ]


def _neox_spec(cfg):
    """GPT-NeoX — the reference's flagship 20B offload benchmark family."""
    import flax.linen as nn

    from .models.neox import GPTNeoXBlock

    embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32)
    block = GPTNeoXBlock(cfg)
    ln = nn.LayerNorm(epsilon=cfg.layer_norm_eps)
    head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32)

    def embed_fn(params, input_ids):
        return embed.apply({"params": params[0]}, input_ids), _positions_like(input_ids)

    def head_fn(params, x, pos):
        x = ln.apply({"params": params[0]}, x)
        return head.apply({"params": params[1]}, x).astype(jnp.float32)

    return [
        Seg("embed", ["gpt_neox/embed_in"], embed_fn),
        LayerSeg("block", "gpt_neox/layers/block", "gpt_neox/layer_{i}",
                 cfg.num_hidden_layers,
                 lambda p, x, pos: (block.apply({"params": p}, x, pos), pos)),
        Seg("head", ["gpt_neox/final_layer_norm", "embed_out"], head_fn),
    ]


def _gpt2_spec(cfg):
    import flax.linen as nn

    from .models.gpt2 import GPT2Block

    wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, param_dtype=jnp.float32)
    wpe = nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype, param_dtype=jnp.float32)
    block = GPT2Block(cfg)
    ln = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon)

    def embed_fn(params, input_ids):
        return wte.apply({"params": params[0]}, input_ids) + wpe.apply(
            {"params": params[1]}, jnp.arange(input_ids.shape[-1])
        )

    def head_fn(params, x):
        x = ln.apply({"params": params[0]}, x)
        return (x @ params[1]["embedding"].T.astype(cfg.dtype)).astype(jnp.float32)

    return [
        Seg("embed", ["transformer/wte", "transformer/wpe"], embed_fn,
            keep=("transformer/wte",)),
        LayerSeg("block", "transformer/h/block", "transformer/h_{i}", cfg.n_layer,
                 lambda p, x: block.apply({"params": p}, x)),
        Seg("head", ["transformer/ln_f", "transformer/wte"], head_fn),
    ]


def _t5_spec(cfg):
    """T5 encoder-decoder — the reference's T0pp-11B benchmark family. Both
    stacks stream; block_0 (owner of the shared relative-position bias) is its
    own segment, the remaining bias-reusing layers are the streamed stack."""
    import flax.linen as nn

    from .models.t5 import T5DecoderBlock, T5EncoderBlock, T5LayerNorm

    shared = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, param_dtype=jnp.float32)
    enc_b0 = T5EncoderBlock(cfg, has_relative_bias=True)
    enc_blk = T5EncoderBlock(cfg)
    dec_b0 = T5DecoderBlock(cfg, has_relative_bias=True)
    dec_blk = T5DecoderBlock(cfg)
    final_ln = T5LayerNorm(cfg.layer_norm_epsilon)

    def enc_embed_fn(params, input_ids, decoder_input_ids):
        mask = (input_ids != cfg.pad_token_id).astype(jnp.int32)
        return shared.apply({"params": params[0]}, input_ids), mask, decoder_input_ids

    def enc_b0_fn(p, x, mask, dec_ids):
        x, bias = enc_b0.apply({"params": p[0]}, x, mask, None)
        return x, bias, mask, dec_ids

    def enc_blk_fn(p, x, bias, mask, dec_ids):
        x, _ = enc_blk.apply({"params": p}, x, mask, bias)
        return x, bias, mask, dec_ids

    def enc_final_fn(p, x, bias, mask, dec_ids):
        return final_ln.apply({"params": p[0]}, x), mask, dec_ids

    def dec_embed_fn(params, enc, mask, dec_ids):
        return shared.apply({"params": params[0]}, dec_ids), enc, mask

    def dec_b0_fn(p, y, enc, mask):
        y, bias = dec_b0.apply({"params": p[0]}, y, enc, None, mask)
        return y, bias, enc, mask

    def dec_blk_fn(p, y, bias, enc, mask):
        y, _ = dec_blk.apply({"params": p}, y, enc, bias, mask)
        return y, bias, enc, mask

    def head_fn(params, y, bias, enc, mask):
        y = final_ln.apply({"params": params[0]}, y)
        return (y * (cfg.d_model ** -0.5)) @ params[1]["embedding"].T.astype(cfg.dtype)

    return [
        Seg("enc_embed", ["shared"], enc_embed_fn, keep=("shared",)),
        Seg("enc_b0", ["encoder/block_0"], enc_b0_fn),
        LayerSeg("enc_blk", "encoder/layers/block", "encoder/block_{i}",
                 cfg.num_layers - 1, enc_blk_fn, offset=1),
        Seg("enc_final", ["encoder/final_ln"], enc_final_fn),
        Seg("dec_embed", ["shared"], dec_embed_fn, keep=("shared",)),
        Seg("dec_b0", ["decoder/block_0"], dec_b0_fn),
        LayerSeg("dec_blk", "decoder/layers/block", "decoder/block_{i}",
                 cfg.n_dec - 1, dec_blk_fn, offset=1),
        Seg("head", ["decoder/final_ln", "shared"], head_fn),
    ]


def _whisper_spec(cfg):
    import flax.linen as nn
    from functools import partial

    from .models.whisper import WhisperDecoderBlock, WhisperEncoderBlock

    conv = partial(nn.Conv, features=cfg.d_model, kernel_size=(3,), padding=1,
                   dtype=cfg.dtype, param_dtype=jnp.float32)
    conv1, conv2 = conv(), conv(strides=(2,))
    enc_blk = WhisperEncoderBlock(cfg)
    dec_blk = WhisperDecoderBlock(cfg)
    ln = nn.LayerNorm(epsilon=cfg.layer_norm_eps)
    embed_tok = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, param_dtype=jnp.float32)
    embed_pos = nn.Embed(cfg.max_target_positions, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=jnp.float32)

    def enc_stem_fn(params, feats, dec_ids):
        x = nn.gelu(conv1.apply({"params": params[0]}, feats.astype(cfg.dtype)),
                    approximate=False)
        x = nn.gelu(conv2.apply({"params": params[1]}, x), approximate=False)
        x = x + params[2][None, : x.shape[1]].astype(x.dtype)
        return x, dec_ids

    def enc_ln_fn(p, x, dec_ids):
        return ln.apply({"params": p[0]}, x), dec_ids

    def dec_embed_fn(params, enc, dec_ids):
        y = embed_tok.apply({"params": params[0]}, dec_ids)
        y = y + embed_pos.apply({"params": params[1]}, jnp.arange(dec_ids.shape[-1]))
        return y, enc

    def head_fn(params, y, enc):
        y = ln.apply({"params": params[0]}, y)
        return (y @ params[1]["embedding"].T.astype(cfg.dtype)).astype(jnp.float32)

    return [
        Seg("enc_stem", ["encoder/conv1", "encoder/conv2", "encoder/embed_positions"],
            enc_stem_fn),
        LayerSeg("enc_blk", "encoder/layers/block", "encoder/layer_{i}",
                 cfg.encoder_layers,
                 lambda p, x, dec_ids: (enc_blk.apply({"params": p}, x), dec_ids)),
        Seg("enc_ln", ["encoder/layer_norm"], enc_ln_fn),
        Seg("dec_embed", ["decoder/embed_tokens", "decoder/embed_positions"],
            dec_embed_fn, keep=("decoder/embed_tokens",)),
        LayerSeg("dec_blk", "decoder/layers/block", "decoder/layer_{i}",
                 cfg.decoder_layers,
                 lambda p, y, enc: (dec_blk.apply({"params": p}, y, enc), enc)),
        Seg("head", ["decoder/layer_norm", "decoder/embed_tokens"], head_fn),
    ]


register_stream_spec("LlamaForCausalLM", _llama_spec)
register_stream_spec("MixtralForCausalLM", _mixtral_spec)
register_stream_spec("OPTForCausalLM", _opt_spec)
register_stream_spec("GPTNeoXForCausalLM", _neox_spec)
register_stream_spec("GPT2LMHeadModel", _gpt2_spec)
register_stream_spec("T5ForConditionalGeneration", _t5_spec)
register_stream_spec("WhisperForConditionalGeneration", _whisper_spec)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


class DispatchedModel(Model):
    """A :class:`Model` whose params live across HBM / host / disk.

    Forward picks the streamed plan when one is registered for the module
    class; otherwise it materializes everything on the execution device for
    the duration of the call (reference ``cpu_offload`` semantics).
    """

    def __init__(
        self,
        module,
        placed_params,
        device_map,
        execution_device,
        sep: str = "/",
        apply_fn=None,
        extra_state=None,
    ):
        super().__init__(
            module=module, apply_fn=apply_fn, params=placed_params, extra_state=extra_state
        )
        self.device_map = dict(device_map)
        self.execution_device = execution_device
        self._sep = sep

    def __call__(self, *args, **kwargs):
        resolver = ParamResolver(self._params, self.execution_device, sep=self._sep)
        cls_name = type(self.module).__name__ if self.module is not None else None
        # Sown-output collections ("losses": MoE aux, "intermediates") are
        # produced BY the forward, never consumed — they don't block streaming.
        consumed_state = {
            k: v for k, v in (self.extra_state or {}).items()
            if k not in ("losses", "intermediates")
        }
        reason = None
        if cls_name is None:
            reason = "no flax module (apply_fn-only model)"
        elif consumed_state:
            reason = f"extra_state collections {sorted(consumed_state)} must feed the forward"
        if reason is None:
            spec_builder = _STREAM_SPECS.get(cls_name)
            # Specs cover the module's canonical positional signature only; a
            # call with kwargs or extra optional args (e.g. an explicit T5
            # attention_mask) falls back to the full apply for correctness.
            if spec_builder is not None and not kwargs:
                segments = spec_builder(self.module.config)
                if _spec_arity(segments) == len(args):
                    out = _run_stream_spec(self.module, resolver, segments, *args)
                    self.last_stream_peak_bytes = resolver.peak_cached_bytes
                    return out
                reason = (
                    f"call arity {len(args)} != spec arity {_spec_arity(segments)} "
                    "(optional args need the full signature)"
                )
            elif spec_builder is not None:
                reason = "keyword arguments need the full apply signature"
            plan = _STREAM_PLANS.get(cls_name)
            if plan is not None:
                out = plan(self.module, resolver, *args, **kwargs)
                self.last_stream_peak_bytes = resolver.peak_cached_bytes
                return out
            reason = reason or "no stream plan registered"
        # Fallback: the FULL param tree transiently lands on the execution
        # device — exactly when offload matters most, so say so.
        _warn_materialize_fallback(cls_name, self._params, reason)
        full = resolver._materialize(self._params)
        variables = {"params": full}
        if self.extra_state:
            variables.update(self.extra_state)
        try:
            return self.apply_fn(variables, *args, **kwargs)
        finally:
            del full  # evict the transient on-device copy

    def hbm_resident_bytes(self) -> int:
        """Bytes of params permanently resident on device (diagnostics)."""
        total = 0
        for leaf in jax.tree.leaves(self._params):
            if isinstance(leaf, jax.Array):
                total += leaf.nbytes
        return total


def dispatch_model(
    model: Model,
    device_map: Mapping[str, Any],
    offload_dir: Optional[str] = None,
    execution_device=None,
    sep: str = "/",
) -> DispatchedModel:
    """Scatter an in-memory model's params per ``device_map``
    (reference: big_modeling.py:315-521)."""
    flat = flatten_state_dict(model.params, sep=sep)
    device_map = normalize_device_map(device_map)
    placed: dict[str, Any] = {}
    disk_entries: dict[str, np.ndarray] = {}
    for name, arr in flat.items():
        p = placement_for(name, device_map, sep=sep)
        if p == "cpu":
            placed[name] = np.asarray(arr)
        elif p == "disk":
            disk_entries[name] = np.asarray(arr)
        else:
            placed[name] = jax.device_put(arr, p)
    if disk_entries:
        if offload_dir is None:
            raise ValueError("device_map contains 'disk' entries but no offload_dir given")
        offload_state_dict(offload_dir, disk_entries)
        for name, arr in disk_entries.items():
            placed[name] = _DiskHandle(name, offload_dir, arr.shape, arr.dtype)
    if execution_device is None:
        execution_device = default_execution_device(device_map)
    return DispatchedModel(
        model.module,
        unflatten_state_dict(placed, sep=sep),
        device_map,
        execution_device,
        sep=sep,
        apply_fn=None if model.module is not None else model.apply_fn,
        extra_state=model.extra_state,
    )


def cpu_offload(model: Model, execution_device=None) -> DispatchedModel:
    """All params to host RAM; faulted to the chip per forward
    (reference: big_modeling.py:179-231)."""
    top = {k: "cpu" for k in model.params}
    return dispatch_model(model, top, execution_device=execution_device)


def disk_offload(model: Model, offload_dir: str, execution_device=None) -> DispatchedModel:
    """All params to a disk memmap store (reference: big_modeling.py:233-276)."""
    top = {k: "disk" for k in model.params}
    return dispatch_model(model, top, offload_dir=offload_dir, execution_device=execution_device)


class UserCpuOffloadHook:
    """Handle returned by :func:`cpu_offload_with_hook` — ``offload()`` pushes
    the model's params back to host RAM (reference: hooks.py UserCpuOffloadHook
    via big_modeling.py:278-314)."""

    def __init__(self, model: "HookedOffloadModel"):
        self.model = model

    def offload(self):
        self.model._to_host()

    def remove(self):
        self.model._hooked = False


class HookedOffloadModel(Model):
    """Params live on host; the first forward moves them to the chip and they
    STAY resident until ``hook.offload()`` — the pipeline-friendly variant of
    :func:`cpu_offload` (each forward of that one re-faults every group)."""

    def __init__(self, inner: Model, execution_device, prev_hook):
        super().__init__(
            apply_fn=inner.apply_fn, params=inner._params,
            extra_state=inner.extra_state, module=inner.module,
            tp_rules=inner.tp_rules,
        )
        self._exec_device = execution_device
        self._prev_hook = prev_hook
        self._on_device = False
        self._hooked = True
        self._to_host()

    def _host_device(self):
        return jax.local_devices(backend="cpu")[0]

    def _to_host(self):
        self._params = jax.device_put(self._params, self._host_device())
        self._on_device = False

    def __call__(self, *args, **kwargs):
        if self._hooked:
            if self._prev_hook is not None:
                # Chaining: evict the previous pipeline stage before loading
                # this one (the reference's prev_module_hook contract).
                self._prev_hook.offload()
            if not self._on_device:
                self._params = jax.device_put(self._params, self._exec_device)
                self._on_device = True
        return super().__call__(*args, **kwargs)


def cpu_offload_with_hook(
    model: Model, execution_device=None, prev_module_hook: Optional[UserCpuOffloadHook] = None
) -> tuple[Model, UserCpuOffloadHook]:
    """Offload to host, but keep params chip-resident between forwards until
    the returned hook's ``offload()`` runs (reference: big_modeling.py:278-314
    — the diffusers-style pipeline pattern where model_i's load evicts
    model_{i-1} via ``prev_module_hook``)."""
    if execution_device is None:
        execution_device = jax.devices()[0]
    hooked = HookedOffloadModel(model, execution_device, prev_module_hook)
    hook = UserCpuOffloadHook(hooked)
    return hooked, hook


def load_checkpoint_and_dispatch(
    module,
    checkpoint: str,
    *sample_args,
    device_map: Any = "auto",
    max_memory: Optional[dict] = None,
    no_split_modules: Optional[list[str]] = None,
    offload_folder: Optional[str] = None,
    dtype=None,
    rng=None,
    sep: str = "/",
    **sample_kwargs,
) -> DispatchedModel:
    """Meta-init + auto device map + shard streaming, in one call
    (reference: big_modeling.py:522-662).

    The full model never exists in one memory: shards stream from disk
    straight into their mapped placement.
    """
    abstract = compute_abstract_params(module, *sample_args, rng=rng, **sample_kwargs)
    if device_map in ("auto", "balanced", "balanced_low_0"):
        mm = (
            get_balanced_memory(
                abstract, max_memory, no_split_modules, dtype=dtype,
                low_zero=(device_map == "balanced_low_0"),
            )
            if device_map in ("balanced", "balanced_low_0")
            else get_max_memory(max_memory)
        )
        device_map = infer_auto_device_map(
            abstract, mm, no_split_modules=no_split_modules, dtype=dtype, sep=sep
        )
    elif device_map is None:
        device_map = {"": jax.local_devices()[0]}
    else:
        device_map = normalize_device_map(device_map)
    check_device_map(abstract, device_map, sep=sep)
    placed, _ = load_checkpoint_in_model(
        abstract, checkpoint, device_map=device_map, offload_folder=offload_folder,
        dtype=dtype, sep=sep,
    )
    execution_device = default_execution_device(device_map)
    return DispatchedModel(module, placed, device_map, execution_device, sep=sep)
