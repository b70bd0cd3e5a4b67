"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` mesh axis.

The reference reaches pipeline parallelism two ways: inference-only via
``torch.distributed.pipelining`` (reference: inference.py:75-187 —
``build_pipeline`` + ``ScheduleGPipe``) and training via the Megatron-LM
engine (reference: utils/megatron_lm.py:926, ``get_forward_backward_func``).
Both are imperative runtimes that move activations with NCCL P2P sends.

The TPU-native design is a *compiled* pipeline: one ``jax.shard_map`` manual
over the leading ``pp`` mesh axis (every other axis stays under GSPMD auto
control, so FSDP/TP/DP sharding of the non-pipeline dims composes untouched),
with the classic GPipe loop expressed as ``lax.scan`` over
``n_microbatches + n_stages - 1`` ticks and activations passed stage→stage+1
by ``lax.ppermute`` over ICI. Because ``scan``/``ppermute``/``where`` all have
transpose rules, the SAME schedule is the backward pass — ``jax.grad``
through ``pipeline_apply`` is 1F1B-shaped for free, no hand-written schedule
runtime.

Stage weights: a stack of L identical layers lives in one pytree whose leaves
have leading dim L (the ``nn.scan`` layout); sharding that dim over ``pp``
gives each stage its contiguous L/pp layers *locally* — ``shard_map`` with
``in_specs=P("pp")`` hands each stage exactly its slice, no reshapes, no
parameter movement.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# Eager-call compile cache: (stage_fn, mesh, schedule, arg structure) → jitted
# pipeline. Inside a jit trace the shard_map inlines and this is bypassed.
_EAGER_CACHE: dict = {}


def _resolve_virtual_stages(virtual_stages: Optional[int]) -> int:
    """Explicit arg > live ParallelismConfig.pp_virtual_stages > env > 1.

    The AcceleratorState peek is PASSIVE (reads the borg dict): constructing
    the singleton here would initialize the whole runtime as a side effect of
    a mesh-only pipeline_apply call — and poison a later
    Accelerator(parallelism_config=...) with 'already initialized'."""
    if virtual_stages is not None:
        v = int(virtual_stages)
        if v < 1:
            raise ValueError(f"virtual_stages must be a positive int, got {virtual_stages}")
        return v
    from ..state import AcceleratorState
    from ..utils.constants import PARALLELISM_CONFIG_PREFIX
    from ..utils.environment import get_int_from_env

    pc = AcceleratorState._shared_state.get("parallelism_config")
    if pc is not None:
        return int(getattr(pc, "pp_virtual_stages", 1) or 1)
    v = get_int_from_env([f"{PARALLELISM_CONFIG_PREFIX}PP_VIRTUAL_STAGES"], 1)
    if v < 1:
        raise ValueError(
            f"PARALLELISM_CONFIG_PP_VIRTUAL_STAGES must be a positive int, got {v}"
        )
    return v


def _active_mesh(mesh: Optional[Mesh]) -> Mesh:
    if mesh is not None:
        return mesh
    from ..state import AcceleratorState, is_initialized

    if is_initialized():
        st = AcceleratorState()
        if getattr(st, "mesh", None) is not None:
            return st.mesh
    raise ValueError("pipeline_apply needs a mesh (pass mesh= or build an Accelerator).")


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    n_microbatches: Optional[int] = None,
    axis_name: str = "pp",
    virtual_stages: Optional[int] = None,
) -> jax.Array:
    """Run ``x`` through a layer stack pipelined over the ``pp`` mesh axis.

    Args:
      stage_fn: ``(local_layer_stack, h) -> h`` — applies ONE stage's worth of
        layers to a microbatch of hidden states. Inside, leaves of
        ``local_layer_stack`` have leading dim ``L // pp`` (``L // (pp*V)``
        under interleaving). Must preserve the shape/dtype of ``h``.
      stage_params: pytree of stacked layer weights; every leaf has leading
        dim L (divisible by the ``pp`` axis size).
      x: ``(B, ...)`` hidden states; ``B`` is split into microbatches.
      n_microbatches: defaults to the ``pp`` degree (the minimum that keeps
        every stage busy outside the fill/drain bubble).
      virtual_stages: Megatron-style interleaving degree V. Each device holds
        V *non-contiguous* layer chunks (device d owns global chunks
        ``v*pp + d``) and microbatches circulate the ring V times, so the
        fill/drain bubble shrinks to ``(pp-1)/(V*m)`` of the work — the
        interleaved schedule's whole point. V>1 requires
        ``n_microbatches == pp`` per call (run several calls for larger
        batches; gradient accumulation sums them anyway). Defaults to
        ``ParallelismConfig.pp_virtual_stages`` when an Accelerator is live.

    Returns ``(B, ...)`` outputs, replicated over ``pp`` like the input.
    """
    mesh = _active_mesh(mesh)
    n_stages = mesh.shape.get(axis_name, 1)
    if n_stages == 1:
        return stage_fn(stage_params, x)
    v_stages = _resolve_virtual_stages(virtual_stages)
    if v_stages > 1:
        return _pipeline_apply_interleaved(
            stage_fn, stage_params, x, mesh=mesh,
            n_microbatches=n_microbatches, axis_name=axis_name,
            v_stages=v_stages,
        )

    n_micro = int(n_microbatches or n_stages)
    batch = x.shape[0]
    if batch % n_micro != 0:
        raise ValueError(f"batch dim {batch} not divisible by n_microbatches {n_micro}")
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[0] % n_stages != 0:
            raise ValueError(
                f"layer-stack leading dim {leaf.shape[0]} not divisible by pp={n_stages}"
            )
    mb = batch // n_micro
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    compute_dtype = x.dtype

    def body(local_params, x_full):
        stage = jax.lax.axis_index(axis_name)
        x_full = x_full.astype(compute_dtype)
        mbs = x_full.reshape(n_micro, mb, *x_full.shape[1:])
        ticks = n_micro + n_stages - 1

        def loop(carry, t):
            state, out_buf = carry
            # Stage 0 pulls microbatch t (clamped during drain); later stages
            # consume what the previous stage sent last tick.
            mb_t = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            inp = jnp.where(stage == 0, mb_t, state)
            out = stage_fn(local_params, inp)
            # The last stage finishes microbatch (t - n_stages + 1) at tick t.
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(out_buf, out_idx, 0, keepdims=False)
            keep = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(keep, out, prev), out_idx, 0
            )
            nxt = jax.lax.ppermute(out, axis_name, fwd)
            return (nxt, out_buf), None

        init = (jnp.zeros_like(mbs[0]), jnp.zeros_like(mbs))
        (_, out_buf), _ = jax.lax.scan(loop, init, jnp.arange(ticks))
        return out_buf

    # Each stage emits its (n_micro, mb, ...) buffer; stacking them over the
    # ``pp`` out-spec keeps the real outputs resident on the last stage with
    # NO collective at pipe exit — the slice below just addresses that block
    # and GSPMD moves it lazily wherever the consumer needs it.
    pipelined = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stage_params), P()),
        out_specs=P(axis_name),
        axis_names={axis_name},
        check_vma=False,
    )
    # The replicated-input spec P() makes autodiff insert a psum over ``pp``
    # for the input cotangent; a bf16 psum inside partial-manual shard_map
    # trips an XLA CPU-backend assertion, so the activation crosses the
    # boundary in f32 (cast back to the compute dtype on entry — the
    # converts fuse, and the bwd psum carries mostly zeros anyway since only
    # stage 0 reads the input).
    x_in = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    # Partial-manual shard_map only lowers under jit, and a fresh jax.jit per
    # call would retrace on every eager call — cache by schedule + argument
    # structure. Under an outer jit/grad trace the cached wrapper inlines.
    key = (
        stage_fn,
        mesh,
        axis_name,
        n_micro,
        jax.tree.structure(stage_params),
        tuple((l.shape, jnp.result_type(l)) for l in jax.tree.leaves(stage_params)),
        x_in.shape,
        jnp.result_type(x_in),
        jnp.result_type(x),  # compute dtype captured by the closure
    )
    jitted = _EAGER_CACHE.get(key)
    if jitted is None:
        jitted = _EAGER_CACHE[key] = jax.jit(pipelined)
    stacked = jitted(stage_params, x_in)
    last = stacked[(n_stages - 1) * n_micro :]
    return last.reshape(batch, *x.shape[1:])


def _pipeline_apply_interleaved(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    n_microbatches: Optional[int],
    axis_name: str,
    v_stages: int,
) -> jax.Array:
    """Megatron-style interleaved schedule on the same synchronous ring.

    Device d owns the V non-contiguous global chunks ``{v*pp + d}``;
    microbatches circulate the ring V times. With m == pp microbatches the
    stream is conflict-free by construction: at tick t device d processes
    microbatch ``(t-d) mod pp`` at round ``(t-d) // pp`` — round-0 slots on
    device 0 are exactly the injection ticks, and no device ever has two
    ready inputs. Total ticks = V*pp + pp - 1 for V*pp units of work per
    device, so the bubble is (pp-1)/(V*pp): 1/V of GPipe's at the same m.
    Like the GPipe body, the whole schedule is one scan — ``jax.grad``
    differentiates through it, and the backward inherits the same shrunken
    bubble.
    """
    import numpy as _np

    n_stages = mesh.shape[axis_name]
    V = v_stages
    n_micro = int(n_microbatches or n_stages)
    if n_micro != n_stages:
        raise ValueError(
            f"virtual_stages>1 requires n_microbatches == pp (got m={n_micro}, "
            f"pp={n_stages}); accumulate over multiple calls for bigger batches"
        )
    batch = x.shape[0]
    if batch % n_micro != 0:
        raise ValueError(f"batch dim {batch} not divisible by n_microbatches {n_micro}")
    n_layers = jax.tree.leaves(stage_params)[0].shape[0]
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[0] != n_layers:
            raise ValueError(
                f"stage_params leaves disagree on layer count "
                f"({leaf.shape[0]} vs {n_layers}); jnp.take would silently "
                "clamp the shorter leaf into wrong weights"
            )
    if n_layers % (n_stages * V) != 0:
        raise ValueError(
            f"layer count {n_layers} not divisible by pp*virtual_stages="
            f"{n_stages}*{V}"
        )
    lc = n_layers // (n_stages * V)
    mb = batch // n_micro
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    compute_dtype = x.dtype

    # Re-arrange layers device-major: position (d, v, l) <- global layer
    # (v*pp + d)*lc + l, so the contiguous P("pp") shard of device d is its V
    # chunks stacked in round order. jnp.take's transpose scatters gradients
    # straight back to the caller's layout.
    perm = _np.asarray(
        [
            (v * n_stages + d) * lc + l
            for d in range(n_stages)
            for v in range(V)
            for l in range(lc)
        ],
        dtype=_np.int32,
    )

    def body(local_params, x_full):
        stage = jax.lax.axis_index(axis_name)
        x_full = x_full.astype(compute_dtype)
        mbs = x_full.reshape(n_micro, mb, *x_full.shape[1:])
        chunks = jax.tree.map(
            lambda leaf: leaf.reshape(V, lc, *leaf.shape[1:]), local_params
        )
        ticks = V * n_stages + n_stages - 1

        def loop(carry, t):
            state, out_buf = carry
            rel = t - stage
            v = jnp.clip(rel // n_stages, 0, V - 1)
            b_idx = jnp.clip(rel, 0, V * n_stages - 1) % n_stages
            chunk = jax.tree.map(
                lambda leaf: jax.lax.dynamic_index_in_dim(leaf, v, 0, keepdims=False),
                chunks,
            )
            mb_t = jax.lax.dynamic_index_in_dim(mbs, b_idx, 0, keepdims=False)
            # Device 0 injects fresh microbatches during its round-0 ticks;
            # everything else consumes the ring.
            inject = jnp.logical_and(stage == 0, rel < n_stages)
            inp = jnp.where(inject, mb_t, state)
            out = stage_fn(chunk, inp)
            # The last device completes microbatch b_idx on its final round.
            keep = jnp.logical_and(
                stage == n_stages - 1,
                jnp.logical_and(rel >= (V - 1) * n_stages, rel < V * n_stages),
            )
            prev = jax.lax.dynamic_index_in_dim(out_buf, b_idx, 0, keepdims=False)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(keep, out, prev), b_idx, 0
            )
            nxt = jax.lax.ppermute(out, axis_name, fwd)
            return (nxt, out_buf), None

        init = (jnp.zeros_like(mbs[0]), jnp.zeros_like(mbs))
        (_, out_buf), _ = jax.lax.scan(loop, init, jnp.arange(ticks))
        return out_buf

    pipelined = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stage_params), P()),
        out_specs=P(axis_name),
        axis_names={axis_name},
        check_vma=False,
    )

    def run(params, x_in):
        # Permute INSIDE the jit so XLA fuses the gather with the resharding
        # (an eager take would materialize a second copy of the whole stack
        # per call) and jnp.take's transpose scatters grads back to the
        # caller's layout.
        params_dm = jax.tree.map(lambda leaf: jnp.take(leaf, perm, axis=0), params)
        return pipelined(params_dm, x_in)

    # f32 at the replicated-input boundary: same bf16-psum workaround as the
    # GPipe path above (see the comment there).
    x_in = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    key = (
        stage_fn, mesh, axis_name, n_micro, V,
        jax.tree.structure(stage_params),
        tuple((l.shape, jnp.result_type(l)) for l in jax.tree.leaves(stage_params)),
        x_in.shape, jnp.result_type(x_in), jnp.result_type(x),
    )
    jitted = _EAGER_CACHE.get(key)
    if jitted is None:
        jitted = _EAGER_CACHE[key] = jax.jit(run)
    stacked = jitted(stage_params, x_in)
    last = stacked[(n_stages - 1) * n_micro :]
    return last.reshape(batch, *x.shape[1:])


# ---------------------------------------------------------------------------
# Flagship-model convenience: pipelined Llama forward. The embedding / final
# norm / LM head run outside the pipeline (they are not sharded over ``pp``,
# and their compute is negligible next to the block stack), matching the
# reference's first/last-stage carve-out (inference.py:101-127 feeds rank 0,
# collects on the last rank).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _llama_stage_fn(config) -> Callable:
    """Stable (per-config) stage function so eager pipeline calls hit the
    compile cache; honors ``config.remat`` per layer like the unpipelined
    ``LlamaModel`` path."""
    from ..models.llama import LlamaBlock, require_single_pass

    require_single_pass(config, "pipeline parallelism")
    block = LlamaBlock(config)

    def one_layer(carry, layer_params):
        h, positions = carry
        h = block.apply({"params": layer_params}, h, positions)
        return (h, positions), None

    if config.remat:
        one_layer = jax.checkpoint(one_layer, prevent_cse=False)

    def stage_fn(local_layers, h):
        positions = jnp.broadcast_to(
            jnp.arange(h.shape[1], dtype=jnp.int32)[None, :], h.shape[:2]
        )
        (h, _), _ = jax.lax.scan(one_layer, (h, positions), local_layers)
        return h

    return stage_fn


def llama_pipeline_forward(
    config,
    params: Any,
    input_ids: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    n_microbatches: Optional[int] = None,
    virtual_stages: Optional[int] = None,
) -> jax.Array:
    """Pipelined equivalent of ``LlamaForCausalLM.apply`` (logits).

    Requires ``config.scan_layers=True`` — the stacked block weights ARE the
    pipeline stages.
    """
    from ..models.llama import rms_norm

    if not config.scan_layers:
        raise ValueError("pipeline parallelism requires scan_layers=True (stacked blocks)")
    model_p = params["model"] if "model" in params else params
    stacked = model_p["layers"]["block"]

    embed = model_p["embed_tokens"]["embedding"]
    x = jnp.take(embed, input_ids, axis=0).astype(config.dtype)

    x = pipeline_apply(
        _llama_stage_fn(config), stacked, x,
        mesh=mesh, n_microbatches=n_microbatches, axis_name="pp",
        virtual_stages=virtual_stages,
    )

    x = rms_norm(x, model_p["norm"]["weight"].astype(x.dtype), config.rms_norm_eps)
    if config.tie_word_embeddings:
        return x @ embed.T.astype(config.dtype)
    return x @ params["lm_head"]["kernel"].astype(config.dtype)
