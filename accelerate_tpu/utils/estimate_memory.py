"""Topology-aware per-chip memory estimation.

The reference's ``estimate-memory`` reports whole-model sizes per dtype
(reference: commands/estimate.py:66-318). The number a TPU user actually
needs is *per chip under a given ParallelismConfig*: will the 7B + Adam
working set fit 16 GB of v5e HBM at dp_shard=64? This module answers that
with the SAME sharding planner the trainer uses (parallel/sharding.py), so
the estimate and the training run can't drift apart:

- params / grads / optimizer moments: exact sharded bytes per chip, leaf by
  leaf, from :func:`plan_parameter_sharding` + :func:`infer_opt_state_sharding`
  over an :class:`~jax.sharding.AbstractMesh` (no devices needed — estimate a
  v5e-64 plan from a laptop).
- activations: a documented closed-form model of what the remat policy saves
  per scanned layer plus the recompute peak (approximate by nature; the
  tensor-state categories above are exact and dominate FSDP fit questions).

Used by ``accelerate-tpu estimate --parallelism ...`` and by the
``dryrun_7b_lowering`` scenario in ``__graft_entry__.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import AbstractMesh, NamedSharding

GiB = 1024 ** 3


def build_abstract_mesh(parallelism_config) -> AbstractMesh:
    """AbstractMesh with the trainer's canonical axis order (so the planner
    produces identical specs to ParallelismConfig.build_mesh's real mesh)."""
    from ..parallelism_config import MESH_AXIS_ORDER

    cfg = parallelism_config
    names = ("pp",) + MESH_AXIS_ORDER
    shape = (cfg.pp_size,) + tuple(cfg.axis_size(ax) for ax in MESH_AXIS_ORDER)
    return AbstractMesh(shape, names)


def _shard_factor(sharding: NamedSharding, mesh) -> int:
    n = 1
    for entry in sharding.spec:
        if entry is None:
            continue
        for ax in entry if isinstance(entry, tuple) else (entry,):
            n *= mesh.shape[ax]
    return n


def _tree_bytes_per_chip(shapes: Any, shardings: Any, mesh, dtype=None) -> int:
    """Exact per-chip bytes of a sharded tree (shapes: ShapeDtypeStructs)."""
    total = 0
    for leaf, sh in zip(
        jax.tree_util.tree_leaves(shapes),
        jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
        ),
    ):
        if not hasattr(leaf, "shape"):
            continue
        nbytes = math.prod(leaf.shape) * np.dtype(dtype or leaf.dtype).itemsize
        total += nbytes // _shard_factor(sh, mesh)
    return total


def replicated_large_leaves(shapes: Any, shardings: Any, mesh,
                            min_bytes: int = 2 ** 20) -> list[str]:
    """Leaves ≥ min_bytes whose sharding is fully replicated — the
    'involuntary replication' check for FSDP plans."""
    from ..parallel.sharding import _path_to_name

    bad = []

    def visit(path, leaf):
        sh = _sh_at(shardings, path)
        if (
            hasattr(leaf, "shape")
            and math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize >= min_bytes
            and _shard_factor(sh, mesh) == 1
        ):
            bad.append(_path_to_name(path))
        return leaf

    def _sh_at(tree, path):
        node = tree
        for p in path:
            key = getattr(p, "key", getattr(p, "idx", None))
            node = node[key]
        return node

    jax.tree_util.tree_map_with_path(visit, shapes)
    return bad


@dataclasses.dataclass
class MemoryEstimate:
    params_gib: float
    grads_gib: float
    opt_state_gib: float
    activations_gib: float
    logits_gib: float

    @property
    def total_gib(self) -> float:
        return (self.params_gib + self.grads_gib + self.opt_state_gib
                + self.activations_gib + self.logits_gib)

    def rows(self) -> list[tuple[str, float]]:
        return [
            ("params", self.params_gib),
            ("grads", self.grads_gib),
            ("optimizer state", self.opt_state_gib),
            ("activations (model)", self.activations_gib),
            ("loss/logits (model)", self.logits_gib),
            ("total", self.total_gib),
        ]


def _decoder_dims(cfg):
    """Field adapter: the builtin families name their dims differently
    (GPT-2: n_embd/n_head/n_layer; OPT/NeoX lack kv-heads or inter size)."""
    h = getattr(cfg, "hidden_size", None) or getattr(cfg, "n_embd")
    nh = getattr(cfg, "num_attention_heads", None) or getattr(cfg, "n_head")
    L = getattr(cfg, "num_hidden_layers", None) or getattr(cfg, "n_layer")
    nkv = getattr(cfg, "num_key_value_heads", None) or nh
    d = getattr(cfg, "head_dim", None) or h // nh
    inter = (getattr(cfg, "intermediate_size", None)
             or getattr(cfg, "n_inner", None)
             or getattr(cfg, "ffn_dim", None)
             or 4 * h)
    return h, nh, L, nkv, d, inter, cfg.vocab_size


def _activation_model(cfg, per_chip_batch: int, seq_local: int,
                      compute_bytes: int) -> tuple[int, int]:
    """(saved_bytes, logits_bytes) per chip for a scanned decoder.

    Model (documented, approximate): with ``remat`` on, each of the L layers
    saves its block input carry (B,S,H); policy "flash" additionally keeps the
    kernel's (out, lse); policy "dots" also keeps every matmul output
    (qkv/o/gate/up/down). The recompute peak is ~one block's working set.
    The fused chunked loss keeps one (B, chunk, V) fp32 logits slice live.
    Without remat every intermediate of every layer stays live — estimated as
    the "dots" footprint plus attention probabilities are never materialized
    (flash kernel), which is what the families compute.
    """
    H, nh, L, nkv, d, inter, vocab = _decoder_dims(cfg)
    B, S = per_chip_batch, seq_local
    c = compute_bytes

    carry = B * S * H * c
    flash_saved = B * S * nh * d * c + B * nh * S * 4  # kernel out + fp32 lse
    dots_saved = B * S * ((nh + 2 * nkv) * d + H + 2 * inter + inter) * c
    policy = getattr(cfg, "remat_policy", "flash")
    if getattr(cfg, "remat", False):
        if policy == "minimal":
            per_layer = carry
        elif policy == "dots":
            per_layer = carry + flash_saved + dots_saved
        else:  # flash
            per_layer = carry + flash_saved
        # Recompute peak: one block's full working set lives during backward.
        peak = dots_saved + flash_saved
    else:
        per_layer = carry + flash_saved + dots_saved
        peak = 0
    chunk = 256  # fused_cross_entropy_loss default
    logits = B * min(chunk, S) * vocab * 4  # fp32 softmax slice
    return per_layer * L + peak, logits


def activation_bytes(
    cfg,
    per_chip_batch: int,
    seq_local: int,
    compute_bytes: int,
    *,
    remat: Optional[bool] = None,
    remat_policy: Optional[str] = None,
) -> tuple[int, int]:
    """(saved_bytes, logits_bytes) of the closed-form activation model, with
    optional remat overrides so callers (the auto-parallelism planner's
    remat-escalation ladder, planner.py) can walk the none → selective →
    full ladder without rebuilding the module per rung."""
    if remat is not None or remat_policy is not None:
        cfg = dataclasses.replace(
            cfg,
            remat=cfg.remat if remat is None else remat,
            remat_policy=cfg.remat_policy if remat_policy is None else remat_policy,
        )
    return _activation_model(cfg, per_chip_batch, seq_local, compute_bytes)


def abstract_param_shapes(module) -> Any:
    """Abstract (ShapeDtypeStruct) param tree of ``module`` — one eval_shape,
    no FLOPs, no memory. Split out so the planner can score many candidate
    topologies against a single shape tree."""
    ids = jax.ShapeDtypeStruct((1, 8), np.int32)
    return jax.eval_shape(
        lambda r, i: module.init(r, i), jax.random.key(0), ids
    )["params"]


def estimate_per_chip(
    module,
    cfg,
    parallelism_config,
    *,
    seq: int,
    per_chip_batch: int = 1,
    optimizer: str = "adamw",
    master_dtype: Any = np.float32,
    moments_dtype: Any = None,
    fsdp_plugin=None,
    tp_rules: Optional[list] = None,
    mesh=None,
    param_shapes: Any = None,
) -> tuple[MemoryEstimate, Any, Any]:
    """Per-chip HBM estimate for training ``module`` under the given
    topology. Returns (estimate, param_shapes, param_shardings) so callers
    (the 7B dryrun, the auto-parallelism planner) can reuse the plan.

    ``mesh`` may be a real Mesh; defaults to an AbstractMesh built from
    ``parallelism_config`` — identical specs either way. ``param_shapes``
    skips the eval_shape when the caller already has the abstract tree
    (the planner scores dozens of topologies against one tree).
    """
    from ..parallel.sharding import infer_opt_state_sharding, plan_parameter_sharding

    mesh = mesh if mesh is not None else build_abstract_mesh(parallelism_config)
    shapes = param_shapes if param_shapes is not None else abstract_param_shapes(module)
    shardings = plan_parameter_sharding(
        shapes, mesh, fsdp_plugin=fsdp_plugin,
        parallelism_config=parallelism_config, tp_rules=tp_rules,
    )
    m_itemsize = np.dtype(master_dtype).itemsize
    params_b = _tree_bytes_per_chip(shapes, shardings, mesh, dtype=master_dtype)
    grads_b = params_b  # grads share the param specs + master dtype in the step

    moments = {"adamw": 2, "adam": 2, "sgd": 0, "momentum": 1, "lion": 1,
               "adafactor": 0}.get(optimizer, 2)
    mo_itemsize = np.dtype(moments_dtype or master_dtype).itemsize
    opt_b = params_b // m_itemsize * mo_itemsize * moments

    # Sequence is sharded over cp/sp; batch over dp axes is the caller's
    # per-chip number already.
    cfgp = parallelism_config
    seq_local = seq // max(1, cfgp.cp_size * cfgp.sp_size)
    compute_bytes = np.dtype(
        getattr(cfg, "dtype", np.dtype("bfloat16"))
    ).itemsize
    act_b, logits_b = _activation_model(cfg, per_chip_batch, seq_local, compute_bytes)

    est = MemoryEstimate(
        params_gib=params_b / GiB,
        grads_gib=grads_b / GiB,
        opt_state_gib=opt_b / GiB,
        activations_gib=act_b / GiB,
        logits_gib=logits_b / GiB,
    )
    return est, shapes, shardings
