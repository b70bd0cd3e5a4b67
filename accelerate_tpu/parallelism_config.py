"""N-D parallelism configuration → one JAX device mesh.

This is the keystone of the TPU-native design. The reference builds a torch
``DeviceMesh`` with canonical dim order ``(dp_replicate, dp_shard, cp, sp, tp)``
plus flattened joint meshes ``dp``, ``dp_shard_cp``, ``dp_cp``
(reference: src/accelerate/parallelism_config.py:34-272). Here the same config
surface produces a :class:`jax.sharding.Mesh`; every parallelism backend in the
reference (DDP, FSDP1/2, HSDP, DeepSpeed-ZeRO, TP, CP, SP) becomes a
``NamedSharding``/``PartitionSpec`` choice over these axes, and XLA's GSPMD
partitioner inserts the collectives over ICI/DCN.

Because JAX ``PartitionSpec`` accepts *tuples* of axis names, the reference's
flattened joint meshes are zero-cost here: ``P(("dp_replicate", "dp_shard"))``
*is* the flattened ``dp`` mesh.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from .utils.constants import MESH_AXIS_ORDER, PARALLELISM_CONFIG_PREFIX
from .utils.environment import get_int_from_env, parse_choice_from_env


class ParallelismOversubscriptionError(ValueError):
    """The configured axis degrees multiply to MORE than the device count —
    a different (and more common) failure than a non-dividing product, so it
    gets its own message naming each offending axis and the env var that
    sets it."""


@dataclasses.dataclass
class ParallelismConfig:
    """Degrees for every first-class parallelism axis.

    Mirrors the reference's ``ParallelismConfig``
    (reference: parallelism_config.py:34-98) with the same validation rules
    (cp and sp mutually exclusive, reference: parallelism_config.py:328-334)
    and adds ``pp_size`` / ``ep_size`` as first-class citizens (the reference
    reaches pipeline and expert parallelism only through Megatron-LM,
    SURVEY.md §2.3).

    Axis semantics:
      - ``dp_replicate``: pure data parallel (DDP-style replication).
      - ``dp_shard``: ZeRO/FSDP-style parameter+optimizer sharding axis.
      - ``cp``: context parallel (ring attention) — sequence sharded, KV rotated.
      - ``sp``: Ulysses sequence parallel — heads sharded via all-to-all.
      - ``tp``: tensor parallel — hidden dims sharded.
      - ``ep``: expert parallel — experts sharded over the joint (dp_shard, sp, tp)
        axes at MoE layers (no extra mesh dim needed; like torchtitan/DeepSpeed-MoE).
      - ``pp``: pipeline parallel — model stages; implemented as a microbatch
        schedule over mesh sub-slices, not an extra GSPMD dim.
    """

    dp_replicate_size: int = 1
    dp_shard_size: int = 1
    cp_size: int = 1
    sp_size: int = 1
    tp_size: int = 1
    ep_size: int = 1
    pp_size: int = 1

    # "alltoall" = ring rotation of KV blocks; "allgather" = gather full KV
    # (reference: TorchContextParallelConfig.set_rotate_method,
    # utils/dataclasses.py:2205-2231).
    cp_rotate_method: str = "alltoall"

    # Interleaving degree for the pipeline schedule (Megatron's
    # num_layers_per_virtual_pipeline_stage knob, expressed as the virtual
    # multiplier: each device holds this many non-contiguous layer chunks and
    # the fill/drain bubble shrinks by the same factor). Consumed by
    # parallel/pp.py's pipeline_apply / llama_pipeline_forward defaults.
    pp_virtual_stages: int = 1

    def __post_init__(self):
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name.endswith("_size") and (not isinstance(v, int) or v < 1):
                raise ValueError(f"{f.name} must be a positive int, got {v!r}")
        if self.cp_size > 1 and self.sp_size > 1:
            # Same rule as the reference (parallelism_config.py:328-334).
            raise ValueError(
                "cp_size and sp_size cannot both be >1: ring context-parallelism "
                "and Ulysses sequence-parallelism are mutually exclusive."
            )
        if self.cp_rotate_method not in ("alltoall", "allgather"):
            raise ValueError(f"cp_rotate_method must be alltoall|allgather, got {self.cp_rotate_method}")
        if self.ep_size > 1 and self.ep_size > self.dp_shard_size * self.sp_size * self.tp_size:
            raise ValueError(
                "ep_size must divide into dp_shard*sp*tp (experts are sharded over "
                f"those axes); got ep={self.ep_size}"
            )
        if not isinstance(self.pp_virtual_stages, int) or self.pp_virtual_stages < 1:
            raise ValueError(
                f"pp_virtual_stages must be a positive int, got {self.pp_virtual_stages!r}"
            )

    # ------------------------------------------------------------------
    # Size properties (reference: parallelism_config.py:100-164)
    # ------------------------------------------------------------------

    @property
    def dp_size(self) -> int:
        return self.dp_replicate_size * self.dp_shard_size

    @property
    def dp_shard_cp_size(self) -> int:
        return self.dp_shard_size * self.cp_size

    @property
    def dp_cp_size(self) -> int:
        return self.dp_size * self.cp_size

    @property
    def non_pp_size(self) -> int:
        return self.dp_cp_size * self.sp_size * self.tp_size

    @property
    def total_size(self) -> int:
        return self.non_pp_size * self.pp_size

    @property
    def active_mesh_dims(self) -> tuple[str, ...]:
        return tuple(ax for ax in MESH_AXIS_ORDER if self.axis_size(ax) > 1)

    def axis_size(self, axis: str) -> int:
        return getattr(self, f"{axis}_size")

    # ------------------------------------------------------------------
    # Flattened logical axis groups — PartitionSpec-ready tuples.
    # (reference flattens real submeshes, parallelism_config.py:211-272;
    #  in JAX a tuple of axis names is equivalent and free.)
    # ------------------------------------------------------------------

    @property
    def dp_axes(self) -> tuple[str, ...]:
        return ("dp_replicate", "dp_shard")

    @property
    def fsdp_axes(self) -> tuple[str, ...]:
        """Axes FSDP-style param sharding spans: dp_shard joined with cp
        (reference: parallelism_config.py:157-164 ``fsdp_dim_names``)."""
        return ("dp_shard", "cp")

    @property
    def batch_axes(self) -> tuple[str, ...]:
        """Axes the global batch dim is sharded over. TP ranks see identical
        batches (reference: data_loader.py:1127-1163); cp/sp ranks share a batch
        but split the sequence dim."""
        return ("dp_replicate", "dp_shard")

    @property
    def seq_axes(self) -> tuple[str, ...]:
        """Axes the sequence dim is sharded over (cp or sp, never both)."""
        return ("cp", "sp")

    @property
    def ep_axes(self) -> tuple[str, ...]:
        """Mesh axes the expert dim of MoE layers is sharded over.

        ``ep`` borrows capacity from existing axes (no extra mesh dim — the
        DeepSpeed-MoE / torchtitan pattern, SURVEY.md §2.3 EP row): whole axes
        are taken greedily from ``(dp_shard, sp, tp)`` until their product is
        exactly ``ep_size``. Sub-axis sharding is not expressible in a
        PartitionSpec, so ``ep_size`` must be a product of full axis sizes."""
        if self.ep_size == 1:
            return ()
        # Exhaustive subset search (candidate count ≤ 3 so 2^3 subsets):
        # greedy-by-order can wrongly consume an early axis and then fail even
        # though a later subset matches exactly. Prefer earlier axes on ties.
        candidates = [ax for ax in ("dp_shard", "sp", "tp") if self.axis_size(ax) > 1]
        from itertools import combinations

        for r in range(1, len(candidates) + 1):
            for combo in combinations(candidates, r):
                prod = 1
                for ax in combo:
                    prod *= self.axis_size(ax)
                if prod == self.ep_size:
                    return tuple(combo)
        raise ValueError(
            f"ep_size={self.ep_size} is not a product of whole mesh axes from "
            f"(dp_shard={self.dp_shard_size}, sp={self.sp_size}, tp={self.tp_size}); "
            "choose ep equal to such a product."
        )

    @property
    def loss_reduce_axes(self) -> tuple[str, ...]:
        """Axes a scalar loss must be averaged over — dp + cp + sp
        (reference: SP loss averaged across sp+dp ranks, SURVEY.md §2.3)."""
        return ("dp_replicate", "dp_shard", "cp", "sp")

    # ------------------------------------------------------------------
    # Env round-trip (reference: parallelism_config.py:274-289)
    # ------------------------------------------------------------------

    @classmethod
    def from_env(cls) -> "ParallelismConfig":
        p = PARALLELISM_CONFIG_PREFIX
        return cls(
            dp_replicate_size=get_int_from_env([f"{p}DP_REPLICATE_SIZE"], 1),
            dp_shard_size=get_int_from_env([f"{p}DP_SHARD_SIZE"], 1),
            cp_size=get_int_from_env([f"{p}CP_SIZE"], 1),
            sp_size=get_int_from_env([f"{p}SP_SIZE"], 1),
            tp_size=get_int_from_env([f"{p}TP_SIZE"], 1),
            ep_size=get_int_from_env([f"{p}EP_SIZE"], 1),
            pp_size=get_int_from_env([f"{p}PP_SIZE"], 1),
            cp_rotate_method=parse_choice_from_env(f"{p}CP_ROTATE_METHOD", "alltoall"),
            pp_virtual_stages=get_int_from_env([f"{p}PP_VIRTUAL_STAGES"], 1),
        )

    def to_env(self) -> dict[str, str]:
        p = PARALLELISM_CONFIG_PREFIX
        env = {
            f"{p}DP_REPLICATE_SIZE": str(self.dp_replicate_size),
            f"{p}DP_SHARD_SIZE": str(self.dp_shard_size),
            f"{p}CP_SIZE": str(self.cp_size),
            f"{p}SP_SIZE": str(self.sp_size),
            f"{p}TP_SIZE": str(self.tp_size),
            f"{p}EP_SIZE": str(self.ep_size),
            f"{p}PP_SIZE": str(self.pp_size),
            f"{p}CP_ROTATE_METHOD": self.cp_rotate_method,
            f"{p}PP_VIRTUAL_STAGES": str(self.pp_virtual_stages),
        }
        return env

    # ------------------------------------------------------------------
    # Mesh construction
    # ------------------------------------------------------------------

    def infer_missing_axis(self, n_devices: int) -> "ParallelismConfig":
        """Fill ``dp_shard_size`` so the mesh covers all devices when the user
        left it at 1 and the product doesn't match (mirrors the reference's
        auto world-size fill)."""
        fixed = self.total_size
        if fixed == n_devices:
            return self
        if fixed > n_devices:
            # "Product does not divide device count" is actively misleading
            # here — nothing can be filled in; an axis must SHRINK. Name the
            # offending axes and their env vars.
            p = PARALLELISM_CONFIG_PREFIX
            axes = [
                f"{ax}={self.axis_size(ax)} ({p}{ax.upper()}_SIZE)"
                for ax in MESH_AXIS_ORDER + ("pp",)
                if self.axis_size(ax) > 1
            ]
            raise ParallelismOversubscriptionError(
                f"parallelism axes multiply to {fixed} but only {n_devices} "
                f"device(s) are visible: {', '.join(axes) or 'none >1'}. "
                f"Reduce one of these axes (or launch with more devices)."
            )
        if n_devices % fixed != 0:
            raise ValueError(
                f"parallelism product {fixed} does not divide device count {n_devices}"
            )
        return dataclasses.replace(self, dp_shard_size=self.dp_shard_size * (n_devices // fixed))

    def build_mesh(self, devices=None):
        """Build the canonical :class:`jax.sharding.Mesh`.

        Axes are always present (size-1 axes are free in GSPMD) so every
        PartitionSpec in the framework can name any canonical axis without
        branching on the active topology. Device order comes from
        ``mesh_utils.create_device_mesh``: on TPU slices the innermost axes
        (tp, sp, cp) land on ICI-adjacent chips, on CPU/virtual devices it is
        a plain reshape. ``allow_split_physical_axes`` covers the one shape it
        otherwise refuses: more logical axes >1 than the slice has physical
        ones (e.g. dp_shard=2 x cp=2 x tp=2 on a 2x4 v5e).
        """
        import jax
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        n = len(devices)
        cfg = self.infer_missing_axis(n)
        # ``pp`` is a real (leading) mesh axis so stage sub-meshes are
        # contiguous device slices; the canonical GSPMD axes follow in the
        # reference's order. The only tensors whose PartitionSpec names
        # ``pp`` are stacked scanned-layer weights (sharded on the layer dim,
        # parallel/sharding.py) — everything else addresses the pipeline
        # through parallel/pp's shard_map schedule.
        axis_names = ("pp",) + MESH_AXIS_ORDER
        shape = (cfg.pp_size,) + tuple(cfg.axis_size(ax) for ax in MESH_AXIS_ORDER)
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True
        )
        return Mesh(dev_array, axis_names)

    def get_device_mesh(self, devices=None):
        return self.build_mesh(devices)

    def layout_dict(self) -> dict:
        """Axis-degree dict in the planner's artifact schema (planner.py
        plans, resharding.py plan manifests, and the ``plan`` CLI all speak
        this form)."""
        return {
            "dp_replicate": self.dp_replicate_size,
            "dp_shard": self.dp_shard_size,
            "cp": self.cp_size,
            "sp": self.sp_size,
            "tp": self.tp_size,
            "pp": self.pp_size,
            "ep": self.ep_size,
        }

    def __repr__(self) -> str:  # compact, hides size-1 axes
        active = {ax: self.axis_size(ax) for ax in MESH_AXIS_ORDER if self.axis_size(ax) > 1}
        if self.ep_size > 1:
            active["ep"] = self.ep_size
        if self.pp_size > 1:
            active["pp"] = self.pp_size
        if self.pp_virtual_stages > 1:
            active["pp_virtual_stages"] = self.pp_virtual_stages
        return f"ParallelismConfig({active or 'single-device'})"


def build_mesh_from_env(devices=None):
    """Convenience: decode ``PARALLELISM_CONFIG_*`` env and build the mesh."""
    return ParallelismConfig.from_env().build_mesh(devices)
