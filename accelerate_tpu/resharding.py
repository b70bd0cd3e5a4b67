"""Elastic resharding: restore a checkpoint written under one mesh/plan onto
a different one, and hot-swap layouts mid-run.

The engine has three layers, shared by cold restore and live migration:

1. **Plan manifest** — ``write_plan_manifest`` records the *source* topology
   next to the model files: mesh layout, world size, and one entry per
   ``TrainState`` leaf with its shape/dtype/``PartitionSpec``. On load,
   ``read_plan_manifest`` + ``check_topology`` detect a mismatch *before* any
   deserialization, so a world-size-N checkpoint on M chips either raises a
   descriptive :class:`TopologyMismatchError` (elastic off) or routes through
   the planned redistribution below (elastic on).

2. **Transfer planning** — each leaf is classified by the collective its
   redistribution implies (``noop`` / ``slice`` / ``all_gather`` /
   ``all_to_all``) and the leaves are greedily batched so the per-device
   bytes resident during a batch never exceed a configurable staging budget
   (the memory-bounding idea of arXiv:2112.01075: planned collectives, not
   gather-to-host). A leaf whose single-transfer footprint cannot fit the
   budget falls back to host-staged chunked ingest — each device reads only
   its destination slices from host memory.

3. **Execution** — on restore, a leaf is ingested from host with its
   *source* spec projected onto the new mesh (every mesh carries all
   canonical axis names, so source specs remain valid), then redistributed
   on-device with a batched ``jax.device_put`` to the destination shardings
   (donating the ingest buffers). Live migration skips the ingest: leaves
   are already ``jax.Array`` s and are re-put directly, donated.

Declarative target layouts (the destination is just the sharding tree the
planner would produce for the new topology) follow SimpleFSDP's
constraint-driven style (arXiv:2411.00284).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Any, Optional

import numpy as np

from .utils.constants import MESH_AXIS_ORDER, PLAN_MANIFEST_NAME

logger = logging.getLogger(__name__)

PLAN_MANIFEST_VERSION = 1

# Ops a leaf redistribution can imply, from cheapest to most general.
RESHARD_OPS = ("noop", "slice", "all_gather", "all_to_all")


class TopologyMismatchError(RuntimeError):
    """A checkpoint written under one topology was loaded on another while
    elastic restore is off. Carries both topologies in the message."""


# ----------------------------------------------------------------------
# PartitionSpec <-> JSON
# ----------------------------------------------------------------------


def spec_to_jsonable(spec) -> list:
    """``PartitionSpec`` -> JSON-serializable list (entry: None | str |
    list[str]). ``None`` and unspecified shardings serialize to ``[]``."""
    if spec is None:
        return []
    out: list = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, str):
            out.append(entry)
        else:
            out.append([str(a) for a in entry])
    return out


def spec_from_jsonable(entries):
    """Inverse of :func:`spec_to_jsonable`."""
    from jax.sharding import PartitionSpec

    if not entries:
        return PartitionSpec()
    fixed = []
    for entry in entries:
        if entry is None or isinstance(entry, str):
            fixed.append(entry)
        else:
            fixed.append(tuple(entry))
    return PartitionSpec(*fixed)


def _entry_axes(entry) -> tuple:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def normalize_spec(entries, axis_sizes: dict) -> tuple:
    """Drop size-1 axes (they shard nothing) and trailing unsharded dims so
    specs compare by *effect*, not spelling."""
    out = []
    for entry in entries:
        axes = tuple(a for a in _entry_axes(entry) if axis_sizes.get(a, 1) > 1)
        out.append(axes)
    while out and not out[-1]:
        out.pop()
    return tuple(out)


def _shard_degrees(norm: tuple, axis_sizes: dict) -> tuple:
    degrees = []
    for axes in norm:
        d = 1
        for a in axes:
            d *= axis_sizes.get(a, 1)
        degrees.append(d)
    return tuple(degrees)


def classify_op(src_entries, dst_entries, src_axis_sizes: dict, dst_axis_sizes: dict) -> str:
    """Name the collective the ``src -> dst`` redistribution implies."""
    src = normalize_spec(src_entries, src_axis_sizes)
    dst = normalize_spec(dst_entries, dst_axis_sizes)
    if src == dst and _shard_degrees(src, src_axis_sizes) == _shard_degrees(dst, dst_axis_sizes):
        return "noop"
    src_sharded = any(src)
    dst_sharded = any(dst)
    if not src_sharded and dst_sharded:
        return "slice"
    if src_sharded and not dst_sharded:
        return "all_gather"
    if not src_sharded and not dst_sharded:
        # replicated -> replicated across a different device count: a
        # broadcast, no re-tiling — noop as far as the schedule is concerned.
        return "noop"
    return "all_to_all"


def mesh_axis_sizes(mesh) -> dict:
    return {str(name): int(size) for name, size in mesh.shape.items()}


def layout_axis_sizes(layout: dict) -> dict:
    """Axis sizes implied by a planner layout dict (missing axes are 1)."""
    sizes = {ax: int(layout.get(ax, 1)) for ax in MESH_AXIS_ORDER}
    sizes["pp"] = int(layout.get("pp", 1))
    return sizes


# ----------------------------------------------------------------------
# Plan manifest (the topology sidecar inside a checkpoint dir)
# ----------------------------------------------------------------------


def _leaf_records(tree, shardings, prefix: str) -> dict:
    """One record per array leaf: shape, dtype, serialized PartitionSpec."""
    import jax

    from .parallel.sharding import _path_to_name

    records: dict = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    shard_flat, _ = jax.tree_util.tree_flatten_with_path(shardings)
    shard_by_name = {_path_to_name(p): s for p, s in shard_flat}
    for path, leaf in flat:
        if not hasattr(leaf, "shape"):
            continue
        name = _path_to_name(path)
        sharding = shard_by_name.get(name)
        spec = getattr(sharding, "spec", None)
        records[f"{prefix}/{name}"] = {
            "shape": [int(d) for d in getattr(leaf, "shape", ())],
            "dtype": str(np.dtype(getattr(leaf, "dtype", np.float32))),
            "spec": spec_to_jsonable(spec),
        }
    return records


def write_plan_manifest(accelerator, out_dir: str) -> Optional[str]:
    """Write the topology sidecar into a (staging) checkpoint dir.

    Main-process only; returns the path written or None. Written *inside*
    the atomic staging dir, so the fault-tolerance manifest hashes and
    certifies it like any other checkpoint file."""
    if not accelerator.is_main_process:
        return None
    state = accelerator.state
    pc = state.parallelism_config
    layout = pc.layout_dict() if pc is not None else {}
    leaves: dict = {}
    for slot, train_state in enumerate(getattr(accelerator, "_train_states", []) or []):
        if train_state is None:
            continue
        metas = getattr(accelerator, "_slot_meta", None) or []
        if isinstance(metas, dict):
            meta = metas.get(slot) or {}
        else:
            meta = metas[slot] if slot < len(metas) else {}
        shardings = meta.get("state_shardings")
        if shardings is None:
            continue
        leaves.update(_leaf_records(train_state, shardings, prefix=f"slot{slot}"))
    plan = getattr(accelerator, "active_plan", None)
    # Monotonic publication guard: the train step of the first prepared
    # slot, matching the fault-tolerance manifest's weights_version.
    weights_version = None
    for train_state in getattr(accelerator, "_train_states", []) or []:
        step = getattr(train_state, "step", None)
        if step is not None:
            try:
                weights_version = int(step)
            except (TypeError, ValueError):
                weights_version = None
            break
    manifest = {
        "version": PLAN_MANIFEST_VERSION,
        "weights_version": weights_version,
        "world_size": int(accelerator.num_processes),
        "n_devices": len(state.devices),
        "layout": layout,
        "mesh_axes": mesh_axis_sizes(state.mesh) if state.mesh is not None else {},
        "plan_key": getattr(plan, "key", None),
        "leaves": leaves,
    }
    path = os.path.join(out_dir, PLAN_MANIFEST_NAME)
    tmp = path + ".part"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def read_plan_manifest(ckpt_dir: str) -> Optional[dict]:
    path = os.path.join(ckpt_dir, PLAN_MANIFEST_NAME)
    if not os.path.isfile(path):
        return None
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        logger.warning("unreadable %s (%s) — treating checkpoint as topology-less", path, e)
        return None
    if not isinstance(manifest, dict) or "leaves" not in manifest:
        return None
    return manifest


def topology_matches(manifest: dict, n_devices: int, layout: Optional[dict]) -> bool:
    """True when the checkpoint's topology equals the live one (same device
    count and, when both sides recorded a layout, the same layout)."""
    if int(manifest.get("n_devices", manifest.get("world_size", 0))) != int(n_devices):
        return False
    src_layout = manifest.get("layout") or {}
    if src_layout and layout:
        return layout_axis_sizes(src_layout) == layout_axis_sizes(layout)
    return True


def describe_topology(n_devices: int, layout: Optional[dict]) -> str:
    sizes = layout_axis_sizes(layout) if layout else {}
    active = {ax: n for ax, n in sizes.items() if n > 1}
    inner = ", ".join(f"{ax}={n}" for ax, n in sorted(active.items())) or "single-axis"
    return f"{n_devices} device(s) [{inner}]"


def raise_topology_mismatch(manifest: dict, n_devices: int, layout: Optional[dict], ckpt_dir: str):
    src = describe_topology(
        int(manifest.get("n_devices", manifest.get("world_size", 0))), manifest.get("layout")
    )
    dst = describe_topology(n_devices, layout)
    raise TopologyMismatchError(
        f"checkpoint at {ckpt_dir!r} was written on {src} but is being "
        f"restored on {dst}. Elastic restore is off, so the sharded state "
        "cannot be redistributed. Pass "
        "ElasticKwargs() in Accelerator(kwargs_handlers=[...]) to restore "
        "across topologies, or relaunch on the original topology."
    )


def shrink_world_size(current: int, lost: int = 1, layout: Optional[dict] = None) -> Optional[int]:
    """The world size the launch supervisor should relaunch at after losing
    ``lost`` host(s) to repeated dead-host exits (commands/launch.py).

    With a recorded layout (a plan artifact's, or the run's parallelism
    config), the answer is the largest size at or below ``current - lost``
    the planner validates via :func:`planner.validate_world_size` — i.e.
    the model-parallel axes still divide it, so the elastic resume reshards
    instead of re-searching. Without one, the largest power of two at or
    below the target, which keeps dp sharding even on any checkpoint.
    Returns None when no viable smaller size exists."""
    target = int(current) - max(1, int(lost))
    if target < 1:
        return None
    if layout:
        from .planner import validate_world_size

        for n in range(target, 0, -1):
            if validate_world_size(n, layout):
                return n
        return None
    n = 1
    while n * 2 <= target:
        n *= 2
    return n


def grow_world_size(current: int, gained: int = 1,
                    layout: Optional[dict] = None) -> Optional[int]:
    """Symmetric inverse of :func:`shrink_world_size`, for the serving
    autoscaler (autoscale.py): the world size to grow to after ``gained``
    spare device(s) became available. With a recorded layout, the largest
    planner-validated size in ``(current, current + gained]`` (same shared
    :func:`planner.validate_world_size` gate as the shrink path); without
    one, the largest power of two at or below the target. Returns None
    when no viable LARGER size exists — growing sideways or down is never
    an answer here."""
    cur = int(current)
    if cur < 1:
        return None
    target = cur + max(1, int(gained))
    if layout:
        from .planner import validate_world_size

        for n in range(target, cur, -1):
            if validate_world_size(n, layout):
                return n
        return None
    n = 1
    while n * 2 <= target:
        n *= 2
    return n if n > cur else None


# ----------------------------------------------------------------------
# Transfer planning
# ----------------------------------------------------------------------


@dataclasses.dataclass
class LeafTransfer:
    """One leaf's redistribution: what moves, how, and its HBM footprint."""

    name: str
    shape: tuple
    dtype: str
    nbytes: int
    src_spec: list
    dst_spec: list
    op: str
    device_bytes: int  # per-device bytes resident while this leaf transfers
    dst_bytes: int = 0  # destination shard bytes alone (host-staged footprint)
    host_staged: bool = False
    index: int = 0  # position in the flat leaf list (execution addressing)

    def to_row(self) -> dict:
        return {
            "leaf": self.name,
            "shape": list(self.shape),
            "dtype": self.dtype,
            "bytes": self.nbytes,
            "op": self.op,
            "host_staged": self.host_staged,
        }


@dataclasses.dataclass
class ReshardSchedule:
    """Batched transfer plan: ``batches`` index into ``transfers`` and each
    batch's summed per-device footprint stays within the staging budget."""

    transfers: list
    batches: list
    staging_budget_bytes: int

    @property
    def depth(self) -> int:
        return len(self.batches)

    @property
    def total_bytes(self) -> int:
        return sum(t.nbytes for t in self.transfers)

    @property
    def moved_bytes(self) -> int:
        return sum(t.nbytes for t in self.transfers if t.op != "noop" or t.host_staged)

    @property
    def moved_leaves(self) -> int:
        return sum(1 for t in self.transfers if t.op != "noop" or t.host_staged)

    @property
    def host_staged_leaves(self) -> int:
        return sum(1 for t in self.transfers if t.host_staged)

    @property
    def peak_batch_bytes(self) -> int:
        if not self.batches:
            return 0
        return max(sum(self.transfers[i].device_bytes for i in batch) for batch in self.batches)

    def summary(self) -> dict:
        ops: dict = {}
        for t in self.transfers:
            ops[t.op] = ops.get(t.op, 0) + 1
        return {
            "leaves": len(self.transfers),
            "moved_leaves": self.moved_leaves,
            "bytes": self.total_bytes,
            "bytes_transferred": self.moved_bytes,
            "host_staged": self.host_staged_leaves,
            "depth": self.depth,
            "peak_batch_bytes": self.peak_batch_bytes,
            "staging_budget_bytes": self.staging_budget_bytes,
            "ops": ops,
        }

    def format_table(self, max_rows: int = 40) -> str:
        header = f"{'leaf':<48} {'shape':<18} {'bytes':>12} {'op':<10} staged"
        lines = [header, "-" * len(header)]
        for t in self.transfers[:max_rows]:
            shape = "x".join(str(d) for d in t.shape) or "scalar"
            lines.append(
                f"{t.name[:48]:<48} {shape:<18} {t.nbytes:>12,} {t.op:<10} "
                f"{'yes' if t.host_staged else 'no'}"
            )
        if len(self.transfers) > max_rows:
            lines.append(f"... {len(self.transfers) - max_rows} more leaves")
        return "\n".join(lines)


def _dst_shard_bytes(nbytes: int, dst_entries, dst_axis_sizes: dict) -> int:
    degree = 1
    for axes in normalize_spec(dst_entries, dst_axis_sizes):
        for a in axes:
            degree *= dst_axis_sizes.get(a, 1)
    return max(1, nbytes // max(1, degree))


def plan_leaf_transfer(
    name: str,
    shape,
    dtype,
    src_entries,
    dst_entries,
    src_axis_sizes: dict,
    dst_axis_sizes: dict,
    index: int = 0,
) -> LeafTransfer:
    nbytes = int(np.dtype(dtype).itemsize * int(np.prod(shape, dtype=np.int64))) if shape else int(
        np.dtype(dtype).itemsize
    )
    op = classify_op(src_entries, dst_entries, src_axis_sizes, dst_axis_sizes)
    dst_bytes = _dst_shard_bytes(nbytes, dst_entries, dst_axis_sizes)
    # Footprint during an ingest-then-redistribute transfer: the leaf staged
    # under its source spec (projected onto the new mesh) plus the
    # destination shard, both resident until the batch's device_put retires.
    src_bytes = _dst_shard_bytes(nbytes, src_entries, dst_axis_sizes)
    device_bytes = dst_bytes if op == "noop" else src_bytes + dst_bytes
    return LeafTransfer(
        name=name,
        shape=tuple(int(d) for d in shape),
        dtype=str(np.dtype(dtype)),
        nbytes=nbytes,
        src_spec=list(src_entries) if src_entries else [],
        dst_spec=list(dst_entries) if dst_entries else [],
        op=op,
        device_bytes=device_bytes,
        dst_bytes=dst_bytes,
        index=index,
    )


def build_schedule(
    transfers: list,
    staging_budget_bytes: int,
    *,
    host_stage_oversize: bool = True,
) -> ReshardSchedule:
    """Greedy deterministic batching (name order) bounded by the staging
    budget. A leaf whose lone footprint exceeds the budget is host-staged —
    each device reads only its destination slices from host, dropping the
    ingest copy from the footprint."""
    budget = max(1, int(staging_budget_bytes))
    ordered = sorted(transfers, key=lambda t: t.name)
    for t in ordered:
        if t.device_bytes > budget and host_stage_oversize and t.op != "noop":
            t.host_staged = True
            t.device_bytes = t.dst_bytes or t.nbytes
    batches: list = []
    current: list = []
    current_bytes = 0
    for t in ordered:
        if t.host_staged:
            if current:
                batches.append(current)
                current, current_bytes = [], 0
            batches.append([t.index])
            continue
        if current and current_bytes + t.device_bytes > budget:
            batches.append(current)
            current, current_bytes = [], 0
        current.append(t.index)
        current_bytes += t.device_bytes
    if current:
        batches.append(current)
    return ReshardSchedule(
        transfers=sorted(transfers, key=lambda t: t.index),
        batches=batches,
        staging_budget_bytes=budget,
    )


def predict_transfer_s(schedule: ReshardSchedule, bandwidths, n_devices: int) -> float:
    """Rough wall-time estimate for the CLI: each leaf at the slowest link
    among the mesh axes it crosses, discounted by collective efficiency.
    Host-staged leaves pay the host link (DCN rate as the pessimistic
    stand-in)."""
    eff = max(1e-6, getattr(bandwidths, "collective_efficiency", 0.7))
    total = 0.0
    for t in schedule.transfers:
        if t.op == "noop" and not t.host_staged:
            continue
        if t.host_staged:
            gbps = getattr(bandwidths, "dcn_gbps", 6.25)
        else:
            axes = set()
            for entry in list(t.src_spec) + list(t.dst_spec):
                axes.update(_entry_axes(entry))
            rates = [bandwidths.axis_gbps(a, n_devices) for a in axes] or [
                getattr(bandwidths, "ici_gbps", 90.0)
            ]
            gbps = min(rates)
        total += t.nbytes / (gbps * 1e9 * eff)
    return total


def schedule_from_manifest(
    manifest: dict,
    dst_layout: dict,
    staging_budget_bytes: int,
    *,
    host_stage_oversize: bool = True,
) -> ReshardSchedule:
    """Plan a migration straight from a checkpoint's plan manifest without a
    live model (the ``accelerate-tpu plan --from-checkpoint`` path). The
    destination spec of each leaf is its source spec re-read under the new
    layout's axis sizes — layout changes re-size axes, they don't rename
    them."""
    src_sizes = layout_axis_sizes(manifest.get("layout") or {})
    if manifest.get("mesh_axes"):
        src_sizes.update({a: int(n) for a, n in manifest["mesh_axes"].items()})
    dst_sizes = layout_axis_sizes(dst_layout)
    transfers = []
    for i, (name, rec) in enumerate(sorted(manifest.get("leaves", {}).items())):
        transfers.append(
            plan_leaf_transfer(
                name,
                rec.get("shape", ()),
                rec.get("dtype", "float32"),
                rec.get("spec", []),
                rec.get("spec", []),
                src_sizes,
                dst_sizes,
                index=i,
            )
        )
    return build_schedule(
        transfers, staging_budget_bytes, host_stage_oversize=host_stage_oversize
    )


# ----------------------------------------------------------------------
# Execution
# ----------------------------------------------------------------------


def _ingest_sharding(mesh, src_entries, shape):
    """Source spec projected onto the *new* mesh (all meshes carry every
    canonical axis name). Returns None when the projection cannot tile the
    leaf — caller falls back to host staging."""
    from jax.sharding import NamedSharding

    sizes = mesh_axis_sizes(mesh)
    norm = normalize_spec(src_entries, sizes)
    if not any(norm):
        return None  # replicated source: nothing to project
    for dim, axes in enumerate(norm):
        degree = 1
        for a in axes:
            if a not in sizes:
                return None
            degree *= sizes[a]
        if degree > 1 and (dim >= len(shape) or shape[dim] % degree != 0):
            return None
    entries = [axes if len(axes) != 1 else axes[0] for axes in norm]
    entries = [e if e else None for e in entries]
    return NamedSharding(mesh, spec_from_jsonable(entries))


class ReshardExecutor:
    """Plans and executes leaf redistributions for one mesh, accumulating
    telemetry across calls (params tree, then per-slot optimizer trees)."""

    def __init__(
        self,
        mesh,
        *,
        manifest: Optional[dict] = None,
        staging_budget_bytes: int = 256 * 1024 * 1024,
        host_stage_oversize: bool = True,
    ):
        self.mesh = mesh
        self.manifest = manifest or {}
        self.staging_budget_bytes = int(staging_budget_bytes)
        self.host_stage_oversize = host_stage_oversize
        self._dst_sizes = mesh_axis_sizes(mesh) if mesh is not None else {}
        # Source axis sizes come from the manifest (cold restore); a live
        # migration has no manifest — leaves carry their own shardings on the
        # same devices, so the live mesh's sizes apply to both sides.
        self._src_sizes = None
        if self.manifest.get("layout") or self.manifest.get("mesh_axes"):
            self._src_sizes = layout_axis_sizes(self.manifest.get("layout") or {})
            if self.manifest.get("mesh_axes"):
                self._src_sizes.update(
                    {a: int(n) for a, n in self.manifest["mesh_axes"].items()}
                )
        self._stats = {
            "leaves": 0,
            "moved_leaves": 0,
            "bytes": 0,
            "bytes_transferred": 0,
            "host_staged": 0,
            "depth": 0,
            "peak_batch_bytes": 0,
            "wall_s": 0.0,
            "ops": {},
        }

    # -- planning ------------------------------------------------------

    def _src_entries(self, name: str, leaf) -> list:
        rec = (self.manifest.get("leaves") or {}).get(name)
        if rec is not None:
            return rec.get("spec", [])
        # Live leaf: its own sharding is the source of truth.
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        return spec_to_jsonable(spec)

    def plan_tree(self, tree, dst_shardings, prefix: str = "") -> ReshardSchedule:
        import jax

        from .parallel.sharding import _path_to_name

        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        shard_flat, _ = jax.tree_util.tree_flatten_with_path(dst_shardings)
        shard_by_name = {_path_to_name(p): s for p, s in shard_flat}
        transfers = []
        for i, (path, leaf) in enumerate(flat):
            local = _path_to_name(path)
            name = f"{prefix}/{local}" if prefix else local
            sharding = shard_by_name.get(local)
            dst_entries = spec_to_jsonable(getattr(sharding, "spec", None))
            shape = tuple(getattr(leaf, "shape", ()) or ())
            dtype = getattr(leaf, "dtype", np.float32)
            src_sizes = self._src_sizes
            if src_sizes is None:
                # Live leaf: its own (old) mesh defines the source degrees.
                src_mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
                src_sizes = (
                    mesh_axis_sizes(src_mesh)
                    if hasattr(src_mesh, "shape")
                    else self._dst_sizes
                )
            transfers.append(
                plan_leaf_transfer(
                    name,
                    shape,
                    dtype,
                    self._src_entries(name, leaf),
                    dst_entries,
                    src_sizes,
                    self._dst_sizes,
                    index=i,
                )
            )
        return build_schedule(
            transfers,
            self.staging_budget_bytes,
            host_stage_oversize=self.host_stage_oversize,
        )

    # -- execution -----------------------------------------------------

    def put_tree(self, tree, dst_shardings, prefix: str = "",
                 donate: bool = True):
        """Redistribute every leaf of ``tree`` to ``dst_shardings``.

        Host (numpy) leaves are ingested under their source spec projected
        onto the live mesh, then redistributed on-device in budget-bounded
        batches; device (``jax.Array``) leaves are re-put directly with
        donated buffers (pass ``donate=False`` to keep the source alive —
        the serving autoscaler's live resize copies params to the new
        layout while in-flight requests still decode on the old one).
        Returns the resharded tree."""
        import jax

        t0 = time.monotonic()
        schedule = self.plan_tree(tree, dst_shardings, prefix=prefix)
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        shard_flat, _ = jax.tree_util.tree_flatten_with_path(dst_shardings)
        from .parallel.sharding import _path_to_name

        shard_by_name = {_path_to_name(p): s for p, s in shard_flat}
        leaves = [leaf for _, leaf in flat]
        names = [_path_to_name(p) for p, _ in flat]
        out: list = list(leaves)

        for batch in schedule.batches:
            staged = []  # (position, ingest_array, dst_sharding)
            batch_outs = []
            for i in batch:
                t = schedule.transfers[i]
                leaf = leaves[t.index]
                sharding = shard_by_name.get(names[t.index])
                if sharding is None:
                    continue
                if not hasattr(leaf, "shape"):
                    if np.isscalar(leaf):
                        leaf = np.asarray(leaf)
                    else:
                        continue
                if isinstance(leaf, jax.Array) and not getattr(leaf, "is_deleted", lambda: False)():
                    # Live migration: redistribute on-device, donate source.
                    staged.append((t.index, leaf, sharding))
                    continue
                host = np.asarray(leaf)
                ingest = None
                if not t.host_staged and t.op != "noop":
                    ingest = _ingest_sharding(self.mesh, t.src_spec, host.shape)
                if ingest is None:
                    # noop, host-staged, or untileable projection: each device
                    # reads its destination slices straight from host memory.
                    arr = jax.make_array_from_callback(
                        host.shape, sharding, lambda idx, a=host: a[idx]
                    )
                    out[t.index] = arr
                    batch_outs.append(arr)
                else:
                    src_arr = jax.make_array_from_callback(
                        host.shape, ingest, lambda idx, a=host: a[idx]
                    )
                    staged.append((t.index, src_arr, sharding))
            if staged:
                positions, arrays, dsts = zip(*staged)
                moved = jax.device_put(list(arrays), list(dsts),
                                       donate=bool(donate))
                for pos, arr in zip(positions, moved):
                    out[pos] = arr
                batch_outs.extend(moved)
            if batch_outs:
                jax.block_until_ready(batch_outs)

        self._accumulate(schedule, time.monotonic() - t0)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _accumulate(self, schedule: ReshardSchedule, wall_s: float):
        s = schedule.summary()
        st = self._stats
        for k in ("leaves", "moved_leaves", "bytes", "bytes_transferred", "host_staged", "depth"):
            st[k] += s[k]
        st["peak_batch_bytes"] = max(st["peak_batch_bytes"], s["peak_batch_bytes"])
        st["staging_budget_bytes"] = s["staging_budget_bytes"]
        st["wall_s"] += wall_s
        for op, n in s["ops"].items():
            st["ops"][op] = st["ops"].get(op, 0) + n

    def stats(self) -> dict:
        out = dict(self._stats)
        out["wall_s"] = round(out["wall_s"], 6)
        return out


# ----------------------------------------------------------------------
# Subsystem manager (the ElasticKwargs-gated handle on the Accelerator)
# ----------------------------------------------------------------------


class ElasticManager:
    """Thin policy holder wired into the Accelerator when ``ElasticKwargs``
    is passed: owns the staging budget, the resize policy consulted after an
    elastic relaunch, and the telemetry hand-off after a reshard."""

    def __init__(self, accelerator, handler):
        self.accelerator = accelerator
        self.handler = handler
        self.reshard_count = 0
        self.last_stats: Optional[dict] = None

    @property
    def enabled(self) -> bool:
        return bool(getattr(self.handler, "enabled", False))

    @property
    def elastic_restore(self) -> bool:
        return self.enabled and bool(getattr(self.handler, "elastic_restore", True))

    @property
    def staging_budget_bytes(self) -> int:
        mb = float(getattr(self.handler, "staging_budget_mb", 256.0))
        return max(1, int(mb * 1024 * 1024))

    @property
    def resize_policy(self) -> str:
        return getattr(self.handler, "resize_policy", "replan")

    def executor(self, mesh, manifest: Optional[dict] = None) -> ReshardExecutor:
        return ReshardExecutor(
            mesh,
            manifest=manifest,
            staging_budget_bytes=self.staging_budget_bytes,
            host_stage_oversize=bool(getattr(self.handler, "host_stage_oversize", True)),
        )

    def note_reshard(self, stats: dict, *, kind: str = "restore", source: Optional[dict] = None):
        """Record a completed reshard in telemetry (the ``reshard`` block)."""
        self.reshard_count += 1
        self.last_stats = dict(stats, kind=kind)
        telemetry = getattr(self.accelerator, "telemetry", None)
        if telemetry is not None:
            try:
                telemetry.record_reshard(dict(stats, kind=kind, count=self.reshard_count))
            except Exception:
                logger.debug("telemetry.record_reshard failed", exc_info=True)
        logger.info(
            "%s reshard #%d: %d/%d leaves moved, %s bytes, depth %d, %.3fs",
            kind,
            self.reshard_count,
            stats.get("moved_leaves", 0),
            stats.get("leaves", 0),
            f"{stats.get('bytes_transferred', 0):,}",
            stats.get("depth", 0),
            stats.get("wall_s", 0.0),
        )
