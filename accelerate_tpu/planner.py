"""Auto-parallelism planner (layer L11 — decision-making).

Every mechanism below this file already exists: ``ParallelismConfig`` builds
any (dp_replicate, dp_shard, cp, sp, tp, pp) mesh, ``plan_parameter_sharding``
shards a param tree over it, and ``utils/estimate_memory.py`` prices the
per-chip working set of any layout without touching a device. What the user
still had to do by hand was *pick* the layout — and on a new model or a new
slice shape the first pick is usually wrong in one of two expensive ways
(OOM, or an ICI-saturated layout that trains at half speed).

:class:`Planner` automates that choice:

1. **Enumerate** every valid factorization of the device count into
   ``(dp_replicate, dp_shard, tp, cp, pp)`` degrees (plus an ``ep`` degree
   riding the dp_shard/tp axes for MoE models), respecting the model's
   divisibility constraints — ``heads % tp``, ``kv_heads % tp``,
   ``layers % pp``, ``seq % cp``, ``experts % ep`` — and any user-pinned
   axes.
2. **Score** each candidate twice: per-chip HBM through the SAME
   ``estimate_per_chip`` path the trainer and ``estimate-memory`` CLI use
   (no drift possible), and predicted step time through an analytic cost
   model — a compute roofline (layout-invariant for balanced
   factorizations) plus per-axis collective volume (FSDP all-gather +
   reduce-scatter, dp_replicate all-reduce, TP activation all-reduces, CP
   ring rotation, PP activation sends and fill/drain bubble) over a
   configurable ICI/DCN :class:`BandwidthTable`.
3. **Escalate** a candidate that misses the HBM budget through the remat /
   microbatch ladder — no remat → selective ("flash") → full ("minimal") →
   split the step into more microbatches — before rejecting it; deeper
   ``dp_shard`` escalation falls out of the candidate ranking (those
   layouts simply fit where shallower ones don't).
4. **Emit** a versioned :class:`ParallelPlan` JSON artifact: the chosen
   layout + remat policy + microbatch count, the predicted step time and
   per-chip HBM with the full cost breakdown, a rejection log for the
   runner-ups, and a calibration block that telemetry fills in with
   measured step time / peak HBM after N real steps
   (:func:`record_calibration`) so repeated runs tighten the
   bandwidth/efficiency constants.

Plan artifacts are deterministic — same inputs produce byte-identical JSON
(no timestamps, sorted keys, rounded floats) — and cached under
``<project_dir>/plans/`` keyed by a hash of every search input, so a second
launch loads the plan instead of re-searching.

Entry points: ``Accelerator(parallelism_config="auto")`` or an
:class:`~accelerate_tpu.utils.AutoPlanKwargs` handler (resolved at
``prepare()``), the ``accelerate-tpu plan`` CLI, or this module directly.
Related work: arXiv:2004.13336 (cross-replica weight-update sharding as a
memory/communication trade) and arXiv:2112.01075 (collective-based array
redistribution) — both resolve layout choice with cheap analytic models,
which is all a first-launch decision needs.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import tempfile
from typing import Any, Optional

import numpy as np

from .logging import get_logger
from .parallelism_config import ParallelismConfig


class _StateSafeLogger:
    """The planner runs standalone too (`accelerate-tpu plan` builds no
    Accelerator), where the multi-process adapter refuses to log before
    PartialState exists — fall back to a plain stdlib logger there."""

    def __init__(self, name: str):
        self._adapter = get_logger(name)
        import logging as _logging

        self._plain = _logging.getLogger(name)

    def _log(self, level: str, msg, *args, **kwargs):
        try:
            getattr(self._adapter, level)(msg, *args, **kwargs)
        except RuntimeError:  # no PartialState yet
            kwargs.pop("main_process_only", None)
            kwargs.pop("in_order", None)
            getattr(self._plain, level)(msg, *args, **kwargs)

    def info(self, msg, *args, **kwargs):
        self._log("info", msg, *args, **kwargs)

    def warning(self, msg, *args, **kwargs):
        self._log("warning", msg, *args, **kwargs)


logger = _StateSafeLogger(__name__)

PLAN_VERSION = 1
GiB = 1024 ** 3

#: Axes the search may raise above 1 by default. ``cp``/``pp``/``ep`` are
#: enumerable too (the CLI enables them all) but need model/loss support the
#: in-training auto path cannot verify, so AutoPlanKwargs keeps them opt-in.
DEFAULT_SEARCH_AXES = ("dp_replicate", "dp_shard", "tp")
ALL_SEARCH_AXES = ("dp_replicate", "dp_shard", "tp", "cp", "pp", "ep")

#: The remat escalation ladder: none → selective (flash residuals kept) →
#: full (recompute everything). Walked per candidate until it fits.
REMAT_LADDER = ((False, "flash"), (True, "flash"), (True, "minimal"))

#: Backward-pass recompute FLOPs per ladder rung, as a multiplier on the
#: 6·P·T roofline (fwd=2, bwd=4; selective remat re-runs most of the fwd
#: ≈ +1.7/6, full remat re-runs all of it ≈ +2/6).
REMAT_COMPUTE_COST = {
    (False, "flash"): 1.0,
    (True, "flash"): 1.28,
    (True, "minimal"): 1.33,
}


class PlannerError(ValueError):
    """No candidate satisfies the constraints (bad pins, indivisible axes)."""


class PlanVersionError(ValueError):
    """Plan artifact written by an incompatible planner version."""


# ----------------------------------------------------------------------
# Bandwidth / efficiency table
# ----------------------------------------------------------------------


@dataclasses.dataclass
class BandwidthTable:
    """Analytic-model constants. Defaults describe a v5e pod slice; every
    field is overridable (AutoPlanKwargs.bandwidths / ``plan --bandwidth``)
    and ``mfu`` + ``collective_efficiency`` are the two the calibration loop
    tightens from measured steps."""

    ici_gbps: float = 90.0          # per-chip ICI bandwidth, GB/s
    dcn_gbps: float = 6.25          # per-chip DCN bandwidth, GB/s (50 Gb/s)
    flops_per_chip: float = 197e12  # peak bf16 FLOP/s (v5e: 197 TFLOP/s)
    mfu: float = 0.4                # achievable model-FLOPs utilization
    collective_efficiency: float = 0.7   # achieved fraction of link bandwidth
    ici_domain: int = 256           # largest device count one ICI fabric spans
    microbatch_overhead_s: float = 1e-4  # per-microbatch dispatch overhead
    # Fraction of data-parallel comm (FSDP all-gather/reduce-scatter, DP
    # all-reduce) XLA's latency-hiding scheduler hides behind compute. TP/CP
    # collectives sit on the critical path and never overlap here.
    dp_overlap: float = 0.7

    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "BandwidthTable":
        if not d:
            return cls()
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"unknown BandwidthTable field(s) {sorted(unknown)}; "
                f"valid: {sorted(known)}"
            )
        return cls(**d)

    def axis_gbps(self, axis: str, n_devices: int) -> float:
        """Bandwidth serving collectives over ``axis``. Inner mesh axes
        (tp/sp/cp) are laid on ICI-adjacent chips by build_mesh; the outer
        data-parallel axes spill onto DCN once the slice outgrows one ICI
        domain."""
        if axis in ("tp", "sp", "cp"):
            return self.ici_gbps
        return self.ici_gbps if n_devices <= self.ici_domain else self.dcn_gbps

    def handoff_gbps(self, n_devices: int) -> float:
        """Bandwidth of the prefill→decode KV-page handoff link (disagg.py).
        Both slices of a split that fits one ICI domain are ICI-adjacent;
        a split spanning domains streams pages over DCN."""
        link = self.ici_gbps if n_devices <= self.ici_domain else self.dcn_gbps
        return link * self.collective_efficiency

    def kv_bytes_per_token(self, cfg, dtype=None) -> int:
        """Dtype-aware KV footprint of one token (both K and V, all
        layers). ``dtype`` overrides the config's cache dtype — pass the
        actual page dtype (e.g. int8 quantized pages) so the handoff link
        is priced on the bytes it really moves, not a hard-coded bf16."""
        return kv_bytes_per_token(cfg, dtype=dtype)


# ----------------------------------------------------------------------
# Model profile (the divisibility constraints + roofline dims)
# ----------------------------------------------------------------------


@dataclasses.dataclass
class ModelProfile:
    """The handful of numbers the enumerator and cost model need. Built from
    any config the builtin families produce (``from_config``); ``params`` is
    exact when a module is supplied (one eval_shape) and closed-form
    otherwise."""

    params: int
    hidden: int
    heads: int
    kv_heads: int
    layers: int
    intermediate: int
    vocab: int
    experts: int = 0  # 0 = dense model
    label: str = "model"

    @classmethod
    def from_config(cls, cfg, module=None, label: Optional[str] = None) -> "ModelProfile":
        from .utils.estimate_memory import _decoder_dims, abstract_param_shapes

        try:
            h, nh, L, nkv, d, inter, vocab = _decoder_dims(cfg)
        except AttributeError as e:
            raise PlannerError(
                f"cannot profile {type(cfg).__name__}: it lacks the decoder "
                f"dims the planner constrains on ({e}). Pass an explicit "
                f"ParallelismConfig instead of 'auto' for this model."
            ) from None
        experts = int(getattr(cfg, "num_local_experts", 0) or 0)
        if module is not None:
            import jax

            shapes = abstract_param_shapes(module)
            params = sum(
                math.prod(s.shape)
                for s in jax.tree_util.tree_leaves(shapes)
                if hasattr(s, "shape")
            )
        else:
            mlp = 3 * h * inter if getattr(cfg, "mlp_gated", True) else 2 * h * inter
            if experts:
                mlp = mlp * experts + h * experts  # experts + router
            per_layer = (nh + 2 * nkv) * d * h + nh * d * h + mlp + 2 * h
            tied = getattr(cfg, "tie_word_embeddings", False)
            params = vocab * h * (1 if tied else 2) + L * per_layer + h
        return cls(
            params=int(params), hidden=h, heads=nh, kv_heads=nkv, layers=L,
            intermediate=inter, vocab=vocab, experts=experts,
            label=label or type(cfg).__name__,
        )

    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}


def default_tp_rules(module, cfg) -> Optional[list]:
    """Family TP-rule table for a builtin module, or None. Lets the auto
    path price tp>1 candidates with real sharding even when the caller never
    passed ``tp_rules`` (without rules, TP'd layouts look fully replicated
    to the memory model and are penalized out of the race)."""
    name = type(module).__name__
    scan = getattr(cfg, "scan_layers", True)
    try:
        if "Mixtral" in name:
            from .models.moe import mixtral_tp_rules

            return mixtral_tp_rules(scan)
        if "Llama" in name:
            from .models.llama import llama_tp_rules

            return llama_tp_rules(scan)
        if "OPT" in name:
            from .models.opt import opt_tp_rules

            return opt_tp_rules(scan)
        if "NeoX" in name:
            from .models.neox import neox_tp_rules

            return neox_tp_rules(scan)
        if "GPT2" in name:
            from .models.gpt2 import gpt2_tp_rules

            return gpt2_tp_rules(scan)
    except ImportError:  # pragma: no cover
        pass
    return None


# ----------------------------------------------------------------------
# Candidate enumeration
# ----------------------------------------------------------------------


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_layouts(
    n_devices: int,
    profile: ModelProfile,
    *,
    seq: int,
    axes: tuple[str, ...] = ALL_SEARCH_AXES,
    pinned: Optional[dict] = None,
) -> list[ParallelismConfig]:
    """Every valid ``ParallelismConfig`` whose mesh covers exactly
    ``n_devices``, in a deterministic order.

    Constraints enforced per candidate:
      - ``dp_replicate * dp_shard * cp * tp * pp == n_devices``
      - ``tp`` divides heads, kv_heads and hidden (Megatron-TP shards all 3)
      - ``pp`` divides layers
      - ``cp`` divides seq
      - ``ep`` divides experts (MoE only) and must be a product of whole
        (dp_shard, tp) axes — ParallelismConfig.ep_axes validates.

    ``pinned`` maps axis name → forced degree (``{"tp": 2}``); an axis not in
    ``axes`` and not pinned stays at 1.
    """
    pinned = dict(pinned or {})
    valid_axes = set(ALL_SEARCH_AXES)
    for ax in pinned:
        if ax not in valid_axes:
            raise PlannerError(
                f"pinned axis {ax!r} is not plannable (valid: {sorted(valid_axes)})"
            )

    def _choices(axis: str, constraint) -> list[int]:
        if axis in pinned:
            v = int(pinned[axis])
            return [v] if constraint(v) else []
        if axis not in axes:
            return [1]
        return [d for d in _divisors(n_devices) if constraint(d)]

    tp_choices = _choices(
        "tp",
        lambda t: profile.heads % t == 0
        and profile.kv_heads % t == 0
        and profile.hidden % t == 0,
    )
    pp_choices = _choices("pp", lambda p: p <= profile.layers and profile.layers % p == 0)
    cp_choices = _choices("cp", lambda c: seq % c == 0)

    out: list[ParallelismConfig] = []
    for pp in pp_choices:
        for tp in tp_choices:
            for cp in cp_choices:
                fixed = pp * tp * cp
                if n_devices % fixed != 0:
                    continue
                dp_total = n_devices // fixed
                for dp_shard in _choices("dp_shard", lambda s: dp_total % s == 0):
                    if dp_total % dp_shard != 0:
                        continue
                    dp_replicate = dp_total // dp_shard
                    if "dp_replicate" in pinned and dp_replicate != int(pinned["dp_replicate"]):
                        continue
                    if "dp_replicate" not in axes and "dp_replicate" not in pinned and dp_replicate != 1:
                        continue
                    ep_choices = [1]
                    if profile.experts:
                        ep_choices = _choices(
                            "ep", lambda e: e <= profile.experts and profile.experts % e == 0
                        )
                    for ep in ep_choices:
                        try:
                            pc = ParallelismConfig(
                                dp_replicate_size=dp_replicate,
                                dp_shard_size=dp_shard,
                                cp_size=cp,
                                tp_size=tp,
                                pp_size=pp,
                                ep_size=ep,
                            )
                            pc.ep_axes  # ep must be a product of whole axes
                        except ValueError:
                            continue
                        out.append(pc)
    if not out:
        raise PlannerError(
            f"no valid layout for {n_devices} devices with pins {pinned or '{}'} "
            f"(heads={profile.heads}, kv_heads={profile.kv_heads}, "
            f"layers={profile.layers}, seq={seq}"
            + (f", experts={profile.experts}" if profile.experts else "")
            + ") — relax a pin or change the device count."
        )
    return out


# ----------------------------------------------------------------------
# Analytic step-time cost model
# ----------------------------------------------------------------------


@dataclasses.dataclass
class CostBreakdown:
    """Per-step predicted seconds and per-axis collective volume (bytes per
    chip per step) — the evidence trail stored in the plan artifact."""

    compute_s: float = 0.0
    fsdp_comm_s: float = 0.0
    dp_comm_s: float = 0.0
    tp_comm_s: float = 0.0
    cp_comm_s: float = 0.0
    pp_comm_s: float = 0.0
    fsdp_bytes: int = 0
    dp_bytes: int = 0
    tp_bytes: int = 0
    cp_bytes: int = 0
    pp_bytes: int = 0
    bubble_fraction: float = 0.0
    microbatch_overhead_s: float = 0.0
    step_s: float = 0.0

    @property
    def comm_s(self) -> float:
        return (self.fsdp_comm_s + self.dp_comm_s + self.tp_comm_s
                + self.cp_comm_s + self.pp_comm_s)

    @property
    def collective_bytes(self) -> int:
        return (self.fsdp_bytes + self.dp_bytes + self.tp_bytes
                + self.cp_bytes + self.pp_bytes)

    def to_dict(self) -> dict:
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d["comm_s"] = self.comm_s
        d["collective_bytes"] = self.collective_bytes
        return {k: (_round6(v) if isinstance(v, float) else v) for k, v in d.items()}


def _round6(x: float) -> float:
    """Stable float rounding so plan JSON is byte-identical across runs."""
    return float(f"{x:.6g}")


def predict_step_time(
    profile: ModelProfile,
    pc: ParallelismConfig,
    bw: BandwidthTable,
    *,
    seq: int,
    per_chip_batch: int,
    microbatches: int = 1,
    compute_bytes: int = 2,
    master_bytes: int = 4,
    params_sharded: bool = True,
    compute_multiplier: float = 1.0,
) -> CostBreakdown:
    """Predicted seconds for ONE optimizer step of the global batch under
    layout ``pc``.

    Model (documented, deliberately cheap — a ranking function, not a
    simulator):

    - **Compute roofline**: total step FLOPs ≈ 6 · params · global_tokens
      (fwd + bwd), spread evenly over every device — layout-invariant for
      balanced factorizations, discounted by ``bw.mfu``.
    - **FSDP** (dp_shard > 1, sharded params): per step each chip
      all-gathers its parameter shard twice (fwd + bwd) and reduce-scatters
      grads once → 3 · P_local · (d−1)/d bytes at ``master_bytes``.
    - **dp_replicate**: one grad all-reduce → 2 · P_local · (d−1)/d.
    - **TP**: per layer 2 fwd all-reduces of the (B·S_local·H) activation,
      doubled for bwd → 8 · (t−1)/t · B·S_local·H · compute_bytes · layers.
    - **CP ring**: per layer, rotate K+V around the ring —
      2 · B·S_local·kv_dim · (c−1) bytes, doubled for bwd.
    - **PP**: boundary activation sends (per microbatch, per stage edge) and
      the fill/drain bubble: step time scales by
      ``(m + pp − 1)/m`` (bubble fraction ``(pp−1)/(m+pp−1)``), plus a fixed
      per-microbatch dispatch overhead that keeps the microbatch ladder from
      degenerating to m→∞.
    - **Remat**: callers pass ``compute_multiplier`` > 1 for rematerialized
      rungs (the backward recompute FLOPs — see ``REMAT_COMPUTE_COST``) so
      the escalation ladder pays for the memory it saves.
    """
    n = pc.total_size
    dp = pc.dp_size
    # The workload is held CONSTANT across candidates so step times compare:
    # ``per_chip_batch`` means samples/chip at pure data parallelism, i.e. a
    # global batch of per_chip_batch · n samples every layout must process.
    # Each data-parallel rank (a tp×cp×pp group) then carries
    # global_batch / dp samples.
    global_tokens = per_chip_batch * n * seq
    batch_per_rank = per_chip_batch * n / max(1, dp)
    seq_local = seq // max(1, pc.cp_size * pc.sp_size)
    eff_flops = bw.flops_per_chip * bw.mfu
    compute_s = (
        6.0 * profile.params * global_tokens / n / eff_flops * compute_multiplier
    )

    # Params a chip touches after the model-sharding axes split them.
    p_local = profile.params / (pc.tp_size * pc.pp_size)
    coll_eff = bw.collective_efficiency
    out = CostBreakdown(compute_s=compute_s)

    d = pc.dp_shard_size
    if d > 1 and params_sharded:
        vol = 3.0 * p_local * master_bytes * (d - 1) / d
        out.fsdp_bytes = int(vol)
        out.fsdp_comm_s = vol / (bw.axis_gbps("dp_shard", n) * 1e9 * coll_eff)
    elif d > 1:
        # Unsharded params on a dp_shard axis reduce like dp_replicate.
        vol = 2.0 * p_local * master_bytes * (d - 1) / d
        out.dp_bytes += int(vol)
        out.dp_comm_s += vol / (bw.axis_gbps("dp_shard", n) * 1e9 * coll_eff)

    r = pc.dp_replicate_size
    if r > 1:
        vol = 2.0 * p_local * master_bytes * (r - 1) / r
        out.dp_bytes += int(vol)
        out.dp_comm_s += vol / (bw.axis_gbps("dp_replicate", n) * 1e9 * coll_eff)

    t = pc.tp_size
    if t > 1:
        act = batch_per_rank * seq_local * profile.hidden * compute_bytes
        vol = 8.0 * act * (t - 1) / t * profile.layers / pc.pp_size
        out.tp_bytes = int(vol)
        out.tp_comm_s = vol / (bw.axis_gbps("tp", n) * 1e9 * coll_eff)

    c = pc.cp_size
    if c > 1:
        kv_dim = profile.kv_heads * (profile.hidden // profile.heads)
        vol = 4.0 * batch_per_rank * seq_local * kv_dim * compute_bytes \
            * (c - 1) * profile.layers / pc.pp_size
        out.cp_bytes = int(vol)
        out.cp_comm_s = vol / (bw.axis_gbps("cp", n) * 1e9 * coll_eff)

    p = pc.pp_size
    m = max(1, microbatches)
    if p > 1:
        # Per-microbatch boundary sends × m microbatches = the full rank
        # batch's activations crossing each of the (p-1) stage edges, fwd+bwd.
        act = batch_per_rank * seq_local * profile.hidden * compute_bytes
        vol = 2.0 * act * (p - 1)
        out.pp_bytes = int(vol)
        out.pp_comm_s = vol / (bw.axis_gbps("pp", n) * 1e9 * coll_eff)
        out.bubble_fraction = (p - 1) / (m + p - 1)
    out.microbatch_overhead_s = bw.microbatch_overhead_s * m

    # Data-parallel collectives overlap with compute (latency-hiding
    # scheduler); only the spill past ``dp_overlap · compute`` is visible.
    # Model-parallel (tp/cp/pp) collectives sit on the critical path.
    dp_visible = max(0.0, out.fsdp_comm_s + out.dp_comm_s - bw.dp_overlap * compute_s)
    work = compute_s + out.tp_comm_s + out.cp_comm_s + out.pp_comm_s + dp_visible
    out.step_s = work * (m + p - 1) / m + out.microbatch_overhead_s
    return out


# ----------------------------------------------------------------------
# Plan artifact
# ----------------------------------------------------------------------


def _layout_dict(pc: ParallelismConfig) -> dict:
    return pc.layout_dict()


def parallelism_config_from_layout(layout: dict) -> ParallelismConfig:
    return ParallelismConfig(
        dp_replicate_size=int(layout.get("dp_replicate", 1)),
        dp_shard_size=int(layout.get("dp_shard", 1)),
        cp_size=int(layout.get("cp", 1)),
        sp_size=int(layout.get("sp", 1)),
        tp_size=int(layout.get("tp", 1)),
        pp_size=int(layout.get("pp", 1)),
        ep_size=int(layout.get("ep", 1)),
    )


def layout_str(layout: dict) -> str:
    active = {k: v for k, v in layout.items() if v > 1}
    return ",".join(f"{k}={v}" for k, v in active.items()) or "single-device"


def resize_pins(layout: dict, n_devices: int) -> dict:
    """Pins for a planner re-search after an elastic resize (resharding.py).

    The model-parallel axes (tp, cp, pp — sp is not plannable) are what the
    previous run's search — and its calibration data — decided was winning
    for this model; a device-count change shifts the *data*-parallel budget,
    not the model's divisibility constraints. Keep each such axis pinned
    while the running product still divides the new device count (greedy, in
    the order the layout priced them); the dp axes are left free so the
    search absorbs the resize there."""
    pins: dict = {}
    prod = 1
    for ax in ("tp", "cp", "pp"):
        n = int(layout.get(ax, 1))
        if n > 1 and n_devices % (prod * n) == 0:
            pins[ax] = n
            prod *= n
    return pins


def scaled_layout(layout: dict, n_devices: int) -> Optional[dict]:
    """The previous layout with only its data-parallel extent rescaled to
    ``n_devices`` (the elastic ``resize_policy="keep"`` path). Returns None
    when the non-dp axes no longer divide the new device count — callers
    fall back to a pinned re-search."""
    fixed = 1
    for ax in ("tp", "cp", "sp", "pp", "dp_replicate"):
        fixed *= int(layout.get(ax, 1))
    if fixed > n_devices or n_devices % fixed != 0:
        return None
    out = {k: int(v) for k, v in layout.items()}
    out["dp_shard"] = n_devices // fixed
    return out


def validate_world_size(n_devices: int, layout: Optional[dict] = None) -> bool:
    """Whether ``n_devices`` is a viable world size — THE shared topology
    gate for everything that proposes one: ``GangSupervisor
    --shrink_after_dead_hosts`` (via :func:`resharding.shrink_world_size`)
    and the serving autoscaler (autoscale.py, directly and via
    ``grow_world_size``) both route through here so their notions of
    "valid" can't drift. With a recorded ``layout`` the answer is the
    planner's: :func:`scaled_layout` must rescale the data-parallel extent
    to ``n_devices`` with every model-parallel axis still dividing it.
    Without one, any positive count is viable (the pow2 preference the
    grow/shrink helpers apply is policy, not validity)."""
    n = int(n_devices)
    if n < 1:
        return False
    if layout:
        return scaled_layout(layout, n) is not None
    return True


@dataclasses.dataclass
class ParallelPlan:
    """Versioned, deterministic plan artifact. ``to_json`` of two plans built
    from identical inputs is byte-identical (sorted keys, rounded floats, no
    timestamps); only :func:`record_calibration` mutates a written plan."""

    version: int
    key: str                 # hash of every search input (cache identity)
    model: str
    n_devices: int
    seq: int
    per_chip_batch: int
    optimizer: str
    hbm_gib_budget: float
    layout: dict
    remat: bool
    remat_policy: str
    microbatches: int
    predicted_step_s: float
    predicted_hbm_gib: float
    memory_rows: dict        # params/grads/opt/activations/logits GiB
    breakdown: dict          # CostBreakdown.to_dict()
    bandwidths: dict         # BandwidthTable used for the search
    over_budget: bool
    rejections: list         # runner-up log: layout, reason, predictions
    profile: dict            # ModelProfile.to_dict()
    calibration: Optional[dict] = None

    def to_parallelism_config(self) -> ParallelismConfig:
        return parallelism_config_from_layout(self.layout)

    def to_json_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_json_dict(), indent=2, sort_keys=True) + "\n"

    @classmethod
    def from_json_dict(cls, d: dict) -> "ParallelPlan":
        version = d.get("version")
        if version != PLAN_VERSION:
            raise PlanVersionError(
                f"plan artifact has version {version!r}; this planner speaks "
                f"version {PLAN_VERSION}. Re-run the search (delete the plan "
                f"file or pass use_cache=False)."
            )
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_json(cls, text: str) -> "ParallelPlan":
        return cls.from_json_dict(json.loads(text))

    def save(self, path: str) -> None:
        _atomic_write(path, self.to_json())

    @classmethod
    def load(cls, path: str) -> "ParallelPlan":
        with open(path) as f:
            return cls.from_json(f.read())


def _atomic_write(path: str, text: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


# ----------------------------------------------------------------------
# The planner
# ----------------------------------------------------------------------


class Planner:
    """Search driver. Construct with a model (module + config) or a bare
    :class:`ModelProfile`; call :meth:`search` for a fresh plan or
    :meth:`resolve` for the cached-artifact path."""

    def __init__(
        self,
        module=None,
        cfg=None,
        *,
        profile: Optional[ModelProfile] = None,
        n_devices: int,
        hbm_gib: float,
        seq: int,
        per_chip_batch: int = 1,
        optimizer: str = "adamw",
        master_dtype: Any = np.float32,
        moments_dtype: Any = None,
        tp_rules: Optional[list] = None,
        axes: tuple[str, ...] = ALL_SEARCH_AXES,
        pinned: Optional[dict] = None,
        bandwidths: Optional[BandwidthTable] = None,
        label: Optional[str] = None,
        max_rejections: int = 16,
    ):
        if module is None and profile is None:
            raise ValueError("Planner needs a module (+cfg) or a ModelProfile")
        self.module = module
        self.cfg = cfg if cfg is not None else getattr(module, "config", None)
        if module is not None and self.cfg is None:
            raise ValueError(
                "Planner needs the module's config (divisibility constraints "
                "+ activation model); pass cfg= explicitly."
            )
        self.profile = profile or ModelProfile.from_config(
            self.cfg, module=module, label=label
        )
        if label:
            self.profile.label = label
        self.n_devices = int(n_devices)
        self.hbm_gib = float(hbm_gib)
        self.seq = int(seq)
        self.per_chip_batch = int(per_chip_batch)
        self.optimizer = optimizer
        self.master_dtype = master_dtype
        self.moments_dtype = moments_dtype
        self.tp_rules = tp_rules
        self.axes = tuple(axes)
        self.pinned = dict(pinned or {})
        self.bandwidths = bandwidths or BandwidthTable()
        self.max_rejections = max_rejections
        self.searches = 0  # incremented by search(); cache hits leave it at 0
        self._param_shapes = None

    # -- cache identity ------------------------------------------------

    def cache_key(self) -> str:
        ident = {
            "version": PLAN_VERSION,
            "profile": self.profile.to_dict(),
            "n_devices": self.n_devices,
            "hbm_gib": self.hbm_gib,
            "seq": self.seq,
            "per_chip_batch": self.per_chip_batch,
            "optimizer": self.optimizer,
            "master_dtype": str(np.dtype(self.master_dtype)),
            "moments_dtype": str(np.dtype(self.moments_dtype or self.master_dtype)),
            "axes": list(self.axes),
            "pinned": {k: self.pinned[k] for k in sorted(self.pinned)},
            "bandwidths": self.bandwidths.to_dict(),
            "has_tp_rules": bool(self.tp_rules),
        }
        blob = json.dumps(ident, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    # -- memory scoring ------------------------------------------------

    def _memory_estimate(self, pc: ParallelismConfig, remat: bool,
                         remat_policy: str, microbatches: int):
        """Per-chip GiB rows for one (layout, remat rung, microbatch) point.
        Tensor state (params/grads/opt) comes from estimate_per_chip — exact,
        remat-invariant, computed once per layout; activations re-priced per
        rung via the closed-form model."""
        from .utils.estimate_memory import (
            activation_bytes,
            estimate_per_chip,
        )

        if self.module is not None:
            if self._param_shapes is None:
                from .utils.estimate_memory import abstract_param_shapes

                self._param_shapes = abstract_param_shapes(self.module)
            est, _, _ = estimate_per_chip(
                self.module, self.cfg, pc,
                seq=self.seq, per_chip_batch=self.per_chip_batch,
                optimizer=self.optimizer, master_dtype=self.master_dtype,
                moments_dtype=self.moments_dtype, tp_rules=self.tp_rules,
                param_shapes=self._param_shapes,
            )
            params_gib, grads_gib, opt_gib = (
                est.params_gib, est.grads_gib, est.opt_state_gib
            )
        else:
            # Profile-only path: closed-form tensor state, evenly sharded
            # over the axes that shard params.
            shard = pc.dp_shard_size * pc.cp_size * pc.tp_size * pc.pp_size
            m_bytes = np.dtype(self.master_dtype).itemsize
            mo_bytes = np.dtype(self.moments_dtype or self.master_dtype).itemsize
            moments = {"adamw": 2, "adam": 2, "sgd": 0, "momentum": 1,
                       "lion": 1, "adafactor": 0}.get(self.optimizer, 2)
            params_gib = self.profile.params * m_bytes / shard / GiB
            grads_gib = params_gib
            opt_gib = self.profile.params * mo_bytes * moments / shard / GiB
        # Per data-parallel rank, the layout carries global_batch/dp samples
        # (global batch = per_chip_batch · n, held constant across
        # candidates); microbatching subdivides that.
        batch_per_rank = self.per_chip_batch * self.n_devices / max(1, pc.dp_size)
        mb_batch = max(1, math.ceil(batch_per_rank / microbatches))
        seq_local = self.seq // max(1, pc.cp_size * pc.sp_size)
        if self.cfg is not None:
            compute_bytes = np.dtype(
                getattr(self.cfg, "dtype", np.dtype("bfloat16"))
            ).itemsize
            act_b, logits_b = activation_bytes(
                self.cfg, mb_batch, seq_local, compute_bytes,
                remat=remat, remat_policy=remat_policy,
            )
            # TP shards the big per-layer intermediates (qkv/ffn outputs,
            # flash residuals) over the tp axis; the unsharded residual
            # stream makes this slightly optimistic for tp > 1.
            act_b = act_b // max(1, pc.tp_size)
        else:
            # Profile-only: carry + flash residuals per layer, full stash
            # without remat.
            H, L = self.profile.hidden, self.profile.layers
            per_layer = mb_batch * seq_local * H * 2
            if not remat:
                per_layer *= 6
            elif remat_policy == "flash":
                per_layer *= 2
            act_b = per_layer * L // max(1, pc.tp_size)
            logits_b = mb_batch * min(256, seq_local) * self.profile.vocab * 4
        rows = {
            "params_gib": params_gib,
            "grads_gib": grads_gib,
            "opt_state_gib": opt_gib,
            "activations_gib": act_b / GiB,
            "logits_gib": logits_b / GiB,
        }
        rows["total_gib"] = sum(rows.values())
        return rows

    # -- per-candidate scoring ----------------------------------------

    def _microbatch_ladder(self, pc: ParallelismConfig) -> list[int]:
        """Microbatch counts worth trying: pp needs ≥ pp in-flight
        microbatches to hide the bubble; memory escalation subdivides the
        per-chip batch while whole samples remain."""
        batch_per_rank = max(
            1, self.per_chip_batch * self.n_devices // max(1, pc.dp_size)
        )
        cap = batch_per_rank * pc.pp_size
        base = [pc.pp_size * k for k in (1, 2, 4, 8)] if pc.pp_size > 1 else [1]
        m = base[-1] * 2
        while m <= cap:
            base.append(m)
            m *= 2
        return sorted({min(b, cap) for b in base})

    def score_candidate(self, pc: ParallelismConfig) -> dict:
        """Walk the remat × microbatch escalation ladder for one layout and
        return its best point: the first rung that fits the HBM budget (or
        the lowest-HBM rung when none does, marked over_budget)."""
        params_sharded = pc.dp_shard_size > 1
        best_fit = None
        min_hbm = None
        for remat, policy in REMAT_LADDER:
            for m in self._microbatch_ladder(pc):
                rows = self._memory_estimate(pc, remat, policy, m)
                cost = predict_step_time(
                    self.profile, pc, self.bandwidths,
                    seq=self.seq, per_chip_batch=self.per_chip_batch,
                    microbatches=m, params_sharded=params_sharded,
                    compute_multiplier=REMAT_COMPUTE_COST[(remat, policy)],
                )
                point = {
                    "layout": _layout_dict(pc),
                    "remat": remat,
                    "remat_policy": policy,
                    "microbatches": m,
                    "hbm_gib": rows["total_gib"],
                    "memory_rows": rows,
                    "cost": cost,
                    "fits": rows["total_gib"] <= self.hbm_gib,
                }
                if min_hbm is None or point["hbm_gib"] < min_hbm["hbm_gib"]:
                    min_hbm = point
                if point["fits"] and (
                    best_fit is None or cost.step_s < best_fit["cost"].step_s
                ):
                    best_fit = point
            if best_fit is not None:
                # A fitting rung exists at this remat level; deeper remat
                # only trades speed for memory we no longer need.
                break
        return best_fit if best_fit is not None else min_hbm

    # -- the search ----------------------------------------------------

    def search(self) -> ParallelPlan:
        self.searches += 1
        candidates = enumerate_layouts(
            self.n_devices, self.profile, seq=self.seq,
            axes=self.axes, pinned=self.pinned,
        )
        scored = [self.score_candidate(pc) for pc in candidates]
        # Rank: fitting plans first, then predicted step time, then less
        # remat, then a stable layout tiebreak for determinism.
        scored.sort(
            key=lambda s: (
                not s["fits"],
                _round6(s["cost"].step_s) if s["fits"] else _round6(s["hbm_gib"]),
                int(s["remat"]),
                s["microbatches"],
                tuple(sorted(s["layout"].items())),
            )
        )
        chosen, rest = scored[0], scored[1:]
        if not chosen["fits"]:
            logger.warning(
                "planner: NO layout fits %.1f GiB/chip for %s on %d devices — "
                "emitting best-effort plan %s (predicted %.2f GiB, over "
                "budget). Expect OOM; lower per_chip_batch/seq or add chips.",
                self.hbm_gib, self.profile.label, self.n_devices,
                layout_str(chosen["layout"]), chosen["hbm_gib"],
            )
        rejections = []
        for s in rest[: self.max_rejections]:
            if not s["fits"]:
                reason = (
                    f"over_budget: {_round6(s['hbm_gib'])} GiB > "
                    f"{_round6(self.hbm_gib)} GiB at full remat"
                )
            else:
                slower = (s["cost"].step_s / chosen["cost"].step_s - 1.0) * 100
                reason = f"slower: +{_round6(slower)}% predicted step time"
            rejections.append({
                "layout": s["layout"],
                "reason": reason,
                "predicted_step_s": _round6(s["cost"].step_s),
                "predicted_hbm_gib": _round6(s["hbm_gib"]),
                "remat": s["remat"],
                "remat_policy": s["remat_policy"],
                "microbatches": s["microbatches"],
            })
        dropped = len(rest) - len(rejections)
        if dropped > 0:
            rejections.append({
                "layout": None,
                "reason": f"... {dropped} more candidates not logged "
                          f"(max_rejections={self.max_rejections})",
            })
        plan = ParallelPlan(
            version=PLAN_VERSION,
            key=self.cache_key(),
            model=self.profile.label,
            n_devices=self.n_devices,
            seq=self.seq,
            per_chip_batch=self.per_chip_batch,
            optimizer=self.optimizer,
            hbm_gib_budget=_round6(self.hbm_gib),
            layout=chosen["layout"],
            remat=chosen["remat"],
            remat_policy=chosen["remat_policy"],
            microbatches=chosen["microbatches"],
            predicted_step_s=_round6(chosen["cost"].step_s),
            predicted_hbm_gib=_round6(chosen["hbm_gib"]),
            memory_rows={k: _round6(v) for k, v in chosen["memory_rows"].items()},
            breakdown=chosen["cost"].to_dict(),
            bandwidths=self.bandwidths.to_dict(),
            over_budget=not chosen["fits"],
            rejections=rejections,
            profile=self.profile.to_dict(),
            calibration=None,
        )
        return plan

    def resolve(
        self, plans_dir: str, *, use_cache: bool = True
    ) -> tuple[ParallelPlan, str, bool]:
        """Load the cached plan for these inputs or search and write one.
        Returns (plan, path, from_cache)."""
        key = self.cache_key()
        path = os.path.join(plans_dir, f"plan_{key}.json")
        if use_cache and os.path.exists(path):
            try:
                plan = ParallelPlan.load(path)
                if plan.key == key:
                    # Calibrated constants feed back into this planner so a
                    # later forced re-search starts from measured reality.
                    cal = plan.calibration or {}
                    if cal.get("mfu_effective"):
                        self.bandwidths.mfu = float(cal["mfu_effective"])
                    return plan, path, True
                logger.warning(
                    "planner: cached plan %s has stale key %s (inputs "
                    "changed); re-searching.", path, plan.key,
                )
            except PlanVersionError as e:
                logger.warning("planner: %s", e)
            except (OSError, ValueError, KeyError) as e:
                logger.warning(
                    "planner: unreadable cached plan %s (%s); re-searching.",
                    path, e,
                )
        plan = self.search()
        plan.save(path)
        return plan, path, False


# ----------------------------------------------------------------------
# Calibration write-back (telemetry → plan artifact)
# ----------------------------------------------------------------------


def record_calibration(
    path: str,
    *,
    measured_step_s: Optional[float] = None,
    measured_peak_hbm_gib: Optional[float] = None,
    steps: int = 0,
) -> Optional[dict]:
    """Fold measured step time / peak HBM back into the plan artifact at
    ``path``. Each calibrated run increments ``runs`` and EMA-blends the
    measurements; ``mfu_effective`` is the MFU the bandwidth table *should*
    have used for predicted == measured — the constant the next cache-miss
    search starts from. Returns the calibration block (None when the file is
    missing/invalid — calibration must never kill training)."""
    try:
        plan = ParallelPlan.load(path)
    except (OSError, ValueError, KeyError) as e:
        logger.warning("planner: calibration skipped — cannot load %s (%s)", path, e)
        return None
    cal = dict(plan.calibration or {})
    runs = int(cal.get("runs", 0)) + 1
    alpha = 1.0 / runs  # running mean across calibrated runs

    def _blend(key, value):
        if value is None:
            return cal.get(key)
        prev = cal.get(key)
        return value if prev is None else (1 - alpha) * prev + alpha * value

    cal["runs"] = runs
    cal["steps"] = int(cal.get("steps", 0)) + int(steps)
    cal["measured_step_s"] = _blend("measured_step_s", measured_step_s)
    cal["measured_peak_hbm_gib"] = _blend("measured_peak_hbm_gib", measured_peak_hbm_gib)
    if cal.get("measured_step_s") and plan.predicted_step_s:
        ratio = cal["measured_step_s"] / plan.predicted_step_s
        cal["step_time_ratio"] = _round6(ratio)
        mfu = float(plan.bandwidths.get("mfu", BandwidthTable.mfu))
        # measured = predicted · ratio and predicted ∝ 1/mfu on the compute
        # term, so the mfu that would have nailed it is mfu/ratio (clamped).
        cal["mfu_effective"] = _round6(min(1.0, max(1e-3, mfu / ratio)))
    if cal.get("measured_peak_hbm_gib") and plan.predicted_hbm_gib:
        cal["hbm_ratio"] = _round6(
            cal["measured_peak_hbm_gib"] / plan.predicted_hbm_gib
        )
    for k in ("measured_step_s", "measured_peak_hbm_gib"):
        if isinstance(cal.get(k), float):
            cal[k] = _round6(cal[k])
    plan.calibration = cal
    try:
        plan.save(path)
    except OSError as e:
        logger.warning("planner: calibration write-back to %s failed: %s", path, e)
        return None
    return cal


# ----------------------------------------------------------------------
# Disaggregated-serving slice sizing (disagg.py)
# ----------------------------------------------------------------------


@dataclasses.dataclass
class DisaggSlicePlan:
    """Planner-sized prefill/decode split for disaggregated serving
    (disagg.py). The same makespan logic as the training cost model, one
    level up: prefill and decode are two heterogeneous programs whose FLOP
    shares are known, so the device set is partitioned to balance them —
    and the KV-page handoff the split creates is priced against the
    BandwidthTable so the artifact records what the link will carry."""

    n_devices: int
    n_prefill: int
    n_decode: int
    flop_ratio: float           # prefill FLOPs : decode FLOPs (per request)
    bottleneck: str             # "prefill" | "decode" | "balanced"
    predicted_speedup: float    # colocated makespan / disagg makespan
    handoff_gbps: float         # effective prefill→decode link bandwidth
    kv_bytes_per_token: int     # one token's K+V pages across all layers
    handoff_s_per_ktoken: float  # predicted handoff seconds per 1k prompt tokens

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        return {
            k: (_round6(v) if isinstance(v, float) else v)
            for k, v in sorted(d.items())
        }


def kv_bytes_per_token(cfg, dtype=None) -> int:
    """Bytes one prompt token's committed K and V occupy across every layer
    — the unit the handoff link is priced in, as the cache itself counts it
    (an int8 cache's scales included), so the handoff is priced on what
    actually moves."""
    from . import kv_cache

    return kv_cache.kv_bytes_per_token(cfg, dtype)


def plan_disagg_slices(
    n_devices: int,
    *,
    prefill_decode_flop_ratio: float,
    bw: Optional[BandwidthTable] = None,
    kv_bytes_per_token: int = 0,
    n_prefill: Optional[int] = None,
) -> DisaggSlicePlan:
    """Partition ``n_devices`` into a prefill slice and a decode slice.

    ``prefill_decode_flop_ratio`` is the measured (or expected) ratio of
    prefill FLOPs to decode FLOPs per request — for a dense causal LM both
    phases cost ~2·P FLOPs/token, so the ratio reduces to
    ``mean_prompt_tokens / mean_new_tokens``. The split minimizes the
    two-phase makespan ``max(ratio / n_p, 1 / n_d)`` (work over devices,
    phases overlapped across requests); ties break toward MORE decode
    devices because decode is the latency-critical, occupancy-bound phase.
    ``n_prefill`` pins the prefill slice size (clamped to [1, n-1]) and
    skips the search.

    The returned plan also prices the handoff the split creates:
    ``handoff_gbps`` from the BandwidthTable's link model (ICI inside one
    domain, DCN across) and ``handoff_s_per_ktoken`` for
    ``kv_bytes_per_token`` (see :func:`kv_bytes_per_token`).
    """
    n = int(n_devices)
    if n < 2:
        raise PlannerError(
            f"disaggregation needs >= 2 devices to split, got {n}"
        )
    r = float(prefill_decode_flop_ratio)
    if not (r > 0):
        raise PlannerError(
            f"prefill_decode_flop_ratio must be > 0, got {prefill_decode_flop_ratio}"
        )
    bw = bw or BandwidthTable()

    def makespan(p: int) -> float:
        return max(r / p, 1.0 / (n - p))

    if n_prefill is not None:
        p_best = min(max(1, int(n_prefill)), n - 1)
    else:
        # Smallest p minimizing the makespan: scanning upward and keeping
        # strict improvement biases ties toward more decode devices.
        p_best, best = 1, makespan(1)
        for p in range(2, n):
            m = makespan(p)
            if m < best - 1e-12:
                p_best, best = p, m
    span = makespan(p_best)
    colocated = (r + 1.0) / n  # both phases time-sliced over every device
    gbps = bw.handoff_gbps(n)
    per_ktoken = (
        1000.0 * kv_bytes_per_token / (gbps * 1e9) if kv_bytes_per_token else 0.0
    )
    prefill_span, decode_span = r / p_best, 1.0 / (n - p_best)
    if abs(prefill_span - decode_span) <= 0.05 * span:
        bottleneck = "balanced"
    else:
        bottleneck = "prefill" if prefill_span > decode_span else "decode"
    return DisaggSlicePlan(
        n_devices=n,
        n_prefill=p_best,
        n_decode=n - p_best,
        flop_ratio=_round6(r),
        bottleneck=bottleneck,
        predicted_speedup=_round6(colocated / span),
        handoff_gbps=_round6(gbps),
        kv_bytes_per_token=int(kv_bytes_per_token),
        handoff_s_per_ktoken=_round6(per_ktoken),
    )
