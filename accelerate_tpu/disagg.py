"""Disaggregated serving (layer L7 — inference serving, two meshes).

The colocated :class:`~accelerate_tpu.serving.ServingEngine` already gets a
dense per-slot KV cache, chunked prefill, and a zero-recompile decode step —
but prefill and decode still share one device queue, so a long prompt burst
stalls every in-flight decode and p95 TTFT spikes under open-loop load.
This module is the DistServe/Splitwise-class fix, planner-shaped: partition
the device set into a **prefill mesh** and a **decode mesh**, sized by
:func:`~accelerate_tpu.planner.plan_disagg_slices` from the prefill:decode
FLOP ratio, and stream each committed KV page across as a device-to-device
transfer the moment its chunk lands.

Architecture (MPMD one level up from arXiv:2412.14374's pipeline stages —
two heterogeneous programs on disjoint device groups, a typed data plane
between them):

- **Prefill lanes** — each lane owns a private ``(L, 1, T_max, Hkv, D)``
  slot cache pinned to one prefill device (round-robin over the slice) and
  runs the SAME jitted prefill program as the colocated engine on it.
  Identical program + identical inputs ⇒ the lane's KV values are
  bit-equal to what an in-place prefill would have written.
- **Streamed KV-page handoff** — after each chunk the lane's freshly
  written page is sliced out and shipped to the decode placement with an
  async ``jax.device_put``; the insert into the decode-side slot cache is
  deferred behind a depth-``handoff_depth`` queue (the double buffer), so
  a page's transfer overlaps the lane's NEXT chunk. The final chunk
  flushes the queue and arms the slot, so decode never observes a
  half-streamed prompt.
- **Two-mesh router** — ``_admit`` grants a request a decode slot AND a
  prefill lane; ``tick()`` advances every lane one chunk (lanes run
  concurrently on their own devices) and then runs the unmodified decode
  step on the decode mesh. The decode program, its donation pattern, and
  its one-executable steady state are untouched — the router only changes
  WHERE cache pages come from, never what they contain.

Bit-equality with the single-mesh engine (pinned by tests/test_disagg.py):
pages are copied pad-tail and all, attention is bounded at each row's true
length, and every request samples from its own PRNG stream — so neither
the transfer nor the two-mesh tick interleaving can change any token.

CPU tier-1 story: force a multi-device host platform
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and the same code
splits the 8 "devices" into disjoint slices — the transfers are real
cross-device copies, just over host memory.

Usage::

    from accelerate_tpu import DisaggConfig, DisaggServingEngine

    engine = DisaggServingEngine(
        model, ServingConfig(n_slots=8, eos_token_id=2),
        disagg=DisaggConfig(n_prefill_lanes=2),
    )
    outs = engine.run(prompts, max_new_tokens=64)   # same API, same tokens
    engine.stats()["disagg"]                        # slices + handoff costs
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from .chaos import InjectedFaultError, deterministic_jitter
from .kv_cache import KVCache, QuantPages, init_slot_cache, slots_partition
from .logging import get_logger
from .planner import (BandwidthTable, PlannerError, kv_bytes_per_token,
                      plan_disagg_slices)
from .resharding import ReshardExecutor
from .serving import (TICK, ServingEngine, SlotState, _cache_size, _release_step,
                      _template_of, init_slot_state, plan_chunks)

logger = get_logger(__name__)


def _log_ok() -> bool:
    """The repo logger needs accelerate state; the engine must also work
    standalone (no Accelerator), where init-time logs are just skipped."""
    from .state import PartialState

    return bool(PartialState._shared_state)


@dataclass
class _Lane:
    """One prefill workspace: a single-slot cache + state pinned to one
    prefill device. A lane prefills one request at a time; ``cache`` and
    ``state`` are rebound to the jitted program's (donated) outputs every
    chunk, so the arrays live on ``device`` for the lane's lifetime."""

    index: int
    device: Any
    params: Any
    cache: KVCache
    state: SlotState


@dataclass
class _Handoff:
    """One committed KV page in flight to the decode mesh."""

    slot: int
    start: int            # write offset in the decode-side cache
    valid: int            # real prompt tokens in the page (rest is pad tail)
    pages: tuple          # (k_page, v_page) already device_put to decode
    nbytes: int
    arm: Optional[tuple] = None   # (tok, done0, rng_carry) on the final chunk
    budget: int = 0
    t0: Optional[float] = None    # perf_counter at dispatch when sampled
    ready_tick: int = 0   # straggler model: background drains wait for this
                          # tick; forced drains (depth overflow, final flush)
                          # await the transfer and proceed
    rid: int = -1                 # owning request (trace span tree key)
    trace_flow: Optional[int] = None  # Chrome-trace flow id: stitches this
                                      # page's lane-side dispatch to its
                                      # decode-slot insert


@dataclass
class _DrainingLayout:
    """A retired topology still finishing its in-flight decodes after a live
    resize. The old decode cache/state and every param version it might
    reference stay bound here (and ONLY here) until ``decoding`` empties —
    then the whole layout drops and its buffers go with it. Draining slots
    index THIS layout's state, never the active free list."""

    layout_id: int
    cache: KVCache
    state: SlotState
    params_by_version: dict
    decoding: dict          # slot -> request, frozen membership, drains down
    trace_span: Optional[int] = None  # open "drain" span handle (tracing.py)


class DisaggServingEngine(ServingEngine):
    """Two-mesh router over the continuous-batching engine: chunked prefill
    on a planner-sized prefill slice, the zero-recompile decode step on the
    complementary decode slice, committed KV pages streamed between them.

    Same front-end API as :class:`~accelerate_tpu.serving.ServingEngine`
    (``submit/tick/poll/run``) and token-for-token the same outputs; the
    extra ``disagg`` kwarg (a :class:`~accelerate_tpu.utils.DisaggConfig`)
    and the ``devices`` override (default: ``jax.devices()``) control the
    split. ``stats()`` gains a ``"disagg"`` block: the slice plan, handoff
    bytes/latency, and measured FLOP ratio for re-planning.
    """

    _fuses_qkv = False  # the decode mesh and every lane hold the model's layout

    def __init__(self, model, config=None, *, disagg=None, devices=None,
                 forward_cached=None, compile_manager=None, telemetry=None,
                 fault_tolerance=None, chaos=None, tracing=None, journal=None,
                 profiler=None):
        from .utils.dataclasses import DisaggConfig

        self.disagg_config = disagg if disagg is not None else DisaggConfig()
        devs = list(devices) if devices is not None else list(jax.devices())
        if len(devs) < 2:
            raise ValueError(
                f"disaggregation needs >= 2 devices to split into a prefill "
                f"and a decode mesh, got {len(devs)}; on CPU force a "
                "multi-device host platform with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N"
            )
        super().__init__(model, config, forward_cached=forward_cached,
                         compile_manager=compile_manager, telemetry=telemetry,
                         fault_tolerance=fault_tolerance, chaos=chaos,
                         tracing=tracing, journal=journal, profiler=profiler)
        # a prompt chunk runs on its lane's mesh, never inside the decode step
        self._decode_chunk = None
        dc = self.disagg_config
        # Degradation state: quarantined lanes leave the pool for good; once
        # EVERY lane is gone the engine latches degraded and prefills
        # colocated on the decode mesh (correct, slower — traffic survives).
        self._quarantined_lanes: set[int] = set()
        self._degraded = False
        # Live-resize state (autoscale.py drives this): the ordered device
        # set the engine currently runs on, retired layouts still draining
        # their in-flight decodes, and the resize telemetry counters.
        self._devices = devs
        self._resize_seq = 0
        self._draining_layouts: list[_DrainingLayout] = []
        self._rstats = {
            "resizes": 0, "resize_aborts": 0, "resize_retries": 0,
            "resize_delays": 0, "drained_layouts": 0, "rebound_requests": 0,
            "retried_decodes": 0, "moved_bytes": 0, "transfer_wall_s": 0.0,
        }

        # -- slice sizing (planner cost model) -----------------------------
        ratio = dc.prefill_decode_flop_ratio
        if ratio is None:
            expected = (dc.expected_prompt_tokens
                        if dc.expected_prompt_tokens is not None
                        else max(1.0, self.t_max / 2.0))
            ratio = expected / max(1, int(self.config.max_new_tokens))
        kvb = kv_bytes_per_token(self.cfg, dtype=self._cache.dtype)
        self.slice_plan = plan_disagg_slices(
            len(devs), prefill_decode_flop_ratio=ratio,
            bw=BandwidthTable.from_dict(dc.bandwidths),
            kv_bytes_per_token=kvb, n_prefill=dc.n_prefill_devices,
        )
        # The decode slice is also what the SDC decode canary (sdc.py)
        # convicts on a bit-wise output mismatch: decode_devices[0] is the
        # quarantine target handed to the autoscaler's mark_device_dead.
        self.prefill_devices = devs[:self.slice_plan.n_prefill]
        self.decode_devices = devs[self.slice_plan.n_prefill:]

        # -- decode mesh ---------------------------------------------------
        # jit caches one executable PER PLACEMENT, so the one-executable
        # decode invariant requires a FIXED decode placement. Default: the
        # decode slice's first device hosts the slot cache (the census then
        # reads exactly 1). Opt-in (shard_decode_slots): slots sharded over
        # the decode slice — same single compiled program, but typed
        # PRNG-key arrays under a multi-device NamedSharding occupy two
        # dispatch-cache entries per program (one backend compile, two
        # entries: checked on jax 0.9.0), so init pre-warms both and the
        # census reads a flat 2.
        (self._decode_mesh, cache_s, vec_s,
         self._decode_sharding) = self._decode_placement(self.decode_devices)
        self._cache = jax.device_put(
            self._cache, KVCache(cache_s, cache_s, vec_s))
        self._state = jax.device_put(
            self._state, SlotState(*([vec_s] * len(SlotState._fields))))
        self._params_decode = jax.device_put(model.params, self._decode_sharding)
        self._params = self._params_decode  # what the decode hook dispatches
        self._params_template = _template_of(self._params_decode)
        # Version 0's buffers are the decode-mesh copy, not the model's own
        # placement — keep the publication double-buffer consistent with
        # what the dispatch hooks actually feed the programs.
        self._params_by_version[0] = self._params_decode

        # -- prefill lanes -------------------------------------------------
        params_by_dev: dict = {}
        self._lanes: list[_Lane] = []
        for i in range(int(dc.n_prefill_lanes)):
            dev = self.prefill_devices[i % len(self.prefill_devices)]
            if dev not in params_by_dev:
                params_by_dev[dev] = jax.device_put(model.params, dev)
            self._lanes.append(_Lane(
                index=i, device=dev, params=params_by_dev[dev],
                cache=jax.device_put(
                    init_slot_cache(self.cfg, 1, self.t_max,
                                    dtype=self.config.cache_dtype), dev),
                state=jax.device_put(
                    init_slot_state(1, seed=self.config.seed,
                                    history=self._spec_ngram), dev),
            ))
        # FIFO lane reuse: grants take the least-recently-freed lane, so a
        # request wave strides across every lane (and warmup covers each
        # lane's device with every ladder rung).
        self._free_lanes: deque[_Lane] = deque(self._lanes)
        # Published versions carry per-prefill-device param copies too (one
        # per unique lane device, like construction): version -> dev -> tree.
        self._lane_params: dict[int, dict] = {0: params_by_dev}

        # -- the data plane ------------------------------------------------
        self._handoffs: deque[_Handoff] = deque()
        self._handoff_lat_s: list[float] = []
        self._hstats = {"transfers": 0, "bytes": 0, "inserts": 0,
                        "flushes": 0, "lane_chunks": 0}

        # Page extract: the lane's freshly written rows out of its one-slot
        # cache. One executable per ladder rung.
        self._extract = jax.jit(KVCache.rows, static_argnums=(2,))

        # Page insert: write a transferred page into the decode-side slot
        # cache at the request's own offset, and commit its true length.
        self._insert = jax.jit(KVCache.insert_rows, donate_argnums=(0,))

        # Slot arming: once the final page has landed, publish the prefill
        # step's terminal state for this slot — exactly the fields the
        # colocated prefill's final chunk writes (garbage written by
        # intermediate chunks is unobservable there too: active stays
        # False until this moment).
        def _arm(state: SlotState, slot, tok, done0, budget, carry, hist):
            return SlotState(
                last_token=state.last_token.at[slot].set(tok),
                active=state.active.at[slot].set(True),
                done=state.done.at[slot].set(done0),
                generated=state.generated.at[slot].set(1),
                budget=state.budget.at[slot].set(budget),
                rng=state.rng.at[slot].set(carry),
                history=state.history.at[slot].set(hist),
            )

        self._arm = jax.jit(_arm, donate_argnums=(0,))

        if self._decode_mesh is not None:
            # Pre-warm BOTH dispatch-cache entries the typed-key NamedSharding
            # path occupies (one compiled program either way — see the
            # shard_decode_slots note in DisaggConfig), so the steady-state
            # census is flat from the first real tick. Safe for bit-equality:
            # every slot is inactive, garbage KV lands below future inserts
            # and past true lengths (never attended), and idle slots' rng
            # streams are dead until _arm rewrites them.
            for _ in range(4):
                # No live rows: lengths pass through unchanged, k/v garbage
                # lands where inserts overwrite or attention never reaches.
                self._cache, self._state, *_ = self._decode(
                    self._params, self._cache, self._state, self._full_mask)

        if _log_ok():
            logger.info(
                "disagg: %d devices -> %d prefill / %d decode (ratio %.3g, "
                "bottleneck %s, predicted speedup %.3gx), %d lane(s), "
                "handoff %.3g GB/s",
                self.slice_plan.n_devices, self.slice_plan.n_prefill,
                self.slice_plan.n_decode, self.slice_plan.flop_ratio,
                self.slice_plan.bottleneck, self.slice_plan.predicted_speedup,
                len(self._lanes), self.slice_plan.handoff_gbps,
            )

    def _decode_placement(self, decode_devices) -> tuple:
        """``(mesh, cache_sharding, vec_sharding, scalar_sharding)`` for a
        decode slice — shared by construction and the live resize so both
        layouts obey the same one-executable placement rules."""
        dc = self.disagg_config
        n_d = len(decode_devices)
        if dc.shard_decode_slots and n_d > 1 and self.n_slots % n_d == 0:
            mesh = Mesh(np.asarray(decode_devices), ("slots",))
            return (mesh, NamedSharding(mesh, slots_partition("slots")),
                    NamedSharding(mesh, P("slots")), NamedSharding(mesh, P()))
        if dc.shard_decode_slots and _log_ok():
            logger.warning_once(
                "disagg: shard_decode_slots needs n_slots (%d) divisible "
                "by the decode slice (%d devices); falling back to "
                "single-device decode placement.", self.n_slots, n_d,
            )
        single = SingleDeviceSharding(decode_devices[0])
        return None, single, single, single

    # -- router scheduling -------------------------------------------------

    def tick(self) -> None:
        """One router round: sweep deadlines/preemption, admit into free
        slots (same policy as the colocated engine — lanes never gate
        admission, only prefill concurrency), drain pages whose transfer had
        a full tick to fly, advance EVERY lane-holding request one chunk
        (disjoint devices — the chunks run concurrently), then one decode
        step on the decode mesh. Degraded mode (every lane quarantined)
        prefills head-of-line colocated on the decode mesh instead."""
        with self._phase(TICK):
            # serving.admit takes in the router-only steps: handoff drain
            # and lane assignment.
            with self._phase("serving.admit"):
                snap = self._begin_tick()
                self._admit()
                self._sample_queue_depth()
                self._drain_handoffs()
                if not self._degraded:
                    self._assign_lanes()
            for _ in range(max(1, int(self.config.prefill_chunks_per_tick))):
                if self._degraded:
                    # Colocated fallback: the base head-of-line discipline,
                    # the base dispatch path (lane is None routes there).
                    if not self._prefilling:
                        break
                    self._prefill_one(self._prefilling[0])
                else:
                    runnable = [r for r in self._prefilling
                                if r.lane is not None]
                    if not runnable:
                        break
                    for req in runnable:
                        self._prefill_one(req)
            if self._decoding:
                self._decode_tick()
            self._drain_decode_tick()
            with self._phase("serving.end_tick"):
                self._end_tick(snap)

    def _assign_lanes(self) -> None:
        """Hand free lanes to lane-less prefilling requests, health-checking
        each lane at grant time (the ``lane_health`` injection point — a
        dead lane is quarantined before it ever touches a request)."""
        for req in list(self._prefilling):
            if req.lane is not None:
                continue
            while self._free_lanes and req.lane is None:
                lane = self._free_lanes.popleft()
                if self.chaos is not None:
                    fault = self.chaos.draw("lane_health",
                                            self._stats["ticks"],
                                            unit=lane.index)
                    if fault is not None and fault.kind == "dead_lane":
                        self._quarantine_lane(lane, "failed health check")
                        continue
                req.lane = lane
            if req.lane is None:  # no healthy free lane left this tick
                break

    # -- prefill mesh + handoff --------------------------------------------

    def _prefill_dispatch(self, req, chunk, valid: int,
                          is_first: bool, is_final: bool):
        """Run the chunk on the request's lane (prefill mesh), then stream
        the committed page to the decode placement. The device_put is
        async: the copy overlaps the lane's next chunk, and the insert is
        deferred behind the handoff queue until it has had time to land.

        A lane-less request (degraded mode — every lane quarantined) routes
        to the base colocated dispatch: same prefill program on the decode
        placement, writing the decode-side cache directly. No handoff, no
        arm; the decode step and its ONE executable never notice."""
        if req.lane is None:
            return super()._prefill_dispatch(req, chunk, valid, is_first,
                                             is_final)
        lane = req.lane
        dc = self.disagg_config
        start = req.consumed  # host-tracked — lane slot 0 IS this request
        lane.cache, lane.state, tok, done0 = self._prefill(
            self._lane_params[req.weights_version][lane.device],
            lane.cache, lane.state, chunk,
            np.int32(0), np.int32(valid), np.int32(req.budget),
            req.rng, is_first, is_final,
        )
        self._hstats["lane_chunks"] += 1

        size = int(chunk.shape[1])
        pages = self._extract(lane.cache, np.int32(start), size)
        self._hstats["transfers"] += 1
        t0 = None
        if self._hstats["transfers"] % dc.handoff_sample_every == 0:
            # Sampled end-to-end handoff timing: settle the source page so
            # the clock starts at transfer dispatch, not at lane compute.
            jax.block_until_ready(pages)
            t0 = time.perf_counter()
        tr = self.tracing
        th0 = time.perf_counter() if tr is not None else None
        pages_d, delay_ticks = self._handoff_put(req, lane, pages)
        nbytes = int(pages[0].nbytes + pages[1].nbytes)
        self._hstats["bytes"] += nbytes

        arm = None
        if is_final:
            # The decode-side slot inherits the lane's terminal per-request
            # state: first token, done flag, and the rng carry the final
            # prefill chunk advanced to — decode then continues the SAME
            # per-request stream the colocated engine would.
            arm = jax.device_put(
                (tok, done0, lane.state.rng[0], lane.state.history[0]),
                self._decode_sharding)
        self._handoffs.append(_Handoff(
            slot=req.slot, start=start, valid=int(valid), pages=pages_d,
            nbytes=nbytes, arm=arm, budget=int(req.budget), t0=t0,
            ready_tick=self._stats["ticks"] + delay_ticks, rid=req.id,
        ))
        if tr is not None:
            # Flow id stitches this page's lane-side span to the decode-slot
            # insert in the Chrome export; set before any forced drain below
            # can pop the handoff back off.
            self._handoffs[-1].trace_flow = tr.handoff(
                req.id, self._stats["ticks"], th0, time.perf_counter(),
                lane=lane.index, slot=req.slot, nbytes=nbytes, final=is_final)
        if is_final:
            # Flush before decode can observe the slot, and release the
            # lane — its buffers are donated to the next occupant's first
            # chunk (XLA keeps pending readers safe).
            tf0 = time.perf_counter() if tr is not None else None
            self._drain_handoffs(drain_all=True)
            if tr is not None:
                tr.handoff_flush(req.id, self._stats["ticks"], tf0,
                                 time.perf_counter())
            self._hstats["flushes"] += 1
            self._free_lanes.append(lane)
            req.lane = None
        else:
            while len(self._handoffs) > dc.handoff_depth:
                self._drain_one()
        return tok, done0

    def _handoff_put(self, req, lane: _Lane, pages) -> tuple:
        """The guarded transfer: one chaos draw at ``handoff_device_put``,
        then the device_put with up to ``handoff_retries`` capped
        jitter-backoff retries. A transient injected transfer error
        (``fault.u < 0.75``) fails exactly one attempt; a persistent one (or
        a real failure that survives every retry) quarantines the lane and
        re-raises — the base recovery path then re-queues the request for
        an idempotent re-prefill. Returns ``(pages_on_decode,
        delay_ticks)`` where ``delay_ticks`` models a straggler transfer."""
        dc = self.disagg_config
        fault = None
        if self.chaos is not None:
            fault = self.chaos.draw("handoff_device_put",
                                    self._stats["ticks"], unit=req.id)
        delay_ticks = 0
        if fault is not None and fault.kind == "delay":
            self._fstats["handoff_delays"] += 1
            delay_ticks = int(self.chaos.delay_ticks)
            fault = None
        poison = fault is not None and fault.kind == "poison"
        attempts = int(dc.handoff_retries) + 1
        for attempt in range(attempts):
            try:
                if (fault is not None and fault.kind == "transfer_error"
                        and (attempt == 0 or fault.u >= 0.75)):
                    raise InjectedFaultError(fault)
                pages_d = jax.device_put(pages, self._decode_sharding)
                break
            except RuntimeError as e:
                if attempt == attempts - 1:
                    self._quarantine_lane(
                        lane, f"handoff failed {attempts}x: {e}")
                    raise
                self._fstats["handoff_retries"] += 1
                backoff = min(
                    float(dc.handoff_backoff_s) * (2 ** attempt),
                    float(dc.handoff_backoff_cap_s),
                ) * deterministic_jitter(
                    self.chaos.seed if self.chaos is not None else 0,
                    self._stats["ticks"], attempt,
                )
                if self.tracing is not None:
                    tb0 = time.perf_counter()
                    if backoff > 0:
                        time.sleep(backoff)
                    # The measured sleep wall (not the computed value) feeds
                    # explain()'s backoff term so it telescopes exactly.
                    self.tracing.handoff_retry(
                        req.id, self._stats["ticks"], attempt=attempt,
                        backoff_s=backoff, lane=lane.index,
                        measured_s=time.perf_counter() - tb0)
                elif backoff > 0:
                    time.sleep(backoff)
        if poison and self._cache.holds_nan:
            # Poisoned page: what lands on the decode mesh is all-NaN. The
            # decode-side nonfinite-logits sentinel must catch it once the
            # slot arms — pinned by tests and the chaos smoke.
            pages_d = jax.device_put(
                (jnp.full_like(pages[0], jnp.nan),
                 jnp.full_like(pages[1], jnp.nan)),
                self._decode_sharding,
            )
        if self._cache.quantized and self.chaos is not None:
            dq = self.chaos.draw("page_dequant", self._stats["ticks"],
                                 unit=req.id)
            if dq is not None and dq.kind == "poison":
                # Quantized twin of the float poison: int8 payloads are
                # always finite, so corrupt the dequant SCALES — attention's
                # in-kernel dequantize then propagates NaN and the same
                # nonfinite-logits sentinel convicts the slot.
                pages_d = jax.device_put(
                    tuple(QuantPages(p.data, jnp.full_like(p.scale, jnp.nan))
                          for p in pages),
                    self._decode_sharding,
                )
        return pages_d, delay_ticks

    def _drain_handoffs(self, drain_all: bool = False) -> None:
        if drain_all:
            while self._handoffs:
                self._drain_one()
        else:
            # Pages queued on earlier ticks have had >= 1 tick of transfer
            # time; keep at most the configured double buffer in flight. A
            # straggler head (ready_tick in the future) blocks background
            # draining — FIFO order is what keeps per-slot lengths
            # monotone — until a forced drain awaits it.
            while (len(self._handoffs) > self.disagg_config.handoff_depth
                   and self._handoffs[0].ready_tick <= self._stats["ticks"]):
                self._drain_one()

    def _purge_slot(self, slot: int) -> None:
        """Drop every in-flight handoff targeting ``slot`` (its request was
        evicted or is being retried) so a stale page can never land in the
        slot's next grant."""
        keep = deque(h for h in self._handoffs if h.slot != slot)
        dropped = len(self._handoffs) - len(keep)
        if dropped:
            self._handoffs = keep
            if _log_ok():
                logger.warning(
                    "disagg: purged %d in-flight handoff page(s) for slot %d",
                    dropped, slot,
                )

    def _release_lane(self, req, failed: bool = False) -> None:
        """Return the request's lane to the free pool — unless it was
        quarantined by the failure that got us here, in which case it stays
        out of rotation."""
        lane, req.lane = req.lane, None
        if lane is None or lane.index in self._quarantined_lanes:
            return
        self._free_lanes.append(lane)

    def _quarantine_lane(self, lane: _Lane, reason: str) -> None:
        if lane.index in self._quarantined_lanes:
            return
        self._quarantined_lanes.add(lane.index)
        self._fstats["lane_quarantines"] += 1
        if self.tracing is not None:
            self.tracing.quarantine("lane", lane.index, self._stats["ticks"],
                                    reason=reason)
        try:
            self._free_lanes.remove(lane)
        except ValueError:
            pass  # held by a request; _release_lane won't re-pool it
        healthy = len(self._lanes) - len(self._quarantined_lanes)
        if _log_ok():
            logger.warning(
                "disagg: quarantined prefill lane %d on %s (%s); %d/%d "
                "lane(s) remain", lane.index, lane.device, reason, healthy,
                len(self._lanes),
            )
        if self.telemetry is not None:
            self.telemetry.record_event(
                "serving_lane_quarantined", lane=lane.index, reason=reason,
            )
        if healthy == 0 and not self._degraded:
            self._degraded = True
            if _log_ok():
                logger.warning_once(
                    "disagg: every prefill lane is quarantined — degrading "
                    "to colocated prefill on the decode mesh (correct but "
                    "slower; p95 TTFT will rise). Restart the engine to "
                    "restore the prefill slice."
                )
            if self.telemetry is not None:
                self.telemetry.record_event("serving_degraded")

    def _drain_one(self) -> None:
        h = self._handoffs.popleft()
        k_page, v_page = h.pages
        self._cache = self._insert(
            self._cache, k_page, v_page,
            np.int32(h.slot), np.int32(h.start), np.int32(h.valid),
        )
        self._hstats["inserts"] += 1
        if h.arm is not None:
            tok, done0, carry, hist = h.arm
            self._state = self._arm(
                self._state, np.int32(h.slot), tok, done0,
                np.int32(h.budget), carry, hist,
            )
        if h.t0 is not None:
            jax.block_until_ready(k_page)
            self._handoff_lat_s.append(time.perf_counter() - h.t0)
        if self.tracing is not None:
            self.tracing.handoff_insert(
                self._stats["ticks"], slot=h.slot, flow=h.trace_flow,
                request_id=(h.rid if h.rid >= 0 else None),
                armed=h.arm is not None)

    # -- live resize (the autoscale.py actuator) ---------------------------

    def resize(self, devices=None, *, n_prefill=None, flop_ratio=None,
               dead_devices=()) -> dict:
        """Live re-split / grow / shrink with zero downtime: build the whole
        target layout (plan, decode placement, param copies for EVERY
        installed version, lanes, pre-warmed executables) BEFORE touching
        live state, then commit in one host-side swap. In-flight decodes
        keep draining on the old layout (:class:`_DrainingLayout`);
        mid-prefill requests re-queue at the head WITHOUT spending a retry
        (their per-request rng replays bit-equal); new admissions bind the
        new layout. A failure anywhere before the commit — planner refusal,
        an injected/real ``resize_transfer`` error surviving the
        ``handoff_retries`` budget — aborts with the old layout untouched
        and nothing half-bound.

        ``devices`` defaults to the current set minus ``dead_devices``;
        ``flop_ratio`` (the observed prompt:decode ratio) re-runs the
        planner split; ``n_prefill`` pins it. Returns a record dict
        (``{"ok": bool, ...}``) that also lands in telemetry."""
        dc = self.disagg_config
        dead = set(dead_devices)
        devs = (list(devices) if devices is not None
                else [d for d in self._devices if d not in dead])
        seq = self._resize_seq
        self._resize_seq += 1
        old_n = len(self._devices)
        tr = self.tracing
        h_resize = (tr.begin("resize", f"resize[{seq}]", self._stats["ticks"],
                             seq=seq, old_devices=old_n,
                             new_devices=len(devs))
                    if tr is not None else None)

        def abort(reason: str) -> dict:
            self._rstats["resize_aborts"] += 1
            if tr is not None:
                # Ending the outer span force-closes whichever phase span
                # (plan/build) was open when the failure hit.
                tr.end(h_resize, self._stats["ticks"], ok=False,
                       reason=reason)
            if _log_ok():
                logger.warning(
                    "disagg: resize %d -> %d devices ABORTED (%s) — old "
                    "layout keeps serving", old_n, len(devs), reason,
                )
            rec = {"ok": False, "seq": seq, "reason": reason,
                   "n_devices": len(devs), "layout_id": self._active_layout_id}
            if self.telemetry is not None:
                try:
                    self.telemetry.record_event(
                        "serving_resize_aborted", seq=seq, reason=reason,
                        n_devices=len(devs))
                except Exception:
                    pass
            return rec

        # -- validate + plan (nothing live touched yet) --------------------
        h_plan = (tr.begin("resize", "plan", self._stats["ticks"])
                  if tr is not None else None)
        if any(d in dead for d in devs):
            return abort("target includes a dead device")
        if len(devs) < 2:
            return abort(f"needs >= 2 devices, got {len(devs)}")
        ratio = (float(flop_ratio) if flop_ratio is not None
                 else float(self.slice_plan.flop_ratio))
        try:
            kvb = kv_bytes_per_token(self.cfg, dtype=self._cache.dtype)
            plan = plan_disagg_slices(
                len(devs), prefill_decode_flop_ratio=ratio,
                bw=BandwidthTable.from_dict(dc.bandwidths),
                kv_bytes_per_token=kvb, n_prefill=n_prefill,
            )
        except PlannerError as e:
            return abort(f"planner refused: {e}")

        new_prefill = devs[:plan.n_prefill]
        new_decode = devs[plan.n_prefill:]
        mesh, cache_s, vec_s, dsh = self._decode_placement(new_decode)
        if tr is not None:
            tr.end(h_plan, self._stats["ticks"], n_prefill=plan.n_prefill,
                   n_decode=plan.n_decode)
            h_build = tr.begin("resize", "build", self._stats["ticks"])

        # -- param redistribution across the topology gap ------------------
        # The reshard executor prices and batches the copies; donate=False
        # keeps the OLD layout's buffers alive for its draining requests.
        # One chaos draw per resize at ``resize_transfer`` (tick = seq), the
        # same transient-vs-persistent retry convention as the handoff path.
        fault = None
        if self.chaos is not None:
            fault = self.chaos.draw("resize_transfer", seq, unit=0)
        if fault is not None and fault.kind == "delay":
            self._rstats["resize_delays"] += 1
            time.sleep(min(float(dc.handoff_backoff_cap_s),
                           float(dc.handoff_backoff_s)
                           * int(self.chaos.delay_ticks)))
            fault = None
        executor = ReshardExecutor(Mesh(np.asarray(new_decode), ("decode",)))
        t0 = time.perf_counter()
        new_params_by_version = None
        attempts = int(dc.handoff_retries) + 1
        for attempt in range(attempts):
            try:
                if (fault is not None and fault.kind == "transfer_error"
                        and (attempt == 0 or fault.u >= 0.75)):
                    raise InjectedFaultError(fault)
                new_params_by_version = {
                    v: executor.put_tree(
                        p, jax.tree_util.tree_map(lambda _: dsh, p),
                        donate=False)
                    for v, p in self._params_by_version.items()
                }
                break
            except RuntimeError as e:
                if attempt == attempts - 1:
                    return abort(f"param transfer failed {attempts}x: {e}")
                self._rstats["resize_retries"] += 1
                backoff = min(
                    float(dc.handoff_backoff_s) * (2 ** attempt),
                    float(dc.handoff_backoff_cap_s),
                ) * deterministic_jitter(
                    self.chaos.seed if self.chaos is not None else 0,
                    seq, attempt,
                )
                if backoff > 0:
                    time.sleep(backoff)
        ex_stats = executor.stats()
        self._rstats["moved_bytes"] += int(ex_stats["bytes"])
        self._rstats["transfer_wall_s"] += time.perf_counter() - t0

        # -- build the rest of the target layout ---------------------------
        new_cache = jax.device_put(
            init_slot_cache(self.cfg, self.n_slots, self.t_max,
                            dtype=self.config.cache_dtype),
            KVCache(cache_s, cache_s, vec_s))
        new_state = jax.device_put(
            init_slot_state(self.n_slots, seed=self.config.seed,
                            history=self._spec_ngram),
            SlotState(*([vec_s] * len(SlotState._fields))))
        new_lane_params: dict[int, dict] = {}
        for v, p in new_params_by_version.items():
            by_dev: dict = {}
            for i in range(int(dc.n_prefill_lanes)):
                dev = new_prefill[i % len(new_prefill)]
                if dev not in by_dev:
                    by_dev[dev] = jax.device_put(p, dev)
            new_lane_params[v] = by_dev
        primary_lane_params = new_lane_params[self._weights_version]
        new_lanes = [
            _Lane(index=i, device=new_prefill[i % len(new_prefill)],
                  params=primary_lane_params[new_prefill[i % len(new_prefill)]],
                  cache=jax.device_put(
                      init_slot_cache(self.cfg, 1, self.t_max,
                                      dtype=self.config.cache_dtype),
                      new_prefill[i % len(new_prefill)]),
                  state=jax.device_put(
                      init_slot_state(1, seed=self.config.seed,
                                      history=self._spec_ngram),
                      new_prefill[i % len(new_prefill)]))
            for i in range(int(dc.n_prefill_lanes))
        ]
        new_cache, new_state = self._warm_layout(
            new_params_by_version[self._weights_version], new_cache,
            new_state, new_lanes, primary_lane_params, dsh, mesh)
        if tr is not None:
            tr.end(h_build, self._stats["ticks"],
                   moved_bytes=int(ex_stats["bytes"]))
            h_commit = tr.begin("resize", "commit", self._stats["ticks"])

        # -- commit: one host-side swap, nothing half-bound ----------------
        old_decode_dead = any(d in dead for d in self.decode_devices)
        retired = _DrainingLayout(
            layout_id=self._active_layout_id, cache=self._cache,
            state=self._state, params_by_version=self._params_by_version,
            decoding=self._decoding,
        )
        retried = 0
        rebound = 0
        self._decoding = {}
        self._handoffs.clear()  # stale pages target the retired placement
        if retired.decoding:
            if old_decode_dead:
                # The old decode placement lost a device: its KV is gone, so
                # every in-flight decode replays from scratch (idempotent —
                # same prompt/rng/version), spending one retry each.
                for req in list(retired.decoding.values()):
                    req.slot = None
                    retried += 1
                    self._rstats["retried_decodes"] += 1
                    self._retry_or_fail(
                        req, reason="decode device lost in resize")
                retired.decoding = {}
            else:
                self._draining_layouts.append(retired)
                if tr is not None:
                    # Detached: the drain outlives this method, ending in
                    # _prune_drained whenever the last decode finishes.
                    retired.trace_span = tr.begin(
                        "resize", f"drain[layout {retired.layout_id}]",
                        self._stats["ticks"], detached=True,
                        draining=len(retired.decoding))
        # Mid-prefill requests re-queue at the head in their original order,
        # WITHOUT spending a retry — a resize is not a failure. reset binds
        # slot/lane to None; weights_version survives (every installed
        # version was copied), so the replay is bit-equal.
        for req in reversed(list(self._prefilling)):
            req.reset_for_retry()
            rebound += 1
            self._rstats["rebound_requests"] += 1
            self._queue.appendleft(req)
        self._prefilling.clear()
        self._free = list(range(self.n_slots - 1, -1, -1))
        self._used_slots = set()
        self._quarantined_slots = set()
        self._quarantined_lanes = set()
        self._degraded = False
        self._cache, self._state = new_cache, new_state
        self._params_by_version = new_params_by_version
        self._params = new_params_by_version[self._weights_version]
        self._params_decode = self._params
        self._params_template = _template_of(self._params)
        self._lane_params = new_lane_params
        self._lanes = new_lanes
        self._free_lanes = deque(new_lanes)
        self.slice_plan = plan
        self.prefill_devices = new_prefill
        self.decode_devices = new_decode
        self._decode_mesh = mesh
        self._decode_sharding = dsh
        self._devices = devs
        self._active_layout_id += 1
        # Per-layout executables are a feature, not a recompile: re-baseline
        # the census at the (pre-warmed) post-commit size, so "steady
        # recompiles" keeps meaning what it meant — growth WITHIN a layout.
        size = _cache_size(self._decode)
        if size is not None:
            self._decode_executables_baseline = size
        if self._prefill_executables_warm is not None:
            self._prefill_executables_warm = self._chunk_executables()
        self._rstats["resizes"] += 1
        if tr is not None:
            tr.end(h_commit, self._stats["ticks"], rebound=rebound,
                   retried=retried)
            tr.end(h_resize, self._stats["ticks"], ok=True,
                   layout_id=self._active_layout_id)
        if _log_ok():
            logger.info(
                "disagg: resized %d -> %d devices (%d prefill / %d decode, "
                "ratio %.3g, layout %d); %d request(s) rebound, %d retried, "
                "%d draining", old_n, len(devs), plan.n_prefill,
                plan.n_decode, ratio, self._active_layout_id, rebound,
                retried, len(retired.decoding),
            )
        rec = {
            "ok": True, "seq": seq, "layout_id": self._active_layout_id,
            "n_devices": len(devs), "n_prefill": plan.n_prefill,
            "n_decode": plan.n_decode, "flop_ratio": round(ratio, 6),
            "rebound": rebound, "retried": retried,
            "draining": len(retired.decoding),
            "moved_bytes": int(ex_stats["bytes"]),
        }
        if self.telemetry is not None:
            try:
                self.telemetry.record_event("serving_resized", **rec)
            except Exception:
                pass
        return rec

    def _warm_layout(self, params, cache, state, lanes, lane_params, dsh,
                     mesh) -> tuple:
        """Pre-commit compile warm for a target layout: every ladder rung on
        one lane per unique prefill device (prefill + extract), the per-rung
        inserts and the arm on the new decode placement, then the decode
        step itself. All on the NEW buffers — a failure here aborts the
        resize with live state untouched; after the commit the new layout
        serves its first real request with zero compile pauses. Safe for
        bit-equality for the same reason construction's pre-warm is: the
        garbage lands in inactive rows/below future inserts, and the one
        armed slot is released before anything can observe it."""
        prompt_len = min(sum(self.ladder), self.t_max - 2)
        chunks = plan_chunks(prompt_len, self.ladder)
        seen = set()
        for lane in lanes:
            if lane.device in seen:
                continue
            seen.add(lane.device)
            start = 0
            arm_args = None
            for j, (size, valid) in enumerate(chunks):
                chunk = np.zeros((1, size), np.int32)
                lane.cache, lane.state, tok, done0 = self._prefill(
                    lane_params[lane.device], lane.cache, lane.state, chunk,
                    np.int32(0), np.int32(valid), np.int32(1),
                    jax.random.key(self.config.seed),
                    j == 0, j == len(chunks) - 1,
                )
                pages = self._extract(lane.cache, np.int32(start), size)
                pages_d = jax.device_put(pages, dsh)
                cache = self._insert(cache, pages_d[0], pages_d[1],
                                     np.int32(0), np.int32(start),
                                     np.int32(valid))
                start += valid
                if j == len(chunks) - 1:
                    arm_args = jax.device_put(
                        (tok, done0, lane.state.rng[0],
                         lane.state.history[0]), dsh)
            if arm_args is not None:
                tok, done0, carry, hist = arm_args
                state = self._arm(state, np.int32(0), tok, done0,
                                  np.int32(1), carry, hist)
                state = _release_step(state, np.int32(0))
        for _ in range(4 if mesh is not None else 1):
            cache, state, *_ = self._decode(params, cache, state, self._full_mask)
        return cache, state

    def _drain_decode_tick(self) -> None:
        """Advance every retired layout's surviving decodes by one step —
        the same compiled decode program, dispatched at the OLD placement
        (its cache entry already exists, so draining never compiles).
        Completions finish ``ok`` directly: a retired slot index must never
        reach the ACTIVE free list."""
        if not self._draining_layouts:
            return
        for L in list(self._draining_layouts):
            versions = sorted({r.weights_version
                               for r in L.decoding.values()})
            for v in versions:
                mask = np.zeros((self.n_slots,), bool)
                for slot, r in L.decoding.items():
                    if r.weights_version == v:
                        mask[slot] = True
                with self._phase("serving.decode_dispatch"):
                    L.cache, L.state, *out = self._decode(
                        L.params_by_version[v], L.cache, L.state, mask)
                    self._stats["decode_steps"] += 1
                with self._phase("serving.decode_fetch"):
                    toks_np, emitted_np, done_np, bad_np = jax.device_get(out)
                with self._phase("serving.bookkeeping"):
                    t_fetch = time.perf_counter()
                    for slot, req in list(L.decoding.items()):
                        if req.weights_version != v or not mask[slot]:
                            continue
                        if bool(bad_np[slot]):
                            del L.decoding[slot]
                            L.state = _release_step(L.state, np.int32(slot))
                            req.slot = None
                            self._retry_or_fail(
                                req, reason=("nonfinite logits while "
                                             f"draining layout {L.layout_id}"))
                            continue
                        cnt = int(emitted_np[slot])
                        self._emit(req, toks_np[slot, :cnt], t_fetch)
                        if self._speculate_k > 0 and cnt > 0:
                            req.spec_drafted += self._speculate_k
                            req.spec_accepted += max(cnt - 1, 0)
                        if bool(done_np[slot]):
                            del L.decoding[slot]
                            self._finish(req, "ok")
        self._prune_drained()

    def _prune_drained(self) -> None:
        alive = [L for L in self._draining_layouts if L.decoding]
        drained = len(self._draining_layouts) - len(alive)
        if drained:
            if self.tracing is not None:
                for L in self._draining_layouts:
                    if not L.decoding and L.trace_span is not None:
                        self.tracing.end(L.trace_span, self._stats["ticks"])
            self._draining_layouts = alive
            self._rstats["drained_layouts"] += drained
            if _log_ok():
                logger.info("disagg: %d retired layout(s) fully drained",
                            drained)

    def _extra_inflight(self) -> list:
        reqs = []
        for L in self._draining_layouts:
            reqs.extend(L.decoding.values())
        return reqs

    def _evict(self, req, status: str) -> None:
        """Drain-aware eviction: a request finishing on a retired layout
        releases THAT layout's row — the base path would free the same slot
        index in the ACTIVE layout, handing one slot to two requests."""
        for L in self._draining_layouts:
            if req.slot is not None and L.decoding.get(req.slot) is req:
                del L.decoding[req.slot]
                L.state = _release_step(L.state, np.int32(req.slot))
                self._finish(req, status)
                self._prune_drained()
                return
        super()._evict(req, status)

    # -- weight publication ------------------------------------------------

    def _install_params(self, params, version: int) -> None:
        """Disagg placement for a published version: ``params`` (validated
        against the decode placement — that is what ``_params`` aliases
        here) becomes the decode-mesh copy, plus one host of per-device
        copies for the prefill lanes, mirroring construction."""
        super()._install_params(params, version)
        by_dev: dict = {}
        for lane in self._lanes:
            if lane.device not in by_dev:
                by_dev[lane.device] = jax.device_put(params, lane.device)
        self._lane_params[int(version)] = by_dev

    def _drop_params(self, version: int) -> None:
        super()._drop_params(version)
        self._lane_params.pop(int(version), None)

    # -- warmup ------------------------------------------------------------

    def warmup(self) -> None:
        """Compile the full two-mesh program set before real traffic: one
        rung-walking request PER LANE (jit caches per placement, so every
        lane device must see every ladder rung — prefill and extract alike),
        which also compiles the per-rung inserts, the arm, and the decode
        step. FIFO lane reuse guarantees coverage even when slots are
        scarcer than lanes. Metrics reset afterwards."""
        prompt_len = min(sum(self.ladder), self.t_max - 2)
        prompt = np.ones((prompt_len,), np.int32)
        self.run([prompt] * len(self._lanes), max_new_tokens=2)
        self._prefill_executables_warm = self._chunk_executables()
        self.reset_metrics()

    def reset_metrics(self) -> None:
        super().reset_metrics()
        for k in self._hstats:
            self._hstats[k] = 0
        for k in self._rstats:
            self._rstats[k] = 0.0 if k == "transfer_wall_s" else 0
        self._handoff_lat_s.clear()

    # -- reporting ---------------------------------------------------------

    def executable_counts(self) -> dict:
        """Adds the data-plane programs to the base census. ``prefill`` is
        now bounded by ``len(ladder) * n_prefill_devices`` (jit compiles
        per placement); ``decode`` stays exactly 1 — the placement is
        fixed, so the invariant survives the split."""
        out = super().executable_counts()
        out["handoff_extract"] = _cache_size(self._extract)
        out["handoff_insert"] = _cache_size(self._insert)
        out["slot_arm"] = _cache_size(self._arm)
        return out

    def stats(self) -> dict:
        out = super().stats()
        hs = self._hstats
        lat = np.asarray(self._handoff_lat_s, np.float64)
        s = self._stats
        measured = (s["prompt_tokens_in"] / s["tokens_out"]
                    if s["tokens_out"] else None)
        out["disagg"] = {
            "slice_plan": self.slice_plan.to_dict(),
            "n_prefill_devices": len(self.prefill_devices),
            "n_decode_devices": len(self.decode_devices),
            "decode_slot_sharded": self._decode_mesh is not None,
            "n_prefill_lanes": len(self._lanes),
            "handoff_depth": int(self.disagg_config.handoff_depth),
            "handoff_transfers": hs["transfers"],
            "handoff_inserts": hs["inserts"],
            "handoff_bytes": hs["bytes"],
            "handoff_final_flushes": hs["flushes"],
            "handoff_lat_sampled": int(lat.size),
            "handoff_lat_mean_s": float(lat.mean()) if lat.size else None,
            "handoff_lat_p95_s": (
                float(np.percentile(lat, 95)) if lat.size else None),
            "quarantined_lanes": sorted(self._quarantined_lanes),
            "healthy_lanes": len(self._lanes) - len(self._quarantined_lanes),
            "degraded": bool(self._degraded),
            # The ratio to feed back into DisaggConfig for the next run —
            # the calibration loop the planner's cost model expects.
            "measured_flop_ratio": (
                round(measured, 6) if measured is not None else None),
        }
        rs = dict(self._rstats)
        rs["transfer_wall_s"] = round(rs["transfer_wall_s"], 6)
        rs["active_layout"] = self._active_layout_id
        rs["n_devices"] = len(self._devices)
        rs["draining_layouts"] = len(self._draining_layouts)
        rs["draining_requests"] = sum(
            len(L.decoding) for L in self._draining_layouts)
        out["disagg"]["resize"] = rs
        return out

    def _push_telemetry_summary(self) -> None:
        super()._push_telemetry_summary()  # serving block (incl. "disagg")
        if self.telemetry is not None:
            try:
                self.telemetry.record_disagg(self.stats()["disagg"])
            except Exception as e:  # observability must never kill serving
                logger.warning_once(f"disagg: telemetry summary failed: {e}")
