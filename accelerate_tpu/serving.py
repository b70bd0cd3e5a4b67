"""Continuous-batching serving engine (layer L7 — inference serving).

:func:`~accelerate_tpu.generation.generate` is a gang-scheduled static
batch: one compiled loop per ``(batch, prompt_len, max_new_tokens)`` tuple, a
batch-global cache ``length`` scalar, and every request stalls until the
slowest row finishes. Under mixed-length traffic most of the chip burns on
finished rows and every new prompt shape recompiles. This module is the
vLLM/TGI-class fix, TPU-shaped:

- **Slot KV cache** — ONE dense buffer pair, ``T_max`` private rows a slot
  (no pages, no sharing; kv_cache.py :func:`init_slot_cache`), whose
  ``length`` is a per-slot vector; a request occupies a slot for exactly its
  own lifetime and the slot is reused mid-flight with no reshape and no
  recompile.
- **Admission scheduler** — incoming requests queue; free slots fill every
  tick; rows that emit EOS (or exhaust their budget) retire immediately and
  hand their slot to the next request.
- **Chunked prefill** — prompts are split into ladder-sized chunks (the
  compile manager's seq buckets when available) and written a chunk per
  tick, so a long prompt never head-of-line-blocks decode latency and every
  possible prompt length compiles at most ``len(ladder)`` prefill
  executables. The tick's chunk rides its decode step, one program
  (:func:`_build_decode_chunk_step`), so the weights are read once a tick.
- **Zero-recompile decode** — the steady-state decode step is ONE jitted
  ``(params, cache, slot_state) -> (cache, slot_state, tokens, emitted,
  done, bad)`` program with donated cache buffers (``bad`` is the
  nonfinite-logits sentinel below); its executable count is watched every tick
  (``stats()["steady_recompiles"]``, cross-checked by the telemetry
  recompile watchdog when a recorder is attached).

Greedy decoding through the engine is token-for-token identical to
:func:`generate` per request (tests/test_serving.py pins it); sampled
decoding uses one PRNG stream per request (the ``rng`` passed at
``submit``), mirroring a batch-1 ``generate`` call.

Request-lifecycle robustness (the serving twin of fault_tolerance.py's
training-side treatment — fails loudly, degrades gracefully, verified by
``make chaos-smoke``):

- **Explicit terminal statuses** — every submitted request finishes with a
  ``status`` in its ``poll()`` result: ``ok`` (delivered), ``timeout``
  (missed its deadline — the slot is freed the same tick), ``shed``
  (dropped by admission control or a preemption drain), or ``failed``
  (recovery retries exhausted). Nothing disappears silently.
- **Admission control + SLOs** — ``ServingConfig.max_queue_depth`` bounds
  the queue with an ``overload_policy`` (``reject`` | ``shed_oldest`` |
  ``block``); ``deadline_s`` (engine default or per-``submit``) is checked
  every tick.
- **Nonfinite-logits sentinel** — the decode step reports per-slot
  nonfinite logits alongside the sampled tokens (one fused fetch — no
  extra dispatch stall, the serving analog of PR 3's lagged divergence
  sentinel). A poisoned slot is quarantined and its request retried
  (bounded by ``max_retries``) with an idempotent, bit-equal resubmission.
- **Hang guard** — ``max_idle_ticks`` ticks with pending requests but zero
  progress raise :class:`ServingStalledError` naming the stuck requests
  instead of spinning forever.
- **Preemption drain** — with a fault-tolerance manager attached
  (``fault_tolerance=`` or via ``Accelerator.build_serving_engine``),
  SIGTERM finishes in-flight requests, sheds the queue, and the engine
  reports :data:`~accelerate_tpu.utils.constants.PREEMPTION_EXIT_CODE`
  (75) for a resumable exit instead of dying mid-flight.
- **Deterministic fault injection** — pass a
  :class:`~accelerate_tpu.chaos.FaultInjector` (``chaos=``) to exercise
  every one of these paths on a seed-replayable schedule.

Off by default everywhere: no engine exists unless you construct one (or
pass a :class:`~accelerate_tpu.utils.ServingConfig` to
``Accelerator.build_serving_engine``), and the training path never touches
this module.

Usage::

    from accelerate_tpu import ServingConfig, ServingEngine

    engine = ServingEngine(model, ServingConfig(n_slots=8, eos_token_id=2))
    # Batch API:
    outs = engine.run(prompts, max_new_tokens=64)
    # Incremental API (a serving front-end's loop):
    rid = engine.submit(prompt, max_new_tokens=64)
    while True:
        engine.tick()
        for res in engine.poll():
            ...  # res["tokens"] is the full prompt+continuation row
"""

from __future__ import annotations

import inspect
import itertools
import os
import time
from collections import deque
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .chaos import InjectedFaultError
from .generation import (
    ENCDEC_GENERATION_PLANS,
    FUSED_QKV_PLANS,
    GENERATION_PLANS,
    PromptChunk,
    _filter_logits,
    fuse_qkv_params,
    sample_logits,
)
from .kv_cache import KVCache, cache_spec, decode_reads, init_slot_cache, kv_bytes_per_token
from .ops.decode_attention import rows_read
from .logging import get_logger
from .utils.constants import PREEMPTION_EXIT_CODE, SERVING_CRASH_EXIT_CODE

logger = get_logger(__name__)


def _log_ok() -> bool:
    """The repo logger needs accelerate state; the engine must also work
    standalone (no Accelerator), where these logs are just skipped."""
    from .state import PartialState

    return bool(PartialState._shared_state)


#: The explicit terminal statuses every request ends with (poll() results).
REQUEST_STATUSES = ("ok", "timeout", "shed", "failed")


class ServingStalledError(RuntimeError):
    """The engine made no progress for ``max_idle_ticks`` consecutive ticks
    while requests were still pending — e.g. every lane wedged or every
    slot quarantined. Raised from ``tick()`` (so ``run()`` and
    :func:`replay_trace` fail loudly instead of spinning), naming the stuck
    requests and their states."""


# ---------------------------------------------------------------------------
# Chunk-ladder math (pure functions — unit-tested directly)
# ---------------------------------------------------------------------------


def default_prefill_ladder(max_len: int, min_chunk: int = 16,
                           max_chunk: int = 256) -> list[int]:
    """Pow2 chunk ladder for chunked prefill: ``min_chunk`` doubling up to
    ``min(max_chunk, max_len)``. Arbitrary prompt lengths then compile at
    most ``len(ladder)`` prefill executables."""
    top = max(1, min(int(max_chunk), int(max_len)))
    rungs, c = set(), max(1, int(min_chunk))
    while c < top:
        rungs.add(c)
        c *= 2
    rungs.add(top)
    return sorted(rungs)


def plan_chunks(prompt_len: int, ladder) -> list[tuple[int, int]]:
    """Split a prompt into ``(chunk_size, valid_tokens)`` pieces: greedy
    largest-rung-that-fits; the final partial piece pads up to the smallest
    rung that covers it (pad slots are never attended — the causal mask
    bounds attention at each row's true length, and the next write
    overwrites them)."""
    rungs = sorted({int(x) for x in ladder})
    if not rungs or prompt_len < 1:
        raise ValueError(f"need a non-empty ladder and prompt, got "
                         f"ladder={rungs} prompt_len={prompt_len}")
    out, rem = [], int(prompt_len)
    while rem > 0:
        fits = [r for r in rungs if r <= rem]
        if fits:
            out.append((fits[-1], fits[-1]))
            rem -= fits[-1]
        else:  # tail shorter than every rung: pad up to the smallest
            out.append((rungs[0], rem))
            rem = 0
    return out


# ---------------------------------------------------------------------------
# Device-side slot state
# ---------------------------------------------------------------------------


class SlotState(NamedTuple):
    """Per-slot decode state — the vectors that replace ``generate()``'s
    batch-global scalars. Threaded (donated) through the jitted decode step
    alongside the slot cache."""

    last_token: jax.Array  # (N,) int32 — most recent sampled token per slot
    active: jax.Array      # (N,) bool  — prompt fully prefilled, decoding
    done: jax.Array        # (N,) bool  — emitted EOS / exhausted budget
    generated: jax.Array   # (N,) int32 — new tokens sampled so far
    budget: jax.Array      # (N,) int32 — per-request max_new_tokens
    rng: jax.Array         # (N,) PRNG keys — one stream per request
    # (N, H) int32 rolling token history (-1 pad), the n-gram self-draft
    # window for speculative decoding. Invariant for armed slots:
    # history[:, -1] == last_token. Inert (but still threaded/donated)
    # when speculate_k == 0.
    history: jax.Array


def init_slot_state(n_slots: int, seed: int = 0,
                    history: int = 16) -> SlotState:
    return SlotState(
        last_token=jnp.zeros((n_slots,), jnp.int32),
        active=jnp.zeros((n_slots,), bool),
        done=jnp.zeros((n_slots,), bool),
        generated=jnp.zeros((n_slots,), jnp.int32),
        budget=jnp.zeros((n_slots,), jnp.int32),
        rng=jax.random.split(jax.random.key(seed), n_slots),
        history=jnp.full((n_slots, int(history)), -1, jnp.int32),
    )


def _commit_params(params):
    """Pin every device leaf to its current placement (a committed
    ``device_put`` no-op). The jit dispatch cache keys on commitment, so
    every installed version must look alike — model-init trees arrive
    uncommitted while published trees (reshard-executor output) arrive
    committed, and mixing them would recompile decode at the first swap."""
    def commit(leaf):
        if isinstance(leaf, jax.Array) and not leaf.committed:
            return jax.device_put(leaf, leaf.sharding)
        return leaf

    return jax.tree.map(commit, params)


def _template_of(params):
    """Each leaf's shape, dtype and sharding: what a published tree is held
    to (``ServingEngine._validate_params_tree``) and placed onto."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        if isinstance(a, jax.Array) else a, params)


def _single_device_sharding_of(params):
    """The replicated sharding on the params' device, in the params' own
    form (NamedSharding over a one-device mesh stays a NamedSharding), or
    ``None`` (default placement) when they span several devices — the
    colocated engine is single-device and jit then refuses the mix loudly."""
    leaf = next((x for x in jax.tree.leaves(params) if isinstance(x, jax.Array)), None)
    if leaf is None or len(leaf.sharding.device_set) != 1:
        return None
    sharding = leaf.sharding
    if isinstance(sharding, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(sharding.mesh, jax.sharding.PartitionSpec())
    return sharding


def _select_keys(mask, a, b):
    """Per-row key select over typed PRNG key arrays: ``a`` where ``mask``,
    else ``b``. Goes through key_data because jnp.where on extended dtypes is
    version-fragile."""
    data = jnp.where(mask[..., None], jax.random.key_data(a),
                     jax.random.key_data(b))
    return jax.random.wrap_key_data(data, impl=jax.random.key_impl(a))


def _ngram_draft(history, last_token, k: int):
    """Deterministic n-gram self-draft: find the most recent PREVIOUS
    occurrence of ``last_token`` in each slot's history window and propose
    the ``k`` tokens that followed it (cycling the followed suffix when it
    is shorter than ``k``). Slots with no match (or -1 history padding)
    fall back to repeating ``last_token`` — a valid, always-verifiable
    draft. Pure jnp over static shapes: compiles into the decode program."""
    h = history.shape[1]
    match = history[:, : h - 1] == last_token[:, None]  # (N, H-1)
    has = match.any(axis=1)
    # Index of the LAST match: reverse, take the first True.
    argrev = jnp.argmax(match[:, ::-1].astype(jnp.int32), axis=1)
    j = jnp.where(has, (h - 2) - argrev, h - 1)
    period = jnp.maximum((h - 1) - j, 1)
    offs = j[:, None] + 1 + (jnp.arange(k, dtype=jnp.int32)[None, :]
                             % period[:, None])
    offs = jnp.minimum(offs, h - 1)
    drafts = jnp.take_along_axis(history, offs, axis=1)  # (N, k)
    return jnp.where(has[:, None] & (drafts >= 0), drafts,
                     last_token[:, None])


def _takes_attn_bound(fwd) -> bool:
    """Whether a cached forward has ``_forward_cached``'s ``attn_bound``: the
    built-in plans do; a caller's own ``forward_cached`` of the older five
    arguments is called as ever and bounds its reads by the lengths."""
    return "attn_bound" in inspect.signature(fwd).parameters


def _takes_chunk(fwd) -> bool:
    """Whether a cached forward takes ``_forward_cached``'s ``chunk``: a
    prompt chunk that rides a decode step (:func:`_build_decode_chunk_step`)."""
    return "chunk" in inspect.signature(fwd).parameters


def _advance_live_rows(cache, new_cache, state, logits, live, *, temperature,
                       top_k, top_p, eos_token_id):
    """A decode step of one token a slot after its forward (``logits``
    (N, V), ``new_cache`` the forward's): sample every live row, advance
    its length, count and stream, and flag what is done or nonfinite.
    Returns the decode program's 6-tuple. Shared by ``decode`` (k = 0) and
    ``decode_chunk``."""
    # fwd advanced every row's write offset; only live rows really did.
    lengths = jnp.where(live, new_cache.length, cache.length)
    pairs = jax.vmap(jax.random.split)(state.rng)  # (N, 2) keys
    carry, sub = pairs[:, 0], pairs[:, 1]
    # Per-slot sampling over a (1, V) row — the same shape a batch-1
    # generate() samples, so per-request streams match it exactly.
    tok = jax.vmap(
        lambda row, key: sample_logits(
            row[None], key, temperature=temperature, top_k=top_k,
            top_p=top_p
        )[0]
    )(logits, sub)
    tok = jnp.where(live, tok, state.last_token)
    # Nonfinite-logits sentinel: flag live rows whose logits went
    # NaN/inf (a poisoned KV page). Computed on the PRE-update live
    # mask so parked rows' masked garbage never flags, and fetched
    # with the same host sync as (tok, done) — no extra dispatch
    # stall.
    bad = live & ~jnp.isfinite(logits).all(axis=-1)
    generated = state.generated + live.astype(jnp.int32)
    newly_done = live & (generated >= state.budget)
    if eos_token_id is not None:
        newly_done = newly_done | (live & (tok == eos_token_id))
    new_state = SlotState(
        last_token=tok,
        active=state.active,
        done=state.done | newly_done,
        generated=generated,
        budget=state.budget,
        # Masked rows' streams must freeze (another version's
        # dispatch owns their advance this tick); free/done slots'
        # streams are dead until realloc rewrites them either way.
        rng=_select_keys(live, carry, state.rng),
        history=state.history,
    )
    return (new_cache._replace(length=lengths), new_state,
            tok[:, None], live.astype(jnp.int32), new_state.done, bad)


def _arm_chunk_slot(cache, state, last_logits, chunk, slot, valid, budget, rng,
                    is_first, is_final, start, *, temperature, top_k, top_p,
                    eos_token_id):
    """A prompt chunk's slot after its forward wrote rows ``start ..``:
    commit ``start + valid`` as its length, sample the request's first token
    from ``last_logits()`` (the row of its last real prompt position; a
    thunk, so that it is read where the prefill program always read it) and,
    on the final chunk, arm the slot for decode. Returns ``(cache, state,
    tok, done0)``. Shared by ``prefill`` and ``decode_chunk``."""
    # Advance by the VALID tokens only; a padded tail is overwritten by
    # the next write and never attended (causal bound at true length).
    lengths = cache.length.at[slot].set(start + valid)

    carry, sub_key = jax.random.split(rng)
    last = last_logits()
    tok = sample_logits(
        last[None], sub_key, temperature=temperature, top_k=top_k, top_p=top_p
    )[0]
    done0 = budget <= 1
    if eos_token_id is not None:
        done0 = done0 | (tok == eos_token_id)
    done0 = is_final & done0
    # Seed the slot's n-gram history: shift the chunk's VALID tokens in
    # (first chunk resets the window to -1 padding first), and on the
    # final chunk shift in the sampled first token so the armed-slot
    # invariant history[:, -1] == last_token holds entering decode.
    h = state.history.shape[1]
    hist0 = jnp.where(is_first,
                      jnp.full((h,), -1, jnp.int32),
                      state.history[slot])
    hbuf = jnp.concatenate([hist0, chunk[0].astype(jnp.int32)])
    hist1 = jax.lax.dynamic_slice_in_dim(hbuf, valid, h)
    hist2 = jnp.where(is_final,
                      jnp.concatenate([hist1[1:], tok[None]]), hist1)
    new_state = SlotState(
        # Intermediate chunks park a garbage token here; the final chunk
        # (the only one decode can observe — active stays False until
        # then) overwrites it with the real first token.
        last_token=state.last_token.at[slot].set(tok),
        active=state.active.at[slot].set(is_final),
        done=state.done.at[slot].set(done0),
        generated=state.generated.at[slot].set(
            jnp.where(is_final, 1, 0).astype(jnp.int32)),
        budget=state.budget.at[slot].set(budget),
        rng=state.rng.at[slot].set(carry),
        history=state.history.at[slot].set(hist2),
    )
    return cache._replace(length=lengths), new_state, tok, done0


def _build_decode_step(fwd, cfg, temperature, top_k, top_p, eos_token_id,
                       speculate_k: int = 0):
    """ONE jitted decode program for the whole engine lifetime: every slot
    advances one token — or, with ``speculate_k > 0``, up to ``k+1`` tokens
    verified in one batched ``(n_slots, k+1)`` forward (rows that are free
    or done compute masked garbage — the fixed shape is what buys zero
    steady-state recompiles; where the forward's attention reads by a per-row
    bound, ``kv_cache.cache_attend``, they are given the bound 0, read nothing
    and come out as zeros: their write offset stays their ``cache.length``).
    Cache and state buffers are donated; params
    are NOT (the weight-publication hot swap relies on rebinding them
    without invalidating live buffers). The donated cache stays ONE buffer a
    side through the step: the forward's layer loop carries it whole and
    scatters each slot's new rows into it in place (``kv_cache.cache_step``),
    so a step copies no cache and holds no second one beside it.

    Both modes return the same 6-tuple
    ``(cache, state, toks (N, k+1) int32, emitted (N,) int32, done (N,),
    bad (N,))`` — ``toks[slot, :emitted[slot]]`` are the tokens the slot
    really produced this tick (k=0 returns ``(N, 1)`` with emitted == live),
    and ``done`` is the state's flag after it, an output of its own: the
    state is donated to the next step, which the engine dispatches before it
    fetches this one's outputs.

    Speculation: an n-gram self-draft proposes ``k`` tokens per slot from
    the slot's token history; the target model scores all ``k+1`` window
    positions in one forward. Greedy acceptance keeps the longest prefix
    where draft == argmax, which makes the emitted token sequence
    IDENTICAL (bit-equal) to the sequential greedy chain — a rejected
    position's argmax is exactly what sequential decode would have
    produced there. Sampled mode accepts draft ``d_i`` with probability
    ``p_i(d_i)`` (the deterministic draft is a delta distribution, so the
    standard min(1, p/q) ratio reduces to ``p_i(d_i)``) and on rejection
    draws from the renormalized residual — the emitted tokens are
    EXACTLY target-distribution samples. KV pages written past the
    accepted prefix are garbage but harmless: the next tick's window
    rewrites ``[start+e, start+e+k]`` bit-identically before attention
    ever reads those rows.

    ``run_mask`` is a host-side (N,) bool vector selecting which slots this
    dispatch advances. Steady state passes all-True — one dispatch per tick,
    bit-identical to the unmasked step. During a canary window the engine
    dispatches the SAME executable once per weights version with
    complementary masks, so slots bound to different param versions advance
    under their own weights: masked-out rows keep their token, length,
    budget accounting, and PRNG stream frozen (a masked live row's stale
    cache write at its frozen offset is overwritten by its owning dispatch
    before attention reads it — the same mechanism that parks done rows)."""
    k_spec = int(speculate_k)
    greedy = temperature is None or temperature <= 0
    bounded = _takes_attn_bound(fwd)
    sampling = dict(temperature=temperature, top_k=top_k, top_p=top_p,
                    eos_token_id=eos_token_id)

    def decode(params, cache: KVCache, state: SlotState, run_mask):
        live = state.active & ~state.done & run_mask
        if k_spec == 0:
            # a live row attends over its rows and the one it writes now
            bound = {"attn_bound": jnp.where(live, cache.length + 1, 0)} if bounded else {}
            logits, new_cache = fwd(cfg, params, state.last_token[:, None],
                                    cache, **bound)
            return _advance_live_rows(cache, new_cache, state, logits, live, **sampling)

        # ---- speculative path: draft k, verify k+1 in ONE forward ----
        n = state.last_token.shape[0]
        drafts = _ngram_draft(state.history, state.last_token, k_spec)
        window = jnp.concatenate([state.last_token[:, None], drafts], axis=1)
        logits_all, new_cache = fwd(cfg, params, window, cache,
                                    return_all=True)  # (N, k+1, V) fp32
        bad = live & ~jnp.isfinite(logits_all).reshape(n, -1).all(axis=-1)
        pairs = jax.vmap(jax.random.split)(state.rng)
        carry, sub = pairs[:, 0], pairs[:, 1]
        idx = jnp.arange(k_spec + 1, dtype=jnp.int32)[None, :]
        if greedy:
            # targets[:, i] is the sequential-greedy continuation of the
            # window prefix ending at position i; the emitted prefix of
            # targets is therefore the exact sequential greedy chain.
            targets = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
            acc = jnp.cumprod(
                (drafts == targets[:, :k_spec]).astype(jnp.int32), axis=1)
            m = jnp.sum(acc, axis=1)  # accepted draft count, 0..k
            out = targets
        else:
            vocab = logits_all.shape[-1]
            flt = _filter_logits(logits_all.reshape(-1, vocab),
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)
            probs = jax.nn.softmax(flt, axis=-1).reshape(n, k_spec + 1, vocab)
            keys = jax.vmap(
                lambda key: jax.random.split(key, 2 * k_spec + 1))(sub)
            u = jax.vmap(
                lambda ks: jax.random.uniform(ks[0], (k_spec,)))(keys)
            p_draft = jnp.take_along_axis(
                probs[:, :k_spec], drafts[..., None], axis=-1)[..., 0]
            acc = jnp.cumprod((u < p_draft).astype(jnp.int32), axis=1)
            m = jnp.sum(acc, axis=1)
            # Residual for a rejection at i: target probs with the draft
            # token removed, renormalized. log(0)=-inf masks it out of the
            # categorical. The bonus token (all k accepted) draws from the
            # unmodified position-k distribution.
            onehot = jax.nn.one_hot(drafts, vocab, dtype=bool)
            resid = jnp.log(jnp.where(onehot, 0.0, probs[:, :k_spec]))
            r_tok = jax.vmap(
                lambda ks, lg: jax.vmap(jax.random.categorical)(
                    ks[1:k_spec + 1], lg)
            )(keys, resid).astype(jnp.int32)  # (N, k)
            bonus = jax.vmap(
                lambda ks, lg: jax.random.categorical(ks[2 * k_spec], lg)
            )(keys, jnp.log(probs[:, k_spec])).astype(jnp.int32)  # (N,)
            cand = jnp.concatenate([r_tok, bonus[:, None]], axis=1)
            drafts_ext = jnp.concatenate(
                [drafts, jnp.zeros((n, 1), jnp.int32)], axis=1)
            out = jnp.where(idx < m[:, None], drafts_ext, cand)
        # Emittable tokens this tick: the accepted prefix + one corrective/
        # bonus token, clamped at the first EOS and the remaining budget.
        avail = m + 1
        if eos_token_id is not None:
            is_eos = (out == eos_token_id) & (idx < avail[:, None])
            any_eos = is_eos.any(axis=1)
            first_eos = jnp.argmax(is_eos, axis=1)
            avail = jnp.where(any_eos, first_eos + 1, avail)
        room = jnp.maximum(state.budget - state.generated, 0)
        e = jnp.where(live, jnp.minimum(avail, room), 0)
        generated = state.generated + e
        newly_done = live & (e > 0) & (generated >= state.budget)
        if eos_token_id is not None:
            newly_done = newly_done | (
                live & (is_eos & (idx < e[:, None])).any(axis=1))
        last = jnp.take_along_axis(
            out, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
        tok_last = jnp.where(live & (e > 0), last, state.last_token)
        # Only the accepted prefix really advanced the cache; the garbage
        # KV past it is rewritten bit-identically next tick.
        lengths = jnp.where(live, cache.length + e, cache.length)
        # Shift the e emitted tokens into the history window.
        h = state.history.shape[1]
        buf = jnp.concatenate([state.history, out], axis=1)
        hist = jnp.take_along_axis(
            buf, jnp.arange(h, dtype=jnp.int32)[None, :] + e[:, None], axis=1)
        new_state = SlotState(
            last_token=tok_last,
            active=state.active,
            done=state.done | newly_done,
            generated=generated,
            budget=state.budget,
            rng=_select_keys(live, carry, state.rng),
            history=hist,
        )
        return (new_cache._replace(length=lengths), new_state,
                out, e, new_state.done, bad)

    return jax.jit(decode, donate_argnums=(1, 2))


def _build_prefill_step(fwd, cfg, temperature, top_k, top_p, eos_token_id):
    """One jitted prefill program; each ladder chunk size is one executable
    inside it. Writes a ``(1, C)`` prompt chunk into ``slot`` at that slot's
    own offset; on the final chunk it samples the request's first token
    (TTFT) and arms the slot for decode."""
    sampling = dict(temperature=temperature, top_k=top_k, top_p=top_p,
                    eos_token_id=eos_token_id)

    def prefill(params, cache: KVCache, state: SlotState, chunk, slot, valid,
                budget, rng, is_first, is_final):
        start = jnp.where(is_first, 0, cache.length[slot])
        # The slot alone, its length a (1,) per-row vector: the slot cache's
        # forward path, at the shape a batch-1 prefill has.
        sub_cache = cache.take_slot(slot, start)
        logits_all, sub_cache = fwd(cfg, params, chunk, sub_cache, return_all=True)
        cache = cache.put_slot(slot, sub_cache)
        return _arm_chunk_slot(cache, state, lambda: logits_all[0, valid - 1], chunk,
                               slot, valid, budget, rng, is_first, is_final, start,
                               **sampling)

    return jax.jit(prefill, donate_argnums=(1, 2))


def _build_decode_chunk_step(fwd, cfg, temperature, top_k, top_p, eos_token_id):
    """ONE jitted program for a tick that advances a prompt chunk: the k = 0
    decode step of every slot and the chunk of the request in ``slot``, in
    one forward (``_forward_cached``'s ``chunk``), so every weight is read
    once a tick and not once for each. The slot's own decode row reads
    nothing (it is not live: its request is still prefilling) and the chunk
    is then committed as :func:`_build_prefill_step` commits it; a request
    it arms decodes from the next tick. Each ladder rung is one executable.
    Returns the decode program's 6-tuple and the chunk's ``(tok, done0)``:
    one fetch brings all of them."""
    sampling = dict(temperature=temperature, top_k=top_k, top_p=top_p,
                    eos_token_id=eos_token_id)
    bounded = _takes_attn_bound(fwd)

    def decode_chunk(params, cache: KVCache, state: SlotState, run_mask, chunk,
                     slot, valid, budget, rng, is_first, is_final):
        live = state.active & ~state.done & run_mask
        start = jnp.where(is_first, 0, cache.length[slot])
        bound = {"attn_bound": jnp.where(live, cache.length + 1, 0)} if bounded else {}
        logits, new_cache = fwd(cfg, params, state.last_token[:, None], cache,
                                chunk=PromptChunk(chunk, slot, start, valid), **bound)
        cache, state, toks, emitted, done, bad = _advance_live_rows(
            cache, new_cache, state, logits[:-1], live, **sampling)
        cache, state, tok, done0 = _arm_chunk_slot(
            cache, state, lambda: logits[-1], chunk, slot, valid, budget, rng,
            is_first, is_final, start, **sampling)
        return cache, state, toks, emitted, done, bad, tok, done0

    return jax.jit(decode_chunk, donate_argnums=(1, 2))


def _release_slot_op(state: SlotState, slot) -> SlotState:
    """Mark one device slot done mid-flight (timeout eviction, quarantine):
    ``live = active & ~done`` goes False so the decode step computes masked
    garbage for the row until a new grant's first prefill chunk rewrites it.
    A separate tiny program — the ONE-decode-executable census is untouched."""
    return SlotState(
        last_token=state.last_token,
        active=state.active,
        done=state.done.at[slot].set(True),
        generated=state.generated,
        budget=state.budget,
        rng=state.rng,
        history=state.history,
    )


_release_step = jax.jit(_release_slot_op, donate_argnums=(0,))


def _slo_aggregate(events) -> dict:
    """Shared SLO arithmetic over terminal-request events (``{"status",
    "ttft_s", "tpot_s"}`` dicts): ok-only latency samples plus per-status
    rates. ONE implementation backs both the canary cohort gates
    (:meth:`ServingEngine.cohort_stats`) and the rolling window the
    autoscaler polls (:meth:`ServingEngine.window_stats`), so the two SLO
    readings can't drift."""
    n = len(events)
    ok = [e for e in events if e["status"] == "ok"]
    ttft = np.asarray([e["ttft_s"] for e in ok if e["ttft_s"] is not None],
                      np.float64)
    tpot = np.asarray([e["tpot_s"] for e in ok if e["tpot_s"] is not None],
                      np.float64)

    def rate(status):
        return (sum(1 for e in events if e["status"] == status) / n
                if n else 0.0)

    return {"n": n, "ok": len(ok), "ttft": ttft, "tpot": tpot,
            "timeout_rate": rate("timeout"), "shed_rate": rate("shed"),
            "failed_rate": rate("failed")}


def _cache_size(fn) -> Optional[int]:
    size_fn = getattr(fn, "_cache_size", None)
    if callable(size_fn):
        try:
            return int(size_fn())
        except Exception:
            return None
    return None


# ---------------------------------------------------------------------------
# The tick's phases
# ---------------------------------------------------------------------------

TICK = "serving.tick"
TICK_PHASES = (
    "serving.admit",              # preemption latch, deadlines, admission, queue sample
    "serving.prefill",            # a prompt chunk: host build, and dispatch where it runs alone
    "serving.first_token_fetch",  # the blocking fetch of a lone final chunk's token
    "serving.decode_dispatch",    # version groups, the decode call (a riding chunk's too), compile watch
    "serving.decode_fetch",       # the last step's fused device_get: host blocked on the device
    "serving.bookkeeping",        # per-slot loop over the settled step's record, retire
    "serving.end_tick",           # journal, chaos draw, hang guard, SDC canary
)
_DEVICE_WAIT_PHASES = ("serving.first_token_fetch", "serving.decode_fetch")
_TOKEN_GAP_SAMPLE = 65536  # newest gaps kept for stats()["token_gap"]


class _Phase:
    """One phase of a tick (``with engine._phase(name):``), timed by one
    pair of clock reads that feeds three sinks: a
    ``jax.profiler.TraceAnnotation`` (so the phase lies on the host plane of
    any running profile, on the device trace's clock), the engine's
    always-on accumulator behind ``stats()["tick_phases"]``, and a span of
    the ``TraceRecorder`` when one is attached.

    A phase is charged its self time: a phase opened inside another pauses
    it. Host time between two phases of a tick is charged to the one that
    follows, and what is left at the tick's end to ``serving.end_tick``, so
    the phases add up to the tick's wall time with no residual."""

    __slots__ = ("_eng", "_name", "_attrs", "_ann", "_span", "_tick")

    def __init__(self, eng, name, attrs):
        self._eng = eng
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        eng, name = self._eng, self._name
        self._ann = jax.profiler.TraceAnnotation(name)
        self._ann.__enter__()
        now = time.perf_counter()
        stack = eng._phase_stack
        if stack:
            top = stack[-1]
            eng._tick_acc[name if top == TICK else top] += now - eng._phase_t
        else:
            eng._tick_t0 = now
        eng._phase_t = now
        stack.append(name)
        self._tick = eng._stats["ticks"]
        tr = eng.tracing
        self._span = (tr.phase_begin(name, self._tick, now, self._attrs)
                      if tr is not None else None)
        return self

    def __exit__(self, *exc):
        eng, name = self._eng, self._name
        now = time.perf_counter()
        stack = eng._phase_stack
        stack.pop()
        eng._tick_acc["serving.end_tick" if name == TICK else name] += (
            now - eng._phase_t)
        eng._phase_t = now
        if self._span is not None:
            eng.tracing.phase_end(self._span, self._tick, now)
        if not stack:  # the outermost tick has closed
            eng._fold_tick(self._tick, now - eng._tick_t0)
        self._ann.__exit__(*exc)
        return False


# ---------------------------------------------------------------------------
# Host-side request bookkeeping
# ---------------------------------------------------------------------------


class _Request:
    __slots__ = (
        "id", "tokens", "budget", "rng", "slot", "lane", "chunks", "next_chunk",
        "consumed", "out", "submit_t", "admit_t", "first_dispatch_t",
        "first_token_t", "done_t", "token_t", "deadline", "retries", "status",
        "weights_version", "canary", "layout",
        "client_request_id", "recoveries", "spec_drafted", "spec_accepted",
    )

    def __init__(self, rid, tokens, budget, rng):
        self.id = rid
        self.tokens = tokens          # np.int32 1-D prompt
        self.budget = budget
        self.rng = rng
        self.slot = None
        self.lane = None              # prefill lane (disagg.py router only)
        self.chunks = None            # [(chunk_size, valid)] once admitted
        self.next_chunk = 0
        self.consumed = 0             # prompt tokens already in the cache
        self.out: list[int] = []      # sampled continuation (incl. EOS)
        self.submit_t = time.perf_counter()
        self.admit_t = None           # slot granted (TTFT = queue + prefill)
        self.first_dispatch_t = None  # its first prompt chunk is dispatched
        self.first_token_t = None
        self.done_t = None
        self.token_t: list[float] = []  # perf_counter stamp of each of `out`
        self.deadline = None          # absolute perf_counter SLO, or None
        self.retries = 0              # recovery resubmissions consumed
        self.status = None            # terminal: ok | timeout | shed | failed
        self.weights_version = None   # param version bound at first grant
        self.canary = False           # admitted inside a canary window
        self.layout = None            # topology generation bound at grant
        self.client_request_id = None  # caller's idempotency key (journal)
        self.recoveries = 0           # crash-restart replays (no retry spend)
        self.spec_drafted = 0         # draft tokens proposed for this request
        self.spec_accepted = 0        # draft tokens accepted (emitted early)

    def reset_for_retry(self) -> None:
        """Back to freshly-queued: prompt, budget, rng, deadline, the
        original submit_t, and the bound weights_version survive, so the
        resubmission is idempotent — the same per-request PRNG stream under
        the same param version replays bit-equal output."""
        self.slot = None
        self.lane = None
        self.chunks = None
        self.next_chunk = 0
        self.consumed = 0
        self.out = []
        self.admit_t = None
        self.first_dispatch_t = None
        self.first_token_t = None
        self.token_t = []
        self.spec_drafted = 0
        self.spec_accepted = 0


class _Chunk(NamedTuple):
    """One prompt chunk on its way to the device: ``ids`` (1, size)."""

    req: _Request
    ids: np.ndarray
    valid: int
    is_first: bool
    is_final: bool
    t0: Optional[float]  # perf_counter at its build, where a tracer or TTFT needs it


class _Step(NamedTuple):
    """A dispatched decode step, as the host knew it at dispatch: settled
    from this record (:meth:`ServingEngine._settle_step`), not from the
    engine's state when its outputs come back."""

    rows: dict                 # slot -> the request the step advanced there
    version: Any               # the weights version it ran under
    ride: Optional[_Chunk]     # the prompt chunk that rode it, or None
    flip_slot: Optional[int]   # a chaos bit flip to apply to its tokens
    out: tuple                 # device outputs: toks, emitted, done, bad[, tok, done0]
    tick: int
    t0: Optional[float]        # perf_counter at dispatch, where timed
    read_block: Optional[int]  # the decode kernel's block of rows, or None


TTFT_TERMS = ("queue_wait_s", "prefill_blocked_s", "prefill_own_s")


def _ttft_terms(req: _Request) -> tuple[float, float, float]:
    """A request's TTFT as :data:`TTFT_TERMS`: queued for a slot; granted
    one but behind other requests' chunks, or waiting for a lane; from its
    own first dispatch to its first token. They telescope to ``ttft_s``."""
    return (req.admit_t - req.submit_t,
            req.first_dispatch_t - req.admit_t,
            req.first_token_t - req.first_dispatch_t)


def timing_row_keys(req: Optional[_Request] = None) -> dict:
    """The timing keys of a ``poll()`` row: :data:`TTFT_TERMS` and each new
    token's seconds after submit. Without a request — a row shed by the
    fleet router, or one replayed from the journal, which keeps no
    timings — every one is None."""
    if req is None:
        return dict.fromkeys(TTFT_TERMS + ("token_times_s",))
    terms = (_ttft_terms(req) if req.first_token_t is not None
             else (None, None, None))
    return dict(zip(TTFT_TERMS, terms),
                token_times_s=[t - req.submit_t for t in req.token_t])


class ServingEngine:
    """Continuous-batching inference over one model.

    Built from a model with params on device (the object
    :func:`~accelerate_tpu.generation.generate` takes) and a
    :class:`~accelerate_tpu.utils.ServingConfig`; or from any custom
    generation plan via ``forward_cached`` (the registry contract:
    ``fwd(cfg, params, ids, cache, return_all=False)``). Pass
    ``compile_manager`` to source the prefill ladder from its seq-bucket
    policy, and ``telemetry`` to stream per-request TTFT/TPOT events and the
    serving summary into the PR-1 recorder.

    Where the plan is a built-in one that reads it
    (``generation.FUSED_QKV_PLANS``), each weights version is installed with
    the attention input projections fused (``generation.fuse_qkv_params``);
    a caller's own ``forward_cached`` is handed the model's layout.

    Robustness knobs: ``fault_tolerance`` (a
    :class:`~accelerate_tpu.fault_tolerance.FaultToleranceManager`) arms the
    preemption drain; ``chaos`` (a
    :class:`~accelerate_tpu.chaos.FaultInjector`) arms deterministic fault
    injection. Both default to None — the hot path then holds one ``is
    None`` check per site.
    """

    # Whether a built-in plan's versions are installed with q, k and v fused
    # (the disagg router keeps the model's layout on its decode mesh and lanes)
    _fuses_qkv = True

    def __init__(self, model, config=None, *, forward_cached: Optional[Callable] = None,
                 compile_manager=None, telemetry=None, fault_tolerance=None,
                 chaos=None, tracing=None, journal=None, profiler=None):
        from .utils.dataclasses import ServingConfig

        self.config = config if config is not None else ServingConfig()
        self.telemetry = telemetry
        self.fault_tolerance = fault_tolerance
        # Request-scoped tracing (tracing.py). Defaults to the telemetry
        # recorder's TraceRecorder (TelemetryKwargs(tracing=...)) so the
        # accelerator wiring enables both with one knob; a standalone
        # recorder can also be passed directly. None -> every hook is one
        # ``is None`` check, same zero-cost contract as telemetry/chaos.
        self.tracing = tracing if tracing is not None else getattr(
            telemetry, "tracing", None)
        # Device-time attribution (profiler.py DeviceTimeProfiler): each
        # tick feeds it a lagged term record made of the tick's phase
        # seconds (_fold_tick) — no extra device syncs. Defaults to the
        # telemetry recorder's profiler (TelemetryKwargs(profile=...));
        # same None contract.
        self._profiler = profiler if profiler is not None else getattr(
            telemetry, "profiler", None)
        # Crash-durable request journal (journal.py): ``journal=`` takes a
        # RequestJournal or a directory path; ``ServingConfig.journal_dir``
        # is the config-only spelling. None (the default everywhere) keeps
        # the WAL fully off — one ``is None`` check per hot-path site.
        jr = journal if journal is not None else self.config.journal_dir
        if jr is not None and isinstance(jr, (str, os.PathLike)):
            from .journal import RequestJournal

            jr = RequestJournal(
                str(jr), fsync=self.config.journal_fsync,
                segment_records=self.config.journal_segment_records,
            )
        self._journal = jr
        self._journal_tokens: dict[int, list[int]] = {}
        self._client_ids: dict[str, int] = {}
        self._cached_rows: dict[int, dict] = {}
        self._jstats = {"recovered_inflight": 0, "recovered_terminal": 0,
                        "deduped": 0}
        self.chaos = chaos
        name = type(model.module).__name__
        if forward_cached is not None:
            fwd = forward_cached
        else:
            if name in ENCDEC_GENERATION_PLANS:
                raise ValueError(
                    "ServingEngine serves causal-LM plans; encoder-decoder "
                    f"families ({name}) keep the static generate() path."
                )
            fwd = GENERATION_PLANS.get(name)
            if fwd is None:
                known = ", ".join(sorted(GENERATION_PLANS))
                raise ValueError(f"No generation plan for {name!r}; built-in: {known}")
        self._fwd = fwd
        self._bounded = _takes_attn_bound(fwd)
        self.cfg = model.module.config

        c = self.config
        self.n_slots = int(c.n_slots)
        max_pos = cache_spec(self.cfg).max_positions
        self.t_max = int(c.max_len) if c.max_len else int(min(max_pos, 4096))
        if self.t_max > max_pos:
            raise ValueError(
                f"ServingConfig.max_len={self.t_max} exceeds "
                f"max_position_embeddings={max_pos}"
            )
        if c.prefill_chunks:
            ladder = sorted({int(x) for x in c.prefill_chunks})
        elif compile_manager is not None:
            ladder = compile_manager.prefill_ladder(
                self.t_max, min_chunk=c.min_prefill_chunk,
                max_chunk=c.max_prefill_chunk,
            )
        else:
            ladder = default_prefill_ladder(
                self.t_max, c.min_prefill_chunk, c.max_prefill_chunk
            )
        self.ladder = [r for r in ladder if r <= self.t_max] or [self.t_max]

        eos = c.eos_token_id
        self.pad_token_id = c.pad_token_id if c.pad_token_id is not None else (
            eos if eos is not None else 0
        )
        self._speculate_k = int(getattr(c, "speculate_k", 0) or 0)
        self._spec_ngram = int(getattr(c, "speculate_ngram", 16) or 16)
        self._decode = _build_decode_step(
            fwd, self.cfg, c.temperature, c.top_k, c.top_p, eos,
            speculate_k=self._speculate_k,
        )
        self._prefill = _build_prefill_step(
            fwd, self.cfg, c.temperature, c.top_k, c.top_p, eos
        )
        # A tick that advances a prompt chunk runs it inside the decode step
        # (one program, the weights read once) where the forward takes a
        # chunk and the step decodes one token a slot; None: two programs.
        self._decode_chunk = (
            _build_decode_chunk_step(fwd, self.cfg, c.temperature, c.top_k, c.top_p, eos)
            if self._speculate_k == 0 and _takes_chunk(fwd) else None)
        self._chunks_per_tick = max(1, int(c.prefill_chunks_per_tick))
        # Cache, slot state, and params all enter the jitted programs
        # committed in place: the jit cache keys on placement commitment,
        # and commitment is infectious — with committed params, the cache
        # the first prefill RETURNS is committed even if the init-time one
        # was not, which would recompile that chunk size on its second call.
        # Published versions (device_put through the reshard executor) also
        # always arrive committed — an uncommitted initial tree would cost
        # one spurious decode recompile at the first hot swap.
        # The param tree the dispatch hooks feed the jitted programs, with
        # q, k and v fused where the plan reads that layout (one kernel a
        # stack: no step copies or lays out a projection weight). The
        # engine keeps no reference to the model's own tree, so a caller
        # that lets it go holds q, k and v once. The disaggregated router
        # (disagg.py) repoints this at the decode-mesh copy; the colocated
        # engine uses the model's own placement. A published version
        # arrives in the model's layout and is held to _params_template.
        params = _commit_params(model.params)
        self._params_template = _template_of(params)
        fuse = self._fuses_qkv and any(fwd is plan for plan in FUSED_QKV_PLANS)
        self._params, fused = fuse_qkv_params(params) if fuse else (params, False)
        # Static facts of the installed layout (stats()["layout"]).
        self._layout = {"qkv_fused": fused}
        # Cache and slot state are built in the params' own sharding form.
        # Params prepared by an Accelerator carry a NamedSharding over its
        # mesh even on one chip; next to them a default-placed cache comes
        # back from its first prefill as a NamedSharding too, and the rung
        # that ran first compiles a second time in steady state (seen on the
        # v5e: 6 prefill executables for a 5-rung ladder).
        place = _single_device_sharding_of(self._params)
        self._cache = _commit_params(jax.device_put(init_slot_cache(
            self.cfg, self.n_slots, self.t_max, dtype=c.cache_dtype
        ), place))
        self._state = _commit_params(jax.device_put(init_slot_state(
            self.n_slots, seed=c.seed,
            history=self._spec_ngram), place))
        # The cache as its owner describes it (stats()["cache"]).
        self._cache_shape = {
            "planes": self._cache.n_layers,
            "bytes_per_token": kv_bytes_per_token(self.cfg, dtype=self._cache.dtype),
        }
        # Weight publication (publish.py): params are double-buffered by
        # monotonic version. ``_params`` always aliases the PRIMARY version;
        # in-flight requests keep decoding whatever version they bound at
        # grant, retired versions are dropped once nothing references them.
        self._weights_version = 0
        self._params_by_version = {0: self._params}
        self._canary = None          # active canary window state, or None
        self._canary_acc = 0.0       # error-diffusion routing accumulator
        self._cohorts: dict[int, dict] = {}
        self._full_mask = np.ones((self.n_slots,), bool)
        # Topology generation: bumped by the disagg router's live resize so
        # in-flight requests can be told apart from post-resize admissions.
        # The colocated engine never resizes — the id stays 0 for life.
        self._active_layout_id = 0

        self._queue: deque[_Request] = deque()
        self._prefilling: deque[_Request] = deque()
        self._decoding: dict[int, _Request] = {}
        self._free: list[int] = list(range(self.n_slots - 1, -1, -1))
        self._used_slots: set[int] = set()
        self._finished: deque[dict] = deque()
        self._ids = itertools.count()
        self._decode_executables_baseline: Optional[int] = None
        self._first_submit_t: Optional[float] = None
        self._last_done_t: Optional[float] = None
        self._ttfts: list[float] = []
        self._tpots: list[float] = []
        # TTFT attribution, noted at each first token: (queued for a slot,
        # granted but behind other requests' chunks or waiting for a lane,
        # from its own first dispatch to its first token). The three
        # telescope to the request's ttft_s.
        self._ttft_terms: list[tuple[float, float, float]] = []
        # Gap before each fetched token since the request's previous one.
        self._token_gaps: deque[float] = deque(maxlen=_TOKEN_GAP_SAMPLE)
        # Rolling-window SLO aggregates (stats()["window"]): the lifetime
        # percentiles above average over the whole run, so a long healthy
        # prefix masks a current breach (and an early shed storm taints the
        # rates forever). The autoscaler and canary gates read this bounded
        # window instead.
        wn = max(1, int(getattr(c, "window_requests", 128) or 128))
        self._window: deque[dict] = deque(maxlen=wn)
        self._queue_depth_window: deque[int] = deque(maxlen=wn)
        self._stats = {
            "submitted": 0, "completed": 0, "ticks": 0, "decode_steps": 0,
            # decode steps dispatched while the one before was unsettled
            "steps_overlapped": 0,
            "prefill_chunks": 0, "prefill_chunks_fused": 0,
            "prefill_pad_tokens": 0, "tokens_out": 0,
            "prompt_tokens_in": 0,
            "slot_allocs": 0, "slot_reuses": 0, "occupancy_sum": 0,
            # Cache rows the decoding slots held, summed over decode steps.
            "live_rows_sum": 0, "read_rows_sum": 0,
            "peak_occupancy": 0, "queue_depth_sum": 0, "queue_samples": 0,
            "steady_recompiles": 0, "prefill_steady_recompiles": 0,
            # Seconds in each phase of the tick and in whole ticks
            # (stats()["tick_phases"]).
            "tick_wall_s": 0.0, **dict.fromkeys(TICK_PHASES, 0.0),
            # Speculative-decoding counters (stats()["speculation"] block +
            # the hub's accelerate_tpu_spec_* series). All zero when
            # speculate_k == 0.
            "spec_drafted": 0, "spec_accepted": 0, "spec_decode_tokens": 0,
            "spec_verify_s": 0.0,
        }
        # Robustness state: fault counters (the telemetry "faults" block),
        # quarantined slots (poisoned rows taken out of rotation), the
        # preemption-drain latch, and the hang-guard idle counter.
        self._fstats = {
            "sheds": 0, "timeouts": 0, "failed": 0, "retries": 0,
            "slot_quarantines": 0, "lane_quarantines": 0,
            "handoff_retries": 0, "handoff_delays": 0,
            "promoted": 0, "rolled_back": 0,
        }
        self._quarantined_slots: set[int] = set()
        self._poison_op = None       # lazily jitted chaos-only program
        self._spoil_op = None        # lazily jitted draft_mismatch program
        self._draining = False
        self._idle_ticks = 0
        # The decode step dispatched and not yet settled (tick()'s one-deep
        # pipeline), or None.
        self._outstanding: Optional[_Step] = None
        # The phases open right now (the tick at the bottom), the last
        # boundary's clock read, and the running tick's seconds per phase,
        # folded into _stats when the tick closes (_Phase, _fold_tick).
        self._phase_stack: list[str] = []
        self._phase_t = 0.0
        self._tick_t0 = 0.0
        self._tick_acc = dict.fromkeys(TICK_PHASES, 0.0)
        # _chunk_executables() when warmup() ended; None until then, and
        # then the programs that carry a chunk compile on demand and nothing
        # is watched.
        self._prefill_executables_warm: Optional[int] = None
        # Decode canary (sdc.py DecodeCanary): attached via
        # attach_sdc_canary(); every tick-end hook is a single None check.
        self._sdc_canary = None
        self._has_deadlines = self.config.deadline_s is not None
        if self.tracing is not None:
            # metrics_text() parity: the Prometheus snapshot reads the same
            # live stats() dict external callers see. register_gauges now
            # delegates to the unified MetricsHub (profiler.py) — one
            # renderer, one naming scheme across every exporter.
            self.tracing.register_gauges("serving", self.stats)
        # SLO burn-rate window on the hub: every terminal request feeds one
        # good/bad sample; the renderer exposes the burn rate and the
        # watchdog warns (once) on sustained budget overspend.
        self._hub = getattr(self.tracing, "hub", None) or getattr(
            telemetry, "hub", None)
        if self._hub is not None:
            self._hub.register_slo("serving_availability", 0.99)
            self._hub.register_provider(
                "spec", self._spec_metrics, replace=True)
            if self._journal is not None:
                self._hub.register_provider(
                    "journal", self._journal.stats, replace=True)

    @property
    def chaos(self):
        """The attached :class:`~accelerate_tpu.chaos.FaultInjector` (or
        None). A property so late attachment (the smokes arm chaos AFTER
        warmup, once ``reset_metrics`` re-zeroed the tick clock) still
        wires the tracing annotation callback."""
        return self._chaos

    @chaos.setter
    def chaos(self, injector) -> None:
        self._chaos = injector
        if injector is not None and self.tracing is not None:
            self.tracing.attach_chaos(injector)
        # The journal draws its torn-write faults from the same injector so
        # one seeded schedule covers serving + journal faults together.
        jr = getattr(self, "_journal", None)
        if jr is not None:
            jr.chaos = injector

    # -- request lifecycle -------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               rng: Optional[jax.Array] = None,
               deadline_s: Optional[float] = None,
               client_request_id: Optional[str] = None) -> int:
        """Queue one request; returns its id. ``prompt`` is a 1-D token id
        sequence; ``rng`` seeds this request's private sampling stream
        (default ``jax.random.key(0)`` — generate()'s default);
        ``deadline_s`` overrides ``ServingConfig.deadline_s`` for this
        request (seconds from submission — miss it and the request finishes
        ``timeout``).

        ``client_request_id`` is the caller's idempotency key (any string,
        unique per logical request). A duplicate submit with a seen key
        DEDUPES instead of re-running: it returns the original id, and if
        that request already finished, its cached terminal row is re-emitted
        to ``poll()`` — exactly-once completion at the API, across retries
        AND (with a journal attached) across crash-restart recovery.

        Admission control: with ``max_queue_depth`` set and the queue full,
        ``overload_policy`` decides — ``reject`` finishes THIS request
        ``shed`` immediately, ``shed_oldest`` drops the oldest queued
        request instead, ``block`` ticks the engine until a queue slot
        frees (bounded by the hang guard). Every path still returns an id
        whose result lands in ``poll()``."""
        cid = str(client_request_id) if client_request_id is not None else None
        if cid is not None:
            known = self._client_ids.get(cid)
            if known is not None:
                self._jstats["deduped"] += 1
                row = self._cached_rows.get(known)
                if row is not None:  # finished: re-emit the cached row
                    self._finished.append(dict(row))
                return known
        tokens = np.asarray(prompt, np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("empty prompt")
        budget = int(max_new_tokens if max_new_tokens is not None
                     else self.config.max_new_tokens)
        if budget < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {budget}")
        if int(tokens.size) + budget > self.t_max:
            raise ValueError(
                f"prompt ({tokens.size}) + max_new_tokens ({budget}) exceeds "
                f"the slot capacity T_max={self.t_max}; raise "
                "ServingConfig.max_len."
            )
        req = _Request(next(self._ids), tokens, budget,
                       rng if rng is not None else jax.random.key(0))
        req.client_request_id = cid
        if cid is not None:
            self._client_ids[cid] = req.id
        dl = deadline_s if deadline_s is not None else self.config.deadline_s
        if dl is not None:
            if float(dl) <= 0:
                raise ValueError(f"deadline_s must be > 0, got {dl}")
            req.deadline = req.submit_t + float(dl)
            self._has_deadlines = True
        self._stats["submitted"] += 1
        if self._first_submit_t is None:
            self._first_submit_t = req.submit_t
        if self._journal is not None:
            # The WAL admission record: everything a bit-equal replay needs
            # (prompt + serialized rng + budget) plus the deadline BUDGET in
            # monotonic-clock terms — never absolute wall time, so a clock
            # step during an outage cannot expire recovered requests.
            try:
                key_data = np.asarray(
                    jax.random.key_data(req.rng)).reshape(-1).tolist()
            except Exception:  # raw legacy uint32 key arrays
                key_data = np.asarray(req.rng).reshape(-1).tolist()
            self._journal.append({
                "t": "admit", "rid": req.id, "cid": cid,
                "tokens": tokens.tolist(), "budget": budget,
                "rng": key_data,
                "deadline_s": float(dl) if dl is not None else None,
                "t_mono": req.submit_t,
                "weights_version": self._weights_version,
            }, tick=self._stats["ticks"], unit=req.id)
        if self.tracing is not None:
            self.tracing.request_submitted(
                req.id, self._stats["ticks"], req.submit_t,
                prompt_tokens=int(tokens.size), budget=budget,
                deadline_s=float(dl) if dl is not None else None)
        if self._draining:  # preemption drain: nothing new gets in
            self._finish(req, "shed")
            return req.id
        cap = self.config.max_queue_depth
        if cap is not None and len(self._queue) >= cap:
            policy = self.config.overload_policy
            if policy == "reject":
                self._finish(req, "shed")
                return req.id
            if policy == "shed_oldest":
                self._finish(self._queue.popleft(), "shed")
            else:  # block: apply backpressure by running the engine
                while len(self._queue) >= cap and not self._draining:
                    self.tick()
                if self._draining:
                    self._finish(req, "shed")
                    return req.id
        self._queue.append(req)
        return req.id

    def poll(self) -> list[dict]:
        """Results finished since the last poll: ``{"id", "status",
        "tokens", "new_tokens", "ttft_s", "tpot_s", "weights_version",
        "attempt", "recovered"}`` —
        ``weights_version`` is the param version the request bound at grant
        (``None`` if it was shed before ever being granted a slot) and
        ``tokens`` is the
        full prompt+continuation row padded to ``prompt+budget`` with
        ``pad_token_id`` (generate()'s row layout). ``status`` is the
        request's explicit terminal state, one of
        :data:`REQUEST_STATUSES` (``ok`` | ``timeout`` | ``shed`` |
        ``failed``) — EVERY submitted id eventually shows up here with
        one. ``attempt`` counts executions (1 + retries + crash-restart
        recoveries) and ``recovered`` flags rows that crossed a crash: a
        cached pre-crash completion replayed from the journal, or an
        in-flight request re-run bit-equal after ``recover()``. The timing
        keys (:func:`timing_row_keys`): ``queue_wait_s`` +
        ``prefill_blocked_s`` + ``prefill_own_s`` = ``ttft_s``, and
        ``token_times_s``, each new token's seconds after ``submit`` (its
        first is ``ttft_s``; tokens of one fetch share a time)."""
        out = list(self._finished)
        self._finished.clear()
        return out

    @property
    def pending(self) -> int:
        """Requests not yet delivered (queued + prefilling + decoding,
        including any draining on a retired layout after a live resize)."""
        return (len(self._queue) + len(self._prefilling) + len(self._decoding)
                + len(self._extra_inflight()))

    # -- the tick ----------------------------------------------------------

    def tick(self) -> None:
        """One scheduler round: sweep deadlines (and the preemption latch),
        admit into free slots, advance one prompt chunk (up to
        ``prefill_chunks_per_tick``), dispatch one decode step for every
        live slot, then settle the step the tick before dispatched: fetch
        its tokens and book them. The device finds this tick's step queued
        behind the last one, so it runs while the host fetches and books
        (:meth:`_pipelined_step`); a step's tokens reach ``poll()`` one tick
        after its dispatch. A mixed-version tick settles first and then runs
        its groups in turn. The tick's last chunk rides the decode step, one
        program for both, where :meth:`_rides` says it can; the others run
        alone before it. Raises :class:`ServingStalledError` via the hang
        guard if ``max_idle_ticks`` rounds pass with pending requests and
        zero progress."""
        with self._phase(TICK):
            with self._phase("serving.admit"):
                snap = self._begin_tick()
                self._admit()
                self._sample_queue_depth()
            ride = None
            for i in range(self._chunks_per_tick):
                if not self._prefilling:
                    break
                req = self._prefilling[0]
                last = i == self._chunks_per_tick - 1 or sum(
                    len(r.chunks) - r.next_chunk for r in self._prefilling) == 1
                if last and self._rides(req):
                    ride = self._chunk_ready(req)
                    break
                self._prefill_one(req)
            if len(self._decode_groups()) > 1:
                # which slots each version's dispatch masks is read off the
                # rows the last step left
                self._settle()
                if self._decoding:
                    self._decode_tick()
            else:
                self._pipelined_step(ride)
            with self._phase("serving.end_tick"):
                self._end_tick(snap)

    def _phase(self, name: str, attrs: Optional[dict] = None) -> _Phase:
        """The one timing mechanism of the tick: see :class:`_Phase`.
        ``attrs`` go onto the recorder's span and are built by the caller
        only when a recorder is attached."""
        return _Phase(self, name, attrs)

    def _fold_tick(self, tick_no: int, wall_s: float) -> None:
        """A tick has closed: its phase seconds go into the always-on
        counters and, as the five sections of its term record, to the
        profiler when one is attached."""
        acc, s = self._tick_acc, self._stats
        s["tick_wall_s"] += wall_s
        for name in TICK_PHASES:
            s[name] += acc[name]
        prof = self._profiler
        if prof is not None:
            prof.on_tick(
                tick_no, wall_s,
                sections={
                    "admit_s": acc["serving.admit"],
                    "prefill_s": (acc["serving.prefill"]
                                  + acc["serving.first_token_fetch"]),
                    "decode_s": acc["serving.decode_dispatch"],
                    "host_fetch_s": acc["serving.decode_fetch"],
                    "bookkeeping_s": (acc["serving.bookkeeping"]
                                      + acc["serving.end_tick"]),
                },
                gauges={
                    "journal_lsn": (self._journal.stats()["appends"]
                                    if self._journal is not None else None),
                    "jit_cache": self.executable_counts(),
                    "occupancy": len(self._decoding),
                },
            )
        for name in TICK_PHASES:
            acc[name] = 0.0

    # -- robustness plumbing (shared with the disagg router's tick) --------

    def _sample_queue_depth(self) -> None:
        """One queue-depth sample per tick, feeding both the lifetime mean
        and the rolling window the autoscaler reads — shared by this tick
        and the disagg router's."""
        depth = len(self._queue)
        self._stats["queue_depth_sum"] += depth
        self._stats["queue_samples"] += 1
        self._queue_depth_window.append(depth)

    def _extra_inflight(self) -> list:
        """Requests in flight outside the active queues — the disagg
        router's draining layouts during a live resize. Colocated engines
        have none."""
        return []

    def _progress_marker(self) -> tuple:
        """Anything that changes when the engine moves: admissions, prefill
        chunks, decode steps, terminal results. Equal across a tick with
        requests pending == an idle tick (the hang-guard's definition)."""
        s = self._stats
        return (s["slot_allocs"], s["prefill_chunks"], s["decode_steps"],
                s["completed"], self._fstats["sheds"],
                self._fstats["timeouts"], self._fstats["failed"])

    def _begin_tick(self) -> tuple:
        ft = self.fault_tolerance
        if not self._draining and ft is not None and getattr(ft, "preempted", False):
            self._draining = True
            if _log_ok():
                logger.warning(
                    "serving: preemption signal — shedding %d queued "
                    "request(s), draining %d in flight, then exiting "
                    "resumable (code %d)",
                    len(self._queue),
                    len(self._prefilling) + len(self._decoding),
                    PREEMPTION_EXIT_CODE,
                )
            while self._queue:
                self._finish(self._queue.popleft(), "shed")
        if self._has_deadlines:
            self._expire_deadlines()
        return self._progress_marker()

    def _end_tick(self, snap: tuple) -> None:
        if self._journal is not None:
            if self._journal_tokens:
                # One batched progress record per tick (observability — a
                # recovery replays from scratch), then the tick's durability
                # point per the fsync policy.
                self._journal.append(
                    {"t": "progress", "tick": self._stats["ticks"],
                     "t_mono": time.perf_counter(),
                     "toks": self._journal_tokens},
                    tick=self._stats["ticks"])
                self._journal_tokens = {}
            self._journal.tick_flush()
        if self._chaos is not None:
            # The process-death draw sits AFTER the journal flush on
            # purpose: what the fsync policy promises durable IS durable
            # when the crash lands — the exact contract the game-day smoke
            # verifies.
            fault = self._chaos.draw("engine_crash", self._stats["ticks"])
            if fault is not None and fault.kind == "crash":
                self._hard_crash(fault)
        self._stats["ticks"] += 1
        if self.pending and self._progress_marker() == snap:
            self._idle_ticks += 1
            if self._idle_ticks >= int(self.config.max_idle_ticks):
                states = (
                    [f"{r.id}:queued" for r in self._queue]
                    + [f"{r.id}:prefilling(chunk {r.next_chunk}/{len(r.chunks or [])})"
                       for r in self._prefilling]
                    + [f"{r.id}:decoding(slot {s})"
                       for s, r in sorted(self._decoding.items())]
                )
                raise ServingStalledError(
                    f"serving engine made no progress for {self._idle_ticks} "
                    f"consecutive ticks with {self.pending} request(s) "
                    f"pending [{', '.join(states)}] — "
                    f"{len(self._quarantined_slots)}/{self.n_slots} slots "
                    "quarantined; see docs/troubleshooting.md"
                )
        else:
            self._idle_ticks = 0
        if self._sdc_canary is not None:
            # Deliberately the LAST thing in the tick: a canary mismatch may
            # quarantine a decode device and resize the engine live, and
            # nothing after this point touches engine state.
            self._sdc_canary.on_tick()

    def _expire_deadlines(self) -> None:
        now = time.perf_counter()
        stale = [r for r in list(self._queue) + list(self._prefilling)
                 + list(self._decoding.values()) + self._extra_inflight()
                 if r.deadline is not None and now >= r.deadline]
        for req in stale:
            self._evict(req, "timeout")

    @property
    def preempted(self) -> bool:
        """True once the preemption drain latched (the fault-tolerance
        manager saw SIGTERM); queued work is shed and nothing new admits."""
        return self._draining

    @property
    def preemption_exit_code(self) -> int:
        """The resumable exit code (75) a serving front-end should exit
        with after a preempted drain — the launch gang restarts it."""
        return PREEMPTION_EXIT_CODE

    def _grant(self, req: _Request, slot: int) -> None:
        """Grant ``slot`` to ``req`` and move it onto the prefill queue —
        shared by this scheduler and the disagg router's two-mesh _admit."""
        req.slot = slot
        req.layout = self._active_layout_id
        req.admit_t = time.perf_counter()
        req.chunks = plan_chunks(int(req.tokens.size), self.ladder)
        if req.weights_version is None or \
                req.weights_version not in self._params_by_version:
            # First grant binds a param version (canary routing decides
            # which); a recovery resubmission keeps its original binding so
            # the retry replays bit-equal.
            req.weights_version = self._route_version()
            req.canary = self._canary is not None
            if self._journal is not None and not self._journal_suppressed(req.id):
                self._journal.append(
                    {"t": "bind", "rid": req.id,
                     "weights_version": req.weights_version,
                     "t_mono": req.admit_t},
                    tick=self._stats["ticks"], unit=req.id)
        self._stats["slot_allocs"] += 1
        if slot in self._used_slots:
            self._stats["slot_reuses"] += 1
        self._used_slots.add(slot)
        self._prefilling.append(req)
        if self.tracing is not None:
            self.tracing.request_granted(
                req.id, self._stats["ticks"], req.admit_t, slot=slot,
                lane=req.lane, weights_version=req.weights_version,
                canary=bool(req.canary))

    def _admit(self) -> None:
        while self._free and self._queue:
            self._grant(self._queue.popleft(), self._free.pop())

    def _prefill_one(self, req: _Request) -> None:
        """Advance ``req`` by one prompt chunk in a program of its own: host
        bookkeeping here, device work in :meth:`_prefill_dispatch` (the hook
        the disagg router overrides to run the chunk on the prefill mesh and
        stream its KV page across)."""
        with self._prefill_phase(req):
            try:
                ch = self._next_chunk(req)
                tok, done0 = self._prefill_dispatch(req, ch.ids, ch.valid,
                                                    ch.is_first, ch.is_final)
            except RuntimeError as e:
                # InjectedFaultError or a real XLA runtime failure — recovery
                # is identical. Programming errors (TypeError etc.) still
                # propagate.
                self._on_prefill_failure(req, e)
                return
            self._chunk_sent(ch)
            if ch.is_final:
                # The TTFT moment is noted as it always was, when the final
                # chunk has been dispatched and before its token is fetched.
                req.first_token_t = time.perf_counter()
                with self._phase("serving.first_token_fetch"):
                    first = int(tok)
                self._armed(req)
                self._first_token(req, first, bool(done0))

    def _prefill_phase(self, req: _Request) -> _Phase:
        """``serving.prefill`` for ``req``'s next chunk: the span says whose."""
        size, _ = req.chunks[req.next_chunk]
        return self._phase("serving.prefill", None if self.tracing is None else {
            "request_id": req.id, "size": size,
            "final": req.next_chunk == len(req.chunks) - 1})

    def _next_chunk(self, req: _Request) -> _Chunk:
        """The host half of ``req``'s next chunk: its tokens, padded to the
        rung, and the chaos draw at ``prefill_dispatch`` (an
        :class:`InjectedFaultError` fails the chunk before any dispatch)."""
        size, valid = req.chunks[req.next_chunk]
        is_first = req.next_chunk == 0
        ids = np.zeros((1, size), np.int32)
        ids[0, :valid] = req.tokens[req.consumed:req.consumed + valid]
        t0 = time.perf_counter() if self.tracing is not None or is_first else None
        if is_first:
            req.first_dispatch_t = t0
        if self.chaos is not None:
            fault = self.chaos.draw("prefill_dispatch",
                                    self._stats["ticks"], unit=req.id)
            if fault is not None:
                raise InjectedFaultError(fault)
        return _Chunk(req, ids, valid, is_first,
                      req.next_chunk == len(req.chunks) - 1, t0)

    def _chunk_sent(self, ch: _Chunk) -> None:
        """A chunk has been dispatched: the request moves past it."""
        req, size = ch.req, ch.ids.shape[1]
        req.next_chunk += 1
        req.consumed += ch.valid
        self._stats["prefill_chunks"] += 1
        self._stats["prefill_pad_tokens"] += size - ch.valid
        self._watch_prefill_recompiles()
        if self.tracing is not None:
            self.tracing.prefill_chunk(
                req.id, self._stats["ticks"], ch.t0, time.perf_counter(),
                size=size, valid=ch.valid, lane=req.lane, slot=req.slot,
                index=req.next_chunk - 1, final=ch.is_final)

    def _armed(self, req: _Request) -> None:
        """A final chunk has been dispatched and armed ``req``'s slot: the
        request leaves the prefill queue and the next step decodes it."""
        self._prefilling.remove(req)
        self._decoding[req.slot] = req

    def _first_token(self, req: _Request, first: int, done0: bool) -> None:
        """An armed request's first token has been fetched: it is out, and
        the request decodes on or is done."""
        self._emit(req, (first,), req.first_token_t)
        # noted here and not at the finish, so that a request still
        # decoding when a window closes counts
        self._ttft_terms.append(_ttft_terms(req))
        if self.tracing is not None:
            self.tracing.first_token(req.id, self._stats["ticks"],
                                     req.first_token_t)
        if done0:
            del self._decoding[req.slot]
            self._retire(req)

    def _rides(self, req: _Request) -> bool:
        """Whether ``req``'s next chunk can ride this tick's decode step: the
        engine has the fused program, and the step is one dispatch under
        the request's own weights (not a canary's or a swap's mixed-version
        tick)."""
        if self._decode_chunk is None:
            return False
        groups = self._decode_groups()
        return len(groups) == 1 and groups[0][0] == req.weights_version

    def _chunk_ready(self, req: _Request) -> Optional[_Chunk]:
        """The host half of a chunk that rides the decode step, in
        ``serving.prefill``; None where it failed there (a chaos fault),
        and the decode step then runs alone."""
        with self._prefill_phase(req):
            try:
                return self._next_chunk(req)
            except RuntimeError as e:
                self._on_prefill_failure(req, e)
                return None

    def _emit(self, req: _Request, toks, t: float) -> None:
        """``toks`` of one fetch, stamped ``t``, join the request's output
        (and the journal's batch); the wait before each since the request's
        previous token joins the gap sample."""
        if not len(toks):
            return
        if req.token_t:
            self._token_gaps.append(t - req.token_t[-1])
            self._token_gaps.extend([0.0] * (len(toks) - 1))
        new = [int(x) for x in toks]
        req.out.extend(new)
        req.token_t.extend([t] * len(new))
        if self._journal is not None and not self._journal_suppressed(req.id):
            self._journal_tokens.setdefault(req.id, []).extend(new)

    def _chunk_executables(self) -> Optional[int]:
        """Executables of the programs that carry a prompt chunk: a rung
        each of ``prefill`` and of ``decode_chunk`` where it runs."""
        sizes = [_cache_size(p) for p in (self._prefill, self._decode_chunk) if p is not None]
        return None if None in sizes else sum(sizes)

    def _watch_prefill_recompiles(self) -> None:
        """After ``warmup()`` every rung of the ladder has its program; one
        more is a compile in steady state."""
        warm = self._prefill_executables_warm
        size = self._chunk_executables() if warm is not None else None
        if size is not None and size > warm:
            self._stats["prefill_steady_recompiles"] += size - warm
            self._prefill_executables_warm = size
            if _log_ok():
                logger.warning(
                    "serving: prefill compiled mid-flight (%d extra "
                    "executable(s)) — warmup() should have built every rung "
                    "of the ladder; see docs/usage_guides/serving.md.",
                    size - warm,
                )

    def _prefill_dispatch(self, req: _Request, chunk, valid: int,
                          is_first: bool, is_final: bool):
        """Device half of one prefill chunk: write it into the slot cache at
        the request's own offset. Returns ``(first_token, done0)`` (device
        scalars; only the final chunk's are fetched)."""
        self._cache, self._state, tok, done0 = self._prefill(
            self._params_for(req.weights_version), self._cache, self._state,
            chunk, np.int32(req.slot), np.int32(valid), np.int32(req.budget),
            req.rng, is_first, is_final,
        )
        return tok, done0

    def _decode_groups(self) -> list:
        """``(version, run_mask)`` dispatch plan for this tick. Steady state
        (every decoding slot on one version) is a single full-mask dispatch;
        a mixed-version window (mid-canary, or old requests draining after a
        swap) dispatches the SAME executable once per version with
        complementary slot masks."""
        versions = sorted({r.weights_version for r in self._decoding.values()})
        if len(versions) <= 1:
            v = versions[0] if versions else self._weights_version
            return [(v, self._full_mask)]
        groups = []
        for v in versions:
            mask = np.zeros((self.n_slots,), bool)
            for slot, r in self._decoding.items():
                if r.weights_version == v:
                    mask[slot] = True
            groups.append((v, mask))
        return groups

    def _decode_rows(self) -> dict:
        """``slot -> request`` of the decoding slots a step dispatched now
        would advance: those owed a token past what the outstanding step
        brings (a step emits at least one a live slot; a final chunk that
        rode it, the first). A slot the outstanding step finishes by its
        budget is left out; one it finishes by EOS the device masks."""
        step = self._outstanding

        def owed(slot, req):
            n = len(req.out)
            if step is not None and (step.rows.get(slot) is req or (
                    step.ride is not None and step.ride.req is req)):
                n += 1
            return n < req.budget

        return {s: r for s, r in self._decoding.items() if owed(s, r)}

    def _decode_faults(self, rows: dict) -> Optional[int]:
        """The tick's chaos draws at ``decode_tick`` (and ``draft_mismatch``
        under speculation) over the slots it advances: a poison is written
        into the cache before the dispatch; a bit flip's slot is returned,
        to be applied to the fetched tokens."""
        flip_slot = None
        if self.chaos is None or not rows:
            return None
        fault = self.chaos.draw("decode_tick", self._stats["ticks"])
        if fault is not None and fault.kind == "poison":
            self._poison_slot(min(rows))
        elif fault is not None and fault.kind == "bit_flip":
            # Silent decode corruption: the emitted token is XOR'd AFTER
            # the host fetch — device state untouched, output finite and
            # wrong. Only the decode canary (sdc.py) can see it.
            flip_slot = int((fault.extra or {}).get("slot", min(rows)))
        if self._speculate_k > 0:
            fault = self.chaos.draw("draft_mismatch", self._stats["ticks"])
            if fault is not None and fault.kind == "poison":
                # Spoil one slot's n-gram history: its drafts degenerate
                # (repeat-last-token fallback) so acceptance collapses,
                # but verification keeps the OUTPUT bit-equal — the
                # property the chaos smoke asserts.
                self._spoil_history(min(rows))
        return flip_slot

    def _pipelined_step(self, ride: Optional[_Chunk]) -> None:
        """A one-group tick: dispatch its step where it can do work (a
        riding chunk, or a slot owed a token), queued on the device behind
        the outstanding one, and only then settle that one. Every input of
        the new step is an output of the last on the device, so the host
        fetches and books while the device runs."""
        before = self._outstanding
        rows = self._decode_rows()
        self._outstanding = None
        if ride is not None or rows:
            flip_slot = self._decode_faults(rows)
            (version, mask), = self._decode_groups()
            self._outstanding = self._dispatch(version, mask, rows, ride, flip_slot)
            if before is not None and rows:
                self._stats["steps_overlapped"] += 1
        if before is not None:
            self._settle_step(before)

    def _settle(self) -> None:
        """Settle the outstanding step, if there is one: wherever the host's
        next choice depends on it (a mixed-version tick, a swap or canary,
        recovery, the end of warm-up)."""
        step, self._outstanding = self._outstanding, None
        if step is not None:
            self._settle_step(step)

    def _decode_tick(self) -> None:
        """One decode step for every live slot, each version group's
        dispatched and settled in turn: the disaggregated router's tick, and
        a mixed-version one (no chunk rides either)."""
        rows = self._decode_rows()
        flip_slot = self._decode_faults(rows)
        for version, mask in self._decode_groups():
            group = {s: r for s, r in rows.items()
                     if r.weights_version == version and mask[s]}
            flip = flip_slot if flip_slot is not None and mask[flip_slot] else None
            if flip is not None:
                flip_slot = None  # one flip per tick, not per version group
            self._settle_step(self._dispatch(version, mask, group, None, flip))

    def _dispatch(self, version, mask, rows: dict, ride: Optional[_Chunk],
                  flip_slot: Optional[int]) -> _Step:
        """The dispatch half of a decode step over ``rows`` (the fused
        program where ``ride`` is given, and the one fetch then brings the
        chunk's first token beside the decode tokens). Returns the step's
        record: what :meth:`_settle_step` books its outputs against."""
        tr = self.tracing
        k_spec = self._speculate_k
        with self._phase("serving.decode_dispatch"):
            t0 = time.perf_counter() if (tr is not None or k_spec > 0) else None
            # rows of a block where the step's attention reads by each
            # slot's bound, None where it reads every row of every slot
            read_block = decode_reads(self._cache) if self._bounded and k_spec == 0 else None
            if ride is None:
                self._cache, self._state, *out = self._decode(
                    self._params_for(version), self._cache, self._state, mask)
            else:
                req = ride.req
                self._cache, self._state, *out = self._decode_chunk(
                    self._params_for(version), self._cache, self._state, mask,
                    ride.ids, np.int32(req.slot), np.int32(ride.valid),
                    np.int32(req.budget), req.rng, ride.is_first, ride.is_final,
                )
                self._stats["prefill_chunks_fused"] += 1
                self._chunk_sent(ride)
                if ride.is_final:
                    # the TTFT moment: the final chunk's dispatch, as ever
                    req.first_token_t = time.perf_counter()
                    self._armed(req)
            # a step whose chunk rides with no slot decoding is a prefill:
            # it counts as no decode step
            if rows:
                self._stats["decode_steps"] += 1
                self._stats["occupancy_sum"] += len(rows)
                self._stats["peak_occupancy"] = max(self._stats["peak_occupancy"], len(rows))
                if read_block is None:
                    self._stats["read_rows_sum"] += self.n_slots * self.t_max
            if ride is None:
                if self.telemetry is not None:
                    # PR-1 recompile-watchdog cross-check: sample the decode
                    # step's executable cache exactly like a train step's —
                    # any mid-flight growth lands as a "recompile" event in
                    # the telemetry JSONL.
                    try:
                        self.telemetry._watch_recompiles(self._decode, out[0])
                    except Exception:
                        pass
                self._watch_decode_recompiles()
        return _Step(rows, version, ride, flip_slot, tuple(out),
                     self._stats["ticks"], t0, read_block)

    def _settle_step(self, step: _Step) -> None:
        """The settle half: one fetch of the step's tokens, emitted counts,
        done and nonfinite flags (and a riding chunk's ``(tok, done0)``),
        then the books, kept against the record of what the step advanced.
        A slot whose request has left it since (retired, quarantined,
        expired, or granted anew) is skipped."""
        tr = self.tracing
        k_spec = self._speculate_k
        with self._phase("serving.decode_fetch"):
            toks_np, emitted_np, done_np, bad_np, *first = jax.device_get(step.out)
        with self._phase("serving.bookkeeping"):
            t_fetch = time.perf_counter()  # this fetch's tokens' stamp
            if step.flip_slot is not None:
                toks_np = np.array(toks_np)
                toks_np[step.flip_slot, 0] ^= 1
            group_drafted = group_accepted = 0
            for slot, req in step.rows.items():
                if self._decoding.get(slot) is not req:
                    continue
                if bool(bad_np[slot]):
                    self._on_poisoned_slot(slot, req)
                    continue
                cnt = int(emitted_np[slot])
                # rows this step attended over: the prompt and every token
                # written so far, the one it wrote among them
                live_rows = req.tokens.size + len(req.out)
                self._stats["live_rows_sum"] += live_rows
                if step.read_block is not None:
                    self._stats["read_rows_sum"] += rows_read(live_rows, step.read_block)
                self._emit(req, toks_np[slot, :cnt], t_fetch)
                if k_spec > 0:
                    req.spec_drafted += k_spec
                    req.spec_accepted += max(cnt - 1, 0)
                    group_drafted += k_spec
                    group_accepted += max(cnt - 1, 0)
                    self._stats["spec_decode_tokens"] += cnt
                if bool(done_np[slot]):
                    del self._decoding[slot]
                    self._retire(req)
            if k_spec > 0:
                self._stats["spec_drafted"] += group_drafted
                self._stats["spec_accepted"] += group_accepted
                # Verify-time attribution: the whole speculative dispatch
                # IS the k+1-position verification forward.
                self._stats["spec_verify_s"] += time.perf_counter() - step.t0
            if tr is not None and step.rows:
                tr.decode_tick(step.tick, step.t0, time.perf_counter(),
                               weights_version=step.version,
                               occupancy=len(step.rows), n_slots=self.n_slots,
                               request_ids=[r.id for r in step.rows.values()],
                               drafted=group_drafted, accepted=group_accepted)
            ride = step.ride
            if ride is not None and ride.is_final and \
                    self._decoding.get(ride.req.slot) is ride.req:
                self._first_token(ride.req, int(first[0]), bool(first[1]))

    def _watch_decode_recompiles(self) -> None:
        size = _cache_size(self._decode)
        if size is not None:
            if self._decode_executables_baseline is None:
                self._decode_executables_baseline = size
            elif size > self._decode_executables_baseline:
                extra = size - self._decode_executables_baseline
                self._stats["steady_recompiles"] += extra
                self._decode_executables_baseline = size
                if _log_ok():
                    logger.warning(
                        "serving: decode step recompiled mid-flight (%d "
                        "extra executable(s)) — the steady state should be "
                        "exactly one program; see "
                        "docs/usage_guides/serving.md.", extra,
                    )

    def _retire(self, req: _Request) -> None:
        """Natural completion: the device row already flagged itself done, so
        the slot goes straight back to the free list."""
        self._free.append(req.slot)
        self._finish(req, "ok")

    def _finish(self, req: _Request, status: str) -> None:
        """The single terminal gate: EVERY submitted request exits through
        here exactly once, with an explicit status."""
        req.status = status
        req.done_t = time.perf_counter()
        self._last_done_t = req.done_t
        n_new = len(req.out)
        row = np.concatenate([
            req.tokens,
            np.asarray(req.out, np.int32),
            np.full((req.budget - n_new,), self.pad_token_id, np.int32),
        ])
        ttft = (req.first_token_t - req.submit_t
                if req.first_token_t is not None else None)
        tpot = ((req.done_t - req.first_token_t) / (n_new - 1)
                if req.first_token_t is not None and n_new > 1 else 0.0)
        if status == "ok":
            self._ttfts.append(ttft)
            self._tpots.append(tpot)
            # Throughput/latency aggregates stay ok-only, so a shed storm
            # can't flatter (or taint) the SLO numbers.
            self._stats["completed"] += 1
            self._stats["tokens_out"] += n_new
            self._stats["prompt_tokens_in"] += int(req.tokens.size)
        else:
            self._fstats[{"timeout": "timeouts", "shed": "sheds",
                          "failed": "failed"}[status]] += 1
        self._window.append({
            "status": status, "ttft_s": ttft, "tpot_s": tpot,
            "prompt_tokens": int(req.tokens.size), "new_tokens": n_new,
        })
        if self._hub is not None:
            # One good/bad sample per terminal request into the hub's SLO
            # rolling window ("shed" during a preemption drain still counts
            # against availability — the client saw a non-answer).
            self._hub.observe_slo("serving_availability", status == "ok")
        if req.canary and req.weights_version in self._cohorts:
            self._cohorts[req.weights_version]["events"].append({
                "status": status, "ttft_s": ttft, "tpot_s": tpot,
            })
        attempt = 1 + req.retries + req.recoveries
        result = {
            "id": req.id, "status": status, "tokens": row, "new_tokens": n_new,
            "ttft_s": ttft, "tpot_s": tpot,
            "weights_version": req.weights_version,
            "attempt": attempt, "recovered": req.recoveries > 0,
            "drafted": req.spec_drafted, "accepted": req.spec_accepted,
            **timing_row_keys(req),
        }
        self._finished.append(result)
        if req.client_request_id is not None:
            # Exactly-once at the API: a duplicate submit with this key
            # re-emits the cached row instead of re-running the request.
            self._cached_rows[req.id] = result
        if self._journal is not None and not self._journal_suppressed(req.id):
            self._journal_tokens.pop(req.id, None)
            # Terminal rows are self-contained (the full padded token row
            # rides along) so compaction can retire the request's working
            # records while dedupe + crash-restart cached replies survive.
            self._journal.append({
                "t": "terminal", "rid": req.id,
                "cid": req.client_request_id, "status": status,
                "row": row.tolist(), "new_tokens": n_new,
                "ttft_s": ttft, "tpot_s": tpot,
                "weights_version": req.weights_version,
                "attempt": attempt, "t_mono": req.done_t,
                "drafted": req.spec_drafted, "accepted": req.spec_accepted,
            }, tick=self._stats["ticks"], unit=req.id)
        if len(self._params_by_version) > 1:
            self._gc_versions()
        if self.tracing is not None:
            self.tracing.request_finished(
                req.id, self._stats["ticks"], req.done_t, status=status,
                new_tokens=n_new, weights_version=req.weights_version,
                drafted=req.spec_drafted, accepted=req.spec_accepted)
        if self.telemetry is not None:
            self.telemetry.record_event(
                "serving_request_done", request_id=req.id, status=status,
                ttft_s=ttft, tpot_s=tpot, new_tokens=n_new,
                prompt_tokens=int(req.tokens.size), slot=req.slot,
                weights_version=req.weights_version,
            )
            if status != "ok":
                self.telemetry.record_event(
                    "serving_fault", request_id=req.id, status=status,
                    retries=req.retries,
                )

    # -- failure recovery --------------------------------------------------

    def _evict(self, req: _Request, status: str) -> None:
        """Terminate an in-flight request (deadline miss, shed): pull it out
        of whichever stage holds it, free its lane/slot IMMEDIATELY (the
        device row is killed so the next decode step masks it), finish with
        ``status``."""
        if req in self._queue:
            self._queue.remove(req)
        elif req in self._prefilling:
            self._prefilling.remove(req)
        elif req.slot is not None and self._decoding.get(req.slot) is req:
            del self._decoding[req.slot]
        self._release_lane(req)
        if req.slot is not None:
            self._purge_slot(req.slot)
            self._release_slot(req.slot)
        self._finish(req, status)

    def _release_slot(self, slot: int) -> None:
        """Free a slot whose occupant left mid-flight: mark the device row
        done (so decode masks it) and return it to the pool."""
        self._state = _release_step(self._state, np.int32(slot))
        self._free.append(slot)

    def _release_lane(self, req: _Request, failed: bool = False) -> None:
        """Disagg-router hook: return (or quarantine) ``req``'s prefill
        lane. Colocated engines have no lanes — no-op."""

    def _purge_slot(self, slot: int) -> None:
        """Disagg-router hook: drop any in-flight KV-page handoffs targeting
        ``slot`` so a stale page can never land in the next grant. Colocated
        engines stream nothing — no-op."""

    def _retry_or_fail(self, req: _Request, reason: str = "") -> None:
        """Idempotent recovery resubmission: reset the request to
        freshly-queued (same prompt, budget, rng → bit-equal replay) and
        put it at the HEAD of the queue, or finish ``failed`` once
        ``max_retries`` is spent."""
        if self._draining or req.retries >= int(self.config.max_retries):
            if _log_ok():
                logger.warning(
                    "serving: request %d failed permanently after %d retr%s%s",
                    req.id, req.retries, "y" if req.retries == 1 else "ies",
                    f" ({reason})" if reason else "",
                )
            self._finish(req, "failed")
            return
        req.retries += 1
        self._fstats["retries"] += 1
        req.reset_for_retry()
        self._queue.appendleft(req)
        if self.tracing is not None:
            self.tracing.request_retry(req.id, self._stats["ticks"],
                                       reason=reason or "retry",
                                       attempt=req.retries)

    def _on_prefill_failure(self, req: _Request, exc: Exception) -> None:
        """A prefill chunk dispatch (or disagg handoff) failed after its own
        local retries: free everything the request held, then resubmit or
        fail it."""
        if _log_ok():
            logger.warning("serving: prefill failed for request %d: %s",
                           req.id, exc)
        if req in self._prefilling:
            self._prefilling.remove(req)
        self._release_lane(req, failed=True)
        if req.slot is not None:
            self._purge_slot(req.slot)
            self._release_slot(req.slot)
            req.slot = None
        self._retry_or_fail(req, reason=str(exc))

    def _on_poisoned_slot(self, slot: int, req: _Request) -> None:
        """The decode sentinel flagged nonfinite logits in ``slot``: its KV
        page is corrupt, so the slot leaves rotation for good and the
        request replays from scratch elsewhere."""
        del self._decoding[slot]
        self._quarantine_slot(slot)
        req.slot = None
        if req.canary and req.weights_version in self._cohorts:
            # The canary SLO comparison counts sentinel trips per cohort — a
            # candidate that NaNs under load must read as a regression.
            self._cohorts[req.weights_version]["poisoned"] += 1
        self._retry_or_fail(req, reason=f"nonfinite logits in slot {slot}")

    def _quarantine_slot(self, slot: int) -> None:
        self._quarantined_slots.add(slot)
        self._fstats["slot_quarantines"] += 1
        if self.tracing is not None:
            self.tracing.quarantine("slot", slot, self._stats["ticks"])
        self._state = _release_step(self._state, np.int32(slot))
        if _log_ok():
            logger.warning(
                "serving: quarantined slot %d (nonfinite logits — poisoned "
                "KV page); %d/%d slots remain", slot,
                self.n_slots - len(self._quarantined_slots), self.n_slots,
            )
        if self.telemetry is not None:
            self.telemetry.record_event("serving_slot_quarantined", slot=slot)

    def _poison_slot(self, slot: int) -> None:
        """Chaos-only: overwrite ``slot``'s KV page with NaN so the decode
        sentinel must catch it. A separate lazily-jitted program — never
        compiled unless a poison fault actually fires, so the decode
        executable census is untouched."""
        if not self._cache.holds_nan:
            if _log_ok():
                logger.warning_once(
                    "serving: poison fault skipped — cache dtype "
                    f"{self._cache.dtype} has no NaN"
                )
            return
        if self._poison_op is None:
            self._poison_op = jax.jit(
                lambda cache, slot: cache.fill_slot(slot, jnp.nan), donate_argnums=(0,))
        self._cache = self._poison_op(self._cache, np.int32(slot))

    def _spoil_history(self, slot: int) -> None:
        """Chaos-only (``draft_mismatch``): blank one slot's n-gram history
        so its self-drafts degenerate — acceptance collapses while the
        verified OUTPUT stays bit-equal. A separate lazily-jitted program,
        like :meth:`_poison_slot`, so the decode census is untouched."""
        if self._spoil_op is None:
            def spoil(state: SlotState, slot):
                return state._replace(history=state.history.at[slot].set(-1))
            self._spoil_op = jax.jit(spoil, donate_argnums=(0,))
        self._state = self._spoil_op(self._state, np.int32(slot))

    # -- crash durability (the journal.py write-ahead log) -----------------

    @property
    def journal(self):
        """The attached :class:`~accelerate_tpu.journal.RequestJournal`
        (or None — journaling is off by default)."""
        return self._journal

    def _hard_crash(self, fault) -> None:
        """An injected ``engine_crash``: die like a real serving-process
        death — no drain, no journal seal (what the fsync policy promised
        durable is the contract under test) — after dumping the flight
        ring and flushing telemetry + the injector's log so the
        post-mortem schedule is never torn."""
        from .chaos import flush_injected_log
        from .profiler import dump_flight

        code = int((fault.extra or {}).get(
            "exit_code", SERVING_CRASH_EXIT_CODE))
        if _log_ok():
            logger.error(
                "serving: injected engine_crash — exiting %d (tick %d); "
                "%d request(s) in flight%s", code, self._stats["ticks"],
                self.pending,
                "" if self._journal is None else
                " — recover() replays them from the journal",
            )
        if self.telemetry is not None:
            try:
                self.telemetry.record_event(
                    "serving_engine_crash", tick=self._stats["ticks"],
                    exit_code=code, pending=self.pending,
                    journaled=self._journal is not None,
                )
            except Exception:  # pragma: no cover - dying anyway
                pass
        flush_injected_log(self._chaos, self.telemetry)
        # Flight dump LAST: the flush above folded the injector's schedule
        # into the ring's gauges and finalized the lagged tick record, so
        # the bundle's newest entries identify the tick that was dying.
        dump_flight(self._profiler, code,
                    reason=f"injected engine_crash at tick "
                           f"{self._stats['ticks']}")
        os._exit(code)

    def recover(self, journal_dir: Optional[str] = None) -> dict:
        """Rebuild request state from the write-ahead journal after a
        process death. Call on a freshly constructed (and ideally warmed)
        engine over the SAME journal directory — via the attached journal,
        or ``journal_dir`` when the engine was built without one.

        - Requests with a journaled terminal status return their CACHED
          rows through ``poll()`` (flagged ``recovered``) — they are never
          re-executed, and their ``client_request_id`` keys keep deduping
          duplicate submits: exactly-once completion across the crash.
        - In-flight requests re-enter the queue in admission order and
          replay from their original prompt + rng via the same
          ``reset_for_retry`` idempotency contract — bit-equal output under
          the same weights version — WITHOUT spending a ``max_retries``
          attempt (``recoveries``, not ``retries``; their ``poll()`` rows
          carry ``recovered: True`` and the bumped ``attempt``).
        - Remaining deadline budget is re-anchored on THIS process's
          monotonic clock: the journal stores ``deadline_s`` plus
          ``t_mono`` stamps, so elapsed pre-crash runtime is charged but a
          wall-clock step during the outage is not.

        The decode executable census is untouched — recovery is pure host
        bookkeeping feeding the existing admission path. Returns a summary
        dict (recovered counts + journal scan stats)."""
        self._settle()
        if self._journal is None and journal_dir is not None:
            from .journal import RequestJournal

            # An explicit foreign directory — some dead engine's WAL this
            # engine is taking over. Claim the adoption sentinel first so a
            # fleet router draining the same cell can't also replay it
            # (double adoption is double execution); raises
            # JournalAdoptionError if someone else already holds it. The
            # claim transfers ownership: this engine keeps journaling here
            # and releases the sentinel on close().
            self._journal = RequestJournal.adopt(
                str(journal_dir), f"serving-recover:pid={os.getpid()}",
                fsync=self.config.journal_fsync,
                segment_records=self.config.journal_segment_records,
            )
            self._journal.chaos = self._chaos
            if self._hub is not None:
                self._hub.register_provider(
                    "journal", self._journal.stats, replace=True)
        if self._journal is None:
            raise ValueError(
                "recover() needs a journal: pass journal_dir=, set "
                "ServingConfig.journal_dir, or construct the engine with "
                "journal=."
            )
        if not self._journal.adopted:
            # The restarting-supervisor side of the same race: if a fleet
            # router claimed this directory (it is draining — or already
            # drained — these requests onto surviving cells), replaying
            # them here too would double-execute.
            holder = self._journal.adoption_holder()
            if holder is not None:
                from .journal import JournalAdoptionError

                raise JournalAdoptionError(
                    f"journal {self._journal.dir!r} is adopted by "
                    f"{holder.get('owner', '<unreadable>')!r} — its requests "
                    "were drained elsewhere; relaunch with a fresh "
                    "journal_dir instead of replaying this one"
                )
        t_start = time.perf_counter()
        tr = self.tracing
        span = (tr.begin("serving", "recover", self._stats["ticks"])
                if tr is not None else None)
        records, scan = self._journal.replay()
        admits: dict[int, dict] = {}
        terminals: dict[int, dict] = {}
        binds: dict[int, int] = {}
        recovers: dict[int, int] = {}
        last_mono = None
        for rec in records:
            tm = rec.get("t_mono")
            if tm is not None:
                last_mono = tm if last_mono is None else max(last_mono, tm)
            rid = rec.get("rid")
            t = rec.get("t")
            if rid is None:
                continue
            rid = int(rid)
            if t == "admit":
                admits[rid] = rec
            elif t == "terminal":
                terminals[rid] = rec
            elif t == "bind" and rec.get("weights_version") is not None:
                binds[rid] = int(rec["weights_version"])
            elif t == "recovered":
                recovers[rid] = recovers.get(rid, 0) + 1
        now = time.perf_counter()
        n_terminal = n_inflight = 0
        # Union, not just admits: compaction retires the admit of a finished
        # request (the terminal row is self-contained), so after a compact +
        # crash a cached reply may exist with no admit left on disk.
        for rid in sorted(set(admits) | set(terminals)):
            a = admits.get(rid)
            trec = terminals.get(rid)
            cid = a.get("cid") if a is not None else trec.get("cid")
            if trec is not None:
                result = {
                    "id": rid, "status": trec.get("status"),
                    "tokens": np.asarray(trec.get("row", []), np.int32),
                    "new_tokens": int(trec.get("new_tokens", 0)),
                    "ttft_s": trec.get("ttft_s"),
                    "tpot_s": trec.get("tpot_s"),
                    "weights_version": trec.get("weights_version"),
                    "attempt": int(trec.get("attempt", 1)),
                    "recovered": True,
                    "drafted": int(trec.get("drafted", 0)),
                    "accepted": int(trec.get("accepted", 0)),
                    **timing_row_keys(),
                }
                self._finished.append(result)
                self._cached_rows[rid] = result
                if cid is not None:
                    self._client_ids[str(cid)] = rid
                n_terminal += 1
                continue
            try:
                rng = jax.random.wrap_key_data(
                    jnp.asarray(a["rng"], jnp.uint32))
            except Exception:
                rng = jax.random.key(0)
            req = _Request(rid, np.asarray(a["tokens"], np.int32),
                           int(a["budget"]), rng)
            req.client_request_id = str(cid) if cid is not None else None
            # Crash replays spend `recoveries`, never the retry budget; the
            # journaled recover markers make the count survive repeated
            # crashes.
            req.recoveries = recovers.get(rid, 0) + 1
            dl = a.get("deadline_s")
            if dl is not None:
                elapsed = 0.0
                if last_mono is not None and a.get("t_mono") is not None:
                    # Pre-crash runtime in the DEAD process's own monotonic
                    # epoch — comparable stamps by construction, immune to
                    # any wall-clock step during the outage.
                    elapsed = max(0.0, float(last_mono) - float(a["t_mono"]))
                req.deadline = now + max(0.0, float(dl) - elapsed)
                self._has_deadlines = True
            v = binds.get(rid)
            if v is not None and v in self._params_by_version:
                # _grant keeps an existing binding, so the replay decodes
                # under the SAME weights — bit-equal. A version that no
                # longer exists in this process rebinds at grant (reported
                # via the row's weights_version).
                req.weights_version = v
            if req.client_request_id is not None:
                self._client_ids[req.client_request_id] = rid
            self._queue.append(req)
            self._journal.append(
                {"t": "recovered", "rid": rid, "tick": self._stats["ticks"],
                 "t_mono": now},
                tick=self._stats["ticks"], unit=rid)
            self._stats["submitted"] += 1
            n_inflight += 1
            if tr is not None:
                tr.request_retry(rid, self._stats["ticks"],
                                 reason="recovered",
                                 attempt=req.retries + req.recoveries)
        if admits or terminals:
            # Fresh ids must never collide with journaled ones.
            self._ids = itertools.count(max([*admits, *terminals]) + 1)
        self._journal.tick_flush()
        self._jstats["recovered_inflight"] += n_inflight
        self._jstats["recovered_terminal"] += n_terminal
        summary = {
            "recovered_inflight": n_inflight,
            "recovered_terminal": n_terminal,
            "records": scan["records"],
            "segments": scan["segments"],
            "torn_tails": scan["torn_tails"],
            "corrupt_skipped": scan["corrupt_skipped"],
            "elapsed_s": round(time.perf_counter() - t_start, 6),
        }
        if span is not None:
            tr.end(span, self._stats["ticks"],
                   recovered_inflight=n_inflight,
                   recovered_terminal=n_terminal,
                   torn_tails=scan["torn_tails"],
                   corrupt_skipped=scan["corrupt_skipped"])
        if self.telemetry is not None:
            try:
                self.telemetry.record_event("serving_recovered", **summary)
            except Exception:
                pass
        if _log_ok():
            logger.info(
                "serving: recovered from journal %s — %d in-flight request(s) "
                "re-queued for bit-equal replay, %d cached terminal row(s) "
                "(%d torn tail(s) truncated, %d corrupt record(s) skipped)",
                self._journal.dir, n_inflight, n_terminal,
                scan["torn_tails"], scan["corrupt_skipped"],
            )
        return summary

    # -- weight publication (the publish.py hot-swap seam) -----------------

    @property
    def weights_version(self) -> int:
        """Monotonic version tag of the PRIMARY param tree — the one new
        admissions bind outside a canary window (0 = the construction-time
        weights)."""
        return self._weights_version

    def _params_for(self, version):
        """The param tree a request bound at grant. Versions stay installed
        until every in-flight reference drains, so this never misses."""
        if version == self._weights_version:
            return self._params
        return self._params_by_version[version]

    def _route_version(self) -> int:
        """Version for a fresh grant. Outside a canary window: the primary.
        Inside one: an error-diffusion accumulator routes EXACTLY the
        configured fraction of admissions to the candidate (deterministic —
        no RNG — so a chaos replay routes identically)."""
        c = self._canary
        if c is None:
            return self._weights_version
        self._canary_acc += c["fraction"]
        if self._canary_acc >= 1.0 - 1e-9:
            self._canary_acc -= 1.0
            c["routed_candidate"] += 1
            return c["version"]
        c["routed_primary"] += 1
        return self._weights_version

    def _install_params(self, params, version: int) -> None:
        """Placement hook: bind ``params`` (already validated) as ``version``,
        in the installed layout: one jitted call per version, never inside a
        step. The disagg router overrides this to place the decode-mesh copy
        and the per-lane prefill copies."""
        params = _commit_params(params)
        if self._layout["qkv_fused"]:
            params, _ = fuse_qkv_params(params)
        self._params_by_version[int(version)] = params

    def _drop_params(self, version: int) -> None:
        """Placement hook: release a retired version's buffers."""
        self._params_by_version.pop(int(version), None)

    def _gc_versions(self) -> None:
        """Drop param versions that are neither primary, candidate, nor
        referenced by any in-flight request — the moment the last old-version
        request drains, the old buffers go."""
        keep = {self._weights_version}
        if self._canary is not None:
            keep.add(self._canary["version"])
        for r in itertools.chain(self._queue, self._prefilling,
                                 self._decoding.values(),
                                 self._extra_inflight()):
            if r.weights_version is not None:
                keep.add(r.weights_version)
        for v in [v for v in self._params_by_version if v not in keep]:
            self._drop_params(v)

    def _validate_params_tree(self, params) -> None:
        """The guarded swap seam: the incoming tree, in the model's layout,
        must match the serving template (``_params_template``) leaf-for-leaf
        in structure, shape, dtype, AND sharding, and every leaf must already
        be a committed device array — anything else would silently recompile
        the decode step (new avals/shardings) or crash mid-tick, so it is
        rejected here with the offending leaf named."""
        from .parallel.sharding import _path_to_name

        cur = self._params_template
        ref = jax.tree_util.tree_structure(cur)
        got = jax.tree_util.tree_structure(params)
        if ref != got:
            raise ValueError(
                "swap_params: param tree structure does not match the "
                f"serving tree (serving {ref.num_leaves} leaves, got "
                f"{got.num_leaves}); publish the same model family/config "
                "the engine was built with."
            )
        new_leaves = jax.tree_util.tree_leaves(params)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(cur)[0], new_leaves):
            name = _path_to_name(path)
            if not isinstance(b, jax.Array):
                raise ValueError(
                    f"swap_params: leaf {name!r} is {type(b).__name__}, not "
                    "a committed jax.Array — redistribute onto the serving "
                    "placement first (publish.py does this via the reshard "
                    "executor)."
                )
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(
                    f"swap_params: leaf {name!r} is {b.shape}/{b.dtype}, "
                    f"serving expects {a.shape}/{a.dtype}."
                )
            sa = getattr(a, "sharding", None)
            sb = getattr(b, "sharding", None)
            if sa is not None and sb is not None and \
                    not sb.is_equivalent_to(sa, a.ndim):
                raise ValueError(
                    f"swap_params: leaf {name!r} sharding {sb} is not "
                    f"equivalent to the serving sharding {sa} — a mismatch "
                    "here would recompile the ONE decode executable."
                )

    def _check_new_version(self, weights_version) -> int:
        v = int(weights_version)
        if v <= self._weights_version:
            raise ValueError(
                f"weights_version {v} is not newer than the serving primary "
                f"{self._weights_version}; versions are monotonic (train "
                "step)."
            )
        if self._canary is not None:
            raise ValueError(
                f"a canary for version {self._canary['version']} is active; "
                "promote or roll it back before publishing again."
            )
        return v

    def swap_params(self, params, *, weights_version: int) -> None:
        """Full cutover: validate ``params`` against the serving tree and
        bind them as the new PRIMARY version. In-flight requests finish on
        the version they bound at grant (the old buffers stay installed
        until they drain); every admission from now on binds the new one.
        Zero downtime, zero decode recompiles (params are a non-donated
        argument of the ONE decode executable)."""
        self._settle()
        v = self._check_new_version(weights_version)
        self._validate_params_tree(params)
        self._install_params(params, v)
        self._weights_version = v
        self._params = self._params_by_version[v]
        self._gc_versions()
        # Per-publish event; the publisher already logs the bind at INFO,
        # so the engine-side echo stays at debug.
        if _log_ok():
            logger.debug("serving: params swapped to version %d", v)

    def begin_canary(self, params, *, weights_version: int,
                     fraction: float = 0.1) -> None:
        """Install ``params`` as a CANDIDATE version and start routing
        ``fraction`` of new admissions to it (error-diffusion — the realized
        fraction is exact, not stochastic). Primary traffic continues
        untouched; per-cohort SLO samples accumulate until
        :meth:`promote_canary` or :meth:`rollback_canary` ends the window
        (publish.py's ``WeightPublisher`` drives that decision)."""
        self._settle()
        if not 0.0 < float(fraction) <= 1.0:
            raise ValueError(f"canary fraction must be in (0, 1], got {fraction}")
        v = self._check_new_version(weights_version)
        self._validate_params_tree(params)
        self._install_params(params, v)
        self._canary = {
            "version": v, "fraction": float(fraction),
            "routed_candidate": 0, "routed_primary": 0,
            "started_tick": self._stats["ticks"],
        }
        self._canary_acc = 0.0
        self._cohorts = {
            self._weights_version: {"events": [], "poisoned": 0},
            v: {"events": [], "poisoned": 0},
        }

    def promote_canary(self) -> dict:
        """End the canary window by making the candidate PRIMARY. In-flight
        old-version requests drain on the old buffers (then they are GC'd);
        all new admissions bind the promoted version."""
        self._settle()
        c = self._require_canary()
        self._canary = None
        self._weights_version = c["version"]
        self._params = self._params_by_version[c["version"]]
        self._fstats["promoted"] += 1
        self._gc_versions()
        if _log_ok():
            logger.info(
                "serving: canary promoted — version %d is primary "
                "(%d canary / %d primary admissions in the window)",
                c["version"], c["routed_candidate"], c["routed_primary"],
            )
        return c

    def rollback_canary(self) -> dict:
        """End the canary window by discarding the candidate: new admissions
        bind the (never unbound) primary again — bit-equal to never having
        published. Candidate-bound in-flight requests finish on the
        candidate buffers, which are GC'd once they drain."""
        self._settle()
        c = self._require_canary()
        self._canary = None
        self._fstats["rolled_back"] += 1
        self._gc_versions()
        if _log_ok():
            logger.warning(
                "serving: canary version %d rolled back — primary stays %d "
                "(%d canary / %d primary admissions in the window)",
                c["version"], self._weights_version,
                c["routed_candidate"], c["routed_primary"],
            )
        return c

    def _require_canary(self) -> dict:
        if self._canary is None:
            raise ValueError("no canary window is active")
        return self._canary

    def canary_status(self) -> Optional[dict]:
        """The active canary window (version, fraction, per-arm routing
        counts), or None."""
        return dict(self._canary) if self._canary is not None else None

    # -- decode canary (sdc.py) --------------------------------------------

    def attach_sdc_canary(self, canary) -> None:
        """Register a :class:`~accelerate_tpu.sdc.DecodeCanary` (called by
        its constructor). The canary rides ``_end_tick`` — one per engine."""
        self._sdc_canary = canary

    def _journal_suppressed(self, rid: int) -> bool:
        """True for the decode canary's in-flight probe: its progress and
        terminal records must reach neither the WAL (phantom replay at
        recover()) nor poll() — the warmup() suppression contract, but
        per-request because probes fly amid real traffic."""
        c = self._sdc_canary
        return c is not None and c._inflight == rid

    def sdc_stats(self) -> Optional[dict]:
        """The ``sdc`` telemetry block: decode-canary probe/mismatch/
        quarantine counters — or None with no canary attached."""
        if self._sdc_canary is None:
            return None
        return self._sdc_canary.summary()

    def cohort_stats(self, version: int, warmup: int = 0) -> Optional[dict]:
        """SLO aggregates for one canary cohort, skipping that cohort's
        first ``warmup`` terminal events (warm caches / first-dispatch noise
        must not decide a rollback). ``None`` until the version has a
        cohort. Rates are over the post-warmup window; TTFT/TPOT means are
        ok-only, matching the engine-wide aggregates."""
        co = self._cohorts.get(version)
        if co is None:
            return None
        agg = _slo_aggregate(co["events"][int(warmup):])
        return {
            "version": int(version),
            "completed": agg["n"],
            "ok": agg["ok"],
            "ok_ttft_mean_s": (float(agg["ttft"].mean())
                               if agg["ttft"].size else None),
            "ok_tpot_mean_s": (float(agg["tpot"].mean())
                               if agg["tpot"].size else None),
            "timeout_rate": agg["timeout_rate"],
            "shed_rate": agg["shed_rate"],
            "failed_rate": agg["failed_rate"],
            "poisoned": int(co["poisoned"]),
        }

    def window_stats(self) -> dict:
        """Rolling-window SLO aggregates over the last
        ``ServingConfig.window_requests`` terminal requests (and as many
        per-tick queue-depth samples) — the signals the autoscaler polls.
        TTFT/TPOT percentiles are ok-only; ``prompt_decode_ratio`` is the
        window's observed prefill:decode work split (ok prompt tokens in
        over ok tokens out), the number a planner consult re-splits the
        disagg slices under."""
        agg = _slo_aggregate(list(self._window))
        qd = np.asarray(self._queue_depth_window, np.float64)
        ok_prompt = sum(e["prompt_tokens"] for e in self._window
                        if e["status"] == "ok")
        ok_new = sum(e["new_tokens"] for e in self._window
                     if e["status"] == "ok")

        def pct(a, q):
            return float(np.percentile(a, q)) if a.size else None

        return {
            "requests": agg["n"],
            "capacity": self._window.maxlen,
            "ok": agg["ok"],
            "ttft_p50_s": pct(agg["ttft"], 50),
            "ttft_p95_s": pct(agg["ttft"], 95),
            "tpot_p50_s": pct(agg["tpot"], 50),
            "tpot_p95_s": pct(agg["tpot"], 95),
            "shed_rate": agg["shed_rate"],
            "timeout_rate": agg["timeout_rate"],
            "failed_rate": agg["failed_rate"],
            "queue_depth_p95": pct(qd, 95),
            "prompt_decode_ratio": (round(ok_prompt / ok_new, 4)
                                    if ok_new else None),
        }

    # -- batch front-end ---------------------------------------------------

    def run(self, prompts, max_new_tokens: Optional[int] = None,
            rngs=None, max_ticks: Optional[int] = None) -> list[np.ndarray]:
        """Synchronous batch API: submit every prompt, tick until drained,
        return one full ``prompt+continuation`` row per prompt in input
        order. ``max_new_tokens`` may be an int or a per-request list;
        ``rngs`` a per-request list of PRNG keys."""
        n = len(prompts)
        budgets = (max_new_tokens if isinstance(max_new_tokens, (list, tuple))
                   else [max_new_tokens] * n)
        keys = rngs if rngs is not None else [None] * n
        ids = [self.submit(p, max_new_tokens=budgets[i], rng=keys[i])
               for i, p in enumerate(prompts)]
        results: dict[int, np.ndarray] = {}
        budget_guard = max_ticks if max_ticks is not None else (
            10 * (sum(len(plan_chunks(len(np.ravel(p)), self.ladder)) for p in prompts)
                  + sum(int(b or self.config.max_new_tokens) for b in budgets))
            + 100
        )
        ticks = 0
        while self.pending:
            self.tick()
            for res in self.poll():
                results[res["id"]] = res["tokens"]
            ticks += 1
            if ticks > budget_guard:
                raise RuntimeError(
                    f"serving engine failed to drain in {budget_guard} ticks "
                    f"({self.pending} requests still pending)"
                )
        self._push_telemetry_summary()
        return [results[i] for i in ids]

    # -- warmup ------------------------------------------------------------

    def warmup(self) -> None:
        """Compile every steady-state program before real traffic: one
        synthetic request whose prompt walks every ladder rung (greedy
        chunking emits each rung once for a ``sum(ladder)``-length prompt)
        plus one decode step. Metric counters are reset afterwards so a
        timed run starts clean; dispatch-cache censuses are live state and
        keep their (now fully warmed) sizes."""
        prompt_len = min(sum(self.ladder), self.t_max - 2)
        prompt = np.ones((prompt_len,), np.int32)
        # The synthetic request must not reach the WAL: a journaled warmup
        # row would replay as a phantom request at the next recover().
        jr, self._journal = self._journal, None
        fused, per_tick = self._decode_chunk, self._chunks_per_tick
        try:
            # One chunk a tick, so that every rung goes through the program
            # a tick runs it in: the fused one where the engine fuses. Where
            # a tick carries more than one chunk, all but its last run
            # alone: their rungs are walked once more, unfused.
            self._chunks_per_tick = 1
            self.run([prompt], max_new_tokens=2)
            if fused is not None and per_tick > 1:
                self._decode_chunk = None
                self.run([prompt], max_new_tokens=2)
            self._settle()
        finally:
            self._journal = jr
            self._decode_chunk, self._chunks_per_tick = fused, per_tick
        self._prefill_executables_warm = self._chunk_executables()
        self.reset_metrics()

    def reset_metrics(self) -> None:
        """Zero every latency/throughput metric (stats counters, TTFT/TPOT
        samples, wall-clock anchors) without touching device state or the
        compiled programs — the boundary between warmup and measurement."""
        for k in self._stats:
            self._stats[k] = 0
        for k in self._fstats:
            self._fstats[k] = 0
        self._idle_ticks = 0
        self._decode_executables_baseline = None
        self._first_submit_t = None
        self._last_done_t = None
        self._ttfts.clear()
        self._tpots.clear()
        self._ttft_terms.clear()
        self._token_gaps.clear()
        self._window.clear()
        self._queue_depth_window.clear()
        self._finished.clear()
        for k in self._jstats:
            self._jstats[k] = 0
        if self.tracing is not None:
            # The trace restarts with the metrics: warmup spans would
            # otherwise pollute explain()/the tick-domain replay invariant.
            self.tracing.reset()
        if self._profiler is not None:
            # Warmup attribution records would skew the term means and the
            # flight ring; the captured cost/plan pricing survives (it
            # fingerprints the program, not the run).
            self._profiler.reset()
        if self._sdc_canary is not None:
            # Probe counters restart with the metrics; the golden row stays
            # armed (it fingerprints the weights, not the run).
            self._sdc_canary.reset_counters()

    # -- reporting ---------------------------------------------------------

    def executable_counts(self) -> dict:
        """Dispatch-cache sizes of the jitted programs — the numbers the
        zero-recompile acceptance bar constrains (decode: exactly 1;
        prefill and decode_chunk, the decode step a chunk rides: each <=
        len(ladder); decode_chunk None where the engine does not fuse)."""
        return {
            "decode": _cache_size(self._decode),
            "prefill": _cache_size(self._prefill),
            "decode_chunk": (_cache_size(self._decode_chunk)
                             if self._decode_chunk is not None else None),
        }

    def stats(self) -> dict:
        """The serving telemetry block: TTFT/TPOT percentiles, TTFT's terms,
        the gap between tokens, the tick's phases, queue depth, slot
        occupancy, aggregate tokens/s, executable census."""
        s = dict(self._stats)
        execs = self.executable_counts()
        elapsed = None
        if self._first_submit_t is not None:
            elapsed = (self._last_done_t or time.perf_counter()) - self._first_submit_t
        ttft = np.asarray(self._ttfts, np.float64)
        tpot = np.asarray(self._tpots, np.float64)
        terms = self._ttft_terms_array()
        out = {
            "requests_submitted": s["submitted"],
            "requests_completed": s["completed"],
            "tokens_out": s["tokens_out"],
            "prompt_tokens_in": s["prompt_tokens_in"],
            "elapsed_s": round(elapsed, 6) if elapsed else None,
            "tokens_per_s": (
                round(s["tokens_out"] / elapsed, 3) if elapsed else None
            ),
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft.size else None,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft.size else None,
            # TTFT attribution: queued-for-a-slot vs prefilling-once-granted
            # means — congestion vs compute (the disagg router exists to
            # shrink the first term without starving the second). Their
            # tails, and the second term split in two, are in "ttft_terms".
            "ttft_queue_wait_mean_s": (
                float(terms[:, 0].mean()) if len(terms) else None
            ),
            "ttft_prefill_mean_s": (
                float(terms[:, 1:].sum(axis=1).mean()) if len(terms) else None
            ),
            "ttft_terms": self.ttft_term_stats(),
            "token_gap": self.token_gap_stats(),
            "tick_phases": self.tick_phase_stats(),
            "tpot_mean_s": float(tpot.mean()) if tpot.size else None,
            "ticks": s["ticks"],
            "decode_steps": s["decode_steps"],
            # of them, those dispatched while the step before was unsettled
            "steps_overlapped": s["steps_overlapped"],
            "prefill_chunks": s["prefill_chunks"],
            # of them, those that rode a decode step (one program a tick)
            "prefill_chunks_fused": s["prefill_chunks_fused"],
            "prefill_pad_tokens": s["prefill_pad_tokens"],
            "prefill_ladder": list(self.ladder),
            "n_slots": self.n_slots,
            "mean_occupancy": (
                round(s["occupancy_sum"] / s["decode_steps"], 3)
                if s["decode_steps"] else None
            ),
            "peak_occupancy": s["peak_occupancy"],
            # The KV cache: planes of the buffer (passes x layers), bytes one
            # token's K and V take over all of them, and the means over decode
            # steps of the rows the decoding slots held (what a step had to
            # read, where the buffer is n_slots x max_len rows) and of the
            # rows its attention did read: each decoding slot's rows rounded
            # up to the decode kernel's block where that runs, the whole
            # buffer where the dots over a layer's slice do.
            "cache": {
                **self._cache_shape,
                "live_rows_mean": (
                    round(s["live_rows_sum"] / s["decode_steps"], 3)
                    if s["decode_steps"] else None
                ),
                "read_rows_mean": (
                    round(s["read_rows_sum"] / s["decode_steps"], 3)
                    if s["decode_steps"] else None
                ),
            },
            "passes": cache_spec(self.cfg).passes,
            # The installed weights' layout: q, k and v as one kernel a stack
            "layout": dict(self._layout),
            "mean_queue_depth": (
                round(s["queue_depth_sum"] / s["queue_samples"], 3)
                if s["queue_samples"] else None
            ),
            "slot_allocs": s["slot_allocs"],
            "slot_reuses": s["slot_reuses"],
            "steady_recompiles": s["steady_recompiles"],
            "prefill_steady_recompiles": s["prefill_steady_recompiles"],
            "decode_executables": execs["decode"],
            "prefill_executables": execs["prefill"],
            "decode_chunk_executables": execs["decode_chunk"],
            "weights_version": self._weights_version,
            "canary": self.canary_status(),
            "sdc": self.sdc_stats(),
            "window": self.window_stats(),
            "faults": self.fault_stats(),
            "journal": self.journal_stats(),
            "speculation": self.speculation_stats(),
        }
        return out

    def _ttft_terms_array(self) -> np.ndarray:
        """(n, 3): a row of :data:`TTFT_TERMS` per first token."""
        return np.asarray(self._ttft_terms, np.float64).reshape(-1, 3)

    def ttft_term_stats(self) -> dict:
        """The ``ttft_terms`` block: median and 95th percentile of each of
        :data:`TTFT_TERMS` over the ``n`` requests that reached their first
        token since the last ``reset_metrics()``."""
        terms = self._ttft_terms_array()
        out = {"n": len(terms)}
        for i, name in enumerate(TTFT_TERMS):
            for q in (50, 95):
                out[f"{name[:-2]}_p{q}_s"] = (
                    float(np.percentile(terms[:, i], q)) if len(terms) else None)
        return out

    def token_gap_stats(self) -> dict:
        """The ``token_gap`` block: the wait before a token since the same
        request's previous one, over the newest ``n`` tokens fetched (the
        first of a request has none; tokens of one fetch after the first
        wait 0)."""
        gaps = np.asarray(self._token_gaps, np.float64)
        return {
            "n": int(gaps.size),
            "p50_s": float(np.percentile(gaps, 50)) if gaps.size else None,
            "p95_s": float(np.percentile(gaps, 95)) if gaps.size else None,
            "max_s": float(gaps.max()) if gaps.size else None,
        }

    def tick_phase_stats(self) -> dict:
        """The ``tick_phases`` block: seconds in each of :data:`TICK_PHASES`
        (they add up to ``wall_s``, the seconds inside ``tick()``), of them
        ``device_wait_s`` blocked in the two fetches and ``host_s`` the rest."""
        s = self._stats
        phases = {name: float(s[name]) for name in TICK_PHASES}
        wait = sum(phases[name] for name in _DEVICE_WAIT_PHASES)
        return {
            "ticks": s["ticks"],
            "wall_s": float(s["tick_wall_s"]),
            "phases_s": phases,
            "device_wait_s": wait,
            "host_s": float(s["tick_wall_s"]) - wait,
        }

    def speculation_stats(self) -> dict:
        """The ``speculation`` telemetry block: draft/accept counters and
        the derived acceptance rate + tokens-per-tick. Present even with
        speculation off (``k == 0``) so the schema is stable."""
        s = self._stats
        drafted = int(s["spec_drafted"])
        accepted = int(s["spec_accepted"])
        steps = int(s["decode_steps"])
        return {
            "k": self._speculate_k,
            "ngram": self._spec_ngram,
            "drafted": drafted,
            "accepted": accepted,
            "acceptance_rate": (
                round(accepted / drafted, 6) if drafted else None
            ),
            "tokens_per_tick": (
                round(s["spec_decode_tokens"] / steps, 6) if steps else None
            ),
            "verify_time_s": round(float(s["spec_verify_s"]), 6),
        }

    def _spec_metrics(self) -> dict:
        """MetricsHub provider: flat numeric ``accelerate_tpu_spec_*``
        gauges (hub names are a schema; None becomes 0.0)."""
        sp = self.speculation_stats()
        return {
            "k": float(sp["k"]),
            "drafted": float(sp["drafted"]),
            "accepted": float(sp["accepted"]),
            "acceptance_rate": float(sp["acceptance_rate"] or 0.0),
            "tokens_per_tick": float(sp["tokens_per_tick"] or 0.0),
            "verify_time_s": float(sp["verify_time_s"]),
        }

    def journal_stats(self) -> Optional[dict]:
        """The ``journal`` telemetry block: WAL counters (appends, syncs,
        rotations, compactions, torn writes/tails, corrupt skips) plus this
        engine's recovery/dedupe counts — or None with journaling off."""
        if self._journal is None:
            return None
        js = self._journal.stats()
        js.update(self._jstats)
        return js

    def fault_stats(self) -> dict:
        """The ``faults`` telemetry block: terminal-status counters plus the
        recovery/degradation state (bench rows and ``make chaos-smoke``
        embed this verbatim)."""
        f = dict(self._fstats)
        f["injected"] = len(self.chaos.injected) if self.chaos is not None else 0
        f["quarantined_slots"] = len(self._quarantined_slots)
        f["degraded"] = bool(getattr(self, "_degraded", False))
        f["preempted"] = bool(self._draining)
        return f

    def _push_telemetry_summary(self) -> None:
        if self.telemetry is not None:
            try:
                self.telemetry.record_serving(self.stats())
            except Exception as e:  # observability must never kill serving
                logger.warning_once(f"serving: telemetry summary failed: {e}")

    def close(self) -> None:
        """Flush the serving summary into the telemetry stream and seal the
        journal's active segment (no device state to tear down — caches are
        plain donated arrays)."""
        self._push_telemetry_summary()
        if self._journal is not None:
            self._journal.close()


# ---------------------------------------------------------------------------
# Open-loop trace replay (shared by benchmarks, smokes, and the disagg router)
# ---------------------------------------------------------------------------


def replay_trace(engine: ServingEngine, prompts, *, arrivals,
                 max_new_tokens=None, rngs=None) -> tuple[list, float]:
    """Replay an open-loop arrival trace through a live engine: submit
    ``prompts[i]`` once ``arrivals[i]`` seconds (monotone, from trace start)
    have elapsed, tick until drained. Unlike :meth:`ServingEngine.run`, the
    offered load is fixed by the trace, not by the engine's drain rate — the
    setup TTFT-under-load comparisons (colocated vs disaggregated) need.

    Returns ``(rows, elapsed_s)`` with one full prompt+continuation row per
    prompt in input order.
    """
    n = len(prompts)
    if len(arrivals) != n:
        raise ValueError(f"{n} prompts but {len(arrivals)} arrivals")
    budgets = (max_new_tokens if isinstance(max_new_tokens, (list, tuple))
               else [max_new_tokens] * n)
    keys = rngs if rngs is not None else [None] * n
    order = sorted(range(n), key=lambda i: float(arrivals[i]))
    ids: dict[int, int] = {}
    results: dict[int, np.ndarray] = {}
    t0 = time.perf_counter()
    nxt = 0
    while nxt < n or engine.pending:
        now = time.perf_counter() - t0
        while nxt < n and float(arrivals[order[nxt]]) <= now:
            i = order[nxt]
            ids[i] = engine.submit(prompts[i], max_new_tokens=budgets[i],
                                   rng=keys[i])
            nxt += 1
        if engine.pending:
            engine.tick()
            for res in engine.poll():
                results[res["id"]] = res["tokens"]
        elif nxt < n:  # idle gap before the next arrival
            time.sleep(min(0.002, max(0.0, float(arrivals[order[nxt]]) - now)))
    elapsed = time.perf_counter() - t0
    engine._push_telemetry_summary()
    return [results[ids[i]] for i in range(n)], elapsed
