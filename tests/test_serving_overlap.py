"""The tick one step deep (``ServingEngine.tick``): step k is dispatched, queued
on the device behind step k-1, before the host fetches and books k-1. Each step
is settled from the record it was dispatched with, so a slot whose request has
left it since is skipped, and a request armed by a riding final chunk decodes in
the very next step, before its first token has been fetched. Every test holds
the engine's own rows to batch-1 ``generate()`` (or, where that has no such
option, to each request served alone). CPU, tiny models, float32."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import (DisaggConfig, DisaggServingEngine, FaultInjector, Model,
                            ServingConfig, ServingEngine, generate)
from accelerate_tpu import models as M
from accelerate_tpu.utils import set_seed

FAMILIES = {
    "llama": (M.LlamaConfig, M.LlamaForCausalLM, {"attention_impl": "native"}),
    "mixtral": (M.MixtralConfig, M.MixtralForCausalLM, {}),
    "looped": (M.LlamaConfig, M.LlamaForCausalLM,
               {"attention_impl": "native", "total_ut_steps": 3, "sandwich_norm": True,
                "early_exit_gate": True}),
}
LADDER = [4, 8]


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            set_seed(0)
            cfg_cls, module_cls, kw = FAMILIES[name]
            cfg = cfg_cls.tiny(dtype=jnp.float32, **kw)
            cache[name] = cfg, Model.from_flax(module_cls(cfg), jax.random.key(0),
                                               np.ones((1, 4), np.int32))
        return cache[name]

    return get


def _prompts(cfg, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32) for n in lengths]


def _engine(model, n_slots=2, **config):
    return ServingEngine(model, ServingConfig(n_slots=n_slots, max_len=64,
                                              prefill_chunks=LADDER, **config))


def _want(model, prompt, budget, **kw):
    return np.asarray(generate(model, prompt[None], max_new_tokens=budget, **kw))[0]


def _drain(engine, ids, max_ticks=400):
    rows = {}
    for _ in range(max_ticks):
        if all(i in rows for i in ids):
            break
        engine.tick()
        rows.update((r["id"], r) for r in engine.poll())
    assert all(i in rows for i in ids), "requests did not drain"
    return rows


def _spy(engine):
    """Log every dispatched step (the request ids it advances, the request whose
    final chunk rode it) and, at each settle, the slots of its record whose
    request has left them since."""
    log = {"steps": [], "left": []}
    dispatch, settle = engine._dispatch, engine._settle_step

    def spying_dispatch(version, mask, rows, ride, flip_slot):
        log["steps"].append({
            "rows": {r.id for r in rows.values()},
            "ride": ride is not None,
            "final": ride.req.id if ride is not None and ride.is_final else None,
        })
        return dispatch(version, mask, rows, ride, flip_slot)

    def spying_settle(step):
        log["left"] += [(slot, req.id, getattr(engine._decoding.get(slot), "id", None))
                        for slot, req in step.rows.items()
                        if engine._decoding.get(slot) is not req]
        return settle(step)

    engine._dispatch, engine._settle_step = spying_dispatch, spying_settle
    return log


# -- a riding final chunk: its slot decodes in the very next step ------------------


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_riding_final_chunk_decodes_in_the_next_step(built, family):
    """R0 (two chunks) alone; once it decodes, R1 (three chunks, a padded final)
    and R2 (one). Each final chunk rides a decode step, and the step after it
    advances that request's slot before its first token is fetched."""
    cfg, model = built(family)
    prompts, budgets = _prompts(cfg, [6, 17, 3]), [9, 6, 4]
    engine = _engine(model, n_slots=3)
    log = _spy(engine)
    ids = [engine.submit(prompts[0], max_new_tokens=budgets[0])]
    rows = {}
    for _ in range(200):
        if not engine.pending:
            break
        engine.tick()
        rows.update((r["id"], r) for r in engine.poll())
        if len(ids) == 1 and ids[0] in {r.id for r in engine._decoding.values()}:
            ids += [engine.submit(p, max_new_tokens=b) for p, b in zip(prompts[1:], budgets[1:])]
    assert len(ids) == 3 and set(rows) == set(ids)
    for rid, prompt, budget in zip(ids, prompts, budgets):
        assert rows[rid]["status"] == "ok"
        np.testing.assert_array_equal(rows[rid]["tokens"], _want(model, prompt, budget))
        k = next(i for i, s in enumerate(log["steps"]) if s["final"] == rid)
        assert rid in log["steps"][k + 1]["rows"]
    # R1's and R2's final chunks rode steps that advanced R0
    assert all(ids[0] in log["steps"][i]["rows"] for i, s in enumerate(log["steps"])
               if s["final"] in ids[1:])
    stats = engine.stats()
    assert stats["prefill_chunks_fused"] == stats["prefill_chunks"] == 2 + 3 + 1
    assert stats["steps_overlapped"] > 0


# -- EOS, quarantine, deadlines and re-grants while a step is in flight ------------


def _common_token(model, prompts, budget):
    """The token that most greedy continuations hold before their last place:
    as EOS, it ends several rows early."""
    seen = [set(_want(model, p, budget)[len(p):-1].tolist()) for p in prompts]
    return max(set().union(*seen), key=lambda t: sum(t in s for s in seen))


def test_eos_inside_an_in_flight_step(built):
    """The host learns of an EOS one step late, so the step after it is already
    dispatched with the slot: the device masks the row (nothing is emitted past
    EOS) and the slot is freed once."""
    cfg, model = built("llama")
    prompts = _prompts(cfg, [5, 9, 5, 9, 7, 3], seed=9)
    eos = _common_token(model, prompts, 8)
    engine = _engine(model, n_slots=2, eos_token_id=eos)
    log = _spy(engine)
    outs = engine.run(prompts, max_new_tokens=8)
    stopped = 0
    for prompt, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, _want(model, prompt, 8, eos_token_id=eos))
        new = got[len(prompt):]
        stopped += bool(eos in new[:-1])
    assert stopped >= 2                   # some rows ended at EOS before their budget
    # a step dispatched with a slot whose request had ended at EOS in the step before
    assert log["left"]
    assert sorted(engine._free) == list(range(engine.n_slots))   # each slot freed once
    stats = engine.stats()
    assert stats["requests_completed"] == len(prompts) and stats["slot_allocs"] == len(prompts)


def test_a_poisoned_slot_is_quarantined_once_while_the_next_step_is_in_flight(built):
    """The poisoned step's nonfinite flag is fetched after the next step, which
    reads the same slot, has been dispatched: that step's flag for the slot is
    skipped (its request has left it), so one quarantine and one retry."""
    cfg, model = built("llama")
    prompts, budgets = _prompts(cfg, [3, 7, 12, 20, 5, 9]), [6, 4, 8, 3, 5, 6]
    chaos = FaultInjector(seed=7, schedule=[{"point": "decode_tick", "kind": "poison",
                                             "tick": 8}])
    engine = ServingEngine(model, ServingConfig(n_slots=3, max_len=64, prefill_chunks=LADDER),
                           chaos=chaos)
    log = _spy(engine)
    ids = [engine.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    rows = _drain(engine, ids)
    assert [f["point"] for f in chaos.injected] == ["decode_tick"]
    stats = engine.stats()
    assert stats["faults"]["slot_quarantines"] == stats["faults"]["retries"] == 1
    assert stats["faults"]["quarantined_slots"] == 1
    quarantined = next(iter(engine._quarantined_slots))
    assert any(slot == quarantined and now is None for slot, _, now in log["left"])
    for rid, prompt, budget in zip(ids, prompts, budgets):
        assert rows[rid]["status"] == "ok"
        np.testing.assert_array_equal(rows[rid]["tokens"], _want(model, prompt, budget))


def test_a_deadline_expires_with_a_step_in_flight_and_its_slot_is_granted_anew(built):
    """One slot. The doomed request times out while a step that advances it is
    outstanding; the same tick grants its slot to the next request, whose one
    chunk rides the next step. Settling the outstanding step then skips the slot:
    its token is the doomed request's, and the slot is the new one's."""
    cfg, model = built("llama")
    prompts = _prompts(cfg, [5, 3])
    engine = _engine(model, n_slots=1)
    log = _spy(engine)
    doomed = engine.submit(prompts[0], max_new_tokens=30, deadline_s=3600.0)
    for _ in range(20):
        engine.tick()
        req = engine._decoding.get(0)
        if req is not None and len(req.out) >= 3:
            break
    assert engine._outstanding is not None and doomed in {
        r.id for r in engine._outstanding.rows.values()}
    req.deadline = time.perf_counter()    # due now: the next tick's sweep expires it
    healthy = engine.submit(prompts[1], max_new_tokens=3)
    rows = _drain(engine, [doomed, healthy])
    assert rows[doomed]["status"] == "timeout" and rows[healthy]["status"] == "ok"
    assert (0, doomed, healthy) in log["left"]
    want = _want(model, prompts[0], 30)
    got, n = rows[doomed]["tokens"], rows[doomed]["new_tokens"]
    assert n >= 3
    np.testing.assert_array_equal(got[:len(prompts[0]) + n], want[:len(prompts[0]) + n])
    np.testing.assert_array_equal(rows[healthy]["tokens"], _want(model, prompts[1], 3))
    assert engine.stats()["faults"]["timeouts"] == 1


def test_a_slot_retired_at_eos_is_granted_anew_before_its_last_step_settles(built):
    """One slot and a queue: each request ends at EOS, which the host sees a step
    late; by the time the step after the EOS settles, the slot may hold the next
    request, whose tokens the stale row never touches."""
    cfg, model = built("llama")
    prompts = _prompts(cfg, [3, 4, 3, 4, 2, 3], seed=9)
    eos = _common_token(model, prompts, 12)
    engine = _engine(model, n_slots=1, eos_token_id=eos)
    log = _spy(engine)
    outs = engine.run(prompts, max_new_tokens=12)
    for prompt, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, _want(model, prompt, 12, eos_token_id=eos))
    assert any(now is not None and now != rid for _, rid, now in log["left"])


# -- ticks that settle first --------------------------------------------------------


def _variant(params, scale=1.25):
    return jax.tree.map(lambda a: jax.device_put((np.asarray(a) * scale).astype(
        np.asarray(a).dtype)), params)


def test_a_mixed_version_tick_settles_before_it_dispatches(built):
    """A canary window with both versions decoding: every such tick settles the
    outstanding step first and runs its groups in turn, each settled before the
    next; every row is its own version's batch-1 ``generate()``."""
    cfg, model = built("llama")
    variant = Model(module=model.module, params=_variant(model.params))
    engine = _engine(model, n_slots=2)
    seen = []
    decode_tick = engine._decode_tick

    def noting():
        seen.append((engine._outstanding, len(engine._decode_groups())))
        decode_tick()
        seen[-1] += (engine._outstanding,)

    engine._decode_tick = noting
    engine.begin_canary(variant.params, weights_version=1, fraction=0.5)
    prompts = _prompts(cfg, [4, 6, 4, 6], seed=5)
    ids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    rows = _drain(engine, ids)
    assert any(groups == 2 for _, groups, _ in seen)
    assert all(before is None and after is None for before, _, after in seen)
    for rid, prompt in zip(ids, prompts):
        v = rows[rid]["weights_version"]
        np.testing.assert_array_equal(rows[rid]["tokens"],
                                      _want(model if v == 0 else variant, prompt, 5))
    assert [rows[i]["weights_version"] for i in ids] == [0, 1, 0, 1]
    engine.promote_canary()
    assert engine._outstanding is None


def test_swap_and_warmup_leave_no_step_outstanding(built):
    cfg, model = built("llama")
    engine = _engine(model, n_slots=2)
    engine.warmup()
    assert engine._outstanding is None and not engine.pending
    rid = engine.submit(_prompts(cfg, [5])[0], max_new_tokens=6)
    for _ in range(4):
        engine.tick()
    assert engine._outstanding is not None
    engine.swap_params(_variant(model.params, 1.0), weights_version=2)
    assert engine._outstanding is None
    rows = _drain(engine, [rid])
    np.testing.assert_array_equal(rows[rid]["tokens"], _want(model, _prompts(cfg, [5])[0], 6))


# -- speculation, an int8 cache, sampled streams ------------------------------------


def test_speculation_rides_the_same_order(built):
    cfg, model = built("llama")
    prompts, budgets = _prompts(cfg, [3, 7, 12, 20, 5]), [9, 6, 8, 5, 7]
    engine = _engine(model, n_slots=2, speculate_k=2, speculate_ngram=8)
    outs = engine.run(prompts, max_new_tokens=budgets)
    for prompt, budget, got in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(got, _want(model, prompt, budget))
    stats = engine.stats()
    assert stats["steps_overlapped"] > 0 and stats["speculation"]["drafted"] > 0
    assert stats["decode_executables"] == 1 and stats["steady_recompiles"] == 0


def test_an_int8_cache_serves_what_each_request_alone_is_served(built):
    cfg, model = built("llama")
    prompts, budgets = _prompts(cfg, [6, 17, 5, 9]), [7, 5, 6, 4]
    engine = _engine(model, n_slots=2, cache_dtype=jnp.int8)
    outs = engine.run(prompts, max_new_tokens=budgets)
    assert engine.stats()["steps_overlapped"] > 0
    for prompt, budget, got in zip(prompts, budgets, outs):
        alone = _engine(model, n_slots=2, cache_dtype=jnp.int8)
        np.testing.assert_array_equal(got, alone.run([prompt], max_new_tokens=budget)[0])


def test_sampled_streams_are_generate_s_on_the_same_seeds(built):
    cfg, model = built("llama")
    sampling = dict(temperature=0.8, top_k=20, top_p=0.9)
    prompts, budgets = _prompts(cfg, [5, 8, 13]), [6, 7, 5]
    keys = [jax.random.key(i) for i in (1, 2, 3)]
    outs = _engine(model, n_slots=2, **sampling).run(prompts, max_new_tokens=budgets,
                                                      rngs=keys)
    for prompt, budget, key, got in zip(prompts, budgets, keys, outs):
        np.testing.assert_array_equal(got, _want(model, prompt, budget, rng=key, **sampling))


# -- what callers see: rows one tick after dispatch, pending, the counter -----------


def test_a_step_s_tokens_reach_poll_one_tick_after_its_dispatch(built):
    cfg, model = built("llama")
    prompt = _prompts(cfg, [3])[0]
    engine = _engine(model, n_slots=1)
    rid = engine.submit(prompt, max_new_tokens=1)
    engine.tick()                      # the one chunk rides: dispatched, not fetched
    assert engine.poll() == [] and engine.pending == 1 and engine._outstanding is not None
    engine.tick()                      # nothing left to dispatch: it settles
    rows = engine.poll()
    assert [r["id"] for r in rows] == [rid] and not engine.pending
    assert engine._outstanding is None
    np.testing.assert_array_equal(rows[0]["tokens"], _want(model, prompt, 1))


def test_run_and_poll_return_every_row_and_pending_holds_while_a_step_is_out(built):
    cfg, model = built("llama")
    prompts, budgets = _prompts(cfg, [3, 7, 12, 20, 3, 7]), [6, 4, 8, 3, 1, 5]
    engine = _engine(model, n_slots=3)
    ids = [engine.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    rows, ticks = {}, 0
    while engine.pending:
        engine.tick()
        ticks += 1
        step = engine._outstanding
        if step is not None and any(r.status is None for r in step.rows.values()):
            assert engine.pending > 0
        for r in engine.poll():
            assert r["id"] not in rows
            rows[r["id"]] = r
        assert ticks < 200
    assert sorted(rows) == sorted(ids)
    for rid, prompt, budget in zip(ids, prompts, budgets):
        np.testing.assert_array_equal(rows[rid]["tokens"], _want(model, prompt, budget))
    outs = _engine(model, n_slots=3).run(prompts, max_new_tokens=budgets)
    for rid, got in zip(ids, outs):
        np.testing.assert_array_equal(got, rows[rid]["tokens"])


def test_steps_overlapped_and_the_census(built):
    """After ``warmup()`` a stream of requests: nearly every decode step is
    dispatched while the one before is unsettled; one ``decode`` executable, one
    ``decode_chunk`` a rung, nothing compiled in steady state."""
    cfg, model = built("llama")
    engine = _engine(model, n_slots=3)
    engine.warmup()
    warm = engine.executable_counts()
    assert warm["decode"] == 1 and warm["decode_chunk"] == len(LADDER)
    prompts = _prompts(cfg, [3, 7, 12, 20, 5, 9, 4, 11])
    budgets = [12, 9, 14, 10, 11, 13, 9, 12]
    engine.run(prompts, max_new_tokens=budgets)
    stats = engine.stats()
    assert stats["steps_overlapped"] <= stats["decode_steps"]
    assert stats["steps_overlapped"] >= 0.9 * stats["decode_steps"]
    assert engine.executable_counts() == warm
    assert stats["steady_recompiles"] == stats["prefill_steady_recompiles"] == 0


def test_no_step_is_dispatched_that_can_do_no_work(built):
    """When every row of the outstanding step is known by budget to finish there,
    the tick settles instead of queueing a step with nothing live."""
    cfg, model = built("llama")
    engine = _engine(model, n_slots=2)
    log = _spy(engine)
    engine.run(_prompts(cfg, [5, 5]), max_new_tokens=4)
    assert all(s["rows"] or s["ride"] for s in log["steps"])
    assert engine._outstanding is None


# -- the disaggregated router keeps today's order ------------------------------------


def test_the_disaggregated_router_dispatches_and_settles_in_turn(built):
    cfg, model = built("llama")
    prompts, budgets = _prompts(cfg, [5, 9, 7]), [4, 6, 5]
    engine = DisaggServingEngine(
        model, ServingConfig(n_slots=2, max_len=64, prefill_chunks=LADDER),
        disagg=DisaggConfig(n_prefill_lanes=2))
    ids = [engine.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    rows = {}
    while engine.pending:
        engine.tick()
        assert engine._outstanding is None
        rows.update((r["id"], r) for r in engine.poll())
    for rid, prompt, budget in zip(ids, prompts, budgets):
        np.testing.assert_array_equal(rows[rid]["tokens"], _want(model, prompt, budget))
    assert engine.stats()["steps_overlapped"] == 0
