"""A prompt chunk that rides the decode step (``serving._build_decode_chunk_step``,
``generation._forward_cached``'s ``chunk``): one program a tick, held on one
schedule against the engine that runs two. Chunks arrive while slots decode: a
first, an intermediate and a final chunk, padded finals, and a first chunk into
a slot whose stale length lies past 0. CPU, tiny models, float32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import FaultInjector, Model, ServingConfig, ServingEngine
from accelerate_tpu import models as M
from accelerate_tpu.generation import GENERATION_PLANS
from accelerate_tpu.utils import set_seed

FAMILIES = {
    "llama": (M.LlamaConfig, M.LlamaForCausalLM, {"attention_impl": "native"}),
    "mixtral": (M.MixtralConfig, M.MixtralForCausalLM, {}),
    "looped": (M.LlamaConfig, M.LlamaForCausalLM,
               {"attention_impl": "native", "total_ut_steps": 3, "sandwich_norm": True,
                "early_exit_gate": True}),
    "gpt2": (M.GPT2Config, M.GPT2LMHeadModel, {}),
    "opt": (M.OPTConfig, M.OPTForCausalLM, {}),
    "neox": (M.GPTNeoXConfig, M.GPTNeoXForCausalLM, {}),
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
            module = module_cls(cfg)
            cache[name] = cfg, Model.from_flax(module, jax.random.key(0),
                                               np.ones((1, 4), np.int32))
        return cache[name]

    return get


def _two_programs(model):
    """The model's plan without ``chunk``: the engine keeps a program for the
    chunk and one for the decode step."""
    fwd = GENERATION_PLANS[type(model.module).__name__]

    def two_programs(cfg, params, ids, cache, return_all=False, attn_bound=None):
        return fwd(cfg, params, ids, cache, return_all=return_all, attn_bound=attn_bound)

    return two_programs


def _engine(model, fused, chaos=None, **config):
    return ServingEngine(
        model, ServingConfig(n_slots=3, max_len=64, prefill_chunks=LADDER, **config),
        forward_cached=None if fused else _two_programs(model), chaos=chaos)


def _serve(engine, cfg):
    """The schedule, by what the engine shows rather than by tick, so that it
    is the same on both engines (a request armed by a riding chunk decodes one
    tick later): R0 (6 tokens: a first and a padded final chunk) alone; once it
    decodes, R1 (21: first, intermediate, final padded) and R2 (9); once R2 is
    done, R3 (7) into R2's slot, whose length lies past 0. Returns each
    request's row and slot."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, (n,), dtype=np.int32) for n in (6, 21, 9, 7)]
    budgets = [14, 6, 3, 4]
    submit = lambda i: engine.submit(prompts[i], max_new_tokens=budgets[i])
    ids, rows, slots = [submit(0)], {}, {}
    for _ in range(200):
        if not engine.pending:
            break
        engine.tick()
        for r in list(engine._prefilling) + list(engine._decoding.values()):
            slots[r.id] = r.slot
        rows.update((r["id"], r) for r in engine.poll())
        if len(ids) == 1 and ids[0] in {r.id for r in engine._decoding.values()}:
            ids += [submit(1), submit(2)]
        if len(ids) == 3 and ids[2] in rows:
            ids.append(submit(3))
    assert not engine.pending and len(ids) == 4
    return [rows[i] for i in ids], [slots[i] for i in ids]


@pytest.mark.parametrize("family,cache", [(f, {}) for f in sorted(FAMILIES)]
                         + [("llama", {"cache_dtype": jnp.int8})],
                         ids=sorted(FAMILIES) + ["llama_int8_cache"])
def test_a_riding_chunk_serves_what_two_programs_serve(built, family, cache):
    cfg, model = built(family)
    fused, plain = _engine(model, True, **cache), _engine(model, False, **cache)
    rows_f, slots_f = _serve(fused, cfg)
    rows_p, slots_p = _serve(plain, cfg)
    assert slots_f == slots_p and slots_f[3] == slots_f[2]   # R3 took R2's slot
    for f, p in zip(rows_f, rows_p):
        assert f["status"] == p["status"] == "ok"
        np.testing.assert_array_equal(f["tokens"], p["tokens"])
    # every slot's rows under its length, the prompts' and the outputs'
    np.testing.assert_array_equal(np.asarray(fused._cache.length),
                                  np.asarray(plain._cache.length))
    for slot, n in enumerate(np.asarray(fused._cache.length)):
        for a, b in zip(jax.tree.leaves((fused._cache.k, fused._cache.v)),
                        jax.tree.leaves((plain._cache.k, plain._cache.v))):
            np.testing.assert_allclose(np.asarray(a[:, slot, :n], np.float32),
                                       np.asarray(b[:, slot, :n], np.float32),
                                       rtol=2e-5, atol=2e-5 if a.dtype != jnp.int8 else 1)
    sf, sp = fused.stats(), plain.stats()
    assert sf["prefill_chunks"] == sp["prefill_chunks"] == 2 + 4 + 2 + 2
    assert sf["prefill_chunks_fused"] == sf["prefill_chunks"]
    assert sp["prefill_chunks_fused"] == 0
    assert sf["tick_phases"]["phases_s"]["serving.first_token_fetch"] == 0.0
    execs = fused.executable_counts()
    assert execs["decode"] == 1 and 1 <= execs["decode_chunk"] <= len(LADDER)
    assert execs["prefill"] == 0 and plain.executable_counts()["decode_chunk"] is None
    assert sf["steady_recompiles"] == 0


def test_a_fault_at_a_riding_chunk_leaves_the_decoding_tokens_as_they_were(built):
    """The chaos draw at ``prefill_dispatch`` comes before the fused dispatch is
    built: R1's first chunk fails while R0 decodes, the tick runs the pure
    decode step, and R1 replays bit-equal."""
    cfg, model = built("llama")
    clean, _ = _serve(_engine(model, True), cfg)
    chaos = FaultInjector(seed=0, schedule=[
        {"point": "prefill_dispatch", "kind": "transfer_error", "unit": 1}])
    engine = _engine(model, True, chaos=chaos, max_retries=2)
    rows, _ = _serve(engine, cfg)
    assert [f["point"] for f in chaos.injected] == ["prefill_dispatch"]
    for got, want in zip(rows, clean):
        assert got["status"] == "ok"
        np.testing.assert_array_equal(got["tokens"], want["tokens"])
    stats = engine.stats()
    assert stats["faults"]["retries"] == 1
    assert stats["prefill_chunks_fused"] == stats["prefill_chunks"] == 10


def test_more_than_one_chunk_a_tick_rides_the_last_and_warms_both(built):
    """``prefill_chunks_per_tick`` 2: all but a tick's last chunk run alone, the
    last rides; ``warmup()`` walks every rung through both programs, so serving
    compiles nothing more."""
    cfg, model = built("llama")
    engine = _engine(model, True, prefill_chunks_per_tick=2)
    engine.warmup()
    warm = engine.executable_counts()
    assert warm["decode"] == 1 and warm["prefill"] == warm["decode_chunk"] == len(LADDER)
    rows, _ = _serve(engine, cfg)
    plain, _ = _serve(_engine(model, False, prefill_chunks_per_tick=2), cfg)
    for got, want in zip(rows, plain):
        np.testing.assert_array_equal(got["tokens"], want["tokens"])
    stats = engine.stats()
    assert 0 < stats["prefill_chunks_fused"] < stats["prefill_chunks"]
    assert engine.executable_counts() == warm
    assert stats["prefill_steady_recompiles"] == stats["steady_recompiles"] == 0


def test_sampled_tokens_are_the_two_programs_tokens(built):
    """A riding chunk draws its first token from its request's stream as the
    prefill program draws it, and the decode rows draw theirs as ever."""
    cfg, model = built("llama")
    sampling = dict(temperature=0.8, top_k=20, top_p=0.9)
    rows, _ = _serve(_engine(model, True, **sampling), cfg)
    plain, _ = _serve(_engine(model, False, **sampling), cfg)
    for got, want in zip(rows, plain):
        np.testing.assert_array_equal(got["tokens"], want["tokens"])
