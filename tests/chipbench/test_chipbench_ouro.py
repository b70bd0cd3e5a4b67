"""Family ``ouro`` (the stack run several times over one set of weights): its
arithmetic against hand-worked numbers, its leaves against the program's
module, and the program against the family's plain reference at a tiny size,
on logits: the module's full forward, cached generation (prefill in two
chunks, then decoding) and the serving engine with two slots at different
lengths. ``U`` is 3 and ``L`` is 2, so planes and layers differ and neither
divides the other's index by accident. A cache whose passes read each other's
planes fails the same comparison, so the test can see the mechanism. Then the
cell's own pieces: its reader, its control at the rehearsal's size."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, spec, weights
from chipbench.families import llama, ouro
from chipbench.metrics._context import MetricContext

with open(os.path.join(spec.HERE, "configs", "ouro-2.6b.json")) as _f:
    OURO = json.load(_f)
CELL = "ouro_serve_reason"
TINY = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32, vocab_size=256,
            rope_theta=1e6, rms_norm_eps=1e-6, hidden_act="silu", tie_word_embeddings=False,
            initializer_range=0.16, total_ut_steps=3, early_exit_threshold=1)

# attention: q, k, v and o 2048x2048 each (16 heads of 128, as many key-value heads)
ATTN = 4 * 2048 * 2048
MLP = 3 * 2048 * 5632


# -- the arithmetic -----------------------------------------------------------------


def test_parameter_counts_by_hand():
    assert ouro.layer_params(OURO) == ATTN + MLP + 4 * 2048 == 51_388_416
    gate = 2048 + 1
    assert ouro.total_params(OURO) == 48 * 51_388_416 + 2 * 49152 * 2048 + 2048 + gate
    assert round(ouro.total_params(OURO) / 1e9, 3) == 2.668   # one set of weights, not four
    assert weights.n_params(ouro.weight_specs(OURO)) == ouro.total_params(OURO)


def test_request_flops_count_the_layers_four_times_and_the_head_once():
    # 100 prompt tokens, 10 outputs: 109 positions through 4 x 48 layer applications,
    # attending 1 + 2 + ... + 109 = 5995 rows in each; the head for 10 positions.
    want = (4 * (4 * 48) * 16 * 128 * 5995 + 2 * (4 * 48) * (ATTN + MLP) * 109
            + 2 * 2048 * 49152 * 10)
    assert ouro.request_flops(OURO, 100, 10) == float(want)
    assert ouro.request_flops(dict(OURO, total_ut_steps=1), 100, 10) == llama.request_flops(
        OURO, 100, 10)


def test_decode_step_bytes_read_the_layers_once_a_pass():
    # the 48 layers' 4.93 GB do not stay on the chip between passes: four reads a step
    assert ouro.decode_step_bytes(OURO, 0) == 2.0 * (4 * 48 * (ATTN + MLP) + 2048 * 49152)
    assert ouro.decode_step_bytes(OURO, 0) == 19_931_332_608
    # a live row: keys and values of 16 heads of 128 in bf16 over 192 planes, 1.5 MiB
    assert (ouro.decode_step_bytes(OURO, 1000) - ouro.decode_step_bytes(OURO, 0)
            == 1000 * 1_572_864)


# -- the leaves ---------------------------------------------------------------------


def test_leaves_are_the_module_s():
    specs = ouro.weight_specs(TINY)
    assert set(specs) - set(llama.weight_specs(TINY)) == {
        "model/layers/block/input_layernorm_2/weight",
        "model/layers/block/post_attention_layernorm_2/weight",
        "model/early_exit_gate/kernel", "model/early_exit_gate/bias"}
    module = ouro.program_module(TINY, 64)
    assert module.config.total_ut_steps == 3
    want = jax.eval_shape(module.init, jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    w = weights.make_weights(specs, TINY["initializer_range"], 2**31 + 5)
    assert jax.tree.map(lambda x: x.shape, want) == jax.tree.map(
        lambda x: x.shape, weights.nest(w))


# -- the program against the reference ------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    """Seeded weights, the program's module in float32, one sequence of 48
    tokens and the reference's logits for it."""
    w = weights.make_weights(ouro.weight_specs(TINY), TINY["initializer_range"], 11)
    module = ouro.program_module(TINY, 64)
    module = type(module)(dataclasses.replace(module.config, dtype=jnp.float32,
                                              attention_impl="native"))
    ids = np.random.default_rng(0).integers(0, 256, size=(1, 48), dtype=np.int32)
    want = np.asarray(jax.jit(lambda w, i: ouro.reference_logits(TINY, w, i))(w, ids[0]))
    return w, module, ids, want


def _cached_logits(module, w, ids):
    """Every position's logits through the cache: prefill in two chunks, then
    token by token."""
    from accelerate_tpu.generation import GENERATION_PLANS, init_cache

    cfg = module.config
    fwd = GENERATION_PLANS[type(module).__name__]
    cache = init_cache(cfg, 1, 64, dtype=jnp.float32)
    assert cache.n_layers == 6
    parts = []
    with jax.default_matmul_precision("highest"):
        for lo, hi in [(0, 20), (20, 40)] + [(t, t + 1) for t in range(40, 48)]:
            logits, cache = fwd(cfg, weights.nest(w), ids[:, lo:hi], cache, return_all=True)
            parts.append(np.asarray(logits[0]))
    return np.concatenate(parts)


def test_module_s_full_forward_equals_the_reference_in_float32(tiny):
    w, module, ids, want = tiny
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": weights.nest(w)}, ids)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)


def test_cached_generation_equals_the_reference_in_float32(tiny):
    w, module, ids, want = tiny
    np.testing.assert_allclose(_cached_logits(module, w, ids), want, atol=2e-4)


def test_a_pass_that_reads_the_previous_pass_s_planes_fails_the_comparison(tiny, monkeypatch):
    """The fault the cache could have: pass ``u`` writes its own planes and
    attends over pass ``u - 1``'s keys and values (the first pass over the
    last's). The logits then lie far from the reference's, so the comparisons
    above do see which plane a pass uses."""
    from accelerate_tpu import kv_cache

    w, module, ids, want = tiny
    step = kv_cache.cache_step
    n, planes = TINY["num_hidden_layers"], TINY["total_ut_steps"] * TINY["num_hidden_layers"]

    def reads_the_previous_pass(ck, cv, k_new, v_new, plane, start):
        ck, cv, _, _ = step(ck, cv, k_new, v_new, plane, start)
        k_i, v_i = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, (plane - n) % planes, 0, False), (ck, cv))
        return ck, cv, k_i, v_i

    monkeypatch.setattr(kv_cache, "cache_step", reads_the_previous_pass)
    got = _cached_logits(module, w, ids)
    assert float(np.max(np.abs(got - want))) > 0.05
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_serving_engine_s_tokens_are_the_reference_s_best_in_float32(tiny):
    """Two slots at different lengths, a third request taking the first slot
    to come free. The engine hands out tokens, not logits: each served token's
    reference logit has to be the reference's best at its position, to
    rounding, as ``check.py`` compares them."""
    from accelerate_tpu import Model, ServingConfig, ServingEngine

    w, module, _, _ = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, size=(n,), dtype=np.int32) for n in (21, 9, 14)]
    budgets = [10, 16, 7]
    with jax.default_matmul_precision("highest"):
        engine = ServingEngine(Model(module=module, params=weights.nest(w)), ServingConfig(
            n_slots=2, max_len=64, prefill_chunks=[8, 16], cache_dtype=jnp.float32,
            temperature=0.0))
        rows = engine.run(prompts, max_new_tokens=budgets)
        stats = engine.stats()
    assert stats["passes"] == 3 and stats["cache"]["planes"] == 6
    assert stats["cache"]["bytes_per_token"] == 2 * 4 * 6 * 2 * 32
    gaps = check.build_gap_fn(ouro, TINY, None)
    for prompt, budget, row in zip(prompts, budgets, rows):
        assert len(row) == len(prompt) + budget
        ids = np.zeros((64,), np.int32)
        ids[: len(row)] = row
        gap, _, _ = gaps(w, ids)
        served = np.asarray(gap)[len(prompt) - 1: len(row) - 1]
        assert float(served.max()) < 1e-3, served


@pytest.mark.parametrize("sandwich", [False, True], ids=["llama", "sandwich_norms"])
def test_one_pass_is_the_llama_reference_exactly_unless_the_norms_are_on(sandwich):
    """``total_ut_steps`` 1 with the sandwich norms off leaves the chassis as
    it was: the Llama reference, to rounding. With them on it is this family's
    reference at one pass, and no longer Llama's."""
    from accelerate_tpu.generation import GENERATION_PLANS, init_cache
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg1 = dict(TINY, total_ut_steps=1)
    w = weights.make_weights(ouro.weight_specs(cfg1), TINY["initializer_range"], 7)
    leaves = w if sandwich else {k: v for k, v in w.items() if k in llama.weight_specs(cfg1)}
    cfg = LlamaConfig(total_ut_steps=1, sandwich_norm=sandwich, early_exit_gate=sandwich,
                      **dict(llama._program_kwargs(cfg1, 64, {}), dtype=jnp.float32))
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 32), dtype=np.int32)
    fwd = GENERATION_PLANS[LlamaForCausalLM.__name__]
    with jax.default_matmul_precision("highest"):
        got, _ = fwd(cfg, weights.nest(leaves), ids, init_cache(cfg, 1, 64), return_all=True)
    got = np.asarray(got[0])
    as_llama = np.asarray(jax.jit(lambda w, i: llama.reference_logits(cfg1, w, i))(w, ids[0]))
    as_ouro = np.asarray(jax.jit(lambda w, i: ouro.reference_logits(cfg1, w, i))(w, ids[0]))
    np.testing.assert_allclose(got, as_ouro if sandwich else as_llama, atol=2e-4)
    assert float(np.max(np.abs(as_ouro - as_llama))) > 0.05


# -- the exit gate --------------------------------------------------------------------


def test_exit_cdf_sums_to_one_and_a_threshold_of_one_exits_at_the_last_pass(tiny):
    w, module, ids, _ = tiny
    cdf = np.asarray(jax.jit(lambda w, i: ouro.exit_cdf(TINY, w, i))(w, ids[0]))
    assert cdf.shape == (3, 48)
    np.testing.assert_allclose(cdf[-1], 1.0, atol=1e-6)
    assert (np.diff(cdf, axis=0) >= -1e-7).all() and (cdf[0] > 0).all() and (cdf[0] < 1).all()
    assert (np.asarray(ouro.exit_pass(cdf, 1)) == 3).all()
    early = np.asarray(ouro.exit_pass(cdf, 0.5))
    np.testing.assert_array_equal(early, 1 + (cdf[:-1] < 0.5).sum(axis=0))
    assert early.min() >= 1 and early.max() <= 3
    # the module's own gate, sown a pass, is the reference's
    with jax.default_matmul_precision("highest"):
        _, sown = module.apply({"params": weights.nest(w)}, ids, mutable=["intermediates"])
    lam = jax.nn.sigmoid(jnp.stack(sown["intermediates"]["model"]["exit_gate_logits"])[:, 0])
    np.testing.assert_allclose(np.asarray(lam[0]), cdf[0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(lam[0] + (1 - lam[0]) * lam[1]), cdf[1], atol=1e-5)


def test_a_threshold_under_one_is_refused_by_the_program_and_by_the_reference():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ouro.program_module(dict(TINY, early_exit_threshold=0.9), 64)
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ouro.reference_logits(dict(TINY, early_exit_threshold=0.9), {}, np.zeros((4,), np.int32))


def test_gap_of_the_reference_s_own_greedy_token_is_nought():
    w = weights.make_weights(ouro.weight_specs(TINY), TINY["initializer_range"], 3)
    ids = np.random.default_rng(1).integers(0, 256, size=(32,), dtype=np.int32)
    ref = jax.jit(lambda w, i: ouro.reference_logits(TINY, w, i))
    for t in range(31):
        ids[t + 1] = np.asarray(ref(w, ids))[t].argmax()
    gap, margin, low = check.build_gap_fn(ouro, TINY, "int8")(w, ids)
    assert float(np.max(np.asarray(gap)[:31])) == 0.0
    assert np.isinf(np.asarray(margin)).all()      # a dense model routes nothing
    assert float(np.max(np.asarray(low)[:31])) >= 0.0


# -- the cell's own pieces --------------------------------------------------------------


def test_the_program_passes_the_cell_s_tiny_limits_and_the_int8_control_fails_one():
    from test_chipbench_control import tiny_readings

    numbers, limits = tiny_readings(CELL, 1)
    assert numbers["tokens"] >= 30 and numbers["tokens_left_out"] == 0
    ok, compared = check.judge(numbers, limits)
    assert ok, compared
    control = {k.removeprefix("control_"): v for k, v in numbers.items()
               if k.startswith("control_")}
    ok, compared = check.judge(control, limits)
    assert not ok, compared


def _read_cache_live_pct(counters):
    cell = spec.load_cell(CELL)
    ctx = MetricContext(cell=cell, peaks={}, result={"counters": counters}, trace=None)
    return cell.readers["cache_live_pct"](ctx)


@pytest.mark.parametrize("counters,want", [
    ({"cache": {"planes": 192, "bytes_per_token": 1572864, "live_rows_mean": 1802.24}}, 44.0),
    ({"cache": {"planes": 192, "bytes_per_token": 1572864, "live_rows_mean": None}}, None),
    ({"ticks": 12, "decode_steps": 12, "mean_occupancy": 7.9}, None),   # the parent's stats()
    ({}, None),
], ids=["counted", "no_decode_step", "a_program_without_the_block", "no_counters"])
def test_cache_live_pct_on_a_hand_written_counters_dict(counters, want):
    # 8 slots of 512 rows: 4096 rows a plane
    got = _read_cache_live_pct(counters)
    assert got is None if want is None else got == pytest.approx(want, rel=1e-12)


def test_the_manifest_has_the_cell_its_configuration_and_its_reader():
    manifest = spec.load_manifest()
    (entry,) = [c for c in manifest["configs"] if c["name"] == "ouro-2.6b"]
    assert entry["reduced"] == [] and OURO["reduced"] == [] and OURO["published"] == {}
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "closed_loop_reasoning", 1)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert per_layer["cache_live_pct"]["workloads"] == [CELL]
    assert per_layer["cache_live_pct"]["moves"] == "serve_tok_s"
    loaded = spec.load_cell(CELL)
    assert {m["name"] for m in loaded.end_to_end} == {"serve_tok_s", "tpot_p95_ms", "setup_s"}
    assert {"slot_occupancy_pct", "decode_step_ms", "prefill_share_pct", "decode_hbm_roofline",
            "serve_mfu_pct", "device_idle_pct.serve", "cache_live_pct"} == set(loaded.readers)


def test_the_cell_s_rehearsal_is_correct_and_reads_the_engine_s_cache_counters():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
                        "2500000011", "--seconds", "3", "--trace", "1", "--rehearse"],
                       cwd=spec.ROOT, env=env, text=True, capture_output=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}, p.stderr[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"cache_live_pct", "slot_occupancy_pct"} <= set(line["readers_with_a_value"])
