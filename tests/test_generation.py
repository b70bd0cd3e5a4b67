"""KV-cache generation: exact parity with the full re-forward loop.

The cached decode path re-implements the Llama block math on raw param trees;
these tests pin it to ``module.apply`` token for token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import Model, generate, init_cache, sample_logits
from accelerate_tpu.generation import _llama_forward_cached
from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.utils import set_seed


@pytest.fixture(scope="module")
def llama():
    set_seed(0)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native")
    module = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(0), ids)
    return cfg, module, model, jnp.asarray(ids)


def test_prefill_logits_match_full_forward(llama):
    cfg, module, model, ids = llama
    cache = init_cache(cfg, ids.shape[0], 32)
    logits, cache = _llama_forward_cached(cfg, model.params, ids, cache)
    full = module.apply({"params": model.params}, ids)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, -1]), rtol=2e-5, atol=2e-5
    )
    assert int(cache.length) == ids.shape[1]


def test_decode_step_matches_full_forward(llama):
    """Incremental decode at position S == column S of a full forward."""
    cfg, module, model, ids = llama
    nxt = jnp.asarray([[7], [11]], jnp.int32)
    cache = init_cache(cfg, 2, 32)
    _, cache = _llama_forward_cached(cfg, model.params, ids, cache)
    step_logits, _ = _llama_forward_cached(cfg, model.params, nxt, cache)
    full = module.apply({"params": model.params}, jnp.concatenate([ids, nxt], 1))
    np.testing.assert_allclose(
        np.asarray(step_logits), np.asarray(full[:, -1]), rtol=2e-5, atol=2e-5
    )


def test_greedy_generate_matches_naive_loop(llama):
    cfg, module, model, ids = llama
    n = 6
    got = generate(model, ids, max_new_tokens=n)
    assert got.shape == (2, ids.shape[1] + n)

    out = ids
    for _ in range(n):
        logits = module.apply({"params": model.params}, out)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        out = jnp.concatenate([out, tok[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(out))


def test_generate_eos_padding(llama):
    cfg, module, model, ids = llama
    # Find what greedy emits first, then declare it EOS: everything after
    # must be EOS too.
    first = generate(model, ids, max_new_tokens=1)[:, -1]
    eos = int(first[0])
    got = generate(model, ids, max_new_tokens=5, eos_token_id=eos)
    row = np.asarray(got[0, ids.shape[1]:])
    assert row[0] == eos and (row == eos).all()


def test_generate_sampling_deterministic_with_key(llama):
    cfg, module, model, ids = llama
    a = generate(model, ids, max_new_tokens=4, temperature=0.8, top_k=20,
                 rng=jax.random.key(3))
    b = generate(model, ids, max_new_tokens=4, temperature=0.8, top_k=20,
                 rng=jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jnp.all(a[:, :ids.shape[1]] == ids)


def test_generate_respects_max_positions(llama):
    cfg, module, model, ids = llama
    with pytest.raises(ValueError, match="max_position_embeddings"):
        generate(model, ids, max_new_tokens=cfg.max_position_embeddings)


def test_sample_logits_top_p_masks_tail():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    # top_p=0.6: keep {0.5, 0.3}; with a key stuck on the tail region the
    # sample must still come from the kept set.
    for seed in range(8):
        tok = int(sample_logits(logits, jax.random.key(seed), temperature=1.0, top_p=0.6)[0])
        assert tok in (0, 1)


def test_sample_logits_top_k():
    logits = jnp.asarray([[1.0, 5.0, 4.0, -2.0]])
    for seed in range(8):
        tok = int(sample_logits(logits, jax.random.key(seed), temperature=1.0, top_k=2)[0])
        assert tok in (1, 2)


def test_gqa_generation_parity():
    """GQA (Hkv < Hq) through the cache == full forward."""
    set_seed(1)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native",
                           num_attention_heads=4, num_key_value_heads=2)
    module = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 5), dtype=np.int32))
    model = Model.from_flax(module, jax.random.key(0), ids)
    got = generate(model, ids, max_new_tokens=4)
    out = ids
    for _ in range(4):
        logits = module.apply({"params": model.params}, out)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        out = jnp.concatenate([out, tok[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(out))


def test_gpt2_greedy_generate_matches_naive_loop():
    from accelerate_tpu.models import GPT2Config, GPT2LMHeadModel

    set_seed(2)
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHeadModel(cfg)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 6), dtype=np.int32))
    model = Model.from_flax(module, jax.random.key(0), ids)
    got = generate(model, ids, max_new_tokens=5)
    out = ids
    for _ in range(5):
        logits = module.apply({"params": model.params}, out)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        out = jnp.concatenate([out, tok[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(out))


def test_hub_model_generates_like_transformers():
    """tiny HF Llama -> convert -> our greedy generate == HF .generate greedy."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from accelerate_tpu.models import model_from_pretrained

    hf_cfg = transformers.LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    hf.eval()
    ids = np.random.default_rng(3).integers(0, 96, (1, 6)).astype(np.int64)
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(ids), max_new_tokens=5, do_sample=False,
            pad_token_id=0,
        ).numpy()
    ours = model_from_pretrained(hf, dtype=jnp.float32)
    got = generate(ours, ids.astype(np.int32), max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))


def test_generation_config_and_pad_token(llama):
    from accelerate_tpu import GenerationConfig

    cfg, module, model, ids = llama
    first = generate(model, ids, max_new_tokens=1)[:, -1]
    eos = int(first[0])
    got = generate(
        model, ids,
        config=GenerationConfig(max_new_tokens=5, eos_token_id=eos, pad_token_id=9),
    )
    row = np.asarray(got[0, ids.shape[1]:])
    assert row[0] == eos and (row[1:] == 9).all()


def test_opt_greedy_generate_matches_naive_loop():
    from accelerate_tpu.models import OPTConfig, OPTForCausalLM

    set_seed(3)
    cfg = OPTConfig.tiny(dtype=jnp.float32)
    module = OPTForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 6), dtype=np.int32))
    model = Model.from_flax(module, jax.random.key(0), ids)
    got = generate(model, ids, max_new_tokens=5)
    out = ids
    for _ in range(5):
        logits = module.apply({"params": model.params}, out)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        out = jnp.concatenate([out, tok[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(out))


def test_neox_greedy_generate_matches_naive_loop():
    from accelerate_tpu.models import GPTNeoXConfig, GPTNeoXForCausalLM

    for parallel in (True, False):
        set_seed(4)
        cfg = GPTNeoXConfig.tiny(dtype=jnp.float32, use_parallel_residual=parallel)
        module = GPTNeoXForCausalLM(cfg)
        ids = jnp.asarray(np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 6), dtype=np.int32))
        model = Model.from_flax(module, jax.random.key(0), ids)
        got = generate(model, ids, max_new_tokens=4)
        out = ids
        for _ in range(4):
            logits = module.apply({"params": model.params}, out)
            tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
            out = jnp.concatenate([out, tok[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(out))


def test_mixtral_greedy_generate_matches_naive_loop():
    import dataclasses

    from accelerate_tpu.models import MixtralConfig, MixtralForCausalLM

    set_seed(5)
    # High capacity so the GShard training path is dropless too — then the
    # dense decode dispatch and the training forward agree exactly.
    cfg = dataclasses.replace(
        MixtralConfig.tiny(dtype=jnp.float32), capacity_factor=8.0
    )
    module = MixtralForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, (1, 5), dtype=np.int32))
    model = Model.from_flax(module, jax.random.key(0), ids)
    got = generate(model, ids, max_new_tokens=4)
    out = ids
    for _ in range(4):
        logits = module.apply({"params": model.params}, out)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        out = jnp.concatenate([out, tok[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(out))


@pytest.fixture(scope="module")
def looped():
    """The Llama chassis with its two layers run three times over one set of
    weights, sandwich norms and the exit gate: six cache planes."""
    set_seed(4)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native", total_ut_steps=3,
                           sandwich_norm=True, early_exit_gate=True)
    module = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 9), dtype=np.int32)
    model = Model.from_flax(module, jax.random.key(4), ids)
    # norm scales away from 1, so that a norm left out or applied twice shows
    leaves, tree = jax.tree.flatten(model.params)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    model.params = jax.tree.unflatten(
        tree, [x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return cfg, module, model, jnp.asarray(ids)


def test_looped_greedy_generate_matches_naive_loop(looped):
    cfg, module, model, ids = looped
    got = generate(model, ids, max_new_tokens=5)
    out = ids
    for _ in range(5):
        logits = module.apply({"params": model.params}, out)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        out = jnp.concatenate([out, tok[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(out))


def test_looped_cached_forward_in_chunks_matches_full_forward(looped):
    """Prefill in two chunks, then token by token: every position's logits
    are the module's, so each pass read its own planes and no other's."""
    cfg, module, model, ids = looped
    cache = init_cache(cfg, 2, 16)
    assert cache.n_layers == 3 * cfg.num_hidden_layers
    parts = []
    for lo, hi in ((0, 4), (4, 7), (7, 8), (8, 9)):
        logits, cache = _llama_forward_cached(cfg, model.params, ids[:, lo:hi], cache,
                                              return_all=True)
        parts.append(logits)
    full = module.apply({"params": model.params}, ids)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, 1)), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    assert int(cache.length) == 9


def test_looped_module_differs_from_one_pass_and_sows_a_gate_logit_a_pass(looped):
    import dataclasses

    cfg, module, model, ids = looped
    full, sown = module.apply({"params": model.params}, ids, mutable=["intermediates"])
    gates = sown["intermediates"]["model"]["exit_gate_logits"]
    assert len(gates) == 3 and all(g.shape == ids.shape for g in gates)
    once = LlamaForCausalLM(dataclasses.replace(cfg, total_ut_steps=1)).apply(
        {"params": model.params}, ids)
    assert float(jnp.max(jnp.abs(full - once))) > 1e-2


@pytest.mark.parametrize("knobs,key", [
    ({"early_exit_threshold": 0.5}, "early_exit_threshold"),
    ({"total_ut_steps": 0}, "total_ut_steps"),
])
def test_a_looped_knob_that_is_not_computed_is_refused_at_config_time(knobs, key):
    with pytest.raises(ValueError, match=key):
        LlamaConfig.tiny(**knobs)


def test_walkers_that_run_the_stack_once_refuse_a_config_that_asks_for_more():
    from accelerate_tpu.big_modeling import _llama_spec
    from accelerate_tpu.models import MixtralConfig, MixtralForCausalLM
    from accelerate_tpu.parallel.pp import _llama_stage_fn

    cfg = LlamaConfig.tiny(total_ut_steps=2)
    for walk in (lambda: _llama_spec(cfg), lambda: _llama_stage_fn(cfg),
                 lambda: MixtralForCausalLM(MixtralConfig.tiny(total_ut_steps=2)).init(
                     jax.random.key(0), np.ones((1, 4), np.int32))):
        with pytest.raises(NotImplementedError, match="total_ut_steps"):
            walk()


def test_beam_search_beam1_equals_greedy(llama):
    from accelerate_tpu import beam_search

    cfg, module, model, ids = llama
    greedy = generate(model, ids, max_new_tokens=5)
    beamed = beam_search(model, ids, max_new_tokens=5, num_beams=1)
    np.testing.assert_array_equal(np.asarray(beamed), np.asarray(greedy))


def test_beam_search_finds_exhaustive_optimum():
    """vocab=16, 2 new tokens, num_beams=16: the beam covers every first
    token, so the result must be the global-logprob argmax (computed by brute
    force over all 256 continuations)."""
    from accelerate_tpu import beam_search
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    set_seed(7)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="native", vocab_size=16)
    module = LlamaForCausalLM(cfg)
    ids = jnp.asarray([[3, 1, 4]], jnp.int32)
    model = Model.from_flax(module, jax.random.key(0), ids)

    got = beam_search(model, ids, max_new_tokens=2, num_beams=16, length_penalty=1.0)

    # Brute force: score every (a, b) continuation by summed logprob.
    best_score, best_pair = -np.inf, None
    logits0 = module.apply({"params": model.params}, ids)
    lp0 = np.asarray(jax.nn.log_softmax(logits0[:, -1].astype(jnp.float32), -1))[0]
    for a in range(16):
        ext = jnp.concatenate([ids, jnp.asarray([[a]], jnp.int32)], 1)
        logits1 = module.apply({"params": model.params}, ext)
        lp1 = np.asarray(jax.nn.log_softmax(logits1[:, -1].astype(jnp.float32), -1))[0]
        for bb in range(16):
            sc = lp0[a] + lp1[bb]
            if sc > best_score:
                best_score, best_pair = sc, (a, bb)
    assert tuple(np.asarray(got[0, 3:]).tolist()) == best_pair


def test_beam_search_eos_freezes_and_pads(llama):
    from accelerate_tpu import beam_search

    cfg, module, model, ids = llama
    first = generate(model, ids, max_new_tokens=1)[:, -1]
    eos = int(first[0])
    out = beam_search(model, ids, max_new_tokens=4, num_beams=3, eos_token_id=eos)
    assert out.shape == (2, ids.shape[1] + 4)
    row = np.asarray(out[0, ids.shape[1]:])
    if row[0] == eos:
        assert (row == eos).all()


def test_speculative_generate_exactly_matches_greedy(llama):
    """Draft-accelerated decoding must reproduce the target's greedy output
    bit-for-bit, whatever the draft proposes."""
    from accelerate_tpu import speculative_generate

    cfg, module, model, ids = llama
    prompt = ids[:1]
    want = generate(model, prompt, max_new_tokens=10)

    # Draft 1: the target itself (all proposals accepted — fastest path).
    got_self = speculative_generate(model, model, prompt, max_new_tokens=10)
    np.testing.assert_array_equal(np.asarray(got_self), np.asarray(want))

    # Draft 2: a DIFFERENT tiny model (frequent rejections).
    set_seed(99)
    other = Model.from_flax(
        type(module)(cfg), jax.random.key(99), np.asarray(prompt)
    )
    got_other = speculative_generate(model, other, prompt, max_new_tokens=10,
                                     num_draft_tokens=3)
    np.testing.assert_array_equal(np.asarray(got_other), np.asarray(want))


def test_speculative_generate_eos(llama):
    from accelerate_tpu import speculative_generate

    cfg, module, model, ids = llama
    prompt = ids[:1]
    eos = int(generate(model, prompt, max_new_tokens=1)[0, -1])
    out = speculative_generate(model, model, prompt, max_new_tokens=6, eos_token_id=eos)
    row = np.asarray(out[0, prompt.shape[1]:])
    assert out.shape == (1, prompt.shape[1] + 6)
    assert row[0] == eos and (row == eos).all()


# ---------------------------------------------------------------------------
# Encoder-decoder generation (T5, Whisper) — round-3
# ---------------------------------------------------------------------------


def _tiny_t5(dtype=jnp.float32):
    from accelerate_tpu.models import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny(dtype=dtype, num_layers=3)
    module = T5ForConditionalGeneration(cfg)
    rng = np.random.default_rng(0)
    enc_ids = rng.integers(1, cfg.vocab_size, (2, 10)).astype(np.int32)
    params = module.init(jax.random.key(0), enc_ids, enc_ids[:, :4])["params"]
    return Model(module=module, params=params), cfg, enc_ids


def _tiny_whisper(dtype=jnp.float32):
    from accelerate_tpu.models import WhisperConfig, WhisperForConditionalGeneration

    cfg = WhisperConfig.tiny(dtype=dtype)
    module = WhisperForConditionalGeneration(cfg)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(2, 24, cfg.num_mel_bins)).astype(np.float32)
    dec0 = np.zeros((2, 1), np.int32)
    params = module.init(jax.random.key(0), feats, dec0)["params"]
    return Model(module=module, params=params), cfg, feats


def test_t5_cached_decode_matches_full_forward():
    from accelerate_tpu.generation import _t5_decode, _t5_encode, init_cache

    model, cfg, enc_ids = _tiny_t5()
    rng = np.random.default_rng(1)
    dec_ids = rng.integers(1, cfg.vocab_size, (2, 7)).astype(np.int32)
    full = model.module.apply({"params": model.params}, enc_ids, dec_ids)

    st = _t5_encode(cfg, model.params, enc_ids)
    logits, _ = _t5_decode(
        cfg, model.params, jnp.asarray(dec_ids), init_cache(cfg, 2, 7), st, return_all=True
    )
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full), rtol=2e-5, atol=2e-5)
    # Token-by-token through the cache must agree with teacher forcing.
    c, outs = init_cache(cfg, 2, 7), []
    for t in range(7):
        lg, c = _t5_decode(cfg, model.params, jnp.asarray(dec_ids[:, t : t + 1]), c, st,
                           return_all=True)
        outs.append(lg[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(full), rtol=2e-5, atol=2e-5
    )


def test_whisper_cached_decode_matches_full_forward():
    from accelerate_tpu.generation import _whisper_decode, _whisper_encode, init_cache

    model, cfg, feats = _tiny_whisper()
    rng = np.random.default_rng(1)
    dec_ids = rng.integers(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    full = model.module.apply({"params": model.params}, feats, dec_ids)

    st = _whisper_encode(cfg, model.params, feats)
    logits, _ = _whisper_decode(
        cfg, model.params, jnp.asarray(dec_ids), init_cache(cfg, 2, 6), st, return_all=True
    )
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full), rtol=2e-5, atol=2e-5)


def test_t5_greedy_generate_matches_naive_loop():
    """generate() == argmax loop over the full (uncached) module forward."""
    model, cfg, enc_ids = _tiny_t5()
    n = 6
    got = generate(model, enc_ids, max_new_tokens=n)

    dec = np.full((2, 1), cfg.decoder_start_token_id, np.int32)
    for _ in range(n):
        logits = model.module.apply({"params": model.params}, enc_ids, jnp.asarray(dec))
        nxt = np.asarray(jnp.argmax(logits[:, -1].astype(jnp.float32), -1))[:, None]
        dec = np.concatenate([dec, nxt.astype(np.int32)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), dec)


def test_whisper_greedy_generate_matches_naive_loop():
    model, cfg, feats = _tiny_whisper()
    n = 5
    prompt = np.asarray([[3], [3]], np.int32)  # a forced SOT-style prompt
    got = generate(model, feats, max_new_tokens=n, decoder_input_ids=prompt)

    dec = prompt.copy()
    for _ in range(n):
        logits = model.module.apply({"params": model.params}, feats, jnp.asarray(dec))
        nxt = np.asarray(jnp.argmax(logits[:, -1].astype(jnp.float32), -1))[:, None]
        dec = np.concatenate([dec, nxt.astype(np.int32)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), dec)


def test_t5_beam1_equals_greedy():
    from accelerate_tpu.generation import beam_search

    model, cfg, enc_ids = _tiny_t5()
    greedy = generate(model, enc_ids, max_new_tokens=5)
    beam = beam_search(model, enc_ids, max_new_tokens=5, num_beams=1)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(beam))


def test_t5_beam_search_runs_multi_beam():
    from accelerate_tpu.generation import beam_search

    model, cfg, enc_ids = _tiny_t5()
    out = beam_search(model, enc_ids, max_new_tokens=4, num_beams=3)
    assert out.shape == (2, 1 + 4)


def test_t5_hub_generates_like_transformers():
    """tiny HF T5 -> convert -> our greedy generate == HF .generate greedy."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from accelerate_tpu.models import model_from_pretrained

    hf_cfg = transformers.T5Config(
        vocab_size=96, d_model=32, d_kv=8, d_ff=64, num_layers=2, num_heads=4,
        relative_attention_num_buckets=8, relative_attention_max_distance=16,
        decoder_start_token_id=0, pad_token_id=0, eos_token_id=1,
    )
    torch.manual_seed(0)
    hf = transformers.T5ForConditionalGeneration(hf_cfg)
    hf.eval()
    ids = np.random.default_rng(3).integers(2, 96, (2, 8)).astype(np.int64)
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(ids), max_new_tokens=5, do_sample=False, min_length=0,
        ).numpy()
    ours = model_from_pretrained(hf, dtype=jnp.float32)
    got = generate(ours, ids.astype(np.int32), max_new_tokens=5, eos_token_id=1)
    np.testing.assert_array_equal(np.asarray(got)[:, : want.shape[1]], want.astype(np.int32))


def test_whisper_hub_transcribe_parity():
    """tiny HF Whisper -> convert -> our greedy tokens == HF greedy loop over
    its own forward (HF whisper.generate injects task-token logic; the
    forward loop is the precise contract)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from accelerate_tpu.models import model_from_pretrained

    hf_cfg = transformers.WhisperConfig(
        vocab_size=96, num_mel_bins=16, d_model=32, encoder_layers=2,
        decoder_layers=2, encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=64, decoder_ffn_dim=64, max_source_positions=24,
        max_target_positions=32, pad_token_id=0, bos_token_id=1, eos_token_id=2,
        decoder_start_token_id=1,
    )
    torch.manual_seed(0)
    hf = transformers.WhisperForConditionalGeneration(hf_cfg)
    hf.eval()
    rng = np.random.default_rng(5)
    feats = rng.normal(size=(1, 16, 48)).astype(np.float32)  # HF layout (B, mel, T)
    prompt = np.asarray([[50]], np.int64)
    dec = prompt.copy()
    with torch.no_grad():
        for _ in range(5):
            logits = hf(
                input_features=torch.from_numpy(feats),
                decoder_input_ids=torch.from_numpy(dec),
            ).logits
            nxt = logits[:, -1].argmax(-1, keepdim=True).numpy()
            dec = np.concatenate([dec, nxt], axis=1)

    ours = model_from_pretrained(hf, dtype=jnp.float32)
    got = generate(
        ours, np.transpose(feats, (0, 2, 1)),  # our layout (B, T, mel)
        max_new_tokens=5, decoder_input_ids=prompt.astype(np.int32),
    )
    np.testing.assert_array_equal(np.asarray(got), dec.astype(np.int32))


def test_llama_padded_batch_matches_transformers():
    """Left-padded batch + attention_mask: greedy tokens match HF exactly
    (the first practical thing a migrating user does with generate)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from accelerate_tpu.models import model_from_pretrained

    hf_cfg = transformers.LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=False, pad_token_id=0,
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    hf.eval()
    rng = np.random.default_rng(7)
    # Row 0: full 6-token prompt. Row 1: 3 tokens, left-padded with 3 zeros.
    row0 = rng.integers(1, 96, (6,))
    row1 = rng.integers(1, 96, (3,))
    ids = np.stack([row0, np.concatenate([[0, 0, 0], row1])]).astype(np.int64)
    mask = np.asarray([[1] * 6, [0, 0, 0, 1, 1, 1]], np.int64)
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(ids), attention_mask=torch.from_numpy(mask),
            max_new_tokens=5, do_sample=False, pad_token_id=0,
        ).numpy()
    ours = model_from_pretrained(hf, dtype=jnp.float32)
    got = generate(
        ours, ids.astype(np.int32), max_new_tokens=5,
        attention_mask=mask.astype(np.int32),
    )
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))


def test_padded_batch_matches_unpadded_row():
    """A left-padded row must generate the same tokens as the same prompt
    alone (padding must be invisible)."""
    llama_model, cfg, _ = _tiny_llama_for_pad()
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, (1, 4)).astype(np.int32)
    alone = generate(llama_model, prompt, max_new_tokens=6)

    padded = np.concatenate([np.zeros((1, 3), np.int32), prompt], axis=1)
    mask = np.asarray([[0, 0, 0, 1, 1, 1, 1]], np.int32)
    batched = generate(llama_model, padded, max_new_tokens=6, attention_mask=mask)
    np.testing.assert_array_equal(np.asarray(batched)[:, 7:], np.asarray(alone)[:, 4:])


def _tiny_llama_for_pad():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    module = LlamaForCausalLM(cfg)
    ids = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    model = Model.from_flax(module, jax.random.key(0), ids)
    return model, cfg, ids


def test_right_padded_mask_rejected():
    llama_model, cfg, ids = _tiny_llama_for_pad()
    bad = np.asarray([[1] * 8, [1, 1, 1, 1, 1, 0, 0, 0]], np.int32)
    with pytest.raises(ValueError, match="left-padded"):
        generate(llama_model, ids, max_new_tokens=2, attention_mask=bad)


@pytest.mark.parametrize("family", ["gpt2", "opt", "neox", "mixtral"])
def test_padded_batch_invisible_all_causal_families(family):
    """Left-padding must be invisible for every causal plan, not just Llama."""
    set_seed(11)
    if family == "gpt2":
        from accelerate_tpu.models import GPT2Config, GPT2LMHeadModel

        cfg = GPT2Config.tiny(dtype=jnp.float32)
        module = GPT2LMHeadModel(cfg)
    elif family == "opt":
        from accelerate_tpu.models import OPTConfig, OPTForCausalLM

        cfg = OPTConfig.tiny(dtype=jnp.float32)
        module = OPTForCausalLM(cfg)
    elif family == "neox":
        from accelerate_tpu.models import GPTNeoXConfig, GPTNeoXForCausalLM

        cfg = GPTNeoXConfig.tiny(dtype=jnp.float32)
        module = GPTNeoXForCausalLM(cfg)
    else:
        from accelerate_tpu.models import MixtralConfig, MixtralForCausalLM

        cfg = MixtralConfig.tiny(dtype=jnp.float32)
        module = MixtralForCausalLM(cfg)

    rng = np.random.default_rng(11)
    prompt = rng.integers(1, cfg.vocab_size, (1, 4)).astype(np.int32)
    model = Model.from_flax(module, jax.random.key(0), prompt)
    alone = generate(model, prompt, max_new_tokens=4)

    padded = np.concatenate([np.zeros((1, 2), np.int32), prompt], axis=1)
    mask = np.asarray([[0, 0, 1, 1, 1, 1]], np.int32)
    batched = generate(model, padded, max_new_tokens=4, attention_mask=mask)
    np.testing.assert_array_equal(np.asarray(batched)[:, 6:], np.asarray(alone)[:, 4:])


def test_generate_reuses_compiled_loop(llama):
    """Repeated generate() calls with identical settings must reuse ONE
    compiled loop (closures used to defeat jit's cache — a full recompile
    per call)."""
    from accelerate_tpu import generation as G

    cfg, module, model, ids = llama
    G._GEN_LOOP_CACHE.clear()
    a = generate(model, ids, max_new_tokens=3)
    assert len(G._GEN_LOOP_CACHE) == 1
    b = generate(model, ids, max_new_tokens=3)
    assert len(G._GEN_LOOP_CACHE) == 1  # same key -> same compiled loop
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    generate(model, ids, max_new_tokens=4)  # different settings -> new entry
    assert len(G._GEN_LOOP_CACHE) == 2


def test_suppress_tokens_matches_transformers():
    """suppress_tokens / begin_suppress_tokens: greedy parity with HF."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from accelerate_tpu.models import model_from_pretrained

    hf_cfg = transformers.LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    hf.eval()
    ids = np.random.default_rng(3).integers(0, 96, (1, 6)).astype(np.int64)
    # Suppress whatever unconstrained greedy picks first, to force divergence.
    with torch.no_grad():
        free = hf.generate(torch.from_numpy(ids), max_new_tokens=1, do_sample=False,
                           pad_token_id=0).numpy()
    banned = int(free[0, -1])
    with torch.no_grad():
        want = hf.generate(
            torch.from_numpy(ids), max_new_tokens=5, do_sample=False, pad_token_id=0,
            suppress_tokens=[banned], begin_suppress_tokens=[(banned + 1) % 96],
        ).numpy()
    ours = model_from_pretrained(hf, dtype=jnp.float32)
    got = generate(
        ours, ids.astype(np.int32), max_new_tokens=5,
        suppress_tokens=(banned,), begin_suppress_tokens=((banned + 1) % 96,),
    )
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))
    assert banned not in np.asarray(got)[0, 6:]


def test_forced_decoder_ids_whisper_style():
    """forced_decoder_ids pin tokens at absolute decoder positions (HF
    Whisper's [(1, lang), (2, task)] convention); the rest decode greedily."""
    model, cfg, feats = _tiny_whisper()
    prompt = np.asarray([[7], [7]], np.int32)  # decoder position 0
    forced = ((1, 40), (2, 41))
    got = generate(
        model, feats, max_new_tokens=5, decoder_input_ids=prompt,
        forced_decoder_ids=forced,
    )
    out = np.asarray(got)
    assert (out[:, 1] == 40).all() and (out[:, 2] == 41).all()

    # Positions 3+ must continue greedily FROM the forced prefix: the tail
    # equals unforced greedy decoding seeded with [7, 40, 41].
    seeded = generate(
        model, feats, max_new_tokens=3,
        decoder_input_ids=np.asarray([[7, 40, 41], [7, 40, 41]], np.int32),
    )
    np.testing.assert_array_equal(out[:, 3:], np.asarray(seeded)[:, 3:])
