"""``generation._moe_mlp`` contracts the tokens with the stacked expert weights
and combines through a (T, E) gate matrix. Here it is held, in float32 at
``MixtralConfig.tiny``'s sizes, to a per-token loop over each token's top-k
experts written in numpy (no code shared): the same experts, the same gates,
every routed product and no other."""

import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import _moe_mlp
from accelerate_tpu.models.moe import MixtralConfig

CFG = MixtralConfig.tiny()
E, K, H, F = (CFG.num_local_experts, CFG.num_experts_per_tok, CFG.hidden_size,
              CFG.intermediate_size)


def _weights(rng):
    return {"router": (rng.normal(size=(H, E)) / np.sqrt(H)).astype(np.float32),
            "w_gate": (rng.normal(size=(E, H, F)) / np.sqrt(H)).astype(np.float32),
            "w_up": (rng.normal(size=(E, H, F)) / np.sqrt(H)).astype(np.float32),
            "w_down": (rng.normal(size=(E, F, H)) / np.sqrt(F)).astype(np.float32)}


def _per_token_loop(p, h):
    """(out, the experts each token took): float64, one token and one expert at a time."""
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    tokens = np.asarray(h, np.float64).reshape(-1, H)
    out, taken = np.zeros_like(tokens), []
    for t, x in enumerate(tokens):
        logits = x @ w["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs)[:K]
        assert logits[top[-1]] - np.sort(logits)[-K - 1] > 1e-3, "a tie: rounding would route"
        taken.append(sorted(int(e) for e in top))
        for e in top:
            pre = x @ w["w_gate"][e]
            y = (pre / (1.0 + np.exp(-pre)) * (x @ w["w_up"][e])) @ w["w_down"][e]
            out[t] += probs[e] / probs[top].sum() * y
    return out.reshape(h.shape), taken


def _routed_to(rng, wanted_logits):
    """Tokens and a router under which token t's router logits are ``wanted_logits[t]``."""
    h = rng.normal(size=(len(wanted_logits), H))
    router = np.linalg.pinv(h) @ np.asarray(wanted_logits, np.float64)
    return h.astype(np.float32)[:, None, :], router.astype(np.float32)


def _moe(p, h):
    return np.asarray(_moe_mlp(CFG, {k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(h)))


def _check(p, h):
    want, taken = _per_token_loop(p, h)
    got = _moe(p, h)
    assert got.shape == h.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    return taken


@pytest.mark.parametrize("b,s", [(1, 1), (4, 1), (2, 5)], ids=["one_token", "slots", "chunks"])
def test_moe_mlp_equals_a_per_token_loop_over_the_routed_experts(b, s):
    rng = np.random.default_rng(10 * b + s)
    _check(_weights(rng), rng.normal(size=(b, s, H)).astype(np.float32))


@pytest.mark.parametrize("case", ["two_tokens_one_expert", "an_expert_no_token_reaches",
                                  "an_unrouted_expert_overflows"])
def test_moe_mlp_under_a_chosen_routing(case):
    rng = np.random.default_rng(7)
    p = _weights(rng)
    if case == "two_tokens_one_expert":
        wanted, routing = [[3, 1, 0, -2], [3, -2, 0, 1]], [[0, 1], [0, 3]]
    else:
        wanted, routing = [[3, 1, 0, -9], [0, 3, 1, -9], [1, -1, 2, -9]], [[0, 1], [1, 2], [0, 2]]
    h, p["router"] = _routed_to(rng, wanted)
    if case == "an_unrouted_expert_overflows":
        sound = _moe(p, h)
        for leaf in ("w_gate", "w_up", "w_down"):
            p[leaf][3] = 1e30   # silu(-inf) is NaN, inf x 0 is NaN: neither may reach the sum
        got = _moe(p, h)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, sound)
    assert _check(p, h) == routing
