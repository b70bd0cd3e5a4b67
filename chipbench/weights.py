"""Seeded weights, made on the device in one jitted call, in bfloat16.

A family's file says which leaves there are (``weight_specs``): a flat dict
from the leaf's path to ``(shape, kind)``, kind ``"normal"`` (mean 0, the
configuration's ``initializer_range``) or ``"norm"`` (1 + 0.1 x normal, an
RMSNorm scale). This file draws them. The program gets the leaves nested by
their paths; the reference reads the same flat dict.
"""

from __future__ import annotations

import math

import numpy as np

# Leaves are drawn in slices of at most this many elements along their leading
# axes, so the float32 draw of a 3.8 GB leaf never exists whole.
_SLICE_ELEMENTS = 2 ** 28


# Drawn with XLA's own bit generator ("rbg"): on the TPU it fills 7.5 GB in
# seconds where the default threefry takes most of a minute. The same seed
# gives the same bits on the same backend, which is all a run needs.
_RNG_IMPL = "rbg"


def key_data(seed: int) -> np.ndarray:
    """Key words for a seed of any size (seeds pass 2**31)."""
    return np.random.SeedSequence(int(seed)).generate_state(4).astype(np.uint32)


def _draw_leaf(key, shape, kind: str, std: float):
    import jax
    import jax.numpy as jnp

    k = 0
    while math.prod(shape[k:]) > _SLICE_ELEMENTS:
        k += 1
    lead, rest = shape[:k], shape[k:]

    def one(sub):
        x = jax.random.normal(sub, rest, jnp.float32)
        x = 1.0 + 0.1 * x if kind == "norm" else std * x
        return x.astype(jnp.bfloat16)

    if not lead:
        return one(key)
    n = math.prod(lead)
    out = jax.lax.map(one, jax.random.split(key, n))
    return out.reshape(*lead, *rest)  # leading axes only: no data moves


def make_weights(specs: dict, std: float, seed: int) -> dict:
    """All leaves of ``specs`` from ``seed``, one jitted call."""
    import jax

    names = sorted(specs)

    def make(words):
        key = jax.random.wrap_key_data(words, impl=_RNG_IMPL)
        return {
            name: _draw_leaf(jax.random.fold_in(key, i), tuple(specs[name][0]),
                             specs[name][1], std)
            for i, name in enumerate(names)
        }

    return jax.jit(make)(key_data(seed))


def nest(flat: dict) -> dict:
    """``{"a/b/c": x}`` to ``{"a": {"b": {"c": x}}}``: the program's tree."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def n_params(specs: dict) -> int:
    return sum(math.prod(shape) for shape, _ in specs.values())
