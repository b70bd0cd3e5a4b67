"""Cross-lower the Pallas kernels for TPU from the CPU sandbox.

``interpret=False`` + ``lowering_platforms=("tpu",)`` runs the whole
Pallas-to-Mosaic lowering (tiling, scalar prefetch, block specs) without a
chip, at the shapes ``chip_smoke.py`` runs, so lowering cannot rot between
chip runs. libtpu's Mosaic compile and the runtime still need the chip."""

import functools
import importlib

import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.parallelism_config import ParallelismConfig

# (the package re-exports a ``flash_attention`` function over the module name)
fa = importlib.import_module("accelerate_tpu.ops.flash_attention")
pf = importlib.import_module("accelerate_tpu.ops.pallas_flash")

SHAPES = [  # chip_smoke.kernels_phase: (batch, seq, q heads, kv heads, head dim)
    (2, 2048, 16, 16, 128),
    (2, 1024, 8, 2, 64),
]


def _lower_for_tpu(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def _mosaic_calls(fn, *args) -> int:
    return _lower_for_tpu(fn, *args).count("tpu_custom_call")


def _qkv(b, s, hq, hkv, d):
    return tuple(jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16) for h in (hq, hkv, hkv))


@pytest.mark.parametrize("shape", SHAPES)
def test_flash_kernels_lower_for_tpu(shape):
    attn = functools.partial(pf.pallas_flash_attention, causal=True, interpret=False)
    assert _mosaic_calls(attn, *_qkv(*shape)) == 1  # forward

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    # forward + dQ + dK/dV
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(*shape)) == 3


@pytest.mark.parametrize("layout", [{"dp_shard_size": 4}, {"dp_shard_size": 2, "tp_size": 2}])
def test_auto_flash_shard_map_branch_lowers_for_tpu(layout, monkeypatch):
    """The multi-device branch only runs on a TPU backend; pretend to be one
    so the shard_map-wrapped kernel is what gets lowered."""
    monkeypatch.setattr(fa, "is_tpu_available", lambda: True)
    monkeypatch.setattr(pf, "is_tpu_available", lambda: True)
    mesh = ParallelismConfig(**layout).build_mesh(jax.devices()[:4])
    attn = functools.partial(fa.auto_flash_attention, causal=True, mesh=mesh)
    text = _lower_for_tpu(attn, *_qkv(8, 2048, 16, 16, 128))
    assert "sdy.manual_computation" in text  # the kernel sits inside shard_map
    assert text.count("tpu_custom_call") == 1
