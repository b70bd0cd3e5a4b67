"""Parity tests: blockwise flash / ring (cp) / Ulysses (sp) attention must all
match the naive reference attention (the reference's CP/SP correctness
contract, SURVEY.md §7 hard-part 4)."""

import numpy as np
import pytest


def _qkv(b=2, s=64, hq=4, hkv=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, s, hq, d)).astype(np.float32)
    k = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2])
def test_blockwise_matches_naive(causal, hkv):
    from accelerate_tpu.models.llama import naive_attention
    from accelerate_tpu.ops import blockwise_attention

    q, k, v = _qkv(hkv=hkv)
    ref = naive_attention(*map(np.asarray, (q, k, v)), causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_blockwise_unpadded_vs_padded_blocks():
    from accelerate_tpu.ops import blockwise_attention
    from accelerate_tpu.models.llama import naive_attention

    q, k, v = _qkv(s=60)  # 60 not divisible by block 16 → padding path
    ref = naive_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def _mesh_cfg(cp=1, sp=1):
    from accelerate_tpu import AcceleratorState, ParallelismConfig

    AcceleratorState._reset_state()
    cfg = ParallelismConfig(cp_size=cp, sp_size=sp)
    state = AcceleratorState(parallelism_config=cfg)
    return state.mesh, cfg


@pytest.mark.parametrize("rotate", ["alltoall", "allgather"])
def test_ring_attention_matches_naive(rotate):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu.models.llama import naive_attention
    from accelerate_tpu.parallel.cp import ring_attention

    mesh, _ = _mesh_cfg(cp=4)
    q, k, v = _qkv(s=64)
    ref = naive_attention(q, k, v, causal=True)
    sharding = NamedSharding(mesh, P(None, "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, causal=True, mesh=mesh, rotate_method=rotate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_inside_jit():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu.models.llama import naive_attention
    from accelerate_tpu.parallel.cp import ring_attention

    mesh, _ = _mesh_cfg(cp=4)
    q, k, v = _qkv(s=64)
    ref = naive_attention(q, k, v, causal=True)
    sharding = NamedSharding(mesh, P(None, "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    fn = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=True, mesh=mesh))
    out = fn(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_naive():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu.models.llama import naive_attention
    from accelerate_tpu.parallel.sp import ulysses_attention

    mesh, _ = _mesh_cfg(sp=4)
    q, k, v = _qkv(s=64, hq=8, hkv=8)
    ref = naive_attention(q, k, v, causal=True)
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    out = ulysses_attention(qs, ks, vs, causal=True, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ulysses_gqa():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu.models.llama import naive_attention
    from accelerate_tpu.parallel.sp import ulysses_attention

    mesh, _ = _mesh_cfg(sp=4)
    q, k, v = _qkv(s=32, hq=8, hkv=2)
    ref = naive_attention(q, k, v, causal=True)
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    qs = jax.device_put(q, sharding)
    ks = jax.device_put(k, sharding)
    vs = jax.device_put(v, sharding)
    out = ulysses_attention(qs, ks, vs, causal=True, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas flash kernel (interpret mode on the CPU mesh; compiled on real TPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2])
def test_pallas_flash_forward_matches_naive(causal, hkv):
    from accelerate_tpu.models.llama import naive_attention
    from accelerate_tpu.ops.pallas_flash import pallas_flash_attention

    q, k, v = _qkv(s=160, hkv=hkv, d=16)  # non-multiple of block → padding path
    ref = naive_attention(*map(np.asarray, (q, k, v)), causal=causal)
    out = pallas_flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_flash_offsets_match_blockwise():
    from accelerate_tpu.ops import blockwise_attention
    from accelerate_tpu.ops.pallas_flash import pallas_flash_attention

    q, k, v = _qkv(s=128, d=16)
    # ring-chunk semantics: q is the second chunk, k the first → fully visible
    ref = blockwise_attention(q, k, v, causal=True, q_offset=128, k_offset=0, block_k=32)
    out = pallas_flash_attention(q, k, v, causal=True, q_offset=128, k_offset=0,
                                 block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # future chunk: q before every key → empty attention, exact zeros
    out = pallas_flash_attention(q, k, v, causal=True, q_offset=0, k_offset=128,
                                 block_q=128, block_k=128, interpret=True)
    assert float(np.max(np.abs(np.asarray(out)))) == 0.0


def test_pallas_flash_gradients_match_blockwise():
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops import blockwise_attention
    from accelerate_tpu.ops.pallas_flash import pallas_flash_attention

    q, k, v = _qkv(s=128, hq=4, hkv=2, d=16)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    g_ref = jax.grad(loss(lambda q, k, v: blockwise_attention(q, k, v, causal=True, block_k=32)),
                     argnums=(0, 1, 2))(q, k, v)
    g_pf = jax.grad(loss(lambda q, k, v: pallas_flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_pf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-3, atol=1e-4,
                                   err_msg=f"d{name}")


def test_merge_flash_chunks_exact():
    """Splitting keys into two chunks and merging (out, lse) must equal
    single-shot attention — the invariant ring attention rests on."""
    import jax.numpy as jnp

    from accelerate_tpu.ops import blockwise_attention
    from accelerate_tpu.ops.pallas_flash import (
        merge_flash_chunks,
        pallas_flash_attention_with_lse,
    )

    q, k, v = _qkv(s=128, d=16)
    ref = blockwise_attention(q, k, v, causal=True, block_k=32)
    o1, l1 = pallas_flash_attention_with_lse(
        q, k[:, :64], v[:, :64], causal=True, q_offset=0, k_offset=0,
        block_q=128, block_k=64, interpret=True)
    o2, l2 = pallas_flash_attention_with_lse(
        q, k[:, 64:], v[:, 64:], causal=True, q_offset=0, k_offset=64,
        block_q=128, block_k=64, interpret=True)
    out, _ = merge_flash_chunks(o1, l1, o2, l2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_flash_under_shard_map_dp_tp():
    """The Mosaic kernel has no GSPMD partition rule, so multi-device meshes
    run it inside shard_map (ops.flash_attention.auto_flash_attention). This
    exercises exactly that wrapper wiring on the virtual mesh with the kernel
    interpreted per-shard."""
    import functools

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import AcceleratorState, ParallelismConfig
    from accelerate_tpu.ops import blockwise_attention
    from accelerate_tpu.ops.pallas_flash import pallas_flash_attention

    AcceleratorState._reset_state()
    state = AcceleratorState(parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2))
    mesh = state.mesh
    q, k, v = _qkv(b=4, s=128, hq=4, hkv=4, d=16)
    spec = P(("dp_replicate", "dp_shard"), None, "tp", None)
    fn = functools.partial(pallas_flash_attention, causal=True, block_q=64, block_k=64,
                           interpret=True)
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                               check_vma=False)
    q_s = jax.device_put(q, NamedSharding(mesh, spec))
    k_s = jax.device_put(k, NamedSharding(mesh, spec))
    v_s = jax.device_put(v, NamedSharding(mesh, spec))
    out = sharded(q_s, k_s, v_s)
    ref = blockwise_attention(q, k, v, causal=True, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
