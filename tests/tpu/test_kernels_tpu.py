"""Compiled-mode Pallas kernel health checks (real TPU only).

Each test compares the Mosaic-compiled kernel against either the Pallas
interpreter (same math, so tolerances are tight) or the pure-jnp blockwise
reference. These are exactly the pieces the CPU suite can only exercise
interpreted: tiling/SMEM lowering, scalar-prefetched dynamic offsets (the
ring-attention rotation contract), the GQA-folded backward, and the int8
decode dequant-at-matmul path.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.flash_attention import blockwise_attention
from accelerate_tpu.ops.pallas_flash import (
    pallas_flash_attention,
    pallas_flash_attention_with_lse,
)


def _qkv(b=2, sq=256, sk=256, hq=8, hkv=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sk, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sk, hkv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [8, 2])
def test_flash_fwd_compiled_matches_interpreter(causal, hkv):
    q, k, v = _qkv(hkv=hkv)
    fn = functools.partial(
        pallas_flash_attention_with_lse, causal=causal, block_q=128, block_k=128
    )
    out_c, lse_c = fn(q, k, v, interpret=False)
    out_i, lse_i = fn(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_i), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(lse_c), np.asarray(lse_i), rtol=2e-3, atol=2e-3)


def test_flash_fwd_matches_blockwise_reference():
    q, k, v = _qkv()
    out = pallas_flash_attention(q, k, v, causal=True, interpret=False)
    with jax.default_matmul_precision("highest"):
        ref = blockwise_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_flash_bwd_compiled_matches_interpreter():
    """The dQ and GQA-folded dK/dV kernels, compiled vs interpreted."""
    q, k, v = _qkv(hkv=2)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(q.shape), q.dtype)

    def loss(q, k, v, interpret):
        out = pallas_flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=interpret
        )
        return jnp.sum(out * cot)

    gc = jax.grad(functools.partial(loss, interpret=False), argnums=(0, 1, 2))(q, k, v)
    gi = jax.grad(functools.partial(loss, interpret=True), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gc, gi, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} compiled/interpreter mismatch",
        )


def test_flash_traced_offsets_compiled():
    """Dynamic q/k offsets via scalar prefetch — what ring attention feeds
    the kernel on rotated KV chunks — must lower and match the reference at
    several traced values without retracing."""
    q, k, v = _qkv(sq=128, sk=256)
    traces = {"n": 0}

    @jax.jit
    def fn(q, k, v, q_off, k_off):
        traces["n"] += 1
        return pallas_flash_attention(
            q, k, v, causal=True, q_offset=q_off, k_offset=k_off,
            block_q=128, block_k=128, interpret=False,
        )

    # Non-degenerate pairs only: a fully-masked chunk (every key after every
    # query) has undefined normalized output — see the fully_masked test.
    for q_off, k_off in [(0, 0), (256, 0), (256, 128)]:
        out = fn(q, k, v, jnp.int32(q_off), jnp.int32(k_off))
        with jax.default_matmul_precision("highest"):
            ref = blockwise_attention(
                q, k, v, causal=True, q_offset=q_off, k_offset=k_off
            )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2,
            err_msg=f"offsets ({q_off}, {k_off})",
        )
    assert traces["n"] == 1, "offsets retraced — not actually dynamic"


def test_fully_masked_chunk_convention():
    """Ring attention hands the kernel fully-masked chunks (causal, all keys
    after all queries). The contract that makes the lse-merge exact: zero
    output and lse == -inf, so the chunk's merge weight is exactly 0."""
    q, k, v = _qkv(sq=128, sk=128)
    out, lse = pallas_flash_attention_with_lse(
        q, k, v, causal=True, q_offset=jnp.int32(0), k_offset=jnp.int32(512),
        block_q=128, block_k=128, interpret=False,
    )
    assert float(jnp.max(jnp.abs(out))) == 0.0
    assert bool(jnp.all(jnp.isneginf(lse) | (lse < -1e29)))


def test_bf16_fwd_smoke():
    """bf16 is the production dtype; assert the compiled kernel lowers and
    stays sane (vs fp32 interpreter ground truth at bf16 tolerance)."""
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = pallas_flash_attention(q, k, v, causal=True, interpret=False)
    ref = pallas_flash_attention(
        jnp.float32(q), jnp.float32(k), jnp.float32(v), causal=True, interpret=True
    )
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=5e-2, atol=5e-2
    )


def test_int8_decode_matmul_parity():
    """DecodeQuant: int8-from-HBM matmul with the scale fused at the dot
    (generation._kernel's decode path) vs the fp32 kernel."""
    from accelerate_tpu.utils.quantization import (
        dequantize_decode_kernel,
        quantize_decode_kernel,
    )

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((4, 512, 256)) * 0.05, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 512)), jnp.bfloat16)
    dq = quantize_decode_kernel(w)
    assert dq.data.dtype == jnp.int8

    @jax.jit
    def decode_dot(x, dq):
        wl = dq.data[0].astype(jnp.bfloat16) * dq.scales[0].astype(jnp.bfloat16)
        return x @ wl

    got = decode_dot(x, dq)
    ref = jnp.asarray(x, jnp.float32) @ w[0]
    # Bound: int8 symmetric quant error ~ amax/127 per weight; with 512-dim
    # contraction the relative output error stays well under 2%.
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref), rtol=5e-2, atol=5e-2
    )
    # Round-trip dequant agrees with what the decode dot consumed.
    back = dequantize_decode_kernel(dq, jnp.float32)
    assert float(jnp.max(jnp.abs(back - w))) < float(jnp.max(dq.scales)) * 0.51


def test_fp8_native_matches_qdq_on_chip():
    """The native f8-operand dot path vs the QDQ formulation, compiled on
    real hardware — catches an XLA fp8 legalization producing different
    numerics than the simulation (fwd and both grads)."""
    from accelerate_tpu.ops.fp8 import fp8_dot_general

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    dn = (((1,), (0,)), ((), ()))
    nat = fp8_dot_general("HYBRID", native=True)
    ref = fp8_dot_general("HYBRID", native=False)
    # The QDQ reference runs at "highest" precision: at the TPU default its
    # f32 dot rounds the dequantized operands to bf16 (~0.4% an element, 0.16
    # absolute here on first contact with the v5e), while the native path
    # multiplies the f8 values exactly and scales afterwards.
    with jax.default_matmul_precision("highest"):
        want = ref(x, w, dn)
        gr = jax.grad(lambda x, w: jnp.sum(ref(x, w, dn) ** 2), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(
        np.asarray(nat(x, w, dn)), np.asarray(want), rtol=2e-3, atol=2e-3
    )
    gn = jax.grad(lambda x, w: jnp.sum(nat(x, w, dn) ** 2), argnums=(0, 1))(x, w)
    for a, b in zip(gn, gr):
        cos = float(jnp.sum(a * b) / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
        assert cos > 0.99, cos


def test_fp8_lowering_has_f8_types():
    """The fp8 recipe must actually lower with float8 types on chip (QDQ
    converts at minimum; native f8 dots where the recipe enables them)."""
    from accelerate_tpu.ops.fp8 import fp8_dot_general

    dot = fp8_dot_general("HYBRID")
    x = jnp.zeros((128, 256), jnp.bfloat16)
    w = jnp.zeros((256, 128), jnp.bfloat16)
    txt = (
        jax.jit(lambda a, b: dot(a, b, (((1,), (0,)), ((), ()))))
        .lower(x, w)
        .as_text()
        .lower()
    )
    assert "f8e4m3" in txt or "f8e5m2" in txt, "no float8 types in lowered HLO"


@pytest.mark.parametrize("hq,hkv,t_max", [(32, 8, 2048), (16, 16, 512)], ids=["gqa", "mha"])
def test_decode_attention_compiled_matches_attend(hq, hkv, t_max):
    """The decode kernel over the whole cache stack, compiled, at the serving
    cells' widths: equal to ``_attend`` over the layer's slice on the slots
    that decode, zeros on the one that does not, with bounds at a block's
    edge, inside a block and at ``T_max``."""
    from accelerate_tpu.generation import _attend
    from accelerate_tpu.ops.decode_attention import block_rows, decode_attention

    planes, slots, d = 3, 5, 128
    block = block_rows(t_max, hkv, d, jnp.bfloat16)
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (slots, 1, hq, d), jnp.bfloat16)
    ck = jax.random.normal(kk, (planes, slots, t_max, hkv, d), jnp.bfloat16)
    cv = jax.random.normal(kv, (planes, slots, t_max, hkv, d), jnp.bfloat16)
    bound = jnp.asarray([0, 1, block, block + 37, t_max], jnp.int32)
    ck, cv = ck.at[:, 0].set(jnp.nan), cv.at[:, 0].set(jnp.nan)   # never read

    out = jax.jit(decode_attention)(q, ck, cv, jnp.int32(2), bound)
    want = _attend(q, ck[2], cv[2], (bound - 1)[:, None])

    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    assert not out[0].any()
    np.testing.assert_allclose(out[1:], want[1:], rtol=3e-2, atol=3e-2)
