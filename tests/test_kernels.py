"""Per-kernel allclose vs the pure-jnp oracles, sweeping shapes and dtypes
(interpret mode executes the kernel bodies on CPU).

Container names resolve to payload geometries through the codec registry
(repro.codecs.fields_for); the kernels themselves are format-agnostic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs
from repro.core import containers as C
from repro.kernels import flash_attention as fa
from repro.kernels import gecko_pack as gp
from repro.kernels import mantissa_quant as mq
from repro.kernels import ops, ref
from repro.kernels import sfp_pack as sp


def _fields(container, dtype):
    return codecs.fields_for(container, dtype)


@pytest.mark.parametrize("shape", [(128,), (3, 100), (5, 7, 64), (2, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [0, 1, 4, 7])
def test_mantissa_quant_kernel_matches_oracle(shape, dtype, n):
    x = (jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32) * 10
         ).astype(dtype)
    got = mq.mantissa_quantize(x, jnp.int32(n), interpret=True, block_rows=8)
    want = ref.mantissa_truncate(x, n)
    np.testing.assert_array_equal(
        np.asarray(C.bitcast_to_int(got)), np.asarray(C.bitcast_to_int(want)))


@pytest.mark.parametrize("rows", [1, 3, 64, 130])
@pytest.mark.parametrize("container,dtype", [("sfp8", jnp.bfloat16),
                                             ("sfp16", jnp.bfloat16),
                                             ("sfp16", jnp.float32)])
def test_sfp_pack_kernel_matches_oracle(rows, container, dtype):
    f = _fields(container, dtype)
    x = (jax.random.normal(jax.random.PRNGKey(1), (rows, 128), jnp.float32)
         * 5).astype(dtype)
    pk, bk = sp.sfp_pack(x, fields=f, interpret=True, block_rows=16)
    pr, br = ref.sfp_pack(x, f)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))
    uk = sp.sfp_unpack(pk, bk, shape=x.shape, dtype=dtype,
                       fields=f, interpret=True, block_rows=16)
    ur = ref.sfp_unpack(pr, br, x.shape, dtype, f)
    np.testing.assert_array_equal(np.asarray(C.bitcast_to_int(uk)),
                                  np.asarray(C.bitcast_to_int(ur)))


@pytest.mark.parametrize("n", [0, 2, 5])
@pytest.mark.parametrize("container,dtype", [("sfp8", jnp.bfloat16),
                                             ("sfp16", jnp.float32)])
def test_fused_quantize_pack_matches_two_kernel_sequence(n, container, dtype):
    """The fused kernel must be bit-exact against mantissa_quantize
    followed by sfp_pack — same payload, same bases."""
    f = _fields(container, dtype)
    x = (jax.random.normal(jax.random.PRNGKey(7), (64, 128), jnp.float32)
         * 3).astype(dtype)
    pk, bk = sp.sfp_quantize_pack(x, jnp.int32(n), fields=f, interpret=True,
                                  block_rows=16)
    q = mq.mantissa_quantize(x, jnp.int32(n), interpret=True, block_rows=16)
    pr, br = sp.sfp_pack(q, fields=f, interpret=True, block_rows=16)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))
    # ...and against the fused jnp oracle.
    po, bo = ref.sfp_pack(x, f, n=jnp.int32(n))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(po))
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(bo))


@pytest.mark.parametrize("container,man_keep", [("sfp8", 3), ("sfp16", 7)])
def test_sfp_roundtrip_exact_when_within_budget(container, man_keep):
    """Values pre-truncated to the container's mantissa budget and within
    the delta-exponent range round-trip bit-exactly."""
    f = _fields(container, jnp.bfloat16)
    x = (jax.random.normal(jax.random.PRNGKey(2), (4, 256), jnp.float32)
         ).astype(jnp.bfloat16)
    x = C.truncate_mantissa(x, man_keep)
    p, b, = ref.sfp_pack_nd(x, f)
    back = ref.sfp_unpack_nd(p, b, jnp.bfloat16, f)
    np.testing.assert_array_equal(np.asarray(x).view(np.uint16),
                                  np.asarray(back).view(np.uint16))


def test_sfp8_bounded_error_out_of_budget():
    f = _fields("sfp8", jnp.bfloat16)
    x = (jax.random.normal(jax.random.PRNGKey(3), (8, 512), jnp.float32)
         ).astype(jnp.bfloat16)
    back = ops.sfp_decompress_nd(ops.sfp_compress_nd(x, f), jnp.bfloat16, f)
    err = np.abs(np.asarray(back, np.float32) - np.asarray(x, np.float32))
    group_max = np.abs(np.asarray(x, np.float32)).reshape(8, 4, 128).max(-1)
    rel = err.reshape(8, 4, 128) / group_max[..., None]
    assert rel.max() < 0.13  # 3 mantissa bits -> <= 2^-3 rel; + flush margin


def test_sfp_nd_matches_flat():
    f = _fields("sfp8", jnp.bfloat16)
    x = (jax.random.normal(jax.random.PRNGKey(4), (2, 3, 256), jnp.float32)
         ).astype(jnp.bfloat16)
    pn, bn = ref.sfp_pack_nd(x, f)
    pf, bf = ref.sfp_pack(x, f)
    np.testing.assert_array_equal(np.asarray(pn).reshape(-1, 128),
                                  np.asarray(pf))
    np.testing.assert_array_equal(np.asarray(bn).reshape(-1, 1),
                                  np.asarray(bf))


def test_sfp_preserves_exact_zeros():
    f = _fields("sfp8", jnp.bfloat16)
    x = jnp.zeros((1, 128), jnp.bfloat16).at[0, 3].set(1.5)
    back = ref.sfp_unpack_nd(*ref.sfp_pack_nd(x, f), jnp.bfloat16, f)
    assert float(back[0, 0]) == 0.0 and float(back[0, 3]) == 1.5


@pytest.mark.parametrize("n_groups", [1, 5, 128, 260])
def test_gecko_pack_kernel_matches_oracle(n_groups):
    rng = np.random.RandomState(0)
    e = jnp.asarray(np.clip(rng.normal(127, 4, (n_groups, 64)).round(),
                            0, 255).astype(np.uint8))
    bk, wk, pk = gp.gecko_pack(e, interpret=True, block_groups=64)
    br, wr, pr = ref.gecko_plane_encode(e)
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    dk = gp.gecko_unpack(bk, pk, interpret=True, block_groups=64)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(e))


def test_gecko_kernel_extreme_exponents():
    """Full-range deltas (|d| up to 255 -> width 8) survive the kernels."""
    e = jnp.asarray(np.array([[0, 255] * 32, [255] + [0] * 63],
                             np.uint8))
    bk, wk, pk = gp.gecko_pack(e, interpret=True)
    dk = gp.gecko_unpack(bk, pk, interpret=True)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(e))
    assert int(np.max(np.asarray(wk))) == 8


@pytest.mark.parametrize("S,window,softcap", [
    (256, None, None), (256, 64, None), (256, None, 50.0), (192, 50, 30.0)])
def test_flash_attention_matches_oracle(S, window, softcap):
    B, H, D = 2, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    got = fa.flash_attention(q, k, v, causal=True, window=window,
                             softcap=softcap, block_q=64, block_k=64,
                             interpret=True)
    want = ref.attention(q, k, v, causal=True, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("H,KH,window,softcap", [
    (8, 2, None, None), (4, 1, 64, None), (6, 3, 40, 20.0)])
def test_flash_attention_gqa_folded_matches_oracle(H, KH, window, softcap):
    """ops.attention folds the q-head group into the query rows (q_rep)
    instead of repeating K/V to H heads; causal/window masks must follow
    the logical position row // q_rep."""
    B, S, D = 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)
    ops.force_backend("interpret")
    try:
        got = ops.attention(q, k, v, causal=True, window=window,
                            softcap=softcap)
    finally:
        ops.force_backend(None)
    want = ref.attention(q, k, v, causal=True, window=window,
                         softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_grad_matches_oracle():
    """The kernel path of ops.attention is differentiable (train
    attention at short S uses it): its gradient is the reference's."""
    B, S, H, KH, D = 1, 64, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)
    w = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(ops.attention(q, k, v, causal=True, window=32) * w)

    grads = {}
    for backend in ("interpret", "ref"):
        ops.force_backend(backend)
        try:
            grads[backend] = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        finally:
            ops.force_backend(None)
    for g, r in zip(grads["interpret"], grads["ref"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-5)


def test_flash_attention_bf16():
    B, S, H, D = 1, 128, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32
                                 ).astype(jnp.bfloat16) for kk in ks)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


def test_ops_dispatch_ref_backend():
    ops.force_backend("ref")
    try:
        x = jnp.ones((4, 128), jnp.bfloat16) * 1.5
        q = ops.mantissa_quantize(x, 2)
        assert q.dtype == jnp.bfloat16
    finally:
        ops.force_backend(None)
