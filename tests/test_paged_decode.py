"""Paged flash-decode: the block-table-gathering kernel must be bit-exact
(interpret mode) against the gather-unpack-attend oracle, agree with the
contiguous kernel on the same logical cache, and the per-row-position
extension of the contiguous kernel must match per-row scalar calls."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs
from repro.kernels import ops, ref
from repro.kernels import packed_flash_decode as pfd


def _pool(key, n_phys, bl, D, container, dtype):
    """Random packed physical blocks (n_phys, bl, D)."""
    ks = jax.random.split(key, 2)
    f = codecs.fields_for(container, dtype)
    parts = []
    for k in ks:
        x = jax.random.normal(k, (n_phys * bl, D), jnp.float32).astype(dtype)
        p, b = ref.sfp_pack_nd(x, f)
        parts.append((p.reshape(n_phys, bl, D),
                      b.reshape(n_phys, bl, D // 128)))
    (kp, kb), (vp, vb) = parts
    return (kp, kb, vp, vb), f


@pytest.mark.parametrize("container,dtype", [("sfp8", jnp.bfloat16),
                                             ("sfp16", jnp.float32)])
@pytest.mark.parametrize("rep", [1, 4])  # GQA ratio H / KH
def test_paged_kernel_bit_exact_vs_oracle(container, dtype, rep):
    B, KH, hd, bl, nb, n_phys = 3, 2, 64, 16, 3, 8
    H = KH * rep
    packed, f = _pool(jax.random.PRNGKey(0), n_phys, bl, KH * hd,
                      container, dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, 1, H, hd),
                          jnp.float32).astype(dtype)
    # Rows at different fill levels; row 1 has unallocated logical blocks
    # pointing at the trash block (0) — masked by position.
    tables = jnp.array([[1, 4, 2], [7, 0, 0], [5, 3, 6]], jnp.int32)
    pos = jnp.array([40, 9, 33], jnp.int32)
    got = pfd.paged_flash_decode(q, *packed, tables, pos, fields=f,
                                 softcap=30.0, interpret=True)
    oracle = jax.jit(functools.partial(ref.paged_flash_decode, fields=f,
                                       softcap=30.0))
    want = oracle(q, *packed, tables, pos)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_paged_matches_contiguous_on_same_logical_cache():
    """A block table that happens to be the identity permutation must
    reproduce the contiguous kernel bit-for-bit: paged decode is the same
    recurrence over the same logical slots."""
    B, KH, rep, hd, bl, nb = 2, 2, 2, 64, 16, 4
    H, D = KH * rep, 2 * 64
    dtype = jnp.float32
    (kp, kb, vp, vb), f = _pool(jax.random.PRNGKey(2), nb, bl, D,
                                "sfp16", dtype)
    q = jax.random.normal(jax.random.PRNGKey(3), (B, 1, H, hd), dtype)
    pos = jnp.array([bl * nb - 1, 17], jnp.int32)
    ident = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32), (B, nb))
    got = pfd.paged_flash_decode(q, kp, kb, vp, vb, ident, pos, fields=f,
                                 interpret=True)
    want = pfd.packed_flash_decode(
        q, jnp.broadcast_to(kp.reshape(1, nb * bl, D), (B, nb * bl, D)),
        jnp.broadcast_to(kb.reshape(1, nb * bl, D // 128),
                         (B, nb * bl, D // 128)),
        jnp.broadcast_to(vp.reshape(1, nb * bl, D), (B, nb * bl, D)),
        jnp.broadcast_to(vb.reshape(1, nb * bl, D // 128),
                         (B, nb * bl, D // 128)),
        pos, fields=f, block_l=bl, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("window", [None, 16])
def test_contiguous_kernel_vector_pos_matches_per_row(window):
    """(B,) per-row positions (continuous-batching slots) must equal B
    separate scalar-pos calls — rows are independent grid lanes."""
    B, KH, rep, hd, L = 3, 2, 2, 64, 16
    H, D = KH * rep, 2 * 64
    dtype = jnp.float32
    f = codecs.fields_for("sfp16", dtype)
    k = jax.random.normal(jax.random.PRNGKey(4), (B, L, D), dtype)
    v = jax.random.normal(jax.random.PRNGKey(5), (B, L, D), dtype)
    kp, kb = ref.sfp_pack_nd(k, f)
    vp, vb = ref.sfp_pack_nd(v, f)
    q = jax.random.normal(jax.random.PRNGKey(6), (B, 1, H, hd), dtype)
    pos = jnp.array([5, 21, 15], jnp.int32)  # 21: wrapped when window=16
    got = pfd.packed_flash_decode(q, kp, kb, vp, vb, pos, fields=f,
                                  window=window, block_l=16, interpret=True)
    for b in range(B):
        one = pfd.packed_flash_decode(
            q[b:b + 1], kp[b:b + 1], kb[b:b + 1], vp[b:b + 1], vb[b:b + 1],
            jnp.asarray(int(pos[b]), jnp.int32), fields=f, window=window,
            block_l=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[b:b + 1], np.float32),
                                      np.asarray(one, np.float32))


def test_ops_paged_dispatch_ref_vs_interpret():
    """ops.paged_flash_decode: ref oracle and interpret kernel agree."""
    B, KH, hd, bl, n_phys = 2, 2, 64, 16, 6
    dtype = jnp.float32
    (kp, kb, vp, vb), f = _pool(jax.random.PRNGKey(7), n_phys, bl, KH * hd,
                                "sfp8", dtype)
    q = jax.random.normal(jax.random.PRNGKey(8), (B, 1, KH, hd), dtype)
    tables = jnp.array([[2, 5], [4, 0]], jnp.int32)
    pos = jnp.array([25, 3], jnp.int32)
    outs = {}
    for backend in ("ref", "interpret"):
        ops.force_backend(backend)
        try:
            outs[backend] = np.asarray(ops.paged_flash_decode(
                q, ops.Packed(payload=kp, bases=kb),
                ops.Packed(payload=vp, bases=vb), tables, pos, fields=f),
                np.float32)
        finally:
            ops.force_backend(None)
    # The ref dispatch runs the oracle op by op, the kernel as one jitted
    # program: XLA fuses and orders the f32 softmax/accumulation math
    # differently, so results agree to f32 rounding, not bit for bit
    # (the jitted oracle above is still bit-exact).
    np.testing.assert_allclose(outs["ref"], outs["interpret"],
                               rtol=1e-5, atol=1e-6)


def test_trailing_trash_blocks_are_exact_noops():
    """Extra logical blocks pointing at the trash block past a row's
    position must not change the output by a single bit (the masked-block
    recurrence contributes exactly zero)."""
    B, KH, hd, bl = 1, 2, 64, 16
    dtype = jnp.float32
    (kp, kb, vp, vb), f = _pool(jax.random.PRNGKey(9), 5, bl, KH * hd,
                                "sfp16", dtype)
    q = jax.random.normal(jax.random.PRNGKey(10), (B, 1, KH, hd), dtype)
    pos = jnp.array([bl - 2], jnp.int32)
    short = jnp.array([[3]], jnp.int32)
    long = jnp.array([[3, 0, 0, 0]], jnp.int32)
    a = pfd.paged_flash_decode(q, kp, kb, vp, vb, short, pos, fields=f,
                               interpret=True)
    b = pfd.paged_flash_decode(q, kp, kb, vp, vb, long, pos, fields=f,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
