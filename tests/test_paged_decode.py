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
from repro.core import containers
from repro.kernels import ops, ref
from repro.kernels import packed_flash_decode as pfd


def _pool(key, n_phys, bl, D, container, dtype):
    """Random packed physical blocks: payload (n_phys, bl, payload
    columns), fixed lanes or dense bit planes as the container packs."""
    ks = jax.random.split(key, 2)
    f = codecs.fields_for(container, dtype)
    pack = ref.bitplane_pack_nd if f.dense else ref.sfp_pack_nd
    parts = []
    for k in ks:
        x = jax.random.normal(k, (n_phys * bl, D), jnp.float32).astype(dtype)
        p, b = pack(x, f)
        parts.append((p.reshape(n_phys, bl, -1),
                      b.reshape(n_phys, bl, D // 128)))
    (kp, kb), (vp, vb) = parts
    return (kp, kb, vp, vb), f


@pytest.mark.parametrize("draft", [False, True],
                         ids=["full", "prefix_planes"])
@pytest.mark.parametrize("container,dtype", [("sfp8", jnp.bfloat16),
                                             ("sfp-m2e4", jnp.bfloat16),
                                             ("sfp16", jnp.float32)])
@pytest.mark.parametrize("rep", [1, 4])  # GQA ratio H / KH
def test_paged_kernel_bit_exact_vs_oracle(container, dtype, rep, draft):
    """The kernel reads a pool stacked over layers at the ``layer`` it is
    given: at each layer it must equal, bit for bit, the oracle on that
    layer's slice alone."""
    B, KH, hd, bl, nb, n_phys, n_layers = 4, 2, 64, 16, 3, 8, 3
    H = KH * rep
    layers = [_pool(jax.random.PRNGKey(11 + i), n_phys, bl, KH * hd,
                    container, dtype) for i in range(n_layers)]
    f = layers[0][1]
    stacked = [jnp.stack(part) for part in zip(*(p for p, _ in layers))]
    q = jax.random.normal(jax.random.PRNGKey(1), (B, 1, H, hd),
                          jnp.float32).astype(dtype)
    # Rows at pos 0, block_l - 1, block_l and L - 1; the unallocated
    # logical blocks point at the trash block (0) — masked by position.
    tables = jnp.array([[1, 0, 0], [7, 0, 0], [5, 3, 0], [2, 4, 6]],
                       jnp.int32)
    pos = jnp.array([0, bl - 1, bl, nb * bl - 1], jnp.int32)
    planes = (max(f.payload_bits - 1, f.dexp_bits + 2) if draft else None)
    oracle = jax.jit(functools.partial(ref.paged_flash_decode, fields=f,
                                       softcap=30.0, prefix_planes=planes))
    for layer, (packed, _) in enumerate(layers):
        got = pfd.paged_flash_decode(q, *stacked, tables, pos,
                                     jnp.int32(layer), fields=f,
                                     softcap=30.0, interpret=True,
                                     prefix_planes=planes)
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
    got = pfd.paged_flash_decode(q, kp[None], kb[None], vp[None], vb[None],
                                 ident, pos, 0, fields=f, interpret=True)
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
    """ops.paged_flash_decode: ref oracle and interpret kernel agree, on
    the second layer of a pool stacked over two."""
    B, KH, hd, bl, n_phys = 2, 2, 64, 16, 6
    dtype = jnp.float32
    (kp, kb, vp, vb), f = _pool(jax.random.PRNGKey(7), n_phys, bl, KH * hd,
                                "sfp8", dtype)
    kp, kb, vp, vb = (jnp.stack([a[::-1], a]) for a in (kp, kb, vp, vb))
    q = jax.random.normal(jax.random.PRNGKey(8), (B, 1, KH, hd), dtype)
    tables = jnp.array([[2, 5], [4, 0]], jnp.int32)
    pos = jnp.array([25, 3], jnp.int32)
    outs = {}
    for backend in ("ref", "interpret"):
        ops.force_backend(backend)
        try:
            outs[backend] = np.asarray(ops.paged_flash_decode(
                q, ops.Packed(payload=kp, bases=kb),
                ops.Packed(payload=vp, bases=vb), tables, pos, 1, fields=f),
                np.float32)
        finally:
            ops.force_backend(None)
    # The ref dispatch runs the oracle op by op, the kernel as one jitted
    # program: XLA fuses and orders the f32 softmax/accumulation math
    # differently, so results agree to f32 rounding, not bit for bit
    # (the jitted oracle above is still bit-exact).
    np.testing.assert_allclose(outs["ref"], outs["interpret"],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("draft", [False, True],
                         ids=["full", "prefix_planes"])
@pytest.mark.parametrize("container,dtype", [("sfp8", jnp.bfloat16),
                                             ("sfp-m2e4", jnp.bfloat16),
                                             ("sfp16", jnp.float32)])
@pytest.mark.parametrize("dead", ["trash", "poison"])
def test_trailing_trash_blocks_are_exact_noops(dead, container, dtype,
                                               draft):
    """Logical blocks past a row's position are skipped: neither fetched
    nor expanded. With every dead table entry (and two extra trailing
    columns) pointing at the trash block ("trash"), or at a block whose
    payload and bases decode to Inf ("poison": Inf K/V would turn the
    masked recurrence's p == 0 terms into NaN), the output must equal, bit
    for bit, the short table's with its dead entries on the clean trash
    block. Rows sit at pos 0, block_l - 1, block_l and L - 1 in one
    batch; ``draft`` runs the speculative draft read mode."""
    KH, hd, bl, nb = 2, 64, 16, 3
    (kp, kb, vp, vb), f = _pool(jax.random.PRNGKey(9), 9, bl, KH * hd,
                                container, dtype)
    poison = 8  # bases 255 with dexp == 0 decode every value to +Inf
    kp, vp = kp.at[poison].set(0), vp.at[poison].set(0)
    kb, vb = kb.at[poison].set(255), vb.at[poison].set(255)
    spec = containers.spec_for(jnp.dtype(dtype))
    inf = ref.unpack_tile(kp[poison], kb[poison], f, spec, rows=bl, KH=KH,
                          hd=hd)
    assert not np.isfinite(np.asarray(inf)).any()

    pos = jnp.array([0, bl - 1, bl, nb * bl - 1], jnp.int32)
    live = [[1], [2], [3, 4], [5, 6, 7]]  # blocks holding slots <= pos
    q = jax.random.normal(jax.random.PRNGKey(10), (len(live), 1, 4 * KH,
                                                    hd)).astype(dtype)

    def table(width, fill):
        return jnp.array([row + [fill] * (width - len(row)) for row in live],
                         jnp.int32)

    planes = (max(f.payload_bits - 1, f.dexp_bits + 2) if draft else None)
    run = functools.partial(pfd.paged_flash_decode, fields=f, softcap=30.0,
                            interpret=True, prefix_planes=planes)
    pool = (kp[None], kb[None], vp[None], vb[None])
    want = run(q, *pool, table(nb, 0), pos, 0)
    got = run(q, *pool, table(nb + 2, poison if dead == "poison" else 0),
              pos, 0)
    assert np.isfinite(np.asarray(want, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
