"""Dense bit-plane containers: variable payload-width packing end to end.

Covers the sub-byte container stack: plane layout vs a pure-Python
oracle, codec registry resolution, backend parity, fused quantize+pack,
packed/paged flash-decode bit-exactness at sub-byte geometries, realized
footprint accounting, per-layer stash containers, pool byte accounting,
and the afloat policy plugin.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs, configs, policies
from repro.configs.base import reduced
from repro.core import containers as C, footprint
from repro.kernels import bitplane_pack as bpk
from repro.kernels import ops, ref
from repro.kernels import packed_flash_decode as pfd
from repro.models.model import DecoderModel
from repro.serve import kvcache, pool


def _x(shape=(4, 256), dtype=jnp.bfloat16, seed=0, scale=3.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Pure-Python bit-plane oracle (independent of kernels/ref.py)
# ---------------------------------------------------------------------------


def py_plane_pack(words: np.ndarray, payload_bits: int) -> np.ndarray:
    """(R, 128) payload words -> (R, P*16) uint8, bit-by-bit in Python."""
    R = words.shape[0]
    out = np.zeros((R, payload_bits * 16), np.uint8)
    for r in range(R):
        for lane in range(128):
            w = int(words[r, lane])
            for p in range(payload_bits):
                if (w >> p) & 1:
                    out[r, p * 16 + lane // 8] |= 1 << (lane % 8)
    return out


def py_plane_unpack(planes: np.ndarray, payload_bits: int) -> np.ndarray:
    R = planes.shape[0]
    out = np.zeros((R, 128), np.int64)
    for r in range(R):
        for p in range(payload_bits):
            for i in range(16):
                byte = int(planes[r, p * 16 + i])
                for j in range(8):
                    if (byte >> j) & 1:
                        out[r, i * 8 + j] |= 1 << p
    return out


@pytest.mark.parametrize("payload_bits", [3, 7, 11, 16])
def test_plane_layout_matches_python_oracle(payload_bits):
    rng = np.random.RandomState(payload_bits)
    words = rng.randint(0, 1 << payload_bits, size=(3, 128)).astype(np.int64)
    got = np.asarray(ref.plane_pack_words(jnp.asarray(words, jnp.int32),
                                          payload_bits))
    want = py_plane_pack(words, payload_bits)
    np.testing.assert_array_equal(got, want)
    back = np.asarray(ref.plane_unpack_words(jnp.asarray(got), payload_bits))
    np.testing.assert_array_equal(back, words)
    np.testing.assert_array_equal(py_plane_unpack(want, payload_bits), words)


# ---------------------------------------------------------------------------
# Dense codec: registry, geometry, roundtrip, backend parity
# ---------------------------------------------------------------------------


def test_dense_name_resolution_and_geometry():
    f = codecs.get("sfp-m2e4").pack_fields(jnp.bfloat16)
    assert (f.man_keep, f.dexp_bits, f.payload_bits, f.dense) == (2, 4, 7,
                                                                  True)
    # lane-width budgets keep the fixed-lane fast path
    assert not codecs.get("sfp-m3e4").pack_fields(jnp.bfloat16).dense
    assert not codecs.get("sfp-m10e5").pack_fields(jnp.float32).dense
    # mantissa clamps to the source dtype (bf16 has 7)
    f2 = codecs.get("sfp-m9e3").pack_fields(jnp.bfloat16)
    assert f2.man_keep == 7 and f2.payload_bits == 11
    # payload caps at 16 bits total
    f3 = codecs.get("sfp-m12e7").pack_fields(jnp.float32)
    assert f3.payload_bits <= 16
    assert codecs.dense_name(1.2, 3.5) == "sfp-m2e4"


def test_dense_roundtrip_equals_same_geometry_fixed_lane():
    """The plane layout changes bytes, not values: a dense m2e4 roundtrip
    must be bit-identical to an 8-bit-lane container with the same
    (man, dexp) geometry."""
    x = _x((4, 256))
    dense = codecs.get("sfp-m2e4").roundtrip(x)
    f_fixed = ref.PackFields(man_keep=2, dexp_bits=4, payload_bits=8)
    pw, bw = ref.sfp_pack_nd(x, f_fixed)
    fixed = ref.sfp_unpack_nd(pw, bw, x.dtype, f_fixed)
    np.testing.assert_array_equal(np.asarray(dense, np.float32),
                                  np.asarray(fixed, np.float32))


def test_dense_backend_parity_and_fused_pack():
    x = _x((2, 3, 128), dtype=jnp.float32)
    codec = codecs.get("sfp-m4e5")  # 10-bit dense payload
    ref_pack = codec.pack(x, bits=3)
    ops.force_backend("interpret")
    try:
        interp_pack = codec.pack(x, bits=3)
        for k in ref_pack.data:
            np.testing.assert_array_equal(np.asarray(ref_pack.data[k]),
                                          np.asarray(interp_pack.data[k]))
        y = codec.unpack(interp_pack)
    finally:
        ops.force_backend(None)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(codec.unpack(ref_pack)))
    # fused quantize+pack == quantize then pack
    plain = codec.pack(C.truncate_mantissa(x, 3))
    for k in plain.data:
        np.testing.assert_array_equal(np.asarray(ref_pack.data[k]),
                                      np.asarray(plain.data[k]))


def test_dense_flat_layout_and_pallas_kernels():
    x = _x((37,), dtype=jnp.bfloat16)  # forces the padded flat layout
    codec = codecs.get("sfp-m2e4")
    packed = codec.pack(x)
    assert packed.data["payload"].shape == (1, 7 * 16)
    np.testing.assert_array_equal(
        np.asarray(codec.unpack(packed)),
        np.asarray(codec.roundtrip(x)))
    # kernel pair vs oracle on the flat rows
    f = codec.pack_fields(x.dtype)
    rows = _x((5, 128))
    kp, kb = bpk.bitplane_pack(rows, fields=f, interpret=True)
    rp, rb = ref.bitplane_pack(rows, f)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
    np.testing.assert_array_equal(np.asarray(kb), np.asarray(rb))
    back = bpk.bitplane_unpack(kp, kb, shape=(5, 128), dtype=rows.dtype,
                               fields=f, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(back, np.float32),
        np.asarray(ref.bitplane_unpack(rp, rb, (5, 128), rows.dtype, f),
                   np.float32))


def test_dense_packed_bits_below_fixed_lane():
    """The realized-footprint claim: dense m2e4 really stores fewer bytes
    than fixed-lane sfp8 (7.06 vs 8.06 bits/value), m1e2 lands at 4.06."""
    x = _x((64, 8192))
    m2e4 = codecs.get("sfp-m2e4").packed_bits(x) / x.size
    sfp8 = codecs.get("sfp8").packed_bits(x) / x.size
    assert m2e4 == 7.0625 and sfp8 == 8.0625
    assert m2e4 < sfp8
    assert codecs.get("sfp-m1e2").packed_bits(x) / x.size == 4.0625
    # encode_host writes exactly those bytes
    stream, _meta = codecs.get("sfp-m2e4").encode_host(np.asarray(x))
    assert stream.nbytes * 8 == int(codecs.get("sfp-m2e4").packed_bits(x))


def test_footprint_realized_report():
    x = _x((4, 256))
    for name in ("sfp-m2e4", "sfp8", "sfp16"):
        rep = footprint.container_realized_report(x, name)
        assert rep.total_bits == int(codecs.get(name).packed_bits(x)), name
    dense = footprint.container_realized_report(x, "sfp-m2e4")
    # dense payload wastes nothing on lane slack: metadata is bases only
    assert dense.metadata_bits == (x.size // 128) * 8
    assert dense.vs_bf16() < footprint.container_realized_report(
        x, "sfp8").vs_bf16()


# ---------------------------------------------------------------------------
# Flash decode over dense sub-byte caches
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("container", ["sfp-m2e4", "sfp-m1e2"])
@pytest.mark.parametrize("window,pos,L", [(None, 31, 32), (16, 37, 16)])
def test_dense_packed_decode_bit_exact(container, window, pos, L):
    B, KH, hd, rep = 2, 2, 64, 2
    H = KH * rep
    dtype = jnp.bfloat16
    f = codecs.fields_for(container, dtype)
    assert f.dense
    k = _x((B, L, KH * hd), dtype, seed=1)
    v = _x((B, L, KH * hd), dtype, seed=2)
    kp, kb = ref.bitplane_pack_nd(k, f)
    vp, vb = ref.bitplane_pack_nd(v, f)
    q = _x((B, 1, H, hd), dtype, seed=3)
    posa = jnp.asarray(pos, jnp.int32)
    got = pfd.packed_flash_decode(q, kp, kb, vp, vb, posa, fields=f,
                                  window=window, block_l=16, interpret=True)
    oracle = jax.jit(functools.partial(ref.packed_flash_decode, fields=f,
                                       window=window, block_l=16))
    want = oracle(q, kp, kb, vp, vb, posa)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_dense_paged_decode_bit_exact_sub_byte():
    """Paged flash-decode over a dense sub-byte pool must be bit-exact vs
    the gather-unpack-attend oracle (interpret mode), including per-row
    positions and a trash-backed row."""
    B, KH, hd, rep, bl, nb = 2, 1, 128, 2, 16, 2
    H = KH * rep
    dtype = jnp.float32
    f = codecs.fields_for("sfp-m2e4", dtype)
    assert f.dense and f.payload_bits == 7
    D = KH * hd
    k = _x((nb * B, bl, D), dtype, seed=4)
    v = _x((nb * B, bl, D), dtype, seed=5)
    kp, kb = ref.bitplane_pack_nd(k, f)
    vp, vb = ref.bitplane_pack_nd(v, f)
    # physical pool with block 0 as trash
    zeros = lambda a: jnp.zeros((1,) + a.shape[1:], a.dtype)
    kp_p = jnp.concatenate([zeros(kp), kp]); kb_p = jnp.concatenate([zeros(kb), kb])
    vp_p = jnp.concatenate([zeros(vp), vp]); vb_p = jnp.concatenate([zeros(vb), vb])
    tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)  # row 1: trash tail
    posv = jnp.asarray([2 * bl - 1, bl - 6], jnp.int32)
    q = _x((B, 1, H, hd), dtype, seed=6)
    got = pfd.paged_flash_decode(q, kp_p[None], kb_p[None], vp_p[None],
                                 vb_p[None], tables, posv, 0, fields=f,
                                 interpret=True)
    oracle = jax.jit(functools.partial(ref.paged_flash_decode, fields=f))
    want = oracle(q, kp_p, kb_p, vp_p, vb_p, tables, posv)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_kvcache_dense_fused_matches_unpack_fallback():
    from repro.models import common, attention
    cfg = dataclasses.replace(reduced(configs.get("mistral-large-123b")),
                              dtype="float32")
    pf = common.ParamFactory(common.MODE_PARAMS, jax.random.PRNGKey(0),
                             jnp.float32)
    params = attention.attn_init(pf, cfg)
    B, L = 2, 12
    h_tok = 0.3 * _x((B, 1, cfg.d_model), jnp.float32, seed=7)
    outs, caches = {}, {}
    for backend in ("ref", "interpret"):
        ops.force_backend(backend)
        try:
            cache = kvcache.packed_cache_init(cfg, "global", B, 256,
                                              "sfp-m2e4")
            o, c = kvcache.attention_decode_packed(
                params, h_tok, cache, jnp.asarray(L, jnp.int32), cfg,
                kind="global", container="sfp-m2e4")
            outs[backend], caches[backend] = o, c
        finally:
            ops.force_backend(None)
    np.testing.assert_allclose(np.asarray(outs["ref"]),
                               np.asarray(outs["interpret"]),
                               rtol=1e-5, atol=1e-5)
    for part in ("payload", "bases"):
        np.testing.assert_array_equal(
            np.asarray(caches["ref"].k.data[part]),
            np.asarray(caches["interpret"].k.data[part]))


# ---------------------------------------------------------------------------
# Pool byte accounting + per-layer stash + afloat plugin
# ---------------------------------------------------------------------------


def test_pool_dense_byte_accounting():
    cfg = dataclasses.replace(reduced(configs.get("mistral-large-123b")),
                              dtype="bfloat16")
    bb_dense = kvcache.paged_block_bytes(cfg, 128, "sfp-m2e4")
    bb_fixed = kvcache.paged_block_bytes(cfg, 128, "sfp8")
    D = cfg.n_kv_heads * cfg.head_dim_
    assert bb_dense == 2 * 128 * ((D // 128) * 7 * 16 + D // 128)
    assert bb_dense < bb_fixed
    p = pool.BlockPool(4, 2, 2, 128, block_bytes=bb_dense)
    assert p.stats().capacity_bytes == 4 * bb_dense
    assert p.bytes_for(129) == 2 * bb_dense
    assert p.alloc_upto(0, 200)
    st = p.stats()
    assert st.used_bytes == 2 * bb_dense and st.peak_bytes == 2 * bb_dense
    p.free_slot(0)
    assert p.stats().free_bytes == 4 * bb_dense
    # the device pool really allocates the dense payload shape
    spec = kvcache.paged_block_spec(cfg, 2, 128, "sfp-m2e4")
    assert spec.k_payload.shape[-1] == (D // 128) * 7 * 16
    assert spec.k_payload.dtype == jnp.uint8


def test_per_layer_stash_plan_and_forward():
    cfg = reduced(configs.get("gemma2-2b"), n_layers=4, d_model=128)
    pol = policies.get("qm+qe", container="sfp-m2e4")
    base_model = DecoderModel(cfg, pol)
    st = pol.init_state(base_model.dims)
    st = st._replace(learn={
        **st.learn,
        "qm": {**st.learn["qm"], "act": jnp.asarray([2.0, 5.0])},
        "qe": {**st.learn["qe"], "act": jnp.asarray([4.0, 6.0])}})
    plan = base_model.stash_plan(st)
    assert plan == ("sfp-m2e4", "sfp-m5e6")  # per-layer, not network-wide
    model = DecoderModel(cfg, pol, stash_containers=plan)
    params = model.init(jax.random.PRNGKey(0))
    run = model.run_state(jax.random.PRNGKey(1), st)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, run)[0])(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in jax.tree.leaves(grads))
    # wrong plan length fails fast
    with pytest.raises(ValueError, match="one codec per period"):
        DecoderModel(cfg, pol, stash_containers=("sfp8",))


def test_layer_decisions_composition():
    dims = policies.ScopeDims.for_dtype(jnp.bfloat16, n_periods=3, n_rem=1)
    pol = policies.get("qm+qe")
    st = pol.init_state(dims)
    ds = pol.layer_decisions(st, dims)
    assert len(ds) == 3 and ds[0] == (7.0, 8.0)  # full width at init
    # network-wide controllers repeat their summary
    bw = policies.get("bitwave")
    assert len(bw.layer_decisions(bw.init_state(dims), dims)) == 3


def test_afloat_policy_learns_bias():
    dims = policies.ScopeDims.for_dtype(jnp.float32, n_periods=2, n_rem=0)
    pol = policies.get("afloat", container="sfp-m3e4")
    st = pol.init_state(dims)
    assert set(st.learn) >= {"act", "w", "act_b", "w_b"}
    view = pol.forward_view(st.learn, pol.control_view(st.ctrl, dims), dims)
    sl = jax.tree.map(lambda a: a[0], pol.scan_slices(view, dims))
    key = jax.random.PRNGKey(0)
    # a tensor far above the e4 window: positive bias recovers range, so
    # the finite-difference bias gradient must push the bias up (negative
    # grad under gradient descent).
    w = jnp.full((4, 128), 1e4, jnp.float32)

    def loss(learn):
        v = pol.forward_view(learn, pol.control_view(st.ctrl, dims), dims)
        s = jax.tree.map(lambda a: a[0], pol.scan_slices(v, dims))
        wq = pol.quantize_weight(w, s, key, dims)
        return jnp.sum((wq - w) ** 2)

    # drive e low so the window clips: bias grads become informative
    learn = dict(st.learn, w=jnp.full((2,), 4.0, jnp.float32))
    g = jax.grad(loss)(learn)
    assert float(g["w_b"][0]) < 0  # descent increases the bias
    new = pol.update_learn(learn, g, dims)
    assert float(new["w_b"][0]) > float(learn["w_b"][0])
    # penalty ignores bias keys but still prices bitlengths
    lam = {k: jnp.ones_like(v) for k, v in st.learn.items()
           if not k.endswith("_b")}
    pen = pol.penalty(learn, lam, jnp.asarray(0), dims)
    assert np.isfinite(float(pen))


def test_afloat_trains_end_to_end():
    from repro.optim import adamw
    from repro.optim.schedule import Schedule
    from repro.train import step as step_mod
    cfg = reduced(configs.get("gemma2-2b"), n_layers=2, d_model=128)
    model = DecoderModel(cfg, policies.get("afloat", container="bit_exact"))
    tc = step_mod.TrainConfig(
        opt=adamw.AdamWConfig(lr=5e-3),
        schedule=Schedule(total_steps=10, warmup_steps=1, base_lr=5e-3))
    step = jax.jit(step_mod.make_train_step(model, tc))
    state = step_mod.init_state(model, jax.random.PRNGKey(0), tc)
    batch = {"tokens": jnp.ones((4, 16), jnp.int32),
             "labels": jnp.ones((4, 16), jnp.int32)}
    for _ in range(2):
        state, met = step(state, batch)
    assert np.isfinite(float(met["loss"]))
    assert "af_act_bias_mean" in met
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in jax.tree.leaves(state.pstate.learn))
