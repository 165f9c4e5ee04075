"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import containers as C, footprint, gecko
from repro.kernels import ref

floats = st.lists(
    st.floats(min_value=-1e6, max_value=1e6, allow_nan=False,
              allow_infinity=False, width=32),
    min_size=1, max_size=200)


@settings(max_examples=40, deadline=None)
@given(floats, st.integers(0, 23))
def test_truncation_never_increases_magnitude(vals, n):
    x = jnp.asarray(vals, jnp.float32)
    q = C.truncate_mantissa(x, n)
    assert (np.abs(np.asarray(q)) <= np.abs(np.asarray(x)) + 0.0).all()
    # sign preserved (or value zeroed)
    same_sign = np.sign(np.asarray(q)) == np.sign(np.asarray(x))
    assert (same_sign | (np.asarray(q) == 0)).all()


@settings(max_examples=40, deadline=None)
@given(floats, st.integers(0, 23))
def test_truncation_idempotent(vals, n):
    x = jnp.asarray(vals, jnp.float32)
    q1 = C.truncate_mantissa(x, n)
    q2 = C.truncate_mantissa(q1, n)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


@settings(max_examples=40, deadline=None)
@given(floats, st.integers(0, 22))
def test_truncation_relative_error_bound(vals, n):
    """|x - Q(x,n)| < 2^-n * |x| for normal x (ulp bound)."""
    x = jnp.asarray(vals, jnp.float32)
    x = jnp.where(jnp.abs(x) < 1e-30, 1.0, x)  # skip denormals
    q = C.truncate_mantissa(x, n)
    rel = np.abs(np.asarray(x - q)) / np.abs(np.asarray(x))
    assert (rel < 2.0 ** (-n)).all()


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 255), min_size=1, max_size=256))
def test_gecko_bits_at_least_metadata(vals):
    e = jnp.asarray(np.asarray(vals, np.uint8))
    bits = float(gecko.compressed_bits(e, "delta"))
    n_groups = -(-len(vals) // 64)
    assert bits >= n_groups * (64 + 21)  # 8 bases x 8b + 7 rows x 3b


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(min_value=-100, max_value=100, allow_nan=False,
                          width=32), min_size=128, max_size=128))
def test_sfp8_roundtrip_closure(vals):
    """decode(encode(x)) is a fixed point: encoding it again is identity."""
    x = jnp.asarray(vals, jnp.float32).astype(jnp.bfloat16).reshape(1, 128)
    from repro import codecs
    f = codecs.fields_for("sfp8", jnp.bfloat16)
    once = ref.sfp_unpack_nd(*ref.sfp_pack_nd(x, f), jnp.bfloat16, f)
    twice = ref.sfp_unpack_nd(*ref.sfp_pack_nd(once, f), jnp.bfloat16, f)
    np.testing.assert_array_equal(np.asarray(once).view(np.uint16),
                                  np.asarray(twice).view(np.uint16))


# ---------------------------------------------------------------------------
# Dense bit-plane containers: every payload width 3..16 vs a pure-Python
# oracle (independent numpy re-implementation of the word encode + the
# plane transpose, bit by bit).
# ---------------------------------------------------------------------------


def _py_sfp_words(x16: np.ndarray, man_keep: int, dexp_bits: int,
                  payload_bits: int) -> np.ndarray:
    """Pure-numpy bf16 SFP word encode over one (R, 128) row block."""
    u = x16.view(np.uint16).astype(np.int64)
    sign, e, man = (u >> 15) & 1, (u >> 7) & 0xFF, u & 0x7F
    base = e.max(axis=-1, keepdims=True)
    dexp = base - e
    dmax = (1 << dexp_bits) - 1
    man_top = man >> (7 - man_keep)
    flush = (e == 0) | (dexp > dmax)
    dexp = np.where(flush, dmax, np.minimum(dexp, dmax))
    man_top = np.where(flush, 0, man_top)
    sign = np.where(e == 0, 0, sign)
    word = ((sign << (payload_bits - 1))
            | (dexp << (payload_bits - 1 - dexp_bits))
            | (man_top << (payload_bits - 1 - dexp_bits - man_keep)))
    return word, base[..., 0]


# The loop-based plane transpose oracle is shared with the dense-codec
# suite — one definition of the byte/bit order, asserted from both sides.
from test_dense_codecs import py_plane_pack as _py_planes  # noqa: E402


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 7), st.integers(1, 8),
       st.lists(st.floats(min_value=-100, max_value=100, allow_nan=False,
                          width=32), min_size=128, max_size=128))
def test_dense_container_all_widths_vs_python_oracle(man, dexp, vals):
    """Sweep every dense payload width 3..16: packed planes match the
    pure-Python bit-plane oracle and the roundtrip is a fixed point."""
    payload = 1 + man + dexp
    if payload > 16:
        man = 16 - 1 - dexp  # clamp like codecs.dense_fields
        payload = 16
    from repro import codecs
    f = codecs.dense_fields(man, dexp, C.BF16)
    assert f.payload_bits == payload
    x = jnp.asarray(vals, jnp.float32).astype(jnp.bfloat16).reshape(1, 128)
    planes, bases = ref.bitplane_pack(x, f)
    words, base_py = _py_sfp_words(np.asarray(x).view(np.uint16),
                                   f.man_keep, f.dexp_bits, f.payload_bits)
    np.testing.assert_array_equal(np.asarray(bases)[:, 0], base_py)
    np.testing.assert_array_equal(np.asarray(planes),
                                  _py_planes(words, f.payload_bits))
    # roundtrip closure: re-encoding the decode is the identity
    once = ref.bitplane_unpack(planes, bases, (1, 128), jnp.bfloat16, f)
    p2, b2 = ref.bitplane_pack(once, f)
    twice = ref.bitplane_unpack(p2, b2, (1, 128), jnp.bfloat16, f)
    np.testing.assert_array_equal(np.asarray(once).view(np.uint16),
                                  np.asarray(twice).view(np.uint16))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 7), st.integers(1, 400))
def test_footprint_accounting_bounds(bits, n):
    x = (jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32)
         ).astype(jnp.bfloat16)
    rep = footprint.sfp_footprint(x, bits)
    assert rep.total_bits > 0
    assert rep.mantissa_bits == bits * n
    assert rep.sign_bits == n
    # never worse than ~9 extra bits/value of exponent+metadata
    assert rep.total_bits <= n * (1 + bits + 10) + 64 * 8


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6))
def test_bitchop_never_leaves_bounds(seed):
    from repro.core import bitchop
    rng = np.random.RandomState(seed)
    cfg = bitchop.BitChopConfig(warmup_steps=1, max_bits=7, min_bits=0)
    stt = bitchop.init(cfg)
    for i in range(50):
        stt = bitchop.update(stt, float(3 + rng.randn()), cfg,
                             lr_changed=(i % 17 == 0))
        assert 0 <= int(stt.n) <= 7


# The loop-based unpack oracle, shared the same way: both directions of
# the byte/bit order asserted against one independent definition.
from test_dense_codecs import py_plane_unpack as _py_plane_unpack  # noqa: E402


@settings(max_examples=30, deadline=None)
@given(st.integers(3, 16), st.integers(1, 5),
       st.integers(0, 2 ** 31 - 1), st.integers(0, 127))
def test_plane_expansion_all_widths_vs_python_oracle(payload, rows, seed,
                                                     tail):
    """The plane transpose (pack and expansion) is bit-exact against the
    loop oracle for every payload width 3..16, including a tail-padded
    final row (only ``128 - tail`` live lanes — the ragged end of a cache
    whose length is not a lane multiple)."""
    rng = np.random.RandomState(seed)
    words = rng.randint(0, 1 << payload, size=(rows, 128)).astype(np.int32)
    if tail:
        words[-1, 128 - tail:] = 0
    planes = np.asarray(ref.plane_pack_words(jnp.asarray(words), payload))
    np.testing.assert_array_equal(planes, _py_planes(words, payload))
    back = np.asarray(ref.plane_unpack_words(jnp.asarray(planes), payload))
    np.testing.assert_array_equal(back, words)
    np.testing.assert_array_equal(_py_plane_unpack(planes, payload), words)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 7), st.integers(1, 8), st.integers(0, 15),
       st.lists(st.floats(min_value=-100, max_value=100, allow_nan=False,
                          width=32), min_size=128, max_size=128))
def test_prefix_plane_expansion_equals_truncated_pack(man, dexp, cut, vals):
    """Self-speculative draft-read invariant, for every dense geometry
    and every valid prefix depth P': the *leading* P' bit planes of a
    packed block are byte-identical to packing the same values at the
    truncated geometry (man_keep - drop, same dexp, P' payload bits),
    and expand to exactly the truncated payload words — all asserted
    against the pure-Python word/plane oracles. This is what lets the
    draft pass read a strict byte subset of the full-width pool and
    still decode a well-formed narrower container."""
    payload = 1 + man + dexp
    if payload > 16:
        man = 16 - 1 - dexp  # clamp like codecs.dense_fields
        payload = 16
    from repro import codecs
    f = codecs.dense_fields(man, dexp, C.BF16)
    lo = f.dexp_bits + 2  # sign + full dexp + >= 1 mantissa bit
    pp = lo + cut % (f.payload_bits - lo + 1)   # valid P' in [lo, P]
    drop = f.payload_bits - pp
    nf = ref.prefix_fields(f, pp)
    assert (nf.payload_bits, nf.dexp_bits, nf.man_keep) == (
        pp, f.dexp_bits, f.man_keep - drop)
    x = jnp.asarray(vals, jnp.float32).astype(jnp.bfloat16).reshape(1, 128)
    planes, bases = ref.bitplane_pack(x, f)
    sliced = np.asarray(ref.prefix_plane_view(planes, f, pp))
    x16 = np.asarray(x).view(np.uint16)
    words, base_wide = _py_sfp_words(x16, f.man_keep, f.dexp_bits,
                                     f.payload_bits)
    narrow_words, base_narrow = _py_sfp_words(x16, f.man_keep - drop,
                                              f.dexp_bits, pp)
    # Truncating the wide word IS the narrow-geometry encode (incl. the
    # flush-to-zero cases), and the shared exponent base is unchanged.
    np.testing.assert_array_equal(narrow_words, words >> drop)
    np.testing.assert_array_equal(base_wide, base_narrow)
    # The leading planes are byte-for-byte the narrow container's pack...
    np.testing.assert_array_equal(sliced, _py_planes(narrow_words, pp))
    # ...and the expansion of the slice yields the truncated words.
    np.testing.assert_array_equal(
        np.asarray(ref.plane_unpack_words(jnp.asarray(sliced), pp)),
        narrow_words)
    # out-of-range prefix depths must be rejected, not mis-sliced
    with pytest.raises(ValueError):
        ref.prefix_fields(f, lo - 1)
    with pytest.raises(ValueError):
        ref.prefix_fields(f, f.payload_bits + 1)


@settings(max_examples=30, deadline=None)
@given(st.integers(3, 16), st.integers(1, 4), st.integers(0, 2 ** 31 - 1))
def test_plane_unpack_bijective_on_trash_blocks(payload, rows, seed):
    """Arbitrary garbage plane bytes (what the pool's trash block holds)
    decode to in-range payload words, match the loop oracle, and
    re-encode to the identical bytes — expansion and packing are inverse
    bijections on the full byte space, so trash-backed reads can never
    fabricate out-of-range state."""
    rng = np.random.RandomState(seed)
    planes = rng.randint(0, 256,
                         size=(rows, payload * 16)).astype(np.uint8)
    words = np.asarray(ref.plane_unpack_words(jnp.asarray(planes),
                                              payload))
    assert (words >= 0).all() and (words < (1 << payload)).all()
    np.testing.assert_array_equal(words, _py_plane_unpack(planes, payload))
    again = np.asarray(ref.plane_pack_words(jnp.asarray(words), payload))
    np.testing.assert_array_equal(again, planes)
