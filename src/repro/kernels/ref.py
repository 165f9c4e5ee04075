"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the kernel allclose tests, the CPU execution
path, and the lowering path used by the multi-pod dry-run (Pallas TPU
kernels cannot lower on the CPU backend; the FLOP/byte structure of these
references matches the kernels').

Kernels here are *format-agnostic bit machines*: SFP pack/unpack take a
``PackFields`` describing the payload word geometry, and the Gecko plane
codec works on raw uint8 exponent groups. The mapping from container
*names* (sfp8, sfp16, gecko8, ...) to bit geometries lives in one place —
the codec registry (``repro.codecs``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import containers

# ---------------------------------------------------------------------------
# Mantissa quantization (paper eq. 5) — oracle for kernels/mantissa_quant.py
# ---------------------------------------------------------------------------


def mantissa_truncate(x: jax.Array, n) -> jax.Array:
    """Q(M, n): keep the top ``n`` mantissa bits. ``n`` scalar (traced ok)."""
    return containers.truncate_mantissa(x, n)


def default_interpret(flag: Optional[bool] = None) -> bool:
    """Resolve a kernel ``interpret`` argument: an explicit flag wins;
    ``None`` auto-selects interpret mode exactly when not running on TPU.

    Every Pallas entry point in this package defaults ``interpret=None``
    and routes through here, so kernels compile for real on TPU without
    each call site threading the flag (``repro.analysis`` lints for
    hard-coded ``interpret=True`` defaults leaking outside tests)."""
    if flag is not None:
        return bool(flag)
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# SFP fixed-width containers — oracles for kernels/sfp_pack.py
#
# Layouts (DESIGN.md D3). One shared 8-bit base exponent per group of 128
# lanes (Gecko column-base in spirit; max-exponent base so deltas are >= 0):
#   payload word = sign<<(P-1) | dexp<<(P-1-E) | man_top<<(P-1-E-K)
# with P = payload bits, E = delta-exponent bits, K = kept mantissa bits.
# dexp saturates; (dexp == max, man == 0) encodes exact zero.
# ---------------------------------------------------------------------------

GROUP = 128
PLANE_BYTES = GROUP // 8  # one byte-aligned bit plane of a 128-lane group


class PackFields(NamedTuple):
    """Payload geometry of an SFP container.

    Kernels receive this instead of a container-name string; the registry
    in ``repro.codecs`` owns the name -> PackFields mapping.

    ``dense=False`` is the fixed-lane layout: one 8/16-bit payload word
    per value. ``dense=True`` is the bit-plane layout: the payload word is
    ``1 + dexp_bits + man_keep`` bits wide (any width 3..16) and each of
    its bits is stored as a contiguous byte-aligned plane over the
    128-lane group (16 bytes/plane, Gecko-style), so a value really
    occupies ``payload_bits`` bits — no rounding up to a lane width.
    """

    man_keep: int       # mantissa bits kept in the payload
    dexp_bits: int      # delta-exponent field width
    payload_bits: int   # total payload word width (3..16)
    dense: bool = False  # True -> byte-aligned bit-plane storage

    @property
    def word_dtype(self):
        """Narrowest uint holding one payload word (kernel-internal)."""
        return jnp.uint8 if self.payload_bits <= 8 else jnp.uint16

    @property
    def payload_dtype(self):
        """Element dtype of the stored payload array (planes are bytes)."""
        return jnp.uint8 if self.dense else self.word_dtype

    @property
    def group_payload_bytes(self) -> int:
        """Payload bytes one 128-lane group occupies (excl. the base)."""
        if self.dense:
            return self.payload_bits * PLANE_BYTES
        return GROUP * (1 if self.payload_bits <= 8 else 2)

    def nd_payload_cols(self, D: int) -> int:
        """Minor-dim width of the rank-preserving payload for a feature
        dim ``D`` (% 128 == 0): D payload words, or (D//128) groups of
        ``payload_bits`` 16-byte planes."""
        if self.dense:
            return (D // GROUP) * self.group_payload_bytes
        return D

    @property
    def sign_shift(self) -> int:
        return self.payload_bits - 1

    @property
    def dexp_shift(self) -> int:
        return self.payload_bits - 1 - self.dexp_bits

    @property
    def man_shift(self) -> int:
        return self.payload_bits - 1 - self.dexp_bits - self.man_keep

    @property
    def dexp_max(self) -> int:
        return (1 << self.dexp_bits) - 1


def _to_rows(x: jax.Array) -> jax.Array:
    """Flatten to (rows, 128) lane groups, zero-padding the tail."""
    flat = x.reshape(-1)
    pad = (-flat.size) % GROUP
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, GROUP)


# The bit machine below is written once for the jnp oracles and the Pallas
# kernels (which call these functions on their VMEM tiles). It works in
# int32 lanes only: Mosaic cannot lower shifts of 8/16-bit vectors or
# bitcasts that change the element width, so narrow containers are widened
# on load and narrowed on store, never shifted in place.


def _float_to_bits(x: jax.Array, spec: containers.FloatSpec) -> jax.Array:
    """Float bits as int32 (16-bit floats zero-extended)."""
    if spec.total_bits == 32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(x, spec.int_dtype).astype(jnp.int32)


def _bits_to_float(bits: jax.Array, spec: containers.FloatSpec) -> jax.Array:
    """Inverse of ``_float_to_bits``: int32 bits -> ``spec.dtype``."""
    if spec.total_bits == 32:
        return jax.lax.bitcast_convert_type(bits, spec.dtype)
    return jax.lax.bitcast_convert_type(bits.astype(spec.int_dtype),
                                        spec.dtype)


def pack_words(x: jax.Array, f: PackFields, spec: containers.FloatSpec,
                n=None) -> Tuple[jax.Array, jax.Array]:
    """Shared pack body over the last (128-lane) axis: floats -> (int32
    payload words, int32 max-exponent bases with a kept unit last axis).

    ``n`` (optional, traced ok) fuses Q(M, n) mantissa truncation into the
    same pass — the quantize+pack fusion of the hardware compressor.
    """
    u = _float_to_bits(x, spec)
    sign = (u >> spec.sign_shift) & 1
    e = (u >> spec.exp_shift) & spec.exp_mask
    man = u & spec.man_mask
    if n is not None:
        drop = spec.man_bits - jnp.clip(n, 0, spec.man_bits)
        man = man & (spec.man_mask ^ ((1 << drop) - 1))

    base = jnp.max(e, axis=-1, keepdims=True)  # max-exponent base: deltas >= 0
    dexp = base - e
    man_top = man >> (spec.man_bits - f.man_keep)
    flush = (e == 0) | (dexp > f.dexp_max)  # exact zeros + below-range values
    dexp = jnp.where(flush, f.dexp_max, jnp.minimum(dexp, f.dexp_max))
    man_top = jnp.where(flush, 0, man_top)
    sign = jnp.where(e == 0, 0, sign)

    word = ((sign << f.sign_shift) | (dexp << f.dexp_shift)
            | (man_top << f.man_shift))
    return word, base


def unpack_words(p: jax.Array, base: jax.Array, f: PackFields,
                  spec: containers.FloatSpec) -> jax.Array:
    """Payload words (any int dtype) + broadcastable bases -> floats of
    ``spec.dtype``. (dexp == max, man == 0) decodes to +0."""
    p = p.astype(jnp.int32)
    sign = (p >> f.sign_shift) & 1
    dexp = (p >> f.dexp_shift) & f.dexp_max
    man_top = (p >> f.man_shift) & ((1 << f.man_keep) - 1)
    e = jnp.maximum(base.astype(jnp.int32) - dexp, 0)
    flush = (dexp == f.dexp_max) & (man_top == 0)
    bits = ((sign << spec.sign_shift) | (e << spec.exp_shift)
            | (man_top << (spec.man_bits - f.man_keep)))
    return _bits_to_float(jnp.where(flush, 0, bits), spec)


def sfp_pack(x: jax.Array, fields: PackFields, n=None):
    """Pack a float tensor into (payload (R, 128), bases (R, 1) uint8).

    Rows are consecutive 128-lane groups of the flattened tensor (Gecko
    columns); identical layout to kernels/sfp_pack.py. ``n`` optionally
    fuses mantissa truncation Q(M, n) into the same pass.
    """
    spec = containers.spec_for(x)
    payload, base = pack_words(_to_rows(x), fields, spec, n)
    return payload.astype(fields.word_dtype), base.astype(jnp.uint8)


def sfp_pack_nd(x: jax.Array, fields: PackFields, n=None):
    """Rank-preserving pack: groups along the last dim (must be %128 == 0).

    Keeps the leading dims (batch, seq, ...) intact so GSPMD shardings
    propagate through the packed stash unchanged. payload has x's shape
    (uint8/uint16); bases has shape (*x.shape[:-1], D//128).
    """
    D = x.shape[-1]
    assert D % GROUP == 0, (x.shape,)
    spec = containers.spec_for(x)
    xg = x.reshape(*x.shape[:-1], D // GROUP, GROUP)
    payload, base = pack_words(xg, fields, spec, n)
    return (payload.astype(fields.word_dtype).reshape(x.shape),
            base[..., 0].astype(jnp.uint8))


def sfp_unpack_nd(payload: jax.Array, bases: jax.Array, dtype,
                  fields: PackFields) -> jax.Array:
    spec = containers.spec_for(jnp.dtype(dtype))
    D = payload.shape[-1]
    p = payload.reshape(*payload.shape[:-1], D // GROUP, GROUP)
    out = unpack_words(p, bases.astype(jnp.int32)[..., None], fields, spec)
    return out.reshape(payload.shape)


def sfp_unpack(payload: jax.Array, bases: jax.Array, shape: tuple,
               dtype, fields: PackFields) -> jax.Array:
    spec = containers.spec_for(jnp.dtype(dtype))
    out = unpack_words(payload, bases, fields, spec)
    n = 1
    for s in shape:
        n *= s
    return out.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# Dense bit-plane containers — oracles for kernels/bitplane_pack.py
#
# The variable payload-width realization: a payload word of P = 1 + E + K
# bits (any width 3..16) is stored as P byte-aligned bit planes per
# 128-lane group. Plane p is 16 contiguous bytes; byte i of plane p holds
# bit p of the payload words of lanes 8i..8i+7 (bit j <-> lane 8i+j). A
# value therefore occupies exactly P bits + the shared 8-bit group base —
# the learned bitlengths become real bytes instead of rounding up to an
# 8/16-bit lane.
# ---------------------------------------------------------------------------


def _lane_bit(shape) -> jax.Array:
    """Bit index j = lane % 8 of each lane's bit inside its plane byte."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) % 8


def plane_pack_words(words: jax.Array, payload_bits: int) -> jax.Array:
    """Transpose payload words (..., 128) into bit planes (..., P*16) u8.

    Plane p byte i is the sum over j of bit p of lane 8i+j's word times
    2^j. The sum over each run of 8 lanes is a matmul against a 0/1
    (128, 16) matrix: inputs 0 or 2^j <= 128 are exact in bf16 and the
    byte sums (<= 255) are exact in the f32 accumulator, on the MXU and
    on the CPU alike.
    """
    w = words.astype(jnp.int32)
    j = _lane_bit(w.shape)
    seg = (jax.lax.broadcasted_iota(jnp.int32, (GROUP, PLANE_BYTES), 0) // 8
           == jax.lax.broadcasted_iota(jnp.int32, (GROUP, PLANE_BYTES), 1)
           ).astype(jnp.bfloat16)
    planes = [jnp.dot((((w >> p) & 1) << j).astype(jnp.bfloat16), seg,
                      preferred_element_type=jnp.float32)
              for p in range(payload_bits)]
    out = jnp.concatenate(planes, axis=-1) if payload_bits > 1 else planes[0]
    return out.astype(jnp.int32).astype(jnp.uint8)


def plane_unpack_words(planes: jax.Array, payload_bits: int) -> jax.Array:
    """Invert plane_pack_words: (..., P*16) uint8 -> (..., 128) int32.

    One matmul against a 0/1 selection matrix repeats each plane byte over
    the 8 lanes it covers (bytes <= 255 are exact in bf16, and each output
    sums exactly one of them), so lane 8i+j of plane p's 128-lane block
    holds byte i; bit j is then shifted out.
    """
    lead = planes.shape[:-1]
    n, cols = planes.shape[-1], payload_bits * GROUP
    k = jax.lax.broadcasted_iota(jnp.int32, (n, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, cols), 1)
    pick = (k == (c // GROUP) * PLANE_BYTES
            + (c % GROUP) // 8).astype(jnp.bfloat16)
    x = planes.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
    byte = jnp.dot(x, pick,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    j = _lane_bit((*lead, GROUP))
    w = jnp.zeros((*lead, GROUP), jnp.int32)
    for p in range(payload_bits):
        w = w | (((byte[..., p * GROUP:(p + 1) * GROUP] >> j) & 1) << p)
    return w


def unpack_planes(planes: jax.Array, bases: jax.Array, fields: PackFields,
                  spec: containers.FloatSpec) -> jax.Array:
    """Dense plane decode: (..., P*16) planes + broadcastable bases ->
    (..., 128) floats. One definition for the ref oracles and the Pallas
    unpack kernel."""
    return unpack_words(plane_unpack_words(planes, fields.payload_bits),
                         bases, fields, spec)


def prefix_fields(fields: PackFields, prefix_planes: int) -> PackFields:
    """Geometry of the leading ``prefix_planes`` bits of a payload word.

    The payload word layout is most-significant-first (sign, delta-exp,
    mantissa top), so truncating a P-bit word to its top P' bits yields a
    valid narrower container with the same sign/dexp fields and
    ``man_keep - (P - P')`` mantissa bits: ``wide_word >> (P - P')`` *is*
    the narrow pack of the same values (flush encodings included — a wide
    flush word truncates to the narrow flush word). In the dense plane
    layout that truncation is free: planes are stored bit-index-ascending,
    so the leading P' bits live in the *last* P' planes of each group and
    a draft read touches a strict byte subset of the packed block.

    ``prefix_planes`` must keep at least one mantissa bit
    (``dexp_bits + 2 <= prefix_planes <= payload_bits``).
    """
    P = int(prefix_planes)
    if not fields.dexp_bits + 2 <= P <= fields.payload_bits:
        raise ValueError(
            f"prefix_planes={P} outside [{fields.dexp_bits + 2}, "
            f"{fields.payload_bits}] for {fields}")
    drop = fields.payload_bits - P
    return PackFields(man_keep=fields.man_keep - drop,
                      dexp_bits=fields.dexp_bits, payload_bits=P,
                      dense=fields.dense)


def prefix_plane_view(payload: jax.Array, fields: PackFields,
                      prefix_planes: int) -> jax.Array:
    """Slice a dense group payload (..., P*16) to its leading-plane prefix
    (..., P'*16): the last P' planes in storage order (planes are stored
    LSB-first, and the prefix keeps the *high* bits of the word)."""
    drop = fields.payload_bits - int(prefix_planes)
    return payload[..., drop * PLANE_BYTES:]


def unpack_tile(payload: jax.Array, bases: jax.Array, fields: PackFields,
                spec: containers.FloatSpec, *, rows: int, KH: int,
                hd: int, prefix_planes: Optional[int] = None) -> jax.Array:
    """Shared per-tile decompressor for the packed decode kernels.

    ``payload`` (rows, nd_payload_cols(KH*hd)) — fixed-lane words or dense
    bit planes — and ``bases`` (rows, G) expand to (rows, KH, hd) float32.
    This is the body both flash-decode kernels run on each KV tile inside
    the online-softmax loop: only the ``rows`` (= block_l) slots being
    consumed are ever expanded, in VMEM, immediately before the dot. Each
    128-lane group is a static lane slice of the tile (no lane reshapes,
    which Mosaic cannot lower at plane-byte granularity).

    ``prefix_planes`` selects the speculative *draft* read mode: only the
    leading P' bits of each payload word are expanded, decoded as the
    truncated geometry (``prefix_fields``). Dense geometries skip the low
    planes, so the expansion work shrinks with P'; fixed-lane words shift
    in place (same bytes, same truncated semantics).
    """
    G = (KH * hd) // GROUP
    f = (fields if prefix_planes is None
         else prefix_fields(fields, prefix_planes))
    drop = fields.payload_bits - f.payload_bits
    width = fields.group_payload_bytes if fields.dense else GROUP
    out = []
    for g in range(G):
        p = payload[:, g * width:(g + 1) * width]
        if fields.dense:
            words = plane_unpack_words(
                prefix_plane_view(p, fields, f.payload_bits), f.payload_bits)
        else:
            words = p.astype(jnp.int32) >> drop
        out.append(unpack_words(words, bases[:, g:g + 1], f, spec))
    x = jnp.concatenate(out, axis=-1) if G > 1 else out[0]
    return x.reshape(rows, KH, hd).astype(jnp.float32)


def bitplane_pack(x: jax.Array, fields: PackFields, n=None):
    """Dense pack: (planes (R, P*16) uint8, bases (R, 1) uint8).

    Same payload-word bit machine as ``sfp_pack`` (``n`` fuses Q(M, n)),
    then the words are transposed into byte-aligned bit planes. Rows are
    128-lane groups of the flattened tensor, zero-padded at the tail.
    """
    spec = containers.spec_for(x)
    words, base = pack_words(_to_rows(x), fields, spec, n)
    return plane_pack_words(words, fields.payload_bits), base.astype(jnp.uint8)


def bitplane_unpack(planes: jax.Array, bases: jax.Array, shape: tuple,
                    dtype, fields: PackFields) -> jax.Array:
    spec = containers.spec_for(jnp.dtype(dtype))
    out = unpack_planes(planes, bases, fields, spec)
    n = 1
    for s in shape:
        n *= s
    return out.reshape(-1)[:n].reshape(shape)


def bitplane_pack_nd(x: jax.Array, fields: PackFields, n=None):
    """Rank-preserving dense pack (last dim % 128 == 0).

    payload has shape (*x.shape[:-1], (D//128) * P * 16) uint8 — each
    position's payload bytes are laid out (group, plane, 16), so one
    sequence row owns its own bytes and splices without read-modify-write;
    bases has shape (*x.shape[:-1], D//128) as in ``sfp_pack_nd``.
    """
    D = x.shape[-1]
    assert D % GROUP == 0, (x.shape,)
    spec = containers.spec_for(x)
    xg = x.reshape(*x.shape[:-1], D // GROUP, GROUP)
    words, base = pack_words(xg, fields, spec, n)
    planes = plane_pack_words(words, fields.payload_bits)
    return (planes.reshape(*x.shape[:-1], fields.nd_payload_cols(D)),
            base[..., 0].astype(jnp.uint8))


def bitplane_unpack_nd(planes: jax.Array, bases: jax.Array, dtype,
                       fields: PackFields) -> jax.Array:
    spec = containers.spec_for(jnp.dtype(dtype))
    G = bases.shape[-1]
    p = planes.reshape(*planes.shape[:-1], G, fields.group_payload_bytes)
    out = unpack_planes(p, bases[..., None], fields, spec)
    return out.reshape(*planes.shape[:-1], G * GROUP)


# ---------------------------------------------------------------------------
# Gecko delta-mode exponent compression — oracle for kernels/gecko_pack.py
#
# Byte-aligned bit-plane realization of core/gecko.py's 8x8 delta scheme:
# each 64-exponent group is an 8x8 matrix; row 0 holds the 8 column bases;
# rows 1..7 store sign+magnitude deltas against the bases as *bit planes* —
# one byte per plane holds that bit for all 8 columns, so a row whose max
# |delta| needs w bits occupies exactly (w + 1) bytes (sign plane + w
# magnitude planes). The dense (G, 63)-byte form below is the jit-friendly
# device representation; repro.codecs.gecko compacts it into the actual
# variable-length byte stream (and proves bit-exactness vs core/gecko.py).
# ---------------------------------------------------------------------------

GECKO_GROUP = 64   # exponents per group (8 rows x 8 cols)
GECKO_ROWS = 7     # delta rows (row 0 is the bases)
GECKO_PLANES = 9   # sign plane + 8 magnitude bit planes
GECKO_PLANE_BYTES = GECKO_ROWS * GECKO_PLANES  # 63 dense bytes per group


def gecko_encode_block(g: jax.Array):
    """Shared encode body: (B, 64) int32 groups -> int32 (bases (B, 8),
    widths (B, 7), planes (B, 63)). Called by both the jnp oracle below
    and the Pallas kernel in kernels/gecko_pack.py, so the plane layout
    has exactly one definition."""
    g = g.reshape(-1, 8, 8)
    bases = g[:, 0, :]
    d = g[:, 1:, :] - bases[:, None, :]          # (B, 7, 8)
    sign = (d < 0).astype(jnp.int32)
    mag = jnp.abs(d)

    width = jnp.zeros(mag.shape[:2], jnp.int32)  # (B, 7)
    row_max = jnp.max(mag, axis=2)
    for b in range(8, -1, -1):                   # 255 needs 8 bits
        width = jnp.where((row_max >> b) > 0, jnp.maximum(width, b + 1),
                          width)

    col = jnp.arange(8, dtype=jnp.int32)
    plane_list = [jnp.sum(sign << col, axis=2)]  # sign plane
    for b in range(8):
        plane_list.append(jnp.sum(((mag >> b) & 1) << col, axis=2))
    planes = jnp.stack(plane_list, axis=2)       # (B, 7, 9)
    return bases, width, planes.reshape(-1, GECKO_PLANE_BYTES)


def gecko_decode_block(bases: jax.Array, planes: jax.Array) -> jax.Array:
    """Shared decode body (int32 in/out): invert gecko_encode_block."""
    pl = planes.reshape(-1, GECKO_ROWS, GECKO_PLANES)
    col = jnp.arange(8, dtype=jnp.int32)
    sign = (pl[:, :, 0:1] >> col[None, None, :]) & 1        # (B, 7, 8)
    mag = jnp.zeros_like(sign)
    for b in range(8):
        mag = mag | (((pl[:, :, b + 1: b + 2] >> col[None, None, :]) & 1)
                     << b)
    d = jnp.where(sign == 1, -mag, mag)
    b0 = bases[:, None, :]
    full = jnp.concatenate([b0, b0 + d], axis=1)            # (B, 8, 8)
    return full.reshape(-1, GECKO_GROUP)


def gecko_plane_encode(groups: jax.Array):
    """Encode (G, 64) uint8 exponent groups into dense plane form.

    Returns (bases (G, 8) uint8, widths (G, 7) uint8, planes (G, 63) uint8).
    ``widths[g, r]`` is the magnitude bitwidth of delta row r+1 — identical
    to core/gecko.py's ``row_widths``; planes above a row's width are zero.
    """
    bases, width, planes = gecko_encode_block(groups.astype(jnp.int32))
    return (bases.astype(jnp.uint8), width.astype(jnp.uint8),
            planes.astype(jnp.uint8))


def gecko_plane_decode(bases: jax.Array, planes: jax.Array) -> jax.Array:
    """Invert gecko_plane_encode: (G, 8), (G, 63) -> (G, 64) uint8."""
    out = gecko_decode_block(bases.astype(jnp.int32),
                             planes.astype(jnp.int32))
    return out.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Decode over the packed KV cache — oracle for kernels/packed_flash_decode.py
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def decode_kv_mask(pos, L: int, window: Optional[int] = None, slots=None):
    """Validity of each KV-cache slot for a decode query at absolute
    position ``pos``.

    Global caches (``window=None``) store position p at slot p. Local
    caches are L-slot ring buffers (L <= window): slot s holds the latest
    position p <= pos with p === s (mod L), valid while inside the window.
    ``slots`` defaults to arange(L); kernels pass their block-relative
    slot indices (padded slots >= L are masked off). ``pos`` may carry
    leading batch dims (broadcast against ``slots``).
    """
    if slots is None:
        slots = jnp.arange(L)
    if window is None:
        return (slots <= pos) & (slots < L)
    k_pos = pos - jnp.mod(pos - slots, L)
    return ((k_pos >= 0) & (k_pos <= pos) & (k_pos > pos - window)
            & (slots < L))


def packed_flash_decode(q: jax.Array, k_payload: jax.Array,
                        k_bases: jax.Array, v_payload: jax.Array,
                        v_bases: jax.Array, pos, fields: PackFields, *,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        block_l: Optional[int] = None,
                        prefix_planes: Optional[int] = None) -> jax.Array:
    """Unpack-then-attend decode oracle for kernels/packed_flash_decode.py.

    Decompresses the whole packed cache (same bit logic as the kernel:
    ``unpack_words``) and attends the single query token with the same
    online-softmax block recurrence over ``block_l``-slot KV blocks, so
    the Pallas kernel validates bit-for-bit in interpret mode.

    q: (B, 1, H, hd); payload (B, L, fields.nd_payload_cols(KH*hd)) and
    bases (B, L, KH*hd // 128) — the rank-preserving layout of
    ``sfp_pack_nd`` (fixed-lane words) or ``bitplane_pack_nd`` (dense bit
    planes; the kernel expands the planes inline). GQA is grouped: q head
    h reads kv head h // (H // KH). ``pos`` is scalar (whole batch at one
    position) or (B,) — one decode position per batch row (the serving
    engine's continuous-batching slots). ``prefix_planes`` is the
    speculative draft read mode: expand only the leading P' payload bits
    (see ``prefix_fields``) of the same packed cache.
    """
    B, _, H, hd = q.shape
    L, G = k_bases.shape[1], k_bases.shape[2]
    D = G * GROUP
    KH = D // hd
    rep = H // KH
    spec = containers.spec_for(jnp.dtype(q.dtype))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    # Kernel-identical blocking: shrink to a divisor of L (the kernel never
    # pads the cache — that would copy the packed arrays every step).
    bl = L if block_l is None else min(block_l, L)
    while L % bl:
        bl -= 1

    def unp(payload, bases):
        # Same tile decompressor the kernels run (rows = every slot here:
        # the oracle expands the whole cache up front).
        x = unpack_tile(payload.reshape(B * L, -1), bases.reshape(B * L, G),
                        fields, spec, rows=B * L, KH=KH, hd=hd,
                        prefix_planes=prefix_planes)
        return x.reshape(B, L, KH, hd)

    k = unp(k_payload, k_bases)
    v = unp(v_payload, v_bases)
    qf = q.reshape(B, KH, rep, hd).astype(jnp.float32)
    scale = 1.0 / (hd ** 0.5)

    # Per-batch block loop mirroring the kernel grid exactly (one grid row
    # per batch element) so accumulation order — and thus every float bit —
    # matches the Pallas kernel in interpret mode.
    outs = []
    for b in range(B):
        m = jnp.full((KH, rep, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((KH, rep, 1), jnp.float32)
        acc = jnp.zeros((KH, rep, hd), jnp.float32)
        for ki in range(L // bl):
            k_c = k[b, ki * bl:(ki + 1) * bl]
            v_c = v[b, ki * bl:(ki + 1) * bl]
            s = jnp.einsum("hgd,lhd->hgl", qf[b], k_c) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            valid = decode_kv_mask(pos[b], L, window,
                                   slots=ki * bl + jnp.arange(bl))
            s = jnp.where(valid[None, None, :], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("hgl,lhd->hgd", p, v_c)
            m = m_new
        outs.append(acc / jnp.maximum(l, 1e-30))
    o = jnp.stack(outs, axis=0)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def paged_gather(part: jax.Array, tables: jax.Array) -> jax.Array:
    """Gather pool blocks into per-row contiguous sequences.

    ``part`` is one packed pool part (P_blocks, block_l, ...) — payload or
    bases; ``tables`` (B, nb) holds physical block ids per logical block
    (invalid logical blocks point at the reserved trash block and are
    masked by position downstream). Returns (B, nb * block_l, ...).
    """
    g = part[tables]                      # (B, nb, block_l, ...)
    return g.reshape(g.shape[0], -1, *g.shape[3:])


def paged_flash_decode(q: jax.Array, k_payload: jax.Array,
                       k_bases: jax.Array, v_payload: jax.Array,
                       v_bases: jax.Array, tables: jax.Array, pos,
                       fields: PackFields, *,
                       softcap: Optional[float] = None,
                       prefix_planes: Optional[int] = None) -> jax.Array:
    """Gather-unpack-attend oracle for the paged flash-decode kernel.

    Pool parts are (P_blocks, block_l, D) / (P_blocks, block_l, D // 128)
    in the ``sfp_pack_nd`` layout; ``tables`` (B, nb) maps each row's
    logical KV blocks to physical pool blocks; ``pos`` is (B,) or scalar.
    Gathers each row's blocks into a contiguous packed cache, then runs
    the exact block recurrence of ``packed_flash_decode`` (block_l = the
    pool block), so the Pallas paged kernel validates bit-for-bit in
    interpret mode. Paged caches are global-attention only (local ring
    buffers are window-bounded and stay per-slot contiguous).
    """
    block_l = k_payload.shape[1]
    return packed_flash_decode(
        q, paged_gather(k_payload, tables), paged_gather(k_bases, tables),
        paged_gather(v_payload, tables), paged_gather(v_bases, tables),
        pos, fields, window=None, softcap=softcap, block_l=block_l,
        prefix_planes=prefix_planes)


# ---------------------------------------------------------------------------
# Attention oracle — for kernels/flash_attention.py
# ---------------------------------------------------------------------------


def attention(
    q: jax.Array,           # (B, Sq, H, D)
    k: jax.Array,           # (B, Sk, KH, D)
    v: jax.Array,           # (B, Sk, KH, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,      # sliding window (local attention)
    softcap: Optional[float] = None,   # gemma2 attn-logit softcap
    prefix_len: int = 0,               # prefix-LM: first P kv fully visible
    q_offset: int = 0,                 # absolute position of q[0] (decode)
) -> jax.Array:
    """Reference multi-head GQA attention, O(Sq*Sk). fp32 accumulation."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    rep = H // KH
    kq = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vq = jnp.repeat(v, rep, axis=2) if rep > 1 else v

    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    q_pos = q_offset + jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask = k_pos <= q_pos
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    if prefix_len > 0:
        mask = mask | (k_pos < prefix_len)
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vq.astype(jnp.float32))
    return out.astype(q.dtype)
