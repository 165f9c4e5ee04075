"""Jitted dispatch wrappers: Pallas kernel on TPU, jnp reference elsewhere.

All model/runtime code calls through these so the same program runs on the
CPU test/dry-run environment (reference path; identical FLOP/byte shape)
and on real TPUs (Pallas path). ``force_backend()`` is the test hook.

These wrappers are format-agnostic: SFP entry points take a
``kernels.ref.PackFields`` payload geometry and the Gecko entry points take
raw exponent groups. Container *names* resolve to geometries in exactly
one place — the codec registry (``repro.codecs``) — which is also the only
API most callers should use.

The SFP packed representation is a plain (payload, bases) array pair —
array-only so it can ride through lax.scan as the compressed stash.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import bitplane_pack as _bp
from repro.kernels import flash_attention as _fa
from repro.kernels import gecko_pack as _gp
from repro.kernels import mantissa_quant as _mq
from repro.kernels import packed_flash_decode as _pfd
from repro.kernels import ref as _ref
from repro.kernels import sfp_pack as _sp

PackFields = _ref.PackFields  # re-export: the kernel-facing format descriptor
decode_kv_mask = _ref.decode_kv_mask  # shared ring-slot validity semantics
prefix_fields = _ref.prefix_fields  # truncated geometry of a draft read
DECODE_BLOCK_L = _pfd.DEFAULT_BLOCK_L  # flash-decode KV block (alloc hint)

_FORCED: Optional[str] = None  # None | 'pallas' | 'ref' | 'interpret'


def force_backend(name: Optional[str]) -> None:
    """Test hook: force 'pallas' (TPU), 'interpret' (CPU pallas), or 'ref'."""
    global _FORCED
    _FORCED = name


def backend() -> str:
    if _FORCED:
        return _FORCED
    return "pallas" if jax.default_backend() == "tpu" else "ref"


class Packed(NamedTuple):
    """SFP-compressed tensor: uint8/uint16 payload + per-group bases."""

    payload: jax.Array  # (R, 128) uint8 or uint16 payload words
    bases: jax.Array    # (R, 1) uint8 shared base exponents


# -- mantissa quantization ---------------------------------------------------

def mantissa_quantize(x: jax.Array, n) -> jax.Array:
    b = backend()
    if b in ("pallas", "interpret"):
        return _mq.mantissa_quantize(x, n, interpret=(b == "interpret"))
    return _ref.mantissa_truncate(x, n)


# -- SFP containers ----------------------------------------------------------
#
# Every entry point dispatches on ``fields.dense``: fixed-lane geometries
# (payload_bits 8/16) go through the word kernels in sfp_pack.py, dense
# sub-byte/odd-width geometries through the bit-plane kernels in
# bitplane_pack.py. Callers never branch — the PackFields carries the
# layout, the Packed pair carries either words or planes.

def sfp_compress(x: jax.Array, fields: PackFields) -> Packed:
    b = backend()
    if b in ("pallas", "interpret"):
        interp = (b == "interpret")
        if fields.dense:
            payload, bases = _bp.bitplane_pack(x, fields=fields,
                                               interpret=interp)
        else:
            payload, bases = _sp.sfp_pack(x, fields=fields, interpret=interp)
    elif fields.dense:
        payload, bases = _ref.bitplane_pack(x, fields)
    else:
        payload, bases = _ref.sfp_pack(x, fields)
    return Packed(payload=payload, bases=bases)


def sfp_decompress(packed: Packed, shape: tuple, dtype,
                   fields: PackFields) -> jax.Array:
    b = backend()
    if b in ("pallas", "interpret"):
        unpack = _bp.bitplane_unpack if fields.dense else _sp.sfp_unpack
        return unpack(packed.payload, packed.bases, shape=tuple(shape),
                      dtype=jnp.dtype(dtype), fields=fields,
                      interpret=(b != "pallas"))
    if fields.dense:
        return _ref.bitplane_unpack(packed.payload, packed.bases,
                                    tuple(shape), jnp.dtype(dtype), fields)
    return _ref.sfp_unpack(packed.payload, packed.bases, tuple(shape),
                           jnp.dtype(dtype), fields)


def sfp_compress_nd(x: jax.Array, fields: PackFields, n=None) -> Packed:
    """Rank-preserving pack (sharding-friendly; last dim % 128 == 0).

    ``n`` (optional traced scalar) fuses Q(M, n) mantissa truncation into
    the pack — a single HBM read instead of the mantissa_quantize ->
    sfp_compress_nd two-kernel sequence. Dense geometries emit bit planes:
    payload (*lead, (D//128) * P * 16) uint8 instead of (*lead, D) words.
    """
    b = backend()
    if b in ("pallas", "interpret"):
        # TPU path: the kernel operates on 128-lane rows; the reshape is a
        # no-op relayout on device. Interpret mode mirrors it for tests.
        rows = x.reshape(-1, _ref.GROUP)
        interp = (b == "interpret")
        if fields.dense:
            if n is None:
                payload, bases = _bp.bitplane_pack(rows, fields=fields,
                                                   interpret=interp)
            else:
                payload, bases = _bp.bitplane_quantize_pack(
                    rows, n, fields=fields, interpret=interp)
        elif n is None:
            payload, bases = _sp.sfp_pack(rows, fields=fields,
                                          interpret=interp)
        else:
            payload, bases = _sp.sfp_quantize_pack(rows, n, fields=fields,
                                                   interpret=interp)
        cols = fields.nd_payload_cols(x.shape[-1])
        return Packed(payload=payload.reshape(*x.shape[:-1], cols),
                      bases=bases.reshape(*x.shape[:-1],
                                          x.shape[-1] // _ref.GROUP))
    if fields.dense:
        payload, bases = _ref.bitplane_pack_nd(x, fields, n=n)
    else:
        payload, bases = _ref.sfp_pack_nd(x, fields, n=n)
    return Packed(payload=payload, bases=bases)


def sfp_decompress_nd(packed: Packed, dtype, fields: PackFields) -> jax.Array:
    b = backend()
    if b in ("pallas", "interpret"):
        G = packed.bases.shape[-1]
        shape = packed.bases.shape[:-1] + (G * _ref.GROUP,)
        if fields.dense:
            rows = packed.payload.reshape(-1, fields.group_payload_bytes)
            unpack = _bp.bitplane_unpack
        else:
            rows = packed.payload.reshape(-1, _ref.GROUP)
            unpack = _sp.sfp_unpack
        bases = packed.bases.reshape(-1, 1)
        return unpack(rows, bases, shape=shape, dtype=jnp.dtype(dtype),
                      fields=fields, interpret=(b != "pallas"))
    if fields.dense:
        return _ref.bitplane_unpack_nd(packed.payload, packed.bases,
                                       jnp.dtype(dtype), fields)
    return _ref.sfp_unpack_nd(packed.payload, packed.bases, jnp.dtype(dtype),
                              fields)


def sfp_quantize_compress(x: jax.Array, n, fields: PackFields) -> Packed:
    """Fused Q(M, n) + flat pack: one pass over ``x`` (single HBM read)."""
    b = backend()
    if b in ("pallas", "interpret"):
        interp = (b == "interpret")
        if fields.dense:
            payload, bases = _bp.bitplane_quantize_pack(
                x, n, fields=fields, interpret=interp)
        else:
            payload, bases = _sp.sfp_quantize_pack(x, n, fields=fields,
                                                   interpret=interp)
        return Packed(payload=payload, bases=bases)
    if fields.dense:
        payload, bases = _ref.bitplane_pack(x, fields, n=n)
    else:
        payload, bases = _ref.sfp_pack(x, fields, n=n)
    return Packed(payload=payload, bases=bases)


def sfp_roundtrip(x: jax.Array, fields: PackFields) -> jax.Array:
    """compress->decompress (fake-quant view of the realized container)."""
    return sfp_decompress(sfp_compress(x, fields), x.shape, x.dtype, fields)


# -- Gecko exponent compression ---------------------------------------------

def gecko_encode(groups: jax.Array):
    """(G, 64) uint8 exponent groups -> (bases, widths, planes)."""
    b = backend()
    if b in ("pallas", "interpret"):
        return _gp.gecko_pack(groups, interpret=(b == "interpret"))
    return _ref.gecko_plane_encode(groups)


def gecko_decode(bases: jax.Array, planes: jax.Array) -> jax.Array:
    """(bases (G, 8), planes (G, 63)) -> (G, 64) uint8 exponents."""
    b = backend()
    if b in ("pallas", "interpret"):
        return _gp.gecko_unpack(bases, planes, interpret=(b == "interpret"))
    return _ref.gecko_plane_decode(bases, planes)


# -- attention ---------------------------------------------------------------

def attention(q, k, v, *, causal=True, window=None, softcap=None,
              prefix_len: int = 0, q_offset: int = 0) -> jax.Array:
    """GQA attention; Pallas flash kernel on TPU, jnp reference off-TPU.

    GQA is native in the kernel: the q-head group is folded into the query
    rows (``q_rep``), so the KH-headed K/V are streamed once per group —
    no repeated-KV materialization in HBM. The kernel path is
    differentiable: its VJP is the reference's (see ``_flash``).
    """
    b = backend()
    if b in ("pallas", "interpret") and prefix_len == 0 and q_offset == 0:
        return _flash(q, k, v, causal, window, softcap, b == "interpret")
    return _ref.attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, prefix_len=prefix_len,
                          q_offset=q_offset)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, softcap, interpret):
    """Forward-only flash kernel with GQA folded into the query rows."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    rep = H // KH
    if rep == 1:
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, interpret=interpret)
    # (B, Sq, KH, rep, D) -> rows ordered (seq, group): row r of the
    # folded query axis is seq r // rep, group member r % rep.
    qg = q.reshape(B, Sq, KH, rep, D).transpose(0, 1, 3, 2, 4)
    qg = qg.reshape(B, Sq * rep, KH, D)
    o = _fa.flash_attention(qg, k, v, causal=causal, window=window,
                            softcap=softcap, q_rep=rep, interpret=interpret)
    o = o.reshape(B, Sq, rep, KH, D).transpose(0, 1, 3, 2, 4)
    return o.reshape(B, Sq, H, D)


def _flash_fwd(q, k, v, causal, window, softcap, interpret):
    return _flash(q, k, v, causal, window, softcap, interpret), (q, k, v)


def _flash_bwd(causal, window, softcap, interpret, res, g):
    # The kernel computes ref.attention up to f32 rounding, so the
    # reference's VJP (recomputed from q, k, v) is the kernel's gradient.
    # It materializes (B, H, Sq, Sk) scores; training routes only short
    # sequences here (models/attention.attention_train chunks long ones).
    _, vjp = jax.vjp(functools.partial(_ref.attention, causal=causal,
                                       window=window, softcap=softcap),
                     *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def packed_flash_decode(q, k_packed: Packed, v_packed: Packed, pos, *,
                        fields: PackFields, window=None, softcap=None,
                        prefix_planes: Optional[int] = None) -> jax.Array:
    """One-token decode attention directly over an SFP-packed KV cache.

    q: (B, 1, H, hd); the packed K/V pairs are in the rank-preserving
    ``sfp_pack_nd`` layout — payload (B, L, KH*hd), bases (B, L, D//128).
    On pallas/interpret this is the fused decompress-attend kernel (the
    bf16 cache never materializes in HBM); on the ref backend it is the
    unpack-then-attend oracle (the kernel matches it bit for bit in
    interpret mode, to f32 rounding on a TPU).
    ``prefix_planes`` is the speculative draft read mode: only the leading
    P' payload bits of the same packed cache are expanded, decoded as the
    truncated geometry (``ref.prefix_fields``) — same blocks, fewer planes.
    """
    b = backend()
    if b in ("pallas", "interpret"):
        return _pfd.packed_flash_decode(
            q, k_packed.payload, k_packed.bases, v_packed.payload,
            v_packed.bases, jnp.asarray(pos, jnp.int32), fields=fields,
            window=window, softcap=softcap, interpret=(b == "interpret"),
            prefix_planes=prefix_planes)
    return _ref.packed_flash_decode(
        q, k_packed.payload, k_packed.bases, v_packed.payload,
        v_packed.bases, pos, fields, window=window, softcap=softcap,
        block_l=_pfd.DEFAULT_BLOCK_L,  # kernel-matching accumulation order
        prefix_planes=prefix_planes)


def paged_flash_decode(q, k_packed: Packed, v_packed: Packed,
                       tables, pos, *, fields: PackFields, softcap=None,
                       prefix_planes: Optional[int] = None) -> jax.Array:
    """One-token decode attention over a paged SFP-packed KV block pool.

    The continuous-batching serving step: pool parts are
    (P_blocks, block_l, D) shared across requests, ``tables`` (B, nb)
    maps each row's logical blocks to physical pool blocks, and ``pos``
    (B,) carries per-row decode positions. On pallas/interpret the block
    table is a scalar-prefetch operand and the gather happens inside the
    kernel grid (no contiguous per-request cache in HBM); on the ref
    backend this is the gather-unpack-attend oracle with the identical
    block recurrence. Global attention only. ``prefix_planes`` is the
    speculative draft read mode (see ``packed_flash_decode``).
    """
    b = backend()
    if b in ("pallas", "interpret"):
        return _pfd.paged_flash_decode(
            q, k_packed.payload, k_packed.bases, v_packed.payload,
            v_packed.bases, jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos, jnp.int32), fields=fields, softcap=softcap,
            interpret=(b == "interpret"), prefix_planes=prefix_planes)
    return _ref.paged_flash_decode(
        q, k_packed.payload, k_packed.bases, v_packed.payload,
        v_packed.bases, tables, pos, fields, softcap=softcap,
        prefix_planes=prefix_planes)
