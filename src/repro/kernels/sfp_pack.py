"""Pallas TPU kernels: fixed-width SFP container pack/unpack (+ fused
quantize+pack).

The paper's compressor/decompressor (§V) adapted to the TPU memory
hierarchy (DESIGN.md §2): instead of a bit-serial packer at the DRAM pins,
values are re-containered in 8/16-bit lanes on the HBM<->VMEM path with one
shared 8-bit base exponent per 128-lane group (a Gecko column base).

Kernels are format-agnostic: the payload word geometry arrives as a
``kernels.ref.PackFields`` (mantissa bits kept, delta-exponent bits,
payload width); the container-name -> geometry mapping lives in the codec
registry (``repro.codecs``). The primary entry point is
``sfp_quantize_pack``: it fuses the mantissa truncation Q(M, n) from
Quantum Mantissa / BitChop with the exponent delta encoding in a single
VMEM pass — one HBM read of the activation instead of two (the separate
``mantissa_quant`` kernel followed by ``sfp_pack``), exactly the fusion the
paper's hardware packers do.

Layouts (see kernels/ref.py for the bit-level oracle):
  payload word = sign<<(P-1) | dexp<<(P-1-E) | man_top<<(P-1-E-K)
(dexp == max, man == 0) encodes exact zero; dexp saturates (values more
than 2^-dexp_max below the group max flush — bounded error, see tests).
Bases are per-128-lane-group shared exponents, stored as (R, 1) uint8.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import containers
from repro.kernels import ref as kref

LANES = kref.GROUP  # 128
DEFAULT_BLOCK_ROWS = 64


def vmem_estimate(*, fields: kref.PackFields,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  dtype=jnp.bfloat16, fused: bool = True) -> int:
    """Static per-grid-step VMEM footprint model, in bytes.

    Double-buffered in/out block windows plus the int32 working tiles of
    ``ref.pack_words`` (bitcast words, exponent/mantissa fields, packed
    word — modeled as four live (block_rows, 128) int32 tiles; the unpack
    direction is bounded by the same count). Budget model for
    ``repro.analysis.vmem``, not an allocator.
    """
    isz = jnp.dtype(dtype).itemsize
    psz = jnp.dtype(fields.payload_dtype).itemsize
    blocks = 2 * (
        block_rows * LANES * isz             # x in
        + block_rows * LANES * psz           # payload out
        + block_rows * 1                     # bases out (uint8)
    )
    if fused:
        blocks += 2 * 4                      # n scalar (1, 1) int32
    temps = 4 * block_rows * LANES * 4
    return blocks + temps


def _pack_kernel(x_ref, payload_ref, base_ref, *, spec, fields):
    word, base = kref.pack_words(x_ref[...], fields, spec)
    payload_ref[...] = word.astype(payload_ref.dtype)
    base_ref[...] = base.astype(jnp.uint8)


def _quantize_pack_kernel(n_ref, x_ref, payload_ref, base_ref, *, spec,
                          fields):
    word, base = kref.pack_words(x_ref[...], fields, spec, n=n_ref[0, 0])
    payload_ref[...] = word.astype(payload_ref.dtype)
    base_ref[...] = base.astype(jnp.uint8)


def _unpack_kernel(payload_ref, base_ref, o_ref, *, spec,
                   fields: kref.PackFields):
    o_ref[...] = kref.unpack_words(payload_ref[...], base_ref[...], fields,
                                    spec)


def _to_rows(x: jax.Array) -> Tuple[jax.Array, int]:
    flat = x.reshape(-1)
    pad = (-flat.size) % LANES
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), pad


def _row_grid(rows2d: jax.Array, block_rows: int):
    rows = rows2d.shape[0]
    block_rows = min(block_rows, rows)
    rpad = (-rows) % block_rows
    if rpad:
        rows2d = jnp.pad(rows2d, ((0, rpad), (0, 0)))
    return rows2d, rows, rpad, block_rows


@functools.partial(jax.jit, static_argnames=("fields", "block_rows",
                                             "interpret"))
def sfp_pack(x: jax.Array, *, fields: kref.PackFields,
             block_rows: int = DEFAULT_BLOCK_ROWS,
             interpret: Optional[bool] = None):
    """Pack ``x`` into (payload rows, per-row base exponents).

    Returns (payload (R, 128) uint8|uint16, bases (R, 1) uint8). Rows are
    128-lane groups of the flattened tensor (Gecko columns).
    """
    interpret = kref.default_interpret(interpret)
    spec = containers.spec_for(x)
    rows2d, _pad = _to_rows(x)
    rows2d, rows, rpad, block_rows = _row_grid(rows2d, block_rows)
    grid = (rows2d.shape[0] // block_rows,)

    payload, bases = pl.pallas_call(
        functools.partial(_pack_kernel, spec=spec, fields=fields),
        name="sfp_pack",
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(rows2d.shape, fields.payload_dtype),
            jax.ShapeDtypeStruct((rows2d.shape[0], 1), jnp.uint8),
        ],
        interpret=interpret,
    )(rows2d)
    if rpad:
        payload, bases = payload[:rows], bases[:rows]
    return payload, bases


@functools.partial(jax.jit, static_argnames=("fields", "block_rows",
                                             "interpret"))
def sfp_quantize_pack(x: jax.Array, n: jax.Array, *, fields: kref.PackFields,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: Optional[bool] = None):
    """Fused Q(M, n) + pack: one VMEM pass, one HBM read of ``x``.

    Bit-exact against mantissa_quant.mantissa_quantize followed by
    sfp_pack; ``n`` is a traced scalar carried in SMEM (updated per step by
    Quantum Mantissa / BitChop).
    """
    interpret = kref.default_interpret(interpret)
    spec = containers.spec_for(x)
    rows2d, _pad = _to_rows(x)
    rows2d, rows, rpad, block_rows = _row_grid(rows2d, block_rows)
    grid = (rows2d.shape[0] // block_rows,)

    payload, bases = pl.pallas_call(
        functools.partial(_quantize_pack_kernel, spec=spec, fields=fields),
        name="sfp_quantize_pack",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # scalar n
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(rows2d.shape, fields.payload_dtype),
            jax.ShapeDtypeStruct((rows2d.shape[0], 1), jnp.uint8),
        ],
        interpret=interpret,
    )(jnp.asarray(n, jnp.int32).reshape(1, 1), rows2d)
    if rpad:
        payload, bases = payload[:rows], bases[:rows]
    return payload, bases


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "fields",
                                             "block_rows", "interpret"))
def sfp_unpack(payload: jax.Array, bases: jax.Array, *, shape: tuple,
               dtype, fields: kref.PackFields,
               block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: Optional[bool] = None) -> jax.Array:
    interpret = kref.default_interpret(interpret)
    spec = containers.spec_for(jnp.dtype(dtype))

    rows = payload.shape[0]
    block_rows = min(block_rows, rows)
    rpad = (-rows) % block_rows
    if rpad:
        payload = jnp.pad(payload, ((0, rpad), (0, 0)))
        bases = jnp.pad(bases, ((0, rpad), (0, 0)))
    grid = (payload.shape[0] // block_rows,)

    out = pl.pallas_call(
        functools.partial(_unpack_kernel, spec=spec, fields=fields),
        name="sfp_unpack",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(payload.shape, spec.dtype),
        interpret=interpret,
    )(payload, bases)
    if rpad:
        out = out[:rows]
    n = 1
    for s in shape:
        n *= s
    return out.reshape(-1)[:n].reshape(shape)
