"""Pallas TPU kernels: dense bit-plane container pack/unpack.

The variable payload-width realization of the paper's containers: instead
of rounding every payload up to an 8/16-bit lane (kernels/sfp_pack.py),
the payload word — sign + delta-exponent + kept mantissa, P = 1 + E + K
bits for any width 3..16 — is stored as P byte-aligned *bit planes* per
128-lane group (16 bytes per plane, Gecko-style), so an ``sfp-m2e4``
tensor really occupies 7 bits/value plus the shared 8-bit group bases.

The pack body is shared with kernels/sfp_pack.py (``ref.pack_words``: the
fused Q(M, n) quantize + delta-exponent encode over one VMEM block); this
module adds the word <-> plane transpose on either side, so quantize,
container encode and plane packing all happen in a single pass over the
activation — one HBM read, exactly like the fixed-lane fused kernel.

Layout (bit-level oracle: kernels/ref.py ``bitplane_pack``/``_unpack``):
  planes (R, P*16) uint8 — row r, plane p, byte i holds bit p of the
  payload words of lanes 8i..8i+7 of group r (bit j <-> lane 8i + j);
  bases  (R, 1)   uint8 — the shared per-group base exponents.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import containers
from repro.kernels import ref as kref
from repro.kernels.sfp_pack import DEFAULT_BLOCK_ROWS, _row_grid, _to_rows

LANES = kref.GROUP  # 128


def vmem_estimate(*, fields: kref.PackFields,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  dtype=jnp.bfloat16, fused: bool = True) -> int:
    """Static per-grid-step VMEM footprint model, in bytes.

    Same accounting as ``sfp_pack.vmem_estimate`` with the plane-packed
    output window ((block_rows, P*16) uint8) and one extra int32 word tile
    for the word <-> plane transpose. Budget model for
    ``repro.analysis.vmem``, not an allocator.
    """
    isz = jnp.dtype(dtype).itemsize
    pb = fields.group_payload_bytes
    blocks = 2 * (
        block_rows * LANES * isz             # x in
        + block_rows * pb                    # plane bytes out (uint8)
        + block_rows * 1                     # bases out (uint8)
    )
    if fused:
        blocks += 2 * 4                      # n scalar (1, 1) int32
    temps = 5 * block_rows * LANES * 4
    return blocks + temps


def _bitplane_pack_kernel(x_ref, plane_ref, base_ref, *, spec, fields):
    word, base = kref.pack_words(x_ref[...], fields, spec)
    plane_ref[...] = kref.plane_pack_words(word, fields.payload_bits)
    base_ref[...] = base.astype(jnp.uint8)


def _bitplane_quantize_pack_kernel(n_ref, x_ref, plane_ref, base_ref, *,
                                   spec, fields):
    word, base = kref.pack_words(x_ref[...], fields, spec, n=n_ref[0, 0])
    plane_ref[...] = kref.plane_pack_words(word, fields.payload_bits)
    base_ref[...] = base.astype(jnp.uint8)


def _bitplane_unpack_kernel(plane_ref, base_ref, o_ref, *, spec,
                            fields: kref.PackFields):
    # Same decode body as the ref oracle and the flash-decode tiles — one
    # definition of the int32 plane expansion and field machine.
    o_ref[...] = kref.unpack_planes(plane_ref[...], base_ref[...], fields,
                                    spec)


def _plane_pack_call(x, n, *, fields: kref.PackFields, block_rows: int,
                     interpret: Optional[bool]):
    interpret = kref.default_interpret(interpret)
    spec = containers.spec_for(x)
    rows2d, _pad = _to_rows(x)
    rows2d, rows, rpad, block_rows = _row_grid(rows2d, block_rows)
    grid = (rows2d.shape[0] // block_rows,)
    pb = fields.group_payload_bytes  # P * 16 plane bytes per group row

    out_specs = [
        pl.BlockSpec((block_rows, pb), lambda i: (i, 0)),
        pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((rows2d.shape[0], pb), jnp.uint8),
        jax.ShapeDtypeStruct((rows2d.shape[0], 1), jnp.uint8),
    ]
    row_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    if n is None:
        planes, bases = pl.pallas_call(
            functools.partial(_bitplane_pack_kernel, spec=spec,
                              fields=fields),
            name="bitplane_pack", grid=grid, in_specs=[row_spec],
            out_specs=out_specs, out_shape=out_shape,
            interpret=interpret)(rows2d)
    else:
        planes, bases = pl.pallas_call(
            functools.partial(_bitplane_quantize_pack_kernel, spec=spec,
                              fields=fields),
            name="bitplane_quantize_pack", grid=grid,
            in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), row_spec],
            out_specs=out_specs, out_shape=out_shape,
            interpret=interpret)(jnp.asarray(n, jnp.int32).reshape(1, 1),
                                 rows2d)
    if rpad:
        planes, bases = planes[:rows], bases[:rows]
    return planes, bases


@functools.partial(jax.jit, static_argnames=("fields", "block_rows",
                                             "interpret"))
def bitplane_pack(x: jax.Array, *, fields: kref.PackFields,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: Optional[bool] = None):
    """Dense pack: (planes (R, P*16) uint8, bases (R, 1) uint8)."""
    return _plane_pack_call(x, None, fields=fields, block_rows=block_rows,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("fields", "block_rows",
                                             "interpret"))
def bitplane_quantize_pack(x: jax.Array, n: jax.Array, *,
                           fields: kref.PackFields,
                           block_rows: int = DEFAULT_BLOCK_ROWS,
                           interpret: Optional[bool] = None):
    """Fused Q(M, n) + dense plane pack: one VMEM pass, one HBM read.

    Bit-exact against mantissa quantization followed by ``bitplane_pack``;
    ``n`` is a traced scalar carried in SMEM (updated per step by the
    precision policy).
    """
    return _plane_pack_call(x, n, fields=fields, block_rows=block_rows,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "fields",
                                             "block_rows", "interpret"))
def bitplane_unpack(planes: jax.Array, bases: jax.Array, *, shape: tuple,
                    dtype, fields: kref.PackFields,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: Optional[bool] = None) -> jax.Array:
    interpret = kref.default_interpret(interpret)
    spec = containers.spec_for(jnp.dtype(dtype))
    pb = fields.group_payload_bytes

    rows = planes.shape[0]
    block_rows = min(block_rows, rows)
    rpad = (-rows) % block_rows
    if rpad:
        planes = jnp.pad(planes, ((0, rpad), (0, 0)))
        bases = jnp.pad(bases, ((0, rpad), (0, 0)))
    grid = (planes.shape[0] // block_rows,)

    out = pl.pallas_call(
        functools.partial(_bitplane_unpack_kernel, spec=spec, fields=fields),
        name="bitplane_unpack",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, pb), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((planes.shape[0], LANES), spec.dtype),
        interpret=interpret,
    )(planes, bases)
    if rpad:
        out = out[:rows]
    n = 1
    for s in shape:
        n *= s
    return out.reshape(-1)[:n].reshape(shape)
