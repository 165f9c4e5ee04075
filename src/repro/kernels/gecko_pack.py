"""Pallas TPU kernels: Gecko delta-mode exponent pack/unpack (paper §IV-C).

Materializes the compressed exponent stream that core/gecko.py only
*accounts* for: each 64-exponent group (an 8x8 matrix) becomes

  bases  (8 bytes)  — row 0, the per-column base exponents;
  widths (7 values) — magnitude bitwidth of each delta row (== the
                      reference encoder's ``row_widths``);
  planes (63 bytes) — rows 1..7 as sign+magnitude *bit planes*: byte
                      ``[row, p]`` holds bit p of all 8 columns (p = 0 is
                      the sign plane, p = 1..8 the magnitude planes), so a
                      row of width w occupies exactly (w + 1) meaningful
                      bytes and planes above w are zero.

The kernels produce the dense fixed-shape form (static shapes keep them
jit/scan-compatible); ``repro.codecs.gecko`` compacts it into the actual
variable-length byte-aligned stream and proves bit-exactness against the
core/gecko.py encoder. Validated against kernels/ref.py's
gecko_plane_encode/decode oracles in interpret mode; on TPU the same
kernels lower natively (the (Bg, 64) -> (Bg, 8, 8) view is a minor-dim
relayout Mosaic handles).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as kref

DEFAULT_BLOCK_GROUPS = 128


def _gecko_pack_kernel(e_ref, base_ref, width_ref, plane_ref):
    # One shared body with the jnp oracle (ref.gecko_encode_block): the
    # kernel owns only the VMEM load/store plumbing.
    bases, width, planes = kref.gecko_encode_block(
        e_ref[...].astype(jnp.int32))
    base_ref[...] = bases.astype(jnp.uint8)
    width_ref[...] = width.astype(jnp.uint8)
    plane_ref[...] = planes.astype(jnp.uint8)


def _gecko_unpack_kernel(base_ref, plane_ref, o_ref):
    out = kref.gecko_decode_block(base_ref[...].astype(jnp.int32),
                                  plane_ref[...].astype(jnp.int32))
    o_ref[...] = out.astype(jnp.uint8)


def _group_grid(x: jax.Array, block_groups: int):
    n = x.shape[0]
    block_groups = min(block_groups, n)
    pad = (-n) % block_groups
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)), mode="edge")
    return x, n, pad, block_groups


@functools.partial(jax.jit, static_argnames=("block_groups", "interpret"))
def gecko_pack(groups: jax.Array, *,
               block_groups: int = DEFAULT_BLOCK_GROUPS,
               interpret: Optional[bool] = None):
    """Encode (G, 64) uint8 exponent groups -> (bases, widths, planes)."""
    interpret = kref.default_interpret(interpret)
    groups, n, pad, block_groups = _group_grid(groups, block_groups)
    grid = (groups.shape[0] // block_groups,)

    bases, widths, planes = pl.pallas_call(
        _gecko_pack_kernel,
        name="gecko_pack",
        grid=grid,
        in_specs=[pl.BlockSpec((block_groups, kref.GECKO_GROUP),
                               lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_groups, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_groups, 7), lambda i: (i, 0)),
            pl.BlockSpec((block_groups, kref.GECKO_PLANE_BYTES),
                         lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((groups.shape[0], 8), jnp.uint8),
            jax.ShapeDtypeStruct((groups.shape[0], 7), jnp.uint8),
            jax.ShapeDtypeStruct((groups.shape[0], kref.GECKO_PLANE_BYTES),
                                 jnp.uint8),
        ],
        interpret=interpret,
    )(groups)
    if pad:
        bases, widths, planes = bases[:n], widths[:n], planes[:n]
    return bases, widths, planes


@functools.partial(jax.jit, static_argnames=("block_groups", "interpret"))
def gecko_unpack(bases: jax.Array, planes: jax.Array, *,
                 block_groups: int = DEFAULT_BLOCK_GROUPS,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Decode (bases (G, 8), planes (G, 63)) -> (G, 64) uint8 exponents."""
    interpret = kref.default_interpret(interpret)
    n = bases.shape[0]
    block_groups = min(block_groups, n)
    pad = (-n) % block_groups
    if pad:
        bases = jnp.pad(bases, ((0, pad), (0, 0)))
        planes = jnp.pad(planes, ((0, pad), (0, 0)))
    grid = (bases.shape[0] // block_groups,)

    out = pl.pallas_call(
        _gecko_unpack_kernel,
        name="gecko_unpack",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_groups, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_groups, kref.GECKO_PLANE_BYTES),
                         lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_groups, kref.GECKO_GROUP),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bases.shape[0], kref.GECKO_GROUP),
                                       jnp.uint8),
        interpret=interpret,
    )(bases, planes)
    return out[:n] if pad else out
