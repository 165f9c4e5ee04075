"""Pallas TPU kernel: mantissa truncation Q(M, n) (paper eq. 5).

The quantizer datapath of the paper's compressor (§V-A) as a VPU kernel:
bitcast -> mask the low (m - n) mantissa bits -> bitcast back, tiled over
(block_rows, 128) VMEM blocks. ``n`` arrives as a scalar (traced per step —
Quantum Mantissa / BitChop update it each batch), carried in SMEM.

Validated against repro.kernels.ref.mantissa_truncate in interpret mode
(CPU) across shape/dtype sweeps; on TPU the same kernel lowers natively.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import containers
from repro.kernels.ref import default_interpret

LANES = 128
DEFAULT_BLOCK_ROWS = 256


def _quant_kernel(n_ref, x_ref, o_ref, *, spec: containers.FloatSpec):
    x = x_ref[...]
    n = jnp.clip(n_ref[0, 0], 0, spec.man_bits)
    u = jax.lax.bitcast_convert_type(x, spec.int_dtype)
    drop = (spec.man_bits - n).astype(spec.int_dtype)
    one = jnp.asarray(1, spec.int_dtype)
    low = jnp.left_shift(one, drop) - one
    keep = jnp.asarray(spec.man_mask, spec.int_dtype) ^ low
    mask = jnp.asarray(
        ~spec.man_mask & ((1 << spec.total_bits) - 1), spec.int_dtype) | keep
    o_ref[...] = jax.lax.bitcast_convert_type(u & mask, spec.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def mantissa_quantize(x: jax.Array, n: jax.Array, *,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Truncate mantissas of ``x`` to ``n`` bits (scalar int32, traced ok)."""
    interpret = default_interpret(interpret)
    spec = containers.spec_for(x)
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % (block_rows * LANES)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, LANES)
    rows = x2.shape[0]
    grid = (rows // block_rows,)

    out = pl.pallas_call(
        functools.partial(_quant_kernel, spec=spec),
        name="mantissa_quantize",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # scalar n
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(jnp.asarray(n, jnp.int32).reshape(1, 1), x2)

    out = out.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape)
