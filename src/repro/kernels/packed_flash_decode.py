"""Pallas TPU kernel: fused decompress-attend flash decode.

One decode step directly over the SFP-packed KV cache — the paper's
"decompressor at the memory interface" realized at the consumer instead of
simulated: each grid step DMAs one packed KV block (payload words + the
per-128-lane shared base exponents) from HBM into VMEM, expands it inline
with the same bit logic as ``sfp_pack._unpack_kernel`` (PackFields
geometry), and feeds the online-softmax accumulator of
``flash_attention.py``. Dense geometries (``fields.dense``) store the
payload as byte-aligned bit planes (kernels/bitplane_pack.py) — the
in-kernel decompressor first re-expands the planes into payload words, so
the HBM read shrinks to the true 1 + E + K bits per value. The bf16 cache never materializes in HBM, so the
decode step's dominant read shrinks by the container ratio (~2x for sfp8)
instead of paying packed-read + bf16-write + bf16-read like the
unpack-then-attend fallback.

GQA is native to the grid: the query block for one batch row carries all
(KH, rep) head groups, so every q head of a kv-head group attends the same
unpacked block — K/V are never repeated, in HBM or VMEM.

Grid is (batch, kv_blocks) with the kv index innermost; VMEM scratch
carries the running (max, denominator, numerator) across kv blocks. Ring
slot validity (local sliding-window caches) is computed in-kernel from the
decode position (scalar, or one per batch row — continuous-batching
slots) via ``ref.decode_kv_mask``.

``paged_flash_decode`` is the continuous-batching variant: KV blocks live
in a request-agnostic pool, stacked over layers, and each row's logical
blocks are gathered through its block table *inside the grid* — the table
and the layer index are scalar-prefetch operands consumed by the BlockSpec
index_maps, so each (row, block) step DMAs its physical block of that
layer straight from the HBM pool. Same recurrence, same bit machine, same
masks; the logical blocks past each row's position are skipped (neither
fetched nor expanded), so a row's cost follows its length rather than the
table's.

Oracles: ``ref.packed_flash_decode`` / ``ref.paged_flash_decode``
(unpack-then-attend with the same block recurrence) — bit-exact against
the jitted oracle in interpret mode. Compiled for a TPU, the f32 softmax
accumulation runs in Mosaic's order, so results agree to f32 rounding.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import containers
from repro.kernels import ref as kref
from repro.kernels.flash_attention import NEG_INF, _vmem_scratch

DEFAULT_BLOCK_L = 128


def _geometry(fields: kref.PackFields) -> str:
    """Kernel-name suffix: dense bit planes, or fixed lanes."""
    return "planes" if fields.dense else "lanes"


def vmem_estimate(*, fields: kref.PackFields, H: int, KH: int, hd: int,
                  block_l: int = DEFAULT_BLOCK_L, dtype=jnp.bfloat16) -> int:
    """Static per-grid-step VMEM footprint model, in bytes.

    Counts what the grid actually keeps resident: the double-buffered
    in/out block windows (×2 for pipelining), the persistent f32
    online-softmax scratch, and the dominant decode-body temporaries (the
    expanded f32 K/V tiles, the int32 payload words mid-expansion, and the
    f32 score/probability tile). Elementwise chains the Mosaic compiler
    fuses are not charged — this is a budget model for the static
    contract check (``repro.analysis.vmem``), not an allocator.

    Both entry points have the same window shapes (positions, and the
    paged variant's block table, are scalar-prefetch operands living in
    SMEM), so one model covers both.
    """
    D = KH * hd
    G = D // kref.GROUP
    Dp = fields.nd_payload_cols(D)
    rep = H // KH
    isz = jnp.dtype(dtype).itemsize
    psz = 1 if fields.dense else jnp.dtype(fields.payload_dtype).itemsize
    blocks = 2 * (
        KH * rep * hd * isz                  # q block
        + 2 * block_l * Dp * psz             # k/v payload blocks
        + 2 * block_l * G                    # k/v base blocks (uint8)
        + KH * rep * hd * isz                # out block
    )
    scratch = 4 * (2 * KH * rep + KH * rep * hd)
    temps = (2 * block_l * D * 4             # expanded f32 k, v tiles
             + block_l * D * 4               # payload words as int32
             + 2 * KH * rep * block_l * 4)   # s, p score tiles
    return blocks + scratch + temps


def _decode_kernel(pos_ref, q_ref, kp_ref, kb_ref, vp_ref, vb_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_l: int, L: int, KH: int,
                   hd: int, window: Optional[int], softcap: Optional[float],
                   scale: float, fields: kref.PackFields, spec,
                   prefix_planes: Optional[int] = None):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[pl.program_id(0)]

    # Softmax-fused expansion: only this grid step's block_l-slot tile is
    # decompressed (ref.unpack_tile — the one inline-decompressor body both
    # decode kernels share), right before it feeds the recurrence. In the
    # draft (prefix_planes) read mode the plane slice happens in VMEM after
    # the full-block DMA; per-plane BlockSpec indexing that also shrinks
    # the HBM transfer is a Mosaic port (ROADMAP: TPU sublanes).
    k = kref.unpack_tile(kp_ref[0], kb_ref[0], fields, spec, rows=block_l,
                         KH=KH, hd=hd,
                         prefix_planes=prefix_planes)  # (block_l, KH, hd)
    v = kref.unpack_tile(vp_ref[0], vb_ref[0], fields, spec, rows=block_l,
                         KH=KH, hd=hd, prefix_planes=prefix_planes)
    q = q_ref[0].astype(jnp.float32)            # (KH, rep, hd)

    s = jnp.einsum("hgd,lhd->hgl", q, k) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    slots = ki * block_l + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, block_l), 2)
    valid = kref.decode_kv_mask(pos, L, window, slots=slots)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.einsum("hgl,lhd->hgd", p, v)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fields", "window", "softcap",
                                             "block_l", "interpret",
                                             "prefix_planes"))
def packed_flash_decode(q: jax.Array, k_payload: jax.Array,
                        k_bases: jax.Array, v_payload: jax.Array,
                        v_bases: jax.Array, pos: jax.Array, *,
                        fields: kref.PackFields,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        block_l: int = DEFAULT_BLOCK_L,
                        interpret: Optional[bool] = None,
                        prefix_planes: Optional[int] = None) -> jax.Array:
    """One-token attention over an SFP-packed (B, L, KH*hd) KV cache.

    q: (B, 1, H, hd); payload (B, L, fields.nd_payload_cols(D)) — 8/16-bit
    words, or uint8 bit planes for dense geometries — and bases
    (B, L, D // 128) uint8 in the rank-preserving ``sfp_pack_nd`` /
    ``bitplane_pack_nd`` layout (D = KH * hd, D % 128 == 0). ``pos`` is
    the absolute decode position — a scalar, or (B,) for
    continuous-batching slots each at their own position; ``window`` not
    None means an L-slot ring buffer (local attention). ``prefix_planes``
    is the speculative *draft* read mode: only the leading P' payload bits
    of the same packed cache are expanded, decoded as the truncated
    geometry (``ref.prefix_fields``). Returns (B, 1, H, hd) in q's dtype.
    """
    interpret = kref.default_interpret(interpret)
    B, one, H, hd = q.shape
    assert one == 1, q.shape
    L, G = k_bases.shape[1], k_bases.shape[2]
    D = G * kref.GROUP
    KH = D // hd
    assert KH * hd == D, (D, hd)
    assert k_payload.shape[2] == fields.nd_payload_cols(D), (
        k_payload.shape, fields)
    rep = H // KH
    assert rep * KH == H, (H, KH)
    Dp = k_payload.shape[2]
    spec = containers.spec_for(jnp.dtype(q.dtype))

    # Never pad the cache arrays: padding would copy the whole packed cache
    # in HBM every step — the exact traffic this kernel exists to avoid.
    # Shrink the block to a divisor of L instead (L is the cache allocation;
    # size max_len to a block_l multiple for peak block efficiency).
    block_l = min(block_l, L)
    while L % block_l:
        block_l -= 1
    grid = (B, L // block_l)

    qg = q.reshape(B, KH, rep, hd)  # q head h shares kv head h // rep
    pos1 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    scale = 1.0 / (hd ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # per-row decode positions, in SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, KH, rep, hd), lambda b, j, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_l, Dp), lambda b, j, pos: (b, j, 0)),
            pl.BlockSpec((1, block_l, G), lambda b, j, pos: (b, j, 0)),
            pl.BlockSpec((1, block_l, Dp), lambda b, j, pos: (b, j, 0)),
            pl.BlockSpec((1, block_l, G), lambda b, j, pos: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, KH, rep, hd),
                               lambda b, j, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            _vmem_scratch((KH, rep, 1)),
            _vmem_scratch((KH, rep, 1)),
            _vmem_scratch((KH, rep, hd)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_l=block_l, L=L, KH=KH,
                          hd=hd, window=window, softcap=softcap, scale=scale,
                          fields=fields, spec=spec,
                          prefix_planes=prefix_planes),
        name="packed_flash_decode_" + _geometry(fields),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, rep, hd), q.dtype),
        interpret=interpret,
    )(pos1, qg, k_payload, k_bases, v_payload, v_bases)
    return out.reshape(B, 1, H, hd)


def _paged_kernel(tab_ref, pos_ref, layer_ref, q_ref, kp_ref, kb_ref,
                  vp_ref, vb_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_l: int, nb: int, KH: int, hd: int,
                  softcap: Optional[float], scale: float,
                  fields: kref.PackFields, spec,
                  prefix_planes: Optional[int] = None):
    """One (batch row, logical KV block) step over the paged pool.

    The DMA gather already happened: the grid spec's index_map routed this
    step's physical block (``tab_ref[b, j]`` of layer ``layer_ref[0]``)
    into kp/kb/vp/vb, so the body is the contiguous decode kernel's on
    logical slots — the recurrence, masking and bit machine are shared,
    which is what makes paged decode bit-exact against the contiguous
    kernel over the same logical cache.

    Logical blocks past the row's position (``ki * block_l > pos``) are
    skipped: the index_map re-points them at the row's last live block
    (no new DMA) and the body below does not run, so they are neither
    fetched nor expanded. Block 0 always holds slot 0 <= pos, so the
    running max is finite before any skip, and each skipped block would
    have contributed exactly p == 0, alpha == 1 — the output is the one
    the masked recurrence over every block gives.
    """
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    L = nb * block_l

    @pl.when(ki * block_l <= pos)
    def _live_block():
        # Same softmax-fused per-tile expansion as the contiguous kernel —
        # one shared decompressor body (ref.unpack_tile) for both grids.
        k = kref.unpack_tile(kp_ref[0], kb_ref[0], fields, spec,
                             rows=block_l, KH=KH, hd=hd,
                             prefix_planes=prefix_planes)
        v = kref.unpack_tile(vp_ref[0], vb_ref[0], fields, spec,
                             rows=block_l, KH=KH, hd=hd,
                             prefix_planes=prefix_planes)
        q = q_ref[0].astype(jnp.float32)

        s = jnp.einsum("hgd,lhd->hgl", q, k) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        # Masking is on *logical* slots: only the row's last live block
        # holds slots past pos here.
        slots = ki * block_l + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_l), 2)
        valid = kref.decode_kv_mask(pos, L, None, slots=slots)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jnp.einsum("hgl,lhd->hgd", p, v))
        m_scr[...] = m_new

    @pl.when(ki == nb - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fields", "softcap",
                                             "interpret", "prefix_planes"))
def paged_flash_decode(q: jax.Array, k_payload: jax.Array,
                       k_bases: jax.Array, v_payload: jax.Array,
                       v_bases: jax.Array, tables: jax.Array,
                       pos: jax.Array, layer: jax.Array, *,
                       fields: kref.PackFields,
                       softcap: Optional[float] = None,
                       interpret: Optional[bool] = None,
                       prefix_planes: Optional[int] = None) -> jax.Array:
    """One-token attention over one layer of a *paged* SFP-packed KV pool.

    The serving engine's continuous-batching decode step: pool parts are
    stacked over layers, (n_layers, P_blocks, block_l, D) payload /
    (n_layers, P_blocks, block_l, D // 128) bases shared by every request,
    and ``layer`` (an int32 scalar) picks the layer this call attends.
    ``tables`` (B, nb) int32 maps each batch row's logical KV blocks to
    physical pool blocks, and ``pos`` (B,) is each row's absolute decode
    position. The block table and the layer are scalar-prefetch operands,
    so the *gather happens inside the kernel grid*: each (b, j) step's
    index_map DMAs block ``tables[b, j]`` of layer ``layer`` straight from
    the HBM pool into VMEM — neither the layer's slice of the pool nor a
    contiguous per-request cache ever materializes, which is what lets a
    layer scan carry the stacked pool and update it in place. A pool of
    one layer is passed as ``part[None]`` with ``layer`` 0 (a bitcast).
    Logical blocks past a row's position are skipped: their steps re-point
    at the row's last live block, so they are neither fetched nor
    expanded, and the output is bit-identical to attending them masked.
    Their table entries must still be valid physical indices (the reserved
    trash block is one). Global attention only (local ring buffers are
    window-bounded and stay per-slot contiguous). Returns (B, 1, H, hd) in
    q's dtype.

    Oracle: ``ref.paged_flash_decode`` on the layer's slice — bit-exact in
    interpret mode, equal to f32 rounding when compiled for a TPU.
    """
    interpret = kref.default_interpret(interpret)

    B, one, H, hd = q.shape
    assert one == 1, q.shape
    assert k_payload.ndim == 4, k_payload.shape  # (layers, P, block_l, Dp)
    block_l, Dp = k_payload.shape[2:]
    G = k_bases.shape[3]
    D = G * kref.GROUP
    KH = D // hd
    assert KH * hd == D, (D, hd)
    assert Dp == fields.nd_payload_cols(D), (k_payload.shape, fields)
    rep = H // KH
    assert rep * KH == H, (H, KH)
    nb = tables.shape[1]
    spec = containers.spec_for(jnp.dtype(q.dtype))

    qg = q.reshape(B, KH, rep, hd)
    pos1 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    layer1 = jnp.asarray(layer, jnp.int32).reshape(1)
    tables = tables.astype(jnp.int32)
    scale = 1.0 / (hd ** 0.5)

    def kv_block(b, j, tab, pos, layer):
        # Steps past the row's last live block repeat its index, so the
        # pipeline issues no copy for them (the body skips them too).
        return (layer[0], tab[b, jnp.minimum(j, pos[b] // block_l)], 0, 0)

    def row(b, j, tab, pos, layer):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (tables, pos, layer) — for the index_maps
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, KH, rep, hd), row),
            pl.BlockSpec((None, 1, block_l, Dp), kv_block),
            pl.BlockSpec((None, 1, block_l, G), kv_block),
            pl.BlockSpec((None, 1, block_l, Dp), kv_block),
            pl.BlockSpec((None, 1, block_l, G), kv_block),
        ],
        out_specs=pl.BlockSpec((1, KH, rep, hd), row),
        scratch_shapes=[
            _vmem_scratch((KH, rep, 1)),
            _vmem_scratch((KH, rep, 1)),
            _vmem_scratch((KH, rep, hd)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_l=block_l, nb=nb, KH=KH,
                          hd=hd, softcap=softcap, scale=scale, fields=fields,
                          spec=spec, prefix_planes=prefix_planes),
        name="paged_flash_decode_" + _geometry(fields),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, rep, hd), q.dtype),
        interpret=interpret,
    )(tables, pos1, layer1, qg, k_payload, k_bases, v_payload, v_bases)
    return out.reshape(B, 1, H, hd)
