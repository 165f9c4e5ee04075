"""Codec-compressed KV cache (beyond-paper application of the containers).

Decode is memory-bandwidth-bound by the KV cache read — exactly the regime
the paper targets at the DRAM interface. The cache stores the packed
representation of whichever registry codec the caller picks (default: the
paper's sfp8 container — 1 sign + 4 delta-exp + 3 mantissa per value, one
shared base exponent per 128 lanes); each decode step packs only the new
token's K/V row. Cache bytes drop ~2x vs bf16 at <= 3 mantissa bits of
precision, matching where Quantum Mantissa lands (paper Fig 4).

Decompression lives at the consumer: for SFP codecs on the pallas or
interpret backends, attention reads the packed (payload, bases) pair
directly through the fused decompress-attend kernel
(kernels/packed_flash_decode.py) — the bf16 cache never materializes in
HBM, so the byte win is also an HBM-traffic win per step. Codecs without a
fixed-width payload geometry (bit_exact, gecko8) and the ref backend fall
back to decompressing the whole cache and attending over it.

All container specifics live behind repro.codecs: this module only splices
packed parts along the sequence axis, so any codec whose parts carry
(batch, seq, ...) leading dims — every fixed-width registry codec — works
unchanged.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import codecs
from repro.configs.base import ArchConfig, LOCAL
from repro.distributed import sharding as shd
from repro.kernels import ops
from repro.models import attention


class PackedKV(NamedTuple):
    k: codecs.PackedTensor  # parts shaped (B, L, ...), D = KH * head_dim
    v: codecs.PackedTensor


def cache_len(cfg: ArchConfig, kind: str, max_len: int) -> int:
    """Packed-cache sequence allocation for a logical budget ``max_len``.

    Lengths past one kernel block round up to a block multiple so the
    fused flash-decode grid always gets full blocks (its no-pad blocking
    shrinks to a divisor of L otherwise — pathological for awkward L).
    Extra slots are dead weight only: masked out when unwritten (global)
    or ring slack beyond the window (local; the modulus is the allocated
    length everywhere, so splice and validity stay consistent).
    """
    L = min(max_len, cfg.window) if kind == LOCAL else max_len
    block = ops.DECODE_BLOCK_L
    if L > block:
        L = -(-L // block) * block
    return L


def _dims(cfg: ArchConfig, kind: str, max_len: int):
    D = cfg.n_kv_heads * cfg.head_dim_
    assert D % 128 == 0, (D, "KV feature dim must align to 128 lanes")
    return D, cache_len(cfg, kind, max_len)


def _codec(container: Optional[str]) -> codecs.Codec:
    return codecs.get(container or codecs.DEFAULT_CONTAINER)


def packed_cache_spec(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                      container: Optional[str] = None) -> PackedKV:
    D, L = _dims(cfg, kind, max_len)
    spec = _codec(container).packed_spec((batch, L, D), cfg.compute_dtype)
    return PackedKV(k=spec, v=spec)


def packed_cache_init(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                      container: Optional[str] = None) -> PackedKV:
    spec = packed_cache_spec(cfg, kind, batch, max_len, container)
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    return zeros


def packed_cache_axes(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                      container: Optional[str] = None) -> PackedKV:
    """Logical sharding axes: every packed part is (batch, seq, ...)."""
    spec = packed_cache_spec(cfg, kind, batch, max_len, container)
    return jax.tree.map(
        lambda s: ("batch", "cache_seq") + (None,) * (len(s.shape) - 2), spec)


def _splice(cache_pt: codecs.PackedTensor, new_pt: codecs.PackedTensor,
            slot) -> codecs.PackedTensor:
    """Write one packed token row into the ring buffer (every part shares
    the sequence axis at dim 1).

    ``slot`` is a scalar (whole batch writes one slot) or (B,) — one slot
    per batch row (continuous-batching decode, rows at distinct
    positions).
    """
    if jnp.ndim(slot) == 0:
        data = {
            k: jax.lax.dynamic_update_slice_in_dim(
                cache_pt.data[k], new_pt.data[k], slot, axis=1)
            for k in cache_pt.data
        }
    else:
        rows = jnp.arange(slot.shape[0])
        data = {k: cache_pt.data[k].at[rows, slot].set(new_pt.data[k][:, 0])
                for k in cache_pt.data}
    return codecs.PackedTensor(cache_pt.codec, cache_pt.shape,
                               cache_pt.dtype, data)


def attention_decode_packed(params, h_tok: jax.Array, cache: PackedKV,
                            pos: jax.Array, cfg: ArchConfig, *, kind: str,
                            container: Optional[str] = None,
                            prefix_planes: Optional[int] = None
                            ) -> Tuple[jax.Array, PackedKV]:
    """One-token decode over the compressed cache.

    Fusion applies when the codec exposes a fixed-width payload geometry
    (``pack_fields`` — the SFP containers) and the backend runs Pallas
    kernels (pallas on TPU, interpret in tests): attention then consumes
    the packed (payload, bases) pair directly and the decompressed cache
    never exists in HBM. Otherwise — bit_exact/gecko8, or the ref
    backend — the whole cache is decompressed first and attended with
    ``decode_attend`` (both paths share the ring-slot semantics of
    ``ops.decode_kv_mask``).

    ``prefix_planes`` (speculative draft steps) makes the attention *read*
    expand only the leading P' payload bits of the packed cache
    (``ops.prefix_fields``); the write path is unchanged — drafts append
    full-width rows, so the cache bytes a later verify reads are identical.
    Requires a fixed-width geometry (``pack_fields``).
    """
    codec = _codec(container)
    B = h_tok.shape[0]
    hd, H, KH = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    D = KH * hd
    L = cache.k.shape[1]
    dtype = h_tok.dtype

    # pos: scalar (shared decode position) or (B,) per-row positions
    # (continuous-batching slots).
    positions = (jnp.full((1,), pos, jnp.int32) if jnp.ndim(pos) == 0
                 else jnp.asarray(pos, jnp.int32)[:, None])
    q, k_new, v_new = attention._project_qkv(params, h_tok, cfg, positions)
    # As in attention_decode: the new token's K/V must arrive replicated
    # over `model` (the packed cache shards its L dim there), or GSPMD
    # reshards the whole ring buffer on every splice.
    if shd.active_mesh() is not None:
        b = shd.batch_axis_for(shd.active_mesh(), B)
        k_new = shd.hint(k_new, b, None, None, None)
        v_new = shd.hint(v_new, b, None, None, None)
        q = shd.hint(q, b, None, None, None)
    slot = attention.decode_slot_index(pos, L, kind)

    # Pack only the new token's row and splice it in.
    k_pt = _splice(cache.k, codec.pack(k_new.reshape(B, 1, D).astype(dtype)),
                   slot)
    v_pt = _splice(cache.v, codec.pack(v_new.reshape(B, 1, D).astype(dtype)),
                   slot)

    fields = codec.pack_fields(dtype)
    if prefix_planes is not None and fields is None:
        raise ValueError(f"prefix_planes needs a fixed-width payload "
                         f"geometry; codec {codec.name!r} has none")
    if fields is not None and (prefix_planes is not None
                               or ops.backend() in ("pallas", "interpret")):
        # Fused decompress-attend: the packed pair is the attention input.
        # Draft (prefix) reads take this path on every backend — the ref
        # oracle implements the same truncated-geometry expansion.
        window = cfg.window if kind == LOCAL else None
        o = ops.packed_flash_decode(
            q.astype(dtype),
            ops.Packed(payload=k_pt.data["payload"],
                       bases=k_pt.data["bases"]),
            ops.Packed(payload=v_pt.data["payload"],
                       bases=v_pt.data["bases"]),
            pos, fields=fields, window=window, softcap=cfg.attn_softcap,
            prefix_planes=prefix_planes)
    else:
        # Fallback: decompress the whole cache, then attend over it.
        k_c = codec.unpack(k_pt).reshape(B, L, KH, hd)
        v_c = codec.unpack(v_pt).reshape(B, L, KH, hd)
        o = attention.decode_attend(q, k_c, v_c, pos, cfg, kind)
    out = o.reshape(B, 1, H * hd) @ params["wo"]
    return out, PackedKV(k=k_pt, v=v_pt)


def pack_prefill_cache(cache_kv: attention.KVCache,
                       container: Optional[str] = None) -> PackedKV:
    """Compress a prefill-produced bf16 cache in one shot."""
    codec = _codec(container)
    B, L, KH, hd = cache_kv.k.shape
    return PackedKV(k=codec.pack(cache_kv.k.reshape(B, L, KH * hd)),
                    v=codec.pack(cache_kv.v.reshape(B, L, KH * hd)))


# ---------------------------------------------------------------------------
# Paged pool attention (continuous-batching serving engine)
# ---------------------------------------------------------------------------


class PagedKV(NamedTuple):
    """The packed block pool of one global-attention layer, or of several
    stacked on a leading layer axis.

    Physical blocks shared by every request: payload
    (P_blocks, block_l, fields.nd_payload_cols(D)) — 8/16-bit words, or
    uint8 bit planes for dense sub-byte geometries — and bases
    (P_blocks, block_l, D // 128) uint8 in the ``sfp_pack_nd`` /
    ``bitplane_pack_nd`` layout; the engine stacks the scanned layers'
    pools as (n_periods, P_blocks, ...). Which blocks belong to which
    request lives outside, in the engine's block tables — the pool itself
    is request-agnostic, which is what lets freed blocks recycle instantly.
    """

    k_payload: jax.Array
    k_bases: jax.Array
    v_payload: jax.Array
    v_bases: jax.Array


class PoolLayer(NamedTuple):
    """One global-attention layer's place in a stacked pool: ``pool``
    (``PagedKV`` parts (n_layers, P_blocks, block_l, cols)) and ``layer``,
    an int32 index on its leading axis. A decode step's layer scan carries
    the stacked pool and hands each layer this view, so the layer writes
    and reads its blocks in the one buffer instead of a slice of it."""

    pool: PagedKV
    layer: jax.Array


def _paged_fields(cfg: ArchConfig, container: Optional[str]):
    codec = _codec(container)
    fields = codec.pack_fields(cfg.compute_dtype)
    if fields is None:
        raise ValueError(
            f"paged KV pools need a fixed-width payload geometry; codec "
            f"{codec.name!r} has none (pack_fields() is None)")
    return fields


def paged_block_bytes(cfg: ArchConfig, block_l: int,
                      container: Optional[str] = None) -> int:
    """Dense-packed bytes one physical block occupies for *one* layer:
    K + V payload (words or bit planes) plus the shared group bases.
    This is the unit the pool's admission accounting is measured in."""
    fields = _paged_fields(cfg, container)
    D = cfg.n_kv_heads * cfg.head_dim_
    row = (fields.nd_payload_cols(D)
           * jnp.dtype(fields.payload_dtype).itemsize + D // 128)
    return 2 * block_l * row


def paged_block_spec(cfg: ArchConfig, num_blocks: int, block_l: int,
                     container: Optional[str] = None) -> PagedKV:
    """ShapeDtypeStruct skeleton of one layer's pool slice."""
    D = cfg.n_kv_heads * cfg.head_dim_
    assert D % 128 == 0, (D, "KV feature dim must align to 128 lanes")
    fields = _paged_fields(cfg, container)
    pd = jnp.dtype(fields.payload_dtype)
    payload = jax.ShapeDtypeStruct(
        (num_blocks, block_l, fields.nd_payload_cols(D)), pd)
    bases = jax.ShapeDtypeStruct((num_blocks, block_l, D // 128), jnp.uint8)
    return PagedKV(k_payload=payload, k_bases=bases,
                   v_payload=payload, v_bases=bases)


def paged_block_init(cfg: ArchConfig, num_blocks: int, block_l: int,
                     container: Optional[str] = None) -> PagedKV:
    spec = paged_block_spec(cfg, num_blocks, block_l, container)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def paged_block_checksums(paged: PagedKV, salt: int = 0) -> jax.Array:
    """Cheap per-physical-block integrity checksum over the packed planes.

    Returns (P,) uint32 — one checksum per physical block, covering K and
    V payload (words or bit planes) and the shared group bases. The hash
    is a position-weighted wraparound sum: each flattened element is
    multiplied by an odd per-position constant (Knuth multiplicative
    step), so any single bit flip changes the block's sum (odd weight
    times a power of two is never 0 mod 2^32), and swapped rows/columns
    do not cancel. ``salt`` decorrelates the K/V/payload/bases streams
    and the per-layer contributions summed by the engine.

    Arrays may carry a leading layer dim ((n_periods, P, ...)): layer
    contributions fold into the same per-block sum. This is the
    "computed at pack/insert, verified on gather" primitive behind the
    serving engine's block quarantine (see serve/faults.py).
    """

    def one(arr: jax.Array, s: int) -> jax.Array:
        a = arr.astype(jnp.uint32)
        if a.ndim == 4:                      # (layers, P, block_l, cols)
            a = jnp.moveaxis(a, 1, 0)
        a = a.reshape(a.shape[0], -1)        # (P, flat)
        n = a.shape[1]
        w = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)
             + jnp.uint32(s & 0xFFFFFFFF)) | jnp.uint32(1)
        return jnp.sum(a * w[None, :], axis=1, dtype=jnp.uint32)

    total = jnp.uint32(0)
    for i, arr in enumerate(paged):
        total = total + one(arr, salt + 0x9E3779B9 * (i + 1))
    return total


def attention_decode_paged(params, h_tok: jax.Array, paged: PoolLayer,
                           tables: jax.Array, pos: jax.Array,
                           cfg: ArchConfig, *,
                           container: Optional[str] = None,
                           prefix_planes: Optional[int] = None
                           ) -> Tuple[jax.Array, PagedKV]:
    """One continuous-batching decode step over the paged block pool.

    ``paged`` is the layer's place in the stacked pool; the updated
    stacked pool is returned. ``tables`` (B, nb) int32 maps each batch
    row's logical KV blocks to physical pool blocks; ``pos`` (B,) is each
    row's absolute decode position. The new token's K/V row is packed and
    scattered into the row's current block of this layer (idle rows must
    point at the reserved trash block) — inside a layer scan that carries
    the donated pool, XLA does this in place — then attention reads the
    pool directly through the paged flash-decode kernel, the gather
    happening inside the kernel grid via the scalar-prefetched block table
    and layer. Global attention only (local ring buffers are
    window-bounded and stay per-slot contiguous). The pool is a
    single-host structure; multi-host pool sharding is future work.
    ``prefix_planes`` (speculative draft steps) expands only the leading
    P' payload bits on the read side; writes stay full width.
    """
    codec = _codec(container)
    B = h_tok.shape[0]
    hd, H, KH = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    D = KH * hd
    pool, layer = paged
    block_l = pool.k_payload.shape[2]
    dtype = h_tok.dtype
    fields = codec.pack_fields(dtype)
    assert fields is not None, codec.name

    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = attention._project_qkv(params, h_tok, cfg,
                                             pos[:, None])

    # Pack only the new rows, then scatter each into its block slot.
    k_pt = codec.pack(k_new.reshape(B, 1, D).astype(dtype))
    v_pt = codec.pack(v_new.reshape(B, 1, D).astype(dtype))
    rows = jnp.arange(B)
    phys = tables[rows, pos // block_l]
    off = pos % block_l
    at = (layer, phys, off)
    pool = PagedKV(
        k_payload=pool.k_payload.at[at].set(k_pt.data["payload"][:, 0]),
        k_bases=pool.k_bases.at[at].set(k_pt.data["bases"][:, 0]),
        v_payload=pool.v_payload.at[at].set(v_pt.data["payload"][:, 0]),
        v_bases=pool.v_bases.at[at].set(v_pt.data["bases"][:, 0]))

    o = ops.paged_flash_decode(
        q.astype(dtype),
        ops.Packed(payload=pool.k_payload, bases=pool.k_bases),
        ops.Packed(payload=pool.v_payload, bases=pool.v_bases),
        tables, pos, layer, fields=fields, softcap=cfg.attn_softcap,
        prefix_planes=prefix_planes)
    out = o.reshape(B, 1, H * hd) @ params["wo"]
    return out, pool
