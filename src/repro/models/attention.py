"""GQA attention blocks: global / sliding-window, train + decode paths.

Training / prefill use a two-level chunked online-softmax schedule (outer
scan over query chunks, inner scan over key chunks) so no S x S tensor is
ever materialized; *local* layers slice only the key band inside the
window, so their FLOPs scale with `window`, not with sequence length.
Decode attends the whole (possibly ring-buffered) cache in one einsum —
scan-over-layers bounds the transient.

On TPU the inner loop is replaced by the Pallas flash kernel via
kernels.ops.attention (prefill fast path).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.kernels import ops
from repro.models import common

NEG_INF = -1e30


def attn_init(p: common.ParamFactory, cfg: ArchConfig):
    d, hd = cfg.d_model, cfg.head_dim_
    H, KH = cfg.n_heads, cfg.n_kv_heads
    params = {
        "wq": p((d, H * hd), ("embed", "heads")),
        "wk": p((d, KH * hd), ("embed", "heads")),
        "wv": p((d, KH * hd), ("embed", "heads")),
        "wo": p((H * hd, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        params["q_norm"] = common.rmsnorm_init(p, hd, axis="norm")
        params["k_norm"] = common.rmsnorm_init(p, hd, axis="norm")
    return params


def _qkv_specs(cfg: ArchConfig, batch_size: int):
    """Explicit activation shardings for (q, k, v): heads over `model` when
    divisible, replicated otherwise. Without these, GSPMD resolves the
    (fused-dim sharded) reshape against downstream uses by replicating
    whole tensors — including the KV cache, once per decode step."""
    mesh = shd.active_mesh()
    if mesh is None:
        return None, None, None
    tp = shd.model_axis_size(mesh)
    b = shd.batch_axis_for(mesh, batch_size)
    target = shd.heads_target()
    hq = target if (target and cfg.n_heads % tp == 0) else None
    hkv = target if (target and cfg.n_kv_heads % tp == 0) else None
    return (b, None, hq, None), (b, None, hkv, None), b


def _project_qkv(params, h, cfg: ArchConfig, positions):
    B, S, _ = h.shape
    hd, H, KH = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    if cfg.attn_scale is None:
        q = h @ params["wq"]
    else:
        # Every attention path scales scores by 1/sqrt(hd); a configured
        # scale is folded into q in f32, before q's one rounding.
        q = (jnp.dot(h, params["wq"], preferred_element_type=jnp.float32)
             * (cfg.attn_scale * hd ** 0.5)).astype(h.dtype)
    q = q.reshape(B, S, H, hd)
    k = (h @ params["wk"]).reshape(B, S, KH, hd)
    v = (h @ params["wv"]).reshape(B, S, KH, hd)
    q_spec, kv_spec, _ = _qkv_specs(cfg, B)
    if q_spec is not None:
        q = shd.hint(q, *q_spec)
        k = shd.hint(k, *kv_spec)
        v = shd.hint(v, *kv_spec)
    if cfg.qk_norm:
        q = common.rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = common.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.use_rope:
        q = common.rope(q, positions, cfg.rope_theta)
        k = common.rope(k, positions, cfg.rope_theta)
    if q_spec is not None:
        q = shd.hint(q, *q_spec)
        k = shd.hint(k, *kv_spec)
    return q, k, v


def _chunk_attend(q, k, v, q_pos, k_pos, *, softcap, scale,
                  carry, prefix_len: int = 0):
    """One online-softmax update. q:(B,cq,H,hd) k/v:(B,ck,KH,hd).

    bf16 contractions with fp32 accumulation (preferred_element_type);
    GQA via grouped einsum — no repeated-KV materialization."""
    m, l, acc = carry
    B, cq, H, hd = q.shape
    KH = k.shape[2]
    rep = H // KH
    qg = q.reshape(B, cq, KH, rep, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(B, H, cq, -1)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = k_pos[None, :] <= q_pos[:, None]
    if prefix_len > 0:
        mask = mask | (k_pos[None, :] < prefix_len)
    mask = mask & (k_pos[None, :] >= 0)
    s = jnp.where(mask[None, None], s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pg = p.reshape(B, KH, rep, cq, -1).astype(v.dtype)
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", pg, v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha[..., None] + pv.reshape(B, H, cq, hd)
    return m_new, l_new, acc_new


def ring_pack_kv(k: jax.Array, v: jax.Array, L: int):
    """Pack full-sequence K/V (B, S, KH, hd) into an L-slot ring cache.

    Slot s receives the latest position p <= S-1 with p === s (mod L);
    unwritten slots (S < L) are left as position p = s (the decode-side
    validity mask handles them: those slots simply equal position s which
    is either the true value or zero-init garbage masked by k_pos <= pos).
    """
    S = k.shape[1]
    slots = jnp.arange(L)
    p = (S - 1) - jnp.mod(S - 1 - slots, L)
    p = jnp.clip(p, 0, S - 1)
    return jnp.take(k, p, axis=1), jnp.take(v, p, axis=1)


def attention_train(params, h: jax.Array, cfg: ArchConfig, *, kind: str,
                    positions: jax.Array, prefix_len: int = 0,
                    chunk: int = 512, return_kv: bool = False):
    """Full-sequence attention (train / prefill). h: (B, S, d)."""
    B, S, d = h.shape
    hd, H = cfg.head_dim_, cfg.n_heads
    window = cfg.window if kind == "local" else None
    q, k, v = _project_qkv(params, h, cfg, positions)

    def _finish(out):
        out = out.reshape(B, S, H * hd) @ params["wo"]
        if return_kv:
            return out, (k, v)
        return out

    if S <= 2 * chunk or (prefix_len > 0 and prefix_len > chunk):
        # Small sequences / prefix-LM: single oracle call (O(S^2) but tiny,
        # or prefix archs whose S is bounded by the training shapes).
        out = ops.attention(q, k, v, causal=True, window=window,
                            softcap=cfg.attn_softcap, prefix_len=prefix_len)
        return _finish(out)

    # largest chunk <= `chunk` that divides S (prefix-LM totals like
    # 4096+256 are not powers of two); tiny remainders fall back to oracle.
    cq = min(chunk, S)
    while cq > 32 and S % cq != 0:
        cq -= 32
    if S % cq != 0:
        out = ops.attention(q, k, v, causal=True, window=window,
                            softcap=cfg.attn_softcap, prefix_len=prefix_len)
        return _finish(out)
    n_q = S // cq
    scale = 1.0 / (hd ** 0.5)

    if window is not None:
        # Banded local attention: each q chunk sees only [start, start+band).
        band = min(((window + cq - 1) // cq + 1) * cq, S)

        def q_step_local(_, qi):
            q_c = jax.lax.dynamic_slice_in_dim(q, qi * cq, cq, axis=1)
            start = jnp.maximum(qi * cq + cq - band, 0)
            k_c = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            q_pos = qi * cq + jnp.arange(cq)
            k_abs = start + jnp.arange(band)
            k_pos = jnp.where(
                (k_abs[None] > q_pos[:, None] - window)
                & (k_abs[None] <= q_pos[:, None]),
                k_abs[None], -jnp.ones_like(k_abs)[None])
            m = jnp.full((B, H, cq), NEG_INF, jnp.float32)
            l = jnp.zeros((B, H, cq), jnp.float32)
            acc = jnp.zeros((B, H, cq, hd), jnp.float32)
            # collapse per-q-row masks: use per-row k_pos by masking in attend
            s_mask = k_pos >= 0
            rep = H // cfg.n_kv_heads
            qg = q_c.reshape(B, cq, cfg.n_kv_heads, rep, hd)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_c,
                           preferred_element_type=jnp.float32) * scale
            s = s.reshape(B, H, cq, -1)
            if cfg.attn_softcap is not None:
                s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
            s = jnp.where(s_mask[None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            pg = p.reshape(B, cfg.n_kv_heads, rep, cq, -1).astype(v_c.dtype)
            o = jnp.einsum("bhgqk,bkhd->bqhgd", pg, v_c,
                           preferred_element_type=jnp.float32)
            o = o.reshape(B, cq, H, hd)
            return None, o.astype(h.dtype)

        _, outs = jax.lax.scan(jax.checkpoint(q_step_local), None,
                               jnp.arange(n_q))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)
    else:
        ck = min(chunk, S)
        n_k = S // ck

        def q_step(_, qi):
            q_c = jax.lax.dynamic_slice_in_dim(q, qi * cq, cq, axis=1)
            q_pos = qi * cq + jnp.arange(cq)

            @jax.checkpoint
            def k_step_inner(carry, ki):
                k_c = jax.lax.dynamic_slice_in_dim(k, ki * ck, ck, axis=1)
                v_c = jax.lax.dynamic_slice_in_dim(v, ki * ck, ck, axis=1)
                k_pos = ki * ck + jnp.arange(ck)
                return _chunk_attend(q_c, k_c, v_c, q_pos, k_pos,
                                     softcap=cfg.attn_softcap, scale=scale,
                                     carry=carry)

            def k_step(carry, ki):
                return k_step_inner(carry, ki), None

            m = jnp.full((B, H, cq), NEG_INF, jnp.float32)
            l = jnp.zeros((B, H, cq), jnp.float32)
            acc = jnp.zeros((B, H, cq, hd), jnp.float32)
            # causal: only key chunks up to this query chunk contribute.
            n_rel = qi + 1

            def masked_k_step(carry, ki):
                new_carry, _ = k_step(carry, ki)
                keep = ki < n_rel
                carry = jax.tree.map(
                    lambda a, b: jnp.where(keep, a, b), new_carry, carry)
                return carry, None


            (m, l, acc), _ = jax.lax.scan(masked_k_step, (m, l, acc),
                                          jnp.arange(n_k))
            o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(h.dtype)
            return None, o

        _, outs = jax.lax.scan(jax.checkpoint(q_step), None, jnp.arange(n_q))
        out = jnp.moveaxis(outs, 0, 1)  # (B, nq, H, cq, hd) -> fix below
        out = out.transpose(0, 1, 3, 2, 4).reshape(B, S, H, hd)

    return _finish(out)


# ---------------------------------------------------------------------------
# Decode path with (ring-buffered) KV cache
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array  # (B, L, KH, hd) bf16 — L = S_max (global) or window (local)
    v: jax.Array


def cache_init(cfg: ArchConfig, kind: str, batch: int, max_len: int,
               dtype) -> KVCache:
    L = min(max_len, cfg.window) if kind == "local" else max_len
    shape = (batch, L, cfg.n_kv_heads, cfg.head_dim_)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def cache_spec(cfg: ArchConfig, kind: str, batch: int, max_len: int, dtype):
    L = min(max_len, cfg.window) if kind == "local" else max_len
    shape = (batch, L, cfg.n_kv_heads, cfg.head_dim_)
    return KVCache(k=jax.ShapeDtypeStruct(shape, dtype),
                   v=jax.ShapeDtypeStruct(shape, dtype))


def decode_attend(q: jax.Array, k_c: jax.Array, v_c: jax.Array,
                  pos: jax.Array, cfg: ArchConfig, kind: str) -> jax.Array:
    """Attend one query token over a (ring-buffered) cache. Returns
    (B, 1, H, hd) output (pre-wo).

    The cache stays in bf16 through the contractions
    (preferred_element_type=f32 accumulates exactly) — casting it up front
    would double the dominant HBM read of the decode step. GQA uses a
    grouped einsum instead of materializing repeated KV heads.
    """
    B = q.shape[0]
    hd, H, KH = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    L = k_c.shape[1]
    window = cfg.window if kind == "local" else None

    # Ring-slot validity shared with the packed flash-decode kernel, so the
    # fused and unpack-fallback decode paths agree on cache semantics.
    # ``pos`` may be scalar or (B,) — continuous-batching slots each sit at
    # their own decode position.
    if jnp.ndim(pos) == 0:
        valid = ops.decode_kv_mask(pos, L, window)[None]          # (1, L)
    else:
        valid = ops.decode_kv_mask(pos[:, None], L, window)       # (B, L)

    rep = H // KH
    qg = q.reshape(B, 1, KH, rep, hd)
    scale = 1.0 / (hd ** 0.5)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_c,
                   preferred_element_type=jnp.float32) * scale
    if cfg.attn_softcap is not None:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(k_c.dtype), v_c,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def decode_slot_index(pos: jax.Array, L: int, kind: str) -> jax.Array:
    return jnp.mod(pos, L) if kind == "local" else pos


def attention_decode(params, h_tok: jax.Array, cache: KVCache,
                     pos: jax.Array, cfg: ArchConfig, *, kind: str,
                     ) -> Tuple[jax.Array, KVCache]:
    """One-token decode. h_tok: (B, 1, d); pos: scalar int32 (current index)."""
    B = h_tok.shape[0]
    hd, H = cfg.head_dim_, cfg.n_heads
    L = cache.k.shape[1]

    q, k_new, v_new = _project_qkv(params, h_tok, cfg,
                                   jnp.full((1,), pos, jnp.int32))
    # New-token K/V must arrive replicated over `model` (the cache shards
    # its L dim there); otherwise GSPMD reshards the whole cache per step.
    b = (shd.batch_axis_for(shd.active_mesh(), B)
         if shd.active_mesh() is not None else None)
    if shd.active_mesh() is not None:
        k_new = shd.hint(k_new, b, None, None, None)
        v_new = shd.hint(v_new, b, None, None, None)
        q = shd.hint(q, b, None, None, None)
    slot = decode_slot_index(pos, L, kind)
    k_c = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype),
                                              slot, axis=1)
    v_c = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype),
                                              slot, axis=1)
    o = decode_attend(q, k_c, v_c, pos, cfg, kind)
    out = o.reshape(B, 1, H * hd) @ params["wo"]
    return out, KVCache(k=k_c, v=v_c)
