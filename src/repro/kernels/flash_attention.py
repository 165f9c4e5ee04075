"""Pallas TPU kernel: online-softmax (flash) attention.

The compute hot-spot the compressed KV cache and activation stash feed
into. Supports causal masking, sliding windows (gemma local layers), logit
soft-capping (gemma2) and native GQA via folded q-head groups (``q_rep``)
— K/V are never repeated to the full q-head count.

Grid is (batch*heads, q_blocks, kv_blocks) with the kv index innermost; a
VMEM scratch accumulator carries the running (max, denominator, numerator)
across kv blocks — the standard TPU flash schedule, sized so one
(block_q x d) + (block_k x d) working set fits VMEM with MXU-aligned dims
(multiples of 128).

Oracle: repro.kernels.ref.attention. Validated in interpret mode on CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import NEG_INF, default_interpret


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, seq_k: int, causal: bool,
                  window: Optional[int], softcap: Optional[float],
                  scale: float, q_rep: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (block_q, d)
    k = k_ref[0].astype(jnp.float32)  # (block_k, d)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    # GQA folding: q_rep consecutive query rows are the head group of one
    # logical sequence position, so their causal position is row // q_rep.
    q_pos = (qi * block_q
             + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)) // q_rep
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < seq_k
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "q_rep", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, q_rep: int = 1,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention over (B, S, H, D); K/V carry the same head count.

    GQA callers fold the q-head group into the query rows instead of
    repeating K/V: pass q as (B, Sq*q_rep, KH, D) with rows ordered
    (seq, group member) and ``q_rep = H // KH`` — the kernel then derives
    the causal position of row r as r // q_rep, and each KV block is
    streamed once per head group (see kernels.ops.attention).
    """
    interpret = default_interpret(interpret)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    assert k.shape == (B, Sk, H, D) and v.shape == (B, Sk, H, D)
    assert Sq % q_rep == 0, (Sq, q_rep)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    q_pad = (-Sq) % block_q
    k_pad = (-Sk) % block_k

    # (B*H, S, D) layout: one grid row per (batch, head).
    qt = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * H, Sk, D)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * H, Sk, D)
    if q_pad:
        qt = jnp.pad(qt, ((0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        kt = jnp.pad(kt, ((0, 0), (0, k_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, k_pad), (0, 0)))

    grid = (B * H, qt.shape[1] // block_q, kt.shape[1] // block_k)
    scale = 1.0 / (D ** 0.5)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          seq_k=Sk, causal=causal, window=window,
                          softcap=softcap, scale=scale, q_rep=q_rep),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            _vmem_scratch((block_q, 1)),
            _vmem_scratch((block_q, 1)),
            _vmem_scratch((block_q, D)),
        ],
        interpret=interpret,
    )(qt, kt, vt)

    if q_pad:
        out = out[:, :Sq]
    return jnp.moveaxis(out.reshape(B, H, Sq, D), 1, 2)


def _vmem_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)
