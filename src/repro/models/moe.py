"""Mixture-of-Experts FFN: top-k routing over every expert, computed for the
experts this chip holds.

The router keeps all ``n_experts`` outputs and its ``top_k``; under expert
parallelism (``cfg.expert_shards`` > 1) a layer holds one slice of the
experts and computes only their part of the result, which is what goes on
to the next layer (the absent slices' parts belong to other chips). An
always-on shared expert (``cfg.d_ff_shared``) is added once.

Two dispatch paths:

* ``moe_dropless`` (serving): every token-expert assignment to a held
  expert is computed. The assignments are sorted by expert and run as one
  grouped matmul over the held experts (``jax.lax.ragged_dot``); it also
  returns per-call counters (assignments that land on held experts, the
  largest load on one).
* ``moe_forward`` (training): segment-sum scatter into an (E, C, d)
  buffer grouped per batch row (T5X-style groups); assignments past
  capacity are dropped (tracked in aux metrics).

Aux losses: Switch-style load-balance + router z-loss.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common


def moe_init(p: common.ParamFactory, cfg: ArchConfig):
    d, E, ffe = cfg.d_model, cfg.experts_held, cfg.d_ff_expert
    params = {
        "router": p((d, cfg.n_experts), ("embed", "experts"),
                    dtype=jnp.float32),
        "w_in": p((E, d, ffe), ("experts", "embed", "expert_ff")),
        "w_out": p((E, ffe, d), ("experts", "expert_ff", "embed")),
    }
    if cfg.glu:
        params["w_gate"] = p((E, d, ffe), ("experts", "embed", "expert_ff"))
    if cfg.d_ff_shared:
        params["shared"] = common.mlp_init(p, d, cfg.d_ff_shared, cfg.glu)
    return params


def capacity_for(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(c, cfg.top_k)


def _route(params, h: jax.Array, cfg: ArchConfig):
    """(router logits, probs, renormalised top-k gates, top-k expert ids,
    ids relative to the held slice) for h (..., d)."""
    logits = h.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, expert_idx, expert_idx - cfg.expert_lo


def _aux(logits, probs, expert_idx, cfg: ArchConfig, dropped):
    E = cfg.n_experts
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32).reshape(-1, E),
        axis=0)
    return {"moe_lb_loss": E * jnp.sum(me * ce),
            "moe_z_loss": jnp.mean(
                jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
            "moe_drop_frac": dropped}


def _shared(params, h, out, cfg: ArchConfig):
    if not cfg.d_ff_shared:
        return out
    return out + common.mlp(params["shared"], h, cfg.act, cfg.glu)


def moe_dropless(params, h: jax.Array, cfg: ArchConfig
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """h: (B, S, d) -> (B, S, d) with no assignment dropped, and counters:
    ``held`` (assignments that land on held experts) and ``max_load``
    (the most assignments one held expert got), int32 scalars."""
    B, S, d = h.shape
    T, K, E = B * S, cfg.top_k, cfg.experts_held
    x = h.reshape(T, d)
    logits, probs, gate_vals, expert_idx, local = _route(params, x, cfg)
    held = (local >= 0) & (local < E)
    # Sort the T*K assignments by held expert; the rest (other chips'
    # experts) sort last, past every group, and the grouped matmul leaves
    # their rows at zero.
    key = jnp.where(held, local, E).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
    xs = x[order // K]
    a = jax.lax.ragged_dot(xs, params["w_in"], sizes)
    a = common.activation(cfg.act)(a.astype(jnp.float32)).astype(h.dtype)
    if cfg.glu:
        a = a * jax.lax.ragged_dot(xs, params["w_gate"], sizes)
    ys = jax.lax.ragged_dot(a, params["w_out"], sizes)
    # back to (token, slot) order, weighted by the gates and summed
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * K, dtype=order.dtype))
    y = jnp.where(held[..., None], ys[inv].reshape(T, K, d), 0)
    out = jnp.einsum("tkd,tk->td", y, gate_vals.astype(y.dtype),
                     preferred_element_type=jnp.float32).astype(h.dtype)
    out = _shared(params, x, out, cfg).reshape(B, S, d)
    stats = {"held": jnp.sum(sizes), "max_load": jnp.max(sizes)}
    return out, stats


def moe_forward(params, h: jax.Array, cfg: ArchConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """h: (B, S, d) -> (B, S, d), aux metrics/losses; assignments past
    capacity are dropped."""
    B, S, d = h.shape
    E, K = cfg.experts_held, cfg.top_k
    C = capacity_for(cfg, S)

    logits, probs, gate_vals, expert_idx, local = _route(params, h, cfg)

    # Position of each (token, slot) within its expert, slot-major order;
    # an expert held elsewhere has an all-zero one-hot row.
    onehot = jax.nn.one_hot(local, E, dtype=jnp.int32)       # (B, S, K, E)
    flat = onehot.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                    # exclusive
    pos = jnp.sum(pos.reshape(B, S, K, E) * onehot, axis=-1)  # (B, S, K)
    held = (local >= 0) & (local < E)
    keep = (pos < C) & held

    # Scatter tokens into the (E*C, d) buffer per batch row.
    seg_ids = jnp.where(keep, local * C + pos, E * C)        # overflow -> drop
    data = jnp.broadcast_to(h[:, :, None, :], (B, S, K, d)).reshape(B, S * K, d)
    seg_flat = seg_ids.reshape(B, S * K)

    def scatter_row(row_data, row_ids):
        return jax.ops.segment_sum(row_data, row_ids, num_segments=E * C + 1)

    buf = jax.vmap(scatter_row)(data, seg_flat)[:, : E * C, :]
    buf = buf.reshape(B, E, C, d).astype(h.dtype)

    # Expert FFN (E sharded over `model`).
    inner = jnp.einsum("becd,edf->becf", buf, params["w_in"])
    a = common.activation(cfg.act)(inner.astype(jnp.float32)).astype(h.dtype)
    if cfg.glu:
        a = a * jnp.einsum("becd,edf->becf", buf, params["w_gate"])
    out_buf = jnp.einsum("becf,efd->becd", a, params["w_out"])

    # Gather back and combine with gate weights.
    out_flat = out_buf.reshape(B, E * C, d)
    gathered = jnp.take_along_axis(
        out_flat, jnp.minimum(seg_flat, E * C - 1)[..., None], axis=1)
    gathered = gathered.reshape(B, S, K, d) * (
        gate_vals * keep.astype(jnp.float32))[..., None].astype(h.dtype)
    out = _shared(params, h, jnp.sum(gathered, axis=2), cfg)

    # the share of the held experts' assignments past capacity
    dropped = 1.0 - jnp.sum(keep) / jnp.maximum(jnp.sum(held), 1)
    return out, _aux(logits, probs, expert_idx, cfg, dropped)
