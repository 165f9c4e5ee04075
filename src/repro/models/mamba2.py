"""Mamba-2 SSD block (state-space duality, arXiv:2405.21060).

Chunked SSD forward: within chunks the recurrence is computed as a masked
quadratic attention-like product; across chunks a linear scan carries the
(H, P, N) state. Decode is the pure recurrence (constant state — no KV
cache), which is what makes long_500k tractable for this family.

Shapes follow the "minimal mamba2" formulation:
  x:  (B, S, H, P)   P = ssm_head_dim, H = d_inner / P
  dt: (B, S, H)      softplus(dt_raw + dt_bias)
  B,C:(B, S, G, N)   G = ssm_groups (broadcast to H), N = ssm_state
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common


def ssd_init(p: common.ParamFactory, cfg: ArchConfig):
    d, di = cfg.d_model, cfg.d_inner
    G, N, H = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    cw = cfg.conv_width
    return {
        "w_x": p((d, di), ("embed", "ssm_inner")),
        "w_z": p((d, di), ("embed", "ssm_inner")),
        "w_B": p((d, G * N), ("embed", "state")),
        "w_C": p((d, G * N), ("embed", "state")),
        "w_dt": p((d, H), ("embed", "heads")),
        "conv_x": p((cw, di), ("conv", "ssm_inner"), scale=cw ** -0.5),
        "conv_B": p((cw, G * N), ("conv", "state"), scale=cw ** -0.5),
        "conv_C": p((cw, G * N), ("conv", "state"), scale=cw ** -0.5),
        "A_log": p((H,), ("heads",), init="zeros", dtype=jnp.float32),
        "D": p((H,), ("heads",), init="ones", dtype=jnp.float32),
        "dt_bias": p((H,), ("heads",), init="zeros", dtype=jnp.float32),
        "norm": common.rmsnorm_init(p, di, axis="ssm_inner"),
        "w_out": p((di, d), ("ssm_inner", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, state=None):
    """Depthwise causal conv along time. x: (B, S, C); w: (cw, C).

    With ``state`` (B, cw-1, C) prepends the carry (decode path) and also
    returns the updated carry.
    """
    cw = w.shape[0]
    if state is not None:
        x = jnp.concatenate([state.astype(x.dtype), x], axis=1)
        new_state = x[:, -(cw - 1):, :]
    else:
        x = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
        new_state = None
    out = sum(
        x[:, i: i + (x.shape[1] - cw + 1), :] * w[i][None, None, :]
        for i in range(cw))
    return out, new_state


def _projections(params, h, cfg: ArchConfig, conv_state=None,
                 return_raw_tail=False):
    B, S, _ = h.shape
    G, N, H, P = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    x = h @ params["w_x"]
    z = h @ params["w_z"]
    Bp = h @ params["w_B"]
    Cp = h @ params["w_C"]
    dt_raw = (h @ params["w_dt"]).astype(jnp.float32)

    raw_tail = None
    if return_raw_tail:
        cw = cfg.conv_width
        raw_tail = {"x": x[:, -(cw - 1):], "B": Bp[:, -(cw - 1):],
                    "C": Cp[:, -(cw - 1):]}
    x, sx = _causal_conv(x, params["conv_x"],
                         conv_state["x"] if conv_state else None)
    Bp, sB = _causal_conv(Bp, params["conv_B"],
                          conv_state["B"] if conv_state else None)
    Cp, sC = _causal_conv(Cp, params["conv_C"],
                          conv_state["C"] if conv_state else None)
    x = jax.nn.silu(x.astype(jnp.float32)).astype(h.dtype)
    Bp = jax.nn.silu(Bp.astype(jnp.float32)).astype(h.dtype)
    Cp = jax.nn.silu(Cp.astype(jnp.float32)).astype(h.dtype)

    x = x.reshape(B, S, H, P)
    Bp = Bp.reshape(B, S, G, N)
    Cp = Cp.reshape(B, S, G, N)
    rep = H // G
    if rep > 1:
        Bp = jnp.repeat(Bp, rep, axis=2)
        Cp = jnp.repeat(Cp, rep, axis=2)
    dt = jax.nn.softplus(dt_raw + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])  # (H,) negative
    new_conv = {"x": sx, "B": sB, "C": sC} if conv_state is not None else None
    return x, z, Bp, Cp, dt, A, (new_conv if conv_state is not None
                                 else raw_tail)


def ssd_forward(params, h: jax.Array, cfg: ArchConfig,
                return_cache: bool = False):
    """Chunked SSD over a full sequence. h: (B, S, d).

    Sequences that do not divide the chunk size are zero-padded; padded
    positions get dt = 0 (decay 1, update 0) so the carried state is
    untouched — prefill state handoff stays exact for any length.
    """
    B, S, d = h.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    cs = min(cfg.ssm_chunk, S)

    x, z, Bp, Cp, dt, A, raw_tail = _projections(
        params, h, cfg, return_raw_tail=return_cache)

    S_orig = S
    pad = (-S) % cs
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bp = jnp.pad(Bp, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cp = jnp.pad(Cp, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0: state frozen
        S = S + pad
    nc = S // cs

    # reshape into chunks
    xc = x.reshape(B, nc, cs, H, P).astype(jnp.float32)
    Bc = Bp.reshape(B, nc, cs, H, N).astype(jnp.float32)
    Cc = Cp.reshape(B, nc, cs, H, N).astype(jnp.float32)
    dtc = dt.reshape(B, nc, cs, H)

    da = dtc * A[None, None, None, :]              # (B, nc, cs, H) log decay
    cum = jnp.cumsum(da, axis=2)                   # within-chunk cumulative
    total = cum[:, :, -1, :]                       # (B, nc, H)

    # --- intra-chunk (quadratic within the chunk) ---
    # L[i, j] = exp(cum_i - cum_j) for i >= j  (per B, chunk, H). Masked
    # before the exp: above the diagonal cum_i - cum_j > 0 can overflow to
    # inf over a long chunk, and where(mask, inf, 0) has a NaN gradient.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B,nc,i,j,H)
    mask = jnp.tril(jnp.ones((cs, cs), bool))
    L = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    G_ = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc)            # C_i . B_j
    M = G_ * L
    xdt = xc * dtc[..., None]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xdt)

    # --- chunk-boundary states + inter-chunk linear scan ---
    decay_to_end = jnp.exp(total[:, :, None, :] - cum)       # (B,nc,cs,H)
    state_c = jnp.einsum("bcjhn,bcjh,bcjhp->bchnp", Bc, decay_to_end * dtc, xc)

    def scan_fn(carry, inp):
        st, dec = inp  # (B,H,N,P), (B,H)
        new = carry * jnp.exp(dec)[:, :, None, None] + st
        return new, carry  # emit state *before* this chunk

    init = jnp.zeros((B, H, N, P), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(state_c, 1, 0), jnp.moveaxis(total, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)            # (B,nc,H,N,P)

    y_inter = jnp.einsum("bcihn,bchnp->bcihp", Cc * jnp.exp(cum)[..., None],
                         prev_states)

    y = (y_intra + y_inter).reshape(B, S, H, P)
    y = y + xc.reshape(B, S, H, P) * params["D"][None, None, :, None]
    y = y.reshape(B, S, H * P).astype(h.dtype)
    if pad:
        y = y[:, :S_orig]

    y = common.rmsnorm(params["norm"], y * jax.nn.silu(z.astype(jnp.float32)
                                                       ).astype(h.dtype))
    out = y @ params["w_out"]
    if return_cache:
        cache = SSDCache(conv_x=raw_tail["x"], conv_B=raw_tail["B"],
                         conv_C=raw_tail["C"], state=final_state)
        return out, cache
    return out


class SSDCache(NamedTuple):
    conv_x: jax.Array   # (B, cw-1, d_inner)
    conv_B: jax.Array   # (B, cw-1, G*N)
    conv_C: jax.Array   # (B, cw-1, G*N)
    state: jax.Array    # (B, H, N, P) fp32


def ssd_cache_init(cfg: ArchConfig, batch: int, dtype) -> SSDCache:
    cw = cfg.conv_width
    return SSDCache(
        conv_x=jnp.zeros((batch, cw - 1, cfg.d_inner), dtype),
        conv_B=jnp.zeros((batch, cw - 1, cfg.ssm_groups * cfg.ssm_state), dtype),
        conv_C=jnp.zeros((batch, cw - 1, cfg.ssm_groups * cfg.ssm_state), dtype),
        state=jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_state,
                         cfg.ssm_head_dim), jnp.float32),
    )


def ssd_cache_spec(cfg: ArchConfig, batch: int, dtype) -> SSDCache:
    init = ssd_cache_init(cfg, 0, dtype)  # shapes only; rebuild with batch
    cw = cfg.conv_width
    return SSDCache(
        conv_x=jax.ShapeDtypeStruct((batch, cw - 1, cfg.d_inner), dtype),
        conv_B=jax.ShapeDtypeStruct(
            (batch, cw - 1, cfg.ssm_groups * cfg.ssm_state), dtype),
        conv_C=jax.ShapeDtypeStruct(
            (batch, cw - 1, cfg.ssm_groups * cfg.ssm_state), dtype),
        state=jax.ShapeDtypeStruct(
            (batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
            jnp.float32),
    )


def ssd_decode(params, h_tok: jax.Array, cache: SSDCache, cfg: ArchConfig
               ) -> Tuple[jax.Array, SSDCache]:
    """One-token step: h = exp(dt*A) h + dt * B x ; y = C . h + D x."""
    B = h_tok.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    conv_state = {"x": cache.conv_x, "B": cache.conv_B, "C": cache.conv_C}
    x, z, Bp, Cp, dt, A, new_conv = _projections(params, h_tok, cfg, conv_state)

    xf = x[:, 0].astype(jnp.float32)         # (B, H, P)
    Bf = Bp[:, 0].astype(jnp.float32)        # (B, H, N)
    Cf = Cp[:, 0].astype(jnp.float32)
    dtf = dt[:, 0]                           # (B, H)

    decay = jnp.exp(dtf * A[None, :])        # (B, H)
    upd = jnp.einsum("bhn,bhp->bhnp", Bf, xf * dtf[..., None])
    state = cache.state * decay[:, :, None, None] + upd
    y = jnp.einsum("bhn,bhnp->bhp", Cf, state)
    y = y + xf * params["D"][None, :, None]
    y = y.reshape(B, 1, H * P).astype(h_tok.dtype)
    y = common.rmsnorm(params["norm"],
                       y * jax.nn.silu(z.astype(jnp.float32)).astype(h_tok.dtype))
    out = y @ params["w_out"]
    return out, SSDCache(conv_x=new_conv["x"], conv_B=new_conv["B"],
                         conv_C=new_conv["C"], state=state)
