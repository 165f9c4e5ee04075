"""Shared model components: param factory, norms, embeddings, RoPE, MLP.

Parameters are plain nested dicts. ``ParamFactory`` lets the same builder
code produce real arrays (init), ShapeDtypeStructs (dry-run) or logical
sharding axes (pjit specs) — the three views stay in sync by construction.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

MODE_PARAMS = "params"
MODE_SHAPE = "shape"
MODE_AXES = "axes"


class ParamFactory:
    """One code path for params / shapes / logical axes."""

    def __init__(self, mode: str, key: Optional[jax.Array] = None,
                 dtype=jnp.bfloat16):
        self.mode = mode
        self.key = key
        self.dtype = jnp.dtype(dtype)
        self._counter = 0

    def _next_key(self):
        self._counter += 1
        return jax.random.fold_in(self.key, self._counter)

    def __call__(self, shape: Sequence[int], axes: Tuple[Optional[str], ...],
                 init: str = "normal", scale: Optional[float] = None,
                 dtype=None):
        shape = tuple(int(s) for s in shape)
        assert len(axes) == len(shape), (shape, axes)
        dtype = jnp.dtype(dtype) if dtype is not None else self.dtype
        if self.mode == MODE_AXES:
            return tuple(axes)
        if self.mode == MODE_SHAPE:
            return jax.ShapeDtypeStruct(shape, dtype)
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if scale is None:
            fan_in = shape[0] if len(shape) > 1 else max(shape[-1], 1)
            scale = fan_in ** -0.5
        return (jax.random.normal(self._next_key(), shape, jnp.float32)
                * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(p: ParamFactory, dim: int, axis: str = "embed"):
    return {"scale": p((dim,), (axis,), init="zeros")}  # gemma-style (1+scale)


def rmsnorm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply rotary embeddings. x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angle = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(p: ParamFactory, vocab: int, d: int):
    # The table's model dim stays logically unsharded ("embed_r"): the
    # lookup shards the *vocab* dim over `model` (see sharded_embed) and the
    # tied unembed matmul contracts over the replicated d.
    return {"table": p((vocab, d), ("vocab", "embed_r"), scale=1.0)}


def sharded_embed(table: jax.Array, tokens: jax.Array, mesh) -> jax.Array:
    """Embedding lookup with the vocab dim sharded over `model`.

    A plain gather along a sharded axis triggers GSPMD "involuntary full
    rematerialization" (replicates the table AND scrambles downstream batch
    shardings). The manual form — local masked gather + psum over `model` —
    partitions exactly.
    """
    from jax.sharding import PartitionSpec as P

    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= sizes[a]
    if tokens.shape[0] % n_batch_shards != 0:  # e.g. batch=1 decode cells
        batch_axes = None

    def local(tab, tok):
        vloc = tab.shape[0]
        idx = jax.lax.axis_index("model")
        rel = tok - idx * vloc
        ok = (rel >= 0) & (rel < vloc)
        out = tab[jnp.clip(rel, 0, vloc - 1)]
        out = jnp.where(ok[..., None], out, jnp.zeros((), out.dtype))
        return jax.lax.psum(out, "model")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None), P(batch_axes, None)),
        out_specs=P(batch_axes, None, None))(table, tokens)


def embed(params, tokens: jax.Array, scale: Optional[float] = None,
          mesh=None) -> jax.Array:
    if mesh is not None and "model" in mesh.axis_names:
        h = sharded_embed(params["table"], tokens, mesh)
    else:
        h = params["table"][tokens]
    if scale is not None:
        h = h * jnp.asarray(scale, h.dtype)
    return h


def unembed(params, h: jax.Array, *, tied: bool,
            softcap: Optional[float] = None,
            valid_vocab: Optional[int] = None) -> jax.Array:
    table = params["embed"]["table"] if tied else params["head"]
    if tied:
        logits = jnp.einsum("bsd,vd->bsv", h, table)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, table)
    logits = logits.astype(jnp.float32)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    if valid_vocab is not None and valid_vocab < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) < valid_vocab
        logits = jnp.where(mask, logits, -1e30)
    return logits


# ---------------------------------------------------------------------------
# Dense MLP (optionally gated)
# ---------------------------------------------------------------------------

def mlp_init(p: ParamFactory, d: int, ff: int, glu: bool):
    out = {
        "w_in": p((d, ff), ("embed", "ff")),
        "w_out": p((ff, d), ("ff", "embed")),
    }
    if glu:
        out["w_gate"] = p((d, ff), ("embed", "ff"))
    return out


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def mlp(params, x: jax.Array, act: str, glu: bool) -> jax.Array:
    h = x @ params["w_in"]
    a = activation(act)(h.astype(jnp.float32)).astype(x.dtype)
    if glu:
        a = a * (x @ params["w_gate"])
    return a @ params["w_out"]


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array,
                 valid_mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean cross-entropy in fp32 over valid positions (vocab-shardable).

    The label pick uses an iota-compare reduction instead of
    take_along_axis: a gather along a model-sharded vocab axis would force
    GSPMD to all-gather the fp32 logits (hundreds of GB at 256k vocab),
    while the masked-sum partitions cleanly (each shard contributes its
    local match, one tiny all-reduce).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                         logits.ndim - 1)
    picked = jnp.sum(jnp.where(vocab_ids == labels[..., None], logits, 0.0),
                     axis=-1)
    nll = lse - picked
    if valid_mask is None:
        return jnp.mean(nll)
    vm = valid_mask.astype(jnp.float32)
    return jnp.sum(nll * vm) / jnp.maximum(jnp.sum(vm), 1.0)
