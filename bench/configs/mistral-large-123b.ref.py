"""Plain float32 reference of the depth-cut mistral-large-123b forward.

Written from the Mistral/Llama decoder as published: per layer RMSNorm ->
q, k, v projections (GQA: query head i reads key/value head
i // (heads / kv_heads)) -> rotary embedding (rotate-half form,
``rope_theta``) -> causal softmax attention scaled by 1/sqrt(head_dim) ->
output projection, added to the residual; RMSNorm -> SiLU-gated MLP
(silu(x W_in) * (x W_gate)) W_out, added; a final RMSNorm and the untied
head. It imports nothing of the program; the weights come in the
program's tree layout (the benchmark made them) and are read in float32.
Departure, as the configuration runs it: RMSNorm multiplies by
(1 + scale).

The whole sequence is processed layer by layer, attention in blocks of
queries, so one long context fits beside the weights. ``prec`` is
``"f32"`` (matmuls at full float32) or ``"fp8"`` (every matmul operand
rounded to float8_e4m3, saturating at 448: the control).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 256
PAD = 1024  # sequences are padded to a multiple (one compile per size)


def _cast(x, prec):
    x = x.astype(F32)
    if prec == "fp8":  # saturating float8_e4m3, gradients straight through
        q = jnp.clip(x, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)
        x = x + jax.lax.stop_gradient(q.astype(F32) - x)
    return x


def _mm(a, b, prec):
    return jnp.matmul(_cast(a, prec), _cast(b, prec), precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, pos, theta):
    """x (T, heads, hd); rotate-half form."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freq[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, prec):
    """Causal GQA attention over a whole sequence, queries in blocks.
    q (T, H, hd), k and v (T, KH, hd)."""
    T, H, hd = q.shape
    KH = k.shape[1]
    rep = H // KH
    kpos = jnp.arange(T)
    k, v = _cast(k, prec), _cast(v, prec)

    def block(_, i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qb = _cast(qb, prec).reshape(Q_BLOCK, KH, rep, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(F32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", _cast(p, prec), v,
                       precision=HIGHEST)
        return None, o.reshape(Q_BLOCK, H * hd)

    _, out = jax.lax.scan(block, None, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, H * hd)


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec"))
def _layer(h, lp, cfg_items, prec):
    cfg = dict(cfg_items)
    T = h.shape[0]
    H, KH, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(T)
    x = _rms(h, lp["pre_norm"]["scale"], eps)
    a = lp["attn"]
    q = _rope(_mm(x, a["wq"], prec).reshape(T, H, hd), pos, theta)
    k = _rope(_mm(x, a["wk"], prec).reshape(T, KH, hd), pos, theta)
    v = _mm(x, a["wv"], prec).reshape(T, KH, hd)
    h = h + _mm(_attention(q, k, v, prec), a["wo"], prec)
    x = _rms(h, lp["mlp_norm"]["scale"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(_mm(x, m["w_in"], prec)) * _mm(x, m["w_gate"], prec)
    return h + _mm(g, m["w_out"], prec)


@functools.partial(jax.jit, static_argnames=("eps", "prec"))
def _head(h, rows, final_scale, head, eps, prec):
    x = _rms(h[rows], final_scale, eps)
    return _mm(x, head, prec)


def logits_at(params, tokens, rows, cfg, prec="f32"):
    """Logits (len(rows), vocab) of the next token after each position in
    ``rows``, for the 1-D int sequence ``tokens``."""
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    toks = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, Tp - T))
    h = params["embed"]["table"][toks].astype(F32)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    slots = params["periods"]["slot0"]
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, jax.tree.map(lambda a: a[i], slots), items, prec)
    n = len(rows)
    padded = jnp.pad(jnp.asarray(rows, jnp.int32), (0, -(-n // 256) * 256 - n),
                     mode="edge")
    return _head(h, padded, params["final_norm"]["scale"], params["head"],
                 cfg["rms_norm_eps"], prec)[:n]
