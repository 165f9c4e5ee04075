"""Plain float32 reference of the depth- and expert-cut granite-4.0-h-small
forward.

Written from the published GraniteMoeHybrid decoder (its config.json and
the Granite 4.0 model card): the token embedding times
``embedding_multiplier``; then per layer

* RMSNorm -> mixer -> times ``residual_multiplier``, added to the residual.
  A ``mamba`` layer's mixer is Mamba-2: in-projections to z, x, B, C and
  dt; a causal depthwise conv of width ``mamba_d_conv`` with a bias over
  x, B and C, then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); the
  recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t h_t +
  D x_t, run one position at a time; a gated RMSNorm of y * SiLU(z); the
  out-projection. An ``attention`` layer is causal GQA with no positional
  encoding (NoPE), scores scaled by ``attention_multiplier``;
* RMSNorm -> the FFN -> times ``residual_multiplier``, added. The FFN is
  the routed experts plus the shared expert: the router's softmax over
  all its experts, the top ``num_experts_per_tok``, renormalised (the
  same as a softmax over the top logits), each expert SiLU-gated
  (silu(x W_in) * (x W_gate)) W_out; the shared expert likewise, added
  once;

a final RMSNorm, the tied embedding as the head, the logits divided by
``logits_scaling``.

The cut, as the configuration states it: the first ``num_hidden_layers``
layers of ``layer_types``, and of the router's experts only the slice
``expert_share`` holds (the program's ``--expert-share``), whose gates
are summed; the other slices' parts belong to other chips and are left
out here as in the program.

It imports nothing of the program; the weights come in the program's
tree layout (the benchmark made them) and are read in float32.
Departures, as the configuration runs them: RMSNorm multiplies by
(1 + scale) where the published model multiplies by a weight (the same
function of the parameter shifted by one); x, B and C take separate
convs and the bias is one row over their channels (the same maths as
one conv over their concatenation).

Each layer runs over the whole sequence, attention in blocks of queries,
so one long context fits beside the weights. ``prec`` is ``"f32"``
(matmuls at full float32) or ``"fp8"`` (every matmul operand, and the
recurrence's B, C and x, rounded to float8_e4m3, saturating at 448: the
control).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 256
# Sequences are padded to a multiple of PAD, so one compile per layer kind
# serves every sequence a chat check reads (up to 4096 prompt tokens plus
# 1024 served ones).
PAD = 6144


def _cast(x, prec):
    x = x.astype(F32)
    if prec == "fp8":  # saturating float8_e4m3
        x = jnp.clip(x, -FP8_MAX, FP8_MAX).astype(
            jnp.float8_e4m3fn).astype(F32)
    return x


def _mm(a, b, prec):
    return jnp.matmul(_cast(a, prec), _cast(b, prec), precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _conv(x, w, b):
    """Causal depthwise conv along time with a bias: x (T, C), w (cw, C)."""
    cw, T = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((cw - 1, 0), (0, 0)))
    w = w.astype(F32)
    return sum(xp[i:i + T] * w[i] for i in range(cw)) + b.astype(F32)


def _mamba(p, x, cfg, prec):
    """Mamba-2 mixer over x (T, d), the recurrence one position at a
    time."""
    T = x.shape[0]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    P, N, G = cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    H = cfg["mamba_n_heads"]
    bias = p["conv_bias"][0]
    xs = _conv(_mm(x, p["w_x"], prec), p["conv_x"], bias[:di])
    Bm = _conv(_mm(x, p["w_B"], prec), p["conv_B"], bias[di:di + G * N])
    Cm = _conv(_mm(x, p["w_C"], prec), p["conv_C"], bias[di + G * N:])
    z = _mm(x, p["w_z"], prec)
    dt = jax.nn.softplus(_mm(x, p["w_dt"], prec) + p["dt_bias"].astype(F32))
    xs = _cast(jax.nn.silu(xs), prec).reshape(T, H, P)
    Bm = jnp.repeat(_cast(jax.nn.silu(Bm), prec).reshape(T, G, N), H // G, 1)
    Cm = jnp.repeat(_cast(jax.nn.silu(Cm), prec).reshape(T, G, N), H // G, 1)
    A = -jnp.exp(p["A_log"].astype(F32))                      # (H,)
    D = p["D"].astype(F32)

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp            # (H,P), (H,N), (H,N), (H,)
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + dt_t[:, None, None] * B_t[:, :, None] * x_t[:, None, :])
        y = jnp.einsum("hn,hnp->hp", C_t, h, precision=HIGHEST)
        return h, y + D[:, None] * x_t

    h0 = jnp.zeros((H, N, P), F32)
    _, y = jax.lax.scan(step, h0, (xs, Bm, Cm, dt), unroll=8)
    y = y.reshape(T, di) * jax.nn.silu(z)
    y = _rms(y, p["norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(y, p["w_out"], prec)


def _attention(a, x, cfg, prec):
    """Causal NoPE GQA over x (T, d), queries in blocks."""
    T = x.shape[0]
    H, KH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    rep = H // KH
    q = _mm(x, a["wq"], prec).reshape(T, KH, rep, hd)
    k = _cast(_mm(x, a["wk"], prec).reshape(T, KH, hd), prec)
    v = _cast(_mm(x, a["wv"], prec).reshape(T, KH, hd), prec)
    kpos = jnp.arange(T)

    def block(_, i):
        qb = _cast(jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0),
                   prec)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HIGHEST)
        s = s * cfg["attention_multiplier"]
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", _cast(pr, prec), v,
                       precision=HIGHEST)
        return None, o.reshape(Q_BLOCK, H * hd)

    _, out = jax.lax.scan(block, None, jnp.arange(T // Q_BLOCK))
    return _mm(out.reshape(T, H * hd), a["wo"], prec)


def _glu(x, w_in, w_gate, w_out, prec):
    """silu(x W_in) * (x W_gate)) W_out; with stacked weights (E, ...),
    every expert over every row: (E, T, d)."""
    return _mm(jax.nn.silu(_mm(x, w_in, prec)) * _mm(x, w_gate, prec),
               w_out, prec)


def _ffn(m, x, cfg, prec):
    """The held experts' part of the routed FFN, plus the shared expert.
    Every held expert runs on every row; a row's gate for an expert it did
    not pick is 0."""
    held = m["w_in"].shape[0]
    lo = dict(cfg["expert_share"])["shard"] * held
    logits = _mm(x, m["router"], prec)                         # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    ids = lo + jnp.arange(held)
    g = jnp.sum(jnp.where(idx[:, None, :] == ids[None, :, None],
                          gate[:, None, :], 0.0), axis=-1)     # (T, held)
    y = _glu(x, m["w_in"], m["w_gate"], m["w_out"], prec)      # (held, T, d)
    out = jnp.einsum("te,etd->td", g, y, precision=HIGHEST)
    return out + _glu(x, m["shared"]["w_in"], m["shared"]["w_gate"],
                      m["shared"]["w_out"], prec)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items", "prec"))
def _layer(h, lp, kind, cfg_items, prec):
    cfg = dict(cfg_items)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = _rms(h, lp["pre_norm"]["scale"], eps)
    if kind == "mamba":
        h = h + r * _mamba(lp["ssd"], x, cfg, prec)
    else:
        h = h + r * _attention(lp["attn"], x, cfg, prec)
    x = _rms(h, lp["mlp_norm"]["scale"], eps)
    return h + r * _ffn(lp["moe"], x, cfg, prec)


@functools.partial(jax.jit, static_argnames=("eps", "div", "vocab", "prec"))
def _head(h, rows, final_scale, table, eps, div, vocab, prec):
    x = _rms(h[rows], final_scale, eps)
    return _mm(x, table[:vocab].T, prec) / div


def logits_at(params, tokens, rows, cfg, prec="f32"):
    """Logits (len(rows), vocab) of the next token after each position in
    ``rows``, for the 1-D int sequence ``tokens``."""
    with jax.default_matmul_precision("highest"):
        T = len(tokens)
        Tp = -(-T // PAD) * PAD
        toks = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, Tp - T))
        table = params["embed"]["table"]
        h = table[toks].astype(F32) * cfg["embedding_multiplier"]
        items = tuple(sorted(
            (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
            for k, v in cfg.items()
            if isinstance(v, (int, float, str)) or k == "expert_share"))
        period = len(params["periods"])
        kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
        for i, kind in enumerate(kinds):
            lp = jax.tree.map(lambda a: a[i // period],
                              params["periods"][f"slot{i % period}"])
            h = _layer(h, lp, kind, items, prec)
        n = len(rows)
        padded = jnp.pad(jnp.asarray(rows, jnp.int32),
                         (0, -(-n // 256) * 256 - n), mode="edge")
        return _head(h, padded, params["final_norm"]["scale"], table,
                     cfg["rms_norm_eps"], cfg["logits_scaling"],
                     cfg["vocab_size"], prec)[:n]
