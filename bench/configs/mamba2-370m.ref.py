"""Plain float32 reference of mamba2-370m for training: forward and loss.

Written from the Mamba-2 paper (arXiv:2405.21060): each layer is
RMSNorm -> in-projections (x, z, B, C, dt) -> causal depthwise conv on x,
B and C -> SiLU -> SSD (the paper's ``ssd_minimal_discrete``, chunked)
-> D skip -> gated RMSNorm (y * SiLU(z)) -> out-projection, added to the
residual; a final RMSNorm and the tied embedding give the logits. It
imports nothing of the program; parameters come in the program's tree
layout (the benchmark made them) and are read in float32.

Departures from the published model, each as the configuration runs it:
RMSNorm multiplies by (1 + scale) and uses ``rms_norm_eps``; the conv has
no bias; x, B and C take separate convs (the same maths as one conv over
their concatenation).

It models training with no precision policy (``POLICIES``): no stash
rounding, no learned-width quantizers of weights or activations, no
bit-length updates. A job with a policy needs a reference that models
it. ``prec`` is ``"f32"`` (matmuls at full float32) or ``"fp8"`` (every
matmul operand rounded to float8_e4m3, saturating at 448, the gradient
passed straight through: the control).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
POLICIES = ("none",)


def _cast(x, prec):
    x = x.astype(F32)
    if prec == "fp8":  # saturating float8_e4m3, gradients straight through
        q = jnp.clip(x, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)
        x = x + jax.lax.stop_gradient(q.astype(F32) - x)
    return x


def _mm(a, b, prec):
    return jnp.matmul(_cast(a, prec), _cast(b, prec), precision=HIGHEST)


def _einsum(spec, *ops, prec):
    return jnp.einsum(spec, *(_cast(o, prec) for o in ops), precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _conv(x, w):
    """Causal depthwise conv along time: x (b, T, C), w (cw, C)."""
    cw, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    w = w.astype(F32)
    return sum(xp[:, i:i + T] * w[i] for i in range(cw))


def _segsum(x):
    """out[..., i, j] = sum_{j < k <= i} x[..., k] for i >= j, else -inf."""
    T = x.shape[-1]
    xr = jnp.broadcast_to(x[..., :, None], x.shape + (T,))
    strict = jnp.tril(jnp.ones((T, T), bool), -1)
    cs = jnp.cumsum(jnp.where(strict, xr, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), cs, -jnp.inf)


def _ssd(X, A, B, C, block, prec):
    """Mamba-2's ssd_minimal_discrete. X (b,T,h,p) already times dt,
    A (b,T,h) = dt * A, B and C (b,T,h,n)."""
    b, T, h, p = X.shape
    c = T // block
    X, B, C = (t.reshape(b, c, block, *t.shape[2:]) for t in (X, B, C))
    A = jnp.moveaxis(A.reshape(b, c, block, h), 3, 1)       # (b,h,c,l)
    Acs = jnp.cumsum(A, axis=-1)
    L = jnp.exp(_segsum(A))
    Y_diag = _einsum("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, L, X, prec=prec)
    decay_states = jnp.exp(Acs[..., -1:] - Acs)
    states = _einsum("bclhn,bhcl,bclhp->bchpn", B, decay_states, X, prec=prec)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(Acs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                        precision=HIGHEST)[:, :-1]
    Y_off = _einsum("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(Acs),
                    prec=prec)
    return (Y_diag + Y_off).reshape(b, T, h, p)


def _mixer(p, x, cfg, prec):
    d = cfg["hidden_size"]
    di = cfg["expand"] * d
    P, N, G = cfg["head_dim"], cfg["state_size"], cfg["n_groups"]
    H = di // P
    b, T, _ = x.shape
    xs = _mm(x, p["w_x"], prec)
    z = _mm(x, p["w_z"], prec)
    Bm = _mm(x, p["w_B"], prec)
    Cm = _mm(x, p["w_C"], prec)
    dt = jax.nn.softplus(_mm(x, p["w_dt"], prec) + p["dt_bias"].astype(F32))
    xs = jax.nn.silu(_conv(xs, p["conv_x"])).reshape(b, T, H, P)
    Bm = jax.nn.silu(_conv(Bm, p["conv_B"])).reshape(b, T, G, N)
    Cm = jax.nn.silu(_conv(Cm, p["conv_C"])).reshape(b, T, G, N)
    Bm = jnp.repeat(Bm, H // G, axis=2)
    Cm = jnp.repeat(Cm, H // G, axis=2)
    A = -jnp.exp(p["A_log"].astype(F32))                    # (H,)
    y = _ssd(xs * dt[..., None], dt * A, Bm, Cm, cfg["chunk_size"], prec)
    y = y + xs * p["D"].astype(F32)[:, None]
    y = y.reshape(b, T, di) * jax.nn.silu(z)
    y = _rms(y, p["norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(y, p["w_out"], prec)


def xent(params, tokens, labels, cfg, prec="f32"):
    """Mean next-token cross-entropy over a block of rows."""
    eps = cfg["rms_norm_eps"]
    V = cfg["vocab_size"]
    table = params["embed"]["table"]
    h = table[tokens].astype(F32)

    def layer(h, lp):
        x = _rms(h, lp["pre_norm"]["scale"], eps)
        return h + _mixer(lp["ssd"], x, cfg, prec), None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h,
                        params["periods"]["slot0"])
    h = _rms(h, params["final_norm"]["scale"], eps)
    logits = _mm(h, table[:V].T, prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
