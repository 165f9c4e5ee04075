"""The plain reference of a training job's first steps, and the numbers
the check compares.

The model's loss comes from the configuration's reference module; the
rest is written here from the job as configured: gradients accumulated
over blocks of rows (so the reference fits beside nothing else), AdamW
with global-norm clipping, bias correction and decoupled weight decay on
every stored array of two or more dimensions, a cosine schedule with
linear warmup, and parameters kept in the types the configuration stores
them in (updates computed in float32).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def lr_at(step: int, opt: dict) -> float:
    """Cosine schedule with linear warmup from 0 (the job's schedule)."""
    warm_steps, total = opt["warmup_steps"], opt["total_steps"]
    warm = min(step / max(warm_steps, 1), 1.0)
    frac = min(max((step - warm_steps) / max(total - warm_steps, 1), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * frac))
    lo = opt["min_lr_frac"]
    return opt["lr"] * (lo + (1 - lo) * cos) * warm


@jax.jit
def leaf_norms(tree) -> jax.Array:
    """Frobenius norm of every leaf, in float32, in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def delta_norms(new, old) -> jax.Array:
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(F32) - b.astype(F32), new, old))


def _grad_fn(xent: Callable, rows_per_block: int):
    def grads(params, tokens, labels):
        p32 = jax.tree.map(lambda p: p.astype(F32), params)
        B = tokens.shape[0]
        nb = B // rows_per_block
        tb = tokens.reshape(nb, rows_per_block, -1)
        lb = labels.reshape(nb, rows_per_block, -1)
        vg = jax.value_and_grad(xent)

        def block(acc, x):
            loss, g = vg(p32, *x)
            return (acc[0] + loss / nb,
                    jax.tree.map(lambda a, b: a + b / nb, acc[1], g)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, p32))
        (loss, g), _ = jax.lax.scan(block, zero, (tb, lb))
        return loss, g

    return jax.jit(grads)


def _adamw(opt: dict):
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def update(params, grads, m, v, lr, count):
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count

        def one(p, a, b):
            pf = p.astype(F32)
            step = (a / c1) / (jnp.sqrt(b / c2) + eps)
            decay = wd if p.ndim >= 2 else 0.0
            return (pf - lr * (step + decay * pf)).astype(p.dtype)

        return jax.tree.map(one, params, m, v), grads, m, v

    return jax.jit(update)


def run(xent: Callable, params, batches: Sequence[Dict[str, jax.Array]],
        opt: dict, rows_per_block: int) -> dict:
    """Train ``len(batches)`` steps from ``params``. Returns the loss of
    each step, the norm of each leaf of the first (clipped) gradient and of
    each leaf's change over all the steps."""
    grads_of = _grad_fn(xent, rows_per_block)
    update = _adamw(opt)
    p = params
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), params)
    losses, first = [], None
    for s, b in enumerate(batches):
        loss, g = grads_of(p, b["tokens"], b["labels"])
        p, g, m, v = update(p, g, m, v, lr_at(s, opt), s + 1)
        if first is None:
            first = np.asarray(leaf_norms(g))
        losses.append(float(loss))
        del g
    return {"xent": losses, "grad": first,
            "delta": np.asarray(delta_norms(p, params))}


def gaps(prog: dict, ref: dict, grad_floor: float = 1e-3) -> Dict[str, float]:
    """The numbers a training cell's check can compare.

    * ``loss``: the largest relative gap of a step's loss;
    * ``grad``: by the worst leaf, the gap between the program's and the
      reference's norm of the first gradient, over the larger of the
      reference leaf's norm and the median leaf's;
    * ``update``: the same for each leaf's change over the steps, leaving
      out leaves whose reference gradient is under ``grad_floor`` of the
      median leaf's (they move by Adam's round-off alone);
    * ``grad_median``, ``update_median``: the median leaf's gap instead of
      the worst's.
    """
    def per_leaf(p, r, keep):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        den = np.maximum(r, np.median(r))
        return (np.abs(p - r) / den)[keep]

    xp, xr = np.asarray(prog["xent"]), np.asarray(ref["xent"])
    g = np.asarray(ref["grad"], np.float64)
    moving = g >= grad_floor * np.median(g)
    grad = per_leaf(prog["grad"], ref["grad"], np.ones_like(moving))
    update = per_leaf(prog["delta"], ref["delta"], moving)
    return {"loss": float(np.max(np.abs(xp - xr) / np.abs(xr))),
            "grad": float(np.max(grad)), "update": float(np.max(update)),
            "grad_median": float(np.median(grad)),
            "update_median": float(np.median(update))}
