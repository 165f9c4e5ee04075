"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For depths beyond what TP x FSDP covers (or to span slow inter-pod links),
layers split into S stages along a `pipe` mesh axis; microbatches stream
through with the standard GPipe schedule expressed as a rotating shard_map
loop: each device holds one stage's parameters, activations move stage to
stage with ppermute, and the loop runs (n_micro + S - 1) ticks (bubble
included).

This module is self-contained and validated in tests/spmd (8 host
devices); the 512-chip dry-run meshes use TP x FSDP x DP which covers the
assigned model sizes (DESIGN.md §4).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x_micro: jax.Array, mesh: Mesh,
                   axis: str = "pipe") -> jax.Array:
    """Run microbatches through S pipeline stages.

    Args:
      stage_fn: (params_for_stage, h) -> h, applied by every device to the
        activation currently resident on it.
      stage_params: pytree whose leaves have leading dim S (one slice per
        stage); sharded over ``axis``.
      x_micro: (n_micro, mb, ...) microbatched input, replicated.
      mesh: mesh containing ``axis``.

    Returns (n_micro, mb, ...) outputs (as produced by the last stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params_local, xs):
        # params_local: leaves (1, ...) — this device's stage.
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf, outs = carry  # buf: activation resident on this device
            # stage 0 ingests microbatch t (when in range)
            feed = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(xs, feed, keepdims=False)
            h = jnp.where(stage == 0, x_in, buf)
            h = stage_fn(params_here, h)
            # last stage emits microbatch (t - S + 1)
            out_idx = t - (n_stages - 1)
            emit = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                out_idx >= 0,
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(
                    jnp.where(emit, h, o[jnp.maximum(out_idx, 0)])),
                lambda o: o, outs)
            # rotate activations to the next stage
            h_next = jax.lax.ppermute(h, axis, perm)
            return (h_next, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them to all.
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_params, P()), out_specs=P(),
        check_vma=False)(stage_params, x_micro)
