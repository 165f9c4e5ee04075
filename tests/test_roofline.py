"""Roofline tooling: the scan-trip-count defect in cost_analysis (why the
jaxpr model exists), jaxpr cost accuracy, HLO collective parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import hlo_collectives, jaxpr_cost


def test_cost_analysis_misses_scan_trips():
    """Documents the backend defect the jaxpr model corrects."""
    def f(c, xs):
        def body(c, x):
            return jnp.tanh(c @ x), None
        out, _ = jax.lax.scan(body, c, xs)
        return out

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    xs = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    compiled = jax.jit(f).lower(a, xs).compile()
    cost = compiled.cost_analysis()
    reported = cost["flops"]
    one_matmul = 2 * 256 ** 3
    assert reported < 2.5 * one_matmul  # counts the body once, not x10


def test_jaxpr_cost_counts_scan_trips_exactly():
    def f(c, xs):
        def body(c, x):
            return jnp.tanh(c @ x), None
        out, _ = jax.lax.scan(body, c, xs)
        return out

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    xs = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    est = jaxpr_cost.estimate(f, a, xs)
    expect = 10 * 2 * 256 ** 3
    assert expect <= est["flops"] < expect * 1.05


def test_jaxpr_cost_counts_grad_and_remat():
    def loss(w, x):
        h = x
        for _ in range(2):
            h = jnp.tanh(h @ w)
        return jnp.sum(h ** 2)

    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    fwd = jaxpr_cost.estimate(loss, w, x)["flops"]
    g = jaxpr_cost.estimate(jax.grad(loss), w, x)["flops"]
    assert g > 2.0 * fwd  # backward ~2x forward matmul cost


def test_jaxpr_cost_handles_jit_and_custom_vjp():
    @jax.custom_vjp
    def f(x, w):
        return x @ w

    def fwd(x, w):
        return x @ w, (x, w)

    def bwd(res, g):
        x, w = res
        return g @ w.T, x.T @ g

    f.defvjp(fwd, bwd)

    def loss(x, w):
        return jnp.sum(jax.jit(f)(x, w))

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    est = jaxpr_cost.estimate(jax.grad(loss, argnums=(0, 1)), x, x)
    assert est["flops"] >= 3 * 2 * 64 ** 3  # fwd + two bwd matmuls


def test_hlo_collective_parse_trip_counts():
    hlo = """
%body.1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ar = f32[128]{0} all-reduce(%x), replica_groups={}
}

%cond.1 (p: (s32[], f32[128])) -> pred[] {
}

ENTRY %main (a: f32[128]) -> f32[128] {
  %w = (s32[], f32[128]) while(%t), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"7"}}
  %ag = f32[512]{0} all-gather(%y), dimensions={0}
}
"""
    stats = hlo_collectives.parse(hlo)
    assert stats["all-reduce"]["count"] == 7
    assert stats["all-reduce"]["bytes"] == 7 * 128 * 4
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 512 * 4


def test_model_flops_formulas():
    from repro import configs
    from repro.configs.base import SHAPES
    from repro.roofline.analysis import model_flops
    cfg = configs.get("mistral-large-123b")
    f_train = model_flops(cfg, SHAPES["train_4k"])
    # 6 * ~123e9 params * 1.05M tokens ~ 7.7e17, attention adds a few %
    assert 7e17 < f_train < 1.4e18
    f_dec = model_flops(cfg, SHAPES["decode_32k"])
    assert f_dec < f_train / 1000
