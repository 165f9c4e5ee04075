"""SPMD correctness worker — run in a subprocess with 8 host devices.

Checks (each prints PASS <name>):
  sharded_vs_single : pjit train step == single-device numerics
  sharded_embed     : shard_map lookup == plain gather
  pipeline          : GPipe ppermute schedule == sequential stages
  grad_compress     : psum_compressed error-feedback collective
  elastic           : checkpoint saved on (4,2) mesh restores on (2,2)
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs, policies
from repro.configs.base import reduced
from repro.distributed import pipeline as pp, sharding as shd
from repro.models import common
from repro.models.model import DecoderModel
from repro.optim.schedule import Schedule
from repro.train import grad_compress, step as step_mod
from repro.train.state import TrainState


def make_mesh():
    return jax.make_mesh((4, 2), ("data", "model"))


def test_sharded_vs_single():
    cfg = dataclasses.replace(reduced(configs.get("gemma2-2b")),
                              dtype="float32")
    tc = step_mod.TrainConfig(schedule=Schedule(total_steps=5,
                                                warmup_steps=0),
                              num_microbatches=2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

    # single-device reference
    model0 = DecoderModel(cfg, policies.get("none"))
    step0 = jax.jit(step_mod.make_train_step(model0, tc))
    state0 = step_mod.init_state(model0, jax.random.PRNGKey(0), tc)
    s0, m0 = step0(state0, batch)

    # sharded
    mesh = make_mesh()
    rules = shd.rules_for(mesh)
    model1 = DecoderModel(cfg, policies.get("none"), mesh=mesh)
    step1 = step_mod.make_train_step(model1, tc)
    state1 = step_mod.init_state(model1, jax.random.PRNGKey(0), tc)
    param_sh = shd.tree_shardings(mesh, model1.param_axes(), rules)
    param_sh = shd.refine_shardings(jax.eval_shape(lambda: state1.params),
                                    param_sh, mesh)
    repl = shd.replicated(mesh)
    state_sh = TrainState(
        params=param_sh,
        opt=state1.opt._replace(m=param_sh, v=param_sh, count=repl),
        pstate=jax.tree.map(lambda _: repl, state1.pstate),
        step=repl, rng=repl, grad_residual=None)
    batch_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    with mesh:
        jstep = jax.jit(step1, in_shardings=(state_sh, batch_sh))
        state1 = jax.device_put(state1, state_sh)
        batch1 = jax.device_put(batch, batch_sh)
        s1, m1 = jstep(state1, batch1)

    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=2e-4)
    np.testing.assert_allclose(float(m0["grad_norm"]),
                               float(m1["grad_norm"]), rtol=2e-3)
    # parameters after one step agree
    w0 = jax.tree.leaves(s0.params)[1]
    w1 = jax.tree.leaves(s1.params)[1]
    np.testing.assert_allclose(np.asarray(w0), np.asarray(w1),
                               atol=3e-5, rtol=1e-3)
    print("PASS sharded_vs_single")


def test_sharded_embed():
    mesh = make_mesh()
    table = jax.random.normal(jax.random.PRNGKey(0), (64, 16), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 5), 0, 64)
    with mesh:
        table_s = jax.device_put(
            table, NamedSharding(mesh, P("model", None)))
        got = jax.jit(lambda t, tok: common.sharded_embed(t, tok, mesh))(
            table_s, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(table[tokens]),
                               rtol=1e-6)
    print("PASS sharded_embed")


def test_pipeline():
    mesh = jax.make_mesh((8,), ("pipe",))
    S, d = 8, 16
    ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.3

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (6, 4, d))  # 6 microbatches
    got = pp.pipeline_apply(stage_fn, ws, x, mesh, axis="pipe")

    want = x
    for s in range(S):
        want = jax.vmap(lambda mb: stage_fn(ws[s], mb))(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    print("PASS pipeline")


def test_grad_compress():
    mesh = jax.make_mesh((8,), ("pods",))
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 32))}
    res = {"w": jnp.zeros((8, 32))}

    def f(g, r):
        def local(gl, rl):
            out, new_r = grad_compress.psum_compressed(
                {"w": gl}, {"w": rl}, bits=3, axis_name="pods")
            return out["w"], new_r["w"]
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P("pods", None), P("pods", None)),
                             out_specs=(P(None, None), P("pods", None)),
                             check_vma=False)(g, r)

    summed, new_res = jax.jit(f)(grads["w"], res["w"])
    exact = jnp.mean(grads["w"], axis=0)
    got = summed[0]
    # 3-bit mantissa + bf16 wire: coarse but correlated; residual holds error
    cos = float(jnp.sum(got * exact)
                / (jnp.linalg.norm(got) * jnp.linalg.norm(exact)))
    assert cos > 0.97, cos
    assert float(jnp.max(jnp.abs(new_res))) > 0
    print("PASS grad_compress")


def test_elastic():
    from repro.checkpoint.manager import CheckpointManager
    from repro.distributed import elastic

    cfg = reduced(configs.get("gemma2-2b"))
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8))}
    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    tree_a = jax.device_put(tree, NamedSharding(mesh_a, P("data", "model")))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, tree_a)
        # "lose" half the fleet: remesh to (2, 2)
        plan = elastic.plan_remesh(4, cfg, global_batch=8, prefer_tp=2)
        mesh_b = elastic.build_mesh(plan)
        sh_b = {"w": NamedSharding(mesh_b, P("data", "model"))}
        back = mgr.restore(1, tree, shardings=sh_b)
        np.testing.assert_allclose(np.asarray(back["w"]),
                                   np.asarray(tree["w"]), rtol=1e-6)
    print("PASS elastic")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    tests = {
        "sharded_vs_single": test_sharded_vs_single,
        "sharded_embed": test_sharded_embed,
        "pipeline": test_pipeline,
        "grad_compress": test_grad_compress,
        "elastic": test_elastic,
    }
    if which == "all":
        for f in tests.values():
            f()
    else:
        tests[which]()
    print("ALL OK")
