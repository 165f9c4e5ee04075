"""The granite-4.0-h-small MoE layer at a tiny size of the same family:
dropless routing under a skewed router against the plain float32
reference, and the expert shares summing to the uncut layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import expert_share
from repro.models import common, moe

from test_granite import TINY, _cfg, ref, ref_conf, weights

_dropless = jax.jit(moe.moe_dropless, static_argnums=2)


def _moe_params(cfg, seed=7):
    shapes = moe.moe_init(common.ParamFactory("shape", dtype=jnp.float32),
                          cfg)
    return weights.generator({"moe": shapes})(weights.seed_key(seed))["moe"]


def test_dropless_under_a_skewed_router():
    """Every token routes to the same top-k experts; a capacity would drop
    most of them, the dropless path keeps every one."""
    cfg = _cfg(shard=0)
    m = _moe_params(cfg)
    m["router"] = m["router"].at[:, :cfg.top_k].add(5.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, 64, cfg.d_model)))
    out, stats = _dropless(m, h, cfg)
    want = ref._ffn(m, h[0], ref_conf(cfg), "f32")
    # float32 both; gathered and sorted rows change summation order only
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               atol=1e-5, rtol=1e-4)
    # experts 0-3 are top for every token; experts 0-1 are held here
    assert int(stats["held"]) == 64 * cfg.experts_held
    assert int(stats["max_load"]) == 64


def test_expert_shares_sum_to_the_uncut_layer():
    whole = dataclasses.replace(TINY, dtype="float32")
    m = _moe_params(whole)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole.d_model))
    full, _ = _dropless(m, h, whole)
    parts = []
    for s in range(8):
        cfg = _cfg(shard=s)
        lo, hi = cfg.expert_lo, cfg.expert_lo + cfg.experts_held
        ms = {k: (v[lo:hi] if k in ("w_in", "w_gate", "w_out") else v)
              for k, v in m.items()}
        parts.append(_dropless(ms, h, cfg)[0])
    shared = moe._shared(m, h, jnp.zeros_like(h), whole)
    total = sum(parts) - 7 * shared  # the shared expert counted once
    # float32; the shares add the same terms in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               atol=1e-5, rtol=1e-4)


def test_expert_share_holds_one_slice():
    cfg = expert_share(configs.get("granite-4.0-h-small"), 3, 8)
    assert (cfg.experts_held, cfg.expert_lo, cfg.n_experts) == (9, 27, 72)


@pytest.mark.parametrize("share", ["0/7", "8/8", "-1/8"])
def test_expert_share_must_divide_the_experts(share):
    shard, shards = (int(v) for v in share.split("/"))
    with pytest.raises(ValueError):
        expert_share(configs.get("granite-4.0-h-small"), shard, shards)
