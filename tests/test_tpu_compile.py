"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX, so each kernel is lowered with
``interpret=False`` and compiled for a described (not attached) v5e chip
at the widths the system serves and trains: mistral-large-123b decode
(8 KV heads x 12 q heads per group, head_dim 128, batch 8, 128-slot KV
blocks), a 16384-row stash pack, flash attention at S=1024. What Mosaic
refuses here (unaligned blocks, narrow-int shifts, width-changing
bitcasts) would fail on the chip. Nothing runs, so results are checked by
the interpret-mode and reference tests, not here.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and pytest-xdist workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import codecs, configs
from repro.kernels import bitplane_pack, flash_attention, ops
from repro.kernels import packed_flash_decode as pfd
from repro.kernels import sfp_pack

KH, REP, HD, B, BLOCK_L = 8, 12, 128, 8, 128  # mistral-large-123b decode
H, D = KH * REP, KH * HD


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("container", ["sfp8", "sfp-m2e4"])
def test_paged_flash_decode_compiles(one_chip, container):
    f = codecs.fields_for(container, jnp.bfloat16)
    n_phys, nb = 64, 16
    part = _spec(one_chip, (n_phys, BLOCK_L, f.nd_payload_cols(D)),
                 f.payload_dtype)
    bases = _spec(one_chip, (n_phys, BLOCK_L, D // 128), jnp.uint8)
    compiled = _compile(
        lambda q, kp, kb, vp, vb, t, p: pfd.paged_flash_decode(
            q, kp, kb, vp, vb, t, p, fields=f, interpret=False),
        _spec(one_chip, (B, 1, H, HD), jnp.bfloat16), part, bases, part,
        bases, _spec(one_chip, (B, nb), jnp.int32),
        _spec(one_chip, (B,), jnp.int32))
    _assert_kernel(compiled)
    # the op the benchmark's trace reduction matches, named by geometry
    geometry = {"sfp8": "lanes", "sfp-m2e4": "planes"}[container]
    assert f"%paged_flash_decode_{geometry}." in compiled.as_text()


@pytest.mark.parametrize("container", ["sfp8", "sfp-m2e4"])
def test_packed_flash_decode_compiles(one_chip, container):
    f = codecs.fields_for(container, jnp.bfloat16)
    L = 2048
    part = _spec(one_chip, (B, L, f.nd_payload_cols(D)), f.payload_dtype)
    bases = _spec(one_chip, (B, L, D // 128), jnp.uint8)
    _assert_kernel(_compile(
        lambda q, kp, kb, vp, vb, p: pfd.packed_flash_decode(
            q, kp, kb, vp, vb, p, fields=f, interpret=False),
        _spec(one_chip, (B, 1, H, HD), jnp.bfloat16), part, bases, part,
        bases, _spec(one_chip, (B,), jnp.int32)))


@pytest.mark.parametrize("container,kernel", [
    ("sfp8", sfp_pack.sfp_quantize_pack),
    ("sfp-m2e4", bitplane_pack.bitplane_quantize_pack)])
def test_fused_quantize_pack_compiles(one_chip, container, kernel):
    f = codecs.fields_for(container, jnp.bfloat16)
    _assert_kernel(_compile(
        lambda x, n: kernel(x, n, fields=f, interpret=False),
        _spec(one_chip, (16384, 128), jnp.bfloat16),
        _spec(one_chip, (), jnp.int32)))


def test_flash_attention_gqa_compiles(one_chip):
    S = 1024
    kv = _spec(one_chip, (1, S, KH, HD), jnp.bfloat16)
    _assert_kernel(_compile(
        lambda q, k, v: flash_attention.flash_attention(
            q, k, v, q_rep=REP, interpret=False),
        _spec(one_chip, (1, S * REP, KH, HD), jnp.bfloat16), kv, kv))


def test_attention_train_grad_compiles(one_chip):
    """Training attention at S <= 1024 goes through the flash kernel; its
    gradient (the kernel's reference VJP) must compile for the chip."""
    from repro.configs.base import depth_cut
    from repro.models import attention
    from repro.models.model import DecoderModel

    cfg = depth_cut(configs.get("mistral-large-123b"), 1)
    shapes = DecoderModel(cfg).param_shapes()["periods"]["slot0"]["attn"]
    params = jax.tree.map(
        lambda s: _spec(one_chip, s.shape[1:], s.dtype), shapes)
    S = 1024

    def loss(p, h):
        out = attention.attention_train(p, h, cfg, kind="global",
                                        positions=jnp.arange(S))
        return out.astype(jnp.float32).sum()

    ops.force_backend("pallas")
    try:
        _compile(jax.grad(loss, argnums=(0, 1)), params,
                 _spec(one_chip, (1, S, cfg.d_model), jnp.bfloat16))
    finally:
        ops.force_backend(None)
