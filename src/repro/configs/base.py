"""Architecture + shape configuration system.

Every assigned architecture is a frozen ArchConfig; reduced() derives the
CPU smoke-test variant of the same family. input_specs() produces
ShapeDtypeStruct stand-ins for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# Layer kinds usable in a period pattern.
GLOBAL = "global"   # full causal attention
LOCAL = "local"     # sliding-window attention
SSD = "ssd"         # mamba2 state-space duality block
RGLRU = "rglru"     # Griffin RG-LRU recurrent block


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense|moe|ssm|hybrid|audio|vlm|cnn
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    period: Tuple[str, ...]      # repeating layer-kind pattern
    # attention
    window: int = 4096
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 10_000.0
    qk_norm: bool = False
    head_dim: Optional[int] = None
    # mlp
    act: str = "silu"
    glu: bool = True
    # moe
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    # ssm (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    ssm_groups: int = 1
    conv_width: int = 4
    # rglru (griffin)
    lru_width: Optional[int] = None
    # multimodal stub frontend
    prefix_tokens: int = 0       # precomputed frame/patch embeddings
    # misc
    tie_embeddings: bool = True
    emb_scale: bool = False
    dtype: str = "bfloat16"
    vocab_pad_multiple: int = 128
    source: str = ""

    # ---- derived ----
    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // max(self.n_heads, 1)

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab + m - 1) // m) * m

    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.period)

    @property
    def remainder(self) -> Tuple[str, ...]:
        return self.period[: self.n_layers % len(self.period)]

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def lru_width_(self) -> int:
        return self.lru_width if self.lru_width else self.d_model

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + per-layer)."""
        d, hd = self.d_model, self.head_dim_
        n = self.padded_vocab * d  # embed (tied head)
        if not self.tie_embeddings:
            n += self.padded_vocab * d
        per_kind = {}
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        if self.is_moe:
            mlp = d * self.n_experts + self.n_experts * (
                (2 if self.glu else 1) * d * self.d_ff_expert + self.d_ff_expert * d)
        else:
            mlp = (2 if self.glu else 1) * d * self.d_ff + self.d_ff * d
        per_kind[GLOBAL] = attn + mlp + 2 * d
        per_kind[LOCAL] = attn + mlp + 2 * d
        di, N, H = self.d_inner, self.ssm_state, self.ssm_heads
        per_kind[SSD] = (d * (2 * di + 2 * self.ssm_groups * N + H) + di * d
                         + 3 * H + 2 * d + di)
        lw = self.lru_width_
        per_kind[RGLRU] = d * 2 * lw + lw * d + 2 * lw * lw + 3 * lw + 2 * d + mlp
        for i in range(self.n_layers):
            kind = self.period[i % len(self.period)]
            n += per_kind[kind]
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of n_experts)."""
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        full_moe = self.n_experts * ((2 if self.glu else 1) * d * self.d_ff_expert
                                     + self.d_ff_expert * d)
        active_moe = self.top_k * ((2 if self.glu else 1) * d * self.d_ff_expert
                                   + self.d_ff_expert * d)
        return self.param_count() - self.n_layers * (full_moe - active_moe)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# Archs allowed to run long_500k (sub-quadratic: SSM / hybrid-with-local-attn).
LONG_CONTEXT_OK = ("mamba2-370m", "recurrentgemma-9b")


def cells_for(arch: "ArchConfig"):
    """The (shape) cells this arch runs in the dry-run matrix."""
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if arch.name in LONG_CONTEXT_OK:
        names.append("long_500k")
    return [SHAPES[n] for n in names]


def input_specs(arch: ArchConfig, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input (dry-run, no alloc).

    train/prefill: full-sequence token batch (+labels for train).
    decode: one new token per sequence (the KV cache is part of serve state,
    built separately by serve.engine.cache_specs).
    """
    B, S = shape.global_batch, shape.seq_len
    specs = {}
    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    else:  # decode
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    if arch.prefix_tokens > 0 and shape.kind != "decode":
        specs["cond_embeddings"] = jax.ShapeDtypeStruct(
            (B, arch.prefix_tokens, arch.d_model), arch.compute_dtype)
    return specs


_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get(name: str) -> ArchConfig:
    from repro import configs as _c  # ensure registration side effects ran
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def names():
    from repro import configs as _c  # noqa: F401
    return sorted(_REGISTRY)


def depth_cut(cfg: ArchConfig, n_layers: int) -> ArchConfig:
    """The same architecture with only ``n_layers`` layers: every width
    (d_model, heads, d_ff, vocab, ...) is kept, and the layer pattern is
    cut in whole periods so each layer kind keeps its share."""
    p = len(cfg.period)
    if not (0 < n_layers <= cfg.n_layers and n_layers % p == 0):
        raise ValueError(
            f"{cfg.name}: --layers must be a multiple of the period length "
            f"{p} in [{p}, {cfg.n_layers}], got {n_layers}")
    return dataclasses.replace(cfg, n_layers=n_layers)


def reduced(cfg: ArchConfig, *, n_layers: Optional[int] = None,
            d_model: int = 128, seq: int = 64) -> ArchConfig:
    """A tiny same-family variant for CPU smoke tests."""
    period = cfg.period
    nl = n_layers if n_layers is not None else max(len(period), 2)
    n_heads = max(2, min(cfg.n_heads, 4))
    kv = max(1, min(cfg.n_kv_heads, n_heads))
    changes = dict(
        name=cfg.name + "-reduced",
        n_layers=nl,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=kv,
        head_dim=d_model // n_heads,
        d_ff=d_model * 3,
        vocab=512,
        window=min(cfg.window, max(seq // 2, 8)),
        vocab_pad_multiple=128,
    )
    if cfg.is_moe:
        changes.update(n_experts=4, top_k=2, d_ff_expert=d_model * 2)
    if SSD in period:
        changes.update(ssm_state=16, ssm_head_dim=32, ssm_chunk=16)
    if RGLRU in period:
        changes.update(lru_width=d_model)
    if cfg.prefix_tokens:
        changes.update(prefix_tokens=8)
    return dataclasses.replace(cfg, **changes)
