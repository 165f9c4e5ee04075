"""Logical-axis sharding rules: one place that decides how everything shards.

Parameters carry *logical* axis names (from common.ParamFactory axes mode);
this module maps them onto mesh axes:

  embed    -> data   (FSDP / ZeRO-3: weights shard their non-TP dim over the
                      data axis; XLA all-gathers per scan step and
                      reduce-scatters gradients)
  heads/ff/vocab/experts/lru/ssm_inner -> model   (tensor parallelism;
                      experts over model = expert parallelism)
  batch    -> (pod, data)
  cache_seq-> model  (decode KV cache shards its sequence dim — the softmax
                      reductions become exact XLA all-reduces, flash-decoding
                      style)

Anything unlisted is replicated. Divisibility is not required (GSPMD pads
uneven shards); rules only choose *where* things live.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def rules_for(mesh: Mesh, *, fsdp: bool = True, layout: str = "tp"
              ) -> Dict[str, Optional[Tuple[str, ...]]]:
    """Logical->mesh mapping.

    layout='tp'   : TP over `model` (heads/ff/vocab/experts) + FSDP over
                    `data` — the default; right when per-device batch is
                    large enough to amortize the 2-per-layer activation
                    all-reduces.
    layout='fsdp' : ZeRO-3 over BOTH axes — weights and batch shard over
                    (data x model); no tensor parallelism, so the only
                    collectives are per-layer parameter all-gathers (bf16)
                    and gradient reduce-scatters. Wins when activation
                    all-reduce traffic dominates (large d_model, small
                    per-device batch) — see EXPERIMENTS.md §Perf.
    """
    multi_pod = "pod" in mesh.axis_names
    if layout == "fsdp":
        batch_axes = (("pod", "data", "model") if multi_pod
                      else ("data", "model"))
        return {
            "embed": batch_axes,
            "embed_r": None,
            "heads": None, "ff": None, "expert_ff": None, "vocab": None,
            "experts": ("model",),  # EP still pays off for MoE
            "lru": None, "ssm_inner": None, "state": None,
            "conv": None, "norm": None, "layers": None,
            "batch": batch_axes,
            "seq": None,
            "cache_seq": ("model",),
            "kv": None,
        }
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return {
        # weights
        "embed": ("data",) if fsdp else None,
        "embed_r": None,  # embedding/head model dim (lookup shards vocab)
        "heads": ("model",),
        "ff": ("model",),
        "expert_ff": None,
        "vocab": ("model",),
        "experts": ("model",),
        "lru": ("model",),
        "ssm_inner": ("model",),
        "state": None,
        "conv": None,
        "norm": None,
        "layers": None,
        # activations / caches
        "batch": batch_axes,
        "seq": None,
        "cache_seq": ("model",),
        "kv": None,
    }


def spec_from_axes(axes: Tuple[Optional[str], ...],
                   rules: Dict[str, Optional[Tuple[str, ...]]]) -> P:
    parts = []
    used = set()
    for ax in axes:
        target = rules.get(ax) if ax is not None else None
        if target is None:
            parts.append(None)
            continue
        # A mesh axis may appear only once per spec; later dims replicate.
        target = tuple(t for t in target if t not in used)
        if not target:
            parts.append(None)
            continue
        used.update(target)
        parts.append(target if len(target) > 1 else target[0])
    return P(*parts)


def tree_specs(axes_tree: Any, rules) -> Any:
    """Map a tree of logical-axes tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda a: spec_from_axes(a, rules), axes_tree,
        is_leaf=lambda a: isinstance(a, tuple) and all(
            x is None or isinstance(x, str) for x in a))


def tree_shardings(mesh: Mesh, axes_tree: Any, rules=None) -> Any:
    rules = rules or rules_for(mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_specs(axes_tree, rules),
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs(rules, kind: str, has_cond: bool) -> Dict[str, P]:
    b = rules["batch"]
    b = b if not isinstance(b, tuple) or len(b) > 1 else b[0]
    specs = {"tokens": P(b, None)}
    if kind == "train":
        specs["labels"] = P(b, None)
    if has_cond and kind != "decode":
        specs["cond_embeddings"] = P(b, None, None)
    return specs


def refine_shardings(shapes_tree: Any, shardings_tree: Any, mesh: Mesh) -> Any:
    """Drop sharding on dims the mesh axes don't divide (e.g. batch=1 cells).

    GSPMD pads uneven shardings for intermediates, but jit in_shardings
    require exact divisibility — this filters per-leaf against the actual
    ShapeDtypeStruct.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def refine(shape_leaf, sh):
        if not isinstance(sh, NamedSharding):
            return sh
        spec = sh.spec
        parts = []
        for i, ax in enumerate(spec):
            if ax is None or i >= len(shape_leaf.shape):
                parts.append(ax)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            prod = 1
            for a in axes:
                prod *= sizes[a]
            parts.append(ax if shape_leaf.shape[i] % prod == 0 else None)
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(refine, shapes_tree, shardings_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# --- trace-time sharding hints -------------------------------------------
# GSPMD propagation sometimes resolves conflicting uses by replicating a
# big tensor ("involuntary full rematerialization", e.g. a KV-cache update
# whose new token arrives heads-sharded). Models set the active mesh once;
# hint() places with_sharding_constraint only when a mesh is active.

_ACTIVE_MESH: list = [None]
_ACTIVE_RULES: list = [None]


def set_active_mesh(mesh: Optional[Mesh], rules=None) -> None:
    _ACTIVE_MESH[0] = mesh
    _ACTIVE_RULES[0] = rules if rules is not None else (
        rules_for(mesh) if mesh is not None else None)


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[0]


def active_rules():
    return _ACTIVE_RULES[0]


def hint(x, *spec):
    mesh = _ACTIVE_MESH[0]
    if mesh is None:
        return x
    spec = spec + (None,) * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def batch_axis_for(mesh: Mesh, size: int):
    rules = _ACTIVE_RULES[0] or rules_for(mesh)
    axes = rules["batch"]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    if size % n != 0:
        return None
    return axes if len(axes) > 1 else axes[0]


def heads_target() -> Optional[str]:
    """Mesh axis for attention heads under the active rules (None = don't
    shard heads; e.g. the fsdp layout keeps them replicated)."""
    rules = _ACTIVE_RULES[0]
    if rules is None:
        return "model"
    t = rules.get("heads")
    return t[0] if t else None


def model_axis_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("model", 1)
