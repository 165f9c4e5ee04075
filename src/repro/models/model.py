"""Unified decoder model over the assigned architecture families.

One `DecoderModel` covers dense / MoE / SSM / hybrid / audio / vlm configs:
the repeating layer-kind *period* (e.g. gemma3's 5xlocal+global) is scanned
with stacked parameters via core.stash.sfp_scan, so (a) HLO size is
depth-independent and (b) the cross-pass activation stash is exactly the
SFP-compressed containers — the paper's technique as a first-class feature
of the training step. Remainder layers (n_layers % len(period)) are
unrolled.

The same parameter tree supports three views (params / ShapeDtypeStruct /
logical sharding axes) via common.ParamFactory — the dry-run lowers the
full-size models without allocating anything.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import codecs, policies
from repro.configs.base import ArchConfig, GLOBAL, LOCAL, RGLRU, SSD
from repro.core import containers, stash
from repro.distributed import sharding as shd
from repro.models import attention, common, mamba2, moe, rglru

MOE_LB_COEF = 0.01
MOE_Z_COEF = 1e-3


class RunState(NamedTuple):
    """Per-step dynamic inputs controlling precision behaviour.

    ``pol`` is the policy's forward view — an opaque pytree produced by
    ``Policy.forward_view`` and only ever handed back to policy methods;
    the model never inspects it.
    """

    key: jax.Array  # PRNG key for this step
    pol: Any        # policy forward view (possibly empty)


def scope_dims(cfg: ArchConfig) -> policies.ScopeDims:
    return policies.ScopeDims.for_dtype(
        cfg.compute_dtype, n_periods=cfg.n_periods,
        n_rem=len(cfg.remainder))


def init_run_state(cfg: ArchConfig, key: jax.Array,
                   policy=None) -> RunState:
    """A fresh-state RunState for ``policy`` (default: full precision)."""
    pol = policies.coerce(policy)
    dims = scope_dims(cfg)
    st = pol.init_state(dims)
    cview = pol.control_view(st.ctrl, dims)
    return RunState(key=key, pol=pol.forward_view(st.learn, cview, dims))


def _zero_moe_aux():
    z = jnp.zeros((), jnp.float32)
    return {"moe_lb_loss": z, "moe_z_loss": z, "moe_drop_frac": z}


def _kvcache():
    # Deferred: serve.kvcache sits above models in the layer order (it
    # imports models.attention); a module-level import would be cyclic-ish.
    from repro.serve import kvcache
    return kvcache


def _split_pools(caches):
    """({slot: paged pool}, {slot: any other cache} or None) of one
    group of layer caches (None without a cache: prefill, training)."""
    if caches is None:
        return {}, None
    paged = _kvcache().PagedKV
    pools = {k: c for k, c in caches.items() if isinstance(c, paged)}
    return pools, {k: c for k, c in caches.items() if k not in pools}


class DecoderModel:
    def __init__(self, cfg: ArchConfig, policy=None, mesh=None,
                 rules=None, kv_container: Optional[str] = None,
                 stash_containers=None):
        """``policy`` is a precision policy: a ``policies.Policy``, a
        registry name (``"qm"``, ``"qm+qe"``, ...), a legacy
        ``core.sfp.SFPPolicy`` (deprecated shim), or None for full
        precision. ``kv_container`` selects a registry codec for the
        serving KV cache: prefill packs the cache, decode splices packed
        token rows and attends through the fused decompress-attend kernel
        (SFP codecs on pallas/interpret) or the unpack fallback. None =
        raw bf16/fp32 cache.

        ``stash_containers`` (optional, one codec name per period) packs
        each period's activation stash at its *own* container geometry —
        per-layer realized containers instead of one network-wide choice.
        Container geometry is static under jit, so the period scan is
        chained into per-period segments (HLO grows with n_periods);
        derive the tuple from the live policy state with ``stash_plan``
        and rebuild the jitted step when it changes (learned bitlengths
        move slowly, so re-lowering is rare).
        """
        self.cfg = cfg
        self.policy = policies.coerce(policy)
        self.mesh = mesh  # enables SPMD-manual paths (sharded embed lookup)
        self.rules = rules
        self.kv_container = kv_container
        if stash_containers is not None:
            stash_containers = tuple(stash_containers)
            if len(stash_containers) != cfg.n_periods:
                raise ValueError(
                    f"stash_containers needs one codec per period "
                    f"({cfg.n_periods}), got {len(stash_containers)}")
        self.stash_containers = stash_containers
        self.man_bits = containers.spec_for(cfg.compute_dtype).man_bits
        self.dims = scope_dims(cfg)

    def run_state(self, key: jax.Array,
                  pstate: Optional[policies.PolicyState] = None) -> RunState:
        """Build the forward view for this model's policy (fresh state if
        ``pstate`` is None — the train step builds its own from live
        state)."""
        pol = self.policy
        if pstate is None:
            pstate = pol.init_state(self.dims)
        cview = pol.control_view(pstate.ctrl, self.dims)
        return RunState(key=key,
                        pol=pol.forward_view(pstate.learn, cview, self.dims))

    # ------------------------------------------------------------------
    # Parameter construction (params / shapes / axes share one code path)
    # ------------------------------------------------------------------

    def _slot_init(self, p: common.ParamFactory, kind: str):
        cfg = self.cfg
        slot: Dict[str, Any] = {"pre_norm": common.rmsnorm_init(p, cfg.d_model)}
        if kind in (GLOBAL, LOCAL):
            slot["attn"] = attention.attn_init(p, cfg)
        elif kind == SSD:
            slot["ssd"] = mamba2.ssd_init(p, cfg)
            if not cfg.ssd_mlp:
                return slot  # mamba2 blocks carry no separate MLP
        elif kind == RGLRU:
            slot["rglru"] = rglru.rglru_init(p, cfg)
        else:  # pragma: no cover
            raise ValueError(kind)
        slot["mlp_norm"] = common.rmsnorm_init(p, cfg.d_model)
        if cfg.is_moe:
            slot["moe"] = moe.moe_init(p, cfg)
        else:
            slot["mlp"] = common.mlp_init(p, cfg.d_model, cfg.d_ff, cfg.glu)
        return slot

    def _build_period(self, p: common.ParamFactory):
        return {f"slot{i}": self._slot_init(p, kind)
                for i, kind in enumerate(self.cfg.period)}

    def build(self, mode: str, key: Optional[jax.Array] = None):
        cfg = self.cfg
        dtype = cfg.compute_dtype

        if mode == common.MODE_PARAMS:
            pf = common.ParamFactory(mode, jax.random.fold_in(key, 0), dtype)
            keys = jax.random.split(jax.random.fold_in(key, 1), cfg.n_periods)
            periods = jax.vmap(
                lambda k: self._build_period(
                    common.ParamFactory(mode, k, dtype)))(keys)
        else:
            pf = common.ParamFactory(mode, dtype=dtype)
            one = self._build_period(common.ParamFactory(mode, dtype=dtype))
            if mode == common.MODE_SHAPE:
                periods = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        (cfg.n_periods,) + tuple(s.shape), s.dtype), one)
            else:  # axes
                periods = jax.tree.map(
                    lambda a: ("layers",) + tuple(a), one,
                    is_leaf=lambda a: isinstance(a, tuple))

        params: Dict[str, Any] = {
            "embed": common.embed_init(pf, cfg.padded_vocab, cfg.d_model),
            "final_norm": common.rmsnorm_init(pf, cfg.d_model),
            "periods": periods,
        }
        if not cfg.tie_embeddings:
            params["head"] = pf((cfg.d_model, cfg.padded_vocab),
                                ("embed_r", "vocab"))
        if cfg.remainder:
            rem_pf = (common.ParamFactory(mode, jax.random.fold_in(key, 2),
                                          dtype)
                      if mode == common.MODE_PARAMS
                      else common.ParamFactory(mode, dtype=dtype))
            params["rem"] = {f"slot{i}": self._slot_init(rem_pf, kind)
                             for i, kind in enumerate(cfg.remainder)}
        return params

    def init(self, key: jax.Array):
        return self.build(common.MODE_PARAMS, key)

    def param_shapes(self):
        return self.build(common.MODE_SHAPE)

    def param_axes(self):
        return self.build(common.MODE_AXES)

    # ------------------------------------------------------------------
    # Weight-side fake-quant (exact VJP for learned policies, paper §IV-A)
    # ------------------------------------------------------------------

    def _quantize_weights(self, slot_params, pslice, key):
        pol = self.policy
        if not pol.quantizes_weights:
            return slot_params

        def quant(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if leaf.ndim < 2 or not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            salt = zlib.crc32(name.encode()) % (2 ** 31)
            return pol.quantize_weight(leaf, pslice,
                                       jax.random.fold_in(key, salt),
                                       self.dims)

        return jax.tree_util.tree_map_with_path(quant, slot_params)

    # ------------------------------------------------------------------
    # Layer application
    # ------------------------------------------------------------------

    def _apply_slot(self, slot_params, h, kind, *, positions, prefix_len,
                    pslice, key):
        cfg = self.cfg
        sp = self._quantize_weights(slot_params, pslice, key)
        aux = _zero_moe_aux()
        extras_loss = jnp.zeros((), jnp.float32)

        hn = common.rmsnorm(sp["pre_norm"], h, cfg.norm_eps)
        if kind in (GLOBAL, LOCAL):
            h = self._res(h, attention.attention_train(
                sp["attn"], hn, cfg, kind=kind, positions=positions,
                prefix_len=prefix_len))
        elif kind == SSD:
            h = self._res(h, mamba2.ssd_forward(sp["ssd"], hn, cfg))
            if not cfg.ssd_mlp:
                return h, extras_loss, aux
        elif kind == RGLRU:
            h = h + rglru.rglru_forward(sp["rglru"], hn, cfg)

        h, moe_aux, _ = self._ffn(sp, h, dropless=False)
        if moe_aux is not None:
            aux = moe_aux
            extras_loss = (MOE_LB_COEF * moe_aux["moe_lb_loss"]
                           + MOE_Z_COEF * moe_aux["moe_z_loss"])
        return h, extras_loss, aux

    def _res(self, h, out):
        """The residual add, the branch scaled by ``residual_mult``."""
        r = self.cfg.residual_mult
        return h + out if r == 1.0 else h + out * jnp.asarray(r, out.dtype)

    def _ffn(self, sp, h, *, dropless: bool):
        """The layer's MLP or MoE on the residual stream: (h, aux losses
        or None, dropless MoE counters or None). Serving routes dropless;
        training drops what passes ``capacity_factor``."""
        cfg = self.cfg
        hm = common.rmsnorm(sp["mlp_norm"], h, cfg.norm_eps)
        if not cfg.is_moe:
            return (self._res(h, common.mlp(sp["mlp"], hm, cfg.act, cfg.glu)),
                    None, None)
        if dropless:
            out, stats = moe.moe_dropless(sp["moe"], hm, cfg)
            return self._res(h, out), None, stats
        out, aux = moe.moe_forward(sp["moe"], hm, cfg)
        return self._res(h, out), aux, None

    def _embed(self, params, tokens):
        return common.embed(params["embed"], tokens, self.cfg.emb_mult,
                            mesh=self.mesh)

    def _logits(self, params, h):
        cfg = self.cfg
        h = common.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return common.unembed(params, h, tied=cfg.tie_embeddings,
                              softcap=cfg.final_softcap,
                              valid_vocab=cfg.vocab, div=cfg.logits_div)

    # ------------------------------------------------------------------
    # Stash codec (compress/decompress at period boundaries)
    # ------------------------------------------------------------------

    def _make_codec(self, dtype):
        del dtype  # carried by the packed representation itself
        if not self.policy.enabled:
            return stash.identity_compress, stash.identity_decompress, None
        return self._codec_fns(codecs.get(self.policy.container))

    def _codec_fns(self, codec):
        """Stash compress/decompress/stash_grad closures for one codec."""
        pol = self.policy
        dims = self.dims

        def compress(h, x):
            # Fused quantize+pack: the mantissa-bitlength signal rides into
            # the pack kernel, one HBM read of the activation. Exponent
            # truncation (QE/BitWave) happens on the way in — the packed
            # container stores the already-clamped exponents, which is what
            # Gecko-side accounting compresses.
            d = pol.act_decision(x["pol"], x["key"], dims)
            if pol.adapts_exponent:
                h = containers.truncate_exponent(h, d.exp_bits)
            return codec.pack(h, bits=d.man_bits)

        def decompress(c, x):
            del x
            return codec.unpack(c)

        stash_grad = None
        if pol.has_stash_grad:
            def stash_grad(dh, c, x):  # noqa: F811
                h_q = decompress(c, x)
                return {"pol": pol.stash_grad(dh, h_q, x["pol"], dims)}

        return compress, decompress, stash_grad

    def stash_plan(self, pstate: Optional[policies.PolicyState] = None
                   ) -> Tuple[str, ...]:
        """Per-period dense container names realized from the policy's
        current per-layer decisions.

        Host-side: call it outside jit (fresh state when ``pstate`` is
        None), pass the result as ``stash_containers`` to a new
        DecoderModel (or rebuild the jitted step) whenever the plan
        changes. Each period's learned (man_bits, exp_bits) maps through
        ``codecs.dense_name`` — so a period that converged to 2 mantissa /
        4 exponent bits stashes 7-bit dense payloads while a
        precision-hungry neighbour keeps a wider container.
        """
        pol = self.policy
        st = pol.init_state(self.dims) if pstate is None else pstate
        return tuple(codecs.dense_name(m, e)
                     for m, e in pol.layer_decisions(st, self.dims))

    # ------------------------------------------------------------------
    # Training / prefill forward
    # ------------------------------------------------------------------

    def forward(self, params, tokens: jax.Array, run: RunState,
                cond_embeddings: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Full-sequence forward. Returns (logits over token positions, metrics)."""
        shd.set_active_mesh(self.mesh, self.rules)
        cfg = self.cfg
        B, S = tokens.shape
        P = cfg.prefix_tokens if cond_embeddings is not None else 0

        h = self._embed(params, tokens)
        if P:
            h = jnp.concatenate(
                [cond_embeddings.astype(h.dtype), h], axis=1)
        S_tot = h.shape[1]
        positions = jnp.arange(S_tot)

        compress, decompress, stash_grad = self._make_codec(
            cfg.compute_dtype)

        period = cfg.period

        pol = self.policy

        def period_fn(carry, x):
            h, extras = carry
            aux_sum = _zero_moe_aux()
            for i, kind in enumerate(period):
                h, eloss, aux = self._apply_slot(
                    x["params"][f"slot{i}"], h, kind,
                    positions=positions, prefix_len=P,
                    pslice=x.get("pol"),
                    key=jax.random.fold_in(x["key"], i))
                extras = extras + eloss
                aux_sum = jax.tree.map(lambda a, b: a + b, aux_sum, aux)
            return (h, extras), aux_sum

        keys = jax.random.split(run.key, cfg.n_periods)
        xs = {"params": params["periods"], "key": keys}
        if pol.enabled:
            xs["pol"] = pol.scan_slices(run.pol, self.dims)

        extras0 = jnp.zeros((), jnp.float32)
        if pol.enabled and self.stash_containers is not None:
            # Per-layer containers: each period's stash packs at its own
            # (static) geometry, so the scan is chained into one sfp_scan
            # segment per period — same custom-VJP remat structure, one
            # codec per segment. aux stacks back to the scanned layout.
            carry = (h, extras0)
            aux_parts = []
            for i, cname in enumerate(self.stash_containers):
                comp, decomp, sgrad = self._codec_fns(codecs.get(cname))
                xs_i = jax.tree.map(lambda a: a[i:i + 1], xs)
                carry, aux_i = stash.sfp_scan(period_fn, comp, decomp,
                                              carry, xs_i, stash_grad=sgrad)
                aux_parts.append(aux_i)
            h, extras = carry
            aux = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0),
                               *aux_parts)
        else:
            (h, extras), aux = stash.sfp_scan(
                period_fn, compress, decompress, (h, extras0), xs,
                stash_grad=stash_grad)

        # Remainder layers (unrolled, decision applied straight-through at
        # the stash boundary).
        for i, kind in enumerate(cfg.remainder):
            rs = (pol.rem_slice(run.pol, i, self.dims) if pol.enabled
                  else None)
            if pol.enabled:
                d = pol.act_decision(
                    rs, jax.random.fold_in(run.key, 1000 + i), self.dims)
                h = policies.apply_decision_ste(
                    h, d, self.dims, adapts_exponent=pol.adapts_exponent)
            h, eloss, _aux = self._apply_slot(
                params["rem"][f"slot{i}"], h, kind, positions=positions,
                prefix_len=P, pslice=rs,
                key=jax.random.fold_in(run.key, 2000 + i))
            extras = extras + eloss

        if P:
            h = h[:, P:]
        logits = self._logits(params, h)
        metrics = {"moe_aux_loss": extras}
        for k in ("moe_lb_loss", "moe_z_loss", "moe_drop_frac"):
            metrics[k] = aux[k].mean() if cfg.is_moe else jnp.zeros((), jnp.float32)
        return logits, metrics

    def loss(self, params, batch: Dict[str, jax.Array], run: RunState
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, metrics = self.forward(
            params, batch["tokens"], run,
            cond_embeddings=batch.get("cond_embeddings"))
        xent = common.softmax_xent(logits, batch["labels"])
        loss = xent + metrics["moe_aux_loss"]
        metrics = dict(metrics, xent=xent)
        return loss, metrics

    # ------------------------------------------------------------------
    # Serving: cache init + prefill + decode
    # ------------------------------------------------------------------

    def _slot_cache(self, kind: str, batch: int, max_len: int, spec_only: bool):
        cfg = self.cfg
        dt = cfg.compute_dtype
        if kind in (GLOBAL, LOCAL):
            if self.kv_container is not None:
                kvc = _kvcache()
                f = (kvc.packed_cache_spec if spec_only
                     else kvc.packed_cache_init)
                return f(cfg, kind, batch, max_len, self.kv_container)
            f = attention.cache_spec if spec_only else attention.cache_init
            return f(cfg, kind, batch, max_len, dt)
        if kind == SSD:
            f = mamba2.ssd_cache_spec if spec_only else mamba2.ssd_cache_init
            return f(cfg, batch, dt)
        f = rglru.lru_cache_spec if spec_only else rglru.lru_cache_init
        return f(cfg, batch, dt)

    def init_cache(self, batch: int, max_len: int, spec_only: bool = False):
        cfg = self.cfg
        per = {f"slot{i}": self._slot_cache(k, batch, max_len, spec_only)
               for i, k in enumerate(cfg.period)}
        if spec_only:
            periods = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (cfg.n_periods,) + tuple(s.shape), s.dtype), per)
        else:
            periods = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), per)
        cache = {"periods": periods}
        if cfg.remainder:
            cache["rem"] = {f"slot{i}": self._slot_cache(k, batch, max_len,
                                                         spec_only)
                            for i, k in enumerate(cfg.remainder)}
        return cache

    def _decode_slot(self, slot_params, h, slot_cache, pos, kind,
                     tables=None, prefix_planes=None):
        """One layer of a decode step: (h, its new cache, its dropless MoE
        counters or None)."""
        cfg = self.cfg
        hn = common.rmsnorm(slot_params["pre_norm"], h, cfg.norm_eps)
        if kind in (GLOBAL, LOCAL):
            if tables is not None and kind == GLOBAL:
                # Paged pool (slot_cache a kvcache.PoolLayer, new_cache
                # the updated stacked pool): blocks gathered through the
                # block table inside the kernel; local ring layers stay
                # per-slot contiguous (window-bounded) and take the
                # packed path below with per-row positions.
                out, new_cache = _kvcache().attention_decode_paged(
                    slot_params["attn"], hn, slot_cache, tables, pos, cfg,
                    container=self.kv_container,
                    prefix_planes=prefix_planes)
            elif self.kv_container is not None:
                out, new_cache = _kvcache().attention_decode_packed(
                    slot_params["attn"], hn, slot_cache, pos, cfg, kind=kind,
                    container=self.kv_container,
                    prefix_planes=prefix_planes)
            else:
                out, new_cache = attention.attention_decode(
                    slot_params["attn"], hn, slot_cache, pos, cfg, kind=kind)
            h = self._res(h, out)
        elif kind == SSD:
            out, new_cache = mamba2.ssd_decode(slot_params["ssd"], hn,
                                               slot_cache, cfg)
            h = self._res(h, out)
            if not cfg.ssd_mlp:
                return h, new_cache, None
        else:
            out, new_cache = rglru.rglru_decode(slot_params["rglru"], hn,
                                                slot_cache, cfg)
            h = h + out
        h, _, stats = self._ffn(slot_params, h, dropless=True)
        return h, new_cache, stats

    def _prefill_slot(self, slot_params, h, kind, *, positions, prefix_len,
                      max_len):
        """One layer of a prefill: (h, its cache, its dropless MoE
        counters or None)."""
        cfg = self.cfg
        hn = common.rmsnorm(slot_params["pre_norm"], h, cfg.norm_eps)
        if kind in (GLOBAL, LOCAL):
            out, (k, v) = attention.attention_train(
                slot_params["attn"], hn, cfg, kind=kind, positions=positions,
                prefix_len=prefix_len, return_kv=True)
            h = self._res(h, out)
            if self.kv_container is not None:
                # Packed caches round up to fused-kernel block multiples;
                # prefill must produce the same allocation as init_cache.
                L = _kvcache().cache_len(cfg, kind, max_len)
            else:
                L = min(max_len, cfg.window) if kind == LOCAL else max_len
            if kind == LOCAL:
                k, v = attention.ring_pack_kv(k, v, L)
            else:
                pad = L - k.shape[1]
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            new_cache = attention.KVCache(k=k.astype(cfg.compute_dtype),
                                          v=v.astype(cfg.compute_dtype))
            if self.kv_container is not None:
                new_cache = _kvcache().pack_prefill_cache(
                    new_cache, self.kv_container)
        elif kind == SSD:
            out, new_cache = mamba2.ssd_forward(slot_params["ssd"], hn, cfg,
                                                return_cache=True)
            h = self._res(h, out)
            if not cfg.ssd_mlp:
                return h, new_cache, None
        else:
            out, new_cache = rglru.rglru_forward(slot_params["rglru"], hn,
                                                 cfg, return_cache=True)
            h = h + out
        h, _, stats = self._ffn(slot_params, h, dropless=True)
        return h, new_cache, stats

    def _layers(self, slot_fn, h, params, cache=None):
        """Run ``slot_fn(slot_params, h, slot_cache, kind) -> (h, cache,
        stats)`` over every layer: the periods as one scan, then the
        remainder. Returns (h, caches, stats), stats being each dropless
        MoE counter stacked over the MoE layers in order ({} without).

        A paged block pool in ``cache`` (``kvcache.PagedKV``, stacked over
        the periods) rides the scan's carry rather than its xs/ys: its
        layer gets ``kvcache.PoolLayer(pool, period index)`` and returns
        the updated stacked pool, so a decode step writes and reads the
        one (donated) buffer in place instead of slicing each layer's pool
        out and stacking it back. A remainder layer's pool is a stack of
        one."""
        cfg = self.cfg

        def run(h, p, c, kinds, pools, layer):
            caches, stats = {}, []
            for i, kind in enumerate(kinds):
                key = f"slot{i}"
                if key in pools:
                    view = _kvcache().PoolLayer(pools[key], layer)
                    h, pools[key], st = slot_fn(p[key], h, view, kind)
                else:
                    h, caches[key], st = slot_fn(
                        p[key], h, None if c is None else c[key], kind)
                if st is not None:
                    stats.append(st)
            return h, caches, pools, {k: jnp.stack([st[k] for st in stats])
                                      for k in (stats[0] if stats else {})}

        def period_fn(carry, x):
            h, pools = carry
            p, c, layer = x
            h, caches, pools, stats = run(h, p, c, cfg.period, dict(pools),
                                          layer)
            return (h, pools), (caches, stats)

        pools, rest = _split_pools(None if cache is None
                                   else cache["periods"])
        xs = (params["periods"], rest,
              jnp.arange(cfg.n_periods) if pools else None)
        (h, pools), (period_caches, stats) = jax.lax.scan(period_fn,
                                                          (h, pools), xs)
        stats = {k: v.reshape(-1) for k, v in stats.items()}
        out = {"periods": {**period_caches, **pools}}
        if cfg.remainder:
            pools, rest = _split_pools(None if cache is None
                                       else cache["rem"])
            pools = jax.tree.map(lambda a: a[None], pools)
            h, rem, pools, rem_stats = run(h, params["rem"], rest,
                                           cfg.remainder, pools, 0)
            out["rem"] = {**rem, **jax.tree.map(lambda a: a[0], pools)}
            stats = {k: jnp.concatenate([stats[k], rem_stats[k]])
                     for k in stats}
        return h, out, stats

    def prefill(self, params, tokens: jax.Array, max_len: int,
                cond_embeddings: Optional[jax.Array] = None,
                stats: bool = False):
        """Process a full prompt, returning (last-position logits, cache),
        and with ``stats`` the dropless MoE counters per MoE layer too.

        ``max_len`` sizes the global-attention KV cache (prompt + decode
        budget). The prompt (with any multimodal prefix) must fit max_len.
        """
        shd.set_active_mesh(self.mesh, self.rules)
        cfg = self.cfg
        B, S = tokens.shape
        P = cfg.prefix_tokens if cond_embeddings is not None else 0
        h = self._embed(params, tokens)
        if P:
            h = jnp.concatenate([cond_embeddings.astype(h.dtype), h], axis=1)
        positions = jnp.arange(h.shape[1])
        max_len = max(max_len, h.shape[1])  # prefix tokens extend the cache

        def slot_fn(p, h, _c, kind):
            return self._prefill_slot(p, h, kind, positions=positions,
                                      prefix_len=P, max_len=max_len)

        h, cache, st = self._layers(slot_fn, h, params)
        logits = self._logits(params, h[:, -1:])
        return (logits, cache, st) if stats else (logits, cache)

    def decode_step(self, params, cache, token: jax.Array, pos: jax.Array,
                    tables: Optional[jax.Array] = None,
                    prefix_planes: Optional[int] = None,
                    stats: bool = False):
        """One decode step. token: (B, 1) int32; pos: scalar int32 absolute
        position (prefix + generated so far). Returns (logits (B, 1, V),
        cache), and with ``stats`` the dropless MoE counters per MoE layer
        ({} for a model without experts).

        With ``tables`` (B, nb) this is the continuous-batching paged
        step: ``pos`` becomes (B,) per-slot positions (idle slots carry
        pos 0 and a trash-block table row; their logits are garbage the
        engine discards), global attention layers in ``cache`` hold
        ``kvcache.PagedKV`` block pools (stacked over the periods, carried
        through the layer scan) addressed through the tables, and local
        ring / SSD / RGLRU layers hold per-slot dense state.
        Requires ``kv_container`` in that mode.

        ``prefix_planes`` makes every packed-attention *read* expand only
        the leading P' payload bits (the speculative draft mode); K/V
        writes and all recurrent state updates stay full-fidelity.
        Requires ``kv_container``.
        """
        assert prefix_planes is None or self.kv_container is not None, \
            "prefix_planes (draft reads) needs a packed kv_container"
        shd.set_active_mesh(self.mesh, self.rules)
        h = self._embed(params, token)

        def slot_fn(p, h, c, kind):
            return self._decode_slot(p, h, c, pos, kind, tables=tables,
                                     prefix_planes=prefix_planes)

        h, new_cache, st = self._layers(slot_fn, h, params, cache)
        logits = self._logits(params, h)
        return (logits, new_cache, st) if stats else (logits, new_cache)

    def decode_step_paged(self, params, cache, token: jax.Array,
                          pos: jax.Array, tables: jax.Array,
                          prefix_planes: Optional[int] = None,
                          stats: bool = False):
        """Paged decode step (see ``decode_step`` with ``tables``)."""
        assert self.kv_container is not None, "paged decode needs a codec"
        return self.decode_step(params, cache, token, pos, tables=tables,
                                prefix_planes=prefix_planes, stats=stats)
