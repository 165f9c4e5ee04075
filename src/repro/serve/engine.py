"""Serving engine: prefill + decode with (optionally compressed) KV cache.

Two serving modes share the model:

* **Contiguous** (``generate``): one prefill + one jitted ``lax.scan``
  decode loop over a per-request cache. Compiled functions are memoized
  per (model, shape) so repeated requests never recompile.
* **Paged** (``PagedEngine``): the continuous-batching substrate. A fixed
  number of batch *slots* share one codec-packed KV block pool
  (serve/pool.py); one jitted fixed-shape decode step advances every
  active slot at its own position, gathering KV blocks through the
  scalar-prefetched block table inside the paged flash-decode kernel.
  Request queueing/admission/preemption live above, in serve/scheduler.py.

`cache_axes` mirrors DecoderModel.init_cache structurally and assigns the
logical sharding: batch over (pod, data), the KV sequence dim over `model`
(flash-decoding style — XLA's softmax reductions over the sharded dim
become exact all-reduces), recurrent-state widths over `model`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import codecs
from repro import obs as obs_mod
from repro.configs.base import ArchConfig, GLOBAL, LOCAL, SSD
from repro.kernels import ops
from repro.models import attention, mamba2, rglru
from repro.models.model import DecoderModel
from repro.serve import kvcache as _kvcache
from repro.serve import pool as _pool


def _slot_axes(kind: str, model: DecoderModel, batch: int, max_len: int):
    if kind in (GLOBAL, LOCAL):
        if model.kv_container is not None:
            # Packed parts are (batch, seq, ...): same logical axes. The
            # real (batch, max_len) matter here: PackedTensor carries its
            # logical shape as pytree aux data, and the axes tree must
            # pair leaf-for-leaf with the actual cache tree.
            return _kvcache.packed_cache_axes(model.cfg, kind, batch,
                                              max_len, model.kv_container)
        return attention.KVCache(k=("batch", "cache_seq", "kv", None),
                                 v=("batch", "cache_seq", "kv", None))
    if kind == SSD:
        return mamba2.SSDCache(conv_x=("batch", None, "ssm_inner"),
                               conv_B=("batch", None, "state"),
                               conv_C=("batch", None, "state"),
                               state=("batch", "heads", None, None))
    return rglru.LRUCache(conv=("batch", None, "lru"),
                          state=("batch", "lru"))


def cache_axes(model: DecoderModel, batch: int = 1, max_len: int = 1):
    """Logical sharding axes matching ``model.init_cache(batch, max_len)``.

    ``batch``/``max_len`` are structural only for raw caches (plain axis
    tuples), but packed caches embed their shapes as pytree metadata —
    pass the same values as init_cache when ``model.kv_container`` is set.
    """
    cfg = model.cfg
    is_tuple = lambda a: isinstance(a, tuple) and all(
        x is None or isinstance(x, str) for x in a)
    per = {f"slot{i}": _slot_axes(k, model, batch, max_len)
           for i, k in enumerate(cfg.period)}
    periods = jax.tree.map(lambda a: ("layers",) + tuple(a), per,
                           is_leaf=is_tuple)
    axes = {"periods": periods}
    if cfg.remainder:
        axes["rem"] = {f"slot{i}": _slot_axes(k, model, batch, max_len)
                       for i, k in enumerate(cfg.remainder)}
    return axes


def make_serve_step(model: DecoderModel, greedy: bool = True):
    """(params, cache, token, pos) -> (next_token, cache). One decode step."""

    def serve_step(params, cache, token, pos):
        logits, cache = model.decode_step(params, cache, token, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    return serve_step


def make_prefill_step(model: DecoderModel, max_len: int):
    def prefill_step(params, tokens, cond_embeddings=None):
        return model.prefill(params, tokens, max_len,
                             cond_embeddings=cond_embeddings)

    return prefill_step


@dataclasses.dataclass
class GenerationResult:
    tokens: Any
    steps: int


def make_decode_loop(model: DecoderModel, n_steps: int):
    """Jitted greedy decode loop: one ``lax.scan`` over ``n_steps`` steps.

    The whole loop is a single XLA executable, so per-step host dispatch
    overhead disappears; the cache is donated (``donate_argnums``) so XLA
    updates it in place instead of copying the (possibly packed) ring
    buffers every step. Returns (tokens (n_steps, B, 1), final cache).
    """

    serve_step = make_serve_step(model)

    def loop(params, cache, tok, pos0):
        def step(carry, i):
            tok, cache = carry
            tok, cache = serve_step(params, cache, tok, pos0 + i)
            return (tok, cache), tok

        (tok, cache), toks = jax.lax.scan(
            step, (tok, cache), jnp.arange(n_steps, dtype=jnp.int32))
        return toks, cache

    return jax.jit(loop, donate_argnums=(1,))


# Compiled prefill/decode-loop functions, memoized per model instance:
# jax's jit cache keys on function identity, so rebuilding the closure on
# every generate() call recompiled prefill AND the scan loop each time.
# The cache hangs off the model itself — NOT a module-level
# WeakKeyDictionary: the cached closures capture the model, and any
# globally-rooted map whose values reference their key would pin every
# model (plus all its XLA executables) for the process lifetime. On the
# instance, cache and model form an ordinary garbage cycle that dies with
# the model. Below the statics key, jax handles per-input-shape caching.
_CACHE_ATTR = "_serve_compiled"


def compiled(model: DecoderModel, key: Tuple, build):
    per_model = model.__dict__.setdefault(_CACHE_ATTR, {})
    if key not in per_model:
        per_model[key] = build()
    return per_model[key]


def generate(model: DecoderModel, params, prompt: jax.Array, max_new: int,
             max_len: Optional[int] = None,
             cond_embeddings: Optional[jax.Array] = None) -> GenerationResult:
    """Greedy batched generation: jitted prefill + one jitted scan loop.

    Compiled functions are memoized on the model keyed by (max_len,
    n_steps), so repeated requests with the same budget reuse both
    executables instead of re-tracing them per call.
    """
    B, S = prompt.shape
    P = model.cfg.prefix_tokens if cond_embeddings is not None else 0
    max_len = max_len or (P + S + max_new)
    prefill = compiled(model, ("prefill", max_len),
                       lambda: jax.jit(make_prefill_step(model, max_len)))
    logits, cache = prefill(params, prompt, cond_embeddings)
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    out = [tok]
    if max_new > 1:
        loop = compiled(model, ("decode_loop", max_new - 1),
                        lambda: make_decode_loop(model, max_new - 1))
        toks, cache = loop(params, cache, tok,
                           jnp.asarray(P + S, jnp.int32))
        out.append(jnp.moveaxis(toks[..., 0], 0, 1))  # (n, B, 1) -> (B, n)
    return GenerationResult(tokens=jnp.concatenate(out, axis=1),
                            steps=max_new)


# ---------------------------------------------------------------------------
# Paged continuous-batching engine
# ---------------------------------------------------------------------------


class PagedEngine:
    """Fixed-shape batch-slot serving over a paged packed-KV block pool.

    ``max_slots`` requests decode together in one jitted step; each
    global-attention layer stores KV in codec-packed physical blocks
    (``block_l`` = the flash-decode kernel block) shared across slots and
    addressed through per-slot block tables. Local ring layers and
    SSD/RGLRU states are window/width-bounded, so they stay per-slot
    dense. Idle slots run the same step on the reserved trash block and
    their outputs are discarded — the executable never re-specializes as
    requests come and go, which is what makes continuous batching free of
    recompiles.

    The engine is mechanism only: it owns device memory, the block pool
    and the compiled step; admission, preemption and streaming live in
    ``serve/scheduler.py``.
    """

    def __init__(self, model: DecoderModel, params, *, max_slots: int = 8,
                 max_len: int = 256, num_blocks: Optional[int] = None,
                 degraded_container: Optional[str] = None,
                 integrity: bool = True):
        if model.kv_container is None:
            raise ValueError("PagedEngine needs a model with kv_container "
                             "set (the pool stores packed blocks)")
        cfg = model.cfg
        if cfg.prefix_tokens:
            raise NotImplementedError(
                "prefix-conditioned archs are not paged-served yet")
        self.model = model
        self.params = params
        self.cfg = cfg
        self.container = model.kv_container
        self.block_l = ops.DECODE_BLOCK_L
        # The pool block is the kernel block; rounding max_len up keeps
        # prefill's packed cache (cache_len) and the pool block grid the
        # same length, so prefill rows scatter into whole blocks.
        self.max_len = -(-max_len // self.block_l) * self.block_l
        self.nmax = self.max_len // self.block_l
        self.max_slots = int(max_slots)
        if num_blocks is None:
            num_blocks = self.max_slots * self.nmax  # full residency
        # Fail fast if the codec cannot page (no fixed-width geometry) —
        # and price one block in dense-packed bytes across the layers that
        # share the pool, so admission accounting is in realized bytes.
        _kvcache.paged_block_spec(cfg, 1, self.block_l, self.container)
        kinds = list(cfg.period) * cfg.n_periods + list(cfg.remainder)
        self.n_global_layers = sum(k == GLOBAL for k in kinds)
        self.block_bytes = self.n_global_layers * _kvcache.paged_block_bytes(
            cfg, self.block_l, self.container)
        # Graceful degradation (serve/precision.PressureController): under
        # memory pressure the scheduler admits new requests at a *narrower*
        # dense geometry, priced at that geometry's per-block bytes against
        # a fixed byte budget. The budget is `num_blocks` worth of blocks
        # at the configured geometry; the physical arrays over-provision
        # rows so that cheaper blocks are actually allocatable (fixed
        # shapes keep the step jittable — the byte accounting models the
        # HBM the blocks would occupy repacked at their admission width).
        self.degraded_container = degraded_container
        if degraded_container is not None:
            self.degraded_block_bytes = (
                self.n_global_layers
                * _kvcache.paged_block_bytes(cfg, self.block_l,
                                             degraded_container))
            if self.degraded_block_bytes >= self.block_bytes:
                raise ValueError(
                    f"degraded container {degraded_container!r} "
                    f"({self.degraded_block_bytes} B/block) is not narrower "
                    f"than {self.container!r} ({self.block_bytes} B/block)")
            budget_bytes = num_blocks * self.block_bytes
            phys_blocks = min(-(-budget_bytes // self.degraded_block_bytes),
                              self.max_slots * self.nmax)
            phys_blocks = max(phys_blocks, num_blocks)
            self._requant = jax.jit(self._requant_fn)
        else:
            self.degraded_block_bytes = self.block_bytes
            budget_bytes = None
            phys_blocks = num_blocks
            self._requant = None
        self.pool = _pool.BlockPool(phys_blocks, self.max_slots, self.nmax,
                                    self.block_l,
                                    block_bytes=self.block_bytes,
                                    budget_bytes=budget_bytes)
        self.mem = self._init_mem()
        self._step = jax.jit(self._step_fn, donate_argnums=(1,))
        self._scatter = jax.jit(self._scatter_fn, donate_argnums=(0,))
        self._bursts: Dict[int, Any] = {}  # K -> compiled scan loop
        # (K, draft_planes) -> compiled self-speculative draft+verify round
        self._specs: Dict[Tuple[int, int], Any] = {}
        self.decode_steps = 0
        self.spec_rounds = 0
        # Block integrity: a cheap per-physical-block checksum over the
        # packed planes (kvcache.paged_block_checksums summed across the
        # global layers), recomputed after every legitimate write
        # (pack/insert) and compared before every gather. The scheduler
        # drives verify/refresh; mismatches quarantine the block and
        # recompute the owning request from its prompt.
        self.integrity = bool(integrity)
        self._sums_fn = jax.jit(self._block_sums_fn)
        self.expected_sums = np.zeros(self.pool.num_blocks + 1, np.uint32)
        # Telemetry sink; the driving Scheduler installs its own. All
        # recording happens at host boundaries — after the jitted call's
        # outputs were pulled to numpy — never inside traced code
        # (enforced by the obs-no-hot-path-sync lint).
        self.obs = obs_mod.Obs()

    def _observe(self, name: str, help: str, seconds: float) -> None:
        self.obs.registry.histogram(name, help, unit="s").observe(seconds)

    def _count_kv_blocks(self, pos: np.ndarray, steps: int = 1,
                         passes: int = 1) -> None:
        """Count the paged decode kernel's KV block steps, on the host.

        ``grid`` is every (slot, logical block) step of a decode step;
        ``live`` the steps whose block holds a slot <= the row's position
        — the only ones the kernel fetches and expands. ``steps``
        consecutive positions from ``pos`` (a burst), each run ``passes``
        times (a speculative round drafts and verifies them). Computed
        from the host ``pos`` array the caller already holds, so counting
        never waits on the device.
        """
        at = (np.asarray(pos, np.int64)[None, :]
              + np.arange(steps, dtype=np.int64)[:, None])
        live = (at // self.block_l + 1).sum()
        fam = self.obs.registry.counter(
            "serve_decode_kv_blocks_total",
            "paged decode KV block steps per decode step: live (expanded) "
            "and grid (all)", labels=("kind",))
        fam.labels(kind="live").inc(passes * int(live))
        fam.labels(kind="grid").inc(
            passes * steps * self.max_slots * self.nmax)

    # -- device memory ---------------------------------------------------

    def _slot_mem(self, kind: str):
        cfg = self.cfg
        if kind == GLOBAL:
            # +1: physical block 0 is the trash block (pool.TRASH_BLOCK).
            return _kvcache.paged_block_init(
                cfg, self.pool.num_blocks + 1, self.block_l, self.container)
        if kind == LOCAL:
            return _kvcache.packed_cache_init(cfg, kind, self.max_slots,
                                              self.max_len, self.container)
        if kind == SSD:
            return mamba2.ssd_cache_init(cfg, self.max_slots,
                                         cfg.compute_dtype)
        return rglru.lru_cache_init(cfg, self.max_slots, cfg.compute_dtype)

    def _init_mem(self):
        cfg = self.cfg
        per = {f"slot{i}": self._slot_mem(k)
               for i, k in enumerate(cfg.period)}
        periods = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), per)
        mem = {"periods": periods}
        if cfg.remainder:
            mem["rem"] = {f"slot{i}": self._slot_mem(k)
                          for i, k in enumerate(cfg.remainder)}
        return mem

    def cache_bytes(self) -> Dict[str, float]:
        """Realized pool bytes (total device allocation) and the
        dense-packed bytes actually *live* (allocated blocks), per the
        host byte accounting."""
        leaves = jax.tree_util.tree_leaves(self.mem)
        total = float(sum(l.size * l.dtype.itemsize for l in leaves))
        st = self.pool.stats()
        return {"total": total,
                "live_block_fraction":
                    st.used_blocks / max(1, st.num_blocks),
                "block_bytes": float(st.block_bytes),
                "pool_capacity_bytes": float(st.capacity_bytes),
                "pool_live_bytes": float(st.used_bytes),
                "pool_peak_bytes": float(st.peak_bytes)}

    # -- block integrity -------------------------------------------------

    def _global_entries(self):
        """(group, key) paths of the paged global-attention layers in mem."""
        out = [("periods", f"slot{i}")
               for i, k in enumerate(self.cfg.period) if k == GLOBAL]
        out += [("rem", f"slot{i}")
                for i, k in enumerate(self.cfg.remainder) if k == GLOBAL]
        return out

    def _block_sums_fn(self, mem):
        """Per-physical-block uint32 checksum summed over global layers."""
        total = jnp.zeros(self.pool.num_blocks + 1, jnp.uint32)
        for j, (grp, key) in enumerate(self._global_entries()):
            total = total + _kvcache.paged_block_checksums(mem[grp][key],
                                                           salt=j + 1)
        return total

    def block_checksums(self) -> np.ndarray:
        """Current checksums of every physical block (trash block = id 0)."""
        with self.obs.span("serve.checksums"):
            return np.asarray(self._sums_fn(self.mem))

    def verify_blocks(self, ids) -> list:
        """Return the subset of physical block ids whose packed planes no
        longer match the checksum recorded at their last legitimate write."""
        ids = [int(p) for p in ids if p != _pool.TRASH_BLOCK]
        if not self.integrity or not ids:
            return []
        t0 = time.perf_counter()
        sums = self.block_checksums()
        bad = [p for p in ids if sums[p] != self.expected_sums[p]]
        self._observe("serve_verify_seconds",
                      "block checksum verification wall time",
                      time.perf_counter() - t0)
        return bad

    def refresh_checksums(self, ids) -> None:
        """Record current checksums as expected — call after every
        legitimate write (prefill scatter / decode step) to the blocks."""
        ids = [int(p) for p in ids if p != _pool.TRASH_BLOCK]
        if not self.integrity or not ids:
            return
        sums = self.block_checksums()
        for p in ids:
            self.expected_sums[p] = sums[p]

    def corrupt_block(self, phys: int, *, layer: int = 0, field: int = 0,
                      row: int = 0, col: int = 0, bit: int = 0) -> None:
        """Chaos/test hook: flip one bit in a packed plane of ``phys``.

        Simulates in-memory corruption (the FaultInjector's bit-flip
        fault). ``layer`` indexes the global layers, ``field`` the PagedKV
        planes (k_payload, k_bases, v_payload, v_bases).
        """
        entries = self._global_entries()
        grp, key = entries[layer % len(entries)]
        kv = self.mem[grp][key]
        field %= len(kv)
        arr = kv[field]
        lead = (0,) if arr.ndim == 4 else ()
        idx = lead + (int(phys), row % arr.shape[-2], col % arr.shape[-1])
        nbits = 8 * arr.dtype.itemsize
        uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[arr.dtype.itemsize]
        word = jax.lax.bitcast_convert_type(arr[idx], uint)
        word = word ^ uint(1 << (bit % nbits))
        arr = arr.at[idx].set(jax.lax.bitcast_convert_type(word, arr.dtype))
        self.mem[grp][key] = kv._replace(**{kv._fields[field]: arr})

    def scrub_block(self, phys: int) -> None:
        """Zero a (quarantined) block's planes and re-record its checksum,
        making it safe to return to the free list (pool.rehabilitate)."""
        for grp, key in self._global_entries():
            kv = self.mem[grp][key]
            self.mem[grp][key] = type(kv)(*(
                a.at[(slice(None), int(phys)) if a.ndim == 4
                     else int(phys)].set(0) for a in kv))
        self.refresh_checksums([phys])
        self.obs.event("scrub_block", block=int(phys))

    # -- prefill ---------------------------------------------------------

    def _requant_fn(self, pref_cache):
        """Narrow-requantize the global-layer KV of a prefill cache.

        Degraded admissions store prompt KV at the *narrower* geometry:
        each packed tensor is unpacked, round-tripped through the degraded
        codec, and repacked at the configured container (narrow values are
        exactly representable in the wider geometry, so the pool arrays
        keep one fixed shape and the jitted step never re-specializes).
        Decode-time appends still write at the configured width — the byte
        accounting (pool rates) is what prices the slot at the narrow
        geometry.
        """
        wide = codecs.get(self.container)
        narrow = codecs.get(self.degraded_container)

        def one_pt(pt):
            pay = pt.data["payload"]
            lead = pay.shape[:-2]
            B = 1
            for d in lead:
                B *= int(d)
            L, D = pay.shape[-2], pt.shape[-1]
            flat = codecs.PackedTensor(
                pt.codec, (B, L, D), pt.dtype,
                {k: v.reshape((B,) + v.shape[len(lead):])
                 for k, v in pt.data.items()})
            vals = narrow.roundtrip(wide.unpack(flat))
            rp = wide.pack(vals)
            return codecs.PackedTensor(
                pt.codec, pt.shape, pt.dtype,
                {k: rp.data[k].reshape(pt.data[k].shape) for k in pt.data})

        out = {"periods": dict(pref_cache["periods"])}
        for i, kind in enumerate(self.cfg.period):
            if kind == GLOBAL:
                kv = pref_cache["periods"][f"slot{i}"]
                out["periods"][f"slot{i}"] = kv._replace(k=one_pt(kv.k),
                                                         v=one_pt(kv.v))
        if self.cfg.remainder:
            out["rem"] = dict(pref_cache["rem"])
            for i, kind in enumerate(self.cfg.remainder):
                if kind == GLOBAL:
                    kv = pref_cache["rem"][f"slot{i}"]
                    out["rem"][f"slot{i}"] = kv._replace(k=one_pt(kv.k),
                                                         v=one_pt(kv.v))
        return out

    def _scatter_fn(self, mem, pref_cache, slot, ids):
        """Write one request's prefill cache into slot ``slot``.

        Global layers scatter block-reshaped packed rows to the physical
        ids in ``ids`` (unallocated logical blocks point at the trash
        block and receive identical packed-zero rows — harmless); per-slot
        layers overwrite their slot row wholesale.
        """
        nmax, bl = self.nmax, self.block_l

        def put_blocks(pool_arr, part, leading):
            if leading:
                blk = part[:, 0].reshape(part.shape[0], nmax, bl,
                                         *part.shape[3:])
                return pool_arr.at[:, ids].set(blk)
            blk = part[0].reshape(nmax, bl, *part.shape[2:])
            return pool_arr.at[ids].set(blk)

        def set_slot(m, p, leading):
            def arr(ma, pa):
                return (ma.at[:, slot].set(pa[:, 0]) if leading
                        else ma.at[slot].set(pa[0]))

            def one(ma, pa):
                if isinstance(ma, codecs.PackedTensor):
                    return codecs.PackedTensor(
                        ma.codec, ma.shape, ma.dtype,
                        {k: arr(ma.data[k], pa.data[k]) for k in ma.data})
                return arr(ma, pa)

            return jax.tree.map(
                one, m, p,
                is_leaf=lambda x: isinstance(x, codecs.PackedTensor))

        def scatter_kind(kind, m, p, leading):
            if kind == GLOBAL:
                return _kvcache.PagedKV(
                    k_payload=put_blocks(m.k_payload, p.k.data["payload"],
                                         leading),
                    k_bases=put_blocks(m.k_bases, p.k.data["bases"],
                                       leading),
                    v_payload=put_blocks(m.v_payload, p.v.data["payload"],
                                         leading),
                    v_bases=put_blocks(m.v_bases, p.v.data["bases"],
                                       leading))
            return set_slot(m, p, leading)

        out = {"periods": {
            f"slot{i}": scatter_kind(kind, mem["periods"][f"slot{i}"],
                                     pref_cache["periods"][f"slot{i}"], True)
            for i, kind in enumerate(self.cfg.period)}}
        if self.cfg.remainder:
            out["rem"] = {
                f"slot{i}": scatter_kind(kind, mem["rem"][f"slot{i}"],
                                         pref_cache["rem"][f"slot{i}"],
                                         False)
                for i, kind in enumerate(self.cfg.remainder)}
        return out

    def prefill_into_slot(self, slot: int, prompt: np.ndarray,
                          narrow: bool = False) -> int:
        """Prefill one request into ``slot``; returns its first token.

        The slot's block table must already cover the prompt
        (``pool.alloc_upto``). Uses the model's packed prefill at the
        engine-wide ``max_len``, so every compile is shared across slots
        and the packed rows are bit-identical to the contiguous serving
        path at the same budget. ``narrow=True`` (degraded admission)
        round-trips the prompt KV through ``degraded_container`` before
        scattering, so the stored planes carry the narrow geometry's
        values while keeping the pool's fixed shapes.
        """
        t0 = time.perf_counter()
        prompt = np.asarray(prompt)
        assert prompt.ndim == 1 and prompt.size >= 1, prompt.shape
        if prompt.size >= self.max_len:
            raise ValueError(f"prompt ({prompt.size}) must leave decode "
                             f"room inside max_len ({self.max_len})")
        if narrow and self._requant is None:
            raise ValueError("narrow prefill needs degraded_container")
        prefill = compiled(
            self.model, ("prefill", self.max_len),
            lambda: jax.jit(make_prefill_step(self.model, self.max_len)))
        logits, pref_cache = prefill(self.params, jnp.asarray(prompt)[None],
                                     None)
        if narrow:
            pref_cache = self._requant(pref_cache)
        ids_np = self.pool.tables[slot]
        self.mem = self._scatter(self.mem, pref_cache,
                                 jnp.asarray(slot, jnp.int32),
                                 jnp.asarray(ids_np, jnp.int32))
        if self.integrity:
            self.refresh_checksums([p for p in ids_np
                                    if p != _pool.TRASH_BLOCK])
        tok = int(jnp.argmax(logits[0, -1]))
        self._observe("serve_prefill_seconds",
                      "prefill-into-slot wall time (incl. scatter)",
                      time.perf_counter() - t0)
        return tok

    # -- decode ----------------------------------------------------------

    def _step_fn(self, params, mem, tables, toks, pos):
        logits, mem = self.model.decode_step_paged(params, mem, toks, pos,
                                                   tables)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        # NaN/Inf logit guard: a per-slot "bad" flag computed inside the
        # jitted step (free — logits are already on device). The scheduler
        # quarantines flagged slots instead of streaming garbage.
        bad = ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
        return nxt, bad, mem

    def decode(self, toks: np.ndarray, pos: np.ndarray):
        """One batched decode step over every slot.

        ``toks``/``pos`` are (max_slots,) host arrays; idle slots carry
        token 0 at position 0 with a trash-block table row, and their
        returned tokens are meaningless. Returns ((max_slots,) next
        tokens, (max_slots,) bool non-finite-logit flags).
        """
        t0 = time.perf_counter()
        tables = jnp.asarray(self.pool.tables)
        nxt, bad, self.mem = self._step(
            self.params, self.mem, tables,
            jnp.asarray(toks, jnp.int32)[:, None],
            jnp.asarray(pos, jnp.int32))
        # Counted while the step runs (dispatch is asynchronous).
        self._count_kv_blocks(pos)
        self.decode_steps += 1
        out = np.asarray(nxt), np.asarray(bad)
        self._observe("serve_decode_seconds",
                      "decode dispatch wall time (whole burst)",
                      time.perf_counter() - t0)
        return out

    def _make_burst(self, K: int):
        """Compiled K-step decode burst: one ``lax.scan`` executable.

        Block tables are fixed for the whole burst (the scheduler
        pre-allocates every running slot to its burst horizon), so the
        scan carries only (token, mem) and the per-step host round-trip —
        table upload, dispatch, token download — is paid once per K
        tokens instead of once per token. The pool memory is donated, so
        XLA updates the packed blocks in place across all K steps.
        """

        def burst(params, mem, tables, toks, pos):
            def step(carry, i):
                tok, mem = carry
                logits, mem = self.model.decode_step_paged(
                    params, mem, tok, pos + i, tables)
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                bad = ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
                return (nxt[:, None], mem), (nxt, bad)

            (_, mem), (out, bad) = jax.lax.scan(
                step, (toks, mem), jnp.arange(K, dtype=jnp.int32))
            return out, bad, mem  # out/bad: (K, max_slots)

        return jax.jit(burst, donate_argnums=(1,))

    def decode_burst(self, toks: np.ndarray, pos: np.ndarray,
                     burst: int):
        """``burst`` greedy decode steps over every slot in one dispatch.

        Each slot chains its own argmax token across the burst; positions
        advance ``pos + i``. Every running slot must already own blocks
        covering ``pos + burst`` (and ``pos + burst <= max_len``) — the
        scheduler guarantees this before calling. Returns the
        (burst, max_slots) int32 token buffer plus a matching bool buffer
        of non-finite-logit flags; the caller replays per-token
        streaming/finish bookkeeping from them. ``burst == 1`` reuses the
        plain compiled step rather than a scan of one.
        """
        K = int(burst)
        assert K >= 1, K
        if K == 1:
            nxt, bad = self.decode(toks, pos)
            return nxt[None], bad[None]
        fn = self._bursts.get(K)
        if fn is None:
            fn = self._bursts[K] = self._make_burst(K)
        t0 = time.perf_counter()
        tables = jnp.asarray(self.pool.tables)
        out, bad, self.mem = fn(self.params, self.mem, tables,
                                jnp.asarray(toks, jnp.int32)[:, None],
                                jnp.asarray(pos, jnp.int32))
        self._count_kv_blocks(pos, K)
        self.decode_steps += K
        res = np.asarray(out), np.asarray(bad)
        self._observe("serve_decode_seconds",
                      "decode dispatch wall time (whole burst)",
                      time.perf_counter() - t0)
        return res

    # -- self-speculative decoding ---------------------------------------

    def default_draft_planes(self) -> int:
        """Deepest valid draft prefix shallower than full width, if any.

        The draft must keep the sign, the full shared-exponent delta and
        at least one mantissa bit (``ops.prefix_fields`` enforces this),
        so very narrow containers (e.g. sfp-m1e2) may only support the
        full width — speculation still works, the draft just reads every
        plane.
        """
        fields = _kvcache._paged_fields(self.cfg, self.container)
        return max(fields.payload_bits - 1, fields.dexp_bits + 2)

    def validate_draft_planes(self, draft_planes: int) -> int:
        """Check ``draft_planes`` against the pool geometry; returns it."""
        fields = _kvcache._paged_fields(self.cfg, self.container)
        ops.prefix_fields(fields, int(draft_planes))  # raises ValueError
        return int(draft_planes)

    def _non_global_keys(self) -> Tuple[tuple, tuple]:
        """slot keys of the per-slot (non paged-pool) layer state in mem."""
        per = tuple(f"slot{i}" for i, k in enumerate(self.cfg.period)
                    if k != GLOBAL)
        rem = tuple(f"slot{i}" for i, k in enumerate(self.cfg.remainder)
                    if k != GLOBAL)
        return per, rem

    def _make_spec(self, K: int, draft_planes: int):
        """Compiled self-speculative round: K draft steps at prefix
        precision, one batched full-width verify, device-side acceptance
        and bit-exact state rollback — a single executable per
        (K, draft_planes), memoized like the burst loops.

        Protocol (greedy, guaranteed token-identical to plain decode):

        * **Draft**: ``lax.scan`` of K decode steps whose packed-attention
          reads expand only the leading ``draft_planes`` bit planes per
          group (``prefix_planes``); KV writes and recurrent updates stay
          full width.
        * **Rewind**: per-slot layer state (local packed rings, SSD and
          RGLRU states) is restored to its round-start snapshot. Paged
          pool rows the draft wrote need no rollback: the verify pass
          rewrites each position before any step can attend to it, and
          rows past the current position are causally masked — so
          speculation allocates and touches exactly the blocks a burst of
          the same horizon would (zero additional pool bytes).
        * **Verify**: ``lax.scan`` of K full-width steps teacher-forced
          with [token, d_1..d_{K-1}] over the same positions, stacking
          the per-slot layer state after every step.
        * **Accept**: per slot, ``m`` = longest prefix with d_i == v_i;
          ``n_emit = min(m+1, K)`` (the verifier's correction token is
          always emitted, so at least one token commits per round). The
          committed per-slot state is the verify stack at step
          ``n_emit-1``; because accepted verify steps consumed exactly
          the tokens a non-speculative decode would have, that state —
          and every emitted token — is bit-exact vs. ``burst=1`` decode.

        The stacked rollback state costs K extra copies of the per-slot
        (window/width-bounded) layers inside the executable — never of
        the block pool itself.
        """
        per_keys, rem_keys = self._non_global_keys()

        def extract(mem):
            out = {"periods": {k: mem["periods"][k] for k in per_keys}}
            if rem_keys:
                out["rem"] = {k: mem["rem"][k] for k in rem_keys}
            return out

        def merge(mem, ng):
            out = {"periods": {**mem["periods"], **ng["periods"]}}
            if "rem" in mem:
                out["rem"] = {**mem["rem"], **ng.get("rem", {})}
            return out

        S = self.max_slots

        def gather_committed(stack, n_emit):
            """Per-slot pick of the verify stack at step n_emit[s]-1.

            Leaves are (K, n_periods, slots, ...) under "periods" and
            (K, slots, ...) under "rem"; the step axis is gathered at a
            different index per slot.
            """
            idx = n_emit - 1  # (S,) in [0, K)

            def pick(leaf, slot_axis):
                ym = jnp.moveaxis(leaf, slot_axis, 1)  # (K, S, ...)
                out = ym[idx, jnp.arange(S)]           # (S, ...)
                return jnp.moveaxis(out, 0, slot_axis - 1)

            out = {"periods": jax.tree.map(lambda a: pick(a, 2),
                                           stack["periods"])}
            if "rem" in stack:
                out["rem"] = jax.tree.map(lambda a: pick(a, 1),
                                          stack["rem"])
            return out

        def spec(params, mem, tables, toks, pos):
            snap = extract(mem)

            def dstep(carry, i):
                tok, mem = carry
                logits, mem = self.model.decode_step_paged(
                    params, mem, tok, pos + i, tables,
                    prefix_planes=draft_planes)
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                return (nxt[:, None], mem), nxt

            (_, mem), drafts = jax.lax.scan(
                dstep, (toks, mem), jnp.arange(K, dtype=jnp.int32))

            mem = merge(mem, snap)  # rewind per-slot state for verify

            vin = jnp.concatenate([toks[:, 0][None], drafts[:-1]], axis=0)

            def vstep(mem, x):
                tok, i = x
                logits, mem = self.model.decode_step_paged(
                    params, mem, tok[:, None], pos + i, tables)
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                bad = ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
                return mem, (nxt, bad, extract(mem))

            mem, (verifs, bad, stack) = jax.lax.scan(
                vstep, mem, (vin, jnp.arange(K, dtype=jnp.int32)))

            match = jnp.cumprod((drafts == verifs).astype(jnp.int32), axis=0)
            accepted = jnp.sum(match, axis=0)           # (S,) m in [0, K]
            n_emit = jnp.minimum(accepted + 1, K)       # (S,) in [1, K]

            mem = merge(mem, gather_committed(stack, n_emit))
            return verifs, bad, accepted, n_emit, mem

        return jax.jit(spec, donate_argnums=(1,))

    def speculate(self, toks: np.ndarray, pos: np.ndarray, K: int,
                  draft_planes: Optional[int] = None):
        """One self-speculative round over every slot.

        Same calling convention as ``decode_burst``: every running slot
        must own blocks covering ``pos + K`` (``pos + K <= max_len``).
        Returns ``(verifs (K, max_slots), bad (K, max_slots),
        accepted (max_slots,), n_emit (max_slots,))`` — ``accepted`` is
        the per-slot count of drafts the verify pass confirmed (0..K);
        ``n_emit = min(accepted+1, K)`` counts the tokens actually
        decoded (the verifier's correction token always commits). The
        caller streams ``verifs[:n_emit[s], s]`` per slot; the rejected
        suffix was rolled back on device.
        """
        K = int(K)
        assert K >= 1, K
        if draft_planes is None:
            draft_planes = self.default_draft_planes()
        dp = self.validate_draft_planes(draft_planes)
        fn = self._specs.get((K, dp))
        if fn is None:
            fn = self._specs[(K, dp)] = self._make_spec(K, dp)
        t0 = time.perf_counter()
        tables = jnp.asarray(self.pool.tables)
        verifs, bad, accepted, n_emit, self.mem = fn(
            self.params, self.mem, tables,
            jnp.asarray(toks, jnp.int32)[:, None],
            jnp.asarray(pos, jnp.int32))
        self._count_kv_blocks(pos, K, passes=2)  # K draft + K verify
        self.decode_steps += 2 * K  # K draft + K verify model steps
        self.spec_rounds += 1
        res = (np.asarray(verifs), np.asarray(bad), np.asarray(accepted),
               np.asarray(n_emit))
        self._observe("serve_spec_seconds",
                      "speculative draft+verify round wall time",
                      time.perf_counter() - t0)
        return res
