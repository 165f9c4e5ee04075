"""Paged serving engine: block pool invariants, scheduler mechanics
(admission gating, preemption, slot recycling, streaming), paged-vs-
contiguous token equivalence under continuous batching, and policy-aware
container resolution from checkpoint metadata."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs, configs
from repro.configs.base import GLOBAL, reduced
from repro.kernels import ops
from repro.models.model import DecoderModel
from repro.serve import engine, kvcache, precision
from repro.serve.pool import TRASH_BLOCK, BlockPool, blocks_for
from repro.serve.scheduler import Request, Scheduler


def _model(name, container, **over):
    cfg = dataclasses.replace(reduced(configs.get(name)), dtype="float32",
                              **over)
    return cfg, DecoderModel(cfg, kv_container=container)


def _prompts(rng, cfg, sizes):
    return [rng.randint(0, cfg.vocab, size=s).astype(np.int32)
            for s in sizes]


# ---------------------------------------------------------------------------
# Block pool
# ---------------------------------------------------------------------------


def test_pool_alloc_free_trash_invariants():
    pool = BlockPool(num_blocks=4, max_slots=2, max_logical=3, block_l=16)
    assert blocks_for(0, 16) == 0 and blocks_for(1, 16) == 1
    assert blocks_for(16, 16) == 1 and blocks_for(17, 16) == 2
    assert pool.free_blocks == 4
    assert pool.alloc_upto(0, 33)  # 3 blocks
    assert pool.used_blocks == 3
    assert TRASH_BLOCK not in pool.tables[0, :3]
    assert pool.tables[0, 2] != TRASH_BLOCK
    assert not pool.alloc_upto(1, 17)   # needs 2, only 1 free
    assert pool.free_blocks == 1        # failed alloc takes nothing
    assert pool.alloc_upto(1, 16)
    assert pool.free_blocks == 0
    assert pool.free_slot(0) == 3
    assert pool.free_blocks == 3
    assert (pool.tables[0] == TRASH_BLOCK).all()
    # growing an existing allocation is idempotent below the watermark
    assert pool.alloc_upto(1, 8) and pool.used_blocks == 1
    with pytest.raises(ValueError):
        pool.alloc_upto(1, 16 * 3 + 1)  # > max_logical


def test_pool_hardening_rejects_misuse():
    """The allocator raises on double free, out-of-range slots, and
    quarantine of blocks a slot does not own — aliasing bugs surface at
    the call site instead of corrupting another request's blocks."""
    pool = BlockPool(num_blocks=4, max_slots=2, max_logical=3, block_l=16)
    with pytest.raises(ValueError, match="slot 2 out of range"):
        pool.alloc_upto(2, 16)
    with pytest.raises(ValueError, match="slot -1 out of range"):
        pool.free_slot(-1)
    with pytest.raises(ValueError, match="n_tokens"):
        pool.alloc_upto(0, -5)
    with pytest.raises(KeyError, match="double free"):
        pool.free_slot(0)               # never allocated
    assert pool.alloc_upto(0, 20)       # 2 blocks
    pool.verify_invariants()
    with pytest.raises(ValueError, match="not owned"):
        pool.free_slot(0, quarantine=(99,))
    with pytest.raises(ValueError, match="trash block"):
        pool.free_slot(0, quarantine=(TRASH_BLOCK,))
    owned = pool.owned_ids()
    assert pool.free_slot(0, quarantine=owned[:1]) == 1
    with pytest.raises(KeyError, match="double free"):
        pool.free_slot(0)
    pool.verify_invariants()
    # quarantined blocks are neither free nor owned until rehabilitated
    assert pool.free_blocks == 3 and pool.quarantined_blocks == owned[:1]
    with pytest.raises(ValueError, match="not quarantined"):
        pool.rehabilitate(owned[1])
    with pytest.raises(ValueError, match="never pooled"):
        pool.rehabilitate(TRASH_BLOCK)
    pool.rehabilitate(owned[0])
    assert pool.free_blocks == 4
    pool.verify_invariants()


def test_pool_admission_gate_keeps_decode_headroom():
    pool = BlockPool(num_blocks=3, max_slots=2, max_logical=4, block_l=16)
    assert pool.can_admit(47)       # prompt + first token fit 3 blocks
    assert not pool.can_admit(48)   # block-aligned prompt needs a 4th
    pool.alloc_upto(0, 17)          # 2 blocks used, 1 free
    assert pool.can_admit(15) and not pool.can_admit(16)
    # Full residency must be reachable: a one-block pool admits a request
    # whose prompt + first token fit one block (B=1 bench regression).
    tiny = BlockPool(num_blocks=1, max_slots=1, max_logical=1, block_l=128)
    assert tiny.can_admit(120) and not tiny.can_admit(128)


# ---------------------------------------------------------------------------
# Scheduler-driven generation == per-request engine.generate
# ---------------------------------------------------------------------------


def test_scheduler_matches_generate_staggered():
    """>= 8 requests with mixed prompt/output lengths and staggered
    arrivals, decoded as a continuous batch over the sfp8 pool, must emit
    exactly the tokens per-request generate emits at the same budget
    (fused interpret kernels on both sides — bit-exact packed paths)."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    sizes = [5, 9, 5, 12, 9, 5, 7, 9]
    news = [4, 3, 5, 2, 4, 3, 2, 3]
    reqs = [Request(uid=i, prompt=p, max_new=n, arrival=0.3 * i)
            for i, (p, n) in enumerate(zip(_prompts(rng, cfg, sizes), news))]
    ops.force_backend("interpret")
    try:
        eng = engine.PagedEngine(model, params, max_slots=3, max_len=128)
        sched = Scheduler(eng)
        clock = {"t": 0.0}

        def now():
            clock["t"] += 0.25
            return clock["t"]

        out = sched.run(reqs, now_fn=now)
        assert sched.stats.preemptions == 0  # full-residency pool
        assert sched.stats.admitted == len(reqs)
        for r in reqs:
            want = engine.generate(model, params,
                                   jnp.asarray(r.prompt)[None],
                                   max_new=r.max_new, max_len=eng.max_len)
            np.testing.assert_array_equal(out[r.uid],
                                          np.asarray(want.tokens[0]))
    finally:
        ops.force_backend(None)
    # Slots were recycled: more requests than slots, all finished.
    assert len(out) == len(reqs) > eng.max_slots


def test_scheduler_matches_generate_gqa4():
    """GQA 4 (one kv head shared by four q heads) through the whole
    engine: grouped q heads share gathered pool blocks in the paged
    kernel; tokens must equal per-request generate."""
    cfg, model = _model("mistral-large-123b", "sfp16", n_kv_heads=1,
                        head_dim=128)
    assert cfg.n_heads // cfg.n_kv_heads == 4
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    reqs = [Request(uid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts(rng, cfg, [5, 8]))]
    ops.force_backend("interpret")
    try:
        eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
        out = Scheduler(eng).run(reqs)
        for r in reqs:
            want = engine.generate(model, params,
                                   jnp.asarray(r.prompt)[None],
                                   max_new=r.max_new, max_len=eng.max_len)
            np.testing.assert_array_equal(out[r.uid],
                                          np.asarray(want.tokens[0]))
    finally:
        ops.force_backend(None)


def test_scheduler_matches_generate_global_remainder():
    """A global layer past the last whole period keeps its own one-layer
    pool outside the layer scan (read as a stack of one): tokens through
    the whole engine must equal per-request generate."""
    cfg, model = _model("mistral-large-123b", "sfp8",
                        period=(GLOBAL, GLOBAL), n_layers=3)
    assert cfg.n_periods == 1 and cfg.remainder == (GLOBAL,)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(19)
    reqs = [Request(uid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts(rng, cfg, [5, 8]))]
    ops.force_backend("interpret")
    try:
        eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
        out = Scheduler(eng).run(reqs)
        for r in reqs:
            want = engine.generate(model, params,
                                   jnp.asarray(r.prompt)[None],
                                   max_new=r.max_new, max_len=eng.max_len)
            np.testing.assert_array_equal(out[r.uid],
                                          np.asarray(want.tokens[0]))
    finally:
        ops.force_backend(None)


@pytest.mark.slow
def test_scheduler_matches_generate_ring_wrap_and_block_crossing():
    """gemma3 (5x local + global): decode past the sliding window wraps
    the per-slot packed rings, and one long prompt crosses the 128-row
    pool block boundary mid-decode — tokens must still equal generate."""
    cfg, model = _model("gemma3-12b", "sfp16", window=16)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    reqs = [
        Request(uid=0, prompt=_prompts(rng, cfg, [8])[0], max_new=20),
        Request(uid=1, prompt=_prompts(rng, cfg, [126])[0], max_new=5),
        Request(uid=2, prompt=_prompts(rng, cfg, [5])[0], max_new=3),
    ]
    ops.force_backend("interpret")
    try:
        eng = engine.PagedEngine(model, params, max_slots=3, max_len=160)
        out = Scheduler(eng).run(reqs)
        for r in reqs:
            want = engine.generate(model, params,
                                   jnp.asarray(r.prompt)[None],
                                   max_new=r.max_new, max_len=eng.max_len)
            np.testing.assert_array_equal(out[r.uid],
                                          np.asarray(want.tokens[0]))
    finally:
        ops.force_backend(None)


# ---------------------------------------------------------------------------
# Scheduler mechanics (ref backend: fast, no bit-exactness needed)
# ---------------------------------------------------------------------------


def _run_ref(model, params, reqs, **eng_kw):
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, **eng_kw)
        sched = Scheduler(eng)
        out = sched.run(reqs)
    finally:
        ops.force_backend(None)
    return eng, sched, out


def test_scheduler_slot_recycling_and_streaming():
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    stream = []
    reqs = [Request(uid=i, prompt=p, max_new=3,
                    on_token=lambda uid, tok, done:
                    stream.append((uid, tok, done)))
            for i, p in enumerate(_prompts(rng, cfg, [4] * 5))]
    eng, sched, out = _run_ref(model, params, reqs, max_slots=2,
                               max_len=128)
    assert sorted(out) == [0, 1, 2, 3, 4]
    assert all(len(v) == 3 for v in out.values())
    assert sched.stats.finished == 5 and sched.stats.admitted == 5
    # Streaming: per uid, tokens arrive in order and exactly the last
    # carries done=True; the stream equals the final results.
    per = {}
    for uid, tok, done in stream:
        per.setdefault(uid, []).append((tok, done))
    for uid, toks in per.items():
        assert [t for t, _ in toks] == out[uid].tolist()
        assert [d for _, d in toks] == [False, False, True]
    # Pool fully drained after the run — everything recycled.
    assert eng.pool.used_blocks == 0


def test_scheduler_admission_gated_on_free_blocks():
    """With a pool that fits one request's blocks (plus headroom), the
    second request must queue until the first finishes."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    reqs = [Request(uid=i, prompt=p, max_new=2)
            for i, p in enumerate(_prompts(rng, cfg, [4, 4]))]
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, max_slots=2, max_len=128,
                                 num_blocks=1)
        sched = Scheduler(eng)
        for r in reqs:
            sched.submit(r)
        first = sched.step()
        # Only request 0 admitted: it holds the pool's single block, so
        # request 1 queues despite a free slot.
        assert {uid for uid, _, _ in first} == {0}
        assert sched.stats.admitted == 1 and len(sched.pending) == 1
        out = sched.run()
    finally:
        ops.force_backend(None)
    assert all(len(out[i]) == 2 for i in (0, 1))
    assert sched.stats.preemptions == 0


def test_scheduler_preempts_youngest_and_recovers():
    """Two long requests crossing a block boundary with a 3-block pool:
    the younger is evicted (recompute), re-admitted after the older
    drains, and still emits its full budget — with every token recorded
    across the preemption."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(4)
    reqs = [Request(uid=i, prompt=p, max_new=6)
            for i, p in enumerate(_prompts(rng, cfg, [126, 126]))]
    eng, sched, out = _run_ref(model, params, reqs, max_slots=2,
                               max_len=256, num_blocks=3)
    assert sched.stats.preemptions >= 1
    assert all(len(out[i]) == 6 for i in (0, 1))
    assert eng.pool.used_blocks == 0


def test_single_oversized_request_raises():
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(5)
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, max_slots=1, max_len=256,
                                 num_blocks=1)
        # Prompt + first token can never fit the pool: rejected up front.
        req = Request(uid=0, prompt=_prompts(rng, cfg, [129])[0], max_new=2)
        with pytest.raises(RuntimeError, match="cannot ever admit"):
            Scheduler(eng).run([req])
        # Admissible but outgrows the pool mid-decode with nobody left to
        # preempt: raises at the growth point instead of spinning.
        req2 = Request(uid=1, prompt=_prompts(rng, cfg, [126])[0],
                       max_new=8)
        with pytest.raises(RuntimeError, match="cannot hold"):
            Scheduler(eng).run([req2])
    finally:
        ops.force_backend(None)


def test_submit_validates_requests_up_front():
    """Malformed requests raise at submit() with the offending field
    named — never deep inside prefill with a shape error."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, max_slots=1, max_len=128,
                                 num_blocks=1)
        sched = Scheduler(eng)
        good = np.arange(4, dtype=np.int32)
        with pytest.raises(ValueError, match="prompt"):
            sched.submit(Request(uid=0, prompt=good[None], max_new=2))
        with pytest.raises(ValueError, match="prompt"):
            sched.submit(Request(uid=0, prompt=good[:0], max_new=2))
        with pytest.raises(ValueError, match="max_new"):
            sched.submit(Request(uid=0, prompt=good, max_new=0))
        # a prompt the pool can never hold is refused at submit, not
        # after it reaches the head of the queue
        big = np.arange(129, dtype=np.int32)
        with pytest.raises(RuntimeError, match="cannot ever admit"):
            sched.submit(Request(uid=0, prompt=big, max_new=2))
        assert not sched.pending  # nothing malformed was enqueued
        sched.submit(Request(uid=1, prompt=good, max_new=2))
        assert len(sched.pending) == 1
    finally:
        ops.force_backend(None)


def test_paged_engine_rejects_raw_and_unfuseable_codecs():
    cfg = dataclasses.replace(reduced(configs.get("mistral-large-123b")),
                              dtype="float32")
    params = DecoderModel(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_container"):
        engine.PagedEngine(DecoderModel(cfg), params)
    with pytest.raises(ValueError, match="fixed-width"):
        engine.PagedEngine(DecoderModel(cfg, kv_container="gecko8"), params)


def test_generate_memoizes_compiled_functions():
    """Repeated generate() calls with the same budget must reuse the
    compiled prefill and decode-loop callables (no per-call re-jit)."""
    cfg, model = _model("mistral-large-123b", None)
    model = DecoderModel(cfg)  # raw cache is fine for this
    params = model.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.arange(6, dtype=np.int32))[None]
    r1 = engine.generate(model, params, prompt, max_new=3)
    cache = model.__dict__[engine._CACHE_ATTR]
    keys1 = set(cache)
    fns1 = dict(cache)
    r2 = engine.generate(model, params, prompt, max_new=3)
    assert set(cache) == keys1
    for k in keys1:
        assert cache[k] is fns1[k]
    np.testing.assert_array_equal(np.asarray(r1.tokens),
                                  np.asarray(r2.tokens))
    # the memo must not immortalize the model: it lives on the instance
    # (an ordinary garbage cycle), not in any module-level registry.
    import gc
    import weakref
    ref = weakref.ref(model)
    del model, cache, fns1, r1, r2
    gc.collect()
    assert ref() is None


# ---------------------------------------------------------------------------
# Decode bursts
# ---------------------------------------------------------------------------


def _burst_stream_run(model, params, reqs, burst, stream=None,
                      speculate=None, draft_planes=None, **eng_kw):
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, **eng_kw)
        sched = Scheduler(
            eng, on_token=None if stream is None else
            (lambda uid, tok, done: stream.append((uid, tok, done))))
        out = sched.run(reqs, burst=burst, speculate=speculate,
                        draft_planes=draft_planes)
    finally:
        ops.force_backend(None)
    return eng, sched, out


def test_burst_token_streams_identical_to_single_step():
    """K-token bursts are a pacing change, not a semantic one: per-uid
    token streams (values, order, done flags) must equal burst=1 exactly,
    including requests that hit their budget mid-burst."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(8)
    # max_new 2/5/9 against burst=4: finishes land mid-burst, at a burst
    # boundary, and across two bursts.
    sizes, news = [4, 6, 5], [2, 5, 9]

    def reqs():
        rng2 = np.random.RandomState(8)
        return [Request(uid=i, prompt=p, max_new=n)
                for i, (p, n) in enumerate(
                    zip(_prompts(rng2, cfg, sizes), news))]

    stream1, streamK = [], []
    _, s1, out1 = _burst_stream_run(model, params, reqs(), 1, stream1,
                                    max_slots=3, max_len=128)
    engK, sK, outK = _burst_stream_run(model, params, reqs(), 4, streamK,
                                       max_slots=3, max_len=128)
    assert set(out1) == set(outK)
    for uid in out1:
        np.testing.assert_array_equal(out1[uid], outK[uid])

    def per_uid(stream):
        per = {}
        for uid, tok, done in stream:
            per.setdefault(uid, []).append((tok, done))
        return per

    assert per_uid(stream1) == per_uid(streamK)
    # Same number of jitted decode steps in total — bursts only chunk
    # them (max remaining budget of 9 after the admission token -> 8
    # decode rounds either way) — and the engine agrees with the
    # scheduler's accounting.
    assert s1.stats.decode_steps == sK.stats.decode_steps == 8
    assert engK.decode_steps == sK.stats.decode_steps
    assert sK.stats.emitted_tokens == sum(news)


def test_decode_kv_block_counter_counts_live_and_grid_blocks():
    """serve_decode_kv_blocks_total counts, per decode step, the paged
    kernel's live KV blocks (those holding a slot <= pos: sum over rows
    of pos // block_l + 1) and its whole grid (slots x logical blocks);
    a burst of K counts K steps at pos, pos+1, ... The count is made on
    the host from the pos array the engine already holds: no transfer
    to or from the device."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, max_slots=3, max_len=384)
        bl = eng.block_l
        assert eng.nmax == 3
        fam = eng.obs.registry.counter("serve_decode_kv_blocks_total",
                                       labels=("kind",))
        toks = np.zeros(3, np.int32)
        eng.decode(toks, np.array([0, bl + 2, 2 * bl + 44]))
        # rows hold 1, 2 and 3 live blocks of 3
        assert fam.total(kind="live") == 1 + 2 + 3
        assert fam.total(kind="grid") == 3 * 3
        eng.decode_burst(toks, np.array([0, bl - 1, 2 * bl - 1]), 3)
        # pos + 0: blocks 1, 1, 2; pos + 1: 1, 2, 3; pos + 2: 1, 2, 3
        assert fam.total(kind="live") == 6 + (4 + 6 + 6)
        assert fam.total(kind="grid") == 9 + 3 * 9
    finally:
        ops.force_backend(None)
    with jax.transfer_guard("disallow"):
        eng._count_kv_blocks(np.array([0, 0, 3 * bl - 1]))
    assert fam.total(kind="live") == 22 + 1 + 1 + 3
    assert fam.total(kind="grid") == 36 + 9


def test_burst_clamps_to_budget_and_capacity():
    """A burst never outruns max_len (hard) or the largest remaining
    token budget (efficiency): with max_new=3 everywhere, burst=32 must
    execute exactly the 2 decode steps burst=1 would."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(9)
    reqs = [Request(uid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts(rng, cfg, [4, 7]))]
    eng, sched, out = _burst_stream_run(model, params, reqs, 32,
                                        max_slots=2, max_len=128)
    assert all(len(out[i]) == 3 for i in (0, 1))
    assert sched.stats.decode_steps == 2
    assert eng.decode_steps == 2
    # near the max_len wall the hard clamp takes over: a prompt of 126
    # in a 128-budget engine leaves exactly 2 positions.
    rng = np.random.RandomState(9)
    req = [Request(uid=0, prompt=_prompts(rng, cfg, [126])[0], max_new=8)]
    eng2, sched2, out2 = _burst_stream_run(model, params, req, 32,
                                           max_slots=1, max_len=128)
    assert len(out2[0]) == 2  # admission token + 2 steps, capped by len
    assert sched2.stats.decode_steps == 2


def test_burst_defers_admission_and_preemption_to_boundaries():
    """Preemption happens only while setting up a burst (never inside
    one), and a slot freed mid-burst is refilled at the next boundary —
    bursts still drain everything with streams equal to burst=1."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))

    def reqs():
        rng = np.random.RandomState(10)
        return [Request(uid=i, prompt=p, max_new=n)
                for i, (p, n) in enumerate(
                    zip(_prompts(rng, cfg, [126, 126, 4]), [6, 6, 4]))]

    # 3-block pool, two block-crossing requests: the younger is evicted
    # at a burst boundary and recovers, exactly as with burst=1.
    _, s1, out1 = _burst_stream_run(model, params, reqs(), 1,
                                    max_slots=2, max_len=256, num_blocks=3)
    _, sK, outK = _burst_stream_run(model, params, reqs(), 4,
                                    max_slots=2, max_len=256, num_blocks=3)
    assert sK.stats.preemptions >= 1
    assert set(out1) == set(outK)
    for uid in out1:
        np.testing.assert_array_equal(out1[uid], outK[uid])


def test_burst_finished_slot_recycled_at_next_boundary():
    """A request finishing mid-burst frees its slot during the burst's
    replay; the very next step's admission must reuse that slot (no idle
    step in between) — and the recycled streams equal burst=1."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))

    def reqs():
        rng = np.random.RandomState(12)
        return [Request(uid=i, prompt=p, max_new=n)
                for i, (p, n) in enumerate(
                    zip(_prompts(rng, cfg, [4, 4, 4]), [2, 9, 3]))]

    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
        slot_of = {}
        sched = Scheduler(eng)
        sched.on_token = lambda uid, tok, done: slot_of.setdefault(
            uid, next(st.slot for st in sched.running.values()
                      if st.req.uid == uid))
        for r in reqs():
            sched.submit(r)
        steps = []
        while not sched.idle:
            steps.append(sched.step(burst=4))
        _, s1, out1 = _burst_stream_run(model, params, reqs(), 1,
                                        max_slots=2, max_len=128)
    finally:
        ops.force_backend(None)
    # uid 0 (max_new=2) finishes inside the first 4-token burst...
    done_step = {u: i for i, em in enumerate(steps)
                 for u, _, d in em if d}
    first_step = {}
    for i, em in enumerate(steps):
        for u, _, _ in em:
            first_step.setdefault(u, i)
    assert done_step[0] == 0
    # ...and uid 2 takes its slot at the very next burst boundary
    assert first_step[2] == 1
    assert slot_of[2] == slot_of[0]
    for u in out1:
        np.testing.assert_array_equal(sched.finished[u], out1[u])

    # preemption during a burst composes with the recycling: with a
    # 3-block pool the younger crosser is evicted mid-run at a burst
    # boundary while the short request recycles the finisher's slot —
    # everything still drains token-identical to burst=1.
    def reqs2():
        rng = np.random.RandomState(13)
        return [Request(uid=i, prompt=p, max_new=n)
                for i, (p, n) in enumerate(
                    zip(_prompts(rng, cfg, [126, 126, 4]), [6, 6, 3]))]

    _, sA, outA = _burst_stream_run(model, params, reqs2(), 1,
                                    max_slots=2, max_len=256, num_blocks=3)
    _, sB, outB = _burst_stream_run(model, params, reqs2(), 4,
                                    max_slots=2, max_len=256, num_blocks=3)
    assert sB.stats.preemptions >= 1
    assert sB.stats.admitted > sB.stats.finished == 3  # readmissions
    for uid in outA:
        np.testing.assert_array_equal(outA[uid], outB[uid])


def test_burst_matches_generate_interpret():
    """Bit-exact end to end: burst-decoded tokens over the fused
    interpret kernels equal per-request contiguous generate."""
    cfg, model = _model("mistral-large-123b", "sfp-m2e4")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(11)
    reqs = [Request(uid=i, prompt=p, max_new=n)
            for i, (p, n) in enumerate(
                zip(_prompts(rng, cfg, [5, 9]), [4, 6]))]
    ops.force_backend("interpret")
    try:
        eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
        out = Scheduler(eng).run(reqs, burst=3)
        for r in reqs:
            want = engine.generate(model, params,
                                   jnp.asarray(r.prompt)[None],
                                   max_new=r.max_new, max_len=eng.max_len)
            np.testing.assert_array_equal(out[r.uid],
                                          np.asarray(want.tokens[0]))
    finally:
        ops.force_backend(None)


def _sliced_step(model, params, mem, tables, toks, pos):
    """A paged decode step of a model of global layers with the pool as
    the layer scan's xs and ys: each layer's pool sliced out of the stack,
    run as a pool of one layer, and stacked back. Returns (next tokens,
    mem)."""
    def layer(h, x):
        p, pool = x
        view = kvcache.PoolLayer(jax.tree.map(lambda a: a[None], pool), 0)
        h, pool, _ = model._decode_slot(p["slot0"], h, view, pos, GLOBAL,
                                        tables=tables)
        return h, jax.tree.map(lambda a: a[0], pool)

    assert model.cfg.period == (GLOBAL,) and not model.cfg.remainder
    h, pool = jax.lax.scan(layer, model._embed(params, toks),
                           (params["periods"], mem["periods"]["slot0"]))
    logits = model._logits(params, h)
    nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    return nxt, {"periods": {"slot0": pool}}


@pytest.mark.parametrize("burst", [1, 2])
def test_carried_pool_matches_pool_sliced_per_layer(burst):
    """The engine's step carries the stacked pool through the layer scan
    and addresses it by layer index. Over the interpret kernels, its
    tokens and its whole new memory must equal, bit for bit, the same
    steps run with each layer's pool sliced out and stacked back: three
    single steps, or a burst of 2. One slot crosses a block boundary, one
    is idle on the trash block."""
    cfg, model = _model("mistral-large-123b", "sfp-m2e4")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(17)
    prompts = _prompts(rng, cfg, [126, 7])
    ops.force_backend("interpret")
    try:
        eng = engine.PagedEngine(model, params, max_slots=3, max_len=256)
        toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        for slot, p in enumerate(prompts):
            assert eng.pool.alloc_upto(slot, p.size + 3)
            toks[slot], pos[slot] = eng.prefill_into_slot(slot, p), p.size
        tables = jnp.asarray(eng.pool.tables)
        sliced = jax.jit(functools.partial(_sliced_step, model))
        mem = jax.tree.map(jnp.copy, eng.mem)
        cur, want = toks, []
        for i in range(3 if burst == 1 else burst):
            nxt, mem = sliced(params, mem, tables, jnp.asarray(cur)[:, None],
                              jnp.asarray(pos + i))
            if burst == 1:
                got, _ = eng.decode(cur, pos + i)
                np.testing.assert_array_equal(got, nxt)
                jax.tree.map(np.testing.assert_array_equal, eng.mem, mem)
            cur = np.asarray(nxt)
            want.append(cur)
        if burst > 1:
            got, _ = eng.decode_burst(toks, pos, burst)
            np.testing.assert_array_equal(got, np.stack(want))
            jax.tree.map(np.testing.assert_array_equal, eng.mem, mem)
    finally:
        ops.force_backend(None)


# ---------------------------------------------------------------------------
# Self-speculative decoding
# ---------------------------------------------------------------------------


def test_speculate_token_streams_identical_to_single_step():
    """Greedy self-speculation is a pacing change, not a semantic one:
    the full-width verify corrects every draft divergence, so per-uid
    streams (values, order, done flags) must equal burst=1 exactly — and
    drafting reads the *same* pool blocks, so peak pool usage must equal
    a burst run of the same horizon (zero additional pool bytes)."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    sizes, news = [4, 6, 5], [2, 5, 9]

    def reqs():
        rng = np.random.RandomState(8)
        return [Request(uid=i, prompt=p, max_new=n)
                for i, (p, n) in enumerate(
                    zip(_prompts(rng, cfg, sizes), news))]

    stream1, streamS = [], []
    _, s1, out1 = _burst_stream_run(model, params, reqs(), 1, stream1,
                                    max_slots=3, max_len=128)
    engB, _, _ = _burst_stream_run(model, params, reqs(), 4,
                                   max_slots=3, max_len=128)
    engS, sS, outS = _burst_stream_run(model, params, reqs(), 1, streamS,
                                       speculate=4,
                                       max_slots=3, max_len=128)
    assert set(out1) == set(outS)
    for uid in out1:
        np.testing.assert_array_equal(out1[uid], outS[uid])

    def per_uid(stream):
        per = {}
        for uid, tok, done in stream:
            per.setdefault(uid, []).append((tok, done))
        return per

    assert per_uid(stream1) == per_uid(streamS)
    # Draft + verify touch only blocks a K-burst would also own: the
    # same-horizon burst run is the pool-bytes ceiling.
    assert engS.pool.stats().peak_used == engB.pool.stats().peak_used
    # The speculative run drafted something and the verify accepted a
    # nonzero prefix somewhere (greedy drafts at 7 of 8 payload bits
    # agree with full width most steps).
    assert sS.stats.spec_rounds >= 1 and sS.stats.drafted > 0
    assert sS.stats.draft_accepted > 0


def test_speculate_acceptance_bookkeeping():
    """Counters and per-request terminal records stay consistent:
    accepted + rejected == drafted globally, per-uid drafted/accepted
    sum to the scheduler totals, and the engine's model-step accounting
    charges K draft + K verify steps per round."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(21)
    reqs = [Request(uid=i, prompt=p, max_new=n)
            for i, (p, n) in enumerate(
                zip(_prompts(rng, cfg, [4, 7]), [6, 9]))]
    eng, sched, out = _burst_stream_run(model, params, reqs, 1,
                                        speculate=3,
                                        max_slots=2, max_len=128)
    s = sched.stats
    assert s.spec_rounds >= 1
    assert s.draft_accepted + s.draft_rejected == s.drafted > 0
    res = [sched.results[r.uid] for r in reqs]
    assert all(r.status == "ok" for r in res)
    assert sum(r.drafted for r in res) == s.drafted
    assert sum(r.draft_accepted for r in res) == s.draft_accepted
    assert all(0 <= r.draft_accepted <= r.drafted for r in res)
    # one spec round = K draft + K verify jitted model steps (K may be
    # clamped below 3 near the budget wall, but always pairs up)
    assert eng.decode_steps == s.decode_steps
    assert s.decode_steps % 2 == 0
    assert s.decode_steps <= 6 * s.spec_rounds
    # the KV block counter charges both passes of every round
    kv = sched.obs.registry.counter("serve_decode_kv_blocks_total",
                                    labels=("kind",))
    assert kv.total(kind="grid") == s.decode_steps * eng.max_slots * eng.nmax
    assert 0 < kv.total(kind="live") <= kv.total(kind="grid")
    assert s.emitted_tokens == sum(len(v) for v in out.values())


def test_speculate_dense_geometry_and_draft_depth():
    """Dense bit-plane pools speculate too, across the legal draft-depth
    range: the minimum prefix (dexp_bits + 2) and the widest
    (payload - 1) both stream token-identical to burst=1."""
    cfg, model = _model("mistral-large-123b", "sfp-m3e5")
    params = model.init(jax.random.PRNGKey(0))

    def reqs():
        rng = np.random.RandomState(5)
        return [Request(uid=i, prompt=p, max_new=n)
                for i, (p, n) in enumerate(
                    zip(_prompts(rng, cfg, [5, 8]), [5, 7]))]

    _, _, out1 = _burst_stream_run(model, params, reqs(), 1,
                                   max_slots=2, max_len=128)
    fields = codecs.get("sfp-m3e5").pack_fields(cfg.compute_dtype)
    lo, hi = fields.dexp_bits + 2, fields.payload_bits - 1
    assert lo <= hi
    for dp in {lo, hi}:
        _, sched, outS = _burst_stream_run(model, params, reqs(), 1,
                                           speculate=2, draft_planes=dp,
                                           max_slots=2, max_len=128)
        for uid in out1:
            np.testing.assert_array_equal(out1[uid], outS[uid])
        assert sched.stats.drafted > 0


def test_speculate_validates_inputs():
    """Bad speculation knobs fail loudly at the host boundary: a
    non-positive K, a draft depth outside the container's legal prefix
    range, and speculation over a raw (uncontainered) cache all raise."""
    cfg, model = _model("mistral-large-123b", "sfp8")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    reqs = [Request(uid=0, prompt=_prompts(rng, cfg, [4])[0], max_new=2)]
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, max_slots=1, max_len=128)
        with pytest.raises(ValueError):
            Scheduler(eng).run(reqs, speculate=0)
        fields = codecs.get("sfp8").pack_fields(cfg.compute_dtype)
        for bad in (fields.dexp_bits + 1, fields.payload_bits + 1):
            with pytest.raises(ValueError):
                eng.validate_draft_planes(bad)
    finally:
        ops.force_backend(None)


# ---------------------------------------------------------------------------
# Policy-aware precision
# ---------------------------------------------------------------------------


def test_container_for_decision_mapping():
    # Learned decisions now deploy as *dense* bit-plane geometries: the
    # payload is exactly 1 + dexp + man bits (an 8-bit budget like m3e4
    # keeps the fixed-lane word layout as the fast path).
    assert precision.container_for_decision(3.0, 4.0) == "sfp-m3e4"
    assert precision.container_for_decision(2.3, 3.7) == "sfp-m3e4"
    assert precision.container_for_decision(7.0, 5.0) == "sfp-m7e5"
    # exponent clamps into the delta field range
    assert precision.container_for_decision(3.0, 8.0) == "sfp-m3e7"
    assert precision.container_for_decision(1.0, 1.0) == "sfp-m1e2"
    f8 = codecs.get("sfp-m3e4").pack_fields(jnp.bfloat16)
    assert f8.payload_bits == 8 and not f8.dense  # fast path survives
    f7 = codecs.get("sfp-m2e4").pack_fields(jnp.bfloat16)
    assert (f7.payload_bits, f7.dense) == (7, True)


def test_parametric_sfp_codec_resolves_and_roundtrips():
    codec = codecs.get("sfp8-m3e4")  # sfp8 by another name
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 128), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(codec.roundtrip(x), np.float32),
        np.asarray(codecs.get("sfp8").roundtrip(x), np.float32))
    # learned geometry narrower than sfp16's default
    c2 = codecs.get("sfp16-m5e3")
    f = c2.pack_fields(jnp.float32)
    assert (f.man_keep, f.dexp_bits, f.payload_bits) == (5, 3, 16)
    y = c2.roundtrip(x)
    assert np.isfinite(np.asarray(y)).all()
    with pytest.raises(KeyError):
        codecs.get("sfp12-m3e4")  # only 8/16-bit payload words exist


def test_container_from_checkpoint_decision_stamp(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": np.zeros((2, 2), np.float32)}
    mgr.save(1, state, extra={"policy": "qm+qe", "container": "sfp8",
                              "decision": {"man_bits": 4.2,
                                           "exp_bits": 5.6}})
    name = precision.container_from_checkpoint(str(tmp_path))
    assert name == "sfp-m5e6"
    # the derived container is servable end-to-end: a dense 12-bit payload
    f = codecs.get(name).pack_fields(jnp.float32)
    assert f.payload_bits == 12 and f.man_keep == 5 and f.dexp_bits == 6
    assert f.dense

    # legacy checkpoints without a decision fall back to the run container
    mgr2 = CheckpointManager(str(tmp_path / "legacy"))
    mgr2.save(1, state, extra={"policy": "qm", "container": "sfp16"})
    assert precision.container_from_checkpoint(
        str(tmp_path / "legacy")) == "sfp16"
    mgr3 = CheckpointManager(str(tmp_path / "bare"))
    mgr3.save(1, state)
    assert (precision.container_from_checkpoint(str(tmp_path / "bare"))
            == codecs.DEFAULT_CONTAINER)
    with pytest.raises(FileNotFoundError):
        precision.container_from_checkpoint(str(tmp_path / "empty"))


def test_paged_engine_serves_policy_derived_container():
    """End to end: a pool built from a policy-derived parametric geometry
    generates tokens identical to contiguous generate with that codec."""
    cfg, model = _model("mistral-large-123b",
                        precision.container_for_decision(6.0, 5.0))
    assert model.kv_container == "sfp-m6e5"  # dense 12-bit payload
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(6)
    reqs = [Request(uid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts(rng, cfg, [5, 7]))]
    ops.force_backend("interpret")
    try:
        eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
        out = Scheduler(eng).run(reqs)
        for r in reqs:
            want = engine.generate(model, params,
                                   jnp.asarray(r.prompt)[None],
                                   max_new=r.max_new, max_len=eng.max_len)
            np.testing.assert_array_equal(out[r.uid],
                                          np.asarray(want.tokens[0]))
    finally:
        ops.force_backend(None)
