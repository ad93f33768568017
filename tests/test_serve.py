"""Paged-KV serving subsystem tests.

Covers: block-pool invariants (no double-booking, exact occupancy),
scheduler policies (FCFS admission by free-block budget, chunked
prefill, preempt-by-recompute) driven model-free by a fake engine loop,
chunked-prefill numerical equivalence against the full forward pass on a
deliberately non-contiguous block table, token-for-token equivalence of
the paged engine vs the contiguous-slot engine on mixed-length request
streams (including under preemption pressure and for MLA), pad
invariance of prefill, >1x effective capacity at equal KV memory, and
streaming + metrics accounting.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import Model
from repro.serve import (BlockPool, PagedServeEngine, PrefixCache, Request,
                         Scheduler, ServeEngine, set_block_tables)

RNG = jax.random.PRNGKey(0)


def _f32(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)


def _model(arch="opt_6_7b", **over):
    cfg = get_reduced(arch).replace(remat=False, dtype="float32",
                                    capacity_factor=8.0, **over)
    m = Model(cfg)
    return m, _f32(m.init(RNG))


def _requests(vocab, lens, max_new=6, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, vocab, (int(l),)),
                    max_new_tokens=max_new, **kw)
            for i, l in enumerate(lens)]


def _by_uid(reqs):
    return {r.uid: r.out_tokens for r in reqs}


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------


class TestBlockPool:
    def test_alloc_never_hands_out_trash_or_duplicates(self):
        pool = BlockPool(num_blocks=9, block_size=4)
        got = pool.alloc("a", 5) + pool.alloc("b", 3)
        assert 0 not in got
        assert len(set(got)) == 8
        assert pool.free_blocks == 0 and pool.alloc("c", 1) is None
        pool.check()

    def test_occupancy_accounting_exact(self):
        pool = BlockPool(num_blocks=11, block_size=4)    # 10 usable
        a = pool.alloc("a", 4)
        assert pool.used_blocks == 4 and pool.occupancy() == 0.4
        pool.free(a[:2], "a")
        assert pool.used_blocks == 2 and pool.free_blocks == 8
        pool.free(a[2:], "a")
        assert pool.occupancy() == 0.0
        pool.check()

    def test_double_free_and_wrong_owner_rejected(self):
        pool = BlockPool(num_blocks=5, block_size=4)
        a = pool.alloc("a", 1)
        pool.free(a, "a")
        with pytest.raises(AssertionError):
            pool.free(a, "a")
        b = pool.alloc("b", 1)
        with pytest.raises(AssertionError):
            pool.free(b, "a")

    def test_alloc_is_all_or_nothing(self):
        pool = BlockPool(num_blocks=4, block_size=4)     # 3 usable
        assert pool.alloc("a", 5) is None
        assert pool.free_blocks == 3                     # nothing stranded
        assert pool.blocks_for(9) == 3 and pool.blocks_for(0) == 0


# ---------------------------------------------------------------------------
# scheduler (model-free: a fake engine just advances kv_len / appends tokens)
# ---------------------------------------------------------------------------


def _drive(sched, max_ticks=200):
    """Fake engine: execute every plan without a model."""
    finished, preempt_events = [], 0
    for _ in range(max_ticks):
        if not sched.has_work():
            break
        plan = sched.plan_tick()
        finished.extend(plan.rejected)
        preempt_events += len(plan.preempted)
        for seq in plan.failed:
            sched.finish(seq)
            seq.req.done = True
            finished.append(seq.req)
        for seq in plan.decode:
            seq.kv_len += 1
            seq.req.out_tokens.append(0)
            if len(seq.req.out_tokens) >= seq.req.max_new_tokens:
                sched.finish(seq)
                seq.req.done = True
                finished.append(seq.req)
        if plan.prefill is not None:
            seq = plan.prefill.seq
            seq.kv_len += plan.prefill.length
            if seq.kv_len >= seq.prefill_target:
                seq.req.out_tokens.append(0)
                if len(seq.req.out_tokens) >= seq.req.max_new_tokens:
                    sched.finish(seq)
                    seq.req.done = True
                    finished.append(seq.req)
    return finished, preempt_events


class TestScheduler:
    def _sched(self, num_blocks=9, block_size=4, rows=2, buckets=(8,),
               max_blocks_per_seq=8):
        pool = BlockPool(num_blocks, block_size)
        return Scheduler(pool, rows=rows, buckets=buckets,
                         max_blocks_per_seq=max_blocks_per_seq), pool

    def test_fcfs_admission_bounded_by_rows(self):
        sched, _ = self._sched(rows=2)
        reqs = _requests(100, [6, 6, 6], max_new=2)
        for r in reqs:
            sched.submit(r)
        plan = sched.plan_tick()
        assert [s.uid for s in plan.admitted] == [0, 1]
        assert list(sched.waiting) == [reqs[2]]

    def test_admission_blocked_by_budget_no_skip_ahead(self):
        # A consumes most of the tick budget; B (the new queue head)
        # doesn't fit the residual, and the smaller C behind it — which
        # WOULD fit — must NOT jump the queue (FCFS)
        sched, pool = self._sched(num_blocks=7, block_size=4, rows=3,
                                  max_blocks_per_seq=6)
        sched.submit(Request(uid=0, prompt=np.zeros(16, np.int32),
                             max_new_tokens=4))     # budget 5 blocks
        sched.submit(Request(uid=1, prompt=np.zeros(9, np.int32),
                             max_new_tokens=2))     # needs 3 > 1 left
        sched.submit(Request(uid=2, prompt=np.zeros(2, np.int32),
                             max_new_tokens=1))     # needs 1 — would fit
        plan = sched.plan_tick()
        assert [s.uid for s in plan.admitted] == [0]
        assert [r.uid for r in sched.waiting] == [1, 2]

    def test_admission_reserve_capped_by_final_footprint(self):
        # final footprint == pool capacity exactly: the decode-headroom
        # reserve must not push the demand past capacity, or the request
        # can never be admitted (wedge found by the fuzz suite)
        sched, pool = self._sched(num_blocks=4, block_size=8, rows=1,
                                  max_blocks_per_seq=3)
        req = Request(uid=0, prompt=np.zeros(21, np.int32), max_new_tokens=3)
        sched.submit(req)
        finished, _ = _drive(sched)
        assert req.done and req.error is None
        assert len(req.out_tokens) == 3
        pool.check()
        assert pool.free_blocks == pool.capacity

    def test_impossible_request_rejected_not_queued_forever(self):
        sched, pool = self._sched(num_blocks=4, block_size=4,
                                  max_blocks_per_seq=8)
        big = Request(uid=0, prompt=np.zeros(20, np.int32), max_new_tokens=8)
        small = Request(uid=1, prompt=np.zeros(3, np.int32), max_new_tokens=2)
        for r in (big, small):
            sched.submit(r)
        finished, _ = _drive(sched)
        assert big.error == "too_long" and big.done
        assert small.error is None and small.done
        pool.check()

    def test_preemption_picks_youngest_and_recomputes(self):
        # 7 usable blocks (bs=2): two seqs of prompt 6 + 6 new tokens
        # need 6 blocks each at the end -> the pool must run dry during
        # decode and preempt the YOUNGER seq (uid 1), never the older
        sched, pool = self._sched(num_blocks=8, block_size=2, rows=2,
                                  buckets=(8,), max_blocks_per_seq=6)
        reqs = _requests(100, [6, 6], max_new=6)
        for r in reqs:
            sched.submit(r)
        finished, preempts = _drive(sched)
        assert preempts >= 1
        assert {r.uid for r in finished} == {0, 1}
        assert all(len(r.out_tokens) == 6 and r.error is None
                   for r in finished)
        # FCFS priority: the older request finished first, untouched
        assert finished[0].uid == 0
        pool.check()
        assert pool.occupancy() == 0.0

    def test_admission_bounded_by_max_seq_len_not_block_rounding(self):
        # max_seq_len=6 with block_size=4 rounds to 2 blocks = 8 slots;
        # a request totalling 7 tokens fits the BLOCKS but not the
        # sequence bound and must be rejected, not decoded past
        # max_seq_len (overrunning learned-position tables)
        pool = BlockPool(9, 4)
        sched = Scheduler(pool, rows=2, buckets=(8,), max_blocks_per_seq=2,
                          max_seq_len=6)
        fits = Request(uid=0, prompt=np.zeros(2, np.int32), max_new_tokens=4)
        over = Request(uid=1, prompt=np.zeros(3, np.int32), max_new_tokens=4)
        for r in (fits, over):
            sched.submit(r)
        finished, _ = _drive(sched)
        assert over.error == "too_long" and over.done
        assert fits.error is None and len(fits.out_tokens) == 4
        pool.check()

    def test_same_tick_admit_preempt_is_net_noop(self):
        # A1/A2 (old, decoding, both at a block boundary) + D (old,
        # mid-block) leave exactly one free block.  B is admitted this
        # tick (reserve 1 fits), then A1's top-up takes the last block
        # and A2's dry top-up preempts the youngest seqs: first B (the
        # same-tick admit), then D.  B must vanish from plan.admitted
        # and NOT appear in plan.preempted — it never held KV — while D
        # is a genuine preempt.
        from repro.serve.scheduler import SeqState
        pool = BlockPool(9, 4)                       # 8 usable
        sched = Scheduler(pool, rows=4, buckets=(16,), max_blocks_per_seq=8)

        def running(uid, kv, nblocks, admit_seq, row):
            req = Request(uid=uid, prompt=np.zeros(kv, np.int32),
                          max_new_tokens=8)
            req.out_tokens = [0]                     # decoding
            seq = SeqState(req=req, row=row, admit_seq=admit_seq,
                           prefill_target=kv, kv_len=kv,
                           table=pool.alloc(uid, nblocks))
            sched.running.append(seq)
            sched._free_rows.remove(row)
            return seq

        a1 = running(0, kv=12, nblocks=3, admit_seq=0, row=0)
        a2 = running(1, kv=12, nblocks=3, admit_seq=1, row=1)
        d = running(2, kv=1, nblocks=1, admit_seq=2, row=2)
        sched._admit_counter = 3
        assert pool.free_blocks == 1
        b = Request(uid=3, prompt=np.zeros(3, np.int32), max_new_tokens=1)
        sched.submit(b)
        plan = sched.plan_tick()
        admitted = {s.uid for s in plan.admitted}
        preempted = {s.uid for s in plan.preempted}
        assert admitted.isdisjoint(preempted)        # the identity
        assert admitted == set() and preempted == {2}
        assert [r.uid for r in sched.waiting] == [2, 3]   # arrival order
        assert {s.uid for s in plan.decode} == {0, 1}
        pool.check()

    def test_prefill_rides_buckets_and_chunks(self):
        sched, pool = self._sched(num_blocks=20, block_size=4, rows=1,
                                  buckets=(4, 8), max_blocks_per_seq=16)
        req = Request(uid=0, prompt=np.zeros(19, np.int32), max_new_tokens=1)
        sched.submit(req)
        chunks = []
        for _ in range(10):
            plan = sched.plan_tick()
            if plan.prefill is None:
                break
            chunks.append((plan.prefill.start, plan.prefill.length))
            plan.prefill.seq.kv_len += plan.prefill.length
        assert chunks == [(0, 8), (8, 8), (16, 3)]   # capped at top bucket


# ---------------------------------------------------------------------------
# chunked prefill == full forward (non-contiguous physical blocks)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["opt_6_7b", "minicpm3_4b"])
def test_chunked_paged_prefill_matches_forward(arch):
    """prefill_chunk x3 into a scrambled block table + decode must equal
    the full-sequence forward logits (teacher forcing, f32 exact-ish)."""
    m, params = _model(arch)
    cfg = m.cfg
    b, s = 1, 24
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                                   jnp.int32)}
    full = m.forward(params, batch)

    bs, nblk = 4, 10
    cache = m.init_paged_cache(b, num_blocks=16, block_size=bs,
                               max_blocks_per_seq=nblk)
    # deliberately scrambled, non-contiguous physical blocks
    table = np.full((1, nblk), -1, np.int32)
    table[0, :8] = [11, 3, 7, 14, 2, 9, 5, 12]
    cache = set_block_tables(cache, table)

    errs = []
    for c0, c1 in ((0, 7), (7, 15), (15, s - 4)):
        toks = batch["tokens"][:, c0:c1]
        logits, cache = m.prefill_chunk(params, {"tokens": toks}, cache,
                                        jnp.int32(c0), jnp.int32(c1 - c0 - 1))
        errs.append(float(jnp.abs(logits - full[:, c1 - 1]).max()))
    for t in range(s - 4, s - 1):
        logits, cache = m.decode_step(params, batch["tokens"][:, t:t + 1],
                                      cache, t)
        errs.append(float(jnp.abs(logits - full[:, t]).max()))
    assert max(errs) < 2e-4, errs


def test_paged_prefill_right_pad_is_dead_write():
    """Right-padded chunk positions must not corrupt later real tokens:
    padding a chunk to a bucket then writing the real tokens gives the
    same logits as never padding."""
    m, params = _model()
    cfg = m.cfg
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    full = m.forward(params, {"tokens": toks})

    cache = m.init_paged_cache(1, num_blocks=8, block_size=4,
                               max_blocks_per_seq=6)
    table = np.full((1, 6), -1, np.int32)
    table[0, :4] = [2, 5, 1, 6]
    cache = set_block_tables(cache, table)
    # chunk 1: 6 real tokens padded to 8 (pads write junk at pos 6..7)
    chunk = jnp.zeros((1, 8), jnp.int32).at[:, :6].set(toks[:, :6])
    _, cache = m.prefill_chunk(params, {"tokens": chunk}, cache,
                               jnp.int32(0), jnp.int32(5))
    # chunk 2: real tokens 6..11 must overwrite the pad junk exactly
    logits, cache = m.prefill_chunk(params, {"tokens": toks[:, 6:]}, cache,
                                    jnp.int32(6), jnp.int32(5))
    assert float(jnp.abs(logits - full[:, 11]).max()) < 2e-4


# ---------------------------------------------------------------------------
# engine equivalence: paged vs contiguous slots
# ---------------------------------------------------------------------------


def test_set_block_tables_does_not_alias_host_tables(monkeypatch):
    """The engine rewrites its host table buffer every tick while the
    step that reads the previous tables can still be in flight.  A host
    array handed to JAX must stay unmodified until its transfer
    completes, so ``set_block_tables`` hands JAX a private copy — a row
    view of the engine's buffer once let a chunked prefill read the
    NEXT tick's tables (all-zero tokens under load)."""
    import repro.serve.paging as paging
    handed = []
    real = jnp.asarray

    def spy(x, *a, **kw):
        handed.append(x)
        return real(x, *a, **kw)

    m, _ = _model()
    cache = m.init_paged_cache(1, num_blocks=8, block_size=4,
                               max_blocks_per_seq=64)
    host = np.arange(4 * 64, dtype=np.int32).reshape(4, 64)
    monkeypatch.setattr(paging.jnp, "asarray", spy)
    out = set_block_tables(cache, host[2:3])
    monkeypatch.undo()
    assert handed and not any(isinstance(x, np.ndarray)
                              and np.shares_memory(x, host) for x in handed)
    host.fill(-1)
    leaves = [l for p, l in jax.tree_util.tree_flatten_with_path(out)[0]
              if "block_tables" in jax.tree_util.keystr(p)]
    assert leaves and all((np.asarray(l).reshape(-1, 64)
                           == np.arange(128, 192)).all() for l in leaves)


def test_paged_engine_matches_contiguous_greedy():
    """Token-for-token greedy equivalence on a mixed-length stream, with
    prompts longer than the largest bucket (forces chunked prefill)."""
    m, params = _model()
    lens = [3, 9, 17, 30, 5, 12]
    ep = PagedServeEngine(m, params, num_blocks=24, block_size=8,
                          max_batch=3, max_seq_len=64,
                          prefill_buckets=(8, 16))
    done_p = ep.run(_requests(m.cfg.vocab_size, lens), max_ticks=400)
    ec = ServeEngine(m, params, slots=3, cache_len=64,
                     prefill_buckets=(8, 16))
    done_c = ec.run(_requests(m.cfg.vocab_size, lens), max_ticks=400)
    assert len(done_p) == len(done_c) == len(lens)
    assert _by_uid(done_p) == _by_uid(done_c)
    ep.pool.check()
    assert ep.pool.occupancy() == 0.0
    assert ep.metrics.counters["prefill_chunks"] > len(lens)  # chunking hit


def test_recycled_block_stale_pos_is_masked():
    """A freed block re-allocated at a different logical index still
    holds the dead owner's pos values; those satisfy kpos <= qpos, so
    the view must mask them (slot live only when stored pos == logical
    index) or the new sequence attends to dead K/V.  Deterministic
    repro: prefill A through physical blocks [1, 2], then hand block 1
    to B as its logical block 1 — B's logits must equal a clean-pool
    run exactly."""
    m, params = _model()
    cfg = m.cfg
    rng = np.random.default_rng(11)
    a_toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    b_toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 5)), jnp.int32)

    dirty = m.init_paged_cache(1, num_blocks=8, block_size=4,
                               max_blocks_per_seq=4)
    ta = np.full((1, 4), -1, np.int32)
    ta[0, :2] = [1, 2]
    dirty = set_block_tables(dirty, ta)
    _, dirty = m.prefill_chunk(params, {"tokens": a_toks}, dirty,
                               jnp.int32(0), jnp.int32(7))
    tb = np.full((1, 4), -1, np.int32)
    tb[0, :2] = [3, 1]                    # block 1 recycled, stale pos 1..3
    dirty = set_block_tables(dirty, tb)
    logits_dirty, _ = m.prefill_chunk(params, {"tokens": b_toks}, dirty,
                                      jnp.int32(0), jnp.int32(4))

    clean = m.init_paged_cache(1, num_blocks=8, block_size=4,
                               max_blocks_per_seq=4)
    clean = set_block_tables(clean, tb)
    logits_clean, _ = m.prefill_chunk(params, {"tokens": b_toks}, clean,
                                      jnp.int32(0), jnp.int32(4))
    np.testing.assert_allclose(np.asarray(logits_dirty),
                               np.asarray(logits_clean), atol=1e-6)


def test_recycled_blocks_never_leak_stale_kv():
    """A freed block re-allocated at a DIFFERENT logical index still
    holds the dead request's pos values; the view must mask them (a slot
    is live only when its stored pos equals its logical index), or a
    later sequence attends to the dead request's K/V.  Short request A
    retires early; long request B's decode top-ups then recycle A's
    blocks at higher logical indices."""
    m, params = _model()
    v = m.cfg.vocab_size

    def mk():       # A: 4 blocks, retires fast; B: grows to 16+ tokens
        rng = np.random.default_rng(7)
        return [Request(uid=0, prompt=rng.integers(0, v, (16,)),
                        max_new_tokens=2),
                Request(uid=1, prompt=rng.integers(0, v, (4,)),
                        max_new_tokens=14)]
    ep = PagedServeEngine(m, params, num_blocks=9, block_size=4,
                          max_batch=2, max_seq_len=32,
                          prefill_buckets=(8, 16))
    done_p = ep.run(mk(), max_ticks=300)
    ec = ServeEngine(m, params, slots=2, cache_len=32,
                     prefill_buckets=(8, 16))
    done_c = ec.run(mk(), max_ticks=300)
    assert _by_uid(done_p) == _by_uid(done_c)
    ep.pool.check()


def test_paged_engine_scan_stacked_layers():
    """scan_layers=True stacks cache leaves with a leading layers axis —
    the paged engine (incl. single-row prefill table slices) must work."""
    m, params = _model(scan_layers=True)
    lens = [3, 9, 17]
    ep = PagedServeEngine(m, params, num_blocks=24, block_size=8,
                          max_batch=2, max_seq_len=64,
                          prefill_buckets=(8, 16))
    done_p = ep.run(_requests(m.cfg.vocab_size, lens), max_ticks=300)
    ec = ServeEngine(m, params, slots=2, cache_len=64,
                     prefill_buckets=(8, 16))
    done_c = ec.run(_requests(m.cfg.vocab_size, lens), max_ticks=300)
    assert _by_uid(done_p) == _by_uid(done_c)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["phi4_mini_3_8b", "minicpm3_4b"])
def test_paged_engine_preemption_still_matches(arch):
    """A pool too small for the whole stream forces preempt-by-recompute;
    greedy outputs must be unchanged (RoPE GQA + MLA paged paths)."""
    m, params = _model(arch)
    lens = [3, 9, 17, 5]
    ep = PagedServeEngine(m, params, num_blocks=10, block_size=4,
                          max_batch=3, max_seq_len=40,
                          prefill_buckets=(8, 16))
    done_p = ep.run(_requests(m.cfg.vocab_size, lens), max_ticks=500)
    ec = ServeEngine(m, params, slots=3, cache_len=40,
                     prefill_buckets=(8, 16))
    done_c = ec.run(_requests(m.cfg.vocab_size, lens), max_ticks=500)
    assert ep.metrics.counters["preempted"] >= 1
    assert _by_uid(done_p) == _by_uid(done_c)
    ep.pool.check()


def test_prefill_pad_invariance():
    """Greedy outputs must not depend on how much padding the length
    bucket adds — pads are masked, not attended (both engines)."""
    m, params = _model()
    outs = []
    for buckets in ((16,), (32,)):
        eng = ServeEngine(m, params, slots=1, cache_len=64,
                          prefill_buckets=buckets)
        done = eng.run(_requests(m.cfg.vocab_size, [9], max_new=5))
        outs.append(done[0].out_tokens)
    for buckets in ((16,), (32,)):
        eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                               max_batch=1, max_seq_len=64,
                               prefill_buckets=buckets)
        done = eng.run(_requests(m.cfg.vocab_size, [9], max_new=5))
        outs.append(done[0].out_tokens)
    assert all(o == outs[0] for o in outs), outs


# ---------------------------------------------------------------------------
# capacity: paged admits beyond the old slot grid at equal KV memory
# ---------------------------------------------------------------------------


def test_paged_capacity_exceeds_slot_grid_at_equal_memory():
    """KV budget = 2 slots x 64 = 128 entries.  The slot grid caps at 2
    concurrent requests; the paged pool (16 usable blocks x 8 = the same
    128 entries) runs ~6 short requests concurrently and completes a
    stream whose old-style reservation (6 x 64 = 384) is 3x the memory."""
    m, params = _model()
    lens = [8, 6, 9, 7, 8, 5]
    eng = PagedServeEngine(m, params, num_blocks=17, block_size=8,
                           max_batch=6, max_seq_len=64,
                           prefill_buckets=(8, 16))
    done = eng.run(_requests(m.cfg.vocab_size, lens, max_new=4),
                   max_ticks=300)
    assert len(done) == len(lens)
    assert all(r.error is None and len(r.out_tokens) == 4 for r in done)
    s = eng.metrics.summary()
    assert s["peak_active"] > 2          # beyond the equal-memory slot grid
    assert s["counters"]["preempted"] == 0   # actual usage fits the pool
    eng.pool.check()


# ---------------------------------------------------------------------------
# streaming + metrics
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def test_streaming_and_metrics_accounting():
    m, params = _model()
    streamed = {}

    def on_token(tok, req):
        streamed.setdefault(req.uid, []).append(tok)

    eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                           max_batch=2, max_seq_len=64,
                           prefill_buckets=(16,), clock=_FakeClock())
    reqs = _requests(m.cfg.vocab_size, [5, 11, 7], max_new=4,
                     on_token=on_token)
    done = eng.run(reqs, max_ticks=200)
    assert len(done) == 3
    for r in done:
        assert streamed[r.uid] == r.out_tokens     # every token, in order
    s = eng.metrics.summary()
    assert s["counters"]["tokens_out"] == sum(len(r.out_tokens) for r in done)
    assert s["counters"]["completed"] == 3
    assert s["ttft_s"]["n"] == 3                   # one TTFT per request
    assert s["per_token_s"]["n"] == s["counters"]["tokens_out"] - 3
    assert 0.0 <= s["occupancy"]["peak"] <= 1.0
    assert eng.pool.occupancy() == 0.0             # fully drained
    blob = json.loads(eng.metrics.to_json())
    assert blob["counters"]["tokens_out"] == s["counters"]["tokens_out"]


def test_empty_prompt_rejected_not_crashed():
    """Zero-length prompts must be rejected by both engines, not crash
    the serving loop mid-run."""
    m, params = _model()
    for make in (lambda: PagedServeEngine(m, params, num_blocks=16,
                                          block_size=8, max_batch=2,
                                          max_seq_len=64,
                                          prefill_buckets=(16,)),
                 lambda: ServeEngine(m, params, slots=2, cache_len=64,
                                     prefill_buckets=(16,))):
        reqs = [Request(uid=0, prompt=np.zeros(0, np.int32),
                        max_new_tokens=3),
                Request(uid=1, prompt=np.arange(5) % m.cfg.vocab_size,
                        max_new_tokens=3)]
        done = make().run(reqs, max_ticks=100)
        assert len(done) == 2
        empty = next(r for r in done if r.uid == 0)
        assert empty.error == "empty_prompt" and empty.out_tokens == []
        assert next(r for r in done if r.uid == 1).error is None


def test_tick_budget_exhaustion_marks_requests_done():
    """``run`` hitting max_ticks must not strand requests neither done
    nor errored (callers polling ``req.done`` would hang forever): the
    drained requests carry error="tick_budget", land in ``finished``,
    and their pool blocks are freed."""
    m, params = _model()
    eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                           max_batch=2, max_seq_len=64,
                           prefill_buckets=(16,))
    reqs = _requests(m.cfg.vocab_size, [5, 7, 4], max_new=50)
    done = eng.run(reqs, max_ticks=2)
    assert len(done) == 3 and all(r.done for r in reqs)
    drained = [r for r in done if r.error == "tick_budget"]
    assert drained, "tick budget hit but nothing marked tick_budget"
    assert eng.metrics.counters["failed"] == len(drained)
    eng.pool.check()
    assert eng.pool.free_blocks == eng.pool.capacity


def test_engine_retires_at_max_seq_len_not_block_capacity():
    """A sequence that (via a deliberately loosened scheduler bound)
    would decode into its last block's slack must be retired by the
    ENGINE at max_seq_len: with max_seq_len=6 and block_size=4 the
    block-rounded capacity is 8, and pre-fix the engine decoded to 8
    tokens — positions 6 and 7 overrun a learned-position table sized
    to max_seq_len."""
    m, params = _model()
    eng = PagedServeEngine(m, params, num_blocks=16, block_size=4,
                           max_batch=2, max_seq_len=6, prefill_buckets=(8,))
    assert eng.max_blocks_per_seq * eng.block_size == 8     # the slack
    eng.sched.max_seq_len = 8        # simulate the old, loose admission
    # total 3 + 5 = 8 fits the loosened bound AND the block budget
    # (blocks_for(8) == 2), so the request is admitted and the ENGINE
    # bound is what must stop it at 6 tokens (pre-fix: decoded all 8)
    req = Request(uid=0, prompt=np.arange(3) % m.cfg.vocab_size,
                  max_new_tokens=5)
    done = eng.run([req], max_ticks=50)
    assert done and done[0].done and req.error is None
    assert len(req.out_tokens) == 3          # stopped at max_seq_len=6
    assert len(req.prompt) + len(req.out_tokens) <= 6
    eng.pool.check()


def test_admission_budget_reserved_within_tick():
    """One tick must not admit two requests whose combined prompt
    footprint exceeds the pool — blocks promised to the first admission
    count against the second's budget."""
    pool = BlockPool(num_blocks=11, block_size=4)     # 10 usable
    sched = Scheduler(pool, rows=2, buckets=(32,), max_blocks_per_seq=10)
    for i in range(2):                                # 8 blocks each
        sched.submit(Request(uid=i, prompt=np.zeros(31, np.int32),
                             max_new_tokens=1))
    plan = sched.plan_tick()
    assert [s.uid for s in plan.admitted] == [0]      # second waits


def test_contiguous_engine_rejects_overlong_prompt():
    """A prompt that can't fit cache_len must be rejected with an error,
    not silently truncated by the ring insert."""
    m, params = _model()
    eng = ServeEngine(m, params, slots=1, cache_len=32, prefill_buckets=(8,))
    reqs = _requests(m.cfg.vocab_size, [40, 6], max_new=3)
    done = eng.run(reqs, max_ticks=100)
    assert len(done) == 2
    big = next(r for r in done if r.uid == 0)
    ok = next(r for r in done if r.uid == 1)
    assert big.error == "too_long" and big.out_tokens == []
    assert ok.error is None and len(ok.out_tokens) == 3


def test_contiguous_engine_streams_too():
    m, params = _model()
    seen = []
    reqs = _requests(m.cfg.vocab_size, [6], max_new=3,
                     on_token=lambda t, r: seen.append(t))
    done = ServeEngine(m, params, slots=1, cache_len=32,
                       prefill_buckets=(8,)).run(reqs)
    assert seen == done[0].out_tokens and len(seen) == 3


# ---------------------------------------------------------------------------
# fuzz: random streams keep paged == contiguous and the books balanced
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fuzz_paged_matches_contiguous_under_pressure(seed):
    """Random prompt lengths / arrival orders / generation budgets on a
    pool small enough to force preemption: the paged engine must stay
    token-for-token equal to the contiguous-slot oracle, the metrics
    token counts must sum to the tokens actually emitted, and the pool
    must drain clean."""
    m, params = _model()
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(4, 8))
    lens = rng.integers(1, 28, n).tolist()
    news = rng.integers(1, 7, n).tolist()
    order = rng.permutation(n).tolist()

    def mk():
        r = np.random.default_rng(seed)
        reqs = [Request(uid=i, prompt=r.integers(0, m.cfg.vocab_size,
                                                 (int(lens[i]),)),
                        max_new_tokens=int(news[i]))
                for i in range(n)]
        return [reqs[i] for i in order]       # shuffled arrival order

    ep = PagedServeEngine(m, params, num_blocks=12, block_size=4,
                          max_batch=3, max_seq_len=48,
                          prefill_buckets=(8, 16))
    done_p = ep.run(mk(), max_ticks=600)
    ec = ServeEngine(m, params, slots=3, cache_len=48,
                     prefill_buckets=(8, 16))
    done_c = ec.run(mk(), max_ticks=600)
    assert len(done_p) == len(done_c) == n
    assert _by_uid(done_p) == _by_uid(done_c)
    s = ep.metrics.summary()
    emitted = sum(len(r.out_tokens) for r in done_p)
    assert s["counters"]["tokens_out"] == emitted
    # every emitted token is either a decode-step token or the token
    # sampled when a prefill completes; preempt-by-recompute adds at
    # most one extra prefill completion per preemption event
    first_toks = sum(1 for r in done_p if r.out_tokens)
    prefill_finishes = emitted - s["counters"]["decode_tokens"]
    assert first_toks <= prefill_finishes \
        <= first_toks + s["counters"]["preempted"]
    ep.pool.check()
    assert ep.pool.occupancy() == 0.0


# ---------------------------------------------------------------------------
# prefix cache: refcounted block sharing, CoW-by-recompute
# ---------------------------------------------------------------------------


def _shared_prefix_requests(vocab, *, prefix_len, tails, max_new=4, seed=3):
    """Requests sharing one random prefix, each with a unique tail."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, (prefix_len,))
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, vocab, (int(t),))]),
                    max_new_tokens=max_new)
            for i, t in enumerate(tails)]


def test_prefix_cache_on_off_token_equivalence():
    """Acceptance: a shared-prefix stream generates token-for-token
    identical outputs with the prefix cache on and off, while the on-run
    actually shares (hits > 0, fewer prefill chunks)."""
    m, params = _model()
    kw = dict(num_blocks=32, block_size=4, max_batch=3, max_seq_len=64,
              prefill_buckets=(8, 16))
    mk = lambda: _shared_prefix_requests(m.cfg.vocab_size, prefix_len=12,
                                         tails=[3, 5, 2, 7, 4])
    off = PagedServeEngine(m, params, **kw)
    done_off = off.run(mk(), max_ticks=400)
    on = PagedServeEngine(m, params, prefix_cache=True, **kw)
    done_on = on.run(mk(), max_ticks=400)
    assert _by_uid(done_on) == _by_uid(done_off)
    s = on.metrics.summary()
    assert s["prefix_cache"]["blocks_saved"] > 0
    assert s["prefix_cache"]["hit_rate"] > 0
    assert on.metrics.counters["prefill_chunks"] \
        < off.metrics.counters["prefill_chunks"]
    # the off-engine emits a neutral prefix section (glossary contract)
    assert off.metrics.summary()["prefix_cache"]["blocks_saved"] == 0
    assert off.metrics.summary()["effective_capacity"]["peak"] == 1.0
    on.pool.check()
    # drain: sequences released everything; only the cache still holds
    assert on.pool.used_blocks == len(on.prefix)
    on.prefix.clear()
    assert on.pool.free_blocks == on.pool.capacity


def test_prefix_cache_warm_probe_skips_prefill():
    """A repeat of an identical prompt adopts every full block short of
    the prefill target: exactly one prefill chunk, tokens_saved ==
    block-aligned cap, and TTFT reflects the skip (fewer ticks to the
    first token)."""
    m, params = _model()
    eng = PagedServeEngine(m, params, num_blocks=16, block_size=4,
                           max_batch=2, max_seq_len=64,
                           prefill_buckets=(8,), prefix_cache=True,
                           clock=_FakeClock())
    prompt = np.random.default_rng(5).integers(0, m.cfg.vocab_size, (16,))
    cold = Request(uid=0, prompt=prompt, max_new_tokens=3)
    eng.run([cold], max_ticks=100)
    chunks_cold = eng.metrics.counters["prefill_chunks"]
    assert chunks_cold == 2                       # 16 tokens / 8-bucket

    warm = Request(uid=1, prompt=prompt, max_new_tokens=3)
    eng.run([warm], max_ticks=100)
    assert warm.out_tokens == cold.out_tokens     # greedy, same prompt
    assert eng.metrics.counters["prefill_chunks"] == chunks_cold + 1
    # cap = (16-1)//4 = 3 full blocks -> 12 of 16 prompt tokens adopted
    assert eng.metrics.counters["prefix_tokens_saved"] == 12
    assert eng.metrics.counters["prefix_hit_requests"] == 1
    eng.pool.check()


def test_admission_budget_counts_only_new_blocks():
    """Regression (the budget bug this PR fixes): a request whose prompt
    is almost fully cache-resident must admit even when the free-block
    count alone could not cover its naive footprint — hit blocks are
    adopted, not allocated, so only NEW blocks count."""
    pool = BlockPool(num_blocks=9, block_size=4)      # 8 usable
    cache = PrefixCache(pool)
    sched = Scheduler(pool, rows=2, buckets=(8,), max_blocks_per_seq=8,
                      prefix_cache=cache)
    prompt = np.arange(16, dtype=np.int32) % 3
    # A prefills 16 tokens and keeps decoding: its 4 prompt blocks are
    # registered and stay PINNED (refcount 2: A + cache), so eviction
    # cannot rescue a naive budget check
    a = Request(uid=0, prompt=prompt, max_new_tokens=16)
    sched.submit(a)
    for _ in range(6):
        plan = sched.plan_tick()
        if plan.prefill is not None:
            plan.prefill.seq.kv_len += plan.prefill.length
        for seq in plan.decode:
            seq.kv_len += 1
            seq.req.out_tokens.append(0)
    assert sched.running and sched.running[0].kv_len > 16
    # A holds 5 blocks (4 prompt + 1 decode): free = 3, evictable = 0,
    # naive need for B = blocks_for(16) + reserve = 5 > 3
    assert pool.free_blocks == 3
    assert cache.evictable() == 0
    b = Request(uid=1, prompt=prompt.copy(), max_new_tokens=2)
    sched.submit(b)
    plan = sched.plan_tick()
    admitted = {s.uid for s in plan.admitted}
    assert 1 in admitted, "cache-resident request was starved"
    bseq = next(s for s in sched.running if s.uid == 1)
    assert bseq.prefix_hit == 3 and bseq.shared_tokens == 12
    # adopted blocks are now held by A, B and the cache
    assert all(pool.refcount(blk) == 3 for blk in bseq.table[:3])
    for seq in list(sched.running):
        sched.finish(seq)
    cache.clear()
    pool.check()
    assert pool.free_blocks == pool.capacity


def test_prefix_cache_cow_divergent_tail_recomputed():
    """Two prompts that diverge INSIDE a block: the divergent request
    must not adopt the partially-matching block (CoW-by-recompute), the
    overlap is reported as cow tokens, and outputs match the cache-off
    run."""
    m, params = _model()
    rng = np.random.default_rng(9)
    base = rng.integers(0, m.cfg.vocab_size, (13,))
    var = base.copy()
    var[9] = (var[9] + 1) % m.cfg.vocab_size      # diverge inside block 2
    kw = dict(num_blocks=16, block_size=4, max_batch=1, max_seq_len=64,
              prefill_buckets=(8,))
    mk = lambda: [Request(uid=0, prompt=base, max_new_tokens=3),
                  Request(uid=1, prompt=var, max_new_tokens=3)]
    off = PagedServeEngine(m, params, **kw)
    done_off = off.run(mk(), max_ticks=200)
    on = PagedServeEngine(m, params, prefix_cache=True, **kw)
    done_on = on.run(mk(), max_ticks=200)
    assert _by_uid(done_on) == _by_uid(done_off)
    # uid 1 adopts blocks 0-1 (8 equal tokens) and hits CoW on block 2:
    # one cached token of overlap (position 8) recomputed, not copied
    assert on.metrics.counters["prefix_cow_events"] == 1
    assert on.metrics.counters["prefix_cow_tokens"] == 1
    assert on.metrics.counters["prefix_hit_blocks"] == 2
    on.pool.check()


@pytest.mark.slow
def test_prefix_cache_equivalence_under_preemption():
    """Acceptance: cache-on == cache-off token-for-token even when the
    pool is small enough to force preempt-by-recompute — victims decref
    (never hard-free shared blocks) and re-probe the index on
    re-admission."""
    m, params = _model()
    kw = dict(num_blocks=11, block_size=4, max_batch=3, max_seq_len=48,
              prefill_buckets=(8, 16))
    mk = lambda: _shared_prefix_requests(m.cfg.vocab_size, prefix_len=9,
                                         tails=[8, 2, 6, 4], max_new=5,
                                         seed=11)
    off = PagedServeEngine(m, params, **kw)
    done_off = off.run(mk(), max_ticks=600)
    on = PagedServeEngine(m, params, prefix_cache=True, **kw)
    done_on = on.run(mk(), max_ticks=600)
    assert _by_uid(done_on) == _by_uid(done_off)
    assert on.metrics.counters["prefix_hit_blocks"] > 0
    on.pool.check()
    on.prefix.clear()
    assert on.pool.free_blocks == on.pool.capacity


def test_evictable_excludes_parents_pinned_under_live_children():
    """Regression: dedup can leave a cache-only PARENT entry above a
    child entry whose block a live sequence pins (refcounts are not
    non-increasing with depth).  Leaf-first eviction cannot free that
    parent, so ``evictable()`` must not count it — an optimistic budget
    made the scheduler over-admit and then crash on a failed alloc."""
    pool = BlockPool(num_blocks=10, block_size=4)
    cache = PrefixCache(pool)
    A, B = (0, 1, 2, 3), (4, 5, 6, 7)
    b1, b2 = pool.alloc(1, 2)                  # seq1's private blocks
    b3, b4 = pool.alloc(2, 2)                  # seq2's private blocks
    # both cold requests write chunk A privately; seq1 registers first,
    # seq2 dedups onto seq1's b1 and keeps its own b3 unindexed
    k0 = cache.register(None, A, b1)
    assert cache.register(None, A, b3) == k0
    # next tick seq2 registers its chunk-B block FIRST, so the CHILD
    # entry points at the second sequence's private block b4
    k1 = cache.register(k0, B, b4)
    assert cache.register(k0, B, b2) == k1
    pool.free([b1, b2], 1)                     # seq1 retires
    # the shape: parent entry -> b1 (cache-only), child entry -> b4
    # (pinned by live seq2) — a cache-only parent above a pinned child
    assert pool.refcount(b1) == 1 and pool.refcount(b4) == 2
    assert cache.evictable() == 0              # was 1: the overcount
    assert cache.evict(5) == 0                 # promise == delivery
    pool.free([b3, b4], 2)                     # seq2 retires
    assert cache.evictable() == 2              # whole chain now freeable
    assert cache.evict(5) == 2
    pool.check()
    assert pool.free_blocks == pool.capacity


def test_prefill_defers_when_eviction_underdelivers():
    """Regression: when ``_available()`` over-promises (historically the
    ``evictable()`` overcount) and ``_alloc`` still comes back empty,
    ``_plan_prefill`` must preempt or defer the chunk — never crash the
    tick extending a table with None."""
    pool = BlockPool(num_blocks=5, block_size=4)      # 4 usable
    cache = PrefixCache(pool)
    sched = Scheduler(pool, rows=2, buckets=(8,), max_blocks_per_seq=4,
                      prefix_cache=cache)
    cache.evictable = lambda: 2        # lie: promise blocks evict() can't free
    a = Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                max_new_tokens=8)
    b = Request(uid=1, prompt=np.arange(8, dtype=np.int32) + 1,
                max_new_tokens=1)
    sched.submit(a)
    sched.submit(b)
    plan = sched.plan_tick()           # over-admits b on the lied budget
    assert {s.uid for s in plan.admitted} == {0, 1}
    assert plan.prefill is not None and plan.prefill.seq.uid == 0
    plan.prefill.seq.kv_len += plan.prefill.length
    # a's decode drains the free list to 0; b's prefill needs 2 blocks,
    # _available() still claims 2, but eviction delivers nothing and b
    # has no younger victim — the chunk must be deferred, not crash
    plan = sched.plan_tick()
    assert [s.uid for s in plan.decode] == [0]
    assert plan.prefill is None
    bseq = next(s for s in sched.running if s.uid == 1)
    assert bseq.kv_len == 0 and bseq.table == []
    sched.finish(next(s for s in sched.running if s.uid == 0))
    plan = sched.plan_tick()           # pressure gone: b prefills now
    assert plan.prefill is not None and plan.prefill.seq.uid == 1
    pool.check()


def test_lookup_and_register_verify_parent_on_key_collision():
    """Regression: a key collision between (parentA, chunk) and
    (parentB, chunk) must degrade to a miss, never adopt KV computed
    under a different prefix.  Forced here with a degenerate chain hash
    that ignores the parent entirely."""
    pool = BlockPool(num_blocks=6, block_size=4)
    cache = PrefixCache(pool)
    cache._key = lambda parent, chunk: hash(chunk)    # drop the chain
    X, Y = (0, 1, 2, 3), (4, 5, 6, 7)
    b1, b2 = pool.alloc("w", 2)
    k0 = cache.register(None, X, b1)
    k1 = cache.register(k0, Y, b2)
    assert k1 is not None
    # querying [Y, ...] collides with the depth-1 entry at depth 0: the
    # tokens match but the parent does not — must be a miss
    hits, last = cache.lookup(list(Y + X), 2)
    assert hits == [] and last is None
    # the genuine chain still serves end-to-end
    hits, last = cache.lookup(list(X + Y), 2)
    assert hits == [b1, b2] and last == k1
    # register's dedup branch applies the same parent check: the same
    # colliding (None, Y) registration must refuse, not alias
    b3 = pool.alloc("v", 1)[0]
    assert cache.register(None, Y, b3) is None
    pool.free([b3], "v")
    pool.free([b1, b2], "w")
    cache.clear()
    pool.check()
    assert pool.free_blocks == pool.capacity


def test_register_with_evicted_parent_stops_chain():
    """Regression: registering under a parent key whose entry has been
    evicted (reachable when a sequence's chain key points at a dedup'd
    entry backed by another, retired sequence's block) must stop the
    chain — an orphaned root would be unreachable by lookup yet pin a
    pool block and pollute the sharing metrics."""
    pool = BlockPool(num_blocks=6, block_size=4)
    cache = PrefixCache(pool)
    b1 = pool.alloc("w", 1)[0]
    k0 = cache.register(None, (0, 1, 2, 3), b1)
    pool.free([b1], "w")                   # writer retires; cache-only
    assert cache.evict(1) == 1             # parent entry evicted
    b2 = pool.alloc("w", 1)[0]
    assert cache.register(k0, (4, 5, 6, 7), b2) is None
    assert len(cache) == 0                 # no orphaned root created
    assert cache.lookup([4, 5, 6, 7], 1) == ([], None)
    pool.free([b2], "w")
    pool.check()
    assert pool.free_blocks == pool.capacity


# ---------------------------------------------------------------------------
# async engine: double-buffered ticks + on-device sampling
# ---------------------------------------------------------------------------


def _mixed_sampling(reqs, base_seed=40):
    """Give every other request a seeded temperature/top-k profile so a
    stream exercises host-greedy AND device-categorical sampling."""
    for r in reqs[::2]:
        r.temperature, r.top_k, r.seed = 0.7, 12, base_seed + r.uid
    return reqs


@pytest.mark.parametrize("scenario", ["mixed_sampling", "preempt", "prefix"])
def test_async_engine_matches_sync(scenario):
    """The tentpole's acceptance bar: the double-buffered async tick is
    token-for-token AND schedule-for-schedule identical to the sync
    engine — under mixed greedy/seeded-sampling streams, under
    preemption pressure, and with the prefix cache adopting blocks."""
    m, params = _model()
    vocab = m.cfg.vocab_size
    kw = dict(num_blocks=16, block_size=8, max_batch=3, max_seq_len=64,
              prefill_buckets=(16,))
    if scenario == "preempt":
        # 9 usable blocks against four 3+-block footprints: forces
        # preempt-by-recompute, which async must replay identically
        kw.update(num_blocks=10, block_size=4)

    def make_reqs():
        if scenario == "prefix":
            rng = np.random.default_rng(5)
            prefix = rng.integers(0, vocab, (16,))
            return [Request(uid=i,
                            prompt=np.concatenate(
                                [prefix, rng.integers(0, vocab, (3 + i,))]),
                            max_new_tokens=5)
                    for i in range(4)]
        reqs = _requests(vocab, [9, 13, 6, 11], max_new=6)
        if scenario == "mixed_sampling":
            _mixed_sampling(reqs)
        return reqs

    runs, counters = {}, {}
    for mode in ("sync", "async"):
        eng = PagedServeEngine(m, params,
                               prefix_cache=(scenario == "prefix"), **kw)
        reqs = make_reqs()
        done = (eng.run(reqs, max_ticks=300) if mode == "sync"
                else eng.run_async(reqs, max_ticks=300))
        assert len(done) == len(reqs)
        assert all(r.error is None for r in done)
        eng.pool.check()
        if eng.prefix is not None:
            eng.prefix.clear()
        assert eng.pool.free_blocks == eng.pool.capacity
        runs[mode] = _by_uid(done)
        counters[mode] = {k: eng.metrics.counters[k]
                         for k in ("admitted", "preempted", "tokens_out",
                                   "prefill_chunks")}
    assert runs["async"] == runs["sync"]
    assert counters["async"] == counters["sync"]
    if scenario == "preempt":
        assert counters["sync"]["preempted"] > 0
    if scenario == "prefix":
        assert counters["sync"]["prefill_chunks"] > 0


def test_async_engine_overlaps_device_windows():
    """The async engine's reason to exist, measured: its union-merged
    dispatch->sync device windows must cover a larger fraction of the
    serving wall time than the sync engine's on the same workload."""
    m, params = _model()
    busy = {}
    for mode in ("sync", "async"):
        eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                               max_batch=3, max_seq_len=64,
                               prefill_buckets=(16,))
        reqs = _requests(m.cfg.vocab_size, [5, 7, 9], max_new=12)
        done = (eng.run(reqs, max_ticks=300) if mode == "sync"
                else eng.run_async(reqs, max_ticks=300))
        assert all(r.error is None for r in done)
        busy[mode] = eng.metrics.decode_inflight_fraction()
    assert 0.0 < busy["sync"] <= 1.0
    assert busy["async"] > busy["sync"], busy


def test_seeded_sampling_deterministic_and_seed_sensitive():
    """Per-request seeds make sampled decode reproducible run-to-run
    (fresh engine, fresh jit) and actually change tokens when changed."""
    m, params = _model()

    def run_once(base_seed):
        eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                               max_batch=2, max_seq_len=64,
                               prefill_buckets=(16,))
        reqs = _requests(m.cfg.vocab_size, [6, 9], max_new=8,
                         temperature=1.2)
        for r in reqs:
            r.seed = base_seed + r.uid
        return _by_uid(eng.run_async(reqs, max_ticks=200))

    a, b, c = run_once(3), run_once(3), run_once(123)
    assert a == b
    assert a != c


def test_sync_engine_honors_request_seed_like_async():
    """The host-side sampler must derive per-token keys exactly like the
    on-device path: same seeded requests, sync vs async, same tokens."""
    m, params = _model()
    outs = {}
    for mode in ("sync", "async"):
        eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                               max_batch=2, max_seq_len=64,
                               prefill_buckets=(16,))
        reqs = _requests(m.cfg.vocab_size, [6, 9], max_new=8,
                         temperature=0.9, top_k=8)
        for r in reqs:
            r.seed = 77 + r.uid
        done = (eng.run(reqs, max_ticks=200) if mode == "sync"
                else eng.run_async(reqs, max_ticks=200))
        outs[mode] = _by_uid(done)
    assert outs["sync"] == outs["async"]


def test_async_mode_interleaves_with_sync_mode():
    """step() flushes any in-flight async step first, so callers can mix
    tick modes mid-stream without losing or duplicating tokens."""
    m, params = _model()
    eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                           max_batch=2, max_seq_len=64,
                           prefill_buckets=(16,))
    reqs = _requests(m.cfg.vocab_size, [5, 8], max_new=6)
    for r in reqs:
        eng.submit(r)
    for i in range(200):
        if all(r.done for r in reqs):
            break
        (eng.step_async if i % 2 else eng.step)()
    eng.flush()
    assert all(r.done and r.error is None for r in reqs)
    mixed = _by_uid(reqs)

    ref_eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                               max_batch=2, max_seq_len=64,
                               prefill_buckets=(16,))
    ref = _by_uid(ref_eng.run(_requests(m.cfg.vocab_size, [5, 8],
                                        max_new=6), max_ticks=200))
    assert mixed == ref
    assert {len(v) for v in mixed.values()} == {6}


# ---------------------------------------------------------------------------
# callback isolation, deadlines, cancellation
# ---------------------------------------------------------------------------


def test_callback_error_fails_only_that_request():
    """Regression: a raising on_token callback must not wedge the tick —
    the offending request retires with error="callback", everyone else
    decodes to completion, and the pool balances.  All three loops:
    paged sync, paged async, slots."""
    m, params = _model()

    def boom(tok, req):
        raise RuntimeError("client went away")

    def check(done, reqs, pool=None):
        bad = next(r for r in done if r.uid == 0)
        good = [r for r in done if r.uid != 0]
        assert bad.done and bad.error == "callback"
        assert all(r.error is None and len(r.out_tokens) == 4
                   for r in good)
        if pool is not None:
            pool.check()
            assert pool.free_blocks == pool.capacity

    for mode in ("sync", "async"):
        eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                               max_batch=2, max_seq_len=64,
                               prefill_buckets=(16,))
        reqs = _requests(m.cfg.vocab_size, [5, 7, 6], max_new=4)
        reqs[0].on_token = boom
        done = (eng.run(reqs, max_ticks=200) if mode == "sync"
                else eng.run_async(reqs, max_ticks=200))
        check(done, reqs, eng.pool)
        assert eng.metrics.counters["failed"] == 1

    slot_eng = ServeEngine(m, params, slots=2, cache_len=64,
                           prefill_buckets=(16,))
    reqs = _requests(m.cfg.vocab_size, [5, 7, 6], max_new=4)
    reqs[0].on_token = boom
    check(slot_eng.run(reqs, max_ticks=200), reqs)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_deadline_expiry_frees_blocks_waiting_and_running(mode):
    """Deadline sweep at the top of every tick, both modes: an expired
    WAITING request fails without ever touching the pool; an expired
    RUNNING request keeps its partial output, retires with
    error="deadline", and releases its blocks."""
    m, params = _model()
    eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                           max_batch=2, max_seq_len=64,
                           prefill_buckets=(16,))
    step = eng.step_async if mode == "async" else eng.step
    reqs = _requests(m.cfg.vocab_size, [5, 7], max_new=6)
    expired, live = reqs
    expired.deadline_s = -1.0              # already past on any clock
    eng.submit(expired)
    eng.submit(live)
    step()
    assert expired.done and expired.error == "deadline"
    assert expired.out_tokens == []
    for _ in range(4):                     # let the live one make progress
        step()
    assert live.out_tokens and not live.done
    live.deadline_s = -1.0
    step()
    eng.flush()
    assert live.done and live.error == "deadline"
    assert 0 < len(live.out_tokens) < 6
    eng.pool.check()
    assert eng.pool.free_blocks == eng.pool.capacity
    assert eng.metrics.counters["deadline_expired"] == 2
    assert eng.metrics.counters["failed"] == 2


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_cancel_waiting_and_running_releases_blocks(mode):
    m, params = _model()
    eng = PagedServeEngine(m, params, num_blocks=16, block_size=8,
                           max_batch=1, max_seq_len=64,
                           prefill_buckets=(16,))
    step = eng.step_async if mode == "async" else eng.step
    running, queued = _requests(m.cfg.vocab_size, [5, 7], max_new=8)
    eng.submit(running)
    eng.submit(queued)                     # max_batch=1: stays waiting
    for _ in range(3):
        step()
    assert running.out_tokens and not running.done
    assert eng.cancel(queued)              # still waiting
    assert queued.done and queued.error == "cancelled"
    assert eng.cancel(running)             # mid-decode
    eng.flush()
    assert running.done and running.error == "cancelled"
    assert not eng.cancel(running)         # already finished
    eng.pool.check()
    assert eng.pool.free_blocks == eng.pool.capacity
    assert eng.metrics.counters["cancelled"] == 2
