"""Chunked ragged prefill + prefix caching: scheduler contracts.

Covers the round-6 serving rewrite: head-of-line-blocking-free admission
(with the aging barrier), abort(), the page-accounting invariant under a
randomized admit/abort/prefix-hit mix, sampled-stream invariance across
chunk/quantum boundaries, and the zero-redundant-prefill-FLOPs property
of a prefix-cache hit (asserted via the prefill-token counter)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.serving import Request, ServingEngine

CFG = LlamaConfig(vocab_size=512, hidden=128, n_layers=2, n_heads=8,
                  n_kv_heads=4, ffn_hidden=256, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)


def _isolated(engine, prompt, max_new):
    m = LlamaForCausalLM(CFG, params=engine.params, max_batch=1,
                         max_seq_len=256)
    toks = m.generate(np.asarray(prompt)[None], max_new_tokens=max_new)
    return [int(t) for t in np.asarray(toks)[0]]


def _drain(engine):
    while engine.step(now=1e9):
        pass


def _assert_accounting(engine):
    acc = engine.page_accounting()
    assert acc["total"] == engine.n_pages - 1, acc
    owned = [p for lst in engine._slot_owned for p in lst]
    shared = {p for lst in engine._slot_shared for p in lst}
    idle = {p for p, r in engine.pool.ref.items() if r == 0}
    groups = [set(engine.pool.free), set(owned), shared, idle,
              set(engine._deferred_free)]
    assert len(owned) == len(set(owned))          # no double-own
    for i in range(len(groups)):
        for j in range(i + 1, len(groups)):
            assert not (groups[i] & groups[j]), (i, j, groups)


def test_admission_skips_pool_blocked_request():
    """A pool-blocked large request must not starve smaller requests
    behind it (head-of-line fix): the small request runs first, and the
    large one still completes — with exactly its isolated tokens —
    once pages free up."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                           n_pages=1 + 6, prefill_budget=64,
                           prefix_cache=False)
    rng = np.random.RandomState(0)
    small0 = rng.randint(1, 512, size=16).astype(np.int32)
    big = rng.randint(1, 512, size=64).astype(np.int32)
    small1 = rng.randint(1, 512, size=16).astype(np.int32)
    r_small0 = Request(rid=0, prompt=small0, max_new_tokens=8)   # 2 pages
    r_big = Request(rid=1, prompt=big, max_new_tokens=16)        # 5 pages
    r_small1 = Request(rid=2, prompt=small1, max_new_tokens=8)   # 2 pages
    for r in (r_small0, r_big, r_small1):
        engine.submit(r)
    engine.step(now=0.0)
    # big is pool-blocked (4 free pages < 5) and SKIPPED: the small
    # request behind it is in a slot, big is still queued and aged
    assert r_small0 in engine.slots and r_small1 in engine.slots
    assert engine.queue == [r_big] and r_big.age >= 1
    _drain(engine)
    for r, p in ((r_small0, small0), (r_big, big), (r_small1, small1)):
        assert r.out_tokens == _isolated(engine, p, r.max_new_tokens), r.rid
    _assert_accounting(engine)


def test_admission_aging_barrier_prevents_starvation():
    """Once a blocked request's age exceeds admit_aging it becomes a
    barrier: nothing behind it is admitted, so every freed page flows to
    it. (Pure allocator test — no compute is dispatched.)"""
    engine = ServingEngine(CFG, max_batch=3, page_size=16, max_seq=128,
                           n_pages=1 + 4, prefill_budget=32,
                           prefix_cache=False, admit_aging=2)
    mk = lambda rid, T: Request(rid=rid,
                                prompt=np.ones(T, np.int32),
                                max_new_tokens=16)
    r0, r_big, r1 = mk(0, 16), mk(1, 48), mk(2, 16)   # 2 / 4 / 2 pages
    for r in (r0, r_big, r1):
        engine.submit(r)
    engine._admit(0.0)
    # first pass: r0 admitted, big skipped (2 free < 4), r1 admitted
    assert r0 in engine.slots and r1 in engine.slots
    assert engine.queue == [r_big] and r_big.age == 1
    for _ in range(3):                                # age past the bar
        engine._admit(0.0)
    assert r_big.age > engine.admit_aging
    # a new small request behind the aged one would fit after r0 leaves,
    # but the barrier must hold it back
    r2 = mk(3, 16)
    engine.submit(r2)
    engine._release_slot_pages(0, defer=False)
    engine._prefilling.pop(0, None)
    engine.slots[0] = None
    engine._admit(0.0)
    assert r2 in engine.queue and r2 not in engine.slots
    # once the big one's demand is met it goes first
    engine._release_slot_pages(engine.slots.index(r1), defer=False)
    engine._prefilling.pop(engine.slots.index(r1), None)
    engine.slots[engine.slots.index(r1)] = None
    engine._admit(0.0)
    assert r_big in engine.slots
    _assert_accounting(engine)


def test_abort_mid_flight_and_queued():
    """abort() releases a slot-resident request's pages through the
    deferred-free path (an in-flight quantum may still write them),
    drops a queued request outright, and neither corrupts the survivor's
    token stream."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                           prefill_budget=64)
    rng = np.random.RandomState(1)
    p0 = rng.randint(1, 512, size=20).astype(np.int32)
    p1 = rng.randint(1, 512, size=24).astype(np.int32)
    r0 = Request(rid=0, prompt=p0, max_new_tokens=40)
    r1 = Request(rid=1, prompt=p1, max_new_tokens=12)
    r_q = Request(rid=2, prompt=p0, max_new_tokens=4, arrival=1e8)
    for r in (r0, r1, r_q):
        engine.submit(r)
    for _ in range(4):                   # both decoding, quantum in flight
        engine.step(now=0.0)
    assert engine._inflight is not None
    assert engine.abort(0) and r0.aborted and r0.t_done is not None
    assert engine.abort(2) and r_q.aborted
    assert not engine.abort(99)          # unknown rid
    _assert_accounting(engine)
    _drain(engine)
    assert len(r0.out_tokens) < 40       # cut short
    assert r1.out_tokens == _isolated(engine, p1, 12)
    assert len(engine.pool.free) + len(
        [p for p, r in engine.pool.ref.items() if r == 0]) \
        == engine.n_pages - 1
    _assert_accounting(engine)


def test_page_accounting_invariant_randomized():
    """Randomized admits/aborts/prefix-cache hits: after EVERY step,
    free + slot-mapped + refcounted-cache + deferred pages must sum to
    n_pages - 1 with all groups disjoint (no leak, no double-free), and
    the occupancy ledger must balance."""
    engine = ServingEngine(CFG, max_batch=3, page_size=16, max_seq=128,
                           n_pages=1 + 14, prefill_budget=32)
    rng = np.random.RandomState(2)
    prefixes = [rng.randint(1, 512, size=32).astype(np.int32)
                for _ in range(2)]
    reqs = []
    for i in range(10):
        if rng.rand() < 0.5:             # shared-prefix request
            tail = rng.randint(1, 512, size=rng.randint(1, 16))
            prompt = np.concatenate([prefixes[rng.randint(2)],
                                     tail.astype(np.int32)])
        else:
            prompt = rng.randint(1, 512,
                                 size=rng.randint(4, 48)).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.randint(3, 12)),
                            temperature=float(rng.rand() < 0.3) * 0.8,
                            seed=i))
        engine.submit(reqs[-1])
    aborts = {4: 3, 9: 7, 15: 9}         # step index -> rid to abort
    steps = 0
    while engine.step(now=1e9):
        steps += 1
        if steps in aborts:
            engine.abort(aborts[steps])
        _assert_accounting(engine)
        assert steps < 500
    _assert_accounting(engine)
    st = engine.stats
    assert st["decode_slot_tokens"] == (
        st["decode_active_tokens"] + st["waste_prefill_slot_tokens"]
        + st["waste_queue_empty_slot_tokens"]
        + st["waste_admission_blocked_slot_tokens"]
        + st["waste_overrun_slot_tokens"]
        + st["waste_spec_rejected_slot_tokens"]), st
    done = [r for r in reqs if not r.aborted]
    assert done and all(
        len(r.out_tokens) == r.max_new_tokens for r in done)


def test_sampled_stream_invariant_to_chunk_and_quantum_boundaries():
    """The keyed-RNG contract end to end: a sampled request's token
    stream is bit-identical whether its prompt prefills in one dispatch
    or three, and whether its prefix came
    from the cache or was prefilled fresh."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 512, size=40).astype(np.int32)
    spec = dict(max_new_tokens=9, temperature=0.9, top_p=0.85, seed=17)

    def run(budget, warm=False):
        engine = ServingEngine(CFG, max_batch=2, page_size=16,
                               max_seq=128, prefill_budget=budget)
        if warm:                         # populate the prefix cache
            w = Request(rid=99, prompt=prompt.copy(), **spec)
            engine.run([w])
            assert engine.pool.cache     # pages actually cached
        r = Request(rid=0, prompt=prompt.copy(), **spec)
        engine.run([r])
        return r.out_tokens, engine

    base, _ = run(budget=64)             # one prefill dispatch
    chunked, _ = run(budget=16)          # three dispatches
    halved, _ = run(budget=32)
    cached, eng = run(budget=64, warm=True)
    assert base == chunked == halved == cached
    assert eng.pool.hits > 0             # the warm run's pages were hit


def test_prefix_cache_hit_skips_redundant_prefill_flops():
    """Acceptance: a repeated prompt prefix costs ZERO redundant prefill
    FLOPs — the prefill-token counter advances only by the non-cached
    tail, and the generated tokens still match exactly (greedy)."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                           prefill_budget=64)
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, 512, size=33).astype(np.int32)  # 2 pages + 1
    a = Request(rid=0, prompt=prompt.copy(), max_new_tokens=6)
    engine.run([a])
    pt0 = engine.stats["prefill_tokens"]
    assert pt0 == 33
    b = Request(rid=1, prompt=prompt.copy(), max_new_tokens=6)
    engine.run([b])
    # only the page holding the last prompt token is re-run (1 token)
    assert engine.stats["prefill_tokens"] == 1
    assert engine.stats["prefill_cached_tokens"] == 32
    assert b.out_tokens == a.out_tokens
    _assert_accounting(engine)


def test_cached_pages_evicted_under_pool_pressure():
    """Idle (refcount-0) cached pages are reclaimed on demand: a pool
    sized for one request at a time still serves a sequence of requests
    with distinct prompts while the cache is on."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                           n_pages=1 + 4, prefill_budget=64)
    rng = np.random.RandomState(5)
    reqs = [Request(rid=i,
                    prompt=rng.randint(1, 512, size=40).astype(np.int32),
                    max_new_tokens=8)
            for i in range(3)]           # each needs 3 pages of 4
    stats = engine.run(reqs)
    assert all(len(r.out_tokens) == 8 for r in reqs)
    assert stats["total_new_tokens"] == 24
    _assert_accounting(engine)


def test_run_reports_occupancy_decomposition():
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                           prefill_budget=32)
    rng = np.random.RandomState(6)
    reqs = [Request(rid=i,
                    prompt=rng.randint(1, 512, size=24).astype(np.int32),
                    max_new_tokens=6)
            for i in range(3)]
    stats = engine.run(reqs)
    parts = (stats["slot_occupancy"] + stats["occ_waste_queue_empty"]
             + stats["occ_waste_admission_blocked"]
             + stats["occ_waste_prefill"] + stats["occ_waste_overrun"]
             + stats["occ_waste_spec_rejected"])
    assert abs(parts - 1.0) < 0.01, stats
    assert 0.0 <= stats["prefill_padding_frac"] < 1.0
    assert "prefix_cache_hit_rate" in stats
