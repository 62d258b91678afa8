"""Overlapped + compressed disagg wire (PR 14): async double-buffered
page shipping (stage_request_pages / finalize_shipment + deferred
batched commit), native int8 shipments with fp<->int8 edge conversion
on mixed-mode pools, the migration.stage / migration.commit chaos
points, measured-load dynamic pool splitting, and the wire
observability counters.

The headline properties: with ``serving_wire_overlap`` on, every
shipped stream is STILL bit-identical to an uninterrupted solo run
(greedy AND sampled, under chaos too) and the 7-class page ledger sums
exactly at every intermediate wire state — mid-stage, mid-adopt,
mid-deferred-commit; an int8 engine's shipment lands on an fp pool
(and vice versa) through an edge conversion that reproduces the
destination engine's own cache bytes, so cross-mode handoffs are
bit-identical too; and wire format v2 stays additive — a v1 shipment
still adopts."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.inference.fleet import FleetRouter, ship_shipment
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.testing import chaos

CFG = LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden=128, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)
EKW = dict(max_batch=2, page_size=16, max_seq=128, n_pages=1 + 24,
           prefill_budget=32)


@pytest.fixture(autouse=True)
def _disarm():
    yield
    chaos.disarm()


def _mk_reqs(rng, n=4, max_new=8, sampled=()):
    reqs = []
    for i in range(n):
        prompt = rng.randint(1, CFG.vocab_size,
                             size=rng.randint(24, 48)).astype(np.int32)
        kw = (dict(temperature=0.8, top_p=0.9, seed=100 + i)
              if i in sampled else {})
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=max_new,
                            arrival=0.0, **kw))
    return reqs


def _mixed_router(donor_quant, decode_quant, overlap=False, **kw):
    """1 prefill + 1 decode sharing params, each pool with its own KV
    quant mode — the mixed-mode wire edge."""
    e0 = ServingEngine(CFG, seed=0, engine_id=0, kv_quant=donor_quant,
                       wire_overlap=overlap, **EKW)
    e1 = ServingEngine(CFG, params=e0.params, seed=0, engine_id=1,
                       kv_quant=decode_quant, wire_overlap=overlap,
                       **EKW)
    return FleetRouter(engines=[e0, e1], disagg_prefill=1,
                       retry_max=2, retry_base_delay=0.0, **kw)


def _solo_run(params, req, kv_quant=False):
    eng = ServingEngine(CFG, params=params, seed=0, kv_quant=kv_quant,
                        **EKW)
    ref = Request(rid=1000 + req.rid, prompt=req.prompt.copy(),
                  max_new_tokens=req.max_new_tokens,
                  temperature=req.temperature, top_p=req.top_p,
                  seed=req.seed)
    eng.run([ref])
    return ref.out_tokens


def _drain(router, limit=3000):
    steps = 0
    while router.step(now=1e18):
        steps += 1
        assert steps < limit, "fleet did not drain"
    return steps


def _settle(engine):
    if engine._deferred_free or engine.pool.pending_evict:
        engine.pool.release(engine._deferred_free)
        engine._deferred_free = []
        engine.pool.commit_evictable()


def _assert_clean(router):
    params = router.replicas[0].engine.params
    for rep in router.replicas:
        if not rep.alive:
            continue
        e = rep.engine
        _settle(e)
        acc = e.page_accounting()
        assert acc["total"] == e.n_pages - 1, (e.engine_id, acc)
        assert not any(acc[k] for k in
                       ("slot_owned", "slot_shared", "deferred_free",
                        "adapter", "in_flight")), (e.engine_id, acc)
    return params


def _run_and_check(router, reqs, kv_quant_solo=False):
    for r in reqs:
        router.submit(r, now=1e18)
    _drain(router)
    params = _assert_clean(router)
    bad = [r.rid for r in reqs if r.aborted or r.t_done is None
           or len(r.out_tokens) != r.max_new_tokens]
    assert not bad, bad
    for r in reqs:
        assert r.out_tokens == _solo_run(params, r,
                                         kv_quant=kv_quant_solo), r.rid


def _first_shipment(donor_quant=False, overlap=False):
    """One engine run far enough to export rid 0's full pages."""
    donor = ServingEngine(CFG, seed=0, engine_id=0,
                          kv_quant=donor_quant, wire_overlap=overlap,
                          **EKW)
    req = Request(rid=0, prompt=np.arange(1, 41, dtype=np.int32),
                  max_new_tokens=8, arrival=0.0)
    donor.submit(req)
    steps = 0
    while len(req.out_tokens) < 4:
        donor.step(now=1e18)
        steps += 1
        assert steps < 200
    return donor, req


# -- overlapped wire: staging, deferred commit, bit-identity ----------------


def test_export_owns_its_page_ids():
    """The staged export's gather runs asynchronously while the engine
    goes on: releasing the slot zeroes its row of ``_full_rows`` at
    once, so the page ids an export hands to the device are a copy, not
    a view of that row (a late gather through a view read page 0 for
    every page and shipped the sink: under six busy workers the fleet's
    stream then left the solo run's at its second token)."""
    donor, req = _first_shipment(overlap=True)
    slot, _tokens, _salt, _hashes, pg = donor._export_meta(req.rid)
    assert len(pg) == 2 and not np.shares_memory(pg, donor._full_rows)
    want = donor.export_request_pages(req.rid)
    staged = donor.stage_request_pages(req.rid)
    donor._full_rows[slot] = 0             # what releasing the slot does
    got = donor.finalize_shipment(staged)
    for name in ("k", "v"):
        np.testing.assert_array_equal(got[name], want[name])
    assert got["crc"] == want["crc"]


def test_overlap_flag_defaults_off_and_solo_engine_unaffected():
    assert GLOBAL_FLAGS.get("serving_wire_overlap") is False
    assert GLOBAL_FLAGS.get("serving_disagg_dynamic") is False
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, CFG.vocab_size, size=40).astype(np.int32)
    base = ServingEngine(CFG, seed=0, **EKW)
    r0 = Request(rid=0, prompt=prompt.copy(), max_new_tokens=8,
                 arrival=0.0)
    base.run([r0])
    # a solo wire_overlap engine never exports or adopts: identical
    over = ServingEngine(CFG, params=base.params, seed=0,
                         wire_overlap=True, **EKW)
    r1 = Request(rid=1, prompt=prompt.copy(), max_new_tokens=8,
                 arrival=0.0)
    over.run([r1])
    assert r0.out_tokens == r1.out_tokens
    assert over.stats["wire_export_ms"] == 0.0


def test_staged_export_finalize_matches_sync_export():
    """stage_request_pages + finalize_shipment must produce the same
    payload bytes, hashes, and crcs as the synchronous export — the
    overlap moves WHEN the copy happens, never WHAT is shipped."""
    donor, _req = _first_shipment()
    sync = donor.export_request_pages(0)
    staged = donor.stage_request_pages(0)
    assert staged["staged"] and staged["crc"] is None
    fin = donor.finalize_shipment(staged)
    assert fin["staged"] is False
    assert fin["hashes"] == sync["hashes"]
    assert fin["crc"] == sync["crc"]
    np.testing.assert_array_equal(np.asarray(fin["k"]), sync["k"])
    np.testing.assert_array_equal(np.asarray(fin["v"]), sync["v"])
    assert donor.shipment_bytes(fin) == donor.shipment_bytes(sync)
    # finalize is a pass-through for an already-materialized shipment
    assert donor.finalize_shipment(sync) is sync


def test_overlap_router_bit_identical_with_ledger_at_every_tick():
    """1 prefill + 1 decode with the overlapped wire: every stream
    (greedy + sampled) bit-identical to solo, and the fleet ledger sums
    exactly after EVERY router tick — including ticks where a staged
    export or a deferred commit is in flight."""
    router = _mixed_router(False, False, overlap=True)
    reqs = _mk_reqs(np.random.RandomState(5), n=4, sampled=(1, 3))
    for r in reqs:
        router.submit(r, now=1e18)
    steps = 0
    while router.step(now=1e18):
        steps += 1
        assert steps < 3000
        for rep in router.replicas:
            acc = rep.engine.page_accounting()
            assert acc["total"] == rep.engine.n_pages - 1, (steps, acc)
    st = router.fleet_stats()
    assert st["n_handoffs"] >= 4 and st["shipped_bytes"] > 0
    assert st["wire_export_ms"] > 0.0
    assert st["ship_queue_depth"] >= 1
    params = _assert_clean(router)
    for r in reqs:
        assert r.out_tokens == _solo_run(params, r), r.rid
    # every deferred commit flushed by drain end — nothing lingers
    assert not any(rep.engine._commit_pending
                   for rep in router.replicas)


def test_ledger_sums_mid_stage_and_mid_deferred_commit():
    """Engine-level: in_flight covers exactly the staged pages between
    begin_adopt and commit_adopt; under wire_overlap the committed
    pages move to the cache (idle) while their bytes wait in
    _commit_pending — the ledger sums exactly in BOTH windows, and the
    next dispatch flushes the pending scatter."""
    donor, _req = _first_shipment()
    ship = donor.export_request_pages(0)
    recv = ServingEngine(CFG, params=donor.params, seed=0,
                         wire_overlap=True, engine_id=1, **EKW)
    free0 = len(recv.pool.free)
    h = recv.begin_adopt(ship)
    assert h is not None
    acc = recv.page_accounting()                     # mid-stage
    assert acc["in_flight"] == len(ship["hashes"])
    assert acc["total"] == recv.n_pages - 1
    n = recv.commit_adopt(h)
    assert n == len(ship["hashes"])
    assert len(recv._commit_pending) == 1            # mid-commit
    acc = recv.page_accounting()
    assert acc["in_flight"] == 0
    assert acc["total"] == recv.n_pages - 1
    assert acc["cache_idle"] >= n
    # the deferred bytes land at the next dispatch, and the adopted
    # pages then serve a prefix-sharing request without re-prefill
    req = Request(rid=9, prompt=np.arange(1, 41, dtype=np.int32),
                  max_new_tokens=4, arrival=0.0)
    recv.submit(req)
    steps = 0
    while recv.step(now=1e18):
        steps += 1
        assert steps < 200
    assert not recv._commit_pending
    assert len(req.out_tokens) == 4
    ref = _solo_run(donor.params, Request(
        rid=99, prompt=np.arange(1, 41, dtype=np.int32),
        max_new_tokens=4, arrival=0.0))
    assert req.out_tokens == ref
    _settle(recv)
    acc = recv.page_accounting()
    assert acc["total"] == recv.n_pages - 1
    assert acc["in_flight"] == 0 and acc["deferred_free"] == 0
    assert acc["free"] + acc["cache_idle"] == free0  # nothing in limbo


# -- chaos: migration.stage / migration.commit ------------------------------


def test_chaos_stage_drop_falls_back_bit_identical():
    """The staging buffer is lost at finalize (chaos drop): the request
    still hands off, the decode pool re-prefills, streams are
    bit-identical and nothing leaks."""
    chaos.arm(chaos.FaultPlan(seed=0)
              .add("migration.stage", "drop", once=True, pool="prefill"))
    router = _mixed_router(False, False, overlap=True)
    reqs = _mk_reqs(np.random.RandomState(5), n=4, sampled=(1,))
    _run_and_check(router, reqs)
    st = router.fleet_stats()
    assert st["n_handoffs"] == 3          # the dropped one shipped 0


def test_chaos_stage_corrupt_rejected_by_crc_bit_identical():
    """A byte flipped after the staging crcs: the adopter rejects the
    poisoned page chain (nothing enters its cache), the persisted
    corruption exhausts the retry ladder, and the stream completes
    through the colocated fallback — bit-identical, leak-free."""
    chaos.arm(chaos.FaultPlan(seed=0)
              .add("migration.stage", "corrupt", once=True,
                   pool="prefill"))
    router = _mixed_router(False, False, overlap=True)
    reqs = _mk_reqs(np.random.RandomState(5), n=4, sampled=(1,))
    _run_and_check(router, reqs)
    st = router.fleet_stats()
    assert st["migration_rejected"] >= 1
    assert st["n_retry_exhausted"] >= 1


def test_chaos_commit_raise_aborts_leak_free_bit_identical():
    """migration.commit fires on the ADOPTER (decode pool — a
    prefill-scoped spec must not match): the raise lands before any
    state moves, adopt_pages aborts the staging leak-free, the wire
    reports a rejection, and the retried delivery (clean second
    attempt) completes the stream bit-identically."""
    chaos.arm(chaos.FaultPlan(seed=0)
              .add("migration.commit", "raise", once=True,
                   pool="decode"))
    router = _mixed_router(False, False, overlap=True)
    reqs = _mk_reqs(np.random.RandomState(5), n=4, sampled=(1,))
    _run_and_check(router, reqs)
    st = router.fleet_stats()
    assert st["migration_rejected"] >= 1


def test_chaos_commit_pool_scoping_prefill_spec_never_fires():
    """Strict pool scoping: a migration.commit spec pinned to the
    prefill pool can never match the decode-side commit ctx."""
    chaos.arm(chaos.FaultPlan(seed=0)
              .add("migration.commit", "raise", once=False,
                   pool="prefill"))
    router = _mixed_router(False, False, overlap=True)
    reqs = _mk_reqs(np.random.RandomState(5), n=3)
    _run_and_check(router, reqs)
    st = router.fleet_stats()
    assert st["migration_rejected"] == 0
    assert st["n_handoffs"] >= 3


# -- native int8 shipments + mixed-mode edges --------------------------------


@pytest.mark.parametrize("overlap", [False, True])
def test_int8_donor_to_fp_pool_bit_identical(overlap):
    """An int8 prefill pool ships native int8 bytes + scale planes; the
    fp decode pool converts at the edge with the kernels' exact dequant
    and the stream equals an fp solo run."""
    router = _mixed_router(True, False, overlap=overlap)
    reqs = _mk_reqs(np.random.RandomState(7), n=4, sampled=(1, 3))
    _run_and_check(router, reqs, kv_quant_solo=False)
    st = router.fleet_stats()
    assert st["n_handoffs"] >= 4
    assert st["migration_rejected"] == 0


@pytest.mark.parametrize("overlap", [False, True])
def test_fp_donor_to_int8_pool_bit_identical(overlap):
    """An fp prefill pool's shipment quantizes at the int8 decode
    pool's edge with the engine's own one-shot absmax/127 scale rule —
    byte-identical to what the int8 engine itself would have written,
    so the stream equals an int8 solo run."""
    router = _mixed_router(False, True, overlap=overlap)
    reqs = _mk_reqs(np.random.RandomState(7), n=4, sampled=(1, 3))
    _run_and_check(router, reqs, kv_quant_solo=True)
    st = router.fleet_stats()
    assert st["n_handoffs"] >= 4
    assert st["migration_rejected"] == 0


def test_int8_wire_ships_fewer_bytes_than_fp():
    """Same workload, same handoffs: the int8 fleet's wire bytes are
    >= 3x smaller than the fp fleet's (fp32 cache: int8 payload + fp32
    scale planes ~ 4x smaller)."""
    fp = _mixed_router(False, False)
    _run_and_check(fp, _mk_reqs(np.random.RandomState(7), n=4))
    q = _mixed_router(True, True)
    _run_and_check(q, _mk_reqs(np.random.RandomState(7), n=4),
                   kv_quant_solo=True)
    bfp, bq = fp.stats["shipped_bytes"], q.stats["shipped_bytes"]
    nfp, nq = fp.stats["n_handoffs"], q.stats["n_handoffs"]
    assert nfp == nq and nfp >= 4
    assert bq > 0 and bfp / bq >= 3.0, (bfp, bq)


def test_int8_shipment_redelivery_skip_safe():
    """At-least-once delivery of an int8 shipment: the second delivery
    to the SAME pool short-circuits on resident hashes (ok/0), and a
    cross-mode redelivery to an fp pool is skip-safe too via the
    target-keyed shipment_cache_hashes re-key."""
    donor, _req = _first_shipment(donor_quant=True)
    ship = donor.export_request_pages(0)
    assert ship["quant_mode"] == "int8" and ship["version"] == 2
    same = ServingEngine(CFG, params=donor.params, seed=0, kv_quant=True,
                         engine_id=1, **EKW)
    first = ship_shipment(ship, 0, same)
    assert first["status"] == "ok" and first["pages"] >= 2
    again = ship_shipment(ship, 0, same)
    assert (again["status"], again["pages"]) == ("ok", 0)
    cross = ServingEngine(CFG, params=donor.params, seed=0,
                          kv_quant=False, engine_id=2, **EKW)
    c1 = ship_shipment(ship, 0, cross)
    assert c1["status"] == "ok" and c1["pages"] >= 2
    c2 = ship_shipment(ship, 0, cross)
    assert (c2["status"], c2["pages"]) == ("ok", 0)
    for e in (same, cross):
        _settle(e)
        acc = e.page_accounting()
        assert acc["total"] == e.n_pages - 1
        assert acc["in_flight"] == 0


def test_wire_v1_shipment_still_adopts():
    """Additivity: a v1 shipment (no quant_mode / tokens / salt) from a
    same-mode donor still adopts; cross-mode v1 is the one remaining
    ValueError (nothing to re-key from)."""
    donor, _req = _first_shipment()
    ship = donor.export_request_pages(0)
    v1 = dict(ship)
    for k in ("quant_mode", "tokens", "salt"):
        v1.pop(k, None)
    v1["version"] = 1
    recv = ServingEngine(CFG, params=donor.params, seed=0, engine_id=1,
                         **EKW)
    assert recv.adopt_pages(v1) == len(ship["hashes"])
    q = ServingEngine(CFG, params=donor.params, seed=0, kv_quant=True,
                      engine_id=2, **EKW)
    with pytest.raises(ValueError, match="wire v1"):
        q.begin_adopt(v1)
    assert q.shipment_cache_hashes(v1) is None
    _settle(recv)
    assert recv.page_accounting()["total"] == recv.n_pages - 1


# -- measured-load dynamic pool splitting ------------------------------------


def test_dynamic_split_follows_phase_imbalance_bit_identical():
    """serving_disagg_dynamic on an unpinned 3-engine fleet: a
    prefill-heavy wave pulls the measured prefill share past the
    hysteresis band and promotes a decode engine; the following
    decode-heavy wave demotes one back. Streams stay bit-identical
    through both re-splits and the trajectory is observable."""
    e = [ServingEngine(CFG, seed=0, engine_id=0, **EKW)]
    for i in (1, 2):
        e.append(ServingEngine(CFG, params=e[0].params, seed=0,
                               engine_id=i, **EKW))
    router = FleetRouter(engines=e, disagg_dynamic=True,
                         dynamic_ewma=0.5, dynamic_hysteresis=0.2,
                         retry_max=2, retry_base_delay=0.0)
    assert router.disagg and not router._split_pinned
    assert router.fleet_stats()["fleet_n_prefill"] == 1
    rng = np.random.RandomState(11)
    # wave 1: long prompts, 1 decode token each — prefill-dominated
    wave1 = [Request(rid=i, prompt=rng.randint(
        1, CFG.vocab_size, size=90).astype(np.int32),
        max_new_tokens=2, arrival=0.0) for i in range(4)]
    for r in wave1:
        router.submit(r, now=1e18)
    _drain(router)
    st = router.fleet_stats()
    assert st["n_resplit"] >= 1
    assert st["fleet_n_prefill"] == 2        # promoted toward prefill
    # wave 2: short prompts, long decodes — decode-dominated
    wave2 = [Request(rid=10 + i, prompt=rng.randint(
        1, CFG.vocab_size, size=24).astype(np.int32),
        max_new_tokens=12, arrival=0.0) for i in range(4)]
    for r in wave2:
        router.submit(r, now=1e18)
    _drain(router)
    st = router.fleet_stats()
    assert st["fleet_n_prefill"] == 1        # demoted back
    assert st["n_resplit"] >= 2
    assert st["split_ratio"] == pytest.approx(1 / 3, abs=1e-3)
    traj = st["split_trajectory"]
    assert traj[0] == pytest.approx(1 / 3, abs=1e-3)
    assert max(traj) == pytest.approx(2 / 3, abs=1e-3)
    params = _assert_clean(router)
    for r in wave1 + wave2:
        assert not r.aborted and len(r.out_tokens) == r.max_new_tokens
        assert r.out_tokens == _solo_run(params, r), r.rid


def test_static_pin_disables_dynamic_controller():
    """An explicit disagg_prefill=N is a pin: the controller never
    moves the split even with the dynamic flag on."""
    router = _mixed_router(False, False, disagg_dynamic=True)
    assert router._split_pinned
    reqs = [Request(rid=i, prompt=np.random.RandomState(13).randint(
        1, CFG.vocab_size, size=90).astype(np.int32),
        max_new_tokens=2, arrival=0.0) for i in range(3)]
    _run_and_check(router, reqs)
    st = router.fleet_stats()
    assert st["n_resplit"] == 0
    assert st["split_trajectory"] == [0.5]


# -- loadgen phase_imbalance knob -------------------------------------------


def test_phase_imbalance_alternates_and_earlier_streams_pinned():
    from paddle_tpu.inference.loadgen import WorkloadSpec, synthesize

    base_spec = dict(n_requests=64, seed=17, vocab_size=256,
                     process="poisson", rate=8.0, new_min=4, new_max=16,
                     tail_min=8, tail_max=64, max_seq=128)
    base = synthesize(WorkloadSpec(**base_spec))
    wl = synthesize(WorkloadSpec(**base_spec, phase_imbalance=0.8,
                                 phase_epoch_s=2.0,
                                 phase_imbalance_len=48))
    # earlier streams byte-identical: arrivals and undecorated requests
    # untouched (the fifth RandomState never perturbs draws 1-4)
    assert [r.arrival for r in wl] == [r.arrival for r in base]
    heavy = raised = 0
    for b, w in zip(base, wl):
        even = int(w.arrival // 2.0) % 2 == 0
        if len(w.prompt) != len(b.prompt):
            assert even
            assert len(w.prompt) >= len(b.prompt)
            np.testing.assert_array_equal(w.prompt[:len(b.prompt)],
                                          b.prompt)
            assert w.max_new_tokens <= b.max_new_tokens
            heavy += 1
        elif w.max_new_tokens != b.max_new_tokens:
            assert not even
            assert w.max_new_tokens > b.max_new_tokens
            raised += 1
        else:
            np.testing.assert_array_equal(w.prompt, b.prompt)
        assert len(w.prompt) + w.max_new_tokens <= base_spec["max_seq"]
    assert heavy >= 5 and raised >= 5, (heavy, raised)
    # determinism: same spec -> same decorated stream
    wl2 = synthesize(WorkloadSpec(**base_spec, phase_imbalance=0.8,
                                  phase_epoch_s=2.0,
                                  phase_imbalance_len=48))
    for a, b2 in zip(wl, wl2):
        np.testing.assert_array_equal(a.prompt, b2.prompt)
        assert a.max_new_tokens == b2.max_new_tokens


# -- flags-off pinning -------------------------------------------------------


def test_new_flags_default_off():
    assert GLOBAL_FLAGS.get("serving_wire_overlap") is False
    assert GLOBAL_FLAGS.get("serving_disagg_dynamic") is False
    assert GLOBAL_FLAGS.get("serving_disagg_ewma") == pytest.approx(0.3)
    assert GLOBAL_FLAGS.get("serving_disagg_hysteresis") \
        == pytest.approx(0.2)
