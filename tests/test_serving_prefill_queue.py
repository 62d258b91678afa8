"""The queue inside the engine (PR 38): when a prompt first gets a row
(lifecycle ``prefill-start``), which slots hold a prompt that got none
(``engine.step``'s slot ledger, ``waste_prefill_wait_slot_tokens``), and
the dispatch split into ``engine.dispatch.schedule`` and ``.launch``."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu.inference.loadgen.metrics import summarize
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.llama import LlamaConfig

CFG = LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden=128, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)
# two rows of 16 tokens a tick
EKW = dict(page_size=16, max_seq=128, n_pages=1 + 24, prefill_budget=32)


@pytest.fixture()
def ring():
    """A fresh ring for the test; the process default (on) after it."""
    st = obs.arm()
    yield st.tracer
    obs.arm()


def _spans(events, name):
    """(begin, end, end attrs) of every closed span of ``name`` on engine
    0's track, in order."""
    out, open_b = [], []
    for e in events:
        if e["name"] != name or e["tid"] != 1:
            continue
        if e["ph"] == "B":
            open_b.append(e["ts"])
        elif e["ph"] == "E":
            out.append((open_b.pop(), e["ts"], e.get("args", {})))
    return out


def _lifecycle(events, rid, event):
    return [e for e in events if e.get("cat") == "req" and e["id"] == rid
            and e["args"]["event"] == event]


def _drive(eng, reqs):
    """Submit and step to the end; per tick, the requests whose prompt
    the dispatched tick carried a row of."""
    for r in reqs:
        eng.submit(r)
    carried = []
    while eng.step(now=1e9):
        rows = eng._inflight[1] if eng._inflight is not None else ()
        carried.append({r[2].rid for r in rows if r[3] != "dec"})
        assert len(carried) < 500
    return carried


def _random_requests(seed, n=10):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(1, 256, size=rng.randint(
        20, 90)).astype(np.int32), max_new_tokens=int(rng.randint(3, 10)),
        arrival=0.0) for i in range(n)]


def test_a_prefix_hit_waits_behind_a_cold_prompts_chunks(ring):
    """FIFO prefill rows: a hit admitted right behind a cold 4-chunk
    prompt gets no row until the cold prompt's last chunk has run, and
    the ticks it waited are the slot ledger's."""
    eng = ServingEngine(CFG, seed=0, max_batch=2, **EKW)
    rng = np.random.RandomState(0)
    doc = rng.randint(1, 256, size=48).astype(np.int32)
    eng.run([Request(rid=0, prompt=doc.copy(), max_new_tokens=2)])
    ring = obs.arm().tracer                       # the second phase only
    cold = Request(rid=1, prompt=rng.randint(1, 256, size=64).astype(
        np.int32), max_new_tokens=3)
    hit = Request(rid=2, prompt=doc.copy(), max_new_tokens=3)
    carried = _drive(eng, [cold, hit])
    events, _ = ring.snapshot()
    steps = _spans(events, "engine.step")
    tick_of = lambda ev: next(i for i, (a, b, _) in enumerate(steps)
                              if a <= ev["ts"] <= b)

    (adm_c,), (adm_h,) = (_lifecycle(events, r, "admit") for r in (1, 2))
    assert (adm_c["args"]["prompt_tokens"],
            adm_c["args"]["cached_tokens"]) == (64, 0)
    assert (adm_h["args"]["prompt_tokens"],
            adm_h["args"]["cached_tokens"]) == (48, 32)
    (start_c,), (start_h,) = (_lifecycle(events, r, "prefill-start")
                              for r in (1, 2))
    cold_last = max(i for i, rids in enumerate(carried) if 1 in rids)
    assert tick_of(adm_c) == tick_of(adm_h) == tick_of(start_c) == 0
    assert cold_last == 1                         # 4 chunks, 2 rows a tick
    assert tick_of(start_h) == cold_last + 1
    assert min(i for i, rids in enumerate(carried) if 2 in rids) == 2
    waited = tick_of(start_h) - tick_of(adm_h)
    assert sum(a["slots_prefill_waiting"] for _, _, a in steps) == waited
    assert waited == 2
    # and the request's phases keep their order
    for rid in (1, 2):
        at = [_lifecycle(events, rid, e)[0]["ts"]
              for e in ("admit", "prefill-start", "first-token", "done")]
        assert at == sorted(at)


def test_slot_ledger_bounds_and_the_slot_token_split(ring):
    """On every tick waiting <= prefilling <= slots; with nothing
    preempted the new counter is the ticks' waiting summed, a part of the
    mid-prefill bucket."""
    eng = ServingEngine(CFG, seed=0, max_batch=4, **EKW)
    reqs = _random_requests(1)
    _drive(eng, reqs)
    events, _ = ring.snapshot()
    steps = _spans(events, "engine.step")
    assert len(steps) == eng.stats["unified_steps"] + 1   # + the idle tick
    for _, _, a in steps:
        assert 0 <= a["slots_prefill_waiting"] <= a["slots_prefilling"]
        assert a["slots_prefilling"] <= a["slots"] == eng.B
    st = eng.stats
    assert st["preemptions"] == 0 and st["waste_preempted_slot_tokens"] == 0
    waiting = sum(a["slots_prefill_waiting"] for _, _, a in steps)
    assert waiting > 0                  # the decode rows crowd the prompts
    assert st["waste_prefill_wait_slot_tokens"] == waiting
    assert st["waste_prefill_wait_slot_tokens"] <= (
        st["waste_prefill_slot_tokens"])
    m = summarize(reqs, eng, wall_s=1.0)
    assert 0 < m["occ_waste_prefill_wait"] <= m["occ_waste_prefill"]
    # every admission's first row is announced once, and after its admit
    for r in reqs:
        (adm,), (start,) = (_lifecycle(events, r.rid, e)
                            for e in ("admit", "prefill-start"))
        assert adm["ts"] <= start["ts"]


def test_dispatch_splits_into_schedule_and_launch(ring):
    """Both nest inside engine.dispatch on its track, the schedule before
    the launch, and together take no more than it; a tick that dispatches
    nothing schedules and launches nothing."""
    eng = ServingEngine(CFG, seed=0, max_batch=4, **EKW)
    _drive(eng, _random_requests(2, n=6))
    events, _ = ring.snapshot()
    sched = _spans(events, "engine.dispatch.schedule")
    launch = _spans(events, "engine.dispatch.launch")
    inside = lambda spans, a, b: [(c, d) for c, d, _ in spans
                                  if a <= c and d <= b]
    dispatches = _spans(events, "engine.dispatch")
    assert len(sched) == len(dispatches)
    assert len(launch) == eng.stats["unified_steps"] == len(dispatches) - 1
    for a, b, _ in dispatches:
        (s,) = inside(sched, a, b)
        ls = inside(launch, a, b)
        assert len(ls) <= 1
        for c, d in ls:
            assert s[1] <= c
            assert (s[1] - s[0]) + (d - c) <= b - a
    # the last tick dispatched nothing
    assert not inside(launch, *dispatches[-1][:2])


def test_a_resumed_request_starts_its_prefill_again(ring):
    """Preempted and admitted again: a second ``prefill-start``, one per
    admission; the slot-tokens it waited are split out of the mid-prefill
    and re-prefill buckets together."""
    eng = ServingEngine(CFG, seed=0, max_batch=4, page_size=16, max_seq=96,
                        n_pages=9, prefill_budget=32, priorities=True)
    rng = np.random.RandomState(2)
    reqs = [Request(rid=i, prompt=rng.randint(1, 256, size=30).astype(
        np.int32), max_new_tokens=16, priority=0) for i in range(2)]
    reqs.append(Request(rid=2, prompt=rng.randint(1, 256, size=30).astype(
        np.int32), max_new_tokens=8, priority=5, arrival=0.001))
    eng.run(reqs)
    assert eng.stats["preemptions"] >= 1
    events, _ = ring.snapshot()
    victims = [r for r in reqs if r.n_preempted]
    assert victims
    for r in reqs:
        n_admit = len(_lifecycle(events, r.rid, "admit"))
        assert len(_lifecycle(events, r.rid, "prefill-start")) == n_admit
    assert any(len(_lifecycle(events, r.rid, "admit")) == 2 for r in victims)
    st = eng.stats
    assert st["waste_prefill_wait_slot_tokens"] <= (
        st["waste_prefill_slot_tokens"] + st["waste_preempted_slot_tokens"])
