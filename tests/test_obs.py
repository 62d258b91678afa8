"""Observability plane (PR 19; always on since PR 27): span tracer +
Chrome export, typed metrics registry, flight recorder, and the contract
that recording changes nothing.

The ring is armed when ``paddle_tpu.obs`` is imported; ``obs.arm()`` gives
a test a fresh ring of its own and ``obs.disarm()`` is the control. The
headline property mirrors the chaos harness: token streams are
bit-identical with the ring on AND after ``disarm()`` — tracing observes
host control flow, never steers it.
"""

import glob
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu.inference.fleet import FleetRouter
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.obs.metrics import (FLEET_STATS_SCHEMA, Histogram,
                                    MetricsRegistry, SERVING_STATS_SCHEMA)
from paddle_tpu.obs.trace import Tracer
from paddle_tpu.testing import chaos

CFG = LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden=128, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)
EKW = dict(max_batch=2, page_size=16, max_seq=128, n_pages=1 + 24,
           prefill_budget=32)


@pytest.fixture(autouse=True)
def _fresh_ring():
    """Every test starts from the process default (a ring that is on) and
    leaves it so for the tests after it."""
    assert obs.active()
    yield
    chaos.disarm()
    obs.arm()


def _mk_reqs(rng, n=4, max_new=10, sampled=()):
    reqs = []
    for i in range(n):
        prompt = rng.randint(1, CFG.vocab_size,
                             size=rng.randint(24, 48)).astype(np.int32)
        kw = (dict(temperature=0.8, top_p=0.9, seed=100 + i)
              if i in sampled else {})
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=max_new,
                            arrival=0.0, **kw))
    return reqs


def _assert_chrome_valid(doc):
    """The structural contract Perfetto needs: JSON-serializable, B/E
    balanced per track, every async end's id opened by an async begin."""
    json.loads(json.dumps(doc))
    evs = doc["traceEvents"]
    stacks: dict = {}
    open_async: dict = {}
    for ev in evs:
        ph = ev["ph"]
        if ph == "B":
            stacks.setdefault(ev["tid"], []).append(ev["name"])
        elif ph == "E":
            assert stacks.get(ev["tid"]), f"orphan E {ev}"
            stacks[ev["tid"]].pop()
        elif ph == "b":
            k = (ev["name"], ev["id"])
            open_async[k] = open_async.get(k, 0) + 1
        elif ph == "e":
            k = (ev["name"], ev["id"])
            assert open_async.get(k), f"orphan async e {ev}"
            open_async[k] -= 1
    assert all(not s for s in stacks.values()), stacks
    assert all(n == 0 for n in open_async.values()), open_async
    names = {e["name"] for e in evs if e["ph"] == "M"}
    assert "process_name" in names and "thread_name" in names


# -- tracer unit behavior ----------------------------------------------------

def test_span_nesting_attrs_and_error_tagging():
    tr = Tracer(capacity=128)
    with tr.span("outer", tid=1, attrs={"k": 1}) as sp:
        with tr.span("inner", tid=1):
            tr.instant("tick", tid=1, attrs={"n": 2})
        sp.set(rows=3)            # counts land on the span's end
    with pytest.raises(RuntimeError):
        with tr.span("boom", tid=0):
            raise RuntimeError("x")
    evs = list(tr.events)
    assert [(e["name"], e["ph"]) for e in evs] == [
        ("outer", "B"), ("inner", "B"), ("tick", "i"), ("inner", "E"),
        ("outer", "E"), ("boom", "B"), ("boom", "E")]
    assert evs[0]["args"] == {"k": 1}
    assert evs[2]["args"] == {"n": 2} and evs[2]["s"] == "t"
    assert evs[4]["args"] == {"rows": 3} and "args" not in evs[3]
    assert evs[-1]["args"] == {"error": "RuntimeError"}
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)
    _assert_chrome_valid(tr.export())


def test_export_balances_truncated_and_overflowed_ring(tmp_path):
    # an open B gets a synthetic closer; an E whose B fell off a tiny
    # ring is dropped; async flows balance the same way
    tr = Tracer(capacity=4)
    tr.begin("lost")          # will fall off the ring
    for i in range(4):
        tr.instant(f"i{i}")
    tr.end("lost")            # orphan E: its B left the ring
    tr.begin("open")          # never ended: synthetic closer
    tr.async_event("req", 7, "b")
    tr.complete("after-the-fact", 0.25)   # ph X: passes through whole
    doc = tr.export(path=str(tmp_path / "t.json"))
    _assert_chrome_valid(doc)
    evs = doc["traceEvents"]
    assert not any(e["ph"] == "E" and e["name"] == "lost" for e in evs)
    closers = [e for e in evs if e.get("args", {}).get("truncated")]
    assert {(e["name"], e["ph"]) for e in closers} == {("open", "E"),
                                                       ("req", "e")}
    x = [e for e in evs if e["ph"] == "X"]
    assert len(x) == 1 and x[0]["dur"] == pytest.approx(0.25e6)
    assert doc["otherData"]["n_emitted"] == 9
    events, n = tr.snapshot()
    assert n == 9 and len(events) == 4 == tr.capacity
    on_disk = json.load(open(tmp_path / "t.json"))
    assert on_disk["traceEvents"] == evs


# -- histogram vs raw percentiles -------------------------------------------

def test_histogram_percentiles_agree_with_raw_lists():
    rng = np.random.RandomState(0)
    xs = np.exp(rng.normal(loc=-3.0, scale=1.2, size=5000))  # ~latencies
    h = Histogram("ttft_seconds")
    for x in xs:
        h.observe(float(x))
    for p in (50.0, 90.0, 99.0):
        raw = float(np.percentile(xs, p))
        got = h.percentile(p)
        assert abs(got - raw) / raw < Histogram.GROWTH - 1.0, (p, got, raw)
    s = h.summary()
    assert s["count"] == 5000 and s["min"] == xs.min() \
        and s["max"] == xs.max()
    assert h.percentile(0.0) == pytest.approx(xs.min())
    assert h.percentile(100.0) == pytest.approx(xs.max())


# -- registry schema round-trip ----------------------------------------------

def test_registry_schema_roundtrip_and_exporters():
    reg = MetricsRegistry()
    reg.absorb({"preemptions": 3, "wire_export_ms": 1.5,
                "not_in_schema": 9, "fleet_versions": [1]},
               SERVING_STATS_SCHEMA)
    reg.absorb({"ship_queue_depth": 7, "n_killed": 1},
               FLEET_STATS_SCHEMA)
    assert reg.get("preemptions") == 3.0
    assert reg.get("not_in_schema", -1.0) == -1.0   # ignored: undeclared
    assert reg.gauge("ship_queue_depth").value == 7.0
    h = reg.histogram("ttft_seconds", "ttft")
    h.observe(0.25)
    snap = json.loads(reg.to_json())
    assert snap["n_killed"] == 1.0
    assert snap["ttft_seconds"]["count"] == 1
    prom = reg.to_prometheus()
    assert "# TYPE preemptions counter" in prom
    assert "# TYPE ship_queue_depth gauge" in prom
    assert "# TYPE ttft_seconds histogram" in prom
    assert 'ttft_seconds_bucket{le="+Inf"} 1' in prom
    with pytest.raises(TypeError):
        reg.counter("ship_queue_depth")   # kind clash is a bug


def test_fleet_schema_covers_router_stats_and_vice_versa():
    router = FleetRouter(CFG, n_engines=2, seed=0, engine_kwargs=EKW)
    eng_keys = set(router.replicas[0].engine.stats)
    assert eng_keys == set(SERVING_STATS_SCHEMA), \
        eng_keys ^ set(SERVING_STATS_SCHEMA)
    assert set(router.stats) == set(FLEET_STATS_SCHEMA), \
        set(router.stats) ^ set(FLEET_STATS_SCHEMA)


# -- disarmed bit-identity ----------------------------------------------------

def test_disarmed_bit_identity_greedy_and_sampled():
    """Armed tracing must not perturb a single token, greedy or keyed
    sampling — identical engines, identical requests, streams equal."""
    obs.disarm()
    base = ServingEngine(CFG, seed=0, **EKW)
    reqs_a = _mk_reqs(np.random.RandomState(5), n=4, sampled=(1, 3))
    base.run(reqs_a)
    assert not obs.active()

    st = obs.arm(capacity=4096)
    traced = ServingEngine(CFG, params=base.params, seed=0, **EKW)
    reqs_b = [Request(rid=r.rid, prompt=r.prompt.copy(),
                      max_new_tokens=r.max_new_tokens,
                      temperature=r.temperature, top_p=r.top_p,
                      seed=r.seed, arrival=0.0) for r in reqs_a]
    traced.run(reqs_b)
    for a, b in zip(reqs_a, reqs_b):
        assert a.out_tokens == b.out_tokens, a.rid

    doc = obs.export()
    _assert_chrome_valid(doc)
    evs = doc["traceEvents"]
    span_names = {e["name"] for e in evs if e["ph"] == "B"}
    assert {"engine.step", "engine.admit", "engine.dispatch",
            "engine.harvest"} <= span_names
    life = [e for e in evs if e.get("cat") == "req"]
    by_event: dict = {}
    for e in life:
        by_event.setdefault(e["args"]["event"], set()).add(e["id"])
    rids = {r.rid for r in reqs_b}
    for ev in ("arrival", "admit", "first-token", "done"):
        assert by_event.get(ev) == rids, (ev, by_event.get(ev))
    assert st.tracer.n_emitted > 0 and not st.dumps


# -- flight recorder on death paths ------------------------------------------

def test_flight_dump_on_chaos_engine_kill(tmp_path):
    """Engine death must auto-dump a flight record carrying the trace
    ring AND the chaos fault that caused it — the postmortem names its
    own injected killer."""
    st = obs.arm(capacity=8192, dump_dir=str(tmp_path))
    chaos.arm(chaos.FaultPlan(seed=0)
              .add("engine.step", "raise", at=6, engine=0))
    router = FleetRouter(CFG, n_engines=2, seed=0, engine_kwargs=EKW)
    reqs = _mk_reqs(np.random.RandomState(11), n=4)
    for r in reqs:
        router.submit(r, now=1e18)
    steps = 0
    while router.step(now=1e18):
        steps += 1
        assert steps < 2000
    assert router.stats["n_killed"] == 1
    assert len(st.dumps) == 1
    doc = json.load(open(st.dumps[0]))
    assert doc["schema"] == "paddle_tpu.flightrec.v1"
    assert doc["reason"] == "engine-death"
    assert [f["point"] for f in doc["faults"]] == ["engine.step"]
    _assert_chrome_valid(doc["trace"])
    names = {e["name"] for e in doc["trace"]["traceEvents"]}
    assert "chaos.engine.step" in names        # fault annotated in-trace
    assert "fleet.death" in names
    assert os.path.basename(st.dumps[0]).startswith("flightrec-")
    assert glob.glob(str(tmp_path / "flightrec-*-engine-death.json"))


def test_flight_dump_on_rollout_swap_death(tmp_path):
    """A mid-rollout swap death is a different death path through
    _declare_dead — it must dump too, tagged with its own reason."""
    import jax

    st = obs.arm(capacity=8192, dump_dir=str(tmp_path))
    chaos.arm(chaos.FaultPlan(seed=0)
              .add("rollout.swap", "raise", at=0, engine=0))
    router = FleetRouter(CFG, n_engines=2, seed=0, engine_kwargs=EKW)
    params = router.replicas[0].engine.params
    reqs = _mk_reqs(np.random.RandomState(3), n=4)
    for r in reqs:
        router.submit(r, now=1e18)
    for _ in range(200):
        router.step(now=1e18)
        if any(rep.engine.slots and any(
                s is not None and 0 < len(s.out_tokens) < s.max_new_tokens
                for s in rep.engine.slots) for rep in router.replicas):
            break
    v2 = jax.tree_util.tree_map(
        lambda w: (np.asarray(w) * 1.001).astype(np.asarray(w).dtype),
        params)
    router.rollout(params=v2)
    steps = 0
    while router.step(now=1e18):
        steps += 1
        assert steps < 4000
    assert router.stats["n_swap_deaths"] >= 1
    reasons = [json.load(open(p))["reason"] for p in st.dumps]
    assert "rollout-swap-death" in reasons
    doc = json.load(open(st.dumps[reasons.index("rollout-swap-death")]))
    assert [f["point"] for f in doc["faults"]] == ["rollout.swap"]
    names = {e["name"] for e in doc["trace"]["traceEvents"]}
    assert "rollout.swap" in names and "chaos.rollout.swap" in names
