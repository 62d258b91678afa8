"""The readers of the program's own ring (benchmark/readers/program_*.py)
on hand-made events, and the tiny cells' traced rehearsal lines: every
metric that reads the ring is on the line of every cell it belongs to."""

import os

import pytest

from benchmark import harness, metrics

from test_benchmark_rehearsal import _run, tiny_bm  # noqa: F401 (fixture)

ring_mod = harness.load_module("readers/program_ring.py")
T0 = 1000.0                 # the tracer's t0 on perf_counter
OBS = {"t0": T0 + 10.0, "t1": T0 + 20.0}       # the window


def _ev(name, ph, s, tid=1, **kw):
    return dict({"name": name, "ph": ph, "ts": s * 1e6, "pid": 0,
                 "tid": tid}, **kw)


def _tick(s, step, wait, admit=0.001, dispatch=0.002):
    """One engine tick that starts ``s`` seconds after T0."""
    a, d = s + 0.0005, s + 0.002
    h = d + dispatch + 0.0005
    return [_ev("engine.step", "B", s),
            _ev("engine.admit", "B", a), _ev("engine.admit", "E", a + admit),
            _ev("engine.dispatch", "B", d),
            _ev("engine.dispatch", "E", d + dispatch),
            _ev("engine.harvest", "B", h),
            _ev("engine.harvest.wait", "B", h),
            _ev("engine.harvest.wait", "E", h + wait),
            _ev("engine.harvest", "E", h + wait + 0.001),
            _ev("engine.step", "E", s + step,
                args={"rows_decode": 1, "rows_prefill": 0, "queued": 0})]


def _life(rid, **at):
    ph = {"arrival": "b", "done": "e"}
    return [_ev("req", ph.get(k.replace("_", "-"), "n"), s, cat="req",
                id=rid, args={"event": k.replace("_", "-")})
            for k, s in at.items()]


def _read(monkeypatch, events, reader, n_emitted=None, capacity=1 << 16,
          **args):
    events = sorted(events, key=lambda e: e["ts"])
    n = len(events) if n_emitted is None else n_emitted
    monkeypatch.setattr(ring_mod, "load",
                        lambda: ring_mod.Ring(events, T0, n, capacity))
    return harness.load_module(f"readers/{reader}.py").read(OBS, **args)


def test_span_stat_reads_the_window_and_self_time(monkeypatch):
    # ticks of 100 ms with 90, 80, 70 ms blocked on the device; one before
    # the window and one that ends after it are left out
    evs = sum([_tick(5.0, 0.1, 0.05), _tick(11.0, 0.1, 0.09),
               _tick(12.0, 0.1, 0.08), _tick(13.0, 0.1, 0.07),
               _tick(19.995, 0.1, 0.05)], [])
    # another engine's tick, on its own track: 300 ms, 285 of them blocked
    evs += [dict(e, tid=2) for e in _tick(14.0, 0.3, 0.285)]
    p50 = lambda **a: _read(monkeypatch, evs, "program_span_stat",
                            percentile=50, scale=1e3, **a)
    assert p50(span="engine.harvest.wait") == pytest.approx(85.0)
    assert p50(span="engine.step") == pytest.approx(100.0)
    # self time, span by span: 10, 20, 30 and 15 ms
    assert p50(span="engine.step",
               minus=["engine.harvest.wait"]) == pytest.approx(17.5)
    assert p50(span="engine.admit") == pytest.approx(1.0)
    assert p50(span="no.such.span") is None


def test_flow_stat_pairs_first_events_per_request(monkeypatch):
    evs = (_life(1, arrival=9.0, admit=10.5, first_token=11.5, done=12.0)
           # admitted twice (preempted): the first admit counts
           + _life(2, arrival=10.0, admit=13.0, first_token=15.0)
           + [_ev("req", "n", 14.0, cat="req", id=2,
                  args={"event": "admit"})]
           # its admit lies before the window: not a sample of queue wait
           + _life(3, arrival=2.0, admit=4.0, first_token=19.0)
           # a resubmitted flow has no arrival here
           + _life(4, admit=12.0, first_token=12.5))
    q = _read(monkeypatch, evs, "program_flow_stat", first="arrival",
              then="admit", percentile=50)
    assert q == pytest.approx((1.5 + 3.0) / 2)
    p = _read(monkeypatch, evs, "program_flow_stat", first="admit",
              then="first-token", percentile=50)
    assert p == pytest.approx(1.5)          # of 0.5, 1, 2 and 15


def test_span_sum_adds_what_ended_before_the_window(monkeypatch):
    x = lambda name, s, dur: _ev(name, "X", s, tid=0, dur=dur * 1e6)
    evs = [x("jax.trace", 1.0, 2.0), x("jax.lower", 3.0, 0.5),
           x("jax.compile", 3.5, 4.0), x("jax.trace", 8.0, 0.25),
           x("jax.compile", 12.0, 1.0)]          # a recompile in the window
    tl = _read(monkeypatch, evs, "program_span_sum",
               spans=["jax.trace", "jax.lower"])
    assert tl == pytest.approx(2.75)
    assert _read(monkeypatch, evs, "program_span_sum",
                 spans=["jax.compile"]) == pytest.approx(4.0)
    assert _read(monkeypatch, evs, "program_span_sum",
                 spans=["train.step"]) is None


def test_event_attr_reads_the_last_before_the_window_closes(monkeypatch):
    plan = lambda s, n: _ev("compiler.plan", "i", s, tid=0,
                            args={"n_sites": n + 1, "n_applied": n})
    evs = [plan(2.0, 73), plan(3.0, 4), plan(25.0, 9)]
    get = lambda evs, **a: _read(monkeypatch, evs, "program_event_attr",
                                 event="compiler.plan", **a)
    assert get(evs, attr="n_applied") == 4
    assert get(evs, attr="n_sites") == 5
    assert get(evs[2:], attr="n_applied") is None
    assert get(evs, attr="no_such_attr") is None


def test_a_ring_that_dropped_the_interval_reads_none(monkeypatch):
    """Never a short count: when events of the interval a reader needs
    have left the ring, it returns None."""
    ticks = sum([_tick(float(s), 0.1, 0.05) for s in range(11, 19)], [])
    setup = [_ev("jax.compile", "X", 1.0, tid=0, dur=2e6)]
    full = setup + ticks
    span = dict(reader="program_span_stat", span="engine.step",
                percentile=50)
    ssum = dict(reader="program_span_sum", spans=["jax.compile"])
    assert _read(monkeypatch, full, **span) == pytest.approx(0.1)
    assert _read(monkeypatch, full, **ssum) == pytest.approx(2.0)
    # the ring overflowed but still begins before the window: the window's
    # spans are whole, set-up's are not
    over = dict(n_emitted=len(full) + 5, capacity=len(full))
    assert _read(monkeypatch, full, **over, **span) == pytest.approx(0.1)
    assert _read(monkeypatch, full, **over, **ssum) is None
    # the oldest event left is younger than the window's start
    late = dict(n_emitted=len(full), capacity=len(ticks) - 10)
    assert _read(monkeypatch, ticks[10:], **late, **span) is None
    flow = _life(1, admit=12.0, first_token=12.5)     # its arrival is gone
    assert _read(monkeypatch, setup + flow, n_emitted=99, capacity=3,
                 reader="program_flow_stat", first="arrival", then="admit",
                 percentile=50) is None


def test_no_ring_reads_none_and_does_not_raise(monkeypatch):
    """A program whose ring is off (every commit before PR 27) gives the
    readers nothing: the metric is left out of the line."""
    from paddle_tpu import obs

    obs.disarm()
    try:
        for name in ("engine_host_ms_per_tick", "queue_wait_p50_s",
                     "setup_compile_s", "train_fusion_sites_applied",
                     "train_dispatch_ms_p50"):
            assert metrics.read(name, dict(OBS)) is None
    finally:
        obs.arm()


@pytest.fixture()
def ring_bm(tiny_bm):  # noqa: F811
    """The toy cells also join the set-up metrics' lists, as a later PR's
    cell would: a per-layer metric that moves ``setup_s`` names its cells
    (``harness.metrics_for`` cannot follow ``moves`` to an end-to-end
    metric that has no list of its own)."""
    toys = [w["name"] for w in tiny_bm["workloads"]
            if w["name"].startswith("tiny-")]
    for m in tiny_bm["per_layer"]:
        if m["moves"] == "setup_s":
            m["workloads"] = m["workloads"] + toys
    return tiny_bm


SERVE_KEYS = {"engine_host_ms_per_tick", "engine_device_wait_ms_p50",
              "engine_admit_ms_p50", "engine_dispatch_ms_p50",
              "queue_wait_p50_s", "prefill_p50_s"}
SETUP_KEYS = {"setup_trace_lower_s", "setup_compile_s"}


@pytest.mark.parametrize("cell,chips,want", [
    ("tiny-gpt.tiny-train", 1,
     {"train_dispatch_ms_p50", "train_fusion_sites_applied"}),
    ("tiny-gpt.tiny-train-dp2mp2", 4, {"train_dispatch_ms_p50"}),
    ("tiny-mistral.tiny-backlog", 1, SERVE_KEYS),
    ("tiny-mistral.tiny-sessions", 1, SERVE_KEYS),
    ("tiny-mistral.tiny-open", 1, SERVE_KEYS)])
def test_traced_rehearsal_line_carries_the_ring_metrics(ring_bm, cell,
                                                        chips, want):
    from paddle_tpu import obs

    obs.arm()        # the ring of a process that starts with the cell
    line = _run(cell, True, chips=chips)
    assert line["correct"] is True, line["compared"]
    got = set(line["metrics"])
    assert want | SETUP_KEYS <= got, (want | SETUP_KEYS) - got
    ring_metrics = {m["name"] for m in ring_bm["per_layer"]
                    if m["source"] in ("program_span", "program_counter")
                    and harness.load_json(os.path.join(
                        harness.BENCH, "metrics", m["name"] + ".json"))[
                            "reader"].startswith("program_")}
    assert len(ring_metrics) == 10
    # the fusion pass is off under sequence parallelism (mp > 1): no plan,
    # no number
    assert got & ring_metrics == want | SETUP_KEYS
    assert all(m["value"] is None for m in line["metrics"].values())
