"""The five metrics of the engine's queue and its dispatch split, each read
through its metric file on hand-made ring events; a program that records
none of what they read (the parent of PR 38) gives None; their entries in
BENCHMARK.json; and the tiny sessions cell's traced line carries them."""

import pytest

from benchmark import harness, metrics

from test_benchmark_rehearsal import _run, tiny_bm  # noqa: F401 (fixture)
from test_program_readers import OBS, T0, _ev, _life, _tick, ring_mod

SERVING = ["mistral-7b-d16.chat-saturated", "mistral-7b-d16.docqa-sessions",
           "joyai-flash-ep16.longdoc-sessions",
           "command-a-plus-ep8.longctx-sessions",
           "granite-4.0-h-micro.chat-turns"]
# name -> (unit, the end-to-end metric it moves)
NEW = {"prefill_row_wait_p50_s": ("s", "serve_output_tokens_per_s"),
       "prefill_run_p50_s": ("s", "serve_output_tokens_per_s"),
       "prefill_waiting_slot_share": ("%", "serve_output_tokens_per_s"),
       "engine_schedule_ms_p50": ("ms", "itl_p90_ms"),
       "engine_launch_ms_p50": ("ms", "itl_p90_ms")}


def _split(s, schedule, launch, waiting, prefilling, slots=4):
    """One tick ``s`` seconds after T0: its dispatch (2 ms, from
    s + 2 ms) holds a schedule and a launch of the given seconds, and
    engine.step's end carries the slot ledger."""
    tick = _tick(s, 0.1, 0.05)
    tick[-1]["args"].update(slots=slots, slots_prefilling=prefilling,
                            slots_prefill_waiting=waiting)
    a = s + 0.002 + 1e-5
    b = a + schedule
    return tick + [_ev("engine.dispatch.schedule", "B", a),
                   _ev("engine.dispatch.schedule", "E", b),
                   _ev("engine.dispatch.launch", "B", b),
                   _ev("engine.dispatch.launch", "E", b + launch)]


def _requests():
    """Five requests: waits for a row of 1.0, 0.2, 3.0, 0.5 s in the
    window and runs of 0.1, 0.8, 0.1, 3.5, 1.5 s that end in it."""
    return (_life(1, arrival=10.2, admit=10.5, prefill_start=11.5,
                  first_token=11.6, done=12.0)
            + _life(2, arrival=11.9, admit=12.0, prefill_start=12.2,
                    first_token=13.0)
            + _life(3, arrival=10.9, admit=11.0, prefill_start=14.0,
                    first_token=14.1)
            # preempted before its first token and admitted again: the
            # readers take each event's first
            + _life(4, arrival=12.9, admit=13.0, prefill_start=13.5,
                    preempt=14.0)
            + [_ev("req", "n", t, cat="req", id=4, args={"event": e})
               for t, e in ((15.0, "admit"), (16.0, "prefill-start"),
                            (17.0, "first-token"))]
            # its row came before the window: a run, not a wait, in it
            + _life(5, arrival=1.9, admit=2.0, prefill_start=9.0,
                    first_token=10.5))


def _ticks():
    """Three ticks in the window (schedule 0.3, 0.5, 0.4 ms; launch 1.2,
    1.0, 1.4 ms; 1, 2 and 0 of 4 slots waiting), one before and one
    that ends after it, whose numbers would show."""
    return sum([_split(5.0, 0.01, 0.01, 4, 4),
                _split(11.0, 0.0003, 0.0012, 1, 2),
                _split(12.0, 0.0005, 0.0010, 2, 3),
                _split(13.0, 0.0004, 0.0014, 0, 1),
                _split(19.995, 0.01, 0.01, 4, 4)], [])


WANT = {"prefill_row_wait_p50_s": (0.5 + 1.0) / 2,
        "prefill_run_p50_s": 0.8,
        "prefill_waiting_slot_share": 100.0 * 3 / 12,
        "engine_schedule_ms_p50": 0.4,
        "engine_launch_ms_p50": 1.2}


def _read(monkeypatch, name, events, n_emitted=None, capacity=1 << 16):
    events = sorted(events, key=lambda e: e["ts"])
    n = len(events) if n_emitted is None else n_emitted
    monkeypatch.setattr(ring_mod, "load",
                        lambda: ring_mod.Ring(events, T0, n, capacity))
    return metrics.read(name, dict(OBS))


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_reads_the_hand_worked_number(monkeypatch, name):
    got = _read(monkeypatch, name, _ticks() + _requests())
    assert got == pytest.approx(WANT[name], rel=1e-6, abs=1e-9)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_events_reads_none(monkeypatch, name):
    """The parent's ring: ticks with one opaque dispatch and no slot
    ledger, requests with no ``prefill-start``."""
    parent = sum([_tick(float(s), 0.1, 0.05) for s in (11, 12, 13)], [])
    parent += _life(1, arrival=10.2, admit=10.5, first_token=11.6)
    parent += _life(2, admit=12.0, first_token=13.0)
    assert _read(monkeypatch, name, parent) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_ring_that_dropped_the_window_reads_none(monkeypatch, name):
    evs = sorted(_ticks() + _requests(), key=lambda e: e["ts"])
    late = [e for e in evs if e["ts"] >= 11.5e6]
    assert _read(monkeypatch, name, late, n_emitted=len(evs),
                 capacity=len(late)) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_no_ring_reads_none_and_does_not_raise(name):
    from paddle_tpu import obs

    obs.disarm()
    try:
        assert metrics.read(name, dict(OBS)) is None
    finally:
        obs.arm()


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_is_the_engines_in_the_five_serving_cells(name):
    """Selected by name: nothing here pins where in ``per_layer`` the
    entry stands (W5)."""
    bm = harness.load_benchmark()
    (m,) = [m for m in bm["per_layer"] if m["name"] == name]
    unit, moves = NEW[name]
    assert m == {"name": name, "unit": unit, "better": "lower",
                 "source": "program_span", "layer": "engine",
                 "moves": moves, "workloads": SERVING}
    reports = next(e["workloads"] for e in bm["end_to_end"]
                   if e["name"] == moves)
    assert set(SERVING) <= set(reports)


def test_traced_rehearsal_line_of_the_sessions_cell_carries_them(tiny_bm):  # noqa: F811
    """The toy sessions cell joins the five metrics' lists, as a later
    cell would; its traced line then carries every one (keys only on
    CPU, and a key only where its reader returned a number)."""
    from paddle_tpu import obs

    cell = "tiny-mistral.tiny-sessions"
    for m in tiny_bm["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = m["workloads"] + [cell]
    obs.arm()        # the ring of a process that starts with the cell
    line = _run(cell, True)
    assert line["correct"] is True, line["compared"]
    assert set(NEW) <= set(line["metrics"]), set(NEW) - set(line["metrics"])
    assert all(line["metrics"][n] == {"value": None, "unit": NEW[n][0]}
               for n in NEW)
