"""The reader of ``serve_token_place_fill_share``
(benchmark/readers/span_attr_ratio.py) on hand-made events, and the
metric's place in BENCHMARK.json."""

import pytest

from benchmark import harness, metrics

from test_program_readers import OBS, _ev, _read, _tick


def _sized(s, places, tokens):
    """One engine tick ``s`` seconds after T0 that ran at a step size of
    ``places`` and carried ``tokens``."""
    tick = _tick(s, 0.1, 0.05)
    tick[-1]["args"].update(places=places, tokens=tokens)
    return tick


READ = dict(reader="span_attr_ratio", span="engine.step", num="tokens",
            den="places")


def test_ratio_is_of_the_sums_over_the_windows_spans(monkeypatch):
    # 30 of 128, 300 of 512, a tick that dispatched nothing; one tick
    # before the window and one that ends after it are left out
    evs = sum([_sized(5.0, 512, 512), _sized(11.0, 128, 30),
               _sized(12.0, 512, 300), _sized(13.0, 0, 0),
               _sized(19.995, 512, 512)], [])
    assert _read(monkeypatch, evs, **READ) == pytest.approx(
        100.0 * 330 / 640)
    assert _read(monkeypatch, evs, **dict(READ, span="no.such")) is None


@pytest.mark.parametrize("events", [
    sum([_tick(11.0, 0.1, 0.05), _tick(12.0, 0.1, 0.05)], []),  # no attrs
    _sized(11.0, 0, 0),                                 # nothing dispatched
    []], ids=["parent", "idle", "empty"])
def test_a_program_that_records_neither_reads_none(monkeypatch, events):
    assert _read(monkeypatch, events, **READ) is None


def test_a_ring_that_dropped_the_window_reads_none(monkeypatch):
    evs = sum([_sized(float(s), 128, 32) for s in range(11, 19)], [])
    assert _read(monkeypatch, evs, **READ) == pytest.approx(25.0)
    late = dict(n_emitted=len(evs), capacity=len(evs) - 10)
    assert _read(monkeypatch, evs[10:], **late, **READ) is None


def test_no_ring_reads_none_and_does_not_raise():
    from paddle_tpu import obs

    obs.disarm()
    try:
        assert metrics.read("serve_token_place_fill_share",
                            dict(OBS)) is None
    finally:
        obs.arm()


def test_the_metric_is_the_engines_in_the_three_serving_cells():
    bm = harness.load_benchmark()
    (m,) = [m for m in bm["per_layer"]
            if m["name"] == "serve_token_place_fill_share"]
    assert m == bm["per_layer"][-1]          # appended, nothing moved
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "program_span", "engine", "serve_output_tokens_per_s")
    serving = next(e["workloads"] for e in bm["end_to_end"]
                   if e["name"] == "serve_output_tokens_per_s")
    assert m["workloads"] == serving
