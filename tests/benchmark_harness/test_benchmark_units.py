"""Unit tests of the benchmark harness (benchmark/): the contract of
BENCHMARK.json, the trace reduction, the work functions against hand
counts, the peaks table, and the traffic generators.  No chip, no timing."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import harness, trace_reduce
from benchmark.kinds import closed_backlog, closed_sessions, open_schedule
from benchmark.kinds import serve_common as sc

BM = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BM["workloads"]]
METRICS = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]


def _traffic(name):
    return harness.load_json(os.path.join(harness.BENCH, "traffic",
                                          name + ".json"))


# -- BENCHMARK.json ----------------------------------------------------------

def test_benchmark_json_has_exactly_the_contract_keys():
    assert sorted(BM) == ["command", "configs", "end_to_end", "paths",
                          "per_layer", "run_seconds", "workloads"]
    assert 1 <= BM["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= max(
        1, len(BM["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in BM["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c, config, traffic = harness.find_cell(BM, cell)
    assert NAME.match(c["name"]) and NAME.match(c["traffic"])
    assert len(c["why"]) <= 200 and c["chips"] in (1, 4)
    assert os.path.exists(os.path.join(
        harness.BENCH, "kinds", traffic["kind"] + ".py"))
    assert os.path.exists(os.path.join(
        harness.BENCH, "systems", config["system"] + ".py"))
    assert os.path.exists(os.path.join(
        harness.BENCH, "configs", c["config"] + ".reference.py"))
    assert traffic["limits"], "every cell compares numbers under limits"
    e2e = [m["name"] for m in harness.metrics_for(BM, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.metrics_for(BM, cell, "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", METRICS)
def test_metric_is_a_data_file_naming_a_reader(metric):
    assert NAME.match(metric)
    spec = harness.load_json(os.path.join(harness.BENCH, "metrics",
                                          metric + ".json"))
    reader = harness.load_module("readers/" + spec["reader"] + ".py")
    assert callable(reader.read)
    entry = [m for m in BM["end_to_end"] + BM["per_layer"]
             if m["name"] == metric][0]
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    if metric.endswith("_roofline") or "mfu" in metric:
        assert entry["unit"] == "%"
        # nothing to read -> nothing returned, never a 0
        assert reader.read({"counters": {}, "shapes": {}},
                           **spec["args"]) is None


# -- peaks -------------------------------------------------------------------

def test_peaks_table_knows_v5e_and_refuses_the_unknown():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9 imaginary", "_source"):
        with pytest.raises(harness.BenchError):
            harness.peaks_for(kind)


def test_no_tpu_is_an_error_not_a_cpu_fallback():
    with pytest.raises(harness.BenchError, match="needs a TPU"):
        harness.require_chips(1)


# -- work functions against hand counts ----------------------------------------

def test_flash_attention_work_hand_count():
    work = harness.load_module("work/flash_attention.py").work
    flops, nbytes = work({"b": 2, "heads": 3, "s": 8, "d": 4, "layers": 5})
    one = 2 * 2 * 3 * 8 * 8 * 4 / 2                  # causal half of a matmul
    assert flops == 6 * one * 5                      # 2 forward + 4 backward
    assert nbytes == 12 * (2 * 3 * 8 * 4 * 2) * 5    # 4 + 8 bf16 tensors
    more = work({"b": 2, "heads": 3, "s": 8, "d": 4, "layers": 5, "steps": 6})
    assert more == (6 * flops, 6 * nbytes)           # a traced segment


def test_fused_ce_work_hand_count():
    work = harness.load_module("work/fused_ce.py").work
    flops, nbytes = work({"tokens": 16, "hidden": 8, "vocab": 32})
    assert flops == 3 * 2 * 16 * 8 * 32
    assert nbytes == 2 * (3 * (16 * 8 + 8 * 32) + 16 * 8 + 8 * 32) + 4 * 16
    assert work({"tokens": 16, "hidden": 8, "vocab": 32, "steps": 3}) == (
        3 * flops, 3 * nbytes)


def test_ragged_attention_work_reads_rows_not_the_grid():
    work = harness.load_module("work/ragged_paged_attention.py").work
    base = {"heads": 4, "kv_heads": 2, "d": 8, "layers": 3}
    # one decode row at context 10 (position 9): 10 keys
    flops, nbytes = work(dict(base, rows=[[9, 1]]))
    assert flops == 4 * 4 * 8 * 10 * 3
    assert nbytes == 2 * (2 * 2 * 8 * 10 + 2 * 4 * 8 * 1) * 3
    # a prefill slice of 4 tokens from position 0: 1 + 2 + 3 + 4 keys
    flops, _ = work(dict(base, rows=[[0, 4]]))
    assert flops == 4 * 4 * 8 * 10 * 3
    # idle rows are not in the list: no rows, no work
    assert work(dict(base, rows=[])) == (0.0, 0.0)


# -- trace reduction -----------------------------------------------------------

def _events():
    ms = 1e6
    dev = [("fusion_kOutput_matmul_bf16_8_8", 0 * ms, 4 * ms),
           (trace_reduce.PALLAS + "flash_fwd", 4 * ms, 2 * ms),
           ("all-reduce_f32_8", 5 * ms, 3 * ms),      # 1 ms under flash
           ("copy_bf16_4_4", 10 * ms, 1 * ms)]        # after a 2 ms gap
    spans = [("bench.train_step", 7.5 * ms, 3 * ms)]
    return {"devices": [dev], "spans": spans}


def test_reduce_events_busy_idle_ops_and_gaps():
    red = trace_reduce.reduce_events(_events())
    assert red["busy_s"] == pytest.approx(9e-3)
    assert red["window_s"] == pytest.approx(11e-3)
    assert red["ops"]["flash_fwd"] == pytest.approx(2e-3)
    assert red["pallas"] == ["flash_fwd"]
    assert red["gaps"] == {"bench.train_step": pytest.approx(2e-3)}
    assert red["breakdown"]["device_ops"][0][0] == \
        "fusion_kOutput_matmul_bf16_8_8"
    assert len(red["breakdown"]["device_ops"]) <= 10


def test_exposed_seconds_counts_only_uncovered_collective_time():
    t = trace_reduce.exposed_seconds(_events(), "^all-reduce", "^copy-start")
    assert t == pytest.approx(2e-3)                   # 3 ms, 1 under flash


def test_stable_names_survive_renumbering():
    """Event names as a v5e trace has them: the HLO instruction's text."""
    mm = ("%fusion.250 = bf16[32,16,14336]{2,1,0:T(8,128)(2,1)S(1)} fusion("
          "bf16[16,4096,14336]{2,1,0:T(8,128)(2,1)} %get-tuple-element.575), "
          "kind=kOutput, calls=%fused_computation.33.clone.clone.clone")
    assert trace_reduce.stable_name(mm) == \
        trace_reduce.stable_name(mm.replace(".250", ".99")) == \
        "fusion_kOutput_matmul_bf16_32_16_14336"
    kernel = ("%jvp_fused_layer_epilogue_.49 = (bf16[4096,2048]{1,0:T(8,128)"
              "(2,1)}, bf16[4096,2048]{1,0}) custom-call(bf16[4096,2048]{1,0}"
              " %bitcast.2967), custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.stable_name(kernel) == \
        trace_reduce.PALLAS + "fused_layer_epilogue"
    pool = ("%copy.135 = bf16[16,448,8,128,128]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[16,448,8,128,128]{4,3,2,1,0} %get-tuple-element.595)")
    assert trace_reduce.stable_name(pool) == "copy_bf16_16_448_8_128_128"
    loop = ("%while.3 = (s32[]{:T(128)}, bf16[32,16,4096]{2,1,0}) while("
            "(s32[]{:T(128)}, bf16[32,16,4096]{2,1,0}) %tuple.75), "
            "condition=%cond, body=%body")
    assert trace_reduce.stable_name(loop) == ""      # its body's ops count


def test_recorded_chip_trace_reduces():
    path = os.path.join(harness.BENCH, "testdata", "small.xplane.pb")
    want = harness.load_json(os.path.join(harness.BENCH, "testdata",
                                          "small.expected.json"))
    events = trace_reduce.read_xplane(path)
    red = trace_reduce.reduce_events(events)
    assert red["n_devices"] == want["n_devices"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    for name, seconds in want["ops"].items():
        assert red["ops"][name] == pytest.approx(seconds, rel=1e-9)
    assert set(want["pallas"]) <= set(red["pallas"])
    assert any(n.startswith("bench.") for n, _, _ in events["spans"])


# -- generators ------------------------------------------------------------------

MODEL = {"model": {"vocab_size": 1000}}


def _drain_backlog(traffic, seed, n):
    src = closed_backlog.Source(traffic, MODEL, seed)
    out = []
    while len(out) < n:
        for spec in src.poll(0.0):
            out.append(spec)
            src.done(None, 0.0)
    return out[:n]


def test_grid_is_the_quantile_grid_of_the_range():
    g = sc.grid({"lo": 128, "hi": 384, "scale": "log"}, 64)
    assert len(g) == 64 and g == sorted(g) and 128 <= g[0] and g[-1] <= 384
    lin = sc.grid({"lo": 16, "hi": 48, "scale": "linear"}, 4)
    assert lin == [20, 28, 36, 44]
    pages = sc.grid({"lo": 1536, "hi": 2560, "scale": "log",
                     "round_to": 128}, 48)
    assert all(p % 128 == 0 for p in pages)


def test_backlog_same_seed_same_requests_other_seed_same_multiset():
    traffic = _traffic("chat-saturated")
    n, skip = traffic["n_shapes"], traffic["concurrency"]
    a = _drain_backlog(traffic, 7, skip + n)
    b = _drain_backlog(traffic, 7, skip + n)
    c = _drain_backlog(traffic, 2 ** 31 + 11, skip + n)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    # past the staggered first `concurrency`, one whole cycle of the grid
    shape = lambda s: (len(s.prompt), s.max_new)       # noqa: E731
    assert sorted(map(shape, a[skip:])) == sorted(map(shape, c[skip:]))
    assert list(map(shape, a[skip:])) != list(map(shape, c[skip:]))


def test_sessions_fixed_multiset_and_shared_documents():
    traffic = _traffic("docqa-sessions")
    shapes = {}
    for seed in (3, 4):
        src = closed_sessions.Source(traffic, MODEL, seed)
        shapes[seed] = sorted((d, tuple(q)) for d, q in src.shapes)
        first = src.poll(1.0)
        assert len(first) == traffic["concurrency"]
        assert src.poll(1.0) == []                    # each waits for its answer
        rec = type("R", (), {"spec": first[0]})
        src.done(rec, 2.0)
        nxt = src.poll(2.5)
        assert len(nxt) == 1 and nxt[0].due == 2.0    # due when answered
        doc = traffic["document"]["lo"]
        if nxt[0].tag == first[0].tag and len(nxt[0].prompt) > doc:
            same = min(len(first[0].prompt), len(nxt[0].prompt), doc)
            shared = np.array_equal(first[0].prompt[:same],
                                    nxt[0].prompt[:same])
            assert shared or src.sessions_done[first[0].tag] == 1
    assert shapes[3] == shapes[4]
    longest = max(d + max(q for q, _ in qs) + max(a for _, a in qs)
                  for d, qs in shapes[3])
    engine = harness.load_json(os.path.join(
        harness.BENCH, "configs", "mistral-7b-d16.json"))["engine"]
    assert longest <= engine["max_seq"] < 4096
    assert traffic["concurrency"] * -(-longest // engine["page_size"]) \
        <= engine["n_pages"] - 1


def test_open_schedule_fixed_gaps_and_rate():
    traffic = _traffic("tiny-open")
    a = open_schedule.Source(traffic, MODEL, 5)
    b = open_schedule.Source(traffic, MODEL, 6)
    ga, gb = np.diff(a.arrivals, prepend=0), np.diff(b.arrivals, prepend=0)
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert ga.mean() == pytest.approx(1.0 / traffic["rate_per_s"])
    assert sorted(a.shapes) == sorted(b.shapes)
    specs = a.poll(100.0) + a.poll(100.0 + a.arrivals[-1] + 1)
    assert len(specs) == traffic["n_shapes"]
    assert [s.due for s in specs] == sorted(s.due for s in specs)
    assert a.idle_until() is None


def test_window_series_times_from_the_due_moment():
    rec = sc.Record(req=None, spec=sc.Spec(None, 3, due=1.0), submitted=1.2,
                    token_times=[2.0, 2.5, 3.5], finished=3.5)
    s = sc.window_series([rec], t0=1.5, t1=3.0)
    assert s["ttft_s"] == [1.0] and s["itl_s"] == [0.5]
    assert s["output_tokens"] == 2


def test_a_fifth_cell_and_a_metric_are_added_by_files_and_entries_only():
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "tiny-mistral", "source": "none",
                          "file": "benchmark/configs/tiny-mistral.json",
                          "reduced": [], "why": "toy"})
    bm["workloads"].append({"name": "tiny-mistral.tiny-open",
                            "config": "tiny-mistral", "traffic": "tiny-open",
                            "chips": 1, "why": "toy"})
    for m in bm["end_to_end"]:
        if m["name"] == "serve_output_tokens_per_s":
            m["workloads"] = m["workloads"] + ["tiny-mistral.tiny-open"]
    bm["per_layer"].append({
        "name": "generator_lag_p99_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "harness",
        "moves": "serve_output_tokens_per_s",
        "workloads": ["tiny-mistral.tiny-open"]})
    cell, config, traffic = harness.find_cell(bm, "tiny-mistral.tiny-open")
    assert traffic["kind"] == "open_schedule"
    names = [m["name"] for m in harness.metrics_for(
        bm, "tiny-mistral.tiny-open", "per_layer")]
    assert "generator_lag_p99_ms" in names and "serve_step_mfu" in names
    assert "train_step_mfu" not in names
