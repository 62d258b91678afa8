"""The mimo-v2.5-ep16 configuration in the harness: a CPU rehearsal of its
toy (`tiny-mimo` under `tiny-sessions-gaps`, the real cell's traffic kind,
through the real engine with its two cache classes of two page shapes and
`systems/mimo_v2_serve.py`), planted faults, the work function of the
split-width attention, and what the configuration and traffic files state.
Nothing here looks at a metric's place in BENCHMARK.json's lists: entries
are found by name."""

import json
import time

import pytest

import jax

from benchmark import harness
from benchmark import run as bench_run

CELL = "tiny-mimo.tiny-sessions-gaps"
REAL = "mimo-v2.5-ep16.longdoc64k-sessions"
NEW = {"split_width_attention_roofline", "split_width_attention_time_share"}
JOINED = {"prefix_token_hit_share", "ttft_p50_s", "ttft_p90_s.sessions",
          "ttft_mean_s", "serve_token_place_fill_share",
          "kv_live_bytes_per_context_token", "prefix_window_lost_share",
          "moe_held_assignment_share", "moe_load_max_over_mean"}


@pytest.fixture()
def toy_bm(monkeypatch):
    """BENCHMARK.json plus the toy cell, added as entries only; the toy
    joins every list the real cell is on."""
    bm = json.loads(json.dumps(harness.load_benchmark()))
    bm["configs"].append({
        "name": "tiny-mimo", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/tiny-mimo.json"})
    bm["workloads"].append({"name": CELL, "config": "tiny-mimo",
                            "traffic": "tiny-sessions-gaps", "chips": 1,
                            "why": "toy"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    monkeypatch.setattr(harness, "load_benchmark", lambda: bm)
    return bm


def _run(trace, seconds=1.0, seed=2 ** 31 + 913, **kw):
    return bench_run.run_cell(CELL, seed, seconds, trace,
                              devices=jax.devices()[:1],
                              t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(toy_bm, trace):
    line = _run(bool(trace))
    assert line["rehearsal_on_cpu"] is True
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(m["value"] is None for m in line["metrics"].values())
    got = set(line["metrics"])
    declared = {m["name"] for m in harness.metrics_for(
        toy_bm, CELL, "per_layer" if trace else "end_to_end")}
    assert got <= declared
    if trace:
        # what the engine's two classes, the experts and the prefix cache
        # feed; the device's metrics need a chip and are declared
        assert {"kv_live_bytes_per_context_token", "prefix_window_lost_share",
                "prefix_token_hit_share", "moe_held_assignment_share",
                "moe_load_max_over_mean", "slot_occupancy",
                "serve_token_place_fill_share"} <= got, got
        assert NEW | JOINED <= declared
    else:
        assert {"setup_s", "serve_output_tokens_per_s", "itl_p90_ms"} <= got


def test_real_cell_reports_every_metric_the_issue_names():
    bm = harness.load_benchmark()
    by_name = {m["name"]: m for m in bm["per_layer"]}
    names = {m["name"] for m in harness.metrics_for(bm, REAL, "per_layer")}
    assert NEW | JOINED | {
        "serve_step_mfu", "kv_pool_copy_time_share", "preemptions",
        "compiles_in_window.serve", "serve_hbm_program_gb",
        "slot_occupancy", "serve_pallas_time_share"} <= names
    assert not names & {"ragged_paged_attention_roofline",
                        "mla_paged_attention_roofline",
                        "window_attention_time_share",
                        "moe_expert_time_share", "ragged_ssm_scan_roofline",
                        "ssm_scan_time_share", "prefix_state_lost_share",
                        "state_snapshot_hit_share"}
    e2e = {m["name"] for m in harness.metrics_for(bm, REAL, "end_to_end")}
    assert e2e == {"serve_output_tokens_per_s", "itl_p90_ms", "setup_s"}
    cell = [w for w in bm["workloads"] if w["name"] == REAL][0]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "mimo-v2.5-ep16", "longdoc64k-sessions")
    assert len(cell["why"]) <= 200
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [REAL]
        assert (m["moves"], m["layer"], m["unit"], m["source"]) == (
            "serve_output_tokens_per_s", "kernels", "%", "device_trace")
    roof = harness.load_json(
        "benchmark/metrics/split_width_attention_roofline.json")
    assert roof == {"reader": "kernel_roofline", "args": {
        "pattern": "^ragged_paged_attention_split",
        "work": "split_width_paged_attention",
        "shapes": "ragged_paged_attention"}}
    share = harness.load_json(
        "benchmark/metrics/split_width_attention_time_share.json")
    assert share == {"reader": "op_time_share", "args": {
        "pattern": "^ragged_paged_attention_split"}}


def test_the_configuration_states_its_cut():
    """The catalog's widths, the three keys cut with the published counts
    beside them, and an engine geometry whose arguments stand near 13.5
    GB (weights 6.86 GB, pages 6.04 + 0.63 GB)."""
    c = harness.load_json("benchmark/configs/mimo-v2.5-ep16.json")
    m, e = c["model"], c["engine"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (7, 16, 19072)
    assert (m["num_hidden_layers_published"], m["n_routed_experts_published"],
            m["vocab_size_published"], m["held_experts"]) == (
        48, 256, 152576, [64, 16])
    assert (c["head_dim"], c["v_head_dim"], c["hidden_size"],
            c["num_key_value_heads"], c["swa_num_key_value_heads"],
            c["sliding_window"], c["moe_intermediate_size"],
            c["intermediate_size"]) == (192, 128, 4096, 4, 8, 128, 2048,
                                        16384)
    pages = (e["n_pages"] * c["cache"]["page_bytes"]["global"]
             + e["class_pages"]["window"] * c["cache"]["page_bytes"]["window"])
    assert c["cache"]["page_bytes"] == {"global": 655360, "window": 3276800}
    assert 13.0e9 < pages + c["weights"]["bytes"] < 14.0e9
    assert e["max_seq"] >= 65536 + 192 + 256
    assert e["max_seq"] % e["page_size"] == 0
    assert {"text_only", "no_mtp", "no_exchange"} <= set(c["deployment"])


def test_the_work_function_counts_each_class_at_its_widths():
    """2 heads (dk + dv) operations a query-key pair under each class's
    mask, and the keys a row can see read once at kv (dk + dv)."""
    work = harness.load_module("work/split_width_paged_attention.py").work
    cls = {"layers": 1, "heads": 64, "kv_heads": 4, "dk": 192, "dv": 128,
           "window": None, "rows": [[1000, 1], [0, 16]]}
    flops, nbytes = work({"classes": [cls]})
    pairs = 1001 + 16 * 17 / 2
    assert flops == 2 * 64 * 320 * pairs
    assert nbytes == 2 * (4 * 320 * (1001 + 16) + 64 * 320 * 17)
    win = dict(cls, kv_heads=8, window=128, layers=5)
    flops, nbytes = work({"classes": [win]})
    # the decode row sees 128 keys, the chunk at 0..15 sees p + 1
    assert flops == 5 * 2 * 64 * 320 * (128 + 16 * 17 / 2)
    assert nbytes == 5 * 2 * (8 * 320 * (128 + 16) + 64 * 320 * 17)
    # a chunk straddling the window's edge: p = 120..135 sees 121..128
    flops, _ = work({"classes": [dict(win, layers=1, rows=[[120, 16]])]})
    assert flops == 2 * 64 * 320 * (sum(range(121, 128)) + 9 * 128)


@pytest.mark.parametrize("fault", ["sinks_dropped", "window_mask_dropped"])
def test_a_broken_timed_path_reads_not_correct(toy_bm, monkeypatch, fault):
    """The timed path broken underneath: the window layers' attention
    without its sinks, or without its window (it then reads the sink page
    where the window class let pages go).  The window layers' rotation at
    the full layers' theta moves the toy's logits by ~5e-4 and flips no
    token in a window of 8; on the chip it is one of the cell's faults
    (PERF.md)."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    real = rpa.ragged_paged_attention
    drop = "sinks" if fault == "sinks_dropped" else "window"
    monkeypatch.setattr(
        rpa, "ragged_paged_attention",
        lambda *a, **k: real(*a, **{n: v for n, v in k.items() if n != drop}))
    line = _run(False)
    assert line["correct"] is False, line["compared"]
