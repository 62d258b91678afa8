"""The command-a-plus configuration in the harness: a CPU rehearsal of its
toy (`tiny-command-a` under `tiny-sessions-gaps`, the real cell's traffic
kind, through the real engine with both cache classes and
`systems/cohere_moe_serve.py`), planted faults, the control, the identity
behind its attention's work shape, and what its configuration and traffic
files state."""

import json
import time

import numpy as np
import pytest

import jax

from benchmark import harness
from benchmark import run as bench_run

CELL = "tiny-command-a.tiny-sessions-gaps"
REAL = "command-a-plus-ep8.longctx-sessions"
NEW = {"kv_live_bytes_per_context_token", "prefix_window_lost_share"}
MOE_KEYS = {"moe_held_assignment_share", "moe_load_max_over_mean"}


@pytest.fixture()
def toy_bm(monkeypatch):
    """BENCHMARK.json plus the toy cell, added as entries only; the toy
    joins every list the real cell is on."""
    bm = json.loads(json.dumps(harness.load_benchmark()))
    bm["configs"].append({
        "name": "tiny-command-a", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/tiny-command-a.json"})
    bm["workloads"].append({"name": CELL, "config": "tiny-command-a",
                            "traffic": "tiny-sessions-gaps", "chips": 1,
                            "why": "toy"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    monkeypatch.setattr(harness, "load_benchmark", lambda: bm)
    return bm


def _run(trace, seconds=1.0, seed=2 ** 31 + 911, **kw):
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
        # what the engine's classes, its expert counters and the prefix
        # cache feed
        assert NEW | MOE_KEYS | {"prefix_token_hit_share", "slot_occupancy",
                                 "preemptions"} <= got, got
        assert {"ragged_paged_attention_roofline",
                "window_attention_time_share"} <= declared
    else:
        assert {"setup_s", "serve_output_tokens_per_s", "itl_p90_ms"} <= got


def test_real_cell_reports_every_metric_the_issue_names():
    bm = harness.load_benchmark()
    names = {m["name"] for m in harness.metrics_for(bm, REAL, "per_layer")}
    assert NEW | MOE_KEYS | {
        "window_attention_time_share", "ragged_paged_attention_roofline",
        "moe_expert_time_share", "serve_step_mfu", "prefix_token_hit_share",
        "ttft_p50_s", "ttft_p90_s.sessions", "ttft_mean_s",
        "serve_token_place_fill_share", "setup_compile_s",
        "setup_trace_lower_s", "compiles_in_window.serve",
        "preemptions"} <= names
    assert "mla_paged_attention_roofline" not in names
    e2e = {m["name"] for m in harness.metrics_for(bm, REAL, "end_to_end")}
    assert e2e == {"serve_output_tokens_per_s", "itl_p90_ms", "setup_s"}
    cell = [w for w in bm["workloads"] if w["name"] == REAL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    # the three metrics this configuration brings are the cell's alone
    for m in bm["per_layer"][-3:]:
        assert m["workloads"] == [REAL]
    assert {m["name"] for m in bm["per_layer"][-3:]} == NEW | {
        "window_attention_time_share"}


@pytest.mark.parametrize("fault", ["no_global_attention", "no_window_mask"])
def test_a_broken_timed_path_reads_not_correct(toy_bm, monkeypatch, fault):
    """The timed path broken underneath: the program's global layer adds
    no attention, or its window layers see every key."""
    if fault == "no_global_attention":
        ref = harness.reference_for("tiny-command-a")
        make = ref.make_params

        def without(model, key):
            params = make(model, key)
            params["global"]["wo"] = 0 * params["global"]["wo"]
            return params

        monkeypatch.setattr(ref, "make_params", without)
    else:
        from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

        real = rpa.ragged_paged_attention
        monkeypatch.setattr(
            rpa, "ragged_paged_attention",
            lambda *a, window=None, **kw: real(*a, **kw))
    line = _run(False)
    assert line["correct"] is False, line["compared"]


def test_fp8_control_fails_the_served_comparison():
    ref = harness.reference_for("tiny-command-a")
    model = harness.load_json("benchmark/configs/tiny-command-a.json")["model"]
    limit = harness.load_json("benchmark/traffic/tiny-sessions-gaps.json")[
        "limits"]["served_logit_gap"]
    worst = []
    for seed in (1, 2, 3):
        key = harness.seed_key(seed)
        tokens = harness.np_rng(seed, 0).integers(
            0, model["vocab_size"], size=(2, 48), dtype="int32")
        pos = [list(range(16, 47))] * 2
        hi = ref.logits_at(model, key, tokens, pos)
        lo = ref.logits_at(model, key, tokens, pos, quant="fp8")
        gaps = np.concatenate([
            h.max(-1) - np.take_along_axis(h, l.argmax(-1)[:, None], -1)[:, 0]
            for h, l in zip(hi, lo)])
        worst.append(float(gaps.max()))
    assert min(worst) > limit, (worst, limit)


def _stub_system(window=4096, layer_types=None):
    from paddle_tpu.models.cohere_moe import CohereMoeConfig

    system = harness.load_module("systems/cohere_moe_serve.py")
    kw = {} if layer_types is None else {"layer_types": layer_types}
    return system, type("S", (), {"cfg": CohereMoeConfig(
        sliding_window=window, **kw)})()


@pytest.mark.parametrize("window", [4096, 1000, 7])
def test_attention_shape_counts_the_masks_own_keys(window):
    """The identity in `masked_rows`: under the work function's `n pos0 +
    n (n + 1) / 2` the rows handed over count, layer by layer, exactly the
    query-key pairs the masks admit: p + 1 in the global layer, min(p + 1,
    W) in each window layer; decode rows' bytes are exact too."""
    system, stub = _stub_system(window)
    work = harness.load_module("work/ragged_paged_attention.py").work
    rng = np.random.default_rng(window)
    rows = [[int(p), int(n)] for p, n in zip(
        rng.integers(0, 3 * window, 40), rng.integers(1, 513, 40))]
    rows += [[0, 1], [window - 1, 1], [window - 2, 3], [window - 1, 512],
             [0, window], [0, window + 5], [5 * window, 1]]
    shape = system.ServeSystem.attention_shape(stub, rows)
    assert shape["layers"] == 1 and shape["heads"] == 128
    assert shape["kv_heads"] == 8 and shape["d"] == 128
    pairs_global = sum(p + 1 for p0, n in rows for p in range(p0, p0 + n))
    pairs_window = sum(min(p + 1, window) for p0, n in rows
                       for p in range(p0, p0 + n))
    flops, nbytes = work(shape)
    assert flops == 4.0 * 128 * 128 * (pairs_global + 3 * pairs_window)
    # each window layer alone, and a row that straddles W - 1 is split
    masked = system.masked_rows(rows, window)
    one = dict(shape, rows=masked)
    assert work(one)[0] == 4.0 * 128 * 128 * pairs_window
    assert len(masked) == len(rows) + sum(
        p0 < window - 1 <= p0 + n - 1 for p0, n in rows)
    # a decode row reads the keys its mask admits and no more
    for p0 in (0, window - 2, window - 1, window, 9 * window):
        (q0, n), = system.masked_rows([[p0, 1]], window)
        assert n == 1 and q0 + 1 == min(p0 + 1, window)


def test_attention_shape_follows_the_layer_pattern():
    """Two global layers and two window layers: each recorded row twice
    as it is and twice masked."""
    system, stub = _stub_system(100, ("sliding_attention", "full_attention",
                                      "full_attention", "sliding_attention"))
    rows = [[500, 1], [0, 8]]
    shape = system.ServeSystem.attention_shape(stub, rows)
    assert shape["rows"] == 2 * rows + 2 * [[99.0, 1], [0, 8]]


def test_configuration_file_states_the_cut():
    c = harness.load_json("benchmark/configs/command-a-plus-ep8.json")
    catalog = {"hidden_size": 4096, "intermediate_size": 4096,
               "head_dim": 128, "num_attention_heads": 128,
               "num_key_value_heads": 8, "num_experts_per_tok": 8,
               "num_shared_experts": 4, "sliding_window": 4096,
               "rope_theta": 50000, "layer_norm_eps": 1e-05,
               "first_k_dense_replace": 0, "logit_scale": 1,
               "max_position_embeddings": 200000, "rotary_pct": 1,
               "layer_switch": 4, "prefix_dense_intermediate_size": 16384,
               "prefix_dense_sliding_window_pattern": 1}
    for k, v in catalog.items():
        assert c[k] == v and c["model"][k] == v, k
    assert c["layer_types"] == 8 * (3 * ["sliding_attention"]
                                    + ["full_attention"])
    assert c["rope_parameters"] == {"rope_theta": 50000,
                                    "rope_type": "default"}
    entry = [e for e in harness.load_benchmark()["configs"]
             if e["name"] == "command-a-plus-ep8"][0]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced_from_source"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 32768)
    m = c["model"]
    assert m["num_experts_published"] == 128 and m["held_experts"] == [32, 16]
    assert m["vocab_size"] * 8 == m["vocab_size_published"] == 262144
    assert m["num_hidden_layers_published"] == 32
    assert c["deployment"]["chips_sharing_every_layer"] == 8
    # the model group is the top level as run, and nothing else differs
    assert all(m[k] == v for k, v in c.items() if k in m)
    # the cache, by class: a page of 128 tokens is 512 KiB a layer
    e, cache = c["engine"], c["cache"]
    assert cache["page_bytes_per_layer"] == 128 * 8 * 128 * 2 * 2 == 2 ** 19
    assert cache["classes"]["global"]["pages"] == e["n_pages"] == 4096
    assert cache["classes"]["window"]["pages"] \
        == e["class_pages"]["window"] == 1024
    assert cache["pool_bytes"] == (4096 + 3 * 1024) * 2 ** 19
    # the weights' arithmetic, and the program's arguments at 12 GB or more
    w = c["weights"]
    assert w["layer_held_here"] == 1149763584
    assert w["bytes"] == 2 * (4 * w["layer_held_here"] + 32768 * 4096)
    assert w["bytes"] + cache["pool_bytes"] >= 12e9
    t = harness.load_json("benchmark/traffic/longctx-sessions.json")
    assert (t["document"]["hi"] + t["question"]["hi"] + t["answer"]["hi"]
            <= e["max_seq"])
    # the control's limit is the mean's (PERF.md §4); the other two guard
    lim = t["limits"]
    assert set(lim) == {"served_gap_mean", "served_gap_p90",
                        "served_logit_gap"}
    assert 0 < lim["served_gap_mean"] < lim["served_gap_p90"] \
        < lim["served_logit_gap"]


def test_traffic_file_is_the_issues_to_the_letter():
    t = harness.load_json("benchmark/traffic/longctx-sessions.json")
    assert t["kind"] == "closed_sessions_gaps" and t["start"] == "fixed"
    assert (t["concurrency"], t["questions"], t["n_shapes"],
            t["check_sample"]) == (12, 4, 36, 4)
    assert t["document"] == {"lo": 8192, "hi": 24576, "scale": "log",
                             "round_to": 128}
    assert (t["question"]["lo"], t["question"]["hi"]) == (64, 192)
    assert (t["answer"]["lo"], t["answer"]["hi"]) == (32, 96)
    docs = harness.load_module("kinds/serve_common.py").grid(
        t["document"], t["n_shapes"])
    assert all(d % 128 == 0 and 8192 <= d <= 24576 for d in docs)
    # every context is two to six windows long
    assert min(docs) >= 2 * 4096 and max(docs) + 192 + 96 <= 6.1 * 4096
