"""The JoyAI-LLM-Flash configuration in the harness: a CPU rehearsal of its
toy (`tiny-joyai` under `tiny-sessions` and, judged as the real cell is, under
`tiny-sessions-gaps`, through the real engine and
`systems/mla_moe_serve.py`), the control, the hand count of its attention's
work function, and what its configuration file states."""

import json
import os
import time

import numpy as np
import pytest

import jax

from benchmark import harness
from benchmark import run as bench_run

CELL = "tiny-joyai.tiny-sessions"
GAPS = "tiny-joyai.tiny-sessions-gaps"      # the real cell's traffic kind
REAL = "joyai-flash-ep16.longdoc-sessions"
MOE_KEYS = {"moe_held_assignment_share", "moe_load_max_over_mean"}


@pytest.fixture()
def toy_bm(monkeypatch):
    """BENCHMARK.json plus the toy cell, added as entries only; the toy
    joins every list the real cell is on."""
    bm = json.loads(json.dumps(harness.load_benchmark()))
    bm["configs"].append({
        "name": "tiny-joyai", "source": "none", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tiny-joyai.json"})
    for cell in (CELL, GAPS):
        bm["workloads"].append({"name": cell, "config": "tiny-joyai",
                                "traffic": cell.split(".")[1], "chips": 1,
                                "why": "toy"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL, GAPS]
    monkeypatch.setattr(harness, "load_benchmark", lambda: bm)
    return bm


def _run(trace, seconds=1.0, seed=2 ** 31 + 777, cell=CELL, **kw):
    return bench_run.run_cell(cell, seed, seconds, trace,
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
        # what the engine's expert counters and the prefix cache feed
        assert MOE_KEYS | {"prefix_token_hit_share", "slot_occupancy",
                           "preemptions"} <= got, got
        assert "ragged_paged_attention_roofline" not in declared
    else:
        assert {"setup_s", "serve_output_tokens_per_s", "itl_p90_ms"} <= got


def test_real_cell_reports_every_metric_the_issue_names():
    bm = harness.load_benchmark()
    names = {m["name"] for m in harness.metrics_for(bm, REAL, "per_layer")}
    assert {"mla_paged_attention_roofline", "moe_expert_time_share",
            "serve_step_mfu", "prefix_token_hit_share", "ttft_p50_s",
            "setup_compile_s", "setup_trace_lower_s"} | MOE_KEYS <= names
    assert "ragged_paged_attention_roofline" not in names
    e2e = {m["name"] for m in harness.metrics_for(bm, REAL, "end_to_end")}
    assert e2e == {"serve_output_tokens_per_s", "itl_p90_ms", "setup_s"}
    cell = [w for w in bm["workloads"] if w["name"] == REAL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


@pytest.mark.parametrize("cell", [CELL, GAPS])
def test_a_missing_shared_expert_reads_not_correct(toy_bm, monkeypatch, cell):
    """The timed path broken underneath: the program's shared expert
    contributes nothing."""
    ref = harness.reference_for("tiny-joyai")
    make = ref.make_params

    def without_shared(model, key):
        params = make(model, key)
        params["moe"]["ws_down"] = 0 * params["moe"]["ws_down"]
        return params

    monkeypatch.setattr(ref, "make_params", without_shared)
    line = _run(False, cell=cell)
    assert line["correct"] is False, line["compared"]


GAP_NAMES = {"served_gap_p90", "served_gap_mean", "served_mismatch_share",
             "served_logit_gap"}


def test_gaps_kind_judges_the_distribution_of_the_gaps(toy_bm):
    """The real cell's kind on the toy: the same traffic, four numbers
    read from the per-token gaps, the one with a limit judged; the control
    reads through the same function, and serve_common's own is back in
    place afterwards."""
    from benchmark.kinds import serve_common as sc

    plain = sc.served_numbers
    line = _run(False, cell=GAPS, control=True)
    assert sc.served_numbers is plain
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == GAP_NAMES
    limits = harness.load_json(
        "benchmark/traffic/tiny-sessions-gaps.json")["limits"]
    # judged: the p90 (precision lost on every token) and, with a wider
    # limit in the real cell, the largest (a rare token that is plain wrong)
    for name in GAP_NAMES:
        assert line["compared"][name]["limit"] == limits.get(name)
    assert set(limits) == {"served_gap_p90", "served_logit_gap"}
    fp8 = line["control"]["fp8"]
    assert GAP_NAMES <= set(fp8) and fp8["_tokens"] > 0


def test_a_rare_wide_gap_owns_the_largest_and_not_the_p90():
    """Why the real cell is judged by the p90: one token in 250 that a
    flipped top-8 choice moved sets the largest gap and leaves the p90
    where it was; noise on every token moves both."""
    gaps = harness.load_module("kinds/closed_sessions_gaps.py").gap_numbers
    sound = np.zeros(250)
    sound[:20] = np.linspace(0.001, 0.03, 20)        # 8% near ties
    flipped = sound.copy()
    flipped[200] = 0.45
    noisy = np.zeros(250)
    noisy[:90] = np.linspace(0.005, 0.5, 90)         # 36%, everyday noise
    a, b, c = gaps(sound), gaps(flipped), gaps(noisy)
    assert b["served_logit_gap"] == 0.45 > 10 * a["served_logit_gap"]
    assert b["served_gap_p90"] == a["served_gap_p90"] == 0.0
    assert b["served_mismatch_share"] == a["served_mismatch_share"] + 0.4
    assert c["served_gap_p90"] > 0.3 and c["served_mismatch_share"] == 36.0
    assert c["served_gap_mean"] > 10 * b["served_gap_mean"]


def test_fp8_control_fails_the_served_comparison():
    ref = harness.reference_for("tiny-joyai")
    model = harness.load_json("benchmark/configs/tiny-joyai.json")["model"]
    limit = harness.load_json(
        "benchmark/traffic/tiny-sessions.json")["limits"]["served_logit_gap"]
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


def test_mla_attention_work_hand_count():
    work = harness.load_module("work/mla_paged_attention.py").work
    shape = {"heads": 32, "qk_dim": 192, "v_dim": 128, "latent": 576,
             "layers": 40, "rows": [[100, 1], [0, 3]]}
    flops, nbytes = work(shape)
    # decode at position 100 attends 101 keys; a 3-token chunk 1 + 2 + 3
    pairs = 101 + 6
    assert flops == 2 * 32 * 320 * pairs * 40
    # the latent rows up to the last query once, queries in, outputs out
    assert nbytes == 2 * (576 * (101 + 3) + 32 * 320 * (1 + 3)) * 40
    # the benchmark's mfu counts the same attention under the other file
    system = harness.load_module("systems/mla_moe_serve.py").ServeSystem
    other = harness.load_module("work/ragged_paged_attention.py").work
    stub = type("S", (), {"cfg": __import__(
        "paddle_tpu.models.mla_moe", fromlist=["x"]).MlaMoeConfig()})()
    got, _ = other(system.attention_shape(stub, shape["rows"]))
    assert got == flops


def test_configuration_file_states_the_cut():
    c = harness.load_json("benchmark/configs/joyai-flash-ep16.json")
    published = {"hidden_size": 2048, "intermediate_size": 7168,
                 "moe_intermediate_size": 768, "q_lora_rank": 1536,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "num_attention_heads": 32, "num_experts_per_tok": 8,
                 "num_hidden_layers": 40, "first_k_dense_replace": 1,
                 "n_shared_experts": 1, "routed_scaling_factor": 2.5,
                 "rope_theta": 32000000, "rms_norm_eps": 1e-06}
    for k, v in published.items():
        assert c[k] == v and c["model"][k] == v, k
    entry = [e for e in harness.load_benchmark()["configs"]
             if e["name"] == "joyai-flash-ep16"][0]
    assert sorted(entry["reduced"]) == sorted(c["reduced_from_source"]) == [
        "n_routed_experts", "num_nextn_predict_layers", "vocab_size"]
    assert (c["n_routed_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"]) == (16, 16160, 0)
    m = c["model"]
    assert m["n_routed_experts_published"] == 256
    assert m["held_experts"] == [48, 16] and m["vocab_size"] * 8 == 129280
    # the model group is the top level as run, and nothing else differs
    assert all(m[k] == v for k, v in c.items() if k in m)
    assert c["cache"]["bytes_per_token"] == 40 * (512 + 64) * 2
    e = c["engine"]
    assert c["cache"]["pool_bytes"] == (e["n_pages"] * e["page_size"]
                                        * c["cache"]["bytes_per_token"])
    t = harness.load_json("benchmark/traffic/longdoc-sessions.json")
    assert (t["document"]["hi"] + t["question"]["hi"] + t["answer"]["hi"]
            <= e["max_seq"])
    assert t["kind"] == "closed_sessions_gaps"
    assert 0 < t["limits"]["served_gap_p90"] < t["limits"]["served_logit_gap"]
