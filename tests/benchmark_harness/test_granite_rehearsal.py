"""The granite-4.0-h-micro configuration in the harness: a CPU rehearsal of
its toy (`tiny-granite` under `tiny-turns`, the real cell's traffic kind,
through the real engine with its state class and
`systems/granite_hybrid_serve.py`), planted faults, the controls, what
`closed_turns` sends, and what the configuration and traffic files state.
Nothing here looks at a metric's place in BENCHMARK.json's lists: entries
are found by name."""

import json
import time

import numpy as np
import pytest

import jax

from benchmark import harness
from benchmark import run as bench_run

CELL = "tiny-granite.tiny-turns"
REAL = "granite-4.0-h-micro.chat-turns"
NEW = {"ragged_ssm_scan_roofline", "ssm_scan_time_share",
       "prefix_state_lost_share", "state_snapshot_hit_share"}
JOINED = {"prefix_token_hit_share", "ttft_p50_s", "ttft_p90_s.sessions",
          "ttft_mean_s", "serve_token_place_fill_share",
          "setup_trace_lower_s", "setup_compile_s",
          "kv_live_bytes_per_context_token",
          "ragged_paged_attention_roofline"}


@pytest.fixture()
def toy_bm(monkeypatch):
    """BENCHMARK.json plus the toy cell, added as entries only; the toy
    joins every list the real cell is on."""
    bm = json.loads(json.dumps(harness.load_benchmark()))
    bm["configs"].append({
        "name": "tiny-granite", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/tiny-granite.json"})
    bm["workloads"].append({"name": CELL, "config": "tiny-granite",
                            "traffic": "tiny-turns", "chips": 1,
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
        # what the engine's state class, its pages and the prefix cache
        # feed; the device's metrics need a chip and are declared
        assert {"prefix_state_lost_share", "state_snapshot_hit_share",
                "kv_live_bytes_per_context_token", "prefix_token_hit_share",
                "slot_occupancy", "preemptions",
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
    assert not names & {"mla_paged_attention_roofline",
                        "window_attention_time_share",
                        "moe_expert_time_share", "prefix_window_lost_share"}
    e2e = {m["name"] for m in harness.metrics_for(bm, REAL, "end_to_end")}
    assert e2e == {"serve_output_tokens_per_s", "itl_p90_ms", "setup_s"}
    cell = [w for w in bm["workloads"] if w["name"] == REAL][0]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "granite-4.0-h-micro", "chat-turns")
    assert len(cell["why"]) <= 200
    # the four metrics this configuration brings are the cell's alone
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [REAL]
        assert m["moves"] == "serve_output_tokens_per_s"
        spec = harness.load_json(f"benchmark/metrics/{name}.json")
        assert harness.load_module(f"readers/{spec['reader']}.py")
    assert by_name["ragged_ssm_scan_roofline"]["unit"] == "%"
    roof = harness.load_json("benchmark/metrics/ragged_ssm_scan_roofline.json")
    assert roof["args"] == {"pattern": "^ragged_ssm_scan",
                            "work": "ragged_ssm_scan",
                            "shapes": "ragged_paged_attention"}


@pytest.mark.parametrize("fault", ["state_never_reset",
                                   "snapshot_from_the_wrong_boundary"])
def test_a_broken_timed_path_reads_not_correct(toy_bm, monkeypatch, fault):
    """The timed path broken underneath: a new tenant starts from its
    row's live slot (the last tenant's state) instead of the zero slot;
    or a prefix hit starts from the snapshot of some other boundary."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import seam

    if fault == "state_never_reset":
        real = ServingEngine._state_table

        def stale(self, sched):
            tab = real(self, sched)
            tab[:-1, 0] = np.where(tab[:-1, 0] == seam.STATE_ZERO,
                                   tab[:-1, 1], tab[:-1, 0])
            return tab

        monkeypatch.setattr(ServingEngine, "_state_table", stale)
    else:
        # every boundary's snapshot under one key: the first one taken is
        # the one every later hit loads, whatever boundary it asked for
        from paddle_tpu.inference import serving

        monkeypatch.setattr(serving._StateSlots, "key",
                            lambda self, h: b"one-for-all")
    line = _run(False)
    assert line["correct"] is False, line["compared"]
    # the state itself says so, whatever the served logits read: a head
    # in a hundred lies its own norm or more from the reference's
    got = line["compared"]["served_state_gap_p99"]
    assert got["value"] > 10 * got["limit"], line["compared"]


def test_the_state_is_compared_and_a_bf16_pool_reads_not_correct(
        toy_bm, monkeypatch):
    """The kept states ride in the reference's one pass and are judged:
    their distance from the reference's a head, and the precision they
    are kept in.  The program on a bf16 pool serves logits and states
    inside every other limit (bf16 activations move a state more than
    its own rounding does) and reads not `correct` by the values
    themselves; so does the control, the reference's own state rounded
    to bf16 at every token."""
    line = _run(False, control=True)
    assert line["correct"] is True, line["compared"]
    got = line["compared"]
    for name in ("served_state_gap_p50", "served_state_gap_p99",
                 "served_state_bf16_share"):
        assert got[name]["limit"] is not None
        assert 0 <= got[name]["value"] < got[name]["limit"] / 2, got[name]
    assert got["served_state_gap"]["limit"] is None       # reported only
    control = line["control"]["fp8"]
    assert control["served_state_bf16_share"] == 100.0
    assert 0 < control["served_state_gap_p50"] < got[
        "served_state_gap_p50"]["value"]

    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    import jax.numpy as jnp

    real = GraniteHybridConfig.from_hf.__func__
    monkeypatch.setattr(GraniteHybridConfig, "from_hf", classmethod(
        lambda cls, c, **over: real(cls, c, **dict(
            over, state_dtype=jnp.bfloat16))))
    low = _run(False)
    assert low["correct"] is False
    wrong = [k for k, v in low["compared"].items()
             if v["limit"] is not None and not v["value"] <= v["limit"]]
    assert wrong == ["served_state_bf16_share"], low["compared"]
    assert low["compared"]["served_state_bf16_share"]["value"] == 100.0


def test_the_controls_read_below_the_configurations_precision():
    """The reference in the precision below against itself.  With every
    matmul's operands in fp8 the token the lower precision puts first
    lies below the reference's best by more than the toy cell's limit.
    With the recurrence's state alone in bf16 (96 tokens, decays that
    forget in tens of tokens) the logits move by 1e-4 to 1e-3, well over
    fp32's noise and under the toy's bf16 program: the control is alive,
    and what it reads at the served sizes is the chip's to say
    (PERF.md)."""
    ref = harness.reference_for("tiny-granite")
    model = harness.load_json("benchmark/configs/tiny-granite.json")["model"]
    limit = harness.load_json("benchmark/traffic/tiny-turns.json")[
        "limits"]["served_logit_gap"]
    worst, moved = [], []
    for seed in (1, 2, 3):
        key = harness.seed_key(seed)
        tokens = harness.np_rng(seed, 0).integers(
            0, model["vocab_size"], size=(2, 96), dtype="int32")
        pos = [list(range(16, 95))] * 2
        hi = ref.logits_at(model, key, tokens, pos)
        lo = ref.logits_at(model, key, tokens, pos, quant="fp8")
        gaps = np.concatenate([
            h.max(-1) - np.take_along_axis(h, l.argmax(-1)[:, None], -1)[:, 0]
            for h, l in zip(hi, lo)])
        worst.append(float(gaps.max()))
        st = ref.logits_at(model, key, tokens, pos, quant="bf16_state")
        moved.append(max(float(np.abs(a - b).max()) for a, b in zip(hi, st)))
    assert min(worst) > limit, (worst, limit)
    assert 2e-5 < min(moved) and max(moved) < limit, moved


def test_closed_turns_builds_a_turns_prompt_from_the_served_tokens():
    kind = harness.load_module("kinds/closed_turns.py")
    traffic = harness.load_json("benchmark/traffic/tiny-turns.json")
    config = harness.load_json("benchmark/configs/tiny-granite.json")
    src = kind.Source(traffic, config, seed=5)
    assert [s[4] for s in src.slots] == [1, 2, 3]     # slots out of step
    specs = src.poll(0.0)
    assert len(specs) == 3 and src.poll(0.1) == []    # one in flight each

    def answer(spec, served):
        req = type("R", (), {"out_tokens": list(served), "aborted": False})()
        src.done(type("Rec", (), {"spec": spec, "req": req})(), 1.0)

    # slot 1's conversation has two turns: its second prompt is the first
    # prompt, the served tokens (not the reference's, not a stand-in), and
    # the new message
    first = specs[1]
    served = list(range(100, 100 + first.max_new))
    answer(first, served)
    second, = src.poll(1.0)
    assert second.tag == 1
    n0 = len(first.prompt)
    assert second.prompt[:n0].tolist() == first.prompt.tolist()
    assert second.prompt[n0:n0 + len(served)].tolist() == served
    k = src.slots[1][0]
    assert len(second.prompt) - n0 - len(served) == src._shape(k)[0][1]
    # the conversation over, a new one takes the place: no history
    answer(second, [7] * second.max_new)
    third, = src.poll(2.0)
    assert src.conversations_done == [0, 1, 0]
    assert len(third.prompt) == src._shape(src.slots[1][0])[0][0]
    # slot 0's first conversation has one turn
    answer(specs[0], [1] * specs[0].max_new)
    assert src.conversations_done == [1, 1, 0] and not src.warm(0.0)
    # nothing is shared between conversations: ids differ from the start
    assert third.prompt[:8].tolist() != first.prompt[:8].tolist()


def test_system_lists_the_state_planes_for_the_pool_copy_metric():
    ref = harness.reference_for("tiny-granite")
    config = harness.load_json("benchmark/configs/tiny-granite.json")
    system = harness.load_module("systems/granite_hybrid_serve.py").build(
        config, jax.devices()[:1], ref, harness.seed_key(3))
    shapes = system.kv_pool_shapes()
    st = system.engine._state
    assert st.n_slots == 2 + 4 + 10
    for pool in (st.k_pages, st.v_pages):
        for s in (pool.shape, (1,) + pool.shape[1:], pool.shape[1:]):
            assert list(s) in shapes
    # one slot of the recurrence's state; the conv's flat slot would match
    # every op that ends in its channels, and is left out
    assert list(st.v_pages.shape[2:]) in shapes
    assert list(st.k_pages.shape[2:]) not in shapes
    shape = system.attention_shape([[0, 40], [100, 1]])
    assert (shape["heads"], shape["kv_heads"], shape["d"],
            shape["layers"]) == (4, 2, 16, 1)
    assert shape["ssm"]["layers"] == 4 and shape["ssm"]["qb"] == 8
    work = harness.load_module("work/ragged_ssm_scan.py")
    assert system.matmul_flops_per_token() > 4 * work.flops_per_token(
        shape["ssm"])
    assert set(system.counters()) >= {
        "prefix_state_lost_tokens", "state_snapshots_hit",
        "admitted_with_cached_prefix", "kv_live_centibytes",
        "context_tokens_live", "prompt_tokens_admitted"}
    config["engine"]["state_slots"] = 3
    with pytest.raises(harness.BenchError, match="state_slots"):
        harness.load_module("systems/granite_hybrid_serve.py").build(
            config, jax.devices()[:1], ref, harness.seed_key(3))
    system.free()


def test_configuration_file_is_the_published_one_whole():
    c = harness.load_json("benchmark/configs/granite-4.0-h-micro.json")
    catalog = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "vocab_size": 100352}
    for k, v in catalog.items():
        assert c[k] == v and c["model"][k] == v, k
    period = 5 * ["mamba"] + ["attention"] + 4 * ["mamba"]
    assert c["layer_types"] == c["model"]["layer_types"] == 4 * period
    assert c["position_embedding_type"] == "nope"
    assert c["tie_word_embeddings"] is True
    entry = [e for e in harness.load_benchmark()["configs"]
             if e["name"] == "granite-4.0-h-micro"][0]
    assert entry["source"] == c["source"]
    assert entry["reduced"] == [] and c["reduced_from_source"] == {}
    assert c["deployment"]["chips_sharing_every_layer"] == 1
    assert all(c["model"][k] == v for k, v in c.items() if k in c["model"])
    assert set(c["assumed"]) >= {"gated_norm_group", "time_step_limit",
                                 "state_dtype", "init"}
    # the cache, by class: 8 KiB a token and 1 MiB a page over the four
    # attention layers; 76.4 MB a request over the 36 state-space layers
    e, cache = c["engine"], c["cache"]
    assert cache["paged"]["bytes_per_token"] == 4 * 2 * 8 * 64 * 2 == 8192
    assert cache["paged"]["page_bytes"] == 2 ** 20
    assert cache["paged"]["pages"] == e["n_pages"] == 1024
    slot = 36 * (64 * 64 * 128 * 4 + 4352 * 3 * 2)
    assert cache["state"]["slot_bytes"] == slot == 76_437_504
    assert cache["state"]["slots"] == {
        "zero": 1, "dump": 1, "live": e["state_slots"],
        "snapshots": e["state_snapshots"]}
    assert (e["state_slots"], e["state_snapshots"], e["max_batch"]) == (
        32, 46, 32)
    # 80 slots: what the engine makes of the issue's 40 snapshot slots
    # (whole bf16 tiles of 16 sublanes), stated as it is run
    assert (2 + e["state_slots"] + e["state_snapshots"]) % 16 == 0
    assert cache["state"]["pool_bytes"] == 80 * slot
    assert cache["pool_bytes"] == 80 * slot + 1024 * 2 ** 20
    # the grid: 32 rows of requests and 8 more for chunks
    assert e["prefill_budget"] // e["qb"] >= e["max_batch"] + 8
    # the weights' arithmetic: 3.19 B parameters, whole
    w = c["weights"]
    assert w["mamba_mixer_per_layer"] == 25_847_232
    assert w["attention_per_layer"] == 10_485_760
    assert w["feed_forward_per_layer"] == 50_331_648
    assert w["parameters"] == (36 * w["mamba_layer"] + 4 * w["attention_layer"]
                               + w["embedding"] + 2048)
    assert 3.19e9 < w["parameters"] < 3.2e9
    assert 13.3e9 < w["bytes"] + cache["pool_bytes"] < 13.7e9
    t = harness.load_json("benchmark/traffic/chat-turns.json")
    longest = (t["first_message"]["hi"] + (t["turns"] - 1)
               * t["later_message"]["hi"] + t["turns"] * t["answer"]["hi"])
    assert longest == 2560 <= e["max_seq"]
    # the program's own view of the same sizes
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    from paddle_tpu.models.seam import cache_classes

    cfg = GraniteHybridConfig.from_hf(c["model"])
    paged, state = cache_classes(cfg.serving_model(), e["page_size"])
    assert state.slot_bytes() == slot
    assert paged.spec.page_bytes(paged.n_layers) == 2 ** 20


def test_traffic_file_is_the_issues_to_the_letter():
    t = harness.load_json("benchmark/traffic/chat-turns.json")
    assert t["kind"] == "closed_turns" and t["start"] == "fixed"
    assert (t["concurrency"], t["turns"], t["n_shapes"], t["check_sample"],
            t["grid_seed"]) == (32, 4, 48, 4, 0)
    assert t["first_message"] == {"lo": 256, "hi": 1024, "scale": "log"}
    assert t["later_message"] == {"lo": 32, "hi": 256, "scale": "log"}
    assert t["answer"] == {"lo": 64, "hi": 192, "scale": "linear"}
    kind = harness.load_module("kinds/closed_turns.py")
    config = harness.load_json("benchmark/configs/granite-4.0-h-micro.json")
    src = kind.Source(t, config, seed=9)
    assert len(src.slots) == 32
    assert [s[4] for s in src.slots[:8]] == [1, 2, 3, 4, 1, 2, 3, 4]
    for messages, answers in src.shapes:
        assert len(messages) == len(answers) == 4
        assert 256 <= messages[0] <= 1024
        assert all(32 <= m <= 256 for m in messages[1:])
        assert all(64 <= a <= 192 for a in answers)
    lim = t["limits"]
    assert set(lim) == {"served_gap_mean", "served_gap_p90",
                        "served_logit_gap", "served_state_gap_p50",
                        "served_state_gap_p99", "served_state_bf16_share"}
