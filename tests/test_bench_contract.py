"""bench.py driver-contract guards (VERDICT r2 weak 9): the secondary
benches' fault isolation must not silently swallow regressions — a
passing secondary contributes its keys, a failing one contributes a
NAMED error marker, and one always-parseable JSON line emits."""

import importlib
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench as b

    importlib.reload(b)
    # stub EVERY secondary (they target the real chip: 1B-class decode,
    # serving engine, 100-step loss curve — hours on the 1-core CPU CI
    # box); individual tests re-patch the ones they exercise
    for name in ("_bench_chip_probe", "_bench_decode", "_bench_serving",
                 "_bench_multitenant", "_bench_fleet", "_bench_disagg",
                 "_bench_loss_curve", "_bench_13b", "_bench_long_ctx",
                 "_bench_multichip", "_bench_fusion", "_bench_phases",
                 "_bench_obs"):
        monkeypatch.setattr(b, name, lambda: {})
    return b


def test_secondary_success_keys_propagate(bench, monkeypatch):
    monkeypatch.setattr(bench, "_bench_decode",
                        lambda: {"llama1b_decode_tokens_per_sec": 450.0})
    monkeypatch.setattr(bench, "_bench_13b",
                        lambda: {"gpt3_1p3b_train_mfu": 0.57})
    extra = bench._run_secondary_benches()
    assert extra == {"llama1b_decode_tokens_per_sec": 450.0,
                     "gpt3_1p3b_train_mfu": 0.57}


def test_secondary_failure_is_visible_not_silent(bench, monkeypatch):
    def boom():
        raise RuntimeError("decode exploded")

    monkeypatch.setattr(bench, "_bench_decode", boom)
    monkeypatch.setattr(bench, "_bench_13b",
                        lambda: {"gpt3_1p3b_train_mfu": 0.57})
    extra = bench._run_secondary_benches()
    # the 1.3B result survives AND the failure is recorded by name
    assert "decode exploded" in extra["llama_decode_error"]
    assert extra["gpt3_1p3b_train_mfu"] == 0.57
    # a failing FIRST bench must not stop the second from running
    order = []
    monkeypatch.setattr(bench, "_bench_decode",
                        lambda: order.append("d") or (_ for _ in ()).throw(
                            RuntimeError("x")))
    monkeypatch.setattr(bench, "_bench_13b",
                        lambda: order.append("b") or {})
    bench._run_secondary_benches()
    assert order == ["d", "b"]


def test_serving_key_contract(bench):
    """_serving_keys is the pure loadgen-metrics -> bench-keys mapping;
    the r07 serving metric surface (TTFT/TPOT percentiles, goodput,
    occupancy decomposition incl. the spec bucket, spec accept rate)
    must be present and correctly sourced."""
    m = {"throughput_tok_s": 400.0, "goodput_tok_s": 380.0,
         "e2e_p50_s": 1.0, "e2e_p99_s": 3.0,
         "ttft_p50_s": 0.2, "ttft_p99_s": 0.9,
         "tpot_p50_s": 0.02, "tpot_p99_s": 0.05,
         "slot_occupancy": 0.85,
         "occ_waste_queue_empty": 0.02,
         "occ_waste_admission_blocked": 0.05,
         "occ_waste_prefill": 0.06, "occ_waste_overrun": 0.01,
         "occ_waste_spec_rejected": 0.01,
         "prefix_cache_hit_rate": 0.7, "spec_accept_rate": 0.0}
    m = dict(m, kv_bytes_per_token=3072.0, kv_quant_enabled=False)
    spec_m = dict(m, spec_accept_rate=0.62, throughput_tok_s=450.0)
    kvq_m = dict(m, throughput_tok_s=430.0, kv_bytes_per_token=800.0,
                 quality_delta=0.01)
    out = bench._serving_keys(m, spec_m, kvq_m)
    for k in ("serving_ttft_p50", "serving_ttft_p99",
              "serving_tpot_p50", "serving_tpot_p99",
              "serving_goodput", "serving_occupancy",
              "serving_spec_accept_rate", "serving_throughput_tok_s",
              "serving_latency_p50_s", "serving_latency_p99_s",
              "serving_occ_waste_queue_empty",
              "serving_occ_waste_admission_blocked",
              "serving_occ_waste_prefill", "serving_occ_waste_overrun",
              "serving_occ_waste_spec_rejected",
              "serving_prefix_cache_hit_rate",
              "serving_kv_bytes_per_token", "serving_kv_quant_enabled"):
        assert k in out, k
    assert out["serving_goodput"] == 380.0
    assert out["serving_ttft_p99"] == 0.9
    assert out["serving_tpot_p50"] == 0.02
    assert out["serving_occupancy"] == 0.85
    assert out["serving_spec_accept_rate"] == 0.62   # from the spec arm
    assert out["serving_spec_throughput_tok_s"] == 450.0
    # int8-KV plane keys: main-run bytes/token + enabled marker, and the
    # quant arm's throughput / bytes / quality delta
    assert out["serving_kv_bytes_per_token"] == 3072.0
    assert out["serving_kv_quant_enabled"] == 0.0
    assert out["serving_kv_quant_tok_s"] == 430.0
    assert out["serving_kv_quant_bytes_per_token"] == 800.0
    assert out["serving_kv_quant_quality_delta"] == 0.01
    # without a speculative arm the rate comes from the main run (0.0);
    # without a kv-quant arm its keys stay absent
    solo = bench._serving_keys(m)
    assert solo["serving_spec_accept_rate"] == 0.0
    assert "serving_spec_throughput_tok_s" not in solo
    assert "serving_kv_quant_tok_s" not in solo
    assert "serving_kv_quant_quality_delta" not in solo
    # a kv_quant main run marks itself enabled
    assert bench._serving_keys(dict(m, kv_quant_enabled=True))[
        "serving_kv_quant_enabled"] == 1.0


def test_multitenant_key_contract(bench):
    """_multitenant_keys is the pure loadgen-metrics -> bench-keys
    mapping for the multi-tenant family (ISSUE 10): LoRA-arm throughput
    and adapter count, priority-arm preemption rate and re-prefill
    occupancy cost, constrained-arm throughput."""
    lora_m = {"throughput_tok_s": 350.0}
    prio_m = {"preemption_rate": 0.25, "occ_waste_preempted": 0.04}
    con_m = {"throughput_tok_s": 390.0}
    out = bench._multitenant_keys(lora_m, prio_m, con_m, 4)
    for k in ("serving_lora_tok_s", "serving_lora_n_adapters",
              "serving_preemption_rate", "serving_occ_waste_preempted",
              "serving_constrained_tok_s"):
        assert k in out, k
    assert out["serving_lora_tok_s"] == 350.0
    assert out["serving_lora_n_adapters"] == 4.0
    assert out["serving_preemption_rate"] == 0.25
    assert out["serving_occ_waste_preempted"] == 0.04
    assert out["serving_constrained_tok_s"] == 390.0
    # error marker name is wired in the secondary list
    import inspect

    src = inspect.getsource(bench._run_secondary_benches)
    assert "_bench_multitenant" in src and "multitenant_error" in src


def test_fleet_key_contract(bench):
    """_fleet_keys is the pure FleetDriver-metrics -> bench-keys mapping
    for the fleet family (ISSUE 11): replica count, fleet goodput and
    TTFT tail measured WITH a mid-run replica loss, pages migrated off
    the dead replica, worst stream-recovery latency, and the deadline
    miss rate under shrunken capacity."""
    m = {"fleet_n_engines": 2, "goodput_tok_s": 310.0,
         "ttft_p99_s": 1.4, "migrated_pages": 9,
         "recovery_ms_max": 220.5, "deadline_miss_rate": 0.021}
    out = bench._fleet_keys(m)
    for k in ("fleet_n_engines", "fleet_goodput", "fleet_ttft_p99",
              "fleet_migrated_pages", "fleet_recovery_ms",
              "fleet_deadline_miss_rate"):
        assert k in out, k
    assert out["fleet_n_engines"] == 2.0
    assert out["fleet_goodput"] == 310.0
    assert out["fleet_ttft_p99"] == 1.4
    assert out["fleet_migrated_pages"] == 9.0
    assert out["fleet_recovery_ms"] == 220.5
    assert out["fleet_deadline_miss_rate"] == 0.021
    # base arm only: no zero-downtime-operations keys
    assert "fleet_rollout_goodput" not in out
    # ops arm (ISSUE 18): goodput measured THROUGH a live weight
    # rollout, the longest drain->swap->canary stall, the autoscaler's
    # live engine-count envelope, and the total shed fraction
    ops = {"goodput_tok_s": 295.0, "rollout_stall_ms": 84.2,
           "autoscale_n_engines_min": 1, "autoscale_n_engines_max": 3,
           "n_shed": 1, "n_slo_shed": 2, "n_submitted": 48}
    out = bench._fleet_keys(m, ops=ops)
    assert out["fleet_rollout_goodput"] == 295.0
    assert out["fleet_rollout_stall_ms"] == 84.2
    assert out["fleet_autoscale_n_engines_min"] == 1.0
    assert out["fleet_autoscale_n_engines_max"] == 3.0
    assert out["fleet_shed_rate"] == round(3 / 48, 3)
    # error marker name is wired in the secondary list
    import inspect

    src = inspect.getsource(bench._run_secondary_benches)
    assert "_bench_fleet" in src and "fleet_error" in src


def test_disagg_key_contract(bench):
    """_disagg_keys is the pure FleetDriver-metrics -> bench-keys
    mapping for the disaggregated-pool family (ISSUE 12): disagg-arm
    TTFT and shipped pages, colocated-arm TTFT with deltas (positive =
    the pool split won), and the failover arm's degraded-mode cost +
    kill -> re-split recovery time."""
    m = {"ttft_p50_s": 0.20, "ttft_p99_s": 0.80,
         "goodput_tok_s": 300.0, "disagg_shipped_pages": 40}
    coloc = {"ttft_p50_s": 0.35, "ttft_p99_s": 1.30}
    fail = {"degraded_steps": 120, "degraded_frac": 0.4,
            "disagg_recovery_ms": 850.5, "ttft_p99_s": 1.9}
    out = bench._disagg_keys(m, coloc, fail)
    for k in ("disagg_ttft_p50", "disagg_ttft_p99", "disagg_goodput",
              "disagg_shipped_pages", "colocated_ttft_p50",
              "colocated_ttft_p99", "disagg_ttft_delta_p50",
              "disagg_ttft_delta_p99", "disagg_degraded_steps",
              "disagg_degraded_frac", "disagg_recovery_ms",
              "disagg_failover_ttft_p99"):
        assert k in out, k
    assert out["disagg_ttft_p50"] == 0.20
    assert out["disagg_ttft_p99"] == 0.80
    assert out["disagg_shipped_pages"] == 40.0
    assert out["colocated_ttft_p99"] == 1.30
    assert out["disagg_ttft_delta_p50"] == pytest.approx(0.15)
    assert out["disagg_ttft_delta_p99"] == pytest.approx(0.50)
    assert out["disagg_degraded_steps"] == 120.0
    assert out["disagg_recovery_ms"] == 850.5
    assert out["disagg_failover_ttft_p99"] == 1.9
    # the wire extension keys only appear when the overlap/int8 arms
    # are passed (the 3-arg call above stays exactly the base set)
    assert "overlap_wire_ms_per_handoff" not in out
    # error marker name is wired in the secondary list
    import inspect

    src = inspect.getsource(bench._run_secondary_benches)
    assert "_bench_disagg" in src and "disagg_error" in src


def test_disagg_wire_key_contract(bench):
    """The ISSUE 14 wire extension of _disagg_keys: per-handoff wire
    cost for the synchronous vs overlapped arms (speedup > 1 = the
    staged export + deferred commit won) and bytes per handoff for the
    fp vs native-int8 arms (compression ~4x on an fp32 cache)."""
    m = {"ttft_p50_s": 0.20, "ttft_p99_s": 0.80,
         "goodput_tok_s": 300.0, "disagg_shipped_pages": 40,
         "shipped_bytes": 400000, "n_handoffs": 10,
         "ship_queue_depth": 3, "wire_export_ms": 50.0,
         "wire_adopt_ms": 30.0}
    coloc = {"ttft_p50_s": 0.35, "ttft_p99_s": 1.30}
    fail = {"degraded_steps": 120, "degraded_frac": 0.4,
            "disagg_recovery_ms": 850.5, "ttft_p99_s": 1.9}
    overlap = {"ttft_p99_s": 0.75, "goodput_tok_s": 310.0,
               "shipped_bytes": 400000, "n_handoffs": 10,
               "wire_export_ms": 10.0, "wire_adopt_ms": 10.0}
    int8 = {"shipped_bytes": 101000, "n_handoffs": 10}
    out = bench._disagg_keys(m, coloc, fail, overlap=overlap, int8=int8)
    for k in ("disagg_shipped_bytes", "disagg_n_handoffs",
              "disagg_ship_queue_depth", "disagg_wire_export_ms",
              "disagg_wire_adopt_ms", "disagg_wire_ms_per_handoff",
              "overlap_wire_ms_per_handoff", "overlap_wire_speedup",
              "overlap_ttft_p99", "overlap_goodput",
              "fp_bytes_per_handoff", "int8_bytes_per_handoff",
              "int8_wire_compression"):
        assert k in out, k
    # the base set rides along unchanged
    assert out["disagg_ttft_p99"] == 0.80
    assert out["disagg_shipped_bytes"] == 400000.0
    assert out["disagg_ship_queue_depth"] == 3.0
    assert out["disagg_wire_ms_per_handoff"] == pytest.approx(8.0)
    assert out["overlap_wire_ms_per_handoff"] == pytest.approx(2.0)
    assert out["overlap_wire_speedup"] == pytest.approx(4.0)
    assert out["fp_bytes_per_handoff"] == pytest.approx(40000.0)
    assert out["int8_bytes_per_handoff"] == pytest.approx(10100.0)
    assert out["int8_wire_compression"] == pytest.approx(3.96, abs=0.01)


def test_multichip_key_contract(bench):
    """_multichip_keys is the pure raw-measurements -> bench-keys mapping
    for the multichip family (ISSUE 9): step time, tok/s/chip, scaling
    efficiency vs the 1-device serial run, comm fraction, and the
    quantized-collective throughput + measured loss delta."""
    m = {"mesh": "dp2xpp2xmp2", "n_devices": 8,
         "step_ms": 100.0, "tok_s_per_chip": 1280.0,
         "serial_step_ms": 640.0, "comm_ms": 25.0,
         "quant_tok_s": 9000.0, "quant_off_tok_s": 8000.0,
         "quant_off_loss": 7.5, "quant_on_loss": 7.50012}
    out = bench._multichip_keys(m)
    for k in ("multichip_mesh", "multichip_n_devices",
              "multichip_step_ms", "multichip_tok_s_per_chip",
              "multichip_scaling_eff", "multichip_comm_frac",
              "dist_allreduce_quant_tok_s",
              "dist_allreduce_quant_loss_delta"):
        assert k in out, k
    assert out["multichip_step_ms"] == 100.0
    # 640 serial vs 8 chips * 100 ms -> 0.8 linear-scaling efficiency
    assert out["multichip_scaling_eff"] == pytest.approx(0.8)
    assert out["multichip_comm_frac"] == pytest.approx(0.25)
    assert out["dist_allreduce_quant_tok_s"] == 9000.0
    assert out["dist_allreduce_quant_loss_delta"] == pytest.approx(
        0.00012, abs=1e-9)
    # comm_frac is a ratio: a microbench slower than the step clamps to 1
    assert bench._multichip_keys(dict(m, comm_ms=500.0))[
        "multichip_comm_frac"] == 1.0


def test_fusion_key_contract(bench):
    """_fusion_keys is the pure fusion-report -> bench-keys mapping for
    the auto-fused step (ISSUE 15): discovered/applied site counts, the
    fused step timing, and whether this session replayed a committed
    per-program autotune record."""
    rep = {"n_sites": 5, "n_applied": 5, "program_cache_hit": True}
    out = bench._fusion_keys(rep, step_ms=125.0, n_tokens=2048)
    assert out == {"fusion_n_sites": 5,
                   "fusion_n_applied": 5,
                   "fusion_step_ms": 125.0,
                   "fusion_tok_s": pytest.approx(16384.0),
                   "autotune_program_cache_hit": True}
    # a matcher regression is visible as a count, not throughput noise
    cold = bench._fusion_keys({"n_sites": 0}, step_ms=0.0, n_tokens=2048)
    assert cold["fusion_n_sites"] == 0
    assert cold["fusion_tok_s"] == 0.0
    assert cold["autotune_program_cache_hit"] is False


def test_obs_key_contract(bench):
    """_obs_keys is the pure obs-measurement -> bench-keys mapping
    (ISSUE 19): armed-vs-disarmed wall overhead fraction and trace-event
    volume per engine step, both zero-guarded."""
    out = bench._obs_keys(n_emitted=1200, steps=60, plain_s=2.0,
                          armed_s=2.1)
    assert out == {"obs_trace_overhead_frac": pytest.approx(0.05),
                   "obs_events_per_step": pytest.approx(20.0)}
    cold = bench._obs_keys(n_emitted=0, steps=0, plain_s=0.0,
                           armed_s=0.0)
    assert cold == {"obs_trace_overhead_frac": 0.0,
                    "obs_events_per_step": 0.0}
    # the measurement arm really drives the serving engine through the
    # obs plane: disarmed control first, armed run second (the fixture
    # stubs the attribute, so read the shipped source instead)
    src = open(bench.__file__).read()
    body = src.split("def _bench_obs():")[1]
    assert "obs.arm" in body and "obs.disarm" in body
    assert "_obs_keys(" in body


def test_cpu_main_emits_one_json_line(bench):
    """The CI-path main() honors the one-JSON-line driver contract."""
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.main()
    lines = [ln for ln in buf.getvalue().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= out.keys()
