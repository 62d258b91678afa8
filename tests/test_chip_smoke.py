"""chip_smoke.py's legs at tiny widths on the CPU (Pallas interpret mode).

The script itself requires a TPU; its legs are functions of a config so
that this test drives the same code — trainer, engine, fusion report,
kernel-vs-XLA comparison — and pins the report's keys.  Also here: the
script refuses to run without a TPU, the HLO kernel census parser, and
the compile-cache helper's placement rule.
"""

import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.core import compile_cache  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig  # noqa: E402


def test_train_leg_tiny():
    # every kernel's support gate holds at these widths: d=128 heads,
    # 1024 rows, lane-aligned hidden/ffn, a vocab the CE tile fits
    leg = chip_smoke.TrainLeg(
        GPTConfig(vocab_size=2048, hidden=256, n_layers=2, n_heads=2,
                  seq_len=256, unroll=True, remat=True),
        batch=4, warmup=1, steps=2)
    r = chip_smoke.train_leg(leg)
    assert r["steps"] == 3 and r["losses"][-1] < r["losses"][0]
    assert r["fusion"]["by_template"] == {
        "layer_epilogue": {"sites": 5, "applied": 5},
        "bias_gelu": {"sites": 2, "applied": 2}}
    assert r["fusion"]["errors"] == []
    assert r["kernels"] == {}           # interpreted: no Mosaic calls
    assert set(r["kernel_vs_xla"]) == {
        "flash_fwd", "flash_bwd", "fused_ce_loss", "fused_ce_dx",
        "fused_ce_dhead", "layer_epilogue_r", "layer_epilogue_y",
        "bias_gelu"}
    assert all(c["rel_err"] <= c["tol"] for c in r["kernel_vs_xla"].values())
    assert {"first_step_s", "memory", "mesh", "batch"} <= set(r)


def test_serve_leg_tiny():
    leg = chip_smoke.ServeLeg(
        LlamaConfig(vocab_size=512, hidden=256, n_layers=2, n_heads=2,
                    n_kv_heads=1, ffn_hidden=512, max_seq_len=512,
                    dtype=jnp.bfloat16),
        max_batch=2, page_size=128, max_seq=512, prefix_len=128,
        tails=(16, 128, 40), new_tokens=(4, 6, 5), ref_request=1)
    r = chip_smoke.serve_leg(leg)
    for run in ("run1", "run2"):
        assert r[run]["requests_completed"] == 3
        assert r[run]["tokens_completed"] == 15
        assert r[run]["pages"]["slot_owned"] == 0
    assert r["run2"]["prefix_cache_hits"] > 0      # the shared prefix
    assert r["streams_identical"] is True
    assert r["fusion"]["by_template"] == {
        "rms_epilogue": {"sites": 3, "applied": 3},
        "rope_attention": {"sites": 1, "applied": 1},
        "swiglu": {"sites": 1, "applied": 1}}
    assert r["kernels"] == {} and r["apply_kernels"] == {}
    assert set(r["kernel_vs_xla"]) == {
        "ragged_paged_attention_kernel_p4", "ragged_paged_attention_kernel_p2",
        "ragged_paged_attention_kernel_p1",
        "ragged_paged_attention_window_kernel_p4",
        "ragged_paged_attention_window_kernel_p2",
        "ragged_paged_attention_window_kernel_p1", "paged_kv_write_k",
        "paged_kv_write_v",
        "rms_epilogue_r", "rms_epilogue_y", "swiglu", "rope_attention"}
    f = r["first_token_vs_llama_apply"]
    assert f["logit_gap"] <= f["tol"]


def test_latent_leg_tiny():
    r = chip_smoke.latent_leg(chip_smoke.LatentLeg(
        n_rows=4, qb=4, n_heads=2, kv_rank=32, rope_dim=8, page_size=16,
        max_blocks=4, n_pages=12, expert_shapes=((64, 128, 128),),
        n_held=4))
    assert set(r["kernel_vs_xla"]) == {
        "mla_paged_attention_kernel_p4", "latent_write_k_rope",
        "latent_write_c_kv",
        "grouped_expert_matmul_h128_f128_kernel_m64_k128_n128",
        "grouped_expert_matmul_h128_f128_kernel_m64_k128_n128_none_held"}
    assert all(c["rel_err"] <= c["tol"] for c in r["kernel_vs_xla"].values())


def test_state_leg_tiny():
    r = chip_smoke.state_leg(chip_smoke.StateLeg(
        n_rows=9, qb=8, n_heads=8, head_dim=16, d_state=128, n_slots=16,
        conv_dim=256))
    assert set(r["kernel_vs_xla"]) == {
        f"ragged_ssm_scan_kernel_h8_{what}" for what in ("y", "pool")} | {
        "ragged_causal_conv_act", "ragged_causal_conv_pool"}
    assert all(c["rel_err"] <= c["tol"] for c in r["kernel_vs_xla"].values())


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="rel_err"):
        chip_smoke.check_close({}, "k", jnp.ones(4), 2 * jnp.ones(4), 2e-2)
    with pytest.raises(chip_smoke.SmokeFailure, match="kernels missing"):
        # (the presence check is live on every backend but the CPU's)
        orig = jax.default_backend
        jax.default_backend = lambda: "tpu"
        try:
            chip_smoke._check_kernels_present({"flash_fwd": 1},
                                              {"flash_fwd", "fused_ce_fwd"})
        finally:
            jax.default_backend = orig


def test_pallas_calls_reads_names_and_shapes_from_hlo():
    hlo = '''
  %flash_fwd.2 = (bf16[2,1024,1024]{2,1,0}, f32[2,8,8,1024]{3,2,1,0}) custom-call(%a, %a, %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/shard_map/jit(_flash_fwd)/flash_fwd/pallas_call" stack_frame_id=47}, backend_config={"custom_call_config":{"body":"TUz"}}
  %x.1 = bf16[4096,8192]{1,0} custom-call(%b, %c), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(fused_bias_gelu))/pallas_call"}
  %y = f32[8]{0} custom-call(%d), custom_call_target="Sharding"
'''
    assert chip_smoke.pallas_calls(hlo) == [
        ("flash_fwd", "bf16[2,1024,1024]"),
        ("fused_bias_gelu", "bf16[4096,8192]")]
    assert chip_smoke.kernels_in(hlo) == {"flash_fwd": 1,
                                          "fused_bias_gelu": 1}


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the helper touches nothing (JAX
    reads the variable itself).  Unset: <checkout>/.jax_cache."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
