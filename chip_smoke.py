#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the system's two main paths once, through the entry
points a user calls, at the full width of a supported model with random
seeded weights:

- **train**: GPT-3 1.3B (24L, H=2048, 16 heads of d=128, vocab 50,304,
  b4·s1024) through ``make_sharded_train_step`` exactly as
  ``bench._bench_13b`` configures it, mesh (1,1,1) on ``jax.devices()[0]``;
- **serve**: ``ServingEngine`` at llama3-8b widths (H=4096, 32/8 heads of
  d=128, ffn 14,336, vocab 128,256), depth cut to 8 of 32 layers so the
  weights (~5.6 GB bf16) plus pages fit one v5e; a handful of seeded
  requests through ``OpenLoopDriver``, twice;
- **mesh** (only with >= 4 devices): ``__graft_entry__._dryrun_impl(4)`` on
  real devices and the same 1.3B trainer on ``build_mesh((2, 1, 2))``.

It prints facts, not metrics — device, versions, compile seconds, losses,
completions, fusion sites, autotune configs, the Pallas kernels present in
each compiled program, peak memory — and compares every kernel a leg uses
with its own XLA reference once, on the chip, at the leg's shapes.  Any
failed check or exception anywhere is a non-zero exit.

Run as a script it REQUIRES a TPU and has no other mode.  Its legs are
functions of a config, so tests/test_chip_smoke.py drives the same code at
tiny widths on the CPU (Pallas interpret mode).  The last line of stdout
is one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import math
import os
import re
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import compiler
from paddle_tpu.core import native
from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu.distributed.process_mesh import build_mesh
from paddle_tpu.inference.loadgen.driver import OpenLoopDriver
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models import gpt as G
from paddle_tpu.models import llama as L
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import fused_bias_act as BA
from paddle_tpu.ops.pallas import fused_ce as CE
from paddle_tpu.ops.pallas import fused_norm_epilogue as NE
from paddle_tpu.ops.pallas import fused_rope_attention as RA
from paddle_tpu.ops.pallas import grouped_expert_matmul as GEM
from paddle_tpu.ops.pallas import mla_paged_attention as MPA
from paddle_tpu.ops.pallas import paged_kv_write as KVW
from paddle_tpu.ops.pallas import ragged_causal_conv as RCC
from paddle_tpu.ops.pallas import ragged_paged_attention as RPA
from paddle_tpu.ops.pallas import ragged_ssm_scan as SSM
from paddle_tpu.parallel import make_sharded_train_step

# what the chip tool brings back from a run (gitignored)
_OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chiprun_out")

# Written tolerances of the on-chip kernel-vs-XLA comparison, as
# max|kernel - ref| / max|ref| over the whole result.  The CPU tests pin
# the interpreted kernels bitwise; that says nothing about what Mosaic
# emits, and the compiled kernels are not held to bits: the two sides
# round intermediates at different points and accumulate in a different
# order.
#   bf16 results: one bf16 ulp is 2^-8 = 0.4% of its value, so 2e-2 is a
#     few ulps at the top of the range — a wrong tile, mask or offset is
#     off by ~100%;
#   fp32 results (the CE loss): both sides sum exact bf16 products in
#     fp32, only the order differs.
TOL_BF16 = 2e-2
TOL_F32 = 1e-3


class SmokeFailure(RuntimeError):
    """A check the smoke makes did not hold."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# leg configurations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainLeg:
    model: G.GPTConfig
    batch: int = 4
    warmup: int = 3
    steps: int = 3
    mesh: tuple = (1, 1, 1)             # (dp, pp, mp)


@dataclasses.dataclass(frozen=True)
class ServeLeg:
    model: L.LlamaConfig
    max_batch: int = 8
    page_size: int = 128
    max_seq: int = 2048
    prefix_len: int = 512               # shared by every prompt
    tails: tuple = (32, 256, 512, 128, 64, 384)
    new_tokens: tuple = (16, 64, 32, 48, 24, 40)
    # request whose first token is checked against llama_apply; its
    # prompt length must be a multiple of 256 (the fused kernels' rows)
    ref_request: int = 1
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class LatentLeg:
    """The latent (MLA) serving kernels at one grid geometry: the
    benchmark's joyai-flash-ep16 cell by default."""
    n_rows: int = 32
    qb: int = 16
    n_heads: int = 32
    kv_rank: int = 512
    rope_dim: int = 64
    page_size: int = 128
    max_blocks: int = 52
    n_pages: int = 256
    # the held experts' products on a chunk tick, (rows, hidden, expert
    # width) of each routed cell: command-a-plus-ep8, joyai-flash-ep16
    expert_shapes: tuple = ((4096, 4096, 4096), (4096, 2048, 768))
    n_held: int = 16


@dataclasses.dataclass(frozen=True)
class StateLeg:
    """The state-space scan at one grid geometry: the benchmark's
    granite-4.0-h-micro cell by default."""
    n_rows: int = 40
    qb: int = 16
    n_heads: int = 64
    head_dim: int = 64
    d_state: int = 128
    n_slots: int = 80
    conv_dim: int = 4352
    d_conv: int = 4


def full_train_leg() -> TrainLeg:
    return TrainLeg(dataclasses.replace(G.gpt_presets("gpt3-1.3b"),
                                        unroll=True, remat=True))


def full_serve_leg() -> ServeLeg:
    return ServeLeg(dataclasses.replace(L.llama_presets("llama3-8b"),
                                        n_layers=8, max_seq_len=2048))


# ---------------------------------------------------------------------------
# facts
# ---------------------------------------------------------------------------

def device_facts() -> dict:
    import jaxlib
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "native_available": native.available()}


class CompileCacheCounter:
    """Persistent-compilation-cache hits and misses of this process,
    from JAX's own monitoring events."""

    def __init__(self, directory: str):
        self.directory = directory
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def facts(self) -> dict:
        return {"dir": self.directory, "hits": self.hits,
                "misses": self.misses}


_RESULT_SHAPE = re.compile(r"= \(?(\w+\[[\d,]*\])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def pallas_calls(compiled_text: str) -> list:
    """(kernel name, first result shape) of every Mosaic custom call in
    a compiled program.  The name is the stable ``name=`` each
    pallas_call carries: op_name ends ``.../<name>/pallas_call``, and
    autodiff wraps it as ``jvp(<name>)``."""
    out = []
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        scope = _OP_NAME.search(line).group(1).split("/")[-2]
        out.append((re.sub(r"^(?:\w+\()*|\)*$", "", scope),
                    _RESULT_SHAPE.search(line).group(1)))
    return out


def kernels_in(compiled_text: str) -> dict:
    """Mosaic kernels of a compiled program, counted by name."""
    return dict(sorted(collections.Counter(
        name for name, _ in pallas_calls(compiled_text)).items()))


def memory_facts(devices=None) -> list:
    out = []
    for d in devices or jax.devices()[:1]:
        st = d.memory_stats() or {}
        out.append({"device": d.id,
                    "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                    "bytes_in_use": st.get("bytes_in_use")})
    return out


def fusion_facts(expected: dict) -> dict:
    """compiler.last_report() by template, held to the expected sites:
    fewer found or applied, or any matcher error, is a failure."""
    rep = compiler.last_report()
    _require(rep is not None, "no fusion report: the step was not fused")
    by_template: dict = {}
    for s in rep.sites:
        t = by_template.setdefault(s["template"], {"sites": 0, "applied": 0})
        t["sites"] += 1
        t["applied"] += int(s["applied"])
    facts = {"n_sites": rep.n_sites, "n_applied": rep.n_applied,
             "by_template": by_template, "errors": list(rep.errors),
             "program_cache_hit": rep.program_cache_hit}
    _require(not rep.errors, f"fusion matcher errors: {rep.errors}")
    want = {t: {"sites": n, "applied": n} for t, n in expected.items()}
    _require(by_template == want,
             f"fusion sites: expected {want}, got {by_template}")
    return facts


def _normal(seed: int, default_dtype):
    """rnd(shape, dtype=default_dtype, scale=1.0): seeded normal operands
    for the kernel comparisons."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def rnd(shape, dtype=default_dtype, scale=1.0):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    return rnd


def check_close(checks: dict, name: str, got, want, tol: float) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    _require(got.shape == want.shape,
             f"{name}: shape {got.shape} != reference {want.shape}")
    _require(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    checks[name] = {"rel_err": float(f"{err:.3g}"), "tol": tol}
    _require(err <= tol, f"{name}: kernel vs XLA reference rel_err "
                         f"{err:.3g} > {tol}")


def _check_kernels_present(found: dict, expected: set) -> None:
    """Expected Mosaic kernels must be IN the compiled program.  On the
    CPU kernels are interpreted into plain HLO and there is nothing to
    find — the one place the legs look at the platform."""
    if jax.default_backend() == "cpu":
        return
    missing = sorted(expected - set(found))
    _require(not missing, f"kernels missing from the compiled program: "
                          f"{missing} (found {found})")


# ---------------------------------------------------------------------------
# train leg
# ---------------------------------------------------------------------------

def _train_kernel_parity(cfg: G.GPTConfig, batch: int) -> dict:
    """Each kernel of the GPT step vs its XLA reference at the step's
    shapes: flash fwd/bwd (fused-qkv entry), fused CE fwd/bwd, the
    residual+bias+layernorm epilogue, bias+gelu."""
    checks: dict = {}
    B, T, H, nH, dH = batch, cfg.seq_len, cfg.hidden, cfg.n_heads, cfg.head_dim
    F, V = cfg.ffn_mult * H, cfg.vocab_size
    rnd = _normal(7, cfg.dtype)

    # flash attention, forward and backward
    qkv, ct = rnd((B, T, 3 * H)), rnd((B, T, nH, dH))
    scale = 1.0 / math.sqrt(dH)

    def flash_kernel(x):
        return FA.flash_attention_qkv_raw(x, nH, causal=True)

    def flash_ref(x):
        q, k, v = (a.reshape(B, T, nH, dH) for a in jnp.split(x, 3, -1))
        return FA._sdpa_fallback(q, k, v, True, scale)

    ok, vjp_k = jax.vjp(jax.jit(flash_kernel), qkv)
    orf, vjp_r = jax.vjp(jax.jit(flash_ref), qkv)
    check_close(checks, "flash_fwd", ok, orf, TOL_BF16)
    check_close(checks, "flash_bwd", vjp_k(ct)[0], vjp_r(ct)[0], TOL_BF16)

    # fused softmax cross-entropy, loss and both gradients
    x, head = rnd((B * T, H)), rnd((H, V), scale=0.02)
    labels = jax.random.randint(jax.random.PRNGKey(8), (B * T,), 0, V)

    def ce_kernel(x, head):
        return CE.fused_softmax_ce(x, head, labels).mean()

    def ce_ref(x, head):
        return G._chunked_ce(x.reshape(B, T, H), head,
                             labels.reshape(B, T), 512)

    lk, (dxk, dhk) = jax.jit(jax.value_and_grad(ce_kernel, (0, 1)))(x, head)
    lr, (dxr, dhr) = jax.jit(jax.value_and_grad(ce_ref, (0, 1)))(x, head)
    check_close(checks, "fused_ce_loss", lk, lr, TOL_F32)
    check_close(checks, "fused_ce_dx", dxk, dxr, TOL_BF16)
    check_close(checks, "fused_ce_dhead", dhk, dhr, TOL_BF16)

    # residual + bias + layernorm epilogue (ln2's shape in block_apply)
    a, sub = rnd((B, T, H)), rnd((B, T, H))
    bias, gain, beta = (rnd((H,), jnp.float32, 0.1),
                        1.0 + rnd((H,), jnp.float32, 0.1),
                        rnd((H,), jnp.float32, 0.1))
    rk, yk = jax.jit(lambda *o: NE.fused_norm_epilogue(
        *o, norm="layer", eps=cfg.eps, use_kernel=True))(
            a, sub, bias, gain, beta)
    rr, yr = jax.jit(lambda *o: NE._epilogue_xla(
        *o, "layer", cfg.eps, None))(a, sub, bias, gain, beta)
    check_close(checks, "layer_epilogue_r", rk, rr, TOL_BF16)
    check_close(checks, "layer_epilogue_y", yk, yr, TOL_BF16)

    # bias + gelu
    h, fb = rnd((B, T, F)), rnd((F,), jnp.float32, 0.1)
    check_close(checks, "bias_gelu",
                jax.jit(lambda h, b: BA.fused_bias_gelu(
                    h, b, use_kernel=True))(h, fb),
                jax.jit(BA._bias_gelu_ref)(h, fb), TOL_BF16)
    return checks


def _expected_gpt_sites(cfg: G.GPTConfig) -> dict:
    # per block: ln1 and ln2 (+ the final lnf) and one bias+gelu; a scan
    # holds one block's worth
    n = cfg.n_layers if cfg.unroll else 1
    return {"layer_epilogue": 2 * n + 1, "bias_gelu": n}


def _run_trainer(leg: TrainLeg, devices) -> tuple:
    """Build the step, take warmup + steps on one fixed batch; returns
    (step, params, opt_state, toks, labs, facts)."""
    cfg = leg.model
    mesh = build_mesh(leg.mesh, ("dp", "pp", "mp"), devices=devices)
    step, params, opt_state = make_sharded_train_step(
        cfg, mesh, lr=1e-4, zero1=len(devices) > 1, m_dtype="bfloat16",
        v_dtype="bfloat16", weights="sr-bf16")
    rng = np.random.RandomState(0)
    toks = step.put_batch(rng.randint(0, cfg.vocab_size,
                                      size=(leg.batch, cfg.seq_len)))
    labs = step.put_batch(rng.randint(0, cfg.vocab_size,
                                      size=(leg.batch, cfg.seq_len)))
    t0 = time.perf_counter()
    loss, params, opt_state = step(params, opt_state, toks, labs)
    losses = [float(loss)]                   # value fetch = device sync
    cold = time.perf_counter() - t0
    for _ in range(leg.warmup + leg.steps - 1):
        loss, params, opt_state = step(params, opt_state, toks, labs)
        losses.append(float(loss))
    _require(all(math.isfinite(x) for x in losses),
             f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0],
             f"loss did not fall on a fixed batch: {losses}")
    facts = {"mesh": list(leg.mesh), "batch": leg.batch,
             "seq_len": cfg.seq_len, "n_layers": cfg.n_layers,
             "hidden": cfg.hidden, "vocab": cfg.vocab_size,
             "first_step_s": round(cold, 2), "steps": len(losses),
             "losses": [round(x, 4) for x in losses]}
    return step, params, opt_state, toks, labs, facts


def _compiled_text(step, params, opt_state, toks, labs) -> str:
    with jax.sharding.set_mesh(step.mesh):
        return step.jitted.lower(params, opt_state, toks,
                                 labs).compile().as_text()


def train_leg(leg: TrainLeg) -> dict:
    step, params, opt_state, toks, labs, facts = _run_trainer(
        leg, [jax.devices()[0]])
    facts["fusion"] = fusion_facts(_expected_gpt_sites(leg.model))
    facts["kernels"] = kernels_in(
        _compiled_text(step, params, opt_state, toks, labs))
    _check_kernels_present(facts["kernels"], {
        "flash_fwd", "flash_bwd_dqkv", "fused_ce_fwd", "fused_ce_bwd_dx",
        "fused_ce_bwd_dh", "fused_layer_epilogue", "fused_bias_gelu"})
    facts["memory"] = memory_facts()
    del step, params, opt_state, toks, labs
    gc.collect()
    facts["kernel_vs_xla"] = _train_kernel_parity(leg.model, leg.batch)
    return facts


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------

def _serve_kernel_parity(leg: ServeLeg, engine: ServingEngine,
                         t_ref: int) -> dict:
    """Each kernel of the Llama paths vs its XLA reference at the leg's
    shapes: the engine's ragged-paged attention and its write into the
    pages, and llama_apply's rms epilogue, swiglu and rope+flash
    attention."""
    cfg = leg.model
    checks: dict = {}
    H, nH, nKV, dH = cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rnd = _normal(11, cfg.dtype)

    # ragged-paged attention, at every group size the gate admits, on a
    # mixed grid: decode rows (n_valid 1) at every depth and full prefill
    # chunks, over random block tables
    C, qb, bs, mb = engine.n_rows, engine.qb, engine.bs, engine.max_blocks
    P = engine.n_pages
    rs = np.random.RandomState(3)
    n_valid = np.where(np.arange(C) % 2 == 0, 1, qb).astype(np.int32)
    pos0 = rs.randint(0, mb * bs - qb, size=C).astype(np.int32)
    rows = rs.randint(1, P, size=(C, mb)).astype(np.int32)
    q = rnd((C, qb, nH, dH))
    kp, vp = rnd((P, nKV, dH, bs)), rnd((P, nKV, bs, dH))
    sm = 1.0 / math.sqrt(dH)
    want = jax.jit(lambda *o: RPA._ragged_paged_xla(*o, sm, "d_major"))(
        q, kp, vp, rows, pos0, n_valid)
    # and under a sliding window that is no multiple of the page, where
    # most rows have groups behind it to skip
    window = 3 * bs + bs // 2
    want_w = jax.jit(lambda *o: RPA._ragged_paged_xla(
        *o, sm, "d_major", window=window))(q, kp, vp, rows, pos0, n_valid)
    for impl in RPA.candidates_for(kp.shape, nH, qb, mb)[:-1]:
        pps = int(impl.split("_p")[1])
        check_close(checks, f"ragged_paged_attention_{impl}",
                    RPA.ragged_paged_attention_kernel(
                        q, kp, vp, rows, pos0, n_valid, sm, pps=pps),
                    want, TOL_BF16)
        check_close(checks, f"ragged_paged_attention_window_{impl}",
                    RPA.ragged_paged_attention_kernel(
                        q, kp, vp, rows, pos0, n_valid, sm, pps=pps,
                        window=window), want_w, TOL_BF16)

    # the write into the pages, on as many chunks of that grid as can own
    # the two pages a chunk may touch (the kernel's contract: one writer
    # a page); the arms agree exactly on every page but the sink
    W = min(C, (P - 1) // 2)
    own = 1 + 2 * np.arange(W)[:, None] + (np.arange(mb)[None, :]
                                            - pos0[:W, None] // bs) % 2
    k_new, v_new = rnd((W, qb, nKV, dH)), rnd((W, qb, nKV, dH))
    got = KVW.paged_kv_write_kernel(kp, vp, k_new, v_new, own, pos0[:W],
                                    n_valid[:W])
    want = jax.jit(KVW._paged_kv_write_xla)(kp, vp, k_new, v_new, own,
                                            pos0[:W], n_valid[:W], 0)
    check_close(checks, "paged_kv_write_k", got[0][1:], want[0][1:], 0.0)
    check_close(checks, "paged_kv_write_v", got[1][1:], want[1][1:], 0.0)

    # rms epilogue (ffn_norm's shape: residual + norm)
    a, sub = rnd((1, t_ref, H)), rnd((1, t_ref, H))
    gain = (1.0 + rnd((H,), jnp.float32, 0.1)).astype(cfg.param_dtype)
    rk, yk = jax.jit(lambda a, s, g: NE.fused_norm_epilogue(
        a, s, None, g, None, norm="rms", eps=cfg.rms_eps,
        use_kernel=True))(a, sub, gain)
    rr, yr = jax.jit(lambda a, s, g: NE._epilogue_xla(
        a, s, None, g, None, "rms", cfg.rms_eps, None))(a, sub, gain)
    check_close(checks, "rms_epilogue_r", rk, rr, TOL_BF16)
    check_close(checks, "rms_epilogue_y", yk, yr, TOL_BF16)

    # swiglu
    gate, up = rnd((1, t_ref, cfg.ffn_hidden)), rnd((1, t_ref, cfg.ffn_hidden))
    check_close(checks, "swiglu",
                jax.jit(lambda g, u: BA.fused_swiglu(
                    g, u, use_kernel=True))(gate, up),
                jax.jit(BA._swiglu_ref)(gate, up), TOL_BF16)

    # rope + flash attention vs rope + plain softmax attention
    qq, kk, vv = (rnd((1, t_ref, nH, dH)) for _ in range(3))
    cos, sin = L.rope_angles(cfg, jnp.arange(t_ref))

    def rope_ref(q, k, v):
        cb, sb = cos[None, :, None, :], sin[None, :, None, :]
        return FA._sdpa_fallback(RA._apply_rope_ref(q, cb, sb),
                                 RA._apply_rope_ref(k, cb, sb), v, True, sm)

    check_close(checks, "rope_attention",
                jax.jit(lambda q, k, v: RA.fused_rope_flash_attention(
                    q, k, v, cos, sin, use_kernel=True))(qq, kk, vv),
                jax.jit(rope_ref)(qq, kk, vv), TOL_BF16)
    return checks


def _requests(leg: ServeLeg) -> list:
    rs = np.random.RandomState(leg.seed)
    V = leg.model.vocab_size
    prefix = rs.randint(0, V, size=leg.prefix_len)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix, rs.randint(0, V, size=t)]).astype(np.int32),
                    max_new_tokens=n, arrival=0.001 * i)
            for i, (t, n) in enumerate(zip(leg.tails, leg.new_tokens))]


def _serve_once(engine: ServingEngine, leg: ServeLeg) -> tuple:
    """One pass of the seeded requests; every request must complete and
    the page pool must balance.  Returns (streams, facts)."""
    reqs = _requests(leg)
    hits0 = engine.pool.hits
    t0 = time.perf_counter()
    stats = OpenLoopDriver(engine, clock="wall").run(reqs)
    wall = time.perf_counter() - t0
    for r in reqs:
        _require(not r.aborted and len(r.out_tokens) == r.max_new_tokens,
                 f"request {r.rid}: {len(r.out_tokens)} of "
                 f"{r.max_new_tokens} tokens, aborted={r.aborted}")
    pages = engine.page_accounting()
    _require(pages["total"] == engine.n_pages - 1
             and pages["free"] + pages["cache_idle"] == pages["total"],
             f"page pool does not balance after the run: {pages}")
    facts = {"wall_s": round(wall, 2), "steps": stats["steps"],
             "requests_completed": len(reqs),
             "tokens_completed": sum(len(r.out_tokens) for r in reqs),
             "prefix_cache_hits": engine.pool.hits - hits0,
             "pages": pages}
    return [list(map(int, r.out_tokens)) for r in reqs], facts


def serve_leg(leg: ServeLeg) -> dict:
    cfg = leg.model
    params = L.init_llama_params(cfg, jax.random.PRNGKey(leg.seed))
    engine = ServingEngine(cfg, params=params, max_batch=leg.max_batch,
                           page_size=leg.page_size, max_seq=leg.max_seq)
    facts = {"n_layers": cfg.n_layers, "hidden": cfg.hidden,
             "heads": [cfg.n_heads, cfg.n_kv_heads], "ffn": cfg.ffn_hidden,
             "vocab": cfg.vocab_size, "page_size": engine.bs,
             "max_batch": engine.B, "qb": engine.qb, "n_rows": engine.n_rows,
             "n_pages": engine.n_pages,
             "page_dtype": str(engine.k_pages.dtype)}
    streams, facts["run1"] = _serve_once(engine, leg)
    again, facts["run2"] = _serve_once(engine, leg)
    _require(streams == again,
             "greedy streams differ between two runs of the same requests")
    facts["streams_identical"] = True

    facts["kernels"] = kernels_in(
        engine.lower_unified().compile().as_text())
    _check_kernels_present(facts["kernels"], {"ragged_paged_attention",
                                              "paged_kv_write"})

    # the engine against the dense model: llama_apply (the fused Pallas
    # forward: 3 rms epilogues, rope+flash attention, swiglu) scores the
    # reference request's prompt; the engine's first token must be its
    # argmax up to bf16 noise at the top logit's magnitude
    prompt = _requests(leg)[leg.ref_request].prompt
    _require(len(prompt) % 256 == 0,
             f"ref_request prompt length {len(prompt)} is not a multiple "
             f"of 256")
    apply = jax.jit(lambda p, t: L.llama_apply(p, t, cfg))
    logits = np.asarray(apply(params, prompt[None])[0, -1], np.float32)
    facts["fusion"] = fusion_facts(
        {"rms_epilogue": 3, "rope_attention": 1, "swiglu": 1})
    facts["apply_kernels"] = kernels_in(
        apply.lower(params, prompt[None]).compile().as_text())
    _check_kernels_present(facts["apply_kernels"], {
        "fused_rms_epilogue", "rope_flash_fwd", "fused_swiglu"})
    first = streams[leg.ref_request][0]
    gap = float(logits.max() - logits[first])
    tol = float(2.0 ** -4 * np.abs(logits).max())
    facts["first_token_vs_llama_apply"] = {
        "engine_token": first, "apply_argmax": int(logits.argmax()),
        "logit_gap": round(gap, 4), "tol": round(tol, 4)}
    _require(gap <= tol, f"engine's first token {first} scores {gap:.3f} "
                         f"below llama_apply's best logit (tol {tol:.3f})")

    facts["memory"] = memory_facts()
    facts["kernel_vs_xla"] = _serve_kernel_parity(leg, engine, len(prompt))
    return facts


# ---------------------------------------------------------------------------
# latent leg: the MLA serving kernels against their XLA arms
# ---------------------------------------------------------------------------

def latent_leg(leg: LatentLeg) -> dict:
    """Absorbed latent attention over latent pages, at the pages-per-
    step choice the autotune knows, and the latent write (paged_kv_write
    with planes of unequal width), each against its XLA arm on a mixed
    grid: decode rows at every depth beside full prefill chunks."""
    C, qb, nH, R, dr = leg.n_rows, leg.qb, leg.n_heads, leg.kv_rank, \
        leg.rope_dim
    bs, mb, P = leg.page_size, leg.max_blocks, leg.n_pages
    dt = jnp.bfloat16
    rnd = _normal(13, dt)
    rs = np.random.RandomState(5)
    n_valid = np.where(np.arange(C) % 2 == 0, 1, qb).astype(np.int32)
    pos0 = rs.randint(0, mb * bs - qb, size=C).astype(np.int32)
    rows = rs.randint(1, P, size=(C, mb)).astype(np.int32)
    q_lat, q_rope = rnd((C, qb, nH, R)), rnd((C, qb, nH, dr))
    ckv, kr = rnd((P, bs, R)), rnd((P, dr, bs))
    sm = 1.0 / math.sqrt(192.0)
    checks: dict = {}
    want = jax.jit(lambda *o: MPA._mla_paged_xla(*o, sm))(
        q_lat, q_rope, ckv, kr, rows, pos0, n_valid)
    for impl in MPA.candidates_for(mb)[:-1]:
        check_close(checks, f"mla_paged_attention_{impl}",
                    MPA.mla_paged_attention_kernel(
                        q_lat, q_rope, ckv, kr, rows, pos0, n_valid, sm,
                        int(impl.split("_p")[1])), want, TOL_BF16)
    W = min(C, (P - 1) // 2)
    own = 1 + 2 * np.arange(W)[:, None] + (np.arange(mb)[None, :]
                                            - pos0[:W, None] // bs) % 2
    k_new, v_new = rnd((W, qb, 1, dr)), rnd((W, qb, 1, R))
    kp, vp = kr[:, None], ckv[:, None]
    got = KVW.paged_kv_write_kernel(kp, vp, k_new, v_new, own, pos0[:W],
                                    n_valid[:W])
    want = jax.jit(KVW._paged_kv_write_xla)(kp, vp, k_new, v_new, own,
                                            pos0[:W], n_valid[:W], 0)
    check_close(checks, "latent_write_k_rope", got[0][1:], want[0][1:], 0.0)
    check_close(checks, "latent_write_c_kv", got[1][1:], want[1][1:], 0.0)
    _grouped_expert_parity(leg, checks, rnd)
    return {"geometry": dataclasses.asdict(leg), "kernel_vs_xla": checks}


def _grouped_expert_parity(leg: LatentLeg, checks: dict, rnd) -> None:
    """The held experts' gate-and-up product (``grouped_expert_matmul``
    on the arm the autotune table names for the shape, else its kernel
    candidate) against ``lax.ragged_dot``: the second layer of a stack
    of two, a skewed draw of group sizes with an empty group and seven
    eighths of the rows behind the last group; then with no row held at
    all (a decode tick of one or two tokens): the visit axis is empty,
    nothing of ``out`` is written, and the caller's ``where(held)`` is
    all that stands before it."""
    count = leg.n_held
    for m, H, F in leg.expert_shapes:
        impl = GEM.choose_impl(m, H, F, count, jnp.bfloat16, gated=True)
        if impl == "xla":
            impl = GEM.candidates_for(m, H, F, 2)[1]
        skewed = np.full(count, m // (8 * count), np.int32)
        skewed[1], skewed[0] = 0, skewed[0] + skewed[1]
        gate, up = (rnd((2 * count, H, F), scale=H ** -0.5)
                    for _ in range(2))
        x = rnd((m, H))
        for tag, sizes in (("", skewed), ("_none_held", skewed * 0)):
            got = GEM.grouped_expert_matmul(x, gate, jnp.asarray(sizes), up,
                                            base=count, impl=impl)
            want = jax.jit(GEM._grouped_xla)(x, gate, jnp.asarray(sizes), up,
                                             count)
            # the rows behind the last group are the caller's to mask
            held = (jnp.arange(m) < sizes.sum())[:, None]
            check_close(checks, f"grouped_expert_matmul_h{H}_f{F}_{impl}{tag}",
                        jnp.where(held, got, 0.0),
                        jnp.where(held, want, 0.0), TOL_BF16)


# ---------------------------------------------------------------------------
# state leg: the state-space scan against its XLA form
# ---------------------------------------------------------------------------

def state_leg(leg: StateLeg) -> dict:
    """``ragged_ssm_scan`` at every head block the gate admits against
    its XLA form on one grid: decode rows (one a fresh request's, from
    the zero slot), a request of three chained chunk rows whose last is
    partial and which leaves its state in another slot than it read, a
    request whose only row is a full chunk, idle rows behind them.  Both
    the read-outs and every slot of the pool are compared (the dump
    apart).  Then ``ragged_causal_conv`` on the same rows."""
    C, qb, nH, hd, N, S = (leg.n_rows, leg.qb, leg.n_heads, leg.head_dim,
                           leg.d_state, leg.n_slots)
    rnd = _normal(17, jnp.bfloat16)
    rs = np.random.RandomState(7)
    n_dec = C - 6
    read = np.r_[2 + np.arange(n_dec), [S - 3] * 3, S - 4, 1, 1]
    write = np.r_[2 + np.arange(n_dec), [S - 2] * 3, S - 4, 1, 1]
    read[1] = 0                                   # a fresh request
    n_valid = np.r_[np.ones(n_dec), qb, qb, qb // 2 + 1, qb, 1, 1]
    pool = rnd((S,) + SSM.state_shape(nH, hd, N), jnp.float32).at[0].set(0.0)
    x, Bm, Cm = rnd((C * qb, nH * hd)), rnd((C * qb, N)), rnd((C * qb, N))
    dt = jnp.asarray(np.exp(rs.uniform(np.log(1e-3), np.log(0.2),
                                       (C, qb, nH))), jnp.float32)
    A = -jnp.asarray(rs.uniform(1.0, 16.0, (nH,)), jnp.float32)
    ops = (x, dt, A, Bm, Cm, jnp.asarray(read, jnp.int32),
           jnp.asarray(write, jnp.int32), jnp.asarray(n_valid, jnp.int32))
    held = (np.arange(qb)[None] < n_valid[:, None]) & (write != 1)[:, None]
    held = jnp.asarray(held).reshape(C * qb, 1)
    checks: dict = {}
    y0, p0 = SSM.ragged_ssm_scan(pool, *ops, dump=1, impl="xla")
    for impl in SSM.candidates_for(pool.shape, hd, qb)[1:]:
        y, p = SSM.ragged_ssm_scan(pool, *ops, dump=1, impl=impl)
        check_close(checks, f"ragged_ssm_scan_{impl}_y",
                    jnp.where(held, y, 0.0), jnp.where(held, y0, 0.0),
                    TOL_F32)
        check_close(checks, f"ragged_ssm_scan_{impl}_pool",
                    p.at[1].set(0.0), p0.at[1].set(0.0), TOL_F32)
    # the mixer's convolution on the same rows, its states in a pool of
    # the same slots
    K, Dc = leg.d_conv - 1, leg.conv_dim
    cpool = rnd((S, K * Dc)).at[0].set(0.0)
    xc, w, b = rnd((C * qb, Dc)), rnd((Dc, K + 1), jnp.float32), \
        rnd((Dc,), jnp.float32)
    conv = {impl: RCC.ragged_causal_conv(
        cpool, xc, w, b, *ops[5:], qb=qb, zero=0, dump=1, impl=impl)
        for impl in ("xla", "kernel")}
    check_close(checks, "ragged_causal_conv_act",
                jnp.where(held, conv["kernel"][0], 0.0),
                jnp.where(held, conv["xla"][0], 0.0), TOL_BF16)
    check_close(checks, "ragged_causal_conv_pool", conv["kernel"][1],
                conv["xla"][1], 0.0)
    return {"geometry": dataclasses.asdict(leg), "kernel_vs_xla": checks}


# ---------------------------------------------------------------------------
# mesh leg (>= 4 devices)
# ---------------------------------------------------------------------------

def mesh_leg(leg: TrainLeg) -> dict:
    """Hybrid parallelism on real chips: the repo's own 4-device dryrun
    (parallel loss == serial loss, no involuntary rematerialization),
    then the 1.3B trainer on dp2 x mp2 with every flash kernel on its
    LOCAL shard shape (batch/dp, heads/mp) and the four devices holding
    comparable bytes."""
    import __graft_entry__ as graft

    n = int(np.prod(leg.mesh))
    devices = jax.devices()[:n]
    graft._dryrun_impl(n)
    facts = {"dryrun": f"_dryrun_impl({n}) passed"}
    step, params, opt_state, toks, labs, run = _run_trainer(leg, devices)
    facts.update(run)
    text = _compiled_text(step, params, opt_state, toks, labs)
    facts["kernels"] = kernels_in(text)
    _check_kernels_present(facts["kernels"], {"flash_fwd"})
    dp, _, mp = leg.mesh
    local = f"[{leg.batch // dp},{leg.model.seq_len},{leg.model.hidden // mp}]"
    shapes = sorted(set(pallas_calls(text)))
    facts["kernel_result_shapes"] = [list(s) for s in shapes]
    # (flash_fwd's presence on the chip is checked just above)
    _require(all(local in shp for nm, shp in shapes if nm == "flash_fwd"),
             f"flash_fwd is not on the local shard shape {local}: {shapes}")
    facts["memory"] = memory_facts(devices)
    peaks = [m["peak_bytes_in_use"] for m in facts["memory"]]
    if all(peaks):
        facts["peak_bytes_max_over_min"] = round(max(peaks) / min(peaks), 3)
        _require(max(peaks) <= 1.5 * min(peaks),
                 f"device memory is not balanced over the mesh: {peaks}")
    if os.path.isdir(_OUT_DIR):     # for reading what feeds each kernel
        with open(os.path.join(_OUT_DIR, "mesh_hlo.txt"), "w") as f:
            f.write(re.sub(r'"body":"[^"]*"', '"body":"..."', text))
    return facts


# ---------------------------------------------------------------------------
# the script
# ---------------------------------------------------------------------------

def autotune_facts() -> dict:
    reg = autotune.GLOBAL_AUTOTUNE
    facts = {**autotune.stats(), "swept_keys": list(reg.swept_keys),
             "resolved": reg.resolved()}
    kind = jax.devices()[0].device_kind
    if reg.committed_covers(kind):
        # a sweep here means two checkouts of one commit could run
        # different programs
        _require(not reg.swept_keys,
                 f"autotune swept {reg.swept_keys} although "
                 f"{autotune.COMMITTED_PATH} covers '{kind}': a kernel or "
                 f"shape changed — commit the table this run left in "
                 f"chiprun_out/pallas_autotune.json")
    return facts


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"'{dev.platform}' ({dev.device_kind}). There is no CPU mode.",
              file=sys.stderr)
        return 1
    cache = CompileCacheCounter(enable_compile_cache())
    report = {"device": device_facts()}
    print(json.dumps(report["device"]), flush=True)

    legs = [("train", train_leg, full_train_leg()),
            ("serve", serve_leg, full_serve_leg()),
            ("latent", latent_leg, LatentLeg()),
            ("state", state_leg, StateLeg())]
    if len(jax.devices()) >= 4:
        # first, so that its per-device peaks are its own: the one-chip
        # legs that follow all land on device 0
        legs.insert(0, ("mesh", mesh_leg, dataclasses.replace(
            full_train_leg(), mesh=(2, 1, 2), warmup=2, steps=2)))
    os.makedirs(_OUT_DIR, exist_ok=True)
    for name, fn, cfg in legs:
        t0 = time.perf_counter()
        report[name] = fn(cfg)
        report[name]["leg_s"] = round(time.perf_counter() - t0, 1)
        print(f"--- {name} leg ---\n{json.dumps(report[name], indent=1)}",
              flush=True)
        gc.collect()

    # the table this run resolved, for committing (see autotune.py)
    if os.path.exists(autotune.cache_path()):
        with open(autotune.cache_path()) as src, open(
                os.path.join(_OUT_DIR, "pallas_autotune.json"), "w") as dst:
            dst.write(src.read())
    report["compile_cache"] = cache.facts()
    report["autotune"] = autotune_facts()
    print(f"--- compile cache ---\n{json.dumps(report['compile_cache'])}\n"
          f"--- autotune ---\n{json.dumps(report['autotune'], indent=1)}",
          flush=True)
    with open(os.path.join(_OUT_DIR, "chip_smoke_report.json"), "w") as f:
        json.dump(report, f, indent=1)
    d = report["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": d["platform"], "kind": d["kind"], "count": d["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
