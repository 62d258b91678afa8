"""Global runtime flag registry.

TPU-native analog of the reference's gflags-like registry
(paddle/common/flags.h:83 ``PD_DEFINE_VARIABLE`` and
paddle/common/flags_native.cc): typed flags, env-var override via
``FLAGS_<name>``, and a ``get_flags``/``set_flags`` API surface
(python/paddle/base/framework.py:157,132 in the reference).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable


@dataclass
class _Flag:
    name: str
    default: Any
    value: Any
    type: type
    help: str


class FlagRegistry:
    """Process-global typed flag store with FLAGS_* env override.

    Backed by the native C++ registry (core/native/flags_native.cc — the
    equivalent of the reference's flags_native.cc) when the toolchain is
    available: values live in the native store so C++ runtime components
    read the same flags; this class keeps the python type metadata and
    falls back to a pure-python store otherwise.
    """

    def __init__(self):
        self._flags: dict[str, _Flag] = {}
        self._lock = threading.RLock()
        self._native = None
        self._native_tried = False

    def _lib(self):
        if not self._native_tried:
            self._native_tried = True
            try:
                from . import native

                self._native = native.load()
            except Exception:
                self._native = None
        return self._native

    def define(self, name: str, default: Any, help: str = "") -> None:
        with self._lock:
            if name in self._flags:
                raise ValueError(f"flag '{name}' already defined")
            value = default
            env = os.environ.get(f"FLAGS_{name}")
            if env is not None:
                value = self._parse(env, type(default))
            self._flags[name] = _Flag(name, default, value, type(default), help)
            lib = self._lib()
            if lib is not None:
                lib.pt_flag_define(name.encode(), str(value).encode(),
                                   help.encode())

    @staticmethod
    def _parse(text: str, ty: type) -> Any:
        if ty is bool:
            return text.lower() in ("1", "true", "yes", "on")
        return ty(text)

    def get(self, name: str) -> Any:
        # reads stay on the python cache (dispatch queries flags per-op);
        # set() writes through to the native store, which is what C++
        # components read
        with self._lock:
            return self._flags[name].value

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            flag = self._flags[name]
            if not isinstance(value, flag.type):
                value = self._parse(str(value), flag.type)
            flag.value = value
            lib = self._native
            if lib is not None:
                lib.pt_flag_set(name.encode(), str(value).encode())

    def has(self, name: str) -> bool:
        with self._lock:
            return name in self._flags

    def all(self) -> dict[str, Any]:
        with self._lock:
            return {k: f.value for k, f in self._flags.items()}


GLOBAL_FLAGS = FlagRegistry()


def define_flag(name: str, default: Any, help: str = "") -> None:
    GLOBAL_FLAGS.define(name, default, help)


def get_flags(flags) -> dict[str, Any]:
    """Query one flag name or a list of names; returns a dict."""
    if isinstance(flags, str):
        flags = [flags]
    return {name: GLOBAL_FLAGS.get(name) for name in flags}


def set_flags(flags: dict[str, Any]) -> None:
    for name, value in flags.items():
        GLOBAL_FLAGS.set(name, value)


# Core runtime flags (subset of the reference's 178 exported flags in
# paddle/common/flags.cc that are meaningful on a trace/compile runtime).
# The tpu-lint TPL006 suppressions below mark reserved API-parity surface:
# flags the reference exports and user code sets via FLAGS_*/set_flags,
# which no lowering on this runtime needs to consult (XLA owns the
# behavior the reference gated behind them).
define_flag("check_nan_inf", False, "Check outputs of every eager op for NaN/Inf.")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; >0: log only.")
define_flag("benchmark", False, "Synchronize after each op for accurate timing.")  # tpu-lint: disable=TPL006 -- parity surface; jax blocks on result use, no per-op sync hook needed
define_flag("eager_op_cache", True, "Cache per-op compiled executables in eager mode.")  # tpu-lint: disable=TPL006 -- parity surface; jax always caches eager executables
define_flag("use_bf16_matmul", False, "Force bf16 accumulation inputs for matmul ops.")  # tpu-lint: disable=TPL006 -- parity surface; AMP auto_cast owns matmul precision here
define_flag("log_compiles", False, "Log XLA compilations triggered by the runtime.")  # tpu-lint: disable=TPL006 -- parity surface; use jax_log_compiles for the same signal
define_flag("deterministic", False, "Prefer deterministic kernel lowering.")  # tpu-lint: disable=TPL006 -- parity surface; XLA:TPU lowering is already deterministic
define_flag("allocator_strategy", "auto_growth", "Kept for API parity; XLA owns HBM.")  # tpu-lint: disable=TPL006 -- parity surface per its own help text
define_flag("device_fft", False,
            "Run paddle.fft on device on TPU (default host numpy; some TPU "
            "runtimes reject FFT programs).")
define_flag("flash_attention_kernel_bwd", True,
            "Use the Pallas tiled backward kernels for flash attention "
            "(512/1024 tiles, fastest measured on v5e); 0 falls back to "
            "the XLA-expression vjp.")
define_flag("use_library_flash_attention", False,
            "Route flash attention to jax's library TPU kernels.")
define_flag("use_fused_ce", True,
            "Use the Pallas fused softmax-CE kernel for the GPT loss on "
            "TPU (single-program path); 0 falls back to the chunked XLA "
            "scan.")
define_flag("flash_attention_native_layout", True,
            "Flash kernels consume the model's (b, s, h, d) layout via "
            "lane-fused 2-D blocks (no transpose copies); 0 restores the "
            "round-2 transpose-based kernels for A/B measurement.")
define_flag("flash_attention_fused_dqkv", True,
            "Fused-qkv flash backward writes dq/dk/dv into ONE dqkv "
            "cotangent tile per program (merged kernel, no concatenate); "
            "0 restores the split two-kernel + concat path for A/B.")
define_flag(
    "use_pallas_attention",
    True,
    "Route scaled_dot_product_attention to the Pallas flash kernel on TPU.",
)
define_flag("use_fused_rope_attention", True,
            "Apply RoPE to Q/K tiles inside the Pallas flash kernel "
            "(ops/pallas/fused_rope_attention.py) instead of a separate "
            "rotary pass with its own HBM round-trip; 0 restores the "
            "unfused apply_rope + flash composition.")
define_flag("use_fused_norm_epilogue", True,
            "Fuse residual-add + bias + RMSNorm/LayerNorm (+ optional "
            "activation) into one VMEM-resident Pallas kernel for the "
            "attention/FFN epilogues; 0 restores the unfused XLA ops.")
define_flag("use_fused_bias_act", True,
            "Let the fusion pass discover FFN activation chains — "
            "bias+gelu (gpt) and swiglu (llama) — and rewrite them to "
            "ops/pallas/fused_bias_act.py; 0 disables discovery of the "
            "two activation templates only.")
define_flag("use_auto_fusion", True,
            "Run the jaxpr-level fusion pass (paddle_tpu/compiler/) over "
            "jitted model steps: discover catalog template matches "
            "(norm epilogues, RoPE+attention, bias+gelu, swiglu) and "
            "rewrite them to the fused Pallas kernels. 0 skips the pass "
            "entirely — the traced jaxpr is bit-identical to the unfused "
            "composition.")

# -- Pallas autotune registry (ops/pallas/autotune.py) --------------------
define_flag("pallas_autotune", True,
            "Route Pallas block/grid shape choices through the autotune "
            "registry (cache lookup + default fallback); 0 pins every "
            "kernel to its hand-tuned default config.")
define_flag("pallas_autotune_sweep", "auto",
            "When a tuned config is missing from the cache: 'auto' sweeps "
            "candidates on TPU only (CPU/interpret always uses defaults), "
            "'1' forces sweeping on any backend, '0' never sweeps.")
define_flag("pallas_autotune_cache", "",
            "Path of the persistent autotune JSON cache; empty uses "
            "artifacts/pallas_autotune.json under the repo root.")

# -- self-healing runtime defaults (parallel/resilient_loop.py reads these
#    when the caller passes None; FLAGS_* env overrides reach child
#    workers through the launcher env like every other flag) --------------
# -- serving-engine defaults (inference/serving.py reads these when the
#    caller passes None) --------------------------------------------------
define_flag("serving_prefill_budget", 512,
            "Prompt tokens per chunked ragged-prefill dispatch (rounded "
            "down to a page-size multiple; the serving engine packs "
            "page-size chunks from any number of requests into ONE "
            "compiled program per step).")
define_flag("serving_prefix_cache", True,
            "Content-hash full prompt pages and share them across "
            "requests (each distinct prefix prefilled once; refcounted "
            "pages, LRU-evicted under pool pressure).")
define_flag("serving_prefix_cache_pages", 0,
            "Max idle (refcount-0) pages the prefix cache retains; 0 = "
            "no cap beyond pool pressure (idle cached pages are evicted "
            "on demand when allocation would otherwise fail).")
define_flag("serving_unified_qb", 16,
            "Query-token width of one unified ragged-paged-attention row "
            "(a decode step occupies 1 of its qb slots; a prefill chunk "
            "fills up to qb). Need not divide the page size.")
define_flag("serving_speculative_k", 0,
            "Draft tokens verified per decode row via self-drafting "
            "n-gram lookup (greedy-verify). 0 disables speculation; the "
            "off path is bit-identical to the non-speculative engine.")
define_flag("serving_spec_ngram", 3,
            "Longest n-gram the speculative prompt-lookup proposer "
            "matches against the request's history (falls back to "
            "shorter grams down to 1).")
define_flag("serving_wire_overlap", False,
            "Overlapped migration wire: a donor engine stages completed "
            "slots' KV pages through an async device->host copy chained "
            "after the in-flight program (no blocking chain sync at "
            "export), and an adopter folds commit_adopt's page scatter "
            "between programs (applied at its next dispatch) instead of "
            "serializing behind the in-flight chain. Off (default) = "
            "the PR 12 synchronous wire, bit-identical.")
define_flag("serving_kv_quant", False,
            "Store KV pages as symmetric int8 with a per-page, per-head "
            "fp32 scale plane ([L, n_pages, n_kv_heads]); dequant is "
            "fused into both ragged-paged-attention arms. Halves KV "
            "bytes per token (~2x sequences per pool). Off = bit-"
            "identical bf16/fp32 pages.")
define_flag("decode_weight_quant", False,
            "Weight-only int8 for the decode path: per-output-channel "
            "absmax scales with dequant fused into the matmul epilogue "
            "(ops/pallas/quant_matmul.py; XLA fallback elsewhere). Off "
            "= full-precision weights, bit-identical.")

# -- multi-tenant serving (inference/multitenant/; all default off =
#    bit-identical streams, pinned in tests/test_multitenant.py) ----------
define_flag("serving_lora", False,
            "Per-request LoRA serving: adapter weights live as "
            "refcounted, content-hashed pages in the KV page pool "
            "(inference/multitenant/lora.py) and heterogeneous adapters "
            "apply across the packed batch in one grouped BGMV program "
            "(ops/pallas/lora_matmul.py). Off = base model only, "
            "bit-identical.")
define_flag("serving_priorities", False,
            "Priority classes with preemption: admission orders by "
            "(priority desc, arrival) and under pool pressure a "
            "low-priority resident request's KV pages are evicted and "
            "it re-admits later through the prefix cache (re-prefill "
            "charged to the occ_waste_preempted bucket). Off = FIFO "
            "admission, bit-identical.")
define_flag("serving_constrained", False,
            "Constrained decoding: per-request JSON-schema/grammar token "
            "masks (inference/multitenant/constrain.py) ride the static "
            "unified program as per-row data and mask logits before "
            "sampling. Off = unmasked sampling, bit-identical.")

# -- fleet serving (inference/fleet/; consulted only by FleetRouter —
#    serving_fleet_engines=0 means no fleet layer exists and a lone
#    ServingEngine is bit-identical to PR 10, pinned in
#    tests/test_fleet.py) -------------------------------------------------
define_flag("serving_fleet_engines", 0,
            "Replica count the FleetRouter builds when not given "
            "engines explicitly. 0 (default) = fleet layer off; a "
            "single ServingEngine never consults any serving_fleet_* "
            "flag, so off is bit-identical by construction.")
define_flag("serving_fleet_migration", True,
            "On engine loss, ship the victims' full KV pages (+ int8 "
            "scale planes) from the dead engine's still-readable pool "
            "to the re-admission target's prefix cache. Off = victims "
            "recover by re-prefill only (same streams, more FLOPs).")
define_flag("serving_fleet_affinity", True,
            "Session affinity in router placement: requests carrying "
            "the same Request.session key prefer the replica that "
            "served the session last (their KV prefix is resident "
            "there). Deadline-tight requests override affinity.")
define_flag("serving_fleet_retry_max", 3,
            "Re-admission attempts per victim request after an engine "
            "loss before the router gives up and aborts it.")
define_flag("serving_fleet_retry_base_delay", 0.05,
            "Base backoff seconds between re-admission attempts "
            "(exponential: base * 2**attempt, deterministic).")
define_flag("serving_fleet_step_budget", 0.0,
            "Wall seconds one ServingEngine.step may take before the "
            "router declares the replica hung and recovers its "
            "requests. 0 (default) = hang detection off.")
define_flag("serving_fleet_fail_threshold", 1,
            "Consecutive step exceptions before a replica is declared "
            "dead (1 = first raise kills it).")
define_flag("serving_fleet_shed_backlog", 0.0,
            "Graceful-degradation knob: when the never-yet-accepted "
            "backlog exceeds this multiple of surviving pool capacity "
            "(in pages) after a replica loss, the router sheds the "
            "lowest-priority queued requests down to the limit. "
            "Accepted streams are never shed. 0 (default) = no "
            "pressure shedding (only never-placeable requests drop).")
define_flag("serving_fleet_tight_deadline", 0.25,
            "Remaining-TTFT-budget threshold (seconds) below which "
            "router placement ignores affinity/cache bonuses and "
            "routes to the least-loaded replica (deadline-aware "
            "routing).")

# -- disaggregated prefill/decode pools (inference/fleet/; consulted
#    only by FleetRouter — serving_disagg_prefill=0 means no pool split
#    and the fleet is bit-identical to the PR 11 colocated layout,
#    pinned in tests/test_disagg.py) --------------------------------------
define_flag("serving_disagg_prefill", 0,
            "Replica count the FleetRouter assigns to the prefill pool "
            "(the first N replicas; the rest form the decode pool). "
            "0 (default) = no disaggregation: every replica serves "
            "both phases exactly as in PR 11. Prefill-pool engines run "
            "chunked prefill + first-token emission only, export full "
            "KV pages over the migration wire, and never hold a decode "
            "row; shipment retries ride serving_fleet_retry_max / "
            "serving_fleet_retry_base_delay.")
define_flag("serving_disagg_ship_deadline", 0.0,
            "Per-shipment wall-clock deadline (seconds) for the "
            "prefill->decode page handoff, measured from export. A "
            "shipment past its deadline stops retrying and the request "
            "falls back to colocated serving (re-prefill through the "
            "prefix cache — same stream, more FLOPs). 0 (default) = "
            "no deadline; only retry exhaustion triggers fallback.")
define_flag("serving_disagg_dynamic", False,
            "Measured-load pool splitting: the router tracks per-role "
            "demand EWMAs (queued prefill tokens vs remaining decode "
            "tokens) and re-splits the prefill/decode pools when the "
            "measured share leaves a hysteresis band around the current "
            "split, moving one replica per tick. serving_disagg_prefill"
            "=N acts as a pin/override (the split never moves). Off "
            "(default) = static split only, bit-identical.")
define_flag("serving_disagg_ewma", 0.3,
            "EWMA smoothing factor (0 < alpha <= 1) for the dynamic-"
            "split per-role demand estimates; higher = faster reaction "
            "to phase shifts, lower = steadier split.")
define_flag("serving_disagg_hysteresis", 0.2,
            "Dead band for dynamic re-splitting: the measured prefill "
            "share must differ from the current pool share by more than "
            "this fraction before a replica changes role (prevents "
            "role flapping at phase boundaries).")

# -- zero-downtime fleet operations (inference/fleet/rollout.py +
#    FleetRouter hooks — rolling weight upgrades, demand autoscale, and
#    SLO-aware shedding. All off by default; with every flag off the
#    router/engine behavior is pinned bit-identical to the PR 17 fleet
#    in tests/test_rollout.py) ---------------------------------------------
define_flag("serving_fleet_rollout_canary", 4,
            "Canary decode length (new tokens) for the post-swap health "
            "check during FleetRouter.rollout: the freshly swapped "
            "engine must complete a solo greedy decode of this many "
            "tokens before it rejoins placement. 0 = skip the canary "
            "(swapped engines rejoin unchecked). Only consulted while a "
            "rollout is in flight, so the default is inert otherwise.")
define_flag("serving_fleet_autoscale", False,
            "Demand-driven engine count: the router reuses the dynamic-"
            "split demand census (queued prefill tokens + remaining "
            "decode tokens) as a fleet-wide utilization EWMA against "
            "aggregate page capacity, adds an engine above the high "
            "watermark and retires one (drain-then-remove, requests "
            "are never dropped) below the low watermark, bounded by "
            "serving_fleet_{min,max}_engines with a cooldown between "
            "actions. Off (default) = fixed fleet, bit-identical.")
define_flag("serving_fleet_min_engines", 1,
            "Autoscale floor: retire never shrinks the fleet below "
            "this many live engines.")
define_flag("serving_fleet_max_engines", 4,
            "Autoscale ceiling: scale-up never grows the fleet above "
            "this many live engines.")
define_flag("serving_fleet_scale_high", 0.85,
            "Utilization EWMA (demand tokens / aggregate token "
            "capacity) above which the autoscaler adds an engine.")
define_flag("serving_fleet_scale_low", 0.2,
            "Utilization EWMA below which the autoscaler drains and "
            "retires the least-loaded engine (subject to the floor).")
define_flag("serving_fleet_scale_ewma", 0.3,
            "EWMA smoothing factor (0 < alpha <= 1) for the autoscale "
            "utilization estimate; higher = faster reaction.")
define_flag("serving_fleet_scale_cooldown", 1.0,
            "Minimum seconds between autoscale actions (hysteresis in "
            "time: prevents add/retire flapping at a watermark).")
define_flag("serving_fleet_slo_shed", False,
            "SLO-aware admission control: on each router tick the "
            "predicted queue wait for every never-yet-accepted request "
            "(tokens ahead of it / measured or prior service rate) is "
            "compared against its remaining TTFT budget, and requests "
            "that cannot make their deadline are shed lowest-priority "
            "first BEFORE the deadline blows (stat n_slo_shed), instead "
            "of counting misses after. Accepted streams are never shed. "
            "Off (default) = deadline misses are only counted.")
define_flag("serving_fleet_slo_rate", 0.0,
            "Service-rate prior (tokens/sec per live engine) for the "
            "SLO shed predictor. 0 (default) = use the measured "
            "per-tick throughput EWMA; a positive value pins the "
            "predictor (deterministic in rush-clock tests).")

define_flag("dist_allreduce_quant", False,
            "EQuARX-style int8 gradient all-reduce for the dp gradient "
            "sync: per-rank-chunk symmetric int8 with fp32 scales on the "
            "wire for BOTH phases (reduce-scatter + all-gather), riding "
            "the ops/quant.py primitives — ~4x less gradient-sync "
            "bandwidth. Off = bit-identical full-precision psum sync.")

define_flag("resilient_max_bad_steps", 3,
            "Consecutive NaN/Inf steps tolerated (skipped) before the "
            "resilient loop rolls state back to the last good checkpoint.")
define_flag("resilient_step_timeout", 120.0,
            "Seconds a compiled step may block before the StepWatchdog "
            "escalates (comm-task dump -> checkpoint -> elastic exit).")
define_flag("resilient_keep_last_k", 3,
            "Rotated checkpoints retained by the resilient loop "
            "(save_checkpoint keep_last_k).")
define_flag("resilient_retry_max", 5,
            "Retry attempts for store/checkpoint IO in with_retries.")
define_flag("resilient_retry_base_delay", 0.05,
            "Base backoff seconds for with_retries (exponential, "
            "full jitter).")

define_flag("obs_buffer_events", 65536,
            "Capacity of the per-process trace ring (events), read when "
            "paddle_tpu.obs is imported: the ring (paddle_tpu/obs) is "
            "always on. The flight recorder dumps whatever the ring "
            "holds, so this is also the postmortem window length.")
define_flag("obs_dir", "artifacts",
            "Directory for observability artifacts: flight-recorder "
            "dumps (flightrec-*.json) and exported Chrome traces.")
