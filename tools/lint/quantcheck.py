"""tpu-quantcheck: static precision & scale-provenance verifier.

shardcheck proves *layout* properties of the registered entry programs
from their jaxprs; this module proves the **numeric** ones.  The same
entry set (the dp×pp×mp train step, both unified serving steps — fp32
and int8-KV — the disagg wire stage/commit, ``dist_allreduce_quant``,
the quant_matmul decode path) is traced shape-only and abstractly
interpreted over a precision lattice: every value carries a *storage
format* (its dtype), a *kind* on the quantization ladder, and a
*scale-provenance* set naming the quantize/rescale/scatter-max events
its bytes were produced under.  Five rule families fire on the
propagated environment:

   TPL300 format-legality  a storage format unknown to the verifier, or
          a known format flowing into an op class whose backend row does
          not admit it.  fp8 lands in this codebase by *declaring* rows
          (KNOWN_FORMATS + FORMAT_LEGALITY) — until then any float8_*
          reaching a traced program is a finding, so the on-ramp is a
          table edit, not a silent pass.
   TPL301 low-precision-accumulation  a dot/conv with a sub-fp32
          operand whose result dtype is not an fp32-class accumulator;
          plus the declared ``ACCUM_DTYPE`` of every Pallas kernel
          module and every applied fusion-catalog Site — the kernel arm
          and the XLA fallback of each op must *agree* on fp32
          accumulation, and the declarations are what pins the kernel
          side (the kernels never appear in CPU traces).
   TPL302 silent-upcast-x64-drift  float64 anywhere in a traced
          program: an f64 entry operand, or an eqn whose output is f64
          with no f64 input (the upcast point).  The repo runs x64-off
          everywhere; a stray f64 doubles HBM traffic silently.
   TPL303 scale-provenance-mismatch  int8 bytes consumed (dequantized,
          rescaled, or quantized-against) under a scale that does not
          trace to the same quantize/rescale/kv_scale_update event that
          produced the bytes.  This is exactly the PR 8 pre-fix bug —
          a reused KV page dequantized against the prior tenant's
          absmax — rebuilt on demand by
          ``kv_admit_first_write(_zero_scale_on_alloc=False)``
          (:func:`build_admit_entry` with ``zero_scale_on_alloc=False``)
          where it must fire exactly once; the shipped tree is clean.
   TPL304 unclamped-scale-divide  a divide by a scale that is not
          dominated by a ``maximum(., SCALE_EPS)`` clamp
          (ops/quant.py::SCALE_EPS) — the zero-row NaN factory.
   TPL305 double-quantization  re-quantizing bytes that are already
          int8 (or their raw float view) without an intervening
          dequantize/rescale — each pass multiplies the rounding error.

The interpreter recurses into scan/remat2/jit/shard_map/custom-vjp
bodies exactly as shardcheck does (scan carries run a 2-sweep
fixpoint), and baseline/EXPLAINED/diff semantics mirror shardcheck:
``python -m tools.lint --quantcheck`` with exit codes 0 clean / 1
findings-or-drift / 2 usage / 3 missing baseline, drift-checked against
``artifacts/quantcheck.json``.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field, replace

from .core import Finding
from .shardcheck import (COLLECTIVE_PRIMS, _eqn_location, _flatten_names,
                         _inner_closed, _count_eqns, _finding_entry, _jax,
                         load_baseline, write_baseline)

__all__ = [
    "QVal",
    "QuantEntry",
    "QuantInterp",
    "EXPLAINED",
    "KNOWN_FORMATS",
    "FORMAT_LEGALITY",
    "QUANTCHECK_RULES",
    "PALLAS_KERNEL_MODULES",
    "build_admit_entry",
    "build_entries",
    "build_report",
    "check_entry",
    "diff_baselines",
    "format_environment",
    "kernel_decl_findings",
    "load_baseline",
    "regression_report",
    "site_accum_findings",
    "stale_explanations",
    "unexplained_findings",
    "write_baseline",
]

QUANTCHECK_RULES = {
    "TPL300": "format-legality",
    "TPL301": "low-precision-accumulation",
    "TPL302": "silent-upcast-x64-drift",
    "TPL303": "scale-provenance-mismatch",
    "TPL304": "unclamped-scale-divide",
    "TPL305": "double-quantization",
}

# ---------------------------------------------------------------------------
# the format-legality table (TPL300)
# ---------------------------------------------------------------------------
# Formats the verifier understands.  A dtype outside this set (float8_*,
# int4, ...) reaching any traced program is a TPL300 finding: a new
# storage format lands by adding it here AND adding it to the legality
# rows of every op class that may carry it — the fp8 on-ramp is these
# two table edits plus whatever kernels make them true.
KNOWN_FORMATS = frozenset({
    "float32", "float64", "bfloat16", "float16",
    "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64",
    "bool", "float0",
})
# Extended dtypes that are opaque-but-fine (new-style PRNG keys).
_KNOWN_PREFIXES = ("key<",)

BACKEND = "tpu"

# (backend, op class) -> formats that class may legally carry.  Op
# classes are the places a format commitment is load-bearing: the MXU
# contraction units (dot/conv), the ICI collectives, and the
# scatter/gather paths the paged-KV plane lives on.
_WIDE = frozenset({
    "float32", "float64", "bfloat16", "float16",
    "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64", "bool",
})
FORMAT_LEGALITY = {
    (BACKEND, "dot"): frozenset({
        "float32", "float64", "bfloat16", "float16", "int8", "int32"}),
    (BACKEND, "conv"): frozenset({
        "float32", "float64", "bfloat16", "float16", "int8", "int32"}),
    (BACKEND, "collective"): _WIDE,
    (BACKEND, "scatter"): _WIDE,
    (BACKEND, "gather"): _WIDE,
}

# Sub-fp32 storage formats: a dot/conv touching one of these must
# accumulate into an fp32-class dtype (TPL301).  float8_* is matched by
# prefix so the rule is already correct the day fp8 rows are declared.
SUB_F32 = frozenset({"bfloat16", "float16", "int8", "uint8", "int16"})
_ACCUM_OK = frozenset({"float32", "float64", "int32"})

# Pallas kernel modules that must declare ``ACCUM_DTYPE``.  CPU traces
# only ever contain the XLA fallback arms (tiny geometries fail the
# *_supported gates), so the kernel side of the "both arms accumulate
# fp32" contract is pinned by these declarations instead.
PALLAS_KERNEL_MODULES = (
    "paddle_tpu.ops.pallas.decode_attention",
    "paddle_tpu.ops.pallas.flash_attention",
    "paddle_tpu.ops.pallas.fused_ce",
    "paddle_tpu.ops.pallas.grouped_expert_matmul",
    "paddle_tpu.ops.pallas.lora_matmul",
    "paddle_tpu.ops.pallas.mla_paged_attention",
    "paddle_tpu.ops.pallas.quant_matmul",
    "paddle_tpu.ops.pallas.ragged_causal_conv",
    "paddle_tpu.ops.pallas.ragged_paged_attention",
    "paddle_tpu.ops.pallas.ragged_ssm_scan",
)

# Known findings with rationales, keyed (entry, rule) — the shardcheck
# EXPLAINED analog.  A finding keyed here is reported in the baseline
# but does not fail the run; a key with no matching finding is itself
# drift (stale rationales must be pruned like stale suppressions).
EXPLAINED = {
    ("train_dp2_pp2_mp2", "TPL301"):
        "the GPT blocks' bf16->bf16 matmuls are deliberate (models/"
        "gpt.py block comment): the TPU MXU accumulates bf16 dots in "
        "fp32 internally regardless of the emitted dtype, and bf16 "
        "outputs halve the residuals' HBM traffic; the rule stays on "
        "so a NEW sub-fp32 dot in any other entry still fails the gate",
}


def _known_fmt(f) -> bool:
    if f is None:
        return True            # no dtype (tokens/effects) — not a format
    return f in KNOWN_FORMATS or any(f.startswith(p)
                                     for p in _KNOWN_PREFIXES)


def _fmt(aval):
    d = getattr(aval, "dtype", None)
    return str(d) if d is not None else None


# ---------------------------------------------------------------------------
# the precision lattice
# ---------------------------------------------------------------------------

# Kind ladder, ordered by join priority (higher wins a merge — once
# bytes are quantized, forgetting that is the unsafe direction):
#   data   plain numeric value
#   abs    an |x| reduction on the way to becoming a scale
#   scale  a dequantization scale (fp32, one per page/channel/chunk)
#   ratio  old_scale / new_scale — the rescale_int8 multiplier
#   qpend  value / scale, not yet rounded to int8 (quantize in flight)
#   raw    the float view of int8 bytes (int8 -> float convert); still
#          carries the bytes' provenance until a scale multiply lands
#   quant  int8 bytes
_KIND_PRIO = {"data": 0, "abs": 1, "scale": 2, "ratio": 3,
              "qpend": 4, "raw": 5, "quant": 6}

# maximum(x, lit) marks x clamped when lit is a tiny positive floor
# (SCALE_EPS = 1e-30; anything <= this bound reads as an epsilon clamp,
# not a data max).
_CLAMP_LIT_MAX = 1e-6


@dataclass(frozen=True)
class QVal:
    """One abstract value: storage format, quantization kind, and scale
    provenance.

    ``origin`` is the id of the scale event (a quantize / rescale /
    scatter-max / scale-plane invar) this value's scale derives from;
    ``anc`` is the full ancestor event set (lineage through rescales and
    running-absmax updates).  ``foreign`` marks a scale plane that may
    hold a *prior tenant's* absmax (the admit entry's invar plane) —
    consuming it without an intervening reset is TPL303.  ``clamped``
    records domination by a ``maximum(., SCALE_EPS)``; ``rfrom`` is, for
    a ratio, the lineage of the OLD scale (the bytes it may legally
    rescale); ``lit`` carries scalar literal values (127.0 / 0.0 /
    SCALE_EPS recognition)."""

    fmt: str | None = "float32"
    kind: str = "data"
    origin: int = -1
    anc: frozenset = frozenset()
    foreign: bool = False
    clamped: bool = False
    rfrom: frozenset = frozenset()
    lit: float | None = None


def _qjoin(a: QVal, b: QVal) -> QVal:
    """Join two lattice values (select_n / concatenate / scan carry):
    the higher kind wins, lineages union, foreign is sticky, clamped
    only survives if both sides were clamped."""
    w = a if _KIND_PRIO.get(a.kind, 0) >= _KIND_PRIO.get(b.kind, 0) else b
    return replace(w, anc=a.anc | b.anc, foreign=a.foreign or b.foreign,
                   clamped=a.clamped and b.clamped,
                   rfrom=a.rfrom | b.rfrom, lit=None)


def _qval_str(q: QVal) -> str:
    """Deterministic rendering for histograms/goldens: format and kind
    plus the boolean flags — event ids are interpreter-run-relative and
    deliberately excluded."""
    s = f"{q.fmt}|{q.kind}"
    if q.clamped:
        s += "|clamped"
    if q.foreign:
        s += "|foreign"
    return s


# ---------------------------------------------------------------------------
# entry programs
# ---------------------------------------------------------------------------

@dataclass
class QuantEntry:
    """One registered program plus the quantization facts the tracer
    cannot recover from the jaxpr alone: which invars are scale planes,
    which int8 invars pair with which plane (their bytes were produced
    under that plane's events), and which planes may carry a foreign
    (prior-tenant) absmax."""

    name: str
    closed: object                        # jax ClosedJaxpr
    source: str
    invar_names: list = field(default_factory=list)
    scale_invars: set = field(default_factory=set)
    foreign_scale_invars: set = field(default_factory=set)
    page_pairs: dict = field(default_factory=dict)   # int8 idx -> scale idx


def _tiny_serving_cfg():
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import LlamaConfig

    return LlamaConfig(vocab_size=128, hidden=32, n_layers=2, n_heads=2,
                       n_kv_heads=2, ffn_hidden=64, max_seq_len=64,
                       dtype=jnp.float32, param_dtype=jnp.float32)


def _tiny_engine(kv_quant: bool):
    from paddle_tpu.inference.serving import ServingEngine

    return ServingEngine(_tiny_serving_cfg(), max_batch=2, page_size=8,
                         max_seq=64, n_pages=1 + 8, kv_quant=kv_quant)


def build_train_entry() -> QuantEntry:
    """The dp×pp×mp sharded train step, reusing shardcheck's tracer (one
    trace serves both verifiers' entry registries)."""
    from .shardcheck import build_train_entry as _sc_train

    ep = _sc_train()
    return QuantEntry(name=ep.name, closed=ep.closed, source=ep.source,
                      invar_names=list(ep.invar_names))


def build_serving_fp32_entry() -> QuantEntry:
    _jax()

    eng = _tiny_engine(kv_quant=False)
    closed = eng.trace_unified()
    names = (["params" + n for n in _flatten_names(eng.params)]
             + ["k_pages", "v_pages", "tokens", "prev_out", "chain_mask",
                "chain_row", "ptable", "row_slot", "pos0", "n_valid",
                "temps", "topps", "seeds", "places"])
    return QuantEntry(name="serving_unified_fp32", closed=closed,
                      source="paddle_tpu/inference/serving.py",
                      invar_names=names)


def build_serving_mla_moe_entry() -> QuantEntry:
    """The unified step over latent (MLA) pages with routed experts
    (models/mla_moe.py), traced in bf16 so that TPL301 sees every
    sub-fp32 dot of the absorbed attention, the page write and the
    grouped expert products."""
    jax = _jax()
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.mla_moe import MlaMoeConfig, init_mla_moe_params

    cfg = MlaMoeConfig(
        vocab_size=128, hidden=32, n_layers=3, n_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        ffn_hidden=64, moe_hidden=16, n_routed_experts=8,
        experts_per_token=2, n_mtp=0, max_seq_len=64, held=(2, 4))
    # shapes only: nothing runs, so no weight is ever drawn
    params = jax.eval_shape(lambda k: init_mla_moe_params(cfg, k),
                            jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params=params, max_batch=2, page_size=8,
                        max_seq=64, n_pages=1 + 8)
    closed = eng.trace_unified()
    names = (["params" + n for n in _flatten_names(eng.params)]
             + ["k_pages", "v_pages", "tokens", "prev_out", "chain_mask",
                "chain_row", "ptable", "row_slot", "pos0", "n_valid",
                "temps", "topps", "seeds", "places"])
    return QuantEntry(name="serving_unified_mla_moe", closed=closed,
                      source="paddle_tpu/models/mla_moe.py",
                      invar_names=names)


def build_serving_int8_entry() -> QuantEntry:
    """The unified step over int8 pages: the page arrays are int8 invars
    paired with their scale-plane invars, the side planes that ride
    behind the step's fixed operands — the engine's allocator maintains
    the no-foreign-scale invariant (proven separately by the admit
    entries), so the planes enter *trusted*."""
    jax = _jax()
    import paddle_tpu  # noqa: F401

    eng = _tiny_engine(kv_quant=True)
    closed = eng.trace_unified()
    n = len(jax.tree_util.tree_leaves(eng.params))
    names = (["params" + s for s in _flatten_names(eng.params)]
             + ["k_pages", "v_pages", "tokens", "prev_out", "chain_mask",
                "chain_row", "ptable", "row_slot", "pos0", "n_valid",
                "temps", "topps", "seeds", "k_scales", "v_scales",
                "places"])
    return QuantEntry(name="serving_unified_int8kv", closed=closed,
                      source="paddle_tpu/models/llama.py",
                      invar_names=names,
                      scale_invars={n + 13, n + 14},
                      page_pairs={n: n + 13, n + 1: n + 14})


def build_wire_entries() -> list:
    """Disagg wire stage/commit over *int8* pages: pure byte movement —
    no scale plane travels on this path (the adoption commit ships
    scales separately), so the pages are anonymous quant values and the
    verifier proves no eqn dequantizes them en route."""
    jax = _jax()
    import numpy as np

    from paddle_tpu.inference.serving import (wire_gather_pages,
                                              wire_scatter_pages)

    eng = _tiny_engine(kv_quant=True)
    kp = eng.k_pages
    n_ship = 2
    pg = jax.ShapeDtypeStruct((n_ship,), np.int32)
    staged = jax.ShapeDtypeStruct(
        (kp.shape[0], n_ship) + kp.shape[2:], kp.dtype)
    gather = jax.make_jaxpr(wire_gather_pages)(
        jax.ShapeDtypeStruct(kp.shape, kp.dtype), pg)
    scatter = jax.make_jaxpr(wire_scatter_pages)(
        jax.ShapeDtypeStruct(kp.shape, kp.dtype), pg, staged)
    out = []
    for nm, closed, names in (
            ("wire_stage_int8", gather, ["k_pages", "page_ids"]),
            ("wire_commit_int8", scatter,
             ["k_pages", "page_ids", "staged"])):
        out.append(QuantEntry(
            name=nm, closed=closed,
            source="paddle_tpu/inference/serving.py", invar_names=names))
    return out


def build_allreduce_entry() -> QuantEntry:
    """``dist_allreduce_quant`` (int8-on-the-wire gradient sync) reusing
    shardcheck's dp2×pp2 trace.  Every property the docstring promises
    is a rule here: both quantize phases divide clamped scales (TPL304),
    the fp32 dequant-accumulate keeps int8 out of the reduction
    (TPL301/TPL305), and each chunk dequantizes against its own absmax
    event (TPL303)."""
    from .shardcheck import build_quant_entry as _sc_quant

    ep = _sc_quant()
    return QuantEntry(name=ep.name, closed=ep.closed, source=ep.source,
                      invar_names=["grads"])


def build_quant_matmul_entry() -> QuantEntry:
    """The weight-only int8 decode matmul's XLA arm (M=4 fails the MXU
    gate, so the trace is the fallback — the kernel arm is pinned by its
    ACCUM_DTYPE declaration): epilogue-dequant means the dot output
    carries raw provenance until the scale row-multiply lands."""
    jax = _jax()
    import paddle_tpu  # noqa: F401
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.quant_matmul import quant_matmul

    x = jax.ShapeDtypeStruct((4, 128), jnp.bfloat16)
    wq = jax.ShapeDtypeStruct((128, 128), jnp.int8)
    sc = jax.ShapeDtypeStruct((128,), jnp.float32)
    closed = jax.make_jaxpr(quant_matmul)(x, wq, sc)
    return QuantEntry(name="quant_matmul_decode", closed=closed,
                      source="paddle_tpu/ops/pallas/quant_matmul.py",
                      invar_names=["x", "wq", "scale"],
                      scale_invars={2}, page_pairs={1: 2})


def build_admit_entry(zero_scale_on_alloc: bool = True) -> QuantEntry:
    """The KV-admit first-write program, with the scale plane marked
    *foreign* (it may hold a prior tenant's absmax — exactly the state
    ``_alloc_pages`` hands ``kv_admit_first_write``).

    With ``zero_scale_on_alloc=True`` (shipped): the kv_scale_reset
    scatter clears the foreign bit before the running-absmax update, so
    the quantize divide is clean.  With ``False``: the PR 8 *pre-fix*
    program — the prior tenant's absmax leaks through scatter-max into
    the quantize scale and TPL303 fires, exactly once, at the
    quantize_to_scale divide."""
    jax = _jax()
    import functools

    import paddle_tpu  # noqa: F401
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import kv_admit_first_write

    n_pages, n_kv, bs, d, n_write = 6, 2, 8, 16, 2
    pages = jax.ShapeDtypeStruct((n_pages, n_kv, bs, d), jnp.int8)
    scales = jax.ShapeDtypeStruct((n_pages, n_kv), jnp.float32)
    pg = jax.ShapeDtypeStruct((n_write,), jnp.int32)
    toks = jax.ShapeDtypeStruct((n_write, n_kv, bs, d), jnp.float32)
    fn = functools.partial(kv_admit_first_write,
                           _zero_scale_on_alloc=zero_scale_on_alloc)
    closed = jax.make_jaxpr(fn)(pages, scales, pg, toks)
    name = ("serving_admit_quant" if zero_scale_on_alloc
            else "serving_admit_quant_noreset")
    return QuantEntry(name=name, closed=closed,
                      source="paddle_tpu/inference/serving.py",
                      invar_names=["pages", "scales", "page_ids", "tokens"],
                      scale_invars={1}, foreign_scale_invars={1},
                      page_pairs={0: 1})


def build_entries(names=None) -> list:
    """All registered entry programs (optionally filtered by name)."""
    entries = [build_train_entry(),
               build_serving_fp32_entry(),
               build_serving_int8_entry(),
               build_serving_mla_moe_entry()]
    entries += build_wire_entries()
    entries.append(build_allreduce_entry())
    entries.append(build_quant_matmul_entry())
    entries.append(build_admit_entry(zero_scale_on_alloc=True))
    if names is not None:
        entries = [e for e in entries if e.name in set(names)]
    return entries


# ---------------------------------------------------------------------------
# the abstract interpreter
# ---------------------------------------------------------------------------

_STRUCTURAL = {
    "reshape", "broadcast_in_dim", "squeeze", "expand_dims", "rev",
    "pad", "sort", "copy", "stop_gradient", "device_put",
    "optimization_barrier", "reduce_precision", "sharding_constraint",
    "transpose",
}

_HIGHER_ORDER = {
    "jit", "scan", "while", "cond", "remat2", "custom_jvp_call",
    "custom_vjp_call", "shard_map",
}

_SCATTER_SET = {"scatter", "scatter-add", "scatter_add",
                "dynamic_update_slice"}
_SCATTER_MAX = {"scatter-max", "scatter_max", "scatter-min", "scatter_min"}


def _is_float(fmt) -> bool:
    return fmt is not None and (fmt.startswith("float")
                                or fmt == "bfloat16") and fmt != "float0"


def _is_sub_f32(fmt) -> bool:
    return fmt is not None and (fmt in SUB_F32 or fmt.startswith("float8"))


class QuantInterp:
    """Propagates QVals through one entry program and collects rule
    events.  One instance per entry; findings accumulate on
    ``self.findings`` (deduplicated by (rule, path, line) so the scan
    2-sweep fixpoint cannot double-report) and the rendered-value
    histogram (for the golden format-environment test) on
    ``self.all_fmts``."""

    def __init__(self, entry: QuantEntry):
        self.entry = entry
        self.findings: list[Finding] = []
        self.all_fmts: dict[str, int] = {}
        self.in_vals: list[QVal] = []
        self.out_vals: list[QVal] = []
        self._seen: set = set()
        self._nev = 0

    # -- bookkeeping --------------------------------------------------------

    def _event(self) -> int:
        e = self._nev
        self._nev += 1
        return e

    def _finding(self, rule, eqn, message, severity="error", key=None):
        path, line = _eqn_location(eqn) if eqn is not None else (None, 0)
        k = key if key is not None else (rule, path, line)
        if k in self._seen:
            return
        self._seen.add(k)
        self.findings.append(Finding(
            rule=rule, name=QUANTCHECK_RULES[rule], severity=severity,
            path=path or self.entry.source, line=line or 1, col=0,
            message=f"[entry {self.entry.name}] {message}"))

    def _record(self, q: QVal):
        s = _qval_str(q)
        self.all_fmts[s] = self.all_fmts.get(s, 0) + 1

    @staticmethod
    def _read(env, atom) -> QVal:
        if type(atom).__name__ == "Literal":
            lit = None
            try:
                v = atom.val
                if getattr(v, "shape", ()) in ((), (1,)):
                    lit = float(v)
            except Exception:
                lit = None
            return QVal(fmt=_fmt(atom.aval), lit=lit)
        return env.get(atom, QVal(fmt=_fmt(atom.aval)))

    # -- driver -------------------------------------------------------------

    def run(self):
        jaxpr = self.entry.closed.jaxpr
        env = {}
        for cv in jaxpr.constvars:
            env[cv] = QVal(fmt=_fmt(cv.aval))
        # first pass: scale planes get their root events...
        pair_event = {}
        for i, v in enumerate(jaxpr.invars):
            fmt = _fmt(v.aval)
            if i in self.entry.scale_invars:
                e = self._event()
                pair_event[i] = e
                env[v] = QVal(fmt=fmt, kind="scale", origin=e,
                              anc=frozenset({e}),
                              foreign=i in self.entry.foreign_scale_invars)
        # ...then int8 invars pair with them (or get anonymous events)
        for i, v in enumerate(jaxpr.invars):
            if v in env:
                continue
            fmt = _fmt(v.aval)
            if fmt in ("int8", "uint8"):
                if i in self.entry.page_pairs:
                    e = pair_event[self.entry.page_pairs[i]]
                else:
                    e = self._event()
                env[v] = QVal(fmt=fmt, kind="quant", origin=e,
                              anc=frozenset({e}))
            else:
                env[v] = QVal(fmt=fmt)
            if fmt == "float64":
                nm = (self.entry.invar_names[i]
                      if i < len(self.entry.invar_names) else f"#{i}")
                self._finding(
                    "TPL302", None,
                    f"entry operand '{nm}' is float64; this repo runs "
                    "x64-off — an f64 operand doubles HBM traffic and "
                    "forces every consumer to upcast silently",
                    key=("TPL302", "invar", i))
        self.in_vals = [env[v] for v in jaxpr.invars]
        for q in self.in_vals:
            self._record(q)
        self._interp(jaxpr, env)
        self.out_vals = [self._read(env, v) for v in jaxpr.outvars]
        return self

    # -- interpretation -----------------------------------------------------

    def _interp(self, jaxpr, env):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [self._read(env, a) for a in eqn.invars]
            self._check_formats(eqn)
            self._check_upcast(eqn)
            if name in _HIGHER_ORDER:
                if name == "scan":
                    outs = self._do_scan(eqn, ins)
                else:
                    outs = self._do_body(eqn, ins)
            else:
                outs = self._transfer(eqn, ins)
            for v, q in zip(eqn.outvars, outs):
                if type(v).__name__ == "DropVar":
                    continue
                env[v] = q
                self._record(q)

    def _run_body(self, jaxpr, in_states):
        env = {}
        for cv in jaxpr.constvars:
            env[cv] = QVal(fmt=_fmt(cv.aval))
        for v, st in zip(jaxpr.invars, in_states):
            env[v] = st
        self._interp(jaxpr, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _do_scan(self, eqn, ins):
        p = eqn.params
        inner = p["jaxpr"].jaxpr
        nc, ncarry = p["num_consts"], p["num_carry"]
        const_in = ins[:nc]
        carry = list(ins[nc:nc + ncarry])
        xs = ins[nc + ncarry:]
        outs = None
        for _ in range(2):                     # carry fixpoint (2 sweeps)
            outs = self._run_body(inner, const_in + carry + xs)
            carry = [_qjoin(a, b) for a, b in zip(carry, outs[:ncarry])]
        return carry + outs[ncarry:]

    def _do_body(self, eqn, ins):
        """Generic higher-order handler (jit/while/cond/remat/custom-
        vjp/shard_map): run every body with the trailing-aligned operand
        states and join the results — QVals are shape-agnostic, so no
        per-dim bookkeeping is needed."""
        bodies = _inner_closed(eqn)
        if not bodies:
            return self._transfer(eqn, ins)
        results = None
        for inner, _consts in bodies:
            states = list(ins)
            if eqn.primitive.name == "cond":
                states = states[1:]            # predicate operand
            n = len(inner.invars)
            if len(states) > n:
                states = states[-n:]
            while len(states) < n:
                states.insert(0, QVal())
            outs = self._run_body(inner, states)
            if results is None:
                results = outs
            else:
                results = [_qjoin(a, b) for a, b in zip(results, outs)]
        n_out = len(eqn.outvars)
        results = (results or [])[:n_out]
        while len(results) < n_out:
            results.append(QVal())
        return [replace(q, fmt=_fmt(v.aval))
                for q, v in zip(results, eqn.outvars)]

    # -- per-eqn rule checks ------------------------------------------------

    def _check_formats(self, eqn):
        name = eqn.primitive.name
        for a in list(eqn.invars) + list(eqn.outvars):
            if type(a).__name__ == "DropVar":
                continue
            f = _fmt(a.aval)
            if not _known_fmt(f):
                self._finding(
                    "TPL300", eqn,
                    f"unknown storage format '{f}' in '{name}'; declare "
                    "it in quantcheck.KNOWN_FORMATS and add it to the "
                    "FORMAT_LEGALITY rows of every op class that may "
                    "carry it (this is how fp8 lands)",
                    key=("TPL300", "fmt", f))
        if name in ("dot_general",):
            opclass = "dot"
        elif name == "conv_general_dilated":
            opclass = "conv"
        elif name in COLLECTIVE_PRIMS:
            opclass = "collective"
        elif name in _SCATTER_SET or name in _SCATTER_MAX:
            opclass = "scatter"
        elif name in ("gather", "dynamic_slice"):
            opclass = "gather"
        else:
            return
        legal = FORMAT_LEGALITY.get((BACKEND, opclass))
        if not legal:
            self._finding(
                "TPL300", eqn,
                f"no FORMAT_LEGALITY row for backend '{BACKEND}' op "
                f"class '{opclass}' — declare one",
                key=("TPL300", "row", opclass))
            return
        for a in eqn.invars:
            f = _fmt(a.aval)
            if f is not None and _known_fmt(f) and f not in legal:
                self._finding(
                    "TPL300", eqn,
                    f"format '{f}' is not declared legal for op class "
                    f"'{opclass}' on backend '{BACKEND}' (legal: "
                    f"{sorted(legal)}); extend the FORMAT_LEGALITY row "
                    "or keep the format off this path",
                    key=("TPL300", opclass, f))

    def _check_upcast(self, eqn):
        outs = [v for v in eqn.outvars if type(v).__name__ != "DropVar"]
        if not any(_fmt(v.aval) == "float64" for v in outs):
            return
        if any(_fmt(a.aval) == "float64" for a in eqn.invars):
            return
        self._finding(
            "TPL302", eqn,
            f"'{eqn.primitive.name}' produces float64 from non-f64 "
            "inputs — a silent x64 upcast point; this repo runs x64-off "
            "(check for python-float promotion or an explicit "
            "astype(float64))")

    def _check_dot_accum(self, eqn, ins):
        sub = [q.fmt for q in ins[:2] if _is_sub_f32(q.fmt)]
        if not sub:
            return
        out_fmt = _fmt(eqn.outvars[0].aval)
        if out_fmt in _ACCUM_OK:
            return
        self._finding(
            "TPL301", eqn,
            f"'{eqn.primitive.name}' contracts sub-fp32 operand(s) "
            f"{sorted(set(sub))} into a {out_fmt} result — accumulation "
            "happens below fp32; set "
            "preferred_element_type=jnp.float32 on the dot (both the "
            "kernel arm and this XLA arm must accumulate fp32)")

    # -- the transfer function ----------------------------------------------

    def _transfer(self, eqn, ins):
        name = eqn.primitive.name
        outs = eqn.outvars

        def mk(q: QVal):
            return [replace(q, fmt=_fmt(v.aval)) for v in outs]

        a = ins[0] if ins else QVal()
        b = ins[1] if len(ins) > 1 else None

        if name == "abs":
            return mk(replace(a, kind="abs") if a.kind == "data" else a)
        if name in ("reduce_max", "reduce_min", "reduce_sum",
                    "reduce_prod", "cumsum", "cummax", "cummin",
                    "cumprod", "cumlogsumexp"):
            return mk(a)
        if name == "max" and b is not None:
            for x, y in ((a, b), (b, a)):
                if (y.lit is not None and 0.0 < y.lit <= _CLAMP_LIT_MAX
                        and x.kind in ("scale", "abs")):
                    return mk(replace(x, clamped=True))
            return mk(_qjoin(a, b))
        if name == "div" and b is not None:
            return mk(self._div(eqn, a, b))
        if name == "mul" and b is not None:
            return mk(self._mul(eqn, a, b))
        if name in ("round", "nextafter", "sign"):
            return mk(a)
        if name == "clamp":
            return mk(ins[1] if len(ins) > 2 else a)
        if name == "convert_element_type":
            return self._convert(eqn, a)
        if name in ("dot_general", "conv_general_dilated"):
            self._check_dot_accum(eqn, ins)
            prov = [q for q in ins[:2] if q.kind in ("quant", "raw")]
            if prov:
                anc = frozenset().union(*[q.anc for q in prov])
                return mk(QVal(kind="raw", origin=prov[0].origin, anc=anc,
                               foreign=any(q.foreign for q in prov)))
            return mk(QVal())
        if name in _SCATTER_MAX:
            u = ins[2] if len(ins) > 2 else (b or a)
            if a.kind == "scale" or u.kind == "scale":
                # running-absmax update: a fresh scale event whose
                # lineage unions the plane's and the update's — foreign
                # propagates (scatter-max cannot launder a leaked scale)
                e = self._event()
                return mk(QVal(kind="scale", origin=e,
                               anc=a.anc | u.anc | {e},
                               foreign=a.foreign or u.foreign,
                               clamped=a.clamped and u.clamped))
            return mk(_qjoin(a, u))
        if name in _SCATTER_SET:
            u = ins[1] if name == "dynamic_update_slice" else (
                ins[2] if len(ins) > 2 else (b or a))
            if a.kind == "scale" and u.lit == 0.0:
                # kv_scale_reset: overwriting plane entries with 0.0
                # severs provenance AND clears the foreign bit — the
                # prior tenant's absmax is gone
                e = self._event()
                return mk(QVal(kind="scale", origin=e, anc=frozenset({e}),
                               clamped=a.clamped))
            if a.kind == "quant" or u.kind == "quant":
                qs = [q for q in (a, u) if q.kind == "quant"]
                origin = u.origin if u.kind == "quant" else a.origin
                return mk(QVal(kind="quant", origin=origin,
                               anc=a.anc | u.anc,
                               foreign=any(q.foreign for q in qs)))
            if a.kind == "scale" or u.kind == "scale":
                return mk(replace(_qjoin(a, u), kind="scale"))
            return mk(_qjoin(a, u))
        if name in ("gather", "take", "dynamic_slice", "slice",
                    "take_along_axis", "argmax", "argmin"):
            return mk(replace(a, lit=None))
        if name in _STRUCTURAL:
            return mk(a)
        if name in ("concatenate", "select_n"):
            parts = ins[1:] if name == "select_n" and len(ins) > 1 else ins
            q = parts[0]
            for other in parts[1:]:
                q = _qjoin(q, other)
            return mk(q)
        if name in COLLECTIVE_PRIMS:
            return mk(a)
        # default: elementwise-style priority join
        q = a
        for other in ins[1:]:
            q = _qjoin(q, other)
        return mk(replace(q, lit=None))

    def _div(self, eqn, a: QVal, b: QVal) -> QVal:
        if a.kind == "scale" and b.kind == "scale":
            # rescale_int8's ratio = old / max(new, EPS): remembers the
            # OLD lineage (rfrom) — the bytes it may legally rescale
            if not b.clamped:
                self._tpl304(eqn, b)
            return QVal(kind="ratio", origin=b.origin, anc=a.anc | b.anc,
                        foreign=a.foreign or b.foreign, rfrom=a.anc)
        if b.kind == "scale":
            if not b.clamped:
                self._tpl304(eqn, b)
            if a.kind in ("quant", "raw"):
                self._finding(
                    "TPL305", eqn,
                    "dividing already-quantized bytes by a scale "
                    "re-quantizes them without an intervening "
                    "dequantize/rescale — each pass multiplies the "
                    "rounding error; dequantize first (or use "
                    "rescale_int8, whose ratio multiply is exact for "
                    "unchanged scales)")
            if b.foreign:
                self._finding(
                    "TPL303", eqn,
                    "quantizing against a scale that may still hold a "
                    "prior tenant's absmax (the scale plane was not "
                    "reset on page alloc) — a leaked larger scale "
                    "silently crushes this tenant's resolution; reset "
                    "the plane first (kv_scale_reset / "
                    "_zero_scale_on_alloc)")
            return QVal(kind="qpend", origin=b.origin, anc=a.anc | b.anc,
                        foreign=b.foreign)
        if a.kind == "abs" and b.lit is not None and b.lit == 127.0:
            # |x|max / 127: a fresh scale is born here
            e = self._event()
            return QVal(kind="scale", origin=e, anc=a.anc | {e},
                        foreign=a.foreign)
        return replace(_qjoin(a, b), lit=None)

    def _mul(self, eqn, a: QVal, b: QVal) -> QVal:
        for x, y in ((a, b), (b, a)):
            if x.kind in ("raw", "qpend") and y.kind == "scale":
                # dequant: bytes * scale — lineages must intersect
                if y.foreign or (x.anc and y.anc and not (x.anc & y.anc)):
                    self._finding(
                        "TPL303", eqn,
                        "dequantizing bytes against a scale from a "
                        f"different event lineage (bytes {sorted(x.anc)}"
                        f" vs scale {sorted(y.anc)}"
                        f"{', foreign plane' if y.foreign else ''}) — "
                        "the bytes were not produced under this scale; "
                        "thread the scale from the same "
                        "quantize/rescale/kv_scale_update event")
                return QVal()
            if x.kind == "raw" and y.kind == "ratio":
                # rescale: the ratio's OLD lineage must cover the bytes
                if x.anc and y.rfrom and not (x.anc & y.rfrom):
                    self._finding(
                        "TPL303", eqn,
                        "rescaling bytes with a ratio whose old-scale "
                        f"lineage {sorted(y.rfrom)} does not cover the "
                        f"bytes' lineage {sorted(x.anc)} — the ratio "
                        "was computed from a different page/chunk's "
                        "scale history")
                return QVal(kind="qpend", origin=y.origin,
                            anc=x.anc | y.anc,
                            foreign=x.foreign or y.foreign)
        return replace(_qjoin(a, b), lit=None)

    def _convert(self, eqn, a: QVal):
        outs = eqn.outvars
        out_fmt = _fmt(outs[0].aval)
        q = a
        if a.kind == "qpend" and out_fmt in ("int8", "uint8"):
            q = replace(a, kind="quant", lit=None)
        elif a.kind == "quant" and _is_float(out_fmt):
            # the raw float view of int8 bytes: provenance sticks until
            # a scale multiply lands (dequant) — TPL305 guards the
            # re-quantize path, TPL303 the wrong-scale path
            q = replace(a, kind="raw", lit=None)
        elif (a.kind == "data" and out_fmt in ("int8", "uint8")
              and _is_float(a.fmt)):
            # float -> int8 with no scale divide in sight: an anonymous
            # quantization event (legal, but its scale is untracked)
            e = self._event()
            q = QVal(kind="quant", origin=e, anc=frozenset({e}))
        return [replace(q, fmt=_fmt(v.aval)) for v in outs]

    def _tpl304(self, eqn, b: QVal):
        self._finding(
            "TPL304", eqn,
            "divide by a scale that is not dominated by a "
            "maximum(., SCALE_EPS) clamp (ops/quant.py::SCALE_EPS) — a "
            "zero row yields a 0.0 scale and this divide mints "
            "NaN/inf; clamp the scale first")


# ---------------------------------------------------------------------------
# declaration-side rules (TPL301 outside the traces)
# ---------------------------------------------------------------------------

def site_accum_findings(entry_name: str, sites) -> list:
    """TPL301 over the fusion catalog: every *applied* Site must declare
    an fp32-class ``accum_dtype`` — the per-site analog of the kernel
    module declarations (a fused replacement that accumulated below
    fp32 would pass the trace check, which only sees the unfused XLA
    arm)."""
    out = []
    for s in sites:
        if not getattr(s, "applied", False):
            continue
        acc = getattr(s, "accum_dtype", "float32")
        if acc not in ("float32", "float64"):
            out.append(Finding(
                rule="TPL301", name=QUANTCHECK_RULES["TPL301"],
                severity="error", path="paddle_tpu/compiler/catalog.py",
                line=1, col=0,
                message=(f"[entry {entry_name}] applied fusion site "
                         f"'{getattr(s, 'template', '?')}' declares "
                         f"accum_dtype={acc!r} — fused kernels must "
                         "accumulate in fp32 like the XLA arms they "
                         "replace")))
    return out


def kernel_decl_findings() -> tuple:
    """(findings, declarations) for every Pallas kernel module's
    ``ACCUM_DTYPE``.  A module missing the declaration, or declaring a
    sub-fp32 accumulator, is TPL301: the kernel arms never appear in
    CPU traces, so the declaration is the only statically checkable
    handle on their accumulation dtype."""
    import importlib

    out, decls = [], {}
    for mod in PALLAS_KERNEL_MODULES:
        path = mod.replace(".", "/") + ".py"
        try:
            m = importlib.import_module(mod)
            acc = getattr(m, "ACCUM_DTYPE", None)
        except Exception as e:  # pragma: no cover - import errors are
            # environment problems, not precision findings
            out.append(Finding(
                rule="TPL301", name=QUANTCHECK_RULES["TPL301"],
                severity="warning", path=path, line=1, col=0,
                message=(f"[entry kernel_decls] could not import {mod}: "
                         f"{type(e).__name__}: {e}")))
            decls[mod] = None
            continue
        decls[mod] = acc
        if acc not in ("float32", "float64"):
            out.append(Finding(
                rule="TPL301", name=QUANTCHECK_RULES["TPL301"],
                severity="error", path=path, line=1, col=0,
                message=(f"[entry kernel_decls] kernel module {mod} "
                         f"declares ACCUM_DTYPE={acc!r} (expected "
                         "'float32'/'float64'); every Pallas kernel "
                         "accumulates in an fp32 scratch — declare it "
                         "so the verifier can hold both arms to the "
                         "same contract")))
    return out, decls


# ---------------------------------------------------------------------------
# report / baseline
# ---------------------------------------------------------------------------

def check_entry(entry: QuantEntry) -> tuple:
    """(interp, findings) for one entry: lattice propagation plus the
    per-entry fusion-site accumulation check."""
    interp = QuantInterp(entry).run()
    findings = list(interp.findings)
    try:
        from paddle_tpu.compiler.fusion_pass import plan_closed

        plan = plan_closed(entry.closed)
        findings += site_accum_findings(entry.name, plan.walk())
    except Exception as e:  # pragma: no cover - planner bugs must not
        # kill the verifier
        findings.append(Finding(
            rule="TPL301", name=QUANTCHECK_RULES["TPL301"],
            severity="warning", path=entry.source, line=1, col=0,
            message=f"[entry {entry.name}] fusion planning failed: "
                    f"{type(e).__name__}: {e}"))
    return interp, findings


def format_environment(entry: QuantEntry) -> dict:
    """Deterministic summary of the derived per-var format environment —
    the golden test pins this for the int8 serving step."""
    interp = QuantInterp(entry).run()
    invars = {}
    for name, q in zip(entry.invar_names, interp.in_vals):
        invars[name] = _qval_str(q)
    return {
        "entry": entry.name,
        "invars": invars,
        "outvars": [_qval_str(q) for q in interp.out_vals],
        "format_histogram": dict(sorted(interp.all_fmts.items())),
    }


def _entry_digest(interp: QuantInterp) -> str:
    blob = json.dumps(
        {"fmts": dict(sorted(interp.all_fmts.items())),
         "outs": [_qval_str(q) for q in interp.out_vals]},
        sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def build_report(names=None) -> dict:
    """Run every registered entry plus the declaration-side checks;
    returns findings + the baseline payload."""
    entries = build_entries(names)
    findings: list[Finding] = []
    payload: dict = {"version": 1, "entries": {}}
    for entry in entries:
        interp, fs = check_entry(entry)
        findings += fs
        counts: dict = {}
        for f in fs:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        payload["entries"][entry.name] = {
            "source": entry.source,
            "n_eqns": _count_eqns(entry.closed.jaxpr),
            "formats": sorted(set(interp.all_fmts)),
            "findings": dict(sorted(counts.items())),
            "fmt_digest": _entry_digest(interp),
        }
    kfs, decls = kernel_decl_findings()
    findings += kfs
    payload["kernel_accum"] = decls
    payload["explained"] = sorted([k, r] for (k, r) in EXPLAINED)
    return {"findings": findings, "baseline": payload}


def regression_report() -> dict:
    """The TPL303 regression harness: the *pre-fix* admit program
    (``_zero_scale_on_alloc=False``) must produce exactly one TPL303 —
    the prior tenant's absmax leaking into the reused page's quantize —
    and the shipped program exactly zero.  ``ok`` is the CI gate's
    pass/fail."""
    out: dict = {}
    for label, flag in (("regression", False), ("shipped", True)):
        entry = build_admit_entry(zero_scale_on_alloc=flag)
        interp = QuantInterp(entry).run()
        t303 = [f for f in interp.findings if f.rule == "TPL303"]
        out[label] = {
            "entry": entry.name,
            "tpl303": len(t303),
            "messages": [f"{f.path}:{f.line} {f.message}" for f in t303],
        }
    out["ok"] = (out["regression"]["tpl303"] == 1
                 and out["shipped"]["tpl303"] == 0)
    return out


def unexplained_findings(findings: list) -> list:
    return [f for f in findings
            if (_finding_entry(f), f.rule) not in EXPLAINED]


def stale_explanations(findings: list) -> list:
    """EXPLAINED keys with no matching finding — stale rationales are
    drift, exactly like a suppression on dead code."""
    seen = {(_finding_entry(f), f.rule) for f in findings}
    return sorted(f"stale explanation: entry '{k}' rule {r} no longer "
                  "fires — prune it from quantcheck.EXPLAINED"
                  for (k, r) in EXPLAINED if (k, r) not in seen)


def diff_baselines(current: dict, base: dict) -> list:
    """Human-readable drift lines, shardcheck.diff_baselines-style."""
    out = []
    cur_e = current.get("entries", {})
    base_e = base.get("entries", {})
    for name in sorted(set(cur_e) | set(base_e)):
        a, b = cur_e.get(name), base_e.get(name)
        if a is None:
            out.append(f"entry '{name}': removed (in baseline only)")
            continue
        if b is None:
            out.append(f"entry '{name}': new (not in baseline)")
            continue
        for key in ("source", "n_eqns", "formats", "findings",
                    "fmt_digest"):
            if a.get(key) != b.get(key):
                out.append(f"entry '{name}': {key} drifted: "
                           f"{b.get(key)!r} -> {a.get(key)!r}")
    if current.get("kernel_accum") != base.get("kernel_accum"):
        out.append("kernel_accum drifted: "
                   f"{base.get('kernel_accum')!r} -> "
                   f"{current.get('kernel_accum')!r}")
    if current.get("explained") != base.get("explained"):
        out.append("explained set drifted: "
                   f"{base.get('explained')!r} -> "
                   f"{current.get('explained')!r}")
    return out
