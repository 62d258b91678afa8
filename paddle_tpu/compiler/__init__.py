"""paddle_tpu.compiler — mini-CINN: jaxpr-level fusion discovery.

Instead of hand-wiring fused Pallas entries at call sites (the PR 6
approach this package replaces), models keep their plain unfused
compositions and a jitted step is wrapped in :func:`auto_fuse`.  At
trace time the wrapper:

1. traces the wrapped function once with ``jax.make_jaxpr``,
2. plans fusions against the template catalog (catalog.py) with the
   validated rewrite pass (fusion_pass.py),
3. looks the program up in the autotune v2 cache by its stable jaxpr
   hash — a warm cache adopts the committed per-kernel configs so the
   re-trace sweeps nothing,
4. re-traces through the plan, emitting fused kernel calls in place of
   the recognized chains, and
5. commits (program hash -> fusion decisions + every autotune entry the
   trace resolved) back to the cache for the next process.

``FLAGS_use_auto_fusion=0`` bypasses everything: the wrapper calls the
original function directly, so the traced jaxpr is bit-identical to the
unfused composition (pinned by tests/test_compiler_fusion.py).

The wrapper composes with jit/grad/shard_map because the rewrite runs
*inside* the enclosing trace: unmatched equations re-bind unchanged and
fused entries are ordinary custom_vjp calls.  Arguments must be
positional pytrees of arrays; close static configuration over with
``functools.partial`` before wrapping.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
from jax import tree_util

from .. import obs as _obs
from .fusion_pass import eval_fused, plan_closed, program_hash

__all__ = ["auto_fuse", "fused_call", "discover", "last_report",
           "FusionReport"]


@dataclasses.dataclass
class FusionReport:
    """What one auto_fuse trace discovered and did."""
    program_hash: str
    n_sites: int            # chains the catalog recognized (applied or not)
    n_applied: int          # chains actually rewritten to fused kernels
    sites: list             # Plan.summary() rows
    program_cache_hit: bool  # plan + configs replayed from the v2 cache
    errors: list            # matcher exceptions (fusion lost, model intact)


_LAST_REPORT: FusionReport | None = None


def last_report() -> FusionReport | None:
    """Report from the most recent auto_fuse/discover trace, or None."""
    return _LAST_REPORT


def _flag(name: str, default):
    from ..core.flags import GLOBAL_FLAGS

    return GLOBAL_FLAGS.get(name) if GLOBAL_FLAGS.has(name) else default


def _trace_key(flat, in_tree):
    """Plan-cache key: argument structure + avals + every flag that can
    change what the catalog matches (the jit-cache caveat from
    flash_attention.py applies here too: already-compiled programs do
    not see later flag flips)."""
    return (in_tree,
            tuple((tuple(np.shape(x)), str(jax.numpy.result_type(x)))
                  for x in flat),
            bool(_flag("use_fused_norm_epilogue", True)),
            bool(_flag("use_fused_rope_attention", True)),
            bool(_flag("use_fused_bias_act", True)))


def _plan_and_trace(fn, flat, in_tree):
    def flat_fn(*xs):
        return fn(*tree_util.tree_unflatten(in_tree, list(xs)))

    closed, out_shape = jax.make_jaxpr(flat_fn, return_shape=True)(*flat)
    out_tree = tree_util.tree_structure(out_shape)
    plan = plan_closed(closed)
    return closed, out_tree, plan, program_hash(closed)


def _report(plan, phash, hit) -> FusionReport:
    sites = list(plan.walk())
    return FusionReport(program_hash=phash,
                        n_sites=len(sites),
                        n_applied=sum(1 for s in sites if s.applied),
                        sites=plan.summary(),
                        program_cache_hit=bool(hit),
                        errors=list(plan.walk_errors()))


def auto_fuse(fn):
    """Wrap a model apply / train step for automatic fusion.

    The plan is computed once per (argument avals, catalog flags) and
    cached on the wrapper; subsequent calls replay it.  With
    ``use_auto_fusion=0`` the wrapper is a transparent passthrough."""
    cache: dict = {}

    @functools.wraps(fn)
    def wrapped(*args):
        global _LAST_REPORT
        from ..core.flags import GLOBAL_FLAGS
        if not bool(GLOBAL_FLAGS.get("use_auto_fusion")
                    if GLOBAL_FLAGS.has("use_auto_fusion") else True):
            return fn(*args)
        from ..ops.pallas.autotune import GLOBAL_AUTOTUNE as reg
        from .catalog import catalog_source

        flat, in_tree = tree_util.tree_flatten(tuple(args))
        key = _trace_key(flat, in_tree)
        state = cache.get(key)
        planned = state is None
        if planned:
            closed, out_tree, plan, phash = _plan_and_trace(
                fn, flat, in_tree)
            state = {"closed": closed, "out_tree": out_tree, "plan": plan,
                     "phash": phash, "warm": None}
            cache[key] = state
        plan, phash = state["plan"], state["phash"]
        src = catalog_source()
        if state["warm"] is None:
            # adopt before evaluating so every tuned() call inside the
            # fused trace hits the committed configs without sweeping
            state["warm"] = (not plan.empty()
                             and reg.adopt_program(phash, src))
        _LAST_REPORT = _report(plan, phash, state["warm"])
        if planned and _LAST_REPORT.n_sites:
            # once per plan computed, never on replay: the sites the
            # catalog found in this program and how many it rewrote. A
            # program in which it finds none is passed through unrecorded
            # (the train step's outer wrapper: the model's own fused_call
            # inside it has rewritten every site already).
            _obs.instant("compiler.plan", n_sites=_LAST_REPORT.n_sites,
                         n_applied=_LAST_REPORT.n_applied, phash=phash,
                         warm=_LAST_REPORT.program_cache_hit)
        if plan.empty():
            return fn(*args)
        capturing = reg.begin_capture()
        try:
            out_flat = eval_fused(state["closed"], plan, flat)
        finally:
            entries = reg.end_capture() if capturing else {}
        if capturing and not state["warm"]:
            reg.program_commit(phash, plan.summary(), entries, src)
            state["warm"] = True  # committed: later identical calls replay
        return tree_util.tree_unflatten(state["out_tree"], out_flat)

    wrapped.__wrapped__ = fn
    return wrapped


_WRAPPERS: dict = {}


def fused_call(key, fn, *args):
    """:func:`auto_fuse` with a process-level wrapper cache keyed by
    static configuration — for call sites (model applies) that rebuild
    their ``functools.partial`` on every invocation and would otherwise
    re-plan each call."""
    w = _WRAPPERS.get(key)
    if w is None:
        w = _WRAPPERS[key] = auto_fuse(fn)
    return w(*args)


def discover(fn, *args):
    """Trace and plan only — the :class:`FusionReport` auto_fuse would
    act on for these arguments, without evaluating anything.  Drives
    tools/fusion_smoke.py and the bench fusion keys."""
    global _LAST_REPORT
    flat, in_tree = tree_util.tree_flatten(tuple(args))
    _closed, _out_tree, plan, phash = _plan_and_trace(fn, flat, in_tree)
    _LAST_REPORT = _report(plan, phash, False)
    return _LAST_REPORT
