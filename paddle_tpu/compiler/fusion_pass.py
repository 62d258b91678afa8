"""Jaxpr-level fusion pass: plan, validate, and re-trace with fused calls.

The mini-CINN core (ROADMAP item 3).  ``plan_closed`` walks a traced
program's jaxpr — recursing through scan bodies, remat wrappers, and
annotation-free nested jit calls — and asks every catalog template
(catalog.py) whether it recognizes a fusable chain anchored at each
equation.  Matches become :class:`Site` records: the set of equations
the fused kernel replaces, the jaxpr variables it must bind, and a
``build`` callable that emits the fused Pallas entry.  A generic
validator then proves each site safe *independently of how the matcher
was written*: every replaced equation's outputs are either re-bound by
the fused call or consumed exclusively inside the site, and every
re-bound output's downstream consumers run after the site executes.  A
matcher bug can therefore cost a fusion opportunity, never correctness.

``eval_fused`` re-traces the program from the planned jaxpr: unmatched
equations re-bind through ``primitive.get_bind_params`` (the
eval_jaxpr idiom — custom_vjp calls, nested jit, sharding constraints all
pass through untouched, so gradients and partitioning survive), matched
chains are skipped, and each site's trigger equation emits the fused
kernel call instead.  Because this happens *inside* the enclosing
trace, the surrounding jit simply sees a jaxpr with fused calls — grad,
vmap and sharding compose as if the model had been hand-wired.

Scan/remat/jit equations whose bodies contain matches are re-wrapped
(``lax.scan`` / ``jax.checkpoint`` with the original static params /
inlined) around a fused evaluation of their body jaxpr; bodies with no
matches re-bind untouched.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Any, Callable, Sequence

import jax
from jax import lax
from jax._src.core import DropVar
from jax.extend.core import Literal, Var

_TRANSPARENT = ("broadcast_in_dim", "reshape", "convert_element_type")


# ---------------------------------------------------------------------------
# graph view
# ---------------------------------------------------------------------------

class Graph:
    """Def/use index over one (open) jaxpr, with the walk helpers the
    catalog matchers share."""

    def __init__(self, jaxpr):
        self.jaxpr = jaxpr
        self.defs: dict[Any, int] = {}
        self.uses: dict[Any, list[int]] = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.outvars:
                self.defs[v] = i
            for a in eqn.invars:
                if isinstance(a, Var):
                    self.uses.setdefault(a, []).append(i)
        self.outvars = {v for v in jaxpr.outvars if isinstance(v, Var)}

    def producer(self, atom):
        """(eqn_index, eqn) defining ``atom``, or (None, None) for
        invars/constvars/literals."""
        if isinstance(atom, Var) and atom in self.defs:
            i = self.defs[atom]
            return i, self.jaxpr.eqns[i]
        return None, None

    def peel(self, atom, prims: Sequence[str] = _TRANSPARENT):
        """Walk backward through single-operand shape/dtype plumbing
        (broadcast/reshape/convert); returns (root_atom, peeled_idxs)."""
        peeled: list[int] = []
        while True:
            i, eqn = self.producer(atom)
            if (eqn is None or eqn.primitive.name not in prims
                    or len(eqn.invars) != 1):
                return atom, peeled
            peeled.append(i)
            atom = eqn.invars[0]

    def consumers(self, var) -> list[int]:
        return self.uses.get(var, [])

    def sole_consumer(self, var):
        """(eqn_index, eqn) when exactly one equation consumes ``var``
        (possibly via several operands) and it does not escape as a
        jaxpr output; else (None, None)."""
        us = set(self.uses.get(var, []))
        if len(us) != 1 or var in self.outvars:
            return None, None
        (i,) = us
        return i, self.jaxpr.eqns[i]

    def forward_through(self, var, prims: Sequence[str] = _TRANSPARENT):
        """Walk forward through exclusively-consumed plumbing; returns
        (last_var, peeled_idxs, consumer_idx, consumer_eqn) where
        consumer is the first non-transparent sole consumer."""
        peeled: list[int] = []
        while True:
            i, eqn = self.sole_consumer(var)
            if eqn is None:
                return var, peeled, None, None
            if eqn.primitive.name in prims and len(eqn.invars) == 1:
                peeled.append(i)
                var = eqn.outvars[0]
                continue
            return var, peeled, i, eqn


def lit_scalar(atom):
    """Python float of a scalar (or size-1) literal atom, else None."""
    if isinstance(atom, Literal):
        try:
            return float(atom.val)
        except (TypeError, ValueError):
            return None
    return None


def peeled_lit_scalar(g: Graph, atom, cons: set):
    """Literal value through broadcast/convert plumbing, marking the
    plumbing consumed."""
    root, peeled = g.peel(atom)
    v = lit_scalar(root)
    if v is not None:
        cons.update(peeled)
    return v


# ---------------------------------------------------------------------------
# sites and plans
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Site:
    """One planned rewrite: replace ``consumed`` equations with a call
    to ``build`` at the position of equation ``trigger``."""
    template: str
    consumed: frozenset
    trigger: int
    inputs: tuple                 # atoms the build reads (vars/literals)
    out_binds: tuple              # ((jaxpr var, build-output index), ...)
    build: Callable[[list], Sequence]
    applied: bool = True          # kernel-supported gate at plan time
    note: str = ""
    # per-site accumulation dtype: what the fused kernel's dots/reduces
    # accumulate in. Every catalog template today is fp32-accumulating
    # (the kernels pin preferred_element_type / fp32 scratch), so the
    # default is the only value in use — tools/lint/quantcheck.py's
    # TPL301 checks it per applied site with sub-fp32 inputs, so a
    # future template that accumulates narrower must say so here and
    # will be flagged.
    accum_dtype: str = "float32"


@dataclasses.dataclass
class Plan:
    sites: list                   # all discovered Sites (applied or not)
    nested: dict                  # eqn index -> Plan (non-empty only)
    errors: list

    def applied_sites(self):
        return [s for s in self.sites if s.applied]

    def empty(self) -> bool:
        """True when nothing anywhere in the plan tree is applied (the
        program needs no rewrite; nested plans may still carry
        discovered-but-unapplied sites for reporting)."""
        return (not self.applied_sites()
                and all(p.empty() for p in self.nested.values()))

    def walk(self):
        """Yield every site in this plan and its nested plans."""
        yield from self.sites
        for p in self.nested.values():
            yield from p.walk()

    def walk_errors(self):
        yield from self.errors
        for p in self.nested.values():
            yield from p.walk_errors()

    def summary(self) -> list:
        """JSON-able fusion-decision record (persisted per program in
        the autotune v2 cache)."""
        return sorted(
            ({"template": s.template, "applied": bool(s.applied),
              "eqns": len(s.consumed), "note": s.note}
             for s in self.walk()),
            key=lambda d: (d["template"], -d["applied"], d["eqns"]))


def site_vmem_bytes(site: Site, block_rows: int = 256) -> int:
    """Static VMEM roofline for one fused site: the double-buffered
    working set of a ``block_rows``-row tile over every input plus the
    rebound outputs. This is the estimate tools/lint/shardcheck.py's
    TPL204 compares against the ~16 MiB per-core budget (and the seed of
    the cost-model scheduler): a site whose tile cannot stay resident
    will thrash HBM no matter how the kernel is scheduled."""
    import numpy as np

    def tile_bytes(aval) -> int:
        shape = tuple(getattr(aval, "shape", ()) or ())
        dt = np.dtype(getattr(aval, "dtype", np.float32))
        if not shape:
            return dt.itemsize
        rows = min(int(shape[0]), block_rows)
        rest = 1
        for d in shape[1:]:
            rest *= int(d)
        return rows * rest * dt.itemsize

    total = 0
    for a in site.inputs:
        aval = getattr(a, "aval", None)
        if aval is not None:
            total += tile_bytes(aval)
    for v, _ in site.out_binds:
        aval = getattr(v, "aval", None)
        if aval is not None:
            total += tile_bytes(aval)
    return 2 * total  # double buffering: next tile streams in while
    #                   the current one computes


def _validate(g: Graph, site: Site) -> bool:
    """Prove the rewrite safe: replaced equations' outputs must be
    re-bound by the fused call or internal to the site, and re-bound
    outputs' external consumers must run after the trigger."""
    cons = set(site.consumed)
    if not cons or site.trigger != max(cons):
        return False
    bound = {v for v, _ in site.out_binds}
    produced = set()
    for i in cons:
        if i < 0 or i >= len(g.jaxpr.eqns):
            return False
        for v in g.jaxpr.eqns[i].outvars:
            if isinstance(v, DropVar):
                continue
            produced.add(v)
            if v in bound:
                if any(u <= site.trigger and u not in cons
                       for u in g.consumers(v)):
                    return False
                continue
            if v in g.outvars:
                return False
            if any(u not in cons for u in g.consumers(v)):
                return False
    if not all(v in produced for v, _ in site.out_binds):
        return False
    # inputs must come from outside the replaced region
    for a in site.inputs:
        if isinstance(a, Var) and g.defs.get(a) in cons:
            return False
    return True


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def _sub_jaxpr(eqn):
    """(open_jaxpr, consts) of a rebuildable higher-order eqn, else
    (None, None).  A nested jit only when every sharding is unspecified —
    inlining an annotated jit would drop its partitioning."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "scan":
        closed = p["jaxpr"]
        return closed.jaxpr, closed.consts
    if name == "remat2":
        return p["jaxpr"], []
    if name == "jit":
        shardings = list(p.get("in_shardings", ())) + \
            list(p.get("out_shardings", ()))
        if all(type(s).__name__ == "UnspecifiedValue" for s in shardings):
            closed = p["jaxpr"]
            return closed.jaxpr, closed.consts
    return None, None


def plan_jaxpr(jaxpr) -> Plan:
    from . import catalog

    templates = catalog.active_templates()
    g = Graph(jaxpr)
    found: list[Site] = []
    errors: list[str] = []
    for i, eqn in enumerate(jaxpr.eqns):
        for name, matcher in templates:
            try:
                cands = matcher(g, i, eqn)
            except Exception as e:  # noqa: BLE001 -- a matcher bug must
                # cost the fusion, never the model; surfaced via report
                errors.append(f"{name}@{i}: {type(e).__name__}: {e}")
                cands = None
            if not cands:
                continue
            for s in cands:
                if _validate(g, s):
                    found.append(s)
                    break
            else:
                found.append(dataclasses.replace(
                    cands[0], applied=False,
                    note=cands[0].note or "unsafe"))
            break
    # de-overlap in program order: first valid site wins its equations
    sites, taken = [], set()
    for s in sorted(found, key=lambda s: s.trigger):
        if s.applied and (s.consumed & taken):
            s = dataclasses.replace(s, applied=False, note="overlap")
        if s.applied:
            taken |= s.consumed
        sites.append(s)
    nested = {}
    for i, eqn in enumerate(jaxpr.eqns):
        if i in taken:
            continue
        sub, _ = _sub_jaxpr(eqn)
        if sub is None:
            continue
        p = plan_jaxpr(sub)
        # keep report-only plans too: sites (applied or not) and errors
        # may live arbitrarily deep (scan -> remat2 -> chain)
        if p.sites or p.nested or p.errors:
            nested[i] = p
    return Plan(sites, nested, errors)


def plan_closed(closed) -> Plan:
    return plan_jaxpr(closed.jaxpr)


# ---------------------------------------------------------------------------
# fused re-trace
# ---------------------------------------------------------------------------

def _eval(jaxpr, consts, plan: Plan, args: list):
    env: dict[Any, Any] = {}

    def read(a):
        return a.val if isinstance(a, Literal) else env[a]

    def write(v, val):
        if not isinstance(v, DropVar):
            env[v] = val

    for v, c in zip(jaxpr.constvars, consts):
        write(v, c)
    for v, a in zip(jaxpr.invars, args):
        write(v, a)

    consumed: dict[int, Site] = {}
    trigger: dict[int, Site] = {}
    for s in plan.applied_sites():
        for i in s.consumed:
            consumed[i] = s
        trigger[s.trigger] = s

    for i, eqn in enumerate(jaxpr.eqns):
        s = trigger.get(i)
        if s is not None:
            outs = s.build([read(a) for a in s.inputs])
            for v, oi in s.out_binds:
                write(v, outs[oi])
            continue
        if i in consumed:
            continue
        invals = [read(a) for a in eqn.invars]
        sub_plan = plan.nested.get(i)
        if sub_plan is not None and not sub_plan.empty():
            ans = _eval_higher_order(eqn, invals, sub_plan)
        else:
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
        if eqn.primitive.multiple_results:
            for v, val in zip(eqn.outvars, ans):
                write(v, val)
        else:
            write(eqn.outvars[0], ans)
    return [read(v) for v in jaxpr.outvars]


def _eval_higher_order(eqn, invals, sub_plan: Plan):
    """Re-wrap a higher-order equation around a fused evaluation of its
    body, preserving the original static params."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "scan":
        closed = p["jaxpr"]
        nc, ncar = p["num_consts"], p["num_carry"]
        body_consts = invals[:nc]
        carry0 = tuple(invals[nc:nc + ncar])
        xs = tuple(invals[nc + ncar:])

        def body(carry, x):
            vals = _eval(closed.jaxpr, closed.consts, sub_plan,
                         list(body_consts) + list(carry) + list(x))
            return tuple(vals[:ncar]), tuple(vals[ncar:])

        carry, ys = lax.scan(body, carry0, xs, length=p["length"],
                             reverse=p["reverse"],
                             unroll=p.get("unroll", 1))
        return list(carry) + list(ys)
    if name == "remat2":
        jx = p["jaxpr"]

        def run(*xs):
            return _eval(jx, [], sub_plan, list(xs))

        return jax.checkpoint(run, policy=p.get("policy"),
                              prevent_cse=p.get("prevent_cse", True))(*invals)
    if name == "jit":
        closed = p["jaxpr"]
        return _eval(closed.jaxpr, closed.consts, sub_plan, invals)
    raise NotImplementedError(f"fusion rewrite inside '{name}'")


def eval_fused(closed, plan: Plan, flat_args):
    return _eval(closed.jaxpr, closed.consts, plan, list(flat_args))


# ---------------------------------------------------------------------------
# program identity (autotune v2 key)
# ---------------------------------------------------------------------------

def source_hash_mod(*mods) -> str:
    """sha1 over the source of whole modules (objects or import names);
    the catalog stamps this into program records so any edit to the
    pass or a matcher invalidates committed fusion plans."""
    import importlib
    import inspect

    h = hashlib.sha1()
    for m in mods:
        if isinstance(m, str):
            m = importlib.import_module(m)
        h.update(inspect.getsource(m).encode())
    return h.hexdigest()[:16]


def program_hash(closed) -> str:
    """Stable hash of a traced program: sha1 over the printed jaxpr with
    runtime object addresses stripped (thunk reprs embed ``0x...``
    pointers that change every process)."""
    s = re.sub(r"0x[0-9a-fA-F]+", "0x", str(closed.jaxpr))
    return hashlib.sha1(s.encode()).hexdigest()[:16]
