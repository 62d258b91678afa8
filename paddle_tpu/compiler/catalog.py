"""Fusion template catalog: jaxpr patterns -> fused Pallas entries.

Each template is ``(name, matcher)``; a matcher inspects one equation
of a :class:`~.fusion_pass.Graph` (the anchor — a primitive that only
occurs inside its chain: ``rsqrt`` for the norms, ``tanh`` for
approximate gelu, ``jit[silu]`` for swiglu, the flash
``custom_vjp_call`` for rope+attention) and walks
producers/consumers to the full chain.  It returns a list of candidate
:class:`~.fusion_pass.Site` objects in preference order (e.g. the
residual+norm epilogue first, norm-only as fallback) or None; the pass
validates and applies the first safe candidate.

Adding a template == adding a matcher here and a row to the README
catalog table.  Matchers only ever *recognize the exact unfused
composition the fused kernel is parity-pinned against* — anything else
(different constants, wrong reduce axis, extra consumers of chain
intermediates) must return None, which the golden near-miss tests in
tests/test_compiler_fusion.py pin per template.

Two standing guards every matcher applies:

- a chain is never followed across a ``sharding_constraint`` — the
  constraint marks a resharding point the fused kernel must not absorb
  (the SP path in models/gpt.py keeps its unfused composition exactly
  as the hand-wiring did);
- ``applied`` is set from the fused entry's own ``*_supported`` gate,
  so unsupported geometry keeps the untouched unfused graph instead of
  a kernel call that would immediately fall back.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
from jax.extend.core import Var

from .fusion_pass import Graph, Site, lit_scalar, source_hash_mod

_SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)


def _aval(atom):
    return getattr(atom, "aval", None)


def _is_sharded(g: Graph, atom) -> bool:
    _, eqn = g.producer(atom)
    return eqn is not None and eqn.primitive.name == "sharding_constraint"


def _lit_operand(eqn, known=None):
    """(literal value, other atom) when exactly one operand of a binary
    eqn is a scalar literal (optionally requiring the other to be
    ``known``)."""
    a, b = eqn.invars
    for lit_at, other in ((a, b), (b, a)):
        v = lit_scalar(lit_at)
        if v is not None and (known is None or other is known):
            return v, other
    return None, None


def _rows(shape) -> int:
    n = 1
    for d in shape[:-1]:
        n *= d
    return n


# ---------------------------------------------------------------------------
# norm epilogues (rms / layer)
# ---------------------------------------------------------------------------

def _mean_last_axis(g: Graph, atom, of_var, cons: set):
    """Match ``mean(of_var, -1, keepdims=True)``: div-by-H over a
    broadcast reduce_sum of the last axis.  True on success (plumbing
    added to ``cons``)."""
    root, peeled = g.peel(atom)
    di, deqn = g.producer(root)
    if deqn is None or deqn.primitive.name != "div":
        return False
    den = lit_scalar(deqn.invars[1])
    if den is None:
        return False
    num, p2 = g.peel(deqn.invars[0])
    ri, reqn = g.producer(num)
    if reqn is None or reqn.primitive.name != "reduce_sum":
        return False
    operand = reqn.invars[0]
    if operand is not of_var:
        return False
    nd = operand.aval.ndim
    if tuple(reqn.params.get("axes", ())) != (nd - 1,):
        return False
    if den != float(operand.aval.shape[-1]):
        return False
    cons.update(peeled)
    cons.update(p2)
    cons.update((di, ri))
    return True


def _norm_tail(g: Graph, y1_var, x_dtype, want_beta: bool, cons: set):
    """Forward walk from the normalized value: mul by a rank-1 gain,
    optional add of a rank-1 beta, convert back to ``x_dtype``.
    Returns (gain_root, beta_root, y_out_var) or None."""
    h = y1_var.aval.shape[-1]

    def rank1_partner(eqn, cur):
        other = eqn.invars[0] if eqn.invars[1] is cur else eqn.invars[1]
        root, peeled = g.peel(other)
        av = _aval(root)
        if (isinstance(root, Var) and av is not None
                and av.shape == (h,)):
            return root, peeled
        return None, None

    gi, geqn = g.sole_consumer(y1_var)
    if geqn is None or geqn.primitive.name != "mul":
        return None
    gain, peeled = rank1_partner(geqn, y1_var)
    if gain is None:
        return None
    cons.add(gi)
    cons.update(peeled)
    cur = geqn.outvars[0]
    beta = None
    if want_beta:
        bi, beqn = g.sole_consumer(cur)
        if beqn is None or beqn.primitive.name != "add":
            return None
        beta, peeled = rank1_partner(beqn, cur)
        if beta is None:
            return None
        cons.add(bi)
        cons.update(peeled)
        cur = beqn.outvars[0]
    if x_dtype != jnp.float32:
        ci, ceqn = g.sole_consumer(cur)
        if (ceqn is None or ceqn.primitive.name != "convert_element_type"
                or ceqn.outvars[0].aval.dtype != x_dtype):
            return None
        cons.add(ci)
        cur = ceqn.outvars[0]
    return gain, beta, cur


def _residual_candidates(g: Graph, x_atom, with_bias: bool):
    """Producer patterns of the norm input that fold into the epilogue:
    ``add(a, b)`` (residual) and — gpt's ln2 shape — the outer
    ``add(add(a, b), broadcast(convert(bias)))``.  Yields
    (extra_consumed, kwargs_inputs, r_var) preferred-first."""
    xi, xeqn = g.producer(x_atom)
    if xeqn is None or xeqn.primitive.name != "add":
        return
    av = _aval(x_atom)
    if with_bias:
        for inner_at, b_at in (xeqn.invars, xeqn.invars[::-1]):
            b_root, peeled = g.peel(b_at)
            bav = _aval(b_root)
            if (not isinstance(b_root, Var) or bav is None
                    or bav.shape != (av.shape[-1],)):
                continue
            ii, ieqn = g.producer(inner_at)
            if ieqn is None or ieqn.primitive.name != "add":
                continue
            a, b = ieqn.invars
            if (_aval(a) is not None and _aval(b) is not None
                    and _aval(a).shape == av.shape
                    and _aval(b).shape == av.shape):
                yield ({xi, ii, *peeled}, {"x": a, "sub": b, "bias": b_root},
                       xeqn.outvars[0])
    a, b = xeqn.invars
    if (_aval(a) is not None and _aval(b) is not None
            and _aval(a).shape == av.shape and _aval(b).shape == av.shape
            and _aval(a).dtype == av.dtype and _aval(b).dtype == av.dtype):
        yield ({xi}, {"x": a, "sub": b}, xeqn.outvars[0])


def _norm_sites(g: Graph, i, eqn, norm: str):
    """Shared driver for the rms/layer templates, anchored at rsqrt."""
    if eqn.primitive.name != "rsqrt":
        return None
    cons = {i}
    ai, aeqn = g.producer(eqn.invars[0])
    if aeqn is None or aeqn.primitive.name != "add":
        return None
    eps, stat_at = _lit_operand(aeqn)
    if eps is None or eps <= 0:
        return None
    cons.add(ai)

    if norm == "rms":
        # stat = mean(x32*x32, -1, keepdims): div over reduce_sum of a
        # self-multiply
        root, peeled = g.peel(stat_at)
        di, deqn = g.producer(root)
        if deqn is None or deqn.primitive.name != "div":
            return None
        den = lit_scalar(deqn.invars[1])
        num, p2 = g.peel(deqn.invars[0])
        ri, reqn = g.producer(num)
        if (den is None or reqn is None
                or reqn.primitive.name != "reduce_sum"):
            return None
        sq = reqn.invars[0]
        nd = sq.aval.ndim
        if tuple(reqn.params.get("axes", ())) != (nd - 1,):
            return None
        if den != float(sq.aval.shape[-1]):
            return None
        mi, meqn = g.producer(sq)
        if (meqn is None or meqn.primitive.name != "mul"
                or meqn.invars[0] is not meqn.invars[1]):
            return None
        u = meqn.invars[0]
        cons.update(peeled)
        cons.update(p2)
        cons.update((di, ri, mi))
    else:
        # stat = var(x32, -1, keepdims): jnp.var traces as jit[_var]
        # applied to (x32, ddof-literal); any ddof other than 0 is a
        # different statistic and must not match
        root, peeled = g.peel(stat_at)
        vi, veqn = g.producer(root)
        if (veqn is None or veqn.primitive.name != "jit"
                or veqn.params.get("name") != "_var"
                or not veqn.invars
                or any(lit_scalar(a) != 0.0 for a in veqn.invars[1:])):
            return None
        u = veqn.invars[0]
        cons.update(peeled)
        cons.add(vi)
    if u.aval.dtype != jnp.float32:
        return None

    # u = convert(x) (or x itself when the model runs fp32)
    ci, ceqn = g.producer(u)
    if (ceqn is not None
            and ceqn.primitive.name == "convert_element_type"):
        x_atom = ceqn.invars[0]
        cons.add(ci)
    else:
        x_atom = u
    x_av = _aval(x_atom)
    if x_av is None:
        return None
    eps = float(eps)

    # normalized value: mul(u, bcast(rsqrt)) for rms;
    # mul(sub(u, mean), bcast(rsqrt)) for layer
    rvar, rpeel, ni, neqn = g.forward_through(eqn.outvars[0])
    if neqn is None or neqn.primitive.name != "mul":
        return None
    cons.update(rpeel)
    partner = neqn.invars[0] if neqn.invars[1] is rvar else neqn.invars[1]
    if norm == "rms":
        if partner is not u:
            return None
    else:
        si, seqn = g.producer(partner)
        if (seqn is None or seqn.primitive.name != "sub"
                or seqn.invars[0] is not u):
            return None
        if not _mean_last_axis(g, seqn.invars[1], u, cons):
            return None
        cons.add(si)
    cons.add(ni)

    tail = _norm_tail(g, neqn.outvars[0], x_av.dtype,
                      want_beta=(norm == "layer"), cons=cons)
    if tail is None:
        return None
    gain, beta, y_out = tail

    n, h = _rows(x_av.shape), x_av.shape[-1]
    from ..ops.pallas.fused_norm_epilogue import (
        fused_norm_epilogue, fused_norm_epilogue_supported)

    supported = fused_norm_epilogue_supported(n, h, x_av.dtype)
    resharded = _is_sharded(g, x_atom)
    template = f"{norm}_epilogue"

    def mk(extra_cons, extra_inputs, r_var):
        all_cons = frozenset(cons | extra_cons)
        names = ["x"] + [k for k in ("sub", "bias") if k in extra_inputs]
        inputs = tuple([extra_inputs.get("x", x_atom)]
                       + [extra_inputs[k] for k in names[1:]]
                       + [gain] + ([beta] if beta is not None else []))
        has_beta = beta is not None

        def build(vals, names=tuple(names), has_beta=has_beta,
                  norm=norm, eps=eps):
            kw = dict(zip(names, vals[:len(names)]))
            kw["gain"] = vals[len(names)]
            if has_beta:
                kw["beta"] = vals[len(names) + 1]
            x = kw.pop("x")
            r, y = fused_norm_epilogue(x, norm=norm, eps=eps, **kw)
            return [r, y]

        binds = ((y_out, 1),) if r_var is None else ((r_var, 0), (y_out, 1))
        return Site(template, all_cons, max(all_cons), inputs, binds, build,
                    applied=supported and not resharded,
                    note="resharded" if resharded else "")

    cands = [mk(ec, ei, rv)
             for ec, ei, rv in _residual_candidates(
                 g, x_atom, with_bias=(norm == "layer"))]
    cands.append(mk(set(), {}, None))
    return cands


def match_rms_epilogue(g: Graph, i, eqn):
    return _norm_sites(g, i, eqn, "rms")


def match_layer_epilogue(g: Graph, i, eqn):
    return _norm_sites(g, i, eqn, "layer")


# ---------------------------------------------------------------------------
# rope -> flash attention
# ---------------------------------------------------------------------------

_FLASH_PROBE: dict = {}


def _strip_addrs(s: str) -> str:
    return re.sub(r"0x[0-9a-fA-F]+", "0x", s)


def _flash_probe_str(avals) -> str:
    """Printed call_jaxpr of ``flash_attention_raw(q, k, v, causal=True)``
    at the given avals (addresses stripped), '' when the geometry is
    unsupported.  A candidate custom_vjp equation is flash — with the
    same causal mask and default scale baked in — iff its call_jaxpr
    prints identically; any other custom_vjp (fused_ce, quant matmuls,
    a non-causal flash) differs structurally."""
    key = tuple((tuple(a.shape), str(a.dtype)) for a in avals)
    if key in _FLASH_PROBE:
        return _FLASH_PROBE[key]
    from ..ops.pallas.flash_attention import flash_attention_raw, supported

    out = ""
    if supported(avals[0].shape, avals[0].dtype):
        try:
            jx = jax.make_jaxpr(
                lambda q, k, v: flash_attention_raw(q, k, v, causal=True))(
                *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in avals])
            for e in jx.jaxpr.eqns:
                if e.primitive.name == "custom_vjp_call":
                    out = _strip_addrs(str(e.params["call_jaxpr"]))
                    break
        except Exception:  # noqa: BLE001 -- unprobeable: just no match
            out = ""
    _FLASH_PROBE[key] = out
    return out


def _is_flash_eqn(eqn):
    """(q, k, v) atoms when the equation is the flash custom_vjp."""
    if eqn.primitive.name != "custom_vjp_call":
        return None
    ncon = eqn.params.get("num_consts", 0)
    prim_in = list(eqn.invars[ncon:])
    if len(prim_in) != 3 or len(eqn.outvars) != 1:
        return None
    avals = [a.aval for a in prim_in]
    if any(av.ndim != 4 for av in avals):
        return None
    probe = _flash_probe_str(avals)
    if not probe or _strip_addrs(str(eqn.params["call_jaxpr"])) != probe:
        return None
    return prim_in


def _half_slice(g: Graph, atom, lo: bool):
    """The equation producing ``atom`` as the lower (``lo``) or upper
    half of the last axis: one output of ``jnp.split(x, 2, -1)``'s
    ``split``, or an explicit ``slice`` at d/2."""
    i, eqn = g.producer(atom)
    if eqn is None:
        return None
    src = eqn.invars[0]
    shape = src.aval.shape
    d = shape[-1]
    if eqn.primitive.name == "split":
        if (eqn.params["axis"] != len(shape) - 1
                or tuple(eqn.params["sizes"]) != (d // 2, d - d // 2)
                or atom is not eqn.outvars[0 if lo else 1]):
            return None
        return i, src
    if eqn.primitive.name != "slice":
        return None
    start = tuple(eqn.params["start_indices"])
    limit = tuple(eqn.params["limit_indices"])
    strides = eqn.params.get("strides")
    if strides is not None and any(s != 1 for s in strides):
        return None
    want = ((0,) * (len(shape) - 1) + (0 if lo else d // 2,),
            tuple(shape[:-1]) + (d // 2 if lo else d,))
    if (start, limit) != want:
        return None
    return i, src


def _table_mul(g: Graph, atom, cons: set):
    """Match ``mul(slice_half, table)`` (the table possibly arriving
    through broadcast/convert peels); returns
    (slice_var, lo, src, table_atom, table_root) or None.

    The peel equations are deliberately NOT consumed: a cos/sin
    broadcast is typically shared by every layer's rope chain (unrolled
    traces compute it once), so eating it into one site's region would
    leak its value to the other layers and fail validation.  The site
    takes the mul's direct table operand as an input instead."""
    mi, meqn = g.producer(atom)
    if meqn is None or meqn.primitive.name != "mul":
        return None
    for half_at, tab_at in (meqn.invars, meqn.invars[::-1]):
        for lo in (True, False):
            hs = _half_slice(g, half_at, lo)
            if hs is None:
                continue
            si, src = hs
            root, _peeled = g.peel(tab_at)
            av = _aval(root)
            if (not isinstance(root, Var) or av is None
                    or av.dtype != jnp.float32):
                continue
            cons.update((mi, si))
            return half_at, lo, src, tab_at, root
    return None


def _rope_chain(g: Graph, atom):
    """Match the apply_rope lowering producing ``atom``:
    concat(x1*cos - x2*sin, x2*cos + x1*sin) over the f32 halves of a
    convert of x, converted back.  Returns
    {x, cos, sin, cons} or None."""
    av = _aval(atom)
    if av is None:
        return None
    cons: set = set()
    cur = atom
    ci, ceqn = g.producer(cur)
    if ceqn is not None and ceqn.primitive.name == "convert_element_type":
        cons.add(ci)
        cur = ceqn.invars[0]
    ki, keqn = g.producer(cur)
    if (keqn is None or keqn.primitive.name != "concatenate"
            or len(keqn.invars) != 2
            or keqn.params.get("dimension") != cur.aval.ndim - 1):
        return None
    cons.add(ki)
    o1, o2 = keqn.invars
    si, seqn = g.producer(o1)
    ai, aeqn = g.producer(o2)
    if (seqn is None or aeqn is None or seqn.primitive.name != "sub"
            or aeqn.primitive.name != "add"):
        return None
    cons.update((si, ai))
    # o1 = x1*cos - x2*sin (operand order fixed by sub)
    m1 = _table_mul(g, seqn.invars[0], cons)
    m2 = _table_mul(g, seqn.invars[1], cons)
    if m1 is None or m2 is None or not m1[1] or m2[1]:
        return None
    x1_var, _, src, cos_at, cos_root = m1
    x2_var, _, src2, sin_at, sin_root = m2
    if src is not src2:
        return None
    # o2 = x2*cos + x1*sin, either operand order
    m3 = _table_mul(g, aeqn.invars[0], cons)
    m4 = _table_mul(g, aeqn.invars[1], cons)
    if m3 is None or m4 is None:
        return None
    if m3[1]:  # lo half first -> it's the x1*sin term
        m3, m4 = m4, m3
    if (m3[1] or not m4[1] or m3[0] is not x2_var or m4[0] is not x1_var
            or m3[4] is not cos_root or m4[4] is not sin_root):
        return None
    # src = convert(x) to f32 (or x when fp32)
    if src.aval.dtype != jnp.float32:
        return None
    ei, eeqn = g.producer(src)
    if (eeqn is not None
            and eeqn.primitive.name == "convert_element_type"):
        x_root = eeqn.invars[0]
        cons.add(ei)
    else:
        x_root = src
    if _aval(x_root) is None or _aval(x_root).dtype != av.dtype:
        return None
    return {"x": x_root, "cos": cos_at, "sin": sin_at,
            "cos_root": cos_root, "sin_root": sin_root, "cons": cons}


def match_rope_attention(g: Graph, i, eqn):
    prim_in = _is_flash_eqn(eqn)
    if prim_in is None:
        return None
    q_at, k_at, v_at = prim_in
    qc = _rope_chain(g, q_at) if isinstance(q_at, Var) else None
    kc = _rope_chain(g, k_at) if isinstance(k_at, Var) else None
    if qc is not None and kc is not None and (
            qc["cos_root"] is not kc["cos_root"]
            or qc["sin_root"] is not kc["sin_root"]):
        kc = None  # different tables: only the q rotation is ours
    if qc is None and kc is None:
        return None

    from ..ops.pallas.fused_rope_attention import (
        fused_rope_flash_attention, fused_rope_supported)

    av = q_at.aval
    supported = fused_rope_supported(tuple(av.shape), av.dtype)
    o_var = eqn.outvars[0]

    def mk(use_q, use_k):
        chain_q = qc if use_q else None
        chain_k = kc if use_k else None
        tables = chain_q or chain_k
        cons = frozenset({i}
                         | (chain_q["cons"] if chain_q else set())
                         | (chain_k["cons"] if chain_k else set()))
        inputs = (chain_q["x"] if chain_q else q_at,
                  chain_k["x"] if chain_k else k_at,
                  v_at, tables["cos"], tables["sin"])

        def build(vals, rq=bool(chain_q), rk=bool(chain_k)):
            q, k, v, cos, sin = vals
            return [fused_rope_flash_attention(q, k, v, cos, sin,
                                               causal=True,
                                               rope_q=rq, rope_k=rk)]

        return Site("rope_attention", cons, max(cons), inputs,
                    ((o_var, 0),), build, applied=supported)

    cands = [mk(qc is not None, kc is not None)]
    if qc is not None and kc is not None:
        # the k chain may escape (prefill returns the rotated k): fall
        # back to fusing only the q rotation, passing k pre-rotated
        cands.append(mk(True, False))
        cands.append(mk(False, True))
    return cands


# ---------------------------------------------------------------------------
# bias + gelu (tanh approximation)
# ---------------------------------------------------------------------------

def match_bias_gelu(g: Graph, i, eqn):
    if eqn.primitive.name != "tanh":
        return None
    cons = {i}
    mi, meqn = g.producer(eqn.invars[0])
    if meqn is None or meqn.primitive.name != "mul":
        return None
    c1, s_at = _lit_operand(meqn)
    if c1 is None or abs(c1 - _SQRT_2_OVER_PI) > 5e-3:
        return None
    cons.add(mi)
    si, seqn = g.producer(s_at)
    if seqn is None or seqn.primitive.name != "add":
        return None
    cons.add(si)
    x_at = None
    for cand_x, cubic_at in (seqn.invars, seqn.invars[::-1]):
        qi, qeqn = g.producer(cubic_at)
        if qeqn is None or qeqn.primitive.name != "mul":
            continue
        c2, pw_at = _lit_operand(qeqn)
        if c2 is None or abs(c2 - 0.044715) > 5e-4:
            continue
        pi, peqn = g.producer(pw_at)
        if (peqn is None or peqn.primitive.name != "integer_pow"
                or peqn.params.get("y") != 3 or peqn.invars[0] is not cand_x):
            continue
        x_at = cand_x
        cons.update((qi, pi))
        break
    if x_at is None:
        return None
    # forward: tanh -> +1 -> *0.5 -> *x
    ai, aeqn = g.sole_consumer(eqn.outvars[0])
    if aeqn is None or aeqn.primitive.name != "add":
        return None
    one, _ = _lit_operand(aeqn, known=eqn.outvars[0])
    if one != 1.0:
        return None
    cons.add(ai)
    hi, heqn = g.sole_consumer(aeqn.outvars[0])
    if heqn is None or heqn.primitive.name != "mul":
        return None
    half, _ = _lit_operand(heqn, known=aeqn.outvars[0])
    if half != 0.5:
        return None
    cons.add(hi)
    fi, feqn = g.sole_consumer(heqn.outvars[0])
    if feqn is None or feqn.primitive.name != "mul":
        return None
    other = feqn.invars[0] if feqn.invars[1] is heqn.outvars[0] \
        else feqn.invars[1]
    if other is not x_at:
        return None
    cons.add(fi)
    y_out = feqn.outvars[0]
    # x = h + broadcast(convert(bias[f]))
    bi, beqn = g.producer(x_at)
    if beqn is None or beqn.primitive.name != "add":
        return None
    x_av = _aval(x_at)
    found = None
    for h_at, b_at in (beqn.invars, beqn.invars[::-1]):
        b_root, peeled = g.peel(b_at)
        bav = _aval(b_root)
        hav = _aval(h_at)
        if (isinstance(b_root, Var) and bav is not None
                and bav.shape == (x_av.shape[-1],)
                and hav is not None and hav.shape == x_av.shape
                and hav.dtype == x_av.dtype):
            found = (h_at, b_root, peeled)
            break
    if found is None:
        return None
    h_at, b_root, peeled = found
    cons.add(bi)
    cons.update(peeled)

    from ..ops.pallas.fused_bias_act import (fused_bias_act_supported,
                                             fused_bias_gelu)

    supported = fused_bias_act_supported(_rows(x_av.shape), x_av.shape[-1],
                                         x_av.dtype)

    def build(vals):
        h, b = vals
        return [fused_bias_gelu(h, b)]

    return [Site("bias_gelu", frozenset(cons), max(cons), (h_at, b_root),
                 ((y_out, 0),), build,
                 applied=supported and not _is_sharded(g, h_at))]


# ---------------------------------------------------------------------------
# swiglu
# ---------------------------------------------------------------------------

def match_swiglu(g: Graph, i, eqn):
    if (eqn.primitive.name != "jit" or eqn.params.get("name") != "silu"
            or len(eqn.invars) != 1 or len(eqn.outvars) != 1):
        return None
    body = eqn.params["jaxpr"].jaxpr
    if not any(e.primitive.name == "logistic" for e in body.eqns):
        return None
    cons = {i}
    g32 = eqn.invars[0]
    if _aval(g32) is None or g32.aval.dtype != jnp.float32:
        return None
    ci, ceqn = g.producer(g32)
    if ceqn is not None and ceqn.primitive.name == "convert_element_type":
        gate_at = ceqn.invars[0]
        cons.add(ci)
    else:
        gate_at = g32
    gate_av = _aval(gate_at)
    if gate_av is None:
        return None
    cur = eqn.outvars[0]
    if gate_av.dtype != jnp.float32:
        di, deqn = g.sole_consumer(cur)
        if (deqn is None or deqn.primitive.name != "convert_element_type"
                or deqn.outvars[0].aval.dtype != gate_av.dtype):
            return None
        cons.add(di)
        cur = deqn.outvars[0]
    mi, meqn = g.sole_consumer(cur)
    if meqn is None or meqn.primitive.name != "mul":
        return None
    up_at = meqn.invars[0] if meqn.invars[1] is cur else meqn.invars[1]
    up_av = _aval(up_at)
    if (up_av is None or up_av.shape != gate_av.shape
            or up_av.dtype != gate_av.dtype):
        return None
    cons.add(mi)

    from ..ops.pallas.fused_bias_act import (fused_bias_act_supported,
                                             fused_swiglu)

    supported = fused_bias_act_supported(_rows(gate_av.shape),
                                         gate_av.shape[-1], gate_av.dtype)

    def build(vals):
        gate, up = vals
        return [fused_swiglu(gate, up)]

    return [Site("swiglu", frozenset(cons), max(cons), (gate_at, up_at),
                 ((meqn.outvars[0], 0),), build,
                 applied=supported and not _is_sharded(g, gate_at))]


# ---------------------------------------------------------------------------
# the catalog
# ---------------------------------------------------------------------------

ALL_TEMPLATES = (
    ("rms_epilogue", match_rms_epilogue),
    ("layer_epilogue", match_layer_epilogue),
    ("rope_attention", match_rope_attention),
    ("bias_gelu", match_bias_gelu),
    ("swiglu", match_swiglu),
)


def active_templates():
    """Catalog filtered by the per-template kill switches.  The PR 6
    flags keep their meaning under the compiler: use_fused_norm_epilogue
    / use_fused_rope_attention now disable *discovery* of their
    templates instead of a hand-wired call site."""
    from ..core.flags import GLOBAL_FLAGS

    out = []
    norm_on = bool(GLOBAL_FLAGS.get("use_fused_norm_epilogue")
                   if GLOBAL_FLAGS.has("use_fused_norm_epilogue") else True)
    rope_on = bool(GLOBAL_FLAGS.get("use_fused_rope_attention")
                   if GLOBAL_FLAGS.has("use_fused_rope_attention") else True)
    act_on = bool(GLOBAL_FLAGS.get("use_fused_bias_act")
                  if GLOBAL_FLAGS.has("use_fused_bias_act") else True)
    for name, matcher in ALL_TEMPLATES:
        if name in ("rms_epilogue", "layer_epilogue") and not norm_on:
            continue
        if name == "rope_attention" and not rope_on:
            continue
        if name in ("bias_gelu", "swiglu") and not act_on:
            continue
        out.append((name, matcher))
    return out


def catalog_source() -> str:
    """Hash of the pass + catalog implementation; stamped into each v2
    program record so editing a matcher invalidates committed plans."""
    from . import fusion_pass

    return source_hash_mod(fusion_pass, __name__)
