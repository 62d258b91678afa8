"""The comparisons that decide `correct`.  Each number compared has a
limit of its own, kept in the cell's traffic file under ``limits`` with
the readings it was set from in PERF.md."""

from __future__ import annotations

import math
import statistics


def _leaf_gaps(prog: dict, ref: dict, skip=()) -> dict:
    """Per leaf, the gap between the program's and the reference's norm
    (not the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return {name: abs(prog[name] - r) / max(r, med, 1e-30)
            for name, r in ref.items() if name not in skip}


def _worst(gaps: dict) -> tuple:
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def training_numbers(prog: dict, ref: dict) -> dict:
    """prog / ref: {"losses": [..], "grad_norms": {leaf: norm},
    "update_norms": {leaf: norm}} over the same first steps."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(prog["losses"][:n], ref["losses"][:n]))
    grad_gap, grad_leaf = _worst(_leaf_gaps(prog["grad_norms"],
                                            ref["grad_norms"]))
    med_g = statistics.median(ref["grad_norms"].values())
    # Norms move only in the second order with a zero-mean error, so the
    # reference in fp8 reads within twice the program on both numbers
    # above (PERF.md, limits).  This one moves in the first order: the
    # distance between the two sides' chunk sums of the first gradient
    # (k signed linear readings per leaf), against that leaf's norm.
    # Leaves whose readings are null in the reference are left out, by
    # rule: every consumer of the residual stream is a LayerNorm, so a
    # gradient's sum over the hidden dimension is nought (wpe, proj_w,
    # fc2_w, and wte behind the final norm), and a chunk of whole rows
    # reads only the program's rounding there.
    def l2(v):
        return math.sqrt(sum(float(x) ** 2 for x in v))

    sums = {name: l2(p - r for p, r in zip(prog["grad_sums"][name],
                                           ref["grad_sums"][name]))
            / max(norm, med_g, 1e-30)
            for name, norm in ref["grad_norms"].items()
            if l2(ref["grad_sums"][name]) >= 0.01 * norm}
    sum_gap, sum_leaf = _worst(sums)
    # a leaf whose gradient is nought to rounding in the reference moves
    # under Adam by round-off alone: left out of the change, by this rule
    dead = [k for k, v in ref["grad_norms"].items() if v < 1e-3 * med_g]
    upd = _leaf_gaps(prog["update_norms"], ref["update_norms"], skip=dead)
    upd_worst, upd_leaf = _worst(upd)
    # The change is judged by its median leaf: the program keeps the
    # stacked LayerNorm gains ([L, H], so "matrices" to its rule) in bf16
    # with stochastic rounding, and at 1.0 one bf16 step is 78 learning
    # rates, so those leaves' norms are rounding noise by configuration
    # (PERF.md, limits).  The worst leaf is printed beside it, unjudged.
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "grad_sum_gap": sum_gap,
            "update_norm_gap": statistics.median(upd.values()),
            "update_norm_gap_worst_leaf": upd_worst,
            "_leaf_sum_gaps": sums,
            "_where": {"grad_norm_gap": grad_leaf, "grad_sum_gap": sum_leaf,
                       "update_norm_gap_worst_leaf": upd_leaf,
                       "left_out": dead}}


def judge(numbers: dict, limits: dict) -> dict:
    """{"correct": bool, "numbers": {name: {"value", "limit"}}}; a number
    with no limit in the cell's file is reported and not judged."""
    out, ok = {}, True
    for name, value in numbers.items():
        if name.startswith("_"):
            continue
        limit = limits.get(name)
        out[name] = {"value": value, "limit": limit}
        if limit is not None and not value <= limit:     # NaN fails
            ok = False
    for name in limits:
        if name not in out:                              # nothing compared
            out[name] = {"value": None, "limit": limits[name]}
            ok = False
    return {"correct": ok, "numbers": out}
