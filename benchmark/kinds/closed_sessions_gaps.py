"""Traffic kind ``closed_sessions_gaps``: ``closed_sessions``' traffic to
the letter, with the served comparison read over all the compared tokens
instead of at the worst one.

``serve_common.served_numbers`` reads the LARGEST of the sample's per-token
gaps (a served token's logit below the reference's best).  Under a model
that routes each token to the top k of many experts that number belongs to
a rare event: one token whose state a choice flipped at a near tie moved,
where the control's largest is its everyday noise, and the two nearly touch
(PERF.md §6, PR 29).  This kind compares the same sample in the same way
and reads the gaps' distribution: ``served_gap_p90``,
``served_gap_mean``, ``served_mismatch_share`` (percent of tokens that are
not the reference's first choice) and, as before, ``served_logit_gap``.
The cell's file says which of them have limits.  The p90 is for precision
lost on every token (the control), and a tenth of the tokens may be as
wrong as they like without moving it; so the largest gap keeps a limit
too, wide of a flipped choice and well under a token that is plain wrong
(a wrong page, a chunk boundary), which reads several logit spreads.

It is a file of its own because serve_common.py is not this PR's to edit:
``run`` lends its ``served_numbers`` to ``serve_common.run`` for the length
of the call.  A ``benchmark`` PR should move ``gap_numbers`` into
serve_common.py and delete this file (PERF.md §7)."""

from __future__ import annotations

import numpy as np

from benchmark.kinds import closed_sessions, serve_common as sc


def gap_numbers(gaps: np.ndarray) -> dict:
    """The compared numbers from the per-token gaps (all >= 0)."""
    return {"served_gap_p90": float(np.percentile(gaps, 90)),
            "served_gap_mean": float(gaps.mean()),
            "served_mismatch_share": 100.0 * float((gaps > 0).mean()),
            "served_logit_gap": float(gaps.max())}


def served_numbers(system, ref, key, records: list, t0: float, t1: float,
                   n_sample: int, rng, pad_to: int, quant=None) -> dict:
    """``serve_common.served_numbers``' sample (the longest request that
    finished in the window and a seeded choice of the rest), reference
    pass and control; only what is read from the gaps differs."""
    done = [r for r in records if r.finished is not None
            and t0 <= r.finished < t1 and not r.req.aborted
            and len(r.req.out_tokens) == r.req.max_new_tokens]
    if not done:
        return dict(gap_numbers(np.full(1, np.nan)), _sampled=0)
    longest = max(done, key=lambda r: len(r.spec.prompt) + r.spec.max_new)
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :max(0, n_sample - 1)]]
    T = max(len(r.spec.prompt) + r.spec.max_new for r in pick)
    tokens = np.zeros((len(pick), -(-T // pad_to) * pad_to), np.int32)
    positions, served = [], []
    for i, r in enumerate(pick):
        out = np.asarray(r.req.out_tokens, np.int32)
        P = len(r.spec.prompt)
        tokens[i, :P] = r.spec.prompt
        tokens[i, P:P + len(out) - 1] = out[:-1]
        positions.append(list(range(P - 1, P - 1 + len(out))))
        served.append(out)
    logits = ref.logits_at(system.model, key, tokens, positions)
    if quant:
        low = ref.logits_at(system.model, key, tokens, positions, quant=quant)
        served = [lo.argmax(-1) for lo in low]
    gaps = np.concatenate([
        lg.max(-1) - np.take_along_axis(lg, s[:, None].astype(np.int64),
                                        -1)[:, 0]
        for lg, s in zip(logits, served)])
    return dict(gap_numbers(gaps), _sampled=len(pick), _tokens=int(gaps.size))


def run(ctx) -> dict:
    plain, sc.served_numbers = sc.served_numbers, served_numbers
    try:
        return sc.run(ctx, closed_sessions.Source)
    finally:
        sc.served_numbers = plain
