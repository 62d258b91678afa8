"""Operations and bytes that a state-space recurrence over the serving
tick's rows needs for the rows the traffic really sent, whatever
implements them.

``shape["rows"]`` holds one entry per (tick, request): [pos0, n] = n tokens
that advance the request's state (a decode row is n = 1).  ``shape["ssm"]``
is the recurrence: ``heads`` of ``head_dim`` with ``d_state``, ``layers``,
the state's and the activations' item sizes, and ``qb``, the rows' width.
Per layer: the request's state ``heads * head_dim * d_state`` is read once
and written once a tick however many rows carry its tokens (they are
consecutive and chain on the chip); a token reads its ``xs`` (heads *
head_dim), ``B`` and ``C`` (d_state each) and ``dt`` (heads, fp32) and
writes its ``y``; its update and its read-out are 2 * head_dim * d_state
operations a head each.  The causal part of a block of m tokens computed
at once (a chunk row) is counted as the blocked form needs it: m (m + 1) /
2 pairs, each a ``B . C`` product (2 * d_state) and a weighted sum into
``y`` (2 * heads * head_dim); a decode row's is its one token's."""


def flops_per_token(ssm: dict) -> float:
    """Update and read-out of one token in one layer."""
    return 2.0 * 2.0 * ssm["heads"] * ssm["head_dim"] * ssm["d_state"]


def work(shape: dict) -> tuple:
    s = shape["ssm"]
    nH, hd, N, qb = s["heads"], s["head_dim"], s["d_state"], s["qb"]
    act, st = s.get("itemsize", 2), s.get("state_itemsize", 4)
    flops = nbytes = 0.0
    for _pos0, n in shape["rows"]:
        full, rest = divmod(int(n), qb)
        pairs = full * qb * (qb + 1) / 2.0 + rest * (rest + 1) / 2.0
        flops += n * flops_per_token(s) + pairs * 2.0 * (N + nH * hd)
        nbytes += (2.0 * st * nH * hd * N
                   + n * (act * (2 * nH * hd + 2 * N) + 4 * nH))
    return flops * s["layers"], nbytes * s["layers"]
