"""One attribute of a span's end over another, in percent: each summed
over the spans of that name in the program's ring that lie inside the
window.  None where the program records neither attribute (a commit
from before it did)."""

from benchmark import harness

_ring = harness.load_module("readers/program_ring.py")


def read(obs, span, num, den):
    ring = _ring.load()
    if ring is None or not ring.whole_since(obs["t0"]):
        return None
    t0, t1 = obs["t0"], obs["t1"]
    ends = [attrs for _, _, a, b, attrs in ring.spans({span})
            if a >= t0 and b <= t1 and num in attrs and den in attrs]
    total = sum(e[den] for e in ends)
    return 100.0 * sum(e[num] for e in ends) / total if total else None
