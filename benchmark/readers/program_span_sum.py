"""Seconds summed over the spans of the given names that end before the
window opens: what set-up spent in them.  Times ``scale``."""

from benchmark import harness

_ring = harness.load_module("readers/program_ring.py")


def read(obs, spans, scale=1.0):
    ring = _ring.load()
    if ring is None or not ring.whole_since(float("-inf")):
        return None
    found = [b - a for _, _, a, b, _ in ring.spans(set(spans))
             if b <= obs["t0"]]
    return scale * sum(found) if found else None
