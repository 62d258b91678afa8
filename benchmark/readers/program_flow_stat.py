"""A percentile, over requests, of the time from one lifecycle event to
another on the same id (``then`` - ``first``, each the request's first of
that name), for requests whose ``then`` falls in the window.  Seconds
times ``scale``."""

from benchmark import harness

_ring = harness.load_module("readers/program_ring.py")


def read(obs, first, then, percentile, scale=1.0):
    ring = _ring.load()
    if ring is None or not ring.whole_since(obs["t0"]):
        return None
    at: dict = {}                  # id -> {event: time of its first}
    for e in ring.events:
        if e.get("cat") == "req":
            at.setdefault(e["id"], {}).setdefault(e["args"]["event"], e["t"])
    gaps = []
    for seen in at.values():
        b = seen.get(then)
        if b is None or not obs["t0"] <= b < obs["t1"]:
            continue
        if first not in seen:
            if ring.whole_since(float("-inf")):
                continue           # a flow that never had it (a resubmit)
            return None            # it began before the ring does
        gaps.append(b - seen[first])
    v = harness.percentile(gaps, percentile)
    return None if v is None else v * scale
