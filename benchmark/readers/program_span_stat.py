"""A percentile of the durations of one span of the program's ring, over
the spans that lie inside the window.  With ``minus``, each duration is
the span's less the listed spans inside it on the same track: its self
time.  Seconds times ``scale``."""

from benchmark import harness

_ring = harness.load_module("readers/program_ring.py")


def read(obs, span, percentile, minus=(), scale=1.0):
    ring = _ring.load()
    if ring is None or not ring.whole_since(obs["t0"]):
        return None
    t0, t1 = obs["t0"], obs["t1"]
    inner = ring.spans(set(minus))
    durs = []
    for _, tid, a, b, _ in ring.spans({span}):
        if a < t0 or b > t1:
            continue
        inside = sum(d - c for _, tid2, c, d, _ in inner
                     if tid2 == tid and a <= c and d <= b)
        durs.append(b - a - inside)
    v = harness.percentile(durs, percentile)
    return None if v is None else v * scale
