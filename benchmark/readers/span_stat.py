"""A percentile of the durations of one harness span inside the window,
in milliseconds."""

from benchmark import harness


def read(obs, span, percentile):
    d = obs["spans"].durations(span, obs["t0"], obs["t1"])
    v = harness.percentile(d, percentile)
    return None if v is None else 1e3 * v
