"""A percentile (or, with ``"percentile": "mean"``, the mean) of a series
of host-clock readings of the window."""

from benchmark import harness


def read(obs, series, percentile, scale=1.0):
    xs = obs.get("series", {}).get(series, [])
    if percentile == "mean":
        v = sum(xs) / len(xs) if xs else None
    else:
        v = harness.percentile(xs, percentile)
    return None if v is None else v * scale
