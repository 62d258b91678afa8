"""A count of the window over the window's measured seconds (t0..t1 on
the host clock, the whole of the work and the whole of the time)."""


def read(obs, count, per_chip=False):
    n = obs.get(count)
    if n is None or obs["t1"] <= obs["t0"]:
        return None
    rate = n / (obs["t1"] - obs["t0"])
    return rate / obs["chips"] if per_chip else rate
