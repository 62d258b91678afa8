"""One counter over another, in percent."""


def read(obs, num, den):
    c = obs.get("counters", {})
    if c.get(num) is None or not c.get(den):
        return None
    return 100.0 * c[num] / c[den]
