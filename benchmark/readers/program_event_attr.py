"""An attribute of the last instant event of a name that the program
recorded before the window closed."""

from benchmark import harness

_ring = harness.load_module("readers/program_ring.py")


def read(obs, event, attr):
    ring = _ring.load()
    if ring is None:
        return None
    # events are dropped oldest first, so the last one is there if any is
    for e in reversed(ring.events):
        if e["name"] == event and e["ph"] == "i" and e["t"] <= obs["t1"]:
            return e.get("args", {}).get(attr)
    return None
