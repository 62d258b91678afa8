"""The program's own span ring (``paddle_tpu.obs``) as the readers see it:
events on the harness's clock, spans paired, and from when on the ring is
whole.  Shared by the ``program_*`` readers; not a reader itself.

The ring is the one of the process the cell ran in.  Its timestamps are
microseconds after ``tracer.t0``, which is a ``time.perf_counter()``
reading: the axis of ``obs["t0"]`` / ``obs["t1"]``.  A program whose ring
is off (every commit before the ring was always on) has no tracer, and
every reader then returns None."""

from __future__ import annotations


class Ring:
    def __init__(self, events: list, t0: float, n_emitted: int,
                 capacity: int):
        self.events = [dict(e, t=t0 + e["ts"] * 1e-6) for e in events]
        dropped = n_emitted > capacity and self.events
        #: the ring holds every event from this time on
        self.whole_from = self.events[0]["t"] if dropped else float("-inf")

    def whole_since(self, t: float) -> bool:
        """False when events of the interval that starts at ``t`` have
        left the ring: a reader then returns None, never a short count."""
        return self.whole_from <= t

    def spans(self, names) -> list:
        """(name, tid, start, end, attrs of the end) of every closed span
        of the given names: B/E pairs per track, and X events."""
        out, open_b = [], {}
        for e in self.events:
            if e["name"] not in names:
                continue
            key = (e["tid"], e["name"])
            if e["ph"] == "B":
                open_b.setdefault(key, []).append(e["t"])
            elif e["ph"] == "E" and open_b.get(key):
                out.append((e["name"], e["tid"], open_b[key].pop(), e["t"],
                            e.get("args", {})))
            elif e["ph"] == "X":
                out.append((e["name"], e["tid"], e["t"],
                            e["t"] + e["dur"] * 1e-6, e.get("args", {})))
        return out


def load():
    """The process's ring, or None where the program has none."""
    from paddle_tpu import obs

    tr = obs.tracer()
    if tr is None:
        return None
    events, n_emitted = tr.snapshot()
    return Ring(events, tr.t0, n_emitted, tr.capacity)
