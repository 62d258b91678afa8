"""Time in which an op matching ``pattern`` runs on a device while no
compute op runs on it, over the traced window, in percent."""

import re

from benchmark import trace_reduce


def read(obs, pattern, not_compute):
    tr, ev = obs.get("trace"), obs.get("trace_events")
    if not tr or not ev or not tr["window_s"]:
        return None
    if not any(re.search(pattern, n) for n in tr["ops"]):
        return None
    return 100.0 * trace_reduce.exposed_seconds(ev, pattern, not_compute) \
        / tr["window_s"]
