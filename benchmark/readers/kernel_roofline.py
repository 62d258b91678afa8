"""A kernel's share of its roofline: the least time the chip could take
for the work the traffic asked of it (the larger of operations over peak
FLOP/s and bytes over peak bytes/s, both from ``work/<work>.py`` on the
shapes the run recorded under obs["shapes"][<shapes>]), over the device
time of the ops matching ``pattern``.  Per chip."""

import re

from benchmark import harness


def read(obs, pattern, work, shapes):
    tr, shape = obs.get("trace"), obs.get("shapes", {}).get(shapes)
    if not tr or shape is None or "peaks" not in obs:
        return None
    pat = re.compile(pattern)
    t = sum(s for name, s in tr["ops"].items() if pat.search(name))
    if t <= 0:
        return None
    flops, nbytes = harness.load_module("work/" + work + ".py").work(shape)
    peaks = obs["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
