"""Device time of ops that are neither matmuls nor Pallas kernels and
whose result has one of the shapes the run recorded under
obs["shapes"][<shapes>] (lists of dims), over device busy time, in
percent.  Reads the result's shape from the op's stable name."""

import re


def read(obs, shapes, exclude="_matmul_"):
    tr, dims = obs.get("trace"), obs.get("shapes", {}).get(shapes)
    if not tr or not tr["busy_s"] or not dims:
        return None
    tails = tuple("_" + "_".join(str(d) for d in shape) for shape in dims)
    exc = re.compile(exclude)
    t = sum(s for name, s in tr["ops"].items()
            if name.endswith(tails) and name not in tr["pallas"]
            and not exc.search(name))
    return 100.0 * t / tr["busy_s"]
