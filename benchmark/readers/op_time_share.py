"""Device time of the ops whose stable name matches ``pattern`` (and not
``exclude``; with ``pallas`` true or false, only Pallas kernels or only
other ops), over device busy time, in percent."""

import re


def read(obs, pattern, exclude=None, pallas=None):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    pat = re.compile(pattern)
    exc = re.compile(exclude) if exclude else None
    t = sum(s for name, s in tr["ops"].items()
            if pat.search(name) and not (exc and exc.search(name))
            and (pallas is None or (name in tr["pallas"]) == pallas))
    return 100.0 * t / tr["busy_s"]
