"""The whole step's share of the chips' peak: model FLOPs of the traced
segment (counter ``flops``: what the valid tokens need, recomputation and
padding not counted) over traced window x peak x chips, in percent."""


def read(obs, flops):
    tr, n = obs.get("trace"), obs.get("counters", {}).get(flops)
    if not tr or not tr["window_s"] or n is None or "peaks" not in obs:
        return None
    return 100.0 * n / (tr["window_s"] * obs["peaks"]["bf16_flops_per_s"]
                        * obs["chips"])
