"""The benchmark's own reduction of a profiler trace (.xplane.pb) to what
the metrics read: device busy time, time by stable op name, and the idle
gaps by the harness span that covered them.  Needs nothing but JAX.

Two stages, so that the arithmetic is testable on hand-made events:
``read_xplane`` -> {"devices": [[(name, start_ns, dur_ns), ...], ...],
"spans": [(name, start_ns, dur_ns), ...]} and ``reduce_events`` on that.
"""

from __future__ import annotations

import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_SPAN_PREFIX = "bench."
SMALL_GAP_NS = 50_000
PALLAS = "pallas:"        # marks a kernel's name until reduce_events

# On a TPU an event of the "XLA Ops" line is named by its HLO instruction:
#   %fusion.250 = bf16[32,16,14336]{2,1,0:T(8,128)(2,1)S(1)} fusion(...), kind=kOutput, calls=...
#   %ragged_paged_attention.6 = bf16[32,8,64,128]{...} custom-call(...), custom_call_target="tpu_custom_call", ...
_HLO = re.compile(
    r"^%?(?P<inst>[\w\-.]+) = \(?(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]"
    r"[^ ]* (?:[^ ]+ )*?(?P<op>[\w\-]+)\(")
_KIND = re.compile(r"kind=(k\w+)")
_TRAIL_NUM = re.compile(r"(\.\d+|\.clone|\.sunk)+$")
_WRAPPERS = re.compile(r"^(?:transpose_|jvp_|vmap_|checkpoint_|remat\d*_)+")
PARENTS = ("while", "conditional", "call")     # their time is their bodies'


def stable_name(name: str) -> str:
    """A name for a device op that survives renumbering.  A Pallas kernel is
    named by the ``name=`` its pallas_call carries (marked PALLAS); any
    other op by opcode, fusion kind, whether it holds a matmul, and its
    (first) result's type and shape: ``fusion_kOutput_matmul_bf16_32_16_14336``.
    Ops that only hold other ops (while, conditional, call) get "" and are
    left out of the sums."""
    m = _HLO.search(name)
    if not m:
        return _TRAIL_NUM.sub("", name.lstrip("%").split(" ")[0])
    inst, op = _TRAIL_NUM.sub("", m.group("inst")), m.group("op")
    if op in PARENTS:
        return ""
    if op == "custom-call" and "tpu_custom_call" in name:
        return PALLAS + _WRAPPERS.sub("", inst).strip("_")
    bits = [op]
    kind = _KIND.search(name)
    if kind:
        bits.append(kind.group(1))
    if "convolution" in inst or (kind and kind.group(1) == "kOutput"):
        bits.append("matmul")
    bits += [m.group("dtype"), m.group("dims").replace(",", "_")]
    return "_".join(b for b in bits if b)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = [ln for ln in plane.lines if ln.name == _OPS_LINE]
            evs = []
            for ln in lines:
                for e in ln.events:
                    name = stable_name(e.name)
                    if name:
                        evs.append((name, float(e.start_ns),
                                    float(e.duration_ns)))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(_SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    return {"devices": [devices[k] for k in sorted(devices)], "spans": spans}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covering_span(spans: list, a: float, b: float) -> str:
    """The harness span that covers most of [a, b]."""
    best, best_cov = "no_harness_span", 0.0
    for name, s, d in spans:
        cov = min(b, s + d) - max(a, s)
        if cov > best_cov:
            best, best_cov = name, cov
    return best


def reduce_events(events: dict, top: int = 10) -> dict:
    """busy_s and window_s averaged over the devices, seconds by op name
    (averaged likewise), and idle gaps summed by covering span."""
    devs = [d for d in events["devices"] if d]
    if not devs:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "gaps": {},
                "n_devices": 0, "pallas": [],
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    n = len(devs)
    ops: dict = {}
    gaps: dict = {}
    pallas: set = set()
    busy = window = 0.0
    for evs in devs:
        merged = _union([(s, s + d) for _, s, d in evs if d > 0])
        busy += sum(e - s for s, e in merged)
        window += merged[-1][1] - merged[0][0]
        for name, _, d in evs:
            if name.startswith(PALLAS):
                name = name[len(PALLAS):]
                pallas.add(name)
            ops[name] = ops.get(name, 0.0) + d
        for (_, a), (b, _) in zip(merged, merged[1:]):
            key = ("gaps_under_50_us_between_operations"
                   if b - a < SMALL_GAP_NS
                   else _covering_span(events["spans"], a, b))
            gaps[key] = gaps.get(key, 0.0) + (b - a)
    to_s = 1e-9 / n
    return {
        "busy_s": busy * to_s, "window_s": window * to_s, "n_devices": n,
        "ops": {k: v * to_s for k, v in ops.items()},
        "gaps": {k: v * to_s for k, v in gaps.items()},
        "pallas": sorted(pallas),
        "breakdown": {
            "device_ops": [[k, v * to_s] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v * to_s] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]]},
    }


def exposed_seconds(events: dict, pattern: str, compute_exclude: str) -> float:
    """Seconds, averaged over devices, in which an op matching ``pattern``
    runs on a device while no other op (bar those matching
    ``compute_exclude``, e.g. other collectives) runs on it."""
    pat, excl = re.compile(pattern), re.compile(compute_exclude)
    total, n = 0.0, 0
    for evs in events["devices"]:
        if not evs:
            continue
        n += 1
        coll = _union([(s, s + d) for nm, s, d in evs if pat.search(nm)])
        comp = _union([(s, s + d) for nm, s, d in evs
                       if not pat.search(nm) and not excl.search(nm)])
        j = 0
        for a, b in coll:
            covered = 0.0
            while j < len(comp) and comp[j][1] <= a:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < b:
                covered += min(b, comp[k][1]) - max(a, comp[k][0])
                k += 1
            total += (b - a) - covered
    return total * 1e-9 / max(n, 1)


def reduce_trace(trace_dir: str) -> tuple:
    events = read_xplane(find_xplane(trace_dir))
    return events, reduce_events(events)
