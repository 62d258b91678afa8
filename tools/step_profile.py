"""Capture a device trace of the flagship train step and break the step
time into kernel categories + inter-kernel gaps.

The profiler rides jax.profiler.trace; the perfetto/chrome trace json
it writes is parsed directly, so no tensorflow/xplane dependency. Not
run on a locally attached chip yet (PERF.md, Chip status).

Usage: PYTHONPATH=. python tools/step_profile.py [--steps 3] [--out DIR]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os


def categorize(e: dict) -> str:
    """Category = kernel family. Pallas kernels carry their jit name;
    everything else falls back to the trace's own hlo_category plus the
    source line for optimizer-vs-model attribution."""
    name = e["name"].lower()
    args = e.get("args", {})
    if "flash_bwd" in name:
        return "flash bwd"
    if "flash_fwd" in name:
        return "flash fwd"
    if "fused_ce" in name:
        return "fused CE"
    cat = args.get("hlo_category", "uncategorized")
    if cat == "loop fusion" and "train_step.py" in args.get("source", ""):
        return "optimizer update"
    if name.startswith("copy"):
        return "relayout copies"
    return cat


def parse_trace(trace_dir: str, n_steps: int) -> dict:
    paths = glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {trace_dir}")
    with gzip.open(sorted(paths)[-1], "rt") as f:
        trace = json.load(f)
    events = trace["traceEvents"]

    # device-side op events live on the "XLA Ops" thread of the TPU pid
    # (the "Steps"/"XLA Modules" threads overlay the same time — summing
    # all device tracks would triple-count)
    dev_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname = e.get("args", {}).get("name", "")
            if "TPU" in pname or "/device:" in pname or "Chip" in pname:
                dev_pids.add(e["pid"])
    xla_ops = {(e["pid"], e["tid"]) for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"
               and e["pid"] in dev_pids
               and e["args"]["name"] == "XLA Ops"}
    kernels = [e for e in events
               if e.get("ph") == "X" and (e.get("pid"), e.get("tid"))
               in xla_ops and e.get("dur", 0) > 0]
    if not kernels:
        raise RuntimeError("no device kernel events found "
                           f"(pids seen: {sorted(dev_pids)})")

    # bucket by category; gaps = busy-span minus kernel time, computed
    # on a per-track merged timeline so parallel tracks don't double-count
    by_cat: dict = collections.defaultdict(float)
    for e in kernels:
        by_cat[categorize(e)] += e["dur"]

    # merged busy interval union across device tracks
    ivs = sorted((e["ts"], e["ts"] + e["dur"]) for e in kernels)
    merged, cur = [], list(ivs[0])
    for s, t in ivs[1:]:
        if s <= cur[1]:
            cur[1] = max(cur[1], t)
        else:
            merged.append(tuple(cur))
            cur = [s, t]
    merged.append(tuple(cur))
    busy = sum(t - s for s, t in merged)
    span = merged[-1][1] - merged[0][0]

    out = {
        "n_steps": n_steps,
        "span_ms_per_step": round(span / 1e3 / n_steps, 2),
        "busy_ms_per_step": round(busy / 1e3 / n_steps, 2),
        "gap_ms_per_step": round((span - busy) / 1e3 / n_steps, 2),
        "categories_ms_per_step": {
            k: round(v / 1e3 / n_steps, 2)
            for k, v in sorted(by_cat.items(), key=lambda kv: -kv[1])},
    }

    # largest individual gaps with their neighbours — where to look
    gaps = []
    flat = sorted(kernels, key=lambda e: e["ts"])
    for a, b in zip(flat, flat[1:]):
        g = b["ts"] - (a["ts"] + a["dur"])
        if g > 0:
            gaps.append((g, a["name"][:60], b["name"][:60]))
    gaps.sort(reverse=True)
    out["top_gaps_us"] = [
        {"gap_us": round(g, 1), "after": a, "before": b}
        for g, a, b in gaps[:12]]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default="/tmp/step_profile")
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args()

    import dataclasses

    import jax
    import numpy as np

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.distributed.process_mesh import build_mesh
    from paddle_tpu.models.gpt import gpt_presets
    from paddle_tpu.parallel import make_sharded_train_step

    enable_compile_cache()
    cfg = dataclasses.replace(gpt_presets("gpt3-350m"), unroll=True,
                              remat=False)
    mesh = build_mesh((1, 1, 1), ("dp", "pp", "mp"))
    step, params, opt = make_sharded_train_step(
        cfg, mesh, lr=1e-4, n_microbatches=1, zero1=False,
        m_dtype="bfloat16", v_dtype="bfloat16")
    rng = np.random.RandomState(0)
    toks = step.put_batch(rng.randint(0, cfg.vocab_size,
                                      size=(args.batch, cfg.seq_len)))
    labs = step.put_batch(rng.randint(0, cfg.vocab_size,
                                      size=(args.batch, cfg.seq_len)))
    for _ in range(3):
        loss, params, opt = step(params, opt, toks, labs)
    float(loss)  # value fetch = device sync

    with jax.profiler.trace(args.out):
        for _ in range(args.steps):
            loss, params, opt = step(params, opt, toks, labs)
        float(loss)

    res = parse_trace(args.out, args.steps)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
