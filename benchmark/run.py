"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name from BENCHMARK.json,
builds the system under test, warms it up (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard
output.  Without the chips the cell asks for, or on a device kind that
benchmark/peaks.json does not hold, it exits non-zero and prints no
result: no number from a CPU is ever printed under a metric's name.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import contextlib
import json
import os
import shutil
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


class _Profile:
    """Profiler trace of a short segment, written under .work and reduced
    by the benchmark's own reader."""

    def __init__(self, tag: str):
        self.dir = os.path.join(harness.WORK_DIR, "trace-" + tag)
        self.result = None

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        from benchmark import trace_reduce

        jax.profiler.stop_trace()
        if exc[0] is None:
            self.result = trace_reduce.reduce_trace(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return False


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices=None, t_start: float | None = None,
             control: bool = False) -> dict:
    """Everything of a run but the look for a chip and the printing.
    ``devices`` is given only by tests, which drive the rest of a run on
    CPU devices; their lines are marked and carry no device metric.
    ``control`` (benchmark/limits.py) also reads the control and the
    planted faults against the reference; a benchmark run never does."""
    bm = harness.load_benchmark()
    cell, config, traffic = harness.find_cell(bm, workload)
    on_chip = devices is None
    if on_chip:
        devices = harness.require_chips(cell["chips"])
    harness.setup_caches()
    ctx = types.SimpleNamespace(
        bm=bm, cell=cell, config=config, traffic=traffic, devices=devices,
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        spans=harness.Spans(), compiles=harness.CompileCounter(),
        t_start=_T_START if t_start is None else t_start, control=control,
        profiler=lambda: _Profile(workload))
    kind = harness.load_module("kinds/" + traffic["kind"] + ".py")
    obs = kind.run(ctx)
    obs["spans"] = ctx.spans
    obs["chips"] = cell["chips"]
    if on_chip:
        obs["peaks"] = harness.peaks_for(devices[0].device_kind)

    from benchmark import metrics

    section = "per_layer" if trace else "end_to_end"
    values = {}
    for m in harness.metrics_for(bm, workload, section):
        v = metrics.read(m["name"], obs)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    if not on_chip:
        # a rehearsal on CPU devices: keys only, never a device number
        values = {k: {"value": None, "unit": v["unit"]}
                  for k, v in values.items()}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": obs["memory_peak_bytes"]}
    line = {"correct": bool(obs["check"]["correct"]),
            "attempted": obs["attempted"], "failed": obs["failed"],
            "metrics": values, "device": device}
    if trace and obs.get("trace"):
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        line["breakdown"] = obs["trace"]["breakdown"]
    if obs.get("detail"):
        line["detail"] = obs["detail"]
    line["rehearsal_on_cpu"] = not on_chip
    if control:
        line["control"] = obs["check"]["control"]
    line["compared"] = obs["check"]["numbers"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        with contextlib.redirect_stdout(sys.stderr):
            line = run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace))
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
