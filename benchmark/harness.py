"""Shared pieces of the benchmark harness: finding a cell's files by
name, the chip check, the peaks table, host spans, the compile counter
and the arithmetic of percentiles.

Everything a cell needs beyond this file is data found by name from
BENCHMARK.json: ``configs/<config>.json`` (+ ``.reference.py``),
``traffic/<traffic>.json``, ``metrics/<metric>.json``; code is looked up
by the names those files give (``systems/``, ``kinds/``, ``readers/``,
``work/``).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK_DIR = os.path.join(BENCH, ".work")          # gitignored


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device kind,
    unknown cell): exit non-zero, print no result line."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


_MODULES: dict = {}


def load_module(relpath: str):
    """Import ``benchmark/<relpath>`` by file path (names may hold dots
    and dashes, so these are not package imports)."""
    path = os.path.join(BENCH, relpath)
    if path not in _MODULES:
        name = "benchmark_file_" + "".join(
            c if c.isalnum() else "_" for c in relpath)
        spec = importlib.util.spec_from_file_location(name, path)
        if spec is None or not os.path.exists(path):
            raise BenchError(f"no such benchmark file: {relpath}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def find_cell(bm: dict, workload: str) -> tuple:
    """(cell, config file contents, traffic file contents)."""
    cells = [w for w in bm["workloads"] if w["name"] == workload]
    if not cells:
        raise BenchError(f"unknown workload '{workload}'; BENCHMARK.json has "
                         f"{[w['name'] for w in bm['workloads']]}")
    cell = cells[0]
    entry = [c for c in bm["configs"] if c["name"] == cell["config"]][0]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def reference_for(config_name: str):
    return load_module(os.path.join("configs", config_name + ".reference.py"))


def metrics_for(bm: dict, workload: str, section: str) -> list:
    """Entries of ``end_to_end`` / ``per_layer`` that this cell reports: an
    entry without ``workloads`` belongs to every cell that reports the
    end-to-end metric it moves (or, end to end, to every cell)."""
    e2e = {m["name"]: m for m in bm["end_to_end"]}

    def in_cell(m: dict) -> bool:
        if "workloads" in m:
            return workload in m["workloads"]
        if section == "end_to_end":
            return True
        return in_cell(e2e[m["moves"]])

    return [m for m in bm[section] if in_cell(m)]


# -- the chip --------------------------------------------------------------

def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchError(
            f"device kind '{device_kind}' is not in benchmark/peaks.json: "
            f"add it with its source; there is no default peak")
    return table[device_kind]


def require_chips(chips: int) -> list:
    """The cell's devices, or BenchError: a benchmark number comes from the
    chip the cell names, never from a CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform "
                         f"'{devs[0].platform}' ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips; JAX found {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# -- caches ----------------------------------------------------------------

def setup_caches() -> dict:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), and the benchmark's own
    autotune table adopted so that no run sweeps."""
    import jax

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = from_env or os.path.join(ROOT, ".jax_cache")
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    # the program's registry reads artifacts/pallas_autotune_committed.json
    # first and this per-checkout file second; it is seeded from the table
    # the benchmark ships, and sweeps (a kernel edited since) land in it
    os.makedirs(WORK_DIR, exist_ok=True)
    work_table = os.path.join(WORK_DIR, "pallas_autotune.json")
    shipped = os.path.join(BENCH, "autotune", "table.json")
    if not os.path.exists(work_table) and os.path.exists(shipped):
        shutil.copyfile(shipped, work_table)
    from paddle_tpu.core.flags import GLOBAL_FLAGS

    GLOBAL_FLAGS.set("pallas_autotune_cache", work_table)
    return {"compile_cache": cache_dir, "autotune_table": work_table}


class CompileCounter:
    """Compile requests (cache hit or miss alike: each is a program the
    window should not have asked for) and autotune sweeps, from JAX's
    monitoring events and the program's autotune registry."""

    def __init__(self):
        import jax

        self.requests = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        from paddle_tpu.ops.pallas import autotune

        return {"requests": self.requests, "misses": self.misses,
                "sweeps": autotune.GLOBAL_AUTOTUNE.sweeps}


# -- spans -----------------------------------------------------------------

class Spans:
    """Harness spans around the calls into each layer: kept in memory on
    the host clock, and mirrored into the profiler's trace (a
    TraceAnnotation costs nothing when no trace is running), where the
    reduction attributes idle gaps to them."""

    def __init__(self):
        self.records: list = []        # (name, t0, t1) perf_counter seconds

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, t_from: float = 0.0,
                  t_to: float = float("inf")) -> list:
        return [b - a for n, a, b in self.records
                if n == name and a >= t_from and b <= t_to]


# -- arithmetic ------------------------------------------------------------

def percentile(xs, p: float):
    """Linear-interpolated percentile (numpy's default), None when there is
    nothing to read.  Copied from inference/loadgen/metrics.py, which
    returns 0.0 for an empty list; a metric here is then left out."""
    import numpy as np

    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), p))


def seed_key(seed: int, impl: str = "rbg"):
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl=impl)
    return jax.random.fold_in(key, seed >> 31)


def np_rng(seed: int, *stream: int):
    """A numpy generator for one named stream of the seed."""
    import numpy as np

    return np.random.default_rng([int(seed), *map(int, stream)])
