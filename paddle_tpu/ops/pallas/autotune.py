"""Persistent Pallas autotune registry: sweep once, cache forever.

TPU-native analog of the reference's ``kernels/autotune/cache.h``: each
Pallas kernel asks the registry for its block/grid config instead of
hardcoding one.  On first use of a (kernel, shape-bucket, dtype,
device-kind) combination the registry times every candidate config with
synthetic operands, picks the fastest, and persists the winner to a JSON
cache under ``artifacts/`` — so tuned configs survive process restart
and production cold-start pays the sweep exactly once per chip kind.

Contract (every adopter follows it):

- ``candidates[0]`` is the kernel's hand-tuned legacy default.  It is
  returned verbatim whenever the registry is disabled or sweeping is off
  for this backend — so behavior without a cache is bit-identical to
  the pre-autotune code.  In a sweep it must measure: a default, or any
  ``"kernel..."`` arm, that fails to compile or run raises instead of
  being skipped; other candidates that fail are recorded with their
  error text under the entry's ``refused``.
- The cache key embeds the **device kind** and the **kernel source
  hash**: a cache file copied from a different chip, or one predating a
  kernel edit, misses cleanly instead of silently applying wrong block
  shapes (ISSUE 6 satellite f).
- ``tuned()`` executes at trace time inside jitted wrappers, where live
  operands are tracers; sweeps therefore run the candidate measure in a
  fresh thread (trace state is thread-local) on synthetic operands
  built from static shapes.
- Sweeping is gated by ``FLAGS_pallas_autotune_sweep`` ('auto' = TPU
  only): CPU test runs never sweep, never write the cache, and always
  see the defaults.

v2 adds a **program level** on top of the per-kernel entries: the fusion
pass (paddle_tpu/compiler/) keys a whole jitted step by a stable jaxpr
hash and commits the step's fusion decisions plus every per-kernel entry
its trace resolved.  A restarted session that traces the same program
adopts the committed entries up front, so every ``tuned()`` call inside
the trace hits without sweeping — the compiled plan replays.  The file
schema is additive: a version-1 file still loads (entries only, no
programs), and v2 files keep the same ``entries`` table v1 readers
wrote.

All file writes take an ``fcntl`` lock on a ``<cache>.lock`` sidecar
around the read-merge-rename, so concurrent fleet engines sharing one
``artifacts/`` can't interleave their merges and drop each other's
winners (two writers each read-before-either-writes used to keep only
the last one's key).

Caveat (same as the flash-flag note in flash_attention.py): configs are
resolved at trace time, and the jit cache does not key on flags or on
this registry — flipping flags or deleting the cache mid-process does
not retrace already-compiled programs.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import hashlib
import inspect
import json
import os
import threading
import time
import weakref
from typing import Any, Callable, Sequence

__all__ = ["AutotuneRegistry", "GLOBAL_AUTOTUNE", "tuned", "stats",
           "reset_stats", "source_hash", "cache_path"]

_CACHE_VERSION = 2


# this file lives at paddle_tpu/ops/pallas/autotune.py
_ARTIFACTS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "artifacts")

# The tracked table: configs a chip run resolved, committed so that two
# fresh checkouts of one commit run the same program instead of each
# re-sweeping best-of-3 wall timings.  The registry reads it and never
# writes it; misses go to the per-checkout file below (gitignored).
# Refresh: run chip_smoke.py on the chip and commit the table it leaves
# in chiprun_out/pallas_autotune.json under this name.
COMMITTED_PATH = os.path.join(_ARTIFACTS, "pallas_autotune_committed.json")


def cache_path() -> str:
    """Resolve the writable per-checkout cache file (flag override or
    repo default)."""
    from ...core.flags import GLOBAL_FLAGS

    p = (GLOBAL_FLAGS.get("pallas_autotune_cache")
         if GLOBAL_FLAGS.has("pallas_autotune_cache") else "")
    return p or os.path.join(_ARTIFACTS, "pallas_autotune.json")


def source_hash(*objs) -> str:
    """Stable hash of the kernel implementation: sha1 over the source of
    the given functions.  Adopters key their cache entries on it so an
    edited kernel invalidates its persisted configs instead of applying
    block shapes tuned for different code."""
    h = hashlib.sha1()
    for o in objs:
        try:
            h.update(inspect.getsource(o).encode())
        except (OSError, TypeError):  # builtins / REPL: name is the best id
            h.update(getattr(o, "__name__", repr(o)).encode())
    return h.hexdigest()[:16]


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


@contextlib.contextmanager
def _file_lock(path: str):
    """Exclusive advisory lock on a ``<path>.lock`` sidecar (the cache
    file itself is replaced atomically, so it can't carry the lock).
    Degrades to unlocked on platforms without fcntl or unwritable
    directories — no worse than the pre-lock behavior."""
    try:
        import fcntl
    except ImportError:  # non-posix: single-writer assumption stands
        yield
        return
    lf = None
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        lf = open(path + ".lock", "a+")
        fcntl.flock(lf.fileno(), fcntl.LOCK_EX)
    except OSError:
        if lf is not None:
            lf.close()
            lf = None
    try:
        yield
    finally:
        if lf is not None:
            try:
                import fcntl

                fcntl.flock(lf.fileno(), fcntl.LOCK_UN)
            except OSError:
                pass
            lf.close()


def _read_cache_file(path: str) -> tuple[dict, dict]:
    """(entries, programs) from a v1 or v2 cache file; missing/corrupt
    reads as empty.  v1 files carry entries only."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}, {}
    if not isinstance(data, dict) or data.get("version") not in (1, 2):
        return {}, {}
    entries = dict(data.get("entries", {}))
    programs = dict(data.get("programs", {})) if data.get("version") == 2 \
        else {}
    return entries, programs


class AutotuneRegistry:
    """Process-wide sweep-and-cache store behind :func:`tuned`."""

    def __init__(self, path: str | None = None,
                 committed: str = COMMITTED_PATH):
        self._path_override = path
        self._committed_path = committed
        self._lock = threading.RLock()
        self._entries: dict[str, dict] | None = None   # lazy file load
        self._programs: dict[str, dict] = {}
        self._committed: tuple[dict, dict] | None = None  # lazy read
        self._adopted: dict[str, dict] = {}   # program-injected entries
        self._capture: dict[str, dict] | None = None
        self._resolved: dict[str, Any] = {}   # key -> config, this process
        self._announced_on = lambda: None     # weakref: the tracer told
        self._announced: set[str] = set()     # keys told to that tracer
        self._loaded_from: str | None = None
        self.hits = 0
        self.misses = 0
        self.swept_keys: list[str] = []   # one per sweep run, in order
        self.sweep_time_s = 0.0
        self.program_hits = 0

    @property
    def sweeps(self) -> int:
        return len(self.swept_keys)

    # -- persistence --------------------------------------------------------

    def _path(self) -> str:
        return self._path_override or cache_path()

    def _load(self) -> dict[str, dict]:
        path = self._path()
        if self._entries is not None and self._loaded_from == path:
            return self._entries
        self._entries, self._programs = _read_cache_file(path)
        self._loaded_from = path
        return self._entries

    def _committed_tables(self) -> tuple[dict, dict]:
        """(entries, programs) of the tracked table; read once."""
        if self._committed is None:
            self._committed = _read_cache_file(self._committed_path)
        return self._committed

    def committed_covers(self, device_kind: str) -> bool:
        """True when the tracked table holds entries for this device
        kind — a sweep on such a device means the table is stale."""
        return any(k.split("|")[1] == device_kind
                   for k in self._committed_tables()[0])

    def _persist(self, mutate: Callable[[dict, dict], None]) -> None:
        """Locked read-merge-write: re-read the file under the sidecar
        lock, apply ``mutate(entries, programs)`` to the merged view,
        and atomically replace — concurrent processes sweeping different
        kernels (or committing different programs) keep each other's
        work."""
        path = self._path()
        with _file_lock(path):
            entries, programs = _read_cache_file(path)
            mutate(entries, programs)
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump({"version": _CACHE_VERSION, "entries": entries,
                               "programs": programs}, f,
                              indent=1, sort_keys=True)
                os.replace(tmp, path)
            except OSError:
                pass  # read-only checkout: keep the in-memory view only
        self._entries, self._programs = entries, programs
        self._loaded_from = path

    # -- policy -------------------------------------------------------------

    @staticmethod
    def _enabled() -> bool:
        from ...core.flags import GLOBAL_FLAGS

        return (bool(GLOBAL_FLAGS.get("pallas_autotune"))
                if GLOBAL_FLAGS.has("pallas_autotune") else True)

    @staticmethod
    def _sweep_enabled() -> bool:
        from ...core.flags import GLOBAL_FLAGS

        mode = (str(GLOBAL_FLAGS.get("pallas_autotune_sweep"))
                if GLOBAL_FLAGS.has("pallas_autotune_sweep") else "auto")
        if mode in ("1", "true", "True"):
            return True
        if mode in ("0", "false", "False"):
            return False
        import jax

        return jax.default_backend() == "tpu"

    # -- the API ------------------------------------------------------------

    def tuned(self, kernel: str, bucket: str, dtype: Any,
              candidates: Sequence[Any],
              measure: Callable[[Any], float] | None = None,
              source: str = "") -> Any:
        """Return the config to use for one kernel-call site.

        ``candidates[0]`` is the legacy default; ``measure(candidate)``
        returns wall ms for one candidate (called only when sweeping).
        """
        if not candidates:
            raise ValueError(f"autotune '{kernel}': empty candidate list")
        default = candidates[0]
        if not self._enabled():
            return default
        key = f"{kernel}|{_device_kind()}|{bucket}|{dtype}"
        with self._lock:
            entries = self._load()
            # the committed table outranks the per-checkout file, so a
            # local re-sweep can never shadow what the commit pinned
            for table in (self._adopted, self._committed_tables()[0],
                          entries):
                entry = table.get(key)
                if entry is not None and entry.get("source") == source:
                    self.hits += 1
                    self._record(key, entry, "hit")
                    return entry["config"]
            # stale-source entries fall through: re-sweep or default
            self.misses += 1
            if (measure is None or len(candidates) < 2
                    or not self._sweep_enabled()):
                self._record(key, {"config": default, "source": source},
                             "default")
                return default
        # the sweep runs unlocked: it compiles and times on the device
        t0 = time.perf_counter()
        timings, refused = [], {}
        for i, cand in enumerate(candidates):
            try:
                ms = _measure_outside_trace(measure, cand)
            except Exception as e:  # noqa: BLE001 -- re-raised or recorded
                # The default and every Pallas arm must compile: a
                # refusal there is a broken kernel, and skipping it
                # would leave the program on another arm without a word.
                # Any other candidate (a larger tile) may be infeasible;
                # its text stays in the entry.
                if i == 0 or str(cand).startswith("kernel"):
                    raise RuntimeError(
                        f"autotune '{key}': candidate {cand!r} failed to "
                        f"compile or run") from e
                refused[repr(cand)] = f"{type(e).__name__}: {e}"[:500]
                ms = float("inf")
            timings.append(ms)
        best = min(range(len(candidates)), key=timings.__getitem__)
        elapsed = time.perf_counter() - t0
        entry = {"config": candidates[best], "ms": round(timings[best], 4),
                 "source": source, "sweep_s": round(elapsed, 3),
                 "candidates": len(candidates)}
        if refused:
            entry["refused"] = refused
        with self._lock:
            self.swept_keys.append(key)
            self.sweep_time_s += elapsed
            self._persist(lambda e, p: e.__setitem__(key, entry))
            self._record(key, entry, "sweep")
        return candidates[best]

    # -- per-program layer (v2; driven by paddle_tpu/compiler) --------------

    def _record(self, key: str, entry: dict, how: str) -> None:
        """Every resolution passes here: ``how`` is ``hit`` (a table had
        the entry), ``sweep`` (measured now) or ``default`` (no entry
        and no sweep: ``candidates[0]``).  The ring gets one instant
        ``autotune.resolve`` per key per tracer, so a flight record or
        a Chrome export says which form a compiled step took."""
        from ... import obs as _obs

        self._resolved[key] = entry["config"]
        if self._capture is not None:
            self._capture[key] = dict(entry)
        tracer = _obs.tracer()
        if tracer is None:
            return
        if tracer is not self._announced_on():  # a fresh ring: tell again
            self._announced_on, self._announced = weakref.ref(tracer), set()
        if key not in self._announced:
            self._announced.add(key)
            kernel, _, bucket = key.split("|", 2)
            _obs.instant("autotune.resolve", kernel=kernel, bucket=bucket,
                         config=str(entry["config"]), how=how)

    def resolved(self) -> dict[str, Any]:
        """Every (key -> config) :meth:`tuned` has resolved in this
        process — hit, sweep winner or default.  What chip_smoke.py
        prints, and what two checkouts of one commit must agree on."""
        with self._lock:
            return dict(self._resolved)

    def begin_capture(self) -> bool:
        """Start recording every entry :meth:`tuned` resolves (hit,
        sweep winner, or default) until :meth:`end_capture` — the fusion
        pass brackets one program trace with this pair.  Returns False
        when a capture is already active (a fused model apply nested
        inside a fused train step records into the outer program)."""
        with self._lock:
            if self._capture is not None:
                return False
            self._capture = {}
            return True

    def end_capture(self) -> dict[str, dict]:
        with self._lock:
            cap, self._capture = self._capture, None
            return cap or {}

    def program_lookup(self, phash: str) -> dict | None:
        with self._lock:
            self._load()
            return (self._committed_tables()[1].get(phash)
                    or self._programs.get(phash))

    def adopt_program(self, phash: str, source: str) -> bool:
        """Inject a committed program's per-kernel entries into the
        in-memory view so the upcoming trace's ``tuned()`` calls hit
        without sweeping.  Refused (False) when the record is missing,
        was committed by different compiler/kernel sources, or belongs
        to a different device kind — stale plans re-sweep instead of
        replaying wrong configs."""
        with self._lock:
            self._load()
            rec = (self._committed_tables()[1].get(phash)
                   or self._programs.get(phash))
            if (not isinstance(rec, dict) or rec.get("source") != source
                    or rec.get("device") != _device_kind()):
                return False
            self._adopted.update(rec.get("entries", {}))
            self.program_hits += 1
            return True

    def program_commit(self, phash: str, fusion: list, entries: dict,
                       source: str) -> None:
        """Persist one program record: the fusion decisions the pass
        made plus every per-kernel entry the trace resolved.  The
        entries also merge into the flat v1 table — program records and
        kernel entries share one key space, so a restarted process hits
        them through the ordinary :meth:`tuned` path even for calls
        that fire before the program hash is known (during the plan
        trace itself)."""
        rec = {"device": _device_kind(), "source": source,
               "fusion": list(fusion), "entries": dict(entries)}

        def mutate(e, p):
            for k, v in rec["entries"].items():
                e.setdefault(k, v)
            p[phash] = rec

        with self._lock:
            self._persist(mutate)

    # -- stats --------------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {"autotune_cache_hits": self.hits,
                    "autotune_cache_misses": self.misses,
                    "autotune_sweeps": self.sweeps,
                    "autotune_sweep_time_s": round(self.sweep_time_s, 3),
                    "autotune_program_hits": self.program_hits}

    def reset_stats(self) -> None:
        with self._lock:
            self.hits = self.misses = 0
            self.swept_keys = []
            self.sweep_time_s = 0.0
            self.program_hits = 0

    def invalidate(self) -> None:
        """Drop the in-memory view, including program-adopted entries
        (next lookup re-reads the file)."""
        with self._lock:
            self._entries = None
            self._programs = {}
            self._committed = None
            self._adopted = {}
            self._loaded_from = None


GLOBAL_AUTOTUNE = AutotuneRegistry()


def tuned(kernel: str, bucket: str, dtype: Any, candidates: Sequence[Any],
          measure: Callable[[Any], float] | None = None,
          source: str = "") -> Any:
    """Module-level convenience over the process-global registry."""
    return GLOBAL_AUTOTUNE.tuned(kernel, bucket, dtype, candidates,
                                 measure=measure, source=source)


def stats() -> dict:
    return GLOBAL_AUTOTUNE.stats()


def reset_stats() -> None:
    GLOBAL_AUTOTUNE.reset_stats()


def _measure_outside_trace(measure: Callable[[Any], float], cand) -> float:
    """``tuned()`` runs at trace time, inside whatever jit is tracing
    its caller, where array constructors stage into that trace instead
    of executing.  JAX's trace state is thread-local, so a fresh thread
    measures with concrete operands and real dispatch (same process:
    the chip has one owner)."""
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as ex:
        return float(ex.submit(measure, cand).result())


def time_candidate(fn: Callable[[], Any], warmup: int = 1,
                   iters: int = 3) -> float:
    """Best-of-N wall ms for one compiled candidate invocation.  ``fn``
    must return a jax array (blocked on for device completion)."""
    import jax

    for _ in range(max(warmup, 1)):
        out = fn()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1000.0
