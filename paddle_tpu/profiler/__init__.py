"""paddle_tpu.profiler: tracing + op statistics.

Re-design of python/paddle/profiler (profiler.py:358 Profiler with
CLOSED/READY/RECORD scheduler states :89, RecordEvent spans,
chrometracing_logger.h Chrome export). TPU translation: the device-side
tracer is the XLA/jax profiler (TensorBoard/perfetto trace, which subsumes
the CUPTI tracer + chrome-trace logger); RecordEvent is a front over
``obs.span``, the one place that emits profiler annotations, so user
spans appear inside the device trace and in the obs ring beside the
engine/fleet spans; host-side per-op stats ride the dispatch funnel hook
(the host_tracer.h role). ``export_chrome_tracing`` writes the ring's
Chrome trace next to the host summary.
"""

from __future__ import annotations

import contextlib
import enum
from collections import defaultdict
from typing import Callable, Iterable, Optional

import jax

from .. import obs as _obs
from ..core.dispatch import DISPATCH_HOOKS
from ..obs import clock as _clock

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result"]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0) -> Callable[[int],
                                                                     ProfilerState]:
    """reference profiler.py:214 make_scheduler."""
    period = closed + ready + record

    def sched(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return sched


class RecordEvent:
    """User span: an ``obs.span`` (the ring and the device trace's host
    plane) counted in the host op-summary (reference:
    paddle.profiler.RecordEvent)."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None
        self._t0 = None

    def begin(self):
        self._span = _obs.span(self.name, src="profiler")
        self._span.__enter__()
        self._t0 = _clock.now()
        _HOST_EVENTS[self.name]["count"] += 1

    def end(self):
        if self._span is not None:
            _HOST_EVENTS[self.name]["total_s"] += _clock.now() - self._t0
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


_HOST_EVENTS: dict = defaultdict(lambda: {"count": 0, "total_s": 0.0})


class Profiler:
    """reference profiler.py:358. start/stop (or context manager) +
    step() driving the scheduler; on_trace_ready fires at
    RECORD_AND_RETURN steps."""

    def __init__(self, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready: Optional[Callable] = None,
                 record_shapes: bool = False, profile_memory: bool = False,
                 timer_only: bool = False, log_dir: str = "/tmp/paddle_tpu_prof"):
        if callable(scheduler):
            self._sched = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._sched = make_scheduler(closed=lo, ready=0, record=hi - lo,
                                         repeat=1)
        else:
            self._sched = lambda step: ProfilerState.RECORD
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._log_dir = log_dir
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._op_counts: dict = defaultdict(int)
        self._hook = None
        self._handler_fired = False
        self._step_times: list = []
        self._last_step_t = None

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        self._handler_fired = False  # fresh start/stop cycle
        self._state = self._sched(self._step)
        self._maybe_toggle_trace()
        hook = lambda name: self._op_counts.__setitem__(
            name, self._op_counts[name] + 1)
        self._hook = hook
        DISPATCH_HOOKS.append(hook)
        self._last_step_t = _clock.now()

    def stop(self):
        if self._hook in DISPATCH_HOOKS:
            DISPATCH_HOOKS.remove(self._hook)
        was_tracing = self._tracing
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
        # fire the handler only if recording happened and step() didn't
        # already fire it at a RECORD_AND_RETURN boundary
        if self._on_trace_ready is not None and was_tracing \
                and not self._handler_fired:
            self._on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        now = _clock.now()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._step += 1
        new_state = self._sched(self._step)
        if new_state != self._state:
            self._state = new_state
            self._maybe_toggle_trace()
        if self._state == ProfilerState.RECORD_AND_RETURN and \
                self._on_trace_ready is not None:
            self._handler_fired = True
            self._on_trace_ready(self)

    def _maybe_toggle_trace(self):
        want = self._state in (ProfilerState.RECORD,
                               ProfilerState.RECORD_AND_RETURN) and \
            not self._timer_only
        if want and not self._tracing:
            try:
                jax.profiler.start_trace(self._log_dir)
                self._tracing = True
            except Exception:
                self._tracing = False
        elif not want and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- reporting ----------------------------------------------------------
    def summary(self, sorted_by=None, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms"):
        lines = ["----- paddle_tpu profiler summary -----"]
        if self._step_times:
            import numpy as np

            ts = np.asarray(self._step_times) * 1000
            lines.append(f"steps: {len(ts)}  avg: {ts.mean():.2f} ms  "
                         f"p50: {np.percentile(ts, 50):.2f}  "
                         f"max: {ts.max():.2f}")
        if op_detail and self._op_counts:
            lines.append("op dispatch counts:")
            for name, c in sorted(self._op_counts.items(),
                                  key=lambda kv: -kv[1])[:30]:
                lines.append(f"  {name:<40} {c}")
        if _HOST_EVENTS:
            lines.append("user events:")
            for name, st in _HOST_EVENTS.items():
                lines.append(f"  {name:<40} x{st['count']} "
                             f"{st['total_s']*1000:.2f} ms")
        out = "\n".join(lines)
        print(out)
        return out

    def export(self, path: str, format: str = "json"):
        """Device trace lives in log_dir (perfetto/tensorboard format);
        export writes the host-side summary."""
        with open(path, "w") as f:
            f.write(self.summary())


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready factory (reference profiler.py export_chrome_tracing):
    the XLA trace in log_dir is already viewable in perfetto/tensorboard."""

    def handler(prof: Profiler):
        import os

        os.makedirs(dir_name, exist_ok=True)
        prof.export(os.path.join(dir_name, "host_summary.txt"))
        # the shared obs ring (RecordEvent spans included) as Chrome
        # trace-event JSON, next to the host summary; nothing when disarmed
        _obs.export(os.path.join(dir_name, "obs_ring.json"))

    return handler


def load_profiler_result(path: str):
    with open(path) as f:
        return f.read()
