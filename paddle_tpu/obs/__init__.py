"""Fleet-wide observability plane: span tracing, metrics, flight
recorder.

One tracer, always recording: the ring is armed when this module is
imported, with ``obs_buffer_events`` capacity — a flight recorder that
is off until somebody thinks to turn it on records no flight. Tracing
observes host control flow only, never touches device programs, RNG or
scheduling decisions, so serving/fleet outputs are pinned bit-identical
with the ring on AND after ``disarm()``.

Usage (host code)::

    from paddle_tpu import obs as _obs

    with _obs.span("engine.step", engine=self.engine_id) as sp:
        ...
        sp.set(rows_decode=n)        # counts, recorded on the span's end
    _obs.lifecycle(req.rid, "first-token", engine=self.engine_id)
    _obs.flight_dump("engine-death", detail=rep.last_error)

Scope: coarse events only — per tick, per request, per train step, per
compile. Nothing per token, per layer or per op goes into the ring.

A span is also a ``jax.profiler.TraceAnnotation`` (obs/trace.py), so a
profiler session shows the program's spans on the device's timeline.
JAX's own phases arrive through ``jax.monitoring``: every trace,
lowering and backend compile (cache loads included) is a ``jax.trace``
/ ``jax.lower`` / ``jax.compile`` span with its duration and the
function's name — a recompile is an event in the ring and in every
flight dump.

``obs.arm()`` / ``obs.disarm()`` are the programmatic switch: tests arm a
ring of their own, and an on/off cost measurement disarms. Disarmed,
every helper is a no-op behind one module-global load. While armed,
chaos faults that actually fire are annotated into the trace (instant
events named ``chaos.<point>``) and logged for the flight recorder
through a chaos observer callback.

Export: ``obs.export(path)`` writes Chrome trace-event JSON — open in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.testing import chaos as _chaos

from . import clock, flight as _flight
from .metrics import (FLEET_STATS_SCHEMA, MetricsRegistry,
                      SERVING_STATS_SCHEMA, TRAIN_STATS_SCHEMA)
from .trace import Tracer

__all__ = ["active", "arm", "disarm", "span",
           "instant", "lifecycle", "flight_dump", "export", "tracer",
           "clock", "Tracer", "MetricsRegistry",
           "SERVING_STATS_SCHEMA", "FLEET_STATS_SCHEMA",
           "TRAIN_STATS_SCHEMA"]


class _NoopSpan:
    """Shared reusable ``with`` guard for the disarmed path."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _NoopSpan()


class _ObsState:
    """Everything one armed session owns."""

    def __init__(self, capacity: int, dump_dir: Optional[str]):
        self.tracer = Tracer(capacity)
        self.faults: list = []          # chaos specs that actually fired
        self.dump_dir = dump_dir        # None: the obs_dir flag, at dump
        self.dumps: list = []           # flightrec paths written


_armed: Optional[_ObsState] = None


def _tid(engine) -> int:
    """Trace track for an engine id: track 0 is the host/fleet track,
    engine N lives on track N+1."""
    return 0 if engine is None else int(engine) + 1


def _on_chaos_fire(point: str, spec, ctx, invocation: int) -> None:
    """Chaos observer: a fault actually fired — annotate the trace and
    remember it for the flight recorder."""
    st = _armed
    if st is None:
        return
    rec = {"point": point, "kind": spec.kind,
           "args": {k: v for k, v in spec.args.items()},
           "ctx": dict(ctx or {}), "invocation": invocation}
    st.faults.append(rec)
    st.tracer.instant("chaos." + point,
                      tid=_tid((ctx or {}).get("engine")),
                      attrs={"kind": spec.kind, "invocation": invocation,
                             **{f"ctx.{k}": str(v)
                                for k, v in (ctx or {}).items()}})


# -- arming ------------------------------------------------------------------

def active() -> bool:
    return _armed is not None


def arm(capacity: Optional[int] = None,
        dump_dir: Optional[str] = None) -> _ObsState:
    """Start a fresh ring process-wide (replaces the armed session)."""
    global _armed
    if capacity is None:
        capacity = int(GLOBAL_FLAGS.get("obs_buffer_events"))
    _armed = _ObsState(capacity, dump_dir)
    _chaos.add_observer(_on_chaos_fire)
    return _armed


def disarm() -> None:
    global _armed
    _armed = None
    _chaos.remove_observer(_on_chaos_fire)


# -- recording ---------------------------------------------------------------

def span(name: str, engine=None, **attrs):
    """``with obs.span("engine.step", engine=0) as sp:`` — a B/E pair on
    the engine's track and a profiler annotation; ``sp.set(k=v)`` puts
    counts on the E event. A no-op shared guard when disarmed (one global
    load)."""
    st = _armed
    if st is None:
        return _NOOP
    if engine is not None:
        attrs["engine"] = engine
    return st.tracer.span(name, tid=_tid(engine), attrs=attrs or None)


def instant(name: str, /, engine=None, **attrs) -> None:
    # ``name`` positional-only, so that an attribute may be called name
    st = _armed
    if st is None:
        return
    if engine is not None:
        attrs["engine"] = engine
    st.tracer.instant(name, tid=_tid(engine), attrs=attrs or None)


_LIFECYCLE_PH = {"arrival": "b", "done": "e"}


def lifecycle(rid: int, event: str, engine=None, **attrs) -> None:
    """One request-lifecycle event: ``arrival`` opens the async flow
    (ph ``b``), ``done`` closes it (ph ``e``), everything between
    (admit, prefill-start, first-token, preempt, migrate, ship, adopt,
    ...) is an
    async instant (ph ``n``) — all sharing ``id=rid`` so Perfetto
    stitches the flow across engine tracks."""
    st = _armed
    if st is None:
        return
    attrs["event"] = event
    if engine is not None:
        attrs["engine"] = engine
    st.tracer.async_event("req", rid, _LIFECYCLE_PH.get(event, "n"),
                          tid=_tid(engine), attrs=attrs)


# JAX's compile phases, by the names of the installed JAX 0.9.0
# (jax/_src/dispatch.py); each arrives with ``fun_name``.
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}


_phase_depth = threading.local()


def _on_jax_phase_start(event: str, value, **kw) -> None:
    """JAX announces a phase's start as a scalar under the phase's name;
    counting them tells the outermost phase from those inside it."""
    if event in _JAX_PHASES:
        _phase_depth.n = getattr(_phase_depth, "n", 0) + 1


def _on_jax_duration(event: str, duration_secs: float, **kw) -> None:
    """Only the outermost phase of a thread is recorded: tracing one step
    traces a jitted ``add`` or ``where`` a thousand times inside it, and
    nothing per op goes into the ring. So the seconds of one name add up
    without counting any twice."""
    name = _JAX_PHASES.get(event)
    if name is None:
        return
    depth = _phase_depth.n = max(0, getattr(_phase_depth, "n", 1) - 1)
    st = _armed
    if depth or st is None:
        return
    fun = kw.get("fun_name")
    st.tracer.complete(name, duration_secs,
                       attrs=None if fun is None else {"fun_name": str(fun)})


# -- artifacts ---------------------------------------------------------------

def flight_dump(reason: str, detail: Optional[str] = None) -> Optional[str]:
    """Dump the ring on a death path, into the session's ``dump_dir`` or
    where the ``obs_dir`` flag says; returns the flightrec path, or None
    when disarmed."""
    st = _armed
    if st is None:
        return None
    st.tracer.instant("flightrec.dump", attrs={"reason": reason})
    path = _flight.dump(st.tracer, reason, detail=detail,
                        faults=st.faults,
                        dump_dir=(st.dump_dir
                                  or str(GLOBAL_FLAGS.get("obs_dir"))))
    st.dumps.append(path)
    return path


def export(path: Optional[str] = None) -> Optional[dict]:
    """Chrome trace-event JSON of the armed tracer (None when
    disarmed)."""
    st = _armed
    if st is None:
        return None
    return st.tracer.export(path)


def tracer() -> Optional[Tracer]:
    return _armed.tracer if _armed is not None else None


# Registered once and for the life of the process: a listener is one
# dict lookup per JAX event and records nothing while disarmed.
jax.monitoring.register_scalar_listener(_on_jax_phase_start)
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
arm()
