"""The always-on ring (PR 27): the program times itself.

One tracer records from import, on the profiler's clock: a span is a B/E
pair in the ring and a ``TraceAnnotation`` in the xplane's host plane; the
engine's tick, the request lifecycles, the train step's dispatch, the
fusion pass's plan and JAX's own trace / lower / compile phases are all in
it without anybody arming anything.
"""

import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu import compiler, obs, profiler
from paddle_tpu.distributed.process_mesh import build_mesh
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.parallel import make_sharded_train_step

CFG = LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden=128, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)
EKW = dict(max_batch=2, page_size=16, max_seq=128, n_pages=1 + 24,
           prefill_budget=32)


@pytest.fixture()
def ring():
    """A fresh ring for the test; the process default (on) after it."""
    st = obs.arm()
    yield st.tracer
    obs.arm()


def _spans(events, tid=None):
    """(name, begin, end, end attrs) of every closed B/E pair."""
    out, stacks = [], {}
    for e in events:
        if tid is not None and e["tid"] != tid:
            continue
        key = (e["tid"], e["name"])
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e["ts"])
        elif e["ph"] == "E":
            out.append((e["name"], stacks[key].pop(), e["ts"],
                        e.get("args", {})))
    return out


def test_ring_is_on_at_import_and_sized_by_the_flag():
    from paddle_tpu.core.flags import GLOBAL_FLAGS

    assert obs.active()
    assert obs.tracer().capacity == GLOBAL_FLAGS.get("obs_buffer_events")
    assert not GLOBAL_FLAGS.has("obs_trace")
    assert not hasattr(obs, "arm_from_flags")


def test_span_lies_in_the_xplane_host_plane_on_the_same_clock(ring, tmp_path):
    """The shared clock: under a profiler session an obs.span is found by
    name in the host plane, as long there as in the ring."""
    import time

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("obs.clock_probe"):
            time.sleep(0.02)
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (name, b, e, _), = [s for s in _spans(ring.snapshot()[0])
                        if s[0] == "obs.clock_probe"]
    ring_ms = (e - b) / 1e3
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = [ev.duration_ns / 1e6 for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "obs.clock_probe"]
    assert len(found) == 1
    assert ring_ms >= 20.0 and abs(found[0] - ring_ms) < 1.0


def test_engine_tick_spans_nest_and_lifecycles_are_ordered(ring):
    eng = ServingEngine(CFG, seed=0, **EKW)
    rng = np.random.RandomState(3)
    reqs = [Request(rid=i, prompt=rng.randint(1, 256, size=rng.randint(
        24, 48)).astype(np.int32), max_new_tokens=6, arrival=0.0)
        for i in range(5)]
    eng.run(reqs)
    events, n = ring.snapshot()
    assert n == len(events)
    spans = _spans(events, tid=1)             # engine 0's track
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == eng.stats["unified_steps"] + 1   # + the idle tick
    inside = lambda name, a, b: [s for s in spans if s[0] == name
                                 and a <= s[1] and s[2] <= b]
    harvested = 0
    for _, a, b, attrs in steps:
        assert set(attrs) == {"rows_decode", "rows_prefill", "queued",
                              "places", "tokens", "slots",
                              "slots_prefilling", "slots_prefill_waiting"}
        assert attrs["places"] in (0,) + eng.rungs
        assert attrs["tokens"] <= attrs["places"]
        assert len(inside("engine.admit", a, b)) == 1
        assert len(inside("engine.dispatch", a, b)) == 1
        harvests = inside("engine.harvest", a, b)
        assert len(harvests) <= 1
        for _, ha, hb, _ in harvests:
            # the host sync sits inside the harvest, on every tick
            assert len(inside("engine.harvest.wait", ha, hb)) == 1
            harvested += 1
    assert harvested == eng.stats["unified_steps"]
    assert harvested == sum(1 for s in spans
                            if s[0] == "engine.harvest.wait")
    assert sum(s[3]["rows_prefill"] for s in steps) > 0
    assert sum(s[3]["rows_decode"] for s in steps) > 0
    assert steps[-1][3] == {"rows_decode": 0, "rows_prefill": 0,
                            "queued": 0, "places": 0, "tokens": 0,
                            "slots": 2, "slots_prefilling": 0,
                            "slots_prefill_waiting": 0}
    assert sum(s[3]["tokens"] for s in steps) == eng.stats["tokens_packed"]

    at = {}
    for e in events:
        if e.get("cat") == "req":
            at.setdefault(e["id"], {}).setdefault(e["args"]["event"],
                                                  e["ts"])
    assert set(at) == {r.rid for r in reqs}
    for rid, seen in at.items():
        order = [seen[k] for k in ("arrival", "admit", "first-token",
                                   "done")]
        assert order == sorted(order), (rid, seen)


def test_first_jit_call_records_its_phases_and_a_second_none(ring):
    def ring_probe_fn(x):
        return jnp.sin(x) * 2.0 + jnp.where(x > 0, x, -x)

    f = jax.jit(ring_probe_fn)
    f(jnp.arange(7.0)).block_until_ready()
    mine = [e for e in ring.snapshot()[0]
            if "ring_probe_fn" in e.get("args", {}).get("fun_name", "")]
    assert [e["name"] for e in mine] == ["jax.trace", "jax.lower",
                                         "jax.compile"]
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in mine)
    # only the outermost phase is recorded: the jitted jnp.where traced
    # inside ring_probe_fn's trace is not an event of its own
    names = [e.get("args", {}).get("fun_name") for e in ring.snapshot()[0]
             if e["name"].startswith("jax.")]
    assert "_where" not in names
    before = ring.n_emitted
    f(jnp.arange(7.0)).block_until_ready()
    assert ring.n_emitted == before


def test_compiler_plan_event_is_what_last_report_holds(ring):
    def rms_times_two(x, g):
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + 1e-5)
        return (y * g.astype(jnp.float32)).astype(x.dtype) * 2.0

    fused = compiler.auto_fuse(rms_times_two)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    args = (jax.random.normal(ks[0], (1, 256, 256), jnp.bfloat16),
            jax.random.normal(ks[1], (256,), jnp.bfloat16))
    fused(*args)
    plans = [e for e in ring.snapshot()[0] if e["name"] == "compiler.plan"]
    assert len(plans) == 1 and plans[0]["ph"] == "i"
    rep = compiler._LAST_REPORT
    assert rep.n_sites >= 1
    assert plans[0]["args"] == {
        "n_sites": rep.n_sites, "n_applied": rep.n_applied,
        "phash": rep.program_hash, "warm": rep.program_cache_hit}
    fused(*args)                               # a replay records nothing
    assert len([e for e in ring.snapshot()[0]
                if e["name"] == "compiler.plan"]) == 1
    # a program in which the catalog finds nothing is passed through
    compiler.auto_fuse(lambda x: x + 1.0)(jnp.ones(4))
    assert compiler._LAST_REPORT.n_sites == 0
    assert len([e for e in ring.snapshot()[0]
                if e["name"] == "compiler.plan"]) == 1


def test_train_step_span_times_the_dispatch(ring):
    cfg = GPTConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                    seq_len=32)
    mesh = build_mesh((1, 1, 1), ("dp", "pp", "mp"),
                      devices=jax.devices()[:1])
    step, params, opt = make_sharded_train_step(cfg, mesh)
    toks = step.put_batch(np.zeros((2, 32), np.int32))
    for _ in range(3):
        loss, params, opt = step(params, opt, toks, toks)
    loss.block_until_ready()
    spans = [s for s in _spans(ring.snapshot()[0]) if s[0] == "train.step"]
    assert len(spans) == 3
    # the first call traces and compiles inside the span; the next two
    # only dispatch
    assert spans[0][2] - spans[0][1] > 10 * (spans[2][2] - spans[2][1])


def test_record_event_is_a_front_over_the_span(ring):
    with profiler.RecordEvent("user.fwd"):
        pass
    (name, b, e, _), = [s for s in _spans(ring.snapshot()[0])
                        if s[0] == "user.fwd"]
    assert e >= b
    begin = [ev for ev in ring.snapshot()[0] if ev["name"] == "user.fwd"][0]
    assert begin["args"] == {"src": "profiler"}
    assert profiler._HOST_EVENTS["user.fwd"]["count"] >= 1
    # one place in the package emits profiler annotations
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        ["grep", "-rl", "--include=*.py", "TraceAnnotation(",
         os.path.join(root, "paddle_tpu")], capture_output=True, text=True)
    assert [os.path.relpath(p, root) for p in out.stdout.split()] == [
        "paddle_tpu/obs/trace.py"]


def _eqns(jaxpr, acc):
    """(primitive, name stack) of every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        acc.append((eqn.primitive.name, str(eqn.source_info.name_stack)))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _eqns(inner, acc)
    return acc


def test_engine_scopes_label_the_step_and_add_no_equation(monkeypatch):
    """Scopes are metadata: the same equations, each now under a name."""
    import contextlib
    import re

    eng = ServingEngine(CFG, seed=0, **EKW)
    scoped = _eqns(eng.trace_unified().jaxpr, [])
    tops = {re.sub(r"^(layer/\w+|\w+).*", r"\1", n) for _, n in scoped if n}
    assert {"embed", "layer/qkv", "layer/kv_write", "layer/attn",
            "layer/mlp", "head", "sample"} <= tops
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _eqns(eng.trace_unified().jaxpr, [])
    assert [p for p, _ in bare] == [p for p, _ in scoped]
    assert not any(n.startswith(("embed", "layer/", "head"))
                   for _, n in bare)


def test_train_step_scopes_reach_the_lowered_program():
    import re

    cfg = GPTConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                    seq_len=32)
    mesh = build_mesh((1, 1, 1), ("dp", "pp", "mp"),
                      devices=jax.devices()[:1])
    step, params, opt = make_sharded_train_step(cfg, mesh)
    toks = jnp.zeros((2, 32), jnp.int32)
    with jax.sharding.set_mesh(mesh):
        txt = step.jitted.lower(params, opt, toks,
                                toks).as_text(debug_info=True)
    ops = set(re.findall(r'loc\("jit\(step\)/([^"/]+)/', txt))
    assert {"jvp(fwd)", "transpose(jvp(fwd))", "adamw"} <= ops
