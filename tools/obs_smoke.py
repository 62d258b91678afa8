"""Observability smoke gate (ci_check.sh exit 160): a 2-replica fleet
takes a chaos engine kill mid-decode with the obs plane ARMED — the
exported Chrome trace must be structurally valid (B/E balanced, async
request flows closed), contain at least one ``fleet.migrate`` span and
the ``chaos.engine.step`` fault annotation, a flight record must have
auto-dumped on the death path naming the injected fault, and every
surviving page ledger must close with zero leak. A second DISARMED pass
under the identical chaos plan must then produce bit-identical token
streams: tracing observes the fleet, it never steers it.

Usage:  JAX_PLATFORMS=cpu python -m tools.obs_smoke
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np


def _mk_reqs(cfg):
    from paddle_tpu.inference.serving import Request

    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, size=40).astype(np.int32)
               for _ in range(5)]
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=12,
                    arrival=0.0) for i, p in enumerate(prompts)]
    # one keyed-sampling stream: non-perturbation must hold through the
    # (seed, position) sampling path too, not just argmax
    reqs[2].temperature, reqs[2].top_p, reqs[2].seed = 0.8, 0.9, 1234
    return reqs


def _run_fleet(cfg, ekw, kill: bool) -> list:
    """One fleet pass under the standard chaos kill; returns requests."""
    from paddle_tpu.inference.fleet import FleetRouter
    from paddle_tpu.testing import chaos

    if kill:
        chaos.arm(chaos.FaultPlan(seed=0)
                  .add("engine.step", "raise", at=6, engine=0))
    router = FleetRouter(cfg, n_engines=2, seed=0, engine_kwargs=ekw)
    reqs = _mk_reqs(cfg)
    for r in reqs:
        router.submit(r, now=1e18)
    steps = 0
    while router.step(now=1e18):
        steps += 1
        if steps > 4000:
            raise RuntimeError("fleet did not drain")
    chaos.disarm()
    return reqs, router


def _check_ledgers(router) -> str:
    for rep in router.replicas:
        e = rep.engine
        if rep.alive and (e._deferred_free or e.pool.pending_evict):
            e.pool.release(e._deferred_free)  # tpu-lint: disable=TPL213 -- post-run settlement: drained, no program in flight
            e._deferred_free = []
            e.pool.commit_evictable()
        acc = e.page_accounting()
        if acc["total"] != e.n_pages - 1:
            return f"engine {e.engine_id} ledger does not sum: {acc}"
        if rep.alive and (acc["slot_owned"] or acc["slot_shared"]
                          or acc["deferred_free"] or acc["in_flight"]):
            return f"engine {e.engine_id} leaked pages: {acc}"
    return ""


def _check_trace(doc) -> str:
    """Perfetto's structural contract: balanced B/E per track, every
    async end opened by a begin."""
    json.loads(json.dumps(doc))
    stacks: dict = {}
    opened: dict = {}
    for ev in doc["traceEvents"]:
        ph = ev["ph"]
        if ph == "B":
            stacks.setdefault(ev["tid"], []).append(ev["name"])
        elif ph == "E":
            if not stacks.get(ev["tid"]):
                return f"orphan E event {ev}"
            stacks[ev["tid"]].pop()
        elif ph == "b":
            k = (ev["name"], ev["id"])
            opened[k] = opened.get(k, 0) + 1
        elif ph == "e":
            k = (ev["name"], ev["id"])
            if not opened.get(k):
                return f"orphan async end {ev}"
            opened[k] -= 1
    if any(s for s in stacks.values()):
        return f"unbalanced B/E stacks: {stacks}"
    if any(n for n in opened.values()):
        return f"unclosed async flows: {opened}"
    return ""


def main() -> int:
    import jax.numpy as jnp

    from paddle_tpu import obs
    from paddle_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_hidden=128, max_seq_len=256,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    ekw = dict(max_batch=2, page_size=16, max_seq=128, n_pages=1 + 24,
               prefill_budget=32)

    # -- pass 1: ARMED, chaos kill ------------------------------------------
    if not obs.active():
        print("obs_smoke: FAIL — the ring is not on at import",
              file=sys.stderr)
        return 1
    st = obs.arm(capacity=16384, dump_dir="artifacts")   # a fresh ring
    armed_reqs, router = _run_fleet(cfg, ekw, kill=True)
    bad = [r.rid for r in armed_reqs if r.aborted or r.t_done is None
           or len(r.out_tokens) != r.max_new_tokens]
    if bad:
        print(f"obs_smoke: FAIL — requests {bad} dropped through the "
              f"kill", file=sys.stderr)
        return 1
    if router.stats["n_killed"] != 1:
        print("obs_smoke: FAIL — the armed engine.step raise never "
              "landed", file=sys.stderr)
        return 1

    doc = obs.export()
    err = _check_trace(doc)
    if err:
        print(f"obs_smoke: FAIL — invalid Chrome trace: {err}",
              file=sys.stderr)
        return 1
    names = {e["name"] for e in doc["traceEvents"]}
    migrates = [e for e in doc["traceEvents"]
                if e["ph"] == "B" and e["name"] == "fleet.migrate"]
    if not migrates:
        print("obs_smoke: FAIL — no fleet.migrate span in the trace of "
              "a run that migrated pages", file=sys.stderr)
        return 1
    if "chaos.engine.step" not in names:
        print("obs_smoke: FAIL — the fired chaos fault was not "
              "annotated into the trace", file=sys.stderr)
        return 1
    if len(st.dumps) != 1:
        print(f"obs_smoke: FAIL — expected exactly one flight dump on "
              f"the death path, got {st.dumps}", file=sys.stderr)
        return 1
    rec = json.load(open(st.dumps[0]))
    if rec["schema"] != "paddle_tpu.flightrec.v1" \
            or rec["reason"] != "engine-death" \
            or [f["point"] for f in rec["faults"]] != ["engine.step"]:
        print(f"obs_smoke: FAIL — flight record does not name its "
              f"killer: {rec['reason']}, {rec['faults']}",
              file=sys.stderr)
        return 1
    err = _check_ledgers(router)
    if err:
        print(f"obs_smoke: FAIL — {err}", file=sys.stderr)
        return 1
    obs.disarm()

    # -- pass 2: DISARMED, identical chaos plan -> identical streams --------
    plain_reqs, router2 = _run_fleet(cfg, ekw, kill=True)
    if obs.active():
        print("obs_smoke: FAIL — obs still armed in the control pass",
              file=sys.stderr)
        return 1
    for a, b in zip(armed_reqs, plain_reqs):
        if a.out_tokens != b.out_tokens:
            print(f"obs_smoke: FAIL — rid {a.rid} stream differs with "
                  f"tracing armed vs disarmed: {a.out_tokens} vs "
                  f"{b.out_tokens}", file=sys.stderr)
            return 1
    err = _check_ledgers(router2)
    if err:
        print(f"obs_smoke: FAIL — control pass: {err}", file=sys.stderr)
        return 1

    print(f"obs_smoke: OK — {len(armed_reqs)} streams bit-identical "
          f"armed vs disarmed through an engine kill; "
          f"{len(migrates)} migration span(s), fault annotated, "
          f"flight record {os.path.basename(st.dumps[0])}, "
          f"ledgers closed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
