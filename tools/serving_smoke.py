"""Serving smoke gate (ci_check.sh exit 50): a tiny-config
ServingEngine.run under JAX_PLATFORMS=cpu must complete every request —
including a shared-prefix pair and a mid-run abort — and return every
page (free + refcounted-cache pages == n_pages - 1). Catches scheduler
regressions (admission, chunked prefill, prefix cache, page accounting)
before a TPU bench round.

Usage:  JAX_PLATFORMS=cpu python -m tools.serving_smoke
"""

from __future__ import annotations

import sys

import numpy as np


def main() -> int:
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_hidden=128, max_seq_len=128,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    engine = ServingEngine(cfg, max_batch=2, page_size=16, max_seq=96,
                           n_pages=1 + 10, prefill_budget=32)
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, 256, size=16).astype(np.int32)
    prompts = [
        rng.randint(1, 256, size=9).astype(np.int32),
        np.concatenate([prefix, rng.randint(1, 256, 7).astype(np.int32)]),
        np.concatenate([prefix, rng.randint(1, 256, 5).astype(np.int32)]),
        rng.randint(1, 256, size=40).astype(np.int32),
    ]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5, arrival=0.0)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    victim = Request(rid=99, prompt=prompts[3].copy(), max_new_tokens=48)
    engine.submit(victim)
    steps = 0
    while engine.step(now=1e9):
        steps += 1
        if not victim.aborted and victim in engine.slots:
            engine.abort(99)     # slot-resident, possibly mid-quantum
        if steps > 300:
            print("serving_smoke: FAIL — engine did not drain in 300 "
                  "steps", file=sys.stderr)
            return 1
    if not victim.aborted or len(victim.out_tokens) >= 48:
        print("serving_smoke: FAIL — abort path did not fire",
              file=sys.stderr)
        return 1
    bad = [r for r in reqs if len(r.out_tokens) != r.max_new_tokens
           or r.t_done is None]
    if bad:
        print(f"serving_smoke: FAIL — incomplete requests "
              f"{[r.rid for r in bad]}", file=sys.stderr)
        return 1
    acc = engine.page_accounting()
    leaked = (acc["total"] != engine.n_pages - 1
              or acc["slot_owned"] or acc["slot_shared"]
              or acc["deferred_free"])
    if leaked:
        print(f"serving_smoke: FAIL — page leak: {acc} "
              f"(expected free+cache_idle == {engine.n_pages - 1})",
              file=sys.stderr)
        return 1
    print(f"serving_smoke: OK — {len(reqs)} requests + 1 abort in "
          f"{steps} steps, {acc['free']} free / {acc['cache_idle']} "
          f"cached pages, no leak")
    return 0


if __name__ == "__main__":
    sys.exit(main())
