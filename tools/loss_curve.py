"""Pin a fixed-config loss trajectory as a regression artifact.

VERDICT r3 weak #5: tokens/s is the bench contract, but nothing pinned a
fixed-config loss curve, so a silent numerics regression could hide
behind a green throughput number. This runs N steps of the sharded train
step (1-device mesh) on a seed-pinned synthetic stream and writes the
curve; consumers:

- tests/test_loss_trajectory.py (slow tier): re-runs the TINY config on
  CPU and asserts equality with artifacts/loss_curve_cpu.json;
- bench.py: re-runs the 350m config's first 100 steps on the chip and
  emits loss_at_step_100 next to artifacts/loss_curve_tpu.json's value.

Regenerate (after an INTENDED numerics change — say so in the commit):

    python tools/loss_curve.py --config tiny --out artifacts/loss_curve_cpu.json
    python tools/loss_curve.py --config 350m --out artifacts/loss_curve_tpu.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


CONFIGS = {
    # tiny: CPU-runnable in the slow tier (~2 min), still exercises the
    # full AdamW step incl. bf16-moment + master-weight paths via f32?
    # -> keep f32 end-to-end so CPU equality is bit-stable across runs
    "tiny": dict(vocab_size=512, hidden=64, n_layers=2, n_heads=4,
                 seq_len=64, batch=8, steps=100, lr=3e-4, dtype="float32"),
    # 350m: the flagship bench config's exact model at b8 (chip artifact)
    "350m": dict(vocab_size=50304, hidden=1024, n_layers=24, n_heads=16,
                 seq_len=1024, batch=8, steps=100, lr=3e-4,
                 dtype="bfloat16"),
}


def run_curve(name: str) -> dict:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.process_mesh import build_mesh
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel import make_sharded_train_step

    c = CONFIGS[name]
    cfg = GPTConfig(vocab_size=c["vocab_size"], hidden=c["hidden"],
                    n_layers=c["n_layers"], n_heads=c["n_heads"],
                    seq_len=c["seq_len"],
                    dtype=jnp.dtype(c["dtype"]))
    mesh = build_mesh((1, 1, 1), ("dp", "pp", "mp"),
                      devices=[jax.devices()[0]])
    step, params, opt = make_sharded_train_step(cfg, mesh, lr=c["lr"],
                                                seed=0)
    rng = np.random.RandomState(1234)
    # ONE fixed batch, reused every step (the bench methodology):
    # memorization gives a decisively-decreasing curve. Fresh random
    # tokens per step — the original formulation — are unlearnable by
    # construction (loss plateaus at ln V), which made the trajectory
    # test's "curve learns" guard unsatisfiable.
    toks = rng.randint(0, cfg.vocab_size, size=(c["batch"], cfg.seq_len))
    labs = np.roll(toks, -1, axis=1)
    losses = []
    for i in range(c["steps"]):
        loss, params, opt = step(params, opt, toks, labs)
        losses.append(float(loss))
    return {
        "config": name,
        "params": c,
        "backend": jax.default_backend(),
        "losses": losses,
        "loss_at_step_100": losses[-1],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(CONFIGS), default="tiny")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    res = run_curve(args.config)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"{args.config}: loss {res['losses'][0]:.4f} -> "
          f"{res['losses'][-1]:.4f}; wrote {args.out}")


if __name__ == "__main__":
    main()
