"""Multichip smoke — ci_check.sh gate "multichip" (exit 80).

Three contracts on an 8-fake-device CPU world
(``--xla_force_host_platform_device_count``):

1. **dryrun**: the full hybrid-parallel train step compiles and runs with
   serial-parity loss AND a clean SPMD log — any "Involuntary full
   rematerialization" line is a hard failure (__graft_entry__ pin,
   embedding-gather regression PR 9 fixed) on the full dp=2·pp=2·mp=2
   mesh.
2. **quant**: a 2-step quantized-collective run on a dp=8 mesh:
   ``dist_allreduce_quant=0`` is bit-identical across independent builds,
   ``=1`` tracks the fp32 loss within the parity bound.

Usage: ``python tools/multichip_smoke.py [--part all|dryrun|quant]``.
The parent process self-provisions the 8-device world in a subprocess
(XLA_FLAGS must be set before jax initializes).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEV = 8
QUANT_REL_BOUND = 5e-3


def _part_dryrun() -> None:
    import __graft_entry__ as g

    g._dryrun_impl(N_DEV)     # _factor_mesh(8) -> the full (2, 2, 2)


def _part_quant() -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel.train_step import make_sharded_train_step

    mesh = Mesh(np.array(jax.devices()[:N_DEV]).reshape(N_DEV, 1, 1),
                ("dp", "pp", "mp"))
    cfg = GPTConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=2,
                    seq_len=16, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, cfg.vocab_size, (16, cfg.seq_len)).astype(np.int32)
    lab = np.roll(tok, -1, axis=1)

    def losses(flag: bool, steps: int = 2):
        set_flags({"dist_allreduce_quant": flag})
        try:
            step, params, opt = make_sharded_train_step(cfg, mesh)
            out = []
            for _ in range(steps):
                loss, params, opt = step(params, opt, tok, lab)
                out.append(float(loss))
        finally:
            set_flags({"dist_allreduce_quant": False})
        return out

    off1, off2, on = losses(False), losses(False), losses(True)
    assert off1 == off2, \
        f"dist_allreduce_quant=0 not bit-identical: {off1} vs {off2}"
    rels = [abs(q - r) / max(abs(r), 1e-9) for q, r in zip(on, off1)]
    assert all(r < QUANT_REL_BOUND for r in rels), \
        f"quant-sync loss off parity bound: off={off1} on={on} rels={rels}"
    print(f"multichip_smoke quant OK: off={off1[-1]:.4f} on={on[-1]:.4f} "
          f"max_rel={max(rels):.1e}", flush=True)


def _child(part: str) -> None:
    if part in ("all", "dryrun"):
        _part_dryrun()
    if part in ("all", "quant"):
        _part_quant()
    print(f"multichip_smoke OK part={part}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--part", choices=("all", "dryrun", "quant"),
                    default="all")
    ap.add_argument("--_child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args._child:
        sys.path.insert(0, _REPO)
        _child(args.part)
        return 0

    env = dict(os.environ)
    extra = f"--xla_force_host_platform_device_count={N_DEV}"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + extra).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--part", args.part,
         "--_child"],
        env=env, cwd=_REPO, timeout=1800)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
