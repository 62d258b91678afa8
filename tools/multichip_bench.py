"""Multichip bench — measurements behind bench.py's ``multichip_*`` keys.

Measures the hybrid-parallel (dp x pp x mp) train step against a serial
1-device run of the SAME config and global batch:

- ``step_ms``: best-of-3 two-step windows of the multichip step;
- ``tok_s_per_chip``: global tokens/s divided by device count;
- ``serial_step_ms``: the 1-device reference (scaling efficiency =
  serial / (n * multichip) — perfect linear scaling is 1.0);
- ``comm_ms``: isolated gradient-sync microbench (a full-parameter-sized
  fp32 psum over the dp axis, the dominant collective of the step) —
  comm_frac = comm_ms / step_ms is an isolated-phase ratio in the
  _bench_phases sense, not an additive partition (compute/comm overlap);
- ``quant_*``: the same step on a dp-only mesh with
  ``dist_allreduce_quant`` off vs on — int8-wire gradient-sync
  throughput plus the measured loss delta after identical step counts.

Mesh: dp=n/4·pp=2·mp=2 when n is a multiple of 8, else dp=n/2·mp=2.

Standalone: ``python tools/multichip_bench.py`` prints one JSON line of
raw measurements from the devices this process has, and refuses with
fewer than two: a multichip number is measured on the chips it names,
never on virtual CPU devices beside them.  (A CPU rehearsal of the code
path is ``XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu``, set by the caller.)  bench.py calls ``measure()``
in-process.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WINDOWS, _WIN_STEPS = 3, 2


def _mesh_shape(n: int) -> tuple[int, int, int]:
    if n % 8 == 0:
        return (n // 4, 2, 2)
    if n % 2 == 0:
        return (n // 2, 1, 2)
    return (n, 1, 1)


def measure() -> dict:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed.process_mesh import build_mesh
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel import make_sharded_train_step

    n = len(jax.devices())
    if n < 2:
        raise RuntimeError(
            f"multichip bench needs >= 2 devices; this "
            f"{jax.devices()[0].platform} host has {n}")
    dp, pp, mp = _mesh_shape(n)
    n_micro = 2 if pp > 1 else 1

    cfg = GPTConfig(vocab_size=2048, hidden=128, n_layers=4, n_heads=4,
                    seq_len=64, dtype=jnp.float32)
    batch = 4 * dp * n_micro
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, size=(batch, cfg.seq_len))
    labs = rng.randint(0, cfg.vocab_size, size=(batch, cfg.seq_len))

    n_params = 0

    def run(mesh, n_microbatches, flag):
        """warm 1 step, then best-of-N windows; returns (ms/step, loss
        after the identical 1 + N*W step schedule — off/on deltas
        compare equal step counts)."""
        nonlocal n_params
        set_flags({"dist_allreduce_quant": flag})
        try:
            step, params, opt = make_sharded_train_step(
                cfg, mesh, n_microbatches=n_microbatches)
            n_params = sum(int(np.prod(x.shape))
                           for x in jax.tree.leaves(params))
            t = step.put_batch(toks)
            l = step.put_batch(labs)
            loss, params, opt = step(params, opt, t, l)
            float(loss)  # fetch = device sync
            best = float("inf")
            for _ in range(_WINDOWS):
                t0 = time.perf_counter()
                for _ in range(_WIN_STEPS):
                    loss, params, opt = step(params, opt, t, l)
                lf = float(loss)
                best = min(best, (time.perf_counter() - t0) / _WIN_STEPS)
            return best * 1000.0, lf
        finally:
            set_flags({"dist_allreduce_quant": False})

    mesh = build_mesh((dp, pp, mp), ("dp", "pp", "mp"))
    step_ms, _ = run(mesh, n_micro, False)

    serial_mesh = build_mesh((1, 1, 1), ("dp", "pp", "mp"),
                             devices=[jax.devices()[0]])
    serial_ms, _ = run(serial_mesh, 1, False)

    # isolated gradient-sync microbench: full-parameter fp32 psum over dp
    dmesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
    g = jnp.zeros((n, n_params), jnp.float32)

    def body(x):
        return jax.lax.psum(x[0], "dp")[None]

    sync = jax.shard_map(body, in_specs=P("dp"), out_specs=P("dp"),
                         axis_names={"dp"}, check_vma=False)
    with jax.sharding.set_mesh(dmesh):
        jf = jax.jit(sync)
        jax.block_until_ready(jf(g))
        comm_best = float("inf")
        for _ in range(_WINDOWS):
            t0 = time.perf_counter()
            jax.block_until_ready(jf(g))
            comm_best = min(comm_best, time.perf_counter() - t0)

    # quantized gradient sync: dp-only mesh, off vs on, equal step counts
    qmesh = build_mesh((n, 1, 1), ("dp", "pp", "mp"))
    qoff_ms, qoff_loss = run(qmesh, 1, False)
    qon_ms, qon_loss = run(qmesh, 1, True)
    qbatch = 4 * n

    return {
        "mesh": f"dp{dp}xpp{pp}xmp{mp}",
        "n_devices": n,
        "step_ms": round(step_ms, 3),
        "tok_s_per_chip": round(batch * cfg.seq_len / (step_ms / 1e3) / n, 1),
        "serial_step_ms": round(serial_ms, 3),
        "comm_ms": round(comm_best * 1000.0, 3),
        "quant_tok_s": round(qbatch * cfg.seq_len / (qon_ms / 1e3), 1),
        "quant_off_tok_s": round(qbatch * cfg.seq_len / (qoff_ms / 1e3), 1),
        "quant_off_loss": qoff_loss,
        "quant_on_loss": qon_loss,
    }


def main(argv=None) -> int:
    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(json.dumps(measure()), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, _REPO)
    sys.exit(main())
