"""A/B the paged-decode kernels on the real chip: GB/s vs the roofline.

Round-3 measured the vector-formulated kernels at ~85-90 GB/s (11% of
the v5e 819 GB/s HBM roofline) and isolated the bound to the per-page
VPU math (PERF.md "Paged decode kernel"). This measures the round-4
MXU-formulated kernel (block-diagonal dots, d-major k pages) against it
at serving shapes. Bandwidth accounting = bytes of k/v actually read
(pages covering seq_len) / device time per call.

    python tools/bench_paged_decode.py            # 1B-class MHA shapes
    python tools/bench_paged_decode.py --gqa      # bench-1B GQA shapes
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def bench(fn, args, short=512, long=2048):
    """Per-call DEVICE time via the delta of two loop lengths:
    (wall_long - wall_short)/(long - short) cancels the per-launch
    overhead, whatever it is (not measured on a locally attached
    chip)."""

    def make_loop(iters):
        @jax.jit
        def loop(q, kp, vp, bt, sl):
            def body(i, acc):
                # acc-dependent input (not algebraically foldable to q),
                # so the call cannot be hoisted out of the loop
                qq = (q.astype(jnp.float32)
                      * (1 + acc * 1e-10)).astype(q.dtype)
                o = fn(qq, kp, vp, bt, sl)
                return acc + o.astype(jnp.float32).mean()

            return jax.lax.fori_loop(0, iters, body,
                                     jnp.zeros((), jnp.float32))

        return loop

    lo, hi = make_loop(short), make_loop(long)
    # float() forces the device->host readback, i.e. device completion
    float(lo(*args))
    float(hi(*args))
    deltas = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(lo(*args))
        t1 = time.perf_counter()
        float(hi(*args))
        t2 = time.perf_counter()
        deltas.append(((t2 - t1) - (t1 - t0)) / (long - short))
    return float(np.median(deltas))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gqa", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--page", type=int, default=128)
    args = ap.parse_args()

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.ops.pallas import decode_attention as da

    enable_compile_cache()
    B, S, bs = args.batch, args.seq, args.page
    nh, d = 16, 128
    nkv = 4 if args.gqa else nh
    max_blocks = S // bs
    n_pages = B * max_blocks
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, nh, d), jnp.bfloat16)
    k_pages = jnp.asarray(rng.randn(n_pages, nkv, bs, d), jnp.bfloat16)
    v_pages = jnp.asarray(rng.randn(n_pages, nkv, bs, d), jnp.bfloat16)
    kt_pages = jnp.swapaxes(k_pages, 2, 3)
    table = jnp.arange(n_pages, dtype=jnp.int32).reshape(B, max_blocks)
    seq_lens = jnp.full((B,), S, jnp.int32)
    scale = 1.0 / np.sqrt(d)

    kv_bytes = 2 * B * max_blocks * nkv * bs * d * 2   # k+v, bf16

    rows = []
    if nkv == nh and da.paged_decode_supported(k_pages.shape, nh,
                                               max_blocks=max_blocks):
        t = bench(functools.partial(da.paged_decode_attention_kernel,
                                    sm_scale=scale),
                  (q, k_pages, v_pages, table, seq_lens))
        rows.append(("vector(index-map)", t))
    if da.paged_decode_mxu_supported(kt_pages.shape, nh,
                                     max_blocks=max_blocks):
        t = bench(functools.partial(da.paged_decode_attention_mxu,
                                    sm_scale=scale),
                  (q, kt_pages, v_pages, table, seq_lens))
        rows.append(("mxu(blkdiag)", t))

    # XLA gather+dot fallback for context
    def xla_gather(q, kp, vp, bt, sl):
        kg = kp[bt]
        vg = vp[bt]
        kg = jnp.swapaxes(kg, 1, 2).reshape(B, nkv, max_blocks * bs, d)
        vg = jnp.swapaxes(vg, 1, 2).reshape(B, nkv, max_blocks * bs, d)
        if nkv != nh:
            kg = jnp.repeat(kg, nh // nkv, axis=1)
            vg = jnp.repeat(vg, nh // nkv, axis=1)
        s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                       kg.astype(jnp.float32)) * scale
        pos = jnp.arange(max_blocks * bs)
        s = jnp.where(pos[None, None, :] < sl[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhk,bhkd->bhd", p.astype(vg.dtype), vg)

    t = bench(xla_gather, (q, k_pages, v_pages, table, seq_lens))
    rows.append(("xla gather+dot", t))

    print(f"B={B} S={S} page={bs} nh={nh} nkv={nkv} d={d} "
          f"kv bytes/call={kv_bytes/2**20:.1f} MiB")
    for name, t in rows:
        print(f"  {name:18s} {t*1e3:7.3f} ms/call  "
              f"{kv_bytes/t/1e9:7.1f} GB/s")


if __name__ == "__main__":
    main()
