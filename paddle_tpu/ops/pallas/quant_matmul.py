"""Pallas TPU weight-only int8 matmul with dequant fused in the epilogue.

The decode-side half of the int8 memory plane (``decode_weight_quant``):
decode at production batch sizes is pinned at the *weight* roofline
(PERF.md), so the win is reading int8 weights from HBM and never
materializing a bf16 copy.  Per-output-channel absmax scales
(ops/quant.py::absmax_quantize_int8) commute with the contraction —
``x @ (w * s_col) == (x @ w) * s_col`` exactly — so dequant is one
fp32 row-vector multiply on the accumulator in the kernel epilogue
instead of a [K, N] upcast before the dot.

- ``quant_matmul(x, wq, scale)``: the tuple-aware matmul entry the
  LLaMA ``_mm`` routes quantized weights through.  x [..., K] (any
  leading dims), wq [K, N] int8, scale [1, N] or [N].  Returns fp32
  [... , N] (callers cast to the compute dtype, exactly like the plain
  ``_mm`` arm).
- MXU kernel: grid (M/bm, N/bn, K/bk), int8 weight tiles cast to the
  activation dtype in VMEM (exact — |w| <= 127), fp32 accumulator
  scratch, scale multiply at the last K step.  Block shapes come from
  the persistent autotune registry (candidates[0] = "xla" keeps the
  legacy dequant-through-XLA behavior on no-sweep backends, so CPU CI
  never pays interpret-mode matmuls).
- XLA fallback everywhere else (unsupported geometry, non-matmul-heavy
  shapes): the same epilogue-dequant algebra, fused by XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode

__all__ = ["quant_matmul", "quant_matmul_supported"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# the MXU kernel accumulates in an fp32 VMEM scratch (every lax.dot
# carries preferred_element_type=jnp.float32) and the XLA fallback's
# einsum pins the same — the verifier checks this declaration against
# the traced fallback so the two arms cannot silently drift.
ACCUM_DTYPE = "float32"


def quant_matmul_supported(M: int, K: int, N: int) -> bool:
    """MXU-kernel gate: sublane-tileable rows and int8-tileable weight
    blocks (min int8 tile is (32, 128), so K and N must carry full
    lanes)."""
    return M % 8 == 0 and K % 128 == 0 and N % 128 == 0


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_sc, *, n_k):
    """One (m, n, k) program: acc += x_tile @ w_tile with the int8
    weight tile cast (exactly) to the activation dtype in VMEM; the
    per-output-channel dequant scale multiplies the fp32 accumulator
    once, at the last K step."""
    import jax.experimental.pallas as pl

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    x = x_ref[...]                                   # [bm, bk]
    w = w_ref[...].astype(x.dtype)                   # [bk, bn] int8 -> exact
    acc_sc[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _fin():
        o_ref[...] = acc_sc[...] * s_ref[...]        # [bm, bn] * [1, bn]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def quant_matmul_kernel(x2, wq, scale, bm: int, bn: int, bk: int):
    """x2 [M, K] @ wq [K, N] int8 -> fp32 [M, N], scale [1, N] fused in
    the epilogue.  Gate with quant_matmul_supported(); block shapes come
    from _tuned_block()."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x2.shape
    N = wq.shape[1]
    grid = (M // bm, N // bn, K // bk)
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_interpret_mode(),
        name="quant_matmul",
    )(x2, wq, scale)
    return out


def _quant_matmul_xla(x, wq, scale):
    """Epilogue-dequant through XLA: int8 operand into the dot (the
    convert fuses into the contraction), one scale row-multiply after."""
    y = jnp.einsum("...k,kn->...n", x, wq.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return y * scale.astype(jnp.float32)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_qmm_kernel, quant_matmul_kernel,
                                    _quant_matmul_xla)
    return _SRC


def _tuned_block(M: int, K: int, N: int, dtype) -> str:
    """Impl + block choice via the autotune registry.  candidates[0] =
    "xla" is the legacy default (there was no Pallas matmul before the
    int8 plane) — no-sweep backends, including CPU CI, keep the XLA
    epilogue path; TPU sweeps race the MXU kernel's block shapes
    against it per shape bucket."""
    from . import autotune

    cands = ["xla"]
    for bm in (128, 64, 32, 16, 8):
        if M % bm or len(cands) > 6:
            continue
        for bn in (256, 128):
            if N % bn:
                continue
            for bk in (1024, 512, 256, 128):
                if K % bk:
                    continue
                vmem = 2 * (bm * bk * 4 + bk * bn) + 2 * bm * bn * 4
                if vmem <= 12 * 2 ** 20:
                    cands.append(f"kernel:{bm}:{bn}:{bk}")
                    break                     # one bk per (bm, bn) bucket

    def measure(impl):
        xz = jnp.zeros((M, K), dtype)
        wz = jnp.zeros((K, N), jnp.int8)
        sz = jnp.ones((1, N), jnp.float32)
        if impl == "xla":
            fn = lambda: _quant_matmul_xla(xz, wz, sz)  # noqa: E731
        else:
            bm, bn, bk = map(int, impl.split(":")[1:])
            fn = lambda: quant_matmul_kernel(xz, wz, sz, bm, bn, bk)  # noqa: E731
        return autotune.time_candidate(fn)

    return str(autotune.tuned(
        "quant_matmul", f"m{M}_k{K}_n{N}", str(jnp.dtype(dtype)), cands,
        measure=measure, source=_autotune_source()))


def quant_matmul(x, wq, scale):
    """Weight-only int8 matmul with epilogue dequant; dispatches the MXU
    kernel when the registry picked one for this shape bucket, else the
    XLA path.  x [..., K]; wq [K, N] int8; scale [1, N] or [N]; returns
    fp32 [..., N]."""
    K, N = wq.shape
    s2 = scale.reshape(1, N)
    lead = x.shape[:-1]
    M = 1
    for n in lead:
        M *= n
    if quant_matmul_supported(M, K, N):
        impl = _tuned_block(M, K, N, x.dtype)
        if impl.startswith("kernel:"):
            bm, bn, bk = map(int, impl.split(":")[1:])
            out = quant_matmul_kernel(x.reshape(M, K), wq,
                                      s2.astype(jnp.float32), bm, bn, bk)
            return out.reshape(*lead, N)
    return _quant_matmul_xla(x, wq, s2)
