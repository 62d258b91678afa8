"""Pallas TPU grouped/segmented BGMV matmul for per-request LoRA serving.

The multi-tenant analogue of the unified-RPA idea (inference/serving.py):
request heterogeneity — here, WHICH low-rank adapter each packed row
carries — is DATA riding one static program, never a shape. Row c of a
packed ``[C, qb, H]`` activation batch belongs to one request whose
adapter slot is ``ids[c]``; the program computes

    out[c] = (x[c] @ A[ids[c]]) @ B[ids[c]]        # [qb, N] fp32

for every row in one dispatch (BGMV: batched gather matrix-vector /
thin-matmul across heterogeneous adapters). Slot 0 is the identity
adapter (all-zero A/B), so rows without an adapter ride the same program
and contribute an exact +0.0 to the base projection.

- MXU kernel: grid ``(C, N/bn)``; the per-row adapter id steers the A/B
  block selection through the scalar-prefetch path (the same mechanism
  the RPA kernel uses for block-table rows), so the gather costs an
  index lookup, not an HBM copy of the stack. Both dots run in fp32
  (r is tiny — the first dot is bandwidth-bound anyway), keeping the
  kernel bit-identical to the XLA arm.
- XLA gather fallback everywhere else: ``take`` the per-row A/B then two
  fp32 einsums — the same op order, so the arms stay equality-pinned
  (tests/test_multitenant.py compares full outputs bitwise on CPU).
- Autotune-registered with "xla" as candidates[0] per repo convention:
  no-sweep backends (including CPU CI) never pay an interpret-mode
  matmul; TPU sweeps race bn block widths against the gather path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode

__all__ = ["lora_matmul", "lora_matmul_supported"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# both BGMV dots accumulate in fp32 (preferred_element_type) in the
# kernel and the XLA fallback alike.
ACCUM_DTYPE = "float32"


def lora_matmul_supported(qb: int, H: int, r: int, N: int) -> bool:
    """MXU-kernel gate: sublane-tileable row blocks, full-lane H/N, and
    a VMEM working set (x block + A/B blocks + fp32 out block) under the
    same 12 MiB bound the other kernels use."""
    if qb % 8 or H % 128 or N % 128 or r % 8 or r > 256:
        return False
    est = 4 * (qb * H + H * r + r * N + qb * N)     # all fp32 in VMEM
    return est <= 12 * 2 ** 20


def _lora_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    """One (row, n-block) program: this row's [qb, H] activations
    through ITS adapter's A/B blocks (selected by the scalar-prefetched
    ids in the index maps — the refs already hold adapter ids[c]'s
    tiles). fp32 on both dots == the XLA arm's op order exactly."""
    x = x_ref[0].astype(jnp.float32)                # [qb, H]
    a = a_ref[0].astype(jnp.float32)                # [H, r]
    b = b_ref[0].astype(jnp.float32)                # [r, bn]
    t = jax.lax.dot(x, a, preferred_element_type=jnp.float32)
    o_ref[0] = jax.lax.dot(t, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn",))
def lora_matmul_kernel(x, a_stack, b_stack, ids, bn: int):
    """x [C, qb, H] @ per-row (A, B) gathered from the stacks -> fp32
    [C, qb, N]. a_stack [S, H, r]; b_stack [S, r, N]; ids [C] int32 in
    [0, S). Gate with lora_matmul_supported(); bn comes from
    _tuned_impl()."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, qb, H = x.shape
    r = a_stack.shape[2]
    N = b_stack.shape[2]

    # index maps receive the scalar-prefetch ref after the grid indices;
    # the adapter id steers the A/B block selection per row
    def _xmap(c, n, ids_ref):
        return (c, 0, 0)

    def _amap(c, n, ids_ref):
        return (ids_ref[c], 0, 0)

    def _bmap(c, n, ids_ref):
        return (ids_ref[c], 0, n)

    def _omap(c, n, ids_ref):
        return (c, 0, n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C, N // bn),
        in_specs=[
            pl.BlockSpec((1, qb, H), _xmap),
            pl.BlockSpec((1, H, r), _amap),
            pl.BlockSpec((1, r, bn), _bmap),
        ],
        out_specs=pl.BlockSpec((1, qb, bn), _omap),
    )
    return pl.pallas_call(
        _lora_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, qb, N), jnp.float32),
        interpret=_interpret_mode(),
        name="lora_matmul",
    )(ids.astype(jnp.int32), x, a_stack, b_stack)


def _lora_xla(x, a_stack, b_stack, ids):
    """XLA gather fallback (and the kernel's numerics reference): gather
    each row's adapter pair, then the same two fp32 dots in the same
    order — full-output bitwise parity with the kernel."""
    a = jnp.take(a_stack, ids, axis=0)              # [C, H, r]
    b = jnp.take(b_stack, ids, axis=0)              # [C, r, N]
    t = jnp.einsum("cqh,chr->cqr", x.astype(jnp.float32),
                   a.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("cqr,crn->cqn", t, b.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_lora_kernel, lora_matmul_kernel,
                                    _lora_xla)
    return _SRC


def _tuned_impl(C: int, qb: int, H: int, r: int, N: int, dtype) -> str:
    """Impl + block choice via the autotune registry. candidates[0] =
    "xla" is the legacy default (there was no LoRA path before the
    multi-tenant subsystem) — no-sweep backends, including CPU CI, keep
    the gather path; TPU sweeps race bn widths of the BGMV kernel
    against it per shape bucket."""
    from . import autotune

    cands = ["xla"]
    for bn in (512, 256, 128):
        if N % bn == 0 and lora_matmul_supported(qb, H, r, bn):
            cands.append(f"kernel:{bn}")

    def measure(impl):
        xz = jnp.zeros((C, qb, H), dtype)
        az = jnp.zeros((2, H, r), dtype)
        bz = jnp.zeros((2, r, N), dtype)
        iz = jnp.zeros((C,), jnp.int32)
        if impl == "xla":
            fn = lambda: _lora_xla(xz, az, bz, iz)  # noqa: E731
        else:
            bn = int(impl.split(":")[1])
            fn = lambda: lora_matmul_kernel(xz, az, bz, iz, bn)  # noqa: E731
        return autotune.time_candidate(fn)

    return str(autotune.tuned(
        "lora_matmul", f"c{C}_qb{qb}_h{H}_r{r}_n{N}",
        str(jnp.dtype(dtype)), cands, measure=measure,
        source=_autotune_source()))


def lora_matmul(x, a_stack, b_stack, ids):
    """Grouped per-row LoRA delta: (x[c] @ A[ids[c]]) @ B[ids[c]] for
    every packed row in one program. x [C, qb, H]; a_stack [S, H, r];
    b_stack [S, r, N]; ids [C] int32. Returns fp32 [C, qb, N] (callers
    add it to the base projection and cast). Dispatches the BGMV kernel
    when the registry picked one for this shape bucket, else the XLA
    gather path."""
    C, qb, H = x.shape
    r = a_stack.shape[2]
    N = b_stack.shape[2]
    if lora_matmul_supported(qb, H, r, N):
        impl = _tuned_impl(C, qb, H, r, N, x.dtype)
        if impl.startswith("kernel:"):
            return lora_matmul_kernel(x, a_stack, b_stack, ids,
                                      int(impl.split(":")[1]))
    return _lora_xla(x, a_stack, b_stack, ids)
