"""Pallas TPU latent (MLA) attention over latent pages, absorbed form.

The serving engine's ONE attention program per step for a model with
multi-head latent attention (models/mla_moe.py): every grid row is a
chunk of qb query tokens of one request — a decode step is a chunk with
n_valid == 1 — exactly as in ragged_paged_attention.py, but a token
stores one shared latent ``(c_kv, k_rope)`` and no per-head keys or
values.  Absorbed form: the query is taken into the latent space
(``q_lat = q_nope W_uk^T``), so all heads score against the same page,

    score[h] = (q_lat[h] . c_kv + q_rope[h] . k_rope) * sm_scale
    o_lat[h] = sum_keys softmax(score[h]) c_kv

and the caller takes ``o_lat`` out of the latent space (``W_uv``).

Contract shared by the kernel and the XLA fallback:

- q_lat [C, qb, nH, R], q_rope [C, qb, nH, dr]: C chunks of qb query
  tokens.  Chunk c holds tokens at positions [pos0[c], pos0[c] +
  n_valid[c]) of ONE request; rows i >= n_valid[c] are padding.  Idle
  grid rows use the sink page with pos0 = 0, n_valid = 1.
- ckv_pages [P, bs, R] token-major; krope_pages [P, dr, bs] d-major.
  The latent is stored once: the scores contract ckv_pages over R (the
  page transposed on the MXU), the values read the same page as it
  lies.  The chunk's own latent must already be written
  (write-before-attend).  pos0 need not be page-aligned.
- rows [C, max_blocks] int32: the owning request's block-table row per
  chunk; pages past the chunk's last valid position are masked by
  causality, so rows may carry future/garbage page ids.
- pos0 [C] int32; n_valid [C] int32 in [1, qb].

Masking is PINNED across both arms: query row i < n_valid attends keys
kpos <= pos0 + i; padding rows i >= n_valid come out as ZEROS from both
arms, so callers may compare full outputs.  (The kernel takes a row
with n_valid == 1 — a decode row — through its first token's nH query
rows alone: the other qb - 1 tokens of the block are not computed.)

Returns o_lat [C, qb, nH, R].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode

__all__ = ["mla_paged_attention", "mla_paged_supported"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# both arms accumulate scores and values in fp32 (kernel: fp32 scratch
# + preferred_element_type on every dot; XLA arm: the same pin on its
# score einsums, an fp32 product for the values).
ACCUM_DTYPE = "float32"

_NT = (((1,), (1,)), ((), ()))      # contract both operands' minor dim


def mla_paged_supported(ckv_pages_shape, krope_pages_shape, n_heads: int,
                        qb: int, itemsize: int = 2) -> bool:
    """Gate for the kernel: lane-tileable pages and latent, a
    sublane-tileable rope plane and head block, and a VMEM working set
    (query blocks + fp32 accumulator + a double-buffered page) under the
    bound the other kernels use."""
    _, bs, R = ckv_pages_shape
    dr = krope_pages_shape[1]
    if bs % 128 or R % 128 or dr % 16 or n_heads % 16 or itemsize != 2:
        return False
    rows = qb * n_heads
    est = (2 * rows * (R + dr) * itemsize + rows * R * (4 + 2 * itemsize)
           + 2 * 2 * bs * (R + dr) * itemsize)
    return est <= 12 * 2 ** 20


def _mla_kernel(rows_ref, pos0_ref, nval_ref, ql_ref, qr_ref, *refs, qb, bs,
                nH, n_steps, pps, sm_scale):
    """One (chunk, page-group) program: the chunk's query rows (row r =
    query token r // nH, head r % nH) against ``pps`` table-selected
    pages at once (their scores side by side, one softmax update for
    the group), online-softmax accumulated in scratch over the
    page-group grid dim.  Groups entirely past the chunk's last valid
    position are skipped (exact: their keys would all be masked).  A
    decode row runs on the first nH query rows and its own small
    accumulators."""
    import jax.experimental.pallas as pl

    pages = [(refs[2 * i], refs[2 * i + 1]) for i in range(pps)]
    o_ref, m_sc, l_sc, acc_sc, m1_sc, l1_sc, acc1_sc = refs[2 * pps:]
    c = pl.program_id(0)
    j = pl.program_id(1)
    n = nval_ref[c]
    last = pos0_ref[c] + n - 1                      # last valid position
    decode = n == 1

    def tier(R, m_ref, l_ref, acc_ref):
        """The R first query rows against this step's pages."""

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref[...], -1e30)
            l_ref[...] = jnp.zeros_like(l_ref[...])
            acc_ref[...] = jnp.zeros_like(acc_ref[...])

        # the request's first page is never skipped (last >= 0), so every
        # valid query row keeps >= 1 real key; a group's later pages may
        # lie past ``last``: their keys are masked like any future key
        @pl.when(j * pps * bs <= last)
        def _pages():
            ql, qr = ql_ref[0:R, :], qr_ref[0:R, :]
            ckvs = [ckv_ref[...] for ckv_ref, _ in pages]       # [bs, R] each
            s = jnp.concatenate(
                [jax.lax.dot_general(ql, ckv, _NT,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot(qr, kr_ref[...],
                               preferred_element_type=jnp.float32)
                 for ckv, (_, kr_ref) in zip(ckvs, pages)],
                axis=1) * sm_scale                           # [R, pps*bs]
            tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // nH
            kpos = j * pps * bs + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = s + jnp.where(
                kpos <= pos0_ref[c] + jnp.minimum(tok, n - 1), 0.0, -1e30)
            m_prev = m_ref[0, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[0, :] = l_ref[0, :] * alpha + jnp.sum(p, axis=1)
            m_ref[0, :] = m_new
            pv = sum(jax.lax.dot(p[:, i * bs:(i + 1) * bs].astype(ckv.dtype),
                                 ckv, preferred_element_type=jnp.float32)
                     for i, ckv in enumerate(ckvs))
            acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

        @pl.when(j == n_steps - 1)
        def _fin():
            o = acc_ref[...] / jnp.maximum(l_ref[0, :], 1e-30)[:, None]
            if R == qb * nH:
                tok = jax.lax.broadcasted_iota(jnp.int32, o.shape, 0) // nH
                o_ref[...] = jnp.where(tok < n, o, 0.0).astype(o_ref.dtype)
            else:
                o_ref[...] = jnp.zeros_like(o_ref[...])
                o_ref[0:R, :] = o.astype(o_ref.dtype)

    @pl.when(decode)
    def _decode():
        tier(nH, m1_sc, l1_sc, acc1_sc)

    @pl.when(jnp.logical_not(decode))
    def _chunk():
        tier(qb * nH, m_sc, l_sc, acc_sc)


@functools.partial(jax.jit, static_argnames=("sm_scale", "pps"))
def mla_paged_attention_kernel(q_lat, q_rope, ckv_pages, krope_pages, rows,
                               pos0, n_valid, sm_scale: float, pps: int = 1):
    """The kernel arm (module docstring has the contract; gate with
    mla_paged_supported()).  ``pps`` pages per grid step, a divisor of
    max_blocks: each step's fixed cost is paid once for the group (the
    pool rides as ``pps`` operands of one buffer, each with its own
    table-steered block)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, qb, nH, R = q_lat.shape
    dr = q_rope.shape[3]
    mb = rows.shape[1]
    bs = ckv_pages.shape[1]
    if mb % pps:
        raise ValueError(f"pages per step {pps} does not divide the block "
                         f"table's {mb} pages")
    n_steps = mb // pps
    ql = q_lat.reshape(C, qb * nH, R)
    qr = q_rope.reshape(C, qb * nH, dr)
    rows_flat = rows.reshape(-1).astype(jnp.int32)

    def _qmap(c, j, *_):
        return (c, 0, 0)

    def _pmap(i):
        return lambda c, j, rf, *_: (rf[c * mb + j * pps + i], 0, 0)

    page_specs = []
    for i in range(pps):
        page_specs += [pl.BlockSpec((None, bs, R), _pmap(i)),
                       pl.BlockSpec((None, dr, bs), _pmap(i))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # rows_flat, pos0, n_valid
        grid=(C, n_steps),
        in_specs=[pl.BlockSpec((None, qb * nH, R), _qmap),
                  pl.BlockSpec((None, qb * nH, dr), _qmap)] + page_specs,
        out_specs=pl.BlockSpec((None, qb * nH, R), _qmap),
        scratch_shapes=[pltpu.VMEM((8, qb * nH), jnp.float32),
                        pltpu.VMEM((8, qb * nH), jnp.float32),
                        pltpu.VMEM((qb * nH, R), jnp.float32),
                        pltpu.VMEM((8, nH), jnp.float32),
                        pltpu.VMEM((8, nH), jnp.float32),
                        pltpu.VMEM((nH, R), jnp.float32)],
    )
    interpret = _interpret_mode()
    out = pl.pallas_call(
        functools.partial(_mla_kernel, qb=qb, bs=bs, nH=nH, n_steps=n_steps,
                          pps=pps, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, qb * nH, R), q_lat.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_paged_attention",
    )(rows_flat, pos0.astype(jnp.int32), n_valid.astype(jnp.int32), ql, qr,
      *([ckv_pages, krope_pages] * pps))
    return out.reshape(C, qb, nH, R)


def _mla_paged_xla(q_lat, q_rope, ckv_pages, krope_pages, rows, pos0,
                   n_valid, sm_scale):
    """XLA gather fallback (and the kernel's numerics reference): gather
    each chunk's pages, one masked softmax over the flattened context,
    the same mask and the same zeroed padding rows as the kernel."""
    C, qb, nH, R = q_lat.shape
    mb, bs = rows.shape[1], ckv_pages.shape[1]
    f32 = jnp.float32
    ckv = jnp.take(ckv_pages, rows, axis=0).reshape(C, mb * bs, R)
    kr = jnp.swapaxes(jnp.take(krope_pages, rows, axis=0), 2, 3).reshape(
        C, mb * bs, -1)
    s = (jnp.einsum("cqhl,csl->chqs", q_lat, ckv, preferred_element_type=f32)
         + jnp.einsum("cqhr,csr->chqs", q_rope, kr,
                      preferred_element_type=f32)) * sm_scale
    off = jnp.arange(qb, dtype=jnp.int32)
    qpos = pos0[:, None] + jnp.minimum(off[None, :], n_valid[:, None] - 1)
    kpos = jnp.arange(mb * bs, dtype=jnp.int32)
    mask = kpos[None, None, :] <= qpos[:, :, None]              # [C, qb, S]
    s = s + jnp.where(mask[:, None], 0.0, -1e30)
    # max-subtracted exp/sum to mirror the kernel's online-softmax
    # epilogue: acc / max(l, 1e-30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    # the probabilities take the page's dtype as in the kernel; the sum
    # over keys is fp32 (an fp32 product: XLA:CPU has no batched
    # bf16 x bf16 = f32 dot)
    o = jnp.einsum("chqs,csl->cqhl", (p / l).astype(ckv.dtype).astype(f32),
                   ckv.astype(f32))
    valid = off[None, :] < n_valid[:, None]
    return jnp.where(valid[:, :, None, None], o, 0.0).astype(q_lat.dtype)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_mla_kernel, mla_paged_attention_kernel,
                                    _mla_paged_xla)
    return _SRC


def candidates_for(mb: int) -> list:
    """The kernel at ``"kernel_p<n>"`` pages a grid step, n the largest
    of 4, 2, 1 that divides the block table's length (more pages a step
    won on every grid measured: PERF.md §6, PR 29), or the XLA gather
    path."""
    return [f"kernel_p{next(n for n in (4, 2, 1) if mb % n == 0)}", "xla"]


def _tuned_impl(C: int, qb: int, nH: int, R: int, dr: int, mb: int, bs: int,
                dtype) -> str:
    """Impl choice via the autotune registry.  The sweep measures a
    table of distinct pages at half the longest context on every row;
    the serving cell's geometry is in the committed table, so no run of
    it sweeps."""
    from . import autotune

    def measure(impl):
        ql = jnp.zeros((C, qb, nH, R), dtype)
        qr = jnp.zeros((C, qb, nH, dr), dtype)
        ckv = jnp.zeros((mb + 1, bs, R), dtype)
        kr = jnp.zeros((mb + 1, dr, bs), dtype)
        rz = jnp.tile(jnp.arange(1, mb + 1, dtype=jnp.int32), (C, 1))
        pz = jnp.full((C,), mb * bs // 2, jnp.int32)
        nz = jnp.where(jnp.arange(C) % 2 == 0, 1, qb).astype(jnp.int32)
        if impl == "xla":
            fn = lambda: _mla_paged_xla(ql, qr, ckv, kr, rz, pz, nz,  # noqa: E731
                                        1.0)
        else:
            fn = lambda: mla_paged_attention_kernel(  # noqa: E731
                ql, qr, ckv, kr, rz, pz, nz, 1.0, int(impl.split("_p")[1]))
        return autotune.time_candidate(fn)

    return str(autotune.tuned(
        "mla_paged_attention",
        f"c{C}_qb{qb}_h{nH}_r{R}_dr{dr}_mb{mb}_bs{bs}",
        str(jnp.dtype(dtype)), candidates_for(mb), measure=measure,
        source=_autotune_source()))


def mla_paged_attention(q_lat, q_rope, ckv_pages, krope_pages, rows, pos0,
                        n_valid, sm_scale: float):
    """Absorbed latent attention over latent pages: the Pallas kernel
    where the page geometry supports it, else the XLA gather path.  See
    the module docstring for shapes."""
    C, qb, nH, R = q_lat.shape
    if mla_paged_supported(ckv_pages.shape, krope_pages.shape, nH, qb,
                           ckv_pages.dtype.itemsize):
        impl = _tuned_impl(C, qb, nH, R, q_rope.shape[3], rows.shape[1],
                           ckv_pages.shape[1], q_lat.dtype)
        if impl != "xla":
            return mla_paged_attention_kernel(
                q_lat, q_rope, ckv_pages, krope_pages, rows, pos0, n_valid,
                sm_scale, int(impl.split("_p")[1]))
    return _mla_paged_xla(q_lat, q_rope, ckv_pages, krope_pages, rows, pos0,
                          n_valid, sm_scale)
