"""Pallas TPU unified ragged-paged attention over the paged KV cache.

The serving engine's ONE attention program per step ("Ragged Paged
Attention", arxiv 2604.15464): every grid row is a chunk of qb query
tokens from one request, and a *decode* step is simply a chunk with
n_valid == 1.  Mixed prefill/decode batches therefore share a single
static compiled [n_rows, qb] program — no prefill-program / decode-
quantum boundary, which is the serving-side analogue of the reference's
fused block_multi_head_attention (phi/kernels/fusion/).

Contract shared by the kernel and the XLA fallback:

- q [C, qb, nH, d]: C chunks of qb query tokens each.  Chunk c holds
  tokens at positions [pos0[c], pos0[c] + n_valid[c]) of ONE request;
  rows i >= n_valid[c] are padding.  Idle grid rows use the sink page
  with pos0 = 0, n_valid = 1.
- k_pages [P, nKV, d, bs] d-major (the MXU decode kernel's native
  layout) or [P, nKV, bs, d]; v_pages [P, nKV, bs, d].  The chunk's own
  k/v must already be written to its pages (write-before-attend).
  pos0 need NOT be page-aligned and qb need not divide bs: a chunk may
  straddle a page boundary.
- rows [C, max_blocks] int32: the owning request's FULL block-table row
  per chunk.  Pages past the chunk's last valid position are masked by
  causality, so rows may carry future/garbage page ids.
- pos0 [C] int32: absolute position of the chunk's first token.
- n_valid [C] int32 in [1, qb]: valid token count per chunk.
- k_scales / v_scales [P, nKV] fp32 (optional): per-page, per-head
  dequant scales for int8 pages (``serving_kv_quant``). Required iff
  the pages are int8. Both arms dequantize identically — fp32 multiply
  on the gathered/VMEM tile, then cast to the compute dtype
  (ops/quant.py::dequantize_int8) — so the arms stay equality-pinned
  on quantized pages too. In the kernel the scales ride the scalar-
  prefetch path next to the block-table rows and are looked up per
  (page, kv-head) program.

Masking is PINNED across both arms: query row i attends keys
kpos <= pos0 + min(i, n_valid - 1).  Padding rows i >= n_valid thus
replicate the LAST valid row's mask — they attend only in-request keys
and both arms produce bit-identical garbage, so callers may compare
full outputs (garbage tail included) across arms.

Returns o [C, qb, nH, d].  Callers read rows < n_valid (the engine
samples at offset n_valid - 1, or at every offset when verifying
speculative drafts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode

__all__ = ["ragged_paged_attention", "ragged_paged_supported"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# both arms accumulate scores and values in fp32 (kernel: fp32 scratch
# + preferred_element_type on every dot; XLA arm: the same pin on its
# einsums) — the verifier checks the declaration against the traced
# XLA arm so the arms cannot drift apart.
ACCUM_DTYPE = "float32"


def ragged_paged_supported(kt_pages_shape, n_q_heads: int, qb: int,
                           itemsize: int = 2) -> bool:
    """Gate for the MXU unified-RPA kernel: d-major pages with
    MXU-tileable blocks — the score dot is [qb*G, d] x [d, bs] and the
    value dot [qb*G, bs] x [bs, d] — plus a VMEM working-set bound
    (q block + fp32 acc + double-buffered k/v pages)."""
    _, nkv, d, bs = kt_pages_shape
    if n_q_heads % nkv:
        return False
    G = n_q_heads // nkv
    if (qb * G) % 8:                                # sublane-tileable rows
        return False
    est = (2 * qb * G * d * (itemsize + 4)          # q block + fp32 acc
           + 2 * 2 * 2 * d * bs * itemsize)         # double-buffered k+v
    if est > 12 * 2 ** 20:
        return False
    return d in (128, 256) and bs % 128 == 0


def _rpa_kernel(rows_ref, pos0_ref, nval_ref, *refs, qb, bs, G, n_blocks,
                sm_scale, quant, mb, nkv):
    """One (chunk, kv-head, page) program: this chunk's qb*G query rows
    (row r = query token r//G, group head r%G) against one table-selected
    page, online-softmax accumulated in scratch over the page grid dim.
    Pages entirely past the chunk's last valid position are skipped —
    their keys would be fully masked, and exp(-1e30 - m) == 0 in fp32,
    so skipping is exact, not an approximation.

    ``quant``: int8 pages — two extra scalar-prefetch refs carry the
    flattened [P * nKV] scale planes; the k/v tiles are dequantized in
    VMEM (fp32 multiply, cast to the q dtype) before the dots, the same
    op order as the XLA arm."""
    import jax.experimental.pallas as pl

    if quant:
        ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc \
            = refs
    else:
        ksc_ref = vsc_ref = None
        q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = refs
    c = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    last = pos0_ref[c] + nval_ref[c] - 1            # last valid position

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], -1e30)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    # j == 0 is never skipped (0 <= last always since n_valid >= 1), so
    # every query row keeps >= 1 real key and l never normalizes junk.
    @pl.when(j * bs <= last)
    def _compute():
        q = q_ref[...]                              # [qb*G, d]
        k = k_ref[...]                              # [d, bs] (d-major)
        if quant:
            pg = rows_ref[c * mb + j]
            k = (k.astype(jnp.float32)
                 * ksc_ref[pg * nkv + h]).astype(q.dtype)
        s = jax.lax.dot(q, k, preferred_element_type=jnp.float32) * sm_scale
        off = jax.lax.iota(jnp.int32, qb * G) // G
        qpos = pos0_ref[c] + jnp.minimum(off, nval_ref[c] - 1)
        kpos = j * bs + jax.lax.iota(jnp.int32, bs)
        s = s + jnp.where(kpos[None, :] <= qpos[:, None], 0.0, -1e30)
        m_prev = m_sc[0, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])             # [qb*G, bs]
        alpha = jnp.exp(m_prev - m_new)
        l_sc[0, :] = l_sc[0, :] * alpha + jnp.sum(p, axis=1)
        m_sc[0, :] = m_new
        v = v_ref[...]                              # [bs, d]
        if quant:
            pg = rows_ref[c * mb + j]
            v = (v.astype(jnp.float32)
                 * vsc_ref[pg * nkv + h]).astype(q_ref.dtype)
        pv = jax.lax.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * alpha[:, None] + pv

    @pl.when(j == n_blocks - 1)
    def _fin():
        o_ref[...] = (acc_sc[...] /
                      jnp.maximum(l_sc[0, :], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def ragged_paged_attention_kernel(q, kt_pages, v_pages, rows, pos0,
                                  n_valid, sm_scale: float,
                                  k_scales=None, v_scales=None):
    """MXU unified-RPA kernel (d-major k pages).  See module docstring
    for the contract; gate with ragged_paged_supported().  int8 pages
    take the per-page scale planes as two extra scalar-prefetch
    operands (flattened [P * nKV]) riding next to the block-table
    rows."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, qb, nH, d = q.shape
    nkv = kt_pages.shape[1]
    G = nH // nkv
    mb = rows.shape[1]
    bs = kt_pages.shape[3]
    quant = k_scales is not None
    # row r of the [qb*G, d] q block = (query token r//G, group head r%G):
    # GQA never inflates the page reads, matching the decode kernels
    qg = q.reshape(C, qb, nkv, G, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(C, nkv, qb * G, d)
    rows_flat = rows.reshape(-1).astype(jnp.int32)

    # index maps take every scalar-prefetch ref after the grid indices;
    # only the block-table rows steer the block selection
    def _qmap(c, h, j, rf, *_):
        return (c, h, 0, 0)

    def _pmap(c, h, j, rf, *_):
        return (rf[c * mb + j], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # rows_flat, pos0, n_valid (+ k/v scale planes when quantized)
        num_scalar_prefetch=5 if quant else 3,
        grid=(C, nkv, mb),
        in_specs=[
            pl.BlockSpec((None, None, qb * G, d), _qmap),
            pl.BlockSpec((None, None, d, bs), _pmap),
            pl.BlockSpec((None, None, bs, d), _pmap),
        ],
        out_specs=pl.BlockSpec((None, None, qb * G, d), _qmap),
        scratch_shapes=[pltpu.VMEM((8, qb * G), jnp.float32),
                        pltpu.VMEM((8, qb * G), jnp.float32),
                        pltpu.VMEM((qb * G, d), jnp.float32)],
    )
    call = pl.pallas_call(
        functools.partial(_rpa_kernel, qb=qb, bs=bs, G=G, n_blocks=mb,
                          sm_scale=sm_scale, quant=quant, mb=mb, nkv=nkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, nkv, qb * G, d), q.dtype),
        interpret=_interpret_mode(),
        name="ragged_paged_attention",
    )
    pre = (rows_flat, pos0.astype(jnp.int32), n_valid.astype(jnp.int32))
    if quant:
        pre = pre + (k_scales.reshape(-1).astype(jnp.float32),
                     v_scales.reshape(-1).astype(jnp.float32))
    out = call(*pre, qg, kt_pages, v_pages)
    return out.reshape(C, nkv, qb, G, d).transpose(0, 2, 1, 3, 4).reshape(
        C, qb, nH, d)


def _ragged_paged_xla(q, k_pages, v_pages, rows, pos0, n_valid, sm_scale,
                      k_layout, k_scales=None, v_scales=None):
    """XLA gather fallback (and the kernel's numerics reference): gather
    each chunk's pages, one masked softmax over the flattened context.
    Applies the SAME clamped mask qpos(i) = pos0 + min(i, n_valid-1) so
    padding rows match the kernel bit-for-bit.  int8 pages gather their
    per-page scales alongside and dequantize exactly as the kernel does
    (fp32 multiply, cast to the q dtype, then the dots)."""
    from ..quant import dequantize_int8

    C, qb, nH, d = q.shape
    nkv = k_pages.shape[1]
    G = nH // nkv
    mb = rows.shape[1]
    bs = k_pages.shape[3] if k_layout == "d_major" else k_pages.shape[2]
    kg = jnp.take(k_pages, rows, axis=0)            # [C, mb, nkv, ., .]
    if k_scales is not None:
        kg = dequantize_int8(
            kg, jnp.take(k_scales, rows, axis=0)[..., None, None], q.dtype)
    if k_layout == "d_major":
        kg = jnp.swapaxes(kg, 3, 4)                 # -> [C, mb, nkv, bs, d]
    vg = jnp.take(v_pages, rows, axis=0)            # [C, mb, nkv, bs, d]
    if v_scales is not None:
        vg = dequantize_int8(
            vg, jnp.take(v_scales, rows, axis=0)[..., None, None], q.dtype)
    kg = jnp.swapaxes(kg, 1, 2).reshape(C, nkv, mb * bs, d)
    vg = jnp.swapaxes(vg, 1, 2).reshape(C, nkv, mb * bs, d)
    qg = q.reshape(C, qb, nkv, G, d)
    s = jnp.einsum("cqhgd,chsd->chgqs", qg, kg,
                   preferred_element_type=jnp.float32) * sm_scale
    off = jnp.arange(qb, dtype=jnp.int32)
    qpos = pos0[:, None] + jnp.minimum(off[None, :],
                                       n_valid[:, None] - 1)
    kpos = jnp.arange(mb * bs, dtype=jnp.int32)
    mask = kpos[None, None, :] <= qpos[:, :, None]  # [C, qb, S]
    s = s + jnp.where(mask[:, None, None, :, :], 0.0, -1e30)
    # max-subtracted exp/sum (not jax.nn.softmax) to mirror the kernel's
    # online-softmax epilogue: acc / max(l, 1e-30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("chgqs,chsd->cqhgd", (p / l).astype(vg.dtype), vg)
    return o.reshape(C, qb, nH, d).astype(q.dtype)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_rpa_kernel,
                                    ragged_paged_attention_kernel,
                                    _ragged_paged_xla)
    return _SRC


def _tuned_impl(C: int, qb: int, nH: int, d: int, nkv: int, mb: int,
                bs: int, dtype, quant: bool = False) -> str:
    """Impl choice via the autotune registry.  As with ragged prefill,
    the unified kernel has no free block parameter (blocks ARE the page
    geometry), so the tunable axis is the implementation itself: the MXU
    kernel wins when chunks are deep (many pages re-read per chunk), the
    XLA gather path when the batch is shallow and per-program latency
    dominates.  candidates[0] = "kernel" keeps legacy behavior on
    no-sweep backends.  Quantized pages tune their own bucket — dequant
    shifts the arms' cost balance (the kernel dequantizes per VMEM tile,
    the XLA arm on the full gathered context)."""
    from . import autotune

    def measure(impl):
        pdt = jnp.int8 if quant else dtype
        qz = jnp.zeros((C, qb, nH, d), dtype)
        ktz = jnp.zeros((1, nkv, d, bs), pdt)
        vz = jnp.zeros((1, nkv, bs, d), pdt)
        rz = jnp.zeros((C, mb), jnp.int32)
        pz = jnp.zeros((C,), jnp.int32)
        nz = jnp.ones((C,), jnp.int32)
        sc = jnp.ones((1, nkv), jnp.float32) if quant else None
        if impl == "kernel":
            fn = lambda: ragged_paged_attention_kernel(  # noqa: E731
                qz, ktz, vz, rz, pz, nz, 1.0, sc, sc)
        else:
            fn = lambda: _ragged_paged_xla(qz, ktz, vz, rz, pz, nz,  # noqa: E731
                                           1.0, "d_major", sc, sc)
        return autotune.time_candidate(fn)

    return str(autotune.tuned(
        "ragged_paged_attention",
        f"c{C}_qb{qb}_h{nH}_d{d}_kv{nkv}_mb{mb}_bs{bs}"
        + ("_q8" if quant else ""),
        str(jnp.dtype(dtype)), ["kernel", "xla"],
        measure=measure, source=_autotune_source()))


def ragged_paged_attention(q, k_pages, v_pages, rows, pos0, n_valid,
                           sm_scale: float, k_layout: str = "d_major",
                           k_scales=None, v_scales=None):
    """Unified ragged-paged attention: dispatches the MXU Pallas kernel
    when the page geometry supports it, else the XLA gather path.  See
    module docstring for shapes; int8 pages require both scale planes."""
    quant = k_pages.dtype == jnp.int8
    if quant and (k_scales is None or v_scales is None):
        raise ValueError("int8 KV pages need k_scales and v_scales "
                         "([P, nKV] fp32 per-page scale planes)")
    if (k_layout == "d_major"
            and ragged_paged_supported(k_pages.shape, q.shape[2],
                                       q.shape[1],
                                       k_pages.dtype.itemsize)):
        C, qb, nH, d = q.shape
        impl = _tuned_impl(C, qb, nH, d, k_pages.shape[1], rows.shape[1],
                           k_pages.shape[3], q.dtype, quant)
        if impl == "kernel":
            return ragged_paged_attention_kernel(q, k_pages, v_pages,
                                                 rows, pos0, n_valid,
                                                 sm_scale, k_scales,
                                                 v_scales)
    return _ragged_paged_xla(q, k_pages, v_pages, rows, pos0, n_valid,
                             sm_scale, k_layout, k_scales, v_scales)
