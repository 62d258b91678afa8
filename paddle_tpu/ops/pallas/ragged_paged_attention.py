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
  causality, so rows may carry future/garbage page ids (the kernel never
  reads those slots; the XLA arm gathers them, so there they must name
  pages of the pool).
- pos0 [C] int32: absolute position of the chunk's first token.
- n_valid [C] int32 in [1, qb]: valid token count per chunk.
- k_scales / v_scales [P, nKV] fp32 (optional): per-page, per-head
  dequant scales for int8 pages (``serving_kv_quant``). Required iff
  the pages are int8. Both arms dequantize identically — fp32 multiply
  on the gathered/VMEM tile, then cast to the compute dtype
  (ops/quant.py::dequantize_int8) — so the arms stay equality-pinned
  on quantized pages too. In the kernel the scales ride the scalar-
  prefetch path next to the block-table rows and are looked up per
  live page slot and kv head, by the page id the slot was steered by.

Masking is PINNED across both arms: query row i < n_valid attends keys
kpos <= pos0 + i; padding rows i >= n_valid come out as ZEROS from both
arms (mla_paged_attention's contract), so callers may compare full
outputs across arms.  The engine reads rows < n_valid only.

``window`` (static; None or an int >= 1) adds a lower bound: query row i
attends ``pos0 + i - window < kpos <= pos0 + i``, itself and the
``window - 1`` keys before it.  Block-table slots wholly behind the
window of the chunk's FIRST query are never read by the kernel and may
hold any id, as future slots may (the XLA arm gathers them, so there
they must name pages of the pool: the engine keeps the sink there).

Returns o [C, qb, nH, d].  Callers read rows < n_valid (the engine
samples at offset n_valid - 1, or at every offset when verifying
speculative drafts).

The kernel's grid is (C, max_blocks / pps): a step is one row's group
of ``pps`` pages across ALL kv heads.  The pool rides as ``pps`` k and
``pps`` v operands of the same two buffers, each with its own
table-steered block of a whole page ([nKV, d, bs] / [nKV, bs, d], both
contiguous in the pool).  Inside, three passes over the kv heads: every
head's scores against the group's live pages, side by side
[qb*G, pps*bs]; one mask, one running-max update and one rescale of the
accumulator a head a group; every head's value dots.  A group past the
chunk's last valid position computes nothing and names the next row's
first blocks, so it moves no byte of its own and the next row's pages
arrive early (_steer); a dead page slot inside a live group repeats a
block the row already named (_slot).  With a window the groups wholly
behind it are dead the same way: they compute nothing and name the row's
first live group, whose blocks are then there when it starts.  A decode
row (n_valid == 1) runs each head's first G query rows alone (padded to
the sublane tile) with small accumulators of its own.

Which form runs is the autotune's choice among ``"kernel_p4"``,
``"kernel_p2"``, ``"kernel_p1"`` (those that divide max_blocks and whose
working set fits VMEM by ragged_paged_supported's estimate, the largest
group first, which is what a backend that never sweeps runs) and
``"xla"``: by what the gate computes from the shapes, never by a flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode

__all__ = ["ragged_paged_attention", "ragged_paged_supported",
           "candidates_for"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# both arms accumulate scores and values in fp32 (kernel: fp32 scratch
# + preferred_element_type on every dot; XLA arm: the same pin on its
# einsums) — the verifier checks the declaration against the traced
# XLA arm so the arms cannot drift apart.
ACCUM_DTYPE = "float32"


_VMEM_BOUND = 12 * 2 ** 20
_PPS = (4, 2, 1)                    # pages a grid step, largest first


def _vmem_bytes(nkv: int, rows: int, d: int, bs: int, itemsize: int,
                pps: int) -> int:
    """The kernel's VMEM working set at ``pps`` pages a step: the
    double-buffered k and v pages (all kv heads), the double-buffered q
    and o blocks, the fp32 accumulator, the lane-replicated softmax
    state, every head's fp32 scores and probabilities of the group (the
    probabilities counted at four bytes, the widest q), and one head's
    score tile with its mask and its exp in flight."""
    return (2 * pps * 2 * nkv * d * bs * itemsize
            + 2 * 2 * nkv * rows * d * itemsize
            + nkv * rows * d * 4
            + 2 * nkv * rows * 128 * 4
            + nkv * rows * pps * bs * (4 + 4)
            + 3 * rows * pps * bs * 4)


def ragged_paged_supported(kt_pages_shape, n_q_heads: int, qb: int,
                           itemsize: int = 2, pps: int = 1) -> bool:
    """Gate for the MXU unified-RPA kernel at ``pps`` pages a grid step:
    d-major pages with MXU-tileable blocks — a head's score dot is
    [qb*G, d] x [d, bs] a page and its value dot [qb*G, bs] x [bs, d] —
    plus the VMEM bound on the step's working set (_vmem_bytes).

    Head widths, and where each runs: 128 and 256 run the kernel as they
    are (Mistral, command-a-plus).  A model whose heads are 64 wide does
    not ask for 64 here: it pages its kv heads in pairs of 128 (a pair's
    k rows one above the other, its v columns side by side) and sends
    query heads 128 wide with zeros under the pair's other head
    (models/granite_hybrid.py), which is the kernel at 128 with exact
    scores; any other width (a toy's 16) takes the XLA arm, as does a
    page size that is no multiple of 128."""
    _, nkv, d, bs = kt_pages_shape
    if n_q_heads % nkv:
        return False
    G = n_q_heads // nkv
    if (qb * G) % 8:                                # sublane-tileable rows
        return False
    if _vmem_bytes(nkv, qb * G, d, bs, itemsize, pps) > _VMEM_BOUND:
        return False
    return d in (128, 256) and bs % 128 == 0


def candidates_for(kt_pages_shape, n_q_heads: int, qb: int, mb: int,
                   itemsize: int = 2) -> list:
    """The forms this geometry may run, in the order the autotune takes
    them: ``"kernel_p<n>"`` for each n of 4, 2, 1 pages a grid step that
    divides the block table's length and fits VMEM, largest first (more
    pages a step won on every grid measured: PERF.md §6, PR 30), then
    the XLA gather path.  Only ``["xla"]`` where no form fits."""
    return [f"kernel_p{n}" for n in _PPS
            if mb % n == 0 and ragged_paged_supported(
                kt_pages_shape, n_q_heads, qb, itemsize, n)] + ["xla"]


def _slot(j, i: int, pps: int, last_page):
    """The block-table slot that page operand ``i`` of a LIVE group
    ``j`` is steered by.  A live slot is its own; a slot past the
    chunk's last live page repeats what operand ``i`` held in the row's
    last group that had it live (else the last live page), so that it
    names the block the step before it named and Pallas issues no copy,
    whatever the table holds there.  Its keys are masked by position
    either way."""
    k = j * pps + i
    # lax.div, not //: nothing here is negative, and floor division's
    # sign fix-up is most of what lowering ten index maps costs
    back = jax.lax.div(jnp.maximum(k - last_page, 0) + pps - 1, pps)
    kb = k - back * pps
    return jnp.where(kb < 0, last_page, kb)


def _first_page(pos0, window: int, bs: int):
    """The page that holds the first key a chunk starting at ``pos0``
    can see under ``window``: key ``pos0 - window + 1``, or key 0."""
    return jax.lax.div(jnp.maximum(pos0 - window + 1, 0), bs)


def _steer(c, j, pos0_ref, nval_ref, n_rows: int, pps: int, bs: int,
           window=None):
    """(row, group, last live page of that row) whose blocks step (c, j)
    names.  A live group names its own.  A group past the chunk's last
    valid position computes nothing, so it names the NEXT row's first
    group: that row's pages arrive while this row's last live group is
    still computing (the pipeline fetches a step's blocks during the
    step before), every further dead step and the next row's first step
    find the same blocks named and issue no copy.  The last row's dead
    groups repeat its own last blocks.  So a dead step moves no byte
    that a live step would not have moved, whatever the table holds.
    With a ``window`` a row's first LIVE group is the one that holds the
    first key its first query sees: the groups before it are dead too
    and name it, and a row ahead is entered at its first live group."""
    last_page = jax.lax.div(pos0_ref[c] + nval_ref[c] - 1, bs)
    ahead = jnp.logical_and(j * pps > last_page, c + 1 < n_rows)
    cc = jnp.where(ahead, c + 1, c)
    last_cc = jnp.where(ahead, jax.lax.div(pos0_ref[cc] + nval_ref[cc] - 1,
                                           bs), last_page)
    if window is None:
        return cc, jnp.where(ahead, 0, j), last_cc
    first = jax.lax.div(_first_page(pos0_ref[cc], window, bs), pps)
    return cc, jnp.where(ahead, first, jnp.maximum(j, first)), last_cc


def _decode_rows(G: int, rows: int, q_itemsize: int) -> int:
    """Query rows a head of the decode tier runs: the first token's G,
    padded to the q dtype's sublane tile."""
    tile = 8 * max(1, 4 // q_itemsize)
    return min(rows, -(-G // tile) * tile)


def _rpa_kernel(rows_ref, pos0_ref, nval_ref, *refs, qb, bs, G, n_steps,
                pps, sm_scale, quant, mb, nkv, rd, window=None):
    """One (chunk, page-group) program: every kv head's qb*G query rows
    (row r = query token r//G, group head r%G) against ``pps``
    table-selected pages at once — a head's scores of the group's live
    pages side by side, [rows, pps*bs], under one mask, one running-max
    update and one rescale of the accumulator a group — online-softmax
    accumulated in scratch over the page-group grid dim.  Groups (and
    a live group's page slots) entirely past the chunk's last valid
    position are skipped — their keys would be fully masked, and
    exp(-1e30 - m) == 0 in fp32, so skipping is exact, not an
    approximation.  A decode row (n_valid == 1) runs on each head's
    first ``rd`` query rows and its own small accumulators.  Under a
    ``window`` the groups and slots wholly behind the first query's
    window are skipped as well; a later query row may find a live
    group's keys all behind ITS window, and what it accumulates from
    them under the running max's initial -1e30 is wiped by the rescale
    (alpha == 0) at its first real key, which always comes: its own.

    ``quant``: int8 pages — two extra scalar-prefetch refs carry the
    flattened [P * nKV] scale planes; the k/v tiles are dequantized in
    VMEM (fp32 multiply, cast to the q dtype) before the dots, the same
    op order as the XLA arm."""
    import jax.experimental.pallas as pl

    ksc_ref = vsc_ref = None
    if quant:
        ksc_ref, vsc_ref, *refs = refs
    q_ref, k_refs, v_refs = refs[0], refs[1:1 + pps], refs[1 + pps:1 + 2 * pps]
    (o_ref, m_sc, l_sc, acc_sc, m1_sc, l1_sc, acc1_sc, s_sc,
     p_sc) = refs[1 + 2 * pps:]
    c = pl.program_id(0)
    j = pl.program_id(1)
    n = nval_ref[c]
    p0 = pos0_ref[c]
    last = p0 + n - 1                               # last valid position
    # first key the chunk's first query sees (may be negative)
    lo = None if window is None else p0 - window + 1

    def live(k0, k1):
        """Whether pages [k0, k1) hold a key some query row can see."""
        ok = k0 * bs <= last
        return ok if window is None else jnp.logical_and(ok, k1 * bs > lo)

    def heads(fn):
        """fn(h) for every kv head.  Traced once and unrolled when
        lowered: left as a loop the chip runs the heads' dots one after
        another and the kernel takes twice as long (PERF.md §6, PR 30);
        unrolled in Python it costs set-up three times the tracing."""
        jax.lax.fori_loop(0, nkv, lambda h, carry: (fn(h), carry)[1], 0,
                          unroll=True)

    def tier(R, m_ref, l_ref, acc_ref):
        """Each head's R first query rows against this step's pages."""

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref[...], -1e30)
            l_ref[...] = jnp.zeros_like(l_ref[...])
            acc_ref[...] = jnp.zeros_like(acc_ref[...])

        def live_pages(fn):
            """fn(i) for each page slot of the group that is live: the
            group's first always is (the caller's guard; not under a
            window); another may lie past ``last`` or behind the window,
            and its two dots are not made."""
            for i in range(pps):
                if i == 0 and window is None:
                    fn(i)
                else:
                    pl.when(live(j * pps + i, j * pps + i + 1))(
                        functools.partial(fn, i))

        def page(ref, sc_ref, i, h):
            """Head h's tile of live page slot i, dequantized."""
            x = ref[h]
            if quant:   # by the page id the block was steered by
                pg = rows_ref[c * mb + j * pps + i]
                x = (x.astype(jnp.float32)
                     * sc_ref[pg * nkv + h]).astype(q_ref.dtype)
            return x

        # the chunk's first page is never skipped (0 <= last always since
        # n_valid >= 1), so every query row keeps >= 1 real key and l
        # never normalizes junk
        @pl.when(live(j * pps, (j + 1) * pps))
        def _pages():
            # three passes, each over every head, so that the MXU runs
            # the heads' independent dots back to back instead of
            # waiting on one head's softmax between its two
            def scores(i):
                def head(h):
                    s_sc[h, 0:R, i * bs:(i + 1) * bs] = jax.lax.dot(
                        q_ref[h, 0:R, :], page(k_refs[i], ksc_ref, i, h),
                        preferred_element_type=jnp.float32)

                heads(head)

            live_pages(scores)
            shape = (R, pps * bs)
            tok = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, shape, 0), G)
            kpos = j * pps * bs + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1)
            # a dead slot's scores are whatever the scratch held: every
            # key position of it is past ``last``, so the select drops it
            qpos = p0 + jnp.minimum(tok, n - 1)
            mask = kpos <= qpos
            if window is not None:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            def softmax(h):
                s = jnp.where(mask, s_sc[h, 0:R, :] * sm_scale, -1e30)
                m_prev = m_ref[h]                   # [R, 128], lanes alike
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new[:, :1])
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
                m_ref[h] = m_new
                acc_ref[h] = acc_ref[h] * alpha[:, :1]
                p_sc[h, 0:R, :] = p.astype(p_sc.dtype)

            heads(softmax)

            def values(i):
                def head(h):
                    acc_ref[h] += jax.lax.dot(
                        p_sc[h, 0:R, i * bs:(i + 1) * bs],
                        page(v_refs[i], vsc_ref, i, h),
                        preferred_element_type=jnp.float32)

                heads(head)

            live_pages(values)

        @pl.when(j == n_steps - 1)
        def _fin():
            if R < qb * G:
                o_ref[...] = jnp.zeros_like(o_ref[...])
            tok = jax.lax.div(jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape[1:], 0), G)
            def head(h):
                o = acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-30)
                o_ref[h, 0:R, :] = jnp.where(tok < n, o, 0.0).astype(
                    o_ref.dtype)

            heads(head)

    if rd == qb * G:                    # the tile holds the whole block
        tier(rd, m_sc, l_sc, acc_sc)
        return

    @pl.when(n == 1)
    def _decode():
        tier(rd, m1_sc, l1_sc, acc1_sc)

    @pl.when(n != 1)
    def _chunk():
        tier(qb * G, m_sc, l_sc, acc_sc)


@functools.partial(jax.jit, static_argnames=("sm_scale", "pps", "window"))
def ragged_paged_attention_kernel(q, kt_pages, v_pages, rows, pos0,
                                  n_valid, sm_scale: float,
                                  k_scales=None, v_scales=None,
                                  pps: int = 1, window=None):
    """MXU unified-RPA kernel (d-major k pages).  See module docstring
    for the contract; gate with ragged_paged_supported().  ``pps`` pages
    a grid step, a divisor of max_blocks: the pool rides as ``pps`` k
    and ``pps`` v operands of the same two buffers, each with its own
    table-steered block of a whole page across heads.  int8 pages take
    the per-page scale planes as two extra scalar-prefetch operands
    (flattened [P * nKV]) riding next to the block-table rows.  With a
    ``window`` the call carries a name of its own, so that a trace tells
    the two forms apart."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, qb, nH, d = q.shape
    nkv = kt_pages.shape[1]
    G = nH // nkv
    mb = rows.shape[1]
    bs = kt_pages.shape[3]
    if mb % pps:
        raise ValueError(f"pages per step {pps} does not divide the block "
                         f"table's {mb} pages")
    n_steps = mb // pps
    quant = k_scales is not None
    R = qb * G
    rd = _decode_rows(G, R, q.dtype.itemsize)
    # row r of a head's [qb*G, d] q block = (query token r//G, group head
    # r%G): GQA never inflates the page reads, matching the decode kernels
    qg = q.reshape(C, qb, nkv, G, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(C, nkv, R, d)
    rows_flat = rows.reshape(-1).astype(jnp.int32)

    # index maps take every scalar-prefetch ref after the grid indices;
    # the block-table rows steer the block selection, pos0 and n_valid
    # say which of a row's groups and slots are live (_steer, _slot)
    def _omap(c, j, *_):
        return (c, 0, 0, 0)

    def _qmap(c, j, rf, p0, nv, *_):
        return (_steer(c, j, p0, nv, C, pps, bs, window)[0], 0, 0, 0)

    def _pmap(i):
        def index(c, j, rf, p0, nv, *_):
            cc, jj, last_page = _steer(c, j, p0, nv, C, pps, bs, window)
            slot = _slot(jj, i, pps, last_page)
            if window is not None:
                # a slot of the first live group that lies behind the
                # window names the row's first live page
                slot = jnp.maximum(slot, _first_page(p0[cc], window, bs))
            return (rf[cc * mb + slot], 0, 0, 0)
        return index

    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # rows_flat, pos0, n_valid (+ k/v scale planes when quantized)
        num_scalar_prefetch=5 if quant else 3,
        grid=(C, n_steps),
        in_specs=([pl.BlockSpec((None, nkv, R, d), _qmap)]
                  + [pl.BlockSpec((None, nkv, d, bs), _pmap(i))
                     for i in range(pps)]
                  + [pl.BlockSpec((None, nkv, bs, d), _pmap(i))
                     for i in range(pps)]),
        out_specs=pl.BlockSpec((None, nkv, R, d), _omap),
        scratch_shapes=[pltpu.VMEM((nkv, R, 128), f32),
                        pltpu.VMEM((nkv, R, 128), f32),
                        pltpu.VMEM((nkv, R, d), f32),
                        pltpu.VMEM((nkv, rd, 128), f32),
                        pltpu.VMEM((nkv, rd, 128), f32),
                        pltpu.VMEM((nkv, rd, d), f32),
                        pltpu.VMEM((nkv, R, pps * bs), f32),
                        pltpu.VMEM((nkv, R, pps * bs), q.dtype)],
    )
    interpret = _interpret_mode()
    call = pl.pallas_call(
        functools.partial(_rpa_kernel, qb=qb, bs=bs, G=G, n_steps=n_steps,
                          pps=pps, sm_scale=sm_scale, quant=quant, mb=mb,
                          nkv=nkv, rd=rd, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, nkv, R, d), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=("ragged_paged_attention" if window is None
              else "ragged_paged_attention_window"),
    )
    pre = (rows_flat, pos0.astype(jnp.int32), n_valid.astype(jnp.int32))
    if quant:
        pre = pre + (k_scales.reshape(-1).astype(jnp.float32),
                     v_scales.reshape(-1).astype(jnp.float32))
    out = call(*pre, qg, *([kt_pages] * pps), *([v_pages] * pps))
    return out.reshape(C, nkv, qb, G, d).transpose(0, 2, 1, 3, 4).reshape(
        C, qb, nH, d)


def _ragged_paged_xla(q, k_pages, v_pages, rows, pos0, n_valid, sm_scale,
                      k_layout, k_scales=None, v_scales=None, window=None):
    """XLA gather fallback (and the kernel's numerics reference): gather
    each chunk's pages, one masked softmax over the flattened context.
    The same mask as the kernel, and the same zeros in padding rows.
    int8 pages gather their per-page scales alongside and dequantize
    exactly as the kernel does (fp32 multiply, cast to the q dtype, then
    the dots)."""
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
    if window is not None:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    s = s + jnp.where(mask[:, None, None, :, :], 0.0, -1e30)
    # max-subtracted exp/sum (not jax.nn.softmax) to mirror the kernel's
    # online-softmax epilogue: acc / max(l, 1e-30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("chgqs,chsd->cqhgd", (p / l).astype(vg.dtype), vg)
    valid = off[None, :] < n_valid[:, None]
    return jnp.where(valid[:, :, None, None], o.reshape(C, qb, nH, d),
                     0.0).astype(q.dtype)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_slot, _first_page, _steer, _decode_rows,
                                    _rpa_kernel,
                                    ragged_paged_attention_kernel,
                                    _ragged_paged_xla)
    return _SRC


def _tuned_impl(C: int, qb: int, nH: int, d: int, nkv: int, mb: int,
                bs: int, dtype, candidates: list,
                quant: bool = False, window=None) -> str:
    """Impl choice via the autotune registry: the kernel at 4, 2 or 1
    pages a grid step (what ``candidates_for`` admits, the largest
    first, so a backend that never sweeps runs the largest group) or the
    XLA gather path.  The sweep measures a table of distinct pages at
    half the longest context, decode and chunk rows alternating; the
    serving cells' geometry is in the committed table, so no run of them
    sweeps.  Quantized pages tune their own bucket — dequant shifts the
    arms' cost balance (the kernel dequantizes per VMEM tile, the XLA
    arm on the full gathered context), and so does a window, which
    skips most of a long context's groups."""
    from . import autotune

    def measure(impl):
        pdt = jnp.int8 if quant else dtype
        qz = jnp.zeros((C, qb, nH, d), dtype)
        ktz = jnp.zeros((mb + 1, nkv, d, bs), pdt)
        vz = jnp.zeros((mb + 1, nkv, bs, d), pdt)
        rz = jnp.tile(jnp.arange(1, mb + 1, dtype=jnp.int32), (C, 1))
        pz = jnp.full((C,), mb * bs // 2, jnp.int32)
        nz = jnp.where(jnp.arange(C) % 2 == 0, 1, qb).astype(jnp.int32)
        sc = jnp.ones((mb + 1, nkv), jnp.float32) if quant else None
        if impl == "xla":
            arm = lambda x: _ragged_paged_xla(x, ktz, vz, rz, pz, nz,  # noqa: E731
                                              1.0, "d_major", sc, sc,
                                              window)
        else:
            arm = lambda x: ragged_paged_attention_kernel(  # noqa: E731
                x, ktz, vz, rz, pz, nz, 1.0, sc, sc,
                pps=int(impl.split("_p")[1]), window=window)
        # eight calls chained in one program: one call alone is under
        # the host's dispatch floor, where every form reads alike
        chain = jax.jit(lambda x: jax.lax.fori_loop(
            0, 8, lambda _, y: arm(y), x))
        return autotune.time_candidate(lambda: chain(qz)) / 8

    return str(autotune.tuned(
        "ragged_paged_attention",
        f"c{C}_qb{qb}_h{nH}_d{d}_kv{nkv}_mb{mb}_bs{bs}"
        + ("_q8" if quant else "") + (f"_w{window}" if window else ""),
        str(jnp.dtype(dtype)), candidates,
        measure=measure, source=_autotune_source()))


def ragged_paged_attention(q, k_pages, v_pages, rows, pos0, n_valid,
                           sm_scale: float, k_layout: str = "d_major",
                           k_scales=None, v_scales=None, window=None):
    """Unified ragged-paged attention: dispatches the MXU Pallas kernel
    when the page geometry supports it, else the XLA gather path.  See
    module docstring for shapes; int8 pages require both scale planes;
    ``window`` (static) is the sliding window in tokens, None for all."""
    quant = k_pages.dtype == jnp.int8
    if quant and (k_scales is None or v_scales is None):
        raise ValueError("int8 KV pages need k_scales and v_scales "
                         "([P, nKV] fp32 per-page scale planes)")
    C, qb, nH, d = q.shape
    mb = rows.shape[1]
    cands = ["xla"] if k_layout != "d_major" else candidates_for(
        k_pages.shape, nH, qb, mb, k_pages.dtype.itemsize)
    if len(cands) > 1:
        impl = _tuned_impl(C, qb, nH, d, k_pages.shape[1], mb,
                           k_pages.shape[3], q.dtype, cands, quant, window)
        if impl != "xla":
            return ragged_paged_attention_kernel(
                q, k_pages, v_pages, rows, pos0, n_valid, sm_scale,
                k_scales, v_scales, pps=int(impl.split("_p")[1]),
                window=window)
    return _ragged_paged_xla(q, k_pages, v_pages, rows, pos0, n_valid,
                             sm_scale, k_layout, k_scales, v_scales, window)
