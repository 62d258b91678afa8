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

Two widths: q and the k pages may be ``dk`` wide and the v pages ``dv``
(k_pages [P, nKV, dk, bs], v_pages [P, nKV, bs, dv], q [C, qb, nH, dk]);
the output is then [C, qb, nH, dv].  Pages hold their widths exactly.

``sinks`` (None or [nH] fp32) adds a learned logit a query head with no
value behind it: the head's softmax runs over its keys and the sink,
``exp(s_j - m) / (exp(sink - m) + sum exp(s_j' - m))``.  Both arms start
every row's online softmax at ``m = sink``, ``l = 1``, ``acc = 0``,
which is that formula exactly; padding rows are still zeros.

Returns o [C, qb, nH, dv].  Callers read rows < n_valid (the engine
samples at offset n_valid - 1, or at every offset when verifying
speculative drafts).

The kernel has two grids.  Without a window it is (C, max_blocks / pps):
step (c, j) is row c's group j of ``pps`` pages across ALL kv heads.
Under a ``window`` it is (C, n) with n = min(max_blocks / pps,
ceil((window + qb - 1) / (pps * bs)) + 1), the most groups a row's
window can reach: step (c, j) is row c's group first(c) + j, first(c)
the group that holds the first key the row's first query sees, so no
step lies behind the window (_group).  The pool rides as ``pps`` k and
``pps`` v operands of the same two buffers, each with its own
table-steered block of a whole page ([nKV, d, bs] / [nKV, bs, d], both
contiguous in the pool).  Inside, three passes over the kv heads: every
head's scores against the group's live pages, side by side
[qb*G, pps*bs]; one mask, one running-max update and one rescale of the
accumulator a head a group; every head's value dots.  A group past the
chunk's last valid position computes nothing and names the next row's
first (live) blocks, so it moves no byte of its own and the next row's
pages arrive early (_steer); a dead page slot inside a live group
repeats a block the row already named (_slot), and a slot of the first
live group that lies behind the window names the row's first live page.
A decode row (n_valid == 1) runs each head's first G query rows alone
(padded to the sublane tile) with small accumulators of its own.  A call
whose widths differ or that has sinks is named
``ragged_paged_attention_split`` (``..._split_window`` under a window),
so that a trace tells those forms from the one-width forms.

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
                pps: int, dv=None) -> int:
    """The kernel's VMEM working set at ``pps`` pages a step: the
    double-buffered k and v pages (all kv heads), the double-buffered q
    and o blocks, the fp32 accumulator, the lane-replicated softmax
    state, every head's fp32 scores and probabilities of the group (the
    probabilities counted at four bytes, the widest q), and one head's
    score tile with its mask and its exp in flight.  ``d`` is the width
    of q and k, ``dv`` that of v and o (``d`` where None)."""
    dv = d if dv is None else dv
    return (2 * pps * nkv * (d + dv) * bs * itemsize
            + 2 * nkv * rows * (d + dv) * itemsize
            + nkv * rows * dv * 4
            + 2 * nkv * rows * 128 * 4
            + nkv * rows * pps * bs * (4 + 4)
            + 3 * rows * pps * bs * 4)


def _widths_ok(d: int, dv) -> bool:
    """Head widths the kernel runs: 128 or 256 for q, k and v alike, or
    q and k 128, 192 or 256 wide beside values of 128 or 256."""
    if dv is None or dv == d:
        return d in (128, 256)
    return d in (128, 192, 256) and dv in (128, 256)


def ragged_paged_supported(kt_pages_shape, n_q_heads: int, qb: int,
                           itemsize: int = 2, pps: int = 1,
                           dv=None) -> bool:
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
    scores.  Keys wider than values (``dv``, the v pages' width) run at
    q and k 192 beside v 128 (models/mimo_v2.py), _widths_ok; any other
    width (a toy's 16) takes the XLA arm, as does a page size that is no
    multiple of 128."""
    _, nkv, d, bs = kt_pages_shape
    if n_q_heads % nkv:
        return False
    G = n_q_heads // nkv
    if (qb * G) % 8:                                # sublane-tileable rows
        return False
    if _vmem_bytes(nkv, qb * G, d, bs, itemsize, pps, dv) > _VMEM_BOUND:
        return False
    return _widths_ok(d, dv) and bs % 128 == 0


def candidates_for(kt_pages_shape, n_q_heads: int, qb: int, mb: int,
                   itemsize: int = 2, dv=None) -> list:
    """The forms this geometry may run, in the order the autotune takes
    them: ``"kernel_p<n>"`` for each n of 4, 2, 1 pages a grid step that
    divides the block table's length and fits VMEM, largest first (more
    pages a step won on every grid measured: PERF.md §6, PR 30), then
    the XLA gather path.  Only ``["xla"]`` where no form fits."""
    return [f"kernel_p{n}" for n in _PPS
            if mb % n == 0 and ragged_paged_supported(
                kt_pages_shape, n_q_heads, qb, itemsize, n, dv)] + ["xla"]


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


def _group(c, j, pos0_ref, pps: int, bs: int, window=None):
    """The group of row ``c``'s block table that grid step ``j`` of the
    row stands for: ``j`` itself, or under a ``window`` the row's first
    live group (the one holding its first visible key) + ``j``, which
    may lie past the table (a row near the longest context): such a step
    is dead and _slot clamps its slots."""
    if window is None:
        return j
    return jax.lax.div(_first_page(pos0_ref[c], window, bs), pps) + j


def _steer(c, j, pos0_ref, nval_ref, n_rows: int, pps: int, bs: int,
           window=None):
    """(row, group, last live page of that row) whose blocks step (c, j)
    names; the group is absolute, a column of the block table (_group).
    A live group names its own.  A group past the chunk's last valid
    position computes nothing, so it names the NEXT row's first group:
    that row's pages arrive while this row's last live group is still
    computing (the pipeline fetches a step's blocks during the step
    before), every further dead step and the next row's first step find
    the same blocks named and issue no copy.  The last row's dead groups
    repeat its own last blocks.  So a dead step moves no byte that a
    live step would not have moved, whatever the table holds.  Under a
    ``window`` a row's first step is its first LIVE group, the one that
    holds the first key its first query sees, and a row ahead is
    entered there."""
    last_page = jax.lax.div(pos0_ref[c] + nval_ref[c] - 1, bs)
    g = _group(c, j, pos0_ref, pps, bs, window)
    ahead = jnp.logical_and(g * pps > last_page, c + 1 < n_rows)
    cc = jnp.where(ahead, c + 1, c)
    last_cc = jnp.where(ahead, jax.lax.div(pos0_ref[cc] + nval_ref[cc] - 1,
                                           bs), last_page)
    return (cc, jnp.where(ahead, _group(cc, 0, pos0_ref, pps, bs, window), g),
            last_cc)


def _grid_steps(mb: int, pps: int, bs: int, qb: int, window=None) -> int:
    """A row's grid steps: every group of the table, or under a
    ``window`` the most groups that the ``window + qb - 1`` keys a
    chunk's queries can see may straddle (one more than they fill)."""
    n = mb // pps
    if window is None:
        return n
    return min(n, -(-(window + qb - 1) // (pps * bs)) + 1)


def _decode_rows(G: int, rows: int, q_itemsize: int) -> int:
    """Query rows a head of the decode tier runs: the first token's G,
    padded to the q dtype's sublane tile."""
    tile = 8 * max(1, 4 // q_itemsize)
    return min(rows, -(-G // tile) * tile)


def _rpa_kernel(rows_ref, pos0_ref, nval_ref, *refs, qb, bs, G, n_steps,
                pps, sm_scale, quant, mb, nkv, rd, window=None, sink=False):
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
    ``window`` step j is the table's group ``g`` = the row's first live
    group + j (_group): no step lies behind the first query's window,
    and the slots of the first group that do are skipped; a step whose
    ``g`` runs past the row's last page is dead as above.  A later
    query row may find a live group's keys all behind ITS window, and
    what it accumulates from them under the running max's initial -1e30
    is wiped by the rescale (alpha == 0) at its first real key, which
    always comes: its own.

    ``quant``: int8 pages — two extra scalar-prefetch refs carry the
    flattened [P * nKV] scale planes; the k/v tiles are dequantized in
    VMEM (fp32 multiply, cast to the q dtype) before the dots, the same
    op order as the XLA arm.

    ``sink``: one more scalar-prefetch ref carries the [nH] sink logits;
    each head's softmax state starts at ``m = sink``, ``l = 1`` (row r
    of kv head h is query head h * G + r % G), and a key that the mask
    hides then adds exp(-1e30 - sink) == 0."""
    import jax.experimental.pallas as pl

    ksc_ref = vsc_ref = sk_ref = None
    if quant:
        ksc_ref, vsc_ref, *refs = refs
    if sink:
        sk_ref, *refs = refs
    q_ref, k_refs, v_refs = refs[0], refs[1:1 + pps], refs[1 + pps:1 + 2 * pps]
    (o_ref, m_sc, l_sc, acc_sc, m1_sc, l1_sc, acc1_sc, s_sc,
     p_sc) = refs[1 + 2 * pps:]
    c = pl.program_id(0)
    j = pl.program_id(1)                            # the row's step
    g = _group(c, j, pos0_ref, pps, bs, window)     # the table's group
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
            if sink:
                group = jax.lax.rem(jax.lax.broadcasted_iota(
                    jnp.int32, m_ref.shape[1:], 0), G)

                def start(h):
                    m = jnp.zeros(m_ref.shape[1:], jnp.float32)
                    for g in range(G):
                        m = jnp.where(group == g, sk_ref[h * G + g], m)
                    m_ref[h] = m

                heads(start)
                l_ref[...] = jnp.ones_like(l_ref[...])
            else:
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
                    pl.when(live(g * pps + i, g * pps + i + 1))(
                        functools.partial(fn, i))

        def page(ref, sc_ref, i, h):
            """Head h's tile of live page slot i, dequantized."""
            x = ref[h]
            if quant:   # by the page id the block was steered by
                pg = rows_ref[c * mb + g * pps + i]
                x = (x.astype(jnp.float32)
                     * sc_ref[pg * nkv + h]).astype(q_ref.dtype)
            return x

        # the chunk's first page is never skipped (0 <= last always since
        # n_valid >= 1), so every query row keeps >= 1 real key and l
        # never normalizes junk
        @pl.when(live(g * pps, (g + 1) * pps))
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
            kpos = g * pps * bs + jax.lax.broadcasted_iota(
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


def _name(split: bool, window) -> str:
    """The call's name in a trace: one form for each of (one width and
    no sinks, or not) x (no window, or a window)."""
    return ("ragged_paged_attention" + ("_split" if split else "")
            + ("" if window is None else "_window"))


@functools.partial(jax.jit, static_argnames=("sm_scale", "pps", "window"))
def ragged_paged_attention_kernel(q, kt_pages, v_pages, rows, pos0,
                                  n_valid, sm_scale: float,
                                  k_scales=None, v_scales=None,
                                  pps: int = 1, window=None, sinks=None):
    """MXU unified-RPA kernel (d-major k pages).  See module docstring
    for the contract; gate with ragged_paged_supported().  ``pps`` pages
    a grid step, a divisor of max_blocks: the pool rides as ``pps`` k
    and ``pps`` v operands of the same two buffers, each with its own
    table-steered block of a whole page across heads.  int8 pages take
    the per-page scale planes as two extra scalar-prefetch operands
    (flattened [P * nKV]) riding next to the block-table rows.  With a
    ``window`` the call carries a name of its own, so that a trace tells
    the two forms apart; under it a row's grid walks only the groups its
    window can reach (_grid_steps).  Values narrower than keys or
    ``sinks`` name the call ``..._split`` (_name); the sinks ride the
    scalar-prefetch path behind the rest.  Tracing the call leaves one
    instant ``ragged_paged_attention.grid`` in the ring: which grid it
    took (and for a ``_split`` form, its widths and whether it has
    sinks)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ... import obs as _obs

    C, qb, nH, d = q.shape
    nkv = kt_pages.shape[1]
    G = nH // nkv
    mb = rows.shape[1]
    bs = kt_pages.shape[3]
    dv = v_pages.shape[3]
    if mb % pps:
        raise ValueError(f"pages per step {pps} does not divide the block "
                         f"table's {mb} pages")
    n_steps = _grid_steps(mb, pps, bs, qb, window)
    sink = sinks is not None
    split = dv != d or sink
    _obs.instant("ragged_paged_attention.grid", name=_name(split, window),
                 rows=C, steps=n_steps, pps=pps, window=window,
                 **(dict(dk=d, dv=dv, sink=sink) if split else {}))
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
        # (+ the sinks)
        num_scalar_prefetch=3 + 2 * quant + sink,
        grid=(C, n_steps),
        in_specs=([pl.BlockSpec((None, nkv, R, d), _qmap)]
                  + [pl.BlockSpec((None, nkv, d, bs), _pmap(i))
                     for i in range(pps)]
                  + [pl.BlockSpec((None, nkv, bs, dv), _pmap(i))
                     for i in range(pps)]),
        out_specs=pl.BlockSpec((None, nkv, R, dv), _omap),
        scratch_shapes=[pltpu.VMEM((nkv, R, 128), f32),
                        pltpu.VMEM((nkv, R, 128), f32),
                        pltpu.VMEM((nkv, R, dv), f32),
                        pltpu.VMEM((nkv, rd, 128), f32),
                        pltpu.VMEM((nkv, rd, 128), f32),
                        pltpu.VMEM((nkv, rd, dv), f32),
                        pltpu.VMEM((nkv, R, pps * bs), f32),
                        pltpu.VMEM((nkv, R, pps * bs), q.dtype)],
    )
    interpret = _interpret_mode()
    call = pl.pallas_call(
        functools.partial(_rpa_kernel, qb=qb, bs=bs, G=G, n_steps=n_steps,
                          pps=pps, sm_scale=sm_scale, quant=quant, mb=mb,
                          nkv=nkv, rd=rd, window=window, sink=sink),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, nkv, R, dv), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=_name(split, window),
    )
    pre = (rows_flat, pos0.astype(jnp.int32), n_valid.astype(jnp.int32))
    if quant:
        pre = pre + (k_scales.reshape(-1).astype(jnp.float32),
                     v_scales.reshape(-1).astype(jnp.float32))
    if sink:
        pre = pre + (sinks.astype(jnp.float32),)
    out = call(*pre, qg, *([kt_pages] * pps), *([v_pages] * pps))
    return out.reshape(C, nkv, qb, G, dv).transpose(0, 2, 1, 3, 4).reshape(
        C, qb, nH, dv)


def _ragged_paged_xla(q, k_pages, v_pages, rows, pos0, n_valid, sm_scale,
                      k_layout, k_scales=None, v_scales=None, window=None,
                      sinks=None):
    """XLA gather fallback (and the kernel's numerics reference): gather
    each chunk's pages, one masked softmax over the flattened context.
    The same mask as the kernel, and the same zeros in padding rows.
    int8 pages gather their per-page scales alongside and dequantize
    exactly as the kernel does (fp32 multiply, cast to the q dtype, then
    the dots).  Values may be narrower than keys; ``sinks`` [nH] join
    each head's max and sum as the kernel's start does."""
    from ..quant import dequantize_int8

    C, qb, nH, d = q.shape
    nkv = k_pages.shape[1]
    G = nH // nkv
    mb = rows.shape[1]
    dv = v_pages.shape[3]
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
    vg = jnp.swapaxes(vg, 1, 2).reshape(C, nkv, mb * bs, dv)
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
    if sinks is not None:
        sk = sinks.astype(jnp.float32).reshape(1, nkv, G, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sinks is not None:
        l = l + jnp.exp(sk - m)
    l = jnp.maximum(l, 1e-30)
    o = jnp.einsum("chgqs,chsd->cqhgd", (p / l).astype(vg.dtype), vg)
    valid = off[None, :] < n_valid[:, None]
    return jnp.where(valid[:, :, None, None], o.reshape(C, qb, nH, dv),
                     0.0).astype(q.dtype)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_slot, _first_page, _group, _steer,
                                    _grid_steps, _decode_rows, _rpa_kernel,
                                    _name, ragged_paged_attention_kernel,
                                    _ragged_paged_xla)
    return _SRC


def _tuned_impl(C: int, qb: int, nH: int, d: int, nkv: int, mb: int,
                bs: int, dtype, candidates: list,
                quant: bool = False, window=None, dv=None,
                sink: bool = False) -> str:
    """Impl choice via the autotune registry: the kernel at 4, 2 or 1
    pages a grid step (what ``candidates_for`` admits, the largest
    first, so a backend that never sweeps runs the largest group) or the
    XLA gather path.  The sweep measures a table of distinct pages at
    half the longest context, decode and chunk rows alternating; the
    serving cells' geometry is in the committed table, so no run of them
    sweeps.  Quantized pages tune their own bucket — dequant shifts the
    arms' cost balance (the kernel dequantizes per VMEM tile, the XLA
    arm on the full gathered context), and so does a window, which
    skips most of a long context's groups.  Values narrower than keys
    (``dv``) and sinks name buckets of their own too."""
    from . import autotune

    dv = d if dv is None else dv

    def measure(impl):
        pdt = jnp.int8 if quant else dtype
        qz = jnp.zeros((C, qb, nH, d), dtype)
        ktz = jnp.zeros((mb + 1, nkv, d, bs), pdt)
        vz = jnp.zeros((mb + 1, nkv, bs, dv), pdt)
        rz = jnp.tile(jnp.arange(1, mb + 1, dtype=jnp.int32), (C, 1))
        pz = jnp.full((C,), mb * bs // 2, jnp.int32)
        nz = jnp.where(jnp.arange(C) % 2 == 0, 1, qb).astype(jnp.int32)
        sc = jnp.ones((mb + 1, nkv), jnp.float32) if quant else None
        sk = jnp.zeros((nH,), jnp.float32) if sink else None
        if impl == "xla":
            arm = lambda x: _ragged_paged_xla(x, ktz, vz, rz, pz, nz,  # noqa: E731
                                              1.0, "d_major", sc, sc,
                                              window, sk)
        else:
            arm = lambda x: ragged_paged_attention_kernel(  # noqa: E731
                x, ktz, vz, rz, pz, nz, 1.0, sc, sc,
                pps=int(impl.split("_p")[1]), window=window, sinks=sk)
        # eight calls chained in one program: one call alone is under
        # the host's dispatch floor, where every form reads alike (an
        # output narrower than q is fed back as its first columns)
        step = arm if dv == d else (
            lambda y: y.at[..., :dv].set(arm(y)))  # noqa: E731
        chain = jax.jit(lambda x: jax.lax.fori_loop(
            0, 8, lambda _, y: step(y), x))
        return autotune.time_candidate(lambda: chain(qz)) / 8

    return str(autotune.tuned(
        "ragged_paged_attention",
        f"c{C}_qb{qb}_h{nH}_d{d}_kv{nkv}_mb{mb}_bs{bs}"
        + (f"_dv{dv}" if dv != d else "") + ("_sink" if sink else "")
        + ("_q8" if quant else "") + (f"_w{window}" if window else ""),
        str(jnp.dtype(dtype)), candidates,
        measure=measure, source=_autotune_source()))


def ragged_paged_attention(q, k_pages, v_pages, rows, pos0, n_valid,
                           sm_scale: float, k_layout: str = "d_major",
                           k_scales=None, v_scales=None, window=None,
                           sinks=None):
    """Unified ragged-paged attention: dispatches the MXU Pallas kernel
    when the page geometry supports it, else the XLA gather path.  See
    module docstring for shapes; int8 pages require both scale planes;
    ``window`` (static) is the sliding window in tokens, None for all;
    ``sinks`` [nH] fp32 the heads' sink logits, None for none."""
    quant = k_pages.dtype == jnp.int8
    if quant and (k_scales is None or v_scales is None):
        raise ValueError("int8 KV pages need k_scales and v_scales "
                         "([P, nKV] fp32 per-page scale planes)")
    C, qb, nH, d = q.shape
    mb = rows.shape[1]
    dv = v_pages.shape[3]
    cands = ["xla"] if k_layout != "d_major" else candidates_for(
        k_pages.shape, nH, qb, mb, k_pages.dtype.itemsize, dv)
    if len(cands) > 1:
        # the split forms' own keys, named only where they apply
        split = ({} if dv == d and sinks is None
                 else dict(dv=dv, sink=sinks is not None))
        impl = _tuned_impl(C, qb, nH, d, k_pages.shape[1], mb,
                           k_pages.shape[3], q.dtype, cands, quant, window,
                           **split)
        if impl != "xla":
            return ragged_paged_attention_kernel(
                q, k_pages, v_pages, rows, pos0, n_valid, sm_scale,
                k_scales, v_scales, pps=int(impl.split("_p")[1]),
                window=window, **({} if sinks is None
                                  else dict(sinks=sinks)))
    return _ragged_paged_xla(q, k_pages, v_pages, rows, pos0, n_valid,
                             sm_scale, k_layout, k_scales, v_scales, window,
                             sinks)
