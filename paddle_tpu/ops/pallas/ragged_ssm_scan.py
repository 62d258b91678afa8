"""Pallas TPU state-space (Mamba-2) recurrence over the serving tick's rows.

One layer's recurrence on the engine's ``[C, qb]`` grid of rows
(ragged_paged_attention.py has the grid's contract): per head, state
``S [hd, N]`` in a pool of *slots*, token ``j`` of a row

    S <- exp(dt_j A) S + dt_j x_j (outer) B_j        y_j = S C_j

with ``B_j``, ``C_j`` ``[N]`` every head's (one group).  A decode row
advances its request's state by one token, a prefill chunk by up to
``qb``, and a request's chunks are consecutive rows of the tick: the
state is read once where the request keeps it, carried from row to row
on the chip, and written once where the request wants it next.  The pool
is updated where it lies (``input_output_aliases``): slots that no row
writes keep what they held.

**The pool's layout.**  A slot is ``[nH / P, N, P * hd]``: the state
transposed (``N`` down the sublanes) and ``P = 128 // hd`` heads side by
side along the lanes (``state_shape``), so that a tile is lane-dense
whatever the head width, and so that ``x`` and ``y`` ride in the layout
the layers around the scan have them in, ``[C, qb, nH * hd]``: a head
tile's ``x`` is 128 lanes of a token's row, and nothing is transposed on
either side of the call.

Contract shared by the kernel and the XLA form:

- pool ``[S, nH / P, N, P * hd]`` (fp32; a narrower pool is widened to
  fp32 for the arithmetic and rounded once at the write); x ``[C * qb, nH
  * hd]``: the grid's tokens row by row, a token's heads side by side
  (two-dimensional on purpose: a reshape of ``[.., nH, hd]`` into the
  lanes is a copy on the chip, and XLA:TPU lays a ``[C, qb, .]`` array out
  tokens-minor when some op nearby slices along ``qb``, which costs a
  transposing copy at the call); dt ``[C, qb, nH]`` fp32, positive
  (after the softplus); A ``[nH]`` fp32, negative; Bm, Cm ``[C * qb, N]``.
- read, write ``[C]`` int32 slot ids, n_valid ``[C]`` int32 in [0, qb].
  The rows of one request are ADJACENT, in position order, and share
  ``write``; no two requests share it.  The first row of a run starts
  from slot ``read`` (a fresh request's names a slot kept at zero, a
  prefix hit's the snapshot it hit), the last leaves the state in slot
  ``write``; the ``read`` of a run's later rows is not looked at.  A
  slot that one run writes is read by no other run of the call.
- a row whose ``write`` is ``dump`` (an int32 scalar, traced or not) is
  idle: it advances nothing and its ``y`` is unspecified (finite).  Slot
  ``dump`` is written, with anything, only when the call's first row is
  idle; idle rows behind a run cost no byte (they name the run's blocks
  again).
- tokens ``j >= n_valid`` of a row are padding: they leave the state as
  it is, and their ``y`` is unspecified (finite).

Returns ``(y [C * qb, nH * hd] fp32, pool)``; the skip ``D x`` is the
caller's.

A row's block is computed in closed form so that the MXU does the outer
products and the read-outs: with ``L_j = sum_{i<=j} dt_i A`` (a padding
token adds 0),

    y_j = exp(L_j) S0 C_j + sum_{i<=j} exp(L_j - L_i) dt_i (B_i . C_j) x_i
    S'  = exp(L_last) S0 + sum_i exp(L_last - L_i) dt_i x_i (outer) B_i

and every exponent is <= 0.  A row of ONE token (a decode row, most of a
serving tick) needs no matrix unit: its update is a scaled copy plus one
outer product, its read-out a sum down the sublanes, both on the VPU.
The kernel's grid is (nH / hb, C): a step is one row of ``hb`` heads,
the state block ``[hb / P, N, P * hd]`` steered by the slot ids (scalar
prefetch); a run of equal ``write`` keeps its output block in VMEM, so a
request's state crosses HBM once each way a layer a tick however many
chunks it has.

**Where the kernel's operands are made.**  XLA makes what is a value a
head, at that size and no wider (``_scan_pallas``): ``L``'s running sum,
``exp(L_last)`` and ``dt_0`` as ``[C * nH]`` scalars (SMEM: all a row of
one token reads), ``exp(L_j)`` beside ``exp(L_last - L_i) dt_i`` as ``[C *
qb, 2 * nH]`` (a lane a head: a row of several tokens fetches its block),
the heads' causal masks ``[C * qb, nH * qb]``, B and C transposed, and the
rows' first tokens ``x_0``.  The kernel spreads a head's value over the
head's ``hd`` lanes itself (``_spread``: selects on a lane index, exact)
and makes ``dt_0 x_0`` and ``exp(L_last - L_i) dt_i x_i`` from ``x``
there: nothing of the grid's ``[C * qb, nH * hd]`` size is written for
the kernel to read but ``x``.

Which form runs is the autotune's choice among ``"xla"`` (a ``lax.scan``
over the rows with the pool as its carry: what runs where nothing sweeps)
and ``"kernel_h<hb>"``, by the static shapes alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _interpret_mode, single_device_program

__all__ = ["candidates_for", "choose_impl", "heads_a_tile",
           "ragged_ssm_scan", "state_shape"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# both forms keep the state, the decays and every product's sum in fp32.
ACCUM_DTYPE = "float32"

_HI = lax.Precision.HIGHEST
_VMEM_BOUND = 48 * 2 ** 20
_LANES = 128


def heads_a_tile(n_heads: int, head_dim: int) -> int:
    """``P``: heads side by side along a tile's lanes."""
    P = _LANES // head_dim if head_dim < _LANES and _LANES % head_dim == 0 \
        else 1
    return P if n_heads % P == 0 else 1


def state_shape(n_heads: int, head_dim: int, d_state: int) -> tuple:
    """One slot of one layer (module docstring: the pool's layout)."""
    P = heads_a_tile(n_heads, head_dim)
    return (n_heads // P, d_state, P * head_dim)


def _unpack(tiles, hd: int):
    """A slot ``[nT, N, P * hd]`` as ``[nH, hd, N]``."""
    nT, N, LW = tiles.shape
    return tiles.reshape(nT, N, LW // hd, hd).transpose(0, 2, 3, 1).reshape(
        nT * (LW // hd), hd, N)


def _pack(S, P: int):
    """``[nH, hd, N]`` as a slot ``[nH / P, N, P * hd]``."""
    nH, hd, N = S.shape
    return S.reshape(nH // P, P, hd, N).transpose(0, 3, 1, 2).reshape(
        nH // P, N, P * hd)


def _runs(write, dump):
    """Per row: idle, first of its run, last of its run; the index of the
    last live row at or before it, and of that row's run's first row (-1
    where no live row came yet)."""
    C = write.shape[0]
    idle = write == dump
    prev = jnp.concatenate([jnp.full((1,), -1, write.dtype), write[:-1]])
    nxt = jnp.concatenate([write[1:], jnp.full((1,), -1, write.dtype)])
    first = ~idle & (write != prev)
    last = ~idle & (write != nxt)
    rows = jnp.arange(C, dtype=jnp.int32)
    live_at = lax.cummax(jnp.where(idle, -1, rows))
    return idle, first, last, live_at, lax.cummax(jnp.where(first, rows, -1))


def _decays(dt, A, n_valid):
    """dt ``[C, qb, nH]`` with padding zeroed, and ``L`` its running sum
    times ``A`` (``[C, qb, nH]`` fp32, <= 0, non-increasing along qb)."""
    qb = dt.shape[1]
    held = jnp.arange(qb, dtype=jnp.int32)[None, :] < n_valid[:, None]
    dt = jnp.where(held[:, :, None], dt.astype(jnp.float32), 0.0)
    return dt, jnp.cumsum(dt * A.astype(jnp.float32), axis=1)


# ---------------------------------------------------------------------------
# the XLA form: a scan over the rows, the pool its carry
# ---------------------------------------------------------------------------

def _row_block(S0, x, dt, L, Bm, Cm):
    """One row, every head: S0 ``[nH, hd, N]``, x ``[qb, nH, hd]``, dt, L
    ``[qb, nH]``, Bm, Cm ``[qb, N]``, all fp32 -> (y ``[qb, nH, hd]``,
    S' ``[nH, hd, N]``)."""
    qb = x.shape[0]
    G = jnp.einsum("jn,in->ji", Cm, Bm, precision=_HI)          # [qb, qb]
    j = jnp.arange(qb)
    causal = (j[None, :] <= j[:, None])[:, :, None]             # [j, i, 1]
    M = jnp.where(causal, jnp.exp(jnp.where(
        causal, L[:, None, :] - L[None, :, :], 0.0)), 0.0) * dt[None]
    y = jnp.einsum("jih,ihd->jhd", G[:, :, None] * M, x, precision=_HI)
    y = y + jnp.exp(L)[:, :, None] * jnp.einsum(
        "jn,hdn->jhd", Cm, S0, precision=_HI)
    w = jnp.exp(L[-1][None] - L) * dt                           # [qb, nH]
    S1 = jnp.exp(L[-1])[:, None, None] * S0 + jnp.einsum(
        "ihd,in->hdn", x * w[:, :, None], Bm, precision=_HI)
    return y, S1


@jax.jit
def _scan_xla(pool, x, dt, A, Bm, Cm, read, write, n_valid, dump):
    f32 = jnp.float32
    S, (C, qb, nH) = pool.shape[0], dt.shape
    P = nH // pool.shape[1]
    hd = x.shape[1] // nH
    x = x.reshape(C, qb, nH, hd)
    Bm, Cm = Bm.reshape(C, qb, -1), Cm.reshape(C, qb, -1)
    _idle, first, last, _live, _run0 = _runs(write, dump)
    dt, L = _decays(dt, A, n_valid)

    def step(carry, inp):
        pool, S_run = carry
        xr, dtr, Lr, Br, Cr, r, w, fst, lst = inp
        S0 = jnp.where(fst, _unpack(pool[r].astype(f32), hd), S_run)
        y, S1 = _row_block(S0, xr.astype(f32), dtr, Lr, Br.astype(f32),
                           Cr.astype(f32))
        # a row that does not end its run writes nowhere (an index past
        # the pool is dropped)
        pool = pool.at[jnp.where(lst, w, S)].set(
            _pack(S1, P).astype(pool.dtype), mode="drop")
        return (pool, S1), y

    (pool, _), y = lax.scan(
        step, (pool, jnp.zeros((nH, hd, pool.shape[2]), f32)),
        (x, dt, L, Bm, Cm, read, write, first, last))
    return y.reshape(C * qb, nH * hd), pool


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _vmem_bytes(hb: int, hd: int, N: int, qb: int, pool_item: int,
                nH: int) -> int:
    """Double-buffered state blocks in and out, the row's blocks as VMEM
    pads them (a block's last dim to 128 lanes, the one before to 8
    sublanes), and a tile's fp32 temporaries."""
    lanes = lambda n: -(-n // _LANES) * _LANES                # noqa: E731
    W = lanes(hb * hd)
    state = hb * hd * N
    per_row = (qb * W * (2 + 4)                     # x, y
               + qb * lanes(2 * nH) * 4             # exp(L) | w, a head
               + qb * lanes(hb * qb) * 4            # the heads' masks
               + 8 * W * 4 + 2 * N * lanes(qb) * 2 + 2 * qb * lanes(N) * 2)
    return (4 * state * pool_item + 2 * per_row
            + 4 * (4 * N * _LANES + 8 * qb * _LANES))


def _tiles_a_group(hb: int, P: int, qb: int) -> int:
    """Tiles whose heads' masks fill a lane tile together (all of a
    step's where they are fewer)."""
    return max(1, min(hb, _LANES // qb) // P)


def _supported(pool_shape, hd: int, qb: int, pool_item: int, hb: int) -> bool:
    """Gate for the kernel at ``hb`` heads a step: lane-dense tiles,
    sublane-tileable rows, whole tiles a step, and the step's working set
    inside the VMEM the call asks for."""
    _, nT, N, LW = pool_shape
    P = LW // hd
    hg = _tiles_a_group(hb, P, qb) * P
    return (LW % _LANES == 0 and N % 8 == 0 and qb % 8 == 0
            and hb % P == 0 and (nT * P) % hb == 0 and pool_item in (2, 4)
            and hb % hg == 0 and (hg == hb or (hg * qb) % _LANES == 0)
            and _vmem_bytes(hb, hd, N, qb, pool_item, nT * P) <= _VMEM_BOUND)


def _spread(heads, lane, hd: int):
    """A lane tile's ``P`` values, one a head (scalars, or ``[qb, 1]``),
    spread over the heads' ``hd`` lanes each: lane ``l`` takes ``heads[l //
    hd]``, by selects on the lane index ``lane [1, P * hd]`` (exact, no
    matrix unit)."""
    out = heads[0]
    for u in range(1, len(heads)):
        out = jnp.where(lane >= u * hd, heads[u], out)
    return out


def _scan_kernel(rd_ref, wr_ref, first_ref, kind_ref, big_ref, elast_ref,
                 dt0_ref, x_ref, ew_ref, m_ref, x0_ref, c_ref, bt_ref,
                 ct_ref, sin_ref, y_ref, so_ref, *, tb, tg, P, hd, qb, nH):
    """One (head block, row) program over ``tb`` tiles of ``P`` heads.
    ``x_ref``, ``y_ref`` ``[qb, tb * LW]``: the row's tokens as the layers
    have them.  What is a value a head reaches the kernel a head and is
    spread over the head's ``hd`` lanes here (``_spread``): ``elast_ref``,
    ``dt0_ref`` ``[C * nH]`` in SMEM, a row's ``exp(L_last)`` and its
    first token's ``dt`` (all a row of one token reads); ``ew_ref [qb, 2 *
    nH]`` fp32, ``exp(L_j)`` of every head and then ``exp(L_last - L_i)
    dt_i`` of every head, a lane a head.  ``m_ref [qb, tb * P * qb]`` the
    heads' masks ``(i <= j) exp(L_j - L_i) dt_i`` side by side, ``[j,
    (head, i)]``, read ``hg`` heads (``tg`` tiles) at a time so that the
    slice starts on a lane tile; ``x0_ref [1, tb * LW]`` the row's first
    token's ``x`` in fp32; ``c_ref`` ``[qb, N]``, and ``bt_ref``,
    ``ct_ref`` ``[N, qb]``: B and C transposed.
    ``kind_ref[c]``: 0 an idle row, 1 a row of one token (update and
    read-out on the VPU), 2 any other.  The output state block is the
    run's carry: its first row fills it from the input block, the later
    ones advance it where it is."""
    import jax.experimental.pallas as pl

    c = pl.program_id(1)
    f32 = jnp.float32
    LW, hg = P * hd, tg * P
    kind = kind_ref[c]
    fst = first_ref[c] == 1
    h0 = pl.program_id(0) * (tb * P)           # the step's first head
    lane = lax.broadcasted_iota(jnp.int32, (1, LW), 1)

    def tiles(live, make_body, group=1):
        """``body(g, [S0 of each of the group's tiles])`` (``make_body()``
        makes it, with what every tile of the row shares) for every group
        of ``group`` tiles of a ``live`` row, from the slot the run
        starts in or from the carry."""
        for src, cond in ((sin_ref, fst), (so_ref, jnp.logical_not(fst))):
            @pl.when(jnp.logical_and(live, cond))
            def _(src=src):
                body = make_body()

                def step(g, _):
                    body(g, [src[g * group + k].astype(f32)
                             for k in range(group)])
                    return 0

                lax.fori_loop(0, tb // group, step, 0)

    @pl.when(kind != 2)
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    def one_token():
        b0 = bt_ref[:, 0:1].astype(f32)                    # [N, 1]
        c0 = ct_ref[:, 0:1].astype(f32)

        def body(t, tile):
            S0, = tile                                     # [N, LW]
            lanes = pl.ds(pl.multiple_of(t * LW, LW), LW)
            at = c * nH + h0 + t * P
            e_last, dt0 = (_spread([ref[at + u] for u in range(P)], lane, hd)
                           for ref in (elast_ref, dt0_ref))
            S1 = e_last * S0 + b0 * (dt0 * x0_ref[:, lanes])
            so_ref[t] = S1.astype(so_ref.dtype)
            y_ref[0:1, lanes] = jnp.sum(S1 * c0, axis=0, keepdims=True)

        return body

    tiles(kind == 1, one_token)

    def block():
        Cm = c_ref[...].astype(f32)                        # [qb, N]
        BT = bt_ref[...].astype(f32)                       # [N, qb]
        # G[j, i] = C_j . B_i
        G = jnp.dot(Cm, BT, precision=_HI, preferred_element_type=f32)
        ew = ew_ref[...]                                   # [qb, 2 * nH]
        col = lax.broadcasted_iota(jnp.int32, ew.shape, 1)

        def tile_of(at):
            """Columns ``at .. at + P`` of ``ew``, a column a head, over
            the lanes of the heads' tile ``[qb, LW]``."""
            return _spread([jnp.sum(jnp.where(col == at + u, ew, 0.0),
                                    axis=1, keepdims=True)
                            for u in range(P)], lane, hd)

        def body(g, group):
            # the group's heads' masks: hg heads of [qb, qb] side by side
            mg = m_ref[:, pl.ds(pl.multiple_of(g * hg * qb, hg * qb),
                                hg * qb)]
            for k, S0 in enumerate(group):
                t = g * tg + k
                lanes = pl.ds(pl.multiple_of(t * LW, LW), LW)
                el = tile_of(h0 + t * P)                   # exp(L_j)
                w = tile_of(nH + h0 + t * P)     # exp(L_last - L_i) dt_i
                y = el * jnp.dot(Cm, S0, precision=_HI,
                                 preferred_element_type=f32)
                x = x_ref[:, lanes].astype(f32)            # [qb, LW]
                intra = [jnp.dot(
                    G * mg[:, (k * P + u) * qb:(k * P + u + 1) * qb],
                    x[:, u * hd:(u + 1) * hd], precision=_HI,
                    preferred_element_type=f32) for u in range(P)]
                y_ref[:, lanes] = y + (intra[0] if P == 1
                                       else jnp.concatenate(intra, axis=1))
                # a padding token adds 0 to L: the last place holds L_last
                so_ref[t] = (el[qb - 1:qb] * S0 + jnp.dot(
                    BT, w * x, precision=_HI,
                    preferred_element_type=f32)).astype(so_ref.dtype)

        return body

    tiles(kind == 2, block, group=tg)


@functools.partial(jax.jit, static_argnames=("hb",))
def _scan_pallas(pool, x, dt, A, Bm, Cm, read, write, n_valid, dump, *, hb):
    """The kernel form (module docstring; gate with _supported())."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, qb, nH = dt.shape
    _, nT, N, LW = pool.shape
    hd = x.shape[1] // nH
    P = LW // hd
    tb, W = hb // P, hb * hd
    f32, i32 = jnp.float32, jnp.int32
    idle, first, _last, live_at, run0 = _runs(write, dump)
    # a run's later rows, and idle rows behind it, name the blocks its
    # first row named (no byte moves; the output block is the carry);
    # idle rows ahead of every run name the dump
    rd = jnp.where(run0 >= 0, read[jnp.maximum(run0, 0)], dump)
    wr = jnp.where(live_at >= 0, write[jnp.maximum(live_at, 0)], dump)
    kind = jnp.where(idle, 0, jnp.where(n_valid == 1, 1, 2))
    # what only a row of several tokens reads, it alone fetches: every
    # other row names the blocks of the last such row before it
    big = jnp.maximum(lax.cummax(jnp.where(
        kind == 2, jnp.arange(C, dtype=i32), -1)), 0)
    dt, L = _decays(dt, A, n_valid)

    def rows(a):
        """``[C, qb, k]`` -> ``[C * qb, k]``."""
        return a.reshape(C * qb, a.shape[2])

    # a value a head, as it is: the kernel spreads it over the head's
    # channels.  A row of one token reads two scalars a head ...
    e_last = jnp.exp(L[:, -1]).reshape(C * nH)
    dt0 = dt[:, 0].reshape(C * nH)
    # ... any other exp(L_j) and exp(L_last - L_i) dt_i, a lane a head
    ew = rows(jnp.concatenate(
        [jnp.exp(L), jnp.exp(L[:, -1:] - L) * dt], axis=2))     # [., 2 nH]
    x0 = x[::qb].astype(f32)[:, None]                           # [C, 1, .]
    # the heads' masks, lane-dense: lane (h, i) of row j
    wide = jnp.repeat(jnp.eye(nH, dtype=f32), qb, axis=1)       # [nH, nH*qb]
    Li, dti = (jnp.transpose(a, (0, 2, 1)).reshape(C, 1, nH * qb)
               for a in (L, dt))
    causal = (jnp.arange(nH * qb) % qb)[None, :] <= jnp.arange(qb)[:, None]
    Lj = jnp.dot(rows(L), wide, precision=_HI, preferred_element_type=f32)
    m = rows(jnp.where(causal, jnp.exp(jnp.where(
        causal, Lj.reshape(C, qb, nH * qb) - Li, 0.0)), 0.0) * dti)
    tg = _tiles_a_group(hb, P, qb)

    def _tok(h, c, rd, wr, first, kind, big, *_):
        return (big[c], h)

    def _heads(h, c, rd, wr, first, kind, big, *_):
        return (big[c], 0)

    def _out(h, c, *_):
        return (c, h)

    def _own(h, c, *_):
        return (c, 0, h)

    def _all(h, c, *_):
        return (c, 0)

    def _all3(h, c, *_):
        return (c, 0, 0)

    def _sin(h, c, rd, *_):
        return (rd[c], h, 0, 0)

    def _sout(h, c, rd, wr, *_):
        return (wr[c], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # rd, wr, first, kind, big, exp(L_last), dt_0
        num_scalar_prefetch=7,
        grid=(nH // hb, C),
        in_specs=[
            pl.BlockSpec((qb, W), _tok),                        # x
            pl.BlockSpec((qb, 2 * nH), _heads),                 # exp(L) | w
            pl.BlockSpec((qb, hb * qb), _tok),                  # masks
            pl.BlockSpec((None, 1, W), _own),                   # x_0
            pl.BlockSpec((qb, N), _all),                        # C
            pl.BlockSpec((None, N, qb), _all3),                 # B^T
            pl.BlockSpec((None, N, qb), _all3),                 # C^T
            pl.BlockSpec((None, tb, N, LW), _sin),
        ],
        out_specs=[pl.BlockSpec((qb, W), _out),
                   pl.BlockSpec((None, tb, N, LW), _sout)],
    )
    interpret = _interpret_mode()
    need = _vmem_bytes(hb, hd, N, qb, pool.dtype.itemsize, nH)
    y, pool = pl.pallas_call(
        functools.partial(_scan_kernel, tb=tb, tg=tg, P=P, hd=hd, qb=qb,
                          nH=nH),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((C * qb, nH * hd), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the scalar-prefetch refs: 14 is the pool
        input_output_aliases={14: 1},
        # the rows are a sequence: a run keeps its state block in VMEM
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 * 2 ** 20, need + need // 2)),
        interpret=interpret,
        name="ragged_ssm_scan",
    )(rd.astype(i32), wr.astype(i32), first.astype(i32), kind.astype(i32),
      big, e_last, dt0, x, ew, m, x0, Cm,
      jnp.swapaxes(Bm.reshape(C, qb, N), 1, 2),
      jnp.swapaxes(Cm.reshape(C, qb, N), 1, 2), pool)
    return y, pool


# ---------------------------------------------------------------------------
# which form
# ---------------------------------------------------------------------------

_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_runs, _decays, _row_block, _unpack,
                                    _pack, _scan_xla, _tiles_a_group,
                                    _spread, _scan_kernel, _scan_pallas)
    return _SRC


def candidates_for(pool_shape, hd: int, qb: int, pool_item: int = 4) -> list:
    """``"xla"`` (what runs where nothing sweeps), then ``"kernel_h<hb>"``
    for each head block of all, a half and a quarter of the heads that
    the gate admits: fewer, larger steps first."""
    nH = pool_shape[1] * (pool_shape[3] // hd)
    out = ["xla"]
    for hb in (nH, nH // 2, nH // 4):
        if hb and f"kernel_h{hb}" not in out and _supported(
                pool_shape, hd, qb, pool_item, hb):
            out.append(f"kernel_h{hb}")
    return out


def _tuned_impl(pool_shape, hd: int, C: int, qb: int, pool_dtype,
                act_dtype) -> str:
    """The form via the autotune registry, keyed by the static shapes
    (the pool's slots apart: a form's time is its rows').  A sweep times
    a tick of decode rows, each request's state in and out once: the
    traffic that binds the serving cells."""
    from . import autotune

    _, nT, N, LW = pool_shape
    nH = nT * (LW // hd)

    def measure(impl):
        pool = jnp.zeros((C + 2, nT, N, LW), pool_dtype)
        x = jnp.zeros((C * qb, nH * hd), act_dtype)
        dt = jnp.full((C, qb, nH), 0.01, jnp.float32)
        A = -jnp.ones((nH,), jnp.float32)
        bc = jnp.zeros((C * qb, N), act_dtype)
        slots = 2 + jnp.arange(C, dtype=jnp.int32)
        nv = jnp.ones((C,), jnp.int32)
        # eight calls chained in one program: one call alone is near
        # the host's dispatch floor
        fn = jax.jit(lambda p: lax.fori_loop(0, 8, lambda _, q: (
            ragged_ssm_scan(q, x, dt, A, bc, bc, slots, slots, nv, dump=1,
                            impl=impl)[1]), p), donate_argnums=0)
        state = [pool]

        def call():
            state[0] = fn(state[0])
            return state[0]

        return autotune.time_candidate(call) / 8

    return str(autotune.tuned(
        "ragged_ssm_scan", f"c{C}_qb{qb}_h{nH}_d{hd}_n{N}",
        f"{jnp.dtype(pool_dtype)}/{jnp.dtype(act_dtype)}",
        candidates_for(pool_shape, hd, qb, jnp.dtype(pool_dtype).itemsize),
        measure=measure, source=_autotune_source()))


def choose_impl(pool_shape, hd: int, C: int, qb: int, pool_dtype,
                act_dtype) -> str:
    """The form a scan of these static shapes runs on: what the registry
    says where the kernel supports them and the program being traced
    runs on one device, else ``"xla"``."""
    if (len(candidates_for(pool_shape, hd, qb,
                           jnp.dtype(pool_dtype).itemsize)) > 1
            and single_device_program()):
        return _tuned_impl(pool_shape, hd, C, qb, pool_dtype, act_dtype)
    return "xla"


def ragged_ssm_scan(pool, x, dt, A, Bm, Cm, read, write, n_valid, *,
                    dump, impl: str | None = None):
    """The recurrence of the module docstring on the form ``impl`` names
    (default: ``choose_impl`` of the shapes).  Returns ``(y, pool)``."""
    if impl is None:
        impl = choose_impl(pool.shape, x.shape[1] // dt.shape[2], dt.shape[0],
                           dt.shape[1], pool.dtype, x.dtype)
    if impl == "xla":
        return _scan_xla(pool, x, dt, A, Bm, Cm, read, write, n_valid, dump)
    return _scan_pallas(pool, x, dt, A, Bm, Cm, read, write, n_valid, dump,
                        hb=int(impl.split("_h")[1]))
