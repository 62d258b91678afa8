"""Pallas TPU grouped product for the held experts of a routed layer.

``out[r] = lhs[r] @ rhs[base + g(r)]`` for the rows ``r`` that
``sizes`` puts in group ``g`` (rows ``[sum sizes[:g], sum sizes[:g+1])``),
fp32 accumulation over the whole of K, one rounding to ``lhs``'s dtype
(``dt`` below).
With a second weight stack ``rhs_up`` the call is the gated pair of a
SwiGLU expert on one read of ``lhs``:

    out[r] = (silu(lhs[r] @ rhs[..]) * (lhs[r] @ rhs_up[..])).astype(dt)

the activation and the product in fp32, ONE rounding.  That is what
XLA:TPU makes of the casts ``models/routed_experts.py`` spelled out
around two ``lax.ragged_dot`` before this kernel, ``silu(g).astype(dt) *
u.astype(dt)``: it keeps the fused product in fp32 and drops the two
inner roundings (on the chip the XLA arm equals the one-rounding form on
99.99% of the elements and the written casts on 64%; PERF.md §6, PR 35).
A kernel that rounded three times would be less precise than the
program it replaces, so it rounds once; the XLA arm keeps the casts as
written, which is what runs off the TPU.

Contract shared by the kernel and the XLA arm:

- lhs ``[m, K]``, rows sorted by group; rhs (and rhs_up) ``[G, K, N]``
  with ``G >= base + count``; sizes ``[count]`` int32, ``sum <= m``.
- ``base`` (None = 0, or an int32 scalar, traced or not) is the first of
  this call's ``count`` groups among the ``G`` matrices: a layer of a
  flattened stack of layers is addressed where it lies, nothing is
  sliced or copied, and the work is over ``count`` groups whatever ``G``.
- Rows behind the last group belong to nobody.  The kernel never visits
  a row tile without a group's row, so those rows of ``out`` are
  whatever the buffer held, NaN included (inside a visited tile:
  zeros); ``lax.ragged_dot`` promises nothing of them either (XLA:CPU's
  gives zeros).  The caller masks them (``moe_ffn``'s ``where(held)``).

The kernel is megablox ``gmm``'s algorithm (jax.experimental.pallas.ops
.tpu.megablox) with the tile sized for the few rows a held expert
gets: the grid walks *visits*, one per (group, row tile the group has
rows in), found by scalar prefetch; a row tile that two groups share is
visited once per group under a row mask, and the grid's visit axis is
as long as this call's visits (a traced bound), so an empty group or a
tile behind the last group costs nothing.  Bound by the met experts'
weight bytes: each visit streams that expert's ``[K, N]`` once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _interpret_mode, single_device_program

__all__ = ["candidates_for", "choose_impl", "grouped_expert_matmul",
           "row_tile"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# both arms accumulate over K in fp32 (kernel: fp32 scratch or an fp32
# dot result; XLA arm: preferred_element_type on lax.ragged_dot).
ACCUM_DTYPE = "float32"

# XLA:TPU's grouped product works in 512-row tiles and pays one for
# every met group (PERF.md §6, PRs 29 and 35): the XLA arm's row tile.
XLA_ROW_TILE = 512

# A weight block a stack: double-buffered and two stacks deep that is
# 16 MiB of v5e's 128 MiB of VMEM.
_WEIGHT_BLOCK_BYTES = 4 * 2 ** 20


def _vmem_bytes(tm: int, tk: int, tn: int, n_rhs: int, itemsize: int,
                n_k: int) -> int:
    """Double-buffered blocks, the fp32 accumulators, and the fp32
    products and epilogue a step holds beside them."""
    return (2 * itemsize * (tm * tk + n_rhs * tk * tn + tm * tn)
            + (n_rhs * tm * tn * 4 if n_k > 1 else 0)
            + 2 * n_rhs * tm * tn * 4)


def _supported(m: int, K: int, N: int, dtype) -> bool:
    """Gate for the kernel: lane-tileable K and N, sublane-tileable
    rows, a 2- or 4-byte float."""
    return (K % 128 == 0 and N % 128 == 0 and m % 32 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _visits(sizes, m: int, tm: int):
    """The grid's visit axis from the group sizes: for visit ``v`` its
    group, its row tile, the group's row range ``[lo, hi)`` and whether
    it is the first visit of its row tile; and the number of visits.
    Group ``g`` with rows ``[s, e)`` has one visit per row tile in
    ``s // tm .. (e - 1) // tm``, an empty group none; at most
    ``m // tm + count - 1`` in all.  Compare-and-sum over ``[V, count]``:
    no gather, no scatter."""
    count = sizes.shape[0]
    V = m // tm + count - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tile0 = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - tile0 + 1, 0)
    v_end = jnp.cumsum(n_tiles)
    v_start = v_end - n_tiles
    v = jnp.arange(V, dtype=jnp.int32)[:, None]
    own = ((v >= v_start[None]) & (v < v_end[None])).astype(jnp.int32)

    def pick(per_group):
        return (own * per_group).sum(1).astype(jnp.int32)

    gid = pick(jnp.arange(count, dtype=jnp.int32)[None])
    tile = pick(tile0[None] + v - v_start[None])
    first = jnp.concatenate([jnp.ones((1,), jnp.int32),
                             (tile[1:] != tile[:-1]).astype(jnp.int32)])
    return (gid, tile, pick(starts[None]), pick(ends[None]), first), \
        v_end[-1].astype(jnp.int32)


def _gem_kernel(base_ref, gid_ref, tile_ref, lo_ref, hi_ref, first_ref,
                lhs_ref, *refs, tm, n_k, n_rhs):
    """One (n tile, visit, k tile) program: the row tile's ``[tm, tk]``
    against the visit's expert's ``[tk, tn]`` (both stacks of a gated
    pair), accumulated over the k axis; on the last k tile the group's
    own rows are stored, the other rows keep what an earlier visit of
    the tile stored (zeros on the tile's first visit)."""
    import jax.experimental.pallas as pl

    del base_ref, gid_ref
    rhs_refs, o_ref, accs = refs[:n_rhs], refs[n_rhs], refs[n_rhs + 1:]
    v = pl.program_id(1)
    kk = pl.program_id(2)
    x = lhs_ref[...]
    parts = [jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
             for w_ref in rhs_refs]

    def store(sums):
        dt = o_ref.dtype
        val = (sums[0] if n_rhs == 1 else
               jax.nn.silu(sums[0]) * sums[1]).astype(dt)
        row = tile_ref[v] * tm + lax.broadcasted_iota(jnp.int32, val.shape, 0)
        mine = (row >= lo_ref[v]) & (row < hi_ref[v])
        keep = jnp.where(first_ref[v] == 1, jnp.zeros_like(val), o_ref[...])
        o_ref[...] = jnp.where(mine, val, keep)

    if n_k == 1:
        store(parts)
        return

    @pl.when(kk == 0)
    def _first():
        for acc, p in zip(accs, parts):
            acc[...] = p

    @pl.when(kk > 0)
    def _later():
        for acc, p in zip(accs, parts):
            acc[...] += p

    @pl.when(kk == n_k - 1)
    def _last():
        store([acc[...] for acc in accs])


@functools.partial(jax.jit, static_argnames=("tiling",))
def _grouped_kernel(lhs, rhs, sizes, rhs_up=None, base=None, *, tiling):
    """The kernel arm (module docstring has the contract; gate with
    _supported()).  ``tiling = (tm, tk, tn)``: ``tm`` divides m, ``tk``
    K and ``tn`` N."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, K = lhs.shape
    N = rhs.shape[2]
    tm, tk, tn = tiling
    if m % tm or K % tk or N % tn:
        raise ValueError(f"tiling {tiling} does not divide [{m}, {K}] x "
                         f"[{K}, {N}]")
    stacks = (rhs,) if rhs_up is None else (rhs, rhs_up)
    n_k = K // tk
    meta, n_visits = _visits(sizes.astype(jnp.int32), m, tm)
    base = jnp.zeros((1,), jnp.int32) if base is None else \
        jnp.asarray(base, jnp.int32).reshape(1)

    def _lmap(n, v, k, base, gid, tile, *_):
        return (tile[v], k)

    def _rmap(n, v, k, base, gid, *_):
        return (base[0] + gid[v], k, n)

    def _omap(n, v, k, base, gid, tile, *_):
        return (tile[v], n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,       # base, gid, tile, lo, hi, first
        grid=(N // tn, n_visits, n_k),
        in_specs=[pl.BlockSpec((tm, tk), _lmap)]
        + [pl.BlockSpec((None, tk, tn), _rmap)] * len(stacks),
        out_specs=pl.BlockSpec((tm, tn), _omap),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * (
            len(stacks) if n_k > 1 else 0),
    )
    interpret = _interpret_mode()
    need = _vmem_bytes(tm, tk, tn, len(stacks), lhs.dtype.itemsize, n_k)
    return pl.pallas_call(
        functools.partial(_gem_kernel, tm=tm, n_k=n_k, n_rhs=len(stacks)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, N), lhs.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 * 2 ** 20, need + need // 4)),
        interpret=interpret,
        name="grouped_expert_matmul",
    )(base, *meta, lhs, *stacks)


def _grouped_xla(lhs, rhs, sizes, rhs_up=None, base=None):
    """``lax.ragged_dot`` (the kernel's numerics reference, what runs off
    the TPU, and the sweep's other arm): the groups of other layers of a
    flattened stack are empty."""
    dt = lhs.dtype
    groups = sizes.astype(jnp.int32)
    if base is not None:
        groups = lax.dynamic_update_slice(
            jnp.zeros((rhs.shape[0],), jnp.int32), groups, (base,))
    f32 = jnp.float32
    out = lax.ragged_dot(lhs, rhs, groups, preferred_element_type=f32)
    if rhs_up is None:
        return out.astype(dt)
    return (jax.nn.silu(out).astype(dt)
            * lax.ragged_dot(lhs, rhs_up, groups,
                             preferred_element_type=f32).astype(dt))


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_visits, _gem_kernel, _grouped_kernel,
                                    _grouped_xla)
    return _SRC


def candidates_for(m: int, K: int, N: int, itemsize: int) -> list:
    """``"xla"`` (``lax.ragged_dot``: what runs where nothing sweeps),
    then the kernel at its one tiling, ``"kernel_m<tm>_k<tk>_n<tn>"``,
    by rule: 128 rows (fewer where m asks it; a held expert of a serving
    tick gets 1-32 rows, and 128 rows cost the MXU no more than latching
    the weight tile does), at most 512 columns, as deep in K as
    ``_WEIGHT_BLOCK_BYTES`` allow: with K one tile a group that
    straddles row tiles keeps the weight block it has, a second k tile
    would stream it again.  The registry chooses between the two arms
    and not among tiles: with the visit axis a traced bound, 64 rows
    read from 8% under to 3% over 128 at both routed cells' shapes and
    three draws of group sizes, 32 rows up to 14% over and 256 up to 16%
    (PERF.md §6, PR 35), and XLA's arm 1.9-3.1 times 128's."""
    tm = next(t for t in (128, 64, 32) if m % t == 0)
    tn = next(t for t in (512, 384, 256, 128) if N % t == 0)
    tk = next(t for t in range(K, 0, -128)
              if K % t == 0 and t * tn * itemsize <= _WEIGHT_BLOCK_BYTES)
    return ["xla", f"kernel_m{tm}_k{tk}_n{tn}"]


def _tiling(impl: str) -> tuple:
    tm, tk, tn = (int(p[1:]) for p in impl.split("_")[1:])
    return tm, tk, tn


def row_tile(impl: str) -> int:
    """Rows a met group pays for under ``impl``."""
    return XLA_ROW_TILE if impl == "xla" else _tiling(impl)[0]


def _tuned_impl(m: int, K: int, N: int, count: int, dtype,
                gated: bool) -> str:
    """The arm via the autotune registry, keyed by the static shapes
    alone.  A sweep times both arms with an eighth of the rows held,
    spread evenly over the groups (the eight-way expert-parallel cell's
    share; at a sixteenth the kernel's lead over XLA's 512-row tiles
    only grows); the serving cells' shapes are in the committed table,
    which is this sweep's on the chip, so no run of them sweeps."""
    from . import autotune

    def measure(impl):
        lhs = jnp.zeros((m, K), dtype)
        ws = [jnp.zeros((count, K, N), dtype)] * (2 if gated else 1)
        sizes = jnp.full((count,), max(1, m // (8 * count)), jnp.int32)
        if impl == "xla":
            fn = jax.jit(lambda a, s, *w: _grouped_xla(a, w[0], s, *w[1:]))
        else:
            fn = lambda a, s, *w: _grouped_kernel(  # noqa: E731
                a, w[0], s, *w[1:], tiling=_tiling(impl))
        return autotune.time_candidate(lambda: fn(lhs, sizes, *ws))

    return str(autotune.tuned(
        "grouped_expert_matmul",
        f"m{m}_k{K}_n{N}_g{count}_{'gated' if gated else 'plain'}",
        str(jnp.dtype(dtype)),
        candidates_for(m, K, N, jnp.dtype(dtype).itemsize),
        measure=measure, source=_autotune_source()))


def choose_impl(m: int, K: int, N: int, count: int, dtype,
                gated: bool) -> str:
    """The arm a product of these static shapes runs on: what the
    registry says where the kernel supports them and the program being
    traced runs on one device (a pallas call has no partitioning rule),
    else ``"xla"``."""
    if _supported(m, K, N, dtype) and single_device_program():
        return _tuned_impl(m, K, N, count, dtype, gated)
    return "xla"


def grouped_expert_matmul(lhs, rhs, sizes, rhs_up=None, base=None,
                          impl: str | None = None):
    """The grouped product of the module docstring on the arm ``impl``
    names (default: ``choose_impl`` of the shapes)."""
    if impl is None:
        impl = choose_impl(lhs.shape[0], lhs.shape[1], rhs.shape[2],
                           sizes.shape[0], lhs.dtype, rhs_up is not None)
    if impl == "xla":
        return _grouped_xla(lhs, rhs, sizes, rhs_up, base)
    return _grouped_kernel(lhs, rhs, sizes, rhs_up, base,
                           tiling=_tiling(impl))
