"""Pallas TPU depthwise causal convolution over the serving tick's rows.

The state-space mixer's short convolution (Mamba-2: width ``K + 1 = 4``)
on the engine's ``[C, qb]`` grid of rows (ragged_ssm_scan.py has the rows'
contract): channel ``d`` of token ``t`` is

    act_t = silu(b + sum_{k=0..K} w[:, k] * x_{t-K+k})

over the request's own tokens, zeros before its first.  A token's ``K``
predecessors are its neighbours in its row, the tail of the row before
where that row is its request's too (a request's chunks are consecutive
rows, every one but the last full), else the request's *conv state*: its
last ``K`` inputs, kept in a pool of slots beside the recurrence's state
and moved here by the same two slot ids a row.

Contract shared by the kernel and the XLA form:

- pool ``[S, K * Dc]``: a slot is the request's last ``K`` inputs,
  oldest first, flat; x ``[C * qb, Dc]``: the grid's tokens row by row
  (two-dimensional on purpose, as ``ragged_ssm_scan``'s); w ``[Dc, K +
  1]``, b ``[Dc]``.
- read, write ``[C]`` int32 slot ids, n_valid ``[C]`` int32 in [0, qb]: a
  run of rows (adjacent, sharing ``write``) starts from slot ``read`` and
  leaves in slot ``write`` the last ``K`` of (what it started from, its
  tokens); a row whose ``write`` is ``dump`` is idle: it reads slot
  ``zero`` (kept at zero), writes nothing, and its ``act`` is finite.
- tokens ``j >= n_valid`` of a row are padding: their ``act`` is
  unspecified (finite), and they are not part of the state the row
  leaves.

Returns ``(act [C * qb, Dc] in x's dtype, pool)``.

The XLA form gathers the rows' states and scatters what they leave (``C``
rows of 26 KB); the kernel moves them itself, the pool an operand left in
HBM and aliased to its output.  A slot is one row of the pool's tiles
there (16 slots of bf16, 8 of fp32, interleaved), and no DMA moves less
than a tile: the kernel keeps the tile of the slot a run writes in VMEM,
in fp32, from the run's first row (which takes what the run starts from
out of it, or out of the tile of ``read`` where that is another) until a
run's first row wants another tile or the call ends, and writes it back
then, rounded once: the live slots are neighbours, so a tick of decode
rows moves each of their few tiles once each way.  The pool is whole
tiles of slots (the gate); XLA makes the taps' ``[8, Dc]`` block and the
rows' flags, nothing else.  The kernel is the window: a
grid step is one row, the row before it rides as a second block of the
same array, predecessors and tokens lie in an fp32 scratch ``[8 + qb,
Dc]`` (the predecessors in its first tile's last ``K`` sublanes), each
tap is a load at a sublane offset, and the state the row leaves is the
tile that ends at its last valid token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _interpret_mode, single_device_program
from .ragged_ssm_scan import _runs

__all__ = ["ragged_causal_conv"]

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# both forms sum the taps in fp32.
ACCUM_DTYPE = "float32"

_TILE = 8                       # fp32 sublanes


def _slots_a_tile(item: int) -> int:
    """Slots (rows of the pool) in one of its tiles in HBM: the least the
    kernel can move."""
    return 4 * _TILE // item


def _supported(Dc: int, K: int, qb: int, slots: int, item: int) -> bool:
    """Gate for the kernel: lane-dense channels, whole sublane tiles a
    row, a state that fits one tile, and a pool of whole tiles of
    slots."""
    return (Dc % 128 == 0 and qb % _TILE == 0 and 0 < K < _TILE
            and item in (2, 4) and slots % _slots_a_tile(item) == 0)


def _conv_kernel(first_ref, last_ref, nval_ref, rd_ref, wr_ref, x_ref,
                 prev_ref, wb_ref, _pool_ref, act_ref, pool_ref, ext_ref,
                 tile_ref, stage_ref, at_ref, sem, *, K, qb, T):
    """One row.  ``prev_ref`` is the row before (its last tile ends with
    what a continuing row needs); ``wb_ref [8, Dc]``: the taps, then the
    bias.  ``pool_ref`` is the pool where it lies in HBM (``_pool_ref``
    the same bytes, the operand it is aliased to); ``tile_ref [T, K *
    Dc]`` fp32 holds the tile of ``T`` slots that the run at hand leaves
    its state in, ``at_ref[0]`` says which (-1: none yet): a run's first
    row brings it in through ``stage_ref`` (the pool's dtype) unless it is
    there, after writing back the one that was, and the call's last row
    writes back the last."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = pl.program_id(0)
    f32 = jnp.float32
    Dc = x_ref.shape[1]

    def move(t, out: bool):
        """Tile ``t`` of the pool into the staging buffer, or out of it."""
        there = pool_ref.at[pl.ds(pl.multiple_of(t * T, T), T), :]
        src, dst = (stage_ref, there) if out else (there, stage_ref)
        copy = pltpu.make_async_copy(src, dst, sem.at[0])
        copy.start()
        copy.wait()

    def write_back():
        @pl.when(at_ref[0] >= 0)
        def _():
            stage_ref[...] = tile_ref[...].astype(stage_ref.dtype)
            move(at_ref[0], True)

    @pl.when(c == 0)
    def _none_yet():
        at_ref[0] = -1

    fst = first_ref[c] == 1

    @pl.when(fst)
    def _a_run_starts():
        rd, t = rd_ref[c], wr_ref[c] // T

        @pl.when(t != at_ref[0])
        def _its_tile():
            write_back()
            move(t, False)
            tile_ref[...] = stage_ref[...].astype(f32)
            at_ref[0] = t

        # a slot one run writes no other run reads: what the run starts
        # from is as good in HBM as in the tile at hand
        @pl.when(rd // T == t)
        def _from_the_tile():
            for k in range(K):
                ext_ref[_TILE - K + k:_TILE - K + k + 1, :] = tile_ref[
                    pl.ds(rd % T, 1), k * Dc:(k + 1) * Dc]

        @pl.when(rd // T != t)
        def _from_another():
            move(rd // T, False)
            it = lax.broadcasted_iota(jnp.int32, (T, Dc), 0) == rd % T
            for k in range(K):
                ext_ref[_TILE - K + k:_TILE - K + k + 1, :] = jnp.sum(
                    jnp.where(it, stage_ref[:, k * Dc:(k + 1) * Dc].astype(
                        f32), 0.0), axis=0, keepdims=True)

    @pl.when(jnp.logical_not(fst))
    def _a_run_goes_on():
        ext_ref[0:_TILE, :] = prev_ref[qb - _TILE:qb, :].astype(f32)

    ext_ref[_TILE:_TILE + qb, :] = x_ref[...].astype(f32)
    acc = wb_ref[K + 1:K + 2, :]
    for k in range(K + 1):
        acc = acc + wb_ref[k:k + 1, :] * ext_ref[pl.ds(_TILE - K + k, qb), :]
    act_ref[...] = (acc * jax.nn.sigmoid(acc)).astype(act_ref.dtype)

    @pl.when(last_ref[c] == 1)
    def _a_run_ends():
        # the last K of (predecessors, the row's n tokens): the tile that
        # ends at the n-th token, brought to the front by a rotation (a
        # load of several sublanes at an offset that is data does not
        # lower; of one, as here below, it does)
        rows = _TILE + qb
        left = pltpu.roll(ext_ref[...], (rows - nval_ref[c]) % rows,
                          0)[_TILE - K:_TILE, :]
        for k in range(K):
            tile_ref[pl.ds(wr_ref[c] % T, 1), k * Dc:(k + 1) * Dc] = left[
                k:k + 1, :]

    @pl.when(c == pl.num_programs(0) - 1)
    def _the_call_ends():
        write_back()


@functools.partial(jax.jit, static_argnames=("K", "qb"))
def _conv_pallas(pool, x, wb, first, last, n_valid, read, write, *, K, qb):
    """The kernel form: ``(act, pool)``, the pool updated where it lies."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Dc = x.shape[1]
    C = x.shape[0] // qb
    T = _slots_a_tile(pool.dtype.itemsize)
    i32 = jnp.int32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,          # first, last, n_valid, read, write
        grid=(C,),
        in_specs=[
            pl.BlockSpec((qb, Dc), lambda c, *_: (c, 0)),
            pl.BlockSpec((qb, Dc), lambda c, *_: (jnp.maximum(c - 1, 0), 0)),
            pl.BlockSpec((_TILE, Dc), lambda c, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((qb, Dc), lambda c, *_: (c, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((_TILE + qb, Dc), jnp.float32),
                        pltpu.VMEM((T, K * Dc), jnp.float32),
                        pltpu.VMEM((T, K * Dc), pool.dtype),
                        pltpu.SMEM((1,), i32),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    interpret = _interpret_mode()
    return pl.pallas_call(  # tpu-lint: disable=TPL007 -- a block IS a row of the grid; nothing to sweep
        functools.partial(_conv_kernel, K=K, qb=qb, T=T),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the scalar-prefetch refs: 8 is the pool
        input_output_aliases={8: 1},
        # the rows are a sequence: a run's tile of slots stays in VMEM
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_causal_conv",
    )(first.astype(i32), last.astype(i32), n_valid.astype(i32),
      read.astype(i32), write.astype(i32), x, x, wb, pool)


def _window_xla(x, kept, wb, first, n_valid, *, K, qb):
    """act ``[C * qb, Dc]`` and what each row leaves ``[C, K, Dc]`` fp32
    from the rows' tokens and the states ``kept [C, K, Dc]`` fp32 they
    start from, in plain XLA."""
    f32 = jnp.float32
    Dc = x.shape[1]
    C = x.shape[0] // qb
    xg = x.reshape(C, qb, Dc).astype(f32)
    tail = jnp.where(first[:, None, None], kept,
                     jnp.roll(xg[:, qb - K:], 1, axis=0))
    ext = jnp.concatenate([tail, xg], axis=1)              # [C, K + qb, Dc]
    acc = wb[K + 1]
    for k in range(K + 1):
        acc = acc + wb[k] * lax.slice_in_dim(ext, k, k + qb, axis=1)
    left = jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(e, n, K, axis=0))(
        ext, n_valid)
    return jax.nn.silu(acc).astype(x.dtype).reshape(C * qb, Dc), left


def ragged_causal_conv(pool, x, w, b, read, write, n_valid, *, qb: int,
                       zero, dump, impl: str | None = None):
    """The convolution of the module docstring; ``impl`` names the form
    (``"kernel"`` or ``"xla"``; default: the kernel where its gate admits
    the shapes and the program runs on one device).  Returns ``(act,
    pool)``."""
    f32 = jnp.float32
    Dc = x.shape[1]
    C, K = x.shape[0] // qb, w.shape[1] - 1
    idle, first, last, _live, _run0 = _runs(write, dump)
    n_valid = jnp.where(idle, 0, n_valid)
    wb = jnp.concatenate([
        w.astype(f32).T, b.astype(f32)[None],
        jnp.zeros((_TILE - K - 2, Dc), f32)])              # [8, Dc]
    if impl is None:
        impl = ("kernel" if _supported(Dc, K, qb, pool.shape[0],
                                       pool.dtype.itemsize)
                and single_device_program() else "xla")
    if impl == "kernel":
        return _conv_pallas(pool, x, wb, first, last, n_valid, read, write,
                            K=K, qb=qb)
    kept = pool.at[jnp.where(idle, zero, read)].get(
        mode="promise_in_bounds").reshape(C, K, Dc).astype(f32)
    act, left = _window_xla(x, kept, wb, first, n_valid, K=K, qb=qb)
    # written where the row ends its request's run (an index past the
    # pool is dropped)
    pool = pool.at[jnp.where(last, write, pool.shape[0])].set(
        left.reshape(C, K * Dc).astype(pool.dtype), mode="drop")
    return act, pool
