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

The gather of the rows' states and the scatter of what they leave are
XLA's on both forms (``C`` rows of 26 KB); the kernel is the window: a
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


def _supported(Dc: int, K: int, qb: int) -> bool:
    """Gate for the kernel: lane-dense channels, whole sublane tiles a
    row, and a state that fits one tile."""
    return Dc % 128 == 0 and qb % _TILE == 0 and 0 < K < _TILE


def _conv_kernel(first_ref, nval_ref, x_ref, prev_ref, kept_ref, wb_ref,
                 act_ref, left_ref, ext_ref, *, K, qb):
    """One row.  ``kept_ref``, ``left_ref`` ``[8, Dc]`` fp32 hold a state's
    ``K`` inputs in their last sublanes; ``prev_ref`` is the row before
    (its last tile ends with what a continuing row needs); ``wb_ref [8,
    Dc]``: the taps, then the bias."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = pl.program_id(0)
    f32 = jnp.float32
    before = jnp.where(first_ref[c] == 1, kept_ref[...],
                       prev_ref[qb - _TILE:qb, :].astype(f32))
    ext_ref[0:_TILE, :] = before
    ext_ref[_TILE:_TILE + qb, :] = x_ref[...].astype(f32)
    acc = wb_ref[K + 1:K + 2, :]
    for k in range(K + 1):
        acc = acc + wb_ref[k:k + 1, :] * ext_ref[pl.ds(_TILE - K + k, qb), :]
    act_ref[...] = (acc * jax.nn.sigmoid(acc)).astype(act_ref.dtype)
    # the last K of (predecessors, the row's n tokens): the tile that
    # ends at the n-th token, brought to the front by a rotation (a load
    # at a sublane offset that is data does not lower)
    rows = _TILE + qb
    left_ref[...] = pltpu.roll(ext_ref[...], (rows - nval_ref[c]) % rows,
                               0)[0:_TILE, :]


@functools.partial(jax.jit, static_argnames=("K", "qb"))
def _window_pallas(x, kept, wb, first, n_valid, *, K, qb):
    """act ``[C * qb, Dc]`` and what each row leaves ``[C, 8, Dc]`` fp32
    (the last ``K`` sublanes) from the rows' tokens and the states
    ``kept [C, 8, Dc]`` fp32 they start from."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Dc = x.shape[1]
    C = x.shape[0] // qb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # first, n_valid
        grid=(C,),
        in_specs=[
            pl.BlockSpec((qb, Dc), lambda c, *_: (c, 0)),
            pl.BlockSpec((qb, Dc), lambda c, *_: (jnp.maximum(c - 1, 0), 0)),
            pl.BlockSpec((None, _TILE, Dc), lambda c, *_: (c, 0, 0)),
            pl.BlockSpec((_TILE, Dc), lambda c, *_: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((qb, Dc), lambda c, *_: (c, 0)),
                   pl.BlockSpec((None, _TILE, Dc), lambda c, *_: (c, 0, 0))],
        scratch_shapes=[pltpu.VMEM((_TILE + qb, Dc), jnp.float32)],
    )
    interpret = _interpret_mode()
    return pl.pallas_call(  # tpu-lint: disable=TPL007 -- a block IS a row of the grid; nothing to sweep
        functools.partial(_conv_kernel, K=K, qb=qb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((C, _TILE, Dc), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_causal_conv",
    )(first.astype(jnp.int32), n_valid.astype(jnp.int32), x, x, kept, wb)


def _window_xla(x, kept, wb, first, n_valid, *, K, qb):
    """The same in plain XLA (the kernel's reference, and what runs where
    the gate refuses the shapes)."""
    f32 = jnp.float32
    Dc = x.shape[1]
    C = x.shape[0] // qb
    xg = x.reshape(C, qb, Dc).astype(f32)
    tail = jnp.where(first[:, None, None], kept[:, _TILE - K:],
                     jnp.roll(xg[:, qb - K:], 1, axis=0))
    ext = jnp.concatenate([tail, xg], axis=1)              # [C, K + qb, Dc]
    acc = wb[K + 1]
    for k in range(K + 1):
        acc = acc + wb[k] * lax.slice_in_dim(ext, k, k + qb, axis=1)
    left = jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(e, n, K, axis=0))(
        ext, n_valid)
    left = jnp.concatenate(
        [jnp.zeros((C, _TILE - K, Dc), f32), left], axis=1)
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
    kept = pool.at[jnp.where(idle, zero, read)].get(
        mode="promise_in_bounds").reshape(C, K, Dc).astype(f32)
    kept = jnp.concatenate([jnp.zeros((C, _TILE - K, Dc), f32), kept], axis=1)
    wb = jnp.concatenate([
        w.astype(f32).T, b.astype(f32)[None],
        jnp.zeros((_TILE - K - 2, Dc), f32)])              # [8, Dc]
    if impl is None:
        impl = ("kernel" if _supported(Dc, K, qb) and single_device_program()
                else "xla")
    window = _window_pallas if impl == "kernel" else _window_xla
    act, left = window(x, kept, wb, first, jnp.where(idle, 0, n_valid),
                       K=K, qb=qb)
    # written where the row ends its request's run (an index past the
    # pool is dropped)
    pool = pool.at[jnp.where(last, write, pool.shape[0])].set(
        left[:, _TILE - K:].reshape(C, K * Dc).astype(pool.dtype),
        mode="drop")
    return act, pool
