"""Pallas TPU write of a step's new K/V tokens into the paged KV pool,
where the pool lies.

The serving engine's write-before-attend: every grid row of the unified
step (ragged_paged_attention.py has the contract) puts its chunk's new
keys and values into the request's pages before attending over them.
As an XLA scatter that write cannot happen in place on the chip: XLA
lays a scatter's operand out token-major (window dims minor), the
attention kernel reads d-major k pages, so the pool — or the layer's
slice of it — is transposed before and after every layer's scatter.
This kernel reads a page in the attention kernel's own layout, puts the
chunk's tokens into it and writes it back through
``input_output_aliases``: the pool is updated where it lies.

Contract shared by the kernel and the XLA fallback:

- k_pages [P, nKV, d, bs] d-major, v_pages [P, nKV, bs, dv]; k
  [C, qb, nKV, d] and v [C, qb, nKV, dv] in the pages' dtype.  The two
  planes need not be of one width: latent (MLA) pages ride them as one
  "head" each, k_rope d-major beside c_kv token-major
  (models/mla_moe.py).
- rows [C, max_blocks], pos0 [C], n_valid [C] as the attention takes
  them: chunk c's token i < n_valid[c] lands at offset
  (pos0[c] + i) % bs of page rows[c, (pos0[c] + i) // bs].
- Tokens i >= n_valid[c] are padding. The XLA arm scatters them into
  page ``sink`` (a scatter has to write somewhere); the kernel writes
  nothing for them. Nothing attends the sink unmasked, so the arms
  agree on every page a request owns.
- Chunks that write the same page in one call are ADJACENT rows (the
  engine packs a request's prefill chunks into consecutive rows, in
  position order). The kernel keeps a page in VMEM across adjacent
  rows; a page revisited after another page came between could be
  fetched before its first write-back landed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode

__all__ = ["paged_kv_write", "paged_kv_write_supported"]


def paged_kv_write_supported(kt_pages_shape, qb: int,
                             itemsize: int = 2, v_width=None) -> bool:
    """Gate for the kernel: MXU/lane-tileable pages (the attention
    kernel's own gate on d and bs; planes of unequal width need the
    d-major one sublane-tileable and the token-major one lane-tileable),
    a chunk that spans at most two pages, and values that a bf16 one-hot
    product places exactly (bf16 or int8 pages)."""
    _, _, d, bs = kt_pages_shape
    widths = (d in (128, 256) if v_width in (None, d)
              else d % 16 == 0 and v_width % 128 == 0)
    return widths and bs % 128 == 0 and qb <= bs and itemsize <= 2


def _write_kernel(pid_ref, pos0_ref, nval_ref, kn_ref, vn_ref, kin_ref,
                  vin_ref, ko_ref, vo_ref, *, qb, bs, nkv):
    """One (chunk, window) program. Window 0 is the page of the chunk's
    first token, window 1 the page of its last valid token (the same
    page unless the chunk straddles a boundary). ``pid_ref`` holds the
    page of every (chunk, window) in grid order; a run of equal ids
    keeps its output block in VMEM, so the first program of a run loads
    the page and the later ones add to it."""
    import jax.experimental.pallas as pl

    c = pl.program_id(0)
    w = pl.program_id(1)
    s = c * 2 + w

    @pl.when(jnp.logical_or(s == 0,
                            pid_ref[s] != pid_ref[jnp.maximum(s - 1, 0)]))
    def _load():
        ko_ref[...] = kin_ref[...]
        vo_ref[...] = vin_ref[...]

    # token i sits at offset start + i of this window's page
    start = pos0_ref[c] % bs - w * bs
    n = nval_ref[c]

    @pl.when(jnp.logical_and(start + n > 0, start < bs))
    def _write():
        f32, cdt = jnp.float32, jnp.bfloat16
        # one-hot placement on the MXU: x * 1 + zeros is exact for the
        # bf16 or int8 values the gate admits, in one bf16 pass whatever
        # the ambient matmul precision asks for
        one_pass = jax.lax.Precision.DEFAULT
        tok = jax.lax.broadcasted_iota(jnp.int32, (qb, bs), 0)
        off = jax.lax.broadcasted_iota(jnp.int32, (qb, bs), 1)
        hit = jnp.logical_and(off == start + tok, tok < n)      # [qb, bs]
        tok_t = jax.lax.broadcasted_iota(jnp.int32, (bs, qb), 1)
        off_t = jax.lax.broadcasted_iota(jnp.int32, (bs, qb), 0)
        hit_t = jnp.logical_and(off_t == start + tok_t, tok_t < n)
        j = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) - start
        lanes = jnp.logical_and(j >= 0, j < n)                  # [1, bs]
        j_t = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0) - start
        sublanes = jnp.logical_and(j_t >= 0, j_t < n)           # [bs, 1]
        for h in range(nkv):
            new = jax.lax.dot(kn_ref[h].astype(cdt), hit.astype(cdt),
                              precision=one_pass,
                              preferred_element_type=f32)       # [d, bs]
            ko_ref[h] = jnp.where(lanes, new,
                                  ko_ref[h].astype(f32)).astype(ko_ref.dtype)
            new = jax.lax.dot(hit_t.astype(cdt), vn_ref[h].astype(cdt),
                              precision=one_pass,
                              preferred_element_type=f32)       # [bs, d]
            vo_ref[h] = jnp.where(sublanes, new,
                                  vo_ref[h].astype(f32)).astype(vo_ref.dtype)


@jax.jit
def paged_kv_write_kernel(kt_pages, v_pages, k, v, rows, pos0, n_valid):
    """The kernel arm (see module docstring; gate with
    paged_kv_write_supported())."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, qb, nkv, d = k.shape
    dv = v.shape[3]
    bs = kt_pages.shape[3]
    pos0 = pos0.astype(jnp.int32)
    n_valid = n_valid.astype(jnp.int32)
    blk = jnp.stack([pos0, pos0 + n_valid - 1], axis=1) // bs   # [C, 2]
    pid = jnp.take_along_axis(rows.astype(jnp.int32), blk, axis=1)
    kn = k.transpose(0, 2, 3, 1)                                # [C,nkv,d,qb]
    vn = v.transpose(0, 2, 1, 3)                                # [C,nkv,qb,d]

    def _new(c, w, *_):
        return (c, 0, 0, 0)

    def _page(c, w, pid_ref, *_):
        return (pid_ref[c * 2 + w], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # pid, pos0, n_valid
        grid=(C, 2),
        in_specs=[
            pl.BlockSpec((None, nkv, d, qb), _new),
            pl.BlockSpec((None, nkv, qb, dv), _new),
            pl.BlockSpec((None, nkv, d, bs), _page),
            pl.BlockSpec((None, nkv, bs, dv), _page),
        ],
        out_specs=[pl.BlockSpec((None, nkv, d, bs), _page),
                   pl.BlockSpec((None, nkv, bs, dv), _page)],
    )
    interpret = _interpret_mode()
    return pl.pallas_call(  # tpu-lint: disable=TPL007 -- blocks ARE the page geometry (a whole page per program); nothing to sweep
        functools.partial(_write_kernel, qb=qb, bs=bs, nkv=nkv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kt_pages.shape, kt_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # operands count the scalar-prefetch refs: 5, 6 are the pools
        input_output_aliases={5: 0, 6: 1},
        # the grid is a sequence: a page stays in VMEM across a run
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_kv_write",
    )(pid.reshape(-1), pos0, n_valid, kn, vn, kt_pages, v_pages)


def _paged_kv_write_xla(k_pages, v_pages, k, v, rows, pos0, n_valid, sink):
    """XLA scatter fallback (and the kernel's reference): one (page,
    offset) per token, padding into ``sink``."""
    C, qb, nkv, d = k.shape
    bs = k_pages.shape[3]
    positions = pos0[:, None] + jnp.arange(qb, dtype=jnp.int32)
    valid = jnp.arange(qb, dtype=jnp.int32)[None, :] < n_valid[:, None]
    pages = jnp.where(
        valid, jnp.take_along_axis(rows, positions // bs, axis=1),
        sink).reshape(-1)
    offs = (positions % bs).reshape(-1)
    k_pages = k_pages.at[pages, :, :, offs].set(k.reshape(C * qb, nkv, d))
    v_pages = v_pages.at[pages, :, offs].set(
        v.reshape(C * qb, nkv, v.shape[3]))
    return k_pages, v_pages


def paged_kv_write(k_pages, v_pages, k, v, rows, pos0, n_valid, sink=0):
    """Write the chunks' new keys and values into their pages: the
    kernel where the page geometry supports it, else the XLA scatter.
    Returns (k_pages, v_pages)."""
    if paged_kv_write_supported(k_pages.shape, k.shape[1],
                                k_pages.dtype.itemsize, v.shape[3]):
        return paged_kv_write_kernel(k_pages, v_pages, k, v, rows, pos0,
                                     n_valid)
    return _paged_kv_write_xla(k_pages, v_pages, k, v, rows, pos0, n_valid,
                               sink)
