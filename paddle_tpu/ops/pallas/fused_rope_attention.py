"""RoPE applied to Q/K tiles INSIDE the Pallas flash kernel.

TPU-native rebuild of the reference's fused rotary attention
(phi/kernels/fusion/: fused_rope + flash-attn pipelines): the unfused
composition materializes rotated q and k as full [B, S, H, D] arrays —
one extra HBM write + read of each per layer — before the attention
kernel re-streams them. This kernel rotates each q tile once after its
VMEM load and each k tile once per (head, q-row-block) program inside
the online-softmax loop, so the separate rotary pass and its HBM
round-trip disappear.

Rotation uses the full-width form of models/llama.py ``apply_rope``:
with C = [cos, cos] and S = [-sin, sin] over the lane dim,

    rope(x) = x * C + swap(x) * S,    swap(x) = [x2, x1]

which is BIT-IDENTICAL to the split-half reference (x1*cos - x2*sin is
x1*cos + x2*(-sin) in IEEE) — pinned by tests/test_fused_rope_attention.py
against the eager apply_rope + flash composition. Two numerics guards
make that exact inside a fused kernel body (same scheme as
fused_norm_epilogue.py): each product is multiplied by a runtime-opaque
1.0 so backend fma contraction cannot skip the product rounding the
op-by-op reference performs, and the result passes through
``lax.reduce_precision`` so the bf16 narrowing cannot be elided by
convert-pair simplification before the MXU dot.

Backward stays XLA + the existing flash backward: rotation is applied
to the saved RAW q/k as plain XLA ops, ``_flash_bwd`` produces
cotangents w.r.t. the rotated tensors, and the rotary pullback
(dx = dy * C - swap(dy) * S, from S∘swap = -S) maps them back. The
extra rotated tensors exist only transiently inside the backward
computation; residuals stay (q, k, v, o, lse) like the unfused path.

Supported geometry: the flash native layout with one head per program
(head_dim in (128, 256)) so the rope tables index cleanly by rows.
``fused_rope_supported`` also mirrors flash_qkv_supported's flag
consultation — this entry hardcodes the native kernels fwd+bwd.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (_block_sizes, _causal_bounds, _flash_bwd,
                              _interpret_mode, _MIN_BLOCK, _tpu_params,
                              flash_attention_raw, supported)

__all__ = ["fused_rope_flash_attention", "fused_rope_supported",
           "rope_tables"]


def fused_rope_supported(shape, dtype) -> bool:
    """Kernel path: flash-supported geometry with hp == 1 (head_dim 128
    or 256) and the flash flags in their native-kernel default state."""
    from ...core.flags import GLOBAL_FLAGS

    def flag(name, default):
        return (GLOBAL_FLAGS.get(name) if GLOBAL_FLAGS.has(name)
                else default)

    if (not flag("flash_attention_native_layout", True)
            or not flag("flash_attention_kernel_bwd", True)
            or flag("use_library_flash_attention", False)):
        return False
    if len(shape) != 4:
        return False
    d = shape[-1]
    return supported(shape, dtype) and d in (128, 256)


def rope_tables(cos, sin, d: int):
    """Full-width fp32 rope tables from half-width angle arrays of any
    broadcastable shape ending in d/2: C = [cos, cos], S = [-sin, sin]."""
    cos = cos.astype(jnp.float32)
    sin = sin.astype(jnp.float32)
    cos_f = jnp.concatenate([cos, cos], axis=-1)
    sin_sgn = jnp.concatenate([-sin, sin], axis=-1)
    return cos_f, sin_sgn


def _apply_rope_ref(x, cos, sin):
    """Textual copy of models/llama.py apply_rope (split-half form) —
    the unfused composition the kernel is pinned against."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.concatenate([o1, o2], -1).astype(x.dtype)


def _rope_pullback(dy, cos_f, sin_sgn):
    """VJP of the full-width rotation: S∘swap = -S, so
    dx = dy * C - swap(dy) * S (fp32, cast back to dy.dtype)."""
    d = dy.shape[-1]
    dy32 = dy.astype(jnp.float32)
    dys = jnp.concatenate([dy32[..., d // 2:], dy32[..., :d // 2]], axis=-1)
    return (dy32 * cos_f - dys * sin_sgn).astype(dy.dtype)


def _rope_rows(x, c_rows, s_rows, one, d: int):
    """Rotate a [rows, d] tile in fp32.  With ``one`` (interpret mode:
    the body is compiled by XLA:CPU) per-product rounding is forced —
    opaque-one against fma contraction, reduce_precision against
    convert-pair elision — so the tile is bitwise what the eager
    apply_rope would have produced.  Mosaic lowers neither guard and the
    compiled kernel is held to a tolerance on the chip instead."""
    x32 = x.astype(jnp.float32)
    xs = jnp.concatenate([x32[:, d // 2:], x32[:, :d // 2]], axis=1)
    if one is None:
        return (x32 * c_rows + xs * s_rows).astype(x.dtype)
    y = (x32 * c_rows) * one + (xs * s_rows) * one
    if x.dtype == jnp.bfloat16:
        y = lax.reduce_precision(y, 8, 7)
    return y.astype(x.dtype)


def _rope_flash_fwd_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, *refs,
                           causal, sm_scale, block_k, seq_len, d, rope_q,
                           rope_k, exact, with_lse):
    """_flash_fwd_kernel_native specialized to hp=1, with the rotary
    applied to the q tile once and to each k tile inside the loop.
    ``refs`` = [one_ref if exact] + [o_ref] + [lse_ref if with_lse]."""
    import jax.experimental.pallas as pl

    refs = list(refs)
    one_ref = refs.pop(0) if exact else None
    o_ref = refs.pop(0)
    lse_ref = refs.pop(0) if with_lse else None

    q_idx = pl.program_id(2)
    bq = q_ref.shape[0]
    q_offs = q_idx * bq + jax.lax.iota(jnp.int32, bq)
    num_full_blocks, num_k_blocks = _causal_bounds(q_idx, bq, block_k,
                                                   seq_len, causal)
    # the barrier keeps the 1.0 runtime-opaque even when the operand is a
    # compile-time constant (it always is under jit: the ones array is
    # created inside the traced wrapper) — without it XLA folds the
    # *one muls away and fma contraction skips the product rounding
    one = lax.optimization_barrier(one_ref[0, 0]) if exact else None

    q = q_ref[...]                                   # [bq, d]
    if rope_q:
        rows = pl.dslice(q_idx * bq, bq)
        q = _rope_rows(q, cos_ref[rows, :], sin_ref[rows, :], one, d)

    m_i = jnp.full((bq,), -1e30, jnp.float32)
    l_i = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    def body(kb, carry, *, masked):
        m_i, l_i, acc = carry
        rows = pl.dslice(kb * block_k, block_k)
        k = k_ref[rows, :]                           # [bk, d]
        if rope_k:
            k = _rope_rows(k, cos_ref[rows, :], sin_ref[rows, :], one, d)
        v = v_ref[rows, :]
        s = jnp.dot(q, k.T,
                    preferred_element_type=jnp.float32) * sm_scale
        if masked:
            k_offs = kb * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_offs[:, None] >= k_offs[None, :], s, -1e30)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    carry = jax.lax.fori_loop(0, num_full_blocks,
                              functools.partial(body, masked=False),
                              (m_i, l_i, acc))
    m_i, l_i, acc = jax.lax.fori_loop(num_full_blocks, num_k_blocks,
                                      functools.partial(body, masked=causal),
                                      carry)
    o_ref[...] = (acc / l_i[:, None]).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = jnp.broadcast_to((m_i + jnp.log(l_i))[None, :],
                                      lse_ref.shape[1:])


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "with_lse", "rope_q", "rope_k",
                                             "block_q", "block_k"))
def _rope_fwd(q, k, v, cos_f, sin_sgn, causal: bool, sm_scale: float,
              with_lse: bool = False, rope_q: bool = True,
              rope_k: bool = True, block_q: int | None = None,
              block_k: int | None = None):
    import jax.experimental.pallas as pl

    b, s, h, d = q.shape
    if block_q is None or block_k is None:
        block_q, block_k = _block_sizes(s)
    qf = q.reshape(b, s, h * d)
    kf = k.reshape(b, s, h * d)
    vf = v.reshape(b, s, h * d)
    grid = (b, h, s // block_q)
    blk = pl.BlockSpec((None, block_q, d), lambda ib, ih, iq: (ib, iq, ih))
    full = pl.BlockSpec((None, s, d), lambda ib, ih, iq: (ib, 0, ih))
    tab = pl.BlockSpec((s, d), lambda ib, ih, iq: (0, 0))
    exact = _interpret_mode()
    ops, in_specs = [qf, kf, vf, cos_f, sin_sgn], [blk, full, full, tab, tab]
    if exact:
        ops.append(jnp.ones((1, 1), jnp.float32))
        in_specs.append(pl.BlockSpec((1, 1), lambda ib, ih, iq: (0, 0)))
    out_shapes = [jax.ShapeDtypeStruct((b, s, h * d), q.dtype)]
    out_specs = [blk]
    if with_lse:
        out_shapes.append(jax.ShapeDtypeStruct((b, h, 8, s), jnp.float32))
        out_specs.append(pl.BlockSpec((None, 1, 8, block_q),
                                      lambda ib, ih, iq: (ib, ih, 0, iq)))
    kern = functools.partial(
        _rope_flash_fwd_kernel, causal=causal, sm_scale=sm_scale,
        block_k=block_k, seq_len=s, d=d, rope_q=rope_q, rope_k=rope_k,
        exact=exact, with_lse=with_lse)
    res = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shapes if with_lse else out_shapes[0],
        interpret=exact,
        compiler_params=_tpu_params(2),
        name="rope_flash_fwd",
    )(*ops)
    if with_lse:
        out, lse = res
        return out.reshape(b, s, h, d), lse
    return res.reshape(b, s, h, d)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_rope_flash_fwd_kernel, _rope_rows,
                                    _rope_fwd)
    return _SRC


def _tuned_rope_blocks(b, s, h, d, dtype, causal) -> tuple[int, int]:
    """Square block candidates via the autotune registry; candidates[0]
    is the flash default so no-sweep backends keep legacy behavior."""
    from . import autotune

    default = _block_sizes(s)
    if min(default) < _MIN_BLOCK:
        return default
    cands = [list(default)]
    for c in (512, 256, 1024):
        if c <= s and s % c == 0 and [c, c] not in cands:
            cands.append([c, c])

    def measure(cand):
        bq, bk = int(cand[0]), int(cand[1])
        qz = jnp.zeros((b, s, h, d), dtype)
        cz = jnp.zeros((s, d), jnp.float32)
        out = _rope_fwd(qz, qz, qz, cz, cz, causal, 1.0, with_lse=True,
                        block_q=bq, block_k=bk)
        return autotune.time_candidate(lambda: _rope_fwd(
            qz, qz, qz, cz, cz, causal, 1.0, with_lse=True,
            block_q=bq, block_k=bk))

    bucket = f"b{b}_s{s}_h{h}_d{d}_c{int(causal)}"
    cfg = autotune.tuned("rope_flash", bucket, str(jnp.dtype(dtype)), cands,
                         measure=measure, source=_autotune_source())
    return int(cfg[0]), int(cfg[1])


def fused_rope_flash_attention(q, k, v, cos, sin, *, causal: bool = True,
                               sm_scale: float | None = None,
                               rope_q: bool = True, rope_k: bool = True,
                               use_kernel: bool | None = None):
    """Flash attention over UNROTATED q/k with RoPE fused in-kernel.

    ``cos``/``sin`` are the half-width angle tables for absolute
    positions 0..S-1 (any shape reshapable to [S, D/2], fp32 — exactly
    what models/llama.py rope_angles produces). ``rope_q``/``rope_k``
    control which side rotates (prefill with an externally-rotated KV
    cache passes rope_k=False). ``use_kernel=False`` pins the XLA
    fallback arm: eager-equivalent apply_rope + the standard flash path
    — also the parity reference."""
    b, s, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    cos = cos.reshape(s, d // 2).astype(jnp.float32)
    sin = sin.reshape(s, d // 2).astype(jnp.float32)
    if use_kernel is None:
        use_kernel = fused_rope_supported(q.shape, q.dtype)
    if not use_kernel:
        cb = cos[None, :, None, :]
        sb = sin[None, :, None, :]
        qr = _apply_rope_ref(q, cb, sb) if rope_q else q
        kr = _apply_rope_ref(k, cb, sb) if rope_k else k
        return flash_attention_raw(qr, kr, v, causal=causal, sm_scale=scale)

    cos_f, sin_sgn = rope_tables(cos, sin, d)
    block_q, block_k = _tuned_rope_blocks(b, s, h, d, q.dtype, causal)
    cfg = (causal, float(scale), bool(rope_q), bool(rope_k),  # tpu-lint: disable=TPL101 -- sm_scale/rope flags are static Python config (shape-derived), never traced arrays
           int(block_q), int(block_k))
    return _fused(q, k, v, cos_f, sin_sgn, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused(q, k, v, cos_f, sin_sgn, cfg):
    causal, scale, rope_q, rope_k, bq, bk = cfg
    return _rope_fwd(q, k, v, cos_f, sin_sgn, causal, scale,
                     rope_q=rope_q, rope_k=rope_k, block_q=bq, block_k=bk)


def _fused_fwd(q, k, v, cos_f, sin_sgn, cfg):
    from jax.ad_checkpoint import checkpoint_name

    causal, scale, rope_q, rope_k, bq, bk = cfg
    o, lse = _rope_fwd(q, k, v, cos_f, sin_sgn, causal, scale, with_lse=True,
                       rope_q=rope_q, rope_k=rope_k, block_q=bq, block_k=bk)
    # same checkpoint names as flash_attention_raw so the models' remat
    # save policies cover this entry too
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, cos_f, sin_sgn, o, lse)


def _fused_bwd(cfg, res, g):
    causal, scale, rope_q, rope_k, _bq, _bk = cfg
    q, k, v, cos_f, sin_sgn, o, lse = res
    cb = cos_f[None, :, None, :]
    sb = sin_sgn[None, :, None, :]

    def rot(x):
        x32 = x.astype(jnp.float32)
        d = x.shape[-1]
        xs = jnp.concatenate([x32[..., d // 2:], x32[..., :d // 2]], axis=-1)
        return (x32 * cb + xs * sb).astype(x.dtype)

    qr = rot(q) if rope_q else q
    kr = rot(k) if rope_k else k
    dqr, dkr, dv = _flash_bwd(qr, kr, v, o, lse, g, causal, scale,
                              native=True)
    dq = _rope_pullback(dqr, cb, sb) if rope_q else dqr
    dk = _rope_pullback(dkr, cb, sb) if rope_k else dkr
    return dq, dk, dv, jnp.zeros_like(cos_f), jnp.zeros_like(sin_sgn)


_fused.defvjp(_fused_fwd, _fused_bwd)
