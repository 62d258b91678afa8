"""Fused softmax cross-entropy as Pallas TPU kernels.

TPU-native replacement for the reference's fused softmax-CE CUDA kernels
(paddle/phi/kernels/gpu/c_softmax_with_cross_entropy_kernel.cu,
cross_entropy_kernel.cu): the full-vocab logit tensor — the largest
activation in GPT training by far ([B*T, V] fp32 = 1.6 GB at 350m/b8) —
never exists in HBM. Profiling the round-2 350m step showed the XLA
chunked-CE path (models/gpt.py _chunked_ce) spending ~44 ms/step
materializing fp32 logit chunks four times (fwd scan, bwd recompute,
softmax grad, lse reductions); these kernels stream [bt, bv] logit tiles
through VMEM with online logsumexp instead, like flash attention does
for scores.

Forward:  grid (token_blocks, vocab_tiles), vocab innermost; running
          (max, sumexp, gold) carried in VMEM scratch; emits per-token
          nll and lse.
Backward: dlogits = g * (softmax - onehot), recomputed tile-wise from
          the saved lse. dx accumulates over vocab tiles in the output
          ref; dhead uses a transposed grid (vocab outer, tokens inner)
          and accumulates over token blocks. Both accumulate in fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode, _tpu_params

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# logits and the bwd dx/dh accumulators are fp32 in every kernel arm.
ACCUM_DTYPE = "float32"

# Tile sizes: head tile [H, bv] bf16 is the VMEM resident; token block
# [BT, H] streams. The final vocab tile may be a partial block (Pallas
# pads reads; the kernels mask col >= V). v5e VMEM is ~16 MB/core, so bv
# is chosen per-H to fit double-buffered operands + fp32 logits + the
# bwd fp32 accumulator block (measured: H=1024 fwd works at bv=2048 but
# its bwd needs 512; H=2048 needs 1024/256).
BLOCK_T = 512
_VMEM_BUDGET_FWD = 12 * 2 ** 20
_VMEM_BUDGET_BWD = 11 * 2 ** 20


_BV_LADDER = (2048, 1024, 512, 256, 128)


def _bv_feasible(H: int, bv: int, is_bwd: bool) -> bool:
    """VMEM feasibility of one vocab tile size."""
    bt = BLOCK_T
    # double-buffered x and h tiles + fp32 logits tile
    est = 2 * (bt * H * 2 + H * bv * 2) + bt * bv * 4
    if is_bwd:
        # p/dl temps + the resident fp32 accumulator output block
        est += bt * bv * 4 + 4 * max(bt * H, H * bv)
        return est <= _VMEM_BUDGET_BWD
    return est <= _VMEM_BUDGET_FWD


def _pick_bv(H: int, is_bwd: bool) -> int:
    """Largest feasible vocab tile, or 0 when NO tile fits VMEM (wide
    hidden sizes: the bwd accumulator block alone is 4*bt*H bytes)."""
    for bv in _BV_LADDER:
        if _bv_feasible(H, bv, is_bwd):
            return bv
    return 0


def fused_ce_supported(n_tokens: int, hidden: int, vocab: int) -> bool:
    """Token count must tile evenly; H must be lane-aligned; BOTH the
    fwd and bwd kernels must have a VMEM-feasible tile (the chunked XLA
    scan serves the rest)."""
    bv_f = _pick_bv(hidden, False)
    bv_b = _pick_bv(hidden, True)
    return (n_tokens % BLOCK_T == 0 and hidden % 128 == 0
            and bv_f > 0 and bv_b > 0 and vocab >= bv_f)


def _fwd_kernel(x_ref, h_ref, lab_ref, nll_ref, lse_ref, m_sc, l_sc, g_sc,
                *, bv, vocab, n_v):
    import jax.experimental.pallas as pl

    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_sc[0, :] = jnp.full_like(m_sc[0, :], -1e30)
        l_sc[0, :] = jnp.zeros_like(l_sc[0, :])
        g_sc[0, :] = jnp.zeros_like(g_sc[0, :])

    x = x_ref[...]                                     # [bt, H] bf16
    col = iv * bv + jax.lax.iota(jnp.int32, bv)
    # the head array is zero-padded to whole tiles by the wrappers, so the
    # tail logits are exactly 0 — push them to -1e30 so they cannot
    # contribute to logsumexp.
    # NOTE: rank-1 select + broadcast arithmetic, NOT jnp.where with a
    # broadcast [None, :] condition — the latter trips an internal Mosaic
    # lowering bug on v5e when combined with the online-softmax carry.
    h = h_ref[...]
    labels = lab_ref[0, :]                             # [bt] int32
    logits = jnp.dot(x, h, preferred_element_type=jnp.float32)
    logits = logits + jnp.where(col < vocab, 0.0, -1e30)[None, :]

    m_prev = m_sc[0, :]
    l_prev = l_sc[0, :]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    l_new = l_prev * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(logits - m_new[:, None]), axis=1)
    m_sc[0, :] = m_new
    l_sc[0, :] = l_new
    # gold logit: exact value, no running max needed
    eq = (labels[:, None] == col[None, :])
    g_sc[0, :] = g_sc[0, :] + jnp.sum(jnp.where(eq, logits, 0.0), axis=1)

    @pl.when(iv == n_v - 1)
    def _fin():
        lse = m_sc[0, :] + jnp.log(l_sc[0, :])
        lse_ref[...] = jnp.broadcast_to(lse[None, :], lse_ref.shape)
        nll_ref[...] = jnp.broadcast_to((lse - g_sc[0, :])[None, :],
                                        nll_ref.shape)


def _bwd_dx_kernel(h_ref, x_ref, lab_ref, lse_ref, g_ref, dx_ref,
                   *, bv, vocab):
    import jax.experimental.pallas as pl

    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref[...])

    x = x_ref[...]
    col = iv * bv + jax.lax.iota(jnp.int32, bv)
    h = h_ref[...]                                     # [H, bv] zero-padded
    labels = lab_ref[0, :]
    lse = lse_ref[0, :]
    gcot = g_ref[0, :]                                 # [bt] f32
    logits = jnp.dot(x, h, preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse[:, None])
    p = p * (col < vocab).astype(jnp.float32)[None, :]
    eq = (labels[:, None] == col[None, :]).astype(jnp.float32)
    dl = (p - eq) * gcot[:, None]                      # [bt, bv] f32
    # contract dl's vocab dim with h's vocab dim directly (dl @ h.T
    # without materializing a transpose — VMEM is the scarce resource)
    dx_ref[...] = dx_ref[...] + jax.lax.dot_general(
        dl.astype(x.dtype), h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_dh_kernel(x_ref, h_ref, lab_ref, lse_ref, g_ref, dh_ref,
                   *, bv, vocab, n_t):
    import jax.experimental.pallas as pl

    iv = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref[...])

    x = x_ref[...]                                     # [bt, H]
    col = iv * bv + jax.lax.iota(jnp.int32, bv)
    h = h_ref[...]                                     # [H, bv] zero-padded
    labels = lab_ref[0, :]
    lse = lse_ref[0, :]
    gcot = g_ref[0, :]
    logits = jnp.dot(x, h, preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse[:, None])
    p = p * (col < vocab).astype(jnp.float32)[None, :]
    eq = (labels[:, None] == col[None, :]).astype(jnp.float32)
    dl = (p - eq) * gcot[:, None]
    # x.T @ dl via dim-0 contraction, no transpose materialization
    dh_ref[...] = dh_ref[...] + jax.lax.dot_general(
        x, dl.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _pad_head(head, v_padded: int):
    """Zero-pad the vocab dim to whole tiles: in-kernel masking of a
    partial tile cannot scrub uninitialized reads (0 * NaN = NaN), so the
    kernels require fully-defined operands."""
    V = head.shape[1]
    if v_padded == V:
        return head
    return jnp.pad(head, ((0, 0), (0, v_padded - V)))


def _pack8(a):
    """[n, bt] -> [n, 8, bt]: Mosaic needs >=2-D blocks with second-minor
    divisible by 8, so small per-token vectors ride 8-row broadcast."""
    return jnp.broadcast_to(a[:, None, :], (a.shape[0], 8, a.shape[1]))


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


@functools.partial(jax.jit, static_argnames=("bv",))
def _fused_ce_fwd(x, head, labels, bv: int = 0):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H = x.shape
    V = head.shape[1]
    bt, bv = BLOCK_T, (bv or _pick_bv(H, False))
    if bv <= 0:
        raise ValueError(f"fused CE fwd has no VMEM-feasible tile for "
                         f"hidden={H}; gate with fused_ce_supported()")
    n_t, n_v = N // bt, _cdiv(V, bv)
    headp = _pad_head(head, n_v * bv)
    lab2 = _pack8(labels.reshape(n_t, bt).astype(jnp.int32))

    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, vocab=V, n_v=n_v),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, H), lambda it, iv: (it, 0)),
            pl.BlockSpec((H, bv), lambda it, iv: (0, iv)),
            pl.BlockSpec((None, 8, bt), lambda it, iv: (it, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((None, 8, bt), lambda it, iv: (it, 0, 0)),
                   pl.BlockSpec((None, 8, bt), lambda it, iv: (it, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_t, 8, bt), jnp.float32),
                   jax.ShapeDtypeStruct((n_t, 8, bt), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((8, bt), jnp.float32)] * 3,
        interpret=_interpret_mode(),
        compiler_params=_tpu_params(1),
        name="fused_ce_fwd",
    )(x, headp, lab2)
    return nll[:, 0, :].reshape(N), lse[:, 0, :].reshape(N)


@functools.partial(jax.jit, static_argnames=("bv",))
def _fused_ce_bwd(x, head, labels, lse, g, bv: int = 0):
    import jax.experimental.pallas as pl

    N, H = x.shape
    V = head.shape[1]
    bt, bv = BLOCK_T, (bv or _pick_bv(H, True))
    if bv <= 0:
        raise ValueError(f"fused CE bwd has no VMEM-feasible tile for "
                         f"hidden={H}; gate with fused_ce_supported()")
    n_t, n_v = N // bt, _cdiv(V, bv)
    headp = _pad_head(head, n_v * bv)
    lab2 = _pack8(labels.reshape(n_t, bt).astype(jnp.int32))
    lse2 = _pack8(lse.reshape(n_t, bt))
    g2 = _pack8(g.reshape(n_t, bt).astype(jnp.float32))

    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, bv=bv, vocab=V),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((H, bv), lambda it, iv: (0, iv)),
            pl.BlockSpec((bt, H), lambda it, iv: (it, 0)),
            pl.BlockSpec((None, 8, bt), lambda it, iv: (it, 0, 0)),
            pl.BlockSpec((None, 8, bt), lambda it, iv: (it, 0, 0)),
            pl.BlockSpec((None, 8, bt), lambda it, iv: (it, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, H), lambda it, iv: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H), jnp.float32),
        interpret=_interpret_mode(),
        compiler_params=_tpu_params(1),
        name="fused_ce_bwd_dx",
    )(headp, x, lab2, lse2, g2)

    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, bv=bv, vocab=V, n_t=n_t),
        grid=(n_v, n_t),
        in_specs=[
            pl.BlockSpec((bt, H), lambda iv, it: (it, 0)),
            pl.BlockSpec((H, bv), lambda iv, it: (0, iv)),
            pl.BlockSpec((None, 8, bt), lambda iv, it: (it, 0, 0)),
            pl.BlockSpec((None, 8, bt), lambda iv, it: (it, 0, 0)),
            pl.BlockSpec((None, 8, bt), lambda iv, it: (it, 0, 0)),
        ],
        out_specs=pl.BlockSpec((H, bv), lambda iv, it: (0, iv)),
        out_shape=jax.ShapeDtypeStruct((H, n_v * bv), jnp.float32),
        interpret=_interpret_mode(),
        compiler_params=_tpu_params(1),
        name="fused_ce_bwd_dh",
    )(x, headp, lab2, lse2, g2)

    return dx.astype(x.dtype), dh[:, :V].astype(head.dtype)


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_fwd_kernel, _bwd_dx_kernel,
                                    _bwd_dh_kernel)
    return _SRC


def _tuned_bv(N: int, H: int, V: int, dtype, is_bwd: bool) -> int:
    """Vocab tile via the autotune registry; candidates[0] is _pick_bv's
    largest-feasible hand default, so no-sweep backends keep legacy
    behavior.  Smaller tiles can win on real chips: the last partial
    vocab tile wastes less MXU work and the fwd/bwd optima differ."""
    from . import autotune

    default = _pick_bv(H, is_bwd)
    if default <= 0:
        return 0
    cands = [default] + [bv for bv in _BV_LADDER
                         if bv != default and V >= bv
                         and _bv_feasible(H, bv, is_bwd)]
    if len(cands) < 2:
        return default

    def measure(bv):
        xz = jnp.zeros((N, H), dtype)
        hz = jnp.zeros((H, V), dtype)
        lz = jnp.zeros((N,), jnp.int32)
        if is_bwd:
            lsez = jnp.zeros((N,), jnp.float32)
            gz = jnp.ones((N,), jnp.float32)
            fn = lambda: _fused_ce_bwd(xz, hz, lz, lsez, gz,  # noqa: E731
                                       bv=int(bv))
        else:
            fn = lambda: _fused_ce_fwd(xz, hz, lz, bv=int(bv))  # noqa: E731
        return autotune.time_candidate(fn)

    kernel = "fused_ce_bwd" if is_bwd else "fused_ce_fwd"
    return int(autotune.tuned(kernel, f"n{N}_h{H}_v{V}",
                              str(jnp.dtype(dtype)), cands, measure=measure,
                              source=_autotune_source()))


def fused_softmax_ce(x, head, labels):
    """Per-token cross-entropy nll [N] (fp32) of softmax(x @ head) vs
    ``labels`` — differentiable w.r.t. x and head, O(bt*bv) live logits.

    x [N, H] (compute dtype), head [H, V], labels [N] int.
    """
    N, H = x.shape
    V = head.shape[1]
    # trace-time choice, like the flash blocks: baked into the jitted
    # wrappers as static args
    bv_f = _tuned_bv(N, H, V, x.dtype, is_bwd=False)
    bv_b = _tuned_bv(N, H, V, x.dtype, is_bwd=True)

    @jax.custom_vjp
    def ce(x, head, labels):
        nll, _ = _fused_ce_fwd(x, head, labels, bv=bv_f)
        return nll

    def fwd(x, head, labels):
        nll, lse = _fused_ce_fwd(x, head, labels, bv=bv_f)
        return nll, (x, head, labels, lse)

    def bwd(res, g):
        x, head, labels, lse = res
        dx, dh = _fused_ce_bwd(x, head, labels, lse, g, bv=bv_b)
        return dx, dh, None

    ce.defvjp(fwd, bwd)
    return ce(x, head, labels)
