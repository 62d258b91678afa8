"""Flash attention as a Pallas TPU kernel.

TPU-native replacement for the reference's dynloaded flash-attn v2 CUDA
library (paddle/phi/kernels/gpu/flash_attn_kernel.cu:132,
paddle/phi/backends/dynload/flashattn.h): an online-softmax blocked
attention that never materializes the [S, S] score matrix, tiled to the
MXU (128-lane) with fp32 running max/sum accumulators.

Layout contract matches the reference flash_attn API: q/k/v are
[batch, seq, num_heads, head_dim].

Two kernel layouts (round 3):

- **native** (default): kernels read/write the model's (b, s, h, d)
  layout through a free (b, s, h*d) reshape — 2-D [block, hp*d] blocks
  whose lane width is always a 128-multiple (hp heads per program; for
  d=64, hp=2 and per-head access is a rank-preserving static lane
  slice). This removes the (b,s,h,d)<->(b,h,s,d) transpose copies that
  cost ~20 ms/step at 350m/b8 (PERF.md round-2 table). Mosaic's
  last-two-block-dims rule (divisible by (8, 128) or equal to the array
  dim) rules out blocking h directly in second-minor position — hence
  the lane-fused view.
- **transpose** (FLAGS_flash_attention_native_layout=0): the round-2
  kernels on swapaxes'd [b, h, s, d] arrays, kept for A/B measurement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.dispatch import op

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# fwd and bwd kernels accumulate every dot in fp32.
ACCUM_DTYPE = "float32"

_INTERPRET = None  # resolved lazily: True on the CPU backend, False on TPU


def _interpret_mode() -> bool:
    """Pallas interpret mode iff the backend is the CPU (tests, lint).
    Every kernel file imports this one gate.  A backend that is neither
    cpu nor tpu is an error: interpreting there would report the
    interpreter's numbers under a device's name."""
    global _INTERPRET
    if _INTERPRET is None:
        backend = jax.default_backend()
        if backend not in ("cpu", "tpu"):
            raise RuntimeError(
                f"paddle_tpu Pallas kernels run compiled on 'tpu' and "
                f"interpreted on 'cpu'; backend is '{backend}'")
        _INTERPRET = backend == "cpu"
    return _INTERPRET


def _tpu_params(n_parallel: int):
    """CompilerParams marking the leading ``n_parallel`` grid dims
    parallel and the ONE remaining dim arbitrary, so Mosaic pipelines
    across grid steps.  Every caller's grid rank is n_parallel + 1
    (Mosaic rejects a dimension_semantics tuple of any other length)."""
    if _interpret_mode():
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))


def single_device_program() -> bool:
    """True when the program being traced runs on one device: no ambient
    mesh (a plain jit) or an ambient mesh of one device.  A pallas
    custom call has no GSPMD partitioning rule, so kernels that are not
    wrapped in a shard_map gate on this — the mesh the step was built on
    (parallel/train_step.py sets it), never the host's device count."""
    am = jax.sharding.get_abstract_mesh()
    return am.empty or am.size == 1


# Row-block heights for the row-streaming epilogue kernels
# (fused_norm_epilogue / fused_bias_act): 256 first — the hand default
# wherever it fits — then larger, then the smaller blocks wide rows need
# (one [bt, width] block must fit VMEM whole; 16 is the bf16 sublane
# tile).  Row counts stay 256-aligned whatever block is picked.
_ROW_BLOCKS = (256, 512, 1024, 128, 64, 32, 16)
# VMEM cap for one row block's working set (operands and results double
# buffered plus the kernel's fp32 temporaries) of the ~16 MB per core.
_ROW_BLOCK_VMEM = 8 * 2 ** 20


def _row_blocks(n: int, row_bytes: int) -> list[int]:
    """Up to three row-block candidates, preferred first, for ``n``
    (256-aligned) rows whose working set is ``row_bytes`` per row; empty
    when the kernel cannot take the shape."""
    if n % _ROW_BLOCKS[0]:
        return []
    return [bt for bt in _ROW_BLOCKS
            if n % bt == 0 and bt * row_bytes <= _ROW_BLOCK_VMEM][:3]


# Default tile-size caps. Measured on v5e at GPT-350M shapes (B8 S1024 H16
# D64): 128x128 runs at ~60% the speed of big tiles — bigger q tiles
# amortize the K/V VMEM residency and keep the MXU fed. block_k == block_q
# so causal skipping works at block granularity: with block_k = S every q
# tile would process the full K range and the causal loop cap saves
# nothing. _block_sizes() picks the largest 128-multiple divisor of the
# sequence length under these caps, so any seq divisible by 128 gets the
# Pallas path.
BLOCK_Q = 512
BLOCK_K = 512
# Heads processed per grid program in the transpose layout (static
# unrolled loop in the kernels): amortizes the per-grid-step latency and
# enlarges DMAs.
HEAD_BLOCK = 4

_MIN_BLOCK = 128


def _divisor_block(s: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``s`` and is <= cap (0 if none)."""
    b = min(cap, s)
    b -= b % _MIN_BLOCK
    while b >= _MIN_BLOCK and s % b:
        b -= _MIN_BLOCK
    return b


def _block_sizes(s: int) -> tuple[int, int]:
    return _divisor_block(s, BLOCK_Q), _divisor_block(s, BLOCK_K)


def supported(shape, dtype) -> bool:
    """Pallas path needs 128-aligned blocks dividing seq and a MXU-friendly
    head dim."""
    if len(shape) != 4:
        return False
    _, s, _, d = shape
    bq, bk = _block_sizes(s)
    return bq >= _MIN_BLOCK and bk >= _MIN_BLOCK and d in (64, 128, 256)


def _head_block(h: int) -> int:
    """Largest divisor of ``h`` that is <= HEAD_BLOCK."""
    hb = min(HEAD_BLOCK, h)
    while h % hb:
        hb -= 1
    return hb


def _heads_per_program(h: int, d: int) -> int:
    """Native layout: heads fused per program so the 2-D block lane width
    hp*d is a 128-multiple (d=64 -> 2, d>=128 -> 1)."""
    return max(1, 128 // d)


def _native_supported(h: int, d: int) -> bool:
    hp = _heads_per_program(h, d)
    return h % hp == 0 and (hp * d) % 128 == 0


def _causal_bounds(q_idx, bq, block_k, seq_len, causal):
    """(num_full_blocks, num_k_blocks): k blocks entirely below the
    diagonal need no mask; blocks crossing it do; blocks above are
    skipped outright."""
    num_k_blocks = seq_len // block_k
    num_full_blocks = num_k_blocks
    if causal:
        num_full_blocks = jax.lax.div(q_idx * bq, block_k)
        num_k_blocks = jax.lax.div((q_idx + 1) * bq + block_k - 1, block_k)
    return num_full_blocks, num_k_blocks


# ---------------------------------------------------------------------------
# native-layout kernels: (b, s, h*d) views, 2-D blocks, hp heads/program
# ---------------------------------------------------------------------------


def _flash_fwd_kernel_native(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *,
                             causal, sm_scale, block_k, seq_len, hp, d):
    import jax.experimental.pallas as pl

    q_idx = pl.program_id(2)
    bq = q_ref.shape[0]
    q_offs = q_idx * bq + jax.lax.iota(jnp.int32, bq)
    num_full_blocks, num_k_blocks = _causal_bounds(q_idx, bq, block_k,
                                                   seq_len, causal)

    ql = q_ref[...]                                 # [bq, hp*d]
    outs = []
    for j in range(hp):
        # per-head lane slice (rank-preserving; for d>=128, hp=1 and this
        # is the whole block). Keep q/k in their input dtype (bf16 on
        # TPU): the MXU runs bf16 inputs with fp32 accumulation at full
        # rate, while fp32xfp32 dots run ~8x slower.
        q = ql[:, j * d:(j + 1) * d]                # [bq, d]

        m_i = jnp.full((bq,), -1e30, jnp.float32)
        l_i = jnp.zeros((bq,), jnp.float32)
        acc = jnp.zeros((bq, d), jnp.float32)

        def body(kb, carry, *, masked, j=j, q=q):
            m_i, l_i, acc = carry
            k = k_ref[pl.dslice(kb * block_k, block_k),
                      j * d:(j + 1) * d]            # [bk, d]
            v = v_ref[pl.dslice(kb * block_k, block_k),
                      j * d:(j + 1) * d]
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
                                          functools.partial(body,
                                                            masked=causal),
                                          carry)
        outs.append((acc / l_i[:, None]).astype(o_ref.dtype))
        if lse_ref is not None:
            lse_ref[j] = jnp.broadcast_to((m_i + jnp.log(l_i))[None, :],
                                          lse_ref.shape[1:])
    o_ref[...] = outs[0] if hp == 1 else jnp.concatenate(outs, axis=1)


def _flash_bwd_dq_kernel_native(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, dq_ref, *, causal, sm_scale,
                                block_k, seq_len, hp, d):
    import jax.experimental.pallas as pl

    q_idx = pl.program_id(2)
    bq = q_ref.shape[0]
    q_offs = q_idx * bq + jax.lax.iota(jnp.int32, bq)
    num_full_blocks, num_k_blocks = _causal_bounds(q_idx, bq, block_k,
                                                   seq_len, causal)

    ql = q_ref[...]                                  # [bq, hp*d]
    dol = do_ref[...]
    outs = []
    for j in range(hp):
        q = ql[:, j * d:(j + 1) * d]
        do = dol[:, j * d:(j + 1) * d]
        lse = lse_ref[j, 0, :]                       # [bq] (8-row packed)
        delta = delta_ref[j, 0, :]

        def body(kb, dq, *, masked, j=j, q=q, do=do, lse=lse, delta=delta):
            k = k_ref[pl.dslice(kb * block_k, block_k), j * d:(j + 1) * d]
            v = v_ref[pl.dslice(kb * block_k, block_k), j * d:(j + 1) * d]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            p = jnp.exp(s - lse[:, None])
            if masked:
                k_offs = kb * block_k + jax.lax.iota(jnp.int32, block_k)
                p = jnp.where(q_offs[:, None] >= k_offs[None, :], p, 0.0)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(k.dtype)
            return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, num_full_blocks,
                               functools.partial(body, masked=False),
                               jnp.zeros((bq, d), jnp.float32))
        dq = jax.lax.fori_loop(num_full_blocks, num_k_blocks,
                               functools.partial(body, masked=causal), dq)
        outs.append((dq * sm_scale).astype(dq_ref.dtype))
    dq_ref[...] = outs[0] if hp == 1 else jnp.concatenate(outs, axis=1)


def _flash_bwd_dkv_kernel_native(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                 delta_ref, dk_ref, dv_ref, *, causal,
                                 sm_scale, block_q, seq_len, hp, d):
    import jax.experimental.pallas as pl

    k_idx = pl.program_id(2)
    bk = k_ref.shape[0]
    k_offs = k_idx * bk + jax.lax.iota(jnp.int32, bk)

    num_q_blocks = seq_len // block_q
    start_q = 0
    # q blocks from start_q up to end_masked cross the diagonal (need the
    # mask); from end_masked on, every q in the tile sees every k.
    end_masked = 0
    if causal:
        start_q = jax.lax.div(k_idx * bk, block_q)
        end_masked = jax.lax.min(
            jax.lax.div((k_idx + 1) * bk + block_q - 1, block_q),
            num_q_blocks)

    kl = k_ref[...]                                  # [bk, hp*d]
    vl = v_ref[...]
    dks, dvs = [], []
    for j in range(hp):
        k = kl[:, j * d:(j + 1) * d]
        v = vl[:, j * d:(j + 1) * d]

        def body(qb, carry, *, masked, j=j, k=k, v=v):
            dk, dv = carry
            q = q_ref[pl.dslice(qb * block_q, block_q), j * d:(j + 1) * d]
            do = do_ref[pl.dslice(qb * block_q, block_q), j * d:(j + 1) * d]
            lse = lse_ref[j, 0, pl.dslice(qb * block_q, block_q)]
            delta = delta_ref[j, 0, pl.dslice(qb * block_q, block_q)]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            p = jnp.exp(s - lse[:, None])
            if masked:
                q_offs = qb * block_q + jax.lax.iota(jnp.int32, block_q)
                p = jnp.where(q_offs[:, None] >= k_offs[None, :], p, 0.0)
            p_lo = p.astype(do.dtype)
            dv_new = dv + jnp.dot(p_lo.T, do,
                                  preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(q.dtype)
            dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
            return dk_new, dv_new

        zero = (jnp.zeros((bk, d), jnp.float32),
                jnp.zeros((bk, d), jnp.float32))
        dk, dv = jax.lax.fori_loop(start_q, end_masked,
                                   functools.partial(body, masked=causal),
                                   zero)
        dk, dv = jax.lax.fori_loop(jax.lax.max(start_q, end_masked),
                                   num_q_blocks,
                                   functools.partial(body, masked=False),
                                   (dk, dv))
        # s was scaled but dk accumulated against unscaled q: scale once.
        dks.append((dk * sm_scale).astype(dk_ref.dtype))
        dvs.append(dv.astype(dv_ref.dtype))
    dk_ref[...] = dks[0] if hp == 1 else jnp.concatenate(dks, axis=1)
    dv_ref[...] = dvs[0] if hp == 1 else jnp.concatenate(dvs, axis=1)


def _flash_bwd_fused_kernel_native(qkv_qblk_ref, qkv_kfull_ref,
                                   qkv_vfull_ref, qkv_kblk_ref,
                                   qkv_vblk_ref, qkv_qfull_ref,
                                   do_blk_ref, do_full_ref, lse_blk_ref,
                                   delta_blk_ref, lse_full_ref,
                                   delta_full_ref, dqkv_ref, *, causal,
                                   sm_scale, block, seq_len, hp, d):
    """Merged backward for the FUSED qkv path: one program computes dq
    for its sequence block (k-loop) AND dk/dv for the same block
    (q-loop), writing all three into one [block, 3, hp*d] tile of the
    dqkv cotangent — the concatenate of the split path (~192 MB of HBM
    traffic per layer at b16) never happens. Under causal the two loops
    are complementary (dq touches blocks <= i, dkv touches >= i), so
    per-program work is uniform across the grid."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    bq = block
    q_offs = i * bq + jax.lax.iota(jnp.int32, bq)
    k_offs_self = q_offs                     # same seq block for dk/dv
    num_full_blocks, num_k_blocks = _causal_bounds(i, bq, block, seq_len,
                                                   causal)
    num_q_blocks = seq_len // block
    start_q = 0
    end_masked = 0
    if causal:
        start_q = i
        end_masked = jax.lax.min(i + 1, num_q_blocks)

    ql = qkv_qblk_ref[...]                   # [bq, hp*d]
    dol = do_blk_ref[...]
    kl = qkv_kblk_ref[...]
    vl = qkv_vblk_ref[...]
    dq_outs, dk_outs, dv_outs = [], [], []
    for j in range(hp):
        # ---- dq for this q block: loop k blocks ----------------------
        q = ql[:, j * d:(j + 1) * d]
        do = dol[:, j * d:(j + 1) * d]
        lse = lse_blk_ref[j, 0, :]
        delta = delta_blk_ref[j, 0, :]

        def dq_body(kb, dq, *, masked, j=j, q=q, do=do, lse=lse,
                    delta=delta):
            k = qkv_kfull_ref[pl.dslice(kb * block, block),
                              j * d:(j + 1) * d]
            v = qkv_vfull_ref[pl.dslice(kb * block, block),
                              j * d:(j + 1) * d]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
                * sm_scale
            p = jnp.exp(s - lse[:, None])
            if masked:
                k_offs = kb * block + jax.lax.iota(jnp.int32, block)
                p = jnp.where(q_offs[:, None] >= k_offs[None, :], p, 0.0)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(k.dtype)
            return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, num_full_blocks,
                               functools.partial(dq_body, masked=False),
                               jnp.zeros((bq, d), jnp.float32))
        dq = jax.lax.fori_loop(num_full_blocks, num_k_blocks,
                               functools.partial(dq_body, masked=causal),
                               dq)
        dq_outs.append((dq * sm_scale).astype(dqkv_ref.dtype))

        # ---- dk/dv for the SAME seq block: loop q blocks -------------
        k = kl[:, j * d:(j + 1) * d]
        v = vl[:, j * d:(j + 1) * d]

        def dkv_body(qb, carry, *, masked, j=j, k=k, v=v):
            dk, dv = carry
            q = qkv_qfull_ref[pl.dslice(qb * block, block),
                              j * d:(j + 1) * d]
            do = do_full_ref[pl.dslice(qb * block, block),
                             j * d:(j + 1) * d]
            lse = lse_full_ref[j, 0, pl.dslice(qb * block, block)]
            delta = delta_full_ref[j, 0, pl.dslice(qb * block, block)]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
                * sm_scale
            p = jnp.exp(s - lse[:, None])
            if masked:
                q_offs2 = qb * block + jax.lax.iota(jnp.int32, block)
                p = jnp.where(q_offs2[:, None] >= k_offs_self[None, :],
                              p, 0.0)
            p_lo = p.astype(do.dtype)
            dv_new = dv + jnp.dot(p_lo.T, do,
                                  preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(q.dtype)
            dk_new = dk + jnp.dot(ds.T, q,
                                  preferred_element_type=jnp.float32)
            return dk_new, dv_new

        zero = (jnp.zeros((bq, d), jnp.float32),
                jnp.zeros((bq, d), jnp.float32))
        dk, dv = jax.lax.fori_loop(start_q, end_masked,
                                   functools.partial(dkv_body,
                                                     masked=causal), zero)
        dk, dv = jax.lax.fori_loop(jax.lax.max(start_q, end_masked),
                                   num_q_blocks,
                                   functools.partial(dkv_body,
                                                     masked=False),
                                   (dk, dv))
        dk_outs.append((dk * sm_scale).astype(dqkv_ref.dtype))
        dv_outs.append(dv.astype(dqkv_ref.dtype))

    dq_t = dq_outs[0] if hp == 1 else jnp.concatenate(dq_outs, axis=1)
    dk_t = dk_outs[0] if hp == 1 else jnp.concatenate(dk_outs, axis=1)
    dv_t = dv_outs[0] if hp == 1 else jnp.concatenate(dv_outs, axis=1)
    # integer index on the middle ref dim = plain offset store (the
    # value-slicing Mosaic hazards in PERF.md don't apply to ref stores)
    dqkv_ref[:, 0, :] = dq_t
    dqkv_ref[:, 1, :] = dk_t
    dqkv_ref[:, 2, :] = dv_t


def _fused_dqkv_ok(s: int, hd: int, itemsize: int = 2,
                   block: int | None = None) -> bool:
    """Merged-kernel gate: one program holds FOUR full-sequence slabs
    (k, v, q, do at [s, hp*d]) plus blocks, lse/delta rows, and fp32
    accumulators; cap the slab set at 6 MB of the ~16 MB v5e VMEM.
    Measured: a 4 MB slab set (1.3B, S=4096, d=128) compiles and runs;
    an 8 MB slab set (S=8192, d=128) hits Mosaic's scoped-vmem limit at
    18 MB total — the non-slab overhead is ~10 MB at that scale, so the
    8 MB cap round 5 started with was too permissive. Larger configs
    take the split two-kernel path (2 slabs each). ``block`` overrides
    the default square block (autotuned callers)."""
    bq, bk = (block, block) if block else _block_sizes(s)
    return bq == bk and bq >= _MIN_BLOCK \
        and 4 * s * hd * itemsize <= 6 * 2 ** 20


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(
            _flash_fwd_kernel_native, _flash_bwd_dq_kernel_native,
            _flash_bwd_dkv_kernel_native, _flash_bwd_fused_kernel_native)
    return _SRC


def _tuned_blocks(b: int, s: int, h: int, d: int, dtype, causal: bool,
                  n_heads: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) via the autotune registry (ops/pallas/autotune.py).

    candidates[0] is the hand default (_block_sizes caps at 512), so CPU
    and no-sweep runs keep the legacy behavior bit-for-bit; on TPU the
    first use of a (shape-bucket, dtype, device-kind) sweeps square
    512/256/1024 alternatives on the native forward and persists the
    winner.  Called from the raw entries (trace time, outside the jitted
    wrappers) so the choice is baked in as a static arg — the same
    contract as the flash flags."""
    from . import autotune

    default = _block_sizes(s)
    if min(default) < _MIN_BLOCK or not _native_supported(h, d):
        return default
    cands = [list(default)]
    for c in (512, 256, 1024):
        if c <= s and s % c == 0 and [c, c] not in cands:
            cands.append([c, c])

    def measure(cand):
        bq, bk = int(cand[0]), int(cand[1])
        if n_heads is not None:
            qz = jnp.zeros((b, s, 3 * n_heads * d), dtype)
            fn = lambda: _flash_fwd(qz, None, None, causal, 1.0,  # noqa: E731
                                    with_lse=True, n_heads=n_heads,
                                    block_q=bq, block_k=bk)
        else:
            qz = jnp.zeros((b, s, h, d), dtype)
            fn = lambda: _flash_fwd(qz, qz, qz, causal, 1.0,  # noqa: E731
                                    with_lse=True, block_q=bq, block_k=bk)
        return autotune.time_candidate(fn)

    bucket = (f"b{b}_s{s}_h{h}_d{d}_c{int(causal)}"
              + ("_qkv" if n_heads is not None else ""))
    cfg = autotune.tuned("flash_attention", bucket, str(jnp.dtype(dtype)),
                         cands, measure=measure, source=_autotune_source())
    return int(cfg[0]), int(cfg[1])


# ---------------------------------------------------------------------------
# transpose-layout kernels (round 2; FLAGS_flash_attention_native_layout=0)
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, causal,
                      sm_scale, block_k, seq_len, head_block):
    import jax.experimental.pallas as pl

    q_idx = pl.program_id(2)
    bq = q_ref.shape[1]
    q_offs = q_idx * bq + jax.lax.iota(jnp.int32, bq)
    num_full_blocks, num_k_blocks = _causal_bounds(q_idx, bq, block_k,
                                                   seq_len, causal)

    # Static python loop over the head block: one grid program handles
    # head_block heads, amortizing the per-program grid-step latency
    # (measured ~60us/program on v5e regardless of block size).
    for i in range(head_block):
        # Keep q/k in their input dtype (bf16 on TPU): the MXU runs bf16
        # inputs with fp32 accumulation at full rate, while fp32xfp32 dots
        # run ~8x slower.
        q = q_ref[i]  # [block_q, d]

        m_i = jnp.full((bq,), -1e30, jnp.float32)
        l_i = jnp.zeros((bq,), jnp.float32)
        acc = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)

        def body(kb, carry, *, masked, i=i):
            m_i, l_i, acc = carry
            k = k_ref[i, pl.dslice(kb * block_k, block_k), :]
            v = v_ref[i, pl.dslice(kb * block_k, block_k), :]
            s = jnp.dot(q, k.T,
                        preferred_element_type=jnp.float32) * sm_scale
            if masked:
                k_offs = kb * block_k + jax.lax.iota(jnp.int32, block_k)
                mask = q_offs[:, None] >= k_offs[None, :]
                s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_i - m_new)
            l_new = alpha * l_i + jnp.sum(p, axis=1)
            acc_new = acc * alpha[:, None] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            return m_new, l_new, acc_new

        carry = jax.lax.fori_loop(0, num_full_blocks,
                                  functools.partial(body, masked=False),
                                  (m_i, l_i, acc))
        m_i, l_i, acc = jax.lax.fori_loop(num_full_blocks, num_k_blocks,
                                          functools.partial(body,
                                                            masked=causal),
                                          carry)
        o_ref[i] = (acc / l_i[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[i] = jnp.broadcast_to((m_i + jnp.log(l_i))[None, :],
                                          lse_ref.shape[1:])


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, causal, sm_scale, block_k, seq_len,
                         head_block):
    import jax.experimental.pallas as pl

    q_idx = pl.program_id(2)
    bq = q_ref.shape[1]
    d = q_ref.shape[-1]
    q_offs = q_idx * bq + jax.lax.iota(jnp.int32, bq)
    num_full_blocks, num_k_blocks = _causal_bounds(q_idx, bq, block_k,
                                                   seq_len, causal)

    # All dots stay in the input dtype (bf16 on TPU) with fp32 accumulation;
    # softmax math (exp, ds) stays fp32. Static head-block loop as in fwd.
    for i in range(head_block):
        q = q_ref[i]                                   # [bq, d]
        do = do_ref[i]                                 # [bq, d]
        lse = lse_ref[i, 0, :]                         # [bq] (8-row packed)
        delta = delta_ref[i, 0, :]

        def body(kb, dq, *, masked, i=i, q=q, do=do, lse=lse, delta=delta):
            k = k_ref[i, pl.dslice(kb * block_k, block_k), :]
            v = v_ref[i, pl.dslice(kb * block_k, block_k), :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            p = jnp.exp(s - lse[:, None])
            if masked:
                k_offs = kb * block_k + jax.lax.iota(jnp.int32, block_k)
                p = jnp.where(q_offs[:, None] >= k_offs[None, :], p, 0.0)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(k.dtype)
            return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, num_full_blocks,
                               functools.partial(body, masked=False),
                               jnp.zeros((bq, d), jnp.float32))
        dq = jax.lax.fori_loop(num_full_blocks, num_k_blocks,
                               functools.partial(body, masked=causal), dq)
        dq_ref[i] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, causal, sm_scale, block_q,
                          seq_len, head_block):
    import jax.experimental.pallas as pl

    k_idx = pl.program_id(2)
    bk = k_ref.shape[1]
    d = k_ref.shape[-1]
    k_offs = k_idx * bk + jax.lax.iota(jnp.int32, bk)

    num_q_blocks = seq_len // block_q
    start_q = 0
    # q blocks from start_q up to end_masked cross the diagonal (need the
    # mask); from end_masked on, every q in the tile sees every k.
    end_masked = 0
    if causal:
        start_q = jax.lax.div(k_idx * bk, block_q)
        end_masked = jax.lax.min(
            jax.lax.div((k_idx + 1) * bk + block_q - 1, block_q),
            num_q_blocks)

    # bf16 dots / fp32 accumulators; static head-block loop as in fwd.
    for i in range(head_block):
        k = k_ref[i]                                   # [bk, d]
        v = v_ref[i]

        def body(qb, carry, *, masked, i=i, k=k, v=v):
            dk, dv = carry
            q = q_ref[i, pl.dslice(qb * block_q, block_q), :]
            do = do_ref[i, pl.dslice(qb * block_q, block_q), :]
            lse = lse_ref[i, 0, pl.dslice(qb * block_q, block_q)]
            delta = delta_ref[i, 0, pl.dslice(qb * block_q, block_q)]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            p = jnp.exp(s - lse[:, None])
            if masked:
                q_offs = qb * block_q + jax.lax.iota(jnp.int32, block_q)
                p = jnp.where(q_offs[:, None] >= k_offs[None, :], p, 0.0)
            p_lo = p.astype(do.dtype)
            dv_new = dv + jnp.dot(p_lo.T, do,
                                  preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(q.dtype)
            dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
            return dk_new, dv_new

        zero = (jnp.zeros((bk, d), jnp.float32),
                jnp.zeros((bk, d), jnp.float32))
        dk, dv = jax.lax.fori_loop(start_q, end_masked,
                                   functools.partial(body, masked=causal),
                                   zero)
        dk, dv = jax.lax.fori_loop(jax.lax.max(start_q, end_masked),
                                   num_q_blocks,
                                   functools.partial(body, masked=False),
                                   (dk, dv))
        # s was scaled but dk accumulated against unscaled q: scale once.
        dk_ref[i] = (dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[i] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# jit wrappers
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "with_lse", "native",
                                             "n_heads", "block_q",
                                             "block_k"))
def _flash_fwd(q, k, v, causal: bool, sm_scale: float, with_lse: bool = False,
               native: bool = True, n_heads: int | None = None,
               block_q: int | None = None, block_k: int | None = None):
    """``n_heads`` set => FUSED input mode: q IS the whole (b, s, 3*h*d)
    qkv projection output (k and v must be None) and the kernels read
    q/k/v through lane-block-offset index maps — the 3-way split copies
    (~96 MB/layer at 350m/b16) never materialize.  ``block_q``/``block_k``
    override the hand defaults (autotuned callers pass _tuned_blocks)."""
    import jax.experimental.pallas as pl

    fused = n_heads is not None
    if fused:
        b, s, hd3 = q.shape
        h = n_heads
        d = hd3 // (3 * h)
    else:
        b, s, h, d = q.shape
    if block_q is None or block_k is None:
        block_q, block_k = _block_sizes(s)
    native = native and _native_supported(h, d)
    assert native or not fused, "fused qkv requires the native layout"

    if native:
        hp = _heads_per_program(h, d)
        hd = hp * d
        HB = h // hp                      # lane blocks per q/k/v tensor
        if fused:
            # one array, three views: block index offsets select the
            # q/k/v regions of the fused lane dim
            qf = kf = vf = q
            off_k, off_v = HB, 2 * HB
        else:
            # free reshapes: (b, s, h, d) -> (b, s, h*d) is contiguous
            qf = q.reshape(b, s, h * d)
            kf = k.reshape(b, s, h * d)
            vf = v.reshape(b, s, h * d)
            off_k = off_v = 0
        grid = (b, HB, s // block_q)
        q_spec = pl.BlockSpec((None, block_q, hd),
                              lambda ib, ih, iq: (ib, iq, ih))
        k_spec = pl.BlockSpec((None, s, hd),
                              lambda ib, ih, iq: (ib, 0, off_k + ih))
        v_spec = pl.BlockSpec((None, s, hd),
                              lambda ib, ih, iq: (ib, 0, off_v + ih))
        out_shapes = [jax.ShapeDtypeStruct((b, s, h * d), q.dtype)]
        out_specs = [pl.BlockSpec((None, block_q, hd),
                                  lambda ib, ih, iq: (ib, iq, ih))]
        if with_lse:
            # lse stays head-major (b, h, 8, s) in both modes — it is tiny
            # (b*h*s fp32), so its layout never costs a large copy. Block
            # covers this program's hp heads.
            out_shapes.append(jax.ShapeDtypeStruct((b, h, 8, s),
                                                   jnp.float32))
            out_specs.append(pl.BlockSpec((None, hp, 8, block_q),
                                          lambda ib, ih, iq: (ib, ih, 0, iq)))
        kern = functools.partial(
            _flash_fwd_kernel_native, causal=causal, sm_scale=sm_scale,
            block_k=block_k, seq_len=s, hp=hp, d=d)
        if not with_lse:
            kern = functools.partial(kern, lse_ref=None)
        res = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[q_spec, k_spec, v_spec],
            out_specs=out_specs if with_lse else out_specs[0],
            out_shape=out_shapes if with_lse else out_shapes[0],
            interpret=_interpret_mode(),
            compiler_params=_tpu_params(2),
            name="flash_fwd",
        )(qf, kf, vf)
        if with_lse:
            out, lse = res
            return out.reshape(b, s, h, d), lse
        return res.reshape(b, s, h, d)

    # transpose layout: kernel works on [b, h, s, d]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    hb = _head_block(h)
    grid = (b, h // hb, s // block_q)
    out_shapes = [jax.ShapeDtypeStruct((b, h, s, d), q.dtype)]
    out_specs = [pl.BlockSpec((None, hb, block_q, d),
                              lambda ib, ih, iq: (ib, ih, iq, 0))]
    if with_lse:
        # rank-4 with an 8-row broadcast dim: Pallas TPU requires the last
        # two block dims divisible by (8, 128), ruling out rank-1 blocks
        out_shapes.append(jax.ShapeDtypeStruct((b, h, 8, s), jnp.float32))
        out_specs.append(pl.BlockSpec((None, hb, 8, block_q),
                                      lambda ib, ih, iq: (ib, ih, 0, iq)))
    kern = functools.partial(
        _flash_fwd_kernel, causal=causal, sm_scale=sm_scale,
        block_k=block_k, seq_len=s, head_block=hb)
    if not with_lse:
        kern = functools.partial(kern, lse_ref=None)
    res = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, hb, block_q, d),
                         lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, hb, s, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
            pl.BlockSpec((None, hb, s, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shapes if with_lse else out_shapes[0],
        interpret=_interpret_mode(),
        compiler_params=_tpu_params(2),
        name="flash_fwd_t",
    )(qt, kt, vt)
    if with_lse:
        out, lse = res
        return jnp.swapaxes(out, 1, 2), lse
    return jnp.swapaxes(res, 1, 2)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "native",
                                             "n_heads", "fused_dqkv",
                                             "block_q", "block_k"))
def _flash_bwd(q, k, v, o, lse, do, causal: bool, sm_scale: float,
               native: bool = True, n_heads: int | None = None,
               fused_dqkv: bool = True, block_q: int | None = None,
               block_k: int | None = None):
    """Tiled backward: dq over q-blocks, dk/dv over k-blocks, never
    materializing the [S, S] score matrix (the role of the reference's
    flash_attn_bwd CUDA kernels, flash_attn_grad_kernel.cu). With
    ``n_heads`` set, q is the FUSED (b, s, 3*h*d) qkv residual (k=v=None)
    read through offset index maps."""
    import jax.experimental.pallas as pl

    fused = n_heads is not None
    if fused:
        b, s, _ = q.shape
        h = n_heads
        d = q.shape[-1] // (3 * h)
    else:
        b, s, h, d = q.shape
    native = native and _native_supported(h, d)
    assert native or not fused, "fused qkv requires the native layout"
    # delta (a reduction) is computed in the ORIGINAL [b, s, h, d] layout so
    # o never needs a 16MB-per-layer transpose — only the tiny [b,s,h]
    # reduction result gets permuted (lse/delta keep the head-major packed
    # layout in both modes).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                   # [b, s, h]
    delta = jnp.transpose(delta, (0, 2, 1))                    # [b, h, s]
    delta = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, s))

    if block_q is None or block_k is None:
        block_q, block_k = _block_sizes(s)

    if native:
        hp = _heads_per_program(h, d)
        hd = hp * d
        HB = h // hp
        if fused:
            qf = kf = vf = q
            off_k, off_v = HB, 2 * HB
        else:
            qf = q.reshape(b, s, h * d)
            kf = k.reshape(b, s, h * d)
            vf = v.reshape(b, s, h * d)
            off_k = off_v = 0
        dtype = qf.dtype
        dof = do.astype(dtype).reshape(b, s, h * d)
        if fused:
            # fused_dqkv is a STATIC arg read by the caller OUTSIDE this
            # jit (the jit cache doesn't key on GLOBAL_FLAGS, so an
            # in-trace read would make in-process flag flips a no-op)
            if fused_dqkv and block_q == block_k and _fused_dqkv_ok(
                    s, hd, jnp.dtype(dtype).itemsize, block=block_q):
                block = block_q
                blk = pl.BlockSpec((None, block, hd),
                                   lambda ib, ih, i: (ib, i, ih))
                kblk = pl.BlockSpec(
                    (None, block, hd),
                    lambda ib, ih, i: (ib, i, off_k + ih))
                vblk = pl.BlockSpec(
                    (None, block, hd),
                    lambda ib, ih, i: (ib, i, off_v + ih))
                qfull = pl.BlockSpec((None, s, hd),
                                     lambda ib, ih, i: (ib, 0, ih))
                kfull = pl.BlockSpec(
                    (None, s, hd), lambda ib, ih, i: (ib, 0, off_k + ih))
                vfull = pl.BlockSpec(
                    (None, s, hd), lambda ib, ih, i: (ib, 0, off_v + ih))
                lse_blk = pl.BlockSpec((None, hp, 8, block),
                                       lambda ib, ih, i: (ib, ih, 0, i))
                lse_full = pl.BlockSpec((None, hp, 8, s),
                                        lambda ib, ih, i: (ib, ih, 0, 0))
                dqkv4 = pl.pallas_call(
                    functools.partial(_flash_bwd_fused_kernel_native,
                                      causal=causal, sm_scale=sm_scale,
                                      block=block, seq_len=s, hp=hp, d=d),
                    grid=(b, HB, s // block),
                    in_specs=[blk, kfull, vfull, kblk, vblk, qfull,
                              blk, qfull, lse_blk, lse_blk, lse_full,
                              lse_full],
                    out_specs=pl.BlockSpec(
                        (None, block, 3, hd),
                        lambda ib, ih, i: (ib, i, 0, ih)),
                    out_shape=jax.ShapeDtypeStruct((b, s, 3, h * d),
                                                   dtype),
                    interpret=_interpret_mode(),
                    compiler_params=_tpu_params(2),
                    name="flash_bwd_dqkv",
                )(qf, qf, qf, qf, qf, qf, dof, dof, lse, delta, lse,
                  delta)
                return dqkv4.reshape(b, s, 3 * h * d)
        blk_q = pl.BlockSpec((None, block_q, hd),
                             lambda ib, ih, iq: (ib, iq, ih))
        blk_kk = pl.BlockSpec((None, block_k, hd),
                              lambda ib, ih, ik: (ib, ik, off_k + ih))
        blk_kv = pl.BlockSpec((None, block_k, hd),
                              lambda ib, ih, ik: (ib, ik, off_v + ih))
        out_blk_k = pl.BlockSpec((None, block_k, hd),
                                 lambda ib, ih, ik: (ib, ik, ih))
        full_q = pl.BlockSpec((None, s, hd),
                              lambda ib, ih, i: (ib, 0, ih))
        full_k = pl.BlockSpec((None, s, hd),
                              lambda ib, ih, i: (ib, 0, off_k + ih))
        full_v = pl.BlockSpec((None, s, hd),
                              lambda ib, ih, i: (ib, 0, off_v + ih))
        pack_q = pl.BlockSpec((None, hp, 8, block_q),
                              lambda ib, ih, iq: (ib, ih, 0, iq))
        full_pack = pl.BlockSpec((None, hp, 8, s),
                                 lambda ib, ih, ik: (ib, ih, 0, 0))

        dq = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel_native, causal=causal,
                              sm_scale=sm_scale, block_k=block_k, seq_len=s,
                              hp=hp, d=d),
            grid=(b, HB, s // block_q),
            in_specs=[blk_q, full_k, full_v, blk_q, pack_q, pack_q],
            out_specs=blk_q,
            out_shape=jax.ShapeDtypeStruct((b, s, h * d), dtype),
            interpret=_interpret_mode(),
            compiler_params=_tpu_params(2),
            name="flash_bwd_dq",
        )(qf, kf, vf, dof, lse, delta)

        dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_dkv_kernel_native, causal=causal,
                              sm_scale=sm_scale, block_q=block_q, seq_len=s,
                              hp=hp, d=d),
            grid=(b, HB, s // block_k),
            in_specs=[full_q, blk_kk, blk_kv, full_q, full_pack, full_pack],
            out_specs=[out_blk_k, out_blk_k],
            out_shape=[jax.ShapeDtypeStruct((b, s, h * d), dtype),
                       jax.ShapeDtypeStruct((b, s, h * d), dtype)],
            interpret=_interpret_mode(),
            compiler_params=_tpu_params(2),
            name="flash_bwd_dkv",
        )(qf, kf, vf, dof, lse, delta)
        if fused:
            return jnp.concatenate([dq, dk, dv], axis=-1)
        return (dq.reshape(b, s, h, d), dk.reshape(b, s, h, d),
                dv.reshape(b, s, h, d))

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    dot_ = jnp.swapaxes(do, 1, 2).astype(q.dtype)
    hb = _head_block(h)

    full = lambda ib, ih, i: (ib, ih, 0, 0)
    blk_q4 = lambda ib, ih, iq: (ib, ih, iq, 0)
    pack_q = lambda ib, ih, iq: (ib, ih, 0, iq)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal,
                          sm_scale=sm_scale, block_k=block_k, seq_len=s,
                          head_block=hb),
        grid=(b, h // hb, s // block_q),
        in_specs=[
            pl.BlockSpec((None, hb, block_q, d), blk_q4),
            pl.BlockSpec((None, hb, s, d), full),
            pl.BlockSpec((None, hb, s, d), full),
            pl.BlockSpec((None, hb, block_q, d), blk_q4),
            pl.BlockSpec((None, hb, 8, block_q), pack_q),
            pl.BlockSpec((None, hb, 8, block_q), pack_q),
        ],
        out_specs=pl.BlockSpec((None, hb, block_q, d), blk_q4),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=_interpret_mode(),
        compiler_params=_tpu_params(2),
        name="flash_bwd_dq_t",
    )(qt, kt, vt, dot_, lse, delta)

    full_pack = lambda ib, ih, ik: (ib, ih, 0, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                          sm_scale=sm_scale, block_q=block_q, seq_len=s,
                          head_block=hb),
        grid=(b, h // hb, s // block_k),
        in_specs=[
            pl.BlockSpec((None, hb, s, d), full),
            pl.BlockSpec((None, hb, block_k, d), blk_q4),
            pl.BlockSpec((None, hb, block_k, d), blk_q4),
            pl.BlockSpec((None, hb, s, d), full),
            pl.BlockSpec((None, hb, 8, s), full_pack),
            pl.BlockSpec((None, hb, 8, s), full_pack),
        ],
        out_specs=[pl.BlockSpec((None, hb, block_k, d), blk_q4),
                   pl.BlockSpec((None, hb, block_k, d), blk_q4)],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, s, d), v.dtype)],
        interpret=_interpret_mode(),
        compiler_params=_tpu_params(2),
        name="flash_bwd_dkv_t",
    )(qt, kt, vt, dot_, lse, delta)

    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


def _sdpa_fallback(q, k, v, causal, sm_scale):
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", qh, kh, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return jnp.swapaxes(o, 1, 2)


def _library_flash(q, k, v, causal: bool, scale: float):
    """Route to jax's TPU Pallas flash kernels (fwd AND bwd kernels) when
    running on real TPU — the custom_vjp below keeps backward memory
    bounded but recomputes full S×S logits (HBM-bound); the library bwd
    kernel tiles it. Returns None when not applicable."""
    if jax.default_backend() != "tpu":
        return None
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention as tpu_flash)
    except Exception:
        return None
    b, s, h, d = q.shape
    if not supported(q.shape, q.dtype):
        return None
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    # Tuned on v5e (GPT-350M shapes): 512/1024 tiles beat the library
    # defaults ~2.5x on fwd+bwd.
    bq, bk = _block_sizes(s)
    bs = BlockSizes(block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
                    block_q_major_dkv=bq, block_k_major_dkv=bk,
                    block_q_dkv=bq, block_k_dkv=bk,
                    block_q_dq=bq, block_k_dq=bk, block_k_major_dq=bk)
    out = tpu_flash(qt, kt, vt, causal=causal, sm_scale=scale,
                    block_sizes=bs)
    return jnp.swapaxes(out, 1, 2)


def flash_attention_raw(q, k, v, causal: bool = False, sm_scale: float | None = None):
    """Differentiable flash attention: Pallas forward, XLA-expression VJP.

    The custom_vjp pairs the Pallas forward with a recompute-based backward
    (standard flash-attention trick: recompute probabilities blockwise from
    the saved output normalizer is subsumed here by XLA rematerialization of
    the fallback expression, keeping backward memory O(S) not O(S^2) once
    the whole step is jitted with remat policies).
    """
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)

    # The jax-library TPU kernel measured 4x SLOWER than this kernel+XLA-bwd
    # on v5e at GPT-350M shapes (default block sizes); opt-in via flag.
    from ...core.flags import GLOBAL_FLAGS

    if GLOBAL_FLAGS.has("use_library_flash_attention") and \
            GLOBAL_FLAGS.get("use_library_flash_attention"):
        lib_out = _library_flash(q, k, v, causal, scale)
        if lib_out is not None:
            return lib_out

    # Backward choice: the Pallas bwd kernels (tiled dq/dkv, O(S) memory)
    # are the default — with the 512/1024 tiles they measure fastest on v5e
    # (GPT-350M train step: 252ms vs 333ms for the sdpa-vjp backward and
    # 271ms for the jax library kernels). Opt out via
    # FLAGS_flash_attention_kernel_bwd=0 to fall back to the XLA-expression
    # vjp (which transiently materializes S×S per layer; outer remat keeps
    # it bounded).
    use_kernel_bwd = (GLOBAL_FLAGS.get("flash_attention_kernel_bwd")
                      if GLOBAL_FLAGS.has("flash_attention_kernel_bwd")
                      else True)
    # Native (b,s,h,d) kernel layout (default): kernels consume the model
    # layout via lane-fused 2-D blocks, eliminating the head-major
    # transpose copies. FLAGS_flash_attention_native_layout=0 restores the
    # transpose-based path for A/B measurement.
    native = (GLOBAL_FLAGS.get("flash_attention_native_layout")
              if GLOBAL_FLAGS.has("flash_attention_native_layout")
              else True)

    # Block shapes come from the autotune registry (trace-time choice,
    # like the flags above); tuning only covers the native kernels, so
    # the transpose A/B path keeps the hand defaults.
    if native and len(q.shape) == 4 and supported(q.shape, q.dtype):
        bq, bk = _tuned_blocks(q.shape[0], q.shape[1], q.shape[2],
                               q.shape[3], q.dtype, causal)
    else:
        bq = bk = None

    @jax.custom_vjp
    def fa(q, k, v):
        return _flash_fwd(q, k, v, causal, scale, native=native,
                          block_q=bq, block_k=bk)

    if use_kernel_bwd:
        def fwd(q, k, v):
            from jax.ad_checkpoint import checkpoint_name

            o, lse = _flash_fwd(q, k, v, causal, scale, with_lse=True,
                                native=native, block_q=bq, block_k=bk)
            # Under jax.checkpoint, pallas outputs are not "dots", so a
            # dots-saveable policy would recompute the whole flash forward
            # in backward. Naming them lets the model's remat policy save
            # them (models/gpt.py pairs this with save_only_these_names).
            o = checkpoint_name(o, "flash_o")
            lse = checkpoint_name(lse, "flash_lse")
            return o, (q, k, v, o, lse)

        def bwd(res, g):
            q, k, v, o, lse = res
            return _flash_bwd(q, k, v, o, lse, g, causal, scale,
                              native=native, block_q=bq, block_k=bk)
    else:
        def fwd(q, k, v):
            return fa(q, k, v), (q, k, v)

        def bwd(res, g):
            q, k, v = res
            _, vjp = jax.vjp(
                lambda a, b, c: _sdpa_fallback(a, b, c, causal, scale),
                q, k, v)
            return vjp(g)

    fa.defvjp(fwd, bwd)
    return fa(q, k, v)


def flash_attention_qkv_raw(qkv, n_heads: int, causal: bool = True,
                            sm_scale: float | None = None):
    """Flash attention straight from the FUSED qkv projection output
    (``qkv`` [B, S, 3*H]): the kernels read q/k/v through lane-block
    offset views, so the FORWARD's 3-way split copies (and their saved
    residuals) never materialize. The backward writes dq/dk/dv into ONE
    dqkv cotangent through the merged kernel
    (_flash_bwd_fused_kernel_native) when _fused_dqkv_ok — no
    concatenate; larger configs fall back to the split two-kernel +
    concat path. Requires the native layout.
    Returns [B, S, n_heads, head_dim]."""
    if not flash_qkv_supported(qkv.shape, n_heads, qkv.dtype):
        raise ValueError(
            f"flash_attention_qkv_raw: shape {tuple(qkv.shape)} with "
            f"{n_heads} heads is not supported (needs 3*h*d fused lanes, "
            "128-aligned seq blocks, head_dim in (64,128,256) dividing "
            "the lane blocks); use flash_attention_raw instead")
    b, s, hd3 = qkv.shape
    d = hd3 // (3 * n_heads)
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    bq, bk = _tuned_blocks(b, s, n_heads, d, qkv.dtype, causal,
                           n_heads=n_heads)

    @jax.custom_vjp
    def fa(qkv):
        return _flash_fwd(qkv, None, None, causal, scale, n_heads=n_heads,
                          block_q=bq, block_k=bk)

    def fwd(qkv):
        from jax.ad_checkpoint import checkpoint_name

        o, lse = _flash_fwd(qkv, None, None, causal, scale, with_lse=True,
                            n_heads=n_heads, block_q=bq, block_k=bk)
        o = checkpoint_name(o, "flash_o")
        lse = checkpoint_name(lse, "flash_lse")
        return o, (qkv, o, lse)

    def bwd(res, g):
        qkv, o, lse = res
        from ...core.flags import GLOBAL_FLAGS as _GF

        merged = (_GF.get("flash_attention_fused_dqkv")
                  if _GF.has("flash_attention_fused_dqkv") else True)
        return (_flash_bwd(qkv, None, None, o, lse, g, causal, scale,
                           n_heads=n_heads, fused_dqkv=bool(merged),
                           block_q=bq, block_k=bk),)

    fa.defvjp(fwd, bwd)
    return fa(qkv)


def flash_qkv_supported(shape, n_heads: int, dtype) -> bool:
    """Also consults the flash flags: the fused entry hardcodes the
    native kernels fwd+bwd, so any flag that redirects
    flash_attention_raw (layout A/B, XLA-expression bwd, library kernel)
    must disable this path too — otherwise the documented escape hatches
    silently stop affecting models using the fused entry."""
    from ...core.flags import GLOBAL_FLAGS

    def flag(name, default):
        return (GLOBAL_FLAGS.get(name) if GLOBAL_FLAGS.has(name)
                else default)

    if (not flag("flash_attention_native_layout", True)
            or not flag("flash_attention_kernel_bwd", True)
            or flag("use_library_flash_attention", False)):
        return False
    if len(shape) != 3:
        return False
    b, s, hd3 = shape
    if hd3 % (3 * n_heads):
        return False
    d = hd3 // (3 * n_heads)
    return (supported((b, s, n_heads, d), dtype)
            and _native_supported(n_heads, d))


# Framework-op wrapper (Tensor in/out, tape-recorded); pure-jnp callers
# (functional models, compiled train steps) use flash_attention_raw.
flash_attention = op("pallas_flash_attention", amp="cast")(flash_attention_raw)
