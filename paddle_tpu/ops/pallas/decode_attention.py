"""Pallas TPU decode-attention kernel (paged/serving path).

TPU replacement for the reference's masked_multihead_attention /
block_multi_head_attention decode kernels
(phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu,
fused_multi_transformer_kernel.cu decode branch): single-token query
against a KV cache. Two wins over the XLA expression path:

- **No GQA inflation**: the q heads sharing one kv head are processed
  together ([G, d] q tile against that kv head's [S, d] cache), so the
  repeated-KV tensor ([B, S, nH, d], 4-8x the cache size for
  LLaMA-2/3 GQA) never exists.
- **Length-bounded reads**: the k loop runs to ceil((pos+1)/block), not
  max_seq — decode cost tracks the actual context length (the kernel
  gets `pos` as a prefetched scalar so the loop bound is dynamic).

Cache layout matches models/llama.py: k/v [B, n_kv, S, d] per layer
(kv-head-major; the engine stores it natively in this layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret_mode

# Accumulation-dtype declaration for tools/lint/quantcheck.py (TPL301):
# score and value dots accumulate in fp32 in every arm.
ACCUM_DTYPE = "float32"

BLOCK_S = 512


def _online_softmax_page(q, k, v, base_pos, bs, seq_len, sm_scale,
                         m_sc, l_sc, acc_sc):
    """One page's contribution to the running (m, l, acc) scratch state —
    shared by the index-map and manual-DMA paged kernels so their
    numerics can never diverge. q [nh, d] fp32; k/v [nh, bs, d] fp32."""
    pos = base_pos + jax.lax.iota(jnp.int32, bs)
    valid = pos < seq_len
    s = jnp.sum(q[:, None, :] * k, axis=-1) * sm_scale       # [nh, bs]
    s = s + jnp.where(valid, 0.0, -1e30)[None, :]
    m_prev = m_sc[0, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_sc[0, :] = l_sc[0, :] * alpha + jnp.sum(p, axis=1)
    m_sc[0, :] = m_new
    acc_sc[...] = (acc_sc[...] * alpha[:, None]
                   + jnp.sum(p[:, :, None] * v, axis=1))


def decode_attention_supported(cache_shape, head_dim: int,
                               num_heads: int | None = None) -> bool:
    _, nKV, S, d = cache_shape         # [B, nKV, S, d]
    if d not in (64, 128, 256):
        return False
    if num_heads is not None:
        # the q block is [G, d] with G = nH // nKV: require exact
        # divisibility, and G >= 2 so the second-minor block dim is never
        # a 1-row tile (a Mosaic-tiling hazard on real TPU that interpret
        # -mode tests would not catch; MHA G=1 takes the XLA path)
        if num_heads % nKV or num_heads // nKV < 2:
            return False
    # the kernel slices fixed BLOCK_S-wide k/v windows: S must be one
    # block (any 128-multiple) or a whole number of blocks — otherwise
    # dynamic-slice clamping would silently misalign the position mask
    return (S % 128 == 0) if S <= BLOCK_S else (S % BLOCK_S == 0)


def _decode_kernel(pos_ref, *refs, block_s, seq_len, sm_scale,
                   quant=False):
    import jax.experimental.pallas as pl

    if quant:
        q_ref, k_ref, v_ref, ksc_ref, vsc_ref, o_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref = refs
    pos = pos_ref[0]
    q = q_ref[...]                       # [G, d] — this kv-head's q group
    G, d = q.shape

    m_i = jnp.full((G,), -1e30, jnp.float32)
    l_i = jnp.zeros((G,), jnp.float32)
    acc = jnp.zeros((G, d), jnp.float32)

    num_blocks = jax.lax.div(pos + block_s, block_s)  # ceil((pos+1)/bs)

    def body(sb, carry):
        m_i, l_i, acc = carry
        k = k_ref[pl.dslice(sb * block_s, block_s), :]      # [bs, d]
        v = v_ref[pl.dslice(sb * block_s, block_s), :]
        if quant:
            # per-position dequant (same fp32-multiply-then-cast contract
            # as ops/quant.py::dequantize_int8)
            ks = ksc_ref[0, pl.dslice(sb * block_s, block_s)]
            vs = vsc_ref[0, pl.dslice(sb * block_s, block_s)]
            k = (k.astype(jnp.float32) * ks[:, None]).astype(q.dtype)
            v = (v.astype(jnp.float32) * vs[:, None]).astype(q.dtype)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        offs = sb * block_s + jax.lax.iota(jnp.int32, block_s)
        s = jnp.where((offs <= pos)[None, :], s, -1e30)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m_i, l_i, acc = jax.lax.fori_loop(0, num_blocks, body, (m_i, l_i, acc))
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


def paged_decode_supported(pages_shape, n_q_heads: int,
                           max_blocks: int | None = None,
                           itemsize: int = 2) -> bool:
    """Paged kernel constraints: page block (bs, d) must satisfy Mosaic's
    last-two-dims rule, the cache must hold every q head (the paged
    cache is full-head, no GQA sharing), and the k_per-page
    double-buffered k+v working set must fit ~16MB VMEM (v5e) — larger
    configs take the XLA gather path. Pass the cache dtype's itemsize
    (default bf16) so the VMEM estimate matches the kernel's k_per."""
    _, nh, bs, d = pages_shape
    page_bytes = nh * bs * d * itemsize
    k_per = _paged_pages_per_program(max_blocks if max_blocks is not None
                                     else 4, page_bytes)
    # double-buffered k+v operands for the whole group + ONE page's fp32
    # cast temps (pages compute serially) — calibrated against the
    # measured-working 1B config (nh=16, bs=128, d=128, k_per=4 ≈ 10MB)
    est = 2 * 2 * k_per * page_bytes + 4 * page_bytes
    if est > 12 * 2 ** 20:
        return False
    return (d in (64, 128, 256) and bs % 8 == 0
            and nh == n_q_heads)


def _paged_pages_per_program(max_blocks: int,
                             page_bytes: int | None = None) -> int:
    """Pages fetched per grid program / DMA group: amortizes per-step
    overhead over the largest power-of-two divisor <= 4 whose
    double-buffered k+v working set also fits VMEM when ``page_bytes``
    is given (2 slots x 2 tensors x k pages <= ~12MB)."""
    for k in (4, 2, 1):
        if max_blocks % k:
            continue
        if page_bytes is not None and 4 * k * page_bytes > 12 * 2 ** 20:
            continue
        return k
    return 1


def _paged_decode_kernel(bt_ref, sl_ref, q_ref, *refs, bs, n_blocks,
                         sm_scale, k_per):
    """One (batch, block-group) program: the K k/v pages for THIS group
    arrived via block-table-driven index maps; accumulate online-softmax
    over the group grid dim in scratch. ``refs`` = K k-page refs, K
    v-page refs, o_ref, then the 3 scratch refs."""
    import jax.experimental.pallas as pl

    k_refs = refs[:k_per]
    v_refs = refs[k_per:2 * k_per]
    o_ref = refs[2 * k_per]
    m_sc, l_sc, acc_sc = refs[2 * k_per + 1:]

    b = pl.program_id(0)
    j = pl.program_id(1)
    nh, d = q_ref.shape

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], -1e30)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    seq_len = sl_ref[b]
    # fully vectorized over heads on the VPU: decode is HBM-bound (one
    # token's worth of flops per page read), so mul-reduce "dots" beat
    # nh separate 1-row MXU dots and need no scalar scratch access
    q = q_ref[...].astype(jnp.float32)                # [nh, d]
    for c in range(k_per):
        _online_softmax_page(
            q, k_refs[c][...].astype(jnp.float32),
            v_refs[c][...].astype(jnp.float32),
            (j * k_per + c) * bs, bs, seq_len, sm_scale,
            m_sc, l_sc, acc_sc)

    @pl.when(j == n_blocks // k_per - 1)
    def _fin():
        o_ref[...] = (acc_sc[...] /
                      jnp.maximum(l_sc[0, :], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


def _paged_decode_dma_kernel(bt_ref, sl_ref, q_ref, k_hbm, v_hbm, o_ref,
                             k_buf, v_buf, sems, m_sc, l_sc, acc_sc, *,
                             bs, max_blocks, sm_scale, gk):
    """One program per SEQUENCE: pages stay in HBM (memory_space=ANY) and
    the kernel issues its own double-buffered async copies driven by the
    prefetched block table — the next GROUP of ``gk`` pages' DMAs are in
    flight while the current group computes (vllm-TPU's pattern). Group
    size amortizes the ~10us/iteration loop overhead that bounds the
    one-page-per-step variants."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    nh, d = q_ref.shape
    n_groups = max_blocks // gk

    def group_dmas(slot, g):
        out = []
        for c in range(gk):
            page = bt_ref[b * max_blocks + g * gk + c]
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, c], sems.at[0, slot, c]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[slot, c], sems.at[1, slot, c]))
        return out

    for dma in group_dmas(0, 0):
        dma.start()

    m_sc[...] = jnp.full_like(m_sc[...], -1e30)
    l_sc[...] = jnp.zeros_like(l_sc[...])
    acc_sc[...] = jnp.zeros_like(acc_sc[...])
    seq_len = sl_ref[b]
    q = q_ref[...].astype(jnp.float32)                # [nh, d]

    def loop(g, _):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _prefetch():
            for dma in group_dmas((g + 1) % 2, g + 1):
                dma.start()

        for dma in group_dmas(slot, g):
            dma.wait()

        for c in range(gk):
            _online_softmax_page(
                q, k_buf[slot, c].astype(jnp.float32),
                v_buf[slot, c].astype(jnp.float32),
                (g * gk + c) * bs, bs, seq_len, sm_scale,
                m_sc, l_sc, acc_sc)
        return 0

    jax.lax.fori_loop(0, n_groups, loop, 0)
    o_ref[...] = (acc_sc[...] /
                  jnp.maximum(l_sc[0, :], 1e-30)[:, None]).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def paged_decode_attention_dma(q, k_pages, v_pages, block_table,
                               seq_lens, sm_scale: float):
    """DMA-pipelined batched paged decode (see _paged_decode_dma_kernel).
    Same contract as paged_decode_attention_kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not paged_decode_supported(k_pages.shape, q.shape[1],
                                  max_blocks=block_table.shape[1],
                                  itemsize=k_pages.dtype.itemsize):
        raise ValueError(
            f"paged_decode_attention_dma: pages {tuple(k_pages.shape)} "
            f"with {q.shape[1]} q heads unsupported; gate with "
            "paged_decode_supported()")
    B, nh, d = q.shape
    bs = k_pages.shape[2]
    max_blocks = block_table.shape[1]
    gk = _paged_pages_per_program(max_blocks,
                                  page_bytes=nh * bs * d *
                                  k_pages.dtype.itemsize)
    bt_flat = block_table.reshape(-1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, nh, d), lambda b, bt, sl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),     # k_pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),     # v_pages stay in HBM
        ],
        out_specs=pl.BlockSpec((None, nh, d), lambda b, bt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, gk, nh, bs, d), k_pages.dtype),
            pltpu.VMEM((2, gk, nh, bs, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2, gk)),
            pltpu.VMEM((8, nh), jnp.float32),
            pltpu.VMEM((8, nh), jnp.float32),
            pltpu.VMEM((nh, d), jnp.float32),
        ],
    )
    return pl.pallas_call(  # tpu-lint: disable=TPL007 -- blocks ARE the page geometry (bs fixed by the cache layout); nothing to sweep
        functools.partial(_paged_decode_dma_kernel, bs=bs,
                          max_blocks=max_blocks, sm_scale=sm_scale, gk=gk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, d), q.dtype),
        interpret=_interpret_mode(),
        name="paged_decode_dma",
    )(bt_flat, seq_lens.astype(jnp.int32), q, k_pages, v_pages)


def paged_decode_mxu_supported(kt_pages_shape, n_q_heads: int,
                               max_blocks: int | None = None,
                               itemsize: int = 2) -> bool:
    """Gate for the MXU paged kernel: d-major k pages [n_pages, nkv, d, bs]
    with MXU-tileable flattened pages — bs a lane multiple for k [nkv*d, bs]
    and d one for v [nkv*bs, d] — plus the same VMEM working-set bound as
    the vector kernel. GQA native: q may carry G = n_q/nkv heads per kv
    head (the repeated-KV tensor never exists)."""
    _, nkv, d, bs = kt_pages_shape
    page_bytes = nkv * bs * d * itemsize
    k_per = _paged_pages_per_program(max_blocks if max_blocks is not None
                                     else 4, page_bytes)
    est = 2 * 2 * k_per * page_bytes + 2 * n_q_heads * nkv * d * itemsize
    if est > 12 * 2 ** 20:
        return False
    return (d in (128, 256) and bs % 128 == 0 and n_q_heads % nkv == 0
            and n_q_heads >= 8)


def _paged_decode_mxu_kernel(bt_ref, sl_ref, q_ref, *refs, bs, n_blocks,
                             sm_scale, k_per):
    """MXU-formulated paged decode program (see paged_decode_attention_mxu):
    per page, scores and weighted values are TWO block-diagonal MXU dots —
    no VPU cross-lane reductions, no fp32 page-sized cast temps. k pages
    arrive d-major [nkv, d, bs]; v pages token-major [nkv, bs, d]; q
    carries all nh = G*nkv query heads."""
    import jax.experimental.pallas as pl

    k_refs = refs[:k_per]
    v_refs = refs[k_per:2 * k_per]
    o_ref = refs[2 * k_per]
    m_sc, l_sc, acc_sc, qblk_sc = refs[2 * k_per + 1:]

    b = pl.program_id(0)
    j = pl.program_id(1)
    nh, d = q_ref.shape
    nkv = k_refs[0].shape[0]
    G = nh // nkv

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], -1e30)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])
        # block-diagonal Q [nh, nkv*d]: row h holds q[h] in the column
        # block of ITS kv head (h//G) — one MXU dot against the flattened
        # page then computes every head's scores with no cross-head terms
        # and no GQA repeat. Built once per sequence (j==0), reused
        # across its pages.
        q = q_ref[...]
        qt = jnp.concatenate([q] * nkv, axis=1)           # [nh, nkv*d]
        col_kv = jax.lax.broadcasted_iota(jnp.int32, (nh, nkv * d), 1) // d
        row_kv = jax.lax.broadcasted_iota(jnp.int32, (nh, nkv * d), 0) // G
        qblk_sc[...] = jnp.where(col_kv == row_kv, qt, 0)

    seq_len = sl_ref[b]
    q_blk = qblk_sc[...]                                  # [nh, nkv*d]
    for c in range(k_per):
        base = (j * k_per + c) * bs
        k_flat = k_refs[c][...].reshape(nkv * d, bs)      # d-major page
        s = jax.lax.dot(q_blk, k_flat,
                        preferred_element_type=jnp.float32) * sm_scale
        pos = base + jax.lax.iota(jnp.int32, bs)
        s = s + jnp.where(pos < seq_len, 0.0, -1e30)[None, :]  # [nh, bs]
        m_prev = m_sc[0, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])                   # [nh, bs]
        alpha = jnp.exp(m_prev - m_new)
        l_sc[0, :] = l_sc[0, :] * alpha + jnp.sum(p, axis=1)
        m_sc[0, :] = m_new
        # block-diagonal P [nh, nkv*bs] against the token-major v page
        pt = jnp.concatenate([p] * nkv, axis=1)           # [nh, nkv*bs]
        col_kv = jax.lax.broadcasted_iota(jnp.int32, (nh, nkv * bs), 1) // bs
        row_kv = jax.lax.broadcasted_iota(jnp.int32, (nh, nkv * bs), 0) // G
        p_blk = jnp.where(col_kv == row_kv, pt, 0).astype(v_refs[c].dtype)
        v_flat = v_refs[c][...].reshape(nkv * bs, d)
        pv = jax.lax.dot(p_blk, v_flat,
                         preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * alpha[:, None] + pv

    @pl.when(j == n_blocks // k_per - 1)
    def _fin():
        o_ref[...] = (acc_sc[...] /
                      jnp.maximum(l_sc[0, :], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def paged_decode_attention_mxu(q, kt_pages, v_pages, block_table,
                               seq_lens, sm_scale: float):
    """Batched paged decode with MXU-formulated per-page math.

    Same contract as paged_decode_attention_kernel EXCEPT k pages are
    stored d-major: kt_pages [n_pages, nkv, d, bs] (PagedKVCache
    k_layout='d_major' writes this layout natively), and GQA is native
    (nkv may divide the q head count; v_pages [n_pages, nkv, bs, d]).
    Motivation (PERF.md round-3 "Paged decode kernel" negative result):
    the vector-formulated per-page softmax/update — not fetch latency —
    bounds the index-map AND manual-DMA variants at ~85-90 GB/s;
    reformulating the per-page score and weighted-value steps as
    block-diagonal MXU dots removes the VPU mul-reduce and its fp32 cast
    temps (reference serving kernel:
    phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nh, d = q.shape
    nkv, bs = kt_pages.shape[1], kt_pages.shape[3]
    max_blocks = block_table.shape[1]
    # page_bytes must match paged_decode_mxu_supported's, or the gate
    # validates a smaller k_per than the kernel runs (VMEM blowout)
    k_per = _paged_pages_per_program(
        max_blocks, page_bytes=nkv * bs * d * kt_pages.dtype.itemsize)
    bt_flat = block_table.reshape(-1).astype(jnp.int32)

    def k_spec(c):
        return pl.BlockSpec(
            (None, nkv, d, bs),
            lambda b, j, bt, sl, c=c: (bt[b * max_blocks + j * k_per + c],
                                       0, 0, 0))

    def v_spec(c):
        return pl.BlockSpec(
            (None, nkv, bs, d),
            lambda b, j, bt, sl, c=c: (bt[b * max_blocks + j * k_per + c],
                                       0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks // k_per),
        in_specs=(
            [pl.BlockSpec((None, nh, d), lambda b, j, bt, sl: (b, 0, 0))]
            + [k_spec(c) for c in range(k_per)]
            + [v_spec(c) for c in range(k_per)]),
        out_specs=pl.BlockSpec((None, nh, d), lambda b, j, bt, sl: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((8, nh), jnp.float32),
                        pltpu.VMEM((8, nh), jnp.float32),
                        pltpu.VMEM((nh, d), jnp.float32),
                        pltpu.VMEM((nh, nkv * d), q.dtype)],
    )
    return pl.pallas_call(  # tpu-lint: disable=TPL007 -- blocks ARE the page geometry (bs fixed by the cache layout); nothing to sweep
        functools.partial(_paged_decode_mxu_kernel, bs=bs,
                          n_blocks=max_blocks, sm_scale=sm_scale,
                          k_per=k_per),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, d), q.dtype),
        interpret=_interpret_mode(),
        name="paged_decode_mxu",
    )(bt_flat, seq_lens.astype(jnp.int32), q,
      *([kt_pages] * k_per), *([v_pages] * k_per))


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def paged_decode_attention_kernel(q, k_pages, v_pages, block_table,
                                  seq_lens, sm_scale: float):
    """Batched paged decode (reference block_multi_head_attention decode
    branch, phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu):
    q [B, nh, d] one token per sequence; k/v_pages
    [n_pages, nh, bs, d]; block_table [B, max_blocks] int32;
    seq_lens [B] int32. The block table rides scalar prefetch, and the
    PAGE fetched for grid step (b, j) is chosen by the table inside the
    BlockSpec index map — the repeated-KV gather of the XLA path never
    materializes. Returns o [B, nh, d]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nh, d = q.shape
    bs = k_pages.shape[2]
    max_blocks = block_table.shape[1]
    # same k_per formula as paged_decode_supported's gate (VMEM bound)
    k_per = _paged_pages_per_program(
        max_blocks, page_bytes=nh * bs * d * k_pages.dtype.itemsize)
    bt_flat = block_table.reshape(-1).astype(jnp.int32)

    def page_spec(c):
        # the page index for (b, group j, offset c) comes FROM the table
        return pl.BlockSpec(
            (None, nh, bs, d),
            lambda b, j, bt, sl, c=c: (bt[b * max_blocks + j * k_per + c],
                                       0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # block_table, seq_lens
        grid=(B, max_blocks // k_per),
        in_specs=(
            [pl.BlockSpec((None, nh, d), lambda b, j, bt, sl: (b, 0, 0))]
            + [page_spec(c) for c in range(k_per)]      # k pages
            + [page_spec(c) for c in range(k_per)]),    # v pages
        out_specs=pl.BlockSpec((None, nh, d), lambda b, j, bt, sl: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((8, nh), jnp.float32),
                        pltpu.VMEM((8, nh), jnp.float32),
                        pltpu.VMEM((nh, d), jnp.float32)],
    )
    return pl.pallas_call(  # tpu-lint: disable=TPL007 -- blocks ARE the page geometry (bs fixed by the cache layout); nothing to sweep
        functools.partial(_paged_decode_kernel, bs=bs,
                          n_blocks=max_blocks, sm_scale=sm_scale,
                          k_per=k_per),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, d), q.dtype),
        interpret=_interpret_mode(),
        name="paged_decode",
    )(bt_flat, seq_lens.astype(jnp.int32), q,
      *([k_pages] * k_per), *([v_pages] * k_per))


_SRC = None


def _autotune_source() -> str:
    global _SRC
    if _SRC is None:
        from . import autotune

        _SRC = autotune.source_hash(_decode_kernel, _online_softmax_page)
    return _SRC


def _tuned_block_s(B: int, nKV: int, G: int, S: int, d: int,
                   dtype) -> int:
    """Sequence-window size for the dense decode kernel via the autotune
    registry; candidates[0] is the hand default min(BLOCK_S, S)."""
    from . import autotune

    default = min(BLOCK_S, S)
    cands = [default] + [c for c in (256, 1024)
                         if c != default and c <= S and S % c == 0]
    if len(cands) < 2:
        return default

    def measure(bs):
        qz = jnp.zeros((B, nKV * G, d), dtype)
        kz = jnp.zeros((B, nKV, S, d), dtype)
        pz = jnp.asarray(S - 1, jnp.int32)
        fn = lambda: decode_attention(qz, kz, kz, pz, 1.0,  # noqa: E731
                                      block_s=int(bs))
        return autotune.time_candidate(fn)

    return int(autotune.tuned("decode_attention",
                              f"b{B}_kv{nKV}_g{G}_s{S}_d{d}",
                              str(jnp.dtype(dtype)), cands, measure=measure,
                              source=_autotune_source()))


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_s"))
def decode_attention(q, cache_k, cache_v, pos, sm_scale: float,
                     block_s: int | None = None,
                     k_scale=None, v_scale=None):
    """q [B, nH, d] (one token); cache_k/v [B, nKV, S, d] (kv-head-major,
    the engine's native layout — no per-step transpose); pos scalar int32
    (last valid cache index). Returns o [B, nH, d].

    int8 caches: pass per-position fp32 scales k_scale/v_scale
    [B, nKV, S]; dequant is fused into the kernel's k/v tile loads (the
    dense cache appends one token per step, so per-position scales need
    no rescue of previously written content — unlike the paged plane's
    running per-page absmax)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if cache_k.dtype == jnp.int8 and (k_scale is None or v_scale is None):
        raise ValueError(
            "decode_attention: int8 caches require k_scale and v_scale "
            "([B, nKV, S] fp32)")
    quant = k_scale is not None

    B, nKV, S, d = cache_k.shape
    nH = q.shape[1]
    G = nH // nKV
    qg = q.reshape(B, nKV, G, d)
    kt, vt = cache_k, cache_v
    if block_s is None:
        block_s = _tuned_block_s(B, nKV, G, S, d, q.dtype)

    _bcast = lambda ib, ih, *_: (ib, ih, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((None, None, G, d), _bcast),
        pl.BlockSpec((None, None, S, d), _bcast),
        pl.BlockSpec((None, None, S, d), _bcast),
    ]
    operands = [qg, kt, vt]
    if quant:
        in_specs += [pl.BlockSpec((None, None, 1, S), _bcast)] * 2
        operands += [k_scale.astype(jnp.float32).reshape(B, nKV, 1, S),
                     v_scale.astype(jnp.float32).reshape(B, nKV, 1, S)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nKV),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, G, d), _bcast),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, seq_len=S,
                          sm_scale=sm_scale, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nKV, G, d), q.dtype),
        interpret=_interpret_mode(),
        name="decode_attention",
    )(jnp.asarray(pos, jnp.int32).reshape(1), *operands)
    return out.reshape(B, nH, d)
