"""Unified ragged-paged-attention: XLA arm vs dense reference, Pallas
kernel (interpret mode) vs XLA arm at 1, 2 and 4 pages a grid step,
garbage-tail pinning with zeros in padding rows, the VMEM gate and the
candidates it admits, and the delegating ragged_prefill shim."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _ragged_paged_xla,
    _slot,
    _steer,
    candidates_for,
    ragged_paged_attention,
    ragged_paged_attention_kernel,
    ragged_paged_supported,
)
from paddle_tpu.ops.pallas import ragged_prefill as shim


def _dense_ref(q, k_pages, v_pages, rows, pos0, n_valid, sm_scale):
    """Numpy reference: per valid token, softmax over its causal keys
    gathered from the block table; padding rows are zeros."""
    C, qb, nH, d = q.shape
    nkv = k_pages.shape[1]
    G = nH // nkv
    bs = k_pages.shape[3]
    out = np.zeros_like(np.asarray(q, dtype=np.float32))
    for c in range(C):
        ks = np.asarray(k_pages)[rows[c]]           # [mb, nkv, d, bs]
        ks = np.moveaxis(ks, 3, 1).reshape(-1, nkv, d)   # [mb*bs, nkv, d]
        vs = np.asarray(v_pages)[rows[c]]           # [mb, nkv, bs, d]
        vs = np.moveaxis(vs, 2, 1).reshape(-1, nkv, d)
        for i in range(n_valid[c]):                 # padding rows stay zeros
            qpos = pos0[c] + i
            n = qpos + 1
            for h in range(nH):
                s = (np.asarray(q)[c, i, h].astype(np.float32)
                     @ ks[:n, h // G].T.astype(np.float32)) * sm_scale
                p = np.exp(s - s.max())
                p /= p.sum()
                out[c, i, h] = p @ vs[:n, h // G].astype(np.float32)
    return out


def _mixed_case(seed=0, C=4, qb=8, nH=4, nkv=2, d=32, bs=16, mb=6,
                n_pages=24):
    """A mixed batch: one decode row (n_valid=1), one full prefill row,
    one partial row, one idle-ish row — pos0 deliberately NOT
    page-aligned for the partial rows."""
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(n_pages, nkv, d, bs)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, nkv, bs, d)),
                     jnp.float32)
    q = jnp.asarray(rng.normal(size=(C, qb, nH, d)), jnp.float32)
    rows = rng.integers(0, n_pages, size=(C, mb)).astype(np.int32)
    pos0 = np.array([37, 0, 21, 3], np.int32)[:C]
    n_valid = np.array([1, qb, 5, 2], np.int32)[:C]
    return q, kp, vp, rows, pos0, n_valid


def test_xla_arm_matches_dense_reference():
    q, kp, vp, rows, pos0, n_valid = _mixed_case()
    got = _ragged_paged_xla(q, kp, vp, jnp.asarray(rows),
                            jnp.asarray(pos0), jnp.asarray(n_valid),
                            0.35, "d_major")
    ref = _dense_ref(q, kp, vp, rows, pos0, n_valid, 0.35)
    np.testing.assert_allclose(np.asarray(got), ref, atol=2e-5, rtol=2e-5)


# kernel geometry of the CPU cases: d=128, bs=128 (the gate's), 8 table
# slots so that 1, 2 and 4 pages a step all divide, sink page 0
_KG = dict(qb=4, nH=4, nkv=2, d=128, bs=128, mb=8, P=20)


def _grid(kind, seed=1):
    """(rows, pos0, n_valid) of one named grid at the _KG geometry."""
    rng = np.random.default_rng(seed)
    qb, bs, mb, P = _KG["qb"], _KG["bs"], _KG["mb"], _KG["P"]
    if kind == "decode_only":       # one token a row at every depth
        pos0 = np.array([0, 127, 128, 300, 511, 640, 1023], np.int32)
        n_valid = np.ones_like(pos0)
    elif kind == "mixed":           # decode, full, partial, tail chunks
        pos0 = np.array([200, 0, 131, 900, 512], np.int32)
        n_valid = np.array([1, qb, 3, 2, qb], np.int32)
    elif kind == "straddle":        # chunks across a page boundary,
        pos0 = np.array([126, 254, 509, 1021], np.int32)     # unaligned
        n_valid = np.array([qb, 3, qb, 3], np.int32)
    elif kind == "ends_in_group":   # context ends inside a group of 4
        pos0 = np.array([130, 257, 640, 5], np.int32)        # or of 2;
        n_valid = np.array([1, qb, 2, 1], np.int32)          # junk after
    elif kind == "idle":            # idle rows: the sink page, pos0 0
        pos0 = np.array([0, 0, 300, 0], np.int32)
        n_valid = np.array([1, 1, qb, 1], np.int32)
    C = len(pos0)
    rows = rng.integers(1, P, size=(C, mb)).astype(np.int32)
    if kind == "ends_in_group":     # ids no pool has, past the context
        for c in range(C):
            rows[c, (pos0[c] + n_valid[c] - 1) // bs + 1:] = 10 ** 6 + c
    if kind == "idle":
        rows[[0, 1, 3]] = 0
    return rows, pos0, n_valid


def _pool(seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    nkv, d, bs, P = _KG["nkv"], _KG["d"], _KG["bs"], _KG["P"]
    return (jnp.asarray(rng.normal(size=(P, nkv, d, bs)), dtype),
            jnp.asarray(rng.normal(size=(P, nkv, bs, d)), dtype))


@pytest.mark.parametrize("pps", [1, 2, 4])
@pytest.mark.parametrize("kind", ["decode_only", "mixed", "straddle",
                                  "ends_in_group", "idle"])
def test_kernel_matches_xla_arm(kind, pps):
    """The kernel at each group size against the XLA arm, valid rows and
    the zeros of padding rows alike (interpret mode on CPU)."""
    rows, pos0, n_valid = _grid(kind)
    kp, vp = _pool(1)
    q = jnp.asarray(np.random.default_rng(3).normal(
        size=(len(pos0), _KG["qb"], _KG["nH"], _KG["d"])), jnp.float32)
    assert ragged_paged_supported(kp.shape, _KG["nH"], _KG["qb"], 4, pps)
    got = ragged_paged_attention_kernel(
        q, kp, vp, jnp.asarray(rows), jnp.asarray(pos0),
        jnp.asarray(n_valid), 0.5, pps=pps)
    # the XLA arm gathers every slot of the table: give it a table whose
    # dead slots name a real page (any: their keys are masked)
    last_page = (pos0 + n_valid - 1) // _KG["bs"]
    live = np.arange(_KG["mb"])[None, :] <= last_page[:, None]
    ref = _ragged_paged_xla(q, kp, vp, jnp.asarray(np.where(live, rows, 0)),
                            jnp.asarray(pos0), jnp.asarray(n_valid), 0.5,
                            "d_major")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    pad = np.arange(_KG["qb"])[None, :] >= n_valid[:, None]
    assert not np.asarray(got)[pad].any()


def test_kernel_decode_tier_in_bf16_matches_xla_arm():
    """bf16 operands take the decode tier at the bf16 sublane tile (16
    query rows a head, G of them real)."""
    rows, pos0, n_valid = _grid("mixed")
    rng = np.random.default_rng(11)
    kp, vp = _pool(12, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(len(pos0), 16, 4, 128)), jnp.bfloat16)
    n_valid = np.where(n_valid == _KG["qb"], 16, n_valid)
    args = (q, kp, vp, jnp.asarray(rows), jnp.asarray(pos0),
            jnp.asarray(n_valid), 0.09)
    got = ragged_paged_attention_kernel(*args, pps=4)
    ref = _ragged_paged_xla(*args, "d_major")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("pps", [1, 2, 4])
def test_dead_slots_repeat_a_block_the_row_already_named(pps):
    """The index maps' rule: a live slot is its own; a dead slot repeats
    what the same operand held in the row's last group that had it live
    (no copy between the two steps), else the last live page; no dead
    slot ever reads the table past the context."""
    for last_page in range(0, 12):
        held = {}
        for j in range(12 // pps):
            for i in range(pps):
                k = int(_slot(j, i, pps, jnp.int32(last_page)))
                assert 0 <= k <= last_page
                if j * pps + i <= last_page:
                    assert k == j * pps + i
                elif i in held:
                    assert k == held[i]         # same block: no DMA
                else:
                    assert k == last_page
                held[i] = k


@pytest.mark.parametrize("pps", [1, 2, 4])
def test_dead_groups_name_the_next_rows_first_blocks(pps):
    """A group past the context computes nothing and names what the next
    row's first step names, so the only copy between a row's last live
    step and the next row's first is the next row's own pages, made
    early; the last row's dead groups repeat its own blocks.  Counted
    over the whole grid, no block is copied that a live step does not
    read or that the same operand did not already hold."""
    bs, mb = 128, 8
    pos0 = jnp.asarray([0, 300, 1023, 5, 640, 130], jnp.int32)
    nv = jnp.asarray([1, 4, 1, 1, 3, 1], jnp.int32)
    C = len(pos0)
    last_page = [int((pos0[c] + nv[c] - 1) // bs) for c in range(C)]
    table = np.arange(1, C * mb + 1).reshape(C, mb)     # distinct pages

    def named(c, j):
        cc, jj, lp = (int(x) for x in _steer(c, j, pos0, nv, C, pps, bs))
        return cc, jj, [table[cc, int(_slot(jj, i, pps, lp))]
                        for i in range(pps)]

    copies, held = 0, [None] * pps
    for c in range(C):
        for j in range(mb // pps):
            cc, jj, blocks = named(c, j)
            if j * pps <= last_page[c]:
                assert (cc, jj) == (c, j)
            elif c + 1 < C:
                assert (cc, jj) == (c + 1, 0)
                assert blocks == named(c + 1, 0)[2]
            else:
                assert cc == c and blocks == held
            copies += sum(b != h for b, h in zip(blocks, held))
            held = blocks
    # one copy per live page, plus the dead slots of a row's FIRST group
    # (they name the row's last live page: pps - live of them)
    want = sum(lp + 1 + max(0, pps - (lp + 1)) for lp in last_page)
    assert copies == want


@pytest.mark.parametrize("arm", ["xla", "kernel_p1", "kernel_p4"])
def test_padding_rows_are_zeros_and_tail_garbage_is_invisible(arm):
    """Padding rows i >= n_valid come out as ZEROS on both arms, and the
    whole output is invariant to garbage beyond the last valid
    position: future page ids in the table and key/value content past
    the mask."""
    rng = np.random.default_rng(2)
    if arm == "xla":
        C, qb, nH, nkv, d, bs, mb, P = 2, 6, 4, 2, 32, 16, 4, 12
    else:
        C, qb, nH, nkv, d, bs, mb, P = 2, 4, 4, 2, 128, 128, 4, 10
    kp = np.asarray(rng.normal(size=(P, nkv, d, bs)), np.float32)
    vp = np.asarray(rng.normal(size=(P, nkv, bs, d)), np.float32)
    q = jnp.asarray(rng.normal(size=(C, qb, nH, d)), jnp.float32)
    # disjoint pages per row so tail scrambles can't hit another row's
    # (or an earlier table slot's) live keys
    rows = rng.permutation(P)[:C * mb].reshape(C, mb).astype(np.int32)
    pos0 = np.array([bs + 3, 0], np.int32)
    n_valid = np.array([2, 1], np.int32)

    def run(kpx, vpx, rowsx):
        a = ((lambda *x: _ragged_paged_xla(*x, "d_major")) if arm == "xla"
             else (lambda *x: ragged_paged_attention_kernel(
                 *x, pps=int(arm[-1]))))
        return np.asarray(a(q, jnp.asarray(kpx), jnp.asarray(vpx),
                            jnp.asarray(rowsx), jnp.asarray(pos0),
                            jnp.asarray(n_valid), 0.4))

    base = run(kp, vp, rows)
    pad = np.arange(qb)[None, :] >= n_valid[:, None]
    assert not base[pad].any() and base[~pad].all()
    # scramble table entries for pages wholly past each row's last pos
    rows2 = rows.copy()
    for c in range(C):
        first_dead = (pos0[c] + n_valid[c] - 1) // bs + 1
        rows2[c, first_dead:] = rng.integers(0, P, size=mb - first_dead)
    # scramble k/v content past the last valid offset within live pages
    kp2, vp2 = kp.copy(), vp.copy()
    for c in range(C):
        last = int(pos0[c] + n_valid[c] - 1)
        pg, off = rows[c, last // bs], last % bs
        kp2[pg, :, :, off + 1:] = rng.normal(
            size=kp2[pg, :, :, off + 1:].shape)
        vp2[pg, :, off + 1:, :] = rng.normal(
            size=vp2[pg, :, off + 1:, :].shape)
    assert np.array_equal(base, run(kp2, vp2, rows2))


def test_shim_delegates_bit_equal():
    """ragged_prefill (n_valid == qb) must be the unified arm exactly."""
    q, kp, vp, rows, pos0, _ = _mixed_case(seed=3)
    rows, pos0 = jnp.asarray(rows), jnp.asarray(pos0 * 0 + 16)
    full = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    a = shim._ragged_prefill_xla(q, kp, vp, rows, pos0, 0.3, "d_major")
    b = _ragged_paged_xla(q, kp, vp, rows, pos0, full, 0.3, "d_major")
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_supported_gate():
    assert ragged_paged_supported((8, 2, 128, 128), 4, 4, 4)
    assert not ragged_paged_supported((8, 2, 64, 128), 4, 4, 4)   # d
    assert not ragged_paged_supported((8, 2, 128, 16), 4, 4, 4)   # bs
    assert not ragged_paged_supported((8, 3, 128, 128), 4, 4, 4)  # GQA
    assert not ragged_paged_supported((8, 2, 128, 128), 4, 3, 4)  # rows%8
    # shim gate: qb == page_size
    assert shim.ragged_prefill_supported((8, 2, 128, 128), 4, 4)
    assert not shim.ragged_prefill_supported((8, 2, 128, 16), 4, 4)


@pytest.mark.parametrize("shape,nH,qb,mb,itemsize,want", [
    # the serving cells' geometry: every group size fits, largest first
    ((448, 8, 128, 128), 32, 16, 24, 2,
     ["kernel_p4", "kernel_p2", "kernel_p1", "xla"]),
    # a table of 6 slots: 4 does not divide it
    ((448, 8, 128, 128), 32, 16, 6, 2, ["kernel_p2", "kernel_p1", "xla"]),
    # 16 kv heads of 256: four pages a step are 16 MiB of double-buffered
    # blocks (20.2 MiB in all), two are 8 (11.1 MiB)
    ((64, 16, 256, 128), 32, 16, 24, 2, ["kernel_p2", "kernel_p1", "xla"]),
    # the same in fp32: only one page a step fits (11.6 MiB)
    ((64, 16, 256, 128), 32, 16, 24, 4, ["kernel_p1", "xla"]),
    # pages of 256 tokens in fp32: not even one (20.1 MiB)
    ((64, 16, 256, 256), 32, 16, 24, 4, ["xla"]),
    # an unsupported head width never reaches the kernel
    ((64, 8, 64, 128), 32, 16, 24, 2, ["xla"]),
])
def test_gate_admits_the_group_sizes_that_fit(shape, nH, qb, mb, itemsize,
                                              want):
    """What runs follows from the shapes: the VMEM estimate of each
    group size, and whether it divides the block table."""
    assert candidates_for(shape, nH, qb, mb, itemsize) == want
    for n in (4, 2, 1):
        if mb % n == 0:
            assert ragged_paged_supported(shape, nH, qb, itemsize, n) == (
                f"kernel_p{n}" in want)


@pytest.mark.parametrize("impl", ["kernel_p4", "kernel_p2", "kernel_p1",
                                  "xla"])
def test_dispatcher_obeys_the_autotune_choice(monkeypatch, impl):
    """The form ('kernel_p<n>' or 'xla') flows through the autotune
    registry: whatever the registry answers is what runs, and it is
    asked with the candidates the gate admits."""
    import paddle_tpu.ops.pallas.ragged_paged_attention as mod

    rng = np.random.default_rng(5)
    C, qb, nH, nkv, d, bs, mb, P = 2, 4, 4, 2, 128, 128, 4, 9
    kp = jnp.asarray(rng.normal(size=(P, nkv, d, bs)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, nkv, bs, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(C, qb, nH, d)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, P, size=(C, mb)), jnp.int32)
    pos0 = jnp.asarray([130, 0], jnp.int32)
    n_valid = jnp.asarray([1, qb], jnp.int32)
    asked = []

    def fake(C_, qb_, nH_, d_, nkv_, mb_, bs_, dtype, cands, quant=False,
             window=None):
        asked.append((C_, qb_, tuple(cands)))
        return impl
    monkeypatch.setattr(mod, "_tuned_impl", fake)
    ran = []
    inner = mod.ragged_paged_attention_kernel
    monkeypatch.setattr(
        mod, "ragged_paged_attention_kernel",
        lambda *a, pps, window=None: ran.append(pps) or inner(*a, pps=pps))
    got = mod.ragged_paged_attention(q, kp, vp, rows, pos0, n_valid, 0.5)
    if impl == "xla":
        want = _ragged_paged_xla(q, kp, vp, rows, pos0, n_valid, 0.5,
                                 "d_major")
        assert ran == []
    else:
        want = inner(q, kp, vp, rows, pos0, n_valid, 0.5,
                     pps=int(impl[-1]))
        assert ran == [int(impl[-1])]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert asked == [(C, qb, ("kernel_p4", "kernel_p2", "kernel_p1",
                              "xla"))]


def test_default_without_a_sweep_is_the_largest_group(monkeypatch):
    """A backend that never sweeps (this CPU) gets candidates[0]: the
    largest group the gate admits."""
    import paddle_tpu.ops.pallas.ragged_paged_attention as mod
    from paddle_tpu.ops.pallas import autotune

    reg = autotune.AutotuneRegistry(path="/nonexistent/none.json",
                                    committed="/nonexistent/none.json")
    monkeypatch.setattr(autotune, "GLOBAL_AUTOTUNE", reg)
    for mb, want in ((24, "kernel_p4"), (6, "kernel_p2"), (5, "kernel_p1")):
        cands = candidates_for((9, 2, 128, 128), 4, 4, mb, 4)
        assert mod._tuned_impl(2, 4, 4, 128, 2, mb, 128, jnp.float32,
                               cands) == want


@pytest.mark.parametrize("C,nH,nkv,mb,window", [
    (32, 32, 8, 24, None), (32, 32, 8, 16, None), (32, 128, 8, 196, None),
    (32, 128, 8, 196, 4096), (40, 32, 4, 24, None)])
def test_committed_table_serves_the_cells_geometry(C, nH, nkv, mb, window):
    """The Mistral cells (32 heads, mb 24), chip_smoke.py (mb 16), the
    command-a-plus cell (128 heads, mb 196: its global layer and, under
    the window, its window layers) and the granite cell (40 rows, heads
    of 64 paged in pairs of 128: 32 over 4) must find their form in the
    tracked table under the kernel's CURRENT source hash: a stale entry
    is a clean miss, every run's set-up then sweeps, and the XLA arm's
    temporaries set the run's memory peak (at mb 196 they do not fit
    the chip at all).  After an edit to the kernel, sweep on the chip
    and commit the entries (``source`` is what ``_autotune_source()``
    returns)."""
    import json

    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        _autotune_source

    entries = json.load(open(autotune.COMMITTED_PATH))["entries"]
    entry = entries["ragged_paged_attention|TPU v5 lite|"
                    f"c{C}_qb16_h{nH}_d128_kv{nkv}_mb{mb}_bs128"
                    + (f"_w{window}" if window else "") + "|bfloat16"]
    assert entry["source"] == _autotune_source()
    assert entry["config"] in candidates_for((448, nkv, 128, 128), nH, 16,
                                             mb)[:-1]


def _int8_case(seed, C, qb, nH, nkv, d, bs, mb, P):
    """int8 pages + per-page/per-kv-head scale planes, plus the
    pre-dequantized fp32 pages they encode."""
    from paddle_tpu.ops.quant import dequantize_int8

    rng = np.random.default_rng(seed)
    kq = jnp.asarray(rng.integers(-127, 128, size=(P, nkv, d, bs)),
                     jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=(P, nkv, bs, d)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, nkv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, nkv)), jnp.float32)
    kf = dequantize_int8(kq, ks[:, :, None, None])
    vf = dequantize_int8(vq, vs[:, :, None, None])
    q = jnp.asarray(rng.normal(size=(C, qb, nH, d)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, P, size=(C, mb)), jnp.int32)
    return q, kq, vq, ks, vs, kf, vf, rows


def test_xla_arm_int8_matches_predequantized_pages():
    """The XLA arm on int8 pages + scales must equal the same arm on
    pages dequantized up front — the dequant placement (per gathered
    page, before the transpose) changes nothing."""
    q, kq, vq, ks, vs, kf, vf, rows = _int8_case(7, 3, 6, 4, 2, 32, 16,
                                                 4, 12)
    pos0 = jnp.asarray([17, 0, 33], jnp.int32)
    n_valid = jnp.asarray([1, 6, 4], jnp.int32)
    got = _ragged_paged_xla(q, kq, vq, rows, pos0, n_valid, 0.3,
                            "d_major", k_scales=ks, v_scales=vs)
    ref = _ragged_paged_xla(q, kf, vf, rows, pos0, n_valid, 0.3,
                            "d_major")
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("pps", [1, 2, 4])
def test_kernel_int8_matches_xla_arm(pps):
    """Pallas kernel with scalar-prefetched scale planes vs the XLA arm,
    on the supported geometry (d=128, bs=128; interpret mode): each page
    slot of a group finds its own (page, head) scale."""
    q, kq, vq, ks, vs, _, _, rows = _int8_case(8, 3, 4, 4, 2, 128, 128,
                                               4, 8)
    pos0 = jnp.asarray([200, 0, 387], jnp.int32)
    n_valid = jnp.asarray([1, 4, 3], jnp.int32)
    got = ragged_paged_attention_kernel(q, kq, vq, rows, pos0, n_valid,
                                        0.5, k_scales=ks, v_scales=vs,
                                        pps=pps)
    ref = _ragged_paged_xla(q, kq, vq, rows, pos0, n_valid, 0.5,
                            "d_major", k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_int8_quality_delta_bounded():
    """Quantified quality delta, fixed seed (recorded in PERF.md round
    8): quantize unit-normal fp pages to per-page/per-kv-head int8 and
    pin the max-abs attention-output delta. Measured 0.206 on this
    geometry; pinned at 0.25."""
    from paddle_tpu.ops.quant import quantize_to_scale

    rng = np.random.default_rng(0)
    C, qb, nH, nkv, d, bs, mb, P = 4, 8, 4, 2, 32, 16, 6, 24
    kf = jnp.asarray(rng.normal(size=(P, nkv, d, bs)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(P, nkv, bs, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(C, qb, nH, d)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, P, size=(C, mb)), jnp.int32)
    pos0 = jnp.asarray([37, 0, 21, 3], jnp.int32)
    n_valid = jnp.asarray([1, qb, 5, 2], jnp.int32)
    ks = jnp.max(jnp.abs(kf), axis=(2, 3)) / 127.0          # [P, nkv]
    vs = jnp.max(jnp.abs(vf), axis=(2, 3)) / 127.0
    kq = quantize_to_scale(kf, ks[:, :, None, None])
    vq = quantize_to_scale(vf, vs[:, :, None, None])
    fp = _ragged_paged_xla(q, kf, vf, rows, pos0, n_valid, 0.35,
                           "d_major")
    q8 = _ragged_paged_xla(q, kq, vq, rows, pos0, n_valid, 0.35,
                           "d_major", k_scales=ks, v_scales=vs)
    delta = float(np.max(np.abs(np.asarray(fp) - np.asarray(q8))))
    assert delta < 0.25, delta


def test_dispatcher_requires_scales_for_int8_pages():
    q, kq, vq, ks, vs, _, _, rows = _int8_case(9, 2, 4, 4, 2, 32, 16,
                                               3, 8)
    pos0 = jnp.asarray([3, 0], jnp.int32)
    n_valid = jnp.asarray([1, 4], jnp.int32)
    with pytest.raises(ValueError, match="scale"):
        ragged_paged_attention(q, kq, vq, rows, pos0, n_valid, 0.5)
    with pytest.raises(ValueError, match="scale"):
        ragged_paged_attention(q, kq, vq, rows, pos0, n_valid, 0.5,
                               k_scales=ks)
    # with both planes it dispatches fine (XLA path on this geometry)
    out = ragged_paged_attention(q, kq, vq, rows, pos0, n_valid, 0.5,
                                 k_scales=ks, v_scales=vs)
    assert np.all(np.isfinite(np.asarray(out)))


def test_dispatcher_uses_xla_on_unsupported_geometry():
    q, kp, vp, rows, pos0, n_valid = _mixed_case(seed=4)
    got = ragged_paged_attention(q, kp, vp, jnp.asarray(rows),
                                 jnp.asarray(pos0),
                                 jnp.asarray(n_valid), 0.35)
    ref = _ragged_paged_xla(q, kp, vp, jnp.asarray(rows),
                            jnp.asarray(pos0), jnp.asarray(n_valid),
                            0.35, "d_major")
    assert np.array_equal(np.asarray(got), np.asarray(ref))
