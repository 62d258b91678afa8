"""The sliding window in unified ragged-paged attention: the kernel
(interpret mode) against the XLA arm against a dense oracle, for 16 and 4
query heads a kv head, windows that are and are not multiples of the
page, a window longer than the context, garbage ids behind the window,
rows at the table's end, under the window, idle and straddling a group,
int8 pages, the windowed grid's extent and its steering, and the instant
that says which grid a traced call took."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _first_page,
    _grid_steps,
    _ragged_paged_xla,
    _slot,
    _steer,
    ragged_paged_attention,
    ragged_paged_attention_kernel,
)

BS, D, MB, P = 128, 128, 8, 24


def _case(G, seed=0, qb=4, nkv=2):
    """Decode rows and chunks at depths on both sides of every window
    tried, some straddling a page, one idle row on the sink."""
    rng = np.random.default_rng(seed)
    pos0 = np.array([0, 5, 127, 300, 511, 640, 1020, 0, 257], np.int32)
    n_valid = np.array([1, qb, 2, 1, qb, 3, qb, qb, 1], np.int32)
    C = len(pos0)
    kp = jnp.asarray(rng.normal(size=(P, nkv, D, BS)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, nkv, BS, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(C, qb, nkv * G, D)), jnp.float32)
    rows = rng.integers(1, P, size=(C, MB)).astype(np.int32)
    return q, kp, vp, rows, pos0, n_valid


def _dense(q, kp, vp, rows, pos0, n_valid, sm_scale, window):
    """Per valid token, a softmax over the keys its mask admits,
    gathered from the block table; padding rows are zeros."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    C, qb, nH, d = q.shape
    nkv = kp.shape[1]
    G = nH // nkv
    out = np.zeros(q.shape)
    for c in range(C):
        ks = np.moveaxis(kp[rows[c]], 3, 1).reshape(-1, nkv, d)
        vs = np.moveaxis(vp[rows[c]], 2, 1).reshape(-1, nkv, d)
        for i in range(n_valid[c]):
            p = pos0[c] + i
            lo = 0 if window is None else max(0, p - window + 1)
            for h in range(nH):
                s = q[c, i, h] @ ks[lo:p + 1, h // G].T * sm_scale
                w = np.exp(s - s.max())
                out[c, i, h] = (w / w.sum()) @ vs[lo:p + 1, h // G]
    return out


def _live_table(rows, pos0, n_valid, window):
    """The table with every slot no query can see on the sink (what the
    engine keeps there, and what the XLA arm's gather needs)."""
    blk = np.arange(rows.shape[1])[None, :]
    live = blk <= ((pos0 + n_valid - 1) // BS)[:, None]
    if window is not None:
        live &= blk >= (np.maximum(pos0 - window + 1, 0) // BS)[:, None]
    return np.where(live, rows, 0)


def _edge_rows(kind, window, qb=4):
    """(pos0, n_valid) of one kind of row the windowed grid must get
    right: windows that end on the table's last page (the walk runs past
    the table), rows wholly under the window, idle sink rows between
    live ones, and chunks that straddle a group boundary (of 1, 2 and 4
    pages)."""
    last = MB * BS - 1
    if kind == "table_end":
        pos0 = [last - qb + 1, last, last - 200, MB * BS - BS, 5, last - 1]
        n_valid = [qb, 1, qb, 1, qb, 2]
    elif kind == "under_window":
        pos0 = [0, 3, min(window - 1, last), max(window - qb, 0),
                window // 2, 1]
        n_valid = [1, qb, 1, qb, 2, 1]
    elif kind == "sink":
        pos0 = [0, 700, 0, 0, 300, 0, 1021, 0]
        n_valid = [1, qb, 1, 1, 3, 1, 1, 1]
    else:                                           # "straddle"
        pos0 = [BS - 2, 2 * BS - 2, 4 * BS - 3, 6 * BS - 1, 3 * BS - 2,
                7 * BS - 2]
        n_valid = [qb, qb, qb, 2, qb, qb]
    return np.array(pos0, np.int32), np.array(n_valid, np.int32)


def _int8_pages(seed, nkv=2):
    """int8 pages with per-page, per-kv-head scales, and the fp32 pages
    they stand for (what the oracle and the fp XLA arm read)."""
    from paddle_tpu.ops.quant import dequantize_int8

    rng = np.random.default_rng(seed)
    kq = jnp.asarray(rng.integers(-127, 128, size=(P, nkv, D, BS)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=(P, nkv, BS, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, nkv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, nkv)), jnp.float32)
    return (kq, vq, ks, vs, dequantize_int8(kq, ks[:, :, None, None]),
            dequantize_int8(vq, vs[:, :, None, None]))


_AGREE = (
    [(G, pps, window, "mixed", False)
     for window in (128, 256, 200, 77, 1, 640)
     for G, pps in ((16, 1), (16, 2), (4, 2), (4, 4))]
    + [(4, pps, window, kind, False)
       for kind in ("table_end", "under_window", "sink", "straddle")
       for pps in (1, 2, 4) for window in (200, 640)]
    + [(4, pps, 200, kind, True) for kind in ("mixed", "table_end")
       for pps in (1, 2, 4)])


@pytest.mark.parametrize("G,pps,window,kind,int8", _AGREE)
def test_kernel_xla_and_oracle_agree_under_a_window(G, pps, window, kind,
                                                    int8):
    q, kp, vp, rows, pos0, n_valid = _case(G)
    if kind != "mixed":
        pos0, n_valid = _edge_rows(kind, window)
        rng = np.random.default_rng(11)
        rows = rng.integers(1, P, size=(len(pos0), MB)).astype(np.int32)
        # idle rows name the sink, as the engine's do
        rows[(pos0 == 0) & (n_valid == 1)] = 0
        q = jnp.asarray(rng.normal(size=(len(pos0),) + q.shape[1:]),
                        jnp.float32)
    scales = ()
    if int8:
        kq, vq, ks, vs, kp, vp = _int8_pages(seed=13)
        scales = (ks, vs)
    args = (jnp.asarray(pos0), jnp.asarray(n_valid), 0.3)
    got = ragged_paged_attention_kernel(
        q, *((kq, vq) if int8 else (kp, vp)), jnp.asarray(rows), *args,
        *scales, pps=pps, window=window)
    live = jnp.asarray(_live_table(rows, pos0, n_valid, window))
    xla = _ragged_paged_xla(q, *((kq, vq) if int8 else (kp, vp)), live,
                            *args, "d_major", *scales, window=window)
    ref = _dense(q, kp, vp, rows, pos0, n_valid, 0.3, window)
    # fp32 operands; the oracle is fp64: what is left is the order of
    # the sums (online softmax over groups against one softmax)
    np.testing.assert_allclose(np.asarray(xla), ref, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(got), ref, atol=3e-5, rtol=3e-5)
    pad = np.arange(q.shape[1])[None, :] >= n_valid[:, None]
    assert not np.asarray(got)[pad].any()
    assert not np.asarray(xla)[pad].any()


@pytest.mark.parametrize("arm", ["kernel", "xla"])
def test_a_window_longer_than_the_context_is_no_window(arm):
    q, kp, vp, rows, pos0, n_valid = _case(4, seed=3)
    rows = _live_table(rows, pos0, n_valid, None)

    def run(window):
        a = (jnp.asarray(rows), jnp.asarray(pos0), jnp.asarray(n_valid),
             0.3)
        if arm == "xla":
            return _ragged_paged_xla(q, kp, vp, *a, "d_major",
                                     window=window)
        return ragged_paged_attention_kernel(q, kp, vp, *a, pps=2,
                                             window=window)

    assert np.array_equal(np.asarray(run(None)), np.asarray(run(MB * BS)))
    assert np.array_equal(np.asarray(run(None)),
                          np.asarray(run(int(pos0.max()) + 8)))


@pytest.mark.parametrize("pps", [1, 2, 4])
def test_garbage_ids_behind_the_window_are_never_read(pps):
    """Slots wholly behind the first query's window may hold any id: the
    kernel names none of them (ids no pool has would fault), and the
    XLA arm's result does not depend on which page of the pool they
    name."""
    window = 200
    q, kp, vp, rows, pos0, n_valid = _case(4, seed=5)
    args = (jnp.asarray(pos0), jnp.asarray(n_valid), 0.3)
    clean = _live_table(rows, pos0, n_valid, window)
    behind = (np.arange(MB)[None, :]
              < (np.maximum(pos0 - window + 1, 0) // BS)[:, None])
    assert behind.any()
    want = ragged_paged_attention_kernel(q, kp, vp, jnp.asarray(clean),
                                         *args, pps=pps, window=window)
    got = ragged_paged_attention_kernel(
        q, kp, vp, jnp.asarray(np.where(behind, 10 ** 6, clean)), *args,
        pps=pps, window=window)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    rng = np.random.default_rng(9)
    other = np.where(behind, rng.integers(1, P, size=clean.shape), clean)
    xla = [_ragged_paged_xla(q, kp, vp, jnp.asarray(t), *args, "d_major",
                             window=window) for t in (clean, other)]
    assert np.array_equal(np.asarray(xla[0]), np.asarray(xla[1]))


@pytest.mark.parametrize("window", [128, 200, 384])
@pytest.mark.parametrize("pps", [1, 2, 4])
def test_groups_behind_the_window_name_the_first_live_group(pps, window):
    """The windowed grid walks ``_grid_steps`` steps a row, the first at
    the row's first live group: no step lies behind the window.  A step
    past the row's last page names the next row's first live group (the
    next row's step 0), the last row's repeat its own blocks, a slot of
    the first live group that lies behind the window names the first
    live page, and no slot names a page outside the row's
    [first page, last page], even where the walk runs past the table.
    Over the whole grid no block is copied but a row's live pages (and
    the dead slots of its first live group)."""
    qb = 4
    pos0 = jnp.asarray([0, 300, 1023, 5, 640, 900], jnp.int32)
    nv = jnp.asarray([1, 4, 1, 1, 3, 4], jnp.int32)
    C = len(pos0)
    last = [int((pos0[c] + nv[c] - 1) // BS) for c in range(C)]
    first = [int(max(int(pos0[c]) - window + 1, 0) // BS) for c in range(C)]
    table = np.arange(1, C * MB + 1).reshape(C, MB)
    n_steps = _grid_steps(MB, pps, BS, qb, window)

    def named(c, j):
        cc, g, lp = (int(x) for x in _steer(c, j, pos0, nv, C, pps, BS,
                                            window))
        fp = int(_first_page(pos0[cc], window, BS))
        return cc, g, [table[cc, max(int(_slot(g, i, pps, lp)), fp)]
                       for i in range(pps)]

    copies, held = 0, [None] * pps
    for c in range(C):
        # the grid reaches every live group of the row
        assert last[c] // pps - first[c] // pps < n_steps
        for j in range(n_steps):
            cc, g, blocks = named(c, j)
            if (first[c] // pps + j) * pps > last[c]:   # past the end
                if c + 1 < C:
                    assert (cc, g) == (c + 1, first[c + 1] // pps)
                    assert blocks == named(c + 1, 0)[2]
                else:
                    assert cc == c and blocks == held
            else:
                assert (cc, g) == (c, first[c] // pps + j)
            assert all(table[cc, first[cc]] <= b <= table[cc, last[cc]]
                       for b in blocks)
            copies += sum(b != h for b, h in zip(blocks, held))
            held = blocks
    # a row's live pages, once each, plus the slots of its first live
    # group that are dead (behind the window or past the end)
    want = 0
    for c in range(C):
        g0 = first[c] // pps * pps
        dead = (first[c] - g0) + max(0, g0 + pps - 1 - last[c])
        want += last[c] - first[c] + 1 + dead
    assert copies == want


def _grids(fn, *args):
    """The grid of every pallas_call in ``fn``'s traced program."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["grid_mapping"].grid
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)
                    elif hasattr(sub, "jaxpr"):
                        yield from walk(getattr(sub.jaxpr, "jaxpr",
                                                sub.jaxpr))

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def _shapes(C, qb, mb, nH=4, nkv=2, pages=24):
    S = jax.ShapeDtypeStruct
    return (S((C, qb, nH, D), jnp.bfloat16),
            S((pages, nkv, D, BS), jnp.bfloat16),
            S((pages, nkv, BS, D), jnp.bfloat16), S((C, mb), jnp.int32),
            S((C,), jnp.int32), S((C,), jnp.int32))


@pytest.mark.parametrize("window,qb,pps,mb", [
    (4096, 16, 2, 196),         # the command-a-plus cell: 18 against 98
    (4096, 16, 1, 196),
    (4096, 16, 4, 196),
    (200, 4, 2, 8),
    (128, 4, 4, 8),
    (256, 16, 1, 24),
    (77, 1, 1, 8),
    (1, 1, 2, 8),
    (640, 16, 2, 24),
    (2000, 4, 1, 8),            # longer than the table: every group
    (MB * BS, 16, 4, 8),
])
def test_the_windowed_grid_has_the_windows_steps(window, qb, pps, mb):
    """Under a window a row walks the most groups its window + qb - 1
    keys can straddle, never more than the table has; without one,
    every group of the table (the parent's grid, to the step)."""
    a = _shapes(2, qb, mb)
    want = min(mb // pps, math.ceil((window + qb - 1) / (pps * BS)) + 1)
    assert _grids(lambda *x: ragged_paged_attention_kernel(
        *x, 0.3, pps=pps, window=window), *a) == [(2, want)]
    assert _grids(lambda *x: ragged_paged_attention_kernel(
        *x, 0.3, pps=pps), *a) == [(2, mb // pps)]


def test_tracing_leaves_one_grid_instant_a_form():
    """Tracing the kernel leaves one ``ragged_paged_attention.grid``
    instant in the ring, with the call's name and its steps a row: 18
    for the window layers of the command-a-plus cell, 98 for its global
    layer (shapes only)."""
    from paddle_tpu import obs

    a = _shapes(32, 16, 196, nH=128, nkv=8, pages=1024)
    ring = obs.arm().tracer
    try:
        ragged_paged_attention_kernel.clear_cache()   # trace anew
        for window in (4096, None):
            jax.make_jaxpr(lambda *x: ragged_paged_attention_kernel(
                *x, 0.088, pps=2, window=window))(*a)
        got = [e["args"] for e in ring.snapshot()[0]
               if e["name"] == "ragged_paged_attention.grid"]
    finally:
        obs.arm()
    assert got == [
        {"name": "ragged_paged_attention_window", "rows": 32, "steps": 18,
         "pps": 2, "window": 4096},
        {"name": "ragged_paged_attention", "rows": 32, "steps": 98,
         "pps": 2, "window": None}]


def test_the_windowed_call_has_a_name_of_its_own():
    q, kp, vp, rows, pos0, n_valid = _case(4)
    a = (q, kp, vp, jnp.asarray(rows), jnp.asarray(pos0),
         jnp.asarray(n_valid))
    plain = str(jax.make_jaxpr(lambda *x: ragged_paged_attention_kernel(
        *x, 0.3, pps=2))(*a))
    windowed = str(jax.make_jaxpr(lambda *x: ragged_paged_attention_kernel(
        *x, 0.3, pps=2, window=256))(*a))
    assert "ragged_paged_attention_window" in windowed
    assert "ragged_paged_attention_window" not in plain
    assert "ragged_paged_attention" in plain


def test_dispatcher_passes_the_window_to_either_arm(monkeypatch):
    """The public function under a window: the kernel where the geometry
    admits it, the XLA arm where it does not, one result."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as mod

    q, kp, vp, rows, pos0, n_valid = _case(4, seed=7)
    rows = _live_table(rows, pos0, n_valid, 200)
    a = (jnp.asarray(rows), jnp.asarray(pos0), jnp.asarray(n_valid), 0.3)
    ref = _dense(q, kp, vp, rows, pos0, n_valid, 0.3, 200)
    seen = []
    monkeypatch.setattr(
        mod, "_tuned_impl",
        lambda *args: seen.append(args[-1]) or "kernel_p2")
    got = ragged_paged_attention(q, kp, vp, *a, window=200)
    assert seen == [200]
    np.testing.assert_allclose(np.asarray(got), ref, atol=3e-5, rtol=3e-5)
    monkeypatch.setattr(mod, "_tuned_impl", lambda *args: "xla")
    got = ragged_paged_attention(q, kp, vp, *a, window=200)
    np.testing.assert_allclose(np.asarray(got), ref, atol=3e-5, rtol=3e-5)
