"""The sliding window in unified ragged-paged attention: the kernel
(interpret mode) against the XLA arm against a dense oracle, for 16 and 4
query heads a kv head, windows that are and are not multiples of the
page, a window longer than the context, garbage ids behind the window,
and the steering of the groups the window leaves dead."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _first_page,
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


@pytest.mark.parametrize("window", [128, 256, 200, 77, 1, 640])
@pytest.mark.parametrize("G,pps", [(16, 1), (16, 2), (4, 2), (4, 4)])
def test_kernel_xla_and_oracle_agree_under_a_window(G, pps, window):
    q, kp, vp, rows, pos0, n_valid = _case(G)
    args = (jnp.asarray(pos0), jnp.asarray(n_valid), 0.3)
    got = ragged_paged_attention_kernel(q, kp, vp, jnp.asarray(rows), *args,
                                        pps=pps, window=window)
    xla = _ragged_paged_xla(
        q, kp, vp, jnp.asarray(_live_table(rows, pos0, n_valid, window)),
        *args, "d_major", window=window)
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
    """A group wholly behind the window computes nothing and names the
    row's first live group, a row ahead is entered at its first live
    group, and a slot of that group that lies behind the window names
    the first live page: over the whole grid no block is copied but a
    row's live pages (and the dead slots of its first live group)."""
    pos0 = jnp.asarray([0, 300, 1023, 5, 640, 900], jnp.int32)
    nv = jnp.asarray([1, 4, 1, 1, 3, 4], jnp.int32)
    C = len(pos0)
    last = [int((pos0[c] + nv[c] - 1) // BS) for c in range(C)]
    first = [int(max(int(pos0[c]) - window + 1, 0) // BS) for c in range(C)]
    table = np.arange(1, C * MB + 1).reshape(C, MB)

    def named(c, j):
        cc, jj, lp = (int(x) for x in _steer(c, j, pos0, nv, C, pps, BS,
                                             window))
        fp = int(_first_page(pos0[cc], window, BS))
        return cc, jj, [table[cc, max(int(_slot(jj, i, pps, lp)), fp)]
                        for i in range(pps)]

    copies, held = 0, [None] * pps
    for c in range(C):
        for j in range(MB // pps):
            cc, jj, blocks = named(c, j)
            if j * pps > last[c]:                       # past the end
                if c + 1 < C:
                    assert (cc, jj) == (c + 1, first[c + 1] // pps)
                else:
                    assert cc == c and blocks == held
            elif (j + 1) * pps * BS <= int(pos0[c]) - window + 1:
                assert (cc, jj) == (c, first[c] // pps)  # behind
            else:
                assert (cc, jj) == (c, j)
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
