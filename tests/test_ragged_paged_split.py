"""Unified ragged-paged attention with keys wider than values and a sink
logit a head: the kernel (interpret mode) against the XLA arm against a
dense oracle, at q and k 192 beside v 128, with and without sinks, 16 and
8 query heads a kv head, with and without a window, decode rows and chunks
that straddle pages; the sink's two limits; the names the new forms take
and the instant that says so; the gate at the MiMo cell's geometry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _ragged_paged_xla,
    ragged_paged_attention,
    ragged_paged_attention_kernel,
)

BS, MB, P, DK, DV = 128, 8, 24, 192, 128


def _case(G, nkv, sink, seed=0, qb=4):
    """Decode rows and chunks at depths on both sides of a window of 128,
    some straddling a page, one idle row on the sink page."""
    rng = np.random.default_rng(seed)
    pos0 = np.array([0, 5, 127, 300, 511, 640, 1020, 0, 257, 126], np.int32)
    n_valid = np.array([1, qb, 2, 1, qb, 3, qb, qb, 1, qb], np.int32)
    C = len(pos0)
    kp = jnp.asarray(rng.normal(size=(P, nkv, DK, BS)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, nkv, BS, DV)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(C, qb, nkv * G, DK)), jnp.float32)
    rows = rng.integers(1, P, size=(C, MB)).astype(np.int32)
    sinks = (jnp.asarray(rng.normal(3.0, 1.0, size=(nkv * G,)), jnp.float32)
             if sink else None)
    return q, kp, vp, rows, pos0, n_valid, sinks


def _dense(q, kp, vp, rows, pos0, n_valid, sm_scale, window, sinks):
    """Per valid token, a softmax over the keys its mask admits and the
    head's sink, gathered from the block table; padding rows are zeros."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    C, qb, nH, dk = q.shape
    nkv, dv = kp.shape[1], vp.shape[3]
    G = nH // nkv
    out = np.zeros((C, qb, nH, dv))
    for c in range(C):
        ks = np.moveaxis(kp[rows[c]], 3, 1).reshape(-1, nkv, dk)
        vs = np.moveaxis(vp[rows[c]], 2, 1).reshape(-1, nkv, dv)
        for i in range(n_valid[c]):
            p = pos0[c] + i
            lo = 0 if window is None else max(0, p - window + 1)
            for h in range(nH):
                s = q[c, i, h] @ ks[lo:p + 1, h // G].T * sm_scale
                m = s.max() if sinks is None else max(s.max(), sinks[h])
                w = np.exp(s - m)
                den = w.sum() + (0.0 if sinks is None
                                 else np.exp(sinks[h] - m))
                out[c, i, h] = (w / den) @ vs[lo:p + 1, h // G]
    return out


def _live_table(rows, pos0, n_valid, window):
    """Every slot no query can see on the sink (what the engine keeps
    there, and what the XLA arm's gather needs)."""
    blk = np.arange(rows.shape[1])[None, :]
    live = blk <= ((pos0 + n_valid - 1) // BS)[:, None]
    if window is not None:
        live &= blk >= (np.maximum(pos0 - window + 1, 0) // BS)[:, None]
    return np.where(live, rows, 0)


@pytest.mark.parametrize("G,nkv,window,sink,pps", [
    (16, 2, None, False, 1), (16, 2, None, True, 2), (16, 1, None, True, 4),
    (8, 2, None, True, 2),
    (8, 2, 128, True, 1), (8, 2, 128, True, 2), (8, 1, 128, False, 4),
    (16, 1, 200, True, 2), (8, 2, 77, True, 4)])
def test_kernel_xla_and_oracle_agree_at_two_widths(G, nkv, window, sink,
                                                   pps):
    q, kp, vp, rows, pos0, n_valid, sinks = _case(G, nkv, sink)
    args = (jnp.asarray(pos0), jnp.asarray(n_valid), 0.07)
    got = ragged_paged_attention_kernel(q, kp, vp, jnp.asarray(rows), *args,
                                        pps=pps, window=window, sinks=sinks)
    xla = _ragged_paged_xla(
        q, kp, vp, jnp.asarray(_live_table(rows, pos0, n_valid, window)),
        *args, "d_major", window=window, sinks=sinks)
    ref = _dense(q, kp, vp, rows, pos0, n_valid, 0.07, window,
                 None if sinks is None else np.asarray(sinks))
    assert got.shape == xla.shape == (len(pos0), 4, nkv * G, DV)
    # fp32 operands; the oracle is fp64: what is left is the order of
    # the sums (online softmax over groups against one softmax)
    np.testing.assert_allclose(np.asarray(xla), ref, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(got), ref, atol=3e-5, rtol=3e-5)
    # padding rows are zeros in both arms (the pinned contract)
    pad = np.arange(4)[None, :] >= n_valid[:, None]
    assert not np.asarray(got)[pad].any() and not np.asarray(xla)[pad].any()


@pytest.mark.parametrize("window", [None, 128])
def test_a_sink_at_minus_infinity_is_no_sink_and_a_huge_one_takes_it_all(
        window):
    """exp(-inf - m) adds nothing: the plain softmax; a sink far above
    every score takes the whole mass, and the output goes to 0."""
    q, kp, vp, rows, pos0, n_valid, _ = _case(8, 2, False, seed=3)
    args = (jnp.asarray(rows), jnp.asarray(pos0), jnp.asarray(n_valid), 0.07)
    live = jnp.asarray(_live_table(rows, pos0, n_valid, window))
    for arm in ("kernel", "xla"):
        def run(sinks):
            if arm == "kernel":
                return np.asarray(ragged_paged_attention_kernel(
                    q, kp, vp, *args, pps=2, window=window, sinks=sinks))
            return np.asarray(_ragged_paged_xla(
                q, kp, vp, live, *args[1:], "d_major", window=window,
                sinks=sinks))

        plain = run(None)
        np.testing.assert_allclose(run(jnp.full((16,), -jnp.inf)), plain,
                                   atol=1e-6, rtol=1e-6)
        assert np.abs(run(jnp.full((16,), 1e4))).max() < 1e-30
        assert np.abs(plain).max() > 0.1


def test_the_forms_names_and_the_grid_instant():
    """dk != dv or sinks: ``..._split`` and ``..._split_window``; one
    width and no sinks keep the names and the instant they had.  A split
    form's instant carries the widths and whether there are sinks (the
    name the instant gives is the
    ``pallas_call``'s, which a trace on the chip shows)."""
    ring = obs.arm().tracer
    q, kp, vp, rows, pos0, n_valid, sinks = _case(8, 2, True)
    # seven rows: a shape no other test traces, so every call traces here
    q, rows, pos0, n_valid = q[:7], rows[:7], pos0[:7], n_valid[:7]
    args = (jnp.asarray(rows), jnp.asarray(pos0), jnp.asarray(n_valid), 0.07)
    for window in (None, 128):
        for s in (None, sinks):
            ragged_paged_attention_kernel(q, kp, vp, *args, pps=2,
                                          window=window, sinks=s)
    # the same widths and no sinks: today's name
    ragged_paged_attention_kernel(q[..., :DV], kp[:, :, :DV], vp, *args,
                                  pps=2, window=128)
    obs.arm()
    events, _ = ring.snapshot()
    grids = [e["args"] for e in events
             if e["name"] == "ragged_paged_attention.grid"]
    names = {(g["name"], g.get("dk"), g.get("dv"), g.get("sink"))
             for g in grids}
    assert names == {("ragged_paged_attention_split", DK, DV, False),
                     ("ragged_paged_attention_split", DK, DV, True),
                     ("ragged_paged_attention_split_window", DK, DV, False),
                     ("ragged_paged_attention_split_window", DK, DV, True),
                     ("ragged_paged_attention_window", None, None, None)}


def test_the_gate_at_the_mimo_cells_geometry():
    """q and k 192 beside v 128 on pages of 128: the kernel for GQA 16
    (the full layers, 4 kv heads) at 4, 2 and 1 pages a step, and for
    GQA 8 (the window layers, 8 kv heads) at 2 and 1, whose working set
    at 4 is over the VMEM bound; one width keeps its gate (192 alone is
    no width the kernel runs)."""
    assert rpa.candidates_for((9216, 4, DK, BS), 64, 16, 516, 2, DV) == [
        "kernel_p4", "kernel_p2", "kernel_p1", "xla"]
    assert rpa.candidates_for((192, 8, DK, BS), 64, 16, 516, 2, DV) == [
        "kernel_p2", "kernel_p1", "xla"]
    assert rpa.candidates_for((10, 2, DK, BS), 16, 4, 8) == ["xla"]
    assert rpa.candidates_for((10, 2, 48, 4), 16, 4, 8, 2, 32) == ["xla"]
    # the counts of one width are what they were
    for nkv, d, pps in ((8, 128, 4), (8, 128, 2), (4, 128, 4)):
        assert rpa._vmem_bytes(nkv, 64, d, BS, 2, pps) == rpa._vmem_bytes(
            nkv, 64, d, BS, 2, pps, d)


def test_the_dispatcher_passes_the_sinks_to_either_arm(monkeypatch):
    """``ragged_paged_attention`` at a geometry the kernel takes and at
    one it does not: both read the sinks, and agree."""
    q, kp, vp, rows, pos0, n_valid, sinks = _case(8, 2, True, seed=5)
    live = jnp.asarray(_live_table(rows, pos0, n_valid, 128))
    args = (live, jnp.asarray(pos0), jnp.asarray(n_valid), 0.07)
    monkeypatch.setattr(rpa, "_tuned_impl", lambda *a, **k: "kernel_p2")
    kernel = ragged_paged_attention(q, kp, vp, *args, window=128,
                                    sinks=sinks)
    monkeypatch.setattr(rpa, "_tuned_impl", lambda *a, **k: "xla")
    xla = ragged_paged_attention(q, kp, vp, *args, window=128, sinks=sinks)
    plain = ragged_paged_attention(q, kp, vp, *args, window=128)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(xla),
                               atol=3e-5, rtol=3e-5)
    assert np.abs(np.asarray(plain) - np.asarray(xla)).max() > 1e-3


@pytest.mark.parametrize("nkv,window", [(4, None), (8, 128)],
                         ids=["full", "window"])
def test_committed_table_serves_the_mimo_cells_geometry(nkv, window):
    """The MiMo cell's two forms (full layers: 4 kv heads, no window, no
    sinks; window layers: 8 kv heads, sinks, window 128) find their form
    in the tracked table under the kernel's current source hash, as the
    other cells' do (tests/test_ragged_paged_attention.py), so that no
    run of the cell sweeps (a sweep's XLA arm does not fit beside the
    cell's pools)."""
    import json

    from paddle_tpu.ops.pallas import autotune

    entries = json.load(open(autotune.COMMITTED_PATH))["entries"]
    entry = entries["ragged_paged_attention|TPU v5 lite|"
                    f"c32_qb16_h64_d192_kv{nkv}_mb516_bs128_dv128"
                    + ("_sink_w128" if window else "") + "|bfloat16"]
    assert entry["source"] == rpa._autotune_source()
    assert entry["config"] in rpa.candidates_for(
        (9216, nkv, DK, BS), 64, 16, 516, 2, DV)[:-1]
