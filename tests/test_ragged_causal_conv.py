"""ops/pallas/ragged_causal_conv.py: the kernel (interpret mode) and the XLA
form against a per-token loop in numpy: decode rows, chunk rows, chained
rows (a partial last chunk), fresh requests from the zero slot, a run
that leaves its state in another slot than it read, idle rows.  fp32 on
both sides; a tap's sum is four products, so 1e-5 on the widest
difference."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import ragged_causal_conv as rcc

TOL = 1e-5
ZERO, DUMP = 0, 1


def _loop(pool, x, w, b, read, write, n_valid, qb):
    """Token by token, run by run: a window of K + 1 inputs that slides."""
    S, K = pool.shape[0], w.shape[1] - 1
    Dc = x.shape[1]
    pool = np.array(pool, np.float32).reshape(S, K, Dc)
    act = np.zeros_like(x)
    hist = None
    for c in range(len(read)):
        if write[c] == DUMP:
            continue
        if c == 0 or write[c] != write[c - 1]:
            hist = list(pool[read[c]])
        for j in range(n_valid[c]):
            hist.append(x[c * qb + j])
            win = np.stack(hist[-(K + 1):])                 # [K + 1, Dc]
            a = b + (w.T * win).sum(0)
            act[c * qb + j] = a / (1 + np.exp(-a))
        if c == len(read) - 1 or write[c + 1] != write[c]:
            pool[write[c]] = np.stack(hist[-K:])
    return act, pool.reshape(S, K * Dc)


GRIDS = {
    "decode_rows": ([2, 3, ZERO, 5], [2, 3, 4, 5], [1, 1, 1, 1]),
    "chained_rows": ([2, 2, 2, 5, 5], [2, 2, 2, 9, 9], [8, 8, 5, 8, 1]),
    "a_short_first_chunk": ([ZERO, 3], [2, 3], [2, 8]),
    "mix_with_idle": ([2, ZERO, 4, 4, 4, 9, 6, 6, DUMP, DUMP],
                      [2, 3, 4, 4, 4, 5, 10, 10, DUMP, DUMP],
                      [1, 1, 8, 8, 5, 1, 8, 8, 1, 1]),
    "idle_ahead": ([DUMP, 2, 2], [DUMP, 2, 2], [1, 8, 3]),
}


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_forms_match_the_per_token_loop(grid, impl):
    read, write, n_valid = (np.asarray(a, np.int32) for a in GRIDS[grid])
    rng = np.random.default_rng(sorted(GRIDS).index(grid))
    C, qb, Dc, K, S = len(read), 8, 256, 3, 16
    pool = rng.normal(size=(S, K * Dc)).astype(np.float32)
    pool[ZERO] = 0
    x = rng.normal(size=(C * qb, Dc)).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, size=(Dc, K + 1)).astype(np.float32)
    b = rng.uniform(-0.5, 0.5, size=(Dc,)).astype(np.float32)
    want_act, want_pool = _loop(pool, x, w, b, read, write, n_valid, qb)
    act, got_pool = rcc.ragged_causal_conv(
        jnp.asarray(pool), jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
        jnp.asarray(read), jnp.asarray(write), jnp.asarray(n_valid), qb=qb,
        zero=jnp.int32(ZERO), dump=jnp.int32(DUMP), impl=impl)
    act, got_pool = np.asarray(act), np.asarray(got_pool)
    held = ((np.arange(qb)[None, :] < n_valid[:, None])
            & (write != DUMP)[:, None]).reshape(-1)
    assert np.isfinite(act).all()
    assert np.abs((act - want_act)[held]).max(initial=0.0) < TOL
    # every slot: written ones hold the run's last K inputs, the others
    # what they held (the zero slot zeros, the dump untouched)
    assert np.abs(got_pool - want_pool).max() < TOL
    assert not got_pool[ZERO].any()


# the state's move inside the kernel, on a bf16 pool of two tiles of 16
# slots (0..15, 16..31): (read, write, n_valid, the slots that change)
MOVES = {
    # a run whose last row is partial: the last K inputs land in `write`
    "partial_last_row": ([2, 2, 2], [2, 2, 2], [8, 8, 3], {2}),
    # an idle row ahead of every run and one behind a run write nothing
    "idle_ahead_and_behind": ([DUMP, 4, 4, DUMP, 7, DUMP],
                              [DUMP, 4, 4, DUMP, 7, DUMP],
                              [1, 8, 2, 1, 1, 1], {4, 7}),
    # read a snapshot slot (the other tile), write a live slot: the
    # snapshot stays; and a run the other way round beside it
    "snapshot_to_live": ([20, 20, 5], [3, 3, 21], [8, 4, 1], {3, 21}),
    # the tile at hand changes and comes back: 0, 1, 0, 1
    "tiles_revisited": ([2, 17, ZERO, 30, 9], [2, 17, 6, 30, 9],
                        [1, 1, 1, 8, 1], {2, 17, 6, 30, 9}),
    "all_idle": ([DUMP, DUMP], [DUMP, DUMP], [1, 1], set()),
}


@pytest.mark.parametrize("move", sorted(MOVES))
def test_the_kernel_moves_the_states_it_names_and_no_other(move):
    """Every slot no run writes is bit-equal to before, the written ones
    hold the run's last K inputs rounded once, and the kernel and the XLA
    form agree on the whole pool to the bit."""
    read, write, n_valid, changed = MOVES[move]
    read, write, n_valid = (np.asarray(a, np.int32)
                            for a in (read, write, n_valid))
    rng = np.random.default_rng(sorted(MOVES).index(move))
    C, qb, Dc, K, S = len(read), 8, 256, 3, 32
    pool = jnp.asarray(rng.normal(size=(S, K * Dc)), jnp.bfloat16).at[
        ZERO].set(0)
    x = rng.normal(size=(C * qb, Dc)).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, size=(Dc, K + 1)).astype(np.float32)
    b = rng.uniform(-0.5, 0.5, size=(Dc,)).astype(np.float32)
    before = np.asarray(pool.astype(jnp.float32))
    _, want = _loop(before, x, w, b, read, write, n_valid, qb)
    want = np.asarray(jnp.asarray(want, jnp.bfloat16).astype(jnp.float32))
    assert rcc._supported(Dc, K, qb, S, 2)
    got = {}
    for impl in ("xla", "kernel"):
        _, out = rcc.ragged_causal_conv(
            pool, jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
            jnp.asarray(read), jnp.asarray(write), jnp.asarray(n_valid),
            qb=qb, zero=jnp.int32(ZERO), dump=jnp.int32(DUMP), impl=impl)
        assert out.dtype == jnp.bfloat16
        got[impl] = np.asarray(out.astype(jnp.float32))
    assert (got["kernel"] == got["xla"]).all()
    stays = np.array([s not in changed for s in range(S)])
    assert (got["kernel"][stays] == before[stays]).all()
    for s in changed:
        # one bf16 step at most from the loop's fp32 (a rounding edge)
        assert np.abs(got["kernel"][s] - want[s]).max() <= 2.0 ** -7 * np.abs(
            want[s]).max()
        assert (got["kernel"][s] != before[s]).any()


def test_the_gate_names_the_served_shapes():
    assert rcc._supported(4352, 3, 16, 36 * 80, 2)     # granite-4.0-h-micro
    assert not rcc._supported(160, 3, 8, 16, 2)      # a toy's channels: XLA
    assert not rcc._supported(4352, 8, 16, 2880, 2)  # a state wider than a tile
    # a pool that is not whole tiles of slots (16 of bf16, 8 of fp32)
    assert not rcc._supported(4352, 3, 16, 36 * 74, 2)
    assert rcc._supported(4352, 3, 16, 36 * 74, 4)
