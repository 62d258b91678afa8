"""ops/pallas/ragged_ssm_scan.py: the kernel (interpret mode) and the XLA
form against a per-token loop in numpy, on rows' grids that hold decode
rows, chunk rows, chained rows, idle rows and mixes of seven and more; the
state in fp32, and rounded once at the write where the pool is narrower.

Tolerance: fp32 on both sides at `highest`; the closed form of a block of
rows sums in another order than the loop (readings 5e-6 to 2e-5 on values
of size ~10), so 1e-4 on the widest difference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import ragged_ssm_scan as rss

TOL = 1e-4
DUMP = 1


def _loop(pool, x, dt, A, Bm, Cm, read, write, n_valid):
    """The recurrence token by token, run by run."""
    pool = np.array(pool, np.float32)
    C, qb, nH, hd = x.shape
    y = np.zeros((C, qb, nH, hd), np.float32)
    S = None
    for c in range(C):
        if write[c] == DUMP:
            continue
        if c == 0 or write[c] != write[c - 1]:
            S = pool[read[c]].copy()
        for j in range(n_valid[c]):
            a = np.exp(dt[c, j] * A)
            S = (a[:, None, None] * S + (dt[c, j][:, None] * x[c, j])[
                :, :, None] * Bm[c, j][None, None, :])
            y[c, j] = S @ Cm[c, j]
        if c == C - 1 or write[c + 1] != write[c]:
            pool[write[c]] = S
    return y, pool


def _case(read, write, n_valid, seed=0, qb=8, nH=8, hd=16, N=128, S=14):
    rng = np.random.default_rng(seed)
    C = len(read)
    pool = rng.normal(size=(S, nH, hd, N)).astype(np.float32)
    pool[0] = 0
    return dict(
        pool=pool, x=rng.normal(size=(C, qb, nH, hd)).astype(np.float32),
        dt=np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                              size=(C, qb, nH))).astype(np.float32),
        A=-rng.uniform(1, 16, size=(nH,)).astype(np.float32),
        Bm=rng.normal(size=(C, qb, N)).astype(np.float32),
        Cm=rng.normal(size=(C, qb, N)).astype(np.float32),
        read=np.asarray(read, np.int32), write=np.asarray(write, np.int32),
        n_valid=np.asarray(n_valid, np.int32))


def _packed(pool, dtype=jnp.float32):
    """The oracle's ``[S, nH, hd, N]`` in the pool's layout (a slot is
    ``[nH / P, N, P * hd]``: module docstring)."""
    P = rss.heads_a_tile(*pool.shape[1:3])
    return jax.vmap(lambda s: rss._pack(s, P))(jnp.asarray(pool, dtype))


def _unpacked(pool, hd):
    return np.asarray(jax.vmap(lambda s: rss._unpack(s, hd))(
        pool.astype(jnp.float32)))


def _args(case):
    """The call's operands: the grid's tokens row by row, a token's
    heads side by side."""
    R = case["x"].shape[0] * case["x"].shape[1]
    flat = {k: jnp.asarray(case[k]).reshape(R, -1) for k in ("x", "Bm", "Cm")}
    return [flat["x"], jnp.asarray(case["dt"]), jnp.asarray(case["A"]),
            flat["Bm"], flat["Cm"]] + [jnp.asarray(case[k]) for k in (
                "read", "write", "n_valid")]


def _run(case, impl, pool_dtype=jnp.float32):
    y, pool = rss.ragged_ssm_scan(_packed(case["pool"], pool_dtype),
                                  *_args(case), dump=jnp.int32(DUMP),
                                  impl=impl)
    return (np.asarray(y).reshape(case["x"].shape),
            _unpacked(pool, case["x"].shape[3]))


def _held(case):
    qb = case["x"].shape[1]
    return ((np.arange(qb)[None, :] < case["n_valid"][:, None])
            & (case["write"] != DUMP)[:, None])


# each: (read, write, n_valid); slot 0 is zeros, slot 1 the dump
GRIDS = {
    "decode_rows": ([2, 3, 4, 5], [2, 3, 4, 5], [1, 1, 1, 1]),
    "chunk_rows": ([2, 0, 4], [2, 3, 4], [8, 8, 3]),
    "chained_rows": ([2, 2, 2, 5, 5], [2, 2, 2, 9, 9], [8, 8, 5, 8, 8]),
    "idle_rows_behind": ([2, 3, 1, 1, 1], [2, 3, 1, 1, 1], [1, 8, 1, 1, 1]),
    "idle_rows_ahead": ([1, 1, 2, 2], [1, 1, 2, 2], [1, 1, 8, 2]),
    "all_idle": ([1, 1, 1], [1, 1, 1], [1, 1, 1]),
    "mix_of_nine": ([2, 0, 4, 4, 4, 9, 6, 6, 1], [2, 3, 4, 4, 4, 5, 10, 10, 1],
                    [1, 1, 8, 8, 5, 1, 8, 8, 1]),
    "mix_of_twelve": ([2, 3, 0, 5, 5, 7, 7, 7, 7, 11, 1, 1],
                      [2, 3, 4, 12, 12, 7, 7, 7, 7, 6, 1, 1],
                      [1, 1, 1, 8, 1, 8, 8, 8, 8, 4, 1, 1]),
}


@pytest.mark.parametrize("impl", ["xla", "kernel_h8"])
@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_forms_match_the_per_token_loop(grid, impl):
    case = _case(*GRIDS[grid], seed=sorted(GRIDS).index(grid))
    want_y, want_pool = _loop(**case)
    y, pool = _run(case, impl)
    held = _held(case)
    assert np.isfinite(y).all()
    assert np.abs((y - want_y)[held]).max(initial=0.0) < TOL
    # every slot but the dump: written ones advanced, the others as they
    # were (slot 0 still zeros)
    keep = np.arange(len(pool)) != DUMP
    assert np.abs(pool[keep] - want_pool[keep]).max() < TOL
    assert not pool[0].any()


@pytest.mark.parametrize("heads", ["2x64", "8x16"])
def test_heads_of_one_lane_tile_keep_their_own_dt(heads):
    """Neighbours along a tile's lanes with ``dt`` of 1e-3 and 1e-1 (the
    cell's two heads of 64, the toys' eight of 16): a spread that took
    another head's value for a lane would read a decay, and a ``dt x``, a
    hundred times off."""
    nH, hd = (int(a) for a in heads.split("x"))
    case = _case(*GRIDS["mix_of_twelve"], seed=11, nH=nH, hd=hd)
    case["dt"] = (case["dt"] * 0 + np.where(np.arange(nH) % 2, 1e-1, 1e-3)
                  ).astype(np.float32)
    want_y, want_pool = _loop(**case)
    swapped = dict(case, dt=case["dt"][..., ::-1].copy())
    assert np.abs(_loop(**swapped)[1] - want_pool).max() > 0.1
    y, pool = _run(case, f"kernel_h{nH}")
    assert np.abs((y - want_y)[_held(case)]).max() < TOL
    assert np.abs(np.delete(pool - want_pool, DUMP, 0)).max() < TOL


def test_head_blocks_of_a_larger_model_agree():
    """16 heads in blocks of 8 and of 16: the grid's outer axis."""
    case = _case(*GRIDS["mix_of_nine"], seed=3, nH=16)
    want_y, want_pool = _loop(**case)
    for impl in ("kernel_h8", "kernel_h16"):
        y, pool = _run(case, impl)
        assert np.abs((y - want_y)[_held(case)]).max() < TOL
        assert np.abs(np.delete(pool - want_pool, DUMP, 0)).max() < TOL


@pytest.mark.parametrize("impl", ["xla", "kernel_h8"])
def test_a_narrower_pool_is_rounded_once_at_the_write(impl):
    """A bf16 pool: the arithmetic is fp32 from the slot's bf16 values,
    the result rounded once."""
    case = _case(*GRIDS["chained_rows"], seed=5)
    case["pool"] = np.asarray(jnp.asarray(case["pool"], jnp.bfloat16)
                              .astype(jnp.float32))
    _, want_pool = _loop(**case)
    _, pool = _run(case, impl, jnp.bfloat16)
    want = np.asarray(jnp.asarray(want_pool, jnp.bfloat16).astype(
        jnp.float32))
    for slot in (2, 9):
        # one bf16 step at most (a value on a rounding edge)
        assert np.abs(pool[slot] - want[slot]).max() <= np.abs(
            want[slot]).max() * 2.0 ** -7


def test_the_dump_may_be_a_traced_scalar_under_jit():
    """The engine's layers find their slots at ``l * S + slot``: the dump
    is a traced value there."""
    case = _case(*GRIDS["mix_of_nine"], seed=7)
    want_y, _ = _loop(**case)
    args = [_packed(case["pool"])] + _args(case)

    @jax.jit
    def f(base, *a):
        return rss.ragged_ssm_scan(*a, dump=base + 1, impl="kernel_h8")[0]

    y = np.asarray(f(jnp.int32(0), *args)).reshape(want_y.shape)
    assert np.abs((y - want_y)[_held(case)]).max() < TOL


def test_candidates_and_the_form_where_nothing_sweeps():
    # heads of 64 lie in pairs along a tile's 128 lanes, the state's 128
    # down its sublanes
    assert rss.state_shape(64, 64, 128) == (32, 128, 128)
    assert rss.state_shape(8, 16, 128) == (1, 128, 128)
    assert rss.state_shape(4, 128, 64) == (4, 64, 128)
    real = (74,) + rss.state_shape(64, 64, 128)
    assert rss.candidates_for(real, 64, 16) == [
        "xla", "kernel_h64", "kernel_h32", "kernel_h16"]
    # heads that fill no tile of 128 lanes: the XLA form alone
    assert rss.state_shape(6, 16, 16) == (6, 16, 16)
    assert rss.candidates_for((10, 6, 16, 16), 16, 8) == ["xla"]
    # the CPU never sweeps: candidate 0
    assert rss.choose_impl(real, 64, 40, 16, jnp.float32,
                           jnp.bfloat16) == "xla"


def test_committed_table_serves_the_cells_geometry():
    """The granite-4.0-h-micro cell's scan (40 rows of 16, 64 heads of 64
    on a state of 128, fp32 pool, bf16 activations) is in the committed
    autotune table under the kernel's current source."""
    import json

    from paddle_tpu.ops.pallas import autotune

    with open(autotune.COMMITTED_PATH) as f:
        entries = json.load(f)["entries"]
    key = ("ragged_ssm_scan|TPU v5 lite|c40_qb16_h64_d64_n128|"
           "float32/bfloat16")
    assert key in entries, sorted(k for k in entries if "ssm" in k)
    assert entries[key]["source"] == rss._autotune_source()
    assert entries[key]["config"] in rss.candidates_for(
        (74, 32, 128, 128), 64, 16)


def test_work_function_counts_state_once_a_request_a_tick():
    from benchmark import harness

    work = harness.load_module("work/ragged_ssm_scan.py")
    ssm = {"heads": 64, "head_dim": 64, "d_state": 128, "layers": 36,
           "qb": 16, "itemsize": 2, "state_itemsize": 4}
    flops, nbytes = work.work({"rows": [[100, 1]] * 32, "ssm": ssm})
    # 32 decode rows: 4.89 GB of state a tick, in and out
    state = 32 * 2 * 36 * 64 * 64 * 128 * 4
    assert state == 4_831_838_208
    per_token = 2 * (2 * 4096 + 2 * 128) + 4 * 64
    assert nbytes == state + 32 * 36 * per_token
    assert flops == 32 * 36 * (work.flops_per_token(ssm)
                               + 2 * (128 + 4096))
    assert work.flops_per_token(ssm) == 2 * 64 * 64 * 128 * 2
    # a request's 40 tokens in one tick (rows of 16, 16 and 8): the state
    # still once, the blocks' causal pairs by row
    f2, b2 = work.work({"rows": [[0, 40]], "ssm": ssm})
    assert b2 == 36 * (2 * 64 * 64 * 128 * 4 + 40 * per_token)
    pairs = 2 * 16 * 17 / 2 + 8 * 9 / 2
    assert f2 == 36 * (40 * work.flops_per_token(ssm)
                       + pairs * 2 * (128 + 4096))
