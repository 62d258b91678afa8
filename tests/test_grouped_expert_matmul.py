"""ops/pallas/grouped_expert_matmul.py: the kernel (interpret mode on
the CPU) against a plain per-group loop in fp32, its visit metadata
against a loop, and ``moe_ffn`` through the kernel against ``moe_ffn``
through ``lax.ragged_dot`` on toy layers of both routed families."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import routed_experts
from paddle_tpu.ops.pallas import grouped_expert_matmul as gem

M, K, N, COUNT, LAYERS = 256, 256, 256, 4, 3

# tokens per group, against row tiles of 32..256
SIZES = {
    "empty_groups": [0, 40, 0, 9],
    "smaller_than_a_tile": [5, 7, 3, 2],
    "equal_to_a_tile": [32, 64, 128, 32],
    "larger_than_a_tile": [200, 0, 30, 0],
    "straddles_two_tiles": [20, 30, 100, 1],
    "every_row_held": [64, 64, 64, 64],
    "no_row_held": [0, 0, 0, 0],
}


def _loop(lhs, rhs, up, sizes, base):
    """The plain loop, fp32 throughout: the gated pair's activation and
    product too (the contract rounds them once, at the end)."""
    lhs, out, r0 = np.asarray(lhs, np.float32), [], 0
    for g, n in enumerate(sizes):
        x = lhs[r0:r0 + n]
        a = x @ np.asarray(rhs[base + g], np.float32)
        if up is not None:
            a = np.asarray(jax.nn.silu(a)) * (
                x @ np.asarray(up[base + g], np.float32))
        out.append(a)
        r0 += n
    return np.concatenate(out) if out else np.zeros((0, rhs.shape[2]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("tm", [32, 64, 128, 256])
@pytest.mark.parametrize("case", list(SIZES))
def test_kernel_matches_a_plain_loop(case, tm, dtype):
    """Layer 1 of a flattened stack of three (the other layers' weights
    poisoned), rows behind the last group poisoned in ``lhs``: the
    groups' rows are the loop's, plain and gated, over two k and two n
    tiles; what lies behind them in a visited tile is zero."""
    sizes = SIZES[case]
    n = sum(sizes)
    ks = jax.random.split(jax.random.PRNGKey(len(case) + tm), 3)
    lhs = jax.random.normal(ks[0], (M, K), dtype).at[n:].set(jnp.nan)
    own = slice(COUNT, 2 * COUNT)
    rhs, up = (jnp.full((LAYERS * COUNT, K, N), jnp.nan, dtype).at[own].set(
        jax.random.normal(k, (COUNT, K, N), dtype) * 0.1) for k in ks[1:])
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for gate_up in ((rhs, None), (rhs, up)):
        got = np.asarray(gem._grouped_kernel(
            lhs, gate_up[0], jnp.asarray(sizes, jnp.int32), gate_up[1],
            jnp.int32(COUNT), tiling=(tm, K // 2, N // 2)), np.float32)
        want = _loop(lhs, *gate_up, sizes, COUNT)
        assert got.shape == (M, N)
        np.testing.assert_allclose(got[:n], want, rtol=tol, atol=tol)
        visited = -(-n // tm) * tm
        assert not got[n:visited].any()       # zeros, never the poison


@pytest.mark.parametrize("tm", [32, 128])
@pytest.mark.parametrize("case", list(SIZES))
def test_visits_are_each_groups_row_tiles_in_order(case, tm):
    sizes = SIZES[case]
    (gid, tile, lo, hi, first), n = gem._visits(
        jnp.asarray(sizes, jnp.int32), M, tm)
    want, r0 = [], 0
    for g, s in enumerate(sizes):
        want += [(g, t, r0, r0 + s)
                 for t in range(r0 // tm, -(-(r0 + s) // tm))] if s else []
        r0 += s
    n = int(n)
    assert n == len(want) <= M // tm + COUNT - 1 == gid.shape[0]
    got = list(zip(*(np.asarray(a)[:n].tolist() for a in (gid, tile, lo,
                                                            hi))))
    assert got == want
    tiles = [t for _, t, _, _ in want]
    assert np.asarray(first)[:n].tolist() == [
        int(i == 0 or t != tiles[i - 1]) for i, t in enumerate(tiles)]


def test_xla_arm_addresses_a_layer_of_the_stack():
    """``lax.ragged_dot`` with the other layers' groups empty is the
    loop too, and gives zeros behind the last group."""
    sizes = SIZES["straddles_two_tiles"]
    n = sum(sizes)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    lhs = jax.random.normal(ks[0], (M, K), jnp.float32)
    rhs, up = (jax.random.normal(k, (LAYERS * COUNT, K, N)) * 0.1
               for k in ks[1:])
    got = np.asarray(gem._grouped_xla(lhs, rhs, jnp.asarray(sizes), up,
                                      jnp.int32(2 * COUNT)))
    np.testing.assert_allclose(
        got[:n], _loop(lhs, rhs, up, sizes, 2 * COUNT),
        rtol=2e-5, atol=2e-5)
    assert not got[n:].any()


def test_off_the_tpu_the_registry_answers_xla_and_the_gate_holds():
    assert gem.choose_impl(4096, 4096, 4096, 16, jnp.bfloat16, True) == "xla"
    assert not gem._supported(96, 64, 32, jnp.bfloat16)
    assert gem.row_tile("xla") == 512
    assert gem.row_tile("kernel_m64_k2048_n768") == 64
    # the kernel's one tiling: 128 rows, <= 512 columns, K as deep as a
    # 4 MiB weight block allows
    for shape, want in (((4096, 4096, 4096, 2), (128, 4096, 512)),
                        ((4096, 4096, 4096, 4), (128, 2048, 512)),
                        ((1024, 2048, 768, 2), (128, 2048, 384)),
                        ((4096, 768, 2048, 2), (128, 768, 512)),
                        ((96, 16384, 640, 2), (32, 16384, 128))):
        xla, kernel = gem.candidates_for(*shape)
        assert xla == "xla" and gem._tiling(kernel) == want


# -- moe_ffn through the kernel ---------------------------------------------

H, F, E, TOKENS = 64, 32, 8, 48
FAMILIES = {
    # sigmoid scores with a correction bias, scaled weights, one shared
    # expert (mla_moe); no bias, shared experts averaged (cohere_moe)
    "mla_moe": (routed_experts.Routing(2, (2, 4), jnp.bfloat16, scaling=2.5),
                True),
    "cohere_moe": (routed_experts.Routing(2, (4, 4), jnp.bfloat16,
                                          shared_scale=0.5), False),
}


def _toy_layers(key, count, bias):
    """Three layers' weights ``[3, ...]`` (bf16 values)."""
    ks = iter(jax.random.split(key, 16))

    def w(*shape, std=0.1):
        return (jax.random.normal(next(ks), (LAYERS,) + shape) * std).astype(
            jnp.bfloat16)

    lp = {"router": w(H, E, std=1.0), "we_gate": w(count, H, F),
          "we_up": w(count, H, F), "we_down": w(count, F, H),
          "ws_gate": w(H, F), "ws_up": w(H, F), "ws_down": w(F, H)}
    if bias:
        lp["router_bias"] = w(E, std=0.01).astype(jnp.float32)
    return lp


@pytest.mark.parametrize("stacked", [False, True], ids=["own", "stack"])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_moe_ffn_through_the_kernel_agrees_with_ragged_dot(
        family, masked, stacked, monkeypatch):
    r, bias = FAMILIES[family]
    count = r.held[1]
    layers = _toy_layers(jax.random.PRNGKey(len(family)), count, bias)
    lp = jax.tree.map(lambda a: a[1], layers)
    stack = poisoned = None
    if stacked:
        # layer 1 among all three; for the kernel the others' experts
        # are poisoned (XLA:CPU's ragged_dot multiplies them by zero)
        flat = {name: layers[name].reshape((-1,) + layers[name].shape[2:])
                for name in routed_experts.EXPERT_STACKS}
        bad = {name: w.at[:count].set(jnp.nan).at[2 * count:].set(jnp.nan)
               for name, w in flat.items()}
        stack, poisoned = (flat, jnp.int32(1)), (bad, jnp.int32(1))
        lp = {k: v for k, v in lp.items()
              if k not in routed_experts.EXPERT_STACKS}
    h = jax.random.normal(jax.random.PRNGKey(5), (TOKENS, H), jnp.bfloat16)
    valid = jnp.arange(TOKENS) % 5 != 0 if masked else None
    want, sizes = routed_experts.moe_ffn(h, lp, r, valid, stack)
    monkeypatch.setattr(
        gem, "choose_impl",
        lambda m, K, N, count, dtype, gated: f"kernel_m32_k{K}_n{N}")
    got, sizes_k = routed_experts.moe_ffn(h, lp, r, valid, poisoned)
    tile = routed_experts.row_tile(TOKENS, lp if stack is None else flat, r)
    assert int(tile) == 32 and 0 < int(sizes.sum()) < TOKENS * r.k
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes_k))
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    # one bf16 rounding of y's magnitude
    assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()


@pytest.mark.parametrize("family", list(FAMILIES))
def test_rows_behind_the_last_group_never_reach_y(family, monkeypatch):
    """The kernel never visits the row tiles behind the last group and
    XLA:TPU's ``ragged_dot`` leaves those rows unwritten: ``out`` holds
    whatever the buffer held there.  With NaN in every such row of both
    products, ``y`` is what it was."""
    r, bias = FAMILIES[family]
    lp = jax.tree.map(lambda a: a[0], _toy_layers(
        jax.random.PRNGKey(11), r.held[1], bias))
    h = jax.random.normal(jax.random.PRNGKey(6), (TOKENS, H), jnp.bfloat16)
    want, sizes = routed_experts.moe_ffn(h, lp, r)
    product = gem.grouped_expert_matmul

    def poisoned(lhs, rhs, sizes, *rest, **kw):
        out = product(lhs, rhs, sizes, *rest, **kw)
        behind = jnp.arange(out.shape[0]) >= sizes.sum()
        return jnp.where(behind[:, None], jnp.nan, out)

    monkeypatch.setattr(gem, "grouped_expert_matmul", poisoned)
    got, _ = routed_experts.moe_ffn(h, lp, r)
    assert 0 < int(sizes.sum()) < TOKENS * r.k
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_tile_rows_round_each_met_expert_up_to_the_layers_tile():
    r = routed_experts.Routing(8, (32, 4), jnp.bfloat16)
    ys = [None,                                     # a dense group
          (np.array([[3, 0, 130, 64], [1, 1, 1, 1]]), np.array([64, 64])),
          (np.array([5, 0, 0, 700]), np.array(512))]
    st = routed_experts.held_expert_stats(ys, 20, r)
    assert set(st) == set(routed_experts.STATS_KEYS)
    assert st["moe_assigned_held"] == 197 + 4 + 705
    assert st["moe_assigned_all"] == 8 * 20 * 3
    assert st["moe_tile_rows"] == (64 + 0 + 192 + 64) + 4 * 64 + (512 + 1024)
    assert st["moe_assigned_at_max"] == 4 * (130 + 1 + 700)


@pytest.mark.parametrize("m", [1024, 4096])
@pytest.mark.parametrize("K,N,gated", [
    (4096, 4096, True), (4096, 4096, False),      # command-a-plus-ep8
    (2048, 768, True), (768, 2048, False)])       # joyai-flash-ep16
def test_committed_table_serves_the_routed_cells_shapes(m, K, N, gated):
    """Both routed cells' products at both step sizes (128 and 512
    places x top-8) must find their arm in the tracked table under the
    kernel's CURRENT source hash: a stale entry is a clean miss and
    every run's set-up then sweeps.  After an edit to the kernel, sweep
    on the chip and commit the entries (``source`` is what
    ``_autotune_source()`` returns)."""
    import json

    from paddle_tpu.ops.pallas import autotune

    entries = json.load(open(autotune.COMMITTED_PATH))["entries"]
    entry = entries["grouped_expert_matmul|TPU v5 lite|"
                    f"m{m}_k{K}_n{N}_g16_{'gated' if gated else 'plain'}"
                    "|bfloat16"]
    assert entry["source"] == gem._autotune_source()
    assert entry["config"] in gem.candidates_for(m, K, N, 2)
