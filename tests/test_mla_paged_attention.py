"""ops/pallas/mla_paged_attention.py: the kernel (interpret mode on the
CPU) against its XLA arm, and the latent write — paged_kv_write with
planes of unequal width — against the scatter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import mla_paged_attention as mpa
from paddle_tpu.ops.pallas import paged_kv_write as pkw

C, QB, NH, R, DR, BS, MB, P = 6, 4, 2, 32, 8, 16, 4, 12


def _case(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_lat = jax.random.normal(ks[0], (C, QB, NH, R), dtype)
    q_rope = jax.random.normal(ks[1], (C, QB, NH, DR), dtype)
    ckv = jax.random.normal(ks[2], (P, BS, R), dtype)
    kr = jax.random.normal(ks[3], (P, DR, BS), dtype)
    rows = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0], [5, 6, 7, 0],
                        [8, 9, 0, 0], [10, 0, 0, 0], [0, 0, 0, 0]],
                       jnp.int32)
    # a decode row deep in its context, two adjacent prefill chunks of one
    # request (the second straddles a page: 14..17 | 18 is past), a short
    # last chunk, a decode row at position 0, an idle row on the sink
    pos0 = jnp.asarray([57, 10, 14, 20, 0, 0], jnp.int32)
    n_valid = jnp.asarray([1, 4, 4, 3, 1, 1], jnp.int32)
    return q_lat, q_rope, ckv, kr, rows, pos0, n_valid


@pytest.mark.parametrize("pps", [1, 2, 4])
def test_kernel_matches_the_xla_arm_on_every_row(pps):
    args = _case()
    want = mpa._mla_paged_xla(*args, 0.25)
    got = mpa.mla_paged_attention_kernel(*args, 0.25, pps)
    assert got.shape == (C, QB, NH, R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # padding rows are zeros from both arms
    assert not np.asarray(got)[0, 1:].any() and not np.asarray(want)[3, 3].any()


def test_xla_arm_is_causal_attention_over_the_latent():
    """The arm itself against a dense computation of one chunk."""
    q_lat, q_rope, ckv, kr, rows, pos0, n_valid = _case(1)
    got = np.asarray(mpa._mla_paged_xla(q_lat, q_rope, ckv, kr, rows, pos0,
                                        n_valid, 0.25))
    c = 2                                            # positions 14..17
    lat = np.asarray(ckv)[np.asarray(rows[c])].reshape(-1, R)
    rope = np.swapaxes(np.asarray(kr)[np.asarray(rows[c])], 1, 2).reshape(
        -1, DR)
    for i in range(4):
        n_keys = 14 + i + 1
        for h in range(NH):
            s = (lat[:n_keys] @ np.asarray(q_lat)[c, i, h]
                 + rope[:n_keys] @ np.asarray(q_rope)[c, i, h]) * 0.25
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ lat[:n_keys]
            np.testing.assert_allclose(got[c, i, h], want, rtol=1e-4,
                                       atol=1e-5)


def test_dispatcher_gates_and_names_its_candidates():
    assert mpa.mla_paged_supported((704 * 40, 128, 512), (704 * 40, 64, 128),
                                   32, 16)
    assert not mpa.mla_paged_supported((8, 16, 32), (8, 8, 16), 2, 4)
    assert mpa.candidates_for(52) == ["kernel_p4", "xla"]
    assert mpa.candidates_for(6) == ["kernel_p2", "xla"]
    assert mpa.ACCUM_DTYPE == "float32"
    # an unsupported geometry takes the XLA arm through the front door
    args = _case(2)
    np.testing.assert_array_equal(
        np.asarray(mpa.mla_paged_attention(*args, 0.25)),
        np.asarray(mpa._mla_paged_xla(*args, 0.25)))


def test_latent_write_with_planes_of_unequal_width():
    """k_rope d-major [P, 1, 8, bs] beside c_kv token-major [P, 1, bs, 32]
    through the page-write kernel: bit-equal to the scatter on every page
    a request owns, on adjacent chunks that straddle a page."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    dt = jnp.bfloat16
    kp = jax.random.normal(ks[0], (P, 1, DR, BS), dt)
    vp = jax.random.normal(ks[1], (P, 1, BS, R), dt)
    k = jax.random.normal(ks[2], (C, QB, 1, DR), dt)
    v = jax.random.normal(ks[3], (C, QB, 1, R), dt)
    _, _, _, _, rows, pos0, n_valid = _case()
    assert pkw.paged_kv_write_supported((P, 1, 64, 128), 16, 2, 512)
    assert not pkw.paged_kv_write_supported((P, 1, 64, 128), 16, 2)
    assert pkw.paged_kv_write_supported((P, 8, 128, 128), 16, 2, 128)
    got = pkw.paged_kv_write_kernel(kp, vp, k, v, rows, pos0, n_valid)
    want = pkw._paged_kv_write_xla(kp, vp, k, v, rows, pos0, n_valid, 0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g[1:], np.float32),
                                      np.asarray(w[1:], np.float32))
