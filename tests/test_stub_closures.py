"""Round-3 stub closures (VERDICT r2 item 10): class_center_sample,
embedding max_norm renorm, functional masked_multihead_attention, and
the compiled-step hang watchdog."""

import math
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F

pytestmark = pytest.mark.smoke


class TestClassCenterSample:
    def test_positives_always_sampled_and_remapped(self):
        paddle.seed(0)
        num_classes, num_samples = 100, 16
        labels = paddle.to_tensor(
            np.array([3, 42, 3, 99, 7, 56], np.int64))
        remapped, sampled = F.class_center_sample(labels, num_classes,
                                                  num_samples)
        s = np.asarray(sampled.numpy())
        r = np.asarray(remapped.numpy())
        assert s.shape == (num_samples,)
        assert len(set(s.tolist())) == num_samples       # no duplicates
        assert np.all(np.diff(s) > 0)                    # ascending
        for lab in (3, 42, 99, 7, 56):
            assert lab in s                              # positives kept
        # remapped labels index into the sampled set
        np.testing.assert_array_equal(s[r], labels.numpy())

    def test_sharded_group_offsets(self):
        paddle.seed(1)

        class FakeGroup:
            rank = 1
            nranks = 2

        # local shard holds classes [50, 100); labels outside pass through
        labels = paddle.to_tensor(np.array([10, 60, 99], np.int64))
        remapped, sampled = F.class_center_sample(
            labels, 50, 8, group=FakeGroup())
        s = np.asarray(sampled.numpy())
        r = np.asarray(remapped.numpy())
        assert np.all((s >= 50) & (s < 100))             # global ids
        assert 60 in s and 99 in s
        # out-of-shard positive remaps into rank-0's sample slots [0, 8):
        # every rank reproduces its peers' sample sets from the shared
        # seed, so the concatenated index is globally consistent
        assert 0 <= r[0] < 8
        # in-shard labels remap into rank-1's sample slots [8, 16)
        assert 8 <= r[1] < 16 and 8 <= r[2] < 16
        assert s[r[1] - 8] == 60 and s[r[2] - 8] == 99

    def test_rank_consistent_cross_shard_remap(self):
        """Rank 0 and rank 1 (same seed) must agree on every remapped
        label — the no-communication consistency contract."""

        def grp(r):
            class G:
                rank = r
                nranks = 2
            return G()

        labels = np.array([10, 60, 3, 99], np.int64)
        outs = []
        for r in (0, 1):
            paddle.seed(77)               # shared seed across "ranks"
            remapped, sampled = F.class_center_sample(
                paddle.to_tensor(labels), 50, 8, group=grp(r))
            outs.append((remapped.numpy(), sampled.numpy()))
        np.testing.assert_array_equal(outs[0][0], outs[1][0])
        # rank 0's samples contain its positives, rank 1's its own
        assert 10 in outs[0][1] and 3 in outs[0][1]
        assert 60 in outs[1][1] and 99 in outs[1][1]

    def test_too_many_positives_raises(self):
        labels = paddle.to_tensor(np.arange(10, dtype=np.int64))
        with pytest.raises(ValueError):
            F.class_center_sample(labels, 100, 4)


def test_embedding_renorm():
    from paddle_tpu.nn.functional.input import embedding_renorm_

    w = paddle.to_tensor(np.array([[3.0, 4.0],     # norm 5
                                   [0.3, 0.4],     # norm .5
                                   [6.0, 8.0]],    # norm 10, untouched
                                  np.float32))
    idx = paddle.to_tensor(np.array([0, 1, 0], np.int64))
    embedding_renorm_(w, idx, max_norm=1.0)
    out = w.numpy()
    np.testing.assert_allclose(np.linalg.norm(out[0]), 1.0, rtol=1e-4)
    np.testing.assert_allclose(out[1], [0.3, 0.4], rtol=1e-5)  # under max
    np.testing.assert_allclose(out[2], [6.0, 8.0])             # untouched


def test_masked_mha_per_batch_positions():
    """Each sequence writes and attends at its OWN length (ragged)."""
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.RandomState(7)
    B, nH, S, dH = 3, 2, 16, 8
    kc = rng.randn(B, nH, S, dH).astype(np.float32)
    vc = rng.randn(B, nH, S, dH).astype(np.float32)
    cache = jnp.asarray(np.stack([kc, vc]))
    x = rng.randn(B, 3 * nH * dH).astype(np.float32)
    lens = np.array([5, 2, 9], np.int32)
    out, new_cache = IF.masked_multihead_attention(
        jnp.asarray(x), cache_kv=cache,
        sequence_lengths=jnp.asarray(lens))
    out = np.asarray(out)
    nc = np.asarray(new_cache)
    qkv = x.reshape(B, 3, nH, dH)
    for b, t in enumerate(lens):
        kb, vb = kc.copy(), vc.copy()
        kb[b, :, t] = qkv[b, 1]
        vb[b, :, t] = qkv[b, 2]
        np.testing.assert_allclose(nc[0, b], kb[b], rtol=1e-6)
        s = np.einsum("hd,hsd->hs", qkv[b, 0], kb[b]) / math.sqrt(dH)
        s[:, t + 1:] = -1e30
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hs,hsd->hd", p, vb[b]).reshape(nH * dH)
        np.testing.assert_allclose(out[b], want, rtol=2e-4, atol=2e-5)


def test_masked_multihead_attention_functional():
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.RandomState(0)
    B, nH, S, dH = 2, 4, 128, 64
    cache = jnp.zeros((2, B, nH, S, dH), jnp.float32)
    # prefill 3 steps through the op itself, checking step 2 vs numpy
    outs = []
    for t in range(3):
        x = jnp.asarray(rng.randn(B, 3 * nH * dH), jnp.float32)
        out, cache = IF.masked_multihead_attention(
            x, cache_kv=cache,
            sequence_lengths=jnp.full((B,), t, jnp.int32))
        outs.append((x, np.asarray(out)))

    # numpy reference replay
    kc = np.zeros((B, nH, S, dH), np.float32)
    vc = np.zeros_like(kc)
    for t, (x, got) in enumerate(outs):
        qkv = np.asarray(x).reshape(B, 3, nH, dH)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        kc[:, :, t] = k
        vc[:, :, t] = v
        s = np.einsum("bhd,bhsd->bhs", q, kc) / math.sqrt(dH)
        s[:, :, t + 1:] = -1e30
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("bhs,bhsd->bhd", p, vc).reshape(B, nH * dH)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_step_watchdog_catches_hang():
    from paddle_tpu.distributed.comm_watchdog import (StepWatchdog,
                                                      watched_step)

    fired = []
    wd = StepWatchdog(timeout=0.3, on_hang=lambda tag, age: fired.append(
        tag))
    with wd.guard("hung_step"):
        time.sleep(0.8)                       # deliberately hung step
    assert fired == ["hung_step"]
    assert wd.hang_count == 1

    # a fast step never fires
    fired.clear()
    with wd.guard("ok"):
        pass
    time.sleep(0.5)
    assert not fired

    # wrapper form: blocks until ready, watchdog attached
    def step(x):
        return x * 2

    ws = watched_step(jax.jit(step), timeout=30.0)
    out = ws(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert ws.watchdog.hang_count == 0


def test_ptq_conv_and_attention_depth():
    """PTQ (VERDICT r2 weak 7): conv layers get per-channel int8 with a
    tight error budget, and attention-block inner Linears are converted
    through recursion."""
    import paddle_tpu.nn as nn
    from paddle_tpu.quantization import (PTQ, QuantizedConv2D,
                                         QuantizedLinear)

    paddle.seed(10)
    rng = np.random.RandomState(0)

    # CNN: conv+linear pipeline, 3% budget on matching calibration data
    cnn = paddle.nn.Sequential(
        nn.Conv2D(3, 8, 3, padding=1), nn.ReLU(),
        nn.Conv2D(8, 8, 3, padding=1, stride=2), nn.ReLU(),
        nn.AdaptiveAvgPool2D(1), nn.Flatten(), nn.Linear(8, 5))
    cnn.eval()
    calib = [paddle.to_tensor(rng.randn(4, 3, 16, 16).astype("float32"))
             for _ in range(4)]
    ref = cnn(calib[0]).numpy()
    ptq = PTQ()
    ptq.quantize(cnn)
    for b in calib:
        cnn(b)
    ptq.convert(cnn)
    assert isinstance(cnn[0], QuantizedConv2D)
    assert isinstance(cnn[6], QuantizedLinear)
    got = cnn(calib[0]).numpy()
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-6)
    assert rel < 0.03, rel

    # attention: the MHA's nested q/k/v/out projections convert too
    attn = nn.MultiHeadAttention(16, 2)
    attn.eval()
    x = paddle.to_tensor(rng.randn(2, 6, 16).astype("float32"))
    ref = attn(x).numpy()
    ptq2 = PTQ()
    ptq2.quantize(attn)
    for _ in range(3):
        attn(x)
    ptq2.convert(attn)
    assert isinstance(attn.q_proj, QuantizedLinear)
    assert isinstance(attn.out_proj, QuantizedLinear)
    got = attn(x).numpy()
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-6)
    assert rel < 0.03, rel

    # NHWC conv: layout must survive conversion (channel-axis dequant)
    nhwc = paddle.nn.Sequential(
        nn.Conv2D(3, 6, 3, padding=1, data_format="NHWC"), nn.ReLU())
    nhwc.eval()
    xs = [paddle.to_tensor(rng.randn(2, 8, 8, 3).astype("float32"))
          for _ in range(3)]
    ref = nhwc(xs[0]).numpy()
    p3 = PTQ()
    p3.quantize(nhwc)
    for b in xs:
        nhwc(b)
    p3.convert(nhwc)
    assert isinstance(nhwc[0], QuantizedConv2D)
    got = nhwc(xs[0]).numpy()
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-6)
    assert rel < 0.03, rel


def test_tuner_calibration():
    """Cost model anchored to real v5e measurements (VERDICT r2 weak 8):
    the calibrated efficiency reproduces the round-3 measured 350m step
    within 10%, and calibrate() back-solves a synthetic measurement."""
    import dataclasses

    from paddle_tpu.distributed.auto_tuner import (AutoTuner, Candidate,
                                                   TunerConfig,
                                                   _calibrated_efficiency)

    assert abs(_calibrated_efficiency(1024) - 0.504) < 1e-6
    assert abs(_calibrated_efficiency(2048) - 0.569) < 1e-6
    assert 0.504 < _calibrated_efficiency(1536) < 0.569   # interpolates

    # single-chip 350m shape: model estimate vs the real 375ms/b16 step
    cfg = TunerConfig(n_devices=1, global_batch_size=16, hidden=1024,
                      n_layers=24, vocab_size=50304, seq_len=1024,
                      max_mp=1, max_pp=1)
    t = AutoTuner(cfg)
    cand = t.evaluate(Candidate(dp=1, mp=1, pp=1, micro_batch=1))
    assert cand.pruned is None
    assert abs(cand.est_step_time - 0.375) / 0.375 < 0.10, \
        cand.est_step_time

    # back-solve: a measurement 2x slower than the estimate halves eff
    eff = t.calibrate(cand, cand.est_step_time * 2)
    assert abs(eff - _calibrated_efficiency(1024) / 2) < 1e-3
    recal = t.evaluate(dataclasses.replace(cand))
    assert abs(recal.est_step_time - 2 * cand.est_step_time) / \
        cand.est_step_time < 0.2


def test_ring_attention_reachable_from_flagship():
    """cfg.ring_axis wires ring attention into the sharded train step
    (VERDICT r2 weak 10): loss must match the dense-attention step."""
    from paddle_tpu.distributed.process_mesh import build_mesh
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel import make_sharded_train_step

    rng = np.random.RandomState(0)
    toks = rng.randint(0, 128, size=(4, 64))
    labs = rng.randint(0, 128, size=(4, 64))

    def run(ring_axis):
        cfg = GPTConfig(vocab_size=128, hidden=64, n_layers=2, n_heads=4,
                        seq_len=64, dtype=jnp.float32, use_flash=False,
                        remat=False, ring_axis=ring_axis)
        mesh = build_mesh((2, 1, 4), ("dp", "pp", "mp"))
        step, params, opt = make_sharded_train_step(
            cfg, mesh, lr=1e-3, zero1=False, seed=0)
        for _ in range(3):
            loss, params, opt = step(params, opt, toks, labs)
        return float(loss)

    dense = run(None)
    ring = run("mp")
    assert abs(dense - ring) < 1e-4, (dense, ring)
