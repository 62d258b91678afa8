"""Flagship GPT tests: functional core, eager wrapper, sharded train step,
compiled pipeline — pipeline-vs-dense equivalence is the key invariant
(reference strategy: parallel loss == serial loss, SURVEY.md §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.models.gpt import (GPT, GPTConfig, init_params, loss_fn,
                                   model_apply)
from paddle_tpu.parallel import make_sharded_train_step, pipeline_blocks_fn
from paddle_tpu.distributed.process_mesh import build_mesh

CFG = GPTConfig(vocab_size=128, hidden=32, n_layers=4, n_heads=4, seq_len=16,
                dtype=jnp.float32, use_flash=False, remat=False)


def test_remat_modes_match_no_remat():
    """remat=True (dots+flash saved) and remat="full" (flash only — the
    long-context memory mode) must compute the same loss AND gradients
    as the unrematerialized step; an unknown mode string must raise
    rather than silently pick a policy."""
    import dataclasses

    params = init_params(CFG, jax.random.PRNGKey(1))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 128, (2, 16)))
    labs = jnp.asarray(rng.randint(0, 128, (2, 16)))

    def lg(remat):
        c = dataclasses.replace(CFG, remat=remat)
        return jax.value_and_grad(lambda p: loss_fn(p, toks, labs, c))(
            params)

    loss0, g0 = jax.jit(lambda: lg(False))()
    for mode in (True, "full"):
        loss1, g1 = jax.jit(lambda mode=mode: lg(mode))()
        np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, remat="Full")


def test_functional_forward_shapes():
    params = init_params(CFG, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    logits, aux = model_apply(params, toks, CFG)
    assert logits.shape == (2, 16, 128)
    assert jnp.isfinite(logits).all()


def test_eager_gpt_trains():
    model = GPT(CFG, seed=0)
    from paddle_tpu.optimizer import AdamW

    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    rng = np.random.RandomState(0)
    toks = pt.to_tensor(rng.randint(0, 128, size=(4, 16)))
    labs = pt.to_tensor(rng.randint(0, 128, size=(4, 16)))
    losses = []
    for _ in range(5):
        loss = model.loss(toks, labs)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]


def test_pipeline_matches_dense():
    """Compiled GPipe over pp=4 must equal the plain dense stack."""
    mesh = build_mesh((1, 4, 1), ("dp", "pp", "mp"))
    params = init_params(CFG, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 128)

    dense_logits, _ = model_apply(params, toks, CFG)

    from paddle_tpu.models.gpt import block_apply
    from jax import lax

    def stage_fn(sp, x):
        def body(c, bp):
            return block_apply(bp, c, CFG), None

        out, _ = lax.scan(body, x, sp)
        return out

    bfn = pipeline_blocks_fn(stage_fn, mesh, n_microbatches=2)
    with jax.sharding.set_mesh(mesh):
        pp_logits, _ = model_apply(params, toks, CFG, blocks_fn=bfn)
    np.testing.assert_allclose(np.asarray(dense_logits),
                               np.asarray(pp_logits), rtol=2e-4, atol=2e-4)


def test_pipeline_grads_match_dense():
    mesh = build_mesh((1, 2, 1), ("dp", "pp", "mp"))
    params = init_params(CFG, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 128)
    labs = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 128)

    from paddle_tpu.models.gpt import block_apply
    from jax import lax

    def stage_fn(sp, x):
        def body(c, bp):
            return block_apply(bp, c, CFG), None

        out, _ = lax.scan(body, x, sp)
        return out

    bfn = pipeline_blocks_fn(stage_fn, mesh, n_microbatches=2)

    g_dense = jax.grad(lambda p: loss_fn(p, toks, labs, CFG))(params)
    with jax.sharding.set_mesh(mesh):
        g_pp = jax.grad(lambda p: loss_fn(p, toks, labs, CFG,
                                          blocks_fn=bfn))(params)
    for kd, kp in zip(jax.tree.leaves(g_dense), jax.tree.leaves(g_pp)):
        np.testing.assert_allclose(np.asarray(kd), np.asarray(kp),
                                   rtol=5e-3, atol=1e-4)


def test_hybrid_train_step_learns():
    cfg = GPTConfig(vocab_size=64, hidden=32, n_layers=4, n_heads=4,
                    seq_len=16, n_experts=2, n_moe_layers=1,
                    dtype=jnp.float32, use_flash=False)
    mesh = build_mesh((2, 2, 2), ("dp", "pp", "mp"))
    step, params, opt_state = make_sharded_train_step(cfg, mesh,
                                                      n_microbatches=2,
                                                      lr=1e-3)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, 64, size=(8, 16))
    labs = rng.randint(0, 64, size=(8, 16))
    losses = []
    for _ in range(4):
        loss, params, opt_state = step(params, opt_state, toks, labs)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.slow  # duplicated by tests/test_graft_entry.py (slow tier)
def test_graft_entry_contract():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", "/root/repo/__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape[-1] == 8192
    mod.dryrun_multichip(8)



import dataclasses as _dc

CFG_ATTN = _dc.replace(CFG, n_heads=2, hidden=32, use_flash=False)


def test_ring_attention_matches_dense():
    """Ring attention over a 4-way sequence ring == plain causal attention
    (fwd and grads)."""
    from paddle_tpu.parallel.ring_attention import ring_attention
    from paddle_tpu.models.gpt import _attention

    mesh = build_mesh((4,), ("sep",))
    rng = jax.random.PRNGKey(0)
    B, S, H, D = 2, 64, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, D))

    ref = _attention(q, k, v, CFG_ATTN)
    out = ring_attention(q, k, v, mesh, axis="sep", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    with jax.sharding.set_mesh(mesh):
        g_ring = jax.jit(jax.grad(lambda q: ring_attention(
            q, k, v, mesh, axis="sep", causal=True).sum()))(q)
    g_ref = jax.grad(lambda q: _attention(q, k, v, CFG_ATTN).sum())(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=2e-3, atol=2e-3)



def test_interleaved_pipeline_matches_serial():
    """VPP (2 virtual stages on pp=2) must match serial grad accumulation
    (reference: hybrid_parallel_pp_interleave tests)."""
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer, PipelineParallelWithInterleave)
    from paddle_tpu.optimizer import SGD

    strat = fleet.DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": 2, "pp_degree": 2}
    strat.pipeline_configs = {"accumulate_steps": 2, "micro_batch_size": 4}
    fleet.init(strategy=strat)

    rng = np.random.RandomState(0)
    Ws = [rng.randn(8, 8).astype(np.float32) * 0.4 for _ in range(4)]
    X = rng.randn(8, 8).astype(np.float32)
    Y = rng.randint(0, 8, size=(8,))

    def loss_fn(pred, label):
        return nn.functional.cross_entropy(pred, label)

    descs = [LayerDesc(nn.Linear, 8, 8, bias_attr=False) for _ in range(4)]
    pipe = PipelineLayer(descs, loss_fn=loss_fn,
                         num_virtual_pipeline_stages=2)
    # model-order layer i lives at _built_by_index[i]
    for i, w in enumerate(Ws):
        pipe._built_by_index[i].weight.set_value(pt.to_tensor(w))
    model = PipelineParallelWithInterleave(
        pipe, fleet.get_hybrid_communicate_group(), strat)
    opt = SGD(learning_rate=0.05, parameters=pipe.parameters())
    vpp_loss = float(model.train_batch(
        (pt.to_tensor(X), pt.to_tensor(Y)), opt).numpy())

    # serial reference with the same 2-microbatch accumulation
    serial = [nn.Linear(8, 8, bias_attr=False) for _ in range(4)]
    for l, w in zip(serial, Ws):
        l.weight.set_value(pt.to_tensor(w))
    tot = 0.0
    for k in range(2):
        h = pt.to_tensor(X[k * 4:(k + 1) * 4])
        for l in serial:
            h = l(h)
        tot += float(loss_fn(h, pt.to_tensor(Y[k * 4:(k + 1) * 4])).numpy())
    np.testing.assert_allclose(vpp_loss, tot / 2, rtol=1e-4)


def test_flash_runs_per_shard_under_a_mesh():
    """Under a dp x mp mesh the flash entries sit in a fully-manual
    shard_map over batch and heads (a bare Mosaic kernel cannot be
    partitioned; interpret mode would hide a replicated one, so the
    specs are pinned here at the jaxpr level)."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.models import gpt as G

    cfg = GPTConfig(vocab_size=64, hidden=512, n_layers=1, n_heads=4,
                    seq_len=128)
    mesh = build_mesh((2, 1, 2), ("dp", "pp", "mp"))
    qkv = jnp.zeros((4, 128, 3 * 512), jnp.bfloat16)
    with jax.sharding.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(lambda x: G._flash_qkv(x, cfg))(qkv).jaxpr
    (sm,) = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
    assert sm.params["manual_axes"] == frozenset({"dp", "pp", "mp"})
    assert sm.params["in_specs"] == (P("dp", None, None, "mp", None),)
    assert sm.params["out_specs"] == (P("dp", None, "mp", None),)
    # a batch the dp axis does not divide stays replicated over it, as
    # in the surrounding program (distributed/placement.py)
    with jax.sharding.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(lambda x: G._flash_qkv(x, cfg))(qkv[:3]).jaxpr
    (sm,) = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
    assert sm.params["in_specs"] == (P(None, None, None, "mp", None),)
    # one device: the kernel is called bare
    jaxpr = jax.make_jaxpr(lambda x: G._flash_qkv(x, cfg))(qkv).jaxpr
    assert not [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
