"""Abstract (AOT) train-step state: parity with the materialized path.

The 13B north-star analysis (tools/aot_analyze.py) lowers the hybrid step
from ShapeDtypeStructs; these tests pin that the abstract state is
exactly the materialized state's shapes/dtypes/shardings, and that the
lowered program compiles with a usable memory analysis.

Reference discipline: test_dist_base.py runs real+parallel and compares —
here the "run" is the compile contract, cheap enough for the full tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.process_mesh import build_mesh
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.parallel import make_sharded_train_step

CFG = GPTConfig(vocab_size=512, hidden=64, n_layers=4, n_heads=4,
                seq_len=32, dtype=jnp.float32)


def _mesh():
    return build_mesh((2, 2, 2), ("dp", "pp", "mp"))


def test_abstract_state_matches_real():
    mesh = _mesh()
    kw = dict(n_microbatches=2, seed=3)
    _, p_abs, o_abs = make_sharded_train_step(CFG, mesh, abstract=True, **kw)
    _, p_real, o_real = make_sharded_train_step(CFG, mesh, **kw)

    flat_a = jax.tree.leaves(p_abs)
    flat_r = jax.tree.leaves(p_real)
    assert len(flat_a) == len(flat_r)
    for a, r in zip(flat_a, flat_r):
        assert a.shape == r.shape
        assert a.dtype == r.dtype
        assert a.sharding.is_equivalent_to(r.sharding, len(r.shape)), (
            a.sharding, r.sharding, r.shape)

    # optimizer state: shapes+dtypes match; moments at least as sharded as
    # the real path (the abstract path deliberately pre-applies the
    # megatron spec the jit would resolve them to)
    for a, r in zip(jax.tree.leaves(o_abs), jax.tree.leaves(o_real)):
        assert a.shape == r.shape
        assert a.dtype == r.dtype


@pytest.mark.parametrize("weights,m_dtype", [("auto", None),
                                             ("sr-bf16", "bfloat16")])
def test_abstract_lower_compile_memory(weights, m_dtype):
    mesh = _mesh()
    cfg = GPTConfig(vocab_size=512, hidden=64, n_layers=4, n_heads=4,
                    seq_len=32)  # bf16 compute: the 13B analysis dtype
    step, params, opt = make_sharded_train_step(
        cfg, mesh, n_microbatches=2, weights=weights, m_dtype=m_dtype,
        abstract=True)
    from jax.sharding import NamedSharding, PartitionSpec as P

    tok = jax.ShapeDtypeStruct((8, cfg.seq_len), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp")))
    with jax.sharding.set_mesh(mesh):
        compiled = step.jitted.lower(params, opt, tok, tok).compile()
    ma = compiled.memory_analysis()
    # arguments must include every param+opt shard: > params bytes / n_dev
    n_bytes = sum(np.prod(p.shape) * p.dtype.itemsize
                  for p in jax.tree.leaves(params))
    assert ma.argument_size_in_bytes > n_bytes / len(jax.devices())
    assert ma.temp_size_in_bytes > 0


def test_collective_inventory_parses():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from aot_analyze import collect_collectives

    hlo = """
  %psum.5 = bf16[2,128,768] all-reduce(%x), replica_groups={{0,1}}, to_apply=%r
  %ag = f32[16,4] all-gather(%y), replica_groups=[2,4]<=[8], dimensions={0}
  %cp = f32[8] collective-permute(%z), source_target_pairs={{0,1}}
  %done = f32[8] all-reduce-done(%cp)
"""
    out = collect_collectives(hlo)
    kinds = {c["kind"] for c in out}
    assert kinds == {"all-reduce", "all-gather", "collective-permute"}
    ar = next(c for c in out if c["kind"] == "all-reduce")
    assert ar["bytes"] == 2 * 128 * 768 * 2
