"""``paged_kv_write``: the Pallas kernel (interpret mode) against the XLA
scatter arm, the gate, the engine on the kernel arm, and — compiled for
a described v5e, nothing runs — the serving step's program with the
pool updated where it lies."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_kv_write as W


def _case(dtype, seed=0, C=8, qb=16, nkv=2, d=128, bs=128, n_pages=20,
          mb=4, base=0):
    """Three adjacent chunks of one request (positions 100..142: the
    second straddles a page boundary, the third is partial), a decode row
    on a page's last offset, a short prefill, three idle rows on the
    sink."""
    rng = np.random.default_rng(seed)

    def mk(shape):
        if dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(shape), dtype)

    kp, vp = mk((n_pages, nkv, d, bs)), mk((n_pages, nkv, bs, d))
    k, v = mk((C, qb, nkv, d)), mk((C, qb, nkv, d))
    rows = np.zeros((C, mb), np.int32)
    rows[0] = rows[1] = rows[2] = [3, 4, 5, 0]
    rows[3] = [7, 8, 0, 0]
    rows[4] = [9, 0, 0, 0]
    pos0 = np.array([100, 116, 132, 255, 5, 0, 0, 0], np.int32)
    n_valid = np.array([16, 16, 11, 1, 7, 1, 1, 1], np.int32)
    return (kp, vp, k, v, jnp.asarray(rows + base), jnp.asarray(pos0),
            jnp.asarray(n_valid))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("base", [0, 7], ids=["layer0", "layer1"])
def test_kernel_matches_scatter_on_every_owned_page(dtype, base):
    """Bit for bit on every page but the sink; on the sink (page
    ``base``) the kernel writes the idle rows' one token and none of the
    padding the scatter parks there."""
    args = _case(dtype, base=base)
    kp, vp = args[:2]
    got = W.paged_kv_write_kernel(*args)
    want = W._paged_kv_write_xla(*args, base)
    keep = np.arange(kp.shape[0]) != base
    for g, w, old in zip(got, want, (kp, vp)):
        g, w, old = (np.asarray(a.astype(jnp.float32)) for a in (g, w, old))
        np.testing.assert_array_equal(g[keep], w[keep])
        touched = np.nonzero((g != old).reshape(len(g), -1).any(1))[0]
        assert set(touched) <= {base, base + 3, base + 4, base + 8,
                                base + 9}, touched
    k_sink = np.asarray(got[0][base].astype(jnp.float32))
    old = np.asarray(kp[base].astype(jnp.float32))
    np.testing.assert_array_equal(k_sink[:, :, 1:], old[:, :, 1:])
    np.testing.assert_array_equal(
        k_sink[:, :, 0], np.asarray(args[2][7, 0].astype(jnp.float32)))


def test_gate_and_dispatch():
    assert W.paged_kv_write_supported((64, 8, 128, 128), 16)
    assert not W.paged_kv_write_supported((64, 8, 128, 16), 16)   # bs
    assert not W.paged_kv_write_supported((64, 8, 64, 128), 16)   # d
    assert not W.paged_kv_write_supported((64, 8, 128, 128), 256)  # qb > bs
    assert not W.paged_kv_write_supported((64, 8, 128, 128), 16, 4)  # fp32
    # an fp32 pool takes the scatter, whatever its geometry
    args = _case(jnp.float32)
    got = W.paged_kv_write(*args, sink=0)
    want = W._paged_kv_write_xla(*args, 0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_engine_streams_equal_on_both_arms(monkeypatch):
    """Pages of 128 tokens and heads of 128 take the kernel: the engine's
    token streams equal those of the same engine held to the scatter, and
    so does every page a request owns."""
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=256, hidden=256, n_layers=2, n_heads=2,
                      n_kv_heads=1, ffn_hidden=256, max_seq_len=256,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)

    def serve():
        engine = ServingEngine(cfg, max_batch=2, page_size=128, max_seq=256,
                               prefill_budget=32, qb=8)
        rng = np.random.RandomState(3)
        reqs = [Request(rid=i, prompt=rng.randint(1, 256, size=n).astype(
            np.int32), max_new_tokens=m, arrival=0.0)
            for i, (n, m) in enumerate([(121, 12), (20, 6), (9, 5)])]
        engine.run(reqs)
        return [r.out_tokens for r in reqs], engine

    kernel_streams, kernel_engine = serve()
    monkeypatch.setattr(W, "paged_kv_write_supported", lambda *a: False)
    scatter_streams, scatter_engine = serve()
    assert kernel_streams == scatter_streams
    for a, b in ((kernel_engine.k_pages, scatter_engine.k_pages),
                 (kernel_engine.v_pages, scatter_engine.v_pages)):
        np.testing.assert_array_equal(
            np.asarray(a[:, 1:].astype(jnp.float32)),
            np.asarray(b[:, 1:].astype(jnp.float32)))


# -- compiled for the chip (described, not attached): nothing runs ---------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels compile (no interpret mode) at the chip's own matmul
    precision (conftest pins ``highest`` for the CPU's fp32 numerics),
    the attention takes its kernel arm without a sweep, and the
    persistent cache stays out of it (an entry compiled for an absent
    chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    monkeypatch.setattr(flash_attention, "_INTERPRET", False)
    monkeypatch.setattr(rpa, "_tuned_impl", lambda *a, **k: "kernel_p4")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kv_quant,size", [
    (False, -1), (True, -1), (False, 0)], ids=["fp", "int8", "fp-quarter"])
def test_step_compiled_for_v5e_updates_the_pool_in_place(
        one_chip, compiled_kernels, kv_quant, size):
    """mistral-7b widths, 2 layers x 448 pages of 128 tokens: the
    compiled step aliases both pools to its outputs, holds the write and
    the attention kernels, and its temporaries are smaller than ONE
    layer's pages (fp), or hold no more than the int8 step's one copy of
    a layer's pages for the attention — no second pool, no transposed
    layer. At the whole grid (``rungs[-1]``, 512 token places) and at
    the smallest step size (128), whose gathers between the packed
    tokens and the grid must not cost the pool its place either."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, init_llama_params

    cfg = LlamaConfig(vocab_size=32000, hidden=4096, n_layers=2, n_heads=32,
                      n_kv_heads=8, ffn_hidden=14336, max_seq_len=3072,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda k: init_llama_params(cfg, k),
                            jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params=params, max_batch=32, page_size=128,
                           max_seq=3072, n_pages=448, prefill_budget=512,
                           prefix_cache=True, qb=16, kv_quant=kv_quant)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        engine.unified_arg_shapes(engine.rungs[size]))
    assert engine.rungs == (128, 512)
    compiled = engine._unified.lower(*args).compile()
    text = compiled.as_text()
    assert "paged_kv_write" in text and "ragged_paged_attention" in text
    ma = compiled.memory_analysis()
    pools = 2 * engine.k_pages.size * engine.k_pages.dtype.itemsize
    assert ma.alias_size_in_bytes >= pools
    layer = pools // cfg.n_layers
    assert ma.temp_size_in_bytes < (1.25 * layer if kv_quant
                                    else 0.5 * layer), ma


@pytest.mark.parametrize("m", [1024, 4096])
@pytest.mark.parametrize("K,N,gated,layers", [
    (4096, 4096, True, 4), (4096, 4096, False, 4),      # command-a-plus-ep8
    (2048, 768, True, 39), (768, 2048, False, 39)],     # joyai-flash-ep16
    ids=["cohere-gate_up", "cohere-down", "joyai-gate_up", "joyai-down"])
def test_grouped_expert_matmul_compiled_for_v5e_at_the_routed_cells_shapes(
        one_chip, compiled_kernels, m, K, N, gated, layers):
    """The held experts' products of both routed cells at both step sizes
    (128 and 512 places x top-8), on the tiling the kernel's rule gives,
    one layer of the whole stack by a traced offset: Mosaic takes it
    (VMEM, tile alignment), and the compiled call holds no temporary the
    size of an expert, let alone of a layer sliced out of the stack."""
    from paddle_tpu.ops.pallas import grouped_expert_matmul as gem

    tiling = gem._tiling(gem.candidates_for(m, K, N, 2)[1])
    bf16 = jnp.bfloat16

    def shape(*dims, dtype=bf16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    stack = shape(layers * 16, K, N)
    compiled = jax.jit(
        lambda *a: gem._grouped_kernel(*a, tiling=tiling)).lower(
            shape(m, K), stack, shape(16, dtype=jnp.int32),
            stack if gated else None, shape(dtype=jnp.int32)).compile()
    assert "grouped_expert_matmul" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < K * N * 2


def test_hybrid_step_compiled_for_v5e_updates_every_pool_in_place(
        one_chip, compiled_kernels, monkeypatch):
    """granite-4.0-h-micro widths, one period of ten layers (nine
    state-space, one attention), the cell's grid of 40 rows: the compiled
    step aliases the page pools and both state planes' pools to its
    outputs, holds the scan, the convolution, the write and the attention
    kernels (the heads of 64 paged in pairs of 128), and nothing else
    touches the
    recurrence's pool: no second pool, no layer's slots copied."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.granite_hybrid import (
        GraniteHybridConfig, init_granite_hybrid_params)
    from paddle_tpu.ops.pallas import ragged_ssm_scan as rss

    monkeypatch.setattr(rss, "_tuned_impl", lambda *a, **k: "kernel_h32")
    cfg = GraniteHybridConfig(
        layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
        max_seq_len=3072)
    params = jax.eval_shape(lambda k: init_granite_hybrid_params(cfg, k),
                            jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params=params, max_batch=32, page_size=128,
                           max_seq=3072, n_pages=64, prefill_budget=640,
                           prefix_cache=True, qb=16,
                           class_pages={"state": 2})
    assert engine.rungs == (160, 640) and engine.n_rows == 40
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        engine.unified_arg_shapes())
    compiled = engine._unified.lower(*args).compile()
    text = compiled.as_text()
    for kernel in ("ragged_ssm_scan", "ragged_causal_conv", "paged_kv_write",
                   "ragged_paged_attention"):
        assert kernel in text, kernel
    st = engine._state
    pools = sum(a.size * a.dtype.itemsize for a in (
        engine.k_pages, engine.v_pages, st.k_pages, st.v_pages))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pools
    # no op but the kernel makes anything of the recurrence's pool's
    # shape (a copy, a fusion over it), and the temporaries are the
    # rows' activations: under a twentieth of the cell's 5.6 GB pool
    import re

    L, S = st.v_pages.shape[:2]
    dims = ",".join(map(str, (L * S,) + st.v_pages.shape[2:]))
    makers = set(re.findall(rf"f32\[{dims}\]\S* ([a-z-]+)\(", text))
    quiet = {"bitcast", "get-tuple-element", "parameter", "custom-call"}
    assert makers <= quiet, makers
    assert ma.temp_size_in_bytes < 100e6, ma
    # the same for the conv plane's pool: its states move inside
    # ragged_causal_conv (no gather's or scatter's fusion over [L * S, K *
    # Dc]), and the flattening of [L, S, .] is no copy.  This test's pool
    # of 11 MB XLA keeps in the faster memory space over the layers' loop
    # and brings back once (an asynchronous copy, no relayout); the
    # cell's 75 MB it leaves in HBM
    conv = f"{L * S},{st.k_pages.shape[2]}"
    assert conv == f"{L * S},13056"
    makers = set(re.findall(rf"bf16\[{conv}\]\S* ([a-z-]+)\(", text))
    assert makers and makers <= quiet | {"copy-done"}, makers
    assert not re.search(rf"scatter[^\n]*bf16\[{conv}\]", text)
    # what is a value a head reaches the scan a head: no product spreads
    # it over the grid's 640 places by 4096 channels
    wide = re.findall(r"f32\[640,4096\]\S* (?:fusion|dot|convolution)\("
                      r"[^\n]*", text)
    assert not [op for op in wide if "kind=kOutput" in op
                or "convolution(" in op or " dot(" in op], wide
