"""Fusion-pass golden tests (ISSUE 15 tentpole).

Three layers of pinning:

- per-template golden jaxprs: a minimal chain each template MUST match,
  and a near-miss (wrong axis / exact gelu / rank-2 bias / foreign
  tables) that must NOT match — the catalog recognizes lowerings, so a
  matcher loosened by accident fails here first;
- the off switch: ``use_auto_fusion=0`` must produce a jaxpr
  bit-identical to the unwrapped function (the wrapper is a transparent
  passthrough, not a no-op rewrite);
- model rediscovery: the pass must find both PR 6 hand-wired sites
  (rms/layer norm epilogues, rope+flash) plus the never-hand-wired
  activation chains (swiglu, bias+gelu) from the real model jaxprs
  alone, inside scan and remat bodies.

Note the pytest harness runs an 8-device virtual CPU platform
(conftest.py), which turns OFF the fused_bias_act kernel gate
(single-program only): activation sites are still discovered and
reported, but stay ``applied=False`` here.  The single-device
subprocess gates (tools/fusion_smoke.py, compiler_program_worker.py)
cover the applied arm.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.compiler import auto_fuse, discover, last_report
from paddle_tpu.core.flags import GLOBAL_FLAGS

pytestmark = pytest.mark.smoke

B, T, H, F = 1, 256, 256, 512


@pytest.fixture
def fusion_flags():
    names = ("use_auto_fusion", "use_fused_norm_epilogue",
             "use_fused_rope_attention", "use_fused_bias_act")
    old = {n: (GLOBAL_FLAGS.get(n) if GLOBAL_FLAGS.has(n) else True)
           for n in names}
    yield
    for n, v in old.items():
        GLOBAL_FLAGS.set(n, v)


def _rms(x, g, eps=1e-5):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _layer(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(x32.var(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (B, T, H), jnp.bfloat16)
    s = jax.random.normal(ks[1], (B, T, H), jnp.bfloat16)
    g = jax.random.normal(ks[2], (H,), jnp.bfloat16)
    b = jax.random.normal(ks[3], (H,), jnp.bfloat16)
    return x, s, g, b


def _check_parity(fn, *args):
    """auto_fuse(fn) must be bit-identical to fn in eager (op-by-op)."""
    ref = fn(*args)
    got = auto_fuse(fn)(*args)
    for r, o in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(r, np.float32),
                                      np.asarray(o, np.float32))


# ---------------------------------------------------------------------------
# golden matches
# ---------------------------------------------------------------------------

def test_rms_epilogue_matches_norm_only():
    x, _, g, _ = _operands()
    rep = discover(lambda x, g: _rms(x, g) * 2.0, x, g)
    assert [s["template"] for s in rep.sites] == ["rms_epilogue"]
    assert rep.n_applied == 1
    _check_parity(lambda x, g: _rms(x, g) * 2.0, x, g)


def test_rms_epilogue_matches_residual():
    x, s, g, _ = _operands()

    def fn(x, s, g):
        r = x + s
        return r, _rms(r, g)

    rep = discover(fn, x, s, g)
    assert [s["template"] for s in rep.sites] == ["rms_epilogue"]
    assert rep.n_applied == 1
    _check_parity(fn, x, s, g)


def test_layer_epilogue_matches_residual_bias():
    x, s, g, b = _operands()

    def fn(x, s, g, b):
        r = x + s + b.astype(x.dtype)
        return r, _layer(r, g, b)

    rep = discover(fn, x, s, g, b)
    assert [s["template"] for s in rep.sites] == ["layer_epilogue"]
    assert rep.n_applied == 1
    _check_parity(fn, x, s, g, b)


def test_bias_gelu_matches():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    h = jax.random.normal(ks[0], (B, T, F), jnp.bfloat16)
    b = jax.random.normal(ks[1], (F,), jnp.bfloat16)

    def fn(h, b):
        return jax.nn.gelu(h + b.astype(h.dtype), approximate=True)

    rep = discover(fn, h, b)
    assert [s["template"] for s in rep.sites] == ["bias_gelu"]
    _check_parity(fn, h, b)


def test_swiglu_matches():
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    gate = jax.random.normal(ks[0], (B, T, F), jnp.bfloat16)
    up = jax.random.normal(ks[1], (B, T, F), jnp.bfloat16)

    def fn(gate, up):
        return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up

    rep = discover(fn, gate, up)
    assert [s["template"] for s in rep.sites] == ["swiglu"]
    _check_parity(fn, gate, up)


def _rope_operands(nH=2, dH=128):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, T, nH, dH), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, nH, dH), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, nH, dH), jnp.bfloat16)
    inv = 1.0 / (10000.0 ** (np.arange(0, dH, 2) / dH))
    ang = np.outer(np.arange(T), inv)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    return q, k, v, cos, sin


def _apply_rope(x, cos, sin):
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def test_rope_attention_matches_both_chains():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v, cos, sin = _rope_operands()

    def fn(q, k, v, cos, sin):
        return flash_attention_raw(_apply_rope(q, cos, sin),
                                   _apply_rope(k, cos, sin), v, causal=True)

    rep = discover(fn, q, k, v, cos, sin)
    assert [s["template"] for s in rep.sites] == ["rope_attention"]
    assert rep.n_applied == 1
    # both chains consumed: q rope (10) + k rope (10) + flash (1)
    assert rep.sites[0]["eqns"] == 21
    _check_parity(fn, q, k, v, cos, sin)


def test_rope_attention_escaping_k_falls_back_to_q_only():
    """The prefill wiring: the rotated k is also a function output (it
    fills the decode cache), so consuming its chain would hide a value
    the caller needs — the validator must reject the both-chain
    candidate and the q-only candidate must win, passing the rotated k
    verbatim."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v, cos, sin = _rope_operands()

    def fn(q, k, v, cos, sin):
        kr = _apply_rope(k, cos, sin)
        return flash_attention_raw(_apply_rope(q, cos, sin), kr, v,
                                   causal=True), kr

    rep = discover(fn, q, k, v, cos, sin)
    assert [s["template"] for s in rep.sites] == ["rope_attention"]
    assert rep.n_applied == 1
    assert rep.sites[0]["eqns"] == 11   # q chain + flash only
    _check_parity(fn, q, k, v, cos, sin)


def test_shared_rope_tables_fuse_every_layer():
    """cos/sin are computed once and shared by all layers (and by the q
    and k chains): the table broadcasts must stay OUTSIDE each site's
    consumed region or only the first layer could fuse."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v, cos, sin = _rope_operands()

    def fn(q, k, v, cos, sin):
        o = flash_attention_raw(_apply_rope(q, cos, sin),
                                _apply_rope(k, cos, sin), v, causal=True)
        return flash_attention_raw(_apply_rope(o, cos, sin),
                                   _apply_rope(k, cos, sin), v, causal=True)

    rep = discover(fn, q, k, v, cos, sin)
    assert [s["template"] for s in rep.sites] == ["rope_attention"] * 2
    assert rep.n_applied == 2


# ---------------------------------------------------------------------------
# near-misses: must NOT match
# ---------------------------------------------------------------------------

def test_rms_wrong_axis_no_match():
    x, _, g, _ = _operands()

    def fn(x, g):
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt((x32 * x32).mean(-2, keepdims=True) + 1e-5)
        return (y * g.astype(jnp.float32)).astype(x.dtype)

    assert discover(fn, x, g).n_sites == 0


def test_layer_nonzero_ddof_no_match():
    """var(ddof=1) is a different statistic than the kernel computes."""
    x, _, g, b = _operands()

    def fn(x, g, b):
        x32 = x.astype(jnp.float32)
        mu = x32.mean(-1, keepdims=True)
        y = (x32 - mu) * lax.rsqrt(x32.var(-1, keepdims=True, ddof=1)
                                   + 1e-5)
        return (y * g.astype(jnp.float32)
                + b.astype(jnp.float32)).astype(x.dtype)

    assert discover(fn, x, g, b).n_sites == 0


def test_exact_gelu_no_match():
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    h = jax.random.normal(ks[0], (B, T, F), jnp.bfloat16)
    b = jax.random.normal(ks[1], (F,), jnp.bfloat16)

    def fn(h, b):
        return jax.nn.gelu(h + b.astype(h.dtype), approximate=False)

    assert discover(fn, h, b).n_sites == 0


def test_rank2_bias_no_bias_gelu_match():
    """The moe expert bias is (E, 1, F)-indexed, not a (F,) vector —
    the template must not claim it."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    h = jax.random.normal(ks[0], (B, T, F), jnp.bfloat16)
    b = jax.random.normal(ks[1], (T, F), jnp.bfloat16)

    def fn(h, b):
        return jax.nn.gelu(h + b.astype(h.dtype), approximate=True)

    assert discover(fn, h, b).n_sites == 0


def test_foreign_tables_fuse_q_only():
    """q and k rotated with DIFFERENT tables is not one rope site: only
    the q rotation may fuse (k's tables are not the kernel's)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v, cos, sin = _rope_operands()
    cos2, sin2 = cos + 1.0, sin + 1.0

    def fn(q, k, v, cos, sin, cos2, sin2):
        return flash_attention_raw(_apply_rope(q, cos, sin),
                                   _apply_rope(k, cos2, sin2), v,
                                   causal=True)

    rep = discover(fn, q, k, v, cos, sin, cos2, sin2)
    assert [s["template"] for s in rep.sites] == ["rope_attention"]
    assert rep.sites[0]["eqns"] == 11   # q chain + flash only


def test_sharding_constraint_blocks_norm_fusion(fusion_flags):
    """The matcher must refuse to fuse across an explicit resharding
    point (the sequence-parallel ln2 site): value-preserving, but the
    constraint the user asked for would end up INSIDE the kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    x, s, g, _ = _operands()
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("sp",))
    sh = NamedSharding(mesh, PartitionSpec(None, "sp", None))

    def fn(x, s, g):
        r = jax.lax.with_sharding_constraint(x + s, sh)
        return r, _rms(r, g)

    rep = discover(fn, x, s, g)
    assert rep.n_applied == 0
    assert any(s["note"] == "resharded" for s in rep.sites) or not rep.sites


# ---------------------------------------------------------------------------
# the off switch
# ---------------------------------------------------------------------------

def _strip_addrs(s: str) -> str:
    return re.sub(r"0x[0-9a-fA-F]+", "0x", s)


def test_flag_off_jaxpr_is_bit_identical(fusion_flags):
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=128, hidden=256, n_layers=2, n_heads=2,
                        n_kv_heads=2, ffn_hidden=512, max_seq_len=256,
                        dtype=jnp.bfloat16)
    params = L.init_llama_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 128)

    raw = functools.partial(L._llama_apply_unfused, cfg=cfg, remat=True)
    GLOBAL_FLAGS.set("use_auto_fusion", False)
    wrapped_jaxpr = jax.make_jaxpr(auto_fuse(raw))(params, tokens)
    raw_jaxpr = jax.make_jaxpr(raw)(params, tokens)
    assert _strip_addrs(str(wrapped_jaxpr)) == _strip_addrs(str(raw_jaxpr))


def test_flag_off_is_passthrough(fusion_flags):
    x, _, g, _ = _operands()
    GLOBAL_FLAGS.set("use_auto_fusion", False)
    fn = lambda x, g: _rms(x, g) * 2.0  # noqa: E731
    np.testing.assert_array_equal(
        np.asarray(auto_fuse(fn)(x, g), np.float32),
        np.asarray(fn(x, g), np.float32))


def test_template_kill_switches(fusion_flags):
    x, _, g, _ = _operands()
    fn = lambda x, g: _rms(x, g) * 2.0  # noqa: E731
    GLOBAL_FLAGS.set("use_fused_norm_epilogue", False)
    assert discover(fn, x, g).n_sites == 0
    GLOBAL_FLAGS.set("use_fused_norm_epilogue", True)
    assert discover(fn, x, g).n_sites == 1


# ---------------------------------------------------------------------------
# model rediscovery: the PR 6 sites from the jaxpr alone
# ---------------------------------------------------------------------------

def _llama_cfg():
    from paddle_tpu.models import llama as L

    return L, L.LlamaConfig(vocab_size=128, hidden=256, n_layers=2,
                            n_heads=2, n_kv_heads=2, ffn_hidden=512,
                            max_seq_len=256, dtype=jnp.bfloat16)


def test_llama_rediscovers_pr6_sites_and_swiglu():
    L, cfg = _llama_cfg()
    params = L.init_llama_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 128)
    rep = discover(functools.partial(L._llama_apply_unfused, cfg=cfg,
                                     remat=True), params, tokens)
    by = {}
    for s in rep.sites:
        by[s["template"]] = by.get(s["template"], 0) + 1
    # scan body: attn rms (norm-only) + ffn rms (residual); outer: final
    # rms.  rope both-chains + swiglu inside the remat'd body.
    assert by == {"rms_epilogue": 3, "rope_attention": 1, "swiglu": 1}
    assert not rep.errors
    # the PR 6 kernels actually engage (rope/norm have no device gate)
    applied = {s["template"] for s in rep.sites if s["applied"]}
    assert {"rms_epilogue", "rope_attention"} <= applied


def test_llama_prefill_gets_q_only_rope():
    """The decode cache keeps the rotated k, so the k chain escapes the
    site: the pass must fall back to the q-only rotation — exactly the
    wiring PR 6 hand-coded with return_kv."""
    L, cfg = _llama_cfg()
    params = L.init_llama_params(cfg, jax.random.PRNGKey(0))
    model = L.LlamaForCausalLM(cfg, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 128)
    cache = model._empty_cache(1)
    rep = discover(functools.partial(L._prefill_unfused, cfg=cfg),
                   params, tokens, cache)
    rope = [s for s in rep.sites if s["template"] == "rope_attention"]
    assert len(rope) == 1
    assert rope[0]["eqns"] == 11   # q chain + flash; k passed pre-rotated


def test_gpt_rediscovers_layer_epilogues_and_bias_gelu():
    from paddle_tpu.models import gpt as G

    cfg = G.GPTConfig(vocab_size=128, hidden=256, n_layers=2, n_heads=2,
                      seq_len=256, dtype=jnp.bfloat16)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 128)
    rep = discover(functools.partial(G._model_apply_unfused, cfg=cfg),
                   params, tokens)
    by = {}
    for s in rep.sites:
        by[s["template"]] = by.get(s["template"], 0) + 1
    # scan body: ln1 (norm-only), ln2 (residual + proj bias), bias+gelu;
    # outer: final lnf (residual + bias)
    assert by == {"layer_epilogue": 3, "bias_gelu": 1}
    assert not rep.errors


def test_unrolled_llama_is_bit_identical_in_eager():
    """No scan (every op dispatches eagerly): the fused evaluation must
    reproduce the unfused composition EXACTLY, site by site."""
    L, cfg = _llama_cfg()
    params = L.init_llama_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 128)

    def unrolled(params, tokens):
        B_, T_ = tokens.shape
        x = params["wte"][tokens].astype(cfg.dtype)  # tpu-lint: disable=TPL008 -- single-host eager parity harness, nothing is mesh-sharded
        cos, sin = L.rope_angles(cfg, jnp.arange(T_))
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
        for i in range(cfg.n_layers):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            x = L.block_apply(bp, x, cfg, cos, sin)
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        return L._mm(x, params["head"], cfg).astype(jnp.float32)

    rep = discover(unrolled, params, tokens)
    assert rep.n_sites >= 3 * cfg.n_layers
    _check_parity(unrolled, params, tokens)


def test_scanned_llama_apply_allclose():
    """Through the real scan+remat model the unfused BASELINE is itself
    compilation-sensitive (XLA elides a bf16 rounding when it fuses the
    scan body), so the model-level pin is allclose — the same standard
    the PR 6 hand-wired sites met; bit-parity is pinned on the eager
    unrolled composition above."""
    L, cfg = _llama_cfg()
    params = L.init_llama_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 128)
    fused = L.llama_apply(params, tokens, cfg)
    old = GLOBAL_FLAGS.get("use_auto_fusion")
    GLOBAL_FLAGS.set("use_auto_fusion", False)
    try:
        unfused = L.llama_apply(params, tokens, cfg)
    finally:
        GLOBAL_FLAGS.set("use_auto_fusion", old)
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(unfused, np.float32),
                               rtol=0.05, atol=0.05)


def test_fused_grads_allclose():
    L, cfg = _llama_cfg()
    params = L.init_llama_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 128)
    labels = jax.random.randint(jax.random.PRNGKey(2), (1, 256), 0, 128)

    def loss(p):
        return L.llama_loss(p, tokens, labels, cfg)

    gf = jax.grad(loss)(params)
    old = GLOBAL_FLAGS.get("use_auto_fusion")
    GLOBAL_FLAGS.set("use_auto_fusion", False)
    try:
        gu = jax.grad(loss)(params)
    finally:
        GLOBAL_FLAGS.set("use_auto_fusion", old)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gu)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.1, atol=0.02)


def test_report_shape():
    x, _, g, _ = _operands()
    rep = discover(lambda x, g: _rms(x, g) * 2.0, x, g)
    assert rep is last_report()
    assert len(rep.program_hash) == 16
    assert rep.program_cache_hit is False
    row = rep.sites[0]
    assert set(row) >= {"template", "applied", "eqns", "note"}
