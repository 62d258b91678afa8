"""models/mimo_v2.py and the engine's two cache classes of two page shapes
against the plain reference (benchmark/configs/mimo-v2.5-ep16.reference.py,
through its toy twin tiny-mimo), at a tiny size on seeded weights, in fp32
at `highest` (conftest pins it): seven layers in the published pattern
(full, four window, full, window; layer 0 dense), q and k 48 wide beside v
32, rotary over the first 16 dims, 8 query heads over 2 kv heads in the
full layers and 4 in the window layers, a window of 8 tokens on pages of
4, 16 experts top-2 with 4 held a share, no shared expert.

Tolerances, and why.  Both sides compute in fp32 here, so they differ by
the order of their sums only: logits of size ~0.6 agree to ~2e-7.  The
limit is 2e-5 on the widest difference of a logit; the engine is held to
it on the whole logits row of every token it served (`_Watch`), not on the
token picked.  What each planted departure reads against it (the last
tests): activations and weights rounded to bf16, the reference's operands
in fp8, no sink, the window layers' rotation at the full layers' theta,
the rotation over the whole head, no value scale, no window mask: each
fails by 20 times or more (the window layers' theta is the closest call:
within a window of 8, theta 1e4 against 1e7 turns the 16 rotated dims by
little, ~25 times the limit)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models import mimo_v2, routed_experts
from paddle_tpu.models.mimo_v2 import MimoV2Config

REF = harness.reference_for("tiny-mimo")
MODEL = harness.load_json("benchmark/configs/tiny-mimo.json")["model"]
TOL = 2e-5
W, BS = 8, 4


def _model(held=(4, 4), **over):
    m = dict(MODEL, **over)
    m.update(held_experts=list(held), n_routed_experts=held[1])
    return m


def _cfg(model, **over):
    kw = dict(n_routed_experts=model["n_routed_experts_published"],
              held=tuple(model["held_experts"]), dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return MimoV2Config.from_hf(model, **kw)


def _params(model, key):
    """The reference's weights (bf16 values, fp32 sinks and bias) as the
    program takes them, carried in fp32 so that both sides compute in one
    precision."""
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        REF.make_params(model, key))


def _tokens(seed, n, T):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, T), 0,
                                         MODEL["vocab_size"]), np.int32)


def _ref_logits(model, key, tokens, quant=None):
    pos = [list(range(tokens.shape[1]))] * len(tokens)
    return np.stack(REF.logits_at(model, key, tokens, pos, quant=quant))


# -- the configuration --------------------------------------------------------

def test_the_published_config_reads_as_the_cell_states():
    """The served configuration's own keys give the published widths, the
    pattern's first seven layers and both thetas."""
    m = harness.load_json("benchmark/configs/mimo-v2.5-ep16.json")["model"]
    cfg = MimoV2Config.from_hf(m, n_routed_experts=256, held=(64, 16))
    assert cfg.kinds == ("full",) + ("window",) * 4 + ("full", "window")
    assert cfg.stacks == ("full_dense",) + ("window_moe",) * 4 + (
        "full_moe", "window_moe")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.swa_n_kv_heads, cfg.head_dim,
            cfg.v_head_dim, cfg.rotary_dim) == (64, 4, 8, 192, 128, 64)
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.sliding_window,
            cfg.value_scale) == (1e7, 1e4, 128, 0.707)
    assert cfg.window_sink and cfg.routing.k == 8 and cfg.held == (64, 16)


@pytest.mark.parametrize("key,value", [
    ("n_shared_experts", 1), ("add_full_attention_sink_bias", True),
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8),
    ("attention_bias", True), ("tie_word_embeddings", True)])
def test_variants_it_does_not_implement_are_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        MimoV2Config.from_hf(dict(MODEL, **{key: value}))


# -- the full-sequence forward ------------------------------------------------

@pytest.mark.parametrize("held", [(0, 16), (4, 4)], ids=["uncut", "held-4..7"])
def test_full_forward_matches_the_reference(held):
    """40 tokens: five windows of 8."""
    model, key = _model(held), harness.seed_key(11)
    tokens = _tokens(1, 2, 40)
    got = mimo_v2.mimo_v2_apply(_params(model, key), jnp.asarray(tokens),
                                _cfg(model))
    assert np.abs(np.asarray(got) - _ref_logits(model, key, tokens)).max() \
        < TOL


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """The reference's own cut into blocks of queries, a window layer's W
    + BLOCK keys a block, and its experts gathered 16 rows at a time
    change nothing: blocks of 16 against one block."""
    model, key = _model(), harness.seed_key(21)
    tokens = _tokens(12, 1, 64)
    whole = _ref_logits(model, key, tokens)
    monkeypatch.setattr(REF, "BLOCK", 16)
    monkeypatch.setattr(REF, "ROWS", 16)
    assert np.abs(_ref_logits(model, key, tokens) - whole).max() < TOL


# -- the mechanisms one at a time ---------------------------------------------

def test_rope_rotates_the_first_third_at_each_kinds_theta():
    """Dims past ``rotary_dim`` pass untouched; the rotated ones turn at the
    full layers' theta in one and the window layers' in the other, as the
    reference turns them."""
    cfg = _cfg(_model())
    pos = jnp.arange(40, dtype=jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 2, 48), jnp.float32)
    angles = mimo_v2.rope_angles(cfg, pos)
    for kind, theta in (("full", 1e7), ("window", 1e4)):
        got = mimo_v2.partial_rope(x, *angles[kind], cfg.rotary_dim)
        np.testing.assert_array_equal(np.asarray(got[..., 16:]),
                                      np.asarray(x[..., 16:]))
        want = REF._rope(x, pos, theta, 16)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6
        assert np.abs(np.asarray(got[..., :16] - x[..., :16])).max() > 0.1
    full = mimo_v2.partial_rope(x, *angles["full"], 16)
    window = mimo_v2.partial_rope(x, *angles["window"], 16)
    assert np.abs(np.asarray(full - window)).max() > 0.1


def test_a_sink_at_minus_infinity_is_no_sink():
    """The window layers' sinks at -inf give the model without sinks; a
    sink far above every score leaves a window layer's attention out."""
    model, key = _model(), harness.seed_key(24)
    params, cfg = _params(model, key), _cfg(model)
    tokens = jnp.asarray(_tokens(3, 1, 24))

    def with_sinks(v):
        p = dict(params)
        p["window_moe"] = dict(p["window_moe"], sinks=jnp.full_like(
            p["window_moe"]["sinks"], v))
        return p

    none = mimo_v2.mimo_v2_apply(params, tokens,
                                 dataclasses.replace(cfg, window_sink=False))
    got = mimo_v2.mimo_v2_apply(with_sinks(-jnp.inf), tokens, cfg)
    assert np.abs(np.asarray(got - none)).max() < 1e-6
    huge = mimo_v2.mimo_v2_apply(with_sinks(1e4), tokens, cfg)
    p = dict(params)
    p["window_moe"] = dict(p["window_moe"],
                           wo=jnp.zeros_like(p["window_moe"]["wo"]))
    no_attention = mimo_v2.mimo_v2_apply(p, tokens, cfg)
    assert np.abs(np.asarray(huge - no_attention)).max() < 1e-6
    assert np.abs(np.asarray(got) - np.asarray(
        mimo_v2.mimo_v2_apply(params, tokens, cfg))).max() > 50 * TOL


# -- the expert layer is told which experts it holds ------------------------

def test_all_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that all four chips' shares of
    four experts give (there is no shared expert to count once) add up to
    the uncut reference's layer."""
    key = harness.seed_key(13)
    x = jax.random.normal(jax.random.PRNGKey(3), (48, 64), jnp.float32)
    whole = _model((0, 16))
    stack = "window_moe"
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     REF.layer_weights(whole, key, 1, stack))
    idx, weight = REF.routing(x, w, whole)
    want = np.asarray(REF.experts_part(
        x, idx, weight, w, whole,
        lambda *a: REF._expert_rows(*a, None)))
    total = np.zeros_like(want)
    counts = []
    for first in (0, 4, 8, 12):
        share = _model((first, 4))
        ws = jax.tree.map(lambda a: a.astype(jnp.float32),
                          REF.layer_weights(share, key, 1, stack))
        # a share's experts are the uncut model's, by their numbers
        np.testing.assert_array_equal(ws["we_up"],
                                      w["we_up"][first:first + 4])
        y, sizes = routed_experts.moe_ffn(x, ws, _cfg(share).routing)
        total += np.asarray(y)
        counts += list(np.asarray(sizes))
    assert np.abs(total - want).max() < TOL
    assert sum(counts) == 48 * 2          # every assignment landed once


def test_moe_ffn_adds_the_shared_term_only_where_the_layer_has_one():
    """A layer without ``ws_*`` is the routed sum alone; with them, the two
    existing families' layer is that sum plus their shared term, as it
    was: one rounding to the compute dtype, then the add."""
    h = jax.random.normal(jax.random.PRNGKey(5), (24, 64), jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(6), 8)
    bf = jnp.bfloat16

    def nrm(k, shape):
        return (0.05 * jax.random.normal(k, shape, jnp.float32)).astype(bf)

    lp = {"router": nrm(keys[0], (64, 8)), "we_gate": nrm(keys[1], (4, 64, 32)),
          "we_up": nrm(keys[2], (4, 64, 32)),
          "we_down": nrm(keys[3], (4, 32, 64))}
    shared = {"ws_gate": nrm(keys[4], (64, 48)), "ws_up": nrm(keys[5], (64, 48)),
              "ws_down": nrm(keys[6], (48, 64))}
    for scale in (1.0, 0.5):
        r = routed_experts.Routing(2, (2, 4), bf, shared_scale=scale)
        alone, sizes = routed_experts.moe_ffn(h, lp, r)
        both, sizes2 = routed_experts.moe_ffn(h, {**lp, **shared}, r)
        term = routed_experts.swiglu(h, shared["ws_gate"], shared["ws_up"],
                                     shared["ws_down"], bf)
        if scale != 1.0:
            term = (term.astype(jnp.float32) * scale).astype(bf)
        assert alone.dtype == both.dtype == bf
        np.testing.assert_array_equal(np.asarray(both, np.float32),
                                      np.asarray(alone + term, np.float32))
        np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes2))


# -- the engine through both cache classes ----------------------------------

def _engine(model, key, **kw):
    cfg = _cfg(model, max_seq_len=256)
    geometry = dict(max_batch=3, page_size=BS, max_seq=128, n_pages=100,
                    class_pages={"window": 60}, prefill_budget=32,
                    prefix_cache=True, qb=8)
    geometry.update(kw)
    return ServingEngine(cfg, params=_params(model, key), **geometry), cfg


class _Watch:
    """The logits row behind every token an engine serves (as in
    tests/test_cohere_moe.py): ``worst(model, key, req)`` is the widest
    difference between a row and the reference's full forward over the
    request's prompt and served tokens at the same position."""

    def __init__(self, eng):
        self.eng, self.rows, self._seen, self._count = eng, [], [], {}
        real = eng.model.logits

        def logits(params, h):
            out = real(params, h)
            jax.debug.callback(lambda l: self._seen.append(np.asarray(l)),
                               out, ordered=True)
            return out

        eng.model.logits = logits

    def step(self) -> bool:
        prev = self.eng._inflight
        busy = self.eng.step()
        jax.effects_barrier()
        now = self.eng._inflight
        if now is not None and now is not prev:
            for idx, _s, req, kind, _m, _d in now[1]:
                if kind != "mid":
                    j = self._count.get(req.rid, 0)
                    self._count[req.rid] = j + 1
                    self.rows.append((req, j, self._seen[-1][idx]))
        return busy

    def serve(self, *reqs):
        for r in reqs:
            self.eng.submit(r)
        while self.step():
            pass

    def worst(self, model, key, req) -> float:
        out = np.asarray(req.out_tokens, np.int32)
        seq = np.concatenate([req.prompt, out[:-1]])[None]
        P = len(req.prompt)
        want = REF.logits_at(model, key, seq,
                             [list(range(P - 1, P - 1 + len(out)))])[0]
        rows = [(j, row) for r, j, row in self.rows if r is req]
        assert sorted(j for j, _ in rows) == list(range(len(out)))
        assert out.tolist() == [int(row.argmax()) for _, row in
                                sorted(rows, key=lambda x: x[0])]
        return max(float(np.abs(row - want[j]).max()) for j, row in rows)


def test_two_classes_of_two_page_shapes():
    """The full layers' class pages 2 kv heads, the window layers' 4: each
    class's pools, table and bytes a page are its own spec's."""
    model, key = _model(), harness.seed_key(14)
    eng, cfg = _engine(model, key)
    g, w = eng.classes
    assert (g.name, g.n_layers, g.window, w.name, w.n_layers, w.window) == (
        "global", 2, None, "window", 5, W)
    assert [p.page_shape for p in g.spec.planes] == [(2, 48, BS), (2, BS, 32)]
    assert [p.page_shape for p in w.spec.planes] == [(4, 48, BS), (4, BS, 32)]
    assert g.spec.hash_tag != w.spec.hash_tag
    x, = eng._extra
    assert eng.k_pages.shape == (2, 100, 2, 48, BS)
    assert eng.v_pages.shape == (2, 100, 2, BS, 32)
    assert x.k_pages.shape == (5, 60, 4, 48, BS)
    assert x.v_pages.shape == (5, 60, 4, BS, 32)
    assert x.full_rows.shape == eng._full_rows.shape == (3, 32)
    # fp32: k 48 and v 32 a kv head, 2 heads x 2 layers, 4 heads x 5
    assert eng.kv_bytes_per_token(0) == 2 * 2 * 80 * 4
    assert eng.kv_bytes_per_token(1) == 5 * 4 * 80 * 4
    assert eng.kv_bytes_per_page(1) == BS * eng.kv_bytes_per_token(1)


def test_engine_serves_through_both_classes_with_a_hit_on_the_windows_tail():
    """Prefill chunks and decode far past the window against the
    reference's full forward on logits: prompts of 37 and 52 on pages of
    4, chunks of 8 that straddle pages and the window of 8, and a
    prefix-cache hit (the second request shares 36 tokens with the first)
    that needs the window class's tail; then the bytes a context token
    both classes hold."""
    model, key = _model(), harness.seed_key(16)
    eng, cfg = _engine(model, key)
    watch = _Watch(eng)
    doc = _tokens(6, 1, 52)[0]
    first = Request(rid=0, prompt=doc[:37].copy(), max_new_tokens=9)
    second = Request(rid=1, prompt=doc.copy(), max_new_tokens=7)
    eng.submit(first)
    for _ in range(3):
        watch.step()
    eng.submit(second)
    while watch.step():
        pass
    assert eng.stats["prefill_cached_tokens"] == 36         # the hit
    assert eng.stats["prefill_window_lost_tokens"] == 0
    assert eng.stats["pages_released_by_window"] > 0
    for req in (first, second):
        assert len(req.out_tokens) == req.max_new_tokens
        assert watch.worst(model, key, req) < TOL
    acc = eng.page_accounting()
    assert acc["classes"]["global"]["total"] == 99
    assert acc["classes"]["window"]["total"] == 59
    held = eng.stats["moe_assigned_held"] / eng.stats["moe_assigned_all"]
    assert 0.05 < held < 0.5                        # 4 of 16 experts held
    # each class at its own bytes a page: the window class holds a window
    # and a chunk, the global class everything
    live = (eng.stats["pages_live.global"] * eng.kv_bytes_per_page(0)
            + eng.stats["pages_live.window"] * eng.kv_bytes_per_page(1))
    per_token = live / eng.stats["context_tokens_live"]
    one_pool = 7 * 4 * 80 * 4
    assert eng.kv_bytes_per_token(0) < per_token < 0.6 * one_pool


def test_the_layers_scopes_name_the_step():
    model, key = _model(), harness.seed_key(23)
    eng, _ = _engine(model, key)
    stacks = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            stacks.add(str(eqn.source_info.name_stack))
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(eng.trace_unified().jaxpr)
    for scope in ("layer/attn_window", "layer/attn_global", "layer/qkv",
                  "layer/kv_write", "layer/router", "layer/experts",
                  "layer/mlp"):
        assert any(scope in n for n in stacks), scope
    assert not any("layer/shared_expert" in n for n in stacks)


@pytest.mark.parametrize("feature,kw", [
    ("kv_quant", {"kv_quant": True}), ("lora", {"lora": True}),
    ("speculative", {"speculative_k": 2})])
def test_features_left_out_fail_with_one_clear_error(feature, kw):
    model, key = _model(), harness.seed_key(18)
    with pytest.raises(NotImplementedError, match=feature):
        _engine(model, key, **kw)


# -- tight enough: each planted departure fails -----------------------------

def _departed(name, params, cfg, tokens, monkeypatch):
    if name == "bf16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    elif name == "no_sink":
        cfg = dataclasses.replace(cfg, window_sink=False)
    elif name == "window_theta_at_full":
        cfg = dataclasses.replace(cfg, swa_rope_theta=cfg.rope_theta)
    elif name == "rotation_over_the_whole_head":
        cfg = dataclasses.replace(cfg, rotary_dim=cfg.head_dim)
    elif name == "no_value_scale":
        cfg = dataclasses.replace(cfg, value_scale=1.0)
    elif name == "no_window_mask":
        cfg = dataclasses.replace(cfg, sliding_window=10 ** 6)
    return mimo_v2.mimo_v2_apply(params, jnp.asarray(tokens), cfg)


@pytest.mark.parametrize("name", [
    "bf16", "no_sink", "window_theta_at_full", "rotation_over_the_whole_head",
    "no_value_scale", "no_window_mask"])
def test_each_departure_from_the_equations_fails_the_tolerance(
        name, monkeypatch):
    model, key = _model(), harness.seed_key(19)
    tokens = _tokens(8, 2, 40)
    want = _ref_logits(model, key, tokens)
    got = _departed(name, _params(model, key), _cfg(model), tokens,
                    monkeypatch)
    assert np.abs(np.asarray(got, np.float32) - want).max() > 20 * TOL, name


def test_an_fp8_operand_fails_the_tolerance():
    """The control of the chip's comparison, at the tiny size."""
    model, key = _model(), harness.seed_key(19)
    tokens = _tokens(8, 2, 40)
    low = _ref_logits(model, key, tokens, quant="fp8")
    assert np.abs(low - _ref_logits(model, key, tokens)).max() > 50 * TOL
