"""models/mla_moe.py against the plain reference
(benchmark/configs/joyai-flash-ep16.reference.py), at a tiny size on
seeded weights, in fp32 at `highest` (conftest pins it).

Tolerances, and why.  Both sides compute in fp32 here, so they differ by
the order of their sums only: logits of size ~0.5 agree to ~1e-6.  The
limit is 2e-5 on the widest logit gap.  What each planted departure reads
against it (the table in the last test): activations and weights rounded
to bf16 3e-3, no shared expert 0.3, no correction bias 0.2 (the choice of
experts changes), no scaling factor 0.3, rotate-half in place of adjacent
pairs 0.06: each fails by two orders or more."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models import mla_moe
from paddle_tpu.models.mla_moe import MlaMoeConfig

REF = harness.reference_for("joyai-flash-ep16")
TOL = 2e-5

MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "num_nextn_predict_layers": 1, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 256}


def _model(held=None, **over):
    m = dict(MODEL, **over)
    if held is not None:
        m.update(held_experts=list(held), n_routed_experts_published=8,
                 n_routed_experts=held[1])
    return m


def _cfg(model, **over):
    kw = dict(n_routed_experts=model.get("n_routed_experts_published",
                                         model["n_routed_experts"]),
              dtype=jnp.float32, param_dtype=jnp.float32)
    if "held_experts" in model:
        kw["held"] = tuple(model["held_experts"])
    kw.update(over)
    return MlaMoeConfig.from_hf(model, **kw)


def _params(model, key):
    """The reference's weights (bf16 values) as the program takes them,
    carried in fp32 so that both sides compute in one precision."""
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        REF.make_params(model, key))


def _tokens(seed, n, T):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, T), 0,
                                         MODEL["vocab_size"]), np.int32)


def _ref_logits(model, key, tokens):
    pos = [list(range(tokens.shape[1]))] * len(tokens)
    return np.stack(REF.logits_at(model, key, tokens, pos))


# -- the full-sequence forward and the MTP module ---------------------------

@pytest.mark.parametrize("held", [None, (2, 4)], ids=["uncut", "held-2..5"])
def test_full_forward_matches_the_reference(held):
    model, key = _model(held), harness.seed_key(11)
    tokens = _tokens(1, 2, 40)
    got = mla_moe.mla_moe_apply(_params(model, key), jnp.asarray(tokens),
                                _cfg(model))
    assert np.abs(np.asarray(got) - _ref_logits(model, key, tokens)).max() \
        < TOL


def test_mtp_logits_match_the_reference():
    model, key = _model((0, 8)), harness.seed_key(12)
    tokens = _tokens(2, 2, 24)
    params, cfg = _params(model, key), _cfg(model)
    hidden = mla_moe.mla_moe_hidden(params, jnp.asarray(tokens), cfg)
    got = mla_moe.mtp_logits(params, hidden, jnp.asarray(tokens), cfg)
    want = REF.mtp_logits(model, key, tokens)
    assert got.shape == want.shape == (2, 23, 256)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


# -- the expert layer is told which experts it holds ------------------------

def _moe_layer(model, key, layer=1):
    w = REF.layer_weights(model, key, layer, "moe")
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def test_all_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that all four shares of two
    experts give, with the shared expert (which every chip computes
    alike) counted once, add up to the uncut reference's layer."""
    key = harness.seed_key(13)
    x = jax.random.normal(jax.random.PRNGKey(3), (48, 64), jnp.float32)
    whole = _model((0, 8))
    w = _moe_layer(whole, key)
    want = np.asarray(REF.experts_part(x, w, whole, None))
    shared = np.asarray(REF._swiglu(x, w["ws_gate"], w["ws_up"],
                                    w["ws_down"], None))
    total = np.zeros_like(want)
    counts = []
    for first in (0, 2, 4, 6):
        share = _model((first, 2))
        ws = _moe_layer(share, key)
        # a share's experts are the uncut model's, by their numbers
        np.testing.assert_array_equal(ws["we_up"],
                                      w["we_up"][first:first + 2])
        y, sizes = mla_moe.moe_ffn(x, ws, _cfg(share))
        total += np.asarray(y) - shared
        counts += list(np.asarray(sizes))
    assert np.abs(total + shared - want).max() < TOL
    assert sum(counts) == 48 * 2          # every assignment landed once


def test_no_token_is_dropped_under_a_skewed_router():
    """A router skewed onto one expert: every token chooses expert 5, and
    all of them get its output (no capacity, no dropping)."""
    key = harness.seed_key(14)
    model = _model((4, 2))
    w = _moe_layer(model, key)
    w["router_bias"] = w["router_bias"].at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 64), jnp.float32)
    y, sizes = mla_moe.moe_ffn(x, w, _cfg(model))
    assert int(sizes[1]) == 64            # expert 5 = held expert 1
    want = np.asarray(REF.experts_part(x, w, model, None))
    assert np.abs(np.asarray(y) - want).max() < TOL
    # padding is routed nowhere
    valid = jnp.arange(64) < 40
    _, sizes = mla_moe.moe_ffn(x, w, _cfg(model), valid)
    assert int(sizes[1]) == 40


# -- absorbed = expanded, and the engine through latent pages ---------------

def _engine(model, key, **kw):
    cfg = _cfg(model, n_mtp=0, max_seq_len=256)
    params = _params(dict(model, num_nextn_predict_layers=0), key)
    geometry = dict(max_batch=3, page_size=16, max_seq=160, n_pages=40,
                    prefill_budget=48, prefix_cache=True, qb=8)
    geometry.update(kw)
    return ServingEngine(cfg, params=params, **geometry), cfg


def _served_gap(model, key, req):
    """Widest gap of a served token's logit below the reference's best."""
    out = np.asarray(req.out_tokens, np.int32)
    seq = np.concatenate([req.prompt, out[:-1]])[None]
    P = len(req.prompt)
    lg = REF.logits_at(model, key, seq, [list(range(P - 1, P - 1 + len(out)))])[0]
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def test_absorbed_step_equals_the_expanded_forward_on_logits():
    """One prefill of 40 tokens through the engine's layers (absorbed
    form over latent pages, chunks of 8 that straddle pages of 16 at
    position 16, 32) against the expanded full-sequence forward."""
    model, key = _model((2, 4)), harness.seed_key(15)
    eng, cfg = _engine(model, key)
    seen = {}
    logits = eng.model.logits
    # the last call is the request's tick (the first dispatch also runs
    # each other step size once, idle)
    eng.model.logits = lambda p, h: seen.update(l=logits(p, h)) or seen["l"]
    tokens = _tokens(5, 1, 40)
    req = Request(rid=0, prompt=tokens[0], max_new_tokens=1)
    eng._unified = eng._unified_step_impl          # untraced: keep logits
    eng.submit(req)
    while eng.step():
        pass
    want = _ref_logits(model, key, tokens)[0, -1]
    got = np.asarray(seen["l"])[4]                 # row 4 = the last chunk
    assert np.abs(got - want).max() < TOL
    assert req.out_tokens == [int(want.argmax())]


def test_engine_serves_through_latent_pages_with_hits_and_resume():
    """Prefill chunks + decode through latent pages against the
    reference's full forward on logits: chunks that straddle a page
    (prompts of 37, 52 and 50 on pages of 16, qb 8), a prefix-cache hit
    (the second request shares 32 tokens with the first), and a preempt-
    and-resume (a pool of 8 pages, and a third request of higher priority
    that needs 4 of them while 5 are taken)."""
    model, key = _model((2, 4)), harness.seed_key(16)
    eng, cfg = _engine(model, key, n_pages=1 + 8, priorities=True)
    doc = _tokens(6, 1, 52)[0]
    first = Request(rid=0, prompt=doc[:37].copy(), max_new_tokens=9)
    second = Request(rid=1, prompt=doc.copy(), max_new_tokens=7)
    third = Request(rid=2, prompt=_tokens(9, 1, 50)[0], max_new_tokens=6,
                    priority=5)
    eng.submit(first)
    for _ in range(3):
        eng.step()
    eng.submit(second)
    for _ in range(4):
        eng.step()
    assert first.out_tokens and second.out_tokens
    assert eng.stats["prefill_cached_tokens"] == 32         # the hit
    eng.submit(third)
    while eng.step():
        pass
    victims = [r for r in (first, second) if r.n_preempted]
    assert victims and eng.stats["preemptions"] == len(victims)
    assert eng.stats["prefill_cached_tokens"] > 32          # the resume
    for req in (first, second, third):
        assert len(req.out_tokens) == req.max_new_tokens
        assert _served_gap(model, key, req) < TOL
    assert eng.page_accounting()["total"] == eng.n_pages - 1
    held_share = eng.stats["moe_assigned_held"] / eng.stats["moe_assigned_all"]
    assert 0.2 < held_share < 0.9                   # 4 of 8 experts held
    assert eng.kv_bytes_per_token() == 3 * (32 + 8) * 4   # L x 40 fp32 values


def test_engine_counts_what_the_layers_counted(monkeypatch):
    """The per-layer expert counters ride out with the picks (one fetch)
    and land in stats and on engine.step's end."""
    from paddle_tpu import obs

    model, key = _model((2, 4)), harness.seed_key(17)
    ring = obs.arm().tracer
    eng, cfg = _engine(model, key)
    fetches = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetches.append(x) or real(x))
    eng.submit(Request(rid=0, prompt=_tokens(7, 1, 20)[0], max_new_tokens=3))
    while eng.step():
        pass
    obs.arm()
    assert len(fetches) == eng.stats["unified_steps"]   # one a harvest
    # 20 prompt tokens + 2 decode inputs, 2 expert layers, top-2
    assert eng.stats["moe_assigned_all"] == 22 * 2 * 2
    events, _ = ring.snapshot()
    ends = [e["args"] for e in events if e["name"] == "engine.step"
            and e["ph"] == "E" and "moe_assigned_held" in e.get("args", {})]
    assert sum(a["moe_assigned_held"] for a in ends) == \
        eng.stats["moe_assigned_held"]
    assert {"rows_decode", "rows_prefill", "queued", "moe_assigned_all",
            "moe_load_max_over_mean"} <= set(ends[0])
    spec = [e for e in events if e["name"] == "engine.cache_spec"]
    assert spec[0]["args"]["bytes_per_token"] == eng.kv_bytes_per_token()
    assert spec[0]["args"]["planes"] == "k_rope:8,c_kv:32"


@pytest.mark.parametrize("feature,kw", [
    ("kv_quant", {"kv_quant": True}), ("lora", {"lora": True}),
    ("constrained", {"constrained": True}),
    ("speculative", {"speculative_k": 2}),
    ("weight_only_int8", {"weight_only_int8": True}),
    ("page_shipment", {"prefill_only": True})])
def test_features_left_out_fail_with_one_clear_error(feature, kw):
    model, key = _model((2, 4)), harness.seed_key(18)
    with pytest.raises(NotImplementedError, match=feature):
        _engine(model, key, **kw)


def test_page_shipment_is_refused_when_asked():
    model, key = _model((2, 4)), harness.seed_key(18)
    eng, _ = _engine(model, key)
    with pytest.raises(NotImplementedError, match="page_shipment"):
        eng.export_request_pages(0)
    with pytest.raises(NotImplementedError, match="page_shipment"):
        eng.adopt_pages({})


# -- tight enough: each planted departure fails -----------------------------

def _departed(name, params, cfg, tokens, monkeypatch):
    if name == "bf16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    elif name == "no_shared_expert":
        params = dict(params, moe=dict(
            params["moe"], ws_down=jnp.zeros_like(params["moe"]["ws_down"])))
    elif name == "no_correction_bias":
        params = dict(params, moe=dict(
            params["moe"],
            router_bias=jnp.zeros_like(params["moe"]["router_bias"])))
    elif name == "no_scaling_factor":
        cfg = dataclasses.replace(cfg, routed_scaling=1.0)
    elif name == "rotate_half":
        def rotate_half(x, cos, sin):
            x = x.astype(jnp.float32)
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)
        monkeypatch.setattr(mla_moe, "apply_rope_pairs", rotate_half)
    return mla_moe.mla_moe_apply(params, jnp.asarray(tokens), cfg)


@pytest.mark.parametrize("name", ["bf16", "no_shared_expert",
                                  "no_correction_bias", "no_scaling_factor",
                                  "rotate_half"])
def test_each_departure_from_the_equations_fails_the_tolerance(
        name, monkeypatch):
    model, key = _model((2, 4)), harness.seed_key(19)
    tokens = _tokens(8, 2, 40)
    # the router's choice must depend on the bias for its absence to show
    want = _ref_logits(model, key, tokens)
    got = _departed(name, _params(model, key), _cfg(model), tokens,
                    monkeypatch)
    assert np.abs(np.asarray(got, np.float32) - want).max() > 50 * TOL, name
