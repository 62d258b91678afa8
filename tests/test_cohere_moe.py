"""models/cohere_moe.py and the engine's cache classes against the plain
reference (benchmark/configs/command-a-plus-ep8.reference.py), at a tiny
size on seeded weights, in fp32 at `highest` (conftest pins it): four
layers window-window-window-global, a window of 12 tokens on pages of 4,
8 experts top-2 with 2 held a share, 2 shared experts.

Tolerances, and why.  Both sides compute in fp32 here, so they differ by
the order of their sums only: logits of size ~0.15 agree to ~1e-6.  The
limit is 2e-5 on the widest difference of a logit; the engine is held to
it on the whole logits row of every token it served (`_Watch`), not on
the token picked.  What each planted departure reads
against it (the last tests): activations and weights rounded to bf16
3e-3, the reference's operands in fp8 3e-2, no window mask, a rotation in
the global layer, the shared experts summed instead of averaged, a
rotation of halves on the source's layout: each fails by 50 times or
more."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models import cohere_moe, routed_experts
from paddle_tpu.models.cohere_moe import CohereMoeConfig

REF = harness.reference_for("command-a-plus-ep8")
TOL = 2e-5
W, BS = 12, 4

MODEL = {
    "hidden_size": 64, "intermediate_size": 32, "head_dim": 16,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": W, "rope_theta": 50000, "layer_norm_eps": 1e-5,
    "num_experts": 8, "num_shared_experts": 2, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "vocab_size": 256,
    "max_position_embeddings": 256,
    # 64 wide, the layers add ~1e-3 a dimension: beside an embedding of
    # 0.02 the tied head would read every token back as itself (the
    # reference's outer_weights says so); so a small embedding, and the
    # logits scaled back to ~0.15 (powers of two: a weight made under
    # jit and one made eagerly then round to bf16 alike)
    "embedding_init_std": 2.0 ** -10, "logit_scale": 16}


def _model(held=None, **over):
    m = dict(MODEL, **over)
    if held is not None:
        m.update(held_experts=list(held), num_experts_published=8,
                 num_experts=held[1])
    return m


def _cfg(model, **over):
    kw = dict(n_routed_experts=model.get("num_experts_published",
                                         model["num_experts"]),
              dtype=jnp.float32, param_dtype=jnp.float32)
    if "held_experts" in model:
        kw["held"] = tuple(model["held_experts"])
    kw.update(over)
    return CohereMoeConfig.from_hf(model, **kw)


def _params(model, key):
    """The reference's weights (bf16 values) as the program takes them,
    carried in fp32 so that both sides compute in one precision."""
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        REF.make_params(model, key))


def _tokens(seed, n, T):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, T), 0,
                                         MODEL["vocab_size"]), np.int32)


def _ref_logits(model, key, tokens, quant=None):
    pos = [list(range(tokens.shape[1]))] * len(tokens)
    return np.stack(REF.logits_at(model, key, tokens, pos, quant=quant))


# -- the full-sequence forward ------------------------------------------------

@pytest.mark.parametrize("held", [None, (2, 2)], ids=["uncut", "held-2..3"])
def test_full_forward_matches_the_reference(held):
    """40 tokens: more than three windows of 12."""
    model, key = _model(held), harness.seed_key(11)
    tokens = _tokens(1, 2, 40)
    got = cohere_moe.cohere_moe_apply(_params(model, key),
                                      jnp.asarray(tokens), _cfg(model))
    assert np.abs(np.asarray(got) - _ref_logits(model, key, tokens)).max() \
        < TOL


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """The reference's own cut into blocks of queries, and a window
    layer's W + BLOCK keys a block, change nothing: blocks of 16 against
    one block."""
    model, key = _model((2, 2)), harness.seed_key(21)
    tokens = _tokens(12, 1, 64)
    whole = _ref_logits(model, key, tokens)
    monkeypatch.setattr(REF, "BLOCK", 16)
    assert np.abs(_ref_logits(model, key, tokens) - whole).max() < TOL


def test_periods_of_layers_run_in_the_models_order():
    """Two periods (w w g w w g): the runs, the stacks and the groups."""
    model = _model((2, 2), num_hidden_layers=6, layer_types=[
        "sliding_attention", "sliding_attention", "full_attention"] * 2)
    key = harness.seed_key(22)
    cfg = _cfg(model)
    assert cfg.runs == (("window", 0, 2), ("global", 0, 1),
                        ("window", 2, 2), ("global", 1, 1))
    tokens = _tokens(13, 1, 30)
    got = cohere_moe.cohere_moe_apply(_params(model, key),
                                      jnp.asarray(tokens), cfg)
    assert np.abs(np.asarray(got) - _ref_logits(model, key, tokens)).max() \
        < TOL
    eng = ServingEngine(cfg, params=_params(model, key), max_batch=2,
                        page_size=BS, max_seq=64, n_pages=40,
                        prefill_budget=16, qb=8, prefix_cache=False)
    assert [(c.name, c.n_layers, c.window) for c in eng.classes] == [
        ("global", 2, None), ("window", 4, W)]
    req = Request(rid=0, prompt=tokens[0], max_new_tokens=5)
    watch = _Watch(eng)
    watch.serve(req)
    assert watch.worst(model, key, req) < TOL


# -- the expert layer is told which experts it holds ------------------------

def _layer_weights(model, key, layer=1):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        REF.layer_weights(model, key, layer))


def test_all_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that all four shares of two
    experts give, with the shared experts (which every chip computes
    alike) counted once, add up to the uncut reference's layer."""
    key = harness.seed_key(13)
    x = jax.random.normal(jax.random.PRNGKey(3), (48, 64), jnp.float32)
    whole = _model((0, 8))
    w = _layer_weights(whole, key)
    want = np.asarray(REF.experts_part(x, w, whole, None))
    shared = np.asarray(REF._swiglu(x, w["ws_gate"], w["ws_up"],
                                    w["ws_down"], None)) / 2.0
    total = np.zeros_like(want)
    counts = []
    for first in (0, 2, 4, 6):
        share = _model((first, 2))
        ws = _layer_weights(share, key)
        # a share's experts are the uncut model's, by their numbers
        np.testing.assert_array_equal(ws["we_up"],
                                      w["we_up"][first:first + 2])
        y, sizes = routed_experts.moe_ffn(x, ws, _cfg(share).routing)
        total += np.asarray(y) - shared
        counts += list(np.asarray(sizes))
    assert np.abs(total + shared - want).max() < TOL
    assert sum(counts) == 48 * 2          # every assignment landed once


# -- the engine through both cache classes ----------------------------------

def _engine(model, key, **kw):
    cfg = _cfg(model, max_seq_len=256)
    geometry = dict(max_batch=3, page_size=BS, max_seq=128, n_pages=100,
                    class_pages={"window": 80}, prefill_budget=32,
                    prefix_cache=True, qb=8)
    geometry.update(kw)
    return ServingEngine(cfg, params=_params(model, key), **geometry), cfg


class _Watch:
    """The logits row behind every token an engine serves.  ``step``
    drives the engine; ``worst(model, key, req)`` is the widest
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
            # the tick's own launch is the last (the first dispatch also
            # runs every other step size once, idle); each row that bears
            # a token bears the request's next one
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


def test_engine_step_equals_the_full_forward_on_logits():
    """One prefill of 43 tokens through the engine's layers (chunks of 8
    on pages of 4; from the second chunk on a chunk's first query has
    keys behind its window of 12, and a chunk straddles it) against the
    full-sequence forward."""
    model, key = _model((2, 2)), harness.seed_key(15)
    eng, cfg = _engine(model, key)
    seen = {}
    logits = eng.model.logits
    eng.model.logits = lambda p, h: seen.update(l=logits(p, h)) or seen["l"]
    tokens = _tokens(5, 1, 43)
    req = Request(rid=0, prompt=tokens[0], max_new_tokens=1)
    eng._unified = eng._unified_step_impl          # untraced: keep logits
    eng.submit(req)
    while eng.step():
        pass
    want = _ref_logits(model, key, tokens)[0, -1]
    got = np.asarray(seen["l"])[1]     # second tick (43 = 32 + 11), row 1
    assert np.abs(got - want).max() < TOL
    assert req.out_tokens == [int(want.argmax())]


def test_engine_serves_through_both_classes_with_hits_and_resume():
    """Prefill chunks + decode far past the window against the
    reference's full forward on logits: chunks that straddle pages and
    the window (prompts of 37, 52 and 50 on pages of 4, qb 8, window
    12), a prefix-cache hit that needs the window's tail (the second
    request shares 36 tokens with the first), and a preempt-and-resume
    (a global pool of 28 pages, and a third request of higher priority
    that needs 14 of them while 18 are taken)."""
    model, key = _model((2, 2)), harness.seed_key(16)
    eng, cfg = _engine(model, key, n_pages=1 + 28, priorities=True)
    watch = _Watch(eng)
    doc = _tokens(6, 1, 52)[0]
    first = Request(rid=0, prompt=doc[:37].copy(), max_new_tokens=9)
    second = Request(rid=1, prompt=doc.copy(), max_new_tokens=7)
    third = Request(rid=2, prompt=_tokens(9, 1, 50)[0], max_new_tokens=6,
                    priority=5)
    eng.submit(first)
    for _ in range(3):
        watch.step()
    eng.submit(second)
    for _ in range(4):
        watch.step()
    assert first.out_tokens and second.out_tokens
    assert eng.stats["prefill_cached_tokens"] == 36         # the hit
    assert eng.stats["prefill_window_lost_tokens"] == 0
    watch.serve(third)
    victims = [r for r in (first, second) if r.n_preempted]
    assert victims and eng.stats["preemptions"] == len(victims)
    assert eng.stats["prefill_cached_tokens"] > 36          # the resume
    for req in (first, second, third):
        assert len(req.out_tokens) == req.max_new_tokens
        assert watch.worst(model, key, req) < TOL
    acc = eng.page_accounting()
    assert acc["total"] == eng.n_pages - 1
    assert acc["classes"]["global"]["total"] == 28
    assert acc["classes"]["window"]["total"] == 79
    assert acc["classes"]["window"]["slot_owned"] == 0
    held_share = eng.stats["moe_assigned_held"] / eng.stats["moe_assigned_all"]
    assert 0.1 < held_share < 0.5                   # 2 of 8 experts held
    # k and v, 2 heads of 16, fp32: a layer's 256 B a token
    assert eng.kv_bytes_per_token() == 1 * 256
    assert eng.kv_bytes_per_token(1) == 3 * 256
    assert eng.stats["pages_released_by_window"] > 0
    _assert_ledger(_window_state(eng))


# -- the allocator ------------------------------------------------------------

def _window_state(eng):
    x, = eng._extra
    return x


def _assert_ledger(x):
    """Every page of a windowed class but the sink is in exactly one
    place: free, a slot's own, a slot's shared mapping, waiting for a
    harvest (let go, or idle and pending), or idle in one of the two
    tiers (interior, tail)."""
    pool = x.pool
    assert set(pool.interior) <= set(pool.evictable)
    parts = [set(pool.free), {p for d in x.owned for p in d.values()},
             {p for d in x.shared for p in d.values()}, set(x.deferred_free),
             set(pool.pending_evict), set(pool.interior),
             set(pool.evictable) - set(pool.interior)]
    acc = x.accounting()
    assert acc["total"] == len(set().union(*parts)) == x.n_pages - 1, acc


def test_pages_go_only_behind_the_window_and_never_under_a_tick_in_flight():
    """While a request of 45 + 12 tokens runs: every block a query can
    still read has a page in the window class and every block behind the
    window has the sink; a page let go waits (neither free nor
    evictable) until the tick that could read it is harvested; the
    request never holds more than its reserved peak."""
    model, key = _model((2, 2)), harness.seed_key(17)
    eng, _ = _engine(model, key)
    x, watch = _window_state(eng), _Watch(eng)
    req = Request(rid=0, prompt=_tokens(7, 1, 45)[0], max_new_tokens=12)
    eng.submit(req)
    waiting_seen = 0
    while True:
        busy = watch.step()
        _assert_ledger(x)
        waiting = set(x.deferred_free) | set(x.pool.pending_evict)
        waiting_seen += len(waiting)
        assert not waiting & set(x.pool.free)
        assert not waiting & set(x.pool.evictable)
        if eng.slots[0] is req:
            nq = (eng._prefilling[0] if 0 in eng._prefilling
                  else int(eng.seq_lens[0]))
            first = max(0, nq - W + 1) // BS
            row = x.full_rows[0]
            assert not row[:first].any()
            assert row[first:-(-nq // BS)].all()
            assert x.tail[0] == first
            assert len(x.owned[0]) + len(x.shared[0]) <= x.peak[0]
            # class 0 keeps every block
            assert eng._full_rows[0][:-(-nq // BS)].all()
        if not busy:
            break
    assert waiting_seen and len(req.out_tokens) == 12
    assert watch.worst(model, key, req) < TOL
    assert x.accounting()["total"] == x.n_pages - 1
    assert x.accounting()["slot_owned"] == x.accounting()["slot_shared"] == 0


def test_a_hit_needs_the_windows_tail():
    """A document served once is cached in both classes.  With its
    window pages whole, the next request over it hits all 13 pages; with
    the last of them evicted from the window class the hit shortens to
    the longest prefix whose tail is there (12 pages, 4 tokens lost);
    with the window class emptied it is void and every token the global
    class had counts as lost.  Each answer still matches the
    reference."""
    model, key = _model((2, 2)), harness.seed_key(18)
    eng, _ = _engine(model, key)
    x, watch = _window_state(eng), _Watch(eng)
    doc = _tokens(8, 1, 52)[0]
    ask = lambda rid, n: Request(                       # noqa: E731
        rid=rid, prompt=np.concatenate([doc, _tokens(30 + rid, 1, n)[0]]),
        max_new_tokens=4)
    watch.serve(Request(rid=0, prompt=doc.copy(), max_new_tokens=3))
    hashes = eng._page_hashes(doc)
    assert len(hashes) == 13
    assert all(h in eng.pool.cache and h in x.pool.cache for h in hashes)

    whole = ask(1, 9)
    watch.serve(whole)
    assert eng.stats["prefill_cached_tokens"] == 52
    assert eng.stats["prefill_window_lost_tokens"] == 0

    # the window class loses the document's last page (a tail, which
    # the pool keeps past every interior page: put it first in line)
    page = x.pool.cache[hashes[12]]
    assert page in x.pool.evictable and page not in x.pool.interior
    x.pool.interior = {page: None, **x.pool.interior}
    assert x.pool.evict(1) == 1 and hashes[12] not in x.pool.cache
    shorter = ask(2, 9)
    watch.serve(shorter)
    assert eng.stats["prefill_cached_tokens"] == 52 + 48
    assert eng.stats["prefill_window_lost_tokens"] == 4

    # and then everything it had
    x.pool.evict(len(x.pool.evictable))
    assert not x.pool.cache
    void = ask(3, 9)
    watch.serve(void)
    assert eng.stats["prefill_cached_tokens"] == 52 + 48
    assert eng.stats["prefill_window_lost_tokens"] == 4 + 52
    for req in (whole, shorter, void):
        assert watch.worst(model, key, req) < TOL
    # only the blocks a query at the hit's end reads were claimed:
    # pages 10..12 of 13 for the whole hit (live from 52 - 12 + 1 = 41)
    assert eng._usable_hit(eng._page_hashes(doc)) == (13, 13)


def test_admission_needs_room_in_every_class():
    """A window pool that holds one request's reserved peak: the second
    waits for the first although the global pool has room for both, and
    a request whose peak the pool can never hold is refused at submit."""
    model, key = _model((2, 2)), harness.seed_key(19)
    eng, _ = _engine(model, key, class_pages={"window": 1 + 21})
    x, watch = _window_state(eng), _Watch(eng)
    a = Request(rid=0, prompt=_tokens(40, 1, 90)[0], max_new_tokens=6)
    b = Request(rid=1, prompt=_tokens(41, 1, 90)[0], max_new_tokens=6)
    assert eng._class_peak(x, 24) == 21       # 3 + 2 * 8 + 2
    eng.submit(a)
    eng.submit(b)
    together = 0
    while watch.step():
        _assert_ledger(x)
        together += sum(s is not None for s in eng.slots) == 2
        assert x.live_pages() <= 21
    assert not together
    assert len(a.out_tokens) == len(b.out_tokens) == 6
    assert b.age > 0                                   # it was skipped
    for req in (a, b):
        assert watch.worst(model, key, req) < TOL
    small, _ = _engine(model, key, class_pages={"window": 1 + 20})
    with pytest.raises(ValueError, match="class 'window'"):
        small.submit(Request(rid=2, prompt=_tokens(42, 1, 90)[0],
                             max_new_tokens=6))


def test_abort_and_preemption_free_every_class():
    model, key = _model((2, 2)), harness.seed_key(20)
    eng, _ = _engine(model, key, prefix_cache=False)
    x, watch = _window_state(eng), _Watch(eng)
    a = Request(rid=0, prompt=_tokens(43, 1, 50)[0], max_new_tokens=30)
    b = Request(rid=1, prompt=_tokens(44, 1, 41)[0], max_new_tokens=30)
    eng.submit(a)
    eng.submit(b)
    for _ in range(6):
        watch.step()
        _assert_ledger(x)
    assert x.live_pages() and a.out_tokens and b.out_tokens
    assert eng.abort(0)
    _assert_ledger(x)
    admit = eng._admit

    def admit_then_preempt(now):
        # where the scheduler preempts: inside an admission pass, so the
        # victim's token in flight lands before it is admitted again
        admit(now)
        eng._admit = admit
        eng._preempt(1)
        assert x.live_pages() == 0 and not x.full_rows.any()
        assert x.peak == [0, 0, 0] and x.tail == [0, 0, 0]
        assert x.deferred_free and not set(x.deferred_free) & set(x.pool.free)
        _assert_ledger(x)

    eng._admit = admit_then_preempt
    while watch.step():
        _assert_ledger(x)
    assert len(b.out_tokens) == 30 and b.n_preempted == 1
    assert watch.worst(model, key, b) < TOL
    for ledger in eng.page_accounting()["classes"].values():
        assert ledger["free"] == ledger["total"]


# -- spans and counters -------------------------------------------------------

def _name_stacks(jaxpr, acc):
    """The name stack of every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        acc.append(str(eqn.source_info.name_stack))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _name_stacks(inner, acc)
    return acc


def test_engine_reports_what_its_classes_hold():
    from paddle_tpu import obs

    model, key = _model((2, 2)), harness.seed_key(23)
    ring = obs.arm().tracer
    eng, cfg = _engine(model, key)
    _Watch(eng).serve(Request(rid=0, prompt=_tokens(45, 1, 40)[0],
                              max_new_tokens=5))
    obs.arm()
    events, _ = ring.snapshot()
    spec = {e["args"]["cache_class"]: e["args"] for e in events
            if e["name"] == "engine.cache_spec"}
    assert spec["global"]["bytes_per_token"] == 256
    assert spec["global"]["window"] == 0 and spec["global"]["layers"] == 1
    assert spec["window"]["bytes_per_token"] == 768
    assert spec["window"]["window"] == W and spec["window"]["layers"] == 3
    ends = [e["args"] for e in events if e["name"] == "engine.step"
            and e["ph"] == "E" and "pages_live.window" in e.get("args", {})]
    keys = ("pages_live.global", "pages_live.window", "context_tokens_live")
    for k in keys:
        assert sum(a[k] for a in ends) == eng.stats[k] > 0
    steps = [e["args"] for e in events if e["name"] == "engine.step"
             and e["ph"] == "E"]
    assert sum(a.get("pages_released_by_window", 0) for a in steps) \
        == eng.stats["pages_released_by_window"] > 0
    # the global class holds the whole request all along, the window
    # class a window's worth: bytes a context token fall well under one
    # pool for all four layers (1024 B)
    live = (eng.stats["pages_live.global"] * eng.kv_bytes_per_page(0)
            + eng.stats["pages_live.window"] * eng.kv_bytes_per_page(1))
    assert live / eng.stats["context_tokens_live"] < 0.8 * 1024
    # the layers' scopes name the equations of the step the engine traces
    stacks = set(_name_stacks(eng.trace_unified().jaxpr, []))
    for scope in ("layer/attn_window", "layer/attn_global", "layer/qkv",
                  "layer/kv_write", "layer/router", "layer/experts",
                  "layer/shared_expert"):
        assert any(scope in n for n in stacks), scope


def test_a_model_with_one_class_traces_the_operands_it_had():
    """LLaMA declares no classes: one class over all its layers, no
    operand for a further one, and none of the classes' counters."""
    from paddle_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=64, hidden=32, n_layers=2, n_heads=2,
                      n_kv_heads=1, ffn_hidden=64, max_seq_len=64,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    eng = ServingEngine(cfg, max_batch=2, page_size=8, max_seq=32,
                        n_pages=9, prefill_budget=16, qb=8)
    assert [(c.name, c.n_layers, c.window) for c in eng.classes] == [
        ("global", 2, None)]
    assert not eng._extra and len(eng.unified_arg_shapes()) == 15
    assert "pages_live.global" not in eng.stats
    assert "classes" not in eng.page_accounting()


@pytest.mark.parametrize("feature,kw", [
    ("kv_quant", {"kv_quant": True}), ("lora", {"lora": True}),
    ("constrained", {"constrained": True}),
    ("speculative", {"speculative_k": 2}),
    ("weight_only_int8", {"weight_only_int8": True}),
    ("page_shipment", {"prefill_only": True})])
def test_features_left_out_fail_with_one_clear_error(feature, kw):
    model, key = _model((2, 2)), harness.seed_key(18)
    with pytest.raises(NotImplementedError, match=feature):
        _engine(model, key, **kw)


# -- tight enough: each planted departure fails -----------------------------

def _departed(name, params, cfg, tokens, monkeypatch):
    if name == "bf16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    elif name == "no_window_mask":
        cfg = dataclasses.replace(cfg, sliding_window=10 ** 6)
    elif name == "window_off_by_one":
        cfg = dataclasses.replace(cfg, sliding_window=W + 1)
    elif name == "rotation_in_the_global_layer":
        real = cohere_moe.project_qkv
        monkeypatch.setattr(
            cohere_moe, "project_qkv",
            lambda h, lp, c, kind, cos, sin: real(h, lp, c, "window", cos,
                                                  sin))
    elif name == "shared_experts_summed":
        monkeypatch.setattr(CohereMoeConfig, "routing", property(
            lambda self: routed_experts.Routing(
                k=self.experts_per_token, held=self.held, dtype=self.dtype)))
    elif name == "halves_on_the_sources_layout":
        model = _model((2, 2))
        params = jax.tree.map(
            lambda a: a.astype(jnp.float32),
            {**REF.outer_weights(model, harness.seed_key(19)),
             **{kind: jax.tree.map(
                 lambda *a: jnp.stack(a),
                 *[REF.layer_weights(model, harness.seed_key(19), l)
                   for l, k in enumerate(REF.kinds(model)) if k == kind])
                for kind in ("window", "global")}})
    return cohere_moe.cohere_moe_apply(params, jnp.asarray(tokens), cfg)


@pytest.mark.parametrize("name", [
    "bf16", "no_window_mask", "window_off_by_one",
    "rotation_in_the_global_layer", "shared_experts_summed",
    "halves_on_the_sources_layout"])
def test_each_departure_from_the_equations_fails_the_tolerance(
        name, monkeypatch):
    model, key = _model((2, 2)), harness.seed_key(19)
    tokens = _tokens(8, 2, 40)
    want = _ref_logits(model, key, tokens)
    got = _departed(name, _params(model, key), _cfg(model), tokens,
                    monkeypatch)
    assert np.abs(np.asarray(got, np.float32) - want).max() > 50 * TOL, name


def test_an_fp8_operand_fails_the_tolerance():
    """The control of the chip's comparison, at the tiny size: the
    reference with every matmul's operands in fp8 lies 50 tolerances and
    more from the reference."""
    model, key = _model((2, 2)), harness.seed_key(19)
    tokens = _tokens(8, 2, 40)
    low = _ref_logits(model, key, tokens, quant="fp8")
    assert np.abs(low - _ref_logits(model, key, tokens)).max() > 50 * TOL
