"""models/granite_hybrid.py and the engine's state class against the plain
reference (benchmark/configs/granite-4.0-h-micro.reference.py), at a tiny
size on seeded weights: five layers mamba-mamba-attention-mamba-mamba, 8
state-space heads of 16 with a state of 16, 4/2 attention heads, pages of
8 tokens.

Tolerances, and why.  In fp32 at `highest` (conftest pins it) both sides
differ by the order of their sums only, and by the program's closed form
of a row's block against the reference's token-by-token recurrence:
logits of size ~0.5 agree to ~2e-6, the limit is ``TOL`` = 2e-5 on the
widest difference of a logit, and the engine is held to it on the whole
logits row of every token it served (`_Watch`).  With bf16 weights,
activations and pages (what the chip runs; the state stays fp32) the
program's logits lie within ``TOL_BF16`` = 0.05 of the fp32 reference's:
bf16 keeps 8 bits, a logit is a sum over 64 products of such values
through 5 layers, and the readings are 5e-3 to 1.5e-2.  What each planted
departure reads against ``TOL`` (the last tests): a state never reset, a
snapshot from the wrong boundary, the state in bf16."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models import granite_hybrid, seam
from paddle_tpu.models.granite_hybrid import GraniteHybridConfig

REF = harness.reference_for("granite-4.0-h-micro")
TOL, TOL_BF16 = 2e-5, 0.05
BS = 8

MODEL = {
    "hidden_size": 64, "shared_intermediate_size": 96,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 5,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_expand": 2,
    "attention_multiplier": 0.25, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 0.125,
    "rms_norm_eps": 1e-5, "vocab_size": 256,
    "max_position_embeddings": 512,
    # 64 wide: matrices at 1/8 so that a layer moves the stream, and a
    # small embedding, or the tied head reads every token back as itself
    # (powers of two: a weight made under jit and one made eagerly then
    # round to bf16 alike)
    "init_std": 0.125, "embedding_init_std": 2.0 ** -10}


def _cfg(model=MODEL, **over):
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32)
    kw.update(over)
    return GraniteHybridConfig.from_hf(model, **kw)


def _params(model, key, dtype=jnp.float32):
    """The reference's weights (bf16 values) as the program takes them,
    carried in fp32 so that both sides compute in one precision."""
    return jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16
                        else a, REF.make_params(model, key))


def _tokens(seed, T, n=None):
    out = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n or 1, T), 0, MODEL["vocab_size"]),
        np.int32)
    return out if n else out[0]


def _ref_logits(model, key, tokens, quant=None):
    pos = [list(range(tokens.shape[1]))] * len(tokens)
    return np.stack(REF.logits_at(model, key, tokens, pos, quant=quant))


def _engine(model=MODEL, key=None, snapshots=6, cfg=None, **kw):
    key = harness.seed_key(5) if key is None else key
    cfg = cfg or _cfg(model)
    opts = dict(max_batch=3, page_size=BS, max_seq=160, n_pages=80,
                prefill_budget=48, qb=8, prefix_cache=True,
                class_pages={"state": snapshots})
    opts.update(kw)
    return ServingEngine(cfg, params=_params(model, key, cfg.param_dtype),
                         **opts), key


class _Watch:
    """The logits row behind every token an engine serves (the helper of
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

    def logits_of(self, req) -> np.ndarray:
        rows = sorted(((j, row) for r, j, row in self.rows if r is req),
                      key=lambda x: x[0])
        assert [j for j, _ in rows] == list(range(len(req.out_tokens)))
        return np.stack([row for _, row in rows])

    def worst(self, model, key, req) -> float:
        out = np.asarray(req.out_tokens, np.int32)
        seq = np.concatenate([req.prompt, out[:-1]])[None]
        P = len(req.prompt)
        want = REF.logits_at(model, key, seq,
                             [list(range(P - 1, P - 1 + len(out)))])[0]
        got = self.logits_of(req)
        assert out.tolist() == got.argmax(-1).tolist()
        return float(np.abs(got - want).max())


# -- the full-sequence forward ------------------------------------------------

def test_full_forward_matches_the_reference():
    key = harness.seed_key(11)
    tokens = _tokens(1, 40, n=2)
    got = granite_hybrid.granite_hybrid_apply(
        _params(MODEL, key), jnp.asarray(tokens), _cfg())
    want = _ref_logits(MODEL, key, tokens)
    assert np.abs(np.asarray(got) - want).max() < TOL
    # the toy is no echo: the served token is rarely the one just read
    assert (want.argmax(-1) == tokens).mean() < 0.2
    assert 0.05 < np.abs(want).max() < 20


def test_bf16_program_lies_within_the_bf16_tolerance():
    """bf16 weights and activations, fp32 state: the precision the
    configuration states, against the fp32 reference."""
    key = harness.seed_key(12)
    tokens = _tokens(2, 40, n=2)
    cfg = _cfg(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    got = granite_hybrid.granite_hybrid_apply(
        _params(MODEL, key, jnp.bfloat16), jnp.asarray(tokens), cfg)
    gap = np.abs(np.asarray(got) - _ref_logits(MODEL, key, tokens)).max()
    assert TOL < gap < TOL_BF16


def test_config_and_runs_follow_the_published_order():
    cfg = GraniteHybridConfig()
    assert cfg.n_layers == 40 and cfg.layer_types.count("attention") == 4
    assert [r[2] for r in cfg.runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert (cfg.d_inner, cfg.conv_dim, cfg.head_dim) == (4096, 4352, 64)
    assert _cfg().runs == (("mamba", 0, 2), ("attention", 0, 1),
                           ("mamba", 2, 2))
    with pytest.raises(NotImplementedError, match="num_local_experts"):
        GraniteHybridConfig.from_hf(dict(MODEL, num_local_experts=8))
    with pytest.raises(NotImplementedError, match="position_embedding"):
        GraniteHybridConfig.from_hf(dict(MODEL,
                                         position_embedding_type="rope"))


def test_cache_classes_are_pages_and_a_state_class():
    model = _cfg().serving_model()
    paged, state = seam.cache_classes(model, BS)
    assert (paged.name, paged.n_layers, paged.window) == ("global", 1, None)
    assert isinstance(state, seam.StateClass) and state.n_layers == 4
    assert [(p.name, p.shape, jnp.dtype(p.dtype).name)
            for p in state.planes] == [("conv", (3 * 160,), "float32"),
                                       ("ssm", (1, 16, 128), "float32")]
    assert state.slot_bytes() == 4 * 4 * (480 + 2048)
    # the published widths: 76.4 MB a request, as much as 9,300 tokens of
    # the four attention layers' pages
    real = seam.cache_classes(GraniteHybridConfig().serving_model(), 128)
    assert real[1].slot_bytes() == 36 * (64 * 64 * 128 * 4 + 4352 * 3 * 2)
    assert real[0].spec.page_bytes(4) == 2 ** 20
    assert [p.page_shape for p in real[0].spec.planes] == [
        (4, 128, 128), (4, 128, 128)]           # kv heads of 64 in pairs

    class Wrong:
        def cache_classes(self, page_size):
            return (state, paged)

    with pytest.raises(ValueError, match="state class"):
        seam.cache_classes(Wrong(), BS)


@pytest.mark.parametrize("feature,kw", [
    ("speculative", {"speculative_k": 2}), ("kv_quant", {"kv_quant": True}),
    ("lora", {"lora": True}), ("page_shipment", {"prefill_only": True}),
    ("weight_only_int8", {"weight_only_int8": True})])
def test_unsupported_features_are_one_error(feature, kw):
    with pytest.raises(NotImplementedError, match=feature):
        _engine(**kw)


# -- the engine: prefill in chunks, decode through the slots ----------------

def test_prefill_in_chunks_then_decode_equals_the_full_forward():
    """Three requests of unlike lengths share ticks: chunk rows of one
    beside decode rows of another (continuous batching across the
    state-space layers), every served token's logits row against the
    reference."""
    eng, key = _engine()
    watch = _Watch(eng)
    reqs = [Request(rid=i, prompt=_tokens(20 + i, T), max_new_tokens=n)
            for i, (T, n) in enumerate([(43, 9), (7, 14), (70, 5)])]
    watch.serve(*reqs)
    for r in reqs:
        assert watch.worst(MODEL, key, r) < TOL
    acc = eng.page_accounting()["classes"]["state"]
    assert acc["total"] == eng._state.n_snapshots
    assert acc["held"] == acc["pending"] == 0
    assert eng.stats["state_snapshots_taken"] > 0


def test_chunks_in_one_tick_equal_one_row_a_tick():
    """A prompt of 45 tokens as six rows of ONE tick (the state chained
    from row to row) and as one row a tick (the state through the pool
    every time): the same logits."""
    key, prompt = harness.seed_key(6), _tokens(31, 45)
    rows = []
    for budget, batch in ((64, 1), (8, 1)):
        eng, _ = _engine(key=key, prefill_budget=budget, max_batch=batch,
                         prefix_cache=False)
        watch = _Watch(eng)
        req = Request(rid=0, prompt=prompt, max_new_tokens=6)
        watch.serve(req)
        assert watch.worst(MODEL, key, req) < TOL
        rows.append((eng.stats["unified_steps"], watch.logits_of(req)))
    assert rows[0][0] < rows[1][0]
    assert np.abs(rows[0][1] - rows[1][1]).max() < TOL


def test_the_kernel_serves_chained_rows_in_the_engine(monkeypatch):
    """The same through the Pallas kernel (interpret mode): a state of
    128, the form named as the autotune would."""
    from paddle_tpu.ops.pallas import ragged_ssm_scan as rss

    monkeypatch.setattr(rss, "choose_impl", lambda *a: "kernel_h8")
    model = dict(MODEL, mamba_d_state=128)
    eng, key = _engine(model)
    watch = _Watch(eng)
    reqs = [Request(rid=0, prompt=_tokens(41, 37), max_new_tokens=4),
            Request(rid=1, prompt=_tokens(42, 9), max_new_tokens=6)]
    watch.serve(*reqs)
    for r in reqs:
        assert watch.worst(model, key, r) < TOL


def test_heads_of_64_are_paged_in_pairs():
    """Attention heads of 64: a page holds the kv heads two a 128-lane
    tile and a query rides with zeros under the other; the logits are
    the reference's."""
    model = dict(MODEL, hidden_size=256, mamba_n_heads=32,
                 init_std=0.0625)
    eng, key = _engine(model)
    assert eng.model.pack == 2
    assert eng.k_pages.shape[2:] == (1, 128, BS)
    assert eng.v_pages.shape[2:] == (1, BS, 128)
    watch = _Watch(eng)
    req = Request(rid=0, prompt=_tokens(43, 29), max_new_tokens=5)
    watch.serve(req)
    assert watch.worst(model, key, req) < TOL


# -- slots: a new tenant reads zeros ----------------------------------------

def test_a_poisoned_slot_never_reaches_the_next_tenant():
    """Every live and snapshot slot (and the dump) filled with NaN, the
    zero slot apart: a fresh request still reads the reference's logits,
    and so does the one that takes its row after it."""
    eng, key = _engine(max_batch=1)
    st = eng._state
    for name in ("k_pages", "v_pages"):
        pool = getattr(st, name)
        setattr(st, name, pool.at[:, 1:].set(jnp.nan))
    watch = _Watch(eng)
    a = Request(rid=0, prompt=_tokens(50, 21), max_new_tokens=4)
    b = Request(rid=1, prompt=_tokens(51, 13), max_new_tokens=4)
    watch.serve(a, b)
    assert watch.worst(MODEL, key, a) < TOL
    assert watch.worst(MODEL, key, b) < TOL
    assert not np.isnan(np.asarray(st.v_pages[:, seam.STATE_ZERO])).any()
    assert float(jnp.abs(st.v_pages[:, seam.STATE_ZERO]).max()) == 0.0


# -- snapshots ---------------------------------------------------------------

def _turns(eng, key, first, later, answers, rid0=0):
    """Serve a conversation turn by turn: a turn's prompt is everything
    sent and served so far and the new message."""
    watch, history, reqs = _Watch(eng), np.zeros((0,), np.int32), []
    for i, (msg, n) in enumerate(zip([first] + later, answers)):
        req = Request(rid=rid0 + i, prompt=np.concatenate([history, msg]),
                      max_new_tokens=n)
        watch.serve(req)
        history = np.concatenate([req.prompt,
                                  np.asarray(req.out_tokens, np.int32)])
        reqs.append(req)
    return watch, reqs


def test_a_turn_from_a_snapshot_equals_the_turn_with_the_cache_off():
    key = harness.seed_key(7)
    msgs = [_tokens(60, 30), _tokens(61, 7), _tokens(62, 11)]
    served = {}
    for cache in (True, False):
        eng, _ = _engine(key=key, prefix_cache=cache)
        watch, reqs = _turns(eng, key, msgs[0], msgs[1:], [10, 12, 6])
        served[cache] = [watch.logits_of(r) for r in reqs]
        for r in reqs:
            assert watch.worst(MODEL, key, r) < TOL
        if cache:
            # turn 2 starts at the last boundary of turn 1's 30 + 10 - 1
            # processed tokens, turn 3 likewise: snapshots taken in decode
            assert eng.stats["state_snapshots_hit"] == 2
            assert eng.stats["prefill_cached_tokens"] == 32 + 56
            assert eng.stats["prefix_state_lost_tokens"] == 0
            assert eng.stats["admitted_with_cached_prefix"] == 2
        else:
            assert eng.stats["state_snapshots_taken"] == 0
            assert eng.stats["prefill_cached_tokens"] == 0
    for a, b in zip(served[True], served[False]):
        assert [x.tolist() for x in a.argmax(-1)] == [
            x.tolist() for x in b.argmax(-1)]
        assert np.abs(a - b).max() < TOL


def test_a_cached_snapshot_is_the_references_state_at_exactly_its_token():
    """``cached_snapshots``: the slot a live request's snapshot stands
    in holds, layer for layer and head for head, the reference's state
    after exactly that many of its tokens (prompt, then served), and so
    does the reference cut there."""
    eng, key = _engine()
    req = Request(rid=0, prompt=_tokens(120, 29), max_new_tokens=30)
    eng.submit(req)
    while len(req.out_tokens) < 14:
        eng.step()
    snaps = eng.cached_snapshots()
    # the one it holds (taken in decode) and earlier ones, oldest first
    assert [t for _r, t, _s in snaps] == sorted(
        {t for _r, t, _s in snaps}) and len(snaps) > 1
    got_req, tokens, slot = snaps[-1]
    assert slot == eng._state.snap0 + eng._state.held[0]
    assert got_req is req and tokens % BS == 0 and 24 < tokens <= 29 + 14
    seq = np.concatenate([req.prompt, np.asarray(req.out_tokens, np.int32)])
    _, (want,) = REF.logits_at(MODEL, key, seq[None], [[len(seq) - 1]],
                               states_at=[tokens])
    _, (cut,) = REF.logits_at(MODEL, key, seq[None, :tokens], [[tokens - 1]],
                              states_at=[tokens])
    assert want.shape == (4, 8, 16, 16) and np.abs(want - cut).max() < 1e-6
    # a slot is [heads / P, d_state, P x head width], the state transposed
    tiles = np.asarray(eng._state.v_pages[:, slot])
    got = tiles.reshape(4, 1, 16, 8, 16).transpose(0, 1, 3, 4, 2).reshape(
        4, 8, 16, 16)
    assert np.linalg.norm(got - want) < 1e-5 * np.linalg.norm(want)
    # the state a token later is another state
    _, (later,) = REF.logits_at(MODEL, key, seq[None], [[len(seq) - 1]],
                                states_at=[tokens + 1])
    assert np.linalg.norm(later - want) > 1e-2 * np.linalg.norm(want)
    while eng.step():
        pass
    assert eng.cached_snapshots() == []


def test_eviction_of_the_needed_snapshot_falls_back_to_token_0():
    """Two snapshot slots: other traffic evicts the conversation's
    snapshot between its turns; class 0 still has its pages, the state
    class has nothing at any boundary, the turn starts at 0 and agrees."""
    eng, key = _engine(snapshots=2)
    watch = _Watch(eng)
    first = Request(rid=0, prompt=_tokens(70, 26), max_new_tokens=8)
    watch.serve(first)
    for i in range(3):                      # evicts: each takes snapshots
        watch.serve(Request(rid=10 + i, prompt=_tokens(80 + i, 19),
                            max_new_tokens=3))
    assert eng.stats["state_snapshots_evicted"] > 0
    lost0 = eng.stats["prefix_state_lost_tokens"]
    second = Request(rid=1, prompt=np.concatenate([
        first.prompt, np.asarray(first.out_tokens, np.int32),
        _tokens(71, 9)]), max_new_tokens=5)
    hit0 = eng.stats["state_snapshots_hit"]
    watch.serve(second)
    assert eng.stats["state_snapshots_hit"] == hit0
    assert eng.stats["prefix_state_lost_tokens"] - lost0 == 32
    assert watch.worst(MODEL, key, second) < TOL


def test_preempt_and_resume_agrees_and_starts_from_the_snapshot():
    eng, key = _engine()
    watch = _Watch(eng)
    req = Request(rid=0, prompt=_tokens(90, 37), max_new_tokens=14)
    eng.submit(req)
    while len(req.out_tokens) < 6:
        watch.step()
    slot = eng.slots.index(req)
    # preempted where the engine preempts: inside an admission pass, so
    # that the token in flight lands before the request comes back
    real = eng._admit

    def admit(now):
        real(now)
        if req.n_preempted == 0:
            eng._preempt(slot)
            assert eng._state.at[slot] == seam.STATE_ZERO

    eng._admit = admit
    while watch.step():
        pass
    assert req.n_preempted == 1 and len(req.out_tokens) == 14
    assert eng.stats["preempt_resumed_from_snapshot"] == 1
    # resumed at the boundary behind what it had processed, not at 0
    assert eng.stats["prefill_cached_tokens"] == 40
    assert watch.worst(MODEL, key, req) < TOL


def test_admission_without_a_snapshot_slot_runs_without_snapshots():
    """No snapshot slot at all: requests are admitted, served and agree;
    every boundary is counted as unavailable and nothing is hit."""
    eng, key = _engine(snapshots=0)
    watch, reqs = _turns(eng, key, _tokens(95, 20), [_tokens(96, 6)], [7, 5])
    for r in reqs:
        assert watch.worst(MODEL, key, r) < TOL
    st = eng.stats
    assert st["state_snapshots_taken"] == st["state_snapshots_hit"] == 0
    assert st["state_snapshots_unavailable"] > 0
    # class 0 had the first prompt's two full pages (pages that fill in
    # decode are offered with the snapshot at their end)
    assert st["prefix_state_lost_tokens"] == 16
    assert st["prefill_cached_tokens"] == 0


def test_the_hit_rule_takes_the_longest_every_class_honours():
    eng, _ = _engine()
    watch = _Watch(eng)
    req = Request(rid=0, prompt=_tokens(97, 45), max_new_tokens=3)
    watch.serve(req)
    hashes = eng._page_hashes(req.prompt)
    # class 0 holds the prompt's five pages; snapshots stand where ticks
    # ended: at 40 (the prompt's last boundary) and no later
    assert eng._usable_hit(hashes, state=False) == (5, 5)
    assert eng._usable_hit(hashes) == (5, 5)
    assert eng._usable_hit(hashes[:4]) == (0, 4)
    assert [eng._state.has(h) for h in hashes] == [
        False, False, False, False, True]


def test_stats_and_step_spans_carry_the_state_class():
    from paddle_tpu import obs

    ring = obs.arm().tracer
    eng, key = _engine()
    _turns(eng, key, _tokens(98, 22), [_tokens(99, 5)], [9, 4])
    obs.arm()
    events, _ = ring.snapshot()
    spec = {e["args"]["cache_class"]: e["args"] for e in events
            if e["name"] == "engine.cache_spec"}
    assert spec["state"]["slot_bytes"] == eng.classes[1].slot_bytes()
    assert spec["state"]["layers"] == 4 and spec["global"]["layers"] == 1
    # six snapshot slots asked for, eleven given: 2 + 3 + 11 slots fill
    # the conv plane's sublane tiles, so that the step's flattening of
    # [layers, slots, .] is no copy whatever count a deployment asks for
    assert (spec["state"]["live_slots"], spec["state"]["snapshot_slots"]) \
        == (3, 11)
    assert eng._state.k_pages.shape[1] == eng._state.n_slots == 16
    st = eng.stats
    ends = [e["args"] for e in events if e["name"] == "engine.step"
            and e["ph"] == "E" and "state_slots_live" in e.get("args", {})]
    for k in ("state_slots_live", "state_bytes_live", "pages_live.global"):
        assert sum(a[k] for a in ends) == st[k] > 0
    assert st["state_bytes_live"] == st["state_slots_live"] * eng.classes[
        1].slot_bytes() > 0
    assert st["context_tokens_live"] > 0 and st["pages_live.global"] > 0
    from paddle_tpu.obs import metrics

    assert set(metrics.STATE_CLASS_STATS_SCHEMA) <= set(st)


# -- planted departures -------------------------------------------------------

def test_a_state_never_reset_reads_wrong(monkeypatch):
    """A new tenant that starts from its row's live slot instead of the
    zero slot sees the last tenant's state."""
    eng, key = _engine(max_batch=1, prefix_cache=False)
    watch = _Watch(eng)
    watch.serve(Request(rid=0, prompt=_tokens(100, 17), max_new_tokens=3))
    real = eng._state_table

    def stale(sched):
        tab = real(sched)
        tab[:-1, 0] = np.where(tab[:-1, 0] == seam.STATE_ZERO,
                               tab[:-1, 1], tab[:-1, 0])
        return tab

    monkeypatch.setattr(eng, "_state_table", stale)
    req = Request(rid=1, prompt=_tokens(101, 12), max_new_tokens=3)
    watch.serve(req)
    out = np.asarray(req.out_tokens, np.int32)
    want = REF.logits_at(MODEL, key,
                         np.concatenate([req.prompt, out[:-1]])[None],
                         [list(range(11, 14))])[0]
    assert np.abs(watch.logits_of(req) - want).max() > 50 * TOL


def test_the_state_in_bf16_reads_wrong():
    """The state rounded to bf16 at every tick (``state_dtype``): the
    precision below the configuration's, by more than the tolerance."""
    key = harness.seed_key(8)
    cfg = _cfg(state_dtype=jnp.bfloat16)
    eng, _ = _engine(key=key, cfg=cfg)
    watch = _Watch(eng)
    req = Request(rid=0, prompt=_tokens(102, 40), max_new_tokens=20)
    watch.serve(req)
    out = np.asarray(req.out_tokens, np.int32)
    want = REF.logits_at(MODEL, key,
                         np.concatenate([req.prompt, out[:-1]])[None],
                         [list(range(39, 59))])[0]
    got = np.abs(watch.logits_of(req) - want).max()
    low = REF.logits_at(MODEL, key,
                        np.concatenate([req.prompt, out[:-1]])[None],
                        [list(range(39, 59))], quant="bf16_state")[0]
    # readings 1.2e-4 (rounded once a tick) and 2e-4 (once a token)
    assert got > 4 * TOL and np.abs(low - want).max() > 4 * TOL, (
        got, np.abs(low - want).max())
