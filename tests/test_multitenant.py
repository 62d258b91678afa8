"""Multi-tenant serving (ISSUE 10): per-request LoRA on the page pool,
priority preemption, constrained decoding — and the invariants that make
them safe to ship on the unified engine:

- 7-class page ledger: free + slot_owned + slot_shared + cache_idle +
  deferred_free + adapter == n_pages - 1, checked per step under
  randomized multi-tenant load;
- adapter residency is refcounted and content-hashed: repeated requests
  under the same adapter (even under different registered ids with
  identical weights) share ONE set of adapter pages;
- the grouped BGMV kernel and its XLA gather arm are bitwise equal;
- a preempted-then-resumed stream is bit-identical to an uninterrupted
  run (keyed sampling + re-prefill through the prefix cache);
- a constrained request emits only schema-legal tokens, greedy and
  sampled alike;
- every flag defaults OFF and off == bit-identical to the pre-ISSUE-10
  engine (streams AND the workload byte stream)."""

import string

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.inference.multitenant import (AdapterStore, TokenDfa,
                                              json_schema_dfa, make_lora)
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.llama import LlamaConfig

CFG = LlamaConfig(vocab_size=512, hidden=128, n_layers=2, n_heads=8,
                  n_kv_heads=4, ffn_hidden=256, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)

VOCAB = [""] * 512
for _i, _ch in enumerate(string.printable[:94]):
    VOCAB[_i + 1] = _ch


def _mk_engine(**kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_seq", 96)
    kw.setdefault("prefill_budget", 32)
    return ServingEngine(CFG, seed=0, **kw)


def _assert_accounting(engine):
    acc = engine.page_accounting()
    assert acc["total"] == engine.n_pages - 1, acc
    owned = [p for lst in engine._slot_owned for p in lst]
    shared = {p for lst in engine._slot_shared for p in lst}
    idle = {p for p, r in engine.pool.ref.items() if r == 0}
    adapter = ([p for pl in engine.adapters._pages.values() for p in pl]
               if engine.adapters is not None else [])
    groups = [set(engine.pool.free), set(owned), shared, idle,
              set(engine._deferred_free), set(adapter)]
    assert len(owned) == len(set(owned))
    assert len(adapter) == len(set(adapter))
    for i in range(len(groups)):
        for j in range(i + 1, len(groups)):
            assert not (groups[i] & groups[j]), (i, j, groups)


# -- LoRA: kernel parity, refcount sharing, stream isolation ----------------


def test_lora_kernel_xla_parity_bitwise():
    """The Pallas BGMV kernel (interpret mode on CPU) and the XLA gather
    arm produce bitwise-identical fp32 outputs — the equality pin that
    lets the autotuner race them per shape bucket."""
    from paddle_tpu.ops.pallas.lora_matmul import (lora_matmul_kernel,
                                                   lora_matmul_supported,
                                                   _lora_xla)

    rng = np.random.RandomState(0)
    C, qb, H, r, N, S = 4, 8, 128, 8, 256, 3
    assert lora_matmul_supported(qb, H, r, N)
    for dt in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(rng.randn(C, qb, H), dt)
        a = jnp.asarray(rng.randn(S, H, r) * 0.1, dt)
        b = jnp.asarray(rng.randn(S, r, N) * 0.1, dt)
        ids = jnp.asarray([0, 2, 1, 2], jnp.int32)
        want = np.asarray(_lora_xla(x, a, b, ids))
        # interpret mode is automatic off-TPU (_interpret_mode())
        got = np.asarray(lora_matmul_kernel(x, a, b, ids, bn=128))
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want)


def test_adapter_store_refcount_and_dedup():
    """Same weight bytes under two registered ids dedupe to one resident
    copy; refcounts track live requests; idle adapters evict LRU and
    return their pages."""
    pool_pages = []
    free = list(range(100, 140))

    def alloc(n):
        if len(free) < n:
            return None
        got = [free.pop() for _ in range(n)]
        pool_pages.extend(got)
        return got

    def release(pages):
        for p in pages:
            pool_pages.remove(p)
            free.append(p)

    st = AdapterStore(CFG, rank=8, n_slots=2, page_bytes=4096,
                      alloc_pages=alloc, release_pages=release)
    w = make_lora(CFG, 8, seed=1)
    st.register("x", w)
    st.register("y", {k: v.copy() for k, v in w.items()})  # same bytes
    st.register("z", make_lora(CFG, 8, seed=2))
    s1 = st.acquire("x")
    s2 = st.acquire("y")
    assert s1 == s2                       # content-hash dedup: one copy
    assert st.ref_of("x") == 2
    assert st.pages_of("x") == st.pages_of("y")
    assert st.n_resident() == 1
    held = st.n_pages_held()
    s3 = st.acquire("z")
    assert s3 != s1
    assert st.n_pages_held() == 2 * held
    st.decref("x")
    st.decref("y")
    assert st.ref_of("x") == 0            # idle but warm
    assert st.n_resident() == 2
    # third adapter forces eviction of the idle one (slots exhausted)
    st.register("w2", make_lora(CFG, 8, seed=3))
    s4 = st.acquire("w2")
    assert s4 == s1                       # reused the evicted slot
    assert st.n_resident() == 2 and st.evictions == 1
    st.decref("z")
    st.decref("w2")
    st._evict_idle()
    st._evict_idle()
    assert st.n_pages_held() == 0 and not pool_pages


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
def test_lora_requests_share_adapter_pages_and_isolate_streams(kv_quant):
    """Two live same-adapter requests hold ONE set of adapter pages
    (refcount == 2 while both are resident); different adapters yield
    different streams; a no-adapter rider in the mix is bit-identical to
    the flag-off engine. Over int8 pages as over fp ones: the deltas are
    the model's, whatever its pages hold."""
    rng = np.random.RandomState(0)
    p0 = rng.randint(1, 512, size=20).astype(np.int32)
    eng = _mk_engine(lora=True, lora_rank=8, lora_slots=2, max_batch=3,
                     kv_quant=kv_quant)
    eng.register_adapter("a0", make_lora(CFG, 8, seed=1, scale=0.3))
    eng.register_adapter("a1", make_lora(CFG, 8, seed=2, scale=0.3))
    reqs = [Request(rid=0, prompt=p0, max_new_tokens=6, adapter_id="a0"),
            Request(rid=1, prompt=p0.copy(), max_new_tokens=6,
                    adapter_id="a0"),
            Request(rid=2, prompt=p0.copy(), max_new_tokens=6,
                    adapter_id="a1")]
    for r in reqs:
        eng.submit(r)
    saw_shared = False
    n = 0
    while eng.step(now=1e9) and n < 60:
        n += 1
        _assert_accounting(eng)
        if eng.adapters.ref_of("a0") == 2:
            saw_shared = True
            assert len(eng.adapters.pages_of("a0")) \
                == eng.adapters.pages_per_adapter
    assert saw_shared, "same-adapter requests never co-resided"
    assert reqs[0].out_tokens == reqs[1].out_tokens
    assert reqs[0].out_tokens != reqs[2].out_tokens
    # no-adapter rider == flag-off engine (identity slot + all-zero delta)
    eng2 = _mk_engine(lora=True, lora_rank=8, lora_slots=2,
                      kv_quant=kv_quant)
    eng2.register_adapter("a0", make_lora(CFG, 8, seed=1, scale=0.3))
    rider = Request(rid=3, prompt=p0.copy(), max_new_tokens=6)
    lead = Request(rid=4, prompt=rng.randint(1, 512, 24).astype(np.int32),
                   max_new_tokens=6, adapter_id="a0")
    eng2.run([lead, rider])
    eng3 = _mk_engine(kv_quant=kv_quant)
    base = Request(rid=5, prompt=p0.copy(), max_new_tokens=6)
    lead2 = Request(rid=6, prompt=lead.prompt.copy(), max_new_tokens=6)
    eng3.run([lead2, base])
    assert rider.out_tokens == base.out_tokens


def test_lora_prefix_cache_never_aliases_across_adapters():
    """KV pages written under adapter X carry X's v-deltas — a request
    under adapter Y (or none) with the SAME prompt must not hit them
    (the adapter digest salts the page hash)."""
    rng = np.random.RandomState(1)
    p0 = rng.randint(1, 512, size=40).astype(np.int32)
    eng = _mk_engine(lora=True, lora_rank=8, lora_slots=2, max_batch=1)
    eng.register_adapter("a0", make_lora(CFG, 8, seed=1, scale=0.3))
    ra = Request(rid=0, prompt=p0, max_new_tokens=4, adapter_id="a0")
    rb = Request(rid=1, prompt=p0.copy(), max_new_tokens=4, arrival=0.001)
    eng.run([ra, rb])
    eng2 = _mk_engine()
    rc = Request(rid=2, prompt=p0.copy(), max_new_tokens=4)
    eng2.run([rc])
    assert rb.out_tokens == rc.out_tokens   # not poisoned by a0's pages
    # and same-adapter requests DO share cached prefix pages
    eng3 = _mk_engine(lora=True, lora_rank=8, lora_slots=2, max_batch=1)
    eng3.register_adapter("a0", make_lora(CFG, 8, seed=1, scale=0.3))
    r1 = Request(rid=3, prompt=p0.copy(), max_new_tokens=4,
                 adapter_id="a0")
    r2 = Request(rid=4, prompt=p0.copy(), max_new_tokens=4,
                 adapter_id="a0", arrival=0.001)
    eng3.run([r1, r2])
    assert r1.out_tokens == ra.out_tokens
    assert r2.out_tokens == ra.out_tokens
    assert eng3.pool.hits > 0


# -- priorities + preemption ------------------------------------------------


def test_preempt_resume_bit_identity():
    """Under pool pressure a high-priority arrival evicts a low-priority
    resident's KV; the victim re-admits through the prefix cache and its
    final stream is bit-identical to an uninterrupted run."""
    rng = np.random.RandomState(2)
    mk = lambda **kw: _mk_engine(max_batch=4, n_pages=9, **kw)  # noqa: E731
    lows = [rng.randint(1, 512, size=30).astype(np.int32)
            for _ in range(2)]
    hi = rng.randint(1, 512, size=30).astype(np.int32)
    eng = mk(priorities=True)
    reqs = [Request(rid=0, prompt=lows[0], max_new_tokens=16, priority=0),
            Request(rid=1, prompt=lows[1], max_new_tokens=16, priority=0),
            Request(rid=2, prompt=hi, max_new_tokens=8, priority=5,
                    arrival=0.001)]
    out = eng.run(reqs)
    assert out["preemptions"] >= 1
    assert out["preemption_rate"] > 0
    victims = [r for r in reqs if r.n_preempted]
    assert victims
    _assert_accounting(eng)
    for v in victims:
        eng2 = mk()
        solo = Request(rid=9, prompt=v.prompt.copy(),
                       max_new_tokens=v.max_new_tokens)
        eng2.run([solo])
        assert solo.out_tokens == v.out_tokens
    # sampled victim: keyed sampling makes resume invisible too
    eng3 = mk(priorities=True)
    reqs3 = [Request(rid=0, prompt=lows[0], max_new_tokens=16, priority=0,
                     temperature=0.9, top_p=0.85, seed=77),
             Request(rid=1, prompt=lows[1], max_new_tokens=16, priority=0),
             Request(rid=2, prompt=hi, max_new_tokens=8, priority=5,
                     arrival=0.001)]
    out3 = eng3.run(reqs3)
    assert out3["preemptions"] >= 1
    for v in (r for r in reqs3 if r.n_preempted):
        eng4 = mk()
        solo = Request(rid=9, prompt=v.prompt.copy(),
                       max_new_tokens=v.max_new_tokens,
                       temperature=v.temperature, top_p=v.top_p,
                       seed=v.seed)
        eng4.run([solo])
        assert solo.out_tokens == v.out_tokens


def test_priority_admission_order_and_no_preempt_within_class():
    """Higher priority admits first from a backlog; equal priority never
    preempts (strict inequality)."""
    rng = np.random.RandomState(3)
    eng = _mk_engine(max_batch=1, priorities=True)
    reqs = [Request(rid=i, prompt=rng.randint(1, 512, 8).astype(np.int32),
                    max_new_tokens=3, priority=pr)
            for i, pr in enumerate([0, 2, 1])]
    for r in reqs:
        eng.submit(r)
    order = []
    n = 0
    while eng.step(now=1e9) and n < 80:
        n += 1
        for s in range(eng.B):
            if eng.slots[s] is not None \
                    and (not order or order[-1] != eng.slots[s].rid):
                order.append(eng.slots[s].rid)
    assert order == [1, 2, 0]
    # same-priority contention: pool pressure must NOT preempt
    eng2 = _mk_engine(max_batch=4, n_pages=9, priorities=True)
    same = [Request(rid=i, prompt=rng.randint(1, 512, 30).astype(np.int32),
                    max_new_tokens=8, priority=1,
                    arrival=0.001 * i) for i in range(3)]
    out = eng2.run(same)
    assert out["preemptions"] == 0


# -- constrained decoding ---------------------------------------------------


def test_constrained_emits_only_schema_legal_tokens():
    """Greedy and sampled constrained requests walk the DFA: a complete
    enum value then pad-token fill; every emitted token was legal at its
    state (advance() raises otherwise, so completing the run proves
    it)."""
    rng = np.random.RandomState(4)
    dfa = json_schema_dfa({"enum": ["cat", "car", "dog"]}, VOCAB,
                          pad_token=0)
    eng = _mk_engine(constrained=True)
    eng.register_schema("animal", dfa.fresh)
    reqs = [Request(rid=0, prompt=rng.randint(1, 512, 20).astype(np.int32),
                    max_new_tokens=6, schema_id="animal"),
            Request(rid=1, prompt=rng.randint(1, 512, 20).astype(np.int32),
                    max_new_tokens=6, schema_id="animal",
                    temperature=1.0, top_p=0.9, seed=11),
            Request(rid=2, prompt=rng.randint(1, 512, 20).astype(np.int32),
                    max_new_tokens=6)]
    eng.run(reqs)
    for r in reqs[:2]:
        s = "".join(VOCAB[t] for t in r.out_tokens)
        assert s[:3] in ("cat", "car", "dog"), (r.rid, r.out_tokens, s)
        assert all(t == 0 for t in r.out_tokens[3:]), r.out_tokens
    _assert_accounting(eng)
    # the unconstrained rider is bit-identical to the flag-off engine
    eng2 = _mk_engine()
    base = Request(rid=9, prompt=reqs[2].prompt.copy(), max_new_tokens=6)
    eng2.run([base])
    assert base.out_tokens == reqs[2].out_tokens


def test_constrained_validation_and_spec_conflict():
    eng = _mk_engine()
    with pytest.raises(ValueError, match="serving_constrained is off"):
        eng.submit(Request(rid=0, prompt=np.ones(4, np.int32),
                           max_new_tokens=2, schema_id="s"))
    with pytest.raises(ValueError, match="serving_lora is off"):
        eng.submit(Request(rid=0, prompt=np.ones(4, np.int32),
                           max_new_tokens=2, adapter_id="a"))
    engc = _mk_engine(constrained=True)
    with pytest.raises(ValueError, match="unknown schema"):
        engc.submit(Request(rid=0, prompt=np.ones(4, np.int32),
                            max_new_tokens=2, schema_id="nope"))
    with pytest.raises(ValueError, match="incompatible"):
        _mk_engine(constrained=True, speculative_k=2)
    # vocab-size mismatch is rejected at submit
    bad = TokenDfa(np.zeros((2, 7), np.int32))
    with pytest.raises(ValueError, match="vocab"):
        engc.submit(Request(rid=0, prompt=np.ones(4, np.int32),
                            max_new_tokens=2, constraint=bad.fresh()))


# -- ledger under randomized multi-tenant load ------------------------------


def test_seven_class_ledger_under_randomized_load():
    """All three axes on at once, randomized traffic (adapters,
    priorities, schemas, sampled rows, preemption pressure): the 7-class
    ledger closes after EVERY step and at drain."""
    rng = np.random.RandomState(5)
    eng = _mk_engine(max_batch=4, n_pages=13, lora=True, lora_rank=8,
                     lora_slots=2, priorities=True, constrained=True)
    eng.register_adapter("a0", make_lora(CFG, 8, seed=1))
    eng.register_adapter("a1", make_lora(CFG, 8, seed=2))
    dfa = json_schema_dfa({"enum": ["cat", "car", "dog"]}, VOCAB,
                          pad_token=0)
    eng.register_schema("s0", dfa.fresh)
    reqs = []
    for i in range(12):
        kw = {}
        if rng.rand() < 0.5:
            kw["adapter_id"] = "a%d" % rng.randint(2)
        if rng.rand() < 0.3:
            kw["schema_id"] = "s0"
        if rng.rand() < 0.3:
            kw.update(temperature=0.9, top_p=0.8, seed=int(rng.randint(99)))
        reqs.append(Request(
            rid=i, prompt=rng.randint(1, 512, rng.randint(5, 40)).astype(
                np.int32),
            max_new_tokens=int(rng.randint(3, 8)),
            priority=int(rng.randint(3)), arrival=0.0, **kw))
    for r in reqs:
        eng.submit(r)
    n = 0
    while eng.step(now=1e9) and n < 400:
        n += 1
        _assert_accounting(eng)
    assert n < 400, "engine did not drain"
    _assert_accounting(eng)
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    for r in reqs:
        if r.schema_id is not None:
            s = "".join(VOCAB[t] for t in r.out_tokens[:3])
            assert s in ("cat", "car", "dog"), (r.rid, s)


# -- default-off bit-identity + workload pins -------------------------------


def test_flags_default_off_and_streams_bit_identical():
    """The three flags default False; an engine built with all three ON
    but serving plain requests streams bit-identically to the flag-off
    engine (identity adapter slot, all-True masks, priorities all 0)."""
    for f in ("serving_lora", "serving_priorities",
              "serving_constrained"):
        assert GLOBAL_FLAGS.get(f) is False
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 512, rng.randint(8, 40)).astype(np.int32)
               for _ in range(4)]

    def run(**kw):
        eng = _mk_engine(**kw)
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=5,
                        **(dict(temperature=0.9, top_p=0.8, seed=3)
                           if i == 1 else {}))
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return [r.out_tokens for r in reqs]

    base = run()
    assert run(lora=True, lora_rank=8, lora_slots=2) == base
    assert run(priorities=True) == base
    assert run(constrained=True) == base
    assert run(lora=True, priorities=True, constrained=True) == base


def test_workload_fields_seeded_and_legacy_byte_identical():
    """Multi-tenant knobs draw from a separate stream: knobs-off output
    is byte-identical to the legacy synthesize, and knobs-on changes
    ONLY the new fields (prompts/arrivals/sampling untouched)."""
    from paddle_tpu.inference.loadgen import WorkloadSpec, synthesize

    base_kw = dict(n_requests=16, seed=9, vocab_size=512, prefix_len=16,
                   n_prefixes=2, sampled_frac=0.5, max_seq=96,
                   tail_max=64, new_min=4, new_max=8)
    a = synthesize(WorkloadSpec(**base_kw))
    b = synthesize(WorkloadSpec(**base_kw))
    mt = synthesize(WorkloadSpec(**base_kw, n_tenants=3, n_adapters=2,
                                 priority_levels=3, constrained_frac=0.4,
                                 n_schemas=2))
    for ra, rb, rm in zip(a, b, mt):
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.arrival, ra.max_new_tokens, ra.temperature, ra.top_p,
                ra.seed) == (rb.arrival, rb.max_new_tokens,
                             rb.temperature, rb.top_p, rb.seed)
        # legacy fields survive the multi-tenant decoration untouched
        assert np.array_equal(ra.prompt, rm.prompt)
        assert (ra.arrival, ra.max_new_tokens, ra.temperature, ra.top_p,
                ra.seed) == (rm.arrival, rm.max_new_tokens,
                             rm.temperature, rm.top_p, rm.seed)
        assert (ra.tenant, ra.priority, ra.adapter_id, ra.schema_id) \
            == (0, 0, None, None)
    assert {r.tenant for r in mt} == {0, 1, 2}
    assert any(r.adapter_id is not None for r in mt)
    assert any(r.schema_id is not None for r in mt)
    assert len({r.priority for r in mt}) > 1
    # decoration is deterministic under the seed
    mt2 = synthesize(WorkloadSpec(**base_kw, n_tenants=3, n_adapters=2,
                                  priority_levels=3, constrained_frac=0.4,
                                  n_schemas=2))
    assert [(r.tenant, r.priority, r.adapter_id, r.schema_id)
            for r in mt] \
        == [(r.tenant, r.priority, r.adapter_id, r.schema_id)
            for r in mt2]


def test_constrain_dfa_compiler():
    """json_schema_dfa subset: enum walk, boolean, bounded integer, and
    illegal-advance detection."""
    dfa = json_schema_dfa({"enum": ["cat", "car", "dog"]}, VOCAB,
                          pad_token=0)
    st = dfa.fresh()
    for ch in "car":
        tok = VOCAB.index(ch)
        assert st.legal(tok)
        st.advance(tok)
    assert st.mask().sum() == 1 and st.legal(0)    # pad only
    st.advance(0)
    st.advance(0)                                   # pad self-loop
    with pytest.raises(ValueError):
        st.advance(VOCAB.index("x"))
    bdfa = json_schema_dfa({"type": "boolean"}, VOCAB, pad_token=0)
    s = bdfa.fresh()
    legal0 = {VOCAB[t] for t in np.nonzero(s.mask())[0]}
    assert legal0 == {"t", "f"}
    idfa = json_schema_dfa({"type": "integer", "minimum": 10,
                            "maximum": 12}, VOCAB, pad_token=0)
    s = idfa.fresh()
    assert {VOCAB[t] for t in np.nonzero(s.mask())[0]} == {"1"}
    with pytest.raises(ValueError):
        json_schema_dfa({"type": "integer", "minimum": 0,
                         "maximum": 99999}, VOCAB)
    with pytest.raises(ValueError):
        json_schema_dfa({"type": "object"}, VOCAB)
