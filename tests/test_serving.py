"""Continuous-batching serving engine: correctness under admission,
completion, and page reuse.

The critical property (VERDICT r3 item 3): admission/eviction must never
corrupt cross-request attention — a request decoded while slots fill,
drain, and pages are recycled must produce EXACTLY the tokens it produces
alone (greedy, fp32). Reference role: analysis_predictor.cc serving path
+ block_multi_head_attention's per-sequence block tables.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.serving import Request, ServingEngine

CFG = LlamaConfig(vocab_size=512, hidden=128, n_layers=2, n_heads=8,
                  n_kv_heads=4, ffn_hidden=256, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)


def _isolated_reference(engine, prompts, max_new):
    """Greedy generations one-at-a-time through the contiguous-cache
    engine (independently implemented path)."""
    m = LlamaForCausalLM(CFG, params=engine.params, max_batch=1,
                         max_seq_len=256)
    outs = []
    for p in prompts:
        toks = m.generate(np.asarray(p)[None], max_new_tokens=max_new)
        outs.append(list(np.asarray(toks)[0]))
    return outs


def test_serving_matches_isolated_generation():
    rng = np.random.RandomState(0)
    # 2 slots, 5 requests, staggered arrivals -> queueing + slot reuse +
    # page recycling while other requests are mid-decode
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                           prefill_budget=64, prefix_cache=False)
    prompts = [rng.randint(1, 512, size=n).astype(np.int32)
               for n in (9, 16, 23, 31, 12)]
    max_new = 6
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new, arrival=0.0)
            for i, p in enumerate(prompts)]
    stats = engine.run(reqs)

    assert stats["n_requests"] == 5
    assert stats["total_new_tokens"] == 5 * max_new
    want = _isolated_reference(engine, prompts, max_new)
    for r, w in zip(reqs, want):
        assert r.out_tokens == w, (r.rid, r.out_tokens, w)
    # every page returned to the pool
    assert len(engine.pool.free) == engine.n_pages - 1
    assert all(s is None for s in engine.slots)


def test_serving_admission_respects_memory():
    engine = ServingEngine(CFG, max_batch=4, page_size=16, max_seq=256,
                           n_pages=1 + 6,  # room for 2 requests
                           prefill_budget=64, prefix_cache=False)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 512, size=20).astype(np.int32)
               for _ in range(3)]
    # each request needs ceil((20 + 13) / 16) = 3 pages
    reqs = [Request(rid=i, prompt=p, max_new_tokens=13, arrival=0.0)
            for i, p in enumerate(prompts)]
    stats = engine.run(reqs)
    # all complete despite the pool forcing serialized admission
    assert all(r.t_done is not None for r in reqs)
    assert len(engine.pool.free) == 6


def test_serving_pipelined_page_recycling_exact():
    """Round-5 pipelined scheduler hazards, pinned by exact-token
    equality: a finish is discovered one quantum late (junk ticks must
    not leak), freed pages sit in _deferred_free for one harvest (a page
    must never reach a new request while an in-flight program can still
    write it), and admissions join mid-flight via the patched token
    vector. Small quantum + tight pool + staggered arrivals force all
    three paths many times over."""
    rng = np.random.RandomState(7)
    engine = ServingEngine(CFG, max_batch=3, page_size=16, max_seq=128,
                           n_pages=1 + 10,          # ~2.5 requests' worth
                           prefill_budget=32, prefix_cache=False)
    prompts = [rng.randint(1, 512, size=n).astype(np.int32)
               for n in (9, 16, 23, 31, 12, 20, 7, 28)]
    max_new = 11                  # not a multiple of the quantum
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new,
                    arrival=0.03 * i)
            for i, p in enumerate(prompts)]
    stats = engine.run(reqs)

    assert stats["total_new_tokens"] == len(prompts) * max_new
    want = _isolated_reference(engine, prompts, max_new)
    for r, w in zip(reqs, want):
        assert r.out_tokens == w, (r.rid, r.out_tokens, w)
    assert len(engine.pool.free) == 10       # deferred frees all drained
    assert engine._deferred_free == []
    assert engine._inflight is None


def test_serving_sampling_contract():
    """Per-request sampling (reference fused top_p_sampling role):
    mixed greedy/sampled batches share one program; a sampled request's
    stream is (seed, position)-keyed — reproducible across runs;
    top_p -> 0 keeps only the max token (== greedy); a
    greedy request's tokens are unaffected by sampled neighbours."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 512, size=n).astype(np.int32)
               for n in (9, 16, 23)]
    max_new = 9

    def run(specs):
        engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                               prefill_budget=64)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new,
                        arrival=0.0, **spec)
                for i, (p, spec) in enumerate(zip(prompts, specs))]
        engine.run(reqs)
        return [r.out_tokens for r in reqs], engine

    greedy_specs = [{}, {}, {}]
    base, engine = run(greedy_specs)
    want = _isolated_reference(engine, prompts, max_new)
    assert base == [list(map(int, w)) for w in want]

    mixed = [{"temperature": 0.9, "top_p": 0.8, "seed": 11}, {}, {}]
    out1, _ = run(mixed)
    out2, _ = run(mixed)
    assert out1[0] == out2[0], "a sampled stream must be reproducible"
    assert out1[1] == base[1] and out1[2] == base[2], \
        "greedy neighbours must be unaffected by a sampled request"
    assert out1[0] != base[0], "hot sampling should diverge from greedy"

    top1 = [{"temperature": 0.9, "top_p": 1e-6, "seed": 11}, {}, {}]
    out3, _ = run(top1)
    assert out3[0] == base[0], "top_p -> 0 must reduce to greedy"


def test_serving_weight_only_int8_matches_isolated_int8():
    """Weight-only int8 serving (the reference weight_only_linear
    serving config): the engine quantizes once at init and the compiled
    prefill/decode paths run on (int8, scale) weights; exact-token
    equality against the isolated int8 generation path on the SAME
    quantized params."""
    rng = np.random.RandomState(5)
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                           prefill_budget=64,
                           weight_only_int8=True)
    assert isinstance(engine.params["blocks"]["wq"], tuple)
    prompts = [rng.randint(1, 512, size=n).astype(np.int32)
               for n in (9, 23, 14)]
    max_new = 6
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new, arrival=0.0)
            for i, p in enumerate(prompts)]
    engine.run(reqs)

    want = _isolated_reference(engine, prompts, max_new)
    for r, w in zip(reqs, want):
        assert r.out_tokens == [int(t) for t in w], (r.rid,)


def test_serving_rejects_oversized():
    engine = ServingEngine(CFG, max_batch=1, page_size=16, max_seq=64,
                           prefill_budget=64)
    with pytest.raises(ValueError):
        engine.submit(Request(rid=0, prompt=np.zeros(60, np.int32),
                              max_new_tokens=10))
