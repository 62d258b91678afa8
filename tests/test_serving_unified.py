"""Unified ragged-paged-attention engine step (PR 7): bit-identity
across packing regimes, one compiled program per step, and speculative
multi-token decode.

The unified step's contract: decode tokens and prefill chunks share one
``[n_rows, qb]`` program per step, so a request's token stream must be
bit-identical whatever the grid geometry (qb, budget), whatever other
traffic shares its dispatches, whether its prefix came warm from the
cache, and whether speculative verification is on (greedy-accept + keyed
sampling make acceptance invisible to the stream)."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.serving import Request, ServingEngine

CFG = LlamaConfig(vocab_size=512, hidden=128, n_layers=2, n_heads=8,
                  n_kv_heads=4, ffn_hidden=256, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)


def _isolated(engine, prompt, max_new):
    m = LlamaForCausalLM(CFG, params=engine.params, max_batch=1,
                         max_seq_len=256)
    toks = m.generate(np.asarray(prompt)[None], max_new_tokens=max_new)
    return [int(t) for t in np.asarray(toks)[0]]


def _assert_accounting(engine):
    acc = engine.page_accounting()
    assert acc["total"] == engine.n_pages - 1, acc
    owned = [p for lst in engine._slot_owned for p in lst]
    shared = {p for lst in engine._slot_shared for p in lst}
    idle = {p for p, r in engine.pool.ref.items() if r == 0}
    groups = [set(engine.pool.free), set(owned), shared, idle,
              set(engine._deferred_free)]
    assert len(owned) == len(set(owned))
    for i in range(len(groups)):
        for j in range(i + 1, len(groups)):
            assert not (groups[i] & groups[j]), (i, j, groups)


def _mk_reqs(rng, n=4, sampled=False):
    reqs = []
    for i in range(n):
        prompt = rng.randint(1, 512, size=rng.randint(5, 40)).astype(
            np.int32)
        kw = {}
        if sampled and i % 2:
            kw = dict(temperature=0.9, top_p=0.85, seed=10 + i)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.randint(4, 10)),
                            arrival=0.0, **kw))
    return reqs


def _run(qb=None, speculative_k=None, seed=11, sampled=True, warm=None,
         **kw):
    rng = np.random.RandomState(seed)
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                           prefill_budget=kw.pop("prefill_budget", 64),
                           qb=qb, speculative_k=speculative_k, **kw)
    if warm is not None:
        engine.run([Request(rid=99, prompt=warm.copy(),
                            max_new_tokens=4, arrival=0.0)])
    reqs = _mk_reqs(rng, sampled=sampled)
    stats = engine.run(reqs)
    assert engine._inflight is None and engine._deferred_free == []
    assert len(engine.pool.free) + sum(
        engine.pool.ref[p] == 0 for p in engine.pool.ref) \
        == engine.n_pages - 1
    return [r.out_tokens for r in reqs], stats, engine


def test_streams_invariant_to_grid_geometry():
    """Same mixed greedy/sampled workload under four grid geometries —
    the pre-PR chunk/quantum boundary is gone, so qb and budget choices
    must be stream-invisible (keyed sampling + one-token-per-row
    decode)."""
    base, _, engine = _run(qb=16, prefill_budget=64)
    for r, toks in zip(_mk_reqs(np.random.RandomState(11), sampled=True),
                       base):
        if r.temperature == 0.0:
            assert toks == _isolated(engine, r.prompt,
                                     r.max_new_tokens), r.rid
    narrow, _, _ = _run(qb=4, prefill_budget=64)
    tiny, _, _ = _run(qb=1, prefill_budget=8)     # 1-token chunks
    wide, _, _ = _run(qb=32, prefill_budget=32)
    assert base == narrow == tiny == wide


def test_streams_invariant_warm_vs_cold_cache():
    rng = np.random.RandomState(11)
    warm_prompt = _mk_reqs(rng, sampled=True)[0].prompt
    cold, _, _ = _run(qb=16)
    warm, _, eng = _run(qb=16, warm=warm_prompt)
    assert cold == warm
    assert eng.pool.hits > 0


def test_speculative_stream_bit_identical_and_reported():
    """serving_speculative_k > 0 must not change a single token (greedy
    OR sampled rows): drafts are greedy-verified at the same keyed
    positions the non-speculative path uses. Accept-rate counters must
    be reported; a repetitive prompt guarantees proposals fire."""
    rng = np.random.RandomState(13)
    pat = rng.randint(1, 512, size=6).astype(np.int32)
    prompts = [np.tile(pat, 5), rng.randint(1, 512, size=17).astype(
        np.int32)]

    def go(k):
        engine = ServingEngine(CFG, max_batch=2, page_size=16,
                               max_seq=256, prefill_budget=64, qb=16,
                               speculative_k=k)
        reqs = [Request(rid=0, prompt=prompts[0].copy(),
                        max_new_tokens=12),
                Request(rid=1, prompt=prompts[1].copy(),
                        max_new_tokens=8, temperature=0.9, top_p=0.8,
                        seed=3)]
        stats = engine.run(reqs)
        _assert_accounting(engine)
        return [r.out_tokens for r in reqs], stats

    off, soff = go(0)
    on, son = go(3)
    assert off == on, (off, on)
    assert soff["spec_proposed_tokens"] == 0
    assert soff["spec_accept_rate"] == 0.0
    assert son["spec_proposed_tokens"] > 0
    assert 0.0 <= son["spec_accept_rate"] <= 1.0
    assert son["spec_accepted_tokens"] + son[
        "waste_spec_rejected_slot_tokens"] >= son["spec_proposed_tokens"]
    # the repetitive request should actually accept some drafts
    assert son["spec_accepted_tokens"] > 0


def test_one_compiled_program_per_step():
    """A mixed prefill/decode batch must cost exactly ONE unified
    dispatch per engine step — no separate prefill program, no decode
    quantum."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                           prefill_budget=32, qb=16)
    calls = {"n": 0}
    inner = engine._unified

    def counting(*a, **k):
        calls["n"] += 1
        return inner(*a, **k)

    engine._unified = counting
    rng = np.random.RandomState(17)
    reqs = [Request(rid=i,
                    prompt=rng.randint(1, 512, size=n).astype(np.int32),
                    max_new_tokens=5, arrival=0.0)
            for i, n in enumerate((40, 9, 25))]
    for r in reqs:
        engine.submit(r)
    # the first dispatch also runs each other step size once, idle, to
    # compile it (the engine's ``rungs``)
    steps, warm = 0, len(engine.rungs) - 1
    while engine.step(now=1e9):
        steps += 1
        assert calls["n"] - warm <= steps   # at most one dispatch per step
        assert steps < 200
    assert calls["n"] - warm == engine.stats["unified_steps"]
    assert all(len(r.out_tokens) == 5 for r in reqs)


def test_page_accounting_under_speculative_load_with_aborts():
    """Satellite 3: randomized open-loop-ish load with speculation ON
    (rollbacks every rejected draft) plus mid-run aborts; the page
    census must balance after EVERY step and the occupancy ledger must
    close over the spec bucket."""
    engine = ServingEngine(CFG, max_batch=3, page_size=16, max_seq=128,
                           n_pages=1 + 14, prefill_budget=32, qb=8,
                           speculative_k=3)
    rng = np.random.RandomState(23)
    pat = rng.randint(1, 512, size=5).astype(np.int32)
    for i in range(9):
        if rng.rand() < 0.5:
            prompt = np.tile(pat, rng.randint(2, 6))   # spec-friendly
        else:
            prompt = rng.randint(1, 512,
                                 size=rng.randint(4, 40)).astype(np.int32)
        engine.submit(Request(rid=i, prompt=prompt,
                              max_new_tokens=int(rng.randint(3, 12)),
                              temperature=float(rng.rand() < 0.3) * 0.8,
                              seed=i))
    aborts = {3: 2, 8: 5}
    steps = 0
    while engine.step(now=1e9):
        steps += 1
        if steps in aborts:
            engine.abort(aborts[steps])
        _assert_accounting(engine)
        assert steps < 500
    _assert_accounting(engine)
    st = engine.stats
    assert st["decode_slot_tokens"] == (
        st["decode_active_tokens"] + st["waste_prefill_slot_tokens"]
        + st["waste_queue_empty_slot_tokens"]
        + st["waste_admission_blocked_slot_tokens"]
        + st["waste_overrun_slot_tokens"]
        + st["waste_spec_rejected_slot_tokens"]), st
    assert not engine.queue
    assert all(s is None for s in engine.slots)


# ---------------------------------------------------------------------------
# int8 KV plane (serving_kv_quant)


def test_kv_quant_default_off_is_structurally_identical():
    """With the flag off (the default) the engine must build the exact
    pre-quant structures: fp pages, no scale planes, and the original
    (non-quant) jitted step — bit-identity for free, pinned here."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128)
    assert engine._kv_quant is False
    assert engine.k_pages.dtype == CFG.dtype
    assert engine.k_scales is None and engine.v_scales is None


def test_kv_quant_streams_and_ledger_close():
    """kv_quant=True end-to-end: int8 pages + scale planes, greedy
    streams still track the isolated model closely, ledger closes."""
    base, _, _ = _run(qb=16, sampled=True)
    quant, _, engine = _run(qb=16, sampled=True, kv_quant=True)
    assert engine._kv_quant and engine.k_pages.dtype == jnp.int8
    assert engine.k_scales.shape == (CFG.n_layers, engine.n_pages,
                                     CFG.n_kv_heads)
    # quantified quality delta, fixed seed (PERF.md round 8): greedy
    # token agreement between the int8 and fp engines
    pairs = [(b, q) for b, q in zip(base, quant)]
    agree = [sum(x == y for x, y in zip(b, q)) / max(len(b), 1)
             for b, q in pairs]
    assert all(len(b) == len(q) for b, q in pairs)
    assert min(agree) >= 0.75, agree
    assert sum(agree) / len(agree) >= 0.9, agree


def test_kv_quant_geometry_invariance():
    """The quantized plane must keep the unified step's core contract:
    the stream cannot depend on grid geometry (qb/budget), even though
    page-scale *history* differs across chunkings — rescale keeps every
    geometry reading the same running-absmax encoding."""
    a, _, _ = _run(qb=16, prefill_budget=64, kv_quant=True)
    b, _, _ = _run(qb=4, prefill_budget=32, kv_quant=True)
    assert a == b


def test_kv_quant_page_accounting_under_speculative_load_with_aborts():
    """Satellite 3: the randomized spec+abort load, on the int8 plane.
    Every step must keep the census balanced; abort/rollback paths run
    through the quantized scatter and allocation-time scale reset."""
    engine = ServingEngine(CFG, max_batch=3, page_size=16, max_seq=128,
                           n_pages=1 + 14, prefill_budget=32, qb=8,
                           speculative_k=3, kv_quant=True)
    rng = np.random.RandomState(23)
    pat = rng.randint(1, 512, size=5).astype(np.int32)
    for i in range(9):
        if rng.rand() < 0.5:
            prompt = np.tile(pat, rng.randint(2, 6))
        else:
            prompt = rng.randint(1, 512,
                                 size=rng.randint(4, 40)).astype(np.int32)
        engine.submit(Request(rid=i, prompt=prompt,
                              max_new_tokens=int(rng.randint(3, 12)),
                              temperature=float(rng.rand() < 0.3) * 0.8,
                              seed=i))
    aborts = {3: 2, 8: 5}
    steps = 0
    while engine.step(now=1e9):
        steps += 1
        if steps in aborts:
            engine.abort(aborts[steps])
        _assert_accounting(engine)
        assert steps < 500
    _assert_accounting(engine)
    st = engine.stats
    assert st["decode_slot_tokens"] == (
        st["decode_active_tokens"] + st["waste_prefill_slot_tokens"]
        + st["waste_queue_empty_slot_tokens"]
        + st["waste_admission_blocked_slot_tokens"]
        + st["waste_overrun_slot_tokens"]
        + st["waste_spec_rejected_slot_tokens"]), st
    assert not engine.queue and all(s is None for s in engine.slots)


def test_kv_quant_prefix_cache_isolated_from_fp_pages():
    """Quantized and fp page hashes must never alias (the ':kvq8' seed
    tag): a warm int8 engine hits its own cache, and the off-path hash
    preimage is unchanged."""
    rng = np.random.RandomState(11)
    warm_prompt = _mk_reqs(rng, sampled=True)[0].prompt
    cold, _, _ = _run(qb=16, kv_quant=True)
    warm, _, eng = _run(qb=16, kv_quant=True, warm=warm_prompt)
    assert cold == warm
    assert eng.pool.hits > 0
    off = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256)
    on = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                       kv_quant=True)
    toks = np.arange(2 * off.bs, dtype=np.int32)
    ha, hb = off._page_hashes(toks), on._page_hashes(toks)
    assert len(ha) == len(hb) == 2
    assert not set(ha) & set(hb)


def test_kv_quant_capacity_doubles_at_fixed_bytes():
    """The point of the plane: at a fixed HBM byte budget the int8 pool
    holds >= 2x the pages (scales included in the int8 ledger)."""
    off = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128)
    on = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                       kv_quant=True)
    assert on.kv_bytes_per_page() * 2 <= off.kv_bytes_per_page()
    budget = 64 * off.kv_bytes_per_page()
    assert budget // on.kv_bytes_per_page() >= 2 * (
        budget // off.kv_bytes_per_page())
    assert on.kv_bytes_per_token() * 2 <= off.kv_bytes_per_token()


# -- one step; what a page holds is the model's to say ----------------------

def _engine_of(kind):
    """The three serving models at toy size: LLaMA with fp pages, LLaMA
    with int8 pages, and the latent (MLA) model with routed experts."""
    if kind != "mla":
        return ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                             kv_quant=kind == "int8")
    from paddle_tpu.models.mla_moe import MlaMoeConfig, init_mla_moe_params

    cfg = MlaMoeConfig(
        vocab_size=128, hidden=32, n_layers=3, n_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        ffn_hidden=64, moe_hidden=16, n_routed_experts=8,
        experts_per_token=2, n_mtp=0, max_seq_len=64, held=(2, 4))
    params = jax.eval_shape(lambda k: init_mla_moe_params(cfg, k),
                            jax.random.PRNGKey(0))    # shapes only
    return ServingEngine(cfg, params=params, max_batch=2, page_size=8,
                         max_seq=64, n_pages=1 + 8)


MODELS = ["fp", "int8", "mla"]


@pytest.mark.parametrize("kind", MODELS)
def test_engine_allocates_what_the_cache_spec_declares(kind):
    """The pool and the side planes have exactly the shapes and dtypes
    ``model.cache_spec(page_size)`` declares, ``[L, P, *page_shape]``
    each, and a page's bytes are the spec's sum: the engine knows no
    page format of its own."""
    engine = _engine_of(kind)
    spec = engine.model.cache_spec(engine.bs)
    assert spec == engine.cache_spec
    lead = (engine.model.n_layers, engine.n_pages)
    for pages, plane in zip((engine.k_pages, engine.v_pages), spec.planes):
        assert pages.shape == lead + plane.page_shape
        assert pages.dtype == spec.dtype
    assert list(engine.side_planes) == [p.name for p in spec.side]
    for plane in spec.side:
        got = engine.side_planes[plane.name]
        assert got.shape == lead + plane.page_shape
        assert got.dtype == plane.dtype
    per_page = sum(a.nbytes for a in (engine.k_pages, engine.v_pages,
                                      *engine.side_planes.values())
                   ) / engine.n_pages
    assert engine.kv_bytes_per_page() == per_page
    assert engine.kv_bytes_per_page() == spec.page_bytes(lead[0])
    assert bool(spec.side) == (kind == "int8")
    # the step's operands: the fixed fourteen, then the side planes,
    # then the packed axis, whose length is the step size
    args = engine.unified_arg_shapes()
    assert len(args) == 14 + len(spec.side) + 1
    assert [(a.shape, a.dtype) for a in args[14:-1]] == [
        (lead + p.page_shape, p.dtype) for p in spec.side]
    assert args[-1].shape == (engine.rungs[-1],) == (
        engine.n_rows * engine.qb,)


@pytest.mark.parametrize("kind", MODELS)
def test_every_engine_jits_the_one_step(kind):
    """There is one step function: every engine's ``_unified`` is a jit
    of ``ServingEngine._unified_step_impl``, whatever model it serves
    and whatever its pages hold."""
    engine = _engine_of(kind)
    assert (engine._unified.__wrapped__.__func__
            is ServingEngine._unified_step_impl)
    closed = engine.trace_unified()
    n_out = len(jax.tree.leaves(closed.out_avals))
    n_ys = len(jax.tree.leaves(jax.eval_shape(
        engine._unified_step_impl, *engine.unified_arg_shapes())[3]))
    assert n_out == 3 + n_ys + len(engine.cache_spec.side)


# -- the pool is a loop carry addressed by (layer, page) -------------------

def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
def test_pool_is_a_scan_carry_not_a_scanned_operand(kv_quant):
    """A scan reads its ``xs`` and stacks fresh ``ys``, so a pool that
    travels that way is copied every step. The step carries the pool,
    flattened to ``[L*P, ...]``, whatever its pages hold, and scans over
    nothing pool-shaped."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=128,
                           kv_quant=kv_quant)
    closed = engine.trace_unified()
    (scan,) = list(_scans(closed.jaxpr))
    nc, nk = scan.params["num_consts"], scan.params["num_carry"]
    carry = [v.aval.shape for v in scan.invars[nc:nc + nk]]
    xs = [v.aval.shape for v in scan.invars[nc + nk:]]
    ys = [v.aval.shape for v in scan.outvars[nk:]]
    L, P = engine.k_pages.shape[:2]
    k_pool = (L * P,) + engine.k_pages.shape[2:]
    v_pool = (L * P,) + engine.v_pages.shape[2:]
    assert k_pool in carry and v_pool in carry, carry
    assert [v.aval.shape for v in scan.outvars[:nk]] == carry
    tails = {engine.k_pages.shape[2:], engine.v_pages.shape[2:]}
    for shape in xs + ys:
        assert shape[-3:] not in tails, (shape, xs, ys)
    assert (L,) in xs                          # the layer index travels as xs
    # the shape at the jit boundary did not change
    assert [v.aval.shape for v in closed.jaxpr.outvars[1:3]] == [
        engine.k_pages.shape, engine.v_pages.shape]


def _reference_step(engine, args):
    """The unified step as a plain loop over the layers in Python: layer
    ``l`` scatters into ``k_pages[l]`` under the layer's own page ids and
    attends over that one layer's pages — the addressing the engine had
    before the pool became a carry. Returns the pools (and scale planes)
    the step leaves behind."""
    from paddle_tpu.models.llama import (_mm, apply_rope, rms_norm,
                                         rope_angles)
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        _ragged_paged_xla
    from paddle_tpu.ops.quant import (kv_scale_update, quantize_to_scale,
                                      rescale_int8)

    cfg, bs, quant = engine.cfg, engine.bs, engine._kv_quant
    (params, kp, vp, tokens, prev_out, cmask, crow, ptable, row_slot,
     pos0, n_valid) = args[:11]
    if quant:
        ksc, vsc = args[14:16]       # side planes ride behind the seeds
    C, qb = tokens.shape
    nH, nKV, dH = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def layer(x, bp, kl, vl, kscl, vscl):
        h = rms_norm(x, bp["attn_norm"], cfg.rms_eps)
        q = apply_rope(_mm(h, bp["wq"], cfg).reshape(C, qb, nH, dH),
                       cos, sin)
        k = apply_rope(_mm(h, bp["wk"], cfg).reshape(C, qb, nKV, dH),
                       cos, sin)
        v = _mm(h, bp["wv"], cfg).reshape(C, qb, nKV, dH)
        sc = {}
        if quant:
            kf = k.reshape(C * qb, nKV, dH).astype(jnp.float32)
            vf = v.reshape(C * qb, nKV, dH).astype(jnp.float32)
            new = [kv_scale_update(s, pages,
                                   jnp.max(jnp.abs(f), axis=-1) / 127.0)
                   for s, f in ((kscl, kf), (vscl, vf))]
            kl, vl = [p.at[pages_rw].set(rescale_int8(
                p[pages_rw], jnp.take(s, pages_rw, axis=0)[:, :, None, None],
                jnp.take(n, pages_rw, axis=0)[:, :, None, None]))
                for p, s, n in ((kl, kscl, new[0]), (vl, vscl, new[1]))]
            kw, vw = [quantize_to_scale(
                f, jnp.take(n, pages, axis=0)[:, :, None])
                for f, n in ((kf, new[0]), (vf, new[1]))]
            kscl, vscl = new
            sc = dict(k_scales=kscl, v_scales=vscl)
        else:
            kw = k.reshape(C * qb, nKV, dH).astype(kl.dtype)
            vw = v.reshape(C * qb, nKV, dH).astype(vl.dtype)
        kl = kl.at[pages, :, :, offs].set(kw)
        vl = vl.at[pages, :, offs].set(vw)
        o = _ragged_paged_xla(q, kl, vl, rows, pos0, n_valid,
                              1.0 / np.sqrt(dH), "d_major", **sc)
        x = x + _mm(o.reshape(C, qb, nH * dH), bp["wo"], cfg)
        h = rms_norm(x, bp["ffn_norm"], cfg.rms_eps)
        x = x + _mm(jax.nn.silu(
            _mm(h, bp["w_gate"], cfg).astype(jnp.float32)).astype(
                cfg.dtype) * _mm(h, bp["w_up"], cfg), bp["w_down"], cfg)
        return x, kl, vl, kscl, vscl

    tok0 = jnp.where(cmask, prev_out[crow, 0], tokens[:, 0])
    tokens = jnp.concatenate([tok0[:, None], tokens[:, 1:]], axis=1)
    rows = ptable[row_slot]
    positions = pos0[:, None] + jnp.arange(qb, dtype=jnp.int32)
    valid = jnp.arange(qb, dtype=jnp.int32)[None, :] < n_valid[:, None]
    pages = jnp.where(valid, jnp.take_along_axis(rows, positions // bs,
                                                 axis=1), 0).reshape(-1)
    offs = (positions % bs).reshape(-1)
    blk_rw = jnp.clip(pos0[:, None] // bs + jnp.arange(
        (qb - 1) // bs + 2, dtype=jnp.int32)[None, :],
        0, engine.max_blocks - 1)
    pages_rw = jnp.take_along_axis(rows, blk_rw, axis=1).reshape(-1)
    x = params["wte"][tokens].astype(cfg.dtype)
    cos, sin = rope_angles(cfg, positions)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    ks, vs, kss, vss = [], [], [], []
    for l in range(cfg.n_layers):
        bp = jax.tree.map(lambda a: a[l], params["blocks"])
        x, kl, vl, kscl, vscl = layer(
            x, bp, kp[l], vp[l], ksc[l] if quant else None,
            vsc[l] if quant else None)
        ks.append(kl), vs.append(vl), kss.append(kscl), vss.append(vscl)
    out = [jnp.stack(ks), jnp.stack(vs)]
    return out + ([jnp.stack(kss), jnp.stack(vss)] if quant else [])


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
def test_pool_bit_identical_to_per_layer_reference(kv_quant):
    """After every dispatch of a mixed prefill/decode run the pool —
    every page of every layer — holds exactly what a per-layer loop
    that scatters into ``k_pages[l]`` leaves there: (l*P + p) addressing
    of the carried pool puts the same values into the same pages. At
    the largest step size that includes each layer's sink; at a smaller
    one the places of the grid that hold no token write SOME token's
    k and v to the sink (models/seam.py: TokenLayout.to_grid) where the
    loop writes those of token id 0, so page 0 is left out there."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                           prefill_budget=32, qb=8, kv_quant=kv_quant)
    inner, seen, sizes = engine._unified, [], set()

    def recording(*args):
        host = jax.tree.map(np.asarray, args)      # before the donation
        out = inner(*args)
        seen.append((host, [np.asarray(o)
                            for o in (out[1], out[2], *out[4])]))
        return out

    engine._unified = recording
    reference = jax.jit(lambda *a: _reference_step(engine, a))
    engine.run(_mk_reqs(np.random.RandomState(5), sampled=True))
    assert len(seen) > 8
    mixed = 0
    for host, got in seen:
        n_valid, row_slot = host[10], host[8]
        live = n_valid[row_slot < engine.B]
        mixed += bool((live > 1).any() and (live == 1).any())
        want = reference(*jax.tree.map(jnp.asarray, host))
        first = int(len(host[-1]) < engine.rungs[-1])
        sizes.add(len(host[-1]))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g[:, first:],
                                          np.asarray(w)[:, first:])
    assert mixed, "no dispatch mixed prefill chunks with decode rows"
    assert sizes == set(engine.rungs)


def test_chunks_of_one_request_are_adjacent_rows():
    """The write kernel keeps a page in VMEM across ADJACENT rows that
    write it (ops/pallas/paged_kv_write.py), so the dispatcher's packing
    is part of its contract: the rows of one request are consecutive and
    in position order, and no other request writes its pages."""
    engine = ServingEngine(CFG, max_batch=2, page_size=16, max_seq=256,
                           prefill_budget=32, qb=8, speculative_k=2)
    inner, tables = engine._unified, []

    def recording(*args):
        tables.append([np.asarray(a) for a in args[7:11]])
        return inner(*args)

    engine._unified = recording
    engine.run(_mk_reqs(np.random.RandomState(7)))
    assert tables
    for ptable, row_slot, pos0, n_valid in tables:
        live = np.nonzero(row_slot < engine.B)[0]
        for s in set(row_slot[live].tolist()):
            idx = np.nonzero(row_slot == s)[0]
            assert (np.diff(idx) == 1).all(), row_slot
            assert (pos0[idx][1:] == (pos0[idx] + n_valid[idx])[:-1]).all()
        owned = [set(ptable[s][ptable[s] > 0].tolist())
                 for s in set(row_slot[live].tolist())]
        assert sum(map(len, owned)) == len(set().union(*owned)), ptable


def test_padding_rows_of_the_attention_never_reach_a_served_token(
        monkeypatch):
    """The attention's contract for padding rows changed (zeros from
    both arms, where both used to repeat the last valid row's mask):
    nothing served may depend on them.  A mixed batch with tail chunks
    (1 < n_valid < qb) beside decode rows serves the same tokens when
    the attention leaves 1e4 in every padding row instead (finite, as
    the old rows were: the last-valid pick is a one-hot product)."""
    import paddle_tpu.ops.pallas.ragged_paged_attention as rpa

    base, _, engine = _run(qb=16, prefill_budget=64)
    inner, tails = engine._unified, []

    def recording(*args):
        tails.append(np.asarray(args[10]))
        return inner(*args)

    engine._unified = recording
    engine.run(_mk_reqs(np.random.RandomState(11), sampled=True))
    n_valid = np.concatenate(tails)
    assert ((n_valid > 1) & (n_valid < 16)).any() and (n_valid == 1).any()

    real = rpa.ragged_paged_attention

    def poisoned(q, k_pages, v_pages, rows, pos0, n_valid, *a, **k):
        o = real(q, k_pages, v_pages, rows, pos0, n_valid, *a, **k)
        pad = jnp.arange(q.shape[1])[None, :] >= n_valid[:, None]
        return jnp.where(pad[:, :, None, None], 1e4, o)

    monkeypatch.setattr(rpa, "ragged_paged_attention", poisoned)
    got, _, _ = _run(qb=16, prefill_budget=64)
    assert got == base
