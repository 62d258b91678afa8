"""The step's dense layers run over the tick's tokens, packed, on a
ladder of step sizes (``ServingEngine.rungs``; models/seam.py: the
packed axis).  A step size changes what is multiplied, never what a
token's row reads: the picks, the pages and the layers' counters of
every size that holds a tick equal the largest's, which is the grid
itself; the dispatcher takes the smallest that holds the tick; and
every size is compiled before the first ``step()`` returns."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.multitenant import make_lora
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.seam import token_layout

CFG = LlamaConfig(vocab_size=512, hidden=128, n_layers=2, n_heads=8,
                  n_kv_heads=4, ffn_hidden=256, max_seq_len=256,
                  dtype=jnp.float32, param_dtype=jnp.float32)


def _mla_cfg():
    from paddle_tpu.models.mla_moe import MlaMoeConfig

    return MlaMoeConfig(
        vocab_size=128, hidden=32, n_layers=3, n_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        ffn_hidden=64, moe_hidden=16, n_routed_experts=8,
        experts_per_token=2, n_mtp=0, max_seq_len=128, held=(2, 4),
        dtype=jnp.float32, param_dtype=jnp.float32)


def _engine(kind):
    """Toy engines on a grid of 4 rows x 8 (step sizes 8 and 32) with
    two slots, so that every tick has idle rows or prefill chunks."""
    geo = dict(max_batch=2, page_size=16, max_seq=128, prefill_budget=32,
               qb=8)
    if kind == "mla":
        return ServingEngine(_mla_cfg(), seed=3, **geo)
    return ServingEngine(
        CFG, seed=0, kv_quant=kind == "int8", lora=kind == "lora",
        speculative_k=2 if kind == "spec" else None,
        **(dict(lora_rank=8, lora_slots=2) if kind == "lora" else {}),
        **geo)


def _requests(kind):
    rng = np.random.RandomState(7)
    vocab = 128 if kind == "mla" else 512
    # 64 tokens fill the grid once (4 rows x 8, twice); 5 and 19 leave
    # ragged chunks; the repetitive prompt makes the n-gram drafts land
    lens, reqs = (64, 5, 19, 33), []
    for i, n in enumerate(lens):
        prompt = rng.randint(1, vocab, size=n).astype(np.int32)
        if kind == "spec" and i == 3:
            prompt = np.tile(prompt[:4], 9)[:n]
        kw = {}
        if kind == "lora" and i % 2:
            kw["adapter_id"] = f"a{i % 4 // 2}"
        if kind not in ("spec", "mla") and i == 2:
            kw.update(temperature=0.9, top_p=0.85, seed=21)
        reqs.append(Request(rid=i, prompt=prompt, arrival=0.0,
                            max_new_tokens=int(rng.randint(5, 9)), **kw))
    return reqs


@functools.lru_cache(maxsize=None)
def _recorded(kind):
    """One scripted run of the engine of ``kind``: the engine and every
    dispatch's operands on the host (taken before the donation), warm-up
    dispatches apart."""
    engine = _engine(kind)
    if kind == "lora":
        engine.register_adapter("a0", make_lora(CFG, 8, seed=1, scale=0.3))
        engine.register_adapter("a1", make_lora(CFG, 8, seed=2, scale=0.3))
    inner, ticks = engine._unified, []

    def recording(*args):
        ticks.append(jax.tree.map(np.asarray, args))
        return inner(*args)

    engine._unified = recording
    reqs = _requests(kind)
    engine.run(reqs)
    engine._unified = inner
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    live = [t for t in ticks if (t[8] < engine.B).any()]
    assert len(ticks) - len(live) == len(engine.rungs) - 1    # the warm-up
    return engine, live


def _m(engine, tick):
    """Tokens each row of a recorded tick carries (an idle row none)."""
    return np.where(tick[8] < engine.B, tick[10], 0)


def _grid_kind(engine, tick):
    m = _m(engine, tick)
    if m.sum() == engine.n_rows * engine.qb:
        return "full"
    if (m[m > 0] == 1).all():
        return "decode_only"
    return "mixed"


def _replay(engine, tick, rung):
    """The recorded tick run at step size ``rung``: (picks, pools and
    side planes, counters), all on the host."""
    args = jax.tree.map(jnp.asarray, tick[:-1])
    out, k, v, ys, side, _further = engine._unified(
        *args, jnp.arange(rung, dtype=jnp.int32))
    return jax.tree.map(np.asarray, (out, (k, v, *side), ys))


LAST_BITS = {"mla": dict(rtol=1e-5, atol=1e-6)}
CASES = [("fp", "decode_only"), ("fp", "mixed"), ("fp", "full"),
         ("fp", "idle_rows"), ("spec", "mixed"), ("int8", "mixed"),
         ("int8", "decode_only"), ("lora", "mixed"), ("lora", "decode_only"),
         ("mla", "mixed"), ("mla", "decode_only"), ("mla", "full")]


@pytest.mark.parametrize("kind,grid", CASES,
                         ids=[f"{k}-{g}" for k, g in CASES])
def test_every_step_size_that_holds_a_grid_picks_what_the_largest_does(
        kind, grid):
    """Picks of the rows that carry tokens, every page but each layer's
    sink, every side-plane entry but the sink's, and the layers'
    counters: equal at every size that holds the tick, bit for bit (the
    latent pages to the last bits)."""
    engine, ticks = _recorded(kind)
    if grid == "idle_rows":
        chosen = [t for t in ticks if (t[8] == engine.B).any()]
    else:
        chosen = [t for t in ticks if _grid_kind(engine, t) == grid]
    assert chosen, f"the scripted run has no {grid} tick"
    if kind == "spec":
        assert any((_m(engine, t)[t[8] < engine.B] > 1).any()
                   and _grid_kind(engine, t) == "mixed" for t in chosen)
    sizes = set()
    for tick in chosen[:3] + chosen[-1:]:
        m = _m(engine, tick)
        top = _replay(engine, tick, engine.rungs[-1])
        for rung in engine.rungs[:-1]:
            if rung < m.sum():
                continue
            sizes.add(rung)
            out, pools, ys = _replay(engine, tick, rung)
            for c in np.flatnonzero(m):
                n = m[c] if engine.spec_k else 1
                np.testing.assert_array_equal(out[c, :n], top[0][c, :n])
            for got, want in zip(pools, top[1]):
                # XLA:CPU picks the latent einsums' loop order by their
                # row count, so the latent pages agree to the last bits
                (np.testing.assert_allclose if kind == "mla" else
                 np.testing.assert_array_equal)(
                     got[:, 1:], want[:, 1:], **LAST_BITS.get(kind, {}))
            jax.tree.map(np.testing.assert_array_equal, ys, top[2])
    assert sizes or grid == "full"       # a full grid has one size only


@pytest.mark.parametrize("kind", ["fp", "spec", "int8", "lora", "mla"])
def test_dispatcher_takes_the_smallest_size_that_holds_the_tick(kind):
    engine, ticks = _recorded(kind)
    full = engine.n_rows * engine.qb
    assert engine.rungs == (full // 4, full)
    taken = []
    for tick in ticks:
        n_tok = int(_m(engine, tick).sum())
        assert len(tick[-1]) == min(r for r in engine.rungs if r >= n_tok)
        np.testing.assert_array_equal(tick[-1], np.arange(len(tick[-1])))
        taken.append((len(tick[-1]), n_tok))
    assert {size for size, _ in taken} == set(engine.rungs)
    assert (full, full) in taken             # a full tick: the grid itself
    # the two counters add up over the run, and so do the span's ends
    assert engine.stats["unified_steps"] == len(taken)
    assert engine.stats["token_places"] == sum(s for s, _ in taken)
    assert engine.stats["tokens_packed"] == sum(n for _, n in taken)
    assert engine.stats["tokens_packed"] >= sum(
        len(r.prompt) for r in _requests(kind))


def test_first_step_compiles_every_size_and_later_traffic_none():
    """Warm-up is the first tick: after ``step()`` has returned once,
    traffic that crosses every step size asks the compiler for nothing,
    and the ring's ``engine.step`` ends carry the sizes and the tokens."""
    from paddle_tpu import obs

    obs.arm()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _d, **kw: compiles.append(kw.get("fun_name"))
        if name == "/jax/core/compile/backend_compile_duration" else None)
    engine = _engine("fp")
    for r in _requests("fp"):
        engine.submit(r)
    assert engine.step(now=1e9)
    assert engine._unified._cache_size() == len(engine.rungs)
    after_first = len(compiles)
    while engine.step(now=1e9):
        pass
    assert len(compiles) == after_first, compiles[after_first:]
    assert engine._unified._cache_size() == len(engine.rungs)
    events, _ = obs.tracer().snapshot()
    ends = [e["args"] for e in events
            if e["name"] == "engine.step" and e["ph"] == "E"]
    assert {e["places"] for e in ends} >= set(engine.rungs)
    assert sum(e["places"] for e in ends) == engine.stats["token_places"]
    assert sum(e["tokens"] for e in ends) == engine.stats["tokens_packed"]
    assert all(e["tokens"] <= e["places"] for e in ends)


def test_moe_counters_count_the_ticks_tokens_at_every_size():
    """``moe_assigned_all`` is 8 (here 2) x valid tokens x expert layers,
    and no padding place, idle row's or rung's, is routed: what the held
    experts were assigned never exceeds it."""
    engine, ticks = _recorded("mla")
    cfg = engine.cfg
    n_moe = cfg.n_layers - cfg.n_dense_layers
    st = engine.stats
    assert st["moe_assigned_all"] == (cfg.experts_per_token * n_moe
                                      * st["tokens_packed"])
    assert 0 < st["moe_assigned_held"] <= st["moe_assigned_all"]
    for tick in ticks:
        n_tok = int(_m(engine, tick).sum())
        for rung in (r for r in engine.rungs if r >= n_tok):
            ys = _replay(engine, tick, rung)[2]
            per_layer = np.concatenate(
                [np.asarray(y[0]).reshape(-1, cfg.held[1])   # (sizes, tile)
                 for y in ys if y is not None]).sum(1)
            assert (per_layer <= cfg.experts_per_token * n_tok).all()


@pytest.mark.parametrize("m,T", [
    ([1, 1, 0, 0], 8), ([8, 3, 1, 0], 16), ([0, 5, 0, 2], 8),
    ([8, 8, 8, 8], 32), ([2, 0, 1, 8], 32), ([4, 4, 0, 0], 8)],
    ids=["decode", "mixed", "gaps", "full", "top-ragged", "exact"])
def test_token_layout_is_row_major_and_its_maps_invert(m, T):
    qb, C = 8, len(m)
    lay = token_layout(jnp.asarray(m, jnp.int32), qb,
                       jnp.arange(T, dtype=jnp.int32))
    grid = np.arange(C * qb).reshape(C, qb) + 100
    held = np.arange(qb)[None, :] < np.asarray(m)[:, None]
    packed = np.asarray(lay.to_packed(jnp.asarray(grid)))
    valid = np.asarray(lay.valid)
    assert valid.sum() == sum(m) and packed.shape == (T,)
    # the tick's tokens in row-major order, padding behind them
    np.testing.assert_array_equal(packed[valid], grid[held])
    if T < C * qb:
        assert valid[:sum(m)].all()
    back = np.asarray(lay.to_grid(jnp.asarray(packed)))
    np.testing.assert_array_equal(back[held], grid[held])
    last = np.asarray(lay.last)
    for c in np.flatnonzero(m):
        assert packed[last[c]] == grid[c, m[c] - 1]
    assert ((0 <= last) & (last < T)).all()
