"""Traffic kind ``train_steps``: back-to-back training steps on seeded
batches for the whole window, one step kept in flight.

Set-up builds one object (the compiled step with its state), drives it
from the seed through its first steps by the window's own call and feed,
keeps what the comparison needs of them, and hands the same object to the
window.  The plain reference follows those first steps once the window
has closed and the program's state is freed.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

from benchmark import checks, harness


def _sq_norms(tree) -> dict:
    """Per-leaf 2-norms, computed where the leaves live."""
    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))(
            tree)
    flat, _ = jax.tree.flatten_with_path(norms)
    return {"/".join(str(p.key) for p in path): float(v) for path, v in flat}


def first_steps(system, params, opt, feed, key, n_follow: int) -> tuple:
    """Steps 1..n_follow through the window's own call; returns the state
    and the program's side of the comparison."""
    b1 = system.train["beta1"]
    losses, grad_norms, grad_sums = [], None, None
    for i in range(n_follow):
        loss, params, opt = system.step(params, opt, *feed[i])
        losses.append(float(loss))
        if i == 0:
            # the first gradient as the optimizer got it: m_1 = (1-b1) g_1
            grad_norms = {k: v / (1.0 - b1)
                          for k, v in _sq_norms(opt["m"]).items()}
            grad_sums = system.ref.chunk_sums(opt["m"], 1.0 / (1.0 - b1))
    init = system.initial_params(key)
    delta = jax.jit(lambda p, q: jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, q))(
            params, init)
    update_norms = _sq_norms(delta)
    del init, delta
    return params, opt, {"losses": losses, "grad_norms": grad_norms,
                         "grad_sums": grad_sums,
                         "update_norms": update_norms}


def drive(system, params, opt, feed, spans, seconds=None, n_steps=None):
    """Back-to-back steps, one in flight, until ``seconds`` have passed or
    ``n_steps`` were made.  Returns (params, opt, t_start, completion
    times): every step dispatched is finished and counted."""
    done, pending, i = [], None, 0
    t0 = time.perf_counter()
    while True:
        with spans.span("bench.train_step"):
            loss, params, opt = system.step(params, opt, *feed[i % len(feed)])
        i += 1
        if pending is not None:
            pending.block_until_ready()
            done.append(time.perf_counter())
        pending = loss
        if (n_steps is not None and i >= n_steps) or (
                seconds is not None and time.perf_counter() - t0 >= seconds):
            break
    pending.block_until_ready()
    done.append(time.perf_counter())
    return params, opt, t0, done


def run(ctx) -> dict:
    traffic, config = ctx.traffic, ctx.config
    ref = harness.reference_for(ctx.cell["config"])
    system = harness.load_module("systems/" + config["system"] + ".py").build(
        config, traffic, ctx.devices, ref)
    key = harness.seed_key(ctx.seed)
    n_follow = traffic["follow_steps"]
    params, opt = system.new_state(key)
    feed, raw = system.batches(jax.random.fold_in(key, 1),
                               traffic["n_batches"])
    params, opt, prog = first_steps(system, params, opt, feed, key, n_follow)
    params, opt, _, _ = drive(system, params, opt, feed, ctx.spans,
                              n_steps=traffic["warm_steps"])
    setup_s = time.perf_counter() - ctx.t_start

    c0 = ctx.compiles.snapshot()
    params, opt, t0, done = drive(system, params, opt, feed, ctx.spans,
                                  seconds=ctx.seconds)
    c1 = ctx.compiles.snapshot()
    obs = {
        "setup_s": setup_s, "t0": t0, "t1": done[-1],
        "attempted": len(done), "failed": 0,
        "tokens": len(done) * system.tokens_per_step(),
        "series": {"train_step_ms": [
            1e3 * (b - a) for a, b in zip(done, done[1:])]},
        "counters": {
            "compiles_in_window": (c1["requests"] - c0["requests"]
                                   + c1["sweeps"] - c0["sweeps"]),
            "autotune_sweeps": c1["sweeps"], "compile_misses": c1["misses"]},
        "shapes": {"flash_attention": system.local_attention_shape(),
                   "fused_ce": system.ce_shape()},
    }

    if ctx.trace:
        n = traffic["trace_steps"]
        with ctx.profiler() as prof:
            params, opt, _, _ = drive(system, params, opt, feed, ctx.spans,
                                      n_steps=n)
        obs["trace_events"], obs["trace"] = prof.result
        for shape in obs["shapes"].values():
            shape["steps"] = n          # the traced segment's work
        obs["counters"]["traced_model_flops"] = (
            n * system.tokens_per_step() * system.flops_per_token())
        ma = system.memory_analysis(params, opt, *feed[0])
        obs["counters"]["hbm_program_bytes"] = (
            ma["argument_bytes"] + ma["temp_bytes"])

    obs["memory_peak_bytes"] = harness.memory_peak_bytes(ctx.devices)
    del params, opt, feed
    gc.collect()
    want = ref.follow_steps(system.model, system.train, key, raw,
                            n_steps=n_follow, devices=ctx.devices)
    numbers = checks.training_numbers(prog, want)
    obs["check"] = checks.judge(numbers, traffic["limits"])
    obs["check"]["where"] = numbers["_where"]
    obs["detail"] = {"where": numbers["_where"],
                     "leaf_sum_gaps": numbers["_leaf_sum_gaps"]}
    obs["check"]["program"] = prog["losses"]
    obs["check"]["reference"] = want["losses"]
    if ctx.control:
        obs["check"]["control"] = {
            name: checks.training_numbers(ref.follow_steps(
                system.model, system.train, key, raw, n_steps=n_follow,
                devices=ctx.devices, **how), want)
            for name, how in (("fp8", {"quant": "fp8"}),
                              ("half_batch", {"fault": "half_batch"}))}
        # (a state left unchanged reads 1 by this measure and needs no run)
    return obs
