"""What the serving traffic kinds share: the fixed grids of request
shapes, the window driver over ``ServingEngine.submit`` / ``.step``, the
reduction of token timestamps to TTFT / ITL, and the comparison of served
tokens with the plain reference.

A traffic kind is a *source* of requests:

    source.poll(now)      -> [Spec, ...] to submit now (each with its due time)
    source.done(rec, now) -> a request finished
    source.idle_until()   -> next time anything is due (open loop) or None

Load never depends on the seed: a cell's file fixes the multiset of request
shapes (quantile grids of the stated ranges) and their cyclic order; the
seed decides only where the cycle starts, the token ids and the weights.
Decoding is greedy.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from benchmark import checks, harness


def grid(spec: dict, n: int) -> list:
    """n lengths on the quantile grid of [lo, hi]: uniform in the log
    (``"scale": "log"``) or uniform, rounded to ``round_to``."""
    lo, hi, r = spec["lo"], spec["hi"], spec.get("round_to", 1)
    q = (np.arange(n) + 0.5) / n
    v = (lo * (hi / lo) ** q if spec.get("scale") == "log"
         else lo + (hi - lo) * q)
    return [int(max(r, round(x / r) * r)) for x in v]


def paired(n: int, stride: int) -> list:
    """A fixed permutation of range(n), so that two grids pair up the same
    way for every seed (stride and n co-prime)."""
    return [(i * stride) % n for i in range(n)]


def request_shapes(traffic: dict) -> list:
    """The file's multiset of (prompt, output) lengths: two quantile grids
    paired by a fixed stride."""
    n = traffic["n_shapes"]
    prompts, outputs = grid(traffic["prompt"], n), grid(traffic["output"], n)
    pair = paired(n, traffic["pair_stride"])
    return [(prompts[i], outputs[pair[i]]) for i in range(n)]


def seeded_order(traffic: dict, seed: int, n: int):
    """The order in which the fixed multiset is offered: one shuffle fixed
    by the file (``grid_seed``).  With ``"start": "seed"`` (the default) the
    seed chooses where the cycle starts, so every seed offers the same
    cyclic sequence from another point; with ``"start": "fixed"`` every
    seed starts at the same point, for cells whose window is so short
    against the cycle that the starting point alone moves the tails."""
    base = harness.np_rng(traffic["grid_seed"], 1).permutation(n)
    if traffic.get("start", "seed") == "fixed":
        return base
    return np.roll(base, -int(harness.np_rng(seed, 1).integers(n)))


@dataclasses.dataclass
class Spec:
    prompt: np.ndarray
    max_new: int
    due: float                      # when the caller wanted it sent
    tag: object = None              # the source's own bookkeeping


@dataclasses.dataclass
class Record:
    req: object
    spec: Spec
    submitted: float
    token_times: list = dataclasses.field(default_factory=list)
    finished: float | None = None


class Driver:
    """One thread: poll the source, submit, step the engine, stamp each new
    token with the host clock after the step that harvested it."""

    def __init__(self, system, source, spans):
        self.system, self.source, self.spans = system, source, spans
        self.live: list = []
        self.records: list = []
        self.failed = 0
        self.lag: list = []         # submit time - due time (generator lag)
        self._rid = 0

    def _submit(self, spec: Spec, now: float) -> None:
        req = self.system.request(self._rid, spec.prompt, spec.max_new)
        self._rid += 1
        rec = Record(req, spec, now)
        try:
            with self.spans.span("bench.submit"):
                self.system.engine.submit(req)
        except ValueError:
            self.failed += 1
            rec.finished = now
            self.source.done(rec, now)
            return
        self.lag.append(now - spec.due)
        self.live.append(rec)
        self.records.append(rec)

    def run_until(self, stop) -> None:
        """Drive until ``stop(now)`` is true (checked between ticks)."""
        engine = self.system.engine
        while True:
            now = time.perf_counter()
            if stop(now):
                return
            for spec in self.source.poll(now):
                self._submit(spec, now)
            with self.spans.span("bench.engine_step"):
                busy = engine.step()
            now = time.perf_counter()
            still = []
            for rec in self.live:
                n = len(rec.req.out_tokens)
                rec.token_times += [now] * (n - len(rec.token_times))
                if rec.req.aborted:
                    self.failed += 1
                    rec.finished = now
                    self.source.done(rec, now)
                elif n >= rec.req.max_new_tokens:
                    rec.finished = now
                    self.source.done(rec, now)
                else:
                    still.append(rec)
            self.live = still
            if not busy and not self.live:
                nxt = self.source.idle_until()
                if nxt is None:
                    return
                with self.spans.span("bench.wait_for_arrival"):
                    time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.05)))


def window_series(records: list, t0: float, t1: float) -> dict:
    """TTFT from the due time, for first tokens that fall in the window;
    every gap between consecutive output tokens of a request that ends in
    the window; output tokens harvested in the window."""
    ttft, itl, tokens = [], [], 0
    for rec in records:
        tt = rec.token_times
        if tt and t0 <= tt[0] < t1:
            ttft.append(tt[0] - rec.spec.due)
        itl += [b - a for a, b in zip(tt, tt[1:]) if t0 <= b < t1]
        tokens += sum(1 for t in tt if t0 <= t < t1)
    return {"ttft_s": ttft, "itl_s": itl, "output_tokens": tokens}


def served_numbers(system, ref, key, records: list, t0: float, t1: float,
                   n_sample: int, rng, pad_to: int, quant=None) -> dict:
    """Sample the requests that finished in the window (the longest among
    them), run the reference once over each prompt with its served tokens,
    and read the widest gap by which a served token's logit lies below the
    reference's best.  With ``quant`` the token read is the one that the
    lower precision puts first at each of the same positions (the control).
    """
    done = [r for r in records if r.finished is not None
            and t0 <= r.finished < t1 and not r.req.aborted
            and len(r.req.out_tokens) == r.req.max_new_tokens]
    if not done:
        return {"served_logit_gap": float("nan"), "_sampled": 0}
    longest = max(done, key=lambda r: len(r.spec.prompt) + r.spec.max_new)
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :max(0, n_sample - 1)]]
    T = max(len(r.spec.prompt) + r.spec.max_new for r in pick)
    T = -(-T // pad_to) * pad_to
    tokens = np.zeros((len(pick), T), np.int32)
    positions, served = [], []
    for i, r in enumerate(pick):
        out = np.asarray(r.req.out_tokens, np.int32)
        P = len(r.spec.prompt)
        tokens[i, :P] = r.spec.prompt
        tokens[i, P:P + len(out) - 1] = out[:-1]
        positions.append(list(range(P - 1, P - 1 + len(out))))
        served.append(out)
    logits = ref.logits_at(system.model, key, tokens, positions)
    if quant:
        low = ref.logits_at(system.model, key, tokens, positions, quant=quant)
        served = [lo.argmax(-1) for lo in low]
    gaps = np.concatenate([
        lg.max(-1) - np.take_along_axis(lg, s[:, None].astype(np.int64),
                                        -1)[:, 0]
        for lg, s in zip(logits, served)])
    return {"served_logit_gap": float(gaps.max()),
            "_sampled": len(pick), "_tokens": int(gaps.size),
            "_gap_p50": float(np.median(gaps)),
            "_mismatch": int((gaps > 0).sum())}


def run(ctx, make_source) -> dict:
    traffic, config = ctx.traffic, ctx.config
    ref = harness.reference_for(ctx.cell["config"])
    key = harness.seed_key(ctx.seed)
    system = harness.load_module("systems/" + config["system"] + ".py").build(
        config, ctx.devices, ref, key)
    source = make_source(traffic, config, ctx.seed)
    driver = Driver(system, source, ctx.spans)

    # warm-up: the same traffic, until the source says the cell is in its
    # steady state (and every program the window uses is compiled)
    t_warm = time.perf_counter()
    driver.run_until(lambda now: source.warm(now - t_warm))
    setup_s = time.perf_counter() - ctx.t_start

    c0, k0 = ctx.compiles.snapshot(), system.counters()
    t0 = time.perf_counter()
    driver.run_until(lambda now: now - t0 >= ctx.seconds)
    t1 = time.perf_counter()
    c1, k1 = ctx.compiles.snapshot(), system.counters()

    obs = {"setup_s": setup_s, "t0": t0, "t1": t1}
    obs["series"] = window_series(driver.records, t0, t1)
    obs["output_tokens"] = obs["series"].pop("output_tokens")
    obs["series"]["generator_lag_s"] = list(driver.lag)
    hist, edges = np.histogram(obs["series"]["ttft_s"], bins=12)
    obs["detail"] = {"ttft_hist": [hist.tolist(), np.round(edges, 4).tolist()],
                     "n_ttft": len(obs["series"]["ttft_s"]),
                     "n_itl": len(obs["series"]["itl_s"])}
    n_done = sum(1 for r in driver.records
                 if r.finished is not None and t0 <= r.finished < t1)
    obs["attempted"], obs["failed"] = n_done + driver.failed, driver.failed
    obs["counters"] = {k: k1[k] - k0[k] for k in k1}
    obs["counters"]["compiles_in_window"] = (
        c1["requests"] - c0["requests"] + c1["sweeps"] - c0["sweeps"])
    obs["counters"]["autotune_sweeps"] = c1["sweeps"]

    if ctx.trace:
        kept = system.record_dispatches()
        n_out0 = sum(len(r.token_times) for r in driver.records)
        with ctx.profiler() as prof:
            t_tr = time.perf_counter()
            driver.run_until(
                lambda now: now - t_tr >= traffic["trace_seconds"])
            system.engine.k_pages.block_until_ready()
        system.stop_recording()
        obs["trace_events"], obs["trace"] = prof.result
        rows = system.rows_of(kept)
        n_out = sum(len(r.token_times) for r in driver.records) - n_out0
        shape = system.attention_shape(rows)
        attn_flops, _ = harness.load_module(
            "work/ragged_paged_attention.py").work(shape)
        obs["shapes"] = {"ragged_paged_attention": shape,
                         "kv_pool": system.kv_pool_shapes()}
        obs["counters"]["traced_model_flops"] = (
            sum(n for _, n in rows) * system.matmul_flops_per_token()
            + n_out * system.head_flops_per_output_token() + attn_flops)
        obs["counters"]["traced_valid_tokens"] = sum(n for _, n in rows)
        ma = system.memory_analysis()
        obs["counters"]["hbm_program_bytes"] = (
            ma["argument_bytes"] + ma["temp_bytes"])

    obs["memory_peak_bytes"] = harness.memory_peak_bytes(ctx.devices)
    records = driver.records
    system.free()
    del driver
    gc.collect()
    sample = (system, ref, key, records, t0, t1, traffic["check_sample"])
    pad = traffic.get("check_pad_to", 256)
    numbers = served_numbers(*sample, harness.np_rng(ctx.seed, 7), pad)
    obs["check"] = checks.judge(numbers, traffic["limits"])
    if ctx.control:
        obs["check"]["control"] = {"fp8": served_numbers(
            *sample, harness.np_rng(ctx.seed, 7), pad, quant="fp8")}
    obs["check"]["sample"] = {k: v for k, v in numbers.items()
                              if k.startswith("_")}
    return obs
