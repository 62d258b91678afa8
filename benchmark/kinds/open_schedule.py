"""Traffic kind ``open_schedule``: requests sent on a schedule at a fixed
mean rate whether or not earlier ones have finished, each timed from the
moment it was due.  The file fixes the multiset of inter-arrival gaps (a
gamma distribution of the stated rate and coefficient of variation, drawn
once from ``grid_seed``: cv 1 is Poisson, cv > 1 bursts) and of request
shapes; the seed decides where their
(fixed, cyclic) order starts and the token ids.  The generator's
lag is reported (series ``generator_lag_s``)."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.kinds import serve_common as sc


class Source:
    def __init__(self, traffic: dict, config: dict, seed: int):
        n = traffic["n_shapes"]
        shapes = sc.request_shapes(traffic)
        self.shapes = [shapes[i] for i in sc.seeded_order(traffic, seed, n)]
        cv, mean = traffic["cv"], 1.0 / traffic["rate_per_s"]
        gaps = np.sort(harness.np_rng(traffic["grid_seed"], 0).gamma(
            1.0 / cv ** 2, mean * cv ** 2, size=n))
        gaps *= mean / gaps.mean()                  # the stated rate, exactly
        self.arrivals = np.cumsum(gaps[np.roll(
            harness.np_rng(traffic["grid_seed"], 2).permutation(n),
            -int(harness.np_rng(seed, 2).integers(n)))])
        self.seed, self.vocab = seed, config["model"]["vocab_size"]
        self.warm_seconds = traffic["warm_seconds"]
        self.sent, self.t_start = 0, None

    def poll(self, now: float) -> list:
        if self.t_start is None:
            self.t_start = now
        specs = []
        while (self.sent < len(self.arrivals)
               and self.t_start + self.arrivals[self.sent] <= now):
            p_len, o_len = self.shapes[self.sent]
            prompt = harness.np_rng(self.seed, 1000 + self.sent).integers(
                0, self.vocab, size=p_len, dtype="int32")
            specs.append(sc.Spec(prompt, o_len,
                                 due=self.t_start + self.arrivals[self.sent]))
            self.sent += 1
        return specs

    def done(self, rec, now: float) -> None:
        pass

    def idle_until(self):
        if self.sent >= len(self.arrivals):
            return None
        return self.t_start + self.arrivals[self.sent]

    def warm(self, elapsed: float) -> bool:
        return elapsed >= self.warm_seconds


def run(ctx) -> dict:
    return sc.run(ctx, Source)
