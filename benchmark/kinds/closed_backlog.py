"""Traffic kind ``closed_backlog``: ``concurrency`` requests resident at
all times and a backlog that is never empty, so a finished request is
replaced at once.  The cell's file fixes the multiset of (prompt, output)
lengths; the seed decides where their
(fixed, cyclic) order starts and the token ids.  The first
``concurrency`` requests have their outputs cut to a uniform share of
their length, so that completions do not come in waves."""

from __future__ import annotations

from benchmark import harness
from benchmark.kinds import serve_common as sc


class Source:
    def __init__(self, traffic: dict, config: dict, seed: int):
        self.shapes = sc.request_shapes(traffic)
        self.order = sc.seeded_order(traffic, seed, len(self.shapes))
        self.seed, self.vocab = seed, config["model"]["vocab_size"]
        self.concurrency = traffic["concurrency"]
        self.target = traffic["concurrency"] + traffic["backlog"]
        self.warm_requests = traffic["warm_requests"]
        self.sent = self.finished = 0

    def next_spec(self, now: float) -> sc.Spec:
        i = self.sent
        p_len, o_len = self.shapes[self.order[i % len(self.order)]]
        if i < self.concurrency:
            o_len = max(1, round(o_len * (i + 1) / self.concurrency))
        prompt = harness.np_rng(self.seed, 1000 + i).integers(
            0, self.vocab, size=p_len, dtype="int32")
        self.sent += 1
        return sc.Spec(prompt, o_len, due=now)

    def poll(self, now: float) -> list:
        return [self.next_spec(now)
                for _ in range(self.target - (self.sent - self.finished))]

    def done(self, rec, now: float) -> None:
        self.finished += 1

    def idle_until(self):
        return 0.0

    def warm(self, elapsed: float) -> bool:
        return self.finished >= self.warm_requests


def run(ctx) -> dict:
    return sc.run(ctx, Source)
