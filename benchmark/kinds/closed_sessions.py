"""Traffic kind ``closed_sessions``: ``concurrency`` sessions, no think
time.  A session takes a fresh document and asks ``questions`` independent
questions about it one after the other (prompt = document + question),
each sent when the previous answer is complete; then a new session with a
new document takes its place.  The file fixes the multiset of session
shapes; the seed decides where their
(fixed, cyclic) order starts and the token ids.  In the warm-up
the first session of slot j asks only (j mod questions) + 1 questions, so
that the slots fall out of step, and the window opens when every slot is
past its first document."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.kinds import serve_common as sc


class Source:
    def __init__(self, traffic: dict, config: dict, seed: int):
        n, q = traffic["n_shapes"], traffic["questions"]
        docs = sc.grid(traffic["document"], n)
        ql = sc.grid(traffic["question"], n * q)
        al = sc.grid(traffic["answer"], n * q)
        pq, pa = (sc.paired(n * q, traffic[k])
                  for k in ("question_stride", "answer_stride"))
        self.shapes = [(docs[i], [(ql[pq[i * q + j]], al[pa[i * q + j]])
                                  for j in range(q)]) for i in range(n)]
        self.order = sc.seeded_order(traffic, seed, n)
        self.seed, self.vocab = seed, config["model"]["vocab_size"]
        self.q = q
        self.started = 0                    # sessions begun so far
        # per slot: [session index, document tokens, next question, due]
        self.slots = [self._new_session(0.0, first_q=q - 1 - j % q)
                      for j in range(traffic["concurrency"])]
        self.sessions_done = [0] * len(self.slots)

    def _new_session(self, due: float, first_q: int = 0) -> list:
        k = self.started
        self.started += 1
        doc_len, _ = self.shapes[self.order[k % len(self.order)]]
        doc = harness.np_rng(self.seed, 1000 + k).integers(
            0, self.vocab, size=doc_len, dtype="int32")
        return [k, doc, first_q, due]

    def poll(self, now: float) -> list:
        specs = []
        for j, slot in enumerate(self.slots):
            k, doc, qi, due = slot
            if due is None:                 # waiting for its answer
                continue
            q_len, a_len = self.shapes[self.order[k % len(self.order)]][1][qi]
            question = harness.np_rng(self.seed, 2000 + k, qi).integers(
                0, self.vocab, size=q_len, dtype="int32")
            specs.append(sc.Spec(np.concatenate([doc, question]), a_len,
                                 due=due or now, tag=j))
            slot[3] = None
        return specs

    def done(self, rec, now: float) -> None:
        j = rec.spec.tag
        slot = self.slots[j]
        slot[2] += 1
        slot[3] = now
        if slot[2] >= self.q:
            self.sessions_done[j] += 1
            self.slots[j] = self._new_session(now)

    def idle_until(self):
        return 0.0

    def warm(self, elapsed: float) -> bool:
        return min(self.sessions_done) >= 1


def run(ctx) -> dict:
    return sc.run(ctx, Source)
