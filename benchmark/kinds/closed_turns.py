"""Traffic kind ``closed_turns``: ``concurrency`` conversations, no think
time.  A conversation has ``turns`` turns; a turn's prompt is the whole
conversation so far (every earlier message and every served answer, token
for token) and the new message, sent when the previous answer is
complete; then a new conversation takes the place.  Nothing is shared
between conversations.  The file fixes the multiset of conversation shapes
(the first message's length, the later messages', the answers'); the seed
decides where their (fixed, cyclic) order starts and the token ids.  In the
warm-up the first conversation of slot j has only (j mod turns) + 1 turns,
so that the slots fall out of step, and the window opens when every slot
is past its first conversation.

The served comparison is ``closed_sessions_gaps``': the sample's per-token
gaps read as a distribution (mean, p90, share of mismatches) beside the
largest, lent to ``serve_common.run`` for the length of the call.  Where
the system kept the recurrent state of some live requests when it was
freed (``system.kept_states``: the state a request's newest snapshot held,
after exactly so many of its tokens), the state itself is compared too, in
the same pass of the reference: served logits forget a state's rounding
within a head's memory, the state does not (``state_numbers``)."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.kinds import closed_sessions_gaps, serve_common as sc


class Source:
    def __init__(self, traffic: dict, config: dict, seed: int):
        n, t = traffic["n_shapes"], traffic["turns"]
        first = sc.grid(traffic["first_message"], n)
        later = sc.grid(traffic["later_message"], n * (t - 1))
        answers = sc.grid(traffic["answer"], n * t)
        pm = sc.paired(n * (t - 1), traffic["message_stride"])
        pa = sc.paired(n * t, traffic["answer_stride"])
        # per shape: the t messages' lengths, the t answers'
        self.shapes = [
            ([first[i]] + [later[pm[i * (t - 1) + k]] for k in range(t - 1)],
             [answers[pa[i * t + k]] for k in range(t)]) for i in range(n)]
        self.order = sc.seeded_order(traffic, seed, n)
        self.seed, self.vocab = seed, config["model"]["vocab_size"]
        self.turns = t
        self.started = 0                    # conversations begun so far
        # per slot: [conversation, history tokens, next turn, due, turns]
        self.slots = [self._new_conversation(0.0, j % t + 1)
                      for j in range(traffic["concurrency"])]
        self.conversations_done = [0] * len(self.slots)

    def _new_conversation(self, due: float, turns: int | None = None) -> list:
        k = self.started
        self.started += 1
        return [k, np.zeros((0,), np.int32), 0, due,
                self.turns if turns is None else turns]

    def _shape(self, k: int) -> tuple:
        return self.shapes[self.order[k % len(self.order)]]

    def poll(self, now: float) -> list:
        specs = []
        for j, slot in enumerate(self.slots):
            k, history, turn, due, _turns = slot
            if due is None:                 # waiting for its answer
                continue
            messages, answers = self._shape(k)
            message = harness.np_rng(self.seed, 2000 + k, turn).integers(
                0, self.vocab, size=messages[turn], dtype="int32")
            specs.append(sc.Spec(np.concatenate([history, message]),
                                 answers[turn], due=due or now, tag=j))
            slot[3] = None
        return specs

    def done(self, rec, now: float) -> None:
        j = rec.spec.tag
        slot = self.slots[j]
        # the conversation so far: what was sent and what was served
        slot[1] = np.concatenate([
            rec.spec.prompt, np.asarray(rec.req.out_tokens, np.int32)])
        slot[2] += 1
        slot[3] = now
        if slot[2] >= slot[4] or len(rec.req.out_tokens) < rec.spec.max_new:
            self.conversations_done[j] += 1
            self.slots[j] = self._new_conversation(now)

    def idle_until(self):
        return 0.0

    def warm(self, elapsed: float) -> bool:
        return min(self.conversations_done) >= 1


def state_numbers(got: list, want: list) -> dict:
    """States ``[layers, heads, head width, d_state]`` against the
    reference's at the same tokens: per request, state-space layer and
    head the norm of the difference over the reference's norm, read as a
    distribution over all of them (a tenth of the heads remember over a
    hundred tokens and show a state that was never the request's own; the
    median shows a state of another boundary).  And the precision the
    state is KEPT in, read from the values themselves: the percentage of
    the non-zero ones that a bf16 holds exactly (the low sixteen bits of
    the float32 nought).  With bf16 activations around it a head's state
    lies 1-10% from the reference's and a bf16 state's rounding adds a
    tenth of that (PERF.md), so no distance shows it; the values do."""
    def heads(s):
        return s.reshape(*s.shape[:2], -1)

    gaps = np.concatenate([
        (np.linalg.norm(heads(g - w), axis=-1)
         / np.linalg.norm(heads(w), axis=-1)).ravel()
        for g, w in zip(got, want)])
    coarse = sum(int(((g.view(np.uint32) & 0xFFFF) == 0)[g != 0].sum())
                 for g in got)
    return {"served_state_gap_p50": float(np.median(gaps)),
            "served_state_gap_p99": float(np.percentile(gaps, 99)),
            "served_state_gap": float(gaps.max()),
            "served_state_bf16_share": 100.0 * coarse / max(
                1, sum(int((g != 0).sum()) for g in got)),
            "_states": len(got)}


class _WithStates:
    """The reference, asked in its plain pass for the kept requests'
    states too: each rides as one more sequence, cut at the token its
    snapshot stood at.  In the control's pass the same sequences alone,
    with the reference's own state rounded to bf16 at every token (the
    precision below the configuration's)."""

    def __init__(self, ref, kept: list):
        self.ref, self.kept, self.want, self.low = ref, kept, None, None
        self.cuts = [t for _req, t, _s in kept]

    def _rows(self, tokens):
        n = len(tokens)
        rows = np.zeros((n + len(self.cuts),
                         max([tokens.shape[1]] + self.cuts)), np.int32)
        rows[:n, :tokens.shape[1]] = tokens
        for i, (req, t, _s) in enumerate(self.kept):
            rows[n + i, :t] = np.concatenate([
                req.prompt, np.asarray(req.out_tokens, np.int32)])[:t]
        return rows, [[t - 1] for t in self.cuts]

    def logits_at(self, model, key, tokens, positions, quant=None):
        if not self.kept:
            return self.ref.logits_at(model, key, tokens, positions, quant)
        if quant:
            rows, at = self._rows(tokens[:0])
            _, self.low = self.ref.logits_at(
                model, key, rows, at, quant="bf16_state", states_at=self.cuts)
            return self.ref.logits_at(model, key, tokens, positions, quant)
        n = len(tokens)
        rows, at = self._rows(tokens)
        logits, states = self.ref.logits_at(
            model, key, rows, positions + at, states_at=[0] * n + self.cuts)
        self.want = states[n:]
        return logits[:n]


def served_numbers(system, ref, key, records, t0, t1, n_sample, rng, pad_to,
                   quant=None) -> dict:
    """``closed_sessions_gaps``' numbers of the sample, and where the
    system kept states ``state_numbers`` of them; as the control, of the
    reference's own states under a bf16 state."""
    kept = getattr(system, "kept_states", [])
    ref = _WithStates(ref, kept)
    numbers = closed_sessions_gaps.served_numbers(
        system, ref, key, records, t0, t1, n_sample, rng, pad_to, quant)
    if ref.want is not None:
        got = ref.low if quant else [s for _req, _t, s in kept]
        numbers.update(state_numbers(got, ref.want))
    return numbers


def run(ctx) -> dict:
    plain = sc.served_numbers
    sc.served_numbers = served_numbers
    try:
        return sc.run(ctx, Source)
    finally:
        sc.served_numbers = plain
