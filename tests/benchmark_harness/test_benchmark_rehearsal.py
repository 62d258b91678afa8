"""Tiny-size rehearsals of the benchmark on CPU devices, through the real
engine and the real train step: each traffic kind prints the contract's
line (marked as a rehearsal, with no device number in it), and `correct`
comes out false when the timed path is broken underneath or when the
reference runs in the precision below (the control)."""

import json
import time

import numpy as np
import pytest

import jax

from benchmark import checks, harness
from benchmark import run as bench_run
from benchmark.kinds import serve_common as sc
from benchmark.kinds import train_steps

TINY = {
    "configs": [
        {"name": "tiny-gpt", "source": "none", "reduced": [], "why": "toy",
         "file": "benchmark/configs/tiny-gpt.json"},
        {"name": "tiny-mistral", "source": "none", "reduced": [],
         "why": "toy", "file": "benchmark/configs/tiny-mistral.json"}],
    "train": ["tiny-train", "tiny-train-dp2mp2"],
    "serve": ["tiny-backlog", "tiny-sessions", "tiny-open"],
}


@pytest.fixture()
def tiny_bm(monkeypatch):
    """BENCHMARK.json plus toy cells, added as a later PR would add cells:
    entries and files only."""
    bm = json.loads(json.dumps(harness.load_benchmark()))
    bm["configs"] += TINY["configs"]
    for kind, config in (("train", "tiny-gpt"), ("serve", "tiny-mistral")):
        for traffic in TINY[kind]:
            name = f"{config}.{traffic}"
            bm["workloads"].append({
                "name": name, "config": config, "traffic": traffic,
                "chips": 4 if "dp2mp2" in traffic else 1, "why": "toy"})
            for m in bm["end_to_end"] + bm["per_layer"]:
                if "workloads" in m and (
                        m["name"].startswith(kind) or kind == "serve"
                        and m["name"].startswith(("itl", "ttft", "prefix"))):
                    m["workloads"] = m["workloads"] + [name]
    monkeypatch.setattr(harness, "load_benchmark", lambda: bm)
    return bm


def _run(cell, trace, seconds=1.0, chips=1, seed=2 ** 31 + 12345):
    return bench_run.run_cell(cell, seed, seconds, trace,
                              devices=jax.devices()[:chips],
                              t_start=time.perf_counter())


@pytest.mark.parametrize("cell,chips", [
    ("tiny-gpt.tiny-train", 1), ("tiny-gpt.tiny-train-dp2mp2", 4),
    ("tiny-mistral.tiny-backlog", 1), ("tiny-mistral.tiny-sessions", 1),
    ("tiny-mistral.tiny-open", 1)])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(tiny_bm, cell, chips, trace):
    line = _run(cell, bool(trace), chips=chips)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["rehearsal_on_cpu"] is True
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    # a CPU run never carries a number under a metric's name
    assert all(m["value"] is None for m in line["metrics"].values())
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in harness.metrics_for(tiny_bm, cell, section)}
    assert set(line["metrics"]) <= declared
    if not trace:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


# -- the timed path broken underneath --------------------------------------------

def _break_train(monkeypatch, fault):
    """Plant a fault under the harness: the step the window drives."""
    build = harness.load_module("systems/gpt_train.py").build

    def broken_build(*a, **k):
        system = build(*a, **k)
        step = system.step

        def unchanged(params, opt, toks, labs):
            copy = jax.tree.map(lambda x: x + 0, (params, opt))
            loss, _, _ = step(params, opt, toks, labs)
            return (loss,) + copy

        def half(params, opt, toks, labs):
            # half of the batch left out, the mean taken over the rest;
            # on a dp mesh this is also what replica 0 computes when the
            # gradients' exchange between chips is left out
            n = toks.shape[0] // 2
            return step(params, opt, toks[:n], labs[:n])

        system.step = {"state_unchanged": unchanged, "half_batch": half,
                       "no_exchange": half}[fault]
        system.step.put_batch = step.put_batch
        return system

    monkeypatch.setattr(harness.load_module("systems/gpt_train.py"), "build",
                        broken_build)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_broken_train_step_reads_not_correct(tiny_bm, monkeypatch, fault):
    _break_train(monkeypatch, fault)
    chips = 4 if fault == "no_exchange" else 1
    cell = "tiny-gpt.tiny-train-dp2mp2" if chips == 4 else \
        "tiny-gpt.tiny-train"
    line = _run(cell, False, seconds=0.5, chips=chips)
    assert line["correct"] is False, line["compared"]
    failed = [k for k, c in line["compared"].items()
              if c["limit"] is not None and not c["value"] <= c["limit"]]
    want = "update_norm_gap" if fault == "state_unchanged" else \
        "grad_norm_gap"
    assert want in failed


def test_altered_token_reads_not_correct(tiny_bm, monkeypatch):
    """A served token altered where it is produced (after the harvest)."""
    orig = sc.Driver.run_until

    def run_until(self, stop):
        engine = self.system.engine
        if not hasattr(engine, "_bench_broken"):
            harvest = engine._harvest

            def altered(inflight):
                harvest(inflight)
                for req in engine.slots:
                    if req is not None and len(req.out_tokens) == 2:
                        req.out_tokens[1] = (req.out_tokens[1] + 1) % 256

            engine._harvest = altered
            engine._bench_broken = True
        return orig(self, stop)

    monkeypatch.setattr(sc.Driver, "run_until", run_until)
    line = _run("tiny-mistral.tiny-backlog", False, seconds=1.0)
    assert line["correct"] is False, line["compared"]


# -- the control: the reference in the precision below --------------------------

def test_fp8_control_fails_the_served_comparison(tiny_bm):
    ref = harness.reference_for("tiny-mistral")
    model = harness.load_json("benchmark/configs/tiny-mistral.json")["model"]
    limit = harness.load_json(
        "benchmark/traffic/tiny-backlog.json")["limits"]["served_logit_gap"]
    worst = []
    for seed in (1, 2, 3):
        key = harness.seed_key(seed)
        tokens = harness.np_rng(seed, 0).integers(
            0, model["vocab_size"], size=(3, 48), dtype="int32")
        pos = [list(range(16, 47))] * 3
        hi = ref.logits_at(model, key, tokens, pos)
        lo = ref.logits_at(model, key, tokens, pos, quant="fp8")
        gaps = np.concatenate([
            h.max(-1) - np.take_along_axis(h, l.argmax(-1)[:, None], -1)[:, 0]
            for h, l in zip(hi, lo)])
        worst.append(float(gaps.max()))
    assert min(worst) > limit, (worst, limit)


def test_fp8_control_fails_the_training_comparison(tiny_bm):
    config = harness.load_json("benchmark/configs/tiny-gpt.json")
    traffic = harness.load_json("benchmark/traffic/tiny-train.json")
    ref = harness.reference_for("tiny-gpt")
    for seed in (1, 2, 3):
        key = harness.seed_key(seed)
        raw = np.asarray(ref.make_batches(
            config["model"], jax.random.fold_in(key, 1), 3,
            traffic["batch"], traffic["seq_len"]))
        want = ref.follow_steps(config["model"], config["train"], key, raw)
        low = ref.follow_steps(config["model"], config["train"], key, raw,
                               quant="fp8")
        verdict = checks.judge(checks.training_numbers(low, want),
                               traffic["limits"])
        assert verdict["correct"] is False, verdict["numbers"]
        same = checks.judge(checks.training_numbers(want, want),
                            traffic["limits"])
        assert same["correct"] is True


def test_first_steps_are_the_windows_own_object(tiny_bm):
    """Set-up's compared steps and the window go through one step and one
    state: the state the window starts from has taken follow + warm
    steps."""
    config = harness.load_json("benchmark/configs/tiny-gpt.json")
    traffic = harness.load_json("benchmark/traffic/tiny-train.json")
    ref = harness.reference_for("tiny-gpt")
    system = harness.load_module("systems/gpt_train.py").build(
        config, traffic, jax.devices()[:1], ref)
    key = harness.seed_key(9)
    params, opt = system.new_state(key)
    feed, _ = system.batches(jax.random.fold_in(key, 1), 4)
    params, opt, prog = train_steps.first_steps(system, params, opt, feed,
                                                key, 3)
    assert int(opt["t"]) == 3 and len(prog["losses"]) == 3
    params, opt, _, done = train_steps.drive(system, params, opt, feed,
                                             harness.Spans(), n_steps=2)
    assert int(opt["t"]) == 5 and len(done) == 2
