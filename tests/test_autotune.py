"""Persistent Pallas autotune registry tests (ISSUE 6 tentpole):
hit/miss accounting, atomic persistence, source-hash and device-kind
keying, sweep gating, and the fresh-subprocess round-trip that proves
the cache actually survives process restart."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import jax

from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.ops.pallas.autotune import (AutotuneRegistry, _device_kind,
                                            cache_path, source_hash)

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def sweep_on():
    old = (GLOBAL_FLAGS.get("pallas_autotune_sweep")
           if GLOBAL_FLAGS.has("pallas_autotune_sweep") else "auto")
    GLOBAL_FLAGS.set("pallas_autotune_sweep", "1")
    yield
    GLOBAL_FLAGS.set("pallas_autotune_sweep", old)


def _measure(timings):
    return lambda cand: timings[cand]


def test_miss_sweeps_persists_then_hits(tmp_path, sweep_on):
    path = str(tmp_path / "cache.json")
    reg = AutotuneRegistry(path)
    cfg = reg.tuned("k", "b1", "bf16", [256, 512],
                    measure=_measure({256: 2.0, 512: 1.0}), source="s1")
    assert cfg == 512
    assert reg.misses == 1 and reg.sweeps == 1 and reg.hits == 0
    # second lookup: in-memory hit, no re-sweep
    cfg = reg.tuned("k", "b1", "bf16", [256, 512],
                    measure=_measure({256: 2.0, 512: 1.0}), source="s1")
    assert cfg == 512 and reg.hits == 1 and reg.sweeps == 1
    # the winner is on disk, keyed by device kind
    data = json.load(open(path))
    (key,) = data["entries"].keys()
    assert key == f"k|{jax.devices()[0].device_kind}|b1|bf16"
    assert data["entries"][key]["config"] == 512
    # a FRESH registry instance on the same file hits without sweeping
    reg2 = AutotuneRegistry(path)
    cfg = reg2.tuned("k", "b1", "bf16", [256, 512],
                     measure=_measure({256: 2.0, 512: 1.0}), source="s1")
    assert cfg == 512 and reg2.hits == 1 and reg2.sweeps == 0


def test_source_hash_mismatch_is_clean_miss(tmp_path, sweep_on):
    path = str(tmp_path / "cache.json")
    reg = AutotuneRegistry(path)
    assert reg.tuned("k", "b1", "bf16", [256, 512],
                     measure=_measure({256: 2.0, 512: 1.0}),
                     source="old") == 512
    # edited kernel: same key, different source -> re-sweep, not reuse
    cfg = reg.tuned("k", "b1", "bf16", [256, 512],
                    measure=_measure({256: 1.0, 512: 2.0}), source="new")
    assert cfg == 256
    assert reg.misses == 2 and reg.sweeps == 2 and reg.hits == 0


def test_no_sweep_returns_legacy_default(tmp_path, monkeypatch):
    old = (GLOBAL_FLAGS.get("pallas_autotune_sweep")
           if GLOBAL_FLAGS.has("pallas_autotune_sweep") else "auto")
    GLOBAL_FLAGS.set("pallas_autotune_sweep", "0")
    try:
        reg = AutotuneRegistry(str(tmp_path / "cache.json"))
        cfg = reg.tuned("k", "b1", "bf16", [256, 512],
                        measure=_measure({256: 2.0, 512: 1.0}), source="s")
        assert cfg == 256  # candidates[0] == pre-autotune behavior
        assert reg.sweeps == 0 and not os.path.exists(
            str(tmp_path / "cache.json"))
    finally:
        GLOBAL_FLAGS.set("pallas_autotune_sweep", old)


def test_disabled_registry_returns_default(tmp_path, sweep_on):
    old = (GLOBAL_FLAGS.get("pallas_autotune")
           if GLOBAL_FLAGS.has("pallas_autotune") else True)
    GLOBAL_FLAGS.set("pallas_autotune", False)
    try:
        reg = AutotuneRegistry(str(tmp_path / "cache.json"))
        assert reg.tuned("k", "b1", "bf16", [256, 512],
                         measure=_measure({256: 2.0, 512: 1.0}),
                         source="s") == 256
        assert reg.misses == 0 and reg.sweeps == 0
    finally:
        GLOBAL_FLAGS.set("pallas_autotune", old)


def test_refused_default_or_kernel_arm_raises(tmp_path, sweep_on):
    """A sweep may not swallow a compile failure of the default or of a
    Pallas arm: that is how a Mosaic refusal used to turn into the XLA
    path without a word."""
    def boom(cand):
        raise RuntimeError("mosaic says no")

    path = str(tmp_path / "cache.json")
    with pytest.raises(RuntimeError, match="candidate 256 failed"):
        AutotuneRegistry(path).tuned("k", "b1", "bf16", [256, 512],
                                     measure=boom, source="s")

    def kernel_boom(cand):
        if cand != "xla":
            raise RuntimeError("mosaic says no")
        return 1.0

    with pytest.raises(RuntimeError, match="'kernel:128' failed"):
        AutotuneRegistry(path).tuned("k", "b2", "bf16",
                                     ["xla", "kernel:128"],
                                     measure=kernel_boom, source="s")
    # a failed sweep must not poison the cache
    assert not os.path.exists(path)


def test_refused_tile_is_skipped_with_its_error_text(tmp_path, sweep_on):
    def measure(cand):
        if cand == 1024:
            raise RuntimeError("scoped vmem exceeded")
        return {256: 2.0, 512: 1.0}[cand]

    path = str(tmp_path / "cache.json")
    reg = AutotuneRegistry(path)
    assert reg.tuned("k", "b1", "bf16", [256, 512, 1024], measure=measure,
                     source="s") == 512
    entry = json.load(open(path))["entries"]["k|%s|b1|bf16" % _device_kind()]
    assert entry["refused"] == {"1024": "RuntimeError: scoped vmem exceeded"}


def test_committed_table_is_read_first_and_never_written(tmp_path, sweep_on):
    """The tracked table pins configs across checkouts: it outranks the
    per-checkout file, hits without sweeping, and is never modified."""
    key = "k|%s|b1|bf16" % _device_kind()
    committed = str(tmp_path / "committed.json")
    with open(committed, "w") as f:
        json.dump({"version": 2, "programs": {}, "entries": {
            key: {"config": 512, "source": "s"}}}, f)
    before = open(committed).read()
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        json.dump({"version": 2, "programs": {}, "entries": {
            key: {"config": 256, "source": "s"}}}, f)

    def never(cand):
        raise AssertionError("committed entry must not be re-swept")

    reg = AutotuneRegistry(path, committed=committed)
    assert reg.tuned("k", "b1", "bf16", [256, 512], measure=never,
                     source="s") == 512
    assert (reg.hits, reg.sweeps) == (1, 0)
    # a kernel edit (new source hash) makes the committed entry stale:
    # the sweep result goes to the per-checkout file only
    assert reg.tuned("k", "b1", "bf16", [256, 512],
                     measure=_measure({256: 1.0, 512: 2.0}),
                     source="s2") == 256
    assert open(committed).read() == before
    assert json.load(open(path))["entries"][key]["source"] == "s2"


@pytest.mark.parametrize("how", ["hit", "sweep", "default"])
def test_resolve_is_announced_once_per_key_with_how(tmp_path, how):
    """Every resolution tells the obs ring once per key per tracer which
    config a call site got and how (a table had it, a sweep measured
    it, or candidates[0] for want of both), so a flight record says
    which form the compiled step took."""
    from paddle_tpu import obs

    committed = str(tmp_path / "committed.json")
    key = "k|%s|b1|bf16" % _device_kind()
    entries = {key: {"config": 512, "source": "s"}} if how == "hit" else {}
    with open(committed, "w") as f:
        json.dump({"version": 2, "programs": {}, "entries": entries}, f)
    old = (GLOBAL_FLAGS.get("pallas_autotune_sweep")
           if GLOBAL_FLAGS.has("pallas_autotune_sweep") else "auto")
    GLOBAL_FLAGS.set("pallas_autotune_sweep", "1" if how == "sweep" else "0")
    obs.arm(capacity=256)
    try:
        reg = AutotuneRegistry(str(tmp_path / "cache.json"),
                               committed=committed)
        want = {"hit": 512, "sweep": 1024, "default": 256}[how]
        for _ in range(3):      # a step traced three times: one instant
            assert reg.tuned("k", "b1", "bf16", [256, 512, 1024],
                             measure=_measure({256: 2., 512: 3., 1024: 1.}),
                             source="s") == want
        reg.tuned("k", "b2", "bf16", [7], source="s")   # another key
        told = [e["args"] for e in obs.tracer().snapshot()[0]
                if e["name"] == "autotune.resolve"]
        assert told == [
            {"kernel": "k", "bucket": "b1|bf16", "config": str(want),
             "how": how},
            {"kernel": "k", "bucket": "b2|bf16", "config": "7",
             "how": "default"}]
        # a fresh ring is told again: its flight record stands alone
        obs.arm(capacity=256)
        reg.tuned("k", "b1", "bf16", [256, 512, 1024], source="s")
        told = [e["args"] for e in obs.tracer().snapshot()[0]
                if e["name"] == "autotune.resolve"]
        assert [t["bucket"] for t in told] == ["b1|bf16"]
        assert told[0]["how"] == ("default" if how == "default" else "hit")
    finally:
        GLOBAL_FLAGS.set("pallas_autotune_sweep", old)
        obs.arm()


def test_corrupt_cache_is_empty_cache(tmp_path, sweep_on):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        f.write("{not json")
    reg = AutotuneRegistry(path)
    assert reg.tuned("k", "b1", "bf16", [256, 512],
                     measure=_measure({256: 2.0, 512: 1.0}),
                     source="s") == 512


def test_source_hash_is_stable_and_content_keyed():
    a = source_hash(cache_path)
    assert a == source_hash(cache_path)
    assert a != source_hash(source_hash)
    assert len(a) == 16


def test_cache_path_flag_override(tmp_path):
    old = (GLOBAL_FLAGS.get("pallas_autotune_cache")
           if GLOBAL_FLAGS.has("pallas_autotune_cache") else "")
    GLOBAL_FLAGS.set("pallas_autotune_cache", str(tmp_path / "x.json"))
    try:
        assert cache_path() == str(tmp_path / "x.json")
    finally:
        GLOBAL_FLAGS.set("pallas_autotune_cache", old)
        assert cache_path().endswith(os.path.join("artifacts",
                                                  "pallas_autotune.json"))


def test_fresh_subprocess_round_trip(tmp_path):
    """The acceptance pin: a second PROCESS skips the sweep entirely —
    the cache is persistent, not per-process."""
    cache = str(tmp_path / "cache.json")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               FLAGS_pallas_autotune_sweep="1",
               FLAGS_pallas_autotune_cache=cache)
    worker = os.path.join(REPO, "tests", "autotune_worker.py")

    def run():
        proc = subprocess.run([sys.executable, worker], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        return json.loads(proc.stdout.strip().splitlines()[-1])

    first = run()
    assert first["config"] == 3  # the fastest candidate won
    assert first["autotune_sweeps"] == 1
    assert first["autotune_cache_hits"] == 0

    second = run()
    assert second["config"] == 3
    assert second["autotune_sweeps"] == 0   # no re-sweep: read from disk
    assert second["autotune_cache_misses"] == 0
    assert second["autotune_cache_hits"] == 1


# ---------------------------------------------------------------------------
# ISSUE 15 satellites: locked persistence + the per-program (v2) layer
# ---------------------------------------------------------------------------


def test_two_writers_keep_both_keys(tmp_path):
    """Regression for the read-merge-rename race: two registries persist
    different keys concurrently, with the read->write window widened by
    a sleep INSIDE the merge.  Without the fcntl sidecar lock both read
    the empty file and the second rename drops the first one's key."""
    path = str(tmp_path / "cache.json")
    rega, regb = AutotuneRegistry(path), AutotuneRegistry(path)
    barrier = threading.Barrier(2)
    errs = []

    def writer(reg, key):
        def mutate(entries, programs):
            entries[key] = {"config": 1, "source": "s"}
            time.sleep(0.25)

        try:
            barrier.wait(timeout=10)
            reg._persist(mutate)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=writer, args=(rega, "ka|cpu|b|f32")),
          threading.Thread(target=writer, args=(regb, "kb|cpu|b|f32"))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs
    data = json.load(open(path))
    assert set(data["entries"]) == {"ka|cpu|b|f32", "kb|cpu|b|f32"}


def test_v1_cache_file_still_loads(tmp_path, sweep_on):
    """Additive schema: a version-1 file (entries only) keeps hitting,
    and the first write upgrades it to v2 without dropping v1 entries."""
    path = str(tmp_path / "cache.json")
    key = f"k|{jax.devices()[0].device_kind}|b1|bf16"
    with open(path, "w") as f:
        json.dump({"version": 1,
                   "entries": {key: {"config": 512, "source": "s1"}}}, f)
    reg = AutotuneRegistry(path)
    cfg = reg.tuned("k", "b1", "bf16", [256, 512],
                    measure=_measure({256: 1.0, 512: 2.0}), source="s1")
    assert cfg == 512  # the v1 entry, not a fresh sweep's winner
    assert reg.hits == 1 and reg.sweeps == 0
    assert reg.program_lookup("nope") is None  # v1: empty program table
    # a new sweep upgrades the file in place, preserving the v1 entry
    reg.tuned("k2", "b1", "bf16", [256, 512],
              measure=_measure({256: 2.0, 512: 1.0}), source="s2")
    data = json.load(open(path))
    assert data["version"] == 2
    assert data["entries"][key]["config"] == 512
    assert data["programs"] == {}


def test_program_commit_adopt_and_refusals(tmp_path):
    path = str(tmp_path / "cache.json")
    kind = jax.devices()[0].device_kind
    key = f"k|{kind}|b1|bf16"
    phash = "ab" * 8
    reg = AutotuneRegistry(path)
    reg.program_commit(phash, [{"template": "rms_epilogue", "applied": True}],
                       {key: {"config": 512, "source": "ks"}}, source="src1")

    # wrong source / unknown hash: refused, nothing adopted
    reg2 = AutotuneRegistry(path)
    assert reg2.adopt_program(phash, "other-src") is False
    assert reg2.adopt_program("ff" * 8, "src1") is False
    assert reg2.program_hits == 0

    # the real adoption: tuned() resolves from the record with no sweep
    assert reg2.adopt_program(phash, "src1") is True
    assert reg2.program_hits == 1
    cfg = reg2.tuned("k", "b1", "bf16", [256, 512], source="ks")
    assert cfg == 512 and reg2.hits == 1 and reg2.sweeps == 0
    rec = reg2.program_lookup(phash)
    assert rec["fusion"] == [{"template": "rms_epilogue", "applied": True}]

    # commit also merged the entry into the flat table: a registry that
    # never adopts still hits through the ordinary tuned() path
    reg3 = AutotuneRegistry(path)
    assert reg3.tuned("k", "b1", "bf16", [256, 512], source="ks") == 512
    assert reg3.hits == 1

    # a record committed on another chip kind is refused
    data = json.load(open(path))
    data["programs"][phash]["device"] = "alien-chip"
    with open(path, "w") as f:
        json.dump(data, f)
    reg4 = AutotuneRegistry(path)
    assert reg4.adopt_program(phash, "src1") is False


def test_program_round_trip_fresh_subprocess(tmp_path):
    """The tentpole pin: a restarted process tracing the same program
    adopts the committed v2 record — program_cache_hit, zero sweeps,
    the same program hash, and bit-identical outputs."""
    cache = str(tmp_path / "cache.json")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               FLAGS_pallas_autotune_sweep="1",
               FLAGS_pallas_autotune_cache=cache)
    env.pop("XLA_FLAGS", None)  # single device, like production restart
    worker = os.path.join(REPO, "tests", "compiler_program_worker.py")

    def run():
        proc = subprocess.run([sys.executable, worker], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    first = run()
    assert first["program_cache_hit"] is False
    assert first["n_sites"] >= 3 and first["n_applied"] == first["n_sites"]
    assert first["outputs_stable"] is True

    second = run()
    assert second["program_cache_hit"] is True
    assert second["autotune_program_hits"] >= 1
    assert second["autotune_sweeps"] == 0        # warm cache: zero sweeps
    assert second["program_hash"] == first["program_hash"]
    assert second["n_applied"] == first["n_applied"]
    assert second["out_sum"] == first["out_sum"]  # replay is bit-stable
    # the committed record carries the fusion decisions
    data = json.load(open(cache))
    rec = data["programs"][first["program_hash"]]
    assert len(rec["fusion"]) == first["n_sites"]
    assert rec["entries"]
