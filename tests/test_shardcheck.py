"""tpu-shardcheck tests: entry-program tracing, spec propagation, the
TPL201-TPL204 rule contracts, and the baseline machinery.

The golden test pins the FULL derived spec environment of the dp4×mp2
train step against tests/data/shardcheck_dp4mp2_env.json — any change
to how specs flow through the model (a new constraint, a dropped pin, a
different layer sharding) shows up as a readable JSON diff.

Regenerate the golden after an intentional sharding change:

    python - <<'PY'
    import json
    from tools.lint import shardcheck as S
    e = S.build_train_entry(name="train_dp4_mp2",
                            mesh_shape=(("dp", 4), ("mp", 2)))
    env = S.spec_environment(e)
    json.dump(env, open("tests/data/shardcheck_dp4mp2_env.json", "w"),
              indent=1, sort_keys=True)
    PY
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import shardcheck as S  # noqa: E402
from tools.lint.core import Finding  # noqa: E402

GOLDEN = os.path.join(REPO, "tests", "data", "shardcheck_dp4mp2_env.json")


def rules_of(findings):
    return sorted({f.rule for f in findings})


@pytest.fixture(scope="module")
def train_entry():
    return S.build_train_entry()


@pytest.fixture(scope="module")
def quant_entry():
    return S.build_quant_entry()


# -- spec domain units (no tracing) ------------------------------------------

def test_spec_from_partition_and_str():
    from jax.sharding import PartitionSpec as P

    assert S._spec_from_partition(P("dp", None, ("mp", "pp")), 3) == \
        (frozenset({"dp"}), frozenset(), frozenset({"mp", "pp"}))
    # padded to ndim; None pspec means fully replicated
    assert S._spec_from_partition(P("dp"), 3) == \
        (frozenset({"dp"}), frozenset(), frozenset())
    assert S._spec_from_partition(None, 2) == (frozenset(), frozenset())
    assert S._spec_str((frozenset({"mp"}), frozenset())) == "(mp,-)"
    assert S._spec_str(None) == "?"


def test_join_spec_prefers_agreement_then_first_nonempty():
    dp, mp, rep = frozenset({"dp"}), frozenset({"mp"}), frozenset()
    assert S._join_spec((dp,), (dp,)) == (dp,)
    assert S._join_spec((rep,), (mp,)) == (mp,)
    assert S._join_spec((dp,), (mp,)) == (dp,)     # conflict: first wins
    assert S._join_spec(None, (dp,)) == (dp,)
    assert S._join_spec((dp,), None) == (dp,)


# -- TPL201: involuntary reshard ---------------------------------------------

def test_tpl201_clean_on_current_train_step(train_entry):
    interp = S.ShardInterp(train_entry).run()
    tpl201 = [f for f in interp.findings if f.rule == "TPL201"]
    assert tpl201 == [], [f.message for f in tpl201]


def test_tpl201_fires_on_pre_fix_embedding_gather():
    # the PR 9 regression rebuilt: emb_constraint hook disabled ->
    # the wte gather is sharded on the lookup dim and never pinned
    entry = S.build_train_entry(name="train_prefix", emb_pin=False)
    interp = S.ShardInterp(entry).run()
    tpl201 = [f for f in interp.findings if f.rule == "TPL201"]
    assert len(tpl201) == 1, [f.message for f in tpl201]
    f = tpl201[0]
    assert f.path.endswith("models/gpt.py"), f.path
    assert "constraint" in f.message
    assert "gather" in f.message


# -- TPL202: collective in a partial-manual region ---------------------------

def test_tpl202_quant_refusal_proven_static(quant_entry):
    # dp-manual shard_map over a dp×pp mesh with pp>1: every collective
    # in the region fires TPL202 without any lowering attempt
    interp = S.ShardInterp(quant_entry).run()
    tpl202 = [f for f in interp.findings if f.rule == "TPL202"]
    assert tpl202, "quant pp>1 entry must fire TPL202"
    msgs = " | ".join(f.message for f in tpl202)
    assert "pp" in msgs
    # ... and the refusal is a *documented* finding, not a failure
    assert S.unexplained_findings(tpl202) == []


def test_tpl202_train_pipeline_region_is_explained(train_entry):
    interp = S.ShardInterp(train_entry).run()
    tpl202 = [f for f in interp.findings if f.rule == "TPL202"]
    assert tpl202, "the 1F1B partial-manual region must be visible"
    assert S.unexplained_findings(tpl202) == []


# -- TPL203: cross-program collective ordering -------------------------------

def _ev(*pairs):
    return [(p, ax, "f.py", i) for i, (p, ax) in enumerate(pairs)]


def test_tpl203_conflicting_order_fires():
    events = {"a": _ev(("psum", ("dp",)), ("all_gather", ("mp",))),
              "b": _ev(("all_gather", ("mp",)), ("psum", ("dp",)))}
    groups = {"a": "wire", "b": "wire"}
    f = S.ordering_findings(events, groups)
    assert len(f) == 1 and f[0].rule == "TPL203"
    assert "deadlock" in f[0].message


def test_tpl203_consistent_or_disjoint_is_clean():
    consistent = {"a": _ev(("psum", ("dp",)), ("all_gather", ("mp",))),
                  "b": _ev(("psum", ("dp",)), ("all_gather", ("mp",)))}
    groups = {"a": "wire", "b": "wire"}
    assert S.ordering_findings(consistent, groups) == []
    # fewer than two common collectives cannot deadlock on order
    one_common = {"a": _ev(("psum", ("dp",)), ("pmax", ("dp",))),
                  "b": _ev(("psum", ("dp",)), ("all_gather", ("mp",)))}
    assert S.ordering_findings(one_common, groups) == []
    # different groups never interleave
    other = {"a": _ev(("psum", ("dp",)), ("all_gather", ("mp",))),
             "b": _ev(("all_gather", ("mp",)), ("psum", ("dp",)))}
    assert S.ordering_findings(other, {"a": "x", "b": "y"}) == []
    # ungrouped entries are exempt
    assert S.ordering_findings(other, {"a": None, "b": None}) == []


# -- TPL204: VMEM roofline per fusion site -----------------------------------

class _Aval:
    def __init__(self, shape, dtype="float32"):
        self.shape, self.dtype = shape, dtype


class _Atom:
    def __init__(self, shape, dtype="float32"):
        self.aval = _Aval(shape, dtype)


def _site(in_shapes, out_shapes, applied=True):
    from paddle_tpu.compiler.fusion_pass import Site

    return Site(template="fx_tmpl", consumed=frozenset(), trigger=0,
                inputs=tuple(_Atom(s) for s in in_shapes),
                out_binds=tuple((_Atom(s), i)
                                for i, s in enumerate(out_shapes)),
                build=None, applied=applied)


def test_site_vmem_bytes_math():
    from paddle_tpu.compiler.fusion_pass import site_vmem_bytes

    # 256-row tile cap, f32, double-buffered:
    # in (1024, 128) -> 256*128*4 ; out (64,) -> 64*4 ; x2
    site = _site([(1024, 128)], [(64,)])
    assert site_vmem_bytes(site) == 2 * (256 * 128 * 4 + 64 * 4)
    # scalars count one element
    assert site_vmem_bytes(_site([()], [])) == 2 * 4


def test_tpl204_fires_over_budget_only():
    big = _site([(1024, 8192)], [(1024, 8192)])       # 32 MiB tile set
    small = _site([(64, 64)], [(64, 64)])
    unapplied = _site([(1024, 8192)], [(1024, 8192)], applied=False)
    f = S.vmem_findings("fx_entry", [big, small, unapplied])
    assert len(f) == 1 and f[0].rule == "TPL204"
    assert "fx_tmpl" in f[0].message and "fx_entry" in f[0].message
    assert S.vmem_findings("fx_entry", [small]) == []


# -- serving / wire entries --------------------------------------------------

def test_serving_entries_share_interleave_group():
    entries = S.build_serving_entries()
    assert [e.name for e in entries] == \
        ["serving_unified", "wire_stage", "wire_commit"]
    assert {e.interleave for e in entries} == {"serving-wire"}
    for e in entries:
        # single-device engine: everything replicated, nothing to fire
        interp = S.ShardInterp(e).run()
        assert interp.findings == [], (e.name,
                                       [f.message for f in interp.findings])


# -- golden spec environment -------------------------------------------------

def test_golden_dp4mp2_spec_environment():
    entry = S.build_train_entry(name="train_dp4_mp2",
                                mesh_shape=(("dp", 4), ("mp", 2)))
    env = S.spec_environment(entry)
    golden = json.load(open(GOLDEN))
    assert env == golden, (
        "derived spec environment drifted from the golden; if the "
        "sharding change is intentional, regenerate tests/data/"
        "shardcheck_dp4mp2_env.json (recipe in this file's docstring)")


# -- explained/baseline machinery --------------------------------------------

def _mk(entry, rule):
    return Finding(rule=rule, name="x", severity="error", path="p.py",
                   line=1, col=0, message=f"[entry {entry}] synthetic")


def test_unexplained_and_stale_filtering():
    known = _mk("train_dp2_pp2_mp2", "TPL202")
    novel = _mk("train_dp2_pp2_mp2", "TPL201")
    assert S.unexplained_findings([known, novel]) == [novel]
    # both EXPLAINED keys fire -> nothing stale; drop one -> stale line
    quant = _mk("quant_allreduce_dp2pp2", "TPL202")
    assert S.stale_explanations([known, quant]) == []
    stale = S.stale_explanations([known])
    assert len(stale) == 1 and "quant_allreduce_dp2pp2" in stale[0]


def test_diff_baselines_reports_drift():
    cur = {"entries": {"a": {"mesh": {"dp": 2}, "n_eqns": 5,
                             "collectives": [], "findings": {},
                             "spec_digest": "x", "source": "s.py"},
                       "c": {"mesh": {}, "n_eqns": 1, "collectives": [],
                             "findings": {}, "spec_digest": "z",
                             "source": "s.py"}},
           "explained": [["a", "TPL202"]]}
    base = {"entries": {"a": {"mesh": {"dp": 2}, "n_eqns": 7,
                              "collectives": [], "findings": {},
                              "spec_digest": "y", "source": "s.py"},
                        "b": {"mesh": {}, "n_eqns": 1, "collectives": [],
                              "findings": {}, "spec_digest": "w",
                              "source": "s.py"}},
            "explained": []}
    lines = "\n".join(S.diff_baselines(cur, base))
    assert "entry 'a': n_eqns drifted" in lines
    assert "entry 'a': spec_digest drifted" in lines
    assert "entry 'b': removed" in lines
    assert "entry 'c': new" in lines
    assert "explained set drifted" in lines
    assert S.diff_baselines(cur, json.loads(json.dumps(cur))) == []


def test_baseline_roundtrip(tmp_path):
    payload = {"version": 1, "entries": {"e": {"n_eqns": 3}},
               "explained": []}
    p = str(tmp_path / "artifacts" / "sc.json")
    S.write_baseline(payload, p)
    assert S.load_baseline(p) == payload


# -- the full report on the current tree -------------------------------------

@pytest.mark.smoke
def test_build_report_current_tree_is_clean_and_current():
    report = S.build_report()
    findings = report["findings"]
    # only the two documented TPL202 families fire on the current tree
    assert S.unexplained_findings(findings) == \
        [], [f.message for f in S.unexplained_findings(findings)]
    assert S.stale_explanations(findings) == []
    names = set(report["baseline"]["entries"])
    assert names == {"train_dp2_pp2_mp2", "serving_unified",
                     "serving_unified_mla_moe", "wire_stage",
                     "wire_commit", "quant_allreduce_dp2pp2"}
    # ... and the committed baseline matches the tree (currency: a PR
    # that changes sharding must regenerate artifacts/shardcheck.json)
    base = S.load_baseline(os.path.join(REPO, "artifacts",
                                        "shardcheck.json"))
    drift = S.diff_baselines(report["baseline"], base)
    assert drift == [], "\n".join(drift)
