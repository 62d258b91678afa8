"""tpu-quantcheck tests: the precision lattice, the TPL300-TPL305 rule
contracts, the scale-leak regression harness, and the baseline
machinery.

The golden test pins the FULL derived format environment of the int8-KV
unified serving step against tests/data/quantcheck_int8_env.json — any
change to how formats/provenance flow through the step (a new quantize
point, a dropped clamp, a different dequant site) shows up as a
readable JSON diff.

Regenerate the golden after an intentional quantization change:

    python - <<'PY'
    import json
    from tools.lint import quantcheck as Q
    env = Q.format_environment(Q.build_serving_int8_entry())
    with open("tests/data/quantcheck_int8_env.json", "w") as f:
        json.dump(env, f, indent=1, sort_keys=True)
        f.write("\\n")
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

from tools.lint import quantcheck as Q  # noqa: E402
from tools.lint.core import Finding  # noqa: E402

GOLDEN = os.path.join(REPO, "tests", "data", "quantcheck_int8_env.json")


def rules_of(findings):
    return sorted({f.rule for f in findings})


def _entry_of(fn, avals, scale_invars=(), pairs=None, foreign=(),
              name="fx_entry"):
    """Trace ``fn`` shape-only into a synthetic QuantEntry — the rule
    fixtures' analog of a registered program."""
    import jax

    closed = jax.make_jaxpr(fn)(*avals)
    return Q.QuantEntry(
        name=name, closed=closed, source="tests/test_quantcheck.py",
        invar_names=[f"a{i}" for i in range(len(avals))],
        scale_invars=set(scale_invars),
        foreign_scale_invars=set(foreign),
        page_pairs=dict(pairs or {}))


def _run(entry):
    return Q.QuantInterp(entry).run()


# -- lattice units (no tracing) ----------------------------------------------

def test_qjoin_priority_and_flags():
    scale = Q.QVal(kind="scale", origin=1, anc=frozenset({1}),
                   clamped=True)
    quant = Q.QVal(kind="quant", origin=2, anc=frozenset({2}))
    j = Q._qjoin(scale, quant)
    assert j.kind == "quant"                  # quantized-ness is sticky
    assert j.anc == frozenset({1, 2})         # lineages union
    assert not j.clamped                      # clamped only if BOTH were
    # foreign is sticky in either direction
    assert Q._qjoin(Q.QVal(foreign=True), Q.QVal()).foreign
    assert Q._qjoin(Q.QVal(), Q.QVal(foreign=True)).foreign
    # literal values never survive a join
    assert Q._qjoin(Q.QVal(lit=0.0), Q.QVal(lit=0.0)).lit is None


def test_qval_str_excludes_event_ids():
    a = Q.QVal(fmt="float32", kind="scale", origin=3, clamped=True)
    b = Q.QVal(fmt="float32", kind="scale", origin=7, clamped=True)
    assert Q._qval_str(a) == Q._qval_str(b) == "float32|scale|clamped"
    assert Q._qval_str(Q.QVal(fmt="int8", kind="quant",
                              foreign=True)) == "int8|quant|foreign"


# -- TPL304: unclamped scale divide ------------------------------------------

def test_tpl304_fires_on_unclamped_divide():
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((4, 8), f32)
    s = jax.ShapeDtypeStruct((4, 1), f32)
    bad = _entry_of(lambda x, s: x / s, [x, s], scale_invars=[1])
    assert rules_of(_run(bad).findings) == ["TPL304"]
    good = _entry_of(lambda x, s: x / jnp.maximum(s, 1e-30), [x, s],
                     scale_invars=[1])
    assert _run(good).findings == []


# -- TPL305: double quantization ---------------------------------------------

def test_tpl305_fires_on_requantize_without_dequant():
    import jax
    import jax.numpy as jnp

    q = jax.ShapeDtypeStruct((4, 8), jnp.int8)
    s = jax.ShapeDtypeStruct((4, 1), jnp.float32)

    def requant(q, s):
        sc = jnp.maximum(s, 1e-30)
        return jnp.round(q.astype(jnp.float32) / sc).astype(jnp.int8)

    bad = _entry_of(requant, [q, s], scale_invars=[1], pairs={0: 1})
    assert rules_of(_run(bad).findings) == ["TPL305"]

    def rescale_instead(q, s):
        # the sanctioned path: a ratio *multiply* is exact for
        # unchanged scales and carries provenance — never TPL305
        from paddle_tpu.ops.quant import rescale_int8

        return rescale_int8(q, s, s * 2.0)

    good = _entry_of(rescale_instead, [q, s], scale_invars=[1],
                     pairs={0: 1})
    assert _run(good).findings == [], \
        [f.message for f in _run(good).findings]


# -- TPL303: scale-provenance mismatch ---------------------------------------

def test_tpl303_fires_on_cross_lineage_dequant():
    import jax
    import jax.numpy as jnp

    q8 = jax.ShapeDtypeStruct((4, 8), jnp.int8)
    sc = jax.ShapeDtypeStruct((4, 1), jnp.float32)

    def deq(q1, s1, q2, s2, wrong):
        s = s2 if wrong else s1
        return q1.astype(jnp.float32) * jnp.maximum(s, 1e-30)

    import functools
    bad = _entry_of(functools.partial(deq, wrong=True), [q8, sc, q8, sc],
                    scale_invars=[1, 3], pairs={0: 1, 2: 3})
    assert rules_of(_run(bad).findings) == ["TPL303"]
    good = _entry_of(functools.partial(deq, wrong=False),
                     [q8, sc, q8, sc],
                     scale_invars=[1, 3], pairs={0: 1, 2: 3})
    assert _run(good).findings == []


def test_tpl303_regression_scale_leak_fires_exactly_once():
    # The PR 8 pre-fix program (_zero_scale_on_alloc=False): the prior
    # tenant's absmax survives page realloc, flows through the
    # scatter-max running-absmax update, and poisons the quantize
    # divide — exactly one finding, at the quantize_to_scale divide.
    entry = Q.build_admit_entry(zero_scale_on_alloc=False)
    t303 = [f for f in _run(entry).findings if f.rule == "TPL303"]
    assert len(t303) == 1, [f.message for f in t303]
    assert t303[0].path.endswith("ops/quant.py"), t303[0].path
    assert "prior tenant" in t303[0].message
    assert "reset" in t303[0].message


def test_tpl303_shipped_admit_program_is_clean():
    # kv_scale_reset severs provenance AND clears the foreign bit
    entry = Q.build_admit_entry(zero_scale_on_alloc=True)
    interp = _run(entry)
    assert interp.findings == [], [f.message for f in interp.findings]
    # the foreign plane is visible in the environment even though the
    # program is clean — the reset is what launders it
    assert "float32|scale|foreign" in interp.all_fmts


def test_regression_report_gates_on_exactly_once():
    rep = Q.regression_report()
    assert rep["ok"] is True
    assert rep["regression"]["tpl303"] == 1
    assert rep["shipped"]["tpl303"] == 0
    assert "quant.py" in rep["regression"]["messages"][0]


# -- TPL301: low-precision accumulation --------------------------------------

def test_tpl301_fires_on_bf16_accumulating_dot():
    import jax
    import jax.numpy as jnp

    a = jax.ShapeDtypeStruct((4, 8), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)
    bad = _entry_of(lambda a, b: jnp.einsum("ij,jk->ik", a, b), [a, b])
    fs = [f for f in _run(bad).findings if f.rule == "TPL301"]
    assert len(fs) == 1 and "bfloat16" in fs[0].message

    good = _entry_of(
        lambda a, b: jnp.einsum("ij,jk->ik", a, b,
                                preferred_element_type=jnp.float32),
        [a, b])
    assert _run(good).findings == []


def test_tpl301_int8_dot_with_f32_accum_is_clean():
    # the quant_matmul XLA arm shape: int8 operand, fp32 accumulator,
    # epilogue dequant — raw provenance must flow through the dot
    entry = Q.build_quant_matmul_entry()
    interp = _run(entry)
    assert interp.findings == [], [f.message for f in interp.findings]
    assert "bfloat16|raw" in interp.all_fmts     # epilogue-dequant alg.


def test_kernel_decl_findings_pin_accum_dtypes(monkeypatch):
    findings, decls = Q.kernel_decl_findings()
    assert findings == [], [f.message for f in findings]
    assert set(decls) == set(Q.PALLAS_KERNEL_MODULES)
    assert set(decls.values()) == {"float32"}
    # a kernel silently dropping to bf16 accumulation is a finding
    import importlib

    mod = importlib.import_module(Q.PALLAS_KERNEL_MODULES[0])
    monkeypatch.setattr(mod, "ACCUM_DTYPE", "bfloat16")
    findings, decls = Q.kernel_decl_findings()
    assert len(findings) == 1 and findings[0].rule == "TPL301"
    assert "bfloat16" in findings[0].message


def test_site_accum_findings():
    from paddle_tpu.compiler.fusion_pass import Site

    def site(applied, accum):
        return Site(template="fx_tmpl", consumed=frozenset(), trigger=0,
                    inputs=(), out_binds=(), build=None, applied=applied,
                    accum_dtype=accum)

    fs = Q.site_accum_findings("fx_entry", [
        site(True, "bfloat16"), site(True, "float32"),
        site(False, "bfloat16")])              # unapplied sites exempt
    assert len(fs) == 1 and fs[0].rule == "TPL301"
    assert "fx_tmpl" in fs[0].message and "fx_entry" in fs[0].message


# -- TPL302: silent x64 drift ------------------------------------------------

def test_tpl302_fires_on_upcast_point_and_f64_invar():
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        up = _entry_of(lambda x: x.astype(jnp.float64) * 2.0,
                       [jax.ShapeDtypeStruct((4,), jnp.float32)])
        inv = _entry_of(lambda x: x + 1.0,
                        [jax.ShapeDtypeStruct((4,), jnp.float64)])
    fs = _run(up).findings
    assert rules_of(fs) == ["TPL302"]
    assert len(fs) == 1                       # upcast POINT, not spread
    assert "upcast" in fs[0].message
    inv_fs = _run(inv).findings
    assert any("operand 'a0' is float64" in f.message for f in inv_fs)


# -- TPL300: format legality (the fp8 on-ramp) -------------------------------

def test_tpl300_unknown_format_reported_until_declared(monkeypatch):
    import jax
    import jax.numpy as jnp

    f8 = getattr(jnp, "float8_e4m3fn", None)
    if f8 is None:
        pytest.skip("no float8 dtype in this jax build")
    x = jax.ShapeDtypeStruct((4, 8), f8)
    entry = _entry_of(lambda x: x + x, [x])
    fs = _run(entry).findings
    assert rules_of(fs) == ["TPL300"]
    assert "float8_e4m3fn" in fs[0].message
    assert "KNOWN_FORMATS" in fs[0].message
    # declaring the format clears the unknown-format finding...
    monkeypatch.setattr(Q, "KNOWN_FORMATS",
                        Q.KNOWN_FORMATS | {"float8_e4m3fn"})
    assert _run(entry).findings == []
    # ...but a dot still needs a legality row (and fp32 accumulation)
    w = jax.ShapeDtypeStruct((8, 4), f8)
    dot = _entry_of(
        lambda x, w: jnp.einsum("ij,jk->ik", x, w,
                                preferred_element_type=jnp.float32),
        [x, w])
    fs = _run(dot).findings
    assert rules_of(fs) == ["TPL300"]
    assert "op class 'dot'" in fs[0].message
    # the full on-ramp: legality row declared -> clean
    legal = dict(Q.FORMAT_LEGALITY)
    legal[(Q.BACKEND, "dot")] = \
        legal[(Q.BACKEND, "dot")] | {"float8_e4m3fn"}
    monkeypatch.setattr(Q, "FORMAT_LEGALITY", legal)
    assert _run(dot).findings == []


def test_tpl300_current_entries_use_only_known_formats():
    for entry in (Q.build_wire_entries()
                  + [Q.build_allreduce_entry(),
                     Q.build_quant_matmul_entry()]):
        fs = [f for f in _run(entry).findings if f.rule == "TPL300"]
        assert fs == [], (entry.name, [f.message for f in fs])


# -- the registered entries --------------------------------------------------

def test_serving_int8_entry_is_clean_with_full_lattice():
    interp = _run(Q.build_serving_int8_entry())
    assert interp.findings == [], [f.message for f in interp.findings]
    # the whole ladder is exercised: running-absmax scales, the rescale
    # ratio, raw views and in-flight quantizations
    for needed in ("int8|quant", "float32|scale", "float32|ratio",
                   "float32|raw", "float32|qpend",
                   "float32|scale|clamped"):
        assert needed in interp.all_fmts, sorted(interp.all_fmts)


def test_allreduce_entry_is_clean():
    # both quantize phases clamp, the reduction is fp32 (dequant before
    # accumulate), each chunk dequantizes against its own absmax event
    interp = _run(Q.build_allreduce_entry())
    assert interp.findings == [], [f.message for f in interp.findings]
    assert "float32|scale|clamped" in interp.all_fmts


def test_train_entry_tpl301_is_explained():
    interp = _run(Q.build_train_entry())
    fs = interp.findings
    assert rules_of(fs) == ["TPL301"]         # the documented bf16 dots
    assert Q.unexplained_findings(fs) == []


# -- golden format environment -----------------------------------------------

def test_golden_int8_format_environment():
    env = Q.format_environment(Q.build_serving_int8_entry())
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert env == golden, (
        "derived format environment drifted from the golden; if the "
        "quantization change is intentional, regenerate tests/data/"
        "quantcheck_int8_env.json (recipe in this file's docstring)")


# -- explained/baseline machinery --------------------------------------------

def _mk(entry, rule):
    return Finding(rule=rule, name="x", severity="error", path="p.py",
                   line=1, col=0, message=f"[entry {entry}] synthetic")


def test_unexplained_and_stale_filtering(monkeypatch):
    monkeypatch.setattr(Q, "EXPLAINED", {("e1", "TPL303"): "known"})
    known = _mk("e1", "TPL303")
    novel = _mk("e1", "TPL304")
    assert Q.unexplained_findings([known, novel]) == [novel]
    assert Q.stale_explanations([known]) == []
    stale = Q.stale_explanations([novel])
    assert len(stale) == 1 and "TPL303" in stale[0]
    assert "quantcheck.EXPLAINED" in stale[0]


def test_diff_baselines_reports_drift():
    cur = {"entries": {"a": {"source": "s.py", "n_eqns": 5,
                             "formats": ["float32|data"], "findings": {},
                             "fmt_digest": "x"},
                       "c": {"source": "s.py", "n_eqns": 1, "formats": [],
                             "findings": {}, "fmt_digest": "z"}},
           "kernel_accum": {"m": "float32"},
           "explained": [["a", "TPL301"]]}
    base = {"entries": {"a": {"source": "s.py", "n_eqns": 7,
                              "formats": ["float32|data"], "findings": {},
                              "fmt_digest": "y"},
                        "b": {"source": "s.py", "n_eqns": 1, "formats": [],
                              "findings": {}, "fmt_digest": "w"}},
            "kernel_accum": {"m": "bfloat16"},
            "explained": []}
    lines = "\n".join(Q.diff_baselines(cur, base))
    assert "entry 'a': n_eqns drifted" in lines
    assert "entry 'a': fmt_digest drifted" in lines
    assert "entry 'b': removed" in lines
    assert "entry 'c': new" in lines
    assert "kernel_accum drifted" in lines
    assert "explained set drifted" in lines
    assert Q.diff_baselines(cur, json.loads(json.dumps(cur))) == []


# -- CLI wiring: select/ignore filtering, SARIF, usage errors ----------------

def _canned_report(findings):
    return {"findings": findings,
            "baseline": {"version": 1, "entries": {}, "kernel_accum": {},
                         "explained": []}}


def test_run_quantcheck_select_ignore_filtering(monkeypatch, capsys):
    from tools.lint import cli

    findings = [_mk("e", "TPL303"), _mk("e", "TPL304")]
    monkeypatch.setattr(Q, "build_report",
                        lambda names=None: _canned_report(findings))
    monkeypatch.setattr(Q, "EXPLAINED", {})
    # select narrows what is REPORTED (rule id or slug)...
    rc = cli.run_quantcheck(None, False, "json", select={"TPL303"})
    out = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in out["unexplained"]] == ["TPL303"]
    assert rc == 1
    # ...ignore then drops from the selection
    rc = cli.run_quantcheck(None, False, "json",
                            ignore={"TPL303", "TPL304"})
    out = json.loads(capsys.readouterr().out)
    assert out["unexplained"] == [] and rc == 0


def test_run_quantcheck_sarif_rule_id_roundtrip(monkeypatch, capsys):
    from tools.lint import cli

    findings = [_mk("e", "TPL303"), _mk("e", "TPL301")]
    monkeypatch.setattr(Q, "build_report",
                        lambda names=None: _canned_report(findings))
    monkeypatch.setattr(Q, "EXPLAINED", {})
    assert cli.run_quantcheck(None, False, "sarif") == 1
    sarif = json.loads(capsys.readouterr().out)
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "tpu-quantcheck"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    result_ids = {r["ruleId"] for r in run["results"]}
    assert rule_ids == result_ids == {"TPL301", "TPL303"}


def test_cli_usage_errors():
    from tools.lint.cli import main

    assert main(["--quantcheck", "--shardcheck"]) == 2
    assert main(["--quantcheck", "--contracts"]) == 2
    assert main(["--quantcheck-regression", "--quantcheck"]) == 2
    assert main(["--quantcheck-regression", "--baseline", "x.json"]) == 2
    assert main(["--quantcheck", "--write-baseline"]) == 2


def test_run_quantcheck_missing_baseline_is_exit_3(tmp_path):
    from tools.lint import cli

    rc = cli.run_quantcheck(str(tmp_path / "missing.json"), False)
    assert rc == 3


# -- the full report on the current tree -------------------------------------

@pytest.mark.smoke
def test_build_report_current_tree_is_clean_and_current():
    report = Q.build_report()
    findings = report["findings"]
    assert Q.unexplained_findings(findings) == \
        [], [f.message for f in Q.unexplained_findings(findings)]
    assert Q.stale_explanations(findings) == []
    names = set(report["baseline"]["entries"])
    assert names == {"train_dp2_pp2_mp2", "serving_unified_fp32",
                     "serving_unified_int8kv", "serving_unified_mla_moe",
                     "wire_stage_int8",
                     "wire_commit_int8", "quant_allreduce_dp2pp2",
                     "quant_matmul_decode", "serving_admit_quant"}
    # ... and the committed baseline matches the tree (currency: a PR
    # that changes quantization must regenerate artifacts/quantcheck.json)
    base = Q.load_baseline(os.path.join(REPO, "artifacts",
                                        "quantcheck.json"))
    drift = Q.diff_baselines(report["baseline"], base)
    assert drift == [], "\n".join(drift)
