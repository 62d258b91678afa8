"""tpu-shardcheck: whole-program static sharding & collective verifier.

The dynamic layers (contracts.py, the multichip smoke) observe sharding
properties by *running* programs; the involuntary-remat guard in
``__graft_entry__.py`` was, until this module, an FD-level grep of the
C++ SPMD partitioner's glog output.  shardcheck proves the same
properties from the **jaxpr**, before any device executes anything:

1. every registered entry program (the dp×pp×mp train step, the unified
   RPA serving step, the disagg wire stage/commit kernels, the
   quantized all-reduce) is traced to a closed jaxpr,
2. an abstract interpreter propagates PartitionSpecs through every
   equation — recursing into scan/remat2/jit/shard_map/custom-vjp
   bodies exactly as ``compiler/fusion_pass.py`` recurses for fusion
   discovery,
3. four rule families fire on the propagated environment:

   TPL201 involuntary-reshard  a gather/dot whose *parameter* operand is
          sharded on a lookup/contraction dim and whose output is not
          pinned by a ``with_sharding_constraint`` — the exact shape of
          the MULTICHIP_r05 involuntary full rematerialization, reported
          at the offending eqn with the missing ``*_constraint`` named.
   TPL202 collective-partial-manual  a collective inside a shard_map
          region whose mesh has size>1 axes *outside* the manual set —
          the ``dist_allreduce_quant`` pp>1/mp>1 refusal (and the
          pipeline's partial-manual 1F1B region), flagged statically
          instead of at lowering time.
   TPL203 collective-order  two programs registered as interleavable
          (fleet wire commit vs. in-flight step) must issue their common
          collectives in a consistent global order or a cross-program
          deadlock is reachable.
   TPL204 vmem-overflow  a static roofline estimate per fusion-catalog
          Site (``fusion_pass.site_vmem_bytes``) against the ~16 MiB
          per-core VMEM budget — the seed of the cost-model scheduler.

Baseline/suppression semantics mirror ``contracts.py``: known findings
carry a rationale in :data:`EXPLAINED` (the JSON analog of a lint
suppression, keyed ``(entry, rule)``), everything else is drift-checked
against ``artifacts/shardcheck.json``.  Wired as ``python -m tools.lint
--shardcheck`` with the same exit codes (0 clean / 1 findings or drift /
2 usage / 3 missing baseline) and rendered by the existing reporters.
"""

from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass, field

from .core import Finding

__all__ = [
    "EntryProgram",
    "ShardInterp",
    "EXPLAINED",
    "VMEM_BUDGET_BYTES",
    "build_entries",
    "build_report",
    "check_entry",
    "diff_baselines",
    "load_baseline",
    "spec_environment",
    "unexplained_findings",
    "write_baseline",
]

# TPU v5e-class cores hold ~16 MiB of VMEM (pallas guide); a fused site
# whose double-buffered working set exceeds this cannot stay resident.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# Known findings with rationales — the contracts.EXPLAINED analog.  A
# finding keyed here is reported in the baseline but does not fail the
# run; an EXPLAINED key with no matching finding is itself drift (stale
# rationales must be pruned like stale suppressions).
EXPLAINED = {
    ("train_dp2_pp2_mp2", "TPL202"):
        "the 1F1B pipeline region is partial-manual by design (pp manual,"
        " dp/mp auto); jax.shard_map lowers it natively, shardcheck"
        " documents it here",
    ("quant_allreduce_dp2pp2", "TPL202"):
        "the known dist_allreduce_quant pp>1 refusal: train_step raises"
        " ValueError for this mesh before tracing; the entry exists so"
        " the refusal is proven static, not discovered at lowering",
}

# Collective primitives as they appear as jaxpr eqn names.
COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter",
}

# Primitives that pass sharding (and parameter-ness) through unchanged.
_TRANSPARENT = {
    "convert_element_type", "copy", "stop_gradient", "device_put",
    "optimization_barrier", "reduce_precision",
}


# ---------------------------------------------------------------------------
# spec domain
# ---------------------------------------------------------------------------
# A spec is a tuple over array dims; each entry is a frozenset of mesh
# axis names the dim is sharded over (empty = replicated on that dim).

def _nd(aval) -> int:
    return len(getattr(aval, "shape", ()) or ())


def _empty_spec(ndim: int) -> tuple:
    return (frozenset(),) * ndim


def _spec_from_partition(pspec, ndim: int) -> tuple:
    """PartitionSpec -> internal spec tuple (padded to ndim)."""
    out = []
    entries = tuple(pspec) if pspec is not None else ()
    for d in range(ndim):
        e = entries[d] if d < len(entries) else None
        if e is None:
            out.append(frozenset())
        elif isinstance(e, (tuple, list)):
            out.append(frozenset(x for x in e if x is not None))
        else:
            out.append(frozenset([e]))
    return tuple(out)


def _spec_str(spec) -> str:
    if spec is None:
        return "?"
    return "(" + ",".join(
        ("+".join(sorted(d)) if d else "-") for d in spec) + ")"


def _join_dim(a: frozenset, b: frozenset) -> frozenset:
    """Join two per-dim assignments: agreement wins, else first
    non-empty (a conflict means the partitioner will reshard — the
    propagation tracks the dominant layout)."""
    if a == b:
        return a
    return a if a else b


def _join_spec(a, b):
    if a is None:
        return b
    if b is None:
        return a
    if len(a) != len(b):
        return a
    return tuple(_join_dim(x, y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# entry programs
# ---------------------------------------------------------------------------

@dataclass
class EntryProgram:
    """One registered program: a closed jaxpr plus the sharding facts
    the tracer cannot recover from the jaxpr alone."""

    name: str
    closed: object                        # jax ClosedJaxpr
    mesh_axes: dict                       # axis name -> size
    in_specs: list                        # spec tuple per invar
    source: str                           # repo path the program comes from
    invar_names: list = field(default_factory=list)
    interleave: str | None = None         # TPL203 group
    param_invars: set = field(default_factory=set)  # invar indices that
    #                                      are weights (TPL201 operands)


def _jax():
    """Import jax late, forcing an 8-device virtual CPU platform when
    this process has not initialized a backend yet (the CLI path; under
    pytest the conftest already did this)."""
    if "jax" not in os.sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    return jax


def _need_devices(n: int):
    jax = _jax()
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"shardcheck needs {n} devices to build meshes but the "
            f"already-initialized backend has {len(devs)}; run in a "
            "fresh process (python -m tools.lint --shardcheck) or set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return devs


def _tiny_gpt_cfg():
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=128, hidden=16, n_layers=2, n_heads=2,
                     seq_len=16)


def _flatten_names(tree) -> list:
    jax = _jax()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [jax.tree_util.keystr(path) for path, _ in leaves]


def build_train_entry(name: str = "train_dp2_pp2_mp2",
                      mesh_shape=(("dp", 2), ("pp", 2), ("mp", 2)),
                      emb_pin: bool = True,
                      batch: int = 8) -> EntryProgram:
    """Trace the sharded train step (parallel/train_step.py) to a jaxpr
    under ``abstract=True`` — no weights materialize.  ``emb_pin=False``
    rebuilds the PR 9 *pre-fix* program (embedding gather with the
    ``emb_constraint`` hook disabled) for the TPL201 regression."""
    import numpy as np

    jax = _jax()
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.train_step import make_sharded_train_step

    axes = [a for a, _ in mesh_shape]
    sizes = [s for _, s in mesh_shape]
    n_dev = int(np.prod(sizes))
    devs = _need_devices(n_dev)[:n_dev]
    mesh = Mesh(np.asarray(devs).reshape(sizes), axes)
    cfg = _tiny_gpt_cfg()
    step_fn, params, opt_state = make_sharded_train_step(
        cfg, mesh, abstract=True, _emb_pin=emb_pin)
    dp = dict(mesh_shape).get("dp", 1)
    tok = jax.ShapeDtypeStruct(
        (batch, cfg.seq_len), np.int32,
        sharding=NamedSharding(mesh, P("dp" if dp > 1 else None)))
    with jax.sharding.set_mesh(mesh):
        closed = jax.make_jaxpr(step_fn.jitted)(params, opt_state, tok, tok)
    leaves = (jax.tree_util.tree_leaves(params)
              + jax.tree_util.tree_leaves(opt_state) + [tok, tok])
    names = (["params" + n for n in _flatten_names(params)]
             + ["opt" + n for n in _flatten_names(opt_state)]
             + ["tokens", "labels"])
    in_specs = []
    for leaf in leaves:
        sh = getattr(leaf, "sharding", None)
        spec = getattr(sh, "spec", None)
        in_specs.append(_spec_from_partition(spec, _nd(leaf)))
    n_params = len(jax.tree_util.tree_leaves(params))
    return EntryProgram(
        name=name, closed=closed, mesh_axes=dict(mesh_shape),
        in_specs=in_specs, invar_names=names,
        source="paddle_tpu/parallel/train_step.py",
        param_invars=set(range(n_params)))


def _tiny_engine():
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import LlamaConfig, ServingEngine

    cfg = LlamaConfig(vocab_size=128, hidden=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, ffn_hidden=64, max_seq_len=64,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    return ServingEngine(cfg, max_batch=2, page_size=8, max_seq=64,
                         n_pages=1 + 8)


def build_serving_entries() -> list:
    """The unified RPA serving step plus the disagg wire stage/commit
    kernels, traced from one tiny single-device engine.  All three share
    the TPL203 interleave group: the wire runs between (stage) and
    before (commit) in-flight unified steps."""
    jax = _jax()
    import numpy as np

    from paddle_tpu.inference.serving import (wire_gather_pages,
                                              wire_scatter_pages)

    eng = _tiny_engine()
    unified = eng.trace_unified()
    out = [EntryProgram(
        name="serving_unified", closed=unified, mesh_axes={},
        in_specs=[_empty_spec(_nd(v.aval)) for v in unified.jaxpr.invars],
        source="paddle_tpu/inference/serving.py",
        interleave="serving-wire",
        param_invars=set(range(len(jax.tree_util.tree_leaves(eng.params)))))]
    kp = eng.k_pages
    n_ship = 2
    pg = jax.ShapeDtypeStruct((n_ship,), np.int32)
    staged = jax.ShapeDtypeStruct(
        (kp.shape[0], n_ship) + kp.shape[2:], kp.dtype)
    gather = jax.make_jaxpr(wire_gather_pages)(
        jax.ShapeDtypeStruct(kp.shape, kp.dtype), pg)
    scatter = jax.make_jaxpr(wire_scatter_pages)(
        jax.ShapeDtypeStruct(kp.shape, kp.dtype), pg, staged)
    for nm, closed in (("wire_stage", gather), ("wire_commit", scatter)):
        out.append(EntryProgram(
            name=nm, closed=closed, mesh_axes={},
            in_specs=[_empty_spec(_nd(v.aval))
                      for v in closed.jaxpr.invars],
            source="paddle_tpu/inference/serving.py",
            interleave="serving-wire"))
    return out


def build_serving_mla_moe_entry() -> EntryProgram:
    """The unified step over latent (MLA) pages with routed experts, on
    one device: quantcheck's trace of it serves both verifiers."""
    from .quantcheck import build_serving_mla_moe_entry as _qc_mla

    mla = _qc_mla()
    return EntryProgram(
        name=mla.name, closed=mla.closed, mesh_axes={},
        in_specs=[_empty_spec(_nd(v.aval))
                  for v in mla.closed.jaxpr.invars],
        source=mla.source)


def build_quant_entry(name: str = "quant_allreduce_dp2pp2",
                      mesh_shape=(("dp", 2), ("pp", 2))) -> EntryProgram:
    """The quantized all-reduce (distributed/autograd_collectives.py)
    inside a dp-manual shard_map over a mesh with a second size>1 axis —
    exactly the partial-manual combination ``make_sharded_train_step``
    refuses with a ValueError.  Traced directly (the guard never runs),
    so TPL202 proves the refusal without executing any lowering."""
    import numpy as np

    jax = _jax()
    import paddle_tpu  # noqa: F401
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.autograd_collectives import (
        dist_allreduce_quant)

    axes = [a for a, _ in mesh_shape]
    sizes = [s for _, s in mesh_shape]
    n_dev = int(np.prod(sizes))
    devs = _need_devices(n_dev)[:n_dev]
    mesh = Mesh(np.asarray(devs).reshape(sizes), axes)
    dp = dict(mesh_shape)["dp"]

    def body(g):
        return dist_allreduce_quant(g, "dp", mean=True, axis_size=dp)

    manual = {"dp"} | {a for a, s in mesh_shape if s == 1}
    run = jax.shard_map(body, in_specs=P("dp"), out_specs=P("dp"),
                        axis_names=manual, check_vma=False)
    g = jax.ShapeDtypeStruct((64, 16), np.float32)
    with jax.sharding.set_mesh(mesh):
        closed = jax.make_jaxpr(run)(g)
    return EntryProgram(
        name=name, closed=closed, mesh_axes=dict(mesh_shape),
        in_specs=[_spec_from_partition(P("dp"), 2)],
        source="paddle_tpu/distributed/autograd_collectives.py")


def build_entries(names=None) -> list:
    """All registered entry programs (optionally filtered by name)."""
    entries = [build_train_entry()]
    entries += build_serving_entries()
    entries.append(build_serving_mla_moe_entry())
    entries.append(build_quant_entry())
    if names is not None:
        entries = [e for e in entries if e.name in set(names)]
    return entries


# ---------------------------------------------------------------------------
# the abstract interpreter
# ---------------------------------------------------------------------------

def _eqn_location(eqn):
    """(repo-relative path, line) of the user frame that created the
    eqn; (None, 0) when the traceback holds no user frame."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, 0
    fname = frame.file_name
    for anchor in ("paddle_tpu/", "tools/", "tests/"):
        i = fname.find(anchor)
        if i >= 0:
            return fname[i:], int(frame.start_line)
    return fname, int(frame.start_line)


def _inner_closed(eqn):
    """[(closed-or-open jaxpr, consts)] bodies of a higher-order eqn —
    the fusion_pass._sub_jaxpr recursion generalized to every body the
    spec propagation must enter."""
    p = eqn.params
    name = eqn.primitive.name
    out = []
    if name == "scan" or name == "jit":
        c = p["jaxpr"]
        out.append((c.jaxpr, c.consts))
    elif name == "remat2":
        out.append((p["jaxpr"], []))                 # an open jaxpr
    elif name in ("custom_jvp_call", "custom_vjp_call"):
        c = p["call_jaxpr"]
        out.append((c.jaxpr, c.consts))
    elif name == "while":
        c = p["body_jaxpr"]
        out.append((c.jaxpr, c.consts))
    elif name == "cond":
        for c in p["branches"]:
            out.append((c.jaxpr, c.consts))
    elif name == "shard_map":
        out.append((p["jaxpr"], []))                 # an open jaxpr
    return out


def _axes_of(eqn) -> tuple:
    """Mesh axis names a collective eqn communicates over."""
    p = eqn.params
    raw = p.get("axes", p.get("axis_name", ()))
    if raw is None:
        raw = ()
    if isinstance(raw, (str,)):
        raw = (raw,)
    out = []
    for a in raw:
        if isinstance(a, str):
            out.append(a)
    return tuple(sorted(out))


@dataclass
class _Region:
    """Ambient shard_map context while interpreting a body."""

    mesh_axes: dict                 # full mesh at this point
    manual: frozenset = frozenset()


class ShardInterp:
    """Propagates specs through one entry program and collects rule
    events.  One instance per entry; findings accumulate on
    ``self.findings`` and the full var environment (for the golden
    spec-environment test) on ``self.all_specs``."""

    def __init__(self, entry: EntryProgram):
        self.entry = entry
        self.findings: list[Finding] = []
        self.collective_events: list[tuple] = []   # (prim, axes, path, line)
        self.all_specs: dict[str, int] = {}        # spec str -> count
        self.out_specs: list = []

    # -- env helpers --------------------------------------------------------

    @staticmethod
    def _read(env, atom):
        if type(atom).__name__ == "Literal":
            return _empty_spec(_nd(atom.aval)), False
        return env.get(atom, (_empty_spec(_nd(atom.aval)), False))

    def _record(self, spec):
        self.all_specs[_spec_str(spec)] = \
            self.all_specs.get(_spec_str(spec), 0) + 1

    def _finding(self, rule, name, eqn, message, severity="error"):
        path, line = _eqn_location(eqn)
        self.findings.append(Finding(
            rule=rule, name=name, severity=severity,
            path=path or self.entry.source, line=line or 1, col=0,
            message=f"[entry {self.entry.name}] {message}"))

    # -- driver -------------------------------------------------------------

    def run(self):
        closed = self.entry.closed
        jaxpr = closed.jaxpr
        env = {}
        for cv in jaxpr.constvars:
            env[cv] = (_empty_spec(_nd(cv.aval)), False)
        params = self.entry.param_invars
        for i, v in enumerate(jaxpr.invars):
            spec = (self.entry.in_specs[i]
                    if i < len(self.entry.in_specs)
                    else _empty_spec(_nd(v.aval)))
            env[v] = (spec, i in params)
        region = _Region(mesh_axes=dict(self.entry.mesh_axes))
        self._interp(jaxpr, env, region)
        self.out_specs = [self._read(env, v)[0] for v in jaxpr.outvars]
        return self

    # -- interpretation -----------------------------------------------------

    def _interp(self, jaxpr, env, region):
        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            ins = [self._read(env, a) for a in eqn.invars]
            if name == "jit":
                outs = self._do_jit(eqn, ins, region)
            elif name == "scan":
                outs = self._do_scan(eqn, ins, region)
            elif name == "shard_map":
                outs = self._do_shard_map(eqn, ins, region)
            elif name in ("remat2", "custom_jvp_call", "custom_vjp_call",
                          "while", "cond"):
                outs = self._do_opaque_body(eqn, ins, region)
            else:
                if name in COLLECTIVE_PRIMS:
                    self._on_collective(eqn, region)
                if name == "gather":
                    self._check_gather(jaxpr, i, eqn, ins)
                if name == "dot_general":
                    self._check_dot(jaxpr, i, eqn, ins)
                outs = _propagate(eqn, ins)
            for v, o in zip(eqn.outvars, outs):
                if type(v).__name__ == "DropVar":
                    continue
                env[v] = o
                self._record(o[0])

    # -- higher-order handlers ----------------------------------------------

    def _run_body(self, jaxpr, consts, in_states, region):
        env = {}
        for cv in jaxpr.constvars:
            env[cv] = (_empty_spec(_nd(cv.aval)), False)
        for v, st in zip(jaxpr.invars, in_states):
            env[v] = st
        self._interp(jaxpr, env, region)
        return [self._read(env, v) for v in jaxpr.outvars], env

    def _do_jit(self, eqn, ins, region):
        inner, consts = eqn.params["jaxpr"].jaxpr, eqn.params["jaxpr"].consts
        states = list(ins)
        for j, sh in enumerate(eqn.params.get("in_shardings", ()) or ()):
            spec = getattr(sh, "spec", None)
            if spec is not None and j < len(states):
                states[j] = (_spec_from_partition(
                    spec, _nd(inner.invars[j].aval)), states[j][1])
        n_consts = len(inner.constvars)
        del n_consts
        outs, _ = self._run_body(inner, consts, states, region)
        for j, sh in enumerate(eqn.params.get("out_shardings", ()) or ()):
            spec = getattr(sh, "spec", None)
            if spec is not None and j < len(outs):
                outs[j] = (_spec_from_partition(
                    spec, _nd(eqn.outvars[j].aval)), outs[j][1])
        return outs

    def _do_scan(self, eqn, ins, region):
        p = eqn.params
        inner = p["jaxpr"].jaxpr
        consts = p["jaxpr"].consts
        nc, ncarry = p["num_consts"], p["num_carry"]
        const_in = ins[:nc]
        carry_in = ins[nc:nc + ncarry]
        xs_in = ins[nc + ncarry:]
        # xs enter the body with the leading scan dim stripped
        xs_body = [((s[1:] if s else s), pf) for s, pf in xs_in]
        carry = list(carry_in)
        outs = None
        for _ in range(2):                     # carry fixpoint (2 sweeps)
            outs, _ = self._run_body(
                inner, consts, const_in + carry + xs_body, region)
            new_carry = outs[:ncarry]
            carry = [(_join_spec(a[0], b[0]), a[1] or b[1])
                     for a, b in zip(carry, new_carry)]
        ys = [((frozenset(),) + s if s is not None else s, pf)
              for s, pf in outs[ncarry:]]
        return carry + ys

    def _do_shard_map(self, eqn, ins, region):
        p = eqn.params
        mesh_axes = dict(p["mesh"].shape) or dict(region.mesh_axes)
        manual = frozenset(p["manual_axes"])
        inner_region = _Region(mesh_axes=mesh_axes,
                               manual=region.manual | manual)
        inner = p["jaxpr"]
        # inside the manual region the axes an in_spec names are local:
        # strip them
        states = []
        for (s, pf), pspec in zip(ins, p["in_specs"]):
            if s is not None:
                named = set().union(*_spec_from_partition(pspec, len(s)))
                s = tuple(d - named for d in s)
            states.append((s, pf))
        outs, _ = self._run_body(inner, [], states, inner_region)
        res = []
        for j, v in enumerate(eqn.outvars):
            nd = _nd(v.aval)
            s = outs[j][0]
            if s is None or len(s) != nd:
                s = _empty_spec(nd)
            named = _spec_from_partition(p["out_specs"][j], nd)
            res.append((tuple(a | b for a, b in zip(s, named)), False))
        return res

    def _do_opaque_body(self, eqn, ins, region):
        bodies = _inner_closed(eqn)
        if not bodies:
            return _propagate(eqn, ins)
        results = None
        for inner, consts in bodies:
            states = list(ins)
            n = len(inner.invars)
            if eqn.primitive.name == "cond":
                states = states[1:]            # predicate operand
            if len(states) > n:
                states = states[-n:]
            while len(states) < n:
                states.insert(0, (_empty_spec(0), False))
            outs, _ = self._run_body(inner, consts, states, region)
            if results is None:
                results = outs
            else:
                results = [(_join_spec(a[0], b[0]), a[1] or b[1])
                           for a, b in zip(results, outs)]
        n_out = len(eqn.outvars)
        results = (results or [])[:n_out]
        while len(results) < n_out:
            results.append((_empty_spec(_nd(eqn.outvars[len(results)].aval)),
                            False))
        return [(s if s is not None and len(s) == _nd(v.aval)
                 else _empty_spec(_nd(v.aval)), pf)
                for (s, pf), v in zip(results, eqn.outvars)]

    # -- rules --------------------------------------------------------------

    def _on_collective(self, eqn, region):
        axes = _axes_of(eqn)
        path, line = _eqn_location(eqn)
        self.collective_events.append(
            (eqn.primitive.name, axes, path, line))
        partial = sorted(
            a for a, size in region.mesh_axes.items()
            if size > 1 and a not in region.manual)
        if region.manual and partial:
            self._finding(
                "TPL202", "collective-partial-manual", eqn,
                f"collective '{eqn.primitive.name}' over axes "
                f"{list(axes)} sits in a partial-manual shard_map region "
                f"(manual={sorted(region.manual & set(region.mesh_axes))}, "
                f"auto size>1 axes={partial}); this lowering is refused "
                "at runtime — restrict the mesh to the manual axes or "
                "make every size>1 axis manual")

    @staticmethod
    def _is_pinned(jaxpr, idx, eqn):
        """The eqn's output is pinned when a sharding_constraint consumes
        it within two transparent hops — the ``*_constraint`` idiom."""
        uses: dict = {}
        for j, e in enumerate(jaxpr.eqns):
            for a in e.invars:
                if type(a).__name__ != "Literal":
                    uses.setdefault(a, []).append(j)
        frontier = [v for v in eqn.outvars]
        for _ in range(3):
            nxt = []
            for v in frontier:
                for j in uses.get(v, []):
                    e = jaxpr.eqns[j]
                    if e.primitive.name == "sharding_constraint":
                        return True
                    if e.primitive.name in _TRANSPARENT:
                        nxt.extend(e.outvars)
            frontier = nxt
            if not frontier:
                break
        return False

    def _check_gather(self, jaxpr, idx, eqn, ins):
        (op_spec, op_param) = ins[0]
        if not op_param or op_spec is None:
            return
        dims = eqn.params.get("dimension_numbers")
        slice_sizes = eqn.params.get("slice_sizes", ())
        op_shape = getattr(eqn.invars[0].aval, "shape", ())
        lookup = set(getattr(dims, "start_index_map", ()) or ())
        hot = sorted(
            d for d in lookup
            if d < len(op_spec) and op_spec[d]
            and d < len(slice_sizes) and d < len(op_shape)
            and slice_sizes[d] < op_shape[d])
        if not hot:
            return
        if self._is_pinned(jaxpr, idx, eqn):
            return
        axes = sorted(a for d in hot for a in op_spec[d])
        self._finding(
            "TPL201", "involuntary-reshard", eqn,
            f"gather over a parameter sharded {_spec_str(op_spec)} on its "
            f"lookup dim(s) {hot} (axes {axes}) has no "
            "with_sharding_constraint pin on its output — GSPMD will "
            "invent an intermediate layout and reshard it, the "
            "involuntary full-rematerialization; pin the output via the "
            "*_constraint hook at the gather (see "
            "train_step.emb_constraint)")

    def _check_dot(self, jaxpr, idx, eqn, ins):
        (l_spec, l_param) = ins[0]
        (r_spec, r_param) = ins[1]
        if l_spec is None or r_spec is None:
            return
        dims = eqn.params.get("dimension_numbers")
        try:
            (lc, rc), _ = dims
        except Exception:
            return
        for dl, dr in zip(lc, rc):
            if dl >= len(l_spec) or dr >= len(r_spec):
                continue
            a, b = l_spec[dl], r_spec[dr]
            if a and b and a != b and (l_param or r_param):
                if self._is_pinned(jaxpr, idx, eqn):
                    continue
                self._finding(
                    "TPL201", "involuntary-reshard", eqn,
                    f"dot contracting dim {dl}x{dr} is sharded "
                    f"{sorted(a)} on the left but {sorted(b)} on the "
                    "right with a parameter operand and no constraint "
                    "pin — meeting the consumer forces a full-replica "
                    "materialization of the parameter; pin one side with "
                    "with_sharding_constraint")


# default propagation --------------------------------------------------------

def _propagate(eqn, ins):
    """Per-primitive spec transfer for first-order eqns."""
    name = eqn.primitive.name
    outs = eqn.outvars
    p = eqn.params

    def mk(spec, pf=False):
        return [(spec if spec is not None and len(spec) == _nd(v.aval)
                 else _empty_spec(_nd(v.aval)), pf) for v in outs]

    if not ins:
        return mk(None)
    (s0, pf0) = ins[0]
    if name in _TRANSPARENT:
        return mk(s0, pf0)
    if name == "sharding_constraint":
        sh = p.get("sharding")
        spec = getattr(sh, "spec", None)
        if spec is not None:
            return mk(_spec_from_partition(spec, _nd(outs[0].aval)))
        return mk(s0)
    if name == "transpose":
        perm = p.get("permutation", ())
        if s0 is not None and len(perm) == len(s0):
            return mk(tuple(s0[d] for d in perm))
        return mk(None)
    if name == "broadcast_in_dim":
        bdims = p.get("broadcast_dimensions", ())
        nd = _nd(outs[0].aval)
        spec = [frozenset()] * nd
        if s0 is not None:
            for src, dst in enumerate(bdims):
                if src < len(s0) and dst < nd:
                    spec[dst] = s0[src]
        return mk(tuple(spec))
    if name in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                "reduce_and", "reduce_or", "argmax", "argmin"):
        axes = set(p.get("axes", ()))
        if s0 is not None:
            return mk(tuple(d for i, d in enumerate(s0) if i not in axes))
        return mk(None)
    if name == "squeeze":
        dims = set(p.get("dimensions", ()))
        if s0 is not None:
            return mk(tuple(d for i, d in enumerate(s0) if i not in dims))
        return mk(None)
    if name == "expand_dims":
        dims = set(p.get("dimensions", ()))
        if s0 is not None:
            spec, j = [], 0
            for i in range(_nd(outs[0].aval)):
                if i in dims:
                    spec.append(frozenset())
                elif j < len(s0):
                    spec.append(s0[j])
                    j += 1
                else:
                    spec.append(frozenset())
            return mk(tuple(spec))
        return mk(None)
    if name == "reshape":
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        out_shape = getattr(outs[0].aval, "shape", ())
        if s0 is not None and tuple(in_shape) == tuple(out_shape):
            return mk(s0)
        # size-1 insertion/removal: map surviving dims in order
        if s0 is not None:
            in_nz = [(i, d) for i, d in enumerate(in_shape) if d != 1]
            out_nz = [i for i, d in enumerate(out_shape) if d != 1]
            if (len(in_nz) == len(out_nz)
                    and [d for _, d in in_nz]
                    == [out_shape[i] for i in out_nz]):
                spec = [frozenset()] * len(out_shape)
                for (src, _), dst in zip(in_nz, out_nz):
                    spec[dst] = s0[src]
                return mk(tuple(spec))
        return mk(None)
    if name == "dot_general":
        (l, _), (r, _) = ins[0], ins[1]
        try:
            (lc, rc), (lb, rb) = p["dimension_numbers"]
        except Exception:
            return mk(None)
        if l is None or r is None:
            return mk(None)
        lf = [d for d in range(len(l)) if d not in set(lc) | set(lb)]
        rf = [d for d in range(len(r)) if d not in set(rc) | set(rb)]
        spec = tuple([l[d] for d in lb] + [l[d] for d in lf]
                     + [r[d] for d in rf])
        seen: set = set()
        clean = []
        for d in spec:
            keep = d - seen
            seen |= keep
            clean.append(keep)
        return mk(tuple(clean))
    if name == "gather":
        # output batch dims follow the indices; slice dims follow the
        # operand's offset dims (replicated lookup dims collapse away)
        s_idx = ins[1][0] if len(ins) > 1 else None
        dims = p.get("dimension_numbers")
        nd = _nd(outs[0].aval)
        offset = list(getattr(dims, "offset_dims", ()) or ())
        spec = [frozenset()] * nd
        if s_idx is not None:
            bi = 0
            for i in range(nd):
                if i not in offset and bi < max(len(s_idx) - 1, 0):
                    spec[i] = s_idx[bi]
                    bi += 1
        if s0 is not None:
            collapsed = set(getattr(dims, "collapsed_slice_dims", ())
                            or ())
            op_dims = [d for d in range(len(s0)) if d not in collapsed]
            for od, d in zip(offset, op_dims):
                if od < nd:
                    spec[od] = s0[d]
        return mk(tuple(spec))
    if name in ("scatter", "scatter-add", "scatter_add", "scatter_mul",
                "scatter_min", "scatter_max", "dynamic_update_slice"):
        return mk(s0, pf0)
    if name in ("dynamic_slice", "slice", "rev", "pad", "cumsum",
                "cumlogsumexp", "cummax", "cummin", "cumprod", "sort",
                "clamp", "select_and_scatter_add"):
        return mk(s0)
    if name == "concatenate":
        spec = None
        for s, _ in ins:
            spec = _join_spec(spec, s)
        if spec is not None:
            dim = p.get("dimension", 0)
            spec = tuple(frozenset() if i == dim else d
                         for i, d in enumerate(spec))
        return mk(spec)
    if name in COLLECTIVE_PRIMS:
        return mk(s0)
    if name == "iota":
        return mk(None)
    # default: positional join over same-rank inputs (elementwise family)
    nd = _nd(outs[0].aval)
    spec = None
    for s, _ in ins:
        if s is not None and len(s) == nd:
            spec = _join_spec(spec, s)
    return mk(spec)


# ---------------------------------------------------------------------------
# cross-program + fusion-site rules
# ---------------------------------------------------------------------------

def ordering_findings(events_by_entry: dict,
                      groups: dict) -> list:
    """TPL203: for every interleave group, every pair of programs must
    issue their *common* collectives (same primitive + axes) in the same
    relative order.  ``events_by_entry`` maps entry name -> ordered
    [(prim, axes, path, line)]; ``groups`` maps entry name -> group."""
    findings = []
    by_group: dict = {}
    for name, grp in groups.items():
        if grp:
            by_group.setdefault(grp, []).append(name)
    for grp, members in sorted(by_group.items()):
        for i in range(len(members)):
            for j in range(i + 1, len(members)):
                a, b = members[i], members[j]
                ea = [(p, ax) for p, ax, *_ in events_by_entry.get(a, [])]
                eb = [(p, ax) for p, ax, *_ in events_by_entry.get(b, [])]
                common = [k for k in dict.fromkeys(ea) if k in set(eb)]
                if len(common) < 2:
                    continue
                order_a = [k for k in dict.fromkeys(ea) if k in common]
                order_b = [k for k in dict.fromkeys(eb) if k in common]
                if order_a != order_b:
                    findings.append(Finding(
                        rule="TPL203", name="collective-order",
                        severity="error", path="tools/lint/shardcheck.py",
                        line=1, col=0,
                        message=(f"[entry {a}] interleavable programs "
                                 f"'{a}' and '{b}' (group {grp}) issue "
                                 f"common collectives in conflicting "
                                 f"order: {order_a} vs {order_b} — a "
                                 "cross-program deadlock is reachable; "
                                 "align the issue order")))
    return findings


def vmem_findings(entry_name: str, sites,
                  budget: int = VMEM_BUDGET_BYTES) -> list:
    """TPL204: static VMEM roofline per applied fusion Site."""
    from paddle_tpu.compiler.fusion_pass import site_vmem_bytes

    out = []
    for s in sites:
        if not getattr(s, "applied", False):
            continue
        est = site_vmem_bytes(s)
        if est > budget:
            out.append(Finding(
                rule="TPL204", name="vmem-overflow", severity="error",
                path="paddle_tpu/compiler/catalog.py", line=1, col=0,
                message=(f"[entry {entry_name}] fusion site "
                         f"'{s.template}' has an estimated double-"
                         f"buffered working set of {est} bytes "
                         f"(> {budget} VMEM budget); the fused kernel "
                         "cannot stay resident — shrink the block or "
                         "leave the site unfused")))
    return out


# ---------------------------------------------------------------------------
# report / baseline
# ---------------------------------------------------------------------------

def check_entry(entry: EntryProgram) -> tuple:
    """(interp, findings) for one entry: propagation rules plus the
    per-entry TPL204 fusion-site roofline."""
    interp = ShardInterp(entry).run()
    findings = list(interp.findings)
    try:
        from paddle_tpu.compiler.fusion_pass import plan_closed

        plan = plan_closed(entry.closed)
        findings += vmem_findings(entry.name, plan.walk())
    except Exception as e:  # pragma: no cover - fusion planning is
        # best-effort here; a planner bug must not kill the verifier
        findings.append(Finding(
            rule="TPL204", name="vmem-overflow", severity="warning",
            path=entry.source, line=1, col=0,
            message=f"[entry {entry.name}] fusion planning failed: "
                    f"{type(e).__name__}: {e}"))
    return interp, findings


def spec_environment(entry: EntryProgram) -> dict:
    """Deterministic summary of the full derived spec environment: the
    golden test pins this for the dp4×mp2 step."""
    interp = ShardInterp(entry).run()
    invars = {}
    for name, spec in zip(entry.invar_names, entry.in_specs):
        invars[name] = _spec_str(spec)
    return {
        "entry": entry.name,
        "mesh": dict(entry.mesh_axes),
        "invars": invars,
        "outvars": [_spec_str(s) for s in interp.out_specs],
        "spec_histogram": dict(sorted(interp.all_specs.items())),
    }


def _entry_digest(interp: ShardInterp) -> str:
    blob = json.dumps(
        {"specs": dict(sorted(interp.all_specs.items())),
         "outs": [_spec_str(s) for s in interp.out_specs]},
        sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def build_report(names=None) -> dict:
    """Run every registered entry; returns findings + the baseline
    payload."""
    entries = build_entries(names)
    findings: list[Finding] = []
    payload = {"version": 1, "entries": {}}
    events: dict = {}
    groups: dict = {}
    for entry in entries:
        interp, fs = check_entry(entry)
        findings += fs
        events[entry.name] = interp.collective_events
        groups[entry.name] = entry.interleave
        counts: dict = {}
        for f in fs:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        payload["entries"][entry.name] = {
            "source": entry.source,
            "mesh": dict(entry.mesh_axes),
            "n_eqns": _count_eqns(entry.closed.jaxpr),
            "collectives": [[p, list(ax)] for p, ax, *_ in
                            interp.collective_events],
            "findings": dict(sorted(counts.items())),
            "spec_digest": _entry_digest(interp),
        }
    order = ordering_findings(events, groups)
    findings += order
    for f in order:
        ent = f.message.split("]")[0].split()[-1]
        e = payload["entries"].get(ent)
        if e is not None:
            e["findings"]["TPL203"] = e["findings"].get("TPL203", 0) + 1
    payload["explained"] = sorted(
        [k, r] for (k, r) in EXPLAINED)
    return {"findings": findings, "baseline": payload}


def _count_eqns(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for inner, _ in _inner_closed(eqn):
            n += _count_eqns(inner)
    return n


def _finding_entry(f: Finding) -> str:
    msg = f.message
    if msg.startswith("[entry "):
        return msg[len("[entry "):].split("]")[0]
    return ""


def unexplained_findings(findings: list) -> list:
    return [f for f in findings
            if (_finding_entry(f), f.rule) not in EXPLAINED]


def stale_explanations(findings: list) -> list:
    """EXPLAINED keys with no matching finding — stale rationales are
    drift, exactly like a suppression on dead code."""
    seen = {(_finding_entry(f), f.rule) for f in findings}
    return sorted(f"stale explanation: entry '{k}' rule {r} no longer "
                  "fires — prune it from shardcheck.EXPLAINED"
                  for (k, r) in EXPLAINED if (k, r) not in seen)


def write_baseline(payload: dict, path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def load_baseline(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def diff_baselines(current: dict, base: dict) -> list:
    """Human-readable drift lines, contracts.diff_baselines-style."""
    out = []
    cur_e = current.get("entries", {})
    base_e = base.get("entries", {})
    for name in sorted(set(cur_e) | set(base_e)):
        a, b = cur_e.get(name), base_e.get(name)
        if a is None:
            out.append(f"entry '{name}': removed (in baseline only)")
            continue
        if b is None:
            out.append(f"entry '{name}': new (not in baseline)")
            continue
        for key in ("mesh", "n_eqns", "collectives", "findings",
                    "spec_digest", "source"):
            if a.get(key) != b.get(key):
                out.append(f"entry '{name}': {key} drifted: "
                           f"{b.get(key)!r} -> {a.get(key)!r}")
    if current.get("explained") != base.get("explained"):
        out.append("explained set drifted: "
                   f"{base.get('explained')!r} -> "
                   f"{current.get('explained')!r}")
    return out
