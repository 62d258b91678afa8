"""Sharded whole-step training program for the flagship GPT.

The TPU-native replacement for the reference's hybrid-parallel training
driver (fleet.distributed_model + HybridParallelOptimizer +
PipelineParallel.train_batch, SURVEY.md §3.3): one jitted SPMD program
containing forward, backward, and the AdamW update, with every parallel
axis expressed as a sharding:

- dp  : batch dim of tokens/activations; XLA reduces grads across dp.
- mp  : tp — vocab & head & ffn dims of weights (Megatron layout).
- sp  : Megatron sequence parallel — activations between blocks constrained
        to shard the token dim over "mp" (sequence_parallel_utils.py parity).
- pp  : stacked-layer axis via parallel/pipeline.py (compiled GPipe).
- ep  : MoE expert dim over "dp" (the reference's expert-parallel group).
- ZeRO: AdamW moments sharded over "dp" (DygraphShardingOptimizer parity) —
        XLA turns the grad reduction into reduce-scatter + the update into
        a sharded computation, all-gathering params at use sites.

Buffer donation keeps params+moments single-buffered like the reference's
inplace optimizer kernels.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs as _obs
from ..models.gpt import GPTConfig, block_apply, init_params, loss_fn
from .pipeline import pipeline_blocks_fn

__all__ = ["shard_gpt_params", "make_sharded_train_step"]


def gpt_param_specs(cfg: GPTConfig) -> dict:
    """Megatron-layout PartitionSpecs for the stacked GPT params."""
    specs = {
        "wte": P("mp", None),
        "wpe": P(),
        "blocks": {
            "ln1_g": P("pp", None), "ln1_b": P("pp", None),
            "qkv_w": P("pp", None, "mp"), "qkv_b": P("pp", "mp"),
            "proj_w": P("pp", "mp", None), "proj_b": P("pp", None),
            "ln2_g": P("pp", None), "ln2_b": P("pp", None),
            "fc_w": P("pp", None, "mp"), "fc_b": P("pp", "mp"),
            "fc2_w": P("pp", "mp", None), "fc2_b": P("pp", None),
        },
        "lnf_g": P(), "lnf_b": P(),
    }
    if not cfg.tie_embeddings:
        specs["head_w"] = P(None, "mp")
    if cfg.n_experts > 0 and cfg.n_moe_layers > 0:
        specs["moe"] = {
            "ln_g": P(), "ln_b": P(),
            "router_w": P(),
            # expert dim over dp = the "ep" group of the reference
            "w1": P(None, "dp", None, "mp"), "b1": P(None, "dp", None),
            "w2": P(None, "dp", "mp", None), "b2": P(None, "dp", None),
        }
    return specs


from ..distributed.placement import sanitize_spec as _sanitize


def shard_gpt_params(params: dict, cfg: GPTConfig, mesh: Mesh) -> dict:
    """device_put the param pytree with Megatron shardings (degenerate axes
    and non-divisible dims fall back to replicated)."""
    specs = gpt_param_specs(cfg)

    def put(a, s):
        return jax.device_put(a, NamedSharding(mesh, _sanitize(s, a.shape,
                                                               mesh)))

    return jax.tree.map(put, params, specs,
                        is_leaf=lambda x: isinstance(x, P))


# -- functional AdamW (the compiled-path optimizer; the dygraph Optimizer
#    classes serve the eager API) ------------------------------------------

_NO_MASTER = None  # sentinel factory below


def _master_leaf(a):
    """fp32 master for leaves that live in low precision; 1-D leaves
    (LN gains/biases, bias vectors) stay fp32 in params themselves
    (AMP-O2 keeps norm params out of the low-precision cast), so a master
    would be a redundant alias — store a size-0 sentinel to keep the
    pytree structure without duplicating (or aliasing) the buffer."""
    if a.ndim >= 2:
        return a.astype(jnp.float32)
    return jnp.zeros((0,), jnp.float32)


# -- memory-lean moment storage -------------------------------------------
#
# The AdamW moments dominate optimizer HBM: fp32 m+v is 8 bytes/param of
# state and ~16 bytes/param/step of read+write traffic (PERF.md: ~17 ms at
# 350m). Two lean representations, both with fp32 update math:
#
# - "bfloat16": plain bf16 storage. Safe for v (relative error ~2^-8
#   everywhere, never rounds a small value to zero, so the sqrt(v)+eps
#   denominator stays sane).
# - "int8": blockwise absmax-quantized int8 (8-bit-Adam style — Dettmers et
#   al., "8-bit Optimizers via Block-wise Quantization"). Used for m only:
#   m's near-zero values quantizing to 0 is benign (they contribute ~0 to
#   the step), whereas v values quantizing to 0 would explode m/(sqrt(v)+eps).
#
# 1-D leaves (LN gains, biases) always keep fp32 moments — they're tiny.

_QBLOCK = 2048


def _quantize_moment(x32):
    """Blockwise absmax int8 with sqrt companding:
    {'qm': int8 [nb, B], 'qs': fp32 [nb]}. The companding (store
    sign*sqrt(|x|/blockmax)) spends the int8 codes on small magnitudes,
    where a linear code would round a slowly-decaying EMA to zero and
    accumulate drift (measured 16% vs 4.7% trajectory error on a quadratic)."""
    flat = x32.reshape(-1)
    pad = (-flat.size) % _QBLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, _QBLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1)
    nrm = blocks / jnp.maximum(scale, 1e-20)[:, None]
    nrm = jnp.sign(nrm) * jnp.sqrt(jnp.abs(nrm))
    q = jnp.clip(jnp.round(nrm * 127.0), -127, 127).astype(jnp.int8)
    return {"qm": q, "qs": scale}


def _is_quant(x) -> bool:
    return isinstance(x, dict) and "qm" in x


def _dequantize_moment(mq, like):
    """fp32 tensor shaped like ``like`` from any moment representation."""
    if not _is_quant(mq):
        return mq.astype(jnp.float32)
    nrm = mq["qm"].astype(jnp.float32) / 127.0
    nrm = jnp.sign(nrm) * jnp.square(nrm)
    flat = (nrm * mq["qs"][:, None]).reshape(-1)
    return flat[:like.size].reshape(like.shape)


def _stochastic_round(x32, dtype, key):
    """fp32 -> bf16 with stochastic rounding: add uniform bits below the
    bf16 mantissa cut, truncate. Makes bf16 weight updates unbiased so a
    separate fp32 master copy is unnecessary ("Revisiting BFloat16
    Training" recipe) — the memory lever that lets a full GPT-3 1.3B AdamW
    step fit one v5e."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return x32.astype(dtype)
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    r = jax.random.bits(key, x32.shape, jnp.uint16).astype(jnp.uint32)
    rounded = (bits + r) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(rounded, jnp.float32).astype(dtype)


def _store_moment(x32, dtype):
    if dtype == "int8":
        return _quantize_moment(x32)
    return x32.astype(jnp.dtype(dtype))


def _moment_like(a, dtype):
    if a.ndim < 2 or dtype in (None, "float32"):
        return jnp.zeros_like(a, dtype=jnp.float32)
    if dtype == "int8":
        return _quantize_moment(jnp.zeros(a.shape, jnp.float32))
    return jnp.zeros(a.shape, jnp.dtype(dtype))


def _moment_dtype_for(a, dtype):
    return "float32" if (a.ndim < 2 or dtype is None) else dtype


def adamw_init(params: dict, master_weights: bool = False,
               m_dtype: str | None = None, v_dtype: str | None = None) -> dict:
    """``master_weights``: keep an fp32 master copy in the state (reference
    AMP-O2 semantics, amp/grad_scaler + master_grad) so ``params`` itself can
    live in the compute dtype — no per-use fp32->bf16 casts in the hot loop.

    ``m_dtype``/``v_dtype``: 'float32' (default), 'bfloat16', or 'int8'
    (blockwise absmax) moment storage — see the memory-lean notes above."""
    state = {
        "m": jax.tree.map(lambda a: _moment_like(a, m_dtype), params),
        "v": jax.tree.map(lambda a: _moment_like(a, v_dtype), params),
        "t": jnp.zeros((), jnp.int32),
    }
    if master_weights:
        state["master"] = jax.tree.map(_master_leaf, params)
    return state


def adamw_update(params, grads, state, lr, wd=0.1, b1=0.9, b2=0.95,
                 eps=1e-8, m_dtype=None, v_dtype=None,
                 stochastic_round=False):
    t = state["t"] + 1
    bc1 = 1.0 - b1 ** t.astype(jnp.float32)
    bc2 = 1.0 - b2 ** t.astype(jnp.float32)
    masters = state.get("master")
    # rbg keys: the XLA RngBitGenerator is ~19x faster than threefry for
    # the SR noise (25ms vs 470ms per 162M u16 on v5e) and SR needs no
    # cryptographic stream quality
    sr_base = (jax.random.fold_in(jax.random.key(0x5e0, impl="rbg"), t)
               if stochastic_round else None)

    def upd(i, p, g, m, v, mw):
        has_master = mw is not None and mw.size
        g32 = g.astype(jnp.float32)
        m = b1 * _dequantize_moment(m, p) + (1 - b1) * g32
        v = b2 * _dequantize_moment(v, p) + (1 - b2) * jnp.square(g32)
        step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        p32 = mw if has_master else p.astype(jnp.float32)
        p32 = p32 - lr * (step + wd * p32)
        new_mw = p32 if has_master else (
            None if mw is None else jnp.zeros((0,), jnp.float32))
        if stochastic_round and not has_master:
            new_p = _stochastic_round(p32, p.dtype,
                                      jax.random.fold_in(sr_base, i))
        else:
            new_p = p32.astype(p.dtype)
        return (new_p,
                _store_moment(m, _moment_dtype_for(p, m_dtype)),
                _store_moment(v, _moment_dtype_for(p, v_dtype)),
                new_mw)

    flat_p, tree = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m, _ = jax.tree.flatten(state["m"], is_leaf=_is_quant)
    flat_v, _ = jax.tree.flatten(state["v"], is_leaf=_is_quant)
    flat_mw = (jax.tree.leaves(masters) if masters is not None
               else [None] * len(flat_p))
    out = [upd(i, p, g, m, v, mw) for i, (p, g, m, v, mw) in
           enumerate(zip(flat_p, flat_g, flat_m, flat_v, flat_mw))]
    new_p = jax.tree.unflatten(tree, [o[0] for o in out])
    new_m = jax.tree.unflatten(tree, [o[1] for o in out])
    new_v = jax.tree.unflatten(tree, [o[2] for o in out])
    new_state = {"m": new_m, "v": new_v, "t": t}
    if masters is not None:
        new_state["master"] = jax.tree.unflatten(tree,
                                                 [o[3] for o in out])
    return new_p, new_state


# -- abstract (AOT) state: ShapeDtypeStructs with the same shardings the
#    materialized path produces, for lowering/compiling configs too large to
#    instantiate on the analysis host (the 13B north-star memory analysis) --


def _abstract_params(cfg: GPTConfig, mesh: Mesh, seed: int) -> dict:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(seed))
    specs = gpt_param_specs(cfg)

    def put(a, s):
        ns = NamedSharding(mesh, _sanitize(s, a.shape, mesh))
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=ns)

    return jax.tree.map(put, shapes, specs,
                        is_leaf=lambda x: isinstance(x, P))


def _abstract_opt_state(params_abs: dict, mesh: Mesh, *, master: bool,
                        m_dtype, v_dtype, zero1: bool) -> dict:
    """adamw_init over abstract params, with moments/masters inheriting the
    param's TP/PP spec plus the ZeRO-1 dp shard (the sharding the jit's
    donated arguments are expected in)."""
    shapes = jax.eval_shape(
        lambda p: adamw_init(p, master_weights=master, m_dtype=m_dtype,
                             v_dtype=v_dtype), params_abs)
    from ..distributed.sharding import shard_spec_over

    flat_p, _ = jax.tree.flatten(params_abs)

    def attach(leaf, p):
        if leaf.shape == p.shape and isinstance(p.sharding, NamedSharding):
            spec = p.sharding.spec
        else:
            spec = P()  # quantized blocks / size-0 sentinels: replicated
        if zero1:
            z = shard_spec_over(leaf.shape, spec, mesh, "dp")
            spec = z if z is not None else spec
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec))

    out = {"t": jax.ShapeDtypeStruct(
        (), shapes["t"].dtype, sharding=NamedSharding(mesh, P()))}
    for key in ("m", "v", "master"):
        if key not in shapes:
            continue
        leaves, tdef = jax.tree.flatten(
            shapes[key], is_leaf=lambda x: isinstance(x, dict) and "qm" in x)
        new = []
        for leaf, p in zip(leaves, flat_p):
            if isinstance(leaf, dict):
                new.append({k: attach(v, p) for k, v in leaf.items()})
            else:
                new.append(attach(leaf, p))
        out[key] = jax.tree.unflatten(tdef, new)
    return out


def make_sharded_train_step(cfg: GPTConfig, mesh: Mesh, lr: float = 1e-4,
                            n_microbatches: int = 1, zero1: bool = True,
                            seed: int = 0, m_dtype: str | None = None,
                            v_dtype: str | None = None,
                            weights: str = "auto", abstract: bool = False,
                            _emb_pin: bool = True):
    """Build (step_fn, params, opt_state): a donated, fully-sharded
    train step. ``step_fn(params, opt_state, tokens, labels) ->
    (loss, params, opt_state)``.

    ``m_dtype``/``v_dtype`` select memory-lean AdamW moment storage
    ('bfloat16' / 'int8'); loss-trajectory equivalence vs fp32 moments is
    measured in PERF.md (round 3).

    ``weights``:
      - 'auto'   : fp32 master in opt state when param_dtype != dtype
                   (reference AMP-O2 semantics).
      - 'sr-bf16': NO master copy — live weights in cfg.dtype, updates
                   written back with stochastic rounding. Halves optimizer
                   HBM traffic and sheds the 4-bytes/param master; the
                   memory mode that fits a full 1.3B AdamW step on one
                   v5e (VERDICT r2 item 1).

    Long-context: set ``cfg.ring_axis='mp'`` (or any mesh axis > 1) and
    attention runs as ring attention over that axis — sequence sharded,
    k/v rotating by ppermute, per-device attention memory O(S/cp)."""
    if weights not in ("auto", "sr-bf16"):
        raise ValueError(f"weights mode {weights!r}: expected 'auto' or "
                         "'sr-bf16'")
    for name, dt in (("m_dtype", m_dtype), ("v_dtype", v_dtype)):
        if dt not in (None, "float32", "bfloat16", "int8"):
            raise ValueError(f"{name}={dt!r}: expected None/'float32'/"
                             "'bfloat16'/'int8'")
    if v_dtype == "int8":
        # int8 v is documented-unsafe: small v values quantizing to zero
        # explode m/(sqrt(v)+eps); refuse rather than silently diverge
        raise ValueError("v_dtype='int8' is unsafe (zeroed second moments "
                         "explode the update); use 'bfloat16'")
    from ..core.flags import GLOBAL_FLAGS
    use_quant_sync = (GLOBAL_FLAGS.has("dist_allreduce_quant")
                      and bool(GLOBAL_FLAGS.get("dist_allreduce_quant"))
                      and "dp" in mesh.axis_names and mesh.shape["dp"] > 1)
    if use_quant_sync and "pp" in mesh.axis_names and mesh.shape["pp"] > 1:
        # the pipeline is its own pp-manual shard_map; nesting it inside a
        # dp-manual region is not a supported lowering — quantized grad
        # sync targets dp(×mp) meshes
        from ..distributed.autograd_collectives import QUANT_SYNC_PP_REFUSAL
        raise ValueError(QUANT_SYNC_PP_REFUSAL)
    # Master-weight mode when params would be cast per-use anyway: keep the
    # fp32 master in the optimizer state and the live MATMUL weights in the
    # compute dtype (matmuls consumed them bf16 either way; the update
    # always accumulates in fp32), shedding every weight-cast and halving
    # grad HBM traffic in the hot loop. 1-D params (LayerNorm gains/biases,
    # bias vectors) stay fp32, matching reference AMP-O2 which excludes
    # norm params from the low-precision cast (amp/auto_cast black list).
    low_precision = jnp.dtype(cfg.param_dtype) != jnp.dtype(cfg.dtype)
    sr = weights == "sr-bf16" and low_precision
    master = low_precision and not sr
    # The state as ShapeDtypeStructs carrying its shardings: params in
    # the Megatron layout, moments/masters inheriting it plus the ZeRO-1
    # dp shard.  AOT mode returns exactly this — configs too large for
    # the analysis host (13B+) lower/compile from it for memory +
    # collective analysis.
    params = _abstract_params(cfg, mesh, seed)
    if master or sr:
        params = jax.tree.map(
            lambda a: (jax.ShapeDtypeStruct(a.shape, cfg.dtype,
                                            sharding=a.sharding)
                       if a.ndim >= 2 else a), params)
    opt_state = _abstract_opt_state(params, mesh, master=master,
                                    m_dtype=m_dtype, v_dtype=v_dtype,
                                    zero1=zero1)
    if not abstract:
        # The real state is that abstract state materialized by ONE
        # program whose outputs are born in those shardings: each device
        # creates only its own shards.  (Built eagerly, the full fp32
        # params and moments land on the default device first, so a
        # model that needs the mesh would not fit at all.)
        def init_state(key):
            p = init_params(cfg, key)
            o = adamw_init(p, master_weights=master, m_dtype=m_dtype,
                           v_dtype=v_dtype)
            if master or sr:
                p = jax.tree.map(
                    lambda a: a.astype(cfg.dtype) if a.ndim >= 2 else a, p)
            return p, o

        params, opt_state = jax.jit(init_state, out_shardings=jax.tree.map(
            lambda a: a.sharding, (params, opt_state)))(
                jax.random.PRNGKey(seed))

    use_pp = "pp" in mesh.axis_names and mesh.shape["pp"] > 1
    use_sp = "mp" in mesh.axis_names and mesh.shape["mp"] > 1
    multichip = any(mesh.shape[a] > 1 for a in mesh.axis_names)

    def _constrain(x, spec):
        # Inside the manual-pp shard_map region the constraint must be
        # built over the context's abstract mesh (pp is Manual there).
        spec = _sanitize(spec, x.shape, mesh)
        am = jax.sharding.get_abstract_mesh()
        target = am if (am is not None and not am.empty) else mesh
        return lax.with_sharding_constraint(x, NamedSharding(target, spec))

    def sp_constraint(x):
        # Megatron-SP: between blocks, tokens shard over mp (+ batch over
        # dp).
        return _constrain(x, P("dp", "mp"))

    def emb_constraint(x):
        # The embedding gather's [B, T, H] output: batch over dp, T and H
        # unsharded. Pinning AT the gather (indices dp-sharded, operand in
        # its Megatron vocab layout, output fixed here) fully specifies the
        # gather, so GSPMD partitions the op itself instead of inventing an
        # intermediate layout and resharding it — the MULTICHIP_r05
        # involuntary-full-rematerialization. The sp layout (T over mp) is
        # re-established one elementwise op later, a cheap activation
        # reshard rather than a gather reshard.
        return _constrain(x, P("dp"))

    sp = sp_constraint if use_sp else None
    # _emb_pin=False rebuilds the pre-fix MULTICHIP_r05 program (gather
    # output unpinned) so shardcheck's TPL201 regression can trace the
    # hazard it proves absent on the default path; never disable in
    # production code.
    emb = emb_constraint if (multichip and _emb_pin) else None
    grad_specs = gpt_param_specs(cfg)

    # -- quantized gradient sync (EQuARX-style, flag-gated) ----------------
    # With use_quant_sync (validated at the top), forward+backward run
    # inside a dp-manual shard_map and gradient sync is an explicit
    # int8-wire all-reduce (autograd_collectives.dist_allreduce_quant)
    # instead of the psum GSPMD would insert. Off (default) the step below
    # is the exact same program as before the flag existed — bit-identical.
    def _quant_sync_grads(params, tokens, labels):
        """(loss, grads) with int8-wire dp gradient sync. Params enter the
        manual region replicated over dp (in_specs P()), so expert-parallel
        MoE leaves are all-gathered in — correct, at the cost of replicated
        expert compute; mp/pp-degenerate axes of size 1 are made manual too
        so the region lowers as full-manual on runtimes without native
        partial-manual shard_map support."""
        from ..distributed.autograd_collectives import dist_allreduce_quant

        sp_local = None
        if use_sp:
            def sp_local(x):
                # dp is manual inside the region: constrain only the
                # Megatron-SP token dim; batch sharding is implicit
                return _constrain(x, P(None, "mp"))

        def body(p, tok, lab):
            def lf_local(pl):
                with jax.named_scope("fwd"):
                    return loss_fn(pl, tok, lab, cfg,
                                   sp_constraint=sp_local)

            loss, grads = jax.value_and_grad(lf_local)(p)
            grads = jax.tree.map(
                lambda g: dist_allreduce_quant(
                    g, "dp", mean=True, axis_size=mesh.shape["dp"]), grads)
            return lax.pmean(loss, "dp"), grads

        manual = {"dp"} | {a for a in mesh.axis_names if mesh.shape[a] == 1}
        run = jax.shard_map(
            body,
            in_specs=(jax.tree.map(lambda _: P(), params), P("dp"),
                      P("dp")),
            out_specs=(P(), jax.tree.map(lambda _: P(), params)),
            axis_names=manual,
            check_vma=False,
        )
        return run(params, tokens, labels)

    blocks_fn = None
    if use_pp:
        def stage_fn(stage_params, x):
            def body(carry, bp):
                return block_apply(bp, carry, cfg, sp), None

            out, _ = lax.scan(body, x, stage_params)
            return out

        blocks_fn = pipeline_blocks_fn(stage_fn, mesh, n_microbatches)

    def step(params, opt_state, tokens, labels):
        if multichip:
            # anchor the batch layout inside the program: put_batch places
            # tokens/labels over dp, but feeding numpy (or a future caller
            # with different placement) must not change what the partitioner
            # sees at the embedding gather's indices
            tokens = _constrain(tokens, P("dp"))
            labels = _constrain(labels, P("dp"))

        def lf(p):
            # device-side scopes (an op's op_name in XProf / Perfetto);
            # the backward inherits transpose(jvp(fwd))
            with jax.named_scope("fwd"):
                return loss_fn(p, tokens, labels, cfg, sp_constraint=sp,
                               emb_constraint=emb,
                               blocks_fn=(functools.partial(_run_blocks,
                                                            blocks_fn)
                                          if blocks_fn else None))

        if use_quant_sync:
            loss, grads = _quant_sync_grads(params, tokens, labels)
        else:
            loss, grads = jax.value_and_grad(lf)(params)
        if multichip:
            # grads leave the model graph in the PARAM layout; the ZeRO-1
            # moment layout (shard_spec_over picks any divisible dim, e.g.
            # wte's hidden dim over dp) is reached by an explicit reshard
            # inside the update instead of back-propagating through the
            # backward pass — unpinned, that propagation is what turned the
            # embedding gather into an involuntary full rematerialization
            # (MULTICHIP_r05) and invents conflicting attention layouts.
            grads = jax.tree.map(lambda g, s: _constrain(g, s),
                                 grads, grad_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        with jax.named_scope("adamw"):
            new_params, new_state = adamw_update(
                params, grads, opt_state, lr, m_dtype=m_dtype,
                v_dtype=v_dtype, stochastic_round=sr)
        return loss, new_params, new_state

    def _run_blocks(fn, bp, x):
        return fn(bp, x)

    # Route the WHOLE step (forward + backward + AdamW) through the
    # fusion compiler: one program hash covers the step, so the v2
    # autotune cache replays every kernel config and fusion decision on
    # restart without re-sweeping.  The pp and quant-sync paths carry
    # shard_map regions the re-trace must not rebuild, and Megatron-SP
    # resharding disables every catalog site anyway (PR 6 never fused
    # under sp either) — those run the step unwrapped.
    if not use_pp and not use_quant_sync and not use_sp:
        from ..compiler import auto_fuse

        step = auto_fuse(step)

    jitted = jax.jit(step, donate_argnums=(0, 1))

    def put_batch(arr):
        """Shard a host batch over dp. Call once per batch; feeding numpy
        directly to step_fn also works but re-uploads every call."""
        return jax.device_put(arr, NamedSharding(
            mesh, _sanitize(P("dp"), arr.shape, mesh)))

    def step_fn(params, opt_state, tokens, labels):
        if not isinstance(tokens, jax.Array):
            tokens = put_batch(tokens)
        if not isinstance(labels, jax.Array):
            labels = put_batch(labels)
        # context mesh for the partial-manual pipeline shard_map; the
        # span is the host's time to dispatch one step (the call returns
        # before the device finishes)
        with jax.sharding.set_mesh(mesh), _obs.span("train.step"):
            return jitted(params, opt_state, tokens, labels)

    step_fn.put_batch = put_batch
    # AOT access: step_fn.jitted.lower(params, opt_state, tok_sds, lab_sds)
    # under `with jax.sharding.set_mesh(mesh)` (abstract=True callers).
    step_fn.jitted = jitted
    step_fn.mesh = mesh

    return step_fn, params, opt_state
