"""GPT: the flagship decoder-only LM, TPU-first.

Capability parity targets (BASELINE.md configs 3-4): the reference trains
GPT-class models through fleet hybrid parallel — VocabParallelEmbedding /
Column/RowParallelLinear (fleet/layers/mpu/mp_layers.py:47,334,541),
PipelineParallel 1F1B (fleet/meta_parallel/pipeline_parallel.py:245), fused
attention kernels (phi/kernels/fusion/gpu/fused_attention*). Here the model
is designed for XLA from the start:

- **Functional core** (`init_params` / `model_apply`): pure jnp over a
  params pytree; blocks are *stacked* ``[L, ...]`` and iterated with
  ``lax.scan`` (constant compile time in depth, and the natural layout for
  pipeline stacking), rematerialised per block (``jax.checkpoint``) like the
  reference's recompute (fleet/recompute/recompute.py:124).
- **Sharding by annotation**: tp = vocab/heads/ffn dims over "mp", dp/ep =
  batch/experts over "dp", Megatron-SP = token dim over "mp" between blocks;
  pipeline = stacked-layer axis over "pp" via parallel/pipeline.py.
- **MXU discipline**: matmuls in bf16 with fp32 accumulation, fp32 master
  params; attention through the Pallas flash kernel (ops/pallas).
- Optional **MoE** FFN layers (GShard/switch top-1 with capacity, one-hot
  einsum dispatch — static shapes, no host loops; reference:
  incubate/distributed/models/moe/moe_layer.py:263 + global_scatter/gather).

The eager ``GPT`` Layer wraps the same functional core through one
registered op, so dygraph autograd, AMP and capture all apply.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["GPTConfig", "init_params", "model_apply", "loss_fn", "GPT",
           "gpt_presets"]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    hidden: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    seq_len: int = 1024
    ffn_mult: int = 4
    # MoE: if n_experts > 0, `n_moe_layers` expert-FFN blocks run after the
    # dense stack's midpoint (expert dim shards over dp = "ep").
    n_experts: int = 0
    n_moe_layers: int = 0
    moe_capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16          # activation/compute dtype (MXU)
    param_dtype: Any = jnp.float32     # master params
    tie_embeddings: bool = True
    use_flash: bool = True
    # False | True (save dots + flash outputs) | "full" (save flash
    # outputs only — long-context memory mode)
    remat: bool | str = True
    # Unroll the layer loop instead of lax.scan: straight-line XLA code has
    # no dynamic-update-slice stacking of saves/grads and schedules ~10%
    # faster on v5e; costs compile time linear in depth (use for the
    # single-program bench/train path, keep scan for quick iteration).
    unroll: bool = False
    # Context parallelism: when set to a mesh axis name (and that axis has
    # size > 1 in the active mesh), attention runs as RING attention over
    # it — the sequence shards the ring, k/v rotate by ppermute, per-device
    # attention memory is O(S/cp) (parallel/ring_attention.py; beyond the
    # reference, which has no context-parallel attention).
    ring_axis: Optional[str] = None
    eps: float = 1e-5

    def __post_init__(self):
        if self.remat not in (False, True, "full"):
            raise ValueError(
                f"remat must be False, True, or 'full'; got "
                f"{self.remat!r} (a truthy unknown string would silently "
                f"take the dots-saveable policy)")

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads


def gpt_presets(name: str) -> GPTConfig:
    """Reference GPT-3 family sizes (BASELINE.md configs)."""
    table = {
        "gpt3-125m": dict(hidden=768, n_layers=12, n_heads=12),
        "gpt3-350m": dict(hidden=1024, n_layers=24, n_heads=16),
        "gpt3-760m": dict(hidden=1536, n_layers=24, n_heads=16),
        "gpt3-1.3b": dict(hidden=2048, n_layers=24, n_heads=16),
        "gpt3-2.7b": dict(hidden=2560, n_layers=32, n_heads=32),
        "gpt3-6.7b": dict(hidden=4096, n_layers=32, n_heads=32),
        "gpt3-13b": dict(hidden=5120, n_layers=40, n_heads=40),
    }
    return GPTConfig(**table[name])


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: GPTConfig, key) -> dict:
    """Initialise the stacked-parameter pytree (normal(0.02), scaled
    residual projections à la GPT-2)."""
    k = iter(jax.random.split(key, 24))
    H, L, F = cfg.hidden, cfg.n_layers, cfg.ffn_mult * cfg.hidden
    std = 0.02
    pstd = std / math.sqrt(2 * L)
    pd = cfg.param_dtype

    def nrm(kk, shape, s=std):
        return (jax.random.normal(kk, shape, jnp.float32) * s).astype(pd)

    params = {
        "wte": nrm(next(k), (cfg.vocab_size, H)),
        "wpe": nrm(next(k), (cfg.seq_len, H), 0.01),
        "blocks": {
            "ln1_g": jnp.ones((L, H), pd),
            "ln1_b": jnp.zeros((L, H), pd),
            "qkv_w": nrm(next(k), (L, H, 3 * H)),
            "qkv_b": jnp.zeros((L, 3 * H), pd),
            "proj_w": nrm(next(k), (L, H, H), pstd),
            "proj_b": jnp.zeros((L, H), pd),
            "ln2_g": jnp.ones((L, H), pd),
            "ln2_b": jnp.zeros((L, H), pd),
            "fc_w": nrm(next(k), (L, H, F)),
            "fc_b": jnp.zeros((L, F), pd),
            "fc2_w": nrm(next(k), (L, F, H), pstd),
            "fc2_b": jnp.zeros((L, H), pd),
        },
        "lnf_g": jnp.ones((H,), pd),
        "lnf_b": jnp.zeros((H,), pd),
    }
    if not cfg.tie_embeddings:
        params["head_w"] = nrm(next(k), (H, cfg.vocab_size))
    if cfg.n_experts > 0 and cfg.n_moe_layers > 0:
        E, M = cfg.n_experts, cfg.n_moe_layers
        params["moe"] = {
            "ln_g": jnp.ones((M, H), pd),
            "ln_b": jnp.zeros((M, H), pd),
            "router_w": nrm(next(k), (M, H, E), 0.01),
            "w1": nrm(next(k), (M, E, H, F)),
            "b1": jnp.zeros((M, E, F), pd),
            "w2": nrm(next(k), (M, E, F, H), pstd),
            "b2": jnp.zeros((M, E, H), pd),
        }
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_norm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _per_shard(kernel, args, in_specs, out_spec, out_shape):
    """Call a Pallas attention entry once per (dp, mp) shard of the
    ambient mesh: batch over "dp", heads over "mp".

    A Mosaic kernel has no GSPMD partitioning rule: bare inside a jit
    partitioned over several devices, lowering refuses it ("wrap the
    call in a shard_map"), and interpret mode hides that — there the
    kernel is plain HLO.  The shard_map makes EVERY remaining mesh axis
    manual (Mosaic also refuses a partially-auto region, size-1 axes
    included); axes already manual (the pipeline region's "pp") are
    skipped.  Specs name "dp"/"mp" only and follow the repo's placement
    rule (distributed/placement.py): a dim an axis does not divide stays
    replicated over it, as it is in the surrounding program.  On a
    one-device mesh, or with no mesh, the kernel is called directly."""
    from ..distributed.placement import sanitize_spec

    am = jax.sharding.get_abstract_mesh()
    if am.empty or am.size == 1:
        return kernel(*args)
    auto = set(am.axis_names) - set(am.manual_axes)

    def local(spec, shape):
        # (sanitize_spec leaves each entry a single axis name or None)
        return P(*(a if a in auto else None
                   for a in sanitize_spec(spec, shape, am)))

    return jax.shard_map(
        kernel, in_specs=tuple(local(s, a.shape)
                               for s, a in zip(in_specs, args)),
        out_specs=local(out_spec, out_shape), axis_names=auto,
        check_vma=False)(*args)


_BTHD = P("dp", None, "mp", None)      # [B, T, nH, dH]


def _attention(q, k, v, cfg: GPTConfig):
    # q,k,v: [B, T, nH, dH]
    if cfg.ring_axis:
        am = jax.sharding.get_abstract_mesh()
        if (am is not None and not am.empty
                and cfg.ring_axis in am.axis_names
                and am.shape[cfg.ring_axis] > 1):
            from ..parallel.ring_attention import ring_attention

            return ring_attention(q, k, v, am, axis=cfg.ring_axis,
                                  causal=True)
    if cfg.use_flash:
        from ..ops.pallas.flash_attention import flash_attention_raw, supported

        # flash_attention takes [B, T, nH, dH] (it handles the head-major
        # transpose internally, ops/pallas/flash_attention.py:_flash_fwd)
        if supported(q.shape, q.dtype):
            return _per_shard(
                functools.partial(flash_attention_raw, causal=True),
                (q, k, v), (_BTHD,) * 3, _BTHD, q.shape)
    # XLA fallback: fp32 logits, causal mask
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    T = q.shape[1]
    mask = jnp.tril(jnp.ones((T, T), bool))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_qkv(qkv, cfg: GPTConfig):
    """The fused-qkv flash entry on [B, T, 3H] -> [B, T, nH, dH].  Per
    shard the kernel sees the same [q | k | v] lane layout with nH/mp
    heads: the free [B, T, 3, nH, dH] view shards over its head axis."""
    from ..ops.pallas.flash_attention import flash_attention_qkv_raw

    B, T, _ = qkv.shape
    nH, dH = cfg.n_heads, cfg.head_dim

    def kernel(x):                       # [b, T, 3, nH_local, dH]
        b, t, _, nh, _ = x.shape
        return flash_attention_qkv_raw(x.reshape(b, t, 3 * nh * dH), nh,
                                       causal=True)

    return _per_shard(kernel, (qkv.reshape(B, T, 3, nH, dH),),
                      (P("dp", None, None, "mp", None),), _BTHD,
                      (B, T, nH, dH))


def block_apply(bp: dict, x, cfg: GPTConfig, sp_constraint=None):
    """One pre-LN transformer block. ``bp`` leaves have NO leading layer dim
    (a single layer's slice). ``sp_constraint`` optionally reshards the
    activation (Megatron-SP: token dim over 'mp') between sublayers."""
    B, T, H = x.shape
    # Matmuls take and produce cfg.dtype (bf16 on TPU): the MXU accumulates
    # in fp32 internally either way, and emitting bf16 halves the HBM
    # traffic of the residuals the remat policy saves per layer (measured
    # ~40ms/step of dynamic-update-slice fusions at 350M/b8 with fp32
    # dot outputs).
    h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"], cfg.eps)
    qkv = jnp.einsum("bth,hk->btk", h, bp["qkv_w"].astype(cfg.dtype))
    qkv = qkv + bp["qkv_b"].astype(cfg.dtype)
    o = None
    if cfg.use_flash and not cfg.ring_axis:
        from ..ops.pallas.flash_attention import flash_qkv_supported

        if flash_qkv_supported(qkv.shape, cfg.n_heads, qkv.dtype):
            # fused entry: kernels read q/k/v from the projection output
            # through lane-offset views — no 3-way split copies
            o = _flash_qkv(qkv, cfg).reshape(B, T, H)
    if o is None:
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, T, cfg.n_heads, cfg.head_dim)
        v = v.reshape(B, T, cfg.n_heads, cfg.head_dim)
        o = _attention(q, k, v, cfg).reshape(B, T, H)
    o = jnp.einsum("bth,hk->btk", o, bp["proj_w"].astype(cfg.dtype))
    # Unfused residual + proj bias + ln2: the compiler pass
    # (paddle_tpu/compiler/, layer_epilogue template) rediscovers this
    # chain in the traced jaxpr and rewrites it to fused_norm_epilogue —
    # and its matcher refuses to fuse across the SP resharding point, so
    # the sp_constraint path stays unfused exactly as the old hand-wired
    # gate kept it.
    x = x + o + bp["proj_b"].astype(cfg.dtype)
    if sp_constraint is not None:
        x = sp_constraint(x)
    h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"], cfg.eps)
    h = jnp.einsum("bth,hf->btf", h, bp["fc_w"].astype(cfg.dtype))
    h = jax.nn.gelu(h + bp["fc_b"].astype(cfg.dtype), approximate=True)
    h = jnp.einsum("btf,fh->bth", h, bp["fc2_w"].astype(cfg.dtype))
    x = x + h + bp["fc2_b"].astype(cfg.dtype)
    if sp_constraint is not None:
        x = sp_constraint(x)
    return x


def moe_block_apply(mp: dict, x, cfg: GPTConfig):
    """Switch-style top-1 MoE FFN (GShard dense-dispatch formulation).

    The reference routes with variable-size all-to-all driven by count
    tensors (moe_utils.py:20 global_scatter). XLA needs static shapes, so
    dispatch is a one-hot capacity einsum: tokens beyond an expert's
    capacity are dropped (their residual passes through), the standard
    TPU MoE trade. Expert dim E shards over the dp axis ("ep").
    Returns (y, aux_loss)."""
    B, T, H = x.shape
    E = mp["router_w"].shape[-1]
    N = B * T
    C = max(1, int(cfg.moe_capacity_factor * N / E))
    h = _layer_norm(x, mp["ln_g"], mp["ln_b"], cfg.eps)
    flat = h.reshape(N, H)
    logits = jnp.einsum("nh,he->ne", flat.astype(jnp.float32),
                        mp["router_w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    gate, idx = probs.max(-1), probs.argmax(-1)  # [N]
    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # [N, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot            # [N, E]
    pos_in_e = pos.sum(-1)                                     # [N]
    keep = pos_in_e < C
    # dispatch tensor [N, E, C]
    disp = (jax.nn.one_hot(idx, E, dtype=cfg.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos_in_e, C), C + 1,
                             dtype=cfg.dtype)[:, None, :C])
    xin = jnp.einsum("nec,nh->ech", disp, flat.astype(cfg.dtype))  # [E,C,H]
    hmid = jnp.einsum("ech,ehf->ecf", xin, mp["w1"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32).astype(cfg.dtype)
    hmid = jax.nn.gelu(hmid + mp["b1"].astype(cfg.dtype)[:, None, :],
                       approximate=True)
    hout = jnp.einsum("ecf,efh->ech", hmid, mp["w2"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32).astype(cfg.dtype)
    hout = hout + mp["b2"].astype(cfg.dtype)[:, None, :]
    combine = disp * gate.astype(cfg.dtype)[:, None, None]
    y = jnp.einsum("nec,ech->nh", combine, hout).reshape(B, T, H)
    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    f = onehot.astype(jnp.float32).mean(0)
    P = probs.mean(0)
    aux = E * jnp.sum(f * P)
    return x + y, aux


def model_apply(params: dict, tokens, cfg: GPTConfig, sp_constraint=None,
                blocks_fn=None, return_hidden: bool = False,
                emb_constraint=None):
    """Forward to logits, routed through the fusion compiler when no
    resharding callables are injected (the condition under which kernel
    fusion used to be hand-wired).  Constrained/pipelined paths run the
    unfused composition here and get their fusion at the train-step
    level (parallel/train_step.py wraps the whole step)."""
    if sp_constraint is None and blocks_fn is None and emb_constraint is None:
        from ..compiler import fused_call

        return fused_call(("gpt_apply", cfg, bool(return_hidden)),
                          functools.partial(_model_apply_unfused, cfg=cfg,
                                            return_hidden=return_hidden),
                          params, tokens)
    return _model_apply_unfused(params, tokens, cfg,
                                sp_constraint=sp_constraint,
                                blocks_fn=blocks_fn,
                                return_hidden=return_hidden,
                                emb_constraint=emb_constraint)


def _model_apply_unfused(params: dict, tokens, cfg: GPTConfig,
                         sp_constraint=None, blocks_fn=None,
                         return_hidden: bool = False, emb_constraint=None):
    """Forward to logits (or the final hidden states with
    ``return_hidden`` — the chunked-loss path projects to vocab itself).
    ``blocks_fn(params_blocks, x)`` overrides the dense-stack execution
    (the pipeline path passes the shard_map'd stage runner); default is a
    remat'd lax.scan over stacked layers.

    ``emb_constraint`` pins the embedding gather's output the moment it
    exists. Left unpinned, GSPMD back-propagates the ZeRO-sharded moment
    layout (hidden dim over dp) onto the forward gather and then reshards
    it to the activation layout with an involuntary full rematerialization
    (MULTICHIP_r05: {devices=[1,1,2,4]} -> {devices=[2,2,1,2]} on
    f32[B,T,H])."""
    B, T = tokens.shape
    emb = params["wte"][tokens]
    if emb_constraint is not None:
        emb = emb_constraint(emb)
    x = emb.astype(cfg.dtype) + params["wpe"][:T].astype(cfg.dtype)
    if sp_constraint is not None:
        x = sp_constraint(x)

    if blocks_fn is not None:
        x = blocks_fn(params["blocks"], x)
    else:
        fn = functools.partial(block_apply, cfg=cfg,
                               sp_constraint=sp_constraint)
        if cfg.remat:
            if cfg.remat == "full":
                # deepest mode: save ONLY the flash outputs (recomputing
                # flash in backward would double the most expensive
                # kernel); every matmul recomputes. The dots-saveable
                # policy below keeps ~300 MB/layer of projection outputs
                # at 1.3B/S=8192 (~7 G total — measured HBM OOM on one
                # v5e); this mode keeps ~35 MB/layer and fits.
                pol = jax.checkpoint_policies.save_only_these_names(
                    "flash_o", "flash_lse")
            else:
                # save matmul outputs AND the flash-attention outputs
                # (named in ops/pallas/flash_attention.py — pallas calls
                # are not dots, so without the names the whole flash
                # forward would run again in backward); recompute
                # elementwise only.
                pol = jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    jax.checkpoint_policies.save_only_these_names(
                        "flash_o", "flash_lse"))
            fn = jax.checkpoint(fn, policy=pol)

        if cfg.unroll:
            for i in range(cfg.n_layers):
                bp = jax.tree.map(lambda a, i=i: a[i], params["blocks"])
                x = fn(bp, x)
        else:
            def body(carry, bp):
                return fn(bp, carry), None

            x, _ = lax.scan(body, x, params["blocks"])

    # MoE layers run after the dense stack in BOTH paths (so the pipeline
    # blocks_fn override cannot silently drop expert compute).
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts > 0 and cfg.n_moe_layers > 0:
        def moe_body(carry, mp):
            y, a = moe_block_apply(mp, carry[0], cfg)
            return (y, carry[1] + a), None

        (x, aux), _ = lax.scan(moe_body, (x, aux), params["moe"])

    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], cfg.eps)
    if return_hidden:
        return x, aux
    head = (params["wte"].T if cfg.tie_embeddings else params["head_w"])
    logits = jnp.einsum("bth,hv->btv", x, head.astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return logits, aux


def _chunked_ce(x, head, labels, chunk: int):
    """Cross-entropy without materializing [B, T, V] logits: scan over
    token chunks, rematerializing each chunk's logits in backward.

    This is the memory role of the reference's fused softmax-CE kernels
    (c_softmax_with_cross_entropy / ParallelCrossEntropy): the full-vocab
    logit tensor (the largest activation in GPT training by far) never
    lives in HBM; peak extra memory is [B, chunk, V].
    """
    B, T, H = x.shape
    n = max(1, T // chunk)
    while T % n:
        n -= 1
    c = T // n
    xs = jnp.moveaxis(x.reshape(B, n, c, H), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)

    @jax.checkpoint
    def body(acc, inp):
        xc, lc = inp
        logits = jnp.einsum("bth,hv->btv", xc, head,
                            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return acc + (lse - gold).sum(), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (B * T)


def loss_fn(params, tokens, labels, cfg: GPTConfig, sp_constraint=None,
            blocks_fn=None, loss_chunk: int = 512, emb_constraint=None):
    """Causal LM cross-entropy in fp32 (the reference's
    ParallelCrossEntropy semantics for mp-sharded logits come from GSPMD
    partitioning the log-sum-exp). ``loss_chunk`` > 0 streams the vocab
    projection (see _chunked_ce); 0 materializes full logits.

    On TPU the Pallas fused softmax-CE kernel (ops/pallas/fused_ce.py)
    replaces the chunked scan: profiling showed the scan spending
    ~44 ms/step at 350m/b8 materializing fp32 logit chunks — the fused
    kernel streams vocab tiles through VMEM instead (the reference's
    c_softmax_with_cross_entropy kernel role). One-device programs only:
    under a mesh GSPMD handles the chunked expression better."""
    if loss_chunk:
        hidden, aux = model_apply(params, tokens, cfg, sp_constraint,
                                  blocks_fn, return_hidden=True,
                                  emb_constraint=emb_constraint)
        head = (params["wte"].T if cfg.tie_embeddings else params["head_w"])
        from ..core.flags import GLOBAL_FLAGS
        from ..ops.pallas.flash_attention import single_device_program
        from ..ops.pallas.fused_ce import fused_ce_supported, fused_softmax_ce

        B, T = tokens.shape
        # one-device programs only (see single_device_program): under a
        # mesh the chunked expression shards cleanly, the kernel cannot
        use_fused = (jax.default_backend() == "tpu"
                     and single_device_program()
                     and sp_constraint is None and blocks_fn is None
                     and fused_ce_supported(B * T, cfg.hidden,
                                            cfg.vocab_size)
                     and (GLOBAL_FLAGS.get("use_fused_ce")
                          if GLOBAL_FLAGS.has("use_fused_ce") else True))
        if use_fused:
            nll_tok = fused_softmax_ce(  # tpu-lint: disable=TPL009 -- TPU-only loss-head kernel; the CE chain streams vocab tiles and has no jaxpr-level template
                hidden.reshape(B * T, cfg.hidden), head.astype(cfg.dtype),
                labels.reshape(B * T))
            return nll_tok.mean() + 0.01 * aux
        nll = _chunked_ce(hidden, head.astype(cfg.dtype), labels, loss_chunk)
        return nll + 0.01 * aux
    logits, aux = model_apply(params, tokens, cfg, sp_constraint, blocks_fn,
                              emb_constraint=emb_constraint)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold).mean()
    return nll + 0.01 * aux


# ---------------------------------------------------------------------------
# eager Layer wrapper
# ---------------------------------------------------------------------------

from ..core.dispatch import op
from ..core.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer


@op("gpt_forward")
def _gpt_forward_op(params, tokens, *, cfg):
    logits, aux = model_apply(params, tokens, cfg)
    return logits


@op("gpt_loss")
def _gpt_loss_op(params, tokens, labels, *, cfg):
    return loss_fn(params, tokens, labels, cfg)


class GPT(Layer):
    """Eager flagship model: owns the functional params as Parameters and
    dispatches the whole forward as one op — so eager stepping costs one
    XLA program instead of per-layer dispatch, and capture/AMP/autograd
    compose through the standard funnel."""

    def __init__(self, cfg: GPTConfig, seed: int = 0):
        super().__init__()
        self.cfg = cfg
        raw = init_params(cfg, jax.random.PRNGKey(seed))
        self._tree, leaves = self._register(raw)
        for i, leaf in enumerate(leaves):
            self.add_parameter(f"p{i}", leaf)

    def _register(self, raw):
        leaves, treedef = jax.tree.flatten(raw)
        params = [Parameter(a) for a in leaves]
        return treedef, params

    def _params_pytree(self):
        return jax.tree.unflatten(
            self._tree, [p for p in self.parameters()])

    def forward(self, tokens: Tensor) -> Tensor:
        return _gpt_forward_op(self._params_pytree(), tokens, cfg=self.cfg)

    def loss(self, tokens: Tensor, labels: Tensor) -> Tensor:
        return _gpt_loss_op(self._params_pytree(), tokens, labels,
                            cfg=self.cfg)
