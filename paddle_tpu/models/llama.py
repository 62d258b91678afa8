"""LLaMA: decoder LM with RoPE/RMSNorm/SwiGLU/GQA + a compiled inference
engine (BASELINE config 5: LLaMA-2 7B fused inference).

The reference serves this with fused CUDA kernels — fused_multi_transformer
(phi/kernels/fusion/gpu/fused_multi_transformer_kernel.cu), masked
multihead attention for decode, fused_rope / fused_rms_norm, and weight-only
quant gemm. TPU translation: prefill and decode are two jitted programs over
a stacked-layer param pytree; decode attends against a static-shape KV cache
updated with ``lax.dynamic_update_slice`` (the masked-MHA kernel becomes a
Pallas decode kernel over the kv-head-major cache, with an
XLA masked-dot fallback for unsupported shapes); rope/rmsnorm/swiglu fuse into
the surrounding matmuls. Weight-only int8 keeps weights quantized in HBM
and dequantizes in-register at each matmul (halves the HBM traffic that
bounds decode).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["LlamaConfig", "llama_presets", "init_llama_params",
           "llama_apply", "llama_loss", "LlamaForCausalLM",
           "quantize_weights_int8"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32          # < n_heads => GQA/MQA
    ffn_hidden: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16   # inference default; fp32 for training
    weight_only_int8: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads


def llama_presets(name: str) -> LlamaConfig:
    table = {
        "llama2-7b": dict(hidden=4096, n_layers=32, n_heads=32,
                          n_kv_heads=32, ffn_hidden=11008),
        "llama2-13b": dict(hidden=5120, n_layers=40, n_heads=40,
                           n_kv_heads=40, ffn_hidden=13824),
        "llama3-8b": dict(hidden=4096, n_layers=32, n_heads=32,
                          n_kv_heads=8, ffn_hidden=14336,
                          vocab_size=128256, rope_theta=500000.0),
        "tinyllama": dict(hidden=256, n_layers=4, n_heads=8, n_kv_heads=4,
                          ffn_hidden=688, vocab_size=1024, max_seq_len=512),
    }
    return LlamaConfig(**table[name])


def init_llama_params(cfg: LlamaConfig, key) -> dict:
    ks = iter(jax.random.split(key, 16))
    H, L = cfg.hidden, cfg.n_layers
    dH, nKV = cfg.head_dim, cfg.n_kv_heads
    F = cfg.ffn_hidden
    pd = cfg.param_dtype
    std = 0.02

    def nrm(shape, s=std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * s).astype(pd)

    return {
        "wte": nrm((cfg.vocab_size, H)),
        "blocks": {
            "attn_norm": jnp.ones((L, H), pd),
            "wq": nrm((L, H, cfg.n_heads * dH)),
            "wk": nrm((L, H, nKV * dH)),
            "wv": nrm((L, H, nKV * dH)),
            "wo": nrm((L, cfg.n_heads * dH, H), std / math.sqrt(2 * L)),
            "ffn_norm": jnp.ones((L, H), pd),
            "w_gate": nrm((L, H, F)),
            "w_up": nrm((L, H, F)),
            "w_down": nrm((L, F, H), std / math.sqrt(2 * L)),
        },
        "final_norm": jnp.ones((H,), pd),
        "head": nrm((H, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# building blocks (the reference's fused-kernel equivalents)
# ---------------------------------------------------------------------------

def rms_norm(x, g, eps):
    """fused_rms_norm equivalent (XLA fuses the expression);
    fp32 accumulation."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rope_angles(cfg: LlamaConfig, positions):
    """positions: [T] or [B] int; returns (cos, sin) [..., dH/2] fp32."""
    dH = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dH, 2,
                                               dtype=jnp.float32) / dH))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """fused_rotary_position_embedding equivalent. x: [..., nH, dH];
    cos/sin broadcastable [..., 1, dH/2] (rotate-half convention)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.concatenate([o1, o2], -1).astype(x.dtype)


def _deq(w, scale):
    return w.astype(jnp.bfloat16) * scale


def _mm(x, w, cfg):
    """Matmul with optional weight-only int8 (reference: weight_only_linear,
    incubate/nn/functional; scale per output column). Quantized weights
    route through quant_matmul: per-output-channel scales commute with
    the contraction, so dequant is fused into the matmul epilogue (one
    fp32 row multiply on the accumulator) instead of materializing a
    bf16 weight copy — the autotune-registered Pallas kernel on TPU,
    the same-algebra XLA path elsewhere."""
    if isinstance(w, tuple):  # (int8 weights, scales)
        from ..ops.pallas.quant_matmul import quant_matmul

        wq, scale = w
        return quant_matmul(x, wq, scale).astype(cfg.dtype)
    return jnp.einsum("...h,hk->...k", x, w.astype(cfg.dtype),
                      preferred_element_type=jnp.float32).astype(cfg.dtype)


def quantize_weights_int8(params: dict) -> dict:
    """Weight-only int8: per-column absmax scales (shared primitive with
    incubate weight_quantize). Norm gains and embeddings stay
    high-precision."""
    from ..ops.quant import absmax_quantize_int8

    def q(path, a):
        if a.ndim < 2 or "norm" in path or path == "wte":
            return a
        return absmax_quantize_int8(a, axis=-2, scale_dtype=jnp.bfloat16)

    out = {"wte": params["wte"], "final_norm": params["final_norm"],
           "head": q("head", params["head"]), "blocks": {}}
    for k, v in params["blocks"].items():
        out["blocks"][k] = q(k, v)
    return out


def _repeat_kv(x, n_rep):
    if n_rep == 1:
        return x
    B, T, nKV, dH = x.shape
    return jnp.repeat(x, n_rep, axis=2)


def _decode_weight_quant_flag() -> bool:
    """Init-time read of the decode weight-quant flag (default off):
    flips the decode engines onto per-output-channel int8 weights with
    epilogue dequant (ops/pallas/quant_matmul.py) without a config
    change, mirroring cfg.weight_only_int8."""
    from ..core.flags import GLOBAL_FLAGS

    return (bool(GLOBAL_FLAGS.get("decode_weight_quant"))
            if GLOBAL_FLAGS.has("decode_weight_quant") else False)


def block_apply(bp, x, cfg: LlamaConfig, cos, sin, use_flash=True,
                return_kv: bool = False):
    """Training/prefill block: full-sequence causal attention, written as
    the plain UNFUSED composition.  Kernel fusion is no longer wired by
    hand here: the compiler pass (paddle_tpu/compiler/) rediscovers the
    rms-epilogue and rope+flash chains in this function's jaxpr — plus
    the swiglu chain nobody ever hand-wired — and rewrites them to the
    fused Pallas entries when the enclosing apply goes through
    ``auto_fuse``.  ``return_kv=True`` additionally returns the
    (pre-repeat) rotated k/v — the prefill path uses this to fill the
    decode cache with the SAME block computation; the escaping rotated k
    is exactly what makes the compiler pick the q-only rope fusion
    there, reproducing the old rope_k=False hand-wiring."""
    B, T, H = x.shape
    nH, nKV, dH = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, bp["attn_norm"], cfg.rms_eps)
    q = _mm(h, bp["wq"], cfg).reshape(B, T, nH, dH)
    k = _mm(h, bp["wk"], cfg).reshape(B, T, nKV, dH)
    v = _mm(h, bp["wv"], cfg).reshape(B, T, nKV, dH)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    kf = _repeat_kv(k, nH // nKV)
    vf = _repeat_kv(v, nH // nKV)
    o = None
    if use_flash:
        from ..ops.pallas.flash_attention import (flash_attention_raw,
                                                  supported)

        if supported(q.shape, q.dtype):
            o = flash_attention_raw(q, kf, vf, causal=True)
    if o is None:
        o = _sdpa(q, kf, vf)
    attn_out = _mm(o.reshape(B, T, nH * dH), bp["wo"], cfg)
    x = x + attn_out
    h = rms_norm(x, bp["ffn_norm"], cfg.rms_eps)
    gate = _mm(h, bp["w_gate"], cfg)
    up = _mm(h, bp["w_up"], cfg)
    x = x + _mm(jax.nn.silu(gate.astype(jnp.float32)).astype(cfg.dtype) * up,
                bp["w_down"], cfg)
    if return_kv:
        return x, k, v
    return x


def _sdpa(q, k, v):
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    T = q.shape[1]
    mask = jnp.tril(jnp.ones((T, T), bool))
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _llama_apply_unfused(params, tokens, cfg: LlamaConfig,
                         remat: bool = True):
    B, T = tokens.shape
    x = params["wte"][tokens].astype(cfg.dtype)
    cos, sin = rope_angles(cfg, jnp.arange(T))
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]

    fn = functools.partial(block_apply, cfg=cfg, cos=cos, sin=sin)
    if remat:
        fn = jax.checkpoint(fn)

    def body(carry, bp):
        return fn(bp, carry), None

    x, _ = lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return _mm(x, params["head"], cfg).astype(jnp.float32)


def llama_apply(params, tokens, cfg: LlamaConfig, remat: bool = True):
    """Forward to logits, routed through the fusion compiler: the pass
    plans over the unfused trace and emits fused Pallas calls where the
    catalog matches (use_auto_fusion=0 runs the unfused composition
    verbatim)."""
    from ..compiler import fused_call

    return fused_call(("llama_apply", cfg, bool(remat)),
                      functools.partial(_llama_apply_unfused, cfg=cfg,
                                        remat=remat),
                      params, tokens)


def llama_loss(params, tokens, labels, cfg: LlamaConfig):
    logits = llama_apply(params, tokens, cfg)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - gold).mean()


# ---------------------------------------------------------------------------
# inference engine
# ---------------------------------------------------------------------------

def _prefill_unfused(params, tokens, cache, cfg: LlamaConfig):
    """Prefill trace body (unfused; the compiler pass fuses it — see
    LlamaForCausalLM._prefill_impl)."""
    B, T = tokens.shape
    x = params["wte"][tokens].astype(cfg.dtype)
    cos, sin = rope_angles(cfg, jnp.arange(T))
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]

    def body(carry, inp):
        x = carry
        bp, ck, cv = inp
        x, k, v = block_apply(bp, x, cfg, cos, sin, return_kv=True)
        ck = lax.dynamic_update_slice(
            ck, jnp.swapaxes(k, 1, 2).astype(ck.dtype), (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(
            cv, jnp.swapaxes(v, 1, 2).astype(cv.dtype), (0, 0, 0, 0))
        return x, (ck, cv)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"], cache["k"],
                                     cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _mm(x[:, -1:], params["head"], cfg).astype(jnp.float32)
    return logits[:, 0], {"k": ks, "v": vs}


def _decode_block(bp, x, cache_k, cache_v, pos, cfg: LlamaConfig, cos, sin):
    """One decode step for one block: x [B, 1, H]; cache [B, nKV, S, dH]
    (kv-head-major so the Pallas decode kernel reads it with no per-step
    transpose). The reference's masked_multihead_attention kernel."""
    B = x.shape[0]
    nH, nKV, dH = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, bp["attn_norm"], cfg.rms_eps)
    q = _mm(h, bp["wq"], cfg).reshape(B, 1, nH, dH)
    k = _mm(h, bp["wk"], cfg).reshape(B, 1, nKV, dH)
    v = _mm(h, bp["wv"], cfg).reshape(B, 1, nKV, dH)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    cache_k = lax.dynamic_update_slice(
        cache_k, jnp.swapaxes(k, 1, 2).astype(cache_k.dtype),
        (0, 0, pos, 0))
    cache_v = lax.dynamic_update_slice(
        cache_v, jnp.swapaxes(v, 1, 2).astype(cache_v.dtype),
        (0, 0, pos, 0))
    S = cache_k.shape[2]
    from ..ops.pallas.decode_attention import (decode_attention,
                                               decode_attention_supported)

    if decode_attention_supported(cache_k.shape, dH, num_heads=nH):
        # Pallas serving kernel: no GQA repeat materialization, k-loop
        # bounded by pos (ops/pallas/decode_attention.py)
        o = decode_attention(q[:, 0], cache_k, cache_v, pos,
                             1.0 / math.sqrt(dH))[:, None]
    else:
        G = nH // nKV
        kf = jnp.repeat(cache_k, G, axis=1)     # [B, nH, S, dH]
        vf = jnp.repeat(cache_v, G, axis=1)
        logits = jnp.einsum("bqhd,bhsd->bhqs", q, kf.astype(q.dtype),
                            preferred_element_type=jnp.float32) \
            / math.sqrt(dH)
        mask = (jnp.arange(S) <= pos)[None, None, None, :]
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, -1).astype(q.dtype)
        o = jnp.einsum("bhqs,bhsd->bqhd", p, vf.astype(q.dtype))
    x = x + _mm(o.reshape(B, 1, nH * dH), bp["wo"], cfg)
    h = rms_norm(x, bp["ffn_norm"], cfg.rms_eps)
    x = x + _mm(jax.nn.silu(_mm(h, bp["w_gate"], cfg).astype(jnp.float32)
                            ).astype(cfg.dtype) * _mm(h, bp["w_up"], cfg),
                bp["w_down"], cfg)
    return x, cache_k, cache_v


class LlamaForCausalLM:
    """Compiled prefill/decode inference engine.

    ``generate`` runs one jitted prefill over the prompt, then a jitted
    per-token decode loop against the static KV cache — the two-executable
    serving pattern that replaces the reference's AnalysisPredictor +
    fused_multi_transformer path.
    """

    def __init__(self, cfg: LlamaConfig, params: Optional[dict] = None,
                 seed: int = 0, max_batch: int = 1,
                 max_seq_len: Optional[int] = None):
        self.cfg = cfg
        self.params = params if params is not None else init_llama_params(
            cfg, jax.random.PRNGKey(seed))
        if (cfg.weight_only_int8 or _decode_weight_quant_flag()) \
                and not isinstance(self.params["blocks"]["wq"], tuple):
            self.params = quantize_weights_int8(self.params)
        self.max_batch = max_batch
        self.max_seq = max_seq_len or cfg.max_seq_len
        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        # only the scan length is static; temperature/top_p are traced
        # operands so per-request sampling configs reuse one executable
        self._decode_n = jax.jit(self._decode_n_impl, donate_argnums=(1,),
                                 static_argnames=("n", "greedy"))

    def _empty_cache(self, B):
        # kv-head-major [L, B, nKV, S, dH]: the decode kernel's native
        # layout (see _decode_block)
        L, S = self.cfg.n_layers, self.max_seq
        nKV, dH = self.cfg.n_kv_heads, self.cfg.head_dim
        z = jnp.zeros((L, B, nKV, S, dH), self.cfg.dtype)
        return {"k": z, "v": z}

    def _prefill_impl(self, params, tokens, cache):
        """Full-sequence forward (the shared block_apply, flash path
        included) that also fills the decode cache.  Routed through the
        fusion compiler: the rotated k escaping into the cache makes the
        rope template pick its q-only arm automatically."""
        from ..compiler import fused_call

        return fused_call(("llama_prefill", self.cfg),
                          functools.partial(_prefill_unfused, cfg=self.cfg),
                          params, tokens, cache)

    def _decode_impl(self, params, cache, token, pos):
        cfg = self.cfg
        B = token.shape[0]
        x = params["wte"][token].astype(cfg.dtype).reshape(B, 1, cfg.hidden)
        cos, sin = rope_angles(cfg, pos[None])
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]

        def body(carry, inp):
            x = carry
            bp, ck, cv = inp
            x, ck, cv = _decode_block(bp, x, ck, cv, pos, cfg, cos, sin)
            return x, (ck, cv)

        x, (ks, vs) = lax.scan(body, x, (params["blocks"], cache["k"],
                                         cache["v"]))
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = _mm(x, params["head"], cfg).astype(jnp.float32)
        return logits[:, 0], {"k": ks, "v": vs}

    def _decode_n_impl(self, params, cache, first_token, start_pos, key,
                       temperature, top_p, *, n, greedy):
        """n decode steps in ONE program (lax.scan): kills the per-token
        host/RPC dispatch that otherwise bounds serving latency — the
        fused_multi_transformer decode loop of the reference, compiled."""

        def tick(carry, _):
            cache, tok, pos, key = carry
            logits, cache = self._decode_impl(params, cache, tok, pos)
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, sub, temperature, top_p, greedy)
            return (cache, nxt, pos + 1, key), nxt

        (cache, _, _, _), toks = lax.scan(
            tick, (cache, first_token, start_pos, key), None, length=n)
        return toks, cache

    @staticmethod
    def _sample(logits, key, temperature, top_p, greedy: bool):
        """Branch-free over traced temperature/top_p; only greedy is a
        program variant."""
        if greedy:
            return jnp.argmax(logits, -1)
        logits = logits / jnp.maximum(jnp.asarray(temperature, jnp.float32),
                                      1e-6)
        from ..ops.nucleus import nucleus_keep

        sorted_logits = jnp.sort(logits, -1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, -1)
        # shared boundary rule (ops/nucleus.py); cutoff = smallest kept
        # sorted logit
        keep = nucleus_keep(probs, jnp.asarray(top_p, jnp.float32))
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), -1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(key, logits, -1)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0):
        """Prefill + greedy/nucleus decode. input_ids: [B, T] numpy/array."""
        tokens = jnp.asarray(input_ids)
        B, T = tokens.shape
        assert T + max_new_tokens <= self.max_seq, "exceeds KV cache length"
        cache = self._empty_cache(B)
        key = jax.random.PRNGKey(seed)
        greedy = temperature == 0.0
        temp_arr = jnp.asarray(temperature, jnp.float32)
        top_p_arr = jnp.asarray(top_p, jnp.float32)
        logits, cache = self._prefill(self.params, tokens, cache)
        key, sub = jax.random.split(key)
        first = self._sample(logits, sub, temp_arr, top_p_arr, greedy)
        if max_new_tokens == 1:
            return np.asarray(first)[:, None]
        if eos_token_id is None:
            # whole decode loop fused into one program; the first decoded
            # token is written at cache slot T (slots 0..T-1 hold the prompt)
            toks, cache = self._decode_n(
                self.params, cache, first, jnp.asarray(T, jnp.int32),
                key, temp_arr, top_p_arr, n=max_new_tokens - 1,
                greedy=greedy)
            return np.concatenate([np.asarray(first)[:, None],
                                   np.asarray(toks).T.reshape(
                                       B, max_new_tokens - 1)], axis=1)
        # early-exit path: per-token dispatch so eos can stop the loop
        out = [first]
        nxt = first
        pos = T - 1
        for _ in range(max_new_tokens - 1):
            pos += 1
            logits, cache = self._decode(self.params, cache, nxt,
                                         jnp.asarray(pos, jnp.int32))
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, sub, temp_arr, top_p_arr, greedy)
            out.append(nxt)
            if bool((nxt == eos_token_id).all()):
                break
        return np.stack([np.asarray(o) for o in out], axis=1)


# ---------------------------------------------------------------------------
# the serving engine's side of the model (models/seam.py has the contract)
# ---------------------------------------------------------------------------

class LlamaServing:
    """LLaMA behind the serving engine's model seam: k pages d-major and
    v pages token-major per kv head, the per-token page write, unified
    ragged-paged attention, SwiGLU.  Norms, projections, rotary and
    SwiGLU run over the tick's packed tokens ``[T, H]``; q, k and v are
    laid onto the rows' grid for the write and the attention, whose
    output comes back packed.  ``lora`` adds the per-row q/v
    low-rank deltas (``ctx["aid"]`` and four stacks beside the blocks).

    ``kv_quant``: the pages are symmetric int8 and each carries an fp32
    scale per kv head in two side planes (``k_scales``, ``v_scales``).
    A page fills incrementally, so its scale is a *running absmax*; a
    layer's write is

    1. scatter-max the planes with this chunk's token absmaxes
       (commutative: deterministic under duplicate page ids);
    2. rescale the int8 content already in every page a chunk straddles
       onto the new scale (an exact no-op where the scale did not grow;
       duplicate writes across rows of one request produce identical
       bytes, so order cannot matter);
    3. quantize the new tokens against the updated scale and write them
       per (page, offset) exactly like the fp pages.

    Speculative rollback and aborts need nothing more: a rejected
    draft's or a reused page's *content* is overwritten before it can be
    attended, and the engine zeroes a page's scale entries when the page
    goes to a new tenant (models/seam.py: side planes), so a stale
    absmax cannot cost a later tenant its precision."""

    unsupported = ()

    def __init__(self, cfg: LlamaConfig, lora: bool = False,
                 kv_quant: bool = False):
        self.cfg, self.lora, self.kv_quant = cfg, lora, kv_quant
        self.n_layers = cfg.n_layers

    def init_params(self, key) -> dict:
        return init_llama_params(self.cfg, key)

    def cache_spec(self, page_size: int):
        from .seam import CachePlane, CacheSpec, SidePlane

        nKV, d = self.cfg.n_kv_heads, self.cfg.head_dim
        planes = (CachePlane("k", (nKV, d, page_size), nKV * d),
                  CachePlane("v", (nKV, page_size, d), nKV * d))
        if not self.kv_quant:
            return CacheSpec(planes, self.cfg.dtype)
        # KV bytes per token drop from 2*itemsize*nKV*dH to 2*nKV*dH (+
        # the amortized scales): a fixed-byte pool holds ~2x the sequences
        return CacheSpec(
            planes, jnp.int8,
            side=tuple(SidePlane(n, (nKV,), jnp.float32)
                       for n in ("k_scales", "v_scales")),
            hash_tag=b":kvq8")

    def embed(self, params, tokens, positions):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["wte"][tokens].astype(cfg.dtype)  # [T, H]
            cos, sin = rope_angles(cfg, positions)       # [T, dH/2]
            cos, sin = cos[:, None, :], sin[:, None, :]
        return x, {"cos": cos, "sin": sin}

    def layer_groups(self, params, lora_stacks=None):
        from .seam import LayerGroup

        xs = (params["blocks"],)
        if self.lora:
            ast = lora_stacks
            xs = xs + (ast["aq"], ast["bq"], ast["av"], ast["bv"])
        return [LayerGroup(0, self.n_layers, xs)]

    def apply(self, x, kp, vp, base, inp, rows, pos0, n_valid, ctx,
              *scales):
        from ..ops.pallas.lora_matmul import lora_matmul
        from ..ops.pallas.paged_kv_write import paged_kv_write
        from ..ops.pallas.ragged_paged_attention import \
            ragged_paged_attention

        cfg = self.cfg
        T, lay = x.shape[0], ctx["layout"]
        nH, nKV, dH = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        cos, sin = ctx["cos"], ctx["sin"]
        sm_scale = 1.0 / math.sqrt(dH)
        if self.lora:
            bp, aq_l, bq_l, av_l, bv_l = inp
            aid = ctx["aid"]
        else:
            bp, = inp
        with jax.named_scope("layer/qkv"):
            h = rms_norm(x, bp["attn_norm"], cfg.rms_eps)
            q = _mm(h, bp["wq"], cfg)
            k = _mm(h, bp["wk"], cfg).reshape(T, nKV, dH)
            v = _mm(h, bp["wv"], cfg)
            if self.lora:
                # grouped BGMV: an adapter is a row's, so the deltas are
                # made on the grid, each row through ITS adapter's q/v
                # low-rank pair (slot 0 = exact +0.0 identity)
                hg = lay.to_grid(h)
                q = q + lay.to_packed(
                    lora_matmul(hg, aq_l, bq_l, aid)).astype(q.dtype)
                v = v + lay.to_packed(
                    lora_matmul(hg, av_l, bv_l, aid)).astype(v.dtype)
            q = apply_rope(q.reshape(T, nH, dH), cos, sin)
            k = apply_rope(k, cos, sin)
            # the write and the attention work by rows
            q, k, v = (lay.to_grid(a) for a in
                       (q, k, v.reshape(T, nKV, dH)))
        if self.kv_quant:
            o, kp, vp, scales = self._write_attend_int8(
                q, k, v, kp, vp, base, rows, pos0, n_valid, sm_scale,
                *scales)
        else:
            with jax.named_scope("layer/kv_write"):
                kp, vp = paged_kv_write(
                    kp, vp, k.astype(kp.dtype), v.astype(vp.dtype),
                    rows + base, pos0, n_valid, sink=base)
            with jax.named_scope("layer/attn"):
                o = ragged_paged_attention(q, kp, vp, rows + base, pos0,
                                           n_valid, sm_scale,
                                           k_layout="d_major")
        with jax.named_scope("layer/attn"):
            o = lay.to_packed(o).reshape(T, nH * dH)
            x = x + _mm(o, bp["wo"], cfg)
        with jax.named_scope("layer/mlp"):
            h = rms_norm(x, bp["ffn_norm"], cfg.rms_eps)
            x = x + _mm(jax.nn.silu(
                _mm(h, bp["w_gate"], cfg).astype(jnp.float32)).astype(
                    cfg.dtype) * _mm(h, bp["w_up"], cfg), bp["w_down"], cfg)
        return (x, kp, vp, None) + tuple(scales)

    def _write_attend_int8(self, q, k, v, kp, vp, base, rows, pos0,
                           n_valid, sm_scale, ksc, vsc):
        """The int8 pages' write and attention (the class docstring's
        three steps).  The pages are the carried ``[L*P, ...]`` pools
        (steps 2 and 3 add ``base``); the scale planes are this layer's
        ``[P, nKV]`` under its own page ids."""
        from ..ops.pallas.paged_kv_write import paged_kv_write
        from ..ops.pallas.ragged_paged_attention import \
            ragged_paged_attention
        from ..ops.quant import (kv_scale_update, quantize_to_scale,
                                 rescale_int8)

        C, qb, nKV, dH = k.shape
        bs, max_blocks = vp.shape[-2], rows.shape[1]
        positions = pos0[:, None] + jnp.arange(qb, dtype=jnp.int32)
        valid = jnp.arange(qb, dtype=jnp.int32)[None, :] < n_valid[:, None]
        pages = jnp.where(
            valid, jnp.take_along_axis(rows, positions // bs, axis=1),
            0).reshape(-1)                           # padding -> sink
        # every page this step's chunks might straddle (per row: the
        # first written page plus any the qb-token span can spill
        # into); entries past a row's span hit its future pages or the
        # sink, where rescaling is the exact no-op described above
        npw = (qb - 1) // bs + 2
        blk_rw = jnp.clip(
            pos0[:, None] // bs + jnp.arange(npw, dtype=jnp.int32)[None, :],
            0, max_blocks - 1)
        pages_rw = jnp.take_along_axis(rows, blk_rw, axis=1).reshape(-1)
        with jax.named_scope("layer/kv_write"):
            kf = k.reshape(C * qb, nKV, dH).astype(jnp.float32)
            vf = v.reshape(C * qb, nKV, dH).astype(jnp.float32)
            ksc_new = kv_scale_update(
                ksc, pages, jnp.max(jnp.abs(kf), axis=-1) / 127.0)
            vsc_new = kv_scale_update(
                vsc, pages, jnp.max(jnp.abs(vf), axis=-1) / 127.0)
            kp = kp.at[pages_rw + base].set(rescale_int8(
                kp[pages_rw + base],
                jnp.take(ksc, pages_rw, axis=0)[:, :, None, None],
                jnp.take(ksc_new, pages_rw, axis=0)[:, :, None, None]))
            vp = vp.at[pages_rw + base].set(rescale_int8(
                vp[pages_rw + base],
                jnp.take(vsc, pages_rw, axis=0)[:, :, None, None],
                jnp.take(vsc_new, pages_rw, axis=0)[:, :, None, None]))
            kp, vp = paged_kv_write(
                kp, vp,
                quantize_to_scale(
                    kf, jnp.take(ksc_new, pages, axis=0)[:, :, None]
                ).reshape(C, qb, nKV, dH),
                quantize_to_scale(
                    vf, jnp.take(vsc_new, pages, axis=0)[:, :, None]
                ).reshape(C, qb, nKV, dH),
                rows + base, pos0, n_valid, sink=base)
        with jax.named_scope("layer/attn"):
            # the attention finds a page's scale by the id it finds
            # the page by, and the scale planes stay one layer's
            # [P, nKV] (they ride SMEM): so it gets this layer's
            # pages under their local ids, which is a copy of them
            P = ksc.shape[0]
            o = ragged_paged_attention(
                q, lax.dynamic_slice_in_dim(kp, base, P),
                lax.dynamic_slice_in_dim(vp, base, P), rows, pos0,
                n_valid, sm_scale, k_layout="d_major",
                k_scales=ksc_new, v_scales=vsc_new)
        return o, kp, vp, (ksc_new, vsc_new)

    def head(self, params, x):
        with jax.named_scope("head"):
            return rms_norm(x, params["final_norm"], self.cfg.rms_eps)

    def logits(self, params, h):
        with jax.named_scope("head"):
            return _mm(h, params["head"], self.cfg).astype(jnp.float32)
