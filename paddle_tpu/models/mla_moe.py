"""Latent-attention (MLA) decoder with routed experts: the DeepSeek-V3
family's block (JoyAI-LLM-Flash, ``model_type: joyai_llm_flash``, is the
configuration the benchmark serves).

Layer equations (RMSNorm everywhere, pre-norm residual blocks; bf16
weights, activations and cache, fp32 accumulation, norms, softmax,
router and logits):

- MLA. ``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per
  head; ``[c_kv' | k_r'] = x W_kva``; ``c_kv = RMSNorm(c_kv')``;
  ``k_rope = RoPE(k_r')`` (one for all heads), ``q_rope = RoPE(q_rope)``;
  RoPE rotates adjacent pairs (2i, 2i+1).  *Expanded* (the full-sequence
  forward below): ``k_nope = c_kv W_uk``, ``v = c_kv W_uv``, score
  ``(q_nope.k_nope + q_rope.k_rope) / sqrt(d_nope + d_rope)``, causal
  softmax, ``out = concat(p v) W_o``.  *Absorbed* (the serving layer):
  ``q_lat = q_nope W_uk[h]^T``, score ``(q_lat.c_kv + q_rope.k_rope) /
  sqrt(.)``, ``o_lat = sum p c_kv``, ``o = o_lat W_uv[h]``: the same in
  exact arithmetic, and a token stores ``(c_kv, k_rope)`` only.
- Dense layers (the leading ``n_dense_layers``): SwiGLU of width
  ``ffn_hidden``.
- Expert layers (``models/routed_experts.py``, shared with the other
  families): sigmoid scores with a correction bias in the selection,
  weights normalised over the chosen and scaled by ``routed_scaling``,
  one shared expert added whole.  **The layer is told which experts it
  holds** (``cfg.held = (first, count)``).
- MTP module: ``h' = [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)] W_eh``, one
  expert-kind layer, its own final norm, the shared embedding and head.

Layout notes (none changes the mathematics): ``W_kvb`` is stored as its
two halves ``wk_b`` / ``wv_b`` ``[kv_rank, heads, d]``; RoPE'd vectors
are kept de-interleaved (all even-pair firsts, then all seconds) in q
and k alike, so every score is the published one.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any


import jax
import jax.numpy as jnp
from jax import lax

from . import routed_experts
from .llama import rms_norm
from .routed_experts import EXPERT_STACKS
from .seam import CachePlane, CacheSpec, LayerGroup

__all__ = ["MlaMoeConfig", "init_mla_moe_params", "mla_moe_apply",
           "mla_moe_hidden", "mtp_logits", "moe_ffn", "MlaMoeServing"]


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 129280
    hidden: int = 2048
    n_layers: int = 40
    n_dense_layers: int = 1           # first_k_dense_replace
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_hidden: int = 7168            # the dense layers' width
    moe_hidden: int = 768             # one expert's width
    n_routed_experts: int = 256       # the router's width, always whole
    n_shared_experts: int = 1
    experts_per_token: int = 8
    routed_scaling: float = 2.5
    norm_topk_prob: bool = True
    n_mtp: int = 1                    # num_nextn_predict_layers
    rope_theta: float = 32e6
    rms_eps: float = 1e-6
    max_seq_len: int = 131072
    # the experts this chip holds, (first, count); None = all of them
    held: Any = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.held is None:
            object.__setattr__(self, "held", (0, self.n_routed_experts))
        first, count = self.held
        if not 0 <= first < first + count <= self.n_routed_experts:
            raise ValueError(f"held {self.held} is not a range of the "
                             f"{self.n_routed_experts} routed experts")

    @classmethod
    def from_hf(cls, c: dict, **over) -> "MlaMoeConfig":
        """From the keys of the model's ``config.json``.  Variants of the
        family that this file does not implement are refused."""
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("rope_interleave", True),
                          ("rope_scaling", None), ("moe_layer_freq", 1)):
            if c.get(key, want) != want:
                raise NotImplementedError(
                    f"{key}={c[key]!r}: models/mla_moe.py implements "
                    f"{key}={want!r} only")
        kw = dict(
            vocab_size=c["vocab_size"], hidden=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_dense_layers=c["first_k_dense_replace"],
            n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_dim=c["qk_nope_head_dim"],
            qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
            ffn_hidden=c["intermediate_size"],
            moe_hidden=c["moe_intermediate_size"],
            n_routed_experts=c["n_routed_experts"],
            n_shared_experts=c["n_shared_experts"],
            experts_per_token=c["num_experts_per_tok"],
            routed_scaling=c["routed_scaling_factor"],
            norm_topk_prob=c["norm_topk_prob"],
            n_mtp=c["num_nextn_predict_layers"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            max_seq_len=c["max_position_embeddings"])
        kw.update(over)
        return cls(**kw)

    @property
    def routing(self) -> routed_experts.Routing:
        return routed_experts.Routing(
            k=self.experts_per_token, held=self.held, dtype=self.dtype,
            scaling=self.routed_scaling, norm_topk=self.norm_topk_prob)

    def serving_model(self) -> "MlaMoeServing":
        return MlaMoeServing(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _attn_shapes(cfg: MlaMoeConfig) -> dict:
    H, nH = cfg.hidden, cfg.n_heads
    return {"wq_a": (H, cfg.q_lora_rank),
            "wq_b": (cfg.q_lora_rank, nH * (cfg.qk_nope_dim
                                            + cfg.qk_rope_dim)),
            "wkv_a": (H, cfg.kv_lora_rank + cfg.qk_rope_dim),
            "wk_b": (cfg.kv_lora_rank, nH, cfg.qk_nope_dim),
            "wv_b": (cfg.kv_lora_rank, nH, cfg.v_head_dim),
            "wo": (nH * cfg.v_head_dim, H)}


def layer_shapes(cfg: MlaMoeConfig, kind: str) -> dict:
    """Matrix shapes of one ``"dense"`` or ``"moe"`` layer (norm gains and
    the router's bias apart)."""
    H, Fm = cfg.hidden, cfg.moe_hidden
    out = _attn_shapes(cfg)
    if kind == "dense":
        F = cfg.ffn_hidden
        out.update(w_gate=(H, F), w_up=(H, F), w_down=(F, H))
    else:
        n, Fs = cfg.held[1], cfg.n_shared_experts * Fm
        out.update(router=(H, cfg.n_routed_experts),
                   we_gate=(n, H, Fm), we_up=(n, H, Fm), we_down=(n, Fm, H),
                   ws_gate=(H, Fs), ws_up=(H, Fs), ws_down=(Fs, H))
    return out


RESIDUAL_OUT = ("wo", "w_down", "we_down", "ws_down")


def norm_gains(cfg: MlaMoeConfig) -> dict:
    return {"attn_norm": (cfg.hidden,), "q_norm": (cfg.q_lora_rank,),
            "kv_norm": (cfg.kv_lora_rank,), "ffn_norm": (cfg.hidden,)}


def _init_layer(cfg: MlaMoeConfig, key, kind: str) -> dict:
    std, pd = 0.02, cfg.param_dtype
    resid = std / math.sqrt(2 * cfg.n_layers)
    shapes = layer_shapes(cfg, kind)
    lp = {name: (jax.random.normal(k, shape, jnp.float32)
                 * (resid if name in RESIDUAL_OUT else std)).astype(pd)
          for k, (name, shape) in zip(
              jax.random.split(key, len(shapes) + 1), shapes.items())}
    lp.update({name: jnp.ones(shape, pd)
               for name, shape in norm_gains(cfg).items()})
    if kind == "moe":
        lp["router_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(key, 99), (cfg.n_routed_experts,),
            jnp.float32)
    return lp


def init_mla_moe_params(cfg: MlaMoeConfig, key) -> dict:
    """``dense`` and ``moe`` are stacked over their layers; ``mtp`` is
    there when the config has a multi-token-prediction module."""
    k_out, k_dense, k_moe, k_mtp = jax.random.split(key, 4)
    H, V, pd = cfg.hidden, cfg.vocab_size, cfg.param_dtype
    n_moe = cfg.n_layers - cfg.n_dense_layers

    def nrm(k, shape):
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(pd)

    params = {
        "wte": nrm(jax.random.fold_in(k_out, 0), (V, H)),
        "head": nrm(jax.random.fold_in(k_out, 1), (H, V)),
        "final_norm": jnp.ones((H,), pd),
        "dense": jax.vmap(lambda k: _init_layer(cfg, k, "dense"))(
            jax.random.split(k_dense, cfg.n_dense_layers)),
        "moe": jax.vmap(lambda k: _init_layer(cfg, k, "moe"))(
            jax.random.split(k_moe, n_moe)),
    }
    if cfg.n_mtp:
        params["mtp"] = {
            "enorm": jnp.ones((H,), pd), "hnorm": jnp.ones((H,), pd),
            "eh_proj": nrm(jax.random.fold_in(k_mtp, 0), (2 * H, H)),
            "layer": _init_layer(cfg, jax.random.fold_in(k_mtp, 1), "moe"),
            "final_norm": jnp.ones((H,), pd)}
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _mm(x, w, cfg):
    return routed_experts.mm(x, w, cfg.dtype)


def _swiglu(h, w_gate, w_up, w_down, cfg):
    return routed_experts.swiglu(h, w_gate, w_up, w_down, cfg.dtype)


def rope_angles(cfg: MlaMoeConfig, positions):
    """(cos, sin) ``[..., 1, d_rope/2]`` fp32 for integer positions."""
    d = cfg.qk_rope_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2,
                                               dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope_pairs(x, cos, sin):
    """Rotate the adjacent pairs (2i, 2i+1) of ``x [..., heads, d]``; the
    result is kept de-interleaved, firsts then seconds (module
    docstring)."""
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla_project(h, lp, cfg: MlaMoeConfig, cos, sin):
    """The projections both forms share, from the normed input ``h
    [..., H]``: q_nope ``[..., nH, d_nope]``, q_rope ``[..., nH, d_rope]``,
    c_kv ``[..., kv_rank]``, k_rope ``[..., d_rope]``."""
    nH, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    with jax.named_scope("layer/mla_q"):
        cq = rms_norm(_mm(h, lp["wq_a"], cfg), lp["q_norm"], cfg.rms_eps)
        q = _mm(cq, lp["wq_b"], cfg).reshape(h.shape[:-1] + (nH, dn + dr))
        q_nope = q[..., :dn]
        q_rope = apply_rope_pairs(q[..., dn:], cos, sin).astype(cfg.dtype)
    with jax.named_scope("layer/mla_kv"):
        kv = _mm(h, lp["wkv_a"], cfg)
        c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"],
                        cfg.rms_eps)
        k_rope = apply_rope_pairs(kv[..., None, cfg.kv_lora_rank:], cos,
                                  sin)[..., 0, :].astype(cfg.dtype)
    return q_nope, q_rope, c_kv, k_rope


def _sm_scale(cfg: MlaMoeConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)


def mla_expanded(h, lp, cfg: MlaMoeConfig, cos, sin):
    """Causal attention over a whole sequence ``h [B, T, H]``, expanded
    form; returns the block's output before the residual add."""
    B, T, _ = h.shape
    q_nope, q_rope, c_kv, k_rope = mla_project(h, lp, cfg, cos, sin)
    f32 = jnp.float32
    k_nope = jnp.einsum("btl,lhd->bthd", c_kv, lp["wk_b"],
                        preferred_element_type=f32).astype(cfg.dtype)
    v = jnp.einsum("btl,lhd->bthd", c_kv, lp["wv_b"],
                   preferred_element_type=f32).astype(cfg.dtype)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                    preferred_element_type=f32)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                      preferred_element_type=f32)) * _sm_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    p = jax.nn.softmax(s, -1).astype(cfg.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   preferred_element_type=f32).astype(cfg.dtype)
    return _mm(o.reshape(B, T, -1), lp["wo"], cfg)


def moe_ffn(h, lp, cfg: MlaMoeConfig, valid=None, stack=None):
    """``routed_experts.moe_ffn`` under this family's routing."""
    return routed_experts.moe_ffn(h, lp, cfg.routing, valid, stack)


def _ffn(x, lp, cfg, kind, valid=None, stack=None):
    """``x + FFN(RMSNorm(x))`` for ``x [..., H]``; (x, expert counts)."""
    h = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
    if kind == "dense":
        with jax.named_scope("layer/mlp"):
            return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"],
                               cfg), None
    y, sizes = moe_ffn(h.reshape(-1, h.shape[-1]), lp, cfg,
                       None if valid is None else valid.reshape(-1), stack)
    return x + y.reshape(x.shape), sizes


# ---------------------------------------------------------------------------
# the full-sequence forward (expanded attention, no cache): the oracle of
# the engine's tests, and what training would differentiate
# ---------------------------------------------------------------------------

def _block(x, lp, cfg, kind, cos, sin):
    x = x + mla_expanded(rms_norm(x, lp["attn_norm"], cfg.rms_eps), lp, cfg,
                         cos, sin)
    return _ffn(x, lp, cfg, kind)[0]


def mla_moe_hidden(params, tokens, cfg: MlaMoeConfig):
    """tokens ``[B, T]`` -> the last layer's residual stream ``[B, T, H]``
    (before the final norm)."""
    T = tokens.shape[1]
    cos, sin = rope_angles(cfg, jnp.arange(T, dtype=jnp.int32))
    x = params["wte"][tokens].astype(cfg.dtype)
    for kind in ("dense", "moe"):
        x, _ = lax.scan(
            lambda x, lp, kind=kind: (_block(x, lp, cfg, kind, cos, sin),
                                      None), x, params[kind])
    return x


def _head(params, x, norm, cfg):
    h = rms_norm(x, norm, cfg.rms_eps).astype(cfg.dtype)
    return jnp.einsum("...h,hv->...v", h, params["head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def mla_moe_apply(params, tokens, cfg: MlaMoeConfig):
    """tokens ``[B, T]`` -> next-token logits ``[B, T, V]`` fp32."""
    return _head(params, mla_moe_hidden(params, tokens, cfg),
                 params["final_norm"], cfg)


def mtp_logits(params, hidden, tokens, cfg: MlaMoeConfig):
    """The multi-token-prediction module: from the main model's
    ``hidden [B, T, H]`` (``mla_moe_hidden``) and the same ``tokens [B,
    T]``, logits ``[B, T-1, V]`` whose row i predicts token i+2.  The
    pair is taken embedding first, ``[RMSNorm(Emb(t_{i+1})) ;
    RMSNorm(h_i)]`` (the published implementations' order; the paper
    writes the hidden state first), and ``h_i`` is the residual stream
    before the main model's final norm."""
    m = params["mtp"]
    emb = params["wte"][tokens[:, 1:]].astype(cfg.dtype)
    pair = jnp.concatenate([rms_norm(emb, m["enorm"], cfg.rms_eps),
                            rms_norm(hidden[:, :-1], m["hnorm"],
                                     cfg.rms_eps)], -1)
    x = _mm(pair, m["eh_proj"], cfg)
    cos, sin = rope_angles(cfg, jnp.arange(x.shape[1], dtype=jnp.int32))
    x = _block(x, m["layer"], cfg, "moe", cos, sin)
    return _head(params, x, m["final_norm"], cfg)


# ---------------------------------------------------------------------------
# the serving engine's side (models/seam.py has the contract)
# ---------------------------------------------------------------------------

class MlaMoeServing:
    """Absorbed MLA over latent pages and the held experts' part of each
    expert layer, one layer at a time: projections, the router and the
    experts over the tick's packed tokens ``[T, H]``, the latent write
    and the attention on the engine's ``[C, qb]`` grid (models/seam.py).

    A token stores ``c_kv`` (kv_rank values) and ``k_rope`` (d_rope
    values) per layer and nothing else.  The two live in the engine's
    pair of planes, split where the lanes require it: ``k_rope`` d-major
    ``[d_rope, bs]`` and ``c_kv`` token-major ``[bs, kv_rank]`` — the
    scores contract ``c_kv`` transposed, the values read it as it lies,
    so the latent is stored once."""

    unsupported = ("kv_quant", "lora", "constrained", "speculative",
                   "page_shipment", "weight_only_int8")
    stats_keys = routed_experts.STATS_KEYS

    def __init__(self, cfg: MlaMoeConfig):
        if cfg.n_dense_layers > 1:
            raise NotImplementedError(
                f"n_dense_layers={cfg.n_dense_layers}: the serving model "
                f"applies one leading dense layer at most")
        self.cfg = cfg
        self.n_layers = cfg.n_layers

    def init_params(self, key) -> dict:
        return init_mla_moe_params(dataclasses.replace(self.cfg, n_mtp=0),
                                   key)

    def cache_spec(self, page_size: int) -> CacheSpec:
        cfg = self.cfg
        return CacheSpec(
            (CachePlane("k_rope", (cfg.qk_rope_dim, page_size),
                        cfg.qk_rope_dim),
             CachePlane("c_kv", (page_size, cfg.kv_lora_rank),
                        cfg.kv_lora_rank)), cfg.dtype)

    def embed(self, params, tokens, positions):
        with jax.named_scope("embed"):
            x = params["wte"][tokens].astype(self.cfg.dtype)
            cos, sin = rope_angles(self.cfg, positions)
        # the held experts of every layer, where they lie (moe_ffn)
        experts = {name: params["moe"][name].reshape(
            (-1,) + params["moe"][name].shape[2:]) for name in EXPERT_STACKS}
        return x, {"cos": cos, "sin": sin, "experts": experts}

    def layer_groups(self, params) -> list:
        nd = self.cfg.n_dense_layers
        n_moe = self.cfg.n_layers - nd
        groups = [LayerGroup(nd, n_moe, dict(
            {name: w for name, w in params["moe"].items()
             if name not in EXPERT_STACKS},
            index=jnp.arange(n_moe, dtype=jnp.int32)))]
        if nd:
            # the one leading dense layer is applied where it stands
            groups.insert(0, LayerGroup(0, 1, jax.tree.map(
                lambda a: a.reshape(a.shape[1:]), params["dense"]),
                stacked=False))
        return groups

    def apply(self, x, kp, vp, base, inp, rows, pos0, n_valid, ctx):
        from ..ops.pallas.mla_paged_attention import mla_paged_attention
        from ..ops.pallas.paged_kv_write import paged_kv_write

        cfg, lp, lay = self.cfg, inp, ctx["layout"]
        kind = "moe" if "router" in lp else "dense"
        f32 = jnp.float32
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q_nope, q_rope, c_kv, k_rope = mla_project(
            h, lp, cfg, ctx["cos"], ctx["sin"])
        with jax.named_scope("layer/mla_q"):
            q_lat = jnp.einsum("thd,lhd->thl", q_nope, lp["wk_b"],
                               preferred_element_type=f32).astype(cfg.dtype)
        # the write and the attention work by rows
        q_lat, q_rope, c_kv, k_rope = (
            lay.to_grid(a) for a in (q_lat, q_rope, c_kv, k_rope))
        with jax.named_scope("layer/latent_write"):
            # paged_kv_write's k is d-major, its v token-major: k_rope
            # and c_kv ride them as one "head" each
            kp4, vp4 = paged_kv_write(
                kp[:, None], vp[:, None], k_rope[:, :, None],
                c_kv[:, :, None], rows + base, pos0, n_valid, sink=base)
            kp, vp = kp4[:, 0], vp4[:, 0]
        with jax.named_scope("layer/attn"):
            o_lat = lay.to_packed(mla_paged_attention(
                q_lat, q_rope, vp, kp, rows + base, pos0, n_valid,
                _sm_scale(cfg)))
            o = jnp.einsum("thl,lhd->thd", o_lat, lp["wv_b"],
                           preferred_element_type=f32).astype(cfg.dtype)
            x = x + _mm(o.reshape(o.shape[0], -1), lp["wo"], cfg)
        x, sizes = _ffn(x, lp, cfg, kind, lay.valid,
                        (ctx["experts"], lp["index"]) if kind == "moe"
                        else None)
        if kind == "moe":
            sizes = sizes, routed_experts.row_tile(
                x.shape[0], ctx["experts"], cfg.routing)
        return x, kp, vp, sizes

    def head(self, params, x):
        with jax.named_scope("head"):
            return rms_norm(x, params["final_norm"], self.cfg.rms_eps)

    def logits(self, params, h):
        with jax.named_scope("head"):
            return jnp.einsum(
                "...h,hv->...v", h.astype(self.cfg.dtype),
                params["head"].astype(self.cfg.dtype),
                preferred_element_type=jnp.float32)

    def tick_stats(self, ys, n_tokens: int) -> dict:
        """From a tick's tokens per held expert per layer (the layer
        groups' counters; dense layers have none)."""
        return routed_experts.held_expert_stats(ys, n_tokens,
                                                self.cfg.routing)
