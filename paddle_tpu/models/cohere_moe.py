"""Window and global attention layers interleaved, a parallel block over
routed experts: the Cohere2-MoE family's layer (``command-a-plus-05-2026``,
``model_type: cohere2_moe``, is the configuration the benchmark serves).

For layer ``l`` of kind window or global (``layer_types``), token at
position ``p``, stream ``x``; bf16 weights, activations, residual and
cache, fp32 accumulation, norm, softmax, router and logits:

- ``h = LayerNorm(x)``: ``(x - mean) / sqrt(var + eps) * g``, no bias;
  the layer's ONE norm (parallel block).
- ``q = h Wq`` as ``n_heads`` heads, ``k = h Wk``, ``v = h Wv`` as
  ``n_kv_heads``; query head ``i`` uses kv head ``i // (n_heads //
  n_kv_heads)``.  In a window layer q and k are rotated over the whole
  head by adjacent pairs ``(2i, 2i+1)`` with angle ``p * theta^(-2i/d)``
  (``rope_gptj``); in a global layer they are not touched (no position
  encoding at all).
- ``a = softmax(q k^T / sqrt(d) + mask) v Wo``; the mask admits key
  ``p' <= p``, and in a window layer also ``p - p' < sliding_window`` (a
  token sees itself and the ``window - 1`` before it).
- On the same ``h`` the routed-expert layer of ``models/routed_experts.py``:
  sigmoid scores, no correction bias, weights normalised over the chosen,
  scaling 1, the shared experts averaged (their sum times ``1 / n``).
  **The layer is told which experts it holds** (``cfg.held``).
- ``x <- x + a + routed + shared``: one norm, one add.
- After the last layer the same LayerNorm with its own gain; ``logits =
  h E^T * logit_scale`` with ``E`` the embedding (tied).

Layout note (it changes no score): every head's columns of ``Wq`` and
``Wk`` are kept de-interleaved, all pair firsts then all pair seconds, so
that the rotation of adjacent pairs is a rotation of the head's two
halves.  q and k are permuted alike, so ``q . k`` is the published one in
window and global layers both.  ``to_program_layout`` makes the stored
form from matrices laid out as the source has them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from . import routed_experts
from .llama import apply_rope
from .llama import rope_angles as llama_rope_angles
from .routed_experts import EXPERT_STACKS
from .seam import CacheClass, CachePlane, CacheSpec, LayerGroup

__all__ = ["CohereMoeConfig", "CohereMoeServing", "cohere_moe_apply",
           "init_cohere_moe_params", "layer_norm", "to_program_layout"]

KINDS = {"sliding_attention": "window", "full_attention": "global"}


@dataclasses.dataclass(frozen=True)
class CohereMoeConfig:
    vocab_size: int = 262144
    hidden: int = 4096
    layer_types: tuple = ("sliding_attention",) * 3 + ("full_attention",)
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    expert_hidden: int = 4096         # one expert's width
    n_routed_experts: int = 128       # the router's width, always whole
    n_shared_experts: int = 4
    experts_per_token: int = 8
    norm_topk_prob: bool = True
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq_len: int = 200000
    # the experts this chip holds, (first, count); None = all of them
    held: Any = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.held is None:
            object.__setattr__(self, "held", (0, self.n_routed_experts))
        first, count = self.held
        if not 0 <= first < first + count <= self.n_routed_experts:
            raise ValueError(f"held {self.held} is not a range of the "
                             f"{self.n_routed_experts} routed experts")
        if set(self.layer_types) - set(KINDS):
            raise ValueError(f"layer_types {set(self.layer_types)}: "
                             f"only {sorted(KINDS)} are implemented")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kinds(self) -> tuple:
        """"window" or "global" per layer."""
        return tuple(KINDS[t] for t in self.layer_types)

    @property
    def runs(self) -> tuple:
        """(kind, index of the run's first layer among its kind's, count)
        for each run of consecutive layers of one kind, in order."""
        out, seen = [], {"window": 0, "global": 0}
        for kind in self.kinds:
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(r) for r in out)

    @property
    def routing(self) -> routed_experts.Routing:
        return routed_experts.Routing(
            k=self.experts_per_token, held=self.held, dtype=self.dtype,
            norm_topk=self.norm_topk_prob,
            shared_scale=1.0 / self.n_shared_experts)

    @classmethod
    def from_hf(cls, c: dict, **over) -> "CohereMoeConfig":
        """From the keys of the model's ``config.json``; ``layer_types``
        is cut to ``num_hidden_layers``.  Variants of the family that
        this file does not implement are refused."""
        for key, want in (("expert_selection_fn", "sigmoid"),
                          ("use_parallel_block", True),
                          ("use_qk_norm", False), ("attention_bias", False),
                          ("position_embedding_type", "rope_gptj"),
                          ("rotary_pct", 1), ("first_k_dense_replace", 0),
                          ("tie_word_embeddings", True),
                          ("shared_expert_combination_strategy", "average"),
                          ("use_gated_activation", True),
                          ("hidden_act", "silu")):
            if c.get(key, want) != want:
                raise NotImplementedError(
                    f"{key}={c[key]!r}: models/cohere_moe.py implements "
                    f"{key}={want!r} only")
        kw = dict(
            vocab_size=c["vocab_size"], hidden=c["hidden_size"],
            layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            sliding_window=c["sliding_window"],
            rope_theta=float(c["rope_theta"]),
            expert_hidden=c["intermediate_size"],
            n_routed_experts=c["num_experts"],
            n_shared_experts=c["num_shared_experts"],
            experts_per_token=c["num_experts_per_tok"],
            norm_topk_prob=c["norm_topk_prob"],
            layer_norm_eps=c["layer_norm_eps"],
            logit_scale=float(c.get("logit_scale", 1.0)),
            max_seq_len=c["max_position_embeddings"])
        kw.update(over)
        return cls(**kw)

    def serving_model(self) -> "CohereMoeServing":
        return CohereMoeServing(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def layer_shapes(cfg: CohereMoeConfig) -> dict:
    """Matrix shapes of one layer (its norm's gain apart)."""
    H, F = cfg.hidden, cfg.expert_hidden
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    n, Fs = cfg.held[1], cfg.n_shared_experts * F
    return {"wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wo": (q, H),
            "router": (H, cfg.n_routed_experts),
            "we_gate": (n, H, F), "we_up": (n, H, F), "we_down": (n, F, H),
            "ws_gate": (H, Fs), "ws_up": (H, Fs), "ws_down": (Fs, H)}


RESIDUAL_OUT = ("wo", "we_down", "ws_down")


def to_program_layout(w, n_heads: int, head_dim: int):
    """``Wq`` / ``Wk`` ``[H, heads * d]`` as the source lays its columns
    out -> every head's columns de-interleaved (module docstring)."""
    H = w.shape[0]
    w = w.reshape(H, n_heads, head_dim // 2, 2)
    return jnp.swapaxes(w, 2, 3).reshape(H, n_heads * head_dim)


def _init_layer(cfg: CohereMoeConfig, key) -> dict:
    std, pd = 0.02, cfg.param_dtype
    resid = std / math.sqrt(2 * cfg.n_layers)
    shapes = layer_shapes(cfg)
    lp = {name: (jax.random.normal(k, shape, jnp.float32)
                 * (resid if name in RESIDUAL_OUT else std)).astype(pd)
          for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                      shapes.items())}
    lp["norm"] = jnp.ones((cfg.hidden,), pd)
    return lp


def init_cohere_moe_params(cfg: CohereMoeConfig, key) -> dict:
    """``window`` and ``global`` are stacked over the layers of their
    kind, in the model's order; the head is the embedding."""
    k_emb, k_w, k_g = jax.random.split(key, 3)
    kinds = cfg.kinds
    params = {
        "wte": (0.02 * jax.random.normal(
            k_emb, (cfg.vocab_size, cfg.hidden), jnp.float32)).astype(
                cfg.param_dtype),
        "final_norm": jnp.ones((cfg.hidden,), cfg.param_dtype)}
    for kind, k in (("window", k_w), ("global", k_g)):
        n = kinds.count(kind)
        if n:
            params[kind] = jax.vmap(lambda kk: _init_layer(cfg, kk))(
                jax.random.split(k, n))
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def layer_norm(x, g, eps):
    """Bias-free LayerNorm in fp32."""
    x32 = x.astype(jnp.float32)
    xc = x32 - x32.mean(-1, keepdims=True)
    y = xc * lax.rsqrt((xc * xc).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w, cfg):
    return routed_experts.mm(x, w, cfg.dtype)


def rope_angles(cfg: CohereMoeConfig, positions):
    """(cos, sin) ``[..., 1, d/2]`` fp32 for integer positions: LLaMA's
    angles (``head_dim``, ``rope_theta``), one for all heads."""
    cos, sin = llama_rope_angles(cfg, positions)
    return cos[..., None, :], sin[..., None, :]


def project_qkv(h, lp, cfg: CohereMoeConfig, kind: str, cos, sin):
    """q ``[..., nH, d]``, k and v ``[..., nKV, d]`` from the normed
    input, rotated where the layer's kind says so."""
    nH, nKV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("layer/qkv"):
        q = _mm(h, lp["wq"], cfg).reshape(h.shape[:-1] + (nH, d))
        k = _mm(h, lp["wk"], cfg).reshape(h.shape[:-1] + (nKV, d))
        v = _mm(h, lp["wv"], cfg).reshape(h.shape[:-1] + (nKV, d))
        if kind == "window":
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return q, k, v


def _join(x, a, y, cfg):
    """The parallel block's one add."""
    f32 = jnp.float32
    return (x.astype(f32) + a.astype(f32) + y.astype(f32)).astype(cfg.dtype)


# ---------------------------------------------------------------------------
# the full-sequence forward (dense attention, no cache): the oracle of the
# engine's tests
# ---------------------------------------------------------------------------

def _block(x, lp, cfg: CohereMoeConfig, kind: str, cos, sin):
    B, T, _ = x.shape
    f32 = jnp.float32
    h = layer_norm(x, lp["norm"], cfg.layer_norm_eps)
    q, k, v = project_qkv(h, lp, cfg, kind, cos, sin)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, T, cfg.n_kv_heads, G, cfg.head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=f32) / math.sqrt(cfg.head_dim)
    qp, kp = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = kp <= qp
    if kind == "window":
        mask &= qp - kp < cfg.sliding_window
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1).astype(cfg.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                   preferred_element_type=f32).astype(cfg.dtype)
    a = _mm(o.reshape(B, T, -1), lp["wo"], cfg)
    y, _ = routed_experts.moe_ffn(h.reshape(B * T, -1), lp, cfg.routing)
    return _join(x, a, y.reshape(x.shape), cfg)


def cohere_moe_apply(params, tokens, cfg: CohereMoeConfig):
    """tokens ``[B, T]`` -> next-token logits ``[B, T, V]`` fp32."""
    cos, sin = rope_angles(cfg, jnp.arange(tokens.shape[1], dtype=jnp.int32))
    x = params["wte"][tokens].astype(cfg.dtype)
    seen = {"window": 0, "global": 0}
    for kind in cfg.kinds:
        lp = jax.tree.map(lambda a, i=seen[kind]: a[i], params[kind])
        x = _block(x, lp, cfg, kind, cos, sin)
        seen[kind] += 1
    return _logits(params, layer_norm(x, params["final_norm"],
                                      cfg.layer_norm_eps), cfg)


def _logits(params, h, cfg):
    out = jnp.einsum("...h,vh->...v", h.astype(cfg.dtype),
                     params["wte"].astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    return out if cfg.logit_scale == 1.0 else out * cfg.logit_scale


# ---------------------------------------------------------------------------
# the serving engine's side (models/seam.py has the contract)
# ---------------------------------------------------------------------------

class CohereMoeServing:
    """Two cache classes (models/seam.py): ``global``, the layers that
    read every token, and ``window``, those that read the last
    ``sliding_window`` and let go of the rest.  Norm, projections, router
    and experts run over the tick's packed tokens ``[T, H]``; the page
    write and the attention, windowed or not by the layer's class, on
    the engine's ``[C, qb]`` grid.  k pages are d-major, v pages
    token-major, per kv head, as LLaMA's."""

    unsupported = ("kv_quant", "lora", "constrained", "speculative",
                   "page_shipment", "weight_only_int8")
    stats_keys = routed_experts.STATS_KEYS
    _KIND_OF_CLASS = ("global", "window")

    def __init__(self, cfg: CohereMoeConfig):
        if "global" not in cfg.kinds:
            raise NotImplementedError(
                "a model of window layers alone: cache class 0 is the "
                "global one (models/seam.py)")
        self.cfg = cfg
        self.n_layers = cfg.n_layers

    def init_params(self, key) -> dict:
        return init_cohere_moe_params(self.cfg, key)

    def cache_spec(self, page_size: int) -> CacheSpec:
        nKV, d = self.cfg.n_kv_heads, self.cfg.head_dim
        return CacheSpec((CachePlane("k", (nKV, d, page_size), nKV * d),
                          CachePlane("v", (nKV, page_size, d), nKV * d)),
                         self.cfg.dtype)

    def cache_classes(self, page_size: int) -> tuple:
        kinds, spec = self.cfg.kinds, self.cache_spec(page_size)
        out = [CacheClass("global", kinds.count("global"), spec)]
        if "window" in kinds:
            out.append(CacheClass("window", kinds.count("window"), spec,
                                  window=self.cfg.sliding_window))
        return tuple(out)

    def embed(self, params, tokens, positions):
        with jax.named_scope("embed"):
            x = params["wte"][tokens].astype(self.cfg.dtype)
            cos, sin = rope_angles(self.cfg, positions)
        # the held experts of every layer of a kind, where they lie
        # (routed_experts.moe_ffn)
        experts = {kind: {name: params[kind][name].reshape(
            (-1,) + params[kind][name].shape[2:]) for name in EXPERT_STACKS}
            for kind in self._KIND_OF_CLASS if kind in params}
        return x, {"cos": cos, "sin": sin, "experts": experts}

    def layer_groups(self, params) -> list:
        groups = []
        for kind, first, count in self.cfg.runs:
            stack = {name: w for name, w in params[kind].items()
                     if name not in EXPERT_STACKS}
            n = stack["norm"].shape[0]
            stack["index"] = jnp.arange(n, dtype=jnp.int32)
            if count != n:          # several runs of the kind: this one's
                stack = jax.tree.map(lambda a: a[first:first + count], stack)
            one = count == 1
            groups.append(LayerGroup(
                first, count,
                jax.tree.map(lambda a: a.reshape(a.shape[1:]), stack)
                if one else stack,
                stacked=not one, cache=self._KIND_OF_CLASS.index(kind)))
        return groups

    def apply(self, x, kp, vp, base, inp, rows, pos0, n_valid, ctx):
        from ..ops.pallas.paged_kv_write import paged_kv_write
        from ..ops.pallas.ragged_paged_attention import \
            ragged_paged_attention

        cfg, lp, lay = self.cfg, inp, ctx["layout"]
        kind = self._KIND_OF_CLASS[ctx["cache_class"]]
        h = layer_norm(x, lp["norm"], cfg.layer_norm_eps)
        q, k, v = project_qkv(h, lp, cfg, kind, ctx["cos"], ctx["sin"])
        # the write and the attention work by rows
        q, k, v = (lay.to_grid(a) for a in (q, k, v))
        with jax.named_scope("layer/kv_write"):
            kp, vp = paged_kv_write(kp, vp, k, v, rows + base, pos0,
                                    n_valid, sink=base)
        with jax.named_scope("layer/attn_" + kind):
            o = ragged_paged_attention(
                q, kp, vp, rows + base, pos0, n_valid,
                1.0 / math.sqrt(cfg.head_dim), k_layout="d_major",
                window=cfg.sliding_window if kind == "window" else None)
            a = _mm(lay.to_packed(o).reshape(x.shape[0], -1), lp["wo"], cfg)
        experts = ctx["experts"][kind]
        y, sizes = routed_experts.moe_ffn(h, lp, cfg.routing, lay.valid,
                                          (experts, lp["index"]))
        return _join(x, a, y, cfg), kp, vp, (
            sizes, routed_experts.row_tile(h.shape[0], experts, cfg.routing))

    def head(self, params, x):
        with jax.named_scope("head"):
            return layer_norm(x, params["final_norm"],
                              self.cfg.layer_norm_eps)

    def logits(self, params, h):
        with jax.named_scope("head"):
            return _logits(params, h, self.cfg)

    def tick_stats(self, ys, n_tokens: int) -> dict:
        return routed_experts.held_expert_stats(ys, n_tokens,
                                                self.cfg.routing)
