"""Window layers with a learned sink beside full GQA layers, keys wider
than values, routed experts behind a leading dense layer: the MiMo-V2
family's decoder (``MiMo-V2.5``, ``model_type: mimo_v2``, is the
configuration the benchmark serves).

For layer ``l`` of kind full or window (``hybrid_layer_pattern``: 0 is
full, 1 is window), token at position ``p``, stream ``x``; bf16 weights,
activations, residual and cache, fp32 accumulation, norms, softmax with
its sink, router and logits:

- ``h = RMSNorm(x)``; ``q = h Wq`` as ``n_heads`` heads of ``head_dim``,
  ``k = h Wk`` as ``n_kv`` heads of ``head_dim`` and ``v = h Wv`` as
  ``n_kv`` heads of ``v_head_dim`` (``n_kv`` is the layer kind's own; no
  bias).  Query head ``i`` uses kv head ``i // (n_heads / n_kv)``.
- q and k are rotated over their first ``rotary_dim`` dims only
  (``partial_rotary_factor``), by halves (pairs ``i, i + rotary_dim/2``)
  with angle ``p * theta^(-2i/rotary_dim)``; ``theta`` is the layer
  kind's own (``rope_theta`` full, ``swa_rope_theta`` window).  The other
  dims are not touched.
- ``v <- attention_value_scale * v``.
- scores ``q . k / sqrt(head_dim)`` over keys ``p' <= p``, and in a
  window layer ``p - p' < sliding_window``; a full layer's softmax is
  plain, a window layer's has a learned logit per head with no value
  behind it, the sink: ``P_j = exp(s_j - m) / (exp(sink - m) + sum
  exp(s_j' - m))``.  ``x <- x + concat_heads(P v) Wo``.
- ``h = RMSNorm(x)``; ``x <- x + SwiGLU(h)`` in the dense layers
  (``moe_layer_freq`` 0), else the routed-expert layer of
  ``models/routed_experts.py``: sigmoid scores, top-k of scores plus a
  correction bias, weights normalised over the chosen, no shared expert.
  **The layer is told which experts it holds** (``cfg.held``).
- After the last layer an RMSNorm and ``logits = h W_head^T`` (its own
  matrix: the embedding is not tied).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import routed_experts
from .llama import apply_rope, rms_norm
from .routed_experts import EXPERT_STACKS
from .seam import CacheClass, CachePlane, CacheSpec, LayerGroup

__all__ = ["MimoV2Config", "MimoV2Serving", "init_mimo_v2_params",
           "mimo_v2_apply"]

KINDS = {0: "full", 1: "window"}


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    hidden: int = 4096
    # per layer: 0 full, 1 window (hybrid_layer_pattern); 0 dense, 1
    # routed experts (moe_layer_freq)
    pattern: tuple = (0, 1, 1, 1, 1, 0)
    moe_layers: tuple = (0, 1, 1, 1, 1, 1)
    n_heads: int = 64
    n_kv_heads: int = 4               # the full layers'
    swa_n_kv_heads: int = 8           # the window layers'
    head_dim: int = 192               # q and k
    v_head_dim: int = 128
    rotary_dim: int = 64
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    window_sink: bool = True          # add_swa_attention_sink_bias
    value_scale: float = 0.707        # attention_value_scale
    dense_hidden: int = 16384         # the dense layers' width
    expert_hidden: int = 2048         # one expert's width
    n_routed_experts: int = 256       # the router's width, always whole
    experts_per_token: int = 8
    norm_topk_prob: bool = True
    rms_eps: float = 1e-5
    max_seq_len: int = 1048576
    # the experts this chip holds, (first, count); None = all of them
    held: Any = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "pattern", tuple(self.pattern))
        object.__setattr__(self, "moe_layers", tuple(self.moe_layers))
        if self.held is None:
            object.__setattr__(self, "held", (0, self.n_routed_experts))
        first, count = self.held
        if not 0 <= first < first + count <= self.n_routed_experts:
            raise ValueError(f"held {self.held} is not a range of the "
                             f"{self.n_routed_experts} routed experts")
        if len(self.moe_layers) != len(self.pattern) or set(
                self.pattern) - set(KINDS) or set(self.moe_layers) - {0, 1}:
            raise ValueError("pattern and moe_layers: one 0 or 1 a layer")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def kinds(self) -> tuple:
        """"full" or "window" per layer."""
        return tuple(KINDS[k] for k in self.pattern)

    @property
    def stacks(self) -> tuple:
        """The parameter stack each layer lies in: ``<kind>_<ffn>``, ffn
        ``dense`` or ``moe``."""
        return tuple(f"{kind}_{'moe' if m else 'dense'}"
                     for kind, m in zip(self.kinds, self.moe_layers))

    def kv_heads(self, kind: str) -> int:
        return self.n_kv_heads if kind == "full" else self.swa_n_kv_heads

    def theta(self, kind: str) -> float:
        return self.rope_theta if kind == "full" else self.swa_rope_theta

    @property
    def routing(self) -> routed_experts.Routing:
        return routed_experts.Routing(
            k=self.experts_per_token, held=self.held, dtype=self.dtype,
            norm_topk=self.norm_topk_prob)

    @classmethod
    def from_hf(cls, c: dict, **over) -> "MimoV2Config":
        """From the keys of the model's ``config.json``; the two patterns
        are cut to ``num_hidden_layers``.  Variants of the family that
        this file does not implement are refused."""
        for key, want in (("n_shared_experts", None),
                          ("add_full_attention_sink_bias", False),
                          ("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu"),
                          ("routed_scaling_factor", None),
                          ("swa_num_attention_heads",
                           c["num_attention_heads"]),
                          ("swa_head_dim", c["head_dim"]),
                          ("swa_v_head_dim", c["v_head_dim"])):
            if c.get(key, want) != want:
                raise NotImplementedError(
                    f"{key}={c[key]!r}: models/mimo_v2.py implements "
                    f"{key}={want!r} only")
        if (c.get("rope_scaling") or {}).get("rope_type",
                                             "default") != "default":
            raise NotImplementedError(
                f"rope_scaling={c['rope_scaling']!r}: models/mimo_v2.py "
                f"implements the default rotation only")
        L = c["num_hidden_layers"]
        kw = dict(
            vocab_size=c["vocab_size"], hidden=c["hidden_size"],
            pattern=tuple(c["hybrid_layer_pattern"][:L]),
            moe_layers=tuple(c["moe_layer_freq"][:L]),
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            swa_n_kv_heads=c["swa_num_key_value_heads"],
            head_dim=c["head_dim"], v_head_dim=c["v_head_dim"],
            rotary_dim=int(c["head_dim"] * c["partial_rotary_factor"]),
            rope_theta=float(c["rope_theta"]),
            swa_rope_theta=float(c["swa_rope_theta"]),
            sliding_window=c["sliding_window"],
            window_sink=bool(c["add_swa_attention_sink_bias"]),
            value_scale=float(c["attention_value_scale"]),
            dense_hidden=c["intermediate_size"],
            expert_hidden=c["moe_intermediate_size"],
            n_routed_experts=c["n_routed_experts"],
            experts_per_token=c["num_experts_per_tok"],
            norm_topk_prob=c["norm_topk_prob"],
            rms_eps=c["layernorm_epsilon"],
            max_seq_len=c["max_position_embeddings"])
        kw.update(over)
        return cls(**kw)

    def serving_model(self) -> "MimoV2Serving":
        return MimoV2Serving(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def layer_shapes(cfg: MimoV2Config, stack: str) -> dict:
    """Matrix shapes of one layer of ``stack`` (norm gains, sinks and the
    router's bias apart)."""
    kind, ffn = stack.split("_")
    H, nH, dk, dv = cfg.hidden, cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    nKV = cfg.kv_heads(kind)
    out = {"wq": (H, nH * dk), "wk": (H, nKV * dk), "wv": (H, nKV * dv),
           "wo": (nH * dv, H)}
    if ffn == "dense":
        F = cfg.dense_hidden
        out.update(w_gate=(H, F), w_up=(H, F), w_down=(F, H))
    else:
        n, F = cfg.held[1], cfg.expert_hidden
        out.update(router=(H, cfg.n_routed_experts), we_gate=(n, H, F),
                   we_up=(n, H, F), we_down=(n, F, H))
    return out


RESIDUAL_OUT = ("wo", "w_down", "we_down")


def _init_layer(cfg: MimoV2Config, key, stack: str) -> dict:
    std, pd = 0.02, cfg.param_dtype
    resid = std / math.sqrt(2 * cfg.n_layers)
    shapes = layer_shapes(cfg, stack)
    keys = jax.random.split(key, len(shapes) + 2)
    lp = {name: (jax.random.normal(k, shape, jnp.float32)
                 * (resid if name in RESIDUAL_OUT else std)).astype(pd)
          for k, (name, shape) in zip(keys, shapes.items())}
    lp["attn_norm"] = jnp.ones((cfg.hidden,), pd)
    lp["ffn_norm"] = jnp.ones((cfg.hidden,), pd)
    if "router" in lp:
        lp["router_bias"] = 0.01 * jax.random.normal(
            keys[-2], (cfg.n_routed_experts,), jnp.float32)
    if stack.startswith("window") and cfg.window_sink:
        lp["sinks"] = 3.0 + jax.random.normal(keys[-1], (cfg.n_heads,),
                                              jnp.float32)
    return lp


def init_mimo_v2_params(cfg: MimoV2Config, key) -> dict:
    """Every ``<kind>_<ffn>`` stack (``MimoV2Config.stacks``) over its
    layers, in the model's order; the embedding and the head apart."""
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    H, V, pd = cfg.hidden, cfg.vocab_size, cfg.param_dtype
    params = {
        "wte": (0.02 * jax.random.normal(k_emb, (V, H), jnp.float32)
                ).astype(pd),
        "head": (0.02 * jax.random.normal(k_head, (V, H), jnp.float32)
                 ).astype(pd),
        "final_norm": jnp.ones((H,), pd)}
    stacks = cfg.stacks
    for i, name in enumerate(dict.fromkeys(stacks)):
        n = stacks.count(name)
        params[name] = jax.vmap(lambda k, name=name: _init_layer(
            cfg, k, name))(jax.random.split(jax.random.fold_in(k_layers, i),
                                            n))
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _mm(x, w, cfg):
    return routed_experts.mm(x, w, cfg.dtype)


def rope_angles(cfg: MimoV2Config, positions) -> dict:
    """Per layer kind, (cos, sin) ``[..., 1, rotary_dim / 2]`` fp32 at
    integer positions, at the kind's own theta."""
    d = cfg.rotary_dim
    out = {}
    for kind in ("full", "window"):
        inv = 1.0 / (cfg.theta(kind) ** (jnp.arange(0, d, 2,
                                                    dtype=jnp.float32) / d))
        ang = positions.astype(jnp.float32)[..., None, None] * inv
        out[kind] = (jnp.cos(ang), jnp.sin(ang))
    return out


def partial_rope(x, cos, sin, rotary_dim: int):
    """Rotate the first ``rotary_dim`` dims of ``x [..., heads, d]`` by
    halves; the rest pass as they are."""
    return jnp.concatenate([apply_rope(x[..., :rotary_dim], cos, sin),
                            x[..., rotary_dim:]], -1)


def project_qkv(h, lp, cfg: MimoV2Config, kind: str, angles):
    """q ``[..., nH, dk]``, k ``[..., nKV, dk]`` and v ``[..., nKV, dv]``
    from the normed input: q and k partly rotated, v scaled (in fp32,
    before its one rounding)."""
    nH, dk, dv = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    nKV = cfg.kv_heads(kind)
    cos, sin = angles[kind]
    with jax.named_scope("layer/qkv"):
        q = _mm(h, lp["wq"], cfg).reshape(h.shape[:-1] + (nH, dk))
        k = _mm(h, lp["wk"], cfg).reshape(h.shape[:-1] + (nKV, dk))
        v = jnp.einsum("...h,hk->...k", h.astype(cfg.dtype),
                       lp["wv"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)
        v = (v * cfg.value_scale).astype(cfg.dtype).reshape(
            h.shape[:-1] + (nKV, dv))
        q = partial_rope(q, cos, sin, cfg.rotary_dim)
        k = partial_rope(k, cos, sin, cfg.rotary_dim)
    return q, k, v


def _ffn(x, lp, cfg, valid=None, stack=None):
    """``x + FFN(RMSNorm(x))`` for packed ``x [N, H]``; (x, the layer's
    counters: None for a dense layer)."""
    h = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
    if "router" not in lp:
        with jax.named_scope("layer/mlp"):
            return x + routed_experts.swiglu(h, lp["w_gate"], lp["w_up"],
                                             lp["w_down"], cfg.dtype), None
    y, sizes = routed_experts.moe_ffn(h, lp, cfg.routing, valid, stack)
    return x + y, sizes


# ---------------------------------------------------------------------------
# the full-sequence forward (dense attention, no cache): the oracle of the
# engine's tests
# ---------------------------------------------------------------------------

def _block(x, lp, cfg: MimoV2Config, kind: str, angles):
    B, T, _ = x.shape
    f32 = jnp.float32
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q, k, v = project_qkv(h, lp, cfg, kind, angles)
    nKV = cfg.kv_heads(kind)
    G = cfg.n_heads // nKV
    qg = q.reshape(B, T, nKV, G, cfg.head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=f32) / math.sqrt(cfg.head_dim)
    qp, kp = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = kp <= qp
    if kind == "window":
        mask &= qp - kp < cfg.sliding_window
    s = jnp.where(mask, s, -1e30)
    m = s.max(-1, keepdims=True)
    if kind == "window" and cfg.window_sink:
        sink = lp["sinks"].astype(f32).reshape(1, nKV, G, 1, 1)
        m = jnp.maximum(m, sink)
    e = jnp.exp(s - m)
    den = e.sum(-1, keepdims=True)
    if kind == "window" and cfg.window_sink:
        den = den + jnp.exp(sink - m)
    p = (e / den).astype(cfg.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                   preferred_element_type=f32).astype(cfg.dtype)
    x = x + _mm(o.reshape(B, T, -1), lp["wo"], cfg)
    y, _ = _ffn(x.reshape(B * T, -1), lp, cfg)
    return y.reshape(x.shape)


def mimo_v2_apply(params, tokens, cfg: MimoV2Config):
    """tokens ``[B, T]`` -> next-token logits ``[B, T, V]`` fp32."""
    angles = rope_angles(cfg, jnp.arange(tokens.shape[1], dtype=jnp.int32))
    x = params["wte"][tokens].astype(cfg.dtype)
    seen = dict.fromkeys(cfg.stacks, 0)
    for kind, stack in zip(cfg.kinds, cfg.stacks):
        lp = jax.tree.map(lambda a, i=seen[stack]: a[i], params[stack])
        x = _block(x, lp, cfg, kind, angles)
        seen[stack] += 1
    return _logits(params, rms_norm(x, params["final_norm"], cfg.rms_eps),
                   cfg)


def _logits(params, h, cfg):
    return jnp.einsum("...h,vh->...v", h.astype(cfg.dtype),
                      params["head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the serving engine's side (models/seam.py has the contract)
# ---------------------------------------------------------------------------

class MimoV2Serving:
    """Two cache classes (models/seam.py) of two page shapes: ``global``,
    the full layers, ``n_kv_heads`` heads a page, and ``window``, the
    window layers, ``swa_n_kv_heads`` heads a page, which let go of what
    lies ``sliding_window`` behind.  k pages are d-major ``[nKV, dk,
    bs]``, v pages token-major ``[nKV, bs, dv]``: each holds its width
    exactly.  Norms, projections, the dense layer, the router and the
    experts run over the tick's packed tokens ``[T, H]``; the page write
    and the attention (``ops/pallas/ragged_paged_attention.py``: two
    widths, and in a window layer its window and the heads' sinks) on
    the engine's ``[C, qb]`` grid."""

    unsupported = ("kv_quant", "lora", "constrained", "speculative",
                   "page_shipment", "weight_only_int8")
    stats_keys = routed_experts.STATS_KEYS
    _KIND_OF_CLASS = ("full", "window")

    def __init__(self, cfg: MimoV2Config):
        if "full" not in cfg.kinds:
            raise NotImplementedError(
                "a model of window layers alone: cache class 0 is the "
                "global one (models/seam.py)")
        self.cfg = cfg
        self.n_layers = cfg.n_layers

    def init_params(self, key) -> dict:
        return init_mimo_v2_params(self.cfg, key)

    def cache_spec(self, page_size: int, kind: str = "full") -> CacheSpec:
        cfg = self.cfg
        nKV, dk, dv = cfg.kv_heads(kind), cfg.head_dim, cfg.v_head_dim
        return CacheSpec((CachePlane("k", (nKV, dk, page_size), nKV * dk),
                          CachePlane("v", (nKV, page_size, dv), nKV * dv)),
                         cfg.dtype,
                         hash_tag=f"mimo_v2:{kind}:{nKV}x{dk}/{dv}".encode())

    def cache_classes(self, page_size: int) -> tuple:
        kinds = self.cfg.kinds
        out = [CacheClass("global", kinds.count("full"),
                          self.cache_spec(page_size, "full"))]
        if "window" in kinds:
            out.append(CacheClass("window", kinds.count("window"),
                                  self.cache_spec(page_size, "window"),
                                  window=self.cfg.sliding_window))
        return tuple(out)

    def embed(self, params, tokens, positions):
        with jax.named_scope("embed"):
            x = params["wte"][tokens].astype(self.cfg.dtype)
            angles = rope_angles(self.cfg, positions)
        # the held experts of every layer of a stack, where they lie
        # (routed_experts.moe_ffn)
        experts = {stack: {name: params[stack][name].reshape(
            (-1,) + params[stack][name].shape[2:]) for name in EXPERT_STACKS}
            for stack in dict.fromkeys(self.cfg.stacks)
            if stack.endswith("_moe")}
        return x, {"angles": angles, "experts": experts}

    def layer_groups(self, params) -> list:
        """Runs of consecutive layers of one stack, each the class's
        layers ``first .. first + count - 1``."""
        cfg, groups = self.cfg, []
        in_class = {"full": 0, "window": 0}
        in_stack = dict.fromkeys(cfg.stacks, 0)
        for kind, stack in zip(cfg.kinds, cfg.stacks):
            if groups and groups[-1][0] == stack:
                groups[-1][3] += 1
            else:
                groups.append([stack, in_class[kind], in_stack[stack], 1])
            in_class[kind] += 1
            in_stack[stack] += 1
        out = []
        for stack, first, at, count in groups:
            xs = {name: w for name, w in params[stack].items()
                  if name not in EXPERT_STACKS}
            xs["index"] = jnp.arange(xs["attn_norm"].shape[0],
                                     dtype=jnp.int32)
            xs = jax.tree.map(lambda a: a[at:at + count], xs)
            one = count == 1
            out.append(LayerGroup(
                first, count,
                jax.tree.map(lambda a: a[0], xs) if one else xs,
                stacked=not one,
                cache=self._KIND_OF_CLASS.index(stack.split("_")[0])))
        return out

    def apply(self, x, kp, vp, base, inp, rows, pos0, n_valid, ctx):
        from ..ops.pallas.paged_kv_write import paged_kv_write
        from ..ops.pallas.ragged_paged_attention import \
            ragged_paged_attention

        cfg, lp, lay = self.cfg, inp, ctx["layout"]
        kind = self._KIND_OF_CLASS[ctx["cache_class"]]
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = project_qkv(h, lp, cfg, kind, ctx["angles"])
        # the write and the attention work by rows
        q, k, v = (lay.to_grid(a) for a in (q, k, v))
        with jax.named_scope("layer/kv_write"):
            kp, vp = paged_kv_write(kp, vp, k, v, rows + base, pos0,
                                    n_valid, sink=base)
        window = kind == "window"
        with jax.named_scope("layer/attn_" + ("window" if window
                                              else "global")):
            o = ragged_paged_attention(
                q, kp, vp, rows + base, pos0, n_valid,
                1.0 / math.sqrt(cfg.head_dim), k_layout="d_major",
                window=cfg.sliding_window if window else None,
                sinks=lp["sinks"] if window and cfg.window_sink else None)
            x = x + _mm(lay.to_packed(o).reshape(x.shape[0], -1), lp["wo"],
                        cfg)
        moe = "router" in lp
        stack = f"{kind}_{'moe' if moe else 'dense'}"
        x, sizes = _ffn(x, lp, cfg, lay.valid,
                        (ctx["experts"][stack], lp["index"]) if moe
                        else None)
        if moe:
            sizes = sizes, routed_experts.row_tile(
                x.shape[0], ctx["experts"][stack], cfg.routing)
        return x, kp, vp, sizes

    def head(self, params, x):
        with jax.named_scope("head"):
            return rms_norm(x, params["final_norm"], self.cfg.rms_eps)

    def logits(self, params, h):
        with jax.named_scope("head"):
            return _logits(params, h, self.cfg)

    def tick_stats(self, ys, n_tokens: int) -> dict:
        """From a tick's tokens per held expert per layer (the layer
        groups' counters; the dense layers have none)."""
        return routed_experts.held_expert_stats(ys, n_tokens,
                                                self.cfg.routing)
