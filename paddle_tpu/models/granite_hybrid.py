"""State-space (Mamba-2) layers interleaved with a few attention layers, a
dense gated feed-forward in every layer: the GraniteMoeHybrid family's
layer (``granite-4.0-h-micro``, ``model_type: granitemoehybrid`` with no
routed experts, is the configuration the benchmark serves).

Stream ``x``, token at position ``p``; bf16 weights, activations and
pages, fp32 norms, softmax, softplus, ``exp``, the recurrence and its
state:

    x = E[tokens] * embedding_multiplier
    per layer l:
        h = rmsnorm(x, g_in)
        m = mamba2(h) if layer_types[l] == "mamba" else attention(h)
        x = x + residual_multiplier * m
        h = rmsnorm(x, g_post);  g, u = split(h W_in, 2)
        x = x + residual_multiplier * ((silu(g) * u) W_out)
    logits = (rmsnorm(x, g_f) E^T) / logits_scaling          (tied)

- ``attention``: ``n_heads`` query heads on ``n_kv_heads`` key/value
  heads of ``head_dim``, no bias, NO position encoding of any kind,
  causal, scores times ``attention_multiplier`` (the model's own, not
  ``1 / sqrt(head_dim)``).
- ``mamba2``: ``z, xBC, dt = split(h W_inproj, [d_inner, d_inner + 2 N,
  nH])``; a depthwise causal convolution of width ``d_conv`` over the
  tokens of ``xBC`` (zeros before position 0), its bias, SiLU; ``xs, B, C
  = split(xBC, [d_inner, N, N])`` with ``xs`` as ``nH`` heads of ``hd``
  (one group: B and C are every head's); ``dt = softplus(dt + dt_bias)``,
  ``a = exp(dt A)`` with ``A = -exp(A_log)``; per head ``S <- a S + dt xs
  (outer) B`` from ``S = 0``, ``y = S C + D xs``; ``y = rmsnorm(y *
  silu(z), g_n)`` over all ``d_inner`` (the gate before the norm);
  ``y W_outproj``.

Serving (``GraniteHybridServing``, on models/seam.py): the attention
layers keep pages (cache class 0), the state-space layers one slot a
request of a *state class*: the last ``d_conv - 1`` inputs of the
convolution, bf16, and the recurrence's state, fp32 (``cfg.state_dtype``)
because the recurrence multiplies it by ``a`` at every token and a bf16
state is rounded a thousand times in a thousand tokens; it lies as
``ops/pallas/ragged_ssm_scan.py`` wants it (transposed, two heads of 64 a
tile of 128 lanes).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .llama import rms_norm
from .routed_experts import mm
from .seam import (CacheClass, CachePlane, CacheSpec, LayerGroup, StateClass,
                   StatePlane)

__all__ = ["GraniteHybridConfig", "GraniteHybridServing",
           "granite_hybrid_apply", "init_granite_hybrid_params"]

KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden: int = 2048
    layer_types: tuple = (("mamba",) * 5 + ("attention",)
                          + ("mamba",) * 4) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 8192
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    n_groups: int = 1
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    max_seq_len: int = 131072
    init_std: float = 0.02
    embedding_init_std: float = 0.005
    state_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if set(self.layer_types) - set(KINDS):
            raise ValueError(f"layer_types {set(self.layer_types)}: only "
                             f"{KINDS} are implemented")
        if self.n_groups != 1:
            raise NotImplementedError(
                "mamba_n_groups != 1: models/granite_hybrid.py implements "
                "one group of B and C for all heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def runs(self) -> tuple:
        """(kind, index of the run's first layer among its kind's, count)
        for each run of consecutive layers of one kind, in order."""
        out, seen = [], dict.fromkeys(KINDS, 0)
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(r) for r in out)

    @classmethod
    def from_hf(cls, c: dict, **over) -> "GraniteHybridConfig":
        """From the keys of the model's ``config.json``.  Variants of the
        family that this file does not implement are refused."""
        for key, want in (("num_local_experts", 0), ("attention_bias", False),
                          ("mamba_proj_bias", False),
                          ("mamba_conv_bias", True),
                          ("position_embedding_type", "nope"),
                          ("hidden_act", "silu"),
                          ("normalization_function", "rmsnorm"),
                          ("tie_word_embeddings", True),
                          ("mamba_n_groups", 1)):
            if c.get(key, want) != want:
                raise NotImplementedError(
                    f"{key}={c[key]!r}: models/granite_hybrid.py implements "
                    f"{key}={want!r} only")
        if c["mamba_expand"] * c["hidden_size"] != (
                c["mamba_n_heads"] * c["mamba_d_head"]):
            raise ValueError("mamba_expand * hidden_size is not "
                             "mamba_n_heads * mamba_d_head")
        kw = dict(
            vocab_size=c["vocab_size"], hidden=c["hidden_size"],
            layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            ffn_hidden=c["shared_intermediate_size"],
            mamba_heads=c["mamba_n_heads"], mamba_head_dim=c["mamba_d_head"],
            d_state=c["mamba_d_state"], d_conv=c["mamba_d_conv"],
            attention_multiplier=float(c["attention_multiplier"]),
            embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            logits_scaling=float(c["logits_scaling"]),
            rms_eps=c["rms_norm_eps"],
            max_seq_len=c["max_position_embeddings"])
        for key in ("init_std", "embedding_init_std"):
            if key in c:
                kw[key] = float(c[key])
        kw.update(over)
        return cls(**kw)

    def serving_model(self) -> "GraniteHybridServing":
        return GraniteHybridServing(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def layer_shapes(cfg: GraniteHybridConfig, kind: str) -> dict:
    """Matrix shapes of one layer of ``kind`` (gains and the mixer's
    vectors apart)."""
    H, F, Di = cfg.hidden, cfg.ffn_hidden, cfg.d_inner
    ff = {"w_in": (H, 2 * F), "w_out": (F, H)}
    if kind == "attention":
        kv = cfg.n_kv_heads * cfg.head_dim
        return {"wq": (H, H), "wk": (H, kv), "wv": (H, kv), "wo": (H, H),
                **ff}
    return {"in_proj": (H, Di + cfg.conv_dim + cfg.mamba_heads),
            "out_proj": (Di, H), **ff}


def mixer_vectors(cfg: GraniteHybridConfig, key) -> dict:
    """The mixer's small parameters, fp32: the convolution ``[conv_dim,
    d_conv]`` and its bias uniform in +-1/sqrt(d_conv); ``A_log`` the log
    of uniform 1..16; ``dt_bias`` the inverse softplus of a log-uniform
    1e-3..1e-1; ``D`` and the gated norm's gain 1."""
    kc, kb, ka, kd = jax.random.split(key, 4)
    f32, nH = jnp.float32, cfg.mamba_heads
    r = 1.0 / math.sqrt(cfg.d_conv)
    dt = jnp.exp(jax.random.uniform(kd, (nH,), f32, math.log(1e-3),
                                    math.log(1e-1)))
    return {"conv_w": jax.random.uniform(kc, (cfg.conv_dim, cfg.d_conv), f32,
                                         -r, r),
            "conv_b": jax.random.uniform(kb, (cfg.conv_dim,), f32, -r, r),
            "A_log": jnp.log(jax.random.uniform(ka, (nH,), f32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((nH,), f32),
            "norm": jnp.ones((cfg.d_inner,), f32)}


def _init_layer(cfg: GraniteHybridConfig, kind: str, key) -> dict:
    pd = cfg.param_dtype
    shapes = layer_shapes(cfg, kind)
    keys = jax.random.split(key, len(shapes) + 1)
    lp = {name: (jax.random.normal(k, shape, jnp.float32)
                 * cfg.init_std).astype(pd)
          for k, (name, shape) in zip(keys, shapes.items())}
    lp["in_norm"] = jnp.ones((cfg.hidden,), pd)
    lp["post_norm"] = jnp.ones((cfg.hidden,), pd)
    if kind == "mamba":
        lp.update(mixer_vectors(cfg, keys[-1]))
    return lp


def init_granite_hybrid_params(cfg: GraniteHybridConfig, key) -> dict:
    """``runs`` holds one stack a run of consecutive layers of a kind
    (``cfg.runs``), leading dim the run's length, in the model's order:
    the step scans each where it lies, and no layer's weights are sliced
    out of a longer stack.  The head is the embedding."""
    k_emb, *k_run = jax.random.split(key, 1 + len(cfg.runs))
    return {
        "wte": (cfg.embedding_init_std * jax.random.normal(
            k_emb, (cfg.vocab_size, cfg.hidden), jnp.float32)).astype(
                cfg.param_dtype),
        "final_norm": jnp.ones((cfg.hidden,), cfg.param_dtype),
        "runs": [jax.vmap(lambda kk, kind=kind: _init_layer(cfg, kind, kk))(
            jax.random.split(k, count))
            for (kind, _first, count), k in zip(cfg.runs, k_run)]}


# ---------------------------------------------------------------------------
# building blocks, over any leading axes
# ---------------------------------------------------------------------------

def _mm(x, w, cfg):
    return mm(x, w, cfg.dtype)


def _residual(x, m, cfg):
    """``x + residual_multiplier * m``, one rounding."""
    f32 = jnp.float32
    return (x.astype(f32) + cfg.residual_multiplier * m.astype(f32)).astype(
        cfg.dtype)


def feed_forward(x, lp, cfg):
    with jax.named_scope("layer/mlp"):
        h = rms_norm(x, lp["post_norm"], cfg.rms_eps)
        g, u = jnp.split(_mm(h, lp["w_in"], cfg), 2, axis=-1)
        y = _mm(jax.nn.silu(g.astype(jnp.float32)).astype(cfg.dtype) * u,
                lp["w_out"], cfg)
        return _residual(x, y, cfg)


def ssm_in(h, lp, cfg):
    """z ``[..., d_inner]``, xBC ``[..., conv_dim]`` (before the
    convolution), dt ``[..., nH]`` fp32 after the softplus."""
    with jax.named_scope("layer/ssm_in_proj"):
        z, xBC, dt = jnp.split(
            _mm(h, lp["in_proj"], cfg),
            [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    return z, xBC, dt


def conv_window(ext, lp, cfg):
    """``ext [..., n + d_conv - 1, conv_dim]``, a run of tokens behind its
    ``d_conv - 1`` predecessors -> the convolution, bias and SiLU at the
    run's ``n`` tokens, in the activations' dtype."""
    n = ext.shape[-2] - cfg.d_conv + 1
    f32 = jnp.float32
    acc = lp["conv_b"].astype(f32)
    for k in range(cfg.d_conv):
        acc = acc + lp["conv_w"][:, k].astype(f32) * lax.slice_in_dim(
            ext, k, k + n, axis=ext.ndim - 2).astype(f32)
    return jax.nn.silu(acc).astype(cfg.dtype)


def ssm_out(y, z, lp, cfg):
    """The gated norm (gate before the norm, over all ``d_inner``) and
    the output projection."""
    with jax.named_scope("layer/ssm_gate_norm"):
        f32 = jnp.float32
        g = y.astype(f32) * jax.nn.silu(z.astype(f32))
        g = g * lax.rsqrt((g * g).mean(-1, keepdims=True) + cfg.rms_eps)
        g = (g * lp["norm"].astype(f32)).astype(cfg.dtype)
    with jax.named_scope("layer/ssm_out_proj"):
        return _mm(g, lp["out_proj"], cfg)


def split_xbc(xBC, cfg):
    """xs ``[..., d_inner]`` (the heads side by side), B and C ``[...,
    N]``."""
    return jnp.split(xBC, [cfg.d_inner, cfg.d_inner + cfg.d_state], axis=-1)


def skip(xs, lp, cfg):
    """``D xs``, fp32: a head's ``D`` over its channels."""
    return jnp.repeat(lp["D"].astype(jnp.float32), cfg.mamba_head_dim) \
        * xs.astype(jnp.float32)


def project_qkv(h, lp, cfg):
    nH, nKV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("layer/qkv"):
        q = _mm(h, lp["wq"], cfg).reshape(h.shape[:-1] + (nH, d))
        k = _mm(h, lp["wk"], cfg).reshape(h.shape[:-1] + (nKV, d))
        v = _mm(h, lp["wv"], cfg).reshape(h.shape[:-1] + (nKV, d))
    return q, k, v


# ---------------------------------------------------------------------------
# the full-sequence forward (no cache): the oracle of the engine's tests
# ---------------------------------------------------------------------------

def _mamba_block(x, lp, cfg):
    B, T, _ = x.shape
    f32 = jnp.float32
    h = rms_norm(x, lp["in_norm"], cfg.rms_eps)
    z, xBC, dt = ssm_in(h, lp, cfg)
    ext = jnp.concatenate([jnp.zeros((B, cfg.d_conv - 1, cfg.conv_dim),
                                     xBC.dtype), xBC], axis=1)
    xs, Bm, Cm = split_xbc(conv_window(ext, lp, cfg), cfg)
    xh = xs.reshape(B, T, cfg.mamba_heads, cfg.mamba_head_dim)
    A = -jnp.exp(lp["A_log"].astype(f32))

    def step(S, inp):
        xt, bt, ct, dtt = inp                     # [B,nH,hd] [B,N] [B,N] [B,nH]
        S = (jnp.exp(dtt * A)[:, :, None, None] * S
             + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :])
        return S, jnp.einsum("bhdn,bn->bhd", S, ct,
                             precision=lax.Precision.HIGHEST)

    S0 = jnp.zeros((B, cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state), f32)
    _, y = lax.scan(step, S0, (
        jnp.swapaxes(xh, 0, 1).astype(f32), jnp.swapaxes(Bm, 0, 1).astype(f32),
        jnp.swapaxes(Cm, 0, 1).astype(f32), jnp.swapaxes(dt, 0, 1)))
    y = jnp.swapaxes(y, 0, 1).reshape(B, T, -1) + skip(xs, lp, cfg)
    return _residual(x, ssm_out(y, z, lp, cfg), cfg)


def _attention_block(x, lp, cfg):
    B, T, _ = x.shape
    f32 = jnp.float32
    h = rms_norm(x, lp["in_norm"], cfg.rms_eps)
    q, k, v = project_qkv(h, lp, cfg)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, T, cfg.n_kv_heads, G, cfg.head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=f32) * cfg.attention_multiplier
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1).astype(cfg.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                   preferred_element_type=f32).astype(cfg.dtype)
    return _residual(x, _mm(o.reshape(B, T, -1), lp["wo"], cfg), cfg)


def _embed(params, tokens, cfg):
    return (params["wte"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cfg.dtype)


def _logits(params, h, cfg):
    out = jnp.einsum("...h,vh->...v", h.astype(cfg.dtype),
                     params["wte"].astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    return out / cfg.logits_scaling


def granite_hybrid_apply(params, tokens, cfg: GraniteHybridConfig):
    """tokens ``[B, T]`` -> next-token logits ``[B, T, V]`` fp32."""
    x = _embed(params, tokens, cfg)
    for (kind, _first, count), stack in zip(cfg.runs, params["runs"]):
        for i in range(count):
            lp = jax.tree.map(lambda a, i=i: a[i], stack)
            x = (_mamba_block if kind == "mamba" else _attention_block)(
                x, lp, cfg)
            x = feed_forward(x, lp, cfg)
    return _logits(params, rms_norm(x, params["final_norm"], cfg.rms_eps),
                   cfg)


# ---------------------------------------------------------------------------
# the serving engine's side (models/seam.py has the contract)
# ---------------------------------------------------------------------------

class GraniteHybridServing:
    """Cache class 0 (``global``) is the attention layers' pages; the
    state-space layers are a *state class* (models/seam.py): one slot a
    request, two planes.  Norms, projections, the gate and the
    feed-forward run over the tick's packed tokens ``[T, H]``; the page
    write and the attention on the engine's ``[C, qb]`` grid, the
    convolution and the recurrence on the grid's places row by row
    (``TokenLayout.to_rows``: their kernels take tokens two-dimensional).

    The attention's heads are 64 wide and the paged kernels' tiles 128:
    a page holds the kv heads in PAIRS, k d-major ``[nKV / 2, 128, bs]``
    and v token-major ``[nKV / 2, bs, 128]`` (a pair's k rows one above
    the other, its v columns side by side), and a query head rides as
    128 wide with zeros where its pair's other head lies: ``q' . k'`` is
    ``q . k`` to the bit and the half of ``p v'`` under the head is ``p
    v``.  Heads of 128 or more are paged as they are."""

    unsupported = ("kv_quant", "lora", "constrained", "speculative",
                   "page_shipment", "weight_only_int8")
    _KIND_OF_CLASS = ("attention", "mamba")

    def __init__(self, cfg: GraniteHybridConfig):
        if not all(k in cfg.layer_types for k in KINDS):
            raise NotImplementedError(
                "a model without attention layers, or without state-space "
                "layers: cache class 0 is the paged one and the state "
                "class follows it (models/seam.py)")
        self.cfg = cfg
        self.n_layers = cfg.n_layers
        d = cfg.head_dim
        # kv heads a 128-lane tile holds
        self.pack = 128 // d if d < 128 and 128 % d == 0 and (
            cfg.n_kv_heads % (128 // d) == 0) else 1

    def init_params(self, key) -> dict:
        return init_granite_hybrid_params(self.cfg, key)

    def cache_spec(self, page_size: int) -> CacheSpec:
        nKV, d = self.cfg.n_kv_heads // self.pack, self.cfg.head_dim * self.pack
        return CacheSpec((CachePlane("k", (nKV, d, page_size), nKV * d),
                          CachePlane("v", (nKV, page_size, d), nKV * d)),
                         self.cfg.dtype)

    def cache_classes(self, page_size: int) -> tuple:
        from ..ops.pallas.ragged_ssm_scan import state_shape

        cfg, types = self.cfg, self.cfg.layer_types
        return (
            CacheClass("global", types.count("attention"),
                       self.cache_spec(page_size)),
            StateClass("state", types.count("mamba"), (
                StatePlane("conv", ((cfg.d_conv - 1) * cfg.conv_dim,),
                           cfg.dtype),
                StatePlane("ssm", state_shape(
                    cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state),
                    cfg.state_dtype)),
                hash_tag=b":ssm-" + jnp.dtype(cfg.state_dtype).name.encode()))

    def embed(self, params, tokens, positions):
        with jax.named_scope("embed"):
            return _embed(params, tokens, self.cfg), {}

    def layer_groups(self, params) -> list:
        return [LayerGroup(
            first, count,
            jax.tree.map(lambda a: a[0], stack) if count == 1 else stack,
            stacked=count > 1, cache=self._KIND_OF_CLASS.index(kind))
            for (kind, first, count), stack in zip(self.cfg.runs,
                                                   params["runs"])]

    def apply(self, x, kp, vp, base, inp, rows, pos0, n_valid, ctx):
        kind = self._KIND_OF_CLASS[ctx["cache_class"]]
        mixer = self._mamba if kind == "mamba" else self._attention
        m, kp, vp = mixer(x, kp, vp, base, inp, rows, pos0, n_valid,
                          ctx["layout"])
        return feed_forward(_residual(x, m, self.cfg), inp, self.cfg), \
            kp, vp, None

    def _attention(self, x, kp, vp, base, lp, rows, pos0, n_valid, lay):
        from ..ops.pallas.paged_kv_write import paged_kv_write
        from ..ops.pallas.ragged_paged_attention import \
            ragged_paged_attention

        cfg, pk = self.cfg, self.pack
        nH, nKV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        h = rms_norm(x, lp["in_norm"], cfg.rms_eps)
        q, k, v = project_qkv(h, lp, cfg)
        if pk > 1:
            # a query head's place in its pair: head i uses kv head
            # i // G, which is half (i // G) % pk of the pair
            mine = jnp.asarray(
                (np.arange(nH) // (nH // nKV) % pk)[:, None] == np.arange(pk),
                q.dtype)                                  # [nH, pk]
            q = (q[..., None, :] * mine[:, :, None]).reshape(
                q.shape[:-1] + (pk * d,))
            k = k.reshape(k.shape[:-2] + (nKV // pk, pk * d))
            v = v.reshape(v.shape[:-2] + (nKV // pk, pk * d))
        q, k, v = (lay.to_grid(a) for a in (q, k, v))
        with jax.named_scope("layer/kv_write"):
            kp, vp = paged_kv_write(kp, vp, k, v, rows + base, pos0,
                                    n_valid, sink=base)
        with jax.named_scope("layer/attn"):
            o = ragged_paged_attention(q, kp, vp, rows + base, pos0, n_valid,
                                       cfg.attention_multiplier,
                                       k_layout="d_major")
            o = lay.to_packed(o)                         # [T, nH, pk * d]
            if pk > 1:
                o = (o.reshape(o.shape[:-1] + (pk, d))
                     * mine[:, :, None]).sum(2)
            return _mm(o.reshape(x.shape[0], -1), lp["wo"], cfg), kp, vp

    def _mamba(self, x, conv_pool, ssm_pool, base, lp, slots, pos0, n_valid,
               lay):
        """``slots [C, 2]``: where each row's request keeps its state now
        and where it wants it after the tick (models/seam.py: the state
        class); slot 1 of the class is the dump, idle rows' target."""
        from ..ops.pallas.ragged_causal_conv import ragged_causal_conv
        from ..ops.pallas.ragged_ssm_scan import ragged_ssm_scan
        from .seam import STATE_DUMP, STATE_ZERO

        cfg, qb = self.cfg, lay.grid[1]
        read, write = slots[:, 0] + base, slots[:, 1] + base
        dump = base + STATE_DUMP
        n_valid = jnp.where(slots[:, 1] == STATE_DUMP, 0, n_valid)
        h = rms_norm(x, lp["in_norm"], cfg.rms_eps)
        z, xBC, dt = ssm_in(h, lp, cfg)
        # the convolution and the recurrence work by rows, on the grid's
        # places row by row
        with jax.named_scope("layer/ssm_conv"):
            xBC, conv_pool = ragged_causal_conv(
                conv_pool, lay.to_rows(xBC), lp["conv_w"], lp["conv_b"],
                read, write, n_valid, qb=qb, zero=base + STATE_ZERO,
                dump=dump)
            xs, Bm, Cm = split_xbc(xBC, cfg)
        with jax.named_scope("layer/ssm_scan"):
            y, ssm_pool = ragged_ssm_scan(
                ssm_pool, xs, lay.to_grid(dt), -jnp.exp(lp["A_log"]), Bm, Cm,
                read, write, n_valid, dump=dump)
            y = lay.from_rows(y + skip(xs, lp, cfg))
        return ssm_out(y, z, lp, cfg), conv_pool, ssm_pool

    def head(self, params, x):
        with jax.named_scope("head"):
            return rms_norm(x, params["final_norm"], self.cfg.rms_eps)

    def logits(self, params, h):
        with jax.named_scope("head"):
            return _logits(params, h, self.cfg)
