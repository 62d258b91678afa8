"""Plain reference for the Mistral-7B family as this benchmark serves it:
RMSNorm, rotary positions (rotate-half), grouped-query attention, SwiGLU,
untied head.  One full causal forward pass over prompt + served tokens in
float32 at `highest` matmul precision, layer by layer so that it fits:
no cache, no paging, no batching of requests, no kernels, and nothing
imported from paddle_tpu.

It also holds the benchmark's weight generator.  The weights are the
benchmark's, made from --seed one layer at a time; the program gets them
stacked in bf16, the reference makes each layer again when it needs it
and carries the same bf16 values in float32.

Departure from the source: no sliding-window mask (the engine has none);
the cells keep every context under the window of 4096, where it never
clips.

``quant="fp8"`` is the control: every matmul's operands rounded to fp8
e4m3 (scaled per token, per output column), the nearest precision below
bf16.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _shapes(model: dict) -> dict:
    H, F = model["hidden_size"], model["intermediate_size"]
    d = H // model["num_attention_heads"]
    q, kv = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    return {"wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wo": (q, H),
            "w_gate": (H, F), "w_up": (H, F), "w_down": (F, H)}


def _normal(key, shape, std):
    w = jax.random.normal(key, shape, jnp.float32) * std
    return w.astype(jnp.bfloat16)


def layer_weights(model: dict, key, layer) -> dict:
    """One layer's matrices, bf16.  ``layer`` may be traced."""
    k = jax.random.fold_in(key, layer)
    resid = 0.02 / math.sqrt(2 * model["num_hidden_layers"])
    return {name: _normal(jax.random.fold_in(k, i), shape,
                          resid if name in ("wo", "w_down") else 0.02)
            for i, (name, shape) in enumerate(_shapes(model).items())}


def outer_weights(model: dict, key) -> dict:
    H, V = model["hidden_size"], model["vocab_size"]
    k = jax.random.fold_in(key, 1 << 20)
    return {"wte": _normal(jax.random.fold_in(k, 0), (V, H), 0.02),
            "head": _normal(jax.random.fold_in(k, 1), (H, V), 0.02)}


def make_params(model: dict, key) -> dict:
    """The whole model stacked as the engine takes it (norm gains are 1)."""
    L, H = model["num_hidden_layers"], model["hidden_size"]
    blocks = lax.map(lambda l: layer_weights(model, key, l),
                     jnp.arange(L, dtype=jnp.int32))
    blocks["attn_norm"] = jnp.ones((L, H), jnp.bfloat16)
    blocks["ffn_norm"] = jnp.ones((L, H), jnp.bfloat16)
    out = outer_weights(model, key)
    return {"wte": out["wte"], "blocks": blocks,
            "final_norm": jnp.ones((H,), jnp.bfloat16), "head": out["head"]}


# -- forward ---------------------------------------------------------------

def _fq(x, axis):
    """Round to fp8 (e4m3) with one scale along ``axis`` (absmax to the
    format's largest number, 448), and back."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def _rope(x, theta):
    T, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, model, quant):
    """x [T, H] float32 -> [T, H]; full causal attention over the T."""
    T, H = x.shape
    nH, nKV = model["num_attention_heads"], model["num_key_value_heads"]
    d = H // nH
    h = _rms(x, model["rms_norm_eps"])
    q = _rope(_mm(h, w["wq"], quant).reshape(T, nH, d), model["rope_theta"])
    k = _rope(_mm(h, w["wk"], quant).reshape(T, nKV, d), model["rope_theta"])
    v = _mm(h, w["wv"], quant).reshape(T, nKV, d)
    k, v = (jnp.repeat(a, nH // nKV, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + _mm(o.reshape(T, nH * d), w["wo"], quant)
    h = _rms(x, model["rms_norm_eps"])
    return x + _mm(jax.nn.silu(_mm(h, w["w_gate"], quant))
                   * _mm(h, w["w_up"], quant), w["w_down"], quant)


def logits_at(model: dict, key, tokens: np.ndarray, positions: list,
              quant=None) -> list:
    """Float32 logits of the full forward pass over ``tokens`` [n, T]
    (right-padded) at ``positions[i]`` (a list of indices) of sequence i."""
    n, T = tokens.shape
    gen_layer = jax.jit(lambda l: layer_weights(model, key, l))
    step = jax.jit(lambda x, w: lax.map(
        lambda xi: _layer(xi, w, model, quant), x))
    outer = jax.jit(lambda: outer_weights(model, key))()
    x = outer["wte"].astype(jnp.float32)[jnp.asarray(tokens)]
    for layer in range(model["num_hidden_layers"]):
        x = step(x, gen_layer(jnp.int32(layer)))
    head = jax.jit(lambda h, w: _mm(_rms(h, model["rms_norm_eps"]), w, quant))
    out = []
    for i, pos in enumerate(positions):
        padded = np.zeros((-(-len(pos) // 64) * 64,), np.int32)
        padded[:len(pos)] = pos                    # one shape per 64
        out.append(np.asarray(head(x[i][padded], outer["head"]))[:len(pos)])
    return out
