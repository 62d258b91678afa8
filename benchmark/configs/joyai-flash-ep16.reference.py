"""Plain reference for JoyAI-LLM-Flash (``model_type: joyai_llm_flash``, the
DeepSeek-V3 family's block) as this benchmark serves it: RMSNorm
(eps 1e-6), multi-head latent attention in its EXPANDED form with
interleaved rotary pairs, one leading dense SwiGLU layer, then expert
layers (sigmoid router in fp32 over all 256 experts, correction bias for
the choice only, top-8, weights normalised over the eight and scaled by
2.5, one shared expert), untied head, and the multi-token-prediction
module.  One full causal forward pass over prompt + served tokens in
float32 at `highest` matmul precision, layer by layer so that it fits: no
cache, no paging, no batching of requests, no kernels, and nothing
imported from paddle_tpu.

**The share.**  ``model["held_experts"] = [first, count]`` names the routed
experts this chip holds (the configuration's deployment: one chip of
sixteen).  The router keeps its published width and its eight experts a
token; the weights are normalised over all eight chosen; the sum runs, by a
plain loop, over the held experts that a token chose; the shared expert is
always added.  What the absent experts would have added is left out, here as
in the program, and that partial result goes on to the next layer.  Expert
``e``'s weights are made from ``e`` itself, so the sixteen held here are the
same matrices the uncut model holds under those numbers.  The vocabulary is
the slice the configuration states (embedding and head rows alike).

It also holds the benchmark's weight generator.  The program gets the
weights stacked in bf16 (``make_params``: ``dense`` and ``moe`` stacks as
models/mla_moe.py takes them); the reference makes each layer again when it
needs it and carries the same bf16 values in float32.

Departures from the published description, each without effect on the
result in exact arithmetic:
- ``kv_b_proj`` is generated whole ``[512, 32 * (128 + 128)]`` as the source
  stores it; the program is handed its halves ``wk_b`` / ``wv_b``.
- MTP: the pair is taken embedding first, ``[RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(h_i)] W_eh``, as the published implementations do (the paper
  writes the hidden state first), and ``h_i`` is the main model's residual
  stream before its final norm.
- The correction bias and every weight are seeded random values
  (``assumed`` in the configuration file).

``quant="fp8"`` is the control, as the Mistral reference has it: the
precision below the configuration's bf16.  Every matmul's operands (the
projections, the attention's scores and values, the experts, the head) are
rounded to fp8 e4m3 (scaled per row, per output column); what the
configuration keeps in fp32 (accumulation, norms, softmax, the router)
stays in fp32.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
RESIDUAL_OUT = ("wo", "w_down", "we_down", "ws_down")


def _dims(model: dict) -> dict:
    nH = model["num_attention_heads"]
    return {"H": model["hidden_size"], "nH": nH,
            "qr": model["q_lora_rank"], "kr": model["kv_lora_rank"],
            "dn": model["qk_nope_head_dim"], "dr": model["qk_rope_head_dim"],
            "dv": model["v_head_dim"], "F": model["intermediate_size"],
            "Fm": model["moe_intermediate_size"],
            "Fs": model["moe_intermediate_size"] * model["n_shared_experts"],
            "E": model.get("n_routed_experts_published",
                           model["n_routed_experts"]),
            "k": model["num_experts_per_tok"],
            "L": model["num_hidden_layers"],
            "nd": model["first_k_dense_replace"]}


def held(model: dict) -> tuple:
    d = _dims(model)
    first, count = model.get("held_experts", [0, d["E"]])
    return int(first), int(count)


def _matrix_shapes(model: dict, kind: str) -> dict:
    d = _dims(model)
    H = d["H"]
    out = {"wq_a": (H, d["qr"]), "wq_b": (d["qr"], d["nH"] * (d["dn"] + d["dr"])),
           "wkv_a": (H, d["kr"] + d["dr"]),
           "wkv_b": (d["kr"], d["nH"] * (d["dn"] + d["dv"])),
           "wo": (d["nH"] * d["dv"], H)}
    if kind == "dense":
        out.update(w_gate=(H, d["F"]), w_up=(H, d["F"]), w_down=(d["F"], H))
    else:
        out.update(router=(H, d["E"]), ws_gate=(H, d["Fs"]),
                   ws_up=(H, d["Fs"]), ws_down=(d["Fs"], H))
    return out


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def _gains(model: dict) -> dict:
    d = _dims(model)
    return {"attn_norm": jnp.ones((d["H"],), jnp.bfloat16),
            "q_norm": jnp.ones((d["qr"],), jnp.bfloat16),
            "kv_norm": jnp.ones((d["kr"],), jnp.bfloat16),
            "ffn_norm": jnp.ones((d["H"],), jnp.bfloat16)}


def layer_weights(model: dict, key, layer, kind: str) -> dict:
    """One layer's matrices in bf16, as the source lays them out.  ``layer``
    may be traced; ``kind`` is "dense" or "moe".  Expert ``e`` of a layer is
    made from (layer, e), whichever experts are held."""
    d = _dims(model)
    k = jax.random.fold_in(key, layer)
    resid = 0.02 / math.sqrt(2 * d["L"])
    w = {name: _normal(jax.random.fold_in(k, i), shape,
                       resid if name in RESIDUAL_OUT else 0.02)
         for i, (name, shape) in enumerate(_matrix_shapes(model, kind).items())}
    w.update(_gains(model))
    if kind == "moe":
        first, count = held(model)
        w["router_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(k, 99), (d["E"],), jnp.float32)

        def expert(e):
            ke = jax.random.fold_in(jax.random.fold_in(k, 1000), e)
            return {"we_gate": _normal(jax.random.fold_in(ke, 0),
                                       (d["H"], d["Fm"]), 0.02),
                    "we_up": _normal(jax.random.fold_in(ke, 1),
                                     (d["H"], d["Fm"]), 0.02),
                    "we_down": _normal(jax.random.fold_in(ke, 2),
                                       (d["Fm"], d["H"]), resid)}

        w.update(lax.map(expert, first + jnp.arange(count, dtype=jnp.int32)))
    return w


def outer_weights(model: dict, key) -> dict:
    H, V = model["hidden_size"], model["vocab_size"]
    k = jax.random.fold_in(key, 1 << 20)
    return {"wte": _normal(jax.random.fold_in(k, 0), (V, H), 0.02),
            "head": _normal(jax.random.fold_in(k, 1), (H, V), 0.02)}


def mtp_weights(model: dict, key) -> dict:
    H = model["hidden_size"]
    k = jax.random.fold_in(key, (1 << 20) + 1)
    return {"enorm": jnp.ones((H,), jnp.bfloat16),
            "hnorm": jnp.ones((H,), jnp.bfloat16),
            "eh_proj": _normal(jax.random.fold_in(k, 0), (2 * H, H), 0.02),
            "layer": layer_weights(model, k, model["num_hidden_layers"],
                                   "moe"),
            "final_norm": jnp.ones((H,), jnp.bfloat16)}


def _for_program(model: dict, w: dict) -> dict:
    """The program's layout of one layer: ``wkv_b`` as its two halves."""
    d = _dims(model)
    w = dict(w)
    kvb = w.pop("wkv_b").reshape(d["kr"], d["nH"], d["dn"] + d["dv"])
    w["wk_b"], w["wv_b"] = kvb[..., :d["dn"]], kvb[..., d["dn"]:]
    return w


def make_params(model: dict, key) -> dict:
    """The whole model stacked as the engine takes it (norm gains are 1)."""
    d = _dims(model)
    out = outer_weights(model, key)
    params = {
        "wte": out["wte"], "head": out["head"],
        "final_norm": jnp.ones((d["H"],), jnp.bfloat16),
        "dense": lax.map(
            lambda l: _for_program(model, layer_weights(model, key, l,
                                                        "dense")),
            jnp.arange(d["nd"], dtype=jnp.int32)),
        "moe": lax.map(
            lambda l: _for_program(model, layer_weights(model, key, l,
                                                        "moe")),
            jnp.arange(d["nd"], d["L"], dtype=jnp.int32))}
    if model["num_nextn_predict_layers"]:
        m = mtp_weights(model, key)
        m["layer"] = _for_program(model, m["layer"])
        params["mtp"] = m
    return params


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


def _rms(x, g, eps):
    return (x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _rope(x, theta):
    """Rotate the adjacent pairs (2i, 2i+1) of ``x [T, heads, d]`` by the
    angle of position t (``rope_interleave``)."""
    T, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(x, w, model, quant):
    """Expanded MLA over the whole sequence ``x [T, H]`` (already normed)."""
    d = _dims(model)
    T, nH, dn, dr, dv = x.shape[0], d["nH"], d["dn"], d["dr"], d["dv"]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    cq = _rms(_mm(x, w["wq_a"], quant), w["q_norm"], eps)
    q = _mm(cq, w["wq_b"], quant).reshape(T, nH, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], theta)
    kv = _mm(x, w["wkv_a"], quant)
    c_kv = _rms(kv[:, :d["kr"]], w["kv_norm"], eps)
    k_rope = _rope(kv[:, None, d["kr"]:], theta)               # one for all
    kvb = _mm(c_kv, w["wkv_b"], quant).reshape(T, nH, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(h):
        qn, qr, kn, vh = h
        # _mm's rounding of both operands (fp8: per row, per column)
        # applies to the attention's two products as to every other
        s = (_mm(qn, kn.T, quant) + _mm(qr, k_rope[:, 0].T, quant)
             ) / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return _mm(p, vh, quant)

    o = lax.map(head, tuple(jnp.swapaxes(a, 0, 1)
                            for a in (q_nope, q_rope, k_nope, v)))
    return _mm(jnp.swapaxes(o, 0, 1).reshape(T, nH * dv), w["wo"], quant)


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def routing(x, w, model):
    """(chosen experts ``[T, k]``, their weights ``[T, k]``), in fp32."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"].astype(jnp.float32),
                                  precision=HI))
    _, idx = lax.top_k(s + w["router_bias"], model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, -1)
    if model["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return idx, chosen * model["routed_scaling_factor"]


def experts_part(x, w, model, quant):
    """This chip's part of the expert layer's feed-forward on normed ``x
    [T, H]``: the held experts' share of the routed sum, and the shared
    expert."""
    first, count = held(model)
    idx, weight = routing(x, w, model)
    y = _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"], quant)
    for e in range(count):                          # a plain loop
        share = jnp.where(idx == first + e, weight, 0.0).sum(-1)   # [T]
        y = y + share[:, None] * _swiglu(x, w["we_gate"][e], w["we_up"][e],
                                         w["we_down"][e], quant)
    return y


def _layer(x, w, model, kind, quant):
    """x [T, H] float32 -> [T, H]; full causal attention over the T."""
    eps = model["rms_norm_eps"]
    x = x + _attention(_rms(x, w["attn_norm"], eps), w, model, quant)
    h = _rms(x, w["ffn_norm"], eps)
    return x + (_swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)
                if kind == "dense" else experts_part(h, w, model, quant))


def hidden_states(model: dict, key, tokens: np.ndarray, quant=None):
    """``[n, T, H]`` float32: the last layer's residual stream (before the
    final norm) of the full forward pass over ``tokens [n, T]``."""
    d = _dims(model)
    outer = jax.jit(lambda: outer_weights(model, key))()
    x = outer["wte"].astype(jnp.float32)[jnp.asarray(tokens)]
    for kind in ("dense", "moe"):
        gen = jax.jit(lambda l, kind=kind: layer_weights(model, key, l, kind))
        step = jax.jit(lambda x, w, kind=kind: lax.map(
            lambda xi: _layer(xi, w, model, kind, quant), x))
        lo, hi = (0, d["nd"]) if kind == "dense" else (d["nd"], d["L"])
        for layer in range(lo, hi):
            x = step(x, gen(jnp.int32(layer)))
    return x, outer


def logits_at(model: dict, key, tokens: np.ndarray, positions: list,
              quant=None) -> list:
    """Float32 logits of the full forward pass over ``tokens`` [n, T]
    (right-padded) at ``positions[i]`` (a list of indices) of sequence i."""
    x, outer = hidden_states(model, key, tokens, quant)
    head = jax.jit(lambda h, w: _mm(
        _rms(h, jnp.ones(h.shape[-1:]), model["rms_norm_eps"]), w, quant))
    out = []
    for i, pos in enumerate(positions):
        padded = np.zeros((-(-len(pos) // 64) * 64,), np.int32)
        padded[:len(pos)] = pos                    # one shape per 64
        out.append(np.asarray(head(x[i][padded], outer["head"]))[:len(pos)])
    return out


def mtp_logits(model: dict, key, tokens: np.ndarray, quant=None):
    """``[n, T-1, V]`` float32: row i of the multi-token-prediction module
    predicts token i+2 from the main model's state at i and token i+1."""
    x, outer = hidden_states(model, key, tokens, quant)
    m = jax.jit(lambda: mtp_weights(model, key))()
    eps = model["rms_norm_eps"]
    emb = outer["wte"].astype(jnp.float32)[jnp.asarray(tokens)[:, 1:]]
    pair = jnp.concatenate([_rms(emb, m["enorm"], eps),
                            _rms(x[:, :-1], m["hnorm"], eps)], -1)
    h = _mm(pair, m["eh_proj"], quant)
    h = lax.map(lambda hi: _layer(hi, m["layer"], model, "moe", quant), h)
    return _mm(_rms(h, m["final_norm"], eps), outer["head"], quant)
