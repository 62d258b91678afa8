"""Plain reference for command-a-plus-05-2026 (``model_type: cohere2_moe``)
as this benchmark serves it.  For layer ``l`` of kind window or global
(``layer_types``), token at position ``p``, stream ``x``:

- ``h = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g`` (no bias): the
  layer's one norm (``use_parallel_block``).
- ``q = h Wq`` as 128 heads of 128; ``k = h Wk``, ``v = h Wv`` as 8 heads;
  query head ``i`` uses kv head ``i // 16``.  In a window layer q and k are
  rotated over all 128 dims by adjacent pairs ``(2i, 2i+1)`` with angle
  ``p * rope_theta^(-2i/128)`` (``rope_gptj``, ``rotary_pct`` 1); in a
  global layer they are not touched.
- ``a = softmax(q k^T / sqrt(128) + mask) v``, heads concatenated, ``Wo``.
  The mask admits key ``p' <= p``, and in a window layer also ``p - p' <
  sliding_window``.
- On the same ``h``: ``s = sigmoid(h Wr)`` over all 128 experts; the 8
  largest; ``w_e = s_e / sum of the 8``; ``routed = sum w_e E_e(h)``,
  ``shared = 1/4 sum_j S_j(h)``; every expert ``(silu(h Wg) * (h Wu)) Wd``.
- ``x <- x + a + routed + shared``.
- After the last layer the same norm with its own gain; ``logits = h E^T *
  logit_scale`` with ``E`` the embedding (tied).

One full causal forward pass over prompt + served tokens in float32 at
`highest` matmul precision: no cache, no paging, no batching of requests,
no kernels, and nothing imported from paddle_tpu.  So that 25k tokens fit,
a sequence goes through a layer in blocks of ``BLOCK`` queries: first every
block's keys and values, then each block's queries against the keys (all
of them under the causal mask in a global layer; in a window layer the
``sliding_window + BLOCK`` that end with the block, the rest being masked
anyway), its output projection, its experts and its residual add.  A
sequence is cut behind the last position asked for (causality: what comes
after changes nothing before).  Weights are made again for each layer.

**The share.**  ``model["held_experts"] = [first, count]`` names the routed
experts this chip holds (the configuration's deployment: one chip of
eight).  The router keeps its published width and its eight experts a
token; the weights are normalised over all eight chosen; the sum runs, by a
plain loop, over the held experts that a token chose; the shared experts
are always added.  What the absent experts would have added is left out,
here as in the program, and that partial result goes on to the next layer.
Expert ``e``'s weights are made from ``e`` itself, so the sixteen held here
are the same matrices the uncut model holds under those numbers.  The
vocabulary is the slice the configuration states.

It also holds the benchmark's weight generator.  The program gets the
weights stacked by layer kind in bf16 (``make_params``, as
models/cohere_moe.py takes them); the reference makes each layer again when
it needs it and carries the same bf16 values in float32.

Departures from the published description, each without effect on the
result in exact arithmetic:
- the program is handed ``Wq`` and ``Wk`` with every head's columns
  de-interleaved (pair firsts, then pair seconds) and rotates halves; the
  reference keeps the source's layout and rotates adjacent pairs;
- the four shared experts' matrices are generated side by side (one SwiGLU
  of width 4 x 4096 is their sum);
- ``shared_expert_combination_strategy: average`` is read as the mean of
  the four shared experts, added to the routed sum (``assumed`` in the
  configuration file, as are the mask's convention and every weight).

``quant="fp8"`` is the control, as the other references have it: the
precision below the configuration's bf16.  Every matmul's operands (the
projections, the attention's scores and values, the experts, the head) are
rounded to fp8 e4m3 (scaled per row, per output column); what the
configuration keeps in fp32 (accumulation, norm, softmax, the router)
stays in fp32.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
RESIDUAL_OUT = ("wo", "we_down", "ws_down")
KINDS = {"sliding_attention": "window", "full_attention": "global"}
BLOCK = 1024                     # queries a pass (cut to short sequences)


def _dims(model: dict) -> dict:
    return {"H": model["hidden_size"], "nH": model["num_attention_heads"],
            "nKV": model["num_key_value_heads"], "d": model["head_dim"],
            "F": model["intermediate_size"],
            "S": model["num_shared_experts"],
            "E": model.get("num_experts_published", model["num_experts"]),
            "k": model["num_experts_per_tok"],
            "L": model["num_hidden_layers"], "W": model["sliding_window"]}


def kinds(model: dict) -> list:
    """"window" or "global" for each layer served."""
    return [KINDS[t] for t in
            model["layer_types"][:model["num_hidden_layers"]]]


def held(model: dict) -> tuple:
    first, count = model.get("held_experts", [0, _dims(model)["E"]])
    return int(first), int(count)


def _matrix_shapes(model: dict) -> dict:
    d = _dims(model)
    H, q, kv = d["H"], d["nH"] * d["d"], d["nKV"] * d["d"]
    Fs = d["S"] * d["F"]
    return {"wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wo": (q, H),
            "router": (H, d["E"]), "ws_gate": (H, Fs), "ws_up": (H, Fs),
            "ws_down": (Fs, H)}


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def layer_weights(model: dict, key, layer) -> dict:
    """One layer's matrices in bf16, as the source lays them out.  ``layer``
    may be traced.  Expert ``e`` of a layer is made from (layer, e),
    whichever experts are held."""
    d = _dims(model)
    k = jax.random.fold_in(key, layer)
    resid = 0.02 / math.sqrt(2 * d["L"])
    w = {name: _normal(jax.random.fold_in(k, i), shape,
                       resid if name in RESIDUAL_OUT else 0.02)
         for i, (name, shape) in enumerate(_matrix_shapes(model).items())}
    w["norm"] = jnp.ones((d["H"],), jnp.bfloat16)
    first, count = held(model)

    def expert(e):
        ke = jax.random.fold_in(jax.random.fold_in(k, 1000), e)
        return {"we_gate": _normal(jax.random.fold_in(ke, 0),
                                   (d["H"], d["F"]), 0.02),
                "we_up": _normal(jax.random.fold_in(ke, 1),
                                 (d["H"], d["F"]), 0.02),
                "we_down": _normal(jax.random.fold_in(ke, 2),
                                   (d["F"], d["H"]), resid)}

    w.update(lax.map(expert, first + jnp.arange(count, dtype=jnp.int32)))
    return w


def outer_weights(model: dict, key) -> dict:
    """The embedding, which is the head too.  ``embedding_init_std`` is the
    toy twins' key: 64 wide, a layer adds about 1e-3 a dimension to the
    stream, which beside an embedding of 0.02 vanishes; the tied head would
    then read every token back as itself and no served token would depend
    on the cache.  The served configuration has no such key."""
    k = jax.random.fold_in(key, 1 << 20)
    return {"wte": _normal(jax.random.fold_in(k, 0),
                           (model["vocab_size"], model["hidden_size"]),
                           model.get("embedding_init_std", 0.02)),
            "final_norm": jnp.ones((model["hidden_size"],), jnp.bfloat16)}


def _deinterleave(w, heads: int, d: int):
    """``[H, heads * d]`` with every head's columns (0, 2, 4, ..., 1, 3,
    5, ...): the program's layout of Wq and Wk."""
    H = w.shape[0]
    return jnp.swapaxes(w.reshape(H, heads, d // 2, 2), 2, 3).reshape(
        H, heads * d)


def _for_program(model: dict, w: dict) -> dict:
    d = _dims(model)
    w = dict(w)
    w["wq"] = _deinterleave(w["wq"], d["nH"], d["d"])
    w["wk"] = _deinterleave(w["wk"], d["nKV"], d["d"])
    return w


def make_params(model: dict, key) -> dict:
    """The whole model as the engine takes it: the layers of a kind
    stacked, in the model's order (norm gains are 1)."""
    ks = kinds(model)
    params = dict(outer_weights(model, key))
    for kind in ("window", "global"):
        layers = [l for l, k in enumerate(ks) if k == kind]
        if layers:
            params[kind] = lax.map(
                lambda l: _for_program(model, layer_weights(model, key, l)),
                jnp.asarray(layers, jnp.int32))
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


def _norm(x, g, eps):
    xc = x - x.mean(-1, keepdims=True)
    return (xc * lax.rsqrt((xc * xc).mean(-1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate the adjacent pairs (2i, 2i+1) of ``x [T, heads, d]`` by the
    angle of ``pos [T]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _keys_values(x, w, model, kind, pos, quant):
    """k, v ``[T, nKV, d]`` of the tokens ``x [T, H]`` at ``pos [T]``."""
    d = _dims(model)
    h = _norm(x, w["norm"], model["layer_norm_eps"])
    k = _mm(h, w["wk"], quant).reshape(-1, d["nKV"], d["d"])
    v = _mm(h, w["wv"], quant).reshape(-1, d["nKV"], d["d"])
    if kind == "window":
        k = _rope(k, pos, float(model["rope_theta"]))
    return k, v


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def routing(h, w, model):
    """(chosen experts ``[T, k]``, their weights ``[T, k]``), in fp32."""
    s = jax.nn.sigmoid(jnp.matmul(h, w["router"].astype(jnp.float32),
                                  precision=HI))
    chosen, idx = lax.top_k(s, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return idx, chosen


def experts_part(h, w, model, quant):
    """This chip's part of the expert layer's feed-forward on normed ``h
    [T, H]``: the held experts' share of the routed sum, and the mean of
    the shared experts."""
    first, count = held(model)
    idx, weight = routing(h, w, model)
    y = _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], quant) / float(
        model["num_shared_experts"])
    for e in range(count):                          # a plain loop
        share = jnp.where(idx == first + e, weight, 0.0).sum(-1)   # [T]
        y = y + share[:, None] * _swiglu(h, w["we_gate"][e], w["we_up"][e],
                                         w["we_down"][e], quant)
    return y


def _block(x, k, v, w, model, kind, pos, kpos, quant):
    """One block of a layer: the tokens ``x [B, H]`` at ``pos [B]`` against
    the keys ``k``, ``v`` ``[S, nKV, d]`` at ``kpos [S]`` (a negative
    ``kpos`` marks a slot that holds no key) -> the stream after the
    layer ``[B, H]``."""
    d = _dims(model)
    G = d["nH"] // d["nKV"]
    h = _norm(x, w["norm"], model["layer_norm_eps"])
    q = _mm(h, w["wq"], quant).reshape(-1, d["nH"], d["d"])
    if kind == "window":
        q = _rope(q, pos, float(model["rope_theta"]))
    mask = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] >= 0)
    if kind == "window":
        mask &= pos[:, None] - kpos[None, :] < d["W"]

    def head(args):
        qh, i = args
        # _mm's rounding of both operands (fp8: per row, per column)
        # applies to the attention's two products as to every other
        s = _mm(qh, k[:, i // G].T, quant) / math.sqrt(d["d"])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return _mm(p, v[:, i // G], quant)

    o = lax.map(head, (jnp.swapaxes(q, 0, 1),
                       jnp.arange(d["nH"], dtype=jnp.int32)))
    a = _mm(jnp.swapaxes(o, 0, 1).reshape(-1, d["nH"] * d["d"]), w["wo"],
            quant)
    return x + a + experts_part(h, w, model, quant)


def _layer(x, w, model, kind, fns, B):
    """x [T, H] float32 -> [T, H], T a multiple of the block ``B``."""
    T, W = x.shape[0], _dims(model)["W"]
    kv_of, block_of = fns
    pos = jnp.arange(T, dtype=jnp.int32)
    k, v = (jnp.concatenate(a) for a in zip(*(
        kv_of(x[t:t + B], w, pos[t:t + B]) for t in range(0, T, B))))
    if kind == "window" and T > W + B:
        # the keys a block can see end with it and start W - 1 before its
        # first query: W + B slots, those before position 0 empty
        pad = jnp.zeros((W,) + k.shape[1:], k.dtype)
        k, v = jnp.concatenate([pad, k]), jnp.concatenate([pad, v])
        kpos = jnp.arange(-W, T, dtype=jnp.int32)
        return jnp.concatenate([
            block_of(x[t:t + B], k[t:t + W + B], v[t:t + W + B], w,
                     pos[t:t + B], kpos[t:t + W + B])
            for t in range(0, T, B)])
    return jnp.concatenate([block_of(x[t:t + B], k, v, w, pos[t:t + B], pos)
                            for t in range(0, T, B)])


def hidden_states(model: dict, key, tokens: np.ndarray, lengths=None,
                  quant=None):
    """Per sequence ``[T_i, H]`` float32: the last layer's residual stream
    (before the final norm) of the full forward pass over ``tokens [n,
    T]``, sequence ``i`` cut behind its first ``lengths[i]`` tokens where
    given and padded to whole blocks."""
    outer = jax.jit(lambda: outer_weights(model, key))()
    xs, blocks = [], []
    for i, row in enumerate(tokens):
        n = len(row) if lengths is None else min(len(row), lengths[i])
        B = BLOCK if n > BLOCK else -(-n // 16) * 16
        padded = np.zeros((-(-n // B) * B,), np.int32)
        padded[:n] = row[:n]
        xs.append(outer["wte"].astype(jnp.float32)[jnp.asarray(padded)])
        blocks.append(B)
    gen = jax.jit(lambda l: layer_weights(model, key, l))
    fns = {kind: (jax.jit(lambda x, w, pos, kind=kind: _keys_values(
                      x, w, model, kind, pos, quant)),
                  jax.jit(lambda x, k, v, w, pos, kpos, kind=kind: _block(
                      x, k, v, w, model, kind, pos, kpos, quant)))
           for kind in set(kinds(model))}
    for layer, kind in enumerate(kinds(model)):
        w = gen(jnp.int32(layer))
        xs = [_layer(x, w, model, kind, fns[kind], B)
              for x, B in zip(xs, blocks)]
    return xs, outer


def logits_at(model: dict, key, tokens: np.ndarray, positions: list,
              quant=None) -> list:
    """Float32 logits of the full forward pass over ``tokens`` [n, T]
    (right-padded) at ``positions[i]`` (a list of indices) of sequence i."""
    xs, outer = hidden_states(model, key, tokens,
                              [max(p) + 1 for p in positions], quant)
    scale = float(model.get("logit_scale", 1))
    head = jax.jit(lambda h, w, g: _mm(
        _norm(h, g, model["layer_norm_eps"]), w.T, quant) * scale)
    out = []
    for x, pos in zip(xs, positions):
        padded = np.zeros((-(-len(pos) // 64) * 64,), np.int32)
        padded[:len(pos)] = pos                    # one shape per 64
        out.append(np.asarray(head(x[padded], outer["wte"],
                                   outer["final_norm"]))[:len(pos)])
    return out
