"""Plain reference for MiMo-V2.5's language model (``model_type: mimo_v2``)
as this benchmark serves it.  For layer ``l`` of kind full or window
(``hybrid_layer_pattern``: 0 full, 1 window), token at position ``p``,
stream ``x``:

- ``h = x / sqrt(mean(x^2) + layernorm_epsilon) * g``.
- ``q = h Wq`` as 64 heads of 192; ``k = h Wk`` as ``nKV`` heads of 192 and
  ``v = h Wv`` as ``nKV`` heads of 128, ``nKV`` 4 in a full layer and 8 in a
  window layer (``swa_num_key_value_heads``); query head ``i`` uses kv
  head ``i // (64 / nKV)``.  No bias.
- q and k rotated over dims 0..63 only (``partial_rotary_factor`` 0.334 of
  192), by halves: the pair ``(i, i + 32)`` by the angle ``p *
  theta^(-2i/64)``, ``theta`` = ``rope_theta`` (1e7) in a full layer and
  ``swa_rope_theta`` (1e4) in a window layer.  Dims 64..191 untouched.
- ``v <- attention_value_scale * v`` (0.707).
- ``s_j = q . k_j / sqrt(192)`` over keys ``p' <= p``, and in a window layer
  ``p - p' < sliding_window`` (128).  A full layer: ``P = softmax(s)``.  A
  window layer: the head's sink logit joins the softmax with no value,
  ``P_j = exp(s_j - m) / (exp(sink - m) + sum_j' exp(s_j' - m))``, ``m``
  the larger of the sink and the scores.  ``x <- x + concat(P v) Wo``.
- ``h = RMSNorm(x)``; layer 0 (``moe_layer_freq`` 0): ``x <- x + (silu(h
  Wg) * (h Wu)) Wd`` of width 16384.  The others: ``s = sigmoid(h Wr)``
  over all 256 experts in fp32, the 8 largest of ``s + b`` (the correction
  bias, selection only), ``w_e = s_e / sum of the 8``, ``x <- x + sum w_e
  E_e(h)``, each expert a SwiGLU of 2048; no shared expert.
- ``logits = RMSNorm(x) W_head^T`` (the head is its own matrix).

One full causal forward pass over prompt + served tokens in float32 at
`highest` matmul precision: no cache, no paging, no batching of requests,
no kernels, and nothing imported from paddle_tpu.  So that four sequences
of up to 66k tokens fit a run, a sequence goes through a layer in blocks of
``BLOCK`` queries: first every block's keys and values, then each block's
queries against the keys (every earlier one in a full layer; in a window
layer the ``sliding_window + BLOCK`` that end with the block, the rest
being masked anyway), its output projection and its residual add; then
the feed-forward: the held experts each over the tokens routed to it,
gathered on the host (not every token through every held expert).  A
sequence is cut behind the last position asked for (causality: what comes
after changes nothing before).  Weights are made again for each layer and
dropped after it.

**The share.**  ``model["held_experts"] = [first, count]`` names the routed
experts this chip holds (the configuration's deployment: one chip of
sixteen).  The router keeps its published width and its eight experts a
token; the weights are normalised over all eight chosen; the sum runs over
the held experts that a token chose.  What the absent experts would have
added is left out, here as in the program, and that partial result goes
on to the next layer.  Expert ``e``'s weights are made from ``e`` itself,
so the sixteen held here are the same matrices the uncut model holds under
those numbers.  The vocabulary is the slice the configuration states.

It also holds the benchmark's weight generator.  The program gets the
weights stacked by ``<kind>_<ffn>`` in bf16 (``make_params``, as
models/mimo_v2.py takes them), the sinks and the correction bias in fp32;
the reference makes each layer again when it needs it and carries the same
values in float32.  No departure from the equations above; what the
source's config does not say is under ``assumed`` in the configuration
file (the rotated dims and their pairing, the score scale, the sink's
form, the value scale in every layer, the inits).

``quant="fp8"`` is the control, as the other references have it: the
precision below the configuration's bf16.  Every matmul's operands (the
projections, the attention's scores and values, the dense layer, the
experts, the head) are rounded to fp8 e4m3 (scaled per row, per output
column); what the configuration keeps in fp32 (accumulation, norms, the
softmax with its sink, the router) stays in fp32.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
RESIDUAL_OUT = ("wo", "w_down", "we_down")
BLOCK = 1024                     # queries a pass (cut to short sequences)
ROWS = 256                       # an expert's gathered tokens, rounded up


def _dims(model: dict) -> dict:
    return {"H": model["hidden_size"], "nH": model["num_attention_heads"],
            "dk": model["head_dim"], "dv": model["v_head_dim"],
            "R": int(model["head_dim"] * model["partial_rotary_factor"]),
            "F": model["intermediate_size"],
            "Fe": model["moe_intermediate_size"],
            "E": model.get("n_routed_experts_published",
                           model["n_routed_experts"]),
            "k": model["num_experts_per_tok"],
            "L": model["num_hidden_layers"], "W": model["sliding_window"]}


def kinds(model: dict) -> list:
    """"full" or "window" for each layer served."""
    return ["window" if t else "full" for t in
            model["hybrid_layer_pattern"][:model["num_hidden_layers"]]]


def stacks(model: dict) -> list:
    """``<kind>_<ffn>`` for each layer served: the program's stack."""
    return [f"{kind}_{'moe' if m else 'dense'}" for kind, m in
            zip(kinds(model), model["moe_layer_freq"])]


def kv_heads(model: dict, kind: str) -> int:
    return (model["num_key_value_heads"] if kind == "full"
            else model["swa_num_key_value_heads"])


def held(model: dict) -> tuple:
    first, count = model.get("held_experts", [0, _dims(model)["E"]])
    return int(first), int(count)


def _matrix_shapes(model: dict, stack: str) -> dict:
    d = _dims(model)
    kind, ffn = stack.split("_")
    H, nKV = d["H"], kv_heads(model, kind)
    out = {"wq": (H, d["nH"] * d["dk"]), "wk": (H, nKV * d["dk"]),
           "wv": (H, nKV * d["dv"]), "wo": (d["nH"] * d["dv"], H)}
    if ffn == "dense":
        out.update(w_gate=(H, d["F"]), w_up=(H, d["F"]),
                   w_down=(d["F"], H))
    else:
        out["router"] = (H, d["E"])
    return out


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def layer_weights(model: dict, key, layer, stack: str) -> dict:
    """One layer's matrices in bf16 (sinks and the correction bias in
    fp32), as the source lays them out.  ``layer`` may be traced.  Expert
    ``e`` of a layer is made from (layer, e), whichever experts are held.
    The sinks are normal(3, 1) and the bias normal(0, 0.01) (``assumed``)."""
    d = _dims(model)
    k = jax.random.fold_in(key, layer)
    resid = 0.02 / math.sqrt(2 * d["L"])
    w = {name: _normal(jax.random.fold_in(k, i), shape,
                       resid if name in RESIDUAL_OUT else 0.02)
         for i, (name, shape) in enumerate(
             _matrix_shapes(model, stack).items())}
    w["attn_norm"] = jnp.ones((d["H"],), jnp.bfloat16)
    w["ffn_norm"] = jnp.ones((d["H"],), jnp.bfloat16)
    if stack.startswith("window") and model["add_swa_attention_sink_bias"]:
        w["sinks"] = 3.0 + jax.random.normal(jax.random.fold_in(k, 500),
                                             (d["nH"],), jnp.float32)
    if stack.endswith("moe"):
        w["router_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(k, 501), (d["E"],), jnp.float32)
        first, count = held(model)

        def expert(e):
            ke = jax.random.fold_in(jax.random.fold_in(k, 1000), e)
            return {"we_gate": _normal(jax.random.fold_in(ke, 0),
                                       (d["H"], d["Fe"]), 0.02),
                    "we_up": _normal(jax.random.fold_in(ke, 1),
                                     (d["H"], d["Fe"]), 0.02),
                    "we_down": _normal(jax.random.fold_in(ke, 2),
                                       (d["Fe"], d["H"]), resid)}

        w.update(lax.map(expert, first + jnp.arange(count, dtype=jnp.int32)))
    return w


def outer_weights(model: dict, key) -> dict:
    """The embedding and the head, the vocabulary slice's rows."""
    k = jax.random.fold_in(key, 1 << 20)
    V, H = model["vocab_size"], model["hidden_size"]
    return {"wte": _normal(jax.random.fold_in(k, 0), (V, H), 0.02),
            "head": _normal(jax.random.fold_in(k, 1), (V, H), 0.02),
            "final_norm": jnp.ones((H,), jnp.bfloat16)}


def make_params(model: dict, key) -> dict:
    """The whole model as the engine takes it: each ``<kind>_<ffn>`` stack
    over its layers, in the model's order (norm gains are 1)."""
    st = stacks(model)
    params = dict(outer_weights(model, key))
    for stack in dict.fromkeys(st):
        layers = [l for l, s in enumerate(st) if s == stack]
        params[stack] = lax.map(
            lambda l, stack=stack: layer_weights(model, key, l, stack),
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
    return (x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _rope(x, pos, theta, R):
    """Rotate dims 0..R-1 of ``x [T, heads, d]`` by halves (pairs ``(i, i
    + R/2)``) by the angle of ``pos [T]``; the rest as they are."""
    inv = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :R // 2], x[..., R // 2:R]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., R:]], -1)


def _theta(model, kind):
    return float(model["rope_theta"] if kind == "full"
                 else model["swa_rope_theta"])


def _keys_values(x, w, model, kind, pos, quant):
    """k ``[T, nKV, dk]``, v ``[T, nKV, dv]`` (scaled) of ``x [T, H]``."""
    d = _dims(model)
    nKV = kv_heads(model, kind)
    h = _norm(x, w["attn_norm"], model["layernorm_epsilon"])
    k = _mm(h, w["wk"], quant).reshape(-1, nKV, d["dk"])
    v = _mm(h, w["wv"], quant).reshape(-1, nKV, d["dv"])
    return (_rope(k, pos, _theta(model, kind), d["R"]),
            v * float(model["attention_value_scale"]))


def _attend(x, k, v, w, model, kind, pos, kpos, quant):
    """The tokens ``x [B, H]`` at ``pos [B]`` against the keys ``k``, ``v``
    at ``kpos [S]`` (a negative ``kpos`` marks a slot that holds no key) ->
    the stream after the attention's residual add ``[B, H]``."""
    d = _dims(model)
    nKV = kv_heads(model, kind)
    G = d["nH"] // nKV
    h = _norm(x, w["attn_norm"], model["layernorm_epsilon"])
    q = _rope(_mm(h, w["wq"], quant).reshape(-1, d["nH"], d["dk"]), pos,
              _theta(model, kind), d["R"])
    mask = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] >= 0)
    if kind == "window":
        mask &= pos[:, None] - kpos[None, :] < d["W"]
    sinks = w.get("sinks")

    def head(args):
        qh, i = args
        # _mm's rounding of both operands (fp8: per row, per column)
        # applies to the attention's two products as to every other
        s = _mm(qh, k[:, i // G].T, quant) / math.sqrt(d["dk"])
        s = jnp.where(mask, s, -jnp.inf)
        m = s.max(-1, keepdims=True)
        if sinks is not None:
            m = jnp.maximum(m, sinks[i])
        e = jnp.exp(s - m)
        den = e.sum(-1, keepdims=True)
        if sinks is not None:
            den = den + jnp.exp(sinks[i] - m)
        return _mm(e / den, v[:, i // G], quant)

    o = lax.map(head, (jnp.swapaxes(q, 0, 1),
                       jnp.arange(d["nH"], dtype=jnp.int32)))
    return x + _mm(jnp.swapaxes(o, 0, 1).reshape(-1, d["nH"] * d["dv"]),
                   w["wo"], quant)


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def routing(h, w, model):
    """(chosen experts ``[T, k]``, their weights ``[T, k]``), in fp32: the
    top k of the scores plus the correction bias, weighted by the scores
    alone."""
    s = jax.nn.sigmoid(jnp.matmul(h, w["router"].astype(jnp.float32),
                                  precision=HI))
    _, idx = lax.top_k(s + w["router_bias"], model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, -1)
    if model["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return idx, chosen


def _dense(x, w, model, quant):
    h = _norm(x, w["ffn_norm"], model["layernorm_epsilon"])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)


def _normed_routed(x, w, model):
    h = _norm(x, w["ffn_norm"], model["layernorm_epsilon"])
    return (h,) + routing(h, w, model)


def _expert_rows(hr, share, gate, up, down, quant):
    """One expert over its gathered tokens ``hr [n, H]`` (padding rows
    carry a share of 0): ``share * E(hr)``."""
    return share[:, None] * _swiglu(hr, gate, up, down, quant)


def experts_part(h, idx, weight, w, model, expert_fn):
    """This chip's part of the expert layer on normed ``h [T, H]`` routed
    to ``idx``, ``weight`` ``[T, k]``: each held expert over the tokens that
    chose it, gathered (``ROWS`` at a time, so that few shapes compile),
    by ``expert_fn(hr, share, gate, up, down)`` (``_expert_rows``)."""
    first, count = held(model)
    idx, weight = np.asarray(idx), np.asarray(weight)
    y = jnp.zeros(h.shape, jnp.float32)
    for e in range(count):                          # a plain loop
        tok, slot = np.nonzero(idx == first + e)
        if not len(tok):
            continue
        n = -(-len(tok) // ROWS) * ROWS
        rows = np.zeros(n, np.int32)
        rows[:len(tok)] = tok
        share = np.zeros(n, np.float32)
        share[:len(tok)] = weight[tok, slot]
        rows = jnp.asarray(rows)
        y = y.at[rows].add(expert_fn(h[rows], jnp.asarray(share),
                                     w["we_gate"][e], w["we_up"][e],
                                     w["we_down"][e]))
    return y


def _layer(x, w, model, kind, ffn, fns, B):
    """x [T, H] float32 -> [T, H], T a multiple of the block ``B``."""
    T, W = x.shape[0], _dims(model)["W"]
    kv_of, attend_of, dense_of, route_of, expert_of = fns
    pos = jnp.arange(T, dtype=jnp.int32)
    k, v = (jnp.concatenate(a) for a in zip(*(
        kv_of(x[t:t + B], w, pos[t:t + B]) for t in range(0, T, B))))
    if kind == "window" and T > W + B:
        # the keys a block can see end with it and start W - 1 before its
        # first query: W + B slots, those before position 0 empty
        pad_k = jnp.zeros((W,) + k.shape[1:], k.dtype)
        pad_v = jnp.zeros((W,) + v.shape[1:], v.dtype)
        k, v = jnp.concatenate([pad_k, k]), jnp.concatenate([pad_v, v])
        kpos = jnp.arange(-W, T, dtype=jnp.int32)
        x = jnp.concatenate([
            attend_of(x[t:t + B], k[t:t + W + B], v[t:t + W + B], w,
                      pos[t:t + B], kpos[t:t + W + B])
            for t in range(0, T, B)])
    else:
        x = jnp.concatenate([attend_of(x[t:t + B], k, v, w, pos[t:t + B],
                                       pos) for t in range(0, T, B)])
    del k, v
    if ffn == "dense":
        return jnp.concatenate([dense_of(x[t:t + B], w)
                                for t in range(0, T, B)])
    h, idx, weight = (jnp.concatenate(a) for a in zip(*(
        route_of(x[t:t + B], w) for t in range(0, T, B))))
    return x + experts_part(h, idx, weight, w, model, expert_of)


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
    dense_of = jax.jit(lambda x, w: _dense(x, w, model, quant))
    route_of = jax.jit(lambda x, w: _normed_routed(x, w, model))
    expert_of = jax.jit(lambda hr, share, g, u, dn: _expert_rows(
        hr, share, g, u, dn, quant))
    fns = {kind: (jax.jit(lambda x, w, pos, kind=kind: _keys_values(
                      x, w, model, kind, pos, quant)),
                  jax.jit(lambda x, k, v, w, pos, kpos, kind=kind: _attend(
                      x, k, v, w, model, kind, pos, kpos, quant)),
                  dense_of, route_of, expert_of)
           for kind in set(kinds(model))}
    gen = {stack: jax.jit(lambda l, stack=stack: layer_weights(
        model, key, l, stack)) for stack in set(stacks(model))}
    for layer, stack in enumerate(stacks(model)):
        kind, ffn = stack.split("_")
        w = gen[stack](jnp.int32(layer))
        xs = [_layer(x, w, model, kind, ffn, fns[kind], B)
              for x, B in zip(xs, blocks)]
        del w
    return xs, outer


def logits_at(model: dict, key, tokens: np.ndarray, positions: list,
              quant=None) -> list:
    """Float32 logits of the full forward pass over ``tokens`` [n, T]
    (right-padded) at ``positions[i]`` (a list of indices) of sequence i."""
    xs, outer = hidden_states(model, key, tokens,
                              [max(p) + 1 for p in positions], quant)
    head = jax.jit(lambda h, w, g: _mm(
        _norm(h, g, model["layernorm_epsilon"]), w.T, quant))
    out = []
    for x, pos in zip(xs, positions):
        padded = np.zeros((-(-len(pos) // 64) * 64,), np.int32)
        padded[:len(pos)] = pos                    # one shape per 64
        out.append(np.asarray(head(x[padded], outer["head"],
                                   outer["final_norm"]))[:len(pos)])
    return out
