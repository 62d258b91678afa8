"""Plain reference for granite-4.0-h-micro (``model_type:
granitemoehybrid``, no routed experts) as this benchmark serves it: whole.
Stream ``x``, 40 layers in the published order (``layer_types``):

    x = E[tokens] * 12                                   # embedding_multiplier
    for l in 0..39:
        h = rmsnorm(x, g_in[l], eps 1e-5)
        m = mamba2(h) if layer_types[l] == "mamba" else attention(h)
        x = x + 0.22 * m                                 # residual_multiplier
        h = rmsnorm(x, g_post[l])
        g, u = split(h @ W_in[l], 2)                     # 2048 -> 2 x 8192
        x = x + 0.22 * ((silu(g) * u) @ W_out[l])        # shared_mlp
    logits = (rmsnorm(x, g_f) @ E^T) / 8                 # tied; logits_scaling

    attention(h):  q, k, v = h Wq, h Wk, h Wv            # 32 / 8 / 8 heads of 64
        no position encoding; causal; scores * 0.015625  # attention_multiplier
        out = softmax(scores) v @ Wo

    mamba2(h):     z, xBC, dt = split(h @ W_inproj, [4096, 4352, 64])
        xBC_t = silu(b_c + sum_{j=0..3} w_c[:, j] * xBC_{t-3+j})   # zeros before t=0
        xs, B, C = split(xBC, [4096, 128, 128]);  xs -> [64 heads, 64]
        dt_t = softplus(dt_t + dt_bias)
        a_t  = exp(dt_t * A),  A = -exp(A_log)
        S_t  = a_t * S_{t-1} + dt_t * xs_t (outer) B_t   # per head [64, 128]; S_{-1} = 0
        y_t  = S_t C_t + D * xs_t
        y    = rmsnorm(y * silu(z), g_n) over all 4096   # gate before the norm
        out  = y @ W_outproj

One full causal forward pass over prompt + served tokens in float32 at
`highest` matmul precision: no cache, no paging, no snapshots, no batching
of requests, no kernels, and nothing imported from paddle_tpu.  **The
recurrence is a ``lax.scan`` over single tokens**, the equations above to
the letter (the program advances it 16 tokens at a time in closed form: the
two are independent); attention is a full masked softmax, a head at a
time.  A sequence is cut behind the last position asked for.  One layer's
weights are made, used for every sequence and dropped, so that the 12.8 GB
of fp32 weights never stand at once.

It also holds the benchmark's weight generator.  The program gets the
weights as models/granite_hybrid.py takes them: one stack a run of
consecutive layers of a kind, bf16 matrices, fp32 mixer vectors
(``make_params``); the reference makes each layer again when it needs it
and carries the same values in float32.

Departures from the published description: none in the arithmetic.  What
the source does not say (``assumed`` in the configuration file): the gated
norm's group is the whole 4096 (``mamba_n_groups`` 1), ``time_step_limit``
is (0, inf), every weight (init below), and the state's dtype (fp32).

Init: matrices normal(``init_std`` 0.02), the embedding
normal(``embedding_init_std`` 0.005: times 12 and tied, an embedding at the
matrices' scale would read every token back as itself whatever the layers
did), the convolution and its bias uniform +-1/2, ``A_log`` the log of
uniform 1..16, ``dt_bias`` the inverse softplus of a log-uniform
1e-3..1e-1, ``D`` and every gain 1.

``quant`` is the control, the precision below the configuration's:
``"fp8"``, as the other references have it, rounds every matmul's operands
(projections, feed-forward, the attention's two products, the head) to fp8
e4m3 (scaled per row, per output column) and leaves what the configuration
keeps in fp32 alone; ``"bf16_state"`` rounds the recurrence's state to
bf16 after every token and nothing else: the one precision this
configuration states that the others do not.

``states_at`` (``hidden_states``, ``logits_at``) also returns the
state-space layers' states after exactly so many tokens of a sequence,
for the comparison of the state a served request's snapshot holds
(benchmark/kinds/closed_turns.py).
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _dims(model: dict) -> dict:
    nH, hd = model["mamba_n_heads"], model["mamba_d_head"]
    return {"H": model["hidden_size"], "F": model["shared_intermediate_size"],
            "nA": model["num_attention_heads"],
            "nKV": model["num_key_value_heads"],
            "d": model["hidden_size"] // model["num_attention_heads"],
            "nH": nH, "hd": hd, "Di": nH * hd, "N": model["mamba_d_state"],
            "K": model["mamba_d_conv"],
            "conv": nH * hd + 2 * model["mamba_n_groups"]
            * model["mamba_d_state"]}


def kinds(model: dict) -> list:
    return list(model["layer_types"][:model["num_hidden_layers"]])


def runs(model: dict) -> list:
    """[kind, first layer, count] for each run of consecutive layers of
    one kind, in order."""
    out = []
    for l, kind in enumerate(kinds(model)):
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, l, 1])
    return out


def _matrix_shapes(model: dict, kind: str) -> dict:
    d = _dims(model)
    H, F = d["H"], d["F"]
    ff = {"w_in": (H, 2 * F), "w_out": (F, H)}
    if kind == "attention":
        kv = d["nKV"] * d["d"]
        return {"wq": (H, H), "wk": (H, kv), "wv": (H, kv), "wo": (H, H),
                **ff}
    return {"in_proj": (H, d["Di"] + d["conv"] + d["nH"]),
            "out_proj": (d["Di"], H), **ff}


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def layer_weights(model: dict, key, layer, kind: str) -> dict:
    """One layer of ``kind``: its matrices in bf16, the mixer's vectors in
    fp32.  ``layer`` may be traced."""
    d = _dims(model)
    k = jax.random.fold_in(key, layer)
    std = float(model.get("init_std", 0.02))
    w = {name: _normal(jax.random.fold_in(k, i), shape, std)
         for i, (name, shape) in enumerate(_matrix_shapes(model,
                                                          kind).items())}
    w["in_norm"] = jnp.ones((d["H"],), jnp.bfloat16)
    w["post_norm"] = jnp.ones((d["H"],), jnp.bfloat16)
    if kind == "mamba":
        f32, r = jnp.float32, 1.0 / math.sqrt(d["K"])
        kc, kb, ka, kd = (jax.random.fold_in(k, 100 + i) for i in range(4))
        dt = jnp.exp(jax.random.uniform(kd, (d["nH"],), f32, math.log(1e-3),
                                        math.log(1e-1)))
        w.update({
            "conv_w": jax.random.uniform(kc, (d["conv"], d["K"]), f32, -r, r),
            "conv_b": jax.random.uniform(kb, (d["conv"],), f32, -r, r),
            "A_log": jnp.log(jax.random.uniform(ka, (d["nH"],), f32, 1.0,
                                                16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((d["nH"],), f32),
            "norm": jnp.ones((d["Di"],), f32)})
    return w


def outer_weights(model: dict, key) -> dict:
    """The embedding, which is the head too."""
    k = jax.random.fold_in(key, 1 << 20)
    return {"wte": _normal(jax.random.fold_in(k, 0),
                           (model["vocab_size"], model["hidden_size"]),
                           float(model.get("embedding_init_std", 0.005))),
            "final_norm": jnp.ones((model["hidden_size"],), jnp.bfloat16)}


def make_params(model: dict, key) -> dict:
    """The whole model as the engine takes it: one stack a run of
    consecutive layers of a kind, in the model's order."""
    params = dict(outer_weights(model, key))
    params["runs"] = [
        lax.map(lambda l, kind=kind: layer_weights(model, key, l, kind),
                first + jnp.arange(count, dtype=jnp.int32))
        for kind, first, count in runs(model)]
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


def _rmsnorm(x, g, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g.astype(
        jnp.float32)


def _attention(h, w, model, quant):
    """h ``[T, H]`` -> the attention's output ``[T, H]``."""
    d = _dims(model)
    T, G = h.shape[0], d["nA"] // d["nKV"]
    q = _mm(h, w["wq"], quant).reshape(T, d["nA"], d["d"])
    k = _mm(h, w["wk"], quant).reshape(T, d["nKV"], d["d"])
    v = _mm(h, w["wv"], quant).reshape(T, d["nKV"], d["d"])
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    scale = float(model["attention_multiplier"])

    def head(args):
        qh, i = args
        s = _mm(qh, k[:, i // G].T, quant) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return _mm(p, v[:, i // G], quant)

    o = lax.map(head, (jnp.swapaxes(q, 0, 1),
                       jnp.arange(d["nA"], dtype=jnp.int32)))
    return _mm(jnp.swapaxes(o, 0, 1).reshape(T, -1), w["wo"], quant)


def _mamba(h, w, model, quant, keep_at):
    """h ``[T, H]`` -> the mixer's output ``[T, H]`` and the state after
    exactly ``keep_at`` tokens, ``[nH, hd, N]`` (zeros where ``keep_at`` is
    0): the recurrence token by token."""
    d = _dims(model)
    T, K = h.shape[0], d["K"]
    z, xBC, dt = jnp.split(_mm(h, w["in_proj"], quant),
                           [d["Di"], d["Di"] + d["conv"]], axis=-1)
    ext = jnp.concatenate([jnp.zeros((K - 1, d["conv"]), jnp.float32), xBC])
    xBC = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][:, j] * ext[j:j + T] for j in range(K)))
    xs, Bm, Cm = jnp.split(xBC, [d["Di"], d["Di"] + d["N"]], axis=-1)
    xs = xs.reshape(T, d["nH"], d["hd"])
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # [T, nH]
    A = -jnp.exp(w["A_log"])

    def token(carry, inp):
        S, kept = carry
        x_t, b_t, c_t, dt_t, t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        if quant == "bf16_state":
            # not a pair of casts: XLA:TPU may keep the excess precision
            S = lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        kept = jnp.where(t + 1 == keep_at, S, kept)
        return (S, kept), jnp.einsum("hdn,n->hd", S, c_t, precision=HI)

    zero = jnp.zeros((d["nH"], d["hd"], d["N"]), jnp.float32)
    (_, kept), y = lax.scan(token, (zero, zero), (
        xs, Bm, Cm, dt, jnp.arange(T, dtype=jnp.int32)))
    y = (y + w["D"][:, None] * xs).reshape(T, -1)
    y = _rmsnorm(y * jax.nn.silu(z), w["norm"], model["rms_norm_eps"])
    return _mm(y, w["out_proj"], quant), kept


def _layer(x, w, model, kind, quant, keep_at):
    """x ``[T, H]`` float32 -> the stream after the layer, and a
    state-space layer's state after ``keep_at`` tokens (else None)."""
    eps, r = model["rms_norm_eps"], float(model["residual_multiplier"])
    h = _rmsnorm(x, w["in_norm"], eps)
    if kind == "mamba":
        m, kept = _mamba(h, w, model, quant, keep_at)
    else:
        m, kept = _attention(h, w, model, quant), None
    x = x + r * m
    h = _rmsnorm(x, w["post_norm"], eps)
    g, u = jnp.split(_mm(h, w["w_in"], quant), 2, axis=-1)
    return x + r * _mm(jax.nn.silu(g) * u, w["w_out"], quant), kept


def hidden_states(model: dict, key, tokens: np.ndarray, lengths=None,
                  quant=None, states_at=None):
    """Per sequence ``[T_i, H]`` float32: the last layer's residual stream
    (before the final norm) of the full forward pass over ``tokens [n,
    T]``, sequence ``i`` cut behind its first ``lengths[i]`` tokens where
    given (padded to a multiple of 64, so that few lengths compile).  With
    ``states_at`` (a token count a sequence, 0 for none) also, third, per
    sequence the state-space layers' states after exactly that many
    tokens, numpy ``[layers, nH, hd, N]`` in the model's order (None
    where 0)."""
    outer = jax.jit(lambda: outer_weights(model, key))()
    xs = []
    for i, row in enumerate(tokens):
        n = len(row) if lengths is None else min(len(row), lengths[i])
        padded = np.zeros((-(-n // 64) * 64,), np.int32)
        padded[:n] = row[:n]
        xs.append(outer["wte"].astype(jnp.float32)[jnp.asarray(padded)]
                  * float(model["embedding_multiplier"]))
    gen = {kind: jax.jit(lambda l, kind=kind: layer_weights(model, key, l,
                                                            kind))
           for kind in set(kinds(model))}
    fwd = {kind: jax.jit(lambda x, w, at, kind=kind: _layer(
        x, w, model, kind, quant, at)) for kind in set(kinds(model))}
    at = [0] * len(xs) if states_at is None else list(states_at)
    states = [[] for _ in xs]
    for layer, kind in enumerate(kinds(model)):
        w = gen[kind](jnp.int32(layer))
        for i, x in enumerate(xs):
            xs[i], kept = fwd[kind](x, w, jnp.int32(at[i]))
            if kept is not None and at[i]:
                states[i].append(np.asarray(kept))
    if states_at is None:
        return xs, outer
    return xs, outer, [np.stack(s) if s else None for s in states]


def logits_at(model: dict, key, tokens: np.ndarray, positions: list,
              quant=None, states_at=None):
    """Float32 logits of the full forward pass over ``tokens`` [n, T]
    (right-padded) at ``positions[i]`` (a list of indices) of sequence i.
    With ``states_at`` (``hidden_states``) the same pass's states too:
    ``(logits, states)``."""
    xs, outer, *states = hidden_states(
        model, key, tokens, [max(p) + 1 for p in positions], quant,
        states_at)
    scale = 1.0 / float(model["logits_scaling"])
    head = jax.jit(lambda h, w, g: _mm(
        _rmsnorm(h, g, model["rms_norm_eps"]), w.T, quant) * scale)
    out = []
    for x, pos in zip(xs, positions):
        padded = np.zeros((-(-len(pos) // 64) * 64,), np.int32)
        padded[:len(pos)] = pos                    # one shape per 64
        out.append(np.asarray(head(x[padded], outer["wte"],
                                   outer["final_norm"]))[:len(pos)])
    return out if states_at is None else (out, states[0])
