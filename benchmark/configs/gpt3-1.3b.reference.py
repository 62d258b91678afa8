"""Plain reference for the GPT-3 family as this benchmark trains it:
pre-LN decoder, learned positions, tied head, tanh-GELU, causal LM loss,
AdamW on every leaf.  Straightforward jax.numpy in float32 at `highest`
matmul precision: no kernels, no fusion, no sharding rules, and nothing
imported from paddle_tpu.

It also holds the benchmark's weight generator: the weights are the
benchmark's, made from --seed, and handed to the program and to this
reference alike.  Leaves of two or more dimensions hold bf16 values (the
configuration's weights are bf16 with no master copy); the reference
carries them on in float32.

Departures from the paper: 16 heads of 128 (Table 2.1's 24 x 128 is not
2048), vocabulary padded to 50304, context 1024 (see the config file).

``quant="fp8"`` is the control of the comparison that decides `correct`:
every matmul's two operands are rounded to fp8 e4m3 (scaled per row of the
activation, per output column of the weight), the nearest precision below
the bf16 the configuration states.  ``fault`` plants one of the faults a
training cell can have into the reference put in the program's place.
"""

from __future__ import annotations

import functools
import gc
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HI = lax.Precision.HIGHEST

_STD = {"wte": 0.02, "wpe": 0.01, "qkv_w": 0.02, "fc_w": 0.02}
_RESID = ("proj_w", "fc2_w")          # std 0.02 / sqrt(2 L)


def param_shapes(model: dict) -> dict:
    H, L, V, S = (model["d_model"], model["n_layers"], model["vocab_size"],
                  model["n_ctx"])
    F = model["ffn_mult"] * H
    return {
        "wte": (V, H), "wpe": (S, H),
        "blocks": {
            "ln1_g": (L, H), "ln1_b": (L, H),
            "qkv_w": (L, H, 3 * H), "qkv_b": (L, 3 * H),
            "proj_w": (L, H, H), "proj_b": (L, H),
            "ln2_g": (L, H), "ln2_b": (L, H),
            "fc_w": (L, H, F), "fc_b": (L, F),
            "fc2_w": (L, F, H), "fc2_b": (L, H),
        },
        "lnf_g": (H,), "lnf_b": (H,),
    }


def make_params(model: dict, key) -> dict:
    """The cell's initial weights in float32 (matrix leaves hold bf16
    values).  Trace it inside one jit; ``key`` is harness.seed_key(seed)."""
    shapes = param_shapes(model)
    flat, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name.endswith("_g"):
            leaf = jnp.ones(shape, jnp.float32)
        elif name.endswith("_b"):
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            std = (0.02 / math.sqrt(2 * model["n_layers"])
                   if name in _RESID else _STD[name])
            leaf = jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std
            leaf = leaf.astype(jnp.bfloat16).astype(jnp.float32)
        out.append(leaf)
    return jax.tree.unflatten(tree, out)


def make_batches(model: dict, key, n_batches: int, batch: int, seq: int):
    """[n_batches, batch, seq + 1] token ids; inputs are [..., :-1] and
    labels [..., 1:], so every row differs and the loss is the LM loss."""
    return jax.random.randint(key, (n_batches, batch, seq + 1), 0,
                              model["vocab_size"], jnp.int32)


# -- forward ---------------------------------------------------------------

def _fq(x, axis):
    """Round to fp8 (e4m3) with one scale along ``axis`` (absmax to the
    format's largest number, 448), and back."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    if quant == "fp8":
        # straight-through: the backward pass sees the rounded operands
        x = x + lax.stop_gradient(_fq(x, -1) - x)
        w = w + lax.stop_gradient(_fq(w, 0) - w)
    return jnp.matmul(x, w, precision=HI)


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _block(x, bp, model, quant):
    B, T, H = x.shape
    nH = model["n_heads"]
    dH = H // nH
    h = _ln(x, bp["ln1_g"], bp["ln1_b"], model["layer_norm_eps"])
    qkv = _mm(h, bp["qkv_w"], quant) + bp["qkv_b"]
    q, k, v = (a.reshape(B, T, nH, dH) for a in jnp.split(qkv, 3, -1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(dH)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(B, T, H)
    x = x + _mm(o, bp["proj_w"], quant) + bp["proj_b"]
    h = _ln(x, bp["ln2_g"], bp["ln2_b"], model["layer_norm_eps"])
    h = jax.nn.gelu(_mm(h, bp["fc_w"], quant) + bp["fc_b"], approximate=True)
    return x + _mm(h, bp["fc2_w"], quant) + bp["fc2_b"]


def loss_fn(params, tokens, labels, model, quant=None):
    """Mean next-token loss over the batch.  The vocabulary projection and
    its softmax are taken one row of the batch at a time and recomputed in
    the backward pass, so that one row's float32 logits are all that live;
    each block is recomputed likewise."""
    T = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:T]

    @jax.checkpoint
    def body(x, bp):
        return _block(x, bp, model, quant), None

    x, _ = lax.scan(body, x, params["blocks"])
    x = _ln(x, params["lnf_g"], params["lnf_b"], model["layer_norm_eps"])

    @jax.checkpoint
    def row_nll(xr, lab):
        logits = _mm(xr, params["wte"].T, quant)
        lse = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lab[..., None], -1)[..., 0]
        return (lse - gold).sum()

    return lax.map(lambda xl: row_nll(*xl), (x, labels)).sum() / labels.size


# -- the first steps, followed in float32 ----------------------------------

def leaf_norms(tree) -> dict:
    flat, _ = jax.tree.flatten_with_path(tree)
    return {"/".join(str(p.key) for p in path): float(
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
        for path, a in flat}


def chunk_sums(tree, scale: float = 1.0, k: int = 64) -> dict:
    """Per leaf, the signed sums of k contiguous chunks of its elements
    (times ``scale``): k fixed linear readings of a gradient, which move
    in the first order with any error in it where a norm moves only in
    the second."""
    def sums(a):
        flat = a.astype(jnp.float32).reshape(-1)
        flat = jnp.pad(flat, (0, (-flat.size) % k))
        return flat.reshape(k, -1).sum(1) * scale

    flat, _ = jax.tree.flatten_with_path(jax.jit(
        lambda t: jax.tree.map(sums, t))(tree))
    return {"/".join(str(p.key) for p in path): np.asarray(v)
            for path, v in flat}


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("hp",))
def _adamw_leaf(p, g, g1, g2, t, hp):
    """AdamW's step ``t`` on one leaf, the moments rebuilt from the
    gradients of this and the two earlier steps (they rest on the host:
    float32 weights, gradients and both moments do not fit one chip)."""
    lr, wd, b1, b2, eps = hp
    tf = t.astype(jnp.float32)
    m = (1 - b1) * (g + b1 * g1 + b1 * b1 * g2)
    v = (1 - b2) * (g * g + b2 * g1 * g1 + b2 * b2 * g2 * g2)
    step = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
    return p - lr * (step + wd * p)


def follow_steps(model: dict, train: dict, seed_key, batches, n_steps: int = 3,
                 devices=None, quant=None, fault=None) -> dict:
    """Losses of the first ``n_steps`` (at most 3), the norm of every leaf
    of the first gradient, and of the weights' change after the steps.

    ``batches`` is make_batches' array (any device); step i trains on
    batches[i].  With several ``devices`` the batch's rows are split over
    them and the weights replicated: data parallelism written as one
    sharding annotation, so that a batch of 16 rows fits."""
    assert n_steps <= 3
    # float32 weights + gradients take two thirds of a chip: nothing of an
    # earlier program (its executable's scratch memory) may linger
    gc.collect()
    jax.clear_caches()
    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), ("d",))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("d"))
    hp = (train["lr"], train["weight_decay"], train["beta1"], train["beta2"],
          train["adam_eps"])

    gen = jax.jit(lambda k: make_params(model, k), out_shardings=repl)
    params = gen(seed_key)

    def lossgrad(p, tok, lab):
        if fault == "half_batch":       # half the rows left out of the mean
            tok, lab = tok[: tok.shape[0] // 2], lab[: lab.shape[0] // 2]
        return jax.value_and_grad(loss_fn)(p, tok, lab, model, quant)

    step = jax.jit(lossgrad, out_shardings=(repl, repl))
    losses, hist, g1_norms, g1_sums = [], [], None, None
    for i in range(n_steps):
        b = jax.device_put(np.asarray(batches[i]), rows)
        loss, grads = step(params, b[:, :-1], b[:, 1:])
        losses.append(float(loss))
        if i == 0:
            g1_norms, g1_sums = leaf_norms(grads), chunk_sums(grads)
        if fault == "state_unchanged":
            continue
        flat_p, tree = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        del params, grads
        host_g, new_p = [], []
        for j, (p, g) in enumerate(zip(flat_p, flat_g)):
            old = [jax.device_put(h[j], repl) for h in hist[::-1]]
            while len(old) < 2:
                old.append(jnp.zeros_like(g))
            new_p.append(_adamw_leaf(p, g, old[0], old[1],
                                     jnp.int32(i + 1), hp))
            if i < n_steps - 1:
                host_g.append(np.asarray(g))
            flat_p[j] = flat_g[j] = None
            del p, g, old
        hist.append(host_g)
        params = jax.tree.unflatten(tree, new_p)
        del new_p
    del hist
    delta = jax.jit(lambda p, k: jax.tree.map(
        lambda a, b: a - b, p, make_params(model, k)), out_shardings=repl)
    dp_norms = leaf_norms(delta(params, seed_key))
    del params
    gc.collect()
    return {"losses": losses, "grad_norms": g1_norms, "grad_sums": g1_sums,
            "update_norms": dp_norms}
