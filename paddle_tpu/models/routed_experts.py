"""The routed-expert layer that is told which experts it holds, shared
by every model family that has one (``models/mla_moe.py``,
``models/cohere_moe.py``).

``s = sigmoid(h W_r)`` in fp32; chosen = top-k of ``s`` (of ``s + b``
where the family has a correction bias ``b``: selection only); ``w =
s[chosen] / sum s[chosen] * scaling``; ``y = sum_chosen w_e E_e(h) +
shared_scale * S(h)``, each expert SwiGLU.  No token is dropped.  The
router keeps its full width, the weights are normalised over all
chosen, and the sum runs over the chosen experts that are *held*
(``Routing.held = (first, count)``); the shared experts, their matrices
side by side as one SwiGLU of their summed width (which is their sum),
are always computed.  On one chip of an expert-parallel deployment this
is the chip's part of the layer, without the exchange.  What differs
between families is data: ``Routing`` and whether ``lp`` has a
``router_bias``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas import grouped_expert_matmul as gem

__all__ = ["EXPERT_STACKS", "Routing", "held_expert_stats", "mm",
           "moe_ffn", "route", "row_tile", "swiglu"]

EXPERT_STACKS = ("we_gate", "we_up", "we_down")
STATS_KEYS = ("moe_assigned_held", "moe_assigned_all",
              "moe_assigned_at_max", "moe_load_max_over_mean",
              "moe_tile_rows")


@dataclasses.dataclass(frozen=True)
class Routing:
    k: int                     # experts a token
    held: tuple                # (first, count) of the experts held here
    dtype: Any
    scaling: float = 1.0       # on the routed weights
    norm_topk: bool = True
    shared_scale: float = 1.0  # on the shared experts' sum (1/n: average)


def mm(x, w, dtype):
    """``x w`` in ``dtype`` with fp32 accumulation."""
    return jnp.einsum("...h,hk->...k", x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


def swiglu(h, w_gate, w_up, w_down, dtype):
    return mm(jax.nn.silu(mm(h, w_gate, dtype).astype(jnp.float32)).astype(
        dtype) * mm(h, w_up, dtype), w_down, dtype)


def route(h, lp, r: Routing):
    """Top-k routing of ``h [N, H]`` over ALL routed experts, in fp32:
    (expert ids ``[N, k]`` int32, weights ``[N, k]`` fp32)."""
    with jax.named_scope("layer/router"):
        s = jax.nn.sigmoid(jnp.matmul(
            h.astype(jnp.float32), lp["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        pick = s + lp["router_bias"] if "router_bias" in lp else s
        _, idx = lax.top_k(pick, r.k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if r.norm_topk:
            w = w / w.sum(-1, keepdims=True)
        if r.scaling != 1.0:
            w = w * r.scaling
        return idx.astype(jnp.int32), w


def _gate_up_arm(n_places: int, we, r: Routing) -> str:
    """The arm of ``grouped_expert_matmul`` that ``moe_ffn``'s
    gate-and-up product over ``n_places`` places of the experts ``we``
    (their ``we_up`` ``[..., H, F]``) runs on."""
    H, F = we["we_up"].shape[-2:]
    return gem.choose_impl(n_places * r.k, H, F, r.held[1], r.dtype,
                           gated=True)


def moe_ffn(h, lp, r: Routing, valid=None, stack=None):
    """The expert layer's feed-forward on normed ``h [N, H]``: this
    chip's part (module docstring).  ``valid [N]`` marks the places that
    hold a token; padding is routed nowhere.  Returns ``(y [N, H], tokens
    per held expert [count] int32)``.

    The held experts' matrices are ``lp``'s ``we_*`` ``[count, ...]``, or
    with ``stack = (weights, i)`` those of ALL expert layers flattened
    ``[layers * count, ...]`` and this layer's index among them: the
    products then find the layer's experts where they lie, because a
    grouped product is a custom call, and a layer sliced out of a
    scanned stack would be copied for it.

    Tokens are grouped by expert: the ``N*k`` assignments are sorted by
    held expert (assignments to experts held elsewhere sort last, into
    no group), the products run once per group over its own rows
    (``ops/pallas/grouped_expert_matmul.py``: gate and up in one call,
    then down; rows behind the last group are never visited), and each
    token sums its chosen experts' rows under its routing weights."""
    N, H = h.shape
    k, (first, count) = r.k, r.held
    idx, w = route(h, lp, r)
    with jax.named_scope("layer/experts"):
        local = idx - first
        held = (local >= 0) & (local < count)
        if valid is not None:
            held &= valid[:, None]
        e = jnp.where(held, local, count).reshape(-1)            # [N*k]
        order = jnp.argsort(e, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[e].add(1)[:count]
        we, base = lp, None
        if stack is not None:
            we, i = stack
            base = i * count
        xs = h.astype(r.dtype)[order // k]                       # [N*k, H]
        f32 = jnp.float32
        act = gem.grouped_expert_matmul(xs, we["we_gate"], sizes,
                                        we["we_up"], base,
                                        impl=_gate_up_arm(N, we, r))
        out = gem.grouped_expert_matmul(act, we["we_down"], sizes, base=base)
        back = jnp.zeros((N * k,), jnp.int32).at[order].set(
            jnp.arange(N * k, dtype=jnp.int32))
        rows = out[back].reshape(N, k, H).astype(f32)
        y = jnp.where(held[..., None], rows * w[..., None], 0.0).sum(1)
    with jax.named_scope("layer/shared_expert"):
        y = y.astype(r.dtype)
        shared = swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                        r.dtype)
        if r.shared_scale != 1.0:
            shared = (shared.astype(f32) * r.shared_scale).astype(r.dtype)
        y = y + shared
    return y, sizes


def row_tile(n_places: int, we, r: Routing):
    """Rows a met expert pays for in ``moe_ffn``'s gate-and-up product
    over ``n_places`` places of the experts ``we``, on the arm that
    product runs here: static a compiled step, an int32 scalar to send
    out beside the layer's tokens per held expert."""
    return jnp.int32(gem.row_tile(_gate_up_arm(n_places, we, r)))


def held_expert_stats(ys, n_tokens: int, r: Routing) -> dict:
    """A tick's counters (``STATS_KEYS``) from its ``(tokens per held
    expert, row_tile())`` per layer (the layer groups' ``ys``; a group
    without experts gives None).
    ``moe_assigned_at_max`` is what the held experts would hold if each
    held as much as its layer's most loaded one, so over
    ``moe_assigned_held`` it is the layers' max over mean weighted by
    their assignments (a tick, or summed over ticks, a run).
    ``moe_tile_rows`` is the rows the gate-and-up product paid for, each
    met expert's rounded up to the layer's row tile, so
    ``moe_assigned_held`` over it is how full the tiles were."""
    count = r.held[1]
    layers = [y for y in ys if y is not None]
    per = np.concatenate([np.asarray(sizes).reshape(-1, count)
                          for sizes, _ in layers])
    tile = np.concatenate([np.asarray(tm).reshape(-1, 1)
                           for _, tm in layers])
    held, at_max = int(per.sum()), int(per.max(1).sum()) * count
    return {"moe_assigned_held": held,
            "moe_assigned_all": r.k * n_tokens * per.shape[0],
            "moe_assigned_at_max": at_max,
            "moe_load_max_over_mean": at_max / held if held else 0.0,
            "moe_tile_rows": int((-(-per // tile) * tile).sum())}
