"""The seam between the serving engine and the model it serves.

``inference/serving.py`` keeps the rows, the pages, admission, prefix
hashing, chaining and sampling; a model brings what happens to a token
inside a layer.  A *serving model* is any object with:

- ``cfg`` (``vocab_size``, ``max_seq_len``, ``dtype``) and ``n_layers``;
- ``cache_spec(page_size) -> CacheSpec``: what one token stores in one
  layer, and so what a page is.  The engine's pool is a pair of planes
  ``[L, P, *page_shape]`` of ``spec.dtype`` (historically k and v; a
  model whose cache is one logical plane split where the lanes require
  it gives both halves).  What a page keeps beside its tokens (a scale
  per kv head for int8 pages) is a *side plane*, ``spec.side``: the
  engine allocates ``[L, P, *page_shape]`` of the plane's own dtype,
  zeroes a page's entries when the page goes to a new tenant (they are
  the page's state, and a fresh page is zeros), counts it in a page's
  bytes, donates it to the step and keeps what the step returns.  ``spec.hash_tag`` joins
  the prefix hash, so pages of two formats never alias in the cache;
- ``unsupported``: names of engine features this model does not serve
  (the engine fails with one error when asked for one);
- ``embed(params, tokens, positions) -> (x, ctx)``: the residual stream
  ``[C, qb, H]`` and whatever every layer shares (rotary angles);
- ``layer_groups(params) -> [LayerGroup]``: runs of alike layers.  A
  stacked group is scanned with the pool as the loop's carry, a single
  layer is applied where it stands;
- ``apply(x, k_pool, v_pool, base, layer_xs, rows, pos0, n_valid, ctx,
  *side) -> (x, k_pool, v_pool, ys, *side)``: one layer on the grid.
  The pools are flattened ``[L*P, ...]`` and ``base = l*P`` is added to
  every page id written or attended (``serving._run_layer_groups``
  states the rule); ``ys`` is the layer's counters or None.  ``side``
  is this layer's ``[P, *page_shape]`` slice of each side plane in the
  spec's order, under the layer's own page ids (no ``base``), and comes
  back updated behind ``ys``; a model that declares none gets and
  returns none, and its traced program has no operand for them;
- ``head(params, x)`` (the final norm) and ``logits(params, h)``;
- ``tick_stats(ys, n_valid_tokens) -> dict`` where ``ys`` is not None:
  what a tick's harvested counters add to the engine's ``stats``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

__all__ = ["CachePlane", "CacheSpec", "LayerGroup", "SidePlane"]


@dataclasses.dataclass(frozen=True)
class CachePlane:
    name: str
    page_shape: tuple          # one page of one layer
    width: int                 # values a token stores in this plane


@dataclasses.dataclass(frozen=True)
class SidePlane:
    name: str
    page_shape: tuple          # one page's entry in one layer
    dtype: Any


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    planes: tuple              # (CachePlane, CachePlane): the engine's pair
    dtype: Any
    side: tuple = ()           # SidePlanes, in the order ``apply`` gets them
    hash_tag: bytes = b""      # the format's mark in the prefix hash

    def page_bytes(self, n_layers: int) -> int:
        """Bytes one page costs across all layers, side planes included."""
        item = np.dtype(self.dtype).itemsize
        return n_layers * (
            item * sum(math.prod(p.page_shape) for p in self.planes)
            + sum(np.dtype(p.dtype).itemsize * math.prod(p.page_shape)
                  for p in self.side))


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    first: int                 # index of the group's first layer
    count: int
    xs: Any                    # what ``apply`` gets as ``layer_xs``
    stacked: bool = True       # leading dim ``count`` on every leaf of xs
