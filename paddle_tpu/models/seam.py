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
- optionally ``cache_classes(page_size) -> (CacheClass, ..., StateClass)``:
  a model whose layers do not all keep a token equally long declares
  *cache classes* (below), and one whose layers keep a state of fixed
  size a request a *state class* behind them.  A model without the
  method has one class, all its layers under ``cache_spec``, and nothing
  in the step it traces says otherwise;
- ``unsupported``: names of engine features this model does not serve
  (the engine fails with one error when asked for one);
- ``embed(params, tokens, positions) -> (x, ctx)``: from the tick's
  tokens and their positions on the *packed* axis ``[T]`` (below), the
  residual stream ``[T, H]`` and whatever every layer shares (rotary
  angles, per packed token).  The engine then puts the tick's
  ``TokenLayout`` into ``ctx["layout"]``;
- ``layer_groups(params) -> [LayerGroup]``: runs of alike layers, in the
  model's order.  A stacked group is scanned with its class's pool as
  the loop's carry, a single layer is applied where it stands;
- ``apply(x, k_pool, v_pool, base, layer_xs, rows, pos0, n_valid, ctx,
  *side) -> (x, k_pool, v_pool, ys, *side)``: one layer.  ``x`` is
  packed, ``[T, H]``: what a token does alone (norms, projections, the
  feed-forward, experts) runs over the packed axis, and
  ``ctx["layout"].valid`` says which of its places hold a token.  What
  works by rows (the page write, the paged attention, a per-row
  adapter) wants the rows' grid ``[C, qb, ...]`` that ``rows``,
  ``pos0`` and ``n_valid`` describe: ``ctx["layout"].to_grid`` lays a
  packed array onto it and ``.to_packed`` brings one back, and they are
  the one way between the layouts.  The pools are flattened
  ``[L*P, ...]`` and ``base = l*P`` is added to every page id written
  or attended (``serving._run_layer_groups`` states the rule); ``ys``
  is the layer's counters (any pytree of arrays) or None.  Pools,
  ``rows`` and ``base`` are those of the layer's cache class, and
  ``ctx["cache_class"]`` is its index.  ``side``
  is this layer's ``[P, *page_shape]`` slice of each side plane in the
  spec's order, under the layer's own page ids (no ``base``), and comes
  back updated behind ``ys``; a model that declares none gets and
  returns none, and its traced program has no operand for them;
- ``head(params, x)`` (the final norm) and ``logits(params, h)``, both
  over ``[N, H]``: each row's last token, or every packed token when
  the engine verifies drafts;
- ``tick_stats(ys, n_valid_tokens) -> dict`` where ``ys`` is not None:
  what a tick's harvested counters add to the engine's ``stats``.

**Cache classes.**  A class is a set of layers that share one pool
``[L_c, P_c, *page_shape]``, one page table and one free list: a page id
of the class costs a page in each of ITS layers and in no other.  Every
class's table is indexed by the request's logical block, as the one
table always was, and a request takes pages in every class as it grows.
``CacheClass.window`` is how many tokens back a layer of the class can
still read (``None``: all of them).  The rules, stated once:

- class 0 is the one whose layers read everything (``window is None``);
  its pools and table are the engine's ``k_pages``, ``v_pages`` and the
  ``ptable`` operand, as for a model with one class.  Further classes'
  pools and tables ride behind the side planes in the step's varargs;
- in a windowed class a page whose last token is ``window`` or more
  behind the request's next query is let go between ticks: its table
  slot gets the sink (page 0 of the class), the page goes where a
  finished request's goes (a cached page to the evictable set once no
  in-flight program can read it, another to the free list).  The
  model's attention must mask what the window hides: slots behind the
  window hold the sink or anything else;
- one hash chain serves every class; each class keeps its pages under
  the chain's hashes (its spec's ``hash_tag`` joins them).  A prefix
  hit of ``b`` tokens needs class 0 to hold ``[0, b)`` and every
  windowed class the pages that cover ``[max(0, b - window), b)``;
- admission needs room in every class; preemption, abort and finish
  release in every class; side planes are class 0's alone.

**The state class.**  Layers whose cache does not grow with the context
(a recurrence's state, a convolution's last inputs) form a *state
class* (``StateClass``): its unit is a **slot**, per layer a tuple of
planes of fixed shape, each of its own dtype, and its pool is one array
a plane, ``[L_s, S, *shape]``.  A model declares at most one, behind its
paged classes; the pools and a ``[B + 1, 2]`` table ride in the step as a
further class's do, and ``apply`` gets as ``rows`` the ``[C, 2]`` slot ids
of each row's request: where its state is READ at the start of the tick
and where it is WRITTEN at its end (under the flattening of
``serving._run_layer_groups``: add ``base``).  The model advances the
state over the row's tokens, carrying it across the consecutive rows of
one request, and writes nothing for an idle row, whose pair is
``(STATE_DUMP, STATE_DUMP)``.  The rules, stated once:

- slot ``STATE_ZERO`` is never written and reads as zeros; slot
  ``STATE_DUMP`` is never read.  Slots ``2 .. B + 1`` are *live* slots,
  one an engine row; the rest are *snapshot* slots;
- a live request holds its live slot from admission to finish, abort or
  preemption.  A new tenant's first tick reads ``STATE_ZERO`` (or the
  snapshot its prefix hit) and writes its live slot: the model never
  sees the last tenant's state;
- snapshots are kept under the prefix chain's hashes (the class's
  ``hash_tag`` joins them) with the refcount / evictable / LRU life that
  cached pages have.  A snapshot under hash ``j`` is the state after
  exactly ``(j + 1) * page_size`` tokens.  **A prefix hit of ``b`` tokens
  needs, beside what the paged classes need, a snapshot at ``b``**;
- when snapshots are taken is the engine's: a request's prefill is cut
  so that a tick's last chunk ends on a page boundary where it can, and
  whenever a request's processed length stands on a page boundary at the
  end of a tick, that tick writes its state into a snapshot slot instead
  of its live slot, and its next tick reads it from there.  No state is
  ever copied: taking a snapshot, loading a hit and resetting a tenant
  are choices of the two slot ids;
- a request holds the newest snapshot it wrote or hit (its *rolling*
  snapshot; the one before becomes an ordinary cached snapshot,
  evictable once unreferenced), so a preempted request resumes from it
  through the prefix cache, or from token 0 when it was evicted;
- when no snapshot slot can be had the tick writes the live slot and a
  counter says so; nothing waits on one.

**The packed axis.**  A tick carries ``sum(m)`` tokens, row ``c`` of the
grid ``m[c]`` of them (an idle row none), and the grid has ``C * qb``
places whatever it carries.  The packed axis holds the tick's tokens in
row-major order, row 0's first, and padding behind them up to ``T``,
one of the engine's step sizes (``ServingEngine.rungs``); the
largest is ``C * qb``, and there the packed axis IS the grid flattened,
so both maps are reshapes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

__all__ = ["CacheClass", "CachePlane", "CacheSpec", "LayerGroup",
           "STATE_DUMP", "STATE_ZERO", "SidePlane", "StateClass",
           "StatePlane", "TokenLayout", "cache_classes", "token_layout"]

# the state class's two fixed slots (module docstring)
STATE_ZERO, STATE_DUMP = 0, 1


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
class CacheClass:
    name: str
    n_layers: int              # layers whose pages live in this pool
    spec: CacheSpec
    window: Optional[int] = None   # tokens back a layer reads; None: all

    def live_from(self, next_query: int) -> int:
        """The first position a query at ``next_query`` (and any later
        one) can still read in this class."""
        return 0 if self.window is None else max(
            0, next_query - self.window + 1)


@dataclasses.dataclass(frozen=True)
class StatePlane:
    name: str
    shape: tuple               # one slot of one layer
    dtype: Any


@dataclasses.dataclass(frozen=True)
class StateClass:
    """Layers whose cache is a slot of fixed size a request (module
    docstring: the state class)."""
    name: str
    n_layers: int
    planes: tuple              # (StatePlane, StatePlane): the step's pair
    hash_tag: bytes = b""      # the format's mark in the snapshots' hashes

    def slot_bytes(self) -> int:
        """Bytes one slot costs across the class's layers."""
        return self.n_layers * sum(
            np.dtype(p.dtype).itemsize * math.prod(p.shape)
            for p in self.planes)


def cache_classes(model, page_size: int) -> tuple:
    """The model's cache classes; one, over every layer, for a model
    that declares none."""
    if hasattr(model, "cache_classes"):
        classes = tuple(model.cache_classes(page_size))
        paged = [c for c in classes if isinstance(c, CacheClass)]
        if (not paged or classes[:len(paged)] != tuple(paged)
                or len(classes) - len(paged) > 1
                or paged[0].window is not None
                or any(c.spec.side for c in paged[1:])
                or any(len(c.planes) != 2 for c in classes[len(paged):])):
            raise ValueError(
                "class 0 is paged, reads everything and owns the side "
                "planes; at most one state class, of two planes, follows "
                "the paged classes (models/seam.py: cache classes, the "
                "state class)")
        return classes
    return (CacheClass("global", model.n_layers,
                       model.cache_spec(page_size)),)


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    first: int                 # the group's first layer, among its class's
    count: int
    xs: Any                    # what ``apply`` gets as ``layer_xs``
    stacked: bool = True       # leading dim ``count`` on every leaf of xs
    cache: int = 0             # index of the group's cache class


@dataclasses.dataclass(frozen=True)
class TokenLayout:
    """Where a tick's tokens lie on the packed axis ``[T]`` and on the
    rows' grid ``[C, qb]`` (module docstring).  ``dst`` and ``src`` are
    None where ``T == C * qb``: the packed axis is then the grid."""
    grid: tuple                # (C, qb)
    valid: Any                 # [T] bool: packed places that hold a token
    last: Any                  # [C] int32: packed place of a row's last token
    dst: Any = None            # [C, qb] int32: packed place a grid place has
    src: Any = None            # [T] int32: flat grid place a packed place has

    def to_grid(self, a):
        """``a [T, ...]`` on the grid ``[C, qb, ...]``.  A place of the
        grid that holds no token reads some token's values: finite, and
        masked by ``n_valid`` or written to the sink as padding is."""
        if self.dst is None:
            return a.reshape(self.grid + a.shape[1:])
        return a.at[self.dst].get(mode="promise_in_bounds")

    def to_packed(self, a):
        """``a [C, qb, ...]`` on the packed axis ``[T, ...]``."""
        return self.from_rows(a.reshape((-1,) + a.shape[2:]))


    def to_rows(self, a):
        """``a [T, ...]`` as the grid's places row by row, ``[C * qb,
        ...]``: ``to_grid`` with the two leading dims as one (a kernel
        that takes its tokens two-dimensional is spared XLA's choice of
        layout for a ``[C, qb, .]`` array)."""
        if self.dst is None:
            return a
        return a.at[self.dst.reshape(-1)].get(mode="promise_in_bounds")

    def from_rows(self, a):
        """``a [C * qb, ...]`` on the packed axis ``[T, ...]``."""
        if self.src is None:
            return a
        return a.at[self.src].get(mode="promise_in_bounds")


def token_layout(m, qb: int, places) -> TokenLayout:
    """The layout of a tick whose row ``c`` carries ``m[c]`` tokens (``m
    [C]`` int32, 0 for a row without any) on a packed axis of
    ``places.shape[0]`` places; ``places`` is that axis, ``arange(T)``."""
    C, T = m.shape[0], places.shape[0]
    j = jnp.arange(qb, dtype=jnp.int32)
    held = j[None, :] < m[:, None]                         # [C, qb]
    if T == C * qb:
        return TokenLayout(
            (C, qb), held.reshape(-1),
            jnp.arange(C, dtype=jnp.int32) * qb + jnp.maximum(m, 1) - 1)
    end = jnp.cumsum(m, dtype=jnp.int32)
    start = end - m
    dst = jnp.where(held, start[:, None] + j[None, :], 0)
    # the row a packed place belongs to: how many rows end at or before it
    row = jnp.minimum(
        (end[None, :] <= places[:, None]).sum(1, dtype=jnp.int32), C - 1)
    valid = places < end[-1]
    src = jnp.where(valid, row * qb + places - start[row], 0)
    return TokenLayout((C, qb), valid, jnp.where(m > 0, end - 1, 0),
                       dst, src)
