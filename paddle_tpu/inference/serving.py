"""Continuous-batching serving engine over the paged KV cache.

The request-serving runtime above the kernels — the role of the
reference's AnalysisPredictor + fused_multi_transformer serving path
(fluid/inference/api/analysis_predictor.cc:1657; block_multi_head_attention
for the paged cache). TPU design:

- ONE compiled program per engine step, static shapes ("Ragged Paged
  Attention", arxiv 2604.15464): a fixed ``[n_rows, qb]`` token grid
  where every row is a chunk of ONE request — a decode step is simply a
  chunk with one valid token, a prefill slice fills up to ``qb``, and a
  speculative decode row verifies k drafts as a (k+1)-token chunk.
  Arbitrary prefill/decode mixes share the program; per-request state
  (block tables, start positions, valid counts, sampling params) is
  DATA, never shape. There is no prefill-program/decode-quantum
  boundary: decode tokens and prefill chunks pack into the same token
  budget, so a 1024-token prompt contributes budget-sized slices that
  ride the same dispatch as every other request's decode row.
- vLLM-style paged KV: per-layer page arrays, physical pages allocated
  per request from a free list and returned on completion; page 0 is a
  write sink for idle rows and padding tokens so the batched program
  needs no masking branches. k pages are d-major — the MXU kernel's
  native operand (ops/pallas/ragged_paged_attention.py: a grid step is
  one row's group of pages across all kv heads, a decode row runs its
  one token's query rows alone, padding rows come out as zeros).
- Prefix caching: page-aligned prompt chunks are content-hashed
  (cumulative chain, so a hit implies the whole prefix matches) and the
  pool refcounts cached pages. A shared system prompt is prefilled ONCE;
  later requests map the cached pages into their block tables and skip
  those tokens entirely (the prefill-token counter proves zero redundant
  FLOPs). Only the page holding the last prompt token is always
  re-prefilled — its logits produce the first token. The hit rule,
  stated once (models/seam.py has the classes): a hit of ``b`` tokens
  needs (1) cache class 0 to hold pages ``[0, b)``, (2) every windowed
  class the pages a query at ``b`` can still read, and (3) the state
  class, where the model has one, a snapshot taken at exactly ``b``;
  admission takes the longest ``b`` every class can honour
  (``_usable_hit``) and counts what class 0 had beyond it.
- Continuous batching: the scheduler admits queued requests into free
  slots every step (admission is page-pool-bound only — no prompt
  buckets), and a pool-blocked large request is skipped (with an aging
  barrier) so it cannot head-of-line-block smaller requests that fit.
- Speculative multi-token decode (``serving_speculative_k`` > 0): a
  host-side n-gram prompt-lookup proposer drafts up to k tokens per
  decode row; the unified step verifies them as a (k+1)-token chunk.
  Greedy-accept + keyed sampling make the accepted stream bit-identical
  to the non-speculative stream (inference/speculative.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
import zlib
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.flags import GLOBAL_FLAGS
from ..models.llama import (LlamaConfig, LlamaServing,
                            quantize_weights_int8)
from ..models.seam import (STATE_DUMP, STATE_ZERO, CacheClass, cache_classes,
                           token_layout)
from ..obs import clock as _clock
from ..testing import chaos as _chaos
from .. import obs as _obs

__all__ = ["Request", "ServingEngine", "kv_admit_first_write",
           "kv_scale_reset", "wire_gather_pages", "wire_scatter_pages"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [T] int32
    max_new_tokens: int
    arrival: float = 0.0               # seconds from engine start
    # sampling (reference serving path: phi top_p_sampling fused kernel).
    # temperature == 0 -> greedy; mixed greedy/sampled batches share ONE
    # compiled program (per-slot params are data, not shape)
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    # multi-tenant surface (inference/multitenant/): all default-None/0
    # = the single-tenant request the engine always served. tenant is
    # pure telemetry; priority steers admission order and preemption
    # when serving_priorities is on; adapter_id names a registered LoRA
    # adapter (serving_lora); schema_id/constraint constrain decoding
    # (serving_constrained; schema_id binds a registered schema factory
    # at admission, constraint is a live ConstraintState)
    tenant: int = 0
    priority: int = 0
    adapter_id: Optional[object] = None
    schema_id: Optional[object] = None
    constraint: Optional[object] = None
    # fleet serving (inference/fleet/): deadline_* are seconds-from-
    # arrival budgets (0 = none) — the loadgen driver aborts expired
    # requests and the router routes deadline-tight ones to the least-
    # loaded replica; session is an opaque affinity key that keeps a
    # conversation on the replica already holding its KV prefix. The
    # engine itself never reads any of these.
    deadline_ttft: float = 0.0
    deadline_e2e: float = 0.0
    session: Optional[object] = None
    # weight-version pin (inference/fleet/rollout.py): stamped by the
    # router at first placement so a stream admitted under version A is
    # only ever resumed on a version-A engine during a rolling upgrade
    # (bit-reproducible streams through a deploy). None = unpinned.
    param_version: Optional[str] = None
    # filled by the engine:
    out_tokens: list = dataclasses.field(default_factory=list)
    t_first: Optional[float] = None    # first-token wall time
    t_done: Optional[float] = None
    aborted: bool = False
    age: int = 0                       # pool-blocked admission skips
    n_preempted: int = 0               # KV evictions this request survived


@jax.named_scope("sample")
def _pick_tokens(logits, temps, topps, seeds, positions):
    """Next-token selection for a batch of rows, IN-program.

    temperature 0 -> greedy argmax; >0 -> top-p (nucleus) sampling at
    that temperature (the reference serving path's fused top_p_sampling
    kernel, phi/kernels/fusion/gpu/top_p_sampling.cu role). Greedy-only
    batches skip the sort entirely through lax.cond — sampling params
    are per-row DATA, so mixed batches share one compiled program.
    Randomness is keyed (seed, position-of-input-token): a request's
    sample stream is reproducible and independent of chunk packing,
    budget, AND speculative verification (a draft position's key is the
    same whether it is verified speculatively or decoded one-by-one).
    logits [N, V] fp32; temps/topps [N] fp32; seeds/positions [N] int32.
    """

    def greedy(_):
        return jnp.argmax(logits, -1).astype(jnp.int32)

    def sampled(_):
        from ..ops.nucleus import nucleus_keep

        lt = logits / jnp.maximum(temps, 1e-6)[:, None]
        srt = jnp.sort(lt, axis=-1)[:, ::-1]
        p = jax.nn.softmax(srt, axis=-1)
        keep = nucleus_keep(p, topps)              # always keeps >= 1
        kth = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
        masked = jnp.where(lt >= kth[:, None], lt, -jnp.inf)

        def one(seed, pos, row):
            k = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
            return row + jax.random.gumbel(k, row.shape)

        noisy = jax.vmap(one)(seeds, positions, masked)
        samp = jnp.argmax(noisy, -1).astype(jnp.int32)
        return jnp.where(temps > 0, samp, greedy(None))

    return lax.cond(jnp.any(temps > 0), sampled, greedy, operand=None)


def wire_gather_pages(pages, pg):
    """Donor-side wire STAGE kernel: snapshot the per-layer pages at
    indices ``pg`` into shipment layout ``[n, L, ...]``. Pure so the
    prefill->decode wire's device half is a traceable program —
    tools/lint/shardcheck.py registers it as the ``wire_stage`` entry
    (TPL203 collective-order group with the unified step)."""
    return jnp.moveaxis(pages[:, pg], 1, 0)


def wire_scatter_pages(pages, pg, payload):
    """Adopter-side wire COMMIT kernel: scatter a shipment payload
    (already in page layout ``[L, n, ...]``) into the page arrays at
    indices ``pg``. The pure half of commit_adopt/_flush_commits;
    shardcheck's ``wire_commit`` entry."""
    return pages.at[:, pg].set(payload)


def _run_layer_groups(body, x, pools, groups, side=()):
    """The unified step's layer loop, with the KV pool as a loop CARRY.

    The addressing rule, stated once: outside the program a cache
    class's pool (models/seam.py) is ``[L, P, ...]``; inside it is
    ``[L*P, ...]`` (a reshape of the two leading dims) and layer ``l``
    of the class finds page ``p`` at ``l*P + p``, so the sink — page 0,
    where padding writes — is page ``l*P`` of each layer. A ``lax.scan``
    reads its ``xs`` and stacks fresh ``ys``, so a pool scanned that way
    can never be updated where it lies; the carry can, and the donated
    input buffer becomes the output.

    ``pools`` holds a ``(k_pages, v_pages)`` pair per cache class, in
    the classes' order; a group's layers see the pair of ITS class
    (``LayerGroup.cache``) and no other rides in its loop.
    ``body(group, x, k_pool, v_pool, base, layer_xs, *side) -> (x,
    k_pool, v_pool, ys, *side)`` sees the class's flattened pools and
    ``base = l*P`` to add to every page id it scatters to or attends
    over. ``groups`` (models/seam.py: LayerGroup) cover the layers in
    the model's order: a stacked group is scanned over its own layers,
    ``first .. first+count-1`` of its class, a single unstacked layer is
    applied where it stands. The side planes (``[L, P, ...]``, small,
    class 0's alone) do travel scanned: a layer gets its own ``[P,
    ...]`` slice under the layer's own page ids and its update is
    stacked. Returns ``(x, pools, [ys per group], side)`` with the
    pools back in their ``[L, P, ...]`` shapes."""
    flat = [tuple(a.reshape((-1,) + a.shape[2:]) for a in kv)
            for kv in pools]
    all_ys, parts = [], []
    for g in groups:
        P = pools[g.cache][0].shape[1]

        def step(carry, inp, g=g, P=P):
            l, layer_xs, side_l = inp
            x, kp, vp, ys, *side_l = body(g, *carry, l * P, layer_xs,
                                          *side_l)
            return (x, kp, vp), (ys, tuple(side_l))

        carry = (x,) + flat[g.cache]
        side_g = (tuple(s[g.first:g.first + g.count] for s in side)
                  if g.cache == 0 else ())
        if g.stacked:
            carry, (ys, side_g) = lax.scan(
                step, carry,
                (jnp.arange(g.first, g.first + g.count, dtype=jnp.int32),
                 g.xs, side_g))
        else:
            carry, (ys, side_g) = step(
                carry, (g.first, g.xs, tuple(s[0] for s in side_g)))
            side_g = tuple(s[None] for s in side_g)
        x, flat[g.cache] = carry[0], carry[1:]
        all_ys.append(ys)
        if g.cache == 0:
            parts.append(side_g)
    side = tuple(p[0] if len(p) == 1 else jnp.concatenate(p)
                 for p in zip(*parts))
    return (x, [tuple(a.reshape(b.shape) for a, b in zip(f, kv))
                for f, kv in zip(flat, pools)], all_ys, side)


def kv_scale_reset(scales, page_ids, axis: int = 0):
    """Zero the scale-plane entries of freshly allocated pages — the
    PR 8 fix: a reused page's stale running-absmax would quantize the
    new tenant's tokens against a garbage (possibly inflated) scale, so
    the allocator resets the plane and the first write sets a fresh
    scale. ``axis`` is the page dimension: single-layer ``[P, nKV]``
    planes use 0, the engine's stacked ``[L, P, nKV]`` planes use 1.
    tools/lint/quantcheck.py recognizes this scatter-set-of-zero as the
    scale-provenance *reset* event that clears TPL303 foreignness."""
    idx = (slice(None),) * axis + (page_ids,)
    return scales.at[idx].set(0.0)


def kv_admit_first_write(pages, scales, page_ids, tokens,
                         _zero_scale_on_alloc: bool = True):
    """A new tenant's FIRST write into freshly allocated (reused) pages,
    as one traceable program: reset -> scatter-max -> quantize ->
    scatter. One layer, v-layout ``pages`` [P, nKV, bs, d] int8,
    ``scales`` [P, nKV] fp32 (the plane as the allocator left it — the
    *previous* tenant's running absmaxes), ``page_ids`` [N] int32,
    ``tokens`` [N, nKV, bs, d] fp32.

    ``_zero_scale_on_alloc``: True is the shipped path (the engine
    zeroes a reallocated page's side-plane entries, models/seam.py:
    kv_scale_reset before the first kv_scale_update); False rebuilds
    the pre-PR 8 program where the
    prior tenant's absmax survives into the new tenant's quantize —
    tools/lint/quantcheck.py traces both and proves TPL303
    (scale-provenance-mismatch) fires exactly on the False variant."""
    from ..ops.quant import kv_scale_update, quantize_to_scale

    if _zero_scale_on_alloc:
        scales = kv_scale_reset(scales, page_ids)
    absmax = jnp.max(jnp.abs(tokens.astype(jnp.float32)),
                     axis=(-2, -1)) / 127.0                  # [N, nKV]
    scales = kv_scale_update(scales, page_ids, absmax)
    s = jnp.take(scales, page_ids, axis=0)[:, :, None, None]
    q = quantize_to_scale(tokens, s)
    return pages.at[page_ids].set(q), scales


class _PagePool:
    """Refcounted free-list page allocator with a content-addressed
    prefix cache. Page 0 is reserved as the idle-slot write sink and
    never handed out.

    Cached-page lifecycle: ``insert`` registers a page at refcount 1
    (the inserting request's own mapping); ``lookup`` increfs every hit;
    ``decref`` at request teardown moves refcount-0 pages to a PENDING
    list, and ``commit_evictable`` — called once no in-flight program
    can still read them — promotes pending pages to the LRU evictable
    set, where ``evict`` reclaims them for allocation (dropping their
    hash entries)."""

    def __init__(self, n_pages: int, cache_limit: int = 0):
        self.n_pages = n_pages
        self.free = list(range(n_pages - 1, 0, -1))
        self.cache: dict[bytes, int] = {}      # prefix hash -> page
        self.ref: dict[int, int] = {}          # cached page -> refcount
        self.hash_of: dict[int, bytes] = {}
        self.evictable: dict[int, None] = {}   # insertion-ordered = LRU
        self.pending_evict: list[int] = []
        self.cache_limit = cache_limit
        self.hits = 0
        self.misses = 0

    def alloc(self, n: int) -> Optional[list[int]]:
        if len(self.free) < n:
            return None
        return [self.free.pop() for _ in range(n)]

    def release(self, pages: list[int]) -> None:
        self.free.extend(pages)

    def lookup(self, hashes: list[bytes]) -> list[int]:
        """Longest cached prefix of ``hashes``; increfs each hit (the
        caller owns the mappings until it decrefs them back)."""
        out: list[int] = []
        for h in hashes:
            p = self.cache.get(h)
            if p is None:
                break
            self.ref[p] += 1
            self.evictable.pop(p, None)
            if p in self.pending_evict:
                self.pending_evict.remove(p)
            out.append(p)
        self.hits += len(out)
        self.misses += len(hashes) - len(out)
        return out

    def peek(self, hashes: list[bytes]) -> int:
        """Length of the longest cached prefix of ``hashes``; claims
        nothing."""
        n = 0
        while n < len(hashes) and hashes[n] in self.cache:
            n += 1
        return n

    def insert(self, h: bytes, page: int) -> bool:
        """Register an (already-written) page under its prefix hash at
        refcount 1; False if the hash is already cached (the caller
        keeps its own copy)."""
        if h in self.cache:
            return False
        self.cache[h] = page
        self.ref[page] = 1
        self.hash_of[page] = h
        return True

    def decref(self, pages: list[int]) -> None:
        for p in pages:
            self.ref[p] -= 1
            if self.ref[p] == 0:
                self.pending_evict.append(p)

    def commit_evictable(self) -> None:
        for p in self.pending_evict:
            self.evictable[p] = None
        self.pending_evict = []
        if self.cache_limit and len(self.evictable) > self.cache_limit:
            self.evict(len(self.evictable) - self.cache_limit)

    def evict(self, n: int) -> int:
        """Reclaim up to ``n`` LRU evictable pages into the free list."""
        done = 0
        while done < n and self.evictable:
            p = next(iter(self.evictable))
            del self.evictable[p]
            del self.cache[self.hash_of.pop(p)]
            del self.ref[p]
            self.free.append(p)
            done += 1
        return done


class _ClassPages:
    """A further cache class's pools, table, allocator and page
    ownership (models/seam.py: cache classes; class 0's are the engine's
    own ``k_pages``, ``pool``, ``_full_rows``, ``_slot_owned`` ...). A
    request holds pages here only for the blocks its layers can still
    read: ``owned`` / ``shared`` map a slot's logical blocks to pages,
    ``tail[slot]`` is its first block not yet let go, and ``peak[slot]``
    what it may hold at once (admission reserves that much)."""

    def __init__(self, cls, n_pages: int, B: int, max_blocks: int,
                 cache_limit: int):
        self.cls, self.n_pages = cls, n_pages
        self.k_pages, self.v_pages = (
            jnp.zeros((cls.n_layers, n_pages) + plane.page_shape,
                      cls.spec.dtype) for plane in cls.spec.planes)
        self.pool = _PagePool(n_pages, cache_limit=cache_limit)
        self.full_rows = np.zeros((B, max_blocks), np.int32)
        self.owned: list[dict] = [{} for _ in range(B)]
        self.shared: list[dict] = [{} for _ in range(B)]
        self.tail = [0] * B
        self.peak = [0] * B
        self.deferred_free: list[int] = []

    def room(self) -> int:
        """Pages an admission may still reserve: what no live request
        has reserved and no finished one has left waiting for a harvest
        (the rest is free or evictable, or will be when asked for)."""
        return (self.n_pages - 1 - sum(self.peak) - len(self.deferred_free)
                - len(self.pool.pending_evict))

    def live_pages(self) -> int:
        return sum(map(len, self.owned)) + sum(map(len, self.shared))

    def alloc(self, n: int) -> Optional[list[int]]:
        """``n`` pages, reclaiming idle cached ones where the free list
        runs short (the engine's _alloc_pages, for this class)."""
        if len(self.pool.free) < n:
            self.pool.evict(n - len(self.pool.free))
        return self.pool.alloc(n)

    def settle(self) -> None:
        """Once no in-flight program can read them: deferred pages to
        the free list, refcount-0 cached pages to the evictable set."""
        self.pool.release(self.deferred_free)
        self.deferred_free = []
        self.pool.commit_evictable()

    def release_block(self, slot: int, blk: int) -> bool:
        """Let go of what ``slot`` holds for logical block ``blk`` (the
        table slot goes to the sink); the page waits for ``settle``."""
        self.full_rows[slot, blk] = 0
        page = self.owned[slot].pop(blk, None)
        if page is not None:
            self.deferred_free.append(page)
            return True
        page = self.shared[slot].pop(blk, None)
        if page is not None:
            self.pool.decref([page])
        return page is not None

    def release_slot(self, slot: int, defer: bool) -> None:
        owned = list(self.owned[slot].values())
        self.pool.decref(list(self.shared[slot].values()))
        self.owned[slot], self.shared[slot] = {}, {}
        self.full_rows[slot] = 0
        self.tail[slot] = self.peak[slot] = 0
        self.deferred_free.extend(owned)
        if not defer:
            self.settle()

    def accounting(self) -> dict:
        counts = {
            "free": len(self.pool.free),
            "slot_owned": sum(map(len, self.owned)),
            "slot_shared": len({p for d in self.shared
                                for p in d.values()}),
            "cache_idle": sum(1 for r in self.pool.ref.values() if r == 0),
            "deferred_free": len(self.deferred_free)}
        counts["total"] = sum(counts.values())
        return counts


class _StateSlots:
    """The state class's pools, slot table and snapshots (models/seam.py:
    the state class). Slot ids: ``STATE_ZERO``, ``STATE_DUMP``, then one
    live slot an engine row (``live(s)``), then the snapshot slots,
    which live under a ``_PagePool``'s rules (its page ``p`` is slot
    ``snap0 + p``): free list, hash -> page, refcounts, pending, LRU.
    Per engine row: ``at`` is the slot its request's state stands in (its
    next tick reads it), ``held`` the cached snapshot it holds a
    reference on (the one it hit, then the newest it wrote), ``pending``
    a snapshot ``(page, hash index)`` the tick in flight writes and the
    host has not yet hashed, ``private`` one it wrote that the cache
    already had under another page. ``k_pages`` / ``v_pages`` are the two
    planes' pools, named as the paged classes' are."""

    def __init__(self, cls, B: int, n_snapshots: int):
        # The step flattens a pool [L, S, ...] to [L * S, ...]. A plane
        # of one row a slot has the slots down its sublanes, and the
        # flattening moves no byte only where S fills whole tiles: the
        # snapshot slots asked for are rounded up to that (none stays
        # none).
        tile = max([32 // jnp.dtype(p.dtype).itemsize
                    for p in cls.planes if len(p.shape) == 1] or [1])
        if n_snapshots:
            n_snapshots += -(2 + B + n_snapshots) % tile
        self.cls, self.B, self.n_snapshots = cls, B, n_snapshots
        self.snap0 = 1 + B                  # slot of snapshot page 1, less 1
        self.n_slots = 2 + B + n_snapshots
        self.k_pages, self.v_pages = (
            jnp.zeros((cls.n_layers, self.n_slots) + plane.shape,
                      plane.dtype) for plane in cls.planes)
        self.pool = _PagePool(n_snapshots + 1)
        self.at = [STATE_ZERO] * B
        self.held = [0] * B
        self.pending: list = [None] * B
        self.private = [0] * B
        self.deferred_free: list[int] = []

    def live(self, slot: int) -> int:
        return 2 + slot

    def key(self, h: bytes) -> bytes:
        return h + self.cls.hash_tag

    def has(self, h: bytes) -> bool:
        return self.key(h) in self.pool.cache

    def alloc(self) -> int:
        """A snapshot page to write, 0 when none can be had: a free one,
        else the least recently used that nobody holds."""
        if not self.pool.free:
            self.pool.evict(1)
        got = self.pool.alloc(1)
        return got[0] if got else 0

    def settle(self) -> None:
        """Once no in-flight program can read them (_ClassPages.settle)."""
        self.pool.release(self.deferred_free)
        self.deferred_free = []
        self.pool.commit_evictable()

    def release_slot(self, slot: int, defer: bool) -> None:
        if self.held[slot]:
            self.pool.decref([self.held[slot]])
        if self.pending[slot] is not None:
            self.deferred_free.append(self.pending[slot][0])
        if self.private[slot]:
            self.deferred_free.append(self.private[slot])
        self.held[slot] = self.private[slot] = 0
        self.pending[slot] = None
        self.at[slot] = STATE_ZERO
        if not defer:
            self.settle()

    def slots_of(self, slot: int) -> int:
        """Slots the request in engine row ``slot`` holds: its live slot
        and the snapshots it holds or writes."""
        return (1 + bool(self.held[slot]) + (self.pending[slot] is not None)
                + bool(self.private[slot]))

    def accounting(self) -> dict:
        counts = {
            "free": len(self.pool.free),
            "held": len({p for p in self.held if p}),
            "pending": sum(p is not None for p in self.pending),
            "private": sum(map(bool, self.private)),
            "cache_idle": sum(1 for r in self.pool.ref.values() if r == 0),
            "deferred_free": len(self.deferred_free)}
        counts["total"] = sum(counts.values())
        return counts


class ServingEngine:
    """Continuous-batching serving over paged KV, for any model behind
    the seam (models/seam.py).

    ``step()`` = admissions + ONE unified ragged-paged-attention
    dispatch (decode rows + prefill chunks in the same token grid) +
    harvest of the previous dispatch; ``run(requests)`` drives
    wall-clock arrivals to completion and returns latency/throughput/
    occupancy stats. There is one step program (``_unified_step_impl``)
    for every model and every page format: the engine allocates,
    carries, donates, resets and sizes what the model's ``cache_spec``
    declares (``kv_quant=`` makes LLaMA declare int8 pages with two
    scale planes beside them).
    """

    def __init__(self, cfg, params: Optional[dict] = None,
                 seed: int = 0, max_batch: int = 8, page_size: int = 128,
                 max_seq: Optional[int] = None, n_pages: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_pages: Optional[int] = None,
                 admit_aging: int = 64,
                 weight_only_int8: Optional[bool] = None,
                 qb: Optional[int] = None,
                 speculative_k: Optional[int] = None,
                 spec_ngram: Optional[int] = None,
                 kv_quant: Optional[bool] = None,
                 lora: Optional[bool] = None,
                 lora_rank: int = 8,
                 lora_slots: int = 4,
                 priorities: Optional[bool] = None,
                 constrained: Optional[bool] = None,
                 engine_id: int = 0,
                 prefill_only: bool = False,
                 wire_overlap: Optional[bool] = None,
                 class_pages: Optional[dict] = None):
        # fleet identity: names this replica in router health/stats and
        # targets chaos specs (fire(..., ctx={"engine": id})); a lone
        # engine keeps the default 0 and never consults it otherwise
        self.engine_id = int(engine_id)
        # disaggregated pool role (inference/fleet/): a prefill-only
        # engine runs chunked prefill through first-token emission,
        # exports the prompt's full KV pages into ``outbox`` for the
        # router to ship, and releases the slot immediately — it never
        # dispatches a decode row. Router-assigned (ctor kwarg or
        # attribute flip for degraded/re-split transitions), never a
        # flag read here: a lone engine keeps the defaults and is
        # bit-identical by construction. ``pool_role`` additionally
        # tags chaos probes so faults can target one pool.
        self.prefill_only = bool(prefill_only)
        self.pool_role: Optional[str] = None
        self.outbox: list = []  # (request, shipment | None), router-drained
        # weight-version tag (inference/fleet/rollout.py): the catalog
        # version of ``params`` currently loaded. Router-assigned (via
        # set_params or attribute write) like the fleet fields above; a
        # lone engine keeps None and never consults it.
        self.param_version: Optional[str] = None
        self.cfg = cfg
        # the model's side of the step (models/seam.py): LLaMA's is built
        # here, any other config brings its own
        if lora is None:
            lora = GLOBAL_FLAGS.get("serving_lora")
        if kv_quant is None:
            kv_quant = GLOBAL_FLAGS.get("serving_kv_quant")
        # read by the migration wire alone (shipments name their format)
        self._kv_quant = bool(kv_quant)
        self.model = (LlamaServing(cfg, lora=bool(lora),
                                   kv_quant=self._kv_quant)
                      if isinstance(cfg, LlamaConfig)
                      else cfg.serving_model())
        self.params = params if params is not None else \
            self.model.init_params(jax.random.PRNGKey(seed))
        if weight_only_int8 is None:
            weight_only_int8 = bool(GLOBAL_FLAGS.get("decode_weight_quant"))
        weight_only_int8 = bool(
            weight_only_int8 or getattr(cfg, "weight_only_int8", False))
        if weight_only_int8:
            self._require("weight_only_int8")
        if weight_only_int8 and not isinstance(
                self.params["blocks"]["wq"], tuple):
            # halves weight HBM (per-column absmax int8 + bf16 scales;
            # embeddings/norms stay high precision) — every matmul in the
            # unified program flows through the tuple-aware _mm, so the
            # compiled path needs no changes. The tuple check skips
            # params that arrive already quantized.
            self.params = quantize_weights_int8(self.params)
        # remembered for set_params (a live weight swap must land in the
        # same quantized format the ctor established)
        self._weight_only_int8 = weight_only_int8
        self.B = max_batch
        self.bs = page_size
        self.max_seq = max_seq or cfg.max_seq_len
        self.max_blocks = (self.max_seq + page_size - 1) // page_size
        self.n_pages = n_pages or (1 + max_batch * self.max_blocks)
        if prefill_budget is None:
            prefill_budget = GLOBAL_FLAGS.get("serving_prefill_budget")
        if prefix_cache is None:
            prefix_cache = GLOBAL_FLAGS.get("serving_prefix_cache")
        if prefix_cache_pages is None:
            prefix_cache_pages = GLOBAL_FLAGS.get(
                "serving_prefix_cache_pages")
        if qb is None:
            qb = GLOBAL_FLAGS.get("serving_unified_qb")
        if speculative_k is None:
            speculative_k = GLOBAL_FLAGS.get("serving_speculative_k")
        if spec_ngram is None:
            spec_ngram = GLOBAL_FLAGS.get("serving_spec_ngram")
        # overlapped migration wire (serving_wire_overlap): export stages
        # an async device->host copy chained after the in-flight program
        # instead of a blocking chain sync, and adoption commits fold
        # into the next dispatch as one batched scatter. Off = the
        # synchronous wire, bit-identical; a lone engine never exports
        # or adopts, so the toggle is inert outside a fleet either way.
        if wire_overlap is None:
            wire_overlap = GLOBAL_FLAGS.get("serving_wire_overlap")
        self._wire_overlap = bool(wire_overlap)
        # unified grid: n_rows chunks of qb tokens each. Every decoding
        # slot gets one row per step, remaining rows carry prefill
        # slices, so n_rows >= max_batch.
        self.qb = max(1, qb)
        self.n_rows = max(1, prefill_budget // self.qb, max_batch)
        self.prefill_budget = self.n_rows * self.qb
        # the step sizes, a rule of the grid alone: a tick's dense layers
        # run over the smallest that holds its tokens, packed, and the
        # largest is the grid itself (_dispatch_unified; seam.py: the
        # packed axis). A quarter of the grid holds every decode-only
        # tick (n_rows >= max_batch) and a short prompt's chunks beside
        # it; under the MXU's ridge a size is bound by the weights'
        # bytes, so a smaller one buys nothing, and each further size
        # is a program to trace, lower and compile before the first
        # tick (PERF.md, PR 32: a size between the two cost more set-up
        # than its ticks gave back). The packed axis of each size, kept
        # on the device, is made at the first dispatch.
        self.rungs = tuple(sorted({max(1, self.prefill_budget // 4),
                                   self.prefill_budget}))
        self._places: dict = {}
        # a decode row holds 1 input token + up to qb-1 verified drafts
        self.spec_k = max(0, min(int(speculative_k), self.qb - 1))
        if self.spec_k:
            from .speculative import NgramProposer

            self._proposer = NgramProposer(max_ngram=max(1, spec_ngram))
        else:
            self._proposer = None
        self._cache_on = bool(prefix_cache)
        self.admit_aging = admit_aging
        # -- multi-tenant axes (inference/multitenant/): all default off
        #    = the exact single-tenant engine (bit-identical, pinned) ---
        if priorities is None:
            priorities = GLOBAL_FLAGS.get("serving_priorities")
        if constrained is None:
            constrained = GLOBAL_FLAGS.get("serving_constrained")
        self._lora_on = bool(lora)
        self._prio_on = bool(priorities)
        self._constr_on = bool(constrained)
        for on, feature in ((self._kv_quant, "kv_quant"),
                            (self._lora_on, "lora"),
                            (self._constr_on, "constrained"),
                            (self.spec_k, "speculative"),
                            (self.prefill_only, "page_shipment")):
            if on:
                self._require(feature)
        if self._constr_on and self.spec_k:
            raise ValueError(
                "serving_constrained is incompatible with "
                "serving_speculative_k: a constraint mask covers one "
                "sampling position per row, not a k-token draft ladder")
        # what a token stores in a layer, and what a page keeps beside
        # its tokens, is the model's to say: the pool is the spec's pair
        # of planes and its side planes, [L, P, *page_shape] each,
        # everywhere outside the step program; inside, layer l finds
        # page p at l*P + p (_run_layer_groups), and page 0 of each
        # layer is its sink.
        # A model whose layers do not all keep a token equally long
        # declares cache classes (models/seam.py): class 0, whose layers
        # read everything, is what this engine always had (k_pages,
        # v_pages, pool, _full_rows, _slot_owned ...); each further
        # class has its own of each in ``_extra``, ``class_pages[name]``
        # pages of it (n_pages where not given).
        # A model with layers whose cache is a slot of fixed size a
        # request declares a state class behind its paged ones
        # (``_state``: _StateSlots, ``class_pages[name]`` snapshot slots).
        self.classes = cache_classes(self.model, self.bs)
        L = self.classes[0].n_layers
        self.cache_spec = spec = self.classes[0].spec
        self.k_pages, self.v_pages = (
            jnp.zeros((L, self.n_pages) + plane.page_shape, spec.dtype)
            for plane in spec.planes)
        self.side_planes = {
            plane.name: jnp.zeros((L, self.n_pages) + plane.page_shape,
                                  plane.dtype) for plane in spec.side}
        self._extra = [
            _ClassPages(c, int((class_pages or {}).get(c.name,
                                                       self.n_pages)),
                        self.B, self.max_blocks, prefix_cache_pages)
            for c in self.classes[1:] if isinstance(c, CacheClass)]
        self._state = None
        for i, c in enumerate(self.classes):
            if isinstance(c, CacheClass):
                _obs.instant("engine.cache_spec", engine=self.engine_id,
                             bytes_per_token=self.kv_bytes_per_token(i),
                             planes=",".join(f"{p.name}:{p.width}"
                                             for p in c.spec.planes),
                             cache_class=c.name, layers=c.n_layers,
                             window=c.window or 0)
                continue
            # where not given: two snapshot slots a row
            self._state = _StateSlots(c, self.B, int(
                (class_pages or {}).get(c.name, 2 * self.B)))
            _obs.instant("engine.cache_spec", engine=self.engine_id,
                         slot_bytes=c.slot_bytes(),
                         planes=",".join(
                             f"{p.name}:{'x'.join(map(str, p.shape))}"
                             for p in c.planes),
                         cache_class=c.name, layers=c.n_layers,
                         live_slots=self.B,
                         snapshot_slots=self._state.n_snapshots)
        # every class beyond class 0, in the order its three operands
        # (two pools and a table) ride in the step
        self._further = self._extra + (
            [self._state] if self._state is not None else [])
        self.table = np.zeros((self.B, self.max_blocks), np.int32)  # sink
        self.seq_lens = np.zeros((self.B,), np.int32)
        self.cur_tok = np.zeros((self.B,), np.int32)
        # per-slot sampling params (temperature 0 = greedy; idle slots 0)
        self.samp_temp = np.zeros((self.B,), np.float32)
        self.samp_topp = np.ones((self.B,), np.float32)
        self.samp_seed = np.zeros((self.B,), np.int32)
        self.slots: list[Optional[Request]] = [None] * self.B
        # page ownership is split: owned pages return to the free list at
        # teardown; shared pages are prefix-cache mappings and only lose
        # a refcount. _full_rows is the request's REAL block-table row;
        # self.table holds the DECODE view (sink row until the prefill
        # flip, kept for abort/teardown compatibility).
        self._slot_owned: list[list[int]] = [[] for _ in range(self.B)]
        self._slot_shared: list[list[int]] = [[] for _ in range(self.B)]
        self._slot_hashes: list[list[bytes]] = [[] for _ in range(self.B)]
        # with a state class: the chain's hasher behind the last hash of
        # _slot_hashes, which then grows past the prompt as answers do
        self._slot_chain: list = [None] * self.B
        self._slot_nshared: list[int] = [0] * self.B
        self._slot_offered: list[int] = [0] * self.B
        self._full_rows = np.zeros((self.B, self.max_blocks), np.int32)
        # slot -> next prompt position to prefill; dict order = admission
        # order, so chunk packing stays FIFO across requests
        self._prefilling: dict[int, int] = {}
        self.pool = _PagePool(self.n_pages, cache_limit=prefix_cache_pages)
        self.queue: list[Request] = []
        # per-slot multi-tenant state: the admitted request's adapter id
        # (refcount handle), its device slot in the adapter stacks (0 =
        # identity), and its EFFECTIVE prompt — original prompt plus any
        # tokens already emitted before a preemption, so a resumed
        # request re-prefills its whole history (mostly through the
        # prefix cache) and its first new pick lands on the same
        # (seed, position) sampling key as the uninterrupted stream
        self._slot_adapter_id: list = [None] * self.B
        self._slot_aslot: list[int] = [0] * self.B
        self._slot_prompt: list = [None] * self.B
        if self._lora_on:
            from .multitenant.lora import AdapterStore

            self.adapters = AdapterStore(
                cfg, lora_rank, lora_slots, self.kv_bytes_per_page(),
                self._alloc_pages, self.pool.release)
        else:
            self.adapters = None
        self._schemas: dict = {}           # schema id -> ConstraintState factory
        # the pools and the side planes (the first of ``rest``) are the
        # step's to update where they lie
        x0 = 14 + len(spec.side)       # the further classes' first operand
        self._unified = jax.jit(
            self._unified_step_impl,
            donate_argnums=(1, 2) + tuple(range(14, x0)) + tuple(
                x0 + 3 * i + j for i in range(len(self._further))
                for j in (0, 1)))
        # pipelining state (see step() docstring): _inflight holds the
        # dispatched-but-unharvested program's (output tokens, row
        # snapshot); _prev_out_dev chains row outputs on-device into the
        # next dispatch; _deferred_free holds page ids for one harvest
        # cycle (an in-flight program may still write them)
        # (out_dev [C, 1|qb], snapshot, ys, step size)
        self._inflight = None
        self._prev_out_dev = None
        self._deferred_free: list[int] = []
        # migration staging (inference/fleet/): pages allocated by
        # begin_adopt but not yet committed into the prefix cache — the
        # ledger's ``in_flight`` class (page_accounting)
        self._adopting: list[dict] = []
        # deferred adoption commits (wire_overlap): committed pages are
        # already published in the prefix cache (ledger class cache_idle)
        # but their device bytes land as one batched scatter at the next
        # dispatch — _flush_commits runs before any program or export
        # could read them
        self._commit_pending: list[dict] = []
        self.stats = {
            "unified_steps": 0, "decode_steps": 0, "prefills": 0,
            "prefill_tokens": 0, "prefill_grid_tokens": 0,
            "prefill_cached_tokens": 0,
            "decode_slot_tokens": 0, "decode_active_tokens": 0,
            # slot_occupancy decomposition (all in slot-token units, so
            # active + the six waste buckets == decode_slot_tokens):
            "waste_prefill_slot_tokens": 0,        # slot mid-prefill
            "waste_queue_empty_slot_tokens": 0,    # idle, nothing arrived
            "waste_admission_blocked_slot_tokens": 0,  # idle, pool-blocked
            "waste_overrun_slot_tokens": 0,        # aborted/over-produced
            "waste_spec_rejected_slot_tokens": 0,  # rejected draft tokens
            "waste_preempted_slot_tokens": 0,      # re-prefill after preempt
            "spec_proposed_tokens": 0, "spec_accepted_tokens": 0,
            "preemptions": 0,
            # what the dense layers computed against what the ticks
            # carried: sum of the step sizes taken, sum of the tokens
            "token_places": 0, "tokens_packed": 0,
            # migration-wire observability: host milliseconds this
            # engine spent materializing export payloads (the donor-side
            # wire cost the overlapped path shrinks to a buffer swap)
            "wire_export_ms": 0.0,
        }
        # counters the model's layers send out with the picks (seam.py:
        # tick_stats), summed here at harvest; the last tick's also go
        # on engine.step's end
        self.stats.update(dict.fromkeys(
            getattr(self.model, "stats_keys", ()), 0))
        self._tick_stats: dict = {}
        if self._extra:
            # what the classes do, summed over ticks: pages live
            # requests hold in each class and the context tokens they
            # stand for (bytes a context token: the benchmark's
            # kv_live_bytes_per_context_token), pages the windows let
            # go, and tokens of prefix hits that class 0 had and a
            # windowed class had lost
            self.stats.update(dict.fromkeys(
                [f"pages_live.{c.name}" for c in self.classes]
                + ["context_tokens_live", "pages_released_by_window",
                   "prefill_window_lost_tokens"], 0))
        if self._state is not None:
            # what the state class does: slots (and their bytes) live
            # requests hold, summed over ticks as the pages are;
            # snapshots written, hit at admission, evicted for room, and
            # ticks that stood on a page boundary and found no slot for
            # one; tokens of prefix hits that the paged classes had and
            # no snapshot stood at; admissions that found a prefix of
            # theirs in class 0 (the hits' denominator); preempted
            # requests that resumed from a snapshot
            self.stats.update(dict.fromkeys(
                ([] if self._extra else
                 [f"pages_live.{self.classes[0].name}",
                  "context_tokens_live"])
                + ["state_slots_live", "state_bytes_live",
                   "state_snapshots_taken", "state_snapshots_hit",
                   "state_snapshots_evicted", "state_snapshots_unavailable",
                   "prefix_state_lost_tokens", "admitted_with_cached_prefix",
                   "preempt_resumed_from_snapshot"], 0))
        # a dispatched tick's share of them, for engine.step's end
        self._class_tick: dict = {}

    def _require(self, feature: str) -> None:
        """One error for every engine feature the served model's seam
        does not cover (``model.unsupported``)."""
        if feature in self.model.unsupported:
            raise NotImplementedError(
                f"{type(self.cfg).__name__} is not served with "
                f"'{feature}': its serving model leaves out "
                f"{', '.join(self.model.unsupported)}")

    # -- compiled program ---------------------------------------------------

    # The first eleven operands keep their places and class 0's pools and
    # table are k_pages, v_pages and ptable whatever classes the model
    # declares: the benchmark's systems wrap this function by position
    # (benchmark/systems/llama_serve.py: record_dispatches). Everything
    # only some engines have rides in ``rest``, ahead of its last
    # operand, whose LENGTH is the step size.
    def _unified_step_impl(self, params, k_pages, v_pages, tokens,
                           prev_out, chain_mask, chain_row, ptable,
                           row_slot, pos0, n_valid, temps, topps, seeds,
                           *rest):
        """THE engine step: one unified ragged-paged-attention program
        serving an arbitrary prefill/decode mix on an ``[n_rows, qb]``
        grid of rows, its dense layers over the tick's tokens packed
        ``[T, H]`` (models/seam.py: the packed axis; ``T`` is the length
        of the last operand, one of ``rungs``). Row c
        holds n_valid[c] tokens of request row_slot[c] starting at
        position pos0[c] — a decode row is n_valid == 1 (plus drafts
        when speculating), a prefill slice up to qb, an idle row targets
        the sink block-table row (row_slot == B). All raggedness is
        data: tokens [C, qb]; ptable [B+1, max_blocks]; row_slot/pos0/
        n_valid [C] int32; temps/topps/seeds [C].

        ``chain_mask``/``chain_row`` splice the PREVIOUS dispatch's row
        outputs into this dispatch's first-token column in-program, so
        the pipelined scheduler feeds decode continuations (and the
        prefill-final -> first-decode handoff) without a host round trip
        — the per-step host sync overlaps device compute instead of
        serializing with it (its cost is not measured on a locally
        attached chip).

        Returns (out, k_pages, v_pages, ys, side, further); ys holds the
        layers' counters, one entry per layer group, None for a group
        that keeps none (models/seam.py), side the updated side planes
        (an empty tuple for a page format without any), further the
        updated pools of the cache classes beyond class 0, k and v a
        class (empty for a model with one class): out [C, 1] — each row's
        pick after its last valid token — or [C, qb] with per-position
        picks when speculative verification needs the full ladder.
        Per-token KV write (ops/pallas/paged_kv_write.py): valid tokens
        land at their own (page, offset), padding never lands in request
        pages (write-before-attend, per layer). The pool is [L, P, ...]
        at this boundary and donated; the layers see it as a loop carry
        under _run_layer_groups' (l*P + p) addressing, so it is updated
        where it lies."""
        model = self.model
        C, qb = tokens.shape

        # what only some engines have rides as trailing varargs: the
        # page format's side planes (cache_spec.side), then each further
        # cache class's k pool, v pool and page table, then the
        # multi-tenant operands — row adapter slot ids + the four adapter
        # stacks (serving_lora), then the per-row [C, V] vocab legality
        # mask (serving_constrained). Behind them all, ``places``: the
        # packed axis arange(T), whose LENGTH is the step size this tick
        # runs at (``rungs``): one jit, one program a length
        n_side = len(self.cache_spec.side)
        n_x = n_side + 3 * len(self._further)
        side, further = rest[:n_side], rest[n_side:n_x]
        mt, places = list(rest[n_x:-1]), rest[-1]
        pools = [(k_pages, v_pages)] + [
            (further[i], further[i + 1]) for i in range(0, len(further), 3)]
        if self._lora_on:
            aid, ast = mt.pop(0), mt.pop(0)
        vmask = mt.pop(0) if self._constr_on else None

        tok0 = jnp.where(chain_mask, prev_out[chain_row, 0], tokens[:, 0])
        tokens = jnp.concatenate([tok0[:, None], tokens[:, 1:]], axis=1)
        # a row's block-table row in each paged class [C, max_blocks],
        # its request's two slots in the state class [C, 2]
        rows = [t[row_slot] for t in (ptable, *further[2::3])]
        positions = pos0[:, None] + jnp.arange(qb, dtype=jnp.int32)
        # the tick's tokens packed: an idle row (the sink's) carries none
        lay = token_layout(
            jnp.where(row_slot == ptable.shape[0] - 1, 0, n_valid), qb,
            places)
        tok_positions = lay.to_packed(positions)
        x, ctx = model.embed(params, lay.to_packed(tokens), tok_positions)
        ctx["layout"] = lay
        if self._lora_on:
            ctx["aid"] = aid
            groups = model.layer_groups(params, ast)
        else:
            groups = model.layer_groups(params)

        def body(g, x, kp, vp, base, inp, *side_l):
            return model.apply(x, kp, vp, base, inp, rows[g.cache], pos0,
                               n_valid, dict(ctx, cache_class=g.cache),
                               *side_l)

        x, pools, ys, side = _run_layer_groups(body, x, pools, groups, side)
        (ks, vs), further = pools[0], tuple(a for kv in pools[1:]
                                             for a in kv)
        if self.spec_k:
            # speculative verify needs the model's pick at EVERY draft
            # position; keying on each input position keeps the accepted
            # stream identical to one-token-at-a-time decoding
            logits = model.logits(params, model.head(params, x))
            temps, topps, seeds = (
                lay.to_packed(jnp.broadcast_to(a[:, None], (C, qb)))
                for a in (temps, topps, seeds))
            out = lay.to_grid(_pick_tokens(logits, temps, topps, seeds,
                                           tok_positions))
        else:
            with jax.named_scope("head"):
                last = x.at[lay.last].get(mode="promise_in_bounds")  # [C, H]
            logits = model.logits(params, model.head(params, last))
            if self._constr_on:
                # constrained rows only see schema-legal logits;
                # unconstrained rows carry an all-True mask, and
                # where(True, x, _) == x exactly (bit-identity pinned)
                logits = jnp.where(vmask, logits, -1e30)
            # keyed on the LAST VALID input position (pos0 + n_valid - 1
            # = T - 1 for a final prefill chunk, the input token's
            # position for a decode row) — sampled streams are
            # bit-identical across chunk/budget/packing boundaries
            out = _pick_tokens(logits, temps, topps, seeds,
                               pos0 + n_valid - 1)[:, None]
        # the layers' counters ride out with the picks
        return out, ks, vs, ys, side, further

    def unified_arg_shapes(self, rung: Optional[int] = None) -> tuple:
        """Shape-only arguments of the unified step, mirroring the live
        dispatch (``_dispatch_unified``) exactly — for tracing it
        (``trace_unified``) or lowering it (``lower_unified``) with no
        device executing anything. ``rung`` is the step size, one of
        ``rungs``; None means the largest, the whole grid."""
        if self._lora_on or self._constr_on:
            raise NotImplementedError(
                "unified_arg_shapes covers the non-multitenant programs; "
                "register a dedicated entry for variant engines")
        C, qb, B = self.n_rows, self.qb, self.B
        rung = self.rungs[-1] if rung is None else rung
        if rung not in self.rungs:
            raise ValueError(f"step size {rung} is not one of {self.rungs}")

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        i32, f32 = jnp.int32, jnp.float32
        tokens = jax.ShapeDtypeStruct((C, qb), i32)
        prev = jax.ShapeDtypeStruct((C, qb if self.spec_k else 1), i32)
        cmask = jax.ShapeDtypeStruct((C,), jnp.bool_)
        crow = jax.ShapeDtypeStruct((C,), i32)
        ptab = jax.ShapeDtypeStruct((B + 1, self.max_blocks), i32)
        col_i = jax.ShapeDtypeStruct((C,), i32)
        col_f = jax.ShapeDtypeStruct((C,), f32)
        return (jax.tree.map(sds, self.params), sds(self.k_pages),
                sds(self.v_pages), tokens, prev, cmask, crow, ptab, col_i,
                col_i, col_i, col_f, col_f, col_i,
                *map(sds, self.side_planes.values()),
                *(a for x in self._extra
                  for a in (sds(x.k_pages), sds(x.v_pages), ptab)),
                *(() if self._state is None else (
                    sds(self._state.k_pages), sds(self._state.v_pages),
                    jax.ShapeDtypeStruct((B + 1, 2), i32))),
                jax.ShapeDtypeStruct((rung,), i32))

    def lower_unified(self, rung: Optional[int] = None):
        """The live jitted unified step lowered at its dispatch shapes
        (``.compile().as_text()`` is the program the chip runs), at the
        step size ``rung`` (None: the largest)."""
        return self._unified.lower(*self.unified_arg_shapes(rung))

    def trace_unified(self, rung: Optional[int] = None):
        """Trace the unified step to a closed jaxpr, shape-only — the
        entry program tools/lint/shardcheck.py propagates partition
        specs through and tools/lint/quantcheck.py interprets over the
        precision lattice (an int8 engine's scale planes, the two
        operands before the last, are the TPL303 provenance roots)."""
        return jax.make_jaxpr(self._unified_step_impl)(
            *self.unified_arg_shapes(rung))

    # -- scheduler ----------------------------------------------------------

    def set_params(self, params, version=None) -> None:
        """Swap the model weights in place (rolling-upgrade path). The
        params dict is the first operand of every jitted dispatch, so a
        same-shape swap takes effect on the next step with no recompile;
        resident KV pages stay valid (they hold attention state, not
        weights). Mirrors the ctor's weight-quant guard so a quantized
        engine receives quantized weights either way."""
        self.params = params
        if ((self._weight_only_int8 or self.cfg.weight_only_int8)
                and not isinstance(self.params["blocks"]["wq"], tuple)):
            self.params = quantize_weights_int8(self.params)
        self.param_version = version

    def register_adapter(self, adapter_id, weights: dict) -> None:
        """Add a LoRA adapter (multitenant.lora.make_lora layout) to the
        host library; requests name it by ``adapter_id``. Residency is
        lazy — first admission loads it onto pool pages."""
        if not self._lora_on:
            raise RuntimeError("register_adapter requires serving_lora")
        self.adapters.register(adapter_id, weights)

    def register_schema(self, schema_id, factory) -> None:
        """Bind ``schema_id`` to a zero-arg ConstraintState factory
        (e.g. ``json_schema_dfa(...).fresh``); a request naming it gets
        a fresh constraint at admission."""
        if not self._constr_on:
            raise RuntimeError(
                "register_schema requires serving_constrained")
        self._schemas[schema_id] = factory

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds max_seq "
                f"{self.max_seq}")
        n_blk = -(-(len(req.prompt) + req.max_new_tokens) // self.bs)
        if n_blk > self.n_pages - 1:       # page 0 is the sink
            raise ValueError(
                f"request {req.rid}: needs {n_blk} pages but the pool "
                f"holds {self.n_pages - 1} — it could never be admitted")
        for x in self._extra:
            if self._class_peak(x, n_blk) > x.n_pages - 1:
                raise ValueError(
                    f"request {req.rid}: holds up to "
                    f"{self._class_peak(x, n_blk)} pages of class "
                    f"'{x.cls.name}' at once but its pool holds "
                    f"{x.n_pages - 1} — it could never be admitted")
        if req.adapter_id is not None:
            if not self._lora_on:
                raise ValueError(
                    f"request {req.rid}: adapter_id set but serving_lora "
                    "is off")
            if not self.adapters.known(req.adapter_id):
                raise ValueError(
                    f"request {req.rid}: unknown adapter "
                    f"{req.adapter_id!r} — register_adapter it first")
        if req.schema_id is not None or req.constraint is not None:
            if not self._constr_on:
                raise ValueError(
                    f"request {req.rid}: constrained-decoding fields set "
                    "but serving_constrained is off")
            if (req.schema_id is not None
                    and req.schema_id not in self._schemas):
                raise ValueError(
                    f"request {req.rid}: unknown schema "
                    f"{req.schema_id!r} — register_schema it first")
            if (req.constraint is not None
                    and req.constraint.dfa.vocab_size
                    != self.cfg.vocab_size):
                raise ValueError(
                    f"request {req.rid}: constraint vocab "
                    f"{req.constraint.dfa.vocab_size} != model vocab "
                    f"{self.cfg.vocab_size}")
        self.queue.append(req)
        # lifecycle flow: first submission opens the request's async
        # track; a resume (preempt/migration/ship re-admission) is an
        # instant on the same id
        _obs.lifecycle(req.rid,
                       "arrival" if (req.t_first is None
                                     and not req.out_tokens)
                       else "resubmit",
                       engine=self.engine_id)

    def abort(self, rid: int) -> bool:
        """Cancel a request by rid, wherever it is: queued (removed) or
        slot-resident (pages released through the deferred-free path —
        an in-flight program may still write them; tokens an in-flight
        program produces for it are discarded at harvest). Returns False
        if the rid is unknown/already done."""
        now = _clock.now()
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                self.queue.pop(i)
                r.aborted = True
                r.t_done = now
                return True
        for s in range(self.B):
            req = self.slots[s]
            if req is not None and req.rid == rid:
                req.aborted = True
                req.t_done = now
                self._release_slot_pages(s, defer=True)
                self._prefilling.pop(s, None)
                self.table[s] = 0
                self.seq_lens[s] = 0
                self.cur_tok[s] = 0
                self.samp_temp[s] = 0.0
                self.slots[s] = None
                return True
        return False

    def _page_hashes(self, prompt: np.ndarray,
                     salt: bytes = b"") -> list[bytes]:
        """Cumulative content hash per FULL prompt page: hash j covers
        pages 0..j, so equal hash j implies the whole prefix matches —
        one dict hit per page, no per-page prefix comparison."""
        n_full = len(prompt) // self.bs
        out: list[bytes] = []
        # the hash preimage covers everything that determines a cached
        # page's bytes: the prefix tokens, the page size, and the KV
        # representation (cache_spec.hash_tag). For int8 pages the
        # stored bytes are the quantized page + its scale-plane entries
        # — a deterministic function of the prefix tokens given the
        # format — so tagging the seed keeps int8 and bf16 page content
        # from ever aliasing in the cache. ``salt`` extends the same
        # argument to per-request LoRA: the v-projection delta changes
        # the page bytes, so the adapter's content digest joins the preimage
        # (same-adapter requests still share; cross-adapter never alias).
        h = self._hash_chain(salt)
        for j in range(n_full):
            h.update(np.ascontiguousarray(
                prompt[j * self.bs:(j + 1) * self.bs],
                dtype=np.int32).tobytes())
            out.append(h.digest())
        return out

    def _hash_chain(self, salt: bytes = b""):
        """The prefix chain's hasher before its first page."""
        return hashlib.sha1(b"pt-prefix:%d" % self.bs
                            + self.cache_spec.hash_tag + salt)

    def _extend_hashes(self, slot: int) -> list[bytes]:
        """With a state class a request's chain grows past its prompt:
        the hashes of every full page of what the host knows of it (its
        prompt and the tokens harvested so far)."""
        req, hashes = self.slots[slot], self._slot_hashes[slot]
        n_known = (len(req.prompt) + len(req.out_tokens)) // self.bs
        if n_known > len(hashes) and self._slot_chain[slot] is not None:
            toks = np.concatenate([np.asarray(req.prompt, np.int32),
                                   np.asarray(req.out_tokens, np.int32)])
            for j in range(len(hashes), n_known):
                self._slot_chain[slot].update(np.ascontiguousarray(
                    toks[j * self.bs:(j + 1) * self.bs]).tobytes())
                hashes.append(self._slot_chain[slot].digest())
        return hashes

    def _cache_salt(self, req: Request) -> bytes:
        """The per-request prefix-cache hash salt: the LoRA adapter's
        content digest when one is bound (the v-projection delta changes
        the page BYTES, so KV written under adapter X must never serve a
        request under adapter Y or none), else empty. Shared by
        admission lookup and migration export so a shipped page lands
        under exactly the hash the victim's re-admission will probe."""
        if self._lora_on and req.adapter_id is not None:
            return b"lora:" + self.adapters.digest_of(req.adapter_id)
        return b""

    def _alloc_pages(self, n: int) -> Optional[list[int]]:
        """Free-list alloc, reclaiming idle (refcount-0) prefix-cache
        pages on demand when the list runs short — then idle (warm but
        unreferenced) LoRA adapters, in that order: cached KV is cheaper
        to rebuild than an adapter reload is frequent."""
        if _chaos.active():               # disarmed: one global load
            spec = _chaos.fire("pool.alloc", ctx={"engine": self.engine_id})
            if spec is not None and spec.kind == "fail":
                return None               # pool reports empty; admission
                                          # backpressure handles the rest
        if len(self.pool.free) < n:
            self.pool.evict(n - len(self.pool.free))
        while (len(self.pool.free) < n and self.adapters is not None
               and self.adapters._evict_idle()):
            pass
        pages = self.pool.alloc(n)
        if pages and self.side_planes:
            # a reused page's stale side-plane entries (an int8 page's
            # running absmax would quantize the new tenant's tokens
            # against a garbage, possibly inflated, scale) are zeroed at
            # allocation, so the first write sets fresh ones. Chained
            # after any in-flight step's donated output, so programs
            # already dispatched are unaffected.
            pg = jnp.asarray(pages, jnp.int32)
            self.side_planes = {
                name: kv_scale_reset(plane, pg, axis=1)
                for name, plane in self.side_planes.items()}
        return pages

    # -- further cache classes (models/seam.py) ---------------------------

    def _class_peak(self, x: _ClassPages, n_blk: int) -> int:
        """Pages of a windowed class a request of ``n_blk`` blocks may
        hold at once: the blocks a query can still read (the window,
        and one more where it straddles), those the ticks in flight and
        being built write (a tick writes ``prefill_budget`` tokens at
        most), and those the last tick let go of, which wait for its
        harvest."""
        per_tick = -(-self.prefill_budget // self.bs)
        return min(n_blk, -(-x.cls.window // self.bs) + 2 * per_tick + 2)

    def _next_query(self, slot: int) -> int:
        """The position of a live slot's next query: how far its context
        has been dispatched."""
        return (self._prefilling[slot] if slot in self._prefilling
                else int(self.seq_lens[slot]))

    def _usable_hit(self, hashes: list[bytes], state: bool = True) -> tuple:
        """(n, n0): the longest prefix hit every class can honour, in
        pages, and the longest class 0 alone has. A hit of ``n`` pages
        needs class 0 to hold pages ``[0, n)``, every windowed class
        the pages a query at ``n * bs`` can still read, and the state
        class (left out with ``state`` false: what the paged classes
        could honour alone) a snapshot taken at ``n * bs``."""
        n0 = self.pool.peek(hashes)
        runs = []                   # per class: cached pages in a row
        for x in self._extra:       # ending at page j, for j < n0
            run, r = [], 0
            for h in hashes[:n0]:
                r = r + 1 if h in x.pool.cache else 0
                run.append(r)
            runs.append(run)
        n = n0
        st = self._state if state else None
        while n and (any(
                run[n - 1] < n - x.cls.live_from(n * self.bs) // self.bs
                for x, run in zip(self._extra, runs))
                or (st is not None and not st.has(hashes[n - 1]))):
            n -= 1
        return n, n0

    def _grow_classes(self, sched: list) -> None:
        """Give every row of the tick being built the pages it writes in
        the further classes (class 0 took its own at admission). The
        room was reserved at admission (_ClassPages.room)."""
        for x in self._extra:
            need = [(s, b) for s, _kind, pos, m, _d in sched
                    for b in range(pos // self.bs,
                                   (pos + m - 1) // self.bs + 1)
                    if not x.full_rows[s, b]]
            need = list(dict.fromkeys(need))
            if not need:
                continue
            pages = x.alloc(len(need))
            if pages is None:
                raise RuntimeError(
                    f"cache class '{x.cls.name}' has no {len(need)} pages "
                    f"for a tick although admission reserved them: "
                    f"{x.accounting()}")
            for (s, b), page in zip(need, pages):
                x.full_rows[s, b] = page
                x.owned[s][b] = page

    def _release_behind_windows(self) -> int:
        """Between ticks: in every windowed class, let go of the blocks
        no query of a live request can read any more (seam.py: the
        rules). The tick in flight may still read them, so the pages
        wait for its harvest as a finished request's do. Returns the
        pages let go."""
        n = 0
        for s in range(self.B):
            if self.slots[s] is None:
                continue
            nq = self._next_query(s)
            for x in self._extra:
                first = x.cls.live_from(nq) // self.bs
                for b in range(x.tail[s], first):
                    n += x.release_block(s, b)
                x.tail[s] = max(x.tail[s], first)
        self.stats["pages_released_by_window"] += n
        return n

    def _admit(self, now: float) -> None:
        """Admit arrived requests into free slots, FIFO with skip: a
        pool-blocked request is stepped over so smaller requests behind
        it can run (no head-of-line blocking), but once its ``age``
        (skip count) exceeds ``admit_aging`` it becomes a barrier —
        nothing behind it is admitted, so every freed page goes to it
        and it cannot starve. Admission maps cached prefix pages into
        the block table (incref) and allocates only the rest. With
        further cache classes it needs room in each (_ClassPages.room)
        and takes the longest hit every class can honour
        (_usable_hit)."""
        free_slots = [s for s in range(self.B) if self.slots[s] is None]
        cand = list(self.queue)
        if self._prio_on:
            # priority classes: admission order is highest-priority-first,
            # FIFO (arrival) within a class; the skip/aging machinery is
            # unchanged. sort is stable, so priorities all-0 reproduces
            # the legacy order exactly.
            cand.sort(key=lambda r: (-r.priority, r.arrival))
        preempted = False
        for req in cand:
            if not free_slots:
                break
            if req.out_tokens and len(req.out_tokens) >= req.max_new_tokens:
                # a preempted request can complete via the token its
                # in-flight row produced: nothing left to decode, so it
                # leaves the queue instead of re-admitting (t_done was
                # already recorded at harvest)
                for j, r in enumerate(self.queue):
                    if r is req:
                        self.queue.pop(j)
                        break
                continue
            if req.arrival > now:
                continue
            # effective prompt: the original plus tokens already emitted
            # before a preemption — a resumed request re-prefills its
            # whole history (mostly through the prefix cache) and its
            # next pick lands on the same (seed, position) key as the
            # uninterrupted stream (preempt-resume bit-identity)
            P = (np.concatenate([np.asarray(req.prompt, np.int32),
                                 np.asarray(req.out_tokens, np.int32)])
                 if req.out_tokens else req.prompt)
            T = len(P)
            n_blk = -(-(len(req.prompt) + req.max_new_tokens) // self.bs)
            # the adapter increfs before the KV alloc so a shared hit
            # cannot be evicted from under us while we evict for pages
            aslot = 0
            if self._lora_on and req.adapter_id is not None:
                aslot = self.adapters.acquire(req.adapter_id)
            if aslot is None or any(      # adapter- or class-blocked
                    x.room() < self._class_peak(x, n_blk)
                    for x in self._extra):       # == pool-blocked
                shared, pages = [], None
            else:
                # never look up the page holding the last prompt token:
                # its chunk must run to produce the first-token logits
                hashes = (self._page_hashes(P, self._cache_salt(req))
                          if self._cache_on else [])
                limit = hashes[:(T - 1) // self.bs]
                if self._further and limit:
                    n_hit, n0 = self._usable_hit(limit)
                    self.pool.misses += len(limit) - n_hit
                    n_paged = n_hit
                    if self._state is not None:
                        n_paged, _ = self._usable_hit(limit, state=False)
                        self.stats["prefix_state_lost_tokens"] += (
                            (n_paged - n_hit) * self.bs)
                    if self._extra:
                        self.stats["prefill_window_lost_tokens"] += (
                            (n0 - n_paged) * self.bs)
                    limit = limit[:n_hit]
                shared = self.pool.lookup(limit)
                pages = self._alloc_pages(n_blk - len(shared))
            if pages is None:
                self.pool.decref(shared)
                if aslot:
                    self.adapters.decref(req.adapter_id)
                if (self._prio_on and not preempted
                        and self._preempt_for(req)):
                    # a lower-priority resident gave up its KV; its pages
                    # settle through deferred-free, so the retry happens
                    # next step (at most one preemption per admit pass)
                    preempted = True
                req.age += 1
                if req.age > self.admit_aging:
                    break                  # aged request becomes a barrier
                continue
            for j, r in enumerate(self.queue):
                if r is req:
                    self.queue.pop(j)
                    break
            slot = free_slots.pop(0)
            n_shared = len(shared)
            self.slots[slot] = req
            _obs.lifecycle(req.rid, "admit", engine=self.engine_id,
                           slot=slot)
            self._slot_shared[slot] = shared
            self._slot_owned[slot] = pages
            self._slot_hashes[slot] = hashes
            self._slot_nshared[slot] = n_shared
            self._slot_offered[slot] = n_shared
            self._slot_prompt[slot] = P
            if self._lora_on and req.adapter_id is not None:
                self._slot_adapter_id[slot] = req.adapter_id
                self._slot_aslot[slot] = aslot
            if (self._constr_on and req.constraint is None
                    and req.schema_id is not None):
                # fresh DFA on first admission only — a resumed request
                # keeps its advanced state (its emitted tokens stand)
                req.constraint = self._schemas[req.schema_id]()
            row = np.zeros((self.max_blocks,), np.int32)
            row[:n_shared] = shared
            row[n_shared:n_blk] = pages
            self._full_rows[slot] = row
            for x in self._extra:
                # the pages a query at the hit's end can still read,
                # claimed under the same hashes; the rest come as the
                # request grows (_grow_classes)
                first = x.cls.live_from(n_shared * self.bs) // self.bs
                got = x.pool.lookup(hashes[first:n_shared])
                x.shared[slot] = dict(zip(range(first, n_shared), got))
                x.full_rows[slot, first:n_shared] = got
                x.tail[slot] = first
                x.peak[slot] = self._class_peak(x, n_blk)
            self.table[slot] = 0           # decode view: sink until flip
            self.seq_lens[slot] = 0
            self.cur_tok[slot] = 0
            # prefill resumes AFTER the cached prefix: a full-prefix hit
            # costs zero redundant prefill FLOPs (prefill_tokens counts
            # only tokens actually run)
            self._prefilling[slot] = n_shared * self.bs
            self.stats["prefill_cached_tokens"] += n_shared * self.bs
            if self._state is not None:
                self._admit_state(slot, req, P, n_shared)

    def _admit_state(self, slot: int, req: Request, P, n_shared: int) -> None:
        """The state class's part of an admission: the request's state
        stands in the snapshot its hit ends on (which it now holds), or
        in the zero slot; its chain is kept so that it can grow."""
        st, hashes = self._state, self._slot_hashes[slot]
        st.at[slot], st.held[slot] = STATE_ZERO, 0
        if n_shared:
            st.held[slot], = st.pool.lookup([st.key(hashes[n_shared - 1])])
            st.at[slot] = st.snap0 + st.held[slot]
            self.stats["state_snapshots_hit"] += 1
            self.stats["preempt_resumed_from_snapshot"] += bool(
                req.n_preempted)
        if hashes and hashes[0] in self.pool.cache:
            self.stats["admitted_with_cached_prefix"] += 1
        self._slot_chain[slot] = None
        if self._cache_on:
            chain = self._hash_chain(self._cache_salt(req))
            chain.update(np.ascontiguousarray(
                P[:len(hashes) * self.bs], dtype=np.int32).tobytes())
            self._slot_chain[slot] = chain

    def _preempt_for(self, req: Request) -> bool:
        """Evict the weakest strictly-lower-priority resident so ``req``
        can admit once the pages settle: lowest priority first, youngest
        (latest arrival) within a class — the request that loses the
        least progress. Returns False when nobody outranks."""
        best = None
        for s in range(self.B):
            r = self.slots[s]
            if r is None or r.priority >= req.priority:
                continue
            key = (r.priority, -r.arrival)
            if best is None or key < best[0]:
                best = (key, s)
        if best is None:
            return False
        self._preempt(best[1])
        return True

    def _preempt(self, slot: int) -> None:
        """Evict a resident request's KV pages and requeue it; emitted
        tokens stand, and re-admission re-prefills prompt + emitted
        history (through the prefix cache, so resumption is nearly
        free). A token an in-flight program holds for it is legitimate
        — it lands via the snapshot at harvest, BEFORE the request can
        re-admit (admission runs at step start, harvest after dispatch),
        so the resumed effective prompt always includes it."""
        req = self.slots[slot]
        req.n_preempted += 1
        req.age = 0                        # re-admission ages afresh
        self.stats["preemptions"] += 1
        _obs.lifecycle(req.rid, "preempt", engine=self.engine_id)
        self._release_slot_pages(slot, defer=True)
        self._prefilling.pop(slot, None)
        self.table[slot] = 0
        self.seq_lens[slot] = 0
        self.cur_tok[slot] = 0
        self.samp_temp[slot] = 0.0
        self.slots[slot] = None
        self.queue.append(req)

    def _release_slot_pages(self, slot: int, defer: bool) -> None:
        """Tear down a slot's page state: owned pages to the free list
        (via _deferred_free when a program may still be in flight),
        shared pages decref'd back to the cache. Refcount-0 cache pages
        become evictable only once no in-flight program can read them
        (commit_evictable at harvest / the idle-release branch)."""
        owned, shared = self._slot_owned[slot], self._slot_shared[slot]
        self._slot_owned[slot] = []
        self._slot_shared[slot] = []
        self.pool.decref(shared)
        if defer:
            self._deferred_free.extend(owned)
        else:
            self.pool.release(owned)
            self.pool.commit_evictable()
        self._full_rows[slot] = 0
        for x in self._further:
            x.release_slot(slot, defer)
        # adapter refcount rides slot residency: every teardown path
        # (finish / abort / preempt / predictive release) lands here.
        # Idempotent — the id is cleared on first release.
        aid = self._slot_adapter_id[slot]
        if aid is not None:
            self.adapters.decref(aid)
            self._slot_adapter_id[slot] = None
        self._slot_aslot[slot] = 0
        self._slot_prompt[slot] = None

    def _finish_if_done(self, slot: int, defer_free: bool = False) -> None:
        req = self.slots[slot]
        if req is not None and len(req.out_tokens) >= req.max_new_tokens:
            req.t_done = _clock.now()
            _obs.lifecycle(req.rid, "done", engine=self.engine_id)
            self._release_slot_pages(slot, defer=defer_free)
            self.table[slot] = 0           # sink
            self.seq_lens[slot] = 0
            self.cur_tok[slot] = 0
            self.samp_temp[slot] = 0.0     # idle rows pick greedily
            self.slots[slot] = None

    def _chaos_step(self) -> None:
        """Armed-only fault probe for ``engine.step`` (kinds: ``raise``
        — the router sees a dead replica; ``hang`` — sleep ``seconds``
        so the router's step-budget watchdog catches the stall). Kept
        out of line so the disarmed ``step()`` cost is exactly the
        ``chaos.active()`` global load."""
        ctx = {"engine": self.engine_id}
        if self.pool_role is not None:
            ctx["pool"] = self.pool_role
        spec = _chaos.fire("engine.step", ctx=ctx)
        if spec is None:
            return
        if spec.kind == "hang":
            time.sleep(float(spec.args.get("seconds", 0.05)))
        else:
            raise _chaos.ChaosInjected(
                f"chaos: engine {self.engine_id} step failure")

    def step(self, now: Optional[float] = None) -> bool:
        """Admissions + ONE unified dispatch (decode rows + prefill
        chunks in the same grid) + harvest. Returns True while work
        remains — `while engine.step(): ...` is the external drive
        contract; an idle tick runs no compute.

        Pipelined (speculation off): the next step is dispatched BEFORE
        the previous step's tokens are fetched, chained on-device
        through the previous output rows — the per-step host round-trip
        overlaps device compute instead of serializing with it (its cost
        is not measured on a locally attached chip). Consequences the
        scheduler handles:

        - a request's finish is predicted at dispatch (each row yields
          exactly one token), so its SLOT is released immediately while
          its pages wait in ``_deferred_free`` for one harvest cycle —
          a page is never handed to a new request while an in-flight
          program that still references it can write to it;
        - a slot admitted while a step is in flight joins the NEXT
          dispatch; the prefill-final -> first-decode handoff rides the
          same chain as decode continuations.

        Speculative (``serving_speculative_k`` > 0): synchronous —
        drafts are proposed from host-side history, so each step is
        harvested before the next dispatch; accepted counts advance
        seq_lens at harvest (a rejected draft's k/v is masked by its
        position and overwritten before it could ever be attended).
        """
        if _chaos.active():               # disarmed: one global load,
            self._chaos_step()            # nothing else on the hot path
        with _obs.span("engine.step", engine=self.engine_id) as sp:
            return self._step_impl(now, sp)

    def _step_impl(self, now: Optional[float], sp) -> bool:
        now = _clock.now() if now is None else now
        with _obs.span("engine.admit", engine=self.engine_id):
            self._admit(now)
        prev = self._inflight
        self._class_tick = {}
        with _obs.span("engine.dispatch", engine=self.engine_id):
            self._dispatch_unified(now)
        # what this tick put on the device, recorded on engine.step's
        # end: decode and prefill rows of the grid, requests left waiting
        # and the step size it ran at against the tokens it carried
        _, rows, _, places = (self._inflight if self._inflight is not prev
                              else (None, (), None, 0))
        n_dec = sum(1 for r in rows if r[3] == "dec")
        tick = dict(rows_decode=n_dec, rows_prefill=len(rows) - n_dec,
                    queued=len(self.queue), places=places,
                    tokens=sum(r[4] for r in rows), **self._class_tick)
        sp.set(**tick)
        # synchronous modes (spec, constrained): drafts and vocab masks
        # are host state derived from the previous step's tokens, so each
        # step harvests before the next dispatch (chaining is moot —
        # nothing stays in flight); pipelined: the step before this one
        harvest = (self._inflight if self.spec_k or self._constr_on
                   else prev)
        if harvest is not None:
            with _obs.span("engine.harvest", engine=self.engine_id):
                self._harvest(harvest)
            if self._tick_stats:
                # and what the harvested step's layers counted
                tick.update(self._tick_stats)
                sp.set(**tick)
        if self.prefill_only:
            self._export_completed()
        if self._state is not None:
            self._publish_snapshots()
        if self._extra:
            # and what the windows let go of behind the tick
            sp.set(**tick, pages_released_by_window=(
                self._release_behind_windows()))
        if self._inflight is None and (
                self._deferred_free or self.pool.pending_evict or any(
                    x.deferred_free or x.pool.pending_evict
                    for x in self._further)):
            # nothing in flight: deferred/pending pages can only be
            # touched by programs already chained BEFORE any future
            # consumer (the donated page arrays serialize every
            # dispatch), so reclaim now — pool-constrained admission
            # would otherwise deadlock waiting for a harvest
            self.pool.release(self._deferred_free)
            self._deferred_free = []
            self.pool.commit_evictable()
            for x in self._further:
                x.settle()
        # predictive release: each in-flight token-bearing row yields
        # exactly one token (speculation off), so a request the just-
        # dispatched step completes can give up its SLOT now — the next
        # step admits into it one dispatch earlier; its token still
        # lands via the snapshot, its pages wait in _deferred_free
        if not self.spec_k and self._inflight is not None:
            for idx, s, req, kind, m, _dr in self._inflight[1]:
                if (kind != "mid" and self.slots[s] is req
                        and req.max_new_tokens - len(req.out_tokens) <= 1):
                    self._release_slot_pages(s, defer=True)
                    self.table[s] = 0
                    self.seq_lens[s] = 0
                    self.samp_temp[s] = 0.0
                    self.slots[s] = None
        return (self._inflight is not None or bool(self.queue)
                or any(s is not None for s in self.slots))

    def _export_completed(self) -> None:
        """Prefill-only sweep (runs post-harvest): a resident slot that
        is past its prefill flip with its first token landed is done
        HERE — export the prompt's full pages (the shipment the router
        hands to a decode engine; None when the prompt spans less than
        one full page and re-prefill is the whole handoff), queue the
        request on ``outbox``, and release the slot immediately. No
        decode residency: pages settle through the deferred-free path
        exactly like a predictive release, so an in-flight program that
        still references them keeps them pinned for one harvest cycle.
        The decode engine re-admits with effective prompt = prompt +
        out_tokens, its cache lookup covers exactly the shipped pages,
        and the tail re-prefills — the same resume path preemption and
        engine loss already use, hence bit-identical streams. Also the
        re-split path: a mid-decode resident on an engine returning to
        the prefill role is swept out the same way and resumes on a
        decode engine. A slot the CURRENT in-flight program references
        is never swept: a resumed request (history in out_tokens) would
        otherwise export before its prefill-final emission is
        harvested, and the snapshot append plus the re-admission's
        re-emission would duplicate that token in the stream."""
        inflight = ({s for _i, s, _r, _k, _m, _d in self._inflight[1]}
                    if self._inflight is not None else set())
        for s in range(self.B):
            req = self.slots[s]
            if (req is None or s in self._prefilling or s in inflight
                    or not req.out_tokens):
                continue
            t0 = _clock.now()
            with _obs.span("wire.stage", engine=self.engine_id,
                           rid=req.rid):
                shipment = (self.stage_request_pages(req.rid)
                            if self._wire_overlap
                            else self.export_request_pages(req.rid))
            self.stats["wire_export_ms"] += (_clock.now() - t0) * 1e3
            self.outbox.append((req, shipment))
            _obs.lifecycle(req.rid, "ship", engine=self.engine_id)
            # immediate (non-deferred) release: the in-flight guard
            # above means no dispatched program references this slot's
            # pages (its prefill-final is harvested, and a prefill-only
            # engine never dispatches its decode rows), so the pool can
            # recycle them for the NEXT admission wave without waiting
            # for a full pipeline drain — the prefill pool's slot
            # turnover is the whole point of the split
            self._release_slot_pages(s, defer=False)
            self.table[s] = 0
            self.seq_lens[s] = 0
            self.cur_tok[s] = 0
            self.samp_temp[s] = 0.0
            self.slots[s] = None

    def _dispatch_unified(self, now: float = 0.0) -> None:
        """Build and dispatch one unified step for the CURRENT slot
        state; does not block. Row assignment: every decoding slot gets
        one row (1 input token + up to spec_k drafts), remaining rows
        carry qb-token prefill slices (FIFO over admission order), the
        rest idle against the sink. Charges the occupancy ledger one
        slot-token per engaged slot (m for a speculative row) — the
        decode/spec split is classified at harvest."""
        if self._commit_pending:
            # deferred adoption commits land HERE, between programs: the
            # scatter chains after the in-flight step's donated output
            # and before this dispatch, so the program about to read the
            # adopted pages sees committed bytes
            self._flush_commits()
        C, qb = self.n_rows, self.qb
        pref_entry = set(self._prefilling)
        decoding = [s for s in range(self.B) if self.slots[s] is not None
                    and s not in pref_entry]
        if self.prefill_only:
            # pool role: this engine never dispatches a decode row — a
            # slot past its prefill flip idles until the export sweep
            # ships its pages and releases it (same step, post-harvest)
            decoding = []
        # previous dispatch's token-bearing rows, for in-program chaining
        prev_rows: dict[int, int] = {}
        if self._inflight is not None:
            for idx, s, req, kind, m, _dr in self._inflight[1]:
                if kind != "mid" and self.slots[s] is req:
                    prev_rows[s] = idx
        sched = []                         # (slot, kind, pos0, m, drafts)
        for s in decoding:
            req = self.slots[s]
            pending = 1 if s in prev_rows else 0
            remaining = req.max_new_tokens - len(req.out_tokens) - pending
            drafts: list = []
            if self.spec_k and remaining > 1:
                hist = req.prompt.tolist() + req.out_tokens
                drafts = self._proposer.propose(
                    hist, min(self.spec_k, remaining - 1))
            sched.append((s, "dec", int(self.seq_lens[s]),
                          1 + len(drafts), drafts))
        fin_slots = set()
        pref_touched: dict[int, int] = {}
        for slot in list(self._prefilling):
            if len(sched) >= C:
                break
            T = len(self._slot_prompt[slot])   # prompt (+ resumed history)
            pos = self._prefilling[slot]
            end = T
            if self._state is not None and self._cache_on:
                # a tick's last chunk ends on a page boundary where it
                # can: the state is snapshotted there (seam.py)
                hi = min(T, pos + (C - len(sched)) * qb)
                cut = hi // self.bs * self.bs
                end = cut if hi % self.bs and cut > pos else hi
            while pos < end and len(sched) < C:
                n = min(qb, end - pos)
                sched.append((slot, "fin" if pos + n >= T else "mid",
                              pos, n, None))
                pos += n
            self._prefilling[slot] = pos
            pref_touched[slot] = pos
        if not sched:
            return
        if self._extra:
            self._grow_classes(sched)
        stab = self._state_table(sched) if self._state is not None else None
        tokens = np.zeros((C, qb), np.int32)
        rs = np.full((C,), self.B, np.int32)       # idle rows -> sink row
        p0 = np.zeros((C,), np.int32)
        nv = np.ones((C,), np.int32)
        tt = np.zeros((C,), np.float32)
        tp = np.ones((C,), np.float32)
        tsd = np.zeros((C,), np.int32)
        cmask = np.zeros((C,), bool)
        crow = np.zeros((C,), np.int32)
        if self._lora_on:
            aidv = np.zeros((C,), np.int32)    # idle rows -> identity slot
        if self._constr_on:
            vm = np.ones((C, self.cfg.vocab_size), bool)
        snap = []
        n_pf_rows = 0
        for idx, (s, kind, pos, m, drafts) in enumerate(sched):
            req = self.slots[s]
            rs[idx] = s
            p0[idx] = pos
            nv[idx] = m
            if self._lora_on:
                aidv[idx] = self._slot_aslot[s]
            if kind == "dec":
                if s in prev_rows:
                    cmask[idx] = True
                    crow[idx] = prev_rows[s]
                else:
                    tokens[idx, 0] = self.cur_tok[s]
                if drafts:
                    tokens[idx, 1:m] = drafts
            else:
                n_pf_rows += 1
                tokens[idx, :m] = self._slot_prompt[s][pos:pos + m]
                if kind == "fin":
                    fin_slots.add(s)
            if kind != "mid":
                tt[idx] = req.temperature
                tp[idx] = req.top_p
                tsd[idx] = req.seed
                if self._constr_on and req.constraint is not None:
                    vm[idx] = req.constraint.mask()
            snap.append((idx, s, req, kind, m, drafts))
        ptab = np.concatenate(
            [self._full_rows, np.zeros((1, self.max_blocks), np.int32)])
        prev_out = self._prev_out_dev
        if prev_out is None:
            prev_out = jnp.zeros((C, qb if self.spec_k else 1), jnp.int32)
        # tpu-lint TPL002 audit: the program below is dispatched
        # asynchronously while the scheduler keeps mutating its numpy
        # state — every operand is a fresh local array here, but
        # jnp.array (copying) keeps the handoff alias-free by
        # construction.
        extra = []              # multi-tenant varargs, behind the side planes
        if self._lora_on:
            extra += [jnp.array(aidv), self.adapters.stacks()]
        if self._constr_on:
            extra.append(jnp.array(vm))
        fixed = (jnp.array(tokens), prev_out, jnp.array(cmask),
                 jnp.array(crow), jnp.array(ptab))
        # each further class's table, as class 0's: the slots' rows and
        # the sink's row for idle rows
        xtabs = [jnp.array(np.concatenate(
            [x.full_rows, np.zeros((1, self.max_blocks), np.int32)]))
            for x in self._extra]
        if stab is not None:
            xtabs.append(jnp.array(stab))
        per_row = (jnp.array(p0), jnp.array(nv), jnp.array(tt),
                   jnp.array(tp), jnp.array(tsd))

        def launch(row_slot, rung):
            out, self.k_pages, self.v_pages, ys, side, further = (
                self._unified(
                    self.params, self.k_pages, self.v_pages, *fixed,
                    row_slot, *per_row, *self.side_planes.values(),
                    *(a for x, t in zip(self._further, xtabs)
                      for a in (x.k_pages, x.v_pages, t)),
                    *extra, self._places[rung]))
            self.side_planes = dict(zip(self.side_planes, side))
            for i, x in enumerate(self._further):
                x.k_pages, x.v_pages = further[2 * i:2 * i + 2]
            return out, ys

        # the smallest step size that holds the tick's tokens
        n_tok = sum(m for _s, _k, _p, m, _d in sched)
        rung = next(r for r in self.rungs if r >= n_tok)
        if not self._places:
            # the first dispatch compiles every size, so that no later
            # tick does: each other size runs once with every row the
            # sink's, which writes what idle rows write and nothing else
            self._places = {r: jnp.arange(r, dtype=jnp.int32)
                            for r in self.rungs}
            for r in self.rungs:
                if r != rung:
                    launch(jnp.full((C,), self.B, jnp.int32), r)
        out, ys = launch(jnp.array(rs), rung)
        self.stats["token_places"] += rung
        self.stats["tokens_packed"] += n_tok
        self._inflight = (out, snap, ys, rung)
        self._prev_out_dev = out
        # post-dispatch bookkeeping: prefix-cache offers for pages this
        # step completed, prefill flips, decode position advance
        for slot, pos_new in pref_touched.items():
            hashes = self._slot_hashes[slot]
            j1 = min(pos_new // self.bs, len(hashes))
            for j in range(self._slot_offered[slot], j1):
                # full prompt page this request prefilled itself: offer
                # it to the cache. On success ownership transfers to the
                # cache (refcount 1 = this request's mapping) — it
                # outlives the request until evicted under pool pressure.
                page = int(self._full_rows[slot][j])
                if self.pool.insert(hashes[j], page):
                    self._slot_owned[slot].remove(page)
                    self._slot_shared[slot].append(page)
                for x in self._extra:
                    # and in every class that still holds the block
                    page = int(x.full_rows[slot, j])
                    if j in x.owned[slot] and x.pool.insert(hashes[j],
                                                            page):
                        x.shared[slot][j] = x.owned[slot].pop(j)
            self._slot_offered[slot] = max(self._slot_offered[slot], j1)
        for idx, s, req, kind, m, drafts in snap:
            if kind == "fin":
                del self._prefilling[s]
                self.table[s] = self._full_rows[s]
                self.seq_lens[s] = len(self._slot_prompt[s])
                self.samp_temp[s] = req.temperature
                self.samp_topp[s] = req.top_p
                self.samp_seed[s] = req.seed
            if kind != "dec":
                self.stats["prefill_tokens"] += m
        if not self.spec_k:
            for s in decoding:
                self.seq_lens[s] += 1
        # occupancy ledger: one slot-token per engaged slot this step
        # (m for a speculative row); decode/fin rows are classified at
        # harvest (active / spec-rejected / overrun)
        n_idle = self.B - len(decoding) - len(pref_entry)
        if n_idle:
            blocked = any(r.arrival <= now for r in self.queue)
            self.stats["waste_admission_blocked_slot_tokens" if blocked
                       else "waste_queue_empty_slot_tokens"] += n_idle
        # a resumed (previously preempted) request's mid-prefill slot-
        # tokens are the price of preemption, not of admission latency —
        # charge them to their own bucket (0 with serving_priorities off)
        mid_slots = [s for s in pref_entry if s not in fin_slots]
        n_mid_pre = sum(
            1 for s in mid_slots
            if self.slots[s] is not None and self.slots[s].n_preempted)
        self.stats["waste_preempted_slot_tokens"] += n_mid_pre
        self.stats["waste_prefill_slot_tokens"] += len(mid_slots) - n_mid_pre
        n_mid_slots = len(mid_slots)
        self.stats["decode_slot_tokens"] += (
            sum(m for _s, kind, _p, m, _d in sched if kind == "dec")
            + len(fin_slots) + n_mid_slots + n_idle)
        self.stats["unified_steps"] += 1
        if self._further:
            # what live requests hold in each class against the context
            # it stands for, this tick and summed
            resident = [s for s in range(self.B)
                        if self.slots[s] is not None]
            live = {f"pages_live.{self.classes[0].name}": sum(
                len(self._slot_owned[s]) + len(self._slot_shared[s])
                for s in resident)}
            live.update({f"pages_live.{x.cls.name}": x.live_pages()
                         for x in self._extra})
            live["context_tokens_live"] = sum(
                self._next_query(s) for s in resident)
            if self._state is not None:
                n = sum(map(self._state.slots_of, resident))
                live["state_slots_live"] = n
                live["state_bytes_live"] = n * self._state.cls.slot_bytes()
            for k, v in live.items():
                self.stats[k] += v
            self._class_tick = live
        if decoding:
            self.stats["decode_steps"] += 1
        if n_pf_rows:
            self.stats["prefills"] += 1
            self.stats["prefill_grid_tokens"] += n_pf_rows * qb

    def _state_table(self, sched: list) -> np.ndarray:
        """The state class's operand for the tick being built: per engine
        row the slot its request's state is read from and the slot it is
        written to (seam.py: the state class), the sink's row for idle
        rows. A request whose processed length stands on a page boundary
        at the end of this tick writes a snapshot slot, where one can be
        had, and reads it from there next tick; every other writes its
        live slot."""
        st = self._state
        tab = np.full((self.B + 1, 2), STATE_DUMP, np.int32)
        ends: dict[int, int] = {}
        for s, _kind, pos, m, _d in sched:
            ends[s] = pos + m
        for s, end in ends.items():
            read, write = st.at[s], st.live(s)
            if self._cache_on and end % self.bs == 0:
                evictable = len(st.pool.evictable)
                page = st.alloc()
                self.stats["state_snapshots_evicted"] += (
                    evictable - len(st.pool.evictable))
                if page:
                    if st.pending[s] is not None:   # never hashed: let go
                        st.deferred_free.append(st.pending[s][0])
                    st.pending[s] = (page, end // self.bs - 1)
                    write = st.snap0 + page
                    self.stats["state_snapshots_taken"] += 1
                else:
                    self.stats["state_snapshots_unavailable"] += 1
            if st.private[s] and read != write:
                # the tick moves the state off a snapshot of its own that
                # the cache had no use for
                st.deferred_free.append(st.private[s])
                st.private[s] = 0
            tab[s] = read, write
            st.at[s] = write
        return tab

    def _publish_snapshots(self) -> None:
        """Put the snapshots the tick in flight writes under their
        hashes, once the host knows the tokens behind them (after the
        harvest of the tick before: seam.py), and offer class 0's pages
        up to them, which a hit on them needs. The request then holds
        the new snapshot in place of the one it held."""
        st = self._state
        for s in range(self.B):
            if st.pending[s] is None or self.slots[s] is None:
                continue
            page, j = st.pending[s]
            hashes = self._extend_hashes(s)
            if len(hashes) <= j:
                continue
            st.pending[s] = None
            for i in range(self._slot_offered[s], j + 1):
                pg = int(self._full_rows[s][i])
                if self.pool.insert(hashes[i], pg):
                    self._slot_owned[s].remove(pg)
                    self._slot_shared[s].append(pg)
            self._slot_offered[s] = max(self._slot_offered[s], j + 1)
            if st.pool.insert(st.key(hashes[j]), page):
                if st.held[s]:
                    st.pool.decref([st.held[s]])
                st.held[s] = page
            else:
                st.private[s] = page

    def cached_snapshots(self) -> list:
        """``(request, tokens, slot)`` for every snapshot of a live
        request that stands under the cache's hashes, the one it holds
        and the earlier ones not yet evicted: slot ``slot`` of the state
        class's pools holds the request's state after exactly its first
        ``tokens`` tokens (its prompt, then what was served). For checks
        that read the state itself; nothing on the serving path calls
        it."""
        st, out = self._state, []
        for s, req in enumerate(self.slots):
            if st is None or req is None:
                continue
            for j, h in enumerate(self._slot_hashes[s]):
                page = st.pool.cache.get(st.key(h))
                if page is not None:
                    out.append((req, (j + 1) * self.bs, st.snap0 + page))
        return out

    def _harvest(self, inflight) -> None:
        """Fetch a completed step's row outputs (the only host sync of
        the serving path) and apply them; release pages freed one cycle
        ago — no in-flight program can reference them anymore."""
        out_dev, snap, ys, _rung = inflight
        with _obs.span("engine.harvest.wait", engine=self.engine_id):
            # [C, 1] or [C, qb], and the layers' counters in the same
            # fetch: one sync
            toks, ys = jax.device_get((out_dev, ys))
        if any(y is not None for y in ys):
            self._tick_stats = self.model.tick_stats(
                ys, sum(m for _i, _s, _r, _k, m, _d in snap))
            for k, v in self._tick_stats.items():
                self.stats[k] += v
        if self._inflight is not None and self._inflight[0] is out_dev:
            self._inflight = None
        self.pool.release(self._deferred_free)
        self._deferred_free = []
        self.pool.commit_evictable()
        for x in self._further:
            x.settle()
        now = _clock.now()
        for idx, s, req, kind, m, drafts in snap:
            if kind == "mid":
                continue
            if req.aborted:
                # aborted after dispatch: its tokens are junk
                self.stats["waste_overrun_slot_tokens"] += (
                    m if kind == "dec" else 1)
                continue
            if kind == "fin":
                # the prefill-final row's own output IS the first token
                # (one program: no cross-program patching needed)
                tok = int(toks[idx, m - 1] if self.spec_k else toks[idx, 0])
                if len(req.out_tokens) < req.max_new_tokens:
                    req.out_tokens.append(tok)
                    if req.constraint is not None:
                        req.constraint.advance(tok)
                    self.stats["decode_active_tokens"] += 1
                else:
                    self.stats["waste_overrun_slot_tokens"] += 1
                if req.t_first is None:
                    req.t_first = now
                    _obs.lifecycle(req.rid, "first-token",
                                   engine=self.engine_id)
                if self.slots[s] is req:
                    self.cur_tok[s] = tok
                    self._finish_if_done(s, defer_free=True)
            elif self.spec_k:
                # greedy-verify: draft j survives iff it equals the pick
                # after the tokens before it — the accepted stream is
                # exactly the one-token-at-a-time stream
                o = [int(t) for t in toks[idx, :m]]
                a = 1
                while a < m and drafts[a - 1] == o[a - 1]:
                    a += 1
                take = min(a, req.max_new_tokens - len(req.out_tokens))
                req.out_tokens.extend(o[:take])
                if req.t_first is None and take:
                    req.t_first = now
                    _obs.lifecycle(req.rid, "first-token",
                                   engine=self.engine_id)
                self.stats["decode_active_tokens"] += take
                self.stats["waste_spec_rejected_slot_tokens"] += m - a
                self.stats["waste_overrun_slot_tokens"] += a - take
                self.stats["spec_proposed_tokens"] += m - 1
                self.stats["spec_accepted_tokens"] += a - 1
                if self.slots[s] is req:
                    # seq_lens advances by the ACCEPTED count only — a
                    # rejected draft's k/v sits past seq_lens, is masked
                    # for every later query, and is overwritten by the
                    # next row's own tokens before it could be attended
                    self.seq_lens[s] += take
                    if take:
                        self.cur_tok[s] = o[take - 1]
                    self._finish_if_done(s, defer_free=True)
            else:
                tok = int(toks[idx, 0])
                if len(req.out_tokens) < req.max_new_tokens:
                    req.out_tokens.append(tok)
                    if req.constraint is not None:
                        req.constraint.advance(tok)
                    self.stats["decode_active_tokens"] += 1
                else:
                    self.stats["waste_overrun_slot_tokens"] += 1
                if self.slots[s] is req:
                    self.cur_tok[s] = tok
                    self._finish_if_done(s, defer_free=True)
            if (self.slots[s] is not req
                    and len(req.out_tokens) >= req.max_new_tokens
                    and req.t_done is None):
                # predictively released at dispatch: the slot may already
                # belong to a newer request; only the completion time
                # remains to record
                req.t_done = now
                _obs.lifecycle(req.rid, "done", engine=self.engine_id)

    # -- KV page migration (inference/fleet/) -----------------------------
    #
    # A KV page is a pure function of (params, token prefix, page size,
    # quant mode, adapter digest) — the exact argument that makes the
    # prefix cache sound — so a page's bytes shipped from a donor engine
    # equal what the adopter would compute itself, and a victim request
    # resumed through adopted pages emits the same stream as an
    # uninterrupted run. The wire format ("shipment") is a dict:
    #
    #   version=2, rid, page_size, kv_quant, dtype, geom=(L, nKV, dH)
    #   hashes  [n]  cumulative prefix-chain hashes (adapter-salted)
    #   k       [n, L, nKV, dH, bs]   page-major contiguous payload
    #   v       [n, L, nKV, bs, dH]
    #   k_scales/v_scales [n, L, nKV] fp32 (int8 payload only, else None)
    #   crc     [n]  crc32 over each page's k+v(+scale) bytes
    #   -- v2 additive fields (v1 shipments lack them and still adopt):
    #   quant_mode  "int8" | "fp"  — the PAYLOAD's representation; a
    #               mismatched adopter converts at the edge instead of
    #               rejecting (fp->int8 one-shot absmax quantization,
    #               int8->fp the kernels' own fp32 dequant multiply)
    #   tokens  [n*bs] int32 prefix tokens — lets a cross-mode adopter
    #               re-key the pages under ITS hash preimage (the cache
    #               tags int8 content, so hashes don't transfer)
    #   salt    adapter-digest hash salt (b"" when no LoRA adapter)
    #   staged  True while the payload is still an in-flight async
    #               device->host copy (wire_overlap donors; crc=None
    #               until finalize_shipment materializes host bytes)
    #
    # Adoption is two-phase so the page ledger stays exact while bytes
    # are in transit: begin_adopt allocates + stages (ledger class
    # ``in_flight``), commit_adopt publishes into the prefix cache at
    # refcount 0 (idle-cached — the victim's normal re-admission lookup
    # increfs and splices them into its block table) and either scatters
    # the device arrays immediately (sync wire) or defers the scatter to
    # the next dispatch as one batched between-programs write
    # (wire_overlap), abort_adopt returns staged pages to the free list.
    #
    # The wire still knows the k/v geometry and the int8 format
    # (ROADMAP D1): it reads ``_kv_quant`` and finds the int8 model's
    # two side planes under the names below.

    def _side_plane(name):
        def put(self, plane):
            self.side_planes[name] = plane
        return property(lambda self: self.side_planes.get(name), put)

    k_scales, v_scales = _side_plane("k_scales"), _side_plane("v_scales")
    del _side_plane

    def _export_meta(self, rid: int):
        """Shared export-prefix computation: the slot serving ``rid``,
        its hashes, and the page ids covering the exportable prefix —
        tokens both (a) known to the host (prompt + harvested out_tokens
        — a chained in-flight token's KV exists but its value doesn't)
        and (b) dispatched into the pool (``seq_lens`` / ``_prefilling``
        advance at dispatch). None for unknown/queued rids or when no
        full page is covered."""
        self._require("page_shipment")
        for slot in range(self.B):
            req = self.slots[slot]
            if req is not None and req.rid == rid:
                break
        else:
            return None
        full = (np.concatenate([np.asarray(req.prompt, np.int32),
                                np.asarray(req.out_tokens, np.int32)])
                if req.out_tokens else np.asarray(req.prompt, np.int32))
        written = (self._prefilling[slot] if slot in self._prefilling
                   else int(self.seq_lens[slot]))
        known = min(written, len(full))
        n_exp = known // self.bs
        if n_exp <= 0:
            return None
        tokens = np.ascontiguousarray(full[:n_exp * self.bs], np.int32)
        salt = self._cache_salt(req)
        hashes = self._page_hashes(tokens, salt)
        # the export's OWN copy of the page ids: the overlapped wire
        # hands them to a gather that runs asynchronously, and the slot's
        # row of _full_rows is zeroed as soon as the slot is released —
        # a view (jnp.asarray may alias an aligned host buffer) would
        # let a late gather read page 0 for every page
        pg = np.array(self._full_rows[slot][:n_exp], np.int32)
        return slot, tokens, salt, hashes, pg

    def _shipment_header(self, rid: int, tokens, salt, hashes) -> dict:
        cfg = self.cfg
        return {"version": 2, "rid": rid, "page_size": self.bs,
                "kv_quant": self._kv_quant,
                "quant_mode": "int8" if self._kv_quant else "fp",
                "dtype": str(self.k_pages.dtype),
                "geom": (cfg.n_layers, cfg.n_kv_heads, cfg.head_dim),
                "hashes": hashes, "tokens": tokens, "salt": salt}

    def export_request_pages(self, rid: int) -> Optional[dict]:
        """Serialize the full KV pages (+ scale planes) a resident
        request has written, for adoption by another engine — the
        synchronous wire: reading the donated page arrays below blocks
        on any in-flight program. Returns None for unknown/queued rids
        or when no full page is covered."""
        if self._commit_pending:
            self._flush_commits()
        meta = self._export_meta(rid)
        if meta is None:
            return None
        _slot, tokens, salt, hashes, pg = meta
        n_exp = len(pg)
        # page-major contiguous payload; np.asarray syncs with in-flight
        # programs, so every dispatched position is actually on host
        k = np.ascontiguousarray(np.moveaxis(
            np.asarray(self.k_pages[:, pg]), 1, 0))
        v = np.ascontiguousarray(np.moveaxis(
            np.asarray(self.v_pages[:, pg]), 1, 0))
        ks = vs = None
        if self._kv_quant:
            ks = np.ascontiguousarray(np.moveaxis(
                np.asarray(self.k_scales[:, pg]), 1, 0))
            vs = np.ascontiguousarray(np.moveaxis(
                np.asarray(self.v_scales[:, pg]), 1, 0))
        crc = [zlib.crc32(k[j].tobytes() + v[j].tobytes()
                          + (ks[j].tobytes() + vs[j].tobytes()
                             if self._kv_quant else b""))
               for j in range(n_exp)]
        out = self._shipment_header(rid, tokens, salt, hashes)
        out.update({"k": k, "v": v, "k_scales": ks, "v_scales": vs,
                    "crc": crc})
        return out

    def stage_request_pages(self, rid: int) -> Optional[dict]:
        """Overlapped-wire export (``wire_overlap``): snapshot the
        request's pages into a staging buffer CHAINED after the
        in-flight program — an on-device gather plus one async
        device->host copy per shipment — and return immediately with
        ``staged=True`` / ``crc=None``. The donor's compute chain never
        blocks; ``finalize_shipment`` (router drain time) materializes
        host bytes and crcs. Safe against the donor's own page reuse:
        the gather is dispatched before the slot's pages return to the
        free list, and any later program writing them serializes after
        it through the donated page arrays."""
        if self._commit_pending:
            self._flush_commits()
        meta = self._export_meta(rid)
        if meta is None:
            return None
        _slot, tokens, salt, hashes, pg = meta
        pgd = jnp.asarray(pg, jnp.int32)
        k = wire_gather_pages(self.k_pages, pgd)
        v = wire_gather_pages(self.v_pages, pgd)
        ks = vs = None
        if self._kv_quant:
            ks = wire_gather_pages(self.k_scales, pgd)
            vs = wire_gather_pages(self.v_scales, pgd)
        for a in (k, v, ks, vs):
            # start the device->host transfer now, without blocking:
            # by finalize time the bytes are (usually) already resident
            if a is not None and hasattr(a, "copy_to_host_async"):
                a.copy_to_host_async()
        out = self._shipment_header(rid, tokens, salt, hashes)
        out.update({"k": k, "v": v, "k_scales": ks, "v_scales": vs,
                    "crc": None, "staged": True})
        return out

    def finalize_shipment(self, shipment: Optional[dict]) -> Optional[dict]:
        """Materialize a staged shipment's host bytes + per-page crcs
        (the router calls this when draining the outbox — the only
        place the staging buffer is read). Chaos point
        ``migration.stage``: ``drop`` loses the staging buffer (the
        shipment is gone; the request falls back to re-prefill),
        ``corrupt`` flips a payload byte AFTER the crcs are computed,
        so the adopter's crc check rejects the page. Pass-through for
        non-staged (sync-wire) shipments."""
        if not shipment or not shipment.get("staged"):
            return shipment
        t0 = _clock.now()
        quant = shipment["k_scales"] is not None
        k = np.ascontiguousarray(np.asarray(shipment["k"]))
        v = np.ascontiguousarray(np.asarray(shipment["v"]))
        ks = vs = None
        if quant:
            ks = np.ascontiguousarray(np.asarray(shipment["k_scales"]))
            vs = np.ascontiguousarray(np.asarray(shipment["v_scales"]))
        crc = [zlib.crc32(k[j].tobytes() + v[j].tobytes()
                          + (ks[j].tobytes() + vs[j].tobytes()
                             if quant else b""))
               for j in range(len(shipment["hashes"]))]
        shipment.update({"k": k, "v": v, "k_scales": ks, "v_scales": vs,
                         "crc": crc, "staged": False})
        self.stats["wire_export_ms"] += (_clock.now() - t0) * 1e3
        _obs.instant("wire.finalize", engine=self.engine_id,
                     rid=shipment.get("rid"),
                     pages=len(shipment.get("hashes", [])))
        if _chaos.active():
            ctx = {"engine": self.engine_id}
            if self.pool_role is not None:
                ctx["pool"] = self.pool_role
            spec = _chaos.fire("migration.stage", ctx=ctx)
            if spec is not None:
                if spec.kind == "drop":
                    return None
                if spec.kind == "corrupt":
                    # np.asarray of a device array is read-only: copy
                    # before flipping so the mutation sticks (and
                    # persists across redelivery retries)
                    k = np.array(k, copy=True)
                    k.reshape(-1).view(np.uint8)[0] ^= 0xFF
                    shipment["k"] = k
        return shipment

    @staticmethod
    def shipment_bytes(shipment: dict) -> int:
        """Wire bytes of a shipment's page payload (int8 pages ship 4x
        cheaper than bf16x2 — the EQuARX argument applied to KV)."""
        n = shipment["k"].nbytes + shipment["v"].nbytes
        if shipment["k_scales"] is not None:
            n += shipment["k_scales"].nbytes + shipment["v_scales"].nbytes
        return int(n)

    def _shipment_quant_mode(self, shipment: dict) -> str:
        """The PAYLOAD representation of a shipment: v2 carries it
        explicitly; v1 predates mixed-mode wires, so its ``kv_quant``
        bool is authoritative."""
        qm = shipment.get("quant_mode")
        if qm is not None:
            return qm
        return "int8" if shipment.get("kv_quant") else "fp"

    def shipment_cache_hashes(self, shipment: dict) -> Optional[list]:
        """The hashes a shipment's pages occupy in THIS pool's cache
        keyspace. Same-mode shipments transfer their hashes verbatim;
        a cross-mode shipment is re-keyed from its token prefix (the
        preimage tags the quant mode, so int8 and fp content never
        alias). None when re-keying is impossible (v1 cross-mode) —
        callers must then treat nothing as cached."""
        want = "int8" if self._kv_quant else "fp"
        if self._shipment_quant_mode(shipment) == want:
            return list(shipment["hashes"])
        toks = shipment.get("tokens")
        if toks is None:
            return None
        return self._page_hashes(
            np.asarray(toks, np.int32),
            shipment.get("salt", b""))[:len(shipment["hashes"])]

    def _convert_shipment(self, shipment: dict) -> Optional[dict]:
        """fp<->int8 edge conversion for a mixed-mode wire: re-express
        a v2 shipment's payload in THIS pool's representation and
        re-key its hashes from the shipped token prefix. fp->int8 is a
        one-shot per-page/per-kv-head absmax quantization — with
        page-aligned prefill chunks that is byte-identical to what the
        int8 engine's own running-absmax write path would have stored;
        int8->fp applies the kernels' exact dequant (fp32 multiply,
        cast). Crcs are checked against the ORIGINAL payload first and
        the conversion truncates at the first bad page — a corrupt
        shipment must not be laundered into a freshly-crc'd one.
        Returns None when the shipment cannot be re-keyed (v1: no
        token prefix on the wire)."""
        toks = shipment.get("tokens")
        if toks is None:
            return None
        from ..ops.quant import SCALE_EPS

        src_q = self._shipment_quant_mode(shipment) == "int8"
        k, v = shipment["k"], shipment["v"]
        ks, vs = shipment["k_scales"], shipment["v_scales"]
        n_ok = 0
        for j in range(len(shipment["hashes"])):
            if zlib.crc32(k[j].tobytes() + v[j].tobytes()
                          + (ks[j].tobytes() + vs[j].tobytes()
                             if src_q else b"")) != shipment["crc"][j]:
                break     # corrupt: pages past j can't extend the chain
            n_ok += 1
        tokens = np.asarray(toks, np.int32)[:n_ok * self.bs]
        hashes = self._page_hashes(tokens, shipment.get("salt", b""))
        dt = self.k_pages.dtype
        if src_q:
            # int8 payload -> fp pool: q * scale in fp32 (exactly what
            # both attention arms compute), cast to the pool dtype
            kc = (k[:n_ok].astype(np.float32)
                  * ks[:n_ok, :, :, None, None]).astype(dt)
            vc = (v[:n_ok].astype(np.float32)
                  * vs[:n_ok, :, :, None, None]).astype(dt)
            ksc = vsc = None
        else:
            # fp payload -> int8 pool: one-shot absmax over each page's
            # [dH, bs] tail dims per (page, layer, kv-head). The STORED
            # scale is the raw absmax/127 (the engine's running plane is
            # never clamped — only the quantizing divide is, exactly as
            # quantize_to_scale does), so a page written in one aligned
            # chunk converts byte-identically to what the int8 engine's
            # own write path stores.
            kf = np.asarray(k[:n_ok], np.float32)
            vf = np.asarray(v[:n_ok], np.float32)
            ksc = (np.abs(kf).max(axis=(3, 4))
                   / np.float32(127.0)).astype(np.float32)
            vsc = (np.abs(vf).max(axis=(3, 4))
                   / np.float32(127.0)).astype(np.float32)
            kc = np.clip(np.round(
                kf / np.maximum(ksc, SCALE_EPS)[:, :, :, None, None]),
                -127, 127).astype(np.int8)
            vc = np.clip(np.round(
                vf / np.maximum(vsc, SCALE_EPS)[:, :, :, None, None]),
                -127, 127).astype(np.int8)
        crc = [zlib.crc32(kc[j].tobytes() + vc[j].tobytes()
                          + (ksc[j].tobytes() + vsc[j].tobytes()
                             if ksc is not None else b""))
               for j in range(n_ok)]
        out = dict(shipment)
        out.update({"kv_quant": self._kv_quant,
                    "quant_mode": "int8" if self._kv_quant else "fp",
                    "dtype": str(dt), "hashes": hashes, "tokens": tokens,
                    "k": kc, "v": vc, "k_scales": ksc, "v_scales": vsc,
                    "crc": crc})
        return out

    def begin_adopt(self, shipment: dict) -> Optional[dict]:
        """Phase 1 of adoption: validate the shipment against this
        pool's geometry (ValueError on mismatch — shipments only move
        between replicas of one model; a mismatched QUANT MODE on a v2
        shipment converts at the edge instead), drop pages whose crc
        fails or whose hash is already resident, allocate pool pages
        for the rest, and stage them (ledger class ``in_flight``).
        Returns the staging handle, or None when nothing is adoptable
        (all cached, crc-dead at page 0, allocation failure, or an
        armed ``migration.adopt`` fault)."""
        self._require("page_shipment")
        cfg = self.cfg
        if (shipment.get("version") not in (1, 2)
                or shipment["page_size"] != self.bs
                or tuple(shipment["geom"]) != (cfg.n_layers,
                                               cfg.n_kv_heads,
                                               cfg.head_dim)):
            raise ValueError(
                f"shipment geometry {shipment.get('page_size')}/"
                f"{shipment.get('dtype')}/{shipment.get('geom')} does "
                f"not match this pool ({self.bs}/{self.k_pages.dtype}/"
                f"{(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim)})")
        want = "int8" if self._kv_quant else "fp"
        if self._shipment_quant_mode(shipment) != want:
            conv = self._convert_shipment(shipment)
            if conv is None:
                raise ValueError(
                    f"shipment quant mode "
                    f"{self._shipment_quant_mode(shipment)} does not "
                    f"match this pool ({want}) and carries no token "
                    f"prefix to re-key from (wire v1)")
            shipment = conv
        elif shipment["dtype"] != str(self.k_pages.dtype):
            raise ValueError(
                f"shipment dtype {shipment['dtype']} does not match "
                f"this pool ({self.k_pages.dtype})")
        if _chaos.active():
            spec = _chaos.fire("migration.adopt",
                               ctx={"engine": self.engine_id})
            if spec is not None and spec.kind == "fail":
                return None
        k, v = shipment["k"], shipment["v"]
        ks, vs = shipment["k_scales"], shipment["v_scales"]
        staged: list[tuple[int, int]] = []     # (shipment idx, pool page)
        for j, h in enumerate(shipment["hashes"]):
            if zlib.crc32(k[j].tobytes() + v[j].tobytes()
                          + (ks[j].tobytes() + vs[j].tobytes()
                             if self._kv_quant else b"")) \
                    != shipment["crc"][j]:
                break     # corrupt: pages past j can't extend the chain
            if h in self.pool.cache:
                continue  # already resident here; chain stays contiguous
            pages = self._alloc_pages(1)
            if pages is None:
                break     # adopter full: keep the prefix we could stage
            staged.append((j, pages[0]))
        if not staged:
            return None
        handle = {"shipment": shipment, "staged": staged}
        self._adopting.append(handle)
        return handle

    def commit_adopt(self, handle: dict) -> int:
        """Phase 2: publish the staged pages in the prefix cache at
        refcount 0 — idle-cached, exactly where a page a finished
        request offered would sit, so the victim's re-admission lookup
        (and anyone sharing the prefix) increfs them from there — and
        write their bytes into the device pool: immediately on the
        synchronous wire (one batched scatter per array, chained after
        any in-flight program's donated output), or deferred to the
        next dispatch under ``wire_overlap`` (_flush_commits folds all
        pending commits into ONE between-programs scatter, so adoption
        never serializes behind the in-flight chain). Chaos point
        ``migration.commit`` (kind ``raise``) fires before any state
        moves — abort_adopt still rolls the staging back leak-free.
        Returns the number of pages adopted."""
        if _chaos.active():
            ctx = {"engine": self.engine_id}
            if self.pool_role is not None:
                ctx["pool"] = self.pool_role
            spec = _chaos.fire("migration.commit", ctx=ctx)
            if spec is not None and spec.kind == "raise":
                raise _chaos.ChaosInjected(
                    f"chaos: engine {self.engine_id} commit failure")
        self._adopting.remove(handle)
        shipment, staged = handle["shipment"], handle["staged"]
        idx = [j for j, _ in staged]
        pages = [p for _, p in staged]
        with _obs.span("wire.commit", engine=self.engine_id,
                       rid=shipment.get("rid"), pages=len(pages)):
            return self._commit_adopt_impl(shipment, staged, idx, pages)

    def _commit_adopt_impl(self, shipment: dict, staged: list,
                           idx: list, pages: list) -> int:
        if self._wire_overlap:
            self._commit_pending.append({
                "pages": pages,
                "hashes": [shipment["hashes"][j] for j in idx],
                "k": np.moveaxis(shipment["k"][idx], 0, 1),
                "v": np.moveaxis(shipment["v"][idx], 0, 1),
                "ks": (np.moveaxis(shipment["k_scales"][idx], 0, 1)
                       if self._kv_quant else None),
                "vs": (np.moveaxis(shipment["v_scales"][idx], 0, 1)
                       if self._kv_quant else None),
            })
        else:
            pg = jnp.asarray(pages, jnp.int32)
            dt = self.k_pages.dtype
            self.k_pages = wire_scatter_pages(
                self.k_pages, pg,
                jnp.asarray(np.moveaxis(shipment["k"][idx], 0, 1), dt))
            self.v_pages = wire_scatter_pages(
                self.v_pages, pg,
                jnp.asarray(np.moveaxis(shipment["v"][idx], 0, 1), dt))
            if self._kv_quant:
                self.k_scales = wire_scatter_pages(
                    self.k_scales, pg,
                    jnp.asarray(np.moveaxis(shipment["k_scales"][idx],
                                            0, 1), jnp.float32))
                self.v_scales = wire_scatter_pages(
                    self.v_scales, pg,
                    jnp.asarray(np.moveaxis(shipment["v_scales"][idx],
                                            0, 1), jnp.float32))
        for (j, p) in staged:
            self.pool.insert(shipment["hashes"][j], p)
        # drop the insert refcount: the pages idle in the cache until a
        # lookup claims them. They settle to evictable at the next
        # harvest/idle commit like any other pending page.
        self.pool.decref(pages)
        if shipment.get("rid") is not None:
            _obs.lifecycle(shipment["rid"], "adopt",
                           engine=self.engine_id, pages=len(pages))
        return len(pages)

    def _flush_commits(self) -> None:
        """Apply all deferred adoption commits (``wire_overlap``) as one
        batched scatter per page array. Runs between programs — at
        dispatch entry, before any program could attend the pages, and
        at export entry, before their bytes could re-ship. A pending
        page whose cache entry no longer matches its commit hash was
        evicted (and possibly re-allocated) since the commit: writing
        it now would clobber the new tenant's bytes — and, under
        kv_quant, its freshly-zeroed scale plane — so it is skipped."""
        pend, self._commit_pending = self._commit_pending, []
        pages: list[int] = []
        karrs, varrs, ksarrs, vsarrs = [], [], [], []
        for ent in pend:
            keep = [i for i, (p, h) in enumerate(zip(ent["pages"],
                                                     ent["hashes"]))
                    if self.pool.hash_of.get(p) == h]
            if not keep:
                continue
            pages += [ent["pages"][i] for i in keep]
            karrs.append(ent["k"][:, keep])
            varrs.append(ent["v"][:, keep])
            if ent["ks"] is not None:
                ksarrs.append(ent["ks"][:, keep])
                vsarrs.append(ent["vs"][:, keep])
        if not pages:
            return
        pg = jnp.asarray(pages, jnp.int32)
        dt = self.k_pages.dtype
        self.k_pages = wire_scatter_pages(
            self.k_pages, pg, jnp.asarray(np.concatenate(karrs, axis=1), dt))
        self.v_pages = wire_scatter_pages(
            self.v_pages, pg, jnp.asarray(np.concatenate(varrs, axis=1), dt))
        if self._kv_quant:
            self.k_scales = wire_scatter_pages(
                self.k_scales, pg,
                jnp.asarray(np.concatenate(ksarrs, axis=1), jnp.float32))
            self.v_scales = wire_scatter_pages(
                self.v_scales, pg,
                jnp.asarray(np.concatenate(vsarrs, axis=1), jnp.float32))

    def abort_adopt(self, handle: dict) -> None:
        """Roll back a staged adoption: pages return to the free list
        untouched (nothing was published, nothing dispatched could have
        referenced them)."""
        self._adopting.remove(handle)
        self.pool.release([p for _, p in handle["staged"]])

    def adopt_pages(self, shipment: dict) -> int:
        """begin_adopt + commit_adopt in one call (the router's path);
        returns pages adopted (0 when nothing was adoptable). A commit
        that raises (chaos ``migration.commit``) aborts the staging
        leak-free and reports 0 — the wire treats it as a rejection
        and the request falls back to retry/re-prefill."""
        handle = self.begin_adopt(shipment)
        if handle is None:
            return 0
        try:
            return self.commit_adopt(handle)
        except Exception:
            self.abort_adopt(handle)
            return 0

    def kv_bytes_per_page(self, cls: int = 0) -> float:
        """HBM bytes one KV page of cache class ``cls`` costs across the
        class's layers (all layers, for a model with one class),
        including the page's share of the side planes. The capacity
        argument for serving_kv_quant: at a fixed page-pool byte budget
        the pool holds bytes_bf16/bytes_int8 ~ 2x the pages, hence ~2x
        the concurrent sequences."""
        c = self.classes[cls]
        return float(c.spec.page_bytes(c.n_layers))

    def kv_bytes_per_token(self, cls: int = 0) -> float:
        """Amortized KV bytes per cached token of class ``cls`` (page
        bytes / page size)."""
        return self.kv_bytes_per_page(cls) / self.bs

    def page_accounting(self) -> dict:
        """Page census for the leak invariant: every non-sink page is in
        exactly one of free / slot-owned / slot-shared (refcounted cache
        mappings, deduplicated) / idle-cached (refcount 0, pending or
        evictable) / deferred-free / adapter (resident LoRA weights) /
        in-flight (migration pages staged by begin_adopt, not yet
        committed or rolled back); the counts sum to n_pages - 1 —
        per engine, and therefore fleet-wide by summation. These are
        class 0's pages; an engine with further cache classes adds
        ``classes``: each class's own ledger by name (free / slot-owned /
        slot-shared / idle-cached / deferred-free, summing to its
        pool's pages less the sink)."""
        owned = [p for lst in self._slot_owned for p in lst]
        shared = {p for lst in self._slot_shared for p in lst}
        cache_idle = [p for p, r in self.pool.ref.items() if r == 0]
        counts = {
            "free": len(self.pool.free),
            "slot_owned": len(owned),
            "slot_shared": len(shared),
            "cache_idle": len(cache_idle),
            "deferred_free": len(self._deferred_free),
            "adapter": (self.adapters.n_pages_held()
                        if self.adapters is not None else 0),
            "in_flight": sum(len(h["staged"]) for h in self._adopting),
        }
        counts["total"] = sum(counts.values())
        if self._further:
            counts["classes"] = {
                self.classes[0].name: dict(counts),
                **{x.cls.name: x.accounting() for x in self._further}}
        return counts

    def run(self, requests: list[Request]) -> dict:
        """Drive all requests to completion against wall-clock arrivals;
        returns throughput + p50/p99 latency stats, the slot-occupancy
        decomposition, speculative-decode counters, and prefix-cache
        counters."""
        for r in sorted(requests, key=lambda r: r.arrival):
            self.submit(r)
        self.stats = {k: 0 for k in self.stats}   # per-run counters
        hits0, misses0 = self.pool.hits, self.pool.misses
        t0 = _clock.now()
        while (any(s is not None for s in self.slots) or self.queue
               or self._inflight is not None):
            self.step(now=_clock.now() - t0)
            if not any(s is not None for s in self.slots) \
                    and self._inflight is None and self.queue:
                # nothing active and next arrival is in the future (or
                # admission is transiently pool-blocked): sleep, don't
                # busy-spin — floor keeps the pool-blocked case off 100%
                # CPU (submit() rejects requests that can NEVER fit)
                nxt = min(r.arrival for r in self.queue)
                wait = max(0.0, nxt - (_clock.now() - t0))
                time.sleep(min(max(wait, 0.001), 0.05))
        wall = _clock.now() - t0
        if (self._deferred_free or self.pool.pending_evict
                or self._further):
            # nothing is in flight after the drive loop: settle deferred
            # frees (e.g. a final-step abort) so page_accounting sees
            # steady state
            self.pool.release(self._deferred_free)
            self._deferred_free = []
            self.pool.commit_evictable()
            for x in self._further:
                x.settle()
        done = [r for r in requests if not r.aborted]
        lat = [r.t_done - (t0 + r.arrival) for r in done
               if r.t_done is not None]
        ttft = [r.t_first - (t0 + r.arrival) for r in done
                if r.t_first is not None]
        total_new = sum(len(r.out_tokens) for r in requests)
        hits = self.pool.hits - hits0
        misses = self.pool.misses - misses0
        st = self.stats
        slot_tok = max(1, st["decode_slot_tokens"])
        q = lambda xs, p: float(np.percentile(np.asarray(xs), p)) \
            if xs else 0.0
        return {
            "n_requests": len(requests),
            "total_new_tokens": total_new,
            "wall_s": round(wall, 3),
            "throughput_tok_s": round(total_new / wall, 1),
            "latency_p50_s": round(q(lat, 50), 3),
            "latency_p99_s": round(q(lat, 99), 3),
            "ttft_p50_s": round(q(ttft, 50), 3),
            "ttft_p99_s": round(q(ttft, 99), 3),
            "slot_occupancy": round(
                st["decode_active_tokens"] / slot_tok, 3),
            # occupancy decomposition: fractions of slot-tokens lost per
            # cause (active + these six == 1)
            "occ_waste_queue_empty": round(
                st["waste_queue_empty_slot_tokens"] / slot_tok, 3),
            "occ_waste_admission_blocked": round(
                st["waste_admission_blocked_slot_tokens"] / slot_tok, 3),
            "occ_waste_prefill": round(
                st["waste_prefill_slot_tokens"] / slot_tok, 3),
            "occ_waste_overrun": round(
                st["waste_overrun_slot_tokens"] / slot_tok, 3),
            "occ_waste_spec_rejected": round(
                st["waste_spec_rejected_slot_tokens"] / slot_tok, 3),
            "occ_waste_preempted": round(
                st["waste_preempted_slot_tokens"] / slot_tok, 3),
            "preemption_rate": round(
                st["preemptions"] / max(1, len(requests)), 3),
            "spec_accept_rate": round(
                st["spec_accepted_tokens"]
                / st["spec_proposed_tokens"], 3)
            if st["spec_proposed_tokens"] else 0.0,
            "prefill_padding_frac": round(
                1.0 - st["prefill_tokens"]
                / max(1, st["prefill_grid_tokens"]), 3),
            "prefix_cache_hit_rate": round(
                hits / (hits + misses), 3) if hits + misses else 0.0,
            "prefix_cache_hits": hits,
            "prefix_cache_misses": misses,
            **(self.adapters.stats() if self.adapters is not None else {}),
            **st,
        }
