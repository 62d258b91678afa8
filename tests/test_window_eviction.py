"""The order in which a windowed cache class evicts its idle cached pages
(inference/serving.py: _WindowPool, _ClassPages.keep_from).

A hit claims in a windowed class only the blocks a query at its end can
read, at most ``claims`` of them, so of a released prompt of ``L`` full
pages only the blocks from ``L - 2 * claims`` on (its *tails*) can serve
a hit that ends within one window of the prompt's last full page; the
rest are *interior*.  The class evicts every interior page before any
tail, least recently released first within each tier, while class 0
keeps its pure LRU.  The pool tests need no model; the engine tests serve
a document, push more pages than the window pool holds through its idle
set, and ask a second question over the document, on the tiny MiMo and
command-a twins against their references."""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import harness
from paddle_tpu.inference.serving import (Request, _ClassPages, _PagePool,
                                          _WindowPool)
from paddle_tpu.models.seam import CacheClass, CachePlane, CacheSpec

import test_cohere_moe
import test_mimo_v2


def _window_class(window, bs, n_pages=64, max_blocks=600):
    spec = CacheSpec((CachePlane("k", (1, 1), 1), CachePlane("v", (1, 1), 1)),
                     jnp.float32)
    return _ClassPages(CacheClass("window", 1, spec, window), n_pages, B=3,
                       max_blocks=max_blocks, cache_limit=0, bs=bs)


def _prompt(x, slot, name, L):
    """A request in ``slot`` whose prompt has ``L`` full pages, each
    prefilled here and offered to the cache (as the engine's dispatch
    does); returns its pages by block."""
    x.keep_from[slot] = L - 2 * x.claims
    pages = x.alloc(L)
    for b, p in enumerate(pages):
        assert x.pool.insert(b"%s.%d" % (name, b), p)
        x.shared[slot][b] = p
    return pages


def _evict_all(pool):
    order = []
    while pool.evictable:
        first = set(pool.evictable)
        assert pool.evict(1) == 1
        order += first - set(pool.evictable)
    return order


def test_interior_pages_go_before_any_tail():
    """Two prompts of 10 pages at claims 1: the second's interior pages
    leave before the first's tails, although those are older."""
    x = _window_class(128, 128)
    assert x.claims == 1
    a = _prompt(x, 0, b"a", 10)
    x.release_slot(0, defer=False)
    b = _prompt(x, 1, b"b", 10)
    x.release_slot(1, defer=False)
    assert set(x.pool.interior) == set(a[:8] + b[:8])
    assert x.pool.evict(16) == 16
    assert set(x.pool.evictable) == set(a[8:] + b[8:])
    assert not x.pool.interior
    assert x.pool.evicted == {"interior": 16, "tail": 0}
    # with no interior page left, a tail has to go: the oldest
    assert x.pool.evict(1) == 1 and a[8] not in x.pool.evictable
    assert x.pool.evicted == {"interior": 16, "tail": 1}


def test_least_recently_released_first_within_each_tier():
    """Blocks let go behind the window as the prompt grows, then the rest
    at teardown, interleaved with a second prompt's: each tier leaves in
    the order its pages were released."""
    x = _window_class(128, 128)
    a = _prompt(x, 0, b"a", 6)
    b = _prompt(x, 1, b"b", 6)
    released = []
    for blk in range(3):                 # behind the window, a before b
        for slot, pages in ((0, a), (1, b)):
            assert x.release_block(slot, blk)
            released.append(pages[blk])
    x.settle()
    x.release_slot(1, defer=False)       # then b's rest, then a's
    x.release_slot(0, defer=False)
    released += b[3:] + a[3:]
    interior = [a[0], b[0], a[1], b[1], a[2], b[2], b[3], a[3]]
    tails = [b[4], b[5], a[4], a[5]]
    assert released == interior[:7] + tails[:2] + [a[3]] + tails[2:]
    assert list(x.pool.interior) == interior
    assert _evict_all(x.pool) == interior + tails
    assert x.pool.evicted == {"interior": 8, "tail": 4}


def test_a_claimed_tail_released_again_goes_to_the_young_end():
    """A hit claims a's last page and its request lets it go as a tail:
    of the tails it is now the last to leave."""
    x = _window_class(128, 128)
    a = _prompt(x, 0, b"a", 4)
    x.release_slot(0, defer=False)
    b = _prompt(x, 1, b"b", 4)
    x.release_slot(1, defer=False)
    assert x.pool.lookup([b"a.3"]) == [a[3]]
    assert a[3] not in x.pool.evictable
    x.shared[2][3] = a[3]                # a hit of 4 pages: block 3
    x.keep_from[2] = 5 - 2 * x.claims    # of a prompt of 5 full pages
    x.release_slot(2, defer=False)
    assert a[3] not in x.pool.interior
    assert _evict_all(x.pool) == a[:2] + b[:2] + [a[2], b[2], b[3], a[3]]


def test_an_interior_page_claimed_again_is_classed_by_its_new_release():
    """A page that was interior in one prompt is a tail of a shorter one
    that hits it, and the other way round; a pending page claimed before
    its harvest is classed by its next release too."""
    x = _window_class(128, 128)
    a = _prompt(x, 0, b"a", 8)
    x.release_slot(0, defer=True)        # pending, not yet evictable
    assert x.pool.lookup([b"a.2"]) == [a[2]]
    x.shared[1][2] = a[2]
    x.keep_from[1] = 4 - 2 * x.claims    # block 2 is a tail of 4 pages
    x.settle()
    assert a[2] not in x.pool.evictable
    x.release_slot(1, defer=False)
    assert a[2] in x.pool.evictable and a[2] not in x.pool.interior
    assert x.pool.lookup([b"a.7"]) == [a[7]]
    x.shared[2][7] = a[7]
    x.keep_from[2] = 20 - 2 * x.claims   # block 7 is interior of 20
    x.release_slot(2, defer=False)
    assert a[7] in x.pool.interior
    assert next(iter(x.pool.interior)) != a[7]       # at the young end


@pytest.mark.parametrize("window,bs,claims", [
    (128, 128, 1), (4096, 128, 32), (12, 4, 3), (8, 4, 2)],
    ids=["mimo", "command-a", "cohere-toy", "mimo-toy"])
def test_the_tail_starts_two_claims_before_the_prompts_last_full_page(
        window, bs, claims):
    """``claims`` is what a hit ending deep in a prompt claims in the
    class; every block from ``L - 2 * claims`` on is a tail."""
    x = _window_class(window, bs, n_pages=200)
    assert x.claims == claims
    n = 100
    assert n - x.cls.live_from(n * bs) // bs == claims
    L = 3 * claims + 5
    pages = _prompt(x, 0, b"p", L)
    x.release_slot(0, defer=False)
    assert list(x.pool.interior) == pages[:L - 2 * claims]
    assert set(x.pool.evictable) - set(x.pool.interior) == set(
        pages[L - 2 * claims:])


def test_a_prompt_within_two_claims_is_all_tail():
    x = _window_class(4096, 128)
    _prompt(x, 0, b"p", 50)
    x.release_slot(0, defer=False)
    assert not x.pool.interior and len(x.pool.evictable) == 50


def test_class_0_evicts_in_pure_lru_order():
    """The engine's own pool (and the state class's snapshot pool) keeps
    the order it always had: the least recently released first, whatever
    the page's place in its prompt."""
    pool = _PagePool(16)
    assert not isinstance(pool, _WindowPool)
    pages = pool.alloc(12)
    for i, p in enumerate(pages):
        assert pool.insert(b"h%d" % i, p)
    order = pages[6:] + pages[:3] + pages[3:6][::-1]
    for p in order:
        pool.decref([p])
    pool.commit_evictable()
    assert list(pool.evictable) == order
    assert pool.lookup([b"h7"]) == [pages[7]]
    pool.decref([pages[7]])
    pool.commit_evictable()
    want = [p for p in order if p != pages[7]] + [pages[7]]
    assert _evict_all(pool) == want


# -- the engine: the second question over a document hits it whole ----------

def _family(name):
    if name == "mimo":
        return test_mimo_v2, test_mimo_v2._model()
    return test_cohere_moe, test_cohere_moe._model((2, 2))


@pytest.mark.parametrize("family", ["mimo", "cohere"])
@pytest.mark.parametrize("question", [3, 6], ids=["short", "one-to-two-pages"])
def test_a_documents_tail_outlives_other_prompts(family, question):
    """A document of 48 tokens and a first question of ``question``
    tokens (shorter than a page of 4, or between one and two pages: the
    document's last page then lies at depth 1 from the prompt's end);
    then six prompts of 100 tokens push 150 pages through a window pool
    of 99; then a second question over the document hits all 12 of its
    pages, with nothing lost to the window, and its answer matches the
    reference."""
    m, model = _family(family)
    key = harness.seed_key(31)
    eng, _ = m._engine(model, key, max_batch=2, n_pages=256,
                       class_pages={"window": 100})
    x, watch = eng._extra[0], m._Watch(eng)
    assert type(eng.pool) is _PagePool
    inserted = []
    offer = x.pool.insert
    x.pool.insert = lambda h, p: offer(h, p) and not inserted.append(p)
    doc = m._tokens(50, 1, 48)[0]
    ask = lambda rid, n: Request(                       # noqa: E731
        rid=rid, prompt=np.concatenate([doc, m._tokens(60 + rid, 1, n)[0]]),
        max_new_tokens=4)
    first = ask(0, question)
    watch.serve(first)
    assert eng.stats["prefill_cached_tokens"] == 0
    others = [Request(rid=10 + i, prompt=m._tokens(70 + i, 1, 100)[0],
                      max_new_tokens=2) for i in range(6)]
    watch.serve(*others)
    assert len(inserted) > x.n_pages - 1
    assert eng.stats["window_pages_evicted_interior"] > 0
    assert eng.stats["window_pages_evicted_tail"] == 0
    # the counters count the pages that left the cache
    assert (eng.stats["window_pages_evicted_interior"]
            == len(inserted) - len(x.pool.cache))
    assert eng.stats["prefill_cached_tokens"] == 0
    second = ask(1, 5)
    watch.serve(second)
    assert eng.stats["prefill_cached_tokens"] == len(doc)
    assert eng.stats["prefill_window_lost_tokens"] == 0
    for req in (first, second):
        assert len(req.out_tokens) == req.max_new_tokens
        assert watch.worst(model, key, req) < m.TOL
    test_cohere_moe._assert_ledger(x)
