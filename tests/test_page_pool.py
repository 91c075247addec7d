"""`serving.page_pool.PagePool` on its own: a bare `threading.Condition`,
a `PrefixCache`, a `LeaseTable` — no net, no compiled program, no
scheduler thread. The ledger the engine's `stats()["pages_in_use"]`
reads must return to zero whatever path gave the pages back."""
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from deeplearning4j_tpu.serving.kv_transfer import LeaseTable
from deeplearning4j_tpu.serving.page_pool import PagePool
from deeplearning4j_tpu.serving.prefix_cache import PrefixCache

PAGE = 4


def _pool(pool_pages=8, cache=None, leases=None, n_pages_max=6):
    cond = threading.Condition()
    if cache is not None:
        cache.bind_guard(cond)
    # every prompt prefills at the next multiple of 8: wider than a page
    pool = PagePool(cond, n_slots=2, page_size=PAGE, pool_pages=pool_pages,
                    n_pages_max=n_pages_max,
                    prefill_width=lambda t0: -(-t0 // 8) * 8,
                    prefix_cache=cache, leases=leases)
    return cond, pool


def _holder():
    """What the pool asks of a request or a lease."""
    return SimpleNamespace(pages=None, n_shared=0, nodes=None)


def _admit(cond, pool, prompt, n_tokens, tenant=None):
    """The scheduler's admission of one request, as `_admit` does it."""
    req = _holder()
    with cond:
        nodes = [] if pool.prefix_cache is None else \
            pool.prefix_cache.lookup(prompt, tenant=tenant)
        need = pool.pages_for(len(prompt), n_tokens)
        if nodes:
            need = pool.pages_for_hit(len(prompt), n_tokens) - len(nodes)
        if not pool.make_room_locked(need, nodes):
            return None
        if nodes:
            pool.pin_locked(nodes)
            req.nodes, req.n_shared = nodes, len(nodes)
        req.pages = pool.take_locked(need, nodes)
    return req


def test_pages_for_is_the_wider_of_the_padded_prefill_and_the_span():
    _, pool = _pool()
    assert pool.pages_for(3, 2) == 2        # padded to 8 positions
    assert pool.pages_for(3, 11) == 4       # 3 + 11 - 1 = 13 positions
    assert pool.pages_for_hit(9, 4) == 3    # 12 positions, no padding
    assert pool.pages_for_hit(9, 4) <= pool.pages_for(9, 4)


def test_reserve_and_release_return_the_ledger_to_zero():
    cond, pool = _pool()
    assert (pool.in_use(), pool.n_free(), pool.in_use_peak) == (0, 8, 0)
    a = _admit(cond, pool, np.arange(5), 4)
    b = _admit(cond, pool, np.arange(9), 8)
    assert len(a.pages) == 2 and len(b.pages) == 4
    assert set(a.pages).isdisjoint(b.pages) and 0 not in a.pages + b.pages
    assert (pool.in_use(), pool.in_use_peak) == (6, 6)
    with cond:
        pool.release_locked(a)
        pool.release_locked(b)
        pool.release_locked(b)              # a second release frees nothing
    assert a.pages is None and (pool.in_use(), pool.n_free()) == (0, 8)
    assert pool.in_use_peak == 6            # the peak stays


def test_never_fits_is_refused_and_must_wait_is_not():
    cond, pool = _pool(pool_pages=4)
    assert not pool.can_hold(pool.pages_for(9, 12))     # 5 pages of 4
    assert pool.can_hold(pool.pages_for(9, 8))
    first = _admit(cond, pool, np.arange(9), 8)         # takes all 4
    assert _admit(cond, pool, np.arange(3), 2) is None  # waits: 2 > 0 free
    assert pool.in_use() == 4                           # and took nothing
    with cond:
        pool.release_locked(first)
    assert _admit(cond, pool, np.arange(3), 2) is not None


def test_a_prefix_hit_shares_pages_and_release_frees_only_unshared_ones():
    cond, pool = _pool(cache=PrefixCache(PAGE))
    prompt = np.arange(10)                  # two full pages + a tail
    a = _admit(cond, pool, prompt, 3)
    with cond:
        pool.promote_locked(a, prompt, None)
    assert a.n_shared == 2 and len(a.nodes) == 2
    b = _admit(cond, pool, prompt, 3)       # the same prefix: a hit
    assert b.pages[:2] == a.pages[:2] and b.n_shared == 2
    assert [n.requests for n in b.nodes] == [2, 2]
    assert (len(a.pages), len(b.pages), pool.in_use()) == (4, 3, 5)
    with cond:
        pool.release_locked(a)
    # a's two unshared pages came back; the two cached ones b reads did not
    assert pool.in_use() == 3
    assert [n.requests for n in b.nodes] == [1, 1]
    with cond:
        pool.release_locked(b)
    # refcount zero, still resident in the cache until reclaimed
    assert pool.in_use() == 2 and pool.prefix_cache.cached_pages == 2
    with cond:
        assert pool.make_room_locked(8, [])         # reclaims both
    assert (pool.in_use(), pool.prefix_cache.cached_pages) == (0, 0)


def test_pinning_a_chain_that_is_not_resident_takes_no_reference():
    cond, pool = _pool(cache=PrefixCache(PAGE))
    prompt = np.arange(9)
    a = _admit(cond, pool, prompt, 2)
    with cond:
        pool.promote_locked(a, prompt, None)
        assert pool.pin_prefix_locked(prompt, None, 3) is None
        assert [n.requests for n in a.nodes] == [1, 1]
        pinned = pool.pin_prefix_locked(prompt, None, 2)
        assert [n.requests for n in pinned] == [2, 2]
        assert pool.pin_prefix_locked(prompt, "other-tenant", 1) is None


def test_a_max_pages_eviction_returns_its_page_to_the_free_list():
    cond, pool = _pool(cache=PrefixCache(PAGE, max_pages=1))
    a = _admit(cond, pool, np.arange(5), 2)
    with cond:
        pool.promote_locked(a, np.arange(5), None)
        pool.release_locked(a)
    assert (pool.in_use(), pool.prefix_cache.cached_pages) == (1, 1)
    b = _admit(cond, pool, 100 + np.arange(5), 2)
    with cond:
        pool.promote_locked(b, 100 + np.arange(5), None)   # evicts a's page
        pool.release_locked(b)
    assert pool.prefix_cache.evictions == 1
    assert (pool.in_use(), pool.prefix_cache.cached_pages) == (1, 1)


def test_reset_voids_lease_ownership_and_keeps_payloads_fetchable():
    leases = LeaseTable(ttl=30.0)
    cond, pool = _pool(cache=PrefixCache(PAGE), leases=leases)
    a = _admit(cond, pool, np.arange(9), 2)
    pool.bind_row(1, a.pages)
    assert list(np.asarray(pool.page_table[1])[:4]) == a.pages
    with cond:
        pool.promote_locked(a, np.arange(9), None)
        lease = leases.grant({"handoff_id": "h1", "blob": b"kv"},
                             pages=a.pages, n_shared=a.n_shared,
                             nodes=a.nodes)
        a.pages = a.nodes = None            # ownership moved to the lease
    assert pool.in_use() == 4
    pool.reset()
    assert (pool.in_use(), pool.n_free()) == (0, 8)
    assert pool.prefix_cache.cached_pages == 0
    assert not np.asarray(pool.page_table).any()
    assert lease.pages is None and lease.nodes is None
    with cond:
        assert leases.touch("h1").payload["blob"] == b"kv"
        pool.release_locked(leases.resolve("h1"))   # frees nothing twice
    assert pool.n_free() == 8


def test_the_pool_wants_its_lock():
    cond, pool = _pool()
    with pytest.raises(AssertionError, match="requires holding"):
        pool.take_locked(1, [])
