"""`PagePool` with its second class of page, the window blocks' rings,
on its own (a bare `threading.Condition`, no net, no program): a request
holds `min(pages_for, ring_pages)` of them, taken and returned with its
other pages under the same lock; `reset` restores both free lists;
`can_hold` and `make_room_locked` see both classes; and a pool without
rings is the one-class pool, table for table."""
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from deeplearning4j_tpu.serving.page_pool import PagePool

PAGE, RING, SLOTS = 4, 3, 2


def _pool(pool_pages=12, ring_pages=RING, n_slots=SLOTS):
    cond = threading.Condition()
    pool = PagePool(cond, n_slots=n_slots, page_size=PAGE,
                    pool_pages=pool_pages, n_pages_max=8,
                    prefill_width=lambda t0: -(-t0 // 8) * 8,
                    ring_pages=ring_pages)
    return cond, pool


def _holder():
    return SimpleNamespace(pages=None, n_shared=0, nodes=None, ring=None)


def _admit(cond, pool, t0, n_tokens):
    req = _holder()
    with cond:
        need = pool.pages_for(t0, n_tokens)
        if not pool.make_room_locked(need, []):
            return None
        req.pages = pool.take_locked(need, [])
        req.ring = pool.take_ring_locked(len(req.pages))
    return req


@pytest.mark.parametrize("t0,n,pages", [(3, 2, 2), (3, 11, 4), (9, 17, 7)])
def test_admission_takes_the_lesser_of_the_requests_pages_and_a_ring(
        t0, n, pages):
    cond, pool = _pool()
    req = _admit(cond, pool, t0, n)
    assert len(req.pages) == pages
    assert len(req.ring) == min(pages, RING) == pool.ring_for(pages)
    assert pool.in_use() == pages and pool.ring_in_use() == len(req.ring)
    assert set(req.ring) <= set(range(1, SLOTS * RING + 1))
    assert pool.ring_in_use_peak == len(req.ring)


@pytest.mark.parametrize("how", ["retirement", "expiry", "failure"])
def test_every_way_out_returns_the_ring_with_the_pages(how):
    """Retirement, expiry and failure all end in one `release_locked`."""
    cond, pool = _pool()
    a, b = _admit(cond, pool, 9, 9), _admit(cond, pool, 3, 2)
    assert pool.ring_in_use() == RING + 2
    with cond:
        pool.release_locked(a)
    assert a.ring is None and a.pages is None
    assert pool.ring_in_use() == 2 and pool.in_use() == 2
    with cond:
        pool.release_locked(b)
        pool.release_locked(b)     # once more is harmless
    assert pool.ring_in_use() == 0 == pool.in_use()
    assert sorted(pool._free_ring) == list(range(1, SLOTS * RING + 1))
    assert pool.ring_in_use_peak == RING + 2


def test_a_lease_without_a_ring_is_released_as_ever():
    cond, pool = _pool()
    lease = SimpleNamespace(pages=[1, 2], n_shared=0, nodes=None)
    with cond:
        pool._free_pages.remove(1), pool._free_pages.remove(2)
        pool.release_locked(lease)
    assert pool.in_use() == 0 and pool.ring_in_use() == 0


def test_can_hold_and_make_room_see_both_classes():
    cond, pool = _pool(pool_pages=12)
    assert pool.can_hold(12) and not pool.can_hold(13)
    none = _pool(ring_pages=RING, n_slots=0)[1]   # no ring provisioned
    assert not none.can_hold(1)
    held = [_admit(cond, pool, 3, 2) for _ in range(SLOTS)]   # 2 + 2 of 6
    assert pool.ring_in_use() == 4 and pool.n_free() == 8
    with cond:
        # the whole-context class has 8 pages free; the rings have 2
        assert pool.make_room_locked(2, [])
        assert not pool.make_room_locked(3, [])
        assert pool.make_room_locked(3, []) is False
        pool.release_locked(held[0])
        assert pool.make_room_locked(3, [])
    # and the first class alone can block too
    cond, pool = _pool(pool_pages=3)
    with cond:
        assert not pool.make_room_locked(4, [])


def test_reset_restores_both_free_lists_and_both_tables():
    cond, pool = _pool()
    req = _admit(cond, pool, 9, 9)
    pool.bind_row(1, req.pages, req.ring)
    assert np.asarray(pool.ring_table)[1].tolist() == req.ring
    assert np.asarray(pool.page_table)[1, :len(req.pages)].tolist() \
        == req.pages
    pool.reset()
    assert pool.in_use() == 0 == pool.ring_in_use()
    assert not np.asarray(pool.ring_table).any()
    assert not np.asarray(pool.page_table).any()
    assert sorted(pool._free_ring) == list(range(1, SLOTS * RING + 1))


def test_a_short_request_binds_a_short_ring():
    cond, pool = _pool()
    req = _admit(cond, pool, 3, 2)        # two pages: two ring entries
    pool.bind_row(0, req.pages, req.ring)
    row = np.asarray(pool.ring_table)[0]
    assert row[:2].tolist() == req.ring and row[2] == 0


def test_write_ids_lays_logical_pages_round_the_ring():
    """Logical page j at entry j % R; pad pages past the prompt's last
    go to the trash page; a span longer than the ring names every entry
    more than once, in order, so its last pages stay."""
    cond, pool = _pool(pool_pages=12)
    req = _admit(cond, pool, 27, 2)       # bucket 32: 8 pages, a ring of 3
    full, ring = pool.write_ids(req, 0, 8, upto=(27 - 1) // PAGE)
    assert np.asarray(full).tolist() == req.pages[:8]
    r = req.ring
    assert np.asarray(ring).tolist() == [r[0], r[1], r[2], r[0], r[1],
                                         r[2], r[0], 0]
    # a chunk of two pages from logical page 4 on
    full, ring = pool.write_ids(req, 4, 2, upto=6)
    assert np.asarray(full).tolist() == req.pages[4:6]
    assert np.asarray(ring).tolist() == [r[1], r[2]]
    tables, rows = pool.tables, pool.rows(1)
    assert tables[0] is pool.page_table and tables[1] is pool.ring_table
    assert rows[0].shape == (8,) and rows[1].shape == (RING,)


def test_a_pool_without_rings_is_the_one_class_pool():
    cond, pool = _pool(ring_pages=0)
    assert pool.ring_table is None and pool.ring_pool_pages == 0
    assert pool.tables is pool.page_table
    assert pool.rows(0).shape == (8,)
    req = _admit(cond, pool, 9, 9)
    assert req.ring == [] and pool.ring_for(7) == 0
    assert pool.ring_in_use() == 0 == pool.ring_in_use_peak
    ids = pool.write_ids(req, 0, 2, upto=2)
    assert np.asarray(ids).tolist() == req.pages[:2]
    pool.bind_row(0, req.pages, req.ring)
    with cond:
        pool.release_locked(req)
    pool.reset()
    assert pool.ring_table is None


def test_the_ring_takers_want_the_lock():
    _, pool = _pool()
    with pytest.raises(AssertionError):
        pool.take_ring_locked(2)
