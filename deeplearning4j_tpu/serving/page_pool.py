"""The decode engine's KV pages: what a page is, and who owns it.

Every block's K/V pools (`serving/block_state.KVPages`) are cut into
`pool_pages` pages of `page_size` positions, plus page 0, a reserved
trash page that absorbs masked writes from inactive slots. `PagePool`
is the one owner of page ids, in TWO classes where the net has blocks
that read a window of their context only:

- the pages of blocks that read their whole context (K/V and latent
  pools), ids 1..pool_pages, held by a request's LENGTH: everything
  below, as it always was;
- the pages of window blocks (`block_state.WindowPages`, pools of their
  own of `n_slots * ring_pages` pages + the trash page), ids
  1..n_slots * ring_pages, of which a request holds `min(pages_for,
  ring_pages)` whatever its length: a RING, logical page `j` at entry
  `j % ring_pages` of the slot's row in a second device table
  `ring_table` `(n_slots, ring_pages)`. They are taken and returned with
  the request's other pages, under the same lock (`take_ring_locked`,
  `release_locked`), and never mid-request; the class cannot run short
  while a slot is free, since every slot's whole ring is provisioned.
  `ring_pages` 0 (a net without window blocks): none of this exists, and
  the pool, its one table and what the programs are handed (`tables`,
  `rows`, `write_ids`) are the one-class pool's.

Of the first class it owns:

- the **free list** of page ids nobody holds;
- the device **page table** `(n_slots, n_pages_max)`: row `s` lists, in
  position order, the pages slot `s` reads and writes through;
- **holders**: a page off the free list belongs to exactly one holder,
  a request (from admission to retirement) or a handoff lease
  (`kv_transfer.LeaseTable`, from export to commit / abort / expiry).
  A holder is anything with `pages` (its page ids, shared prefix
  first), `n_shared` (how many leading pages the prefix cache owns)
  and `nodes` (the cache nodes it holds a reference on);
- the **prefix cache's** refcounts (`serving/prefix_cache.PrefixCache`):
  pages promoted into the cache are owned by it, shared read-only by
  every holder that references their node, and come back to the free
  list only through LRU reclaim, a `max_pages` eviction or `reset`.

Memory-side admission: a request needs `pages_for(t0, n_tokens)` pages
(its padded prefill width or its prompt + output span, whichever is
larger). Pages are taken at ADMISSION (queued requests hold none) and
returned on retirement, expiry or failure, so slots-per-chip is bound by
actual request lengths, not by `max_len` per slot. A request that can
never fit (`can_hold`) is refused at the door; one that does not fit
now waits (`make_room_locked` is False) for a retirement.

The pool knows no tenant, deadline or queue. It is NOT self-locking:
the owning engine's condition guards every `*_locked` method (asserted
under tests), the same lock that guards the queue and the slots, so
taking pages is atomic with what the scheduler does around it. The
page table is written by the scheduler thread alone.
"""
from __future__ import annotations

from typing import Callable, List, Optional

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.util.concurrency import assert_owned


class PagePool:
    """See the module docstring. `prefill_width(t0)` is the padded
    width the scheduler prefills a `t0`-token prompt at (its bucket or
    a whole number of chunks); `leases` the engine's handoff
    `LeaseTable`, whose page ownership `reset` voids."""

    def __init__(self, cond, *, n_slots: int, page_size: int,
                 pool_pages: int, n_pages_max: int,
                 prefill_width: Callable[[int], int],
                 prefix_cache=None, leases=None, recorder=None,
                 ring_pages: int = 0):
        self._cond = cond
        self.n_slots = n_slots
        self.page_size = page_size
        self.pool_pages = pool_pages
        self.n_pages_max = n_pages_max
        self.prefix_cache = prefix_cache
        self._prefill_width = prefill_width
        self._leases = leases
        self._recorder = recorder
        self._free_pages = list(range(pool_pages, 0, -1))  # guarded by: _cond
        self.in_use_peak = 0  # guarded by: _cond
        # scheduler-thread-owned, like the pools
        self.page_table = jnp.zeros((n_slots, n_pages_max), jnp.int32)
        # the second class: every slot's ring of window pages
        self.ring_pages = ring_pages
        self.ring_pool_pages = n_slots * ring_pages
        self._free_ring = list(range(self.ring_pool_pages, 0, -1))  # guarded by: _cond
        self.ring_in_use_peak = 0  # guarded by: _cond
        self.ring_table = jnp.zeros((n_slots, ring_pages), jnp.int32) \
            if ring_pages else None

    # -- arithmetic --------------------------------------------------------
    def pages_for(self, t0: int, n_tokens: int) -> int:
        """Pages a request must hold: its padded prefill width (pad-
        tail KV lands in owned pages) or prompt+output KV span,
        whichever is larger. The last generated token is never written
        back, hence n_tokens - 1. This is the COLD cost — reservations
        and queue demand always use it, so a cache hit can only shrink
        the allocation at admission, never under-reserve."""
        span = max(self._prefill_width(t0), t0 + n_tokens - 1)
        return -(-span // self.page_size)

    def pages_for_hit(self, t0: int, n_tokens: int) -> int:
        """Total LOGICAL pages of a prefix-hit request (shared + owned):
        the hit path suffix-prefills in chunks whose padded tail never
        runs past page·ceil(t0/page), so the span is just the KV the
        request actually writes — always <= the cold `pages_for`."""
        return -(-(t0 + n_tokens - 1) // self.page_size)

    def ring_for(self, n_pages: int) -> int:
        """Window pages a request of `n_pages` logical pages holds: all
        of them, or a whole ring (0 where no block reads a window)."""
        return min(n_pages, self.ring_pages)

    def can_hold(self, n_pages: int) -> bool:
        """False: no retirement can ever make room for this many, in
        either class."""
        return n_pages <= self.pool_pages \
            and self.ring_for(n_pages) <= self.ring_pool_pages

    def in_use(self) -> int:
        return self.pool_pages - len(self._free_pages)

    def ring_in_use(self) -> int:
        return self.ring_pool_pages - len(self._free_ring)

    def n_free(self) -> int:
        return len(self._free_pages)

    # -- taking and returning pages ----------------------------------------
    def make_room_locked(self, need: int, pinned: list) -> bool:
        """Whether `need` pages are free, after releasing idle cached
        pages (LRU, leaf-first) if they are not: caching never shrinks
        effective capacity. `pinned` — the caller's own hit chain — is
        held across the reclaim so it cannot eat it. The request's
        `need + len(pinned)` logical pages must also find their window
        pages (`ring_for`) free."""
        assert_owned(self._cond, "PagePool.make_room_locked")
        if self.ring_for(need + len(pinned)) > len(self._free_ring):
            return False
        short = need - len(self._free_pages)
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.acquire(pinned)
            try:
                reclaimed = self.prefix_cache.reclaim(short)
            finally:
                self.prefix_cache.release(pinned)
            self._free_pages.extend(reclaimed)
            if reclaimed and self._recorder is not None:
                self._recorder.event(
                    "page-reclaim", pages=len(reclaimed),
                    free_after=len(self._free_pages))
        return need <= len(self._free_pages)

    def take_locked(self, need: int, nodes: list) -> List[int]:
        """The page list of a request admitted on the cached chain
        `nodes` (already referenced by the caller: `pin_locked`) plus
        `need` fresh pages. `make_room_locked(need, ...)` was True
        under this same hold of the lock, or a retirement since."""
        assert_owned(self._cond, "PagePool.take_locked")
        pages = [n.page_id for n in nodes] + \
            [self._free_pages.pop() for _ in range(need)]
        self.in_use_peak = max(self.in_use_peak, self.in_use())
        return pages

    def take_ring_locked(self, n_pages: int) -> List[int]:
        """The window pages of a request just admitted with `n_pages`
        logical pages (`take_locked`'s list), under the same hold of the
        lock: its ring, in entry order; [] where no block reads a
        window."""
        assert_owned(self._cond, "PagePool.take_ring_locked")
        ring = [self._free_ring.pop() for _ in range(self.ring_for(n_pages))]
        self.ring_in_use_peak = max(self.ring_in_use_peak,
                                    self.ring_in_use())
        return ring

    def release_locked(self, holder) -> None:
        """Drop a holder's page references: owned pages return to the
        free list; shared (cached) pages only lose this holder's
        refcount — the cache keeps them resident until LRU reclaim, and
        a prefix another slot still shares is never freed here; its
        window pages (`holder.ring`, where it has any) return to theirs.
        Once per request (retirement, expiry, failure) and once per
        lease (commit, abort, expiry)."""
        assert_owned(self._cond, "PagePool.release_locked")
        if getattr(holder, "ring", None):
            self._free_ring.extend(holder.ring)
            holder.ring = None
        if holder.nodes:
            self.prefix_cache.release(holder.nodes)
            holder.nodes = None
        if holder.pages:
            self._free_pages.extend(holder.pages[holder.n_shared:])
        holder.pages = None

    # -- the prefix cache's references -------------------------------------
    def pin_locked(self, nodes: list) -> None:
        """One more reference on each cached node."""
        assert_owned(self._cond, "PagePool.pin_locked")
        self.prefix_cache.acquire(nodes)

    def unpin_locked(self, nodes: list) -> None:
        assert_owned(self._cond, "PagePool.unpin_locked")
        self.prefix_cache.release(nodes)

    def pin_prefix_locked(self, prompt, tenant: Optional[str],
                          n_pages: int) -> Optional[list]:
        """Reference the first `n_pages` cached pages of `prompt`'s
        chain and return their nodes; None (nothing referenced) when
        the chain is not resident that deep."""
        assert_owned(self._cond, "PagePool.pin_prefix_locked")
        have = [] if self.prefix_cache is None else \
            self.prefix_cache.match(prompt, tenant=tenant)
        if len(have) < n_pages:
            return None
        have = have[:n_pages]
        self.prefix_cache.acquire(have)
        return have

    def promote_locked(self, holder, prompt, tenant: Optional[str],
                       n_pages: Optional[int] = None) -> None:
        """Publish the pages of `holder` that `prompt` fully covers
        (its first `n_pages`, or all of them) into the prefix cache so
        the NEXT same-prefix request shares them; the holder keeps
        using them, page ownership moves to the cache, refcounted.
        Pages evicted to respect the cache's `max_pages` cap go
        straight back to the free list — a cap-driven eviction must
        never leak."""
        assert_owned(self._cond, "PagePool.promote_locked")
        if self.prefix_cache is None or holder.pages is None:
            return
        pages = holder.pages if n_pages is None \
            else holder.pages[:n_pages]
        holder.nodes, freed = self.prefix_cache.insert(
            prompt, pages, holder.nodes or [], tenant=tenant)
        holder.n_shared = len(holder.nodes)
        self._free_pages.extend(freed)

    # -- the device page table ---------------------------------------------
    # graftlint: hot-loop
    def bind_row(self, slot: int, pages: List[int], ring=()) -> None:
        """Slot `slot` reads and writes through `pages` from now on
        (scheduler thread), and its window blocks through the ring
        `ring`."""
        row = np.zeros((self.n_pages_max,), np.int32)
        row[:len(pages)] = pages
        self.page_table = self.page_table.at[slot].set(jnp.asarray(row))
        if self.ring_pages:
            row = np.zeros((self.ring_pages,), np.int32)
            row[:len(ring)] = ring
            self.ring_table = self.ring_table.at[slot].set(jnp.asarray(row))

    # what the compiled programs are handed: one array each for a pool of
    # one class, as ever; a pair (whole-context class, ring) for two
    @property
    def tables(self):
        """The `page_table` argument of a decode dispatch."""
        return self.page_table if not self.ring_pages \
            else (self.page_table, self.ring_table)

    def rows(self, slot: int):
        """The `page_row` argument of a prefill chunk."""
        return self.page_table[slot] if not self.ring_pages \
            else (self.page_table[slot], self.ring_table[slot])

    # graftlint: hot-loop
    def write_ids(self, holder, first: int, count: int, upto: int):
        """The `wpids` argument of a prefill or a prefill chunk that
        writes `count` pages from logical page `first` on, of a prompt
        whose last position lies in logical page `upto`: the holder's
        pages; and of its ring, logical page `j` at entry `j %
        ring_pages`, the pad pages past `upto` redirected to the trash
        page (in a ring they would land on pages the window still
        reads). A span longer than the ring writes in order, so its last
        pages stay."""
        ids = jnp.asarray(np.asarray(holder.pages[first:first + count],
                                     np.int32))
        if not self.ring_pages:
            return ids
        ring = [holder.ring[j % self.ring_pages] if j <= upto else 0
                for j in range(first, first + count)]
        return ids, jnp.asarray(np.asarray(ring, np.int32))

    def reset(self) -> None:
        """The pools were rebuilt (construction, weight swap, recovery
        after a failed donated dispatch) and no slot holds a request:
        the free list is whole again, every cached page id is stale,
        and leased page ids index into pools that vanished — their
        ownership is void, but payloads stay fetchable (a receiver
        mid-resume holds host copies and must still be able to
        finish). Queued requests keep their reservations: they hold no
        pages."""
        self.page_table = jnp.zeros((self.n_slots, self.n_pages_max),
                                    jnp.int32)
        if self.ring_pages:
            self.ring_table = jnp.zeros((self.n_slots, self.ring_pages),
                                        jnp.int32)
        # the free list is read by submit()/stats() on caller threads:
        # publish the rebuilt state under the lock
        with self._cond:
            self._free_pages = list(range(self.pool_pages, 0, -1))
            self._free_ring = list(range(self.ring_pool_pages, 0, -1))
            if self.prefix_cache is not None:
                self.prefix_cache.clear()
            if self._leases is not None:
                self._leases.invalidate_pages()
