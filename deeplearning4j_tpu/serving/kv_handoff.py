"""The decode engine's KV hand-off plane: everything that moves KV pages
between engines, beside the scheduler that runs the three serve cells.

`HandoffPlane` is the engine's collaborator for

- **leased handoffs** (`serving/kv_transfer.py`): a slot's decode state
  or a prefix chain's pages serialized into a payload the sender keeps
  under a TTL lease until the receiver commits, aborts or vanishes
  (`export_slot`, `export_cold`, `fetch_handoff*`, `commit_handoff`,
  `abort_handoff`, the lease sweep);
- **live migration and disaggregated roles**: `migrate_slots` arms a
  one-shot pass in which the scheduler exports everything in flight;
  `resume_submit` / `resume_generate` admit a fetched payload, whose
  shipped pages re-bind at admission (`import_into`);
- **the cluster-global prefix cache** (`serving/prefix_directory.py`):
  `bind_prefix_directory`, the holder-side `export_prefix` (queued by
  RPC threads, served by the scheduler thread between dispatches), and
  the submit-thread fetch with its single-flight state
  (`fetch_prefix_for`), bound at admission (`bind_prefix_import`).

None of it runs unless a directory is bound, a role is split or a
migration is asked for: `step()` returns at once, `pending()` is False.
A net with per-slot recurrent state refuses all of it, typed
(`RecurrentStateUnsupported`): pages are moved, states are not.

The plane holds no engine. It is given the engine's condition (the one
lock that guards the queue, the slots, the page pool and the leases, so
a lease's pages change owner atomically with the free list), the
`PagePool` of the current build, and four callables of the scheduler:

    read_slot(slot, pages)  -> (registers, blocks, n_pages): the pool
        pages as host arrays, and a slot's position / last token / PRNG
        key / temperature (scheduler thread only: under donation every
        dispatch replaces the buffers);
    write_slot(slot, pages, blocks, registers): the reverse;
    enqueue_resumed(payload, timeout, on_token) -> request: the
        scheduler's door for a verified payload;
    in_flight() -> int: queued + in-slot requests.

Requests reach it duck-typed (`prompt`, `tokens`, `pages`, `nodes`, ...):
it never builds one. Fault discipline is `kv_transfer`'s: every wire
failure is a typed `KVTransferError` on the caller that can act on it,
and the fetch path degrades to the cold prefill the request would have
run anyway.
"""
from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Callable, Optional

import numpy as np

from deeplearning4j_tpu.serving import kv_transfer
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.kv_transfer import KVTransferError
from deeplearning4j_tpu.serving.model_server import (
    ServerClosedError,
    ServingError,
)
from deeplearning4j_tpu.util.concurrency import assert_owned

logger = logging.getLogger("deeplearning4j_tpu")


class HandoffPlane:
    """See the module docstring."""

    def __init__(self, cond, *, role: str, handoff_ttl: float, recorder,
                 breaker, read_slot: Callable, write_slot: Callable,
                 enqueue_resumed: Callable, in_flight: Callable):
        self._cond = cond
        self.role = role
        self.recorder = recorder
        self.breaker = breaker
        self._read_slot = read_slot
        self._write_slot = write_slot
        self._enqueue_resumed = enqueue_resumed
        self._in_flight = in_flight
        # sender-side lease ledger; the pool voids its page ownership
        # when the pools are rebuilt
        self.leases = kv_transfer.LeaseTable(ttl=handoff_ttl)
        self._closed = False  # guarded by: _cond
        self._migrate_all = False  # guarded by: _cond
        # what the current build serves (`on_rebuild`)
        self._pool = None
        self._weight_version: Optional[str] = None
        self._kv_quant: Optional[str] = None
        self._page_size = 0
        self._max_len = 0
        self._n_blocks = 0
        self._kv_only = True
        # KV migration counters: slots exported under lease / imported
        # and resumed, lease resolutions, and outbound KV wire bytes
        self.migrations_out = 0  # guarded by: _cond
        self.migrations_in = 0  # guarded by: _cond
        self.handoffs_committed = 0  # guarded by: _cond
        self.handoffs_aborted = 0  # guarded by: _cond
        self.handoffs_expired = 0  # guarded by: _cond
        self.kv_transfer_bytes = 0  # guarded by: _cond
        # cluster prefix tier (`bind_prefix_directory`): the directory,
        # this engine's holder id, and the peers resolver are all None
        # until bound — every cluster path is a no-op without them
        self._directory = None
        self._holder_id: Optional[str] = None
        self._peers = None  # holder_id -> peer handle, or None
        self._fetch_frame_pages = 8
        self._fetch_timeout = 5.0
        self._min_fetch_pages = 1
        # scheduler-serviced prefix export queue: RPC threads park an
        # export request here and wait; the scheduler thread — the only
        # thread allowed to touch device pools under donation — fills
        # it between dispatches
        # guarded by: _cond
        self._exports: collections.deque = collections.deque()
        # single-flight: chains with a cluster fetch in progress, so a
        # burst of same-prefix admits pulls the pages over the wire
        # ONCE — the rest wait and re-check the local cache
        # guarded by: _cond
        self._fetching: set = set()
        # fetched bundles still riding the queue toward the cache
        # (bound at ADMISSION, not at submit): waiters share the
        # winner's bundle instead of re-fetching; TTL'd by the fetch
        # timeout, duplicate binds dropped by admission's stale-check
        # guarded by: _cond
        self._fetch_ready: dict = {}
        self.prefix_fetches = 0  # guarded by: _cond
        self.prefix_fetch_fallbacks = 0  # guarded by: _cond
        self.prefix_fetch_bytes = 0  # guarded by: _cond
        self.prefix_fetch_seconds = 0.0  # guarded by: _cond
        self.prefix_exports_served = 0  # guarded by: _cond
        self.cluster_prefix_hit_tokens = 0  # guarded by: _cond

    # -- what the scheduler tells the plane --------------------------------
    def on_rebuild(self, *, pool, weight_version: str,
                   kv_quant: Optional[str], max_len: int, n_blocks: int,
                   kv_only: bool) -> None:
        """The engine was (re)built: a new pool and prefix cache, maybe
        new weights and geometry. A rebuild keeps the engine's cluster
        membership: the fresh cache re-publishes under the NEW weight
        version as it warms (old entries age out / were dropped)."""
        self._pool = pool
        self._weight_version = weight_version
        self._kv_quant = kv_quant
        self._page_size = pool.page_size
        self._max_len = max_len
        self._n_blocks = n_blocks
        self._kv_only = kv_only
        if self._directory is not None and pool.prefix_cache is not None:
            pool.prefix_cache.bind_directory(self._directory,
                                             self._holder_id)

    def close_locked(self) -> None:
        """The engine stopped admitting: refuse new exports and
        migrations typed instead of parking them for a scheduler that
        is about to exit."""
        assert_owned(self._cond, "HandoffPlane.close_locked")
        self._closed = True

    def pending(self) -> bool:
        """Work that must wake an idle scheduler: an armed migration
        pass, an expired lease to sweep, a peer waiting on an export."""
        return self._migrate_all or self.leases.expired_pending() \
            or bool(self._exports)

    def fail_all(self, err: BaseException) -> None:
        """Release every parked `export_prefix` waiter with `err` — a
        scheduler exiting (shutdown/kill) or failing must not leave RPC
        threads blocked until their timeout."""
        assert_owned(self._cond, "HandoffPlane.fail_all")
        while self._exports:
            item = self._exports.popleft()
            item["error"] = err
            item["done"].set()

    # graftlint: hot-loop
    def step(self) -> bool:
        """The plane's share of one scheduler iteration (scheduler
        thread, under `housekeeping`): serve parked prefix exports,
        sweep expired leases. True, once, when `migrate_slots()` armed
        the migrate-everything pass: the scheduler then exports what it
        has in flight."""
        if self._exports:
            self._serve_prefix_exports()
        if len(self.leases):
            self._sweep_leases()
        if not self._migrate_all:
            return False
        with self._cond:
            self._migrate_all = False
        return True

    def stats(self) -> dict:
        """The plane's counters under their `DecodeEngine.stats()`
        names."""
        assert_owned(self._cond, "HandoffPlane.stats")
        return {
            # slots exported under lease / imported, lease
            # resolutions, live leases, wire bytes
            "migrations_out": self.migrations_out,
            "migrations_in": self.migrations_in,
            "handoffs_committed": self.handoffs_committed,
            "handoffs_aborted": self.handoffs_aborted,
            "handoffs_expired": self.handoffs_expired,
            "handoff_leases": len(self.leases),
            "handoffs_unfetched": self.leases.unfetched(),
            "kv_transfer_bytes": self.kv_transfer_bytes,
            # cluster prefix plane: all zero while no directory is bound
            "prefix_fetches": self.prefix_fetches,
            "prefix_fetch_fallbacks": self.prefix_fetch_fallbacks,
            "prefix_fetch_bytes": self.prefix_fetch_bytes,
            "prefix_fetch_ms": round(1e3 * self.prefix_fetch_seconds, 2),
            "prefix_exports": self.prefix_exports_served,
            "cluster_prefix_hit_tokens": self.cluster_prefix_hit_tokens,
        }

    def _require_kv_only(self, what: str) -> None:
        if not self._kv_only:
            raise RecurrentStateUnsupported(
                f"{what} moves K/V pages only; this engine's blocks also "
                "keep per-slot recurrent state or latent pages")

    # -- cluster-global prefix cache (prefix_directory) --------------------
    def bind_prefix_directory(self, directory, holder_id: str,
                              peers: Optional[Callable] = None, *,
                              fetch_timeout: float = 5.0,
                              frame_pages: int = 8,
                              min_fetch_pages: int = 1) -> None:
        """Join a cluster-wide `PrefixDirectory`: this engine's prefix
        cache publishes its promoted chains under `holder_id` (and
        retracts on evict/clear), and — when `peers` is given — a
        local prefix miss with a directory hit FETCHES the chain's
        pages from the holder instead of re-prefilling them.
        `peers(holder_id)` resolves a holder name to an engine-shaped
        handle exposing `export_prefix` / `fetch_handoff_frame` /
        `commit_handoff` / `abort_handoff` (an in-process engine, a
        `ModelServer`, or a `RemoteReplica` — the deployment seam);
        returning None skips the fetch. Every wire failure degrades to
        cold prefill — the fetch path is never load-bearing."""
        self._require_kv_only("the cluster prefix cache")
        with self._cond:
            self._directory = directory
            self._holder_id = str(holder_id)
            self._peers = peers
            self._fetch_timeout = float(fetch_timeout)
            self._fetch_frame_pages = max(1, int(frame_pages))
            self._min_fetch_pages = max(1, int(min_fetch_pages))
            cache = self._pool.prefix_cache
            if cache is not None:
                cache.bind_directory(directory, self._holder_id)
                chains = cache.chains()
                if chains:  # late bind: announce what is already warm
                    directory.publish(self._weight_version,
                                      self._page_size, chains,
                                      self._holder_id)

    def prefix_depth(self, prompt_ids,
                     tenant: Optional[str] = None) -> int:
        """Fully-covered resident prefix pages this engine holds for
        `prompt_ids` at its CURRENT weight version — the receiver-side
        answer a delta sender asks before choosing `skip_pages`."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        with self._cond:
            cache = self._pool.prefix_cache
            if cache is None:
                return 0
            return len(cache.match(prompt, tenant=tenant))

    def prefix_chains(self) -> dict:
        """Snapshot of every resident chain key at the current weight
        version — the pull-mode directory refresh for remote replicas
        whose promotions cannot ride a shared in-process directory."""
        with self._cond:
            cache = self._pool.prefix_cache
            chains = [] if cache is None else cache.chains()
            return {"weight_version": self._weight_version,
                    "page_size": self._page_size, "chains": chains}

    def export_prefix(self, prompt_ids, have_pages: int = 0,
                      tenant: Optional[str] = None,
                      frame_pages: Optional[int] = None,
                      timeout: Optional[float] = None) -> dict:
        """Holder-side cluster-prefix export: serialize this engine's
        resident chain pages for `prompt_ids` (beyond the receiver's
        `have_pages`) into a leased `kind="prefix"` handoff and return
        its framed HEADER — the receiver then drains
        `fetch_handoff_frame` and commits. The device read runs on the
        scheduler thread via a parked work item (only that thread may
        touch the pools between dispatches under donation); this
        caller blocks up to `timeout`. Typed `KVTransferError` when
        the chain is no longer resident deeper than `have_pages` (the
        directory entry was stale)."""
        self._require_kv_only("a prefix export")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        item = {"prompt": prompt, "have": max(0, int(have_pages)),
                "tenant": tenant, "frame_pages": frame_pages,
                "done": threading.Event(), "result": None, "error": None}
        with self._cond:
            if self._closed:
                raise ServerClosedError("decode engine is shut down")
            self._exports.append(item)
            self._cond.notify_all()
        wait = self._fetch_timeout if timeout is None else float(timeout)
        if not item["done"].wait(wait):
            raise KVTransferError(
                f"prefix export timed out after {wait:.1f}s (scheduler "
                "busy); fall back to cold prefill")
        if item["error"] is not None:
            raise item["error"]
        return item["result"]

    def _touch_lease_locked(self, handoff_id: str):
        assert_owned(self._cond, "HandoffPlane._touch_lease_locked")
        lease = self.leases.touch(handoff_id)
        if lease is None:
            raise KVTransferError(
                f"unknown or expired handoff lease {handoff_id!r}; "
                "fall back to re-prefill from the prompt")
        return lease

    def fetch_handoff_header(self, handoff_id: str, skip_pages: int = 0,
                             frame_pages: Optional[int] = None) -> dict:
        """Framed-transfer entry for ANY leased handoff (migration or
        prefix export): the blockless header, advanced by `skip_pages`
        pages the receiver proved it holds (delta transfer). Extends
        the lease TTL. Typed `KVTransferError` on an unknown lease."""
        with self._cond:
            return kv_transfer.payload_header(
                self._touch_lease_locked(handoff_id).payload,
                skip_pages=skip_pages, frame_pages=frame_pages)

    def fetch_handoff_frame(self, handoff_id: str, frame: int,
                            skip_pages: int = 0,
                            frame_pages: Optional[int] = None) -> dict:
        """One bounded frame of a leased handoff (host-side numpy
        slicing — safe on any RPC thread). Extends the lease TTL, so a
        receiver mid-drain cannot lose the race against the orphan
        sweep."""
        with self._cond:
            return kv_transfer.slice_frame(
                self._touch_lease_locked(handoff_id).payload, frame,
                skip_pages=skip_pages, frame_pages=frame_pages)

    def fetch_handoff(self, handoff_id: str) -> dict:
        """The leased payload for `handoff_id` (extends the lease TTL,
        so an actively-resuming receiver cannot lose the race against
        the orphan sweep). Typed `KVTransferError` for an unknown or
        already-expired lease."""
        with self._cond:
            return self._touch_lease_locked(handoff_id).payload

    def fetch_prefix_for(self, prompt: np.ndarray,
                         tenant: Optional[str]) -> Optional[dict]:
        """Submit-thread cluster-prefix fetch — wire I/O must never
        stall the scheduler: on a local miss with a directory hit, pull
        the chain's missing pages from a holder and return a verified
        ``{"payload", "have", "depth", "source"}`` bundle for admission
        to bind. Returns None — never raises — when no directory and
        peers are bound, and on any miss, skew, or wire failure: the
        request then cold-prefills exactly as it would today (the
        never-slower contract)."""
        if self._directory is None or self._peers is None:
            return None
        t0 = int(prompt.shape[0])
        page = self._page_size
        cap = max(0, (t0 - 1) // page)
        if cap < self._min_fetch_pages:
            return None
        with self._cond:
            cache = self._pool.prefix_cache
            if cache is None:
                return None
            local = len(cache.match(prompt, tenant=tenant))
        if cap - local < self._min_fetch_pages:
            return None
        hit = self._directory.best_holder(
            prompt, tenant, exclude=(self._holder_id,))
        if hit is None or hit["weight_version"] != self._weight_version \
                or int(hit["page_size"]) != page:
            return None
        depth = min(int(hit["depth"]), cap)
        if depth - local < self._min_fetch_pages:
            return None
        holder = hit["holders"][0]
        # single-flight per chain: a same-prefix burst on a cold engine
        # must not become a thundering herd of identical wire fetches —
        # one admit pulls the pages, the rest wait (bounded by the
        # fetch timeout) and re-check the cache the winner filled
        sf_key = (hit["weight_version"], tenant,
                  prompt[:depth * page].tobytes())
        sf_deadline = time.monotonic() + self._fetch_timeout
        with self._cond:
            while sf_key in self._fetching:
                remaining = sf_deadline - time.monotonic()
                if remaining <= 0:
                    return None  # waited out: cold prefill, never slower
                self._cond.wait(remaining)
            cache = self._pool.prefix_cache
            if cache is None:
                return None
            local = len(cache.match(prompt, tenant=tenant))
            if depth - local < self._min_fetch_pages:
                return None  # the winner's bind covers us: warm admit
            ready = self._fetch_ready.get(sf_key)
            if ready is not None:
                bundle, expires = ready
                if time.monotonic() < expires:
                    # the winner's bundle is still queued toward the
                    # cache (binding happens at admission, on the
                    # scheduler thread) — share it instead of pulling
                    # the same pages over the wire again; every bind
                    # after the first is dropped by the stale-check
                    self.recorder.event("prefix-fetch",
                                        decision="reused", depth=depth)
                    return dict(bundle)
                del self._fetch_ready[sf_key]
            self._fetching.add(sf_key)
        bundle = None
        try:
            bundle = self._fetch_prefix_chain(
                prompt, tenant, depth, local, holder)
            return bundle
        finally:
            with self._cond:
                if bundle is not None:
                    now = time.monotonic()
                    stale = [k for k, (_, exp)
                             in self._fetch_ready.items() if exp <= now]
                    for k in stale:
                        del self._fetch_ready[k]
                    self._fetch_ready[sf_key] = (
                        bundle, now + self._fetch_timeout)
                self._fetching.discard(sf_key)
                self._cond.notify_all()

    def _fetch_prefix_chain(self, prompt, tenant, depth, local,
                            holder) -> Optional[dict]:
        """The wire leg of `fetch_prefix_for`, run under the chain's
        single-flight slot: export → frames → verify → commit."""
        page = self._page_size
        start = time.monotonic()
        header = None
        try:
            peer = self._peers(holder)
            if peer is None:
                return None
            header = peer.export_prefix(
                [int(x) for x in prompt[:depth * page]],
                have_pages=local, tenant=tenant,
                frame_pages=self._fetch_frame_pages,
                timeout=self._fetch_timeout)
            frames = [peer.fetch_handoff_frame(
                          header["handoff_id"], i, skip_pages=0,
                          frame_pages=header["frame_pages"])
                      for i in range(int(header["n_frames"]))]
            payload = kv_transfer.assemble_payload(header, frames)
            payload = kv_transfer.verify_payload(
                payload, weight_version=self._weight_version,
                kv_quant=self._kv_quant, page_size=page,
                n_blocks=self._n_blocks, max_len=self._max_len,
                kinds=("prefix",))
        # graftlint: disable=typed-error  never-slower contract: ANY
        # fetch-path failure (wire fault, refusal, corruption) degrades
        # to cold prefill; the typed cause is recorded, not raised
        except BaseException as e:
            if header is not None:
                try:
                    peer.abort_handoff(header["handoff_id"])
                # graftlint: disable=typed-error  best-effort abort of
                # a lease on a peer that may already be dead — its TTL
                # sweep unpins regardless
                except BaseException:
                    pass
            with self._cond:
                self.prefix_fetch_fallbacks += 1
            self.recorder.event(
                "prefix-fetch", decision="fallback", holder=holder,
                depth=depth, have=local, error=type(e).__name__)
            logger.warning(
                "cluster prefix fetch from %s failed (%s: %s); cold "
                "prefill", holder, type(e).__name__, e)
            return None
        try:
            peer.commit_handoff(header["handoff_id"])
        # graftlint: disable=typed-error  commit is an optimization
        # (early unpin on the holder); its lease TTL unpins regardless
        except BaseException:
            logger.warning(
                "prefix fetch commit_handoff(%s) failed; the holder's "
                "lease sweep will unpin", header["handoff_id"])
        dt = time.monotonic() - start
        nbytes = kv_transfer.payload_nbytes(payload)
        with self._cond:
            self.prefix_fetches += 1
            self.prefix_fetch_bytes += nbytes
            self.prefix_fetch_seconds += dt
        omitted = int(payload.get("pages_omitted", 0))
        self.recorder.event(
            "prefix-fetch", decision="fetched", holder=holder,
            depth=depth, have=local,
            pages=int(payload["pages_shipped"]), skipped=omitted,
            bytes=nbytes, ms=round(1e3 * dt, 2))
        return {"payload": payload, "have": omitted, "depth": depth,
                "source": holder}

    def prefix_import_is_stale(self, pim: dict, n_local: int) -> bool:
        """Admission's check of a fetched bundle against the chain the
        local cache holds NOW (`n_local` pages): True when it went stale
        between submit and admission (weight swap, seed-chain eviction,
        or the local cache caught up) — drop it; prefill covers the
        request regardless."""
        pay = pim["payload"]
        stale = pay["weight_version"] != self._weight_version \
            or int(pay["page_size"]) != self._page_size \
            or not (int(pim["have"]) <= n_local < int(pim["depth"]))
        if stale:
            self.recorder.event("prefix-fetch", decision="dropped",
                                have=n_local)
        return stale

    # graftlint: hot-loop
    def bind_prefix_import(self, req) -> Optional[int]:
        """Bind a verified cluster-prefix fetch into this request's
        pages (scheduler thread, at admission): scatter the shipped
        chain pages into the pool (eager `.at[].set`, like
        `import_into`), insert the now-resident chain into the local
        prefix cache (publishing to the directory exactly as a locally
        promoted prefix would), and extend the request's hit span so
        suffix prefill starts at the fetched depth. Returns the prompt
        tokens gained over the local hit; None when the bundle was
        dropped or the scatter failed — the request still serves from
        the local hit, just colder."""
        pim, req.prefix_import = req.prefix_import, None
        payload = pim["payload"]
        page = self._page_size
        have = req.n_shared          # local chain pages already bound
        depth = int(pim["depth"])
        omitted = int(payload.get("pages_omitted", 0))
        shipped = int(payload["pages_shipped"])
        off = have - omitted         # leading shipped pages held here
        n_new = depth - have
        if off < 0 or off + n_new > shipped or n_new <= 0:
            self.recorder.event("prefix-fetch", decision="dropped",
                                have=have, depth=depth, skipped=omitted)
            return None
        try:
            self._write_slot(
                None, req.pages[have:depth],
                [{name: np.asarray(arr)[off:off + n_new]
                  for name, arr in blk.items()}
                 for blk in payload["blocks"]], None)
        # graftlint: disable=typed-error  never-slower contract: a
        # failed scatter falls back to prefilling from the local hit;
        # the pools stay valid (eager updates are not donated
        # dispatches)
        except BaseException as e:
            with self._cond:
                self.prefix_fetch_fallbacks += 1
            self.recorder.event("prefix-fetch", decision="bind-failed",
                                error=type(e).__name__)
            logger.warning("cluster prefix bind failed (%s: %s); "
                           "prefilling from the local hit",
                           type(e).__name__, e)
            return None
        with self._cond:
            self._pool.promote_locked(req, req.prompt[:depth * page],
                                      req.tenant, depth)
            gained = (len(req.nodes) - have) * page
            req.hit_len = len(req.nodes) * page
            self.cluster_prefix_hit_tokens += gained
            self._cond.notify_all()
        req.trace.event("prefix-fetch-bind",
                        pages=len(req.nodes) - have,
                        hit_tokens=req.hit_len, source=pim["source"])
        self.recorder.event("prefix-fetch", decision="bound",
                            holder=pim["source"],
                            pages=len(req.nodes) - have,
                            hit_tokens=req.hit_len)
        return gained

    # graftlint: hot-loop
    def _serve_prefix_exports(self) -> None:
        """Scheduler-thread service for parked `export_prefix` items:
        only this thread may read the pools between dispatches (a
        donated dispatch invalidates the old buffers), so the read of
        the chain's pages happens here; the lease grant pins the chain
        nodes for the drain, and the waiting RPC thread gets the framed
        header."""
        while True:
            with self._cond:
                if not self._exports:
                    return
                item = self._exports.popleft()
                cache = self._pool.prefix_cache
                nodes = [] if cache is None else \
                    cache.match(item["prompt"], tenant=item["tenant"])
                depth = len(nodes)
                have = item["have"]
                if depth <= have:
                    item["error"] = KVTransferError(
                        f"prefix chain no longer resident here beyond "
                        f"{have} pages (holds {depth}); the directory "
                        "entry was stale — fall back to cold prefill")
                    item["done"].set()
                    continue
                self._pool.pin_locked(nodes)
                pages = [n.page_id for n in nodes]
            try:
                _, blocks, _ = self._read_slot(None, pages[have:])
                handoff_id = kv_transfer.LeaseTable.new_id()
                payload = kv_transfer.build_payload(
                    handoff_id=handoff_id, kind="prefix",
                    weight_version=self._weight_version,
                    kv_quant=self._kv_quant, page_size=self._page_size,
                    n_blocks=self._n_blocks,
                    prompt=item["prompt"][:depth * self._page_size],
                    n_tokens=0, temperature=0.0, seed=0, resumed_at=0,
                    tokens=[], blocks=blocks,
                    pages_shipped=depth - have, pages_omitted=have,
                    tenant=item["tenant"], source=self._holder_id)
                header = kv_transfer.payload_header(
                    payload,
                    frame_pages=item["frame_pages"]
                    or self._fetch_frame_pages)
            # graftlint: disable=typed-error  the export dies typed on
            # the WAITER (a wire edge), never in the scheduler loop;
            # the pins release like an aborted lease
            except BaseException as e:
                with self._cond:
                    self._pool.unpin_locked(nodes)
                    self._cond.notify_all()
                item["error"] = e if isinstance(e, ServingError) else \
                    KVTransferError(
                        f"prefix export failed: {type(e).__name__}: {e}")
                item["done"].set()
                continue
            nbytes = kv_transfer.payload_nbytes(payload)
            with self._cond:
                # n_shared == len(pages): lease resolution releases the
                # pins and returns NOTHING to the free list — the cache
                # owns these pages; the lease only pins them while the
                # receiver drains frames
                self.leases.grant(payload, pages=pages,
                                  n_shared=len(pages), nodes=nodes)
                self.prefix_exports_served += 1
                self._cond.notify_all()
            item["result"] = header
            item["done"].set()
            self.recorder.event(
                "prefix-export", holder=self._holder_id,
                handoff_id=handoff_id, pages=depth - have,
                skipped=have, bytes=nbytes)

    # -- leases ------------------------------------------------------------
    def _resolve_locked(self, handoff_id: str) -> bool:
        """Pop the lease and return its pages; False when it is already
        resolved or expired."""
        assert_owned(self._cond, "HandoffPlane._resolve_locked")
        lease = self.leases.resolve(handoff_id)
        if lease is None:
            return False
        self._pool.release_locked(lease)
        self._cond.notify_all()
        return True

    def commit_handoff(self, handoff_id: str) -> bool:
        """The receiver resumed successfully: release the lease and
        free the shipped pages on this side. Idempotent (False when the
        lease is already resolved or expired)."""
        with self._cond:
            if not self._resolve_locked(handoff_id):
                return False
            self.handoffs_committed += 1
        self.recorder.event("handoff-commit", handoff_id=handoff_id)
        return True

    def abort_handoff(self, handoff_id: str) -> bool:
        """The transfer failed downstream: reclaim the leased pages now
        instead of waiting out the TTL. Idempotent."""
        with self._cond:
            if not self._resolve_locked(handoff_id):
                return False
            self.handoffs_aborted += 1
        self.recorder.event("handoff-abort", handoff_id=handoff_id)
        return True

    def _sweep_leases(self) -> None:
        """Orphan reclamation: a receiver that died (or never
        committed) lets its lease expire; the pages come home here, so
        a dead receiver can never leak sender pages."""
        now = time.monotonic()
        with self._cond:
            if not self.leases.expired_pending(now):
                return
            for lease in self.leases.sweep(now):
                self._pool.release_locked(lease)
                self.handoffs_expired += 1
                self.recorder.event("lease-expired",
                                    handoff_id=lease.handoff_id)
            self._cond.notify_all()

    # -- export: live migration and the prefill role -----------------------
    def migrate_slots(self, wait: Optional[float] = 5.0) -> int:
        """Export EVERY in-flight request (queued, mid-prefill,
        decoding) as a leased handoff: each waiter's `result()` raises
        the `SlotMigratedError` redirect and the pool/coordinator
        resumes it on a peer. Returns the number of requests marked.
        Blocks up to `wait` seconds for the scheduler's migration pass
        to drain the engine (pass `wait=None`/0 for fire-and-forget).
        Idempotent — an empty engine migrates nothing."""
        self._require_kv_only("slot migration")
        with self._cond:
            if self._closed:
                raise ServerClosedError("decode engine is shut down")
            n = self._in_flight()
            if n == 0:
                return 0
            self._migrate_all = True
            self._cond.notify_all()
            if wait:
                deadline = time.monotonic() + wait
                while self._migrate_all or self._in_flight():
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(min(remaining, 0.05))
        return n

    def _payload_fields(self, req) -> dict:
        """What every handoff of `req` carries, warm or cold."""
        return dict(
            weight_version=self._weight_version,
            kv_quant=self._kv_quant, page_size=self._page_size,
            n_blocks=self._n_blocks, prompt=req.prompt,
            n_tokens=req.n_tokens, temperature=req.temperature,
            seed=req.seed, resumed_at=req.resumed_at,
            tokens=req.tokens, tenant=req.tenant, priority=req.priority,
            preempted=req.preempted, logprobs=req.logprobs,
            logprob_values=list(req.logprob_values),
            deadline_remaining=None if req.deadline is None
            else max(0.0, req.deadline - time.monotonic()))

    def export_slot(self, slot: int, req, reason: str):
        """Scheduler-thread export of a decoding slot: serialize its
        decode state (used KV pages of every block + scale sidecars,
        page span, position/last-token registers, the LIVE per-slot
        PRNG key, the emitted transcript) into a leased handoff
        payload. Page ownership moves from the request to the lease —
        freed exactly once by commit, abort, or TTL expiry. Returns the
        `SlotMigratedError` redirect the scheduler finishes the request
        with once it has released the slot."""
        (pos, tok, key, temp), blocks, used = self._read_slot(slot,
                                                              req.pages)
        handoff_id = kv_transfer.LeaseTable.new_id()
        payload = kv_transfer.build_payload(
            handoff_id=handoff_id, kind="warm", blocks=blocks,
            pages_shipped=used, pos=pos, tok=tok, key=key, temp=temp,
            **self._payload_fields(req))
        nbytes = kv_transfer.payload_nbytes(payload)
        with self._cond:
            self.leases.grant(payload, pages=req.pages,
                              n_shared=req.n_shared, nodes=req.nodes)
            req.pages = None  # ownership moved to the lease
            req.nodes = None
            self.migrations_out += 1
            self.kv_transfer_bytes += nbytes
            self._cond.notify_all()
        if self.breaker is not None:
            # an export is a routing decision, not sickness: the device
            # work so far was healthy, and the token must not be dropped
            self.breaker.record_success(req.probe)
        req.trace.event("migrate-out", handoff_id=handoff_id, slot=slot,
                        pos=pos, pages_shipped=used, bytes=nbytes,
                        reason=reason)
        self.recorder.event("migrate-out", handoff_id=handoff_id,
                            slot=slot, pos=pos, pages_shipped=used,
                            bytes=nbytes, reason=reason)
        return kv_transfer.SlotMigratedError(
            f"slot exported under lease {handoff_id} ({reason}); fetch "
            "the handoff and resume on a peer",
            handoff_id=handoff_id, tokens=list(req.tokens))

    def export_cold(self, req, reason: str):
        """Export a request that holds no (complete) KV — queued, or
        parked mid-prefill — as a cold handoff: the peer re-prefills
        from the prompt with the same seed, reproducing the exact
        output. No pages ride the lease (there is nothing complete to
        ship), but the payload stays fetchable until resolution.
        Returns the `SlotMigratedError` redirect."""
        handoff_id = kv_transfer.LeaseTable.new_id()
        payload = kv_transfer.build_payload(
            handoff_id=handoff_id, kind="cold", blocks=[],
            pages_shipped=0, **self._payload_fields(req))
        with self._cond:
            self.leases.grant(payload)
            self.migrations_out += 1
            self._cond.notify_all()
        req.trace.event("migrate-out", handoff_id=handoff_id,
                        kind="cold", reason=reason)
        self.recorder.event("migrate-out", handoff_id=handoff_id,
                            handoff_kind="cold", reason=reason)
        return kv_transfer.SlotMigratedError(
            f"request exported cold under lease {handoff_id} ({reason});"
            " resume re-prefills from the prompt on a peer",
            handoff_id=handoff_id, tokens=list(req.tokens))

    # -- import: resuming a handoff ----------------------------------------
    def resume_submit(self, payload: dict,
                      timeout: Optional[float] = None, *,
                      on_token: Optional[Callable] = None):
        """Admit a fetched handoff payload: validate it against this
        engine's weights/geometry (typed `KVTransferError` on ANY
        mismatch or corruption — nothing is touched), then hand it to
        the scheduler's door, which enqueues a request whose shipped
        pages re-bind at admission (warm) or that re-prefills from the
        prompt (cold). The deadline is the SMALLER of the sender's
        remaining budget and `timeout`. `on_token` re-attaches a stream
        sink so a mid-stream migration keeps publishing under the
        sender's cursor."""
        self._require_kv_only("resuming a migrated slot")
        if self.role == "prefill":
            raise KVTransferError(
                "prefill-role engine does not accept KV handoffs — "
                "route resumes to a decode-capable replica")
        payload = kv_transfer.verify_payload(
            payload, weight_version=self._weight_version,
            kv_quant=self._kv_quant, page_size=self._page_size,
            n_blocks=self._n_blocks, max_len=self._max_len)
        return self._enqueue_resumed(payload, timeout, on_token)

    def resume_generate(self, payload: dict,
                        timeout: Optional[float] = None, *,
                        on_token: Optional[Callable] = None):
        """Blocking `resume_submit`: returns only the TAIL tokens this
        engine generates — the caller splices them after the redirect's
        already-emitted `tokens`. When the handoff carries logprobs, a
        dict `{"tokens", "logprobs"}` holding only the tail's share."""
        req = self.resume_submit(payload, timeout=timeout,
                                 on_token=on_token)
        already = len(req.tokens)
        already_lp = len(req.logprob_values)
        out = req.result()
        if req.logprobs:
            return {"tokens": out[already:],
                    "logprobs": list(req.logprob_values[already_lp:])}
        return out[already:]

    # graftlint: hot-loop
    def import_into(self, slot: int, req) -> None:
        """Re-bind a validated warm handoff into a free slot (scheduler
        thread, at admission): scatter the shipped pages into every
        block's pools (+ scale sidecars) and restore the position /
        last-token / temperature registers and the live PRNG key. The
        scheduler then activates the slot, and its next decode step
        continues the sequence argmax-exact."""
        payload = req.import_state
        shipped = int(payload["pages_shipped"])
        omitted = int(payload.get("pages_omitted", 0))
        pos = int(payload["pos"])
        # delta handoff: the first `omitted` pages are the locally
        # resident prefix chain (pinned at resume_submit, already in
        # req.pages as shared pages) — shipped pages land after them
        self._write_slot(
            slot, req.pages[omitted:omitted + shipped], payload["blocks"],
            (pos, int(payload["tok"]),
             np.asarray(payload["key"], np.uint32),
             float(payload["temp"])))
        with self._cond:
            self.migrations_in += 1
        req.trace.event("migrate-in", slot=slot, pages_shipped=shipped,
                        pos=pos)
        self.recorder.event("migrate-in", slot=slot,
                            handoff_id=payload["handoff_id"],
                            pages_shipped=shipped, pos=pos)
