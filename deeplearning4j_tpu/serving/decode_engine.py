"""Continuous-batching decode engine: the scheduler.

`models/transformer.generate` is a whole-batch synchronous sampler:
every request in a batch decodes the same number of tokens in lockstep,
so at mixed output lengths every request waits for the slowest sequence
and the chip idles between calls. At serving shapes decode is
dispatch+cache-bandwidth bound, so the batch dimension is the
scheduling resource. `DecodeEngine` turns it into a pool of `n_slots`
decode **slots** (Orca's iteration-level scheduling, OSDI '22) over KV
memory managed as **pages** (PagedAttention, SOSP '23), with long
prompts prefilled in **chunks interleaved with decode** (Sarathi-Serve,
2024). It is four parts, and the imports point one way:

    decode_engine.py     this file: construction, `submit` and the QoS
      |                  door, the scheduler thread (`_schedule`:
      |                  `_admit`, prefill and decode dispatches,
      |                  `_retire_or_poison`, `_emit_token`), weight
      |                  swap, `stats`, shutdown
      +-> decode_programs.py   the four jitted programs (`decode_step`,
      |                        `decode_chunked`, `prefill`,
      |                        `prefill_chunk_fn`): plan + geometry in
      +-> page_pool.py         what a page is and who owns it: free
      |                        list, page table, prefix-cache refcounts,
      |                        lease ownership
      +-> kv_handoff.py        everything that moves pages BETWEEN
                               engines: leases, migration, disaggregated
                               roles, the cluster prefix cache
          (all three stand on block_state, kv_transfer, prefix_cache,
           models/transformer and ops/, and never import this file)

This file and the programs name no cache kind and no router: what a kind
keeps, counts, refuses and copies, and what routed blocks count, is
`block_state`'s to say; `stats()` merges what each part reports.

**The path of a token.** `submit` judges a request at the door (typed
sheds: `ServerOverloadedError`, `OutOfPagesError`,
`TenantQuotaExceededError`, `DeadlineExceededError`, breaker) and
queues it holding a page RESERVATION, no pages. Each iteration of the
scheduler thread `_admit`s queued requests into free slots (pages taken
from the pool, the longest cached prefix bound), one-shot prefills short
prompts at a pow-2 bucket and parks long or prefix-hit ones for
`_step_prefills` (one chunk an iteration), then `_step_active` advances
ALL active slots one token — or `decode_chunk` tokens in one dispatch
when no scheduling event can land inside. A dispatch is two halves:
*issue* hands the program to the chip and keeps the device handles of
its tokens in an `_InFlight` record; *collect* is the one host sync,
after which `_retire_or_poison` delivers each slot's tokens
(`_emit_token`), retires on EOS / max-tokens and fails a slot whose
logits went non-finite, typed, while its neighbours keep decoding.
`_step_active` issues dispatch n+1 BEFORE it collects dispatch n (and
the one-shot prefills admitted between them), so delivery, retirement
and admission happen while the chip runs: every input of the next
program is a device array the last one returned. The host counts the
tokens it has issued (`_GenRequest.in_flight`), a slot's pages go back
to the pool only after the last uncollected dispatch that had it active
(`_vacate_locked`), and whatever reads or rewrites slot state from the
host `_drain`s the pipeline first. The thread is always in one leaf
phase of `observability.LEAF_PHASES`; the hand-off plane's share of an
iteration is one `plane.step()` under `housekeeping`.

Robustness rides the PR-4 serving tier: a deadline expiring in the
queue sheds BEFORE prefill, one expiring in flight frees its slot AND
its pages, an optional `CircuitBreaker` gates admission and counts
device failures, a failed donated dispatch fails what the lost pools
backed and rebuilds them, and `drain_and_swap(net)` lets a hot reload
finish in-flight requests on the old weights, swap, and keep serving.
Opt-in on top: `prefix_cache=` (`serving/prefix_cache.py`: page-aligned
shared prefixes bind the same resident pages, reclaimed LRU-first under
pressure) and `speculative=` (`serving/speculative.py`: a draft proposes
k tokens a slot, verified in one batched chunk).
"""
from __future__ import annotations

import collections
import logging
import threading
import time
from types import SimpleNamespace
from typing import Callable, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.serving import observability
from deeplearning4j_tpu.serving.model_server import (
    DeadlineExceededError,
    InferenceFailedError,
    OutOfPagesError,
    ServerClosedError,
    ServerOverloadedError,
    ServiceUnavailableError,
    ServingError,
    TenantQuotaExceededError,
)
from deeplearning4j_tpu.util.concurrency import assert_owned

logger = logging.getLogger("deeplearning4j_tpu")


class _GenRequest:
    """One generation request's lifecycle: queued → (shed | admitted
    into a slot, prefilled — one-shot or chunk by chunk) → decoding →
    (completed | expired | failed). `tokens` grows as the engine
    emits — tokens are delivered per-request as they complete, never
    held for a batch. `n_pages` is the page reservation taken at
    submit; `pages` the pool pages held from admission to
    retirement; `prefill_pos` the next chunk offset while a long
    prompt is mid-prefill (None once decoding); `in_flight` the tokens
    issued to the chip for it and not yet collected."""

    __slots__ = ("prompt", "n_tokens", "temperature", "seed", "deadline",
                 "event", "tokens", "error", "enqueued_at", "probe",
                 "slot", "completed_at", "n_pages", "pages", "ring",
                 "prefill_pos", "hit_len", "n_shared", "nodes", "digests",
                 "trace", "tenant", "priority", "resumed_at",
                 "preempted", "handoff", "import_state", "prefix_import",
                 "sink", "logprobs", "logprob_values", "decode_span",
                 "in_flight")

    def __init__(self, prompt: np.ndarray, n_tokens: int,
                 temperature: float, seed: int,
                 deadline: Optional[float],
                 tenant: Optional[str] = None,
                 priority: str = "interactive"):
        self.prompt = prompt
        self.n_tokens = n_tokens
        self.temperature = temperature
        self.seed = seed
        self.deadline = deadline
        self.tenant = tenant
        self.priority = priority
        # preemption bookkeeping: a preempted batch request folds its
        # emitted tokens into the prompt for re-prefill (prefix-cached,
        # so the re-prefill mostly re-binds resident pages).
        # `resumed_at` = len(tokens) at the moment the current prompt
        # was formed (0 for a fresh request), so logical span math
        # stays exact: span = len(prompt) - resumed_at + n_tokens
        self.resumed_at = 0
        self.preempted = 0
        self.event = threading.Event()
        self.tokens: List[int] = []
        self.error: Optional[BaseException] = None
        self.enqueued_at = time.monotonic()
        self.completed_at: Optional[float] = None
        self.probe = False
        self.slot: Optional[int] = None
        self.n_pages = 0
        self.pages: Optional[List[int]] = None
        # its ring of window pages (the pool's second class), where the
        # net has blocks that read a window; taken and returned with `pages`
        self.ring: Optional[List[int]] = None
        self.prefill_pos: Optional[int] = None
        self.in_flight = 0
        # prefix-cache binding: hit_len prompt positions ride shared
        # pages (the first n_shared entries of `pages`, refcounted via
        # `nodes`); only pages[n_shared:] are this request's to free
        self.hit_len = 0
        self.n_shared = 0
        self.nodes: Optional[list] = None
        self.digests: list = []  # memoized per-chunk prompt digests
        # KV handoff (kv_transfer): `handoff` requests export their
        # slot state under a lease instead of entering/continuing the
        # decode loop; `import_state` carries a validated inbound
        # payload whose shipped pages re-bind at admission
        self.handoff = False
        self.import_state: Optional[dict] = None
        # cluster prefix fetch: a verified "prefix" payload fetched from
        # a directory holder on the SUBMIT thread; the scheduler binds
        # its pages at admission (or silently drops it and prefills
        # cold — the fetch is an optimization, never a dependency)
        self.prefix_import: Optional[dict] = None
        # streaming emission hook: `sink(cursor, token, logprob)` fires
        # per emitted token (serving.streaming.TokenStream.publish);
        # None = unary request, zero per-token overhead
        self.sink = None
        # per-step logprob returns: K > 0 asks for {token logprob +
        # top-K alternatives} per emitted token (requires an engine
        # built with logprobs=K'); entries accumulate alongside tokens
        self.logprobs = 0
        self.logprob_values: List[dict] = []
        # the request timeline, carried across the caller-thread →
        # scheduler-thread hop (thread-locals do not cross it)
        self.trace = observability.NULL_TRACE
        # the request's ONE `decode` (or `spec-verify`) span, opened at
        # its first decode dispatch and extended in place by each later
        # one; the per-dispatch record is the scheduler's timeline
        self.decode_span = None

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and \
            (now if now is not None else time.monotonic()) >= self.deadline

    def finish(self, error: Optional[BaseException] = None) -> None:
        self.error = error
        self.completed_at = time.monotonic()
        self.event.set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until this request completes; the generated tokens
        (1-D int32, possibly shorter than n_tokens on EOS) or a typed
        `ServingError`."""
        wait = timeout
        if wait is None and self.deadline is not None:
            # belt-and-braces bound: the scheduler always finishes
            # deadline-stamped requests shortly after expiry
            wait = max(0.0, self.deadline - time.monotonic()) + 30.0
        if not self.event.wait(wait):
            raise InferenceFailedError(
                "generation request was never completed (engine stalled)")
        if self.error is not None:
            raise self.error
        return np.asarray(self.tokens, np.int32)


class _TenantState:
    """One tenant's QoS ledger: a token bucket over REQUESTED tokens
    (charged at submit, so a flood hits its own wall before consuming
    queue capacity) plus the per-tenant counters `stats()["tenants"]`
    publishes. Every field is synchronized by the owning engine's
    `_cond` — the ledger is only ever touched inside the engine's
    locked admission/retire sections."""

    __slots__ = ("rate", "burst", "tokens", "last_refill", "submitted",
                 "served", "shed_quota", "shed_page_quota",
                 "tokens_generated", "preemptions", "max_pages", "weight")

    def __init__(self, rate: Optional[float] = None,
                 burst: Optional[float] = None,
                 max_pages: Optional[int] = None,
                 weight: Optional[float] = None):
        self.rate = None if rate is None else float(rate)
        self.burst = float(burst) if burst is not None \
            else (self.rate if self.rate else 0.0)
        self.tokens = self.burst
        # page-pool ceiling: the sum of this tenant's page RESERVATIONS
        # (queued + resident) may not exceed max_pages — a tenant
        # inside its token-rate budget can still hoard the shared page
        # pool with a few huge-prompt requests; this caps that
        self.max_pages = None if max_pages is None else int(max_pages)
        # batch-lane stride-scheduling share: admissions charge
        # span/weight, so weight 2 gets twice the admitted work of
        # weight 1 under saturation (interactive traffic is unaffected)
        self.weight = 1.0 if weight is None else float(weight)
        self.last_refill = time.monotonic()
        self.submitted = 0
        self.served = 0
        self.shed_quota = 0
        self.shed_page_quota = 0
        self.tokens_generated = 0
        self.preemptions = 0

    def refill(self, now: float) -> None:
        # elapsed clamps at 0: a ledger created mid-admission carries a
        # `last_refill` stamped AFTER the door's `now`, and a negative
        # elapsed would start the bucket fractionally below burst —
        # spuriously rejecting a first-sight tenant's full-burst request
        if self.rate:
            self.tokens = min(
                self.burst,
                self.tokens + max(0.0, now - self.last_refill)
                * self.rate)
        self.last_refill = now

    def counters(self) -> dict:
        # rate/burst stay None (JSON null) for unquota'd tenants — a
        # 0.0 sentinel would read as "zero allowance"
        return {"submitted": self.submitted, "served": self.served,
                "shed_quota": self.shed_quota,
                "shed_page_quota": self.shed_page_quota,
                "tokens_generated": self.tokens_generated,
                "preemptions": self.preemptions,
                "rate": self.rate, "burst": self.burst or None,
                "tokens": round(self.tokens, 3),
                "max_pages": self.max_pages,
                "weight": self.weight}


def _dispatched(thunk):
    """Run one half of a compiled dispatch, the jitted call of its issue
    or the `device_get` of its collect, tagging any exception raised so
    the caller can tell a FAILED DISPATCH (which, under buffer donation,
    may have invalidated the donated pool buffers) apart from failures
    raised around it (non-finite screens, hooks) — only the former
    justifies failing other slots. On asynchronous backends a
    device-side error surfaces at materialization: in the collect, one
    dispatch after the issue that caused it, so the collect is tagged
    as the call is."""
    try:
        return thunk()
    except BaseException as e:
        e._dispatch_failure = True
        raise


class _InFlight:
    """One dispatch the chip has been handed and the host has not read
    back: what an issue leaves for its collect. `program` is
    ``"prefill"``, ``"decode_step"`` or ``"decode_chunked"``; `live` the
    ``(slot, request)`` pairs it was issued for; `handles` the device
    arrays the collect reads, ``(tok0, ok[, lp0])`` or ``(toks, oks,
    lps, counts)``; `t0` the issue time; `info` the hooks' dict, the
    same object at `pre_*` and `post_*`; `n_steps` the tokens a slot
    gets from it; `draft` the speculative draft's prefill inputs."""

    __slots__ = ("program", "live", "handles", "t0", "info", "n_steps",
                 "draft")

    def __init__(self, program, live, handles, t0, info, n_steps=1,
                 draft=None):
        self.program, self.live, self.handles = program, live, handles
        self.t0, self.info, self.n_steps = t0, info, n_steps
        self.draft = draft


class DecodeEngine:
    """Continuous-batching generation over a fixed pool of decode slots
    backed by a paged KV pool (see module docstring).

    Parameters
    ----------
    net : a fitted `gpt_configuration` network (TokenEmbedding first).
    n_slots : decode slots = max concurrently-decoding requests; also
        the batch dimension of the one compiled decode step. With
        paging, KV memory is sized by `pool_pages`, not by
        `n_slots × max_len` — size `n_slots` for concurrency and the
        pool for memory.
    max_len : per-request length cap (prompt + generated tokens).
        Defaults to the embedding's max_length (clamped to it for
        learned-positional models). Also sizes the per-slot page-table
        width.
    page_size : pow-2 KV page length (positions per page). Clamped to
        the pow-2 ceiling of `max_len`. 128 matches the TPU lane width
        of the decode layouts; tests use small pages to force
        multi-page requests.
    pool_pages : allocatable KV pages shared by all slots (page 0, the
        trash page, is extra). Default `n_slots × ceil(max_len/page)` —
        the dense r5 slotted cache's exact memory budget, so the
        default cannot regress capacity. The real win runs the other
        way: on a fixed memory budget, raise `n_slots` well past
        `pool_pages × page / max_len` and let ACTUAL request lengths,
        not the worst case, decide how many decode concurrently.
    max_queued_pages : memory axis of the bounded queue: max aggregate
        page demand allowed to WAIT (queued requests hold no pages;
        this bounds how deep the page-wait room gets). Beyond it,
        `submit` sheds with the typed `OutOfPagesError` + retry_after.
        A lone waiter always queues regardless of the cap — only
        aggregate demand sheds, so any request that fits the pool is
        eventually servable. Default `4 × pool_pages` (~four pool
        turnovers of patience).
    prompt_buckets : pow-2 prompt pad lengths the one-shot prefill
        compiles for; a longer prompt falls back to the next power of
        two ≤ max_len, or to CHUNKED prefill when it is also longer
        than `prefill_chunk`.
    prefill_chunk : pow-2 chunk width for chunked prefill of long
        prompts. Chunking activates for prompts longer than both the
        largest bucket and this value (and only when it is < max_len).
    max_queue : bounded admission queue; beyond it `submit` sheds with
        the typed `ServerOverloadedError`.
    eos_token : optional token id that retires a slot early.
    top_k : static top-k for sampled (temperature > 0) requests.
    breaker : optional `CircuitBreaker` shared with a `ModelServer` —
        admission is rejected while open, device failures count.
    step_hooks : chaos/observability seam — called as `hook(phase,
        info)` at pre/post_prefill (info carries `chunk_off`/`final`
        for chunked prefill) and pre/post_decode. `pre_*` fires when a
        dispatch is issued, `post_*` with the same `info` object when it
        is collected, one decode dispatch later: `pre_decode(n+1)`
        precedes `post_decode(n)`.
    decode_chunk : fuse up to this many decode iterations into ONE
        dispatch (a `lax.scan` over the same step body — identical
        numerics) whenever no scheduling event can fall inside the
        chunk: every in-flight request needs ≥chunk more tokens, no
        deadline can expire within it, no prompt is mid-prefill, and no
        queued request is waiting on a free slot. 1 disables fusion.
        Ignored while `speculative` is active (the verify step is the
        fused dispatch then).
    prefix_cache : None (off), True, or a dict of
        `serving.prefix_cache.PrefixCache` kwargs (`max_pages`): share
        page-aligned prompt-prefix KV pages across requests —
        admission binds the longest cached prefix into the slot's page
        table (refcounts bumped, prefill skipped for those positions),
        retirement frees only refcount-zero pages, and cached pages are
        reclaimed LRU-first under pool pressure. Invalidated on every
        weight swap / pool rebuild.
    speculative : None (off) or a dict: `{"draft": <gpt net | "self" |
        config json dict>, "k": 4}` — draft-verify speculative decoding
        (`serving.speculative.SpeculativeDecoder`): up to k+1 tokens
        per scheduler iteration in two dispatches, greedy argmax-exact
        and sampled distribution-exact for any draft.
    recorder, metrics : optional shared
        `serving.observability.FlightRecorder` / `MetricsRegistry` — a
        `ModelServer`-owned engine passes its own so one
        ``flight_record`` / ``metrics`` surface covers both layers;
        a standalone engine builds private instances. Request
        timelines (queue-wait, admission, prefix-bind, prefill chunks,
        one decode/spec-verify span) ride `_GenRequest.trace`; the
        scheduler thread's own timeline (leaf phases, one set per
        dispatch) goes to `observability.TIMELINE` and, as counters,
        to ``stats()["loop"]``; `_build`'s phases and JAX's compile
        pipeline go the same two ways, to ``stats()["build"]`` and the
        process-wide ``stats()["compile"]``. All recording is host-side and
        kill-switched by ``DL4J_TPU_NO_TRACING=1``; counters stay on.
    quantize : None or ``{"kv": "int8"}`` — the quantized KV tier
        (`serving/quantize.py`): pools allocate int8 elements plus
        per-(head, position) f32 scale pools riding the same page
        table/free list, K/V quantize symmetrically per head at every
        cache write, and attention dequantizes at the read site (the
        Pallas page loop on TPU, `paged_gather_quant` on CPU/fallback).
        Halves KV bytes per token — the decode path's bandwidth
        bound — at the price of bounded numeric drift, which the
        `ModelServer` drift gates police. ``DL4J_TPU_NO_INT8_KV=1``
        overrides to full-precision pools (the bench's A/B lever).
    excursion : p99-excursion auto-dump config: None (on, defaults),
        False (off), or ``{"quantile": 0.99, "min_count": 50}`` — a
        generate-latency observation past the histogram's live
        quantile bound pins that request's timeline in the flight
        recorder's failures ring with an ``excursion`` event.
    parallel : None or ``{"tp": N}`` — tensor-parallel decode
        (`serving/tp_engine.py`): shard THIS engine Megatron-style over
        a named `tp` mesh axis — attention heads and FFN width
        partitioned, head-sharded paged K/V pools (each device owns
        Hkv/N heads of every page), two all-reduces per block. The
        page table, free list, refcounts, prefix cache, speculative
        verify and int8 KV tier all ride unchanged; per-device
        weight+KV residency drops ~1/N so a model too big for one
        chip's HBM can serve. Geometry is validated at construction
        (N must divide every block's head counts and FFN width; MoE
        rejected) — a bad config is a typed ValueError, never a trace
        error. ``{"tp": 1}``/None is the single-device engine.
    """

    def __init__(self, net, *, n_slots: int = 4,
                 max_len: Optional[int] = None,
                 page_size: int = 128,
                 pool_pages: Optional[int] = None,
                 max_queued_pages: Optional[int] = None,
                 prompt_buckets: Sequence[int] = (32, 64, 128),
                 prefill_chunk: int = 256,
                 max_queue: int = 64,
                 default_timeout: Optional[float] = None,
                 eos_token: Optional[int] = None,
                 top_k: int = 0,
                 breaker=None,
                 step_hooks: Sequence[Callable] = (),
                 decode_chunk: int = 4,
                 prefix_cache=None,
                 speculative: Optional[dict] = None,
                 recorder=None,
                 metrics=None,
                 quantize: Optional[dict] = None,
                 excursion=None,
                 parallel: Optional[dict] = None,
                 qos: Optional[dict] = None,
                 role: str = "both",
                 handoff_ttl: float = 30.0,
                 logprobs: int = 0):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                'role must be "both", "prefill" or "decode", got %r'
                % (role,))
        if handoff_ttl <= 0:
            raise ValueError("handoff_ttl must be > 0")
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if decode_chunk < 1:
            raise ValueError("decode_chunk must be >= 1")
        if page_size < 1 or page_size & (page_size - 1):
            raise ValueError("page_size must be a power of two")
        if prefill_chunk < 1 or prefill_chunk & (prefill_chunk - 1):
            raise ValueError("prefill_chunk must be a power of two")
        if pool_pages is not None and pool_pages < 1:
            raise ValueError("pool_pages must be >= 1")
        if max_queued_pages is not None and max_queued_pages < 0:
            raise ValueError("max_queued_pages must be >= 0")
        if quantize is not None:
            unknown = set(quantize) - {"kv"}
            if unknown:
                raise ValueError("unknown quantize keys: %s"
                                 % sorted(unknown))
            if quantize.get("kv") not in (None, "int8"):
                raise ValueError("quantize['kv'] must be 'int8', got %r"
                                 % (quantize.get("kv"),))
        if logprobs < 0:
            raise ValueError("logprobs must be >= 0")
        if logprobs and speculative:
            raise ValueError(
                "logprobs=K cannot combine with speculative decoding: "
                "accepted draft tokens have no single per-step target "
                "distribution to report")
        if logprobs and parallel and parallel.get("tp", 1) > 1:
            raise ValueError(
                "logprobs=K cannot combine with tensor parallelism yet "
                "(the top-K gather is not sharded)")
        self._logprobs_k = int(logprobs)
        self._quantize_cfg = dict(quantize) if quantize else None
        if excursion not in (None, False) and not isinstance(excursion, dict):
            raise ValueError("excursion must be None, False, or a dict")
        if qos is not None:
            if not isinstance(qos, dict):
                raise ValueError(
                    'qos must be a dict like {"tenants": {...}, '
                    '"default": {...}, "preempt": bool, "slo_shed": bool}')
            unknown = set(qos) - {"tenants", "default", "preempt",
                                  "slo_shed"}
            if unknown:
                raise ValueError("unknown qos keys: %s" % sorted(unknown))
            for name, spec in {**(qos.get("tenants") or {}),
                               "default": qos.get("default") or {}}.items():
                bad = set(spec) - {"rate", "burst", "max_pages", "weight"}
                if bad:
                    raise ValueError(
                        "unknown qos tenant keys for %r: %s"
                        % (name, sorted(bad)))
                if "rate" in spec and spec["rate"] is not None \
                        and float(spec["rate"]) <= 0:
                    raise ValueError(
                        "qos tenant %r rate must be > 0" % (name,))
                if "max_pages" in spec and spec["max_pages"] is not None \
                        and int(spec["max_pages"]) < 1:
                    raise ValueError(
                        "qos tenant %r max_pages must be >= 1" % (name,))
                if "weight" in spec and spec["weight"] is not None \
                        and float(spec["weight"]) <= 0:
                    raise ValueError(
                        "qos tenant %r weight must be > 0" % (name,))
        self._qos_cfg = dict(qos) if qos else None
        tp_degree = 1
        if parallel is not None:
            if not isinstance(parallel, dict):
                raise ValueError('parallel must be a dict like {"tp": N}')
            unknown = set(parallel) - {"tp"}
            if unknown:
                raise ValueError("unknown parallel keys: %s"
                                 % sorted(unknown))
            tp_degree = parallel.get("tp", 1)
            if not isinstance(tp_degree, int) or tp_degree < 1:
                raise ValueError("parallel['tp'] must be a positive int, "
                                 "got %r" % (tp_degree,))
        self._tp_degree = tp_degree
        self._tp = None  # TPPlan, built per (re)build when tp_degree > 1
        self.n_slots = n_slots
        self.max_queue = max_queue
        self.default_timeout = default_timeout
        self.eos_token = eos_token
        self.top_k = top_k
        self.decode_chunk = decode_chunk
        self.breaker = breaker
        self.step_hooks: List[Callable] = list(step_hooks)
        self._requested_max_len = max_len
        self._requested_page_size = page_size
        self._requested_pool_pages = pool_pages
        self._requested_max_queued_pages = max_queued_pages
        self._requested_prefill_chunk = prefill_chunk
        self._prefix_cache_cfg = prefix_cache
        self._speculative_cfg = dict(speculative) if speculative else None
        self._draft_net = None  # resolved once; "self" re-resolves per swap
        self._prompt_buckets = tuple(sorted(set(int(b) for b in
                                                prompt_buckets)))
        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()  # guarded by: _cond
        self._slots: List[Optional[_GenRequest]] = [None] * n_slots  # guarded by: _cond
        self._closed = False  # guarded by: _cond
        self._kill = False  # guarded by: _cond
        self._draining = False  # guarded by: _cond
        self._swap_net = None  # guarded by: _cond
        self._swap_in_progress = False  # guarded by: _cond
        self._swap_error: Optional[BaseException] = None  # guarded by: _cond
        self._swap_done = threading.Event()
        self._step_ewma = 0.01  # guarded by: _cond
        self._pages_demand_queued = 0  # guarded by: _cond
        # QoS control plane (armed by `qos={...}`): per-tenant token
        # buckets, the batch→interactive preemption switch, and the
        # SLO-shed estimators (queue-wait + prefill-chunk EWMAs; the
        # decode-step EWMA above is shared with retry_after estimates)
        _q = self._qos_cfg or {}
        self._preempt_enabled = self._qos_cfg is not None \
            and _q.get("preempt", True) is not False
        self._slo_shed_enabled = self._qos_cfg is not None \
            and _q.get("slo_shed", True) is not False
        self._default_quota = dict(_q.get("default") or {}) or None
        self._tenants: dict = {}  # guarded by: _cond
        for _name, _spec in (_q.get("tenants") or {}).items():
            self._tenants[_name] = _TenantState(
                rate=_spec.get("rate"), burst=_spec.get("burst"),
                max_pages=_spec.get("max_pages"),
                weight=_spec.get("weight"))
        # batch-lane weighted-fair queueing (stride scheduling): each
        # tenant's pass value advances by admitted-span/weight; the
        # floor tracks the last admitted tenant's pre-charge pass so an
        # idle tenant rejoins AT the floor instead of banking credit
        self._wfq_pass: dict = {}  # guarded by: _cond
        self._wfq_floor = 0.0  # guarded by: _cond
        self._queue_wait_ewma = 0.0  # guarded by: _cond
        # admission wait, summed where admission happens: seconds
        # queued over requests that left the queue, into a slot or,
        # expired, to a typed shed
        self.queue_wait_s = 0.0  # guarded by: _cond
        self.admitted = 0  # guarded by: _cond
        # the scheduler thread's account of its own time; written by
        # that thread alone, read by stats()
        self._phases = observability.SchedulerPhases()
        # set-up's account: `_build`'s own leaf phases, on whichever
        # thread builds (the constructor's, or the scheduler's at a
        # swap that rebuilds), and JAX's compile pipeline, which the
        # whole process shares
        self._build_phases = observability.ThreadPhases(
            observability.BUILD_PHASES)
        self._weight_hash_bytes = 0  # cumulative over builds
        self._weight_hash_host_bytes = 0  # of them, crossed to the host
        self._compile = observability.compile_account()
        # dispatches issued and not yet collected, oldest first: at most
        # one decode dispatch and the prefills issued before it while
        # the scheduler blocks in a collect (scheduler-thread-owned)
        self._inflight: collections.deque = collections.deque()
        self._collected_at = 0.0  # monotonic time of the last collect
        self._chunk_ewma = 0.0  # guarded by: _cond
        self._role = role
        # counters (observable state for tests/telemetry)
        self.submitted = 0  # guarded by: _cond
        self.served = 0  # guarded by: _cond
        self.shed_overload = 0  # guarded by: _cond
        self.shed_out_of_pages = 0  # guarded by: _cond
        self.shed_deadline = 0  # guarded by: _cond
        self.shed_unavailable = 0  # guarded by: _cond
        self.failures = 0  # guarded by: _cond
        self.prefills = 0  # guarded by: _cond
        self.prefill_chunks = 0  # guarded by: _cond
        self.decode_steps = 0  # guarded by: _cond
        self.active_slot_steps = 0  # guarded by: _cond
        self.tokens_generated = 0  # guarded by: _cond
        self.swaps = 0  # guarded by: _cond
        self.weight_casts = 0  # guarded by: _cond
        # QoS counters: batch-lane slots yielded to interactive
        # pressure, SLO-estimator door sheds, per-tenant quota sheds
        self.preemptions = 0  # guarded by: _cond
        self.slo_sheds = 0  # guarded by: _cond
        self.shed_quota = 0  # guarded by: _cond
        self.shed_page_quota = 0  # guarded by: _cond
        # latency-tier counters (prefix cache + speculative decoding)
        self.prompt_tokens = 0  # guarded by: _cond
        self.prefix_hits = 0  # guarded by: _cond
        self.prefix_misses = 0  # guarded by: _cond
        self.prefix_hit_tokens = 0  # guarded by: _cond
        # slot states overwritten from zeros at admission
        self.state_resets = 0  # guarded by: _cond
        self.spec_steps = 0  # guarded by: _cond
        self.spec_proposed = 0  # guarded by: _cond
        self.spec_accepted = 0  # guarded by: _cond
        self.spec_emitted = 0  # guarded by: _cond
        # observability: a ModelServer-owned engine shares the server's
        # recorder + registry (one flight_record / metrics surface per
        # replica); a standalone engine gets its own
        self.recorder = recorder if recorder is not None \
            else observability.FlightRecorder()
        self.metrics = metrics if metrics is not None \
            else observability.MetricsRegistry()
        self.metrics.register_stats("decode_engine", self.stats)
        self._gen_latency_hist = self.metrics.histogram(
            "decode_engine_generate_latency_ms")
        # time-to-first-token: observed at the first emitted token of
        # every FRESH request (resumed/migrated requests already paid
        # their TTFT on the original replica)
        self._ttft_hist = self.metrics.histogram(
            "decode_engine_ttft_ms")
        if excursion is not False:
            exc_cfg = dict(excursion) if excursion else {}
            self._gen_latency_hist.enable_excursion(
                quantile=float(exc_cfg.get("quantile", 0.99)),
                min_count=int(exc_cfg.get("min_count", 50)),
                hook=lambda v, bound, trace: self.recorder.pin(
                    trace, "excursion", latency_ms=round(v, 3),
                    bound_ms=round(bound, 3)))
        self.metrics.gauge("decode_engine_queued",
                           lambda: len(self._queue))
        self.metrics.gauge("decode_engine_pages_in_use",
                           lambda: self._pool.in_use())
        if self._tp_degree > 1:
            # per-shard gauges carry a {tp_rank} label (parsed out of
            # the series name by MetricsRegistry.exposition — one
            # metric name, degree series on the gateway scrape page);
            # shards are symmetric by construction, so every rank
            # reports the same per-shard KV residency
            for _r in range(self._tp_degree):
                self.metrics.gauge(
                    'decode_engine_tp_shard_kv_bytes_per_token'
                    '{tp_rank="%d"}' % _r,
                    lambda: self._kinds.counters["kv_bytes_per_token"]
                    // self._tp_degree)
        if self.breaker is not None \
                and getattr(self.breaker, "on_event", None) is None:
            # standalone engines wire breaker transitions themselves; a
            # server-owned breaker already feeds the shared recorder
            self.breaker.on_event = lambda state: self.recorder.event(
                "breaker", state=state)
        # everything that moves KV pages between engines: leases,
        # migration, disaggregated roles, the cluster prefix cache. It
        # gets the lock, the pool of each build and four callables,
        # never the engine
        from deeplearning4j_tpu.serving.kv_handoff import HandoffPlane
        self._pool = None  # the PagePool of the current build
        self._routing = None  # its RoutingAccount
        self._plane = HandoffPlane(
            self._cond, role=role, handoff_ttl=handoff_ttl,
            recorder=self.recorder, breaker=self.breaker,
            read_slot=self._read_slot, write_slot=self._write_slot,
            enqueue_resumed=self._enqueue_resumed, in_flight=self.pending)
        self._build(net)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="decode-engine-scheduler")
        self._thread.start()

    # -- compiled machinery ------------------------------------------------
    def _build(self, net) -> None:
        """(Re)build the compiled prefill/decode machinery and the paged
        device state for `net`. Called at construction and after a
        drained weight swap; jit caches are per-engine closures, so a
        swap to a differently-shaped net recompiles cleanly.

        The calling thread is in one phase of
        `observability.BUILD_PHASES` from the first statement to the
        last (docs/observability.md, "Set-up and rebuilds"); they time
        the host and add no sync."""
        ph = self._build_phases
        ph.begin_iteration()
        ph.enter("build.plan")
        try:
            self._build_in_phases(net, ph)
        finally:
            ph.close()

    def _build_in_phases(self, net, ph) -> None:
        import jax

        from deeplearning4j_tpu.models.transformer import GPTPlan
        from deeplearning4j_tpu.serving import (
            block_state,
            decode_programs,
            weight_digest,
        )
        from deeplearning4j_tpu.serving.page_pool import PagePool

        plan = GPTPlan(net)
        self._refuse_unsupported(plan)
        # tensor-parallel plan: geometry validated HERE (construction /
        # weight swap), so a bad tp config is a typed ValueError before
        # any device work; None means the single-device engine
        tp = None
        if self._tp_degree > 1:
            from deeplearning4j_tpu.serving.tp_engine import TPPlan

            tp = TPPlan(net, plan, self._tp_degree)
        self._tp = tp
        tp_axis = tp.axis if tp is not None else None
        tp_shard = tp.degree if tp is not None else None
        L = self._requested_max_len or plan.emb.max_length
        if plan.emb.positional:
            L = min(L, plan.emb.max_length)
        if L < 2:
            raise ValueError(f"max_len {L} leaves no room to decode")
        S, cdt = self.n_slots, plan.cdt
        buckets = tuple(b for b in self._prompt_buckets if b <= L) or \
            (min(32, L),)
        from deeplearning4j_tpu.serving.model_server import _bucket

        # page geometry: the logical per-slot cache length is max_len
        # rounded up to a whole number of pages AND (when chunking can
        # activate) a whole number of prefill chunks, so every padded
        # prefill width fits the slot's page-table row. A page longer
        # than max_len is clamped to max_len's pow-2 ceiling (one page
        # per slot)
        page = _bucket(L, self._requested_page_size)
        C = self._requested_prefill_chunk
        chunk_enabled = C < L
        M = max(page, C) if chunk_enabled else page
        L_logical = -(-L // M) * M
        n_pages_max = L_logical // page
        pool_pages = self._requested_pool_pages
        if pool_pages is None:
            # default: the dense r5 slotted cache's exact KV budget
            pool_pages = S * n_pages_max
        max_queued_pages = self._requested_max_queued_pages
        if max_queued_pages is None:
            max_queued_pages = 4 * pool_pages
        # buffer donation lets the steps write the page pools where they
        # lie in HBM; it is necessary, not sufficient: the decode write
        # must also keep the pools' layout (`_write_token`), or XLA
        # copies ~pool_pages*page*layers of KV twice a step around it.
        # CPU (the test backend) does not support donation and would
        # warn once per dispatch
        donate = jax.default_backend() != "cpu"
        self._donate = donate

        # quantized-KV tier: resolved at BUILD time so the kill switch
        # (DL4J_TPU_NO_INT8_KV) flips the pool dtypes themselves, not
        # just the kernel dispatch — the bench A/B compares genuinely
        # different cache residency, and a killed build serves the
        # exact full-precision numerics
        from deeplearning4j_tpu.serving import quantize as _qz
        kv_quant = "int8" if (self._quantize_cfg is not None
                              and self._quantize_cfg.get("kv") == "int8"
                              and _qz.int8_kv_enabled()) else None

        # what each block keeps between tokens, by the kind the plan
        # declares for it (serving/block_state.py)
        # a slot's ring of window pages, the page pool's second class (0:
        # no block reads a window, and nothing below is other than it was)
        ring = block_state.ring_pages(plan, page, C if chunk_enabled
                                      else page)
        env = SimpleNamespace(
            n_slots=S, page=page, pool_pages=pool_pages, cdt=cdt,
            kv_quant=kv_quant, tp_shard=tp_shard, tp_axis=tp_axis,
            ring_pages=ring)
        states = block_state.block_states(plan, env)
        # the routed experts' counts: the new plan's facts, the totals
        # the engine's across swaps
        routing = block_state.RoutingAccount(plan, self._cond,
                                             self._routing)
        programs = decode_programs.build_programs(
            plan, states, n_slots=S, page=page, L_logical=L_logical,
            decode_chunk=self.decode_chunk, top_k=self.top_k,
            logprobs=self._logprobs_k, tp=tp, donate=donate,
            ring_pages=ring)
        # weights placed once per (re)build: permuted + head/width-
        # sharded over the mesh under TP (a weight swap reshards from
        # the swapped net's clean host copy), then cast to the compute
        # dtype by a program of their own; where the net's two dtypes
        # are equal, the net's own tree. A rebuild drops the old
        # resident trees (the draft's with its decoder) before it makes
        # the new: two of them beside two nets' masters may not fit
        ph.enter("build.weights")
        self._weights = self._spec = None
        placed = tp.shard_params(net._params) if tp is not None \
            else net._params
        self._weights = plan.resident_weights(placed)
        uncast = {id(x) for x in jax.tree_util.tree_leaves(placed)}
        self._weights_resident_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self._weights)
            if id(x) not in uncast)
        with self._cond:
            self.weight_casts += int(self._weights is not placed)
        ph.enter("build.plan")
        self._plan = plan
        self._states = states
        self._kinds = block_state.describe(states, env)
        self._routing = routing
        self._net = net
        self.max_len = L
        self.page_size = page
        self.pool_pages = pool_pages
        self.max_queued_pages = max_queued_pages
        self.prefill_chunk = C
        self._chunk_enabled = chunk_enabled
        self._n_pages_max = n_pages_max
        self._L_logical = L_logical
        self.prompt_buckets = buckets
        self._decode_step = programs.decode_step
        self._decode_chunked = programs.decode_chunked
        self._prefill = programs.prefill
        self._prefill_chunk_fn = programs.prefill_chunk_fn
        # content digest of the served weights, folded on the device
        # (`weight_digest`): KV handoffs are stamped with the sender's
        # digest and refused typed on mismatch — a page of KV computed
        # under other weights must never re-bind here (and never seed
        # this engine's prefix cache)
        _leaves = jax.tree_util.tree_leaves(net._params)
        _nbytes = sum(int(_leaf.nbytes) for _leaf in _leaves)
        ph.enter("build.weight_hash", bytes=_nbytes)
        self._weight_version, _crossed = weight_digest.weight_version(
            _leaves)
        with self._cond:
            self._weight_hash_bytes += _nbytes
            self._weight_hash_host_bytes += _crossed
        ph.enter("build.plan")
        # latency tier: prefix cache + speculative decoder are rebuilt
        # with the geometry on every (re)build, so a weight swap always
        # starts them cold — stale pages can never serve new weights
        self._prefix_cache = None
        if self._prefix_cache_cfg is not None \
                and self._prefix_cache_cfg is not False:
            from deeplearning4j_tpu.serving.prefix_cache import PrefixCache

            pc_kw = {} if self._prefix_cache_cfg is True \
                else dict(self._prefix_cache_cfg)
            self._prefix_cache = PrefixCache(page, **pc_kw) \
                .bind_guard(self._cond).bind_recorder(self.recorder) \
                .bind_version(self._weight_version)
        self._spec = None
        if self._speculative_cfg is not None:
            from deeplearning4j_tpu.serving.speculative import (
                SpeculativeDecoder,
                resolve_draft_net,
            )

            cfg = dict(self._speculative_cfg)
            draft = cfg.pop("draft", None)
            if draft is None:
                draft = cfg.pop("net", None)  # alias; both given ->
                # "net" survives into the unknown-option check below
            k = int(cfg.pop("k", 4))
            if cfg:
                raise ValueError(
                    f"unknown speculative options {sorted(cfg)}")
            if draft == "self" or self._draft_net is None:
                self._draft_net = resolve_draft_net(draft, net)
            self._spec = SpeculativeDecoder(
                target_plan=plan, target_net=net,
                draft_net=self._draft_net, k=k, n_slots=S, page=page,
                L_logical=L_logical, pool_pages=pool_pages,
                top_k=self.top_k, donate=donate, kv_quant=kv_quant,
                tp=tp, target_weights=self._weights)
        ph.enter("build.state")
        old = self._pool
        self._pool = PagePool(
            self._cond, n_slots=S, page_size=page, pool_pages=pool_pages,
            n_pages_max=n_pages_max, prefill_width=self._prefill_width,
            prefix_cache=self._prefix_cache, leases=self._plane.leases,
            recorder=self.recorder, ring_pages=ring)
        if old is not None:
            with self._cond:  # the peaks are the engine's, across swaps
                self._pool.in_use_peak = old.in_use_peak
                self._pool.ring_in_use_peak = old.ring_in_use_peak
        self._plane.on_rebuild(
            pool=self._pool, weight_version=self._weight_version,
            kv_quant=kv_quant, max_len=L, n_blocks=len(states),
            kv_only=self._kinds.kv_only)
        self._reset_device_state()

    def _refuse_unsupported(self, plan) -> None:
        """Engine features that cannot hold a composed block, or what
        one of the plan's kinds of block keeps, yet: refused typed when
        the engine is built (construction, weight swap), so nothing is
        silently wrong later. Replay (preemption folding emitted tokens
        back into the prompt) needs no state of the old slot and works."""
        from deeplearning4j_tpu.serving import block_state

        refused = []
        if plan.composed and self._speculative_cfg is not None:
            refused.append("speculative decoding (draft and verifier "
                           "assume TransformerBlock K/V)")
        if plan.composed and self._tp_degree > 1:
            refused.append("parallel={'tp': N} (no sharding rule for "
                           "composed blocks)")
        # what keeps, requantizes or carries away a block's cache, in this
        # engine's words; a kind that cannot hold one says `{what}` of it
        asked = {}
        if self._prefix_cache_cfg not in (None, False):
            asked["prefix_cache"] = ("prefix_cache (a hit needs {what} at "
                                     "the shared boundary; only K/V pages "
                                     "are kept)")
        if self._quantize_cfg and self._quantize_cfg.get("kv"):
            asked["quantize_kv"] = ("quantize={{'kv': 'int8'}} (no "
                                    "quantized form of {what})")
        if self._role != "both":
            asked["role"] = (f"role={self._role!r} (KV handoff does not "
                             "carry {what})")
        refused += block_state.refused(plan, asked)
        if refused:
            raise block_state.RecurrentStateUnsupported(
                "not supported for this network's blocks yet: "
                + "; ".join(refused))

    def _reset_device_state(self) -> None:
        """Fresh page pools + page table + per-slot state (construction,
        weight swap, or recovery after a failed device step — a raised
        dispatch may have invalidated donated buffers). Callers
        guarantee no slot holds a request when this runs, so the free
        list rebuilds to the full pool; queued requests keep their
        reservations (they hold no device state). What the chip still
        holds unread ran on the state this replaces: dropped."""
        import jax
        import jax.numpy as jnp

        self._discard_in_flight()
        S = self.n_slots
        caches = [st.alloc() for st in self._states]
        if self._tp is not None:
            # head axis (axis 1 in every pool + scale-sidecar layout)
            # over `tp`: each device owns Hkv/N heads of EVERY page, so
            # the page table / free list / refcounts below stay
            # host-global and byte-identical to the single-device engine
            caches = [tuple(self._tp.shard_pool(x) for x in c)
                      for c in caches]
        self._caches = caches
        self._tok = jnp.zeros((S,), jnp.int32)
        self._pos = jnp.zeros((S,), jnp.int32)
        self._keys = jnp.stack([jax.random.PRNGKey(i) for i in range(S)])
        self._temps = jnp.zeros((S,), jnp.float32)
        # whole free list, zeroed page table, cleared prefix cache,
        # leases' page ownership voided
        self._pool.reset()
        # the active mask is read by stats() on caller threads — publish
        # it under the lock (the device arrays above are
        # scheduler-thread-owned)
        with self._cond:
            self._active = np.zeros((S,), bool)  # guarded by: _cond
        if self._spec is not None:
            self._spec.reset_state()

    # the pool's state under the names tests and `chip_smoke.py` read
    @property
    def _page_table(self):
        return self._pool.page_table

    @property
    def _free_pages(self):
        return self._pool._free_pages

    @property
    def pages_in_use_peak(self) -> int:
        return self._pool.in_use_peak

    # -- prefill geometry --------------------------------------------------
    def _bucket_for(self, t0: int) -> int:
        from deeplearning4j_tpu.serving.model_server import _bucket

        for b in self.prompt_buckets:
            if b >= t0:
                return b
        return _bucket(t0, self.max_len)  # pow-2 fallback past the buckets

    def _is_chunked(self, t0: int) -> bool:
        return self._chunk_enabled and t0 > self.prompt_buckets[-1] \
            and t0 > self.prefill_chunk

    def _prefill_width(self, t0: int) -> int:
        C = self.prefill_chunk
        return -(-t0 // C) * C if self._is_chunked(t0) \
            else self._bucket_for(t0)

    # -- observability -----------------------------------------------------
    # graftlint: hot-loop
    def _finish_obs(self, req: _GenRequest,
                    err: Optional[BaseException] = None, **attrs) -> None:
        """Terminal path for one generation request: stamp the
        timeline's decision, attach it to the typed error (in-process
        callers and the gateway payload both carry it), ring the flight
        recorder, deliver. Pure host-side work — safe inside hot-loop
        scopes. A batch-shared error instance is stamped last-writer-
        wins (see `observability.attach_trace`)."""
        decision = "served" if err is None else type(err).__name__
        req.trace.finish(decision)
        if err is not None:
            observability.attach_trace(err, req.trace)
        self.recorder.record(req.trace, decision, kind="generate",
                             tokens=len(req.tokens), **attrs)
        req.finish(err)

    # graftlint: hot-loop
    def _queue_waited(self, req: _GenRequest, now: float,
                      decision: Optional[str] = None) -> None:
        """A request leaves the queue: its own `queue-wait` span, and
        the serving front's two sums."""
        req.trace.add_timed("queue-wait", req.enqueued_at, now,
                            decision=decision)
        with self._cond:
            self.queue_wait_s += now - req.enqueued_at
            self.admitted += 1

    # graftlint: hot-loop
    def _shed_obs(self, trace, err: BaseException, **attrs) -> None:
        """Door-shed path (no request handle yet): finish the timeline
        with the typed decision and pin it in the failures ring."""
        decision = type(err).__name__
        trace.finish(decision)
        observability.attach_trace(err, trace)
        self.recorder.record(trace, decision, kind="generate", **attrs)

    # graftlint: hot-loop
    def _emit_token(self, req: _GenRequest, lp=None,
                    lp_idx: int = 0) -> None:
        """Per-emitted-token bookkeeping, called right after a token is
        appended to `req.tokens`: record the request's logprob entry
        (when it asked for K > 0; `lp` is the device-fetched
        (chosen, top_values, top_ids) batch, `lp_idx` this token's row),
        observe TTFT on a fresh request's first token, and publish into
        the request's stream sink (`streaming.TokenStream.publish` —
        O(1), never blocks on a consumer). A raising sink is a consumer
        bug: it is disarmed loudly so it can never poison the scheduler
        loop — the unary result still delivers."""
        if lp is not None and req.logprobs:
            kk = req.logprobs
            chosen, top_v, top_i = lp
            req.logprob_values.append({
                "token": int(req.tokens[-1]),
                "logprob": float(chosen[lp_idx]),
                "top_tokens": [int(t) for t in top_i[lp_idx][:kk]],
                "top_logprobs": [float(v) for v in top_v[lp_idx][:kk]],
            })
        if len(req.tokens) == 1 and req.resumed_at == 0 \
                and not req.preempted:
            self._ttft_hist.observe(
                1e3 * (time.monotonic() - req.enqueued_at),
                trace=req.trace)
        sink = req.sink
        if sink is not None:
            entry = req.logprob_values[-1] \
                if req.logprobs and req.logprob_values else None
            # the consumer's time inside the scheduler thread: a
            # counter, not a span a token
            t_sink = time.perf_counter()
            try:
                sink(len(req.tokens), req.tokens[-1], entry)
            # graftlint: disable=typed-error  scheduler protection: a
            # broken stream sink must cost the CONSUMER its stream, not
            # the engine its loop — logged + disarmed, decode continues
            except Exception:
                logger.exception(
                    "decode engine: stream sink failed; detaching it")
                req.sink = None
            ph = self._phases
            ph.sink_s += time.perf_counter() - t_sink
            ph.sink_n += 1

    def flight_record(self) -> dict:
        """Dump the flight recorder (request timelines + scheduler
        events) — shared with the owning `ModelServer` when there is
        one."""
        return self.recorder.dump()

    def metrics_snapshot(self) -> dict:
        return self.metrics.snapshot()

    def metrics_text(self, labels=None) -> str:
        return self.metrics.exposition(labels=labels)

    # -- public surface ----------------------------------------------------
    def submit(self, prompt_ids, n_tokens: int, *,
               temperature: float = 0.0, seed: int = 0,
               timeout: Optional[float] = None,
               tenant: Optional[str] = None,
               priority: str = "interactive",
               logprobs: int = 0,
               on_token: Optional[Callable] = None) -> _GenRequest:
        """Admit one generation request (non-blocking). Typed give-ups:
        `ServerOverloadedError` (queue full), `OutOfPagesError` (the
        paged KV pool cannot reserve this request's pages right now),
        `TenantQuotaExceededError` (THIS tenant's token-rate budget is
        spent — never another tenant's overload), `DeadlineExceededError`
        (already expired, or the SLO estimator proves the deadline
        cannot be met), `ServiceUnavailableError` (breaker open),
        `ServerClosedError`. `priority` is `"interactive"` (default) or
        `"batch"` — the batch lane fills otherwise-idle slots and
        yields them (preemption, `qos={...}`) under interactive
        pressure. Returns the request handle; `request.result()` blocks
        for the tokens. `logprobs=K` (K > 0; requires an engine built
        with `logprobs >= K`) asks for per-token logprob entries
        alongside the tokens; `on_token(cursor, token, logprob)` is the
        streaming emission hook — called from the scheduler thread per
        emitted token, it must be O(1) and non-blocking
        (`serving.streaming.TokenStream.publish` is the intended
        sink)."""
        if priority not in ("interactive", "batch"):
            raise ValueError(
                f"priority must be 'interactive' or 'batch', got "
                f"{priority!r}")
        if logprobs < 0:
            raise ValueError("logprobs must be >= 0")
        if logprobs > self._logprobs_k:
            raise ValueError(
                f"logprobs={logprobs} exceeds the engine's configured "
                f"logprobs={self._logprobs_k} — build the engine with "
                "logprobs=K to enable per-token logprob returns")
        prompt = np.asarray(prompt_ids)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError(
                f"submit expects one 1-D prompt of token ids, got shape "
                f"{prompt.shape}")
        if n_tokens < 1:
            raise ValueError("n_tokens must be >= 1")
        T0 = prompt.shape[0]
        if T0 + n_tokens > self.max_len:
            raise ValueError(
                f"prompt ({T0}) + n_tokens ({n_tokens}) exceeds the "
                f"engine's max_len {self.max_len} — raise max_len or "
                "shorten the request")
        need = self._pool.pages_for(T0, n_tokens)
        if not self._pool.can_hold(need):
            raise ValueError(
                f"request needs {need} KV pages of {self.page_size} "
                f"tokens but the pool holds only {self.pool_pages} — "
                "raise pool_pages or shorten the request")
        if self._role == "decode":
            from deeplearning4j_tpu.serving.kv_transfer import (
                KVTransferError,
            )

            raise KVTransferError(
                "decode-role engine accepts only resume_generate "
                "handoffs, not fresh prompts — route prefills to a "
                "prefill-role replica")
        trace = observability.maybe_trace()
        with self._cond:
            if self._closed:  # before the breaker door check: a closed
                # engine must say "closed" (terminal), not "retry later"
                err = ServerClosedError("decode engine is shut down")
                self._shed_obs(trace, err)
                raise err
        if self.breaker is not None:
            try:
                self.breaker.reject_if_open()
            except ServiceUnavailableError as e:
                with self._cond:
                    self.shed_unavailable += 1
                self._shed_obs(trace, e)
                raise
        timeout = self.default_timeout if timeout is None else timeout
        deadline = None if timeout is None else time.monotonic() + timeout
        req = _GenRequest(prompt.astype(np.int32), int(n_tokens),
                          float(temperature), int(seed), deadline,
                          tenant=tenant, priority=priority)
        req.n_pages = need
        req.trace = trace
        req.logprobs = int(logprobs)
        req.sink = on_token
        # a prefill-role engine never decodes: the finished prefill is
        # exported under a lease and the caller redirected
        req.handoff = self._role == "prefill"
        # cluster prefix fetch (None unless a directory and peers are
        # bound) rides the SUBMIT thread — wire I/O must never stall the
        # scheduler. `_admit` binds the verified payload, or drops it
        # and prefills cold (a fetch wasted on a door refusal below is
        # accepted; it touched no engine state)
        req.prefix_import = self._plane.fetch_prefix_for(req.prompt, tenant)
        with self._cond:
            if self._closed:
                err = ServerClosedError("decode engine is shut down")
                self._shed_obs(trace, err)
                raise err
            now = time.monotonic()
            # door-order contract (pinned by tests): expired corpses are
            # swept and the incoming request's own deadline is judged
            # BEFORE any capacity verdict — a dead request must hear
            # DeadlineExceededError, and a queue padded with dead
            # entries is not real backpressure. Then the tenant's OWN
            # quota, then the SLO estimate, and only then the shared
            # queue/page limits.
            if len(self._queue) >= self.max_queue \
                    or (self._pages_demand_queued
                        and self._pages_demand_queued + need
                        > self.max_queued_pages):
                self._sweep_expired_locked(now)
            if deadline is not None and deadline <= now:
                self.shed_deadline += 1
                err = DeadlineExceededError(
                    "deadline expired before admission; request shed at "
                    "the door")
                self._shed_obs(trace, err)
                raise err
            tstate = self._tenant_locked(tenant)
            if tstate is not None and tstate.rate:
                tstate.refill(now)
                if tstate.tokens < n_tokens:
                    tstate.shed_quota += 1
                    self.shed_quota += 1
                    retry = max(0.001,
                                (n_tokens - tstate.tokens) / tstate.rate)
                    err = TenantQuotaExceededError(
                        f"tenant {tenant!r} token-rate quota exhausted "
                        f"({tstate.tokens:.0f} of {n_tokens} tokens "
                        f"available at {tstate.rate:.0f} tok/s); retry "
                        f"in {retry:.3f}s", retry_after=retry)
                    self._shed_obs(trace, err, tenant=tenant,
                                   bucket_tokens=round(tstate.tokens, 1),
                                   rate=tstate.rate, n_tokens=int(n_tokens))
                    self.recorder.event(
                        "quota-shed", tenant=tenant,
                        bucket_tokens=round(tstate.tokens, 1),
                        rate=tstate.rate, n_tokens=int(n_tokens))
                    raise err
            if tstate is not None and tstate.max_pages is not None:
                # page-pool ceiling: this tenant's RESERVATIONS (queued
                # demand + resident requests) may not exceed max_pages.
                # Reservation accounting (n_pages, the cold cost) is
                # leak-proof by construction — it is recomputed from the
                # live queue/slots, never an incremental ledger
                live = self._tenant_pages_locked(tenant)
                if live + need > tstate.max_pages:
                    tstate.shed_page_quota += 1
                    self.shed_page_quota += 1
                    retry = max(0.001, self._step_ewma
                                * (len(self._queue) + 1))
                    err = TenantQuotaExceededError(
                        f"tenant {tenant!r} KV page quota exhausted "
                        f"({live} of {tstate.max_pages} pages reserved; "
                        f"{need} more needed); retry in {retry:.3f}s",
                        retry_after=retry)
                    self._shed_obs(trace, err, tenant=tenant,
                                   pages_reserved=live,
                                   max_pages=tstate.max_pages,
                                   pages_needed=need)
                    self.recorder.event(
                        "quota-shed", tenant=tenant, resource="pages",
                        pages_reserved=live,
                        max_pages=tstate.max_pages, pages_needed=need)
                    raise err
            if self._slo_shed_enabled and deadline is not None \
                    and self.decode_steps:
                # can this request provably not meet its deadline? The
                # estimate is grounded in OBSERVED EWMAs (hence the
                # decode_steps gate): expected queue wait + its prefill
                # chunks at the chunk EWMA + its tokens at the decode-
                # step EWMA. Shedding here costs nothing; admitting it
                # costs prefill the deadline then throws away.
                n_chunks = -(-T0 // self.prefill_chunk) \
                    if self._is_chunked(T0) else 1
                est = self._queue_wait_ewma \
                    + n_chunks * self._chunk_ewma \
                    + n_tokens * self._step_ewma
                if now + est > deadline:
                    self.slo_sheds += 1
                    err = DeadlineExceededError(
                        f"deadline unmeetable: needs ~{est:.3f}s "
                        f"(queue {self._queue_wait_ewma:.3f}s + "
                        f"{n_chunks} prefill chunks + {n_tokens} decode "
                        f"steps) but only "
                        f"{max(0.0, deadline - now):.3f}s remain; shed "
                        "before prefill")
                    self._shed_obs(trace, err,
                                   estimate_s=round(est, 4),
                                   queue_wait_ewma_s=round(
                                       self._queue_wait_ewma, 4),
                                   prefill_chunks=n_chunks,
                                   step_ewma_s=round(self._step_ewma, 5))
                    self.recorder.event(
                        "slo-shed", tenant=tenant,
                        estimate_s=round(est, 4),
                        queue_wait_ewma_s=round(self._queue_wait_ewma, 4),
                        prefill_chunks=n_chunks,
                        step_ewma_s=round(self._step_ewma, 5),
                        budget_s=round(max(0.0, deadline - now), 4))
                    raise err
            if len(self._queue) >= self.max_queue:
                self.shed_overload += 1
                retry = max(0.001, self._step_ewma
                            * (len(self._queue) / self.n_slots + 1))
                err = ServerOverloadedError(
                    f"generation queue full ({self.max_queue} pending); "
                    f"retry in {retry:.3f}s", retry_after=retry)
                self._shed_obs(trace, err, queue_depth=len(self._queue))
                raise err
            if self._pages_demand_queued \
                    and self._pages_demand_queued + need \
                    > self.max_queued_pages:
                # memory-side admission control: queued requests hold
                # no pages, but their aggregate DEMAND is bounded —
                # beyond `max_queued_pages` of page-wait-room, shed at
                # the door, typed, instead of queueing work the pool
                # cannot turn over soon. A LONE waiter always queues
                # (first clause): a request that fits the pool must
                # never be permanently shed by the aggregate cap, and
                # its retry_after would otherwise promise a retry that
                # could never succeed
                self.shed_out_of_pages += 1
                held = self._pool.in_use()
                n_live = sum(1 for r in self._slots if r is not None)
                retry = max(0.001, self._step_ewma
                            * (len(self._queue) + n_live + 1))
                err = OutOfPagesError(
                    f"KV page pool exhausted ({held}/{self.pool_pages} "
                    f"pages in use, {self._pages_demand_queued} queued "
                    f"demand of {self.max_queued_pages} allowed; {need} "
                    f"more needed); retry in {retry:.3f}s",
                    retry_after=retry)
                # the shed timeline AND the events ring both name the
                # page-demand decision — a flight_record dump after an
                # OutOfPages burst shows exactly which reservation the
                # door refused and what the pool looked like
                demand = self._pages_demand_queued
                self._shed_obs(trace, err, pages_needed=need,
                               pages_in_use=held,
                               queued_page_demand=demand,
                               max_queued_pages=self.max_queued_pages)
                self.recorder.event(
                    "shed", error="OutOfPagesError", pages_needed=need,
                    pages_in_use=held, queued_page_demand=demand,
                    max_queued_pages=self.max_queued_pages)
                raise err
            # debit the tenant's bucket only once EVERY door has passed:
            # a request shed by the shared queue/page limits above must
            # not also burn its tenant's budget
            if tstate is not None:
                if tstate.rate:
                    tstate.tokens -= n_tokens
                tstate.submitted += 1
            self._pages_demand_queued += need
            self.submitted += 1
            self._queue.append(req)
            trace.event("enqueue", queue_depth=len(self._queue),
                        pages_reserved=need,
                        prompt_len=int(T0), n_tokens=int(n_tokens))
            self._cond.notify_all()
        return req

    def _tenant_locked(self, tenant: Optional[str]):
        """This tenant's ledger (created on first sight, `default` quota
        applied), or None for untenanted traffic — which is untracked
        and unlimited, so pre-QoS callers see zero behavior change."""
        assert_owned(self._cond, "DecodeEngine._tenant_locked")
        if tenant is None:
            return None
        state = self._tenants.get(tenant)
        if state is None:
            spec = self._default_quota or {}
            state = _TenantState(rate=spec.get("rate"),
                                 burst=spec.get("burst"),
                                 max_pages=spec.get("max_pages"),
                                 weight=spec.get("weight"))
            self._tenants[tenant] = state
        return state

    def _tenant_pages_locked(self, tenant: str) -> int:
        """Pages currently reserved by `tenant`: queued demand plus
        every resident request's reservation."""
        assert_owned(self._cond, "DecodeEngine._tenant_pages_locked")
        return sum(r.n_pages for r in self._queue if r.tenant == tenant) \
            + sum(r.n_pages for r in self._slots
                  if r is not None and r.tenant == tenant)

    def _sweep_expired_locked(self, now: float) -> None:
        """Shed every already-expired QUEUED request with ITS truth
        (`DeadlineExceededError`), releasing its page reservation — so
        a queue padded with dead entries can never be the reason a live
        request hears `ServerOverloadedError`/`OutOfPagesError`."""
        assert_owned(self._cond, "DecodeEngine._sweep_expired_locked")
        if not any(r.expired(now) for r in self._queue):
            return
        keep: collections.deque = collections.deque()
        for req in self._queue:
            if req.expired(now):
                self._pages_demand_queued -= req.n_pages
                self._pool.release_locked(req)  # delta-pin release
                self.shed_deadline += 1
                self._queue_waited(req, now, "expired")
                self._finish_obs(req, DeadlineExceededError(
                    "deadline expired while queued; request shed before "
                    "prefill"))
            else:
                keep.append(req)
        self._queue = keep

    def set_tenant_quota(self, tenant: str, rate: Optional[float] = None,
                         burst: Optional[float] = None,
                         max_pages: Optional[int] = None,
                         weight: Optional[float] = None) -> None:
        """Install (or with `rate=None` clear) tenant `tenant`'s
        token-rate quota — and with `max_pages` its KV page ceiling
        (`None` clears it), with `weight` its batch-lane fair-queueing
        share (`None` keeps the current weight; default 1.0) — at
        runtime; the seam the gateway's `set_tenant_quota` RPC lands
        on. The bucket restarts full at the new burst; counters survive
        the change."""
        if weight is not None and float(weight) <= 0:
            raise ValueError("tenant weight must be > 0")
        with self._cond:
            state = self._tenant_locked(tenant)
            state.rate = None if rate is None else float(rate)
            state.burst = float(burst) if burst is not None \
                else (state.rate if state.rate else 0.0)
            state.tokens = state.burst
            state.max_pages = None if max_pages is None else int(max_pages)
            if weight is not None:
                state.weight = float(weight)
            state.last_refill = time.monotonic()
        self.recorder.event("quota-set", tenant=tenant, rate=rate,
                            burst=burst, max_pages=max_pages,
                            weight=weight)

    # -- the KV hand-off plane's public surface (serving/kv_handoff.py) ----
    def bind_prefix_directory(self, directory, holder_id: str,
                              peers: Optional[Callable] = None, *,
                              fetch_timeout: float = 5.0,
                              frame_pages: int = 8,
                              min_fetch_pages: int = 1) -> "DecodeEngine":
        """Join a cluster-wide `PrefixDirectory`
        (`HandoffPlane.bind_prefix_directory`). Chainable."""
        self._plane.bind_prefix_directory(
            directory, holder_id, peers, fetch_timeout=fetch_timeout,
            frame_pages=frame_pages, min_fetch_pages=min_fetch_pages)
        return self

    def prefix_depth(self, prompt_ids, tenant: Optional[str] = None) -> int:
        """Resident prefix pages held for `prompt_ids`."""
        return self._plane.prefix_depth(prompt_ids, tenant)

    def prefix_chains(self) -> dict:
        """Every resident chain key at the current weight version."""
        return self._plane.prefix_chains()

    def export_prefix(self, prompt_ids, have_pages: int = 0,
                      tenant: Optional[str] = None,
                      frame_pages: Optional[int] = None,
                      timeout: Optional[float] = None) -> dict:
        """Holder-side cluster-prefix export: the framed header of a
        leased `kind="prefix"` handoff."""
        return self._plane.export_prefix(prompt_ids, have_pages, tenant,
                                         frame_pages, timeout)

    def fetch_handoff_header(self, handoff_id: str, skip_pages: int = 0,
                             frame_pages: Optional[int] = None) -> dict:
        """The blockless header of a leased handoff."""
        return self._plane.fetch_handoff_header(handoff_id, skip_pages,
                                                frame_pages)

    def fetch_handoff_frame(self, handoff_id: str, frame: int,
                            skip_pages: int = 0,
                            frame_pages: Optional[int] = None) -> dict:
        """One bounded frame of a leased handoff."""
        return self._plane.fetch_handoff_frame(handoff_id, frame,
                                               skip_pages, frame_pages)

    def fetch_handoff(self, handoff_id: str) -> dict:
        """The leased payload for `handoff_id` (extends its TTL)."""
        return self._plane.fetch_handoff(handoff_id)

    def commit_handoff(self, handoff_id: str) -> bool:
        """The receiver resumed: free the shipped pages. Idempotent."""
        return self._plane.commit_handoff(handoff_id)

    def abort_handoff(self, handoff_id: str) -> bool:
        """The transfer failed downstream: reclaim the pages now."""
        return self._plane.abort_handoff(handoff_id)

    def migrate_slots(self, wait: Optional[float] = 5.0) -> int:
        """Export EVERY in-flight request as a leased handoff; each
        waiter's `result()` raises the `SlotMigratedError` redirect."""
        return self._plane.migrate_slots(wait)

    def resume_submit(self, payload: dict,
                      timeout: Optional[float] = None, *,
                      on_token: Optional[Callable] = None) -> _GenRequest:
        """Admit a fetched handoff payload (verified, then through
        `_enqueue_resumed`)."""
        return self._plane.resume_submit(payload, timeout,
                                         on_token=on_token)

    def resume_generate(self, payload: dict,
                        timeout: Optional[float] = None, *,
                        on_token: Optional[Callable] = None):
        """Blocking `resume_submit`: the TAIL tokens generated here."""
        return self._plane.resume_generate(payload, timeout,
                                           on_token=on_token)

    def _enqueue_resumed(self, payload: dict, timeout: Optional[float],
                         on_token: Optional[Callable]) -> _GenRequest:
        """The scheduler's door for a handoff payload the plane has
        verified against this engine's weights and geometry: a request
        whose shipped pages re-bind at admission (warm) or that
        re-prefills from the prompt (cold). The deadline is the SMALLER
        of the sender's remaining budget and `timeout`. No token-rate
        debit (the sender charged it); the queue bound, the deadline and
        the tenant's page ceiling apply as in `submit`."""
        from deeplearning4j_tpu.serving.kv_transfer import KVTransferError

        prompt = np.asarray(payload["prompt"], np.int32)
        n_tokens = int(payload["n_tokens"])
        rems = [t for t in (payload.get("deadline_remaining"), timeout)
                if t is not None]
        if not rems and self.default_timeout is not None:
            rems = [self.default_timeout]
        deadline = time.monotonic() + min(rems) if rems else None
        req = _GenRequest(prompt, n_tokens,
                          float(payload["temperature"]),
                          int(payload["seed"]), deadline,
                          tenant=payload.get("tenant"),
                          priority=payload.get("priority") or "interactive")
        req.trace = observability.maybe_trace()
        req.tokens = [int(t) for t in payload["tokens"]]
        req.resumed_at = int(payload["resumed_at"])
        req.preempted = int(payload["preempted"])
        req.logprobs = int(payload.get("logprobs", 0) or 0)
        if req.logprobs > self._logprobs_k:
            raise KVTransferError(
                f"handoff requests logprobs={req.logprobs} but the "
                f"receiving engine was built with logprobs="
                f"{self._logprobs_k}")
        req.logprob_values = list(payload.get("logprob_values") or [])
        req.sink = on_token
        omitted = 0
        if payload["kind"] == "cold":
            # fold emitted tokens into the prompt exactly like a
            # preemption resume: re-prefill reproduces the sequence
            if len(req.tokens) > req.resumed_at:
                req.prompt = np.concatenate(
                    [req.prompt, np.asarray(req.tokens[req.resumed_at:],
                                            np.int32)])
                req.resumed_at = len(req.tokens)
            t0 = req.prompt.shape[0]
            req.n_pages = self._pool.pages_for(
                t0, max(1, n_tokens - req.resumed_at))
        else:
            req.import_state = payload
            omitted = int(payload.get("pages_omitted", 0))
            t0 = prompt.shape[0]
            span = t0 + max(1, n_tokens - req.resumed_at) - 1
            req.n_pages = max(-(-span // self.page_size),
                              omitted + int(payload["pages_shipped"]))
        if req.n_pages > self.pool_pages:
            raise KVTransferError(
                f"handoff needs {req.n_pages} KV pages but the "
                f"receiving pool holds only {self.pool_pages}")
        with self._cond:
            if self._closed:
                err = ServerClosedError("decode engine is shut down")
                self._shed_obs(req.trace, err)
                raise err
            now = time.monotonic()
            if deadline is not None and deadline <= now:
                self.shed_deadline += 1
                err = DeadlineExceededError(
                    "deadline expired before handoff admission")
                self._shed_obs(req.trace, err)
                raise err
            if len(self._queue) >= self.max_queue:
                self.shed_overload += 1
                retry = max(0.001, self._step_ewma
                            * (len(self._queue) / self.n_slots + 1))
                err = ServerOverloadedError(
                    f"generation queue full ({self.max_queue} pending); "
                    f"retry in {retry:.3f}s", retry_after=retry)
                self._shed_obs(req.trace, err)
                raise err
            tstate = self._tenant_locked(req.tenant)
            if tstate is not None:
                # no token-rate debit: the sender already charged this
                # request's tokens at original submission — migrating
                # must not bill a tenant twice. The page ceiling still
                # applies: resident pages are resident pages
                if tstate.max_pages is not None:
                    live = self._tenant_pages_locked(req.tenant)
                    if live + req.n_pages > tstate.max_pages:
                        tstate.shed_page_quota += 1
                        self.shed_page_quota += 1
                        err = TenantQuotaExceededError(
                            f"tenant {req.tenant!r} KV page quota "
                            f"exhausted ({live} of {tstate.max_pages} "
                            f"pages reserved; {req.n_pages} more needed)",
                            retry_after=max(0.001, self._step_ewma))
                        self._shed_obs(req.trace, err, tenant=req.tenant)
                        self.recorder.event(
                            "quota-shed", tenant=req.tenant, resource="pages",
                            pages_reserved=live,
                            max_pages=tstate.max_pages,
                            pages_needed=req.n_pages)
                        raise err
                tstate.submitted += 1
            if omitted:
                # delta handoff: the sender elided the first `omitted`
                # chain pages because this engine's directory entry
                # claimed them resident — pin them NOW (refcounted), so
                # eviction cannot race the bind; refused typed when the
                # chain is no longer deep enough (the sender's ladder
                # re-sends without skip_pages)
                have = self._pool.pin_prefix_locked(prompt, req.tenant,
                                                    omitted)
                if have is None:
                    err = KVTransferError(
                        f"delta handoff omits {omitted} prefix pages "
                        "but fewer are resident here; re-send without "
                        "skip_pages")
                    self._shed_obs(req.trace, err, tenant=req.tenant)
                    raise err
                req.nodes = have
                req.n_shared = omitted
            self.submitted += 1
            self._pages_demand_queued += req.n_pages
            self._queue.append(req)
            req.trace.event("resume-enqueue", kind=payload["kind"],
                            handoff_id=payload["handoff_id"],
                            pages_shipped=int(payload["pages_shipped"]),
                            emitted=len(req.tokens))
            self._cond.notify_all()
        return req

    def generate(self, prompt_ids, n_tokens: int, *,
                 temperature: float = 0.0, seed: int = 0,
                 timeout: Optional[float] = None,
                 tenant: Optional[str] = None,
                 priority: str = "interactive",
                 logprobs: int = 0,
                 on_token: Optional[Callable] = None):
        """Blocking convenience: submit + wait. Returns the generated
        tokens (1-D int32; shorter than `n_tokens` only on EOS) — or,
        with `logprobs=K > 0`, a dict `{"tokens", "logprobs"}` where
        `logprobs` carries one per-step entry (chosen-token logprob +
        top-K) per generated token."""
        req = self.submit(prompt_ids, n_tokens, temperature=temperature,
                          seed=seed, timeout=timeout, tenant=tenant,
                          priority=priority, logprobs=logprobs,
                          on_token=on_token)
        out = req.result()
        if logprobs:
            return {"tokens": out, "logprobs": list(req.logprob_values)}
        return out

    def pending(self) -> int:
        """Queued + in-slot generation requests — the engine's share of
        the load number least-loaded routing compares (folded into
        `ModelServer.pending()`)."""
        with self._cond:
            return len(self._queue) \
                + sum(1 for r in self._slots if r is not None)

    def stats(self) -> dict:
        with self._cond:
            queued = len(self._queue)
            active = sum(1 for r in self._slots if r is not None)
            held = self._pool.in_use()
            demand = self._pages_demand_queued
            used_positions = 0
            for r in self._slots:
                if r is None:
                    continue
                t0 = r.prompt.shape[0]
                used_positions += min(r.prefill_pos, t0) \
                    if r.prefill_pos is not None \
                    else t0 + len(r.tokens) - r.resumed_at
            tenants = {name: state.counters()
                       for name, state in sorted(self._tenants.items())}
            for name, counters in tenants.items():
                counters["pages_reserved"] = self._tenant_pages_locked(name)
            handoff = self._plane.stats()
        occupancy = (100.0 * self.active_slot_steps
                     / (self.decode_steps * self.n_slots)
                     if self.decode_steps else 0.0)
        # internal fragmentation of pages actually held by slots: the
        # tail of each request's last page (and not-yet-filled growth
        # room) is allocated-but-unused
        frag = (100.0 * (1.0 - used_positions
                         / (held * self.page_size))
                if held else 0.0)
        out = {"submitted": self.submitted, "served": self.served,
               "shed_overload": self.shed_overload,
               "shed_out_of_pages": self.shed_out_of_pages,
               "shed_deadline": self.shed_deadline,
               "shed_unavailable": self.shed_unavailable,
               "failures": self.failures, "prefills": self.prefills,
               "prefill_chunks": self.prefill_chunks,
               "decode_steps": self.decode_steps,
               "tokens_generated": self.tokens_generated,
               "slot_occupancy_pct": round(occupancy, 1),
               "n_slots": self.n_slots, "active_slots": active,
               "queued": queued, "swaps": self.swaps,
               # times the weights were cast to the compute dtype (once
               # a build; 0 where the net's two dtypes are equal) and
               # the bytes of that tree held beyond the net's own
               "weight_casts": self.weight_casts,
               "weights_resident_bytes": self._weights_resident_bytes,
               "max_len": self.max_len,
               "page_size": self.page_size,
               "pool_pages": self.pool_pages,
               "pages_in_use": held,
               "pages_in_use_peak": self._pool.in_use_peak,
               # the pool's second class, the window blocks' rings (0
               # where the net has none)
               "window_pages_in_use": self._pool.ring_in_use(),
               "window_pages_in_use_peak": self._pool.ring_in_use_peak,
               "queued_page_demand": demand,
               "max_queued_pages": self.max_queued_pages,
               "page_fragmentation_pct": round(frag, 1),
               "prefill_chunk": self.prefill_chunk,
               # what the caches say of themselves: bits and bytes a
               # token and a slot hold, blocks by kind
               **self._kinds.counters,
               "state_resets": self.state_resets,
               # the routed experts' decode-step counts, `moe_*`
               **self._routing.counters(),
               # tensor-parallel tier: degree 1 when off, so dashboards
               # can chart capacity without branching on key presence;
               # per-shard KV bytes is the per-chip residency claim
               "tp_degree": self._tp_degree,
               "tp_kv_bytes_per_token_per_shard":
                   self._kinds.counters["kv_bytes_per_token"]
                   // self._tp_degree,
               # QoS control plane: unconditional (zero / empty when
               # qos is off) so dashboards and the stats-schema
               # contract never branch on key presence
               "preemptions": self.preemptions,
               "slo_sheds": self.slo_sheds,
               "shed_quota": self.shed_quota,
               "shed_page_quota": self.shed_page_quota,
               "tenants": tenants,
               # the KV hand-off plane's counters: unconditional (all
               # zero while nothing is bound or migrated) so the
               # stats-schema contract and dashboards never branch on
               # key presence
               **handoff,
               "cluster_prefix_hit_tokens_pct": round(
                   100.0 * handoff["cluster_prefix_hit_tokens"]
                   / self.prompt_tokens, 1) if self.prompt_tokens
                   else 0.0,
               "prompt_buckets": list(self.prompt_buckets),
               # the scheduler thread's time by leaf phase, and the
               # admission wait: cumulative, so two readings bracket a
               # window
               "loop": dict(
                   self._phases.counters(),
                   prefill_sorted_n=self._routing.prefill_sorted_n),
               "queue_wait_s": self.queue_wait_s,
               "admitted": self.admitted,
               # set-up's account, cumulative over builds: seconds and
               # spans of each phase of `_build`, and JAX's compile
               # pipeline (the PROCESS's, not this engine's alone)
               "build": dict(
                   self._build_phases.counters(),
                   builds=self._build_phases.iterations,
                   weight_hash_bytes=self._weight_hash_bytes,
                   weight_hash_host_bytes=self._weight_hash_host_bytes),
               "compile": self._compile.counters()}
        if self._prefix_cache is not None:
            hit_pct = (100.0 * self.prefix_hit_tokens / self.prompt_tokens
                       if self.prompt_tokens else 0.0)
            out["prefix_hit_tokens_pct"] = round(hit_pct, 1)
            out["prefix_cache"] = dict(
                self._prefix_cache.stats(),
                hits=self.prefix_hits, misses=self.prefix_misses,
                hit_tokens=self.prefix_hit_tokens,
                prompt_tokens=self.prompt_tokens)
        if self._spec is not None:
            rate = (100.0 * self.spec_accepted / self.spec_proposed
                    if self.spec_proposed else 0.0)
            per_step = (self.spec_emitted / self.spec_steps
                        if self.spec_steps else 0.0)
            out["spec_accept_rate"] = round(rate, 1)
            out["spec_tokens_per_step"] = round(per_step, 3)
            out["speculative"] = dict(
                self._spec.stats(), verify_steps=self.spec_steps,
                proposed=self.spec_proposed, accepted=self.spec_accepted,
                emitted=self.spec_emitted)
        return out

    def model_bytes_per_chip(self) -> int:
        """Per-chip residency (weights + KV pools + scale sidecars), the
        bench's `tp_max_model_bytes_per_chip` capacity claim: under
        parallel={"tp": N} the sharded matmul slices and the pools' head
        axis each divide by N (replicated tensors — embeddings, LNs,
        biases, logits head — don't), so the largest servable model
        grows ~N× per chip. Array `.nbytes` is the GLOBAL size, hence
        the explicit division."""
        import jax

        pool_bytes = sum(x.nbytes
                         for c in self._caches
                         for x in c) // self._tp_degree
        if self._tp is not None:
            return self._tp.weight_bytes_per_chip(self._net._params) \
                + pool_bytes
        weight_bytes = sum(
            x.nbytes
            for p in self._net._params
            for x in jax.tree_util.tree_leaves(p))
        return weight_bytes + pool_bytes

    def drain_and_swap(self, net, timeout: Optional[float] = None) -> None:
        """Hot-reload seam: pause admission, let every in-flight request
        FINISH on the current weights (KV caches were computed with
        them — mixing would corrupt numerics), swap to `net` (recompiling
        lazily), then resume admission. Queued requests survive the swap
        and decode on the new weights. Raises the swap-build error (e.g.
        `net` is not a gpt network) with the old weights still serving."""
        with self._cond:
            if self._closed:
                raise ServerClosedError("decode engine is shut down")
            self._swap_net = net
            self._swap_error = None
            self._swap_done.clear()
            self._draining = True
            self._cond.notify_all()
        self.recorder.event("drain", reason="weight-swap")
        if not self._swap_done.wait(timeout):
            with self._cond:
                # race guard: the scheduler may already be PAST the
                # _swap_net check and mid-build — abandoning then would
                # report "old weights serving" while the new ones land.
                # Only abandon a swap the scheduler has not picked up
                abandon = not self._swap_in_progress \
                    and not self._swap_done.is_set()
                if abandon:  # resume serving the old weights
                    self._swap_net = None
                    self._draining = False
                    self._cond.notify_all()
            if abandon:
                raise ServingError(
                    f"decode engine drain did not complete within "
                    f"{timeout}s (long in-flight generations); old "
                    "weights still serving")
            self._swap_done.wait()  # build already running: finish it out
        err = self._swap_error
        if err is not None:
            raise err

    def shutdown(self, drain_timeout: float = 10.0) -> bool:
        """Stop admission (typed `ServerClosedError` for queued + new
        requests), let in-flight generations finish for up to
        `drain_timeout` seconds, then fail the rest. Returns True on a
        clean drain. Idempotent."""
        deadline = time.monotonic() + drain_timeout
        with self._cond:
            self._closed = True
            self._plane.close_locked()
            self._cond.notify_all()
        drained = True
        with self._cond:
            while any(r is not None for r in self._slots):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    drained = False
                    self._kill = True
                    self._cond.notify_all()
                    break
                self._cond.wait(min(remaining, 0.05))
        self._thread.join(max(0.0, deadline - time.monotonic()) + 5.0)
        if not self._thread.is_alive():
            # a stopped scheduler dispatches nothing more: the resident
            # weights go now, not when the last reference to the engine
            # does (a failed request's traceback keeps one), and what
            # stays on the device is the net's own tree and the pools
            self._weights = None
            self._weights_resident_bytes = 0
            if self._spec is not None:
                self._spec._weights = None
        if not drained:
            logger.warning("decode engine: shutdown drain timed out with "
                           "generations still in flight")
        return drained

    # -- scheduler ---------------------------------------------------------
    def _hook(self, phase: str, info: dict) -> None:
        for hook in self.step_hooks:
            hook(phase, info)

    def _loop(self) -> None:
        """The scheduler thread. Every moment of it is in one leaf
        phase of `observability.LEAF_PHASES`: the methods below move
        `self._phases` on as they go, and one pass of the try-block is
        one iteration, the `cause` its spans share."""
        try:
            self._schedule()
        finally:
            self._phases.close()

    def _schedule(self) -> None:
        ph = self._phases
        while True:
            with self._cond:
                while not self._closed and not self._kill \
                        and not self._work_pending():
                    # one span per stay, not one per 50 ms wake
                    ph.enter("wait-work")
                    self._cond.wait(0.05)
                kill = self._kill
            if kill:
                # what the chip was handed is delivered, outside the
                # lock, before the rest is failed
                try:
                    self._drain()
                # graftlint: disable=typed-error  the thread is leaving:
                # whatever the last collect raises, the requests below
                # are failed typed all the same
                except BaseException:
                    logger.exception("decode engine: the last collect "
                                     "before a kill failed")
            with self._cond:
                if kill:
                    self._fail_all_locked(ServerClosedError(
                        "engine shut down before this request finished"))
                    self._abort_pending_swap_locked()
                    return
                if self._closed:
                    while self._queue:
                        req = self._queue.popleft()
                        self._pages_demand_queued -= req.n_pages
                        self._pool.release_locked(req)
                        self._finish_obs(req, ServerClosedError(
                            "engine shut down before this request "
                            "could be served"))
                    self._plane.fail_all(ServerClosedError(
                        "decode engine is shut down"))
                    if not any(r is not None for r in self._slots):
                        self._abort_pending_swap_locked()
                        self._cond.notify_all()
                        return
            ph.begin_iteration()
            try:
                if not self._draining and not self._closed:
                    self._admit()
                ph.enter("housekeeping")
                self._expire_in_flight()
                if self._plane.step():
                    self._migrate_in_flight()
                self._step_prefills()
                self._step_active()
                # until the next iteration's first phase: the swap
                # check and the top of this loop
                ph.enter("housekeeping")
                self._maybe_swap()
            # graftlint: disable=typed-error  scheduler firewall: the
            # iteration's failure is converted to InferenceFailedError and
            # fails all in-flight requests; the loop itself must survive
            except BaseException:  # scheduler must never die silently
                logger.exception("decode engine: scheduler iteration "
                                 "failed; failing in-flight requests")
                with self._cond:
                    self._fail_all_locked(InferenceFailedError(
                        "decode engine scheduler failure"))
                self._reset_device_state()

    def _abort_pending_swap_locked(self) -> None:
        """A scheduler exit (shutdown/kill) with a drain pending must
        release the `drain_and_swap` caller — a reload blocked forever
        on a dead scheduler would also pin the ModelServer reload lock."""
        assert_owned(self._cond, "DecodeEngine._abort_pending_swap_locked")
        if self._draining or self._swap_net is not None:
            self._swap_net = None
            self._draining = False
            self._swap_error = ServerClosedError(
                "engine shut down while draining for a weight swap")
            self._swap_done.set()

    def _work_pending(self) -> bool:
        if any(r is not None for r in self._slots):
            return True
        if self._draining:
            return True  # reach _maybe_swap even with empty slots
        if self._plane.pending():
            return True  # a migration pass, a lease sweep, an export
        return bool(self._queue) and not self._draining

    def _fail_all_locked(self, err: BaseException) -> None:
        assert_owned(self._cond, "DecodeEngine._fail_all_locked")
        self._plane.fail_all(err)
        while self._queue:
            req = self._queue.popleft()
            self._pages_demand_queued -= req.n_pages
            self._pool.release_locked(req)
            self._finish_obs(req, err)  # never acquired the breaker
        for s, req in enumerate(self._slots):
            if req is not None:
                self._slots[s] = None
                self._active[s] = False
                self._pool.release_locked(req)
                if req.completed_at is not None:
                    continue  # ended at a collect; only its pages stayed
                if self.breaker is not None:
                    # release the request's breaker token — a dropped
                    # half-open probe would wedge the shared breaker in
                    # half_open and reject ALL traffic until a reload
                    self.breaker.record_failure(req.probe)
                self._finish_obs(req, err)
        self._cond.notify_all()

    def _select_head_locked(self) -> int:
        """Index of the next request to admit: the FIRST queued
        interactive request when one exists (an interactive request
        jumps a page-blocked batch head, so the batch lane only
        consumes capacity interactive traffic is not asking for; under
        sustained interactive saturation the batch lane starves by
        design, its deadline sweep still failing batch requests typed).
        The batch lane itself is weighted-fair, not FIFO: the queued
        batch request whose tenant holds the LOWEST stride-scheduling
        pass value wins, so two equal-weight tenants split admitted
        work ~50/50 under saturation instead of one backlog serializing
        in front of the other — and a weight-2 tenant gets twice the
        admitted span of a weight-1 peer. FIFO within one tenant
        (earliest queued wins the tie on equal pass values);
        untenanted batch traffic rides one shared implicit ledger."""
        assert_owned(self._cond, "DecodeEngine._select_head_locked")
        best = 0
        best_pass = None
        for i, r in enumerate(self._queue):
            if r.priority == "interactive":
                return i
            p = self._wfq_pass.get(r.tenant, self._wfq_floor)
            if best_pass is None or p < best_pass:
                best, best_pass = i, p
        return best

    def _wfq_charge_locked(self, req: "_GenRequest") -> None:
        """Advance the admitted batch request's tenant pass: virtual
        start = max(own pass, floor) — an idle tenant rejoins AT the
        floor, never banking credit — charged by the request's logical
        decode span over the tenant's weight. The floor then advances
        to the winner's pre-charge pass, keeping every ledger within
        one span of each other (bounded unfairness, O(1) state)."""
        assert_owned(self._cond, "DecodeEngine._wfq_charge_locked")
        state = self._tenant_locked(req.tenant)
        weight = state.weight if state is not None else 1.0
        start = max(self._wfq_pass.get(req.tenant, self._wfq_floor),
                    self._wfq_floor)
        span = float(max(1, int(req.n_tokens)))
        self._wfq_pass[req.tenant] = start + span / max(weight, 1e-9)
        self._wfq_floor = start

    def _may_preempt(self, head: _GenRequest) -> bool:
        """Whether a blocked `head` is one a batch-lane slot could yield
        to: what `_admit` asks before it drains for a preemption."""
        return self._preempt_enabled and head.priority == "interactive" \
            and not head.expired() \
            and any(v is not None and v.priority == "batch"
                    for v in self._slots)

    def _maybe_preempt_locked(self, head: _GenRequest, reason: str):
        """Retire-to-queue one DECODING batch-lane slot so a blocked
        interactive head can take its slot and pages. The victim's
        emitted tokens fold into its prompt (`resumed_at` marks the
        fold point, keeping the logical span constant), its prompt's
        fully-covered pages are promoted into the prefix cache so the
        re-prefill re-binds them instead of recomputing, and it rejoins
        the queue FRONT with its position preserved. Mid-prefill slots
        are never preempted: their pages hold partial KV, which must
        not reach the prefix cache. The victim's `tokens` must be all
        the chip has computed for it: the caller drains the pipeline
        first. Returns ``(victim, old_probe, reason, slot)`` or None
        (caller releases the breaker token outside the lock)."""
        assert_owned(self._cond, "DecodeEngine._maybe_preempt_locked")
        if not self._may_preempt(head):
            return None
        best = None
        for s in range(self.n_slots):
            v = self._slots[s]
            if v is None or v.priority != "batch":
                continue
            if v.prefill_pos is not None or not self._active[s]:
                continue  # mid-prefill KV is partial: not promotable
            if v.n_tokens - len(v.tokens) < 1:
                continue  # retiring on its own this iteration
            if best is None or \
                    len(v.tokens) < len(self._slots[best].tokens):
                best = s  # least progress = least re-prefill to redo
        if best is None:
            return None
        v = self._slots[best]
        old_probe = v.probe
        # promote only the CURRENT prompt's fully-covered pages: the
        # latest decoded token's KV is not written yet, so pages
        # touching the decoded tail are not provably complete
        self._pool.promote_locked(v, v.prompt, v.tenant)
        self._vacate_locked(best, v)
        emitted = len(v.tokens)
        if emitted > v.resumed_at:
            v.prompt = np.concatenate(
                [v.prompt, np.asarray(v.tokens[v.resumed_at:], np.int32)])
        v.resumed_at = emitted
        v.prefill_pos = None
        v.slot = None
        v.hit_len = 0
        v.n_shared = 0
        v.nodes = None
        v.digests = []
        v.probe = False
        v.preempted += 1
        v.n_pages = self._pool.pages_for(v.prompt.shape[0],
                                    max(1, v.n_tokens - emitted))
        self._pages_demand_queued += v.n_pages
        # queue FRONT: the victim was admitted before anything queued,
        # so it keeps seniority within the batch lane (interactive
        # selection still jumps it)
        self._queue.appendleft(v)
        self.preemptions += 1
        ts = self._tenants.get(v.tenant)
        if ts is not None:
            ts.preemptions += 1
        self.recorder.event(
            "preempt", slot=best, reason=reason, tenant=v.tenant,
            victim_emitted=emitted, victim_remaining=v.n_tokens - emitted,
            head_tenant=head.tenant, free_pages=self._pool.n_free(),
            head_need_pages=head.n_pages)
        self._cond.notify_all()
        return (v, old_probe, reason, best)

    # graftlint: hot-loop
    def _admit(self) -> None:
        """Move queued requests into free slots. Expired queued requests
        are shed BEFORE any device work. Head selection is
        priority-aware: the first queued INTERACTIVE request goes
        first (FIFO within a class), and when it is slot- or
        page-blocked a decoding batch-lane slot is preempted
        (retire-to-queue) to make room. The selected head otherwise
        waits when the free list cannot cover its pages — a retirement
        frees them in bounded time, and unreferenced prefix-cache pages
        are reclaimed LRU-first before waiting (caching never shrinks
        effective capacity). With a prefix hit, the longest cached
        chain binds into the slot's page table (refcounts bumped), only
        the uncached tail allocates fresh pages, and prefill starts at
        the first uncached page boundary. A short cold prompt prefills
        one-shot immediately; a long or prefix-hit one is parked
        mid-prefill and chunk-prefilled by `_step_prefills` interleaved
        with decode."""
        while True:
            # again after each one-shot prefill, which has phases of its
            # own
            self._phases.enter("admit")
            if self._prefix_cache is not None and any(
                    rec.program == "prefill" for rec in self._inflight):
                # a prefill's pages reach the prefix cache at its
                # collect, and the lookup below has to find them
                self._drain()
            preempt = None
            blocked = None
            with self._cond:
                if not self._queue:
                    return
                free = [s for s in range(self.n_slots)
                        if self._slots[s] is None]
                head_idx = self._select_head_locked()
                head = self._queue[head_idx]
                nodes: list = []
                pim = None
                pre_pinned = False
                need = head.n_pages
                if not free:
                    # every slot taken, an interactive head waiting: the
                    # batch lane yields a slot (retire-to-queue) or we
                    # wait for a retirement like any full house
                    blocked = "slots"
                elif not head.expired():
                    if head.import_state is not None and head.nodes:
                        # delta handoff: its prefix-chain pages were
                        # pinned at resume_submit — they bind as shared
                        # pages, only the shipped tail allocates fresh
                        nodes = head.nodes
                        pre_pinned = True
                        need = head.n_pages - len(nodes)
                    elif self._prefix_cache is not None \
                            and head.import_state is None:
                        # only the scheduler thread mutates the cache,
                        # so this lookup stays valid through the bind;
                        # a page-blocked head retries every iteration —
                        # its chunk digests are memoized on the request
                        nodes = self._prefix_cache.lookup(
                            head.prompt, head.digests,
                            tenant=head.tenant)
                        pim = head.prefix_import
                        if pim is not None and \
                                self._plane.prefix_import_is_stale(
                                    pim, len(nodes)):
                            head.prefix_import = pim = None
                        if nodes or pim is not None:
                            # resumed (preempted) requests span only
                            # their REMAINING tokens past the extended
                            # prompt
                            need = self._pool.pages_for_hit(
                                head.prompt.shape[0],
                                max(1, head.n_tokens - head.resumed_at)) \
                                - len(nodes)
                    if not self._pool.make_room_locked(need, nodes):
                        # page-blocked even after idle cached pages
                        # were reclaimed: a batch slot's pages can
                        # cover an interactive head (preemption), else
                        # wait for a retirement to free pages
                        blocked = "pages"
                if blocked is not None:
                    if not self._may_preempt(head):
                        return
                    if not self._inflight:
                        preempt = self._maybe_preempt_locked(head, blocked)
                        if preempt is None:
                            return
                else:
                    req = head
                    del self._queue[head_idx]
                    self._pages_demand_queued -= req.n_pages
                    if req.priority != "interactive":
                        # charge the batch lane's fair-queueing ledger
                        # at the admission that actually consumed
                        # capacity (preempted re-admissions re-charge:
                        # they consume capacity again)
                        self._wfq_charge_locked(req)
            if blocked is not None and preempt is None:
                # a victim is chosen, and its tokens folded into its
                # prompt, only once the host has seen all of them
                self._drain()
                continue
            if preempt is not None:
                victim, old_probe, reason, vslot = preempt
                if self.breaker is not None:
                    # the victim's device work so far was healthy —
                    # preemption is a scheduling decision, not sickness
                    self.breaker.record_success(old_probe)
                victim.trace.event("preempt", reason=reason, slot=vslot,
                                   emitted=len(victim.tokens))
                continue
            now = time.monotonic()
            if req.expired(now):
                with self._cond:
                    self.shed_deadline += 1
                self._queue_waited(req, now, "expired")
                self._finish_obs(req, DeadlineExceededError(
                    "deadline expired while queued; request shed before "
                    "prefill"))
                continue
            self._queue_waited(req, now)
            with self._cond:
                # ground the SLO estimator's queue-wait term on every
                # admission (preempted re-admissions fold in too: their
                # requeue wait is real interactive-pressure wait)
                self._queue_wait_ewma = 0.8 * self._queue_wait_ewma \
                    + 0.2 * (now - req.enqueued_at)
            probe = False
            if self.breaker is not None:
                try:
                    probe = self.breaker.acquire()
                except ServiceUnavailableError as e:
                    with self._cond:
                        self.shed_unavailable += 1
                    self._finish_obs(req, e)
                    continue
            req.probe = probe
            slot = free[0]
            with self._cond:
                if pre_pinned:
                    # acquired at resume_submit — only account here
                    req.n_shared = len(nodes)
                elif nodes:
                    self._pool.pin_locked(nodes)
                    req.nodes = nodes
                    req.n_shared = len(nodes)
                    req.hit_len = len(nodes) * self.page_size
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += req.hit_len
                elif self._prefix_cache is not None:
                    self.prefix_misses += 1
                self.prompt_tokens += int(req.prompt.shape[0])
                req.pages = self._pool.take_locked(need, nodes)
                req.ring = self._pool.take_ring_locked(len(req.pages))
                held = self._pool.in_use()
            if nodes:
                req.trace.event("prefix-bind", shared_pages=req.n_shared,
                                hit_tokens=req.hit_len)
            req.trace.event("admission", slot=slot, pages=len(req.pages),
                            shared_pages=req.n_shared,
                            pages_in_use=held)
            self.recorder.event("admit", slot=slot, pages=len(req.pages),
                                hit_tokens=req.hit_len,
                                pages_in_use=held, tenant=req.tenant,
                                priority=req.priority)
            self._pool.bind_row(slot, req.pages, req.ring)
            if req.prefix_import is not None:
                # fetched cluster-prefix pages scatter into the freshly
                # allocated tail pages and promote into the local cache
                # as if prefilled here; ANY failure falls back to
                # prefilling from the local hit (or cold)
                had_hit = req.n_shared > 0
                gained = self._plane.bind_prefix_import(req)
                if gained is not None:
                    with self._cond:
                        if not had_hit:
                            # the local lookup missed but the CLUSTER
                            # hit: fold the request back into the hit
                            # column
                            self.prefix_hits += 1
                            self.prefix_misses -= 1
                        self.prefix_hit_tokens += gained
            if req.import_state is not None:
                # shipped KV re-binds directly into the slot: no
                # prefill — the pages already hold the sender's state
                try:
                    self._import_into(slot, req)
                # graftlint: disable=typed-error  converts to a typed
                # failure: _import_failure maps the cause to
                # KVTransferError and fails only the one request
                except BaseException as e:
                    self._import_failure(slot, req, e)
                continue
            t0 = req.prompt.shape[0]
            if req.hit_len or pim is not None or self._is_chunked(t0):
                # `pim is not None` forces the chunk path even when the
                # bind failed with no local hit: the hit-style page
                # allocation cannot cover a one-shot prefill's padded
                # bucket width
                with self._cond:
                    # hit requests always ride the chunk path: suffix
                    # prefill starts at the first uncached page
                    # boundary and attends over the shared pages
                    # through the slot's page row
                    req.prefill_pos = req.hit_len
                    req.slot = slot
                    self._slots[slot] = req
                    # _active stays False until the final chunk lands
                continue
            self._issue_prefill(slot, req)
            if req.handoff or self._spec is not None:
                # a prefill that leaves under a lease, or that the draft
                # mirrors, is read back before anything else is issued
                self._drain()

    # graftlint: hot-loop
    def _issue_prefill(self, slot: int, req: _GenRequest) -> None:
        """First half of a one-shot prefill: hand the program to the
        chip and put the request into its slot, active for the next
        decode dispatch unless this token is its last by count (or it
        leaves under a lease). Nothing is read back: the first token is
        `_collect_prefill`'s, and what only the token can say (EOS, a
        non-finite prompt) costs the slot one dispatch of overshoot."""
        import jax
        import jax.numpy as jnp

        ph = self._phases
        page = self.page_size
        t0 = req.prompt.shape[0]
        bucket = self._bucket_for(t0)
        ph.enter("prefill.dispatch", program="prefill", chunk=bucket,
                 active=int(self._active.sum()), tp=self._tp_degree,
                 trace_id=req.trace.trace_id)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :t0] = req.prompt
        n_w = -(-bucket // page)
        wpids = self._pool.write_ids(req, 0, n_w, (t0 - 1) // page)
        key = jax.random.PRNGKey(req.seed)
        kp, kdec = jax.random.split(key)  # generate()'s prefill/decode split
        info = {"slot": slot, "bucket": bucket, "t0": t0}
        tp0 = time.monotonic()
        try:
            self._hook("pre_prefill", info)
            args = (self._weights, self._caches, jnp.asarray(ids),
                    jnp.asarray(t0, jnp.int32),
                    jnp.asarray(slot, jnp.int32),
                    wpids, self._tok, self._pos, self._keys, self._temps,
                    kp, kdec, jnp.asarray(req.temperature, jnp.float32))
            out = _dispatched(lambda: self._prefill(*args))
        # graftlint: disable=typed-error  converts to a typed failure:
        # _prefill_failure wraps non-ServingError causes in
        # InferenceFailedError and fails only the one request
        except BaseException as e:
            self._drain()  # what was issued before it is still good
            self._prefill_failure(slot, req, e, attached=False)
            return
        (self._caches, self._tok, self._pos, self._keys,
         self._temps) = out[:5]
        self._routing.count_prefill(bucket)
        req.in_flight = 1
        with self._cond:
            req.slot = slot
            self._slots[slot] = req
            # >= len comparison, not n_tokens == 1: a preempted request
            # re-prefills with its emitted tokens folded into the prompt,
            # so this "first" token may already be its last
            self._active[slot] = not req.handoff \
                and len(req.tokens) + 1 < req.n_tokens
        ph.ahead_n += bool(self._inflight)
        self._inflight.append(_InFlight(
            "prefill", [(slot, req)], out[5:], tp0, info,
            draft=(ids, wpids) if self._spec is not None else None))

    # graftlint: hot-loop
    def _collect_prefill(self, rec: _InFlight) -> None:
        """Second half of a one-shot prefill: wait for its first token,
        read it back and deliver it."""
        import jax

        ph = self._phases
        (slot, req), = rec.live
        info = rec.info
        req.in_flight -= 1
        ph.enter("prefill.wait")
        try:
            got = _dispatched(lambda: jax.device_get(rec.handles))
            ph.enter("prefill.deliver")
            tp1 = time.monotonic()
            # host clock from the issue to the materialization — already
            # synced, so the span costs no extra device round-trip
            req.trace.add_timed("prefill", rec.t0, tp1,
                                bucket=info["bucket"], prompt_len=info["t0"])
            first = int(got[0][0])
            lp0 = got[2] if self._logprobs_k else None
            if not bool(got[1]):
                raise InferenceFailedError(
                    "model produced non-finite logits during prefill "
                    "(poisoned parameters or a numerically broken graph)")
            if rec.draft is not None:
                # mirror the prompt into the draft's pools (same pages,
                # same padded ids) so proposing can start from a complete
                # context
                ph.enter("prefill.dispatch", program="draft_prefill",
                         chunk=info["bucket"], tp=self._tp_degree,
                         trace_id=req.trace.trace_id)
                _dispatched(lambda: self._spec.prefill_one_shot(*rec.draft))
                ph.enter("prefill.deliver")
            self._hook("post_prefill", info)
            with self._cond:
                self.prefills += 1
                self.tokens_generated += 1
                self.state_resets += int(self._kinds.resets_on_admission)
                # a one-shot prefill grounds the SLO estimator as a
                # single chunk observation (same dispatch scale as a
                # chunk): the time the chip had it to itself
                self._chunk_ewma = 0.8 * self._chunk_ewma \
                    + 0.2 * (tp1 - max(rec.t0, self._collected_at))
                self._pool.promote_locked(req, req.prompt, req.tenant)
            self._collected_at = tp1
            if self._spec is not None:
                self._spec.seed_slot(slot, req.seed)
            req.tokens.append(first)
            self._emit_token(req, lp0)
            if len(req.tokens) >= req.n_tokens or first == self.eos_token:
                self._retire(slot, req)
            elif req.handoff:
                # prefill-role (disagg): the freshly computed KV leaves
                # under a lease instead of entering this engine's decode
                # loop
                self._hand_off(slot, req, reason="disagg")
        # graftlint: disable=typed-error  converts to a typed failure:
        # _prefill_failure wraps non-ServingError causes in
        # InferenceFailedError and fails only the one request
        except BaseException as e:
            self._prefill_failure(slot, req, e, attached=True)

    # graftlint: hot-loop
    def _step_prefills(self) -> None:
        """Drive pending chunked prefills, one chunk dispatch per
        scheduler iteration — the interleaving that keeps a long prompt
        from head-of-line-blocking in-flight decodes."""
        for s in range(self.n_slots):
            req = self._slots[s]
            if req is not None and req.prefill_pos is not None:
                self._prefill_chunk_into(s, req)
                return

    # graftlint: hot-loop
    def _prefill_chunk_into(self, slot: int, req: _GenRequest) -> None:
        import jax
        import jax.numpy as jnp

        # a path of its own, synchronous: the chunk is issued and read
        # back with nothing else uncollected
        self._drain()
        ph = self._phases
        ph.enter("prefill.dispatch", program="prefill_chunk_fn",
                 chunk=self.prefill_chunk,
                 active=int(self._active.sum()), tp=self._tp_degree,
                 trace_id=req.trace.trace_id)
        C, page = self.prefill_chunk, self.page_size
        off = req.prefill_pos
        t0 = req.prompt.shape[0]
        rem = t0 - off
        final = rem <= C
        if not final:
            W = C
        elif C < page:
            W = C  # C divides page: the padded tail never straddles
        else:
            # final chunk padded only to the next PAGE multiple (<= C):
            # a prefix-hit suffix must never write past
            # page*ceil(t0/page), which its reservation covers
            W = -(-rem // page) * page
        ids = np.zeros((1, W), np.int32)
        take = min(W, rem)
        ids[0, :take] = req.prompt[off:off + take]
        n_w = max(1, W // page)
        pids = req.pages[off // page: off // page + n_w]
        woff = 0 if W >= page else off % page
        key = jax.random.PRNGKey(req.seed)
        kp, kdec = jax.random.split(key)
        info = {"slot": slot, "t0": t0, "chunk": W, "chunk_off": off,
                "final": final}
        self._hook("pre_prefill", info)

        def run():
            args = (self._weights, self._caches, self._pool.rows(slot),
                    jnp.asarray(ids), jnp.asarray(off, jnp.int32),
                    jnp.asarray(woff, jnp.int32),
                    jnp.asarray(t0, jnp.int32),
                    jnp.asarray(slot, jnp.int32),
                    self._pool.write_ids(req, off // page, n_w,
                                         (t0 - 1) // page),
                    self._tok, self._pos, self._keys, self._temps, kp,
                    kdec, jnp.asarray(req.temperature, jnp.float32))
            if self._logprobs_k:
                (self._caches, self._tok, self._pos, self._keys,
                 self._temps, tok0, ok, lp0) = self._prefill_chunk_fn(
                    *args)
            else:
                (self._caches, self._tok, self._pos, self._keys,
                 self._temps, tok0, ok) = self._prefill_chunk_fn(*args)
                lp0 = None
            ph.enter("prefill.wait")
            return jax.device_get((tok0, ok, lp0))

        tp0 = time.monotonic()
        try:
            first, ok, lp0 = _dispatched(run)
            ph.enter("prefill.deliver")
            tp1 = time.monotonic()
            req.trace.add_timed("prefill-chunk", tp0, tp1,
                                chunk_off=off, width=W, final=final)
            if not bool(ok):
                raise InferenceFailedError(
                    "model produced non-finite activations during chunked "
                    "prefill (poisoned parameters or a numerically broken "
                    "graph)")
            if self._spec is not None:
                ph.enter("prefill.dispatch", program="draft_prefill_chunk",
                         chunk=W, tp=self._tp_degree,
                         trace_id=req.trace.trace_id)
                _dispatched(lambda: self._spec.prefill_chunk(
                    self._page_table[slot], ids, off, woff, pids))
                ph.enter("prefill.deliver")
        # graftlint: disable=typed-error  converts to a typed failure:
        # _prefill_failure wraps non-ServingError causes in
        # InferenceFailedError and fails only the one request
        except BaseException as e:
            self._prefill_failure(slot, req, e, attached=True)
            return
        self._hook("post_prefill", info)
        self._routing.count_prefill(W)
        with self._cond:
            self.prefill_chunks += 1
            self.state_resets += int(self._kinds.resets_on_admission
                                     and off == 0)
            self._chunk_ewma = 0.8 * self._chunk_ewma + 0.2 * (tp1 - tp0)
        if not final:
            req.prefill_pos = off + C
            return
        req.prefill_pos = None
        with self._cond:
            self.prefills += 1
            self.tokens_generated += 1
            self._pool.promote_locked(req, req.prompt, req.tenant)
        if self._spec is not None:
            self._spec.seed_slot(slot, req.seed)
        first = int(first[0])
        req.tokens.append(first)
        self._emit_token(req, lp0)
        # >= len, not n_tokens == 1: a resumed (preempted) request may
        # complete on its re-prefill token
        if len(req.tokens) >= req.n_tokens or first == self.eos_token:
            self._retire(slot, req)
            return
        if req.handoff:
            self._hand_off(slot, req, reason="disagg")
            return
        with self._cond:
            self._active[slot] = True

    def _prefill_failure(self, slot: int, req: _GenRequest,
                         e: BaseException, *, attached: bool) -> None:
        """Shared give-up path for one-shot and chunked prefill: free
        the slot + pages, count the failure, and — on a failed DISPATCH
        under donation — fail every in-flight slot (the donated pool
        buffers may be gone with it, and whatever was issued behind it
        ran on them) and rebuild device state."""
        lost = self._donate and getattr(e, "_dispatch_failure", False)
        if lost:
            self._discard_in_flight()
        if self.breaker is not None:
            self.breaker.record_failure(req.probe)
        with self._cond:
            self.failures += 1
            if attached:
                self._vacate_locked(slot, req)
            else:
                self._pool.release_locked(req)
            self._cond.notify_all()
        err = e if isinstance(e, ServingError) else \
            InferenceFailedError(
                f"prefill failed: {type(e).__name__}: {e}")
        logger.warning("decode engine: prefill failure (%s)", err)
        self._finish_obs(req, err, phase="prefill")
        if lost:
            # the raised DISPATCH may have invalidated the DONATED page
            # pools — every in-flight slot's KV is gone with them, so
            # those requests must fail too (queued ones survive: they
            # hold no device state), then the state rebuilds.
            # Post-dispatch failures (non-finite screen, hooks) and the
            # no-donation CPU path leave the pools valid: only this
            # request fails
            self._fail_occupied_slots(InferenceFailedError(
                "paged KV pool lost to a failed prefill dispatch "
                "(donated buffers)"))
            self._reset_device_state()

    def _fail_occupied_slots(self, err: BaseException) -> None:
        """Fail EVERY slot-holding request (decoding or mid-prefill) —
        used when a failed dispatch may have invalidated the donated
        pools, which back all of them."""
        with self._cond:
            for s, r in enumerate(self._slots):
                if r is not None:
                    self._slots[s] = None
                    self._active[s] = False
                    # the pools, both classes, rebuild wholesale after this
                    r.pages = r.ring = None
                    r.nodes = None  # ... and the prefix cache clears
                    if r.completed_at is not None:
                        continue  # ended at a collect; only pages stayed
                    if self.breaker is not None:
                        self.breaker.record_failure(r.probe)
                    self._finish_obs(r, err)
            self._cond.notify_all()

    def _vacate_locked(self, slot: int, req: _GenRequest) -> None:
        """`req` leaves `slot`: no later dispatch has it active, and its
        pages (and with the slot its recurrent state) go back to the
        pool — now, or, while a dispatch that had the slot active is
        uncollected, when `_collect` has read the last such one: its
        overshoot still writes through those pages. Until then the slot
        stays taken."""
        assert_owned(self._cond, "DecodeEngine._vacate_locked")
        self._active[slot] = False
        if any(s == slot for rec in self._inflight for s, _ in rec.live):
            return
        self._slots[slot] = None
        self._pool.release_locked(req)

    def _retire(self, slot: int, req: _GenRequest) -> None:
        """Successful completion: free the slot AND its pages, credit
        the breaker, deliver the tokens."""
        with self._cond:
            self._vacate_locked(slot, req)
            self.served += 1
            ts = self._tenants.get(req.tenant)
            if ts is not None:
                ts.served += 1
                ts.tokens_generated += len(req.tokens)
            self._cond.notify_all()
        if self.breaker is not None:
            self.breaker.record_success(req.probe)
        # trace rides along so a p99 excursion can pin THIS request's
        # timeline in the failure ring (observability excursion hook)
        self._gen_latency_hist.observe(
            1e3 * (time.monotonic() - req.enqueued_at), trace=req.trace)
        self.recorder.event("retire", slot=slot, tokens=len(req.tokens))
        self._finish_obs(req)

    # -- what the KV hand-off plane asks of the scheduler --------------------
    def _read_slot(self, slot: Optional[int], pages: List[int]):
        """Scheduler-thread device read (every dispatch replaces the
        buffers functionally): `(registers, blocks, n_pages)` — the
        pool pages `pages` of every block as host arrays, and, for a
        slot, its (position, last token, live PRNG key, temperature)
        with only the pages its position has reached. Drained first:
        the registers have to match the tokens the host has seen."""
        import jax
        import jax.numpy as jnp

        self._drain()
        regs = None
        if slot is not None:
            pos_, tok_, key_, temp_ = jax.device_get(
                (self._pos[slot], self._tok[slot], self._keys[slot],
                 self._temps[slot]))
            regs = (int(pos_), int(tok_), np.asarray(key_, np.uint32),
                    float(temp_))
            pages = pages[:min(-(-regs[0] // self.page_size), len(pages))]
        jidx = jnp.asarray(np.asarray(pages, np.int32))
        blocks = [st.read_pages(c, jidx)
                  for st, c in zip(self._states, self._caches)]
        return regs, blocks, len(pages)

    # graftlint: hot-loop
    def _write_slot(self, slot: Optional[int], pages: List[int],
                    blocks: List[dict], regs) -> None:
        """The reverse of `_read_slot`: each block writes its part of
        `blocks` into the pool pages `pages` and, for a slot, its
        registers are restored. Drained first, like `_read_slot`."""
        import jax.numpy as jnp

        self._drain()
        jidx = jnp.asarray(np.asarray(pages, np.int32))
        new_caches = []
        for st, blk, c in zip(self._states, blocks, self._caches):
            c = st.write_pages(c, jidx, blk)
            if self._tp is not None:
                c = tuple(self._tp.shard_pool(arr) for arr in c)
            new_caches.append(c)
        self._caches = new_caches
        if regs is not None:
            pos, tok, key, temp = regs
            self._pos = self._pos.at[slot].set(pos)
            self._tok = self._tok.at[slot].set(tok)
            self._keys = self._keys.at[slot].set(jnp.asarray(key))
            self._temps = self._temps.at[slot].set(temp)

    def _hand_off(self, slot: int, req: _GenRequest, *,
                  reason: str = "migrate") -> None:
        """A decoding slot leaves under a lease (the prefill role's
        finished prefill, or the migration pass): the plane takes its
        state and its pages, then the slot is released and the request
        finished with the `SlotMigratedError` redirect."""
        err = self._plane.export_slot(slot, req, reason)
        with self._cond:
            self._slots[slot] = None
            self._active[slot] = False
            self._cond.notify_all()
        self._finish_obs(req, err)

    def _migrate_in_flight(self) -> None:
        """The one-shot migrate-everything pass `migrate_slots()`
        armed: decoding slots export warm (their KV pages ship), queued
        and mid-prefill requests export cold (partial KV is never
        shipped — it is not provably complete)."""
        self._drain()
        with self._cond:
            queued = list(self._queue)
            self._queue.clear()
            for r in queued:
                self._pages_demand_queued -= r.n_pages
                self._pool.release_locked(r)  # delta-pin release
            parked = []
            decoding = []
            for s, r in enumerate(self._slots):
                if r is None:
                    continue
                if self._active[s] and r.prefill_pos is None:
                    decoding.append((s, r))
                else:
                    parked.append((s, r))
            for s, r in parked:
                self._vacate_locked(s, r)
            self._cond.notify_all()
        for r in queued:
            self._finish_obs(r, self._plane.export_cold(r, "migrate"))
        for s, r in parked:
            if self.breaker is not None:
                self.breaker.record_success(r.probe)
            self._finish_obs(r, self._plane.export_cold(r, "migrate"))
        for s, r in decoding:
            self._hand_off(s, r, reason="migrate")

    # graftlint: hot-loop
    def _import_into(self, slot: int, req: _GenRequest) -> None:
        """A warm handoff takes a free slot: the plane re-binds its
        shipped pages and registers, the prompt-covered pages are
        promoted into the prefix cache (weight versions already proven
        equal by validation), and the slot activates — the next
        `_step_active` continues the sequence argmax-exact."""
        self._plane.import_into(slot, req)
        with self._cond:
            req.slot = slot
            req.import_state = None
            self._slots[slot] = req
            self._active[slot] = True
            self._pool.promote_locked(req, req.prompt, req.tenant)
            self._cond.notify_all()
        if self._spec is not None:
            # cold draft mirror: proposals start from draft-side
            # garbage and greedy verify rejects them — still
            # target-exact, just zero speedup until the draft re-warms
            self._spec.seed_slot(slot, req.seed)

    def _import_failure(self, slot: int, req: _GenRequest,
                        e: BaseException) -> None:
        """A failed import touches only this request: the eager pool
        updates are not donated dispatches, so other slots' KV is
        intact. The breaker token returns as success — a transfer
        failure is wire trouble, not model sickness."""
        from deeplearning4j_tpu.serving.kv_transfer import KVTransferError

        if self.breaker is not None:
            self.breaker.record_success(req.probe)
        with self._cond:
            self.failures += 1
            self._vacate_locked(slot, req)
            self._cond.notify_all()
        err = e if isinstance(e, ServingError) else KVTransferError(
            f"KV import failed: {type(e).__name__}: {e}")
        logger.warning("decode engine: KV import failure (%s)", err)
        self._finish_obs(req, err, phase="import")

    # graftlint: hot-loop
    def _expire_in_flight(self) -> None:
        """An expired in-flight request (decoding OR mid-prefill) frees
        its slot and pages immediately — the next queued request takes
        them on the following iteration. Expired QUEUED requests are
        also swept here (not only at admission), so a doomed request
        behind long-running slots fails promptly."""
        now = time.monotonic()
        expired_queued = []
        with self._cond:
            keep = collections.deque()
            while self._queue:
                req = self._queue.popleft()
                if req.expired(now):
                    expired_queued.append(req)
                    self._pages_demand_queued -= req.n_pages
                    self._pool.release_locked(req)
                else:
                    keep.append(req)
            self._queue = keep
            self.shed_deadline += len(expired_queued)
        for req in expired_queued:
            self._queue_waited(req, now, "expired")
            self._finish_obs(req, DeadlineExceededError(
                "deadline expired while queued; request shed before "
                "prefill"))
        if self._inflight and any(r is not None and r.expired(now)
                                  for r in self._slots):
            # the tokens it got in time are delivered, and its pages
            # released with nothing uncollected writing through them
            self._drain()
        for s in range(self.n_slots):
            req = self._slots[s]
            if req is not None and req.expired(now):
                with self._cond:
                    self._vacate_locked(s, req)
                    self.shed_deadline += 1
                    self._cond.notify_all()
                if self.breaker is not None:
                    # the device work done so far was healthy; expiry is
                    # a deadline event, not a model failure
                    self.breaker.record_success(req.probe)
                self._finish_obs(req, DeadlineExceededError(
                    f"deadline expired after {len(req.tokens)} of "
                    f"{req.n_tokens} tokens; slot freed"))

    def _chunk_eligible(self, live, now: float) -> bool:
        """A chunked decode dispatch is allowed only when no scheduling
        event the host can foresee lands inside it: every request of
        `live` has at least a full chunk of tokens still to ASK for
        (`n_tokens` less what it has and what is in flight for it: the
        count is known at issue, whatever the tokens turn out to be), no
        deadline could expire before the chunk returns, no prompt is
        mid-prefill (its chunks must interleave with decode, not wait
        behind a fused run), and — when EOS can retire a slot mid-chunk
        — no queued request is waiting to take a freed slot (without an
        eos_token, the count already proves nothing retires mid-chunk).
        Admission waits at most one chunk — `_admit` runs before every
        dispatch."""
        if self.decode_chunk <= 1:
            return False
        with self._cond:
            if any(r is not None and r.prefill_pos is not None
                   for r in self._slots):
                return False
            if self.eos_token is not None and self._queue:
                return False  # a mid-chunk EOS would strand the slot
        margin = 2.0 * self.decode_chunk * max(self._step_ewma, 1e-4)
        for _, r in live:
            if r.n_tokens - len(r.tokens) - r.in_flight < self.decode_chunk:
                return False
            if r.deadline is not None and r.deadline - now < margin:
                return False
        return True

    def _decode_failure(self, live, e: BaseException) -> None:
        """Shared decode-step give-up: fail every request of `live`, the
        failed dispatch's own list, typed, free slots + pages, and — on
        a failed DISPATCH under donation — drop what was issued behind
        it, fail mid-prefill slots too and rebuild the device state (the
        donated pools back all of them)."""
        lost = getattr(e, "_dispatch_failure", False)
        if lost:
            self._discard_in_flight()
        err = e if isinstance(e, ServingError) else \
            InferenceFailedError(
                f"decode step failed: {type(e).__name__}: {e}")
        logger.warning("decode engine: decode failure (%s)", err)
        # a request that ended at an earlier collect (EOS, a poisoned
        # step) rode this dispatch as overshoot: it has its verdict
        live = [(s, req) for s, req in live if req.completed_at is None]
        with self._cond:
            self.failures += len(live)
        for s, req in live:
            if self.breaker is not None:
                self.breaker.record_failure(req.probe)
            with self._cond:
                self._vacate_locked(s, req)
                self._cond.notify_all()
            self._finish_obs(req, err, phase="decode")
        if lost:
            # only a failed DISPATCH can have invalidated the donated
            # pool buffers; hook failures leave them valid. Mid-prefill
            # slots are backed by the same pools — they go down with
            # them before the rebuild
            self._fail_occupied_slots(InferenceFailedError(
                "paged KV pool lost to a failed decode dispatch "
                "(donated buffers)"))
            self._reset_device_state()

    # graftlint: hot-loop
    def _extend_decode_span(self, req: _GenRequest, name: str, t0: float,
                            t1: float, steps: int) -> None:
        """The request's one `decode` / `spec-verify` span: opened by
        its first decode dispatch, stretched by each later one."""
        sp = req.decode_span
        if sp is None:
            req.decode_span = req.trace.add_timed(
                name, t0, t1, steps=steps, dispatches=1)
        else:
            sp.t1 = t1
            sp.attrs["steps"] += steps
            sp.attrs["dispatches"] += 1

    # graftlint: hot-loop
    def _retire_or_poison(self, s: int, req: _GenRequest, toks, oks,
                          n_steps: int, lps=None) -> int:
        """Consume one slot's emitted tokens from a decode/verify
        dispatch: append until done (count or EOS — overshoot dropped
        with the slot) or until a poisoned step fails the request typed
        while healthy neighbors keep decoding. `lps` is the slot's
        per-step (chosen, top_values, top_ids) logprob batch when the
        engine computes logprobs. Returns the steps consumed."""
        done = False
        poisoned = False
        t = -1
        for t in range(n_steps):
            if not bool(oks[t]):
                poisoned = True
                break
            tok = int(toks[t])
            req.tokens.append(tok)
            self._emit_token(req, lps, t)
            with self._cond:
                self.tokens_generated += 1
            if len(req.tokens) >= req.n_tokens \
                    or tok == self.eos_token:
                done = True
                break
        if poisoned:
            nf_err = InferenceFailedError(
                "model produced non-finite logits during decode "
                "(poisoned parameters or a numerically broken graph)")
            logger.warning("decode engine: %s", nf_err)
            with self._cond:
                self.failures += 1
                self._vacate_locked(s, req)
                self._cond.notify_all()
            if self.breaker is not None:
                self.breaker.record_failure(req.probe)
            self._finish_obs(req, nf_err, phase="decode")
        elif done:
            self._retire(s, req)
        return t + 1

    # graftlint: hot-loop
    def _step_active_spec(self, live) -> bool:
        """One speculative iteration: draft proposes k tokens per slot,
        the target verifies them in one batched chunk — up to k+1
        tokens per slot in two dispatches. Returns False (caller falls
        back to the vanilla step) when no live slot has the write
        budget to speculate."""
        import jax
        import jax.numpy as jnp

        spec = self._spec
        k = spec.k
        # a slot can commit m speculative tokens only while its writes
        # stay within the reserved span: pos + m <= t0 + n_tokens - 2
        # (the last token is never written back). With pos = t0 + len - 1
        # that cap is rem - 1 (rem = tokens still to emit), so a slot
        # with rem >= 2 can still accept; when EVERY slot is down to its
        # final token the plain step is strictly cheaper
        if all(r.n_tokens - len(r.tokens) < 2 for _, r in live):
            return False
        wl = np.zeros((self.n_slots,), np.int32)
        for s, r in live:
            # resumed_at keeps the write limit at the ORIGINAL logical
            # span: a preempted request's prompt absorbed its emitted
            # tokens, which its n_tokens budget already spans
            wl[s] = r.prompt.shape[0] - r.resumed_at + r.n_tokens - 2
        ph = self._phases
        ph.enter("decode.dispatch", program="spec_verify", chunk=k + 1,
                 active=len(live), tp=self._tp_degree)
        info = {"active": len(live), "step": self.decode_steps,
                "spec": True, "k": k}
        t0c = time.monotonic()
        try:
            self._hook("pre_decode", info)

            def run():
                wlimit = jnp.asarray(wl)
                active = jnp.asarray(self._active)
                (spec._caches, spec._keys, props, qd) = spec._propose(
                    spec._weights, spec._caches, self._page_table,
                    self._tok, self._pos, spec._keys, self._temps,
                    active, wlimit)
                (self._caches, self._tok, self._pos, self._keys, out,
                 n_emit, oks) = spec._verify(
                    self._weights, self._caches, self._page_table,
                    self._tok, self._pos, self._keys, self._temps,
                    active, wlimit, props, qd)
                ph.enter("decode.wait")
                return jax.device_get((out, n_emit, oks))

            out, n_emit, oks = _dispatched(run)
            ph.enter("decode.deliver")
            self._hook("post_decode", info)
            t1c = time.monotonic()
        # graftlint: disable=typed-error  converts to a typed failure:
        # _decode_failure wraps the cause in InferenceFailedError for the
        # affected slots and recovers the pool
        except BaseException as e:
            self._decode_failure(live, e)
            return True
        emitted = int(sum(max(1, int(n_emit[s])) for s, _ in live))
        with self._cond:
            self._step_ewma = (0.8 * self._step_ewma
                               + 0.2 * (t1c - t0c)
                               * len(live) / max(1, emitted))
            self.decode_steps += 1
            self.active_slot_steps += len(live)
            self.spec_steps += 1
            for s, r in live:
                # proposals that could actually be consumed: the device
                # cap is m_cap = wlimit - pos = rem - 1, so accepted
                # (= n_emit - 1 <= m_cap) never exceeds this count and
                # the accept RATE stays a true <=100% ratio
                self.spec_proposed += min(
                    k, max(0, r.n_tokens - len(r.tokens) - 1))
                self.spec_accepted += max(0, int(n_emit[s]) - 1)
        delivered = 0
        for s, req in live:
            n = max(1, int(n_emit[s]))
            self._extend_decode_span(req, "spec-verify", t0c, t1c, n)
            before = len(req.tokens)
            self._retire_or_poison(s, req, out[s, :n],
                                   np.repeat(oks[s], n), n)
            delivered += len(req.tokens) - before
        with self._cond:
            # spec_tokens_per_step is a DELIVERED-throughput number:
            # tokens appended to requests, not device emissions — a
            # mid-verify EOS's dropped overshoot must not inflate it
            self.spec_emitted += delivered
        return True

    # graftlint: hot-loop
    def _step_active(self) -> None:
        """One decode dispatch ahead: issue the next program for every
        slot that still has tokens to ask for, and only then wait for,
        read back and deliver what was issued before it — the last
        decode dispatch and the prefills admitted since — while the
        chip runs the new one. A request whose count is already covered
        by what is in flight sits this dispatch out and retires at its
        collect. The speculative step needs each dispatch's `n_emit`
        before it can issue the next: it stays synchronous."""
        live = [(s, r) for s, r in enumerate(self._slots)
                if r is not None and self._active[s]
                and len(r.tokens) + r.in_flight < r.n_tokens]
        if not live:
            self._collect()
        elif self._spec is not None:
            if not self._step_active_spec(live):
                self._issue_decode(live)
                self._drain()
        else:
            self._issue_decode(live)
            self._collect(keep=1)

    # graftlint: hot-loop
    def _issue_decode(self, live) -> None:
        """First half of a decode dispatch: one step, or a fused chunk,
        for the slots of `live`. Every input is a device array the last
        program returned, so nothing here waits for the chip."""
        import jax.numpy as jnp

        chunked = self._spec is None \
            and self._chunk_eligible(live, time.monotonic())
        n_steps = self.decode_chunk if chunked else 1
        program = "decode_chunked" if chunked else "decode_step"
        ph = self._phases
        ph.enter("decode.dispatch", program=program, chunk=n_steps,
                 active=len(live), tp=self._tp_degree)
        info = {"active": len(live), "chunk": n_steps,
                "step": self.decode_steps + sum(
                    rec.n_steps for rec in self._inflight
                    if rec.program != "prefill")}
        mask = np.zeros((self.n_slots,), bool)
        mask[[s for s, _ in live]] = True
        t0 = time.monotonic()
        try:
            self._hook("pre_decode", info)
            fn = self._decode_chunked if chunked else self._decode_step
            out = _dispatched(lambda: fn(
                self._weights, self._caches, self._pool.tables, self._tok,
                self._pos, self._keys, self._temps, jnp.asarray(mask)))
        # graftlint: disable=typed-error  converts to a typed failure:
        # _decode_failure wraps the cause in InferenceFailedError for the
        # affected slots and recovers the pool
        except BaseException as e:
            self._drain()  # what was issued before it is still good
            self._decode_failure(live, e)
            return
        self._caches, self._tok, self._pos, self._keys = out[:4]
        # after the state: (toks,) oks[, logprobs][, the routed
        # blocks' counts, as their account packed them]
        rest = list(out[4:])
        toks_d = rest.pop(0) if chunked else self._tok
        oks_d = rest.pop(0)
        lps_d = rest.pop(0) if self._logprobs_k else None
        counts_d = rest.pop(0) if rest else None
        page, width = self.page_size, self._n_pages_max
        first = []
        for _, r in live:
            # the slot's position at the dispatch's first step, from
            # what the host holds: the pages `kv.attend` walks there
            pos = r.prompt.shape[0] - r.resumed_at + len(r.tokens) \
                + r.in_flight - 1
            ph.kv_pages_walked += sum(min((pos + j) // page + 1, width)
                                      for j in range(n_steps))
            first.append(pos + 1)
            r.in_flight += n_steps
        ph.kv_pages_table += len(live) * n_steps * width
        # and the positions the K/V blocks' attention reads over the
        # dispatch, beside what it would read with no window: once a
        # dispatch, on the span too (a reader of a traced stretch sums
        # the spans inside it)
        read, ctx = self._kinds.positions(first, n_steps)
        ph.kv_positions_attended += read
        ph.kv_positions_context += ctx
        ph.annotate(kv_positions_attended=read, kv_positions_context=ctx)
        ph.ahead_n += bool(self._inflight)
        self._inflight.append(_InFlight(
            program, live, (toks_d, oks_d, lps_d, counts_d), t0, info,
            n_steps))

    # graftlint: hot-loop
    def _collect_decode(self, rec: _InFlight) -> None:
        """Second half of a decode dispatch: wait for it, read its
        tokens back and deliver them slot by slot."""
        import jax

        ph = self._phases
        live, n_steps = rec.live, rec.n_steps
        for _, r in live:
            r.in_flight -= n_steps
        # THE host sync of the hot loop, one a dispatch — the price of
        # iteration-level scheduling; chunking amortizes it to
        # (chunk, S) tokens + per-step flags in ONE sync, the experts'
        # counts ride the same one, and the next dispatch is already on
        # the chip while the host waits here
        ph.enter("decode.wait")
        try:
            toks, oks, lps, counts = _dispatched(
                lambda: jax.device_get(rec.handles))
            ph.enter("decode.deliver")
            if counts is not None:
                self._routing.add(counts, len(live))
            self._hook("post_decode", rec.info)
        # graftlint: disable=typed-error  converts to a typed failure:
        # _decode_failure wraps the cause in InferenceFailedError for the
        # affected slots and recovers the pool
        except BaseException as e:
            self._decode_failure(live, e)
            return
        t1 = time.monotonic()
        if rec.program == "decode_step":
            toks, oks = toks[None], oks[None]
            lps = None if lps is None else tuple(a[None] for a in lps)
        with self._cond:
            # the time the chip had this dispatch to itself: it started
            # when the one before it was done
            self._step_ewma = (0.8 * self._step_ewma + 0.2
                               * (t1 - max(rec.t0, self._collected_at))
                               / n_steps)
            self.decode_steps += n_steps
            self.active_slot_steps += len(live) * n_steps
        self._collected_at = t1
        dropped = 0
        for s, req in live:
            if req.completed_at is not None:
                # it ended at the collect before (EOS, a poisoned step,
                # a failed hook), after this dispatch was issued
                dropped += n_steps
                continue
            self._extend_decode_span(req, "decode", rec.t0, t1, n_steps)
            # per-step, per-slot non-finite screen (predict's breaker
            # discipline): a poisoned step fails THIS request typed —
            # unless it already completed via EOS at an earlier step of
            # the chunk — and healthy neighbors keep decoding (their
            # pages are untouched)
            lp_s = None if lps is None else \
                (lps[0][:, s], lps[1][:, s], lps[2][:, s])
            dropped += n_steps - self._retire_or_poison(
                s, req, toks[:, s], oks[:, s], n_steps, lps=lp_s)
        ph.overshoot_tokens += dropped

    # graftlint: hot-loop
    def _collect(self, keep: int = 0) -> None:
        """Read back and deliver, strictly in issue order, all but the
        newest `keep` of the dispatches in flight. A slot whose request
        ended while a later dispatch still had it active is released
        here, after the last such dispatch: release follows the last
        uncollected dispatch."""
        while len(self._inflight) > keep:
            rec = self._inflight.popleft()
            if rec.program == "prefill":
                self._collect_prefill(rec)
            else:
                self._collect_decode(rec)
            ended = [(s, req) for s, req in rec.live
                     if self._slots[s] is req
                     and req.completed_at is not None]
            if ended:
                with self._cond:
                    for s, req in ended:
                        self._vacate_locked(s, req)
                    self._cond.notify_all()

    def _drain(self) -> None:
        """Collect everything in flight. Called by whatever reads or
        rewrites slot state from the host, which has to see every token
        the chip has computed first: preemption, expiry, migration and
        hand-off, slot reads and writes, the chunked prefill and the
        speculative step, a failed issue, kill. The thread goes back to
        the phase it was in."""
        if not self._inflight:
            return
        ph = self._phases
        ph.drained_n += 1
        phase = ph.current
        self._collect()
        ph.enter(phase)

    def _discard_in_flight(self) -> None:
        """Drop what the chip still holds unread: it ran on device state
        a failed dispatch lost, or that is about to be rebuilt."""
        import jax

        while self._inflight:
            rec = self._inflight.popleft()
            for _, r in rec.live:
                r.in_flight = 0
            self._phases.overshoot_tokens += rec.n_steps * len(rec.live)
            try:
                jax.block_until_ready(rec.handles)
            # graftlint: disable=typed-error  deliberate absorb: the
            # results are thrown away, and so is whatever they raise
            except Exception:
                pass

    # graftlint: hot-loop
    def _maybe_swap(self) -> None:
        if not self._draining:
            return
        with self._cond:
            if any(r is not None for r in self._slots):
                return  # still draining: in-flight finish on old weights
            # a slot stays taken until its last dispatch is collected,
            # so nothing is in flight here
            net = self._swap_net
            if net is None:  # drain abandoned (timeout in drain_and_swap)
                self._draining = False
                return
            # claimed: from here the swap WILL complete (or fail) and
            # set _swap_done — a timing-out drain_and_swap caller sees
            # this flag and waits it out instead of mis-reporting
            # "old weights still serving"
            self._swap_in_progress = True
        try:
            if net is self._net:
                # swap target IS the net the pools/prefix pages were
                # built under (ModelServer.restore_model hands back the
                # same object on rollback): skip the rebuild, keeping
                # warm page pools and every prefix-cache entry — a
                # failed canary rolls back FREE instead of serving the
                # next burst cold (ROADMAP item 5)
                with self._cond:
                    self.swaps += 1
                self.recorder.event("swap", decision="preserved-pools")
                return
            self._build(net)
            misfit = []
            with self._cond:
                self.swaps += 1
                # queued requests were validated against the OLD
                # max_len/page geometry; the rebuilt engine may be
                # tighter. A request that no longer fits would decode
                # silently-wrong tail tokens past the new cache length —
                # fail it typed instead. Survivors' page demand is
                # recomputed against the NEW geometry, re-applying both
                # admission bounds (per-request pool fit + wait-room cap)
                keep: collections.deque = collections.deque()
                reserved = 0
                while self._queue:
                    r = self._queue.popleft()
                    # delta-import pins reference the PRE-swap cache
                    # object (replaced by the rebuild, its pages
                    # reclaimed wholesale): null them, never release
                    # against the fresh cache
                    r.nodes = None
                    r.n_shared = 0
                    if r.import_state is not None:
                        # queued warm handoff: its KV was computed under
                        # the PRE-swap weights — binding it now would
                        # decode silently-wrong tokens. Fail it typed;
                        # the caller's fallback ladder re-prefills
                        misfit.append(r)
                        continue
                    if r.prompt.shape[0] - r.resumed_at + r.n_tokens \
                            > self.max_len:
                        misfit.append(r)
                        continue
                    r.n_pages = self._pool.pages_for(
                        r.prompt.shape[0],
                        max(1, r.n_tokens - r.resumed_at))
                    if r.n_pages > self.pool_pages or \
                            reserved + r.n_pages > self.max_queued_pages:
                        misfit.append(r)  # incl. pool shrunk below the
                        continue          # surviving queue's demand
                    reserved += r.n_pages
                    keep.append(r)
                self._queue = keep
                self._pages_demand_queued = reserved
            from deeplearning4j_tpu.serving.kv_transfer import (
                KVTransferError,
            )

            for r in misfit:
                if r.import_state is not None:
                    self._finish_obs(r, KVTransferError(
                        "queued KV handoff refused: the engine's "
                        "weights swapped while it waited — stale KV "
                        "must not bind; fall back to re-prefill"))
                    continue
                self._finish_obs(r, ServingError(
                    f"request (prompt {r.prompt.shape[0]} + n_tokens "
                    f"{r.n_tokens}) no longer fits the swapped engine's "
                    f"max_len {self.max_len} / {self.pool_pages}-page "
                    "pool"))
            self.recorder.event("swap", decision="complete",
                                misfit=len(misfit))
        # graftlint: disable=typed-error  deliberate absorb: a rejected
        # swap keeps the OLD weights serving; the error is stored for
        # drain_and_swap's caller to re-raise
        except BaseException as e:
            with self._cond:
                self._swap_error = e
            self.recorder.event("swap", decision="rejected",
                                error=type(e).__name__)
            logger.warning("decode engine: weight swap rejected (%s); "
                           "old weights still serving", e)
        finally:
            with self._cond:
                self._swap_net = None
                self._draining = False
                self._swap_in_progress = False
                self._cond.notify_all()
            self._swap_done.set()
