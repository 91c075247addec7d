"""Serving-tier observability: request tracing, metrics registry,
flight recorder and thread timeline for the gateway → ReplicaPool → ModelServer →
DecodeEngine stack.

The serving layers (PRs 4-9) expose only point-in-time ``stats()``
counters — when a request sheds, fails over, hedges, or takes a p99
excursion there is no record of *where the time went* or *which layer
decided what*. This module closes that gap with four pieces, in the
spirit of Dapper-style always-on tracing:

- **Request tracing** (`Trace`/`Span`): a ``trace_id`` minted at the
  gateway (or at `ReplicaPool`/`ModelServer`/`DecodeEngine` entry for
  in-process callers) and threaded through every layer. Each layer
  records typed spans — queue-wait, admission, prefix-bind, per-chunk
  prefill, decode, speculative verify rounds, failover hops, hedge
  fire/win, reload drain — with `time.monotonic()` timestamps and the
  decision that ended them (``served`` / a typed-error class name /
  ``evicted`` / ``rolled-back``). Propagation is by thread-local
  (`use_trace`/`current_trace`) across the synchronous gateway → pool
  → server call chain, and by the request object (`_Request.trace`,
  `_GenRequest.trace`) across the executor/scheduler thread hop. The
  timeline rides responses and every `ServingError` (`attach_trace`),
  so `GatewayError` payloads carry it over the wire.

- **Metrics registry** (`MetricsRegistry`): lock-cheap counters,
  gauges (bindable to a callable) and bounded-bucket histograms, plus
  `register_stats` adapters that pull today's ad-hoc ``stats()`` dicts
  into one `snapshot()` and a Prometheus-style `exposition()` text
  format served by the gateway ``metrics`` RPC.

- **Flight recorder** (`FlightRecorder`): fixed-size rings of completed
  request timelines and scheduler events (admissions, retirements,
  page reclaims, probe verdicts, breaker transitions). Timelines that
  end in a typed failure are additionally pinned in a separate
  ``failures`` ring (the auto-snapshot: a burst of successes cannot
  push a postmortem out), dumpable via the gateway ``flight_record``
  RPC.

- **Thread timeline** (`Timeline`/`ThreadPhases`): the other axis — not
  one request's life but one THREAD's. `TIMELINE` is a bounded
  process-wide ring of phase spans on `time.perf_counter()`; a
  `ThreadPhases` (the decode scheduler owns one) switches its thread
  from leaf phase to leaf phase, so the spans partition the thread's
  time, and keeps the same boundaries as cumulative counters that stay
  on under the kill switch. Every phase also opens a
  `jax.profiler.TraceAnnotation("dl4j:<phase>")`, so any profiler
  capture shows the phases beside the XLA op timeline with no knob.
  Set-up is on the same ring: `DecodeEngine._build` owns a second
  `ThreadPhases` over `BUILD_PHASES`, and the process's one
  `CompileAccount` (`compile_account()`) turns JAX's own trace / lower /
  backend-compile events into `compile.*` spans and cumulative counters.

Hot-path discipline: every recording call is pure host-side arithmetic
(monotonic reads, int/str attrs, deque appends). Nothing here may
receive a device array — formatting one would block the scheduler
thread on the device stream, which is exactly the hazard the graftlint
``host-sync`` rule now also flags for recorder calls inside
``# graftlint: hot-loop`` scopes. The whole subsystem is kill-switched
by ``DL4J_TPU_NO_TRACING=1`` (spans become no-ops on the shared
`NULL_TRACE`, the recorder and the timeline drop writes; counters stay).
"""
from __future__ import annotations

import os
import threading
import time
from bisect import bisect_right
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Sequence

__all__ = [
    "BUILD_PHASES", "COMPILE_SPANS", "CompileAccount", "Counter",
    "FlightRecorder", "Gauge", "Histogram", "LEAF_PHASES",
    "MetricsRegistry", "NULL_TRACE", "SchedulerPhases", "Span", "TIMELINE",
    "ThreadPhases", "Timeline", "Trace", "attach_trace", "compile_account",
    "current_trace", "graft_remote_trace", "maybe_trace", "new_trace_id",
    "tracing_enabled", "use_trace", "wire_trace_context",
]

_KILL_ENV = "DL4J_TPU_NO_TRACING"


def tracing_enabled() -> bool:
    """The kill switch: ``DL4J_TPU_NO_TRACING=1`` turns every trace
    into `NULL_TRACE` and every recorder or timeline write into a
    no-op."""
    return os.environ.get(_KILL_ENV, "") not in ("1", "true", "yes")


def new_trace_id() -> str:
    return os.urandom(8).hex()


# -- spans / traces --------------------------------------------------------

class Span:
    """One typed interval on a request timeline. ``decision`` is how it
    ended: None (still open / informational event), ``"ok"``, or the
    layer's verdict (``"served"``, a typed-error class name,
    ``"evicted"``, ``"rolled-back"``)."""

    __slots__ = ("name", "t0", "t1", "decision", "attrs")

    def __init__(self, name: str, t0: float, t1: Optional[float] = None,
                 decision: Optional[str] = None,
                 attrs: Optional[dict] = None):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.decision = decision
        self.attrs = attrs

    def to_dict(self) -> dict:
        d = {"name": self.name, "t0": self.t0,
             "t1": self.t1 if self.t1 is not None else self.t0}
        if self.decision is not None:
            d["decision"] = self.decision
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


class Trace:
    """A request's causal timeline: a ``trace_id`` plus a bounded,
    thread-safe list of `Span`s (monotonic-clock timestamps — compare
    within a process, not across hosts). Spans are appended from
    several threads (gateway handler, pool hedges, server executor,
    engine scheduler); `to_dict` orders them by start time, which is
    causal order for the single request they all describe."""

    MAX_SPANS = 512

    __slots__ = ("trace_id", "decision", "_spans", "_lock", "_dropped",
                 "created_at", "created_mono")

    def __init__(self, trace_id: Optional[str] = None):
        self.trace_id = trace_id or new_trace_id()
        self.decision: Optional[str] = None
        # the trace's WALL-CLOCK ANCHOR: the same instant read on both
        # clocks. Span timestamps stay monotonic (immune to NTP steps),
        # and the (mono, wall) pair lets another process convert them —
        # remote spans are grafted into a local timeline by going
        # remote-monotonic → wall → local-monotonic through the two
        # anchors (`graft_remote_trace`)
        self.created_at = time.time()
        self.created_mono = time.monotonic()
        self._spans: List[Span] = []
        self._lock = threading.Lock()
        self._dropped = 0

    def _append(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) >= self.MAX_SPANS:
                self._dropped += 1
                return
            self._spans.append(span)

    @contextmanager
    def span(self, name: str, **attrs):
        """Record ``name`` over the with-block. An escaping exception
        stamps the span's decision with the exception class name and
        re-raises; otherwise the decision is ``"ok"`` (callers may
        overwrite via the yielded span)."""
        sp = Span(name, time.monotonic(), attrs=attrs or None)
        self._append(sp)
        try:
            yield sp
        except BaseException as e:
            sp.t1 = time.monotonic()
            sp.decision = type(e).__name__
            raise
        sp.t1 = time.monotonic()
        if sp.decision is None:
            sp.decision = "ok"

    def event(self, name: str, **attrs) -> None:
        """A point-in-time mark (zero-width span)."""
        self._append(Span(name, time.monotonic(), attrs=attrs or None))

    def add_timed(self, name: str, t0: float, t1: float,
                  decision: Optional[str] = None, **attrs) -> Span:
        """Record an interval measured by the caller (e.g. queue-wait
        from a request's ``enqueued_at`` to its admission). Returns the
        span, so a caller that keeps measuring the same thing (the
        engine's one ``decode`` span per request) extends it in place
        instead of appending one per observation."""
        sp = Span(name, t0, t1, decision, attrs or None)
        self._append(sp)
        return sp

    def finish(self, decision: str) -> None:
        self.decision = decision

    def to_dict(self) -> dict:
        with self._lock:
            spans = sorted(self._spans, key=lambda s: s.t0)
            out = {"trace_id": self.trace_id,
                   "anchor": {"mono": self.created_mono,
                              "wall": self.created_at},
                   "spans": [s.to_dict() for s in spans]}
            if self.decision is not None:
                out["decision"] = self.decision
            if self._dropped:
                out["dropped_spans"] = self._dropped
            return out

    def __bool__(self) -> bool:
        return True


class _NullContext:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


class _NullTrace:
    """Shared no-op trace returned by `maybe_trace` when the kill
    switch is set — callers record unconditionally and pay one falsy
    method call instead of branching everywhere."""

    __slots__ = ()
    trace_id = None
    decision = None
    _null_ctx = _NullContext()

    def span(self, name, **attrs):
        return self._null_ctx

    def event(self, name, **attrs):
        pass

    def add_timed(self, name, t0, t1, decision=None, **attrs):
        pass

    def finish(self, decision):
        pass

    def to_dict(self):
        return None

    def __bool__(self):
        return False


NULL_TRACE = _NullTrace()

_tls = threading.local()


def current_trace() -> Optional[Trace]:
    """The trace bound to this thread by `use_trace` (None outside)."""
    return getattr(_tls, "trace", None)


@contextmanager
def use_trace(trace):
    """Bind `trace` to the current thread so downstream layers on the
    same synchronous call chain (`maybe_trace`) join it instead of
    minting their own — how the gateway's trace_id reaches the engine
    without threading a parameter through every signature."""
    prev = getattr(_tls, "trace", None)
    _tls.trace = trace
    try:
        yield trace
    finally:
        _tls.trace = prev


def maybe_trace(trace=None):
    """Resolve the trace for a request entering a serving layer: an
    explicit one wins, else the thread-local one (an upstream layer's),
    else mint a fresh `Trace` — or `NULL_TRACE` when kill-switched."""
    t = trace if trace is not None else current_trace()
    if t is not None:
        return t
    return Trace() if tracing_enabled() else NULL_TRACE


def attach_trace(err: BaseException, trace) -> None:
    """Stamp ``trace_id`` and the serialized timeline onto a
    `ServingError` (best-effort — same idiom as the pool's replica_id
    tagging) so in-process callers and the gateway error payload both
    carry the timeline. A batch-shared exception instance can be
    stamped by several waiter threads; last writer wins, and each
    writer's timeline names the same batch, so any of them serves the
    postmortem."""
    if not trace:
        return
    try:
        err.trace_id = trace.trace_id
        err.trace = trace.to_dict()
    # graftlint: disable=typed-error  best-effort attachment: a slotted
    # or exotic exception type that rejects new attributes must not turn
    # error delivery itself into a second failure
    except Exception:
        pass


# -- cross-process trace propagation ---------------------------------------

def wire_trace_context(trace) -> Optional[dict]:
    """The trace context a gateway client sends alongside a request so
    the remote server JOINS the caller's trace instead of minting its
    own: the trace_id plus the LOCAL wall-clock anchor (informational —
    the remote side answers with its own anchor, which is what the
    caller grafts by). None for no/null traces: the request travels
    context-free and the remote side keeps its historical minting."""
    if not trace or getattr(trace, "trace_id", None) is None:
        return None
    ctx = {"trace_id": trace.trace_id}
    mono = getattr(trace, "created_mono", None)
    wall = getattr(trace, "created_at", None)
    if mono is not None and wall is not None:
        ctx["anchor"] = {"mono": mono, "wall": wall}
    return ctx


def graft_remote_trace(trace, remote: Optional[dict], **attrs) -> int:
    """Splice a REMOTE process's serialized trace (`Trace.to_dict()`
    shipped over the gateway wire) into the local `trace` as spans on
    the local monotonic clock, so a cross-process request still reads
    as ONE causally-ordered timeline in the flight recorder.

    Clock conversion rides the wall-clock anchors both traces carry:
    ``local_t = remote_t + ((r_wall - r_mono) - (l_wall - l_mono))`` —
    remote-monotonic → shared wall time → local-monotonic. Accurate to
    the hosts' wall-clock skew (NTP-level; fine for ms-scale serving
    spans — docs/observability.md states the caveat). Every grafted
    span carries ``remote=True`` plus caller `attrs` (e.g. the replica
    endpoint), and the remote decision lands as a zero-width
    ``remote-decision`` event. Returns the number of spans grafted;
    anchorless remote payloads graft 0 spans but still record one
    ``remote-trace`` marker naming the remote trace_id."""
    if not trace or not isinstance(remote, dict):
        return 0
    r_anchor = remote.get("anchor") or {}
    l_mono = getattr(trace, "created_mono", None)
    l_wall = getattr(trace, "created_at", None)
    r_mono, r_wall = r_anchor.get("mono"), r_anchor.get("wall")
    if None in (l_mono, l_wall, r_mono, r_wall):
        trace.event("remote-trace", remote_trace_id=remote.get("trace_id"),
                    anchorless=True, **attrs)
        return 0
    offset = (r_wall - r_mono) - (l_wall - l_mono)
    grafted = 0
    for sp in remote.get("spans", ()):
        if not isinstance(sp, dict) or "t0" not in sp:
            continue
        sp_attrs = dict(sp.get("attrs") or {})
        sp_attrs.update(attrs)
        sp_attrs["remote"] = True
        trace.add_timed(sp.get("name", "remote"),
                        sp["t0"] + offset,
                        sp.get("t1", sp["t0"]) + offset,
                        sp.get("decision"), **sp_attrs)
        grafted += 1
    decision = remote.get("decision")
    if decision is not None:
        trace.event("remote-decision", decision=decision,
                    remote_trace_id=remote.get("trace_id"), **attrs)
    return grafted


# -- metrics registry ------------------------------------------------------

class Counter:
    """Monotonic counter. One uncontended lock per `inc` — cheap
    against the ~ms-scale operations it counts."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self):
        return self._v


class Gauge:
    """Point-in-time value: either `set()` by the owner or bound to a
    zero-argument callable sampled at snapshot time (how queue depth /
    pages-in-use track the live scheduler state without a write on
    every transition)."""

    __slots__ = ("name", "_v", "_fn")

    def __init__(self, name: str, fn: Optional[Callable[[], float]] = None):
        self.name = name
        self._v = 0.0
        self._fn = fn

    def set(self, v: float) -> None:
        self._v = v

    @property
    def value(self):
        if self._fn is not None:
            try:
                return self._fn()
            # graftlint: disable=typed-error  a gauge reads live
            # component state that may be mid-teardown; a scrape must
            # report None, never propagate the component's failure
            except Exception:
                return None
        return self._v


#: upper bounds (ms) for latency histograms — bounded cardinality by
#: construction, wide enough for queue-wait through whole-generate.
DEFAULT_LATENCY_BUCKETS_MS = (
    1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0)


class Histogram:
    """Fixed-bucket histogram (`buckets` are inclusive upper bounds;
    one implicit +Inf bucket). `observe` is a bisect plus two adds
    under an uncontended lock.

    **p99-excursion auto-dump** (`enable_excursion`): an observation
    landing past the histogram's own live `quantile` bound fires the
    configured hook OUTSIDE the lock with ``(value, bound, trace)`` —
    the engine wires this to `FlightRecorder.pin`, so the excursion
    request's full timeline lands in the failures ring the moment the
    tail event happens, instead of being reconstructed from counters
    after the fact. The bound is computed from the bucket counts
    BEFORE the new observation (an excursion cannot raise the bar it
    is judged against) and only once `min_count` observations exist
    (a cold histogram's 'p99' is noise)."""

    __slots__ = ("name", "buckets", "_counts", "_count", "_sum", "_lock",
                 "_exc_quantile", "_exc_min_count", "_exc_hook")

    def __init__(self, name: str,
                 buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS_MS):
        self.name = name
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self.buckets) + 1)
        self._count = 0
        self._sum = 0.0
        self._lock = threading.Lock()
        self._exc_quantile = 0.99
        self._exc_min_count = 50
        self._exc_hook: Optional[Callable] = None

    def enable_excursion(self, quantile: float = 0.99,
                         min_count: int = 50,
                         hook: Optional[Callable] = None) -> None:
        """Arm the excursion hook: observations past the live
        `quantile` bound (once `min_count` observations exist) call
        ``hook(value, bound, trace)`` outside the histogram lock."""
        if not 0.0 < quantile < 1.0:
            raise ValueError("excursion quantile must be in (0, 1)")
        if min_count < 1:
            raise ValueError("excursion min_count must be >= 1")
        self._exc_quantile = float(quantile)
        self._exc_min_count = int(min_count)
        self._exc_hook = hook

    def _quantile_bound_locked(self, q: float) -> Optional[float]:
        """Smallest bucket upper bound covering quantile `q` of the
        recorded observations — None when the quantile falls in the
        implicit +Inf bucket (no finite bar to judge against)."""
        if not self._count:
            return None
        target = q * self._count
        cum = 0
        for bound, cnt in zip(self.buckets, self._counts):
            cum += cnt
            if cum >= target:
                return bound
        return None

    def quantile_bound(self, q: float) -> Optional[float]:
        """Public read of the live bucket-quantile bound (telemetry,
        tests, the bench's excursion line)."""
        with self._lock:
            return self._quantile_bound_locked(q)

    def observe(self, v: float, trace=None) -> None:
        i = bisect_right(self.buckets, v)
        fire_bound = None
        with self._lock:
            if self._exc_hook is not None \
                    and self._count >= self._exc_min_count:
                bound = self._quantile_bound_locked(self._exc_quantile)
                if bound is not None and v > bound:
                    fire_bound = bound
            self._counts[i] += 1
            self._count += 1
            self._sum += v
        if fire_bound is not None:
            # outside the lock: the hook appends to recorder rings and
            # must not serialize every concurrent observe behind it
            self._exc_hook(v, fire_bound, trace)

    def snapshot(self) -> dict:
        with self._lock:
            return {"buckets": list(self.buckets),
                    "counts": list(self._counts),
                    "count": self._count,
                    "sum": round(self._sum, 3)}


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() or c == "_" else "_" for c in name)


def _flatten_numeric(prefix: str, obj, out: List) -> None:
    if isinstance(obj, bool):
        out.append((prefix, int(obj)))
    elif isinstance(obj, (int, float)):
        out.append((prefix, obj))
    elif isinstance(obj, dict):
        for k, v in obj.items():
            _flatten_numeric(f"{prefix}_{_sanitize(str(k))}", v, out)
    # strings / lists / None are identity or timeline data, not metrics


class MetricsRegistry:
    """Named counters/gauges/histograms plus ``stats()`` adapters.

    `snapshot()` is the one structured view — first-class instruments
    under ``counters``/``gauges``/``histograms`` and every registered
    component's ad-hoc ``stats()`` dict under ``components`` (the
    schema the contract test in `tests/test_observability.py` pins).
    `exposition()` renders the same data as Prometheus text; numeric
    leaves of component stats become gauges with underscore-joined
    paths, so today's counters are scrapeable without re-plumbing each
    one as a first-class instrument."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._stats_fns: Dict[str, Callable[[], dict]] = {}

    # get-or-create: layers can share one registry without coordinating
    # construction order
    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str,
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name, fn)
            elif fn is not None:
                g._fn = fn
            return g

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS_MS
                  ) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(name, buckets)
            return h

    def register_stats(self, name: str, fn: Callable[[], dict]) -> None:
        """Adopt a component's existing ``stats()`` provider under
        ``components[name]`` in the snapshot."""
        with self._lock:
            self._stats_fns[name] = fn

    def snapshot(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = dict(self._histograms)
            stats_fns = dict(self._stats_fns)
        components = {}
        for name, fn in stats_fns.items():
            try:
                components[name] = fn()
            # graftlint: disable=typed-error  a dying component must
            # not take the whole metrics snapshot down; its slot names
            # the failure instead
            except Exception as e:
                components[name] = {"error": type(e).__name__}
        return {
            "counters": {n: c.value for n, c in counters.items()},
            "gauges": {n: g.value for n, g in gauges.items()},
            "histograms": {n: h.snapshot() for n, h in hists.items()},
            "components": components,
        }

    def exposition(self, namespace: str = "dl4j",
                   labels: Optional[dict] = None) -> str:
        """Prometheus-style text exposition of `snapshot()`."""
        snap = self.snapshot()
        lab = ""
        if labels:
            lab = "{" + ",".join(
                f'{_sanitize(str(k))}="{v}"'
                for k, v in sorted(labels.items())) + "}"
        lines: List[str] = []

        def emit(name, kind, value):
            # a series may embed its OWN labels in the registered name
            # (e.g. 'x{tp_rank="0"}' — per-shard gauges register one
            # series per rank); split them off before sanitizing and
            # merge with the call-level labels so the exposition stays
            # one metric name with several labelled series
            own = ""
            if "{" in name:
                name, own = name.split("{", 1)
                own = own.rstrip("}")
            full = f"{namespace}_{_sanitize(name)}"
            merged = lab
            if own:
                merged = lab[:-1] + "," + own + "}" if lab \
                    else "{" + own + "}"
            lines.append(f"# TYPE {full} {kind}")
            lines.append(f"{full}{merged} {value}")

        for name, v in sorted(snap["counters"].items()):
            emit(name, "counter", v)
        for name, v in sorted(snap["gauges"].items()):
            if v is not None:
                emit(name, "gauge", v)
        for name, h in sorted(snap["histograms"].items()):
            full = f"{namespace}_{_sanitize(name)}"
            lines.append(f"# TYPE {full} histogram")
            cum = 0
            for bound, cnt in zip(h["buckets"], h["counts"]):
                cum += cnt
                if labels:
                    le = lab[:-1] + f',le="{bound}"}}'
                else:
                    le = f'{{le="{bound}"}}'
                lines.append(f"{full}_bucket{le} {cum}")
            if labels:
                le = lab[:-1] + ',le="+Inf"}'
            else:
                le = '{le="+Inf"}'
            lines.append(f"{full}_bucket{le} {h['count']}")
            lines.append(f"{full}_sum{lab} {h['sum']}")
            lines.append(f"{full}_count{lab} {h['count']}")
        flat: List = []
        for comp, stats in sorted(snap["components"].items()):
            _flatten_numeric(_sanitize(comp), stats, flat)
        for name, v in flat:
            emit(f"stats_{name}", "gauge", v)
        return "\n".join(lines) + "\n"


# -- flight recorder -------------------------------------------------------

class FlightRecorder:
    """Bounded rings of (a) completed request timelines, (b) timelines
    that ended in a typed failure (the auto-snapshot ring: success
    traffic cannot push a postmortem out before anyone looks), and
    (c) scheduler/control-plane events. Traces are stored by reference
    and serialized at `dump()` time, so spans recorded after the
    initial `record` (e.g. a pool-level failover wrapping a replica's
    already-recorded attempt) still appear in the dump.

    Sizing: the defaults (256 requests / 64 failures / 1024 events)
    hold a few seconds of saturated decode traffic — see
    docs/observability.md for the arithmetic. All writes are O(1) deque
    appends and respect the `tracing_enabled` kill switch."""

    def __init__(self, capacity: int = 256, failure_capacity: int = 64,
                 event_capacity: int = 1024):
        self._lock = threading.Lock()
        self._requests = deque(maxlen=capacity)
        self._failures = deque(maxlen=failure_capacity)
        self._events = deque(maxlen=event_capacity)

    def record(self, trace, decision: str, kind: str = "request",
               **attrs) -> None:
        """Ring a completed request timeline. ``decision`` is the
        verdict that ended it (``served`` or a typed-error class name);
        non-served timelines are also pinned in the failures ring."""
        if not trace or not tracing_enabled():
            return
        entry = {"kind": kind, "decision": decision,
                 "wall_time": time.time(), "trace": trace}
        if attrs:
            entry["attrs"] = attrs
        with self._lock:
            self._requests.append(entry)
            if decision != "served":
                self._failures.append(entry)

    def pin(self, trace, decision: str, kind: str = "excursion",
            **attrs) -> None:
        """Pin a request timeline in the FAILURES ring without a
        request completion — the p99-excursion auto-dump: the latency
        histogram's excursion hook calls this the moment an
        observation lands past the quantile bound, so the tail
        request's full span timeline survives success traffic (the
        failures ring is the one a burst of served requests cannot
        push a postmortem out of). Also rings a matching control-plane
        event carrying the trace id."""
        if not trace or not tracing_enabled():
            return
        entry = {"kind": kind, "decision": decision,
                 "wall_time": time.time(), "trace": trace}
        if attrs:
            entry["attrs"] = attrs
        with self._lock:
            self._failures.append(entry)
        self.event(kind, decision=decision,
                   trace_id=getattr(trace, "trace_id", None), **attrs)

    def event(self, kind: str, **attrs) -> None:
        """Ring a scheduler/control-plane event (admission, retirement,
        page reclaim, probe verdict, breaker transition, chaos)."""
        if not tracing_enabled():
            return
        e = {"kind": kind, "t": time.monotonic(), "wall_time": time.time()}
        if attrs:
            e.update(attrs)
        with self._lock:
            self._events.append(e)

    @staticmethod
    def _ser(entry: dict) -> dict:
        out = {k: v for k, v in entry.items() if k != "trace"}
        tr = entry["trace"]
        out["trace"] = tr.to_dict() if hasattr(tr, "to_dict") else tr
        return out

    def dump(self) -> dict:
        with self._lock:
            requests = list(self._requests)
            failures = list(self._failures)
            events = list(self._events)
        return {
            "requests": [self._ser(e) for e in requests],
            "failures": [self._ser(e) for e in failures],
            "events": events,
            "capacity": {"requests": self._requests.maxlen,
                         "failures": self._failures.maxlen,
                         "events": self._events.maxlen},
        }


# -- thread timeline -------------------------------------------------------

class Timeline:
    """Bounded ring of thread-phase spans, shared by the whole process:
    a reader that comes after the engine and the server are gone (the
    benchmark's metric readers do) can still import it.

    A span is the plain tuple ``(name, t0, t1, cause, tid, attrs)``:
    `t0`/`t1` are `time.perf_counter()` seconds, `cause` is what set the
    work off (for the decode scheduler, its iteration's number, shared
    by every span of that iteration), `tid` the recording thread's
    ident — several schedulers in one process (in-process replicas)
    read as one timeline each — and `attrs` a dict or None. The oldest
    span is dropped for the newest once `capacity` is reached, and
    `dropped` counts them."""

    def __init__(self, capacity: int = 32768):
        self._lock = threading.Lock()
        self._spans = deque(maxlen=capacity)
        self._appended = 0

    def record(self, name: str, t0: float, t1: float, cause=None,
               tid: Optional[int] = None,
               attrs: Optional[dict] = None) -> None:
        """Append one span measured by the caller. Not switched: the
        writer tests `tracing_enabled` once for many spans."""
        with self._lock:
            self._spans.append((name, t0, t1, cause, tid, attrs))
            self._appended += 1

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._appended - len(self._spans)

    def snapshot(self, t0: Optional[float] = None,
                 t1: Optional[float] = None) -> List[tuple]:
        """The spans that overlap [t0, t1] (either side open), oldest
        first."""
        with self._lock:
            spans = list(self._spans)
        return [s for s in spans
                if (t1 is None or s[1] <= t1) and (t0 is None or s[2] >= t0)]


#: the one timeline of the process
TIMELINE = Timeline()

#: what a decode scheduler's thread can be doing; every moment of it is
#: in exactly one of these (docs/observability.md names what each holds)
LEAF_PHASES = (
    "wait-work", "admit", "housekeeping",
    "prefill.dispatch", "prefill.wait", "prefill.deliver",
    "decode.dispatch", "decode.wait", "decode.deliver")

#: what the thread inside `DecodeEngine._build` can be doing, from the
#: build's first statement to its last (construction, and every weight
#: swap that rebuilds)
BUILD_PHASES = (
    "build.plan", "build.weights", "build.weight_hash", "build.state")

_TraceAnnotation = None


class ThreadPhases:
    """One thread's time, cut into the leaf phases `phases` names.
    `enter(name)` ends the phase the thread was in and starts the next
    at the same instant, so phases cannot overlap and leave no gap
    between an `enter` and the next `close`. Each ended phase becomes a
    span on `timeline`, a `jax.profiler.TraceAnnotation("dl4j:<name>")`
    while it lasts (a flag test when no profiler session is open) and
    two cumulative counters, `<name>_s` and `<name>_n`; the kill switch,
    read once per `begin_iteration`, stops the spans and annotations
    and leaves the counters.

    One thread at a time calls `begin_iteration`/`enter`/`close` (the
    thread that makes the first `enter` after a `close` is the one the
    spans name); `counters()` may be read from any thread."""

    def __init__(self, phases: Sequence[str], timeline: Timeline = TIMELINE):
        self._timeline = timeline
        self._acc = {p: [0.0, 0] for p in phases}
        self._labels = {p: "dl4j:" + p for p in phases}
        self.iterations = 0
        self._on = tracing_enabled()
        self._tid = None
        # (name, t0, cause, attrs) of the phase the thread is in
        self._open = None
        self._annotation = None

    def begin_iteration(self) -> None:
        """A new cause for the spans that follow."""
        self.iterations += 1
        self._on = tracing_enabled()

    def enter(self, name: str, **attrs) -> None:
        """Move the thread into phase `name`. Entering the phase it is
        already in changes nothing: the phase goes on."""
        if self._open is not None and self._open[0] == name:
            return
        if name not in self._acc:
            raise KeyError(f"{name!r} is none of the phases "
                           f"{tuple(self._acc)}")
        now = time.perf_counter()
        if self._open is None:
            self._tid = threading.get_ident()
        self._end(now)
        self._open = (name, now, self.iterations, attrs or None)
        if self._on:
            global _TraceAnnotation
            if _TraceAnnotation is None:
                from jax.profiler import TraceAnnotation as _TraceAnnotation
            self._annotation = _TraceAnnotation(self._labels[name])
            self._annotation.__enter__()

    def annotate(self, **attrs) -> None:
        """Add `attrs` to the span of the phase the thread is in."""
        name, t0, cause, have = self._open
        self._open = (name, t0, cause, {**(have or {}), **attrs})

    @property
    def current(self) -> Optional[str]:
        """The phase the thread is in."""
        return None if self._open is None else self._open[0]

    def close(self) -> None:
        """End the open phase; the thread is leaving."""
        self._end(time.perf_counter())

    def _end(self, now: float) -> None:
        if self._open is None:
            return
        name, t0, cause, attrs = self._open
        self._open = None
        acc = self._acc[name]
        acc[0] += now - t0
        acc[1] += 1
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._on:
            self._timeline.record(name, t0, now, cause, self._tid, attrs)

    def counters(self) -> dict:
        """``{"<phase>_s", "<phase>_n"}`` of every phase: cumulative, so
        the difference of two readings is a window's account. The phase
        still open counts with the seconds it has lasted so far, so the
        `_s` of all phases add up to the time spent between a first
        `enter` and the `close` that followed it (or now)."""
        out = {}
        for name, (seconds, n) in self._acc.items():
            out[name + "_s"] = seconds
            out[name + "_n"] = n
        open_ = self._open
        if open_ is not None:
            out[open_[0] + "_s"] += time.perf_counter() - open_[1]
        return out


class SchedulerPhases(ThreadPhases):
    """The decode scheduler's `ThreadPhases` over `LEAF_PHASES`, and the
    counts the scheduler keeps beside them (written by its thread
    alone)."""

    def __init__(self, timeline: Timeline = TIMELINE):
        super().__init__(LEAF_PHASES, timeline)
        self.sink_s = 0.0
        self.sink_n = 0
        # the decode scheduler's dispatch-ahead: dispatches issued while
        # an earlier one was unread, times the pipeline was drained for
        # a host read or write of slot state, tokens computed and dropped
        self.ahead_n = 0
        self.drained_n = 0
        self.overshoot_tokens = 0
        # the page walk of `kv.attend`, reckoned on the host at each
        # decode dispatch: live pages of the dispatched slots over its
        # steps, and those slots x steps x the page table's width
        self.kv_pages_walked = 0
        self.kv_pages_table = 0
        # and of the K/V blocks' attention: the positions read over those
        # steps, slots and blocks (a window block: at most its window),
        # and what they would be with no window
        self.kv_positions_attended = 0
        self.kv_positions_context = 0

    def counters(self) -> dict:
        """The phases' `<phase>_s`, `<phase>_n` and ``{"iterations",
        "sink_s", "sink_n", "ahead_n", "drained_n", "overshoot_tokens",
        "kv_pages_walked", "kv_pages_table", "kv_positions_attended",
        "kv_positions_context", "spans_dropped"}`` (the
        engine's `stats()["loop"]` adds `prefill_sorted_n`, which its
        routing account keeps)."""
        out = {"iterations": self.iterations}
        out.update(super().counters())
        out["sink_s"] = self.sink_s
        out["sink_n"] = self.sink_n
        out["ahead_n"] = self.ahead_n
        out["drained_n"] = self.drained_n
        out["overshoot_tokens"] = self.overshoot_tokens
        out["kv_pages_walked"] = self.kv_pages_walked
        out["kv_pages_table"] = self.kv_pages_table
        out["kv_positions_attended"] = self.kv_positions_attended
        out["kv_positions_context"] = self.kv_positions_context
        out["spans_dropped"] = self._timeline.dropped
        return out


# -- JAX's compile pipeline ------------------------------------------------

# JAX's own duration events (`jax.monitoring`), by the counter each adds
# to. The backend's event wraps the look in the persistent cache, so a
# program the cache held counts under `backend` with its load's seconds
# and under `cache_load` again.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

#: the spans a `CompileAccount` writes; they lie INSIDE whatever phase
#: the thread that compiled was in (`build.weights`, a first
#: `prefill.dispatch` or `decode.dispatch`), so a reader of a thread's
#: leaf phases selects by name
COMPILE_SPANS = ("compile.trace", "compile.lower", "compile.backend")


class CompileAccount:
    """What JAX's compile pipeline cost this process, from JAX's own
    monitoring events: seconds and counts of tracing, lowering and the
    backend's compile-or-load (`trace_*`, `lower_*`, `backend_*`), of the
    persistent cache's reads that hit (`cache_load_*`), the cache's
    `cache_hits` / `cache_misses`, and the first three again by the
    function's name (`by_fun`: at most `MAX_FUNS` names, the ones that
    cost least under `"other"`). Each traced, lowered or compiled
    function also leaves a span `compile.<stage>` on `timeline` with
    ``t1`` the callback's `perf_counter()`, ``t0 = t1 - seconds`` and
    attr ``fun``; the kill switch stops the spans and leaves the
    counters.

    Sums of JAX's events as they come: a jitted function traced inside
    another's trace counts in both `trace_s`. Process-wide: every engine
    of the process reads the same account (`compile_account()`)."""

    MAX_FUNS = 64
    STAGES = ("trace", "lower", "backend")

    def __init__(self, timeline: Timeline = TIMELINE):
        self._timeline = timeline
        self._lock = threading.Lock()
        # guarded by: _lock
        self._totals = {k: [0.0, 0] for k in self.STAGES + ("cache_load",)}
        self._cache = dict.fromkeys(_CACHE_EVENTS.values(), 0)
        self._by_fun: Dict[str, dict] = {}
        self._other = {k: [0.0, 0] for k in self.STAGES}
        self._floor = 0.0

    def on_event(self, event: str, **_kw) -> None:
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            with self._lock:
                self._cache[key] += 1

    def on_duration(self, event: str, secs: float, **kw) -> None:
        stage = _COMPILE_EVENTS.get(event)
        if stage is None:
            return
        t1 = time.perf_counter()
        fun = kw.get("fun_name")
        if fun is not None and fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]  # lowering and the backend name it `jit(f)`
        with self._lock:
            total = self._totals[stage]
            total[0] += secs
            total[1] += 1
            if stage not in self.STAGES:  # the cache's read names nothing
                return
            if fun is not None:
                row = self._row(fun, secs)[stage]
                row[0] += secs
                row[1] += 1
        if tracing_enabled():
            self._timeline.record("compile." + stage, t1 - secs, t1, None,
                                  threading.get_ident(), {"fun": fun})

    def _row(self, fun: str, secs: float) -> dict:
        """`fun`'s row of `by_fun`. A name that finds the table full
        takes the place of the one that has cost least so far if this
        one event cost more, and counts under `"other"` if not: the
        programs that cost seconds get their rows, the hundreds of
        one-line `jax.numpy` functions traced inside them do not push
        them out. `_floor` is a lower bound of every row's cost (rows
        only grow), so a cheap event looks at no row."""
        rows = self._by_fun
        row = rows.get(fun)
        if row is not None:
            return row
        if len(rows) >= self.MAX_FUNS:
            if secs <= self._floor:
                return self._other

            def cost(f):
                return sum(v[0] for v in rows[f].values())

            least = min(rows, key=cost)
            self._floor = cost(least)
            if secs <= self._floor:
                return self._other
            for k, (seconds, n) in rows.pop(least).items():
                self._other[k][0] += seconds
                self._other[k][1] += n
        row = rows[fun] = {k: [0.0, 0] for k in self.STAGES}
        return row

    def counters(self) -> dict:
        """``{"trace_s", "trace_n", "lower_s", "lower_n", "backend_s",
        "backend_n", "cache_load_s", "cache_load_n", "cache_hits",
        "cache_misses", "by_fun": {name: {"<stage>_s", "<stage>_n"}}}``,
        cumulative since the account was made."""
        def flat(rows):
            return {f"{k}{sfx}": v[i] for k, v in rows.items()
                    for i, sfx in enumerate(("_s", "_n"))}

        with self._lock:
            out = flat(self._totals)
            out.update(self._cache)
            out["by_fun"] = {f: flat(rows)
                             for f, rows in self._by_fun.items()}
            if any(n for _, n in self._other.values()):
                out["by_fun"]["other"] = flat(self._other)
        return out


_compile_account: Optional[CompileAccount] = None
_compile_account_lock = threading.Lock()


def compile_account() -> CompileAccount:
    """The process's one `CompileAccount`, made and registered with
    `jax.monitoring` the first time it is asked for; events from before
    that are not in it."""
    global _compile_account
    with _compile_account_lock:
        if _compile_account is None:
            import jax.monitoring

            account = CompileAccount()
            jax.monitoring.register_event_listener(account.on_event)
            jax.monitoring.register_event_duration_secs_listener(
                account.on_duration)
            _compile_account = account
        return _compile_account


# -- stats-schema contracts ------------------------------------------------
# The single source of truth for the key sets the serving layers'
# ``stats()`` dicts promise (tests and external scrapers rely on them;
# the gateway `server_stats`/`pool_stats` RPCs return these dicts
# verbatim). Layers may ADD keys; removing or renaming one is a
# breaking change and must update these sets plus
# docs/observability.md. Pinned in one place by
# tests/test_observability.py via `MetricsRegistry.snapshot()`.

MODEL_SERVER_STATS_KEYS = frozenset({
    "served", "batches", "batch_fill_pct", "shed_overload",
    "shed_deadline", "shed_unavailable", "failures", "reloads",
    "reload_rejections", "breaker_state", "breaker_opens",
    "model_version", "queued", "in_flight", "queue_depth",
    "ewma_latency_ms",
    # quantized serving tier: weight precision actually serving (32 /
    # 16 / 8) and the drift-gate verdict counters — all numeric, so
    # `_flatten_numeric` carries them into the Prometheus exposition
    "weight_bits", "drift_gate_checks", "drift_gate_failures",
})

DECODE_ENGINE_STATS_KEYS = frozenset({
    "submitted", "served", "shed_overload", "shed_out_of_pages",
    "shed_deadline", "shed_unavailable", "failures", "prefills",
    "prefill_chunks", "decode_steps", "tokens_generated",
    "slot_occupancy_pct", "n_slots", "active_slots", "queued", "swaps",
    "max_len", "page_size", "pool_pages", "pages_in_use",
    "pages_in_use_peak", "queued_page_demand", "max_queued_pages",
    # quantized KV tier: bits per cache element actually allocated
    # (8 = int8 pools, else the compute dtype's width) and the
    # per-generated-token KV byte cost including the scale sidecar
    "kv_quant_bits", "kv_bytes_per_token",
    # composed blocks: bytes a slot holds whatever its length (recurrent
    # state + convolution tails), slot states overwritten at admission,
    # blocks by the kind of cache they keep (none: a block without a
    # mixer), and the routed experts' decode-step counts (choices made,
    # choices on experts held here, held experts hit, held experts the
    # grouped product was told to read, steps, experts held)
    "state_bytes_per_slot", "state_resets", "recurrent_blocks",
    "kv_blocks", "stateless_blocks", "moe_routed",
    "moe_held_choices", "moe_experts_hit", "moe_experts_read", "moe_steps",
    "moe_experts_held",
    # window blocks (attention that reads the last W positions): how
    # many, a slot's ring of pages and its bytes over those blocks, and
    # the page pool's second class in use (all 0 on a net without them)
    "window_blocks", "window_ring_pages", "window_bytes_per_slot",
    "window_pages_in_use", "window_pages_in_use_peak",
    # tensor-parallel tier: mesh degree (1 = single-device engine, so
    # capacity dashboards never branch on key presence) and the
    # per-shard slice of kv_bytes_per_token — each device's actual
    # per-token KV residency under head sharding
    "tp_degree", "tp_kv_bytes_per_token_per_shard",
    # multi-tenant QoS tier: batch-lane preemptions, SLO-infeasible
    # sheds, quota rejections, and the per-tenant sub-dicts (keyed by
    # tenant name; each value pins TENANT_STATS_KEYS)
    "preemptions", "slo_sheds", "shed_quota", "tenants",
    # KV transfer tier (`serving.kv_transfer`): page-quota sheds, slots
    # exported/imported as leased handoffs, lease resolutions by
    # outcome, live leases, and total payload bytes shipped out
    "shed_page_quota", "migrations_out", "migrations_in",
    "handoffs_committed", "handoffs_aborted", "handoffs_expired",
    "handoff_leases", "handoffs_unfetched", "kv_transfer_bytes",
    # cluster prefix cache tier (`serving.prefix_directory`): fetches
    # landed vs degraded to cold prefill, wire bytes/latency of prefix
    # page pulls, chains exported to peers, and prompt tokens whose
    # prefill was skipped via pages fetched from ANOTHER host (the
    # cluster-level hit ratio next to the local prefix_hit_tokens_pct)
    "prefix_fetches", "prefix_fetch_fallbacks", "prefix_fetch_bytes",
    "prefix_fetch_ms", "prefix_exports", "cluster_prefix_hit_tokens",
    "cluster_prefix_hit_tokens_pct",
    # the scheduler thread's own account (`ThreadPhases.counters`:
    # seconds and counts per leaf phase, cumulative), and admission
    # wait summed where admission happens: seconds queued over requests
    # that left the queue for a slot
    "loop", "queue_wait_s", "admitted",
    # set-up's account: `_build`'s phases (`ThreadPhases.counters` over
    # BUILD_PHASES, with `builds`, `weight_hash_bytes` and
    # `weight_hash_host_bytes`), and the process-wide
    # `CompileAccount.counters`
    "build", "compile",
})

# Per-tenant counters nested under DecodeEngine ``stats()["tenants"]``
# — one dict per tenant name the engine has seen (quota'd or not).
TENANT_STATS_KEYS = frozenset({
    "submitted", "served", "shed_quota", "tokens_generated",
    "preemptions", "rate", "burst", "tokens",
    # KV page quota tier: page-ceiling rejections, the configured
    # ceiling (None = unlimited), and the tenant's live page footprint
    "shed_page_quota", "max_pages", "pages_reserved",
    # batch-lane weighted-fair queueing: this tenant's stride share
    # (1.0 default; weight 2 earns twice the admitted span of weight 1)
    "weight",
})

REPLICA_POOL_STATS_KEYS = frozenset({
    "n_replicas", "healthy_replicas", "pool_in_flight",
    "admission_budget", "served", "failovers", "hedges_fired",
    "hedge_wins", "evictions", "readmissions", "rolling_reloads",
    "rollbacks", "shed_overload", "shed_unavailable", "ewma_latency_ms",
    "replicas",
    # elasticity tier: replicas added/drained-out by the autoscaler (or
    # an operator) since construction
    "replicas_added", "replicas_removed",
    # live decode-state migration: redirects resumed on a peer vs
    # degraded to the full re-prefill fallback
    "migrations", "migration_fallbacks",
    # cluster prefix cache: dispatches steered to a chain holder within
    # the affinity margin, and the shared directory's live entry count
    # (0 when no directory is bound)
    "affinity_routes", "directory_entries",
})

# `Autoscaler.stats()` — registered under the pool's metrics registry
# as component "autoscaler", so the gateway `metrics` exposition and
# `autoscaler_stats` RPC both carry it.
AUTOSCALER_STATS_KEYS = frozenset({
    "autoscale_events", "scale_ups", "scale_downs",
    "autoscale_failures", "samples", "pressure", "pressure_ewma",
    "min_replicas", "max_replicas", "cooldown_remaining",
    "last_decision",
    # migrate-then-drain shrink: wall time of the most recent
    # scale-down — the regression alarm for "scale_down no longer
    # blocks on the longest in-flight generation"
    "last_scale_down_ms",
})

# `ExactlyOnceDoor.stats()["cache"]` (`serving.exactly_once`) — the
# gateway `exactly_once_stats` RPC returns the enclosing dict verbatim;
# the ledger counters the crash/reclaim drills and the bench assert on.
EXACTLY_ONCE_STATS_KEYS = frozenset({
    "completed", "inflight", "capacity", "ttl_s", "dedup_hits",
    "executions", "expired", "evicted", "double_executions",
    "durable_loaded",
})

POOL_REPLICA_STATS_KEYS = frozenset({
    "state", "consecutive_failures", "evictions", "stale",
}) | MODEL_SERVER_STATS_KEYS

# `StreamRegistry.stats()` (`serving.streaming`) — registered under the
# serving tier's metrics as component "streaming" by the first streamed
# request, so the gateway `metrics` exposition carries the resumable-
# streaming counters (`stream_resumes`, backpressure sheds, the cursor
# dedup totals) the chaos drills and bench assert on.
STREAMING_STATS_KEYS = frozenset({
    "streams_active", "streams_opened", "streams_finished",
    "stream_resumes", "stream_backpressure_sheds",
    "duplicate_tokens_dropped", "ring_capacity", "ttl_s",
})
