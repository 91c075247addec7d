"""Serving-tier observability (`serving/observability.py`): request
tracing, metrics registry, flight recorder, and their wiring through
gateway → ReplicaPool → ModelServer → DecodeEngine.

The ladders:

1. **Trace/Span primitives** — span decisions (``ok`` vs escaping
   exception class), causal ordering by start time, the MAX_SPANS
   bound, thread-local propagation (`use_trace`/`maybe_trace`), the
   falsy `NULL_TRACE`, and the ``DL4J_TPU_NO_TRACING`` kill switch.
2. **Metrics registry** — counters/gauges/histograms, the
   `snapshot()` schema, failure isolation (a dying component or gauge
   must not take a scrape down), and the Prometheus text exposition
   (cumulative ``le`` buckets, labels, flattened ``stats_`` gauges).
3. **Flight recorder** — ring bounds, the pinned failures ring, the
   serialize-at-dump-time contract (late spans still appear), and the
   kill switch.
4. **The stats-schema contract, pinned in ONE place** — the key sets
   each layer's ``stats()`` dict promises (the gateway
   ``server_stats``/``pool_stats`` RPCs return them verbatim), read
   through `MetricsRegistry.snapshot()` as external scrapers would.
5. **Chaos postmortems** — an `OutOfPagesError` shed and a
   `ReplicaCrashInjector` failover must each leave a flight-recorder
   dump naming the page-demand decision / the failing replica.
6. **The end-to-end acceptance drill** — a chaos-injected failing
   ``generate`` through the WIRE gateway yields, via the
   ``flight_record`` RPC, a complete causally-ordered span timeline
   whose trace_id also rides the error payload back to the client.
"""
import json
import signal
import threading
import time

import numpy as np
import pytest

import deeplearning4j_tpu as dl4j
from deeplearning4j_tpu.models.transformer import (
    generate,
    gpt_configuration,
)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.ops.activations import Activation
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.serving import (
    DeadlineExceededError,
    DecodeEngine,
    InferenceFailedError,
    InjectedServingFault,
    ModelServer,
    OutOfPagesError,
    ReplicaCrashInjector,
    ReplicaPool,
)
from deeplearning4j_tpu.serving import observability as obs

VOCAB = 48
WEDGE_GUARD_S = 120


@pytest.fixture(autouse=True)
def _wedge_guard():
    """Same tier-1 safety net as the replica-pool suite: a wedged
    serving experiment dies by SIGALRM, not by eating the budget."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def boom(signum, frame):
        raise TimeoutError(
            f"observability test exceeded the {WEDGE_GUARD_S} s wedge "
            "guard")

    old = signal.signal(signal.SIGALRM, boom)
    signal.alarm(WEDGE_GUARD_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _gpt_net(seed: int = 12345, **kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_layers", 2)
    kw.setdefault("max_length", 64)
    net = dl4j.MultiLayerNetwork(gpt_configuration(seed=seed, **kw))
    net.init()
    return net


@pytest.fixture(scope="module")
def net():
    return _gpt_net()


def _prompts(n, t0, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, VOCAB, (n, t0)).astype(np.int32)


def _dense_conf(seed=7):
    return (dl4j.NeuralNetConfiguration.Builder()
            .seed(seed).learning_rate(0.3)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3,
                               activation=Activation.SOFTMAX,
                               loss=LossFunction.MCXENT))
            .build())


def _dense_net(seed=7):
    n = dl4j.MultiLayerNetwork(_dense_conf(seed=seed))
    n.init()
    return n


def _x(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 4)).astype(np.float32)


def _span_names(trace_dict):
    return [s["name"] for s in trace_dict["spans"]]


# ------------------------------------------------------- trace primitives


def test_span_context_stamps_ok_and_exception_decisions():
    tr = obs.Trace()
    with tr.span("fine", slot=3):
        pass
    with pytest.raises(ValueError):
        with tr.span("broken"):
            raise ValueError("boom")
    d = tr.to_dict()
    assert d["trace_id"] == tr.trace_id and len(d["trace_id"]) == 16
    fine, broken = d["spans"]
    assert fine["name"] == "fine" and fine["decision"] == "ok"
    assert fine["attrs"] == {"slot": 3}
    assert fine["t1"] >= fine["t0"]
    assert broken["decision"] == "ValueError"


def test_trace_orders_spans_causally_and_carries_events():
    tr = obs.Trace()
    # recorded out of order (as concurrent layers would): to_dict must
    # sort by start time — causal order for a single request
    tr.add_timed("decode", 10.0, 11.0, steps=4)
    tr.add_timed("queue-wait", 1.0, 2.0)
    tr.event("enqueue", queue_depth=1)  # stamped with the real clock,
    # which monotonic()-dwarfs the synthetic interval times above
    tr.finish("served")
    d = tr.to_dict()
    t0s = [s["t0"] for s in d["spans"]]
    assert t0s == sorted(t0s)
    assert _span_names(d) == ["queue-wait", "decode", "enqueue"]
    assert d["decision"] == "served"
    enq = d["spans"][2]
    assert enq["t1"] == enq["t0"]  # zero-width mark
    assert "decision" not in enq  # informational, no verdict


def test_trace_bounds_spans_and_counts_drops(monkeypatch):
    monkeypatch.setattr(obs.Trace, "MAX_SPANS", 4)
    tr = obs.Trace()
    for i in range(7):
        tr.event(f"e{i}")
    d = tr.to_dict()
    assert len(d["spans"]) == 4
    assert d["dropped_spans"] == 3


def test_null_trace_is_falsy_and_absorbs_everything():
    assert not obs.NULL_TRACE
    assert bool(obs.Trace())
    with obs.NULL_TRACE.span("x", a=1):
        pass
    obs.NULL_TRACE.event("y")
    obs.NULL_TRACE.add_timed("z", 0.0, 1.0)
    obs.NULL_TRACE.finish("served")
    assert obs.NULL_TRACE.to_dict() is None
    assert obs.NULL_TRACE.trace_id is None


def test_use_trace_binds_thread_local_and_restores():
    assert obs.current_trace() is None
    outer, inner = obs.Trace(), obs.Trace()
    with obs.use_trace(outer):
        assert obs.current_trace() is outer
        with obs.use_trace(inner):
            assert obs.current_trace() is inner
        assert obs.current_trace() is outer
    assert obs.current_trace() is None


def test_use_trace_does_not_leak_across_threads():
    seen = []
    with obs.use_trace(obs.Trace()):
        t = threading.Thread(target=lambda: seen.append(obs.current_trace()))
        t.start()
        t.join()
    assert seen == [None]


def test_maybe_trace_precedence_explicit_then_bound_then_fresh():
    explicit, bound = obs.Trace(), obs.Trace()
    with obs.use_trace(bound):
        assert obs.maybe_trace(explicit) is explicit
        assert obs.maybe_trace() is bound
    minted = obs.maybe_trace()
    assert isinstance(minted, obs.Trace)
    assert minted is not bound and minted is not explicit


def test_kill_switch_mints_null_trace(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_NO_TRACING", "1")
    assert not obs.tracing_enabled()
    assert obs.maybe_trace() is obs.NULL_TRACE
    # an upstream layer's real trace still wins: in-process callers who
    # passed one explicitly keep their timeline even when minting is off
    tr = obs.Trace()
    assert obs.maybe_trace(tr) is tr


def test_attach_trace_stamps_errors_and_skips_null():
    tr = obs.Trace()
    tr.finish("ValueError")
    err = ValueError("boom")
    obs.attach_trace(err, tr)
    assert err.trace_id == tr.trace_id
    assert err.trace["decision"] == "ValueError"
    bare = ValueError("no trace")
    obs.attach_trace(bare, obs.NULL_TRACE)
    assert not hasattr(bare, "trace_id")


# ------------------------------------------------------- metrics registry


def test_counter_gauge_histogram_basics():
    reg = obs.MetricsRegistry()
    reg.counter("served").inc()
    reg.counter("served").inc(4)  # get-or-create: same instrument
    assert reg.counter("served").value == 5
    reg.gauge("depth").set(7)
    reg.gauge("live", fn=lambda: 3.5)
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 5000.0):
        h.observe(v)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms", "components"}
    assert snap["counters"]["served"] == 5
    assert snap["gauges"]["depth"] == 7
    assert snap["gauges"]["live"] == 3.5
    hs = snap["histograms"]["lat_ms"]
    assert hs["buckets"] == [1.0, 10.0, 100.0]
    assert hs["counts"] == [1, 1, 1, 1]  # one overflow past the last bound
    assert hs["count"] == 4 and hs["sum"] == pytest.approx(5055.5)


def test_gauge_and_component_failures_cannot_break_a_scrape():
    reg = obs.MetricsRegistry()

    def dying_gauge():
        raise RuntimeError("mid-teardown")

    def dying_stats():
        raise RuntimeError("component gone")

    reg.gauge("sick", fn=dying_gauge)
    reg.register_stats("sick_component", dying_stats)
    reg.register_stats("fine_component", lambda: {"served": 1})
    snap = reg.snapshot()
    assert snap["gauges"]["sick"] is None
    assert snap["components"]["sick_component"] == {"error": "RuntimeError"}
    assert snap["components"]["fine_component"] == {"served": 1}
    # and the text form still renders (the sick gauge is simply omitted)
    text = reg.exposition()
    assert "sick" not in text.split("stats_")[0]
    assert "dl4j_stats_fine_component_served 1" in text


def test_exposition_text_format():
    reg = obs.MetricsRegistry()
    reg.counter("served").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0))
    for v in (0.5, 0.6, 5.0, 500.0):
        h.observe(v)
    reg.register_stats("engine", lambda: {
        "served": 9, "nested": {"pages": 4}, "state": "closed",
        "flag": True})
    text = reg.exposition(labels={"model": "m"})
    lines = text.splitlines()
    assert '# TYPE dl4j_served counter' in lines
    assert 'dl4j_served{model="m"} 3' in lines
    assert 'dl4j_depth{model="m"} 2' in lines
    # histogram buckets are CUMULATIVE and +Inf equals the total count
    assert 'dl4j_lat_ms_bucket{model="m",le="1.0"} 2' in lines
    assert 'dl4j_lat_ms_bucket{model="m",le="10.0"} 3' in lines
    assert 'dl4j_lat_ms_bucket{model="m",le="+Inf"} 4' in lines
    assert 'dl4j_lat_ms_count{model="m"} 4' in lines
    # component stats flatten to gauges; strings drop, bools become ints
    assert 'dl4j_stats_engine_served{model="m"} 9' in lines
    assert 'dl4j_stats_engine_nested_pages{model="m"} 4' in lines
    assert 'dl4j_stats_engine_flag{model="m"} 1' in lines
    assert not any("state" in ln for ln in lines)


def test_histogram_excursion_hook_semantics():
    """The p99-excursion primitive: no firing below `min_count`, the
    bound is the live bucket-quantile computed BEFORE the observation
    lands, only strictly-past-the-bound values fire, and the hook
    receives (value, bound, trace) outside the lock."""
    h = obs.Histogram("lat_ms", buckets=(1, 10, 100))
    fired = []
    h.enable_excursion(quantile=0.5, min_count=2,
                       hook=lambda v, b, tr: fired.append((v, b, tr)))
    h.observe(0.5)
    h.observe(500.0, trace="t0")  # count=1 < min_count: silent
    assert fired == []
    h.observe(0.5)
    assert h.quantile_bound(0.5) == 1.0
    h.observe(1.0, trace="t1")    # == bound: NOT an excursion
    assert fired == []
    h.observe(50.0, trace="t2")   # past the bound: fires
    assert fired == [(50.0, 1.0, "t2")]
    with pytest.raises(ValueError):
        h.enable_excursion(quantile=1.5)
    with pytest.raises(ValueError):
        h.enable_excursion(min_count=0)


def test_histogram_excursion_silent_in_inf_bucket():
    """When the quantile falls in the implicit +Inf bucket there is no
    finite bar to judge against — the hook must stay silent instead of
    firing on every observation."""
    h = obs.Histogram("lat_ms", buckets=(1,))
    fired = []
    h.enable_excursion(quantile=0.5, min_count=1,
                       hook=lambda v, b, tr: fired.append(v))
    for _ in range(4):
        h.observe(100.0)  # all mass in +Inf
    h.observe(500.0)
    assert fired == []


# -------------------------------------------------------- flight recorder


def test_flight_recorder_rings_bound_and_pin_failures():
    rec = obs.FlightRecorder(capacity=4, failure_capacity=2,
                             event_capacity=3)
    for i in range(6):
        tr = obs.Trace()
        tr.finish("served")
        rec.record(tr, "served", n=i)
    for name in ("OutOfPagesError", "InferenceFailedError",
                 "ServerOverloadedError"):
        tr = obs.Trace()
        tr.finish(name)
        rec.record(tr, name)
    for i in range(5):
        rec.event("admit", slot=i)
    d = rec.dump()
    assert len(d["requests"]) == 4  # ring: only the newest survive
    # the failures ring pins postmortems: success traffic cannot push
    # them out, and the OLDEST failure fell off its own (smaller) ring
    assert [f["decision"] for f in d["failures"]] == \
        ["InferenceFailedError", "ServerOverloadedError"]
    assert [e["slot"] for e in d["events"]] == [2, 3, 4]
    assert all(e["kind"] == "admit" for e in d["events"])
    assert d["capacity"] == {"requests": 4, "failures": 2, "events": 3}


def test_flight_recorder_serializes_traces_at_dump_time():
    rec = obs.FlightRecorder()
    tr = obs.Trace()
    tr.add_timed("attempt", 0.0, 1.0, decision="InjectedServingFault")
    rec.record(tr, "served")
    # a pool-level failover span lands AFTER the replica's attempt was
    # recorded — by-reference storage means the dump still shows it
    tr.add_timed("failover-retry", 1.0, 2.0)
    d = rec.dump()
    assert _span_names(d["requests"][0]["trace"]) == \
        ["attempt", "failover-retry"]


def test_flight_recorder_respects_kill_switch(monkeypatch):
    rec = obs.FlightRecorder()
    monkeypatch.setenv("DL4J_TPU_NO_TRACING", "1")
    tr = obs.Trace()  # built by hand: only minting is switched off
    rec.record(tr, "served")
    rec.event("admit")
    monkeypatch.delenv("DL4J_TPU_NO_TRACING")
    d = rec.dump()
    assert d["requests"] == [] and d["events"] == []


# ------------------------------------- the stats-schema contract (ONE place)


def test_stats_schema_contracts_via_metrics_snapshot(net):
    """THE schema pin: every serving layer's ``stats()`` keys, read
    through the metrics-registry snapshot exactly as a scraper would.
    Layers may add keys; removing/renaming one fails here and nowhere
    else."""
    srv = ModelServer(_dense_net())
    try:
        srv.predict(_x())
        comp = srv.metrics_snapshot()["components"]["model_server"]
        assert obs.MODEL_SERVER_STATS_KEYS <= set(comp)
    finally:
        srv.shutdown()

    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,))
    try:
        comp = eng.metrics_snapshot()["components"]["decode_engine"]
        assert obs.DECODE_ENGINE_STATS_KEYS <= set(comp)
    finally:
        eng.shutdown()

    pool = ReplicaPool.from_net(_dense_net(), 2, probe_interval=30.0)
    try:
        pool.predict(_x(), timeout=30.0)
        comp = pool.metrics_snapshot()["components"]["replica_pool"]
        assert obs.REPLICA_POOL_STATS_KEYS <= set(comp)
        for rep in comp["replicas"].values():
            assert obs.POOL_REPLICA_STATS_KEYS <= set(rep)
    finally:
        pool.shutdown(drain_timeout=3.0)


def test_quantization_stats_keys_in_contract_and_exposition(net):
    """ISSUE 13 schema satellite: the quantized-serving keys are part
    of the frozenset contracts and land on the Prometheus page
    UNCONDITIONALLY — a dense/unquantized deployment scrapes the same
    schema with full-precision values, so dashboards never branch."""
    assert {"weight_bits", "drift_gate_checks", "drift_gate_failures"} \
        <= obs.MODEL_SERVER_STATS_KEYS
    assert {"kv_quant_bits", "kv_bytes_per_token"} \
        <= obs.DECODE_ENGINE_STATS_KEYS
    srv = ModelServer(net, quantize={"weights": "bf16", "kv": "int8"},
                      generation={"n_slots": 2, "max_len": 32,
                                  "prompt_buckets": (8,)})
    try:
        srv.generate(_prompts(1, 5)[0], 3)
        s = srv.stats()
        assert s["weight_bits"] == 16
        assert s["generation"]["kv_quant_bits"] == 8
        text = srv.metrics_text()
        assert "dl4j_stats_model_server_weight_bits 16" in text
        assert "dl4j_stats_model_server_drift_gate_checks 0" in text
        assert "dl4j_stats_model_server_drift_gate_failures 0" in text
        assert "dl4j_stats_decode_engine_kv_quant_bits 8" in text
        assert "dl4j_stats_decode_engine_kv_bytes_per_token" in text
    finally:
        srv.shutdown()
    # unquantized engine: SAME keys, full-precision values
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,))
    try:
        assert eng.stats()["kv_quant_bits"] == 32
        assert "dl4j_stats_decode_engine_kv_quant_bits 32" \
            in eng.metrics_text()
    finally:
        eng.shutdown()


def test_server_generation_shares_one_registry_and_recorder(net):
    """One dump, one scrape page per server: the lazily-built engine's
    timelines and scheduler events land in the SAME recorder/registry
    as the server's predicts — the gateway RPCs expose one object."""
    srv = ModelServer(net, generation={
        "n_slots": 2, "max_len": 32, "prompt_buckets": (8,)})
    try:
        toks = srv.generate(_prompts(1, 5)[0], 4)
        assert toks.shape == (4,)
        snap = srv.metrics_snapshot()
        comps = snap["components"]
        assert {"model_server", "decode_engine"} <= set(comps)
        assert comps["decode_engine"]["served"] == 1
        assert snap["histograms"][
            "decode_engine_generate_latency_ms"]["count"] == 1
        dump = srv.flight_record()
        assert any(e["kind"] == "admit" for e in dump["events"])
        assert any(r["kind"] == "generate" and r["decision"] == "served"
                   for r in dump["requests"])
    finally:
        srv.shutdown()


# -------------------------------------------- engine timelines end to end


def test_engine_served_request_leaves_causal_timeline(net):
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,))
    try:
        req = eng.submit(_prompts(1, 5, seed=3)[0], 6)
        toks = req.result(timeout=120.0)
        assert toks.shape == (6,)
        assert req.trace.trace_id
        dump = eng.flight_record()
    finally:
        eng.shutdown()
    entry = next(r for r in dump["requests"]
                 if r["trace"]["trace_id"] == req.trace.trace_id)
    assert entry["decision"] == "served" and entry["attrs"]["tokens"] == 6
    names = _span_names(entry["trace"])
    # the request's life, in causal order: enqueued, waited, admitted
    # to a slot, prefilled, decoded
    for phase in ("enqueue", "queue-wait", "admission", "prefill",
                  "decode"):
        assert phase in names, f"missing span {phase!r} in {names}"
    assert names.index("enqueue") < names.index("admission") \
        < names.index("prefill") < names.index("decode")
    t0s = [s["t0"] for s in entry["trace"]["spans"]]
    assert t0s == sorted(t0s)
    assert entry["trace"]["decision"] == "served"
    kinds = {e["kind"] for e in dump["events"]}
    assert {"admit", "retire"} <= kinds


def test_engine_kill_switch_serves_without_recording(net, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_NO_TRACING", "1")
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,))
    try:
        req = eng.submit(_prompts(1, 5, seed=4)[0], 4)
        assert req.result(timeout=120.0).shape == (4,)
        assert not req.trace  # NULL_TRACE rode the request
        dump = eng.flight_record()
        assert dump["requests"] == [] and dump["events"] == []
        assert eng.stats()["served"] == 1  # counters are not switched
    finally:
        eng.shutdown()


# ------------------------------------------------------ chaos postmortems


@pytest.mark.chaos
def test_out_of_pages_shed_leaves_page_demand_postmortem(net):
    """An OutOfPages shed must be reconstructible after the fact: the
    typed error carries the timeline, the failures ring pins it, and
    the events ring names the exact reservation the door refused."""
    gate = threading.Event()

    def slow_hook(phase, info):
        if phase == "pre_decode":
            gate.wait(0.05)

    # 4-page pool; each request (t0=5 -> bucket 8, span 28) needs 4
    # pages: one in flight fills the pool, one queued fills the demand
    # cap, the third sheds at the door
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,),
                       page_size=8, pool_pages=4, max_queued_pages=4,
                       step_hooks=[slow_hook])
    try:
        prompts = _prompts(3, 5, seed=43)
        holder = eng.submit(prompts[0], 24)
        while not holder.tokens:
            assert holder.error is None, holder.error
            time.sleep(0.005)
        waiter = eng.submit(prompts[1], 24)
        with pytest.raises(OutOfPagesError) as ei:
            eng.submit(prompts[2], 24)
        gate.set()
        holder.result(timeout=120.0)
        waiter.result(timeout=120.0)
        dump = eng.flight_record()
    finally:
        gate.set()
        eng.shutdown()
    # the error itself carries the timeline over any wire
    assert ei.value.trace_id
    assert ei.value.trace["decision"] == "OutOfPagesError"
    # the failures ring pinned the shed with the page-demand verdict
    shed = next(f for f in dump["failures"]
                if f["trace"]["trace_id"] == ei.value.trace_id)
    assert shed["decision"] == "OutOfPagesError"
    assert shed["attrs"]["pages_needed"] == 4
    assert shed["attrs"]["pages_in_use"] == 4
    assert shed["attrs"]["queued_page_demand"] == 4
    assert shed["attrs"]["max_queued_pages"] == 4
    # and the scheduler events ring names the same decision
    ev = next(e for e in dump["events"]
              if e["kind"] == "shed"
              and e.get("error") == "OutOfPagesError")
    assert ev["pages_needed"] == 4 and ev["queued_page_demand"] == 4


@pytest.mark.chaos
def test_failover_leaves_flight_record_naming_dead_replica():
    """A crash-driven failover must be attributable afterwards: the
    pool's events ring names the replica that failed, and the served
    request's own timeline records the hop."""
    crash = ReplicaCrashInjector(crashed=True)
    servers = [ModelServer(_dense_net(), infer_hooks=[crash]),
               ModelServer(_dense_net(seed=8))]
    pool = ReplicaPool(servers, probe_interval=30.0)  # probes quiet:
    # the request path, not the prober, must produce the postmortem
    try:
        out = pool.predict(_x(), timeout=30.0)
        assert out.shape == (8, 3)
        stats = pool.stats()
        assert stats["failovers"] >= 1
        dump = pool.flight_record()
    finally:
        pool.shutdown(drain_timeout=3.0)
    fo = next(e for e in dump["pool"]["events"] if e["kind"] == "failover")
    assert fo["replica"] == 0  # the crashed replica, by id
    assert fo["error"] == "InferenceFailedError"
    # the request served: its pool-level timeline shows the hop
    served = next(r for r in dump["pool"]["requests"]
                  if r["decision"] == "served")
    hop = next(s for s in served["trace"]["spans"]
               if s["name"] == "failover")
    assert hop["attrs"]["replica"] == 0
    # two-level dump: the dead replica's OWN ring pinned its failure
    rep0 = dump["replicas"]["0"]
    assert any(f["decision"] == "InferenceFailedError"
               for f in rep0["failures"])


# --------------------------------------- the wire-level acceptance drill


@pytest.mark.chaos
def test_gateway_generate_failure_postmortem_over_the_wire(net):
    """ISSUE 12 acceptance: a chaos-injected failing generate through
    the WIRE gateway yields (a) a GatewayError whose payload carries
    trace_id + the span timeline, and (b) via the ``flight_record``
    RPC, the same timeline pinned in the failures ring, causally
    ordered gateway → engine. The ``metrics`` RPC scrapes the same
    story as Prometheus text."""
    from deeplearning4j_tpu.gateway import (
        GatewayClient,
        GatewayError,
        GatewayServer,
    )

    boom = {"armed": True}

    def chaos_hook(phase, info):
        if phase == "pre_decode" and boom["armed"]:
            boom["armed"] = False  # one-shot: the retry must succeed
            raise InjectedServingFault("injected decode fault")

    gw = GatewayServer(serving={"generation": {
        "n_slots": 2, "max_len": 32, "prompt_buckets": (8,),
        "step_hooks": [chaos_hook]}})
    gw.start()
    cl = None
    try:
        cl = GatewayClient(port=gw.port)
        conf = gpt_configuration(vocab_size=VOCAB, d_model=32, n_heads=2,
                                 n_layers=2, max_length=64)
        cl.call("create_model", name="m",
                config=json.loads(conf.to_json()))
        prompt = _prompts(1, 5, seed=9)[0]
        with pytest.raises(GatewayError) as ei:
            cl.call("generate", name="m", prompt_ids=prompt, n_tokens=6)
        err = ei.value
        assert err.error_type == "InferenceFailedError"
        # the timeline rode the ERROR payload over the wire
        assert err.trace_id and err.trace["trace_id"] == err.trace_id
        assert err.trace_id == cl.last_trace_id
        names = _span_names(err.trace)
        for phase in ("gateway", "enqueue", "queue-wait", "admission",
                      "prefill"):
            assert phase in names, f"missing span {phase!r} in {names}"
        # causal order: the gateway span opened before any engine work
        t0s = [s["t0"] for s in err.trace["spans"]]
        assert t0s == sorted(t0s) and names[0] == "gateway"
        assert err.trace["decision"] == "InferenceFailedError"

        # the flight_record RPC pins the SAME postmortem server-side
        dump = cl.call("flight_record", name="m")
        pinned = next(f for f in dump["failures"]
                      if f["trace"]["trace_id"] == err.trace_id)
        assert pinned["decision"] == "InferenceFailedError"
        assert "prefill" in _span_names(pinned["trace"])

        # the chaos was one-shot: the retry serves, and the SUCCESS
        # response carries its own timeline too
        toks = cl.call("generate", name="m", prompt_ids=prompt,
                       n_tokens=6)
        assert toks.shape == (6,)
        assert cl.last_trace_id and cl.last_trace_id != err.trace_id
        assert cl.last_trace["decision"] == "served"
        assert "decode" in _span_names(cl.last_trace)

        # the metrics RPC scrapes the same registry as Prometheus text
        text = cl.call("metrics")
        assert '# TYPE dl4j_stats_decode_engine_served gauge' in text
        assert 'dl4j_stats_decode_engine_served{model="m"} 1' in text
        assert 'dl4j_stats_decode_engine_failures{model="m"} 1' in text
        assert 'dl4j_decode_engine_generate_latency_ms_count{model="m"}' \
            in text
    finally:
        if cl is not None:
            cl.close()
        gw.stop()


# ------------------------------------------- the scheduler's own timeline


def test_timeline_is_bounded_and_counts_what_it_drops():
    tl = obs.Timeline(capacity=4)
    for i in range(10):
        tl.record("decode.wait", float(i), i + 0.5, cause=i)
    spans = tl.snapshot()
    assert [s[3] for s in spans] == [6, 7, 8, 9]  # the oldest went
    assert tl.dropped == 6
    assert all(isinstance(s, tuple) and len(s) == 6 for s in spans)
    # a window keeps every span that overlaps it, cut spans included
    assert [s[3] for s in tl.snapshot(t0=7.2, t1=8.1)] == [7, 8]
    assert obs.TIMELINE.snapshot() is not obs.TIMELINE.snapshot()


def test_thread_phases_partition_a_threads_time():
    tl = obs.Timeline()
    ph = obs.SchedulerPhases(tl)
    ph.enter("wait-work")
    ph.begin_iteration()
    ph.enter("admit")
    ph.enter("admit")  # the phase goes on: no second span
    ph.enter("decode.dispatch", program="decode_step", chunk=1, active=2)
    ph.annotate(kv_positions_attended=7)  # on the open phase's span
    ph.enter("decode.wait")
    still_open = ph.counters()
    ph.close()
    spans = tl.snapshot()
    assert [s[0] for s in spans] == ["wait-work", "admit",
                                     "decode.dispatch", "decode.wait"]
    for a, b in zip(spans, spans[1:]):
        assert a[2] == b[1]  # one instant ends a phase and starts the next
    assert [s[3] for s in spans] == [0, 1, 1, 1]
    assert {s[4] for s in spans} == {threading.get_ident()}
    assert spans[2][5] == {"program": "decode_step", "chunk": 1,
                           "active": 2, "kv_positions_attended": 7}
    assert spans[3][5] is None
    c = ph.counters()
    assert c["iterations"] == 1 and c["admit_n"] == 1
    total = sum(v for k, v in c.items()
                if k.endswith("_s") and k != "sink_s")
    assert total == pytest.approx(spans[-1][2] - spans[0][1], abs=1e-9)
    # the open phase already counted with what it had lasted
    assert 0.0 < still_open["decode.wait_s"] <= c["decode.wait_s"]
    assert still_open["decode.wait_n"] == 0
    with pytest.raises(KeyError):
        ph.enter("no-such-phase")


def _thread_spans(eng, since):
    return sorted((s for s in obs.TIMELINE.snapshot(t0=since)
                   if s[4] == eng._thread.ident), key=lambda s: s[1])


def _scheduler_spans(eng, since):
    """The scheduler thread's leaf phases. What else the thread leaves
    on the timeline lies INSIDE one of them: JAX's compile pipeline
    under a first dispatch, `_build`'s phases under a swap."""
    spans = _thread_spans(eng, since)
    assert {s[0] for s in spans} <= set(obs.LEAF_PHASES) \
        | set(obs.COMPILE_SPANS) | set(obs.BUILD_PHASES)
    return [s for s in spans if s[0] in obs.LEAF_PHASES]


def test_scheduler_leaf_spans_partition_its_thread(net):
    """The property the per-layer metrics rest on: over a stretch in
    which the engine runs, its thread is in exactly one leaf phase at
    any moment, and the counters say what the spans say."""
    since = time.perf_counter()
    seen = []
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,),
                       decode_chunk=4)
    try:
        reqs = [eng.submit(p, 9, on_token=lambda c, t, e: seen.append(c))
                for p in _prompts(5, 5, seed=11)]
        for r in reqs:
            assert r.result(timeout=120.0).shape == (9,)
    finally:
        eng.shutdown()
    spans = _scheduler_spans(eng, since)
    assert {s[0] for s in spans} <= set(obs.LEAF_PHASES)
    assert {"admit", "housekeeping", "prefill.dispatch", "prefill.wait",
            "prefill.deliver", "decode.dispatch", "decode.wait",
            "decode.deliver"} <= {s[0] for s in spans}
    gaps = 0.0
    for a, b in zip(spans, spans[1:]):
        assert b[1] >= a[2], f"{a} overlaps {b}"
        gaps += b[1] - a[2]
        assert b[3] >= a[3]  # an iteration's spans lie together
    assert gaps <= 0.01 * (spans[-1][2] - spans[0][1])
    loop = eng.stats()["loop"]
    for phase in obs.LEAF_PHASES:
        mine = [s for s in spans if s[0] == phase]
        assert loop[phase + "_n"] == len(mine)
        assert loop[phase + "_s"] == pytest.approx(
            sum(s[2] - s[1] for s in mine), abs=1e-3)
    assert loop["iterations"] == max(s[3] for s in spans)
    assert loop["spans_dropped"] == obs.TIMELINE.dropped
    assert loop["sink_n"] == len(seen) == 5 * 9 and loop["sink_s"] > 0.0
    # a dispatch names its program, and a prefill the request it serves
    ids = {r.trace.trace_id for r in reqs}
    prefills = [s for s in spans if s[0] == "prefill.dispatch"]
    assert {s[5]["trace_id"] for s in prefills} == ids
    assert {s[5]["program"] for s in prefills} == {"prefill"}
    decodes = [s for s in spans if s[0] == "decode.dispatch"]
    assert {s[5]["program"] for s in decodes} <= {"decode_chunked",
                                                  "decode_step"}
    assert all(1 <= s[5]["active"] <= 2 and s[5]["chunk"] in (1, 4)
               for s in decodes)
    # within an iteration: the issue of one dispatch, then the wait for
    # and the delivery of the one before it; the first dispatch has
    # nothing to collect and the last collect nothing to issue
    by_cause = {}
    for s in spans:
        by_cause.setdefault(s[3], []).append(s[0])
    shapes = set()
    for names in by_cause.values():
        d = tuple(n[7:] for n in names if n.startswith("decode."))
        assert d in ((), ("dispatch",), ("dispatch", "wait", "deliver"),
                     ("wait", "deliver"))
        shapes.add(d)
    assert ("dispatch", "wait", "deliver") in shapes
    assert loop["decode.dispatch_n"] == loop["decode.wait_n"] \
        == loop["decode.deliver_n"]
    assert 0 < loop["ahead_n"] \
        <= loop["decode.dispatch_n"] + loop["prefill.dispatch_n"]
    assert loop["overshoot_tokens"] == 0 and loop["drained_n"] == 0


def test_leaf_spans_partition_the_thread_through_drains_and_overshoot(net):
    """The partition holds where the pipeline is drained (a chunked
    prefill, an expired request) and where dispatches are dropped (EOS
    seen one dispatch late): no overlap, no gap, and the counters' `_s`
    add up to the thread's time between two readings."""
    since = time.perf_counter()
    rng = np.random.default_rng(3)
    full = generate(net, _prompts(1, 5, seed=13), 12, temperature=0.0)[0]
    def drag(phase, info):
        if phase == "pre_decode":
            time.sleep(0.01)

    eng = DecodeEngine(net, n_slots=2, max_len=64, prompt_buckets=(8,),
                       prefill_chunk=8, page_size=8, decode_chunk=4,
                       eos_token=int(full[5]), step_hooks=[drag])
    try:
        first = eng.stats()["loop"]
        t_first = time.perf_counter()
        # alone, so EOS is seen with the next dispatch in flight
        eng.submit(_prompts(1, 5, seed=13)[0], 12).result(timeout=120.0)
        # expires while it decodes: its release drains
        with pytest.raises(DeadlineExceededError):
            eng.submit(_prompts(1, 5, seed=14)[0], 50,
                       timeout=0.08).result(timeout=120.0)
        # a prompt prefilled in chunks beside a decoding request: every
        # chunk drains
        beside = eng.submit(_prompts(1, 5, seed=15)[0], 40)
        while not beside.tokens:
            time.sleep(0.002)
        eng.submit(rng.integers(0, VOCAB, 21).astype(np.int32),
                   9).result(timeout=120.0)
        beside.result(timeout=120.0)
        last = eng.stats()["loop"]
        t_last = time.perf_counter()
    finally:
        eng.shutdown()
    assert last["drained_n"] >= first["drained_n"] + 3
    assert last["overshoot_tokens"] > 0
    seconds = sum(last[k] - first[k] for k in last
                  if k.endswith("_s") and k != "sink_s")
    assert seconds == pytest.approx(t_last - t_first, abs=5e-3)
    spans = _scheduler_spans(eng, since)
    assert {s[0] for s in spans} <= set(obs.LEAF_PHASES)
    gaps = 0.0
    for a, b in zip(spans, spans[1:]):
        assert b[1] >= a[2], f"{a} overlaps {b}"
        gaps += b[1] - a[2]
    assert gaps <= 0.01 * (spans[-1][2] - spans[0][1])


def test_request_carries_one_decode_span_however_many_dispatches(net):
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,),
                       decode_chunk=1)
    try:
        req = eng.submit(_prompts(1, 5, seed=5)[0], 12)
        req.result(timeout=120.0)
    finally:
        eng.shutdown()
    decode = [s for s in req.trace.to_dict()["spans"]
              if s["name"] == "decode"]
    assert len(decode) == 1
    assert decode[0]["attrs"] == {"steps": 11, "dispatches": 11}
    assert decode[0]["t1"] > decode[0]["t0"]


def test_kill_switch_stops_the_timeline_and_not_the_loop_counters(
        net, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_NO_TRACING", "1")
    since = time.perf_counter()
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,))
    try:
        assert eng.submit(_prompts(1, 5, seed=6)[0], 6) \
            .result(timeout=120.0).shape == (6,)
    finally:
        eng.shutdown()
    assert _thread_spans(eng, since) == []
    st = eng.stats()
    assert st["loop"]["iterations"] >= 1
    assert st["loop"]["decode.dispatch_n"] >= 1
    assert st["loop"]["decode.wait_s"] > 0.0
    assert st["admitted"] == 1 and st["queue_wait_s"] > 0.0


def test_queue_wait_sums_are_the_requests_own_spans(net):
    eng = DecodeEngine(net, n_slots=1, max_len=32, prompt_buckets=(8,))
    try:
        reqs = [eng.submit(p, 4) for p in _prompts(4, 5, seed=8)]
        for r in reqs:
            r.result(timeout=120.0)
        st = eng.stats()
    finally:
        eng.shutdown()
    waits = [s["t1"] - s["t0"] for r in reqs
             for s in r.trace.to_dict()["spans"] if s["name"] == "queue-wait"]
    assert st["admitted"] == len(waits) == 4
    assert st["queue_wait_s"] == pytest.approx(sum(waits), abs=1e-6)
    # one slot: the later requests really waited for it
    assert max(waits) > 10 * min(waits)


def test_loop_and_front_keys_in_contract_and_exposition(net):
    assert {"loop", "queue_wait_s", "admitted"} \
        <= obs.DECODE_ENGINE_STATS_KEYS
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,))
    try:
        eng.submit(_prompts(1, 5, seed=9)[0], 3).result(timeout=120.0)
        loop = eng.metrics_snapshot()["components"]["decode_engine"]["loop"]
        assert set(loop) == {"iterations", "sink_s", "sink_n", "ahead_n",
                             "drained_n", "overshoot_tokens",
                             "kv_pages_walked", "kv_pages_table",
                             "kv_positions_attended",
                             "kv_positions_context",
                             "prefill_sorted_n", "spans_dropped"} \
            | {p + sfx for p in obs.LEAF_PHASES for sfx in ("_s", "_n")}
        text = eng.metrics_text()
    finally:
        eng.shutdown()
    assert "dl4j_stats_decode_engine_admitted 1" in text
    assert "dl4j_stats_decode_engine_queue_wait_s " in text
    assert "dl4j_stats_decode_engine_loop_iterations " in text
    assert "dl4j_stats_decode_engine_loop_decode_deliver_s " in text
    assert "dl4j_stats_decode_engine_loop_wait_work_n " in text
    # 3 tokens: the prefill's, then two decode steps at positions 5 and
    # 6 of a 32-position table whose page is the whole of it
    assert "dl4j_stats_decode_engine_loop_kv_pages_walked 2" in text
    assert "dl4j_stats_decode_engine_loop_kv_pages_table 2" in text
