"""ISSUE 19: resumable token streaming. The emitted-token ring
(cursor-addressed replay), the `generate_stream`/`resume_stream` wire
legs, transparent client reconnect at the cursor, slow-consumer
backpressure typed the whole ladder down, mid-stream replica migration,
and the exactly-once guarantee that no tear/resume sequence can ever
lose, duplicate, or reorder a token — every streamed concatenation must
be bit-identical to the unary result."""
import socket
import threading
import time

import numpy as np
import pytest

import deeplearning4j_tpu.serving.observability as obs
from deeplearning4j_tpu.gateway import (
    GatewayClient,
    GatewayError,
    GatewayServer,
)
from deeplearning4j_tpu.models.transformer import gpt_configuration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import (
    ConnectionResetInjector,
    DecodeEngine,
    SlowConsumerInjector,
    StreamBackpressureError,
    StreamRegistry,
    TokenStream,
)
from deeplearning4j_tpu.serving.chaos import ChaosProxy
from deeplearning4j_tpu.serving.exactly_once import (
    DEDUPED_RPCS,
    JOURNALED_RPCS,
    SIDE_EFFECT_FREE_RPCS,
)

VOCAB = 48


def _gpt_net(seed: int = 12345, **kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_layers", 2)
    kw.setdefault("max_length", 64)
    net = MultiLayerNetwork(gpt_configuration(seed=seed, **kw))
    net.init()
    return net


def _engine(net, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prompt_buckets", (8,))
    return DecodeEngine(net, **kw)


def _prompt(n=5, seed=3):
    return np.random.default_rng(seed).integers(
        0, VOCAB, n).astype(np.int32)


def _slow(dt=0.02):
    """A pre-decode drag hook: one token per ~dt keeps a tiny-model
    sequence in flight long enough for tears, resumes, and scale-downs
    to land MID-stream instead of racing it to completion."""
    def hook(phase, info):
        if phase == "pre_decode":
            time.sleep(dt)
    return hook


def _await(cond, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out after {timeout:.0f}s waiting for {what}")


def _server(generation=None, **kw):
    gen = {"n_slots": 2, "max_len": 48, "prompt_buckets": (8,)}
    gen.update(generation or {})
    serving = {"generation": gen}
    serving.update(kw.pop("serving_extra", {}))
    server = GatewayServer(serving=serving, **kw)
    server.entry.create_model("g", gpt_configuration(
        seed=12345, vocab_size=VOCAB, d_model=32, n_heads=2, n_layers=2,
        max_length=64).to_json())
    return server.start()


# ------------------------------------------------------ ring (unit)


def test_ring_publish_read_roundtrip():
    st = TokenStream("r1", capacity=16)
    for c in range(1, 6):
        assert st.publish(c, 100 + c)
    toks, lps, cursor, body = st.read(0, timeout=0)
    assert toks == [101, 102, 103, 104, 105]
    assert lps is None and cursor == 5 and body is None
    # partial replay from a mid-stream cursor
    toks, _, cursor, _ = st.read(3, timeout=0)
    assert toks == [104, 105] and cursor == 5


def test_duplicate_and_gap_cursors_dropped():
    st = TokenStream("r2", capacity=16)
    assert st.publish(1, 7) and st.publish(2, 8)
    # duplicate (failover re-run replaying history): dropped + counted
    assert not st.publish(1, 7)
    assert not st.publish(2, 8)
    assert st.duplicate_tokens_dropped == 2
    # a gap would desync every downstream cursor: refused + counted
    assert not st.publish(5, 9)
    assert st.gap_tokens_dropped == 1
    toks, _, cursor, _ = st.read(0, timeout=0)
    assert toks == [7, 8] and cursor == 2


def test_ring_overflow_drops_oldest_and_types_backpressure():
    st = TokenStream("r3", capacity=4)
    for c in range(1, 11):
        assert st.publish(c, c)
    # cursor 0 fell out of the 4-token ring: typed verdict, not silence
    with pytest.raises(StreamBackpressureError, match="ring"):
        st.read(0, timeout=0)
    toks, _, cursor, _ = st.read(6, timeout=0)
    assert toks == [7, 8, 9, 10] and cursor == 10


def test_finish_idempotent_first_body_wins():
    st = TokenStream("r4", capacity=4)
    assert st.finish({"result": "a"})
    assert not st.finish({"result": "b"})
    toks, _, _, body = st.read(0, timeout=0)
    assert toks == [] and body == {"result": "a"}


def test_read_linger_batches_and_finish_aborts_it():
    st = TokenStream("r5", capacity=16)
    st.publish(1, 1)

    def feed():
        st.publish(2, 2)
        st.publish(3, 3)
        st.finish({"result": 1})

    t = threading.Thread(target=feed)
    t0 = time.monotonic()
    t.start()
    # a 10s linger must return the moment finish() lands, with every
    # token published during the linger folded into ONE frame
    toks, _, cursor, body = st.read(0, timeout=5.0, linger=10.0)
    t.join()
    assert time.monotonic() - t0 < 5.0
    assert toks == [1, 2, 3] and cursor == 3 and body == {"result": 1}


def test_registry_open_reuses_live_stream_for_failover_dedup():
    reg = StreamRegistry(ring=8)
    st = reg.open("req-1")
    for c in range(1, 4):
        st.publish(c, c)
    # a failover re-run re-opens the SAME ring: its replay of history
    # dedups against the cursor high-water mark
    again = reg.open("req-1")
    assert again is st
    assert not again.publish(1, 1) and not again.publish(2, 2)
    assert again.publish(4, 4)
    assert reg.stats()["duplicate_tokens_dropped"] == 2
    # a finished stream is replaced: re-execution is a new attempt
    reg.finish(st, {"result": 1})
    assert reg.open("req-1") is not st


def test_registry_attach_shed_ttl_and_stats_contract():
    reg = StreamRegistry(ring=8, ttl=0.05)
    assert set(reg.stats()) == obs.STREAMING_STATS_KEYS
    st = reg.open("req-1")
    assert reg.attach("req-1") is st
    reg.shed(st)
    reg.finish(st, {"result": 1})
    s = reg.stats()
    assert s["streams_opened"] == 1 and s["streams_finished"] == 1
    assert s["stream_resumes"] == 1
    assert s["stream_backpressure_sheds"] == 1
    assert s["streams_active"] == 0
    time.sleep(0.08)
    # aged out: the resuming consumer falls back to the parked outcome
    assert reg.attach("req-1") is None


def test_streaming_rpcs_classified_in_exactly_once_ledger():
    assert "generate_stream" in DEDUPED_RPCS
    assert "generate_stream" in JOURNALED_RPCS
    assert "resume_stream" in SIDE_EFFECT_FREE_RPCS


# ------------------------------------------------- engine emission


def test_engine_sink_parity_and_contiguous_cursors():
    net = _gpt_net()
    eng = _engine(net)
    p = _prompt()
    try:
        expected = eng.generate(p, 8, seed=7, timeout=120.0)
        seen = []
        out = eng.generate(p, 8, seed=7, timeout=120.0,
                           on_token=lambda c, t, logprob=None:
                           seen.append((c, t)) or True)
        np.testing.assert_array_equal(out, expected)
        assert [c for c, _ in seen] == list(range(1, 9))
        np.testing.assert_array_equal([t for _, t in seen], expected)
    finally:
        eng.shutdown()


def test_engine_logprobs_entries_and_unary_dict():
    net = _gpt_net()
    eng = _engine(net, logprobs=3)
    p = _prompt()
    try:
        plain = eng.generate(p, 6, seed=7, timeout=120.0)
        out = eng.generate(p, 6, seed=7, timeout=120.0, logprobs=2)
        assert isinstance(out, dict)
        np.testing.assert_array_equal(out["tokens"], plain)
        assert len(out["logprobs"]) == 6
        for tok, entry in zip(plain, out["logprobs"]):
            assert entry["token"] == int(tok)
            assert entry["logprob"] <= 0.0
            assert len(entry["top_tokens"]) == 2
            assert len(entry["top_logprobs"]) == 2
            # top-K sorted descending; the chosen (greedy) token IS the top
            assert entry["top_logprobs"][0] >= entry["top_logprobs"][1]
            assert entry["logprob"] == pytest.approx(
                entry["top_logprobs"][0])
    finally:
        eng.shutdown()


def test_engine_logprobs_validation_edges():
    net = _gpt_net()
    with pytest.raises(ValueError, match="logprobs"):
        _engine(net, logprobs=-1)
    with pytest.raises(ValueError, match="speculative"):
        _engine(net, logprobs=2, speculative={"draft": "self", "k": 2})
    eng = _engine(net, logprobs=2)
    try:
        with pytest.raises(ValueError, match="exceeds"):
            eng.generate(_prompt(), 4, logprobs=3)
    finally:
        eng.shutdown()


# ------------------------------------------------- gateway wire legs


def test_stream_concat_identical_to_unary_with_incremental_frames():
    # drag each decode step so tokens trickle: even on a one-core box
    # the pump must see multiple frames, not one all-at-once replay
    server = _server(generation={"decode_chunk": 1,
                                 "step_hooks": [_slow(0.01)]},
                     stream_coalesce=0.0)
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        unary = np.asarray(client.call("generate", name="g", prompt_ids=p,
                                       n_tokens=16, seed=11,
                                       _timeout=120.0))
        frames = 0
        with client.generate_stream("g", p, 16, seed=11,
                                    _timeout=120.0) as s:
            for frame in s:
                frames += 1
                assert frame["cursor"] == len(s.tokens)
        np.testing.assert_array_equal(np.asarray(s.tokens), unary)
        assert frames >= 2, "no incremental delivery — unary in disguise"
        assert s.trace_id is not None  # trace rides the terminal frame
        client.close()
    finally:
        server.stop()


def test_unary_logprobs_knob_and_streamed_logprob_frames():
    server = _server(generation={"logprobs": 2})
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        out = client.call("generate", name="g", prompt_ids=p,
                          n_tokens=8, seed=11, logprobs=2, _timeout=120.0)
        assert isinstance(out, dict) and len(out["logprobs"]) == 8
        with client.generate_stream("g", p, 8, seed=11, logprobs=2,
                                    _timeout=120.0) as s:
            for _ in s:
                pass
        np.testing.assert_array_equal(s.tokens, np.asarray(out["tokens"]))
        assert len(s.logprobs) == 8
        assert [e["token"] for e in s.logprobs] == list(s.tokens)
        client.close()
    finally:
        server.stop()


@pytest.mark.chaos
def test_torn_connection_resumes_at_cursor_bit_identical():
    server = _server()
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        unary = np.asarray(client.call("generate", name="g", prompt_ids=p,
                                       n_tokens=16, seed=11,
                                       _timeout=120.0))
        with client.generate_stream("g", p, 16, seed=11,
                                    _timeout=120.0) as s:
            next(s)
            # tear the wire mid-stream: the iterator must reconnect and
            # resume at its cursor without surfacing anything
            s._conn.sock.shutdown(socket.SHUT_RDWR)
            for _ in s:
                pass
        np.testing.assert_array_equal(np.asarray(s.tokens), unary)
        assert s.resumes >= 1
        assert server.entry.streams.stats()["stream_resumes"] >= 1
        client.close()
    finally:
        server.stop()


@pytest.mark.chaos
def test_midstream_reset_injector_rides_resume_ladder():
    """`ConnectionResetInjector` semantics through a ChaosProxy: the
    connection RSTs the moment frame bytes arrive; the iterator eats
    resets (with backoff) until heal, then resumes at the cursor."""
    server = _server()
    proxy = ChaosProxy("127.0.0.1", server.port)
    try:
        client = GatewayClient(port=proxy.port)
        p = _prompt()
        unary = np.asarray(client.call("generate", name="g", prompt_ids=p,
                                       n_tokens=16, seed=11,
                                       _timeout=120.0))
        inj = ConnectionResetInjector(proxy)
        inj.inject()
        healer = threading.Timer(0.6, inj.release)
        healer.start()
        with client.generate_stream("g", p, 16, seed=11, _timeout=120.0,
                                    max_resumes=32) as s:
            for _ in s:
                pass
        healer.cancel()
        np.testing.assert_array_equal(np.asarray(s.tokens), unary)
        assert s.resumes >= 1
        client.close()
    finally:
        proxy.close()
        server.stop()


@pytest.mark.chaos
def test_slow_consumer_never_blocks_other_streams():
    """The scheduler contract under a stalled reader: the decode slots
    keep running, a CONCURRENT stream completes while the slow one is
    mid-stall, and the stalled consumer still completes afterwards from
    buffered frames + ring replay (its tokens bit-identical)."""
    server = _server()
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        unary = np.asarray(client.call("generate", name="g", prompt_ids=p,
                                       n_tokens=12, seed=11,
                                       _timeout=120.0))
        inj = SlowConsumerInjector(client, "g", prompt=p, n_tokens=12,
                                   read_frames=1, stall=1.5, seed=11,
                                   _timeout=120.0)
        res = {}
        t = threading.Thread(target=lambda: res.update(out=inj.run()))
        t.start()
        # wait until the injector is inside its stall window
        deadline = time.monotonic() + 10.0
        while inj.counters()["stalls"] == 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        t0 = time.monotonic()
        with client.generate_stream("g", p, 12, seed=11,
                                    _timeout=120.0) as fast:
            for _ in fast:
                pass
        fast_dt = time.monotonic() - t0
        t.join(30.0)
        assert not t.is_alive()
        assert fast_dt < 1.5, "a stalled reader blocked another stream"
        np.testing.assert_array_equal(np.asarray(fast.tokens), unary)
        np.testing.assert_array_equal(np.asarray(res["out"]["tokens"]),
                                      unary)
        assert inj.counters()["completions"] == 1
        client.close()
    finally:
        server.stop()


@pytest.mark.chaos
def test_backpressure_shed_types_error_and_claim_recovers():
    """The ladder's last rung: a consumer that tears and resumes only
    AFTER its cursor fell out of a tiny ring gets the typed
    `StreamBackpressureError` on the wire — and with an exactly-once
    door, the iterator transparently claims the parked outcome, so the
    caller STILL sees the bit-identical sequence."""
    server = _server(generation={"decode_chunk": 1,
                                 "step_hooks": [_slow()]},
                     streaming={"ring": 4}, exactly_once=True)
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        unary = np.asarray(client.call("generate", name="g", prompt_ids=p,
                                       n_tokens=24, seed=11,
                                       _timeout=120.0))
        with client.generate_stream("g", p, 24, seed=11,
                                    _timeout=120.0) as s:
            first = next(s)
            assert first["cursor"] <= 4
            # tear, then stall PAST the end of the generation: the ring
            # (4 tokens) rolls far beyond our cursor
            s._conn.sock.shutdown(socket.SHUT_RDWR)
            rid = s.request_id
            _await(lambda: (st := server.entry.streams.get(rid))
                   is not None and st.finished_at is not None,
                   60.0, "the detached generation to finish")
            for _ in s:  # resume -> typed backpressure -> claim
                pass
        np.testing.assert_array_equal(np.asarray(s.tokens), unary)
        assert server.entry.streams.stats()[
            "stream_backpressure_sheds"] >= 1
        st = client.call("exactly_once_stats")
        assert st["cache"]["double_executions"] == 0
        client.close()
    finally:
        server.stop()


@pytest.mark.chaos
def test_backpressure_without_door_raises_typed_not_masked():
    server = _server(generation={"decode_chunk": 1,
                                 "step_hooks": [_slow()]},
                     streaming={"ring": 4})
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        with client.generate_stream("g", p, 24, seed=11,
                                    _timeout=120.0) as s:
            next(s)
            s._conn.sock.shutdown(socket.SHUT_RDWR)
            rid = s.request_id
            _await(lambda: (st := server.entry.streams.get(rid))
                   is not None and st.finished_at is not None,
                   60.0, "the detached generation to finish")
            with pytest.raises(GatewayError) as ei:
                for _ in s:
                    pass
        assert ei.value.error_type == "StreamBackpressureError"
        client.close()
    finally:
        server.stop()


@pytest.mark.chaos
def test_stream_survives_replica_migration_zero_lost_or_dup():
    """A replica scaled away mid-stream rides PR-17 live migration: the
    pool resumes the slot on the survivor publishing into the SAME
    ring, the stream never tears, tokens stay bit-identical, and the
    exactly-once ledger balances."""
    gen = {"n_slots": 2, "max_len": 48, "prompt_buckets": (8,),
           "decode_chunk": 1, "step_hooks": [_slow()]}
    server = _server(generation=gen, exactly_once=True,
                     serving_extra={"replicas": 2,
                                    "pool": {"probe_interval": 30.0}})
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        unary = np.asarray(client.call("generate", name="g", prompt_ids=p,
                                       n_tokens=30, seed=11,
                                       _timeout=120.0))
        pool = server.entry._servers["g"]

        def find_victim():
            for rid, r in pool.stats()["replicas"].items():
                if r.get("generation", {}).get("active_slots", 0) > 0:
                    return int(rid)
            return None

        victim_server = None
        with client.generate_stream("g", p, 30, seed=11,
                                    _timeout=120.0) as s:
            next(s)
            _await(lambda: find_victim() is not None, 30.0,
                   "an active decode slot to scale away from")
            victim_server = pool.remove_replica(find_victim(),
                                                drain_timeout=30.0)
            for _ in s:
                pass
        if victim_server is not None:
            victim_server.shutdown()
        np.testing.assert_array_equal(np.asarray(s.tokens), unary)
        assert pool.stats()["migrations"] == 1
        st = client.call("exactly_once_stats")
        assert st["cache"]["double_executions"] == 0
        client.close()
    finally:
        server.stop()


def test_dedup_replay_after_ring_ttl_serves_cached_terminal():
    """A reconnect AFTER the ring aged out re-enters through the
    exactly-once door: same request_id -> the cached terminal, not a
    re-execution (`double_executions == 0`)."""
    server = _server(exactly_once=True)
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        with client.generate_stream("g", p, 12, seed=11,
                                    _timeout=120.0) as s:
            for _ in s:
                pass
        # simulate the TTL sweep having retired the ring entirely
        with server.entry.streams._lock:
            server.entry.streams._streams.clear()
        with client.generate_stream("g", p, 12, seed=11, _timeout=120.0,
                                    _request_id=s.request_id) as s2:
            for _ in s2:
                pass
        np.testing.assert_array_equal(np.asarray(s2.tokens),
                                      np.asarray(s.tokens))
        st = client.call("exactly_once_stats")
        assert st["cache"]["double_executions"] == 0
        client.close()
    finally:
        server.stop()


# ------------------------------------------- observability contract


def test_streaming_stats_exposed_on_metrics_page_with_ttft():
    server = _server()
    try:
        client = GatewayClient(port=server.port)
        p = _prompt()
        with client.generate_stream("g", p, 8, seed=11,
                                    _timeout=120.0) as s:
            for _ in s:
                pass
        assert len(s.tokens) == 8
        text = client.call("metrics", name="g")
        for key in ("streams_opened", "streams_active",
                    "stream_resumes", "stream_backpressure_sheds"):
            assert f"streaming_{key}" in text
        assert "decode_engine_ttft_ms" in text
        client.close()
    finally:
        server.stop()
