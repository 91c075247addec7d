"""Set-up's own account (`serving/observability.py`): `DecodeEngine._build`
in the leaf phases of `BUILD_PHASES`, and JAX's compile pipeline in the
process's one `CompileAccount`: spans on the one `TIMELINE`, counters
under `stats()["build"]` and `stats()["compile"]`, the spans stopped by
`DL4J_TPU_NO_TRACING` and the counters not."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeplearning4j_tpu as dl4j
from deeplearning4j_tpu.models.transformer import gpt_configuration
from deeplearning4j_tpu.serving import DecodeEngine
from deeplearning4j_tpu.serving import observability as obs

VOCAB = 48
ENGINE = dict(n_slots=2, max_len=32, prompt_buckets=(8,))
STAGES = ("trace", "lower", "backend")


def _gpt_net(seed=12345, **kw):
    net = dl4j.MultiLayerNetwork(
        gpt_configuration(seed=seed, vocab_size=VOCAB, d_model=32,
                          n_heads=2, n_layers=2, max_length=64), **kw)
    net.init()
    return net


@pytest.fixture(scope="module")
def net():
    return _gpt_net()


@pytest.fixture
def build_walls(monkeypatch):
    """[(t0, t1)] of every `DecodeEngine._build`, on the timeline's
    clock, taken around the call."""
    walls, real = [], DecodeEngine._build

    def timed(self, net):
        t0 = time.perf_counter()
        try:
            return real(self, net)
        finally:
            walls.append((t0, time.perf_counter()))

    monkeypatch.setattr(DecodeEngine, "_build", timed)
    return walls


def _spans(names, since, tid=None):
    return sorted((s for s in obs.TIMELINE.snapshot(t0=since)
                   if s[0] in names and (tid is None or s[4] == tid)),
                  key=lambda s: s[1])


def _seconds(build: dict) -> float:
    return sum(build[p + "_s"] for p in obs.BUILD_PHASES)


# ------------------------------------------------------- `_build`'s phases


def test_build_spans_are_leaf_gap_free_and_add_up_to_the_build(build_walls):
    # f32 masters under bf16 compute: the build casts, in a program of
    # its own whose compile lies inside `build.weights`
    net = _gpt_net(compute_dtype=jnp.bfloat16)
    since = time.perf_counter()
    eng = DecodeEngine(net, **ENGINE)
    try:
        build = eng.stats()["build"]
    finally:
        eng.shutdown()
    (w0, w1), me = build_walls[0], threading.get_ident()
    spans = _spans(obs.BUILD_PHASES, since, tid=me)
    assert [s[0] for s in spans] == [
        "build.plan", "build.weights", "build.plan", "build.weight_hash",
        "build.plan", "build.state"]
    assert {s[3] for s in spans} == {1}  # the cause: the build's number
    for a, b in zip(spans, spans[1:]):
        assert a[2] == b[1]  # one instant ends a phase and starts the next
    # from `_build`'s first statement to its last
    assert 0.0 <= spans[0][1] - w0 < 2e-3 and 0.0 <= w1 - spans[-1][2] < 2e-3
    assert _seconds(build) == pytest.approx(w1 - w0, abs=4e-3)
    for phase in obs.BUILD_PHASES:
        mine = [s for s in spans if s[0] == phase]
        assert build[phase + "_n"] == len(mine)
        assert build[phase + "_s"] == pytest.approx(
            sum(s[2] - s[1] for s in mine), abs=1e-6)
    assert build["builds"] == 1
    # what nests under what: the cast program was compiled, on this
    # thread, while it was in `build.weights`
    weights = next(s for s in spans if s[0] == "build.weights")
    cast = [s for s in _spans(("compile.backend",), since, tid=me)
            if s[5]["fun"] == "cast_weights"]
    assert cast and all(weights[1] <= s[2] <= weights[2] for s in cast)


def test_weight_hash_bytes_are_the_leaves_nbytes(net):
    since = time.perf_counter()
    eng = DecodeEngine(net, **ENGINE)
    try:
        build = eng.stats()["build"]
    finally:
        eng.shutdown()
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(net._params))
    assert build["weight_hash_bytes"] == nbytes > 0
    (span,) = _spans(("build.weight_hash",), since,
                     tid=threading.get_ident())
    assert span[5] == {"bytes": nbytes}
    assert build["build.weight_hash_s"] <= _seconds(build)


def test_a_rebuilding_swap_is_a_second_build_and_the_swap_back_none(net):
    since = time.perf_counter()
    eng = DecodeEngine(net, **ENGINE)
    try:
        first = eng.stats()["build"]
        other = _gpt_net(seed=2)
        eng.drain_and_swap(other)  # rebuilds, on the scheduler's thread
        second = eng.stats()["build"]
        eng.drain_and_swap(eng._net)  # the net it serves: pools kept
        third = eng.stats()
        assert eng.submit(np.arange(5, dtype=np.int32), 4) \
            .result(timeout=120.0).shape == (4,)
    finally:
        eng.shutdown()
    assert (first["builds"], second["builds"]) == (1, 2)
    assert third["build"] == second and third["swaps"] == 2
    for phase in obs.BUILD_PHASES:
        assert second[phase + "_n"] == 2 * first[phase + "_n"]
        assert second[phase + "_s"] > first[phase + "_s"]
    assert second["weight_hash_bytes"] == 2 * first["weight_hash_bytes"]
    rebuilt = [s for s in _spans(obs.BUILD_PHASES, since) if s[3] == 2]
    assert len(rebuilt) == 6
    assert {s[4] for s in rebuilt} == {eng._thread.ident}
    # the rebuild lies inside one `housekeeping` span of that thread
    house = [s for s in _spans(("housekeeping",), since,
                               tid=eng._thread.ident)
             if s[1] <= rebuilt[0][1] and rebuilt[-1][2] <= s[2]]
    assert len(house) == 1


def test_a_build_that_refuses_still_closes_its_phase(net):
    since = time.perf_counter()
    with pytest.raises(ValueError):
        DecodeEngine(net, n_slots=2, max_len=1)
    spans = _spans(obs.BUILD_PHASES, since, tid=threading.get_ident())
    assert [s[0] for s in spans] == ["build.plan"]


# ------------------------------------------------- JAX's compile pipeline


def test_the_account_is_one_however_often_it_is_asked_for():
    account = obs.compile_account()
    assert obs.compile_account() is account
    x = jnp.arange(4)
    before = account.counters()

    def fresh_asked_twice(x):  # `lax`: nothing jitted is traced inside
        return jax.lax.add(jax.lax.mul(x, x), x)

    jax.jit(fresh_asked_twice)(x).block_until_ready()
    after = account.counters()
    for stage in STAGES:  # one listener: each event counted once
        assert after[stage + "_n"] == before[stage + "_n"] + 1


def test_a_fresh_jit_function_leaves_its_counts_and_spans():
    account, x = obs.compile_account(), jnp.ones((8, 8))
    before, since = account.counters(), time.perf_counter()

    def fresh_counted_once(x):
        return jax.lax.dot(jax.lax.sin(x), x)

    jax.jit(fresh_counted_once)(x).block_until_ready()
    after = account.counters()
    for stage in STAGES:
        assert after[stage + "_n"] - before[stage + "_n"] == 1
    mine = [s for s in _spans(obs.COMPILE_SPANS, since,
                              tid=threading.get_ident())
            if s[5]["fun"] == "fresh_counted_once"]
    assert [s[0] for s in mine] == list(obs.COMPILE_SPANS)
    for s, stage in zip(mine, STAGES):
        # the span's length is the event's seconds, its end the callback
        seconds = after[stage + "_s"] - before[stage + "_s"]
        assert s[2] - s[1] == pytest.approx(seconds, abs=1e-6) and seconds > 0
        assert since < s[2] <= time.perf_counter() and s[3] is None


def test_the_table_by_function_is_capped_and_keeps_what_cost_most():
    account = obs.CompileAccount(obs.Timeline())
    backend = "/jax/core/compile/backend_compile_duration"
    account.on_duration(backend, 5.0, fun_name="jit(decode_step)")
    for i in range(account.MAX_FUNS + 10):
        account.on_duration(backend, 0.001 * (i + 1), fun_name=f"tiny{i}")
    account.on_duration("/jax/core/compile/jaxpr_trace_duration", 2.0,
                        fun_name="decode_step")
    # cheaper than every row of a full table: no row of its own
    account.on_duration(backend, 0.0005, fun_name="cheapest")
    account.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                        0.25)
    account.on_event("/jax/compilation_cache/cache_hits")
    account.on_duration("/some/other/event", 9.0)
    c = account.counters()
    assert len(c["by_fun"]) == account.MAX_FUNS + 1  # and "other"
    assert c["by_fun"]["decode_step"] == {
        "trace_s": 2.0, "trace_n": 1, "lower_s": 0.0, "lower_n": 0,
        "backend_s": 5.0, "backend_n": 1}
    assert c["by_fun"]["other"]["backend_n"] == 12
    assert "cheapest" not in c["by_fun"] and "tiny73" in c["by_fun"]
    assert sum(r["backend_s"] for r in c["by_fun"].values()) \
        == pytest.approx(c["backend_s"])
    assert (c["backend_n"], c["trace_n"], c["lower_n"]) == (76, 1, 0)
    assert (c["cache_load_s"], c["cache_load_n"]) == (0.25, 1)
    assert (c["cache_hits"], c["cache_misses"]) == (1, 0)


# ------------------------------------------- the switch, and the contract


def test_kill_switch_stops_the_setup_spans_and_not_their_counters(
        net, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_NO_TRACING", "1")
    since = time.perf_counter()
    before = obs.compile_account().counters()
    eng = DecodeEngine(net, **ENGINE)
    try:
        def fresh_under_the_switch(x):
            return jax.lax.neg(x)

        jax.jit(fresh_under_the_switch)(jnp.arange(3)).block_until_ready()
        st = eng.stats()
    finally:
        eng.shutdown()
    assert _spans(obs.BUILD_PHASES + obs.COMPILE_SPANS, since) == []
    assert st["build"]["builds"] == 1 and _seconds(st["build"]) > 0.0
    assert st["build"]["weight_hash_bytes"] > 0
    for stage in STAGES:
        assert st["compile"][stage + "_n"] > before[stage + "_n"]


def test_build_and_compile_keys_in_contract_and_exposition(net):
    assert {"build", "compile"} <= obs.DECODE_ENGINE_STATS_KEYS
    eng = DecodeEngine(net, **ENGINE)
    try:
        comp = eng.metrics_snapshot()["components"]["decode_engine"]
        text = eng.metrics_text()
    finally:
        eng.shutdown()
    # the build's counters, and none of the scheduler's own among them
    assert set(comp["build"]) == {"builds", "weight_hash_bytes",
                                  "weight_hash_host_bytes"} \
        | {p + sfx for p in obs.BUILD_PHASES for sfx in ("_s", "_n")}
    assert not set(comp["loop"]) & set(comp["build"])
    assert set(comp["compile"]) == {"cache_hits", "cache_misses", "by_fun"} \
        | {k + sfx for k in STAGES + ("cache_load",) for sfx in ("_s", "_n")}
    # process-wide: what the engine shows is the one account's reading
    assert 0 < comp["compile"]["trace_n"] \
        <= obs.compile_account().counters()["trace_n"]
    assert "dl4j_stats_decode_engine_build_builds 1" in text
    assert "dl4j_stats_decode_engine_build_build_weight_hash_s " in text
    assert "dl4j_stats_decode_engine_build_weight_hash_bytes " in text
    assert "dl4j_stats_decode_engine_build_weight_hash_host_bytes " in text
    assert "dl4j_stats_decode_engine_compile_backend_s " in text
    assert "dl4j_stats_decode_engine_compile_trace_n " in text
