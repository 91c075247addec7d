"""Continuous-batching generation serving
(`serving/decode_engine.DecodeEngine` + `ModelServer.generate`).

The load-bearing contract is PARITY: paged slotted decode must
reproduce whole-batch `models.transformer.generate` argmax-exactly at
f32 for the same prompts, REGARDLESS of admission order — slot AND
page reuse, mixed prompt lengths, mixed output lengths, fused decode
chunks, CHUNKED PREFILL of long prompts, and GQA/RoPE variants all
included. On top of that, the serving ladders: overload and
page-pool exhaustion shed typed, a deadline expiring in the queue
sheds before prefill, one expiring in flight frees its slot and
pages, and `reload()` during active decode finishes in-flight
requests on the OLD weights before swapping.

Everything here runs on CPU in the quick tier except the bench smoke
(`slow`): the fast tests keep shapes tiny so the jitted prefill/decode
pair compiles in seconds while still driving the scheduler loop for
real (the satellite ask: ≥3 decode steps through the jit path in
tier-1)."""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (
    generate,
    gpt_configuration,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import (
    DeadlineExceededError,
    DecodeEngine,
    ModelServer,
    OutOfPagesError,
    ServerClosedError,
    ServerOverloadedError,
)
from deeplearning4j_tpu.util.checkpoint_store import CheckpointStore
from deeplearning4j_tpu.util.serialization import write_model

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


def _prompts(n, t0, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, VOCAB, (n, t0)).astype(np.int32)


@pytest.fixture(scope="module")
def net():
    return _gpt_net()


def _engine(net, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prompt_buckets", (8,))
    return DecodeEngine(net, **kw)


# ------------------------------------------------------------- parity


def test_engine_matches_whole_batch_generate_two_admission_orders(net):
    """The acceptance pin: argmax-exact f32 parity with whole-batch
    generate under at least two different admission orders. 4 requests
    through 2 slots also forces slot reuse and in-flight admission —
    this IS the 3+-decode-steps-through-jit tier-1 scheduler drill."""
    prompts = _prompts(4, 5)
    expected = generate(net, prompts, 6, temperature=0.0)
    for order in ([0, 1, 2, 3], [3, 1, 0, 2]):
        eng = _engine(net)
        try:
            reqs = {i: eng.submit(prompts[i], 6) for i in order}
            for i in order:
                np.testing.assert_array_equal(
                    reqs[i].result(timeout=120.0), expected[i])
            assert eng.stats()["decode_steps"] >= 3
        finally:
            eng.shutdown()


def test_slot_reuse_after_retirement_keeps_parity(net):
    """Retire a slot, admit a NEW prompt into it, and require parity —
    the freed slot's stale KV must be fully masked/overwritten for its
    next occupant (the cache-hygiene failure mode of slotted reuse)."""
    prompts = _prompts(6, 5, seed=3)
    expected = generate(net, prompts, 5, temperature=0.0)
    eng = _engine(net)
    try:
        # wave 1 fills both slots, completes, THEN wave 2 reuses them
        first = [eng.submit(prompts[i], 5) for i in range(2)]
        for i, r in enumerate(first):
            np.testing.assert_array_equal(r.result(timeout=120.0),
                                          expected[i])
        second = [eng.submit(prompts[i], 5) for i in range(2, 6)]
        for i, r in enumerate(second, start=2):
            np.testing.assert_array_equal(r.result(timeout=120.0),
                                          expected[i])
    finally:
        eng.shutdown()


def test_mixed_prompt_and_output_lengths_parity(net):
    """Different prompt lengths ride different prefill buckets and
    different n_tokens retire at different iterations — every request
    must still match its own single-request whole-batch decode."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, VOCAB, t).astype(np.int32)
               for t in (3, 5, 9, 12)]
    n_toks = [7, 3, 10, 5]
    eng = _engine(net, n_slots=3, prompt_buckets=(4, 8, 16))
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, n_toks)]
        for p, n, r in zip(prompts, n_toks, reqs):
            exp = generate(net, p[None], n, temperature=0.0)[0]
            np.testing.assert_array_equal(r.result(timeout=120.0), exp)
        assert len(eng.stats()["prompt_buckets"]) == 3
    finally:
        eng.shutdown()


def test_gqa_rope_engine_parity():
    """The modern-decoder stack (GQA + RoPE + SwiGLU) through the
    slotted cache: grouped Hkv cache rows + per-slot rotary positions."""
    net = _gpt_net(n_heads=4, n_kv_heads=2, rope=True,
                   ffn_activation="swiglu")
    prompts = _prompts(3, 6, seed=11)
    expected = generate(net, prompts, 5, temperature=0.0)
    eng = _engine(net)
    try:
        got = np.stack([eng.generate(prompts[i], 5) for i in (2, 0, 1)])
        np.testing.assert_array_equal(got, expected[[2, 0, 1]])
    finally:
        eng.shutdown()


def test_sampled_generation_matches_generate_key_discipline(net):
    """Per-request seeds follow generate()'s exact kp/kd split, so even
    SAMPLED single-request generation reproduces generate() — stronger
    than the pinned greedy contract, and it proves per-slot PRNG streams
    are independent of admission order."""
    prompts = _prompts(2, 5, seed=5)
    eng = _engine(net)
    try:
        for i, seed in ((0, 3), (1, 9)):
            exp = generate(net, prompts[i:i + 1], 5, temperature=0.8,
                           seed=seed)[0]
            got = eng.generate(prompts[i], 5, temperature=0.8, seed=seed)
            np.testing.assert_array_equal(got, exp)
    finally:
        eng.shutdown()


def test_unchunked_engine_parity(net):
    """decode_chunk=1 (pure iteration-level scheduling) must agree with
    the default chunked path — fusion is an optimization, not a
    semantics change."""
    prompts = _prompts(3, 5, seed=13)
    expected = generate(net, prompts, 6, temperature=0.0)
    eng = _engine(net, decode_chunk=1)
    try:
        reqs = [eng.submit(prompts[i], 6) for i in range(3)]
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(r.result(timeout=120.0),
                                          expected[i])
    finally:
        eng.shutdown()


def test_eos_token_retires_slot_early(net):
    """An EOS hit ends the request (possibly mid-chunk: overshoot
    tokens are dropped), frees the slot, and the next request decodes
    correctly in it."""
    prompts = _prompts(2, 5, seed=17)
    full = generate(net, prompts[:1], 12, temperature=0.0)[0]
    eos = int(full[3])  # a token the greedy rollout actually emits
    eng = _engine(net, n_slots=1, eos_token=eos)
    try:
        got = eng.generate(prompts[0], 12)
        stop = int(np.argmax(full == eos))
        np.testing.assert_array_equal(got, full[:stop + 1])
        # slot freed: a follow-up request still decodes correctly
        exp2 = generate(net, prompts[1:2], 4, temperature=0.0)[0]
        got2 = eng.generate(prompts[1], 4)
        if eos in exp2:
            exp2 = exp2[:int(np.argmax(exp2 == eos)) + 1]
        np.testing.assert_array_equal(got2, exp2)
    finally:
        eng.shutdown()


def test_long_prompt_chunked_prefill_parity(net):
    """A prompt longer than every bucket AND the prefill chunk rides
    the CHUNKED prefill path (several chunk dispatches through the
    paged cache) and must still match whole-batch generate
    argmax-exactly — the chunked-prefill acceptance pin."""
    rng = np.random.default_rng(29)
    long_prompt = rng.integers(0, VOCAB, 20).astype(np.int32)
    eng = _engine(net, max_len=48, prompt_buckets=(4,), prefill_chunk=8,
                  page_size=8)
    try:
        exp = generate(net, long_prompt[None], 6, temperature=0.0)[0]
        np.testing.assert_array_equal(eng.generate(long_prompt, 6), exp)
        st = eng.stats()
        assert st["prefills"] == 1
        assert st["prefill_chunks"] >= 3, \
            "a 20-token prompt over 8-token chunks must take >= 3 chunks"
    finally:
        eng.shutdown()


def test_chunked_prefill_gqa_rope_parity():
    """Chunked prefill through the paged cache with the modern-decoder
    stack (GQA grouped cache pages + per-position rotary embeddings +
    SwiGLU) — the acceptance criteria's GQA-under-chunking pin."""
    net = _gpt_net(n_heads=4, n_kv_heads=2, rope=True,
                   ffn_activation="swiglu")
    rng = np.random.default_rng(31)
    prompt = rng.integers(0, VOCAB, 19).astype(np.int32)
    eng = _engine(net, max_len=48, prompt_buckets=(4,), prefill_chunk=8,
                  page_size=8)
    try:
        exp = generate(net, prompt[None], 5, temperature=0.0)[0]
        np.testing.assert_array_equal(eng.generate(prompt, 5), exp)
        assert eng.stats()["prefill_chunks"] >= 3
    finally:
        eng.shutdown()


def test_chunked_prefill_interleaves_with_decode(net):
    """THE head-of-line pin: while a long prompt chunk-prefills, an
    in-flight decode keeps stepping — decode dispatches land BETWEEN
    that prompt's chunk dispatches, and both requests stay
    argmax-exact."""
    events = []
    lock = threading.Lock()

    def recorder(phase, info):
        with lock:
            events.append((phase, dict(info)))

    rng = np.random.default_rng(37)
    short = rng.integers(0, VOCAB, 5).astype(np.int32)
    long_p = rng.integers(0, VOCAB, 24).astype(np.int32)
    eng = _engine(net, n_slots=2, max_len=64, prompt_buckets=(8,),
                  prefill_chunk=8, page_size=8, decode_chunk=1,
                  step_hooks=[recorder])
    try:
        short_req = eng.submit(short, 24)
        while not short_req.tokens:      # decoding, not queued
            assert short_req.error is None, short_req.error
            time.sleep(0.005)
        long_req = eng.submit(long_p, 4)
        exp_short = generate(net, short[None], 24, temperature=0.0)[0]
        exp_long = generate(net, long_p[None], 4, temperature=0.0)[0]
        np.testing.assert_array_equal(short_req.result(timeout=120.0),
                                      exp_short)
        np.testing.assert_array_equal(long_req.result(timeout=120.0),
                                      exp_long)
        with lock:
            chunk_idx = [i for i, (ph, info) in enumerate(events)
                         if ph == "pre_prefill" and "chunk_off" in info]
            decode_idx = [i for i, (ph, _) in enumerate(events)
                          if ph == "pre_decode"]
        assert len(chunk_idx) >= 3
        assert any(chunk_idx[0] < d < chunk_idx[-1] for d in decode_idx), \
            "no decode step landed between the long prompt's prefill " \
            "chunks — chunked prefill is not interleaving"
    finally:
        eng.shutdown()


def test_page_reuse_after_retirement_keeps_parity(net):
    """Pages freed by retired requests are REUSED by the next wave —
    the pool is sized so wave 2 cannot avoid wave 1's pages — and the
    new occupants' decode must be argmax-exact: no stale KV from the
    pages' previous owner may leak into a new request's attention."""
    prompts = _prompts(4, 9, seed=41)
    expected = generate(net, prompts, 6, temperature=0.0)
    # 9-token prompt -> 16-wide bucket = 2 pages of 8; span 9+6-1=14 -> 2
    # pages. pool_pages=4 == exactly wave 1's demand, so wave 2's pages
    # are all reallocations
    eng = _engine(net, n_slots=2, max_len=32, prompt_buckets=(16,),
                  page_size=8, pool_pages=4)
    try:
        first = [eng.submit(prompts[i], 6) for i in range(2)]
        for i, r in enumerate(first):
            np.testing.assert_array_equal(r.result(timeout=120.0),
                                          expected[i])
        assert eng.stats()["pages_in_use"] == 0
        assert eng.stats()["pages_in_use_peak"] == 4
        second = [eng.submit(prompts[i], 6) for i in range(2, 4)]
        for i, r in enumerate(second, start=2):
            np.testing.assert_array_equal(r.result(timeout=120.0),
                                          expected[i])
    finally:
        eng.shutdown()


def test_pool_exhaustion_sheds_typed_with_retry_after(net):
    """Memory-side admission control: when the queued page demand
    exceeds `max_queued_pages`, submit sheds with the typed
    `OutOfPagesError` (a ServerOverloadedError subclass, so every
    existing overload handler composes) carrying retry_after — and
    page-blocked waiters admit and complete once a retirement frees
    the pool."""
    gate = threading.Event()

    def slow_hook(phase, info):
        if phase == "pre_decode":
            gate.wait(0.05)

    # 4-page pool; each request (t0=5 -> bucket 8, span 5+24-1=28) needs
    # 4 pages: one in flight fills the pool
    eng = _engine(net, n_slots=2, max_len=32, prompt_buckets=(8,),
                  page_size=8, pool_pages=4, max_queued_pages=4,
                  step_hooks=[slow_hook])
    try:
        prompts = _prompts(3, 5, seed=43)
        expected = generate(net, prompts, 24, temperature=0.0)
        holder = eng.submit(prompts[0], 24)    # takes all 4 pages
        while not holder.tokens:
            assert holder.error is None, holder.error
            time.sleep(0.005)
        assert eng.stats()["pages_in_use"] == 4
        waiter = eng.submit(prompts[1], 24)    # queued page demand: 4
        with pytest.raises(OutOfPagesError) as ei:
            eng.submit(prompts[2], 24)         # demand 8 > 4 allowed
        assert ei.value.retry_after > 0
        assert isinstance(ei.value, ServerOverloadedError)
        st = eng.stats()
        assert st["shed_out_of_pages"] == 1
        assert st["queued_page_demand"] == 4
        gate.set()
        # the pool turns over: holder retires, waiter takes its pages
        np.testing.assert_array_equal(holder.result(timeout=120.0),
                                      expected[0])
        np.testing.assert_array_equal(waiter.result(timeout=120.0),
                                      expected[1])
    finally:
        eng.shutdown()
    # a request that can NEVER fit the pool is a config error, not a shed
    eng2 = _engine(net, n_slots=1, max_len=32, prompt_buckets=(8,),
                   page_size=8, pool_pages=2)
    try:
        with pytest.raises(ValueError, match="pool"):
            eng2.submit(_prompts(1, 5)[0], 24)  # needs 4 > 2 pages
    finally:
        eng2.shutdown()


# -------------------------------------------- admission / deadlines


def test_overload_sheds_typed_with_retry_after(net):
    """The bounded queue sheds at the door with retry_after — the same
    admission-control contract predict has."""
    gate = threading.Event()

    def slow_hook(phase, info):
        if phase == "pre_decode":
            gate.wait(0.05)

    eng = _engine(net, n_slots=1, max_queue=2, step_hooks=[slow_hook])
    try:
        prompts = _prompts(1, 5)
        keep = [eng.submit(prompts[0], 20)]       # occupies the slot
        while not keep[0].tokens:                 # wait until admitted
            assert keep[0].error is None, keep[0].error
            time.sleep(0.005)
        keep += [eng.submit(prompts[0], 4) for _ in range(2)]  # fills queue
        with pytest.raises(ServerOverloadedError) as ei:
            eng.submit(prompts[0], 4)
        assert ei.value.retry_after > 0
        assert eng.stats()["shed_overload"] == 1
        gate.set()
        for r in keep:
            r.result(timeout=120.0)  # every admitted request completes
    finally:
        eng.shutdown()


def test_deadline_expired_in_queue_sheds_before_prefill(net):
    """A sheddable request leaves the queue BEFORE prefill: no device
    work for a request nobody is waiting for."""
    def drag(phase, info):  # keep the slot pinned past the doomed
        if phase == "pre_decode":  # request's deadline
            time.sleep(0.005)

    eng = _engine(net, n_slots=1, step_hooks=[drag])
    try:
        prompts = _prompts(2, 5)
        long_req = eng.submit(prompts[0], 24)   # pins the only slot
        doomed = eng.submit(prompts[1], 4, timeout=0.01)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=60.0)
        long_req.result(timeout=120.0)
        st = eng.stats()
        assert st["shed_deadline"] == 1
        assert st["prefills"] == 1, \
            "an expired queued request must never reach prefill"
    finally:
        eng.shutdown()


def test_deadline_expiry_in_flight_frees_slot(net):
    """An expired IN-FLIGHT request fails typed, frees its slot, and
    the next request admits into it and completes with parity."""
    slow = threading.Event()

    def drag(phase, info):
        if phase == "pre_decode" and not slow.is_set():
            time.sleep(0.03)

    eng = _engine(net, n_slots=1, step_hooks=[drag], decode_chunk=1)
    try:
        prompts = _prompts(2, 5)
        doomed = eng.submit(prompts[0], 25, timeout=0.08)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=60.0)
        assert 0 < len(doomed.tokens) < 25, \
            "expiry should interrupt an in-flight generation"
        slow.set()
        exp = generate(net, prompts[1:2], 4, temperature=0.0)[0]
        np.testing.assert_array_equal(eng.generate(prompts[1], 4), exp)
        assert eng.stats()["shed_deadline"] == 1
    finally:
        eng.shutdown()


def test_shutdown_rejects_new_and_fails_queued(net):
    eng = _engine(net)
    eng.shutdown()
    with pytest.raises(ServerClosedError):
        eng.submit(_prompts(1, 5)[0], 4)


# ------------------------------------------------- one dispatch ahead
#
# The scheduler issues decode dispatch n+1 (and the one-shot prefills
# admitted before it) BEFORE it waits for, reads back and delivers
# dispatch n. The tokens a request gets must not know: they are
# `generate`'s, bit for bit, whatever ends the request.


def _ahead(eng) -> dict:
    return eng.stats()["loop"]


def _await(cond, what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _drag(dt=0.01):
    """Slow every decode issue down, so a request stays in flight long
    enough for the test's next move to land mid-decode."""
    def hook(phase, info):
        if phase == "pre_decode":
            time.sleep(dt)
    return hook


@pytest.mark.parametrize("case", ["greedy", "sampled", "one-token",
                                  "two-tokens", "eos-inside-a-chunk",
                                  "eos-as-first-token"])
def test_dispatch_ahead_tokens_equal_generate(net, case):
    prompts = _prompts(3, 5, seed=29)
    n = {"one-token": 1, "two-tokens": 2}.get(case, 14)
    temp, seed = (0.8, 7) if case == "sampled" else (0.0, 0)
    want = [generate(net, prompts[i:i + 1], n, temperature=temp,
                     seed=seed)[0] for i in range(3)]
    eos = None
    if case.startswith("eos"):
        # a token the first rollout emits inside its second fused chunk,
        # or as its very first token
        eos = int(want[0][6 if case == "eos-inside-a-chunk" else 0])
        want = [w[:int(np.argmax(w == eos)) + 1] if eos in w else w
                for w in want]
    eng = _engine(net, n_slots=2, eos_token=eos)
    try:
        reqs = [eng.submit(prompts[i], n, temperature=temp, seed=seed)
                for i in range(3)]
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(r.result(timeout=120.0), w)
        _await(lambda: eng.stats()["active_slots"] == 0, "the last collect")
        loop, st = _ahead(eng), eng.stats()
        assert st["pages_in_use"] == 0 and st["served"] == 3
        assert loop["ahead_n"] > 0
        # a count is known when a dispatch is issued: only what the
        # tokens themselves say (EOS) costs computed-and-dropped tokens
        if eos is None:
            assert loop["overshoot_tokens"] == 0
        else:
            assert loop["overshoot_tokens"] > 0
        assert st["tokens_generated"] == sum(len(w) for w in want)
    finally:
        eng.shutdown()


def test_next_dispatch_is_issued_before_the_last_is_collected(net):
    """`pre_decode(n+1)` fires before `post_decode(n)`, each `post_*`
    gets the `info` object its `pre_*` got, and dispatches are collected
    strictly in issue order."""
    events = []
    eng = _engine(net, n_slots=2, decode_chunk=1,
                  step_hooks=[lambda ph, info: events.append((ph, info))])
    try:
        reqs = [eng.submit(p, 10) for p in _prompts(2, 5, seed=31)]
        for r in reqs:
            assert r.result(timeout=120.0).shape == (10,)
        _await(lambda: eng.stats()["active_slots"] == 0, "the last collect")
        loop = _ahead(eng)
    finally:
        eng.shutdown()
    pre = [i for i, (ph, _) in enumerate(events) if ph == "pre_decode"]
    post = [i for i, (ph, _) in enumerate(events) if ph == "post_decode"]
    assert len(pre) == len(post) >= 9
    # the same info object, in the same order
    assert [id(events[i][1]) for i in pre] == \
        [id(events[i][1]) for i in post]
    assert [events[i][1]["step"] for i in pre] == list(range(len(pre)))
    # dispatch n is collected after dispatch n+1 was issued, and before
    # dispatch n+2 is
    for k in range(len(pre) - 1):
        assert pre[k + 1] < post[k]
        if k + 2 < len(pre):
            assert post[k] < pre[k + 2]
    for kind in ("prefill", "decode"):
        issued = [id(i) for ph, i in events if ph == "pre_" + kind]
        collected = [id(i) for ph, i in events if ph == "post_" + kind]
        assert issued == collected
    n_dispatches = loop["decode.dispatch_n"] + loop["prefill.dispatch_n"]
    assert 0 < loop["ahead_n"] <= n_dispatches
    assert loop["overshoot_tokens"] == 0 and loop["drained_n"] == 0


@pytest.mark.parametrize("decode_chunk", [1, 4])
def test_page_walk_counters_equal_what_the_lengths_imply(net,
                                                         decode_chunk):
    """`kv_pages_walked` / `kv_pages_table` are reckoned on the host at
    each decode issue, with dispatches still in flight: after a run
    without EOS they equal the live pages of every decode step of every
    request (positions `t0 .. t0 + n - 2`: the first token is the
    prefill's) and those steps x the table's width, single steps or
    fused chunks alike."""
    page, max_len = 4, 32
    shapes = [(5, 12), (3, 9), (7, 14)]           # (prompt length, n)
    eng = _engine(net, n_slots=2, page_size=page, max_len=max_len,
                  decode_chunk=decode_chunk)
    try:
        reqs = [eng.submit(_prompts(1, t0, seed=53 + i)[0], n)
                for i, (t0, n) in enumerate(shapes)]
        for r, (_, n) in zip(reqs, shapes):
            assert r.result(timeout=120.0).shape == (n,)
        _await(lambda: eng.stats()["active_slots"] == 0, "the last collect")
        loop = _ahead(eng)
    finally:
        eng.shutdown()
    assert loop["overshoot_tokens"] == 0
    steps = sum(n - 1 for _, n in shapes)
    assert loop["kv_pages_table"] == steps * (max_len // page)
    assert loop["kv_pages_walked"] == sum(
        (t0 + k) // page + 1 for t0, n in shapes for k in range(n - 1))
    assert loop["kv_pages_walked"] < loop["kv_pages_table"]


def test_a_poisoned_step_fails_one_request_one_dispatch_late(net):
    """A non-finite step is seen at its collect, when the next dispatch
    already has the slot active: the request fails typed with the tokens
    it had, that one dispatch is dropped, its neighbour never notices,
    and the slot's pages come back."""
    prompts = _prompts(3, 5, seed=37)
    want = generate(net, prompts, 12, temperature=0.0)
    eng = _engine(net, n_slots=2, decode_chunk=1)
    calls = {"n": 0}
    step = eng._decode_step

    def poisoned(*args):
        out = step(*args)
        calls["n"] += 1
        if calls["n"] == 4:  # slot 0's fourth decode step
            out = out[:4] + (out[4].at[0].set(False),) + out[5:]
        return out

    eng._decode_step = poisoned
    try:
        bad, good = eng.submit(prompts[0], 12), eng.submit(prompts[1], 12)
        with pytest.raises(Exception, match="non-finite") as ei:
            bad.result(timeout=120.0)
        assert type(ei.value).__name__ == "InferenceFailedError"
        np.testing.assert_array_equal(bad.tokens, want[0][:4])
        np.testing.assert_array_equal(good.result(timeout=120.0), want[1])
        np.testing.assert_array_equal(eng.generate(prompts[2], 12),
                                      want[2])
        _await(lambda: eng.stats()["active_slots"] == 0, "the last collect")
        st = eng.stats()
        assert st["failures"] == 1 and st["pages_in_use"] == 0
        assert st["loop"]["overshoot_tokens"] == 1
    finally:
        eng.shutdown()


def test_pages_are_released_after_the_last_uncollected_dispatch(net):
    """Rule 3: while a dispatch that had a slot active is uncollected,
    the slot stays taken and none of its request's pages is on the free
    list, so nothing can be admitted onto them — checked from inside the
    scheduler at every hook, on traffic where EOS retires requests with
    a dispatch in flight and a queue waiting for the pages."""
    prompts = _prompts(6, 9, seed=43)
    full = generate(net, prompts, 12, temperature=0.0)
    eos = int(full[0][5])
    broken = []

    def check(phase, info):
        free = set(eng._free_pages)
        for rec in eng._inflight:
            for s, r in rec.live:
                if eng._slots[s] is not r or r.pages is None \
                        or free & set(r.pages):
                    broken.append((phase, rec.program, s))
        if phase == "pre_prefill" and any(
                s == info["slot"] for rec in eng._inflight
                for s, _ in rec.live):
            broken.append((phase, "admitted under a dispatch", info))

    # 9-token prompts at a 16-wide bucket, span 9+12-1=20: 3 pages each,
    # and a pool of 6: the third request needs pages the first two hold
    eng = _engine(net, n_slots=2, max_len=32, prompt_buckets=(16,),
                  page_size=8, pool_pages=6, eos_token=eos,
                  step_hooks=[check])
    try:
        reqs = [eng.submit(p, 12) for p in prompts]
        for r, w in zip(reqs, full):
            w = w[:int(np.argmax(w == eos)) + 1] if eos in w else w
            np.testing.assert_array_equal(r.result(timeout=120.0), w)
        _await(lambda: eng.stats()["active_slots"] == 0, "the last collect")
        assert eng.stats()["pages_in_use"] == 0
        assert _ahead(eng)["overshoot_tokens"] > 0
    finally:
        eng.shutdown()
    assert not broken, broken[:5]


def test_preemption_with_a_dispatch_in_flight(net):
    """Preemption folds the victim's tokens into its prompt: it drains
    first, so the fold holds every token the chip has computed."""
    prompts = _prompts(2, 5, seed=47)
    want = generate(net, prompts, 20, temperature=0.0)
    eng = _engine(net, n_slots=1, prefix_cache=True,
                  qos={"preempt": True}, step_hooks=[_drag()])
    try:
        victim = eng.submit(prompts[0], 20, tenant="bulk",
                            priority="batch")
        _await(lambda: len(victim.tokens) >= 3, "the victim to decode")
        urgent = eng.submit(prompts[1], 6, tenant="live")
        np.testing.assert_array_equal(urgent.result(timeout=120.0),
                                      want[1][:6])
        np.testing.assert_array_equal(victim.result(timeout=120.0), want[0])
        st = eng.stats()
        assert st["preemptions"] == 1 and st["loop"]["drained_n"] >= 1
    finally:
        eng.shutdown()


def test_migrate_slots_with_a_dispatch_in_flight(net):
    """The exported registers (position, last token, key) must match the
    tokens the redirect carries: the migration pass drains first, and
    the spliced sequence is `generate`'s."""
    prompt = _prompts(1, 5, seed=53)[0]
    want = generate(net, prompt[None], 20, temperature=0.7, seed=3)[0]
    src = _engine(net, step_hooks=[_drag()])
    dst = _engine(net)
    try:
        req = src.submit(prompt, 20, temperature=0.7, seed=3)
        _await(lambda: len(req.tokens) >= 3, "tokens before the migration")
        assert src.migrate_slots(wait=10.0) == 1
        with pytest.raises(Exception) as ei:
            req.result(timeout=60.0)
        redirect = ei.value
        assert type(redirect).__name__ == "SlotMigratedError"
        tail = dst.resume_generate(src.fetch_handoff(redirect.handoff_id),
                                   timeout=120.0)
        np.testing.assert_array_equal(
            np.concatenate([redirect.tokens, np.asarray(tail).reshape(-1)]),
            want)
        assert src.commit_handoff(redirect.handoff_id)
        assert src.stats()["pages_in_use"] == 0
        assert _ahead(src)["drained_n"] >= 1
    finally:
        src.shutdown()
        dst.shutdown()


def test_drain_and_swap_with_a_dispatch_in_flight():
    old_net, new_net = _gpt_net(seed=1), _gpt_net(seed=2)
    prompts = _prompts(2, 5, seed=59)
    eng = _engine(old_net, step_hooks=[_drag()])
    try:
        req = eng.submit(prompts[0], 18)
        _await(lambda: len(req.tokens) >= 2, "the request to decode")
        eng.drain_and_swap(new_net, timeout=120.0)
        np.testing.assert_array_equal(
            req.result(timeout=120.0),
            generate(old_net, prompts[:1], 18, temperature=0.0)[0])
        np.testing.assert_array_equal(
            eng.generate(prompts[1], 9),
            generate(new_net, prompts[1:], 9, temperature=0.0)[0])
        assert eng.stats()["swaps"] == 1
    finally:
        eng.shutdown()


@pytest.mark.parametrize("drain_timeout", [30.0, 0.0],
                         ids=["drained", "killed"])
def test_shutdown_with_a_dispatch_in_flight(net, drain_timeout):
    """A drained shutdown finishes what is in flight; a kill delivers
    what the chip was handed, then fails the request typed — and either
    way the tokens are a prefix of `generate`'s, nothing is left in
    flight and every page is back."""
    prompt = _prompts(1, 5, seed=61)[0]
    want = generate(net, prompt[None], 24, temperature=0.0)[0]
    eng = _engine(net, step_hooks=[_drag()])
    req = eng.submit(prompt, 24)
    _await(lambda: len(req.tokens) >= 2, "the request to decode")
    clean = eng.shutdown(drain_timeout=drain_timeout)
    if drain_timeout:
        assert clean
        np.testing.assert_array_equal(req.result(timeout=10.0), want)
    else:
        assert not clean
        with pytest.raises(ServerClosedError):
            req.result(timeout=10.0)
        assert 2 <= len(req.tokens) < 24
        np.testing.assert_array_equal(req.tokens, want[:len(req.tokens)])
    assert not eng._thread.is_alive() and not eng._inflight
    assert eng.stats()["pages_in_use"] == 0


class _Lost:
    """A device handle whose read-back raises: how a dispatch that
    failed on the chip looks from the host."""

    def __array__(self, *a, **kw):
        raise RuntimeError("injected device failure")


@pytest.mark.parametrize("where", ["issue-hook", "issue-call", "collect"])
def test_a_failing_dispatch_with_one_in_flight(net, where):
    """A failure at the issue of dispatch n+1 (a hook, the call itself)
    or at the collect of dispatch n (where a device error surfaces):
    every request the failed dispatch had fails typed, what was
    delivered before is `generate`'s, the state is rebuilt where the
    pools may be lost, and the engine serves on."""
    prompts = _prompts(3, 5, seed=67)
    want = generate(net, prompts, 12, temperature=0.0)
    armed = {"at": 4, "n": 0}

    def hook(phase, info):
        if phase == "pre_decode" and where == "issue-hook":
            armed["n"] += 1
            if armed["n"] == armed["at"]:
                raise RuntimeError("injected hook failure")

    eng = _engine(net, n_slots=2, decode_chunk=1, step_hooks=[hook])
    step = eng._decode_step

    def failing(*args):
        armed["n"] += 1
        if armed["n"] == armed["at"] and where == "issue-call":
            raise RuntimeError("injected dispatch failure")
        out = step(*args)
        if armed["n"] == armed["at"]:
            out = out[:4] + (_Lost(),) + out[5:]
        return out

    if where != "issue-hook":
        eng._decode_step = failing
    try:
        reqs = [eng.submit(prompts[i], 12) for i in range(2)]
        for i, r in enumerate(reqs):
            with pytest.raises(Exception, match="decode step failed") as ei:
                r.result(timeout=120.0)
            assert type(ei.value).__name__ == "InferenceFailedError"
            assert 1 <= len(r.tokens) < 12
            np.testing.assert_array_equal(r.tokens, want[i][:len(r.tokens)])
        np.testing.assert_array_equal(eng.generate(prompts[2], 12), want[2])
        _await(lambda: eng.stats()["active_slots"] == 0, "the last collect")
        st = eng.stats()
        assert st["failures"] == 2 and st["pages_in_use"] == 0
        assert st["served"] == 1 and not eng._inflight
    finally:
        eng.shutdown()


# ------------------------------------------- ModelServer integration


def test_model_server_generate_and_stats(net):
    srv = ModelServer(net, generation={"n_slots": 2, "max_len": 32,
                                       "prompt_buckets": (8,)})
    try:
        prompts = _prompts(3, 5)
        expected = generate(net, prompts, 4, temperature=0.0)
        got = np.stack([srv.generate(prompts[i], 4) for i in range(3)])
        np.testing.assert_array_equal(got, expected)
        st = srv.stats()
        assert "slot_occupancy_pct" in st
        assert st["generation"]["served"] == 3
        # predict-side starvation observability rides the same stats()
        srv.predict(prompts)
        st = srv.stats()
        assert 0 < st["batch_fill_pct"] <= 100.0
    finally:
        srv.shutdown()


def test_model_server_without_generation_config_raises(net):
    srv = ModelServer(net)
    try:
        with pytest.raises(RuntimeError, match="generation"):
            srv.generate(_prompts(1, 5)[0], 4)
    finally:
        srv.shutdown()


@pytest.mark.chaos
def test_reload_under_active_decode(tmp_path):
    """reload() during active decode: the in-flight generation FINISHES
    on the old weights (its KV cache was computed with them), the swap
    lands, and the next generation uses the new weights — both pinned
    against whole-batch generate on the respective nets."""
    old_net = _gpt_net(seed=1)
    new_net = _gpt_net(seed=2)
    store = CheckpointStore(tmp_path)
    store.save(1, lambda tmp: write_model(new_net, tmp, atomic=False))

    hold = threading.Event()

    def drag(phase, info):
        if phase == "pre_decode" and not hold.is_set():
            time.sleep(0.02)

    prompts = _prompts(2, 5, seed=23)
    srv = ModelServer(old_net, auto_canary=False,
                      generation={"n_slots": 2, "max_len": 64,
                                  "prompt_buckets": (8,),
                                  "step_hooks": [drag],
                                  "decode_chunk": 1})
    try:
        engine = srv._ensure_engine()
        in_flight = engine.submit(prompts[0], 30)
        while not in_flight.tokens:  # ensure it is decoding, not queued
            assert in_flight.error is None, in_flight.error
            time.sleep(0.005)
        version = srv.reload(store)  # drains slots, swaps, keeps serving
        hold.set()
        assert version == 1
        old_exp = generate(old_net, prompts[:1], 30, temperature=0.0)[0]
        np.testing.assert_array_equal(in_flight.result(timeout=120.0),
                                      old_exp)
        new_exp = generate(new_net, prompts[1:2], 5, temperature=0.0)[0]
        np.testing.assert_array_equal(srv.generate(prompts[1], 5),
                                      new_exp)
        assert srv.stats()["generation"]["swaps"] == 1
    finally:
        srv.shutdown()


def test_gateway_generate_round_trip(net):
    """generate over the wire: the RPC rides the serving tier and
    returns the same tokens the in-process engine produces."""
    from deeplearning4j_tpu.gateway import GatewayClient, GatewayServer

    gw = GatewayServer(serving={"generation": {"n_slots": 2,
                                               "max_len": 32,
                                               "prompt_buckets": (8,)}})
    gw.start()
    cl = None
    try:
        import json

        cl = GatewayClient(port=gw.port)
        conf = gpt_configuration(vocab_size=VOCAB, d_model=32, n_heads=2,
                                 n_layers=2, max_length=64)
        cl.call("create_model", name="g", config=json.loads(conf.to_json()))
        prompts = _prompts(1, 5)
        toks = cl.call("generate", name="g", prompt_ids=prompts[0],
                       n_tokens=4)
        assert toks.shape == (4,) and toks.dtype == np.int32
        stats = cl.call("server_stats", name="g")
        assert stats["generation"]["served"] == 1
        assert "generate" in GatewayClient._IDEMPOTENT
    finally:
        if cl is not None:
            cl.close()
        gw.stop()
