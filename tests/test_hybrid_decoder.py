"""The composed `DecoderBlock` (Mamba-2 or position-free grouped-query
attention, then top-k dropless routed experts + a shared expert, under
RMSNorm) against the plain reference the benchmark keeps
(`perfbench/families/granite_hybrid_reference.py`: a `lax.scan` over
time, naive attention, a masked loop over the experts held) and against
the sequential recurrence, on seeded weights at a small size: the
network's forward and gradients, and the decode engine's prefill and
decode through recurrent state and paged K/V side by side."""
import copy
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.decoder_block import (
    DecoderBlock,
    Mamba2Mixer,
    MoEFeedForward,
    sub,
)
from deeplearning4j_tpu.ops import ssm
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
from perfbench.families import granite_hybrid as fam
from perfbench.families import granite_hybrid_reference as ref

REPO = Path(__file__).resolve().parents[1]
V = 97


def _config(**over) -> dict:
    """The benchmark's configuration file, cut to a toy: d 64, pattern
    m a m, 8 experts top-2 with 4 held."""
    cfg = json.loads((REPO / "perfbench/configs/granite-4.0-h-small.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=32, shared_intermediate_size=48,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               mamba_chunk_size=8, num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               num_local_experts=4, num_experts_per_tok=2, vocab_size=V,
               attention_multiplier=0.1)
    cfg["deployment"] = dict(num_local_experts_published=8,
                             experts_held_first=0)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def model():
    """(sizes, reference constants, bf16-valued weights, the program's
    f32 net holding them)."""
    cfg = _config()
    sz, c = fam.sizes(cfg), ref.consts_from_config(cfg)
    w = fam.make_weights(5, sz)
    net = fam.build_net(sz, training=True, dtype=jnp.float32)
    fam.install(net, jax.tree.map(lambda a: a.astype(jnp.float32), w))
    return sz, c, w, net


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


def _ref_logp(model, ids, rows):
    sz, c, w, _ = model
    lg = ref.logits_at(w, jnp.asarray(ids)[None], jnp.asarray(rows), c=c,
                       n_heads=sz["H"], eps=sz["eps"])
    return np.asarray(jax.nn.log_softmax(lg, axis=-1))


# ------------------------------------------------------------ the network
def test_forward_logits_equal_the_reference(model):
    ids = _ids(21)
    out = model[3].output(ids[None])                  # softmax over logits
    want = _ref_logp(model, ids, np.arange(21))
    np.testing.assert_allclose(np.log(out[0]), want, atol=2e-5)


@pytest.mark.parametrize("T,chunk", [(37, 8), (5, 8), (64, 16)])
def test_chunked_scan_equals_the_sequential_recurrence(T, chunk):
    k = jax.random.split(jax.random.PRNGKey(T), 7)
    B, H, P, N = 2, 4, 8, 16
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm, Cm = (jax.random.normal(k[i], (B, T, N)) for i in (3, 4))
    D, h0 = jax.random.normal(k[5], (H,)), jax.random.normal(k[6],
                                                             (B, H, P, N))
    with jax.default_matmul_precision("highest"):
        y1, h1 = ssm.ssm_sequential(x, dt, A, Bm, Cm, D, h0)
        y2, h2 = ssm.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk, h0=h0)
    np.testing.assert_allclose(y2, y1, atol=5e-5)
    np.testing.assert_allclose(h2, h1, atol=5e-5)


def test_pad_positions_and_a_split_leave_the_state_of_the_last_real_one():
    """A stretch padded past `n_valid`, and the same stretch cut in two
    with state and tail carried, end in the state and tail of the
    unpadded whole."""
    mixer = Mamba2Mixer(n_heads=4, head_dim=8, d_state=16, chunk=8)
    p = mixer.init_params(jax.random.PRNGKey(0), 32, jnp.float32,
                          lambda k, s, fi, fo: 0.1 * jax.random.normal(k, s))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 32))
    y, h, tail = mixer.scan(p, x[:, :19])
    yp, hp, tailp = mixer.scan(p, x, n_valid=jnp.asarray(19))
    np.testing.assert_allclose(yp[:, :19], y, atol=1e-5)
    np.testing.assert_allclose(hp, h, atol=1e-6)
    np.testing.assert_array_equal(tailp, tail)
    _, h1, t1 = mixer.scan(p, x[:, :8])
    y2, h2, t2 = mixer.scan(p, x[:, 8:24], h1, t1, n_valid=jnp.asarray(11))
    np.testing.assert_allclose(y2[:, :11], y[:, 8:], atol=1e-5)
    np.testing.assert_allclose(h2, h, atol=1e-5)
    np.testing.assert_array_equal(t2, tail)


def test_gradients_through_fit_loss_equal_the_reference(model):
    sz, c, w, net = model
    ids = np.stack([_ids(13, 1), _ids(13, 2)])
    feats, labels = ids[:, :-1], ids[:, 1:]
    onehot = np.eye(V, dtype=np.float32)[labels]
    grad, score = net.compute_gradient_and_score(DataSet(feats, onehot))

    wf = jax.tree.map(lambda a: a.astype(jnp.float32), w)

    def loss(wf):
        total = 0.0
        for b in range(2):
            lg = ref.logits_at(wf, jnp.asarray(feats[b])[None],
                               jnp.arange(12), c=c, n_heads=sz["H"],
                               eps=sz["eps"])
            logp = jax.nn.log_softmax(lg, axis=-1)
            total = total - jnp.sum(jnp.take_along_axis(
                logp, jnp.asarray(labels[b])[:, None], 1))
        return total / labels.size

    want_score, g = jax.value_and_grad(loss)(wf)
    want = ravel_pytree(fam.to_program(g))[0]
    assert abs(score - float(want_score)) < 1e-5
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1e-4
    np.testing.assert_allclose(grad, want, atol=2e-4 * scale)


def test_a_tiny_fit_lowers_the_loss(model):
    net = model[3].clone()
    net._params = copy.deepcopy(model[3]._params)
    ids = np.stack([_ids(17, s) for s in range(4)])
    ds = DataSet(ids[:, :-1], np.eye(V, dtype=np.float32)[ids[:, 1:]])
    first = net.score(ds)
    for _ in range(8):
        net.fit(ds)
    assert net.score(ds) < first


def test_json_round_trip_of_the_new_layer_kinds(model):
    conf = model[3].conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    blocks = [l for l in again.layers if isinstance(l, DecoderBlock)]
    assert [b.mixer.state for b in blocks] == ["recurrent", "kv",
                                               "recurrent"]
    assert blocks[0].mixer == conf.layers[1].mixer
    assert blocks[1].ffn.experts_held == (0, 4)
    assert again.layers[0].multiplier == 12
    assert again.layers[-1].tied_to == 0 \
        and again.layers[-1].logits_scaling == 16


# ------------------------------------------------------------ the experts
def _moe_layer(held):
    ffn = MoEFeedForward(n_experts=8, top_k=3, expert_width=16,
                         shared_width=24, experts_held=held)
    return ffn, ffn.init_params(
        jax.random.PRNGKey(3), 32, jnp.float32,
        lambda k, s, fi, fo: jax.random.normal(k, s) / fi ** 0.5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """[0, n/2) and [n/2, n), the shared expert counted once, give the
    whole layer: the tie between the chip's share and the model."""
    whole, p = _moe_layer(None)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 32))
    y, _ = whole.forward(p, x)
    parts = []
    for first in (0, 4):
        ffn, _ = _moe_layer((first, 4))
        part = dict(p, **{n: p[n][first:first + 4]
                          for n in ("Wg", "Wu", "Wd")})
        parts.append(ffn.forward(part, x)[0])
    from deeplearning4j_tpu.parallel.experts import gated_mlp

    shared = gated_mlp(x.reshape(-1, 32), p["sWg"], p["sWu"],
                       p["sWd"]).reshape(x.shape)
    np.testing.assert_allclose(parts[0] + parts[1] - shared, y, atol=1e-5)


def test_a_router_that_sends_every_token_to_one_expert_loses_none():
    ffn, p = _moe_layer(None)
    # expert 2 wins every token by a wide margin, then 5, then 0
    router = np.zeros((32, 8), np.float32)
    p = dict(p, router=jnp.asarray(router))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (40, 32)))
    p["router"] = p["router"].at[:, 2].set(3.0).at[:, 5].set(2.0) \
        .at[:, 0].set(1.0)
    y, counts = ffn.forward(p, x, jnp.ones((40,), bool))
    # how many tokens chose each held expert, and whether it was read
    np.testing.assert_array_equal(
        counts.experts, [[40, 0, 40, 0, 0, 40, 0, 0],
                         [1, 0, 1, 0, 0, 1, 0, 0]])
    # every token got its three experts' gated outputs: rebuild them
    from deeplearning4j_tpu.parallel.experts import gated_mlp, topk_gates

    gates = topk_gates(x @ p["router"], 3)
    want = gated_mlp(x, p["sWg"], p["sWu"], p["sWd"])
    for e in (0, 2, 5):
        want = want + gates[:, e:e + 1] * gated_mlp(
            x, p["Wg"][e], p["Wu"][e], p["Wd"][e])
    assert float(jnp.min(jnp.sum(gates > 0, axis=1))) == 3
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_grouped_product_kernel_equals_the_batched_products():
    from deeplearning4j_tpu.ops.pallas_moe_experts import moe_experts
    from deeplearning4j_tpu.parallel.experts import (
        grouped_expert_ffn_xla,
        held_gates,
    )

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    N, d, f, E = 16, 128, 128, 4
    x = jax.random.normal(k[0], (N, d))
    Wg, Wu = (jax.random.normal(k[i], (E, d, f)) / 11 for i in (1, 2))
    Wd = jax.random.normal(k[3], (E, f, d)) / 11
    gates = held_gates(jax.random.normal(k[4], (N, 8)), 2, (2, 4))
    got = moe_experts(x, gates, Wg, Wu, Wd, jnp.any(gates != 0, axis=0),
                      interpret=True)
    np.testing.assert_allclose(
        got, grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd), atol=1e-5)


# ------------------------------------------------------------- the engine
ENGINE = dict(n_slots=3, max_len=96, page_size=8, prompt_buckets=(16, 32),
              prefill_chunk=16, decode_chunk=4, logprobs=4)


def _served(net, prompt, n, **kw):
    eng = DecodeEngine(net, **dict(ENGINE, **kw))
    try:
        return eng.generate(prompt, n, logprobs=4), eng.stats()
    finally:
        eng.shutdown(drain_timeout=30.0)


def _assert_served_equals_reference(model, prompt, out):
    """Every served token's logprob, and the top four at its position,
    against the reference's full forward over prompt + served tokens."""
    toks = np.asarray(out["tokens"])
    full = np.concatenate([prompt, toks])
    t0, n = len(prompt), len(toks)
    want = _ref_logp(model, full, np.arange(t0 - 1, t0 + n - 1))
    for j, entry in enumerate(out["logprobs"]):
        assert entry["token"] == toks[j]
        assert abs(entry["logprob"] - want[j, toks[j]]) < 5e-5
        np.testing.assert_allclose(
            entry["top_logprobs"], np.sort(want[j])[::-1][:4], atol=5e-5)


@pytest.mark.parametrize("t0,kw", [
    (11, {}),                       # a padded bucket, inactive slots
    (16, {}),                       # a bucket filled exactly
    (45, {}),                       # longer than every bucket: 3 chunks
    (11, {"decode_chunk": 1}),      # the single step, never the scan
    (37, {"n_slots": 1}),           # chunked, the last chunk padded
], ids=["padded-bucket", "full-bucket", "chunked-prefill", "decode-step",
        "chunked-padded"])
def test_engine_prefill_and_decode_equal_the_reference(model, t0, kw):
    prompt = _ids(t0, seed=t0)
    out, st = _served(model[3], prompt, 13, **kw)
    _assert_served_equals_reference(model, prompt, out)
    assert st["state_resets"] == 1
    assert st["state_bytes_per_slot"] == 2 * (8 * 16 * 16 * 4
                                              + (128 + 32) * 3 * 4)
    # 12 decode steps, 1 live slot, top-2 in each of 3 blocks
    assert st["moe_routed"] == 12 * 2 * 3
    assert 0 < st["moe_held_choices"] <= st["moe_routed"]
    assert st["moe_experts_hit"] <= st["moe_held_choices"]
    # two of the three slots stand empty: what only they chose is not read
    assert st["moe_experts_read"] == st["moe_experts_hit"]
    assert st["moe_experts_held"] == 12


def test_decode_chunked_equals_decode_step(model):
    prompt = _ids(9, seed=3)
    a, sa = _served(model[3], prompt, 17)
    b, sb = _served(model[3], prompt, 17, decode_chunk=1)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_allclose([e["logprob"] for e in a["logprobs"]],
                               [e["logprob"] for e in b["logprobs"]],
                               atol=1e-5)
    assert sa["moe_held_choices"] == sb["moe_held_choices"]


def test_a_reused_slot_equals_a_fresh_engine(model):
    """One slot: the second request takes over the state the first one
    left, and must overwrite it, not add to it."""
    first, second = _ids(14, seed=8), _ids(12, seed=9)
    eng = DecodeEngine(model[3], **dict(ENGINE, n_slots=1))
    try:
        eng.generate(first, 10)
        got = eng.generate(second, 10, logprobs=4)
        assert eng.stats()["state_resets"] == 2
    finally:
        eng.shutdown(drain_timeout=30.0)
    fresh, _ = _served(model[3], second, 10, n_slots=1)
    np.testing.assert_array_equal(got["tokens"], fresh["tokens"])
    np.testing.assert_allclose([e["logprob"] for e in got["logprobs"]],
                               [e["logprob"] for e in fresh["logprobs"]],
                               atol=1e-6)
    _assert_served_equals_reference(model, second, got)


def test_concurrent_requests_do_not_touch_each_others_state(model):
    prompts = [_ids(n, seed=20 + n) for n in (7, 19, 33)]
    alone = [_served(model[3], p, 11)[0] for p in prompts]
    eng = DecodeEngine(model[3], **ENGINE)
    try:
        reqs = [eng.submit(p, 11, logprobs=4) for p in prompts]
        for r, want in zip(reqs, alone):
            np.testing.assert_array_equal(r.result(timeout=120.0),
                                          want["tokens"])
            np.testing.assert_allclose(
                [e["logprob"] for e in r.logprob_values],
                [e["logprob"] for e in want["logprobs"]], atol=2e-5)
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_a_batch_served_through_the_kernel(model, monkeypatch):
    """Three requests of different lengths, so that slots stand empty
    while others decode, with the grouped product as the kernel a TPU
    would dispatch (interpreted): told what the live slots chose, and
    told to read every held expert, as it did before it was told
    anything. Both serve the batched products' tokens, and each other's
    logprobs to the bit: an expert no live slot chose adds exactly 0.0
    to a live slot's row."""
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    def batch():
        eng = DecodeEngine(model[3], **ENGINE)
        try:
            reqs = [eng.submit(_ids(n, seed=20 + n), m, logprobs=4)
                    for n, m in ((7, 5), (19, 14), (33, 9))]
            toks = [list(r.result(timeout=120.0)) for r in reqs]
            return toks, [[e["logprob"] for e in r.logprob_values]
                          for r in reqs], eng.stats()
        finally:
            eng.shutdown(drain_timeout=30.0)

    def through(mark):
        monkeypatch.setattr(
            pme, "moe_experts_or_none",
            lambda x, gates, Wg, Wu, Wd, hit, act=pme.GATED_SILU:
            pme.moe_experts(x, gates, Wg, Wu, Wd, mark(hit), act=act,
                            interpret=True))
        return batch()

    want_toks, want_lps, _ = batch()
    every_toks, every_lps, _ = through(jnp.ones_like)
    toks, lps, st = through(lambda hit: hit)
    assert toks == every_toks == want_toks
    assert lps == every_lps
    for got, want in zip(lps, want_lps):
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert st["moe_experts_read"] == st["moe_experts_hit"] \
        < st["moe_steps"] * st["moe_experts_held"]


def test_preemption_by_replay_gives_the_same_tokens(model):
    p_batch, p_int = _ids(8, seed=1), _ids(8, seed=2)
    eng = DecodeEngine(model[3], **dict(ENGINE, n_slots=1, logprobs=0,
                                        qos={"preempt": True}))
    try:
        # 64 tokens: long enough that the victim is still decoding when
        # the polling thread, starved on a loaded machine, submits `urgent`
        want = eng.submit(p_batch, 64).result(timeout=120.0)
        victim = eng.submit(p_batch, 64, tenant="bulk", priority="batch")
        deadline = time.monotonic() + 60.0
        while not victim.tokens and time.monotonic() < deadline:
            time.sleep(0.002)
        urgent = eng.submit(p_int, 4, tenant="live", priority="interactive")
        assert len(urgent.result(timeout=120.0)) == 4
        np.testing.assert_array_equal(victim.result(timeout=120.0), want)
        assert eng.stats()["preemptions"] == 1
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_a_dispatch_ahead_carries_the_recurrent_state(model):
    """The next step is issued before the last one's tokens are read:
    the state it starts from is the device's, carried from program to
    program, and what is served is still the reference's."""
    prompts = [_ids(n, seed=40 + n) for n in (9, 13)]
    eng = DecodeEngine(model[3], **dict(ENGINE, n_slots=2))
    try:
        reqs = [eng.submit(p, 15, logprobs=4) for p in prompts]
        for r, p in zip(reqs, prompts):
            toks = r.result(timeout=120.0)
            _assert_served_equals_reference(
                model, p, {"tokens": toks, "logprobs": r.logprob_values})
        loop = eng.stats()["loop"]
        assert loop["ahead_n"] > 0 and loop["overshoot_tokens"] == 0
        # 14 decode steps a request, top-2 in each of 3 blocks
        assert eng.stats()["moe_routed"] == 2 * 14 * 2 * 3
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_overshoot_leaves_the_next_occupants_state_alone(model):
    """EOS is seen one dispatch late: the slot steps on through the
    dropped dispatch, state and all, stays taken until that dispatch is
    collected, and the request admitted after it starts from zeros: its
    tokens are a fresh engine's."""
    first, second = _ids(14, seed=8), _ids(12, seed=9)
    whole, _ = _served(model[3], first, 16, n_slots=1)
    eos = int(whole["tokens"][6])
    stop = int(np.argmax(np.asarray(whole["tokens"]) == eos))
    eng = DecodeEngine(model[3], **dict(ENGINE, n_slots=1, eos_token=eos))
    try:
        a = eng.submit(first, 16, logprobs=4)
        b = eng.submit(second, 10, logprobs=4)
        np.testing.assert_array_equal(a.result(timeout=120.0),
                                      whole["tokens"][:stop + 1])
        got = b.result(timeout=120.0)
        st = eng.stats()
        assert st["state_resets"] == 2
        assert st["loop"]["overshoot_tokens"] > 0
    finally:
        eng.shutdown(drain_timeout=30.0)
    fresh, _ = _served(model[3], second, 10, n_slots=1, eos_token=eos)
    np.testing.assert_array_equal(got, fresh["tokens"])
    np.testing.assert_allclose([e["logprob"] for e in b.logprob_values],
                               [e["logprob"] for e in fresh["logprobs"]],
                               atol=1e-6)


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative": {"draft": "self", "k": 2}}, "speculative"),
    ({"parallel": {"tp": 2}}, "tp"),
    ({"quantize": {"kv": "int8"}}, "int8"),
    ({"role": "prefill"}, "role"),
    ({"role": "decode"}, "role"),
], ids=["prefix-cache", "speculative", "tensor-parallel", "int8-kv",
        "prefill-role", "decode-role"])
def test_features_that_cannot_hold_recurrent_state_are_refused(model, kw,
                                                               what):
    with pytest.raises(RecurrentStateUnsupported, match=what):
        DecodeEngine(model[3], n_slots=2, max_len=32, page_size=8, **kw)


@pytest.mark.parametrize("call", [
    lambda e: e.migrate_slots(wait=0),
    lambda e: e.resume_submit({}),
    lambda e: e.export_prefix(np.arange(8)),
    lambda e: e.bind_prefix_directory(object(), "h"),
], ids=["migrate", "resume", "export-prefix", "prefix-directory"])
def test_kv_moving_calls_are_refused_on_a_recurrent_engine(model, call):
    eng = DecodeEngine(model[3], n_slots=1, max_len=32, page_size=8)
    try:
        with pytest.raises(RecurrentStateUnsupported, match="recurrent"):
            call(eng)
    finally:
        eng.shutdown(drain_timeout=10.0)


def test_generate_refuses_a_composed_network(model):
    from deeplearning4j_tpu.models.transformer import generate

    with pytest.raises(ValueError, match="DecodeEngine"):
        generate(model[3], _ids(4), 2)


def test_block_params_split_by_prefix(model):
    p = model[3]._params[1]
    assert set(sub(p, "mx_")) == {"Win", "conv_w", "conv_b", "dt_bias",
                                  "A_log", "D", "norm_w", "Wout"}
    assert set(sub(p, "ff_")) == {"router", "Wg", "Wu", "Wd", "sWg",
                                  "sWu", "sWd"}
