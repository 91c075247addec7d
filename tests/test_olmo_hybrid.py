"""The post-norm composed `DecoderBlock` of the `olmo_hybrid` family (a
gated delta-rule mixer or full attention with QK-norm, then a dense
gated MLP, each sub-layer's OUTPUT normed) against the plain reference
the benchmark keeps (`perfbench/families/olmo_hybrid_reference.py`: the
delta rule as a `lax.scan` over time, naive attention) on seeded weights
at a small size: the kinds' JSON, one block of each mixer, the network's
forward, and the decode engine's prefill and decode through the matrix
state and paged K/V side by side."""
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
    AttentionMixer,
    DecoderBlock,
    GatedDeltaNetMixer,
    GatedMLP,
    kind_from_json,
    sub,
)
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
from perfbench.families import olmo_hybrid as fam
from perfbench.families import olmo_hybrid_reference as ref

REPO = Path(__file__).resolve().parents[1]
V = 97
LINEAR, FULL = "linear_attention", "full_attention"


def _config(**over) -> dict:
    """The benchmark's configuration file, cut to a toy: d 64, pattern
    linear full linear, 2 linear heads of 8 x 16, 4 attention heads."""
    cfg = json.loads((REPO / "perfbench/configs/olmo-hybrid-7b.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               intermediate_size=48, num_hidden_layers=3,
               layer_types=[LINEAR, FULL, LINEAR],
               linear_num_key_heads=2, linear_num_value_heads=2,
               linear_key_head_dim=8, linear_value_head_dim=16,
               vocab_size=V)
    cfg.update(over)
    return cfg


def _build(cfg, seed=5, edit=None):
    """(sizes, reference constants, bf16-valued weights, the program's
    f32 net holding them). `edit` is applied to every block (how a test
    builds the net WITHOUT something the model has)."""
    sz, c = fam.sizes(cfg), ref.consts_from_config(cfg)
    w = fam.make_weights(seed, sz)
    net = fam.build_net(sz, training=False, dtype=jnp.float32)
    for layer in net.layers:
        if edit is not None and isinstance(layer, DecoderBlock):
            edit(layer)
    fam.install(net, jax.tree.map(lambda a: a.astype(jnp.float32), w))
    return sz, c, w, net


@pytest.fixture(scope="module")
def model():
    return _build(_config())


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


def _ref_logp(model, ids, rows):
    sz, c, w, _ = model
    lg = ref.logits_at(w, jnp.asarray(ids)[None], jnp.asarray(rows), c=c,
                       n_heads=sz["H"], eps=sz["eps"])
    return np.asarray(jax.nn.log_softmax(lg, axis=-1))


# -------------------------------------------------------------- the kinds
@pytest.mark.parametrize("kind", [
    GatedDeltaNetMixer(n_heads=3, key_dim=8, value_dim=24, d_conv=4,
                       chunk=32, allow_neg_eigval=True, eps=1e-6),
    GatedMLP(width=112),
    AttentionMixer(n_heads=6, qk_norm=True, eps=1e-6),
], ids=["gated-delta-net", "gated-mlp", "attention-qk-norm"])
def test_a_kind_round_trips_through_json(kind):
    d = json.loads(json.dumps(kind.to_json()))
    assert d["kind"] == kind.KIND
    assert kind_from_json(d) == kind


def test_a_post_norm_network_round_trips_through_json(model):
    conf = model[3].conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    block = again.layers[1]
    assert block.norm_placement == "post"
    assert block.mixer == conf.layers[1].mixer
    assert again.layers[2].mixer.qk_norm is True
    assert block.ffn == GatedMLP(width=48)
    assert again.layers[-1].has_bias is False
    assert again.to_json() == conf.to_json()


def test_norm_placement_is_pre_or_post():
    with pytest.raises(ValueError, match="norm_placement"):
        DecoderBlock(n_in=8, n_out=8, mixer=AttentionMixer(n_heads=2),
                     ffn=GatedMLP(width=8), norm_placement="sandwich")


def test_the_declared_state_is_the_unpadded_matrix_state():
    mixer = GatedDeltaNetMixer(n_heads=30, key_dim=96, value_dim=192)
    (state, sdt), (tail, tdt) = mixer.state_shapes(64, jnp.bfloat16)
    assert state == (64, 96, 5760) and sdt == jnp.float32
    assert tail == (3, 64, 11520) and tdt == jnp.bfloat16
    # 12 x 8 sublanes by 45 x 128 lanes: nothing of it is padding
    assert state[1] % 8 == 0 and state[2] % 128 == 0


@pytest.mark.parametrize("i,kind", [(0, LINEAR), (1, FULL)])
def test_one_block_equals_the_reference_layer(model, i, kind):
    sz, c, w, net = model
    x = jax.random.normal(jax.random.PRNGKey(i), (23, sz["d"]))
    want = ref.layer(w["layers"][i], x, c=c, kind=kind, n_heads=sz["H"],
                     eps=sz["eps"], precision="float32")
    with jax.default_matmul_precision("highest"):
        got, _ = net.layers[1 + i].forward(net._params[1 + i], None,
                                           x[None])
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_block_params_split_by_prefix(model):
    linear, full = model[3]._params[1], model[3]._params[2]
    assert set(sub(linear, "mx_")) == {"Win", "conv_w", "dt_bias", "A_log",
                                       "norm_w", "Wout"}
    assert set(sub(full, "mx_")) == {"Wqkv", "qn_w", "kn_w", "Wo"}
    assert set(sub(full, "ff_")) == {"Wg", "Wu", "Wd"}
    assert set(model[3]._params[-1]) == {"W"}          # no head bias


# ------------------------------------------------------------ the network
def test_forward_logits_equal_the_reference(model):
    ids = _ids(21)
    out = model[3].output(ids[None])                  # softmax over logits
    want = _ref_logp(model, ids, np.arange(21))
    np.testing.assert_allclose(np.log(out[0]), want, atol=3e-5)


def test_gradients_through_fit_loss_equal_the_reference(model):
    """Serving is what the benchmark measures, but the kinds are layers
    like any other: `fit()`'s loss and its gradients through the chunked
    delta rule, the post-norm block and the bias-free head are the
    reference's (whose delta rule is the sequential scan)."""
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


def _pre_norm(layer):
    layer.norm_placement = "pre"


def _without_qk_norm(layer):
    if isinstance(layer.mixer, AttentionMixer):
        layer.mixer = AttentionMixer(n_heads=layer.mixer.n_heads)


@pytest.mark.parametrize("edit", [_pre_norm, _without_qk_norm],
                         ids=["pre-norm", "no-qk-norm"])
def test_a_net_built_without_it_misses_the_tolerance(model, edit):
    """Norm placement and QK-norm each matter: the same weights in a net
    that norms before its sub-layers, or leaves the query and key norms
    out, lie far outside the tolerance the model is held to."""
    *_, net = _build(_config(), edit=edit)
    ids = _ids(21)
    got = np.log(net.output(ids[None])[0])
    want = _ref_logp(model, ids, np.arange(21))
    assert float(np.max(np.abs(got - want))) > 100 * 3e-5


def test_pad_positions_and_a_split_leave_the_state_of_the_last_real_one():
    """A stretch padded past `n_valid`, and the same stretch cut in two
    with state and tail carried, end in the state and tail of the
    unpadded whole."""
    mixer = GatedDeltaNetMixer(n_heads=2, key_dim=8, value_dim=16, chunk=8,
                               allow_neg_eigval=True)
    p = mixer.init_params(jax.random.PRNGKey(0), 32, jnp.float32,
                          lambda k, s, fi, fo: 0.3 * jax.random.normal(k, s))
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
    # and the one-token step walks on from there
    slots_tail = jnp.swapaxes(t1, 0, 1)
    y3, h3, _ = mixer.step(p, x[:, 8], h1, slots_tail)
    y9, h9, _ = mixer.scan(p, x[:, :9])
    np.testing.assert_allclose(y3, y9[:, 8], atol=1e-5)
    np.testing.assert_allclose(h3, h9, atol=1e-5)


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
    # two linear blocks: a float32 (8, 2 x 16) state and three taps of
    # the 2 x 16 + 32 convolution channels, in the float32 compute dtype
    assert st["state_bytes_per_slot"] == 2 * (8 * 32 * 4 + 3 * 64 * 4)
    assert st["recurrent_blocks"] == 2 and st["kv_blocks"] == 1
    # one attention block: 4 heads of 16, keys and values, float32
    assert st["kv_bytes_per_token"] == 2 * 4 * 16 * 4
    assert st["moe_steps"] == 0


def test_decode_chunked_equals_decode_step(model):
    prompt = _ids(9, seed=3)
    a, _ = _served(model[3], prompt, 17)
    b, _ = _served(model[3], prompt, 17, decode_chunk=1)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_allclose([e["logprob"] for e in a["logprobs"]],
                               [e["logprob"] for e in b["logprobs"]],
                               atol=1e-5)


def test_a_reused_slot_equals_a_fresh_engine(model):
    """One slot: the second request takes over the matrix state the
    first one left, and must overwrite it, not add to it."""
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
    eng = DecodeEngine(model[3], **ENGINE)
    try:
        reqs = [eng.submit(p, 11, logprobs=4) for p in prompts]
        for r, p in zip(reqs, prompts):
            toks = r.result(timeout=120.0)
            _assert_served_equals_reference(
                model, p, {"tokens": toks, "logprobs": r.logprob_values})
        loop = eng.stats()["loop"]
        assert loop["ahead_n"] > 0 and loop["overshoot_tokens"] == 0
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_preemption_by_replay_gives_the_same_tokens(model):
    p_batch, p_int = _ids(8, seed=1), _ids(8, seed=2)
    eng = DecodeEngine(model[3], **dict(ENGINE, n_slots=1, logprobs=0,
                                        qos={"preempt": True}))
    try:
        want = eng.submit(p_batch, 24).result(timeout=120.0)
        victim = eng.submit(p_batch, 24, tenant="bulk", priority="batch")
        deadline = time.monotonic() + 60.0
        while not victim.tokens and time.monotonic() < deadline:
            time.sleep(0.002)
        urgent = eng.submit(p_int, 4, tenant="live", priority="interactive")
        assert len(urgent.result(timeout=120.0)) == 4
        np.testing.assert_array_equal(victim.result(timeout=120.0), want)
        assert eng.stats()["preemptions"] == 1
    finally:
        eng.shutdown(drain_timeout=30.0)
    full = np.concatenate([p_batch, want])
    logp = _ref_logp(model, full, np.arange(7, 7 + 24))
    best = logp.max(-1)
    assert np.all(best - logp[np.arange(24), want] < 5e-5)


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative": {"draft": "self", "k": 2}}, "speculative"),
    ({"parallel": {"tp": 2}}, "tp"),
    ({"quantize": {"kv": "int8"}}, "int8"),
    ({"role": "prefill"}, "role"),
    ({"role": "decode"}, "role"),
], ids=["prefix-cache", "speculative", "tensor-parallel", "int8-kv",
        "prefill-role", "decode-role"])
def test_features_that_cannot_hold_the_matrix_state_are_refused(model, kw,
                                                                what):
    with pytest.raises(RecurrentStateUnsupported, match=what):
        DecodeEngine(model[3], n_slots=2, max_len=32, page_size=8, **kw)


@pytest.mark.parametrize("call", [
    lambda e: e.migrate_slots(wait=0),
    lambda e: e.resume_submit({}),
    lambda e: e.export_prefix(np.arange(8)),
], ids=["migrate", "resume", "export-prefix"])
def test_kv_moving_calls_are_refused_on_the_new_kind(model, call):
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


def test_the_configuration_file_keeps_every_published_width():
    cfg = json.loads((REPO / "perfbench/configs/olmo-hybrid-7b.json")
                     .read_text())
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text()
                   .splitlines() if '"Olmo-Hybrid-7B"' in line)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key == "num_hidden_layers" or cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    sz = fam.sizes(cfg)
    assert (sz["d"], sz["f"], sz["H"], sz["hd"]) == (3840, 11008, 30, 128)
    assert (sz["lh"], sz["lk"], sz["lv"], sz["lconv"]) == (30, 96, 192, 4)
    assert sz["V"] == 100352 and sz["L"] % 4 == 0
    assert sz["layer_types"][:4] == (LINEAR, LINEAR, LINEAR, FULL)
