"""Blocks of ONE sub-layer (`nemotron_h`: a Mamba-2 mixer with B/C
groups, grouped-query attention whose heads have their own width, or
sigmoid-routed ungated relu^2 experts plus a shared one, each under one
norm and one residual) against the plain reference the benchmark keeps
(`perfbench/families/nemotron_h_reference.py`: a `lax.scan` over time
with explicit groups, naive attention with repeated K/V heads, a loop
over the experts held) on seeded weights at a small size: the kinds'
JSON, one block of each kind, the three forms of `ops/ssm.py` at more
than one group, the router, the share of the experts a chip holds, the
network's forward, and the decode engine's prefill and decode through
Mamba state, paged K/V and blocks that keep nothing, side by side."""
import json
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
    GatedMLP,
    Mamba2Mixer,
    MoEFeedForward,
    kind_from_json,
    sub,
)
from deeplearning4j_tpu.ops import ssm
from deeplearning4j_tpu.parallel import experts
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
from perfbench.families import nemotron_h as fam
from perfbench.families import nemotron_h_reference as ref

REPO = Path(__file__).resolve().parents[1]
CONFIG = REPO / "perfbench/configs/nemotron-3-nano-30b-a3b.json"
V = 97
PATTERN = "MEM*E"


def _config(**over) -> dict:
    """The benchmark's configuration file, cut to a toy: d 64, pattern
    `MEM*E`, 4 Mamba heads of 16 in 2 groups, 4 query over 2 K/V heads of
    32 (not d / 4 = 16), 8 experts top-2 of width 24 (a multiple of 8
    and not of 128), all held."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(hidden_size=64, num_hidden_layers=len(PATTERN),
               hybrid_override_pattern=PATTERN, mamba_num_heads=4,
               mamba_head_dim=16, ssm_state_size=16, n_groups=2,
               chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, n_routed_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=24,
               moe_shared_expert_intermediate_size=40, vocab_size=V)
    cfg["deployment"] = dict(n_routed_experts_published=8,
                             experts_held_first=0)
    cfg.update(over)
    return cfg


def _build(cfg, seed=5, dtype=jnp.float32, compute_dtype=None):
    """(sizes, reference constants, bf16-valued weights, the program's
    net holding them in `dtype`)."""
    sz, c = fam.sizes(cfg), ref.consts_from_config(cfg)
    w = fam.make_weights(seed, sz)
    net = fam.build_net(sz, training=True, dtype=dtype)
    if compute_dtype is not None:
        net.compute_dtype = compute_dtype
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
    Mamba2Mixer(n_heads=64, head_dim=64, d_state=128, chunk=128, n_groups=8),
    AttentionMixer(n_heads=32, n_kv_heads=2, head_dim=128),
    MoEFeedForward(n_experts=128, top_k=6, expert_width=1856,
                   shared_width=3712, experts_held=(0, 64),
                   activation="relu2", scoring="sigmoid", routed_scale=2.5),
], ids=["mamba2-groups", "attention-head-dim", "moe-relu2-sigmoid"])
def test_a_kind_round_trips_through_json(kind):
    d = json.loads(json.dumps(kind.to_json()))
    assert d["kind"] == kind.KIND
    assert kind_from_json(d) == kind


def test_the_defaults_are_the_kinds_as_they_were():
    """What granite-4.0-h-small and Olmo-Hybrid-7B compose is what a
    kind gives when the new fields are left alone."""
    assert Mamba2Mixer().n_groups == 1
    assert Mamba2Mixer(n_heads=8, head_dim=16, d_state=16).conv_width \
        == 128 + 2 * 16
    assert AttentionMixer(n_heads=4).kv_geometry(64) == (4, 16)
    ffn = MoEFeedForward()
    assert (ffn.activation, ffn.scoring, ffn.routed_scale) \
        == ("gated_silu", "softmax", 1.0)
    p = ffn.init_params(jax.random.PRNGKey(0), 32, jnp.float32,
                        lambda k, s, fi, fo: jnp.zeros(s))
    assert set(p) == {"router", "Wg", "Wu", "Wd"}
    assert p["Wu"].shape == (8, 32, 64)


@pytest.mark.parametrize("field,value", [("activation", "gelu"),
                                         ("scoring", "topk")])
def test_an_unknown_variant_is_refused(field, value):
    with pytest.raises(ValueError, match=field):
        MoEFeedForward(**{field: value})


def test_a_network_of_single_sub_layers_round_trips_through_json(model):
    conf = model[3].conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    blocks = [l for l in again.layers if isinstance(l, DecoderBlock)]
    assert [b.state for b in blocks] == ["recurrent", "none", "recurrent",
                                         "kv", "none"]
    assert [b.mixer is None for b in blocks] == [c == "E" for c in PATTERN]
    assert [b.ffn is None for b in blocks] == [c != "E" for c in PATTERN]
    assert blocks[0].mixer.n_groups == 2
    assert blocks[3].mixer.head_dim == 32
    assert blocks[1].ffn == conf.layers[2].ffn
    assert blocks[1].ffn.scoring == "sigmoid"
    assert again.layers[-1].has_bias is False


def test_a_block_needs_a_sub_layer():
    with pytest.raises(ValueError, match="mixer kind or a feed-forward"):
        DecoderBlock(n_in=8, n_out=8)
    whole = DecoderBlock(n_in=8, n_out=8, mixer=AttentionMixer(n_heads=2),
                         ffn=GatedMLP(width=8))
    assert whole.state == "kv"


def test_block_params_by_kind(model):
    """One norm a block and no second; no gate matrix in an ungated
    expert, whose up matrices lie (E, f, d); the correction bias a
    float32 vector that is no weight to regularise."""
    m, e, _, a, _ = model[3]._params[1:6]
    assert set(m) == {"n1_w"} | {"mx_" + n for n in (
        "Win", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm_w",
        "Wout")}
    assert m["mx_conv_w"].shape == (64 + 2 * 2 * 16, 4)
    assert set(a) == {"n1_w", "mx_Wqkv", "mx_Wo"}
    assert a["mx_Wqkv"].shape == (64, 4 * 32 + 2 * 2 * 32)
    assert a["mx_Wo"].shape == (4 * 32, 64)
    assert set(sub(e, "ff_")) == {"router", "router_b", "Wu", "Wd", "sWu",
                                  "sWd"}
    assert e["ff_Wu"].shape == e["ff_Wd"].shape == (8, 24, 64)
    block = model[3].layers[2]
    fresh = block.init_params(jax.random.PRNGKey(0), None, jnp.bfloat16)
    assert set(fresh) == set(e)
    assert fresh["ff_router_b"].dtype == jnp.float32
    assert fresh["ff_Wu"].dtype == jnp.bfloat16
    assert block.param_flags("ff_router_b") == {"is_bias": True,
                                                "regularizable": False}
    assert block.param_flags("ff_Wu")["regularizable"] is True


@pytest.mark.parametrize("i", range(len(PATTERN)),
                         ids=[f"{i}-{c}" for i, c in enumerate(PATTERN)])
def test_one_block_equals_the_reference_layer(model, i):
    sz, c, w, net = model
    x = jax.random.normal(jax.random.PRNGKey(i), (23, sz["d"]))
    want = ref.layer(w["layers"][i], x, c=c, kind=PATTERN[i],
                     n_heads=sz["H"], eps=sz["eps"], precision="float32")
    with jax.default_matmul_precision("highest"):
        got, _ = net.layers[1 + i].forward(net._params[1 + i], None,
                                           x[None])
    np.testing.assert_allclose(got[0], want, atol=2e-5)


# ------------------------------------------------- the state-space forms
def _ssm_inputs(T, G, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    B, H, P, N = 2, 8, 8, 16
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    shape = (B, T, N) if G is None else (B, T, G, N)
    Bm, Cm = (jax.random.normal(k[i], shape) for i in (3, 4))
    out = (x, dt, A, Bm, Cm, jax.random.normal(k[5], (H,)),
           jax.random.normal(k[6], (B, H, P, N)))
    return tuple(a.astype(jnp.float32) for a in out)


@pytest.mark.parametrize("T,chunk,G", [(37, 8, 2), (5, 8, 4), (64, 16, 8)])
def test_the_three_forms_agree_at_more_than_one_group(T, chunk, G):
    """Sequential, chunked and step by step, each head reading its own
    group; and the sequential form is the one-group recurrence run group
    by group over that group's heads."""
    x, dt, A, Bm, Cm, D, h0 = _ssm_inputs(T, G, seed=T)
    with jax.default_matmul_precision("highest"):
        y1, h1 = ssm.ssm_sequential(x, dt, A, Bm, Cm, D, h0)
        y2, h2 = ssm.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk, h0=h0)
        h3, ys = h0, []
        for t in range(T):
            y, h3 = ssm.ssm_step(h3, x[:, t], dt[:, t], A, Bm[:, t],
                                 Cm[:, t], D)
            ys.append(y)
        per = x.shape[2] // G
        parts = [ssm.ssm_sequential(
            x[:, :, g * per:(g + 1) * per], dt[:, :, g * per:(g + 1) * per],
            A[g * per:(g + 1) * per], Bm[:, :, g], Cm[:, :, g],
            D[g * per:(g + 1) * per], h0[:, g * per:(g + 1) * per])
            for g in range(G)]
    np.testing.assert_allclose(y2, y1, atol=5e-5)
    np.testing.assert_allclose(h2, h1, atol=5e-5)
    np.testing.assert_allclose(jnp.stack(ys, 1), y1, atol=1e-5)
    np.testing.assert_allclose(h3, h1, atol=1e-5)
    np.testing.assert_allclose(jnp.concatenate([p[0] for p in parts], 2),
                               y1, atol=1e-6)
    np.testing.assert_allclose(jnp.concatenate([p[1] for p in parts], 1),
                               h1, atol=1e-6)


def test_one_group_is_the_arithmetic_it_was_to_the_bit():
    """Without a group axis the step is the expression it was before
    groups came (granite-4.0-h-small's numbers do not move), and a group
    axis of one gives the same numbers."""
    x, dt, A, Bm, Cm, D, h0 = _ssm_inputs(3, None)

    @jax.jit
    def was(h, x, dt, A, Bm, Cm, D):
        decay = jnp.exp(dt * A)
        dx = dt[..., None] * x
        h = decay[..., None, None] * h + dx[..., None] * Bm[:, None, None, :]
        y = jnp.einsum("shpn,sn->shp", h, Cm) + D[None, :, None] * x
        return y, h

    args = (h0, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    got, want = jax.jit(ssm.ssm_step)(*args), was(*args)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    one = ssm.ssm_step(h0, x[:, 0], dt[:, 0], A, Bm[:, 0, None],
                       Cm[:, 0, None], D)
    np.testing.assert_allclose(one[0], want[0], atol=1e-6)
    np.testing.assert_allclose(one[1], want[1], atol=1e-6)


def test_pad_positions_and_a_split_leave_the_state_of_the_last_real_one():
    mixer = Mamba2Mixer(n_heads=4, head_dim=8, d_state=16, chunk=8,
                        n_groups=2)
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
    # and the one-token step walks on from there
    y3, h3, _ = mixer.step(p, x[:, 8], h1, jnp.swapaxes(t1, 0, 1))
    y9, h9, _ = mixer.scan(p, x[:, :9])
    np.testing.assert_allclose(y3, y9[:, 8], atol=1e-5)
    np.testing.assert_allclose(h3, h9, atol=1e-5)
    assert mixer.state_shapes(3, jnp.bfloat16)[1][0] == (3, 3, 32 + 2 * 32)


# ------------------------------------------------------------- the router
def _hand_router(logits, bias, top_k, scale):
    """Row by row in numpy: scores, the choice on score + bias, the
    weights the unbiased scores over their sum."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    gates = np.zeros_like(s)
    for n, row in enumerate(s):
        chosen = np.argsort(-(row + bias), kind="stable")[:top_k]
        gates[n, chosen] = row[chosen] / (row[chosen].sum() + 1e-20) * scale
    return gates


def test_the_router_equals_a_hand_written_one():
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    logits = jax.random.normal(k1, (40, 16))
    bias = 0.3 * jax.random.normal(k2, (16,))
    got = experts.sigmoid_topk_gates(logits, bias, 3, 2.5)
    np.testing.assert_allclose(got, _hand_router(logits, np.asarray(bias),
                                                 3, 2.5), atol=1e-6)
    np.testing.assert_allclose(got.sum(-1), 2.5, atol=1e-5)
    assert np.all((np.asarray(got) > 0).sum(-1) == 3)
    held = experts.held_gates(logits, 3, (4, 8), bias=bias, scale=2.5)
    np.testing.assert_array_equal(held, got[:, 4:12])


def test_the_bias_moves_the_choice_and_never_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    plain = np.asarray(experts.sigmoid_topk_gates(logits, jnp.zeros(4), 2,
                                                  1.0))[0]
    np.testing.assert_allclose(plain, [s[0] / (s[0] + s[1]),
                                       s[1] / (s[0] + s[1]), 0, 0],
                               atol=1e-6)
    # a bias that lifts expert 3 past experts 1 and 2: chosen are 0 and
    # 3, weighed by their UNBIASED scores
    lifted = np.asarray(experts.sigmoid_topk_gates(
        logits, jnp.asarray([0.0, 0.0, 0.0, 0.6]), 2, 1.0))[0]
    np.testing.assert_allclose(lifted, [s[0] / (s[0] + s[3]), 0, 0,
                                        s[3] / (s[0] + s[3])], atol=1e-6)
    # a bias that changes nothing of the order changes nothing at all
    same = np.asarray(experts.sigmoid_topk_gates(
        logits, jnp.asarray([0.3, 0.3, 0.0, 0.0]), 2, 1.0))[0]
    np.testing.assert_array_equal(same, plain)


# ------------------------------------------------------------ the experts
def _moe_layer(held):
    ffn = MoEFeedForward(n_experts=8, top_k=3, expert_width=24,
                         shared_width=40, experts_held=held,
                         activation="relu2", scoring="sigmoid",
                         routed_scale=2.5)
    p = ffn.init_params(
        jax.random.PRNGKey(3), 32, jnp.float32,
        lambda k, s, fi, fo: jax.random.normal(k, s) / fi ** 0.5)
    p["router_b"] = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (8,))
    return ffn, p


def test_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 held in turn: the two routed parts, with the
    shared expert (which every chip computes alike) counted once, give
    the whole layer: the tie between the chip's share and the model."""
    whole, p = _moe_layer(None)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 32))
    y, _ = whole.forward(p, x)
    parts = []
    for first in (0, 4):
        ffn, _ = _moe_layer((first, 4))
        part = dict(p, **{n: p[n][first:first + 4] for n in ("Wu", "Wd")})
        parts.append(ffn.forward(part, x)[0])
    shared = experts.relu2_mlp(x.reshape(-1, 32), p["sWu"],
                               p["sWd"]).reshape(x.shape)
    np.testing.assert_allclose(parts[0] + parts[1] - shared, y, atol=1e-5)
    assert float(jnp.max(jnp.abs(parts[0] - shared))) > 1e-2


def test_the_layer_equals_its_experts_one_by_one():
    ffn, p = _moe_layer(None)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 32))
    y, counts = ffn.forward(p, x, jnp.ones((40,), bool))
    gates = _hand_router(x @ p["router"], np.asarray(p["router_b"]), 3, 2.5)
    chosen = (gates > 0).sum(0)
    np.testing.assert_array_equal(counts.experts, [chosen, chosen > 0])
    want = experts.relu2_mlp(x, p["sWu"], p["sWd"])
    for e in range(8):
        want = want + gates[:, e:e + 1] * experts.relu2_mlp(
            x, p["Wu"][e].T, p["Wd"][e])
    np.testing.assert_allclose(y, want, atol=1e-5)


@pytest.mark.parametrize("f", (24, 232), ids=("f-24", "f-232"))
def test_ungated_kernel_equals_the_batched_products(f):
    """The kernel in interpret mode at an expert width that is a multiple
    of 8 and not of 128, against the XLA form."""
    from deeplearning4j_tpu.ops.pallas_moe_experts import moe_experts

    k = jax.random.split(jax.random.PRNGKey(f), 5)
    N, d, E = 16, 128, 4
    x = jax.random.normal(k[0], (N, d))
    Wu = jax.random.normal(k[1], (E, f, d)) / 11
    Wd = jax.random.normal(k[2], (E, f, d)) / f ** 0.5
    gates = experts.held_gates(jax.random.normal(k[3], (N, 8)), 2, (2, 4),
                               bias=0.2 * jax.random.normal(k[4], (8,)),
                               scale=2.5)
    got = moe_experts(x, gates, None, Wu, Wd, jnp.any(gates != 0, axis=0),
                      act="relu2", interpret=True)
    want = experts.grouped_expert_ffn_xla(x, gates, None, Wu, Wd, "relu2")
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_the_dispatch_tells_the_variants_apart(monkeypatch):
    """Same rows and widths, two variants: two probe keys, and the width
    1856 (232 x 8, 14.5 x 128) is declined for the gated layout alone."""
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    seen = []
    monkeypatch.setattr(pme, "_platform_supported", lambda: True)
    monkeypatch.setattr(pme, "_vmem_limit", lambda: 112 << 20)
    monkeypatch.setattr(pme, "_probe_verdict",
                        lambda fam_, key, fn, args: seen.append(key) or False)
    monkeypatch.setattr(pme, "_record_decline",
                        lambda fam_, key, msg: seen.append(("no", key)))
    S = lambda *s: jnp.zeros(s, jnp.bfloat16)
    x, g, hit = S(64, 256), jnp.zeros((64, 4), jnp.float32), jnp.ones(4, bool)
    pme.moe_experts_or_none(x, g, S(4, 256, 1856), S(4, 256, 1856),
                            S(4, 1856, 256), hit)
    pme.moe_experts_or_none(x, g, None, S(4, 1856, 256), S(4, 1856, 256),
                            hit, "relu2")
    pme.moe_experts_or_none(x, g, S(4, 256, 768), S(4, 256, 768),
                            S(4, 768, 256), hit)
    assert seen == [("no", ("bfloat16", 64, 256, 1856)),
                    ("bfloat16", 64, 256, 1856, "relu2"),
                    ("bfloat16", 64, 256, 768)]


# ------------------------------------------------------------ the network
def test_forward_logits_equal_the_reference(model):
    ids = _ids(21)
    out = model[3].output(ids[None])                  # softmax over logits
    want = _ref_logp(model, ids, np.arange(21))
    np.testing.assert_allclose(np.log(out[0]), want, atol=2e-5)


def test_gradients_through_fit_loss_equal_the_reference(model):
    """Serving is what the benchmark measures, but the kinds are layers
    like any other: `fit()`'s loss and its gradients through grouped
    B/C, the wide attention heads and the sigmoid-routed relu^2 experts
    are the reference's."""
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


# ------------------------------------------------------------- the engine
ENGINE = dict(n_slots=3, max_len=96, page_size=8, prompt_buckets=(16, 32),
              prefill_chunk=16, decode_chunk=4, logprobs=4)


def _served(net, prompt, n, **kw):
    eng = DecodeEngine(net, **dict(ENGINE, **kw))
    try:
        return eng.generate(prompt, n, logprobs=4), eng.stats()
    finally:
        eng.shutdown(drain_timeout=30.0)


def _assert_served_equals_reference(model, prompt, out, atol=5e-5):
    """Every served token's logprob, and the top four at its position,
    against the reference's full forward over prompt + served tokens
    (float32 on both sides: 5e-5 is summation order over d 64 and 13
    positions of state; bfloat16 anywhere misses it by two orders)."""
    toks = np.asarray(out["tokens"])
    full = np.concatenate([prompt, toks])
    t0, n = len(prompt), len(toks)
    want = _ref_logp(model, full, np.arange(t0 - 1, t0 + n - 1))
    for j, entry in enumerate(out["logprobs"]):
        assert entry["token"] == toks[j]
        assert abs(entry["logprob"] - want[j, toks[j]]) < atol
        np.testing.assert_allclose(
            entry["top_logprobs"], np.sort(want[j])[::-1][:4], atol=atol)


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
    assert (st["recurrent_blocks"], st["kv_blocks"],
            st["stateless_blocks"]) == (2, 1, 2)
    # two Mamba blocks: a float32 (4, 16, 16) state and three taps of the
    # 64 + 2 x 2 x 16 convolution channels in the float32 compute dtype
    assert st["state_bytes_per_slot"] == 2 * (4 * 16 * 16 * 4
                                              + 3 * 128 * 4)
    # one attention block: 2 K/V heads of 32, keys and values, float32
    assert st["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    # 12 decode steps, 1 live slot, top-2 in each of the 2 routed blocks,
    # every expert held
    assert st["moe_routed"] == st["moe_held_choices"] == 12 * 2 * 2
    assert st["moe_experts_hit"] == st["moe_held_choices"]
    # two of the three slots stand empty: what only they chose is not read
    assert st["moe_experts_read"] == st["moe_experts_hit"]
    assert st["moe_experts_held"] == 2 * 8


def test_bfloat16_in_float32s_place_misses_the_tolerance(model):
    """The tolerance above is float32's: the same weights served with
    bfloat16 compute lie far outside it."""
    *_, net = _build(_config(), compute_dtype=jnp.bfloat16)
    prompt = _ids(11, seed=11)
    out, _ = _served(net, prompt, 13)
    toks = np.asarray(out["tokens"])
    want = _ref_logp(model, np.concatenate([prompt, toks]),
                     np.arange(10, 10 + 13))
    off = max(abs(e["logprob"] - want[j, toks[j]])
              for j, e in enumerate(out["logprobs"]))
    assert off > 20 * 5e-5


def test_half_the_experts_held_is_half_the_choices(model):
    """Experts 4-7 of 8 held: the engine serves the reference's partial
    sum, and its counters see the share."""
    half = _build(_config(n_routed_experts=4, deployment=dict(
        n_routed_experts_published=8, experts_held_first=4)))
    prompt = _ids(14, seed=3)
    out, st = _served(half[3], prompt, 13)
    _assert_served_equals_reference(half, prompt, out)
    assert st["moe_routed"] == 12 * 2 * 2
    assert 0 < st["moe_held_choices"] < st["moe_routed"]
    assert st["moe_experts_held"] == 2 * 4


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
    first, second = _ids(14, seed=8), _ids(12, seed=9)
    eng = DecodeEngine(model[3], **dict(ENGINE, n_slots=1))
    try:
        eng.generate(first, 10)
        got = eng.generate(second, 10, logprobs=4)
        assert eng.stats()["state_resets"] == 2
    finally:
        eng.shutdown(drain_timeout=30.0)
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


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative": {"draft": "self", "k": 2}}, "speculative"),
    ({"parallel": {"tp": 2}}, "tp"),
    ({"quantize": {"kv": "int8"}}, "int8"),
    ({"role": "prefill"}, "role"),
], ids=["prefix-cache", "speculative", "tensor-parallel", "int8-kv",
        "prefill-role"])
def test_features_that_cannot_hold_recurrent_state_are_refused(model, kw,
                                                               what):
    with pytest.raises(RecurrentStateUnsupported, match=what):
        DecodeEngine(model[3], n_slots=2, max_len=32, page_size=8, **kw)


def test_generate_refuses_a_composed_network(model):
    from deeplearning4j_tpu.models.transformer import generate

    with pytest.raises(ValueError, match="DecodeEngine"):
        generate(model[3], _ids(4), 2)


# ------------------------------------------------------ the configuration
def test_the_configuration_file_keeps_every_published_width():
    cfg = json.loads(CONFIG.read_text())
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    cut = {"num_hidden_layers", "n_routed_experts",
           "hybrid_override_pattern"}
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text()
                   .splitlines() if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"'
                   in line)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in cut or cfg[key] == value, key
        dep = cfg["deployment"]
        assert dep["hybrid_override_pattern_published"] \
            == row["config"]["hybrid_override_pattern"]
        assert dep["num_hidden_layers_published"] \
            == row["config"]["num_hidden_layers"]
        assert dep["n_routed_experts_published"] \
            == row["config"]["n_routed_experts"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    sz = fam.sizes(cfg)
    assert (sz["d"], sz["H"], sz["Hkv"], sz["hd"]) == (2688, 32, 2, 128)
    assert (sz["mh"], sz["mp"], sz["mn"], sz["mg"], sz["mk"]) \
        == (64, 64, 128, 8, 4)
    assert (sz["E"], sz["held"], sz["topk"], sz["route_scale"]) \
        == (128, (0, 64), 6, 2.5)
    assert (sz["f"], sz["fs"], sz["V"]) == (1856, 3712, 131072)
    assert sz["pattern"] == "MEMEM*EMEMEM*EME" and sz["L"] == 16
    assert [sz["pattern"].count(c) for c in "ME*"] == [7, 7, 2]
    assert cfg["deployment"]["hybrid_override_pattern_published"] \
        .startswith(sz["pattern"])
    shapes = fam._leaf_shapes(sz)
    assert shapes["Win"] == (2688, 4096 + 6144 + 64)
    assert shapes["conv_w"] == (6144, 4)
    assert shapes["Wqkv"] == (2688, 4096 + 2 * 256)
    assert shapes["Wu"] == shapes["Wd"] == (64, 1856, 2688)


@pytest.mark.parametrize("over,what", [
    ({"n_group": 8, "topk_group": 4}, "one group of experts"),
    ({"hybrid_override_pattern": "ME-*E"}, "M, \\* or E"),
    ({"n_routed_experts": 9}, "outside the router"),
    ({"tie_word_embeddings": True}, "untied head"),
], ids=["grouped-routing", "dense-layer", "held-past-router", "tied-head"])
def test_the_family_refuses_what_it_does_not_run(over, what):
    with pytest.raises(ValueError, match=what):
        fam.sizes(_config(**over))
