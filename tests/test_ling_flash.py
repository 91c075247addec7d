"""Channel-gated delta-rule layers beside gated latent attention under
sigmoid group-limited routing (`ling_flash`: Ling-3.0-flash) against the
plain reference the benchmark keeps
(`perfbench/families/ling_flash_reference.py`: the delta rule one
position at a time, expanded attention a head and a block of queries at
a time, the group rule, a loop over the experts held) on seeded weights
at a small size: the bounded gate, the group rule against a NumPy
transcription, the eight shares of a layer's experts, the delta mixer's
scan and step, the latent mixer's three forms with full-rank queries and
a gate a head, the attend kernel at 32 heads, one layer, the network's
forward, and the decode engine's prefill and decode through recurrent
slots AND latent pages in one net."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.decoder_block import (
    ChannelGatedDeltaMixer,
    DecoderBlock,
    GatedDeltaNetMixer,
    GatedMLP,
    LatentAttentionMixer,
    MoEFeedForward,
    kind_from_json,
    sub,
)
from deeplearning4j_tpu.ops import pallas_mla_attend as mla
from deeplearning4j_tpu.parallel import experts
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
from perfbench.families import ling_flash as fam
from perfbench.families import ling_flash_reference as ref

REPO = Path(__file__).resolve().parents[1]
CONFIG = REPO / "perfbench/configs/ling-3.0-flash.json"
V, L = 97, 6


def _config(**over) -> dict:
    """The benchmark's configuration file, cut to a toy: d 64, two
    periods of (KDA, KDA, MLA), two dense layers and 4 routed ones, 4
    heads (KDA: 8 x 8 states; MLA: a key/value latent of 16, 8 nope + 8
    rope, values 8), a dense FFN 48 wide, 16 experts 24 wide in 4 groups
    of which a token reaches 2, top-3, a shared expert, every expert
    held."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(hidden_size=64, num_hidden_layers=L, layer_group_size=3,
               num_attention_heads=4, num_key_value_heads=4, head_dim=8,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
               qk_head_dim=16, rotary_dim=8, v_head_dim=8,
               intermediate_size=48, moe_intermediate_size=24,
               moe_shared_expert_intermediate_size=24, num_experts=16,
               n_group=4, topk_group=2, num_experts_per_tok=3, vocab_size=V)
    cfg["deployment"] = dict(num_experts_published=16, experts_held_first=0)
    cfg.update(over)
    return cfg


def _build(cfg, seed=5, compute_dtype=None):
    """(sizes, reference constants, bf16-valued weights, the program's
    float32 net holding them)."""
    sz, c = fam.sizes(cfg), ref.consts_from_config(cfg)
    w = fam.make_weights(seed, sz)
    net = fam.build_net(sz, training=True, dtype=jnp.float32)
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


# -------------------------------------------------------- the bounded gate
KDA = ChannelGatedDeltaMixer(n_heads=4, key_dim=8, value_dim=8, chunk=16,
                             gate_lower_bound=-5.0)
D = 48


def _kda_params(mixer=KDA, seed=0):
    p = mixer.init_params(
        jax.random.PRNGKey(seed), D, jnp.float32,
        lambda k, shape, fi, fo: jax.random.normal(k, shape) / fi ** 0.5)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    p["norm_w"] = 1.0 + 0.1 * jax.random.normal(k1, p["norm_w"].shape)
    p["dt_bias"] = -1.0 + 0.5 * jax.random.normal(k2, p["dt_bias"].shape)
    p["A_log"] = 0.3 * jax.random.normal(k3, p["A_log"].shape)
    return p


def _x(seed=3, t=37):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, D))


@pytest.mark.parametrize("lower", [-5.0, -0.5])
def test_the_bounded_gate_lies_between_its_bound_and_zero(lower):
    """`g = lower * sigmoid(exp(A)(f + bias))`: a vector over the key
    channels, every entry in [lower, 0] whatever the projection says,
    strictly inside where the sigmoid is not saturated; masked-out rows
    are exactly 0."""
    mixer = dataclasses.replace(KDA, gate_lower_bound=lower)
    p = _kda_params(mixer)
    f = 30.0 * jax.random.normal(jax.random.PRNGKey(0), (5, 4 * 8))
    b = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
    g, beta = mixer._gates(p, f, b)
    assert g.shape == (5, 4, 8) and g.dtype == jnp.float32
    assert beta.shape == (5, 4)
    assert float(jnp.min(g)) >= lower and float(jnp.max(g)) <= 0.0
    mild, _ = mixer._gates(p, 0.1 * f, b)
    assert lower < float(jnp.min(mild)) and float(jnp.max(mild)) < 0.0
    assert float(jnp.min(beta)) > 0.0 and float(jnp.max(beta)) < 1.0
    keep = jnp.asarray([True, False, True, True, False])[:, None]
    g0, b0 = mixer._gates(p, f, b, keep)
    assert not np.any(np.asarray(g0[1])) and not np.any(np.asarray(b0[4]))
    np.testing.assert_array_equal(g0[0], g[0])
    # the formula, transcribed
    want = lower / (1.0 + np.exp(-np.exp(np.asarray(p["A_log"]))[:, None]
                                 * (np.asarray(f) + np.asarray(p["dt_bias"]))
                                 .reshape(5, 4, 8)))
    np.testing.assert_allclose(g, want, rtol=2e-5, atol=1e-7)


def test_a_bound_that_is_not_negative_is_refused():
    with pytest.raises(ValueError, match="below 0"):
        ChannelGatedDeltaMixer(gate_lower_bound=0.0)


def test_the_delta_kinds_round_trip_and_keep_their_state():
    kind = ChannelGatedDeltaMixer(n_heads=32, key_dim=128, value_dim=128,
                                  gate_lower_bound=-5.0, eps=1e-6)
    d = json.loads(json.dumps(kind.to_json()))
    assert d["kind"] == "channel_gated_delta" and d["gate_lower_bound"] == -5.0
    assert kind_from_json(d) == kind and kind.state == "recurrent"
    # a slot's state a layer: 32 x 128 x 128 float32 and three bfloat16
    # taps of 12,288 columns
    (s, sd), (t, td) = kind.state_shapes(1, jnp.bfloat16)
    assert s == (1, 128, 4096) and sd == jnp.float32
    assert t == (3, 1, 12288) and td == jnp.bfloat16
    assert int(np.prod(s)) * 4 + int(np.prod(t)) * 2 == 2_097_152 + 73_728
    # the in-projection: [q | k | v | gate | f | b]
    p = kind.init_params(jax.random.PRNGKey(0), 64, jnp.float32,
                         lambda k, shape, fi, fo: jnp.zeros(shape))
    assert p["Win"].shape == (64, 3 * 4096 + 4096 + 4096 + 32)
    assert p["dt_bias"].shape == (4096,) and p["A_log"].shape == (32,)
    # the kind with one decay a head is what it was
    old = GatedDeltaNetMixer(n_heads=30, key_dim=96, value_dim=192)
    assert kind_from_json(old.to_json()) == old
    assert "gate_lower_bound" not in old.to_json()


def _kda_reference(p, x, mixer=KDA):
    c = ref.Consts(layer_types=(), l_heads=mixer.n_heads,
                   l_key=mixer.key_dim, l_value=mixer.value_dim,
                   gate_lower=mixer.gate_lower_bound, kv_rank=1, nope=1,
                   rope=2, v_dim=1, rope_theta=1.0, n_experts=0, n_groups=1,
                   topk_groups=1, top_k=0, routed_scale=1.0, held_first=0)
    names = {"Win": "Win", "conv_w": "conv", "A_log": "A", "dt_bias": "fb",
             "norm_w": "on", "Wout": "Wout"}
    with jax.default_matmul_precision("highest"):
        return ref.kda({names[k]: v for k, v in p.items()}, x[0], c,
                       eps=mixer.eps, precision="float32")


def test_the_delta_mixers_scan_equals_the_reference_recurrence():
    p, x = _kda_params(), _x()
    np.testing.assert_allclose(KDA.forward(p, x)[0], _kda_reference(p, x),
                               atol=2e-5)


def test_the_delta_mixers_step_continues_its_scan():
    """A prefix by chunks, then the rest one token at a time through the
    step (two slots, one of them inactive), is the whole scan; pad
    positions past `n_valid` move neither state nor tail."""
    p, x = _kda_params(), _x()
    want, h_all, _ = KDA.scan(p, x)
    cut = 21
    padded = jnp.concatenate([x[:, :cut], 9.0 * jnp.ones((1, 11, D))], 1)
    _, h, tail = KDA.scan(p, padded, n_valid=cut)
    _, h_cut, tail_cut = KDA.scan(p, x[:, :cut])
    np.testing.assert_allclose(h, h_cut, atol=1e-6)
    np.testing.assert_array_equal(tail, tail_cut)
    h2 = jnp.concatenate([h, h + 1.0])
    tail2 = jnp.concatenate([jnp.swapaxes(tail, 0, 1)] * 2, axis=1)
    active = jnp.asarray([True, False])
    for t in range(cut, x.shape[1]):
        y, h_new, tail_new = KDA.step(
            p, jnp.concatenate([x[:, t], x[:, t]]), h2, tail2, active)
        np.testing.assert_allclose(y[0], want[0, t], atol=2e-5)
        np.testing.assert_array_equal(h_new[1], h2[1])
        np.testing.assert_array_equal(tail_new[:, 1], tail2[:, 1])
        h2, tail2 = h_new, tail_new
    np.testing.assert_allclose(h2[0], h_all[0], atol=2e-5)


def test_the_delta_mixer_names_its_scopes():
    p, x = _kda_params(), _x()
    hlo = jax.jit(lambda p, x: KDA.forward(p, x)).lower(p, x).as_text(
        debug_info=True)
    for scope in ("kda.in_proj", "kda.conv", "kda.gate", "kda.scan",
                  "kda.gate_norm", "kda.out_proj"):
        assert scope in hlo, scope
    h, tail = (jnp.zeros(s, d) for s, d in KDA.state_shapes(2, jnp.float32))
    step = jax.jit(lambda p, x, h, t: KDA.step(p, x, h, t)).lower(
        p, x[0, :2], h, tail).as_text(debug_info=True)
    assert "kda.step" in step and "kda.scan" not in step


# ---------------------------------------------------------- the group rule
def _published_gate(logits, bias, n_group, topk_group, top_k, scale):
    """The `noaux_tc` gate (DeepSeek-V3's `get_topk_indices` and
    `forward`, `norm_topk_prob` true) transcribed to NumPy, with what
    lies outside the kept groups set to -inf: (N, E) gates."""
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    choice = scores + bias.astype(np.float64)
    N, E = scores.shape
    by_group = choice.reshape(N, n_group, -1)
    group_scores = np.sort(by_group, axis=-1)[..., -2:].sum(-1)
    group_idx = np.argsort(-group_scores, axis=1)[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1.0, axis=1)
    score_mask = np.repeat(group_mask, E // n_group, axis=1).astype(bool)
    tmp = np.where(score_mask, choice, -np.inf)
    topk_idx = np.argsort(-tmp, axis=1)[:, :top_k]
    w = np.take_along_axis(scores, topk_idx, 1)
    w = w / (w.sum(1, keepdims=True) + 1e-20) * scale
    gates = np.zeros_like(scores)
    np.put_along_axis(gates, topk_idx, w, axis=1)
    return gates


def _logits(n=64, e=512, seed=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, e))


def _bias(e=512, seed=9, scale=0.05):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (e,))


def test_the_sigmoid_group_rule_equals_the_transcribed_gate():
    lg, b = _logits(), _bias()
    got = np.asarray(experts.routed_gates(
        lg, 8, bias=b, scale=2.5, scoring="sigmoid", n_groups=8,
        topk_groups=4))
    want = _published_gate(np.asarray(lg), np.asarray(b), 8, 4, 8, 2.5)
    assert np.array_equal(got != 0, want != 0)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the rule changes the choice: some row's plain top-8 reach a fifth
    # group; and the bias moves the choice, never the weight
    plain = np.asarray(experts.routed_gates(lg, 8, bias=b, scale=2.5,
                                            scoring="sigmoid"))
    assert np.any((plain != 0) != (got != 0))
    unbiased = np.asarray(experts.routed_gates(
        lg, 8, bias=jnp.zeros(512), scale=2.5, scoring="sigmoid",
        n_groups=8, topk_groups=4))
    assert np.any((unbiased != 0) != (got != 0))
    both = (unbiased != 0) & (got != 0)
    s = 1.0 / (1.0 + np.exp(-np.asarray(lg, np.float64)))
    np.testing.assert_allclose(
        got[both] / got.sum(1, keepdims=True).repeat(512, 1)[both],
        (s / np.where(got != 0, s, 0).sum(1, keepdims=True))[both],
        rtol=1e-5)


def test_a_rows_sigmoid_gates_lie_in_at_most_topk_groups_groups():
    lg = _logits(seed=3)
    # a bias strong enough to make biased scores negative: nothing
    # leaves the kept groups all the same
    for b in (_bias(), _bias(scale=0.3) - 0.6):
        g = np.asarray(experts.routed_gates(
            lg, 8, bias=b, scale=2.5, scoring="sigmoid", n_groups=8,
            topk_groups=4))
        assert np.all((g != 0).sum(1) == 8)
        np.testing.assert_allclose(g.sum(1), 2.5, rtol=1e-5)
        groups = (g != 0).reshape(len(g), 8, 64).any(-1).sum(1)
        assert groups.max() <= 4 and groups.min() >= 1
    # a group's score is the SUM of its two largest: a group with one
    # towering score loses to a group with two good ones
    row = np.full((1, 16), -4.0, np.float32)
    row[0, 0] = 6.0                      # group 0: one expert near 1
    row[0, 4:6] = 1.5                    # group 1: two at 0.82
    row[0, 8:10] = 1.4                   # group 2: two at 0.80
    g = np.asarray(experts.routed_gates(
        jnp.asarray(row), 2, bias=jnp.zeros(16), scale=1.0,
        scoring="sigmoid", n_groups=4, topk_groups=2))
    assert not g[0, 0] and g[0, 4] and g[0, 5]
    largest = np.asarray(experts.group_limited(
        jax.nn.sigmoid(jnp.asarray(row)), 4, 2))
    assert largest[0, 0] and not largest[0, 8]


def test_one_group_is_todays_sigmoid_router():
    lg, kw = _logits(e=128), dict(bias=_bias(128), scale=2.5,
                                  scoring="sigmoid")
    one = experts.routed_gates(lg, 6, n_groups=1, topk_groups=1, **kw)
    np.testing.assert_array_equal(one, experts.routed_gates(lg, 6, **kw))
    np.testing.assert_array_equal(
        one, experts.sigmoid_topk_gates(lg, kw["bias"], 6, 2.5))
    every = experts.routed_gates(lg, 6, n_groups=8, topk_groups=8, **kw)
    np.testing.assert_array_equal(every, one)
    fn = lambda n: str(jax.make_jaxpr(lambda x: experts.routed_gates(
        x, 6, n_groups=n, topk_groups=n, **kw))(lg))
    # every choice is a mask made without `lax.top_k` (`chosen_mask`, a
    # count at these shapes): the experts' alone, or after a group's
    # best two, their sum and the groups kept
    assert "top_k" not in fn(8) and fn(1).count("reduce_sum") + 3 \
        == fn(8).count("reduce_sum")
    assert fn(1) == str(jax.make_jaxpr(
        lambda x: experts.routed_gates(x, 6, **kw))(lg))


def test_the_routed_kind_round_trips_through_json():
    kind = MoEFeedForward(n_experts=512, top_k=8, expert_width=768,
                          shared_width=768, experts_held=(0, 64),
                          scoring="sigmoid", routed_scale=2.5, n_groups=8,
                          topk_groups=4)
    d = json.loads(json.dumps(kind.to_json()))
    assert (d["n_groups"], d["topk_groups"], d["scoring"]) == (8, 4,
                                                               "sigmoid")
    assert kind_from_json(d) == kind
    p = kind.init_params(jax.random.PRNGKey(0), 32, jnp.float32,
                         lambda k, s, fi, fo: jnp.zeros(s))
    # the router keeps its 512 outputs; one group's experts are held
    assert p["router"].shape == (32, 512) and p["Wg"].shape == (64, 32, 768)
    assert p["router_b"].shape == (512,) and p["router_b"].dtype == jnp.float32
    with pytest.raises(ValueError, match="largest"):
        MoEFeedForward(n_experts=16, top_k=3, scoring="softmax", n_groups=4,
                       topk_groups=2)


# --------------------------------------------------------- the eight shares
def _moe_args(seed=4, n=40, d=64, f=24, n_experts=32, shared=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    mk = lambda key, shape, s: jax.random.normal(key, shape) / s
    return dict(x=mk(k[0], (n, d), 1), router=mk(k[1], (d, n_experts), 4),
                rb=0.05 * jax.random.normal(k[8], (n_experts,)),
                eWg=mk(k[2], (n_experts, d, f), 8),
                eWu=mk(k[3], (n_experts, d, f), 8),
                eWd=mk(k[4], (n_experts, f, d), 5),
                sWg=mk(k[5], (d, shared), 8), sWu=mk(k[6], (d, shared), 8),
                sWd=mk(k[7], (shared, d), 4))


def _route_consts(**kw):
    base = dict(layer_types=(), l_heads=1, l_key=1, l_value=1,
                gate_lower=-5.0, kv_rank=1, nope=1, rope=2, v_dim=1,
                rope_theta=1.0, n_experts=32, n_groups=8, topk_groups=4,
                top_k=8, routed_scale=2.5, held_first=0)
    return ref.Consts(**dict(base, **kw))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """32 experts in 8 groups of 4, a token reaching 4 groups: held one
    group at a time, the eight chips' routed parts plus the shared MLP
    ONCE are the uncut reference's feed-forward, and every chip computes
    the same shared part."""
    a = _moe_args()
    x, c = a["x"], _route_consts()
    with jax.default_matmul_precision("highest"):
        want = ref.routed(a, x, c, precision="float32") \
            + ref.ffn(x, a["sWg"], a["sWu"], a["sWd"], precision="float32")
    parts, shared, local = [], None, []
    for first in range(0, 32, 4):
        held = slice(first, first + 4)
        kind = MoEFeedForward(n_experts=32, top_k=8, expert_width=24,
                              shared_width=16, experts_held=(first, 4),
                              scoring="sigmoid", routed_scale=2.5,
                              n_groups=8, topk_groups=4)
        p = {"router": a["router"], "router_b": a["rb"],
             "Wg": a["eWg"][held], "Wu": a["eWu"][held], "Wd": a["eWd"][held],
             "sWg": a["sWg"], "sWu": a["sWu"], "sWd": a["sWd"]}
        y, counts = kind.forward(p, x, jnp.ones(len(x), bool))
        no_shared, _ = dataclasses.replace(kind, shared_width=0).forward(
            p, x)
        parts.append(no_shared)
        each = y - no_shared            # every share computes it alike
        if shared is not None:
            np.testing.assert_allclose(each, shared, atol=1e-5)
        shared = each
        local.append(int(counts.rows_local))
        assert int(counts.experts[0].sum()) >= local[-1]
    assert float(jnp.max(jnp.abs(shared))) > 0.01
    np.testing.assert_allclose(sum(parts) + shared, want, atol=5e-5)
    # a row reaches at most 4 of the 8 chips, and every choice is held
    # by exactly one of them
    assert 2.0 * len(x) < sum(local) <= 4 * len(x)
    # one share alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0] + shared - want))) > 0.05


# ------------------------------------------------- the latent mixer, gated
T = 37
MIXER = LatentAttentionMixer(
    n_heads=4, q_rank=None, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
    rope_theta=6e6, eps=1e-6, head_gate=True)
CONSTS = _route_consts(kv_rank=16, nope=8, rope=8, v_dim=8, rope_theta=6e6)


def _mla_params(mixer=MIXER, seed=0):
    p = mixer.init_params(
        jax.random.PRNGKey(seed), D, jnp.float32,
        lambda k, shape, fi, fo: jax.random.normal(k, shape) / fi ** 0.5)
    p["kvn_w"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                               p["kvn_w"].shape)
    return p


def _mla_reference(p, x, c=CONSTS):
    names = {"Wqn": "Wqn", "Wqr": "Wqr", "Wkvc": "Wkvc", "Wkr": "Wkr",
             "kvn_w": "kvn", "Wkb": "Wkb", "Wvb": "Wvb", "Wa": "Wa",
             "Wo": "Wo"}
    with jax.default_matmul_precision("highest"):
        return ref.mla({names[k]: v for k, v in p.items()}, x[0],
                       jnp.arange(x.shape[1]), c, n_heads=MIXER.n_heads,
                       eps=MIXER.eps, precision="float32")


def test_full_rank_queries_and_the_head_gate_are_fields():
    p = _mla_params()
    assert "Wqa" not in p and "qn_w" not in p
    assert p["Wqn"].shape == (D, 4 * 8) and p["Wqr"].shape == (D, 4 * 8)
    assert p["Wa"].shape == (D, 4)
    d = json.loads(json.dumps(MIXER.to_json()))
    assert d["q_rank"] is None and d["head_gate"] is True
    assert kind_from_json(d) == MIXER
    assert abs(MIXER.sm_scale - 16 ** -0.5) < 1e-9
    with pytest.raises(ValueError, match="scale_q_lora"):
        dataclasses.replace(MIXER, scale_q_lora=True)
    # the defaults keep a query latent and no gate: nothing of the gate
    # is traced, no `Wa` is held
    plain = LatentAttentionMixer(n_heads=4, q_rank=24, kv_rank=16,
                                 nope_dim=8, rope_dim=8, v_dim=8)
    pp = plain.init_params(jax.random.PRNGKey(0), D, jnp.float32,
                           lambda k, shape, fi, fo: jnp.zeros(shape))
    assert "Wa" not in pp and pp["Wqa"].shape == (D, 24)
    assert (plain.head_gate, plain.q_rank) == (False, 24)
    x = _x(t=T)
    text = jax.jit(plain.forward).lower(pp, x).as_text(debug_info=True)
    assert "mla.gate" not in text
    gated = jax.jit(MIXER.forward).lower(p, x).as_text(debug_info=True)
    assert "mla.gate" in gated


def test_the_gated_expanded_forward_equals_the_reference():
    p, x = _mla_params(), _x(t=T)
    np.testing.assert_allclose(MIXER.forward(p, x)[0], _mla_reference(p, x),
                               atol=2e-5)
    # the gate is in the arithmetic
    off = jnp.max(jnp.abs(dataclasses.replace(MIXER, head_gate=False)
                          .forward(p, x)[0] - _mla_reference(p, x)))
    assert float(off) > 50 * 2e-5


def test_the_three_gated_forms_agree():
    """The whole sequence expanded; its second half as a chunk of
    absorbed queries against the cached latents; its last position as
    the absorbed one-token step: each under the gate of its own input
    rows, full-rank queries in all three."""
    p, x = _mla_params(), _x(t=T)
    want = MIXER.forward(p, x)[0]
    pos = jnp.arange(T)
    q_n, q_r, latent = MIXER.project(p, x, pos)
    assert q_n.shape == (1, T, 4, 8) and latent.shape == (1, T, 24)
    q_abs = MIXER.absorb(p, q_n[:, 20:], q_r[:, 20:])
    got = MIXER.out(p, MIXER.attend_latents(q_abs, latent, pos[None, 20:]),
                    x[:, 20:])
    np.testing.assert_allclose(got[0], want[20:], atol=2e-5)
    q_n1, q_r1, lat1 = MIXER.project(p, x[:, -1:], pos[None, -1:])
    np.testing.assert_allclose(lat1[0, 0], latent[0, -1], atol=1e-6)
    step = MIXER.out(p, MIXER.attend_latents(
        MIXER.absorb(p, q_n1, q_r1), latent, pos[None, -1:]), x[:, -1:])
    np.testing.assert_allclose(step[0, 0], want[-1], atol=2e-5)
    # gather-and-attend, the kernel's XLA form, one slot
    pool = jnp.zeros((7, 24, 8)).at[1:6].set(
        jnp.swapaxes(jnp.pad(latent[0], ((0, 3), (0, 0)))
                     .reshape(5, 8, 24), 1, 2))
    att = mla.mla_attend_xla(
        MIXER.absorb(p, q_n1, q_r1)[:, 0], pool,
        jnp.asarray([[1, 2, 3, 4, 5, 0]]), jnp.asarray([T - 1]),
        kv_rank=16, sm_scale=MIXER.sm_scale)
    np.testing.assert_allclose(MIXER.out(p, att, x[:, -1])[0], want[-1],
                               atol=2e-5)


def test_the_attend_kernel_at_32_heads_equals_gather_and_attend():
    """H 32 over 512 + 64 latents at the kernel's block of pages
    (interpret mode), the shape class this model adds: slots ending on a
    page's last position, the next page's first, past a whole block, and
    one inactive; dead table entries name a NaN page."""
    H, R, KV, PAGE = 32, 576, 512, 128
    assert mla.attend_key(jnp.bfloat16, H, R, KV, PAGE) \
        == ("bfloat16", 32, 576, 512, 128, "block8")
    B = mla.block_pages(PAGE, R, H, jnp.float32)
    rng = np.random.default_rng(0)
    pos = np.asarray([PAGE - 1, PAGE, B * PAGE + 5, 3], np.int32)
    active = np.asarray([True, True, True, False])
    live = pos // PAGE + 1
    P = int(live.sum())
    dead = P + 1
    pt = np.full((4, int(live.max()) + 1), dead, np.int32)
    at = 1
    for s, n in enumerate(live):
        pt[s, :n] = at + np.arange(n)
        at += n
    pool = jnp.asarray(rng.standard_normal((P + 2, R, PAGE)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((4, H, R)) / R ** 0.25, jnp.float32)
    kw = dict(kv_rank=KV, sm_scale=192 ** -0.5)
    want = mla.mla_attend_xla(q, pool, jnp.asarray(np.where(pt == dead, 0,
                                                            pt)),
                              jnp.asarray(pos), **kw)
    got = mla.mla_attend(q, pool.at[dead].set(jnp.nan), jnp.asarray(pt),
                         jnp.asarray(pos), jnp.asarray(active),
                         interpret=True, **kw)
    np.testing.assert_allclose(got[active], want[active], atol=2e-5)
    assert not np.any(np.asarray(got[~active]))


# ------------------------------------------------------------- the network
def test_the_network_round_trips_through_json(model):
    conf = model[3].conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    blocks = [l for l in again.layers if isinstance(l, DecoderBlock)]
    assert [b.state for b in blocks] == ["recurrent", "recurrent",
                                         "latent"] * 2
    assert blocks[0].ffn == blocks[1].ffn == GatedMLP(width=48)
    assert all(isinstance(b.ffn, MoEFeedForward) for b in blocks[2:])
    ffn = blocks[2].ffn
    assert (ffn.n_groups, ffn.topk_groups, ffn.top_k, ffn.shared_width,
            ffn.scoring, ffn.routed_scale) == (4, 2, 3, 24, "sigmoid", 2.5)
    assert isinstance(blocks[0].mixer, ChannelGatedDeltaMixer)
    assert blocks[0].mixer.gate_lower_bound == -5.0
    mixer = blocks[5].mixer
    assert (mixer.q_rank, mixer.head_gate, mixer.rope_scaling,
            mixer.rope_theta) == (None, True, None, 6e6)
    assert mixer == blocks[2].mixer and blocks[0].mixer == blocks[4].mixer


def test_layer_params_carry_the_programs_names(model):
    p = model[3]._params
    assert sorted(sub(p[1], "ff_")) == ["Wd", "Wg", "Wu"]
    assert sorted(sub(p[3], "ff_")) == sorted(
        ["router", "router_b", "Wg", "Wu", "Wd", "sWg", "sWu", "sWd"])
    assert p[3]["ff_router_b"].dtype == jnp.float32 \
        and np.any(np.asarray(p[3]["ff_router_b"]))
    assert sorted(sub(p[1], "mx_")) == sorted(
        ["Win", "conv_w", "A_log", "dt_bias", "norm_w", "Wout"])
    assert sorted(sub(p[3], "mx_")) == sorted(
        ["Wqn", "Wqr", "Wkvc", "Wkr", "kvn_w", "Wkb", "Wvb", "Wa", "Wo"])


@pytest.mark.parametrize("i", range(L))
def test_one_layer_equals_the_reference_layer(model, i):
    sz, c, w, net = model
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 29, 64))
    got, _ = net.layers[1 + i].forward(net._params[1 + i], {}, x)
    want = ref.layer(w["layers"][i], x[0], c=c, kind=c.layer_types[i],
                     n_heads=sz["H"], eps=sz["eps"], precision="float32")
    np.testing.assert_allclose(got[0], want, atol=5e-5)


def test_forward_logits_equal_the_reference(model):
    ids = _ids(41, seed=1)
    got = np.asarray(model[3].output(jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(np.log(got), _ref_logp(model, ids,
                                                      np.arange(41)),
                               atol=5e-5)


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
    (float32 on both sides: 5e-5 is summation order over d 64, the
    chunked against the sequential delta rule and the absorbed against
    the expanded products; bfloat16 anywhere misses it by two orders).
    Logits, not tokens."""
    toks = np.asarray(out["tokens"])
    full = np.concatenate([prompt, toks])
    t0, n = len(prompt), len(toks)
    want = _ref_logp(model, full, np.arange(t0 - 1, t0 + n - 1))
    for j, entry in enumerate(out["logprobs"]):
        assert entry["token"] == toks[j]
        assert abs(entry["logprob"] - want[j, toks[j]]) < atol
        np.testing.assert_allclose(
            entry["top_logprobs"], np.sort(want[j])[::-1][:4], atol=atol)


@pytest.mark.parametrize("t0,n,kw", [
    (11, 13, {}),                    # a padded bucket, inactive slots
    (32, 21, {}),                    # a bucket filled; 7 pages in the end
    (45, 13, {}),                    # longer than every bucket: 3 chunks
    (11, 13, {"decode_chunk": 1}),   # the single step, never the scan
], ids=["padded-bucket", "full-bucket-many-pages", "chunked-prefill",
        "decode-step"])
def test_engine_prefill_and_decode_equal_the_reference(model, t0, n, kw):
    """Prefill, then decode through `RecurrentSlots` AND `LatentPages`
    over several pages, in one net."""
    prompt = _ids(t0, seed=t0)
    out, st = _served(model[3], prompt, n, **kw)
    _assert_served_equals_reference(model, prompt, out)
    # four blocks keep a slot's state, two a pool of latent pages: a
    # position costs 16 + 8 float32 numbers in each of the two
    assert (st["recurrent_blocks"], st["latent_blocks"], st["kv_blocks"],
            st["stateless_blocks"]) == (4, 2, 0, 0)
    assert st["latent_bytes_per_token"] == 2 * 24 * 4
    # 4 heads of 8 x 8 in float32 and three taps of 96 columns
    assert st["state_bytes_per_slot"] == 4 * (4 * 8 * 8 * 4 + 3 * 96 * 4)
    # n - 1 decode steps, 1 live slot, top-3 in each of the 4 routed
    # blocks; every expert is held, so every row is local
    assert st["moe_routed"] == (n - 1) * 3 * 4
    assert st["moe_held_choices"] == st["moe_routed"]
    assert st["moe_rows_local"] == (n - 1) * 4
    assert st["moe_experts_held"] == 4 * 16


def test_one_group_held_is_the_references_partial_sum(model):
    """Group 1 of 4 held (experts 4-7 of 16): the engine serves the
    reference's partial sum with the shared part whole, and its counters
    see the share: a row is local where group 1 is among its two."""
    part = _build(_config(num_experts=4, deployment=dict(
        num_experts_published=16, experts_held_first=4)))
    prompt = _ids(14, seed=3)
    out, st = _served(part[3], prompt, 25)
    _assert_served_equals_reference(part, prompt, out)
    rows = 24 * 4
    assert st["moe_routed"] == rows * 3
    assert 0 < st["moe_rows_local"] < rows
    assert st["moe_rows_local"] <= st["moe_held_choices"] \
        <= 3 * st["moe_rows_local"]
    assert st["moe_experts_held"] == 4 * 4
    # and it is another function than the whole layer's
    whole, _ = _served(model[3], prompt, 25)
    assert max(abs(a["logprob"] - b["logprob"]) for a, b in
               zip(out["logprobs"], whole["logprobs"])) > 1e-3


def test_slots_admitted_and_retired_out_of_order_and_recycled(model):
    """Five requests of different lengths over three slots: they retire
    out of order, the two that wait take over recycled slots whose
    recurrent state is reset and whose latent pages are bound anew, and
    every one is the reference's."""
    shapes = ((7, 21), (19, 6), (33, 11), (12, 9), (26, 14))
    prompts = [_ids(n, seed=20 + n) for n, _ in shapes]
    eng = DecodeEngine(model[3], **ENGINE)
    try:
        kinds = [len(c) for c in eng._caches]
        assert kinds == [2, 2, 1, 2, 2, 1]       # (state, tail) / (pool,)
        assert eng._caches[0][0].shape == (3, 8, 32)
        assert eng._caches[2][0].shape == (eng.pool_pages + 1, 24, 8)
        reqs = [eng.submit(p, m, logprobs=4)
                for p, (_, m) in zip(prompts, shapes)]
        for r, p in zip(reqs, prompts):
            toks = r.result(timeout=180.0)
            _assert_served_equals_reference(
                model, p, {"tokens": toks, "logprobs": r.logprob_values})
        st = eng.stats()
        assert st["completed"] == 5 if "completed" in st else True
        loop = st["loop"]
        assert loop["overshoot_tokens"] == 0
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_a_recycled_slots_state_is_reset(model):
    """One slot, three requests in a row: each starts from zeros, not
    from what the slot's last tenant left in its matrix state, its
    convolution tail or its pages."""
    eng = DecodeEngine(model[3], **dict(ENGINE, n_slots=1))
    try:
        for n, m in ((21, 9), (5, 13), (30, 7)):
            prompt = _ids(n, seed=40 + n)
            out = eng.generate(prompt, m, logprobs=4)
            _assert_served_equals_reference(model, prompt, out)
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_a_router_that_ignores_the_groups_misses_the_tolerance(
        model, monkeypatch):
    """Top-3 among all 16 experts, the groups forgotten, is another
    function: the served logprobs leave the reference's."""
    monkeypatch.setattr(experts, "group_limited",
                        lambda scores, *a, **kw: scores)
    prompt = _ids(11, seed=11)
    out, _ = _served(model[3], prompt, 21)
    toks = np.asarray(out["tokens"])
    want = _ref_logp(model, np.concatenate([prompt, toks]),
                     np.arange(10, 10 + 21))
    off = max(abs(e["logprob"] - want[j, toks[j]])
              for j, e in enumerate(out["logprobs"]))
    assert off > 10 * 5e-5


def test_bfloat16_in_float32s_place_misses_the_tolerance(model):
    *_, net = _build(_config(), compute_dtype=jnp.bfloat16)
    prompt = _ids(11, seed=11)
    out, _ = _served(net, prompt, 13)
    toks = np.asarray(out["tokens"])
    want = _ref_logp(model, np.concatenate([prompt, toks]),
                     np.arange(10, 10 + 13))
    off = max(abs(e["logprob"] - want[j, toks[j]])
              for j, e in enumerate(out["logprobs"]))
    assert off > 10 * 5e-5


def test_a_batch_served_through_the_kernels(model, monkeypatch):
    """Three requests of different lengths with the four kernels a TPU
    would dispatch (interpreted): the delta step with a decay a channel,
    the paged latent attention, the latent's in-place write and the
    grouped expert product. They serve the XLA forms' tokens and
    logprobs."""
    from deeplearning4j_tpu.ops import pallas_delta_step as pds
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    def batch():
        eng = DecodeEngine(model[3], **ENGINE)
        try:
            reqs = [eng.submit(_ids(n, seed=20 + n), m, logprobs=4)
                    for n, m in ((7, 5), (19, 14), (33, 9))]
            toks = [list(r.result(timeout=180.0)) for r in reqs]
            return toks, [[e["logprob"] for e in r.logprob_values]
                          for r in reqs]
        finally:
            eng.shutdown(drain_timeout=30.0)

    want_toks, want_lps = batch()
    calls = {"step": 0, "attend": 0, "write": 0, "experts": 0}

    def counted(name, fn):
        def run(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw, interpret=True)
        return run

    # the toy's 8 x 8 heads lie off the tile grid the dispatch admits;
    # interpreted, the kernel's arithmetic runs at any size
    monkeypatch.setattr(pds, "_group", lambda dv: 1)
    monkeypatch.setattr(pds, "delta_step_or_none",
                        counted("step", pds.kda_step))
    monkeypatch.setattr(mla, "mla_attend_or_none",
                        counted("attend", mla.mla_attend))
    monkeypatch.setattr(mla, "latent_write_or_none",
                        counted("write", mla.latent_write))
    monkeypatch.setattr(
        pme, "moe_experts_or_none",
        lambda x, gates, Wg, Wu, Wd, hit, act=pme.GATED_SILU:
        counted("experts", pme.moe_experts)(x, gates, Wg, Wu, Wd, hit,
                                            act=act))
    toks, lps = batch()
    assert toks == want_toks
    for got, want in zip(lps, want_lps):
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert min(calls.values()) > 0


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative": {"draft": "self", "k": 2}}, "speculative"),
    ({"parallel": {"tp": 2}}, "tp"),
    ({"quantize": {"kv": "int8"}}, "int8"),
    ({"role": "prefill"}, "role"),
], ids=["prefix-cache", "speculative", "tensor-parallel", "int8-kv",
        "prefill-role"])
def test_features_that_cannot_hold_either_kind_are_refused(model, kw, what):
    with pytest.raises(RecurrentStateUnsupported, match=what) as e:
        DecodeEngine(model[3], n_slots=2, max_len=32, page_size=8, **kw)
    if what in ("prefix_cache", "int8", "role"):
        # both kinds say what of theirs cannot be held
        assert "the recurrent state" in str(e.value)
        assert "latent pages" in str(e.value)


@pytest.mark.parametrize("call", [
    lambda eng: eng.export_prefix([1, 2, 3]),
    lambda eng: eng.migrate_slots(),
    lambda eng: eng.resume_generate({}),
], ids=["export-prefix", "migrate", "resume"])
def test_kv_moving_calls_are_refused_on_both_kinds(model, call):
    eng = DecodeEngine(model[3], n_slots=2, max_len=32, page_size=8)
    try:
        with pytest.raises(RecurrentStateUnsupported):
            call(eng)
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_refused_merges_both_kinds_refusals(model):
    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.serving import block_state

    plan = GPTPlan(model[3])
    assert plan.state_kinds() == ["recurrent", "recurrent", "latent"] * 2
    said = block_state.refused(plan, {"prefix_cache": "no {what} in a "
                                                      "prefix"})
    assert said == ["no the recurrent state in a prefix",
                    "no latent pages in a prefix"]
    assert block_state.refused(plan, {}) == []


def test_generate_refuses_a_composed_network(model):
    from deeplearning4j_tpu.models.transformer import generate

    with pytest.raises(ValueError, match="DecodeEngine"):
        generate(model[3], _ids(4), 2)


# ------------------------------------------------------ the configuration
def test_the_configuration_file_keeps_every_published_width():
    cfg = json.loads(CONFIG.read_text())
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    cut = {"num_hidden_layers", "num_experts", "vocab_size"}
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text()
                   .splitlines() if '"name": "Ling-3.0-flash"' in line)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in cut or cfg[key] == value, key
        dep = cfg["deployment"]
        for key in cut:
            assert dep[key + "_published"] == row["config"][key]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"]) == (8, 4)
    assert dep["experts_held_first"] == 0 and "1/8" in dep["tokens_per_expert"]
    for said in ("layer_rule", "kda", "safe_gate", "mla", "router", "mtp",
                 "weights"):
        assert said in cfg["assumed"], said
    sz = fam.sizes(cfg)
    assert (sz["d"], sz["H"], sz["kr"], sz["nope"], sz["rope"], sz["vd"],
            sz["theta"]) == (2560, 32, 512, 128, 64, 128, 6e6)
    assert (sz["lh"], sz["lk"], sz["lv"], sz["conv"], sz["gate_lower"]) \
        == (32, 128, 128, 4, -5.0)
    assert (sz["ffn"], sz["f"], sz["shared"]) == (6144, 768, 768)
    assert (sz["L"], sz["L_dense"], sz["L_moe"], sz["mla_sub_layers"],
            sz["period"], sz["V"]) == (12, 2, 10, 2, 6, 19648)
    assert (sz["E"], sz["held"], sz["groups"], sz["topk_groups"], sz["topk"],
            sz["route_scale"]) == (512, (0, 64), 8, 4, 8, 2.5)
    assert sz["layer_types"] == ((ref.LINEAR,) * 5 + (ref.FULL,)) * 2
    assert ref.consts_from_config(cfg).layer_types == sz["layer_types"]
    net = fam.build_net(sz, training=False)
    mixers = [net.layers[1 + i].mixer for i in range(12)]
    assert [m.state for m in mixers] == (["recurrent"] * 5 + ["latent"]) * 2
    assert abs(mixers[5].sm_scale - 192 ** -0.5) < 1e-9
    assert (mixers[5].q_rank, mixers[5].head_gate) == (None, True)
    assert isinstance(mixers[0], ChannelGatedDeltaMixer)
    shapes = fam._leaf_shapes(sz)
    assert shapes["router"] == (2560, 512) and shapes["rb"] == (512,)
    assert shapes["eWg"] == (64, 2560, 768)
    assert shapes["Win"] == (2560, 20512) and shapes["conv"] == (12288, 4)
    assert (shapes["Wqn"], shapes["Wqr"], shapes["Wa"]) \
        == ((2560, 4096), (2560, 2048), (2560, 32))
    # 4,737 M parameters, as the issue reckons them
    count = lambda names: sum(int(np.prod(shapes[k])) for k in names)
    n = count(fam.TOP_LEAVES) + sum(count(fam.layer_leaves(sz, i))
                                    for i in range(12))
    assert abs(n - 4.737e9) < 5e6
    # a KDA layer's mixer 63.06 M, an MLA layer's 31.97 M
    assert abs(count(fam._KDA[1:-1]) - 63.06e6) < 2e4
    assert abs(count(fam._MLA[1:-1]) - 31.97e6) < 2e4


@pytest.mark.parametrize("over,what", [
    ({"num_experts": 17}, "outside the router"),
    ({"topk_method": "greedy"}, "noaux_tc"),
    ({"use_qkv_bias": True}, "bias-free"),
    ({"q_lora_rank": 1536}, "full-rank queries"),
    ({"kda_safe_gate": False}, "safe"),
    ({"expert_swiglu_limit_list": [0, 0, 4, 0, 0, 0]}, "clamps"),
], ids=["held-past-router", "topk-method", "qkv-bias", "query-rank",
        "unsafe-gate", "clamped-swiglu"])
def test_the_family_refuses_what_it_does_not_run(over, what):
    with pytest.raises(ValueError, match=what):
        fam.sizes(_config(**over))


def test_the_step_bench_rehearses_in_interpret_mode(tmp_path, capsys):
    """`tools/kda_step_bench.py` end to end at toy shapes, so that a chip
    call is not lost to a typo: a row a form and slot count, each within
    rounding of `delta_step`, and no time printed as a device's (the
    reduction of the traced stretch is held to a recorded device's
    events below)."""
    from tools import kda_step_bench as bench

    out = tmp_path / "bench.json"
    assert bench.main(
        ["--slots", "3,2", "--heads", "2", "--key-dim", "8", "--value-dim",
         "128", "--dtype", "float32", "--calls", "2", "--iters", "1",
         "--live", "0.67", "--xla", "--interpret", "--out", str(out)]) == 0
    table = json.loads(out.read_text())
    assert [(r["form"], r["slots"]) for r in table["rows"]] == [
        ("kda_step", 3), ("delta_step", 3), ("kda_step", 2),
        ("delta_step", 2)]
    assert all(r["gap_o"] < 1e-5 and r["gap_state"] < 1e-5
               for r in table["rows"])
    # the traced stretch ran too (the kernel's own time beside the
    # entry's on a chip) and found no device to read
    assert not any(key in r for r in table["rows"] for key in (
        "call_ms", "roofline_pct", "device_ms", "kernel_ms",
        "entry_not_kernel_pct"))
    assert not (tmp_path / ".kda_step_trace").exists()
    assert len(capsys.readouterr().out.splitlines()) == 4


def test_the_step_bench_reads_the_kernel_beside_its_entry():
    """`device_times` on a hand-made device trace of two calls: each a
    transposing fusion of 250 us and the kernel's 750 us, so a quarter
    of the entry is not the kernel; a form without the kernel has its
    device time alone, a trace without a device nothing."""
    from perfbench.harness import trace_reduce as tr
    from tools import kda_step_bench as bench

    def ev(name, start, dur, plane="/device:TPU:0", line=tr.OPS_LINE):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    kernel = ('%kda_step.{n} = (f32[128,32,128]{{2,1,0}}, '
              'f32[128,128,4096]{{2,1,0}}) custom-call(f32[128,32,128] %k), '
              'custom_call_target="tpu_custom_call"')
    events = []
    for n in range(2):
        events.append(ev(f"%fusion.{n} = f32[128,128,32] fusion(f32[8] %x)",
                         2_000_000 * n, 250_000))
        events.append(ev(kernel.format(n=n), 2_000_000 * n + 250_000,
                         750_000))
    got = bench.device_times(tr.TraceView(events), 2)
    assert got == pytest.approx({"device_ms": 1.0, "kernel_ms": 0.75,
                                 "entry_not_kernel_pct": 25.0})
    xla = [e for e in events if "kda_step" not in e["name"]]
    assert bench.device_times(tr.TraceView(xla), 2) \
        == pytest.approx({"device_ms": 0.25})
    host = [ev("$a.py:1 f", 0, 10, plane="/host:CPU", line="python3")]
    assert bench.device_times(tr.TraceView(host), 2) == {}


def test_nothing_in_the_program_branches_on_the_models_name():
    import re

    named = re.compile(r"ling[-_ .]?(3|flash)|bailing|kimi", re.I)
    hits = []
    for path in (REPO / "deeplearning4j_tpu").rglob("*.py"):
        for n, line in enumerate(path.read_text().splitlines(), 1):
            if named.search(line) and "ling_flash_configuration" not in line \
                    and not _in_docstring_or_comment(path, n):
                hits.append(f"{path.name}:{n}")
    assert not hits, hits


def _in_docstring_or_comment(path, lineno) -> bool:
    """Whether line `lineno` of a module is a comment or lies inside a
    string literal (a docstring)."""
    import ast

    line = path.read_text().splitlines()[lineno - 1]
    if line.lstrip().startswith("#"):
        return True
    tree = ast.parse(path.read_text())
    return any(isinstance(node, ast.Constant) and isinstance(node.value, str)
               and node.lineno <= lineno <= node.end_lineno
               for node in ast.walk(tree))
