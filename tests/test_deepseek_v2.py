"""Latent attention under YaRN and device-limited routing (`deepseek_v2`)
against the plain reference the benchmark keeps
(`perfbench/families/deepseek_v2_reference.py`: expanded attention a head
and a block of queries at a time, the group rule, a loop over the experts
held) on seeded weights at a small size: YaRN against a transcription of
the published class, the group rule against one, the eight shares of a
layer's experts, the mixer's three forms and its blocked prefill, the
kernel at 128 heads, one layer, the network's forward, and the decode
engine's prefill and decode through one pool of latent pages a layer."""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.decoder_block import (
    DecoderBlock,
    GatedMLP,
    LatentAttentionMixer,
    MoEFeedForward,
    YarnScaling,
    kind_from_json,
    sub,
)
from deeplearning4j_tpu.ops import pallas_mla_attend as mla
from deeplearning4j_tpu.ops import rope as rope_ops
from deeplearning4j_tpu.parallel import experts
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
from perfbench.families import deepseek_v2 as fam
from perfbench.families import deepseek_v2_reference as ref

REPO = Path(__file__).resolve().parents[1]
CONFIG = REPO / "perfbench/configs/deepseek-v2.json"
V, L = 97, 3
# the toy's YaRN: trained on 16 positions, stretched 4 times
TOY_YARN = dict(type="yarn", factor=4, original_max_position_embeddings=16,
                beta_fast=4, beta_slow=1, mscale=0.707, mscale_all_dim=0.707)


def _config(**over) -> dict:
    """The benchmark's configuration file, cut to a toy: d 64, a dense
    layer and 2 routed ones, 4 heads over a query latent of 24 and a
    key/value latent of 16 (8 nope + 8 rope, values 8), a dense FFN 48
    wide, 16 experts 24 wide in 4 groups of which a token reaches 2,
    top-3, 2 shared experts, every expert held; YaRN from 16
    positions."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(hidden_size=64, num_hidden_layers=L, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
               intermediate_size=48, moe_intermediate_size=24,
               n_routed_experts=16, n_group=4, topk_group=2,
               num_experts_per_tok=3, vocab_size=V, rope_scaling=TOY_YARN)
    cfg["deployment"] = dict(n_routed_experts_published=16,
                             experts_held_first=0)
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


# ------------------------------------------------------------------- YaRN
def _published_yarn(dim, base, factor, original_max, beta_fast, beta_slow,
                    mscale, mscale_all_dim, positions):
    """`DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache`, transcribed to
    NumPy: (inv_freq, cos, sin) with cos and sin (positions, dim / 2),
    one column a pair."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original_max / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    freq_inter = 1.0 / (factor * base ** (
        np.arange(0, dim, 2, dtype=np.float32) / dim))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    freqs = np.outer(np.asarray(positions, np.float32),
                     inv_freq.astype(np.float32))
    m = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    return inv_freq, np.cos(freqs) * m, np.sin(freqs) * m


PUBLISHED = dict(dim=64, base=10000.0, factor=40.0, original_max=4096,
                 beta_fast=32.0, beta_slow=1.0)


def test_yarn_equals_the_published_class_at_the_published_numbers():
    pos = np.arange(0, 8193)
    inv, cos, sin = _published_yarn(**PUBLISHED, mscale=0.707,
                                    mscale_all_dim=0.707, positions=pos)
    got = rope_ops.yarn_inv_freq(**PUBLISHED)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    # the ramp runs from pair 10 to pair 23, as the issue reckons them
    f = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 40.0, rtol=1e-6)
    assert np.all((got[11:23] < f[11:23]) & (got[11:23] > f[11:23] / 40))
    c, s = rope_ops.rope_angles(jnp.asarray(pos), 64, inv_freq=got)
    # float32 angles up to 8192 rad: cos and sin to a few 1e-4
    np.testing.assert_allclose(c, cos, atol=1e-3)
    np.testing.assert_allclose(s, sin, atol=1e-3)
    assert abs(rope_ops.yarn_mscale(40.0, 0.707) - 1.260805) < 1e-5
    assert rope_ops.yarn_mscale(1.0, 0.707) == 1.0


def test_the_mixers_softmax_scale_follows_the_scaling():
    kind = YarnScaling(factor=40.0, original_max=4096, beta_fast=32.0,
                       beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
    mixer = LatentAttentionMixer(n_heads=128, q_rank=1536, kv_rank=512,
                                 nope_dim=128, rope_dim=64, v_dim=128,
                                 rope_scaling=kind)
    assert abs(mixer.sm_scale - 0.114722) < 1e-6
    assert kind.table_scale == 1.0
    plain = dataclasses.replace(mixer, rope_scaling=None)
    assert abs(plain.sm_scale - 192 ** -0.5) < 1e-12
    # mscale_all_dim 0: the temperature is in the tables, not the scale
    tables = dataclasses.replace(kind, mscale=1.0, mscale_all_dim=0.0)
    assert tables.softmax_scale == 1.0
    assert abs(tables.table_scale - (0.1 * math.log(40.0) + 1.0)) < 1e-12


def test_factor_one_is_plain_rotary():
    got = rope_ops.yarn_inv_freq(64, 10000.0, 1.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(
        got, 10000.0 ** (-np.arange(32, dtype=np.float32) / 32), rtol=1e-6)
    pos = jnp.arange(0, 8193, 37)
    want = rope_ops.rope_angles(pos, 64, 10000.0)
    have = rope_ops.rope_angles(pos, 64, inv_freq=got)
    np.testing.assert_allclose(have[0], want[0], atol=1e-3)
    np.testing.assert_allclose(have[1], want[1], atol=1e-3)


def test_the_scaling_kind_round_trips_inside_the_mixer():
    kind = LatentAttentionMixer(
        n_heads=128, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
        v_dim=128, rope_scaling=YarnScaling(
            factor=40.0, original_max=4096, beta_fast=32.0, beta_slow=1.0,
            mscale=0.707, mscale_all_dim=0.707))
    d = json.loads(json.dumps(kind.to_json()))
    assert d["rope_scaling"]["kind"] == "yarn"
    assert kind_from_json(d) == kind
    plain = LatentAttentionMixer()
    assert plain.to_json()["rope_scaling"] is None
    assert kind_from_json(plain.to_json()) == plain
    # a mixer's JSON from before the field reads as no scaling
    old = {k: v for k, v in plain.to_json().items() if k != "rope_scaling"}
    assert kind_from_json(old) == plain


# ---------------------------------------------------------- the group rule
def _published_gate(logits, n_group, topk_group, top_k, scale):
    """`MoEGate.forward` with `group_limited_greedy`, softmax scores and
    `norm_topk_prob` false, transcribed to NumPy: (N, E) gates."""
    z = logits.astype(np.float64)
    scores = np.exp(z - z.max(1, keepdims=True))
    scores /= scores.sum(1, keepdims=True)
    N, E = scores.shape
    group_scores = scores.reshape(N, n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, axis=1)[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1.0, axis=1)
    score_mask = np.repeat(group_mask, E // n_group, axis=1).astype(bool)
    tmp = np.where(score_mask, scores, 0.0)
    topk_idx = np.argsort(-tmp, axis=1)[:, :top_k]
    gates = np.zeros_like(scores)
    np.put_along_axis(gates, topk_idx,
                      np.take_along_axis(tmp, topk_idx, 1) * scale, axis=1)
    return gates


def _logits(n=64, e=160, seed=2):
    return 1.4 * jax.random.normal(jax.random.PRNGKey(seed), (n, e))


def test_the_group_rule_equals_the_published_gate():
    lg = _logits()
    got = np.asarray(experts.routed_gates(
        lg, 6, bias=jnp.zeros(160), scale=16.0, scoring="softmax_all",
        n_groups=8, topk_groups=3))
    want = _published_gate(np.asarray(lg), 8, 3, 6, 16.0)
    assert np.array_equal(got != 0, want != 0)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the rule changes the choice: some row's plain top-6 reach a fourth
    # group
    plain = np.asarray(experts.routed_gates(
        lg, 6, bias=jnp.zeros(160), scale=16.0, scoring="softmax_all"))
    assert np.any((plain != 0) != (got != 0))


def test_a_rows_gates_lie_in_at_most_topk_groups_groups():
    g = np.asarray(experts.routed_gates(
        _logits(seed=3), 6, bias=jnp.zeros(160), scale=16.0,
        scoring="softmax_all", n_groups=8, topk_groups=3)) != 0
    assert np.all(g.sum(1) == 6)
    groups = g.reshape(len(g), 8, 20).any(-1).sum(1)
    assert groups.max() <= 3 and groups.min() >= 1
    # a group's score is its LARGEST score: each row's best expert of all
    # is always among the chosen
    best = np.argmax(np.asarray(_logits(seed=3)), axis=1)
    assert np.all(g[np.arange(len(g)), best])


def test_one_group_is_softmax_all():
    lg, kw = _logits(), dict(bias=jnp.zeros(160), scale=16.0,
                             scoring="softmax_all")
    one = experts.routed_gates(lg, 6, n_groups=1, topk_groups=1, **kw)
    np.testing.assert_array_equal(one, experts.routed_gates(lg, 6, **kw))
    every = experts.routed_gates(lg, 6, n_groups=8, topk_groups=8, **kw)
    np.testing.assert_array_equal(every, one)
    fn = lambda n: str(jax.make_jaxpr(lambda x: experts.routed_gates(
        x, 6, n_groups=n, topk_groups=n, **kw))(lg))
    assert "moe.groups" not in fn(1) and fn(1) == str(jax.make_jaxpr(
        lambda x: experts.routed_gates(x, 6, **kw))(lg))


@pytest.mark.parametrize("kw,what", [
    (dict(scoring="sigmoid", n_groups=3, topk_groups=2), "equal"),
    (dict(scoring="softmax", n_groups=4, topk_groups=2), "largest"),
    (dict(scoring="softmax_all", n_zero_experts=4, n_groups=4,
          topk_groups=2), "real experts"),
    (dict(scoring="softmax_all", n_groups=3, topk_groups=2), "equal"),
    (dict(scoring="softmax_all", n_groups=4, topk_groups=5), "chosen"),
], ids=["sigmoid", "softmax", "zero-experts", "unequal-groups",
        "too-many-groups"])
def test_groups_the_rule_is_not_written_for_are_refused(kw, what):
    with pytest.raises(ValueError, match=what):
        MoEFeedForward(n_experts=16, top_k=3, **kw)


def test_the_routed_kind_round_trips_through_json():
    kind = MoEFeedForward(n_experts=160, top_k=6, expert_width=1536,
                          shared_width=3072, experts_held=(0, 20),
                          scoring="softmax_all", routed_scale=16.0,
                          n_groups=8, topk_groups=3)
    d = json.loads(json.dumps(kind.to_json()))
    assert (d["n_groups"], d["topk_groups"]) == (8, 3)
    assert kind_from_json(d) == kind
    p = kind.init_params(jax.random.PRNGKey(0), 32, jnp.float32,
                         lambda k, s, fi, fo: jnp.zeros(s))
    # the router keeps its 160 outputs; one group's experts are held
    assert p["router"].shape == (32, 160) and p["Wg"].shape == (20, 32, 1536)
    assert p["sWg"].shape == (32, 3072)
    assert (MoEFeedForward().n_groups, MoEFeedForward().topk_groups) == (1, 1)


# --------------------------------------------------------- the eight shares
def _moe_args(seed=4, n=40, d=64, f=24, n_experts=32, shared=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    mk = lambda key, shape, s: jax.random.normal(key, shape) / s
    return dict(x=mk(k[0], (n, d), 1), router=mk(k[1], (d, n_experts), 4),
                eWg=mk(k[2], (n_experts, d, f), 8),
                eWu=mk(k[3], (n_experts, d, f), 8),
                eWd=mk(k[4], (n_experts, f, d), 5),
                sWg=mk(k[5], (d, shared), 8), sWu=mk(k[6], (d, shared), 8),
                sWd=mk(k[7], (shared, d), 4))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """32 experts in 8 groups of 4, a token reaching 3 groups: held one
    group at a time, the eight chips' routed parts plus the shared MLP
    ONCE are the uncut reference's feed-forward, and every chip computes
    the same shared part."""
    a = _moe_args()
    x = a["x"]
    c = ref.Consts(q_rank=1, kv_rank=1, nope=1, rope=2, v_dim=1,
                   rope_theta=1.0, yarn=None, n_experts=32, n_groups=8,
                   topk_groups=3, top_k=6, routed_scale=16.0, held_first=0)
    with jax.default_matmul_precision("highest"):
        want = ref.routed(a, x, c, precision="float32") \
            + ref.ffn(x, a["sWg"], a["sWu"], a["sWd"], precision="float32")
    parts, shared, local = [], None, []
    for first in range(0, 32, 4):
        held = slice(first, first + 4)
        kind = MoEFeedForward(n_experts=32, top_k=6, expert_width=24,
                              shared_width=16, experts_held=(first, 4),
                              scoring="softmax_all", routed_scale=16.0,
                              n_groups=8, topk_groups=3)
        p = {"router": a["router"], "router_b": jnp.zeros(32),
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
    # a row reaches exactly 3 of the 8 chips unless a kept group goes
    # unchosen; never more
    assert 2.0 * len(x) < sum(local) <= 3 * len(x)
    # one share alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0] + shared - want))) > 0.05


# ------------------------------------------------------------- the mixer
D, T = 48, 37
MIXER = LatentAttentionMixer(
    n_heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
    rope_theta=1e4, eps=1e-6, rope_scaling=YarnScaling(
        factor=4.0, original_max=16, beta_fast=4.0, beta_slow=1.0,
        mscale=0.707, mscale_all_dim=0.707))
CONSTS = ref.Consts(q_rank=24, kv_rank=16, nope=8, rope=8, v_dim=8,
                    rope_theta=1e4, yarn=(4.0, 16, 4.0, 1.0, 0.707, 0.707),
                    n_experts=0, n_groups=1, topk_groups=1, top_k=0,
                    routed_scale=1.0, held_first=0)


def _params(mixer=MIXER, seed=0):
    p = mixer.init_params(
        jax.random.PRNGKey(seed), D, jnp.float32,
        lambda k, shape, fi, fo: jax.random.normal(k, shape) / fi ** 0.5)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    p["qn_w"] = 1.0 + 0.1 * jax.random.normal(k1, p["qn_w"].shape)
    p["kvn_w"] = 1.0 + 0.1 * jax.random.normal(k2, p["kvn_w"].shape)
    return p


def _x(seed=3, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, D))


def _reference(p, x, c=CONSTS):
    names = {"Wqa": "Wqa", "qn_w": "qn", "Wqn": "Wqn", "Wqr": "Wqr",
             "Wkvc": "Wkvc", "Wkr": "Wkr", "kvn_w": "kvn", "Wkb": "Wkb",
             "Wvb": "Wvb", "Wo": "Wo"}
    with jax.default_matmul_precision("highest"):
        return ref.mla({names[k]: v for k, v in p.items()}, x[0],
                       jnp.arange(x.shape[1]), c, n_heads=MIXER.n_heads,
                       eps=MIXER.eps, precision="float32")


def test_the_expanded_forward_under_yarn_equals_the_reference():
    p, x = _params(), _x()
    np.testing.assert_allclose(MIXER.forward(p, x)[0], _reference(p, x),
                               atol=2e-5)


@pytest.mark.parametrize("broken", [
    dataclasses.replace(MIXER, rope_scaling=None),
    dataclasses.replace(MIXER, rope_scaling=dataclasses.replace(
        MIXER.rope_scaling, mscale_all_dim=0.0)),
    dataclasses.replace(MIXER, rope_scaling=dataclasses.replace(
        MIXER.rope_scaling, factor=2.0)),
], ids=["no-scaling", "no-softmax-temperature", "another-factor"])
def test_the_scaling_is_in_the_arithmetic(broken):
    """Past the toy's 16 trained positions the blended frequencies and
    the softmax temperature both show: without either the forward leaves
    the reference by far more than the tolerance."""
    p, x = _params(), _x()
    off = jnp.max(jnp.abs(broken.forward(p, x)[0] - _reference(p, x)))
    assert float(off) > 50 * 2e-5


def test_the_three_forms_agree_under_yarn():
    """The whole sequence expanded; its second half as a chunk of
    absorbed queries against the cached latents; its last position as
    the absorbed one-token step; positions run past `original_max`."""
    p, x = _params(), _x()
    want = MIXER.forward(p, x)[0]
    pos = jnp.arange(T)
    q_n, q_r, latent = MIXER.project(p, x, pos)
    q_abs = MIXER.absorb(p, q_n[:, 20:], q_r[:, 20:])
    got = MIXER.out(p, MIXER.attend_latents(q_abs, latent, pos[None, 20:]))
    np.testing.assert_allclose(got[0], want[20:], atol=2e-5)
    q_n1, q_r1, lat1 = MIXER.project(p, x[:, -1:], pos[None, -1:])
    np.testing.assert_allclose(lat1[0, 0], latent[0, -1], atol=1e-6)
    step = MIXER.out(p, MIXER.attend_latents(
        MIXER.absorb(p, q_n1, q_r1), latent, pos[None, -1:]))
    np.testing.assert_allclose(step[0, 0], want[-1], atol=2e-5)
    # gather-and-attend, the kernel's XLA form, at the mixer's scale
    pool = jnp.zeros((7, 24, 8)).at[1:6].set(
        jnp.swapaxes(jnp.pad(latent[0], ((0, 3), (0, 0)))
                     .reshape(5, 8, 24), 1, 2))
    att = mla.mla_attend_xla(
        MIXER.absorb(p, q_n1, q_r1)[:, 0], pool,
        jnp.asarray([[1, 2, 3, 4, 5, 0]]), jnp.asarray([T - 1]),
        kv_rank=16, sm_scale=MIXER.sm_scale)
    np.testing.assert_allclose(MIXER.out(p, att)[0], want[-1], atol=2e-5)


@pytest.mark.parametrize("t", [256, 300, 513])
def test_the_blocked_prefill_equals_the_unblocked_one(t, monkeypatch):
    """Blocks of 128 queries against the keys up to each block's end:
    the (H, T, T) array is never made and the outputs are the whole
    form's; the last block of 300 or 513 positions is ragged."""
    from deeplearning4j_tpu.nn.conf import decoder_block as db

    p, x = _params(), _x(t=t)
    q_n, q_r, latent = MIXER.project(p, x, jnp.arange(t))
    want = MIXER.attend_expanded(p, q_n, q_r, latent)
    assert MIXER.query_block(t) == t
    monkeypatch.setattr(db, "_SCORE_BYTES", 4 * MIXER.n_heads * t * 128)
    assert MIXER.query_block(t) == 128
    got = MIXER.attend_expanded(p, q_n, q_r, latent)
    np.testing.assert_allclose(got, want, atol=2e-6)
    hlo = jax.jit(lambda *a: MIXER.attend_expanded(p, *a)) \
        .lower(q_n, q_r, latent).as_text()
    assert f"x{t}x{t}x" not in hlo.replace(" ", "")


@pytest.mark.parametrize("n_valid", [None, 700])
def test_the_prefill_kernel_serves_the_blocked_prefill(n_valid, monkeypatch):
    """Where the prefill kernel serves (interpreted here), a prompt too
    long for one array goes through it, heads first at their own widths,
    the one rope key a position not broadcast, the mixer's YaRN scale
    its argument: the outputs are the blocks' and the whole form's up to
    `n_valid`, and zeros from the first block past it."""
    from deeplearning4j_tpu.nn.conf import decoder_block as db

    t, calls = 3 * mla.PREFILL_BLOCK, []
    p, x = _params(), _x(t=t)
    q_n, q_r, latent = MIXER.project(p, x, jnp.arange(t))
    want = MIXER.attend_expanded(p, q_n, q_r, latent)

    def served(q_n, q_r, k_n, k_r, v, n, *, sm_scale):
        calls.append((q_n.shape, q_r.shape, k_r.shape, v.shape, sm_scale))
        return mla.mla_prefill(q_n, q_r, k_n, k_r, v, n, sm_scale=sm_scale,
                               interpret=True)

    monkeypatch.setattr(db, "_SCORE_BYTES", 4 * MIXER.n_heads * t * 128)
    monkeypatch.setattr(mla, "mla_prefill_or_none", served)
    got = MIXER.attend_expanded(p, q_n, q_r, latent, n_valid=n_valid)
    assert calls == [((4, t, 8), (4, t, 8), (t, 8), (4, t, 8),
                      MIXER.sm_scale)]
    live = t if n_valid is None else n_valid
    np.testing.assert_allclose(got[:, :live], want[:, :live], atol=5e-6)
    if n_valid is not None:
        # the third block is padding: attended to nothing, only `Wo`'s zero
        assert not np.any(np.asarray(got[:, 2 * mla.PREFILL_BLOCK:]))
    # a prompt that goes whole never asks for the kernel
    monkeypatch.setattr(db, "_SCORE_BYTES", 1 << 29)
    MIXER.attend_expanded(p, q_n, q_r, latent)
    assert len(calls) == 1


def test_the_prefill_kernel_equals_one_array_of_scores():
    rng = np.random.default_rng(0)
    H, T = 2, 2 * mla.PREFILL_BLOCK
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape) / 3,
                                    jnp.float32)
    args = (mk(H, T, 16), mk(H, T, 8), mk(H, T, 16), mk(T, 8), mk(H, T, 24))
    want = mla.mla_prefill_xla(*args, sm_scale=0.2)
    got = mla.mla_prefill(*args, jnp.asarray([T], jnp.int32), sm_scale=0.2,
                          interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_block_follows_heads_and_length():
    big = dataclasses.replace(MIXER, n_heads=128)
    # 128 heads: (128, T, T) float32 fits 512 MiB up to 1,024 positions
    assert [big.query_block(t) for t in (256, 1024, 2048, 4096)] \
        == [256, 1024, 512, 256]
    # 64 heads at the buckets another configuration lowers: unblocked
    half = dataclasses.replace(MIXER, n_heads=64)
    assert [half.query_block(t) for t in (256, 512, 1024)] \
        == [256, 512, 1024]
    assert half.query_block(4096) == 512


def test_the_attend_kernel_at_128_heads_equals_gather_and_attend():
    """H 128 at the kernel's block of pages (interpret mode): slots
    ending on a page's last position, the next page's first, past a whole
    block, and one inactive; dead table entries name a NaN page."""
    H, R, KV, PAGE = 128, 40, 32, 128
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
    kw = dict(kv_rank=KV, sm_scale=0.114722)
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
    assert len(blocks) == L and [b.state for b in blocks] == ["latent"] * L
    assert blocks[0].ffn == GatedMLP(width=48)
    assert all(isinstance(b.ffn, MoEFeedForward) for b in blocks[1:])
    ffn = blocks[1].ffn
    assert (ffn.n_groups, ffn.topk_groups, ffn.top_k, ffn.shared_width,
            ffn.scoring, ffn.routed_scale) == (4, 2, 3, 48, "softmax_all",
                                               16.0)
    mixer = blocks[2].mixer
    assert mixer.rope_scaling == YarnScaling(
        factor=4.0, original_max=16, beta_fast=4.0, beta_slow=1.0,
        mscale=0.707, mscale_all_dim=0.707)
    assert mixer == blocks[0].mixer


def test_layer_params_carry_the_programs_names(model):
    dense, routed = model[3]._params[1], model[3]._params[2]
    assert sorted(sub(dense, "ff_")) == ["Wd", "Wg", "Wu"]
    assert sorted(sub(routed, "ff_")) == sorted(
        ["router", "router_b", "Wg", "Wu", "Wd", "sWg", "sWu", "sWd"])
    assert routed["ff_router_b"].dtype == jnp.float32 \
        and not np.any(np.asarray(routed["ff_router_b"]))
    assert sorted(sub(dense, "mx_")) == sorted(sub(routed, "mx_"))


@pytest.mark.parametrize("i", range(L))
def test_one_layer_equals_the_reference_layer(model, i):
    sz, c, w, net = model
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 29, 64))
    got, _ = net.layers[1 + i].forward(net._params[1 + i], {}, x)
    want = ref.layer(w["layers"][i], x[0], c=c, n_heads=sz["H"],
                     eps=sz["eps"], precision="float32")
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
    (float32 on both sides: 5e-5 is summation order over d 64 and the
    absorbed against the expanded products; bfloat16 anywhere misses it
    by two orders). Logits, not tokens."""
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
    """Prefill, then decode through `LatentPages` over several pages and
    past the toy's `original_max` of 16 positions."""
    prompt = _ids(t0, seed=t0)
    out, st = _served(model[3], prompt, n, **kw)
    _assert_served_equals_reference(model, prompt, out)
    # one pool a layer: a position costs 16 + 8 float32 numbers in each
    assert st["latent_blocks"] == L
    assert st["latent_bytes_per_token"] == L * 24 * 4
    assert (st["kv_blocks"], st["recurrent_blocks"],
            st["stateless_blocks"]) == (0, 0, 0)
    # n - 1 decode steps, 1 live slot, top-3 in each of the 2 routed
    # blocks; every expert is held, so every row is local
    assert st["moe_routed"] == (n - 1) * 3 * (L - 1)
    assert st["moe_held_choices"] == st["moe_routed"]
    assert st["moe_rows_local"] == (n - 1) * (L - 1)
    assert st["moe_zero_choices"] == 0
    assert st["moe_experts_read"] == st["moe_experts_hit"]
    assert st["moe_experts_held"] == (L - 1) * 16


def test_one_group_held_is_the_references_partial_sum(model):
    """Group 1 of 4 held (experts 4-7 of 16): the engine serves the
    reference's partial sum with the shared part whole, and its counters
    see the share: a row is local where group 1 is among its two."""
    part = _build(_config(n_routed_experts=4, deployment=dict(
        n_routed_experts_published=16, experts_held_first=4)))
    prompt = _ids(14, seed=3)
    out, st = _served(part[3], prompt, 25)
    _assert_served_equals_reference(part, prompt, out)
    rows = 24 * (L - 1)
    assert st["moe_routed"] == rows * 3
    assert 0 < st["moe_rows_local"] < rows
    assert st["moe_rows_local"] <= st["moe_held_choices"] \
        <= 3 * st["moe_rows_local"]
    assert st["moe_experts_held"] == (L - 1) * 4
    # and it is another function than the whole layer's
    whole, _ = _served(model[3], prompt, 25)
    assert max(abs(a["logprob"] - b["logprob"]) for a, b in
               zip(out["logprobs"], whole["logprobs"])) > 1e-3


def test_a_router_that_ignores_the_groups_misses_the_tolerance(
        model, monkeypatch):
    """Top-3 among all 16 experts, the groups forgotten, is another
    function: the served logprobs leave the reference's."""
    monkeypatch.setattr(experts, "group_limited",
                        lambda scores, n_groups, topk_groups: scores)
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


def test_concurrent_requests_do_not_touch_each_others_pages(model):
    prompts = [_ids(n, seed=20 + n) for n in (7, 19, 33)]
    eng = DecodeEngine(model[3], **ENGINE)
    try:
        assert [len(c) for c in eng._caches] == [1] * L
        assert {c[0].shape for c in eng._caches} \
            == {(eng.pool_pages + 1, 24, 8)}
        reqs = [eng.submit(p, 11, logprobs=4) for p in prompts]
        for r, p in zip(reqs, prompts):
            toks = r.result(timeout=120.0)
            _assert_served_equals_reference(
                model, p, {"tokens": toks, "logprobs": r.logprob_values})
        loop = eng.stats()["loop"]
        assert loop["ahead_n"] > 0 and loop["overshoot_tokens"] == 0
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_a_batch_served_through_the_kernels(model, monkeypatch):
    """Three requests of different lengths with the three kernels a TPU
    would dispatch (interpreted): the paged latent attention at the
    mixer's YaRN scale, the latent's in-place write and the grouped
    expert product. They serve the XLA forms' tokens and logprobs."""
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    def batch():
        eng = DecodeEngine(model[3], **ENGINE)
        try:
            reqs = [eng.submit(_ids(n, seed=20 + n), m, logprobs=4)
                    for n, m in ((7, 5), (19, 14), (33, 9))]
            toks = [list(r.result(timeout=120.0)) for r in reqs]
            return toks, [[e["logprob"] for e in r.logprob_values]
                          for r in reqs]
        finally:
            eng.shutdown(drain_timeout=30.0)

    want_toks, want_lps = batch()
    calls = {"attend": 0, "write": 0, "experts": 0}
    scales = set()

    def counted(name, fn):
        def run(*a, **kw):
            calls[name] += 1
            scales.add(kw.get("sm_scale"))
            return fn(*a, **kw, interpret=True)
        return run

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
    # the kernel is handed the scaling's temperature, not 1 / sqrt(16)
    m = 0.1 * 0.707 * math.log(4.0) + 1.0
    assert sorted(scales - {None}) == [pytest.approx(16 ** -0.5 * m * m)]


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative": {"draft": "self", "k": 2}}, "speculative"),
    ({"parallel": {"tp": 2}}, "tp"),
    ({"quantize": {"kv": "int8"}}, "int8"),
    ({"role": "prefill"}, "role"),
], ids=["prefix-cache", "speculative", "tensor-parallel", "int8-kv",
        "prefill-role"])
def test_features_that_cannot_hold_latent_pages_are_refused(model, kw, what):
    with pytest.raises(RecurrentStateUnsupported, match=what):
        DecodeEngine(model[3], n_slots=2, max_len=32, page_size=8, **kw)


@pytest.mark.parametrize("call", [
    lambda eng: eng.export_prefix([1, 2, 3]),
    lambda eng: eng.migrate_slots(),
    lambda eng: eng.resume_generate({}),
], ids=["export-prefix", "migrate", "resume"])
def test_kv_moving_calls_are_refused_on_latent_pages(model, call):
    eng = DecodeEngine(model[3], n_slots=2, max_len=32, page_size=8)
    try:
        with pytest.raises(RecurrentStateUnsupported, match="latent pages"):
            call(eng)
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_generate_refuses_a_composed_network(model):
    from deeplearning4j_tpu.models.transformer import generate

    with pytest.raises(ValueError, match="DecodeEngine"):
        generate(model[3], _ids(4), 2)


# ------------------------------------------------------ the configuration
def test_the_configuration_file_keeps_every_published_width():
    cfg = json.loads(CONFIG.read_text())
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    cut = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text()
                   .splitlines() if '"name": "DeepSeek-V2"' in line)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in cut or cfg[key] == value, key
        dep = cfg["deployment"]
        for key in cut:
            assert dep[key + "_published"] == row["config"][key]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"]) == (8, 12)
    assert dep["experts_held_first"] == 0 and "1/8" in dep["tokens_per_expert"]
    sz = fam.sizes(cfg)
    assert (sz["d"], sz["H"], sz["qr"], sz["kr"]) == (5120, 128, 1536, 512)
    assert (sz["nope"], sz["rope"], sz["vd"]) == (128, 64, 128)
    assert (sz["ffn"], sz["f"], sz["shared"]) == (12288, 1536, 3072)
    assert (sz["L"], sz["L_dense"], sz["L_moe"], sz["mla_sub_layers"],
            sz["V"]) == (5, 1, 4, 5, 12800)
    assert (sz["E"], sz["held"], sz["groups"], sz["topk_groups"], sz["topk"],
            sz["route_scale"]) == (160, (0, 20), 8, 3, 6, 16.0)
    assert sz["yarn"] == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    c = ref.consts_from_config(cfg)
    assert abs(ref.softmax_scale(c) - 0.114722) < 1e-6
    net_mixer = fam.build_net(sz, training=False).layers[1].mixer
    assert abs(net_mixer.sm_scale - 0.114722) < 1e-6
    shapes = fam._leaf_shapes(sz)
    assert shapes["router"] == (5120, 160)
    assert shapes["eWg"] == (20, 5120, 1536)
    assert (shapes["Wkb"], shapes["Wvb"]) == ((128, 128, 512),
                                              (128, 512, 128))
    # 3,145 M parameters, as the issue reckons them
    count = lambda names: sum(int(np.prod(shapes[k])) for k in names)
    n = count(fam.TOP_LEAVES) + count(fam.DENSE_LEAVES) \
        + 4 * count(fam.MOE_LEAVES)
    assert abs(n - 3.145e9) < 5e6


@pytest.mark.parametrize("over,what", [
    ({"n_routed_experts": 17}, "outside the router"),
    ({"topk_method": "greedy"}, "group_limited_greedy"),
    ({"attention_bias": True}, "bias-free"),
    ({"rope_scaling": dict(TOY_YARN, type="linear")}, "yarn"),
], ids=["held-past-router", "topk-method", "attention-bias", "rope-type"])
def test_the_family_refuses_what_it_does_not_run(over, what):
    with pytest.raises(ValueError, match=what):
        fam.sizes(_config(**over))


def test_nothing_in_the_program_branches_on_the_models_name():
    hits = []
    for path in (REPO / "deeplearning4j_tpu").rglob("*.py"):
        for n, line in enumerate(path.read_text().splitlines(), 1):
            if "deepseek" in line.lower() and "deepseek_v2_configuration" \
                    not in line and not _in_docstring_or_comment(path, n):
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
