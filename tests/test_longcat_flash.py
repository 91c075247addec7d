"""The shortcut layer of two latent-attention sub-layers, two dense FFNs
and one routed block with zero-compute experts (`longcat_flash`) against
the plain reference the benchmark keeps
(`perfbench/families/longcat_flash_reference.py`: expanded attention a
head at a time, a loop over the experts held) on seeded weights at a
small size: the kinds' JSON, the router, the zero experts' identity
part, the share of the experts a chip holds, one layer and its topology,
the network's forward, and the decode engine's prefill, chunked prefill
and decode through two pools of latent pages a layer."""
import json
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
    ShortcutDecoderBlock,
    kind_from_json,
    sub,
)
from deeplearning4j_tpu.parallel import experts
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
from perfbench.families import longcat_flash as fam
from perfbench.families import longcat_flash_reference as ref

REPO = Path(__file__).resolve().parents[1]
CONFIG = REPO / "perfbench/configs/longcat-flash-chat.json"
V, L = 97, 2


def _config(**over) -> dict:
    """The benchmark's configuration file, cut to a toy: d 64, 2 layers,
    4 heads over a query latent of 24 and a key/value latent of 16 (8
    nope + 4 rope, values 8), dense FFNs 48 wide, 8 real experts 24 wide
    (a multiple of 8 and not of 128) and 4 zero-compute ones, top-3, all
    real experts held."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(hidden_size=64, num_layers=L, num_attention_heads=4,
               q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
               qk_rope_head_dim=4, v_head_dim=8, ffn_hidden_size=48,
               expert_ffn_hidden_size=24, n_routed_experts=8,
               zero_expert_num=4, moe_topk=3, vocab_size=V,
               rope_theta=1e4)
    cfg["deployment"] = dict(n_routed_experts_published=8,
                             experts_held_first=0)
    cfg.update(over)
    return cfg


def _build(cfg, seed=5, compute_dtype=None, bias=0.02):
    """(sizes, reference constants, bf16-valued weights, the program's
    float32 net holding them). The correction bias is redrawn at the
    toy's scale: scores over 12 outputs lie about 0.02 apart."""
    sz, c = fam.sizes(cfg), ref.consts_from_config(cfg)
    w = dict(fam.make_weights(seed, sz))
    w["layers"] = [dict(p, router_b=p["router_b"] * (bias / fam.ROUTER_BIAS_STD))
                   for p in w["layers"]]
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


# -------------------------------------------------------------- the kinds
def test_the_routed_kind_round_trips_through_json():
    kind = MoEFeedForward(n_experts=512, n_zero_experts=256, top_k=12,
                          expert_width=2048, experts_held=(0, 16),
                          scoring="softmax_all", routed_scale=6.0)
    d = json.loads(json.dumps(kind.to_json()))
    assert kind_from_json(d) == kind
    p = kind.init_params(jax.random.PRNGKey(0), 32, jnp.float32,
                         lambda k, s, fi, fo: jnp.zeros(s))
    # the router keeps its 768 outputs, the zero experts have no weights
    assert p["router"].shape == (32, 768) and p["router_b"].shape == (768,)
    assert p["router_b"].dtype == jnp.float32
    assert p["Wg"].shape == (16, 32, 2048)
    assert MoEFeedForward().n_zero_experts == 0


def test_the_network_round_trips_through_json(model):
    conf = model[3].conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    blocks = [l for l in again.layers
              if isinstance(l, ShortcutDecoderBlock)]
    assert len(blocks) == L
    assert [b.state for b in blocks] == [("latent", "latent")] * L
    b = blocks[0]
    assert isinstance(b.first, DecoderBlock) \
        and isinstance(b.second.mixer, LatentAttentionMixer)
    assert b.first.ffn == GatedMLP(width=48)
    assert b.shortcut == conf.layers[1].shortcut
    assert b.feed_forwards() == [b.first.ffn, b.shortcut, b.second.ffn]
    assert b.mixers() == [b.first.mixer, b.second.mixer]


def test_layer_params_lie_under_their_prefixes(model):
    p = model[3]._params[1]
    assert {k[:2] for k in p if not k.startswith("sc_")} == {"a_", "b_"}
    assert sorted(sub(p, "sc_")) == ["Wd", "Wg", "Wu", "router", "router_b"]
    assert sorted(sub(p, "a_")) == sorted(sub(p, "b_")) == sorted(
        ["n1_w", "n2_w", "ff_Wg", "ff_Wu", "ff_Wd", "mx_Wqa", "mx_qn_w",
         "mx_Wqn", "mx_Wqr", "mx_Wkvc", "mx_Wkr", "mx_kvn_w", "mx_Wkb",
         "mx_Wvb", "mx_Wo"])


@pytest.mark.parametrize("kw,what", [
    (dict(first=DecoderBlock(ffn=GatedMLP())), "pre-norm block with a mixer"),
    (dict(second=DecoderBlock(ffn=GatedMLP())), "pre-norm block with a mixer"),
    (dict(shortcut=None), "shortcut feed-forward"),
], ids=["first-without-mixer", "second-without-mixer", "no-shortcut"])
def test_a_shortcut_block_needs_its_parts(kw, what):
    pair = DecoderBlock(mixer=LatentAttentionMixer(), ffn=GatedMLP())
    with pytest.raises(ValueError, match=what):
        ShortcutDecoderBlock(**dict(dict(first=pair, second=pair,
                                         shortcut=GatedMLP()), **kw))


# ------------------------------------------------------------- the router
def _logits(n=40, e=12, seed=2):
    return 1.5 * jax.random.normal(jax.random.PRNGKey(seed), (n, e))


def test_the_gates_are_unnormalised_scores_times_the_scale():
    lg = _logits()
    g = np.asarray(experts.routed_gates(lg, 3, bias=jnp.zeros(12), scale=6.0,
                                        scoring="softmax_all"))
    s = np.asarray(jax.nn.softmax(lg, axis=-1))
    top = np.argsort(-s, axis=1)[:, :3]
    assert np.all((g != 0).sum(1) == 3)
    for n in range(len(s)):
        np.testing.assert_allclose(g[n, top[n]], 6.0 * s[n, top[n]],
                                   rtol=1e-6)
    # not renormalised: a row's gates add up to 6 x its chosen scores
    assert np.all(g.sum(1) < 6.0) and g.sum(1).std() > 0.1


def test_the_bias_moves_the_choice_and_never_the_weight():
    lg = _logits()
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (12,))
    plain = np.asarray(experts.routed_gates(
        lg, 3, bias=jnp.zeros(12), scale=6.0, scoring="softmax_all"))
    moved = np.asarray(experts.routed_gates(
        lg, 3, bias=bias, scale=6.0, scoring="softmax_all"))
    changed = np.any((plain != 0) != (moved != 0), axis=1)
    assert 0.2 < changed.mean() < 1.0
    s = 6.0 * np.asarray(jax.nn.softmax(lg, axis=-1))
    np.testing.assert_allclose(moved[moved != 0], s[moved != 0], rtol=1e-6)


def test_the_published_bias_draw_changes_the_choice_for_most_tokens():
    """At the router's real width (768 outputs, top 12, logits as the
    benchmark's weights give them) the drawn bias changes some choice of
    most tokens and leaves most choices as they were."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    lg = 1.57 * jax.random.normal(k1, (256, 768))
    bias = fam.ROUTER_BIAS_STD * jax.random.normal(k2, (768,))
    kw = dict(scale=6.0, scoring="softmax_all")
    plain = np.asarray(experts.routed_gates(lg, 12, bias=0 * bias, **kw)) != 0
    moved = np.asarray(experts.routed_gates(lg, 12, bias=bias, **kw)) != 0
    assert np.any(plain != moved, axis=1).mean() > 0.5
    assert (plain & moved).sum() > 0.8 * plain.sum()


def _moe_args(held=(0, 8), seed=4, n=24, d=64, f=24, n_real=8, n_zero=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (n, d))
    router = jax.random.normal(k[1], (d, n_real + n_zero)) / 4
    bias = 0.02 * jax.random.normal(k[2], (n_real + n_zero,))
    Wg = jax.random.normal(k[3], (n_real, d, f)) / 8
    Wu = jax.random.normal(k[4], (n_real, d, f)) / 8
    Wd = jax.random.normal(k[5], (n_real, f, d)) / 5
    lo, cnt = held
    return x, router, bias, Wg[lo:lo + cnt], Wu[lo:lo + cnt], \
        Wd[lo:lo + cnt]


def _moe(held, count_mask=None, n_zero=4, **kw):
    x, router, bias, Wg, Wu, Wd = _moe_args(held, n_zero=n_zero, **kw)
    return x, experts.dropless_moe(
        x, router, Wg, Wu, Wd, top_k=3, experts_held=held,
        count_mask=count_mask, router_bias=bias, routed_scale=6.0,
        scoring="softmax_all", n_zero=n_zero)


def test_a_chosen_zero_expert_returns_the_token_under_its_gate():
    x, (y, counts) = _moe((0, 8))
    assert counts is None
    _, router, bias, *_ = _moe_args()
    g = np.asarray(experts.routed_gates(x @ router, 3, bias=bias, scale=6.0,
                                        scoring="softmax_all"))
    # holding no real expert's choices: what is left is the identity part
    _, (y_none, _) = _moe((0, 8), seed=4)
    real = experts.grouped_expert_ffn_xla(x, jnp.asarray(g[:, :8]),
                                          *_moe_args()[3:])
    np.testing.assert_allclose(y - real, g[:, 8:].sum(1, keepdims=True) * x,
                               atol=1e-5)
    assert (g[:, 8:] != 0).any() and (g[:, :8] != 0).any()


def test_zero_choices_are_counted_over_the_rows_that_count():
    live = jnp.arange(24) < 10
    x, (y, (counts, rows_local, zero)) = _moe((0, 8), count_mask=live)
    _, router, bias, *_ = _moe_args()
    g = np.asarray(experts.routed_gates(x @ router, 3, bias=bias, scale=6.0,
                                        scoring="softmax_all")) != 0
    assert counts.shape == (2, 8)
    assert int(zero) == g[:10, 8:].sum() > 0
    assert int(counts[0].sum()) + int(zero) == 10 * 3
    assert int(rows_local) == g[:10, :8].any(axis=1).sum()
    # without zero experts the per-expert counts are the array they
    # always were, and there is no count of zero choices
    _, (_, plain) = _moe((0, 8), count_mask=live, n_zero=0, n_real=12)
    assert plain.experts.shape == (2, 8) and plain.zero is None


def test_the_shares_add_up_with_the_zero_part_counted_once():
    """32 real experts in 4 shares of 8, and 6 zero-compute experts:
    the held parts of all shares plus the identity part ONCE are the
    uncut reference's routed block."""
    kw = dict(n_real=32, n_zero=6, seed=11)
    x, router, bias, Wg, Wu, Wd = _moe_args((0, 32), **kw)
    c = ref.Consts(q_rank=1, kv_rank=1, nope=1, rope=2, v_dim=1,
                   rope_theta=1.0, scale_q_lora=False, scale_kv_lora=False,
                   n_experts=32, n_zero=6, top_k=3, routed_scale=6.0,
                   held_first=0)
    with jax.default_matmul_precision("highest"):
        want = ref.sc_moe({"router": router, "router_b": bias, "eWg": Wg,
                           "eWu": Wu, "eWd": Wd}, x, c, precision="float32")
    gates = experts.routed_gates(x @ router, 3, bias=bias, scale=6.0,
                                 scoring="softmax_all")
    parts, identity = [], None
    for first in range(0, 32, 8):
        _, (with_zero, _) = _moe((first, 8), **kw)
        held = slice(first, first + 8)
        held_only = experts.grouped_expert_ffn_xla(
            x, gates[:, held], Wg[held], Wu[held], Wd[held])
        parts.append(held_only)
        each = with_zero - held_only      # every share computes it alike
        if identity is not None:
            np.testing.assert_allclose(each, identity, atol=1e-5)
        identity = each
    assert float(jnp.max(jnp.abs(identity))) > 0.1
    np.testing.assert_allclose(sum(parts) + identity, want, atol=2e-5)


# --------------------------------------------------------------- one layer
def _layer_in(model, seed=6, t=19):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, 64))


@pytest.mark.parametrize("i", range(L))
def test_one_layer_equals_the_reference_layer(model, i):
    sz, c, w, net = model
    x = _layer_in(model)
    got, _ = net.layers[1 + i].forward(net._params[1 + i], {}, x)
    want = ref.layer(w["layers"][i], x[0], c=c, n_heads=sz["H"],
                     eps=sz["eps"], precision="float32")
    np.testing.assert_allclose(got[0], want, atol=5e-5)


def test_the_shortcut_joins_after_the_second_pair(model):
    """The routed block's output skips the second attention and FFN: the
    layer that adds it to the stream before them is another function."""
    sz, c, w, net = model
    x = _layer_in(model)
    got, _ = net.layers[1].forward(net._params[1], {}, x)
    early = ref.layer(w["layers"][0], x[0], c=c, n_heads=sz["H"],
                      eps=sz["eps"], precision="float32", early_join=True)
    assert float(jnp.max(jnp.abs(got[0] - early))) > 20 * 5e-5


def test_forward_logits_equal_the_reference(model):
    ids = _ids(23, seed=1)
    got = np.asarray(model[3].output(jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(np.log(got), _ref_logp(model, ids,
                                                      np.arange(23)),
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
    by two orders)."""
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
    # two pools a layer: a position costs 16 + 4 float32 numbers in each
    assert st["latent_blocks"] == 2 * L
    assert st["latent_bytes_per_token"] == 2 * L * 20 * 4
    assert (st["kv_blocks"], st["recurrent_blocks"],
            st["stateless_blocks"]) == (0, 0, 0)
    assert st["kv_bytes_per_token"] == st["state_bytes_per_slot"] == 0
    # 12 decode steps, 1 live slot, top-3 in each of the 2 routed blocks;
    # every real expert is held, so what is not a held choice fell on a
    # zero expert
    assert st["moe_routed"] == 12 * 3 * L
    assert st["moe_held_choices"] + st["moe_zero_choices"] \
        == st["moe_routed"]
    assert 0 < st["moe_zero_choices"] < st["moe_routed"]
    assert st["moe_experts_read"] == st["moe_experts_hit"]
    assert st["moe_experts_held"] == L * 8
    assert st["loop"]["kv_pages_walked"] > 0


def test_a_layer_keeps_two_pools_from_the_shared_page_table(model):
    eng = DecodeEngine(model[3], **ENGINE)
    try:
        assert [len(c) for c in eng._caches] == [2] * L
        pools = [part[0] for c in eng._caches for part in c]
        # (pool pages + the trash page, latent + rope key, page)
        assert {p.shape for p in pools} == {(eng.pool_pages + 1, 20, 8)}
        eng.generate(_ids(20, seed=2), 6)
        written = [float(jnp.sum(jnp.abs(part[0][1:])))
                   for c in eng._caches for part in c]
        assert all(v > 0 for v in written) and len(set(written)) == 2 * L
    finally:
        eng.shutdown(drain_timeout=30.0)


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


def test_a_share_of_the_experts_held_is_the_references_partial_sum(model):
    """Experts 4-7 of 8 held: the engine serves the reference's partial
    sum with the identity part whole, and its counters see the share."""
    half = _build(_config(n_routed_experts=4, deployment=dict(
        n_routed_experts_published=8, experts_held_first=4)))
    prompt = _ids(14, seed=3)
    out, st = _served(half[3], prompt, 13)
    _assert_served_equals_reference(half, prompt, out)
    assert st["moe_routed"] == 12 * 3 * L
    assert 0 < st["moe_held_choices"] \
        < st["moe_routed"] - st["moe_zero_choices"]
    assert st["moe_experts_held"] == L * 4


def test_decode_chunked_equals_decode_step(model):
    prompt = _ids(9, seed=3)
    a, sa = _served(model[3], prompt, 17)
    b, sb = _served(model[3], prompt, 17, decode_chunk=1)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_allclose([e["logprob"] for e in a["logprobs"]],
                               [e["logprob"] for e in b["logprobs"]],
                               atol=1e-5)
    assert sa["moe_zero_choices"] == sb["moe_zero_choices"] > 0


def test_concurrent_requests_do_not_touch_each_others_pages(model):
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


def test_a_batch_served_through_the_kernels(model, monkeypatch):
    """Three requests of different lengths, so that slots stand empty
    while others decode, with the three kernels a TPU would dispatch
    (interpreted): the paged latent attention, the latent's in-place
    write and the grouped expert product. They serve the XLA forms'
    tokens and logprobs."""
    from deeplearning4j_tpu.ops import pallas_mla_attend as mla
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

    def counted(name, fn):
        def run(*a, **kw):
            calls[name] += 1
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
    cut = {"num_layers", "n_routed_experts", "vocab_size"}
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text()
                   .splitlines() if '"name": "LongCat-Flash-Chat"' in line)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in cut or cfg[key] == value, key
        dep = cfg["deployment"]
        for key in cut:
            assert dep[key + "_published"] == row["config"][key]
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"]) == (32, 7)
    assert "1/32" in dep["tokens_per_expert"]
    sz = fam.sizes(cfg)
    assert (sz["d"], sz["H"], sz["qr"], sz["kr"]) == (6144, 64, 1536, 512)
    assert (sz["nope"], sz["rope"], sz["vd"]) == (128, 64, 128)
    assert (sz["ffn"], sz["f"], sz["L"], sz["V"]) == (12288, 2048, 4, 16384)
    assert (sz["E"], sz["Z"], sz["held"], sz["topk"], sz["route_scale"]) \
        == (512, 256, (0, 16), 12, 6.0)
    shapes = fam._leaf_shapes(sz)
    assert shapes["router"] == (6144, 768)
    assert (shapes["Wkvc0"], shapes["Wkr0"]) == ((6144, 512), (6144, 64))
    assert (shapes["Wqn1"], shapes["Wqr1"]) == ((1536, 8192), (1536, 4096))
    assert (shapes["Wkb1"], shapes["Wvb1"]) == ((64, 128, 512),
                                                (64, 512, 128))
    assert shapes["eWg"] == (16, 6144, 2048)
    # 5,173 M parameters, as the issue reckons them
    n = sum(int(np.prod(shapes[k])) for k in fam.TOP_LEAVES) \
        + sz["L"] * sum(int(np.prod(shapes[k])) for k in fam.LAYER_LEAVES)
    assert abs(n - 5.173e9) < 5e6


@pytest.mark.parametrize("over,what", [
    ({"n_routed_experts": 9}, "outside the router"),
    ({"zero_expert_type": "copy"}, "return their input"),
    ({"attention_bias": True}, "bias-free"),
], ids=["held-past-router", "zero-expert-type", "attention-bias"])
def test_the_family_refuses_what_it_does_not_run(over, what):
    with pytest.raises(ValueError, match=what):
        fam.sizes(_config(**over))
