"""Window layers with rotary beside full layers without positions, under
ONE norm a block, grouped queries and averaged shared experts
(`cohere2_moe`: Command A+) against the plain reference the benchmark
keeps (`perfbench/families/cohere2_moe_reference.py`: one K/V head's
group of queries and one block of queries at a time, a loop over the
experts held and over the shared experts) on seeded weights at a small
size: window 8 and pages of 4, so that a slot's ring of 3 pages wraps
within a dozen tokens. Rotary on the window layers only, the parallel
block, the LayerNorm, the eight shares of a layer's experts, one layer,
the network's forward, and the decode engine's prefill, chunked prefill
and decode through `WindowPages` AND `KVPages` in one net, to more than
three windows' length."""
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.decoder_block import (
    AttentionMixer,
    DecoderBlock,
    LayerNorm,
    MoEFeedForward,
    RMSNorm,
    Rotary,
    kind_from_json,
    sub,
)
from deeplearning4j_tpu.serving import block_state
from deeplearning4j_tpu.serving.block_state import RecurrentStateUnsupported
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
from deeplearning4j_tpu.serving.observability import TIMELINE
from perfbench.families import cohere2_moe as fam
from perfbench.families import cohere2_moe_reference as ref

REPO = Path(__file__).resolve().parents[1]
CONFIG = REPO / "perfbench/configs/command-a-plus-05-2026.json"
V, L, W = 97, 4, 8


def _config(**over) -> dict:
    """The benchmark's configuration file, cut to a toy: d 64, one
    period of (window, window, window, full), 8 query heads over 2 K/V
    heads of 8, window 8, 16 experts 24 wide, top-3, two shared experts,
    every expert held."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               head_dim=8, sliding_window=W, intermediate_size=24,
               num_experts=16, num_experts_per_tok=3, num_shared_experts=2,
               vocab_size=V)
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


def _ref_logp(model, ids, rows, **kw):
    sz, c, w, _ = model
    lg = ref.logits_at(w, jnp.asarray(ids)[None], jnp.asarray(rows), c=c,
                       n_heads=sz["H"], eps=sz["eps"], **kw)
    return np.asarray(jax.nn.log_softmax(lg, axis=-1))


# ------------------------------------------------------ kinds and fields
def test_the_new_kinds_and_fields_round_trip_through_json():
    mixer = AttentionMixer(n_heads=8, n_kv_heads=2, head_dim=8,
                           rope=Rotary(theta=50000.0), window=8)
    again = kind_from_json(json.loads(json.dumps(mixer.to_json())))
    assert again == mixer and again.rope.interleaved and again.state \
        == "window"
    assert AttentionMixer(n_heads=4).state == "kv"
    assert kind_from_json(AttentionMixer(n_heads=4).to_json()).rope is None
    norm = LayerNorm(eps=1e-5)
    assert kind_from_json(norm.to_json()) == norm
    ffn = MoEFeedForward(n_experts=8, top_k=2, expert_width=16,
                         shared_width=32, shared_scale=0.5,
                         scoring="sigmoid")
    assert kind_from_json(ffn.to_json()) == ffn
    assert MoEFeedForward().shared_scale == 1.0
    with pytest.raises(ValueError, match="window 0"):
        AttentionMixer(n_heads=4, window=0)
    with pytest.raises(ValueError, match="norm_placement"):
        DecoderBlock(n_in=8, n_out=8, mixer=mixer, ffn=ffn,
                     norm_placement="sandwich")
    with pytest.raises(ValueError, match="a mixer AND a feed-forward"):
        DecoderBlock(n_in=8, n_out=8, mixer=mixer,
                     norm_placement="parallel")


def test_the_network_round_trips_through_json(model):
    conf = model[3].conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    blocks = [l for l in again.layers if isinstance(l, DecoderBlock)]
    assert [b.state for b in blocks] == ["window"] * 3 + ["kv"]
    assert all(b.norm_placement == "parallel" for b in blocks)
    assert all(b.norm == LayerNorm(eps=1e-5) for b in blocks)
    win, full = blocks[0].mixer, blocks[3].mixer
    assert (win.window, win.rope) == (W, Rotary(theta=50000.0,
                                                interleaved=True))
    assert (full.window, full.rope) == (None, None)
    assert (win.n_heads, win.n_kv_heads, win.head_dim) == (8, 2, 8)
    ffn = blocks[0].ffn
    assert (ffn.n_experts, ffn.top_k, ffn.expert_width, ffn.shared_width,
            ffn.shared_scale, ffn.scoring) == (16, 3, 24, 48, 0.5, "sigmoid")
    final = again.layers[-2]
    assert type(final).__name__ == "LayerNormalization" \
        and final.has_bias is False


def test_layer_params_carry_the_programs_names(model):
    p = model[3]._params
    for i in range(1, 1 + L):
        assert sorted(p[i]) == sorted(
            ["n1_w", "mx_Wqkv", "mx_Wo", "ff_router", "ff_router_b",
             "ff_Wg", "ff_Wu", "ff_Wd", "ff_sWg", "ff_sWu", "ff_sWd"])
        assert not np.any(np.asarray(p[i]["ff_router_b"]))  # no bias
    assert sorted(p[1 + L]) == ["gamma"] and p[2 + L] == {}
    assert p[1]["mx_Wqkv"].shape == (64, 8 * 8 + 2 * 2 * 8)
    assert p[1]["ff_sWg"].shape == (64, 2 * 24)


def test_the_layer_norm_takes_the_mean_out_and_has_no_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16)) + 2.0
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    got = LayerNorm(eps=1e-5).apply(w, x)
    xn = np.asarray(x, np.float64)
    want = (xn - xn.mean(-1, keepdims=True)) \
        / np.sqrt(xn.var(-1, keepdims=True) + 1e-5) * np.asarray(w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got, ref._ln(x, w, 1e-5), atol=1e-5)
    assert sorted(LayerNorm().init_params(16, jnp.float32)) == ["w"]


def test_interleaved_rotary_equals_the_references_pairs():
    """`Rotary.turn` leaves a head evens-first, queries and keys alike:
    the products are the reference's, which turns pairs in place."""
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 13, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 13, 2, 8))
    pos = jnp.arange(13) + 3
    rot = Rotary(theta=50000.0)
    got = jnp.einsum("bqhd,bkhd->bhqk", rot.turn(q, pos),
                     jnp.repeat(rot.turn(k, pos), 2, axis=2))
    want = jnp.einsum("qhd,khd->hqk", ref.rope(q[0], pos, 50000.0),
                      jnp.repeat(ref.rope(k[0], pos, 50000.0), 2, axis=1))
    np.testing.assert_allclose(got[0], want, atol=1e-5)


def _attention_block(full: bool):
    mixer = AttentionMixer(
        n_heads=8, n_kv_heads=2, head_dim=8,
        rope=None if full else Rotary(theta=50000.0),
        window=None if full else W)
    p = mixer.init_params(jax.random.PRNGKey(0), 64, jnp.float32,
                          lambda k, s, fi, fo: jax.random.normal(k, s)
                          / fi ** 0.5)
    return mixer, p


def test_rotary_is_on_the_window_layers_only():
    """A full layer's heads do not move when every position is shifted;
    a window layer's do, and its OUTPUT over whole sequences does not
    (rotary is relative)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 21, 64))
    full, pf = _attention_block(True)
    for a, b in zip(full.heads(pf, x, jnp.arange(21)),
                    full.heads(pf, x, jnp.arange(21) + 1000)):
        np.testing.assert_array_equal(a, b)
    win, pw = _attention_block(False)
    q0, k0, v0 = win.heads(pw, x, jnp.arange(21))
    q1, k1, v1 = win.heads(pw, x, jnp.arange(21) + 1000)
    assert float(jnp.abs(q0 - q1).max()) > 0.1
    assert float(jnp.abs(k0 - k1).max()) > 0.1
    np.testing.assert_array_equal(v0, v1)
    s0 = jnp.einsum("bqhd,bkhd->bhqk", q0, jnp.repeat(k0, 4, axis=2))
    s1 = jnp.einsum("bqhd,bkhd->bhqk", q1, jnp.repeat(k1, 4, axis=2))
    np.testing.assert_allclose(s0, s1, atol=2e-3)


def test_a_window_layer_forgets_what_lies_behind_its_window():
    """Changing a token more than W - 1 positions back moves a full
    layer's output at the last position and not a window layer's."""
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 20, 64))
    y = x.at[0, 20 - 1 - W].add(3.0)      # W behind the last: outside
    z = x.at[0, 20 - W].add(3.0)          # the window's oldest: inside
    win, pw = _attention_block(False)
    full, pf = _attention_block(True)
    last = lambda m, p, a: np.asarray(m.forward(p, a))[0, -1]
    np.testing.assert_allclose(last(win, pw, x), last(win, pw, y), atol=1e-6)
    assert np.abs(last(win, pw, x) - last(win, pw, z)).max() > 1e-3
    assert np.abs(last(full, pf, x) - last(full, pf, y)).max() > 1e-3


def test_the_parallel_block_is_one_norm_and_one_add():
    mixer, _ = _attention_block(False)
    ffn = MoEFeedForward(n_experts=4, top_k=2, expert_width=16,
                         shared_width=32, shared_scale=0.5,
                         scoring="sigmoid")
    blk = DecoderBlock(n_in=64, n_out=64, mixer=mixer, ffn=ffn,
                       norm=LayerNorm(), norm_placement="parallel")
    p = blk.init_params(jax.random.PRNGKey(1), None)
    assert "n2_w" not in p and "n1_w" in p
    p["n1_w"] = p["n1_w"] + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                    (64,))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 11, 64))
    u = LayerNorm().apply(p["n1_w"], x)
    want = x + mixer.forward(sub(p, "mx_"), u) \
        + ffn.forward(sub(p, "ff_"), u)[0]
    got, _ = blk.forward(p, {}, x)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_shared_scale_is_the_mean_of_the_shared_experts(model):
    """One MLP `n f` wide times 1 / n IS the n shared experts averaged:
    the reference loops over them."""
    sz, c, w, net = model
    p = ref._f32(w["layers"][0])
    u = jax.random.normal(jax.random.PRNGKey(6), (9, 64))
    want = ref.shared(p, u, c, precision="float32")
    from deeplearning4j_tpu.parallel.experts import gated_mlp
    got = 0.5 * gated_mlp(u, p["sWg"].astype(jnp.float32),
                          p["sWu"].astype(jnp.float32),
                          p["sWd"].astype(jnp.float32))
    np.testing.assert_allclose(got, want, atol=1e-5)
    each = [ref.ffn(u, p["sWg"][:, s], p["sWu"][:, s], p["sWd"][s],
                    precision="float32")
            for s in (slice(0, 24), slice(24, 48))]
    np.testing.assert_allclose(want, (each[0] + each[1]) / 2, atol=1e-6)


# ------------------------------------------------------------ the shares
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of one routed layer's 16: their
    routed parts, each computed by the program for its share, add up to
    the uncut reference layer's routed part, with the shared experts
    counted ONCE."""
    whole = _build(_config())
    sz, c, w, net = whole
    u = jax.random.normal(jax.random.PRNGKey(7), (1, 17, 64))
    p_ref = ref._f32(w["layers"][1])
    want_routed = ref.routed(p_ref, u[0], c, precision="float32")
    want_shared = ref.shared(p_ref, u[0], c, precision="float32")
    p = sub(net._params[2], "ff_")
    total = jnp.zeros_like(u)
    shared_seen = []
    for share in range(8):
        ffn = dataclasses.replace(net.layers[2].ffn,
                                  experts_held=(2 * share, 2))
        held = dict(p, Wg=p["Wg"][2 * share:2 * share + 2],
                    Wu=p["Wu"][2 * share:2 * share + 2],
                    Wd=p["Wd"][2 * share:2 * share + 2])
        y, _ = ffn.forward(held, u)
        no_shared = dataclasses.replace(ffn, shared_width=0)
        routed_part, _ = no_shared.forward(
            {k: v for k, v in held.items() if not k.startswith("s")}, u)
        total = total + routed_part
        shared_seen.append(y - routed_part)
    np.testing.assert_allclose(total[0], want_routed, atol=5e-5)
    for s in shared_seen:       # every chip computes the same shared part
        np.testing.assert_allclose(s[0], want_shared, atol=5e-5)
    uncut, _ = net.layers[2].ffn.forward(p, u)
    np.testing.assert_allclose(uncut[0], want_routed + want_shared,
                               atol=5e-5)


# ----------------------------------------------------- layers and forward
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
ENGINE = dict(n_slots=3, max_len=96, page_size=4, prompt_buckets=(8, 16, 32),
              prefill_chunk=4, decode_chunk=4, logprobs=4)
RING = W // 4 + 1


def _served(net, prompt, n, **kw):
    eng = DecodeEngine(net, **dict(ENGINE, **kw))
    try:
        return eng.generate(prompt, n, logprobs=4), eng.stats()
    finally:
        eng.shutdown(drain_timeout=30.0)


def _served_off(model, prompt, out) -> float:
    """The most a served token's logprob, or one of the top four at its
    position, lies from the reference's full forward over prompt +
    served tokens. Logits, not tokens."""
    toks = np.asarray(out["tokens"])
    t0, n = len(prompt), len(toks)
    want = _ref_logp(model, np.concatenate([prompt, toks]),
                     np.arange(t0 - 1, t0 + n - 1))
    off = 0.0
    for j, entry in enumerate(out["logprobs"]):
        assert entry["token"] == toks[j]
        off = max(off, abs(entry["logprob"] - want[j, toks[j]]),
                  float(np.abs(np.asarray(entry["top_logprobs"])
                               - np.sort(want[j])[::-1][:4]).max()))
    return off


@pytest.mark.parametrize("t0,n,kw", [
    (5, 30, {}),                     # four windows of decode, ring wraps 9x
    (32, 21, {}),                    # a bucket of 8 pages into a ring of 3
    (45, 13, {}),                    # longer than every bucket: 12 chunks
    (11, 26, {"decode_chunk": 1}),   # the single step, never the scan
    (14, 27, {"prefill_chunk": 8}),  # a chunk of two pages: a ring of 4
], ids=["decode-past-three-windows", "bucket-longer-than-the-ring",
        "chunked-prompt-longer-than-the-ring", "decode-step",
        "two-page-chunks"])
def test_engine_prefill_and_decode_equal_the_reference(model, t0, n, kw):
    """Prefill, then decode through three `WindowPages` blocks and one
    `KVPages` block in one net, the rings wrapping, `decode_chunked`
    dispatches lying across wraps."""
    prompt = _ids(t0, seed=t0)
    since = time.perf_counter()
    out, st = _served(model[3], prompt, n, **kw)
    assert _served_off(model, prompt, out) < 5e-5
    ring = W // 4 + max(1, kw.get("prefill_chunk", 4) // 4)
    assert (st["window_blocks"], st["kv_blocks"], st["recurrent_blocks"],
            st["latent_blocks"], st["stateless_blocks"]) == (3, 1, 0, 0, 0)
    assert st["window_ring_pages"] == ring
    # 2 K/V heads of 8 in float32, K and V, a position; 3 window blocks
    assert st["window_bytes_per_slot"] == 3 * ring * 4 * (2 * 2 * 8 * 4)
    assert st["kv_bytes_per_token"] == 2 * 2 * 8 * 4     # the full block's
    assert st["state_bytes_per_slot"] == 0 and st["state_resets"] == 0
    pages = -(-max(t0 + n - 1, -(-t0 // 4) * 4) // 4)
    assert st["window_pages_in_use_peak"] == min(ring, max(
        pages, min(b for b in (8, 16, 32, 10**6) if b >= t0) // 4
        if t0 <= 32 else 0))
    assert st["window_pages_in_use"] == 0 == st["pages_in_use"]
    # decode steps n - 1, contexts t0 + 1 .. t0 + n - 1: a window block
    # reads min(ctx, W), the full block all of it
    ctx = np.arange(t0 + 1, t0 + n)
    loop = st["loop"]
    assert loop["kv_positions_context"] == 4 * ctx.sum()
    assert loop["kv_positions_attended"] \
        == ctx.sum() + 3 * np.minimum(ctx, W).sum()
    # and each dispatch's share of both on its span (what a reader of a
    # traced stretch sums)
    spans = [s[5] for s in TIMELINE.snapshot(t0=since)
             if s[0] == "decode.dispatch"]
    for key in ("kv_positions_attended", "kv_positions_context"):
        assert sum(a[key] for a in spans) == loop[key]
    assert sum(a["chunk"] for a in spans) == n - 1
    assert st["moe_routed"] == (n - 1) * 3 * 4


def test_a_program_that_ignores_the_window_misses_the_tolerance(model):
    """The same run on a net whose window layers read their whole
    context (rotary kept) is another function: it FAILS the tolerance,
    and so does the reference with its windows ignored against the
    program that keeps them."""
    sz, c, w, net = model
    prompt = _ids(5, seed=5)
    out, _ = _served(net, prompt, 30)
    toks = np.asarray(out["tokens"])
    blind = _ref_logp(model, np.concatenate([prompt, toks]),
                      np.arange(4, 34), window_ignored=True)
    off = max(abs(e["logprob"] - blind[j, toks[j]])
              for j, e in enumerate(out["logprobs"]))
    assert off > 100 * 5e-5
    broken = fam.build_net(sz, training=True, dtype=jnp.float32)
    for layer in broken.layers:
        if isinstance(layer, DecoderBlock) and layer.mixer.window:
            layer.mixer = dataclasses.replace(layer.mixer, window=None)
    fam.install(broken, jax.tree.map(lambda a: a.astype(jnp.float32), w))
    out, st = _served(broken, prompt, 30)
    assert st["window_blocks"] == 0 and st["kv_blocks"] == 4
    assert _served_off(model, prompt, out) > 100 * 5e-5
    # up to the window's length the two are one function
    early = {"tokens": out["tokens"][:W - 5],
             "logprobs": out["logprobs"][:W - 5]}
    assert _served_off(model, prompt, early) < 5e-5


def test_bfloat16_in_float32s_place_misses_the_tolerance(model):
    *_, net = _build(_config(), compute_dtype=jnp.bfloat16)
    prompt = _ids(11, seed=11)
    out, _ = _served(net, prompt, 13)
    assert _served_off(model, prompt, out) > 10 * 5e-5


def test_one_share_held_is_the_references_partial_sum(model):
    """Experts 4-7 of 16 held: the engine serves the reference's partial
    sum with the shared part whole."""
    part = _build(_config(num_experts=4, deployment=dict(
        num_experts_published=16, experts_held_first=4)))
    prompt = _ids(14, seed=3)
    out, st = _served(part[3], prompt, 25)
    assert _served_off(part, prompt, out) < 5e-5
    assert st["moe_experts_held"] == 4 * 4
    assert 0 < st["moe_held_choices"] < st["moe_routed"]
    # and it is another function than the whole layer's (at d 64 the
    # experts' 0.02 draws weigh little beside the attention's, whose
    # output the family draws eight times as large: 1.4e-4)
    whole, _ = _served(model[3], prompt, 25)
    assert max(abs(a["logprob"] - b["logprob"]) for a, b in
               zip(out["logprobs"], whole["logprobs"])) > 2 * 5e-5


def test_slots_admitted_and_retired_out_of_order_and_recycled(model):
    """Five requests of different lengths over three slots: they retire
    out of order, the two that wait take over recycled slots and rings,
    and every one is the reference's."""
    shapes = ((7, 31), (19, 6), (33, 11), (12, 19), (26, 14))
    prompts = [_ids(n, seed=20 + n) for n, _ in shapes]
    eng = DecodeEngine(model[3], **ENGINE)
    try:
        assert [len(c) for c in eng._caches] == [2, 2, 2, 2]
        assert eng._caches[0][0].shape == (3 * RING + 1, 2, 8, 4)
        assert eng._caches[0][1].shape == (3 * RING + 1, 2, 4, 8)
        assert eng._caches[3][0].shape == (eng.pool_pages + 1, 2, 8, 4)
        assert eng._pool.ring_table.shape == (3, RING)
        assert isinstance(eng._pool.tables, tuple)
        reqs = [eng.submit(p, m, logprobs=4)
                for p, (_, m) in zip(prompts, shapes)]
        for r, p in zip(reqs, prompts):
            toks = r.result(timeout=180.0)
            assert _served_off(model, p, {"tokens": toks,
                                          "logprobs": r.logprob_values}) \
                < 5e-5
        st = eng.stats()
        assert st["window_pages_in_use"] == 0
        assert st["window_pages_in_use_peak"] == 3 * RING
        assert sorted(eng._pool._free_ring) == list(range(1, 3 * RING + 1))
        assert st["loop"]["overshoot_tokens"] == 0
    finally:
        eng.shutdown(drain_timeout=30.0)


def test_a_batch_served_through_the_kernels(model, monkeypatch):
    """Three requests with the kernels a TPU would dispatch
    (interpreted): the windowed paged attention over the rings and the
    full one over the table, C = 1."""
    from deeplearning4j_tpu.ops import pallas_paged_attention as ppa

    seen = []

    def attend(q, k_pool, v_pool, page_table, positions, active=None,
               k_scale=None, v_scale=None, window=None):
        seen.append((q.shape[1], window, page_table.shape[1]))
        return ppa.paged_attention(q, k_pool, v_pool, page_table, positions,
                                   active=active, interpret=True,
                                   window=window)

    monkeypatch.setattr(ppa, "paged_attention_or_none", attend)
    shapes = ((7, 21), (19, 14), (12, 25))
    prompts = [_ids(n, seed=60 + n) for n, _ in shapes]
    eng = DecodeEngine(model[3], **dict(ENGINE, prompt_buckets=(8, 32)))
    try:
        reqs = [eng.submit(p, m, logprobs=4)
                for p, (_, m) in zip(prompts, shapes)]
        for r, p in zip(reqs, prompts):
            toks = r.result(timeout=300.0)
            assert _served_off(model, p, {"tokens": toks,
                                          "logprobs": r.logprob_values}) \
                < 5e-5
    finally:
        eng.shutdown(drain_timeout=30.0)
    assert (1, W, RING) in seen and (1, None, 96 // 4) in seen


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache (a hit needs a window layer's "
                              "ring of pages at the shared boundary"),
    (dict(quantize={"kv": "int8"}), "no quantized form of a window "
                                    "layer's ring of pages"),
    (dict(role="prefill"), "KV handoff does not carry a window layer's "
                           "ring of pages"),
    (dict(speculative={"draft": "self"}, logprobs=0),
     "speculative decoding"),
    (dict(parallel={"tp": 2}, logprobs=0),
     "no sharding rule for composed blocks"),
])
def test_features_that_cannot_hold_a_ring_are_refused(model, kw, what):
    import re

    with pytest.raises(RecurrentStateUnsupported, match=re.escape(what)):
        DecodeEngine(model[3], **dict(ENGINE, **kw))


def test_the_window_kind_says_what_it_counts_and_refuses(model):
    kind = block_state.WindowPages
    assert block_state._KINDS["window"] is kind
    assert (kind.kind, kind.blocks_key, kind.token_bytes_key) \
        == ("window", "window_blocks", None)
    assert set(kind.refuses) == {"prefix_cache", "quantize_kv", "role"}
    from deeplearning4j_tpu.models.transformer import GPTPlan

    plan = GPTPlan(model[3])
    assert plan.state_kinds() == ["window"] * 3 + ["kv"]
    assert block_state.ring_pages(plan, 4, 4) == 3
    assert block_state.ring_pages(plan, 4, 16) == 6
    assert block_state.ring_pages(plan, 128, 128) == 2
    got = block_state.refused(plan, {"prefix_cache": "cache ({what})",
                                     "role": "role ({what})"})
    assert got == ["cache (a window layer's ring of pages)",
                   "role (a window layer's ring of pages)"]


def test_generate_refuses_a_composed_network(model):
    from deeplearning4j_tpu.models.transformer import generate

    with pytest.raises(ValueError):
        generate(model[3], _ids(5)[None], 3)


# --------------------------------------------------------- the config file
def test_the_configuration_file_keeps_every_published_width():
    cfg = json.loads(CONFIG.read_text())
    rows = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if Path("/opt/skills/guides/model-configs/architectures.jsonl"
                ).exists() else []
    row = next((r for r in rows if r["name"] == cfg["name"]), None)
    if row is not None:
        assert cfg["source"] == row["source_url"]
        moved = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert moved == {"num_hidden_layers", "num_experts", "vocab_size",
                         "layer_types"}
        assert cfg["layer_types"] == row["config"]["layer_types"][:4]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["sliding_window"]) \
        == (4096, 128, 8, 128, 4096, 8, 4, 4096)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 32768)
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"],
            dep["num_hidden_layers_published"],
            dep["num_experts_published"], dep["vocab_size_published"]) \
        == (8, 8, 32, 128, 262144)
    assert cfg["precision"] == {"parameters": "bfloat16",
                                "compute": "bfloat16", "control": "float8"}
    for key in ("shared_average", "window", "router", "prefix_dense",
                "vision", "precision", "weights", "positions"):
        assert len(cfg["assumed"][key]) > 40
    sz = fam.sizes(cfg)
    assert (sz["d"], sz["H"], sz["Hkv"], sz["hd"], sz["W"], sz["f"],
            sz["L"], sz["window_layers"], sz["full_layers"], sz["E"],
            sz["held"], sz["topk"], sz["n_shared"], sz["V"], sz["eps"]) \
        == (4096, 128, 8, 128, 4096, 4096, 4, 3, 1, 128, (0, 16), 8, 4,
            32768, 1e-5)
    # the parameters the chip holds, as the issue reckons them
    shapes = fam._leaf_shapes(sz)
    layer = sum(int(np.prod(shapes[n])) for n in fam.LAYER_LEAVES)
    assert layer == pytest.approx(1149.8e6, rel=1e-3)
    total = 4 * layer + int(np.prod(shapes["emb"])) + 4096
    assert total == pytest.approx(4733.3e6, rel=1e-3)
    # a page a layer and the pools of the cell
    assert 2 * 8 * 128 * 128 * 2 == 524288
    assert 3 * (48 * 33 + 1) * 524288 == pytest.approx(2.49e9, rel=3e-3)
    assert (3168 + 1) * 524288 == pytest.approx(1.66e9, rel=3e-3)


@pytest.mark.parametrize("over,what", [
    (dict(use_parallel_block=False), "parallel block"),
    (dict(shared_expert_combination_strategy="sum"), "averages"),
    (dict(expert_selection_fn="softmax"), "sigmoid"),
    (dict(tie_word_embeddings=False), "tied head"),
    (dict(layer_types=["full_attention"] * 4), "layer_switch"),
    (dict(position_embedding_type="rope_neox"), "interleaved"),
])
def test_the_family_refuses_what_it_does_not_run(over, what):
    with pytest.raises(ValueError, match=what):
        fam.sizes(_config(**over))


def test_nothing_in_the_program_branches_on_the_models_name():
    pkg = REPO / "deeplearning4j_tpu"
    for path in pkg.rglob("*.py"):
        text = path.read_text().lower()
        for word in ("cohere2", "command_a_plus", "command-a"):
            assert word not in text or path.name == "transformer.py", \
                (path, word)
    src = (pkg / "models" / "transformer.py").read_text()
    assert src.count("cohere2_moe") == 1     # the docstring's family name
