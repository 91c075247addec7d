"""Every field PR 49 added has a default that traces the program PR 48
traced: the mixers of granite's, olmo's and nemotron's attention layers
(no `rope`, no `window`), `DecoderBlock` "pre" and "post",
`MoEFeedForward.shared_scale` 1.0, the flash forward and backward and
the paged kernel without a window. `PARENT` holds the SHA-256 of each
jaxpr as PR 48's tree (commit 65de515) printed it under this JAX; the
texts are made by `_texts` below, run against a `git archive` of that
commit. Another JAX prints other text: the digests are then skipped and
the structural assertions stay. (The engine's four programs for five
toy nets were compared whole, StableHLO text for text: PERF.md §6.)
PR 51 changed how every router chooses (`experts.chosen_mask`); the two
`block.*` texts are taken with the softmax router's body as PR 48 had it
(`_scattered_topk_gates`), so they still say that nothing else moved."""
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from deeplearning4j_tpu.nn.conf.decoder_block import (
    AttentionMixer,
    DecoderBlock,
    MoEFeedForward,
    RMSNorm,
)
from deeplearning4j_tpu.ops import pallas_attention as pa
from deeplearning4j_tpu.ops import pallas_paged_attention as ppa
from deeplearning4j_tpu.parallel import experts



def _strip(text: str) -> str:
    return re.sub(r"0x[0-9a-f]+", "0x", text)


MIXERS = {
    "granite": dict(n_heads=4, n_kv_heads=2),
    "olmo": dict(n_heads=4, qk_norm=True),
    "nemotron": dict(n_heads=4, n_kv_heads=1, head_dim=32, scale=0.1),
}


def _mixer(kw: dict):
    mixer = AttentionMixer(**kw)
    p = mixer.init_params(jax.random.PRNGKey(0), 64, jnp.float32,
                          lambda k, s, fi, fo: jax.random.normal(k, s))
    return mixer, p


def _block(place: str, **ffn):
    blk = DecoderBlock(
        n_in=64, n_out=64, mixer=AttentionMixer(n_heads=4),
        ffn=MoEFeedForward(n_experts=4, top_k=2, expert_width=16,
                           shared_width=16, **ffn),
        norm=RMSNorm(), norm_placement=place)
    return blk, blk.init_params(jax.random.PRNGKey(0), None)


def _scattered_topk_gates(logits, top_k):
    top_v, top_i = lax.top_k(logits.astype(jnp.float32), top_k)
    w = jax.nn.softmax(top_v, axis=-1)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros(logits.shape, jnp.float32).at[rows, top_i].set(w)


def _texts(monkeypatch) -> dict:
    monkeypatch.setattr(experts, "topk_gates", _scattered_topk_gates)
    out, x = {}, jnp.zeros((1, 12, 64))
    for name, kw in MIXERS.items():
        mixer, p = _mixer(kw)
        out[f"mixer.{name}.forward"] = str(jax.make_jaxpr(mixer.forward)(p, x))
        out[f"mixer.{name}.heads"] = str(jax.make_jaxpr(mixer.heads)(p, x))
    for place in ("pre", "post"):
        blk, p = _block(place)
        out[f"block.{place}"] = str(jax.make_jaxpr(
            lambda p, x: blk.forward(p, {}, x)[0])(p, x))
    q = jnp.zeros((2, 1, 4, 128))
    kp, vp = jnp.zeros((5, 2, 128, 8)), jnp.zeros((5, 2, 8, 128))
    pt, p0 = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    out["paged_attention"] = str(jax.make_jaxpr(
        lambda *a: ppa.paged_attention(*a, active=jnp.ones((2,), bool),
                                       interpret=True))(q, kp, vp, pt, p0))
    a = jnp.zeros((1, 256, 2, 128))
    flash = lambda a: pa.flash_attention(a, a, a, causal=True, block_q=128,
                                         block_k=128, interpret=True)
    out["flash_forward"] = str(jax.make_jaxpr(flash)(a))
    out["flash_backward"] = str(jax.make_jaxpr(
        jax.grad(lambda a: flash(a).sum()))(a))
    return {k: _strip(v) for k, v in out.items()}


def digests(monkeypatch) -> dict:
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16]
            for k, v in _texts(monkeypatch).items()}


JAX = '0.9.0'
PARENT = {
    'mixer.granite.forward': '553250b80a15c27c',
    'mixer.granite.heads': '42fa94f4ae8efc43',
    'mixer.olmo.forward': '7216e5a7a93e312c',
    'mixer.olmo.heads': '2c22b8d906c133bc',
    'mixer.nemotron.forward': 'a57d600f15af4134',
    'mixer.nemotron.heads': '0da16278ebf366bf',
    'block.pre': '108e72d05bb78c29',
    'block.post': '5d92baf611e9f160',
    'paged_attention': '812289f660a89acd',
    'flash_forward': '2bc462203c6f4c79',
    'flash_backward': '9c799ee3ce029f4f',
}


def test_the_defaults_trace_the_parents_programs(monkeypatch):
    if jax.__version__ != JAX:
        pytest.skip(f"digests taken under JAX {JAX}, this is "
                    f"{jax.__version__}")
    assert digests(monkeypatch) == PARENT


@pytest.mark.parametrize("name", sorted(MIXERS))
def test_a_mixer_without_rope_or_window_takes_no_positions(name):
    mixer, p = _mixer(MIXERS[name])
    assert mixer.rope is None and mixer.window is None
    assert mixer.state == "kv"
    x = jnp.zeros((1, 12, 64))
    plain = str(jax.make_jaxpr(mixer.heads)(p, x))
    handed = str(jax.make_jaxpr(
        lambda p, x, pos: mixer.heads(p, x, pos))(p, x, jnp.arange(12)))
    # positions handed to it are not read: the same equations
    assert plain.count("\n") == handed.count("\n")
    assert "cos" not in plain and "sin" not in plain
    text = str(jax.make_jaxpr(mixer.forward)(p, x))
    assert "cos" not in text and " gt " not in text


@pytest.mark.parametrize("place", ["pre", "post"])
def test_a_shared_scale_of_one_multiplies_nothing(place):
    blk, p = _block(place)
    same, _ = _block(place, shared_scale=1.0)
    half, _ = _block(place, shared_scale=0.5)
    x = jnp.zeros((1, 12, 64))
    text = lambda b: _strip(str(jax.make_jaxpr(
        lambda p, x: b.forward(p, {}, x)[0])(p, x)))
    assert text(blk) == text(same)
    assert text(half).count(" mul ") == text(blk).count(" mul ") + 1
    assert "n2_w" in p
