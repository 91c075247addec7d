"""Multi-head latent attention (`LatentAttentionMixer`): its three forms
held to one another and to the plain reference the benchmark keeps
(`perfbench/families/longcat_flash_reference.mla`: expanded keys and
values, a full score matrix a head), the paged pool of latents, and the
two kernels of `ops/pallas_mla_attend.py` in interpret mode."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.decoder_block import (
    LatentAttentionMixer,
    kind_from_json,
)
from deeplearning4j_tpu.ops import pallas_mla_attend as mla
from perfbench.families import longcat_flash_reference as ref

D, T = 48, 21
MIXER = LatentAttentionMixer(n_heads=4, q_rank=24, kv_rank=16, nope_dim=8,
                             rope_dim=4, v_dim=8, rope_theta=1e4,
                             scale_q_lora=True, scale_kv_lora=True)
CONSTS = ref.Consts(q_rank=24, kv_rank=16, nope=8, rope=4, v_dim=8,
                    rope_theta=1e4, scale_q_lora=True, scale_kv_lora=True,
                    n_experts=0, n_zero=0, top_k=0, routed_scale=1.0,
                    held_first=0)


# the benchmark's three latent configurations' head counts, each on the
# toy widths and in its own flavour: LongCat's (both lora scales),
# DeepSeek-V2's (YaRN), Ling's (full-rank queries, a gate a head)
BY_HEADS = {
    4: MIXER,
    64: dataclasses.replace(MIXER, n_heads=64),
    128: dataclasses.replace(
        MIXER, n_heads=128, scale_q_lora=False, scale_kv_lora=False,
        rope_scaling=dict(kind="yarn", factor=4.0, original_max=8,
                          beta_fast=4.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707)),
    32: dataclasses.replace(MIXER, n_heads=32, q_rank=None,
                            scale_q_lora=False, head_gate=True)}
heads = pytest.mark.parametrize("n_heads", list(BY_HEADS),
                                ids=lambda h: f"{h}-heads")


# the loss of `test_training_through_the_forward_is_the_parents` and its
# gradient's norm by leaf as the PARENT of PR 48 computes them (its
# checkout at c511a3c under this file's `_params` and `_x`, on the CPU
# under x64, as `conftest.py` sets it)
PARENT_GRADIENTS = {
    4: (1.9019822190507227, {
        "Wkb": 0.6376142695503351, "Wkr": 0.3802957251456847,
        "Wkvc": 1.6468304808787475, "Wo": 0.685206151384827,
        "Wqa": 1.1112564869412824, "Wqn": 0.6582311431813809,
        "Wqr": 0.22858757317118558, "Wvb": 0.8512571896333411,
        "kvn_w": 0.7027468144284919, "qn_w": 0.20423964369677153}),
    32: (1.2408171880238354, {
        "Wa": 0.1385942009937708, "Wkb": 0.14171130026453743,
        "Wkr": 0.10405339072378934, "Wkvc": 0.5282793152893134,
        "Wo": 0.71624989624226, "Wqn": 0.2249589082793907,
        "Wqr": 0.07191471657507807, "Wvb": 0.19943235601383444,
        "kvn_w": 0.20271327238101983})}


def _params(mixer=MIXER, seed=0):
    p = mixer.init_params(
        jax.random.PRNGKey(seed), D, jnp.float32,
        lambda k, shape, fi, fo: jax.random.normal(k, shape) / fi ** 0.5)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    if "qn_w" in p:
        p["qn_w"] = 1.0 + 0.1 * jax.random.normal(k1, p["qn_w"].shape)
    p["kvn_w"] = 1.0 + 0.1 * jax.random.normal(k2, p["kvn_w"].shape)
    return p


def _x(seed=3, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, D))


def _reference(p, x, c=CONSTS, n_heads=MIXER.n_heads):
    names = {"Wqa": "Wqa0", "qn_w": "qn0", "Wqn": "Wqn0", "Wqr": "Wqr0",
             "Wkvc": "Wkvc0", "Wkr": "Wkr0", "kvn_w": "kvn0", "Wkb": "Wkb0",
             "Wvb": "Wvb0", "Wo": "Wo0"}
    with jax.default_matmul_precision("highest"):
        return ref.mla({names[k]: v for k, v in p.items()}, 0, x[0],
                       jnp.arange(x.shape[1]), c, n_heads=n_heads,
                       eps=MIXER.eps, precision="float32")


def test_the_kind_round_trips_through_json():
    kind = LatentAttentionMixer(n_heads=64, q_rank=1536, kv_rank=512,
                                nope_dim=128, rope_dim=64, v_dim=128,
                                rope_theta=1e7, scale_q_lora=True,
                                scale_kv_lora=True)
    d = json.loads(json.dumps(kind.to_json()))
    assert d["kind"] == "latent_attention"
    assert kind_from_json(d) == kind
    assert kind.state == "latent"
    assert kind.latent_geometry() == (512, 64)
    assert abs(kind.sm_scale - 192 ** -0.5) < 1e-12


@heads
def test_the_expanded_forward_equals_the_reference(n_heads):
    """At the toy's four heads and at the three cells' head counts (the
    reference is LongCat's: its flavour at every count; the other two
    flavours are held to their own in `test_deepseek_v2.py` and
    `test_ling_flash.py`)."""
    mixer = dataclasses.replace(MIXER, n_heads=n_heads)
    p, x = _params(mixer), _x()
    np.testing.assert_allclose(mixer.forward(p, x)[0],
                               _reference(p, x, n_heads=n_heads), atol=2e-5)


@pytest.mark.parametrize("drop", ["rope_theta", "scale_q_lora",
                                  "scale_kv_lora"])
def test_rotary_and_both_lora_scales_matter(drop):
    """Each is in the arithmetic: without it the forward leaves the
    reference by far more than the tolerance."""
    p, x = _params(), _x()
    broken = dataclasses.replace(
        MIXER, **{drop: 1.0 if drop == "rope_theta" else False})
    off = np.max(np.abs(broken.forward(p, x)[0] - _reference(p, x)))
    assert off > 100 * 2e-5


@heads
def test_the_three_forms_agree(n_heads):
    """The whole sequence expanded; its second half as a chunk of
    absorbed queries against the cached latents; its last position as
    the absorbed one-token step, and that step as the decode program
    runs it: one query a SLOT, no sequence axis."""
    mixer = BY_HEADS[n_heads]
    p, x = _params(mixer), _x()
    want = mixer.forward(p, x)[0]
    pos = jnp.arange(T)
    q_n, q_r, latent = mixer.project(p, x, pos)
    # the chunk form: positions 10.. against every cached latent (those
    # past a query's position are masked)
    q_abs = mixer.absorb(p, q_n[:, 10:], q_r[:, 10:])
    got = mixer.out(p, mixer.attend_latents(q_abs, latent, pos[None, 10:]),
                    x[:, 10:])
    np.testing.assert_allclose(got[0], want[10:], atol=2e-5)
    # the absorbed step: ONE query, projected alone at its own position
    q_n1, q_r1, lat1 = mixer.project(p, x[:, -1:], pos[None, -1:])
    np.testing.assert_allclose(lat1[0, 0], latent[0, -1], atol=1e-6)
    q_abs1 = mixer.absorb(p, q_n1, q_r1)
    u = mixer.attend_latents(q_abs1, latent, pos[None, -1:])
    step = mixer.out(p, u, x[:, -1:])
    np.testing.assert_allclose(step[0, 0], want[-1], atol=2e-5)
    # `LatentPages.mix_decode`'s shapes: (S, H, .) in, (S, d) out
    np.testing.assert_array_equal(
        mixer.absorb(p, q_n1[:, 0], q_r1[:, 0]), q_abs1[:, 0])
    np.testing.assert_allclose(mixer.out(p, u[:, 0], x[:, -1]), step[:, 0],
                               atol=1e-6)


@pytest.mark.parametrize("n_heads", (4, 32), ids=lambda h: f"{h}-heads")
def test_training_through_the_forward_is_the_parents(n_heads, monkeypatch):
    """`project` pins its two query products as written
    (`decoder_block._as_written`, an optimization barrier): the identity
    to the loss and to its gradient by every leaf and by the input.
    Against the same forward with the barrier taken out, which is the
    parent's program, and against the parent's own numbers (its checkout
    at c511a3c, this file's `_params` and `_x`, on the CPU under x64)."""
    from deeplearning4j_tpu.nn.conf import decoder_block

    mixer = BY_HEADS[n_heads]
    p, x = _params(mixer), _x()

    def traced_now():  # a new function each time: nothing traced before
        def loss(p, x):
            y = mixer.forward(p, x)
            return jnp.mean((y - jnp.roll(x, 1, axis=1)) ** 2)
        return loss

    value, (gp, gx) = jax.value_and_grad(traced_now(), argnums=(0, 1))(p, x)
    assert "optimization_barrier" in str(jax.make_jaxpr(traced_now())(p, x))
    monkeypatch.setattr(decoder_block, "_as_written", lambda a: a)
    assert "optimization_barrier" not in str(
        jax.make_jaxpr(traced_now())(p, x))
    want, (wp, wx) = jax.value_and_grad(traced_now(), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(value, want, rtol=1e-6)
    np.testing.assert_allclose(gx, wx, rtol=1e-5, atol=1e-8)
    for name in p:
        np.testing.assert_allclose(gp[name], wp[name], rtol=1e-5, atol=1e-8,
                                   err_msg=name)
    norms = {name: float(jnp.linalg.norm(g)) for name, g in gp.items()}
    parent_value, parent_norms = PARENT_GRADIENTS[n_heads]
    np.testing.assert_allclose(value, parent_value, rtol=1e-4)
    assert norms.keys() == parent_norms.keys()
    for name, n in parent_norms.items():
        np.testing.assert_allclose(norms[name], n, rtol=1e-4, err_msg=name)


def test_a_cached_latent_is_the_normed_latent_and_the_turned_rope_key():
    p, x = _params(), _x()
    _, _, latent = MIXER.project(p, x, jnp.arange(T))
    assert latent.shape == (1, T, 16 + 4)
    c = x[0] @ p["Wkvc"]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + MIXER.eps) \
        * p["kvn_w"]
    np.testing.assert_allclose(latent[0, :, :16], c, atol=1e-5)
    # the program keeps the turned pairs evens-first; the norm of each
    # pair is what a rotation leaves alone
    raw = x[0] @ p["Wkr"]
    k_r = raw.reshape(T, 2, 2)
    got = latent[0, :, 16:].reshape(T, 2, 2)        # [evens | odds]
    np.testing.assert_allclose(
        got[:, 0] ** 2 + got[:, 1] ** 2,
        k_r[..., 0] ** 2 + k_r[..., 1] ** 2, atol=1e-5)
    np.testing.assert_allclose(latent[0, 0, 16:],
                               raw[0].reshape(2, 2).T.reshape(-1),
                               atol=1e-6)        # position 0: no turn


# ------------------------------------------------------------ the kernels
PAGE, R, KV, H = 8, 20, 16, 4


def _pool_case(seed=0, S=3):
    """Slots at positions 7 (a page's last), 8 (the next one's first)
    and 19, over a table four pages wide whose dead entries name a page
    of NaNs."""
    rng = np.random.default_rng(seed)
    pos = np.asarray([7, 8, 19][:S], np.int32)
    live = pos // PAGE + 1
    P = int(live.sum())
    dead = P + 1
    pt = np.full((S, 4), dead, np.int32)
    at = 1
    for s, n in enumerate(live):
        pt[s, :n] = at + np.arange(n)
        at += n
    pool = jnp.asarray(rng.standard_normal((P + 2, R, PAGE)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((S, H, R)), jnp.float32)
    return q, pool, pt, pos, dead


def test_the_attend_kernel_equals_gather_and_attend():
    q, pool, pt, pos, dead = _pool_case()
    kw = dict(kv_rank=KV, sm_scale=0.3)
    want = mla.mla_attend_xla(q, pool, jnp.asarray(np.where(pt == dead, 0,
                                                            pt)),
                              jnp.asarray(pos), **kw)
    got = mla.mla_attend(q, pool.at[dead].set(jnp.nan), jnp.asarray(pt),
                         jnp.asarray(pos), jnp.ones(3, bool),
                         interpret=True, **kw)
    # a page past a slot's live ones is never read: no NaN came through
    np.testing.assert_allclose(got, want, atol=2e-5)


B = mla.block_pages(PAGE, R, H, jnp.float32)   # pages an iteration takes


def _walk_case(lives, ends, seed=0):
    """Slots of `lives[s]` live pages ending on their last page's
    `ends[s]` ("last" / "first") entry, over a table two entries wider
    than the longest whose dead entries name a page of NaNs."""
    rng = np.random.default_rng(seed)
    lives = np.asarray(lives)
    pos = np.asarray([n * PAGE - 1 if e == "last" else (n - 1) * PAGE
                      for n, e in zip(lives, ends)], np.int32)
    P = int(lives.sum())
    dead = P + 1
    pt = np.full((len(lives), int(lives.max()) + 2), dead, np.int32)
    at = 1
    for s, n in enumerate(lives):
        pt[s, :n] = at + np.arange(n)
        at += n
    pool = jnp.asarray(rng.standard_normal((P + 2, R, PAGE)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((len(lives), H, R)), jnp.float32)
    return q, pool, pt, pos, dead


def _kernel_and_gather(q, pool, pt, pos, dead, active):
    """The kernel over the pool with its NaN page, gather-and-attend
    over the same pool with the dead entries turned to the trash page."""
    kw = dict(kv_rank=KV, sm_scale=0.3)
    want = mla.mla_attend_xla(
        q, pool, jnp.asarray(np.where(pt == dead, 0, pt)), jnp.asarray(pos),
        **kw)
    got = mla.mla_attend(q, pool.at[dead].set(jnp.nan), jnp.asarray(pt),
                         jnp.asarray(pos), jnp.asarray(active),
                         interpret=True, **kw)
    return got, want


def test_the_block_is_a_few_pages_and_fits():
    assert B == 8                  # toy pages: the unrolled copies' bound
    assert mla.block_pages(128, 576, 64, jnp.bfloat16) \
        == mla.BLOCK_POSITIONS // 128
    assert mla.block_pages(1024, 576, 64, jnp.bfloat16) == 1


@pytest.mark.parametrize("end", ["last", "first"])
@pytest.mark.parametrize("n_live", [1, B - 1, B, B + 1, 2 * B + 1])
def test_a_walk_of_blocks_equals_gather_and_attend(n_live, end):
    """A slot of `n_live` pages between two others: whole blocks, a
    part-empty last block, one page more than a block."""
    case = _walk_case([2, n_live, 3], ["first", end, "last"], seed=n_live)
    got, want = _kernel_and_gather(*case, [True] * 3)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_hand_over_crosses_an_inactive_slot():
    """The slot after an inactive one starts its own first copies, into
    the buffer the walk before left free."""
    q, pool, pt, pos, dead = _walk_case([B + 1, 2, B + 2],
                                        ["last", "first", "first"])
    pt[1] = dead
    got, want = _kernel_and_gather(q, pool, pt, pos, dead,
                                   [True, False, True])
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(got[1] == 0)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-5)


def test_a_part_empty_block_after_a_full_one_sees_no_stale_lane():
    """A short first slot (its block's other lanes are the zeros the
    call began with), whole blocks in both buffers, then part-empty
    blocks over what those left: the mask covers pool pages, and a dead
    entry's NaN page is in no buffer."""
    case = _walk_case([1, B, B + 1, 2 * B + 1, 2],
                      ["first", "last", "first", "last", "first"])
    got, want = _kernel_and_gather(*case, [True] * 5)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_probe_walks_part_empty_and_many_blocks(monkeypatch):
    """The dispatch probe's own slots, through the kernel in interpret
    mode: a page past one block, more than two blocks, an inactive slot,
    the dead entries naming the NaN page."""
    import functools

    monkeypatch.setattr(mla, "mla_attend", functools.partial(
        mla.mla_attend, interpret=True))
    assert mla._attend_probe(jnp.float32, H, R, KV, PAGE, 0.3)
    assert mla.attend_key(jnp.float32, H, R, KV, PAGE) \
        == ("float32", H, R, KV, PAGE, f"block{B}")


def test_gather_and_attend_is_the_mixers_chunk_form():
    q, pool, pt, pos, dead = _pool_case()
    pt = jnp.asarray(np.where(pt == dead, 0, pt))
    want = mla.mla_attend_xla(q, pool, pt, jnp.asarray(pos), kv_rank=KV,
                              sm_scale=MIXER.sm_scale)
    lat = mla.gather_latents(pool, pt)
    got = MIXER.attend_latents(q[:, None], lat, jnp.asarray(pos)[:, None])
    np.testing.assert_allclose(got[:, 0], want, atol=2e-5)


def test_an_inactive_slot_reads_no_page_and_comes_out_zeros():
    q, pool, pt, pos, dead = _pool_case()
    pt[1] = dead                         # its whole row names the NaN page
    got = mla.mla_attend(q, pool.at[dead].set(jnp.nan), jnp.asarray(pt),
                         jnp.asarray(pos), jnp.asarray([True, False, True]),
                         kv_rank=KV, sm_scale=0.3, interpret=True)
    assert np.all(np.isfinite(got))
    assert np.all(np.asarray(got[1]) == 0)
    assert np.any(np.asarray(got[0]) != 0)


def test_the_write_kernel_equals_the_scatter_off_the_trash_page():
    rng = np.random.default_rng(1)
    got = want = jnp.asarray(rng.standard_normal((5, R, PAGE)), jnp.float32)
    pids = jnp.asarray([3, 0, 1, 0], jnp.int32)     # two collide on page 0
    for loff in ([0, 5, PAGE - 2, 7], [1, 6, PAGE - 1, 0]):
        new = jnp.asarray(rng.standard_normal((4, R)), jnp.float32)
        loff = jnp.asarray(loff, jnp.int32)
        got = mla.latent_write(got, new, pids, loff, interpret=True)
        want = mla.latent_write_xla(want, new, pids, loff)
    np.testing.assert_array_equal(got[1:], want[1:])
    # pages nobody named are as they were
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[4], want[4])


def test_the_kernels_never_dispatch_on_the_cpu():
    q, pool, pt, pos, _ = _pool_case()
    assert mla.mla_attend_or_none(q, pool, jnp.asarray(pt),
                                  jnp.asarray(pos), jnp.ones(3, bool),
                                  kv_rank=KV, sm_scale=0.3) is None
    assert mla.latent_write_or_none(pool, q[:, 0], jnp.zeros(3, jnp.int32),
                                    jnp.zeros(3, jnp.int32)) is None


def test_the_kernel_bench_rehearses_in_interpret_mode(tmp_path, capsys):
    """`tools/mla_attend_bench.py` end to end at toy shapes, so that a
    chip call is not lost to a typo: a row a block and context, each
    within rounding of gather-and-attend, and no time printed as a
    device's."""
    from tools import mla_attend_bench as bench

    out = tmp_path / "bench.json"
    assert bench.main(
        ["--slots", "3", "--heads", str(H), "--kv-rank", str(KV), "--rope",
         str(R - KV), "--page", str(PAGE), "--dtype", "float32",
         "--contexts", "9,20,5-40", "--blocks", "1,2,0", "--calls", "2",
         "--iters", "1", "--interpret", "--out", str(out)]) == 0
    table = json.loads(out.read_text())
    assert [r["block"] for r in table["rows"]] == [1] * 3 + [2] * 3 + [B] * 3
    assert all(r["gap_to_xla"] < 2e-5 for r in table["rows"])
    assert not any("call_ms" in r or "roofline_pct" in r
                   for r in table["rows"]) and table["fits"] == []
    assert len(capsys.readouterr().out.splitlines()) == 9
    # with times, a block's rows fit to a cost a slot and a cost a page
    rows = [{"block": 4, "live_pages": n, "slot_us": 1.0 + 0.25 * n}
            for n in (5, 8, 13)]
    (fit,) = bench.fits(rows)
    assert abs(fit["fit_slot_us"] - 1.0) < 1e-9
    assert abs(fit["fit_page_us"] - 0.25) < 1e-9
