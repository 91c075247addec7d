"""The grouped expert kernel's hit-first walk (`ops/pallas_moe_experts`),
in interpret mode, both variants: it reads the experts it is told were
hit and no others, rows that chose only hit experts come out bit for bit
as from a walk over every expert, and `dropless_moe` tells it what the
rows that count chose. Also the benchmark's reader of the counter that
says so."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import pallas_moe_experts as pme
from deeplearning4j_tpu.parallel import experts

N, D, F, E = 16, 128, 40, 6
VARIANTS = (pme.GATED_SILU, pme.RELU2)


def _weights(act, seed=0, n_experts=E):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    up = (n_experts, F, D) if act == pme.RELU2 else (n_experts, D, F)
    Wg = None if act == pme.RELU2 else \
        jax.random.normal(k[0], up, jnp.float32) / 11
    Wu = jax.random.normal(k[1], up, jnp.float32) / 11
    Wd = jax.random.normal(k[2], (n_experts, F, D), jnp.float32) / F ** 0.5
    return jax.random.normal(k[3], (N, D), jnp.float32), Wg, Wu, Wd


def _gates(unchosen, seed=1):
    """Every row weighs every expert but the `unchosen` ones."""
    g = jax.random.uniform(jax.random.PRNGKey(seed), (N, E), jnp.float32,
                           0.1, 1.0)
    return g.at[:, list(unchosen)].set(0.0)


def _through_the_kernel(monkeypatch):
    """What a TPU would dispatch, interpreted."""
    monkeypatch.setattr(
        pme, "moe_experts_or_none",
        lambda x, gates, Wg, Wu, Wd, hit, act=pme.GATED_SILU:
        pme.moe_experts(x, gates, Wg, Wu, Wd, hit, act=act, interpret=True))


@pytest.mark.parametrize("hit", [
    (0, 1, 0, 1), (0, 0, 0, 0), (1, 1, 1, 1), (1, 0, 0, 0), (0, 0, 0, 1),
    (0, 1, 1, 0, 0, 1, 0), (1,), (0,),
], ids=lambda h: "".join(map(str, h)))
def test_the_walk_names_the_hit_experts_first_and_in_order(hit):
    walk, n_hit = pme.hit_first_walk(jnp.asarray(hit, bool))
    idx = np.flatnonzero(hit)
    # past the last hit expert that one again, so that nothing is copied
    want = np.full(len(hit), idx[-1] if len(idx) else len(hit) - 1)
    want[:len(idx)] = idx
    np.testing.assert_array_equal(walk, want)
    assert n_hit.tolist() == [len(idx)]
    # Mosaic takes 32-bit block indices only, and the suite runs under x64
    assert walk.dtype == n_hit.dtype == jnp.int32


@pytest.mark.parametrize("unchosen", [(), (2,), (0, 3, 5), tuple(range(E))],
                         ids=["none", "one", "some", "all"])
@pytest.mark.parametrize("act", VARIANTS)
def test_the_walk_equals_the_batched_products(act, unchosen):
    x, Wg, Wu, Wd = _weights(act)
    gates = _gates(unchosen)
    hit = jnp.any(gates != 0, axis=0)
    assert int(hit.sum()) == E - len(unchosen)
    got = pme.moe_experts(x, gates, Wg, Wu, Wd, hit, act=act,
                          interpret=True)
    want = experts.grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd, act)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # an expert left out added exactly 0.0: bit for bit the walk over all
    every = pme.moe_experts(x, gates, Wg, Wu, Wd, jnp.ones(E, bool),
                            act=act, interpret=True)
    assert np.array_equal(got, every)
    if len(unchosen) == E:
        assert not np.any(np.asarray(got))
    else:
        assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.mark.parametrize("act", VARIANTS)
def test_an_unmarked_expert_is_not_read(act):
    """The kernel believes `hit`, not the gates: poison in an unmarked
    expert's matrices reaches no row, where the walk over all spreads
    it."""
    x, Wg, Wu, Wd = _weights(act)
    gates = _gates((2,))
    Wd = Wd.at[2].set(jnp.nan)
    hit = jnp.any(gates != 0, axis=0)
    got = pme.moe_experts(x, gates, Wg, Wu, Wd, hit, act=act,
                          interpret=True)
    assert np.all(np.isfinite(got))
    every = pme.moe_experts(x, gates, Wg, Wu, Wd, jnp.ones(E, bool),
                            act=act, interpret=True)
    assert np.all(np.isnan(every))


@pytest.mark.parametrize("act", VARIANTS)
def test_rows_nobody_reads_choose_nothing_for_the_kernel(act, monkeypatch):
    """A decode step's inactive slots route like any row; what only they
    chose is not read, the live rows come out bit for bit as without the
    mask, and the counts say what was read."""
    _through_the_kernel(monkeypatch)
    n_experts, live_rows = 8, 3
    x, Wg, Wu, Wd = _weights(act, seed=3, n_experts=n_experts)
    router = jax.random.normal(jax.random.PRNGKey(7), (D, n_experts))
    kw = dict(top_k=1, experts_held=(0, n_experts), act=act)
    live = jnp.arange(N) < live_rows
    y, counts = experts.dropless_moe(x, router, Wg, Wu, Wd,
                                     count_mask=live, **kw)
    every, none = experts.dropless_moe(x, router, Wg, Wu, Wd, **kw)
    assert none is None
    chosen, read = np.asarray(counts.experts)
    gates = experts.held_gates(x @ router, 1, (0, n_experts))
    np.testing.assert_array_equal(
        chosen, np.sum(np.asarray(gates)[:live_rows] != 0, axis=0))
    np.testing.assert_array_equal(read, chosen > 0)
    # the dead rows chose experts that no live row did
    assert read.sum() < np.any(np.asarray(gates) != 0, axis=0).sum()
    assert np.array_equal(y[:live_rows], every[:live_rows])
    assert not np.array_equal(y[live_rows:], every[live_rows:])
    np.testing.assert_allclose(
        every, experts.grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd, act),
        atol=2e-5)


@pytest.mark.parametrize("stats,want", [
    ({"moe_steps": 5, "moe_experts_read": 5 * 3 * 48}, 75.0),
    ({"moe_steps": 5}, None),                 # a program without it
    ({"moe_steps": 0, "moe_experts_read": 0}, None),
], ids=["read", "no-counter", "no-steps"])
def test_the_benchmark_reads_the_share_told_to_be_read(stats, want):
    from perfbench.harness.manifest import Manifest

    read = Manifest().reader("moe.experts_read_pct.chat")
    after = dict(stats, moe_experts_held=3 * 64)
    run = SimpleNamespace(facts={"stats_before": {k: 0 for k in stats},
                                 "stats_after": after})
    assert read(run) == want


# ------------------------------------------------------- tiles of the width
@pytest.mark.parametrize("unchosen", [(), (0, 3, 5), tuple(range(E))],
                         ids=["none", "some", "all"])
@pytest.mark.parametrize("tf", (8, 20), ids=["5-tiles", "2-tiles"])
@pytest.mark.parametrize("act", VARIANTS)
def test_the_tiled_walk_equals_the_batched_products(act, tf, unchosen):
    """An expert brought in tiles of its width `f` (40 = 5 x 8 = 2 x 20)
    sums to the whole expert's product, under the same hit-first walk."""
    x, Wg, Wu, Wd = _weights(act)
    gates = _gates(unchosen)
    hit = jnp.any(gates != 0, axis=0)
    got = pme.moe_experts(x, gates, Wg, Wu, Wd, hit, act=act, tf=tf,
                          interpret=True)
    want = experts.grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd, act)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # one tile is the program it always was
    whole = pme.moe_experts(x, gates, Wg, Wu, Wd, hit, act=act,
                            interpret=True)
    np.testing.assert_allclose(got, whole, atol=2e-5)
    every = pme.moe_experts(x, gates, Wg, Wu, Wd, jnp.ones(E, bool),
                            act=act, tf=tf, interpret=True)
    assert np.array_equal(got, every)


@pytest.mark.parametrize("act", VARIANTS)
def test_the_tiled_walk_skips_an_unmarked_expert(act):
    x, Wg, Wu, Wd = _weights(act)
    gates = _gates((2, 5))               # the last expert among them
    Wd = Wd.at[2].set(jnp.nan).at[5].set(jnp.nan)
    Wu = Wu.at[5].set(jnp.nan)
    hit = jnp.any(gates != 0, axis=0)
    got = pme.moe_experts(x, gates, Wg, Wu, Wd, hit, act=act, tf=8,
                          interpret=True)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(
        got, experts.grouped_expert_ffn_xla(
            x, gates, Wg, jnp.nan_to_num(Wu), jnp.nan_to_num(Wd), act),
        atol=2e-5)


@pytest.mark.parametrize("rows,d,f,act,want", [
    (64, 4096, 768, pme.GATED_SILU, 768),     # granite-4.0-h-small, decode
    (512, 4096, 768, pme.GATED_SILU, 768),    # ... its widest prefill tile
    (64, 2688, 1856, pme.RELU2, 1856),        # nemotron-3-nano, decode
    (512, 2688, 1856, pme.RELU2, 1856),
    (128, 6144, 2048, pme.GATED_SILU, 1024),  # 75.5 MB an expert: 2 tiles
    (512, 6144, 2048, pme.GATED_SILU, 512),   # beside a 512-row token tile
    (128, 8192, 8192, pme.GATED_SILU, 1024),
], ids=["granite-decode", "granite-prefill", "nemotron-decode",
        "nemotron-prefill", "d6144-f2048-decode", "d6144-f2048-prefill",
        "d8192-f8192"])
def test_the_tile_rule(rows, d, f, act, want):
    """Shapes that fit whole take one tile, as they always did (the
    two-axis program); an expert over the VMEM ceiling takes the largest
    tile on the grid that fits."""
    tf = pme.f_tile(rows, d, f, jnp.bfloat16, act)
    assert tf == want
    limit = pme._vmem_limit()
    assert pme.vmem_bytes_estimate(rows, d, tf, jnp.bfloat16, act) <= limit
    if tf < f:
        assert pme.vmem_bytes_estimate(rows, d, f, jnp.bfloat16, act) > limit
        assert f % tf == 0 and tf % 128 == 0


def test_a_width_with_no_tile_that_fits_is_declined():
    assert pme.f_tile(512, 1 << 17, 256, jnp.bfloat16) == 0


@pytest.mark.parametrize("act", VARIANTS)
def test_one_tile_keeps_the_two_axis_grid(act):
    """A width brought whole is the program it always was: (token tiles,
    experts), three (or two) whole matrices a step; tiles add a third,
    innermost axis."""
    grid, in_specs, _ = pme._grid_specs(64, 256, 6, 128, 64, 128, act)
    assert grid == (1, 6)
    assert [s.block_shape for s in in_specs[2:]] == (
        [(1, 128, 256)] * 2 if act == pme.RELU2
        else [(1, 256, 128)] * 2 + [(1, 128, 256)])
    grid, in_specs, _ = pme._grid_specs(64, 256, 6, 128, 64, 32, act)
    assert grid == (1, 6, 4)
    assert in_specs[-1].block_shape == (1, 32, 256)


# ------------------------------------------------- the sorted product
def _sorted_case(n=700, n_experts=12, k=3, seed=5, held=(0, 12)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, D))
    router = jax.random.normal(ks[1], (D, n_experts))
    Wg, Wu = (jax.random.normal(kk, (n_experts, D, F)) / 8 for kk in ks[2:4])
    Wd = jax.random.normal(ks[4], (n_experts, F, D)) / 5
    gates = experts.held_gates(x @ router, k, held)
    lo, cnt = held
    return x, gates, Wg[lo:lo + cnt], Wu[lo:lo + cnt], Wd[lo:lo + cnt]


def _sorted_interpreted(monkeypatch):
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    calls = []

    def served(xs, gs, tile_expert, n_used, Wg, Wu, Wd):
        calls.append((xs.shape, int(n_used[0])))
        return pme.moe_experts_sorted(xs, gs, tile_expert, n_used, Wg, Wu,
                                      Wd, interpret=True)

    monkeypatch.setattr(pme, "moe_experts_sorted_or_none", served)
    return calls


@pytest.mark.parametrize("held", [(0, 12), (4, 6)], ids=["all", "a-share"])
def test_the_sorted_product_equals_the_batched_products(held, monkeypatch):
    """A prefill's rows sorted by expert, each expert over its own rows
    only: the sum the batched products give, whether every expert is held
    or a share (rows that chose no held expert come out zeros)."""
    calls = _sorted_interpreted(monkeypatch)
    x, gates, Wg, Wu, Wd = _sorted_case(held=held)
    want = experts.grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd)
    got = experts.sorted_expert_ffn_or_none(x, gates, Wg, Wu, Wd, 3)
    np.testing.assert_allclose(got, want, atol=2e-5)
    (shape, n_used), = calls
    E = held[1]
    assert shape == ((-(-700 * 3 // 128) + E) * 128, D)
    # the tiles that hold rows: every expert's choices in whole tiles
    counts = np.sum(np.asarray(gates) != 0, axis=0)
    assert n_used == int(np.sum(-(-counts // 128)))
    none = np.all(np.asarray(gates) == 0, axis=1)
    assert none.any() == (held != (0, 12))
    assert not np.any(np.asarray(got)[none])


def test_the_sort_lays_every_choice_in_its_experts_tiles():
    x, gates, *_ = _sorted_case(n=300, n_experts=5, k=2, held=(0, 5))
    rows, gs, tile_expert, n_used, back = experts.sort_by_expert(gates, 2, 16)
    rows, gs, tile_expert, back = map(np.asarray,
                                      (rows, gs[:, 0], tile_expert, back))
    g = np.asarray(gates)
    assert rows.shape == ((-(-300 * 2 // 16) + 5) * 16,)
    assert back.shape == (300, 2)
    used = int(n_used[0]) * 16
    assert not gs[used:].any()
    for at in np.flatnonzero(gs):
        assert gs[at] == g[rows[at], tile_expert[at // 16]]
    # every choice made is somewhere, once
    assert np.count_nonzero(gs) == np.count_nonzero(g)
    np.testing.assert_allclose(gs[back].sum(1), g.sum(1), rtol=1e-6)
    assert np.all(np.diff(tile_expert[:used // 16]) >= 0)
    assert np.all(tile_expert[used // 16:] == tile_expert[used // 16 - 1])


def test_the_sorted_product_serves_prefills_of_sparse_choices_only():
    from deeplearning4j_tpu.ops.pallas_moe_experts import sorted_serves

    # DeepSeek-V2's share: 20 held, top-6; a prefill, not a decode step
    assert sorted_serves(4096, 20, 6, "gated_silu")
    assert sorted_serves(1024, 20, 6, "gated_silu")
    assert not sorted_serves(128, 20, 6, "gated_silu")
    # granite's, LongCat's and nemotron's prefills keep the walk
    assert not sorted_serves(512, 36, 10, "gated_silu")
    assert not sorted_serves(1024, 16, 12, "gated_silu")
    assert not sorted_serves(512, 64, 6, "relu2")


def test_a_prefill_goes_sorted_and_a_decode_step_walks(monkeypatch):
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    calls = _sorted_interpreted(monkeypatch)
    walked = []
    monkeypatch.setattr(
        pme, "moe_experts_or_none",
        lambda x, *a, **k: walked.append(x.shape) or None)
    x, gates, Wg, Wu, Wd = _sorted_case()
    router = jax.random.normal(jax.random.PRNGKey(1), (D, 12))
    kw = dict(top_k=3, experts_held=(0, 12))
    want, _ = experts.dropless_moe(x[:64], router, Wg, Wu, Wd, **kw)
    assert walked == [(64, D)] and not calls
    got, _ = experts.dropless_moe(x, router, Wg, Wu, Wd, **kw)
    assert len(calls) == 1 and len(walked) == 1
    np.testing.assert_allclose(got[:64], want, atol=2e-5)
