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
def _sorted_case(n=700, n_experts=12, k=3, seed=5, held=(0, 12),
                 act=pme.GATED_SILU):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, D))
    router = jax.random.normal(ks[1], (D, n_experts))
    up = (n_experts, F, D) if act == pme.RELU2 else (n_experts, D, F)
    Wg, Wu = (jax.random.normal(kk, up) / 8 for kk in ks[2:4])
    Wd = jax.random.normal(ks[4], (n_experts, F, D)) / 5
    gates = experts.held_gates(x @ router, k, held)
    lo, cnt = held
    return x, gates, None if act == pme.RELU2 else Wg[lo:lo + cnt], \
        Wu[lo:lo + cnt], Wd[lo:lo + cnt]


def _sorted_interpreted(monkeypatch, tf=0):
    calls = []

    def served(xs, gs, tile_expert, n_used, Wg, Wu, Wd,
               act=pme.GATED_SILU):
        calls.append((xs.shape, n_used))
        return pme.moe_experts_sorted(xs, gs, tile_expert, n_used, Wg, Wu,
                                      Wd, act=act, tf=tf, interpret=True)

    monkeypatch.setattr(pme, "moe_experts_sorted_or_none", served)
    return calls


@pytest.mark.parametrize("held", [(0, 12), (4, 6)], ids=["all", "a-share"])
@pytest.mark.parametrize("tf", (0, 8, 20), ids=["whole", "5-tiles",
                                                "2-tiles"])
@pytest.mark.parametrize("act", VARIANTS)
def test_the_sorted_product_equals_the_batched_products(act, tf, held,
                                                        monkeypatch):
    """A prefill's rows sorted by expert, each expert over its own rows
    only, whole or in tiles of its width `f` (40 = 5 x 8 = 2 x 20), gated
    or not: the sum the batched products give, whether every expert is
    held or a share (rows that chose no held expert come out zeros)."""
    calls = _sorted_interpreted(monkeypatch, tf)
    x, gates, Wg, Wu, Wd = _sorted_case(held=held, act=act)
    want = experts.grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd, act)
    got = experts.sorted_expert_ffn_or_none(x, gates, Wg, Wu, Wd, 3, act)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    (shape, n_used), = calls
    E = held[1]
    assert shape == ((-(-700 * 3 // 128) + E) * 128, D)
    # the tiles that hold rows: every expert's choices in whole tiles
    counts = np.sum(np.asarray(gates) != 0, axis=0)
    assert int(n_used[0]) == int(np.sum(-(-counts // 128)))
    none = np.all(np.asarray(gates) == 0, axis=1)
    assert none.any() == (held != (0, 12))
    assert not np.any(np.asarray(got)[none])


@pytest.mark.parametrize("n_used", (0, 1, 3), ids=lambda n: f"{n}-used")
@pytest.mark.parametrize("tf", (0, 8), ids=["whole", "5-tiles"])
@pytest.mark.parametrize("act", VARIANTS)
def test_the_sorted_kernel_leaves_its_unused_tail_alone(act, tf, n_used):
    """Tiles past the used ones bring nothing in (poison in the rows and
    in every expert but the last used tile's reaches nothing), the last
    tile comes out zeros (where a choice not made points), and the used
    tiles are float32 rows of their own expert."""
    tn, T, n_experts = pme.SORTED_ROWS, 6, 4
    _, Wg, Wu, Wd = _weights(act, seed=2, n_experts=n_experts)
    tile_expert = np.array([0, 2, 2, 2, 2, 2], np.int32)
    xs = jax.random.normal(jax.random.PRNGKey(4), (T * tn, D))
    gs = jax.random.uniform(jax.random.PRNGKey(5), (T * tn, 1))
    clean = (xs, Wu, Wd)
    xs = xs.at[n_used * tn:].set(jnp.nan)
    unread = [e for e in range(n_experts)
              if e not in tile_expert[:max(n_used, 1)]]
    Wu, Wd = (w.at[jnp.asarray(unread)].set(jnp.nan) for w in (Wu, Wd))
    got = pme.moe_experts_sorted(
        xs, gs, jnp.asarray(tile_expert), jnp.asarray([n_used], jnp.int32),
        Wg, Wu, Wd, act=act, tf=tf, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (T * tn, D)
    assert not np.any(np.asarray(got[-tn:]))
    used = n_used * tn
    assert np.all(np.isfinite(got[:used]))
    xs, Wu, Wd = clean
    gates = np.zeros((T * tn, n_experts), np.float32)
    for t in range(n_used):
        gates[t * tn:(t + 1) * tn, tile_expert[t]] = \
            gs[t * tn:(t + 1) * tn, 0]
    want = experts.grouped_expert_ffn_xla(xs, jnp.asarray(gates), Wg, Wu,
                                          Wd, act)
    np.testing.assert_allclose(got[:used], want[:used], atol=2e-5)


def test_the_sort_lays_every_choice_in_its_experts_tiles():
    x, gates, *_ = _sorted_case(n=300, n_experts=5, k=2, held=(0, 5))
    rows, gs, tile_expert, n_used, back, fits = experts.sort_by_expert(
        gates, 2, 16)
    assert bool(fits)
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


@pytest.mark.parametrize("n,n_experts,k,held", [
    (64, 4, 4, (0, 4)),       # every row chooses every expert
    (33, 3, 1, (0, 3)),
    (256, 8, 8, (0, 8)),
    (40, 6, 3, (2, 1)),       # one expert held
], ids=["dense", "top-1", "all-of-8", "one-held"])
def test_no_routing_fills_the_last_tile(n, n_experts, k, held):
    """The static size is the worst case and a tile to spare: the last
    tile never holds a row, so it is where a choice not made points and
    what the kernel zeroes."""
    x, gates, *_ = _sorted_case(n=n, n_experts=n_experts, k=k, held=held)
    kk = min(k, held[1])
    tile = 16
    rows, gs, tile_expert, n_used, back, fits = experts.sort_by_expert(
        gates, kk, tile)
    assert bool(fits) and int(n_used[0]) < rows.shape[0] // tile
    assert not np.asarray(gs)[-tile:].any()
    made = np.asarray(jax.lax.top_k(gates, kk)[0] != 0)
    assert np.all(np.asarray(back)[~made] == rows.shape[0] - 1)
    assert np.all(np.asarray(back)[made] < int(n_used[0]) * tile)


# (rows, held experts, top-k, the router's width): a configuration of
# the benchmark, as its cell holds it
GRANITE, NEMOTRON = (36, 10, 72), (64, 6, 128)
LONGCAT, DSV2 = (16, 12, 768), (20, 6, 160)


@pytest.mark.parametrize("rows,shape,want", [
    (64, GRANITE, False), (128, GRANITE, False), (256, GRANITE, False),
    (512, GRANITE, False),
    (64, NEMOTRON, False), (128, NEMOTRON, False), (256, NEMOTRON, False),
    (512, NEMOTRON, True),
    (128, LONGCAT, False), (256, LONGCAT, False), (512, LONGCAT, True),
    (1024, LONGCAT, True),
    (128, DSV2, False), (1024, DSV2, True), (2048, DSV2, True),
    (4096, DSV2, True),
    # every choice on a held expert, half of them chosen: the walk
    (4096, (8, 4, 8), False),
    # a router as wide as the share held: top-2 of 64
    (4096, (64, 2, 64), True),
    (1024, (0, 0, 0), False),
], ids=lambda v: str(v).replace(" ", ""))
def test_the_sorted_product_serves_where_the_arithmetic_says(rows, shape,
                                                             want):
    """One case a (configuration, bucket) of the benchmark, and the
    decode steps' 64 / 128 rows: shapes alone decide."""
    assert pme.sorted_serves(rows, *shape) == want


def test_the_rule_takes_shapes_and_nothing_a_user_sets():
    import inspect

    assert list(inspect.signature(pme.sorted_serves).parameters) == [
        "N", "E", "k", "router_width"]
    src = inspect.getsource(pme.sorted_serves)
    assert "environ" not in src and "getenv" not in src


@pytest.mark.parametrize("act", VARIANTS)
def test_a_prefill_goes_sorted_and_a_decode_step_walks(act, monkeypatch):
    calls = _sorted_interpreted(monkeypatch)
    walked = []
    monkeypatch.setattr(
        pme, "moe_experts_or_none",
        lambda x, *a, **k: walked.append(x.shape) or None)
    x, gates, Wg, Wu, Wd = _sorted_case(act=act)
    # 12 of the 48 experts the router scores are held
    router = jax.random.normal(jax.random.PRNGKey(1), (D, 48))
    kw = dict(top_k=3, experts_held=(0, 12), act=act)
    want, _ = experts.dropless_moe(x[:64], router, Wg, Wu, Wd, **kw)
    assert walked == [(64, D)] and not calls
    got, _ = experts.dropless_moe(x, router, Wg, Wu, Wd, **kw)
    assert len(calls) == 1 and len(walked) == 1
    np.testing.assert_allclose(got[:64], want, atol=2e-5)


@pytest.mark.parametrize("act", VARIANTS)
def test_a_tokens_rows_are_rounded_once_like_the_walks(act, monkeypatch):
    """In bfloat16 the sorted product's rows stay float32 until a
    token's sum: what comes out is the walk's output to a rounding of
    the sum, where rows rounded one by one would lie several apart."""
    _sorted_interpreted(monkeypatch)
    x, gates, Wg, Wu, Wd = _sorted_case(n=300, k=6, act=act)
    bf = lambda a: None if a is None else a.astype(jnp.bfloat16)
    x, Wg, Wu, Wd = map(bf, (x, Wg, Wu, Wd))
    got = experts.sorted_expert_ffn_or_none(x, gates, Wg, Wu, Wd, 6, act)
    walk = pme.moe_experts(x, gates, Wg, Wu, Wd, jnp.ones(12, bool),
                           act=act, interpret=True)
    assert got.dtype == walk.dtype == jnp.bfloat16
    got, walk = (np.asarray(a, np.float32) for a in (got, walk))
    # both round ONE float32 sum of the same products (summed in another
    # order): at most one bfloat16 step of the value apart
    step = np.maximum(np.abs(walk), 1e-3) * 2.0 ** -7
    assert np.all(np.abs(got - walk) <= step)
    assert np.mean(got == walk) > 0.9


@pytest.mark.parametrize("rows,shape,want", [
    # LongCat: 1 choice in 48 is held: a sixth of the worst case's rows,
    # a third of its choices a row
    (512, LONGCAT, (24, 4)), (1024, LONGCAT, (24, 4)),
    # an eighth, or half, of the router held: a row's choices, the
    # larger copy, would not halve: the worst case and no second branch
    (1024, DSV2, (68, 6)), (4096, DSV2, (212, 6)),
    (512, NEMOTRON, (88, 6)), (512, GRANITE, (76, 10)),
], ids=lambda v: str(v).replace(" ", ""))
def test_the_static_size_of_the_sorted_rows(rows, shape, want):
    E, k, R = shape
    k = min(k, E)
    assert pme.sorted_bound(rows, E, k, R) == want
    worst = pme.sorted_worst(rows, E, k)
    assert worst == (-(-rows * k // 128) + E, k)
    assert want == worst or 2 * want[1] <= k and want[0] < worst[0]


def test_the_sort_says_when_a_routing_overflows_a_smaller_size():
    x, gates, *_ = _sorted_case(n=300, n_experts=5, k=2, held=(0, 5))
    tiles = -(-300 * 2 // 16) + 5
    for t, k, want in ((tiles, 2, True), (tiles - 1, 2, True),
                       (30, 2, False), (tiles, 1, False)):
        *_, back, fits = experts.sort_by_expert(gates, k, 16, t)
        assert bool(fits) == want
        assert int(jnp.max(back)) <= t * 16 - 1


@pytest.mark.parametrize("alike", (False, True), ids=["fits", "overflows"])
def test_a_routing_past_the_static_size_takes_the_walk(alike, monkeypatch):
    """Under a router 32 times as wide as the share held, the sorted
    rows' static size is half the worst case's choices a row and a fifth
    of its rows; tokens that all choose held experts overflow it, and
    the walk gives their sum."""
    n, held, width, k = 1400, 12, 384, 6
    assert pme.sorted_serves(n, held, k, width)
    assert pme.sorted_bound(n, held, k, width) == (16, 3)
    assert pme.sorted_worst(n, held, k) == (78, 6)
    x, gates, Wg, Wu, Wd = _sorted_case(n=n, k=k, n_experts=width
                                        if not alike else held)
    gates, Wg, Wu, Wd = gates[:, :held], Wg[:held], Wu[:held], Wd[:held]
    calls = _sorted_interpreted(monkeypatch)
    # the branch taken shows: the walk's rows come out poisoned
    monkeypatch.setattr(
        pme, "moe_experts_or_none",
        lambda x, gates, Wg, Wu, Wd, hit, act=pme.GATED_SILU:
        jnp.full_like(x, jnp.nan))
    got = jax.jit(lambda *a: experts.grouped_expert_ffn(
        *a, jnp.ones(held, bool), pme.GATED_SILU, k, width))(
        x, gates, Wg, Wu, Wd)
    (shape, _), = calls
    assert shape == (16 * 128, D)
    if alike:
        assert np.all(np.isnan(got))
    else:
        np.testing.assert_allclose(
            got, experts.grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd),
            atol=2e-5)


# ----------------------------------- the four routed nets and their programs
V_TOY = 53
TOY_MOE = dict(n_experts=64, top_k=2, expert_width=32,
               experts_held=(0, 4))
TOY_MAMBA = dict(mamba_heads=8, mamba_head_dim=16, mamba_state=16,
                 mamba_chunk=8)
TOY_MLA = dict(n_heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=8,
               v_dim=8, ffn_width=48)


def _toy_net(name):
    """A toy of each routed configuration of the benchmark: granite's
    hybrid, nemotron's one-sub-layer blocks with ungated experts,
    LongCat's shortcut layer with zero experts, DeepSeek-V2's groups."""
    from deeplearning4j_tpu.models import transformer as T
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = {
        "granite": lambda: T.hybrid_moe_configuration(
            V_TOY, 64, ["mamba", "attention"], n_heads=4, n_kv_heads=2,
            shared_width=48, **TOY_MAMBA, **TOY_MOE),
        "nemotron": lambda: T.hybrid_sublayer_configuration(
            V_TOY, 64, "ME*E", n_heads=4, n_kv_heads=2, head_dim=16,
            shared_width=48, **TOY_MAMBA, **TOY_MOE),
        "longcat": lambda: T.longcat_configuration(
            V_TOY, 64, 1, n_zero_experts=4, **TOY_MLA, **TOY_MOE),
        "dsv2": lambda: T.deepseek_v2_configuration(
            V_TOY, 64, 2, shared_width=48, n_groups=2, topk_groups=1,
            **TOY_MLA, **TOY_MOE),
    }[name]()
    net = MultiLayerNetwork(conf)
    net.init()
    return net


ROUTED_NETS = ("granite", "nemotron", "longcat", "dsv2")


def _decode_jaxprs(net, S=3, page=8, L=32):
    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.serving import block_state, decode_programs

    plan = GPTPlan(net)
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=S, page=page, pool_pages=12, cdt=plan.cdt, kv_quant=None,
        tp_shard=None, tp_axis=None))
    programs = decode_programs.build_programs(
        plan, states, n_slots=S, page=page, L_logical=L, decode_chunk=2,
        top_k=0, logprobs=0, tp=None, donate=False)
    args = (plan.resident_weights(net._params),
            [st.alloc() for st in states],
            jnp.zeros((S, L // page), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32),
            jnp.stack([jax.random.PRNGKey(i) for i in range(S)]),
            jnp.zeros((S,), jnp.float32), jnp.ones((S,), bool))
    return [str(jax.make_jaxpr(fn)(*args))
            for fn in (programs.decode_step, programs.decode_chunked)]


@pytest.mark.parametrize("name", ROUTED_NETS)
def test_the_decode_programs_are_the_walks_whatever_the_rule(name,
                                                             monkeypatch):
    """Every decode step keeps the walk and its program: with the kernels
    a TPU would dispatch traced in, the decode programs of each routed
    net are, character for character, what they are when the sorted
    product does not exist; a rule that said yes to their rows would
    show."""
    _through_the_kernel(monkeypatch)
    _sorted_interpreted(monkeypatch)
    net = _toy_net(name)
    mine = _decode_jaxprs(net)
    monkeypatch.setattr(pme, "sorted_serves", lambda *a: False)
    walks = _decode_jaxprs(net)
    assert mine == walks
    assert all("moe_experts" in j and "moe_experts_sorted" not in j
               for j in mine)
    monkeypatch.setattr(pme, "sorted_serves", lambda *a: True)
    assert all("moe_experts_sorted" in j for j in _decode_jaxprs(net))


@pytest.mark.parametrize("name", ROUTED_NETS)
def test_the_engine_counts_the_prefills_that_went_sorted(name, monkeypatch):
    """`stats()["loop"]["prefill_sorted_n"]`: the prefill dispatches
    whose routed blocks took the sorted product: the rule says yes to the
    bucket AND the kernel's verdict is a pass. Served tokens are the
    walk's."""
    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.ops import kernel_dispatch
    from deeplearning4j_tpu.serving import block_state
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    net = _toy_net(name)
    ids = lambda n, seed: np.random.default_rng(seed).integers(
        0, V_TOY, n).astype(np.int32)
    gen = dict(n_slots=2, max_len=448, page_size=8,
               prompt_buckets=(16, 384), prefill_chunk=512)

    def serve():
        eng = DecodeEngine(net, **gen)
        try:
            toks = [list(eng.submit(ids(n, n), 3).result(timeout=300.0))
                    for n in (9, 300, 12, 290)]
            return toks, eng.stats()["loop"]
        finally:
            eng.shutdown(drain_timeout=30.0)

    want, loop = serve()
    assert loop["prefill_sorted_n"] == 0 and loop["prefill.dispatch_n"] == 4
    calls = _sorted_interpreted(monkeypatch)
    verdicts = {}
    monkeypatch.setattr(
        kernel_dispatch, "engaged", lambda family, match=lambda k: True:
        [k for k in verdicts.get(family, ()) if match(k)])
    ffn = block_state.routed_ffns(GPTPlan(net))[0]
    assert pme.sorted_serves(384, 4, 2, 64 + ffn.n_zero_experts)
    assert not pme.sorted_serves(16, 4, 2, 64 + ffn.n_zero_experts)
    # the rule says yes, the probe has no verdict: walked, as counted
    got, loop = serve()
    assert got == want and calls
    assert loop["prefill_sorted_n"] == 0
    verdicts[pme.FAMILY] = [pme.sorted_key(jnp.float32, 64, 32,
                                           ffn.activation)]
    got, loop = serve()
    assert got == want
    assert loop["prefill_sorted_n"] == 2 and loop["prefill.dispatch_n"] == 4


@pytest.mark.parametrize("loop,want", [
    ({"prefill.dispatch_n": 40, "prefill_sorted_n": 10}, 25.0),
    ({"prefill.dispatch_n": 40}, None),        # the parent: no counter
    ({"prefill.dispatch_n": 0, "prefill_sorted_n": 0}, None),
], ids=["a-quarter", "no-counter", "no-prefill"])
def test_the_benchmark_reads_the_share_of_prefills_sorted(loop, want):
    from perfbench.harness.manifest import Manifest

    read = Manifest().reader("moe.prefill_sorted_pct.chat")
    front = {"queue_wait_s": 0.0, "admitted": 0, "decode_steps": 0}
    run = SimpleNamespace(facts={
        "stats_before": dict(front, loop={k: 0 for k in loop}),
        "stats_after": dict(front, loop=loop)})
    assert read(run) == want
    assert read(SimpleNamespace(facts={})) is None


@pytest.mark.parametrize("act", VARIANTS)
def test_the_probes_hold_on_an_interpreted_tpu(act):
    """What a TPU runs once a shape class before it dispatches: both
    kernels against the XLA products on rotations of one drawn expert,
    the sorted one with an unused last tile that must come out zeros."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        assert pme._sorted_probe(jnp.float32, D, 64, act)
        assert pme._eager_probe(jnp.float32, 16, D, 64, act)
    Wg, Wu, Wd = pme._probe_experts(np.random.default_rng(0), 3, D, 64,
                                    jnp.float32, act)
    assert (Wg is None) == (act == pme.RELU2)
    assert Wu.shape == ((3, 64, D) if act == pme.RELU2 else (3, D, 64))
    assert Wd.shape == (3, 64, D) and bool(jnp.any(Wd[0] != Wd[1]))
    np.testing.assert_allclose(jnp.var(Wd) * 64, 1.0, rtol=0.1)
