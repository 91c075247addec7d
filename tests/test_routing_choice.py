"""The routers' choice of k of E as a mask (`parallel.experts.chosen_mask`)
against `lax.top_k`, whose set it must name for any input, and the four
routers' gates against the bodies they had while they chose by
`lax.top_k` and wrote by a scatter (kept here as the plain reference)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.parallel import experts
from tools import router_choice_bench

# the six routed cells' routers: lanes, top-k, scoring, groups, kept
CELLS = {
    "granite": (72, 10, "softmax", 1, 1),
    "nemotron": (128, 6, "sigmoid", 1, 1),
    "longcat": (768, 12, "softmax_all", 1, 1),
    "dsv2": (160, 6, "softmax_all", 8, 3),
    "ling": (512, 8, "sigmoid", 8, 4),
    "cmdaplus": (128, 8, "sigmoid", 1, 1),
}
# the two group rules: a row's groups (lanes a group), the best of a
# group that score it, the groups kept
GROUP_RULES = {"dsv2-largest": (8, 20, 1, 3), "ling-best-two": (8, 64, 2, 4)}
ROWS = (1, 128, 2048)


def _scores(kind: str, shape, seed: int) -> np.ndarray:
    """float32 rows of `shape`: "random"; "ties": drawn from 5 values,
    so every row ties across and inside its groups; "equal": one value a
    row; "few": three values, one of them -0.0 beside +0.0; "fills":
    random with all but 1 to k - 1 lanes of a row at -inf."""
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.standard_normal(shape).astype(np.float32)
    if kind == "ties":
        return rng.choice(np.float32([-1.5, 0.25, 0.5, 0.75, 2.0]), shape)
    if kind == "equal":
        return np.broadcast_to(
            rng.standard_normal(shape[:-1] + (1,)).astype(np.float32),
            shape).copy()
    if kind == "few":
        return rng.choice(np.float32([-0.0, 0.0, 1.0]), shape)
    assert kind == "fills"
    x = rng.standard_normal(shape).astype(np.float32)
    finite = rng.integers(1, 4, shape[:-1] + (1,))
    order = np.argsort(rng.random(shape), axis=-1)
    return np.where(order < finite, x, -np.inf).astype(np.float32)


def _top_k_set(scores, k):
    idx = np.asarray(lax.top_k(jnp.asarray(scores), k)[1])
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, idx, True, axis=-1)
    return want


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("kind", ("random", "ties", "equal", "few", "fills"))
@pytest.mark.parametrize("cell", CELLS)
def test_the_mask_is_top_ks_set(cell, kind, rows):
    """At every routed cell's (lanes, k), from one row to a long
    prompt's, so both forms of the shape rule are met: the lanes
    `lax.top_k` names, ties to the lower lane, -0 under +0, and -inf
    lanes only once the finite ones run out."""
    E, k = CELLS[cell][:2]
    x = _scores(kind, (rows, E), seed=rows + E)
    got = np.asarray(jax.jit(experts.chosen_mask, static_argnums=1)(x, k))
    np.testing.assert_array_equal(got, _top_k_set(x, k))
    assert (got.sum(-1) == k).all()


@pytest.mark.parametrize("form", ("_by_rank", "_by_rounds"))
@pytest.mark.parametrize("kind", ("random", "ties", "few", "fills"))
@pytest.mark.parametrize("cell", CELLS)
def test_each_form_is_top_ks_set(cell, kind, form):
    """Either form alone at every cell's lanes, whichever the rule
    picks there."""
    E, k = CELLS[cell][:2]
    x = _scores(kind, (16, E), seed=E + k)
    got = np.asarray(jax.jit(
        lambda s: getattr(experts, form)(experts._total_order(s), k))(x))
    np.testing.assert_array_equal(got, _top_k_set(x, k))


def _form_taken(shape, k) -> str:
    text = str(jax.make_jaxpr(lambda s: experts.chosen_mask(s, k))(
        jnp.zeros(shape, jnp.float32)))
    if "reduce_max" not in text:
        return "rank"
    # a loop of `length` rounds, `unroll` of them an iteration
    return "rolled" if "unroll=1\n" in text else "written out"


def test_the_rule_reads_shapes_alone():
    """The rank while rows x lanes^2 is under `RANK_COMPARES`, rounds
    from there on, rolled into a loop when they are more than two: what
    was traced says which was taken, at the six cells' decode rows,
    their groups and their longest prompts."""
    assert experts.RANK_COMPARES == 1 << 22
    for shape, k in (((64, 72), 10), ((512, 72), 10), ((64, 128), 6),
                     ((48, 128), 8), ((128, 160), 6), ((128, 8), 3),
                     ((4096, 8), 4)):
        assert _form_taken(shape, k) == "rank", shape
    for shape, k in (((128, 512), 8), ((1024, 512), 8), ((128, 768), 12),
                     ((512, 128), 6), ((4096, 128), 8), ((4096, 160), 6)):
        assert _form_taken(shape, k) == "rolled", shape
    for rows in (128, 1024):
        assert _form_taken((rows, 8, 64), 2) == "written out"
    # more lanes asked for than there are: all of them
    assert np.asarray(experts.chosen_mask(jnp.zeros((3, 4)), 9)).all()


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("kind", ("random", "ties", "equal", "fills"))
@pytest.mark.parametrize("rule", GROUP_RULES)
def test_the_choice_of_groups_is_top_ks(rule, kind, rows):
    """A group's score (its largest, or the sum of its best two as a
    masked sum) and the groups kept, against `lax.top_k` on the same
    rows; "fills" leaves groups with fewer than two finite scores and
    rows with fewer finite groups than are kept."""
    G, lanes, best, kept = GROUP_RULES[rule]
    fill = -np.inf if best == 2 else 0.0
    x = _scores(kind, (rows, G * lanes), seed=rows + lanes)
    if kind == "fills":
        gone = np.random.default_rng(rows).random((rows, G)) < 0.7
        x = np.where(np.repeat(gone, lanes, axis=1), -np.inf, x)
    got = np.asarray(jax.jit(lambda s: experts.group_limited(
        s, G, kept, best=best, fill=fill))(x))
    want = np.asarray(jax.jit(lambda s: _old_group_limited(
        s, G, kept, best=best, fill=fill))(x))
    np.testing.assert_array_equal(got, want)


# -- the routers as they were: `lax.top_k`, a gather, a scatter -------------

def _old_topk_gates(logits, top_k):
    top_v, top_i = lax.top_k(logits.astype(jnp.float32), top_k)
    w = jax.nn.softmax(top_v, axis=-1)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros(logits.shape, jnp.float32).at[rows, top_i].set(w)


def _old_group_limited(scores, n_groups, topk_groups, best=1, fill=0.0):
    N, E = scores.shape
    by_group = scores.reshape(N, n_groups, E // n_groups)
    of_group = jnp.max(by_group, axis=-1) if best == 1 else \
        jnp.sum(lax.top_k(by_group, best)[0], axis=-1)
    _, top_g = lax.top_k(of_group, topk_groups)
    keep = jnp.zeros((N, n_groups), bool).at[
        jnp.arange(N)[:, None], top_g].set(True)
    return jnp.where(keep[:, :, None], by_group, fill).reshape(N, E)


def _old_sigmoid_topk_gates(logits, bias, top_k, scale, n_groups=1,
                            topk_groups=1):
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    choose_on = s + bias.astype(jnp.float32)
    if n_groups > 1:
        choose_on = _old_group_limited(choose_on, n_groups, topk_groups,
                                       best=2, fill=-jnp.inf)
    _, top_i = lax.top_k(choose_on, top_k)
    rows = jnp.arange(logits.shape[0])[:, None]
    top_s = s[rows, top_i]
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.zeros(logits.shape, jnp.float32).at[rows, top_i].set(w)


def _old_softmax_all_topk_gates(logits, bias, top_k, scale, n_groups=1,
                                topk_groups=1):
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    choose_on = s + bias.astype(jnp.float32)
    if n_groups > 1:
        choose_on = _old_group_limited(choose_on, n_groups, topk_groups)
    _, top_i = lax.top_k(choose_on, top_k)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros(logits.shape, jnp.float32).at[rows, top_i].set(
        s[rows, top_i] * scale)


def _old_routed_gates(logits, top_k, *, bias, scale, scoring, n_groups,
                      topk_groups):
    if scoring == "softmax":
        return _old_topk_gates(logits, top_k)
    old = _old_sigmoid_topk_gates if scoring == "sigmoid" \
        else _old_softmax_all_topk_gates
    return old(logits, bias, top_k, scale, n_groups, topk_groups)


def _ulps(a, b):
    """How many float32 values lie between `a` and `b`, at most."""
    return int(np.abs(a.view(np.int32).astype(np.int64)
                      - b.view(np.int32).astype(np.int64)).max())


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("kind", ("random", "ties"))
@pytest.mark.parametrize("cell", CELLS)
def test_the_gates_are_the_scattered_routers(cell, kind, rows, monkeypatch):
    """Each cell's router through `routed_gates` against what
    `lax.top_k`, the gather of the chosen scores and the scatter of
    their weights gave on the same logits and bias. The same set
    always. Bit for bit the same weights where no sum runs over the
    chosen ("softmax_all", groups or none) and, for the two routers
    that normalise over the chosen, bit for bit once the parent's choice
    is handed to the same arithmetic: what is left between them and the
    parent's bodies is the ORDER of that one sum (the parent added the
    chosen scores largest first, the mask adds them where they lie), a
    few units in the last place."""
    E, k, scoring, G, kept = CELLS[cell]
    logits = 2.0 * _scores(kind, (rows, E), seed=rows + k)
    bias = None if scoring == "softmax" else \
        0.1 * _scores("random", (E,), seed=E)
    kw = dict(bias=bias, scale=2.5, scoring=scoring, n_groups=G,
              topk_groups=kept)
    got = np.asarray(jax.jit(
        lambda x: experts.routed_gates(x, k, **kw))(logits))
    old = np.asarray(jax.jit(
        lambda x: _old_routed_gates(x, k, **kw))(logits))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got != 0, old != 0)
    assert _ulps(got, old) <= (0 if scoring == "softmax_all" else 8)
    # the parent's choice as a mask: `lax.top_k`'s lanes, scattered
    monkeypatch.setattr(experts, "chosen_mask",
                        router_choice_bench.form_of("sort"))
    same_sum = np.asarray(jax.jit(
        lambda x: experts.routed_gates(x, k, **kw))(logits))
    assert _ulps(got, same_sum) == 0


@pytest.mark.parametrize("cell", ("granite", "ling"))
def test_the_gradient_is_the_chosen_scores(cell):
    """Under `fit()` the mask passes what the scatter passed: a gradient
    to the chosen lanes' logits and none through the choice."""
    E, k, scoring, G, kept = CELLS[cell]
    logits = jnp.asarray(_scores("random", (8, E), seed=3))
    bias = None if scoring == "softmax" else jnp.zeros((E,), jnp.float32)
    kw = dict(bias=bias, scale=1.0, scoring=scoring, n_groups=G,
              topk_groups=kept)
    weigh = jnp.asarray(_scores("random", (8, E), seed=4))
    new = jax.grad(lambda x: jnp.sum(
        experts.routed_gates(x, k, **kw) * weigh))(logits)
    old = jax.grad(lambda x: jnp.sum(
        _old_routed_gates(x, k, **kw) * weigh))(logits)
    np.testing.assert_allclose(new, old, rtol=1e-5, atol=1e-7)
    chosen = np.asarray(experts.routed_gates(logits, k, **kw)) != 0
    assert not np.asarray(new)[~chosen].any()


def test_the_choice_bench_rehearses_on_any_backend(tmp_path, capsys):
    """`tools/router_choice_bench.py` end to end at granite's and
    DeepSeek-V2's decode rows and longest buckets, so that a chip call
    is not lost to a typo: a row a (rows, choice, form), each form
    marking `lax.top_k`'s lanes, the rule's rows saying which form it
    took, a last row a (cell, rows) for the router whole, and no time
    printed as a device's."""
    import json

    bench = router_choice_bench
    out = tmp_path / "bench.json"
    assert bench.main(
        ["--cells", "granite4hs-serve-chat,dsv2-serve-longgen", "--forms",
         "sort,rank,rolled,rule", "--calls", "1", "--iters", "1",
         "--rehearse", "--out", str(out)]) == 0
    rows = json.loads(out.read_text())["rows"]
    assert [(r["rows"], r["choice"], r["form"]) for r in rows[:5]] == [
        (64, "experts", "sort"), (64, "experts", "rank"),
        (64, "experts", "rolled"), (64, "experts", "rule"),
        (64, "routed_gates", "rule")]
    assert all(r["same"] for r in rows if "same" in r)
    took = {(r["cell"][:4], r["rows"], r["choice"]): r["took"]
            for r in rows if "took" in r}
    assert took == {
        ("gran", 64, "experts"): "rank", ("gran", 512, "experts"): "rank",
        ("dsv2", 128, "experts"): "rank", ("dsv2", 128, "groups"): "rank",
        ("dsv2", 4096, "experts"): "rolled",
        ("dsv2", 4096, "groups"): "rank"}
    assert all(r["compile_s"] > 0 for r in rows)
    assert not any(key in r for r in rows for key in ("call_us",
                                                      "router_us"))
    assert not (tmp_path / ".router_choice_trace").exists()
    assert len(capsys.readouterr().out.splitlines()) == len(rows) == 28


def test_the_choice_bench_reads_device_time_a_call():
    """`device_us` on a hand-made device trace of four calls, two
    operations each with a gap between: the time in which an operation
    ran, a call; nothing where the trace holds no device."""
    from perfbench.harness import trace_reduce as tr

    bench = router_choice_bench
    events = [{"plane": "/device:TPU:0", "line": tr.OPS_LINE,
               "name": f"%fusion.{n} = pred[128,512] fusion(s32[8] %x)",
               "start_ns": 10_000.0 * n, "dur_ns": 3_000.0}
              for n in range(8)]
    assert bench.device_us(tr.TraceView(events), 4) == pytest.approx(6.0)
    assert bench.device_us(tr.TraceView([]), 4) is None
