"""The gated delta rule three ways (`ops/delta_rule.py`: the sequential
definition, the chunked WY form, the one-token step) and the Pallas
decode steps in interpret mode, held to one another on seeded inputs:
with one decay a head ("head": Olmo-Hybrid's cases) and with one a key
channel ("channel": the same tests over a vector decay bounded in
(-5, 0), and the forms against a float64 recurrence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import delta_rule as dr
from deeplearning4j_tpu.ops import pallas_delta_step as pk


def _inputs(B, T, H, dk, dv, seed=0, dtype=jnp.float32, shared=0.0,
            channels=False, lower=-5.0):
    """q, k normalised as the mixer hands them over, v, a log decay
    spread over three decades, beta in (0, 2). `shared` adds a common
    direction to every key (correlated keys are the hard case of the
    chunk's triangular system). `channels`: the log decay is a vector
    over the key channels, `lower * sigmoid(.)`, all of (lower, 0)."""
    r = np.random.default_rng(seed)
    q = r.standard_normal((B, T, H, dk))
    k = r.standard_normal((B, T, H, dk)) + shared
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.standard_normal((B, T, H, dv))
    g = -np.exp(r.uniform(np.log(1e-3), np.log(2.0), (B, T, H)))
    beta = 2.0 / (1.0 + np.exp(-2.0 * r.standard_normal((B, T, H))))
    if channels:
        g = lower / (1.0 + np.exp(-3.0 * r.standard_normal((B, T, H, dk))))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


DECAYS = pytest.mark.parametrize("channels", [False, True],
                                 ids=["head", "channel"])


def _recurrence64(q, k, v, g, beta, h0=None):
    """The recurrence as the module's docstring writes it, one position
    at a time in float64 NumPy: (o, state in the flat layout)."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    S = np.zeros((B, H, dv, dk)) if h0 is None else np.transpose(
        np.asarray(h0, np.float64).reshape(B, dk, H, dv), (0, 2, 3, 1))
    o = np.zeros((B, T, H, dv))
    for t in range(T):
        a = np.exp(g[:, t])
        S = S * (a[..., None, :] if a.ndim == 3 else a[..., None, None])
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhvk,bhk->bhv", S, k[:, t]))
        S = S + u[..., None] * k[:, t][..., None, :]
        o[:, t] = np.einsum("bhvk,bhk->bhv", S, q[:, t])
    return o, np.transpose(S, (0, 3, 1, 2)).reshape(B, dk, H * dv)


@DECAYS
@pytest.mark.parametrize("T,chunk", [(1, 8), (5, 8), (37, 8), (64, 16),
                                     (200, 64), (70, 64)])
def test_chunked_equals_sequential_in_float32(T, chunk, channels):
    a = _inputs(2, T, 3, 8, 16, seed=T, channels=channels)
    o1, s1 = dr.delta_sequential(*a)
    o2, s2 = dr.delta_chunked(*a, chunk=chunk)
    # (a decay a channel: the products between sub-blocks round their
    # factored operands once more; 6e-6 read on outputs of order 3)
    np.testing.assert_allclose(o2, o1, atol=1e-5 if channels else 2e-6)
    np.testing.assert_allclose(s2, s1, atol=1e-5)
    assert s2.shape == (2, 8, 3 * 16) and s2.dtype == jnp.float32


@pytest.mark.parametrize("dk,dv,channels", [(96, 192, False),
                                            (128, 128, True)],
                         ids=["head-96x192", "channel-128x128"])
@pytest.mark.parametrize("shared", [0.0, 3.0], ids=["spread", "aligned"])
def test_chunked_equals_sequential_at_the_published_head_sizes(
        shared, dk, dv, channels):
    a = _inputs(1, 150, 2, dk, dv, seed=1, shared=shared, channels=channels)
    o1, s1 = dr.delta_sequential(*a)
    o2, s2 = dr.delta_chunked(*a, chunk=64)
    np.testing.assert_allclose(o2, o1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-4)


@DECAYS
def test_bfloat16_values_keep_a_float32_state(channels):
    a = _inputs(2, 45, 2, 8, 16, seed=3, dtype=jnp.bfloat16,
                channels=channels)
    o1, s1 = dr.delta_sequential(*a)
    o2, s2 = dr.delta_chunked(*a, chunk=16)
    assert o1.dtype == o2.dtype == jnp.bfloat16
    assert s1.dtype == s2.dtype == jnp.float32
    np.testing.assert_allclose(o2.astype(jnp.float32),
                               o1.astype(jnp.float32), atol=2e-2)
    np.testing.assert_allclose(s2, s1, atol=1e-5)


@DECAYS
def test_step_equals_one_position_of_the_chunked_form(channels):
    q, k, v, g, beta = _inputs(3, 1, 2, 8, 16, seed=4, channels=channels)
    h0 = jnp.asarray(np.random.default_rng(5).standard_normal((3, 8, 32)),
                     jnp.float32)
    o1, s1 = dr.delta_step(h0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                           beta[:, 0])
    o2, s2 = dr.delta_chunked(q, k, v, g, beta, h0=h0)
    np.testing.assert_allclose(o1, o2[:, 0], atol=1e-6)
    np.testing.assert_allclose(s1, s2, atol=1e-6)


@DECAYS
@pytest.mark.parametrize("cut", [8, 19, 64])
def test_a_carried_state_continues_the_sequence(cut, channels):
    a = _inputs(2, 90, 2, 8, 16, seed=6, channels=channels)
    o, s = dr.delta_chunked(*a, chunk=16)
    o1, s1 = dr.delta_chunked(*(x[:, :cut] for x in a), chunk=16)
    o2, s2 = dr.delta_chunked(*(x[:, cut:] for x in a), chunk=16, h0=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=2e-6)
    np.testing.assert_allclose(s2, s, atol=1e-5)


@DECAYS
def test_a_position_with_beta_0_and_g_0_leaves_the_state_bit_for_bit(
        channels):
    q, k, v, g, beta = _inputs(4, 1, 2, 8, 16, seed=7, channels=channels)
    h0 = jnp.asarray(np.random.default_rng(8).standard_normal((4, 8, 32)),
                     jnp.float32)
    live = jnp.asarray([True, False, True, False])[:, None]
    _, s = dr.delta_step(h0, q[:, 0], k[:, 0], v[:, 0],
                         jnp.where(live[..., None] if channels else live,
                                   g[:, 0], 0.0),
                         jnp.where(live, beta[:, 0], 0.0))
    np.testing.assert_array_equal(s[1], h0[1])
    np.testing.assert_array_equal(s[3], h0[3])
    assert not np.array_equal(s[0], h0[0])
    # a stretch of nothing but such positions, by chunks
    z = jnp.zeros((4, 24, 2))
    a = _inputs(4, 24, 2, 8, 16, seed=9)
    _, s = dr.delta_chunked(a[0], a[1], a[2],
                            jnp.zeros((4, 24, 2, 8)) if channels else z, z,
                            chunk=8, h0=h0)
    np.testing.assert_array_equal(s, h0)


@DECAYS
@pytest.mark.parametrize("n_valid", [0, 11, 16, 29])
def test_positions_past_n_valid_are_padding(n_valid, channels):
    a = _inputs(1, 40, 2, 8, 16, seed=10, channels=channels)
    h0 = jnp.asarray(np.random.default_rng(11).standard_normal((1, 8, 32)),
                     jnp.float32)
    o, s = jax.jit(lambda n: dr.delta_chunked(*a, chunk=16, h0=h0,
                                              n_valid=n))(n_valid)
    if n_valid == 0:
        np.testing.assert_array_equal(s, h0)
        return
    o1, s1 = dr.delta_chunked(*(x[:, :n_valid] for x in a), chunk=16, h0=h0)
    np.testing.assert_allclose(o[:, :n_valid], o1, atol=2e-6)
    np.testing.assert_allclose(s, s1, atol=1e-5)


def test_beta_near_2_stays_bounded():
    """`linear_allow_neg_eigval`: with beta up to 2 the transition's
    eigenvalue along k reaches -1, never beyond: a long stretch of
    near-reflections with almost no decay keeps the state of the order
    of its inputs, in every form."""
    q, k, v, _, _ = _inputs(1, 512, 2, 8, 16, seed=12, shared=1.0)
    g = jnp.full((1, 512, 2), -1e-4)
    beta = jnp.full((1, 512, 2), 1.999)
    o1, s1 = dr.delta_sequential(q, k, v, g, beta)
    o2, s2 = dr.delta_chunked(q, k, v, g, beta, chunk=64)
    assert float(jnp.max(jnp.abs(s1))) < 50.0
    assert bool(jnp.all(jnp.isfinite(o2)))
    # both lie about 4e-4 from a float64 recurrence here (the state is
    # of order 40); 16 rows a block in the chunk's solve gave 0.07
    np.testing.assert_allclose(o2, o1, atol=2e-3)
    np.testing.assert_allclose(s2, s1, atol=4e-3)


@pytest.mark.parametrize("T,dk,dv,chunk,lower", [
    (37, 8, 16, 8, -5.0),       # chunks shorter than a sub-block
    (150, 128, 128, 64, -5.0),  # the published head sizes, four sub-blocks
    (70, 16, 8, 64, -5.0),      # a last chunk of 6
    (200, 32, 32, 64, -30.0),   # decays far past float32's exp range
], ids=["short-chunks", "published", "ragged", "unbounded"])
def test_a_decay_a_channel_equals_a_float64_recurrence(T, dk, dv, chunk,
                                                       lower):
    """The three forms with a vector decay against the recurrence in
    float64: the chunked form's sub-blocks lose nothing the sequential
    float32 form keeps, also where a chunk's summed decay (64 x 30)
    would overflow a factored operand many times over."""
    a = _inputs(2, T, 2, dk, dv, seed=T, shared=1.0, channels=True,
                lower=lower)
    o64, s64 = _recurrence64(*a)
    o1, s1 = dr.delta_sequential(*a)
    o2, s2 = dr.delta_chunked(*a, chunk=chunk)
    # read: the sequential form 1e-7 to 3e-6 from float64 (states of
    # order 3 under keys that share a direction), the chunked form
    # within a factor of two of that in every case
    for o, s in ((o1, s1), (o2, s2)):
        assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
        np.testing.assert_allclose(o, o64, atol=1e-5)
        np.testing.assert_allclose(s, s64, atol=1e-5)
    err = lambda s: float(np.max(np.abs(np.asarray(s) - s64)))
    assert err(s2) < 4.0 * max(err(s1), 1e-6)
    # and the step, from a carried state
    h0 = jnp.asarray(s64[:, :, :], jnp.float32)
    b = tuple(x[:, :1] for x in _inputs(2, 1, 2, dk, dv, seed=T + 1,
                                        channels=True, lower=lower))
    o3, s3 = dr.delta_step(h0, *(x[:, 0] for x in b))
    o64, s64 = _recurrence64(*b, h0=h0)
    np.testing.assert_allclose(o3, o64[:, 0], atol=2e-6)
    np.testing.assert_allclose(s3, s64, atol=2e-6)


def test_a_decay_equal_over_the_channels_is_the_decay_a_head():
    """A vector decay whose channels all carry the head's number is the
    scalar rule, in every form."""
    q, k, v, g, beta = _inputs(2, 45, 3, 8, 16, seed=14)
    gv = jnp.broadcast_to(g[..., None], (*g.shape, 8))
    for form in (dr.delta_sequential,
                 lambda *a: dr.delta_chunked(*a, chunk=16)):
        o1, s1 = form(q, k, v, g, beta)
        o2, s2 = form(q, k, v, gv, beta)
        np.testing.assert_allclose(o2, o1, atol=2e-6)
        np.testing.assert_allclose(s2, s1, atol=1e-5)


def test_a_scalar_decay_traces_no_sub_blocks():
    """With one decay a head the chunked form is the program it was: no
    (sub-block, sub-block, d_k) array, one (C, C) table."""
    a = _inputs(1, 70, 2, 8, 16, seed=15)
    text = str(jax.make_jaxpr(lambda *x: dr.delta_chunked(*x, chunk=64))(*a))
    assert "16,16,8" not in text
    b = _inputs(1, 70, 2, 8, 16, seed=15, channels=True)
    assert "16,16,8" in str(jax.make_jaxpr(
        lambda *x: dr.delta_chunked(*x, chunk=64))(*b))


def test_heads_of_and_flat_of_are_inverse():
    s = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 20)
    per_head = dr.heads_of(s, 4)
    assert per_head.shape == (2, 4, 3, 5)
    np.testing.assert_array_equal(per_head[1, 2, 1], s[1, 1, 10:15])
    np.testing.assert_array_equal(dr.flat_of(per_head), s)


# ------------------------------------------------------ the Pallas kernel
@pytest.mark.parametrize("S,H,dk,dv,dtype", [
    (3, 2, 96, 192, jnp.float32),    # the published head sizes, one pair
    (3, 6, 96, 192, jnp.bfloat16),   # three pairs, bfloat16 values
    (3, 3, 16, 128, jnp.float32),    # a value width of whole lane tiles
    (11, 4, 128, 128, jnp.bfloat16),  # slots no multiple of 8; H < d_k
    (2, 130, 8, 128, jnp.float32),   # more heads than one 128-row tile
], ids=["pair-f32", "pairs-bf16", "single-f32", "eleven-slots-bf16",
        "two-row-tiles-f32"])
@DECAYS
def test_pallas_step_in_interpret_mode_equals_delta_step(S, H, dk, dv, dtype,
                                                         channels):
    """Both kernels against `delta_step` at the probe's tolerances, the
    last slot inactive and bit for bit what it was. The channel kernel
    takes its head rows as they are and pads them to whole 128-row tiles
    before it turns them into columns: fewer heads than key channels,
    more heads than a tile, and a slot count off the sublane grid (its
    `beta` is read by slot from the resident (S, H))."""
    q, k, v, g, beta = (x[:, 0] for x in _inputs(
        S, 1, H, dk, dv, seed=H, dtype=dtype, channels=channels))
    live = (jnp.arange(S) < S - 1)[:, None]
    g = jnp.where(live[..., None] if channels else live, g, 0.0)
    beta = jnp.where(live, beta, 0.0)
    h0 = jnp.asarray(np.random.default_rng(13)
                     .standard_normal((S, dk, H * dv)), jnp.float32)
    want_o, want_s = dr.delta_step(h0, q, k, v, g, beta)
    step = pk.kda_step if channels else pk.gdn_step
    got_o, got_s = step(h0, q, k, v, g, beta, interpret=True)
    assert got_o.dtype == dtype and got_s.dtype == jnp.float32
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    np.testing.assert_allclose(got_o.astype(jnp.float32),
                               want_o.astype(jnp.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 2e-6)
    np.testing.assert_array_equal(got_s[S - 1], h0[S - 1])


def _entry_equations(step, *args):
    """The equations of a jitted entry's own body (the kernel's body is
    one of them, not looked into)."""
    (call,) = jax.make_jaxpr(lambda *a: step(*a))(*args).jaxpr.eqns
    return call.params["jaxpr"].jaxpr.eqns


def test_the_channel_step_at_the_published_state_in_interpret_mode():
    """Ling-3.0-flash's linear layers' state: 32 heads of 128 x 128,
    `(S, 128, 4096)` float32, one head a group of whole lane tiles. The
    entry hands the kernel q, k and `exp(g)` by head row, v in its own
    dtype and beta a head: nothing is transposed, joined or spread to
    lanes before the call."""
    S, H, dk, dv = 3, 32, 128, 128
    q, k, v, g, beta = (x[:, 0] for x in _inputs(
        S, 1, H, dk, dv, seed=16, dtype=jnp.bfloat16, channels=True))
    live = jnp.asarray([True, False, True])[:, None]
    g, beta = jnp.where(live[..., None], g, 0.0), jnp.where(live, beta, 0.0)
    h0 = jnp.asarray(np.random.default_rng(17)
                     .standard_normal((S, dk, H * dv)), jnp.float32)
    assert pk._group(dv) == 1 and h0.shape == (S, 128, 4096)
    want_o, want_s = dr.delta_step(h0, q, k, v, g, beta)
    got_o, got_s = pk.kda_step(h0, q, k, v, g, beta, interpret=True)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    np.testing.assert_allclose(got_o.astype(jnp.float32),
                               want_o.astype(jnp.float32), atol=2e-2)
    np.testing.assert_array_equal(got_s[1], h0[1])
    eqns = _entry_equations(pk.kda_step, h0, q, k, v, g, beta)
    assert {e.primitive.name for e in eqns} <= {
        "exp", "reshape", "convert_element_type", "pallas_call"}
    (call,) = (e for e in eqns if e.primitive.name == "pallas_call")
    assert [(x.aval.shape, x.aval.dtype.name) for x in call.invars] == [
        ((S, H, dk), "float32")] * 3 + [
        ((S, H, dv), "bfloat16"), ((S, H), "float32"),
        (h0.shape, "float32")]
    # the scalar kernel's entry is the one that lays its operands out
    scalar = _entry_equations(pk.gdn_step, h0, q, k, v, g[..., 0], beta)
    assert {"transpose", "concatenate"} <= {e.primitive.name
                                            for e in scalar}


@DECAYS
def test_the_kernel_never_dispatches_on_the_cpu(monkeypatch, channels):
    or_none = pk.delta_step_or_none
    q, k, v, g, beta = (x[:, 0] for x in _inputs(2, 1, 2, 96, 192,
                                                 channels=channels))
    h0 = jnp.zeros((2, 96, 384), jnp.float32)
    assert or_none(h0, q, k, v, g, beta) is None
    # head sizes off the tile grid are declined with a record, not tried
    from deeplearning4j_tpu.ops import kernel_dispatch

    monkeypatch.setattr(pk, "_platform_supported", lambda: True)
    small = tuple(x[:, 0] for x in _inputs(2, 1, 2, 8, 16,
                                           channels=channels))
    assert or_none(jnp.zeros((2, 8, 32), jnp.float32), *small) is None
    family = pk.KDA_FAMILY if channels else pk.FAMILY
    assert (pk.FAMILY, pk.KDA_FAMILY) == ("gdn_step", "kda_step")
    # the channel kernel's key names its operand form: a verdict probed
    # on the transposed operand of before is not this one's
    key = pk.step_key(jnp.float32, 2, 8, 16, channels)
    assert key == ("float32", 2, 8, 16) + (("rows",) if channels else ())
    verdict = kernel_dispatch.kernel_verdicts()[family][key]
    assert not verdict.ok and "tile grid" in verdict.message
