"""The gated delta rule three ways (`ops/delta_rule.py`: the sequential
definition, the chunked WY form, the one-token step) and the Pallas
decode step in interpret mode, held to one another on seeded inputs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import delta_rule as dr
from deeplearning4j_tpu.ops import pallas_delta_step as pk


def _inputs(B, T, H, dk, dv, seed=0, dtype=jnp.float32, shared=0.0):
    """q, k normalised as the mixer hands them over, v, a log decay
    spread over three decades, beta in (0, 2). `shared` adds a common
    direction to every key (correlated keys are the hard case of the
    chunk's triangular system)."""
    r = np.random.default_rng(seed)
    q = r.standard_normal((B, T, H, dk))
    k = r.standard_normal((B, T, H, dk)) + shared
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.standard_normal((B, T, H, dv))
    g = -np.exp(r.uniform(np.log(1e-3), np.log(2.0), (B, T, H)))
    beta = 2.0 / (1.0 + np.exp(-2.0 * r.standard_normal((B, T, H))))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


@pytest.mark.parametrize("T,chunk", [(1, 8), (5, 8), (37, 8), (64, 16),
                                     (200, 64), (70, 64)])
def test_chunked_equals_sequential_in_float32(T, chunk):
    a = _inputs(2, T, 3, 8, 16, seed=T)
    o1, s1 = dr.delta_sequential(*a)
    o2, s2 = dr.delta_chunked(*a, chunk=chunk)
    np.testing.assert_allclose(o2, o1, atol=2e-6)
    np.testing.assert_allclose(s2, s1, atol=1e-5)
    assert s2.shape == (2, 8, 3 * 16) and s2.dtype == jnp.float32


@pytest.mark.parametrize("shared", [0.0, 3.0], ids=["spread", "aligned"])
def test_chunked_equals_sequential_at_the_published_head_sizes(shared):
    a = _inputs(1, 150, 2, 96, 192, seed=1, shared=shared)
    o1, s1 = dr.delta_sequential(*a)
    o2, s2 = dr.delta_chunked(*a, chunk=64)
    np.testing.assert_allclose(o2, o1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-4)


def test_bfloat16_values_keep_a_float32_state():
    a = _inputs(2, 45, 2, 8, 16, seed=3, dtype=jnp.bfloat16)
    o1, s1 = dr.delta_sequential(*a)
    o2, s2 = dr.delta_chunked(*a, chunk=16)
    assert o1.dtype == o2.dtype == jnp.bfloat16
    assert s1.dtype == s2.dtype == jnp.float32
    np.testing.assert_allclose(o2.astype(jnp.float32),
                               o1.astype(jnp.float32), atol=2e-2)
    np.testing.assert_allclose(s2, s1, atol=1e-5)


def test_step_equals_one_position_of_the_chunked_form():
    q, k, v, g, beta = _inputs(3, 1, 2, 8, 16, seed=4)
    h0 = jnp.asarray(np.random.default_rng(5).standard_normal((3, 8, 32)),
                     jnp.float32)
    o1, s1 = dr.delta_step(h0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                           beta[:, 0])
    o2, s2 = dr.delta_chunked(q, k, v, g, beta, h0=h0)
    np.testing.assert_allclose(o1, o2[:, 0], atol=1e-6)
    np.testing.assert_allclose(s1, s2, atol=1e-6)


@pytest.mark.parametrize("cut", [8, 19, 64])
def test_a_carried_state_continues_the_sequence(cut):
    a = _inputs(2, 90, 2, 8, 16, seed=6)
    o, s = dr.delta_chunked(*a, chunk=16)
    o1, s1 = dr.delta_chunked(*(x[:, :cut] for x in a), chunk=16)
    o2, s2 = dr.delta_chunked(*(x[:, cut:] for x in a), chunk=16, h0=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=2e-6)
    np.testing.assert_allclose(s2, s, atol=1e-5)


def test_a_position_with_beta_0_and_g_0_leaves_the_state_bit_for_bit():
    q, k, v, g, beta = _inputs(4, 1, 2, 8, 16, seed=7)
    h0 = jnp.asarray(np.random.default_rng(8).standard_normal((4, 8, 32)),
                     jnp.float32)
    live = jnp.asarray([True, False, True, False])[:, None]
    _, s = dr.delta_step(h0, q[:, 0], k[:, 0], v[:, 0],
                         jnp.where(live, g[:, 0], 0.0),
                         jnp.where(live, beta[:, 0], 0.0))
    np.testing.assert_array_equal(s[1], h0[1])
    np.testing.assert_array_equal(s[3], h0[3])
    assert not np.array_equal(s[0], h0[0])
    # a stretch of nothing but such positions, by chunks
    z = jnp.zeros((4, 24, 2))
    a = _inputs(4, 24, 2, 8, 16, seed=9)
    _, s = dr.delta_chunked(a[0], a[1], a[2], z, z, chunk=8, h0=h0)
    np.testing.assert_array_equal(s, h0)


@pytest.mark.parametrize("n_valid", [0, 11, 16, 29])
def test_positions_past_n_valid_are_padding(n_valid):
    a = _inputs(1, 40, 2, 8, 16, seed=10)
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


def test_heads_of_and_flat_of_are_inverse():
    s = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 20)
    per_head = dr.heads_of(s, 4)
    assert per_head.shape == (2, 4, 3, 5)
    np.testing.assert_array_equal(per_head[1, 2, 1], s[1, 1, 10:15])
    np.testing.assert_array_equal(dr.flat_of(per_head), s)


# ------------------------------------------------------ the Pallas kernel
@pytest.mark.parametrize("H,dk,dv,dtype", [
    (2, 96, 192, jnp.float32),     # the published head sizes, one pair
    (6, 96, 192, jnp.bfloat16),    # three pairs, bfloat16 values
    (3, 16, 128, jnp.float32),     # a value width of whole lane tiles
], ids=["pair-f32", "pairs-bf16", "single-f32"])
def test_pallas_step_in_interpret_mode_equals_delta_step(H, dk, dv, dtype):
    S = 3
    q, k, v, g, beta = (x[:, 0] for x in _inputs(S, 1, H, dk, dv, seed=H,
                                                 dtype=dtype))
    live = jnp.asarray([True, True, False])[:, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    h0 = jnp.asarray(np.random.default_rng(13)
                     .standard_normal((S, dk, H * dv)), jnp.float32)
    want_o, want_s = dr.delta_step(h0, q, k, v, g, beta)
    got_o, got_s = pk.gdn_step(h0, q, k, v, g, beta, interpret=True)
    assert got_o.dtype == dtype and got_s.dtype == jnp.float32
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    np.testing.assert_allclose(got_o.astype(jnp.float32),
                               want_o.astype(jnp.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 2e-6)
    np.testing.assert_array_equal(got_s[2], h0[2])


def test_the_kernel_never_dispatches_on_the_cpu(monkeypatch):
    q, k, v, g, beta = (x[:, 0] for x in _inputs(2, 1, 2, 96, 192))
    h0 = jnp.zeros((2, 96, 384), jnp.float32)
    assert pk.gdn_step_or_none(h0, q, k, v, g, beta) is None
    # head sizes off the tile grid are declined with a record, not tried
    from deeplearning4j_tpu.ops import kernel_dispatch

    monkeypatch.setattr(pk, "_platform_supported", lambda: True)
    small = tuple(x[:, 0] for x in _inputs(2, 1, 2, 8, 16))
    assert pk.gdn_step_or_none(jnp.zeros((2, 8, 32), jnp.float32),
                               *small) is None
    verdict = kernel_dispatch.kernel_verdicts()[pk.FAMILY][
        ("float32", 2, 8, 16)]
    assert not verdict.ok and "tile grid" in verdict.message
