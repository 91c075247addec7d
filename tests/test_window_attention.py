"""A window on the three attention paths (`ops/attention.py`, the flash
forward of `ops/pallas_attention.py`, the paged kernel of
`ops/pallas_paged_attention.py`), each against plain masked attention;
the ring's arithmetic; and `window=None` lowering to what it always
did. The kernels run interpreted."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention as A
from deeplearning4j_tpu.ops import pallas_attention as PA
from deeplearning4j_tpu.ops import pallas_paged_attention as PPA


def _plain(q, k, v, window=None):
    """Masked softmax attention, one array of scores, K/V repeated."""
    B, T, H, D = q.shape
    G = H // k.shape[2]
    k, v = np.repeat(k, G, axis=2), np.repeat(v, G, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    keep = j <= i
    if window is not None:
        keep &= i - j < window
    s = np.where(keep, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(T, H, Hkv, D, seed=0, B=1):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((B, T, H, D)).astype(np.float32),
            rng.standard_normal((B, T, Hkv, D)).astype(np.float32),
            rng.standard_normal((B, T, Hkv, D)).astype(np.float32))


@pytest.mark.parametrize("window", [None, 1, 5, 40])
@pytest.mark.parametrize("Hkv", [4, 2])
def test_the_dense_paths_mask_behind_the_window(window, Hkv):
    q, k, v = _qkv(23, 4, Hkv, 8)
    want = _plain(q, k, v, window)
    got = A.full_attention_grouped(*map(jnp.asarray, (q, k, v)),
                                   causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if Hkv == 4:
        got = A.full_attention(*map(jnp.asarray, (q, k, v)), causal=True,
                               window=window)
        np.testing.assert_allclose(got, want, atol=2e-5)
    # key blocks of 8, the last one padded; grouped K/V read as they are
    got = A.blockwise_attention(*map(jnp.asarray, (q, k, v)), causal=True,
                                window=window, block_size=8)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if window is not None:
        got = A.multi_head_attention(*map(jnp.asarray, (q, k, v)),
                                     causal=True, window=window)
        np.testing.assert_allclose(got, want, atol=2e-5)
        # past `block_size` keys: the long path (blocks of keys here)
        got = A.multi_head_attention(*map(jnp.asarray, (q, k, v)),
                                     causal=True, window=window,
                                     block_size=16)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_window_is_refused_where_it_is_not_written():
    q, k, v = map(jnp.asarray, _qkv(8, 2, 2, 8))
    with pytest.raises(NotImplementedError, match="causal self-attention"):
        A.multi_head_attention(q, k, v, causal=False, window=4)
    with pytest.raises(NotImplementedError, match="causal self-attention"):
        A.multi_head_attention(q, k, v, causal=True, window=4,
                               key_mask=jnp.ones((1, 8)))


@pytest.mark.parametrize("window,Hkv", [(None, 1), (100, 1), (128, 4),
                                        (300, 2), (1, 4)])
def test_the_flash_forward_with_a_window_and_grouped_heads(window, Hkv):
    """Three blocks of 128 queries: key blocks wholly behind the window
    are skipped, the first one left is masked in part, and a query
    head's slab reads its group's K/V slab."""
    q, k, v = _qkv(384, 4, Hkv, 128, seed=Hkv)
    got = PA.flash_attention(*map(jnp.asarray, (q, k, v)), causal=True,
                             block_q=128, block_k=128, interpret=True,
                             window=window)
    np.testing.assert_allclose(got, _plain(q, k, v, window), atol=2e-4)


def test_the_flash_forward_has_no_backward_and_says_so():
    q, k, v = map(jnp.asarray, _qkv(128, 2, 1, 128))
    with pytest.raises(PA.WindowedBackwardUnsupported, match="no backward"):
        jax.grad(lambda a: PA.flash_attention(
            a, k, v, causal=True, interpret=True, window=64).sum())(q)
    # grouped K/V alone are forward only too
    q4 = jnp.concatenate([q, q], axis=2)
    with pytest.raises(PA.WindowedBackwardUnsupported, match="no backward"):
        jax.grad(lambda a: PA.flash_attention(
            a, k, v, causal=True, interpret=True).sum())(q4)


def _strip(text):
    return re.sub(r"0x[0-9a-f]+", "0x", text)


def test_the_flash_kernel_without_a_window_traces_what_it_did():
    """`window=None` and equal head counts: the forward's jaxpr is the
    differentiable entry's, op for op (a window or a group adds ops)."""
    x = jnp.zeros((1, 256, 2, 128))
    fwd = lambda w: _strip(str(jax.make_jaxpr(
        lambda a: PA._flash_forward(a, a, a, True, 0.1, 128, 128, True,
                                    False, **w)[0])(x)))
    assert fwd({}) == fwd({"window": None})
    assert fwd({}) != fwd({"window": 100})
    entry = _strip(str(jax.make_jaxpr(lambda a: PA._flash_mha(
        a, a, a, True, 0.1, 128, 128, True, "fused"))(x)))
    assert fwd({}).count("pallas_call") == entry.count("pallas_call") == 1


# -------------------------------------------------------------- the ring
def _ring_case(S, C, H, Hkv, D, page, window, p0, seed=0):
    """Pools in which slot s's logical page j lies at ring entry j % R,
    written from a dense (S, L) history, and the dense windowed answer."""
    rng = np.random.default_rng(seed)
    R = -(-window // page) + max(1, -(-C // page))
    p0 = np.asarray(p0, np.int32)
    L = int(p0.max()) + C
    L = -(-L // page) * page
    kd = rng.standard_normal((S, L, Hkv, D)).astype(np.float32)
    vd = rng.standard_normal((S, L, Hkv, D)).astype(np.float32)
    q = rng.standard_normal((S, C, H, D)).astype(np.float32)
    table = 1 + rng.permutation(S * R).reshape(S, R).astype(np.int32)
    k_pool = np.zeros((S * R + 1, Hkv, D, page), np.float32)
    v_pool = np.zeros((S * R + 1, Hkv, page, D), np.float32)
    for s in range(S):
        last = int(p0[s]) + C - 1
        for pos in range(last + 1):         # in order: later pages win
            pid, off = table[s, (pos // page) % R], pos % page
            k_pool[pid, :, :, off] = kd[s, pos]
            v_pool[pid, :, off, :] = vd[s, pos]
    want = np.zeros((S, C, H, D), np.float32)
    G = H // Hkv
    for s in range(S):
        for c in range(C):
            qp = int(p0[s]) + c
            lo = max(0, qp - window + 1)
            ks = np.repeat(kd[s, lo:qp + 1], G, axis=1)   # (n, H, D)
            vs = np.repeat(vd[s, lo:qp + 1], G, axis=1)
            sc = np.einsum("hd,nhd->hn", q[s, c], ks) / np.sqrt(D)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want[s, c] = np.einsum("hn,nhd->hd", p, vs)
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(p0)), want, R


@pytest.mark.parametrize("C,p0", [(1, (3, 41)), (1, (16, 7)), (4, (0, 36)),
                                  (8, (8, 48))])
def test_the_gathered_ring_equals_dense_windowed_attention(C, p0):
    args, want, R = _ring_case(2, C, 4, 2, 8, 4, 8, p0)
    got = A.ring_attention_chunk(*args, 8)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=2e-5)
    if C == 1:
        q, kp, vp, table, pos = args
        got = A.paged_attention_step_auto(q[:, 0], kp, vp, table, pos,
                                          window=8)
        np.testing.assert_allclose(got.reshape(want.shape), want,
                                   atol=2e-5)


@pytest.mark.parametrize("C,p0", [(1, (130, 1300)), (1, (1023, 1024)),
                                  (128, (0, 1152))])
def test_the_paged_kernel_walks_the_ring(C, p0):
    """Pages of 128, window 512: the walk starts at the window's first
    page, masks its older positions and finds logical page j at entry
    j % R; one slot's ring has wrapped, one has not."""
    args, want, R = _ring_case(2, C, 4, 2, 128, 128, 512, p0)
    got = PPA.paged_attention(*args, interpret=True, window=512)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(
        A.ring_attention_chunk(*args, 512).reshape(want.shape), want,
        atol=2e-4)


def test_an_inactive_slot_walks_no_page_of_its_ring():
    args, want, _ = _ring_case(2, 1, 4, 2, 128, 128, 256, (700, 900))
    got = PPA.paged_attention(*args, interpret=True, window=256,
                              active=jnp.asarray([True, False]))
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)
    assert not np.asarray(got[1]).any()


def test_the_paged_kernel_without_a_window_lowers_to_what_it_did():
    q = jnp.zeros((2, 1, 4, 128))
    kp, vp = jnp.zeros((5, 2, 128, 8)), jnp.zeros((5, 2, 8, 128))
    pt, p0 = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    low = lambda **kw: _strip(PPA.paged_attention.lower(
        q, kp, vp, pt, p0, active=jnp.ones((2,), bool), interpret=True,
        **kw).as_text())
    assert low() == low(window=None)
    assert low() != low(window=16)
    jaxpr = lambda **kw: _strip(str(jax.make_jaxpr(
        lambda *a: PPA.paged_attention(*a, interpret=True, **kw))(
            q, kp, vp, pt, p0)))
    assert jaxpr() == jaxpr(window=None)
    assert jaxpr() != jaxpr(window=16)     # a window adds arithmetic


def test_ring_positions_name_the_newest_page_an_entry_holds():
    pos = A.ring_key_positions(jnp.asarray([5, 13, 2]), 3, 4)
    # last 5: page 1 at entry 1, page 0 at entry 0, entry 2 never written
    assert np.asarray(pos[0]).tolist() == [0, 1, 2, 3, 4, 5, 6, 7,
                                           -4, -3, -2, -1]
    # last 13: page 3 at entry 0, page 1 at entry 1, page 2 at entry 2
    assert np.asarray(pos[1]).tolist() == [12, 13, 14, 15, 4, 5, 6, 7,
                                           8, 9, 10, 11]
    assert np.asarray(pos[2])[:4].tolist() == [0, 1, 2, 3]
    assert (np.asarray(pos[2])[4:] < 0).all()
