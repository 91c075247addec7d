"""Pallas paged-attention kernel parity + dispatch tests.

The kernel (`ops/pallas_paged_attention.py`) runs here in interpreter
mode (tests execute on the virtual CPU mesh, conftest.py) and is pinned
against BOTH references:

- `paged_gather` + `cached_attention_step`/`cached_attention_chunk` —
  the XLA fallback path the dispatch contract guarantees identical
  semantics with (fuzzed over randomized page tables with holes and
  cross-slot page reuse, ragged positions straddling page boundaries,
  GQA groupings, chunk widths);
- `full_attention(causal=True)` — the training-path ground truth, via a
  coherent single-sequence cache.

Dispatch tests prove the CPU fallback is CLEAN: `paged_attention_or_none`
declines, and the `*_auto` wrappers return bit-identical results to the
gather path — tier-1 never executes a compiled Pallas-TPU path.

A real-TPU compile/run of the same kernel happens via
`chip_smoke.py serve`, gated by the parity-checking eager probe.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from deeplearning4j_tpu.ops.attention import (  # noqa: E402
    cached_attention_chunk,
    cached_attention_step,
    full_attention,
    paged_attention_chunk_auto,
    paged_attention_step_auto,
    paged_attention_step,
    paged_gather,
    paged_gather_quant,
)
from deeplearning4j_tpu.ops.pallas_paged_attention import (  # noqa: E402
    paged_attention,
    paged_attention_or_none,
    vmem_bytes_estimate,
)


def _rand_pools(rng, P, Hkv, hd, page):
    k_pool = rng.standard_normal((P + 1, Hkv, hd, page)).astype(np.float32)
    v_pool = rng.standard_normal((P + 1, Hkv, page, hd)).astype(np.float32)
    return k_pool, v_pool


def _gather_chunk_ref(q, k_pool, v_pool, pt, p0):
    kd, vd = paged_gather(jnp.asarray(k_pool), jnp.asarray(v_pool),
                          jnp.asarray(pt))
    C = q.shape[1]
    qpos = jnp.asarray(p0)[:, None] + jnp.arange(C)[None, :]
    out = jax.vmap(cached_attention_chunk)(jnp.asarray(q), kd, vd, qpos)
    return np.asarray(out).reshape(q.shape)


@pytest.mark.parametrize("H,Hkv,C", [(2, 2, 1), (4, 2, 1), (4, 1, 3),
                                     (4, 2, 4)])
def test_kernel_matches_gather_reference_fuzz(H, Hkv, C):
    """Randomized page tables (holes → trash page, scrambled pool order,
    cross-slot page REUSE as the prefix cache creates) and ragged
    positions straddling page boundaries: the kernel must match the
    gather+dense reference at every shape class."""
    rng = np.random.default_rng(100 * H + 10 * Hkv + C)
    S, hd, page, n_pages = 3, 8, 4, 4
    P = S * n_pages
    for trial in range(3):
        k_pool, v_pool = _rand_pools(rng, P, Hkv, hd, page)
        perm = rng.permutation(np.arange(1, P + 1))
        pt = perm.reshape(S, n_pages).astype(np.int32)
        # cross-slot sharing: slot 1 rides slot 0's first page (a cached
        # prefix); holes: slot 2's tail entries unallocated (trash page)
        pt[1, 0] = pt[0, 0]
        pt[2, 2:] = 0
        # positions straddle page boundaries (page-1, page, mid-page),
        # slot 2 confined to its allocated pages
        p0 = np.array([int(rng.integers(0, n_pages * page - C)),
                       int(rng.integers(0, n_pages * page - C)),
                       int(rng.integers(0, 2 * page - C))], np.int32)
        q = rng.standard_normal((S, C, H, hd)).astype(np.float32)
        ref = _gather_chunk_ref(q, k_pool, v_pool, pt, p0)
        got = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(pt), jnp.asarray(p0), interpret=True))
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_kernel_decode_matches_dense_step_and_full_attention():
    """C=1 decode semantics: the kernel row equals `cached_attention_step`
    on the gathered view AND the last row of whole-sequence causal
    `full_attention` over the same coherent cache."""
    rng = np.random.default_rng(7)
    S, H, Hkv, hd, page, n_pages = 2, 4, 2, 8, 4, 4
    L = page * n_pages
    P = S * n_pages
    # coherent per-slot sequences scattered into pages
    k_seq = rng.standard_normal((S, L, Hkv, hd)).astype(np.float32)
    v_seq = rng.standard_normal((S, L, Hkv, hd)).astype(np.float32)
    pt = (1 + np.arange(P)).reshape(S, n_pages).astype(np.int32)
    k_pool = np.zeros((P + 1, Hkv, hd, page), np.float32)
    v_pool = np.zeros((P + 1, Hkv, page, hd), np.float32)
    for s in range(S):
        for j in range(n_pages):
            pid = pt[s, j]
            k_pool[pid] = np.transpose(
                k_seq[s, j * page:(j + 1) * page], (1, 2, 0))
            v_pool[pid] = np.transpose(
                v_seq[s, j * page:(j + 1) * page], (1, 0, 2))
    pos = np.array([5, L - 1], np.int32)
    q = rng.standard_normal((S, H, hd)).astype(np.float32)
    got = np.asarray(paged_attention(
        jnp.asarray(q[:, None]), jnp.asarray(k_pool),
        jnp.asarray(v_pool), jnp.asarray(pt), jnp.asarray(pos),
        interpret=True)).reshape(S, H * hd)
    # dense-step reference
    kd, vd = paged_gather(jnp.asarray(k_pool), jnp.asarray(v_pool),
                          jnp.asarray(pt))
    ref = np.asarray(cached_attention_step(jnp.asarray(q), kd, vd,
                                           jnp.asarray(pos)))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    # ground truth: row pos[s] of causal full attention, GQA widened
    g = H // Hkv
    for s in range(S):
        t = int(pos[s]) + 1
        kf = np.repeat(k_seq[s:s + 1, :t], g, axis=2)
        vf = np.repeat(v_seq[s:s + 1, :t], g, axis=2)
        qf = np.zeros((1, t, H, hd), np.float32)
        qf[0, -1] = q[s]
        full = np.asarray(full_attention(
            jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf),
            causal=True))[0, -1].reshape(H * hd)
        np.testing.assert_allclose(got[s], full, rtol=2e-5, atol=2e-5)


def test_kernel_trash_page_and_stale_pages_masked():
    """Garbage past each slot's position — poisoned previous-owner
    pages, a poisoned trash page, tail table entries remapped to 0 —
    must never move the output (the reallocation-safety convention the
    engine relies on)."""
    rng = np.random.default_rng(11)
    S, H, Hkv, hd, page, n_pages = 2, 2, 2, 4, 4, 4
    P = S * n_pages
    k_pool, v_pool = _rand_pools(rng, P, Hkv, hd, page)
    pt = (1 + np.arange(P)).reshape(S, n_pages).astype(np.int32)
    pos = np.array([2, 5], np.int32)
    q = rng.standard_normal((S, 1, H, hd)).astype(np.float32)
    base = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos), interpret=True))
    k2, v2 = k_pool.copy(), v_pool.copy()
    for pid in (0, 2, 3, 4, 7, 8):  # trash page + pages past positions
        k2[pid] = 1e6
        v2[pid] = -1e6
    pt2 = pt.copy()
    pt2[0, 2:] = 0
    out = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2),
        jnp.asarray(pt2), jnp.asarray(pos), interpret=True))
    np.testing.assert_array_equal(out, base)


def test_kernel_inactive_lanes_zero_and_all_inactive_batch():
    """`active=False` lanes skip the page loop and emit exact zeros via
    the l == 0 finalization; active lanes are untouched by their
    neighbors' state. The all-inactive batch (engine idle-slot shape)
    returns all zeros."""
    rng = np.random.default_rng(13)
    S, H, Hkv, hd, page, n_pages = 3, 4, 2, 8, 4, 2
    P = S * n_pages
    k_pool, v_pool = _rand_pools(rng, P, Hkv, hd, page)
    pt = (1 + np.arange(P)).reshape(S, n_pages).astype(np.int32)
    pos = np.array([3, 4, 7], np.int32)
    q = rng.standard_normal((S, 1, H, hd)).astype(np.float32)
    all_on = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos), interpret=True))
    active = np.array([True, False, True])
    mixed = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos),
        active=jnp.asarray(active), interpret=True))
    np.testing.assert_array_equal(mixed[0], all_on[0])
    np.testing.assert_array_equal(mixed[2], all_on[2])
    np.testing.assert_array_equal(mixed[1], np.zeros_like(mixed[1]))
    idle = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos),
        active=jnp.zeros((S,), bool), interpret=True))
    np.testing.assert_array_equal(idle, np.zeros_like(idle))


def test_kernel_chunk_width_matches_prefill_chunk_semantics():
    """The S=1 chunk shape (chunked-prefill suffix): kernel rows equal
    `cached_attention_chunk` — and therefore
    `_prefill_chunk_block_attention` — over the slot's gathered row,
    including a padded tail past the true prompt length."""
    rng = np.random.default_rng(17)
    Hkv, H, hd, page, n_pages, C = 2, 4, 8, 4, 4, 8
    P = n_pages
    k_pool, v_pool = _rand_pools(rng, P, Hkv, hd, page)
    pt = (1 + np.arange(P)).reshape(1, n_pages).astype(np.int32)
    off = 4
    q = rng.standard_normal((1, C, H, hd)).astype(np.float32)
    ref = _gather_chunk_ref(q, k_pool, v_pool, pt,
                            np.array([off], np.int32))
    got = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray([off], jnp.int32), interpret=True))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def _walk_case(rng, p0, active, C, H, Hkv, hd, page, n_pages, quantized):
    """Pools, a table WIDER than any slot's live pages whose entries
    past them name a page of NaNs, and the gather reference (which
    reads the trash page there, as the engine's table has it)."""
    S = len(p0)
    live = np.minimum((p0 + C - 1) // page + 1, n_pages)
    P = int(live.sum())
    dead = P + 1
    pt = np.full((S, n_pages), dead, np.int32)
    ids = iter(rng.permutation(np.arange(1, P + 1)))
    for s in range(S):
        pt[s, :live[s]] = [next(ids) for _ in range(live[s])]
    pt_ref = np.where(pt == dead, 0, pt)
    q = rng.standard_normal((S, C, H, hd)).astype(np.float32)
    if quantized:
        k_pool, v_pool, ks, vs = _rand_quant_pools(rng, P + 1, Hkv, hd,
                                                   page)
        ref = _gather_quant_chunk_ref(q, k_pool, v_pool, ks, vs, pt_ref,
                                      p0)
        ks[dead] = vs[dead] = np.nan
        scales = dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    else:
        k_pool, v_pool = _rand_pools(rng, P + 1, Hkv, hd, page)
        ref = _gather_chunk_ref(q, k_pool, v_pool, pt_ref, p0)
        k_pool[dead] = v_pool[dead] = np.nan
        scales = {}
    got = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(p0), active=jnp.asarray(active),
        interpret=True, **scales))
    return got, ref, live


@pytest.mark.parametrize("quantized", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("H,Hkv", [(2, 2), (8, 2)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("C", [1, 3])
def test_walk_reads_only_the_live_pages_of_a_wider_table(C, H, Hkv,
                                                         quantized):
    """One call over slots with 1, 2 and all pages live, first
    positions at `page - 1`, `page` and 0, and an inactive lane, under
    a table six pages wide: equal to the gather reference, exact zeros
    on the inactive lane, and no NaN — every entry past a slot's live
    pages names a page of NaNs, so one read of a dead page shows."""
    rng = np.random.default_rng(1000 + 100 * H + 10 * C + quantized)
    hd, page, n_pages = 8, 4, 6
    p0 = np.array([page - 1, page, 0, n_pages * page - C, 2 * page],
                  np.int32)
    active = np.array([True, True, True, True, False])
    got, ref, live = _walk_case(rng, p0, active, C, H, Hkv, hd, page,
                                n_pages, quantized)
    assert {1, 2, n_pages} <= set(live.tolist())
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[:4], ref[:4], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[4], np.zeros_like(got[4]))


@pytest.mark.parametrize("quantized", [False, True], ids=["dense", "int8"])
def test_walk_serves_a_page_crossing_prefill_chunk(quantized):
    """The chunked-prefill shape: one slot, 256 query rows from the
    middle of the first 128-position page to the middle of its third,
    under a table six pages wide: three pages read, not six."""
    rng = np.random.default_rng(41 + quantized)
    C, H, Hkv, hd, page, n_pages = 256, 4, 1, 8, 128, 6
    got, ref, live = _walk_case(
        rng, np.array([64], np.int32), np.array([True]), C, H, Hkv, hd,
        page, n_pages, quantized)
    assert live.tolist() == [3]
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_walk_stops_at_the_table_width():
    """A position at or past the table's last page (the engine clamps
    its writes there) walks every page and no further."""
    rng = np.random.default_rng(43)
    page, n_pages = 4, 3
    p0 = np.array([n_pages * page - 1, n_pages * page + 5], np.int32)
    got, ref, live = _walk_case(rng, p0, np.array([True, True]), 1, 2, 2,
                                8, page, n_pages, False)
    assert live.tolist() == [n_pages, n_pages]
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_probe_checks_the_walk_under_a_wider_table(monkeypatch):
    """The probe as the chip runs it (interpreted here): passes on the
    real kernel at the decode, verify and page-crossing chunk widths,
    dense and int8, and declines a kernel that reads the table's whole
    width (the dead entries name a page of NaNs)."""
    import functools

    import deeplearning4j_tpu.ops.pallas_paged_attention as pk

    real = pk.paged_attention
    monkeypatch.setattr(pk, "paged_attention",
                        functools.partial(real, interpret=True))
    f32 = jnp.dtype(jnp.float32)
    assert pk._eager_probe(f32, 1, 4, 2, 8, 4)
    assert pk._eager_probe(f32, 3, 2, 2, 8, 4)
    assert pk._eager_probe(f32, 6, 2, 2, 8, 4)   # wider than a page
    assert pk._eager_probe(f32, 1, 2, 2, 8, 4, True)

    def whole_table(q, k_pool, v_pool, pt, p0, **kw):
        kd, vd = paged_gather(k_pool, v_pool, pt)
        qpos = p0[:, None] + jnp.arange(q.shape[1])[None, :]
        return jax.vmap(cached_attention_chunk)(q, kd, vd, qpos) \
            .reshape(q.shape)

    monkeypatch.setattr(pk, "paged_attention", whole_table)
    assert pk._eager_probe(f32, 1, 2, 2, 8, 4) is False


def test_dispatch_hands_the_kernel_one_signature_a_class(monkeypatch):
    """`active=None` reaches the jitted entry as a mask of ones, so a
    shape class is one traced function whether or not the caller
    gates."""
    import deeplearning4j_tpu.ops.pallas_paged_attention as pk

    seen = []

    def fake(q, k_pool, v_pool, pt, p0, **kw):
        seen.append(kw)
        return q

    monkeypatch.setattr(pk, "_platform_supported", lambda: True)
    monkeypatch.setattr(pk, "_probe_verdict", lambda *a, **k: True)
    monkeypatch.setattr(pk, "_vmem_limit", lambda: 1 << 30)
    monkeypatch.setattr(pk, "paged_attention", fake)
    rng = np.random.default_rng(47)
    k_pool, v_pool = _rand_pools(rng, 2, 2, 8, 4)
    q = jnp.zeros((2, 1, 2, 8), jnp.float32)
    pt = jnp.zeros((2, 2), jnp.int32)
    pk.paged_attention_or_none(q, jnp.asarray(k_pool), jnp.asarray(v_pool),
                               pt, jnp.zeros((2,), jnp.int32))
    (kw,) = seen
    assert kw["active"].dtype == jnp.bool_ and bool(kw["active"].all())
    assert kw["k_scale"] is None and kw["v_scale"] is None


def test_dispatch_declines_on_cpu_and_auto_is_bitwise_gather():
    """Tier-1 contract: on the CPU backend `paged_attention_or_none`
    returns None (never a compiled Pallas-TPU path), and the `*_auto`
    wrappers the engine traces are BIT-IDENTICAL to the gather
    reference — the kernel's existence cannot perturb CPU tests."""
    rng = np.random.default_rng(19)
    S, H, Hkv, hd, page, n_pages = 2, 4, 2, 8, 4, 2
    P = S * n_pages
    k_pool, v_pool = _rand_pools(rng, P, Hkv, hd, page)
    pt = (1 + np.arange(P)).reshape(S, n_pages).astype(np.int32)
    pos = np.array([3, 7], np.int32)
    q1 = rng.standard_normal((S, H, hd)).astype(np.float32)
    assert paged_attention_or_none(
        jnp.asarray(q1[:, None]), jnp.asarray(k_pool),
        jnp.asarray(v_pool), jnp.asarray(pt), jnp.asarray(pos)) is None
    auto = np.asarray(paged_attention_step_auto(
        jnp.asarray(q1), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos)))
    ref = np.asarray(paged_attention_step(
        jnp.asarray(q1), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos)))
    np.testing.assert_array_equal(auto, ref)
    qc = rng.standard_normal((S, 3, H, hd)).astype(np.float32)
    auto_c = np.asarray(paged_attention_chunk_auto(
        jnp.asarray(qc), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos)))
    ref_c = _gather_chunk_ref(qc, k_pool, v_pool, pt, pos)
    np.testing.assert_array_equal(auto_c, ref_c.reshape(S, 3, H * hd))


def test_kill_switch_forces_gather_path(monkeypatch):
    """`DL4J_TPU_NO_PALLAS_PAGED_ATTENTION`
    must decline dispatch before any platform probing."""
    monkeypatch.setenv("DL4J_TPU_NO_PALLAS_PAGED_ATTENTION", "1")
    from deeplearning4j_tpu.ops.pallas_paged_attention import (
        _platform_supported,
    )

    assert _platform_supported() is False


def test_vmem_estimate_scales_and_gates():
    """The residency estimate grows with every tile dimension and the
    dispatcher declines shapes above the generation-derived ceiling
    (here: proven arithmetically — a serving-shaped config fits the
    112 MiB v4/v5-class ceiling with orders-of-magnitude headroom, a
    absurdly wide one does not)."""
    small = vmem_bytes_estimate(C=1, H=8, Hkv=8, hd=128, page=128,
                                itemsize=2)
    assert small < 16 * 1024 * 1024  # fits even a v2/v3 core
    assert vmem_bytes_estimate(2, 8, 8, 128, 128, 2) > small
    assert vmem_bytes_estimate(1, 16, 8, 128, 128, 2) > small
    assert vmem_bytes_estimate(1, 8, 8, 128, 256, 2) > small
    huge = vmem_bytes_estimate(C=4096, H=64, Hkv=64, hd=256, page=512,
                               itemsize=4)
    assert huge > 112 * 1024 * 1024
    # int8 pools halve the KV tile bytes at the same shape
    assert vmem_bytes_estimate(1, 8, 8, 128, 128, 4, kv_itemsize=1) \
        < vmem_bytes_estimate(1, 8, 8, 128, 128, 4)


# ------------------------------------------------ int8-KV variant


def _rand_quant_pools(rng, P, Hkv, hd, page):
    """int8 payload pages + per-(head, position) f32 scale pages — the
    engine's quantized-pool layout (`serving/quantize.py`)."""
    k_pool = rng.integers(-127, 128, (P + 1, Hkv, hd, page)).astype(np.int8)
    v_pool = rng.integers(-127, 128, (P + 1, Hkv, page, hd)).astype(np.int8)
    k_scale = rng.uniform(0.005, 0.05, (P + 1, Hkv, page)).astype(np.float32)
    v_scale = rng.uniform(0.005, 0.05, (P + 1, Hkv, page)).astype(np.float32)
    return k_pool, v_pool, k_scale, v_scale


def _gather_quant_chunk_ref(q, k_pool, v_pool, ks, vs, pt, p0):
    kd, vd = paged_gather_quant(jnp.asarray(k_pool), jnp.asarray(v_pool),
                                jnp.asarray(ks), jnp.asarray(vs),
                                jnp.asarray(pt), jnp.float32)
    C = q.shape[1]
    qpos = jnp.asarray(p0)[:, None] + jnp.arange(C)[None, :]
    out = jax.vmap(cached_attention_chunk)(jnp.asarray(q), kd, vd, qpos)
    return np.asarray(out).reshape(q.shape)


@pytest.mark.parametrize("H,Hkv,C", [(2, 2, 1), (4, 2, 1), (4, 1, 3),
                                     (4, 2, 4)])
def test_int8_kernel_matches_gather_quant_reference_fuzz(H, Hkv, C):
    """The quantized kernel variant (dequant inside the page loop) is
    pinned against the `paged_gather_quant` + dense oracle over the
    same fuzz surface as the dense kernel: scrambled page tables,
    cross-slot page reuse, holes to the trash page, GQA groupings,
    decode and chunk widths."""
    rng = np.random.default_rng(300 + 100 * H + 10 * Hkv + C)
    S, hd, page, n_pages = 3, 8, 4, 4
    P = S * n_pages
    for trial in range(3):
        k_pool, v_pool, ks, vs = _rand_quant_pools(rng, P, Hkv, hd, page)
        perm = rng.permutation(np.arange(1, P + 1))
        pt = perm.reshape(S, n_pages).astype(np.int32)
        pt[1, 0] = pt[0, 0]   # shared prefix page
        pt[2, 2:] = 0         # holes -> trash page
        p0 = np.array([int(rng.integers(0, n_pages * page - C)),
                       int(rng.integers(0, n_pages * page - C)),
                       int(rng.integers(0, 2 * page - C))], np.int32)
        q = rng.standard_normal((S, C, H, hd)).astype(np.float32)
        ref = _gather_quant_chunk_ref(q, k_pool, v_pool, ks, vs, pt, p0)
        got = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(pt), jnp.asarray(p0),
            k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs),
            interpret=True))
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_int8_kernel_trash_and_stale_pages_masked():
    """Poisoned int8 pages AND poisoned scale pages past each slot's
    position (plus the trash page itself) must never move the output —
    the same reallocation-safety convention as the dense kernel, now
    covering the scale sidecar too."""
    rng = np.random.default_rng(23)
    S, H, Hkv, hd, page, n_pages = 2, 2, 2, 4, 4, 4
    P = S * n_pages
    k_pool, v_pool, ks, vs = _rand_quant_pools(rng, P, Hkv, hd, page)
    pt = (1 + np.arange(P)).reshape(S, n_pages).astype(np.int32)
    pos = np.array([2, 5], np.int32)
    q = rng.standard_normal((S, 1, H, hd)).astype(np.float32)

    def run(kp, vp, kss, vss, table):
        return np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(pos),
            k_scale=jnp.asarray(kss), v_scale=jnp.asarray(vss),
            interpret=True))

    base = run(k_pool, v_pool, ks, vs, pt)
    k2, v2 = k_pool.copy(), v_pool.copy()
    ks2, vs2 = ks.copy(), vs.copy()
    for pid in (0, 2, 3, 4, 7, 8):  # trash page + pages past positions
        k2[pid], v2[pid] = 127, -127
        ks2[pid], vs2[pid] = 1e6, 1e6
    pt2 = pt.copy()
    pt2[0, 2:] = 0
    np.testing.assert_array_equal(run(k2, v2, ks2, vs2, pt2), base)


def test_int8_dispatch_declines_on_cpu_and_auto_matches_oracle():
    """Tier-1 contract for the int8 tier: on CPU
    `paged_attention_or_none` declines quantized calls, and the
    `*_auto` wrappers with scales are BIT-IDENTICAL to the
    `paged_gather_quant` + dense oracle the engine's numerics are
    certified against."""
    rng = np.random.default_rng(29)
    S, H, Hkv, hd, page, n_pages = 2, 4, 2, 8, 4, 2
    P = S * n_pages
    k_pool, v_pool, ks, vs = _rand_quant_pools(rng, P, Hkv, hd, page)
    pt = (1 + np.arange(P)).reshape(S, n_pages).astype(np.int32)
    pos = np.array([3, 7], np.int32)
    q1 = rng.standard_normal((S, H, hd)).astype(np.float32)
    assert paged_attention_or_none(
        jnp.asarray(q1[:, None]), jnp.asarray(k_pool),
        jnp.asarray(v_pool), jnp.asarray(pt), jnp.asarray(pos),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs)) is None
    auto = np.asarray(paged_attention_step_auto(
        jnp.asarray(q1), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs)))
    kd, vd = paged_gather_quant(jnp.asarray(k_pool), jnp.asarray(v_pool),
                                jnp.asarray(ks), jnp.asarray(vs),
                                jnp.asarray(pt), jnp.float32)
    ref = np.asarray(cached_attention_step(jnp.asarray(q1), kd, vd,
                                           jnp.asarray(pos)))
    np.testing.assert_array_equal(auto, ref)
    qc = rng.standard_normal((S, 3, H, hd)).astype(np.float32)
    auto_c = np.asarray(paged_attention_chunk_auto(
        jnp.asarray(qc), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray(pos),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs)))
    ref_c = _gather_quant_chunk_ref(qc, k_pool, v_pool, ks, vs, pt, pos)
    np.testing.assert_array_equal(auto_c, ref_c.reshape(S, 3, H * hd))


def test_int8_kill_switch_gates_dispatch_before_probing(monkeypatch):
    """`DL4J_TPU_NO_INT8_KV=1` must decline QUANTIZED dispatch even on
    a platform where the dense kernel would run — the scales-present
    path has its own gate ahead of any probe."""
    import deeplearning4j_tpu.ops.pallas_paged_attention as pk

    monkeypatch.setenv("DL4J_TPU_NO_INT8_KV", "1")
    monkeypatch.setattr(pk, "_platform_supported", lambda: True)
    rng = np.random.default_rng(31)
    S, H, Hkv, hd, page, n_pages = 2, 2, 2, 4, 4, 2
    P = S * n_pages
    k_pool, v_pool, ks, vs = _rand_quant_pools(rng, P, Hkv, hd, page)
    pt = (1 + np.arange(P)).reshape(S, n_pages).astype(np.int32)
    q = rng.standard_normal((S, 1, H, hd)).astype(np.float32)
    assert pk._int8_kv_allowed() is False
    assert pk.paged_attention_or_none(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pt), jnp.asarray([1, 3], np.int32),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs)) is None


@pytest.mark.parametrize("quantize", [None, {"kv": "int8"}],
                         ids=["dense", "int8"])
def test_engine_tokens_equal_gather_build(monkeypatch, quantize):
    """A `DecodeEngine` whose `kv.attend` rides the (interpreted) kernel
    emits the gather build's tokens: single steps and fused chunks, a
    prompt that rides the chunked prefill (C = prefill_chunk over a
    page edge), slot and page reuse, inactive lanes, under a page table
    eight wide of which a slot holds two to four pages."""
    import deeplearning4j_tpu.ops.pallas_paged_attention as pk
    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine

    net = MultiLayerNetwork(gpt_configuration(
        seed=7, vocab_size=48, d_model=32, n_heads=2, n_layers=2,
        max_length=64))
    net.init()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 48, (n,)).astype(np.int32)
               for n in (6, 6, 21, 6, 6)]
    n_tokens = [9, 4, 12, 7, 5]

    def tokens():
        eng = DecodeEngine(net, n_slots=2, max_len=64, page_size=8,
                           prompt_buckets=(8,), prefill_chunk=8,
                           decode_chunk=4, quantize=quantize)
        try:
            reqs = [eng.submit(p, n) for p, n in zip(prompts, n_tokens)]
            return [np.asarray(r.result(timeout=300.0)) for r in reqs]
        finally:
            eng.shutdown(drain_timeout=10.0)

    want = tokens()
    widths = set()
    real = pk.paged_attention

    def interpreted(q, *a, **kw):
        widths.add((q.shape[1], a[0].dtype, a[2].shape[1]))
        return real(q, *a, interpret=True, **kw)

    monkeypatch.setattr(pk, "_platform_supported", lambda: True)
    monkeypatch.setattr(pk, "_probe_verdict", lambda *a: True)
    monkeypatch.setattr(pk, "paged_attention", interpreted)
    got = tokens()
    pool = jnp.dtype(jnp.int8 if quantize else jnp.float32)
    assert widths == {(1, pool, 8), (8, pool, 8)}
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)

