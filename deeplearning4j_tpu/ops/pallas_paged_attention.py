"""Pallas TPU paged-attention decode kernel: walk the page table in
place, no dense gather.

The paged decode engine (`serving/decode_engine.py`) stores each block's
KV cache as a pool of fixed-size pages in the r4 decode layouts — K
`(P+1, Hkv, hd, page)`, V `(P+1, Hkv, page, hd)`, page 0 the reserved
trash page — with an int32 per-slot page table mapping logical page
index to pool page id. The portable XLA path
(`ops.attention.paged_gather` + `cached_attention_step` /
`cached_attention_chunk`) first REASSEMBLES each slot's pages into a
dense transient, then attends: every cache byte moves through HBM twice
(pool → transient write, transient → compute read) on a path that is
cache-bandwidth-bound by construction. This kernel is the PagedAttention
move (Kwon et al., SOSP 2023): the pools stay in HBM, the page table
rides in as a scalar-prefetch operand (`pltpu.PrefetchScalarGridSpec`),
and for each slot the kernel copies pages `page_table[slot, 0 ..
(pos + C - 1) // page]` — the slot's LIVE pages, however wide the table
— from the pool into VMEM exactly once, double-buffered, while the
flash-style online-softmax accumulator (the `ops/pallas_attention.py`
recurrence) runs over them in logical order with no intermediate
materialization. A table entry past a slot's live pages costs nothing:
no grid step, no fetch.

One kernel serves every paged shape of the serving hot path via the
chunk width `C` of the query block `(S, C, H, hd)`:

- `C == 1`: the decode step (`cached_attention_step` semantics — each
  slot's single query at position `pos[s]` attends to cache entries
  `<= pos[s]`);
- `C == k+1`: the speculative verify chunk
  (`_verify_block_attention` semantics);
- `C == prefill_chunk`: the chunked-prefill suffix
  (`cached_attention_chunk` semantics, S=1 per dispatch).

All three mask identically because the serving paths only ever issue
CONTIGUOUS query positions: row `c` of slot `s` attends to entries
`<= positions[s] + c`. GQA contracts the un-repeated `Hkv` pool heads
against query groups of `G = H // Hkv` heads folded into the matmul's
sublane axis. Unallocated page-table entries (page 0, the trash page)
lie past the slot's live pages and are never read; within the last live
page, positions past the slot's limit are masked; inactive lanes
(optional `active` mask) walk no page and emit zeros via the `l == 0`
finalization, the same discipline the flash kernel uses for
fully-masked rows.

Dispatch rides the `ops/kernel_dispatch.py` contract: the probe
compiles AND runs the kernel at the exact shape class and CHECKS the
output against the gather+dense reference (a miscompiling Mosaic
toolchain degrades to the XLA path, never to wrong tokens); VMEM
residency (double-buffered K/V page tiles + accumulators) is sized
against the generation-derived `vmem_limit_bytes()` ceiling and
oversized shapes decline; `DL4J_TPU_NO_PALLAS_PAGED_ATTENTION` forces
the gather path (the bench's A/B kill switch); CPU backends never
dispatch, so tier-1 runs the XLA numerics bit-for-bit unchanged.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.kernel_dispatch import (
    dot as _dot,
    mxu_dtype as _mxu_dtype,
    platform_supported as _kernels_dispatch,
    probe_verdict as _probe_verdict,
    record_decline as _record_decline,
    stat_dtype as _stat_dtype,
    vmem_limit_bytes as _vmem_limit,
)

FAMILY = "paged_attention"  # this module's row in kernel_verdicts()


NEG_INF = -1e30  # matches ops/attention.py: exp()/where() stay NaN-free


def _paged_kernel(pt_ref, p0_ref, gate_ref, q_ref, k_hbm, v_hbm, *rest,
                  page: int, C: int, G: int, Hkv: int, hd: int,
                  n_pages: int, sm_scale: float, quantized: bool = False,
                  window: Optional[int] = None):
    """Grid (S,), slots in order: slot `s` walks pages
    `0 .. (p0 + C - 1) // page` of its page-table row and no others — a
    loop whose trip count comes from the slot's position, not from the
    table's width — with one (C·G, page) score tile per KV head per
    page, accumulated with the online-softmax recurrence in VMEM
    scratch. The pools stay in HBM; each live page is copied once into
    one of two VMEM buffers while the page before it is computed on,
    and a slot's last iteration starts the next slot's first copy, so
    only the call's very first copy is waited for in full. Scalar-
    prefetch refs: the page table (the page ids the copies dereference),
    the per-slot start positions, and the active gate. `state` carries
    across grid steps which buffer the slot's first page lands in and
    whether the slot before it already started that copy.

    `quantized=True` is the int8-KV variant (ROADMAP item 1's
    "dequant inside the page loop"): the pools hold int8 pages —
    HALF the DMA bytes of bf16 — and each page's two (Hkv, page) f32
    scale tiles are copied with their payload. Dequant happens in VMEM
    right before each matmul: one f32 multiply per element by the
    per-(head, position) scale row, then the cast to the MXU feed
    dtype. Numerics are pinned against the `paged_gather_quant` + dense
    reference by the dispatch probe and the interpret-mode tests.

    `window` W (static; None: the walk above, op for op): row `c` of
    slot `s` sees the W positions that end at `p0 + c`. The walk starts
    at logical page `max(0, p0 - W + 1) // page`, that page's older
    positions are masked, and the slot's row is a RING of `n_pages`
    entries: logical page `j` lies at entry `j % n_pages`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quantized:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf = rest[:7]
        pools = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                 (vs_hbm, vs_buf))
    else:
        o_ref, k_buf, v_buf = rest[:3]
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    sem, state, acc_scr, m_scr, l_scr = rest[-5:]

    s = pl.program_id(0)
    S = pl.num_programs(0)
    CG = C * G

    def first_page(slot):
        # the logical page of the oldest position slot's first row sees
        return jnp.maximum(p0_ref[slot] - window + 1, 0) // page

    def live_pages(slot):
        # pages holding a position some query row can see; an inactive
        # lane walks none: its l stays 0 and the finalize emits exact
        # zeros (the flash kernel's fully-masked-row discipline)
        n = (p0_ref[slot] + C - 1) // page + 1
        if window is not None:
            n = n - first_page(slot)
        n = jnp.minimum(n, n_pages)
        return jnp.where(gate_ref[slot] != 0, n, 0)

    def copies(slot, j, buf):
        # the slot's `j`-th live page
        pid = pt_ref[slot, j] if window is None \
            else pt_ref[slot, (first_page(slot) + j) % n_pages]
        return [pltpu.make_async_copy(pool.at[pid], dst.at[buf],
                                      sem.at[i, buf])
                for i, (pool, dst) in enumerate(pools)]

    @pl.when(s == 0)
    def _first_slot():
        state[0] = 0   # the buffer this slot's first page lands in
        state[1] = 0   # 1: the slot before already started that copy

    acc_scr[:] = jnp.zeros_like(acc_scr)
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)

    p0 = p0_ref[s]
    n_live = live_pages(s)
    nxt = jnp.minimum(s + 1, S - 1)
    n_next = jnp.where(s + 1 < S, live_pages(nxt), 0)
    buf0 = state[0]

    @pl.when((n_live > 0) & (state[1] == 0))
    def _start_cold():
        for c in copies(s, 0, buf0):
            c.start()

    def _page(j, carry):
        buf = (buf0 + j) % 2

        @pl.when(j + 1 < n_live)
        def _fetch_next_page():
            for c in copies(s, j + 1, 1 - buf):
                c.start()

        @pl.when((j + 1 == n_live) & (n_next > 0))
        def _fetch_next_slot():
            for c in copies(nxt, 0, 1 - buf):
                c.start()

        for c in copies(s, j, buf):
            c.wait()
        dt = _mxu_dtype(q_ref.dtype)
        q = q_ref[0]                                       # (C, H, hd)
        kpos = (j if window is None else first_page(s) + j) * page \
            + jax.lax.broadcasted_iota(jnp.int32, (CG, page), 1)
        rowc = jax.lax.broadcasted_iota(jnp.int32, (CG, page), 0) // G
        mask = kpos <= p0 + rowc
        if window is not None:
            mask = mask & (kpos > p0 + rowc - window)
        for h in range(Hkv):
            # query heads h*G..(h+1)*G-1 share KV head h; fold (C, G)
            # into the sublane axis so one matmul serves the group
            qh = q[:, h * G:(h + 1) * G, :].reshape(CG, hd).astype(dt)
            if quantized:
                # dequant-in-VMEM: int8 page × per-position f32 scale
                # row, then the MXU-feed cast — the DMA moved 1 byte
                # per element, the matmul sees full-precision values
                ks = ks_buf[buf, h].reshape(1, page)
                kh = (k_buf[buf, h].astype(jnp.float32) * ks).astype(dt)
                vs = vs_buf[buf, h].reshape(page, 1)
                vh = (v_buf[buf, h].astype(jnp.float32) * vs).astype(dt)
            else:
                kh = k_buf[buf, h].astype(dt)              # (hd, page)
                vh = v_buf[buf, h].astype(dt)              # (page, hd)
            sc = _dot(qh, kh, ((1,), (0,)), dt) * sm_scale
            sc = jnp.where(mask, sc, NEG_INF)
            m_prev = m_scr[h][:, :1]
            l_prev = l_scr[h][:, :1]
            m_blk = jnp.max(sc, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_blk)
            p = jnp.exp(sc - m_new)
            # fully-masked-so-far rows sit at m ~ NEG_INF: zero their
            # weights so l stays 0 and finalize maps them to output 0
            p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + _dot(p.astype(dt), vh,
                                                  ((1,), (0,)), dt)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr[h].shape)
            l_scr[h] = jnp.broadcast_to(l_new, l_scr[h].shape)
        return carry

    jax.lax.fori_loop(0, n_live, _page, 0)

    @pl.when(n_live > 0)
    def _hand_over():
        state[0] = (buf0 + n_live) % 2
        state[1] = (n_next > 0).astype(jnp.int32)

    for h in range(Hkv):
        l = l_scr[h][:, :1]
        o = jnp.where(l > 0, acc_scr[h] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0, :, h * G:(h + 1) * G, :] = \
            o.reshape(C, G, hd).astype(o_ref.dtype)


# jitted, like the family's other serving kernels, so that a program
# which attends in 24 layers traces and lowers the kernel once and calls
# it 24 times: lowering is paid on every start-up, compile cache or not
# (PERF.md, PR 26 and PR 33)
@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                    v_pool: jnp.ndarray, page_table: jnp.ndarray,
                    positions: jnp.ndarray, *,
                    k_scale: Optional[jnp.ndarray] = None,
                    v_scale: Optional[jnp.ndarray] = None,
                    active: Optional[jnp.ndarray] = None,
                    interpret: bool = False,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Paged decode/verify/chunk attention, streamed from the pool.

    `q`: (S, C, H, hd) — C contiguous query tokens per slot (C=1 for
    the decode step). `k_pool`/`v_pool`: (P+1, Hkv, hd, page) /
    (P+1, Hkv, page, hd) — the resident pool layouts, page 0 = trash.
    `page_table`: (S, n_pages) int32 pool page ids in logical order.
    `positions`: (S,) int32 — row c of slot s attends to cache entries
    `<= positions[s] + c`, exactly `cached_attention_step` (C=1,
    positions=pos) and `cached_attention_chunk` (positions=first query
    position) over the gathered view. Only the table's entries
    `0 .. (positions[s] + C - 1) // page` are read: what lies past a
    slot's live pages (unallocated entries, 0 by the engine's
    convention) is never dereferenced. `active`: optional (S,) bool —
    False lanes read no page and emit zeros (their output is discarded
    downstream by the engine's masking; the gather path computes
    garbage-but-finite values for them instead, equally discarded).

    int8 pools pass `k_scale`/`v_scale` ((P+1, Hkv, page) f32): each
    page's scale tiles are copied with its payload and the kernel
    dequantizes in VMEM inside the page loop — the
    `serving/quantize.py` tier's fast path.

    `window` W: row c of slot s attends to the entries in `(positions[s]
    + c - W, positions[s] + c]`, and `page_table` (S, R) is then a ring:
    logical page j at entry `j % R` (`_paged_kernel`). The caller sees
    to it that the pages a dispatch's rows can see number at most R.

    Returns (S, C, H, hd) in q.dtype.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, C, H, hd = q.shape
    _, Hkv, _, page = k_pool.shape
    n_pages = page_table.shape[1]
    G = H // Hkv
    quantized = k_scale is not None
    sdt = _stat_dtype(q.dtype)
    gate = jnp.ones((S,), jnp.int32) if active is None \
        else jnp.asarray(active).astype(jnp.int32)
    kernel = functools.partial(
        _paged_kernel, page=page, C=C, G=G, Hkv=Hkv, hd=hd,
        n_pages=n_pages, sm_scale=1.0 / float(hd) ** 0.5,
        quantized=quantized, window=window)

    def slot(s, pt, p0, g):
        return (s, 0, 0, 0)

    # the pools stay where they are: the kernel copies the pages it
    # walks, and nothing else of them moves
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quantized else [])
    buffers = [pltpu.VMEM((2,) + p.shape[1:], p.dtype) for p in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, C, H, hd), slot)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, C, H, hd), slot),
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((2,), jnp.int32),         # buffer, copy started
            pltpu.VMEM((Hkv, C * G, hd), sdt),   # unnormalised output
            pltpu.VMEM((Hkv, C * G, 128), sdt),  # running max m
            pltpu.VMEM((Hkv, C * G, 128), sdt),  # running denom l
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, C, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # in order: a slot's last iteration starts the next slot's
            # first copy
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(page_table.astype(jnp.int32), positions.astype(jnp.int32), gate, q,
      *pools)


def vmem_bytes_estimate(C: int, H: int, Hkv: int, hd: int, page: int,
                        itemsize: int, kv_itemsize: Optional[int] = None
                        ) -> int:
    """Resident VMEM of one grid step: double-buffered q/K/V/out tiles
    plus the f32 accumulator scratch. Used to decline shapes that
    cannot fit under the generation-derived ceiling before Mosaic
    discovers it mid-serving. `kv_itemsize` prices the K/V page tiles
    separately from the q/out tiles (int8 pools: 1 byte per element
    plus the double-buffered f32 scale tiles); default: `itemsize`."""
    CG = C * (H // Hkv)
    kvi = itemsize if kv_itemsize is None else kv_itemsize
    tiles = 2 * itemsize * 2 * C * H * hd             # q + out
    tiles += 2 * kvi * 2 * Hkv * hd * page            # K + V page tiles
    if kv_itemsize == 1:
        tiles += 2 * 4 * 2 * Hkv * page               # f32 scale tiles
    scratch = 4 * (Hkv * CG * hd + 2 * Hkv * CG * 128)
    return tiles + scratch


def _platform_supported() -> bool:
    # the switch forces the gather fallback (A/B benches, tests)
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_PAGED_ATTENTION")


def _int8_kv_allowed() -> bool:
    """The int8-KV kill switch at the DISPATCH layer: with
    ``DL4J_TPU_NO_INT8_KV=1`` the int8 kernel declines and callers run
    the `paged_gather_quant` + dense reference. (The engine honors the
    same switch at BUILD time — pools stay full-precision — so flipping
    it before construction is the bench's whole-tier A/B lever; here it
    additionally protects a live engine whose pools are already
    int8.)"""
    import os

    return os.environ.get("DL4J_TPU_NO_INT8_KV", "") \
        not in ("1", "true", "yes")


def _eager_probe(dtype, C: int, H: int, Hkv: int, hd: int, page: int,
                 quantized: bool = False,
                 window: Optional[int] = None) -> bool:
    """Compile + run the kernel once at this exact shape class on tiny
    concrete pools, out of trace, and CHECK the output against the
    gather+dense reference — the dispatch contract's parity-probed
    variant: a toolchain that compiles-but-miscompiles falls back to
    XLA instead of serving wrong tokens. The int8 variant probes with
    int8 pools + f32 scale pages against the `paged_gather_quant`
    oracle, so the page-loop dequant is parity-checked before the
    first live dispatch. A windowed class probes a ring that has
    wrapped (`_eager_probe_window`)."""
    import numpy as np

    if window is not None:
        return _eager_probe_window(dtype, C, H, Hkv, hd, page, window)

    from deeplearning4j_tpu.ops.attention import (
        cached_attention_chunk,
        paged_gather,
        paged_gather_quant,
    )

    # a table wider than any slot's live pages, as the engine's is: one
    # slot ends on a page's last position, one starts on a page's
    # first. The entries past a slot's live pages name a page of NaNs,
    # so a read of a page no query can see fails the comparison; the
    # reference gathers the trash page there
    S = 2
    p0 = np.asarray([max(page - C, 0), page], np.int32)
    live = (p0 + C - 1) // page + 1
    n_pages = int(live.max()) + 2
    P = int(live.sum())
    dead = P + 1
    pt = np.full((S, n_pages), dead, np.int32)
    pt[0, :live[0]] = 1 + np.arange(live[0])
    pt[1, :live[1]] = 1 + live[0] + np.arange(live[1])
    pt_ref = jnp.asarray(np.where(pt == dead, 0, pt))
    pt, p0 = jnp.asarray(pt), jnp.asarray(p0)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, C, H, hd)), dtype)
    qpos = p0[:, None] + jnp.arange(C)[None, :]
    if quantized:
        k_pool = jnp.asarray(rng.integers(
            -127, 128, (P + 2, Hkv, hd, page)), jnp.int8)
        v_pool = jnp.asarray(rng.integers(
            -127, 128, (P + 2, Hkv, page, hd)), jnp.int8)
        k_scale = jnp.asarray(
            rng.uniform(0.005, 0.02, (P + 2, Hkv, page)), jnp.float32)
        v_scale = jnp.asarray(
            rng.uniform(0.005, 0.02, (P + 2, Hkv, page)), jnp.float32)
        out = np.asarray(paged_attention(
            q, k_pool, v_pool, pt, p0,
            k_scale=k_scale.at[dead].set(jnp.nan),
            v_scale=v_scale.at[dead].set(jnp.nan)))
        kd, vd = paged_gather_quant(k_pool, v_pool, k_scale, v_scale,
                                    pt_ref, dtype)
    else:
        k_pool = jnp.asarray(
            rng.standard_normal((P + 2, Hkv, hd, page)), dtype)
        v_pool = jnp.asarray(
            rng.standard_normal((P + 2, Hkv, page, hd)), dtype)
        out = np.asarray(paged_attention(
            q, k_pool.at[dead].set(jnp.nan), v_pool.at[dead].set(jnp.nan),
            pt, p0))
        kd, vd = paged_gather(k_pool, v_pool, pt_ref)
    ref = np.asarray(jax.vmap(cached_attention_chunk)(q, kd, vd, qpos))
    ref = ref.reshape(S, C, H, hd).astype(np.float32)
    out = out.astype(np.float32)
    if not np.all(np.isfinite(out)):
        return False
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    if not np.allclose(out, ref, atol=tol, rtol=tol):
        raise ValueError(
            "kernel compiled but disagrees with the gather reference: "
            f"max abs err {np.max(np.abs(out - ref)):.3g} at atol=rtol="
            f"{tol:g}")
    return True


def _eager_probe_window(dtype, C: int, H: int, Hkv: int, hd: int,
                        page: int, window: int) -> bool:
    """The windowed class: two slots whose rings of R entries have
    wrapped (one whose window starts inside a page, one on a page's
    first position), against `ops.attention.ring_attention_chunk` over
    the gathered rings."""
    import numpy as np

    from deeplearning4j_tpu.ops.attention import ring_attention_chunk

    S = 2
    # one entry more than the engine's ring: its chunks start on a
    # multiple of their width, these rows need not
    R = -(-window // page) + -(-C // page) + 1
    p0 = np.asarray([2 * R * page + 3 * page // 2,
                     R * page + window - 1], np.int32)
    rng = np.random.default_rng(0)
    pt = jnp.asarray(1 + rng.permutation(S * R).reshape(S, R), jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, C, H, hd)), dtype)
    k_pool = jnp.asarray(
        rng.standard_normal((S * R + 1, Hkv, hd, page)), dtype)
    v_pool = jnp.asarray(
        rng.standard_normal((S * R + 1, Hkv, page, hd)), dtype)
    p0 = jnp.asarray(p0)
    out = np.asarray(paged_attention(q, k_pool, v_pool, pt, p0,
                                     window=window)).astype(np.float32)
    ref = np.asarray(ring_attention_chunk(
        q, k_pool, v_pool, pt, p0, window)).reshape(S, C, H, hd) \
        .astype(np.float32)
    if not np.all(np.isfinite(out)):
        return False
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    if not np.allclose(out, ref, atol=tol, rtol=tol):
        raise ValueError(
            "windowed kernel compiled but disagrees with the gathered "
            f"ring: max abs err {np.max(np.abs(out - ref)):.3g} at "
            f"atol=rtol={tol:g}")
    return True


def paged_attention_or_none(q, k_pool, v_pool, page_table, positions,
                            active=None, k_scale=None, v_scale=None,
                            window=None) -> Optional[jnp.ndarray]:
    """Dispatch probe (the reflective cuDNN-helper load): returns None
    when the kernel can't serve this call — CPU backend, kill switch,
    unsupported dtype, VMEM overflow at this shape — or when the shape
    class failed its compile+parity probe. Callers fall back to
    `paged_gather` + the dense step/chunk (`paged_gather_quant` for
    int8 pools). The int8 variant (scales present) is additionally
    gated by ``DL4J_TPU_NO_INT8_KV`` and probes its own shape-class
    key. A `window` (the table a ring) is a shape class of its own, its
    key ending `("window", W)`; int8 pools have no windowed form."""
    S, C, H, hd = q.shape
    _, Hkv, _, page = k_pool.shape
    quantized = k_scale is not None
    if not _platform_supported() \
            or q.dtype not in (jnp.float32, jnp.bfloat16) \
            or H % Hkv or (quantized and window is not None):
        return None
    if quantized and not _int8_kv_allowed():
        return None
    key = (jnp.dtype(q.dtype).name, C, H, Hkv, hd, page,
           "int8" if quantized else "dense")
    if window is not None:
        key += ("window", int(window))
    kv_itemsize = 1 if quantized else q.dtype.itemsize
    est = vmem_bytes_estimate(C, H, Hkv, hd, page, q.dtype.itemsize,
                              kv_itemsize=kv_itemsize)
    if est > _vmem_limit():
        _record_decline(FAMILY, key,
                        f"needs ~{est >> 20} MiB VMEM > "
                        f"{_vmem_limit() >> 20} MiB ceiling")
        return None
    if not _probe_verdict(FAMILY, key, _eager_probe,
                          (q.dtype, C, H, Hkv, hd, page, quantized)
                          + (() if window is None else (int(window),))):
        return None
    if active is None:  # one traced function a shape class
        active = jnp.ones((S,), jnp.bool_)
    try:
        return paged_attention(q, k_pool, v_pool, page_table, positions,
                               k_scale=k_scale, v_scale=v_scale,
                               active=active, window=window)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, key,
                        f"staging at {q.shape}: {type(e).__name__}: {e}")
        return None
