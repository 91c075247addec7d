"""Scaled-dot-product attention primitives.

The reference (DL4J 0.7.3 era) has no attention — its sequence toolbox is
LSTM+tBPTT (`LSTMHelpers.java:58`, `MultiLayerNetwork.doTruncatedBPTT:1140`)
and its only long-sequence mechanism is window slicing. This build treats
long-context as first-class: the core primitive here is **blockwise
(flash-style) attention** — an online-softmax accumulation over KV chunks via
`lax.scan` — which gives O(T) memory on one chip and is the per-device inner
loop of ring attention (`parallel/sequence.py`) when the sequence axis is
sharded across chips.

Layout: (B, T, H, D) for q/k/v — batch, time, heads, head_dim. The matmuls
are einsums over (T, D)×(D, T') per head: large, batched, MXU-friendly.
"""
from __future__ import annotations

from contextlib import contextmanager
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/where() NaN-free


def mask_bias(key_mask: jnp.ndarray) -> jnp.ndarray:
    """(B, Tk) 1=valid key mask → additive (B, 1, 1, Tk) attention bias."""
    return jnp.where(key_mask[:, None, None, :] > 0, 0.0, NEG_INF)


def _behind_window(s, window: int):
    """Scores `s` (..., Tq, Tk) of causal attention with the keys more
    than `window` - 1 positions behind their query masked off: query `i`
    keeps keys `j` with `i - j < window` (the queries aligned to the end
    of the keys, as the causal mask aligns them)."""
    Tq, Tk = s.shape[-2], s.shape[-1]
    iq = jnp.arange(Tq)[:, None]
    ik = jnp.arange(Tk)[None, :]
    return jnp.where(ik > iq + (Tk - Tq) - window, s, NEG_INF)


def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None,
                   causal: bool = False,
                   window: Optional[int] = None) -> jnp.ndarray:
    """Plain softmax(QKᵀ/√d + bias)·V. q/k/v: (B, T, H, D); bias broadcastable
    to (B, H, Tq, Tk). Reference semantics for the blockwise/ring variants'
    parity tests (the cuDNN-vs-builtin parity pattern,
    `deeplearning4j-cuda/src/test/.../TestConvolution.java`). `window`
    (with `causal`): a query sees the `window` keys that end at its
    own."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if bias is not None:
        s = s + bias
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        iq = jnp.arange(Tq)[:, None]
        ik = jnp.arange(Tk)[None, :]
        s = jnp.where(ik <= iq + (Tk - Tq), s, NEG_INF)
        if window is not None:
            s = _behind_window(s, window)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: softmax over all-NEG_INF is uniform garbage — zero
    # masked positions so such rows produce output 0, matching
    # blockwise_attention's l == 0 finalisation (the two dispatch paths must
    # agree for any mask)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def full_attention_grouped(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           bias: Optional[jnp.ndarray] = None,
                           causal: bool = False,
                           window: Optional[int] = None) -> jnp.ndarray:
    """`full_attention` for grouped-query attention WITHOUT materializing
    the repeated K/V: q (B, T, H, D) against k/v carrying only Hkv
    grouped heads (H a multiple of Hkv; query head j reads KV head
    j // (H/Hkv)). The queries fold into (B, T, Hkv, G, D) and the
    score/weighted-sum einsums batch over Hkv with G as a free query
    axis — each K/V element is touched once and BROADCAST across its
    G query heads, instead of being copied G× through HBM by
    `jnp.repeat` (the training path's old cost). Per-head numerics are
    the exact dots `full_attention` computes on the repeated operands,
    so the two paths agree bitwise (pinned in tests/test_ops.py).
    `bias` broadcastable to (B, H, Tq, Tk) — a full H-headed bias is
    regrouped, a broadcasting (B, 1, 1, Tk) mask bias passes through."""
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Tq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   k) / jnp.sqrt(jnp.asarray(D, q.dtype))
    if bias is not None:
        if bias.ndim == 4 and bias.shape[1] == H:
            bias = bias.reshape(B, Hkv, G, *bias.shape[2:])
        else:  # broadcasting head axis (e.g. mask_bias): keep it 1-wide
            bias = bias[:, :, None]
        s = s + bias
    if causal:
        iq = jnp.arange(Tq)[:, None]
        ik = jnp.arange(Tk)[None, :]
        s = jnp.where(ik <= iq + (Tk - Tq), s, NEG_INF)
        if window is not None:
            s = _behind_window(s, window)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    att = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return att.reshape(B, Tq, H, D)


def attention_block_accum(carry: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
                          q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          bias: Optional[jnp.ndarray]):
    """One online-softmax accumulation step against a KV block.

    carry = (o, l, m): running un-normalised output (B, Tq, H, D), running
    softmax denominator (B, H, Tq) and running row max (B, H, Tq). The final
    attention output is o / l. This is the flash-attention recurrence; it is
    exact (not an approximation) for any KV block order.
    """
    o, l, m = carry
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if bias is not None:
        s = s + bias
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    # masked scores sit near NEG_INF; exp(s - m_new) does NOT underflow to 0
    # when the whole row is masked (m_new is then ~NEG_INF too), so zero them
    # explicitly — this keeps l == 0 for fully-masked rows, which
    # attention_finalize maps to output 0 instead of softmax-over-garbage
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = (o * jnp.transpose(corr, (0, 2, 1))[..., None]
             + jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return o_new, l_new, m_new


def _accum_init(q: jnp.ndarray):
    B, Tq, H, D = q.shape
    o = jnp.zeros((B, Tq, H, D), q.dtype)
    l = jnp.zeros((B, H, Tq), q.dtype)
    m = jnp.full((B, H, Tq), NEG_INF, q.dtype)
    return o, l, m


def attention_finalize(o: jnp.ndarray, l: jnp.ndarray) -> jnp.ndarray:
    """o / l with fully-masked rows (l == 0) mapped to 0, not NaN."""
    l_t = jnp.transpose(l, (0, 2, 1))[..., None]
    return jnp.where(l_t > 0, o / jnp.where(l_t > 0, l_t, 1.0), 0.0)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = False,
                        key_mask: Optional[jnp.ndarray] = None,
                        block_size: int = 512,
                        window: Optional[int] = None) -> jnp.ndarray:
    """Memory-efficient exact attention: scan over KV blocks with the
    online-softmax recurrence. Peak memory is O(Tq·block) for scores instead
    of O(Tq·Tk). q/k/v: (B, T, H, D); key_mask: (B, Tk) with 1=valid.
    `k`/`v` may carry fewer heads than `q` (Hkv dividing H; query head j
    reads KV head j // G): they are read as they are, a K/V head's G
    query heads riding the query axis. `window` (with `causal`): a query
    sees the `window` keys that end at its own.

    Under jit the scan compiles to a single XLA while-loop — static shapes,
    no data-dependent Python control flow.
    """
    B, Tk, H, D = k.shape
    Tq, G = q.shape[1], q.shape[2] // H
    if G > 1:
        q = jnp.moveaxis(q.reshape(B, Tq, H, G, D), 3, 1).reshape(
            B, G * Tq, H, D)
    Tk_orig = Tk
    blk = min(block_size, Tk)
    if Tk % blk != 0:  # pad keys to a block multiple; padded keys masked off
        pad = blk - Tk % blk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        km = key_mask if key_mask is not None else jnp.ones((B, Tk), q.dtype)
        key_mask = jnp.pad(km, ((0, 0), (0, pad)))
        Tk = Tk + pad
    n_blocks = Tk // blk
    # (n_blocks, B, blk, H, D) for scan
    ks = jnp.moveaxis(k.reshape(B, n_blocks, blk, H, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, n_blocks, blk, H, D), 1, 0)
    if key_mask is not None:
        ms = jnp.moveaxis(key_mask.reshape(B, n_blocks, blk), 1, 0)
    else:
        ms = jnp.ones((n_blocks, B, blk), q.dtype)
    iq = jnp.arange(Tq) if G == 1 else jnp.tile(jnp.arange(Tq), G)
    # Tq != Tk: align queries to the END of the keys (decode-style), matching
    # full_attention's `ik <= iq + (Tk - Tq)` — offset uses the UNPADDED Tk
    causal_off = Tk_orig - Tq

    def body(carry, xs):
        k_blk, v_blk, m_blk, blk_idx = xs
        bias = mask_bias(m_blk)
        if causal:
            ik = blk_idx * blk + jnp.arange(blk)
            cb = jnp.where(ik[None, :] <= iq[:, None] + causal_off, 0.0, NEG_INF)
            if window is not None:
                cb = jnp.where(
                    ik[None, :] > iq[:, None] + causal_off - window, cb,
                    NEG_INF)
            bias = bias + cb[None, None, :, :]
        carry = attention_block_accum(carry, q, k_blk, v_blk, bias)
        return carry, None

    init = _accum_init(q)
    (o, l, _), _ = lax.scan(body, init,
                            (ks, vs, ms, jnp.arange(n_blocks)))
    out = attention_finalize(o, l)
    if G > 1:
        out = jnp.moveaxis(out.reshape(B, G, Tq, H, D), 1, 3).reshape(
            B, Tq, H * G, D)
    return out


def cached_attention_step(q: jnp.ndarray, k_cache: jnp.ndarray,
                          v_cache: jnp.ndarray, pos) -> jnp.ndarray:
    """One autoregressive decode step against decode-layout KV caches.

    `q`: (B, H, D) — this step's query heads for every sequence (or slot).
    `k_cache`: (B, Hkv, D, L) and `v_cache`: (B, Hkv, L, D) — the TPU
    decode layouts (r4): the score einsum contracts D with L on the minor
    (lane) axis and the weighted sum contracts L with D minor, so each
    step streams the cache without a strided transpose. `pos`: position of
    the token being consumed — a scalar (whole-batch decode: every row at
    the same position) or a (B,) vector (slotted decode: every slot at its
    own position); cache entries past a row's `pos` are masked off, which
    is what makes one compiled step correct for slots holding sequences of
    different lengths (inactive/garbage tail entries are never attended).

    GQA: `H` may be a multiple of `Hkv`; query heads are grouped by the
    KV head they share and the einsums batch over Hkv against the
    UN-repeated caches — each cache byte (the decode bandwidth bound) is
    read once and serves H/Hkv query heads.

    Returns (B, H*D), ready for the output projection.
    """
    B, Hkv, D, L = k_cache.shape
    H = q.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bkgd,bkdl->bkgl", qg,
                   k_cache) / jnp.sqrt(jnp.asarray(D, q.dtype))
    pos = jnp.asarray(pos)
    limit = pos[:, None, None, None] if pos.ndim else pos
    s = jnp.where(jnp.arange(L)[None, None, None, :] <= limit, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("bkgl,bkld->bkgd", w, v_cache)
    return att.reshape(B, H * D)


def paged_gather(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                 page_table: jnp.ndarray):
    """Materialize per-slot dense decode-layout caches from a paged pool.

    `k_pool`: (P, Hkv, D, page) and `v_pool`: (P, Hkv, page, D) — the
    decode layouts of `cached_attention_step` with the length axis cut
    into fixed-size pages; page 0 is the reserved trash page (never
    allocated, absorbs masked writes). `page_table`: (S, n_pages) int32
    mapping each slot's logical page index to a pool page id
    (unallocated entries point at page 0). Returns (k, v) in the dense
    layouts (S, Hkv, D, n_pages*page) / (S, Hkv, n_pages*page, D): the
    gather is ordered by logical page index, so logical position
    `p` lands at index `p` exactly as in the contiguous cache — downstream
    attention numerics are the DENSE step's numerics, which is what
    keeps paged decode argmax-identical to `generate`. Garbage in
    unwritten/trash regions is masked by position downstream (and is
    always finite — pages only ever hold zeros or real KV — so masked
    `0 * garbage` terms stay exact zeros)."""
    P, Hkv, D, page = k_pool.shape
    S, n_pages = page_table.shape
    k = jnp.take(k_pool, page_table, axis=0)     # (S, n_pages, Hkv, D, page)
    k = jnp.transpose(k, (0, 2, 3, 1, 4)).reshape(S, Hkv, D, n_pages * page)
    v = jnp.take(v_pool, page_table, axis=0)     # (S, n_pages, Hkv, page, D)
    v = jnp.transpose(v, (0, 2, 1, 3, 4)).reshape(S, Hkv, n_pages * page, D)
    return k, v


def paged_gather_quant(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                       k_scale: jnp.ndarray, v_scale: jnp.ndarray,
                       page_table: jnp.ndarray, dtype=jnp.float32):
    """`paged_gather` over INT8 pools: gather the int8 pages and their
    per-(head, position) f32 scale pages (`k_scale`/`v_scale`:
    (P, Hkv, page), riding the same page table), dequantize, and return
    dense `dtype` caches in the decode layouts. This is the int8 tier's
    CPU/tier-1/kill-switch numerics ORACLE: the Pallas int8 kernel's
    page-loop dequant is parity-pinned against exactly this path
    (tests/test_pallas_paged_attention.py and the dispatch probe), the
    same role `paged_gather` plays for the full-precision kernel.
    Trash-page semantics hold for free: int8 zeros dequantize to exact
    0.0 under any scale, so unwritten regions stay finite and are
    masked by position downstream."""
    P, Hkv, D, page = k_pool.shape
    S, n_pages = page_table.shape
    L = n_pages * page
    k = jnp.take(k_pool, page_table, axis=0)   # (S, n_pages, Hkv, D, page)
    ks = jnp.take(k_scale, page_table, axis=0)  # (S, n_pages, Hkv, page)
    k = k.astype(jnp.float32) * ks[:, :, :, None, :]
    k = jnp.transpose(k, (0, 2, 3, 1, 4)).reshape(S, Hkv, D, L)
    v = jnp.take(v_pool, page_table, axis=0)   # (S, n_pages, Hkv, page, D)
    vs = jnp.take(v_scale, page_table, axis=0)
    v = v.astype(jnp.float32) * vs[..., None]
    v = jnp.transpose(v, (0, 2, 1, 3, 4)).reshape(S, Hkv, L, D)
    return k.astype(dtype), v.astype(dtype)


def paged_attention_step(q: jnp.ndarray, k_pool: jnp.ndarray,
                         v_pool: jnp.ndarray, page_table: jnp.ndarray,
                         pos) -> jnp.ndarray:
    """One decode step against a PAGED KV pool: gather each slot's pages
    into the dense decode layout, then run `cached_attention_step`
    unchanged — paged storage, dense numerics. The persistent allocation
    is the pool (pages actually held per request), not
    slots × max-length; the gathered dense view is a transient of the
    step. This XLA form is the portable reference semantics AND the
    dispatch fallback: `paged_attention_step_auto` runs the fused Pallas
    kernel that walks the page table in-place (vLLM's PagedAttention,
    `ops/pallas_paged_attention.py`) when the platform supports it.

    Head-count contract: Hkv here is whatever the POOLS carry — under
    tensor-parallel serving (`serving.tp_engine`) this runs per shard
    inside `shard_map` with the LOCAL head count Hkv/tp (pools are
    sharded on the head axis), and neither this step nor the kernel can
    tell: heads never mix in attention, so the per-shard computation is
    the single-device one at a smaller Hkv."""
    k, v = paged_gather(k_pool, v_pool, page_table)
    return cached_attention_step(q, k, v, pos)


def ring_key_positions(last, n_entries: int, page: int):
    """The position each column of a slot's gathered ring holds: `last`
    (S,) the newest position written; entry `e` of the ring holds the
    newest logical page `j <= last // page` with `j % n_entries == e`
    (negative: never written). (S, n_entries * page)."""
    jlast = (jnp.asarray(last) // page)[:, None]
    j = jlast - (jlast - jnp.arange(n_entries)[None, :]) % n_entries
    return (j[:, :, None] * page + jnp.arange(page)).reshape(
        j.shape[0], n_entries * page)


def ring_attention_chunk(q, k_pool, v_pool, ring_table, pos0,
                         window: int) -> jnp.ndarray:
    """The portable form of windowed paged attention, and the kernel's
    oracle: `q` (S, C, H, D), C contiguous queries a slot from `pos0[s]`
    on, each attending to the `window` positions that end at its own,
    against pools whose pages a slot holds as a RING, `ring_table` (S,
    R): logical page `j` at entry `j % R`, the chunk's own K/V already
    written. Gathers each slot's ring and masks by the position every
    column holds. Returns (S, C, H*D)."""
    S, C, H, D = q.shape
    Hkv, page = k_pool.shape[1], k_pool.shape[3]
    G = H // Hkv
    kd, vd = paged_gather(k_pool, v_pool, ring_table)
    qpos = jnp.asarray(pos0)[:, None] + jnp.arange(C)[None, :]
    kpos = ring_key_positions(qpos[:, -1], ring_table.shape[1], page)
    qg = jnp.transpose(q.reshape(S, C, Hkv, G, D), (0, 2, 3, 1, 4))
    s = jnp.einsum("skgcd,skdl->skgcl", qg,
                   kd) / jnp.sqrt(jnp.asarray(D, q.dtype))
    kp, qp = kpos[:, None, None, None, :], qpos[:, None, None, :, None]
    s = jnp.where((kp <= qp) & (kp > qp - window) & (kp >= 0), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("skgcl,skld->skgcd", w, vd)
    return jnp.transpose(att, (0, 3, 1, 2, 4)).reshape(S, C, H * D)


def paged_attention_step_auto(q: jnp.ndarray, k_pool: jnp.ndarray,
                              v_pool: jnp.ndarray,
                              page_table: jnp.ndarray, pos,
                              active=None, k_scale=None,
                              v_scale=None, window=None) -> jnp.ndarray:
    """`paged_attention_step` behind the kernel-dispatch contract: on
    TPU the Pallas paged-attention kernel walks the page table in place
    (`ops/pallas_paged_attention.py` — no dense transient, each cache
    byte read once); everywhere else (CPU tier-1, kill switch, failed
    probe) the `paged_gather` + `cached_attention_step` reference path
    runs unchanged. `q`: (S, H, D); `pos`: (S,) per-slot positions.
    Inactive lanes (optional `active` (S,) bool) are a compute skip on
    the kernel path (exact-zero rows) and plain masked-downstream
    garbage on the gather path — both discarded by the engine.
    int8 pools pass their f32 scale pools as `k_scale`/`v_scale`
    ((P+1, Hkv, page)): the kernel dequantizes inside the page loop,
    the fallback dequantizes via `paged_gather_quant` — same dispatch
    contract, halved DMA bytes. `window` W: each query sees the W
    positions that end at its own, and `page_table` is the slots' RING
    table (`ring_attention_chunk`). Returns (S, H*D)."""
    from deeplearning4j_tpu.ops.pallas_paged_attention import (
        paged_attention_or_none,
    )

    S, H, D = q.shape
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (S,))
    if window is not None:
        return paged_attention_chunk_auto(
            q[:, None], k_pool, v_pool, page_table, pos, active,
            window=window)[:, 0]
    out = paged_attention_or_none(q[:, None], k_pool, v_pool, page_table,
                                  pos, active, k_scale=k_scale,
                                  v_scale=v_scale)
    if out is not None:
        return out.reshape(S, H * D)
    if k_scale is not None:
        kd, vd = paged_gather_quant(k_pool, v_pool, k_scale, v_scale,
                                    page_table, q.dtype)
        return cached_attention_step(q, kd, vd, pos)
    return paged_attention_step(q, k_pool, v_pool, page_table, pos)


def paged_attention_chunk_auto(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray,
                               page_table: jnp.ndarray, pos0,
                               active=None, k_scale=None,
                               v_scale=None, window=None) -> jnp.ndarray:
    """Chunk-width paged attention behind the same dispatch contract —
    the speculative (k+1)-verify and chunked-prefill-suffix shapes.
    `q`: (S, C, H, D) — C CONTIGUOUS query tokens per slot starting at
    absolute position `pos0[s]` (row c attends to cache entries
    `<= pos0[s] + c`, the `cached_attention_chunk` mask). Kernel path:
    one fused page-walk dispatch; fallback: `paged_gather` + slot-vmapped
    `cached_attention_chunk` (exactly `_verify_block_attention`, and for
    S=1 exactly `_prefill_chunk_block_attention`). int8 pools pass
    `k_scale`/`v_scale` exactly as in `paged_attention_step_auto`, and
    `window` with the ring table as there (no int8 form).
    Returns (S, C, H*D)."""
    from deeplearning4j_tpu.ops.pallas_paged_attention import (
        paged_attention_or_none,
    )

    S, C, H, D = q.shape
    pos0 = jnp.asarray(pos0)
    if pos0.ndim == 0:
        pos0 = jnp.broadcast_to(pos0, (S,))
    if window is not None:
        if k_scale is not None:
            raise NotImplementedError(
                "windowed paged attention has no int8 form")
        out = paged_attention_or_none(q, k_pool, v_pool, page_table, pos0,
                                      active, window=window)
        if out is not None:
            return out.reshape(S, C, H * D)
        return ring_attention_chunk(q, k_pool, v_pool, page_table, pos0,
                                    window)
    out = paged_attention_or_none(q, k_pool, v_pool, page_table, pos0,
                                  active, k_scale=k_scale,
                                  v_scale=v_scale)
    if out is not None:
        return out.reshape(S, C, H * D)
    if k_scale is not None:
        kd, vd = paged_gather_quant(k_pool, v_pool, k_scale, v_scale,
                                    page_table, q.dtype)
    else:
        kd, vd = paged_gather(k_pool, v_pool, page_table)
    qpos = pos0[:, None] + jnp.arange(C)[None, :]
    return jax.vmap(cached_attention_chunk)(q, kd, vd, qpos)


def cached_attention_chunk(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, q_pos) -> jnp.ndarray:
    """Chunked-prefill attention for ONE slot: a block of C queries
    against that slot's dense-layout cache.

    `q`: (C, H, D) — the prompt chunk's query heads, at absolute
    positions `q_pos` (C,). `k_cache`: (Hkv, D, L), `v_cache`:
    (Hkv, L, D) — the slot's cache (typically `paged_gather` output for
    one slot) which already contains this chunk's own K/V, so masking
    each query to cache entries `<= q_pos` yields exactly causal
    attention over [prompt-so-far ‖ this chunk]. GQA contracts against
    the un-repeated Hkv caches, like `cached_attention_step`.

    Returns (C, H*D), ready for the output projection."""
    Hkv, D, L = k_cache.shape
    C, H = q.shape[0], q.shape[1]
    G = H // Hkv
    qg = jnp.transpose(q.reshape(C, Hkv, G, D), (1, 2, 0, 3))  # (Hkv,G,C,D)
    s = jnp.einsum("kgcd,kdl->kgcl", qg,
                   k_cache) / jnp.sqrt(jnp.asarray(D, q.dtype))
    limit = jnp.asarray(q_pos)[None, None, :, None]
    s = jnp.where(jnp.arange(L)[None, None, None, :] <= limit, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("kgcl,kld->kgcd", w, v_cache)    # (Hkv, G, C, D)
    return jnp.transpose(att, (2, 0, 1, 3)).reshape(C, H * D)


_SEQ_PARALLEL: list = []  # (mesh, seq_axis, batch_axis) stack


@contextmanager
def sequence_parallel_scope(mesh, axis_name: str = "seq",
                            batch_axis: Optional[str] = None):
    """Within this scope, `multi_head_attention` (and therefore every
    attention layer traced under it) computes via ring attention with the
    time axis sharded over `axis_name` — how a SelfAttention/Transformer
    model trains with sequences longer than one chip holds. Trace-time
    static: enter the scope around the jit/trace of the step."""
    _SEQ_PARALLEL.append((mesh, axis_name, batch_axis))
    try:
        yield
    finally:
        _SEQ_PARALLEL.pop()


def grouped_causal_attention(q, k, v, *, window: Optional[int] = None,
                             one_array_to: Optional[int] = None
                             ) -> jnp.ndarray:
    """Causal self-attention, forward only, K/V read by group and never
    repeated: q (B, T, H, D), k and v (B, T, Hkv, D), every query seeing
    the `window` keys that end at its own (None: all before it). Up to
    `one_array_to` keys (None: any number) the scores are one array
    (`full_attention_grouped`). Past it they would not fit (128 heads at
    4,096 keys: 8.6 GB): the flash kernel where it serves (key blocks
    behind the window skipped: `pallas_attention.flash_attention_or_none`),
    `blockwise_attention` elsewhere, the CPU's tests among them."""
    if one_array_to is None or k.shape[1] <= one_array_to:
        return full_attention_grouped(q, k, v, causal=True, window=window)
    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_attention_or_none,
    )

    out = flash_attention_or_none(q, k, v, causal=True, window=window)
    if out is None:
        out = blockwise_attention(q, k, v, causal=True, window=window,
                                  block_size=256)
    return out


def multi_head_attention(q, k, v, *, causal=False, key_mask=None,
                         block_size: Optional[int] = None,
                         window: Optional[int] = None):
    """Dispatch (the cuDNN-helper pattern: same contract, fastest available
    path picked): ring attention when a sequence-parallel scope is active,
    pallas flash kernel for long unmasked sequences, XLA blockwise beyond
    `block_size`, full attention otherwise.

    GQA: `k`/`v` may carry fewer heads than `q` (Hkv dividing H). The
    full-attention path computes the grouping as a broadcast einsum
    (`full_attention_grouped` — no materialized repeat); the kernel
    paths (ring/flash/blockwise) require equal head counts and widen
    via `jnp.repeat`, exactly the layers' historical behavior.

    `window` (causal self-attention without a key mask only): a query
    sees the `window` keys that end at its own
    (`grouped_causal_attention`, forward only past `block_size` keys)."""
    H, Hkv = q.shape[2], k.shape[2]
    if window is not None:
        if not causal or key_mask is not None or _SEQ_PARALLEL \
                or q.shape[1] != k.shape[1]:
            raise NotImplementedError(
                "a window is written for causal self-attention without a "
                "key mask, outside a sequence-parallel scope")
        return grouped_causal_attention(q, k, v, window=window,
                                        one_array_to=block_size)

    def widened():
        if Hkv == H:
            return k, v
        g = H // Hkv
        return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)

    if _SEQ_PARALLEL:
        from deeplearning4j_tpu.parallel.sequence import ring_attention

        kf, vf = widened()
        mesh, axis_name, batch_axis = _SEQ_PARALLEL[-1]
        return ring_attention(q, kf, vf, mesh, axis_name=axis_name,
                              causal=causal, key_mask=key_mask,
                              batch_axis=batch_axis)
    long_seq = block_size is not None and k.shape[1] > block_size
    if long_seq and key_mask is None:
        from deeplearning4j_tpu.ops.pallas_attention import flash_attention_or_none

        kf, vf = widened()
        out = flash_attention_or_none(q, kf, vf, causal=causal)
        if out is not None:
            return out
    if long_seq:
        kf, vf = widened()
        return blockwise_attention(q, kf, vf, causal=causal,
                                   key_mask=key_mask,
                                   block_size=block_size)
    bias = None if key_mask is None else mask_bias(key_mask)
    if Hkv != H:
        return full_attention_grouped(q, k, v, bias=bias, causal=causal)
    return full_attention(q, k, v, bias=bias, causal=causal)
