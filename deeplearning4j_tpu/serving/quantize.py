"""Quantized inference tier: int8 paged KV cache + int8/bf16 weight
serving (ROADMAP item 2 / round-4 ask #4).

The serving decode path is bandwidth-bound on KV bytes — the r4 decode
profile and PR 9's paged-attention kernel both priced the cache stream
as the dominant cost. This module halves it again: K/V are quantized to
**symmetric per-head int8 at cache-write time** and dequantized at the
read site — inside the Pallas page loop on TPU (int8 pages DMA'd,
scales prefetched, dequant-in-VMEM before the matmul,
`ops/pallas_paged_attention.py`) and in the `paged_gather`-path int8
reference on CPU (`ops.attention.paged_gather_quant`), which is the
tier-1 / kill-switch numerics oracle.

**Scale layout.** Pools stay in the r4 decode layouts with int8
elements — K `(P+1, Hkv, hd, page)`, V `(P+1, Hkv, page, hd)` — plus
two small f32 scale pools `(P+1, Hkv, page)`: one scale per
(page, head, position). Per-position granularity (not per-page) is what
makes the page pools soundly *appendable*: the decode step writes one
position into a page that already holds earlier positions, and a
coarser per-page scale could only absorb the new abs-max by re-scaling
(rewriting) the old int8 entries or clipping against a stale bound.
One f32 scalar per (head, position) costs ``4/hd`` of the int8 payload
(~3% at hd=128) and rides the SAME page table / free list / refcounts
as the payload pools — PrefixCache sharing, speculative draft pools,
and trash-page masking (int8 zeros dequantize to exact 0.0) all work
unchanged.

**Weight quantization** (`quantize_net_weights`) follows the LLM.int8
per-output-channel recipe (Dettmers et al., 2022): symmetric int8 over
the contraction axis, stored dequantized-to-bf16 so every compiled
serving path (predict, prefill, decode) runs unmodified; ``"bf16"`` is
the plain cast. Embeddings, positional tables, biases and LayerNorm
parameters keep full precision — they are neither bandwidth-bound nor
outlier-tolerant.

**Drift gates** (`drift_report`): quantization is a *numerics change*,
so it ships through the canary ladder like any other candidate — an
argmax-drift gate (token-disagreement rate vs the f32 rollout on a
pinned eval set) and a perplexity-delta gate, enforced by
`ModelServer._validate_candidate` before a quantized candidate swaps
in, and rolled back for free by the PR-4/PR-7 reload machinery when
breached.

Kill switch: ``DL4J_TPU_NO_INT8_KV=1`` (checked by the engine at build
time AND by the kernel dispatch) forces full-precision pools.
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

KV_KILL_ENV = "DL4J_TPU_NO_INT8_KV"

#: block-parameter matmul weights eligible for weight quantization
#: (attention projections + FFN/SwiGLU); everything else — embedding,
#: positional table, biases, LayerNorm gains — keeps full precision
BLOCK_MATMUL_KEYS = ("Wqkv", "Wo", "W1", "W2", "W3")


def int8_kv_enabled() -> bool:
    """The int8-KV kill switch: ``DL4J_TPU_NO_INT8_KV=1`` makes the
    engine allocate full-precision pools (and the int8 kernel decline
    dispatch)."""
    return os.environ.get(KV_KILL_ENV, "") not in ("1", "true", "yes")


# -- int8 KV quantization (traced inside the engine's step closures) -------

def quantize_heads(x, axis: int = -1):
    """Symmetric per-head int8 quantization of one KV write span.

    Reduces abs-max over `axis` (the head_dim axis of the span — the
    last axis for the decode step's (S, Hkv, hd) single-position write,
    axis 2 / 3 for the prefill span's lane-last (1, Hkv, hd, W) /
    (1, Hkv, W, hd) layouts), yielding one f32 scale per (head,
    position). Returns ``(q, scale)`` with ``q`` int8 in [-127, 127]
    and ``scale = abs_max / 127`` (1.0 for all-zero spans, so dequant
    is exact 0.0 — the trash-page convention). Round-trip error is
    bounded by scale/2 per element (ULP-bound pinned in
    tests/test_quantize.py)."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(xf / jnp.expand_dims(scale, axis))
    q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_heads(q, scale, axis: int = -1, dtype=None):
    """Inverse of `quantize_heads`: broadcast the per-(head, position)
    scale back over `axis`. The reference read path
    (`ops.attention.paged_gather_quant`) inlines exactly this."""
    import jax.numpy as jnp

    out = q.astype(jnp.float32) * jnp.expand_dims(scale, axis)
    return out if dtype is None else out.astype(dtype)


def _write_scale_pages(sp, scol, wpids, woff, page):
    """Scatter one prefill span's per-position scales (1, Hkv, W) into
    the f32 scale pool (P+1, Hkv, page) — the exact write discipline of
    `decode_engine._write_pages` with the lane (position) axis last:
    floor(W/page) aligned full-page writes, then a partial tail at
    in-page offset `woff`. Module level so the speculative draft's
    prefill mirrors the same writes into its own scale pools."""
    import jax
    import jax.numpy as jnp

    W = scol.shape[2]
    z = jnp.zeros((), jnp.int32)
    nfull = W // page
    for j in range(nfull):
        sp = jax.lax.dynamic_update_slice(
            sp, scol[..., j * page:(j + 1) * page], (wpids[j], z, z))
    if W % page:
        sp = jax.lax.dynamic_update_slice(
            sp, scol[..., nfull * page:], (wpids[nfull], z, woff))
    return sp


def kv_bytes_per_token(kv_geometry: Sequence[Tuple[int, int]],
                       kv_quant: Optional[str],
                       cache_itemsize: int) -> int:
    """Resident KV bytes one generated token adds across all blocks —
    the number `stats()["kv_bytes_per_token"]` reports. int8 pools pay 1 byte/element plus the f32 scale sidecar
    (2 heads-worth of 4-byte scalars per position — ``8·Hkv`` vs the
    payload's ``2·Hkv·hd``, i.e. a 4/hd overhead); full-precision pools
    pay ``cache_itemsize`` per element. `kv_geometry` is
    `GPTPlan.kv_geometry()`: per-block (Hkv, hd) pairs."""
    total = 0
    for Hkv, hd in kv_geometry:
        if kv_quant == "int8":
            total += 2 * Hkv * hd + 2 * Hkv * 4
        else:
            total += 2 * Hkv * hd * cache_itemsize
    return total


# -- weight quantization ---------------------------------------------------

def quantize_weight_int8(w):
    """Per-output-channel symmetric int8 fake-quantization of one
    matmul weight, stored dequantized-to-bf16. The scale reduces over
    axis -2 — the contraction (input) dimension — so each output
    channel keeps its own dynamic range (the LLM.int8 layout; a single
    tensor-wide scale lets one outlier channel crush the rest). Works
    for 2-D (d_in, d_out) and any leading-batched layout."""
    import jax.numpy as jnp

    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127.0, 127.0)
    return (q * scale).astype(jnp.bfloat16)


def quantize_net_weights(net, mode: str):
    """Clone `net` with its transformer matmul weights quantized.

    ``mode="int8"``: per-output-channel symmetric int8
    (`quantize_weight_int8`), stored dequantized-to-bf16 — every
    compiled serving path runs unmodified on the quantized clone.
    ``mode="bf16"``: plain bf16 cast of the same weight set. Both
    quantize the block projections (`BLOCK_MATMUL_KEYS`) and the output
    head's ``W``; embeddings, positional tables, biases and LayerNorm
    parameters keep full precision. The original `net` is untouched —
    `ModelServer` keeps it (or the raw reload candidate) as the
    drift-gate reference and the rollback target."""
    if mode not in ("int8", "bf16"):
        raise ValueError(
            f'unknown weight quantization mode {mode!r} — expected '
            '"int8" or "bf16"')
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    plan = GPTPlan(net)
    cast = quantize_weight_int8 if mode == "int8" \
        else (lambda w: jnp.asarray(w, jnp.bfloat16))
    params = [dict(p) for p in net._params]
    for i in plan.block_is:
        for key in BLOCK_MATMUL_KEYS:
            w = params[i].get(key)
            if w is not None and getattr(w, "ndim", 0) >= 2:
                params[i][key] = cast(w)
    out_w = params[plan.out_i].get("W")
    if out_w is not None and getattr(out_w, "ndim", 0) >= 2:
        params[plan.out_i]["W"] = cast(out_w)
    clone = MultiLayerNetwork(net.conf, dtype=net.dtype,
                              compute_dtype=net.compute_dtype)
    clone.init()  # allocates layer state; params replaced wholesale
    clone._params = params
    clone._layer_state = net._layer_state
    if net.get_normalizer() is not None:
        clone.set_normalizer(net.get_normalizer())
    return clone


# -- drift gates -----------------------------------------------------------

def _log_softmax(out: np.ndarray) -> np.ndarray:
    m = out.max(axis=-1, keepdims=True)
    lse = m + np.log(np.exp(out - m).sum(axis=-1, keepdims=True))
    return out - lse


def argmax_drift_rate(ref_out: np.ndarray, cand_out: np.ndarray) -> float:
    """Token-disagreement rate between two models' outputs (B, T, V)
    over a pinned eval set: the fraction of positions whose greedy
    (argmax) token differs. THE serving-facing drift number — greedy
    decode emits exactly these argmaxes, so a 0.0 rate means the
    quantized model serves identical greedy tokens on the eval set."""
    ref = np.argmax(np.asarray(ref_out), axis=-1)
    cand = np.argmax(np.asarray(cand_out), axis=-1)
    return float(np.mean(ref != cand))


def perplexity(out: np.ndarray, ids: np.ndarray) -> float:
    """Next-token perplexity of `ids` (B, T) under model outputs `out`
    (B, T, V): position t's output scores token t+1. `out` is treated
    as unnormalized logits (log-softmax applied here); already-
    normalized log-probs pass through unchanged, so the DELTA between
    two models is well-defined either way."""
    out = np.asarray(out, np.float64)
    ids = np.asarray(ids)
    logp = _log_softmax(out[:, :-1, :])
    B, Tm1 = ids.shape[0], ids.shape[1] - 1
    nll = -logp[np.arange(B)[:, None], np.arange(Tm1)[None, :],
                ids[:, 1:]]
    return float(np.exp(nll.mean()))


def drift_report(ref_out: np.ndarray, cand_out: np.ndarray,
                 ids: np.ndarray) -> dict:
    """The drift-gate verdict numerics for one (reference, candidate)
    pair on the pinned eval set: argmax disagreement rate plus the
    perplexity delta (candidate - reference; positive = worse). These
    are the numbers `ModelServer._validate_candidate` compares against
    `drift_gate={"max_argmax_drift": ..., "max_ppl_delta": ...}` and
    surfaces through ``stats()["drift"]`` / the flight recorder."""
    rate = argmax_drift_rate(ref_out, cand_out)
    ppl_ref = perplexity(ref_out, ids)
    ppl_cand = perplexity(cand_out, ids)
    return {"argmax_drift": round(rate, 6),
            "ppl_ref": round(ppl_ref, 6),
            "ppl_cand": round(ppl_cand, 6),
            "ppl_delta": round(ppl_cand - ppl_ref, 6)}
