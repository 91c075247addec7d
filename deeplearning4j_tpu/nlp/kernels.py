"""Jitted embedding-training kernels — the TPU replacement for the
reference's native `AggregateSkipGram` / `AggregateCBOW` ops
(`models/embeddings/learning/impl/elements/SkipGram.java:258`,
`CBOW.java`; C++ in external libnd4j).

Where the reference updates one word pair per native call inside Java
producer threads, each function here consumes a BATCH of pairs as dense
int32 arrays and applies all updates with XLA scatter-adds in one compiled
computation (buffers donated, params stay in HBM). Negative sampling and
hierarchical softmax share the same kernel shape: a (B, K) target matrix
with per-target binary labels and a validity mask.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def require_partitionable_rng() -> None:
    """The documented mesh-vs-single-chip bit-parity of device-side
    negative sampling requires the partitionable threefry implementation
    (sharded draws == single-chip draws). Called when an NS kernel is
    built — not at import, which would clobber an explicit user setting
    process-wide just by importing the nlp package."""
    if not jax.config.jax_threefry_partitionable:
        import warnings

        warnings.warn(
            "jax_threefry_partitionable is disabled: sharded negative-"
            "sampling draws will differ from single-chip draws, so the "
            "mesh-vs-single-chip parity claim is void. Enable it via "
            "jax.config.update('jax_threefry_partitionable', True) if you "
            "need bit-parity. (Not flipped here: the flag is process-"
            "global and would change RNG streams for unrelated code.)",
            stacklevel=3)


_ROW_CLIP = 1.0  # max L2 norm of one row's aggregated per-batch update


def _scatter_clipped(table, idx, upd):
    """table[idx] += upd with the AGGREGATE per-row update clipped to
    `_ROW_CLIP`. A batch may hit one row hundreds of times (tiny vocabs,
    stop words); plain summed scatter then applies an effective lr of
    lr×count, which diverges. Clipping the aggregate keeps faithful
    minibatch-SGD semantics in the normal regime (update norms ≪ 1) while
    bounding the pathological one.

    Two regimes, chosen by shape at trace time:
    - table-shaped accumulator (scatter into zeros, clip per-row, add):
      three streaming full-table passes, no sort — measured 1.35-2.8×
      faster than the sort path at the bench shapes (B·K within ~8× of V)
      because it avoids a TPU bitonic sort over B·K keys per call;
    - argsort + compact segment-sum (batch-bounded): for vocabularies much
      larger than the batch (e.g. V=1M, B·K=100k) the accumulator variant
      would stream a table-sized temp per call, so the sort path wins
      despite the sort."""
    n_upd = int(np.prod(idx.shape))
    if table.shape[0] <= 8 * n_upd:
        agg = jnp.zeros_like(table).at[idx.reshape(-1)].add(
            upd.reshape(-1, upd.shape[-1]))
        norms = jnp.linalg.norm(agg, axis=-1, keepdims=True)
        scale = jnp.minimum(1.0, _ROW_CLIP / jnp.maximum(norms, 1e-12))
        return table + agg * scale
    flat_idx = idx.reshape(-1)
    flat_upd = upd.reshape(-1, upd.shape[-1])
    order = jnp.argsort(flat_idx)
    si = flat_idx[order]
    su = flat_upd[order]
    first = jnp.concatenate([jnp.ones((1,), bool), si[1:] != si[:-1]])
    ranks = jnp.cumsum(first) - 1                      # compact segment ids
    agg = jnp.zeros_like(su).at[ranks].add(su)         # (B·K, D) compact
    norms = jnp.linalg.norm(agg, axis=-1, keepdims=True)
    scale = jnp.minimum(1.0, _ROW_CLIP / jnp.maximum(norms, 1e-12))
    contrib = agg[ranks] * scale[ranks] * first[:, None]
    return table.at[si].add(contrib)


def _pair_update(syn0, syn1, center, targets, labels, mask, lr):
    """Shared skip-gram/HS update math (see skipgram_step docstring)."""
    v = syn0[center]                                   # (B, D)
    u = syn1[targets]                                  # (B, K, D)
    logits = jnp.einsum("bd,bkd->bk", v, u)
    p = jax.nn.sigmoid(logits)
    g = (labels - p) * mask * lr                       # (B, K)
    dv = jnp.einsum("bk,bkd->bd", g, u)                # (B, D)
    du = g[..., None] * v[:, None, :]                  # (B, K, D)
    syn0 = _scatter_clipped(syn0, center, dv)
    syn1 = _scatter_clipped(syn1, targets, du)
    ll = jnp.where(labels > 0, jax.nn.log_sigmoid(logits),
                   jax.nn.log_sigmoid(-logits))
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return syn0, syn1, loss


@partial(jax.jit, donate_argnums=(0, 1))
def skipgram_step(syn0, syn1, center, targets, labels, mask, lr):
    """One batched skip-gram update (negative sampling OR hierarchical
    softmax — the label/target semantics differ, the math is identical).

    syn0: (V, D) input vectors; syn1: (V', D) output weights
    center (B,) int32; targets (B, K) int32 rows of syn1
    labels (B, K) float 1/0; mask (B, K) float validity
    """
    return _pair_update(syn0, syn1, center, targets, labels, mask, lr)


def _ns_batch(syn0, syn1, key, center, context, cdf, lr, nvalid, negative):
    """One NS batch with negatives drawn ON DEVICE: inverse-CDF over the
    0.75-power unigram table, `cdf` in uint32 FIXED POINT (host f64 cumsum
    scaled by 2^32) — f32 spacing near 1.0 (~6e-8) would collapse the tail
    probabilities of large vocabularies to zero, silently excluding rare
    words from the negative distribution; 2^-32 resolution does not."""
    key, sub = jax.random.split(key)
    B = center.shape[0]
    u = jax.random.bits(sub, (B, negative), jnp.uint32)
    negs = jnp.clip(jnp.searchsorted(cdf, u, side="right"), 0,
                    cdf.shape[0] - 1).astype(jnp.int32)
    targets = jnp.concatenate([context[:, None], negs], axis=1)
    one = jnp.ones((B, 1), jnp.float32)
    labels = jnp.concatenate(
        [one, jnp.zeros((B, negative), jnp.float32)], axis=1)
    mask = jnp.concatenate(
        [one, (negs != context[:, None]).astype(jnp.float32)], axis=1)
    mask = mask * (jnp.arange(B) < nvalid)[:, None]
    syn0, syn1, loss = _pair_update(syn0, syn1, center, targets, labels,
                                    mask, lr)
    return syn0, syn1, loss, key


@partial(jax.jit, donate_argnums=(0, 1, 6), static_argnums=(9,))
def skipgram_ns_scan(syn0, syn1, centers, contexts, cdf, key, loss_acc,
                     lrs, nvalids, negative):
    """K sequential NS batches in ONE dispatch via `lax.scan` — the
    device-side negative-sampling skip-gram kernel (replaces the
    reference's native `AggregateSkipGram` inner loop).

    Every device operation (transfer or step) carries a fixed host
    dispatch cost, so one dispatch per 1024-pair batch caps throughput
    regardless of how fast the scatter math is. Scanning K batches per
    dispatch amortizes that fixed cost K×:
    centers/contexts are (K, B) int32, lrs/nvalids are (K,) per-batch
    learning rates and valid-row counts (tail batches may be partial or
    empty — nvalid=0 rows are fully masked). `key` is the carried PRNG
    state (threefry; `jax_threefry_partitionable` makes draws identical
    under any sharding, preserving mesh vs single-chip parity); `loss_acc`
    is a carried (donated) running loss sum — folding accumulation into
    the step keeps the hot loop at exactly one dispatch per flush."""

    def body(carry, xs):
        syn0, syn1, key, acc = carry
        center, context, lr, nvalid = xs
        syn0, syn1, loss, key = _ns_batch(syn0, syn1, key, center, context,
                                          cdf, lr, nvalid, negative)
        return (syn0, syn1, key, acc + loss), None

    (syn0, syn1, key, loss_acc), _ = jax.lax.scan(
        body, (syn0, syn1, key, loss_acc), (centers, contexts, lrs, nvalids))
    return syn0, syn1, loss_acc, key


@partial(jax.jit, donate_argnums=(0, 1))
def cbow_step(syn0, syn1, context, cmask, targets, labels, tmask, lr):
    """One batched CBOW update: mean of context vectors predicts targets.

    context (B, W) int32 padded context windows; cmask (B, W) validity
    targets/labels/tmask as in skipgram_step
    """
    cm = cmask[..., None]
    cv = syn0[context] * cm                            # (B, W, D)
    denom = jnp.maximum(jnp.sum(cmask, axis=1, keepdims=True), 1.0)
    h = jnp.sum(cv, axis=1) / denom                    # (B, D)
    u = syn1[targets]
    logits = jnp.einsum("bd,bkd->bk", h, u)
    p = jax.nn.sigmoid(logits)
    g = (labels - p) * tmask * lr
    dh = jnp.einsum("bk,bkd->bd", g, u)                # (B, D)
    du = g[..., None] * h[:, None, :]
    # word2vec.c adds the FULL hidden error to every context word; the
    # exact mean-pool gradient is 1/|ctx| of that, which batches better
    dctx = jnp.broadcast_to(dh[:, None, :], cv.shape) * cm / denom[..., None]
    syn0 = _scatter_clipped(syn0, context, dctx)
    syn1 = _scatter_clipped(syn1, targets, du)
    ll = jnp.where(labels > 0, jax.nn.log_sigmoid(logits),
                   jax.nn.log_sigmoid(-logits))
    loss = -jnp.sum(ll * tmask) / jnp.maximum(jnp.sum(tmask), 1.0)
    return syn0, syn1, loss


@partial(jax.jit, donate_argnums=(0,))
def infer_step(vec, syn1, targets, labels, mask, lr):
    """ParagraphVectors inference: update ONLY the inferred doc vector
    against frozen output weights (reference
    `ParagraphVectors.inferVector`)."""
    u = syn1[targets]                                  # (B, K, D)
    logits = jnp.einsum("d,bkd->bk", vec, u)
    p = jax.nn.sigmoid(logits)
    g = (labels - p) * mask * lr
    dv = jnp.einsum("bk,bkd->d", g, u)
    ll = jnp.where(labels > 0, jax.nn.log_sigmoid(logits),
                   jax.nn.log_sigmoid(-logits))
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return vec + dv, loss


@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def glove_step(W, b, hW, hb, Wc, bc, hWc, hbc, rows, cols, logX, fX, lr):
    """One batched GloVe AdaGrad update (reference `models/glove/Glove.java`
    + external `nd4j` AdaGrad; co-occurrence factorization
    J = Σ f(X) (w_i·w̃_j + b_i + b̃_j − log X)²).

    W/b + history hW/hb: main vectors; Wc/bc + hWc/hbc: context vectors.
    rows/cols (B,) int32; logX/fX (B,) float.
    """
    wi, wj = W[rows], Wc[cols]
    diff = jnp.einsum("bd,bd->b", wi, wj) + b[rows] + bc[cols] - logX
    wdiff = fX * diff                                   # (B,)
    gWi = wdiff[:, None] * wj
    gWj = wdiff[:, None] * wi
    gb = wdiff

    hW = hW.at[rows].add(gWi ** 2)
    hWc = hWc.at[cols].add(gWj ** 2)
    hb = hb.at[rows].add(gb ** 2)
    hbc = hbc.at[cols].add(gb ** 2)
    eps = 1e-8
    W = W.at[rows].add(-lr * gWi / jnp.sqrt(hW[rows] + eps))
    Wc = Wc.at[cols].add(-lr * gWj / jnp.sqrt(hWc[cols] + eps))
    b = b.at[rows].add(-lr * gb / jnp.sqrt(hb[rows] + eps))
    bc = bc.at[cols].add(-lr * gb / jnp.sqrt(hbc[cols] + eps))
    loss = 0.5 * jnp.mean(fX * diff ** 2)
    return W, b, hW, hb, Wc, bc, hWc, hbc, loss
