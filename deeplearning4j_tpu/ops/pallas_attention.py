"""Pallas TPU flash-attention: forward AND backward (custom VJP).

The role of `deeplearning4j-cuda`'s helpers in the reference (SURVEY §2.3):
a hand-written accelerator kernel behind the same contract as the built-in
path, picked when available, falling through (with the reason recorded
in `kernel_dispatch.kernel_verdicts()`) otherwise
(`ConvolutionLayer.initializeHelper`, `ConvolutionLayer.java:69-79`). Here
the built-in paths are `ops/attention.py` full/blockwise attention (XLA);
this module is the Mosaic/Pallas fast path for the no-mask case — and since
it carries a custom VJP (a Pallas backward), it serves TRAINING too, the
analogue of the cuDNN backward helpers gradient-checked in
`CuDNNGradientChecks.java`. What the chip reads for it is in `PERF.md`
(§5's train cell, `flash_attention_roofline`; §6, PR 43) and comes from
`tools/flash_attention_bench.py`, which times each kernel alone. Block
sizes beyond 1024 are exhausted as a lever: with the scoped-VMEM ceiling
raised to admit them, (bq, bk) in {2048x1024, 1024x2048, 2048x2048,
4096x2048} all timed within 0.3% of 1024x1024 at B=8, H=8, T=4096, D=128
(before this round's records began), so the ladder keeps 1024 as its top
candidate and the raised limit exists to stop spurious probe declines at
wider head dims, not for speed.

Kernel shape (fwd): grid (B·H, Tq/block_q, Tk/block_k), innermost KV
dimension sequential so the online-softmax accumulator lives in VMEM
scratch across KV steps (m/l/acc — the flash recurrence); the TRAINING
forward also writes the row logsumexp L = m + log l for the backward (the
inference primal skips it). Q·Kᵀ and P·V hit the MXU; HBM sees each K/V
tile exactly once.

Backward recomputes P = exp(S - L) tile by tile (no O(T²) residual):
  D  = rowsum(dO ∘ O)
  dV = Pᵀ dO          dP = dO Vᵀ       dS = P ∘ (dP - D)
  dQ = dS K · scale   dK = dSᵀ Q · scale
in ONE kernel (`_flash_bwd_dkv_kernel` with dQ): S, P, dP and dS of a tile
pair are computed once, five products, one `exp`, one mask. The grid is
(B·H, Tk/block_k, Tq/block_q) with the query blocks innermost, so dK and dV
of a key block accumulate in (block_k, D) scratch; dQ's rows are revisited
once a key block, so it accumulates in float32 for the whole sequence of
the (batch, head), (Tq, D) of VMEM, and is written out once. A sequence too
long for that (`_backward_form`, read off the shape) takes the standard
split: a dQ kernel on the forward's grid and a dK/dV kernel, each computing
S and dP for itself, seven products a pair. `kernel_verdicts()` names the
form a process engaged (the key's last entry).

Dtype policy: bf16 inputs feed the MXU natively; f32 multiplies at HIGHEST
precision (measured ~100x more accurate gradients than the XLA
default-precision reference); f64 (interpret-mode gradient checks) keeps
the whole pipeline f64 so eps-scale central differences stay meaningful.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


NEG_INF = -1e30


# shared kernel-dispatch policy helpers (kept under the historical private
# names — this module's kernels use them pervasively)
from deeplearning4j_tpu.ops.kernel_dispatch import (  # noqa: E402
    vmem_limit_bytes as _vmem_limit,
    dot as _dot,
    mxu_dtype as _mxu_dtype,
    platform_supported as _kernels_dispatch,
    probe_verdict as _probe_verdict,
    record_decline as _record_decline,
    stat_dtype as _stat_dtype,
    traced_mesh as _traced_mesh,
)

FAMILY = "flash_attention"  # this module's row in kernel_verdicts()


def _masked_scores(q_ref, k_ref, qi, ki, *, sm_scale, causal, block_q,
                   block_k, window=None):
    """One (block_q, block_k) tile of scaled scores with the causal mask
    applied — the SINGLE implementation shared by the forward and every
    backward kernel, so mask/scale semantics cannot drift between them."""
    dt = _mxu_dtype(q_ref.dtype)
    q = q_ref[0].astype(dt)
    k = k_ref[0].astype(dt)
    s = _dot(q, k, ((1,), (1,)), dt) * sm_scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        if window is not None:   # the `window` keys that end at the query
            s = jnp.where(k_pos > q_pos - window, s, NEG_INF)
    return s, dt


def _tile_p(s, lse):
    """P = exp(S - L) with fully-masked entries zeroed (matches the
    forward's l == 0 finalisation)."""
    p = jnp.exp(s - lse)
    return jnp.where(s <= NEG_INF / 2, 0.0, p)


def _causal_needed_kv(qi, ki, block_q, block_k, causal, window=None):
    # KV blocks strictly above the diagonal contribute nothing
    need = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    if window is not None:
        # nor do those wholly behind the window of the block's first query
        need = need & (ki * block_k + block_k - 1 > qi * block_q - window)
    return need


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float,
                      causal: bool, block_q: int, block_k: int,
                      with_lse: bool, window=None):
    from jax.experimental import pallas as pl

    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_causal_needed_kv(qi, ki, block_q, block_k, causal, window))
    def _step():
        s, dt = _masked_scores(q_ref, k_ref, qi, ki, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, window=window)
        m_prev = m_scr[:, :1]                                 # (bq, 1)
        l_prev = l_scr[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        # rows fully masked so far sit at m ~ NEG_INF: zero their weights so
        # l stays 0 (finalize maps them to output 0, matching
        # attention.attention_finalize)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + _dot(p.astype(dt),
                                              v_ref[0].astype(dt),
                                              ((1,), (0,)), dt)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        o = jnp.where(l > 0, acc_scr[:] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = o.astype(o_ref.dtype)
        if with_lse:
            # row logsumexp (scaled-score space) for the backward's
            # tile-by-tile P recomputation; fully-masked rows get NEG_INF
            m = m_scr[:, :1]
            lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)),
                            NEG_INF)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                         dq_ref, dq_scr, *, sm_scale: float, causal: bool,
                         block_q: int, block_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_causal_needed_kv(qi, ki, block_q, block_k, causal))
    def _step():
        s, dt = _masked_scores(q_ref, k_ref, qi, ki, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k)
        p = _tile_p(s, lse_ref[0][:, :1])
        do = do_ref[0].astype(dt)
        dp = _dot(do, v_ref[0].astype(dt), ((1,), (1,)), dt)  # (bq, bk)
        ds = p * (dp - dsum_ref[0][:, :1])
        dq_scr[:] += _dot(ds.astype(dt), k_ref[0].astype(dt),
                          ((1,), (0,)), dt) * sm_scale

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                          *rest, sm_scale: float, causal: bool, block_q: int,
                          block_k: int, with_dq: bool):
    """dK and dV of a key block, the query blocks innermost (`dk_scr` /
    `dv_scr` accumulate over them). `with_dq`: the WHOLE backward of a
    (batch, head), dQ from the same S, P, dP and dS. Its rows come round
    again once a key block, so it accumulates in `dq_acc`, float32 for the
    whole sequence, and the `(1, Tq, D)` output block, whose index is
    constant over both inner axes, is written once, at the (batch, head)'s
    last step."""
    from jax.experimental import pallas as pl

    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest

    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if with_dq:
        @pl.when((kj == 0) & (qi == 0))
        def _init_dq():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_causal_needed_kv(qi, kj, block_q, block_k, causal))
    def _step():
        s, dt = _masked_scores(q_ref, k_ref, qi, kj, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k)
        p = _tile_p(s, lse_ref[0][:, :1])
        do = do_ref[0].astype(dt)
        dv_scr[:] += _dot(p.astype(dt), do, ((0,), (0,)), dt)   # (bk, D)
        dp = _dot(do, v_ref[0].astype(dt), ((1,), (1,)), dt)    # (bq, bk)
        ds = (p * (dp - dsum_ref[0][:, :1])).astype(dt)
        dk_scr[:] += _dot(ds, q_ref[0].astype(dt),
                          ((0,), (0,)), dt) * sm_scale          # (bk, D)
        if with_dq:
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_acc[rows, :] += _dot(ds, k_ref[0].astype(dt),
                                    ((1,), (0,)), dt) * sm_scale  # (bq, D)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if with_dq:
        @pl.when((kj == nk - 1) & (qi == nq - 1))
        def _finalize_dq():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _to_slabs(x):
    """(B, T, H, D) -> (B*H, T, D)."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_slabs(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   with_lse, window=None):
    """`k` and `v` may carry fewer heads than `q` (Hkv dividing H): a
    query head's slab then reads its GROUP's K/V slab, nothing
    repeated. `window` (with `causal`): `_flash_fwd_kernel`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    groups = H // k.shape[2]
    qf, kf, vf = _to_slabs(q), _to_slabs(k), _to_slabs(v)
    kernel = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, with_lse=with_lse,
                               window=window)
    sdt = _stat_dtype(q.dtype)
    out_specs = [pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype)]
    if with_lse:
        # stats stored broadcast along the 128-lane axis: the natural TPU
        # tile; row-vector (1, block_q) layouts are fragile under Mosaic
        out_specs.append(pl.BlockSpec((1, block_q, 128),
                                      lambda b, i, j: (b, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B * H, Tq, 128), sdt))

    # NOTE: clamping the KV index map for skipped causal blocks (so they
    # issue no DMA) was measured SLOWER on v5e — the skipped steps leave no
    # compute to hide the next real tile's DMA behind. Plain indexing + the
    # kernel-side compute skip wins.
    def kv_index(b, i, j):
        # query slab b = batch * H + head reads K/V slab batch * Hkv +
        # head // groups: b // groups (b itself at equal head counts)
        return (b if groups == 1 else b // groups, j, 0)

    res = pl.pallas_call(
        kernel,
        grid=(B * H, Tq // block_q, Tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), sdt),  # running max m
            pltpu.VMEM((block_q, 128), sdt),  # running denom l
            pltpu.VMEM((block_q, D), sdt),    # unnormalised output
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(qf, kf, vf)
    if with_lse:
        out, lse = res
        return _from_slabs(out, B, H), lse
    return _from_slabs(res, B, H), None


# The fused backward keeps dQ of one (batch, head)'s WHOLE sequence in VMEM:
# the float32 accumulator and the two buffers of its output block. It serves
# while those are within this share of the ceiling (a half: bf16 sequences
# up to 57,344 at head size 128, float32 ones to 38k; the tiles and their
# score slabs need the rest), and the split serves what lies beyond.
_DQ_RESIDENT_SHARE = 2


def _backward_form(Tq: int, D: int, dtype) -> str:
    """`"fused"` (one kernel) or `"split"` (a dQ and a dK/dV kernel), read
    off the shape alone: whether what the fused form holds for the whole
    sequence fits its share of `vmem_limit_bytes()`."""
    per_element = (jnp.dtype(_stat_dtype(dtype)).itemsize
                   + 2 * jnp.dtype(dtype).itemsize)
    resident = Tq * D * per_element
    return ("fused" if resident * _DQ_RESIDENT_SHARE <= _vmem_limit()
            else "split")


class WindowedBackwardUnsupported(NotImplementedError):
    """A gradient was asked of the flash kernel's forward-only form (a
    window, or K/V read by group): its backward is not written."""


FORWARD_ONLY = "forward"  # in `bwd_form`'s place where there is no backward


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_mha(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               bwd_form, window=None):
    # inference primal: no lse output (skips an f32 HBM write larger than
    # the attention output itself)
    out, _ = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                            interpret, with_lse=False, window=window)
    return out


def _flash_mha_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   bwd_form, window=None):
    if bwd_form == FORWARD_ONLY:
        raise WindowedBackwardUnsupported(
            "flash attention with a window, or with grouped K/V read by "
            "group, has no backward: train on full causal attention with "
            "equal head counts, or through the XLA paths")
    out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                              interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _kv_major_in_specs(block_q, block_k, D):
    """q, k, v, dO, lse, dsum on a (batch·head, key block, query block)
    grid: the dK/dV kernel's and the fused kernel's."""
    from jax.experimental import pallas as pl

    return [
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
    ]


def _backward_split(qf, kf, vf, dof, lse, dsum, *, causal, sm_scale,
                    block_q, block_k, interpret):
    """dQ on the forward's grid (key blocks innermost), then dK and dV with
    the query blocks innermost: each kernel computes S and dP for itself.
    For a sequence whose dQ the fused kernel cannot hold."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = qf.shape
    Tk = kf.shape[1]
    sdt = _stat_dtype(qf.dtype)
    tiles = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                 block_k=block_k)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit())
    dqf = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **tiles),
        grid=(BH, Tq // block_q, Tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), qf.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), sdt)],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, dof, lse, dsum)
    dkf, dvf = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, with_dq=False, **tiles),
        grid=(BH, Tk // block_k, Tq // block_q),
        in_specs=_kv_major_in_specs(block_q, block_k, D),
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), kf.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), sdt),
            pltpu.VMEM((block_k, D), sdt),
        ],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, dof, lse, dsum)
    return dqf, dkf, dvf


def _backward_fused(qf, kf, vf, dof, lse, dsum, *, causal, sm_scale,
                    block_q, block_k, interpret):
    """dQ, dK and dV from ONE kernel (`_flash_bwd_dkv_kernel` with dQ)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = qf.shape
    Tk = kf.shape[1]
    sdt = _stat_dtype(qf.dtype)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          with_dq=True),
        grid=(BH, Tk // block_k, Tq // block_q),
        in_specs=_kv_major_in_specs(block_q, block_k, D),
        out_specs=[
            # dQ's block is the (batch, head)'s whole row: a (block_q, D)
            # block indexed by the query block would be written back each
            # time the index moved, once a key block, with a part sum
            pl.BlockSpec((1, Tq, D), lambda b, j, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), kf.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((Tq, D), sdt),
            pltpu.VMEM((block_k, D), sdt),
            pltpu.VMEM((block_k, D), sdt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, dsum)


_BACKWARD = {"fused": _backward_fused, "split": _backward_split}


def _flash_mha_bwd(causal, sm_scale, block_q, block_k, interpret, bwd_form,
                   window, res, do):
    q, k, v, out, lse = res
    B, Tq, H, D = q.shape
    sdt = _stat_dtype(q.dtype)
    # D_i = rowsum(dO ∘ O), broadcast along the 128-lane stat axis like lse
    dsum = jnp.sum(do.astype(sdt) * out.astype(sdt), axis=-1)  # (B, Tq, H)
    dsum = dsum.transpose(0, 2, 1).reshape(B * H, Tq, 1)
    dsum = jnp.broadcast_to(dsum, (B * H, Tq, 128))
    grads = _BACKWARD[bwd_form](
        _to_slabs(q), _to_slabs(k), _to_slabs(v), _to_slabs(do), lse, dsum,
        causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return tuple(_from_slabs(g, B, H) for g in grads)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Exact attention, (B, T, H, D) layout, no key mask; differentiable
    (custom VJP: one Pallas backward kernel, or the dQ / dKV pair where the
    sequence is too long for it, `_backward_form`). Requires Tq/Tk
    divisible by the block sizes (callers pad or fall back).

    FORWARD ONLY (a gradient raises `WindowedBackwardUnsupported`): `k`
    and `v` with fewer heads than `q` (Hkv dividing H) are read by group,
    nothing repeated, and `window` (with `causal`) lets every query see
    the `window` keys that end at its own, key blocks wholly behind it
    skipped as those ahead of the diagonal are."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if Tq % block_q or Tk % block_k:
        raise ValueError(f"Tq={Tq}/Tk={Tk} not divisible by blocks "
                         f"({block_q}, {block_k})")
    if causal and Tq != Tk:
        raise ValueError("causal flash path requires Tq == Tk")
    if window is not None and not causal:
        raise ValueError("a window is written for causal attention")
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    if window is not None or k.shape[2] != H:
        return _flash_mha(q, k, v, causal, scale, block_q, block_k,
                          interpret, FORWARD_ONLY, window)
    return _flash_mha(q, k, v, causal, scale, block_q, block_k, interpret,
                      _backward_form(Tq, D, q.dtype))


def _platform_supported() -> bool:
    # the switch forces the XLA-blockwise fallback (A/B runs, tests)
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_ATTENTION")


def _eager_probe(dtype, block: int, head_dim: int, bwd_form: str,
                 groups: int = 1, window: Optional[int] = None) -> bool:
    """Compile + run the forward AND backward kernels once on tiny
    concrete inputs, OUTSIDE any trace. The dispatch itself usually runs
    inside a jit trace, where a Mosaic compile failure would surface at
    the OUTER jit's compile — far from any try/except here. Probing
    eagerly up front turns a platform that can't compile the kernels into
    a recorded XLA fallback instead of a training crash. Probed per
    (dtype, block, backward form) at T=block so the exact tile
    configuration and kernels that will run are the ones proven to
    compile. A forward-only class (`FORWARD_ONLY`: grouped K/V, a
    window) runs the forward on three blocks of queries, one K/V head
    and a window clipped to a block and a half, and is held to the
    grouped XLA attention."""
    if bwd_form == FORWARD_ONLY:
        import numpy as np

        from deeplearning4j_tpu.ops.attention import full_attention_grouped

        T = 3 * block
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, T, groups, head_dim)), dtype)
        k = jnp.asarray(rng.standard_normal((1, T, 1, head_dim)), dtype)
        v = jnp.asarray(rng.standard_normal((1, T, 1, head_dim)), dtype)
        w = None if window is None else min(int(window), block + block // 2)
        out = np.asarray(flash_attention(q, k, v, causal=True, block_q=block,
                                         block_k=block, window=w),
                         np.float32)
        ref = np.asarray(
            full_attention_grouped(q, k, v, causal=True, window=w),
            np.float32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
        if not np.allclose(out, ref, atol=tol, rtol=tol):
            raise ValueError(
                "flash forward compiled but disagrees with the XLA "
                f"attention: max abs err {np.max(np.abs(out - ref)):.3g}")
        return True
    B, T, H = 1, block, 1
    x = jnp.zeros((B, T, H, head_dim), dtype)

    def l(q, k, v):
        out = _flash_mha(q, k, v, True, head_dim ** -0.5, block, block,
                         False, bwd_form)
        return jnp.sum(out.astype(jnp.float32))

    g = jax.grad(l, argnums=(0, 1, 2))(x, x, x)
    return bool(jnp.all(jnp.isfinite(g[0].astype(jnp.float32))))


# _vmem_limit() (generation-derived ceiling, kernel_dispatch): the default 16 MiB
# scoped-stack limit rejects 2048-wide tiles whose f32 score slabs
# alone are 16 MiB

_BLOCK_CANDIDATES = (1024, 512, 256, 128)


def _verdict_key(dtype, block: int, D: int, bwd_form: str,
                 groups: int = 1, window: Optional[int] = None) -> tuple:
    # the backward's form last: `kernel_verdicts()` says which one engaged;
    # a forward-only class adds its group width and its window
    key = (jnp.dtype(dtype).name, block, D, bwd_form)
    if bwd_form == FORWARD_ONLY:
        key += (groups, "full" if window is None else ("window", int(window)))
    return key


def _probed_block(dtype, Tq: int, Tk: int, D: int, bwd_form: str,
                  groups: int = 1,
                  window: Optional[int] = None) -> Optional[int]:
    """Largest candidate tile that divides the sequence AND passes the
    fwd+bwd compile probe (a forward-only class: its forward's compile
    and parity probe). A block whose probe fails (e.g. VMEM overflow
    at a bigger head dim) falls through to the next smaller candidate
    instead of abandoning the kernel outright."""
    extra = () if bwd_form != FORWARD_ONLY else (groups, window)
    for block in _BLOCK_CANDIDATES:
        if Tq % block or Tk % block:
            continue
        if _probe_verdict(FAMILY, _verdict_key(dtype, block, D, bwd_form,
                                               *extra),
                          _eager_probe, (dtype, block, D, bwd_form) + extra):
            return block
    return None


def flash_attention_over_mesh(q, k, v, mesh, batch_axis, *, causal: bool,
                              block: int, interpret: bool = False):
    """`flash_attention` for a step jitted over `mesh`: the kernel is
    independent per (batch, head), so it runs as the per-device body of a
    `shard_map` with EVERY mesh axis manual (Mosaic's condition) — batch
    over `batch_axis`, heads over the remaining axes where the head count
    divides (the Megatron layout a column-sharded `Wqkv` already
    produces), replicated over them otherwise. A spec that does not match
    how the operands arrive costs a reshard, never correctness. Returns
    None when the batch does not divide its axis."""
    from jax.sharding import PartitionSpec as P

    B, _, H, _ = q.shape
    if batch_axis is not None and B % mesh.shape[batch_axis]:
        return None
    head_axes = tuple(a for a in mesh.axis_names if a != batch_axis)
    n_head = 1
    for a in head_axes:
        n_head *= mesh.shape[a]
    spec = P(batch_axis, None, head_axes if H % n_head == 0 else None, None)
    body = functools.partial(flash_attention, causal=causal, block_q=block,
                             block_k=block, interpret=interpret)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def flash_attention_or_none(q, k, v, *, causal: bool = False,
                            window: Optional[int] = None
                            ) -> Optional[jnp.ndarray]:
    """Dispatch probe (the reflective cuDNN-helper load): returns None when
    the kernel can't serve this call — wrong platform, non-divisible shapes,
    tiny sequences — or when every candidate tile failed its fwd+bwd
    compile probe. Biggest tile first: larger tiles amortise the
    per-grid-step overhead that dominates on v5e. K/V with fewer heads
    than `q`, or a `window`, take the kernel's forward-only form (a class
    of its own a group width and window; causal only, and not under a
    mesh)."""
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    forward_only = window is not None or Hkv != H
    if (not _platform_supported() or (causal and Tq != Tk)
            or D % 128 or q.dtype not in (jnp.float32, jnp.bfloat16)):
        return None
    if forward_only and (not causal or H % Hkv
                         or _traced_mesh() is not None):
        return None
    bwd_form = FORWARD_ONLY if forward_only \
        else _backward_form(Tq, D, q.dtype)
    extra = (H // Hkv, window) if forward_only else ()
    block = _probed_block(q.dtype, Tq, Tk, D, bwd_form, *extra)
    if block is None:
        return None
    key = _verdict_key(q.dtype, block, D, bwd_form, *extra)
    try:
        scope = _traced_mesh()
        if scope is None:
            return flash_attention(q, k, v, causal=causal, block_q=block,
                                   block_k=block, window=window)
        out = flash_attention_over_mesh(q, k, v, *scope, causal=causal,
                                        block=block)
        if out is None:
            _record_decline(
                FAMILY, key + ("mesh",),
                f"batch {B} does not divide mesh axis {scope[1]!r} of "
                f"{dict(scope[0].shape)}")
        return out
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, key,
                        f"staging at {q.shape}: {type(e).__name__}: {e}")
        return None
