"""Pallas TPU fused LSTM cell: the recurrent scan as ONE kernel (fwd + bwd).

The reference's hottest loop is the LSTM time loop
(`deeplearning4j-nn/.../recurrent/LSTMHelpers.java:157` forward,
`:311` BPTT backward), which it accelerates with cuDNN-class fused RNN
kernels. The XLA lowering here (`nn/layers/recurrent.py` `lax.scan`)
compiles the cell once, but on v5e each scan iteration still runs ~5
separate kernels (recurrent-GEMM fusion, gate elementwise, carry copies,
dynamic-update-slice output stacking) at ~14 us/step measured — mostly
per-iteration overhead around a 1.4 us matmul.

This module fuses the whole time loop into one Pallas kernel per
direction:

- grid = (B/block_b, T): batch blocks parallel, time sequential
  (`dimension_semantics=("parallel", "arbitrary")`); the (h, c) carries
  live in f32 VMEM scratch ACROSS grid steps, so HBM sees no carry
  traffic at all.
- Per step the kernel does exactly one MXU matmul (h @ RW) plus the gate
  elementwise chain, and streams in the pre-computed input projections
  xw[t] (the (B,T,nIn)@(nIn,4H) GEMM is batched over time OUTSIDE the
  kernel where the MXU runs it at full tilt).
- The TRAINING forward also stashes post-activation gates (i,f,o,g) and
  the cell states — the residuals the backward needs. The backward kernel
  walks the grid time-reversed computing only the truly-sequential part
  (dz per step + one (B,4H)@(4H,H) matmul for dh_prev); every batched
  gradient contraction (dW, dRW, db, d-peephole, dx) is a single big XLA
  GEMM/reduction over the stashed slabs outside the kernel.

Gate math (order [i, f, o, g], matching GravesLSTMParamInitializer):
  z  = xw[t] + h @ RW;  zi += pI*c;  zf += pF*c          (peepholes)
  i, f = sigmoid(zi), sigmoid(zf);  g = tanh(zg)
  c' = f*c + i*g;  o = sigmoid(zo + pO*c');  h' = o*tanh(c')

Dispatch follows the cuDNN-helper pattern (`ConvolutionLayer.java:69-79`,
as in `ops/pallas_attention.py`): an eager compile probe per shape class,
recorded fall-through to the lax.scan path when the kernel can't serve
(non-sigmoid/tanh activations, non-MXU-friendly sizes, or a platform
where Mosaic won't compile). Masked (variable-length) sequences run a
dedicated kernel pair: a masked step passes (h, c) through and emits
zeros (`LSTMHelpers`/`GradientCheckTestsMasking` semantics, binary
masks), with the carries stashed separately from the outputs — under
masking they differ.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.kernel_dispatch import (
    vmem_limit_bytes as _vmem_limit,
    dot as _dot,
    mxu_dtype as _mxu_dtype,
    platform_supported as _kernels_dispatch,
    probe_verdict as _probe_verdict,
    record_decline as _record_decline,
    stat_dtype as _stat_dtype,
    traced_mesh as _traced_mesh,
)

FAMILY = "fused_lstm"  # this module's row in kernel_verdicts()



def _lstm_fwd_kernel(xw_ref, rw_ref, peep_ref, h0_ref, c0_ref,
                     h_out_ref, cT_ref, c_stash_ref, gates_ref,
                     h_scr, c_scr, *, n_out: int, with_stash: bool):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    nt = pl.num_programs(1)
    dt = _mxu_dtype(xw_ref.dtype)
    sdt = _stat_dtype(xw_ref.dtype)
    H = n_out

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(sdt)
        c_scr[:] = c0_ref[:].astype(sdt)

    c = c_scr[:]
    z = xw_ref[0].astype(sdt) + _dot(h_scr[:].astype(dt), rw_ref[:],
                                     ((1,), (0,)), dt)
    pI = peep_ref[0:1].astype(sdt)
    pF = peep_ref[1:2].astype(sdt)
    pO = peep_ref[2:3].astype(sdt)
    i = jax.nn.sigmoid(z[:, :H] + pI * c)
    f = jax.nn.sigmoid(z[:, H:2 * H] + pF * c)
    g = jnp.tanh(z[:, 3 * H:])
    c_new = f * c + i * g
    o = jax.nn.sigmoid(z[:, 2 * H:3 * H] + pO * c_new)
    h_new = o * jnp.tanh(c_new)

    h_out_ref[0] = h_new.astype(h_out_ref.dtype)
    if with_stash:
        c_stash_ref[0] = c_new.astype(c_stash_ref.dtype)
        gates_ref[0] = jnp.concatenate([i, f, o, g], axis=1).astype(
            gates_ref.dtype)
    h_scr[:] = h_new
    c_scr[:] = c_new

    @pl.when(t == nt - 1)
    def _final_cell():
        cT_ref[:] = c_new.astype(cT_ref.dtype)


def _lstm_bwd_kernel(gates_ref, c_ref, c_prev_ref, dh_out_ref, dcT_ref,
                     rw_ref, peep_ref, c0_ref, dz_ref, dhc0_ref,
                     dh_scr, dc_scr, *, n_out: int):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    nt = pl.num_programs(1)
    s_is_first = t == nt - 1  # reversed walk: last grid step is timestep 0
    dt = _mxu_dtype(dz_ref.dtype)
    sdt = _stat_dtype(dz_ref.dtype)
    H = n_out

    @pl.when(t == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = dcT_ref[:].astype(sdt)

    gates = gates_ref[0].astype(sdt)
    i, f, o, g = (gates[:, :H], gates[:, H:2 * H], gates[:, 2 * H:3 * H],
                  gates[:, 3 * H:])
    c_t = c_ref[0].astype(sdt)
    # c_{t-1}: the block index is clamped to 0 at the first timestep, where
    # the real previous state is c0
    c_prev = jnp.where(s_is_first, c0_ref[:].astype(sdt),
                       c_prev_ref[0].astype(sdt))
    pI = peep_ref[0:1].astype(sdt)
    pF = peep_ref[1:2].astype(sdt)
    pO = peep_ref[2:3].astype(sdt)

    tanh_c = jnp.tanh(c_t)
    dh = dh_out_ref[0].astype(sdt) + dh_scr[:]
    do = dh * tanh_c
    dzo = do * o * (1.0 - o)
    dct = dh * o * (1.0 - tanh_c * tanh_c) + dc_scr[:] + dzo * pO
    dzg = dct * i * (1.0 - g * g)
    dzi = dct * g * i * (1.0 - i)
    dzf = dct * c_prev * f * (1.0 - f)
    dc_prev = dct * f + dzi * pI + dzf * pF
    dz = jnp.concatenate([dzi, dzf, dzo, dzg], axis=1)
    dh_prev = _dot(dz.astype(dt), rw_ref[:], ((1,), (1,)), dt)

    dz_ref[0] = dz.astype(dz_ref.dtype)
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev

    @pl.when(s_is_first)
    def _emit_carry_grads():
        dhc0_ref[0] = dh_prev.astype(dhc0_ref.dtype)
        dhc0_ref[1] = dc_prev.astype(dhc0_ref.dtype)


def _lstm_fwd_kernel_masked(xw_ref, rw_ref, peep_ref, h0_ref, c0_ref,
                            m_ref, h_out_ref, hT_ref, cT_ref, hsel_ref,
                            csel_ref, gates_ref, h_scr, c_scr, *,
                            n_out: int, with_stash: bool):
    """Masked forward (reference `LSTMHelpers` masking semantics): a
    masked timestep passes (h, c) through unchanged and emits zeros. The
    carry h_sel = m*h_new + (1-m)*h_prev DIFFERS from the emitted output
    m*h_new, so the training stash keeps both (the backward's h_prev /
    c_prev come from the carries)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    nt = pl.num_programs(1)
    dt = _mxu_dtype(xw_ref.dtype)
    sdt = _stat_dtype(xw_ref.dtype)
    H = n_out

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(sdt)
        c_scr[:] = c0_ref[:].astype(sdt)

    c = c_scr[:]
    h_prev = h_scr[:]
    z = xw_ref[0].astype(sdt) + _dot(h_prev.astype(dt), rw_ref[:],
                                     ((1,), (0,)), dt)
    pI = peep_ref[0:1].astype(sdt)
    pF = peep_ref[1:2].astype(sdt)
    pO = peep_ref[2:3].astype(sdt)
    i = jax.nn.sigmoid(z[:, :H] + pI * c)
    f = jax.nn.sigmoid(z[:, H:2 * H] + pF * c)
    g = jnp.tanh(z[:, 3 * H:])
    c_new = f * c + i * g
    o = jax.nn.sigmoid(z[:, 2 * H:3 * H] + pO * c_new)
    h_new = o * jnp.tanh(c_new)
    m = m_ref[0].astype(sdt)
    # hard select on m > 0 (NOT a linear blend): matches the scan path's
    # where() for any mask values; the emitted output scales by m like
    # the reference (`out = h_new * m`). The mask is non-differentiable.
    mpos = m > 0
    h_sel = jnp.where(mpos, h_new, h_prev)
    c_sel = jnp.where(mpos, c_new, c)

    h_out_ref[0] = (h_sel * m).astype(h_out_ref.dtype)
    if with_stash:
        hsel_ref[0] = h_sel.astype(hsel_ref.dtype)
        csel_ref[0] = c_sel.astype(csel_ref.dtype)
        gates_ref[0] = jnp.concatenate([i, f, o, g], axis=1).astype(
            gates_ref.dtype)
    h_scr[:] = h_sel
    c_scr[:] = c_sel

    @pl.when(t == nt - 1)
    def _final_state():
        # the final CARRY differs from the last output under masking:
        # emit it explicitly (the unmasked kernel's h_out[-1] trick
        # would return m*h_new instead of the carried state)
        hT_ref[:] = h_sel.astype(hT_ref.dtype)
        cT_ref[:] = c_sel.astype(cT_ref.dtype)


def _lstm_bwd_kernel_masked(gates_ref, cprev_ref, dh_out_ref,
                            dhT_ref, dcT_ref, m_ref, rw_ref, peep_ref,
                            c0_ref, dz_ref, dhc0_ref, dh_scr, dc_scr,
                            *, n_out: int):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    nt = pl.num_programs(1)
    s_is_first = t == nt - 1
    dt = _mxu_dtype(dz_ref.dtype)
    sdt = _stat_dtype(dz_ref.dtype)
    H = n_out

    @pl.when(t == 0)
    def _init():
        dh_scr[:] = dhT_ref[:].astype(sdt)
        dc_scr[:] = dcT_ref[:].astype(sdt)

    gates = gates_ref[0].astype(sdt)
    i, f, o, g = (gates[:, :H], gates[:, H:2 * H], gates[:, 2 * H:3 * H],
                  gates[:, 3 * H:])
    c_prev = jnp.where(s_is_first, c0_ref[:].astype(sdt),
                       cprev_ref[0].astype(sdt))
    m = m_ref[0].astype(sdt)
    # the stash keeps the SELECTED carry; the cell backward needs the
    # candidate cell state, reconstructed from the gates
    c_pre = f * c_prev + i * g
    pI = peep_ref[0:1].astype(sdt)
    pF = peep_ref[1:2].astype(sdt)
    pO = peep_ref[2:3].astype(sdt)

    dhc = dh_scr[:]
    dcc = dc_scr[:]
    # out = h_sel*m; carry h_sel = where(m>0, h_new, h_prev) — the
    # select's transpose routes the whole cotangent to ONE side
    mpos = m > 0
    d_hsel = m * dh_out_ref[0].astype(sdt) + dhc
    zero = jnp.zeros_like(d_hsel)
    dh_new = jnp.where(mpos, d_hsel, zero)
    dh_prev_bypass = jnp.where(mpos, zero, d_hsel)
    dc_new = jnp.where(mpos, dcc, zero)
    dc_prev_bypass = jnp.where(mpos, zero, dcc)

    tanh_c = jnp.tanh(c_pre)
    do = dh_new * tanh_c
    dzo = do * o * (1.0 - o)
    dct = dh_new * o * (1.0 - tanh_c * tanh_c) + dc_new + dzo * pO
    dzg = dct * i * (1.0 - g * g)
    dzi = dct * g * i * (1.0 - i)
    dzf = dct * c_prev * f * (1.0 - f)
    dz = jnp.concatenate([dzi, dzf, dzo, dzg], axis=1)
    dh_prev = _dot(dz.astype(dt), rw_ref[:], ((1,), (1,)), dt) \
        + dh_prev_bypass
    dc_prev = dct * f + dzi * pI + dzf * pF + dc_prev_bypass

    dz_ref[0] = dz.astype(dz_ref.dtype)
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev

    @pl.when(s_is_first)
    def _emit_carry_grads():
        dhc0_ref[0] = dh_prev.astype(dhc0_ref.dtype)
        dhc0_ref[1] = dc_prev.astype(dhc0_ref.dtype)


# _vmem_limit() (generation-derived ceiling, kernel_dispatch): the default 16 MiB
# scoped-stack limit caps the batch block at 512 for H=256 (bb=1024
# needs 18.4 MiB of double-buffered xw/gates slabs) and rejects H=1024
# outright (100.1 MiB at bb=1024); the raised ceiling lets the probe
# ladder serve MXU-width hidden sizes, and the fall-through still lands
# on whatever the hardware accepts (bb=2048 at H=1024 wants 145 MiB >
# the physical 128 and falls to 1024)

_BLOCK_CANDIDATES = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)


def _batch_block(B: int) -> Optional[int]:
    """Largest batch block dividing B (the starting candidate — the
    dispatch probes downward from here, see _probed_batch_block)."""
    for bb in _BLOCK_CANDIDATES:
        if B % bb == 0:
            return bb
    return None


def _fwd_call(xw, rw, peep, h0, c0, *, bb: int, with_stash: bool,
              interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, B, G = xw.shape
    H = G // 4
    sdt = _stat_dtype(xw.dtype)
    kernel = functools.partial(_lstm_fwd_kernel, n_out=H,
                               with_stash=with_stash)
    blk = lambda shape: pl.BlockSpec(shape, lambda b, t: (t, b, 0))
    const2 = lambda shape: pl.BlockSpec(shape, lambda b, t: (b, 0))
    small = pl.BlockSpec((1, 1, 1), lambda b, t: (0, 0, 0))
    h_out, cT, c_stash, gates = pl.pallas_call(
        kernel,
        grid=(B // bb, T),
        in_specs=[
            blk((1, bb, G)),                                   # xw[t]
            pl.BlockSpec((H, G), lambda b, t: (0, 0)),         # RW
            pl.BlockSpec((3, H), lambda b, t: (0, 0)),         # peepholes
            const2((bb, H)),                                   # h0
            const2((bb, H)),                                   # c0
        ],
        out_specs=[blk((1, bb, H)),
                   const2((bb, H)),
                   blk((1, bb, H)) if with_stash else small,
                   blk((1, bb, G)) if with_stash else small],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), xw.dtype),
            jax.ShapeDtypeStruct((B, H), xw.dtype),
            jax.ShapeDtypeStruct((T, B, H) if with_stash else (1, 1, 1),
                                 xw.dtype),
            jax.ShapeDtypeStruct((T, B, G) if with_stash else (1, 1, 1),
                                 xw.dtype)],
        scratch_shapes=[pltpu.VMEM((bb, H), sdt),
                        pltpu.VMEM((bb, H), sdt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(xw, rw, peep, h0, c0)
    return h_out, cT, c_stash, gates


def _bwd_call(gates, c_stash, dh_out, dcT, rw, peep, c0, *, bb: int,
              interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, B, G = gates.shape
    H = G // 4
    sdt = _stat_dtype(gates.dtype)
    kernel = functools.partial(_lstm_bwd_kernel, n_out=H)
    rev = lambda shape: pl.BlockSpec(shape, lambda b, t: (T - 1 - t, b, 0))
    const2 = lambda shape: pl.BlockSpec(shape, lambda b, t: (b, 0))
    dz, dhc0 = pl.pallas_call(
        kernel,
        grid=(B // bb, T),
        in_specs=[
            rev((1, bb, G)),                                   # gates[s]
            rev((1, bb, H)),                                   # c[s]
            # c[s-1] (block index clamped at s == 0; kernel swaps in c0)
            pl.BlockSpec((1, bb, H),
                         lambda b, t: (jnp.maximum(T - 2 - t, 0), b, 0)),
            rev((1, bb, H)),                                   # dh_out[s]
            const2((bb, H)),                                   # dcT
            pl.BlockSpec((H, G), lambda b, t: (0, 0)),         # RW
            pl.BlockSpec((3, H), lambda b, t: (0, 0)),         # peepholes
            const2((bb, H)),                                   # c0
        ],
        out_specs=[rev((1, bb, G)),
                   pl.BlockSpec((2, bb, H), lambda b, t: (0, b, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, B, G), gates.dtype),
                   jax.ShapeDtypeStruct((2, B, H), sdt)],
        scratch_shapes=[pltpu.VMEM((bb, H), sdt),
                        pltpu.VMEM((bb, H), sdt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(gates, c_stash, c_stash, dh_out, dcT, rw, peep, c0)
    return dz, dhc0


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _lstm_core(xw, rw, peep, h0, c0, interpret, bb):
    """(T,B,4H) projected inputs -> ((T,B,H) hidden states, cT (B,H))."""
    h_out, cT, _, _ = _fwd_call(xw, rw, peep, h0, c0, bb=bb,
                                with_stash=False, interpret=interpret)
    return h_out, cT


def _lstm_core_fwd(xw, rw, peep, h0, c0, interpret, bb):
    h_out, cT, c_stash, gates = _fwd_call(xw, rw, peep, h0, c0, bb=bb,
                                          with_stash=True,
                                          interpret=interpret)
    return (h_out, cT), (gates, c_stash, h_out, rw, peep, h0, c0)


def _lstm_core_bwd(interpret, bb, res, cots):
    dh_out, dcT = cots
    gates, c_stash, h_out, rw, peep, h0, c0 = res
    T, B, G = gates.shape
    H = G // 4
    sdt = _stat_dtype(gates.dtype)
    dz, dhc0 = _bwd_call(gates, c_stash, dh_out, dcT.astype(gates.dtype),
                         rw, peep, c0, bb=bb, interpret=interpret)
    # batched contractions over the full (T*B) slab — big single XLA GEMMs,
    # the MXU-friendly shape the per-step kernel deliberately leaves out
    dt = _mxu_dtype(gates.dtype)
    h_prev = jnp.concatenate([h0[None], h_out[:-1]], axis=0)
    drw = _dot(h_prev.reshape(T * B, H).astype(dt).T,
               dz.reshape(T * B, G).astype(dt), ((1,), (0,)), dt)
    c_prev = jnp.concatenate([c0[None], c_stash[:-1]], axis=0).astype(sdt)
    dzf32 = dz.astype(sdt)
    dpi = jnp.sum(dzf32[..., :H] * c_prev, axis=(0, 1))
    dpf = jnp.sum(dzf32[..., H:2 * H] * c_prev, axis=(0, 1))
    dpo = jnp.sum(dzf32[..., 2 * H:3 * H] * c_stash.astype(sdt),
                  axis=(0, 1))
    dpeep = jnp.stack([dpi, dpf, dpo]).astype(peep.dtype)
    return (dz, drw.astype(rw.dtype), dpeep,
            dhc0[0].astype(h0.dtype), dhc0[1].astype(c0.dtype))


_lstm_core.defvjp(_lstm_core_fwd, _lstm_core_bwd)


def _fwd_call_masked(xw, rw, peep, h0, c0, mask, *, bb: int,
                     with_stash: bool, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, B, G = xw.shape
    H = G // 4
    sdt = _stat_dtype(xw.dtype)
    kernel = functools.partial(_lstm_fwd_kernel_masked, n_out=H,
                               with_stash=with_stash)
    blk = lambda shape: pl.BlockSpec(shape, lambda b, t: (t, b, 0))
    const2 = lambda shape: pl.BlockSpec(shape, lambda b, t: (b, 0))
    small = pl.BlockSpec((1, 1, 1), lambda b, t: (0, 0, 0))
    stash = (T, B, H) if with_stash else (1, 1, 1)
    outs = pl.pallas_call(
        kernel,
        grid=(B // bb, T),
        in_specs=[
            blk((1, bb, G)),                                   # xw[t]
            pl.BlockSpec((H, G), lambda b, t: (0, 0)),         # RW
            pl.BlockSpec((3, H), lambda b, t: (0, 0)),         # peepholes
            const2((bb, H)),                                   # h0
            const2((bb, H)),                                   # c0
            blk((1, bb, H)),                                   # mask[t]
        ],
        out_specs=[blk((1, bb, H)),
                   const2((bb, H)), const2((bb, H)),
                   blk((1, bb, H)) if with_stash else small,
                   blk((1, bb, H)) if with_stash else small,
                   blk((1, bb, G)) if with_stash else small],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), xw.dtype),         # masked out
            jax.ShapeDtypeStruct((B, H), xw.dtype),            # hT carry
            jax.ShapeDtypeStruct((B, H), xw.dtype),            # cT carry
            jax.ShapeDtypeStruct(stash, xw.dtype),             # h_sel
            jax.ShapeDtypeStruct(stash, xw.dtype),             # c_sel
            jax.ShapeDtypeStruct((T, B, G) if with_stash else (1, 1, 1),
                                 xw.dtype)],                   # gates
        scratch_shapes=[pltpu.VMEM((bb, H), sdt),
                        pltpu.VMEM((bb, H), sdt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(xw, rw, peep, h0, c0, mask)
    return outs


def _bwd_call_masked(gates, c_sel, dh_out, dhT, dcT, mask, rw, peep, c0,
                     *, bb: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, B, G = gates.shape
    H = G // 4
    sdt = _stat_dtype(gates.dtype)
    kernel = functools.partial(_lstm_bwd_kernel_masked, n_out=H)
    rev = lambda shape: pl.BlockSpec(shape, lambda b, t: (T - 1 - t, b, 0))
    const2 = lambda shape: pl.BlockSpec(shape, lambda b, t: (b, 0))
    dz, dhc0 = pl.pallas_call(
        kernel,
        grid=(B // bb, T),
        in_specs=[
            rev((1, bb, G)),                                   # gates[s]
            # c_sel shifted: c_prev[s] (clamped at s == 0; kernel uses c0)
            pl.BlockSpec((1, bb, H),
                         lambda b, t: (jnp.maximum(T - 2 - t, 0), b, 0)),
            rev((1, bb, H)),                                   # dh_out[s]
            const2((bb, H)),                                   # dhT
            const2((bb, H)),                                   # dcT
            rev((1, bb, H)),                                   # mask[s]
            pl.BlockSpec((H, G), lambda b, t: (0, 0)),         # RW
            pl.BlockSpec((3, H), lambda b, t: (0, 0)),         # peepholes
            const2((bb, H)),                                   # c0
        ],
        out_specs=[rev((1, bb, G)),
                   pl.BlockSpec((2, bb, H), lambda b, t: (0, b, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, B, G), gates.dtype),
                   jax.ShapeDtypeStruct((2, B, H), sdt)],
        scratch_shapes=[pltpu.VMEM((bb, H), sdt),
                        pltpu.VMEM((bb, H), sdt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(gates, c_sel, dh_out, dhT, dcT, mask, rw, peep, c0)
    return dz, dhc0


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _lstm_core_masked(xw, rw, peep, h0, c0, mask, interpret, bb):
    """Masked variant: returns (masked outputs (T,B,H), hT, cT)."""
    h_out, hT, cT, _, _, _ = _fwd_call_masked(
        xw, rw, peep, h0, c0, mask, bb=bb, with_stash=False,
        interpret=interpret)
    return h_out, hT, cT


def _lstm_core_masked_fwd(xw, rw, peep, h0, c0, mask, interpret, bb):
    h_out, hT, cT, h_sel, c_sel, gates = _fwd_call_masked(
        xw, rw, peep, h0, c0, mask, bb=bb, with_stash=True,
        interpret=interpret)
    return (h_out, hT, cT), (gates, h_sel, c_sel, mask, rw, peep, h0, c0)


def _lstm_core_masked_bwd(interpret, bb, res, cots):
    dh_out, dhT, dcT = cots
    gates, h_sel, c_sel, mask, rw, peep, h0, c0 = res
    T, B, G = gates.shape
    H = G // 4
    sdt = _stat_dtype(gates.dtype)
    dz, dhc0 = _bwd_call_masked(gates, c_sel, dh_out,
                                dhT.astype(gates.dtype),
                                dcT.astype(gates.dtype), mask, rw, peep,
                                c0, bb=bb, interpret=interpret)
    dt = _mxu_dtype(gates.dtype)
    h_prev = jnp.concatenate([h0[None], h_sel[:-1]], axis=0)
    drw = _dot(h_prev.reshape(T * B, H).astype(dt).T,
               dz.reshape(T * B, G).astype(dt), ((1,), (0,)), dt)
    c_prev = jnp.concatenate([c0[None], c_sel[:-1]], axis=0).astype(sdt)
    dzf32 = dz.astype(sdt)
    gi = gates[..., :H].astype(sdt)
    gf = gates[..., H:2 * H].astype(sdt)
    gg = gates[..., 3 * H:].astype(sdt)
    # candidate cell state reconstructed (the stash keeps the carry)
    c_pre = gf * c_prev + gi * gg
    dpi = jnp.sum(dzf32[..., :H] * c_prev, axis=(0, 1))
    dpf = jnp.sum(dzf32[..., H:2 * H] * c_prev, axis=(0, 1))
    dpo = jnp.sum(dzf32[..., 2 * H:3 * H] * c_pre, axis=(0, 1))
    dpeep = jnp.stack([dpi, dpf, dpo]).astype(peep.dtype)
    return (dz, drw.astype(rw.dtype), dpeep,
            dhc0[0].astype(h0.dtype), dhc0[1].astype(c0.dtype),
            jnp.zeros_like(mask))


_lstm_core_masked.defvjp(_lstm_core_masked_fwd, _lstm_core_masked_bwd)


def _platform_ok() -> bool:
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_LSTM")


def _eager_probe(dtype, bb, H, masked: bool = False) -> bool:
    """Compile + run fwd AND bwd once at the TILE configuration the real
    call will use — (T=2, B=batch block, H) — outside any trace, so a
    Mosaic failure becomes a recorded scan fallback instead of an outer-jit
    compile crash (same rationale as the flash-attention probe). The block
    shapes are what Mosaic compiles; T and the number of batch blocks only
    set the grid length, so a tiny-T probe proves the real kernel without
    allocating GB-scale probe buffers (the real (T, B, 4H) could rival the
    training step itself near HBM capacity). `masked` probes the masked
    kernel pair instead."""
    T = 2
    kx, kr = jax.random.split(jax.random.PRNGKey(0))
    xw = jax.random.normal(kx, (T, bb, 4 * H), dtype)
    rw = jax.random.normal(kr, (H, 4 * H), dtype) * 0.05
    peep = jnp.zeros((3, H), dtype)
    z = jnp.zeros((bb, H), dtype)

    def loss(xw, rw):
        if masked:
            m = jnp.ones((T, bb, H), dtype)
            h, hT, cT = _lstm_core_masked(xw, rw, peep, z, z, m, False, bb)
            return (jnp.sum(h.astype(jnp.float32))
                    + jnp.sum(hT.astype(jnp.float32))
                    + jnp.sum(cT.astype(jnp.float32)))
        h, cT = _lstm_core(xw, rw, peep, z, z, False, bb)
        return jnp.sum(h.astype(jnp.float32)) + jnp.sum(
            cT.astype(jnp.float32))

    g = jax.grad(loss, argnums=(0, 1))(xw, rw)
    return bool(jnp.all(jnp.isfinite(g[1].astype(jnp.float32))))


def _probed_batch_block(dtype, B: int, H: int, masked: bool) -> Optional[int]:
    """Largest batch block dividing B whose (compile + run) probe passes.
    Falls through to the next smaller candidate on failure — a bb that
    overflows VMEM at a large H must not disqualify the kernel outright
    (per-candidate verdicts are cached, so the fallback probes run once
    per shape class)."""
    for bb in _BLOCK_CANDIDATES:
        if B % bb:
            continue
        key = (jnp.dtype(dtype).name, bb, H, masked)
        if _probe_verdict(FAMILY, key, _eager_probe,
                          (dtype, bb, H, masked)):
            return bb
    return None


def lstm_fused_or_none(x, W, RW, b, peephole, h0, c0, *,
                       gate_is_sigmoid: bool, cell_is_tanh: bool,
                       mask=None, reverse: bool = False,
                       interpret: bool = False
                       ) -> Optional[Tuple[jnp.ndarray,
                                           Tuple[jnp.ndarray,
                                                 jnp.ndarray]]]:
    """Fused-path dispatch: returns (out (B,T,H), (hT, cT)) or None when
    the kernel can't serve this call (the reflective cuDNN-helper
    contract). `interpret=True` runs the Pallas interpreter (any platform;
    used by parity/gradient-check tests)."""
    B, T, _ = x.shape
    H = RW.shape[0]
    f64 = (jnp.float64,) if interpret else ()
    if (not gate_is_sigmoid or not cell_is_tanh
            or H % 128 or T < 2 or _batch_block(B) is None
            or x.dtype not in (jnp.float32, jnp.bfloat16, *f64)):
        return None
    if not interpret and not _platform_ok():
        return None
    masked = mask is not None
    if not interpret and _traced_mesh() is not None:
        # a Mosaic kernel traced into a mesh-wide jit fails at lowering;
        # the cell is not shard_map-wrapped (as flash attention is) yet
        _record_decline(FAMILY, (jnp.dtype(x.dtype).name, "mesh", H, masked),
                        "step is jitted over a device mesh and the fused "
                        "cell has no shard_map wrap; the scan path runs")
        return None
    if interpret:
        bb = _batch_block(B)  # no probe: the interpreter always works
    else:
        bb = _probed_batch_block(x.dtype, B, H, masked)
        if bb is None:
            return None
    # time-major input projection: ONE big GEMM, with the transpose to the
    # layout the kernel streams fused into the GEMM output
    xw = jnp.einsum("bti,ig->tbg", x, W) + b
    if reverse:
        xw = xw[::-1]
    if peephole is None:
        peep = jnp.zeros((3, H), x.dtype)
    else:
        peep = jnp.stack(peephole).astype(x.dtype)
    zh = jnp.zeros((B, H), x.dtype)
    h0 = zh if h0 is None else h0.astype(x.dtype)
    c0 = zh if c0 is None else c0.astype(x.dtype)
    try:
        if masked:
            # (B, T) -> an (T, B, H) slab the kernel streams per step
            # (the lane-broadcast layout Mosaic tiles natively)
            m = jnp.swapaxes(jnp.asarray(mask), 0, 1)
            if reverse:
                m = m[::-1]
            m_slab = jnp.broadcast_to(m[..., None].astype(x.dtype),
                                      (T, B, H))
            h_tbh, hT, cT = _lstm_core_masked(xw, RW, peep, h0, c0,
                                              m_slab, interpret, bb)
        else:
            h_tbh, cT = _lstm_core(xw, RW, peep, h0, c0, interpret, bb)
            hT = None
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, (jnp.dtype(x.dtype).name, bb, H, masked),
                        f"staging at {x.shape}: {type(e).__name__}: {e}")
        return None
    if reverse:
        h_tbh = h_tbh[::-1]
        if hT is None:
            hT = h_tbh[0]
    elif hT is None:
        hT = h_tbh[-1]
    return jnp.swapaxes(h_tbh, 0, 1), (hT, cT)


__all__ = ["lstm_fused_or_none"]
