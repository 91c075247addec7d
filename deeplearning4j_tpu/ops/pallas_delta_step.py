"""Pallas TPU gated delta-rule decode step: every slot's matrix state
read ONCE and written ONCE, in place.

    S' = a S + (b (v - a S k)) k^T;   o = S' q        per slot and head

(`ops/delta_rule.delta_step`, whose signature and layout this keeps.)
As XLA elementwise products and reductions the step sweeps each slot's
state at least three times a layer: a read for `S k`, a read-modify-
write for the update, and a read for `S' q` where the compiler does not
fuse it into the write. Here the grid runs over slots; one grid step
brings one slot's whole state `(d_k, H * d_v)` into VMEM, computes
`S k`, the update and `S' q` on the resident tile and writes it back
through the aliased operand, so under donation (or inside the decode
chunk's scan carry) no state is copied and HBM sees 2 x 4 bytes an
element.

**Layout.** The state arrives as the engine keeps it, `(S, d_k,
H * d_v)` float32 (`ops/delta_rule.py`): d_k rows on sublanes, the
heads' value columns side by side on lanes. Heads are handled
`group` at a time so that a group's columns are whole 128-lane tiles
(d_v 192: two heads, 384 lanes; d_v a multiple of 128: one). Inside a
group a key or query column is broadcast along the lanes of its own
head by one select on the lane index; the per-head scalars `a`, `b` and
the row `v` arrive already expanded to a group's lanes, `(S, 3,
H / group, group * d_v)`, computed by XLA outside (3 x 23 KB a slot
beside 2 x 2.2 MB of state), and `k`, `q` as one transposed `(S, d_k,
2 H)` operand, so the kernel needs no relayout. All arithmetic is
float32 on the vector unit: a slot with `b = 0, a = 1` keeps its state
bit for bit, as in the XLA form.

Dispatch rides `ops/kernel_dispatch.py` under the family name
`gdn_step`: the probe compiles and runs the kernel at the exact shape
class and holds it to `delta_step`; `DL4J_TPU_NO_PALLAS_GDN_STEP` forces
the XLA form; CPU backends never dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.delta_rule import delta_step
from deeplearning4j_tpu.ops.kernel_dispatch import (
    platform_supported as _kernels_dispatch,
    probe_verdict as _probe_verdict,
    record_decline as _record_decline,
    vmem_limit_bytes as _vmem_limit,
)

FAMILY = "gdn_step"  # this module's row in kernel_verdicts()
F32 = jnp.float32


def _group(dv: int) -> int:
    """Heads handled together so that their value columns are whole
    lane tiles: 1 or 2; 0 where neither does."""
    return 1 if dv % 128 == 0 else 2 if (2 * dv) % 128 == 0 else 0


def _step_kernel(kq_ref, vab_ref, s_ref, o_ref, s_out_ref, *, H: int,
                 dv: int, group: int):
    """Grid (S,): slot `s` owns its whole state tile. `kq_ref`
    (1, d_k, 2H): column h is head h's key, column H + h its query;
    `vab_ref` (1, 3, H / group, group * d_v): v, a, b by group row."""
    dk = s_ref.shape[1]
    W = group * dv
    kq = kq_ref[0]
    first = jax.lax.broadcasted_iota(jnp.int32, (dk, W), 1) < dv

    def column(c, p):
        """Column(s) `c` of the group's heads along their own lanes."""
        if group == 1:
            return jnp.broadcast_to(kq[:, c + p:c + p + 1], (dk, W))
        return jnp.where(first, kq[:, c + 2 * p:c + 2 * p + 1],
                         kq[:, c + 2 * p + 1:c + 2 * p + 2])

    for p in range(H // group):
        m = s_ref[0, :, p * W:(p + 1) * W]                    # (dk, W)
        kx, qx = column(0, p), column(H, p)
        v = vab_ref[0, 0, p:p + 1, :]                          # (1, W)
        a = vab_ref[0, 1, p:p + 1, :]
        b = vab_ref[0, 2, p:p + 1, :]
        sk = jnp.sum(m * kx, axis=0, keepdims=True)
        m = a * m + kx * (b * (v - a * sk))
        s_out_ref[0, :, p * W:(p + 1) * W] = m
        o_ref[0, p:p + 1, :] = jnp.sum(m * qx, axis=0, keepdims=True)


# jitted so that a step over many layers traces and lowers the kernel
# once and calls it once a layer (`pallas_paged_kv_write`'s lesson)
@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_step(state, q, k, v, g, beta, *, interpret: bool = False):
    """`delta_step` as one in-place kernel call (same arguments, same
    return)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, dk, HV = state.shape
    H = q.shape[1]
    dv = HV // H
    G = _group(dv)
    P, W = H // G, G * dv
    kq = jnp.concatenate([jnp.swapaxes(k.astype(F32), 1, 2),
                          jnp.swapaxes(q.astype(F32), 1, 2)], axis=2)
    rows = jnp.stack(
        [v.astype(F32).reshape(S, P, W)]
        + [jnp.repeat(x.astype(F32), dv, axis=1).reshape(S, P, W)
           for x in (jnp.exp(g.astype(F32)), beta)], axis=1)
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, H=H, dv=dv, group=G),
        grid=(S,),
        in_specs=[pl.BlockSpec((1, dk, 2 * H), lambda s: (s, 0, 0)),
                  pl.BlockSpec((1, 3, P, W), lambda s: (s, 0, 0, 0)),
                  pl.BlockSpec((1, dk, HV), lambda s: (s, 0, 0))],
        out_specs=[pl.BlockSpec((1, P, W), lambda s: (s, 0, 0)),
                   pl.BlockSpec((1, dk, HV), lambda s: (s, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, P, W), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(kq, rows, state)
    return o.reshape(S, H, dv).astype(v.dtype), state


def vmem_bytes_estimate(H: int, dk: int, dv: int) -> int:
    """Resident VMEM of one grid step: the slot's state tile, double-
    buffered on the way in and on the way out; the small operands are
    noise beside it."""
    return 4 * 4 * dk * H * dv


def _platform_supported() -> bool:
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_GDN_STEP")


def _eager_probe(dtype, H: int, dk: int, dv: int) -> bool:
    """Compile and run the kernel at this shape class (three slots, one
    of them inactive) and hold it to `delta_step`."""
    import numpy as np

    rng = np.random.default_rng(0)
    S = 3
    state = jnp.asarray(rng.standard_normal((S, dk, H * dv)), F32)
    q, k = (jnp.asarray(rng.standard_normal((S, H, dk)) / dk ** 0.5, dtype)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((S, H, dv)), dtype)
    live = np.array([1.0, 1.0, 0.0])[:, None]
    g = jnp.asarray(-rng.random((S, H)) * live, F32)
    beta = jnp.asarray(2.0 * rng.random((S, H)) * live, F32)
    want_o, want_s = delta_step(state, q, k, v, g, beta)
    got_o, got_s = gdn_step(state + 0.0, q, k, v, g, beta)
    if not bool(jnp.array_equal(got_s[2], state[2])):
        raise ValueError("kernel compiled but moved an inactive slot's "
                         "state")
    for name, got, want in (("o", got_o, want_o), ("state", got_s, want_s)):
        err = float(jnp.max(jnp.abs(got.astype(F32) - want.astype(F32))))
        tol = 2e-2 if name == "o" and dtype == jnp.bfloat16 else 1e-4
        if not err <= tol:
            raise ValueError(f"kernel compiled but its {name} lies "
                             f"{err:.3g} from delta_step's")
    return True


def gdn_step_or_none(state, q, k, v, g, beta):
    """Dispatch probe: the step as the kernel computes it, or None when
    the kernel cannot serve this call (CPU backend, kill switch, head
    sizes off the tile grid, VMEM overflow) or its shape class failed
    the compile+parity probe."""
    if not _platform_supported() or state.dtype != F32:
        return None
    S, dk, HV = state.shape
    H = q.shape[1]
    dv = HV // H
    key = (jnp.dtype(v.dtype).name, H, dk, dv)
    G = _group(dv)
    if not G or H % G or dk % 8:
        _record_decline(FAMILY, key, f"{H} heads of {dk} x {dv}: off the "
                                     "(8, 128) tile grid")
        return None
    est = vmem_bytes_estimate(H, dk, dv)
    if est > _vmem_limit():
        _record_decline(FAMILY, key,
                        f"needs ~{est >> 20} MiB VMEM > "
                        f"{_vmem_limit() >> 20} MiB ceiling")
        return None
    if not _probe_verdict(FAMILY, key, _eager_probe, (v.dtype, H, dk, dv)):
        return None
    try:
        return gdn_step(state, q, k, v, g, beta)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, key, f"staging at {state.shape}: "
                                     f"{type(e).__name__}: {e}")
        return None
