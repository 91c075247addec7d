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
head by one select on the lane index. All arithmetic is float32 on the
vector unit: a slot with `b = 0, a = 1` keeps its state bit for bit, as
in the XLA form. How the small operands arrive differs by kernel:

`gdn_step` (one decay a head, `g` (S, H)) has XLA lay them out: the
per-head scalars `a`, `b` and the row `v` expanded to a group's lanes,
`(S, 3, H / group, group * d_v)` (3 x 23 KB a slot beside 2 x 2.2 MB of
state), and `k`, `q` as one transposed `(S, d_k, 2 H)` operand, so the
kernel needs no relayout. Its (30, 96) row tile lies off the (8, 128)
grid on both axes, and whether Mosaic would transpose it is not known.

`kda_step` (one decay a KEY CHANNEL, `g` (S, H, d_k):
`ops/delta_rule.py`) takes its operands AS THE MIXER HAS THEM, and its
entry makes nothing but `exp(g)`, elementwise on `g`'s own layout:

    S' = S Diag(a) + (b (v - S Diag(a) k)) k^T;   o = S' q

`k`, `q` and `a = exp(g)` go in as `(S, H, d_k)` float32, a `(1, H,
d_k)` block a slot; `v` `(S, H / group, group * d_v)` in its own dtype
(a reshape of nothing where one head is a group); `beta` `(S, H)`,
whole and resident, a slot's row read by its grid index. A key channel
is a ROW of the resident state tile, so the kernel wants `k`, `q` and
`a` as COLUMNS: once a slot it pads each `(H, d_k)` row tile to whole
128-row tiles and transposes it in VMEM (`_as_columns`; the XLU is idle
beside the state's DMA), then slices head h's column and broadcasts it
along its head's lanes exactly as the scalar kernel does. The tile is
decayed once (`m * a`), and the rest is the scalar kernel's arithmetic
on the decayed tile, in its order: the values are those of columns
made by XLA outside, which cost a layer three lane-padded transposes,
three transposing fusions and two concatenates every decode step
(`gdn_step`'s entry pays its share of them still). At 32 heads of 128
x 128 a slot's state is `(128, 4096)` float32, whole lane tiles with one
head a group, 2.1 MB in VMEM.

Each kernel is a static variant of its own, traced and probed under its
own jitted entry, so a program lowers each once and a device trace names
each.

Dispatch rides `ops/kernel_dispatch.py` under the family names
`gdn_step` and `kda_step`: the probe compiles and runs the kernel at the
exact shape class and holds it to `delta_step` (`kda_step`'s class ends
in `"rows"`, its operand form: `step_key`);
`DL4J_TPU_NO_PALLAS_GDN_STEP` forces the XLA form of both; CPU backends
never dispatch.
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

FAMILY = "gdn_step"  # this module's rows in kernel_verdicts(): one decay
KDA_FAMILY = "kda_step"  # a head, and one a key channel
F32 = jnp.float32


def _group(dv: int) -> int:
    """Heads handled together so that their value columns are whole
    lane tiles: 1 or 2; 0 where neither does."""
    return 1 if dv % 128 == 0 else 2 if (2 * dv) % 128 == 0 else 0


def _columns(cols, dk: int, dv: int, group: int):
    """`column(c, p)`: column(s) `c` of group `p`'s heads of the
    transposed operand `cols` (d_k, n H), each along its own head's
    lanes, (d_k, group * d_v)."""
    W = group * dv
    first = jax.lax.broadcasted_iota(jnp.int32, (dk, W), 1) < dv

    def column(c, p):
        if group == 1:
            return jnp.broadcast_to(cols[:, c + p:c + p + 1], (dk, W))
        return jnp.where(first, cols[:, c + 2 * p:c + 2 * p + 1],
                         cols[:, c + 2 * p + 1:c + 2 * p + 2])

    return column


def _step_kernel(kq_ref, vab_ref, s_ref, o_ref, s_out_ref, *, H: int,
                 dv: int, group: int):
    """Grid (S,): slot `s` owns its whole state tile. `kq_ref`
    (1, d_k, 2H): column h is head h's key, column H + h its query;
    `vab_ref` (1, 3, H / group, group * d_v): v, a, b by group row."""
    dk = s_ref.shape[1]
    W = group * dv
    column = _columns(kq_ref[0], dk, dv, group)

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


def _as_columns(rows):
    """A `(H, d_k)` row tile as columns `(d_k, H')`, made in VMEM: the
    rows padded to whole 128-row tiles and transposed once (column h is
    row h; the columns past H are never read)."""
    H, dk = rows.shape
    pad = -H % 128
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, dk), rows.dtype)],
                               axis=0)
    return rows.T


def _channel_kernel(k_ref, q_ref, a_ref, v_ref, b_ref, s_ref, o_ref,
                    s_out_ref, *, H: int, dv: int, group: int):
    """`_step_kernel` with a decay a key channel, its operands as the
    mixer has them: `k_ref`, `q_ref`, `a_ref` (1, H, d_k) the slot's
    keys, queries and decays `exp(g)` by head ROW, turned into columns
    here; `v_ref` (1, H / group, group * d_v) in its own dtype; `b_ref`
    (S, H), every slot's beta, resident."""
    from jax.experimental import pallas as pl

    dk = s_ref.shape[1]
    W = group * dv
    key, query, decay = (_columns(_as_columns(r[0]), dk, dv, group)
                         for r in (k_ref, q_ref, a_ref))
    beta = _columns(b_ref[pl.ds(pl.program_id(0), 1), :], 1, dv, group)

    for p in range(H // group):
        m = s_ref[0, :, p * W:(p + 1) * W] * decay(0, p)       # (dk, W)
        kx, qx = key(0, p), query(0, p)
        v = v_ref[0, p:p + 1, :].astype(F32)                   # (1, W)
        sk = jnp.sum(m * kx, axis=0, keepdims=True)
        m = m + kx * (beta(0, p) * (v - sk))
        s_out_ref[0, :, p * W:(p + 1) * W] = m
        o_ref[0, p:p + 1, :] = jnp.sum(m * qx, axis=0, keepdims=True)


def _call(kernel, state, operands, specs, H: int, interpret: bool):
    """One grid step a slot over the aliased state: `operands` the
    kernel's small operands in its order, `specs` the block of each.
    Returns (o (S, H, d_v) float32, state)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, dk, HV = state.shape
    dv = HV // H
    G = _group(dv)
    P, W = H // G, G * dv
    o, state = pl.pallas_call(
        functools.partial(kernel, H=H, dv=dv, group=G),
        grid=(S,),
        in_specs=[*specs, pl.BlockSpec((1, dk, HV), lambda s: (s, 0, 0))],
        out_specs=[pl.BlockSpec((1, P, W), lambda s: (s, 0, 0)),
                   pl.BlockSpec((1, dk, HV), lambda s: (s, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, P, W), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={len(specs): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(*operands, state)
    return o.reshape(S, H, dv), state


def _slot_block(x):
    """The block of one slot of a per-slot operand `x` (S, ...)."""
    from jax.experimental import pallas as pl

    zeros = (0,) * (x.ndim - 1)
    return pl.BlockSpec((1, *x.shape[1:]), lambda s: (s, *zeros))


# jitted so that a step over many layers traces and lowers the kernel
# once and calls it once a layer (`pallas_paged_kv_write`'s lesson)
@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_step(state, q, k, v, g, beta, *, interpret: bool = False):
    """`delta_step` with one decay a head (`g` (S, H)) as one in-place
    kernel call (same arguments, same return)."""
    S, H = q.shape[:2]
    dv = state.shape[2] // H
    shape = (S, H // _group(dv), _group(dv) * dv)
    cols = jnp.concatenate([jnp.swapaxes(x.astype(F32), 1, 2)
                            for x in (k, q)], axis=2)
    rows = jnp.stack(
        [v.astype(F32).reshape(shape)]
        + [jnp.repeat(x.astype(F32), dv, axis=1).reshape(shape)
           for x in (jnp.exp(g.astype(F32)), beta)], axis=1)
    o, state = _call(_step_kernel, state, (cols, rows),
                     (_slot_block(cols), _slot_block(rows)), H, interpret)
    return o.astype(v.dtype), state


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(state, q, k, v, g, beta, *, interpret: bool = False):
    """`delta_step` with one decay a key channel (`g` (S, H, d_k)) as
    one in-place kernel call (same arguments, same return). The operands
    go to the kernel as the mixer has them; only `exp(g)` is made here,
    elementwise on `g`'s own layout."""
    from jax.experimental import pallas as pl

    S, H = q.shape[:2]
    dv = state.shape[2] // H
    G = _group(dv)
    rows = (k.astype(F32), q.astype(F32), jnp.exp(g.astype(F32)),
            v.reshape(S, H // G, G * dv))
    beta = beta.astype(F32)
    o, state = _call(
        _channel_kernel, state, (*rows, beta),
        (*map(_slot_block, rows), pl.BlockSpec((S, H), lambda s: (0, 0))),
        H, interpret)
    return o.astype(v.dtype), state


def vmem_bytes_estimate(H: int, dk: int, dv: int) -> int:
    """Resident VMEM of one grid step: the slot's state tile, double-
    buffered on the way in and on the way out; the small operands are
    noise beside it."""
    return 4 * 4 * dk * H * dv


def _platform_supported() -> bool:
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_GDN_STEP")


def _eager_probe(dtype, H: int, dk: int, dv: int,
                 channels: bool = False) -> bool:
    """Compile and run the kernel at this shape class (three slots, one
    of them inactive) and hold it to `delta_step`; `channels`: the
    kernel with a decay a key channel."""
    import numpy as np

    rng = np.random.default_rng(0)
    S = 3
    state = jnp.asarray(rng.standard_normal((S, dk, H * dv)), F32)
    q, k = (jnp.asarray(rng.standard_normal((S, H, dk)) / dk ** 0.5, dtype)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((S, H, dv)), dtype)
    live = np.array([1.0, 1.0, 0.0])[:, None]
    beta = jnp.asarray(2.0 * rng.random((S, H)) * live, F32)
    if channels:
        g = jnp.asarray(-5.0 * rng.random((S, H, dk)) * live[..., None], F32)
    else:
        g = jnp.asarray(-rng.random((S, H)) * live, F32)
    want_o, want_s = delta_step(state, q, k, v, g, beta)
    got_o, got_s = (kda_step if channels else gdn_step)(
        state + 0.0, q, k, v, g, beta)
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


def step_key(dtype, H: int, dk: int, dv: int, channels: bool) -> tuple:
    """The shape class a verdict of this module is kept under.
    `kda_step`'s names its operand form, head rows turned to columns in
    the kernel, so a verdict probed on the transposed form of before is
    told from this one's."""
    key = (jnp.dtype(dtype).name, H, dk, dv)
    return key + ("rows",) if channels else key


def delta_step_or_none(state, q, k, v, g, beta):
    """Dispatch probe: the step through the kernel of `g`'s shape
    (`gdn_step` for (S, H), `kda_step` for (S, H, d_k)), or None when it
    cannot serve this call (CPU backend, kill switch, head sizes off the
    tile grid, VMEM overflow) or its shape class failed the
    compile+parity probe."""
    if not _platform_supported() or state.dtype != F32:
        return None
    channels = g.ndim == 3
    family = KDA_FAMILY if channels else FAMILY
    S, dk, HV = state.shape
    H = q.shape[1]
    dv = HV // H
    key = step_key(v.dtype, H, dk, dv, channels)
    G = _group(dv)
    if not G or H % G or dk % 8:
        _record_decline(family, key, f"{H} heads of {dk} x {dv}: off the "
                                     "(8, 128) tile grid")
        return None
    est = vmem_bytes_estimate(H, dk, dv)
    if est > _vmem_limit():
        _record_decline(family, key,
                        f"needs ~{est >> 20} MiB VMEM > "
                        f"{_vmem_limit() >> 20} MiB ceiling")
        return None
    if not _probe_verdict(family, key, _eager_probe,
                          (v.dtype, H, dk, dv, channels)):
        return None
    try:
        return (kda_step if channels else gdn_step)(state, q, k, v, g, beta)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(family, key, f"staging at {state.shape}: "
                                     f"{type(e).__name__}: {e}")
        return None
