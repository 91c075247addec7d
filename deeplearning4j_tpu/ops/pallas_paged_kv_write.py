"""Pallas TPU paged KV write: one decode position per slot, written into
the donated page pools IN PLACE.

The decode step appends one K/V position per slot to the paged pools of
`serving/decode_engine.py` — K `(P+1, Hkv, hd, page)` indexed on its
LANE axis, V `(P+1, Hkv, page, hd)` on its sublane axis. As an XLA
scatter (`pool.at[pids, :, :, loff].set(k)`) that write costs two
whole-pool copies per pool per step on the TPU: the scatter wants the
scattered axis off the minor dimension, so the compiler transposes the
donated pool into the scatter's layout and, because the next consumer is
the paged-attention Mosaic call with its fixed row-major operand layout,
transposes it back (PERF.md, PR 26: `copy` was 47% of the serve cell's
device time). This kernel takes the layout choice away from the
compiler: the pools are `input_output_aliases` operands in the same
row-major layout the attention kernel reads, the grid runs over slots,
`pids`/`loff` ride as scalar-prefetch operands and the BlockSpec index
maps dereference them, and each grid step reads the tile that holds the
position, replaces one lane (K, scales) or one row (V) and writes it
back. K moves a whole `(Hkv, hd, page)` page tile per slot — a lane
cannot be addressed below the 128-wide tile — V only the sublane tile
of `32 / itemsize` rows that holds `loff`.

Semantics are the scatter's, with the engine's trash-page convention:
inactive lanes arrive redirected to page 0, several of them may collide
there, and what page 0 holds afterwards is unspecified (the pipeline may
fetch a tile before an earlier slot's write of the same tile has
landed). Every other page is written by at most one slot per call and
comes out bit-identical to the scatter's.

Dispatch rides the `ops/kernel_dispatch.py` contract under the family
name `paged_kv_write`: the probe compiles AND runs the kernel at the
exact shape class and checks it bit for bit against the scatter;
`DL4J_TPU_NO_PALLAS_PAGED_KV_WRITE` forces the scatter; CPU backends
never dispatch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.kernel_dispatch import (
    platform_supported as _kernels_dispatch,
    probe_verdict as _probe_verdict,
    record_decline as _record_decline,
    vmem_limit_bytes as _vmem_limit,
)

FAMILY = "paged_kv_write"  # this module's row in kernel_verdicts()


def scatter_kv_write(k_pool, v_pool, k_new, v_new, pids, loff,
                     k_scale=None, v_scale=None, k_scale_new=None,
                     v_scale_new=None) -> Tuple:
    """The portable XLA write the kernel replaces, and its reference
    numerics: one position per slot scattered into the pools. `k_new`/
    `v_new`: (S, Hkv, hd); `pids`/`loff`: (S,) pool page and in-page
    offset; int8 pools also take their f32 scale pools (P+1, Hkv, page)
    and the per-(slot, head) scales (S, Hkv). Returns the pools in the
    order given, scales (or None) last."""
    k_pool = k_pool.at[pids, :, :, loff].set(k_new)
    v_pool = v_pool.at[pids, :, loff, :].set(v_new)
    if k_scale is not None:
        k_scale = k_scale.at[pids, :, loff].set(k_scale_new)
        v_scale = v_scale.at[pids, :, loff].set(v_scale_new)
    return k_pool, v_pool, k_scale, v_scale


def _sublane_rows(dtype, page: int) -> int:
    """Rows of V's page axis one grid step moves: the dtype's sublane
    tile (8 rows of 32 bits, packed narrower types more), or the whole
    page where the tile does not divide it."""
    rows = 32 // jnp.dtype(dtype).itemsize
    return rows if page % rows == 0 else page


def _write_kernel(pid_ref, off_ref, kt_ref, vn_ref, *rest, Hkv: int,
                  rows: int, quantized: bool):
    """Grid (S,): slot `s` owns K page tile `pids[s]` and V's row tile
    `(pids[s], loff[s] // rows)`; both arrive in VMEM through the
    aliased pools' BlockSpecs and leave the same way. `kt_ref` holds the
    slot's new K as (hd, Hkv) — head_dim on sublanes, as a K page keeps
    it — so a head's column broadcasts along lanes without a relayout."""
    from jax.experimental import pallas as pl

    if quantized:
        (ksn_ref, vsn_ref, k_in, v_in, ks_in, vs_in,
         k_out, v_out, ks_out, vs_out) = rest
    else:
        k_in, v_in, k_out, v_out = rest
    off = off_ref[pl.program_id(0)]
    _, _, hd, page = k_in.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (hd, page), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
    r = off % rows
    # the selects run on 32-bit values: every pool dtype (bf16, f32,
    # int8) widens and narrows back exactly
    wide = jnp.int32 if k_in.dtype == jnp.int8 else jnp.float32
    kt = kt_ref[0].astype(wide)                            # (hd, Hkv)
    vn = vn_ref[0].astype(wide)                            # (Hkv, hd)
    for h in range(Hkv):
        k_out[0, h] = jnp.where(lane == off, kt[:, h:h + 1],
                                k_in[0, h].astype(wide)).astype(k_out.dtype)
        v_out[0, h] = jnp.where(row == r, vn[h:h + 1, :],
                                v_in[0, h].astype(wide)).astype(v_out.dtype)
    if quantized:
        lane_s = jax.lax.broadcasted_iota(jnp.int32, (Hkv, page), 1)
        ks_out[0] = jnp.where(lane_s == off, ksn_ref[0], ks_in[0])
        vs_out[0] = jnp.where(lane_s == off, vsn_ref[0], vs_in[0])


# jitted so that a step which writes 24 layers' pools traces and lowers
# the kernel once and calls it 24 times: un-jitted, the two decode
# programs' lowering grew by a third (PERF.md, PR 26), and lowering is
# paid on every start-up, compile cache or not
@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(k_pool, v_pool, k_new, v_new, pids, loff,
                   k_scale=None, v_scale=None, k_scale_new=None,
                   v_scale_new=None, *, interpret: bool = False) -> Tuple:
    """`scatter_kv_write` as one in-place kernel call (same arguments,
    same return). The pools are aliased input→output: under donation
    (or inside a loop's carry) no pool is copied, and tiles no slot
    names are never touched."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Hkv, hd = k_new.shape
    page = k_pool.shape[3]
    quantized = k_scale is not None
    rows = _sublane_rows(v_pool.dtype, page)
    kernel = functools.partial(_write_kernel, Hkv=Hkv, rows=rows,
                               quantized=quantized)

    def slot(s, pid, off):
        return (s, 0, 0)

    def k_tile(s, pid, off):
        return (pid[s], 0, 0, 0)

    def v_tile(s, pid, off):
        return (pid[s], 0, off[s] // rows, 0)

    def scale_tile(s, pid, off):
        return (pid[s], 0, 0)

    pool_specs = [pl.BlockSpec((1, Hkv, hd, page), k_tile),
                  pl.BlockSpec((1, Hkv, rows, hd), v_tile)]
    new_specs = [pl.BlockSpec((1, hd, Hkv), slot),
                 pl.BlockSpec((1, Hkv, hd), slot)]
    # (S, hd, Hkv): a tiny XLA transpose out here spares the kernel a
    # lane→sublane relayout per slot
    news = [jnp.swapaxes(k_new, 1, 2).astype(k_pool.dtype),
            v_new.astype(v_pool.dtype)]
    pools = [k_pool, v_pool]
    if quantized:
        pool_specs += [pl.BlockSpec((1, Hkv, page), scale_tile)] * 2
        new_specs += [pl.BlockSpec((1, Hkv, 1), slot)] * 2
        news += [k_scale_new.astype(k_scale.dtype)[..., None],
                 v_scale_new.astype(v_scale.dtype)[..., None]]
        pools += [k_scale, v_scale]
    n_prefetch = 2
    first_pool = n_prefetch + len(news)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(S,),
            in_specs=new_specs + pool_specs,
            out_specs=pool_specs),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={first_pool + i: i
                              for i in range(len(pools))},
        compiler_params=pltpu.CompilerParams(
            # sequential: colliding trash-page writes stay ordered
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(pids.astype(jnp.int32), loff.astype(jnp.int32), *news, *pools)
    return tuple(out) if quantized else (out[0], out[1], None, None)


def vmem_bytes_estimate(Hkv: int, hd: int, page: int, dtype,
                        quantized: bool = False) -> int:
    """Resident VMEM of one grid step: the K page tile and V row tile,
    each double-buffered on the way in and on the way out (plus the f32
    scale tiles of an int8 pool); the new values are noise beside it."""
    tiles = 4 * jnp.dtype(dtype).itemsize * Hkv * hd \
        * (page + _sublane_rows(dtype, page))
    if quantized:
        tiles += 4 * 4 * 2 * Hkv * page
    return tiles


def _platform_supported() -> bool:
    # the switch forces the scatter (A/B benches, tests)
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_PAGED_KV_WRITE")


def _eager_probe(dtype, Hkv: int, hd: int, page: int,
                 quantized: bool = False) -> bool:
    """Compile + run the kernel once at this exact shape class on small
    concrete pools, out of trace, and CHECK every page but the trash
    page against the scatter, bit for bit: first and last offset of a
    page, two inactive lanes colliding on page 0, then a second call at
    the next offsets over the first call's output (the aliased pool
    carried from step to step)."""
    import numpy as np

    S, P = 4, 4
    rng = np.random.default_rng(0)

    def draw(shape, dt):
        if dt == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(shape), dt)

    pools = [draw((P + 1, Hkv, hd, page), dtype),
             draw((P + 1, Hkv, page, hd), dtype)]
    if quantized:
        pools += [draw((P + 1, Hkv, page), jnp.float32),
                  draw((P + 1, Hkv, page), jnp.float32)]
    else:
        pools += [None, None]
    pids = jnp.asarray([3, 0, 1, 0], jnp.int32)
    got = want = tuple(pools)
    for loff in ([0, 5, page - 2, 7], [1, 6, page - 1, 8]):
        loff = jnp.asarray(loff, jnp.int32)
        new = [draw((S, Hkv, hd), dtype), draw((S, Hkv, hd), dtype)]
        scales = [draw((S, Hkv), jnp.float32),
                  draw((S, Hkv), jnp.float32)] if quantized else []
        got = paged_kv_write(*got[:2], *new, pids, loff, *got[2:], *scales)
        want = scatter_kv_write(*want[:2], *new, pids, loff, *want[2:],
                                *scales)
    for g, w in zip(got, want):
        if g is None:
            continue
        g, w = np.asarray(g[1:]), np.asarray(w[1:])
        if g.tobytes() != w.tobytes():
            raise ValueError(
                "kernel compiled but its pools differ from the "
                f"scatter's in {int(np.sum(g != w))} of {g.size} "
                "elements outside the trash page")
    return True


def paged_kv_write_or_none(k_pool, v_pool, k_new, v_new, pids, loff,
                           k_scale=None, v_scale=None, k_scale_new=None,
                           v_scale_new=None) -> Optional[Tuple]:
    """Dispatch probe: the written pools, or None when the kernel
    cannot serve this call — CPU backend, kill switch, pool dtypes that
    differ or that Mosaic does not tile, VMEM overflow — or when the
    shape class failed its compile+parity probe. Callers fall back to
    `scatter_kv_write`."""
    _, Hkv, hd, page = k_pool.shape
    dtype = k_pool.dtype
    quantized = k_scale is not None
    if not _platform_supported() or v_pool.dtype != dtype \
            or dtype not in (jnp.float32, jnp.bfloat16, jnp.int8) \
            or quantized != (dtype == jnp.int8):
        return None
    key = (jnp.dtype(dtype).name, Hkv, hd, page,
           "int8" if quantized else "dense")
    est = vmem_bytes_estimate(Hkv, hd, page, dtype, quantized)
    if est > _vmem_limit():
        _record_decline(FAMILY, key,
                        f"needs ~{est >> 20} MiB VMEM > "
                        f"{_vmem_limit() >> 20} MiB ceiling")
        return None
    if not _probe_verdict(FAMILY, key, _eager_probe,
                          (dtype, Hkv, hd, page, quantized)):
        return None
    try:
        return paged_kv_write(k_pool, v_pool, k_new, v_new, pids, loff,
                              k_scale, v_scale, k_scale_new, v_scale_new)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, key, f"staging at {k_pool.shape}: "
                                     f"{type(e).__name__}: {e}")
        return None
