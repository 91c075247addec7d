"""Pallas TPU grouped expert product: the FFNs of the experts a chip
holds, summed under the router's gates, in the two variants
`parallel/experts.py` names:

    gated_silu  y = sum_e gates[:, e] * (silu(x Wg[e]) * (x Wu[e])) Wd[e]
    relu2       y = sum_e gates[:, e] * relu(x Wu[e]^T)^2 Wd[e]

`x` (N, d) tokens, `gates` (N, E) float32 (zero where the router did
not choose expert e), `Wd` (E, f, d); gated: `Wg`/`Wu` (E, d, f);
ungated: no `Wg`, and `Wu` (E, f, d) like `Wd`, so that the first
product contracts over `d` on both operands and `f` lies on sublanes in
both matrices: an expert width off the 128-lane grid (1856 = 232 x 8)
then costs no padding, where a (d, 1856) block compiles too but makes
XLA copy the whole stack into a lane-padded layout before every call
(my sandbox compile, PR 36: 0.66 GB of temporaries at 64 experts of
2688 x 1856). The variant is static: one kernel body a variant, and the
probe's key and `kernel_verdicts()` row name it. The grid runs
over (token tiles, experts): one grid step brings one whole expert
(its three, or two, (d, f)-sized matrices) into VMEM, while the token tile and a
float32 accumulator stay resident, so an expert's weights cross HBM once
per token tile.

An expert too large for VMEM (three 6144 x 2048 bfloat16 matrices are
75.5 MB, 151 MB double-buffered, over the 112 MiB ceiling) is brought in
TILES of its width `f`: a third grid axis, innermost, over `f / tf`
column tiles of `Wg` / `Wu` and row tiles of `Wd` (the gated product is
a sum over `f`, so each tile adds its part to the same accumulator).
`f_tile` picks `tf`: all of `f` where one whole expert fits, as every
shape did before there were tiles, and the program is then the
two-axis one it always was; else the largest divisor of `f` on the
tile grid that fits.

The walk is hit-first. `hit` (E,) bool says which held experts some row
that matters chose (`parallel.experts.dropless_moe`: a gate that is not
zero on a live row). A Pallas TPU grid is static, so the expert axis
keeps its `E` steps, but the block indices of the weights and of the
gate column come from a scalar-prefetched vector: step `j` names the
`j`-th hit expert in the experts' own order, and every step past the
last hit one names that one again (and its last tile), which copies
nothing (a block whose index is the previous step's stays where it is)
and computes nothing.
So the kernel moves the matrices of the experts that were hit and no
others: the least a decode step can move, whether every held expert is
chosen (64 slots x top-10 of 72: every step) or a fifth of them are not
(64 slots x top-6 of 128 with tokens that choose alike). An expert left
out added exactly 0.0 to every row that matters, and the hit ones are
summed in the order they always were: those rows come out bit for bit
as from a walk over all `E`. A row that does not matter (an inactive
slot's) loses what the skipped experts would have added to it.

Every token meets every hit expert (the gate weighs the result), so the
kernel does as many times the multiply-adds of a sorted grouped product
as experts are hit for each one a token chose: at decode sizes the
weights' bytes bound it, not the MXU. As XLA batched einsums the same product materialises the
(E, N, f) intermediates in HBM.

A prefill is the other case: every held expert is hit and each token
chose few of them, so the walk above runs every token through every
expert, compute-bound from about 256 rows. `moe_experts_sorted` takes
the (token, expert) choices SORTED by expert instead, each expert's rows
padded to whole tiles of `SORTED_ROWS`: a grid over row tiles, a
scalar-prefetched vector naming each tile's expert (consecutive tiles of
one expert bring its weights once), nothing computed or copied past the
last used tile; both variants, an expert whole or in tiles of `f` (a
second, innermost axis adding into the float32 output tile). The
multiply-adds are those of the choices made; `parallel.experts` sorts,
gathers and adds a token's float32 rows back. `sorted_serves` says from
the shapes alone which product a block's rows take, `sorted_bound` how
many sorted rows the program holds.

Dispatch rides `ops/kernel_dispatch.py` under the family name
`moe_experts`: the probe compiles and runs the kernel at the exact shape
class and checks it against `parallel.experts.grouped_expert_ffn_xla`;
`DL4J_TPU_NO_PALLAS_MOE_EXPERTS` forces the XLA products; CPU backends
never dispatch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.kernel_dispatch import (
    platform_supported as _kernels_dispatch,
    probe_verdict as _probe_verdict,
    record_decline as _record_decline,
    vmem_limit_bytes as _vmem_limit,
)

FAMILY = "moe_experts"  # this module's row in kernel_verdicts()
GATED_SILU, RELU2 = "gated_silu", "relu2"  # the experts' activations
_MAX_ROWS = 512         # token rows per tile


def _hidden(x_ref, g_ref, w_refs, act: str):
    """A row tile's gated hidden activations over what `w_refs` hold of
    ONE expert's width `f` (all of it, or a tile), weighed by the rows'
    gates `g_ref` (1, rows, 1): (rows, f) float32, for the caller's
    product with the expert's `Wd`. The one copy of the experts'
    arithmetic: the walk and the sorted product differ in which rows
    meet which expert."""
    x = x_ref[...]
    if act == RELU2:
        wu_ref, _ = w_refs
        u = jax.lax.dot_general(x, wu_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        u = jnp.maximum(u, 0.0)
        return u * u * g_ref[0]
    wg_ref, wu_ref, _ = w_refs
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    return g * jax.nn.sigmoid(g) * u * g_ref[0]


def _experts_kernel(walk_ref, n_hit_ref, x_ref, g_ref, *refs, act: str,
                    tiled: bool):
    from jax.experimental import pallas as pl

    del walk_ref  # the index maps read it
    *w_refs, o_ref, acc_ref = refs
    e = pl.program_id(1)
    first = e == 0
    if tiled:
        first &= pl.program_id(2) == 0

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e < n_hit_ref[0])
    def _():
        h = _hidden(x_ref, g_ref, w_refs, act)
        acc_ref[...] += jnp.dot(h.astype(x_ref.dtype), w_refs[-1][0],
                                preferred_element_type=jnp.float32)

    last = e == pl.num_programs(1) - 1
    if tiled:
        last &= pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(last)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _row_tile(n: int) -> int:
    """Rows per token tile: all of them up to `_MAX_ROWS`, else the
    largest multiple of 8 that divides `n` and fits; 0 if none does."""
    if n <= _MAX_ROWS:
        return n
    for t in range(_MAX_ROWS, 7, -8):
        if n % t == 0:
            return t
    return 0


def hit_first_walk(hit):
    """`hit` (E,) bool -> (walk (E,) int32, n_hit (1,) int32): `walk[j]`
    is the `j`-th hit expert in the experts' own order, and past the
    last hit one that one again (with none hit, expert E - 1 all
    along). Two (E, E) compare-and-count reductions: no sort, no
    scatter, no gather; int32 under x64 too (Mosaic takes 32-bit block
    indices only)."""
    E = hit.shape[0]
    e = jnp.arange(E, dtype=jnp.int32)
    # hit experts among 0..e
    upto = jnp.sum(hit[None, :] & (e[None, :] <= e[:, None]), axis=1,
                   dtype=jnp.int32)
    n_hit = upto[-1:]
    # step j wants the expert at which that count first reaches j + 1:
    # as many experts as lie before it; counted over the first E - 1,
    # which is all that can, and keeps the index inside E when none does
    want = jnp.minimum(e + 1, jnp.maximum(n_hit, 1))
    walk = jnp.sum(upto[None, :-1] < want[:, None], axis=1, dtype=jnp.int32)
    return walk, n_hit


def _grid_specs(N: int, d: int, E: int, f: int, tn: int, tf: int, act: str):
    """(grid, in_specs, out_spec) of the walk: two axes (token tiles,
    experts) where one tile is the whole width, and a third, innermost,
    over the `f // tf` tiles where it is not."""
    from jax.experimental import pallas as pl

    nf = f // tf
    if nf > 1:
        # past the last hit expert its last tile again: nothing moves
        def held(e, j, n_hit):
            return jnp.where(e < n_hit[0], j, nf - 1)

        rows = lambda *shape: pl.BlockSpec(       # a tile of f on rows
            (1,) + shape, lambda n, e, j, walk, n_hit:
            (walk[e], held(e, j, n_hit), 0))
        cols = lambda *shape: pl.BlockSpec(       # a tile of f on lanes
            (1,) + shape, lambda n, e, j, walk, n_hit:
            (walk[e], 0, held(e, j, n_hit)))
        tile = pl.BlockSpec((tn, d), lambda n, e, j, walk, n_hit: (n, 0))
        # (E, N, 1): one expert's gate column arrives as a (tn, 1) block
        gate = pl.BlockSpec((1, tn, 1), lambda n, e, j, walk, n_hit:
                            (walk[e], n, 0))
    else:
        rows = cols = lambda *shape: pl.BlockSpec(
            (1,) + shape, lambda n, e, walk, n_hit: (walk[e], 0, 0))
        tile = pl.BlockSpec((tn, d), lambda n, e, walk, n_hit: (n, 0))
        gate = pl.BlockSpec((1, tn, 1), lambda n, e, walk, n_hit:
                            (walk[e], n, 0))
    specs = [rows(tf, d), rows(tf, d)] if act == RELU2 \
        else [cols(d, tf), cols(d, tf), rows(tf, d)]
    return (N // tn, E) + ((nf,) if nf > 1 else ()), \
        [tile, gate, *specs], tile


# jitted so that a step over many layers traces and lowers the kernel
# once and calls it once a layer (`pallas_paged_kv_write`'s lesson)
@functools.partial(jax.jit, static_argnames=("act", "interpret", "tf"))
def moe_experts(x, gates, Wg, Wu, Wd, hit, *, act: str = GATED_SILU,
                interpret: bool = False, tf: int = 0):
    """The grouped product over the experts `hit` (E,) bool marks; the
    caller marks every expert whose gate is not zero on a row it will
    read. `tf`: the tile of the experts' width `f` one grid step brings
    (0: `f_tile`'s choice)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, d = x.shape
    E, f, _ = Wd.shape
    tn = _row_tile(N)
    tf = tf or f_tile(tn, d, f, x.dtype, act) or f
    grid, in_specs, out_spec = _grid_specs(N, d, E, f, tn, tf, act)
    g3 = jnp.swapaxes(gates.astype(jnp.float32), 0, 1)[..., None]
    return pl.pallas_call(
        functools.partial(_experts_kernel, act=act, tiled=len(grid) == 3),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((tn, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
            + ("arbitrary",) * (len(grid) - 1),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(*hit_first_walk(hit), x, g3,
      *((Wu, Wd) if act == RELU2 else (Wg, Wu, Wd)))


SORTED_ROWS = 128       # rows a tile of the sorted product holds
# What a row of the sorted product costs, its experts' half-empty last
# tiles counted, in rows of the walk: the walk multiplies tiles of 512
# rows near the MXU's peak (155-192 TFLOP/s); the sorted product's tiles
# of 128 rows are bound by their expert's bytes, and the sort, the gather
# of the rows and the add-back ride on it. The largest that the
# benchmark's shapes read (3.1 nemotron's, 3.3 granite's, 4.1-4.7
# DeepSeek-V2's, 4.7-4.8 LongCat's: `tools/moe_experts_bench.py`, PERF.md
# section 6).
SORTED_ROW_COST = 4.8
# How far over their mean the static size of the sorted rows reaches, in
# standard deviations of independent choices (`sorted_bound`).
SORTED_MARGIN = 6.0


def _sorted_kernel(expert_ref, n_used_ref, x_ref, g_ref, *refs, act: str,
                   tiled: bool):
    from jax.experimental import pallas as pl

    del expert_ref  # the index maps read it
    *w_refs, o_ref = refs
    t, n_used = pl.program_id(0), n_used_ref[0]
    j = pl.program_id(1) if tiled else 0

    @pl.when(t < n_used)
    def _():
        h = _hidden(x_ref, g_ref, w_refs, act)
        part = jnp.dot(h.astype(x_ref.dtype), w_refs[-1][0],
                       preferred_element_type=jnp.float32)
        if not tiled:
            o_ref[...] = part
            return

        @pl.when(j == 0)
        def _():
            o_ref[...] = part

        @pl.when(j > 0)
        def _():
            o_ref[...] += part

    # every tile past the used ones is the LAST tile (the index maps):
    # zeroed once, it stays where it is and is written back once
    @pl.when((t == n_used) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("act", "interpret", "tf"))
def moe_experts_sorted(xs, gs, tile_expert, n_used, Wg, Wu, Wd, *,
                       act: str = GATED_SILU, interpret: bool = False,
                       tf: int = 0):
    """The experts' product over rows sorted by expert: `xs` (M, d),
    tile `t` (rows `t * SORTED_ROWS` on) all of expert `tile_expert[t]`
    (int32, (M / SORTED_ROWS,)), `gs` (M, 1) float32 each row's gate (0
    on a padding row), `n_used` (1,) int32 the tiles that hold rows,
    fewer than there are (`sort_by_expert` keeps the last one free);
    the matrices as `moe_experts` takes them for `act`. Returns (M, d)
    FLOAT32, so that a token's rows are rounded once, after their sum:
    the used tiles' rows, zeros in the LAST tile, and nothing defined in
    the unused tiles before it: those steps name the last used tile's
    expert (and its last tile of `f`) and the last tile's rows, so that
    nothing is copied in or out for them. `tf`: the tile of `f` a grid
    step brings (0: `f_tile`'s choice at `SORTED_ROWS` rows): all of `f`
    is the one-axis program, less a second, innermost axis over `f / tf`
    tiles that add into the output tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, d = xs.shape
    _, f, _ = Wd.shape
    tn = SORTED_ROWS
    T = M // tn
    tf = tf or f_tile(tn, d, f, xs.dtype, act) or f
    nf = f // tf

    def at(t, rest):
        """(expert, tile of f, row tile) of grid step `t` (and `j`, the
        first of `rest` under two axes; then the prefetched vectors):
        past the used tiles the last used tile's expert, its last tile
        of `f`, and the LAST row tile."""
        expert, n_used = rest[-2:]
        used = t < n_used[0]
        return expert[t], jnp.where(used, rest[0] if nf > 1 else 0, nf - 1), \
            jnp.where(used, t, T - 1)

    rows = lambda *shape: pl.BlockSpec(           # a tile of f on rows
        (1,) + shape, lambda t, *rest: (*at(t, rest)[:2], 0))
    cols = lambda *shape: pl.BlockSpec(           # a tile of f on lanes
        (1,) + shape, lambda t, *rest: (at(t, rest)[0], 0, at(t, rest)[1]))
    tile = pl.BlockSpec((tn, d), lambda t, *rest: (at(t, rest)[2], 0))
    gate = pl.BlockSpec((1, tn, 1), lambda t, *rest: (0, at(t, rest)[2], 0))
    specs = [rows(tf, d), rows(tf, d)] if act == RELU2 \
        else [cols(d, tf), cols(d, tf), rows(tf, d)]
    grid = (T,) + ((nf,) if nf > 1 else ())
    return pl.pallas_call(
        functools.partial(_sorted_kernel, act=act, tiled=nf > 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[tile, gate, *specs], out_specs=tile),
        out_shape=jax.ShapeDtypeStruct((M, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(tile_expert, n_used, xs, gs.astype(jnp.float32)[None],
      *((Wu, Wd) if act == RELU2 else (Wg, Wu, Wd)))


def sorted_serves(N: int, E: int, k: int, router_width: int) -> bool:
    """Whether `N` rows that each chose `k` of the `router_width`
    experts the router scores, `E` of them held here, are cheaper sorted
    by expert than walked. Arithmetic on two row counts an expert: the
    walk multiplies all `N` rows with it; the sorted product the
    `N * k / router_width` expected to choose it and half a tile of
    padding, each at `SORTED_ROW_COST` rows of the walk. Half a tile at
    that cost is 307 rows: a decode step's rows and every bucket at or
    under the 256 from which a walk is compute-bound (2 operations a
    weight element a row against its 2 bytes: 197e12 / 819e9 = 240 rows)
    keep the walk and its program; under it an expert costs its bytes,
    which the sorted product reads too."""
    if not k or not E:
        return False
    chosen = N * min(k, E) / max(router_width, E)
    return SORTED_ROW_COST * (chosen + SORTED_ROWS / 2) < N


def sorted_worst(N: int, E: int, k: int) -> tuple:
    """(tiles, choices a row) of the sorted rows if every choice falls
    on a held expert: no routing overflows it."""
    return -(-N * k // SORTED_ROWS) + E, k


def sorted_bound(N: int, E: int, k: int, router_width: int) -> tuple:
    """(tiles, choices a row): the static size of the sorted rows
    (`parallel.experts.sort_by_expert`). The worst case
    (`sorted_worst`: `N * k` rows in whole tiles and a tile an expert,
    `k` choices a row) costs `k / E` of the walk's rows at the slower
    rate, with copies of `N * k` rows around the kernel. Where the router
    is so much wider than the share held that independent choices,
    `SORTED_MARGIN` standard deviations over their mean, number under
    half of `k` a row, that and the rows they fill: the add-back, the
    larger copy, shrinks with `k` and the gather of the rows with the
    tiles, and the caller keeps the walk as the other branch of a
    `lax.cond` for the routing that does not fit (tokens that choose
    alike more than chance)."""
    worst = sorted_worst(N, E, k)
    p = min(1.0, E / max(router_width, E))
    spread = lambda n: SORTED_MARGIN * (n * p * (1.0 - p)) ** 0.5
    kk = min(k, math.ceil(k * p + spread(k)))
    tiles = math.ceil((N * k * p + spread(N * k)) / SORTED_ROWS) + E + 1
    # in eights: neighbouring buckets then share one program of the kernel
    tiles = min(worst[0], -(-tiles // 8) * 8)
    return (tiles, kk) if 2 * kk <= k else worst


def vmem_bytes_estimate(tn: int, d: int, f: int, dtype,
                        act: str = GATED_SILU) -> int:
    """Resident VMEM of one grid step that brings `f` of an expert's
    width (all of it, or one tile): the expert's matrices (three, or two
    ungated), double-buffered; the token tile and the output tile,
    double-buffered; the float32 accumulator and the (tn, f) float32
    intermediates."""
    item = jnp.dtype(dtype).itemsize
    n_mat = 2 if act == RELU2 else 3
    return 2 * n_mat * d * f * item + 4 * tn * d * item + 4 * tn * d \
        + n_mat * 4 * tn * f


def _f_grid(dtype, act: str) -> int:
    """`f` lies on lanes in the gated variant's (d, f) matrices and on
    sublanes (16 rows a bf16 tile) in the ungated one's (f, d)."""
    return 128 if act != RELU2 else 32 // jnp.dtype(dtype).itemsize


def f_tile(tn: int, d: int, f: int, dtype, act: str = GATED_SILU) -> int:
    """The tile of `f` one grid step brings: `f` itself where a whole
    expert fits under the VMEM ceiling (one tile: the two-axis program),
    else the largest divisor of `f` on the tile grid that fits; 0 where
    none does."""
    limit, grid = _vmem_limit(), _f_grid(dtype, act)
    if vmem_bytes_estimate(tn, d, f, dtype, act) <= limit:
        return f
    for n in range(2, f // grid + 1):
        if f % n == 0 and (f // n) % grid == 0 and \
                vmem_bytes_estimate(tn, d, f // n, dtype, act) <= limit:
            return f // n
    return 0


def _platform_supported() -> bool:
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_MOE_EXPERTS")


@functools.partial(jax.jit, static_argnames=("E", "rows", "fan", "shift",
                                             "dtype"))
def _rotations(base, *, E: int, rows: int, fan: int, shift: int, dtype):
    m = base.reshape(rows, -1) * fan ** -0.5
    return jnp.stack([jnp.roll(m, (e + 1) * shift, axis=0)
                      for e in range(E)]).astype(dtype)


def _probe_experts(rng, E: int, d: int, f: int, dtype, act: str):
    """(Wg or None, Wu, Wd) of `E` probe experts in the variant's
    layouts, entries of variance 1 / fan-in: ONE uniform float32 draw of
    an expert's size, each matrix a rotation of it by rows made on the
    device (three 75 MB experts drawn entry by entry in float64 on the
    host cost a start-up seconds)."""
    import numpy as np

    base = jnp.asarray((rng.random(d * f, dtype=np.float32) - 0.5)
                       * 12.0 ** 0.5)
    dtype = jnp.dtype(dtype)
    up = f if act == RELU2 else d
    stack = lambda rows, fan, shift: _rotations(
        base, E=E, rows=rows, fan=fan, shift=shift, dtype=dtype)
    return None if act == RELU2 else stack(up, d, 3), stack(up, d, 5), \
        stack(f, f, 7)


def _eager_probe(dtype, tn: int, d: int, f: int, act: str) -> bool:
    """Compile and run the kernel at this shape class (two experts, one
    of them chosen by no token) and hold it to the XLA products."""
    import numpy as np

    from deeplearning4j_tpu.parallel.experts import grouped_expert_ffn_xla

    rng = np.random.default_rng(0)
    E = 2
    x = jnp.asarray(rng.standard_normal((tn, d)), dtype)
    Wg, Wu, Wd = _probe_experts(rng, E, d, f, dtype, act)
    gates = jnp.asarray(np.stack([rng.random(tn), np.zeros(tn)], 1),
                        jnp.float32)
    got = np.asarray(moe_experts(x, gates, Wg, Wu, Wd,
                                 jnp.any(gates != 0, axis=0), act=act),
                     np.float32)
    want = np.asarray(grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd, act),
                      np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    err = float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))
    if not np.isfinite(err) or err > tol:
        raise ValueError("kernel compiled but lies %.3g of the largest "
                         "output from the XLA products" % err)
    return True


def moe_experts_or_none(x, gates, Wg, Wu, Wd, hit, act: str = GATED_SILU):
    """Dispatch probe: the grouped product, or None when the kernel
    cannot serve this call (CPU backend, kill switch, a dtype Mosaic
    does not tile, a width off the tile grid of the axis it lies on,
    VMEM overflow) or its shape class failed the compile+parity probe.
    The gated variant's key is `(dtype, rows, d, f)` as it always was;
    the ungated one's ends in its name. An expert that does not fit
    whole is brought in tiles of `f` (`f_tile`)."""
    N, d = x.shape
    E, f, _ = Wd.shape
    dtype = x.dtype
    if not _platform_supported() or Wu.dtype != dtype \
            or dtype not in (jnp.float32, jnp.bfloat16):
        return None
    tn = _row_tile(N)
    key = (jnp.dtype(dtype).name, tn, d, f)
    if act == RELU2:
        key += (RELU2,)
    if not tn or tn % 8 or d % 128 or f % _f_grid(dtype, act):
        _record_decline(FAMILY, key, f"{N} rows, widths {d} x {f}: off "
                                     "the (8, 128) tile grid")
        return None
    if not f_tile(tn, d, f, dtype, act):
        est = vmem_bytes_estimate(tn, d, _f_grid(dtype, act), dtype, act)
        _record_decline(FAMILY, key,
                        f"needs ~{est >> 20} MiB VMEM at the smallest "
                        f"tile of f > {_vmem_limit() >> 20} MiB ceiling")
        return None
    if not _probe_verdict(FAMILY, key, _eager_probe,
                          (dtype, tn, d, f, act)):
        return None
    try:
        return moe_experts(x, gates, Wg, Wu, Wd, hit, act=act)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, key, f"staging at {x.shape}: "
                                     f"{type(e).__name__}: {e}")
        return None


def sorted_key(dtype, d: int, f: int, act: str = GATED_SILU) -> tuple:
    """The sorted product's row in `kernel_verdicts()`: the gated
    variant's ends in "sorted", the ungated one's in ("relu2",
    "sorted")."""
    return (jnp.dtype(dtype).name, SORTED_ROWS, d, f) \
        + ((RELU2,) if act == RELU2 else ()) + ("sorted",)


def _sorted_probe(dtype, d: int, f: int, act: str) -> bool:
    """Compile and run the sorted kernel at this shape class (three
    experts: one of two tiles, one chosen by no row, one of one tile;
    then an unused tile, the last) and hold it to the XLA products."""
    import numpy as np

    from deeplearning4j_tpu.parallel.experts import grouped_expert_ffn_xla

    rng = np.random.default_rng(0)
    E, tn = 3, SORTED_ROWS
    tile_expert = jnp.asarray([0, 0, 2, 2], jnp.int32)
    xs = jnp.asarray(rng.standard_normal((4 * tn, d)), dtype)
    Wg, Wu, Wd = _probe_experts(rng, E, d, f, dtype, act)
    gs = jnp.asarray(rng.random((4 * tn, 1)), jnp.float32)
    got = np.asarray(moe_experts_sorted(
        xs, gs, tile_expert, jnp.asarray([3], jnp.int32), Wg, Wu, Wd,
        act=act))
    gates = jnp.zeros((4 * tn, E), jnp.float32) \
        .at[:2 * tn, 0].set(gs[:2 * tn, 0]) \
        .at[2 * tn:3 * tn, 2].set(gs[2 * tn:3 * tn, 0])
    want = np.asarray(grouped_expert_ffn_xla(xs, gates, Wg, Wu, Wd, act),
                      np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    err = float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))
    if not np.isfinite(err) or err > tol or np.any(got[3 * tn:]):
        raise ValueError("sorted kernel compiled but lies %.3g of the "
                         "largest output from the XLA products" % err)
    return True


def moe_experts_sorted_or_none(xs, gs, tile_expert, n_used, Wg, Wu, Wd,
                               act: str = GATED_SILU):
    """Dispatch probe of the sorted product: its rows in float32, or
    None when the kernel cannot serve (CPU backend, kill switch, a dtype
    or widths off the tile grid, no tile of `f` that fits VMEM beside a
    row tile) or its shape class failed the probe (`sorted_key`)."""
    M, d = xs.shape
    _, f, _ = Wd.shape
    dtype = xs.dtype
    if not _platform_supported() or Wu.dtype != dtype \
            or dtype not in (jnp.float32, jnp.bfloat16):
        return None
    key = sorted_key(dtype, d, f, act)
    if d % 128 or f % _f_grid(dtype, act) or M % SORTED_ROWS or \
            not f_tile(SORTED_ROWS, d, f, dtype, act):
        _record_decline(FAMILY, key, f"widths {d} x {f}: off the tile "
                                     "grid, or no tile of f fits VMEM")
        return None
    if not _probe_verdict(FAMILY, key, _sorted_probe, (dtype, d, f, act)):
        return None
    try:
        return moe_experts_sorted(xs, gs, tile_expert, n_used, Wg, Wu, Wd,
                                  act=act)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, key, f"staging at {xs.shape}: "
                                     f"{type(e).__name__}: {e}")
        return None
