"""Pallas TPU paged latent attention: the absorbed decode step of
multi-head latent attention (`nn/conf/decoder_block.LatentAttentionMixer`)
over a paged pool of latents, and the pool's one-position write.

A position's cache is ONE vector for all heads, `[c | k_r]`: the normed
key/value latent (`kv_rank`, 512) and the turned rope key (`rope`, 64).
A sub-layer's pool is `(P+1, kv_rank + rope, page)`: the latent's 576
numbers on sublanes (36 bfloat16 tiles of 16 rows: none of it padding,
where a `(page, 576)` page would be stored 640 lanes wide), the page's
positions on lanes, page 0 the trash page, allocated and named by the
same page table as every other paged pool. The existing
`pallas_paged_attention` kernel has separate K and V pools of one head
size: through it the latent would be stored and read twice.

`mla_attend`: grid over slots; for each slot a loop over its LIVE pages
(`pos // page + 1` of them, whatever the table's width), a BLOCK of them
an iteration (`block_pages`: `BLOCK_POSITIONS` positions, eight pages of
128). A block's live pages are copied from HBM, once each, into the lane
ranges of one of two VMEM block buffers while the block before it is
computed on, and the block is used twice: all `H` heads' absorbed
queries `(H, 576)` against the whole block for the scores, and the
probabilities against its first `kv_rank` rows for the values, under
the online-softmax recurrence, whose mask, reductions, rescale and
stores are paid once a block (one page an iteration left every product
64 rows wide against nine fresh weight tiles and every step waiting for
the one before it: PERF.md section 6, PR 41). A slot's last block is part
empty: only its live pages are copied, the mask covers the rest. Out
comes `u (S, H, kv_rank)`, the probabilities' sum of latents a head; the
mixer takes it up through `W^V` (`mla.out`).

`latent_write`: one decode position a slot, written into the donated
pool in place (`pallas_paged_kv_write`'s discipline: the pool is an
aliased operand in the layout the attend kernel reads, so neither decode
program copies it; as an XLA scatter on the lane axis the write would
cost two whole-pool copies a step). A lane cannot be addressed below the
128-wide tile, so a slot's write moves its page tile in and out.

`mla_prefill`: the EXPANDED attention of a whole prompt too long for one
array of scores (`LatentAttentionMixer.attend_expanded`): a flash walk
over (head, block of queries, block of keys) with the operands at their
own widths: a head's nope queries and keys (128), the rope queries (64)
against the ONE rope key of a position, shared by all heads and never
broadcast, and values of 128, so the multiply-adds are the attention's
own (the flash kernel of `ops/pallas_attention.py` takes one head size
for all three and would need 256 for each). Blocks of queries at and
past `n_valid`, the prompt's padding in its bucket, compute nothing and
come out zeros.

Dispatch rides `ops/kernel_dispatch.py` under the families `mla_attend`,
`mla_prefill` and `latent_write`: each probe compiles and runs its kernel at the exact
shape class and holds it to the `jax.numpy` form beside it
(`mla_attend_xla`: gather the slot's pages, then attend; `latent_write_xla`:
the scatter), the attend's key ending in the block it ran with
(`"block8"`); `DL4J_TPU_NO_PALLAS_MLA_ATTEND` forces the XLA forms; CPU
backends never dispatch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.kernel_dispatch import (
    platform_supported as _kernels_dispatch,
    probe_verdict as _probe_verdict,
    record_decline as _record_decline,
    vmem_limit_bytes as _vmem_limit,
)

FAMILY = "mla_attend"          # this module's rows in kernel_verdicts()
PREFILL_FAMILY = "mla_prefill"
WRITE_FAMILY = "latent_write"
NEG_INF = -1e30


# ------------------------------------------------------------ XLA forms
def gather_latents(pool, page_table):
    """Each row of `page_table` (S, n_pages) gathered into a dense run
    of latents (S, n_pages * page, kv_rank + rope), entry `s` the
    position `s`."""
    S, n_pages = page_table.shape
    lat = jnp.swapaxes(pool[page_table], 2, 3)   # (S, n_pages, page, R)
    return lat.reshape(S, n_pages * pool.shape[2], pool.shape[1])


def mla_attend_xla(q_abs, pool, page_table, pos, *, kv_rank: int,
                   sm_scale: float):
    """Gather-and-attend: each slot's page-table row gathered into a
    dense `(Tk, kv_rank + rope)` run of latents, then one absorbed query
    a head against it. `q_abs` (S, H, kv_rank + rope); `pool` (P+1,
    kv_rank + rope, page); `page_table` (S, n_pages); `pos` (S,): slot
    `s` sees entries `<= pos[s]`. Returns `u` (S, H, kv_rank)."""
    lat = gather_latents(pool, page_table)
    s = jnp.einsum("shr,str->sht", q_abs, lat,
                   preferred_element_type=jnp.float32) * sm_scale
    seen = jnp.arange(lat.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(seen[:, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
    return jnp.einsum("sht,str->shr", prob, lat[..., :kv_rank],
                      preferred_element_type=jnp.float32).astype(q_abs.dtype)


def latent_write_xla(pool, new, pids, loff):
    """One position a slot scattered into the pool: `new` (S, kv_rank +
    rope) lands at in-page offset `loff[s]` of page `pids[s]`."""
    return pool.at[pids, :, loff].set(new.astype(pool.dtype))


# ------------------------------------------------------- attend kernel
# Positions one iteration of a slot's walk takes: `block_pages` makes it
# whole pages. From the sweep of 1 / 2 / 4 / 8 pages of 128 on a TPU v5e
# at LongCat's widths, where 8 was the fastest at every context
# (PERF.md section 6, PR 41; `tools/mla_attend_bench.py`).
BLOCK_POSITIONS = 1024
_MAX_BLOCK_PAGES = 8     # copies an iteration starts and waits for, unrolled


def block_pages(page: int, R: int, H: int, dtype) -> int:
    """Pages an iteration of the walk takes (`B`): `BLOCK_POSITIONS` in
    whole pages, halved until the two block buffers and the block's
    float32 scores and probabilities leave half the VMEM ceiling free."""
    B = max(1, min(_MAX_BLOCK_PAGES, BLOCK_POSITIONS // page))
    item = jnp.dtype(dtype).itemsize
    while B > 1 and B * page * (2 * R * item + 4 * H * 4) > _vmem_limit() // 2:
        B //= 2
    return B


def _attend_kernel(pt_ref, pos_ref, gate_ref, q_ref, pool_hbm, o_ref,
                   buf, sem, state, acc_scr, m_scr, l_scr, *, page: int,
                   block: int, kv_rank: int, n_pages: int, sm_scale: float):
    """Grid (S,), slots in order: slot `s` walks pages `0 .. pos //
    page` of its page-table row, `block` of them an iteration. The pool
    stays in HBM; each live page is copied once, into its lane range of
    one of two VMEM block buffers, while the block before it is computed
    on, and a slot's last iteration starts the copies of the next slot's
    first block (`pallas_paged_attention`'s hand-over: `state` carries
    which buffer the slot's first block lands in and whether the slot
    before it started those copies). A slot's last block is part empty:
    only its live pages are copied, and the lanes past them hold what an
    earlier block left there, under the mask."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    S = pl.num_programs(0)
    span = block * page

    def live_pages(slot):
        n = jnp.minimum(pos_ref[slot] // page + 1, n_pages)
        return jnp.where(gate_ref[slot] != 0, n, 0)

    def copies(slot, i, n, b, wait=False):
        """Block `i` of a slot with `n` live pages into buffer `b`: one
        copy a live page, started or waited for."""
        for k in range(block):
            @pl.when(i * block + k < n)
            def _live_page():
                cp = pltpu.make_async_copy(
                    pool_hbm.at[pt_ref[slot, i * block + k]],
                    buf.at[b, :, pl.ds(k * page, page)], sem.at[b, k])
                cp.wait() if wait else cp.start()

    @pl.when(s == 0)
    def _first_slot():
        state[0] = 0
        state[1] = 0
        # a masked lane's probability is 0, and 0 * NaN is NaN in the
        # value product: from here on the buffers hold zeros or pages of
        # the pool, never what the VMEM held before the call
        buf[...] = jnp.zeros_like(buf)

    acc_scr[...] = jnp.zeros_like(acc_scr)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)

    p0 = pos_ref[s]
    n_live = live_pages(s)
    n_blocks = (n_live + block - 1) // block
    nxt = jnp.minimum(s + 1, S - 1)
    n_next = jnp.where(s + 1 < S, live_pages(nxt), 0)
    buf0 = state[0]

    @pl.when(state[1] == 0)
    def _start_cold():
        copies(s, 0, n_live, buf0)

    q = q_ref[0]                                          # (H, R)
    H = q.shape[0]

    def _block(i, carry):
        b = (buf0 + i) % 2
        # the next block's copies, or the next slot's first block's
        more = i + 1 < n_blocks
        copies(jnp.where(more, s, nxt), jnp.where(more, i + 1, 0),
               jnp.where(more, n_live, n_next), 1 - b)
        copies(s, i, n_live, b, wait=True)
        c = buf[b]                                        # (R, span)
        sc = jnp.dot(q, c, preferred_element_type=jnp.float32) * sm_scale
        kpos = i * span + jax.lax.broadcasted_iota(jnp.int32, (H, span), 1)
        sc = jnp.where(kpos <= p0, sc, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(sc <= NEG_INF / 2, 0.0, jnp.exp(sc - m_new))
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        # the block again, its first kv_rank rows: positions on lanes in
        # both operands, so the product contracts the lane axes
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(c.dtype), c[:kv_rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, n_blocks, _block, 0)

    @pl.when(n_live > 0)
    def _hand_over():
        state[0] = (buf0 + n_blocks) % 2
        state[1] = (n_next > 0).astype(jnp.int32)

    l = l_scr[:, :1]
    o_ref[0] = jnp.where(l > 0, acc_scr[...] / jnp.where(l > 0, l, 1.0),
                         0.0).astype(o_ref.dtype)


def _attend_call(q_abs, pool, page_table, pos, active, *, kv_rank: int,
                 sm_scale: float, block: int, interpret: bool = False):
    """The kernel call at `block` pages an iteration (`mla_attend` asks
    `block_pages`; `tools/mla_attend_bench.py` sweeps it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, R = q_abs.shape
    page = pool.shape[2]
    kernel = functools.partial(
        _attend_kernel, page=page, block=block, kv_rank=kv_rank,
        n_pages=page_table.shape[1], sm_scale=sm_scale)
    slot = lambda s, pt, p0, g: (s, 0, 0)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, R), slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, kv_rank), slot),
            scratch_shapes=[
                pltpu.VMEM((2, R, block * page), pool.dtype),
                pltpu.SemaphoreType.DMA((2, block)),
                pltpu.SMEM((2,), jnp.int32),        # buffer, copies started
                pltpu.VMEM((H, kv_rank), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),  # running max m
                pltpu.VMEM((H, 128), jnp.float32),  # running denom l
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H, kv_rank), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            # in order: a slot's last iteration starts the next slot's
            # first copies
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(active).astype(jnp.int32), q_abs, pool)


# jitted, like the family's other serving kernels, so that a program
# which attends in eight sub-layers traces and lowers the kernel once
# and calls it eight times (PERF.md, PR 26 and PR 34)
@functools.partial(jax.jit, static_argnames=("kv_rank", "sm_scale",
                                             "interpret"))
def mla_attend(q_abs, pool, page_table, pos, active, *, kv_rank: int,
               sm_scale: float, interpret: bool = False):
    """`mla_attend_xla` streamed from the pool: only entries `0 ..
    pos[s] // page` of a slot's table row are read; a slot that `active`
    (S,) bool leaves out reads no page and comes out zeros."""
    _, H, R = q_abs.shape
    return _attend_call(
        q_abs, pool, page_table, pos, active, kv_rank=kv_rank,
        sm_scale=sm_scale, interpret=interpret,
        block=block_pages(pool.shape[2], R, H, pool.dtype))


# -------------------------------------------------------- write kernel
def _write_kernel(pid_ref, off_ref, new_ref, pool_in, pool_out):
    from jax.experimental import pallas as pl

    del pid_ref  # the index maps read it
    off = off_ref[pl.program_id(0)]
    _, R, page = pool_in.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, page), 1)
    # the select runs on 32-bit values: bfloat16 widens and narrows
    # back exactly
    pool_out[0] = jnp.where(lane == off, new_ref[0].astype(jnp.float32),
                            pool_in[0].astype(jnp.float32)) \
        .astype(pool_out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def latent_write(pool, new, pids, loff, *, interpret: bool = False):
    """`latent_write_xla` as one in-place kernel call: the pool is
    aliased input to output, so under donation (or inside a loop's
    carry) it is not copied, and pages no slot names are never touched.
    Inactive lanes arrive redirected to the trash page 0, where they may
    collide; what it holds afterwards is unspecified."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, R = new.shape
    page = pool.shape[2]
    tile = pl.BlockSpec((1, R, page), lambda s, pid, off: (pid[s], 0, 0))
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, R, 1), lambda s, pid, off:
                                   (s, 0, 0)), tile],
            out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            # sequential: colliding trash-page writes stay ordered
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(pids.astype(jnp.int32), loff.astype(jnp.int32),
      new.astype(pool.dtype)[..., None], pool)


# ------------------------------------------------- the prompt's own attention
PREFILL_BLOCK = 512     # queries and keys a step of the prefill walk takes


def _prefill_kernel(n_valid_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                    o_ref, m_scr, l_scr, acc_scr, *, sm_scale: float,
                    block: int):
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # keys past the diagonal add nothing, queries past the prompt are padding
    @pl.when((ki <= qi) & (qi * block < n_valid_ref[0]))
    def _step():
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], kn_ref[0], dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr_ref[...], dims,
                                   preferred_element_type=jnp.float32)) \
            * sm_scale
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # every row sees its own position, so no row of a step is all masked
        prob = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(prob, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            prob.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = jnp.where(l > 0, acc_scr[...] / jnp.where(l > 0, l, 1.0),
                             0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def mla_prefill(q_n, q_r, k_n, k_r, v, n_valid, *, sm_scale: float,
                interpret: bool = False):
    """Causal attention of one prompt, heads first: `q_n`, `k_n` (H, T,
    nope), `q_r` (H, T, rope), `k_r` (T, rope) the one rope key a
    position, `v` (H, T, v_dim); `n_valid` (1,) int32 the prompt's length
    in its bucket of `T` (a multiple of `PREFILL_BLOCK`). Returns (H, T,
    v_dim): rows from the first block at or past `n_valid` on are
    zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, T, nope = q_n.shape
    rope, vd, B = q_r.shape[2], v.shape[2], PREFILL_BLOCK
    head_q = lambda w: pl.BlockSpec((1, B, w), lambda h, i, j, n: (h, i, 0))
    head_k = lambda w: pl.BlockSpec((1, B, w), lambda h, i, j, n: (h, j, 0))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, sm_scale=sm_scale, block=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, T // B, T // B),
            in_specs=[head_q(nope), head_q(rope), head_k(nope),
                      pl.BlockSpec((B, rope), lambda h, i, j, n: (j, 0)),
                      head_k(vd)],
            out_specs=head_q(vd),
            scratch_shapes=[pltpu.VMEM((B, 128), jnp.float32),
                            pltpu.VMEM((B, 128), jnp.float32),
                            pltpu.VMEM((B, vd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((H, T, vd), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
    )(n_valid, q_n, q_r, k_n, k_r, v)


def mla_prefill_xla(q_n, q_r, k_n, k_r, v, *, sm_scale: float):
    """The same attention as one array of scores a head: what the kernel
    is held to (small shapes only)."""
    s = (jnp.einsum("htn,hsn->hts", q_n, k_n,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("htr,sr->hts", q_r, k_r,
                      preferred_element_type=jnp.float32)) * sm_scale
    T = s.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, NEG_INF)
    return jnp.einsum("hts,hsv->htv", jax.nn.softmax(s, -1).astype(v.dtype),
                      v, preferred_element_type=jnp.float32).astype(v.dtype)


# ------------------------------------------------------------ dispatch
def _platform_supported() -> bool:
    return _kernels_dispatch("DL4J_TPU_NO_PALLAS_MLA_ATTEND")


def _attend_probe(dtype, H: int, R: int, kv_rank: int, page: int,
                  sm_scale: float) -> bool:
    """Compile and run the attend kernel at this shape class and hold it
    to gather-and-attend: a table wider than any slot's live pages whose
    dead entries name a page of NaNs (a read of a page no query can see
    fails the comparison), one slot ending on a page's last position,
    one on the next page's first, one a page past a whole block, one
    inactive, one of more than two blocks ending mid-page."""
    import numpy as np

    B = block_pages(page, R, H, dtype)
    pos = np.asarray([page - 1, page, (B + 1) * page - 1, 5,
                      (2 * B + 1) * page + 5], np.int32)
    active = np.asarray([True, True, True, False, True])
    live = pos // page + 1
    n_pages = int(live.max()) + 2
    P = int(live.sum())
    dead = P + 1
    pt = np.full((len(pos), n_pages), dead, np.int32)
    at = 1
    for s, n in enumerate(live):
        pt[s, :n] = at + np.arange(n)
        at += n
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((len(pos), H, R)) / R ** 0.25, dtype)
    pool = jnp.asarray(rng.standard_normal((P + 2, R, page)), dtype)
    got = np.asarray(mla_attend(
        q, pool.at[dead].set(jnp.nan), jnp.asarray(pt), jnp.asarray(pos),
        jnp.asarray(active), kv_rank=kv_rank, sm_scale=sm_scale), np.float32)
    want = np.asarray(mla_attend_xla(
        q, pool, jnp.asarray(np.where(pt == dead, 0, pt)), jnp.asarray(pos),
        kv_rank=kv_rank, sm_scale=sm_scale), np.float32)
    if not np.all(np.isfinite(got)):
        return False
    if np.any(got[~active] != 0):
        raise ValueError("an inactive slot did not come out zeros")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    if not np.allclose(got[active], want[active], atol=tol, rtol=tol):
        raise ValueError(
            "kernel compiled but disagrees with gather-and-attend: max "
            f"abs err {np.max(np.abs(got[active] - want[active])):.3g} at "
            f"atol=rtol={tol:g}")
    return True


def _write_probe(dtype, R: int, page: int) -> bool:
    """Compile and run the write kernel at this shape class and check
    every page but the trash page against the scatter, bit for bit: a
    page's first and last offsets, two inactive lanes colliding on page
    0, then a second call over the first call's output."""
    import numpy as np

    S, P = 4, 4
    rng = np.random.default_rng(0)
    got = want = jnp.asarray(rng.standard_normal((P + 1, R, page)), dtype)
    pids = jnp.asarray([3, 0, 1, 0], jnp.int32)
    for loff in ([0, 5, page - 2, 7], [1, 6, page - 1, 8]):
        loff = jnp.asarray(loff, jnp.int32)
        new = jnp.asarray(rng.standard_normal((S, R)), dtype)
        got = latent_write(got, new, pids, loff)
        want = latent_write_xla(want, new, pids, loff)
    g, w = np.asarray(got[1:]), np.asarray(want[1:])
    if g.tobytes() != w.tobytes():
        raise ValueError("kernel compiled but its pool differs from the "
                         "scatter's outside the trash page")
    return True


def attend_key(dtype, H: int, R: int, kv_rank: int, page: int) -> tuple:
    """The attend kernel's shape class in `kernel_verdicts()`; its last
    entry names the form that ran: the pages an iteration takes."""
    return (jnp.dtype(dtype).name, H, R, kv_rank, page,
            f"block{block_pages(page, R, H, dtype)}")


def mla_attend_or_none(q_abs, pool, page_table, pos, active, *,
                       kv_rank: int, sm_scale: float) -> Optional[jnp.ndarray]:
    """Dispatch probe: the streamed attention, or None when the kernel
    cannot serve this call (CPU backend, kill switch, a dtype or widths
    Mosaic does not tile) or its shape class failed the compile+parity
    probe; callers run `mla_attend_xla`."""
    S, H, R = q_abs.shape
    page = pool.shape[2]
    dtype = q_abs.dtype
    if not _platform_supported() or pool.dtype != dtype \
            or dtype not in (jnp.float32, jnp.bfloat16):
        return None
    key = attend_key(dtype, H, R, kv_rank, page)
    rows = 32 // jnp.dtype(dtype).itemsize
    if H % 8 or R % rows or kv_rank % 128 or page % 128:
        _record_decline(FAMILY, key, f"{H} heads, latent {kv_rank} of {R}, "
                                     f"page {page}: off the tile grid")
        return None
    if not _probe_verdict(FAMILY, key, _attend_probe,
                          (dtype, H, R, kv_rank, page, sm_scale)):
        return None
    try:
        return mla_attend(q_abs, pool, page_table, pos, active,
                          kv_rank=kv_rank, sm_scale=sm_scale)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(FAMILY, key, f"staging at {q_abs.shape}: "
                                     f"{type(e).__name__}: {e}")
        return None


def latent_write_or_none(pool, new, pids, loff) -> Optional[jnp.ndarray]:
    """Dispatch probe: the written pool, or None (callers run
    `latent_write_xla`)."""
    _, R, page = pool.shape
    dtype = pool.dtype
    if not _platform_supported() \
            or dtype not in (jnp.float32, jnp.bfloat16):
        return None
    key = (jnp.dtype(dtype).name, R, page)
    if R % (32 // jnp.dtype(dtype).itemsize) or page % 128:
        _record_decline(WRITE_FAMILY, key, f"latent {R}, page {page}: off "
                                           "the tile grid")
        return None
    if not _probe_verdict(WRITE_FAMILY, key, _write_probe,
                          (dtype, R, page)):
        return None
    try:
        return latent_write(pool, new, pids, loff)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(WRITE_FAMILY, key, f"staging at {pool.shape}: "
                                           f"{type(e).__name__}: {e}")
        return None


def _prefill_probe(dtype, nope: int, rope: int, vd: int) -> bool:
    """Compile and run the prefill kernel at this shape class (two heads,
    three blocks, the last one padding) and hold it to one array of
    scores."""
    import numpy as np

    rng = np.random.default_rng(0)
    H, T = 2, 3 * PREFILL_BLOCK
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape) / 4, dtype)
    args = (mk(H, T, nope), mk(H, T, rope), mk(H, T, nope), mk(T, rope),
            mk(H, T, vd))
    live = 2 * PREFILL_BLOCK - 5
    got = np.asarray(mla_prefill(*args, jnp.asarray([live], jnp.int32),
                                 sm_scale=0.1), np.float32)
    want = np.asarray(mla_prefill_xla(*args, sm_scale=0.1), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    if np.any(got[:, 2 * PREFILL_BLOCK:]) or not np.allclose(
            got[:, :live], want[:, :live], atol=tol, rtol=tol):
        raise ValueError("prefill kernel compiled but disagrees with the "
                         "whole scores: max abs err "
                         f"{np.max(np.abs(got - want)[:, :live]):.3g}")
    return True


def mla_prefill_or_none(q_n, q_r, k_n, k_r, v, n_valid, *, sm_scale: float):
    """Dispatch probe: the prompt's attention, or None when the kernel
    cannot serve this call (CPU backend, kill switch, a dtype, widths off
    the tile grid, a length off its blocks) or its shape class failed the
    probe; callers take blocks of queries in `jax.numpy`."""
    H, T, nope = q_n.shape
    rope, vd, dtype = q_r.shape[2], v.shape[2], q_n.dtype
    if not _platform_supported() or dtype not in (jnp.float32, jnp.bfloat16):
        return None
    key = (jnp.dtype(dtype).name, nope, rope, vd, PREFILL_BLOCK)
    if nope % 128 or vd % 128 or rope % 8 or T % PREFILL_BLOCK:
        # under the length's own key: a prompt off the blocks says nothing
        # of the class the bucketed prompts run in
        _record_decline(PREFILL_FAMILY, key[:-1] + (f"{T} positions",),
                        f"widths {nope} + {rope} / {vd}: off the tile grid "
                        f"or the blocks of {PREFILL_BLOCK}")
        return None
    if not _probe_verdict(PREFILL_FAMILY, key, _prefill_probe,
                          (dtype, nope, rope, vd)):
        return None
    try:
        return mla_prefill(q_n, q_r, k_n, k_r, v, n_valid, sm_scale=sm_scale)
    except Exception as e:  # per-shape staging failure: fall back
        _record_decline(PREFILL_FAMILY, key, f"staging at {q_n.shape}: "
                        f"{type(e).__name__}: {e}")
        return None


def attend(q_abs, pool, page_table, pos, active, *, kv_rank: int,
           sm_scale: float):
    """The kernel on a TPU, gather-and-attend elsewhere."""
    out = mla_attend_or_none(q_abs, pool, page_table, pos, active,
                             kv_rank=kv_rank, sm_scale=sm_scale)
    return mla_attend_xla(q_abs, pool, page_table, pos, kv_rank=kv_rank,
                          sm_scale=sm_scale) if out is None else out


def write(pool, new, pids, loff):
    """The in-place kernel on a TPU, the scatter elsewhere."""
    out = latent_write_or_none(pool, new, pids, loff)
    return latent_write_xla(pool, new, pids, loff) if out is None else out
