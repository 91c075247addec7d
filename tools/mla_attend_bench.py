#!/usr/bin/env python3
"""The latent attention kernel alone, timed as a decode step calls it:

    python3 tools/mla_attend_bench.py [--slots 128] [--heads 64] [--kv-rank 512]
        [--rope 64] [--page 128] [--dtype bfloat16] [--sm-scale 0.0722]
        [--contexts 560,1000,1600,256-2048] [--blocks 1,2,4,8]
        [--calls 8] [--iters 20] [--seed 0]

`--calls` sub-layers' attends under one `jit`, each over a pool of its
own, for every `--blocks` entry (pages an iteration of the slot's walk
takes; 0: what `pallas_mla_attend.block_pages` picks) and every
`--contexts` entry (positions a slot has cached: one number for all
slots, or `lo-hi` drawn uniformly per slot from `--seed`). Prints, a
row each, milliseconds a call, microseconds a slot and a live page, the
share of `mla_roofline.latent_decode`'s least time, and the largest gap
to gather-and-attend; then a line a block: microseconds a slot and a
page by least squares over the contexts. The rows also go to
`chiprun_out/mla_attend_bench.json`. Needs a TPU; `--interpret` runs the
kernel in interpret mode on any backend and prints no time as a
device's (a rehearsal of the tool, not a measurement).
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def contexts_of(spec: str, slots: int, rng):
    """`"1000"`: every slot at 1000 cached positions; `"256-2048"`: each
    slot's drawn uniformly from the range."""
    import numpy as np

    lo, _, hi = spec.partition("-")
    if not hi:
        return np.full(slots, int(lo), np.int32)
    return rng.integers(int(lo), int(hi) + 1, slots).astype(np.int32)


def make_case(ctx, n_pages: int, pool_pages: int, rng):
    """A page table over distinct pool pages (0 the trash page) and the
    decode position of each slot: its newest cached entry."""
    import numpy as np

    S = len(ctx)
    pt = rng.permutation(np.arange(1, pool_pages + 1))[:S * n_pages]
    return pt.reshape(S, n_pages).astype(np.int32), ctx - 1


def bench(args) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import pallas_mla_attend as mla
    from perfbench.harness import device, mla_roofline, roofline

    dev = device.describe()
    if not args.interpret:
        device.require_chips(1)
    dtype = jnp.dtype(args.dtype)
    S, H, R, page = args.slots, args.heads, args.kv_rank + args.rope, args.page
    rng = np.random.default_rng(args.seed)
    cases = [(spec, contexts_of(spec, S, rng)) for spec in args.contexts]
    n_pages = max(int(-(-ctx.max() // page)) for _, ctx in cases)
    pool_pages = S * n_pages
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 2 * args.calls)
    pools = [jax.random.normal(k, (pool_pages + 1, R, page), dtype)
             for k in keys[:args.calls]]
    qs = [(jax.random.normal(k, (S, H, R)) / R ** 0.25).astype(dtype)
          for k in keys[args.calls:]]
    active = jnp.ones(S, bool)
    kw = dict(kv_rank=args.kv_rank, sm_scale=args.sm_scale)
    rows = []
    for B in args.blocks:
        B = B or mla.block_pages(page, R, H, dtype)
        attend = functools.partial(mla._attend_call, block=B,
                                   interpret=args.interpret, **kw)

        @jax.jit
        def step(qs, pools, pt, pos):
            return [attend(q, pool, pt, pos, active)
                    for q, pool in zip(qs, pools)]

        for spec, ctx in cases:
            pt, pos = make_case(ctx, n_pages, pool_pages, rng)
            pt, pos = jnp.asarray(pt), jnp.asarray(pos)
            out = jax.block_until_ready(step(qs, pools, pt, pos))
            want = mla.mla_attend_xla(qs[0], pools[0], pt, pos, **kw)
            gap = float(jnp.max(jnp.abs(out[0].astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = step(qs, pools, pt, pos)
            jax.block_until_ready(out)
            call_s = (time.perf_counter() - t0) / args.iters / args.calls
            live = float(np.mean(-(-ctx // page)))
            ops, nbytes = mla_roofline.latent_decode(
                float(ctx.sum()), S, H, args.kv_rank, args.rope,
                dtype.itemsize)
            row = {"block": B, "contexts": spec, "live_pages": live,
                   "gap_to_xla": gap}
            if not args.interpret:
                row.update(
                    call_ms=1e3 * call_s, slot_us=1e6 * call_s / S,
                    page_us=1e6 * call_s / S / live,
                    roofline_pct=roofline.share_pct(
                        ops, nbytes, call_s, device.peaks(dev["kind"])))
            rows.append(row)
            print(json.dumps(row), flush=True)
    return dev, rows


def fits(rows: list) -> list:
    """For each block: microseconds a slot and a live page, by least
    squares of a call's time a slot over the contexts' live pages."""
    import numpy as np

    out = []
    for B in sorted({r["block"] for r in rows}):
        mine = [r for r in rows if r["block"] == B and "slot_us" in r]
        if len({r["live_pages"] for r in mine}) < 2:
            continue
        per_page, per_slot = np.polyfit([r["live_pages"] for r in mine],
                                        [r["slot_us"] for r in mine], 1)
        out.append({"block": B, "fit_slot_us": float(per_slot),
                    "fit_page_us": float(per_page)})
    return out


def main(argv=None) -> int:
    ints = lambda s: [int(x) for x in s.split(",")]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--kv-rank", type=int, default=512)
    ap.add_argument("--rope", type=int, default=64)
    ap.add_argument("--page", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--sm-scale", type=float, default=192 ** -0.5)
    ap.add_argument("--contexts", type=lambda s: s.split(","),
                    default=["560", "1000", "1600", "256-2048"])
    ap.add_argument("--blocks", type=ints, default=[1, 2, 4, 8])
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "mla_attend_bench.json"))
    args = ap.parse_args(argv)
    dev, rows = bench(args)
    table = {"device": dev, "interpret": args.interpret, "rows": rows,
             "fits": fits(rows)}
    for fit in table["fits"]:
        print(json.dumps(fit), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
