#!/usr/bin/env python3
"""The routed experts' two products alone, timed as a prefill calls them:

    python3 tools/moe_experts_bench.py [--workloads <cell>,...]
        [--shape d,f,E,k,R,act --buckets 256,512] [--calls 2] [--iters 10]
        [--seed 0]

For each routed cell of `BENCHMARK.json` (or the one `--shape`: model
width, expert width, experts held, top-k, the router's width, the
variant) and each row count (the cell's decode rows and prompt buckets,
or `--buckets`): the hit-first walk over every held expert
(`pallas_moe_experts.moe_experts`), the sorted product whole (the sort,
the gather of the rows, `moe_experts_sorted`, the add-back:
`parallel.experts.sorted_expert_ffn_or_none` at the static size
`sorted_bound` gives, under its `lax.cond` where that is less than the
worst case) and its kernel alone, `--calls` layers' worth under one `jit`, each over
weights of its own. A row's `k` choices are drawn uniformly among the
router's `R` outputs from `--seed`, so the held experts get the
configuration's share of them. Prints, a row each, milliseconds a call,
TFLOP/s over the rows each product multiplies (the walk: all of them
with every held expert; the sorted one: the choices made, and its used
tiles' padded rows), the walk's time over the sorted product's, what
`sorted_serves` says, and the largest gap between the two outputs; last,
the sorted kernel's verdicts from `kernel_verdicts()`. The rows also go to `chiprun_out/moe_experts_bench.json`. Needs a TPU;
`--interpret` runs the kernels in interpret mode on any backend and
prints no time as a device's (a rehearsal of the tool at a toy
`--shape`, not a measurement).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def cells_of(names) -> list:
    """(label, shape, row counts) of the benchmark's routed cells: the
    shape from the net the cell's family builds, the row counts the
    cell's decode rows and its prompt buckets."""
    from deeplearning4j_tpu.nn.conf.decoder_block import MoEFeedForward
    from perfbench.harness.manifest import Manifest

    manifest, out = Manifest(), []
    for w in manifest.raw["workloads"]:
        if names and w["name"] not in names:
            continue
        cfg = manifest.config(w["config"])
        fam = manifest.family(cfg)
        if not hasattr(fam, "build_net"):
            continue
        # the net as configured: nothing is initialised or placed
        net = fam.build_net(fam.sizes(cfg), training=False)
        routed = [ffn for layer in net.layers
                  for ffn in getattr(layer, "feed_forwards", list)()
                  if isinstance(ffn, MoEFeedForward)]
        if not routed:
            continue
        ffn, eng = routed[0], manifest.traffic(w["traffic"])["engine"]
        shape = (net.layers[1].n_out, ffn.expert_width, ffn.held[1],
                 ffn.top_k, ffn.n_experts + ffn.n_zero_experts,
                 ffn.activation)
        out.append((w["name"], shape,
                    [eng["n_slots"], *eng["prompt_buckets"]]))
    return out


def draw_gates(rng, N: int, E: int, k: int, R: int):
    """(N, E) float32: each row chooses `k` of `R` experts uniformly and
    weighs them; the first `E` are the held ones."""
    import numpy as np

    chosen = np.argsort(rng.random((N, R)), axis=1)[:, :k]
    gates = np.zeros((N, R), np.float32)
    np.put_along_axis(gates, chosen,
                      rng.uniform(0.05, 1.0, (N, k)).astype(np.float32), 1)
    return gates[:, :E]


def bench(args) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import kernel_dispatch
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme
    from deeplearning4j_tpu.parallel import experts
    from perfbench.harness import device

    dev = device.describe()
    if not args.interpret:
        device.require_chips(1)
    if args.interpret:  # the dispatch entry never serves a CPU
        pme.moe_experts_sorted_or_none = lambda *a: pme.moe_experts_sorted(
            *a[:-1], act=a[-1], interpret=True)
    dtype = jnp.dtype(args.dtype)
    cases = [("shape", args.shape, args.buckets)] if args.shape \
        else cells_of(args.workloads)
    rng = np.random.default_rng(args.seed)
    rows = []
    for label, (d, f, E, k, R, act), buckets in cases:
        if args.buckets:
            buckets = args.buckets
        keys = iter(jax.random.split(jax.random.PRNGKey(args.seed),
                                     3 * args.calls + 1))
        up = (E, f, d) if act == pme.RELU2 else (E, d, f)
        draw = lambda shape, fan: (jax.random.normal(next(keys), shape)
                                   / fan ** 0.5).astype(dtype)
        weights = [(None if act == pme.RELU2 else draw(up, d), draw(up, d),
                    draw((E, f, d), f)) for _ in range(args.calls)]
        kk, every = min(k, E), jnp.ones(E, bool)
        # operations a row a held expert: three (two) d x f products
        row_ops = (4 if act == pme.RELU2 else 6) * d * f
        for N in buckets:
            x = jax.random.normal(jax.random.PRNGKey(N), (N, d)).astype(dtype)
            gates = jnp.asarray(draw_gates(rng, N, E, k, R))
            made = int(jnp.sum(gates != 0))
            tiles, ks = pme.sorted_bound(N, E, kk, R)
            bounded = (tiles, ks) != pme.sorted_worst(N, E, kk)
            sort = experts.sort_by_expert(gates, ks, pme.SORTED_ROWS, tiles)
            used_rows = int(sort[3][0]) * pme.SORTED_ROWS
            kernel = lambda xs, w: pme.moe_experts_sorted(
                xs, sort[1], sort[2], sort[3], *w, act=act,
                interpret=args.interpret)
            walk_one = lambda x, gates, w: pme.moe_experts(
                x, gates, *w, every, act=act, interpret=args.interpret)

            @jax.jit
            def walk(x, gates, weights):
                return [walk_one(x, gates, w) for w in weights]

            @jax.jit
            def sorted_whole(x, gates, weights):
                # as `grouped_expert_ffn` runs it: at the static size
                # the rule gives, the walk the other branch under it
                return [experts.sorted_expert_ffn_or_none(
                    x, gates, *w, ks, act, tiles,
                    (lambda w=w: walk_one(x, gates, w)) if bounded
                    else None) for w in weights]

            @jax.jit
            def sorted_kernel(xs, weights):
                return [kernel(xs, w) for w in weights]

            xs = x[sort[0]]
            timed = {}
            for name, fn, fn_args in (
                    ("walk", walk, (x, gates, weights)),
                    ("sorted", sorted_whole, (x, gates, weights)),
                    ("sorted_kernel", sorted_kernel, (xs, weights))):
                out = jax.block_until_ready(fn(*fn_args))
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = fn(*fn_args)
                jax.block_until_ready(out)
                timed[name] = ((time.perf_counter() - t0) / args.iters
                               / args.calls, out[0])
            gap = float(jnp.max(jnp.abs(
                timed["walk"][1].astype(jnp.float32)
                - timed["sorted"][1].astype(jnp.float32))))
            row = {"cell": label, "d": d, "f": f, "held": E, "top_k": k,
                   "router_width": R, "act": act, "rows": N,
                   "choices_made": made, "used_rows": used_rows,
                   "sorted_rows_static": int(sort[0].shape[0]),
                   "sorted_choices_static": ks, "fits": bool(sort[5]),
                   "sorted_serves": pme.sorted_serves(N, E, k, R),
                   "gap_walk_to_sorted": gap}
            if not args.interpret:
                t = {name: s for name, (s, _) in timed.items()}
                row.update(
                    walk_ms=1e3 * t["walk"], sorted_ms=1e3 * t["sorted"],
                    sorted_kernel_ms=1e3 * t["sorted_kernel"],
                    walk_tflops=N * E * row_ops / t["walk"] / 1e12,
                    sorted_tflops_made=made * row_ops / t["sorted"] / 1e12,
                    sorted_kernel_tflops_padded=used_rows * row_ops
                    / t["sorted_kernel"] / 1e12,
                    walk_over_sorted=t["walk"] / t["sorted"])
            rows.append(row)
            print(json.dumps(row), flush=True)
    verdicts = {str(key): {"ok": v.ok, "message": v.message}
                for key, v in kernel_dispatch.kernel_verdicts()
                .get(pme.FAMILY, {}).items() if key[-1] == "sorted"}
    return dev, rows, verdicts


def main(argv=None) -> int:
    ints = lambda s: [int(x) for x in s.split(",")]

    def shape(s):
        *n, act = s.split(",")
        return (*map(int, n), act)

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", type=lambda s: s.split(","), default=[])
    ap.add_argument("--shape", type=shape, default=None)
    ap.add_argument("--buckets", type=ints, default=[])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "moe_experts_bench.json"))
    args = ap.parse_args(argv)
    if args.shape and not args.buckets:
        ap.error("--shape needs --buckets")
    dev, rows, verdicts = bench(args)
    print(json.dumps({"sorted_verdicts": verdicts}), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"device": dev, "interpret": args.interpret, "rows": rows,
         "sorted_verdicts": verdicts}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
