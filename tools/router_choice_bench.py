#!/usr/bin/env python3
"""The routers' choice of k of E alone, each candidate form timed and
sized as a routed layer would make it:

    python3 tools/router_choice_bench.py [--cells ling3flash-serve-reason,...]
        [--forms sort,rank,rolled,unrolled,rule] [--calls 8] [--iters 10]
        [--seed 0] [--rehearse]

For every routed cell (`CELLS`), at its decode rows (its slots) and at
its largest prefill bucket, every choice its router makes (the experts';
with groups, a group's best two and the groups kept) as a boolean mask
over the last axis, in each of `--forms`:

- `sort`: `lax.top_k` and a scatter of its indices, as the routers chose
  until PR 51;
- `rank`: `parallel.experts._by_rank`, one compare-and-count over
  (rows, E, E);
- `rolled`: `parallel.experts._by_rounds`, k rounds of first-maximum in
  one `lax.fori_loop` (written out where k is 2 or less);
- `unrolled`: the same rounds written out k times (what PR 50 shipped);
- `rule`: `parallel.experts.chosen_mask`, whichever of `rank` / `rolled`
  its shape rule takes.

`--calls` layers' choices under one `jit`, each on scores of its own.
Prints, a row a (cell, rows, choice, form): `call_us`, microseconds of
device time a call (every operation of `--iters` traced dispatches over
their calls); `compile_s`, seconds to compile the `--calls` layers with
the persistent cache off; `exe_bytes`, the serialized executable's
length (what a warm start loads); `same`, whether the form marked
`lax.top_k`'s lanes on the device; and for `rule`, `took`. A last row a
(cell, rows) times `routed_gates` whole as the tree has it
(`router_us`). The rows also go to `chiprun_out/router_choice_bench.json`.
Needs a TPU; `--rehearse` runs anywhere at the same shapes and prints no
time as a device's. It touches no code a cell runs.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# cell: (decode rows, largest prefill bucket, router width, top_k,
# scoring, n_groups, topk_groups, scale), as `perfbench/families/*`
# build the cells' routed layers and `perfbench/traffic/*` their engines
CELLS = {
    "granite4hs-serve-chat": (64, 512, 72, 10, "softmax", 1, 1, 1.0),
    "nemo3nano-serve-chat": (64, 512, 128, 6, "sigmoid", 1, 1, 2.5),
    "longcatflash-serve-reason": (128, 1024, 768, 12, "softmax_all", 1, 1,
                                  6.0),
    "dsv2-serve-longgen": (128, 4096, 160, 6, "softmax_all", 8, 3, 16.0),
    "ling3flash-serve-reason": (128, 1024, 512, 8, "sigmoid", 8, 4, 2.5),
    "cmdaplus-serve-longgen": (48, 4096, 128, 8, "sigmoid", 1, 1, 1.0),
}
FORMS = ("sort", "rank", "rolled", "unrolled", "rule")


def choices(cell: str, rows: int) -> list:
    """(name, scores' shape, k) of every choice `cell`'s router makes
    over `rows` rows."""
    _, _, E, k, scoring, G, kept, _ = CELLS[cell]
    out = [("experts", (rows, E), k)]
    if G > 1:
        if scoring == "sigmoid":
            out.append(("best_two", (rows, G, E // G), 2))
        out.append(("groups", (rows, G), kept))
    return out


def form_of(name: str):
    """`name` of `FORMS` as a function (scores, k) -> mask."""
    import jax.numpy as jnp
    from jax import lax

    from deeplearning4j_tpu.parallel import experts

    def sort(scores, k):
        idx = lax.top_k(scores, k)[1]
        flat = idx.reshape(-1, idx.shape[-1])
        return jnp.zeros((flat.shape[0], scores.shape[-1]), bool).at[
            jnp.arange(flat.shape[0])[:, None], flat].set(True).reshape(
            scores.shape)

    def unrolled(scores, k):
        key = experts._total_order(scores)
        E = key.shape[-1]
        lane = lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
        chosen = jnp.zeros(key.shape, bool)
        for _ in range(min(k, E)):
            top = jnp.max(jnp.where(chosen, jnp.iinfo(jnp.int32).min, key),
                          axis=-1, keepdims=True)
            first = jnp.min(jnp.where(~chosen & (key >= top), lane, E),
                            axis=-1, keepdims=True)
            chosen |= lane == first
        return chosen

    keyed = lambda form: lambda scores, k: form(
        experts._total_order(scores), min(k, scores.shape[-1]))
    return {"sort": sort, "unrolled": unrolled,
            "rank": keyed(experts._by_rank),
            "rolled": keyed(experts._by_rounds),
            "rule": experts.chosen_mask}[name]


def device_us(view, calls: int):
    """Microseconds a call in which any operation ran on the device, of
    a trace of `calls` calls; None where the trace holds no device
    (`--rehearse`)."""
    return 1e6 * view.busy_s() / calls if view.chips else None


def measure(layers, operands, args, trace_dir: Path) -> tuple:
    """Compile `layers` for `operands` with the persistent cache off,
    run it once, then trace `--iters` dispatches: (`compile_s`,
    `exe_bytes`, `call_us` on a device, the first layer's output)."""
    import jax
    import numpy as np

    from perfbench.harness import trace_reduce

    t0 = time.perf_counter()
    compiled = jax.jit(layers).lower(*operands).compile()
    row = {"compile_s": time.perf_counter() - t0,
           "exe_bytes": len(compiled.runtime_executable().serialize())}
    out = jax.block_until_ready(compiled(*operands))
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    for _ in range(args.iters):
        last = compiled(*operands)
    jax.block_until_ready(last)
    jax.profiler.stop_trace()
    view = trace_reduce.TraceView(trace_reduce.load_xplane(str(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    if not args.rehearse:
        row["call_us"] = device_us(view, args.iters * args.calls)
    return row, np.asarray(out[0])


def bench_cell(cell: str, N: int, args, rng, trace_dir: Path, say) -> None:
    """Every choice of `cell`'s router over `N` rows in every form, then
    its router whole as the tree has it: a row each to `say`."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel import experts

    _, _, E, k, scoring, G, kept, scale = CELLS[cell]
    for choice, shape, kk in choices(cell, N):
        scores = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
                  for _ in range(args.calls)]
        want = np.asarray(form_of("sort")(scores[0], kk))
        for name in args.forms:
            form = form_of(name)
            row, got = measure(lambda scores: [form(s, kk) for s in scores],
                               (scores,), args, trace_dir)
            row = {"cell": cell, "rows": N, "choice": choice,
                   "lanes": shape[-1], "k": kk, "form": name, **row,
                   "same": bool((got == want).all())}
            if name == "rule":
                row["took"] = "rank" if experts.ranks(shape) else "rolled"
            say(row)
    logits = [jnp.asarray(rng.standard_normal((N, E)), jnp.float32)
              for _ in range(args.calls)]
    bias = None if scoring == "softmax" else jnp.asarray(
        0.1 * rng.standard_normal(E), jnp.float32)
    row, _ = measure(
        lambda logits: [experts.routed_gates(
            lg, k, bias=bias, scale=scale, scoring=scoring, n_groups=G,
            topk_groups=kept) for lg in logits], (logits,), args, trace_dir)
    if "call_us" in row:
        row["router_us"] = row.pop("call_us")
    say({"cell": cell, "rows": N, "choice": "routed_gates", "lanes": E,
         "k": k, "form": "rule", **row})


def bench(args) -> tuple:
    import jax
    import numpy as np

    from perfbench.harness import device

    dev = device.describe()
    if not args.rehearse:
        device.require_chips(1)
    rng = np.random.default_rng(args.seed)
    trace_dir = Path(args.out).parent / ".router_choice_trace"
    rows = []

    def say(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    # a compile second and an executable's size are a cold compile's
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for cell in args.cells:
            for N in CELLS[cell][:2]:
                bench_cell(cell, N, args, rng, trace_dir, say)
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    return dev, rows


def main(argv=None) -> int:
    names = lambda s: s.split(",")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", type=names, default=list(CELLS))
    ap.add_argument("--forms", type=names, default=list(FORMS))
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "router_choice_bench.json"))
    args = ap.parse_args(argv)
    dev, rows = bench(args)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"device": dev, "rehearse": args.rehearse, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
