#!/usr/bin/env python3
"""The flash attention kernels alone, timed as a train step calls them:

    python3 tools/flash_attention_bench.py [--batch 4] [--heads 12]
        [--head-dim 128] [--seqs 2048,4096,8192] [--dtype bfloat16]
        [--block 0] [--forms split,fused] [--iters 20] [--seed 0]

For every `--seqs` entry, causal attention over `(batch x heads, T,
head_dim)` slabs drawn from `--seed`: the training forward (it writes the
row logsumexp) and each `--forms` entry of the backward
(`pallas_attention._BACKWARD`: the dQ and dK/dV kernels of the `split`,
the one kernel of the `fused`), every call on its own with the slabs, the
statistics and `rowsum(dO * O)` made beforehand, so that a row times the
kernels and nothing beside them. `--block 0` takes the largest tile of the
ladder that divides T. Prints, a row each, milliseconds a call, the share
of `perfbench.harness.roofline`'s least time (which prices two forward and
five backward products on the causal pairs), the backward's largest gap to
the split's gradients and, for the first slab, to `jax.grad` through
`full_attention`. The rows also go to
`chiprun_out/flash_attention_bench.json`. Needs a TPU; `--interpret` runs
the kernels in interpret mode on any backend and prints no time as a
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


def largest_gap(got, want) -> float:
    import jax.numpy as jnp

    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(got, want))


def reference_grads(q, k, v, do):
    """`jax.grad` through `full_attention` for slabs `(n, T, D)`, in
    float32."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import full_attention

    f32 = lambda x: x.astype(jnp.float32)[:, :, None, :]   # (n, T, 1, D)
    _, vjp = jax.vjp(functools.partial(full_attention, causal=True),
                     f32(q), f32(k), f32(v))
    return [g[:, :, 0, :] for g in vjp(f32(do))]


def bench(args) -> tuple:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_attention as pa
    from perfbench.harness import device, roofline

    dev = device.describe()
    if not args.interpret:
        device.require_chips(1)
    dtype = jnp.dtype(args.dtype)
    B, H, D = args.batch, args.heads, args.head_dim
    rows = []
    for T in args.seqs:
        block = args.block or next(b for b in pa._BLOCK_CANDIDATES
                                   if T % b == 0)
        tiles = dict(causal=True, sm_scale=D ** -0.5, block_q=block,
                     block_k=block, interpret=args.interpret)
        keys = jax.random.split(jax.random.PRNGKey(args.seed + T), 4)
        q, k, v, do = (jax.random.normal(key, (B * H, T, D), dtype)
                       for key in keys)

        @jax.jit
        def forward(q, k, v):
            # a slab is a (n, T, 1, D) batch: its transposes move nothing
            out, lse = pa._flash_forward(
                q[:, :, None], k[:, :, None], v[:, :, None], True,
                tiles["sm_scale"], block, block, args.interpret,
                with_lse=True)
            return out[:, :, 0], lse

        out, lse = forward(q, k, v)
        dsum = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                       axis=-1, keepdims=True)
        dsum = jnp.broadcast_to(dsum, (B * H, T, 128))
        calls = [("forward", forward, (q, k, v), roofline.flash_forward)]
        for form in args.forms:
            calls.append((f"backward_{form}",
                          jax.jit(functools.partial(pa._BACKWARD[form],
                                                    **tiles)),
                          (q, k, v, do, lse, dsum), roofline.flash_backward))
        want = reference_grads(q[:1], k[:1], v[:1], do[:1])
        split = None
        for name, fn, operands, least in calls:
            got = jax.block_until_ready(fn(*operands))
            row = {"T": T, "block": block, "kernel": name}
            if name.startswith("backward_"):
                row["gap_to_xla"] = largest_gap([g[:1] for g in got], want)
                if split is not None:
                    row["gap_to_split"] = largest_gap(got, split)
                if name == "backward_split":
                    split = got
            if not args.interpret:
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    got = fn(*operands)
                jax.block_until_ready(got)
                call_s = (time.perf_counter() - t0) / args.iters
                ops, nbytes = least(B, H, T, D, dtype.itemsize)
                row.update(call_ms=1e3 * call_s,
                           roofline_pct=roofline.share_pct(
                               ops, nbytes, call_s,
                               device.peaks(dev["kind"])))
            rows.append(row)
            print(json.dumps(row), flush=True)
    return dev, rows


def main(argv=None) -> int:
    ints = lambda s: [int(x) for x in s.split(",")]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--seqs", type=ints, default=[2048, 4096, 8192])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--block", type=int, default=0)
    ap.add_argument("--forms", type=lambda s: s.split(","),
                    default=["split", "fused"])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "flash_attention_bench.json"))
    args = ap.parse_args(argv)
    dev, rows = bench(args)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"device": dev, "interpret": args.interpret, "rows": rows},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
