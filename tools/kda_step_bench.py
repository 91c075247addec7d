#!/usr/bin/env python3
"""The delta-rule decode step with a decay a key channel alone, timed as
a decode step calls it:

    python3 tools/kda_step_bench.py [--slots 128,64] [--heads 32]
        [--key-dim 128] [--value-dim 128] [--dtype bfloat16]
        [--calls 10] [--iters 20] [--live 1.0] [--seed 0] [--xla]

`--calls` layers' steps under one `jit`, each over a donated state of
its own (a slot's 2.1 MB at the defaults, 268 MB a layer at 128 slots:
nothing stays in fast memory between calls), for every `--slots` entry;
`--live` is the share of the slots that are active (the others arrive
with `beta = 0, g = 0`, as the engine hands them over). Prints, a row
each, milliseconds a call, microseconds a slot, the share of
`kda_roofline.channel_gated_delta_step`'s least time (every slot's state
read once and written once: the kernel sweeps all slots, live or not, so
the share is of ALL slots' bytes here) and the largest gap of output and
state to `delta_step`; `--xla` times that XLA form too. `call_ms` is the
jitted ENTRY by the host's clock, whatever XLA does to the operands
around the kernel included; beside it `kernel_ms`, the custom call alone
(`kda_roofline.is_step_kernel`'s events in a device trace of `--iters`
more steps), `device_ms`, every operation of the traced steps, and
`entry_not_kernel_pct`, the share of the device's time a call spends
outside the kernel. The rows also go
to `chiprun_out/kda_step_bench.json`. Needs a TPU; `--interpret` runs
the kernel in interpret mode on any backend and prints no time as a
device's (a rehearsal of the tool, not a measurement).
"""
from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def make_case(S: int, H: int, dk: int, dv: int, dtype, live: float, rng):
    """One layer's operands as the mixer hands them to the step: unit
    keys, scaled unit queries, log decays in (-5, 0), beta in (0, 1);
    the slots past `live * S` inactive."""
    import jax.numpy as jnp
    import numpy as np

    def unit(shape):
        x = rng.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    on = (np.arange(S) < round(live * S)).astype(np.float32)
    return (jnp.asarray(rng.standard_normal((S, dk, H * dv)), jnp.float32),
            jnp.asarray(unit((S, H, dk)) * dk ** -0.5, jnp.float32),
            jnp.asarray(unit((S, H, dk)), jnp.float32),
            jnp.asarray(rng.standard_normal((S, H, dv)), dtype),
            jnp.asarray(-5.0 * rng.random((S, H, dk))
                        * on[:, None, None], jnp.float32),
            jnp.asarray(rng.random((S, H)) * on[:, None], jnp.float32))


def bench(args) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import delta_rule, pallas_delta_step
    from perfbench.harness import device, kda_roofline, roofline

    dev = device.describe()
    if not args.interpret:
        device.require_chips(1)
    dtype = jnp.dtype(args.dtype)
    H, dk, dv = args.heads, args.key_dim, args.value_dim
    rng = np.random.default_rng(args.seed)
    forms = {"kda_step": functools.partial(pallas_delta_step.kda_step,
                                           interpret=args.interpret)}
    if args.xla:
        forms["delta_step"] = delta_rule.delta_step
    rows = []
    for S in args.slots:
        cases = [make_case(S, H, dk, dv, dtype, args.live, rng)
                 for _ in range(args.calls)]
        states = [c[0] for c in cases]
        operands = [c[1:] for c in cases]
        want_o, want_s = delta_rule.delta_step(*cases[0])
        want_o, want_s = np.asarray(want_o, np.float32), np.asarray(want_s)
        ops, nbytes = kda_roofline.channel_gated_delta_step(
            S, 1, H, dk, dv, dtype.itemsize)
        for name, form in forms.items():

            @functools.partial(jax.jit, donate_argnums=0)
            def step(states, operands):
                outs = [form(s, *x) for s, x in zip(states, operands)]
                return [s for _, s in outs], [o for o, _ in outs]

            mine, out = step([s + 0.0 for s in states], operands)
            jax.block_until_ready(out)
            row = {"form": name, "slots": S, "live": args.live,
                   "gap_o": float(np.max(np.abs(
                       np.asarray(out[0], np.float32) - want_o))),
                   "gap_state": float(np.max(np.abs(
                       np.asarray(mine[0]) - want_s)))}
            t0 = time.perf_counter()
            for _ in range(args.iters):
                mine, out = step(mine, operands)
            jax.block_until_ready(out)
            call_s = (time.perf_counter() - t0) / args.iters / args.calls
            if not args.interpret:
                row.update(
                    call_ms=1e3 * call_s, slot_us=1e6 * call_s / S,
                    gb_per_s=nbytes / call_s / 1e9,
                    roofline_pct=roofline.share_pct(
                        ops, nbytes, call_s, device.peaks(dev["kind"])))
            mine = traced(row, step, mine, operands, args)
            rows.append(row)
            print(json.dumps(row), flush=True)
            del mine, out
    return dev, rows


def traced(row: dict, step, mine, operands, args):
    """`--iters` more steps under the profiler: `device_times` of their
    trace into `row`. Returns the states the last step left."""
    import jax

    from perfbench.harness import trace_reduce

    trace_dir = Path(args.out).parent / ".kda_step_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    for _ in range(args.iters):
        mine, out = step(mine, operands)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    view = trace_reduce.TraceView(trace_reduce.load_xplane(str(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    row.update(device_times(view, args.iters * args.calls))
    return mine


def device_times(view, calls: int) -> dict:
    """Of a device trace of `calls` calls of the entry: `device_ms`, the
    time a call in which any operation ran; `kernel_ms`, the custom call
    alone (`kda_roofline.is_step_kernel`), and `entry_not_kernel_pct`,
    what of the device's time the entry spends around it. Nothing where
    the trace holds no device (`--interpret`), no kernel rows for a form
    without the kernel."""
    from perfbench.harness import kda_roofline, trace_reduce

    if not view.chips:
        return {}
    out = {"device_ms": 1e3 * view.busy_s() / calls}
    kernel = sum(e["dur_ns"] for e in view.events
                 if e["plane"] == view.chips[0]
                 and e["line"] == trace_reduce.OPS_LINE
                 and kda_roofline.is_step_kernel(e["name"])) / 1e6 / calls
    if kernel:
        out.update(kernel_ms=kernel, entry_not_kernel_pct=100.0 * (
            1.0 - kernel / out["device_ms"]))
    return out


def main(argv=None) -> int:
    ints = lambda s: [int(x) for x in s.split(",")]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=ints, default=[128, 64])
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--key-dim", type=int, default=128)
    ap.add_argument("--value-dim", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--live", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "kda_step_bench.json"))
    args = ap.parse_args(argv)
    dev, rows = bench(args)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"device": dev, "interpret": args.interpret, "rows": rows},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
