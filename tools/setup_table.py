#!/usr/bin/env python3
"""Where one run's set-up went, from the program's own account:

    python3 tools/setup_table.py --workload <cell> --seed <n> [--seconds 40] [--trace 1]

Runs one serve cell of `BENCHMARK.json` as `perfbench/run.py` does (same
harness call, same clock from the process's start) and prints, beside
the cell's metrics and a traced run's `breakdown`, `setup_s` cut into
what lies outside the program, `_build`'s four phases and the scheduler thread's warm-up and filling
(`weight_hash`: the fold of the served leaves on the device, its bytes,
the bytes of them that crossed to the host and the rate);
JAX's compile pipeline by function and by the phase each event fell in;
what the spans and events of set-up numbered, and what one costs here;
the process's resident set after the run.
The whole table also goes to `chiprun_out/setup_table.<cell>.<seed>.json`.
Needs a TPU, as the benchmark does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _union_s(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def spans_of_setup(t_open: float) -> dict:
    """The timeline's `build.*` and `compile.*` spans that ended before
    the window opened: each compile span under the build or scheduler
    phase of its own thread that its end fell in, and the stretch of
    each thread in which something was being traced."""
    from deeplearning4j_tpu.serving import observability as obs

    spans = [s for s in obs.TIMELINE.snapshot(t1=t_open) if s[2] <= t_open]
    phases = collections.defaultdict(list)
    for s in spans:
        if s[0] not in obs.COMPILE_SPANS:
            phases[s[4]].append(s)
    under = collections.defaultdict(lambda: [0.0, 0])
    traced = collections.defaultdict(list)
    for name, t0, t1, _, tid, _ in spans:
        if name not in obs.COMPILE_SPANS:
            continue
        holder = next((p[0] for p in phases[tid] if p[1] <= t1 <= p[2]),
                      "no phase")
        row = under[f"{holder} > {name}"]
        row[0] += t1 - t0
        row[1] += 1
        if name == "compile.trace":
            traced[tid].append((t0, t1))
    return {
        "spans": len(spans),
        "spans_by_name": dict(collections.Counter(s[0] for s in spans)),
        "build_spans": [(s[0], s[2] - s[1], s[5]) for s in spans
                        if s[0] in obs.BUILD_PHASES],
        "compile_under": dict(sorted(under.items())),
        "trace_union_s": sum(_union_s(v) for v in traced.values()),
        "dropped": obs.TIMELINE.dropped}


def resident_set_bytes() -> int | None:
    """`VmRSS` of this process now, where `/proc/self/status` gives it
    (a sealed machine's may leave fields out)."""
    for line in Path("/proc/self/status").read_text().splitlines():
        if line.startswith("VmRSS:"):
            return int(line.split()[1]) * 1024
    return None


def cost_of_one(n: int = 20000) -> dict:
    """Seconds one compile event costs its listener, and one phase
    change, spans on and off, on a timeline of their own."""
    import os

    from deeplearning4j_tpu.serving import observability as obs

    out = {}
    for label, off in (("on", ""), ("off", "1")):
        os.environ["DL4J_TPU_NO_TRACING"] = off
        account = obs.CompileAccount(obs.Timeline())
        t = time.perf_counter()
        for i in range(n):
            account.on_duration("/jax/core/compile/jaxpr_trace_duration",
                                1e-4, fun_name=f"f{i % 80}")
        out[f"compile_event_{label}_s"] = (time.perf_counter() - t) / n
        ph = obs.ThreadPhases(obs.BUILD_PHASES, obs.Timeline())
        ph.begin_iteration()
        t = time.perf_counter()
        for i in range(n):
            ph.enter(obs.BUILD_PHASES[i % 4])
        out[f"phase_change_{label}_s"] = (time.perf_counter() - t) / n
        ph.close()
    del os.environ["DL4J_TPU_NO_TRACING"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    args.control = 0

    from perfbench.harness import device, result
    from perfbench.harness.cell import run_cell
    from perfbench.harness.manifest import Manifest

    manifest = Manifest(ROOT / "BENCHMARK.json")
    try:
        out = run_cell(manifest, args, t_start=T_START)
    except device.NoChipError as e:
        print(f"setup_table: {e}", file=sys.stderr)
        return 3
    run = out["run"]
    before = run.facts["stats_before"]
    build, comp, loop = before["build"], before["compile"], before["loop"]
    build_s = manifest.reader("setup.build_s.batch")(run)
    loop_s = manifest.reader("setup.warm_and_fill_s.batch")(run)
    by_fun = sorted(comp["by_fun"].items(),
                    key=lambda kv: -sum(v for k, v in kv[1].items()
                                        if k.endswith("_s")))
    table = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "correct": out["correct"], "failed": out["failed"],
        "breakdown": out.get("breakdown"),
        "device": out["device"],
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "end_to_end": {k: v["value"] for k, v in result.metric_values(
            manifest, args.workload, "end_to_end", run).items()},
        "setup_s": run.setup_s,
        "outside_the_program_s": run.setup_s - build_s - loop_s,
        "build_s": build_s, "warm_and_fill_s": loop_s,
        "build": build,
        "weight_hash": {
            "s": build["build.weight_hash_s"],
            "bytes": build["weight_hash_bytes"],
            # absent at a commit whose hash pulls every leaf to the host
            "host_bytes": build.get("weight_hash_host_bytes"),
            "GB_per_s": build["weight_hash_bytes"] / 1e9
            / max(build["build.weight_hash_s"], 1e-9)},
        "host_rss_after_the_run_bytes": resident_set_bytes(),
        "loop_at_open": {k: v for k, v in loop.items() if v},
        "compile": {k: v for k, v in comp.items() if k != "by_fun"},
        "by_fun": by_fun[:16], "by_fun_names": len(by_fun),
        "harness_meter_at_open": run.setup_compile,
        "timeline": spans_of_setup(run.facts["t_open"]),
        "cost_of_one": cost_of_one()}
    events = sum(comp[k + "_n"] for k in ("trace", "lower", "backend",
                                          "cache_load")) \
        + comp["cache_hits"] + comp["cache_misses"]
    cost = table["cost_of_one"]
    changes = sum(v for k, v in build.items()
                  if k.startswith("build.") and k.endswith("_n"))
    table["instrumentation"] = {
        "compile_events": events, "phase_changes": changes,
        "estimate_s": events * cost["compile_event_on_s"]
        + changes * cost["phase_change_on_s"]}
    path = ROOT / "chiprun_out" / \
        f"setup_table.{args.workload}.{args.seed}.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(table, indent=1))
    print("setup_table: " + json.dumps(table), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
