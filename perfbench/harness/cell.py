"""One run of one cell, without the printing: what `run.py` drives and
what the tests drive with the look for a chip skipped."""
from __future__ import annotations

import dataclasses
import shutil
from pathlib import Path

from perfbench.harness import device, result, trace_reduce
from perfbench.harness.manifest import Manifest


@dataclasses.dataclass
class Context:
    manifest: Manifest
    workload: dict
    config: dict
    mix: dict
    cell: dict
    seed: int
    seconds: float
    trace: bool
    control: bool
    chips: int
    device: dict
    meter: device.CompileMeter
    t_start: float
    trace_dir: Path

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))

    def stop_trace(self) -> trace_reduce.TraceView:
        import jax

        jax.profiler.stop_trace()
        view = trace_reduce.TraceView(
            trace_reduce.load_xplane(str(self.trace_dir)))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return view


def run_cell(manifest: Manifest, args, *, look_for_chip: bool = True,
             t_start: float) -> dict:
    """Everything of a run but the printing. `look_for_chip=False` is
    for the tests alone: it skips the look for a TPU and drives the rest
    of the run on whatever device JAX has."""
    workload = manifest.workload(args.workload)
    dev = device.require_chips(workload["chips"]) if look_for_chip \
        else device.describe()
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    config = manifest.config(workload["config"])
    mix = manifest.traffic(workload["traffic"])
    ctx = Context(
        manifest=manifest, workload=workload, config=config, mix=mix,
        cell=manifest.cell(workload["name"]), seed=int(args.seed),
        seconds=float(args.seconds), trace=bool(int(args.trace)),
        control=bool(int(args.control)), chips=workload["chips"], device=dev,
        meter=device.CompileMeter(), t_start=t_start,
        trace_dir=manifest.root / ".perfbench_trace" / workload["name"])
    out = manifest.runner(mix["kind"])(ctx)
    run = out["run"]
    section = "per_layer" if ctx.trace else "end_to_end"
    out["metrics"] = result.metric_values(manifest, workload["name"],
                                          section, run)
    out["device"] = dict(dev, count=workload["chips"],
                         memory_peak_bytes=out["memory_peak_bytes"])
    out["breakdown"] = None
    if ctx.trace and run.trace is not None:
        out["device"].update(busy_s=run.trace.busy_s(),
                             window_s=run.trace.window_s)
        out["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.idle_gaps(10)}
    return out
