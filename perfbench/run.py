#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Makes its inputs and weights from the seed, warms up every shape the cell
uses (set-up), measures for `--seconds`, then checks what the timed path
produced against the configuration's plain reference. The last line of
standard output is the result the driver reads. Exits with another code
than 0, and prints no result, where JAX finds no TPU or fewer chips than
the cell asks for.

`--control 1` (never set by the driver) also computes the comparison's
control: the reference in the program's place, one precision down.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.harness import compare, device, result  # noqa: E402
from perfbench.harness.cell import run_cell  # noqa: E402
from perfbench.harness.manifest import Manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = Manifest(ROOT / "BENCHMARK.json")
    try:
        out = run_cell(manifest, args, t_start=T_START)
    except device.NoChipError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    if out.get("control") is not None:
        ok, judged = compare.verdict(
            out["control"], manifest.cell(args.workload)["limits"])
        print(f"perfbench: control correct={str(ok).lower()} "
              f"{ {k: v['value'] for k, v in judged.items()} }", flush=True)
    compare.report(out["compared"], out["correct"])
    result.emit(correct=out["correct"], attempted=out["attempted"],
                failed=out["failed"], metrics=out["metrics"],
                device=out["device"], compared=out["compared"],
                breakdown=out["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
