"""The last line of standard output, as the driver reads it."""
from __future__ import annotations

import json
import sys


def metric_values(manifest, workload: str, section: str, run) -> dict:
    """Every metric of `section` that this cell reports and whose reader
    finds something to read, as {"value", "unit"}."""
    out = {}
    for m in manifest.metrics_of(workload, section):
        value = manifest.reader(m["name"])(run)
        if value is None:
            print(f"perfbench: {m['name']}: nothing to read",
                  file=sys.stderr)
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, compared: dict, breakdown=None) -> None:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared  # last, as the contract asks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
