"""What decides `correct`: each number compared, beside its limit.

A cell's file (`cells/<cell>.json`) gives the limit of every number its
comparison reads; a number with no limit is an error, not a pass. PERF.md
gives, for each limit, the readings it was set from.
"""
from __future__ import annotations

import math
import statistics
import sys


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, compared): `compared` holds each number with its limit,
    under short plain names; `correct` is false where any number is over
    its limit or is no number at all."""
    compared, correct = {}, bool(numbers)
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"the cell's file gives no limit for {name!r}")
        value = float(value)
        ok = math.isfinite(value) and value <= float(limits[name])
        compared[name] = {"value": value, "limit": float(limits[name])}
        correct = correct and ok
    return correct, compared


def report(compared: dict, correct: bool) -> None:
    """The numbers compared, as the last lines on standard error."""
    print(f"perfbench: correct={str(correct).lower()}", file=sys.stderr)
    for name, c in compared.items():
        mark = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"perfbench: compared {name} = {c['value']:.6g} "
              f"(limit {c['limit']:.6g}) {mark}", file=sys.stderr)
    sys.stderr.flush()


def norm_gaps(program: dict, reference: dict) -> tuple:
    """Leaf by leaf, the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some leaves' gradients are all
    but zero). Returns (worst gap, its leaf, the median leaf's gap)."""
    if set(program) != set(reference):
        raise KeyError("the program and the reference name different leaves")
    floor = statistics.median(reference.values())
    gaps = {leaf: abs(program[leaf] - ref) / max(ref, floor)
            for leaf, ref in reference.items()}
    broken = [leaf for leaf, g in gaps.items() if not math.isfinite(g)]
    if broken:
        return math.inf, broken[0], math.inf
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, statistics.median(gaps.values())
