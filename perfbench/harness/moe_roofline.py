"""What the grouped expert product has to do in one decode step, from
its shapes and the router's counts: the operations and bytes the
algorithm needs at the least (not what an implementation happens to
do), and the reader of `moe_experts_roofline`.

Least bytes: the three matrices of every held expert that some live
slot chose (an expert nobody chose need not be read), once each, plus
one read of the tokens and one write of the result per block. Least
operations: 2 a multiply-add over the three matrices for each (token,
expert) choice that fell on a held expert; a token need not meet an
expert it did not choose. The kernel reads every held expert and runs
every token through each, so it can only do more than this, and its
share cannot pass 100%.
"""
from __future__ import annotations

from perfbench.harness import device, roofline
from perfbench.harness.trace_reduce import is_pallas_kernel, op_name

KERNEL = "moe_experts"  # the grouped product's operation in a device trace
DECODE_PROGRAMS = ("decode_chunked", "decode_step")


def grouped_experts(experts_hit: float, choices_held: float, tokens: float,
                    blocks: int, d: int, f: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one decode step's routed experts over all
    its blocks: `experts_hit` held experts chosen by some token and
    `choices_held` (token, held expert) choices, both summed over the
    blocks; `tokens` live slots."""
    ops = 2 * 3 * d * f * choices_held
    nbytes = 3 * d * f * itemsize * experts_hit \
        + 2 * tokens * d * itemsize * blocks
    return ops, nbytes


def is_experts_kernel(event_name: str) -> bool:
    return is_pallas_kernel(event_name) \
        and op_name(event_name).startswith(KERNEL)


def stats_delta(run, key: str):
    """How far one of the engine's cumulative counters moved over the
    window, or None where the program has no such counter."""
    before, after = run.facts["stats_before"], run.facts["stats_after"]
    if key not in before or key not in after:
        return None
    return after[key] - before[key]


def roofline_pct(run):
    """The grouped product's device time per decode step in the trace
    against the least the chip could take for the experts the window's
    steps hit (memory-bound at decode sizes)."""
    if run.trace is None or not run.traced:
        return None
    steps = stats_delta(run, "moe_steps")
    if not steps:
        return None
    hit = stats_delta(run, "moe_experts_hit") / steps
    held = stats_delta(run, "moe_held_choices") / steps
    chunk = run.facts["decode_chunk"]
    n_chunked, _ = run.trace.program_seconds(DECODE_PROGRAMS[0])
    n_single, _ = run.trace.program_seconds(DECODE_PROGRAMS[1])
    traced_steps = n_chunked * chunk + n_single
    seconds = run.trace.op_seconds_within(DECODE_PROGRAMS,
                                          is_experts_kernel)
    f = run.facts
    inside = [d for d in f["decodes"]
              if f["t_open"] <= d[0] and d[1] <= f["t_close"]]
    n = sum(c for _, _, c, _, _ in inside)
    if not traced_steps or not seconds or not n:
        return None
    tokens = sum(c * a for _, _, c, a, _ in inside) / n
    sz = run.sizes
    blocks = sz["L"]
    ops, nbytes = grouped_experts(hit, held, tokens, blocks, sz["d"],
                                  sz["f"])
    return roofline.share_pct(ops, nbytes, seconds / traced_steps,
                              device.peaks(run.device_kind))
