"""What the UNGATED grouped expert product (`relu(x Wu^T)^2 Wd`, two
matrices an expert) has to do in one decode step, from its shapes and
the router's counts: the operations and bytes the algorithm needs at the
least (not what an implementation happens to do), and the readers of
`moe_relu2_experts_roofline` and `step.moe_experts_device_ms.chat`.

Least bytes: the two matrices of every held expert that some live slot
chose (an expert nobody chose need not be read), once each, plus one
read of the tokens and one write of the result per routed block. Least
operations: 2 a multiply-add over the two matrices for each (token,
expert) choice that fell on a held expert; a token need not meet an
expert it did not choose. The kernel reads every held expert and runs
every token through each, so it can only do more than this, and its
share cannot pass 100%. The routed blocks are the `E` letters of the
family's `pattern`: a block of another kind calls no expert kernel.
"""
from __future__ import annotations

from perfbench.harness import device, roofline
from perfbench.harness.moe_roofline import is_experts_kernel, stats_delta
from perfbench.harness.readers import DECODE_CHUNKED, DECODE_STEP

EXPERTS = "E"  # the letter of a routed block in a family's `pattern`


def grouped_relu2_experts(experts_hit: float, choices_held: float,
                          tokens: float, blocks: int, d: int, f: int,
                          itemsize: int = 2) -> tuple:
    """(operations, bytes) of one decode step's routed experts over all
    its routed blocks: `experts_hit` held experts chosen by some token
    and `choices_held` (token, held expert) choices, both summed over
    the blocks; `tokens` live slots."""
    ops = 2 * 2 * d * f * choices_held
    nbytes = 2 * d * f * itemsize * experts_hit \
        + 2 * tokens * d * itemsize * blocks
    return ops, nbytes


def _traced(run):
    """(device seconds of the expert kernel inside the decode programs'
    traced runs, decode steps those runs made), or None where the trace
    holds neither."""
    if run.trace is None or not run.traced:
        return None
    n_chunked, _ = run.trace.program_seconds(DECODE_CHUNKED)
    n_single, _ = run.trace.program_seconds(DECODE_STEP)
    steps = n_chunked * run.facts["decode_chunk"] + n_single
    seconds = run.trace.op_seconds_within((DECODE_CHUNKED, DECODE_STEP),
                                          is_experts_kernel)
    return (seconds, steps) if steps and seconds else None


def step_device_ms(run):
    """Device milliseconds of the expert kernel per decode step."""
    traced = _traced(run)
    return None if traced is None else 1e3 * traced[0] / traced[1]


def roofline_pct(run):
    """The ungated grouped product's device time per decode step in the
    trace against the least the chip could take for the experts the
    window's steps hit (memory-bound at decode sizes)."""
    sz, traced = run.sizes, _traced(run)
    steps = stats_delta(run, "moe_steps")
    blocks = str(sz.get("pattern", "")).count(EXPERTS)
    if traced is None or not steps or not blocks:
        return None
    hit = stats_delta(run, "moe_experts_hit") / steps
    held = stats_delta(run, "moe_held_choices") / steps
    f = run.facts
    inside = [d for d in f["decodes"]
              if f["t_open"] <= d[0] and d[1] <= f["t_close"]]
    n = sum(c for _, _, c, _, _ in inside)
    if not n:
        return None
    tokens = sum(c * a for _, _, c, a, _ in inside) / n
    ops, nbytes = grouped_relu2_experts(hit, held, tokens, blocks, sz["d"],
                                        sz["f"])
    return roofline.share_pct(ops, nbytes, traced[0] / traced[1],
                              device.peaks(run.device_kind))
