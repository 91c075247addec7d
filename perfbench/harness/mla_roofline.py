"""What paged latent attention has to do in one decode step, from its
shapes and the live contexts: the operations and bytes the absorbed
algorithm needs at the least (not what an implementation happens to do),
and the readers of `mla_attend_roofline` and
`step.mla_attend_device_ms.reason`.

Least bytes: a sub-layer's cached latent of every live position of every
live slot, once (`kv_rank + rope` numbers: all heads read the same
vector), plus each live slot's absorbed queries in and its latent-space
values out. Least operations: 2 a multiply-add, for every head and live
position the score over `kv_rank + rope` and the value sum over
`kv_rank`. A page is read whole and a slot's last page is part empty, so
the kernel can only do more than this, and its share cannot pass 100%.
Positions come from the decodes' live contexts, as the benchmark's own
hooks counted them, not from the pages the program says it walked.
"""
from __future__ import annotations

from perfbench.harness import device, roofline
from perfbench.harness.trace_reduce import is_pallas_kernel, op_name

KERNEL = "mla_attend"  # the kernel's jitted entry: its name in a trace
DECODE_PROGRAMS = ("decode_chunked", "decode_step")


def latent_decode(positions: float, slots: float, heads: int, kv_rank: int,
                  rope: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of ONE sub-layer's absorbed decode attention
    over `positions` cached positions, summed over the `slots` live
    slots of a step."""
    ops = 2 * heads * ((kv_rank + rope) + kv_rank) * positions
    nbytes = (kv_rank + rope) * itemsize * positions \
        + slots * heads * ((kv_rank + rope) + kv_rank) * itemsize
    return ops, nbytes


def is_attend_kernel(event_name: str) -> bool:
    return is_pallas_kernel(event_name) \
        and op_name(event_name).startswith(KERNEL)


def _traced(run):
    """(device seconds of the kernel inside the decode programs' traced
    runs, decode steps those runs made), or None where the trace holds
    neither: a program without the kernel reads as nothing."""
    if run.trace is None or not run.traced:
        return None
    n_chunked, _ = run.trace.program_seconds(DECODE_PROGRAMS[0])
    n_single, _ = run.trace.program_seconds(DECODE_PROGRAMS[1])
    steps = n_chunked * run.facts["decode_chunk"] + n_single
    seconds = run.trace.op_seconds_within(DECODE_PROGRAMS, is_attend_kernel)
    return (seconds, steps) if steps and seconds else None


def step_device_ms(run):
    """Device milliseconds of the kernel per decode step, all sub-layers
    together."""
    traced = _traced(run)
    return None if traced is None else 1e3 * traced[0] / traced[1]


def roofline_pct(run):
    """The kernel's device time per decode step in the trace against the
    least the chip could take for the positions the traced steps
    attended."""
    traced, sz = _traced(run), run.sizes
    if traced is None or not all(k in sz for k in ("kr", "rope", "H", "L")):
        return None
    # positions attended and live slots per step, from the dispatches the
    # hooks saw in the traced stretch: a chunk's j-th step sees j more
    # positions per live slot
    ctx = live = n = 0
    for pre, post, c, active, context in run.facts["decodes"]:
        if pre >= run.traced["t0"] and post <= run.traced["t1"]:
            ctx += sum(context + j * active for j in range(c))
            live += c * active
            n += c
    if not n:
        return None
    ops, nbytes = latent_decode(ctx / n, live / n, sz["H"], sz["kr"],
                                sz["rope"])
    sub_layers = 2 * sz["L"]
    return roofline.share_pct(ops * sub_layers, nbytes * sub_layers,
                              traced[0] / traced[1],
                              device.peaks(run.device_kind))
