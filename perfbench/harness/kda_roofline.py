"""What the delta-rule decode step with a decay a key channel (KDA) has
to do in one decode step, from the configuration's sizes and the run's
live slots: the operations and bytes the algorithm needs at the least
(not what an implementation happens to do), and the readers of
`kda_step_roofline` and `step.kda_step_device_ms.reason`.

Least bytes, for every live slot and every linear-attention block: the
matrix state of every head read once and written once in float32 (no
form of the rule can do with less: the decay touches every element),
plus the step's operands in (q and k in float32 as the normalisation
leaves them, the `(H, d_k)` log decay in float32, v in the compute
dtype, beta) and its output out. Least operations: one multiply-add an
element for `S k`, for the rank-one correction and for `S' q`, and the
decay's multiply: 7 a state element, against 8 bytes. A slot that is not
live need not be touched; an implementation that sweeps all slots can
only take longer, so the share cannot pass 100%. A program without the
kernel, a run without a trace or another family's sizes read as
nothing.
"""
from __future__ import annotations

from perfbench.harness import device, roofline
from perfbench.harness.readers import DECODE_CHUNKED, DECODE_STEP
from perfbench.harness.trace_reduce import is_pallas_kernel, op_name

KERNEL = "kda_step"  # the step's jitted entry: its name in a device trace
LINEAR = "linear_attention"


def channel_gated_delta_step(slots: float, blocks: int, heads: int,
                             key_dim: int, value_dim: int,
                             itemsize: int = 2) -> tuple:
    """(operations, bytes) of one decode step's delta-rule updates over
    `blocks` linear-attention blocks and `slots` live slots."""
    state = heads * key_dim * value_dim
    ops = (2 * 3 + 1) * state
    nbytes = 2 * 4 * state \
        + heads * (3 * key_dim * 4 + 2 * value_dim * itemsize + 4)
    return ops * slots * blocks, nbytes * slots * blocks


def is_step_kernel(event_name: str) -> bool:
    return is_pallas_kernel(event_name) \
        and op_name(event_name).startswith(KERNEL)


def _traced(run):
    """(device seconds of the step kernel inside the decode programs'
    traced runs, decode steps those runs made), or None where the trace
    holds neither."""
    if run.trace is None or not run.traced:
        return None
    n_chunked, _ = run.trace.program_seconds(DECODE_CHUNKED)
    n_single, _ = run.trace.program_seconds(DECODE_STEP)
    steps = n_chunked * run.facts["decode_chunk"] + n_single
    seconds = run.trace.op_seconds_within((DECODE_CHUNKED, DECODE_STEP),
                                          is_step_kernel)
    return (seconds, steps) if steps and seconds else None


def step_device_ms(run):
    """Device milliseconds of the step kernel per decode step, all
    linear-attention blocks together."""
    traced = _traced(run)
    return None if traced is None else 1e3 * traced[0] / traced[1]


def roofline_pct(run):
    """The step kernel's device time per decode step in the trace
    against the least the chip could take for the window's live slots
    (memory-bound: 8 bytes of state for 7 operations)."""
    sz, traced = run.sizes, _traced(run)
    if traced is None or not all(
            k in sz for k in ("lh", "lk", "lv", "layer_types")):
        return None
    f = run.facts
    inside = [d for d in f["decodes"]
              if f["t_open"] <= d[0] and d[1] <= f["t_close"]]
    n = sum(c for _, _, c, _, _ in inside)
    blocks = sum(1 for kind in sz["layer_types"] if kind == LINEAR)
    if not n or not blocks:
        return None
    slots = sum(c * a for _, _, c, a, _ in inside) / n
    ops, nbytes = channel_gated_delta_step(slots, blocks, sz["lh"],
                                           sz["lk"], sz["lv"])
    return roofline.share_pct(ops, nbytes, traced[0] / traced[1],
                              device.peaks(run.device_kind))
