"""What paged attention has to do in one decode step of a net whose K/V
blocks read a WINDOW of their context (or all of it), from the engine's
own count of the positions attended: the operations and bytes the
algorithm needs at the least (not what an implementation happens to
do), and the reader of `window_attention_roofline.longgen`.

Least bytes, over every live slot and every K/V block: the keys and the
values of every position inside the block's span once (`min(context,
window)` positions for a window block, the context for a full one: the
engine sums them as `loop.kv_positions_attended`, and writes each
dispatch's on its `decode.dispatch` span, from which the roofline takes
the dispatches of the traced stretch alone), `2 * Hkv * hd` elements a
position, plus the queries in and the output out. Least
operations: `q . k` and `p . v` for every query head, `2 * 2 * H * hd` a
position. At 128 query heads over 8 K/V heads of 128 that is 16
operations a byte against the chip's 240: memory-bound, and the kernel
copies whole pages (the first and the last of a span partly masked), so
it can only read more than this and its share cannot pass 100%: the
positions and the kernel's seconds are of the same stretch. A program
without the counter or the spans' counts, a trace without the kernel, a
run without a trace or another family's sizes read as nothing.
"""
from __future__ import annotations

from perfbench.harness import device, program_timeline, roofline
from perfbench.harness.readers import DECODE_CHUNKED, DECODE_STEP
from perfbench.harness.trace_reduce import is_pallas_kernel, op_name

KERNEL = "paged_attention"  # the kernel's jitted entry: its name in a trace
ATTENDED, CONTEXT = "kv_positions_attended", "kv_positions_context"


def paged_window_decode(positions: float, slots: float, blocks: int,
                        heads: int, kv_heads: int, head_dim: int,
                        itemsize: int = 2) -> tuple:
    """(operations, bytes) of one decode step's paged attention:
    `positions` attended, summed over the live slots and the K/V blocks;
    `slots` live slots and `blocks` K/V blocks (the queries in, the
    output out)."""
    ops = 2 * 2 * heads * head_dim * positions
    nbytes = 2 * kv_heads * head_dim * itemsize * positions \
        + 2 * heads * head_dim * itemsize * slots * blocks
    return ops, nbytes


def loop_delta(run, key: str):
    """How far one of the scheduler's cumulative `loop` counters moved
    over the window, or None where the program has no such counter."""
    before = run.facts["stats_before"].get("loop", {})
    after = run.facts["stats_after"].get("loop", {})
    if key not in before or key not in after:
        return None
    return after[key] - before[key]


def attended_pct(run):
    """Of the positions the K/V blocks' contexts hold, the share their
    attention reads (100 on a net without windows)."""
    read, ctx = loop_delta(run, ATTENDED), loop_delta(run, CONTEXT)
    return 100.0 * read / ctx if read is not None and ctx else None


def is_attend_kernel(event_name: str) -> bool:
    return is_pallas_kernel(event_name) \
        and op_name(event_name).startswith(KERNEL)


def traced_dispatches(run):
    """(positions attended, decode steps, slot-steps) of the decode
    dispatches the scheduler issued inside the traced stretch, from the
    `decode.dispatch` spans' own attributes (the engine writes a
    dispatch's `kv_positions_attended` on its span), or None where the
    program's spans carry no such count."""
    spans = program_timeline.program_spans(run.traced["t0"],
                                           run.traced["t1"])
    read = steps = live = 0
    for name, a, b, _cause, _tid, attrs in spans or ():
        if name != "decode.dispatch" or not attrs or ATTENDED not in attrs \
                or a < run.traced["t0"] or b > run.traced["t1"]:
            continue
        read += attrs[ATTENDED]
        steps += attrs["chunk"]
        live += attrs["chunk"] * attrs["active"]
    return (read, steps, live) if steps else None


def roofline_pct(run):
    """The paged-attention calls' device time per decode step in the
    trace against the least the chip could take for the positions the
    steps dispatched in the SAME traced stretch attended (contexts grow
    through a closed loop's window, so the window's mean would not
    do)."""
    sz = run.sizes
    if run.trace is None or not run.traced or not all(
            k in sz for k in ("H", "Hkv", "hd", "window_layers",
                              "full_layers")):
        return None
    n_chunked, _ = run.trace.program_seconds(DECODE_CHUNKED)
    n_single, _ = run.trace.program_seconds(DECODE_STEP)
    traced_steps = n_chunked * run.facts["decode_chunk"] + n_single
    seconds = run.trace.op_seconds_within((DECODE_CHUNKED, DECODE_STEP),
                                          is_attend_kernel)
    issued = traced_dispatches(run)
    if issued is None or not issued[0] or not traced_steps or not seconds:
        return None
    read, steps, live = issued
    ops, nbytes = paged_window_decode(
        read / steps, live / steps, sz["window_layers"] + sz["full_layers"],
        sz["H"], sz["Hkv"], sz["hd"])
    return roofline.share_pct(ops, nbytes, seconds / traced_steps,
                              device.peaks(run.device_kind))
