"""What the metric readers share: each metric's file under `metrics/` is a
few lines that call into here with its own parameters."""
from __future__ import annotations

from perfbench.harness import device, roofline
from perfbench.harness.trace_reduce import is_pallas_kernel
from perfbench.harness.runrecord import percentile

# how the program's compiled steps are named in a device trace
# (`jit_<function>`), and what marks its Pallas kernels among the
# operations of a program
TRAIN_STEP = ("step",)
DECODE_CHUNKED, DECODE_STEP, PREFILL = "decode_chunked", "decode_step", \
    "prefill"


def in_window(run, times):
    a, b = run.facts["t_open"], run.facts["t_close"]
    return [t for t in times if a <= t < b]


def inter_token_gaps_ms(run, window_only: bool) -> list:
    """Gaps between one request's consecutive tokens, over all requests;
    with `window_only`, the gaps that end inside the window."""
    a, b = run.facts["t_open"], run.facts["t_close"]
    gaps = []
    for r in run.facts["requests"]:
        t = r["token_t"]
        gaps += [1e3 * (t[i] - t[i - 1]) for i in range(1, len(t))
                 if not window_only or a <= t[i] < b]
    return gaps


def per_run_ms(run, programs, per_run_steps=None):
    """Device milliseconds per step of `programs`' whole runs in the
    trace; `per_run_steps` gives the steps one run of each makes."""
    if run.trace is None:
        return None
    steps = seconds = 0.0
    for p in programs:
        n, s = run.trace.program_seconds(p)
        steps += n * (per_run_steps or {}).get(p, 1)
        seconds += s
    return 1e3 * seconds / steps if steps else None


def families_engaged(run):
    v = run.facts.get("kernel_verdicts")
    if v is None:
        return None
    return float(sum(1 for classes in v.values() if any(classes.values())))


def flash_roofline_pct(run):
    """Flash attention's device time in the traced steps against the
    least the chip could take for the forward and backward passes of
    every layer (compute-bound at these shapes)."""
    if run.trace is None or not run.traced:
        return None
    n_steps = sum(run.trace.program_seconds(p)[0] for p in TRAIN_STEP)
    seconds = run.trace.op_seconds_within(TRAIN_STEP, is_pallas_kernel)
    if not n_steps or not seconds:
        return None
    sz, mix = run.sizes, run.mix
    shape = (mix["batch"], sz["H"], mix["seq_len"], sz["hd"])
    ops = nbytes = 0
    # with rematerialisation the step runs the forward kernel twice
    for count, calls in ((roofline.flash_forward, 2 if mix["remat"] else 1),
                         (roofline.flash_backward, 1)):
        o, b = count(*shape)
        ops, nbytes = ops + calls * o, nbytes + calls * b
    layers = sz["L"] * n_steps
    return roofline.share_pct(ops * layers, nbytes * layers, seconds,
                              device.peaks(run.device_kind))


def paged_roofline_pct(run):
    """Paged attention's device time per decode step in the trace
    against the least the chip could take to read the cached keys and
    values that the traced steps attended (memory-bound)."""
    if run.trace is None or not run.traced:
        return None
    chunk = run.facts["decode_chunk"]
    n_chunked, _ = run.trace.program_seconds(DECODE_CHUNKED)
    n_single, _ = run.trace.program_seconds(DECODE_STEP)
    steps = n_chunked * chunk + n_single
    seconds = run.trace.op_seconds_within((DECODE_CHUNKED, DECODE_STEP),
                                          is_pallas_kernel)
    # positions attended per step, from the dispatches the hooks saw in
    # the traced stretch: a chunk's j-th step sees j more per live slot
    ctx = n = 0
    for pre, post, c, active, context in run.facts["decodes"]:
        if pre >= run.traced["t0"] and post <= run.traced["t1"]:
            ctx += sum(context + j * active for j in range(c))
            n += c
    if not steps or not seconds or not n:
        return None
    sz = run.sizes
    ops, nbytes = roofline.paged_decode(ctx / n, sz["H"], sz["H"], sz["hd"])
    per_step = seconds / steps
    return roofline.share_pct(ops * sz["L"], nbytes * sz["L"], per_step,
                              device.peaks(run.device_kind))


def p95(values):
    return percentile(values, 95.0)
