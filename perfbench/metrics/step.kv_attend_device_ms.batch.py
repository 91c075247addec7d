"""Device time of the paged-attention kernel per decode step. The
kernel's operation carries the name of the scope it is called in,
`kv.attend`, where the program calls it inline, and the name of its
jitted entry, `paged_attention`, where it has one."""
from perfbench.harness.readers import DECODE_CHUNKED, DECODE_STEP
from perfbench.harness.trace_reduce import is_pallas_kernel, op_name

KERNELS = ("kv.attend", "paged_attention")


def is_attend_kernel(event_name: str) -> bool:
    return is_pallas_kernel(event_name) \
        and op_name(event_name).startswith(KERNELS)


def read(run):
    if run.trace is None or not run.traced:
        return None
    n_chunked, _ = run.trace.program_seconds(DECODE_CHUNKED)
    n_single, _ = run.trace.program_seconds(DECODE_STEP)
    steps = n_chunked * run.facts["decode_chunk"] + n_single
    seconds = run.trace.op_seconds_within((DECODE_CHUNKED, DECODE_STEP),
                                          is_attend_kernel)
    return 1e3 * seconds / steps if steps and seconds else None
