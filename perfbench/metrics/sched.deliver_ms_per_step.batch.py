"""Host milliseconds spent delivering tokens (retire-or-poison, emit,
the consumers' sinks) per decode step, from the `loop` counters."""
from perfbench.harness import program_timeline


def read(run):
    return program_timeline.ratio(run, "decode.deliver_s", "decode_steps",
                                  1e3)
