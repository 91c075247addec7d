"""Gap between a request's consecutive tokens, over the gaps that end
inside the window: one fused decode chunk, or a chunk and a prefill."""
from perfbench.harness import readers


def read(run):
    return readers.p95(readers.inter_token_gaps_ms(run, window_only=True))
