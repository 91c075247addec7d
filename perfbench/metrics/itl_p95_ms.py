"""Gap between consecutive tokens, over all gaps of all requests due
inside the window."""
from perfbench.harness import readers


def read(run):
    return readers.p95(readers.inter_token_gaps_ms(run, window_only=False))
