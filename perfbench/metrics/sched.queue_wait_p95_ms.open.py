"""From when a request was due to when its prefill was dispatched."""
from perfbench.harness import readers


def read(run):
    return readers.p95([1e3 * (r["prefill_pre"] - r["due"])
                        for r in run.facts["requests"]
                        if r["prefill_pre"] is not None])
