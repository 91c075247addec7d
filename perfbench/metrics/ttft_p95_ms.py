"""Time to first token, from when the request was due, over all requests
due inside the window."""
from perfbench.harness import readers


def read(run):
    return readers.p95([1e3 * (r["token_t"][0] - r["due"])
                        for r in run.facts["requests"] if r["token_t"]])
