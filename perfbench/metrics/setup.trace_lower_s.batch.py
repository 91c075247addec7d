"""Seconds JAX spent tracing functions and lowering them to StableHLO
before the window's opening, since the engine was constructed: `trace_s`
+ `lower_s` of the engine's `stats()["compile"]`, the program's account
of JAX's own monitoring events (sums of events: a jitted function traced
inside another's trace counts in both). Paid on every start, compile
cache or not. A program without the account reads as nothing."""


def read(run):
    c = (run.facts.get("stats_before") or {}).get("compile")
    if c is None:
        return None
    return c["trace_s"] + c["lower_s"]
