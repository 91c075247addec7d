"""Seconds the backend spent giving JAX its executables before the
window's opening, since the engine was constructed: `backend_s` of the
engine's `stats()["compile"]`: a compile where the persistent cache did
not hold the program, the cache's read and the executable's load where
it did. A program without the account reads as nothing."""


def read(run):
    c = (run.facts.get("stats_before") or {}).get("compile") or {}
    return c.get("backend_s")
