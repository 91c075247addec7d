"""Seconds the engine's `_build` took, all builds before the window's
opening (one, in a cell without a weight swap): the four `build.*_s`
phase counters of the engine's `stats()["build"]`, which partition the
calling thread's time inside `_build`. A program without the counters
reads as nothing."""


def read(run):
    build = (run.facts.get("stats_before") or {}).get("build")
    if build is None:
        return None
    return sum(v for k, v in build.items()
               if k.startswith("build.") and k.endswith("_s"))
