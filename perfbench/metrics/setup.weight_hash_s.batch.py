"""Seconds `_build` spent hashing the net's parameters into the engine's
`_weight_version` (every leaf through the host): the `build.weight_hash_s`
counter of the engine's `stats()["build"]` at the window's opening, a
part of `setup.build_s.batch`. A program without the counter reads as
nothing."""


def read(run):
    build = (run.facts.get("stats_before") or {}).get("build") or {}
    return build.get("build.weight_hash_s")
