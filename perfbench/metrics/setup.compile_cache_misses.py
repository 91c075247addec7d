"""Programs that set-up had to compile because the persistent cache did
not hold them (0 in every run of a checkout but its first)."""


def read(run):
    return run.setup_compile["cache_misses"]
