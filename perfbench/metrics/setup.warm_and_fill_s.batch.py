"""The scheduler thread's lifetime at the window's opening: every
`<phase>_s` of the engine's `stats()["loop"]`, waits included. The
thread starts when `_build` has ended and the window opens when every
slot decodes, so this is set-up's warm-up dispatches (with what they
traced, lowered and loaded) and the filling of the slots. A program
without the `loop` counters reads as nothing."""


def read(run):
    loop = (run.facts.get("stats_before") or {}).get("loop")
    if loop is None:
        return None
    return sum(v for k, v in loop.items()
               if k.endswith("_s") and k != "sink_s")
