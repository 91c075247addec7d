"""The five `setup.*` readers on a hand-made `stats_before` (the engine's
`stats()` at the window's opening): each reads the counters the program
keeps of its own set-up, finds nothing, without raising, where the
program keeps none (a parent commit), and the manifest lists them for the
four serve cells and not for the train cell."""
import pytest

from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

NAMES = ("setup.build_s.batch", "setup.weight_hash_s.batch",
         "setup.trace_lower_s.batch", "setup.backend_compile_s.batch",
         "setup.warm_and_fill_s.batch")
SERVE = ("cgpt1.3b-serve-batch", "granite4hs-serve-chat",
         "olmohyb7b-serve-chat", "nemo3nano-serve-chat")
LOOP = {"iterations": 40, "wait-work_s": 1.5, "wait-work_n": 3,
        "admit_s": 0.25, "admit_n": 40, "housekeeping_s": 0.125,
        "housekeeping_n": 40, "prefill.dispatch_s": 20.0,
        "prefill.dispatch_n": 35, "prefill.wait_s": 2.0,
        "prefill.wait_n": 35, "prefill.deliver_s": 0.5,
        "prefill.deliver_n": 35, "decode.dispatch_s": 8.0,
        "decode.dispatch_n": 30, "decode.wait_s": 1.0, "decode.wait_n": 30,
        "decode.deliver_s": 0.625, "decode.deliver_n": 30,
        "sink_s": 0.375, "sink_n": 99, "ahead_n": 60, "drained_n": 0,
        "spans_dropped": 0}
BUILD = {"build.plan_s": 0.5, "build.plan_n": 3, "build.weights_s": 1.25,
         "build.weights_n": 1, "build.weight_hash_s": 9.0,
         "build.weight_hash_n": 1, "build.state_s": 0.75,
         "build.state_n": 1, "builds": 1, "weight_hash_bytes": 5_700_000_000}
COMPILE = {"trace_s": 4.5, "trace_n": 900, "lower_s": 2.25, "lower_n": 40,
           "backend_s": 3.5, "backend_n": 40, "cache_load_s": 3.0,
           "cache_load_n": 38, "cache_hits": 38, "cache_misses": 0,
           "by_fun": {"decode_step": {"trace_s": 1.0, "trace_n": 1,
                                      "lower_s": 0.5, "lower_n": 1,
                                      "backend_s": 0.25, "backend_n": 1}}}


def _run(**stats_before):
    return Run(workload="w", kind="closed", chips=1,
               device_kind="TPU v5 lite", sizes={}, mix={}, setup_s=60.0,
               window_s=40.0, setup_compile={}, window_programs=0,
               facts={"t_open": 0.0, "t_close": 40.0,
                      "stats_before": stats_before})


@pytest.mark.parametrize("name, value", zip(NAMES, (
    0.5 + 1.25 + 9.0 + 0.75, 9.0, 4.5 + 2.25, 3.5,
    1.5 + 0.25 + 0.125 + 20.0 + 2.0 + 0.5 + 8.0 + 1.0 + 0.625)))
def test_each_reads_its_counters(name, value):
    read = Manifest().reader(name)
    assert read(_run(loop=LOOP, build=BUILD, compile=COMPILE)) == value


@pytest.mark.parametrize("name", NAMES)
def test_each_finds_nothing_where_the_program_keeps_no_such_counter(name):
    read = Manifest().reader(name)
    assert read(_run(served=3)) is None
    run = _run()
    del run.facts["stats_before"]  # the fit runner keeps no `stats()`
    assert read(run) is None


def test_the_parent_reads_warm_and_fill_alone():
    # `loop` is older than this account: a parent's run has it
    values = {n: Manifest().reader(n)(_run(loop=LOOP)) for n in NAMES}
    assert values.pop("setup.warm_and_fill_s.batch") == 34.0
    assert set(values.values()) == {None}


def test_the_parts_lie_inside_what_holds_them():
    run = _run(loop=LOOP, build=BUILD, compile=COMPILE)
    v = {n: Manifest().reader(n)(run) for n in NAMES}
    assert v["setup.weight_hash_s.batch"] <= v["setup.build_s.batch"]
    assert v["setup.build_s.batch"] + v["setup.warm_and_fill_s.batch"] \
        <= run.setup_s


def test_the_manifest_lists_them_for_the_serve_cells_alone():
    m = Manifest(ROOT / "BENCHMARK.json")
    assert check(m) == []
    for cell in SERVE + ("cgpt590m-train-t2048",):
        names = {x["name"] for x in m.metrics_of(cell, "per_layer")}
        assert (set(NAMES) <= names) is (cell in SERVE)
        assert (not set(NAMES) & names) is (cell not in SERVE)
    for x in m.raw["per_layer"]:
        if x["name"] in NAMES:
            assert (x["layer"], x["moves"], x["unit"], x["better"],
                    x["source"]) == ("start-up", "setup_s", "s", "lower",
                                     "program_counter")
            assert tuple(x["workloads"]) == SERVE
