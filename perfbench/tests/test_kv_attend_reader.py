"""`step.kv_attend_device_ms.batch` on hand-made device traces: it reads
the attend kernel under the name a program that calls it inline gives
it and under its jitted entry's name, leaves the paged write out, and
finds nothing, without raising, where nothing was traced."""
import pytest

from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

NAME = "step.kv_attend_device_ms.batch"
DEV, HOST = "/device:TPU:0", "/host:CPU"
CALL = ('%{name}.{n} = bf16[32,1,16,128] custom-call(bf16[8] %q), '
        'custom_call_target="tpu_custom_call"')


def _run(kernel, traced=True):
    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    events = [ev(HOST, "python3", "perfbench.window", 0, 30_000_000)]
    for prog, t, calls in (("decode_step", 1e6, 2), ("decode_step", 4e6, 2),
                           ("decode_chunked", 8e6, 8)):
        events.append(ev(DEV, tr.MODULE_LINE, f"jit_{prog}(7)", t,
                         calls * 700_000))
        for j in range(calls):
            events.append(ev(DEV, tr.OPS_LINE, CALL.format(name=kernel, n=j),
                             t + 600_000 * j, 300_000))
            events.append(ev(DEV, tr.OPS_LINE,
                             CALL.format(name="paged_kv_write", n=j),
                             t + 600_000 * j + 300_000, 100_000))
    return Run(workload="w", kind="closed", chips=1,
               device_kind="TPU v5 lite", sizes={}, mix={}, setup_s=0.0,
               window_s=1.0, setup_compile={}, window_programs=0,
               facts={"t_open": 0.0, "t_close": 1.0, "decode_chunk": 4,
                      "decodes": []},
               trace=tr.TraceView(events) if traced else None,
               traced={"t0": 0.0, "t1": 1.0} if traced else None)


@pytest.mark.parametrize("kernel", ("kv.attend", "paged_attention"))
def test_it_reads_the_kernel_under_either_name(kernel):
    read = Manifest().reader(NAME)
    # 12 calls of 300 us over 2 + 4 steps; the write's 100 us stay out
    assert read(_run(kernel)) == pytest.approx(0.6)


def test_it_finds_nothing_without_a_trace_or_without_the_kernel():
    read = Manifest().reader(NAME)
    assert read(_run("kv.attend", traced=False)) is None
    assert read(_run("gdn_step")) is None


def test_the_manifest_lists_it_for_the_two_cells_with_attention_layers():
    m = Manifest(ROOT / "BENCHMARK.json")
    assert check(m) == []
    for cell, there in (("cgpt1.3b-serve-batch", True),
                        ("olmohyb7b-serve-chat", True),
                        ("granite4hs-serve-chat", False),
                        ("cgpt590m-train-t2048", False)):
        names = {x["name"] for x in m.metrics_of(cell, "per_layer")}
        assert (NAME in names) is there
