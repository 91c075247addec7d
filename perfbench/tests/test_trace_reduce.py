"""The reduction from a trace to numbers, on hand-made events and on a
small trace recorded on the chip (`data/recorded_trace.json`: the tiny
rehearsal configuration's train steps on a TPU v5 lite, as
`trace_reduce.load_xplane` gave them)."""
import json

import pytest

from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def hand_made():
    return [
        ev(HOST, "python3", "perfbench.window", 0, 1000),
        ev(DEV, tr.MODULE_LINE, "jit_step(123)", 100, 400),
        ev(DEV, tr.MODULE_LINE, "jit_step(123)", 600, 300),
        ev(DEV, tr.MODULE_LINE, "jit_step(123)", 950, 300),  # cut by the end
        ev(DEV, tr.OPS_LINE, "%fusion.1 = f32[8] fusion(f32[8] %p)", 100, 200),
        ev(DEV, tr.OPS_LINE, "%fusion.2 = f32[8] fusion(f32[8] %p)", 250, 100),
        ev(DEV, tr.OPS_LINE,
           '%jvp__.5 = (bf16[8], f32[8]) custom-call(bf16[8] %fusion.1), '
           'custom_call_target="tpu_custom_call"', 400, 100),
        ev(DEV, tr.OPS_LINE, "%copy.7 = f32[8] copy(f32[8] %custom-call.3)",
           600, 300),
        ev(DEV, tr.OPS_LINE, "%fusion.1 = f32[8] fusion(f32[8] %p)", 950, 100),
        ev(HOST, "python3", "$engine.py:10 loop", 0, 1000),
        ev(HOST, "python3", "$engine.py:20 admit", 500, 90),
        ev(HOST, "waiter", "$threading.py:300 wait", 0, 1000),
    ]


def test_busy_idle_programs_kernels_and_gaps_on_hand_made_events():
    v = tr.TraceView(hand_made())
    assert v.window_s == pytest.approx(1000e-9)
    # busy: [100,350] U [400,500] U [600,900] U [950,1000] = 700
    assert v.busy_s() == pytest.approx(700e-9)
    assert v.idle_pct() == pytest.approx(30.0)
    assert v.program_seconds("step") == (2, pytest.approx(700e-9))
    # only the Pallas call itself, not the op that names one as operand
    assert v.op_seconds_within(("step",), tr.is_pallas_kernel) == \
        pytest.approx(100e-9)
    assert dict(v.top_ops())["fusion"] == pytest.approx(350e-9)
    gaps = dict(v.idle_gaps())
    # [0,100], [350,400], [900,950] under `loop`; [500,600] under `admit`;
    # the thread that only waits is never blamed
    assert gaps == {"engine.py:10_loop": pytest.approx(200e-9),
                    "engine.py:20_admit": pytest.approx(100e-9)}


def test_names():
    assert tr.program_name("jit_decode_step(77)") == "decode_step"
    hlo = "%convert_element_type.514 = bf16[4]{0} convert(f32[4]{0} %p.1)"
    assert tr.op_name(hlo) == "convert_element_type"
    assert tr.opcode(hlo) == "convert"
    tup = "%c.3 = (bf16[96,8]{1,0:T(8,128)(2,1)}, f32[2]) custom-call(bf16[8] %b)"
    assert tr.opcode(tup) == "custom-call" and not tr.is_pallas_kernel(tup)


def test_a_trace_with_no_device_events_reads_as_nothing():
    v = tr.TraceView([ev(HOST, "python3", "$a.py:1 f", 0, 10)])
    assert v.busy_s() == 0.0 and v.idle_pct() is None
    assert v.top_ops() == [] and v.idle_gaps() == []


def test_the_recorded_trace_reduces_to_what_was_read_by_hand():
    rec = json.loads((ROOT / "perfbench/tests/data/recorded_trace.json")
                     .read_text())
    v = tr.TraceView(rec["events"])
    want = rec["read_by_hand"]
    assert v.chips == ["/device:TPU:0"]
    runs, seconds = v.program_seconds(want["program"])
    assert runs == want["runs"]
    assert seconds == pytest.approx(want["program_seconds"], rel=1e-6)
    assert v.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert v.busy_s() == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0.0 < v.idle_pct() < 100.0
    assert v.top_ops(3)[0][0] == want["top_op"]
    assert v.idle_gaps(3)[0][0] == want["top_gap"]
