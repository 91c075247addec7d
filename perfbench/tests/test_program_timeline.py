"""The program's own timeline and counters, as the metric readers read
them: on hand-made events, on a small timeline recorded on the chip beside
its device trace (`data/recorded_timeline.json`: the tiny rehearsal
configuration's closed-loop serving on a TPU v5 lite), and through the
one-cell command on the CPU."""
import argparse
import json
import time

import pytest

from perfbench.harness import cell, program_timeline as pt
from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = ROOT / "perfbench" / "tests" / "data"
SPAN_METRICS = ("device.idle_deliver_pct.batch",
                "device.idle_dispatch_pct.batch",
                "device.idle_wait_pct.batch",
                "device.idle_unattributed_pct.batch")
COUNTER_METRICS = ("sched.host_share_pct.batch",
                   "sched.deliver_ms_per_step.batch",
                   "front.queue_wait_mean_ms.batch")


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def a_run(events, traced, facts=None):
    return Run(workload="w", kind="closed", chips=1, device_kind="TPU v5 lite",
               sizes={}, mix={}, setup_s=1.0, window_s=1.0, setup_compile={},
               window_programs=0, facts=facts or {},
               trace=tr.TraceView(events), traced=traced)


def hand_made():
    """A window of 1000 ns that opens at 5000 ns on the trace's clock and
    at 2.0 s on the program's; the chip is busy 200-500 and 600-900."""
    events = [
        ev(HOST, "python3", "perfbench.window", 5000, 1000),
        ev(DEV, tr.MODULE_LINE, "jit_decode_step(1)", 5200, 300),
        ev(DEV, tr.MODULE_LINE, "jit_decode_step(1)", 5600, 300),
        ev(DEV, tr.OPS_LINE, "%fusion.1 = f32[8] fusion(f32[8] %p)", 5200, 300),
        ev(DEV, tr.OPS_LINE, "%fusion.2 = f32[8] fusion(f32[8] %p)", 5600, 300),
    ]
    s = 1e-9
    spans = [  # (name, t0, t1, cause, tid, attrs), perf_counter seconds
        ("decode.deliver", 2.0 - 300 * s, 2.0 + 100 * s, 1, 7, None),
        ("decode.dispatch", 2.0 + 100 * s, 2.0 + 250 * s, 2, 7,
         {"program": "decode_step", "chunk": 1, "active": 2}),
        ("decode.wait", 2.0 + 250 * s, 2.0 + 520 * s, 2, 7, None),
        ("decode.deliver", 2.0 + 520 * s, 2.0 + 560 * s, 2, 7, None),
        ("admit", 2.0 + 560 * s, 2.0 + 570 * s, 3, 7, None),
        ("decode.dispatch", 2.0 + 570 * s, 2.0 + 650 * s, 3, 7,
         {"program": "decode_step", "chunk": 1, "active": 2}),
        ("decode.wait", 2.0 + 650 * s, 2.0 + 950 * s, 3, 7, None),
        # 950-1000 under no span; another thread's span is not read
        ("decode.deliver", 2.0, 2.0 + 1000 * s, 9, 8, None),
    ]
    return events, {"t0": 2.0, "t1": 2.0 + 1000 * s}, spans


def test_idle_gaps_are_cut_by_the_phase_the_scheduler_was_in(monkeypatch):
    events, traced, spans = hand_made()
    monkeypatch.setattr(pt, "program_spans", lambda a, b: spans)
    run = a_run(events, traced)
    by = pt.idle_seconds_by_phase(run)
    ns = {k: round(v * 1e9) for k, v in by.items() if round(v * 1e9)}
    # idle: 0-200, 500-600, 900-1000
    assert ns == {"decode.deliver": 100 + 40, "decode.dispatch": 100 + 30,
                  "admit": 10, "decode.wait": 20 + 50, None: 50}
    parts = [pt.idle_pct(run, ("decode.deliver", "prefill.deliver")),
             pt.idle_pct(run, ("admit", "housekeeping", "decode.dispatch",
                               "prefill.dispatch")),
             pt.idle_pct(run, pt.WAITING), pt.idle_pct(run, None)]
    assert parts == pytest.approx([14.0, 14.0, 7.0, 5.0])
    assert sum(parts) == pytest.approx(run.trace.idle_pct())
    c = pt.dispatch_containment(run)
    assert c == {"runs": 2, "inside": 2, "worst_outside_s": 0.0}


def test_a_device_run_outside_its_dispatch_is_counted(monkeypatch):
    events, traced, spans = hand_made()
    late = [(n, a + 35e-9, b + 35e-9, c, t, at)
            for n, a, b, c, t, at in spans]
    monkeypatch.setattr(pt, "program_spans", lambda a, b: late)
    c = pt.dispatch_containment(a_run(events, traced), tolerance_s=0.0)
    assert c["runs"] == 2 and c["inside"] == 1
    # the second run starts at 600, its dispatch now at 605
    assert c["worst_outside_s"] == pytest.approx(5e-9, abs=1e-12)


def test_anchors_that_disagree_leave_every_span_reader_with_nothing(
        monkeypatch, capsys):
    events, traced, spans = hand_made()
    monkeypatch.setattr(pt, "program_spans", lambda a, b: spans)
    m = Manifest()
    good = a_run(events, traced)
    assert all(m.reader(n)(good) is not None for n in SPAN_METRICS)
    off = a_run(events, dict(traced, t1=traced["t1"] + 1e-3))
    assert [m.reader(n)(off) for n in SPAN_METRICS] == [None] * 4
    assert pt.dispatch_containment(off) is None
    assert "1.000 ms apart" in capsys.readouterr().err
    within = a_run(events, dict(traced, t1=traced["t1"] + 0.4e-3))
    assert all(m.reader(n)(within) is not None for n in SPAN_METRICS)


def test_nothing_to_read_is_none_and_never_raises(monkeypatch):
    events, traced, spans = hand_made()
    m = Manifest()
    # a program from before the timeline: no spans, no `loop` counters
    monkeypatch.setattr(pt, "program_spans", lambda a, b: None)
    old = a_run(events, traced, {"stats_before": {"decode_steps": 1},
                                 "stats_after": {"decode_steps": 9}})
    for n in SPAN_METRICS + COUNTER_METRICS:
        assert m.reader(n)(old) is None
    # no trace, or a trace without a device plane
    monkeypatch.setattr(pt, "program_spans", lambda a, b: spans)
    untraced = a_run(events, None)
    untraced.trace = None
    no_chip = a_run([e for e in events if e["plane"] == HOST], traced)
    for n in SPAN_METRICS:
        assert m.reader(n)(untraced) is None
        assert m.reader(n)(no_chip) is None


def test_the_counters_give_the_windows_account():
    phases = ("wait-work", "admit", "housekeeping", "prefill.dispatch",
              "prefill.wait", "prefill.deliver", "decode.dispatch",
              "decode.wait", "decode.deliver")

    def stats(scale):
        loop = {"iterations": 10 * scale, "sink_s": 0.01 * scale,
                "sink_n": 100 * scale, "spans_dropped": 0}
        for i, p in enumerate(phases):
            loop[p + "_s"] = scale * (i + 1) * 0.1
            loop[p + "_n"] = scale * 10
        return {"loop": loop, "queue_wait_s": 3.0 * scale,
                "admitted": 4 * scale, "decode_steps": 50 * scale}

    run = a_run([], None, {"stats_before": stats(1), "stats_after": stats(3)})
    m = Manifest()
    # all phases: 2 * 0.1 * (1 + ... + 9) = 9.0; waiting: wait-work 0.2,
    # prefill.wait 1.0, decode.wait 1.6
    assert m.reader("sched.host_share_pct.batch")(run) == \
        pytest.approx(100.0 * (9.0 - 2.8) / 9.0)
    assert m.reader("sched.deliver_ms_per_step.batch")(run) == \
        pytest.approx(1e3 * 1.8 / 100)
    assert m.reader("front.queue_wait_mean_ms.batch")(run) == \
        pytest.approx(1e3 * 6.0 / 8)
    idle = a_run([], None, {"stats_before": stats(1),
                            "stats_after": stats(1)})
    assert [m.reader(n)(idle) for n in COUNTER_METRICS] == [None] * 3


def test_the_manifest_holds_the_seven_and_resolves():
    m = Manifest()
    assert check(m) == []
    mine = {x["name"]: x for x in m.raw["per_layer"]
            if x["name"] in SPAN_METRICS + COUNTER_METRICS}
    assert len(mine) == 7
    for name, x in mine.items():
        assert x["workloads"] == ["cgpt1.3b-serve-batch"]
        assert x["moves"] == "serve_tokens_per_s" and x["better"] == "lower"
        assert x["source"] == ("program_span" if name in SPAN_METRICS
                               else "program_counter")
    assert {x["layer"] for x in mine.values()} == {"scheduler", "device",
                                                   "serving front"}
    reported = {x["name"] for x in
                m.metrics_of("cgpt1.3b-serve-batch", "per_layer")}
    assert set(mine) <= reported
    assert not set(mine) & {x["name"] for x in m.metrics_of(
        "cgpt590m-train-t2048", "per_layer")}


def recorded():
    rec = json.loads((DATA / "recorded_timeline.json").read_text())
    spans = [tuple(s) for s in rec["spans"]]
    return rec, a_run(rec["events"], rec["traced"]), spans


def test_the_recorded_timeline_accounts_for_the_chips_idle_time(monkeypatch):
    rec, run, spans = recorded()
    monkeypatch.setattr(pt, "program_spans", lambda a, b: spans)
    m = Manifest()
    parts = [m.reader(n)(run) for n in SPAN_METRICS]
    assert all(p is not None and p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(run.trace.idle_pct(), abs=0.01)
    want = rec["read_by_hand"]
    assert run.trace.idle_pct() == pytest.approx(want["idle_pct"], abs=1e-6)
    assert dict(zip(SPAN_METRICS, parts)) == pytest.approx(want["parts"],
                                                          abs=1e-6)
    # the spans sit where the device's work sits
    c = pt.dispatch_containment(run)
    assert c["runs"] == want["runs"] > 0
    assert c["inside"] == c["runs"] and c["worst_outside_s"] < 0.5e-3
    # leaves never overlap on the recorded thread either
    on_clock = pt.spans_on_trace_clock(run)
    for a, b in zip(on_clock, on_clock[1:]):
        assert b[0] >= a[1]
    off = a_run(rec["events"], dict(rec["traced"],
                                    t1=rec["traced"]["t1"] + 1e-3))
    assert [m.reader(n)(off) for n in SPAN_METRICS] == [None] * 4


def test_the_tiny_serve_cell_reports_the_programs_counters(tmp_path):
    """The one-cell command on the CPU, with the seven entries put into
    the rehearsal's manifest: the counters are read from the program as
    it runs; nothing ran on an accelerator, so the span readers find no
    chip and leave their metrics out."""
    raw = json.loads((DATA / "BENCHMARK.json").read_text())
    real = {x["name"]: x for x in Manifest().raw["per_layer"]}
    for n in SPAN_METRICS + COUNTER_METRICS:
        raw["per_layer"].append(dict(real[n], workloads=["tiny-batch"]))
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(raw))
    m = Manifest(p, root=ROOT)
    args = argparse.Namespace(workload="tiny-batch", seed=2**31 + 11,
                              seconds=1.5, trace=1, control=0)
    out = cell.run_cell(m, args, look_for_chip=False,
                        t_start=time.perf_counter())
    assert out["correct"] is True
    got = out["metrics"]
    assert set(COUNTER_METRICS) <= set(got)
    assert not set(SPAN_METRICS) & set(got)
    assert 0.0 < got["sched.host_share_pct.batch"]["value"] < 100.0
    assert got["sched.deliver_ms_per_step.batch"]["value"] > 0.0
    assert got["front.queue_wait_mean_ms.batch"]["value"] > 0.0
    # the anchors the span readers rest on, as the runner leaves them
    run = out["run"]
    apart = abs(run.trace.t1 - run.trace.t0
                - 1e9 * (run.traced["t1"] - run.traced["t0"])) / 1e9
    assert apart < pt.ANCHOR_TOLERANCE_S
