"""The `olmo_hybrid` family's serve cell at tiny widths on the CPU,
through `cell.run_cell` with the look for a chip skipped: the cell ends
on the contract's line, the two readers this family brings find nothing
to read where nothing ran on an accelerator and read a hand-made device
trace right, the float8 control comes out as not correct, and pad
positions that move the matrix state do too. Nothing here is a
measurement."""
import argparse
import time

import pytest

from perfbench.harness import cell, compare, gdn_roofline
from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

TINY = ROOT / "perfbench" / "tests" / "data" / "olmo" / "BENCHMARK.json"
NEW = ("gdn_step_roofline", "step.gdn_step_device_ms.chat")


def _run(*, trace=0, control=0, seed=2**31 + 7, seconds=2.0):
    args = argparse.Namespace(workload="tiny-chat", seed=seed,
                              seconds=seconds, trace=trace, control=control)
    return cell.run_cell(Manifest(TINY, root=ROOT), args,
                         look_for_chip=False, t_start=time.perf_counter())


def test_the_toy_manifest_and_the_benchmarks_own_resolve():
    assert check(Manifest(TINY, root=ROOT)) == []
    real = Manifest(ROOT / "BENCHMARK.json")
    assert check(real) == []
    mine = {m["name"] for m in
            real.metrics_of("olmohyb7b-serve-chat", "per_layer")}
    assert set(NEW) <= mine and "paged_attention_roofline" not in mine
    assert not any(name.startswith("moe") for name in mine)


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_ends_correct_and_untraced_readers_find_nothing(trace):
    out = _run(trace=trace)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0
    after = out["run"].facts["stats_after"]
    assert after["recurrent_blocks"] == 2 and after["kv_blocks"] == 1
    for name in NEW:
        # nothing ran on an accelerator: no device metric is reported,
        # and the readers say so without raising
        assert name not in out["metrics"]
        assert Manifest(TINY, root=ROOT).reader(name)(out["run"]) is None


def test_the_control_comes_out_as_not_correct():
    out = _run(control=1, seed=11)
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell("tiny-chat")["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged


def test_pad_positions_that_move_the_state_are_not_correct(monkeypatch):
    """A prefill that lets the pad positions of its bucket advance the
    matrix state and the convolution tail must show in the comparison."""
    from deeplearning4j_tpu.nn.conf.decoder_block import sub
    from deeplearning4j_tpu.serving import block_state

    def prefill(self, p, x, cache, d):
        y, h1, tail1 = self.mixer.scan(sub(p, "mx_"),
                                       self.layer.mixer_in(p, x))
        x = block_state._finish_composed(self.layer, p, x, y, d)
        return x, self._store(cache, h1, tail1, d.slot)

    monkeypatch.setattr(block_state.RecurrentSlots, "prefill", prefill)
    out = _run()
    assert out["correct"] is False
    c = out["compared"]["served_gap_max"]
    assert c["value"] > c["limit"]


# ------------------------------------------- the readers on a device trace
DEV, HOST = "/device:TPU:0", "/host:CPU"
KERNEL = ('%gdn_step.{n} = (f32[64,15,384]{{2,1,0}}, f32[64,96,5760]{{2,1,0}}) '
          'custom-call(f32[64,96,60] %a, f32[64,96,5760] %s), '
          'custom_call_target="tpu_custom_call"')


def _traced_run(sizes):
    """Two single steps and one chunk of 4 in the window, three linear
    blocks: each step holds three 400 us kernel calls, each run one
    other Pallas call that is not the step's."""
    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    events = [ev(HOST, "python3", "perfbench.window", 0, 30_000_000)]
    for prog, t, calls in (("decode_step", 1e6, 3), ("decode_step", 4e6, 3),
                           ("decode_chunked", 8e6, 12)):
        events.append(ev(DEV, tr.MODULE_LINE, f"jit_{prog}(7)", t,
                         calls * 600_000))
        for j in range(calls):
            events.append(ev(DEV, tr.OPS_LINE, KERNEL.format(n=j),
                             t + 450_000 * j, 400_000))
        events.append(ev(
            DEV, tr.OPS_LINE, '%kv.attend.1 = bf16[64,30,128] custom-call('
            'bf16[8] %q), custom_call_target="tpu_custom_call"',
            t + 450_000 * calls, 20_000))
    facts = {"t_open": 0.0, "t_close": 1.0, "decode_chunk": 4,
             "decodes": [(0.1, 0.2, 1, 60, 0), (0.3, 0.4, 4, 64, 0)]}
    return Run(workload="w", kind="closed", chips=1,
               device_kind="TPU v5 lite", sizes=sizes, mix={}, setup_s=0.0,
               window_s=1.0, setup_compile={}, window_programs=0,
               facts=facts, trace=tr.TraceView(events),
               traced={"t0": 0.0, "t1": 1.0})


def test_the_readers_on_a_hand_made_device_trace():
    sizes = {"layer_types": ("linear_attention",) * 3 + ("full_attention",),
             "lh": 30, "lk": 96, "lv": 192}
    run = _traced_run(sizes)
    # 18 kernel calls of 400 us over 2 + 4 steps
    assert gdn_roofline.step_device_ms(run) == pytest.approx(1.2)
    # live slots: (1 * 60 + 4 * 64) / 5 = 63.2, three blocks
    state = 30 * 96 * 192
    per = 2 * 4 * state + 30 * (2 * 96 * 4 + 2 * 192 * 2 + 8)
    ops, nbytes = gdn_roofline.gated_delta_step(63.2, 3, 30, 96, 192)
    assert nbytes == pytest.approx(per * 63.2 * 3)
    assert ops == pytest.approx(7 * state * 63.2 * 3)
    share = gdn_roofline.roofline_pct(run)
    assert share == pytest.approx(100.0 * (nbytes / 819e9) / 1.2e-3)
    assert 80.0 < share < 90.0
    # a family without linear blocks, or a run without a trace: nothing
    assert gdn_roofline.roofline_pct(_traced_run({"layer_types": ()})) is None
    run.trace = None
    assert gdn_roofline.roofline_pct(run) is None
    assert gdn_roofline.step_device_ms(run) is None
