"""The `nemotron_h` family's serve cell at tiny widths on the CPU,
through `cell.run_cell` with the look for a chip skipped: the cell ends
on the contract's line with the engine's counters read (Mamba state,
paged K/V and blocks that keep nothing in one engine), the two readers
this family brings find nothing to read where nothing ran on an
accelerator and read a hand-made device trace right, the float8 control
comes out as not correct, and pad positions that move the recurrent
state do too. Nothing here is a measurement."""
import argparse
import time

import pytest

from perfbench.harness import cell, compare, moe_relu2_roofline
from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

TINY = ROOT / "perfbench" / "tests" / "data" / "nemotron" / "BENCHMARK.json"
NEW = ("moe_relu2_experts_roofline", "step.moe_experts_device_ms.chat")


def _run(*, trace=0, control=0, seed=2**31 + 7, seconds=2.0):
    args = argparse.Namespace(workload="tiny-nemo-chat", seed=seed,
                              seconds=seconds, trace=trace, control=control)
    return cell.run_cell(Manifest(TINY, root=ROOT), args,
                         look_for_chip=False, t_start=time.perf_counter())


def test_the_toy_manifest_and_the_benchmarks_own_resolve():
    assert check(Manifest(TINY, root=ROOT)) == []
    real = Manifest(ROOT / "BENCHMARK.json")
    assert check(real) == []
    mine = {m["name"] for m in
            real.metrics_of("nemo3nano-serve-chat", "per_layer")}
    assert set(NEW) <= mine
    assert {"moe.experts_hit_pct.chat", "moe.local_share_pct.chat"} <= mine
    # their readers price three matrices an expert, H K/V heads, or
    # another family's kernel
    assert not mine & {"moe_experts_roofline", "paged_attention_roofline",
                       "gdn_step_roofline", "step.gdn_step_device_ms.chat"}
    for name in NEW:
        listed = next(m for m in real.raw["per_layer"]
                      if m["name"] == name)["workloads"]
        assert listed == ["nemo3nano-serve-chat"]


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_ends_correct_with_its_counters_read(trace):
    out = _run(trace=trace)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0
    after = out["run"].facts["stats_after"]
    assert (after["recurrent_blocks"], after["kv_blocks"],
            after["stateless_blocks"]) == (2, 1, 2)
    assert after["moe_experts_held"] == 2 * 4
    if trace:
        m = out["metrics"]
        # half the experts are held (at 8 experts the drawn bias tilts
        # the share), and at 4 slots x top-2 of 8 over 2 routed blocks
        # not every held expert is hit every step
        assert 10.0 < m["moe.local_share_pct.chat"]["value"] < 90.0
        assert 0.0 < m["moe.experts_hit_pct.chat"]["value"] <= 100.0
    for name in NEW:
        # nothing ran on an accelerator: no device metric is reported,
        # and the readers say so without raising
        assert name not in out["metrics"]
        assert Manifest(TINY, root=ROOT).reader(name)(out["run"]) is None


def test_the_control_comes_out_as_not_correct():
    out = _run(control=1, seed=11)
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell("tiny-nemo-chat")["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged


def test_pad_positions_that_move_the_state_are_not_correct(monkeypatch):
    """A prefill that lets the pad positions of its bucket advance the
    recurrent state and the convolution tail must show in the
    comparison, with blocks that keep no state between the ones that
    do."""
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
KERNEL = ('%moe_experts.{n} = bf16[64,2688]{{1,0}} custom-call('
          'bf16[64,2688] %x, f32[64,64,1] %g, bf16[64,1856,2688] %wu, '
          'bf16[64,1856,2688] %wd), custom_call_target="tpu_custom_call"')


def _traced_run(sizes, stats):
    """Two single steps and one chunk of 4 in the window, three routed
    blocks: each step holds three 1.5 ms kernel calls, each run one other
    Pallas call that is not the experts'."""
    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    events = [ev(HOST, "python3", "perfbench.window", 0, 60_000_000)]
    for prog, t, calls in (("decode_step", 1e6, 3), ("decode_step", 8e6, 3),
                           ("decode_chunked", 16e6, 12)):
        events.append(ev(DEV, tr.MODULE_LINE, f"jit_{prog}(7)", t,
                         calls * 2_000_000))
        for j in range(calls):
            events.append(ev(DEV, tr.OPS_LINE, KERNEL.format(n=j),
                             t + 1_600_000 * j, 1_500_000))
        events.append(ev(
            DEV, tr.OPS_LINE, '%paged_attention.1 = bf16[64,32,128] '
            'custom-call(bf16[8] %q), custom_call_target="tpu_custom_call"',
            t + 1_600_000 * calls, 20_000))
    facts = {"t_open": 0.0, "t_close": 1.0, "decode_chunk": 4,
             "decodes": [(0.1, 0.2, 1, 60, 0), (0.3, 0.4, 4, 64, 0)],
             "stats_before": {k: 0 for k in stats}, "stats_after": stats}
    return Run(workload="w", kind="closed", chips=1,
               device_kind="TPU v5 lite", sizes=sizes, mix={}, setup_s=0.0,
               window_s=1.0, setup_compile={}, window_programs=0,
               facts=facts, trace=tr.TraceView(events),
               traced={"t0": 0.0, "t1": 1.0})


def test_the_readers_on_a_hand_made_device_trace():
    sizes = {"pattern": "MEME*E", "d": 2688, "f": 1856}
    # 5 steps: 61 of 64 held experts hit a block a step, 190 choices
    stats = {"moe_steps": 5, "moe_experts_hit": 5 * 3 * 61,
             "moe_held_choices": 5 * 3 * 190}
    run = _traced_run(sizes, stats)
    # 18 kernel calls of 1.5 ms over 2 + 4 steps
    assert moe_relu2_roofline.step_device_ms(run) == pytest.approx(4.5)
    # live slots: (1 * 60 + 4 * 64) / 5 = 63.2, three routed blocks
    ops, nbytes = moe_relu2_roofline.grouped_relu2_experts(
        3 * 61, 3 * 190, 63.2, 3, 2688, 1856)
    assert nbytes == pytest.approx(2 * 2688 * 1856 * 2 * 183
                                   + 2 * 63.2 * 2688 * 2 * 3)
    assert ops == pytest.approx(2 * 2 * 2688 * 1856 * 570)
    share = moe_relu2_roofline.roofline_pct(run)
    assert share == pytest.approx(100.0 * (nbytes / 819e9) / 4.5e-3)
    assert 95.0 < share < 100.0
    # a family without routed blocks, a program without the counters, or
    # a run without a trace: nothing, and no raise
    assert moe_relu2_roofline.roofline_pct(
        _traced_run({"layer_types": ("mamba",)}, stats)) is None
    assert moe_relu2_roofline.roofline_pct(_traced_run(sizes, {})) is None
    run.trace = None
    assert moe_relu2_roofline.roofline_pct(run) is None
    assert moe_relu2_roofline.step_device_ms(run) is None
