"""The `longcat_flash` family's serve cell at tiny widths on the CPU,
through `cell.run_cell` with the look for a chip skipped: the cell ends
on the contract's line with the engine's counters read (two pools of
latent pages a layer, zero-compute experts' choices counted), the three
readers this family brings find nothing to read where nothing ran on an
accelerator and read a hand-made device trace right, and the float8
control comes out as not correct. Nothing here is a measurement."""
import argparse
import time

import pytest

from perfbench.harness import cell, compare, mla_roofline
from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

TINY = ROOT / "perfbench" / "tests" / "data" / "longcat" / "BENCHMARK.json"
CELL = "longcatflash-serve-reason"
NEW = ("mla_attend_roofline", "step.mla_attend_device_ms.reason",
       "moe.zero_choice_pct.reason")


def _run(*, trace=0, control=0, seed=2**31 + 7, seconds=2.0):
    args = argparse.Namespace(workload="tiny-longcat-reason", seed=seed,
                              seconds=seconds, trace=trace, control=control)
    return cell.run_cell(Manifest(TINY, root=ROOT), args,
                         look_for_chip=False, t_start=time.perf_counter())


def test_the_toy_manifest_and_the_benchmarks_own_resolve():
    assert check(Manifest(TINY, root=ROOT)) == []
    real = Manifest(ROOT / "BENCHMARK.json")
    assert check(real) == []
    mine = {m["name"] for m in real.metrics_of(CELL, "per_layer")}
    assert set(NEW) <= mine
    assert {"moe.experts_hit_pct.chat", "moe.local_share_pct.chat",
            "moe.experts_read_pct.chat", "moe_experts_roofline",
            "step.moe_experts_device_ms.chat",
            "step.prefill_device_ms.chat"} <= mine
    # their readers price K and V pools of one head size, or another
    # family's kernel
    assert not mine & {"paged_attention_roofline",
                       "step.kv_attend_device_ms.batch",
                       "moe_relu2_experts_roofline", "gdn_step_roofline",
                       "step.gdn_step_device_ms.chat"}
    for name in NEW:
        listed = next(m for m in real.raw["per_layer"]
                      if m["name"] == name)
        assert listed["workloads"] == [CELL]
        assert listed["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in real.metrics_of(CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    mix = real.traffic(real.workload(CELL)["traffic"])
    assert (mix["engine"]["n_slots"], mix["engine"]["pool_pages"],
            mix["engine"]["max_len"], mix["backlog"]) == (128, 2560, 4096, 64)
    sz = real.family(real.config("longcat-flash-chat")).sizes(
        real.config("longcat-flash-chat"))
    # what `harness/moe_roofline.py` multiplies by: the routed blocks
    assert (sz["d"], sz["f"], sz["L"]) == (6144, 2048, 4)


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_ends_correct_with_its_counters_read(trace):
    out = _run(trace=trace)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0
    after = out["run"].facts["stats_after"]
    assert (after["latent_blocks"], after["kv_blocks"],
            after["recurrent_blocks"]) == (4, 0, 0)
    assert after["latent_bytes_per_token"] == 4 * 20 * 2
    assert after["moe_experts_held"] == 2 * 4
    if trace:
        m = out["metrics"]
        # 4 of 12 router outputs are zero experts and 4 of 8 real ones
        # are held (the drawn bias tilts both shares)
        assert 5.0 < m["moe.zero_choice_pct.reason"]["value"] < 70.0
        assert 5.0 < m["moe.local_share_pct.chat"]["value"] < 80.0
        assert 0.0 < m["moe.experts_hit_pct.chat"]["value"] <= 100.0
        assert m["moe.experts_read_pct.chat"]["value"] \
            == m["moe.experts_hit_pct.chat"]["value"]
    for name in NEW[:2] + ("moe_experts_roofline",
                           "step.moe_experts_device_ms.chat"):
        # nothing ran on an accelerator: no device metric is reported,
        # and the readers say so without raising
        assert name not in out["metrics"]
        assert Manifest(TINY, root=ROOT).reader(name)(out["run"]) is None


def test_the_control_comes_out_as_not_correct():
    out = _run(control=1, seed=11)
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell("tiny-longcat-reason")["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged


def test_a_shortcut_that_never_joins_is_not_correct(monkeypatch):
    """A layer that computes the routed block and never adds its output
    back to the stream must show in the comparison (the order of the
    join is held by `tests/test_longcat_flash.py`, at a layer)."""
    from deeplearning4j_tpu.nn.conf import decoder_block as db

    whole = db.ShortcutDecoderBlock.compose

    def compose(self, p, x, mix_a, mix_b, count_mask=None):
        out, counts = whole(self, p, x, mix_a, mix_b, count_mask)
        m, _ = self.shortcut.forward(
            db.sub(p, "sc_"),
            self.first.ffn_in(db.sub(p, "a_"), self.first.after_mixer(
                db.sub(p, "a_"), x, mix_a(db.sub(db.sub(p, "a_"), "mx_"),
                                          self.first.mixer_in(
                                              db.sub(p, "a_"), x)))))
        return out - m, counts

    monkeypatch.setattr(db.ShortcutDecoderBlock, "compose", compose)
    out = _run()
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


# ------------------------------------------- the readers on a device trace
DEV, HOST = "/device:TPU:0", "/host:CPU"
KERNEL = ('%mla_attend.{n} = bf16[128,64,512]{{2,1,0}} custom-call('
          's32[128,32] %pt, s32[128] %pos, s32[128] %g, bf16[128,64,576] %q, '
          'bf16[2561,576,128] %pool), custom_call_target="tpu_custom_call"')
OTHERS = ('%latent_write.1 = bf16[2561,576,128] custom-call(bf16[8] %q), '
          'custom_call_target="tpu_custom_call"',
          '%moe_experts.1 = bf16[128,6144] custom-call(bf16[8] %q), '
          'custom_call_target="tpu_custom_call"')


def _traced_run(sizes, stats=None):
    """Two single steps and one chunk of 4 in the window, eight
    sub-layers: each step holds eight 0.5 ms attend calls, each run two
    other Pallas calls that are not the attention's."""
    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    events = [ev(HOST, "python3", "perfbench.window", 0, 90_000_000)]
    for prog, t, calls in (("decode_step", 1e6, 8), ("decode_step", 9e6, 8),
                           ("decode_chunked", 20e6, 32)):
        events.append(ev(DEV, tr.MODULE_LINE, f"jit_{prog}(7)", t,
                         calls * 700_000))
        for j in range(calls):
            events.append(ev(DEV, tr.OPS_LINE, KERNEL.format(n=j),
                             t + 600_000 * j, 500_000))
        for k, other in enumerate(OTHERS):
            events.append(ev(DEV, tr.OPS_LINE, other,
                             t + 600_000 * calls + 30_000 * k, 20_000))
    stats = stats or {}
    facts = {"t_open": 0.0, "t_close": 1.0, "decode_chunk": 4,
             # (pre, post, chunk, live slots, live context at its issue)
             "decodes": [(0.1, 0.2, 1, 120, 150_000),
                         (0.3, 0.4, 1, 120, 150_120),
                         (0.5, 0.6, 4, 128, 160_000)],
             "stats_before": {k: 0 for k in stats}, "stats_after": stats}
    return Run(workload="w", kind="closed", chips=1,
               device_kind="TPU v5 lite", sizes=sizes, mix={}, setup_s=0.0,
               window_s=1.0, setup_compile={}, window_programs=0,
               facts=facts, trace=tr.TraceView(events),
               traced={"t0": 0.0, "t1": 1.0})


def test_the_readers_on_a_hand_made_device_trace():
    sizes = {"H": 64, "kr": 512, "rope": 64, "L": 4, "d": 6144, "f": 2048}
    run = _traced_run(sizes)
    # 48 attend calls of 0.5 ms over 2 + 4 steps; the write and the
    # experts' kernels are not counted
    assert mla_roofline.step_device_ms(run) == pytest.approx(4.0)
    # positions a step: the chunk's j-th step sees j more a live slot
    ctx = (150_000 + 150_120 + sum(160_000 + 128 * j for j in range(4))) / 6
    live = (120 + 120 + 4 * 128) / 6
    ops, nbytes = mla_roofline.latent_decode(ctx, live, 64, 512, 64)
    assert nbytes == pytest.approx(ctx * 1152 + live * 64 * 1088 * 2)
    assert ops == pytest.approx(2 * 64 * 1088 * ctx)
    share = mla_roofline.roofline_pct(run)
    # 121 operations a byte against the chip's 240: memory sets the least
    assert share == pytest.approx(100.0 * (8 * nbytes / 819e9) / 4.0e-3)
    assert 40.0 < share < 50.0
    real = Manifest(ROOT / "BENCHMARK.json")
    assert real.reader("mla_attend_roofline")(run) == share
    assert real.reader("step.mla_attend_device_ms.reason")(run) \
        == pytest.approx(4.0)
    # another family's sizes, or a run without a trace: nothing, no raise
    assert mla_roofline.roofline_pct(
        _traced_run({"H": 32, "hd": 128, "L": 16})) is None
    run.trace = None
    assert mla_roofline.roofline_pct(run) is None
    assert mla_roofline.step_device_ms(run) is None


@pytest.mark.parametrize("stats,want", [
    ({"moe_routed": 6000, "moe_zero_choices": 2000}, 100.0 / 3),
    ({"moe_routed": 6000}, None),             # a program without it
    ({"moe_routed": 0, "moe_zero_choices": 0}, None),
], ids=["read", "no-counter", "no-steps"])
def test_the_zero_choice_share(stats, want):
    read = Manifest(ROOT / "BENCHMARK.json").reader(
        "moe.zero_choice_pct.reason")
    got = read(_traced_run({}, stats))
    assert got is None if want is None else got == pytest.approx(want)


def test_a_trace_without_the_kernel_reads_as_nothing():
    """The parent's program has no `mla_attend`: the readers find no
    such operation and say so without raising."""
    run = _traced_run({"H": 64, "kr": 512, "rope": 64, "L": 4})
    run.trace = tr.TraceView([e for e in run.trace.events
                              if "mla_attend" not in e["name"]])
    assert mla_roofline.step_device_ms(run) is None
    assert mla_roofline.roofline_pct(run) is None
