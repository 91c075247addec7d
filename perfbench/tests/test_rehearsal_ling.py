"""The `ling_flash` family's serve cell at tiny widths on the CPU,
through `cell.run_cell` with the look for a chip skipped: the cell ends
on the contract's line with the engine's counters read (recurrent slots
AND latent pages in one net, the rows that chose a held expert counted),
the two readers this family brings find nothing to read where nothing
ran on an accelerator and read a hand-made device trace right, and the
float8 control comes out as not correct. Nothing here is a
measurement."""
import argparse
import time

import pytest

from perfbench.harness import cell, compare, kda_roofline
from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

TINY = ROOT / "perfbench" / "tests" / "data" / "ling" / "BENCHMARK.json"
CELL = "ling3flash-serve-reason"
NEW = ("kda_step_roofline", "step.kda_step_device_ms.reason")


def _run(*, trace=0, control=0, seed=2**31 + 7, seconds=2.0):
    args = argparse.Namespace(workload="tiny-ling-reason", seed=seed,
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
            "moe.experts_read_pct.chat", "moe.prefill_sorted_pct.chat",
            "moe.rows_local_pct.longgen", "moe_experts_roofline",
            "step.moe_experts_device_ms.chat", "step.prefill_device_ms.chat",
            "step.mla_attend_device_ms.reason",
            "mla_attend_roofline.longgen"} <= mine
    # the others price another family's kernel, counter or layer count
    assert not mine & {"mla_attend_roofline", "moe.zero_choice_pct.reason",
                       "paged_attention_roofline",
                       "step.kv_attend_device_ms.batch",
                       "moe_relu2_experts_roofline", "gdn_step_roofline",
                       "step.gdn_step_device_ms.chat"}
    for name in NEW:
        listed = next(m for m in real.raw["per_layer"]
                      if m["name"] == name)
        assert listed["workloads"] == [CELL]
        assert listed["moves"] == "serve_tokens_per_s"
    assert real.raw["workloads"][-1]["name"] == CELL
    assert real.raw["configs"][-1]["name"] == "ling-3.0-flash"
    assert [m["name"] for m in real.raw["per_layer"][-2:]] == list(NEW)
    assert {m["name"] for m in real.metrics_of(CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    # LongCat's traffic file, unchanged
    assert real.workload(CELL)["traffic"] \
        == real.workload("longcatflash-serve-reason")["traffic"]
    mix = real.traffic(real.workload(CELL)["traffic"])
    eng = mix["engine"]
    assert (eng["n_slots"], eng["pool_pages"], eng["max_len"],
            eng["page_size"], eng["decode_chunk"], mix["backlog"]) \
        == (128, 2560, 4096, 128, 4, 64)
    assert eng["prompt_buckets"] == [256, 512, 1024]
    cfg = real.config("ling-3.0-flash")
    sz = real.family(cfg).sizes(cfg)
    assert (sz["d"], sz["f"], sz["L"], sz["L_dense"], sz["L_moe"],
            sz["mla_sub_layers"], sz["H"], sz["kr"], sz["rope"]) \
        == (2560, 768, 12, 2, 10, 2, 32, 512, 64)
    assert (sz["lh"], sz["lk"], sz["lv"], sz["V"], sz["eps"]) \
        == (32, 128, 128, 19648, 1e-6)
    assert sz["layer_types"] == (("linear_attention",) * 5
                                 + ("full_attention",)) * 2
    # every prompt fits a bucket (none is chunked) and every request its
    # row of the page table
    from perfbench.harness import traffic
    pairs = traffic.length_pairs(mix, mix["requests"])
    assert pairs[:, 0].max() <= 1024 and pairs.sum(1).max() <= 4096
    # the state a slot and the latent pool, as the issue reckons them
    assert 128 * 10 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) \
        == pytest.approx(2.78e9, rel=2e-3)
    assert 2 * 2561 * 576 * 128 * 2 == pytest.approx(0.755e9, rel=2e-3)


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_ends_correct_with_its_counters_read(trace):
    out = _run(trace=trace)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0
    after = out["run"].facts["stats_after"]
    # two periods of (KDA, KDA, MLA): both cache kinds in one net
    assert (after["recurrent_blocks"], after["latent_blocks"],
            after["kv_blocks"], after["stateless_blocks"]) == (4, 2, 0, 0)
    assert after["latent_bytes_per_token"] == 2 * 24 * 2
    # 4 heads of 8 x 8 in float32 and three bfloat16 taps of 96 columns
    assert after["state_bytes_per_slot"] == 4 * (4 * 8 * 8 * 4 + 3 * 96 * 2)
    assert after["moe_experts_held"] == 4 * 4
    assert 0 < after["moe_rows_local"] <= after["moe_held_choices"]
    if trace:
        m = out["metrics"]
        # group 1 of 4 held and 2 reached: a row is local where group 1
        # is among its two and takes one of its three choices
        assert 10.0 < m["moe.rows_local_pct.longgen"]["value"] < 90.0
        assert m["moe.local_share_pct.chat"]["value"] \
            <= m["moe.rows_local_pct.longgen"]["value"]
        assert 0.0 < m["moe.experts_hit_pct.chat"]["value"] <= 100.0
    for name in (*NEW, "step.mla_attend_device_ms.reason",
                 "mla_attend_roofline.longgen", "moe_experts_roofline",
                 "step.moe_experts_device_ms.chat"):
        # nothing ran on an accelerator: no device metric is reported,
        # and the readers say so without raising
        assert name not in out["metrics"]
        assert Manifest(TINY, root=ROOT).reader(name)(out["run"]) is None


def test_the_control_comes_out_as_not_correct():
    out = _run(control=1, seed=11)
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell("tiny-ling-reason")["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged


# ------------------------------------------- the readers on a device trace
DEV, HOST = "/device:TPU:0", "/host:CPU"
KERNEL = ('%kda_step.{n} = (f32[128,32,128]{{2,1,0}}, '
          'f32[128,128,4096]{{2,1,0}}) custom-call(f32[128,128,96] %a, '
          'f32[128,2,32,128] %r, f32[128,128,4096] %s), '
          'custom_call_target="tpu_custom_call"')
OTHERS = ('%mla_attend.1 = bf16[128,32,512] custom-call(bf16[8] %q), '
          'custom_call_target="tpu_custom_call"',
          '%gdn_step.1 = f32[128,128,4096] custom-call(f32[8] %q), '
          'custom_call_target="tpu_custom_call"')
SIZES = {"layer_types": (("linear_attention",) * 5 + ("full_attention",)) * 2,
         "lh": 32, "lk": 128, "lv": 128}


def _traced_run(sizes):
    """Two single steps and one chunk of 4 in the window, ten linear
    blocks: each step holds ten 800 us kernel calls, each run two other
    Pallas calls that are not the step's (another family's step kernel
    among them)."""
    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    events = [ev(HOST, "python3", "perfbench.window", 0, 90_000_000)]
    for prog, t, calls in (("decode_step", 1e6, 10), ("decode_step", 12e6, 10),
                           ("decode_chunked", 24e6, 40)):
        events.append(ev(DEV, tr.MODULE_LINE, f"jit_{prog}(7)", t,
                         calls * 1_000_000))
        for j in range(calls):
            events.append(ev(DEV, tr.OPS_LINE, KERNEL.format(n=j),
                             t + 900_000 * j, 800_000))
        for k, other in enumerate(OTHERS):
            events.append(ev(DEV, tr.OPS_LINE, other,
                             t + 900_000 * calls + 30_000 * k, 20_000))
    facts = {"t_open": 0.0, "t_close": 1.0, "decode_chunk": 4,
             "decodes": [(0.1, 0.2, 1, 120, 0), (0.3, 0.4, 4, 128, 0)]}
    return Run(workload="w", kind="closed", chips=1,
               device_kind="TPU v5 lite", sizes=sizes, mix={}, setup_s=0.0,
               window_s=1.0, setup_compile={}, window_programs=0,
               facts=facts, trace=tr.TraceView(events),
               traced={"t0": 0.0, "t1": 1.0})


def test_the_readers_on_a_hand_made_device_trace():
    run = _traced_run(SIZES)
    real = Manifest(ROOT / "BENCHMARK.json")
    # 60 kernel calls of 800 us over 2 + 4 steps
    assert real.reader(NEW[1])(run) == pytest.approx(8.0)
    # live slots: (1 * 120 + 4 * 128) / 5 = 126.4, ten blocks
    state = 32 * 128 * 128
    per = 2 * 4 * state + 32 * (3 * 128 * 4 + 2 * 128 * 2 + 4)
    ops, nbytes = kda_roofline.channel_gated_delta_step(126.4, 10, 32, 128,
                                                        128)
    assert nbytes == pytest.approx(per * 126.4 * 10)
    assert ops == pytest.approx(7 * state * 126.4 * 10)
    share = real.reader(NEW[0])(run)
    assert share == pytest.approx(100.0 * (nbytes / 819e9) / 8.0e-3)
    assert 80.0 < share < 85.0
    # another family's sizes, a trace without the kernel (the parent's
    # program), or a run without a trace: nothing, and no raise
    assert real.reader(NEW[0])(_traced_run({"layer_types": ()})) is None
    assert real.reader(NEW[0])(_traced_run({"H": 64})) is None
    bare = _traced_run(SIZES)
    bare.trace = tr.TraceView([e for e in bare.trace.events
                               if "kda_step" not in e["name"]])
    assert real.reader(NEW[0])(bare) is None
    assert real.reader(NEW[1])(bare) is None
    run.trace = None
    assert real.reader(NEW[0])(run) is None
    assert real.reader(NEW[1])(run) is None
