"""The `deepseek_v2` family's serve cell at tiny widths on the CPU,
through `cell.run_cell` with the look for a chip skipped: the cell ends
on the contract's line with the engine's counters read (one pool of
latent pages a layer, the rows that chose a held expert counted), the two
readers this family brings find nothing to read where nothing ran on an
accelerator (or where the program has no such counter) and read a
hand-made device trace right, and the float8 control comes out as not
correct. Nothing here is a measurement."""
import argparse
import time

import pytest

from perfbench.harness import cell, compare, mla_roofline
from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

TINY = ROOT / "perfbench" / "tests" / "data" / "dsv2" / "BENCHMARK.json"
CELL = "dsv2-serve-longgen"
NEW = ("mla_attend_roofline.longgen", "moe.rows_local_pct.longgen")


def _run(*, trace=0, control=0, seed=2**31 + 7, seconds=2.0):
    args = argparse.Namespace(workload="tiny-dsv2-longgen", seed=seed,
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
            "step.moe_experts_device_ms.chat", "step.prefill_device_ms.chat",
            "step.mla_attend_device_ms.reason"} <= mine
    # `mla_attend_roofline` prices two sub-layers a layer: it would read
    # double here; the others price another family's kernel or counter
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
    assert real.raw["configs"][-1]["name"] == "deepseek-v2"
    assert [m["name"] for m in real.raw["per_layer"][-2:]] == list(NEW)
    assert {m["name"] for m in real.metrics_of(CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    mix = real.traffic(real.workload(CELL)["traffic"])
    eng = mix["engine"]
    assert (eng["n_slots"], eng["pool_pages"], eng["max_len"],
            eng["page_size"], eng["decode_chunk"], mix["backlog"]) \
        == (128, 8448, 12288, 128, 4, 64)
    assert eng["prompt_buckets"] == [1024, 2048, 4096]
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"kind": "log_uniform", "lo": 1024, "hi": 4096},
        {"kind": "log_uniform", "lo": 4096, "hi": 8192})
    assert (mix["requests"], mix["cycles"], mix["fill_min_output"],
            mix["trace_s"]) == (256, 2, 8, 4.0)
    cfg = real.config("deepseek-v2")
    sz = real.family(cfg).sizes(cfg)
    assert (sz["d"], sz["f"], sz["L"], sz["L_moe"], sz["mla_sub_layers"],
            sz["H"], sz["kr"], sz["rope"]) \
        == (5120, 1536, 5, 4, 5, 128, 512, 64)
    # every prompt fits a bucket (none is chunked) and every request its
    # row of the page table; the pool holds the 128 longest at once
    from perfbench.harness import traffic
    pairs = traffic.length_pairs(mix, mix["requests"])
    assert pairs[:, 0].max() <= 4096 and pairs.sum(1).max() <= 12288
    assert 5 * 8449 * 576 * 128 * 2 == pytest.approx(6.23e9, rel=1e-3)


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_ends_correct_with_its_counters_read(trace):
    out = _run(trace=trace)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0
    after = out["run"].facts["stats_after"]
    assert (after["latent_blocks"], after["kv_blocks"],
            after["recurrent_blocks"]) == (3, 0, 0)
    assert after["latent_bytes_per_token"] == 3 * 24 * 2
    assert after["moe_experts_held"] == 2 * 4
    assert 0 < after["moe_rows_local"] <= after["moe_held_choices"]
    if trace:
        m = out["metrics"]
        # group 1 of 4 held and 2 reached: a row is local where group 1
        # is among its two and takes one of its three choices
        assert 10.0 < m["moe.rows_local_pct.longgen"]["value"] < 90.0
        assert m["moe.local_share_pct.chat"]["value"] \
            <= m["moe.rows_local_pct.longgen"]["value"]
        assert 0.0 < m["moe.experts_hit_pct.chat"]["value"] <= 100.0
        assert m["moe.experts_read_pct.chat"]["value"] \
            == m["moe.experts_hit_pct.chat"]["value"]
    for name in (NEW[0], "step.mla_attend_device_ms.reason",
                 "moe_experts_roofline", "step.moe_experts_device_ms.chat"):
        # nothing ran on an accelerator: no device metric is reported,
        # and the readers say so without raising
        assert name not in out["metrics"]
        assert Manifest(TINY, root=ROOT).reader(name)(out["run"]) is None


def test_the_control_comes_out_as_not_correct():
    out = _run(control=1, seed=11)
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell("tiny-dsv2-longgen")["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged


# ------------------------------------------- the readers on a device trace
DEV, HOST = "/device:TPU:0", "/host:CPU"
KERNEL = ('%mla_attend.{n} = bf16[128,128,512]{{2,1,0}} custom-call('
          's32[128,96] %pt, s32[128] %pos, s32[128] %g, bf16[128,128,576] '
          '%q, bf16[8449,576,128] %pool), '
          'custom_call_target="tpu_custom_call"')
OTHERS = ('%latent_write.1 = bf16[8449,576,128] custom-call(bf16[8] %q), '
          'custom_call_target="tpu_custom_call"',
          '%moe_experts.1 = bf16[128,5120] custom-call(bf16[8] %q), '
          'custom_call_target="tpu_custom_call"')
SIZES = {"H": 128, "kr": 512, "rope": 64, "L": 5, "mla_sub_layers": 5,
         "d": 5120, "f": 1536, "topk": 6}


def _traced_run(sizes, stats=None):
    """Two single steps and one chunk of 4 in the window, five
    sub-layers: each step holds five 1 ms attend calls, each run two
    other Pallas calls that are not the attention's."""
    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    events = [ev(HOST, "python3", "perfbench.window", 0, 90_000_000)]
    for prog, t, calls in (("decode_step", 1e6, 5), ("decode_step", 9e6, 5),
                           ("decode_chunked", 20e6, 20)):
        events.append(ev(DEV, tr.MODULE_LINE, f"jit_{prog}(7)", t,
                         calls * 1_300_000))
        for j in range(calls):
            events.append(ev(DEV, tr.OPS_LINE, KERNEL.format(n=j),
                             t + 1_100_000 * j, 1_000_000))
        for k, other in enumerate(OTHERS):
            events.append(ev(DEV, tr.OPS_LINE, other,
                             t + 1_100_000 * calls + 30_000 * k, 20_000))
    stats = stats or {}
    facts = {"t_open": 0.0, "t_close": 1.0, "decode_chunk": 4,
             # (pre, post, chunk, live slots, live context at its issue)
             "decodes": [(0.1, 0.2, 1, 120, 400_000),
                         (0.3, 0.4, 1, 120, 400_120),
                         (0.5, 0.6, 4, 128, 420_000)],
             "stats_before": {k: 0 for k in stats}, "stats_after": stats}
    return Run(workload="w", kind="closed", chips=1,
               device_kind="TPU v5 lite", sizes=sizes, mix={}, setup_s=0.0,
               window_s=1.0, setup_compile={}, window_programs=0,
               facts=facts, trace=tr.TraceView(events),
               traced={"t0": 0.0, "t1": 1.0})


def test_the_roofline_reader_on_a_hand_made_device_trace():
    run = _traced_run(SIZES)
    real = Manifest(ROOT / "BENCHMARK.json")
    # 30 attend calls of 1 ms over 2 + 4 steps
    assert real.reader("step.mla_attend_device_ms.reason")(run) \
        == pytest.approx(5.0)
    ctx = (400_000 + 400_120 + sum(420_000 + 128 * j for j in range(4))) / 6
    live = (120 + 120 + 4 * 128) / 6
    ops, nbytes = mla_roofline.latent_decode(ctx, live, 128, 512, 64)
    assert ops == pytest.approx(2 * 128 * 1088 * ctx)
    # 242 operations a cached byte against the chip's 240: the compute
    # side sets the least time here, by a little (the queries' and the
    # outputs' bytes pull the whole under the ridge)
    least = max(5 * ops / 197e12, 5 * nbytes / 819e9)
    share = real.reader(NEW[0])(run)
    assert share == pytest.approx(100.0 * least / 5.0e-3)
    assert 55.0 < share < 70.0
    # the two-sub-layer reader would read double on the same trace
    assert mla_roofline.roofline_pct(run) == pytest.approx(2 * share)
    # another family's sizes, a trace without the kernel (the parent's
    # program), or a run without a trace: nothing, and no raise
    assert real.reader(NEW[0])(_traced_run(
        {"H": 64, "kr": 512, "rope": 64, "L": 4})) is None
    bare = _traced_run(SIZES)
    bare.trace = tr.TraceView([e for e in bare.trace.events
                               if "mla_attend" not in e["name"]])
    assert real.reader(NEW[0])(bare) is None
    run.trace = None
    assert real.reader(NEW[0])(run) is None


@pytest.mark.parametrize("stats,sizes,want", [
    ({"moe_routed": 6000, "moe_rows_local": 350}, SIZES, 35.0),
    ({"moe_routed": 6000}, SIZES, None),          # a program without it
    ({"moe_routed": 0, "moe_rows_local": 0}, SIZES, None),
    ({"moe_routed": 6000, "moe_rows_local": 350}, {"H": 64}, None),
], ids=["read", "no-counter", "no-steps", "another-family"])
def test_the_rows_local_share(stats, sizes, want):
    read = Manifest(ROOT / "BENCHMARK.json").reader(NEW[1])
    got = read(_traced_run(sizes, stats))
    assert got is None if want is None else got == pytest.approx(want)
