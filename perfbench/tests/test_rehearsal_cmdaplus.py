"""The `cohere2_moe` family's serve cell at tiny widths on the CPU,
through `cell.run_cell` with the look for a chip skipped: the cell ends
on the contract's line with the engine's counters read (window rings AND
whole-context pages in one net, every ring wrapped: window 8, pages of
4), the two readers this family brings find nothing to read where
nothing ran on an accelerator or the program has no such counter, and
read a hand-made device trace right; the float8 control AND a reference
that ignores the window each come out as not correct. Nothing here is a
measurement."""
import argparse
import time

import numpy as np
import pytest

from perfbench.harness import cell, compare, window_roofline
from perfbench.harness import trace_reduce as tr
from perfbench.harness.manifest import ROOT, Manifest, check
from perfbench.harness.runrecord import Run

TINY = ROOT / "perfbench" / "tests" / "data" / "cmdaplus" / "BENCHMARK.json"
CELL = "cmdaplus-serve-longgen"
NEW = ("window_attention_roofline.longgen", "kv.window_attended_pct.longgen")


def _run(*, trace=0, control=0, seed=2**31 + 7, seconds=2.0):
    args = argparse.Namespace(workload="tiny-cmdaplus-longgen", seed=seed,
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
            "step.kv_attend_device_ms.batch",
            "dispatch.families_engaged.batch", "setup.build_s.batch"} <= mine
    # the others price another family's kernel, counter or head count
    assert not mine & {"paged_attention_roofline", "mla_attend_roofline",
                       "mla_attend_roofline.longgen", "kda_step_roofline",
                       "gdn_step_roofline", "moe_relu2_experts_roofline",
                       "moe.zero_choice_pct.reason"}
    for name, layer in zip(NEW, ("kernels", "block state")):
        listed = next(m for m in real.raw["per_layer"] if m["name"] == name)
        assert listed["workloads"] == [CELL]
        assert listed["moves"] == "serve_tokens_per_s"
        assert listed["layer"] == layer
    assert real.raw["workloads"][-1]["name"] == CELL
    assert real.raw["workloads"][-1]["chips"] == 1
    assert real.raw["configs"][-1]["name"] == "command-a-plus-05-2026"
    assert real.raw["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert [m["name"] for m in real.raw["per_layer"][-2:]] == list(NEW)
    assert {m["name"] for m in real.metrics_of(CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    mix = real.traffic(real.workload(CELL)["traffic"])
    eng = mix["engine"]
    assert (eng["n_slots"], eng["pool_pages"], eng["max_len"],
            eng["page_size"], eng["prefill_chunk"], eng["decode_chunk"],
            mix["backlog"]) == (48, 3168, 12288, 128, 128, 4, 24)
    assert eng["prompt_buckets"] == [1024, 2048, 4096]
    # closed-longgen-s128's lengths exactly
    other = real.traffic("closed-longgen-s128")
    for key in ("prompt_len", "output_len", "requests", "cycles",
                "fill_min_output", "trace_s"):
        assert mix[key] == other[key], key
    # no prompt is chunked; every queued request ends between 5,120 and
    # 12,288 positions, so every ring (33 pages of 128) wraps
    from perfbench.harness import traffic
    pairs = traffic.length_pairs(mix, mix["requests"])
    assert pairs[:, 0].max() <= 4096 and pairs[:, 0].min() >= 1024
    ends = pairs.sum(1)
    assert 5120 <= ends.min() and ends.max() <= 12288
    assert ends.min() > 33 * 128
    # all 72 clients' requests in flight fit the pool's first class
    assert np.sort(-(-ends // 128))[-48:].sum() <= 48 * 96
    cfg = real.config("command-a-plus-05-2026")
    sz = real.family(cfg).sizes(cfg)
    assert (sz["d"], sz["f"], sz["L"], sz["H"], sz["Hkv"], sz["hd"],
            sz["W"], sz["window_layers"], sz["full_layers"], sz["V"],
            sz["eps"]) == (4096, 4096, 4, 128, 8, 128, 4096, 3, 1, 32768,
                           1e-5)
    # the pools, as the issue reckons them
    page = 2 * 8 * 128 * 128 * 2
    assert 3 * (48 * 33 + 1) * page == pytest.approx(2.49e9, rel=3e-3)
    assert (3168 + 1) * page == pytest.approx(1.66e9, rel=3e-3)


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_ends_correct_with_its_counters_read(trace):
    out = _run(trace=trace)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0
    after = out["run"].facts["stats_after"]
    assert (after["window_blocks"], after["kv_blocks"],
            after["recurrent_blocks"], after["latent_blocks"]) == (3, 1, 0, 0)
    assert after["window_ring_pages"] == 3
    # 2 K/V heads of 8 in bfloat16, K and V, 12 positions, 3 blocks
    assert after["window_bytes_per_slot"] == 3 * 12 * 2 * 2 * 8 * 2
    assert 0 < after["window_pages_in_use"] <= 4 * 3 \
        == after["window_pages_in_use_peak"]
    assert after["moe_experts_held"] == 4 * 4
    loop = after["loop"]
    assert 0 < loop["kv_positions_attended"] < loop["kv_positions_context"]
    if trace:
        m = out["metrics"]
        # contexts of 8 to 60 positions, windows of 8: well under all
        assert 25.0 < m["kv.window_attended_pct.longgen"]["value"] < 90.0
        assert 0.0 < m["moe.experts_hit_pct.chat"]["value"] <= 100.0
        assert 10.0 < m["moe.rows_local_pct.longgen"]["value"] < 100.0
    for name in (NEW[0], "moe_experts_roofline",
                 "step.moe_experts_device_ms.chat",
                 "step.kv_attend_device_ms.batch"):
        # nothing ran on an accelerator: no device metric is reported,
        # and the readers say so without raising
        assert name not in out["metrics"]
        assert Manifest(TINY, root=ROOT).reader(name)(out["run"]) is None


def test_the_control_and_a_window_ignored_each_come_out_not_correct():
    """One precision down, and the reference with its window layers
    reading their whole context, each over the same served tokens: both
    pass a limit the served program meets."""
    import jax.numpy as jnp

    from perfbench.harness import serve_cell

    sampled = {}
    keep = serve_cell._compare_served

    def spy(ctx, fam, sz, sample, cell_, ref):
        sampled.update(ctx=ctx, fam=fam, sz=sz, sample=sample, ref=ref)
        return keep(ctx, fam, sz, sample, cell_, ref)

    serve_cell._compare_served = spy
    try:
        # the runner module is loaded anew by path: patch what it imports
        import perfbench.harness.serve_cell as sc
        assert sc is serve_cell
        out = _run(control=1, seed=11)
    finally:
        serve_cell._compare_served = keep
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell("tiny-cmdaplus-longgen")["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged
    if not sampled:
        pytest.skip("the runner did not go through the patched harness")
    # every sampled request ran past its ring; the longest past 3 windows
    ctx, fam, sz, ref = (sampled[k] for k in ("ctx", "fam", "sz", "ref"))
    longest = sampled["sample"][0]
    assert len(longest.prompt) + len(longest.tokens) > 3 * 8
    w = fam.make_weights(ctx.seed, sz, "stacked")
    gaps = []
    for r in sampled["sample"]:
        ids = np.concatenate([r.prompt, r.tokens[:-1]])
        rows = np.arange(len(r.prompt) - 1, len(ids))
        kw = dict(n_heads=sz["H"], eps=sz["eps"])
        right = np.asarray(ref.logits_at(w, jnp.asarray(ids)[None],
                                         jnp.asarray(rows), **kw))
        blind = np.asarray(ref.logits_at(w, jnp.asarray(ids)[None],
                                         jnp.asarray(rows),
                                         window_ignored=True, **kw))
        # how far the window-blind model's own choice lies under the
        # reference's best: what `served_gap_*` would read of a program
        # that ignored the window
        choice = blind.argmax(-1)
        gaps.append(right.max(-1) - right[np.arange(len(choice)), choice])
    g = np.concatenate(gaps)
    numbers = {"served_gap_max": float(g.max()),
               "served_gap_mean": float(g.mean()),
               "served_miss_share": float((g > 0).mean())}
    ok, judged = compare.verdict(numbers, limits)
    assert ok is False, judged


# ------------------------------------------- the readers on a device trace
DEV, HOST = "/device:TPU:0", "/host:CPU"
KERNEL = ('%paged_attention.{n} = bf16[48,1,128,128]{{3,2,1,0}} '
          'custom-call(s32[48,33] %t, bf16[48,1,128,128] %q), '
          'custom_call_target="tpu_custom_call"')
OTHERS = ('%moe_experts.1 = bf16[48,4096] custom-call(bf16[8] %q), '
          'custom_call_target="tpu_custom_call"',
          '%paged_kv_write.1 = bf16[1585,8,128,128] custom-call(bf16[8] %q), '
          'custom_call_target="tpu_custom_call"')
SIZES = {"H": 128, "Hkv": 8, "hd": 128, "window_layers": 3, "full_layers": 1}


# the hand-made run's stretch on the program's clock: `perf_counter`
# never reads under 0, so no span of another test lies in it
T0 = -1000.0


def _traced_run(sizes, loop=True):
    """Two single steps and one chunk of 4 in the window, four K/V
    blocks: each step holds four 1.5 ms attention calls, each run two
    other Pallas calls that are not the attention's. The program's
    timeline holds the three dispatches' spans with the positions each
    attended (none with `loop=False`: the parent's program), and one
    dispatch issued before the traced stretch at contexts that would
    halve the share."""
    from deeplearning4j_tpu.serving.observability import TIMELINE

    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start_ns": float(start), "dur_ns": float(dur)}

    events = [ev(HOST, "python3", "perfbench.window", 0, 90_000_000)]
    for prog, t, calls in (("decode_step", 1e6, 4), ("decode_step", 12e6, 4),
                           ("decode_chunked", 24e6, 16)):
        events.append(ev(DEV, tr.MODULE_LINE, f"jit_{prog}(7)", t,
                         calls * 2_000_000))
        for j in range(calls):
            events.append(ev(DEV, tr.OPS_LINE, KERNEL.format(n=j),
                             t + 1_700_000 * j, 1_500_000))
        for k, other in enumerate(OTHERS):
            events.append(ev(DEV, tr.OPS_LINE, other,
                             t + 1_700_000 * calls + 30_000 * k, 20_000))
    # 6 steps of 48 live slots at a context of 6,000: a window block
    # reads 4,096 of it
    steps, slots, ctx = 6, 48, 6000
    read = steps * slots * (3 * 4096 + ctx)
    before = {"decode_steps": 100, "loop": {}}
    after = {"decode_steps": 100 + steps, "loop": {}}
    if loop:
        before["loop"] = {"kv_positions_attended": 7,
                          "kv_positions_context": 9}
        after["loop"] = {"kv_positions_attended": 7 + read,
                         "kv_positions_context": 9 + steps * slots * 4 * ctx}
    facts = {"t_open": T0 - 2.0, "t_close": T0 + 38.0, "decode_chunk": 4,
             "decodes": [(T0 + 0.1, T0 + 0.2, 1, slots, 0),
                         (T0 + 0.3, T0 + 0.4, 1, slots, 0),
                         (T0 + 0.5, T0 + 0.6, 4, slots, 0)],
             "stats_before": before, "stats_after": after}
    if not any(s[0] == "decode.dispatch" for s in
               TIMELINE.snapshot(T0 - 1.0, T0 + 1.0)):
        for a, c, each in ((-0.5, 4, 2 * ctx), (0.1, 1, ctx), (0.3, 1, ctx),
                           (0.5, 4, ctx)):
            TIMELINE.record(
                "decode.dispatch", T0 + a, T0 + a + 0.01, 1, 7,
                {"program": "decode_step", "chunk": c, "active": slots,
                 "kv_positions_attended": c * slots * (3 * 4096 + each),
                 "kv_positions_context": c * slots * 4 * each})
            TIMELINE.record("decode.wait", T0 + a + 0.01, T0 + a + 0.02, 1,
                            7, None)
    run = Run(workload="w", kind="closed", chips=1,
              device_kind="TPU v5 lite", sizes=sizes, mix={}, setup_s=0.0,
              window_s=40.0, setup_compile={}, window_programs=0,
              facts=facts, trace=tr.TraceView(events),
              traced={"t0": T0, "t1": T0 + 1.0})
    if not loop:
        # a program whose spans carry no count: the stretch before ours
        run.traced = {"t0": T0 - 5.0, "t1": T0 - 4.0}
    return run


def test_the_readers_on_a_hand_made_device_trace():
    run = _traced_run(SIZES)
    real = Manifest(ROOT / "BENCHMARK.json")
    roof, pct = (real.reader(n) for n in NEW)
    assert pct(run) == pytest.approx(
        100.0 * (3 * 4096 + 6000) / (4 * 6000))
    # 24 attention calls of 1.5 ms over 2 + 4 steps: 6 ms a step
    per_step = 48 * (3 * 4096 + 6000)
    ops, nbytes = window_roofline.paged_window_decode(per_step, 48, 4, 128,
                                                      8, 128)
    assert nbytes == per_step * 4096 + 2 * 128 * 128 * 2 * 48 * 4
    assert ops == 2 * 2 * 128 * 128 * per_step
    assert ops / nbytes < 17              # memory-bound
    share = roof(run)
    assert share == pytest.approx(100.0 * (nbytes / 819e9) / 6.0e-3)
    assert 60.0 < share < 100.0
    # the same kernel's time, by the accepted reader
    assert real.reader("step.kv_attend_device_ms.batch")(run) \
        == pytest.approx(6.0)
    # another family's sizes, a program without the counters (the
    # parent's), a trace without the kernel, or a run without a trace:
    # nothing, and no raise
    assert roof(_traced_run({"H": 64})) is None
    bare = _traced_run(SIZES, loop=False)
    assert roof(bare) is None and pct(bare) is None
    quiet = _traced_run(SIZES)
    quiet.trace = tr.TraceView([e for e in quiet.trace.events
                                if "paged_attention" not in e["name"]])
    assert roof(quiet) is None and pct(quiet) is not None
    run.trace = None
    assert roof(run) is None and pct(run) is not None
