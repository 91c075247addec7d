"""The one-cell command at tiny widths on the CPU: every kind of cell,
with and without a trace, ends on the contract's line; the control and a
timed path broken underneath both come out as not correct.

These drive `cell.run_cell` with the look for a chip skipped; nothing here
is a measurement."""
import argparse
import json
import time

import pytest

from perfbench.harness import cell, compare, result
from perfbench.harness.manifest import ROOT, Manifest

TINY = ROOT / "perfbench" / "tests" / "data" / "BENCHMARK.json"
CELLS = ("tiny-train", "tiny-batch", "tiny-open")


def _run(workload, *, trace=0, control=0, seed=2**31 + 7, seconds=1.5):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, control=control)
    return cell.run_cell(Manifest(TINY, root=ROOT), args,
                         look_for_chip=False, t_start=time.perf_counter())


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("workload", CELLS)
def test_a_cell_ends_on_the_contracts_line(workload, trace, capsys):
    out = _run(workload, trace=trace)
    m = Manifest(TINY, root=ROOT)
    section = "per_layer" if trace else "end_to_end"
    named = {x["name"] for x in m.metrics_of(workload, section)}
    assert set(out["metrics"]) <= named
    if not trace:
        assert set(out["metrics"]) == named  # host-clock metrics all read
        assert out["metrics"]["setup_s"]["value"] > 0
    else:
        # nothing ran on an accelerator here: device metrics are left
        # out, never reported as 0
        assert not any(k.endswith("_roofline") or k.startswith("device.")
                       for k in out["metrics"])
        assert {"busy_s", "window_s"} <= set(out["device"])
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0  # nothing compiled in the window
    result.emit(correct=out["correct"], attempted=out["attempted"],
                failed=out["failed"], metrics=out["metrics"],
                device=out["device"], compared=out["compared"],
                breakdown=out["breakdown"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])


@pytest.mark.parametrize("workload", ("tiny-train", "tiny-batch"))
def test_the_control_comes_out_as_not_correct(workload):
    """The reference in the program's place, computed in float8, fails a
    limit that the bfloat16 program keeps."""
    out = _run(workload, control=1, seed=11)
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell(workload)["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.nn import multilayer

    monkeypatch.setattr(multilayer, "apply_layer_update",
                        lambda layer, upd, params, grads, it: (params, upd))
    out = _run("tiny-train")
    assert out["correct"] is False
    assert out["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.models import transformer

    plain = transformer.GPTPlan.final_logits
    monkeypatch.setattr(transformer.GPTPlan, "final_logits",
                        lambda self, bp, params, x: -plain(self, bp, params,
                                                           x))
    out = _run("tiny-batch")
    assert out["correct"] is False
    c = out["compared"]["served_gap_max"]
    assert c["value"] > c["limit"]
