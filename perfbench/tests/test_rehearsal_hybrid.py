"""The `granite_hybrid` family's serve cell at tiny widths on the CPU,
through `cell.run_cell` with the look for a chip skipped: the cell ends
on the contract's line with the new counters' metrics read, the float8
control comes out as not correct, and pad positions that move the
recurrent state do too. Nothing here is a measurement."""
import argparse
import time

import pytest

from perfbench.harness import cell, compare
from perfbench.harness.manifest import ROOT, Manifest, check

TINY = ROOT / "perfbench" / "tests" / "data" / "hybrid" / "BENCHMARK.json"


def _run(*, trace=0, control=0, seed=2**31 + 7, seconds=2.0):
    args = argparse.Namespace(workload="tiny-chat", seed=seed,
                              seconds=seconds, trace=trace, control=control)
    return cell.run_cell(Manifest(TINY, root=ROOT), args,
                         look_for_chip=False, t_start=time.perf_counter())


def test_the_toy_manifest_resolves():
    assert check(Manifest(TINY, root=ROOT)) == []


@pytest.mark.parametrize("trace", (0, 1))
def test_the_cell_ends_correct_with_its_counters_read(trace):
    out = _run(trace=trace)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["run"].window_programs == 0
    if trace:
        m = out["metrics"]
        # half the experts are held, and at 4 slots x top-2 of 8 over 3
        # blocks not every held expert is hit every step
        assert 25.0 < m["moe.local_share_pct.chat"]["value"] < 75.0
        assert 0.0 < m["moe.experts_hit_pct.chat"]["value"] <= 100.0
        # nothing ran on an accelerator: no device metric is reported
        assert "moe_experts_roofline" not in m
        assert "step.prefill_device_ms.chat" not in m


def test_the_control_comes_out_as_not_correct():
    out = _run(control=1, seed=11)
    assert out["correct"] is True, out["compared"]
    limits = Manifest(TINY, root=ROOT).cell("tiny-chat")["limits"]
    ok, judged = compare.verdict(out["control"], limits)
    assert ok is False, judged


def test_pad_positions_that_move_the_state_are_not_correct(monkeypatch):
    """A prefill that lets the pad positions of its bucket advance the
    recurrent state and the convolution tail must show in the
    comparison (a state merely carried over from the slot's last tenant
    decays over the prompt and reads 0.0004 against a limit of 0.001
    here: `tests/test_hybrid_decoder.py` holds that one to the bit)."""
    from deeplearning4j_tpu.nn.conf.decoder_block import sub
    from deeplearning4j_tpu.serving import block_state

    def prefill(self, p, x, cache, d):
        y, h1, tail1 = self.mixer.scan(sub(p, "mx_"),
                                       self.layer.norm1(p, x))
        x = block_state._finish_composed(self.layer, p, x, y, d)
        return x, self._store(cache, h1, tail1, d.slot)

    monkeypatch.setattr(block_state.RecurrentSlots, "prefill", prefill)
    out = _run()
    assert out["correct"] is False
    c = out["compared"]["served_gap_max"]
    assert c["value"] > c["limit"]
