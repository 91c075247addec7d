"""The work a run offers does not depend on its seed."""
import json
from collections import Counter

import numpy as np
import pytest

from perfbench.harness import traffic
from perfbench.harness.manifest import ROOT

MIXES = sorted((ROOT / "perfbench" / "traffic").glob("*.json")) + sorted(
    (ROOT / "perfbench" / "tests" / "data" / "traffic").glob("*.json"))
SEEDS = (3, 2**31 + 11)


def _mix(path):
    return json.loads(path.read_text())


def _multiset(pairs):
    return Counter(map(tuple, np.asarray(pairs).tolist()))


@pytest.mark.parametrize("path", [p for p in MIXES
                                  if _mix(p)["kind"] == "closed"],
                         ids=lambda p: p.stem)
def test_closed_mix_offers_the_same_work_for_any_seed(path):
    mix = _mix(path)
    a, b = (traffic.closed_plan(mix, s, cycles=2) for s in SEEDS)
    for part in ("fill", "queue"):
        assert _multiset(a[part]) == _multiset(b[part])
        assert a[part].sum(axis=0).tolist() == b[part].sum(axis=0).tolist()
        assert len(a[part]) == len(b[part])
        assert a[part].tolist() != b[part].tolist()  # another order
    n = mix["requests"]  # every cycle is the whole multiset again
    assert _multiset(a["queue"][:n]) == _multiset(a["queue"][n:])
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    assert lo <= a["queue"][:, 0].min() and a["queue"][:, 0].max() <= hi
    assert a["queue"][:, 0].max() <= max(mix["engine"]["prompt_buckets"])
    assert (a["queue"].sum(axis=1) <= mix["engine"]["max_len"]).all()


@pytest.mark.parametrize("path", [p for p in MIXES
                                  if _mix(p)["kind"] == "open"],
                         ids=lambda p: p.stem)
def test_open_mix_offers_the_same_work_at_its_rate_for_any_seed(path):
    mix, seconds = _mix(path), 40.0
    a, b = (traffic.open_plan(mix, s, seconds) for s in SEEDS)
    assert len(a["due"]) == len(b["due"]) and a["window"] == b["window"]
    lo, hi = a["window"]
    for part in (slice(0, lo), slice(lo, hi), slice(hi, None)):
        assert _multiset(a["pairs"][part]) == _multiset(b["pairs"][part])
        assert np.allclose(np.sort(a["gaps"][part]), np.sort(b["gaps"][part]))
    assert np.allclose(np.diff(a["due"]), a["gaps"][:-1])
    assert a["pairs"].tolist() != b["pairs"].tolist()
    assert not np.allclose(a["due"], b["due"])
    # offered rate inside the window is the mix's rate, in every run
    w0, w1 = a["window_s"]
    assert (hi - lo) / (w1 - w0) == pytest.approx(mix["rate_per_s"])
    assert w1 - w0 == pytest.approx(seconds, abs=1.0 / mix["rate_per_s"])
    assert a["due"][lo] >= w0 - 1e-9 and a["due"][hi - 1] < w1
    assert (a["pairs"].sum(axis=1) <= mix["engine"]["max_len"]).all()


def test_token_ids_and_batches_come_from_the_seed():
    mix = {"batch": 4, "seq_len": 16}
    ids, labels = traffic.fit_batch(mix, SEEDS[1], 0, 50257)
    again, _ = traffic.fit_batch(mix, SEEDS[1], 0, 50257)
    other, _ = traffic.fit_batch(mix, SEEDS[0], 0, 50257)
    nxt, _ = traffic.fit_batch(mix, SEEDS[1], 1, 50257)
    assert (ids == again).all() and (ids != other).any()
    assert (ids != nxt).any()
    assert (ids[:, 1:] == labels[:, :-1]).all()      # next-token labels
    assert len({tuple(r) for r in ids.tolist()}) == 4  # rows all differ
    p = traffic.prompt_ids(SEEDS[1], 5, 33, 100)
    assert p.shape == (33,) and p.dtype == np.int32 and p.max() < 100
    assert (p != traffic.prompt_ids(SEEDS[0], 5, 33, 100)).any()


@pytest.mark.parametrize("dist,lo,hi", [
    ({"kind": "log_uniform", "lo": 128, "hi": 512}, 128, 512),
    ({"kind": "log_normal", "median": 96, "sigma": 0.6, "lo": 32,
      "hi": 256}, 32, 256),
    ({"kind": "fixed", "value": 7}, 7, 7)])
def test_quantile_grid_is_fixed_and_in_range(dist, lo, hi):
    g = traffic.quantile_grid(dist, 64)
    assert (g == traffic.quantile_grid(dist, 64)).all()
    assert lo <= g.min() and g.max() <= hi and (np.diff(g) >= 0).all()
    if dist["kind"] == "log_normal":
        assert abs(np.median(g) - dist["median"]) <= 2
