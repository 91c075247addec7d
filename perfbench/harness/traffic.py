"""One generator for every traffic mix: a mix is a data file of parameters.

The work a run offers does not depend on its seed. A length distribution
becomes a fixed quantile grid, the same multiset of (prompt, output)
lengths for every seed, and the seed decides only the order, the token ids
and the weights. An open mix's arrivals are built the same way: a fixed
number of gaps, the quantile grid of the exponential at the mix's rate,
which the seed permutes: as bursty as Poisson arrivals, with the same
offered load in every run.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """`n` whole-number lengths at the quantiles (i + 0.5) / n of `dist`,
    in rising order."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "log_uniform":
        lo, hi = math.log(dist["lo"]), math.log(dist["hi"])
        x = np.exp(lo + q * (hi - lo))
    elif kind == "log_normal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        x = np.clip(x, dist["lo"], dist["hi"])
    else:
        raise ValueError(f"length distribution {kind!r}: fixed, "
                         "log_uniform or log_normal")
    return np.maximum(1, np.rint(x)).astype(np.int64)


def length_pairs(mix: dict, n: int) -> np.ndarray:
    """The fixed multiset of `n` (prompt_len, output_len) pairs: each
    grid of its own, paired through one fixed shuffle so that long
    prompts do not always carry long outputs."""
    prompts = quantile_grid(mix["prompt_len"], n)
    outputs = quantile_grid(mix["output_len"], n)
    pairing = np.random.default_rng(20240923).permutation(n)
    return np.stack([prompts, outputs[pairing]], axis=1)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    return rng_for(seed, 1, index).integers(
        0, vocab, int(length)).astype(np.int32)


def ordered(pairs: np.ndarray, seed: int, *stream: int) -> np.ndarray:
    return pairs[rng_for(seed, *stream).permutation(len(pairs))]


def closed_plan(mix: dict, seed: int, cycles: int) -> dict:
    """A closed loop's requests. `fill` takes the slots before the
    window opens: one request per slot whose output is cut to a fixed
    staggered share, so that the window opens on slots at every stage of
    their requests and not on `n_slots` that retire together. `queue` is
    what the clients then draw from, in order: the mix's multiset, in a
    new order of the seed's for every cycle."""
    n_slots = mix["engine"]["n_slots"]
    pairs = length_pairs(mix, mix["requests"])
    fill = length_pairs(mix, n_slots)
    share = (np.random.default_rng(20240924).permutation(n_slots) + 1.0) \
        / n_slots  # which request is how far along: fixed, like the pairs
    fill[:, 1] = np.maximum(mix["fill_min_output"],
                            np.rint(fill[:, 1] * share)).astype(np.int64)
    fill = ordered(fill, seed, 2)
    queue = np.concatenate([ordered(pairs, seed, 3, c)
                            for c in range(cycles)])
    return {"fill": fill, "queue": queue}


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """`n` gaps, the quantile grid of the exponential at `rate` a
    second, scaled so that they sum to exactly n / rate."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate / gaps.sum())


def open_plan(mix: dict, seed: int, seconds: float) -> dict:
    """An open loop's schedule in three stretches, each with its own
    fixed multiset of lengths and of gaps in an order of the seed's: the
    ramp that fills the system before the window, the window itself (its
    gaps sum to `seconds`), and a tail that keeps the load on while the
    window's last requests finish. The order is a plain permutation, so a
    seed can put short gaps together as Poisson arrivals do. Returns due
    times from the start of the ramp, the gap that follows each arrival,
    the (prompt, output) pairs, and where the window lies."""
    rate = float(mix["rate_per_s"])
    due, pairs, all_gaps, t = [], [], [], 0.0
    for k, length in enumerate((mix["ramp_s"], seconds, mix["tail_s"])):
        n = max(1, int(round(rate * length)))
        gaps = exponential_gaps(rate, n)[rng_for(seed, 4, k).permutation(n)]
        due.append(t + np.cumsum(gaps) - gaps)
        all_gaps.append(gaps)
        pairs.append(ordered(length_pairs(mix, n), seed, 5, k))
        t += n / rate
    n_ramp, n_win = len(due[0]), len(due[1])
    return {"due": np.concatenate(due), "pairs": np.concatenate(pairs),
            "gaps": np.concatenate(all_gaps),
            "window": (n_ramp, n_ramp + n_win),
            "window_s": (n_ramp / rate, n_ramp / rate + n_win / rate)}


def fit_batch(mix: dict, seed: int, step: int, vocab: int):
    """Step `step`'s (ids, next-token labels): every row is different,
    and the same for the same seed."""
    ids = rng_for(seed, 6, step).integers(
        0, vocab, (mix["batch"], mix["seq_len"] + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)
