"""Profiling / tracing hooks.

Reference (SURVEY §5 "Tracing / profiling"): the reference has only coarse
per-phase timing (`PerformanceListener` samples/sec, Spark per-phase stats,
`BaseStatsListener` fwd/bwd wall-clock). The prescribed TPU equivalent is
"per-step timing + XLA profiler hooks; keep the listener SPI" — so:

- `ProfilerListener`: an `IterationListener` capturing per-iteration
  wall-clock (with an optional sync so timings mean device time, not
  dispatch time) and summarizing percentiles.
- `XlaTraceListener`: starts/stops a `jax.profiler` trace around a chosen
  iteration window; the dump is viewable in TensorBoard/Perfetto and shows
  the real XLA op timeline on the TPU.
- `trace_annotation`: names host-side phases so they show up in the trace.
"""
from __future__ import annotations

import logging
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.optimize.listeners import IterationListener

logger = logging.getLogger("deeplearning4j_tpu")


class ProfilerListener(IterationListener):
    """Per-iteration wall-clock capture.

    `sync=True` blocks on the model's score each iteration so an interval
    covers the device step it timed (one sync per iteration — use for
    profiling runs, not production training: it defeats step pipelining)."""

    def __init__(self, sync: bool = False, log_every: int = 0):
        self.sync = sync
        self.log_every = log_every
        self.durations_ms: List[float] = []
        self._last: Optional[float] = None

    def iteration_done(self, model, iteration: int) -> None:
        if self.sync:
            _ = model.score_value  # forces device sync (lazy score read)
        now = time.perf_counter()
        if self._last is not None:
            ms = (now - self._last) * 1000.0
            self.durations_ms.append(ms)
            if self.log_every and len(self.durations_ms) % self.log_every == 0:
                logger.info("iteration %d: %.2f ms/step (mean over last %d)",
                            iteration,
                            float(np.mean(self.durations_ms[-self.log_every:])),
                            self.log_every)
        self._last = now

    def summary(self) -> Dict[str, float]:
        if not self.durations_ms:
            return {}
        d = np.asarray(self.durations_ms)
        return {
            "iterations": int(d.size),
            "mean_ms": float(d.mean()),
            "p50_ms": float(np.percentile(d, 50)),
            "p90_ms": float(np.percentile(d, 90)),
            "p99_ms": float(np.percentile(d, 99)),
            "max_ms": float(d.max()),
        }

    def reset(self) -> None:
        self.durations_ms = []
        self._last = None


class XlaTraceListener(IterationListener):
    """Captures a `jax.profiler` trace for iterations
    [start_iteration, start_iteration + num_iterations) — the XLA-level
    view (op timeline, HBM traffic) of the compiled step."""

    def __init__(self, log_dir: str, start_iteration: int = 5,
                 num_iterations: int = 5):
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.num_iterations = num_iterations
        self._active = False
        self.completed = False

    def iteration_done(self, model, iteration: int) -> None:
        import jax

        if (not self._active and not self.completed
                and iteration >= self.start_iteration):
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            self._until = iteration + self.num_iterations
        elif self._active and iteration >= self._until:
            # sync first so the trace includes the steps' device work
            _ = model.score_value
            jax.profiler.stop_trace()
            self._active = False
            self.completed = True
            logger.info("XLA trace written to %s (view in TensorBoard)",
                        self.log_dir)

    def stop(self) -> None:
        """Force-stop an in-flight trace (e.g. training ended early)."""
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self.completed = True


@contextmanager
def trace_annotation(name: str):
    """Names a host-side phase in the profiler timeline (reference analogue:
    the per-phase wall-clock keys of `SparkTrainingStats`)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextmanager
def trace_capture(log_dir: str):
    """Capture a `jax.profiler` trace over the with-block (the
    block-scoped sibling of `XlaTraceListener`'s iteration window). The
    trace always stops, even when the block raises, so an aborted
    run never leaves the profiler armed for the next one."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("XLA trace written to %s (view in TensorBoard)",
                    log_dir)
