"""Benchmark: training/inference throughput for every BASELINE config.

BASELINE.md metrics (the reference publishes no numbers —
`BASELINE.json "published": {}`).

Usage: `python bench.py [--trace[=DIR]] [lenet|resnet50|lstm|gpt|
word2vec|generate|serve_pool|serve_generate|...]` (default: ALL
configs; see `_CONFIGS` for the full set). `--trace` wraps each
config's first steady-state timed pass in a `jax.profiler` capture
(default DIR /tmp/dl4j_tpu_trace). Prints ONE JSON line:
  {"device": {"platform", "kind", "count"},
   "configs": {name: {metric, value, unit, mfu, spread, ...}, ...},
   "kernels": {family: [{class, ok, message}, ...]}}
with a computed MFU estimate (XLA-counted step FLOPs over the peak
`_PEAK_FLOPS` lists for the device's `device_kind`) per training config.

This is a MEASURING entry point: `main()` refuses to run unless JAX's
default backend is a TPU whose `device_kind` the peak table knows — a
CPU run of these configs is a correctness smoke, never a rate, and the
`slow` CPU tests get that by calling the `bench_*` functions directly.

Measurement methodology: every timed region ends with a HOST
MATERIALIZATION of a value that depends on the whole step chain (the
last loss) — dispatch is asynchronous, so a timing that does not consume
the result measures the enqueue. Batches are staged in HBM up front
(DeviceCacheDataSetIterator) and the timed pass is a steady-state epoch,
so the figures measure the chip, not the host-to-device copy. Every
config repeats the timed pass 5x and reports the MEDIAN plus a "spread"
(max/min) field: a number quoted without a spread is a single-run
observation, not a claim.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time

import numpy as np

# set by `--trace[=DIR]`: each config's FIRST steady-state timed pass is
# wrapped in a `jax.profiler` capture (profiler.trace_capture) — compile
# and warm passes already ran, so the trace holds only steady-state
# steps. On-chip, `python bench.py --trace gpt_long` is ROADMAP S3's
# "trace the unattributed part of the step" ask as one command; view the
# capture in TensorBoard.
_TRACE_DIR = None


def _maybe_trace_capture():
    """A `trace_capture(_TRACE_DIR)` context when `--trace` armed the
    capture, else a free no-op."""
    if _TRACE_DIR is None:
        return contextlib.nullcontext()
    from deeplearning4j_tpu.profiler import trace_capture

    return trace_capture(_TRACE_DIR)


def _sync(net) -> float:
    """Host sync: materialize the last step's loss. The loss depends on
    the whole preceding step chain, so this only returns once every
    dispatched step has executed — one scalar crosses to the host, so
    the sync itself costs nothing measurable."""
    return float(np.asarray(net._score))


_REPEATS = 5  # median-of-5: tolerates TWO stalled passes (median-of-3
# only survives one)


def _median_spread(dts):
    """Median + run-to-run spread (max/min) of repeated timings: the
    median is the number of record and the spread is its error bar (the
    reference's PerformanceListener reports per-interval rates for the
    same reason, `optimize/listeners/PerformanceListener.java`)."""
    return float(np.median(dts)), float(max(dts) / min(dts))


def _throughput(net, batches, warmup, bench, scan_steps=1,
                epochs_per_pass=1, return_dts=False):
    """Time `bench` training steps (x `epochs_per_pass`), `_REPEATS`
    times; return (median seconds-per-epoch, spread) — or the raw
    per-repeat list with `return_dts` (the device-time differencing
    helpers pair full/half runs by index). Batches are staged
    in HBM up front (DeviceCacheDataSetIterator) — the realistic pipeline
    for benchmark-sized datasets, and the only way the measurement
    reflects the chip rather than the host-to-device copy.
    `scan_steps` is an experiment knob: with resident batches the async
    dispatch queue already hides the per-dispatch host cost, and
    scan's extra device-side batch stacking measured SLOWER for every
    config, so all configs run scan_steps=1. `epochs_per_pass`: configs
    whose epoch is under ~100 ms (lenet) repeat it inside the timed
    region — same workload, longer window, so one host hiccup no longer
    shows up as a 1.5x spread."""
    from deeplearning4j_tpu.datasets.iterators import (
        DeviceCacheDataSetIterator,
    )

    warm_it = DeviceCacheDataSetIterator(batches[:warmup])
    bench_it = DeviceCacheDataSetIterator(batches[warmup:warmup + bench])
    net.fit(warm_it, scan_steps=scan_steps)   # compile pass
    # one untimed pass over the bench data, so every timed pass starts
    # from the steady state (buffers resident, queue primed)
    net.fit(bench_it, scan_steps=scan_steps)
    _sync(net)
    dts = []
    for rep in range(_REPEATS):
        t0 = time.perf_counter()
        # --trace: capture the first timed pass only (the sync before
        # stop_trace keeps the device work in-window). Profiling skews
        # that pass's wall time; median-of-_REPEATS absorbs it.
        with _maybe_trace_capture() if rep == 0 \
                else contextlib.nullcontext():
            for _e in range(epochs_per_pass):
                bench_it.reset()
                net.fit(bench_it, scan_steps=scan_steps)
            _sync(net)
        dts.append((time.perf_counter() - t0) / epochs_per_pass)
    if return_dts:
        return dts
    return _median_spread(dts)


def _device_differenced(net, batches, warmup, bench, units_per_step,
                        scan_steps=1, epochs_per_pass=1, full_dts=None):
    """Half-work differencing (ROADMAP item 4, the `device_ms_per_token`
    discipline generalized to the remaining training configs): time a
    half-length epoch at the SAME compiled shapes and take the
    incremental cost of the extra steps. The per-pass fixed cost —
    dispatch bookkeeping, host hiccups — cancels in
    (dt_full − dt_half), so the number attributes to the chip, not to
    host noise on the dispatch-bound configs. Full/half repeats are paired BY INDEX so slow host drift
    cancels within each pair, giving the differenced value its own
    honest spread. Pass `full_dts` (a `return_dts=True` run at the same
    arguments) to reuse the caller's wall measurement instead of paying
    a third timed run. Returns (device_units_per_sec,
    device_ms_per_unit, spread) or (None, None, None) when noise swamps
    the differencing (any pair non-positive) — callers then fall back
    to wall numbers."""
    half = bench // 2
    if half < 1 or half == bench:
        return None, None, None
    if full_dts is None:
        full_dts = _throughput(net, batches, warmup, bench,
                               scan_steps=scan_steps,
                               epochs_per_pass=epochs_per_pass,
                               return_dts=True)
    half_dts = _throughput(net, batches, warmup, half,
                           scan_steps=scan_steps,
                           epochs_per_pass=epochs_per_pass,
                           return_dts=True)
    diffs = [f - h for f, h in zip(full_dts, half_dts)]
    if any(d <= 0 for d in diffs):
        return None, None, None
    d_med, spread = _median_spread(diffs)
    units = (bench - half) * units_per_step
    return units / d_med, 1e3 * d_med / units, spread


# Peak matmul FLOP/s per chip, keyed by `device_kind` prefix: (bf16, f32).
# bf16 is the published MXU peak (Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s); f32 matmuls run at roughly half the bf16 rate. A device
# the table does not list is an error, never a default: an MFU against
# the wrong peak is a wrong number with the right name.
_PEAK_FLOPS = {
    "TPU v5 lite": (197e12, 98.5e12),
    "TPU v5e": (197e12, 98.5e12),
}


def _peak_flops(device_kind: str, bf16: bool) -> float:
    for prefix, (peak_bf16, peak_f32) in _PEAK_FLOPS.items():
        if device_kind.startswith(prefix):
            return peak_bf16 if bf16 else peak_f32
    raise ValueError(
        f"no peak FLOP/s for device_kind {device_kind!r}: add it (with "
        "its source) to bench._PEAK_FLOPS before reporting MFU on it")


def _device() -> dict:
    """The device every printed result names."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _step_flops(net, ds) -> float:
    """FLOPs of one compiled train step, counted by XLA's cost analysis on
    the optimized HLO (covers fwd + bwd + updater + fused normalizer —
    the whole computation the throughput numbers time)."""
    import jax
    import jax.numpy as jnp

    f, l, fm, lm = net._batch_arrays(ds)
    step = net.train_step_fn()
    c = jax.jit(step).lower(net._params, net._upd_state, net._layer_state,
                            jnp.asarray(0, jnp.int32), f, l, fm,
                            lm).compile()
    return float(c.cost_analysis()["flops"])


def _mfu(flops_per_unit: float, units_per_sec: float, bf16: bool) -> float:
    """Model FLOPs utilization vs the device's per-chip peak."""
    import jax

    return flops_per_unit * units_per_sec / _peak_flops(
        jax.devices()[0].device_kind, bf16)


def bench_lenet():
    from deeplearning4j_tpu.datasets.fetchers import MnistDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    # batch sweep on resident data (steady state): 1024->250k, 4096->459k,
    # 8192->444k samples/s; 4096 is the knee. MNIST is 60k examples, so
    # warmup+bench stays within 14 batches at B=4096; the ~90 ms epoch is
    # repeated 6x inside each timed pass (epochs_per_pass) purely to widen
    # the timing window — same workload, hiccup-resistant spread. This
    # model is dispatch-rate-bound: its throughput measures the dispatch
    # path, not the MXU
    batch_size, warmup, bench, scan = 4096, 4, 10, 1
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.normalizers import ImagePreProcessingScaler

    # mixed precision is the TPU-native training mode (MXU feeds bf16);
    # params/optimizer state stay f32
    net = MultiLayerNetwork(lenet_configuration(), compute_dtype=jnp.bfloat16)
    net.init()
    # raw uint8 pixels staged in HBM (4x fewer transfer bytes than f32);
    # /255 scale fused into the compiled step by the device-side normalizer
    net.set_normalizer(ImagePreProcessingScaler())
    it = MnistDataSetIterator(batch_size, num_examples=batch_size * (warmup + bench),
                              raw_uint8=True)
    batches = list(it)
    full_dts = _throughput(net, batches, warmup, bench, scan_steps=scan,
                           epochs_per_pass=6, return_dts=True)
    dt, wall_spread = _median_spread(full_dts)
    wall_value = bench * batch_size / dt
    # the HEADLINE is the device-time throughput from half-epoch
    # differencing — this config is dispatch-bound, so its wall number
    # moves with host load. Differencing cancels the per-pass fixed
    # cost; the wall number stays as a satellite.
    dev_rate, dev_ms, spread = _device_differenced(
        net, batches, warmup, bench, batch_size, scan_steps=scan,
        epochs_per_pass=6, full_dts=full_dts)
    if dev_rate is None:  # noise swamped the differencing: wall bound
        dev_rate, dev_ms, spread = wall_value, 1e3 * dt / (
            bench * batch_size), wall_spread
    bench_lenet.device_ms = round(dev_ms, 6)
    bench_lenet.wall_samples_per_sec = round(wall_value, 1)
    mfu = _mfu(_step_flops(net, batches[0]) / batch_size, dev_rate,
               bf16=True)
    return ("lenet_mnist_train_samples_per_sec_device", dev_rate, mfu,
            spread)


def bench_resnet50():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.resnet import resnet_configuration
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    # batch sweep (steady state): 256->7.1k, 512->6.1k, 1024->6.3k,
    # 2048->5.9k samples/s — 256 wins (BN reductions + HBM locality).
    # r3: folded one-pass BN lifted 256 to ~7.7-8k (re-swept: 512 -> 7.5k,
    # still behind); remaining time is BN-backward channel reductions,
    # which are HBM-bandwidth-bound at CIFAR's 32x32xwide-channel shapes
    batch_size, warmup, bench, scan = 256, 4, 16, 1
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.normalizers import ImagePreProcessingScaler

    net = ComputationGraph(resnet_configuration(depth=50, n_classes=10),
                           compute_dtype=jnp.bfloat16)
    net.init()
    # raw uint8 pixels (CIFAR's native storage dtype) staged in HBM,
    # /255 fused on-device
    net.set_normalizer(ImagePreProcessingScaler())
    rng = np.random.default_rng(0)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch_size)]
    batches = [DataSet(rng.integers(0, 256, (batch_size, 32, 32, 3)).astype(np.uint8), y)
               for _ in range(warmup + bench)]
    full_dts = _throughput(net, batches, warmup, bench, scan_steps=scan,
                           return_dts=True)
    dt, spread = _median_spread(full_dts)
    value = bench * batch_size / dt
    # device-time satellite (ROADMAP item 4 remainder): half-epoch
    # differencing cancels the per-pass fixed cost — wall stays the
    # headline (compute-bound config; the satellite attributes any
    # future regression to chip vs host)
    _, dev_ms, _ = _device_differenced(net, batches, warmup, bench,
                                       batch_size, scan_steps=scan,
                                       full_dts=full_dts)
    bench_resnet50.device_ms = None if dev_ms is None \
        else round(dev_ms, 6)
    mfu = _mfu(_step_flops(net, batches[0]) / batch_size, value, bf16=True)
    return "resnet50_cifar10_train_samples_per_sec_per_chip", value, mfu, spread


def _lstm_train_bench(metric, *, vocab, hidden, T, batch_size,
                      warmup=3, bench=8, scan=1, device_time=False):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import (
        GravesLSTM,
        InputType,
        NeuralNetConfiguration,
        RnnOutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    conf = (NeuralNetConfiguration.Builder()
            .seed(1).learning_rate(0.1).updater(Updater.RMSPROP)
            .list()
            .layer(GravesLSTM(n_in=vocab, n_out=hidden, activation=Activation.TANH))
            .layer(GravesLSTM(n_out=hidden, activation=Activation.TANH))
            .layer(RnnOutputLayer(n_out=vocab, loss=LossFunction.MCXENT,
                                  activation=Activation.SOFTMAX))
            .set_input_type(InputType.recurrent(vocab))
            .build())
    import jax.numpy as jnp

    net = MultiLayerNetwork(conf, compute_dtype=jnp.bfloat16)
    net.init()
    from deeplearning4j_tpu.datasets.normalizers import OneHotEncoder

    # char ids stage as uint8 (B, T); the one-hot expansion the LSTM
    # input expects happens ON DEVICE (OneHotEncoder) and labels are
    # sparse ids — vocab x fewer staged bytes than one-hot
    net.set_normalizer(OneHotEncoder(vocab))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (warmup + bench, batch_size, T + 1))
    batches = [DataSet(ids[i, :, :-1].astype(np.uint8),
                       ids[i, :, 1:].astype(np.int32))
               for i in range(warmup + bench)]
    full_dts = _throughput(net, batches, warmup, bench, scan_steps=scan,
                           return_dts=True)
    dt, spread = _median_spread(full_dts)
    value = bench * batch_size / dt
    dev_ms = None
    if device_time:
        # half-epoch differencing (ROADMAP item 4 remainder): device
        # cost per sample with the per-pass fixed cost cancelled
        _, dev_ms, _ = _device_differenced(net, batches, warmup, bench,
                                           batch_size, scan_steps=scan,
                                           full_dts=full_dts)
        if dev_ms is not None:
            dev_ms = round(dev_ms, 6)
    # count step FLOPs on the lax.scan path, not the Pallas one: XLA's cost
    # analysis can't see inside custom-call kernels, and the MFU metric
    # should not change just because the implementation moved into one.
    # Also time the scan path at THIS batch size: fused_speedup_vs_scan
    # is the kernel-only ratio at matched batch/shape, measured in-bench.
    import os

    # Did the main timed net actually ride the fused kernel? Its trace
    # left a passing (dtype, batch block, H, masked) verdict if so.
    from deeplearning4j_tpu.ops.kernel_dispatch import engaged

    fused_ran = bool(engaged(
        "fused_lstm", lambda k: k[0] == "bfloat16" and k[2] == hidden
        and not k[3] and batch_size % k[1] == 0))
    prior = os.environ.get("DL4J_TPU_NO_PALLAS_LSTM")  # never clobber a
    os.environ["DL4J_TPU_NO_PALLAS_LSTM"] = "1"        # user-set override
    try:
        flops = _step_flops(net, batches[0])  # traces fresh under the env
        if fused_ran:
            scan_net = MultiLayerNetwork(conf, compute_dtype=jnp.bfloat16)
            scan_net.init()
            scan_net.set_normalizer(OneHotEncoder(vocab))
            scan_dt, _ = _throughput(scan_net, batches, warmup, bench,
                                     scan_steps=scan)
            fused_speedup = round(scan_dt / dt, 3)
        else:
            # the main net already ran the scan path (user override, CPU
            # platform, or every tile probe failed) — a scan-vs-scan
            # ratio labeled "fused_speedup" would be misleading
            fused_speedup = None
    finally:
        if prior is None:
            del os.environ["DL4J_TPU_NO_PALLAS_LSTM"]
        else:
            os.environ["DL4J_TPU_NO_PALLAS_LSTM"] = prior
    mfu = _mfu(flops / batch_size, value, bf16=True)
    return metric, value, mfu, spread, fused_speedup, dev_ms


def bench_lstm():
    # r3: the Pallas fused LSTM cell (ops/pallas_lstm.py) replaces the
    # lax.scan time loop; its batch-parallel grid scales where the scan
    # plateaued. Fused-path sweep: 512->68k, 2048->76k, 4096->98k,
    # 8192->113k samples/s (16384 exhausts HBM); r2 scan path peaked ~55k
    # at 512. bf16 throughout (MXU native feed).
    metric, value, mfu, spread, fused, dev_ms = _lstm_train_bench(
        "lstm_charrnn_train_samples_per_sec_per_chip",
        vocab=64, hidden=256, T=64, batch_size=8192, device_time=True)
    bench_lstm.fused_speedup_vs_scan = fused
    bench_lstm.device_ms = dev_ms
    return metric, value, mfu, spread


def bench_lstm_large():
    # r4: MXU-width recurrence. At H=256 the fused kernel is bound by the
    # per-element gate chain (VPU) — batch-block sweeps 512/1024/2048 time
    # identically — so whole-net MFU plateaus near 7.5%. At H=1024 the
    # per-step recurrent GEMM (bb,1024)@(1024,4096) dominates the gate
    # elementwise and kernel-level MFU rises ~8x (measured 178 ms/step for
    # fwd+bwd at B=4096/T=64 single layer ≈ 19% of bf16 peak). B=2048:
    # 4096 exhausts HBM (the two layers' (T,B,4H) gate/dz training slabs
    # alone are ~8.5 GB at B=4096; measured 16.5 G > the 15.75 G chip).
    # New metric name: a shape change resets baseline comparability
    # (r3 advisor).
    metric, value, mfu, spread, fused, _dms = _lstm_train_bench(
        "lstm_large_h1024_train_samples_per_sec_per_chip",
        vocab=256, hidden=1024, T=64, batch_size=2048)
    bench_lstm_large.fused_speedup_vs_scan = fused
    return metric, value, mfu, spread


def _gpt_train_bench(metric, *, vocab, d_model, n_heads, n_layers, T,
                     batch_size, warmup, bench, attention_block_size,
                     device_time=False, dropout=0.0):
    """Shared staging/measurement for the gpt-family training configs:
    build the bf16 net, stage sparse-int-label batches in HBM, time the
    steady-state epoch (median of _REPEATS), count MFU from XLA cost
    analysis. One implementation so a methodology fix cannot miss a
    config. With `device_time`, also difference a half-length epoch out
    of the full one — bench_generate's r5 trick, generalized per
    ROADMAP item 4: the per-epoch fixed cost (dispatch bookkeeping,
    host hiccups) cancels in (dt_full - dt_half), leaving
    a device-time-per-token median that separates host noise from real
    step regressions. Returns (metric, tokens/sec, mfu, spread, net,
    batches, device_ms_per_token-or-None)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(
        gpt_configuration(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers, max_length=T,
                          attention_block_size=attention_block_size,
                          dropout=dropout),
        compute_dtype=jnp.bfloat16)
    net.init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (warmup + bench, batch_size, T + 1))
    # sparse int labels: (B, T) ids are vocab x fewer staged bytes than
    # (B, T, V) one-hot
    batches = [DataSet(ids[i, :, :-1].astype(np.int32),
                       ids[i, :, 1:].astype(np.int32))
               for i in range(warmup + bench)]
    dt, spread = _throughput(net, batches, warmup, bench)
    value = bench * batch_size * T / dt
    mfu = _mfu(_step_flops(net, batches[0]) / (batch_size * T), value,
               bf16=True)
    dms = None
    if device_time and bench > 1:
        half = bench // 2
        # same compiled step, same staged data, half the steps per
        # timed epoch; medians of _REPEATS both sides
        dt_half, _ = _throughput(net, batches, warmup, half)
        if dt > dt_half:
            dms = round(1e3 * (dt - dt_half)
                        / ((bench - half) * batch_size * T), 6)
        else:  # host noise swamped the differencing: wall-time bound
            dms = round(1e3 * dt / (bench * batch_size * T), 6)
    return metric, value, mfu, spread, net, batches, dms


def bench_gpt():
    """Causal transformer LM, toy short-context config: bf16 mixed
    precision. T=256 rides FULL attention: measured 892k vs 840k tok/s
    for the blockwise path at this length (blockwise/flash win only at
    T >> 1k); batch sweep: 32->892k, 64->1.25M, 128->1.43M, 256+->1.33M."""
    out = _gpt_train_bench(
        "gpt_causal_lm_train_tokens_per_sec_per_chip",
        vocab=256, d_model=256, n_heads=8, n_layers=4, T=256,
        batch_size=128, warmup=4, bench=16, attention_block_size=1024,
        device_time=True)
    bench_gpt.device_ms_per_token = out[6]
    return out[:4]


def bench_gpt_med():
    """Mid-scale causal LM (d_model=512, 8 layers, T=512) — the bridge
    between the toy gpt config (d256/4L, shape-capped ~17% MFU) and
    gpt_long (d1024/T4096, ~42% MFU): realistic short-context training
    shapes where fusion wins are visible (r3 verdict ask #9). Batch sweep
    on chip: 32->335k, 64->360k, 128->351k tok/s. `device_ms_per_token`
    (half-length differencing) ships every round so a regression is
    attributable to host vs chip.

    r6: the config now trains with **dropout=0.1** —
    the configuration every real training run uses and no bench config
    exercised — which RENAMES the metric (workload change resets
    baseline comparability, the lstm_large/lenet precedent). The
    per-row partition-invariant RNG (`ops/rng_rows`) is A/B-priced
    in-bench: the same net re-traced under `row_offset_scope(0)` takes
    the per-row fold_in+vmap stream, the default single-device trace
    takes the r6 bulk-draw specialization, and
    `dropout_rng_overhead_pct` = how much the per-row stream costs over
    the bulk draw (the number that justifies the specialization)."""
    out = _gpt_train_bench(
        "gpt_med_d512_dropout_train_tokens_per_sec_per_chip",
        vocab=512, d_model=512, n_heads=8, n_layers=8, T=512,
        batch_size=64, warmup=3, bench=10, attention_block_size=1024,
        device_time=True, dropout=0.1)
    bench_gpt_med.device_ms_per_token = out[6]

    # per-row RNG A/B: identical config, trace under row_offset_scope(0)
    # → every dropout site draws B per-row keys instead of one bulk
    # mask. Positive pct = the per-row stream is that much slower.
    from deeplearning4j_tpu.ops.rng_rows import row_offset_scope

    with row_offset_scope(0):
        per_row = _gpt_train_bench(
            "gpt_med_d512_dropout_perrow_probe",
            vocab=512, d_model=512, n_heads=8, n_layers=8, T=512,
            batch_size=64, warmup=3, bench=6,
            attention_block_size=1024, dropout=0.1)
    bench_gpt_med.dropout_rng_overhead_pct = round(
        (out[1] / per_row[1] - 1.0) * 100.0, 2)

    # attention_block_size A/B (ROADMAP item 4 carried ask): same config
    # re-traced with block 512 — at T=512 this turns full attention into
    # the blockwise path. Positive pct = block 512 is that much slower
    # at this shape (expected on-chip: full attention wins at short T,
    # the gpt/gpt_long sweeps' crossover story, now measured here).
    blk512 = _gpt_train_bench(
        "gpt_med_d512_block512_probe",
        vocab=512, d_model=512, n_heads=8, n_layers=8, T=512,
        batch_size=64, warmup=3, bench=6,
        attention_block_size=512, dropout=0.1)
    bench_gpt_med.attention_block512_overhead_pct = round(
        (out[1] / blk512[1] - 1.0) * 100.0, 2)
    return out[:4]


def bench_gpt_long():
    """Long-context causal LM (T=4096) riding the Pallas flash fwd+bwd
    kernels (`ops/pallas_attention.py`) — the flagship long-context config.
    d_model=1024, 8 layers, head_dim=128, attention through the
    flash/blockwise dispatch with block 512. Sweeps (on-chip, steady
    state): d512/B32 257k tok/s (~23% MFU); d1024: B8 103k, B16 OOM
    without remat, 82-84k with per-block remat (recompute not paid back at
    this scale) -> d1024/B8 no-remat wins on MFU. Also measures the
    flash-vs-XLA-blockwise kernel ratio in-bench (`flash_speedup`) at this
    exact shape instead of claiming it in a docstring."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    vocab, d_model, heads = 256, 1024, 8
    T, batch_size = 4096, 8
    metric, value, _, spread, net, batches, _dms = _gpt_train_bench(
        "gpt_long_t4096_train_tokens_per_sec_per_chip",
        vocab=vocab, d_model=d_model, n_heads=heads, n_layers=8, T=T,
        batch_size=batch_size, warmup=2, bench=6, attention_block_size=512)

    # MFU accounting: XLA's cost analysis counts everything EXCEPT inside
    # the flash custom calls; add the kernel's matmul FLOPs analytically.
    # Per causally-needed (blk, blk) tile: fwd = 2 matmuls, bwd = 7 across
    # the dQ/dKV kernels (incl. 2 score recomputes) -> 18*bq*bk*D MACs.
    # Gate on the dispatch's ACTUAL probe verdict: if flash declined (all
    # tiles failed to compile here), attention ran on the XLA blockwise
    # path whose FLOPs cost analysis already counts — adding the analytic
    # term then would double-count the dominant component.
    from deeplearning4j_tpu.ops.kernel_dispatch import engaged

    xla_flops = _step_flops(net, batches[0])
    # the dispatch takes the largest passing tile that divides T
    blk = max((k[1] for k in engaged(
        "flash_attention", lambda k: k[0] == "bfloat16"
        and k[2] == d_model // heads and T % k[1] == 0)), default=None)
    if blk is not None:
        nb = T // blk
        needed_tiles = nb * (nb + 1) // 2
        flash_flops = (batch_size * heads * needed_tiles
                       * 18 * blk * blk * (d_model // heads))
    else:
        flash_flops = 0.0
    mfu = _mfu((xla_flops + flash_flops) / (batch_size * T), value,
               bf16=True)

    # kernel-level flash vs XLA-blockwise A/B at the bench shape AND the
    # dispatched tile size (full-net A/B is impossible: the blockwise
    # scan's saved residuals alone exceed HBM at T=4096, which is the
    # flash kernel's point). Skipped when the dispatch declined flash —
    # hardcoding a tile the probe rejected would crash the whole bench.
    if blk is None:
        bench_gpt_long.flash_speedup = None
        return metric, value, mfu, spread
    from deeplearning4j_tpu.ops.attention import blockwise_attention
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (batch_size, T, heads, d_model // heads)), jnp.bfloat16)

    def mk_loss(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))
        g = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit
        def g_scalar(q, k, v):
            # reduce grads to ONE scalar on device: materializing a full
            # gradient would time the device-to-host copy, not the kernel
            gq, gk, gv = g(q, k, v)
            return (jnp.sum(gq.astype(jnp.float32))
                    + jnp.sum(gk.astype(jnp.float32))
                    + jnp.sum(gv.astype(jnp.float32)))
        return g_scalar

    flash = mk_loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=blk, block_k=blk))
    xla = mk_loss(lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, block_size=512))
    times = {}
    for name, f in (("flash", flash), ("xla", xla)):
        float(f(x, x, x))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(6):
            s = f(x, x, x)
        float(s)  # true host sync (scalar)
        times[name] = (time.perf_counter() - t0) / 6
    bench_gpt_long.flash_speedup = round(times["xla"] / times["flash"], 3)
    return metric, value, mfu, spread


def bench_checkpoint():
    """Durability tax of the checkpoint subsystem (`util/checkpoint_store`):
    one full durable cycle = atomic save (temp + fsync + os.replace +
    integrity manifest), manifest verify (full re-hash), restore
    (newest-verified fallback load) for a ~1.1 M-param MLP (≈4.3 MB zip
    payload, Adam state included). Metric: verified round-trips/sec so
    higher stays better like every other config; per-phase medians are
    reported alongside as `latency_ms` so BENCH_*.json tracks where the
    tax goes (hashing vs fsync vs params host-transfer) across rounds."""
    import tempfile

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.util.checkpoint_store import CheckpointStore
    from deeplearning4j_tpu.util.serialization import (
        restore_model,
        write_model,
    )

    conf = (NeuralNetConfiguration.Builder()
            .seed(0).learning_rate(0.01).updater(Updater.ADAM)
            .list()
            .layer(DenseLayer(n_out=1024, activation=Activation.RELU))
            .layer(DenseLayer(n_out=512, activation=Activation.RELU))
            .layer(OutputLayer(n_out=10, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(512))
            .build())
    net = MultiLayerNetwork(conf)
    net.init()
    rng = np.random.default_rng(0)
    ds = DataSet(rng.standard_normal((32, 512)).astype(np.float32),
                 np.eye(10, dtype=np.float32)[rng.integers(0, 10, 32)])
    net.fit(ds)  # populate Adam moments so the round-trip covers them
    phases = {"save": [], "verify": [], "restore": []}
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d, keep_last=2)
        for i in range(_REPEATS + 1):  # +1 warmup (first save pays jit/IO
            t0 = time.perf_counter()   # cache warm-up)
            store.save(i, lambda tmp: write_model(net, tmp, atomic=False))
            t1 = time.perf_counter()
            store.verify(i)
            t2 = time.perf_counter()
            store.load_latest_verified(restore_model)
            t3 = time.perf_counter()
            if i:
                phases["save"].append(t1 - t0)
                phases["verify"].append(t2 - t1)
                phases["restore"].append(t3 - t2)
    medians = {k: float(np.median(v)) for k, v in phases.items()}
    total = sum(medians.values())
    spread = max(max(v) / min(v) for v in phases.values())
    bench_checkpoint.latency_ms = {k: round(1e3 * v, 2)
                                   for k, v in medians.items()}
    return ("checkpoint_durable_save_verify_restore_roundtrips_per_sec",
            1.0 / total, None, spread)


def bench_sentinel():
    """Per-step overhead of the training health sentinel
    (`optimize/health.HealthSentinel`): the fused guard adds one global
    grad-norm reduction + a where-select commit to the compiled step, and
    the host check forces ONE small (3,)-vector device→host sync per step
    — which un-pipelines the async dispatch queue, so the sync, not the
    reduction, is the real tax. Metric: guarded steps/sec (higher
    better); `sentinel_overhead_pct` records the unguarded-vs-guarded
    gap so BENCH_*.json tracks the guard's price across rounds."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        DeviceCacheDataSetIterator,
    )
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.optimize.health import HealthSentinel

    def make_net():
        conf = (NeuralNetConfiguration.Builder()
                .seed(0).learning_rate(0.01).updater(Updater.ADAM)
                .list()
                .layer(DenseLayer(n_out=1024, activation=Activation.RELU))
                .layer(DenseLayer(n_out=512, activation=Activation.RELU))
                .layer(OutputLayer(n_out=10, loss=LossFunction.MCXENT,
                                   activation=Activation.SOFTMAX))
                .set_input_type(InputType.feed_forward(512))
                .build())
        net = MultiLayerNetwork(conf)
        net.init()
        return net

    rng = np.random.default_rng(0)
    n_steps, B = 32, 128
    batches = [DataSet(rng.standard_normal((B, 512)).astype(np.float32),
                       np.eye(10, dtype=np.float32)[
                           rng.integers(0, 10, B)])
               for _ in range(n_steps)]
    it = DeviceCacheDataSetIterator(batches)

    def time_epochs(net):
        net.fit(it)   # compile
        net.fit(it)   # settle: timed passes start from steady state
        _sync(net)
        dts = []
        for _ in range(_REPEATS):
            t0 = time.perf_counter()
            net.fit(it)
            _sync(net)
            dts.append(time.perf_counter() - t0)
        return dts

    base_dt, _ = _median_spread(time_epochs(make_net()))

    guarded = make_net()
    # escalation disarmed (huge budgets): this measures the guard's cost
    # on a HEALTHY run, the steady-state every real run pays
    sentinel = HealthSentinel(skip_budget=10**9, warmup_steps=10**9)
    guarded.set_health_sentinel(sentinel)
    guard_dts = time_epochs(guarded)
    guard_dt, spread = _median_spread(guard_dts)
    assert sentinel.steps >= (2 + _REPEATS) * n_steps
    assert sentinel.skips == 0, "healthy bench run must skip nothing"
    bench_sentinel.sentinel_overhead_pct = round(
        (guard_dt / base_dt - 1.0) * 100.0, 1)
    return ("sentinel_guarded_train_steps_per_sec", n_steps / guard_dt,
            None, spread)


def bench_serving():
    """Serving-tier tax (`serving/model_server.ModelServer`): steady-state
    predict latency and throughput THROUGH the robust path — admission
    control, deadline stamping, micro-batch assembly, breaker accounting,
    non-finite output screen — for the same ~1.1 M-param MLP the
    checkpoint config uses, driven by 4 closed-loop client threads of
    8-row requests. Metric: rows/sec served (higher better); `latency_ms`
    records per-request p50/p99 so tail regressions show up even when
    throughput holds, and `shed_rate_pct` records the typed-shed fraction
    under a synthetic overload phase (tiny queue + slow-step injector) —
    the admission-control contract, priced every round."""
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.serving import (
        ModelServer,
        ServerOverloadedError,
        SlowInferenceInjector,
    )
    import threading

    conf = (NeuralNetConfiguration.Builder()
            .seed(0).learning_rate(0.01).updater(Updater.ADAM)
            .list()
            .layer(DenseLayer(n_out=1024, activation=Activation.RELU))
            .layer(DenseLayer(n_out=512, activation=Activation.RELU))
            .layer(OutputLayer(n_out=10, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(512))
            .build())
    net = MultiLayerNetwork(conf)
    net.init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 512)).astype(np.float32)

    n_threads, reqs_per_thread = 4, 24
    latencies = []
    lock = threading.Lock()
    srv = ModelServer(net, max_queue=256, max_batch_size=64,
                      batch_window=0.001)
    try:
        for _ in range(6):  # warm the jit cache across pad buckets
            srv.predict(x)

        def client():
            mine = []
            for _ in range(reqs_per_thread):
                t0 = time.perf_counter()
                srv.predict(x, timeout=60.0)
                mine.append(time.perf_counter() - t0)
            with lock:
                latencies.extend(mine)

        dts = []
        for _ in range(_REPEATS):
            latencies.clear()
            threads = [threading.Thread(target=client)
                       for _ in range(n_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dts.append(time.perf_counter() - t0)
        dt, spread = _median_spread(dts)
        lat = np.asarray(latencies)
        bench_serving.latency_ms = {
            "p50": round(1e3 * float(np.percentile(lat, 50)), 2),
            "p99": round(1e3 * float(np.percentile(lat, 99)), 2)}
        rows_per_sec = n_threads * reqs_per_thread * x.shape[0] / dt
        assert srv.stats()["failures"] == 0, \
            "healthy bench run must not fail inference"
    finally:
        srv.shutdown(drain_timeout=10.0)

    # overload phase: tiny queue + slow steps; record the shed fraction
    slow = SlowInferenceInjector(delay=0.05)
    overloaded = ModelServer(net, max_queue=4, max_batch_size=8,
                             batch_window=0.0, infer_hooks=[slow])
    offered = 48
    shed = [0]

    def flood():
        try:
            overloaded.predict(x, timeout=30.0)
        except ServerOverloadedError:
            with lock:
                shed[0] += 1

    try:
        overloaded.predict(x)  # compile before the clock matters
        threads = [threading.Thread(target=flood) for _ in range(offered)]
        for t in threads:
            t.start()
        slow.release()
        for t in threads:
            t.join()
    finally:
        overloaded.shutdown(drain_timeout=10.0)
    bench_serving.shed_rate_pct = round(100.0 * shed[0] / offered, 1)
    return "serving_predict_rows_per_sec", rows_per_sec, None, spread


def bench_serve_pool():
    """Replicated-pool serving tax (`serving/replica_pool.ReplicaPool`):
    steady-state p50/p99 predict latency and rows/sec for a 3-REPLICA
    pool (least-loaded routing, health probes, shared admission budget)
    vs a single `ModelServer` under the SAME offered closed-loop load —
    the price/benefit of the dispatch tier, measured every round. Plus
    the chaos line the tier exists for: one replica KILLED mid-bench
    (`ReplicaCrashInjector`), reporting `availability_pct` (fraction of
    offered requests answered) and the failover count — the number that
    should read 100.0 / >0 when failover works and <100 when it
    doesn't.

    Cross-process WIRE DRILL (`serving/remote_replica`, the `wire_drill`
    entry): three supervised replica SUBPROCESSES behind the gateway
    wire protocol, one SIGKILLed mid-traffic — `availability_pct` and
    `respawns` are counts (failover + supervisor respawn keep the first
    at 100). It reports no rate: a chip belongs to one process, this
    one, so the supervisor pins its children to the CPU
    (`children_platform`), and a CPU child's speed says nothing about
    the chip."""
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.serving import (
        ModelServer,
        ReplicaCrashInjector,
        ReplicaPool,
    )
    import threading

    conf = (NeuralNetConfiguration.Builder()
            .seed(0).learning_rate(0.01).updater(Updater.ADAM)
            .list()
            .layer(DenseLayer(n_out=1024, activation=Activation.RELU))
            .layer(DenseLayer(n_out=512, activation=Activation.RELU))
            .layer(OutputLayer(n_out=10, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(512))
            .build())
    net = MultiLayerNetwork(conf)
    net.init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 512)).astype(np.float32)
    n_threads, reqs_per_thread = 6, 16
    lock = threading.Lock()

    def drive(predict, latencies=None):
        """One closed-loop pass: n_threads clients, each sending
        reqs_per_thread back-to-back requests. Returns wall time."""
        def client():
            mine = []
            for _ in range(reqs_per_thread):
                t0 = time.perf_counter()
                predict(x, timeout=60.0)
                mine.append(time.perf_counter() - t0)
            if latencies is not None:
                with lock:
                    latencies.extend(mine)

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    total_rows = n_threads * reqs_per_thread * x.shape[0]
    server_kw = dict(max_queue=256, max_batch_size=64, batch_window=0.001)

    # single-server reference under the identical load
    single = ModelServer(net, **server_kw)
    try:
        for _ in range(6):
            single.predict(x)
        single_dts = [drive(single.predict) for _ in range(_REPEATS)]
    finally:
        single.shutdown(drain_timeout=10.0)
    single_dt, _ = _median_spread(single_dts)

    # the 3-replica pool, same offered load
    pool = ReplicaPool.from_net(net, 3, server_kwargs=server_kw,
                                probe_batch=x, probe_interval=1.0,
                                watchdog_timeout=10.0)
    latencies = []
    try:
        for rep in pool._replicas:  # compile every replica's buckets
            for _ in range(6):
                rep.server.predict(x)
        # latencies accumulate across ALL repeats (the rows/sec headline
        # is the median over the same passes — one sampling story, and
        # p99 over _REPEATS x 96 samples instead of one pass's 96)
        dts = [drive(pool.predict, latencies) for _ in range(_REPEATS)]
        dt, spread = _median_spread(dts)
        lat = np.asarray(latencies)
        bench_serve_pool.latency_ms = {
            "p50": round(1e3 * float(np.percentile(lat, 50)), 2),
            "p99": round(1e3 * float(np.percentile(lat, 99)), 2)}
        assert pool.stats()["failovers"] == 0, \
            "healthy pool bench must not fail over"
    finally:
        pool.shutdown(drain_timeout=10.0)
    rows_per_sec = total_rows / dt
    bench_serve_pool.single_rows_per_sec = round(total_rows / single_dt, 1)
    bench_serve_pool.pool_vs_single = round(single_dt / dt, 3)

    # chaos line: one replica killed mid-bench; failover must keep
    # availability at 100
    crash = ReplicaCrashInjector()
    chaos_kw = dict(server_kw, breaker_threshold=3,
                    breaker_reset_timeout=0.5)
    servers = [ModelServer(net.clone() if i else net,
                           **(dict(chaos_kw, infer_hooks=[crash])
                              if i == 1 else chaos_kw))
               for i in range(3)]
    chaos_pool = ReplicaPool(servers, probe_batch=x, probe_interval=0.25,
                             watchdog_timeout=5.0, evict_threshold=2)
    ok = [0]
    offered = n_threads * reqs_per_thread

    def chaos_client():
        for i in range(reqs_per_thread):
            try:
                chaos_pool.predict(x, timeout=60.0)
                with lock:
                    ok[0] += 1
            except Exception:  # noqa: BLE001 — availability accounting
                pass
            if i == 2:
                crash.crash()  # dies while requests are in flight

    try:
        for rep in chaos_pool._replicas:
            rep.server.predict(x)
        threads = [threading.Thread(target=chaos_client)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        bench_serve_pool.availability_pct = round(100.0 * ok[0] / offered,
                                                  2)
        bench_serve_pool.failovers = chaos_pool.stats()["failovers"]
    finally:
        chaos_pool.shutdown(drain_timeout=10.0)

    from deeplearning4j_tpu.serving import spawn_replica_pool
    import tempfile

    # wire drill: the same 3-replica topology as supervised PROCESSES
    # (CPU children — see the docstring), one killed -9 mid-traffic —
    # failover absorbs the in-flight loss and the supervisor respawns
    # the process; availability should read 100.0 with respawns > 0
    remote_chaos = spawn_replica_pool(
        net, 3,
        scratch_dir=tempfile.mkdtemp(prefix="bench-remote-chaos-"),
        server_kwargs=server_kw,
        pool_kwargs=dict(probe_batch=x, probe_interval=0.25,
                         watchdog_timeout=5.0, evict_threshold=2,
                         readmit_successes=2, max_failovers=3),
        supervisor_kwargs=dict(restart_backoff=0.25, poll_interval=0.1))
    ok_remote = [0]
    killed = threading.Event()

    def remote_chaos_client():
        for i in range(reqs_per_thread):
            try:
                remote_chaos.predict(x, timeout=60.0)
                with lock:
                    ok_remote[0] += 1
            except Exception:  # noqa: BLE001 — availability accounting
                pass
            if i == 2 and not killed.is_set():
                killed.set()
                remote_chaos.supervisor.kill(1)  # SIGKILL mid-flight

    try:
        for _ in range(3):
            remote_chaos.predict(x, timeout=60.0)
        threads = [threading.Thread(target=remote_chaos_client)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the respawn lands after the supervisor's restart backoff —
        # give it a moment so the line reports the recovery, not a race
        respawn_deadline = time.perf_counter() + 15.0
        while (remote_chaos.supervisor.respawns < 1
               and time.perf_counter() < respawn_deadline):
            time.sleep(0.1)
        bench_serve_pool.wire_drill = {
            "children_platform": remote_chaos.supervisor.child_platform,
            "availability_pct": round(100.0 * ok_remote[0] / offered, 2),
            "respawns": remote_chaos.supervisor.respawns}
    finally:
        remote_chaos.shutdown(drain_timeout=10.0)
    return ("serve_pool_predict_rows_per_sec", rows_per_sec, None, spread)


def _zipf_corpus(vocab_size, n_sentences, sent_len, seed=0):
    """Synthetic Zipf corpus as pre-tokenized sentences."""
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab_size + 1)
    probs /= probs.sum()
    words = np.array([f"w{i}" for i in range(vocab_size)])
    draws = rng.choice(vocab_size, (n_sentences, sent_len), p=probs)
    return [list(words[row]) for row in draws]


def _time_w2v(w2v, sentences):
    """Median/spread of _REPEATS full training passes; each pass ends with a
    host sync (one scalar reduced from the table, which depends on every
    scatter of the pass)."""
    w2v.fit(sentences[:300])  # warm-up: compile the scanned NS kernel
    # one untimed full pass, so one-time first-use costs do not land in
    # the first timed pass and inflate the spread
    w2v.fit(sentences)
    float(np.asarray(w2v.lookup_table.syn0).sum())
    dts = []
    for _ in range(_REPEATS):
        t0 = time.perf_counter()
        w2v.fit(sentences)
        float(np.asarray(w2v.lookup_table.syn0).sum())
        dts.append(time.perf_counter() - t0)
    return _median_spread(dts)


def _w2v_device_ms_per_word(w2v, sentences, dt_full):
    """Half-corpus differencing (ROADMAP item 4, the
    `device_ms_per_token` discipline generalized to the word2vec
    configs): time a half-length corpus pass at the same compiled shapes
    and take the incremental cost of the extra words. The per-pass fixed
    cost — vocab-side host bookkeeping, dispatch setup —
    cancels in (dt_full − dt_half), so the number attributes to the
    chip-side scatter path, not to host noise. Falls back to the
    wall bound when noise swamps the differencing. Both passes share
    `_time_w2v`'s timing discipline so the two sides of the difference
    cannot drift."""
    half = sentences[:len(sentences) // 2]
    dt_half, _ = _time_w2v(w2v, half)
    words_full = sum(len(s) for s in sentences)
    words_half = sum(len(s) for s in half)
    if dt_full > dt_half and words_full > words_half:
        return round(1e3 * (dt_full - dt_half)
                     / (words_full - words_half), 6)
    return round(1e3 * dt_full / words_full, 6)  # noise swamped: wall


def bench_word2vec():
    """Skip-gram with negative sampling (BASELINE config 4: the reference's
    `SkipGram.iterateSample` / `AggregateSkipGram` native-op path, here a
    batched XLA scatter step). Metric: corpus words/sec trained."""
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    # synthetic corpus with Zipf-ish structure — vocab ~2k, 200k words
    n_sentences, sent_len = 10_000, 20
    sentences = _zipf_corpus(2000, n_sentences, sent_len)
    w2v = Word2Vec(layer_size=128, window=5, negative=5,
                   min_word_frequency=1, epochs=1, seed=1)
    w2v.build_vocab(sentences)
    dt, spread = _time_w2v(w2v, sentences)
    bench_word2vec.device_ms_per_word = _w2v_device_ms_per_word(
        w2v, sentences, dt)
    total_words = n_sentences * sent_len
    # scatter/bandwidth-bound by design: MFU is not a meaningful figure
    return ("word2vec_skipgram_train_words_per_sec_per_chip",
            total_words / dt, None, spread)


def bench_word2vec_50k():
    """Skip-gram NS at a realistic vocabulary (50k types, 2M corpus words —
    the r3 verdict's scale ask: at vocab 2k/200k words, vocab build and
    host looping dominated and the number measured host contention). Same
    training path as `word2vec`, new metric name so baselines stay
    comparable."""
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    n_sentences, sent_len = 50_000, 40
    sentences = _zipf_corpus(50_000, n_sentences, sent_len)
    # batch sweep on chip (vectorized host path): 1024->102k, 4096->134k,
    # 8192->132k, 16384->144k, 32768->141k, 65536->118k words/s — 16384 is
    # the knee (enough scatter rows per dispatch; beyond that the staged
    # (scan_k, B) transfer grows faster than the dispatch savings)
    w2v = Word2Vec(layer_size=128, window=5, negative=5,
                   min_word_frequency=1, epochs=1, seed=1,
                   batch_size=16384, scan_flushes=32)
    w2v.build_vocab(sentences)
    dt, spread = _time_w2v(w2v, sentences)
    bench_word2vec_50k.device_ms_per_word = _w2v_device_ms_per_word(
        w2v, sentences, dt)
    total_words = n_sentences * sent_len
    return ("word2vec_skipgram_50kvocab_train_words_per_sec_per_chip",
            total_words / dt, None, spread)


def bench_generate():
    """Jitted KV-cache sampler throughput (tokens/sec generated) — the
    inference-side companion of the gpt training config. r4: decode runs
    in bf16 mixed precision and the KV caches use the TPU decode layouts
    (K (B,H,hd,L), V (B,H,L,hd)) so each step's score/weighted-sum
    einsums stream the cache without a strided transpose. Correctness is
    asserted in-bench so perf work cannot silently break sampling: the
    KV-cache decode must reproduce the naive full-context argmax loop
    exactly at f32, and the timed bf16 path must be deterministic.

    By construction the per-block cache-attention fusions stream the
    full padded cache every step (scan shapes are static, so ~6x the
    causally-needed bytes): B=32/d256 decode is dispatch+bandwidth
    bound, not MXU bound, and throughput scales with batch. Shrinking
    the cached KV heads (`gpt_configuration(n_kv_heads=...)`) cuts
    exactly those bytes; the bench config stays full-MHA so the metric
    keeps its meaning, and GQA is the knob a serving deployment would
    turn. Not measured on the current machine."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import (
        generate,
        gpt_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    vocab, d_model, B, T0, n_new = 256, 256, 32, 32, 256
    conf = gpt_configuration(vocab_size=vocab, d_model=d_model, n_heads=8,
                             n_layers=4, max_length=T0 + n_new)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, (B, T0)).astype(np.int32)

    # cache-mechanics spot check at the bench shape (f32 = exact argmax
    # parity; dtype only changes numerics, not the cache indexing/position
    # logic being validated)
    f32net = MultiLayerNetwork(conf)
    f32net.init()
    small, n_chk = prompt[:4], 8
    fast = generate(f32net, small, n_chk, temperature=0.0)
    ids = small.copy()
    for _ in range(n_chk):
        nxt = np.argmax(np.asarray(f32net.output(ids))[:, -1], axis=-1)
        ids = np.concatenate([ids, nxt[:, None].astype(np.int32)], axis=1)
    assert np.array_equal(fast, ids[:, small.shape[1]:]), \
        "KV-cache decode diverged from the full-context argmax loop"

    net = MultiLayerNetwork(conf, compute_dtype=jnp.bfloat16)
    net.init()
    generate(net, prompt, n_new, temperature=0.0)  # compile
    # two untimed settling passes: this config has had the widest spread
    # in the suite, from transient stalls landing in the first timed pass
    generate(net, prompt, n_new, temperature=0.0)
    generate(net, prompt, n_new, temperature=0.0)
    dts = []
    for _ in range(_REPEATS):
        t0 = time.perf_counter()
        out = generate(net, prompt, n_new, temperature=0.0)
        out = np.asarray(out)  # host sync
        dts.append(time.perf_counter() - t0)
    dt, spread = _median_spread(dts)
    assert out.shape == (B, n_new)
    out2 = np.asarray(generate(net, prompt, n_new, temperature=0.0))
    assert np.array_equal(out, out2), "bf16 greedy decode nondeterministic"
    # device_ms_per_token: per-token decode cost with the per-call fixed
    # cost (dispatch bookkeeping) differenced out — time a half-length
    # generation at the same shape and take the incremental cost of the
    # extra tokens. The wall tokens/sec metric keeps its meaning; this
    # satellite number keeps host jitter out of the decode story.
    n_half = n_new // 2
    generate(net, prompt, n_half, temperature=0.0)  # compile
    generate(net, prompt, n_half, temperature=0.0)  # settle
    dts_half = []
    for _ in range(_REPEATS):
        t0 = time.perf_counter()
        np.asarray(generate(net, prompt, n_half, temperature=0.0))
        dts_half.append(time.perf_counter() - t0)
    dt_half, _ = _median_spread(dts_half)
    if dt > dt_half:
        bench_generate.device_ms_per_token = round(
            1e3 * (dt - dt_half) / (B * (n_new - n_half)), 4)
    else:  # host noise swamped the differencing: report the wall bound
        bench_generate.device_ms_per_token = round(
            1e3 * dt / (B * n_new), 4)
    return "gpt_generate_tokens_per_sec_per_chip", B * n_new / dt, None, spread


# serve_generate workload shape — module-level so the slow CPU smoke
# test (tests/test_serving_generate.py) can shrink it without forking
# the measurement logic. r6: the workload is MIXED-LENGTH prompts
# (mostly short, a long tail of `long_frac` prompts at the top length)
# — the traffic shape paging + chunked prefill exist for: short
# requests must not pay long requests' worst-case KV, and a long
# prompt's prefill must not stall their decodes.
_SERVE_GEN_SHAPE = {
    "vocab": 256, "d_model": 256, "n_heads": 8, "n_layers": 4,
    "prompt_lengths": (128, 4096), "long_frac": 0.25,
    "n_requests": 32, "out_lengths": (32, 48, 64, 96, 128),
    "r5_n_slots": 8, "slots_multiplier": 4,
    "page_size": 128, "prefill_chunk": 256,
    "mean_interarrival": 0.01, "gqa_kv_heads": 2,
    "repeats": _REPEATS,
    # shared-prefix latency-tier workload (ISSUE 8): every request =
    # one shared "system prompt" + a unique tail — the traffic shape
    # prefix caching + speculative decoding exist for
    "shared_prefix_len": 1024, "shared_tail_len": 64,
    "sp_n_requests": 24, "sp_out_lengths": (32, 64),
    "sp_mean_interarrival": 0.01, "spec_k": 4,
}


def _serve_gen_workload(shp, rng):
    """Mixed-length prompts (list of 1-D id arrays), output lengths and
    Poisson arrival offsets for one serve_generate pass."""
    short, long_ = min(shp["prompt_lengths"]), max(shp["prompt_lengths"])
    t0s = np.where(rng.random(shp["n_requests"]) < shp["long_frac"],
                   long_, short)
    prompts = [rng.integers(0, shp["vocab"], int(t)).astype(np.int32)
               for t in t0s]
    outs = rng.choice(np.asarray(shp["out_lengths"]), shp["n_requests"])
    arrivals = np.cumsum(rng.exponential(shp["mean_interarrival"],
                                         shp["n_requests"]))
    return prompts, outs.astype(int), arrivals


def _shared_prefix_workload(shp, rng):
    """Chat-shaped traffic: every prompt is one shared system prefix
    plus a unique user tail, Poisson arrivals — the workload the prefix
    cache turns from O(n_requests × prefix) prefill into one."""
    prefix = rng.integers(0, shp["vocab"],
                          shp["shared_prefix_len"]).astype(np.int32)
    n = shp["sp_n_requests"]
    prompts = [np.concatenate(
        [prefix,
         rng.integers(0, shp["vocab"],
                      shp["shared_tail_len"]).astype(np.int32)])
        for _ in range(n)]
    outs = rng.choice(np.asarray(shp["sp_out_lengths"]), n).astype(int)
    arrivals = np.cumsum(rng.exponential(shp["sp_mean_interarrival"], n))
    return prompts, outs, arrivals


def _serve_gen_engine_pass(engine, prompts, outs, arrivals):
    """One timed pass: submit requests at their Poisson arrival offsets
    (a feeder thread — submit is non-blocking), wait for all, return
    (goodput tokens/sec, per-request latencies). Latency is
    completion − INTENDED arrival (time.monotonic, the clock the
    request stamps completed_at with): feeder scheduling drift counts
    AGAINST the engine, matching how the serial baseline is charged
    from the same ideal arrival times."""
    import threading

    n = len(outs)
    reqs = [None] * n
    t_start = time.monotonic()

    def feeder():
        for i in range(n):
            lag = t_start + arrivals[i] - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            reqs[i] = engine.submit(prompts[i], int(outs[i]), timeout=300.0)

    th = threading.Thread(target=feeder)
    th.start()
    th.join()
    toks = 0
    for r in reqs:
        toks += len(r.result(timeout=300.0))
    dt = time.monotonic() - t_start
    lats = [r.completed_at - (t_start + arrivals[i])
            for i, r in enumerate(reqs)]
    return toks / dt, lats


def bench_serve_generate():
    """Continuous-batching generation goodput
    (`serving.decode_engine.DecodeEngine`) under Poisson arrivals with
    MIXED-LENGTH prompts (mostly 128-token, a `long_frac` tail at 4096)
    and mixed output lengths — the paged-KV + chunked-prefill
    acceptance workload.

    Two configurations of the SAME engine run the same traffic on the
    SAME KV memory budget:

    - **r5**: `r5_n_slots` slots, buckets covering the longest prompt
      (one-shot prefill, no chunking) and the pool sized to give every
      slot a full max-length allocation — the dense r5 slotted-cache
      configuration, reproduced exactly under the paged engine.
    - **paged**: `slots_multiplier ×` the slot count on the IDENTICAL
      pool (pages bound by ACTUAL request lengths, so short requests
      stop paying the 4096-token worst case), short buckets + chunked
      prefill (`prefill_chunk`) so a 4096-token prompt prefills
      interleaved with decode instead of head-of-line-blocking it.

    Headline metric: paged-config goodput tokens/sec (median of
    `repeats` passes) — renamed from r5's `serve_generate_goodput_*`
    because the workload changed shape (mixed prompts), which resets
    baseline comparability (the lstm_large precedent). Satellites:
    paged p50/p99 arrival→completion latency, `slot_occupancy_pct`,
    `pages_in_use_peak` + `prefill_chunks` (the new paging/chunking
    accounting), the r5 configuration's goodput + latency on the same
    traffic, their ratio `paged_vs_r5_goodput`, and a GQA variant line
    (`gpt_configuration(n_kv_heads=...)`) kept OFF the headline. What
    serving observability costs is measured at real widths on the chip
    (`PERF.md`, PR 25), no longer here."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    shp = _SERVE_GEN_SHAPE
    rng = np.random.default_rng(0)
    prompts, outs, arrivals = _serve_gen_workload(shp, rng)
    short_t0 = min(shp["prompt_lengths"])
    long_t0 = max(shp["prompt_lengths"])
    max_len = long_t0 + int(max(shp["out_lengths"]))

    def build_net(n_kv_heads=0):
        net = MultiLayerNetwork(
            gpt_configuration(vocab_size=shp["vocab"],
                              d_model=shp["d_model"],
                              n_heads=shp["n_heads"],
                              n_layers=shp["n_layers"],
                              max_length=max_len,
                              n_kv_heads=n_kv_heads),
            compute_dtype=jnp.bfloat16)
        net.init()
        return net

    def engine_goodput(net, n_slots, outs_override=None, workload=None,
                       **engine_kw):
        run_prompts, run_arrivals = prompts, arrivals
        run_outs = outs if outs_override is None else outs_override
        if workload is not None:
            run_prompts, run_outs, run_arrivals = workload
        engine = DecodeEngine(
            net, n_slots=n_slots, max_len=max_len,
            page_size=shp["page_size"],
            prefill_chunk=shp["prefill_chunk"],
            max_queue=max(64, 2 * shp["n_requests"]),
            max_queued_pages=10 ** 9,  # latency priced, not queue sheds
            **engine_kw)
        prompts_, outs_, arrivals_ = run_prompts, run_outs, run_arrivals

        def one_pass():
            return _serve_gen_engine_pass(engine, prompts_, outs_,
                                          arrivals_)

        try:
            one_pass()
            one_pass()
            # occupancy over the TIMED passes only: the compile pass
            # saturates the slots while XLA works and would bias the
            # lifetime ratio upward
            base_steps = engine.decode_steps
            base_active = engine.active_slot_steps
            passes = [one_pass() for _ in range(shp["repeats"])]
            goodputs = [p[0] for p in passes]
            lats = np.asarray([l for p in passes for l in p[1]])
            d_steps = engine.decode_steps - base_steps
            occupancy = round(
                100.0 * (engine.active_slot_steps - base_active)
                / max(1, d_steps * engine.n_slots), 1)
            stats = engine.stats()
        finally:
            engine.shutdown(drain_timeout=30.0)
        return (float(np.median(goodputs)),
                float(max(goodputs) / min(goodputs)), lats, occupancy,
                stats)

    def pct(lats):
        return {"p50": round(1e3 * float(np.percentile(lats, 50)), 2),
                "p99": round(1e3 * float(np.percentile(lats, 99)), 2)}

    net = build_net()
    # r5 configuration: one-shot prefill for every prompt (buckets cover
    # the longest) and a full max-length KV allocation per slot
    r5_goodput, _, r5_lats, _, r5_stats = engine_goodput(
        net, shp["r5_n_slots"],
        prompt_buckets=(short_t0, long_t0))
    kv_budget_pages = r5_stats["pool_pages"]  # n_slots x pages-per-slot

    # paged configuration: 4x the slots on the SAME pool, short buckets
    # so the 4096-token prompts ride chunked prefill
    goodput, spread, lats, occupancy, stats = engine_goodput(
        net, shp["r5_n_slots"] * shp["slots_multiplier"],
        pool_pages=kv_budget_pages,
        prompt_buckets=(short_t0,))
    bench_serve_generate.latency_ms = pct(lats)
    bench_serve_generate.slot_occupancy_pct = occupancy
    bench_serve_generate.pages_in_use_peak = stats["pages_in_use_peak"]
    bench_serve_generate.pool_pages = stats["pool_pages"]
    bench_serve_generate.prefill_chunks = stats["prefill_chunks"]
    bench_serve_generate.r5_goodput_tokens_per_sec = round(r5_goodput, 1)
    bench_serve_generate.r5_latency_ms = pct(r5_lats)
    bench_serve_generate.paged_vs_r5_goodput = round(
        goodput / r5_goodput, 3)

    # device_ms_per_token for the serving path (ROADMAP item 4: the
    # generate-adjacent config still lacked a device-time number): run
    # the SAME paged configuration and arrivals with HALVED output
    # lengths and difference out the per-pass fixed cost (prefills,
    # arrival idle, dispatch floor) — the incremental cost of the
    # extra tokens is the decode path's device-side price per token
    half_outs = np.maximum(1, outs // 2)

    def paged_dms(g_full=None, **extra_kw):
        """device_ms_per_token of the paged config under the CURRENT
        dispatch environment: full vs halved output lengths, the
        per-pass fixed cost (prefills, arrival idle, dispatch floor)
        differenced out. ONE implementation for the kernel and
        gather sides (and the int8-KV A/B, via `extra_kw`) so a
        committed ratio can never compare numbers computed under
        different rules. `g_full`: reuse an already-measured
        full-lengths goodput instead of re-running."""
        if g_full is None:
            g_full = engine_goodput(
                net, shp["r5_n_slots"] * shp["slots_multiplier"],
                pool_pages=kv_budget_pages,
                prompt_buckets=(short_t0,), **extra_kw)[0]
        g_half = engine_goodput(
            net, shp["r5_n_slots"] * shp["slots_multiplier"],
            outs_override=half_outs,
            pool_pages=kv_budget_pages, prompt_buckets=(short_t0,),
            **extra_kw)[0]
        toks_full, toks_half = int(outs.sum()), int(half_outs.sum())
        dt_full, dt_half = toks_full / g_full, toks_half / g_half
        if dt_full > dt_half and toks_full > toks_half:
            return round(1e3 * (dt_full - dt_half)
                         / (toks_full - toks_half), 4)
        # noise swamped the differencing: report the wall bound
        return round(1e3 * dt_full / toks_full, 4)

    bench_serve_generate.device_ms_per_token = paged_dms(g_full=goodput)

    # paged-kernel vs gather A/B (ISSUE 9): the headline runs above
    # dispatched the Pallas page-walk kernel wherever the platform
    # supports it; re-run the IDENTICAL paged config and traffic with
    # the kill switch set (fresh engines re-trace their dispatch), so
    # the kernel's device-time win is a committed number, not a claim.
    # `paged_kernel_vs_gather` = gather-path device_ms_per_token over
    # kernel-path device_ms_per_token (>1 = kernel wins). On CPU smoke
    # runs both sides are the gather path and the ratio sits at ~1.
    import os

    bench_serve_generate.paged_kernel_device_ms_per_token = \
        bench_serve_generate.device_ms_per_token
    # save/restore: never clobber a user-set override (the LSTM A/B
    # discipline) — a driver run forcing the gather path everywhere
    # must stay forced after this block
    prior = os.environ.get("DL4J_TPU_NO_PALLAS_PAGED_ATTENTION")
    os.environ["DL4J_TPU_NO_PALLAS_PAGED_ATTENTION"] = "1"
    try:
        gather_dms = paged_dms()
    finally:
        if prior is None:
            os.environ.pop("DL4J_TPU_NO_PALLAS_PAGED_ATTENTION", None)
        else:
            os.environ["DL4J_TPU_NO_PALLAS_PAGED_ATTENTION"] = prior
    bench_serve_generate.paged_gather_device_ms_per_token = gather_dms
    bench_serve_generate.paged_kernel_vs_gather = round(
        gather_dms / bench_serve_generate.device_ms_per_token, 3)

    # GQA variant line (not the headline: baseline comparability)
    gqa_net = build_net(n_kv_heads=shp["gqa_kv_heads"])
    gqa_goodput = engine_goodput(
        gqa_net, shp["r5_n_slots"] * shp["slots_multiplier"],
        pool_pages=kv_budget_pages, prompt_buckets=(short_t0,))[0]
    bench_serve_generate.gqa_goodput_tokens_per_sec = round(gqa_goodput, 1)

    # -- latency tier (ISSUE 8): shared-prefix Poisson traffic through
    # the SAME paged configuration on the IDENTICAL page budget, with
    # and without prefix caching + speculative decoding. The tier's
    # p50/p99 arrival→completion latency against the bare paged config
    # is the success metric (ROADMAP item 5); `prefix_hit_tokens_pct`,
    # `spec_accept_rate` and `spec_tokens_per_step` are the committed
    # tuning numbers. The draft is the target itself ("self"): these
    # bench nets are untrained, so a genuinely smaller draft would
    # propose noise — self-speculation prices the verify machinery at
    # its acceptance-rate ceiling while remaining exactly the config
    # knob (`speculative={"draft": <smaller net>}`) a real deployment
    # would point at a distilled model.
    sp_workload = _shared_prefix_workload(shp, rng)
    n_slots = shp["r5_n_slots"] * shp["slots_multiplier"]
    sp_base_goodput, _, sp_base_lats, _, _ = engine_goodput(
        net, n_slots, workload=sp_workload,
        pool_pages=kv_budget_pages, prompt_buckets=(short_t0,))
    (sp_tier_goodput, _, sp_tier_lats, _,
     sp_stats) = engine_goodput(
        net, n_slots, workload=sp_workload,
        pool_pages=kv_budget_pages, prompt_buckets=(short_t0,),
        prefix_cache=True,
        speculative={"draft": "self", "k": shp["spec_k"]})
    bench_serve_generate.shared_prefix_latency_ms = pct(sp_tier_lats)
    bench_serve_generate.shared_prefix_base_latency_ms = pct(sp_base_lats)
    bench_serve_generate.shared_prefix_goodput_tokens_per_sec = round(
        sp_tier_goodput, 1)
    bench_serve_generate.shared_prefix_base_goodput_tokens_per_sec = \
        round(sp_base_goodput, 1)
    base_p50 = pct(sp_base_lats)["p50"]
    tier_p50 = pct(sp_tier_lats)["p50"]
    bench_serve_generate.latency_tier_p50_speedup = round(
        base_p50 / tier_p50, 3) if tier_p50 > 0 else None
    bench_serve_generate.prefix_hit_tokens_pct = \
        sp_stats["prefix_hit_tokens_pct"]
    bench_serve_generate.spec_accept_rate = sp_stats["spec_accept_rate"]
    bench_serve_generate.spec_tokens_per_step = \
        sp_stats["spec_tokens_per_step"]

    # -- quantized KV tier (ISSUE 13): int8 paged KV vs full-precision
    # pools, priced with the SAME differencing rule as every other
    # serving A/B. Both sides request quantize={"kv": "int8"}; the
    # bf16 side flips the DL4J_TPU_NO_INT8_KV kill switch, which makes
    # a fresh engine build full-precision pools — so the ratio measures
    # exactly what the switch toggles in production. >1 = int8 wins.
    int8_kw = dict(quantize={"kv": "int8"})
    int8_dms = paged_dms(**int8_kw)
    prior = os.environ.get("DL4J_TPU_NO_INT8_KV")
    os.environ["DL4J_TPU_NO_INT8_KV"] = "1"
    try:
        bf16_dms = paged_dms(**int8_kw)
    finally:
        if prior is None:
            os.environ.pop("DL4J_TPU_NO_INT8_KV", None)
        else:
            os.environ["DL4J_TPU_NO_INT8_KV"] = prior
    bench_serve_generate.int8_kv_device_ms_per_token = int8_dms
    bench_serve_generate.bf16_kv_device_ms_per_token = bf16_dms
    bench_serve_generate.int8_kv_vs_bf16_device_ms_per_token = round(
        bf16_dms / int8_dms, 3) if int8_dms > 0 else None

    # slots-per-chip on the IDENTICAL KV-pool byte budget: int8 pages
    # cost half the bytes of the bf16 compute dtype's, so the same
    # budget holds 2x the pages — run 2x the slots over the same
    # traffic and require ZERO OutOfPagesError sheds. The committed
    # line degrades toward 1.0 with every shed, so a 2.0 here is a
    # measured admission win, not an arithmetic identity.
    (int8_goodput, _, _, _, int8_stats) = engine_goodput(
        net, 2 * n_slots, pool_pages=2 * kv_budget_pages,
        prompt_buckets=(short_t0,), **int8_kw)
    admitted = 1.0 - (int8_stats["shed_out_of_pages"]
                      / max(1, int8_stats["submitted"]))
    bench_serve_generate.int8_kv_slots_per_chip = round(2.0 * admitted, 2)
    bench_serve_generate.int8_kv_out_of_pages_sheds = \
        int8_stats["shed_out_of_pages"]
    bench_serve_generate.int8_kv_goodput_tokens_per_sec = round(
        int8_goodput, 1)
    bench_serve_generate.kv_bytes_per_token = {
        "int8": int8_stats["kv_bytes_per_token"],
        "bf16": stats["kv_bytes_per_token"]}

    # -- tensor-parallel tier (ISSUE 15): the IDENTICAL paged config
    # sharded Megatron-style over a tp mesh vs the single-device runs
    # above, priced with the shared differencing rule. The tier's
    # headline is `tp_max_model_bytes_per_chip` — per-chip weight + KV
    # residency under tp vs one chip holding everything (the capacity
    # claim: the sharded portion divides by the degree, so models too
    # big for a chip fit a mesh). `tp_vs_single_goodput` < 1 on CPU
    # smoke is expected — two virtual host devices share one core and
    # psum is pure overhead there; on a real mesh the same line prices
    # the all-reduce tax against the memory win. Guarded on device
    # count: a 1-device run commits no tp lines (tier-1's CPU smoke
    # forces 8 host devices, so the lines commit there).
    import jax

    tp_degree = shp.get("tp_degree", 2)
    if len(jax.devices()) >= tp_degree:
        tp_kw = dict(parallel={"tp": tp_degree})
        # --trace wraps the tp passes like the step benches' first timed
        # pass: the capture shows per-shard dispatch and the psum pair
        # per block (named `tp-allreduce`), the profile that decides
        # whether the all-reduce tax or the serving host loop bounds a
        # tp deployment
        with _maybe_trace_capture():
            (tp_goodput, _, _, _, tp_stats) = engine_goodput(
                net, n_slots, pool_pages=kv_budget_pages,
                prompt_buckets=(short_t0,), **tp_kw)
        bench_serve_generate.tp_degree = tp_stats["tp_degree"]
        bench_serve_generate.tp_goodput_tokens_per_sec = round(
            tp_goodput, 1)
        bench_serve_generate.tp_vs_single_goodput = round(
            tp_goodput / goodput, 3)
        bench_serve_generate.tp_device_ms_per_token = paged_dms(
            g_full=tp_goodput, **tp_kw)
        bench_serve_generate.tp_kv_bytes_per_token_per_shard = \
            tp_stats["tp_kv_bytes_per_token_per_shard"]

        def bytes_per_chip(**kw):
            # construction only: params are placed (and under tp,
            # permuted + sharded) at build time, but nothing compiles
            # until a request arrives — cheap enough to price residency
            eng = DecodeEngine(
                net, n_slots=n_slots, max_len=max_len,
                page_size=shp["page_size"],
                prompt_buckets=(short_t0,),
                pool_pages=kv_budget_pages, **kw)
            try:
                return eng.model_bytes_per_chip()
            finally:
                eng.shutdown()

        single_bytes = bytes_per_chip()
        tp_bytes = bytes_per_chip(**tp_kw)
        bench_serve_generate.single_model_bytes_per_chip = single_bytes
        bench_serve_generate.tp_max_model_bytes_per_chip = tp_bytes
        bench_serve_generate.tp_bytes_per_chip_vs_single = round(
            tp_bytes / single_bytes, 3)

    return ("serve_generate_paged_goodput_tokens_per_sec", goodput, None,
            spread)


_SERVE_QOS_SHAPE = {
    "vocab": 256, "d_model": 128, "n_heads": 4, "n_layers": 2,
    "prompt_len": 16, "n_tokens": 8, "n_interactive": 24,
    "mean_interarrival": 0.01, "n_slots": 4, "page_size": 16,
    "flood_rate": 60.0, "flood_burst": 24.0, "flood_concurrency": 2,
    # diurnal predict replay (part B): threads per phase and per-phase
    # request budget for the closed-loop drive
    "low_threads": 1, "peak_threads": 8, "reqs_per_thread": 40,
    "replica_max_queue": 8, "slow_step": 0.02,
}


def bench_serve_qos():
    """The adaptive control plane priced end to end (ISSUE 16), two
    drills in one config:

    **Cross-tenant isolation** — one `DecodeEngine` with a quota'd
    batch tenant (`TenantFloodInjector` hammering it) while an
    interactive tenant runs the same Poisson traffic it ran unloaded.
    Committed lines: `cross_tenant_isolation` (interactive p99 flooded
    over unloaded — the ≤ 2 acceptance ratio), the flooder's quota
    rejections (its OWN typed wall, proving the flood never converts
    into everyone's `ServerOverloadedError`), and
    `batch_lane_utilization_pct` — the batch lane's share of tokens
    while isolation holds (quota'd, not starved to zero). The headline
    is the interactive lane's goodput UNDER flood.

    **Autoscale vs static** — a diurnal closed-loop predict replay
    (low → peak → low offered load) against a 1-replica `ReplicaPool`,
    static vs the same pool under an `Autoscaler` (spawned replicas
    enter through the probe ladder). Committed lines:
    `autoscale_p99_vs_static` (static peak p99 over autoscaled —
    > 1 = elasticity bought tail latency), `scale_up_reaction_ms`
    (peak onset → replica added), `autoscale_events`, and
    `autoscale_failed_requests` (the zero-failed-requests drain
    discipline, measured not asserted)."""
    import threading

    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.serving import (
        Autoscaler,
        ModelServer,
        ReplicaPool,
        ServingError,
        SlowInferenceInjector,
        TenantFloodInjector,
    )
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    shp = _SERVE_QOS_SHAPE
    rng = np.random.default_rng(0)

    # -- part A: tenant isolation on one decode engine -------------------
    max_len = shp["prompt_len"] + shp["n_tokens"] + 8
    gen_net = MultiLayerNetwork(
        gpt_configuration(vocab_size=shp["vocab"], d_model=shp["d_model"],
                          n_heads=shp["n_heads"], n_layers=shp["n_layers"],
                          max_length=max_len),
        compute_dtype=jnp.bfloat16)
    gen_net.init()
    prompts = [rng.integers(0, shp["vocab"],
                            shp["prompt_len"]).astype(np.int32)
               for _ in range(shp["n_interactive"])]
    arrivals = np.cumsum(rng.exponential(shp["mean_interarrival"],
                                         shp["n_interactive"]))
    engine = DecodeEngine(
        gen_net, n_slots=shp["n_slots"], max_len=max_len,
        page_size=shp["page_size"], prompt_buckets=(shp["prompt_len"],),
        max_queue=256, max_queued_pages=10 ** 9,
        qos={"tenants": {"flood": {"rate": shp["flood_rate"],
                                   "burst": shp["flood_burst"]}},
             "preempt": True, "slo_shed": True})

    def interactive_pass():
        t_start = time.monotonic()
        reqs = []
        for i, p in enumerate(prompts):
            lag = t_start + arrivals[i] - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            reqs.append(engine.submit(p, shp["n_tokens"], timeout=120.0,
                                      tenant="user",
                                      priority="interactive"))
        toks = sum(len(r.result(timeout=120.0)) for r in reqs)
        dt = time.monotonic() - t_start
        lats = [r.completed_at - (t_start + arrivals[i])
                for i, r in enumerate(reqs)]
        return toks / dt, lats

    def p99(lats):
        return float(np.percentile(np.asarray(lats), 99))

    try:
        interactive_pass()  # compile
        _, base_lats = interactive_pass()
        pre = engine.stats()
        flood = TenantFloodInjector(
            engine, tenant="flood",
            prompt=prompts[0], n_tokens=shp["n_tokens"],
            concurrency=shp["flood_concurrency"]).start()
        try:
            goodput, flood_lats = interactive_pass()
        finally:
            flood.release()
        post = engine.stats()
    finally:
        engine.shutdown(drain_timeout=30.0)
    fc = flood.counters()
    flood_tokens = (post["tenants"]["flood"]["tokens_generated"]
                    - pre["tenants"].get("flood", {}).get(
                        "tokens_generated", 0))
    total_tokens = post["tokens_generated"] - pre["tokens_generated"]
    bench_serve_qos.cross_tenant_isolation = round(
        p99(flood_lats) / max(1e-9, p99(base_lats)), 3)
    bench_serve_qos.flood_quota_rejections = fc["quota_rejections"]
    bench_serve_qos.flood_other_errors = fc["other_errors"]
    bench_serve_qos.batch_lane_utilization_pct = round(
        100.0 * flood_tokens / max(1, total_tokens), 1)
    bench_serve_qos.interactive_flooded_latency_ms = {
        "p50": round(1e3 * float(np.percentile(flood_lats, 50)), 2),
        "p99": round(1e3 * p99(flood_lats), 2)}
    bench_serve_qos.preemptions = post["preemptions"]

    # -- part B: diurnal replay, static vs autoscaled pool ---------------
    conf = (NeuralNetConfiguration.Builder()
            .seed(0).learning_rate(0.01)
            .list()
            .layer(DenseLayer(n_out=256, activation=Activation.RELU))
            .layer(OutputLayer(n_out=10, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(128))
            .build())
    mlp = MultiLayerNetwork(conf)
    mlp.init()
    x = rng.standard_normal((4, 128)).astype(np.float32)
    # the slow step makes one replica saturable on any host, so the
    # diurnal swell is a real overload and not a CPU-speed lottery
    server_kw = dict(max_queue=shp["replica_max_queue"],
                     max_batch_size=8, batch_window=0.001,
                     infer_hooks=[SlowInferenceInjector(shp["slow_step"])])
    lock = threading.Lock()

    def drive(pool, n_threads, latencies=None, failures=None):
        def client():
            mine = []
            for _ in range(shp["reqs_per_thread"]):
                t0 = time.perf_counter()
                try:
                    pool.predict(x, timeout=60.0)
                    mine.append(time.perf_counter() - t0)
                except ServingError:
                    if failures is not None:
                        with lock:
                            failures.append(1)
            if latencies is not None:
                with lock:
                    latencies.extend(mine)

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def diurnal(pool, scaler=None):
        """low → peak → low; returns (peak latencies, failures,
        scale-up reaction seconds or None)."""
        lats, fails = [], []
        reaction = None
        drive(pool, shp["low_threads"], failures=fails)
        t_peak = time.perf_counter()
        watcher_stop = threading.Event()

        def watch():
            nonlocal reaction
            while not watcher_stop.is_set():
                if pool.stats()["replicas_added"] > 0:
                    reaction = time.perf_counter() - t_peak
                    return
                time.sleep(0.005)

        w = None
        if scaler is not None:
            w = threading.Thread(target=watch)
            w.start()
        drive(pool, shp["peak_threads"], latencies=lats, failures=fails)
        drive(pool, shp["peak_threads"], latencies=lats, failures=fails)
        watcher_stop.set()
        if w is not None:
            w.join()
        drive(pool, shp["low_threads"], failures=fails)
        if scaler is not None:
            # give the low phase time to register and drain a replica
            deadline = time.perf_counter() + 10.0
            while (time.perf_counter() < deadline
                   and scaler.stats()["scale_downs"] == 0):
                time.sleep(0.05)
        return lats, fails, reaction

    static = ReplicaPool.from_net(mlp, 1, server_kwargs=server_kw,
                                  probe_batch=x, probe_interval=0.1)
    try:
        static.predict(x)  # compile
        static_lats, static_fails, _ = diurnal(static)
    finally:
        static.shutdown(drain_timeout=10.0)

    auto_pool = ReplicaPool.from_net(mlp, 1, server_kwargs=server_kw,
                                     probe_batch=x, probe_interval=0.05)
    scaler = Autoscaler(
        auto_pool, min_replicas=1, max_replicas=3, interval=0.05,
        alpha=0.5, high_watermark=0.6, low_watermark=0.2, hysteresis=2,
        cooldown=0.5, drain_timeout=10.0,
        spawn=lambda: ModelServer(mlp.clone(), **server_kw)).start()
    try:
        auto_pool.predict(x)  # compile
        auto_lats, auto_fails, reaction = diurnal(auto_pool, scaler)
        sc_stats = scaler.stats()
    finally:
        scaler.stop()
        auto_pool.shutdown(drain_timeout=10.0)

    static_p99 = p99(static_lats)
    auto_p99 = p99(auto_lats)
    bench_serve_qos.autoscale_p99_vs_static = round(
        static_p99 / max(1e-9, auto_p99), 3)
    bench_serve_qos.scale_up_reaction_ms = (
        None if reaction is None else round(1e3 * reaction, 1))
    bench_serve_qos.autoscale_events = sc_stats["autoscale_events"]
    bench_serve_qos.autoscale_failed_requests = len(auto_fails)
    bench_serve_qos.static_failed_requests = len(static_fails)
    bench_serve_qos.static_peak_latency_ms = {
        "p50": round(1e3 * float(np.percentile(static_lats, 50)), 2),
        "p99": round(1e3 * static_p99, 2)}
    bench_serve_qos.autoscaled_peak_latency_ms = {
        "p50": round(1e3 * float(np.percentile(auto_lats, 50)), 2),
        "p99": round(1e3 * auto_p99, 2)}

    return ("serve_qos_interactive_flooded_tokens_per_sec", goodput,
            None, 1.0)


_SERVE_DISAGG_SHAPE = {
    "vocab": 256, "d_model": 128, "n_heads": 4, "n_layers": 2,
    # the two Poisson mixes: prefill-heavy amortises long prompts,
    # decode-heavy amortises long emission — disaggregation trades
    # a KV wire hop for not letting one phase starve the other's slots
    "prefill_heavy": {"prompt_len": 48, "n_tokens": 4},
    "decode_heavy": {"prompt_len": 8, "n_tokens": 24},
    "n_requests": 16, "mean_interarrival": 0.01,
    "n_slots": 4, "page_size": 16,
}


def bench_serve_disagg():
    """Disaggregated prefill/decode + KV shipping priced end to end
    (ISSUE 17), three numbers in one config:

    **disagg_vs_colocated_goodput** — the same Poisson request mix
    driven through a `DisaggCoordinator` (prefill-role replica ships
    leased KV pages to a decode-role replica) and through one
    colocated engine, on a prefill-heavy and a decode-heavy mix. The
    ratio prices what the wire hop costs (or buys) per mix; the
    headline is the disaggregated decode-heavy goodput.

    **kv_transfer_mbytes_per_sec** — handoff wire throughput from the
    coordinator's own transfer ledger, bf16 KV vs int8 KV (int8 ships
    ~half the bytes per page plus f32 scale sidecars, so the SAME link
    moves ~2x the sequence-state per second).

    **migration_resume_ms** — one mid-sequence decode-state migration,
    warm (pages ride the lease, receiver re-binds) vs the degradation
    ladder's cold fallback (receiver re-prefills prompt + emitted
    tokens): the gap is what fault-tolerant page shipping saves on
    every live migration."""
    import threading

    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DisaggCoordinator,
        ModelServer,
        SlotMigratedError,
    )
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    shp = _SERVE_DISAGG_SHAPE
    rng = np.random.default_rng(0)
    max_len = max(m["prompt_len"] + m["n_tokens"]
                  for m in (shp["prefill_heavy"], shp["decode_heavy"])) + 8
    buckets = tuple(sorted({m["prompt_len"]
                            for m in (shp["prefill_heavy"],
                                      shp["decode_heavy"])}))

    def _net():
        net = MultiLayerNetwork(
            gpt_configuration(vocab_size=shp["vocab"],
                              d_model=shp["d_model"],
                              n_heads=shp["n_heads"],
                              n_layers=shp["n_layers"],
                              max_length=max_len),
            compute_dtype=jnp.bfloat16)
        net.init()
        return net

    def _gen_kw(**extra):
        return dict(n_slots=shp["n_slots"], max_len=max_len,
                    page_size=shp["page_size"], prompt_buckets=buckets,
                    max_queue=256, **extra)

    def _drive(generate_fn, mix):
        """Poisson-arrival closed set: N threads, one request each,
        arrivals drawn once (shared across all servers under test)."""
        n = shp["n_requests"]
        arrivals = np.cumsum(rng.exponential(shp["mean_interarrival"], n))
        prompts = [rng.integers(0, shp["vocab"],
                                mix["prompt_len"]).astype(np.int32)
                   for _ in range(n)]
        toks = [0] * n
        errs = []

        def one(i):
            try:
                toks[i] = len(generate_fn(prompts[i], mix["n_tokens"]))
            except Exception as e:  # noqa: BLE001 — bench counts, not hides
                errs.append(e)

        t0 = time.monotonic()
        threads = []
        for i in range(n):
            lag = t0 + arrivals[i] - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            th = threading.Thread(target=one, args=(i,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        dt = time.monotonic() - t0
        if errs:
            raise errs[0]
        return sum(toks) / dt

    net = _net()
    mixes = ("prefill_heavy", "decode_heavy")

    # -- colocated baseline: one engine does both phases ------------------
    colocated = {}
    server = ModelServer(net, generation=_gen_kw())
    try:
        for mix in mixes:
            _drive(lambda p, n: server.generate(p, n, timeout=120.0),
                   shp[mix])  # compile
            colocated[mix] = _drive(
                lambda p, n: server.generate(p, n, timeout=120.0),
                shp[mix])
    finally:
        server.shutdown(drain_timeout=30.0)

    # -- disaggregated: prefill replica ships KV to a decode replica ------
    disagg = {}
    wire = {}
    for tier, quant in (("bf16", None), ("int8", {"kv": "int8"})):
        co = DisaggCoordinator(
            net, server_kwargs={"generation": _gen_kw(
                **({} if quant is None else {"quantize": quant}))})
        try:
            for mix in mixes if tier == "bf16" else ("decode_heavy",):
                _drive(lambda p, n: co.generate(p, n, timeout=120.0),
                       shp[mix])  # compile
                g = _drive(lambda p, n: co.generate(p, n, timeout=120.0),
                           shp[mix])
                if tier == "bf16":
                    disagg[mix] = g
            st = co.stats()
            wire[tier] = round(st["kv_transfer_mbytes_per_sec"], 4)
            if tier == "bf16":
                bench_serve_disagg.disagg_handoffs = st["handoffs"]
                bench_serve_disagg.disagg_fallbacks = st["fallbacks"]
        finally:
            co.shutdown(drain_timeout=30.0)
    bench_serve_disagg.disagg_vs_colocated_goodput = {
        mix: round(disagg[mix] / max(1e-9, colocated[mix]), 3)
        for mix in mixes}
    bench_serve_disagg.kv_transfer_mbytes_per_sec = wire

    # -- delta vs full handoff bytes: shared-prefix traffic ---------------
    # with the cluster prefix cache on (ISSUE 20), the decode replica's
    # resident chain lets each prefill→decode handoff ship only the
    # pages the receiver doesn't already hold; the MB gap is pure wire
    # saved on every handoff of chat-shaped (shared-prefix) traffic
    shared = rng.integers(0, shp["vocab"], 32).astype(np.int32)
    sp_prompts = [
        np.concatenate([shared,
                        rng.integers(0, shp["vocab"], 8).astype(np.int32)])
        for _ in range(8)]
    sp_kw = _gen_kw(prefix_cache=True, prefill_chunk=16)
    sp_kw["prompt_buckets"] = (16,)  # force the chunked-prefill path
    handoff_mb = {}
    for label, cluster in (("full", False), ("delta", True)):
        co = DisaggCoordinator(net, server_kwargs={"generation": sp_kw},
                               prefix_cluster=cluster)
        try:
            for p in sp_prompts:
                co.generate(p, 4, timeout=120.0)
            st = co.stats()
            handoff_mb[label] = round(st["kv_transfer_mbytes"], 4)
            if cluster:
                bench_serve_disagg.delta_pages_skipped = \
                    st["delta_pages_skipped"]
        finally:
            co.shutdown(drain_timeout=30.0)
    bench_serve_disagg.delta_vs_full_handoff_mbytes = handoff_mb

    # -- migration resume: warm re-bind vs cold re-prefill ----------------
    mix = shp["prefill_heavy"]  # long prompt: the cold path repays it

    def hold(phase, info):  # keep the source sequence in flight long
        if phase == "pre_decode":  # enough to export it mid-decode
            time.sleep(0.02)

    src = DecodeEngine(net, **_gen_kw(step_hooks=[hold]))
    resume_ms = {}
    try:
        prompt = rng.integers(0, shp["vocab"],
                              mix["prompt_len"]).astype(np.int32)
        req = src.submit(prompt, mix["n_tokens"] + 4, timeout=120.0)
        while len(req.tokens) < 2:
            time.sleep(0.005)
        src.migrate_slots(wait=10.0)
        try:
            req.result(timeout=60.0)
            raise RuntimeError("bench expected the export redirect")
        except SlotMigratedError as redirect:
            warm = src.fetch_handoff(redirect.handoff_id)
        warm = dict(warm)
        warm["deadline_remaining"] = None
        cold = dict(warm, kind="cold", blocks=[], sums=[],
                    pages_shipped=0)
        for label, payload in (("warm", warm),
                               ("cold_reprefill", cold)):
            dst = DecodeEngine(net, **_gen_kw())
            try:
                dst.resume_generate(payload, timeout=120.0)  # compile
                t0 = time.monotonic()
                dst.resume_generate(payload, timeout=120.0)
                resume_ms[label] = round(
                    1e3 * (time.monotonic() - t0), 2)
            finally:
                dst.shutdown(drain_timeout=30.0)
    finally:
        src.shutdown(drain_timeout=30.0)
    bench_serve_disagg.migration_resume_ms = resume_ms

    return ("serve_disagg_decode_heavy_tokens_per_sec",
            disagg["decode_heavy"], None, 1.0)


_SERVE_PREFIX_CLUSTER_SHAPE = {
    "vocab": 256, "d_model": 128, "n_heads": 4, "n_layers": 2,
    # chat-shaped: one long shared system prefix, short unique tails —
    # the workload where cross-host sharing pays (each replica would
    # otherwise cold-prefill the SAME prefix once per pool member)
    "shared_prefix_len": 192, "tail_len": 8,
    "n_requests": 18, "n_tokens": 6, "mean_interarrival": 0.02,
    # margin sized to the fetch path: once the spread leg has warmed
    # every replica over the wire (~a few hundred ms each), packing
    # the mix onto the holder forfeits the pool's decode parallelism —
    # a small margin takes the free affinity wins but spills the bulk
    # to the (now warm) peers; the margin is the policy knob that
    # encodes exactly that trade
    "affinity_margin": 2,
    # slots sized so affinity CONCENTRATION doesn't forfeit decode
    # batching: the warm holder can decode the whole absorbed burst in
    # one iteration-level batch instead of queueing it 4 at a time
    "n_replicas": 3, "n_slots": 8, "page_size": 16, "prefill_chunk": 32,
}


def bench_serve_prefix_cluster():
    """Cluster-global prefix cache priced end to end (ISSUE 20), three
    numbers in one config:

    **cluster_vs_local_prefix_goodput** — the same shared-system-prompt
    Poisson mix driven through a 3-replica `ReplicaPool` twice: once
    with only per-replica prefix caches (every replica cold-prefills
    the shared prefix on first contact) and once with a bound
    `PrefixDirectory` (the first replica prefills it, the others fetch
    the KV pages over the handoff wire and suffix-prefill only the
    tail). The ratio prices what cross-host sharing buys; > 1.0 means
    the wire fetch beats re-prefilling.

    **first_token_ms (local vs cluster)** — p50/p99 time-to-first-token
    from an `on_token` sink, same arrival schedule both runs. The p99
    is where the win concentrates: the unlucky requests that land on a
    cold replica.

    **fetch_vs_reprefill_ms** — the crossover economics, measured
    directly: average wall time of one directory fetch of the shared
    chain (wire + checksum + bind) vs one cold chunked prefill of the
    same prefix. Fetch cost is mostly fixed per transfer while prefill
    grows with depth, so `crossover_pages` ~ fetch_ms / per-page
    prefill ms is the depth above which fetching always wins."""
    import threading

    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import PrefixDirectory, ReplicaPool

    shp = _SERVE_PREFIX_CLUSTER_SHAPE
    rng = np.random.default_rng(0)
    t0 = shp["shared_prefix_len"] + shp["tail_len"]
    max_len = t0 + shp["n_tokens"] + 8
    net = MultiLayerNetwork(
        gpt_configuration(vocab_size=shp["vocab"], d_model=shp["d_model"],
                          n_heads=shp["n_heads"], n_layers=shp["n_layers"],
                          max_length=max_len),
        compute_dtype=jnp.bfloat16)
    net.init()
    gen = dict(n_slots=shp["n_slots"], max_len=max_len,
               page_size=shp["page_size"],
               prompt_buckets=(shp["page_size"],),  # chunked prefill path
               prefill_chunk=shp["prefill_chunk"],
               prefix_cache=True, max_queue=256)
    shared = rng.integers(0, shp["vocab"],
                          shp["shared_prefix_len"]).astype(np.int32)
    n = shp["n_requests"]
    prompts = [np.concatenate(
        [shared,
         rng.integers(0, shp["vocab"], shp["tail_len"]).astype(np.int32)])
        for _ in range(n)]
    arrivals = np.cumsum(rng.exponential(shp["mean_interarrival"], n))
    # warmup prefix DISTINCT from the measured one: compiles every
    # replica's engine without pre-warming the shared chain anywhere
    warm_prompts = [np.concatenate(
        [rng.integers(0, shp["vocab"],
                      shp["shared_prefix_len"]).astype(np.int32),
         rng.integers(0, shp["vocab"], shp["tail_len"]).astype(np.int32)])
        for _ in range(shp["n_replicas"])]

    def _drive(pool):
        ttfts = [None] * n
        toks = [0] * n
        errs = []
        # the LB-spread leg: after a scale-out (or failover) the
        # front-end fans traffic across ALL replicas, so pin one
        # shared-prefix request on each cold replica at window open.
        # This leg is the work the A/B prices — local arm: one full
        # cold prefill per replica; cluster arm: one page fetch per
        # replica — and pinning it makes the ratio measure the
        # feature, not least-loaded routing luck (which otherwise
        # concentrates the whole mix on the warm holder in BOTH arms)
        spread = shp["n_replicas"] - 1

        def one(i, t_req):
            def sink(cursor, token, logprob):
                if ttfts[i] is None:
                    ttfts[i] = time.perf_counter() - t_req
            try:
                if i < spread:
                    toks[i] = len(pool._replicas[1 + i].server.generate(
                        prompts[i], shp["n_tokens"], timeout=300.0,
                        on_token=sink))
                else:
                    toks[i] = len(pool.generate(
                        prompts[i], shp["n_tokens"], timeout=300.0,
                        on_token=sink))
            except Exception as e:  # noqa: BLE001 — bench counts, not hides
                errs.append(e)

        t_start = time.monotonic()
        threads = []
        for i in range(n):
            if i >= spread:  # spread requests burst at window open
                lag = t_start + arrivals[i] - time.monotonic()
                if lag > 0:
                    time.sleep(lag)
            th = threading.Thread(target=one, args=(i, time.perf_counter()))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        dt = time.monotonic() - t_start
        if errs:
            raise errs[0]
        return sum(toks) / dt, [t for t in ttfts if t is not None]

    def _pct(xs):
        return {"p50": round(1e3 * float(np.percentile(xs, 50)), 2),
                "p99": round(1e3 * float(np.percentile(xs, 99)), 2)}

    goodput, ttft = {}, {}
    fetch_stats = {}
    for label, cluster in (("local", False), ("cluster", True)):
        # probe_interval stretched past the measured window: the auto-
        # armed generation canary is a LONG prompt here, and a 1 s probe
        # cadence would re-prefill (or re-fetch) it on every replica
        # mid-drive, drowning the A/B in canary traffic
        pool = ReplicaPool.from_net(
            net, shp["n_replicas"], server_kwargs={"generation": gen},
            prefix_directory=PrefixDirectory() if cluster else None,
            affinity_margin=shp["affinity_margin"], probe_interval=120.0)
        try:
            # concurrent warmups: one per replica, distinct prefix
            ws = [threading.Thread(
                target=lambda p=p: pool.generate(p, 2, timeout=300.0))
                for p in warm_prompts]
            for w in ws:
                w.start()
            for w in ws:
                w.join()
            # warm the measured prefix on ONE pinned replica (both
            # runs): the scenario the cluster tier targets is a pool
            # where the prefix is already hot SOMEWHERE — after a
            # failover, autoscale-up, or simply yesterday's traffic —
            # and the question is what the OTHER replicas pay: a cold
            # prefill each (local) vs an affinity route or page fetch
            # (cluster). Pinned so the drive's spread leg knows which
            # replicas start cold
            pool._replicas[0].server.generate(prompts[0], 2,
                                              timeout=300.0)
            goodput[label], ts = _drive(pool)
            ttft[label] = _pct(ts)
            if cluster:
                st = pool.stats()
                fetch_stats = {
                    "affinity_routes": st["affinity_routes"],
                    "directory_entries": st["directory_entries"]}
                agg = {}
                for rep in pool._replicas:
                    g = rep.server.stats().get("generation", {})
                    for k in ("prefix_fetches", "prefix_fetch_ms",
                              "prefix_fetch_bytes",
                              "prefix_fetch_fallbacks"):
                        agg[k] = agg.get(k, 0) + g.get(k, 0)
                fetch_stats.update(agg)
        finally:
            pool.shutdown(drain_timeout=30.0)
    bench_serve_prefix_cluster.cluster_vs_local_prefix_goodput = round(
        goodput["cluster"] / max(1e-9, goodput["local"]), 3)
    bench_serve_prefix_cluster.first_token_ms = ttft
    bench_serve_prefix_cluster.cluster_fetch = fetch_stats

    # -- fetch-vs-reprefill crossover: one fetch vs one cold prefill ------
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    d = PrefixDirectory()
    prefix_pages = (t0 - 1) // shp["page_size"]
    holder = DecodeEngine(net, **gen)
    peers = {"holder": holder}
    holder.bind_prefix_directory(d, "holder", peers.get)
    fetch_ms, reprefill_ms = [], []
    try:
        holder.generate(prompts[0], 2)  # warm + publish the chain
        for trial in range(2):
            cold = DecodeEngine(net, **gen)
            try:
                tw = time.perf_counter()
                cold.generate(prompts[0], 2)  # cold chunked prefill
                reprefill_ms.append(1e3 * (time.perf_counter() - tw))
            finally:
                cold.shutdown(drain_timeout=30.0)
            fetcher = DecodeEngine(net, **gen)
            fetcher.bind_prefix_directory(d, f"f{trial}", peers.get)
            try:
                tw = time.perf_counter()
                fetcher.generate(prompts[0], 2)  # fetch + suffix prefill
                fetch_ms.append(1e3 * (time.perf_counter() - tw))
                assert fetcher.stats()["prefix_fetches"] == 1
            finally:
                fetcher.shutdown(drain_timeout=30.0)
    finally:
        holder.shutdown(drain_timeout=30.0)
    f_ms = float(np.median(fetch_ms))
    r_ms = float(np.median(reprefill_ms))
    per_page = max(1e-9, r_ms / max(1, prefix_pages))
    bench_serve_prefix_cluster.fetch_vs_reprefill_ms = {
        "fetch_ms": round(f_ms, 2), "reprefill_ms": round(r_ms, 2),
        "prefix_pages": prefix_pages,
        "crossover_pages": round(f_ms / per_page, 1)}

    return ("serve_prefix_cluster_tokens_per_sec", goodput["cluster"],
            None, 1.0)


def bench_serve_exactly_once():
    """Exactly-once serving priced end to end (ISSUE 18), three numbers
    in one config:

    **dedup_overhead_pct** — steady-state gateway predict throughput
    with every request stamped through the dedup door (idempotency key
    + completed-result ring + in-flight registry) vs the same wire
    path unstamped. This is the always-on tax of the at-most-once
    promise.

    **journal_append_latency_ms** — one durable WAL admit (CRC'd
    record, flush + fsync): the at-least-once side's cost per accepted
    generate/predict/fit, fsync included because that is the number
    that survives kill -9.

    **gateway_crash_recovery_ms** — a journal left exactly as a dead
    gateway leaves it (accepted admits, no completes) is mounted by a
    fresh gateway; the clock runs from server start until every
    orphaned request has replayed through fresh prefill and its
    outcome is claimable. `requests_lost` and `double_executions`
    ride along and must both be ZERO — recovery speed only counts if
    the ledger balances."""
    import tempfile
    import threading

    from deeplearning4j_tpu.gateway import GatewayClient, GatewayServer
    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.updater import Updater
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.serving.exactly_once import RequestJournal

    conf = (NeuralNetConfiguration.Builder()
            .seed(0).learning_rate(0.01).updater(Updater.ADAM)
            .list()
            .layer(DenseLayer(n_out=256, activation=Activation.RELU))
            .layer(OutputLayer(n_out=10, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(128))
            .build())
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 128)).astype(np.float32)
    n_threads, reqs_per_thread = 4, 16

    def _drive(client):
        def worker():
            for _ in range(reqs_per_thread):
                client.call("predict", name="m", features=x,
                            _timeout=60.0)

        dts = []
        for _ in range(_REPEATS):
            threads = [threading.Thread(target=worker)
                       for _ in range(n_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dts.append(time.perf_counter() - t0)
        dt, spread = _median_spread(dts)
        return n_threads * reqs_per_thread / dt, spread

    # -- leg 1: the dedup door's steady-state tax --------------------------
    server = GatewayServer(exactly_once=True).start()
    try:
        plain = GatewayClient(port=server.port)
        stamped = GatewayClient(port=server.port, exactly_once=True)
        plain.call("create_model", name="m", config=conf.to_json())
        for _ in range(4):
            plain.call("predict", name="m", features=x)  # compile warm
        plain_rps, _ = _drive(plain)
        stamped_rps, spread = _drive(stamped)
        st = stamped.call("exactly_once_stats")
        assert st["cache"]["double_executions"] == 0
        plain.close()
        stamped.close()
    finally:
        server.stop()
    bench_serve_exactly_once.dedup_overhead_pct = round(
        100.0 * (1.0 - stamped_rps / max(1e-9, plain_rps)), 1)

    # -- leg 2: the durable admit (flush + fsync per record) ---------------
    with tempfile.TemporaryDirectory() as d:
        j = RequestJournal(d, fsync=True)
        params = {"name": "m", "n_tokens": 8}
        lats = []
        for i in range(200):
            t0 = time.perf_counter()
            j.admit(f"bench-{i}", "generate", params)
            lats.append(time.perf_counter() - t0)
        j.close()
    bench_serve_exactly_once.journal_append_latency_ms = round(
        1e3 * float(np.median(np.asarray(lats))), 3)

    # -- leg 3: crash recovery — replay a dead gateway's journal -----------
    from deeplearning4j_tpu.gateway import encode_value

    gconf = gpt_configuration(vocab_size=48, d_model=32, n_heads=2,
                              n_layers=2, max_length=64)
    n_orphans = 6
    with tempfile.TemporaryDirectory() as d:
        j = RequestJournal(d)
        prompts = [rng.integers(0, 48, 8).astype(np.int32)
                   for _ in range(n_orphans)]
        for i, p in enumerate(prompts):
            j.admit(f"orphan-{i}", "generate",
                    encode_value({"name": "g", "prompt_ids": p,
                                  "n_tokens": 6}))
        j.close()

        t0 = time.perf_counter()
        server = GatewayServer(
            serving={"generation": {"n_slots": 2, "max_len": 32,
                                    "prompt_buckets": (8,)}},
            exactly_once={"journal_dir": d, "replay_timeout": 120.0})
        server.entry.create_model("g", gconf.to_json())
        server.start()
        lost = 0
        try:
            client = GatewayClient(port=server.port, exactly_once=True)
            for i in range(n_orphans):
                try:
                    client.claim(f"orphan-{i}", timeout=120.0)
                except Exception:  # noqa: BLE001 — bench counts, not hides
                    lost += 1
            recovery_ms = round(1e3 * (time.perf_counter() - t0), 1)
            st = client.call("exactly_once_stats")
            bench_serve_exactly_once.crash_double_executions = \
                st["cache"]["double_executions"]
            client.close()
        finally:
            server.stop()
    bench_serve_exactly_once.gateway_crash_recovery_ms = recovery_ms
    bench_serve_exactly_once.crash_requests_lost = lost
    assert lost == 0, "crash recovery lost accepted requests"
    assert bench_serve_exactly_once.crash_double_executions == 0

    return ("serve_exactly_once_predict_roundtrips_per_sec",
            stamped_rps, None, spread)


# serve_stream workload shape — module-level so the slow CPU smoke test
# (tests/test_streaming.py) can shrink it without forking the
# measurement logic. The wire legs (TTFT, resume) use a small GPT —
# they price the streaming WIRE, not the model; the goodput-tax leg
# uses the paged Poisson config's model scale so per-token compute is
# serving-shaped rather than microbenchmark-shaped.
_SERVE_STREAM_SHAPE = {
    "vocab": 48, "d_model": 32, "n_heads": 2, "n_layers": 2,
    "max_length": 64, "n_slots": 4, "max_len": 48,
    "prompt_buckets": (8,), "prompt_len": 8,
    "n_tokens": 24, "n_requests": 8, "repeats": _REPEATS,
    # goodput-tax leg (engine-level, paged Poisson config)
    "tax_vocab": 256, "tax_d_model": 256, "tax_n_heads": 8,
    "tax_n_layers": 4, "tax_prompt_len": 128, "tax_max_len": 256,
    "tax_n_slots": 8, "tax_n_requests": 10, "tax_out_lengths": (32, 48),
    "tax_mean_interarrival": 0.01, "tax_repeats": 3,
}


def bench_serve_stream():
    """Token streaming priced end to end (ISSUE 19):

    **ttft_ms** — time-to-first-token of `generate_stream` (issue →
    first frame on the wire), p50/p99 across `n_requests` serial
    requests, vs **unary_latency_ms** (the full `generate` round-trip
    the stream's first frame undercuts).

    **goodput_tax_pct** — per-frame overhead on the paged Poisson
    config: the ONLY streaming code on the scheduler's critical path
    is the `on_token` ring publish (pumps and consumers run on their
    own threads), so the tax on goodput is `publish cost / per-token
    decode time`, both measured on the tax-leg config — the publish
    micro-timed against a live lingering pump, the per-token time from
    the unary engine pass. Acceptance: < 2%. A full streamed-vs-unary
    wall-clock A/B on the same Poisson workload rides along as
    `streamed_vs_unary_wall_pct` — informational, because on a 1-core
    CI host it also charges the pump/consumer threads' timeslices to
    the server and its run-to-run noise floor (±4-7%) swamps a 2% bar.

    **resume_after_tear_ms** — the connection is torn (RST) after the
    first frame; the clock runs from the tear until the next token
    arrives on the transparently re-attached stream (reconnect +
    `resume_stream` + ring replay). Tokens must be bit-identical to
    unary — the resume only counts if the concatenation balances."""
    import socket as _socket

    from deeplearning4j_tpu.gateway import GatewayClient, GatewayServer
    from deeplearning4j_tpu.models.transformer import gpt_configuration

    shp = _SERVE_STREAM_SHAPE
    gconf = gpt_configuration(seed=12345, vocab_size=shp["vocab"],
                              d_model=shp["d_model"],
                              n_heads=shp["n_heads"],
                              n_layers=shp["n_layers"],
                              max_length=shp["max_length"])
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, shp["vocab"],
                            shp["prompt_len"]).astype(np.int32)
               for _ in range(shp["n_requests"])]

    def pct(lats):
        return {"p50": round(1e3 * float(np.percentile(lats, 50)), 2),
                "p99": round(1e3 * float(np.percentile(lats, 99)), 2)}

    server = GatewayServer(
        serving={"generation": {"n_slots": shp["n_slots"],
                                "max_len": shp["max_len"],
                                "prompt_buckets": shp["prompt_buckets"]}})
    server.entry.create_model("g", gconf.to_json())
    server.start()
    try:
        client = GatewayClient(port=server.port)
        # compile warm: one unary pass over every prompt
        unary = [np.asarray(client.call(
            "generate", name="g", prompt_ids=p,
            n_tokens=shp["n_tokens"], seed=11, _timeout=120.0))
            for p in prompts]

        # TTFT: serial streams, clock from issue to first frame; unary
        # round-trips over the same prompts are the comparison column
        ttfts, unary_lats = [], []
        for _ in range(shp["repeats"]):
            for i, p in enumerate(prompts):
                t_req = time.perf_counter()
                with client.generate_stream(
                        "g", p, shp["n_tokens"], seed=11,
                        _timeout=120.0) as s:
                    first = True
                    for _tok in s:
                        if first:
                            ttfts.append(time.perf_counter() - t_req)
                            first = False
                assert np.array_equal(np.asarray(s.tokens), unary[i]), \
                    "streamed tokens diverged from unary"
                t_req = time.perf_counter()
                client.call("generate", name="g", prompt_ids=p,
                            n_tokens=shp["n_tokens"], seed=11,
                            _timeout=120.0)
                unary_lats.append(time.perf_counter() - t_req)

        # resume-after-tear: RST after the first frame, clock to the
        # next token on the re-attached stream
        resume_lats = []
        for i in range(min(3, shp["n_requests"])):
            with client.generate_stream("g", prompts[i],
                                        shp["n_tokens"], seed=11,
                                        _timeout=120.0) as s:
                next(s)
                s._conn.sock.shutdown(_socket.SHUT_RDWR)
                t_tear = time.perf_counter()
                next(s)
                resume_lats.append(time.perf_counter() - t_tear)
                for _tok in s:
                    pass
            assert np.array_equal(np.asarray(s.tokens), unary[i]), \
                "resumed stream diverged from unary"
            assert s.resumes >= 1
        client.close()
    finally:
        server.stop()

    bench_serve_stream.ttft_ms = pct(ttfts)
    bench_serve_stream.unary_latency_ms = pct(unary_lats)
    bench_serve_stream.resume_after_tear_ms = round(
        1e3 * float(np.median(np.asarray(resume_lats))), 1)

    # -- goodput-tax leg: the paged Poisson config, engine-level -----------
    import threading

    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, TokenStream

    tax_net = MultiLayerNetwork(gpt_configuration(
        seed=12345, vocab_size=shp["tax_vocab"],
        d_model=shp["tax_d_model"], n_heads=shp["tax_n_heads"],
        n_layers=shp["tax_n_layers"],
        max_length=4 * shp["tax_max_len"]))
    tax_net.init()
    engine = DecodeEngine(tax_net, n_slots=shp["tax_n_slots"],
                          max_len=shp["tax_max_len"],
                          prompt_buckets=(shp["tax_prompt_len"],))
    n = shp["tax_n_requests"]
    tax_prompts = [rng.integers(0, shp["tax_vocab"],
                                shp["tax_prompt_len"]).astype(np.int32)
                   for _ in range(n)]
    tax_outs = rng.choice(np.asarray(shp["tax_out_lengths"]), n)
    arrivals = np.cumsum(rng.exponential(shp["tax_mean_interarrival"], n))

    def engine_pass(with_sink: bool) -> float:
        """One Poisson pass; returns goodput tokens/sec. With sinks, a
        pump per stream drains its ring exactly like the gateway does
        (linger-coalesced reads on an off-scheduler thread)."""
        streams = [TokenStream(f"tax-{i}") for i in range(n)]

        def pump(st):
            c = 0
            while True:
                toks, _lps, c, body = st.read(c, timeout=0.25,
                                              linger=0.02)
                if body is not None and not toks:
                    return

        pumps = [threading.Thread(target=pump, args=(st,), daemon=True)
                 for st in streams]
        reqs = [None] * n
        t0 = time.monotonic()
        if with_sink:
            for p_ in pumps:
                p_.start()
        for i in range(n):
            lag = t0 + arrivals[i] - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            kw = {"on_token": streams[i].publish} if with_sink else {}
            reqs[i] = engine.submit(tax_prompts[i], int(tax_outs[i]),
                                    seed=11, timeout=300.0, **kw)
        toks = 0
        for i, r in enumerate(reqs):
            toks += len(r.result(timeout=300.0))
            streams[i].finish({"done": True})
        dt = time.monotonic() - t0
        if with_sink:
            for p_ in pumps:
                p_.join(timeout=10.0)
        return toks / dt

    try:
        engine_pass(True)  # compile + thread warm
        streamed_gp, unary_gp, token_s = [], [], []
        for _ in range(shp["tax_repeats"]):
            streamed_gp.append(engine_pass(True))
            unary_gp.append(engine_pass(False))
        unary_goodput = float(np.median(unary_gp))
        token_s = 1.0 / unary_goodput  # engine-seconds per token

        # per-frame overhead: publish micro-timed against a live
        # lingering pump — the only streaming work the scheduler pays
        st = TokenStream("tax-publish", capacity=1 << 16)
        done = threading.Event()

        def micro_pump():
            c = 0
            while not done.is_set():
                _t, _l, c, body = st.read(c, timeout=0.25, linger=0.02)
                if body is not None:
                    return

        pt = threading.Thread(target=micro_pump, daemon=True)
        pt.start()
        n_pub = 20000
        t0 = time.perf_counter()
        for cur in range(1, n_pub + 1):
            st.publish(cur, 7)
        publish_s = (time.perf_counter() - t0) / n_pub
        st.finish({"done": True})
        done.set()
        pt.join(timeout=5.0)
    finally:
        engine.shutdown(drain_timeout=30.0)

    streamed_goodput = float(np.median(streamed_gp))
    spread = float(max(streamed_gp) / min(streamed_gp))
    bench_serve_stream.goodput_tax_pct = round(
        100.0 * publish_s / (publish_s + token_s), 3)
    bench_serve_stream.publish_us = round(1e6 * publish_s, 2)
    bench_serve_stream.streamed_vs_unary_wall_pct = round(
        100.0 * (unary_goodput / max(1e-9, streamed_goodput) - 1.0), 1)
    return ("serve_stream_tokens_per_sec", streamed_goodput,
            None, spread)


_CONFIGS = {"lenet": bench_lenet, "resnet50": bench_resnet50,
            "lstm": bench_lstm, "lstm_large": bench_lstm_large,
            "gpt": bench_gpt,
            "gpt_med": bench_gpt_med, "gpt_long": bench_gpt_long,
            "word2vec": bench_word2vec,
            "word2vec_50k": bench_word2vec_50k,
            "generate": bench_generate,
            "checkpoint": bench_checkpoint,
            "sentinel": bench_sentinel,
            "serving": bench_serving,
            "serve_pool": bench_serve_pool,
            "serve_generate": bench_serve_generate,
            "serve_qos": bench_serve_qos,
            "serve_disagg": bench_serve_disagg,
            "serve_prefix_cluster": bench_serve_prefix_cluster,
            "serve_exactly_once": bench_serve_exactly_once,
            "serve_stream": bench_serve_stream}


def _unit(metric: str) -> str:
    if "roundtrips" in metric:
        return "roundtrips/sec"
    if "rows" in metric:
        return "rows/sec"
    if "words" in metric:
        return "words/sec/chip"
    if "steps" in metric:
        return "steps/sec/chip"
    return "tokens/sec/chip" if "tokens" in metric else "samples/sec/chip"


def main() -> None:
    """No argument: run ALL configs and print ONE JSON line with every
    metric + MFU. With a config name: that config only (same line
    shape, single entry). Fails — before running anything — unless the
    default backend is a TPU the peak table knows."""
    global _TRACE_DIR
    args = list(sys.argv[1:])
    for a in list(args):
        if a == "--trace" or a.startswith("--trace="):
            _TRACE_DIR = a.split("=", 1)[1] if "=" in a \
                else "/tmp/dl4j_tpu_trace"
            args.remove(a)
    which = args[0] if args else "all"
    if which != "all" and which not in _CONFIGS:
        sys.exit(f"unknown bench config {which!r}; choose from "
                 f"{sorted(_CONFIGS)} or no arg for all")
    names = list(_CONFIGS) if which == "all" else [which]

    device = _device()
    if device["platform"] != "tpu":
        sys.exit(f"bench.py measures the chip and found none: JAX's "
                 f"default backend is {device['platform']!r} "
                 f"({device['kind']}). A CPU run of these configs is a "
                 "correctness smoke, not a rate — the `slow` tests call "
                 "the bench_* functions directly for that.")
    _peak_flops(device["kind"], bf16=True)  # unknown chip: fail up front
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()

    entries = {}
    for name in names:
        t0 = time.perf_counter()
        metric, value, mfu, spread = _CONFIGS[name]()
        print(f"[bench] {name}: {time.perf_counter() - t0:.0f}s",
              file=sys.stderr, flush=True)
        entries[name] = {
            "metric": metric, "value": round(value, 1),
            "unit": _unit(metric),
            "mfu": None if mfu is None else round(mfu, 4),
            "spread": round(spread, 3),
        }
        # per-config satellite numbers, emitted under their own keys when
        # the bench fn recorded one ((attr, output_key) pairs)
        for attr, key in (
                ("flash_speedup", "flash_speedup_vs_xla_blockwise"),
                ("fused_speedup_vs_scan", "fused_speedup_vs_scan"),
                ("latency_ms", "latency_ms"),
                ("sentinel_overhead_pct", "sentinel_overhead_pct"),
                ("shed_rate_pct", "shed_rate_pct"),
                ("device_ms_per_token", "device_ms_per_token"),
                ("dropout_rng_overhead_pct", "dropout_rng_overhead_pct"),
                ("attention_block512_overhead_pct",
                 "attention_block512_overhead_pct"),
                ("paged_kernel_device_ms_per_token",
                 "paged_kernel_device_ms_per_token"),
                ("paged_gather_device_ms_per_token",
                 "paged_gather_device_ms_per_token"),
                ("paged_kernel_vs_gather", "paged_kernel_vs_gather"),
                ("device_ms_per_word", "device_ms_per_word"),
                ("device_ms", "device_ms"),
                ("wall_samples_per_sec", "wall_samples_per_sec"),
                ("single_rows_per_sec", "single_rows_per_sec"),
                ("pool_vs_single", "pool_vs_single"),
                ("availability_pct", "availability_pct"),
                ("failovers", "failovers"),
                ("wire_drill", "wire_drill"),
                ("slot_occupancy_pct", "slot_occupancy_pct"),
                ("pages_in_use_peak", "pages_in_use_peak"),
                ("pool_pages", "pool_pages"),
                ("prefill_chunks", "prefill_chunks"),
                ("r5_goodput_tokens_per_sec", "r5_goodput_tokens_per_sec"),
                ("r5_latency_ms", "r5_latency_ms"),
                ("paged_vs_r5_goodput", "paged_vs_r5_goodput"),
                ("gqa_goodput_tokens_per_sec",
                 "gqa_goodput_tokens_per_sec"),
                ("shared_prefix_latency_ms", "shared_prefix_latency_ms"),
                ("shared_prefix_base_latency_ms",
                 "shared_prefix_base_latency_ms"),
                ("shared_prefix_goodput_tokens_per_sec",
                 "shared_prefix_goodput_tokens_per_sec"),
                ("shared_prefix_base_goodput_tokens_per_sec",
                 "shared_prefix_base_goodput_tokens_per_sec"),
                ("latency_tier_p50_speedup", "latency_tier_p50_speedup"),
                ("prefix_hit_tokens_pct", "prefix_hit_tokens_pct"),
                ("spec_accept_rate", "spec_accept_rate"),
                ("spec_tokens_per_step", "spec_tokens_per_step"),
                ("int8_kv_device_ms_per_token",
                 "int8_kv_device_ms_per_token"),
                ("bf16_kv_device_ms_per_token",
                 "bf16_kv_device_ms_per_token"),
                ("int8_kv_vs_bf16_device_ms_per_token",
                 "int8_kv_vs_bf16_device_ms_per_token"),
                ("int8_kv_slots_per_chip", "int8_kv_slots_per_chip"),
                ("int8_kv_out_of_pages_sheds",
                 "int8_kv_out_of_pages_sheds"),
                ("int8_kv_goodput_tokens_per_sec",
                 "int8_kv_goodput_tokens_per_sec"),
                ("kv_bytes_per_token", "kv_bytes_per_token"),
                ("tp_degree", "tp_degree"),
                ("tp_goodput_tokens_per_sec", "tp_goodput_tokens_per_sec"),
                ("tp_vs_single_goodput", "tp_vs_single_goodput"),
                ("tp_device_ms_per_token", "tp_device_ms_per_token"),
                ("tp_kv_bytes_per_token_per_shard",
                 "tp_kv_bytes_per_token_per_shard"),
                ("cross_tenant_isolation", "cross_tenant_isolation"),
                ("flood_quota_rejections", "flood_quota_rejections"),
                ("flood_other_errors", "flood_other_errors"),
                ("batch_lane_utilization_pct",
                 "batch_lane_utilization_pct"),
                ("interactive_flooded_latency_ms",
                 "interactive_flooded_latency_ms"),
                ("preemptions", "preemptions"),
                ("autoscale_p99_vs_static", "autoscale_p99_vs_static"),
                ("scale_up_reaction_ms", "scale_up_reaction_ms"),
                ("autoscale_events", "autoscale_events"),
                ("autoscale_failed_requests",
                 "autoscale_failed_requests"),
                ("static_failed_requests", "static_failed_requests"),
                ("static_peak_latency_ms", "static_peak_latency_ms"),
                ("autoscaled_peak_latency_ms",
                 "autoscaled_peak_latency_ms"),
                ("single_model_bytes_per_chip",
                 "single_model_bytes_per_chip"),
                ("tp_max_model_bytes_per_chip",
                 "tp_max_model_bytes_per_chip"),
                ("tp_bytes_per_chip_vs_single",
                 "tp_bytes_per_chip_vs_single"),
                ("disagg_vs_colocated_goodput",
                 "disagg_vs_colocated_goodput"),
                ("kv_transfer_mbytes_per_sec",
                 "kv_transfer_mbytes_per_sec"),
                ("migration_resume_ms", "migration_resume_ms"),
                ("disagg_handoffs", "disagg_handoffs"),
                ("disagg_fallbacks", "disagg_fallbacks"),
                ("dedup_overhead_pct", "dedup_overhead_pct"),
                ("journal_append_latency_ms",
                 "journal_append_latency_ms"),
                ("gateway_crash_recovery_ms",
                 "gateway_crash_recovery_ms"),
                ("crash_requests_lost", "crash_requests_lost"),
                ("crash_double_executions", "crash_double_executions"),
                ("delta_vs_full_handoff_mbytes",
                 "delta_vs_full_handoff_mbytes"),
                ("delta_pages_skipped", "delta_pages_skipped"),
                ("cluster_vs_local_prefix_goodput",
                 "cluster_vs_local_prefix_goodput"),
                ("first_token_ms", "first_token_ms"),
                ("cluster_fetch", "cluster_fetch"),
                ("fetch_vs_reprefill_ms", "fetch_vs_reprefill_ms")):
            extra = getattr(_CONFIGS[name], attr, None)
            if extra is not None:
                entries[name][key] = extra
    from deeplearning4j_tpu.ops.kernel_dispatch import verdicts_as_json

    # "kernels": which Pallas shape classes the run engaged or declined
    print(json.dumps({"device": device, "configs": entries,
                      "kernels": verdicts_as_json()}))


if __name__ == "__main__":
    main()
