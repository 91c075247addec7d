"""Traffic of kind `fit`: the timed window drives `MultiLayerNetwork.fit()`.

A traffic file's `kind` names the runner, `runners/<kind>.py` under any of
`paths`, so a later PR brings another way to drive a net (over a mesh,
through the wire) as a file of its own.

Set-up builds ONE net with its compiled step and its optimizer state,
drives it from the seed through its first three steps (which compile, and
which the reference later follows), and hands that same object to the
window. Every step, in set-up and in the window, goes through the same
`net.fit(...)` call on rows that all differ.
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from perfbench.harness import compare, device, traffic
from perfbench.harness.runrecord import Run

REFERENCE_STEPS = 3


def _flatten_reference(norms: dict, L: int) -> dict:
    out = {k: float(v) for k, v in norms.items() if k != "blocks"}
    for name, per_layer in norms["blocks"].items():
        for i in range(L):
            out[f"blocks.{i}.{name}"] = float(per_layer[i])
    return out


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    cfg, mix, cell = ctx.config, ctx.mix, ctx.cell
    fam = ctx.manifest.family(cfg)
    sz = fam.sizes(cfg)
    seed, V = ctx.seed, sz["V"]
    tokens_per_step = mix["batch"] * mix["seq_len"]

    # the net as a user comes by it: `init()`, then the seed's weights
    # through `set_params()`
    net = fam.build_net(sz, training=True,
                        learning_rate=mix["learning_rate"],
                        remat=mix["remat"])
    t0 = time.perf_counter()
    fam.init_with_weights(net, seed, sz)
    init_s = time.perf_counter() - t0

    def batch(k):
        return DataSet(*traffic.fit_batch(mix, seed, k, V))

    # the first steps: the window's own call, and what is compared
    program = {"losses": []}
    for k in range(REFERENCE_STEPS):
        net.fit(batch(k))
        program["losses"].append(float(net.score_value))
        if k == 0:
            program["grad_norms"] = fam.first_gradient_norms(net, sz)
    t0 = time.perf_counter()
    program["delta_norms"] = fam.change_norms(net, seed, sz)
    readback_s = time.perf_counter() - t0
    step = REFERENCE_STEPS

    def fit_steps(n, first):
        net.fit(ListDataSetIterator([batch(first + i) for i in range(n)]))
        return float(net.score_value)  # the barrier: the last step's loss

    per_call = int(mix["steps_per_call"])
    fit_steps(per_call, step)  # the window's call shape, once, warm
    step += per_call
    at_open = ctx.meter.read()
    setup_s = time.perf_counter() - ctx.t_start

    # ------------------------------------------------------------ window
    t_open = time.perf_counter()
    steps_done, losses, call_s, traced, view = 0, [], [], None, None
    while time.perf_counter() - t_open < ctx.seconds:
        tracing = ctx.trace and traced is None and steps_done >= per_call
        t_call = time.perf_counter()
        if tracing:
            ctx.start_trace()
            with jax.profiler.TraceAnnotation("perfbench.window"):
                t0 = time.perf_counter()
                losses.append(fit_steps(per_call, step))
                traced = {"steps": per_call,
                          "seconds": time.perf_counter() - t0}
            view = ctx.stop_trace()
        else:
            losses.append(fit_steps(per_call, step))
        call_s.append(time.perf_counter() - t_call)
        step += per_call
        steps_done += per_call
    window_s = time.perf_counter() - t_open
    in_window = device.CompileMeter.programs(ctx.meter.read(), at_open)
    peak = device.memory_peak_bytes(ctx.chips)
    failed = sum(1 for l in losses if not np.isfinite(l))

    from deeplearning4j_tpu.ops.kernel_dispatch import kernel_verdicts

    verdicts = {fam_: {str(k): bool(v.ok) for k, v in classes.items()}
                for fam_, classes in kernel_verdicts().items()}

    # free the program's state before the reference takes the chip
    del net
    gc.collect()

    # --------------------------------------------------------- reference
    t_ref = time.perf_counter()
    ref = ctx.manifest.reference(cfg)
    batches = [tuple(jnp.asarray(a) for a in
                     traffic.fit_batch(mix, seed, k, V))
               for k in range(REFERENCE_STEPS)]
    out = ref.train_steps(
        fam.make_weights(seed, sz, "stacked"),
        lambda: fam.make_weights(seed, sz, "stacked"), batches,
        n_heads=sz["H"], eps=sz["eps"], lr=mix["learning_rate"],
        row_block=cell["reference_row_block"],
        precision="float32")
    numbers = numbers_compared(program, out, sz["L"])
    reference_s = time.perf_counter() - t_ref
    control = None
    if ctx.control:
        # the reference in the program's place, one precision down
        low = ref.train_steps(
            fam.make_weights(seed, sz, "stacked"),
            lambda: fam.make_weights(seed, sz, "stacked"), batches,
            n_heads=sz["H"], eps=sz["eps"], lr=mix["learning_rate"],
            row_block=cell["reference_row_block"],
            precision=cfg["precision"]["control"])
        control = numbers_compared(
            {"losses": low["losses"],
             "grad_norms": _flatten_reference(low["grad_norms"], sz["L"]),
             "delta_norms": _flatten_reference(low["delta_norms"], sz["L"])},
            out, sz["L"])
    correct, compared = compare.verdict(numbers, cell["limits"])
    correct = correct and failed == 0

    run_ = Run(workload=ctx.workload["name"], kind="fit", chips=ctx.chips,
               device_kind=ctx.device["kind"], sizes=sz, mix=mix,
               setup_s=setup_s, window_s=window_s, setup_compile=at_open,
               window_programs=in_window,
               facts={"steps": steps_done, "tokens_per_step": tokens_per_step,
                      "kernel_verdicts": verdicts},
               trace=view, traced=traced)
    print(f"perfbench: set-up: init() and set_params() {init_s:.1f} s, "
          f"params() read back and compared {readback_s:.1f} s", flush=True)
    print(f"perfbench: {steps_done} steps in {window_s:.3f} s, the longest "
          f"fit() call {max(call_s):.3f} s against a median of "
          f"{statistics.median(call_s):.3f} s; programs "
          f"compiled or loaded inside the window: {in_window}; reference "
          f"{reference_s:.1f} s; losses {program['losses']} vs "
          f"{out['losses']}", flush=True)
    return {"run": run_, "correct": correct, "compared": compared,
            "attempted": steps_done // per_call, "failed": failed,
            "memory_peak_bytes": peak, "control": control}


def numbers_compared(program: dict, reference: dict, L: int) -> dict:
    """Each step's loss (the first step's apart: the later steps' carry
    the noise of the updates before them), the first gradient's norms
    and the parameters' change, by the worst and by the median leaf,
    program against reference, as the numbers `correct` reads."""
    loss_gaps = [abs(p - r) / abs(r) for p, r in
                 zip(program["losses"], reference["losses"])]
    grad, g_leaf, grad_median = compare.norm_gaps(
        program["grad_norms"],
        _flatten_reference(reference["grad_norms"], L))
    delta, d_leaf, delta_median = compare.norm_gaps(
        program["delta_norms"],
        _flatten_reference(reference["delta_norms"], L))
    print(f"perfbench: worst leaves: gradient {g_leaf}, change {d_leaf}",
          flush=True)
    return {"loss_gap_first": loss_gaps[0], "loss_gap": max(loss_gaps),
            "grad_norm_gap": grad,
            "grad_norm_gap_median": grad_median, "delta_norm_gap": delta,
            "delta_norm_gap_median": delta_median}
