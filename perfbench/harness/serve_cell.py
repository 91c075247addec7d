"""What the runners of `closed` and `open` traffic share: the timed window
drives `ModelServer(net, generation={...}).generate(..., on_token=...)`
in-process.

All warm-up and slot filling is set-up. Every program the window can
dispatch is dispatched once before it; the window opens with every slot
decoding (closed) or after the ramp (open) and closes with requests still
queued or the schedule still running. Token times are the benchmark's own,
taken in `on_token`; spans are taken on the engine's `step_hooks`.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from perfbench.harness import compare, device, traffic
from perfbench.harness.runrecord import Run


class _Req:
    __slots__ = ("index", "prompt", "n_tokens", "due", "submit_t",
                 "token_t", "tokens", "error", "prefill_pre", "done_t",
                 "late_s")

    def __init__(self, index, prompt, n_tokens, due=None):
        self.index, self.prompt, self.n_tokens = index, prompt, int(n_tokens)
        self.due, self.submit_t, self.token_t = due, None, []
        self.tokens = self.error = self.prefill_pre = self.done_t = None
        self.late_s = 0.0


class Recorder:
    """Spans and counts taken from outside the program. `hook` and the
    `on_token` sinks all run on the engine's one scheduler thread."""

    def __init__(self):
        self.decodes = []    # (pre_t, post_t, chunk, active, context_sum)
        self.prefills = []   # (pre_t, post_t, bucket)
        self.live_context = 0
        self._pre_decode = self._pre_prefill = None

    def hook(self, phase: str, info: dict) -> None:
        t = time.perf_counter()
        if phase == "pre_decode":
            self._pre_decode = (t, info["chunk"], info["active"],
                                self.live_context)
        elif phase == "post_decode":
            self.decodes.append((self._pre_decode[0], t)
                                + self._pre_decode[1:])
        elif phase == "pre_prefill":
            self._pre_prefill = (t, info.get("bucket", 0))
        elif phase == "post_prefill":
            self.prefills.append((self._pre_prefill[0], t,
                                  self._pre_prefill[1]))

    def sink(self, req: _Req):
        t0 = len(req.prompt)

        def on_token(cursor, token, entry):
            req.token_t.append(time.perf_counter())
            if cursor == 1:
                req.prefill_pre = self._pre_prefill[0]
                self.live_context += t0 + 1
            else:
                self.live_context += 1
            if cursor >= req.n_tokens:
                self.live_context -= t0 + cursor
        return on_token


def _warm_lengths(buckets, lengths) -> list:
    """One prompt length per prefill program the traffic will use."""
    out = set()
    for n in lengths:
        fits = [b for b in buckets if b >= n]
        out.add(min(fits) if fits else int(max(lengths)))
    return sorted(out)


def run(ctx) -> dict:
    import jax

    from deeplearning4j_tpu.serving.model_server import (
        ModelServer,
        ServerClosedError,
    )

    cfg, mix, cell = ctx.config, ctx.mix, ctx.cell
    fam = ctx.manifest.family(cfg)
    sz = fam.sizes(cfg)
    seed, V, eng = ctx.seed, sz["V"], dict(mix["engine"])
    closed = mix["kind"] == "closed"

    # not through `init()`: it does not fit the chip at the 1.3 B that is
    # served (see `install`), so set-up leaves out what it would cost
    net = fam.build_net(sz, training=False)
    fam.install(net, fam.make_weights(seed, sz))
    rec = Recorder()
    server = ModelServer(net, max_queue=eng.pop("max_queue", 256),
                         generation=dict(eng, step_hooks=[rec.hook]))
    reqs, lock, stop = [], threading.Lock(), threading.Event()

    def serve(req: _Req) -> None:
        req.submit_t = time.perf_counter()
        try:
            req.tokens = np.asarray(server.generate(
                req.prompt, req.n_tokens, on_token=rec.sink(req)))
            req.done_t = time.perf_counter()
        except Exception as e:  # recorded; judged once the run is over
            req.error = e

    # every program the window can dispatch, once: each prefill width the
    # traffic uses, the fused decode chunk and the single decode step
    if closed:
        plan = traffic.closed_plan(mix, seed, cycles=mix["cycles"])
        pairs = np.concatenate([plan["fill"], plan["queue"]])
    else:
        plan = traffic.open_plan(mix, seed, ctx.seconds)
        pairs = plan["pairs"]
    for k, n in enumerate(_warm_lengths(eng["prompt_buckets"], pairs[:, 0])):
        warm = _Req(-1 - k, traffic.prompt_ids(seed, 10**6 + k, n, V),
                    eng.get("decode_chunk", 4) + 2 if k == 0 else 1)
        serve(warm)
        if warm.error is not None:
            raise warm.error

    def request(i: int, due=None) -> _Req:
        req = _Req(i, traffic.prompt_ids(seed, i, pairs[i, 0], V),
                   pairs[i, 1], due)
        reqs.append(req)
        return req

    threads = []
    if closed:
        n_slots, cursor = eng["n_slots"], [0]

        def client():
            while not stop.is_set():
                with lock:
                    i = cursor[0]
                    cursor[0] += 1
                    if i >= len(pairs):
                        return
                    req = request(i)
                serve(req)

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(n_slots + mix["backlog"])]
        for t in threads:
            t.start()
        deadline = time.perf_counter() + 300.0
        while sum(1 for r in list(reqs) if r.index < n_slots
                  and r.token_t) < n_slots:
            if time.perf_counter() > deadline:
                raise RuntimeError("the slots did not fill in 300 s")
            time.sleep(0.02)
        t_open = time.perf_counter()
        t_close = t_open + ctx.seconds
    else:
        t_sched = time.perf_counter() + 0.25

        def dispatcher():
            for i, due in enumerate(plan["due"]):
                wait = t_sched + due - time.perf_counter()
                if wait > 0 and stop.wait(wait):
                    return
                if stop.is_set():
                    return
                with lock:
                    req = request(i, t_sched + due)
                req.late_s = time.perf_counter() - req.due
                t = threading.Thread(target=serve, args=(req,), daemon=True)
                t.start()
                threads.append(t)

        feeder = threading.Thread(target=dispatcher, daemon=True)
        feeder.start()
        t_open = t_sched + plan["window_s"][0]
        t_close = t_sched + plan["window_s"][1]
        time.sleep(max(0.0, t_open - time.perf_counter()))
    at_open = ctx.meter.read()
    setup_s = t_open - ctx.t_start
    before = server.stats()["generation"]

    # ------------------------------------------------------------ window
    traced, view = None, None
    if ctx.trace:
        lead = min(2.0, ctx.seconds / 4)
        time.sleep(max(0.0, t_open + lead - time.perf_counter()))
        ctx.start_trace()
        with jax.profiler.TraceAnnotation("perfbench.window"):
            t0 = time.perf_counter()
            time.sleep(min(mix["trace_s"], ctx.seconds / 2))
            traced = {"t0": t0, "t1": time.perf_counter()}
        view = ctx.stop_trace()
    time.sleep(max(0.0, t_close - time.perf_counter()))
    after = server.stats()["generation"]
    in_window = device.CompileMeter.programs(ctx.meter.read(), at_open)
    if not closed:
        # the load stays on while the window's last requests finish
        lo, hi = plan["window"]
        t_end = t_sched + plan["due"][-1]
        while time.perf_counter() < t_end and any(
                r.done_t is None and r.error is None
                for r in list(reqs) if lo <= r.index < hi):
            time.sleep(0.05)
    peak = device.memory_peak_bytes(ctx.chips)
    stop.set()
    server.shutdown(drain_timeout=0.0)
    if not closed:
        feeder.join(30.0)
    for t in list(threads):
        t.join(30.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("client threads outlived the server's shutdown")

    from deeplearning4j_tpu.ops.kernel_dispatch import kernel_verdicts

    verdicts = {fam_: {str(k): bool(v.ok) for k, v in classes.items()}
                for fam_, classes in kernel_verdicts().items()}
    if closed:
        mine = [r for r in reqs if r.token_t]
        done = [r for r in reqs if r.done_t is not None
                and t_open <= r.done_t <= t_close]
        failed = [r for r in reqs if r.error is not None
                  and r.submit_t < t_close
                  and not isinstance(r.error, ServerClosedError)]
    else:
        lo, hi = plan["window"]
        mine = [r for r in reqs if lo <= r.index < hi]
        done = [r for r in mine if r.done_t is not None]
        failed = [r for r in mine if r.done_t is None]
    facts = {
        "t_open": t_open, "t_close": t_close, "n_slots": eng["n_slots"],
        "decode_chunk": eng.get("decode_chunk", 4),
        "token_times": [t for r in reqs for t in r.token_t],
        "requests": [{"due": r.due, "submit_t": r.submit_t,
                      "prefill_pre": r.prefill_pre, "token_t": r.token_t,
                      "late_s": r.late_s, "done": r.done_t is not None}
                     for r in mine],
        "decodes": rec.decodes, "prefills": rec.prefills,
        "stats_before": before, "stats_after": after,
        "kernel_verdicts": verdicts,
        "late_max_s": max((r.late_s for r in reqs), default=0.0)}

    # free the program's state before the reference takes the chip
    del server, net
    gc.collect()

    # --------------------------------------------------------- reference
    t_ref = time.perf_counter()
    sample = _sample(done, seed, cell["sample_requests"])
    numbers, control = _compare_served(
        ctx, fam, sz, sample, cell, ctx.manifest.reference(cfg))
    reference_s = time.perf_counter() - t_ref
    correct, compared = compare.verdict(numbers, cell["limits"])
    correct = correct and not failed and bool(sample)

    run_ = Run(workload=ctx.workload["name"], kind=mix["kind"],
               chips=ctx.chips, device_kind=ctx.device["kind"], sizes=sz,
               mix=mix, setup_s=setup_s, window_s=t_close - t_open,
               setup_compile=at_open, window_programs=in_window,
               facts=facts, trace=view, traced=traced)
    n_tok = sum(1 for t in facts["token_times"] if t_open <= t < t_close)
    print(f"perfbench: {len(done)} requests finished and {n_tok} tokens "
          f"emitted in the window of {t_close - t_open:.3f} s; programs "
          f"compiled or loaded inside the window: {in_window}; the "
          f"generator ran at most {facts['late_max_s'] * 1e3:.2f} ms late; "
          f"requests queued and in slots at its opening "
          f"{before['queued']}+{before['active_slots']}, at its close "
          f"{after['queued']}+{after['active_slots']}; "
          f"compared {sum(r.n_tokens for r in sample)} served tokens of "
          f"{len(sample)} requests in {reference_s:.1f} s", flush=True)
    return {"run": run_, "correct": correct, "compared": compared,
            "attempted": len(done) + len(failed), "failed": len(failed),
            "memory_peak_bytes": peak, "control": control}


def _sample(done: list, seed: int, n: int) -> list:
    """`n` finished requests drawn from the seed, the longest among
    them."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.index)
    longest = max(done, key=lambda r: len(r.prompt) + r.n_tokens)
    rest = [r for r in done if r is not longest]
    pick = traffic.rng_for(seed, 7).permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in pick]


def _compare_served(ctx, fam, sz, sample, cell, ref) -> tuple:
    """Run the reference once over each sampled prompt with its served
    tokens, and read how far each served token's logit lies below the
    reference's best at its position."""
    import jax
    import jax.numpy as jnp

    if not sample:
        return {"served_gap_max": float("inf")}, None
    w = fam.make_weights(ctx.seed, sz, "stacked")
    pad = int(cell["reference_pad"])

    @jax.jit
    def gaps(logits, tokens):
        best = jnp.max(logits, axis=-1)
        return best - jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]

    served, lower = [], []
    for r in sample:
        t0, n = len(r.prompt), len(r.tokens)
        ids = np.zeros(-(-(t0 + n) // pad) * pad, np.int32)
        ids[:t0], ids[t0:t0 + n - 1] = r.prompt, r.tokens[:-1]
        rows = np.zeros(-(-n // pad) * pad, np.int32)
        rows[:n] = np.arange(t0 - 1, t0 + n - 1)
        toks = np.zeros(len(rows), np.int32)
        toks[:n] = r.tokens
        kw = dict(n_heads=sz["H"], eps=sz["eps"])
        logits = ref.logits_at(w, jnp.asarray(ids)[None], jnp.asarray(rows),
                               precision="float32", **kw)
        served.append(np.asarray(gaps(logits, jnp.asarray(toks)))[:n])
        if ctx.control:
            low = ref.logits_at(w, jnp.asarray(ids)[None], jnp.asarray(rows),
                                precision=ctx.config["precision"]["control"],
                                **kw)
            lower.append(np.asarray(gaps(
                logits, jnp.argmax(low, -1).astype(jnp.int32)))[:n])
            del low
        del logits

    def numbers(parts):
        g = np.concatenate(parts)
        return {"served_gap_max": float(g.max()),
                "served_gap_mean": float(g.mean()),
                "served_miss_share": float((g > 0).mean())}

    return numbers(served), (numbers(lower) if ctx.control else None)
