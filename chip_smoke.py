#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

`python3 chip_smoke.py` drives the train and serve paths once, through
the entry points a user calls, at the full width of the widest model the
repo supports — `gpt_configuration(vocab 256, d_model 1024, 8 heads of
128, 8 layers)`, bf16 compute, random weights from a seed:

- **train**: `MultiLayerNetwork.fit` over a repeated batch at the
  `gpt_long` shape (T=4096, B=8, block 512): finite falling loss, flash
  fwd+bwd kernels engaged.
- **serve**: `GatewayServer` over TCP on localhost with a `ModelServer`
  generation tier behind it (`ReplicaEntryPoint.serve_net`), concurrent
  128-token prompts plus one long prompt that rides chunked prefill,
  donated KV pools; then the same prompts through a gather-path engine
  (token agreement) and a short int8-KV engine. Paged kernel engaged for
  the decode, chunk and int8 shape classes, the in-place KV write for
  the bf16 and int8 pools, and the optimised `decode_step` /
  `decode_chunked` programs hold no copy or layout change of a pool.
- **hybrid**: two composed blocks at granite-4.0-h-small's published
  widths (one Mamba-2, one position-free grouped-query attention, each
  with 8 of 72 top-10 dropless experts held plus the shared expert)
  through `DecodeEngine`: bucketed and chunked prefill, the fused decode
  scan, recurrent state beside paged K/V; tokens against an engine on
  the XLA expert products; the grouped-expert, paged-attention and
  KV-write kernels engaged at its shape classes; no state- or
  pool-shaped copy in the decode programs.
- **linear**: two post-norm blocks at Olmo-Hybrid-7B's published widths
  (one gated delta-rule mixer of 30 heads of 96 x 192, one 30-head full
  attention with QK-norm, each with the 11008-wide gated MLP) through
  `DecodeEngine`, on the benchmark family's seeded weights: served
  tokens against the family's plain float32 reference and against an
  engine on the XLA form of the step; the `gdn_step`, paged-attention
  and KV-write kernels engaged at its shape classes (30 K/V heads); no
  state- or pool-shaped copy in the decode programs.
- **sublayer**: three blocks of ONE sub-layer each at
  NVIDIA-Nemotron-3-Nano-30B-A3B's published widths (a Mamba-2 mixer of
  64 heads in 8 B/C groups, a 32-over-2-head attention with heads of 128
  on a hidden size of 2688, and 64 of 128 sigmoid-routed top-6 ungated
  relu^2 experts 1856 wide plus the shared one) through `DecodeEngine`,
  on the benchmark family's seeded weights: recurrent state, paged K/V
  and a block that keeps nothing side by side; served tokens against the
  family's plain float32 reference and against an engine on the XLA
  expert products; about half the router's choices on the experts held;
  the ungated grouped-expert kernel (a width off the 128-lane grid),
  paged attention and the KV write at 2 K/V heads engaged; no state- or
  pool-shaped copy in the decode programs.
- **latent**: one shortcut layer at LongCat-Flash-Chat's published
  widths (two 64-head latent-attention sub-layers over a 512 + 64 latent,
  two 12288-wide dense FFNs, 16 of 512 real experts 2048 wide and 256
  zero-compute ones on the shortcut, top-12) through `DecodeEngine`, on
  the benchmark family's seeded weights: two pools of latent pages from
  one page table; served tokens against the family's plain float32
  reference and against an engine on the XLA forms (gather-and-attend,
  the scatter); about a third of the router's choices on zero experts;
  the `mla_attend`, `latent_write` and f-tiled grouped-expert kernels
  engaged; no pool-shaped copy in the decode programs and no weight
  re-laid in them (`weight_layout_copies`, all three runs). Then the same
  stage (`latent_h128` in the summary) at DeepSeek-V2's published widths:
  a dense and a routed layer of ONE 128-head latent-attention sub-layer
  each under YaRN, group 0 of 8 groups of 20 experts 1536 wide held,
  3 groups reached, top-6, two shared experts; a bucket of 2,048 tokens,
  so that the prompt goes through the `mla_prefill` kernel and the sorted
  expert product. Then (`latent_kda`) at Ling-3.0-flash's published
  widths: a delta-rule layer with a decay a key channel (32 heads of
  128 x 128) under the dense MLP and a 32-head latent-attention layer with
  full-rank queries and a gate a head under group 0 of 8 groups of 64
  sigmoid-routed experts 768 wide, 4 groups reached, top-8: recurrent
  slots AND latent pages in one net, the `kda_step` kernel engaged, and
  the XLA run on the XLA form of the step too. The blocks of each cache
  kind are counted from the net's mixers (`_blocks_by_state`), here and
  in `linear`, so the one stage serves the three families and a net with
  both kinds passes both.
- **lstm**: `lstm_large` (H=1024, T=64, B=2048) with the fused cell.
- **multichip** (>= 4 devices): the train step through `ParallelWrapper`
  on a {data 2, model 2} mesh against the one-chip loss, tp=4 decode
  against tp=1 tokens, and where params, KV and pool replicas land.

It refuses to run unless JAX's first device is a TPU whose `device_kind`
the repo's tables know; a phase that fails raises, so the exit code is 0
only if every phase passed. One process holds the chip throughout: each
phase frees its state before the next (16 GB of HBM does not hold train,
serve and lstm together). The second-to-last line of stdout is the JSON
summary (versions, per-phase facts, kernel verdicts, compile seconds,
ending `"claim": null`); the last line is the verdict the driver reads,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
with exactly those keys. A failed phase prints the same line with
`"ok": false` and re-raises.

`python3 chip_smoke.py train lstm` runs only the named phases
(`train serve hybrid linear sublayer latent lstm multichip`).
"""
from __future__ import annotations

import collections
import gc
import json
import math
import os
import re
import shutil
import sys
import threading
import time

import numpy as np

from deeplearning4j_tpu.ops.kernel_dispatch import engaged

GPT = dict(vocab_size=256, d_model=1024, n_heads=8, n_layers=8)
TRAIN = dict(T=4096, batch=8, block=512, steps=3)
SERVE = dict(n_slots=8, max_len=4224, page_size=128, prefill_chunk=256,
             n_short=6, short_len=128, long_len=2304, n_tokens=64,
             int8_tokens=16)
HYBRID = dict(vocab_size=256, d_model=4096, layer_types=("mamba", "attention"),
              n_heads=32, n_kv_heads=8, attention_multiplier=0.0078125,
              mamba_heads=128, mamba_head_dim=64, mamba_state=128,
              n_experts=72, top_k=10, expert_width=768, shared_width=1536,
              experts_held=(0, 8), embedding_multiplier=12.0,
              residual_multiplier=0.22, logits_scaling=16.0)
# 64 slots, the benchmark cell's: at 8 the whole recurrent state (34 MB)
# fits the chip's fast memory, XLA parks it there (one same-layout
# `copy-start` a decode program) and the copy count would say nothing
# about a deployment's 268 MB a layer
HYBRID_SERVE = dict(n_slots=64, max_len=1024, page_size=128,
                    prefill_chunk=256, n_short=5, short_len=100,
                    long_len=600, n_tokens=24)
# Olmo-Hybrid-7B's published widths under its config's own keys
# (`perfbench/families/olmo_hybrid.py` reads them), one layer of each kind
LINEAR = dict(vocab_size=256, hidden_size=3840, intermediate_size=11008,
              num_hidden_layers=2,
              layer_types=("linear_attention", "full_attention"),
              num_attention_heads=30, num_key_value_heads=30,
              linear_num_key_heads=30, linear_num_value_heads=30,
              linear_key_head_dim=96, linear_value_head_dim=192,
              linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
              rms_norm_eps=1e-6, tie_word_embeddings=False,
              attention_bias=False)
LINEAR_SERVE = HYBRID_SERVE
# the routed nets' short prompts fill a bucket of 512: the row count from
# which a prefill's experts go sorted (`pallas_moe_experts.sorted_serves`)
ROUTED_SERVE = dict(HYBRID_SERVE, short_len=512, n_short=3)
# NVIDIA-Nemotron-3-Nano-30B-A3B's published widths under its config's
# own keys (`perfbench/families/nemotron_h.py` reads them), one layer of
# each kind, half of the 128 routed experts held as in the benchmark cell
SUBLAYER = dict(vocab_size=256, hidden_size=2688, num_hidden_layers=3,
                hybrid_override_pattern="M*E", num_attention_heads=32,
                num_key_value_heads=2, head_dim=128, mamba_num_heads=64,
                mamba_head_dim=64, ssm_state_size=128, n_groups=8,
                conv_kernel=4, chunk_size=128, n_routed_experts=64,
                num_experts_per_tok=6, moe_intermediate_size=1856,
                moe_shared_expert_intermediate_size=3712,
                routed_scaling_factor=2.5, n_group=1, topk_group=1,
                norm_topk_prob=True, n_shared_experts=1,
                mlp_hidden_act="relu2", layer_norm_epsilon=1e-5,
                norm_eps=1e-5, tie_word_embeddings=False,
                attention_bias=False, mlp_bias=False, use_bias=False,
                mamba_proj_bias=False, use_conv_bias=True,
                deployment=dict(n_routed_experts_published=128,
                                experts_held_first=0))
SUBLAYER_SERVE = ROUTED_SERVE
# LongCat-Flash-Chat's published widths under its config's own keys
# (`perfbench/families/longcat_flash.py` reads them), one layer, 16 of
# the 512 real experts held as in the benchmark cell
LATENT = dict(vocab_size=256, hidden_size=6144, num_layers=1,
              num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
              qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
              mla_scale_q_lora=True, mla_scale_kv_lora=True,
              rope_theta=1e7, ffn_hidden_size=12288,
              expert_ffn_hidden_size=2048, n_routed_experts=16,
              zero_expert_num=256, zero_expert_type="identity",
              moe_topk=12, routed_scaling_factor=6.0, rms_norm_eps=1e-5,
              attention_bias=False, attention_method="MLA",
              deployment=dict(n_routed_experts_published=512,
                              experts_held_first=0))
LATENT_SERVE = ROUTED_SERVE
# DeepSeek-V2's published widths under its config's own keys
# (`perfbench/families/deepseek_v2.py` reads them): the leading dense
# layer and one routed layer, group 0 of the 8 groups of 20 experts held
# as in the benchmark cell, 128 heads, YaRN
LATENT_H128 = dict(vocab_size=256, hidden_size=5120, num_hidden_layers=2,
                   first_k_dense_replace=1, moe_layer_freq=1,
                   hidden_act="silu", intermediate_size=12288,
                   moe_intermediate_size=1536, num_attention_heads=128,
                   num_key_value_heads=128, q_lora_rank=1536,
                   kv_lora_rank=512, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000,
                   rope_scaling=dict(type="yarn", factor=40,
                                     original_max_position_embeddings=4096,
                                     beta_fast=32, beta_slow=1, mscale=0.707,
                                     mscale_all_dim=0.707),
                   n_routed_experts=20, n_shared_experts=2, n_group=8,
                   topk_group=3, num_experts_per_tok=6,
                   topk_method="group_limited_greedy",
                   scoring_func="softmax", norm_topk_prob=False,
                   routed_scaling_factor=16, rms_norm_eps=1e-6,
                   attention_bias=False, tie_word_embeddings=False,
                   deployment=dict(n_routed_experts_published=160,
                                   experts_held_first=0))
# a bucket of 2,048: at 128 heads its prefill is too long for one array of
# scores (the prefill kernel) and its rows go to the experts sorted; the
# long prompt still rides chunks of 256 against a row of 32 pages
LATENT_H128_SERVE = dict(n_slots=64, max_len=4096, page_size=128,
                         prefill_chunk=256, n_short=3, short_len=2048,
                         long_len=2304, n_tokens=24)
# Ling-3.0-flash's published widths under its config's own keys
# (`perfbench/families/ling_flash.py` reads them), a period of two so
# that two layers hold one of each mixer: a KDA layer under the leading
# dense MLP, a gated MLA layer under the routed experts, group 0 of the
# 8 groups of 64 held as in the benchmark cell
LATENT_KDA = dict(vocab_size=256, hidden_size=2560, num_hidden_layers=2,
                  layer_group_size=2, first_k_dense_replace=1,
                  hidden_act="silu", intermediate_size=6144,
                  moe_intermediate_size=768,
                  moe_shared_expert_intermediate_size=768,
                  num_shared_experts=1, num_attention_heads=32,
                  head_dim=128, short_conv_kernel_size=4,
                  kda_lower_bound=-5, kda_safe_gate=True, no_kda_lora=True,
                  linear_silu=True, group_norm_size=1, q_lora_rank=None,
                  kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_theta=6000000,
                  rope_scaling=None, rope_interleave=True,
                  gated_attention_proj_granularity_type="head_wise",
                  num_experts=64, n_group=8, topk_group=4,
                  num_experts_per_tok=8, topk_method="noaux_tc",
                  score_function="sigmoid", norm_topk_prob=True,
                  moe_router_enable_expert_bias=True,
                  routed_scaling_factor=2.5,
                  expert_swiglu_limit_list=[0, 0],
                  share_expert_swiglu_limit_list=[0, 0],
                  rms_norm_eps=1e-6, use_bias=False, use_qkv_bias=False,
                  tie_word_embeddings=False,
                  deployment=dict(num_experts_published=512,
                                  experts_held_first=0))
LATENT_KDA_SERVE = ROUTED_SERVE
# Command A+'s published widths under its config's own keys
# (`perfbench/families/cohere2_moe.py` reads them): one period of three
# sliding-window layers with rotary and one full layer without positions,
# 128 query heads over 8 K/V heads, 8 of the 128 experts held
WINDOW = dict(vocab_size=256, hidden_size=4096, num_hidden_layers=4,
              layer_switch=4,
              layer_types=["sliding_attention"] * 3 + ["full_attention"],
              order_of_interleaved_layers="local_attn_first",
              num_attention_heads=128, num_key_value_heads=8, head_dim=128,
              sliding_window=4096, position_embedding_type="rope_gptj",
              rotary_pct=1, rope_theta=50000, attention_bias=False,
              use_qk_norm=False, use_parallel_block=True,
              hidden_act="silu", use_gated_activation=True,
              intermediate_size=4096, num_experts=8, num_experts_per_tok=8,
              num_shared_experts=4, expert_selection_fn="sigmoid",
              norm_topk_prob=True,
              shared_expert_combination_strategy="average",
              first_k_dense_replace=0, layer_norm_eps=1e-5, logit_scale=1,
              tie_word_embeddings=True,
              deployment=dict(num_experts_published=128,
                              experts_held_first=0))
# a prompt that fills the 4,096 bucket's last page and decodes past 4,096
# + 128 positions, so that its ring of 33 pages wraps; two short ones; and
# one longer than every bucket AND than the ring, in chunks of 128
WINDOW_SERVE = dict(n_slots=16, max_len=4864, page_size=128,
                    prefill_chunk=128, n_short=2, short_len=512,
                    ring_len=4000, long_len=4400, n_tokens=320)
LSTM = dict(vocab=256, hidden=1024, T=64, batch=2048, steps=2)

# A greedy token may differ between two correct attention paths only
# where the model itself is undecided: the log-probability gap between
# the two candidates, read off the net's own forward pass, must be inside
# bf16 noise (8 bits of mantissa on d_model-long sums).
TIE_MARGIN_NATS = 0.1
# how far a served token's logit may lie under the float32 reference's
# best at its position, two bf16 blocks deep (logits are of order 1)
REFERENCE_GAP = 0.1
# the same under routed experts whose output dominates the hidden state:
# bf16 rounding now and then flips one of a token's six experts against
# the float32 reference, which moves a logit by a few tenths (0.25 read
# on the chip, PR 36); a net that computes something else reads 2 to 3
ROUTED_REFERENCE_GAP = 1.0
# bf16 tolerance for one loss computed two ways (1 chip vs the mesh)
LOSS_RTOL = 2e-2


class SmokeFailure(Exception):
    """A phase ran and what came out was wrong."""


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _hbm_in_use(n_devices: int = 1) -> list:
    """Bytes in use on each of the first `n_devices` (0 where the
    backend keeps no count, as on the CPU)."""
    import jax

    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()[:n_devices]]


def _compile_reading(account) -> dict:
    """Backend-compile seconds and persistent-cache hits/misses of this
    process, from the program's own account of JAX's compile pipeline
    (`serving.observability.compile_account`)."""
    c = account.counters()
    return {"compile_seconds": round(c["backend_s"], 2),
            "cache_hits": c["cache_hits"],
            "cache_misses": c["cache_misses"]}


def _host_copies(net) -> int:
    """Leaves of `net._params` that hold a host copy of themselves
    (`np.asarray` of a device array leaves one on the array)."""
    import jax

    return sum(getattr(x, "_npy_value", None) is not None
               for x in jax.tree_util.tree_leaves(net._params))


def _gpt_net(gpt: dict, max_length: int, block: int = 1024):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(
        gpt_configuration(max_length=max_length,
                          attention_block_size=block, **gpt),
        compute_dtype=jnp.bfloat16)
    net.init()
    return net


def _lm_batch(vocab: int, batch: int, T: int):
    from deeplearning4j_tpu.datasets.dataset import DataSet

    ids = np.random.default_rng(0).integers(0, vocab, (batch, T + 1))
    return DataSet(ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32))


def _fit_steps(fit, net, ds, steps: int, vocab: int,
               must_fall: bool = True) -> dict:
    """`steps` calls of `fit(ds)` over the SAME batch: per-step loss and
    wall seconds (the first step's include tracing and compiling). The
    loss must be finite, start near ln(vocab) — random weights predict
    uniformly — and, over an Adam-sized step, fall."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        fit(ds)
        losses.append(float(net.score_value))  # host read = the barrier
        secs.append(round(time.perf_counter() - t0, 3))
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _check(0.8 < losses[0] / np.log(vocab) < 1.25,
           f"first loss {losses[0]} is not near ln({vocab})")
    _check(losses[-1] < losses[0] or not must_fall,
           f"loss did not fall over a repeated batch: {losses}")
    return {"losses": [round(l, 4) for l in losses], "step_seconds": secs}


# ------------------------------------------------------------------ train
def phase_train(gpt: dict, shape: dict, *, kernels: bool) -> dict:
    net = _gpt_net(gpt, shape["T"], shape["block"])
    ds = _lm_batch(gpt["vocab_size"], shape["batch"], shape["T"])
    out = _fit_steps(net.fit, net, ds, shape["steps"], gpt["vocab_size"])
    if kernels:
        hd = gpt["d_model"] // gpt["n_heads"]
        tiles = engaged("flash_attention", lambda k: k[0] == "bfloat16"
                         and k[2] == hd and shape["T"] % k[1] == 0)
        _check(tiles, "the step ran but no flash fwd+bwd tile engaged")
        out["flash_tile"] = max(k[1] for k in tiles)
    return out


# ------------------------------------------------------------------- lstm
def phase_lstm(shape: dict, *, kernels: bool) -> dict:
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.normalizers import OneHotEncoder
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

    vocab, hidden = shape["vocab"], shape["hidden"]
    conf = (NeuralNetConfiguration.Builder()
            .seed(1).learning_rate(0.1).updater(Updater.RMSPROP)
            .list()
            .layer(GravesLSTM(n_in=vocab, n_out=hidden,
                              activation=Activation.TANH))
            .layer(GravesLSTM(n_out=hidden, activation=Activation.TANH))
            .layer(RnnOutputLayer(n_out=vocab, loss=LossFunction.MCXENT,
                                  activation=Activation.SOFTMAX))
            .set_input_type(InputType.recurrent(vocab))
            .build())
    net = MultiLayerNetwork(conf, compute_dtype=jnp.bfloat16)
    net.init()
    net.set_normalizer(OneHotEncoder(vocab))
    ids = np.random.default_rng(0).integers(
        0, vocab, (shape["batch"], shape["T"] + 1))
    ds = DataSet(ids[:, :-1].astype(np.uint8), ids[:, 1:].astype(np.int32))
    # lstm_large's own updater (RMSprop at 0.1) overshoots on its second
    # step at any size: finite and well-scaled is what this phase asks
    out = _fit_steps(net.fit, net, ds, shape["steps"], vocab,
                     must_fall=False)
    if kernels:
        blocks = engaged("fused_lstm", lambda k: k[0] == "bfloat16"
                          and k[2] == hidden and not k[3]
                          and shape["batch"] % k[1] == 0)
        _check(blocks, "the step ran but the fused LSTM cell did not engage")
        out["batch_block"] = max(k[1] for k in blocks)
    return out


# ------------------------------------------------------------------ serve
def _serve_prompts(vocab: int, shape: dict) -> list:
    rng = np.random.default_rng(1)
    lens = [shape["short_len"]] * shape["n_short"] + [shape["long_len"]]
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _engine_kwargs(shape: dict) -> dict:
    return dict(n_slots=shape["n_slots"], max_len=shape["max_len"],
                page_size=shape["page_size"],
                prefill_chunk=shape["prefill_chunk"],
                prompt_buckets=(shape["short_len"],))


def _through_gateway(net, prompts, n_tokens: int, gen: dict):
    """The normal serving entry points in one process: a gateway on real
    TCP, the net installed behind a `ModelServer` generation tier, one
    client connection per concurrent request."""
    from deeplearning4j_tpu.gateway import GatewayClient, GatewayServer
    from deeplearning4j_tpu.serving.remote_replica import ReplicaEntryPoint

    entry = ReplicaEntryPoint(serving={"generation": gen})
    entry.serve_net(net, "gpt")
    gw = GatewayServer(entry_point=entry).start()
    results: list = [None] * len(prompts)

    def one(i: int) -> None:
        # the first request waits out the compiles: a client timeout
        # would RETRY the idempotent generate and double the work
        client = GatewayClient(port=gw.port, timeout=1100.0, max_retries=0)
        try:
            results[i] = client.call("generate", name="gpt",
                                     prompt_ids=prompts[i],
                                     n_tokens=n_tokens)
        except BaseException as e:  # re-raised by the caller below
            results[i] = e
        finally:
            client.close()

    try:
        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(1150.0)
        _check(not any(t.is_alive() for t in threads),
               "generate requests still in flight after 1150 s")
        for r in results:
            if isinstance(r, BaseException):
                raise r
        client = GatewayClient(port=gw.port)
        try:
            stats = client.call("server_stats", name="gpt")["generation"]
        finally:
            client.close()
    finally:
        gw.stop()
    return [np.asarray(r) for r in results], stats


def _through_engine(net, prompts, n_tokens: int, **kw):
    """The same prompts straight through a `DecodeEngine`, all submitted
    at once; returns (tokens, stats)."""
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    engine = DecodeEngine(net, **kw)
    try:
        reqs = [engine.submit(p, n_tokens, timeout=1100.0) for p in prompts]
        toks = [np.asarray(r.result(timeout=1150.0)) for r in reqs]
        return toks, engine.stats()
    finally:
        engine.shutdown(drain_timeout=30.0)


def _check_tokens(toks, n_tokens: int, vocab: int, stats: dict,
                  n_requests: int, who: str) -> None:
    for t in toks:
        _check(t.shape == (n_tokens,) and t.dtype.kind == "i"
               and 0 <= t.min() and t.max() < vocab,
               f"{who}: bad tokens shape={t.shape} dtype={t.dtype}")
    _check(stats["failures"] == 0, f"{who}: {stats['failures']} failures")
    _check(stats["served"] == n_requests,
           f"{who}: served {stats['served']} of {n_requests}")
    _check(stats["pages_in_use"] == 0,
           f"{who}: {stats['pages_in_use']} pages still held after drain")


def _tie_margin(net, prompt, a, b) -> float:
    """Log-probability gap, by the net's own full-context forward pass,
    between the two tokens on which paths `a` and `b` first disagree.
    The context is right-padded to one fixed width (causal attention:
    padding after a position cannot change it), so every call shares
    one compile."""
    i = int(np.argmax(a != b))
    ctx = np.concatenate([prompt, a[:i]])
    width = -(-(len(prompt) + len(a)) // 128) * 128
    ids = np.zeros((1, width), np.int32)
    ids[0, :len(ctx)] = ctx
    probs = np.asarray(net.output(ids), np.float64)[0, len(ctx) - 1]
    return float(abs(np.log(probs[a[i]]) - np.log(probs[b[i]])))


def _agreement(net, prompts, got, ref, who: str) -> dict:
    """How far two greedy token streams agree, request by request, and —
    where they part — whether the model itself was undecided there."""
    prefix = [int(np.argmax(a != b)) if np.any(a != b) else len(a)
              for a, b in zip(got, ref)]
    margins = [round(_tie_margin(net, p, a, b), 4)
               for p, a, b, n in zip(prompts, got, ref, prefix) if n < len(a)]
    _check(all(m < TIE_MARGIN_NATS for m in margins),
           f"{who} disagree where the model is not undecided: "
           f"margins {margins} nats (prefixes {prefix})")
    return {"common_prefix_tokens": prefix, "of": len(got[0]),
            "tie_margins_nats": margins}


_HLO_DTYPES = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}
_LAYOUT_OPCODES = ("copy", "copy-start", "transpose")


def _count_instructions(hlo_text: str, opcodes, shapes) -> int:
    """Instructions of an HLO module, fused computations included, with
    one of `opcodes` and a result of one of `shapes` (HLO strings such
    as "bf16[137,16,128,128]")."""
    line = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(")
    n = 0
    for text in hlo_text.splitlines():
        m = line.match(text)
        if m and m.group(2) in opcodes \
                and any(shape in m.group(1) for shape in shapes):
            n += 1
    return n


def pool_layout_copies(hlo_text: str, pool_shapes) -> int:
    """Instructions of an optimised HLO module whose result has a KV
    pool's shape and whose opcode copies it or changes its layout. A
    decode program that writes its donated pools in place has none;
    each one is a whole pool read and written per step."""
    return _count_instructions(hlo_text, _LAYOUT_OPCODES, pool_shapes)


def weight_converts(hlo_text: str, weight_shapes) -> int:
    """`convert`s of an optimised HLO module, alone or fused into the
    matmul that reads them, whose result is a weight matrix in the
    compute dtype (`weight_shapes`: the served matrices as the programs
    are handed them). A serving program has none: the engine casts its
    weights once, when it is built; each one is a matrix read at the
    master's width, every dispatch."""
    return _count_instructions(hlo_text, ("convert",), weight_shapes)


# what hands an array on as it is, whole or in parts: the operand's
# elements in the result's, nothing computed
_MOVE_OPCODES = _LAYOUT_OPCODES + (
    "reshape", "bitcast", "copy-done", "slice", "slice-start", "slice-done",
    "dynamic-slice", "opt-barrier")
_HLO_ITEMSIZE = {"bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}  # else 4


def _hlo_computations(hlo_text: str) -> tuple:
    """An HLO module's computations, {name: [(result, type, opcode,
    operand text, attributes)]} in the order printed (operands before
    their users), and the entry computation's name."""
    head = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
    line = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
    comps, entry, body = {}, None, None
    for text in hlo_text.splitlines():
        m = head.match(text)
        if m:
            body = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif body is not None and (m := line.match(text)):
            operands, _, attrs = m.group(4).partition(")")
            body.append((*m.group(1, 2, 3), operands, attrs))
    return comps, entry


def _hlo_bytes(typ: str) -> int:
    """The bytes of an HLO type's (first) array."""
    m = re.search(r"(\w+)\[([\d,]*)\]", typ)
    return math.prod(map(int, filter(None, m.group(2).split(",")))) \
        * _HLO_ITEMSIZE.get(m.group(1), 4)


def _is_prefetch(opcode: str, typ: str) -> bool:
    """A `copy-start` into the layout it reads, another memory space
    (`S(1)`) apart: the compiler fetching an array ahead of its use, the
    one read it would cost anyway."""
    layouts = [re.sub(r"S\(\d+\)", "", m) for m in
               re.findall(r"\w+\[[\d,]*\]\{([^}]*)\}", typ)]
    return opcode == "copy-start" and len(layouts) >= 2 \
        and layouts[0] == layouts[1]


def weight_layout_ops(hlo_text: str, weight_shapes) -> list:
    """The instructions of an optimised HLO module that re-lay a weight,
    as "opcode type" strings: a `copy`, `copy-start`, `transpose` or a
    `reshape` left in the module that writes 1 MB or more and whose
    operand IS a weight (an entry parameter of one of `weight_shapes`)
    or comes from one by such moves, slices and bitcasts alone, whatever
    the order or grouping of its dimensions, through fusions and loops.
    A serving program has none: a weight lies as the products read it,
    and what a step turns is its activations. Followed by dataflow, not
    told by size: at 128 slots and heads of 128 the absorbed query (S,
    H, 512) has a (H, 128, 512) matrix's elements exactly."""
    comps, entry = _hlo_computations(hlo_text)
    found = []

    def walk(comp: str, params) -> object:
        """Look through `comp`, whose parameters hold a weight where
        `params` says so (True, or a dict by index for a tuple; None:
        the entry's, told by shape); returns what its root holds."""
        held, root = {}, None
        for name, typ, opcode, operands, attrs in comps[comp]:
            args = [held.get(o) for o in re.findall(r"%([\w.\-]+)", operands)]
            subs = [c for c in re.findall(r"%([\w.\-]+)", attrs) if c in comps]
            root = None
            if opcode == "parameter" and params is None:
                root = any(typ.startswith(w) for w in weight_shapes)
            elif opcode == "parameter":
                root = params[int(operands)] if int(operands) < len(params) \
                    else None
            elif opcode == "tuple":
                root = dict(enumerate(args))
            elif opcode == "get-tuple-element" and isinstance(args[0], dict):
                root = args[0].get(int(re.search(r"index=(\d+)", attrs)[1]))
            elif opcode in _MOVE_OPCODES or "ConcatBitcast" in attrs:
                root = args[0] if args and isinstance(args[0], dict) \
                    else True if True in args else None
                if root is True and _hlo_bytes(typ) >= 1 << 20 \
                        and opcode in (*_LAYOUT_OPCODES, "reshape") \
                        and not _is_prefetch(opcode, typ):
                    found.append(f"{opcode} {typ}")
            elif opcode == "while":  # a weight goes round a loop as it is
                for sub in subs:
                    walk(sub, args[:1])
                root = args[0]
            elif opcode == "conditional":
                for sub, arg in zip(subs, args[1:]):
                    walk(sub, [arg])
            elif opcode in ("fusion", "call") and subs:
                root = walk(subs[0], args)
            held[name] = root
        return root

    if entry:
        walk(entry, None)
    return found


def weight_layout_copies(hlo_text: str, weight_shapes) -> int:
    """How many instructions of an optimised HLO module re-lay a weight
    (`weight_layout_ops`)."""
    return len(weight_layout_ops(hlo_text, weight_shapes))


def route_sorts(hlo_text: str) -> int:
    """`sort` instructions of an optimised HLO module that a routed
    block's choice left there: those traced under `moe.route` (the
    groups' `moe.groups` lies inside it). The routers choose by a mask
    made without one (`parallel.experts.chosen_mask`), so a program
    holds none; a prefill's `sort_by_expert` sorts under `moe.experts`
    and is not counted."""
    line = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .+? sort\(.*"
                      r"op_name=\"[^\"]*moe\.(?:route|groups)[/\"]")
    return sum(1 for text in hlo_text.splitlines() if line.match(text))


def _hlo_shapes(arrays) -> set:
    return {f"{_HLO_DTYPES[a.dtype.name]}[{','.join(map(str, a.shape))}]"
            for a in arrays}


def _decode_program_counts(engine) -> dict:
    """Compile the engine's two decode programs as its scheduler calls
    them and count in each the copies of what the blocks keep between
    tokens (`pool_layout_copies`): K/V pools, their scale pools, and a
    recurrent block's state. Its convolution tail is left out: 3 taps
    a channel, 0.1% of the state's bytes, and XLA moves it to fast
    memory and back each step by choice (same layout, `S(1)`). And the
    casts of a weight matrix (`weight_converts`), the instructions that
    re-lay one (`weight_layout_copies`) and the sorts a router's choice
    left (`route_sorts`)."""
    import jax
    import jax.numpy as jnp

    pools = _hlo_shapes(jax.tree_util.tree_leaves(
        [c[:1] if st.kind == "recurrent" else c
         for st, c in zip(engine._states, engine._caches)]))
    plan = engine._plan
    weights = _hlo_shapes(
        w for i in (plan.emb_i, *plan.block_is)
        for w in jax.tree_util.tree_leaves(engine._weights[i])
        if w.ndim >= 2 and w.dtype == plan.cdt)
    args = (engine._weights, engine._caches, engine._pool.tables,
            engine._tok, engine._pos, engine._keys, engine._temps,
            jnp.asarray(engine._active))
    texts = {name: fn.lower(*args).compile().as_text()
             for name, fn in (("decode_step", engine._decode_step),
                              ("decode_chunked", engine._decode_chunked))}
    return {"pool_layout_copies": {n: pool_layout_copies(t, pools)
                                   for n, t in texts.items()},
            "weight_converts": {n: weight_converts(t, weights)
                                for n, t in texts.items()},
            "weight_layout_copies": {n: weight_layout_copies(t, weights)
                                     for n, t in texts.items()},
            "route_sorts": {n: route_sorts(t) for n, t in texts.items()}}


def phase_serve(gpt: dict, shape: dict, *, kernels: bool) -> dict:
    vocab, n_tokens = gpt["vocab_size"], shape["n_tokens"]
    net = _gpt_net(gpt, shape["max_len"])
    prompts = _serve_prompts(vocab, shape)
    n_chunks = -(-shape["long_len"] // shape["prefill_chunk"])
    dispatches = collections.Counter()

    def count(phase: str, info: dict) -> None:
        if phase == "pre_decode":
            dispatches["decode_chunk" if info["chunk"] > 1
                       else "decode_step"] += 1
        elif phase == "pre_prefill":
            dispatches["prefill"] += 1

    gen = _engine_kwargs(shape)
    toks, stats = _through_gateway(net, prompts, n_tokens,
                                   dict(gen, step_hooks=[count]))
    _check_tokens(toks, n_tokens, vocab, stats, len(prompts), "gateway")
    _check(stats["prefill_chunks"] >= n_chunks,
           f"long prompt did not ride chunked prefill: "
           f"{stats['prefill_chunks']} chunks < {n_chunks}")
    _check(dispatches["decode_chunk"] > 0 and dispatches["prefill"] > 0,
           f"fused decode_chunk scan never dispatched: {dict(dispatches)}")
    out = {"requests": len(prompts), "tokens": int(sum(map(len, toks))),
           "prefill_chunks": stats["prefill_chunks"],
           "decode_steps": stats["decode_steps"],
           "dispatches": dict(dispatches),
           # pages the attend kernel walked, of the page table's width
           "kv_pages": [stats["loop"]["kv_pages_walked"],
                        stats["loop"]["kv_pages_table"]]}
    _check(0 < out["kv_pages"][0] <= out["kv_pages"][1],
           f"decode steps walked {out['kv_pages'][0]} KV pages of "
           f"{out['kv_pages'][1]} table entries")
    gc.collect()  # free the device state just dropped

    # the same prompts down the gather path — the XLA reference the
    # kernel's probe is checked against — in a fresh engine (the dispatch
    # is traced into each engine's jit closures, so the switch is read
    # at build time)
    os.environ["DL4J_TPU_NO_PALLAS_PAGED_ATTENTION"] = "1"
    try:
        ref, ref_stats = _through_engine(net, prompts, n_tokens, **gen)
    finally:
        del os.environ["DL4J_TPU_NO_PALLAS_PAGED_ATTENTION"]
    _check_tokens(ref, n_tokens, vocab, ref_stats, len(prompts), "gather")
    out["agreement"] = _agreement(net, prompts, toks, ref,
                                  "kernel and gather paths")
    gc.collect()  # free the device state just dropped

    # int8 KV: one short and the long prompt, so the quantized decode
    # AND chunk classes dispatch
    q_prompts = [prompts[0], prompts[-1]]
    q_toks, q_stats = _through_engine(net, q_prompts, shape["int8_tokens"],
                                      quantize={"kv": "int8"}, **gen)
    _check_tokens(q_toks, shape["int8_tokens"], vocab, q_stats,
                  len(q_prompts), "int8")
    _check(q_stats["kv_quant_bits"] == 8, "int8 engine built bf16 pools")
    out["int8"] = {"requests": len(q_prompts),
                   "first_token_matches_bf16": [
                       bool(q[0] == t[0]) for q, t in
                       zip(q_toks, (toks[0], toks[-1]))]}

    # the decode programs themselves: a pool copied or re-laid-out per
    # step is half the step's device time (PERF.md, PR 26)
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    host_copies = _host_copies(net)
    engine = DecodeEngine(net, **gen)
    try:
        out.update(_decode_program_counts(engine))
        built = engine.stats()
        out["weight_version"] = engine._weight_version
    finally:
        engine.shutdown(drain_timeout=30.0)
    # the digest of the served weights is folded where they lie: a few
    # words a leaf reach the host and no leaf gains a host copy, and the
    # integers are the CPU's (PERF.md, PR 39)
    import jax

    from deeplearning4j_tpu.serving import weight_digest

    n_leaves = len(jax.tree_util.tree_leaves(net._params))
    out["weight_hash"] = {
        "s": round(built["build"]["build.weight_hash_s"], 4),
        "bytes": built["build"]["weight_hash_bytes"],
        "host_bytes": built["build"]["weight_hash_host_bytes"],
        "golden": weight_digest.weight_version(jax.tree_util.tree_leaves(
            weight_digest.known_answer_tree()))[0]}
    print(f"serve: weight version {out['weight_version']}, "
          f"build.weight_hash_s {out['weight_hash']['s']} for "
          f"{out['weight_hash']['bytes']} bytes in {n_leaves} leaves, "
          f"{out['weight_hash']['host_bytes']} of them to the host; the "
          f"golden tree reads {out['weight_hash']['golden']}", flush=True)
    _check(out["weight_hash"]["golden"] == weight_digest.KNOWN_ANSWER,
           f"the golden tree's digest is {out['weight_hash']['golden']} "
           f"here and {weight_digest.KNOWN_ANSWER} on the CPU")
    gained = _host_copies(net) - host_copies
    _check(0 < out["weight_hash"]["host_bytes"] <= 64 * n_leaves
           and not gained,
           f"the weight hash took {out['weight_hash']['host_bytes']} bytes "
           f"of {n_leaves} leaves to the host and left {gained} host "
           f"copies behind")
    # f32 masters, bf16 compute: cast once when the engine is built,
    # and by no decode program (PERF.md, PR 29)
    out["weight_casts"] = built["weight_casts"]
    out["weights_resident_bytes"] = built["weights_resident_bytes"]
    print(f"serve: pool copies in the decode programs "
          f"{out['pool_layout_copies']}, weight casts "
          f"{out['weight_converts']}, cast when built "
          f"{out['weight_casts']} ({out['weights_resident_bytes']} bytes)",
          flush=True)
    _check(out["weight_casts"] == 1 and out["weights_resident_bytes"] > 0
           and not any(out["weight_converts"].values()),
           f"the decode programs cast their weights: "
           f"{out['weight_converts']} converts, {out['weight_casts']} "
           f"cast(s) when built")

    if kernels:
        H = gpt["n_heads"]
        hd = gpt["d_model"] // H
        for C, kind in ((1, "dense"), (shape["prefill_chunk"], "dense"),
                        (1, "int8"), (shape["prefill_chunk"], "int8")):
            key = ("bfloat16", C, H, H, hd, shape["page_size"], kind)
            _check(engaged("paged_attention", lambda k: k == key),
                   f"paged kernel did not engage for shape class {key}")
        out["paged_classes"] = len(engaged("paged_attention"))
        for key in (("bfloat16", H, hd, shape["page_size"], "dense"),
                    ("int8", H, hd, shape["page_size"], "int8")):
            _check(engaged("paged_kv_write", lambda k: k == key),
                   f"in-place KV write did not engage for {key}")
        _check(not any(out["pool_layout_copies"].values()),
               f"the decode programs copy their KV pools: "
               f"{out['pool_layout_copies']}")
    return out


def _blocks_by_state(net) -> collections.Counter:
    """The net's mixers by the cache state each declares (`"kv"`,
    `"recurrent"`, `"latent"`): what the engine's block counts must
    say, whatever the family puts in a layer."""
    return collections.Counter(
        mixer.state for layer in net.layers if hasattr(layer, "mixers")
        for mixer in layer.mixers())


def _check_recurrent_run(stats: dict, shape: dict, n_prompts: int) -> None:
    """The long prompt rode chunked prefill, and every admission
    overwrote its slot's recurrent state."""
    n_chunks = -(-shape["long_len"] // shape["prefill_chunk"])
    _check(stats["prefill_chunks"] >= n_chunks,
           f"long prompt did not ride chunked prefill: "
           f"{stats['prefill_chunks']} chunks < {n_chunks}")
    _check(stats["state_resets"] == n_prompts,
           f"{stats['state_resets']} slot states reset for "
           f"{n_prompts} admissions")


def _hybrid_net(hyb: dict, dtype):
    """Composed blocks from `hybrid_moe_configuration`, `init()`'s own
    weights, the embedding scaled down so that a token's own logit does
    not decide every step (tied head, multiplier 12)."""
    from deeplearning4j_tpu.models.transformer import (
        hybrid_moe_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    hyb = dict(hyb)
    net = MultiLayerNetwork(hybrid_moe_configuration(
        hyb.pop("vocab_size"), hyb.pop("d_model"), hyb.pop("layer_types"),
        **hyb), dtype=dtype)
    net.init()
    net._params[0]["W"] = net._params[0]["W"] * 0.1
    return net


def _experts_read(stats: dict, phase: str) -> dict:
    """The held experts some live slot chose over the decode steps, and
    those the grouped product was told to read: one number."""
    hit, read = stats["moe_experts_hit"], stats["moe_experts_read"]
    print(f"{phase}: moe_experts_hit {hit} moe_experts_read {read} of "
          f"{stats['moe_steps'] * stats['moe_experts_held']} held",
          flush=True)
    _check(read == hit, f"the grouped product was told to read {read} "
                        f"held experts where live slots chose {hit}")
    return {"moe_experts_hit": hit, "moe_experts_read": read}


def _sorted_prefills(net, shape: dict, stats: dict) -> dict:
    """Where the rule over shapes (`pallas_moe_experts.sorted_serves`)
    sends the bucket's rows to the sorted expert product, its kernel's
    verdict at the net's shape class is a pass and the engine counted
    every bucketed prefill as sorted; where it does not, none."""
    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme
    from deeplearning4j_tpu.serving.block_state import routed_ffns

    ffn = routed_ffns(GPTPlan(net))[0]
    bucket, d = shape["short_len"], net.layers[1].n_out
    rule = pme.sorted_serves(bucket, ffn.held[1], ffn.top_k,
                             ffn.n_experts + ffn.n_zero_experts)
    key = pme.sorted_key("bfloat16", d, ffn.expert_width, ffn.activation)
    counted = stats["loop"]["prefill_sorted_n"]
    if rule:
        _check(engaged("moe_experts", lambda k: k == key),
               f"sorted expert kernel did not engage for {key}")
        _check(counted >= shape["n_short"],
               f"{counted} prefill dispatches counted as sorted of the "
               f"{shape['n_short']} prompts in the {bucket}-token bucket")
    else:
        _check(not counted, f"{counted} prefill dispatches counted as "
                            "sorted where the rule keeps the walk")
    return {"sorted_rule": rule, "prefill_sorted_n": counted}


def phase_hybrid(hyb: dict, shape: dict, *, kernels: bool,
                 dtype=None) -> dict:
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    vocab, n_tokens = hyb["vocab_size"], shape["n_tokens"]
    net = _hybrid_net(hyb, dtype or jnp.bfloat16)
    prompts = _serve_prompts(vocab, shape)
    gen = _engine_kwargs(shape)
    toks, stats = _through_engine(net, prompts, n_tokens, **gen)
    _check_tokens(toks, n_tokens, vocab, stats, len(prompts), "hybrid")
    _check_recurrent_run(stats, shape, len(prompts))
    share = stats["moe_held_choices"] / max(1, stats["moe_routed"])
    held = hyb["experts_held"][1] / hyb["n_experts"]
    _check(0.5 * held < share < 2.0 * held,
           f"{share:.3f} of the router's choices fell on the "
           f"{held:.3f} of the experts held")
    out = {"requests": len(prompts), "tokens": int(sum(map(len, toks))),
           "prefill_chunks": stats["prefill_chunks"],
           "decode_steps": stats["decode_steps"],
           "state_bytes_per_slot": stats["state_bytes_per_slot"],
           "held_share_of_choices": round(share, 4),
           **_experts_read(stats, "hybrid")}
    gc.collect()

    # the same prompts with the experts as XLA batched products
    os.environ["DL4J_TPU_NO_PALLAS_MOE_EXPERTS"] = "1"
    try:
        ref, ref_stats = _through_engine(net, prompts, n_tokens, **gen)
    finally:
        del os.environ["DL4J_TPU_NO_PALLAS_MOE_EXPERTS"]
    _check_tokens(ref, n_tokens, vocab, ref_stats, len(prompts), "xla-moe")
    out["agreement"] = _agreement(net, prompts, toks, ref,
                                  "kernel and XLA expert products")
    gc.collect()

    engine = DecodeEngine(net, **gen)
    try:
        out.update(_decode_program_counts(engine))
    finally:
        engine.shutdown(drain_timeout=30.0)
    print(f"hybrid: state and pool copies in the decode programs "
          f"{out['pool_layout_copies']}", flush=True)
    if kernels:
        d, f = hyb["d_model"], hyb["expert_width"]
        for rows in (shape["n_slots"], shape["prefill_chunk"]):
            key = ("bfloat16", rows, d, f)
            _check(engaged("moe_experts", lambda k: k == key),
                   f"grouped expert kernel did not engage for {key}")
        out.update(_sorted_prefills(net, shape, stats))
        H, Hkv = hyb["n_heads"], hyb["n_kv_heads"]
        key = ("bfloat16", 1, H, Hkv, d // H, shape["page_size"], "dense")
        _check(engaged("paged_attention", lambda k: k == key),
               f"paged kernel did not engage for shape class {key}")
        key = ("bfloat16", Hkv, d // H, shape["page_size"], "dense")
        _check(engaged("paged_kv_write", lambda k: k == key),
               f"in-place KV write did not engage for {key}")
        _check(not any(out["pool_layout_copies"].values()),
               f"the decode programs copy their state or pools: "
               f"{out['pool_layout_copies']}")
    return out


def _reference_gaps(fam, ref, cfg, sz, weights, prompts, toks) -> list:
    """For each request, the most by which a served token's logit lies
    under the plain reference's best logit at its position (0: every
    served token is the reference's own argmax)."""
    import jax.numpy as jnp

    c = ref.consts_from_config(cfg)
    gaps = []
    for prompt, served in zip(prompts, toks):
        ids = np.concatenate([prompt, served[:-1]])
        rows = np.arange(len(prompt) - 1, len(ids))
        logits = np.asarray(ref.logits_at(
            weights, jnp.asarray(ids)[None], jnp.asarray(rows), c=c,
            n_heads=sz["H"], eps=sz["eps"]))
        gaps.append(float(np.max(
            logits.max(-1) - logits[np.arange(len(served)), served])))
    return gaps


def phase_linear(lin: dict, shape: dict, *, kernels: bool,
                 dtype=None) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
    from perfbench.families import olmo_hybrid as fam
    from perfbench.families import olmo_hybrid_reference as ref

    dtype = dtype or jnp.bfloat16
    vocab, n_tokens = lin["vocab_size"], shape["n_tokens"]
    sz = fam.sizes(lin)
    weights = fam.make_weights(0, sz)
    net = fam.build_net(sz, training=False, dtype=dtype)
    fam.install(net, jax.tree.map(lambda a: a.astype(dtype), weights))
    prompts = _serve_prompts(vocab, shape)
    gen = _engine_kwargs(shape)
    toks, stats = _through_engine(net, prompts, n_tokens, **gen)
    _check_tokens(toks, n_tokens, vocab, stats, len(prompts), "linear")
    _check_recurrent_run(stats, shape, len(prompts))
    blocks = _blocks_by_state(net)
    _check(stats["recurrent_blocks"] == blocks["recurrent"] > 0
           and stats["kv_blocks"] == blocks["kv"],
           f"blocks by cache kind: {stats['recurrent_blocks']} recurrent, "
           f"{stats['kv_blocks']} K/V of the net's {dict(blocks)}")
    out = {"requests": len(prompts), "tokens": int(sum(map(len, toks))),
           "prefill_chunks": stats["prefill_chunks"],
           "decode_steps": stats["decode_steps"],
           "state_bytes_per_slot": stats["state_bytes_per_slot"],
           "kv_bytes_per_token": stats["kv_bytes_per_token"]}
    gc.collect()

    # a bucketed and the chunked prompt against the plain reference's
    # full forward: prefill, then decode through both caches
    picked = (0, len(prompts) - 1)
    out["reference_gaps"] = [round(g, 5) for g in _reference_gaps(
        fam, ref, lin, sz, weights, [prompts[i] for i in picked],
        [toks[i] for i in picked])]
    _check(max(out["reference_gaps"]) < REFERENCE_GAP,
           f"served tokens lie {out['reference_gaps']} under the "
           f"reference's best logit")

    # the same prompts with the step as XLA's elementwise form
    os.environ["DL4J_TPU_NO_PALLAS_GDN_STEP"] = "1"
    try:
        xla, xla_stats = _through_engine(net, prompts, n_tokens, **gen)
    finally:
        del os.environ["DL4J_TPU_NO_PALLAS_GDN_STEP"]
    _check_tokens(xla, n_tokens, vocab, xla_stats, len(prompts), "xla-step")
    out["agreement"] = _agreement(net, prompts, toks, xla,
                                  "kernel and XLA delta-rule steps")
    gc.collect()

    engine = DecodeEngine(net, **gen)
    try:
        out.update(_decode_program_counts(engine))
    finally:
        engine.shutdown(drain_timeout=30.0)
    print(f"linear: state and pool copies in the decode programs "
          f"{out['pool_layout_copies']}", flush=True)
    if kernels:
        key = ("bfloat16", sz["lh"], sz["lk"], sz["lv"])
        _check(engaged("gdn_step", lambda k: k == key),
               f"gated delta step kernel did not engage for {key}")
        H, hd = sz["H"], sz["hd"]
        key = ("bfloat16", 1, H, H, hd, shape["page_size"], "dense")
        _check(engaged("paged_attention", lambda k: k == key),
               f"paged kernel did not engage for shape class {key}")
        key = ("bfloat16", H, hd, shape["page_size"], "dense")
        _check(engaged("paged_kv_write", lambda k: k == key),
               f"in-place KV write did not engage for {key}")
        _check(not any(out["pool_layout_copies"].values()),
               f"the decode programs copy their state or pools: "
               f"{out['pool_layout_copies']}")
    return out


def phase_sublayer(sub: dict, shape: dict, *, kernels: bool,
                   dtype=None) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
    from perfbench.families import nemotron_h as fam
    from perfbench.families import nemotron_h_reference as ref

    dtype = dtype or jnp.bfloat16
    vocab, n_tokens = sub["vocab_size"], shape["n_tokens"]
    sz = fam.sizes(sub)
    weights = fam.make_weights(0, sz)
    net = fam.build_net(sz, training=False, dtype=dtype)
    fam.install(net, jax.tree.map(
        lambda a: a if a.dtype == jnp.float32 else a.astype(dtype), weights))
    prompts = _serve_prompts(vocab, shape)
    gen = _engine_kwargs(shape)
    toks, stats = _through_engine(net, prompts, n_tokens, **gen)
    _check_tokens(toks, n_tokens, vocab, stats, len(prompts), "sublayer")
    _check_recurrent_run(stats, shape, len(prompts))
    by_kind = [sz["pattern"].count(c) for c in (fam.MAMBA, fam.ATTENTION,
                                                fam.EXPERTS)]
    got = [stats[k] for k in ("recurrent_blocks", "kv_blocks",
                              "stateless_blocks")]
    _check(got == by_kind, f"blocks by cache kind (recurrent, K/V, none): "
                           f"{got} of {sz['pattern']!r}")
    share = stats["moe_held_choices"] / max(1, stats["moe_routed"])
    held = sz["held"][1] / sz["E"]
    _check(0.5 * held < share < min(1.0, 2.0 * held) + 1e-9,
           f"{share:.3f} of the router's choices fell on the "
           f"{held:.3f} of the experts held")
    out = {"requests": len(prompts), "tokens": int(sum(map(len, toks))),
           "prefill_chunks": stats["prefill_chunks"],
           "decode_steps": stats["decode_steps"],
           "state_bytes_per_slot": stats["state_bytes_per_slot"],
           "kv_bytes_per_token": stats["kv_bytes_per_token"],
           "stateless_blocks": stats["stateless_blocks"],
           "held_share_of_choices": round(share, 4),
           **_experts_read(stats, "sublayer")}
    gc.collect()

    # a bucketed and the chunked prompt against the plain reference's
    # full forward: prefill, then decode through state, pages and the
    # block that keeps neither
    picked = (0, len(prompts) - 1)
    out["reference_gaps"] = [round(g, 5) for g in _reference_gaps(
        fam, ref, sub, sz, weights, [prompts[i] for i in picked],
        [toks[i] for i in picked])]
    _check(max(out["reference_gaps"]) < ROUTED_REFERENCE_GAP,
           f"served tokens lie {out['reference_gaps']} under the "
           f"reference's best logit")

    # the same prompts with the experts as XLA batched products
    os.environ["DL4J_TPU_NO_PALLAS_MOE_EXPERTS"] = "1"
    try:
        xla, xla_stats = _through_engine(net, prompts, n_tokens, **gen)
    finally:
        del os.environ["DL4J_TPU_NO_PALLAS_MOE_EXPERTS"]
    _check_tokens(xla, n_tokens, vocab, xla_stats, len(prompts), "xla-moe")
    out["agreement"] = _agreement(net, prompts, toks, xla,
                                  "kernel and XLA expert products")
    gc.collect()

    engine = DecodeEngine(net, **gen)
    try:
        out.update(_decode_program_counts(engine))
    finally:
        engine.shutdown(drain_timeout=30.0)
    print(f"sublayer: state and pool copies in the decode programs "
          f"{out['pool_layout_copies']}", flush=True)
    if kernels:
        for rows in (shape["n_slots"], shape["prefill_chunk"]):
            key = ("bfloat16", rows, sz["d"], sz["f"], "relu2")
            _check(engaged("moe_experts", lambda k: k == key),
                   f"ungated grouped expert kernel did not engage for "
                   f"{key}")
        out.update(_sorted_prefills(net, shape, stats))
        H, Hkv, hd = sz["H"], sz["Hkv"], sz["hd"]
        key = ("bfloat16", 1, H, Hkv, hd, shape["page_size"], "dense")
        _check(engaged("paged_attention", lambda k: k == key),
               f"paged kernel did not engage for shape class {key}")
        key = ("bfloat16", Hkv, hd, shape["page_size"], "dense")
        _check(engaged("paged_kv_write", lambda k: k == key),
               f"in-place KV write did not engage for {key}")
        _check(not any(out["pool_layout_copies"].values()),
               f"the decode programs copy their state or pools: "
               f"{out['pool_layout_copies']}")
    return out


def phase_latent(lat: dict, shape: dict, *, kernels: bool,
                 dtype=None, family: str = "longcat_flash") -> dict:
    """A net whose mixers are latent attention, of the benchmark's
    `family` (`longcat_flash`: two sub-layers a layer and zero-compute
    experts; `deepseek_v2`: one, under YaRN, behind device-limited
    routing; `ling_flash`: one layer of it beside a delta-rule layer
    with a decay a key channel, recurrent slots and latent pages in one
    net), through the engine, against the family's reference and
    against its own XLA forms."""
    import importlib

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_delta_step, pallas_mla_attend
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

    fam = importlib.import_module(f"perfbench.families.{family}")
    ref = importlib.import_module(f"perfbench.families.{family}_reference")
    dtype = dtype or jnp.bfloat16
    vocab, n_tokens = lat["vocab_size"], shape["n_tokens"]
    sz = fam.sizes(lat)
    weights = fam.make_weights(0, sz)
    net = fam.build_net(sz, training=False, dtype=dtype)
    fam.install(net, jax.tree.map(
        lambda a: a if a.dtype == jnp.float32 else a.astype(dtype), weights))
    prompts = _serve_prompts(vocab, shape)
    gen = _engine_kwargs(shape)
    toks, stats = _through_engine(net, prompts, n_tokens, **gen)
    _check_tokens(toks, n_tokens, vocab, stats, len(prompts), "latent")
    n_chunks = -(-shape["long_len"] // shape["prefill_chunk"])
    _check(stats["prefill_chunks"] >= n_chunks,
           f"long prompt did not ride chunked prefill: "
           f"{stats['prefill_chunks']} chunks < {n_chunks}")
    # one pool a latent mixer and one set of slot arrays a recurrent
    # one, whatever the family puts in a layer
    blocks = _blocks_by_state(net)
    _check(stats["latent_blocks"] == blocks["latent"] > 0
           and stats["recurrent_blocks"] == blocks["recurrent"],
           f"blocks by cache kind: {stats['latent_blocks']} latent, "
           f"{stats['recurrent_blocks']} recurrent of the net's "
           f"{dict(blocks)}")
    if blocks["recurrent"]:
        _check_recurrent_run(stats, shape, len(prompts))
    routed = max(1, stats["moe_routed"])
    zero = stats["moe_zero_choices"] / routed
    want = sz.get("Z", 0) / (sz["E"] + sz.get("Z", 0))
    _check(0.5 * want < zero < min(1.0, 2.0 * want) if want else not zero,
           f"{zero:.3f} of the router's choices fell on the {want:.3f} "
           f"of its outputs that are zero experts")
    rows_local = stats["moe_rows_local"] * sz["topk"] / routed
    _check(stats["moe_held_choices"] / routed <= rows_local <= 1.0,
           f"{rows_local:.3f} of the routed rows chose a held expert, "
           f"under the held share of the choices")
    out = {"requests": len(prompts), "tokens": int(sum(map(len, toks))),
           "prefill_chunks": stats["prefill_chunks"],
           "decode_steps": stats["decode_steps"],
           "latent_blocks": stats["latent_blocks"],
           "latent_bytes_per_token": stats["latent_bytes_per_token"],
           "recurrent_blocks": stats["recurrent_blocks"],
           "state_bytes_per_slot": stats["state_bytes_per_slot"],
           "zero_share_of_choices": round(zero, 4),
           "held_share_of_choices": round(
               stats["moe_held_choices"] / routed, 4),
           "rows_local_share": round(rows_local, 4),
           **_experts_read(stats, "latent")}
    gc.collect()

    # a bucketed and the chunked prompt against the plain reference's
    # full forward: expanded prefill, attention over cached latents in
    # the chunks, then the absorbed step through both pools
    picked = (0, len(prompts) - 1)
    out["reference_gaps"] = [round(g, 5) for g in _reference_gaps(
        fam, ref, lat, sz, weights, [prompts[i] for i in picked],
        [toks[i] for i in picked])]
    _check(max(out["reference_gaps"]) < ROUTED_REFERENCE_GAP,
           f"served tokens lie {out['reference_gaps']} under the "
           f"reference's best logit")

    # the same prompts on gather-and-attend and the scatter (and, where
    # the net has a delta-rule layer, on the XLA form of its step)
    off = ("DL4J_TPU_NO_PALLAS_MLA_ATTEND", "DL4J_TPU_NO_PALLAS_GDN_STEP")
    os.environ.update(dict.fromkeys(off, "1"))
    try:
        xla, xla_stats = _through_engine(net, prompts, n_tokens, **gen)
    finally:
        for name in off:
            del os.environ[name]
    _check_tokens(xla, n_tokens, vocab, xla_stats, len(prompts), "xla-mla")
    out["agreement"] = _agreement(net, prompts, toks, xla,
                                  "kernel and XLA latent attention")
    gc.collect()

    engine = DecodeEngine(net, **gen)
    try:
        out.update(_decode_program_counts(engine))
    finally:
        engine.shutdown(drain_timeout=30.0)
    print(f"latent: pool copies in the decode programs "
          f"{out['pool_layout_copies']}, weights re-laid "
          f"{out['weight_layout_copies']}", flush=True)
    if kernels:
        R, page = sz["kr"] + sz["rope"], shape["page_size"]
        key = pallas_mla_attend.attend_key(jnp.bfloat16, sz["H"], R,
                                           sz["kr"], page)
        _check(engaged("mla_attend", lambda k: k == key),
               f"paged latent attention did not engage for {key}")
        key = ("bfloat16", R, page)
        _check(engaged("latent_write", lambda k: k == key),
               f"in-place latent write did not engage for {key}")
        for rows in (shape["n_slots"], shape["prefill_chunk"]):
            key = ("bfloat16", rows, sz["d"], sz["f"])
            _check(engaged("moe_experts", lambda k: k == key),
                   f"grouped expert kernel did not engage for {key}")
        if blocks["recurrent"]:
            key = pallas_delta_step.step_key(jnp.bfloat16, sz["lh"],
                                             sz["lk"], sz["lv"], True)
            _check(engaged("kda_step", lambda k: k == key),
                   f"channel-gated delta step kernel did not engage for "
                   f"{key}")
        out.update(_sorted_prefills(net, shape, stats))
        bucket = shape["short_len"]
        mixer = next(m for layer in net.layers if hasattr(layer, "mixers")
                     for m in layer.mixers() if m.state == "latent")
        if mixer.query_block(bucket) < bucket:
            _check(engaged("mla_prefill", lambda k: True),
                   f"the prefill kernel did not serve the {bucket}-token "
                   "prompt")
        _check(not any(out["pool_layout_copies"].values()),
               f"the decode programs copy their state or pools: "
               f"{out['pool_layout_copies']}")
        _check(not any(out["weight_layout_copies"].values()),
               f"the decode programs re-lay their weights: "
               f"{out['weight_layout_copies']}")
        _check(not any(out["route_sorts"].values()),
               f"the decode programs sort for their routers: "
               f"{out['route_sorts']}")
    return out


def phase_window(win: dict, shape: dict, *, kernels: bool,
                 dtype=None) -> dict:
    """A net of window layers with rotary and full layers without
    positions (`cohere2_moe`), through the engine: a slot decodes past
    its window and a page, so that its ring wraps; a prompt longer than
    the ring rides chunks; against the family's reference and against
    the dense paths (gather-and-attend over the ring, attention by
    blocks of keys)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
    from perfbench.families import cohere2_moe as fam
    from perfbench.families import cohere2_moe_reference as ref

    dtype = dtype or jnp.bfloat16
    vocab, n_tokens = win["vocab_size"], shape["n_tokens"]
    sz = fam.sizes(win)
    weights = fam.make_weights(0, sz)
    net = fam.build_net(sz, training=False, dtype=dtype)
    fam.install(net, jax.tree.map(
        lambda a: a if a.dtype == jnp.float32 else a.astype(dtype), weights))
    rng = np.random.default_rng(1)
    lens = [shape["short_len"]] * shape["n_short"] \
        + [shape["ring_len"], shape["long_len"]]
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lens]
    bucket = -(-shape["ring_len"] // shape["page_size"]) * shape["page_size"]
    gen = dict(_engine_kwargs(shape),
               prompt_buckets=(shape["short_len"], bucket))
    toks, stats = _through_engine(net, prompts, n_tokens, **gen)
    _check_tokens(toks, n_tokens, vocab, stats, len(prompts), "window")
    page, W = shape["page_size"], sz["W"]
    ring = W // page + 1
    blocks = _blocks_by_state(net)
    _check(stats["window_blocks"] == blocks["window"] == sz["window_layers"]
           and stats["kv_blocks"] == blocks["kv"] == sz["full_layers"],
           f"blocks by cache kind: {stats['window_blocks']} window, "
           f"{stats['kv_blocks']} kv of the net's {dict(blocks)}")
    _check(stats["window_ring_pages"] == ring
           and stats["window_pages_in_use"] == 0
           and stats["window_pages_in_use_peak"]
           <= shape["n_slots"] * ring,
           f"ring of {stats['window_ring_pages']} pages (want {ring}), "
           f"{stats['window_pages_in_use']} still held, peak "
           f"{stats['window_pages_in_use_peak']}")
    _check(shape["ring_len"] + n_tokens > W + page,
           "the ring prompt does not decode past a window and a page")
    n_chunks = -(-shape["long_len"] // shape["prefill_chunk"])
    _check(stats["prefill_chunks"] >= n_chunks,
           f"long prompt did not ride chunked prefill: "
           f"{stats['prefill_chunks']} chunks < {n_chunks}")
    loop = stats["loop"]
    _check(0 < loop["kv_positions_attended"] < loop["kv_positions_context"],
           f"positions attended {loop['kv_positions_attended']} of "
           f"{loop['kv_positions_context']}: the windows never bit")
    out = {"requests": len(prompts), "tokens": int(sum(map(len, toks))),
           "prefill_chunks": stats["prefill_chunks"],
           "decode_steps": stats["decode_steps"],
           "window_blocks": stats["window_blocks"],
           "kv_blocks": stats["kv_blocks"],
           "window_ring_pages": stats["window_ring_pages"],
           "window_pages_in_use_peak": stats["window_pages_in_use_peak"],
           "window_bytes_per_slot": stats["window_bytes_per_slot"],
           "window_attended_pct": round(
               100.0 * loop["kv_positions_attended"]
               / loop["kv_positions_context"], 2),
           **_experts_read(stats, "window")}
    gc.collect()

    # the prompt whose ring wrapped and the chunked one against the plain
    # reference's full forward
    picked = (len(prompts) - 2, len(prompts) - 1)
    out["reference_gaps"] = [round(g, 5) for g in _reference_gaps(
        fam, ref, win, sz, weights, [prompts[i] for i in picked],
        [toks[i] for i in picked])]
    _check(max(out["reference_gaps"]) < ROUTED_REFERENCE_GAP,
           f"served tokens lie {out['reference_gaps']} under the "
           f"reference's best logit")
    gc.collect()

    # the same prompts on the dense paths
    off = ("DL4J_TPU_NO_PALLAS_PAGED_ATTENTION",
           "DL4J_TPU_NO_PALLAS_ATTENTION")
    os.environ.update(dict.fromkeys(off, "1"))
    try:
        xla, xla_stats = _through_engine(net, prompts, n_tokens, **gen)
    finally:
        for name in off:
            del os.environ[name]
    _check_tokens(xla, n_tokens, vocab, xla_stats, len(prompts),
                  "xla-window")
    out["agreement"] = _agreement(net, prompts, toks, xla,
                                  "kernel and XLA windowed attention")
    gc.collect()

    engine = DecodeEngine(net, **gen)
    try:
        out.update(_decode_program_counts(engine))
    finally:
        engine.shutdown(drain_timeout=30.0)
    print(f"window: pool copies in the decode programs "
          f"{out['pool_layout_copies']}, weights re-laid "
          f"{out['weight_layout_copies']}", flush=True)
    if kernels:
        H, Hkv, hd = sz["H"], sz["Hkv"], sz["hd"]
        for tail in (("dense",), ("dense", "window", W)):
            key = ("bfloat16", 1, H, Hkv, hd, page) + tail
            _check(engaged("paged_attention", lambda k: k == key),
                   f"paged attention did not engage for {key}")
        for w in ("full", ("window", W)):
            _check(engaged("flash_attention", lambda k: k[3:] == (
                "forward", H // Hkv, w)),
                   f"the flash forward did not serve the {bucket}-token "
                   f"prompt's {w} layers")
        key = ("bfloat16", shape["n_slots"], sz["d"], sz["f"])
        _check(engaged("moe_experts", lambda k: k == key),
               f"grouped expert kernel did not engage for {key}")
        _check(not any(out["pool_layout_copies"].values()),
               f"the decode programs copy their pools: "
               f"{out['pool_layout_copies']}")
        _check(not any(out["weight_layout_copies"].values()),
               f"the decode programs re-lay their weights: "
               f"{out['weight_layout_copies']}")
        _check(not any(out["route_sorts"].values()),
               f"the decode programs sort for their routers: "
               f"{out['route_sorts']}")
    return out


# -------------------------------------------------------------- multichip
def phase_multichip(gpt: dict, train: dict, serve: dict,
                    one_chip_loss: float) -> dict:
    """Four chips, one process. `one_chip_loss`: the train phase's
    first-step loss (same seed, same batch) the mesh run must match."""
    import jax
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.serving.decode_engine import DecodeEngine
    from deeplearning4j_tpu.serving.replica_pool import ReplicaPool

    out = {}
    # (a) the train step over {data 2, model 2}, Megatron specs
    net = _gpt_net(gpt, train["T"], train["block"])
    megatron = {"Wqkv": P(None, "model"), "bqkv": P("model"),
                "Wo": P("model", None), "W1": P(None, "model"),
                "b1": P("model"), "W2": P("model", None)}
    specs = {i: megatron for i, layer in enumerate(net.layers)
             if type(layer).__name__ == "TransformerBlock"}
    mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    pw = ParallelWrapper(net, mesh=mesh, param_specs=specs)
    ds = _lm_batch(gpt["vocab_size"], train["batch"], train["T"])
    # judged against the one-chip loss, not against its own trajectory
    fitted = _fit_steps(pw.fit, net, ds, train["steps"], gpt["vocab_size"],
                        must_fall=False)
    mesh_loss = fitted["losses"][0]
    _check(abs(mesh_loss - one_chip_loss) <= LOSS_RTOL * abs(one_chip_loss),
           f"mesh loss {mesh_loss} vs one-chip {one_chip_loss}")
    out["train"] = dict(fitted, mesh=dict(mesh.shape),
                        one_chip_loss=one_chip_loss,
                        hbm_in_use=_hbm_in_use(4))
    del pw, net
    gc.collect()  # free the device state just dropped

    # (b) tp=4 decode against tp=1 tokens, (c) where params and KV sit
    net = _gpt_net(gpt, serve["max_len"])
    prompts = _serve_prompts(gpt["vocab_size"], serve)[:3]
    gen = _engine_kwargs(serve)
    n_tokens = serve["int8_tokens"]
    tp1, _ = _through_engine(net, prompts, n_tokens, **gen)
    gc.collect()  # free the device state just dropped
    before = _hbm_in_use(4)
    engine = DecodeEngine(net, parallel={"tp": 4}, **gen)
    try:
        reqs = [engine.submit(p, n_tokens, timeout=1100.0) for p in prompts]
        tp4 = [np.asarray(r.result(timeout=1150.0)) for r in reqs]
        held = [a - b for a, b in zip(_hbm_in_use(4), before)]
        tp_stats = engine.stats()
        # every KV pool and every sharded weight: a quarter on each chip
        spread = [sorted(sh.device.id for sh in leaf.addressable_shards
                         if sh.data.nbytes * 4 == leaf.nbytes)
                  for leaf in jax.tree_util.tree_leaves(engine._caches)
                  + [engine._weights[engine._plan.block_is[0]]["Wqkv"]]]
    finally:
        engine.shutdown(drain_timeout=30.0)
    _check(tp_stats["failures"] == 0, f"tp=4: {tp_stats['failures']} failed")
    _check(all(len(set(ids)) == 4 for ids in spread),
           f"tp=4 pools/weights not quartered over 4 devices: {spread[:2]}")
    out["tp4"] = dict(_agreement(net, prompts, tp4, tp1, "tp=4 and tp=1"),
                      engine_bytes_per_device=held)
    del engine
    gc.collect()  # free the device state just dropped

    # (d) where an in-process pool's four replicas land: recorded, not
    # judged — placement is ROADMAP R5's
    pool = ReplicaPool.from_net(net, 4)
    try:
        out["replica_devices"] = [
            sorted({d.id for leaf in jax.tree_util.tree_leaves(
                rep.server.net._params) for d in leaf.devices()})
            for rep in pool._replicas]
    finally:
        pool.shutdown(drain_timeout=10.0)
    return out


# ------------------------------------------------------------------- main
def _versions() -> dict:
    import importlib.metadata as md

    import jax

    return {"jax": jax.__version__, "jaxlib": md.version("jaxlib"),
            "libtpu": md.version("libtpu")}


def main(argv=None) -> int:
    import jax

    from deeplearning4j_tpu.native.loader import native_available
    from deeplearning4j_tpu.ops.kernel_dispatch import (
        verdicts_as_json,
        vmem_limit_for_kind,
    )
    from deeplearning4j_tpu.serving.observability import compile_account
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    names = list(sys.argv[1:] if argv is None else argv) \
        or ["train", "serve", "hybrid", "linear", "sublayer", "latent",
            "window", "lstm", "multichip"]
    unknown = set(names) - {"train", "serve", "hybrid", "linear",
                            "sublayer", "latent", "window", "lstm",
                            "multichip"}
    if unknown or ("multichip" in names and "train" not in names):
        print(f"chip_smoke: phases are train serve hybrid linear sublayer "
              f"latent window lstm multichip "
              f"(multichip compares against train's loss, so name both); "
              f"got {names}", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no accelerator — JAX's first device is "
              f"{dev.platform}:{dev.device_kind}; this script only runs "
              "on a TPU", file=sys.stderr)
        return 2
    vmem_limit_for_kind(dev.device_kind)  # a chip the tables do not know
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cache_dir = enable_compile_cache()
    account = compile_account()
    print(f"chip_smoke: {device}, {_versions()}, compile cache {cache_dir}",
          flush=True)

    phases: dict = {}

    def run(name: str, fn, *args, **kw) -> None:
        before, t0 = _compile_reading(account), time.perf_counter()
        result = fn(*args, **kw)
        gc.collect()  # free the device state just dropped
        after = _compile_reading(account)
        result.update(
            ok=True, seconds=round(time.perf_counter() - t0, 1),
            hbm_in_use_after=_hbm_in_use()[0],
            **{k: round(after[k] - before[k], 2) for k in after})
        phases[name] = result
        print(f"{name}: {json.dumps(result)}", flush=True)

    try:
        if "train" in names:
            run("train", phase_train, GPT, TRAIN, kernels=True)
        if "serve" in names:
            run("serve", phase_serve, GPT, SERVE, kernels=True)
        if "hybrid" in names:
            run("hybrid", phase_hybrid, HYBRID, ROUTED_SERVE, kernels=True)
        if "linear" in names:
            run("linear", phase_linear, LINEAR, LINEAR_SERVE, kernels=True)
        if "sublayer" in names:
            run("sublayer", phase_sublayer, SUBLAYER, SUBLAYER_SERVE,
                kernels=True)
        if "latent" in names:
            run("latent", phase_latent, LATENT, LATENT_SERVE, kernels=True)
            run("latent_h128", phase_latent, LATENT_H128,
                LATENT_H128_SERVE, kernels=True, family="deepseek_v2")
            run("latent_kda", phase_latent, LATENT_KDA, LATENT_KDA_SERVE,
                kernels=True, family="ling_flash")
        if "window" in names:
            run("window", phase_window, WINDOW, WINDOW_SERVE, kernels=True)
        if "lstm" in names:
            run("lstm", phase_lstm, LSTM, kernels=True)
        if "multichip" in names:
            if device["count"] >= 4:
                run("multichip", phase_multichip, GPT, TRAIN, SERVE,
                    phases["train"]["losses"][0])
            else:
                print(f"multichip: not run ({device['count']} device)",
                      flush=True)
                phases["multichip"] = {
                    "ok": None, "not_run": f"{device['count']} device"}
    except BaseException:
        print(json.dumps({"ok": False, "device": device}), flush=True)
        raise

    print(json.dumps({
        "ok": True, "device": device, "versions": _versions(),
        "phases": phases, "kernels": verdicts_as_json(),
        "compile": dict(_compile_reading(account), cache_dir=cache_dir),
        "native": {"gxx": shutil.which("g++") is not None,
                   "loaded": native_available()},
        "claim": None}), flush=True)
    # the driver's contract: exactly these keys, on the last line
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
