"""GPT-style causal transformer language model.

No counterpart in the reference (its sequence toolbox is LSTM + tBPTT,
SURVEY §5); this is the long-context flagship of the TPU build: token +
positional embedding → N pre-LN `TransformerBlock`s (attention dispatches
to the pallas flash kernel / XLA blockwise path for long sequences) →
final LayerNorm → per-timestep softmax head. Scales via:
- data/tensor parallel: `ParallelWrapper` over a mesh;
- long sequences: `parallel/sequence.py` ring/Ulysses attention;
- deep stacks: homogeneous blocks fit `parallel/pipeline.py`;
- wide FFN: `parallel/experts.py` Switch MoE.

Decode machinery: `GPTPlan` + the `_block_heads`/`_block_ffn`/
`_final_logits`/`_sample_logits`/`_prefill_block_attention`/
`_prefill_chunk_block_attention` helpers are the SINGLE implementation
of per-token transformer compute, shared by whole-batch `generate()`
below and by the continuous-batching
`serving.decode_engine.DecodeEngine` (paged KV cache + chunked
prefill) — the engine's argmax-parity guarantee against `generate`
holds by construction, not only by test.
"""
from __future__ import annotations

from deeplearning4j_tpu.nn.conf import (
    InputType,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.decoder_block import (
    AttentionMixer,
    ChannelGatedDeltaMixer,
    DecoderBlock,
    GatedDeltaNetMixer,
    GatedMLP,
    LatentAttentionMixer,
    LayerNorm,
    Mamba2Mixer,
    MoEFeedForward,
    RMSNorm,
    Rotary,
    ShortcutDecoderBlock,
)
from deeplearning4j_tpu.nn.conf.layers import (
    LayerNormalization,
    RMSNormalization,
    RnnOutputLayer,
    TiedRnnOutputLayer,
    TokenEmbedding,
    TransformerBlock,
)
from deeplearning4j_tpu.nn.updater import Updater
from deeplearning4j_tpu.ops.activations import Activation
from deeplearning4j_tpu.ops.losses import LossFunction

_GEN_CACHE_MAX = 8  # compiled prefill+decode pairs kept per network (LRU)


def gpt_configuration(vocab_size: int,
                      d_model: int = 256,
                      n_heads: int = 4,
                      n_layers: int = 4,
                      max_length: int = 512,
                      ffn_mult: int = 4,
                      dropout: float = 0.0,
                      seed: int = 12345,
                      learning_rate: float = 3e-4,
                      updater: Updater = Updater.ADAM,
                      attention_block_size: int = 1024,
                      moe_experts: int = 0,
                      remat: bool = False,
                      n_kv_heads: int = 0,
                      rope: bool = False,
                      ffn_activation: str = "gelu",
                      ) -> MultiLayerConfiguration:
    """Causal LM over int token ids (B, T) with next-token targets
    (B, T, vocab) one-hot (per-timestep MCXENT, masked). `n_kv_heads`:
    grouped-query attention (0 = full MHA, 1 = MQA) — `generate()`'s KV
    caches shrink by n_heads/n_kv_heads. `rope`: rotary position
    embeddings in every block, and NO learned positional table (position
    is relative, encoded in the attention rotation)."""
    b = (NeuralNetConfiguration.Builder()
         .seed(seed)
         .learning_rate(learning_rate)
         .updater(updater)
         .drop_out(dropout)
         .list()
         .layer(TokenEmbedding(n_in=vocab_size, n_out=d_model,
                               max_length=max_length,
                               positional=not rope)))
    for _ in range(n_layers):
        b = b.layer(TransformerBlock(n_in=d_model, n_out=d_model,
                                     n_heads=n_heads, ffn_mult=ffn_mult,
                                     causal=True,
                                     block_size=attention_block_size,
                                     moe_experts=moe_experts,
                                     remat=remat, n_kv_heads=n_kv_heads,
                                     rope=rope,
                                     ffn_activation=ffn_activation))
    return (b
            .layer(LayerNormalization(n_in=d_model, n_out=d_model,
                                      dropout=0.0))
            .layer(RnnOutputLayer(n_in=d_model, n_out=vocab_size,
                                  activation=Activation.SOFTMAX,
                                  loss=LossFunction.MCXENT, dropout=0.0))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())


def composed_configuration(vocab_size: int, d_model: int, blocks, *,
                           eps: float, tied_head: bool = False,
                           embedding_multiplier: float = 1.0,
                           logits_scaling: float = 1.0, seed: int = 12345,
                           learning_rate: float = 3e-4,
                           updater: Updater = Updater.ADAM,
                           final_norm: str = "rms_norm",
                           ) -> MultiLayerConfiguration:
    """Causal LM around the composed `blocks` (`DecoderBlock`s or
    `ShortcutDecoderBlock`s of width `d_model`): a token embedding
    without positions, scaled by `embedding_multiplier`; the blocks; one
    trailing RMSNorm (`final_norm` "layer_norm": a LayerNorm with a gain
    and no bias); and the output head, untied and bias-free, or with
    `tied_head` the embedding, transposed, over `logits_scaling`. What
    the named families below share."""
    if final_norm not in ("rms_norm", "layer_norm"):
        raise ValueError(f"final_norm {final_norm!r}: 'rms_norm' or "
                         "'layer_norm'")
    b = (NeuralNetConfiguration.Builder()
         .seed(seed)
         .learning_rate(learning_rate)
         .updater(updater)
         .drop_out(0.0)
         .list()
         .layer(TokenEmbedding(n_in=vocab_size, n_out=d_model,
                               positional=False,
                               multiplier=embedding_multiplier)))
    for block in blocks:
        b = b.layer(block)
    head = dict(n_in=d_model, n_out=vocab_size,
                activation=Activation.SOFTMAX, loss=LossFunction.MCXENT,
                dropout=0.0)
    return (b
            .layer(RMSNormalization(n_in=d_model, n_out=d_model, eps=eps,
                                    dropout=0.0)
                   if final_norm == "rms_norm" else
                   LayerNormalization(n_in=d_model, n_out=d_model, eps=eps,
                                      has_bias=False, dropout=0.0))
            .layer(TiedRnnOutputLayer(tied_to=0,
                                      logits_scaling=logits_scaling, **head)
                   if tied_head else RnnOutputLayer(has_bias=False, **head))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())


def hybrid_moe_configuration(vocab_size: int, d_model: int,
                             layer_types, *,
                             n_heads: int, n_kv_heads: int,
                             attention_multiplier: float = None,
                             mamba_heads: int, mamba_head_dim: int,
                             mamba_state: int, mamba_conv: int = 4,
                             mamba_chunk: int = 256,
                             n_experts: int, top_k: int,
                             expert_width: int, shared_width: int = 0,
                             experts_held=None,
                             embedding_multiplier: float = 1.0,
                             residual_multiplier: float = 1.0,
                             logits_scaling: float = 1.0,
                             eps: float = 1e-5, seed: int = 12345,
                             learning_rate: float = 3e-4,
                             updater: Updater = Updater.ADAM,
                             ) -> MultiLayerConfiguration:
    """Causal LM of composed `DecoderBlock`s, one per entry of
    `layer_types` ("mamba": a Mamba-2 mixer, "attention": grouped-query
    attention without positions), each followed by top-k dropless routed
    experts plus a shared expert, under RMSNorm; the token embedding is
    scaled by `embedding_multiplier`, both residual branches by
    `residual_multiplier`, and the output head is the embedding,
    transposed, over `logits_scaling` (the Hugging Face
    `granitemoehybrid` family's layout). `experts_held = (first,
    count)`: the share of each layer's experts this network holds."""
    ffn = MoEFeedForward(n_experts=n_experts, top_k=top_k,
                         expert_width=expert_width,
                         shared_width=shared_width,
                         experts_held=experts_held)
    mixers = {
        "mamba": Mamba2Mixer(n_heads=mamba_heads, head_dim=mamba_head_dim,
                             d_state=mamba_state, d_conv=mamba_conv,
                             chunk=mamba_chunk, eps=eps),
        "attention": AttentionMixer(n_heads=n_heads, n_kv_heads=n_kv_heads,
                                    scale=attention_multiplier)}
    blocks = [DecoderBlock(n_in=d_model, n_out=d_model, mixer=mixers[kind],
                           ffn=ffn, norm=RMSNorm(eps=eps),
                           residual_multiplier=residual_multiplier)
              for kind in layer_types]
    return composed_configuration(
        vocab_size, d_model, blocks, eps=eps, tied_head=True,
        embedding_multiplier=embedding_multiplier,
        logits_scaling=logits_scaling, seed=seed,
        learning_rate=learning_rate, updater=updater)


def hybrid_linear_configuration(vocab_size: int, d_model: int,
                                layer_types, *,
                                n_heads: int, n_kv_heads: int = 0,
                                linear_heads: int, linear_key_dim: int,
                                linear_value_dim: int, linear_conv: int = 4,
                                allow_neg_eigval: bool = False,
                                ffn_width: int, eps: float = 1e-6,
                                seed: int = 12345,
                                learning_rate: float = 3e-4,
                                updater: Updater = Updater.ADAM,
                                ) -> MultiLayerConfiguration:
    """Causal LM of composed post-norm `DecoderBlock`s, one per entry of
    `layer_types` ("linear_attention": a gated delta-rule mixer,
    "full_attention": multi-head attention with QK-norm and without
    positions), each followed by a dense gated MLP, under RMSNorm; no
    positional layer, one trailing norm and an untied, bias-free output
    head (the Hugging Face `olmo_hybrid` family's layout)."""
    mixers = {
        "linear_attention": GatedDeltaNetMixer(
            n_heads=linear_heads, key_dim=linear_key_dim,
            value_dim=linear_value_dim, d_conv=linear_conv,
            allow_neg_eigval=allow_neg_eigval, eps=eps),
        "full_attention": AttentionMixer(n_heads=n_heads,
                                         n_kv_heads=n_kv_heads,
                                         qk_norm=True, eps=eps)}
    blocks = [DecoderBlock(n_in=d_model, n_out=d_model, mixer=mixers[kind],
                           ffn=GatedMLP(width=ffn_width),
                           norm=RMSNorm(eps=eps), norm_placement="post")
              for kind in layer_types]
    return composed_configuration(
        vocab_size, d_model, blocks, eps=eps, seed=seed,
        learning_rate=learning_rate, updater=updater)


def hybrid_sublayer_configuration(vocab_size: int, d_model: int,
                                  pattern: str, *,
                                  n_heads: int, n_kv_heads: int,
                                  head_dim: int,
                                  mamba_heads: int, mamba_head_dim: int,
                                  mamba_state: int, mamba_groups: int = 1,
                                  mamba_conv: int = 4,
                                  mamba_chunk: int = 256,
                                  n_experts: int, top_k: int,
                                  expert_width: int, shared_width: int = 0,
                                  routed_scale: float = 1.0,
                                  experts_held=None,
                                  eps: float = 1e-5, seed: int = 12345,
                                  learning_rate: float = 3e-4,
                                  updater: Updater = Updater.ADAM,
                                  ) -> MultiLayerConfiguration:
    """Causal LM of `DecoderBlock`s of ONE sub-layer each, one per
    character of `pattern`: "M" a Mamba-2 mixer with `mamba_groups` B/C
    groups, "*" grouped-query attention with heads of `head_dim`
    (whatever `d_model // n_heads` is) and without positions, "E"
    sigmoid-routed ungated relu^2 experts plus a shared one; each under
    one pre-RMSNorm and one residual, no multipliers, no positional
    layer, one trailing norm and an untied, bias-free output head (the
    Hugging Face `nemotron_h` family's layout). `experts_held = (first,
    count)`: the share of each expert layer's experts this network
    holds."""
    kinds = {
        "M": dict(mixer=Mamba2Mixer(
            n_heads=mamba_heads, head_dim=mamba_head_dim,
            d_state=mamba_state, d_conv=mamba_conv, chunk=mamba_chunk,
            eps=eps, n_groups=mamba_groups)),
        "*": dict(mixer=AttentionMixer(n_heads=n_heads,
                                       n_kv_heads=n_kv_heads,
                                       head_dim=head_dim)),
        "E": dict(ffn=MoEFeedForward(
            n_experts=n_experts, top_k=top_k, expert_width=expert_width,
            shared_width=shared_width, experts_held=experts_held,
            activation="relu2", scoring="sigmoid",
            routed_scale=routed_scale))}
    if set(pattern) - set(kinds):
        raise ValueError(f"pattern {pattern!r}: each layer is M (Mamba-2), "
                         "* (attention) or E (experts)")
    blocks = [DecoderBlock(n_in=d_model, n_out=d_model,
                           norm=RMSNorm(eps=eps), **kinds[kind])
              for kind in pattern]
    return composed_configuration(
        vocab_size, d_model, blocks, eps=eps, seed=seed,
        learning_rate=learning_rate, updater=updater)


def longcat_configuration(vocab_size: int, d_model: int, n_layers: int, *,
                          n_heads: int, q_rank: int, kv_rank: int,
                          nope_dim: int, rope_dim: int, v_dim: int,
                          rope_theta: float = 1e7,
                          scale_q_lora: bool = True,
                          scale_kv_lora: bool = True,
                          ffn_width: int, n_experts: int,
                          n_zero_experts: int, top_k: int,
                          expert_width: int, routed_scale: float = 1.0,
                          experts_held=None, eps: float = 1e-5,
                          seed: int = 12345, learning_rate: float = 3e-4,
                          updater: Updater = Updater.ADAM,
                          ) -> MultiLayerConfiguration:
    """Causal LM of `n_layers` `ShortcutDecoderBlock`s: each layer two
    (latent attention, dense gated MLP) pairs and, on a shortcut around
    the second pair, `n_experts` routed gated-silu experts plus
    `n_zero_experts` zero-compute ones, `top_k` a token chosen on a
    softmax over all of them; rotary on the latent attention's rope
    dimensions, RMSNorm, no multipliers, no positional layer, one
    trailing norm and an untied, bias-free output head (the Hugging
    Face `longcat_flash` family's layout). `experts_held = (first,
    count)`: the share of each layer's real experts this network
    holds."""
    mixer = LatentAttentionMixer(
        n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope_dim,
        rope_dim=rope_dim, v_dim=v_dim, rope_theta=rope_theta,
        scale_q_lora=scale_q_lora, scale_kv_lora=scale_kv_lora, eps=eps)
    pair = lambda: DecoderBlock(n_in=d_model, n_out=d_model, mixer=mixer,
                                ffn=GatedMLP(width=ffn_width),
                                norm=RMSNorm(eps=eps))
    shortcut = MoEFeedForward(
        n_experts=n_experts, n_zero_experts=n_zero_experts, top_k=top_k,
        expert_width=expert_width, experts_held=experts_held,
        scoring="softmax_all", routed_scale=routed_scale)
    blocks = [ShortcutDecoderBlock(n_in=d_model, n_out=d_model,
                                   first=pair(), second=pair(),
                                   shortcut=shortcut)
              for _ in range(n_layers)]
    return composed_configuration(
        vocab_size, d_model, blocks, eps=eps, seed=seed,
        learning_rate=learning_rate, updater=updater)


def deepseek_v2_configuration(vocab_size: int, d_model: int, n_layers: int,
                              *, n_heads: int, q_rank: int, kv_rank: int,
                              nope_dim: int, rope_dim: int, v_dim: int,
                              rope_theta: float = 10000.0,
                              rope_scaling=None, n_dense_layers: int = 1,
                              ffn_width: int, n_experts: int, top_k: int,
                              expert_width: int, shared_width: int = 0,
                              routed_scale: float = 1.0, n_groups: int = 1,
                              topk_groups: int = 1, experts_held=None,
                              eps: float = 1e-6, seed: int = 12345,
                              learning_rate: float = 3e-4,
                              updater: Updater = Updater.ADAM,
                              ) -> MultiLayerConfiguration:
    """Causal LM of `n_layers` pre-norm `DecoderBlock`s, each a latent
    attention (MLA) mixer and a feed-forward: the first `n_dense_layers`
    a dense gated-silu MLP of `ffn_width`, the rest `n_experts` routed
    gated-silu experts, `top_k` a token chosen on a softmax over all of
    them among the token's `topk_groups` best of `n_groups` groups
    (device-limited routing), gates the scores times `routed_scale`, plus
    a shared MLP of `shared_width`; rotary on the rope dimensions,
    stretched by `rope_scaling` (a `YarnScaling`, its JSON dict, or
    None); RMSNorm, no positional layer, one trailing norm and an
    untied, bias-free output head (the Hugging Face `deepseek_v2`
    family's layout). `experts_held = (first, count)`: the share of each
    routed layer's experts this network holds."""
    mixer = LatentAttentionMixer(
        n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope_dim,
        rope_dim=rope_dim, v_dim=v_dim, rope_theta=rope_theta, eps=eps,
        rope_scaling=rope_scaling)
    routed = MoEFeedForward(
        n_experts=n_experts, top_k=top_k, expert_width=expert_width,
        shared_width=shared_width, experts_held=experts_held,
        scoring="softmax_all", routed_scale=routed_scale,
        n_groups=n_groups, topk_groups=topk_groups)
    blocks = [DecoderBlock(
        n_in=d_model, n_out=d_model, mixer=mixer, norm=RMSNorm(eps=eps),
        ffn=GatedMLP(width=ffn_width) if i < n_dense_layers else routed)
        for i in range(n_layers)]
    return composed_configuration(
        vocab_size, d_model, blocks, eps=eps, seed=seed,
        learning_rate=learning_rate, updater=updater)


def ling_flash_configuration(vocab_size: int, d_model: int, n_layers: int,
                             *, layer_group_size: int, n_heads: int,
                             kv_rank: int, nope_dim: int, rope_dim: int,
                             v_dim: int, rope_theta: float = 6e6,
                             linear_heads: int, linear_key_dim: int,
                             linear_value_dim: int, linear_conv: int = 4,
                             gate_lower_bound: float = -5.0,
                             n_dense_layers: int = 2, ffn_width: int,
                             n_experts: int, top_k: int, expert_width: int,
                             shared_width: int = 0,
                             routed_scale: float = 1.0, n_groups: int = 1,
                             topk_groups: int = 1, experts_held=None,
                             eps: float = 1e-6, seed: int = 12345,
                             learning_rate: float = 3e-4,
                             updater: Updater = Updater.ADAM,
                             ) -> MultiLayerConfiguration:
    """Causal LM of `n_layers` pre-norm `DecoderBlock`s, a mixer and a
    feed-forward each. Layer `l`'s mixer is latent attention (MLA) with
    full-rank queries and a sigmoid gate a head where `(l + 1) %
    layer_group_size == 0`, else a delta-rule layer with a decay a key
    channel under a gate bounded by `gate_lower_bound` (KDA): with 6,
    five linear layers to one full. The first `n_dense_layers`
    feed-forwards are a dense gated-silu MLP of `ffn_width`, the rest
    `n_experts` routed gated-silu experts, `top_k` a token chosen on
    sigmoid scores plus a correction bias among the token's
    `topk_groups` best of `n_groups` groups, gates the unbiased scores
    normalised to sum `routed_scale`, plus a shared MLP of
    `shared_width`; rotary on the latent attention's rope dimensions
    only, RMSNorm, no positional layer, one trailing norm and an untied,
    bias-free output head (the Hugging Face `bailing_hybrid` family's
    layout: Ling-3.0-flash). `experts_held = (first, count)`: the share
    of each routed layer's experts this network holds."""
    full = LatentAttentionMixer(
        n_heads=n_heads, q_rank=None, kv_rank=kv_rank, nope_dim=nope_dim,
        rope_dim=rope_dim, v_dim=v_dim, rope_theta=rope_theta, eps=eps,
        head_gate=True)
    linear = ChannelGatedDeltaMixer(
        n_heads=linear_heads, key_dim=linear_key_dim,
        value_dim=linear_value_dim, d_conv=linear_conv, eps=eps,
        gate_lower_bound=gate_lower_bound)
    routed = MoEFeedForward(
        n_experts=n_experts, top_k=top_k, expert_width=expert_width,
        shared_width=shared_width, experts_held=experts_held,
        scoring="sigmoid", routed_scale=routed_scale, n_groups=n_groups,
        topk_groups=topk_groups)
    blocks = [DecoderBlock(
        n_in=d_model, n_out=d_model, norm=RMSNorm(eps=eps),
        mixer=full if (i + 1) % layer_group_size == 0 else linear,
        ffn=GatedMLP(width=ffn_width) if i < n_dense_layers else routed)
        for i in range(n_layers)]
    return composed_configuration(
        vocab_size, d_model, blocks, eps=eps, seed=seed,
        learning_rate=learning_rate, updater=updater)


def command_a_configuration(vocab_size: int, d_model: int, n_layers: int,
                            *, layer_switch: int = 4, window: int,
                            n_heads: int, n_kv_heads: int, head_dim: int = 0,
                            rope_theta: float = 50000.0, n_experts: int,
                            top_k: int, expert_width: int,
                            n_shared_experts: int = 0,
                            shared_width: int = 0, experts_held=None,
                            logit_scale: float = 1.0, eps: float = 1e-5,
                            seed: int = 12345, learning_rate: float = 3e-4,
                            updater: Updater = Updater.ADAM,
                            ) -> MultiLayerConfiguration:
    """Causal LM of `n_layers` PARALLEL `DecoderBlock`s: one LayerNorm
    (a gain, no bias) of the stream, read by grouped-query attention and
    by the feed-forward alike, both added in one residual. Layer `l`
    attends its whole context WITHOUT positions where `(l + 1) %
    layer_switch == 0`; every other layer attends the last `window`
    positions with rotary over the whole head (interleaved pairs,
    `rope_theta`): with 4, three window layers to one full. Every
    feed-forward is `n_experts` routed gated-silu experts, `top_k` a
    token on sigmoid scores, gates the chosen scores normalised to sum
    1, beside `n_shared_experts` shared experts of `shared_width` each
    whose outputs are AVERAGED (held as one MLP `n_shared_experts *
    shared_width` wide times `1 / n_shared_experts`: the same sums); a
    trailing LayerNorm and the tied embedding as head, times
    `logit_scale` (the Hugging Face `cohere2_moe` family's layout:
    Command A+). `experts_held = (first, count)`: the share of each
    layer's routed experts this network holds."""
    def mixer(full: bool):
        return AttentionMixer(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            rope=None if full else Rotary(theta=rope_theta,
                                          interleaved=True),
            window=None if full else window)

    routed = MoEFeedForward(
        n_experts=n_experts, top_k=top_k, expert_width=expert_width,
        shared_width=n_shared_experts * shared_width,
        shared_scale=1.0 / n_shared_experts if n_shared_experts else 1.0,
        experts_held=experts_held, scoring="sigmoid")
    blocks = [DecoderBlock(
        n_in=d_model, n_out=d_model, norm=LayerNorm(eps=eps),
        norm_placement="parallel",
        mixer=mixer((i + 1) % layer_switch == 0), ffn=routed)
        for i in range(n_layers)]
    return composed_configuration(
        vocab_size, d_model, blocks, eps=eps, tied_head=True,
        logits_scaling=1.0 / logit_scale, final_norm="layer_norm",
        seed=seed, learning_rate=learning_rate, updater=updater)


# ---------------------------------------------------------------------------
# shared decode plan + per-block compute (generate() AND the serving
# decode engine trace through these — one implementation of the numerics)


class GPTPlan:
    """Static decode plan for a `gpt_configuration` network: layer
    indices, the embedding layer, and the mixed-precision policy
    (embedding/block math and KV caches in the net's compute dtype — bf16
    halves cache bandwidth, the decode step's dominant cost — with the
    logits head and sampling in the param dtype, mirroring the training
    step's precision boundary)."""

    def __init__(self, net):
        net._ensure_init()
        layers = net.layers
        if not isinstance(layers[0], TokenEmbedding):
            raise ValueError("generate() expects a gpt_configuration "
                             "network (TokenEmbedding first)")
        self.net = net
        self.layers = layers
        self.emb_i = 0
        self.emb = layers[0]
        self.block_is = [i for i, l in enumerate(layers)
                        if isinstance(l, (TransformerBlock, DecoderBlock,
                                          ShortcutDecoderBlock))]
        self.ln_is = [i for i, l in enumerate(layers)
                      if isinstance(l, (LayerNormalization,
                                        RMSNormalization))]
        self.out_i = next(i for i, l in enumerate(layers)
                          if isinstance(l, RnnOutputLayer))
        self.dtype = net.dtype
        self.cdt = net.compute_dtype or net.dtype

    def state_kinds(self):
        """Per block, the cache state a decode engine keeps for it:
        "kv" (paged key/value pools), "window" (the same as a ring of
        pages a slot), "recurrent" (per-slot arrays),
        "latent" (one paged pool of latents) or "none". A
        `TransformerBlock` keeps K/V; a composed `DecoderBlock` keeps
        what its mixer kind declares, and nothing where it has no
        mixer; a `ShortcutDecoderBlock` the pair of its two mixers'
        kinds."""
        return ["kv" if isinstance(self.layers[i], TransformerBlock)
                else self.layers[i].state for i in self.block_is]

    @property
    def composed(self) -> bool:
        """Whether any block is composed of kinds (`DecoderBlock`,
        `ShortcutDecoderBlock`)."""
        return any(not isinstance(self.layers[i], TransformerBlock)
                   for i in self.block_is)

    def kv_geometry(self):
        """(Hkv, head_dim) pairs of the blocks that keep K/V — the
        KV-cache geometry the paged pools allocate per block. One source
        of truth for the
        serving tier's byte accounting (`quantize.kv_bytes_per_token`,
        the engine's ``kv_bytes_per_token`` stat, the bench's
        slots-per-chip line) so a GQA or head-width change reprices all
        of them at once."""
        out = []
        for i in self.block_is:
            layer = self.layers[i]
            if isinstance(layer, TransformerBlock):
                out.append((layer._kv_heads, layer.n_out // layer.n_heads))
            elif layer.state in ("kv", "window"):
                out.append(layer.mixer.kv_geometry(layer._d))
        return out

    def latent_geometry(self):
        """(kv_rank, rope_dim) pairs of the sub-layers that keep a pool
        of latents, in order: a position's cache there is their sum, in
        the compute dtype, for all heads."""
        return [m.latent_geometry() for i in self.block_is
                for m in getattr(self.layers[i], "mixers", list)()
                if m.state == "latent"]

    def _cast(self, params, wrap):
        """`params` with the layers that are read in the compute dtype
        cast to it by `wrap(cast_weights)`: every block, and the
        embedding unless the head is tied to it (a tied head reads the
        table in the param dtype, so one table is held, not two, and its
        gathered rows are cast with the activations). The other leaves
        are `params` own arrays; where the two dtypes are equal the
        result IS `params`."""
        if self.cdt == self.dtype:
            return params
        from deeplearning4j_tpu.nn.precision import tree_cast

        def cast_weights(tree):
            return tree_cast(tree, self.cdt)

        head = self.layers[self.out_i]
        tied = head.tied_to if isinstance(head, TiedRnnOutputLayer) else None
        done = wrap(cast_weights)(
            {i: params[i] for i in (self.emb_i, *self.block_is)
             if i != tied})
        return [done.get(i, p) for i, p in enumerate(params)]

    def cast_blocks(self, params):
        """Embedding + block params in the compute dtype; head params
        stay in the param dtype. Traced into the caller's program: for
        one that runs once per call (`generate`)."""
        import jax

        with jax.named_scope("cast_params"):
            return self._cast(params, lambda cast: cast)

    def resident_weights(self, params):
        """`cast_blocks` run ONCE, as a program of its own
        (`jit_cast_weights` in a trace), for the decode engine, which
        keeps the result on the device and hands it to every dispatch:
        no serving program converts a weight. Sharded leaves keep their
        sharding."""
        import jax

        return self._cast(params, jax.jit)

    def final_logits(self, bp, params, x):
        """Trailing LN(s) from `bp`, then the output head in the param
        dtype from `params` — the same precision boundary the training
        step draws (`MultiLayerNetwork._loss_pure` restores the param
        dtype for the loss head). `cast_blocks` and `resident_weights`
        leave the head's leaves in the param dtype, so a caller that
        holds only their result passes it twice."""
        import jax

        with jax.named_scope("head"):
            for i in self.ln_is:
                if i > max(self.block_is, default=-1):
                    x = self.layers[i].forward(bp[i], None, x)[0]
            x = x.astype(self.dtype)
            head = self.layers[self.out_i]
            if isinstance(head, TiedRnnOutputLayer):
                return head.pre_output(params[head.tied_to], x)
            if not head.has_bias:
                return head.pre_output(params[self.out_i], x)
            return x @ params[self.out_i]["W"] + params[self.out_i]["b"]


def _block_heads(layer, p, x, positions=None, shard=None):
    """(..., d) -> q (..., H, hd) and k/v (..., Hkv, hd) for one block —
    K/V stay at the layer's (possibly grouped) head count, so GQA caches
    carry only Hkv heads. `positions`: RoPE rotation positions (prefill:
    arange(T); whole-batch decode: the current scalar pos; slotted
    decode: a per-slot vector) — keys enter the cache already rotated at
    their absolute position.

    `shard`: tensor-parallel degree when running inside a `shard_map`
    body over head-sharded `Wqkv`/`bqkv` (columns permuted so each
    device's slice is [Q_t | K_t | V_t] — `serving/tp_engine.py`): the
    local projection yields H/shard query and Hkv/shard KV heads. RoPE
    rotates per head, so local slices rotate identically to their
    global positions. `shard=None` is byte-identical to the
    single-device path (qw == d)."""
    import jax

    from deeplearning4j_tpu.nn.conf.layers import layer_norm

    d = x.shape[-1]
    hd = d // layer.n_heads
    H = layer.n_heads // shard if shard else layer.n_heads
    Hkv = layer._kv_heads // shard if shard else layer._kv_heads
    qw = H * hd
    kvw = Hkv * hd
    with jax.named_scope("ln1"):
        h1 = layer_norm(x, p["ln1_g"], p["ln1_b"], layer.eps)
    with jax.named_scope("attn.qkv"):
        qkv = h1 @ p["Wqkv"] + p["bqkv"]
        q = qkv[..., :qw].reshape(*x.shape[:-1], H, hd)
        k = qkv[..., qw:qw + kvw].reshape(*x.shape[:-1], Hkv, hd)
        v = qkv[..., qw + kvw:].reshape(*x.shape[:-1], Hkv, hd)
        if layer.rope:
            from deeplearning4j_tpu.ops.rope import rope_angles, rope_rotate

            cos, sin = rope_angles(positions, hd, layer.rope_base)
            q = rope_rotate(q, cos, sin)
            k = rope_rotate(k, cos, sin)
    return q, k, v


def _psum_partial(y, axis_name):
    """Sum a row-parallel matmul's partial products over the named
    tensor-parallel mesh axis — the ONE all-reduce each Megatron-sharded
    half-block performs. Identity when `axis_name` is None (single
    device), so callers thread it unconditionally."""
    if axis_name is None:
        return y
    import jax

    with jax.named_scope("tp-allreduce"):
        return jax.lax.psum(y, axis_name)


def _block_out_proj(p, att, axis_name=None):
    """Attention output projection on flattened head outputs
    (..., H·hd). Under tensor parallelism `att` carries the local
    H/tp head slice and `Wo` the matching row slice; the replicated
    bias is added AFTER the all-reduce so it lands exactly once."""
    import jax

    with jax.named_scope("attn.out"):
        return _psum_partial(att @ p["Wo"], axis_name) + p["bo"]


def _block_ffn(layer, p, x, axis_name=None):
    """Post-attention half of the block on (B, T, d) or (B, d).

    `axis_name`: tensor-parallel mesh axis when `W1`/`W3` are
    column-sharded and `W2` row-sharded (Megatron FFN) — the partial
    W2 product is all-reduced before the replicated `b2` is added.
    MoE blocks don't compose with serving TP (rejected at
    `TPPlan` construction)."""
    import jax

    from deeplearning4j_tpu.nn.conf.layers import layer_norm

    with jax.named_scope("ln2"):
        h2 = layer_norm(x, p["ln2_g"], p["ln2_b"], layer.eps)
    if layer.moe_experts > 0:
        from deeplearning4j_tpu.parallel.experts import switch_ffn

        lead = h2.shape[:-1]
        ffn = switch_ffn(p, h2.reshape(-1, h2.shape[-1]),
                         act=jax.nn.gelu,
                         capacity_factor=layer.moe_capacity_factor,
                         aux_weight=layer.moe_aux_weight,
                         train=False,
                         passthrough="zero").reshape(*lead, -1)
    elif layer.ffn_activation == "swiglu":
        with jax.named_scope("mlp.up"):
            gate, up = h2 @ p["W1"], h2 @ p["W3"]
        with jax.named_scope("mlp.act"):
            act = jax.nn.silu(gate) * up
        with jax.named_scope("mlp.down"):
            ffn = _psum_partial(act @ p["W2"], axis_name) + p["b2"]
    else:
        with jax.named_scope("mlp.up"):
            up = h2 @ p["W1"] + p["b1"]
        with jax.named_scope("mlp.act"):
            act = jax.nn.gelu(up)
        with jax.named_scope("mlp.down"):
            ffn = _psum_partial(act @ p["W2"], axis_name) + p["b2"]
    return x + ffn


def _top_k_filter(logits, top_k: int):
    """Mask everything below the k-th largest logit per row — the ONE
    implementation of top-k truncation (generate's static-temperature
    sampler and the decode engine's dynamic-temperature one both call
    it, so the truncation numerics cannot drift apart)."""
    import jax
    import jax.numpy as jnp

    if top_k <= 0:
        return logits
    kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _sample_logits(logits, key, temperature: float, top_k: int):
    """Greedy argmax when temperature <= 0, else temperature/top-k
    categorical sampling. Static temperature/top_k (compiled in)."""
    import jax
    import jax.numpy as jnp

    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _top_k_filter(logits / jnp.asarray(temperature, logits.dtype),
                           top_k)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _prefill_block_attention(layer, q, k, v):
    """Causal prefill attention for one block: GQA keys/values widened to
    the full head count (training-path semantics; the grouped-decode win
    only applies to the cached step)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import full_attention

    with jax.named_scope("attn.core"):
        kf, vf = k, v
        if layer._kv_heads != layer.n_heads:
            g = layer.n_heads // layer._kv_heads
            kf = jnp.repeat(k, g, axis=2)
            vf = jnp.repeat(v, g, axis=2)
        return full_attention(q, kf, vf, causal=True)


def _prefill_chunk_block_attention(layer, q, k_cache, v_cache, q_pos):
    """Causal attention for ONE prompt chunk of one block against the
    slot's (paged-gathered) dense cache — the chunked-prefill
    counterpart of `_prefill_block_attention`. Since r6 the engine
    dispatches `ops.attention.paged_attention_chunk_auto` instead (the
    Pallas page-walk kernel on TPU); this helper IS that path's
    fallback numerics and stays as the documented reference. `q`:
    (1, C, H, hd) fresh chunk queries at absolute positions `q_pos`
    (C,); `k_cache`/`v_cache`: (Hkv, hd, L)/(Hkv, L, hd) already
    holding the chunk's own K/V, so masking to entries `<= q_pos` is
    exactly causal over [prior chunks ‖ this chunk]. Returns
    (1, C, H*hd)."""
    from deeplearning4j_tpu.ops.attention import cached_attention_chunk

    return cached_attention_chunk(q[0], k_cache, v_cache, q_pos)[None]


def _verify_block_attention(layer, q, k_cache, v_cache, q_pos):
    """Batched-over-slots chunk attention for the speculative VERIFY
    step of one block: every slot scores a (k+1)-token candidate block
    against its own paged-gathered cache in one dispatch — the
    slot-batched counterpart of `_prefill_chunk_block_attention`
    (since r6 the verify dispatches
    `ops.attention.paged_attention_chunk_auto`, whose fallback is
    exactly this helper's numerics), built
    on the same `cached_attention_chunk` numerics (which is what keeps
    greedy speculative decode argmax-exact against `generate`). `q`:
    (S, C, H, hd) candidate-block queries at absolute positions `q_pos`
    (S, C); `k_cache`/`v_cache`: (S, Hkv, hd, L)/(S, Hkv, L, hd) —
    `paged_gather` output, already holding the block's own K/V, so the
    `<= q_pos` mask is exactly causal over [context ‖ candidates].
    Returns (S, C, H*hd)."""
    import jax

    from deeplearning4j_tpu.ops.attention import cached_attention_chunk

    return jax.vmap(cached_attention_chunk)(q, k_cache, v_cache, q_pos)


def generate(net, prompt_ids, n_tokens: int, temperature: float = 1.0,
             top_k: int = 0, seed: int = 0, include_prompt: bool = False):
    """Jitted autoregressive sampler for a `gpt_configuration` network:
    ONE compiled prefill dispatch + ONE `lax.scan` decode dispatch, with
    per-block KV caches living in HBM for the whole generation.

    The reference's closest analogue is the stateful
    `MultiLayerNetwork.rnnTimeStep` (`MultiLayerNetwork.java:2196`) driven
    from a Python loop — one device round trip per token. A scanned
    decode is the difference between dispatch-bound and compute-bound
    generation.

    temperature <= 0 means greedy (argmax); `top_k > 0` restricts sampling
    to the k most probable tokens.

    Every sequence in the batch decodes the same n_tokens in lockstep —
    mixed output lengths and per-request admission live in
    `serving.decode_engine.DecodeEngine` (continuous batching), which
    reproduces this function's greedy decode argmax-exactly.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    plan = GPTPlan(net)
    if plan.composed:
        raise ValueError(
            "generate() runs TransformerBlock networks; a network of "
            "composed DecoderBlocks generates through "
            "serving.decode_engine.DecodeEngine / ModelServer.generate")
    layers = plan.layers
    emb_i, block_is = plan.emb_i, plan.block_is
    emb = plan.emb

    prompt = np.asarray(prompt_ids)
    if prompt.ndim == 1:
        prompt = prompt[None, :]
    B, T0 = prompt.shape
    L = T0 + n_tokens
    if emb.positional and L > emb.max_length:
        # RoPE models (positional=False) have no table to outgrow; the
        # caches size to L directly
        raise ValueError(f"prompt ({T0}) + n_tokens ({n_tokens}) exceeds "
                         f"max_length {emb.max_length}")
    cdt = plan.cdt

    from collections import OrderedDict

    cache_key = (B, T0, n_tokens, float(temperature), int(top_k))
    gen_cache = net.__dict__.setdefault("_gen_cache", OrderedDict())
    if cache_key in gen_cache:
        gen_cache.move_to_end(cache_key)  # LRU hit
        prefill, decode = gen_cache[cache_key]
        return _run_generation(net, prefill, decode, prompt, n_tokens, seed,
                               include_prompt)

    @jax.jit
    def prefill(params, ids, key):
        bp = plan.cast_blocks(params)
        x = bp[emb_i]["W"][ids]
        if emb.positional:
            x = x + bp[emb_i]["P"][:T0]
        x = x.astype(cdt)
        caches = []
        for i in block_is:
            p = bp[i]
            layer = layers[i]
            q, k, v = _block_heads(layer, p, x, jnp.arange(T0))
            att = _prefill_block_attention(layer, q, k, v)
            d = x.shape[-1]
            att = att.reshape(B, T0, d) @ p["Wo"] + p["bo"]
            x = _block_ffn(layer, p, x + att)
            # fixed-size caches so the decode scan has one static shape;
            # positions >= T0 are filled during decode. Layouts are the
            # TPU decode-friendly ones: K as (B, Hkv, hd, L) so the score
            # einsum contracts hd with L on the minor (lane) axis, V as
            # (B, Hkv, L, hd) so the weighted sum contracts L with hd
            # minor — the (B, L, H, hd) layout made each step's cache read
            # a strided transpose and dominated decode device time. Under
            # GQA the caches hold only the Hkv grouped heads: cache bytes
            # — the decode bandwidth bound — shrink by H/Hkv.
            hd = k.shape[-1]
            Hkv = layer._kv_heads
            kc = jnp.transpose(k, (0, 2, 3, 1))          # (B, Hkv, hd, T0)
            vc = jnp.transpose(v, (0, 2, 1, 3))          # (B, Hkv, T0, hd)
            kc = jnp.concatenate(
                [kc, jnp.zeros((B, Hkv, hd, L - T0), k.dtype)], axis=3)
            vc = jnp.concatenate(
                [vc, jnp.zeros((B, Hkv, L - T0, hd), v.dtype)], axis=2)
            caches.append((kc, vc))
        logits = plan.final_logits(bp, params, x[:, -1])
        return _sample_logits(logits, key, temperature, top_k), caches

    @jax.jit
    def decode(params, tok0, caches, key0):
        from deeplearning4j_tpu.ops.attention import cached_attention_step

        bp = plan.cast_blocks(params)

        def body(carry, t):
            tok, caches, key = carry
            key, sub = jax.random.split(key)
            pos = T0 + t  # position of the token being consumed
            x = bp[emb_i]["W"][tok]
            if emb.positional:
                x = x + bp[emb_i]["P"][pos]
            x = x.astype(cdt)
            new_caches = []
            for bi, i in enumerate(block_is):
                p = bp[i]
                layer = layers[i]
                # heads computed on (B, 1, d) — the same operand ranks the
                # prefill uses, so XLA picks the same matmul accumulation
                # (bf16 argmax stability depends on it); squeezed to the
                # (B, H, hd) step shape after
                q, k, v = _block_heads(layer, p, x[:, None, :], pos)
                q, k, v = q[:, 0], k[:, 0], v[:, 0]
                kc, vc = caches[bi]
                # k (B,Hkv,hd) -> one (B,Hkv,hd,1) lane column at pos;
                # v -> one (B,Hkv,1,hd) row at pos
                kc = jax.lax.dynamic_update_slice(
                    kc, k[..., None], (0, 0, 0, pos))
                vc = jax.lax.dynamic_update_slice(
                    vc, v[:, :, None, :], (0, 0, pos, 0))
                att = cached_attention_step(q, kc, vc, pos)
                att = att @ p["Wo"] + p["bo"]
                x = _block_ffn(layer, p, x + att)
                new_caches.append((kc, vc))
            logits = plan.final_logits(bp, params, x)
            nxt = _sample_logits(logits, sub, temperature, top_k)
            return (nxt, new_caches, key), nxt
        _, toks = jax.lax.scan(
            body, (tok0, caches, key0), jnp.arange(n_tokens - 1))
        return jnp.swapaxes(toks, 0, 1)  # (B, n_tokens - 1)

    gen_cache[cache_key] = (prefill, decode)
    # bound the cache: each entry pins a compiled prefill+decode pair (XLA
    # executables) for the net's lifetime — serving varied prompt lengths
    # must not leak executables, so evict least-recently-used beyond 8
    while len(gen_cache) > _GEN_CACHE_MAX:
        gen_cache.popitem(last=False)
    return _run_generation(net, prefill, decode, prompt, n_tokens, seed,
                           include_prompt)


def _run_generation(net, prefill, decode, prompt, n_tokens, seed,
                    include_prompt):
    """Drive a (cached) compiled prefill/decode pair."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    B = prompt.shape[0]
    if n_tokens == 0:
        return np.asarray(prompt if include_prompt
                          else np.zeros((B, 0), np.int32))
    key = jax.random.PRNGKey(seed)
    kp, kd = jax.random.split(key)
    ids = jnp.asarray(prompt.astype(np.int32))
    # token 0 comes from the prefill's last-position logits; each decode
    # step consumes the previous token and emits the next
    tok0, caches = prefill(net._params, ids, kp)
    gen = (jnp.concatenate([tok0[:, None],
                            decode(net._params, tok0, caches, kd)], axis=1)
           if n_tokens > 1 else tok0[:, None])
    return (np.concatenate([prompt, np.asarray(gen)], axis=1)
            if include_prompt else np.asarray(gen))
