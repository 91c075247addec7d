"""`chip_smoke.py` on the CPU: the phases are importable functions, so
tier-1 drives the same code at toy shapes (kernels declined — the CPU
never dispatches them) and pins the two contracts a chip-less machine
can check: the script refuses to run without a TPU, and the compile
cache lands where `JAX_COMPILATION_CACHE_DIR` says or at one fixed
in-checkout path."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import chip_smoke

REPO = Path(__file__).resolve().parents[1]
GPT = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2)
SERVE = dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
             n_short=3, short_len=8, long_len=40, n_tokens=8, int8_tokens=4)


def test_train_phase_toy():
    out = chip_smoke.phase_train(
        GPT, dict(T=32, batch=4, block=16, steps=3), kernels=False)
    assert len(out["losses"]) == 3 and out["losses"][2] < out["losses"][0]
    assert "flash_tile" not in out  # asserted only where kernels dispatch


def test_train_phase_demands_an_engaged_kernel_when_told_to():
    # on the CPU no flash tile can engage: with kernels=True the phase
    # must FAIL, not report a step that merely ran
    with pytest.raises(chip_smoke.SmokeFailure, match="no flash"):
        chip_smoke.phase_train(
            GPT, dict(T=32, batch=4, block=16, steps=2), kernels=True)


def test_lstm_phase_toy():
    out = chip_smoke.phase_lstm(
        dict(vocab=32, hidden=128, T=8, batch=16, steps=2), kernels=False)
    assert len(out["losses"]) == 2 and np.all(np.isfinite(out["losses"]))


def test_serve_phase_toy():
    out = chip_smoke.phase_serve(GPT, SERVE, kernels=False)
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3  # the 40-token prompt, 16 at a time
    assert out["dispatches"]["decode_chunk"] >= 1
    walked, table = out["kv_pages"]
    assert 0 < walked < table
    # on the CPU both engines ARE the gather path: tokens identical
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    assert out["agreement"]["tie_margins_nats"] == []
    # counted on every backend, judged only where kernels dispatch
    assert set(out["pool_layout_copies"]) == {"decode_step",
                                              "decode_chunked"}
    json.dumps(out)  # the summary line must serialize


def test_hybrid_phase_toy():
    import jax.numpy as jnp

    hyb = dict(vocab_size=64, d_model=64, layer_types=("mamba", "attention"),
               n_heads=4, n_kv_heads=2, attention_multiplier=0.1,
               mamba_heads=8, mamba_head_dim=16, mamba_state=16,
               mamba_chunk=8, n_experts=8, top_k=2, expert_width=32,
               shared_width=48, experts_held=(0, 4),
               embedding_multiplier=12.0, residual_multiplier=0.22,
               logits_scaling=16.0)
    out = chip_smoke.phase_hybrid(
        hyb, dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
                  n_short=3, short_len=8, long_len=40, n_tokens=8),
        kernels=False, dtype=jnp.float32)
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3
    # on the CPU both engines ARE the XLA products: tokens identical
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    # state (f32) and pools are counted together, by shape
    assert set(out["pool_layout_copies"]) == {"decode_step",
                                              "decode_chunked"}
    assert out["state_bytes_per_slot"] == 8 * 16 * 16 * 4 + 160 * 3 * 4
    json.dumps(out)


def test_linear_phase_toy():
    import jax.numpy as jnp

    lin = dict(chip_smoke.LINEAR, vocab_size=64, hidden_size=64,
               intermediate_size=48, num_attention_heads=4,
               num_key_value_heads=4, linear_num_key_heads=2,
               linear_num_value_heads=2, linear_key_head_dim=8,
               linear_value_head_dim=16)
    out = chip_smoke.phase_linear(
        lin, dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
                  n_short=3, short_len=8, long_len=40, n_tokens=8),
        kernels=False, dtype=jnp.float32)
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3
    # float32 against the float32 reference: the served tokens are its own
    assert max(out["reference_gaps"]) < 1e-4
    # on the CPU both engines ARE the XLA form of the step
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    assert out["pool_layout_copies"].keys() == {"decode_step",
                                                "decode_chunked"}
    assert out["state_bytes_per_slot"] == 8 * 32 * 4 + 3 * 64 * 4
    assert out["kv_bytes_per_token"] == 2 * 4 * 16 * 4
    json.dumps(out)


def test_linear_phase_publishes_olmo_hybrids_widths():
    from perfbench.families import olmo_hybrid as fam

    sz = fam.sizes(chip_smoke.LINEAR)
    assert (sz["d"], sz["f"], sz["H"], sz["hd"]) == (3840, 11008, 30, 128)
    assert (sz["lh"], sz["lk"], sz["lv"]) == (30, 96, 192)
    assert sz["layer_types"] == ("linear_attention", "full_attention")


def test_sublayer_phase_toy():
    import jax.numpy as jnp

    sub = dict(chip_smoke.SUBLAYER, vocab_size=64, hidden_size=64,
               num_hidden_layers=5, hybrid_override_pattern="MEM*E",
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
               n_groups=2, chunk_size=8, n_routed_experts=4,
               num_experts_per_tok=2, moe_intermediate_size=24,
               moe_shared_expert_intermediate_size=40,
               deployment=dict(n_routed_experts_published=8,
                               experts_held_first=4))
    out = chip_smoke.phase_sublayer(
        sub, dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
                  n_short=3, short_len=8, long_len=40, n_tokens=8),
        kernels=False, dtype=jnp.float32)
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3
    # float32 against the float32 reference: the served tokens are its own
    assert max(out["reference_gaps"]) < 1e-4
    # on the CPU both engines ARE the XLA products
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    assert out["pool_layout_copies"].keys() == {"decode_step",
                                                "decode_chunked"}
    # two Mamba blocks: a float32 (4, 16, 16) state and three taps of the
    # 64 + 2 x 2 x 16 convolution channels; one attention block of 2 K/V
    # heads of 32; two blocks that keep nothing
    assert out["state_bytes_per_slot"] == 2 * (4 * 16 * 16 * 4
                                               + 3 * 128 * 4)
    assert out["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert out["stateless_blocks"] == 2
    assert 0.25 < out["held_share_of_choices"] < 0.75
    json.dumps(out)


def test_sublayer_phase_publishes_nemotron_nanos_widths():
    from perfbench.families import nemotron_h as fam

    sz = fam.sizes(chip_smoke.SUBLAYER)
    assert (sz["d"], sz["H"], sz["Hkv"], sz["hd"]) == (2688, 32, 2, 128)
    assert (sz["mh"], sz["mp"], sz["mn"], sz["mg"]) == (64, 64, 128, 8)
    assert (sz["E"], sz["held"], sz["topk"]) == (128, (0, 64), 6)
    assert (sz["f"], sz["fs"], sz["route_scale"]) == (1856, 3712, 2.5)
    assert sz["pattern"] == "M*E"


def test_latent_phase_toy():
    import jax.numpy as jnp

    lat = dict(chip_smoke.LATENT, vocab_size=64, hidden_size=64,
               num_layers=2, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, ffn_hidden_size=48, expert_ffn_hidden_size=24,
               n_routed_experts=4, zero_expert_num=4, moe_topk=3,
               rope_theta=1e4,
               deployment=dict(n_routed_experts_published=8,
                               experts_held_first=4))
    out = chip_smoke.phase_latent(
        lat, dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
                  n_short=3, short_len=8, long_len=40, n_tokens=8),
        kernels=False, dtype=jnp.float32)
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3
    # float32 against the float32 reference: the served tokens are its own
    assert max(out["reference_gaps"]) < 1e-4
    # on the CPU both engines ARE the XLA forms
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    assert out["pool_layout_copies"].keys() == {"decode_step",
                                                "decode_chunked"}
    # two layers of two sub-layers: a float32 latent of 16 + 4 in each
    assert out["latent_blocks"] == 4
    assert out["latent_bytes_per_token"] == 4 * 20 * 4
    assert 0.1 < out["zero_share_of_choices"] < 0.6
    json.dumps(out)


def test_latent_phase_toy_of_the_one_sub_layer_family():
    """The same stage on the `deepseek_v2` family: one pool a layer,
    counted from the net's mixers; no zero experts; one group held."""
    import jax.numpy as jnp

    lat = dict(chip_smoke.LATENT_H128, vocab_size=64, hidden_size=64,
               num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
               intermediate_size=48, moe_intermediate_size=24,
               n_routed_experts=4, n_group=4, topk_group=2,
               num_experts_per_tok=3,
               rope_scaling=dict(chip_smoke.LATENT_H128["rope_scaling"],
                                 factor=4,
                                 original_max_position_embeddings=16,
                                 beta_fast=4),
               deployment=dict(n_routed_experts_published=16,
                               experts_held_first=4))
    out = chip_smoke.phase_latent(
        lat, dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
                  n_short=3, short_len=8, long_len=40, n_tokens=8),
        kernels=False, dtype=jnp.float32, family="deepseek_v2")
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3
    assert max(out["reference_gaps"]) < 1e-4
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    # three layers of one sub-layer: a float32 latent of 16 + 8 in each
    assert out["latent_blocks"] == 3
    assert out["latent_bytes_per_token"] == 3 * 24 * 4
    assert out["zero_share_of_choices"] == 0
    assert out["held_share_of_choices"] <= out["rows_local_share"] < 1.0
    json.dumps(out)


def test_latent_phase_toy_of_the_family_with_both_cache_kinds():
    """The same stage on the `ling_flash` family: a delta-rule layer with
    a decay a key channel beside a gated latent-attention layer, so the
    engine keeps recurrent slots AND latent pages; both block counts come
    from the net's mixers."""
    import jax.numpy as jnp

    lat = dict(chip_smoke.LATENT_KDA, vocab_size=64, hidden_size=64,
               num_hidden_layers=4, num_attention_heads=4, head_dim=8,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, intermediate_size=48, moe_intermediate_size=24,
               moe_shared_expert_intermediate_size=24, num_experts=4,
               n_group=4, topk_group=2, num_experts_per_tok=3,
               rope_theta=1e4, expert_swiglu_limit_list=[0] * 4,
               share_expert_swiglu_limit_list=[0] * 4,
               deployment=dict(num_experts_published=16,
                               experts_held_first=4))
    out = chip_smoke.phase_latent(
        lat, dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
                  n_short=3, short_len=8, long_len=40, n_tokens=8),
        kernels=False, dtype=jnp.float32, family="ling_flash")
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3
    assert max(out["reference_gaps"]) < 1e-4
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    # four layers of period two: two float32 latents of 16 + 4, and two
    # states of 4 heads of 8 x 8 with three taps of 3 x 32 columns
    assert (out["latent_blocks"], out["recurrent_blocks"]) == (2, 2)
    assert out["latent_bytes_per_token"] == 2 * 20 * 4
    assert out["state_bytes_per_slot"] == 2 * (8 * 32 * 4 + 3 * 96 * 4)
    assert out["zero_share_of_choices"] == 0
    assert out["held_share_of_choices"] <= out["rows_local_share"] < 1.0
    json.dumps(out)


def test_window_phase_toy():
    """The window stage at toy widths: window 8 and pages of 4, so the
    ring prompt's three entries wrap within a dozen tokens and the long
    prompt rides chunks past its ring."""
    import jax.numpy as jnp

    win = dict(chip_smoke.WINDOW, vocab_size=64, hidden_size=64,
               num_attention_heads=8, num_key_value_heads=2, head_dim=8,
               sliding_window=8, intermediate_size=24, num_experts=8,
               num_experts_per_tok=3, num_shared_experts=2,
               deployment=dict(num_experts_published=16,
                               experts_held_first=0))
    out = chip_smoke.phase_window(
        win, dict(n_slots=3, max_len=96, page_size=4, prefill_chunk=4,
                  n_short=2, short_len=8, ring_len=14, long_len=37,
                  n_tokens=24),
        kernels=False, dtype=jnp.float32)
    assert out["requests"] == 4 and out["tokens"] == 4 * 24
    assert out["prefill_chunks"] >= 10
    assert (out["window_blocks"], out["kv_blocks"]) == (3, 1)
    assert out["window_ring_pages"] == 3
    assert out["window_pages_in_use_peak"] == 3 * 3
    assert out["window_bytes_per_slot"] == 3 * 12 * 2 * 2 * 8 * 4
    assert 30.0 < out["window_attended_pct"] < 70.0
    assert max(out["reference_gaps"]) < 1e-4
    assert out["agreement"]["common_prefix_tokens"] == [24] * 4
    json.dumps(out)


def test_window_phase_publishes_command_a_pluss_widths():
    from perfbench.families import cohere2_moe as fam

    sz = fam.sizes(chip_smoke.WINDOW)
    assert (sz["d"], sz["H"], sz["Hkv"], sz["hd"], sz["W"], sz["f"],
            sz["n_shared"], sz["topk"], sz["E"], sz["held"], sz["L"]) \
        == (4096, 128, 8, 128, 4096, 4096, 4, 8, 128, (0, 8), 4)
    assert sz["layer_types"] == ("sliding_attention",) * 3 \
        + ("full_attention",)
    shape = chip_smoke.WINDOW_SERVE
    # the ring prompt fills the 4,096 bucket's last page and decodes past
    # a window and a page; the long one outruns every bucket and the ring
    assert shape["ring_len"] + shape["n_tokens"] > 4096 + 128
    assert shape["long_len"] > 33 * 128
    assert shape["long_len"] + shape["n_tokens"] <= shape["max_len"]


def test_latent_phase_publishes_ling_flashs_widths():
    from perfbench.families import ling_flash as fam

    sz = fam.sizes(chip_smoke.LATENT_KDA)
    assert (sz["d"], sz["H"], sz["kr"]) == (2560, 32, 512)
    assert (sz["nope"], sz["rope"], sz["vd"]) == (128, 64, 128)
    assert (sz["lh"], sz["lk"], sz["lv"], sz["conv"], sz["gate_lower"]) \
        == (32, 128, 128, 4, -5.0)
    assert (sz["ffn"], sz["f"], sz["shared"]) == (6144, 768, 768)
    assert (sz["L"], sz["L_dense"], sz["L_moe"]) == (2, 1, 1)
    assert sz["layer_types"] == ("linear_attention", "full_attention")
    assert (sz["E"], sz["held"], sz["groups"], sz["topk_groups"],
            sz["topk"], sz["route_scale"]) == (512, (0, 64), 8, 4, 8, 2.5)


def test_latent_phase_publishes_deepseek_v2s_widths():
    from perfbench.families import deepseek_v2 as fam

    sz = fam.sizes(chip_smoke.LATENT_H128)
    assert (sz["d"], sz["H"], sz["qr"], sz["kr"]) == (5120, 128, 1536, 512)
    assert (sz["nope"], sz["rope"], sz["vd"]) == (128, 64, 128)
    assert (sz["ffn"], sz["f"], sz["shared"]) == (12288, 1536, 3072)
    assert (sz["L"], sz["L_dense"], sz["L_moe"]) == (2, 1, 1)
    assert (sz["E"], sz["held"], sz["groups"], sz["topk_groups"],
            sz["topk"], sz["route_scale"]) == (160, (0, 20), 8, 3, 6, 16.0)
    assert sz["yarn"] == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)


def test_latent_phase_publishes_longcat_flashs_widths():
    from perfbench.families import longcat_flash as fam

    sz = fam.sizes(chip_smoke.LATENT)
    assert (sz["d"], sz["H"], sz["qr"], sz["kr"]) == (6144, 64, 1536, 512)
    assert (sz["nope"], sz["rope"], sz["vd"]) == (128, 64, 128)
    assert (sz["ffn"], sz["f"], sz["L"]) == (12288, 2048, 1)
    assert (sz["E"], sz["Z"], sz["held"], sz["topk"], sz["route_scale"]) \
        == (512, 256, (0, 16), 12, 6.0)


# lines as XLA:TPU prints them (PR 26's parent, layouts and configs
# kept, operand lists cut): what the count must and must not see
_CANNED_HLO = """\
HloModule jit_decode_chunked, is_scheduled=true, input_output_alias={ {0}: (30, {}, may-alias) }
%fused_computation.3 (param_0.9: bf16[137,16,128,128], param_1.14: s32[32]) -> bf16[137,16,128,128] {
  %param_0.9 = bf16[137,16,128,128]{2,1,3,0:T(8,128)(2,1)S(1)} parameter(0)
  ROOT %scatter.6 = bf16[137,16,128,128]{2,1,3,0:T(8,128)(2,1)S(1)} scatter(%param_0.9, %custom-call.3, %transpose.99), update_window_dims={1,2}
}
%fused_computation.9 (param_0.755: bf16[4,8,16,128]) -> bf16[4,8,16,128] {
  %copy.40 = bf16[4,8,16,128]{3,1,2,0:T(8,128)(2,1)} copy(%param_0.755)
  ROOT %transpose.7 = bf16[32,16,128]{1,2,0:T(8,128)(2,1)} transpose(%copy.40), dimensions={0,2,1}
}
ENTRY %main.33 (caches_0__0_.1: bf16[137,16,128,128], pos.1: s32[32]) -> (bf16[137,16,128,128], s32[32]) {
  %caches_0__0_.1 = bf16[137,16,128,128]{3,2,1,0:T(8,128)(2,1)} parameter(30), sharding={replicated}, metadata={op_name="caches[0][0]"}
  %copy.42 = bf16[137,16,128,128]{2,1,3,0:T(8,128)(2,1)S(1)} copy(%caches_0__0_.1), sharding={replicated}, metadata={op_name="caches[0][0]"}
  %fusion.3 = bf16[137,16,128,128]{2,1,3,0:T(8,128)(2,1)S(1)} fusion(%copy.42, %fusion.217), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(decode_step)/kv.write/scatter"}
  %copy-start.2 = (bf16[137,16,128,128]{3,1,2,0:T(8,128)(2,1)S(1)}, bf16[137,16,128,128]{3,1,2,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%fusion.3)
  %copy-done.2 = bf16[137,16,128,128]{3,1,2,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.2)
  %transpose.1 = bf16[137,16,128,128]{3,2,1,0:T(8,128)(2,1)} transpose(%copy-done.2), dimensions={0,1,3,2}
  %copy-start.4 = (f32[2048,2048]{1,0:T(8,128)S(1)}, f32[2048,2048]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%params_1___Wo__.1)
  %kv.attend.2 = bf16[32,1,16,128]{3,2,1,0:T(8,128)(2,1)} custom-call(%copy.44, %pos.1, %transpose.1), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[32]{0}, bf16[137,16,128,128]{3,2,1,0}}
  %kv.write.1 = (bf16[137,16,128,128]{3,2,1,0:T(8,128)(2,1)}, bf16[137,16,128,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(%pos.1, %caches_0__0_.1), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{0}: (1, {})}
  ROOT %copy.52 = bf16[137,16,128,128]{3,2,1,0:T(8,128)(2,1)} copy(%transpose.1)
}
"""


def test_pool_layout_copies_counts_pool_shaped_copies_only():
    """`copy`, `copy-start` and `transpose` of a pool's shape count,
    `ROOT` or not, tuple-typed or not; the scatter, the kernels, the
    fusion that wraps them, `copy-done` (its `copy-start` counted) and
    copies of anything smaller or of a weight do not."""
    count = chip_smoke.pool_layout_copies
    assert count(_CANNED_HLO, {"bf16[137,16,128,128]"}) == 4
    assert count(_CANNED_HLO, {"bf16[4,8,16,128]"}) == 1
    assert count(_CANNED_HLO, {"s8[137,16,128,128]", "f32[137,16,128]"}) == 0
    assert count(_CANNED_HLO, {"f32[2048,2048]"}) == 1  # a weight's prefetch
    assert count("", {"bf16[137,16,128,128]"}) == 0


# lines as XLA:TPU prints them (PR 48's parent, DeepSeek-V2's
# `decode_chunked`; layouts kept, operand lists and configs cut): a
# weight re-laid in the entry, handed to the loop and re-tiled there
# alone and inside a fusion, and what is NOT a weight's re-lay: the
# absorbed query of the same elements, a prefetch in the same layout
_CANNED_WEIGHTS_HLO = """\
HloModule jit_decode_chunked, is_scheduled=true
%fused_computation.6.clone (param_0.1: bf16[1536,8192], param_1.2: bf16[128,1,1536]) -> bf16[128,1,8192] {
  %param_0.1 = bf16[1536,8192]{0,1:T(8,128)(2,1)} parameter(0)
  %param_1.2 = bf16[128,1,1536]{2,0,1:T(8,128)(2,1)S(1)} parameter(1)
  %bitcast.9 = bf16[128,32,2,1536]{3,2,1,0:T(8,128)(2,1)} bitcast(%param_0.1)
  %copy.77 = bf16[128,32,2,1536]{3,0,2,1:T(8,128)(2,1)} copy(%bitcast.9), metadata={op_name="jit(decode_chunked)/while/body/closed_call/mla.q/dot_general"}
  ROOT %convolution.5 = bf16[128,1,8192]{2,0,1:T(8,128)(2,1)} convolution(%param_1.2, %copy.77), dim_labels=0bf_io0->0bf
}
%wide.region_0.46 (wide.param: (s32[], bf16[1536,8192], bf16[128,128,512], bf16[128,128,512])) -> (s32[], bf16[1536,8192], bf16[128,128,512], bf16[128,128,512]) {
  %wide.param = (s32[]{:T(128)}, bf16[1536,8192]{0,1:T(8,128)(2,1)}, bf16[128,128,512]{1,2,0:T(8,128)(2,1)}, bf16[128,128,512]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.7 = bf16[1536,8192]{0,1:T(8,128)(2,1)} get-tuple-element(%wide.param), index=1
  %reshape.31 = bf16[128,32,2,1536]{3,2,1,0:T(2,128)(2,1)S(1)} reshape(%get-tuple-element.7)
  %fusion.30 = bf16[128,1,8192]{2,0,1:T(8,128)(2,1)} fusion(%get-tuple-element.7, %rsqrt.4), kind=kOutput, calls=%fused_computation.6.clone
  %get-tuple-element.9 = bf16[128,128,512]{2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.param), index=3
  %fusion.309 = bf16[128,512,128]{2,1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.9, %bitcast.347), kind=kOutput, calls=%fused_computation.25
  %copy.234 = bf16[128,512,128]{1,0,2:T(8,128)(2,1)S(1)} copy(%fusion.309), metadata={op_name="jit(decode_chunked)/while/body/closed_call/mla.absorb/...hn,hnr->...hr/dot_general"}
  ROOT %tuple.3 = (s32[]{:T(128)}, bf16[1536,8192]{0,1:T(8,128)(2,1)}, bf16[128,128,512]{1,2,0:T(8,128)(2,1)}, bf16[128,128,512]{2,1,0:T(8,128)(2,1)}) tuple(%add.1, %get-tuple-element.7, %get-tuple-element.8, %get-tuple-element.9)
}
ENTRY %main.33 (bp_1___mx_Wqr__.1: bf16[1536,8192], bp_1___mx_Wkb__.1: bf16[128,128,512], bp_1___ff_Wd__.1: bf16[12288,5120]) -> bf16[128,1,8192] {
  %bp_1___mx_Wqr__.1 = bf16[1536,8192]{1,0:T(8,128)(2,1)} parameter(4), metadata={op_name="bp[1][\'mx_Wqr\']"}
  %bp_1___mx_Wkb__.1 = bf16[128,128,512]{2,1,0:T(8,128)(2,1)} parameter(7), metadata={op_name="bp[1][\'mx_Wkb\']"}
  %bp_1___ff_Wd__.1 = bf16[12288,5120]{1,0:T(8,128)(2,1)} parameter(9)
  %copy.146 = bf16[1536,8192]{0,1:T(8,128)(2,1)S(1)} copy(%bp_1___mx_Wqr__.1)
  %copy.147 = bf16[128,128,512]{1,2,0:T(8,128)(2,1)S(1)} copy(%bp_1___mx_Wkb__.1)
  %copy-start.1 = (bf16[128,128,512]{1,2,0:T(8,128)(2,1)}, bf16[128,128,512]{1,2,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%copy.147)
  %copy-done.1 = bf16[128,128,512]{1,2,0:T(8,128)(2,1)} copy-done(%copy-start.1)
  %copy-start.2 = (bf16[12288,5120]{1,0:T(8,128)(2,1)S(1)}, bf16[12288,5120]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%bp_1___ff_Wd__.1)
  %tuple.9 = (s32[]{:T(128)}, bf16[1536,8192]{0,1:T(8,128)(2,1)}, bf16[128,128,512]{1,2,0:T(8,128)(2,1)}, bf16[128,128,512]{2,1,0:T(8,128)(2,1)}) tuple(%constant.1, %copy.146, %copy-done.1, %bp_1___mx_Wkb__.1)
  %while.1 = (s32[]{:T(128)}, bf16[1536,8192]{0,1:T(8,128)(2,1)}, bf16[128,128,512]{1,2,0:T(8,128)(2,1)}, bf16[128,128,512]{2,1,0:T(8,128)(2,1)}) while(%tuple.9), condition=%wide.cond, body=%wide.region_0.46
  ROOT %fusion.9 = bf16[128,1,8192]{2,0,1:T(8,128)(2,1)} fusion(%while.1), kind=kLoop, calls=%fused_computation.77
}
"""


def test_route_sorts_counts_the_sorts_of_a_choice_only():
    """Counted: a `sort` traced under `moe.route`, the groups' under
    `moe.groups` inside it. Not counted: `sort_by_expert`'s under
    `moe.experts`, a sort outside any routed block, and another opcode
    under the router's scope."""
    sort = ('  %sort.{n} = (f32[128,512]{{0,1:T(8,128)}}, s32[128,512]{{0,1}}) '
            'sort(%copy.{n}, %iota.{n}), dimensions={{1}}, is_stable=true, '
            'to_apply=%lt.{n}, metadata={{op_name="jit(decode_step)/{at}" '
            'stack_frame_id=20}}, backend_config={{"flag_configs":[]}}')
    text = "\n".join([
        sort.format(n=1, at="blocks/moe.route/top_k"),
        sort.format(n=2, at="blocks/moe.route/moe.groups/top_k"),
        sort.format(n=3, at="blocks/moe.experts/sort"),
        sort.format(n=4, at="sample/sort"),
        '  ROOT %scatter.5 = f32[65536]{0} scatter(%a, %b, %c), metadata='
        '{op_name="jit(decode_step)/blocks/moe.route/scatter"}'])
    assert chip_smoke.route_sorts(text) == 2
    assert chip_smoke.route_sorts("") == 0


def test_weight_layout_copies_follows_a_weight_and_nothing_else():
    """Counted: the entry's two copies of a parameter of a weight's
    shape, and in the loop's body, which is handed the copy, its
    `reshape` and the `copy` inside the fusion that multiplies by it.
    Not counted: the copy of the absorbed query (a product's result
    with a weight's elements), `copy-start`s into the same layout, and
    anything of a parameter that is no weight."""
    ops = chip_smoke.weight_layout_ops
    weights = {"bf16[1536,8192]", "bf16[128,128,512]", "bf16[12288,5120]"}
    assert [op.split("{")[0] for op in ops(_CANNED_WEIGHTS_HLO, weights)] == [
        "copy bf16[1536,8192]", "copy bf16[128,128,512]",
        "reshape bf16[128,32,2,1536]", "copy bf16[128,32,2,1536]"]
    assert chip_smoke.weight_layout_copies(_CANNED_WEIGHTS_HLO, weights) == 4
    assert ops(_CANNED_WEIGHTS_HLO, {"bf16[128,128,512]"}) == [
        "copy bf16[128,128,512]{1,2,0:T(8,128)(2,1)S(1)}"]
    assert ops(_CANNED_WEIGHTS_HLO, {"bf16[128,512,128]"}) == []
    assert ops("", weights) == []


def test_multichip_phase_toy_on_the_virtual_mesh():
    gpt = dict(GPT, n_heads=4)  # tp=4 needs four heads
    train = dict(T=32, batch=4, block=16, steps=3)
    one_chip = chip_smoke.phase_train(gpt, train, kernels=False)
    out = chip_smoke.phase_multichip(gpt, train, dict(SERVE, max_len=128),
                                     one_chip["losses"][0])
    assert out["train"]["mesh"] == {"data": 2, "model": 2}
    assert out["train"]["losses"][0] == pytest.approx(
        one_chip["losses"][0], rel=1e-3)
    assert out["tp4"]["common_prefix_tokens"] == [4, 4, 4]
    # an in-process pool places nothing: four replicas, one device
    assert out["replica_devices"] == [[0]] * 4
    json.dumps(out)


def test_tie_margin_reads_the_nets_own_forward():
    net = chip_smoke._gpt_net(GPT, 128)  # the context pads to 128s
    prompt = np.arange(8, dtype=np.int32)
    a = np.asarray(net.output(prompt[None])).argmax(-1)[0, -1:]
    a = np.concatenate([a, [1, 2, 3]]).astype(np.int32)
    b = a.copy()
    b[0] = (a[0] + 1) % GPT["vocab_size"]  # disagree on the first token
    margin = chip_smoke._tie_margin(net, prompt, a, b)
    probs = np.asarray(net.output(prompt[None]), np.float64)[0, -1]
    assert margin == pytest.approx(
        abs(np.log(probs[a[0]]) - np.log(probs[b[0]])), rel=1e-3)
    assert margin > 0  # a is the argmax, b is not


def test_main_refuses_a_cpu_platform(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line
    assert "no accelerator" in captured.err


class _FakeTpu:
    platform, device_kind, id = "tpu", "TPU v5 lite", 0

    def memory_stats(self):
        return {"bytes_in_use": 0}


def test_last_line_is_the_drivers_verdict(monkeypatch, capsys):
    """The driver reads the LAST stdout line and wants exactly
    {"ok", "device": {"platform", "kind", "count"}}; the rich summary
    (ending "claim": null) is the line before it."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setattr(chip_smoke, "phase_lstm",
                        lambda shape, kernels: {"losses": [1.0]})
    saved = jax.config.jax_compilation_cache_dir
    try:
        assert chip_smoke.main(["lstm"]) == 0
        summary, verdict = capsys.readouterr().out.splitlines()[-2:]
        assert json.loads(verdict) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
        assert summary.endswith('"claim": null}')
        assert json.loads(summary)["phases"]["lstm"]["ok"] is True

        def fails(shape, kernels):
            raise chip_smoke.SmokeFailure("wrong")

        monkeypatch.setattr(chip_smoke, "phase_lstm", fails)
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.main(["lstm"])
        verdict = capsys.readouterr().out.splitlines()[-1]
        assert json.loads(verdict)["ok"] is False
        assert set(json.loads(verdict)) == {"ok", "device"}
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_script_fails_without_a_chip_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=str(REPO),
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no accelerator" in proc.stderr


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    import jax

    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    saved = jax.config.jax_compilation_cache_dir
    try:
        # unset: one fixed, git-ignored path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first, second = enable_compile_cache(), enable_compile_cache()
        assert first == second == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
        # set: that directory, and no other path set in code
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_the_benchmark_knows_its_peaks_and_refuses_the_cpu():
    """The measuring entry point finds no TPU here and says so
    (`NoChipError`: what makes `perfbench/run.py` exit 3 and print no
    result); a share of a peak is only computed against a peak the
    table lists for the `device_kind`."""
    from perfbench.harness import device

    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    for kind in ("TPU v9 hypothetical", "cpu"):
        with pytest.raises(KeyError, match="no peaks for device_kind"):
            device.peaks(kind)
    with pytest.raises(device.NoChipError, match="measures only on a TPU"):
        device.require_chips(1)


def test_supervisor_states_each_childs_platform(tmp_path, monkeypatch):
    """One process per chip: a replica child never inherits the parent's
    accelerator by accident — its platform is the CPU unless the
    caller's `env` names another."""
    from deeplearning4j_tpu.serving.remote_replica import ReplicaSupervisor

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # what a chip parent has
    sup = ReplicaSupervisor(tmp_path / "m.zip", 1, scratch_dir=tmp_path)
    try:
        assert sup.child_platform == "cpu"
        assert sup._env["JAX_PLATFORMS"] == "cpu"
    finally:
        sup.stop()
    sup = ReplicaSupervisor(tmp_path / "m.zip", 1, scratch_dir=tmp_path,
                            env={"JAX_PLATFORMS": "tpu"})
    try:
        assert sup.child_platform == "tpu"
    finally:
        sup.stop()
