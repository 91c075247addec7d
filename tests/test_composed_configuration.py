"""`models.transformer.composed_configuration`: the one builder under the
five composed families. Each named function still returns, for a toy of
its family, the configuration the parent commit (PR 44) built when every
one of them wrote the opening chain, the trailing norm and the head out
itself: `to_json()` text equal, held by its sha-256 recorded there.
"""
import hashlib
import inspect
import re

import pytest

from deeplearning4j_tpu.models import transformer as T
from deeplearning4j_tpu.nn.updater import Updater

CASES = {
    "hybrid_moe": (lambda: T.hybrid_moe_configuration(
        97, 64, ["mamba", "attention", "mamba"], n_heads=4, n_kv_heads=2,
        attention_multiplier=0.1, mamba_heads=8, mamba_head_dim=16,
        mamba_state=16, mamba_conv=4, mamba_chunk=8, n_experts=8, top_k=2,
        expert_width=32, shared_width=48, experts_held=(0, 4),
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, eps=1e-5, seed=3, learning_rate=1e-3,
        updater=Updater.SGD),
        "731f66b1c9e7089ba5b793fea03f388a3437410b53d796f8a565bc7dc6e375bf"),
    "hybrid_linear": (lambda: T.hybrid_linear_configuration(
        97, 64, ["linear_attention", "full_attention", "linear_attention"],
        n_heads=4, n_kv_heads=4, linear_heads=2, linear_key_dim=8,
        linear_value_dim=16, linear_conv=4, allow_neg_eigval=True,
        ffn_width=48, eps=1e-6),
        "6014646d118c1dfae09533a3bb50ccb3e53bf2d6ef3ca39dc634b532f3ff9345"),
    "hybrid_sublayer": (lambda: T.hybrid_sublayer_configuration(
        97, 64, "MEM*E", n_heads=4, n_kv_heads=2, head_dim=32,
        mamba_heads=4, mamba_head_dim=16, mamba_state=16, mamba_groups=2,
        mamba_chunk=8, n_experts=8, top_k=2, expert_width=24,
        shared_width=40, routed_scale=2.5, experts_held=(0, 8), eps=1e-5),
        "4713e0245b02cf2bbb4d18317744cb3065b02514b7d2fe72f4ed344d8e5ad726"),
    "longcat": (lambda: T.longcat_configuration(
        97, 64, 2, n_heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4,
        v_dim=8, rope_theta=1e4, ffn_width=48, n_experts=8,
        n_zero_experts=4, top_k=3, expert_width=24, routed_scale=6.0,
        experts_held=(0, 8), eps=1e-5, updater=Updater.SGD),
        "439a46711038de5a9e899d49450f4a14820db0863b42e50d5fb6169fe3014623"),
    "deepseek_v2": (lambda: T.deepseek_v2_configuration(
        97, 64, 3, n_heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=8,
        v_dim=8, rope_scaling=dict(
            kind="yarn", factor=4.0, original_max=16, beta_fast=4.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        n_dense_layers=1, ffn_width=48, n_experts=16, top_k=3,
        expert_width=24, shared_width=48, routed_scale=16.0, n_groups=4,
        topk_groups=2, experts_held=(0, 16), eps=1e-6),
        "43ecf5258c2017382e5f8873202db3ca3ae30235380e67f46d3ae3c069f00bbc"),
}


@pytest.mark.parametrize("family", sorted(CASES))
def test_a_family_builds_the_configuration_it_built_alone(family):
    make, parents = CASES[family]
    # (PR 46 gave the latent mixer a field the parent's text lacks,
    # `head_gate`, false in every family here)
    text = re.sub(r',\s*"head_gate": false', "", make().to_json())
    # (and PR 49 the attention mixer's `rope` and `window`, null, and the
    # routed feed-forward's `shared_scale`, 1.0, in every family here)
    text = re.sub(r',\s*"(rope|window)": null', "", text)
    text = re.sub(r',\s*"shared_scale": 1\.0', "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == parents, (
        f"{family}: to_json() is not the parent's ({len(text)} characters)")
    # and it is the shared builder's: no chain of its own
    src = inspect.getsource(getattr(T, f"{family}_configuration"))
    assert "composed_configuration(" in src
    assert "NeuralNetConfiguration" not in src and "OutputLayer" not in src


def test_the_two_heads():
    blocks = lambda: [T.DecoderBlock(
        n_in=32, n_out=32, mixer=T.AttentionMixer(n_heads=2, n_kv_heads=2),
        ffn=T.GatedMLP(width=48), norm=T.RMSNorm(eps=1e-5))]
    untied = T.composed_configuration(53, 32, blocks(), eps=1e-5)
    tied = T.composed_configuration(53, 32, blocks(), eps=1e-5,
                                    tied_head=True, logits_scaling=4.0,
                                    embedding_multiplier=3.0)
    kinds = lambda conf: [type(layer).__name__ for layer in conf.layers]
    assert kinds(untied) == ["TokenEmbedding", "DecoderBlock",
                             "RMSNormalization", "RnnOutputLayer"]
    assert kinds(tied)[-1] == "TiedRnnOutputLayer"
    assert not untied.layers[-1].has_bias
    assert tied.layers[-1].logits_scaling == 4.0
    assert tied.layers[0].multiplier == 3.0 \
        and untied.layers[0].multiplier == 1.0
