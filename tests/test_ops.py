"""Unit tests for activations and loss functions (reference analogue:
ND4J transform op tests + `LossFunctionGradientCheck` score paths)."""
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.activations import Activation, activation_fn
from deeplearning4j_tpu.ops.losses import LossFunction, loss_fn, loss_score


def test_relu_sigmoid_tanh_values():
    x = jnp.asarray([-2.0, -0.5, 0.0, 0.5, 2.0])
    np.testing.assert_allclose(activation_fn(Activation.RELU)(x),
                               [0, 0, 0, 0.5, 2.0])
    np.testing.assert_allclose(activation_fn(Activation.SIGMOID)(x),
                               1 / (1 + np.exp(-np.asarray(x))), rtol=1e-6)
    np.testing.assert_allclose(activation_fn(Activation.TANH)(x),
                               np.tanh(np.asarray(x)), rtol=1e-6)


def test_softmax_rows_sum_to_one():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 7)))
    s = activation_fn(Activation.SOFTMAX)(x)
    np.testing.assert_allclose(np.sum(np.asarray(s), axis=-1), np.ones(4), rtol=1e-6)


@pytest.mark.parametrize("act", list(Activation))
def test_all_activations_finite(act):
    x = jnp.asarray(np.linspace(-3, 3, 31))
    y = activation_fn(act)(x)
    assert np.all(np.isfinite(np.asarray(y)))


def test_mcxent_softmax_fused_matches_generic():
    rng = np.random.default_rng(1)
    pre = jnp.asarray(rng.normal(size=(8, 5)))
    labels = jnp.asarray(np.eye(5)[rng.integers(0, 5, 8)])
    fused = loss_score(LossFunction.MCXENT, Activation.SOFTMAX, labels, pre)
    probs = activation_fn(Activation.SOFTMAX)(pre)
    generic = loss_fn(LossFunction.MCXENT)(labels, probs)
    np.testing.assert_allclose(float(fused), float(generic), rtol=1e-5)


def test_xent_sigmoid_fused_matches_generic():
    rng = np.random.default_rng(2)
    pre = jnp.asarray(rng.normal(size=(8, 3)))
    labels = jnp.asarray(rng.integers(0, 2, (8, 3)).astype(float))
    fused = loss_score(LossFunction.XENT, Activation.SIGMOID, labels, pre)
    probs = activation_fn(Activation.SIGMOID)(pre)
    generic = loss_fn(LossFunction.XENT)(labels, probs)
    np.testing.assert_allclose(float(fused), float(generic), rtol=1e-5)


def test_mse_loss_masked():
    labels = jnp.asarray([[1.0], [2.0], [3.0]])
    out = jnp.asarray([[1.0], [0.0], [3.0]])
    mask = jnp.asarray([1.0, 0.0, 1.0])
    # masked row (the wrong one) excluded -> loss 0
    assert float(loss_fn(LossFunction.MSE)(labels, out, mask)) == pytest.approx(0.0)
    assert float(loss_fn(LossFunction.MSE)(labels, out)) == pytest.approx(4.0 / 3.0)


def test_mcxent_extreme_logits_stable():
    pre = jnp.asarray([[1000.0, -1000.0], [-1000.0, 1000.0]])
    labels = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    v = float(loss_score(LossFunction.MCXENT, Activation.SOFTMAX, labels, pre))
    assert np.isfinite(v) and v < 1e-3


# ---------------------------------------------------------------------------
# GQA grouped-einsum attention (r6: the training path's K/V grouping is a
# broadcast einsum, not a materialized jnp.repeat)


def test_full_attention_grouped_bit_parity_with_repeat():
    """`full_attention_grouped` must reproduce repeat-then-full_attention
    BITWISE: per-head dots are the same contractions on the same
    operands, only the HBM copies are gone."""
    from deeplearning4j_tpu.ops.attention import (
        full_attention,
        full_attention_grouped,
        mask_bias,
    )

    rng = np.random.default_rng(0)
    B, T, H, Hkv, D = 2, 7, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    kr = jnp.repeat(k, H // Hkv, axis=2)
    vr = jnp.repeat(v, H // Hkv, axis=2)
    for causal in (False, True):
        for mask in (None,
                     jnp.asarray(rng.integers(0, 2, (B, T)), jnp.float32)):
            bias = None if mask is None else mask_bias(mask)
            ref = np.asarray(full_attention(q, kr, vr, bias=bias,
                                            causal=causal))
            got = np.asarray(full_attention_grouped(q, k, v, bias=bias,
                                                    causal=causal))
            np.testing.assert_array_equal(got, ref)


def test_multi_head_attention_accepts_grouped_kv():
    """The dispatch takes un-repeated Hkv-headed K/V and agrees with the
    widened reference on both the full and blockwise paths."""
    from deeplearning4j_tpu.ops.attention import (
        full_attention,
        multi_head_attention,
    )

    rng = np.random.default_rng(1)
    B, T, H, Hkv, D = 2, 6, 4, 1, 8
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    kr = jnp.repeat(k, H // Hkv, axis=2)
    vr = jnp.repeat(v, H // Hkv, axis=2)
    ref = np.asarray(full_attention(q, kr, vr, causal=True))
    got = np.asarray(multi_head_attention(q, k, v, causal=True))
    # the grouped einsum contracts the same terms in another order:
    # float32 sums of 8 products reassociated differ by a few ulp
    # (1.2e-6 relative, met on this backend), never by an equation
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    # long-seq path (blockwise widens internally): same numerics as the
    # widened blockwise call
    from deeplearning4j_tpu.ops.attention import blockwise_attention

    ref_b = np.asarray(blockwise_attention(q, kr, vr, causal=True,
                                           block_size=4))
    got_b = np.asarray(multi_head_attention(q, k, v, causal=True,
                                            block_size=4))
    np.testing.assert_array_equal(got_b, ref_b)


def test_gqa_transformer_block_forward_bit_parity():
    """gpt-config parity pin for the satellite: a GQA block's forward
    through the grouped-einsum dispatch equals the historical
    materialized-repeat computation to float32 reassociation (the
    grouped path sums the same products in another order; the name
    dates from when the two happened to agree bit for bit)."""
    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops import attention as att_mod

    net = MultiLayerNetwork(gpt_configuration(
        vocab_size=32, d_model=32, n_heads=4, n_kv_heads=2,
        max_length=16, n_layers=2))
    net.init()
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 32, (3, 10))
    got = np.asarray(net.output(ids))

    # reference: force the historical repeat path by widening K/V before
    # the dispatch ever sees them
    orig = att_mod.multi_head_attention

    def widened_dispatch(q, k, v, **kw):
        if k.shape[2] != q.shape[2]:
            g = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        return orig(q, k, v, **kw)

    att_mod.multi_head_attention = widened_dispatch
    try:
        net2 = MultiLayerNetwork(gpt_configuration(
            vocab_size=32, d_model=32, n_heads=4, n_kv_heads=2,
            max_length=16, n_layers=2))
        net2.init()
        ref = np.asarray(net2.output(ids))
    finally:
        att_mod.multi_head_attention = orig
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# VMEM ceiling derived from the device generation (r6 advisor item)


def test_vmem_limit_per_generation_table():
    from deeplearning4j_tpu.ops.kernel_dispatch import (
        _VMEM_PER_CORE_BYTES,
        vmem_limit_for_kind,
    )

    for kind, physical in _VMEM_PER_CORE_BYTES.items():
        assert vmem_limit_for_kind(kind) == physical * 7 // 8, kind
    # v2/v3 cores carry 16 MiB: the ceiling must drop below the old
    # constant there, not overflow physical VMEM
    assert vmem_limit_for_kind("TPU v3") == 14 * 1024 * 1024
    assert vmem_limit_for_kind("TPU v5 lite") == 112 * 1024 * 1024


def test_vmem_limit_prefix_matching_and_unknown_kind():
    import pytest

    from deeplearning4j_tpu.ops.kernel_dispatch import vmem_limit_for_kind

    # longest prefix wins: "TPU v5 lite" must not resolve through
    # "TPU v5"'s row
    assert vmem_limit_for_kind("TPU v5 lite chip") == \
        vmem_limit_for_kind("TPU v5 lite")
    # the CPU backend (interpret mode) keeps the v4/v5-class ceiling ...
    assert vmem_limit_for_kind("cpu") == 112 * 1024 * 1024
    # ... but an accelerator the table does not know is an error, not a
    # guessed 128 MiB
    for kind in ("TPU v9 hypothetical", "", "NVIDIA H100"):
        with pytest.raises(ValueError, match="unknown device_kind"):
            vmem_limit_for_kind(kind)


def test_kernel_verdicts_report_a_raising_probe_with_its_message(monkeypatch):
    """The dispatch verdict is a public fact: a probe that raises is
    reported False WITH the compiler's message, a passing one True, each
    probed once, and a staging failure overrides an earlier pass."""
    from deeplearning4j_tpu.ops import kernel_dispatch as kd

    monkeypatch.setattr(kd, "_verdicts", {})
    assert kd.kernel_verdicts() == {}
    calls = []

    def mosaic_says_no(shape):
        calls.append(shape)
        raise RuntimeError("Mosaic failed to compile TPU kernel: "
                           "unsupported shape cast")

    assert kd.probe_verdict("fam", ("bf16", 1), mosaic_says_no,
                            ((1, 128),)) is False
    assert kd.probe_verdict("fam", ("bf16", 1), mosaic_says_no,
                            ((1, 128),)) is False
    assert calls == [(1, 128)]  # cached: the probe ran once
    assert kd.probe_verdict("fam", ("bf16", 8), lambda: True, ()) is True
    table = kd.kernel_verdicts()
    assert table["fam"][("bf16", 8)] == kd.KernelVerdict(True, "")
    bad = table["fam"][("bf16", 1)]
    assert bad.ok is False
    assert "RuntimeError" in bad.message
    assert "unsupported shape cast" in bad.message
    # the accessor hands out a copy
    table["fam"].clear()
    assert len(kd.kernel_verdicts()["fam"]) == 2
    kd.record_decline("fam", ("bf16", 8), "staging at (2, 8): boom")
    assert kd.kernel_verdicts()["fam"][("bf16", 8)] == \
        kd.KernelVerdict(False, "staging at (2, 8): boom")
    assert kd.probe_verdict("fam", ("bf16", 8), lambda: True, ()) is False


def test_vmem_limit_bytes_cached_and_positive():
    from deeplearning4j_tpu.ops import kernel_dispatch as kd

    kd._vmem_limit_cache.clear()
    v1 = kd.vmem_limit_bytes()
    assert v1 > 0
    assert kd.vmem_limit_bytes() is v1 or kd.vmem_limit_bytes() == v1
    assert kd._vmem_limit_cache  # verdict cached after first detection


# ---------------------------------------------------------------------------
# Pallas kernels under a device mesh: Mosaic cannot auto-partition, so a
# mesh-jitted step announces itself and the kernel wraps in shard_map


def test_flash_over_mesh_matches_unwrapped_kernel():
    """The all-axes-manual shard_map wrap is a pure re-layout: same
    values and same gradients as the unwrapped kernel (interpret mode),
    with heads over the non-batch axis when they divide and replicated
    over it when they do not."""
    import functools

    import jax

    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_attention,
        flash_attention_over_mesh,
    )
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    rng = np.random.default_rng(3)
    for H in (2, 3):  # 3 heads do not divide the model axis
        q, k, v = (jnp.asarray(rng.standard_normal((2, 128, H, 128)),
                               jnp.float32) for _ in range(3))

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)

        plain = functools.partial(flash_attention, causal=True,
                                  block_q=128, block_k=128, interpret=True)
        meshed = functools.partial(flash_attention_over_mesh, mesh=mesh,
                                   batch_axis="data", causal=True,
                                   block=128, interpret=True)
        np.testing.assert_allclose(np.asarray(meshed(q, k, v)),
                                   np.asarray(plain(q, k, v)),
                                   rtol=1e-5, atol=1e-5)
        g_plain = jax.grad(functools.partial(loss, plain),
                           argnums=(0, 1, 2))(q, k, v)
        g_mesh = jax.grad(functools.partial(loss, meshed),
                          argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_mesh, g_plain):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
    # a batch that does not divide its axis is a decline, not a crash
    q3 = jnp.zeros((3, 128, 2, 128), jnp.float32)
    assert flash_attention_over_mesh(q3, q3, q3, mesh, "data", causal=True,
                                     block=128, interpret=True) is None


def test_parallel_wrapper_traces_its_step_inside_mesh_scope():
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops import kernel_dispatch as kd
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = MultiLayerNetwork(
        NeuralNetConfiguration.Builder().seed(0).list()
        .layer(DenseLayer(n_out=8))
        .layer(OutputLayer(n_out=2))
        .set_input_type(InputType.feed_forward(4)).build())
    net.init()
    seen = []
    make_step = net.train_step_fn

    def spying_step_fn():
        step = make_step()

        def spy(*args):
            seen.append(kd.traced_mesh())
            return step(*args)
        return spy

    net.train_step_fn = spying_step_fn
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    assert kd.traced_mesh() is None
    ParallelWrapper(net, mesh=mesh).fit(
        DataSet(np.ones((8, 4), np.float32),
                np.eye(2, dtype=np.float32)[[0, 1] * 4]))
    assert seen and all(s == (mesh, "data") for s in seen)
    assert kd.traced_mesh() is None  # the scope closed with the trace
