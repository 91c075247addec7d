"""Pallas flash-attention kernel parity tests.

Runs the kernel in interpreter mode (tests execute on the virtual CPU mesh,
conftest.py) against the XLA full-attention reference — the accelerated-path
parity strategy of the reference's cuDNN tests
(`deeplearning4j-cuda/src/test/.../TestConvolution.java`). A real-TPU
compile/run of the same kernel happens via `chip_smoke.py train`.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.attention import full_attention
from deeplearning4j_tpu.ops.pallas_attention import flash_attention

pytestmark = pytest.mark.slow  # bench/convergence-shaped module: excluded from the quick tier


def _qkv(B=2, T=256, H=2, D=128, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_full(causal):
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    # kernel feeds the MXU bf16 operands (f32 accumulate) — tolerance is
    # bf16 mantissa granularity, matching the on-device error vs XLA f32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_flash_multiple_kv_blocks():
    # Tk spans 4 KV blocks: exercises the online-softmax rescale chain
    q, k, v = _qkv(B=1, T=512, H=1, D=128, seed=1)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_flash_rejects_unaligned():
    q, k, v = _qkv(T=200)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, interpret=True)


def test_dispatch_probe_declines_on_cpu():
    """On the CPU test platform the probe must decline (compiled Mosaic
    kernels are TPU-only) and multi_head_attention must fall back to the
    XLA blockwise path with identical results."""
    from deeplearning4j_tpu.ops.attention import multi_head_attention
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention_or_none

    q, k, v = _qkv(B=1, T=256, H=1, D=128)
    assert flash_attention_or_none(q, k, v) is None
    out = multi_head_attention(q, k, v, block_size=128)
    ref = full_attention(q, k, v)
    # probe declined -> XLA blockwise path: exact-math parity applies
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_full(causal):
    """Custom-VJP backward kernels (dQ / dKV) against jax.grad through the
    XLA full-attention reference — the CuDNNGradientChecks pattern for the
    accelerated training path."""
    import jax

    q, k, v = _qkv(B=2, T=256, H=2, D=128, seed=3)
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) * w)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"d{name}")


def test_flash_backward_f64_numeric_gradient():
    """f64 central-difference check of the analytic backward kernels (the
    reference's core validation strategy, GradientCheckUtil: fp64,
    eps=1e-6, maxRelError=1e-3)."""
    import jax

    rng = np.random.default_rng(7)
    B, T, H, D = 1, 256, 1, 128
    q = jnp.asarray(rng.normal(size=(B, T, H, D)))  # f64 (x64 enabled)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)))
    w = jnp.asarray(rng.normal(size=(B, T, H, D)))
    assert q.dtype == jnp.float64

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) * w)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    eps = 1e-6
    checked = 0
    for ai, (name, arr) in enumerate(zip("qkv", (q, k, v))):
        flat = np.asarray(arr).ravel()
        gflat = np.asarray(grads[ai]).ravel()
        for idx in rng.choice(flat.size, 8, replace=False):
            # separate buffers: jnp.asarray can zero-copy a numpy buffer
            # on CPU, so reusing/mutating one array would silently alias
            pert_p = flat.copy()
            pert_p[idx] += eps
            pert_m = flat.copy()
            pert_m[idx] -= eps
            args_p = [q, k, v]
            args_p[ai] = jnp.asarray(pert_p.reshape(arr.shape))
            args_m = [q, k, v]
            args_m[ai] = jnp.asarray(pert_m.reshape(arr.shape))
            num = (float(loss(*args_p)) - float(loss(*args_m))) / (2 * eps)
            ana = float(gflat[idx])
            denom = abs(num) + abs(ana)
            if denom < 1e-8:
                continue
            rel = abs(num - ana) / denom
            assert rel < 1e-3, (name, idx, num, ana, rel)
            checked += 1
    assert checked >= 12


def test_flash_training_through_transformer_block():
    """A TransformerBlock whose attention dispatches to the flash kernel
    must train (grad flows through the custom VJP); CPU falls back, so
    exercise the kernel explicitly through a toy train step."""
    import jax

    q, k, v = _qkv(B=1, T=256, H=1, D=128, seed=9)
    params = {"w": jnp.ones((128, 128), jnp.float32) * 0.01}

    def loss(p):
        o = flash_attention(q @ p["w"], k, v, causal=True, interpret=True)
        return jnp.mean(o * o)

    g = jax.grad(loss)(params)
    assert np.isfinite(np.asarray(g["w"])).all()
    assert float(jnp.max(jnp.abs(g["w"]))) > 0
