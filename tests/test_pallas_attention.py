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

# the module's first tests are bench/convergence-shaped: excluded from the
# quick tier; the fused backward's cases below run in it
slow = pytest.mark.slow


def _qkv(B=2, T=256, H=2, D=128, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    return mk(), mk(), mk()


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_full(causal):
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    # kernel feeds the MXU bf16 operands (f32 accumulate) — tolerance is
    # bf16 mantissa granularity, matching the on-device error vs XLA f32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


@slow
def test_flash_multiple_kv_blocks():
    # Tk spans 4 KV blocks: exercises the online-softmax rescale chain
    q, k, v = _qkv(B=1, T=512, H=1, D=128, seed=1)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


@slow
def test_flash_rejects_unaligned():
    q, k, v = _qkv(T=200)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, interpret=True)


@slow
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


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_full(causal):
    """The custom VJP's backward kernel against jax.grad through the
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


def _central_differences_agree(loss, args, grads, rng, per_arg, eps=1e-6):
    """`per_arg` entries of each argument's analytic gradient against a
    central difference of `loss`; returns how many were compared."""
    checked = 0
    for ai, (name, arr) in enumerate(zip("qkv", args)):
        flat = np.asarray(arr).ravel()
        gflat = np.asarray(grads[ai]).ravel()
        for idx in rng.choice(flat.size, per_arg, replace=False):
            # separate buffers: jnp.asarray can zero-copy a numpy buffer
            # on CPU, so reusing/mutating one array would silently alias
            pert_p = flat.copy()
            pert_p[idx] += eps
            pert_m = flat.copy()
            pert_m[idx] -= eps
            args_p = list(args)
            args_p[ai] = jnp.asarray(pert_p.reshape(arr.shape))
            args_m = list(args)
            args_m[ai] = jnp.asarray(pert_m.reshape(arr.shape))
            num = (float(loss(*args_p)) - float(loss(*args_m))) / (2 * eps)
            ana = float(gflat[idx])
            denom = abs(num) + abs(ana)
            if denom < 1e-8:
                continue
            rel = abs(num - ana) / denom
            assert rel < 1e-3, (name, idx, num, ana, rel)
            checked += 1
    return checked


@slow
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
    assert _central_differences_agree(loss, (q, k, v), grads, rng, 8) >= 12


@slow
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


# ---------------------------------------------------------------------------
# The fused backward: one kernel computes a tile pair's S, P, dP and dS once
# and accumulates dQ, dK and dV from them. dQ's rows come round again once a
# key block and dK/dV's once a query block, so every case below has several
# blocks on the axis it names.

def _grads(form, q, k, v, w, *, causal, block_q, block_k):
    """Gradients of sum(attention * w) through the custom VJP with the
    backward's form forced (`flash_attention` reads it off the shape)."""
    import jax

    from deeplearning4j_tpu.ops.pallas_attention import _flash_mha

    def loss(q, k, v):
        out = _flash_mha(q, k, v, causal, q.shape[-1] ** -0.5, block_q,
                         block_k, True, form)
        return jnp.sum(out.astype(w.dtype) * w)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _case(Tq, Tk, dtype, seed, H=2, D=128):
    rng = np.random.default_rng(seed)
    mk = lambda T: jnp.asarray(rng.normal(size=(1, T, H, D)), dtype)
    return mk(Tq), mk(Tk), mk(Tk), jnp.asarray(
        rng.normal(size=(1, Tq, H, D)), jnp.float32)


# name: causal, Tq, Tk, block_q, block_k, dtype, gap to the split backward,
# gap to full_attention's gradients
FUSED_CASES = {
    "causal-4x4-blocks": (True, 512, 512, 128, 128, "float32", 1e-5, 1e-4),
    "full-4x4-blocks": (False, 512, 512, 128, 128, "float32", 1e-5, 1e-4),
    "full-tq-shorter": (False, 256, 512, 128, 128, "float32", 1e-5, 1e-4),
    "full-tq-longer": (False, 512, 256, 128, 128, "float32", 1e-5, 1e-4),
    "causal-block-k-wider": (True, 512, 512, 128, 256, "float32", 1e-5,
                             1e-4),
    "causal-block-q-wider": (True, 512, 512, 256, 128, "float32", 1e-5,
                             1e-4),
    "full-block-q-wider": (False, 512, 256, 256, 128, "float32", 1e-5, 1e-4),
    # bf16 operands and results: the two forms round alike (same products
    # in the same order), the float32 reference differs by the mantissa
    "causal-bfloat16": (True, 512, 512, 128, 128, "bfloat16", 2e-2, 1.5e-1),
    "full-bfloat16": (False, 256, 512, 128, 128, "bfloat16", 2e-2, 1.5e-1),
}


@pytest.mark.parametrize("name", FUSED_CASES)
def test_fused_backward_matches_split_and_full(name):
    import jax

    causal, Tq, Tk, bq, bk, dtype, to_split, to_full = FUSED_CASES[name]
    q, k, v, w = _case(Tq, Tk, dtype, seed=len(name))
    tiles = dict(causal=causal, block_q=bq, block_k=bk)
    fused = _grads("fused", q, k, v, w, **tiles)
    split = _grads("split", q, k, v, w, **tiles)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    want = jax.grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=causal) * w),
        argnums=(0, 1, 2))(*(x.astype(jnp.float32) for x in (q, k, v)))
    for arg, a, b, c in zip("qkv", fused, split, want):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(f32(a), f32(b), atol=to_split,
                                   err_msg=f"d{arg} against the split")
        np.testing.assert_allclose(f32(a), f32(c), atol=to_full,
                                   err_msg=f"d{arg} against full_attention")


@pytest.mark.parametrize("causal, Tq, Tk", [(True, 256, 256),
                                            (False, 128, 256)])
def test_fused_backward_f64_numeric_gradient(causal, Tq, Tk):
    """The f64 central-difference check through the fused kernel, several
    key blocks to a query block and (causal) several query blocks too."""
    import jax

    from deeplearning4j_tpu.ops.pallas_attention import _backward_form

    rng = np.random.default_rng(11)
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, T, 1, 128)))
                  for T in (Tq, Tk, Tk, Tq))
    assert q.dtype == jnp.float64 and _backward_form(
        Tq, 128, q.dtype) == "fused"

    @jax.jit
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) * w)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert _central_differences_agree(loss, (q, k, v), grads, rng, 4) >= 8


def _pallas_calls(fn, *args):
    import jax

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "pallas_call"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += count(sub)
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


# what the fused form holds of a float32 (256, 128) dQ: the accumulator and
# the output block's two buffers; it may take half the ceiling
_DQ_RESIDENT = 256 * 128 * (4 + 2 * 4)


@pytest.mark.parametrize("ceiling, form, kernels", [
    (None, "fused", 2),            # the forward and ONE backward kernel
    (2 * _DQ_RESIDENT - 1, "split", 3),    # a byte short
    (2 * _DQ_RESIDENT, "fused", 2),
])
def test_the_backward_form_is_read_off_the_shape(monkeypatch, ceiling, form,
                                                 kernels):
    """Through the dispatch itself (`flash_attention_or_none`, the kernels
    in the TPU interpreter): a sequence whose dQ, as the fused kernel would
    hold it, passes half the VMEM ceiling takes the two-kernel split, one
    under it the fused kernel; `kernel_verdicts()` names the form that
    engaged, and either gives `full_attention`'s gradients."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops import kernel_dispatch
    from deeplearning4j_tpu.ops import pallas_attention as pa

    probe = pa._eager_probe

    def interpreted_probe(*args):  # it runs on a thread of its own
        with pltpu.force_tpu_interpret_mode():
            return probe(*args)

    monkeypatch.setattr(kernel_dispatch, "_verdicts", {})
    monkeypatch.setattr(pa, "_platform_supported", lambda: True)
    monkeypatch.setattr(pa, "_eager_probe", interpreted_probe)
    if ceiling is not None:
        monkeypatch.setattr(pa, "_vmem_limit", lambda: ceiling)
    q, k, v, w = _case(256, 256, "float32", seed=5, H=1)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention_or_none(q, k, v, causal=True) * w)

    with pltpu.force_tpu_interpret_mode():
        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert _pallas_calls(jax.grad(loss, argnums=(0, 1, 2)),
                             q, k, v) == kernels
    assert kernel_dispatch.kernel_verdicts()["flash_attention"] == {
        ("float32", 256, 128, form): kernel_dispatch.KernelVerdict(True, "")}
    want = jax.grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=True) * w),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_the_kernel_bench_rehearses_in_interpret_mode(tmp_path, capsys):
    """`tools/flash_attention_bench.py` end to end at a toy shape, so that
    a chip call is not lost to a typo: a row a kernel and sequence length,
    both backward forms within rounding of one another and of
    `full_attention`'s gradients, and no time printed as a device's."""
    import json

    from tools import flash_attention_bench as bench

    out = tmp_path / "bench.json"
    assert bench.main(["--batch", "1", "--heads", "2", "--seqs", "256,384",
                       "--dtype", "float32", "--interpret",
                       "--out", str(out)]) == 0
    table = json.loads(out.read_text())
    assert [(r["T"], r["block"], r["kernel"]) for r in table["rows"]] == [
        (T, block, kernel) for T, block in ((256, 256), (384, 128))
        for kernel in ("forward", "backward_split", "backward_fused")]
    backward = [r for r in table["rows"] if r["kernel"] != "forward"]
    assert all(r["gap_to_xla"] < 1e-5 for r in backward)
    assert [r["gap_to_split"] < 1e-6 for r in backward
            if r["kernel"] == "backward_fused"] == [True, True]
    assert table["interpret"] and not any(
        "call_ms" in r or "roofline_pct" in r for r in table["rows"])
    assert len(capsys.readouterr().out.splitlines()) == 6
