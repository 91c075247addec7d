"""Fused Pallas LSTM cell: parity + gradient checks vs the lax.scan path.

Reference strategy analogue: `CuDNNGradientChecks.java` /
`TestConvolution.java` — the accelerated helper must produce the same
outputs and pass gradient checks against the built-in path. Runs the
kernel in Pallas interpret mode so the same math executes on the CPU CI
mesh (Mosaic-compiled execution is exercised on-chip by `chip_smoke.py lstm`
and the probe)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.recurrent import lstm_forward
from deeplearning4j_tpu.ops.pallas_lstm import lstm_fused_or_none

pytestmark = pytest.mark.slow  # interpret-mode kernels are CPU-heavy


def _inputs(dt, B=8, T=5, NI=16, H=128, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, T, NI)), dt)
    W = jnp.asarray(rng.standard_normal((NI, 4 * H)) * 0.2, dt)
    RW = jnp.asarray(rng.standard_normal((H, 4 * H)) * 0.2, dt)
    b = jnp.asarray(rng.standard_normal(4 * H) * 0.1, dt)
    peep = tuple(jnp.asarray(rng.standard_normal(H) * 0.1, dt)
                 for _ in range(3))
    h0 = jnp.asarray(rng.standard_normal((B, H)) * 0.5, dt)
    c0 = jnp.asarray(rng.standard_normal((B, H)) * 0.5, dt)
    return x, W, RW, b, peep, h0, c0


def _fused(x, W, RW, b, peep, h0, c0, **kw):
    res = lstm_fused_or_none(x, W, RW, b, peep, h0, c0,
                             gate_is_sigmoid=True, cell_is_tanh=True,
                             interpret=True, **kw)
    assert res is not None, "fused dispatch declined a qualifying call"
    return res


def test_forward_matches_scan_exactly():
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float32)
    ref, (rh, rc) = lstm_forward(x, W, RW, b, peep, jax.nn.sigmoid,
                                 jnp.tanh, h0, c0)
    out, (hT, cT) = _fused(x, W, RW, b, peep, h0, c0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(rh), atol=2e-6)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(rc), atol=2e-6)


def test_reverse_matches_scan():
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float32)
    ref, (rh, rc) = lstm_forward(x, W, RW, b, peep, jax.nn.sigmoid,
                                 jnp.tanh, h0, c0, reverse=True)
    out, (hT, cT) = _fused(x, W, RW, b, peep, h0, c0, reverse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(rh), atol=2e-6)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(rc), atol=2e-6)


def test_gradients_match_scan_f64():
    """Analytic VJP of the kernel vs the scan transpose, f64: every
    parameter, the input, and both initial carries."""
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float64)
    weights = jnp.asarray(
        np.random.default_rng(1).standard_normal((8, 5, 128)))

    def loss(fwd, W, RW, b, peep, h0, c0, x):
        out, (hT, cT) = fwd(x, W, RW, b, peep, h0, c0)
        return (jnp.sum(out * weights) + jnp.sum(hT * cT)
                + jnp.sum(jnp.tanh(cT)))

    def scan_fwd(x, W, RW, b, peep, h0, c0):
        return lstm_forward(x, W, RW, b, peep, jax.nn.sigmoid, jnp.tanh,
                            h0, c0)

    def fused_fwd(x, W, RW, b, peep, h0, c0):
        return _fused(x, W, RW, b, peep, h0, c0)

    args = (W, RW, b, peep, h0, c0, x)
    g_ref = jax.grad(lambda *a: loss(scan_fwd, *a),
                     argnums=tuple(range(7)))(*args)
    g_fus = jax.grad(lambda *a: loss(fused_fwd, *a),
                     argnums=tuple(range(7)))(*args)
    flat_r, _ = jax.tree_util.tree_flatten(g_ref)
    flat_f, _ = jax.tree_util.tree_flatten(g_fus)
    for r, f in zip(flat_r, flat_f):
        np.testing.assert_allclose(np.asarray(f), np.asarray(r),
                                   rtol=1e-9, atol=1e-11)


def test_numeric_gradient_check_f64():
    """f64 central differences vs the kernel's custom VJP (the reference's
    gradient-check bar: eps 1e-6, maxRelError 1e-3,
    `GradientCheckUtil.java:62`)."""
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float64, B=8, T=3, NI=8, H=128)

    def loss_rw(RW_flat):
        out, (hT, cT) = _fused(x, W, RW_flat.reshape(RW.shape), b, peep,
                               h0, c0)
        return jnp.sum(out ** 2) + jnp.sum(hT * cT)

    rw_flat = RW.ravel()
    g = np.asarray(jax.grad(loss_rw)(rw_flat))
    rng = np.random.default_rng(2)
    eps = 1e-6
    for idx in rng.choice(rw_flat.size, 25, replace=False):
        e = np.zeros(rw_flat.size)
        e[idx] = eps
        num = (float(loss_rw(rw_flat + e)) - float(loss_rw(rw_flat - e))) \
            / (2 * eps)
        denom = max(abs(num), abs(g[idx]), 1e-8)
        assert abs(num - g[idx]) / denom < 1e-3, (
            f"RW[{idx}]: numeric {num} vs analytic {g[idx]}")


def test_dispatch_declines_unsupported_calls():
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float32)
    assert lstm_fused_or_none(x, W, RW, b, peep, h0, c0,
                              gate_is_sigmoid=False, cell_is_tanh=True,
                              interpret=True) is None
    # H not a lane multiple
    x2, W2, RW2, b2, p2, h2, c2 = _inputs(jnp.float32, H=96)
    assert lstm_fused_or_none(x2, W2, RW2, b2, p2, h2, c2,
                              gate_is_sigmoid=True, cell_is_tanh=True,
                              interpret=True) is None
    # T == 1 (single-step path belongs to lstm_step)
    assert lstm_fused_or_none(x[:, :1], W, RW, b, peep, h0, c0,
                              gate_is_sigmoid=True, cell_is_tanh=True,
                              interpret=True) is None


def test_zero_initial_state_defaults():
    x, W, RW, b, peep, _, _ = _inputs(jnp.float32)
    ref, (rh, rc) = lstm_forward(x, W, RW, b, peep, jax.nn.sigmoid,
                                 jnp.tanh)
    out, (hT, cT) = _fused(x, W, RW, b, peep, None, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(rc), atol=2e-6)


def test_batch_not_multiple_of_8_declines():
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float32, B=6)
    assert lstm_fused_or_none(x, W, RW, b, peep, h0, c0,
                              gate_is_sigmoid=True, cell_is_tanh=True,
                              interpret=True) is None


def _mask(B=8, T=5, seed=3):
    rng = np.random.default_rng(seed)
    # variable-length: each row valid for a prefix, plus one interior hole
    m = np.ones((B, T), np.float64)
    lens = rng.integers(2, T + 1, B)
    for b in range(B):
        m[b, lens[b]:] = 0.0
    m[0, 1] = 0.0  # interior masked step: state must pass through
    return m


def test_masked_forward_matches_scan():
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float64)
    m = jnp.asarray(_mask())
    ref, (rh, rc) = lstm_forward(x, W, RW, b, peep, jax.nn.sigmoid,
                                 jnp.tanh, h0, c0, mask=m)
    out, (hT, cT) = _fused(x, W, RW, b, peep, h0, c0, mask=m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(rh), atol=1e-12)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(rc), atol=1e-12)


def test_masked_reverse_matches_scan():
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float64)
    m = jnp.asarray(_mask(seed=4))
    ref, (rh, rc) = lstm_forward(x, W, RW, b, peep, jax.nn.sigmoid,
                                 jnp.tanh, h0, c0, mask=m, reverse=True)
    out, (hT, cT) = _fused(x, W, RW, b, peep, h0, c0, mask=m,
                           reverse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(rh), atol=1e-12)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(rc), atol=1e-12)


def test_masked_gradients_match_scan_f64():
    x, W, RW, b, peep, h0, c0 = _inputs(jnp.float64)
    m = jnp.asarray(_mask(seed=5))
    weights = jnp.asarray(
        np.random.default_rng(6).standard_normal((8, 5, 128)))

    def loss(fwd, W, RW, b, peep, h0, c0, x):
        out, (hT, cT) = fwd(x, W, RW, b, peep, h0, c0)
        return (jnp.sum(out * weights) + jnp.sum(hT * cT)
                + jnp.sum(jnp.tanh(cT)))

    def scan_fwd(x, W, RW, b, peep, h0, c0):
        return lstm_forward(x, W, RW, b, peep, jax.nn.sigmoid, jnp.tanh,
                            h0, c0, mask=m)

    def fused_fwd(x, W, RW, b, peep, h0, c0):
        return _fused(x, W, RW, b, peep, h0, c0, mask=m)

    args = (W, RW, b, peep, h0, c0, x)
    g_ref = jax.grad(lambda *a: loss(scan_fwd, *a),
                     argnums=tuple(range(7)))(*args)
    g_fus = jax.grad(lambda *a: loss(fused_fwd, *a),
                     argnums=tuple(range(7)))(*args)
    for r, f in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_fus)):
        np.testing.assert_allclose(np.asarray(f), np.asarray(r),
                                   rtol=1e-9, atol=1e-11)


def test_probe_falls_back_to_smaller_batch_block(monkeypatch):
    """A failed probe at the largest batch block must fall through to the
    next dividing candidate instead of declining the kernel outright
    (advisor r3: a VMEM overflow at bb=512 with large H cached False and
    disabled the fused path entirely)."""
    from deeplearning4j_tpu.ops import pallas_lstm as mod

    calls = []

    def fake_probe(dtype, bb, H, masked=False):
        calls.append(bb)
        return bb <= 64  # big tiles "overflow VMEM"

    from deeplearning4j_tpu.ops import kernel_dispatch as kd

    monkeypatch.setattr(mod, "_eager_probe", fake_probe)
    monkeypatch.setattr(kd, "_verdicts", {})
    bb = mod._probed_batch_block(jnp.float32, 512, 128, False)
    assert bb == 64
    assert calls == [512, 256, 128, 64]
    # verdicts cached per candidate: a second call probes nothing
    calls.clear()
    assert mod._probed_batch_block(jnp.float32, 512, 128, False) == 64
    assert calls == []
    # the smallest candidate still dispatches when it alone passes
    assert mod._probed_batch_block(jnp.float32, 8, 128, False) == 8
    # every dividing candidate failing -> decline
    monkeypatch.setattr(mod, "_eager_probe",
                        lambda dtype, bb, H, masked=False: False)
    monkeypatch.setattr(kd, "_verdicts", {})
    assert mod._probed_batch_block(jnp.float32, 512, 128, False) is None
    # ... and every declined candidate is on the public record
    declined = kd.kernel_verdicts()[mod.FAMILY]
    assert set(declined) == {("float32", bb, 128, False)
                             for bb in (512, 256, 128, 64, 32, 16, 8)}
    assert not any(v.ok for v in declined.values())
