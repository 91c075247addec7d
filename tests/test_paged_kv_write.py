"""The decode step's in-place KV write (`ops/pallas_paged_kv_write.py`).

The kernel runs here in interpreter mode (CPU tier-1 never dispatches
it) and is held to the XLA scatter it replaces BIT FOR BIT on every
page but the trash page: the engine's pools after a step must not
depend on which form wrote them. The engine-level test forces the
helper through the interpreted kernel and asks for the scatter build's
tokens."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import gpt_configuration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import kernel_dispatch
from deeplearning4j_tpu.ops import pallas_paged_kv_write as pk
from deeplearning4j_tpu.serving import DecodeEngine

P = 6  # allocatable pages; page 0 is the trash page


def _draw(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _pools(rng, dtype, Hkv, hd, page):
    pools = [_draw(rng, (P + 1, Hkv, hd, page), dtype),
             _draw(rng, (P + 1, Hkv, page, hd), dtype)]
    if dtype == jnp.int8:
        return pools + [_draw(rng, (P + 1, Hkv, page), jnp.float32),
                        _draw(rng, (P + 1, Hkv, page), jnp.float32)]
    return pools + [None, None]


def _step(write, pools, rng, dtype, pids, loff):
    S, Hkv, hd = len(pids), pools[0].shape[1], pools[0].shape[2]
    new = [_draw(rng, (S, Hkv, hd), dtype), _draw(rng, (S, Hkv, hd), dtype)]
    scales = []
    if dtype == jnp.int8:
        scales = [_draw(rng, (S, Hkv), jnp.float32),
                  _draw(rng, (S, Hkv), jnp.float32)]
    return write(*pools[:2], *new, jnp.asarray(pids, jnp.int32),
                 jnp.asarray(loff, jnp.int32), *pools[2:], *scales)


def _bits(x):
    return np.asarray(x).tobytes()


CASES = {
    # bf16 at the serving tile, every edge of the 16-row sublane tile
    # (each case takes a second step at `loff + 1`)
    "bf16-loff0": (jnp.bfloat16, 2, 128, 128, [1, 2, 3, 4], [0] * 4),
    "bf16-loff15": (jnp.bfloat16, 2, 128, 128, [1, 2, 3, 4], [15] * 4),
    "bf16-loff16": (jnp.bfloat16, 2, 128, 128, [1, 2, 3, 4], [16] * 4),
    "bf16-loff126-127": (jnp.bfloat16, 2, 128, 128, [4, 3, 2, 1], [126] * 4),
    "bf16-mixed-offsets": (jnp.bfloat16, 2, 128, 128, [5, 1, 6, 2],
                           [0, 15, 16, 126]),
    # a GQA pool: one KV head under a 4-head query group
    "bf16-gqa-hkv1": (jnp.bfloat16, 1, 128, 128, [2, 4, 6, 1],
                      [3, 31, 32, 64]),
    # several inactive lanes, all redirected to the trash page
    "bf16-inactive-lanes": (jnp.bfloat16, 2, 128, 128, [0, 3, 0, 0],
                            [7, 40, 7, 99]),
    "bf16-all-inactive": (jnp.bfloat16, 2, 128, 128, [0, 0, 0, 0],
                          [1, 2, 3, 4]),
    # narrow heads and short pages (the tier-1 engines' geometry)
    "f32-hd16-page8": (jnp.float32, 2, 16, 8, [1, 0, 2, 5], [0, 3, 6, 1]),
    "f32-hd64-page32": (jnp.float32, 4, 64, 32, [6, 5, 0, 1],
                        [7, 8, 9, 30]),
    # int8 payload pools with their f32 scale pools
    "int8-scales": (jnp.int8, 2, 128, 128, [1, 0, 4, 2], [0, 5, 31, 126]),
    "int8-scales-page32": (jnp.int8, 2, 64, 32, [3, 0, 0, 6],
                           [30, 1, 2, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_pools_equal_scatter_pools_bit_for_bit(case):
    """Two decode steps in a row (`loff`, then `loff + 1`): after each,
    every page but the trash page is bit-identical to the scatter's,
    and a page no slot named still holds what it held."""
    dtype, Hkv, hd, page, pids, loff = CASES[case]
    start = _pools(np.random.default_rng(3), dtype, Hkv, hd, page)
    kernel = functools.partial(pk.paged_kv_write, interpret=True)
    got, want = start, start
    for step in range(2):
        offs = [o + step for o in loff]
        got = _step(kernel, got, np.random.default_rng(10 + step), dtype,
                    pids, offs)
        want = _step(pk.scatter_kv_write, want,
                     np.random.default_rng(10 + step), dtype, pids, offs)
        for g, w, before in zip(got, want, start):
            if before is None:
                assert g is None and w is None
                continue
            assert g.dtype == before.dtype and g.shape == before.shape
            assert _bits(g[1:]) == _bits(w[1:])
            for page_id in set(range(1, P + 1)) - set(pids):
                assert _bits(g[page_id]) == _bits(before[page_id])
    # the write landed: a named page differs from what it held
    live = [p for p in pids if p]
    if live:
        assert _bits(got[0][live[0]]) != _bits(start[0][live[0]])


def test_kernel_under_jit_and_scan_carry():
    """The kernel as the engine stages it: inside a `lax.scan` whose
    carry is the pools, offsets advancing one position a step."""
    rng = np.random.default_rng(5)
    kp, vp, _, _ = _pools(rng, jnp.bfloat16, 2, 128, 128)
    pids = jnp.asarray([2, 0, 5, 1], jnp.int32)
    new = _draw(rng, (3, 2, 4, 2, 128), jnp.bfloat16)  # (step, k|v, S..)

    def run(write):
        def body(carry, x):
            kp_, vp_, off = carry
            out = write(kp_, vp_, x[0], x[1], pids, off)
            return (out[0], out[1], off + 1), None

        off0 = jnp.asarray([14, 3, 125, 0], jnp.int32)
        return jax.jit(lambda: jax.lax.scan(body, (kp, vp, off0), new)[0])()

    got = run(functools.partial(pk.paged_kv_write, interpret=True))
    want = run(pk.scatter_kv_write)
    assert _bits(got[0][1:]) == _bits(want[0][1:])
    assert _bits(got[1][1:]) == _bits(want[1][1:])


def test_dispatch_declines_on_cpu_and_leaves_no_verdict():
    kp, vp, _, _ = _pools(np.random.default_rng(0), jnp.float32, 2, 16, 8)
    new = jnp.zeros((2, 2, 16), jnp.float32)
    idx = jnp.zeros((2,), jnp.int32)
    assert pk.paged_kv_write_or_none(kp, vp, new, new, idx, idx) is None
    assert pk.FAMILY not in kernel_dispatch.kernel_verdicts()


def test_kill_switch_and_unserved_dtypes_decline(monkeypatch):
    """Where kernels dispatch, the family's switch still forces the
    scatter; pools the kernel does not tile (f16, mixed dtypes, int8
    without scales) decline before any probe."""
    kp, vp, _, _ = _pools(np.random.default_rng(0), jnp.float32, 2, 16, 8)
    new = jnp.zeros((2, 2, 16), jnp.float32)
    idx = jnp.zeros((2,), jnp.int32)
    monkeypatch.setattr(pk, "_kernels_dispatch",
                        lambda switch: not __import__("os").environ.get(
                            switch))
    probed = []
    monkeypatch.setattr(pk, "_probe_verdict",
                        lambda *a: probed.append(a) or False)
    monkeypatch.setenv("DL4J_TPU_NO_PALLAS_PAGED_KV_WRITE", "1")
    assert pk.paged_kv_write_or_none(kp, vp, new, new, idx, idx) is None
    monkeypatch.delenv("DL4J_TPU_NO_PALLAS_PAGED_KV_WRITE")
    for a, b in ((kp.astype(jnp.float16), vp.astype(jnp.float16)),
                 (kp, vp.astype(jnp.bfloat16)),
                 (kp.astype(jnp.int8), vp.astype(jnp.int8))):
        assert pk.paged_kv_write_or_none(a, b, new, new, idx, idx) is None
    assert not probed
    assert pk.paged_kv_write_or_none(kp, vp, new, new, idx, idx) is None
    assert [a[:2] for a in probed] == [
        (pk.FAMILY, ("float32", 2, 16, 8, "dense"))]


def test_probe_checks_the_kernel_against_the_scatter(monkeypatch):
    """The probe as the chip runs it (interpreted here): passes on the
    real kernel, and raises — a recorded decline — on a kernel that
    compiles but writes the wrong lane."""
    real = pk.paged_kv_write
    monkeypatch.setattr(pk, "paged_kv_write",
                        functools.partial(real, interpret=True))
    assert pk._eager_probe(jnp.dtype(jnp.bfloat16), 2, 128, 128)
    assert pk._eager_probe(jnp.dtype(jnp.int8), 2, 64, 32, True)

    def off_by_one(kp, vp, kn, vn, pids, loff, *scales):
        return real(kp, vp, kn, vn, pids, (loff + 1) % kp.shape[3],
                    *scales, interpret=True)

    monkeypatch.setattr(pk, "paged_kv_write", off_by_one)
    with pytest.raises(ValueError, match="differ from the scatter"):
        pk._eager_probe(jnp.dtype(jnp.float32), 2, 16, 8)


@pytest.mark.parametrize("quantize", [None, {"kv": "int8"}],
                         ids=["dense", "int8"])
def test_engine_tokens_equal_scatter_build(monkeypatch, quantize):
    """A `DecodeEngine` whose decode write rides the (interpreted)
    kernel emits the scatter build's tokens: single steps and fused
    chunks, slot and page reuse, inactive lanes on the trash page."""
    net = MultiLayerNetwork(gpt_configuration(
        seed=7, vocab_size=48, d_model=32, n_heads=2, n_layers=2,
        max_length=64))
    net.init()
    prompts = np.random.default_rng(1).integers(0, 48, (5, 6)).astype(
        np.int32)
    n_tokens = [9, 4, 12, 7, 5]

    def tokens():
        eng = DecodeEngine(net, n_slots=2, max_len=32, page_size=8,
                           prompt_buckets=(8,), decode_chunk=4,
                           quantize=quantize)
        try:
            reqs = [eng.submit(p, n) for p, n in zip(prompts, n_tokens)]
            return [np.asarray(r.result(timeout=300.0)) for r in reqs]
        finally:
            eng.shutdown(drain_timeout=10.0)

    want = tokens()
    calls = []
    real = pk.paged_kv_write

    def interpreted(*a, **kw):
        calls.append(a[0].dtype)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(pk, "_platform_supported", lambda: True)
    monkeypatch.setattr(pk, "_probe_verdict", lambda *a: True)
    monkeypatch.setattr(pk, "paged_kv_write", interpreted)
    got = tokens()
    assert calls and all(
        d == (jnp.int8 if quantize else jnp.float32) for d in calls)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
