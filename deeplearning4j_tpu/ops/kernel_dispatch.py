"""Shared plumbing for Pallas kernel dispatch (the cuDNN-helper pattern).

Every accelerated kernel in `ops/` follows the reference's reflective
helper contract (`ConvolutionLayer.initializeHelper`,
`ConvolutionLayer.java:69-79`): probe once whether the fast path compiles
here, fall through to the XLA path otherwise and keep the reason. This
module holds the pieces that contract needs so each new kernel doesn't
re-implement them: MXU dtype policy, accumulation dtype, a
precision-pinned dot_general, out-of-trace probe execution, and the
process-wide verdict table (`kernel_verdicts`).

Dispatch contract (every kernel family — `pallas_attention`,
`pallas_lstm`, `pallas_paged_attention`, `pallas_paged_kv_write` — holds
all five):

1. **Same signature, same semantics** as the XLA path it replaces; the
   XLA path stays in-tree as the portable reference numerics.
2. **Probe before first dispatch**, out of trace (`probe_verdict`):
   compile AND run the kernel once at the exact shape class on tiny
   concrete inputs. A kernel whose probe also CHECKS its output against
   the XLA reference (the paged-attention family does) turns a
   miscompiling Mosaic toolchain into a fallback instead of a
   wrong-numerics serving path.
3. **Fallback with a record**: a probe that raises (or a kernel that
   fails at staging, `record_decline`) runs the XLA path, and the
   decline — family, shape class, the compiler's message — is kept in
   the process-wide verdict table that `kernel_verdicts()` returns, so
   a smoke run or a benchmark asserts on which path ran instead of
   inferring it. CPU/interpret platforms never dispatch (tier-1 tests
   run the XLA paths bit-for-bit unchanged); a backend that fails to
   initialise raises — it is never read as "no accelerator".
4. **Kill switch**: a `DL4J_TPU_NO_<KERNEL>` env var forces the XLA
   path — how the benches price kernel-vs-XLA A/B lines on identical
   configs.
5. **VMEM ceiling**: kernels size their resident slabs against
   `vmem_limit_bytes()` (generation-derived, below) and decline shapes
   that cannot fit rather than letting Mosaic fail mid-training.
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

logger = logging.getLogger("deeplearning4j_tpu")


def mxu_dtype(ref_dtype):
    """bf16 inputs feed the MXU natively; f32 stays f32; f64 (interpret
    mode on CPU, gradient checks) stays f64."""
    return jnp.bfloat16 if ref_dtype == jnp.bfloat16 else ref_dtype


def stat_dtype(dt):
    """Accumulator/statistic dtype: f32 for bf16/f32 inputs, f64 for f64
    (interpret-mode gradient checks need the whole pipeline at f64, or
    eps-scale central differences drown in f32 forward noise)."""
    return jnp.float64 if dt == jnp.float64 else jnp.float32


def dot_precision(dt):
    """f32 operands multiply at HIGHEST precision (bf16x3 passes on the
    MXU) — measured ~100x more accurate gradients than the XLA
    default-precision einsum; bf16 takes the native single-pass feed."""
    return (jax.lax.Precision.DEFAULT if dt == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)


def dot(a, b, dims, dt):
    """dot_general with the kernel dtype policy applied."""
    return jax.lax.dot_general(a, b, dimension_numbers=(dims, ((), ())),
                               preferred_element_type=stat_dtype(dt),
                               precision=dot_precision(dt))


def run_probe_out_of_trace(fn, *args) -> bool:
    """Run an eager compile probe OUTSIDE any live jit trace. Dispatch
    usually happens while the caller's step function is being traced, and
    JAX trace contexts are dynamic: ops on concrete probe arrays would be
    staged into the caller's jaxpr and the probe's `bool()` would raise
    TracerBoolConversionError (a False verdict for the wrong reason). Trace
    state is thread-local, so a worker thread gives the probe a clean
    eval context."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(1) as ex:
        return ex.submit(fn, *args).result()


class KernelVerdict(NamedTuple):
    """Whether one kernel family serves one shape class in this process,
    and — when it does not — the compiler's (or the probe's) message."""
    ok: bool
    message: str


_MESSAGE_CHARS = 600  # of a compiler message kept per decline

# family -> shape class -> verdict, for the life of the process
_verdicts: Dict[str, Dict[tuple, KernelVerdict]] = {}  # guarded by: _verdict_lock
_verdict_lock = threading.Lock()


def kernel_verdicts() -> Dict[str, Dict[tuple, KernelVerdict]]:
    """Every dispatch verdict reached so far: kernel family ->
    shape-class key -> `KernelVerdict`. A shape class appears once its
    probe has run (or a staging failure / VMEM decline was recorded);
    platforms that never dispatch (CPU) leave the table empty. Returns
    a copy — the table itself only changes through `probe_verdict` and
    `record_decline`."""
    with _verdict_lock:
        return {family: dict(classes)
                for family, classes in _verdicts.items()}


def engaged(family: str, match=lambda key: True) -> list:
    """Shape classes of `family` whose verdict is a pass and whose key
    satisfies `match` — how a smoke run or a benchmark establishes,
    after the fact, that a net rode the Pallas path."""
    return sorted(key for key, v in kernel_verdicts().get(family, {}).items()
                  if v.ok and match(key))


def verdicts_as_json() -> Dict[str, list]:
    """`kernel_verdicts()` for a JSON result line: family ->
    [{"class", "ok", "message"}, ...]."""
    return {family: [{"class": list(key), "ok": v.ok, "message": v.message}
                     for key, v in sorted(classes.items(), key=repr)]
            for family, classes in kernel_verdicts().items()}


def _set_verdict(family: str, key, verdict: KernelVerdict) -> None:
    with _verdict_lock:
        _verdicts.setdefault(family, {})[key] = verdict


def record_decline(family: str, key, message: str) -> None:
    """Record that `family` does NOT serve shape class `key`, with the
    reason. Overrides an earlier passing probe: a kernel that probed
    fine and then failed at staging is declined for that class."""
    if len(message) > _MESSAGE_CHARS:  # Mosaic appends the whole program
        message = (f"{message[:_MESSAGE_CHARS]} ... "
                   f"[{len(message) - _MESSAGE_CHARS} more chars]")
    logger.warning("pallas %s declined for %s (%s); using the XLA path",
                   family, key, message)
    _set_verdict(family, key, KernelVerdict(False, message))


def probe_verdict(family: str, key, probe_fn, args) -> bool:
    """Out-of-trace compile-probe verdict for `family` at shape class
    `key`, reached once per process: True once `probe_fn(*args)`
    compiled, ran and returned True; a raise is recorded with its
    message (`kernel_verdicts`) and the caller runs the XLA path."""
    with _verdict_lock:
        verdict = _verdicts.get(family, {}).get(key)
    if verdict is not None:
        return verdict.ok
    try:
        ok = run_probe_out_of_trace(probe_fn, *args)
    except Exception as e:  # Mosaic/compile failure: keep the message
        record_decline(family, key, f"{type(e).__name__}: {e}")
        return False
    if ok:
        _set_verdict(family, key, KernelVerdict(True, ""))
    else:
        record_decline(family, key, "probe compiled and ran but its "
                                    "output was not finite")
    return bool(ok)


# Mosaic kernels cannot be partitioned automatically: traced into a jit
# that spans a device mesh they fail at lowering ("wrap the call in a
# shard_map"), far from any dispatch code. A wrapper that jits over a
# mesh therefore traces its step inside `mesh_scope`, and each kernel
# family either wraps itself in an all-axes-manual `shard_map` over that
# mesh or declines with a recorded reason. Trace-time static, like
# `ops.attention.sequence_parallel_scope`.
_MESH_SCOPE: list = []  # (mesh, batch_axis) stack


@contextlib.contextmanager
def mesh_scope(mesh, batch_axis: Optional[str]):
    """Announce, around the TRACE of a step, that it is being jitted
    over `mesh` with the batch dimension sharded on `batch_axis` (None:
    not sharded)."""
    _MESH_SCOPE.append((mesh, batch_axis))
    try:
        yield
    finally:
        _MESH_SCOPE.pop()


def traced_mesh() -> Optional[Tuple[object, Optional[str]]]:
    """The innermost `mesh_scope`'s (mesh, batch_axis), or None when the
    trace is single-device (or already inside a fully-manual
    `shard_map`, as the tensor-parallel decode engine's steps are)."""
    return _MESH_SCOPE[-1] if _MESH_SCOPE else None


def platform_supported(kill_switch: str) -> bool:
    """Whether kernels dispatch in this process: never on the CPU
    backend (tier-1 runs the XLA reference numerics) and never under the
    family's `DL4J_TPU_NO_*` kill switch. A backend that cannot
    initialise raises out of `jax.default_backend()`."""
    if os.environ.get(kill_switch):
        return False
    return jax.default_backend() != "cpu"


# Mosaic's default scoped-VMEM stack limit is 16 MiB; modern cores carry
# far more. Kernels whose double-buffered slabs exceed the default (the
# fused LSTM at H=1024 needs 100.1 MiB; 2048-wide attention tiles carry
# 16 MiB f32 score slabs) pass a shared ceiling via
# CompilerParams(vmem_limit_bytes=...). The ceiling is DERIVED from the
# detected device generation (one table so a new TPU generation retunes
# every kernel family at once): 7/8 of the core's physical VMEM, the
# same headroom fraction the old hardcoded 112-of-128 MiB constant
# carried — the reserve absorbs Mosaic's own scratch and avoids
# spilling at exactly-full occupancy. A kind the table does not know is an
# error: a guessed ceiling either over-asks (every big-slab probe fails
# and the kernels silently disappear) or under-asks.
_MIB = 1024 * 1024
_VMEM_PER_CORE_BYTES = {
    # device_kind prefix -> physical scoped VMEM per core
    "TPU v2": 16 * _MIB,
    "TPU v3": 16 * _MIB,
    "TPU v4 lite": 128 * _MIB,   # v4i inference cores
    "TPU v4": 128 * _MIB,
    "TPU v5 lite": 128 * _MIB,   # v5e (device_kind "TPU v5 lite"/"TPU v5e")
    "TPU v5e": 128 * _MIB,
    "TPU v5p": 128 * _MIB,
    "TPU v5": 128 * _MIB,
    "TPU v6 lite": 128 * _MIB,   # v6e / Trillium
    "TPU v6e": 128 * _MIB,
}
# The CPU backend only ever runs kernels in interpret mode, where the
# number is carried but never read by a compiler: the v4/v5-class ceiling
# keeps the interpret-mode parity tests on the same tiles the chip uses.
_INTERPRET_VMEM_PER_CORE = 128 * _MIB

_vmem_limit_cache: dict = {}


def vmem_limit_for_kind(device_kind: str) -> int:
    """Scoped-VMEM ceiling for one `device_kind` string: 7/8 of the
    generation's physical per-core VMEM (longest-prefix match over the
    table, so "TPU v5 lite" resolves before "TPU v5"). "cpu" gets the
    interpret-mode constant; any other unknown kind raises."""
    if device_kind == "cpu":
        return _INTERPRET_VMEM_PER_CORE * 7 // 8
    best = None
    for prefix, size in _VMEM_PER_CORE_BYTES.items():
        if device_kind.startswith(prefix) and \
                (best is None or len(prefix) > len(best[0])):
            best = (prefix, size)
    if best is None:
        raise ValueError(
            f"unknown device_kind {device_kind!r}: add its per-core VMEM "
            "to ops/kernel_dispatch._VMEM_PER_CORE_BYTES before running "
            "Pallas kernels on it")
    return best[1] * 7 // 8


def vmem_limit_bytes() -> int:
    """The Pallas `vmem_limit_bytes` ceiling for THIS process's default
    device, detected once and cached. Every kernel family
    (`pallas_attention`, `pallas_lstm`) reads the same number, so a new
    TPU generation retunes all of them in one table row."""
    key = "default"
    if key not in _vmem_limit_cache:
        _vmem_limit_cache[key] = vmem_limit_for_kind(
            jax.devices()[0].device_kind)
    return _vmem_limit_cache[key]
