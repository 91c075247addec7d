"""What a kernel has to do, computed from its shapes: the operations and
bytes the algorithm needs (not what an implementation happens to do), and
the share of the roofline a measured time is.

Attention counts 2 operations per multiply-add. Causal attention needs
only the pairs at or below the diagonal, T (T + 1) / 2 of them.
"""
from __future__ import annotations


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def flash_forward(batch: int, heads: int, t: int, head_dim: int,
                  itemsize: int = 2) -> tuple:
    """(operations, bytes) of one causal attention forward: QK^T and PV
    over the causal pairs; q, k, v read and o written once."""
    ops = 2 * 2 * head_dim * causal_pairs(t) * batch * heads
    nbytes = 4 * batch * heads * t * head_dim * itemsize
    return ops, nbytes


def flash_backward(batch: int, heads: int, t: int, head_dim: int,
                   itemsize: int = 2) -> tuple:
    """(operations, bytes) of its backward without stored probabilities:
    the scores again, then dV, dP, dQ and dK: five products where the
    forward has two. Reads q, k, v, o, do; writes dq, dk, dv."""
    ops = 5 * 2 * head_dim * causal_pairs(t) * batch * heads
    nbytes = 8 * batch * heads * t * head_dim * itemsize
    return ops, nbytes


def paged_decode(context_tokens: int, heads: int, kv_heads: int,
                 head_dim: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one layer's decode attention over
    `context_tokens` cached positions, summed over the slots of a step:
    q.K and p.V for every head; K and V read once."""
    ops = 2 * 2 * head_dim * heads * context_tokens
    nbytes = 2 * kv_heads * head_dim * itemsize * context_tokens
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which limit sets it."""
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")


def share_pct(ops: float, nbytes: float, seconds: float, peak: dict):
    """Roofline share in percent, or None where no time was measured."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * least_seconds(ops, nbytes, peak)[0] / seconds
