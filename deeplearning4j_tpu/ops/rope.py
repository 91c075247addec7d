"""Rotary position embeddings (RoPE, Su et al. 2021 — rotate-half form).

No counterpart in the reference (no transformer tier); included because
relative-position attention is how modern long-context decoders encode
order: each head's feature pairs (x_a, x_b) rotate by angle pos * base
^(-2a/hd), so the q·k inner product depends only on the RELATIVE
distance between query and key — attention generalizes past the trained
context window, and there is no learned positional table to bound
`max_length`. TPU-friendly: pure elementwise mul/add on (B, T, H, hd)
slabs, fused by XLA into the surrounding projections; the precomputed
cos/sin tables are (T, hd/2) and broadcast over batch and heads.

Decode contract (models/transformer.py): keys are rotated at their own
absolute position BEFORE entering the KV cache — a cached key never
needs re-rotation — and each step's query rotates at the current
position.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_angles(positions, head_dim: int, base: float = 10000.0,
                inv_freq=None):
    """cos/sin tables for `positions` (any shape P...): ((P..., hd/2) x 2).
    `head_dim` must be even (pairs rotate together). `inv_freq` (hd/2,)
    float32: the pairs' inverse frequencies where they are not
    `base^(-2i/hd)` (a rotary scaling: `yarn_inv_freq`); the angles are
    float32 whatever the positions' range."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    half = head_dim // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's blended inverse frequencies (Peng et al. 2023,
    arXiv:2309.00071, as the published `DeepseekV2YarnRotaryEmbedding`
    writes them), (dim/2,) float32: with `f_i = base^(-2i/dim)`, a pair
    that turns more than `beta_fast` times over the `original_max`
    positions keeps `f_i`, one that turns fewer than `beta_slow` times
    takes `f_i / factor`, and between the two correction dimensions
    `low = floor(corr(beta_fast))`, `high = ceil(corr(beta_slow))`,
    `corr(n) = dim ln(original_max / (2 pi n)) / (2 ln base)`, the two
    blend linearly: `f_i (1 - r_i) + (f_i / factor) r_i`, `r_i =
    clip((i - low) / (high - low), 0, 1)`. Host arithmetic: the result
    is a constant of the program."""
    half = dim // 2
    f = base ** (-np.arange(half, dtype=np.float32) / np.float32(half))
    corr = lambda n: dim * math.log(original_max / (2 * math.pi * n)) \
        / (2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001               # as published: no division by zero
    r = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                0.0, 1.0).astype(np.float32)
    return (f * (1.0 - r) + f / np.float32(factor) * r).astype(np.float32)


def yarn_mscale(factor: float, m: float = 1.0) -> float:
    """YaRN's attention temperature `0.1 m ln(factor) + 1` (1 where the
    context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_rotate(x, cos, sin):
    """Rotate (..., T, H, hd) by per-position tables (..., T, hd/2) — or
    a single position's (hd/2,) tables for one decode step. Computed in
    f32 (angles are precision-sensitive at long range) and cast back."""
    half = x.shape[-1] // 2
    dt = x.dtype
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    if cos.ndim == 1:            # single position: broadcast over heads
        c, s = cos, sin
    else:                        # (..., T, half) -> (..., T, 1, half):
        # an axis inserted before `half` broadcasts over heads; leading
        # dims (e.g. the slotted decode's per-slot position batch) align
        # with x's leading dims
        c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(dt)
