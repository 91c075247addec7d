"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) sequence math,
shared by the layer's full-sequence forward, the serving engine's
prefill and its one-token decode step.

One head keeps a matrix state `S` (d_v x d_k) and moves it by a decayed
rank-one correction per position:

    S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T,   a_t = exp(g_t)
    o_t = S_t q_t

`q`, `k` (B, T, H, d_k) arrive already normalised and scaled (the mixer
does that), `v` (B, T, H, d_v), `g` (B, T, H) the log decay (<= 0) and
`b` = `beta` (B, T, H) in [0, 2]. The transition `a (I - b k k^T)` is
not diagonal, which is why `ops/ssm.py` cannot express it.

**The decay's two shapes.** `g` (B, T, H): one number a head, the rule
above. `g` (B, T, H, d_k): one number a KEY CHANNEL (Kimi Delta
Attention, arXiv:2510.26692), `a_t` a vector and the state's columns
decayed each by its own,

    S_t = S_{t-1} Diag(a_t) + b_t (v_t - S_{t-1} Diag(a_t) k_t) k_t^T

All three forms below take both; with a scalar `g` each traces the
operations it always did. Three forms
compute it: `delta_sequential` (a `lax.scan` over time, the definition),
`delta_chunked` (the WY / UT-transform form of arXiv:2406.06484: inside
a chunk of C positions the C corrections are the solution of one
unit-lower-triangular system, and the recurrence runs only between
chunks) and `delta_step` (one token for every decode slot). All three
keep the state in float32 whatever the activations' dtype; tests hold
them to one another.

**The state's layout** is the same in all three and in the engine's
slots: `(B, d_k, H * d_v)`, the transposed per-head states side by side
along the minor axis (`state[b, i, h * d_v + j] = S_h[j, i]`). At the
published sizes of Olmo-Hybrid (d_k 96, d_v 192, 30 heads) neither 96
nor 192 is a multiple of the TPU's 128 lanes, so a `(.., 192, 96)` or
`(.., 96, 192)` state would be stored a third larger than it is; 96 x
5760 = 12 x 8 sublanes by 45 x 128 lanes is stored as it is.

A position with `beta = 0` and `g = 0` leaves the state exactly as it
was (`1 * S + k * 0`): that is how pad positions of a padded prompt
bucket and inactive decode slots are kept out of the state, with no
select over the state itself.

**The chunked form under a decay a channel.** Inside a chunk the
corrections solve `(I + A) u = b (v - ...)` with `A_ij = b_i sum_c k_ic
k_jc exp(G_ic - G_jc)` (`G` the running sum of `g` from the chunk's
start), and the outputs need the same sum with `q_i` for `b_i k_i`. With
one decay a head the exponential leaves the sum and `A` is one matmul
times a (C, C) table; with one a channel it does not. Factored, `(k_i
exp(G_i - R)) . (k_j exp(R - G_j))` about a reference `R`, the second
operand GROWS like `exp(|G|)`: float32 holds it only while a span's
summed `|g|` stays under about 85, which a gate bounded below by -5
(the published `kda_safe_gate`, `kda_lower_bound` -5) keeps true over 16
positions and no further. So a chunk goes by sub-blocks of `_SUB` = 16
positions: a sub-block against an EARLIER one is factored about the later
sub-block's own start, where both exponents are <= 0 whatever the gate (a
decay that underflows there is one whose true product is smaller
still); a sub-block against ITSELF is the direct `(16, 16, d_k)` sum,
the difference masked before the exponential, which needs no bound
either. 16 is the factored form's span under the published bound and
keeps the direct part at a quarter of a 64-chunk's pairs; the form is
held to a float64 recurrence in `tests/test_delta_rule.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_BLOCK = 8  # rows solved at once inside a chunk's triangular system
_SUB = 16   # positions a sub-block: a decay a channel (module docstring)


def heads_of(state, n_heads: int):
    """(B, d_k, H * d_v) -> (B, H, d_k, d_v)."""
    B, dk, HV = state.shape
    return jnp.transpose(state.reshape(B, dk, n_heads, HV // n_heads),
                         (0, 2, 1, 3))


def flat_of(per_head):
    """(B, H, d_k, d_v) -> (B, d_k, H * d_v)."""
    B, H, dk, dv = per_head.shape
    return jnp.transpose(per_head, (0, 2, 1, 3)).reshape(B, dk, H * dv)


def delta_step(state, q, k, v, g, beta):
    """One position for every row: `state` (S, d_k, H * d_v) float32,
    `q`/`k` (S, H, d_k), `v` (S, H, d_v), `beta` (S, H), `g` (S, H) or,
    a decay a key channel, (S, H, d_k). Returns
    (o (S, H, d_v) in v's dtype, new state). Products and sums are
    elementwise in float32 (no matrix unit, so no reduced-precision
    pass); a row with `beta == 0` and `g == 0` keeps its state bit for
    bit."""
    S, dk, HV = state.shape
    H = q.shape[1]
    s4 = state.astype(F32).reshape(S, dk, H, HV // H)
    kt = jnp.swapaxes(k.astype(F32), 1, 2)[..., None]        # (S, dk, H, 1)
    qt = jnp.swapaxes(q.astype(F32), 1, 2)[..., None]
    if g.ndim == 3:                       # a decay a channel: the rows'
        s4 = s4 * jnp.swapaxes(jnp.exp(g.astype(F32)), 1, 2)[..., None]
        u = beta.astype(F32)[..., None] \
            * (v.astype(F32) - jnp.sum(s4 * kt, axis=1))
        s4 = s4 + kt * u[:, None]
        o = jnp.sum(s4 * qt, axis=1)
        return o.astype(v.dtype), s4.reshape(S, dk, HV)
    a = jnp.exp(g.astype(F32))                                # (S, H)
    sk = jnp.sum(s4 * kt, axis=1)                             # (S, H, dv)
    u = beta.astype(F32)[..., None] * (v.astype(F32) - a[..., None] * sk)
    s4 = a[:, None, :, None] * s4 + kt * u[:, None]
    o = jnp.sum(s4 * qt, axis=1)
    return o.astype(v.dtype), s4.reshape(S, dk, HV)


def delta_sequential(q, k, v, g, beta, h0=None):
    """The recurrence as written, one step at a time. Returns
    (o (B, T, H, d_v) in v's dtype, final state (B, d_k, H * d_v)
    float32)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    h0 = jnp.zeros((B, dk, H * dv), F32) if h0 is None else h0.astype(F32)

    def step(h, inp):
        o, h = delta_step(h, *inp)
        return h, o

    h, os_ = jax.lax.scan(
        step, h0, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta)))
    return jnp.swapaxes(os_, 0, 1), h


def _solve_unit_lower(A, rhs):
    """X with (I + A) X = rhs, `A` (..., C, C) strictly lower
    triangular, by block forward substitution: every product is a
    float32 matmul at full precision, and the only inverses taken are
    of the `_BLOCK`-row diagonal blocks, through the finite product
    (I - D)(I + D^2)(I + D^4) of their Neumann series (D^_BLOCK = 0).
    The series over a whole chunk of 64 would be exact too, but its
    terms grow like binomial coefficients before they cancel: with keys
    that point the same way it came out 1e7 off, and at 16 rows a block
    0.07 off, where 8 rows are as close to a float64 recurrence as the
    sequential float32 form is (`tests/test_delta_rule.py`)."""
    C = A.shape[-1]
    out = []
    for lo in range(0, C, _BLOCK):
        hi = min(lo + _BLOCK, C)
        r = rhs[..., lo:hi, :]
        if out:
            r = r - jnp.matmul(A[..., lo:hi, :lo],
                               jnp.concatenate(out, axis=-2), precision=_HI)
        D, eye = A[..., lo:hi, lo:hi], jnp.eye(hi - lo, dtype=F32)
        inv, power, n = eye - D, D, 2
        while n < hi - lo:
            power = jnp.matmul(power, power, precision=_HI)
            inv = jnp.matmul(inv, eye + power, precision=_HI)
            n *= 2
        out.append(jnp.matmul(inv, r, precision=_HI))
    return jnp.concatenate(out, axis=-2)


def _channel_decayed(gc, kc, rows):
    """For each `x` of `rows`: `sum_c x_ic k_jc exp(G_ic - G_jc)` for j
    <= i, 0 above the diagonal, (..., C, C); `gc` (..., C, d_k) the
    running log decay a channel, `kc` and every `x` (..., C, d_k). By
    sub-blocks of `_SUB` positions (module docstring): against earlier
    positions factored about the sub-block's own start, against itself
    the direct sum."""
    C = gc.shape[-2]
    out = [[] for _ in rows]
    for lo in range(0, C, _SUB):
        hi = min(lo + _SUB, C)
        gi, ki = gc[..., lo:hi, :], kc[..., lo:hi, :]
        lower = jnp.tril(jnp.ones((hi - lo, hi - lo), bool))[..., None]
        kd = ki[..., None, :, :] * jnp.exp(jnp.where(
            lower, gi[..., :, None, :] - gi[..., None, :, :], -jnp.inf))
        parts = [[jnp.sum(x[..., lo:hi, None, :] * kd, axis=-1)]
                 for x in rows]
        if lo:
            start = gc[..., lo - 1:lo, :]     # the sum up to the sub-block
            cols = kc[..., :lo, :] * jnp.exp(start - gc[..., :lo, :])
            into = jnp.exp(gi - start)
            for part, x in zip(parts, rows):
                part.insert(0, jnp.einsum(
                    "...id,...jd->...ij", x[..., lo:hi, :] * into, cols,
                    precision=_HI))
        if hi < C:
            for part in parts:
                part.append(jnp.zeros((*gc.shape[:-2], hi - lo, C - hi),
                                      F32))
        for o, part in zip(out, parts):
            o.append(jnp.concatenate(part, axis=-1))
    return [jnp.concatenate(o, axis=-2) for o in out]


def delta_chunked(q, k, v, g, beta, *, chunk: int = 64, h0=None,
                  n_valid=None):
    """The same recurrence by chunks of `chunk` positions. T need not be
    a multiple of the chunk, and positions at and after `n_valid` (a
    traced scalar, default T) are padding: both get `beta = 0, g = 0`,
    which leaves the state alone. Returns (o, final state) as
    `delta_sequential`."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(int(chunk), T)
    channels = g.ndim == 4                # a decay a key channel
    if n_valid is not None:
        keep = (jnp.arange(T) < n_valid)[None, :, None]
        g = jnp.where(keep[..., None] if channels else keep, g, 0.0)
        beta = jnp.where(keep, beta, 0.0)
    pad = -T % C
    n = (T + pad) // C

    def chunks(x):  # (B, T, H, w) -> (B, H, n, C, w), float32
        x = jnp.pad(x.astype(F32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return jnp.transpose(x.reshape(B, n, C, H, x.shape[-1]),
                             (0, 3, 1, 2, 4))

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    # the running log decay from a chunk's start: (B,H,n,C), or with a
    # decay a channel (B,H,n,C,dk)
    gc = jnp.cumsum(chunks(g), axis=-2) if channels \
        else jnp.cumsum(chunks(g[..., None])[..., 0], axis=-1)
    bc = chunks(beta[..., None])
    lower = jnp.tril(jnp.ones((C, C), bool))
    if not channels:
        # decay from position j to position i of one chunk, 0 above the
        # diagonal (masked before the exp: the difference is positive
        # there)
        decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                                  -jnp.inf))
    kb = kc * bc
    if channels:
        A, qk = _channel_decayed(gc, kc, (kb, qc))
        A = jnp.where(jnp.tril(lower, -1), A, 0.0)
        into = jnp.exp(gc)                        # chunk start -> position
        k_end = kc * jnp.exp(gc[..., -1:, :] - gc)
    else:
        A = jnp.einsum("bhnid,bhnjd->bhnij", kb, kc, precision=_HI) * decay
        A = jnp.where(jnp.tril(lower, -1), A, 0.0)
        into = jnp.exp(gc)[..., None]             # chunk start -> position
    sol = _solve_unit_lower(
        A, jnp.concatenate([vc * bc, kb * into], axis=-1))
    u, w = sol[..., :dv], sol[..., dv:]
    if not channels:
        qk = jnp.einsum("bhnid,bhnjd->bhnij", qc, kc, precision=_HI) * decay
        k_end = kc * jnp.exp(gc[..., -1:] - gc)[..., None]
    s0 = jnp.zeros((B, H, dk, dv), F32) if h0 is None \
        else heads_of(h0.astype(F32), H)

    def carry(s, inp):
        u_i, w_i, q_i, qk_i, k_i, end_i = inp
        vn = u_i - jnp.matmul(w_i, s, precision=_HI)
        o = jnp.matmul(q_i, s, precision=_HI) \
            + jnp.matmul(qk_i, vn, precision=_HI)
        s = s * (end_i[..., None] if channels else end_i[..., None, None]) \
            + jnp.matmul(jnp.swapaxes(k_i, -1, -2), vn, precision=_HI)
        return s, o

    # last: the decay over a whole chunk, (B,H,n) or a channel's (B,H,n,dk)
    per_chunk = (u, w, qc * into, qk, k_end,
                 jnp.exp(gc[..., -1, :] if channels else gc[..., -1]))
    s, o = jax.lax.scan(carry, s0,
                        tuple(jnp.moveaxis(x, 2, 0) for x in per_chunk))
    # (n, B, H, C, dv) -> (B, T, H, dv)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(B, n * C, H, dv)[:, :T]
    return o.astype(v.dtype), flat_of(s)
