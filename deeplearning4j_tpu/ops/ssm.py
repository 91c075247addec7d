"""Mamba-2 (state-space duality) sequence math, shared by the layer's
full-sequence forward, the serving engine's prefill and its one-token
decode step.

One head's recurrence, with scalar decay per head and step:

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t      (P, N)
    y_t = h_t C_t + D * x_t                                     (P,)

`x` is (B, T, H, P), `dt` (B, T, H) already through softplus, `A` (H,)
negative, `D` (H,); `Bm`/`Cm` are (B, T, N) where every head shares them
(one group) or (B, T, G, N) where head `h` reads group `h // (H / G)`
(each of the three forms then takes the heads as (G, H / G) and leaves
the one-group arithmetic as it is). Three forms compute it:
`ssm_sequential` (a `lax.scan` over time,
the definition), `ssd_chunked` (the block-decomposed form of the Mamba-2
paper, arXiv:2405.21060 §6: quadratic attention-like products inside a
chunk, the recurrence only between chunks) and `ssm_step` (one token for
every decode slot). All three keep the state in float32 whatever the
activations' dtype; tests hold them to one another.

A position whose `dt` is 0 leaves the state exactly as it was (decay
`exp(0) = 1`, input `0`): that is how pad positions of a padded prompt
bucket and inactive decode slots are kept out of the state, with no
select over the state itself.

The depthwise causal convolution in front of the SSM keeps its last
`K - 1` inputs as a per-slot tail; `causal_conv` takes the tail in and
hands back the tail after `n_valid` positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _by_group(Bm, x):
    """How the three forms read `Bm`/`Cm` against `x`'s head axis: (the
    einsum letters of the head axis, of the group axis, and the function
    that splits an array's head axis `axis` into (G, H / G))."""
    if Bm.ndim == x.ndim - 1:           # one group: no group axis
        return "h", "", lambda u, axis: u
    G = Bm.shape[-2]

    def split(u, axis):
        axis %= u.ndim
        return u.reshape(*u.shape[:axis], G, u.shape[axis] // G,
                         *u.shape[axis + 1:])

    return "gr", "g", split


def ssm_sequential(x, dt, A, Bm, Cm, D, h0=None):
    """The recurrence as written, one step at a time. Returns
    (y (B, T, H, P) in x's dtype, final state (B, H, P, N) float32)."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    h0 = jnp.zeros((B, H, P, N), F32) if h0 is None else h0.astype(F32)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        y, h = ssm_step(h, xt, dtt, A, bt, ct, D)
        return h, y

    xs = (jnp.swapaxes(x, 0, 1), jnp.swapaxes(dt, 0, 1),
          jnp.swapaxes(Bm, 0, 1), jnp.swapaxes(Cm, 0, 1))
    h, ys = jax.lax.scan(step, h0, xs)
    return jnp.swapaxes(ys, 0, 1), h


def ssm_step(h, x, dt, A, Bm, Cm, D):
    """One position for every row: `h` (S, H, P, N) float32, `x`
    (S, H, P), `dt` (S, H), `Bm`/`Cm` (S, N) or (S, G, N). Returns
    (y (S, H, P) in x's dtype, new state). A row with `dt == 0` keeps
    its state bit for bit."""
    hd, g, split = _by_group(Bm, x)
    xf, dt = split(x.astype(F32), 1), split(dt.astype(F32), 1)
    decay = jnp.exp(dt * split(A.astype(F32), 0))             # (S, H)
    dx = dt[..., None] * xf                                   # (S, H, P)
    hs = decay[..., None, None] * split(h, 1) \
        + dx[..., None] * jnp.expand_dims(Bm.astype(F32), (-3, -2))
    y = jnp.einsum(f"s{hd}pn,s{g}n->s{hd}p", hs, Cm.astype(F32)) \
        + split(D.astype(F32), 0)[..., None] * xf
    return y.reshape(x.shape).astype(x.dtype), hs.reshape(h.shape)


def _segsum(a):
    """(..., L) -> (..., L, L): entry [i, j] is sum(a[j+1 .. i]) for
    j <= i and -inf above the diagonal, so that exp() gives the decay
    from position j to position i."""
    L = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    d = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((L, L), bool)), d, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, D, *, chunk: int, h0=None):
    """The same recurrence by chunks of `chunk` positions. T need not be
    a multiple of the chunk: the tail is padded with `dt = 0`, which
    leaves the state alone. `Bm`/`Cm` (B, T, N) or (B, T, G, N). Returns
    (y, final state) as `ssm_sequential`."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    hd, g, split = _by_group(Bm, x)
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:
        along_t = lambda u: jnp.pad(
            u, ((0, 0), (0, pad)) + ((0, 0),) * (u.ndim - 2))
        x, dt, Bm, Cm = along_t(x), along_t(dt), along_t(Bm), along_t(Cm)
    nc = (T + pad) // Q
    dtf = dt.astype(F32)
    xd = split((x.astype(F32) * dtf[..., None]).reshape(B, nc, Q, H, P), 3)
    a = (dtf * A.astype(F32)).reshape(B, nc, Q, H)
    a = jnp.transpose(a, (0, 3, 1, 2))                        # (B, H, c, Q)
    Bc = Bm.astype(F32).reshape(B, nc, Q, *Bm.shape[2:])
    Cc = Cm.astype(F32).reshape(B, nc, Q, *Cm.shape[2:])
    a_cum = jnp.cumsum(a, axis=-1)
    # inside each chunk: y_i += sum_{j<=i} (C_i . B_j) decay(j->i) dt_j x_j
    decay_in = split(jnp.exp(_segsum(a)), 1)                  # (B,H,c,Q,Q)
    cb = jnp.einsum(f"bcl{g}n,bcs{g}n->b{g}cls", Cc, Bc)
    y = jnp.einsum(f"b{g}cls,b{hd}cls,bcs{hd}p->bcl{hd}p", cb, decay_in, xd)
    # what each chunk adds to the state by its end
    to_end = split(jnp.exp(a_cum[..., -1:] - a_cum), 1)       # (B,H,c,Q)
    states = jnp.einsum(f"bcl{g}n,b{hd}cl,bcl{hd}p->bc{hd}pn", Bc, to_end,
                        xd).reshape(B, nc, H, P, N)
    # the recurrence between chunks: the state entering each chunk
    h0 = jnp.zeros((B, H, P, N), F32) if h0 is None else h0.astype(F32)
    chunk_decay = jnp.exp(a_cum[..., -1])                     # (B, H, c)

    def carry(h, inp):
        s, dec = inp
        return dec[..., None, None] * h + s, h

    h, entering = jax.lax.scan(
        carry, h0, (jnp.swapaxes(states, 0, 1),
                    jnp.moveaxis(chunk_decay, 2, 0)))
    entering = jnp.swapaxes(entering, 0, 1)                   # (B,c,H,P,N)
    y = y + jnp.einsum(f"bcl{g}n,bc{hd}pn,b{hd}cl->bcl{hd}p", Cc,
                       split(entering, 2), split(jnp.exp(a_cum), 1))
    y = y.reshape(B, nc * Q, H, P)[:, :T] \
        + D.astype(F32)[None, None, :, None] * x[:, :T].astype(F32)
    return y.astype(x.dtype), h


def causal_conv(u, w, b, tail=None, n_valid=None):
    """Depthwise causal convolution over time with a carried tail.
    `u` (B, T, Cw); `w` (Cw, K); `b` (Cw,) or None; `tail` (B, K - 1, Cw), the
    K - 1 inputs before u[:, 0] (zeros at the start of a sequence; the
    channel axis is minor, as a TPU lays it out anyway). Returns
    (out (B, T, Cw), the tail after `n_valid` positions: the last K - 1
    inputs of [tail | u[:, :n_valid]]; `n_valid` defaults to T and may
    be traced)."""
    B, T, Cw = u.shape
    K = w.shape[1]
    if tail is None:
        tail = jnp.zeros((B, K - 1, Cw), u.dtype)
    full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    out = 0.0 if b is None else b.astype(F32)
    for k in range(K):  # four taps, summed in float32
        out = out + full[:, k:k + T].astype(F32) * w[:, k].astype(F32)
    n = T if n_valid is None else n_valid
    return out.astype(u.dtype), \
        jax.lax.dynamic_slice_in_dim(full, n, K - 1, axis=1)


def conv_step(u, w, b, tail):
    """One position for every slot: `u` (S, Cw), `tail` (K - 1, S, Cw):
    the slots' tails are kept tap-major, the layout the TPU compiler
    gives them whatever is asked (a 3-long axis anywhere but outermost
    is padded to a tile, and the step's programs copied the array into
    this layout and back). Returns (out (S, Cw), new tail)."""
    window = jnp.concatenate([tail.astype(u.dtype), u[None]], axis=0)
    out = jnp.sum(window.astype(F32) * w.astype(F32).T[:, None, :], axis=0)
    if b is not None:
        out = out + b.astype(F32)
    return out.astype(u.dtype), window[1:]
