"""Plain reference of the `olmo_hybrid` family (Hugging Face
`model_type` `olmo_hybrid`: gated delta-rule layers and full attention
layers, each followed by a dense gated MLP, post-norm blocks, an untied
head): the forward pass in straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`. The delta rule is a
`lax.scan` over time exactly as written below, attention is the naive
softmax over a full score matrix; no cache, no kernels, no batching,
no chunking, and nothing of the program under test. A configuration's
own reference file (`configs/<name>_reference.py`) binds `Consts` from
the configuration file beside it and documents that configuration's
departures; the tests bind a tiny set.

It is handed weights the benchmark drew from the seed, in bfloat16; one
layer's weights at a time are taken up to float32, so that 8.2 GB of
weights and one float32 layer fit a 16 GB chip together.

All norms are RMSNorm with a learned gain, `Norm(u) = u / sqrt(mean(u^2)
+ eps) * w`. With `x` a layer's input:

    h    = x + Norm_a(Mixer(x))                                 a layer,
    y    = h + Norm_f((silu(h Wg) * (h Wu)) Wd)                 post-norm
    out  = Norm(y_last) W_head                                  (untied)

    full attention: q = Norm_q(x Wq), k = Norm_k(x Wk) over the WHOLE
        projection, then split into heads; v = x Wv; causal softmax at
        1 / sqrt(head size); no positions, no bias; . Wo
    linear attention, per head of d_k keys and d_v values:
        [q | k | v | gate | a | b] = x W_in
        [q; k; v] = silu(conv([q; k; v]))     depthwise, causal, no bias
        q = q / sqrt(|q|^2 + 1e-6) / sqrt(d_k);  k = k / sqrt(|k|^2 + 1e-6)
        beta = 2 sigmoid(b)   (2: `linear_allow_neg_eigval`; else 1)
        g = -exp(A_log) softplus(a + dt_bias);  alpha = exp(g)
        S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
        o_t = S_t q_t                           S (d_v, d_k), S_0 = 0
        out = (Norm_o(o) * silu(gate)) W_o      Norm_o per head over d_v

`precision` selects what the arithmetic is done in. "float32" is the
reference proper. "float8" is the control, one precision below the
bfloat16 the family's configurations state: every weight matrix and
every intermediate a bfloat16 program would round to bfloat16 is rounded
to float8 (e4m3, under a per-tensor power-of-two scale); the matrix
state stays float32, as the program's does.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense_reference import _low

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Consts:
    """What the forward pass needs beside the weights' own shapes."""
    layer_types: tuple
    l_heads: int
    l_key: int
    l_value: int
    neg_eigval: bool


def consts_from_config(cfg: dict) -> Consts:
    """From a configuration file of the family (Hugging Face's keys)."""
    return Consts(
        layer_types=tuple(cfg["layer_types"])[:int(cfg["num_hidden_layers"])],
        l_heads=int(cfg["linear_num_value_heads"]),
        l_key=int(cfg["linear_key_head_dim"]),
        l_value=int(cfg["linear_value_head_dim"]),
        neg_eigval=bool(cfg["linear_allow_neg_eigval"]))


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w, precision)), precision)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def linear_mixer(p, x, c: Consts, *, eps: float, precision: str):
    """`x` (T, d) -> (T, d): the delta rule one position at a time."""
    low = functools.partial(_low, precision=precision)
    T = x.shape[0]
    H, dk, dv = c.l_heads, c.l_key, c.l_value
    qw, vw = H * dk, H * dv
    cw = 2 * qw + vw
    z = _mm(x, p["Win"], precision)
    qkv, gate = z[:, :cw], z[:, cw:cw + vw]
    a, b = z[:, cw + vw:cw + vw + H], z[:, cw + vw + H:]
    K = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, cw)), qkv], axis=0)
    qkv = low(_silu(sum(padded[k:k + T] * p["conv_w"][:, k]
                        for k in range(K))))
    q = qkv[:, :qw].reshape(T, H, dk)
    k = qkv[:, qw:2 * qw].reshape(T, H, dk)
    v = qkv[:, 2 * qw:].reshape(T, H, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = (2.0 if c.neg_eigval else 1.0) / (1.0 + jnp.exp(-b))
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jnp.log1p(jnp.exp(a + p["dt_bias"])))

    def step(S, inp):
        qt, kt, vt, at, bt = inp
        S = at[:, None, None] * S                             # (H, dv, dk)
        S = S + (bt[:, None] * (vt - jnp.einsum("hvk,hk->hv", S, kt))
                 )[:, :, None] * kt[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, dv, dk)),
                        (q, k, v, alpha, beta))
    y = low(low(_rms(low(o), p["gn"], eps)).reshape(T, vw) * _silu(gate))
    return _mm(y, p["Wout"], precision)


def full_mixer(p, x, *, n_heads: int, eps: float, precision: str):
    low = functools.partial(_low, precision=precision)
    T, d = x.shape
    hd = d // n_heads
    qkv = _mm(x, p["Wqkv"], precision)
    q = low(_rms(qkv[:, :d], p["qn"], eps)).reshape(T, n_heads, hd)
    k = low(_rms(qkv[:, d:2 * d], p["kn"], eps)).reshape(T, n_heads, hd)
    v = qkv[:, 2 * d:].reshape(T, n_heads, hd)
    s = jnp.einsum("thd,shd->hts", q, k) / hd ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", low(jax.nn.softmax(s, axis=-1)), v)
    return _mm(low(a.reshape(T, d)), p["Wo"], precision)


def mlp(p, h, *, precision: str):
    u = _low(_silu(_mm(h, p["Wg"], precision)) * _mm(h, p["Wu"], precision),
             precision)
    return _mm(u, p["Wd"], precision)


@functools.partial(jax.jit, static_argnames=("c", "kind", "n_heads", "eps",
                                             "precision"))
def layer(p, x, *, c: Consts, kind: str, n_heads: int, eps: float,
          precision: str):
    """One layer on (T, d), its weights taken up to float32 here."""
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(jnp.float32) for k, v in p.items()}
        low = functools.partial(_low, precision=precision)
        if kind == LINEAR:
            m = linear_mixer(p, x, c, eps=eps, precision=precision)
        else:
            m = full_mixer(p, x, n_heads=n_heads, eps=eps,
                           precision=precision)
        h = low(x + low(_rms(m, p["n1"], eps)))
        f = mlp(p, h, precision=precision)
        return low(h + low(_rms(f, p["n2"], eps)))


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(emb, ids, *, precision: str):
    return _low(emb[ids].astype(jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(head, lnf, x, *, eps: float, precision: str):
    with jax.default_matmul_precision("highest"):
        x = _low(_rms(x, lnf.astype(jnp.float32), eps), precision)
        return jnp.matmul(x, _low(head.astype(jnp.float32), precision))


def logits_at(w, ids, rows, *, c: Consts, n_heads: int, eps: float,
              precision: str = "float32"):
    """Next-token logits (len(rows), V) at positions `rows` of the one
    sequence `ids` (1, T). `w` is the family's tree: `emb`, `lnf`,
    `head` and one dict of bfloat16 leaves a layer."""
    x = _embed(w["emb"], ids[0], precision=precision)
    for kind, p in zip(c.layer_types, w["layers"]):
        x = layer(p, x, c=c, kind=kind, n_heads=n_heads, eps=eps,
                  precision=precision)
    return _head(w["head"], w["lnf"], x[rows], eps=eps,
                 precision=precision)


def bound_logits_at(config_file):
    """`logits_at` with the constants of one configuration file, under
    the signature `harness/serve_cell.py` calls."""
    c = consts_from_config(json.loads(open(config_file).read()))

    def bound(w, ids, rows, *, n_heads: int, eps: float,
              precision: str = "float32"):
        return logits_at(w, ids, rows, c=c, n_heads=n_heads, eps=eps,
                         precision=precision)
    return bound
