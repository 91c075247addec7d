"""Plain reference of the `granite_hybrid` family (Hugging Face
`GraniteMoeHybridForCausalLM`): the forward pass in straightforward
float32 `jax.numpy` under `jax.default_matmul_precision("highest")`. The
state-space recurrence is a `lax.scan` over time, attention is the naive
softmax over a full score matrix, the experts are a masked loop over the
experts held; no cache, no kernels, no batching, and nothing of the
program under test. A configuration's own reference file
(`configs/<name>_reference.py`) binds `Consts` from the configuration
file beside it and documents that configuration's departures; the tests
bind a tiny set.

It is handed weights the benchmark drew from the seed, in bfloat16; one
layer's weights at a time are taken up to float32, so that 9.9 GB of
weights and one float32 layer fit a 16 GB chip together.

    x    = embedding_multiplier * E[ids]
    x    = x + residual_multiplier * mixer(RMSNorm(x))            a layer,
    x    = x + residual_multiplier * (routed(u) + shared(u)),     u = RMSNorm(x)
    out  = RMSNorm(x) E^T / logits_scaling                        (tied head)

    mamba mixer: [z | xBC | dt] = u W_in; xBC = silu(conv(xBC) + b);
        [x | B | C] = xBC; dt = softplus(dt + dt_bias); A = -exp(A_log);
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;  y_t = h_t C_t + D x_t;
        y = RMSNorm(y * silu(z)) * w over the whole inner width; out = y W_out
    attention mixer: grouped-query heads, no bias, no positions,
        scores * attention_multiplier, causal
    routed: logits = u W_r over all the router's experts; the top_k by
        logit; gates = softmax over those; expert e gives
        W_d,e (silu(W_g,e u) * W_u,e u); shared: the same, ungated.
        Only the experts held (from `held_first` on, as many as the
        weights carry) add to the sum.

`precision` selects what the arithmetic is done in. "float32" is the
reference proper. "float8" is the control, one precision below the
bfloat16 the family's configurations state: every weight matrix and
every intermediate a bfloat16 program would round to bfloat16 is rounded
to float8 (e4m3, under a per-tensor power-of-two scale); the recurrent
state stays float32, as the program's does.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense_reference import _low


@dataclasses.dataclass(frozen=True)
class Consts:
    """What the forward pass needs beside the weights' own shapes."""
    layer_types: tuple
    emb_mult: float
    res_mult: float
    logit_div: float
    attn_mult: float
    kv_heads: int
    top_k: int
    held_first: int
    m_heads: int
    m_head: int
    m_state: int


def consts_from_config(cfg: dict) -> Consts:
    """From a configuration file of the family (Hugging Face's keys)."""
    return Consts(
        layer_types=tuple(cfg["layer_types"])[:int(cfg["num_hidden_layers"])],
        emb_mult=float(cfg["embedding_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]),
        logit_div=float(cfg["logits_scaling"]),
        attn_mult=float(cfg["attention_multiplier"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        top_k=int(cfg["num_experts_per_tok"]),
        held_first=int(cfg.get("deployment", {})
                       .get("experts_held_first", 0)),
        m_heads=int(cfg["mamba_n_heads"]), m_head=int(cfg["mamba_d_head"]),
        m_state=int(cfg["mamba_d_state"]))


EXPERT_STACKS = ("Wg", "Wu", "Wd")


def _f32(p: dict) -> dict:
    """A layer's leaves in float32, but for the stacked routed experts,
    which `experts` takes up one expert at a time: whole, they are
    1.4 GB of a 16 GB chip that also holds 9.9 GB of bfloat16 weights
    and whatever the program has not let go of."""
    return {k: v if k in EXPERT_STACKS else v.astype(jnp.float32)
            for k, v in p.items()}


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w, precision)), precision)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def mamba_mixer(p, u, c: Consts, *, eps: float, precision: str):
    """`u` (T, d) -> (T, d): the recurrence one position at a time."""
    low = functools.partial(_low, precision=precision)
    T = u.shape[0]
    di = c.m_heads * c.m_head
    cw = di + 2 * c.m_state
    zxd = _mm(u, p["Win"], precision)
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + cw], zxd[:, di + cw:]
    K = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, cw)), xbc], axis=0)
    conv = p["conv_b"] + sum(padded[k:k + T] * p["conv_w"][:, k]
                             for k in range(K))
    xbc = low(_silu(conv))
    x = xbc[:, :di].reshape(T, c.m_heads, c.m_head)
    Bm, Cm = xbc[:, di:di + c.m_state], xbc[:, di + c.m_state:]
    dt = jnp.log1p(jnp.exp(dt + p["dt_bias"]))               # softplus
    A = -jnp.exp(p["A_log"])

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt * A)[:, None, None] * h \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, ct) + p["D"][:, None] * xt

    h0 = jnp.zeros((c.m_heads, c.m_head, c.m_state))
    _, y = jax.lax.scan(step, h0, (x, dt, Bm, Cm))
    y = low(_rms(low(y.reshape(T, di)) * _silu(z), p["gn"], eps))
    return _mm(y, p["Wout"], precision)


def attention_mixer(p, u, c: Consts, *, n_heads: int, precision: str):
    low = functools.partial(_low, precision=precision)
    T, d = u.shape
    hd = d // n_heads
    kvw = c.kv_heads * hd
    qkv = _mm(u, p["Wqkv"], precision)
    q = qkv[:, :d].reshape(T, n_heads, hd)
    k = qkv[:, d:d + kvw].reshape(T, c.kv_heads, hd)
    v = qkv[:, d + kvw:].reshape(T, c.kv_heads, hd)
    g = n_heads // c.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * c.attn_mult
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", low(jax.nn.softmax(s, axis=-1)), v)
    return _mm(low(a.reshape(T, d)), p["Wo"], precision)


def experts(p, u, c: Consts, *, precision: str):
    """Routed experts held here under the router's gates, plus the
    shared expert, for `u` (T, d)."""
    low = functools.partial(_low, precision=precision)

    def mlp(Wg, Wu, Wd):
        h = low(_silu(_mm(u, Wg, precision)) * _mm(u, Wu, precision))
        return _mm(h, Wd, precision)

    logits = jnp.matmul(u, p["router"])
    top_v, top_i = jax.lax.top_k(logits, c.top_k)
    gates = jax.nn.softmax(top_v, axis=-1)

    def held_expert(out, ew):
        e, Wg, Wu, Wd = (ew[0],) + tuple(w.astype(jnp.float32)
                                         for w in ew[1:])
        gate = jnp.sum(jnp.where(top_i == c.held_first + e, gates, 0.0), -1)
        return out + gate[:, None] * mlp(Wg, Wu, Wd), None

    out, _ = jax.lax.scan(
        held_expert, mlp(p["sWg"], p["sWu"], p["sWd"]),
        (jnp.arange(p["Wg"].shape[0]), p["Wg"], p["Wu"], p["Wd"]))
    return low(out)


@functools.partial(jax.jit, static_argnames=("c", "kind", "n_heads",
                                             "eps", "precision"))
def layer(p, x, *, c: Consts, kind: str, n_heads: int, eps: float, precision: str):
    """One layer on (T, d), its weights taken up to float32 here."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        low = functools.partial(_low, precision=precision)
        u = low(_rms(x, p["n1"], eps))
        if kind == "mamba":
            m = mamba_mixer(p, u, c, eps=eps, precision=precision)
        else:
            m = attention_mixer(p, u, c, n_heads=n_heads,
                                precision=precision)
        x = low(x + c.res_mult * m)
        u = low(_rms(x, p["n2"], eps))
        return low(x + c.res_mult * experts(p, u, c, precision=precision))


@functools.partial(jax.jit, static_argnames=("mult", "precision"))
def _embed(emb, ids, *, mult: float, precision: str):
    return _low(mult * emb[ids].astype(jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("div", "eps", "precision"))
def _head(emb, lnf, x, *, div: float, eps: float, precision: str):
    with jax.default_matmul_precision("highest"):
        x = _low(_rms(x, lnf.astype(jnp.float32), eps), precision)
        return jnp.matmul(x, _low(emb.astype(jnp.float32), precision).T) \
            / div


def logits_at(w, ids, rows, *, c: Consts, n_heads: int, eps: float,
              precision: str = "float32"):
    """Next-token logits (len(rows), V) at positions `rows` of the one
    sequence `ids` (1, T). `w` is the family's tree: `emb`, `lnf` and
    one dict of bfloat16 leaves a layer."""
    x = _embed(w["emb"], ids[0], mult=c.emb_mult, precision=precision)
    for kind, p in zip(c.layer_types, w["layers"]):
        x = layer(p, x, c=c, kind=kind, n_heads=n_heads, eps=eps,
                  precision=precision)
    return _head(w["emb"], w["lnf"], x[rows], div=c.logit_div, eps=eps,
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
