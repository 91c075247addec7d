"""Plain reference of the `nemotron_h` family (Hugging Face
`NemotronHForCausalLM`): the forward pass in straightforward float32
`jax.numpy` under `jax.default_matmul_precision("highest")`. The
state-space recurrence is a `lax.scan` over time with its B/C groups
written out, attention is the naive softmax over a full score matrix
with the K/V heads repeated, the router and the experts are a loop over
the experts held; no cache, no kernels, no batching, and nothing of the
program under test. A configuration's own reference file
(`configs/<name>_reference.py`) binds `Consts` from the configuration
file beside it and documents that configuration's departures; the tests
bind a tiny set.

It is handed weights the benchmark drew from the seed, in bfloat16; one
layer's weights at a time are taken up to float32, and a layer's routed
experts one expert at a time.

Every layer is ONE sub-layer under one norm and one residual, all
projections without bias, `eps` the config's `layer_norm_epsilon`:

    x    = E[ids]
    x    = x + mixer(RMSNorm(x))        a layer; the mixer by its letter
    out  = RMSNorm(x) W_head            (untied head)

    M (Mamba-2, H heads of P, state N, G groups, K taps):
        [z | xBC | dt] = u W_in; xBC = silu(conv(xBC) + b);
        [x | B | C] = xBC, B and C (G, N); dt = softplus(dt + dt_bias);
        A = -exp(A_log); for head h, g = h // (H / G):
        S_h = exp(dt_h A_h) S_h + dt_h x_h (x) B_g;  y_h = S_h C_g + D_h x_h;
        y = RMSNorm_by_group(y * silu(z)) (each group's H P / G channels
        on its own, one gain an element); out = y W_out
    * (attention): q = u W_q (H_q heads of hd), k, v = u W_k, u W_v
        (H_kv heads), causal softmax(q k^T / sqrt(hd)), query head j
        reads K/V head j // (H_q / H_kv); no positions; out = a W_o
    E (experts): s = sigmoid(u W_r) over all the router's experts;
        chosen = the top_k of s + b; w = s[chosen] / (sum s[chosen] +
        1e-20) * scale; y = sum_chosen w_e relu(u Wu_e^T)^2 Wd_e
        + relu(u sWu)^2 sWd. Only the experts held (from `held_first`
        on, as many as the weights carry) add to the sum; `Wu_e` is
        held (f, d), as a `Linear(d, f)` layer stores its weight.

`precision` selects what the arithmetic is done in. "float32" is the
reference proper. "float8" is the control, one precision below the
bfloat16 the family's configurations state: every weight matrix and
every intermediate a bfloat16 program would round to bfloat16 is rounded
to float8 (e4m3, under a per-tensor power-of-two scale); the recurrent
state, the router's scores and the correction bias stay float32, as the
program's do.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense_reference import _low

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


@dataclasses.dataclass(frozen=True)
class Consts:
    """What the forward pass needs beside the weights' own shapes."""
    pattern: str
    kv_heads: int
    head_dim: int
    m_heads: int
    m_head: int
    m_state: int
    m_groups: int
    top_k: int
    routed_scale: float
    held_first: int


def consts_from_config(cfg: dict) -> Consts:
    """From a configuration file of the family (Hugging Face's keys)."""
    return Consts(
        pattern=cfg["hybrid_override_pattern"][:int(cfg["num_hidden_layers"])],
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        m_heads=int(cfg["mamba_num_heads"]),
        m_head=int(cfg["mamba_head_dim"]),
        m_state=int(cfg["ssm_state_size"]),
        m_groups=int(cfg["n_groups"]),
        top_k=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        held_first=int(cfg.get("deployment", {})
                       .get("experts_held_first", 0)))


EXPERT_STACKS = ("Wu", "Wd")


def _f32(p: dict) -> dict:
    """A layer's leaves in float32, but for the stacked routed experts,
    which `experts` takes up one expert at a time: whole, they are
    2.6 GB of a 16 GB chip that also holds 11.3 GB of bfloat16 weights."""
    return {k: v if k in EXPERT_STACKS else v.astype(jnp.float32)
            for k, v in p.items()}


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w, precision)), precision)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def mamba_mixer(p, u, c: Consts, *, eps: float, precision: str):
    """`u` (T, d) -> (T, d): the recurrence one position at a time, every
    head reading its own group's B and C."""
    low = functools.partial(_low, precision=precision)
    T = u.shape[0]
    H, P, N, G = c.m_heads, c.m_head, c.m_state, c.m_groups
    di = H * P
    cw = di + 2 * G * N
    zxd = _mm(u, p["Win"], precision)
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + cw], zxd[:, di + cw:]
    K = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, cw)), xbc], axis=0)
    conv = p["conv_b"] + sum(padded[k:k + T] * p["conv_w"][:, k]
                             for k in range(K))
    xbc = low(_silu(conv))
    x = xbc[:, :di].reshape(T, H, P)
    Bm = xbc[:, di:di + G * N].reshape(T, G, N)
    Cm = xbc[:, di + G * N:].reshape(T, G, N)
    group_of = jnp.arange(H) // (H // G)                     # head -> group
    dt = jnp.log1p(jnp.exp(dt + p["dt_bias"]))               # softplus
    A = -jnp.exp(p["A_log"])

    def step(S, inp):
        xt, dtt, bt, ct = inp
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * bt[group_of][:, None, :]
        y = jnp.einsum("hpn,hn->hp", S, ct[group_of])
        return S, y + p["D"][:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, dt, Bm, Cm))
    gated = (low(y.reshape(T, di)) * _silu(z)).reshape(T, G, di // G)
    y = low(_rms(gated, p["gn"].reshape(G, di // G), eps)).reshape(T, di)
    return _mm(y, p["Wout"], precision)


def attention_mixer(p, u, c: Consts, *, n_heads: int, precision: str):
    low = functools.partial(_low, precision=precision)
    T, hd = u.shape[0], c.head_dim
    qw, kvw = n_heads * hd, c.kv_heads * hd
    qkv = _mm(u, p["Wqkv"], precision)
    q = qkv[:, :qw].reshape(T, n_heads, hd)
    k = qkv[:, qw:qw + kvw].reshape(T, c.kv_heads, hd)
    v = qkv[:, qw + kvw:].reshape(T, c.kv_heads, hd)
    g = n_heads // c.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / hd ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", low(jax.nn.softmax(s, axis=-1)), v)
    return _mm(low(a.reshape(T, qw)), p["Wo"], precision)


def route(p, u, c: Consts):
    """(chosen experts (T, top_k), their weights): sigmoid scores, the
    choice made on score + bias, the weight the unbiased score over the
    chosen scores' sum, scaled."""
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(u, p["router"])))
    _, top_i = jax.lax.top_k(s + p["router_b"], c.top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20) \
        * c.routed_scale


def experts(p, u, c: Consts, *, precision: str):
    """Routed experts held here under the router's weights, plus the
    shared expert, for `u` (T, d)."""
    low = functools.partial(_low, precision=precision)

    def mlp(Wu, Wd):
        h = jnp.maximum(_mm(u, Wu, precision), 0.0)
        return _mm(low(h * h), Wd, precision)

    top_i, w = route(p, u, c)

    def held_expert(out, ew):
        e, Wu, Wd = ew[0], ew[1].astype(jnp.float32).T, \
            ew[2].astype(jnp.float32)
        gate = jnp.sum(jnp.where(top_i == c.held_first + e, w, 0.0), -1)
        return out + gate[:, None] * mlp(Wu, Wd), None

    out, _ = jax.lax.scan(
        held_expert, mlp(p["sWu"], p["sWd"]),
        (jnp.arange(p["Wu"].shape[0]), p["Wu"], p["Wd"]))
    return low(out)


@functools.partial(jax.jit, static_argnames=("c", "kind", "n_heads",
                                             "eps", "precision"))
def layer(p, x, *, c: Consts, kind: str, n_heads: int, eps: float,
          precision: str):
    """One layer on (T, d), its weights taken up to float32 here."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        low = functools.partial(_low, precision=precision)
        u = low(_rms(x, p["n"], eps))
        if kind == MAMBA:
            m = mamba_mixer(p, u, c, eps=eps, precision=precision)
        elif kind == ATTENTION:
            m = attention_mixer(p, u, c, n_heads=n_heads,
                                precision=precision)
        else:
            m = experts(p, u, c, precision=precision)
        return low(x + m)


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
    `head` and one dict of leaves a layer."""
    x = _embed(w["emb"], ids[0], precision=precision)
    for kind, p in zip(c.pattern, w["layers"]):
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
