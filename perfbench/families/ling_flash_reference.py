"""Plain reference of the `ling_flash` family (Hugging Face `model_type`
`bailing_hybrid`: Ling-3.0-flash; delta-rule layers with a decay a key
channel (Kimi Delta Attention, arXiv:2510.26692) and, one layer in
`layer_group_size`, latent attention (MLA) with full-rank queries and a
gate a head; the first `first_k_dense_replace` feed-forwards dense, the
others sigmoid-routed experts chosen among a token's best groups plus a
shared expert; RMSNorm, an untied bias-free head): the forward pass in
straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`. The delta rule is a
`lax.scan` over positions exactly as written below, no chunks; attention
is EXPANDED, every head's keys and values made from the latent and the
scores soft-maxed one head and one block of `QUERY_BLOCK` queries at a
time; the router scores every published expert, applies the group rule,
and the experts are a loop over the experts held, each token weighed by
the gate of that expert if it chose it; no cache, no absorbed products,
no kernels, no batching, and nothing of the program under test. A
configuration's own reference file (`configs/<name>_reference.py`) binds
`Consts` from the configuration file beside it and documents that
configuration's departures; the tests bind a tiny set.

It is handed weights the benchmark drew from the seed, in bfloat16 (the
router's correction bias in float32); one layer's weights at a time are
taken up to float32, and a layer's routed experts one expert at a time.

`N` is RMSNorm with the config's `rms_norm_eps`, all projections without
bias; layer `l` of the net:

    x = E[ids]
    h = h + Mixer_l(N(h), pos);   h = h + F_l(N(h))
    Mixer_l = MLA   where (l + 1) % layer_group_size == 0,  else KDA
    F_l = FFN                          l < first_k_dense_replace
    F_l = Routed + Shared              otherwise
    logits = N(h) W_head               (untied head)

    KDA(u), H heads of d_k keys and d_v values, no positions. The six
    published projections side by side as ONE matrix,
    [q | k | v | gate | f | b] = u W_in:
        [q; k; v] = silu(conv([q; k; v]))     depthwise, causal, K taps,
                                              no bias
        q_h = q_h / sqrt(|q_h|^2 + 1e-6) / sqrt(d_k)
        k_h = k_h / sqrt(|k_h|^2 + 1e-6)
        beta_h = sigmoid(b_h)
        g_h = lower * sigmoid(exp(A_h) * (f_h + bias_h))    a VECTOR over
            the d_k key channels, in (lower, 0): `kda_safe_gate` with
            `kda_lower_bound` = lower; `A` one number a head, `bias` one
            a channel
        S_t = S_{t-1} Diag(exp g_t)
              + beta_t (v_t - S_{t-1} Diag(exp g_t) k_t) k_t^T
        o_t = S_t q_t                       S (d_v, d_k), S_0 = 0
        out = concat_h(N_o(o_h) * sigmoid(gate_h)) W_out    N_o per head
                                                            over d_v
    MLA(u) at positions pos (H heads; kv_rank; nope, rope, v), the
    queries at FULL rank; `W_q` handed over as its nope and rope columns
    (`Wqn`, `Wqr`), `W_kva` as the latent's and the rope key's (`Wkvc`,
    `Wkr`), `W_kvb` by head as `Wkb` (H, nope, kv_rank) / `Wvb` (H,
    kv_rank, v): the same products:
        [q_n | q_r]_h = u W_q
        [c_kv | k_r] = u W_kva;  c = N(c_kv)
        q_r, k_r = RoPE(., pos): pairs (2i, 2i + 1) turned by
            pos * theta^(-2i / rope); ONE rope key a position, shared by
            all heads
        [k_n | v]_h = c W_kvb
        o_h = causal softmax((q_n.k_n + q_r.k_r) / sqrt(nope + rope)) v_h
        out = concat_h(o_h * sigmoid((u W_a)_h)) W_o       one gate a head
    FFN(u) = (silu(u W_g) * (u W_u)) W_d
    Routed(u): s = sigmoid(u W_r) over ALL `n_experts` outputs, float32;
        the choice is made on s + b (`b` the correction bias: it moves
        the choice and never the weight); the experts lie in `n_groups`
        equal groups (expert e in group e // (n_experts / n_groups)); a
        group's score is the SUM of its two largest s + b; the
        `topk_groups` best groups are kept; chosen = the `top_k` largest
        s + b of what is left; w_e = scale * s_e / sum_chosen s;
        Routed(u) = sum_{chosen e} w_e FFN_e(u)
        Only the experts held (from `held_first` on, as many as the
        weights carry) add to the sum.
    Shared(u) = FFN_s(u), unweighted, whole on every chip.

`precision` selects what the arithmetic is done in. "float32" is the
reference proper. "float8" is the control, one precision below the
bfloat16 the family's configurations state: every weight matrix and
every intermediate a bfloat16 program would round to bfloat16 is rounded
to float8 (e4m3, under a per-tensor power-of-two scale); the router's
scores, the decay and the matrix state stay float32, as the program's
do.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense_reference import _low

LINEAR, FULL = "linear_attention", "full_attention"
QUERY_BLOCK = 1024   # queries a head's scores are made for at a time


@dataclasses.dataclass(frozen=True)
class Consts:
    """What the forward pass needs beside the weights' own shapes."""
    layer_types: tuple
    l_heads: int
    l_key: int
    l_value: int
    gate_lower: float
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    n_experts: int          # experts the router scores (published)
    n_groups: int
    topk_groups: int
    top_k: int
    routed_scale: float
    held_first: int


def layer_types(cfg: dict) -> tuple:
    """Layer `l` is full attention where `(l + 1) % layer_group_size ==
    0`, else linear."""
    period = int(cfg["layer_group_size"])
    return tuple(FULL if (i + 1) % period == 0 else LINEAR
                 for i in range(int(cfg["num_hidden_layers"])))


def consts_from_config(cfg: dict) -> Consts:
    """From a configuration file of the family (Hugging Face's keys)."""
    dep = cfg.get("deployment", {})
    return Consts(
        layer_types=layer_types(cfg),
        l_heads=int(cfg["num_attention_heads"]),
        l_key=int(cfg["head_dim"]), l_value=int(cfg["head_dim"]),
        gate_lower=float(cfg["kda_lower_bound"]),
        kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v_dim=int(cfg["v_head_dim"]), rope_theta=float(cfg["rope_theta"]),
        n_experts=int(dep.get("num_experts_published", cfg["num_experts"])),
        n_groups=int(cfg["n_group"]), topk_groups=int(cfg["topk_group"]),
        top_k=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        held_first=int(dep.get("experts_held_first", 0)))


def _f32(p: dict) -> dict:
    """A layer's vectors, its convolution taps and its router in
    float32; every other matrix is taken up where it is used (`_mm`), so
    that a layer's float32 matrices never stand beside one another, and
    the stacked routed experts one expert at a time (`routed`)."""
    small = ("router", "conv")
    return {k: v.astype(jnp.float32) if v.ndim == 1 or k in small else v
            for k, v in p.items()}


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w.astype(jnp.float32), precision)),
                precision)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def kda(p, u, c: Consts, *, eps: float, precision: str):
    """`u` (T, d) -> (T, d): the delta rule with a decay a key channel,
    one position at a time."""
    low = functools.partial(_low, precision=precision)
    T = u.shape[0]
    H, dk, dv = c.l_heads, c.l_key, c.l_value
    qw, vw = H * dk, H * dv
    cw = 2 * qw + vw
    z = _mm(u, p["Win"], precision)
    qkv, gate = z[:, :cw], z[:, cw:cw + vw]
    f, b = z[:, cw + vw:cw + vw + qw], z[:, cw + vw + qw:]
    K = p["conv"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, cw)), qkv], axis=0)
    qkv = low(_silu(sum(padded[k:k + T] * p["conv"][:, k]
                        for k in range(K))))
    q = qkv[:, :qw].reshape(T, H, dk)
    k = qkv[:, qw:2 * qw].reshape(T, H, dk)
    v = qkv[:, 2 * qw:].reshape(T, H, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = _sigmoid(b)                                        # (T, H)
    g = c.gate_lower * _sigmoid(
        jnp.exp(p["A"])[:, None] * (f + p["fb"]).reshape(T, H, dk))

    def step(S, inp):
        qt, kt, vt, at, bt = inp
        S = S * at[:, None, :]                                # (H, dv, dk)
        S = S + (bt[:, None] * (vt - jnp.einsum("hvk,hk->hv", S, kt))
                 )[:, :, None] * kt[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, dv, dk)),
                        (q, k, v, jnp.exp(g), beta))
    y = low(low(_rms(low(o), p["on"], eps)).reshape(T, vw) * _sigmoid(gate))
    return _mm(y, p["Wout"], precision)


def rope(x, pos, theta: float):
    """`x` (T, ..., r) turned at `pos` (T,): features 2i and 2i + 1 are a
    pair, turned by `pos * theta^(-2i / r)` (the interleaved layout the
    published weights are in)."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def mla(p, u, pos, c: Consts, *, n_heads: int, eps: float, precision: str):
    """The latent attention on `u` (T, d) at positions `pos` (T,),
    expanded: one head's scores for one block of queries at a time."""
    low = functools.partial(_low, precision=precision)
    T, H = u.shape[0], n_heads
    q_n = _mm(u, p["Wqn"], precision).reshape(T, H, c.nope)
    q_r = low(rope(_mm(u, p["Wqr"], precision).reshape(T, H, c.rope), pos,
                   c.rope_theta))
    lat = low(_rms(_mm(u, p["Wkvc"], precision), p["kvn"], eps))
    k_r = low(rope(_mm(u, p["Wkr"], precision), pos, c.rope_theta))
    k_n = low(jnp.einsum("tr,hnr->thn", lat,
                         _low(p["Wkb"].astype(jnp.float32), precision)))
    v = low(jnp.einsum("tr,hrv->thv", lat,
                       _low(p["Wvb"].astype(jnp.float32), precision)))
    scale = (c.nope + c.rope) ** -0.5
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def head(qkv):
        qn, qr, kn, vh = qkv

        def block(q):
            qn_b, qr_b, pos_b = q
            s = (jnp.matmul(qn_b, kn.T) + jnp.matmul(qr_b, k_r.T)) * scale
            s = jnp.where(pos[None, :] <= pos_b[:, None], s, -jnp.inf)
            return jnp.matmul(low(jax.nn.softmax(s, axis=-1)), vh)

        blocks = lambda a: a.reshape(T // qb, qb, *a.shape[1:])
        return jax.lax.map(block, (blocks(qn), blocks(qr), blocks(pos))) \
            .reshape(T, c.v_dim)

    heads_first = lambda a: jnp.swapaxes(a, 0, 1)
    o = heads_first(jax.lax.map(
        head, tuple(map(heads_first, (q_n, q_r, k_n, v)))))   # (T, H, v)
    gate = _sigmoid(jnp.matmul(
        u, _low(p["Wa"].astype(jnp.float32), precision)))     # (T, H)
    return _mm(low(low(o) * gate[:, :, None]).reshape(T, H * c.v_dim),
               p["Wo"], precision)


def ffn(u, Wg, Wu, Wd, *, precision: str):
    h = _low(_silu(_mm(u, Wg, precision)) * _mm(u, Wu, precision),
             precision)
    return _mm(h, Wd, precision)


def route(p, u, c: Consts):
    """(chosen experts (T, top_k), their gates): sigmoid scores over all
    the outputs; on the scores plus the correction bias, each group
    scored by the sum of its two largest, all but each token's best
    groups set aside, the top_k of what is left; the gate the unbiased
    score over the chosen scores' sum, times the scale."""
    s = _sigmoid(jnp.matmul(u, p["router"]))
    T, E = s.shape
    by_group = (s + p["rb"]).reshape(T, c.n_groups, E // c.n_groups)
    of_group = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, best = jax.lax.top_k(of_group, c.topk_groups)
    kept = jnp.any(best[:, :, None] == jnp.arange(c.n_groups), axis=1)
    left = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(T, E)
    _, top_i = jax.lax.top_k(left, c.top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=1)
    return top_i, top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20) \
        * c.routed_scale


def routed(p, u, c: Consts, *, precision: str):
    """The routed experts' part for `u` (T, d): the experts held here
    under the router's gates."""
    top_i, g = route(p, u, c)

    def held_expert(out, ew):
        e, Wg, Wu, Wd = ew
        gate = jnp.sum(jnp.where(top_i == c.held_first + e, g, 0.0), -1)
        return out + gate[:, None] * ffn(u, Wg, Wu, Wd,
                                         precision=precision), None

    out, _ = jax.lax.scan(
        held_expert, jnp.zeros_like(u),
        (jnp.arange(p["eWg"].shape[0]), p["eWg"], p["eWu"], p["eWd"]))
    return _low(out, precision)


@functools.partial(jax.jit, static_argnames=("c", "kind", "n_heads", "eps",
                                             "precision"))
def layer(p, x, *, c: Consts, kind: str, n_heads: int, eps: float,
          precision: str):
    """One layer on (T, d) from position 0, its weights taken up to
    float32 here: `kind` its mixer; dense where it carries `Wg`, routed
    where it carries a `router`."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        low = functools.partial(_low, precision=precision)
        u = low(_rms(x, p["an"], eps))
        if kind == LINEAR:
            m = kda(p, u, c, eps=eps, precision=precision)
        else:
            m = mla(p, u, jnp.arange(x.shape[0]), c, n_heads=n_heads,
                    eps=eps, precision=precision)
        h = low(x + m)
        u = low(_rms(h, p["fn"], eps))
        if "router" not in p:
            f = ffn(u, p["Wg"], p["Wu"], p["Wd"], precision=precision)
        else:
            f = low(routed(p, u, c, precision=precision)
                    + ffn(u, p["sWg"], p["sWu"], p["sWd"],
                          precision=precision))
        return low(h + f)


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
