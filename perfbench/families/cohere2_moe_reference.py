"""Plain reference of the `cohere2_moe` family (Hugging Face `model_type`
`cohere2_moe`: Command A+; sliding-window attention layers with rotary
and, one layer in `layer_switch`, a full-attention layer without
positions; attention and the feed-forward side by side under one
LayerNorm; sigmoid-routed experts beside shared experts that are
averaged; a tied head): the forward pass in straightforward float32
`jax.numpy` under `jax.default_matmul_precision("highest")`. Attention
is one K/V head's group of query heads and one block of `QUERY_BLOCK`
queries at a time, the whole sequence's keys masked by position (so
12,288 positions of 128 heads fit); the router scores every published
expert, and the experts are a loop over the experts held, each token
weighed by the gate of that expert if it chose it; the shared experts
are a loop over the `n_shared` of them, their outputs summed and divided
by their number; no cache, no ring, no kernels, no batching, and nothing
of the program under test. A configuration's own reference file
(`configs/<name>_reference.py`) binds `Consts` from the configuration
file beside it and documents that configuration's departures; the tests
bind a tiny set.

It is handed weights the benchmark drew from the seed, in bfloat16; one
layer's weights at a time are taken up to float32, and a layer's routed
experts one expert at a time.

`LN` is LayerNorm with a gain and NO bias, `(x - mean x) / sqrt(var x +
layer_norm_eps) * g`; all projections without bias; layer `l` of the
net, at positions `pos`:

    x = E[ids]
    u = LN_l(h)
    h = h + Attn_l(u, pos) + FFN_l(u)           ONE norm, ONE add
    logits = LN_f(h) E^T * logit_scale          (tied head)

    Attn_l(u), H query heads over Hkv K/V heads of `hd` (query head j
    reads K/V head j // (H / Hkv)); `Wqkv` = [Wq | Wk | Wv] side by
    side:
        q = u Wq,  k = u Wk,  v = u Wv
        sliding layer (`(l + 1) % layer_switch != 0`):
            q, k = RoPE(., pos): pairs (2i, 2i + 1) of ALL hd features
                turned by pos * theta^(-2i / hd)      (`rope_gptj`)
            query i sees keys j with j <= i and i - j < window
        full layer: NO position is applied; query i sees keys j <= i
        o = softmax(q . k / sqrt(hd)) v;  Attn = concat_heads(o) Wo
    FFN_l(u) = Routed(u) + (1 / n_shared) sum_j S_j(u)
        Routed(u): s = sigmoid(u W_r) over ALL `n_experts`, float32;
            chosen = the `top_k` largest s; w_e = s_e / sum_chosen s
            (`norm_topk_prob`); Routed(u) = sum_{chosen e} w_e E_e(u).
            Only the experts held (from `held_first` on, as many as the
            weights carry) add to the sum.
        E_e, S_j: gated-silu MLPs `(silu(u Wg) * (u Wu)) Wd`; shared
            expert j is columns (of `sWd`: rows) `j f .. (j + 1) f` of
            the leaves `sWg`, `sWu`, `sWd`.

`precision` selects what the arithmetic is done in. "float32" is the
reference proper. "float8" is the control, one precision below the
bfloat16 the family's configurations state: every weight matrix and
every intermediate a bfloat16 program would round to bfloat16 is rounded
to float8 (e4m3, under a per-tensor power-of-two scale); the router's
scores and the softmax stay float32, as the program's do.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense_reference import _low

SLIDING, FULL = "sliding_attention", "full_attention"
QUERY_BLOCK = 512   # queries a group's scores are made for at a time


@dataclasses.dataclass(frozen=True)
class Consts:
    """What the forward pass needs beside the weights' own shapes."""
    layer_types: tuple
    n_kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    n_experts: int          # experts the router scores (published)
    top_k: int
    n_shared: int
    held_first: int
    logit_scale: float


def consts_from_config(cfg: dict) -> Consts:
    """From a configuration file of the family (Hugging Face's keys)."""
    dep = cfg.get("deployment", {})
    L = int(cfg["num_hidden_layers"])
    return Consts(
        layer_types=tuple(cfg["layer_types"][:L]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), window=int(cfg["sliding_window"]),
        rope_theta=float(cfg["rope_theta"]),
        n_experts=int(dep.get("num_experts_published", cfg["num_experts"])),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared=int(cfg["num_shared_experts"]),
        held_first=int(dep.get("experts_held_first", 0)),
        logit_scale=float(cfg["logit_scale"]))


def _f32(p: dict) -> dict:
    """A layer's vectors and its router in float32; every other matrix
    is taken up where it is used (`_mm`), so that a layer's float32
    matrices never stand beside one another, and the stacked routed
    experts one expert at a time (`routed`)."""
    return {k: v.astype(jnp.float32) if v.ndim == 1 or k == "router" else v
            for k, v in p.items()}


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w.astype(jnp.float32), precision)),
                precision)


def _ln(x, g, eps):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc / jnp.sqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) * g


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def rope(x, pos, theta: float):
    """`x` (T, heads, hd) turned at `pos` (T,): features (2i, 2i + 1)
    together, by `pos * theta^(-2i / hd)` (the interleaved form,
    `rope_gptj`)."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(p, u, pos, c: Consts, *, kind: str, n_heads: int,
              precision: str, window_ignored: bool = False):
    """The layer's attention on `u` (T, d) at positions `pos` (T,): one
    K/V head's group of queries and one block of queries at a time.
    `window_ignored` (the tests' and the rehearsal's broken variant
    only): a sliding layer reads its whole context."""
    low = functools.partial(_low, precision=precision)
    T, H, Hkv, hd = u.shape[0], n_heads, c.n_kv_heads, c.head_dim
    G = H // Hkv
    qkv = _mm(u, p["Wqkv"], precision)
    q = qkv[:, :H * hd].reshape(T, H, hd)
    k = qkv[:, H * hd:(H + Hkv) * hd].reshape(T, Hkv, hd)
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    if kind == SLIDING:
        q, k = low(rope(q, pos, c.rope_theta)), low(rope(k, pos,
                                                         c.rope_theta))
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def group(qkv_g):
        qg, kg, vg = qkv_g           # (T, G, hd), (T, hd), (T, hd)

        def block(qp):
            q_b, pos_b = qp                           # (qb, G, hd), (qb,)
            s = jnp.einsum("qgd,kd->gqk", q_b, kg) / hd ** 0.5
            keep = pos[None, :] <= pos_b[:, None]
            if kind == SLIDING and not window_ignored:
                keep = keep & (pos_b[:, None] - pos[None, :] < c.window)
            s = jnp.where(keep[None], s, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd",
                              low(jax.nn.softmax(s, axis=-1)), vg)

        blocks = lambda a: a.reshape(T // qb, qb, *a.shape[1:])
        return jax.lax.map(block, (blocks(qg), blocks(pos))) \
            .reshape(T, G, hd)

    o = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(T, Hkv, G, hd), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))   # (Hkv, T, G, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * hd)
    return _mm(low(o), p["Wo"], precision)


def ffn(u, Wg, Wu, Wd, *, precision: str):
    h = _low(_silu(_mm(u, Wg, precision)) * _mm(u, Wu, precision),
             precision)
    return _mm(h, Wd, precision)


def route(p, u, c: Consts):
    """(chosen experts (T, top_k), their gates): sigmoid scores over all
    the router's outputs, the `top_k` largest chosen, each weighed by
    its score over the chosen scores' sum."""
    s = _sigmoid(jnp.matmul(u, p["router"]))
    top_s, top_i = jax.lax.top_k(s, c.top_k)
    return top_i, top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)


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


def shared(p, u, c: Consts, *, precision: str):
    """The `n_shared` shared experts, each on its own, AVERAGED."""
    f = p["sWg"].shape[1] // c.n_shared
    out = jnp.zeros_like(u)
    for j in range(c.n_shared):
        cols = slice(j * f, (j + 1) * f)
        out = out + ffn(u, p["sWg"][:, cols], p["sWu"][:, cols],
                        p["sWd"][cols], precision=precision)
    return _low(out / c.n_shared, precision)


@functools.partial(jax.jit, static_argnames=(
    "c", "kind", "n_heads", "eps", "precision", "window_ignored"))
def layer(p, x, *, c: Consts, kind: str, n_heads: int, eps: float,
          precision: str, window_ignored: bool = False):
    """One layer on (T, d) from position 0, its weights taken up to
    float32 here: ONE norm, read by the attention and by the
    feed-forward, both added in one residual."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        low = functools.partial(_low, precision=precision)
        u = low(_ln(x, p["ln"], eps))
        a = attention(p, u, jnp.arange(x.shape[0]), c, kind=kind,
                      n_heads=n_heads, precision=precision,
                      window_ignored=window_ignored)
        f = low(routed(p, u, c, precision=precision)
                + shared(p, u, c, precision=precision))
        return low(x + a + f)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(emb, ids, *, precision: str):
    return _low(emb[ids].astype(jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "precision"))
def _head(emb, lnf, x, *, eps: float, scale: float, precision: str):
    with jax.default_matmul_precision("highest"):
        x = _low(_ln(x, lnf.astype(jnp.float32), eps), precision)
        return jnp.matmul(x, _low(emb.astype(jnp.float32), precision).T) \
            * scale


def logits_at(w, ids, rows, *, c: Consts, n_heads: int, eps: float,
              precision: str = "float32", window_ignored: bool = False):
    """Next-token logits (len(rows), V) at positions `rows` of the one
    sequence `ids` (1, T). `w` is the family's tree: `emb`, `lnf` and
    one dict of leaves a layer."""
    x = _embed(w["emb"], ids[0], precision=precision)
    for kind, p in zip(c.layer_types, w["layers"]):
        x = layer(p, x, c=c, kind=kind, n_heads=n_heads, eps=eps,
                  precision=precision, window_ignored=window_ignored)
    return _head(w["emb"], w["lnf"], x[rows], eps=eps, scale=c.logit_scale,
                 precision=precision)


def bound_logits_at(config_file):
    """`logits_at` with the constants of one configuration file, under
    the signature `harness/serve_cell.py` calls."""
    c = consts_from_config(json.loads(open(config_file).read()))

    def bound(w, ids, rows, *, n_heads: int, eps: float,
              precision: str = "float32", window_ignored: bool = False):
        return logits_at(w, ids, rows, c=c, n_heads=n_heads, eps=eps,
                         precision=precision, window_ignored=window_ignored)
    return bound
