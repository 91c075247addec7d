"""Plain reference of the `longcat_flash` family (Hugging Face
`LongcatFlashForCausalLM`, arXiv:2509.01322): the forward pass in
straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`. Attention is EXPANDED: every
head's keys and values are made from the latent and a full score matrix
is soft-maxed, one head at a time so that 64 heads of 4,096 positions fit
beside the weights; the router and the experts are a loop over the
experts held, each token weighed by the gate of that expert if it chose
it; no cache, no absorbed products, no kernels, no batching, and nothing
of the program under test. A configuration's own reference file
(`configs/<name>_reference.py`) binds `Consts` from the configuration
file beside it and documents that configuration's departures; the tests
bind a tiny set.

It is handed weights the benchmark drew from the seed, in bfloat16 (the
router's correction bias float32); one layer's weights at a time are
taken up to float32, and a layer's routed experts one expert at a time.

One layer holds TWO attention sub-layers, TWO dense gated-silu FFNs and
ONE routed block on a shortcut; `N` is RMSNorm with the config's
`rms_norm_eps`, all projections without bias:

    x    = E[ids]
    h1   = h  + MLA_0(N(h));    x1 = N(h1)
    m    = ScMoE(x1)                        # leaves the stream here ...
    h2   = h1 + FFN_0(x1)
    h3   = h2 + MLA_1(N(h2));   h4 = h3 + FFN_1(N(h3))
    out  = h4 + m                           # ... and joins it here
    logits = N(x) W_head                    (untied head)

    MLA(u) at positions pos (H heads; q_rank, kv_rank; nope, rope, v);
    each of the published `q_b_proj`, `kv_a_proj_with_mqa` and
    `kv_b_proj` is handed over as its two parts (`Wqn` / `Wqr` the
    queries' nope and rope columns, `Wkvc` / `Wkr` the latent's and the
    rope key's, `Wkb` (H, nope, kv_rank) / `Wvb` (H, kv_rank, v) a
    head's key and value expansions): the same products:
        c_q = N(u W_qa);  [q_n | q_r]_h = (s_q c_q) W_qb,
            s_q = sqrt(d / q_rank) (`mla_scale_q_lora`)
        [c_kv | k_r] = u W_kva;  c = N(c_kv)
        q_r, k_r = RoPE(., pos): pairs (2i, 2i + 1) turned by
            pos * theta^(-2i / rope); ONE rope key a position, shared
            by all heads
        [k_n | v]_h = (s_kv c) W_kvb,  s_kv = sqrt(d / kv_rank)
            (`mla_scale_kv_lora`)
        causal softmax((q_n.k_n + q_r.k_r) / sqrt(nope + rope)) v, heads
        concatenated, times W_o
    FFN(u) = (silu(u W_g) * (u W_u)) W_d
    ScMoE(u): s = softmax(u W_r) over ALL the router's outputs, the
        `n_experts` real experts and then the `n_zero` zero-compute
        ones; chosen = the top_k of s + b (`b`: the correction bias,
        choice only); g_e = scale * s_e, not renormalised;
        m = sum_{chosen e < n_experts} g_e FFN_e(u)
          + (sum_{chosen e >= n_experts} g_e) u
        Only the real experts held (from `held_first` on, as many as the
        weights carry) add to the first sum; the second is whole.

`precision` selects what the arithmetic is done in. "float32" is the
reference proper. "float8" is the control, one precision below the
bfloat16 the family's configurations state: every weight matrix and
every intermediate a bfloat16 program would round to bfloat16 is rounded
to float8 (e4m3, under a per-tensor power-of-two scale); the router's
scores and the correction bias stay float32, as the program's do.
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
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    scale_q_lora: bool
    scale_kv_lora: bool
    n_experts: int          # real experts the router scores (published)
    n_zero: int             # zero-compute experts it scores after them
    top_k: int
    routed_scale: float
    held_first: int


def consts_from_config(cfg: dict) -> Consts:
    """From a configuration file of the family (Hugging Face's keys)."""
    dep = cfg.get("deployment", {})
    return Consts(
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v_dim=int(cfg["v_head_dim"]), rope_theta=float(cfg["rope_theta"]),
        scale_q_lora=bool(cfg["mla_scale_q_lora"]),
        scale_kv_lora=bool(cfg["mla_scale_kv_lora"]),
        n_experts=int(dep.get("n_routed_experts_published",
                              cfg["n_routed_experts"])),
        n_zero=int(cfg["zero_expert_num"]), top_k=int(cfg["moe_topk"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        held_first=int(dep.get("experts_held_first", 0)))


def _f32(p: dict) -> dict:
    """A layer's gains, router and correction bias in float32; every
    other matrix is taken up where it is used (`_mm`), so that a
    layer's 2.5 GB of float32 matrices never stand beside one another,
    and the stacked routed experts one expert at a time (`sc_moe`)."""
    return {k: v.astype(jnp.float32) if v.ndim == 1 or k == "router" else v
            for k, v in p.items()}


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w.astype(jnp.float32), precision)),
                precision)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def rope(x, pos, theta: float):
    """`x` (T, ..., r) turned at `pos` (T,): features 2i and 2i + 1 are a
    pair, turned by `pos * theta^(-2i / r)` (the interleaved layout the
    published weights are in)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (T, r / 2)
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def mla(p, i: int, u, pos, c: Consts, *, n_heads: int, eps: float,
        precision: str):
    """Sub-layer `i`'s latent attention on `u` (T, d) at positions `pos`
    (T,), expanded: one head's full score matrix at a time."""
    low = functools.partial(_low, precision=precision)
    w = lambda name: p[f"{name}{i}"]
    T, d = u.shape
    H = n_heads
    s_q = (d / c.q_rank) ** 0.5 if c.scale_q_lora else 1.0
    s_kv = (d / c.kv_rank) ** 0.5 if c.scale_kv_lora else 1.0
    cq = low(s_q * low(_rms(_mm(u, w("Wqa"), precision), w("qn"), eps)))
    q_n = _mm(cq, w("Wqn"), precision).reshape(T, H, c.nope)
    q_r = low(rope(_mm(cq, w("Wqr"), precision).reshape(T, H, c.rope), pos,
                   c.rope_theta))
    lat = low(_rms(_mm(u, w("Wkvc"), precision), w("kvn"), eps))
    k_r = low(rope(_mm(u, w("Wkr"), precision), pos, c.rope_theta))
    ls = low(s_kv * lat)
    k_n = low(jnp.einsum("tr,hnr->thn", ls,
                         _low(w("Wkb").astype(jnp.float32), precision)))
    v = low(jnp.einsum("tr,hrv->thv", ls,
                       _low(w("Wvb").astype(jnp.float32), precision)))
    causal = pos[None, :] <= pos[:, None]

    def head(qkv):
        qn, qr, kn, vh = qkv
        s = (jnp.matmul(qn, kn.T) + jnp.matmul(qr, k_r.T)) \
            / (c.nope + c.rope) ** 0.5
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.matmul(low(jax.nn.softmax(s, axis=-1)), vh)

    heads_first = lambda a: jnp.swapaxes(a, 0, 1)
    o = jax.lax.map(head, tuple(map(heads_first, (q_n, q_r, k_n, v))))
    return _mm(low(heads_first(o).reshape(T, H * c.v_dim)), w("Wo"),
               precision)


def ffn(p, i: int, u, *, precision: str):
    low = functools.partial(_low, precision=precision)
    g = _mm(u, p[f"Wg{i}"], precision)
    return _mm(low(_silu(g) * _mm(u, p[f"Wu{i}"], precision)),
               p[f"Wd{i}"], precision)


def route(p, u, c: Consts):
    """(chosen router outputs (T, top_k), their gates): softmax over all
    the outputs, the choice made on score + bias, the gate the unbiased
    score times the scale."""
    s = jax.nn.softmax(jnp.matmul(u, p["router"]), axis=-1)
    _, top_i = jax.lax.top_k(s + p["router_b"], c.top_k)
    return top_i, jnp.take_along_axis(s, top_i, axis=-1) * c.routed_scale


def sc_moe(p, u, c: Consts, *, precision: str):
    """The routed block for `u` (T, d): the real experts held here under
    the router's gates, plus the zero-compute experts' identity part."""
    low = functools.partial(_low, precision=precision)
    top_i, g = route(p, u, c)

    def held_expert(out, ew):
        e, Wg, Wu, Wd = ew
        gate = jnp.sum(jnp.where(top_i == c.held_first + e, g, 0.0), -1)
        h = low(_silu(_mm(u, Wg, precision)) * _mm(u, Wu, precision))
        return out + gate[:, None] * _mm(h, Wd, precision), None

    zero = jnp.sum(jnp.where(top_i >= c.n_experts, g, 0.0), -1)
    out, _ = jax.lax.scan(
        held_expert, zero[:, None] * u,
        (jnp.arange(p["eWg"].shape[0]), p["eWg"], p["eWu"], p["eWd"]))
    return low(out)


@functools.partial(jax.jit, static_argnames=("c", "n_heads", "eps",
                                             "precision", "early_join"))
def layer(p, x, *, c: Consts, n_heads: int, eps: float, precision: str,
          early_join: bool = False):
    """One layer on (T, d) from position 0, its weights taken up to
    float32 here. `early_join` (never the model: for a test that tells
    the topologies apart) adds the routed block's output to the stream
    BEFORE the second attention."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        low = functools.partial(_low, precision=precision)
        pos = jnp.arange(x.shape[0])
        attn = functools.partial(mla, p, pos=pos, c=c, n_heads=n_heads,
                                 eps=eps, precision=precision)
        h1 = low(x + attn(0, low(_rms(x, p["an0"], eps))))
        x1 = low(_rms(h1, p["fn0"], eps))
        m = sc_moe(p, x1, c, precision=precision)
        h2 = low(h1 + ffn(p, 0, x1, precision=precision))
        if early_join:
            h2 = low(h2 + m)
        h3 = low(h2 + attn(1, low(_rms(h2, p["an1"], eps))))
        h4 = low(h3 + ffn(p, 1, low(_rms(h3, p["fn1"], eps)),
                          precision=precision))
        return h4 if early_join else low(h4 + m)


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
    for p in w["layers"]:
        x = layer(p, x, c=c, n_heads=n_heads, eps=eps, precision=precision)
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
