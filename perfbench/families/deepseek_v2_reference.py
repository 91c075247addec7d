"""Plain reference of the `deepseek_v2` family (Hugging Face
`DeepseekV2ForCausalLM`, arXiv:2405.04434): the forward pass in
straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`. Attention is EXPANDED: every
head's keys and values are made from the latent and the scores are
soft-maxed one head and one block of `QUERY_BLOCK` queries at a time, so
that 128 heads of a request of several thousand positions fit beside the
weights; the router scores every published expert, applies the group
rule, and the experts are a loop over the experts held, each token
weighed by the gate of that expert if it chose it; no cache, no absorbed
products, no kernels, no batching, and nothing of the program under
test. A configuration's own reference file
(`configs/<name>_reference.py`) binds `Consts` from the configuration
file beside it and documents that configuration's departures; the tests
bind a tiny set.

It is handed weights the benchmark drew from the seed, in bfloat16; one
layer's weights at a time are taken up to float32, and a layer's routed
experts one expert at a time.

`N` is RMSNorm with the config's `rms_norm_eps`, all projections without
bias; layer `l` of the net:

    x = E[ids]
    h = h + MLA(N(h), pos);   h = h + F_l(N(h))
    F_l = FFN                          l < first_k_dense_replace
    F_l = Routed + Shared              otherwise
    logits = N(h) W_head               (untied head)

    MLA(u) at positions pos (H heads; q_rank, kv_rank; nope, rope, v);
    each of the published `q_b_proj`, `kv_a_proj_with_mqa` and
    `kv_b_proj` is handed over as its two parts (`Wqn` / `Wqr` the
    queries' nope and rope columns, `Wkvc` / `Wkr` the latent's and the
    rope key's, `Wkb` (H, nope, kv_rank) / `Wvb` (H, kv_rank, v) a
    head's key and value expansions): the same products:
        c_q = N(u W_qa);  [q_n | q_r]_h = c_q W_qb
        [c_kv | k_r] = u W_kva;  c = N(c_kv)
        q_r, k_r = RoPE(., pos): pairs (2i, 2i + 1) turned by
            pos * inv_freq_i; ONE rope key a position, shared by all
            heads. With YaRN (`rope_scaling` of type "yarn", as the
            published `DeepseekV2YarnRotaryEmbedding` writes it), with
            f_i = theta^(-2i / rope), i = 0 .. rope/2 - 1:
                corr(n) = rope ln(original_max / (2 pi n)) / (2 ln theta)
                low = floor(corr(beta_fast)), high = ceil(corr(beta_slow))
                r_i = clip((i - low) / (high - low), 0, 1)
                inv_freq_i = f_i (1 - r_i) + (f_i / factor) r_i
            cos and sin times m(mscale) / m(mscale_all_dim), m(x) =
            0.1 x ln(factor) + 1; without it inv_freq_i = f_i
        [k_n | v]_h = c W_kvb
        causal softmax((q_n.k_n + q_r.k_r) * s) v, heads concatenated,
        times W_o;  s = (nope + rope)^-1/2, times m(mscale_all_dim)^2
        with YaRN
    FFN(u) = (silu(u W_g) * (u W_u)) W_d
    Routed(u): p = softmax(u W_r) over ALL `n_experts` outputs; the
        experts lie in `n_groups` equal groups (expert e in group
        e // (n_experts / n_groups)); a group's score is the LARGEST p
        in it; the `topk_groups` best groups are kept and every other
        group's p set to 0; chosen = the `top_k` largest of what is
        left; g_e = scale * p_e, not renormalised;
        Routed(u) = sum_{chosen e} g_e FFN_e(u)
        Only the experts held (from `held_first` on, as many as the
        weights carry) add to the sum.
    Shared(u) = FFN_s(u), the `n_shared_experts` as ONE gated MLP of
        their summed width, unweighted, whole on every chip.

`precision` selects what the arithmetic is done in. "float32" is the
reference proper. "float8" is the control, one precision below the
bfloat16 the family's configurations state: every weight matrix and
every intermediate a bfloat16 program would round to bfloat16 is rounded
to float8 (e4m3, under a per-tensor power-of-two scale); the router's
scores stay float32, as the program's do.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense_reference import _low

QUERY_BLOCK = 1024   # queries a head's scores are made for at a time


@dataclasses.dataclass(frozen=True)
class Consts:
    """What the forward pass needs beside the weights' own shapes."""
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    # (factor, original_max, beta_fast, beta_slow, mscale,
    # mscale_all_dim) of a "yarn" rope_scaling; None: plain rotary
    yarn: Optional[Tuple[float, ...]]
    n_experts: int          # experts the router scores (published)
    n_groups: int
    topk_groups: int
    top_k: int
    routed_scale: float
    held_first: int


def consts_from_config(cfg: dict) -> Consts:
    """From a configuration file of the family (Hugging Face's keys)."""
    dep = cfg.get("deployment", {})
    rs = cfg.get("rope_scaling")
    if rs is not None and rs["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {rs['type']!r}: the "
                         "family writes 'yarn' only")
    if cfg["topk_method"] != "group_limited_greedy" \
            or cfg["scoring_func"] != "softmax" or cfg["norm_topk_prob"]:
        raise ValueError("the family routes by group_limited_greedy over "
                         "softmax scores, not renormalised")
    return Consts(
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v_dim=int(cfg["v_head_dim"]), rope_theta=float(cfg["rope_theta"]),
        yarn=None if rs is None else (
            float(rs["factor"]),
            float(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs["mscale"]), float(rs["mscale_all_dim"])),
        n_experts=int(dep.get("n_routed_experts_published",
                              cfg["n_routed_experts"])),
        n_groups=int(cfg["n_group"]), topk_groups=int(cfg["topk_group"]),
        top_k=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        held_first=int(dep.get("experts_held_first", 0)))


def _f32(p: dict) -> dict:
    """A layer's gains and router in float32; every other matrix is
    taken up where it is used (`_mm`), so that a layer's float32
    matrices never stand beside one another, and the stacked routed
    experts one expert at a time (`routed`)."""
    return {k: v.astype(jnp.float32) if v.ndim == 1 or k == "router" else v
            for k, v in p.items()}


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w.astype(jnp.float32), precision)),
                precision)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _m(factor: float, x: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * x * math.log(factor) + 1.0


def inv_freq(r: int, theta: float, yarn):
    """The rotary pairs' inverse frequencies (r / 2,) float32."""
    i = jnp.arange(0, r, 2, dtype=jnp.float32)
    f = 1.0 / theta ** (i / r)
    if yarn is None:
        return f
    factor, original_max, beta_fast, beta_slow = yarn[:4]
    corr = lambda n: r * math.log(original_max / (n * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), r - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp               # the published `inv_freq_mask`
    return f / factor * (1.0 - keep) + f * keep


def softmax_scale(c: Consts) -> float:
    s = (c.nope + c.rope) ** -0.5
    if c.yarn is not None and c.yarn[5]:
        s *= _m(c.yarn[0], c.yarn[5]) ** 2
    return s


def rope(x, pos, theta: float, yarn=None):
    """`x` (T, ..., r) turned at `pos` (T,): features 2i and 2i + 1 are a
    pair, turned by `pos * inv_freq_i` (the interleaved layout the
    published weights are in)."""
    r = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * inv_freq(r, theta, yarn)
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    ms = 1.0 if yarn is None else _m(yarn[0], yarn[4]) / _m(yarn[0], yarn[5])
    cos, sin = jnp.cos(ang) * ms, jnp.sin(ang) * ms
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def mla(p, u, pos, c: Consts, *, n_heads: int, eps: float, precision: str):
    """The latent attention on `u` (T, d) at positions `pos` (T,),
    expanded: one head's scores for one block of queries at a time."""
    low = functools.partial(_low, precision=precision)
    T, H = u.shape[0], n_heads
    cq = low(_rms(_mm(u, p["Wqa"], precision), p["qn"], eps))
    q_n = _mm(cq, p["Wqn"], precision).reshape(T, H, c.nope)
    q_r = low(rope(_mm(cq, p["Wqr"], precision).reshape(T, H, c.rope), pos,
                   c.rope_theta, c.yarn))
    lat = low(_rms(_mm(u, p["Wkvc"], precision), p["kvn"], eps))
    k_r = low(rope(_mm(u, p["Wkr"], precision), pos, c.rope_theta, c.yarn))
    k_n = low(jnp.einsum("tr,hnr->thn", lat,
                         _low(p["Wkb"].astype(jnp.float32), precision)))
    v = low(jnp.einsum("tr,hrv->thv", lat,
                       _low(p["Wvb"].astype(jnp.float32), precision)))
    scale = softmax_scale(c)
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
    o = jax.lax.map(head, tuple(map(heads_first, (q_n, q_r, k_n, v))))
    return _mm(low(heads_first(o).reshape(T, H * c.v_dim)), p["Wo"],
               precision)


def ffn(u, Wg, Wu, Wd, *, precision: str):
    h = _low(_silu(_mm(u, Wg, precision)) * _mm(u, Wu, precision),
             precision)
    return _mm(h, Wd, precision)


def route(p, u, c: Consts):
    """(chosen experts (T, top_k), their gates): softmax over all the
    outputs, all but each token's best groups set to 0, the top_k of
    what is left, the gate the score times the scale."""
    s = jax.nn.softmax(jnp.matmul(u, p["router"]), axis=-1)
    T, E = s.shape
    by_group = s.reshape(T, c.n_groups, E // c.n_groups)
    _, best = jax.lax.top_k(jnp.max(by_group, axis=-1), c.topk_groups)
    kept = jnp.any(best[:, :, None] == jnp.arange(c.n_groups), axis=1)
    left = jnp.where(kept[:, :, None], by_group, 0.0).reshape(T, E)
    top_v, top_i = jax.lax.top_k(left, c.top_k)
    return top_i, top_v * c.routed_scale


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


@functools.partial(jax.jit, static_argnames=("c", "n_heads", "eps",
                                             "precision"))
def layer(p, x, *, c: Consts, n_heads: int, eps: float, precision: str):
    """One layer on (T, d) from position 0, its weights taken up to
    float32 here: dense where it carries `Wg`, routed where it carries a
    `router`."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        low = functools.partial(_low, precision=precision)
        pos = jnp.arange(x.shape[0])
        h = low(x + mla(p, low(_rms(x, p["an"], eps)), pos, c,
                        n_heads=n_heads, eps=eps, precision=precision))
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
