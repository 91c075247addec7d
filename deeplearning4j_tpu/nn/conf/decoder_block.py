"""A decoder block composed of kinds: a sequence mixer, a feed-forward
and a norm, each a small config object of its own.

    h <- h + r * mixer(norm(h));  h <- h + r * ffn(norm(h))      "pre"
    h <- h + r * norm(mixer(h));  h <- h + r * norm(ffn(h))      "post"
    u = norm(h);  h <- h + r * (mixer(u) + ffn(u))               "parallel"

A block may also be ONE sub-layer, a mixer and no feed-forward or a
feed-forward and no mixer (the `nemotron_h` family's layers): it then
has one norm, `n1_w`, and one residual.

`TransformerBlock` is one fixed composition (LayerNorm, biased
multi-head attention, dense MLP) and stays as it is; a model whose block
differs in kind (a state-space mixer, routed experts, RMSNorm) composes
a `DecoderBlock` from the kinds here instead of adding flags there
(ROADMAP D6). Each mixer kind declares the cache state a decode engine
must keep for it, `state`:

    "kv"         paged key/value pools, one position a token
                 (`AttentionMixer`; `kv_geometry` gives heads and width)
    "recurrent"  per-slot arrays of fixed size, overwritten in place
                 (`Mamba2Mixer`, `GatedDeltaNetMixer`,
                 `ChannelGatedDeltaMixer`; `state_shapes`
                 gives them: the state, slot axis first, then the
                 convolution tail, tap-major)

    "latent"     ONE paged pool of compressed key/value latents, one
                 position a token, shared by all heads
                 (`LatentAttentionMixer`; `latent_geometry` gives the
                 latent's and the rope key's widths)

a block without a mixer declares `DecoderBlock.state` "none": it keeps
nothing between tokens; a `ShortcutDecoderBlock` declares the pair of its
two mixers' kinds; and `serving/block_state.py` turns that declaration into the engine's
allocation and its prefill / decode steps. A kind serialises as
`{"kind": <name>, ...fields}` inside the layer's JSON.

Parameters of a block are one flat dict, as every layer's: the mixer's
under `mx_`, the feed-forward's under `ff_`, the norms' `n1_w`, `n2_w`
(a block of one sub-layer, or a "parallel" block, has no `n2_w`); a `ShortcutDecoderBlock`'s
first block's under `a_`, its second's under `b_`, the shortcut
feed-forward's under `sc_`.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import (
    _FIELD_DECODERS,
    FeedForwardLayer,
    register_layer,
    rms_norm,
)
from deeplearning4j_tpu.ops import delta_rule, rope, ssm

_KINDS = {}
_FLASH_FROM = 1024  # keys: beyond it attention takes the flash / blockwise path


def _kind(cls):
    _KINDS[cls.KIND] = cls
    return cls


def kind_from_json(d):
    """A kind from its `to_json()` dict (or the kind itself, passed
    through)."""
    if not isinstance(d, dict):
        return d
    d = dict(d)
    cls = _KINDS[d.pop("kind")]
    names = {f.name for f in dataclasses.fields(cls)}
    value = lambda v: tuple(v) if isinstance(v, list) else \
        kind_from_json(v) if isinstance(v, dict) and "kind" in v else v
    return cls(**{k: value(v) for k, v in d.items() if k in names})


class _Kind:
    def to_json(self) -> dict:
        out = {"kind": self.KIND}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            out[f.name] = list(v) if isinstance(v, tuple) else \
                v.to_json() if isinstance(v, _Kind) else v
        return out


for _field in ("mixer", "ffn", "norm", "shortcut"):
    _FIELD_DECODERS[_field] = kind_from_json


def sub(params: dict, prefix: str) -> dict:
    """The parameters under one prefix, with the prefix taken off."""
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


# --------------------------------------------------------------- norm kinds
@_kind
@dataclass(frozen=True)
class RMSNorm(_Kind):
    KIND = "rms_norm"
    eps: float = 1e-5

    def init_params(self, d: int, dtype) -> dict:
        return {"w": jnp.ones((d,), dtype)}

    def apply(self, w, x):
        return rms_norm(x, w, self.eps)


@_kind
@dataclass(frozen=True)
class LayerNorm(_Kind):
    """The mean taken out, then one gain and NO bias: `(x - mean(x)) /
    sqrt(var(x) + eps) * w`, statistics in float32 (Cohere's)."""
    KIND = "layer_norm"
    eps: float = 1e-5

    def init_params(self, d: int, dtype) -> dict:
        return {"w": jnp.ones((d,), dtype)}

    def apply(self, w, x):
        xf = x.astype(jnp.float32)
        xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(
            jnp.mean(xc * xc, axis=-1, keepdims=True) + self.eps)
        return (y * w.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------ rotary kinds
@_kind
@dataclass(frozen=True)
class Rotary(_Kind):
    """Rotary positions over a head's whole width: pair `i` turns by
    `pos * theta^(-2i / head_dim)`, angles in float32. `interleaved`:
    the pair is features (2i, 2i + 1) (`rope_gptj`); else (i, i +
    head_dim / 2) (rotate-half)."""
    KIND = "rotary"
    theta: float = 10000.0
    interleaved: bool = True

    def turn(self, u, positions):
        """`u` (..., T, H, hd) at `positions` (..., T) or (T,). An
        interleaved head comes back evens-first, queries and keys alike,
        so their product is the published one and the pages hold keys
        that never turn again."""
        hd = u.shape[-1]
        if self.interleaved:
            u = jnp.swapaxes(u.reshape(*u.shape[:-1], hd // 2, 2), -1, -2) \
                .reshape(u.shape)
        cos, sin = rope.rope_angles(positions, hd, self.theta)
        return rope.rope_rotate(u, cos, sin)


# ----------------------------------------------------- rotary scaling kinds
@_kind
@dataclass(frozen=True)
class YarnScaling(_Kind):
    """YaRN (arXiv:2309.00071) as a published `rope_scaling` of type
    "yarn" states it (`ops/rope.py` has the arithmetic): the rotary
    pairs' inverse frequencies blended between `f_i` and `f_i / factor`
    by how often a pair turns over the `original_max` positions the
    model was trained on, the cos and sin tables times `m(mscale) /
    m(mscale_all_dim)`, and the attention's softmax scale times
    `m(mscale_all_dim)^2` (`mscale_all_dim` 0: times 1), `m(x) = 0.1 x
    ln(factor) + 1`."""
    KIND = "yarn"
    factor: float = 1.0
    original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def inv_freq(self, dim: int, base: float):
        return rope.yarn_inv_freq(dim, base, self.factor, self.original_max,
                                  self.beta_fast, self.beta_slow)

    @property
    def table_scale(self) -> float:
        return rope.yarn_mscale(self.factor, self.mscale) \
            / rope.yarn_mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        return rope.yarn_mscale(self.factor, self.mscale_all_dim) ** 2 \
            if self.mscale_all_dim else 1.0


# -------------------------------------------------------------- mixer kinds
# float32 scores one block of a prompt's queries may take in the expanded
# attention: a prompt whose whole (H, T, T) is larger goes by blocks
_SCORE_BYTES = 1 << 29


def _as_written(a):
    """`a`, made where and as the code makes it: the identity, to values
    and to gradients. The latent mixer puts it around each small product
    it splits or turns afterwards. Without it XLA carries the split or the
    turn through the product onto the weight beside it, and a compiled
    decode step re-lays 17 to 50 MB matrices that never change to spare
    a 4 MB activation (`tests/test_tpu_compile.py` counts)."""
    return jax.lax.optimization_barrier(a)


def _decay_params(k_dt, k_a, n_heads: int, dtype) -> dict:
    """A recurrent mixer's per-head decay as Mamba-2 draws it: `dt_bias`
    the inverse softplus of a step log-uniform in [1e-3, 1e-1], `A_log`
    the log of a uniform in [1, 16]."""
    dt = jnp.exp(jax.random.uniform(k_dt, (n_heads,)) *
                 (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {"dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (n_heads,), minval=1.0, maxval=16.0)).astype(dtype)}


@_kind
@dataclass(frozen=True)
class AttentionMixer(_Kind):
    """Causal grouped-query attention without biases; without positional
    encoding unless `rope` names a `Rotary` kind, which then turns the
    queries and the keys at the positions the caller hands (`heads`; the
    turned keys are what the pages hold); `window` W (None: the whole
    context): query `i` attends keys `j` with `j <= i` and `i - j < W`,
    and a decode engine keeps such a layer's pages as a ring
    (`state` "window"); `head_dim` 0 is `d // n_heads`, any other is
    the heads' own width (the projections are then `n_heads * head_dim`
    wide, which need not be `d`); `scale` multiplies the scores (None:
    the usual 1 / sqrt(head_dim)). With `qk_norm` the query and key projections
    each pass an RMSNorm over their whole width (one gain an element,
    before the split into heads: the Olmo 2/3 convention), and the
    normed keys are what the pages hold. Keeps paged K/V."""
    KIND = "attention"
    n_heads: int = 4
    n_kv_heads: int = 0          # 0: as many as n_heads
    scale: Optional[float] = None
    qk_norm: bool = False
    eps: float = 1e-6            # of the query / key norms
    head_dim: int = 0            # 0: d // n_heads
    rope: Optional[Rotary] = None
    window: Optional[int] = None

    def __post_init__(self):
        if self.window is not None and self.window < 1:
            raise ValueError(f"window {self.window}: at least the "
                             "query's own position")
        object.__setattr__(self, "rope", kind_from_json(self.rope))

    @property
    def state(self) -> str:
        """Paged K/V; as a ring of pages a slot where a window bounds
        what a query reads."""
        return "kv" if self.window is None else "window"

    @property
    def _kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def kv_geometry(self, d: int) -> Tuple[int, int]:
        return self._kv_heads, self.head_dim or d // self.n_heads

    def init_params(self, key, d: int, dtype, winit) -> dict:
        hd = self.kv_geometry(d)[1]
        qw, kvw = self.n_heads * hd, self._kv_heads * hd
        k1, k2 = jax.random.split(key)
        p = {"Wqkv": winit(k1, (d, qw + 2 * kvw), d, qw + 2 * kvw),
             "Wo": winit(k2, (qw, d), qw, d)}
        if self.qk_norm:
            p.update(qn_w=jnp.ones((qw,), dtype),
                     kn_w=jnp.ones((kvw,), dtype))
        return p

    def _qk_normed(self, p, gain: str, u):
        if not self.qk_norm:
            return u
        with jax.named_scope("attn.qk_norm"):
            return rms_norm(u, p[gain], self.eps)

    def heads(self, p, x, positions=None):
        """(..., T, d) -> q (..., T, H, hd), k and v (..., T, Hkv, hd),
        `q` and `k` turned at `positions` (..., T) or (T,) where the
        mixer has a `rope` (None: `arange(T)`). The attention paths all
        divide the scores by sqrt(hd); `q` is scaled here so that their
        product comes out at `scale`."""
        hd = p["Wo"].shape[0] // self.n_heads
        qw, kvw = self.n_heads * hd, self._kv_heads * hd
        with jax.named_scope("attn.qkv"):
            qkv = x @ p["Wqkv"]
            q = self._qk_normed(p, "qn_w", qkv[..., :qw]) \
                .reshape(*x.shape[:-1], self.n_heads, hd)
            k = self._qk_normed(p, "kn_w", qkv[..., qw:qw + kvw]) \
                .reshape(*x.shape[:-1], self._kv_heads, hd)
            v = qkv[..., qw + kvw:].reshape(*x.shape[:-1],
                                            self._kv_heads, hd)
            if self.scale is not None:
                q = q * jnp.asarray(self.scale * math.sqrt(hd), q.dtype)
        if self.rope is not None:
            if positions is None:
                positions = jnp.arange(x.shape[-2])
            with jax.named_scope("attn.rope"):
                q = self.rope.turn(q, positions)
                k = self.rope.turn(k, positions)
        return q, k, v

    def out(self, p, att):
        with jax.named_scope("attn.out"):
            return att @ p["Wo"]

    def forward(self, p, x):
        from deeplearning4j_tpu.ops.attention import multi_head_attention

        q, k, v = self.heads(p, x)
        with jax.named_scope("attn.core"):
            att = multi_head_attention(q, k, v, causal=True,
                                       block_size=_FLASH_FROM,
                                       window=self.window)
        return self.out(p, att.reshape(*x.shape[:-1], -1))


@_kind
@dataclass(frozen=True)
class LatentAttentionMixer(_Kind):
    """Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434) as
    the Hugging Face `DeepseekV3Attention` / `LongcatFlashMLA` layers
    write it, no bias. Queries through a low-rank pair with an RMSNorm
    between, `[q_n | q_r]_h = (s_q N(x Wqa)) Wqb` (`nope_dim` +
    `rope_dim` a head); keys and values through ONE latent a position,
    `[c_kv | k_r] = x Wkva`, `c = N(c_kv)` (`kv_rank` wide) and a single
    rope key `k_r` (`rope_dim`) shared by all heads, expanded by
    `[k_n | v]_h = (s_kv c) Wkvb` (`nope_dim` + `v_dim` a head); each
    of `Wqb`, `Wkva`, `Wkvb` is held as its two parts (`init_params`).
    Rotary
    (`ops/rope.py`, interleaved pairs as published: features 2i and
    2i + 1 turn together) on `q_r` and `k_r` at the positions the caller
    gives; scores `(q_n.k_n + q_r.k_r) / sqrt(nope_dim + rope_dim)`,
    causal, float32 softmax; `o = concat_h(P v_h) Wo`. `scale_q_lora` /
    `scale_kv_lora` switch `s_q = sqrt(d / q_rank)` and `s_kv = sqrt(d /
    kv_rank)` on (else 1). `q_rank` None: FULL-RANK queries, `[q_n |
    q_r]_h = x Wq` with no latent and no norm between (`Wq` held as its
    nope and rope columns `Wqn`, `Wqr`, (d, H * nope) and (d, H * rope)).
    `head_gate`: a sigmoid gate a head on the heads' outputs before
    `Wo`, `o_h <- o_h * sigmoid((x Wa)_h)`, `Wa` (d, H), `x` the mixer's
    input (the published `head_wise` gated attention); off, nothing of
    it is traced.

    Three forms of the same attention, held to one another by
    `tests/test_latent_attention.py`: `forward` (expanded keys and
    values, a whole sequence), `attend_latents` with a chunk of queries
    against cached latents, and the same with one query a slot, the
    ABSORBED step: `q~_h = s_kv Wkvb_h^K q_n,h` so that scores are
    `q~_h.c + q_r,h.k_r` and `o_h = s_kv (Wkvb_h^V)^T sum_s p c(s)`:
    nothing is expanded, and a position's cache is `[c | k_r]`,
    `kv_rank + rope_dim` numbers for all heads. Keeps those latents,
    paged."""
    KIND = "latent_attention"
    state = "latent"
    n_heads: int = 4
    q_rank: Optional[int] = 32   # None: full-rank queries
    kv_rank: int = 16
    nope_dim: int = 8
    rope_dim: int = 4
    v_dim: int = 8
    rope_theta: float = 10000.0
    scale_q_lora: bool = False
    scale_kv_lora: bool = False
    eps: float = 1e-5
    rope_scaling: Optional[YarnScaling] = None
    head_gate: bool = False

    def __post_init__(self):
        object.__setattr__(self, "rope_scaling",
                           kind_from_json(self.rope_scaling))
        if self.q_rank is None and self.scale_q_lora:
            raise ValueError("scale_q_lora scales a query latent: there "
                             "is none with q_rank None")

    def latent_geometry(self) -> Tuple[int, int]:
        return self.kv_rank, self.rope_dim

    @property
    def sm_scale(self) -> float:
        """What multiplies the scores, in all three forms and in the
        kernel: `(nope + rope)^-1/2`, times the rotary scaling's
        temperature where the mixer has one."""
        s = 1.0 / math.sqrt(self.nope_dim + self.rope_dim)
        return s if self.rope_scaling is None \
            else s * self.rope_scaling.softmax_scale

    def init_params(self, key, d: int, dtype, winit) -> dict:
        """The published `q_b_proj`, `kv_a_proj_with_mqa` and `kv_b_proj`
        are each held as their two parts (fused, XLA re-lays a 38 MB
        matrix out every decode step to split heads of 128 + 64 off the
        lane grid: my sandbox compile, PR 40): `Wqn` / `Wqr` the
        queries' nope and rope columns, `Wkvc` / `Wkr` the latent's and
        the rope key's, `Wkb` (H, nope, kv_rank) / `Wvb` (H, kv_rank, v)
        a head's key and value expansions. The absorbed step reads each
        of them AS IT LIES here: `project`, `absorb` and `out` are
        written so that what a decode step turns is its own small
        products (`_as_written`), and `tests/test_tpu_compile.py` holds
        the compiled `decode_step` and `decode_chunked` of all three
        latent configurations to no copy, transpose or re-tiling of a
        weight (until PR 48 `Wqr` was re-laid three times and `Wqn`,
        `Wkb` and `Wvb` once a dispatch)."""
        H, kr = self.n_heads, self.kv_rank
        k = jax.random.split(key, 9)
        kvw = H * (self.nope_dim + self.v_dim)
        # full-rank queries read the stream itself: no `Wqa`, no `qn_w`
        qr = d if self.q_rank is None else self.q_rank
        low = {} if self.q_rank is None else {
            "Wqa": winit(k[0], (d, qr), d, qr),
            "qn_w": jnp.ones((qr,), dtype)}
        gate = {"Wa": winit(k[8], (d, H), d, H)} if self.head_gate else {}
        return {**low, **gate,
                "Wqn": winit(k[1], (qr, H * self.nope_dim), qr,
                             H * (self.nope_dim + self.rope_dim)),
                "Wqr": winit(k[2], (qr, H * self.rope_dim), qr,
                             H * (self.nope_dim + self.rope_dim)),
                "Wkvc": winit(k[3], (d, kr), d, kr + self.rope_dim),
                "Wkr": winit(k[4], (d, self.rope_dim), d,
                             kr + self.rope_dim),
                "kvn_w": jnp.ones((kr,), dtype),
                "Wkb": winit(k[5], (H, self.nope_dim, kr), kr, kvw),
                "Wvb": winit(k[6], (H, kr, self.v_dim), kr, kvw),
                "Wo": winit(k[7], (H * self.v_dim, d), H * self.v_dim, d)}

    def _lora_scale(self, p, on: bool, rank: int) -> float:
        return math.sqrt(p["Wkvc"].shape[0] / rank) if on else 1.0

    def _s_kv(self, p) -> float:
        return self._lora_scale(p, self.scale_kv_lora, self.kv_rank)

    def _rope(self, u, positions, heads: bool):
        """Rotary on (..., T, H, rope_dim) (`heads`) or (..., T,
        rope_dim) at `positions` (..., T) or (T,):
        pairs (2i, 2i + 1) turn by `pos * theta^(-2i / rope_dim)`, or
        by the blended frequencies of `rope_scaling`; the result lies
        evens-first, the same for queries and keys, so their product is
        the published one."""
        half, scaling = self.rope_dim // 2, self.rope_scaling
        with jax.named_scope("mla.rope"):
            u = jnp.swapaxes(u.reshape(*u.shape[:-1], half, 2), -1, -2) \
                .reshape(u.shape)
            if scaling is None:
                cos, sin = rope.rope_angles(positions, self.rope_dim,
                                            self.rope_theta)
            else:
                cos, sin = rope.rope_angles(
                    positions, self.rope_dim, inv_freq=scaling.inv_freq(
                        self.rope_dim, self.rope_theta))
                if scaling.table_scale != 1.0:
                    cos, sin = (t * scaling.table_scale for t in (cos, sin))
            if not heads:                   # one key a position
                return rope.rope_rotate(u[..., None, :], cos, sin)[..., 0, :]
            return rope.rope_rotate(u, cos, sin)

    def project(self, p, x, positions):
        """`x` (..., T, d) at `positions` (..., T) -> (q_n (..., T, H,
        nope), q_r (..., T, H, rope) turned, latent (..., T, kv_rank +
        rope): `[c | k_r]`, the normed latent and the turned rope key:
        what a position's cache holds)."""
        H = self.n_heads
        with jax.named_scope("mla.q"):
            if self.q_rank is None:
                cq = x
            else:
                cq = rms_norm(x @ p["Wqa"], p["qn_w"], self.eps)
                cq = cq * jnp.asarray(
                    self._lora_scale(p, self.scale_q_lora, self.q_rank),
                    cq.dtype)
            # fewer rows than the matrices have (a decode step's slots, a
            # short prompt): both products as written, so that the heads'
            # split and the rope's evens-first swap stay on them, off
            # `Wqn` / `Wqr`; a long prompt's products are the larger
            # side, and XLA may turn the matrices for them as it did
            few = math.prod(x.shape[:-1]) < cq.shape[-1]
            pin = _as_written if few else lambda a: a
            q_n = pin(cq @ p["Wqn"]).reshape(*x.shape[:-1], H, self.nope_dim)
            q_r = self._rope(
                pin(cq @ p["Wqr"]).reshape(*x.shape[:-1], H, self.rope_dim),
                positions, True)
        with jax.named_scope("mla.kv_down"):
            c = rms_norm(x @ p["Wkvc"], p["kvn_w"], self.eps)
            k_r = self._rope(x @ p["Wkr"], positions, False)
            latent = jnp.concatenate([c, k_r.astype(c.dtype)], axis=-1)
        return q_n, q_r, latent

    def absorb(self, p, q_n, q_r):
        """The absorbed queries (..., H, kv_rank + rope): `[s_kv W^K_h
        q_n,h | q_r,h]`, so that one product with a cached latent is the
        whole score."""
        with jax.named_scope("mla.absorb"):
            # the product by head comes out heads first and is turned
            # then: `Wkb` is read as it lies
            qt = _as_written(jnp.einsum("...hn,hnr->h...r", q_n, p["Wkb"]))
            qt = jnp.moveaxis(qt, 0, -2) \
                * jnp.asarray(self._s_kv(p), q_n.dtype)
            return jnp.concatenate([qt, q_r], axis=-1)

    def _gated(self, p, o, x):
        """The heads' outputs `o` (..., H, v) under the head gate of the
        mixer's input `x` (..., d); as they are where there is none."""
        if not self.head_gate:
            return o
        with jax.named_scope("mla.gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                x, p["Wa"], preferred_element_type=jnp.float32))
            return o * gate[..., None].astype(o.dtype)

    def out(self, p, u, x=None):
        """Latent-space values `u` (..., H, kv_rank), `sum_s p c(s)` a
        head, -> (..., d): up through `s_kv W^V_h`, under the head gate
        of the mixer's input `x` where the mixer has one, then `Wo`."""
        with jax.named_scope("mla.out"):
            # heads first and turned then, as in `absorb`: `Wvb` as it lies
            o = _as_written(jnp.einsum("...hr,hrv->h...v", u, p["Wvb"]))
            o = jnp.moveaxis(o, 0, -2) * jnp.asarray(self._s_kv(p), u.dtype)
            o = self._gated(p, o, x)
            return o.reshape(*u.shape[:-2], -1) @ p["Wo"]

    def attend_latents(self, q_abs, latents, q_pos):
        """Absorbed queries `q_abs` (..., C, H, kv_rank + rope) at
        positions `q_pos` (..., C) against cached `latents` (..., Tk,
        kv_rank + rope), entry `s` the position `s`: causal, float32
        softmax. Returns `u` (..., C, H, kv_rank) in the queries'
        dtype."""
        with jax.named_scope("mla.attend"):
            s = jnp.einsum("...chr,...sr->...hcs", q_abs, latents,
                           preferred_element_type=jnp.float32) \
                * self.sm_scale
            seen = jnp.arange(latents.shape[-2]) <= q_pos[..., None]
            s = jnp.where(seen[..., None, :, :], s, -1e30)
            prob = jax.nn.softmax(s, axis=-1).astype(latents.dtype)
            return jnp.einsum("...hcs,...sr->...chr", prob,
                              latents[..., :self.kv_rank],
                              preferred_element_type=jnp.float32) \
                .astype(q_abs.dtype)

    def query_block(self, T: int) -> int:
        """Queries the expanded attention takes at a time over a prompt
        of `T` positions: all of them where the heads' float32 scores
        (H, T, T) fit `_SCORE_BYTES`, else the power of two of rows
        whose scores against all `T` keys do (at least 128)."""
        rows = _SCORE_BYTES // (4 * self.n_heads * T)
        return T if rows >= T else max(128, 1 << (rows.bit_length() - 1))

    def _attend_kernel(self, q_n, q_r, k_n, k_r, v, n_valid):
        """The expanded attention of ONE prompt through the prefill
        kernel of `ops/pallas_mla_attend.py`, heads first, or None where
        it cannot serve (a CPU, the kill switch, a length off its
        blocks, a batch)."""
        from deeplearning4j_tpu.ops.pallas_mla_attend import (
            mla_prefill_or_none,
        )

        B, T = q_n.shape[:2]
        if B != 1:
            return None
        heads_first = lambda a: jnp.swapaxes(a[0], 0, 1)
        n_valid = jnp.full((1,), T if n_valid is None else n_valid,
                           jnp.int32)
        o = mla_prefill_or_none(
            heads_first(q_n), heads_first(q_r), heads_first(k_n), k_r[0],
            heads_first(v), n_valid, sm_scale=self.sm_scale)
        return None if o is None else jnp.swapaxes(o, 0, 1)[None]

    def attend_expanded(self, p, q_n, q_r, latent, n_valid=None, x=None):
        """One sequence's queries (B, T, H, .) against its own latents
        (B, T, kv_rank + rope), keys and values expanded per head:
        causal, float32 softmax; where the heads' scores over the whole
        prompt pass `_SCORE_BYTES`, through the prefill kernel (which
        leaves the rows from `n_valid` on, a bucket's padding, zeros) or
        by blocks of `query_block(T)` queries; `x` (B, T, d) the mixer's
        input, which the head gate reads. Returns (B, T, d)."""
        c, k_r = latent[..., :self.kv_rank], latent[..., self.kv_rank:]
        with jax.named_scope("mla.kv_up"):
            cs = c * jnp.asarray(self._s_kv(p), c.dtype)
            k_n = jnp.einsum("btr,hnr->bthn", cs, p["Wkb"],
                             preferred_element_type=jnp.float32) \
                .astype(c.dtype)
            v = jnp.einsum("btr,hrv->bthv", cs, p["Wvb"],
                           preferred_element_type=jnp.float32) \
                .astype(c.dtype)
        T = c.shape[1]

        def attend(t0, t1):
            """Queries t0..t1-1 against keys 0..t1-1 (all of both: the
            arrays as they are, so that a prompt that goes whole lowers
            to the program it always did)."""
            whole = (t0, t1) == (0, T)
            cut = (lambda a, lo: a) if whole else (lambda a, lo: a[:, lo:t1])
            s = (jnp.einsum("bthn,bshn->bhts", cut(q_n, t0), cut(k_n, 0),
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthr,bsr->bhts", cut(q_r, t0), cut(k_r, 0),
                              preferred_element_type=jnp.float32)) \
                * self.sm_scale
            seen = jnp.tril(jnp.ones((T, T), bool)) if whole else \
                jnp.arange(t0, t1)[:, None] >= jnp.arange(t1)
            s = jnp.where(seen, s, -1e30)
            prob = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("bhts,bshv->bthv", prob, cut(v, 0),
                              preferred_element_type=jnp.float32) \
                .astype(v.dtype)

        block = self.query_block(T)
        with jax.named_scope("mla.attend"):
            # a prompt too long for one (H, T, T) array: the prefill
            # kernel where it serves, else blocks of queries, each
            # against the keys up to its own last position (the keys a
            # block cannot see are not multiplied at all)
            o = attend(0, T) if block >= T else \
                self._attend_kernel(q_n, q_r, k_n, k_r, v, n_valid)
            if o is None:
                o = jnp.concatenate(
                    [attend(t0, min(t0 + block, T))
                     for t0 in range(0, T, block)], axis=1)
        with jax.named_scope("mla.out"):
            o = self._gated(p, o, x)
            return o.reshape(*o.shape[:2], -1) @ p["Wo"]

    def forward(self, p, x, positions=None):
        """`x` (B, T, d), a whole sequence from position 0 (or at
        `positions` (T,))."""
        if positions is None:
            positions = jnp.arange(x.shape[1])
        return self.attend_expanded(p, *self.project(p, x, positions), x=x)


@_kind
@dataclass(frozen=True)
class Mamba2Mixer(_Kind):
    """Mamba-2 (arXiv:2405.21060) as Hugging Face's `GraniteMoeHybrid`,
    `NemotronH` and `Mamba2` layers write it: in-projection to
    [z | xBC | dt], depthwise causal convolution and silu over xBC, the
    selective state-space recurrence of `ops/ssm.py`, a gated RMSNorm,
    out-projection. `n_groups` G: B and C are (G, d_state) each, head
    `h` reads group `h // (n_heads / G)`, and the gated norm is taken
    over each group's `d_inner / G` channels on its own (one gain an
    element either way); at one group that is the whole inner width.
    Keeps a per-slot recurrent state (float32) and the convolution's
    last inputs."""
    KIND = "mamba2"
    state = "recurrent"
    n_heads: int = 8
    head_dim: int = 16
    d_state: int = 16
    d_conv: int = 4
    chunk: int = 256
    eps: float = 1e-5
    n_groups: int = 1

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    def state_shapes(self, n_slots: int, dtype) -> tuple:
        """((shape, dtype), ...) of what one block keeps for `n_slots`
        slots: the recurrent state, then the convolution tail (tap-major:
        `ops/ssm.conv_step`)."""
        return (((n_slots, self.n_heads, self.head_dim, self.d_state),
                 jnp.float32),
                ((self.d_conv - 1, n_slots, self.conv_width), dtype))

    def init_params(self, key, d: int, dtype, winit) -> dict:
        di, cw, H = self.d_inner, self.conv_width, self.n_heads
        k = jax.random.split(key, 5)
        width = di + cw + H
        return {"Win": winit(k[0], (d, width), d, width),
                "conv_w": (jax.random.normal(k[1], (cw, self.d_conv))
                           / math.sqrt(self.d_conv)).astype(dtype),
                "conv_b": jnp.zeros((cw,), dtype),
                **_decay_params(k[3], k[4], H, dtype),
                "D": jnp.ones((H,), dtype),
                "norm_w": jnp.ones((di,), dtype),
                "Wout": winit(k[2], (di, d), di, d)}

    def _split_in(self, p, x):
        with jax.named_scope("ssm.in_proj"):
            zxd = x @ p["Win"]
        di, cw = self.d_inner, self.conv_width
        return zxd[..., :di], zxd[..., di:di + cw], zxd[..., di + cw:]

    def _dt(self, p, dt_raw, keep):
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        return dt if keep is None else jnp.where(keep, dt, 0.0)

    def _bc(self, xbc):
        """The B and C parts of silu(conv) output (..., Cw): (..., N)
        each at one group, (..., G, N) at more."""
        di, w = self.d_inner, self.n_groups * self.d_state
        Bm, Cm = xbc[..., di:di + w], xbc[..., di + w:]
        if self.n_groups == 1:
            return Bm, Cm
        shape = (*xbc.shape[:-1], self.n_groups, self.d_state)
        return Bm.reshape(shape), Cm.reshape(shape)

    def _finish(self, p, y, z):
        with jax.named_scope("ssm.gate_norm"):
            y = y * jax.nn.silu(z)
            by_group = (*y.shape[:-1], self.n_groups, -1)
            y = rms_norm(y.reshape(by_group),
                         p["norm_w"].reshape(by_group[-2:]),
                         self.eps).reshape(y.shape)
        with jax.named_scope("ssm.out_proj"):
            return y @ p["Wout"]

    def scan(self, p, x, h0=None, tail=None, n_valid=None):
        """A whole stretch (B, T, d) from state `h0` and convolution
        tail `tail` (None: the start of a sequence). Positions at and
        after `n_valid` (a traced scalar, default T) are padding: they
        move neither the state nor the tail. Returns (out (B, T, d),
        state, tail)."""
        B, T, _ = x.shape
        z, xbc, dt_raw = self._split_in(p, x)
        with jax.named_scope("ssm.conv"):
            xbc, tail = ssm.causal_conv(xbc, p["conv_w"], p["conv_b"],
                                        tail, n_valid)
            xbc = jax.nn.silu(xbc)
        di = self.d_inner
        keep = None if n_valid is None else \
            (jnp.arange(T) < n_valid)[None, :, None]
        with jax.named_scope("ssm.scan"):
            y, h = ssm.ssd_chunked(
                xbc[..., :di].reshape(B, T, self.n_heads, self.head_dim),
                self._dt(p, dt_raw, keep),
                -jnp.exp(p["A_log"].astype(jnp.float32)),
                *self._bc(xbc), p["D"], chunk=self.chunk, h0=h0)
        return self._finish(p, y.reshape(B, T, di), z), h, tail

    def step(self, p, x, h, tail, active=None):
        """One token for every slot: `x` (S, d), `h` (S, H, P, N),
        `tail` (K - 1, S, Cw). Slots that `active` (S,) bool leaves out
        keep state and tail as they are."""
        S = x.shape[0]
        z, xbc, dt_raw = self._split_in(p, x)
        with jax.named_scope("ssm.conv"):
            xbc, new_tail = ssm.conv_step(xbc, p["conv_w"], p["conv_b"],
                                          tail)
            xbc = jax.nn.silu(xbc)
            if active is not None:
                new_tail = jnp.where(active[None, :, None], new_tail,
                                     tail)
        di = self.d_inner
        keep = None if active is None else active[:, None]
        with jax.named_scope("ssm.step"):
            y, h = ssm.ssm_step(
                h, xbc[..., :di].reshape(S, self.n_heads, self.head_dim),
                self._dt(p, dt_raw, keep),
                -jnp.exp(p["A_log"].astype(jnp.float32)),
                *self._bc(xbc), p["D"])
        return self._finish(p, y.reshape(S, di), z), h, \
            new_tail.astype(tail.dtype)

    def forward(self, p, x):
        return self.scan(p, x)[0]


@_kind
@dataclass(frozen=True)
class GatedDeltaNetMixer(_Kind):
    """The gated delta-rule layer (Gated DeltaNet, arXiv:2412.06464) as
    the `fla` / Hugging Face layers that use the `linear_*` config keys
    write it: one in-projection to [q | k | v | gate | a | b], a
    depthwise causal convolution and silu over [q | k | v], q and k
    L2-normed per head (q also scaled by d_k^-1/2), `beta = sigmoid(b)`
    (doubled with `allow_neg_eigval`), log decay `g = -exp(A_log) *
    softplus(a + dt_bias)`, the recurrence of `ops/delta_rule.py`, an
    RMSNorm per head over d_v whose output the gate multiplies (AFTER
    the norm; `Mamba2Mixer` gates before it), out-projection. Keeps a
    per-slot matrix state (float32, `(d_k, H * d_v)`: no lane of it is
    padding) and the convolution's last inputs."""
    KIND = "gated_delta_net"
    SCOPE = "gdn"  # the prefix of the mixer's named scopes
    state = "recurrent"
    n_heads: int = 4
    key_dim: int = 8
    value_dim: int = 16
    d_conv: int = 4
    chunk: int = 64
    allow_neg_eigval: bool = False
    eps: float = 1e-6
    _out_gate = staticmethod(jax.nn.silu)  # on the gate, after the norm

    @property
    def qk_width(self) -> int:
        return self.n_heads * self.key_dim

    @property
    def decay_width(self) -> int:
        """The in-projection's columns the log decay is made of: one a
        head."""
        return self.n_heads

    @property
    def v_width(self) -> int:
        return self.n_heads * self.value_dim

    @property
    def conv_width(self) -> int:
        return 2 * self.qk_width + self.v_width

    def state_shapes(self, n_slots: int, dtype) -> tuple:
        """((shape, dtype), ...) of what one block keeps for `n_slots`
        slots: the matrix states (`ops/delta_rule.py`'s layout), then
        the convolution tail (tap-major: `ops/ssm.conv_step`)."""
        return (((n_slots, self.key_dim, self.v_width), jnp.float32),
                ((self.d_conv - 1, n_slots, self.conv_width), dtype))

    def init_params(self, key, d: int, dtype, winit) -> dict:
        cw, vw, H = self.conv_width, self.v_width, self.n_heads
        k = jax.random.split(key, 5)
        width = cw + vw + self.decay_width + H
        return {"Win": winit(k[0], (d, width), d, width),
                "conv_w": (jax.random.normal(k[1], (cw, self.d_conv))
                           / math.sqrt(self.d_conv)).astype(dtype),
                **self._decay_params(k[3], k[4], dtype),
                "norm_w": jnp.ones((self.value_dim,), dtype),
                "Wout": winit(k[2], (vw, d), vw, d)}

    def _decay_params(self, k_dt, k_a, dtype) -> dict:
        return _decay_params(k_dt, k_a, self.n_heads, dtype)

    def _split_in(self, p, x):
        with jax.named_scope(f"{self.SCOPE}.in_proj"):
            z = x @ p["Win"]
        cw, vw, dw = self.conv_width, self.v_width, self.decay_width
        return (z[..., :cw], z[..., cw:cw + vw],
                z[..., cw + vw:cw + vw + dw], z[..., cw + vw + dw:])

    def _heads(self, qkv):
        """silu(conv) output (..., Cw) -> q, k (..., H, d_k) float32,
        normalised, and v (..., H, d_v)."""
        qw, H = self.qk_width, self.n_heads
        lead = qkv.shape[:-1]

        def unit(u):
            u = u.astype(jnp.float32).reshape(*lead, H, self.key_dim)
            return u * jax.lax.rsqrt(
                jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)

        return (unit(qkv[..., :qw]) * self.key_dim ** -0.5,
                unit(qkv[..., qw:2 * qw]),
                qkv[..., 2 * qw:].reshape(*lead, H, self.value_dim))

    def _beta(self, b_raw):
        beta = jax.nn.sigmoid(b_raw.astype(jnp.float32))
        return 2.0 * beta if self.allow_neg_eigval else beta

    def _gates(self, p, a_raw, b_raw, keep=None):
        """(g, beta) float32; where `keep` is False both are 0 and the
        slot's state stays as it was."""
        beta = self._beta(b_raw)
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        if keep is None:
            return g, beta
        return jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)

    def _finish(self, p, o, gate):
        with jax.named_scope(f"{self.SCOPE}.gate_norm"):
            y = rms_norm(o, p["norm_w"], self.eps).reshape(gate.shape) \
                * self._out_gate(gate)
        with jax.named_scope(f"{self.SCOPE}.out_proj"):
            return y @ p["Wout"]

    def scan(self, p, x, h0=None, tail=None, n_valid=None):
        """A whole stretch (B, T, d) from state `h0` and convolution
        tail `tail` (None: the start of a sequence). Positions at and
        after `n_valid` (a traced scalar, default T) are padding: they
        move neither the state nor the tail. Returns (out (B, T, d),
        state, tail)."""
        qkv, gate, a_raw, b_raw = self._split_in(p, x)
        with jax.named_scope(f"{self.SCOPE}.conv"):
            qkv, tail = ssm.causal_conv(qkv, p["conv_w"], None, tail,
                                        n_valid)
            qkv = jax.nn.silu(qkv)
        with jax.named_scope(f"{self.SCOPE}.scan"):
            o, h = delta_rule.delta_chunked(
                *self._heads(qkv), *self._gates(p, a_raw, b_raw),
                chunk=self.chunk, h0=h0, n_valid=n_valid)
        return self._finish(p, o, gate), h, tail

    def step(self, p, x, h, tail, active=None):
        """One token for every slot: `x` (S, d), `h` (S, d_k, H * d_v),
        `tail` (K - 1, S, Cw). Slots that `active` (S,) bool leaves out
        keep state and tail as they are. The update goes through the
        kernel of the decay's shape where it serves
        (`ops/pallas_delta_step.py`)."""
        from deeplearning4j_tpu.ops.pallas_delta_step import (
            delta_step_or_none,
        )

        qkv, gate, a_raw, b_raw = self._split_in(p, x)
        with jax.named_scope(f"{self.SCOPE}.conv"):
            qkv, new_tail = ssm.conv_step(qkv, p["conv_w"], None, tail)
            qkv = jax.nn.silu(qkv)
            if active is not None:
                new_tail = jnp.where(active[None, :, None], new_tail,
                                     tail)
        keep = None if active is None else active[:, None]
        with jax.named_scope(f"{self.SCOPE}.step"):
            args = (h, *self._heads(qkv),
                    *self._gates(p, a_raw, b_raw, keep))
            out = delta_step_or_none(*args)
            o, h = delta_rule.delta_step(*args) if out is None else out
        return self._finish(p, o, gate), h, new_tail.astype(tail.dtype)

    def forward(self, p, x):
        return self.scan(p, x)[0]


@_kind
@dataclass(frozen=True)
class ChannelGatedDeltaMixer(GatedDeltaNetMixer):
    """The delta-rule layer with a decay a KEY CHANNEL (Kimi Delta
    Attention, arXiv:2510.26692, as the `fla` layer writes it with
    full-rank projections): `GatedDeltaNetMixer`'s in-projection,
    convolution, L2-normed q and k and `beta = sigmoid(b)`, and in place
    of one decay a head the vector `g_h = lower * sigmoid(exp(A_h) *
    (f_h + bias_h))` over head h's `key_dim` channels (`f` a projection
    of its own, `H * d_k` wide; `A` a number a head, `bias` one a
    channel; `gate_lower_bound` = `lower` < 0 bounds every log decay in
    (lower, 0), the "safe gate": `ops/delta_rule.py` says what the
    bound buys the chunked form), the recurrence of `ops/delta_rule.py`
    with that vector, and an RMSNorm per head over d_v whose output a
    SIGMOID gate multiplies, a channel each. The in-projection is
    [q | k | v | gate | f | b]; state, tail, `scan` and `step` are
    `GatedDeltaNetMixer`'s, under the scopes `kda.*`."""
    KIND = "channel_gated_delta"
    SCOPE = "kda"
    gate_lower_bound: float = -5.0
    _out_gate = staticmethod(jax.nn.sigmoid)

    def __post_init__(self):
        if not self.gate_lower_bound < 0:
            raise ValueError(f"gate_lower_bound {self.gate_lower_bound}: "
                             "a log decay's bound lies below 0")

    @property
    def decay_width(self) -> int:
        return self.qk_width

    def _decay_params(self, k_dt, k_a, dtype) -> dict:
        return {"A_log": _decay_params(k_dt, k_a, self.n_heads,
                                       dtype)["A_log"],
                "dt_bias": jnp.zeros((self.qk_width,), dtype)}

    def _gates(self, p, f_raw, b_raw, keep=None):
        """(g (..., H, d_k), beta (..., H)) float32; where `keep` is
        False both are 0 and the slot's state stays as it was."""
        H, dk = self.n_heads, self.key_dim
        with jax.named_scope("kda.gate"):
            beta = self._beta(b_raw)
            f = (f_raw.astype(jnp.float32)
                 + p["dt_bias"].astype(jnp.float32)) \
                .reshape(*f_raw.shape[:-1], H, dk)
            g = self.gate_lower_bound * jax.nn.sigmoid(
                jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * f)
            if keep is None:
                return g, beta
            return jnp.where(keep[..., None], g, 0.0), \
                jnp.where(keep, beta, 0.0)


# ------------------------------------------------------- feed-forward kinds
@_kind
@dataclass(frozen=True)
class GatedMLP(_Kind):
    """One dense gated MLP of width `width`, no bias:
    `(silu(x Wg) * (x Wu)) Wd`."""
    KIND = "gated_mlp"
    width: int = 64

    def init_params(self, key, d: int, dtype, winit) -> dict:
        f = self.width
        k = jax.random.split(key, 3)
        return {"Wg": winit(k[0], (d, f), d, f),
                "Wu": winit(k[1], (d, f), d, f),
                "Wd": winit(k[2], (f, d), f, d)}

    def forward(self, p, x, count_mask=None):
        """`x` (..., d) -> (y, None): no router, nothing to count."""
        from deeplearning4j_tpu.parallel.experts import gated_mlp

        with jax.named_scope("mlp"):
            return gated_mlp(x, p["Wg"], p["Wu"], p["Wd"]), None


@_kind
@dataclass(frozen=True)
class MoEFeedForward(_Kind):
    """`n_experts` routed MLPs of width `expert_width`, `top_k` a token,
    no capacity and no token dropped; plus one shared MLP of width
    `shared_width` (0: none) added times `shared_scale` (1.0:
    unweighted; 1 / n where the one MLP stands for n shared experts
    side by side whose outputs are averaged). `experts_held = (first,
    count)` is the share of the experts whose weights this layer holds
    and computes (None: all of them): it routes over all `n_experts` and
    leaves the absent experts' part of the sum out
    (`parallel/experts.py`).

    `activation`, of the routed and the shared experts alike:
    "gated_silu", `(silu(x Wg) * (x Wu)) Wd`, three matrices; or
    "relu2", ungated `relu(x Wu)^2 Wd`, two matrices (no `Wg` leaf, the
    routed `Wu` held (E, f, d): `parallel/experts.py`).
    `scoring`, of the router: "softmax", gates a softmax over the chosen
    logits; or "sigmoid", the experts chosen on `sigmoid(logit) +
    router_b` (a float32 leaf, one number an expert: it moves the choice
    and never the weight) and weighed by their unbiased scores
    normalised to sum 1, times `routed_scale`; or "softmax_all", chosen
    on a softmax over all the router's outputs (`n_zero_experts` of them
    zero-compute experts after the real ones) and weighed by that score
    times `routed_scale`. `n_groups` > 1 (with "softmax_all" or
    "sigmoid"): the experts lie in that many equal groups and a token
    chooses among its `topk_groups` best groups only, a group scored by
    its largest score under "softmax_all" and by the sum of its two
    largest biased scores under "sigmoid" (device-limited routing:
    `parallel.experts.group_limited`)."""
    KIND = "moe"
    n_experts: int = 8
    top_k: int = 2
    expert_width: int = 64
    shared_width: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    activation: str = "gated_silu"
    scoring: str = "softmax"
    routed_scale: float = 1.0
    n_zero_experts: int = 0
    n_groups: int = 1
    topk_groups: int = 1
    shared_scale: float = 1.0

    def __post_init__(self):
        from deeplearning4j_tpu.parallel.experts import check_groups

        if self.activation not in ("gated_silu", "relu2"):
            raise ValueError(f"activation {self.activation!r}: "
                             "'gated_silu' or 'relu2'")
        if self.scoring not in ("softmax", "sigmoid", "softmax_all"):
            raise ValueError(f"scoring {self.scoring!r}: 'softmax', "
                             "'sigmoid' or 'softmax_all'")
        check_groups(self.scoring, self.n_experts, self.n_groups,
                     self.topk_groups, self.n_zero_experts)

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held) if self.experts_held \
            else (0, self.n_experts)

    @property
    def _gated(self) -> bool:
        return self.activation == "gated_silu"

    def init_params(self, key, d: int, dtype, winit) -> dict:
        E, f, s = self.held[1], self.expert_width, self.shared_width
        k = jax.random.split(key, 7)
        n_out = self.n_experts + self.n_zero_experts
        p = {"router": winit(k[0], (d, n_out), d, n_out),
             "Wd": winit(k[3], (E, f, d), f, d)}
        if self._gated:
            p.update(Wg=winit(k[1], (E, d, f), d, f),
                     Wu=winit(k[2], (E, d, f), d, f))
        else:
            p["Wu"] = winit(k[2], (E, f, d), d, f)
        if self.scoring != "softmax":
            p["router_b"] = jnp.zeros((n_out,), jnp.float32)
        if s:
            p.update({"sWu": winit(k[5], (d, s), d, s),
                      "sWd": winit(k[6], (s, d), s, d)})
            if self._gated:
                p["sWg"] = winit(k[4], (d, s), d, s)
        return p

    def goes_sorted(self, rows: int, d: int, dtype) -> bool:
        """Whether `rows` rows of width `d` take these experts sorted by
        expert: the rule over shapes says so
        (`pallas_moe_experts.sorted_serves`) and the sorted kernel's
        probe passed at this shape class in this process. Asked after
        the program that holds the rows was traced: the probe runs
        then."""
        from deeplearning4j_tpu.ops import kernel_dispatch
        from deeplearning4j_tpu.ops import pallas_moe_experts as pme

        key = pme.sorted_key(dtype, d, self.expert_width, self.activation)
        return pme.sorted_serves(rows, self.held[1], self.top_k,
                                 self.n_experts + self.n_zero_experts) \
            and bool(kernel_dispatch.engaged(pme.FAMILY,
                                             lambda k: k == key))

    def forward(self, p, x, count_mask=None):
        """`x` (..., d) -> (y, counts): with `count_mask` (one bool a
        token: the rows anyone will read), a `RouteCounts` over the
        masked-in tokens (how many chose each held expert and whether it
        was read, (2, held); how many chose any held expert; with zero
        experts, how many choices fell on those); else None
        (`parallel.experts.dropless_moe`)."""
        from deeplearning4j_tpu.parallel.experts import (
            dropless_moe,
            gated_mlp,
            relu2_mlp,
        )

        flat = x.reshape(-1, x.shape[-1])
        y, counts = dropless_moe(
            flat, p["router"], p.get("Wg"), p["Wu"], p["Wd"],
            top_k=self.top_k, experts_held=self.held, count_mask=count_mask,
            act=self.activation, router_bias=p.get("router_b"),
            routed_scale=self.routed_scale, scoring=self.scoring,
            n_zero=self.n_zero_experts, n_groups=self.n_groups,
            topk_groups=self.topk_groups)
        if self.shared_width:
            with jax.named_scope("moe.shared"):
                shared = gated_mlp(flat, p["sWg"], p["sWu"], p["sWd"]) \
                    if self._gated else relu2_mlp(flat, p["sWu"], p["sWd"])
                if self.shared_scale != 1.0:
                    shared = shared * jnp.asarray(self.shared_scale,
                                                  shared.dtype)
                y = y + shared
        return y.reshape(x.shape), counts


# ---------------------------------------------------------------- the layer
@register_layer
@dataclass
class DecoderBlock(FeedForwardLayer):
    """One decoder block composed of a mixer kind, a feed-forward kind
    and a norm kind (module docstring), or of ONE of the two sub-layers
    (`mixer` or `ffn` None) under one norm; `norm_placement` says
    whether the norm stands before each sub-layer ("pre": its INPUT is
    normed) or after it ("post": its OUTPUT is normed before the
    residual add, the Olmo 2/3 convention); `residual_multiplier` scales
    every branch before it is added."""

    TYPE = "decoder_block"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    mixer: object = None
    ffn: object = None
    norm: object = None
    residual_multiplier: float = 1.0
    norm_placement: str = "pre"

    def __post_init__(self):
        if self.norm_placement not in ("pre", "post", "parallel"):
            raise ValueError(f"norm_placement {self.norm_placement!r}: "
                             "'pre', 'post' or 'parallel'")
        self.mixer = kind_from_json(self.mixer)
        self.ffn = kind_from_json(self.ffn)
        self.norm = kind_from_json(self.norm) or RMSNorm()
        if self.mixer is None and self.ffn is None:
            raise ValueError("DecoderBlock needs a mixer kind or a "
                             "feed-forward kind (or both)")
        if self.n_in and self.n_out and self.n_in != self.n_out:
            raise ValueError("DecoderBlock keeps width: n_in == n_out")
        if self._parallel and (self.mixer is None or self.ffn is None):
            raise ValueError("a 'parallel' DecoderBlock has a mixer AND a "
                             "feed-forward: they share its one norm")

    @property
    def _d(self) -> int:
        return self.n_out or self.n_in

    @property
    def _parallel(self) -> bool:
        return self.norm_placement == "parallel"

    @property
    def state(self) -> str:
        """The cache state a decode engine keeps for this block: what
        its mixer declares, "none" for a block without a mixer."""
        return "none" if self.mixer is None else self.mixer.state

    def output_type(self, it):
        return it

    def init_params(self, key, it, dtype=jnp.float32):
        d = self._d
        k1, k2 = jax.random.split(key)
        mk = lambda k, shape, fi, fo: self._winit(k, shape, fi, fo, dtype)
        p = {"n1_w": self.norm.init_params(d, dtype)["w"]}
        if self.mixer is not None:
            p.update({"mx_" + n: v for n, v in
                      self.mixer.init_params(k1, d, dtype, mk).items()})
        if self.ffn is not None:
            p.update({"ff_" + n: v for n, v in
                      self.ffn.init_params(k2, d, dtype, mk).items()})
        if self.mixer is not None and self.ffn is not None \
                and not self._parallel:
            p["n2_w"] = self.norm.init_params(d, dtype)["w"]
        return p

    def norm1(self, p, x):
        with jax.named_scope("norm1"):
            return self.norm.apply(p["n1_w"], x)

    def norm2(self, p, x):
        with jax.named_scope("norm2"):
            return self.norm.apply(p["n2_w"], x)

    def mixer_in(self, p, x):
        """What the mixer reads: the block's input, normed first where
        the norm stands before the sub-layer."""
        return x if self.norm_placement == "post" else self.norm1(p, x)

    def after_mixer(self, p, x, mixed):
        """The stream after the mixer's residual (`mixed` None: the
        block has no mixer, the stream as it came). A "parallel" block
        has ONE residual, added in `finish`."""
        if self.mixer is None:
            return x
        r = jnp.asarray(self.residual_multiplier, x.dtype)
        post = self.norm_placement == "post"
        return x + r * (self.norm1(p, mixed) if post else mixed)

    def _ffn_norm(self):
        # the block's second norm, or the one norm of a feed-forward alone
        return self.norm1 if self.mixer is None else self.norm2

    def ffn_in(self, p, h):
        """What the feed-forward reads of the stream `h`."""
        return h if self.norm_placement == "post" \
            else self._ffn_norm()(p, h)

    def after_ffn(self, p, h, f):
        """The stream after the feed-forward's residual."""
        r = jnp.asarray(self.residual_multiplier, h.dtype)
        post = self.norm_placement == "post"
        return h + r * (self._ffn_norm()(p, f) if post else f)

    def finish(self, p, x, mixed, count_mask=None):
        """The block from the mixer's output `mixed` on (None: the block
        has no mixer): the mixer's residual, then norm, feed-forward and
        its residual where the block has a feed-forward. Returns (h, the
        feed-forward's counts under `count_mask`, or None)."""
        if self._parallel:
            # the feed-forward reads what the mixer read; XLA makes the
            # one norm once where both are traced in one program
            f, counts = self.ffn.forward(sub(p, "ff_"), self.norm1(p, x),
                                         count_mask)
            r = jnp.asarray(self.residual_multiplier, x.dtype)
            return x + r * (mixed + f), counts
        h = self.after_mixer(p, x, mixed)
        if self.ffn is None:
            return h, None
        f, counts = self.ffn.forward(sub(p, "ff_"), self.ffn_in(p, h),
                                     count_mask)
        return self.after_ffn(p, h, f), counts

    def feed_forwards(self) -> list:
        """The block's feed-forward kinds, in order."""
        return [] if self.ffn is None else [self.ffn]

    def mixers(self) -> list:
        """The block's mixer kinds, in order."""
        return [] if self.mixer is None else [self.mixer]

    def to_json(self) -> dict:
        from deeplearning4j_tpu.nn.conf.layers import layer_to_json

        return layer_to_json(self)

    def forward(self, params, state, x, *, train=False, rng=None,
                mask=None):
        mixed = None if self.mixer is None else self.mixer.forward(
            sub(params, "mx_"), self.mixer_in(params, x))
        return self.finish(params, x, mixed)[0], state

    def param_flags(self, name):
        vector = name in ("n1_w", "n2_w", "mx_norm_w", "mx_conv_b",
                          "mx_dt_bias", "mx_A_log", "mx_D", "mx_qn_w",
                          "mx_kn_w", "mx_kvn_w", "ff_router_b")
        return {"is_bias": name in ("mx_conv_b", "mx_dt_bias",
                                    "ff_router_b"),
                "regularizable": not vector}


def _block_from_json(d):
    from deeplearning4j_tpu.nn.conf.layers import layer_from_json

    return layer_from_json(d) if isinstance(d, dict) else d


for _field in ("first", "second"):
    _FIELD_DECODERS[_field] = _block_from_json


@register_layer
@dataclass
class ShortcutDecoderBlock(FeedForwardLayer):
    """Two pre-norm `DecoderBlock`s in a row and one more feed-forward
    on a SHORTCUT around the second (the shortcut-connected
    mixture-of-experts layer of LongCat-Flash, arXiv:2509.01322): the
    shortcut reads what the first block's feed-forward reads, leaves the
    residual stream there and joins it after the second block,

        h1 = h + mixer_a(N(h));   x1 = N(h1)
        m  = shortcut(x1);        h2 = h1 + ffn_a(x1)
        out = second(h2) + m

    so neither sub-layer of `second` sees `m` (in a deployment the
    routed experts' exchange overlaps them). `x1` is normed once and
    feeds both. ONE layer of the net with two mixers: `state` is the
    pair of their kinds, and a decode engine keeps a cache for each.
    Parameters: `first`'s under `a_`, `second`'s under `b_`, the
    shortcut's under `sc_`."""

    TYPE = "shortcut_decoder_block"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    first: object = None
    second: object = None
    shortcut: object = None

    def __post_init__(self):
        self.first = _block_from_json(self.first)
        self.second = _block_from_json(self.second)
        self.shortcut = kind_from_json(self.shortcut)
        a, b = self.first, self.second
        if not (isinstance(a, DecoderBlock) and isinstance(b, DecoderBlock)
                and self.shortcut is not None):
            raise ValueError("ShortcutDecoderBlock needs two DecoderBlocks "
                             "and a shortcut feed-forward kind")
        if a.mixer is None or a.ffn is None or b.mixer is None \
                or a.norm_placement != "pre" or b.norm_placement != "pre":
            raise ValueError(
                "ShortcutDecoderBlock: `first` is a pre-norm block with a "
                "mixer and a feed-forward (the shortcut reads its "
                "feed-forward's input), `second` a pre-norm block with a "
                "mixer")
        widths = {w for blk in (self, a, b) for w in (blk.n_in, blk.n_out)
                  if w}
        if len(widths) > 1:
            raise ValueError("ShortcutDecoderBlock keeps width: every "
                             "n_in and n_out equal")
        for blk in (self, a, b):      # one width, stated anywhere
            blk.n_in = blk.n_out = max(widths, default=0)

    @property
    def _d(self) -> int:
        return self.n_out

    @property
    def state(self) -> tuple:
        """The pair of the two mixers' cache kinds."""
        return (self.first.state, self.second.state)

    def output_type(self, it):
        return it

    def feed_forwards(self) -> list:
        return self.first.feed_forwards() + [self.shortcut] \
            + self.second.feed_forwards()

    def mixers(self) -> list:
        return self.first.mixers() + self.second.mixers()

    def init_params(self, key, it, dtype=jnp.float32):
        d = self._d
        ka, kb, ks = jax.random.split(key, 3)
        mk = lambda k, shape, fi, fo: self._winit(k, shape, fi, fo, dtype)
        p = {}
        for pre, blk, k in (("a_", self.first, ka), ("b_", self.second, kb)):
            p.update({pre + n: v
                      for n, v in blk.init_params(k, it, dtype).items()})
        p.update({"sc_" + n: v for n, v in
                  self.shortcut.init_params(ks, d, dtype, mk).items()})
        return p

    def compose(self, p, x, mix_a, mix_b, count_mask=None):
        """The layer with its two mixers given as callables `(mixer's
        parameters, its normed input) -> its output`, so that the whole
        forward and the decode engine's cached steps are one arithmetic.
        Returns (out, the shortcut feed-forward's counts under
        `count_mask`, or None); a routed `first.ffn` / `second.ffn` is
        not counted."""
        a, b = self.first, self.second
        pa, pb = sub(p, "a_"), sub(p, "b_")
        h1 = a.after_mixer(pa, x, mix_a(sub(pa, "mx_"), a.mixer_in(pa, x)))
        x1 = a.ffn_in(pa, h1)
        with jax.named_scope("moe.shortcut"):
            m, counts = self.shortcut.forward(sub(p, "sc_"), x1, count_mask)
        h2 = a.after_ffn(pa, h1, a.ffn.forward(sub(pa, "ff_"), x1)[0])
        h4, _ = b.finish(pb, h2,
                         mix_b(sub(pb, "mx_"), b.mixer_in(pb, h2)))
        return h4 + m, counts

    def forward(self, params, state, x, *, train=False, rng=None,
                mask=None):
        out, _ = self.compose(params, x, self.first.mixer.forward,
                              self.second.mixer.forward)
        return out, state

    def param_flags(self, name):
        if name.startswith("sc_"):
            return self.first.param_flags("ff_" + name[3:])
        return self.first.param_flags(name[2:])
