"""A decoder block composed of kinds: a sequence mixer, a feed-forward
and a norm, each a small config object of its own.

    h <- h + r * mixer(norm(h));  h <- h + r * ffn(norm(h))

`TransformerBlock` is one fixed composition (LayerNorm, biased
multi-head attention, dense MLP) and stays as it is; a model whose block
differs in kind (a state-space mixer, routed experts, RMSNorm) composes
a `DecoderBlock` from the kinds here instead of adding flags there
(ROADMAP D6). Each mixer kind declares the cache state a decode engine
must keep for it, `state`:

    "kv"         paged key/value pools, one position a token
                 (`AttentionMixer`; `kv_geometry` gives heads and width)
    "recurrent"  per-slot arrays of fixed size, overwritten in place
                 (`Mamba2Mixer`; `state_shapes` gives them)

and `serving/block_state.py` turns that declaration into the engine's
allocation and its prefill / decode steps. A kind serialises as
`{"kind": <name>, ...fields}` inside the layer's JSON.

Parameters of a block are one flat dict, as every layer's: the mixer's
under `mx_`, the feed-forward's under `ff_`, the norms' `n1_w`, `n2_w`.
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
from deeplearning4j_tpu.ops import ssm

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
    return cls(**{k: tuple(v) if isinstance(v, list) else v
                  for k, v in d.items() if k in names})


class _Kind:
    def to_json(self) -> dict:
        out = {"kind": self.KIND}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            out[f.name] = list(v) if isinstance(v, tuple) else v
        return out


for _field in ("mixer", "ffn", "norm"):
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


# -------------------------------------------------------------- mixer kinds
@_kind
@dataclass(frozen=True)
class AttentionMixer(_Kind):
    """Causal grouped-query attention without biases and without
    positional encoding; `scale` multiplies the scores (None: the usual
    1 / sqrt(head_dim)). Keeps paged K/V."""
    KIND = "attention"
    state = "kv"
    n_heads: int = 4
    n_kv_heads: int = 0          # 0: as many as n_heads
    scale: Optional[float] = None

    @property
    def _kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def kv_geometry(self, d: int) -> Tuple[int, int]:
        return self._kv_heads, d // self.n_heads

    def init_params(self, key, d: int, dtype, winit) -> dict:
        hd = d // self.n_heads
        qw, kvw = self.n_heads * hd, self._kv_heads * hd
        k1, k2 = jax.random.split(key)
        return {"Wqkv": winit(k1, (d, qw + 2 * kvw), d, qw + 2 * kvw),
                "Wo": winit(k2, (qw, d), qw, d)}

    def heads(self, p, x):
        """(..., d) -> q (..., H, hd), k and v (..., Hkv, hd). The
        attention paths all divide the scores by sqrt(hd); `q` is
        scaled here so that their product comes out at `scale`."""
        hd = p["Wo"].shape[0] // self.n_heads
        qw, kvw = self.n_heads * hd, self._kv_heads * hd
        with jax.named_scope("attn.qkv"):
            qkv = x @ p["Wqkv"]
            q = qkv[..., :qw].reshape(*x.shape[:-1], self.n_heads, hd)
            k = qkv[..., qw:qw + kvw].reshape(*x.shape[:-1],
                                              self._kv_heads, hd)
            v = qkv[..., qw + kvw:].reshape(*x.shape[:-1],
                                            self._kv_heads, hd)
            if self.scale is not None:
                q = q * jnp.asarray(self.scale * math.sqrt(hd), q.dtype)
        return q, k, v

    def out(self, p, att):
        with jax.named_scope("attn.out"):
            return att @ p["Wo"]

    def forward(self, p, x):
        from deeplearning4j_tpu.ops.attention import multi_head_attention

        q, k, v = self.heads(p, x)
        with jax.named_scope("attn.core"):
            att = multi_head_attention(q, k, v, causal=True,
                                       block_size=_FLASH_FROM)
        return self.out(p, att.reshape(*x.shape[:-1], -1))


@_kind
@dataclass(frozen=True)
class Mamba2Mixer(_Kind):
    """Mamba-2 (arXiv:2405.21060) as Hugging Face's `GraniteMoeHybrid`
    and `Mamba2` layers write it, one B/C group: in-projection to
    [z | xBC | dt], depthwise causal convolution and silu over xBC, the
    selective state-space recurrence of `ops/ssm.py`, a gated RMSNorm
    over the whole inner width, out-projection. Keeps a per-slot
    recurrent state (float32) and the convolution's last inputs."""
    KIND = "mamba2"
    state = "recurrent"
    n_heads: int = 8
    head_dim: int = 16
    d_state: int = 16
    d_conv: int = 4
    chunk: int = 256
    eps: float = 1e-5

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.d_state

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
        # dt_bias: inverse softplus of a step drawn log-uniform in
        # [1e-3, 1e-1]; A_log: log of uniform [1, 16] (Mamba-2's init)
        dt = jnp.exp(jax.random.uniform(k[3], (H,)) *
                     (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {"Win": winit(k[0], (d, width), d, width),
                "conv_w": (jax.random.normal(k[1], (cw, self.d_conv))
                           / math.sqrt(self.d_conv)).astype(dtype),
                "conv_b": jnp.zeros((cw,), dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "A_log": jnp.log(jax.random.uniform(
                    k[4], (H,), minval=1.0, maxval=16.0)).astype(dtype),
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

    def _finish(self, p, y, z):
        y = rms_norm(y * jax.nn.silu(z), p["norm_w"], self.eps)
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
        di, N = self.d_inner, self.d_state
        keep = None if n_valid is None else \
            (jnp.arange(T) < n_valid)[None, :, None]
        with jax.named_scope("ssm.scan"):
            y, h = ssm.ssd_chunked(
                xbc[..., :di].reshape(B, T, self.n_heads, self.head_dim),
                self._dt(p, dt_raw, keep),
                -jnp.exp(p["A_log"].astype(jnp.float32)),
                xbc[..., di:di + N], xbc[..., di + N:], p["D"],
                chunk=self.chunk, h0=h0)
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
        di, N = self.d_inner, self.d_state
        keep = None if active is None else active[:, None]
        with jax.named_scope("ssm.step"):
            y, h = ssm.ssm_step(
                h, xbc[..., :di].reshape(S, self.n_heads, self.head_dim),
                self._dt(p, dt_raw, keep),
                -jnp.exp(p["A_log"].astype(jnp.float32)),
                xbc[..., di:di + N], xbc[..., di + N:], p["D"])
        return self._finish(p, y.reshape(S, di), z), h, \
            new_tail.astype(tail.dtype)

    def forward(self, p, x):
        return self.scan(p, x)[0]


# ------------------------------------------------------- feed-forward kinds
@_kind
@dataclass(frozen=True)
class MoEFeedForward(_Kind):
    """`n_experts` routed gated MLPs of width `expert_width`, `top_k` a
    token, gates a softmax over the chosen logits, no capacity and no
    token dropped; plus one shared gated MLP of width `shared_width`
    (0: none) added ungated. `experts_held = (first, count)` is the
    share of the experts whose weights this layer holds and computes
    (None: all of them): it routes over all `n_experts` and leaves the
    absent experts' part of the sum out (`parallel/experts.py`)."""
    KIND = "moe"
    n_experts: int = 8
    top_k: int = 2
    expert_width: int = 64
    shared_width: int = 0
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held) if self.experts_held \
            else (0, self.n_experts)

    def init_params(self, key, d: int, dtype, winit) -> dict:
        E, f, s = self.held[1], self.expert_width, self.shared_width
        k = jax.random.split(key, 7)
        p = {"router": winit(k[0], (d, self.n_experts), d, self.n_experts),
             "Wg": winit(k[1], (E, d, f), d, f),
             "Wu": winit(k[2], (E, d, f), d, f),
             "Wd": winit(k[3], (E, f, d), f, d)}
        if s:
            p.update({"sWg": winit(k[4], (d, s), d, s),
                      "sWu": winit(k[5], (d, s), d, s),
                      "sWd": winit(k[6], (s, d), s, d)})
        return p

    def forward(self, p, x, count_mask=None):
        """`x` (..., d) -> (y, counts): with `count_mask` (one bool a
        token), how many masked-in tokens chose each held expert; else
        None."""
        from deeplearning4j_tpu.parallel.experts import (
            dropless_moe,
            gated_mlp,
        )

        flat = x.reshape(-1, x.shape[-1])
        y, counts = dropless_moe(
            flat, p["router"], p["Wg"], p["Wu"], p["Wd"], top_k=self.top_k,
            experts_held=self.held, count_mask=count_mask)
        if self.shared_width:
            with jax.named_scope("moe.shared"):
                y = y + gated_mlp(flat, p["sWg"], p["sWu"], p["sWd"])
        return y.reshape(x.shape), counts


# ---------------------------------------------------------------- the layer
@register_layer
@dataclass
class DecoderBlock(FeedForwardLayer):
    """One pre-norm decoder block composed of a mixer kind, a
    feed-forward kind and a norm kind (module docstring);
    `residual_multiplier` scales both branches before they are added."""

    TYPE = "decoder_block"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    mixer: object = None
    ffn: object = None
    norm: object = None
    residual_multiplier: float = 1.0

    def __post_init__(self):
        self.mixer = kind_from_json(self.mixer)
        self.ffn = kind_from_json(self.ffn)
        self.norm = kind_from_json(self.norm) or RMSNorm()
        if self.mixer is None or self.ffn is None:
            raise ValueError("DecoderBlock needs a mixer kind and a "
                             "feed-forward kind")
        if self.n_in and self.n_out and self.n_in != self.n_out:
            raise ValueError("DecoderBlock keeps width: n_in == n_out")

    @property
    def _d(self) -> int:
        return self.n_out or self.n_in

    def output_type(self, it):
        return it

    def init_params(self, key, it, dtype=jnp.float32):
        d = self._d
        k1, k2 = jax.random.split(key)
        mk = lambda k, shape, fi, fo: self._winit(k, shape, fi, fo, dtype)
        p = {"n1_w": self.norm.init_params(d, dtype)["w"],
             "n2_w": self.norm.init_params(d, dtype)["w"]}
        p.update({"mx_" + n: v for n, v in
                  self.mixer.init_params(k1, d, dtype, mk).items()})
        p.update({"ff_" + n: v for n, v in
                  self.ffn.init_params(k2, d, dtype, mk).items()})
        return p

    def norm1(self, p, x):
        with jax.named_scope("norm1"):
            return self.norm.apply(p["n1_w"], x)

    def finish(self, p, x, mixed, count_mask=None):
        """The block from the mixer's output on: first residual, norm,
        feed-forward, second residual. Returns (h, the feed-forward's
        counts under `count_mask`, or None)."""
        r = jnp.asarray(self.residual_multiplier, x.dtype)
        h = x + r * mixed
        with jax.named_scope("norm2"):
            u = self.norm.apply(p["n2_w"], h)
        f, counts = self.ffn.forward(sub(p, "ff_"), u, count_mask)
        return h + r * f, counts

    def forward(self, params, state, x, *, train=False, rng=None,
                mask=None):
        mixed = self.mixer.forward(sub(params, "mx_"),
                                   self.norm1(params, x))
        return self.finish(params, x, mixed)[0], state

    def param_flags(self, name):
        vector = name in ("n1_w", "n2_w", "mx_norm_w", "mx_conv_b",
                          "mx_dt_bias", "mx_A_log", "mx_D")
        return {"is_bias": name in ("mx_conv_b", "mx_dt_bias"),
                "regularizable": not vector}
