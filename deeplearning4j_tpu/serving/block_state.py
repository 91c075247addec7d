"""What the decode engine keeps per block between tokens, by kind.

The engine's compiled programs (`decode_step`, `decode_chunked`,
`prefill`, `prefill_chunk_fn`) walk the network's blocks and, for each,
hand the block's parameters, the hidden states and the block's cache to
the block's *state object*, which advances both. The plan says which
kind each block keeps (`GPTPlan.state_kinds`):

    KVPages         paged key/value pools `(P+1, Hkv, hd, page)` /
                    `(P+1, Hkv, page, hd)`: allocated by page, written
                    one position a token, read through the page table
                    (`TransformerBlock`, or a composed block whose mixer
                    is attention);
    RecurrentSlots  per-slot arrays of fixed size, as the mixer's
                    `state_shapes` declares them (a Mamba-2 mixer's
                    float32 state `(S, H, P, N)`, a gated delta-rule
                    mixer's `(S, d_k, H * d_v)`: any rank, the slot
                    axis first; and the convolution tail `(K-1, S,
                    Cw)`, tap-major): allocated by slot, OVERWRITTEN
                    when a slot is admitted (a one-shot prefill, or the
                    first chunk of a chunked one, starts from zeros),
                    carried from one prefill chunk to the next, left
                    alone by pad positions and by inactive slots, and
                    advanced in place by every decode step;
    Stateless       nothing: a composed block without a mixer (a
                    feed-forward under its norm and residual) reads no
                    cache and writes none, whatever the slot or the
                    position.

Each has `alloc()`, `decode(p, x, cache, d)`, `prefill(p, x, cache, d)`
and `prefill_chunk(p, x, cache, d)`, the last three returning
`(x, cache)`. `d` (a `SimpleNamespace`) carries what the program computed once for all blocks
(page ids, offsets, positions, the active mask, the slot); `env` the
numbers the engine fixed at build time and the tensor-parallel axis
(no function: the pool writers `_write_pages` / `_write_token` live
here, beside `KVPages`, and the speculative draft and verifier import
them from here too).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.decoder_block import sub
from deeplearning4j_tpu.nn.conf.layers import TransformerBlock
from deeplearning4j_tpu.serving.quantize import (
    _write_scale_pages,
    quantize_heads,
)


class RecurrentStateUnsupported(ValueError):
    """An engine feature was asked for that cannot hold a block with
    per-slot recurrent state yet (or a composed block at all). Raised
    when the engine is built, never from inside a step."""


class _DenseBlock:
    """`TransformerBlock`: LayerNorm + biased fused QKV in, out-projection
    + residual + MLP out (`models/transformer.py`'s helpers)."""

    def __init__(self, layer, env):
        from deeplearning4j_tpu.models import transformer as T

        self.layer, self.env, self._T = layer, env, T
        self.kv_heads = layer._kv_heads
        self.head_dim = layer.n_out // layer.n_heads

    def heads(self, p, x, positions):
        return self._T._block_heads(self.layer, p, x, positions,
                                    shard=self.env.tp_shard)

    def finish(self, p, x, att, d):
        att = self._T._block_out_proj(p, att, self.env.tp_axis)
        return self._T._block_ffn(self.layer, p, x + att,
                                  axis_name=self.env.tp_axis)

    def prefill_attention(self, q, k, v):
        return self._T._prefill_block_attention(self.layer, q, k, v)


class _ComposedAttention:
    """A `DecoderBlock` whose mixer is attention."""

    def __init__(self, layer, env):
        from deeplearning4j_tpu.models import transformer as T

        self.layer, self.env, self._T = layer, env, T
        self.kv_heads, self.head_dim = layer.mixer.kv_geometry(layer._d)

    def heads(self, p, x, positions):
        return self.layer.mixer.heads(sub(p, "mx_"),
                                      self.layer.mixer_in(p, x))

    def finish(self, p, x, att, d):
        return _finish_composed(self.layer, p, x,
                                self.layer.mixer.out(sub(p, "mx_"), att), d)

    def prefill_attention(self, q, k, v):
        return self._T._prefill_block_attention(self.layer.mixer, q, k, v)


def _finish_composed(layer, p, x, mixed, d):
    """The composed block after its mixer; a decode step's `d.counts`
    collects the feed-forward's per-expert counts over active slots."""
    out, counts = layer.finish(p, x, mixed, getattr(d, "count_mask", None))
    if counts is not None:
        d.counts.append(counts)
    return out


def _write_pages(kp_, vp_, kcol, vrow, wpids, woff, page):
    """Scatter one contiguous prefill span (1, Hkv, hd, W) /
    (1, Hkv, W, hd) into the pool pages `wpids`: floor(W/page) aligned
    full-page writes, then a partial tail (a non-pow-2 fallback bucket,
    or a sub-page chunk) at in-page offset `woff` — which is nonzero
    only in the W < page chunked case, where chunk-aligned pow-2
    offsets guarantee the span never straddles a page boundary. The
    speculative draft's prefill mirrors the exact same write discipline
    into its own pools."""
    W = kcol.shape[3]
    z = jnp.zeros((), jnp.int32)
    nfull = W // page
    for j in range(nfull):
        kp_ = jax.lax.dynamic_update_slice(
            kp_, kcol[..., j * page:(j + 1) * page], (wpids[j], z, z, z))
        vp_ = jax.lax.dynamic_update_slice(
            vp_, vrow[:, :, j * page:(j + 1) * page, :], (wpids[j], z, z, z))
    if W % page:
        kp_ = jax.lax.dynamic_update_slice(
            kp_, kcol[..., nfull * page:], (wpids[nfull], z, z, woff))
        vp_ = jax.lax.dynamic_update_slice(
            vp_, vrow[:, :, nfull * page:, :], (wpids[nfull], z, woff, z))
    return kp_, vp_


def _write_token(cache, k, v, pids, loff, scales=None):
    """Write ONE decode position per slot into a block's pools: `k`/`v`
    (S, Hkv, hd) land at in-page offset `loff[s]` of pool page
    `pids[s]` (inactive lanes arrive redirected to trash page 0).
    `cache` is the block's (K, V) pools, or (K, V, K-scale, V-scale)
    for int8 KV with `scales` = the (S, Hkv) per-head scale pair;
    returns the same tuple, written. On TPU the `paged_kv_write`
    kernel family updates the donated pools in place
    (`ops/pallas_paged_kv_write.py`), so neither `decode_step` nor the
    `decode_chunked` scan copies a pool; on CPU, under
    `DL4J_TPU_NO_PALLAS_PAGED_KV_WRITE`, or where the family's probe
    declined, the XLA scatter runs — bit-identical on every page but
    the trash page, at two whole-pool layout copies per pool per step
    on the TPU. The speculative draft and verifier write their pools
    the same way."""
    from deeplearning4j_tpu.ops.pallas_paged_kv_write import (
        paged_kv_write_or_none,
        scatter_kv_write,
    )

    args = (*cache[:2], k, v, pids, loff, *cache[2:], *(scales or ()))
    out = paged_kv_write_or_none(*args)
    if out is None:
        out = scatter_kv_write(*args)
    return out[:len(cache)]


class KVPages:
    kind = "kv"

    def __init__(self, layer, env):
        self.env = env
        self.block = _DenseBlock(layer, env) \
            if isinstance(layer, TransformerBlock) \
            else _ComposedAttention(layer, env)

    def alloc(self) -> tuple:
        env, b = self.env, self.block
        P, page, Hkv, hd = env.pool_pages, env.page, b.kv_heads, b.head_dim
        # +1: page 0 is the reserved trash page for masked writes
        if env.kv_quant:
            # int8 payload pools + f32 per-(head, position) scale
            # pools riding the same page table; zero scales never
            # dequantize stale garbage (0 * s == 0 either way), but
            # 1.0 keeps the trash page's dequant exactly 0.0 in one
            # multiply like a real all-zero write would
            return (jnp.zeros((P + 1, Hkv, hd, page), jnp.int8),
                    jnp.zeros((P + 1, Hkv, page, hd), jnp.int8),
                    jnp.ones((P + 1, Hkv, page), jnp.float32),
                    jnp.ones((P + 1, Hkv, page), jnp.float32))
        return (jnp.zeros((P + 1, Hkv, hd, page), env.cdt),
                jnp.zeros((P + 1, Hkv, page, hd), env.cdt))

    def bytes_per_slot(self) -> int:
        return 0  # pages are held by length, not by slot

    def decode(self, p, x, cache, d):
        from deeplearning4j_tpu.ops.attention import (
            paged_attention_step_auto,
        )

        env = self.env
        # same operand ranks as generate's decode ((S,1,d) heads,
        # squeezed) so XLA picks the same accumulation order —
        # argmax parity is a numerics property, not just a logic
        # one. positions: a per-slot column vector
        q, k, v = self.block.heads(p, x[:, None, :], d.pos[:, None])
        q, k, v = q[:, 0], k[:, 0], v[:, 0]
        with jax.named_scope("kv.write"):
            if env.kv_quant:
                # quantize the single-position (S, Hkv, hd)
                # write per head; the scale lands at the SAME
                # (page, head, offset) the payload does, so
                # trash-page redirection masks both together
                kq, ksc = quantize_heads(k)
                vq, vsc = quantize_heads(v)
                kp_, vp_, ks_, vs_ = _write_token(
                    cache, kq, vq, d.pids, d.loff, (ksc, vsc))
            else:
                ks_ = vs_ = None
                kp_, vp_ = _write_token(cache, k, v, d.pids, d.loff)
        # kernel-dispatched paged attention: on TPU the Pallas
        # kernel streams pages straight from the pool (no dense
        # gather transient — the decode path's dominant cache-
        # byte cost halves); on CPU/fallback the gather + dense
        # step reference numerics run unchanged
        with jax.named_scope("kv.attend"):
            att = paged_attention_step_auto(
                q, kp_, vp_, d.page_table, d.pos, d.active,
                k_scale=ks_, v_scale=vs_)
        x = self.block.finish(p, x, att, d)
        return x, ((kp_, vp_, ks_, vs_) if env.kv_quant else (kp_, vp_))

    def prefill(self, p, x, cache, d):
        env = self.env
        P = x.shape[1]
        q, k, v = self.block.heads(p, x, jnp.arange(P))
        att = self.block.prefill_attention(q, k, v)
        x = self.block.finish(p, x, att.reshape(1, P, -1), d)
        kcol = jnp.transpose(k, (0, 2, 3, 1))   # (1, Hkv, hd, P)
        vrow = jnp.transpose(v, (0, 2, 1, 3))   # (1, Hkv, P, hd)
        z0 = jnp.zeros((), jnp.int32)
        with jax.named_scope("kv.write"):
            if env.kv_quant:
                # the prompt span quantizes per (head,
                # position): abs-max over the hd axis of each
                # lane-last layout
                kp_, vp_, ks_, vs_ = cache
                kcol, kscol = quantize_heads(kcol, axis=2)
                vrow, vscol = quantize_heads(vrow, axis=3)
                ks_ = _write_scale_pages(ks_, kscol, d.wpids, z0, env.page)
                vs_ = _write_scale_pages(vs_, vscol, d.wpids, z0, env.page)
                kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, d.wpids, z0,
                                        env.page)
                return x, (kp_, vp_, ks_, vs_)
            kp_, vp_ = cache
            kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, d.wpids, z0,
                                    env.page)
            return x, (kp_, vp_)

    def prefill_chunk(self, p, x, cache, d):
        from deeplearning4j_tpu.ops.attention import (
            paged_attention_chunk_auto,
        )

        env = self.env
        Cw = x.shape[1]
        q, k, v = self.block.heads(p, x, d.qpos)
        kcol = jnp.transpose(k, (0, 2, 3, 1))   # (1, Hkv, hd, C)
        vrow = jnp.transpose(v, (0, 2, 1, 3))   # (1, Hkv, C, hd)
        with jax.named_scope("kv.write"):
            if env.kv_quant:
                kp_, vp_, ks_, vs_ = cache
                kcol, kscol = quantize_heads(kcol, axis=2)
                vrow, vscol = quantize_heads(vrow, axis=3)
                ks_ = _write_scale_pages(ks_, kscol, d.wpids, d.woff,
                                         env.page)
                vs_ = _write_scale_pages(vs_, vscol, d.wpids, d.woff,
                                         env.page)
            else:
                kp_, vp_ = cache
                ks_ = vs_ = None
            kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, d.wpids, d.woff,
                                    env.page)
        # attend AFTER the write: the chunk attends to itself
        # through the cache, which is exactly causal with the
        # <= qpos mask; the auto path walks the slot's page row
        # in place on TPU and falls back to gather + chunk
        # (`_prefill_chunk_block_attention` numerics) elsewhere
        with jax.named_scope("kv.attend"):
            att = paged_attention_chunk_auto(
                q, kp_, vp_, d.page_row[None], d.off[None],
                k_scale=ks_, v_scale=vs_)
        x = self.block.finish(p, x, att.reshape(1, Cw, -1), d)
        return x, ((kp_, vp_, ks_, vs_) if env.kv_quant else (kp_, vp_))


class RecurrentSlots:
    kind = "recurrent"

    def __init__(self, layer, env):
        self.layer, self.env, self.mixer = layer, env, layer.mixer

    def alloc(self) -> tuple:
        return tuple(jnp.zeros(shape, dtype) for shape, dtype in
                     self.mixer.state_shapes(self.env.n_slots,
                                             self.env.cdt))

    def bytes_per_slot(self) -> int:
        return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
                   for shape, dtype in
                   self.mixer.state_shapes(1, self.env.cdt))

    def decode(self, p, x, cache, d):
        h, tail = cache
        y, h, tail = self.mixer.step(
            sub(p, "mx_"), self.layer.mixer_in(p, x), h, tail, d.active)
        return _finish_composed(self.layer, p, x, y, d), (h, tail)

    def _store(self, cache, h1, tail1, slot):
        z = jnp.zeros((), jnp.int32)
        h = jax.lax.dynamic_update_slice(
            cache[0], h1, (slot,) + (z,) * (cache[0].ndim - 1))
        tail = jax.lax.dynamic_update_slice(
            cache[1], jnp.swapaxes(tail1, 0, 1).astype(cache[1].dtype),
            (z, slot, z))
        return h, tail

    def prefill(self, p, x, cache, d):
        # from zeros: whatever the slot's last tenant left is
        # overwritten, not accumulated into; pad positions past t0 do
        # not move the state
        y, h1, tail1 = self.mixer.scan(sub(p, "mx_"),
                                       self.layer.mixer_in(p, x),
                                       n_valid=d.t0)
        x = _finish_composed(self.layer, p, x, y, d)
        return x, self._store(cache, h1, tail1, d.slot)

    def prefill_chunk(self, p, x, cache, d):
        Cw = x.shape[1]
        z = jnp.zeros((), jnp.int32)
        first = d.off == 0
        h0 = jax.lax.dynamic_slice(
            cache[0], (d.slot,) + (z,) * (cache[0].ndim - 1),
            (1,) + cache[0].shape[1:])
        tail0 = jnp.swapaxes(jax.lax.dynamic_slice(
            cache[1], (z, d.slot, z),
            (cache[1].shape[0], 1, cache[1].shape[2])), 0, 1)
        h0 = jnp.where(first, jnp.zeros_like(h0), h0)
        tail0 = jnp.where(first, jnp.zeros_like(tail0), tail0)
        y, h1, tail1 = self.mixer.scan(
            sub(p, "mx_"), self.layer.mixer_in(p, x), h0, tail0,
            n_valid=jnp.clip(d.t0 - d.off, 0, Cw))
        x = _finish_composed(self.layer, p, x, y, d)
        return x, self._store(cache, h1, tail1, d.slot)


class Stateless:
    kind = "none"

    def __init__(self, layer, env):
        self.layer = layer

    def alloc(self) -> tuple:
        return ()

    def bytes_per_slot(self) -> int:
        return 0

    def decode(self, p, x, cache, d):
        return _finish_composed(self.layer, p, x, None, d), cache

    prefill = prefill_chunk = decode


_KINDS = {"kv": KVPages, "recurrent": RecurrentSlots, "none": Stateless}


def block_states(plan, env) -> list:
    """One state object per block of the plan, by the kind it declares."""
    return [_KINDS[kind](plan.layers[i], env)
            for kind, i in zip(plan.state_kinds(), plan.block_is)]


def routed_ffns(plan) -> list:
    """The routed-expert feed-forward kinds of the plan's blocks."""
    from deeplearning4j_tpu.nn.conf.decoder_block import MoEFeedForward

    return [plan.layers[i].ffn for i in plan.block_is
            if isinstance(getattr(plan.layers[i], "ffn", None),
                          MoEFeedForward)]


def moe_held(plan) -> int:
    """How many routed experts each routed block holds (0: the net has
    none; the blocks that route must agree, since the step returns one
    count vector; blocks that do not route add nothing to it)."""
    held = {ffn.held[1] for ffn in routed_ffns(plan)}
    if len(held) > 1:
        raise ValueError(
            f"routed blocks hold different numbers of experts "
            f"{sorted(held)}: the decode step returns one per-expert "
            "count vector")
    return held.pop() if held else 0
