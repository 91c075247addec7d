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
    WindowPages     the K/V pools of an attention mixer that reads a
                    WINDOW of its context: pools of their own of
                    `n_slots * ring_pages` pages (+ the trash page), a
                    slot's pages a ring of `ring_pages` entries in the
                    page pool's second table (`serving/page_pool.py`);
                    `KVPages`' writers with the ring's page ids, the
                    windowed paged attention to read; a prompt's whole
                    bucket attends in flight, so one longer than the
                    ring keeps its last pages;
    RecurrentSlots  per-slot arrays of fixed size, as the mixer's
                    `state_shapes` declares them (a Mamba-2 mixer's
                    float32 state `(S, H, P, N)`, a gated delta-rule
                    mixer's `(S, d_k, H * d_v)`, one decay a head or
                    one a key channel: any rank, the slot
                    axis first; and the convolution tail `(K-1, S,
                    Cw)`, tap-major): allocated by slot, OVERWRITTEN
                    when a slot is admitted (a one-shot prefill, or the
                    first chunk of a chunked one, starts from zeros),
                    carried from one prefill chunk to the next, left
                    alone by pad positions and by inactive slots, and
                    advanced in place by every decode step;
    LatentPages     ONE paged pool of latents a sub-layer, `(P+1,
                    kv_rank + rope, page)` (`ops/pallas_mla_attend.py`):
                    a position's cache is one vector for all heads, the
                    normed key/value latent and the turned rope key
                    (`LatentAttentionMixer`); allocated by page from the
                    same page table as K/V pools, written one position a
                    token, read through the page table. Expanded
                    attention over the prompt in `prefill`, a chunk's
                    absorbed queries against the cached latents in
                    `prefill_chunk`, the absorbed step in `decode`;
    ShortcutPair    a `ShortcutDecoderBlock`'s two mixers' states, each
                    with its own cache: the block's cache is the pair;
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

What the engine needs to know ABOUT a kind it asks here, and names none
itself: each class says which `stats()` keys count it (`blocks_key`,
`token_bytes_key`) and which engine features it cannot hold, in what
words (`refuses`); K/V pages copy themselves between pools and the host
(`KVPages.read_pages` / `write_pages`, the first half of ROADMAP M5's
`export` / `import_` contract: a kind without them cannot be moved).
`refused` and `describe` sum that up for a plan and its built states, and
`RoutingAccount` owns a plan's routed-expert counts from the step's
blocks to the `moe_*` keys of `stats()`.
"""
from __future__ import annotations

import math
import threading
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.decoder_block import sub
from deeplearning4j_tpu.nn.conf.layers import TransformerBlock
from deeplearning4j_tpu.serving.quantize import (
    _write_scale_pages,
    kv_bytes_per_token,
    quantize_heads,
)

# the engine features a kind may say it cannot hold (`refuses`): the
# ones that keep, requantize or carry away what a block caches
_CACHE_FEATURES = ("prefix_cache", "quantize_kv", "role")


class RecurrentStateUnsupported(ValueError):
    """An engine feature was asked for that cannot hold a block with
    per-slot recurrent state or latent pages yet (or a composed block at
    all). Raised when the engine is built, never from inside a step."""


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
        # a mixer without rotary reads no position
        return self.layer.mixer.heads(sub(p, "mx_"),
                                      self.layer.mixer_in(p, x), positions)

    def finish(self, p, x, att, d):
        return _finish_composed(self.layer, p, x,
                                self.layer.mixer.out(sub(p, "mx_"), att), d)

    def prefill_attention(self, q, k, v):
        """A bucket at or under `_FLASH_FROM` keys without a window: the
        parent's program, the (H, P, P) scores in one array. A window,
        or a longer bucket (128 heads at 4,096 keys: 8.6 GB of scores):
        `ops.attention.grouped_causal_attention`."""
        from deeplearning4j_tpu.nn.conf.decoder_block import _FLASH_FROM
        from deeplearning4j_tpu.ops import attention

        mixer = self.layer.mixer
        if q.shape[1] > _FLASH_FROM or mixer.window is not None:
            with jax.named_scope("attn.core"):
                return attention.grouped_causal_attention(
                    q, k, v, window=mixer.window, one_array_to=_FLASH_FROM)
        return self._T._prefill_block_attention(mixer, q, k, v)


def _finish_composed(layer, p, x, mixed, d):
    """The composed block after its mixer; a decode step's `d.counts`
    collects the feed-forward's per-expert counts over active slots."""
    out, counts = layer.finish(p, x, mixed, getattr(d, "count_mask", None))
    RoutingAccount.collect(d, counts)
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


class _Kind:
    """What a kind says of itself where it has nothing to say."""
    token_bytes_key = None  # the `stats()` key its bytes a token add to
    refuses = {}  # engine feature -> what of this kind it cannot hold

    def bytes_per_slot(self) -> int:
        return 0  # pages are held by length, not by slot


class _ByPhase:
    """`decode`, `prefill` and `prefill_chunk` as one `_block(which, p, x,
    cache, d)`: the state objects whose three programs differ only in
    which of a mixer's `mix_<which>` they run."""

    def decode(self, p, x, cache, d):
        return self._block("decode", p, x, cache, d)

    def prefill(self, p, x, cache, d):
        return self._block("prefill", p, x, cache, d)

    def prefill_chunk(self, p, x, cache, d):
        return self._block("prefill_chunk", p, x, cache, d)


class KVPages(_Kind):
    kind = "kv"
    blocks_key, token_bytes_key = "kv_blocks", "kv_bytes_per_token"
    window = None  # positions a query reads back (None: its whole context)

    def __init__(self, layer, env):
        self.env = env
        self.block = _DenseBlock(layer, env) \
            if isinstance(layer, TransformerBlock) \
            else _ComposedAttention(layer, env)
        # a hand-off names the pools so; int8 pools bring their scales
        self.names = ("k", "v", "ks", "vs") if env.kv_quant else ("k", "v")

    def _pool_pages(self) -> int:
        return self.env.pool_pages

    def _ids(self, d, name: str):
        """The step's page ids and tables for this kind's class of page:
        `pids`, `page_table`, `wpids`, `page_row` of `d`."""
        return getattr(d, name)

    def alloc(self) -> tuple:
        env, b = self.env, self.block
        P, page, Hkv, hd = self._pool_pages(), env.page, b.kv_heads, \
            b.head_dim
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

    def bytes_per_token(self) -> int:
        return kv_bytes_per_token(
            [(self.block.kv_heads, self.block.head_dim)], self.env.kv_quant,
            jnp.dtype(self.env.cdt).itemsize)

    def read_pages(self, cache, page_ids) -> dict:
        """The pool pages `page_ids` of this block as host arrays, by
        name."""
        return {name: np.asarray(jax.device_get(arr[page_ids]))
                for name, arr in zip(self.names, cache)}

    def write_pages(self, cache, page_ids, block: dict) -> tuple:
        """The reverse: `block` scattered into the pool pages `page_ids`
        (eager `.at[].set`, not a donated dispatch: a failure leaves the
        pools valid); the block's cache, written."""
        return tuple(
            arr.at[page_ids].set(jnp.asarray(np.asarray(block[name])))
            for name, arr in zip(self.names, cache))

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
                    cache, kq, vq, self._ids(d, "pids"), d.loff,
                    (ksc, vsc))
            else:
                ks_ = vs_ = None
                kp_, vp_ = _write_token(cache, k, v, self._ids(d, "pids"),
                                        d.loff)
        # kernel-dispatched paged attention: on TPU the Pallas
        # kernel streams pages straight from the pool (no dense
        # gather transient — the decode path's dominant cache-
        # byte cost halves); on CPU/fallback the gather + dense
        # step reference numerics run unchanged
        with jax.named_scope("kv.attend"):
            att = paged_attention_step_auto(
                q, kp_, vp_, self._ids(d, "page_table"), d.pos, d.active,
                k_scale=ks_, v_scale=vs_, window=self.window)
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
                wpids = self._ids(d, "wpids")
                ks_ = _write_scale_pages(ks_, kscol, wpids, z0, env.page)
                vs_ = _write_scale_pages(vs_, vscol, wpids, z0, env.page)
                kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, wpids, z0,
                                        env.page)
                return x, (kp_, vp_, ks_, vs_)
            kp_, vp_ = cache
            kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow,
                                    self._ids(d, "wpids"), z0, env.page)
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
        wpids = self._ids(d, "wpids")
        with jax.named_scope("kv.write"):
            if env.kv_quant:
                kp_, vp_, ks_, vs_ = cache
                kcol, kscol = quantize_heads(kcol, axis=2)
                vrow, vscol = quantize_heads(vrow, axis=3)
                ks_ = _write_scale_pages(ks_, kscol, wpids, d.woff,
                                         env.page)
                vs_ = _write_scale_pages(vs_, vscol, wpids, d.woff,
                                         env.page)
            else:
                kp_, vp_ = cache
                ks_ = vs_ = None
            kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, wpids, d.woff,
                                    env.page)
        # attend AFTER the write: the chunk attends to itself
        # through the cache, which is exactly causal with the
        # <= qpos mask; the auto path walks the slot's page row
        # in place on TPU and falls back to gather + chunk
        # (`_prefill_chunk_block_attention` numerics) elsewhere
        with jax.named_scope("kv.attend"):
            att = paged_attention_chunk_auto(
                q, kp_, vp_, self._ids(d, "page_row")[None], d.off[None],
                k_scale=ks_, v_scale=vs_, window=self.window)
        x = self.block.finish(p, x, att.reshape(1, Cw, -1), d)
        return x, ((kp_, vp_, ks_, vs_) if env.kv_quant else (kp_, vp_))


class WindowPages(_ByPhase, KVPages):
    """A block whose attention reads the last `window` positions: K/V
    pools of its own class of page, `n_slots * ring_pages` of them, a
    slot's a ring (module docstring). It IS `KVPages` with the ring's
    ids and tables (`d.ring_pids`, `d.ring_page_table`, `d.ring_wpids`,
    `d.ring_page_row`: the page pool's second table, handed by the programs)
    and the window handed to the paged attention."""
    kind = "window"
    blocks_key, token_bytes_key = "window_blocks", None
    refuses = dict.fromkeys(
        _CACHE_FEATURES, "a window layer's ring of pages")

    def __init__(self, layer, env):
        super().__init__(layer, env)
        self.window = layer.mixer.window

    def _pool_pages(self) -> int:
        return self.env.n_slots * self.env.ring_pages

    def _ids(self, d, name: str):
        return getattr(d, "ring_" + name)

    def ring_bytes_per_slot(self) -> int:
        """A slot's ring in this block, whatever its request's length."""
        return self.env.ring_pages * self.env.page * self.bytes_per_token()

    # its pages are not `read_pages` / `write_pages`' to move: a ring's
    # ids mean nothing in another engine's table
    read_pages = write_pages = None

    def _block(self, which, p, x, cache, d):
        with jax.named_scope("kv.window"):
            return getattr(KVPages, which)(self, p, x, cache, d)


def ring_pages(plan, page: int, prefill_chunk: int) -> int:
    """Entries of a slot's ring of window pages: the pages of the
    plan's widest window, and those a prefill chunk (a decode step: one)
    writes ahead of the window it still reads; 0 where no block reads a
    window."""
    windows = [m.window for i in plan.block_is
               for m in getattr(plan.layers[i], "mixers", list)()
               if getattr(m, "window", None) is not None]
    if not windows:
        return 0
    return -(-max(windows) // page) + max(1, -(-prefill_chunk // page))


class RecurrentSlots(_Kind):
    kind, blocks_key = "recurrent", "recurrent_blocks"
    refuses = dict.fromkeys(_CACHE_FEATURES, "the recurrent state")

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


class LatentPages(_ByPhase, _Kind):
    """A block whose mixer is latent attention. The mixing itself is
    `mix_decode` / `mix_prefill` / `mix_prefill_chunk`, `(mixer's
    parameters, its normed input, cache, d) -> (mixed, cache)`, so that a
    block of two mixers (`ShortcutPair`) runs each through its own."""
    kind = "latent"
    blocks_key, token_bytes_key = "latent_blocks", "latent_bytes_per_token"
    refuses = dict.fromkeys(_CACHE_FEATURES, "latent pages")

    def __init__(self, layer, env):
        self.layer, self.env, self.mixer = layer, env, layer.mixer
        self.kv_rank, self.rope = self.mixer.latent_geometry()

    def alloc(self) -> tuple:
        env = self.env
        # +1: page 0 is the reserved trash page for masked writes
        return (jnp.zeros((env.pool_pages + 1, self.kv_rank + self.rope,
                           env.page), env.cdt),)

    def bytes_per_token(self) -> int:
        return (self.kv_rank + self.rope) * jnp.dtype(self.env.cdt).itemsize

    def _write_span(self, pool, latent, wpids, woff):
        """One contiguous prefill span (1, W, R) into the pool pages
        `wpids` (`_write_pages`' discipline: aligned full pages, then a
        partial tail at in-page offset `woff`)."""
        page = self.env.page
        cols = jnp.swapaxes(latent, 1, 2).astype(pool.dtype)  # (1, R, W)
        W = cols.shape[2]
        z = jnp.zeros((), jnp.int32)
        nfull = W // page
        for j in range(nfull):
            pool = jax.lax.dynamic_update_slice(
                pool, cols[..., j * page:(j + 1) * page], (wpids[j], z, z))
        if W % page:
            pool = jax.lax.dynamic_update_slice(
                pool, cols[..., nfull * page:], (wpids[nfull], z, woff))
        return pool

    def mix_decode(self, mp, u, cache, d):
        from deeplearning4j_tpu.ops import pallas_mla_attend as mla

        m = self.mixer
        q_n, q_r, latent = m.project(mp, u[:, None, :], d.pos[:, None])
        with jax.named_scope("mla.write"):
            pool = mla.write(cache[0], latent[:, 0], d.pids, d.loff)
        q_abs = m.absorb(mp, q_n[:, 0], q_r[:, 0])
        with jax.named_scope("mla.attend"):
            att = mla.attend(q_abs, pool, d.page_table, d.pos, d.active,
                             kv_rank=self.kv_rank, sm_scale=m.sm_scale)
        return m.out(mp, att, u), (pool,)

    def mix_prefill(self, mp, u, cache, d):
        m = self.mixer
        q_n, q_r, latent = m.project(mp, u, jnp.arange(u.shape[1]))
        mixed = m.attend_expanded(mp, q_n, q_r, latent, n_valid=d.t0, x=u)
        with jax.named_scope("mla.write"):
            pool = self._write_span(cache[0], latent, d.wpids,
                                    jnp.zeros((), jnp.int32))
        return mixed, (pool,)

    def mix_prefill_chunk(self, mp, u, cache, d):
        m = self.mixer
        q_n, q_r, latent = m.project(mp, u, d.qpos)
        with jax.named_scope("mla.write"):
            pool = self._write_span(cache[0], latent, d.wpids, d.woff)
        # attend AFTER the write: the chunk attends to itself through
        # the cache, which is exactly causal with the <= qpos mask
        from deeplearning4j_tpu.ops.pallas_mla_attend import gather_latents

        att = m.attend_latents(m.absorb(mp, q_n, q_r),
                               gather_latents(pool, d.page_row[None]),
                               d.qpos[None])
        return m.out(mp, att, u), (pool,)

    def _block(self, which, p, x, cache, d):
        mixed, cache = getattr(self, "mix_" + which)(
            sub(p, "mx_"), self.layer.mixer_in(p, x), cache, d)
        return _finish_composed(self.layer, p, x, mixed, d), cache


class ShortcutPair(_ByPhase):
    """A `ShortcutDecoderBlock`: its two mixers' states, each with a
    cache of its own; the block's cache is the pair of theirs."""

    def __init__(self, layer, env):
        self.layer = layer
        kinds = layer.state
        if set(kinds) != {"latent"}:
            raise RecurrentStateUnsupported(
                f"a shortcut block's mixers keep {kinds}: only latent "
                "pages are held for it yet")
        self.parts = (LatentPages(layer.first, env),
                      LatentPages(layer.second, env))
        self.kind = kinds

    def alloc(self) -> tuple:
        return tuple(part.alloc() for part in self.parts)

    def bytes_per_slot(self) -> int:
        return sum(part.bytes_per_slot() for part in self.parts)

    def _block(self, which, p, x, cache, d):
        new = [None, None]

        def mix(i):
            def run(mp, u):
                mixed, new[i] = getattr(self.parts[i], "mix_" + which)(
                    mp, u, cache[i], d)
                return mixed
            return run

        out, counts = self.layer.compose(
            p, x, mix(0), mix(1), getattr(d, "count_mask", None))
        RoutingAccount.collect(d, counts)
        return out, tuple(new)


class Stateless(_Kind):
    kind, blocks_key = "none", "stateless_blocks"

    def __init__(self, layer, env):
        self.layer = layer

    def alloc(self) -> tuple:
        return ()

    def read_pages(self, cache, page_ids) -> dict:
        return {}  # nothing kept: nothing to move

    def write_pages(self, cache, page_ids, block: dict) -> tuple:
        return cache

    def decode(self, p, x, cache, d):
        return _finish_composed(self.layer, p, x, None, d), cache

    prefill = prefill_chunk = decode


_KINDS = {"kv": KVPages, "recurrent": RecurrentSlots, "none": Stateless,
          "latent": LatentPages, "window": WindowPages}


def block_states(plan, env) -> list:
    """One state object per block of the plan, by the kind it declares
    (a pair of kinds: the shortcut block's)."""
    return [(ShortcutPair if isinstance(kind, tuple) else _KINDS[kind])(
                plan.layers[i], env)
            for kind, i in zip(plan.state_kinds(), plan.block_is)]


def refused(plan, asked: dict) -> list:
    """What the kinds of `plan`'s blocks cannot hold of the engine
    features `asked` (feature -> the engine's words for it, with
    `{what}` where the kind's own go), as the phrases of a typed
    refusal; kind by kind, each in the order asked."""
    kinds = {k for kind in plan.state_kinds()
             for k in (kind if isinstance(kind, tuple) else (kind,))}
    return [words.format(what=cls.refuses[feature])
            for name, cls in _KINDS.items() if name in kinds
            for feature, words in asked.items() if feature in cls.refuses]


def describe(states, env) -> SimpleNamespace:
    """What the built `states` say of themselves: `counters`, the keys
    of `stats()` about the caches (every kind's, 0 where no block is of
    it; numbers, so that they survive into the Prometheus exposition);
    whether an admission overwrites per-slot state; whether every
    block's pages can be moved (`kv_only`: what the hand-off plane
    asks); and `positions(ctx)`, what the K/V blocks read of a
    context."""
    c = {key: 0 for cls in _KINDS.values()
         for key in (cls.blocks_key, cls.token_bytes_key) if key}
    # a two-mixer block counts once for each cache it keeps
    for st in (part for st in states for part in getattr(st, "parts", (st,))):
        c[st.blocks_key] += 1
        if st.token_bytes_key:
            c[st.token_bytes_key] += st.bytes_per_token()
    c["state_bytes_per_slot"] = sum(st.bytes_per_slot() for st in states)
    c["kv_quant_bits"] = 8 if env.kv_quant \
        else 8 * jnp.dtype(env.cdt).itemsize  # of the BUILT pools
    rings = [st for st in states if isinstance(st, WindowPages)]
    c["window_ring_pages"] = env.ring_pages if rings else 0
    c["window_bytes_per_slot"] = sum(st.ring_bytes_per_slot()
                                     for st in rings)
    # what each block that keeps K/V reads back of a context: a window's
    # width, or None for all of it
    spans = [st.window for st in states if isinstance(st, KVPages)]

    def positions(first, n_steps: int = 1) -> tuple:
        """Over `n_steps` consecutive steps of slots whose contexts at
        the first of them are `first` (one a slot), how many positions
        the K/V blocks' attention reads, and how many it would with no
        window: a slot's contexts are an arithmetic series, a window
        block's clipped at its width."""
        first = np.asarray(first, np.int64).reshape(-1)
        whole = int(n_steps * first.sum()
                    + first.size * (n_steps * (n_steps - 1) // 2))
        read = 0
        for w in spans:
            if w is None:
                read += whole
                continue
            # the steps whose context still lies inside the window
            m = np.clip(w - first + 1, 0, n_steps)
            read += int((m * first + m * (m - 1) // 2
                         + (n_steps - m) * w).sum())
        return read, whole * len(spans)

    return SimpleNamespace(
        counters=c, resets_on_admission=c["state_bytes_per_slot"] > 0,
        kv_only=all(getattr(st, "read_pages", None) is not None
                    for st in states),
        positions=positions)


def routed_ffns(plan) -> list:
    """The routed-expert feed-forward kinds of the plan's blocks."""
    from deeplearning4j_tpu.nn.conf.decoder_block import MoEFeedForward

    return [ffn for i in plan.block_is
            for ffn in getattr(plan.layers[i], "feed_forwards", list)()
            if isinstance(ffn, MoEFeedForward)]


class RoutingAccount:
    """A plan's routed-expert counts, from the blocks of a decode step
    to `stats()`: the routers' facts; what a step's namespace carries
    for counting (`step_fields`), what a routed block adds to it
    (`collect`) and the entry the step returns of it (`packed`); on the
    host, that entry added (`add`) to the totals `counters` reports
    (docs/observability.md says what each counts). Packing and unpacking
    live here alone, so a new count is one edit. `guard` is the owning
    engine's lock (the programs' builder makes an account of its own,
    for the traced side); `before`, the account this one replaces at a
    swap: the totals are the engine's, the facts follow the plan."""

    def __init__(self, plan, guard=None, before=None):
        self._cond = guard if guard is not None else threading.Lock()
        self._ffns = routed_ffns(plan)
        held = {ffn.held[1] for ffn in self._ffns}
        if len(held) > 1:
            raise ValueError(
                f"routed blocks hold different numbers of experts "
                f"{sorted(held)}: the decode step returns one per-expert "
                "count vector")
        # experts held a routed block (0: the net routes nowhere and the
        # step returns no counts) and zero-compute experts scored, all
        # blocks together (0: no count of their choices either)
        self.held = held.pop() if held else 0
        self.n_zero = sum(ffn.n_zero_experts for ffn in self._ffns)
        self.blocks = len(self._ffns)
        self.top_k = max([ffn.top_k for ffn in self._ffns], default=0)
        self._width, self._cdt = plan.emb.n_out, plan.cdt
        self._sorted_rows = {}  # a prefill's rows -> its experts went sorted
        # decode steps only; `prefill_sorted_n`, of the prefill
        # dispatches those that went sorted, is the scheduler thread's
        self.totals = dict(before.totals) if before else dict.fromkeys(
            ("moe_routed", "moe_held_choices", "moe_experts_hit",
             "moe_experts_read", "moe_steps", "moe_zero_choices",
             "moe_rows_local"), 0)  # guarded by: _cond
        self.prefill_sorted_n = before.prefill_sorted_n if before else 0

    def step_fields(self, active) -> dict:
        """The mask of the rows that count (the active slots; None where
        the net routes nowhere) and the lists routed blocks append to."""
        return dict(count_mask=active if self.held else None, counts=[],
                    rows_local=[], zero_counts=[])

    @staticmethod
    def collect(d, counts) -> None:
        """A routed feed-forward's `RouteCounts` into the step's lists:
        per-expert `(2, held)` into `d.counts`, the rows that chose a
        held expert into `d.rows_local`, and, where the router has zero
        experts, their scalar into `d.zero_counts`."""
        if counts is None:
            return
        d.counts.append(counts.experts)
        d.rows_local.append(counts.rows_local)
        if counts.zero is not None:
            d.zero_counts.append(counts.zero)

    def packed(self, d) -> tuple:
        """What the step appends to its outputs: nothing where the net
        routes nowhere, else ONE entry `(counts (3, held), rows_local[,
        zero])`: choices that fell on each held expert, summed over
        blocks, in how many blocks it was hit, and in how many the
        grouped product was told to read it; how many (active slot,
        block) rows chose a held expert at all; and, where the routers
        score zero-compute experts, how many choices fell on those."""
        if not self.held:
            return ()
        chosen, read = jnp.stack(d.counts, axis=1)
        counts = jnp.stack([chosen.sum(0), (chosen > 0).sum(0),
                            read.sum(0)]).astype(jnp.int32)
        return ((counts, sum(d.rows_local))
                + ((sum(d.zero_counts),) if self.n_zero else ()),)

    # graftlint: hot-loop
    def add(self, packed, n_live: int) -> None:
        """One dispatch's `packed` entry, read back: one step's or a
        chunk's (a leading axis), of `n_live` live slots."""
        counts, rows_local, *zero = packed
        counts = np.asarray(counts).reshape(-1, 3, self.held)
        with self._cond:
            t = self.totals
            t["moe_zero_choices"] += int(np.sum(zero))
            t["moe_rows_local"] += int(np.sum(rows_local))
            t["moe_routed"] += counts.shape[0] * n_live \
                * self.top_k * self.blocks
            t["moe_held_choices"] += int(counts[:, 0].sum())
            t["moe_experts_hit"] += int(counts[:, 1].sum())
            t["moe_experts_read"] += int(counts[:, 2].sum())
            t["moe_steps"] += counts.shape[0]

    def counters(self) -> dict:
        """`stats()`'s `moe_*` keys: 0 for a net that routes nowhere."""
        with self._cond:
            return dict(self.totals,
                        moe_experts_held=self.held * self.blocks)

    def count_prefill(self, rows: int) -> None:
        """A prefill program of `rows` rows went out: counted where it
        runs every routed block's experts through the sorted product
        (`MoEFeedForward.goes_sorted`; never for a net that routes
        nowhere); asked after that program's first dispatch, when its
        kernels' probes have run, and kept."""
        went = self._sorted_rows.get(rows)
        if went is None:
            went = self._sorted_rows[rows] = bool(self._ffns) and all(
                ffn.goes_sorted(rows, self._width, self._cdt)
                for ffn in self._ffns)
        self.prefill_sorted_n += went
