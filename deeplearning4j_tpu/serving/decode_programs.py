"""The decode engine's compiled programs: plan and geometry in, four
jitted functions out.

`build_programs` traces nothing of requests, queues or locks. It takes
the network's `GPTPlan`, the per-block state objects
(`serving/block_state.py`), the cache geometry and the sampling
constants, and returns `decode_step`, `decode_chunked`, `prefill` and
`prefill_chunk_fn`. Each program's first argument `bp` is the engine's
resident weights (`GPTPlan.resident_weights`, made once per build):
embedding and blocks in the compute dtype, trailing norms and head in
the param dtype; no program converts a weight. The second argument is
the list of per-block caches, donated where the backend supports it, so
the steps write the page pools where they lie in HBM.

The programs keep these function names: a device trace shows them as
`jit_<name>`, and the benchmark's readers find them by it.

**Parity**: the block math is the same per-block helpers
`models/transformer.generate` traces (`_block_heads`, `_block_ffn`,
`_prefill_block_attention`, `cached_attention_step` semantics through
the paged dispatch), so slotted greedy decode reproduces whole-batch
`generate` argmax-exactly at f32, whatever the admission order, page
reuse or prefill chunking (`tests/test_serving_generate.py`).
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import (
    _sample_logits,
    _top_k_filter,
)
from deeplearning4j_tpu.serving import block_state


def scale_and_filter(logits, temps, top_k: int):
    """Dynamic-temperature scale + shared top-k truncation.
    `temps` broadcasts over the row dim; <= 0 rows are scaled by
    1 (their categorical draw is discarded for greedy argmax)."""
    safe_t = jnp.where(temps > 0, temps, 1.0).astype(logits.dtype)
    return _top_k_filter(logits / safe_t[..., None], top_k)


def sample_slots(logits, keys, temps, top_k: int):
    """Per-slot sampling: greedy argmax where temps <= 0 (the
    parity-pinned path — identical to `_sample_logits` at
    temperature 0), per-slot-key categorical otherwise."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ks = jax.vmap(jax.random.split)(keys)      # (S, 2, 2)
    new_keys, subs = ks[:, 0], ks[:, 1]
    scaled = scale_and_filter(logits, temps, top_k)
    sampled = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg))(subs, scaled)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy), \
        new_keys


def logits_ok(logits, active):
    """Per-slot non-finite screen, the predict path's breaker
    discipline applied to generation: a slot whose logits go
    NaN/Inf must FAIL typed (and count toward the breaker), not
    'succeed' with garbage argmax tokens. Returns (S,) bool;
    inactive rows pass — freed slots hold stale state by
    design. Per-slot attribution means one poisoned sequence
    does not take healthy neighbors down with it."""
    row_ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                     axis=-1)
    return jnp.where(active, row_ok, True)


def token_logprobs(logits, chosen_tok, K: int):
    """(chosen logprob, top-K logprobs, top-K ids) from the UNSCALED
    model distribution — a report on the model, not on the
    temperature/top-k sampling transform, so greedy and sampled
    requests read the same per-token numbers."""
    lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(
        lsm, chosen_tok[..., None].astype(jnp.int32),
        axis=-1)[..., 0]
    top_v, top_i = jax.lax.top_k(lsm, K)
    return chosen, top_v, top_i.astype(jnp.int32)


def build_programs(plan, states, *, n_slots: int, page: int,
                   L_logical: int, decode_chunk: int, top_k: int,
                   logprobs: int, tp, donate: bool,
                   ring_pages: int = 0) -> SimpleNamespace:
    """The four jitted programs for `plan` over `states` (one state
    object per block, `block_state.block_states`). `L_logical` is the
    per-slot cache length in positions (a whole number of pages),
    `logprobs` the width K of the per-token logprob report (0: none;
    never combined with `tp`, so the extra tuple never crosses a
    `shard_map` boundary), `tp` the `TPPlan` or None.

    `ring_pages` R > 0: the page pool has a second class of page, each
    slot's a ring of R entries (`serving/page_pool.py`), and the
    programs' `page_table`, `page_row` and `wpids` arguments are then
    PAIRS, the pool's first table's and the ring's (`PagePool.tables`,
    `.rows`, `.write_ids`); the step's `d` carries the second as
    `ring_page_table`, `ring_pids`, `ring_page_row`, `ring_wpids` for the
    blocks
    that keep such pages. 0: one array each, and the programs are what
    they were."""
    S, K = n_slots, logprobs
    emb_i, block_is = plan.emb_i, plan.block_is
    emb, cdt = plan.emb, plan.cdt
    # the traced side of the routed blocks' counts: what the step's
    # namespace carries for them and what the step returns of it
    account = block_state.RoutingAccount(plan)

    def _shard(fn, n_in, n_out):
        """Identity on one device; under TP the body becomes the
        per-shard program of a `shard_map` over the tp mesh
        (serving/tp_engine.py) — params head/width-sharded, pools
        head-sharded, page table and slot state replicated."""
        if tp is None:
            return fn
        return tp.shard(fn, n_in=n_in, n_out=n_out)

    def decode_step(bp, caches, page_table, tok, pos, keys, temps,
                    active):
        """Advance ALL slots one token: inactive slots are masked
        (token/position carried through unchanged, cache writes
        redirected to the trash page so a reallocated page is never
        corrupted), so every iteration compiles to this single
        shape."""
        x = bp[emb_i]["W"][tok]
        if emb.positional:
            x = x + bp[emb_i]["P"][jnp.minimum(pos, emb.max_length - 1)]
        x = emb.scaled(x).astype(cdt)
        wpos = jnp.minimum(pos, L_logical - 1)
        lpage = wpos // page
        rows = jnp.arange(S)
        ring = {}
        if ring_pages:
            # logical page j of a slot's ring lies at entry j % R
            page_table, ring_table = page_table
            ring = dict(ring_page_table=ring_table, ring_pids=jnp.where(
                active, ring_table[rows, lpage % ring_pages], 0))
        d = SimpleNamespace(
            page_table=page_table, pos=pos, active=active,
            loff=wpos % page,
            # inactive lanes write to the reserved trash page 0
            pids=jnp.where(active, page_table[rows, lpage], 0),
            # the active slots' choices are counted, where the net routes
            **account.step_fields(active), **ring)
        new_caches = []
        for bi, i in enumerate(block_is):
            x, cache = states[bi].decode(bp[i], x, caches[bi], d)
            new_caches.append(cache)
        logits = plan.final_logits(bp, bp, x)
        with jax.named_scope("sample"):
            nxt, new_keys = sample_slots(logits, keys, temps, top_k)
            nxt = jnp.where(active, nxt, tok)
        new_pos = jnp.where(active, pos + 1, pos)
        with jax.named_scope("finite-check"):
            step_ok = logits_ok(logits, active)
        out = (new_caches, nxt, new_pos, new_keys, step_ok)
        if K:
            out += (token_logprobs(logits, nxt, K),)
        return out + account.packed(d)

    # the chunk scans the step's body, not the jitted program the
    # name is rebound to below
    step_math = decode_step

    def decode_chunked(bp, caches, page_table, tok, pos, keys,
                       temps, active):
        """`decode_chunk` iterations of the SAME step body fused into
        one dispatch via lax.scan — used only when the scheduler
        proves no admission/retirement/deadline/prefill event can
        land inside the chunk (page tables are therefore invariant
        across it). Returns every intermediate token (chunk, S)."""
        def body(carry, _):
            out = step_math(bp, *carry[:1], page_table,
                            *carry[1:], temps, active)
            # per-STEP outputs (chunk, S): the host attributes a
            # poisoned step to the right iteration, so a request
            # that completed via EOS before the bad step still
            # succeeds
            return out[:4], (out[1],) + out[4:]

        carry, per_step = jax.lax.scan(
            body, (caches, tok, pos, keys), None,
            length=decode_chunk)
        # caches, tok, pos, keys, then toks, oks[, lps][, counts]
        return carry + per_step

    def prefill(bp, caches, ids, t0, slot, wpids, tok, pos, keys,
                temps, kp, kdec, temp):
        """One-shot prefill: write one prompt's KV into the slot's
        pages and emit its first token. `ids` is (1, bucket) — pow-2
        padded; the pad region's KV entries land in the request's
        own pages and are masked off by position until decode
        overwrites them, so padding never changes a real token's
        numerics. The block math is IDENTICAL to `generate`'s
        prefill (`_prefill_block_attention`) — only the cache
        write targets pages instead of a slot row."""
        P = ids.shape[1]
        x = bp[emb_i]["W"][ids]
        if emb.positional:
            x = x + bp[emb_i]["P"][:P]
        x = emb.scaled(x).astype(cdt)
        ring = {}
        if ring_pages:
            wpids, ring_wpids = wpids
            ring = dict(ring_wpids=ring_wpids)
        d = SimpleNamespace(wpids=wpids, t0=t0, slot=slot, **ring)
        new_caches = []
        for bi, i in enumerate(block_is):
            x, cache = states[bi].prefill(bp[i], x, caches[bi], d)
            new_caches.append(cache)
        logits = plan.final_logits(bp, bp, x[0, t0 - 1][None])
        # kp samples the prefill token, kdec seeds the slot's decode
        # key — the same split generate() draws from PRNGKey(seed).
        # Temperature is dynamic per request, so the greedy/sampled
        # select mirrors sample_slots (same scale_and_filter core)
        with jax.named_scope("sample"):
            greedy = _sample_logits(logits, kp, 0.0, 0)
            drawn = jax.random.categorical(
                kp, scale_and_filter(logits, temp[None], top_k),
                axis=-1).astype(jnp.int32)
            tok0 = jnp.where(temp > 0, drawn, greedy)
        tok = tok.at[slot].set(tok0[0])
        pos = pos.at[slot].set(t0)
        keys = keys.at[slot].set(kdec)
        temps = temps.at[slot].set(temp)
        with jax.named_scope("finite-check"):
            ok0 = jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
        if K:
            return new_caches, tok, pos, keys, temps, tok0, ok0, \
                token_logprobs(logits, tok0, K)
        return new_caches, tok, pos, keys, temps, tok0, ok0

    def prefill_chunk_fn(bp, caches, page_row, ids, off, woff,
                         t0, slot, wpids, tok, pos, keys, temps, kp,
                         kdec, temp):
        """One prefill CHUNK: embed `ids` (1, prefill_chunk) at
        absolute positions off..off+C-1, write its KV into pages
        `wpids`, attend causally over [prior chunks ‖ this chunk]
        through the slot's gathered page row, and emit logits at
        prompt position t0-1 (only meaningful — and only consumed
        by the host — on the FINAL chunk). Slot token/position/key
        state is set every chunk; the final chunk's values are the
        ones that stick before decode starts."""
        Cw = ids.shape[1]
        qpos = off + jnp.arange(Cw)
        x = bp[emb_i]["W"][ids]
        if emb.positional:
            # gather (not dynamic_slice): a padded final chunk may
            # run past the positional table, and dynamic_slice's
            # start-clamping would silently shift REAL positions —
            # the per-position clamp only garbles the masked pad
            # tail
            x = x + bp[emb_i]["P"][jnp.minimum(qpos,
                                               emb.max_length - 1)]
        x = emb.scaled(x).astype(cdt)
        ring = {}
        if ring_pages:
            (wpids, ring_wpids), (page_row, ring_row) = wpids, page_row
            ring = dict(ring_wpids=ring_wpids, ring_page_row=ring_row)
        d = SimpleNamespace(wpids=wpids, woff=woff, off=off,
                            qpos=qpos, page_row=page_row,
                            t0=t0, slot=slot, **ring)
        new_caches = []
        for bi, i in enumerate(block_is):
            x, cache = states[bi].prefill_chunk(bp[i], x, caches[bi],
                                                d)
            new_caches.append(cache)
        r = jnp.clip(t0 - 1 - off, 0, Cw - 1)
        logits = plan.final_logits(bp, bp, x[0, r][None])
        with jax.named_scope("sample"):
            greedy = _sample_logits(logits, kp, 0.0, 0)
            drawn = jax.random.categorical(
                kp, scale_and_filter(logits, temp[None], top_k),
                axis=-1).astype(jnp.int32)
            tok0 = jnp.where(temp > 0, drawn, greedy)
        tok = tok.at[slot].set(tok0[0])
        pos = pos.at[slot].set(t0)
        keys = keys.at[slot].set(kdec)
        temps = temps.at[slot].set(temp)
        # screen the whole chunk's hidden states, not only the
        # logits row: a non-finite mid-prompt chunk poisons the
        # cache it just wrote, and must fail HERE, typed
        with jax.named_scope("finite-check"):
            ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32))) \
                & jnp.all(jnp.isfinite(x.astype(jnp.float32)))
        if K:
            return new_caches, tok, pos, keys, temps, tok0, ok, \
                token_logprobs(logits, tok0, K)
        return new_caches, tok, pos, keys, temps, tok0, ok

    # jit OUTSIDE the shard_map (donation must alias the sharded
    # pool buffers, and an inner jit would be inlined by the
    # per-shard trace) — the literal jax.jit assign keeps
    # graftlint's donation rule pointed at these call sites
    decode_step = jax.jit(_shard(decode_step, 8, 5),
                          donate_argnums=(1,) if donate else ())
    decode_chunked = jax.jit(_shard(decode_chunked, 8, 6),
                             donate_argnums=(1,) if donate else ())
    prefill = jax.jit(_shard(prefill, 13, 7),
                      donate_argnums=(1,) if donate else ())
    prefill_chunk_fn = jax.jit(_shard(prefill_chunk_fn, 16, 7),
                               donate_argnums=(1,) if donate else ())
    return SimpleNamespace(
        decode_step=decode_step, decode_chunked=decode_chunked,
        prefill=prefill, prefill_chunk_fn=prefill_chunk_fn)
