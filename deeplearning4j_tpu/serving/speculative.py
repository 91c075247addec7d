"""Draft-verify speculative decoding for the paged decode engine.

The decode engine emits ONE token per verified target-model step — at
serving shapes that step is dispatch/cache-bandwidth bound, so the chip
spends most of each iteration waiting on a single token's worth of
work. Speculative decoding (Leviathan et al., 2023) converts that slack
into tokens: a cheap DRAFT model proposes `k` candidate tokens per slot
(one scanned dispatch), then the target model scores all proposals in
ONE batched verify step — a (k+1)-wide chunk per slot through the paged
KV cache, the exact shape `ops.attention.cached_attention_chunk`
already computes for chunked prefill. Accepted tokens advance the slot;
the first disagreement emits the target's own token instead.

Exactness is the load-bearing contract, inherited per-path:

- **greedy (temperature <= 0)**: a proposal is accepted only when it
  EQUALS the target's argmax at that position, and the stop position
  emits the target argmax itself — the emitted stream is the vanilla
  greedy rollout token for token, for ANY draft (a garbage draft only
  costs acceptance rate, never correctness). Argmax-exact parity with
  whole-batch `generate` is pinned in `tests/test_prefix_spec.py`.
- **sampled (temperature > 0)**: proposals drawn from the draft
  distribution q are accepted with probability min(1, p/q) against the
  target distribution p; the first rejection resamples from the
  residual norm(max(p - q, 0)), and a stop forced by anything OTHER
  than a rejection (all k accepted, or the slot nearing its token
  budget) draws from p directly. Each emitted token is distributed
  EXACTLY as a vanilla sample from p (Leviathan Thm. 1; the
  forced-stop draw is unbiased because it ignores the unconsumed
  accept coin) — pinned by a Monte-Carlo distribution test.

Rollback is free by construction: speculative KV writes land at
positions past each slot's committed length, where the engine's
position masking already hides them, and are overwritten in place when
decoding actually reaches those positions — the same trash-page
discipline that protects reallocated pages. Writes that would run past
a slot's reserved span (tail slots) are redirected to the trash page.

The draft model keeps its OWN paged KV pools indexed by the engine's
page table — same page ids, same refcounts — so prefix-cache hits skip
the draft's prefill too, and a page promotion shares both models'
KV in one move.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def resolve_draft_net(draft, target_net):
    """Materialize the `speculative={"draft": ...}` config value:
    a fitted network instance is used as-is; the string "self" means
    self-speculation (draft = the target — every step still amortizes
    dispatches via the batched verify); a JSON config dict builds a
    fresh (randomly initialized) net, the wire-friendly form the
    gateway can ship."""
    if draft is None:
        raise ValueError(
            'speculative={...} needs a "draft": a gpt net instance, '
            '"self", or a gpt_configuration JSON dict')
    if isinstance(draft, str):
        if draft != "self":
            raise ValueError(f'unknown speculative draft {draft!r} — '
                             'pass a net, "self", or a config dict')
        return target_net
    if isinstance(draft, dict):
        import json

        from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        net = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(json.dumps(draft)))
        net.init()
        return net
    return draft


class SpeculativeDecoder:
    """Compiled draft-propose + target-verify machinery for one
    `DecodeEngine` geometry. Built by the engine's `_build` (and
    rebuilt on weight swap); owns the draft model's paged KV pools and
    per-slot draft PRNG keys, reset alongside the engine's device state.
    """

    def __init__(self, *, target_plan, target_net, draft_net, k: int,
                 n_slots: int, page: int, L_logical: int,
                 pool_pages: int, top_k: int, donate: bool,
                 kv_quant: Optional[str] = None,
                 tp=None, target_weights=None):
        if k < 1:
            raise ValueError("speculative k must be >= 1")
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.transformer import (
            GPTPlan,
            _block_ffn,
            _block_heads,
            _block_out_proj,
            _prefill_block_attention,
            _top_k_filter,
        )
        from deeplearning4j_tpu.ops.attention import (
            paged_attention_chunk_auto,
            paged_attention_step_auto,
        )
        from deeplearning4j_tpu.serving.block_state import (
            _write_pages,
            _write_token,
        )
        from deeplearning4j_tpu.serving.quantize import (
            _write_scale_pages,
            quantize_heads,
        )

        # the engine's resolved KV quantization mode is inherited
        # verbatim: the verify step writes into the ENGINE's pools, and
        # the draft pools mirror the same halved-residency layout so
        # "same page ids" stays memory-true
        self._kv_quant = kv_quant
        self.k = int(k)
        self.n_slots = n_slots
        self.page = page
        self.pool_pages = pool_pages
        self.draft_net = draft_net
        self._donate = donate
        tplan = target_plan
        # NOTE: self-speculation still allocates its own draft pools
        # (reset_state) — ~2x KV HBM. Aliasing the engine's pools is
        # unsound under donation (propose would invalidate the target's
        # cache reference), so "self" is the acceptance-rate-ceiling /
        # dispatch-amortization config, not a memory-neutral one
        self.self_draft = draft_net is target_net
        # host-side mirror counters (surfaced via stats() → the engine's
        # metrics registry): how often the draft pools were (re)filled
        self.draft_prefills = 0
        self.draft_chunk_prefills = 0
        dplan = tplan if self.self_draft else GPTPlan(draft_net)
        self.draft_plan = dplan
        if dplan.emb.n_in != tplan.emb.n_in:
            raise ValueError(
                f"draft vocab {dplan.emb.n_in} != target vocab "
                f"{tplan.emb.n_in} — speculative verification compares "
                "token ids, so the vocabularies must match")
        if dplan.emb.positional and dplan.emb.max_length < L_logical \
                and dplan.emb.max_length < tplan.emb.max_length:
            raise ValueError(
                f"draft max_length {dplan.emb.max_length} is shorter than "
                f"the engine's logical cache ({L_logical}) — the draft "
                "could not embed positions the target serves")
        # tensor parallelism: the engine's TPPlan (target geometry) is
        # shared for the verify step; the draft gets its OWN plan unless
        # self-drafting. A draft whose heads/FFN don't divide the degree
        # fails HERE with the same typed ValueError the engine raises
        # for the target.
        self._tp = tp
        dtp = tp
        if tp is not None and not self.self_draft:
            from deeplearning4j_tpu.serving.tp_engine import TPPlan

            dtp = TPPlan(draft_net, dplan, tp.degree)
        self._dtp = dtp
        # the weights the draft's programs are handed, resident in the
        # draft's compute dtype like the engine's own
        # (`target_weights`, which the verifier takes and a self-draft
        # shares): placed and cast once here, converted by no program
        self._weights = target_weights if self.self_draft \
            else dplan.resident_weights(
                dtp.shard_params(draft_net._params) if dtp is not None
                else draft_net._params)
        tp_axis = tp.axis if tp is not None else None
        tp_shard = tp.degree if tp is not None else None

        def _shard_d(fn, n_in, n_out):
            return fn if dtp is None else dtp.shard(
                fn, n_in=n_in, n_out=n_out, caches_out_at=0)

        S, kk = n_slots, self.k
        C = kk + 1

        def scale_and_filter(logits, temps):
            # temps broadcasts over every leading dim of `logits`
            safe_t = jnp.where(temps > 0, temps, 1.0).astype(logits.dtype)
            while safe_t.ndim < logits.ndim:
                safe_t = safe_t[..., None]
            return _top_k_filter(logits / safe_t, top_k)

        # -- draft prefill (one-shot + chunk): KV writes only, no head --
        def draft_prefill(bp, dcaches, ids, wpids):
            P = ids.shape[1]
            x = bp[dplan.emb_i]["W"][ids]
            if dplan.emb.positional:
                x = x + bp[dplan.emb_i]["P"][
                    jnp.minimum(jnp.arange(P), dplan.emb.max_length - 1)]
            x = x.astype(dplan.cdt)
            new_caches = []
            for bi, i in enumerate(dplan.block_is):
                p = bp[i]
                layer = dplan.layers[i]
                q, kh, vh = _block_heads(layer, p, x, jnp.arange(P),
                                         shard=tp_shard)
                att = _prefill_block_attention(layer, q, kh, vh)
                att = _block_out_proj(p, att.reshape(1, P, -1), tp_axis)
                x = _block_ffn(layer, p, x + att, axis_name=tp_axis)
                kcol = jnp.transpose(kh, (0, 2, 3, 1))
                vrow = jnp.transpose(vh, (0, 2, 1, 3))
                z0 = jnp.zeros((), jnp.int32)
                if kv_quant:
                    kp_, vp_, ks_, vs_ = dcaches[bi]
                    kcol, kscol = quantize_heads(kcol, axis=2)
                    vrow, vscol = quantize_heads(vrow, axis=3)
                    ks_ = _write_scale_pages(ks_, kscol, wpids, z0, page)
                    vs_ = _write_scale_pages(vs_, vscol, wpids, z0, page)
                    kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, wpids,
                                            z0, page)
                    new_caches.append((kp_, vp_, ks_, vs_))
                else:
                    kp_, vp_ = dcaches[bi]
                    kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, wpids,
                                            z0, page)
                    new_caches.append((kp_, vp_))
            return new_caches

        def draft_prefill_chunk(bp, dcaches, page_row, ids, off, woff,
                                wpids):
            Cw = ids.shape[1]
            qpos = off + jnp.arange(Cw)
            x = bp[dplan.emb_i]["W"][ids]
            if dplan.emb.positional:
                x = x + bp[dplan.emb_i]["P"][
                    jnp.minimum(qpos, dplan.emb.max_length - 1)]
            x = x.astype(dplan.cdt)
            new_caches = []
            for bi, i in enumerate(dplan.block_is):
                p = bp[i]
                layer = dplan.layers[i]
                q, kh, vh = _block_heads(layer, p, x, qpos, shard=tp_shard)
                kcol = jnp.transpose(kh, (0, 2, 3, 1))
                vrow = jnp.transpose(vh, (0, 2, 1, 3))
                if kv_quant:
                    kp_, vp_, ks_, vs_ = dcaches[bi]
                    kcol, kscol = quantize_heads(kcol, axis=2)
                    vrow, vscol = quantize_heads(vrow, axis=3)
                    ks_ = _write_scale_pages(ks_, kscol, wpids, woff, page)
                    vs_ = _write_scale_pages(vs_, vscol, wpids, woff, page)
                else:
                    kp_, vp_ = dcaches[bi]
                    ks_ = vs_ = None
                kp_, vp_ = _write_pages(kp_, vp_, kcol, vrow, wpids, woff,
                                        page)
                att = paged_attention_chunk_auto(q, kp_, vp_,
                                                 page_row[None], off[None],
                                                 k_scale=ks_, v_scale=vs_)
                att = _block_out_proj(p, att.reshape(1, Cw, -1), tp_axis)
                x = _block_ffn(layer, p, x + att, axis_name=tp_axis)
                new_caches.append((kp_, vp_, ks_, vs_) if kv_quant
                                  else (kp_, vp_))
            return new_caches

        # -- draft proposal: k+1 scanned draft steps ------------------------
        # k proposals plus one cache-completion step, so the draft's KV
        # covers every position the NEXT round may start from (an
        # all-accepted verify advances the slot past the k-th write)
        def draft_propose(bp, dcaches, page_table, tok, pos, dkeys,
                          temps, active, wlimit):
            rows = jnp.arange(S)

            def body(carry, j):
                caches, cur, keys = carry
                p_j = pos + j
                x = bp[dplan.emb_i]["W"][cur]
                if dplan.emb.positional:
                    x = x + bp[dplan.emb_i]["P"][
                        jnp.minimum(p_j, dplan.emb.max_length - 1)]
                x = x.astype(dplan.cdt)
                wpos = jnp.minimum(p_j, L_logical - 1)
                # writes past a slot's reserved span go to the trash
                # page — speculative state never corrupts another
                # request's pages
                writable = active & ((j == 0) | (p_j <= wlimit))
                pids = jnp.where(writable, page_table[rows, wpos // page], 0)
                loff = wpos % page
                new_caches = []
                for bi, i in enumerate(dplan.block_is):
                    p = bp[i]
                    layer = dplan.layers[i]
                    q, kh, vh = _block_heads(layer, p, x[:, None, :],
                                             p_j[:, None], shard=tp_shard)
                    q, kh, vh = q[:, 0], kh[:, 0], vh[:, 0]
                    if kv_quant:
                        kq, ksc = quantize_heads(kh)
                        vq, vsc = quantize_heads(vh)
                        kp_, vp_, ks_, vs_ = _write_token(
                            caches[bi], kq, vq, pids, loff, (ksc, vsc))
                    else:
                        ks_ = vs_ = None
                        kp_, vp_ = _write_token(caches[bi], kh, vh, pids,
                                                loff)
                    att = paged_attention_step_auto(q, kp_, vp_,
                                                    page_table, p_j,
                                                    active,
                                                    k_scale=ks_,
                                                    v_scale=vs_)
                    att = _block_out_proj(p, att, tp_axis)
                    x = _block_ffn(layer, p, x + att, axis_name=tp_axis)
                    new_caches.append((kp_, vp_, ks_, vs_) if kv_quant
                                      else (kp_, vp_))
                logits = dplan.final_logits(bp, bp, x)
                scaled = scale_and_filter(logits, temps)
                qdist = jax.nn.softmax(scaled.astype(jnp.float32), axis=-1)
                ks = jax.vmap(jax.random.split)(keys)
                keys2, subs = ks[:, 0], ks[:, 1]
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                drawn = jax.vmap(
                    lambda kx, lg: jax.random.categorical(kx, lg))(
                        subs, scaled).astype(jnp.int32)
                nxt = jnp.where(temps > 0, drawn, greedy)
                nxt = jnp.where(active, nxt, cur)
                return (new_caches, nxt, keys2), (nxt, qdist)

            (caches, _, keys), (toks, qdists) = jax.lax.scan(
                body, (dcaches, tok, dkeys), jnp.arange(C))
            props = jnp.swapaxes(toks[:kk], 0, 1)          # (S, k)
            qd = jnp.moveaxis(qdists[:kk], 0, 1)           # (S, k, V)
            return caches, keys, props, qd

        # -- target verify: one (k+1)-wide chunk per slot -------------------
        def verify(bp, caches, page_table, tok, pos, keys, temps,
                   active, wlimit, props, qdists):
            rows = jnp.arange(S)
            block = jnp.concatenate([tok[:, None], props], axis=1)  # (S,C)
            qpos = pos[:, None] + jnp.arange(C)[None, :]            # (S,C)
            x = bp[tplan.emb_i]["W"][block]
            if tplan.emb.positional:
                x = x + bp[tplan.emb_i]["P"][
                    jnp.minimum(qpos, tplan.emb.max_length - 1)]
            x = x.astype(tplan.cdt)
            new_caches = []
            for bi, i in enumerate(tplan.block_is):
                p = bp[i]
                layer = tplan.layers[i]
                q, kh, vh = _block_heads(layer, p, x, qpos, shard=tp_shard)
                cache = caches[bi]
                for j in range(C):
                    p_j = pos + j
                    wpos = jnp.minimum(p_j, L_logical - 1)
                    writable = active & ((j == 0) | (p_j <= wlimit))
                    pids = jnp.where(writable,
                                     page_table[rows, wpos // page], 0)
                    loff = wpos % page
                    if kv_quant:
                        kq, ksc = quantize_heads(kh[:, j])
                        vq, vsc = quantize_heads(vh[:, j])
                        cache = _write_token(cache, kq, vq, pids, loff,
                                             (ksc, vsc))
                    else:
                        cache = _write_token(cache, kh[:, j], vh[:, j],
                                             pids, loff)
                kp_, vp_ = cache[:2]
                ks_, vs_ = cache[2:] if kv_quant else (None, None)
                # one (k+1)-wide paged chunk per slot: the kernel walks
                # the page table in place; the fallback is exactly
                # `_verify_block_attention` (gather + vmapped chunk)
                att = paged_attention_chunk_auto(q, kp_, vp_, page_table,
                                                 pos, active,
                                                 k_scale=ks_, v_scale=vs_)
                att = _block_out_proj(p, att, tp_axis)
                x = _block_ffn(layer, p, x + att, axis_name=tp_axis)
                new_caches.append((kp_, vp_, ks_, vs_) if kv_quant
                                  else (kp_, vp_))
            logits = tplan.final_logits(bp, bp, x)       # (S, C, V)

            # --- acceptance (Leviathan rejection sampling; greedy =
            # argmax equality). Query j consumed [tok, props][j] and its
            # distribution governs the token at offset j+1.
            e = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (S, C)
            scaled = scale_and_filter(logits, temps)
            pdist = jax.nn.softmax(scaled.astype(jnp.float32), axis=-1)
            qn = jnp.where(jnp.isfinite(qdists), qdists, 0.0)
            ks = jax.vmap(lambda kx: jax.random.split(kx, 3))(keys)
            new_keys, ku, kr = ks[:, 0], ks[:, 1], ks[:, 2]
            us = jax.vmap(lambda kx: jax.random.uniform(kx, (kk,)))(ku)
            p_at = jnp.take_along_axis(pdist[:, :kk], props[..., None],
                                       axis=-1)[..., 0]            # (S, k)
            q_at = jnp.take_along_axis(qn, props[..., None],
                                       axis=-1)[..., 0]            # (S, k)
            accept = us < jnp.minimum(1.0, p_at / jnp.maximum(q_at, 1e-30))
            match = e[:, :kk] == props
            acc = jnp.where(temps[:, None] > 0, accept, match)
            lead = jnp.cumprod(acc.astype(jnp.int32), axis=1)
            m_rej = jnp.sum(lead, axis=1)                   # 0..k
            # the slot's remaining write budget caps how deep this round
            # may commit; m_cap == 0 degrades the slot to a vanilla step
            m_cap = jnp.clip(wlimit - pos, 0, kk)
            m = jnp.minimum(m_rej, m_cap)
            # stop forced by the cap or by running out of proposals
            # (m_rej >= m_cap): the unconsumed accept coin is IGNORED
            # and the stop token samples from the full target
            # distribution — conditioning on it would bias the draw.
            # A genuine rejection (m_rej < m_cap) resamples the residual
            forced = m_rej >= m_cap
            resid = jnp.maximum(pdist[:, :kk] - qn, 0.0)
            rsum = jnp.sum(resid, axis=-1, keepdims=True)
            resid = jnp.where(rsum > 0, resid, pdist[:, :kk])
            rlog = jnp.where(resid > 0,
                             jnp.log(jnp.maximum(resid, 1e-38)), -1e30)
            res_draws = jax.vmap(
                lambda kx, lg: jax.random.categorical(kx, lg, axis=-1))(
                    kr, rlog).astype(jnp.int32)             # (S, k)
            # graftlint: disable=rng-reuse  deliberate: res_draws and
            # full_draws are mutually exclusive per row (jnp.where picks
            # one), so reusing kr keeps the accepted draw identical to the
            # single-sample rejection-sampling recurrence
            full_draws = jax.vmap(
                lambda kx, lg: jax.random.categorical(kx, lg, axis=-1))(
                    kr, scaled.astype(jnp.float32)).astype(jnp.int32)
            m1 = m[:, None]
            res_at_m = jnp.take_along_axis(
                res_draws, jnp.minimum(m1, kk - 1), axis=1)[:, 0]
            full_at_m = jnp.take_along_axis(full_draws, m1, axis=1)[:, 0]
            e_at_m = jnp.take_along_axis(e, m1, axis=1)[:, 0]
            fin_sampled = jnp.where(forced, full_at_m, res_at_m)
            fin = jnp.where(temps > 0, fin_sampled, e_at_m).astype(jnp.int32)
            idx = jnp.arange(C)[None, :]
            acc_tok = jnp.where(temps[:, None] > 0,
                                jnp.concatenate([props, props[:, -1:]],
                                                axis=1), e)
            out = jnp.where(idx < m1, acc_tok, 0)
            out = jnp.where(idx == m1, fin[:, None], out).astype(jnp.int32)
            n_emit = jnp.where(active, m + 1, 0)
            new_tok = jnp.where(active,
                                jnp.take_along_axis(out, m1, axis=1)[:, 0],
                                tok)
            new_pos = jnp.where(active, pos + m + 1, pos)
            new_keys = jnp.where(active[:, None], new_keys, keys)
            row_ok = jnp.all(
                jnp.isfinite(logits.astype(jnp.float32)), axis=(1, 2))
            oks = jnp.where(active, row_ok, True)
            return new_caches, new_tok, new_pos, new_keys, out, n_emit, oks

        # jit OUTSIDE shard_map (identity when tp is off) so pool
        # donation aliases the sharded buffers; draft closures shard
        # with the DRAFT plan's specs, verify with the target's
        draft_prefill = jax.jit(_shard_d(draft_prefill, 4, 1),
                                donate_argnums=(1,) if donate else ())
        draft_prefill_chunk = jax.jit(_shard_d(draft_prefill_chunk, 7, 1),
                                      donate_argnums=(1,) if donate else ())
        draft_propose = jax.jit(_shard_d(draft_propose, 9, 4),
                                donate_argnums=(1,) if donate else ())
        verify = jax.jit(
            verify if tp is None else tp.shard(verify, n_in=11, n_out=7),
            donate_argnums=(1,) if donate else ())
        self._draft_prefill = draft_prefill
        self._draft_prefill_chunk = draft_prefill_chunk
        self._propose = draft_propose
        self._verify = verify
        self.reset_state()

    # -- device state ------------------------------------------------------
    def reset_state(self) -> None:
        """Fresh draft pools + per-slot draft keys (construction, weight
        swap, post-failure recovery — always alongside the engine's own
        `_reset_device_state`, so draft and target pages can never skew)."""
        import jax
        import jax.numpy as jnp

        dplan, S = self.draft_plan, self.n_slots
        page, P = self.page, self.pool_pages
        caches = []
        for i in dplan.block_is:
            layer = dplan.layers[i]
            hd = layer.n_out // layer.n_heads
            Hkv = layer._kv_heads
            if self._kv_quant:
                # int8 draft pools + f32 scale sidecars, mirroring the
                # engine's layout (see DecodeEngine._reset_device_state)
                caches.append(
                    (jnp.zeros((P + 1, Hkv, hd, page), jnp.int8),
                     jnp.zeros((P + 1, Hkv, page, hd), jnp.int8),
                     jnp.ones((P + 1, Hkv, page), jnp.float32),
                     jnp.ones((P + 1, Hkv, page), jnp.float32)))
            else:
                caches.append(
                    (jnp.zeros((P + 1, Hkv, hd, page), dplan.cdt),
                     jnp.zeros((P + 1, Hkv, page, hd), dplan.cdt)))
        if self._dtp is not None:
            # head axis over tp, mirroring the engine's pools — the
            # shared page table addresses the same per-device head slice
            # in both models' pools
            caches = [tuple(self._dtp.shard_pool(x) for x in c)
                      for c in caches]
        self._caches = caches
        self._keys = jnp.stack(
            [jax.random.PRNGKey(1000 + i) for i in range(S)])

    def seed_slot(self, slot: int, seed: int) -> None:
        """Per-request draft PRNG stream (deterministic per seed, on a
        different fold than the target's kp/kd split)."""
        import jax

        self._keys = self._keys.at[slot].set(
            jax.random.fold_in(jax.random.PRNGKey(seed), 7))

    # -- host drivers (called by the engine scheduler) ---------------------
    def prefill_one_shot(self, ids, wpids) -> None:
        """Mirror one target one-shot prefill into the draft pools (same
        pages, same padded ids). Materializes a probe scalar so a failed
        draft dispatch surfaces HERE, attributable, not inside a later
        verify."""
        import jax
        import jax.numpy as jnp

        self._caches = self._draft_prefill(
            self._weights, self._caches, jnp.asarray(ids), wpids)
        jax.device_get(self._caches[0][0][0, 0, 0, 0])
        self.draft_prefills += 1

    def prefill_chunk(self, page_row, ids, off, woff, pids) -> None:
        """Mirror one target prefill chunk into the draft pools."""
        import jax
        import jax.numpy as jnp

        self._caches = self._draft_prefill_chunk(
            self._weights, self._caches, page_row,
            jnp.asarray(ids), jnp.asarray(off, jnp.int32),
            jnp.asarray(woff, jnp.int32),
            jnp.asarray(np.asarray(pids, np.int32)))
        jax.device_get(self._caches[0][0][0, 0, 0, 0])
        self.draft_chunk_prefills += 1

    def stats(self) -> dict:
        return {"k": self.k, "draft_is_target": self.self_draft,
                "draft_prefills": self.draft_prefills,
                "draft_chunk_prefills": self.draft_chunk_prefills}
