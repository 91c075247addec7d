"""Expert parallelism: Switch-style top-1 mixture-of-experts over a mesh
axis.

No counterpart in the reference (DP-only, SURVEY §2.4); included because
expert parallelism is the remaining first-class axis of the TPU sharding
design space (dp/tp/sp/pp/ep). Design: E experts' FFN parameters are
STACKED and sharded one-per-device over the `expert` mesh axis; a linear
router picks top-1 per token; tokens travel to their expert's device via
`lax.all_to_all` over ICI (the standard MoE dispatch collective), are
processed in one batched expert matmul, and return the same way.

Capacity: each expert processes at most `capacity = ceil(tokens/E) *
capacity_factor` tokens per device-shard; overflow tokens pass through
unchanged (Switch Transformer semantics). Everything is static-shaped —
routing is by sort/scatter, no data-dependent control flow.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops.pallas_moe_experts import GATED_SILU, RELU2

# -- expert-parallel mesh scope ------------------------------------------
# ParallelWrapper enters this scope inside its (traced) step so that
# MoELayer.forward — which has no mesh in its signature — can discover the
# mesh and route through the all_to_all path. Trace-time state: the scope
# is active while jit traces the step, and costs nothing afterwards.
_MESH_SCOPE: list = []


@contextlib.contextmanager
def expert_mesh_scope(mesh: Mesh, data_axis: Optional[str] = None):
    """Declare the active mesh (and its data axis, if any) for expert-
    parallel MoE layers traced within the scope."""
    _MESH_SCOPE.append((mesh, data_axis))
    try:
        yield
    finally:
        _MESH_SCOPE.pop()


def current_expert_mesh() -> Optional[Tuple[Mesh, Optional[str]]]:
    return _MESH_SCOPE[-1] if _MESH_SCOPE else None


def router_probs(x: jnp.ndarray, router_w: jnp.ndarray) -> jnp.ndarray:
    """(N, D) tokens × (D, E) router → (N, E) softmax probabilities."""
    return jax.nn.softmax(x @ router_w, axis=-1)


def _dispatch_indices(expert_idx: jnp.ndarray, E: int, capacity: int,
                      valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Position of each token within its expert's capacity buffer, and a
    keep-mask for tokens under capacity. `valid` (N,) bool excludes tokens
    (padding) from dispatch AND from capacity accounting."""
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (N, E)
    if valid is not None:
        onehot = onehot * valid[:, None].astype(jnp.int32)
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot      # 1-based
    pos = jnp.max(pos_in_expert, axis=-1) - 1                # (N,)
    keep = pos < capacity
    if valid is not None:
        keep = keep & (pos >= 0)  # invalid tokens have pos == -1
    return pos, keep


def moe_apply_reference(expert_fn: Callable, stacked_params, x: jnp.ndarray,
                        router_w: jnp.ndarray, *,
                        capacity_factor: float = 1.25,
                        token_mask=None,
                        passthrough: str = "identity",
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device reference semantics (also the parity baseline for the
    sharded path): top-1 routing with capacity, overflow passes through.

    `token_mask` (N,) with 1=real: padding tokens bypass the experts
    entirely — no routing, no capacity consumption, no weight in the
    load-balancing loss.

    `passthrough` is what dropped (overflow/masked) tokens yield:
    "identity" → the input token (a layer with no external residual, e.g.
    MoELayer, leaves them unchanged); "zero" → 0, for callers that add
    their own residual (TransformerBlock's `x + ffn`) — identity there
    would double-add the input.

    Returns (y, aux_loss) — aux_loss is the Switch load-balancing loss
    (mean fraction routed × mean router prob, scaled by E)."""
    N, D = x.shape
    E = router_w.shape[1]
    capacity = int(np.ceil(N / E * capacity_factor))
    probs = router_probs(x, router_w)
    expert_idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]
    valid = None if token_mask is None else token_mask > 0
    pos, keep = _dispatch_indices(expert_idx, E, capacity, valid)  # global cap

    # scatter tokens into (E, capacity, D) buffers
    buf = jnp.zeros((E, capacity, D), x.dtype)
    safe_pos = jnp.where(keep, pos, 0)
    buf = buf.at[expert_idx, safe_pos].add(
        jnp.where(keep[:, None], x, 0.0))
    # one batched expert application: vmap over the expert axis
    out_buf = jax.vmap(expert_fn)(stacked_params, buf)
    # gather back
    y_expert = out_buf[expert_idx, safe_pos]
    if passthrough not in ("identity", "zero"):
        raise ValueError(f"unknown passthrough {passthrough!r}")
    dropped = x if passthrough == "identity" else jnp.zeros_like(x)
    y = jnp.where(keep[:, None], gate[:, None] * y_expert, dropped)

    # load-balancing loss (Switch eq. 4) over REAL tokens only
    oh = jax.nn.one_hot(expert_idx, E)
    if valid is not None:
        w = valid.astype(x.dtype)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        frac_routed = jnp.sum(oh * w[:, None], axis=0) / denom
        mean_prob = jnp.sum(probs * w[:, None], axis=0) / denom
    else:
        frac_routed = jnp.mean(oh, axis=0)
        mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_routed * mean_prob)
    return y, aux


def moe_apply(expert_fn: Callable, stacked_params, x: jnp.ndarray,
              router_w: jnp.ndarray, mesh: Mesh, *,
              axis_name: str = "expert", capacity_factor: float = 1.25,
              passthrough: str = "identity",
              data_axis: Optional[str] = None,
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE: experts sharded over `axis_name`, token
    dispatch/return via all_to_all. `passthrough` as in
    `moe_apply_reference` ("zero" for callers with an external residual).

    Matches `moe_apply_reference` exactly while no expert overflows
    (parity-tested). UNDER OVERFLOW the two drop different tokens: here
    capacity is enforced per (expert, source-device) slice — the
    GShard-style static dispatch shape that keeps the all_to_all regular —
    while the reference caps each expert globally in token order. Both are
    valid Switch semantics; don't expect bitwise agreement when routing is
    skewed and capacity is tight.

    `data_axis`: composes ep with data parallelism on a 2-D mesh — tokens
    shard over (data_axis, axis_name) jointly, the all_to_all rides the
    expert axis within each data row, and the load-balancing loss means
    over both axes (the network path ParallelWrapper drives).

    x: (N, D) tokens (flatten (B, T, D) first); stacked_params: pytree with
    leading expert dim E == mesh axis size; router_w: (D, E).
    """
    E = mesh.shape[axis_name]
    leaf = jax.tree_util.tree_leaves(stacked_params)[0]
    if leaf.shape[0] != E:
        raise ValueError(f"{leaf.shape[0]} experts but mesh axis "
                         f"'{axis_name}' has size {E}")
    dp = mesh.shape.get(data_axis, 1) if data_axis else 1
    N, D = x.shape
    if N % (E * dp):
        raise ValueError(f"token count {N} not divisible by expert axis "
                         f"{E} x data axis {dp}")
    # capacity derives from the tokens ONE DATA ROW routes among E experts
    # (dp=1 reduces to the global formula)
    capacity = int(np.ceil(N / dp / E * capacity_factor))
    # per-device capacity slice must be whole
    capacity = int(np.ceil(capacity / E) * E)
    if passthrough not in ("identity", "zero"):
        raise ValueError(f"unknown passthrough {passthrough!r}")
    reduce_axes = (data_axis, axis_name) if dp > 1 else axis_name

    def local(stage_p, x_local, rw):
        # x_local: (N/E, D) this device's token shard; stage_p: this
        # device's expert params (leading dim 1)
        p = jax.tree.map(lambda a: a[0], stage_p)
        probs = router_probs(x_local, rw)              # (n, E)
        expert_idx = jnp.argmax(probs, axis=-1)
        gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]
        cap_local = capacity // E  # per (expert, source-device) slots
        pos, keep = _dispatch_indices(expert_idx, E, cap_local)
        safe_pos = jnp.where(keep, pos, 0)
        buf = jnp.zeros((E, cap_local, x_local.shape[1]), x_local.dtype)
        buf = buf.at[expert_idx, safe_pos].add(
            jnp.where(keep[:, None], x_local, 0.0))
        # all_to_all: (E, cap_local, D) -> expert e's device receives every
        # source's slice for e: (E_src, cap_local, D) concat on axis 0
        recv = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
        out = expert_fn(p, recv.reshape(-1, recv.shape[-1]))
        out = out.reshape(E, cap_local, -1)
        back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
        y_expert = back[expert_idx, safe_pos]
        dropped = (x_local if passthrough == "identity"
                   else jnp.zeros_like(x_local))
        y = jnp.where(keep[:, None], gate[:, None] * y_expert, dropped)
        frac = jnp.mean(jax.nn.one_hot(expert_idx, E), axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(lax.pmean(frac, reduce_axes)
                          * lax.pmean(mean_prob, reduce_axes))
        return y, aux

    tok = P((data_axis, axis_name)) if dp > 1 else P(axis_name)
    y, aux = shard_map(local, mesh=mesh,
                       in_specs=(P(axis_name), tok, P()),
                       out_specs=(tok, P()), check_vma=False)(
        stacked_params, x, router_w)
    return y, aux


def switch_ffn_sharded(params, tokens: jnp.ndarray, mesh: Mesh, *,
                       axis_name: str, data_axis: Optional[str],
                       act: Callable, capacity_factor: float,
                       aux_weight: float, train: bool = False,
                       passthrough: str = "identity") -> jnp.ndarray:
    """Expert-PARALLEL twin of `switch_ffn`: same stacked router/W1/b1/W2/b2
    params and aux-loss contract, dispatch through `moe_apply`'s
    all_to_all over `axis_name` (composing with data parallelism over
    `data_axis`). This is the network-step path MoELayer(expert_axis=...)
    takes under ParallelWrapper."""
    from deeplearning4j_tpu.ops.aux_loss import add_aux_loss

    def expert_fn(p, t):
        return act(t @ p["W1"] + p["b1"]) @ p["W2"] + p["b2"]

    stacked = {"W1": params["W1"], "b1": params["b1"],
               "W2": params["W2"], "b2": params["b2"]}
    y, aux = moe_apply(expert_fn, stacked, tokens, params["router"], mesh,
                       axis_name=axis_name, data_axis=data_axis,
                       capacity_factor=capacity_factor,
                       passthrough=passthrough)
    if train:
        add_aux_loss(aux_weight * aux)
    return y


def switch_ffn(params, tokens: jnp.ndarray, *, act: Callable,
               capacity_factor: float, aux_weight: float,
               token_mask=None, train: bool = False,
               passthrough: str = "identity") -> jnp.ndarray:
    """Shared Switch-MoE FFN dispatch used by MoELayer and
    TransformerBlock's MoE branch (one implementation, one behavior):
    params needs router/W1/b1/W2/b2 (experts stacked on axis 0); the
    load-balancing aux loss is contributed via ops/aux_loss when training."""
    from deeplearning4j_tpu.ops.aux_loss import add_aux_loss

    def expert_fn(p, t):
        return act(t @ p["W1"] + p["b1"]) @ p["W2"] + p["b2"]

    stacked = {"W1": params["W1"], "b1": params["b1"],
               "W2": params["W2"], "b2": params["b2"]}
    y, aux = moe_apply_reference(expert_fn, stacked, tokens,
                                 params["router"],
                                 capacity_factor=capacity_factor,
                                 token_mask=token_mask,
                                 passthrough=passthrough)
    if train:
        add_aux_loss(aux_weight * aux)
    return y


# -- top-k dropless routing, with the experts held here --------------------
# The layer is told which experts it holds, `experts_held = (first,
# count)`: it routes over ALL `n_experts`, and computes the gated outputs
# of its own `count` experts only. On one chip of a deployment that
# splits a layer's experts over several, that partial sum is the chip's
# part of the layer; what the other chips' experts would add is left out
# (their exchange is the all-to-all above, not run here). Dropless: there
# is no capacity, so no routing can lose a token.


# `chosen_mask` ranks every lane against every other lane of its row
# while that takes fewer than this many compares over all its rows
# (rows x lanes^2), and marks the largest in rounds from there on, whose
# work is k x rows x lanes. On a v5e the rank is as quick or quicker up
# to (512, 72) and (128, 160), 2.7 M and 3.3 M compares (3.7 against
# 13.7 us, 4.4 against 4.9), and the rounds from (128, 8, 64) and
# (512, 128) on, 4.2 M and 8.4 M (1.4 against 5.6 us, 8.1 against 39.7;
# 6.3 against 49.4 at (128, 512)); the form taken runs 4 to 120 times
# quicker than the sort it replaces and compiles to a twentieth to four
# fifths of it (`tools/router_choice_bench.py`; PERF.md section 6,
# PR 51).
RANK_COMPARES = 1 << 22


def _total_order(scores: jnp.ndarray) -> jnp.ndarray:
    """float32 -> int32 whose signed order is the floats' TOTAL order
    (-NaN < -inf < ... < -0 < +0 < ... < +inf < +NaN), what `lax.top_k`
    ranks by."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _by_rank(key: jnp.ndarray, k: int) -> jnp.ndarray:
    """A lane is chosen where fewer than `k` lanes of its row lie ahead
    of it (a larger key, or the same key at a lower index): one
    compare-and-count over (..., E, E), whose program does not grow
    with `k`."""
    E = key.shape[-1]
    mine, other = key[..., :, None], key[..., None, :]
    shape = key.shape + (E,)
    i = lax.broadcasted_iota(jnp.int32, shape, key.ndim - 1)
    j = lax.broadcasted_iota(jnp.int32, shape, key.ndim)
    ahead = (other > mine) | ((other == mine) & (j < i))
    return jnp.sum(ahead, axis=-1, dtype=jnp.int32) < k


def _by_rounds(key: jnp.ndarray, k: int) -> jnp.ndarray:
    """`k` rounds, each marking the FIRST largest key among the lanes
    not yet marked, rolled into one loop: the program holds one round,
    whatever `k`. Two rounds or fewer are written out (a loop holds as
    much, and one whose carry has three axes compiles to seven times
    that on the TPU)."""
    E = key.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)

    def mark(_, chosen):
        top = jnp.max(jnp.where(chosen, jnp.iinfo(jnp.int32).min, key),
                      axis=-1, keepdims=True)
        first = jnp.min(jnp.where(~chosen & (key >= top), lane, E),
                        axis=-1, keepdims=True)
        return chosen | (lane == first)

    return lax.fori_loop(0, k, mark, jnp.zeros(key.shape, bool),
                         unroll=k <= 2)


def ranks(shape) -> bool:
    """Whether `chosen_mask` ranks scores of `shape` (rounds else)."""
    return math.prod(shape) * shape[-1] < RANK_COMPARES


@functools.partial(jax.jit, static_argnums=1)
def chosen_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """(..., E) float32 scores -> (..., E) bool, true on the `min(k, E)`
    lanes of each row that `lax.top_k(scores, k)[1]` names, for any
    input: the larger score first in the floats' total order, equal
    scores to the lower lane, a -inf lane only once fewer than `k`
    others are left. No sort (the TPU's `top_k` is a full stable one),
    no index array to gather by or to scatter to. The form follows the
    static shape alone: a rank by compare-and-count while rows x E^2
    stays under `RANK_COMPARES`, rounds of first-maximum from there. A
    `jit` entry, so that a program's routed layers trace it once a shape
    and not once a layer (tracing is paid on every start, cache or
    not)."""
    key = _total_order(scores)
    # graftlint: disable=recompile  the form is MEANT to be baked in by
    # the shape; a program's routers have a handful of shapes, each
    # traced once
    if ranks(key.shape):
        return _by_rank(key, k)
    return _by_rounds(key, min(k, key.shape[-1]))


def topk_gates(logits: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """(N, E) router logits -> (N, E) float32 gates: softmax over each
    row's `top_k` largest logits (`chosen_mask`; the sum runs over the
    chosen in lane order), zero elsewhere."""
    logits = logits.astype(jnp.float32)
    return jax.nn.softmax(
        jnp.where(chosen_mask(logits, top_k), logits, -jnp.inf), axis=-1)


def sigmoid_topk_gates(logits: jnp.ndarray, bias: jnp.ndarray, top_k: int,
                       scale: float, n_groups: int = 1,
                       topk_groups: int = 1) -> jnp.ndarray:
    """(N, E) router logits -> (N, E) float32 gates, scored by sigmoid
    (the DeepSeek-V3 `noaux_tc` / `NemotronH` router): the `top_k`
    experts are chosen on `sigmoid(logits) + bias` (`bias` (E,), the
    score-correction bias: it moves the choice and never the weight),
    and a chosen expert's gate is its UNBIASED score over the chosen
    scores' sum (in lane order), times `scale`; zero elsewhere. With
    `n_groups` > 1 the choice is made among each row's `topk_groups`
    best groups only, a group scored by the SUM of its two largest
    biased scores (`group_limited`); a biased score may be negative, so
    what lies outside the kept groups is set to -inf, not 0, and no
    choice leaves them."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    choose_on = s + bias.astype(jnp.float32)
    if n_groups > 1:
        choose_on = group_limited(choose_on, n_groups, topk_groups, best=2,
                                  fill=-jnp.inf)
    top_s = jnp.where(chosen_mask(choose_on, top_k), s, 0.0)
    return top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * scale


def group_limited(scores: jnp.ndarray, n_groups: int, topk_groups: int,
                  best: int = 1, fill: float = 0.0) -> jnp.ndarray:
    """Device-limited routing's choice of groups (DeepSeek-V2,
    arXiv:2405.04434 section 2.1.3): the E experts lie in `n_groups`
    groups of `E / n_groups` (expert `e` in group `e // (E / n_groups)`),
    a group's score is the sum of the `best` largest scores in it (1:
    the LARGEST, the published `group_limited_greedy`; 2: DeepSeek-V3's
    `noaux_tc`), each row keeps its `topk_groups` best groups
    (`chosen_mask`) and every other group's scores are set to `fill` (0
    where the scores are >= 0; -inf where they may be negative).
    `scores` (N, E) float32."""
    N, E = scores.shape
    with jax.named_scope("moe.groups"):
        by_group = scores.reshape(N, n_groups, E // n_groups)
        of_group = jnp.max(by_group, axis=-1) if best == 1 else jnp.sum(
            jnp.where(chosen_mask(by_group, best), by_group, 0.0), axis=-1)
        keep = chosen_mask(of_group, topk_groups)
        return jnp.where(keep[:, :, None], by_group, fill).reshape(N, E)


def softmax_all_topk_gates(logits: jnp.ndarray, bias: jnp.ndarray,
                           top_k: int, scale: float, n_groups: int = 1,
                           topk_groups: int = 1) -> jnp.ndarray:
    """(N, E) router logits -> (N, E) float32 gates, scored by a
    softmax over ALL the logits (the LongCat-Flash router, and
    DeepSeek-V2's): the `top_k` experts are chosen on `softmax(logits) +
    bias` (`bias` (E,): it moves the choice and never the weight), and a
    chosen expert's gate is its unbiased score times `scale`, NOT
    renormalised over the chosen; zero elsewhere. With `n_groups` > 1
    the choice is made among each row's `topk_groups` best groups only
    (`group_limited`)."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    choose_on = s + bias.astype(jnp.float32)
    if n_groups > 1:
        choose_on = group_limited(choose_on, n_groups, topk_groups)
    return jnp.where(chosen_mask(choose_on, top_k), s * scale, 0.0)


def check_groups(scoring: str, n_experts: int, n_groups: int,
                 topk_groups: int, n_zero: int = 0) -> None:
    """Refuse a choice of groups the routers here are not written for:
    the group rules ("softmax_all": a group's score its largest;
    "sigmoid": the sum of its two largest biased scores) are over real
    experts alone, and "softmax" (over the chosen) has none."""
    if n_groups == 1:
        return
    if scoring not in ("softmax_all", "sigmoid") or n_zero:
        raise ValueError(
            f"n_groups {n_groups} with scoring {scoring!r} and {n_zero} "
            "zero-compute experts: groups are chosen under 'softmax_all' "
            "(by their largest score) or 'sigmoid' (by the sum of their "
            "two largest), over real experts only")
    if n_groups < 1 or n_experts % n_groups \
            or not 1 <= topk_groups <= n_groups:
        raise ValueError(
            f"{n_experts} experts in {n_groups} groups, {topk_groups} "
            "chosen: the groups are equal and at least one is chosen")


def routed_gates(logits: jnp.ndarray, top_k: int, *, bias=None,
                 scale: float = 1.0, scoring: str = None,
                 n_groups: int = 1, topk_groups: int = 1) -> jnp.ndarray:
    """(N, E) float32 gates over everything the router scores, by
    `scoring`: "softmax" (over the chosen: `topk_gates`), "sigmoid"
    (`sigmoid_topk_gates`) or "softmax_all"
    (`softmax_all_topk_gates`); None: sigmoid where there is a `bias`,
    else softmax. `n_groups`, `topk_groups`: the experts' groups and how
    many of them a row may reach (1: no groups)."""
    scoring = scoring or ("softmax" if bias is None else "sigmoid")
    check_groups(scoring, logits.shape[1], n_groups, topk_groups)
    if scoring == "softmax":
        return topk_gates(logits, top_k)
    if scoring == "sigmoid":
        return sigmoid_topk_gates(logits, bias, top_k, scale, n_groups,
                                  topk_groups)
    return softmax_all_topk_gates(logits, bias, top_k, scale, n_groups,
                                  topk_groups)


def held_gates(logits: jnp.ndarray, top_k: int, experts_held, *,
               bias=None, scale: float = 1.0,
               scoring: str = None) -> jnp.ndarray:
    """The gates of the experts held here, (N, count): a column of
    zeros for a held expert that no token chose. `scoring`, `bias` and
    `scale` as `routed_gates`."""
    first, count = experts_held
    return routed_gates(logits, top_k, bias=bias, scale=scale,
                        scoring=scoring)[:, first:first + count]


def grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd, act: str = GATED_SILU):
    """sum_e gates[:, e] * expert_e(x) over the experts held, as batched
    products: every token meets every held expert and the gate (zero
    where the router did not choose it) weighs the result. `x` (N, d);
    `gates` (N, E) float32; products accumulate in float32.

    `act` "gated_silu": expert_e(x) = (silu(x Wg[e]) * (x Wu[e])) Wd[e],
    `Wg`, `Wu` (E, d, f), `Wd` (E, f, d). "relu2": ungated,
    expert_e(x) = relu(x Wu[e]^T)^2 Wd[e]; there is no `Wg` (None) and
    the up matrices are held (E, f, d) like the down matrices, so that a
    width `f` off the 128-lane grid lies on sublanes in both
    (`ops/pallas_moe_experts.py`)."""
    scale = jnp.swapaxes(gates, 0, 1)[..., None]
    if act == RELU2:
        u = jnp.einsum("nd,efd->enf", x, Wu,
                       preferred_element_type=jnp.float32)
        h = jnp.square(jax.nn.relu(u)) * scale
    else:
        g = jnp.einsum("nd,edf->enf", x, Wg,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("nd,edf->enf", x, Wu,
                       preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * u * scale
    y = jnp.einsum("enf,efd->nd", h.astype(x.dtype), Wd,
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def sort_by_expert(gates, k: int, tile: int, tiles: int = 0):
    """The choices behind `gates` (N, E) (at most `k` a row not zero),
    sorted by expert with each expert's rows padded to whole tiles of
    `tile`: (rows (M,) int32, the token each sorted row is (0 on
    padding); gs (M, 1) float32, its gate (0 on padding); tile_expert
    (M / tile,) int32; n_used (1,) int32, the tiles that hold rows; back
    (N, k) int32, where each of a token's `k` choices lies among the
    sorted rows (a choice it did not make: the last row, whose gate is
    0); fits () bool). `tiles` is the static M / tile. 0: N * k in whole
    tiles + E, more than the rows take if every choice is made and every
    expert's last tile is all but empty, so the last tile never holds a
    row, no routing overflows M and `fits` is true. Fewer (with a `k`
    under the router's top-k: `pallas_moe_experts.sorted_bound`): `fits`
    says whether no row made more than `k` choices and the rows left the
    last tile free; where it is false the other outputs are not to be
    used."""
    N, E = gates.shape
    tiles = tiles or -(-N * k // tile) + E
    M = tiles * tile
    vals, idx = lax.top_k(gates, k)
    expert = jnp.where(vals != 0, idx, E).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    by_expert = expert[order]
    counts = jnp.sum(expert[:, None] == jnp.arange(E, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    first = jnp.concatenate([ends - padded, ends[-1:]])       # (E + 1,)
    first_unpadded = jnp.concatenate([jnp.cumsum(counts) - counts,
                                      jnp.sum(counts, dtype=jnp.int32)[None]])
    # a choice not made goes to the last row: past every expert's rows
    dest = jnp.where(by_expert < E, first[by_expert]
                     + jnp.arange(N * k, dtype=jnp.int32)
                     - first_unpadded[by_expert], M - 1)
    # (a scatter drops what lies past a smaller M: `fits` is false then)
    rows = jnp.zeros((M,), jnp.int32).at[dest].set(order // k)
    gs = jnp.zeros((M,), jnp.float32).at[dest].set(
        jnp.where(by_expert < E, vals.reshape(-1)[order], 0.0))
    n_used = ends[-1:] // tile
    tile_expert = jnp.searchsorted(
        ends, jnp.arange(tiles, dtype=jnp.int32) * tile, side="right")
    last = jnp.searchsorted(ends, ends[-1] - 1, side="right")
    tile_expert = jnp.minimum(tile_expert, last).astype(jnp.int32)
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.minimum(dest, M - 1))
    fits = (n_used[0] < tiles) \
        & (jnp.max(jnp.sum(gates != 0, axis=1)) <= k)
    return rows, gs[:, None], tile_expert, n_used.astype(jnp.int32), \
        back.reshape(N, k), fits


class _Declined(Exception):
    """The sorted kernel cannot serve, said while a branch was traced."""


def sorted_expert_ffn_or_none(x, gates, Wg, Wu, Wd, k: int,
                              act: str = GATED_SILU, tiles: int = 0,
                              walk=None):
    """`grouped_expert_ffn_xla`'s sum through the sorted kernel of
    `ops/pallas_moe_experts.py`: every choice one row, the rows sorted by
    expert, each expert's FFN over its own rows only, and a token's rows
    added back in float32, as they left the kernel: one rounding a
    token, the walk's. `k`, `tiles`: the static size of the sorted rows
    (`sort_by_expert`); under the worst case's, `walk()` gives the same
    sum another way and a `lax.cond` takes it for the routing that does
    not fit. None where the kernel cannot serve."""
    from deeplearning4j_tpu.ops.pallas_moe_experts import (
        SORTED_ROWS,
        moe_experts_sorted_or_none,
    )

    rows, gs, tile_expert, n_used, back, fits = sort_by_expert(
        gates, k, SORTED_ROWS, tiles)

    def product():
        ys = moe_experts_sorted_or_none(x[rows], gs, tile_expert, n_used,
                                        Wg, Wu, Wd, act)
        if ys is None:
            raise _Declined
        # choice-major: (k, N, d) sums over its leading axis tile by tile
        return jnp.sum(ys[back.T], axis=0).astype(x.dtype)

    try:
        return product() if walk is None else lax.cond(fits, product, walk)
    except _Declined:
        return None


def grouped_expert_ffn(x, gates, Wg, Wu, Wd, hit, act: str = GATED_SILU,
                       top_k: int = 0, router_width: int = 0):
    """The grouped product behind the kernel-dispatch contract: the
    Pallas kernels of `ops/pallas_moe_experts.py` on a TPU, the batched
    XLA products over every expert elsewhere. A decode step's rows walk
    the experts that `hit` (E,) bool marks (each one's weights streamed
    through VMEM once, the others left in HBM); a prefill's rows, each
    with at most `top_k` choices among the `router_width` experts the
    router scores, go sorted by expert instead wherever that multiplies
    clearly fewer rows (`pallas_moe_experts.sorted_serves`: shapes
    alone decide), at the static size `sorted_bound` gives and with the
    walk for the routing that overflows it. A row whose gate is not zero
    on an unmarked expert comes out without that expert's part."""
    from deeplearning4j_tpu.ops.pallas_moe_experts import (
        moe_experts_or_none,
        sorted_bound,
        sorted_serves,
        sorted_worst,
    )

    def walk():
        out = moe_experts_or_none(x, gates, Wg, Wu, Wd, hit, act)
        return grouped_expert_ffn_xla(x, gates, Wg, Wu, Wd, act) \
            if out is None else out

    N, E = gates.shape
    k = min(top_k, E)
    if not sorted_serves(N, E, k, router_width):
        return walk()
    tiles, kk = sorted_bound(N, E, k, router_width)
    out = sorted_expert_ffn_or_none(
        x, gates, Wg, Wu, Wd, kk, act, tiles,
        walk if (tiles, kk) != sorted_worst(N, E, k) else None)
    return walk() if out is None else out


def gated_mlp(x, Wg, Wu, Wd):
    """(silu(x Wg) * (x Wu)) Wd: the shared expert, and any gated MLP."""
    g = jnp.dot(x, Wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, Wu, preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), Wd,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def relu2_mlp(x, Wu, Wd):
    """relu(x Wu)^2 Wd: the ungated shared expert (`Wu` (d, f))."""
    u = jnp.dot(x, Wu, preferred_element_type=jnp.float32)
    return jnp.dot(jnp.square(jax.nn.relu(u)).astype(x.dtype), Wd,
                   preferred_element_type=jnp.float32).astype(x.dtype)


class RouteCounts(NamedTuple):
    """What one routed block counted over the rows under its
    `count_mask`."""
    experts: jnp.ndarray        # int32 (2, held): chosen, read
    rows_local: jnp.ndarray     # int32 (): rows that chose a held expert
    zero: Optional[jnp.ndarray]  # int32 (): choices on zero experts


def dropless_moe(x, router, Wg, Wu, Wd, *, top_k: int, experts_held,
                 count_mask=None, act: str = GATED_SILU, router_bias=None,
                 routed_scale: float = 1.0, scoring: str = None,
                 n_zero: int = 0, n_groups: int = 1, topk_groups: int = 1):
    """Top-k dropless routing over `router.shape[1]` experts, computed
    for the experts held. `x` (N, d). `act` and the matrices as
    `grouped_expert_ffn_xla`; `router_bias`, `routed_scale` and
    `scoring` as `routed_gates`. The router's last `n_zero` outputs are
    zero-compute experts: one that a token chose adds the token itself
    under its gate, `(sum of its chosen zero experts' gates) * x`. That
    part is the chip's own tokens', whatever share of the real experts
    it holds, and is never exchanged. `count_mask` (N,) bool says which
    rows anyone will
    read (a decode step's active slots; None: all): an expert that none
    of them chose is not read, and a masked-out row comes out without
    it. Returns (y (N, d), counts): with a `count_mask`, `counts` is
    int32 (2, count), how many of the masked-in tokens chose each held
    expert, and whether the grouped product was told to read it (with
    `n_zero`, the pair of that and an int32 scalar: how many of the
    masked-in tokens' choices fell on zero experts); else None."""
    first, count = experts_held
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
        all_gates = routed_gates(logits, top_k, bias=router_bias,
                                 scale=routed_scale, scoring=scoring,
                                 n_groups=n_groups, topk_groups=topk_groups)
        gates = all_gates[:, first:first + count]
        # every router's gates are >= 0: not zero is chosen
        chose = gates != 0
        if count_mask is not None:
            chose &= count_mask[:, None]
        hit = jnp.any(chose, axis=0)
    with jax.named_scope("moe.experts"):
        y = grouped_expert_ffn(x, gates, Wg, Wu, Wd, hit, act, top_k,
                               router.shape[1])
    if n_zero:
        with jax.named_scope("moe.zero"):
            zero_gates = all_gates[:, all_gates.shape[1] - n_zero:]
            y = y + (jnp.sum(zero_gates, axis=1, keepdims=True)
                     * x.astype(jnp.float32)).astype(y.dtype)
    if count_mask is None:
        return y, None
    return y, RouteCounts(
        jnp.stack([jnp.sum(chose, axis=0), hit]).astype(jnp.int32),
        jnp.sum(jnp.any(chose, axis=1), dtype=jnp.int32),
        jnp.sum((zero_gates != 0) & count_mask[:, None], dtype=jnp.int32)
        if n_zero else None)
