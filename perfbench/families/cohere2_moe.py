"""The `cohere2_moe` family (Hugging Face `model_type` `cohere2_moe`:
Command A+; `layer_switch` - 1 sliding-window attention layers with
rotary to one full-attention layer without positions, attention and the
feed-forward side by side under ONE LayerNorm, grouped queries, sigmoid-
routed experts beside shared experts whose outputs are averaged, a tied
head) as this repo runs it: how a configuration file's sizes become the
program's network, and the weights every run makes from its seed.

As in `ling_flash`, the weights are the benchmark's: one jitted call per
leaf draws it from the seed on the device, and the same arrays
feed the program's net and, later, the plain reference. They are held in
bfloat16, the precision the configuration states for parameters (the
router's correction bias, which this model does not use, as a zero
float32 leaf: the program's sigmoid router kind keeps one); the
reference up-casts them where it uses them. The leaves carry the
reference's names; `to_program` renames them. The fused `Wqkv` is the
three projections' columns side by side, [q | k | v]; `sWg`, `sWu`,
`sWd` hold the `num_shared_experts` shared experts side by side (expert
j the columns, and of `sWd` the rows, `j f .. (j + 1) f`): the
reference slices them apart and averages, the program runs them as one
MLP times 1 / `num_shared_experts`.

`num_experts` in a configuration file is the number of experts HELD by
the chip the cell stands for, and `vocab_size` the slice of the
vocabulary it holds; the router keeps the published width
`deployment.num_experts_published`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense import seed_key

SLIDING, FULL = "sliding_attention", "full_attention"
TOP_LEAVES = ("emb", "lnf")
LAYER_LEAVES = ("ln", "Wqkv", "Wo", "router", "rb", "eWg", "eWu", "eWd",
                "sWg", "sWu", "sWd")
# the program's names for the reference's leaves (`DecoderBlock`)
PROGRAM_NAMES = {
    "ln": "n1_w", "Wqkv": "mx_Wqkv", "Wo": "mx_Wo", "router": "ff_router",
    "rb": "ff_router_b", "eWg": "ff_Wg", "eWu": "ff_Wu", "eWd": "ff_Wd",
    "sWg": "ff_sWg", "sWu": "ff_sWu", "sWd": "ff_sWd"}


def sizes(cfg: dict) -> dict:
    """The sizes the family needs, under short names; every value is
    hashable (the jitted draws take them as static arguments). `L` is
    the number of layers (all routed), `layer_types` each layer's
    attention, `W` the window, `Hkv` and `hd` the K/V heads and their
    size, `f` the routed experts' width: what the rooflines price."""
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu" \
            or not cfg["use_gated_activation"] \
            or not cfg["tie_word_embeddings"] or cfg["use_qk_norm"]:
        raise ValueError("the family runs bias-free projections, "
                         "gated-silu experts, no QK-norm and a tied head")
    if not cfg["use_parallel_block"] \
            or cfg["position_embedding_type"] != "rope_gptj" \
            or cfg["rotary_pct"] != 1 \
            or cfg["order_of_interleaved_layers"] != "local_attn_first":
        raise ValueError("the family runs the parallel block, interleaved "
                         "rotary over the whole head and window layers "
                         "first in each period")
    if cfg["expert_selection_fn"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["shared_expert_combination_strategy"] != "average" \
            or cfg["first_k_dense_replace"] != 0:
        raise ValueError("the family routes on sigmoid scores normalised "
                         "over the chosen, averages its shared experts and "
                         "has no leading dense layer")
    L, period = int(cfg["num_hidden_layers"]), int(cfg["layer_switch"])
    types = tuple(cfg["layer_types"][:L])
    if types != tuple(FULL if (i + 1) % period == 0 else SLIDING
                      for i in range(L)):
        raise ValueError("layer_types is not layer_switch - 1 sliding "
                         "layers to one full layer")
    dep = cfg.get("deployment", {})
    n_experts = int(dep.get("num_experts_published", cfg["num_experts"]))
    held = (int(dep.get("experts_held_first", 0)), int(cfg["num_experts"]))
    if held[0] + held[1] > n_experts:
        raise ValueError(f"experts held {held} lie outside the router's "
                         f"{n_experts} experts")
    return {"d": int(cfg["hidden_size"]), "L": L, "period": period,
            "layer_types": types,
            "window_layers": sum(1 for t in types if t == SLIDING),
            "full_layers": sum(1 for t in types if t == FULL),
            "H": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]), "W": int(cfg["sliding_window"]),
            "theta": float(cfg["rope_theta"]),
            "f": int(cfg["intermediate_size"]),
            "n_shared": int(cfg["num_shared_experts"]),
            "E": n_experts, "held": held,
            "topk": int(cfg["num_experts_per_tok"]),
            "logit_scale": float(cfg["logit_scale"]),
            "V": int(cfg["vocab_size"]),
            "eps": float(cfg["layer_norm_eps"])}


def _leaf_shapes(sz: dict) -> dict:
    d, f, E = sz["d"], sz["f"], sz["held"][1]
    qw, kvw = sz["H"] * sz["hd"], sz["Hkv"] * sz["hd"]
    sf = sz["n_shared"] * f
    return {"emb": (sz["V"], d), "lnf": (d,), "ln": (d,),
            "Wqkv": (d, qw + 2 * kvw), "Wo": (qw, d),
            "router": (d, sz["E"]), "rb": (sz["E"],),
            "eWg": (E, d, f), "eWu": (E, d, f), "eWd": (E, f, d),
            "sWg": (d, sf), "sWu": (d, sf), "sWd": (sf, d)}


# the attention's draws (`_draw`): the deviation of a score and the
# gain on the output projection
SCORE_DEVIATION, OUT_GAIN = 2.0, 8.0


def _draw(key, name: str, shape: tuple, sz: dict):
    """One leaf, or one expert's slice of a stacked leaf, in float32.
    Normal 0.02 for the embedding, the experts and the router (its
    logits over a normed token then have a deviation of 0.02 sqrt(d),
    1.3 at d 4096: sigmoid scores spread over 0.1-0.9; an expert's
    output has entries near 1.3, the feed-forward's sum near 0.65 with
    one chosen expert in eight held). The LayerNorm gains 1 + 0.1 z, so
    that a fault in applying one shows. **Attention is drawn to be a
    material part of the stream AT THE CELL'S CONTEXTS**: over random
    tokens an attention head's output is a weighted mean of some
    thousands of unrelated value rows, which shrinks as 1 / sqrt(the
    rows that carry the weight), so unit queries, keys and values leave
    entries of 0.025 beside the feed-forward's 0.65 and a key let in or
    kept out moves nothing that a comparison can see. `Wqkv` is normal
    1 / sqrt(d) with the query and key columns times
    sqrt(`SCORE_DEVIATION`): scores q.k / sqrt(hd) with a deviation of 2,
    a softmax that puts its weight on a hundred-odd of 4,096 keys (a
    head's output then has entries near 0.1); `Wo` normal `OUT_GAIN` /
    sqrt(fan-in): the attention's output has entries near 1, rather more
    than the feed-forward's. A program that read keys behind the
    window, lost one inside it or turned a full layer's moves the
    logits by about their own size. The router's bias is zero: this
    model has none."""
    if name == "rb":
        return jnp.zeros(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "Wqkv":
        qk = (sz["H"] + sz["Hkv"]) * sz["hd"]  # [q | k | v]: q and k
        gain = jnp.where(jnp.arange(shape[1]) < qk,
                         jnp.sqrt(jnp.float32(SCORE_DEVIATION)), 1.0)
        return z * gain / jnp.sqrt(jnp.float32(shape[0]))
    if name == "Wo":
        return z * (OUT_GAIN / jnp.sqrt(jnp.float32(shape[0])))
    return 1.0 + 0.1 * z if len(shape) == 1 else 0.02 * z


@functools.partial(jax.jit, static_argnames=("name", "sz_items"))
def _make_leaf(key, name: str, sz_items: tuple):
    """One leaf on the device in the dtype it is held in. A stacked leaf
    is drawn an expert at a time, so that the float32 draw beside it is
    one expert's (67 MB) and not the stack's (1.07 GB): set-up's peak is
    then the weights and not the drawing of them."""
    sz = dict(sz_items)
    shape = _leaf_shapes(sz)[name]
    dtype = jnp.float32 if name == "rb" else jnp.bfloat16
    if len(shape) == 3:
        return jax.lax.map(
            lambda k: _draw(k, name, shape[1:], sz).astype(dtype),
            jax.random.split(key, shape[0]))
    return _draw(key, name, shape, sz).astype(dtype)


def _make_leaves(key, names: tuple, sz_items: tuple) -> dict:
    return {n: _make_leaf(jax.random.fold_in(key, i), n, sz_items)
            for i, n in enumerate(names)}


_DRAWN: dict = {}  # (seed, sizes) -> the last tree drawn


def make_weights(seed: int, sz: dict, layout: str = "layers",
                 dtype=jnp.bfloat16) -> dict:
    """Every leaf of the model from `seed`, on the device, one jitted
    call a leaf: `{"emb", "lnf", "layers": [one dict a layer]}`
    (`layout` is accepted for the harness's sake). A second call for the
    same seed and sizes hands back the SAME arrays, as
    `ling_flash.make_weights` does and for its reason: the program reads
    its parameters and never donates them, and a second 9.5 GB does not
    fit beside the first."""
    if dtype != jnp.bfloat16:
        raise ValueError("the family holds its parameters in bfloat16")
    key, items = seed_key(seed), tuple(sorted(sz.items()))
    if (int(seed), items) in _DRAWN:
        return _DRAWN[int(seed), items]
    out = _make_leaves(jax.random.fold_in(key, 0), TOP_LEAVES, items)
    out["layers"] = [
        _make_leaves(jax.random.fold_in(key, 1 + i), LAYER_LEAVES, items)
        for i in range(sz["L"])]
    _DRAWN.clear()
    _DRAWN[int(seed), items] = out
    return out


# ----------------------------------------------------------- the program
def build_net(sz: dict, *, training: bool, learning_rate: float = 3e-4,
              remat: bool = False, dtype=jnp.bfloat16):
    """The program's own network for these sizes:
    `command_a_configuration` through `MultiLayerNetwork`, parameters
    and compute in bfloat16 (no float32 masters: `cast_blocks` is the
    identity)."""
    from deeplearning4j_tpu.models.transformer import (
        command_a_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = command_a_configuration(
        sz["V"], sz["d"], sz["L"], layer_switch=sz["period"],
        window=sz["W"], n_heads=sz["H"], n_kv_heads=sz["Hkv"],
        head_dim=sz["hd"], rope_theta=sz["theta"], n_experts=sz["E"],
        top_k=sz["topk"], expert_width=sz["f"],
        n_shared_experts=sz["n_shared"], shared_width=sz["f"],
        experts_held=sz["held"], logit_scale=sz["logit_scale"],
        eps=sz["eps"], learning_rate=learning_rate,
        updater=Updater.ADAM if training else Updater.SGD)
    return MultiLayerNetwork(conf, dtype=dtype)


def to_program(weights: dict) -> list:
    """The tree as the program's per-layer parameter list (the tied
    head has no leaf of its own)."""
    layers = [{PROGRAM_NAMES[n]: v for n, v in layer.items()}
              for layer in weights["layers"]]
    return ([{"W": weights["emb"]}] + layers
            + [{"gamma": weights["lnf"]}, {}])


def install(net, weights: dict) -> None:
    """Give a net the benchmark's weights and a fresh optimizer and layer
    state by writing the three fields `MultiLayerNetwork.init()` fills
    (`granite_hybrid.install`: `init()` would draw 9.5 GB of its own
    first and end in `ravel_pytree(params)`)."""
    from deeplearning4j_tpu.nn.updater import init_updater_state

    params = to_program(weights)
    net._params = params
    net._upd_state = [
        {name: init_updater_state(layer.updater_cfg, v)
         for name, v in p.items()} if layer.updater_cfg is not None else {}
        for layer, p in zip(net.layers, params)]
    net._layer_state = [layer.init_state(it) for layer, it in
                        zip(net.layers, net._input_types)]
