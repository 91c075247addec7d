"""The `deepseek_v2` family (Hugging Face `DeepseekV2`: every layer a
latent-attention (MLA) sub-layer with YaRN-stretched rotary and a
feed-forward, the first `first_k_dense_replace` layers' a dense
gated-silu MLP, the others' routed experts chosen among a token's best
groups (device-limited routing) plus shared experts; RMSNorm, an untied
bias-free head) as this repo runs it: how a configuration file's sizes
become the program's network, and the weights every run makes from its
seed.

As in `longcat_flash`, the weights are the benchmark's: one jitted call
per layer draws every leaf from the seed on the device, and the same
arrays feed the program's net and, later, the plain reference. They are
held in bfloat16, the precision the configuration states for parameters;
the reference up-casts them where it uses them. The leaves carry the
reference's names; `to_program` renames them and adds the zero
correction bias the program's router kind keeps and the published router
does not have.

`n_routed_experts` in a configuration file is the number of experts HELD
by the chip the cell stands for (one of the router's groups), and
`vocab_size` the slice of the vocabulary it holds; the router keeps the
published width `deployment.n_routed_experts_published`, its groups and
its choices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense import seed_key

TOP_LEAVES = ("emb", "lnf", "head")
_MLA = ("an", "Wqa", "qn", "Wqn", "Wqr", "Wkvc", "Wkr", "kvn", "Wkb", "Wvb",
        "Wo", "fn")
DENSE_LEAVES = _MLA + ("Wg", "Wu", "Wd")
MOE_LEAVES = _MLA + ("router", "eWg", "eWu", "eWd", "sWg", "sWu", "sWd")
# the program's names for the reference's leaves (`DecoderBlock`)
PROGRAM_NAMES = {
    "an": "n1_w", "Wqa": "mx_Wqa", "qn": "mx_qn_w", "Wqn": "mx_Wqn",
    "Wqr": "mx_Wqr", "Wkvc": "mx_Wkvc", "Wkr": "mx_Wkr", "kvn": "mx_kvn_w",
    "Wkb": "mx_Wkb", "Wvb": "mx_Wvb", "Wo": "mx_Wo", "fn": "n2_w",
    "Wg": "ff_Wg", "Wu": "ff_Wu", "Wd": "ff_Wd", "router": "ff_router",
    "eWg": "ff_Wg", "eWu": "ff_Wu", "eWd": "ff_Wd", "sWg": "ff_sWg",
    "sWu": "ff_sWu", "sWd": "ff_sWd"}


def sizes(cfg: dict) -> dict:
    """The sizes the family needs, under short names; every value is
    hashable (the jitted draws take them as static arguments). `L` is
    the number of layers, `L_moe` of them routed, `mla_sub_layers` the
    latent-attention sub-layers (one a layer), and `f` the routed
    experts' width: what the rooflines price."""
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu" \
            or cfg["moe_layer_freq"] != 1 or cfg["tie_word_embeddings"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the family runs bias-free latent attention, "
                         "gated-silu feed-forwards, experts in every layer "
                         "after the dense ones and an untied head")
    if cfg["topk_method"] != "group_limited_greedy" \
            or cfg["scoring_func"] != "softmax" or cfg["norm_topk_prob"]:
        raise ValueError("the family routes by group_limited_greedy over "
                         "softmax scores, not renormalised")
    dep = cfg.get("deployment", {})
    n_experts = int(dep.get("n_routed_experts_published",
                            cfg["n_routed_experts"]))
    held = (int(dep.get("experts_held_first", 0)),
            int(cfg["n_routed_experts"]))
    if held[0] + held[1] > n_experts:
        raise ValueError(f"experts held {held} lie outside the router's "
                         f"{n_experts} experts")
    rs = cfg.get("rope_scaling")
    if rs is not None and rs["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {rs['type']!r}: the "
                         "family writes 'yarn' only")
    L, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return {"d": int(cfg["hidden_size"]), "L": L, "L_dense": dense,
            "L_moe": L - dense, "mla_sub_layers": L,
            "H": int(cfg["num_attention_heads"]),
            "qr": int(cfg["q_lora_rank"]), "kr": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]),
            "theta": float(cfg["rope_theta"]),
            "yarn": None if rs is None else (
                float(rs["factor"]),
                int(rs["original_max_position_embeddings"]),
                float(rs["beta_fast"]), float(rs["beta_slow"]),
                float(rs["mscale"]), float(rs["mscale_all_dim"])),
            "ffn": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"]),
            "shared": int(cfg["n_shared_experts"])
            * int(cfg["moe_intermediate_size"]),
            "E": n_experts, "held": held,
            "groups": int(cfg["n_group"]),
            "topk_groups": int(cfg["topk_group"]),
            "topk": int(cfg["num_experts_per_tok"]),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "V": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"])}


def _leaf_shapes(sz: dict) -> dict:
    d, H, E = sz["d"], sz["H"], sz["held"][1]
    return {"emb": (sz["V"], d), "lnf": (d,), "head": (d, sz["V"]),
            "an": (d,), "Wqa": (d, sz["qr"]), "qn": (sz["qr"],),
            # q_b_proj's columns, every head's nope part and rope part
            "Wqn": (sz["qr"], H * sz["nope"]),
            "Wqr": (sz["qr"], H * sz["rope"]),
            # kv_a_proj_with_mqa's columns: the latent and the rope key
            "Wkvc": (d, sz["kr"]), "Wkr": (d, sz["rope"]),
            "kvn": (sz["kr"],),
            # kv_b_proj by head: the key part as its Linear stores it
            # (out, in) and the value part (in, out)
            "Wkb": (H, sz["nope"], sz["kr"]), "Wvb": (H, sz["kr"], sz["vd"]),
            "Wo": (H * sz["vd"], d), "fn": (d,),
            "Wg": (d, sz["ffn"]), "Wu": (d, sz["ffn"]), "Wd": (sz["ffn"], d),
            "router": (d, sz["E"]),
            "eWg": (E, d, sz["f"]), "eWu": (E, d, sz["f"]),
            "eWd": (E, sz["f"], d),
            "sWg": (d, sz["shared"]), "sWu": (d, sz["shared"]),
            "sWd": (sz["shared"], d)}


def _draw(key, name: str, shape: tuple):
    """Normal 0.02 for every matrix (the head is untied, so the
    embedding needs no smaller draw than the rest; the router's logits
    over a normed token then have a deviation of 0.02 sqrt(d), 1.4 at d
    5120: scores that differ enough for the groups' largest to differ,
    and no expert starved); the RMSNorm gains (the stream's, the query
    latent's and the key/value latent's) 1 + 0.1 z, so that a fault in
    applying one shows."""
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + 0.1 * z if len(shape) == 1 else 0.02 * z


@functools.partial(jax.jit, static_argnames=("names", "sz_items"))
def _make_leaves(key, names: tuple, sz_items: tuple):
    shapes = _leaf_shapes(dict(sz_items))
    return {n: _draw(jax.random.fold_in(key, i), n, shapes[n])
            .astype(jnp.bfloat16) for i, n in enumerate(names)}


_DRAWN: dict = {}  # (seed, sizes) -> the last tree drawn


def make_weights(seed: int, sz: dict, layout: str = "layers",
                 dtype=jnp.bfloat16) -> dict:
    """Every leaf of the model from `seed`, on the device, one jitted
    call a layer: `{"emb", "lnf", "head", "layers": [one dict a
    layer]}` (`layout` is accepted for the harness's sake). A second
    call for the same seed and sizes hands back the SAME arrays, as
    `longcat_flash.make_weights` does and for its reason: the program
    reads its parameters and never donates them, and a second 6.3 GB
    does not fit beside the first and the pool."""
    if dtype != jnp.bfloat16:
        raise ValueError("the family holds its parameters in bfloat16")
    key, items = seed_key(seed), tuple(sorted(sz.items()))
    if (int(seed), items) in _DRAWN:
        return _DRAWN[int(seed), items]
    out = _make_leaves(jax.random.fold_in(key, 0), TOP_LEAVES, items)
    out["layers"] = [
        _make_leaves(jax.random.fold_in(key, 1 + i),
                     DENSE_LEAVES if i < sz["L_dense"] else MOE_LEAVES, items)
        for i in range(sz["L"])]
    _DRAWN.clear()
    _DRAWN[int(seed), items] = out
    return out


# ----------------------------------------------------------- the program
def rope_scaling(sz: dict):
    """The program's rotary-scaling kind for these sizes, as its JSON."""
    if sz["yarn"] is None:
        return None
    names = ("factor", "original_max", "beta_fast", "beta_slow", "mscale",
             "mscale_all_dim")
    return dict(zip(names, sz["yarn"]), kind="yarn")


def build_net(sz: dict, *, training: bool, learning_rate: float = 3e-4,
              remat: bool = False, dtype=jnp.bfloat16):
    """The program's own network for these sizes:
    `deepseek_v2_configuration` through `MultiLayerNetwork`, parameters
    and compute in bfloat16 (no float32 masters: `cast_blocks` is the
    identity)."""
    from deeplearning4j_tpu.models.transformer import (
        deepseek_v2_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = deepseek_v2_configuration(
        sz["V"], sz["d"], sz["L"], n_heads=sz["H"], q_rank=sz["qr"],
        kv_rank=sz["kr"], nope_dim=sz["nope"], rope_dim=sz["rope"],
        v_dim=sz["vd"], rope_theta=sz["theta"],
        rope_scaling=rope_scaling(sz), n_dense_layers=sz["L_dense"],
        ffn_width=sz["ffn"], n_experts=sz["E"], top_k=sz["topk"],
        expert_width=sz["f"], shared_width=sz["shared"],
        routed_scale=sz["route_scale"], n_groups=sz["groups"],
        topk_groups=sz["topk_groups"], experts_held=sz["held"],
        eps=sz["eps"], learning_rate=learning_rate,
        updater=Updater.ADAM if training else Updater.SGD)
    return MultiLayerNetwork(conf, dtype=dtype)


def to_program(weights: dict) -> list:
    """The tree as the program's per-layer parameter list. The program's
    router kind keeps a float32 correction bias (it moves the choice and
    never the weight); the published router has none: a zero leaf."""
    layers = []
    for layer in weights["layers"]:
        p = {PROGRAM_NAMES[n]: v for n, v in layer.items()}
        if "router" in layer:
            p["ff_router_b"] = jnp.zeros((layer["router"].shape[1],),
                                         jnp.float32)
        layers.append(p)
    return ([{"W": weights["emb"]}] + layers
            + [{"gamma": weights["lnf"]}, {"W": weights["head"]}])


def install(net, weights: dict) -> None:
    """Give a net the benchmark's weights and a fresh optimizer and layer
    state by writing the three fields `MultiLayerNetwork.init()` fills
    (`granite_hybrid.install`: `init()` would draw 6.3 GB of its own
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
