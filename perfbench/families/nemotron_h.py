"""The `nemotron_h` family (Hugging Face `NemotronH`: layers of ONE
sub-layer each, by the letters of `hybrid_override_pattern`: `M` a
Mamba-2 mixer with `n_groups` B/C groups, `*` grouped-query attention
whose heads have their own `head_dim`, `E` sigmoid-routed ungated relu^2
experts plus a shared one; RMSNorm, no positions, an untied bias-free
head) as this repo runs it: how a configuration file's sizes become the
program's network, and the weights every run makes from its seed.

As in `granite_hybrid`, the weights are the benchmark's: one jitted call
per layer draws every leaf from the seed on the device, and the same
arrays feed the program's net and, later, the plain reference. They are
held in bfloat16, the precision the configuration states for parameters,
but for the router's score-correction bias, which is float32 as
published; the reference up-casts them a layer at a time. The leaves
carry the reference's names; `to_program` renames them.

`n_routed_experts` in a configuration file is the number of experts
HELD by the chip the cell stands for; the router keeps the published
width `deployment.n_routed_experts_published`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense import seed_key
from perfbench.families.granite_hybrid import _draw as _mamba_draw

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
TOP_LEAVES = ("emb", "lnf", "head")
LAYER_LEAVES = {
    MAMBA: ("n", "Win", "conv_w", "conv_b", "dt_bias", "A_log", "D", "gn",
            "Wout"),
    ATTENTION: ("n", "Wqkv", "Wo"),
    EXPERTS: ("n", "router", "router_b", "Wu", "Wd", "sWu", "sWd")}
FLOAT32_LEAVES = ("router_b",)
# the program's names for the reference's leaves (`DecoderBlock`)
PROGRAM_NAMES = {
    "n": "n1_w", "router": "ff_router", "router_b": "ff_router_b",
    "Wu": "ff_Wu", "Wd": "ff_Wd", "sWu": "ff_sWu", "sWd": "ff_sWd",
    "Win": "mx_Win", "conv_w": "mx_conv_w", "conv_b": "mx_conv_b",
    "dt_bias": "mx_dt_bias", "A_log": "mx_A_log", "D": "mx_D",
    "gn": "mx_norm_w", "Wout": "mx_Wout", "Wqkv": "mx_Wqkv",
    "Wo": "mx_Wo"}


def sizes(cfg: dict) -> dict:
    """The sizes the family needs, under short names; every value is
    hashable (the jitted draws take them as static arguments)."""
    L = int(cfg["num_hidden_layers"])
    pattern = cfg["hybrid_override_pattern"][:L]
    if len(pattern) != L or set(pattern) - set(LAYER_LEAVES):
        raise ValueError("hybrid_override_pattern must name "
                         "num_hidden_layers layers, each M, * or E (the "
                         "family runs no dense `-` layer)")
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    mh, G = int(cfg["mamba_num_heads"]), int(cfg["n_groups"])
    if H % Hkv or mh % G:
        raise ValueError(f"{H} query heads over {Hkv} K/V heads, {mh} "
                         f"Mamba heads over {G} groups: not whole")
    if int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1 \
            or not cfg["norm_topk_prob"] or int(cfg["n_shared_experts"]) != 1:
        raise ValueError("the family routes over one group of experts, "
                         "normalises the chosen scores and has one shared "
                         "expert")
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["mlp_bias"] or cfg["mamba_proj_bias"] or cfg["use_bias"] \
            or not cfg["use_conv_bias"] or cfg["mlp_hidden_act"] != "relu2" \
            or float(cfg["norm_eps"]) != float(cfg["layer_norm_epsilon"]):
        raise ValueError("the family runs an untied head, no projection "
                         "bias, a convolution bias, relu2 experts and one "
                         "epsilon")
    dep = cfg.get("deployment", {})
    n_experts = int(dep.get("n_routed_experts_published",
                            cfg["n_routed_experts"]))
    held = (int(dep.get("experts_held_first", 0)),
            int(cfg["n_routed_experts"]))
    if held[0] + held[1] > n_experts:
        raise ValueError(f"experts held {held} lie outside the router's "
                         f"{n_experts}")
    return {"d": int(cfg["hidden_size"]), "L": L, "pattern": pattern,
            "H": H, "Hkv": Hkv, "hd": int(cfg["head_dim"]),
            "mh": mh, "mp": int(cfg["mamba_head_dim"]),
            "mn": int(cfg["ssm_state_size"]), "mg": G,
            "mk": int(cfg["conv_kernel"]), "mchunk": int(cfg["chunk_size"]),
            "E": n_experts, "held": held,
            "topk": int(cfg["num_experts_per_tok"]),
            "f": int(cfg["moe_intermediate_size"]),
            "fs": int(cfg["moe_shared_expert_intermediate_size"]),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "V": int(cfg["vocab_size"]),
            "eps": float(cfg["layer_norm_epsilon"])}


def _leaf_shapes(sz: dict) -> dict:
    d, f, fs, E = sz["d"], sz["f"], sz["fs"], sz["held"][1]
    di = sz["mh"] * sz["mp"]
    cw = di + 2 * sz["mg"] * sz["mn"]
    qw, kvw = sz["H"] * sz["hd"], sz["Hkv"] * sz["hd"]
    return {"emb": (sz["V"], d), "lnf": (d,), "head": (d, sz["V"]),
            "n": (d,), "router": (d, sz["E"]), "router_b": (sz["E"],),
            # an expert's up matrix as a Linear(d, f) stores it: (f, d)
            "Wu": (E, f, d), "Wd": (E, f, d), "sWu": (d, fs),
            "sWd": (fs, d),
            # [z | xBC | dt]
            "Win": (d, di + cw + sz["mh"]), "conv_w": (cw, sz["mk"]),
            "conv_b": (cw,), "dt_bias": (sz["mh"],), "A_log": (sz["mh"],),
            "D": (sz["mh"],), "gn": (di,), "Wout": (di, d),
            "Wqkv": (d, qw + 2 * kvw), "Wo": (qw, d)}


def _draw(key, name: str, shape: tuple):
    """Normal 0.02 for every matrix (the head is untied, so the
    embedding needs no smaller draw than the rest). As in
    `granite_hybrid`, the gains and `D` are drawn 1 + 0.1 z, the
    convolution normal 0.5 with bias 0.1 z, `dt_bias` and `A_log` by
    Mamba-2's own initialisation. The router's score-correction bias is
    drawn 0.02 z: about the distance between neighbouring scores at the
    sixth rank of 128, so it changes which experts are chosen for a good
    part of the tokens and a fault in applying it shows in the
    comparison, while the load stays near the balance a trained bias is
    there to keep (drawn 0.1 z, half the scores' own spread of 0.21, it
    sent every token to the same few experts: 56% of the held experts
    hit a step where 85% are at 0.02; my chip run and sandbox
    simulation, PR 36)."""
    if name in ("dt_bias", "A_log"):
        return _mamba_draw(key, name, shape)
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("n", "lnf", "gn", "D"):
        return 1.0 + 0.1 * z
    return {"conv_w": 0.5, "conv_b": 0.1}.get(name, 0.02) * z


@functools.partial(jax.jit, static_argnames=("names", "sz_items"))
def _make_leaves(key, names: tuple, sz_items: tuple):
    shapes = _leaf_shapes(dict(sz_items))
    return {n: _draw(jax.random.fold_in(key, i), n, shapes[n]).astype(
                jnp.float32 if n in FLOAT32_LEAVES else jnp.bfloat16)
            for i, n in enumerate(names)}


_DRAWN: dict = {}  # (seed, sizes) -> the last tree drawn


def make_weights(seed: int, sz: dict, layout: str = "layers",
                 dtype=jnp.bfloat16) -> dict:
    """Every leaf of the model from `seed`, on the device, one jitted
    call a layer: `{"emb", "lnf", "head", "layers": [one dict a
    layer]}` (`layout` is accepted for the harness's sake). A second
    call for the same seed and sizes hands back the SAME arrays, as
    `granite_hybrid.make_weights` does and for its reason: the program
    reads its parameters and never donates them, and a second 11.3 GB
    does not fit beside the first."""
    if dtype != jnp.bfloat16:
        raise ValueError("the family holds its parameters in bfloat16")
    key, items = seed_key(seed), tuple(sorted(sz.items()))
    if (int(seed), items) in _DRAWN:
        return _DRAWN[int(seed), items]
    out = _make_leaves(jax.random.fold_in(key, 0), TOP_LEAVES, items)
    out["layers"] = [
        _make_leaves(jax.random.fold_in(key, 1 + i), LAYER_LEAVES[kind],
                     items)
        for i, kind in enumerate(sz["pattern"])]
    _DRAWN.clear()
    _DRAWN[int(seed), items] = out
    return out


# ----------------------------------------------------------- the program
def build_net(sz: dict, *, training: bool, learning_rate: float = 3e-4,
              remat: bool = False, dtype=jnp.bfloat16):
    """The program's own network for these sizes:
    `hybrid_sublayer_configuration` through `MultiLayerNetwork`,
    parameters and compute in bfloat16 (no float32 masters:
    `cast_blocks` is the identity)."""
    from deeplearning4j_tpu.models.transformer import (
        hybrid_sublayer_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = hybrid_sublayer_configuration(
        sz["V"], sz["d"], sz["pattern"], n_heads=sz["H"],
        n_kv_heads=sz["Hkv"], head_dim=sz["hd"], mamba_heads=sz["mh"],
        mamba_head_dim=sz["mp"], mamba_state=sz["mn"],
        mamba_groups=sz["mg"], mamba_conv=sz["mk"],
        mamba_chunk=sz["mchunk"], n_experts=sz["E"], top_k=sz["topk"],
        expert_width=sz["f"], shared_width=sz["fs"],
        routed_scale=sz["route_scale"], experts_held=sz["held"],
        eps=sz["eps"], learning_rate=learning_rate,
        updater=Updater.ADAM if training else Updater.SGD)
    return MultiLayerNetwork(conf, dtype=dtype)


def to_program(weights: dict) -> list:
    """The tree as the program's per-layer parameter list."""
    return ([{"W": weights["emb"]}]
            + [{PROGRAM_NAMES[n]: v for n, v in layer.items()}
               for layer in weights["layers"]]
            + [{"gamma": weights["lnf"]}, {"W": weights["head"]}])


def install(net, weights: dict) -> None:
    """Give a net the benchmark's weights and a fresh optimizer and layer
    state by writing the three fields `MultiLayerNetwork.init()` fills
    (`granite_hybrid.install`: `init()` would draw 11.3 GB of its own
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
