"""The `granitemoehybrid` family (Hugging Face `GraniteMoeHybrid`:
Mamba-2 and position-free grouped-query attention layers, each followed
by top-k routed experts plus a shared expert, RMSNorm, a tied head) as
this repo runs it: how a configuration file's sizes become the program's
network, and the weights every run makes from its seed.

As in `gpt_dense`, the weights are the benchmark's: one jitted call per
layer draws every leaf from the seed on the device, and the same calls
(same keys, same bits) feed the program's net and, later, the plain
reference. They are held in bfloat16, the precision the configuration
states for parameters; the reference up-casts them a layer at a time.
The leaves carry the reference's names; `to_program` renames them.

`num_local_experts` in a configuration file is the number of experts
HELD by the chip the cell stands for; the router keeps the published
width `deployment.num_local_experts_published`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense import seed_key

TOP_LEAVES = ("emb", "lnf")
FFN_LEAVES = ("n2", "router", "Wg", "Wu", "Wd", "sWg", "sWu", "sWd")
MIXER_LEAVES = {
    "mamba": ("n1", "Win", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "gn", "Wout"),
    "attention": ("n1", "Wqkv", "Wo")}
# the program's names for the reference's leaves (`DecoderBlock`)
PROGRAM_NAMES = {
    "n1": "n1_w", "n2": "n2_w", "router": "ff_router", "Wg": "ff_Wg",
    "Wu": "ff_Wu", "Wd": "ff_Wd", "sWg": "ff_sWg", "sWu": "ff_sWu",
    "sWd": "ff_sWd", "Win": "mx_Win", "conv_w": "mx_conv_w",
    "conv_b": "mx_conv_b", "dt_bias": "mx_dt_bias", "A_log": "mx_A_log",
    "D": "mx_D", "gn": "mx_norm_w", "Wout": "mx_Wout",
    "Wqkv": "mx_Wqkv", "Wo": "mx_Wo"}


def sizes(cfg: dict) -> dict:
    """The sizes the family needs, under short names; every value is
    hashable (the jitted draws take them as static arguments)."""
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if d % H:
        raise ValueError(f"hidden_size {d} is not a multiple of "
                         f"num_attention_heads {H}")
    L = int(cfg["num_hidden_layers"])
    types = tuple(cfg["layer_types"])[:L]
    if len(types) != L or set(types) - set(MIXER_LEAVES):
        raise ValueError("layer_types must name num_hidden_layers layers, "
                         "each mamba or attention")
    dep = cfg.get("deployment", {})
    n_experts = int(dep.get("num_local_experts_published",
                            cfg["num_local_experts"]))
    held = (int(dep.get("experts_held_first", 0)),
            int(cfg["num_local_experts"]))
    if held[0] + held[1] > n_experts:
        raise ValueError(f"experts held {held} lie outside the router's "
                         f"{n_experts}")
    mh, mp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if mh * mp != int(cfg["mamba_expand"]) * d \
            or int(cfg["mamba_n_groups"]) != 1:
        raise ValueError("the family runs one B/C group and mamba_n_heads "
                         "* mamba_d_head == mamba_expand * hidden_size")
    return {"d": d, "L": L, "layer_types": types, "H": H,
            "Hkv": int(cfg["num_key_value_heads"]), "hd": d // H,
            "attn_mult": float(cfg["attention_multiplier"]),
            "mh": mh, "mp": mp, "mn": int(cfg["mamba_d_state"]),
            "mk": int(cfg["mamba_d_conv"]),
            "mchunk": int(cfg["mamba_chunk_size"]),
            "E": n_experts, "held": held,
            "topk": int(cfg["num_experts_per_tok"]),
            "f": int(cfg["intermediate_size"]),
            "fs": int(cfg["shared_intermediate_size"]),
            "V": int(cfg["vocab_size"]),
            "emb_mult": float(cfg["embedding_multiplier"]),
            "res_mult": float(cfg["residual_multiplier"]),
            "logit_div": float(cfg["logits_scaling"]),
            "eps": float(cfg["rms_norm_eps"])}


def _leaf_shapes(sz: dict) -> dict:
    d, f, fs, E = sz["d"], sz["f"], sz["fs"], sz["held"][1]
    di = sz["mh"] * sz["mp"]
    cw = di + 2 * sz["mn"]
    kvw = sz["Hkv"] * sz["hd"]
    return {"emb": (sz["V"], d), "lnf": (d,), "n1": (d,), "n2": (d,),
            "router": (d, sz["E"]), "Wg": (E, d, f), "Wu": (E, d, f),
            "Wd": (E, f, d), "sWg": (d, fs), "sWu": (d, fs),
            "sWd": (fs, d),
            "Win": (d, di + cw + sz["mh"]), "conv_w": (cw, sz["mk"]),
            "conv_b": (cw,), "dt_bias": (sz["mh"],), "A_log": (sz["mh"],),
            "D": (sz["mh"],), "gn": (di,), "Wout": (di, d),
            "Wqkv": (d, d + 2 * kvw), "Wo": (d, d)}


def _draw(key, name: str, shape: tuple):
    """Normal 0.02 for every matrix but the embedding, which is drawn at
    0.002: with a tied head and `embedding_multiplier` 12 a larger table
    makes every token's own logit the largest by far (12 d sigma^2
    against sqrt(d) sigma for the others), the model repeats its input
    whatever the layers compute, and the comparison would see nothing.
    Gains, the convolution, `D`, `dt_bias` and `A_log` are moved off
    their trivial values, so that a fault in how each is applied shows
    in the comparison. `dt_bias` is the
    inverse softplus of a step log-uniform in [1e-3, 1e-1] and `A_log`
    the log of a uniform in [1, 16], Mamba-2's own initialisation."""
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("n1", "n2", "lnf", "gn", "D"):
        return 1.0 + 0.1 * z
    if name == "conv_w":
        return 0.5 * z
    if name == "conv_b":
        return 0.1 * z
    return (0.002 if name == "emb" else 0.02) * z


@functools.partial(jax.jit, static_argnames=("names", "sz_items"))
def _make_leaves(key, names: tuple, sz_items: tuple):
    shapes = _leaf_shapes(dict(sz_items))
    return {n: _draw(jax.random.fold_in(key, i), n,
                     shapes[n]).astype(jnp.bfloat16)
            for i, n in enumerate(names)}


_DRAWN: dict = {}  # (seed, sizes) -> the last tree drawn


def make_weights(seed: int, sz: dict, layout: str = "layers",
                 dtype=jnp.bfloat16) -> dict:
    """Every leaf of the model from `seed`, on the device, one jitted
    call a layer: `{"emb", "lnf", "layers": [one dict a layer]}`. The
    layers differ in kind, so there is one layout: the reference walks
    the list (`layout` is accepted for the harness's sake).

    A second call for the same seed and sizes hands back the SAME
    arrays: `harness/serve_cell.py` keeps the served requests' errors,
    and through their tracebacks the server and its 9.9 GB of
    parameters, alive while the reference runs, and a second 9.9 GB does
    not fit beside them (my chip run, PR 28: "Attempting to allocate
    64.00M ... 47.90M free"). The program reads its parameters and never
    donates them, so the bits the reference gets are the seed's."""
    if dtype != jnp.bfloat16:
        raise ValueError("the family holds its parameters in bfloat16")
    key, items = seed_key(seed), tuple(sorted(sz.items()))
    if (int(seed), items) in _DRAWN:
        return _DRAWN[int(seed), items]
    out = _make_leaves(jax.random.fold_in(key, 0), TOP_LEAVES, items)
    out["layers"] = [
        _make_leaves(jax.random.fold_in(key, 1 + i),
                     MIXER_LEAVES[kind] + FFN_LEAVES, items)
        for i, kind in enumerate(sz["layer_types"])]
    _DRAWN.clear()
    _DRAWN[int(seed), items] = out
    return out


# ----------------------------------------------------------- the program
def build_net(sz: dict, *, training: bool, learning_rate: float = 3e-4,
              remat: bool = False, dtype=jnp.bfloat16):
    """The program's own network for these sizes:
    `hybrid_moe_configuration` through `MultiLayerNetwork`, parameters
    and compute in bfloat16 (no float32 masters: `cast_blocks` is the
    identity)."""
    from deeplearning4j_tpu.models.transformer import (
        hybrid_moe_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = hybrid_moe_configuration(
        sz["V"], sz["d"], sz["layer_types"], n_heads=sz["H"],
        n_kv_heads=sz["Hkv"], attention_multiplier=sz["attn_mult"],
        mamba_heads=sz["mh"], mamba_head_dim=sz["mp"],
        mamba_state=sz["mn"], mamba_conv=sz["mk"],
        mamba_chunk=sz["mchunk"], n_experts=sz["E"], top_k=sz["topk"],
        expert_width=sz["f"], shared_width=sz["fs"],
        experts_held=sz["held"], embedding_multiplier=sz["emb_mult"],
        residual_multiplier=sz["res_mult"], logits_scaling=sz["logit_div"],
        eps=sz["eps"], learning_rate=learning_rate,
        updater=Updater.ADAM if training else Updater.SGD)
    return MultiLayerNetwork(conf, dtype=dtype)


def to_program(weights: dict) -> list:
    """The tree as the program's per-layer parameter list."""
    return ([{"W": weights["emb"]}]
            + [{PROGRAM_NAMES[n]: v for n, v in layer.items()}
               for layer in weights["layers"]]
            + [{"gamma": weights["lnf"]}, {}])


def install(net, weights: dict) -> None:
    """Give a net the benchmark's weights and a fresh optimizer and layer
    state by writing the three fields `MultiLayerNetwork.init()` fills:
    `init()` would draw 9.9 GB of weights of its own first and end in
    `ravel_pytree(params)`, which does not fit beside them."""
    from deeplearning4j_tpu.nn.updater import init_updater_state

    params = to_program(weights)
    net._params = params
    net._upd_state = [
        {name: init_updater_state(layer.updater_cfg, v)
         for name, v in p.items()} if layer.updater_cfg is not None else {}
        for layer, p in zip(net.layers, params)]
    net._layer_state = [layer.init_state(it) for layer, it in
                        zip(net.layers, net._input_types)]
