"""The `olmo_hybrid` family (Hugging Face `model_type` `olmo_hybrid`:
gated delta-rule layers and full-attention layers with QK-norm, each
followed by a dense gated MLP, post-norm blocks under RMSNorm, no
positions, an untied bias-free head) as this repo runs it: how a
configuration file's sizes become the program's network, and the weights
every run makes from its seed.

As in `granite_hybrid`, the weights are the benchmark's: one jitted call
per layer draws every leaf from the seed on the device, and the same
arrays feed the program's net and, later, the plain reference. They are
held in bfloat16, the precision the configuration states for parameters;
the reference up-casts them a layer at a time. The leaves carry the
reference's names; `to_program` renames them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense import seed_key
from perfbench.families.granite_hybrid import _draw as _mamba_draw

LINEAR, FULL = "linear_attention", "full_attention"
TOP_LEAVES = ("emb", "lnf", "head")
FFN_LEAVES = ("n1", "n2", "Wg", "Wu", "Wd")
MIXER_LEAVES = {
    LINEAR: ("Win", "conv_w", "dt_bias", "A_log", "gn", "Wout"),
    FULL: ("Wqkv", "qn", "kn", "Wo")}
# the program's names for the reference's leaves (`DecoderBlock`)
PROGRAM_NAMES = {
    "n1": "n1_w", "n2": "n2_w", "Wg": "ff_Wg", "Wu": "ff_Wu",
    "Wd": "ff_Wd", "Win": "mx_Win", "conv_w": "mx_conv_w",
    "dt_bias": "mx_dt_bias", "A_log": "mx_A_log", "gn": "mx_norm_w",
    "Wout": "mx_Wout", "Wqkv": "mx_Wqkv", "qn": "mx_qn_w",
    "kn": "mx_kn_w", "Wo": "mx_Wo"}


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
                         f"each {LINEAR} or {FULL}")
    if int(cfg["num_key_value_heads"]) != H \
            or int(cfg["linear_num_key_heads"]) \
            != int(cfg["linear_num_value_heads"]):
        raise ValueError("the family runs as many K/V heads as query "
                         "heads, in both kinds of layer")
    if cfg.get("rope_parameters", {}).get("rope_theta") is not None \
            or cfg["tie_word_embeddings"] or cfg["attention_bias"]:
        raise ValueError("the family runs no rotary, no tied head and no "
                         "attention bias")
    return {"d": d, "L": L, "layer_types": types, "H": H, "hd": d // H,
            "lh": int(cfg["linear_num_value_heads"]),
            "lk": int(cfg["linear_key_head_dim"]),
            "lv": int(cfg["linear_value_head_dim"]),
            "lconv": int(cfg["linear_conv_kernel_dim"]),
            "neg_eigval": bool(cfg["linear_allow_neg_eigval"]),
            "f": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"])}


def _leaf_shapes(sz: dict) -> dict:
    d, f, H = sz["d"], sz["f"], sz["lh"]
    qw, vw = H * sz["lk"], H * sz["lv"]
    return {"emb": (sz["V"], d), "lnf": (d,), "head": (d, sz["V"]),
            "n1": (d,), "n2": (d,), "Wg": (d, f), "Wu": (d, f),
            "Wd": (f, d),
            # [q | k | v | gate | a | b]
            "Win": (d, 2 * qw + 2 * vw + 2 * H),
            "conv_w": (2 * qw + vw, sz["lconv"]), "dt_bias": (H,),
            "A_log": (H,), "gn": (sz["lv"],), "Wout": (vw, d),
            "Wqkv": (d, 3 * d), "qn": (d,), "kn": (d,), "Wo": (d, d)}


def _draw(key, name: str, shape: tuple):
    """Normal 0.02 for every matrix (the head is untied, so the
    embedding needs no smaller draw than the rest). Gains (block norms,
    QK-norms, the gate's per-head norm, the trailing norm) are drawn 1 +
    0.1 z and the convolution normal 0.5, so that a fault in how each is
    applied shows in the comparison. `dt_bias` and `A_log` are
    `granite_hybrid`'s draws (Mamba-2's own initialisation, which `fla`'s
    gated delta-net layer shares)."""
    if name in ("dt_bias", "A_log"):
        return _mamba_draw(key, name, shape)
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("n1", "n2", "lnf", "gn", "qn", "kn"):
        return 1.0 + 0.1 * z
    return (0.5 if name == "conv_w" else 0.02) * z


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
    call a layer: `{"emb", "lnf", "head", "layers": [one dict a
    layer]}` (`layout` is accepted for the harness's sake). A second
    call for the same seed and sizes hands back the SAME arrays, as
    `granite_hybrid.make_weights` does and for its reason: the program
    reads its parameters and never donates them, and a second 8.2 GB
    does not fit beside whatever the served run has not let go of."""
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
    `hybrid_linear_configuration` through `MultiLayerNetwork`, parameters
    and compute in bfloat16 (no float32 masters: `cast_blocks` is the
    identity)."""
    from deeplearning4j_tpu.models.transformer import (
        hybrid_linear_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = hybrid_linear_configuration(
        sz["V"], sz["d"], sz["layer_types"], n_heads=sz["H"],
        linear_heads=sz["lh"], linear_key_dim=sz["lk"],
        linear_value_dim=sz["lv"], linear_conv=sz["lconv"],
        allow_neg_eigval=sz["neg_eigval"], ffn_width=sz["f"],
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
    (`granite_hybrid.install`: `init()` would draw 8.2 GB of its own
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
