"""The `longcat_flash` family (Hugging Face `LongcatFlash`: one layer is
TWO latent-attention (MLA) sub-layers, TWO dense gated-silu FFNs and ONE
routed block on a shortcut, whose router scores real experts and
zero-compute experts in one softmax; RMSNorm, rotary on the rope
dimensions, an untied bias-free head) as this repo runs it: how a
configuration file's sizes become the program's network, and the weights
every run makes from its seed.

As in `nemotron_h`, the weights are the benchmark's: one jitted call per
layer draws every leaf from the seed on the device, and the same arrays
feed the program's net and, later, the plain reference. They are held in
bfloat16, the precision the configuration states for parameters, but for
the router's correction bias, which is float32; the reference up-casts
them where it uses them. The leaves carry the reference's names;
`to_program` renames them.

`n_routed_experts` in a configuration file is the number of real experts
HELD by the chip the cell stands for, and `vocab_size` the slice of the
vocabulary it holds; the router keeps the published width
`deployment.n_routed_experts_published` + `zero_expert_num`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense import seed_key

TOP_LEAVES = ("emb", "lnf", "head")
_PAIR = ("an", "Wqa", "qn", "Wqn", "Wqr", "Wkvc", "Wkr", "kvn", "Wkb", "Wvb",
         "Wo", "fn", "Wg", "Wu", "Wd")
LAYER_LEAVES = tuple(f"{n}{i}" for i in (0, 1) for n in _PAIR) \
    + ("router", "router_b", "eWg", "eWu", "eWd")
FLOAT32_LEAVES = ("router_b",)
# the program's names for the reference's leaves (`ShortcutDecoderBlock`)
_PAIR_NAMES = {"an": "n1_w", "Wqa": "mx_Wqa", "qn": "mx_qn_w",
               "Wqn": "mx_Wqn", "Wqr": "mx_Wqr", "Wkvc": "mx_Wkvc",
               "Wkr": "mx_Wkr", "kvn": "mx_kvn_w", "Wkb": "mx_Wkb",
               "Wvb": "mx_Wvb", "Wo": "mx_Wo", "fn": "n2_w",
               "Wg": "ff_Wg", "Wu": "ff_Wu", "Wd": "ff_Wd"}
PROGRAM_NAMES = {
    **{f"{n}{i}": f"{pre}{name}" for i, pre in ((0, "a_"), (1, "b_"))
       for n, name in _PAIR_NAMES.items()},
    "router": "sc_router", "router_b": "sc_router_b", "eWg": "sc_Wg",
    "eWu": "sc_Wu", "eWd": "sc_Wd"}


def sizes(cfg: dict) -> dict:
    """The sizes the family needs, under short names; every value is
    hashable (the jitted draws take them as static arguments). `L` is
    the number of layers, which is the number of routed blocks, and `f`
    the routed experts' width: what `harness/moe_roofline.py` prices."""
    if cfg["attention_bias"] or cfg["attention_method"] != "MLA" \
            or cfg["zero_expert_type"] != "identity":
        raise ValueError("the family runs bias-free latent attention and "
                         "zero-compute experts that return their input")
    dep = cfg.get("deployment", {})
    n_experts = int(dep.get("n_routed_experts_published",
                            cfg["n_routed_experts"]))
    held = (int(dep.get("experts_held_first", 0)),
            int(cfg["n_routed_experts"]))
    if held[0] + held[1] > n_experts:
        raise ValueError(f"experts held {held} lie outside the router's "
                         f"{n_experts} real experts")
    return {"d": int(cfg["hidden_size"]), "L": int(cfg["num_layers"]),
            "H": int(cfg["num_attention_heads"]),
            "qr": int(cfg["q_lora_rank"]), "kr": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]),
            "theta": float(cfg["rope_theta"]),
            "scale_q": bool(cfg["mla_scale_q_lora"]),
            "scale_kv": bool(cfg["mla_scale_kv_lora"]),
            "ffn": int(cfg["ffn_hidden_size"]),
            "f": int(cfg["expert_ffn_hidden_size"]),
            "E": n_experts, "Z": int(cfg["zero_expert_num"]), "held": held,
            "topk": int(cfg["moe_topk"]),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "V": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"])}


def _leaf_shapes(sz: dict) -> dict:
    d, H, E = sz["d"], sz["H"], sz["held"][1]
    shapes = {"emb": (sz["V"], d), "lnf": (d,), "head": (d, sz["V"]),
              "router": (d, sz["E"] + sz["Z"]),
              "router_b": (sz["E"] + sz["Z"],),
              "eWg": (E, d, sz["f"]), "eWu": (E, d, sz["f"]),
              "eWd": (E, sz["f"], d)}
    pair = {"an": (d,), "Wqa": (d, sz["qr"]), "qn": (sz["qr"],),
            # q_b_proj's columns, every head's nope part and rope part
            "Wqn": (sz["qr"], H * sz["nope"]),
            "Wqr": (sz["qr"], H * sz["rope"]),
            # kv_a_proj_with_mqa's columns: the latent and the rope key
            "Wkvc": (d, sz["kr"]), "Wkr": (d, sz["rope"]),
            "kvn": (sz["kr"],),
            # kv_b_proj by head: the key part as its Linear stores it
            # (out, in) and the value part (in, out)
            "Wkb": (H, sz["nope"], sz["kr"]), "Wvb": (H, sz["kr"], sz["vd"]),
            "Wo": (H * sz["vd"], d), "fn": (d,), "Wg": (d, sz["ffn"]),
            "Wu": (d, sz["ffn"]), "Wd": (sz["ffn"], d)}
    shapes.update({f"{n}{i}": s for i in (0, 1) for n, s in pair.items()})
    return shapes


ROUTER_BIAS_STD = 1e-3


def _draw(key, name: str, shape: tuple):
    """Normal 0.02 for every matrix (the head is untied, so the
    embedding needs no smaller draw than the rest); the RMSNorm gains
    (the stream's, the query latent's and the key/value latent's) 1 +
    0.1 z, so that a fault in applying one shows. The router's
    correction bias is drawn `ROUTER_BIAS_STD` z: scores are a softmax
    over 768 outputs (mean 1/768 = 0.0013; about 0.011 at the twelfth
    rank, where neighbouring scores lie about 6e-4 apart), so a bias of
    that order changes some choice of most tokens (of 7 in 10 at 1e-3,
    of under half at 5e-4: `tests/test_longcat_flash.py`), leaves nine
    choices in ten as they were, and the load near the balance a trained
    bias is there to keep."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("lnf",) or name[:-1] in ("an", "fn", "qn", "kvn"):
        return 1.0 + 0.1 * z
    return (ROUTER_BIAS_STD if name == "router_b" else 0.02) * z


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
    `nemotron_h.make_weights` does and for its reason: the program reads
    its parameters and never donates them, and a second 10.3 GB does not
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
    """The program's own network for these sizes: `longcat_configuration`
    through `MultiLayerNetwork`, parameters and compute in bfloat16 (no
    float32 masters: `cast_blocks` is the identity)."""
    from deeplearning4j_tpu.models.transformer import longcat_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = longcat_configuration(
        sz["V"], sz["d"], sz["L"], n_heads=sz["H"], q_rank=sz["qr"],
        kv_rank=sz["kr"], nope_dim=sz["nope"], rope_dim=sz["rope"],
        v_dim=sz["vd"], rope_theta=sz["theta"], scale_q_lora=sz["scale_q"],
        scale_kv_lora=sz["scale_kv"], ffn_width=sz["ffn"],
        n_experts=sz["E"], n_zero_experts=sz["Z"], top_k=sz["topk"],
        expert_width=sz["f"], routed_scale=sz["route_scale"],
        experts_held=sz["held"], eps=sz["eps"],
        learning_rate=learning_rate,
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
    (`granite_hybrid.install`: `init()` would draw 10.3 GB of its own
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
