"""The `ling_flash` family (Hugging Face `model_type` `bailing_hybrid`:
Ling-3.0-flash; `layer_group_size` - 1 delta-rule layers with a decay a
key channel (KDA) to one latent-attention (MLA) layer with full-rank
queries and a gate a head, the first `first_k_dense_replace`
feed-forwards a dense gated-silu MLP, the others sigmoid-routed experts
chosen among a token's best groups plus a shared expert; RMSNorm, an
untied bias-free head) as this repo runs it: how a configuration file's
sizes become the program's network, and the weights every run makes from
its seed.

As in `deepseek_v2`, the weights are the benchmark's: one jitted call
per layer draws every leaf from the seed on the device, and the same
arrays feed the program's net and, later, the plain reference. They are
held in bfloat16, the precision the configuration states for parameters
(the router's correction bias in float32, as the program's router kind
keeps it); the reference up-casts them where it uses them. The leaves
carry the reference's names; `to_program` renames them.

`num_experts` in a configuration file is the number of experts HELD by
the chip the cell stands for (one of the router's groups), and
`vocab_size` the slice of the vocabulary it holds; the router keeps the
published width `deployment.num_experts_published`, its groups and its
choices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.families.gpt_dense import seed_key

LINEAR, FULL = "linear_attention", "full_attention"
TOP_LEAVES = ("emb", "lnf", "head")
_KDA = ("an", "Win", "conv", "A", "fb", "on", "Wout", "fn")
_MLA = ("an", "Wqn", "Wqr", "Wkvc", "Wkr", "kvn", "Wkb", "Wvb", "Wa", "Wo",
        "fn")
_DENSE = ("Wg", "Wu", "Wd")
_MOE = ("router", "rb", "eWg", "eWu", "eWd", "sWg", "sWu", "sWd")
# the program's names for the reference's leaves (`DecoderBlock`)
PROGRAM_NAMES = {
    "an": "n1_w", "fn": "n2_w",
    "Win": "mx_Win", "conv": "mx_conv_w", "A": "mx_A_log",
    "fb": "mx_dt_bias", "on": "mx_norm_w", "Wout": "mx_Wout",
    "Wqn": "mx_Wqn", "Wqr": "mx_Wqr", "Wkvc": "mx_Wkvc", "Wkr": "mx_Wkr",
    "kvn": "mx_kvn_w", "Wkb": "mx_Wkb", "Wvb": "mx_Wvb", "Wa": "mx_Wa",
    "Wo": "mx_Wo",
    "Wg": "ff_Wg", "Wu": "ff_Wu", "Wd": "ff_Wd", "router": "ff_router",
    "rb": "ff_router_b", "eWg": "ff_Wg", "eWu": "ff_Wu", "eWd": "ff_Wd",
    "sWg": "ff_sWg", "sWu": "ff_sWu", "sWd": "ff_sWd"}


def layer_leaves(sz: dict, i: int) -> tuple:
    """The names of layer `i`'s leaves: its mixer's, then its
    feed-forward's."""
    mixer = _MLA if sz["layer_types"][i] == FULL else _KDA
    return mixer + (_DENSE if i < sz["L_dense"] else _MOE)


def sizes(cfg: dict) -> dict:
    """The sizes the family needs, under short names; every value is
    hashable (the jitted draws take them as static arguments). `L` is
    the number of layers, `L_moe` of them routed, `mla_sub_layers` the
    latent-attention layers, `layer_types` each layer's mixer, `lh`,
    `lk`, `lv` the linear layers' heads and head sizes, and `f` the
    routed experts' width: what the rooflines price."""
    if cfg["use_bias"] or cfg["use_qkv_bias"] or cfg["hidden_act"] != "silu" \
            or cfg["tie_word_embeddings"]:
        raise ValueError("the family runs bias-free projections, "
                         "gated-silu feed-forwards and an untied head")
    if cfg["topk_method"] != "noaux_tc" \
            or cfg["score_function"] != "sigmoid" \
            or not cfg["norm_topk_prob"] \
            or not cfg["moe_router_enable_expert_bias"]:
        raise ValueError("the family routes by noaux_tc over sigmoid scores "
                         "with a correction bias, renormalised")
    if not cfg["kda_safe_gate"] or not cfg["no_kda_lora"] \
            or not cfg["linear_silu"] or cfg["group_norm_size"] != 1:
        raise ValueError("the family runs full-rank KDA under the safe "
                         "gate, silu after its convolution and a norm a "
                         "head")
    if cfg["q_lora_rank"] is not None or cfg["rope_scaling"] is not None \
            or not cfg["rope_interleave"] \
            or cfg["gated_attention_proj_granularity_type"] != "head_wise":
        raise ValueError("the family runs full-rank queries, interleaved "
                         "unscaled rotary and a head_wise attention gate")
    L, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    clamps = cfg["expert_swiglu_limit_list"][:L] \
        + cfg["share_expert_swiglu_limit_list"][:L]
    if any(clamps):
        raise ValueError("a layer held clamps its swiglu: the family "
                         "writes none")
    dep = cfg.get("deployment", {})
    n_experts = int(dep.get("num_experts_published", cfg["num_experts"]))
    held = (int(dep.get("experts_held_first", 0)), int(cfg["num_experts"]))
    if held[0] + held[1] > n_experts:
        raise ValueError(f"experts held {held} lie outside the router's "
                         f"{n_experts} experts")
    period = int(cfg["layer_group_size"])
    types = tuple(FULL if (i + 1) % period == 0 else LINEAR
                  for i in range(L))
    return {"d": int(cfg["hidden_size"]), "L": L, "L_dense": dense,
            "L_moe": L - dense, "period": period, "layer_types": types,
            "mla_sub_layers": sum(1 for t in types if t == FULL),
            "H": int(cfg["num_attention_heads"]),
            "kr": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]),
            "theta": float(cfg["rope_theta"]),
            "lh": int(cfg["num_attention_heads"]),
            "lk": int(cfg["head_dim"]), "lv": int(cfg["head_dim"]),
            "conv": int(cfg["short_conv_kernel_size"]),
            "gate_lower": float(cfg["kda_lower_bound"]),
            "ffn": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"]),
            "shared": int(cfg["num_shared_experts"])
            * int(cfg["moe_shared_expert_intermediate_size"]),
            "E": n_experts, "held": held,
            "groups": int(cfg["n_group"]),
            "topk_groups": int(cfg["topk_group"]),
            "topk": int(cfg["num_experts_per_tok"]),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "V": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"])}


def _leaf_shapes(sz: dict) -> dict:
    d, H, E = sz["d"], sz["H"], sz["held"][1]
    qw, vw = sz["lh"] * sz["lk"], sz["lh"] * sz["lv"]
    return {"emb": (sz["V"], d), "lnf": (d,), "head": (d, sz["V"]),
            "an": (d,), "fn": (d,),
            # KDA's six projections side by side: [q | k | v | gate | f | b]
            "Win": (d, 2 * qw + vw + vw + qw + sz["lh"]),
            "conv": (2 * qw + vw, sz["conv"]),
            "A": (sz["lh"],), "fb": (qw,), "on": (sz["lv"],),
            "Wout": (vw, d),
            # full-rank q_proj's columns, every head's nope and rope part
            "Wqn": (d, H * sz["nope"]), "Wqr": (d, H * sz["rope"]),
            # kv_a_proj_with_mqa's columns: the latent and the rope key
            "Wkvc": (d, sz["kr"]), "Wkr": (d, sz["rope"]),
            "kvn": (sz["kr"],),
            # kv_b_proj by head: the key part as its Linear stores it
            # (out, in) and the value part (in, out)
            "Wkb": (H, sz["nope"], sz["kr"]), "Wvb": (H, sz["kr"], sz["vd"]),
            "Wa": (d, H), "Wo": (H * sz["vd"], d),
            "Wg": (d, sz["ffn"]), "Wu": (d, sz["ffn"]), "Wd": (sz["ffn"], d),
            "router": (d, sz["E"]), "rb": (sz["E"],),
            "eWg": (E, d, sz["f"]), "eWu": (E, d, sz["f"]),
            "eWd": (E, sz["f"], d),
            "sWg": (d, sz["shared"]), "sWu": (d, sz["shared"]),
            "sWd": (sz["shared"], d)}


def _draw(key, name: str, shape: tuple):
    """Normal 0.02 for every matrix (the head is untied; the router's
    logits over a normed token then have a deviation of 0.02 sqrt(d), 1.0
    at d 2560: sigmoid scores spread over 0.1-0.9). The RMSNorm gains
    (the stream's, the key/value latent's, the linear heads' output) 1 +
    0.1 z, so that a fault in applying one shows. The correction bias
    0.05 z: larger than the gap between a token's eighth and ninth
    score, so it moves choices. The convolution's taps normal 1/2 (four
    taps: a unit-variance sum). KDA's decay: `A` 0.1 z (exp(A) about 1)
    and the channel bias -3 + 0.5 z, so that a log decay -5 sigmoid(f -
    3) spreads from -0.03 (f = -2) over -0.24 (f = 0) to -1.3 (f = 2) a
    position: channels that remember thirty positions beside channels
    that forget in one."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "rb":
        return 0.05 * z
    if name == "conv":
        return 0.5 * z
    if name == "A":
        return 0.1 * z
    if name == "fb":
        return -3.0 + 0.5 * z
    return 1.0 + 0.1 * z if len(shape) == 1 else 0.02 * z


@functools.partial(jax.jit, static_argnames=("names", "sz_items"))
def _make_leaves(key, names: tuple, sz_items: tuple):
    shapes = _leaf_shapes(dict(sz_items))
    return {n: _draw(jax.random.fold_in(key, i), n, shapes[n])
            .astype(jnp.float32 if n == "rb" else jnp.bfloat16)
            for i, n in enumerate(names)}


_DRAWN: dict = {}  # (seed, sizes) -> the last tree drawn


def make_weights(seed: int, sz: dict, layout: str = "layers",
                 dtype=jnp.bfloat16) -> dict:
    """Every leaf of the model from `seed`, on the device, one jitted
    call a layer: `{"emb", "lnf", "head", "layers": [one dict a
    layer]}` (`layout` is accepted for the harness's sake). A second
    call for the same seed and sizes hands back the SAME arrays, as
    `deepseek_v2.make_weights` does and for its reason: the program
    reads its parameters and never donates them, and a second 9.5 GB
    does not fit beside the first."""
    if dtype != jnp.bfloat16:
        raise ValueError("the family holds its parameters in bfloat16")
    key, items = seed_key(seed), tuple(sorted(sz.items()))
    if (int(seed), items) in _DRAWN:
        return _DRAWN[int(seed), items]
    out = _make_leaves(jax.random.fold_in(key, 0), TOP_LEAVES, items)
    out["layers"] = [
        _make_leaves(jax.random.fold_in(key, 1 + i), layer_leaves(sz, i),
                     items)
        for i in range(sz["L"])]
    _DRAWN.clear()
    _DRAWN[int(seed), items] = out
    return out


# ----------------------------------------------------------- the program
def build_net(sz: dict, *, training: bool, learning_rate: float = 3e-4,
              remat: bool = False, dtype=jnp.bfloat16):
    """The program's own network for these sizes:
    `ling_flash_configuration` through `MultiLayerNetwork`, parameters
    and compute in bfloat16 (no float32 masters: `cast_blocks` is the
    identity)."""
    from deeplearning4j_tpu.models.transformer import (
        ling_flash_configuration,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = ling_flash_configuration(
        sz["V"], sz["d"], sz["L"], layer_group_size=sz["period"],
        n_heads=sz["H"], kv_rank=sz["kr"], nope_dim=sz["nope"],
        rope_dim=sz["rope"], v_dim=sz["vd"], rope_theta=sz["theta"],
        linear_heads=sz["lh"], linear_key_dim=sz["lk"],
        linear_value_dim=sz["lv"], linear_conv=sz["conv"],
        gate_lower_bound=sz["gate_lower"], n_dense_layers=sz["L_dense"],
        ffn_width=sz["ffn"], n_experts=sz["E"], top_k=sz["topk"],
        expert_width=sz["f"], shared_width=sz["shared"],
        routed_scale=sz["route_scale"], n_groups=sz["groups"],
        topk_groups=sz["topk_groups"], experts_held=sz["held"],
        eps=sz["eps"], learning_rate=learning_rate,
        updater=Updater.ADAM if training else Updater.SGD)
    return MultiLayerNetwork(conf, dtype=dtype)


def to_program(weights: dict) -> list:
    """The tree as the program's per-layer parameter list."""
    layers = [{PROGRAM_NAMES[n]: v for n, v in layer.items()}
              for layer in weights["layers"]]
    return ([{"W": weights["emb"]}] + layers
            + [{"gamma": weights["lnf"]}, {"W": weights["head"]}])


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
