"""The dense pre-LN GPT family as this repo runs it: how a configuration
file's sizes become the program's network, and the weights every run makes
from its seed.

The weights are the benchmark's, not the program's: one jitted call draws
every leaf from the seed on the device, and the same call (same keys, same
bits) feeds the program's net and, later, the plain reference. A family is
named by a configuration file's `family` key; a later PR adds a model of
another family by adding a module beside this one.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

BLOCK_LEAVES = ("ln1_g", "ln1_b", "Wqkv", "bqkv", "Wo", "bo",
                "ln2_g", "ln2_b", "W1", "b1", "W2", "b2")
TOP_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b", "head_w", "head_b")


def sizes(cfg: dict) -> dict:
    """The sizes the family needs, under the source's own key names."""
    d, H = int(cfg["n_embd"]), int(cfg["n_head"])
    if d % H:
        raise ValueError(f"n_embd {d} is not a multiple of n_head {H}")
    ffn = int(cfg["n_inner"])
    if ffn % d:
        raise ValueError("the repo's block takes n_inner as a multiple "
                         f"of n_embd; got {ffn} over {d}")
    return {"d": d, "L": int(cfg["n_layer"]), "H": H, "hd": d // H,
            "ffn": ffn, "V": int(cfg["vocab_size"]),
            "P": int(cfg["n_positions"]),
            "eps": float(cfg["layer_norm_epsilon"])}


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also past 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf_shapes(sz: dict) -> dict:
    d, ffn, V, P = sz["d"], sz["ffn"], sz["V"], sz["P"]
    return {"wte": (V, d), "wpe": (P, d), "lnf_g": (d,), "lnf_b": (d,),
            "head_w": (d, V), "head_b": (V,),
            "ln1_g": (d,), "ln1_b": (d,), "Wqkv": (d, 3 * d),
            "bqkv": (3 * d,), "Wo": (d, d), "bo": (d,),
            "ln2_g": (d,), "ln2_b": (d,), "W1": (d, ffn), "b1": (ffn,),
            "W2": (ffn, d), "b2": (d,)}


def _draw(key, name: str, shape: tuple, n_layers: int):
    """GPT-2's initialisation (normal 0.02, residual projections scaled
    by 1/sqrt(2 L)), with gains and biases moved off 1 and 0 so that a
    fault in how they are applied shows in the comparison."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + 0.1 * z
    if name in ("Wo", "W2"):
        return z * (0.02 / (2.0 * n_layers) ** 0.5)
    return z * (0.01 if name == "wpe" else 0.02)


@functools.partial(jax.jit, static_argnames=("sz_items", "layout", "dtype"))
def _make(key, sz_items: tuple, layout: str, dtype):
    sz = dict(sz_items)
    shapes, L = _leaf_shapes(sz), sz["L"]
    names = TOP_LEAVES + BLOCK_LEAVES
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {n: _draw(keys[n], n, shapes[n], L).astype(dtype)
           for n in TOP_LEAVES}
    stacked = {n: _draw(keys[n], n, (L,) + shapes[n], L).astype(dtype)
               for n in BLOCK_LEAVES}
    if layout == "stacked":
        out["blocks"] = stacked
    else:
        out["blocks"] = [{n: stacked[n][i] for n in BLOCK_LEAVES}
                         for i in range(L)]
    return out


def make_weights(seed: int, sz: dict, layout: str = "layers",
                 dtype=jnp.float32) -> dict:
    """Every leaf of the model from `seed`, on the device, in one jitted
    call. `layout="layers"` gives `blocks` as one dict per layer (what
    the program's net holds); `"stacked"` gives each block leaf with a
    leading layer axis (what the reference scans over). The values are
    the same either way."""
    return _make(seed_key(seed), tuple(sorted(sz.items())), layout, dtype)


def per_leaf(tree: dict, sz: dict, fn) -> dict:
    """`fn(leaf)` for every leaf of a `layers`-layout tree, by name."""
    out = {n: fn(tree[n]) for n in TOP_LEAVES}
    for i in range(sz["L"]):
        for n in BLOCK_LEAVES:
            out[f"blocks.{i}.{n}"] = fn(tree["blocks"][i][n])
    return out


# ----------------------------------------------------------- the program
def build_net(sz: dict, *, training: bool,
              learning_rate: float = 3e-4, remat: bool = False):
    """The program's own network for these sizes: `gpt_configuration`
    through `MultiLayerNetwork`, f32 parameters, bf16 compute;
    `init_with_weights` or `install` gives it its state. A serving net
    keeps no optimizer moments (`Updater.SGD` holds no state), as a
    deployment that only serves would not."""
    from deeplearning4j_tpu.models.transformer import gpt_configuration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Updater

    conf = gpt_configuration(
        vocab_size=sz["V"], d_model=sz["d"], n_heads=sz["H"],
        n_layers=sz["L"], max_length=sz["P"],
        ffn_mult=sz["ffn"] // sz["d"], learning_rate=learning_rate,
        updater=Updater.ADAM if training else Updater.SGD, remat=remat)
    return MultiLayerNetwork(conf, compute_dtype=jnp.bfloat16)


def to_program(weights: dict) -> list:
    """A `layers`-layout tree as the program's per-layer parameter list."""
    return ([{"W": weights["wte"], "P": weights["wpe"]}]
            + [dict(b) for b in weights["blocks"]]
            + [{"gamma": weights["lnf_g"], "beta": weights["lnf_b"]},
               {"W": weights["head_w"], "b": weights["head_b"]}])


def from_program(params: list) -> dict:
    """The inverse of `to_program`, over any per-layer list of the same
    structure (parameters, or one optimizer moment of each)."""
    return {"wte": params[0]["W"], "wpe": params[0]["P"],
            "blocks": [dict(p) for p in params[1:-2]],
            "lnf_g": params[-2]["gamma"], "lnf_b": params[-2]["beta"],
            "head_w": params[-1]["W"], "head_b": params[-1]["b"]}


def install(net, weights: dict) -> None:
    """Give a net the benchmark's weights and a fresh optimizer and layer
    state by writing the three fields that `MultiLayerNetwork.init()`
    fills, for a net whose `init()` does not fit the chip: it ends in
    `ravel_pytree(params)`, three times the parameters on the device,
    which fails at 1.42 B float32 parameters on 16 GB (PERF.md, Open
    questions). A net that `init()` can build goes through
    `init_with_weights`."""
    from deeplearning4j_tpu.nn.updater import init_updater_state

    params = to_program(weights)
    net._params = params
    net._upd_state = [
        {name: init_updater_state(layer.updater_cfg, v)
         for name, v in p.items()} if layer.updater_cfg is not None else {}
        for layer, p in zip(net.layers, params)]
    net._layer_state = [layer.init_state(it) for layer, it in
                        zip(net.layers, net._input_types)]


# the program's flat parameter vector, `net.params()` / `set_params()`:
# `ravel_pytree` of the per-layer list, so list order, then each layer's
# names in sorted order
@functools.partial(jax.jit, static_argnames=("sz_items",))
def _make_flat(key, sz_items: tuple):
    return ravel_pytree(to_program(_make(key, sz_items, "layers",
                                         jnp.float32)))[0]


def flat_leaves(sz: dict) -> list:
    """(name, size) of every leaf in the flat vector's order."""
    shapes = _leaf_shapes(sz)
    named = {n: (n, shapes[n]) for n in TOP_LEAVES}
    named["blocks"] = [{n: (f"blocks.{i}.{n}", shapes[n])
                        for n in BLOCK_LEAVES} for i in range(sz["L"])]
    leaves = jax.tree_util.tree_leaves(
        to_program(named), is_leaf=lambda x: isinstance(x, tuple))
    return [(name, math.prod(shape)) for name, shape in leaves]


def init_with_weights(net, seed: int, sz: dict) -> None:
    """The public way to a net that holds given weights: `init()`, then
    `set_params()` with the flat vector, which one jitted call draws from
    the seed (the same keys and bits as `make_weights`)."""
    net.init()
    net.set_params(_make_flat(seed_key(seed), tuple(sorted(sz.items()))))


@functools.partial(jax.jit, static_argnames=("sizes",))
def _delta_norms(flat, base, sizes: tuple):
    out, at = [], 0
    for n in sizes:
        out.append(jnp.sqrt(jnp.sum(jnp.square(flat[at:at + n]
                                               - base[at:at + n]))))
        at += n
    return jnp.stack(out)


def change_norms(net, seed: int, sz: dict) -> dict:
    """L2 norm, leaf by leaf, of how far the net's parameters
    (`net.params()`, the public flat vector) have moved from the seed's
    weights."""
    leaves = flat_leaves(sz)
    base = _make_flat(seed_key(seed), tuple(sorted(sz.items())))
    norms = _delta_norms(jnp.asarray(net.params()), base,
                         tuple(n for _, n in leaves))
    return {name: float(v) for (name, _), v in
            zip(leaves, jax.device_get(norms))}


ADAM_B1 = 0.9  # of `Updater.ADAM`, which `build_net` gives a training net


def first_gradient_norms(net, sz: dict) -> dict:
    """After the net's first step: L2 norm, leaf by leaf, of the gradient
    as the optimizer got it. Adam's first moment after one step is
    (1 - b1) * gradient."""
    moments = from_program([{n: s["m"] for n, s in layer.items()}
                            for layer in net.get_updater_state()])
    norms = jax.device_get(per_leaf(moments, sz, _norm))
    return {n: float(v) / (1.0 - ADAM_B1) for n, v in norms.items()}


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
