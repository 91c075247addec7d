"""Plain reference of the `gpt_dense` family: a dense pre-LN GPT-2-style
decoder (Cerebras-GPT, arXiv:2304.03208) in straightforward float32
`jax.numpy`. No kernel, no cache, no batching, and nothing of the
program under test: it is handed weights the benchmark drew from the seed.

Departures from the published model, all of them the repo's and listed in
each configuration file under `assumed`: the output head is its own matrix
with a bias (the published model ties it to the token embedding), and gelu
is the tanh approximation (`jax.nn.gelu`'s default) where the published
`config.json` names the erf form.

`precision` selects what the arithmetic is done in. "float32" is the
reference proper: every product at `highest`, nothing rounded. "float8" is
the control, the same mathematics computed one precision below the
bfloat16 that the configuration states: every weight of the blocks and the
head and every intermediate of the forward pass is rounded to float8
(e4m3, under a per-tensor power-of-two scale), as a bfloat16 program rounds
them to bfloat16, and every gradient that flows back through one of them
likewise (e5m2).

Each configuration of the family names a file of its own beside it,
`configs/<name>_reference.py`, which takes everything from here; one that
departs from the family gets a reference of its own there instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _round8(x, dtype, top: float):
    """`x` through an 8-bit float type under a per-tensor power-of-two
    scale that puts its largest magnitude just under the type's top."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.floor(jnp.log2(top / jnp.where(amax > 0, amax,
                                                         1.0))))
    return (x * scale).astype(dtype).astype(x.dtype) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _low(x, precision: str):
    """`x` as the stated precision holds it: float8 keeps values in e4m3
    on the way forward and their gradients in e5m2 on the way back, the
    usual pairing of the two formats."""
    if precision == "float32":
        return x
    if precision != "float8":
        raise ValueError(f"precision {precision!r}: float32 or float8")
    return _round8(x, jnp.float8_e4m3fn, 448.0)


def _low_fwd(x, precision):
    return _low(x, precision), None


def _low_bwd(precision, _, g):
    return ((g if precision == "float32"
             else _round8(g, jnp.float8_e5m2, 57344.0)),)


_low.defvjp(_low_fwd, _low_bwd)


def _mm(a, w, precision: str):
    return _low(jnp.matmul(a, _low(w, precision), precision=HIGHEST),
                precision)


def _ln(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def block(x, p, *, n_heads: int, eps: float, precision: str):
    """One pre-LN block on (B, T, d): x + MHA(LN(x)), then x + FFN(LN(x)),
    causal, every head full."""
    B, T, d = x.shape
    hd = d // n_heads
    low = functools.partial(_low, precision=precision)
    h = low(_ln(x, p["ln1_g"], p["ln1_b"], eps))
    qkv = low(_mm(h, p["Wqkv"], precision) + p["bqkv"])
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(B, T, n_heads, hd)
               for i in range(3))
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = low(jnp.einsum("bhts,bshd->bthd", low(jax.nn.softmax(s, axis=-1)),
                       v, precision=HIGHEST).reshape(B, T, d))
    x = low(x + _mm(a, p["Wo"], precision) + p["bo"])
    h = low(_ln(x, p["ln2_g"], p["ln2_b"], eps))
    f = low(jax.nn.gelu(_mm(h, p["W1"], precision) + p["b1"],
                        approximate=True))
    return low(x + _mm(f, p["W2"], precision) + p["b2"])


def hidden(w, ids, *, n_heads: int, eps: float, precision: str):
    """Final-LayerNorm hidden states (B, T, d) for token ids (B, T);
    `w` holds the block leaves stacked over a leading layer axis."""
    T = ids.shape[1]
    x = _low(w["wte"][ids] + w["wpe"][:T], precision)
    body = jax.checkpoint(functools.partial(
        block, n_heads=n_heads, eps=eps, precision=precision))
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, w["blocks"])
    return _low(_ln(x, w["lnf_g"], w["lnf_b"], eps), precision)


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "precision"))
def logits_at(w, ids, rows, *, n_heads: int, eps: float,
              precision: str = "float32"):
    """Next-token logits (len(rows), V) at positions `rows` of the one
    sequence `ids` (1, T)."""
    x = hidden(w, ids, n_heads=n_heads, eps=eps, precision=precision)
    return _mm(x[0][rows], w["head_w"], precision) + w["head_b"]


def _loss(w, ids, labels, n_heads, eps, precision):
    x = hidden(w, ids, n_heads=n_heads, eps=eps, precision=precision)
    logits = _mm(x, w["head_w"], precision) + w["head_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "precision"))
def loss_and_grad(w, ids, labels, *, n_heads: int, eps: float,
                  precision: str = "float32"):
    """Mean next-token cross-entropy over the rows given, and its
    gradient by every leaf."""
    return jax.value_and_grad(_loss)(w, ids, labels, n_heads, eps,
                                     precision)


@functools.partial(jax.jit, donate_argnums=(0,))
def accumulate(acc, grads, weight):
    return jax.tree.map(lambda a, g: a + weight * g, acc, grads)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def adam_step(w, m, v, grads, step, lr):
    """Adam as the repo's updater writes it (ND4J's form: the bias
    corrections folded into the step size, epsilon outside the root).
    `step` counts from 0."""
    t = step.astype(jnp.float32) + 1.0
    alpha = lr * jnp.sqrt(1.0 - ADAM_B2 ** t) / (1.0 - ADAM_B1 ** t)
    m = jax.tree.map(lambda m_, g: ADAM_B1 * m_ + (1 - ADAM_B1) * g,
                     m, grads)
    v = jax.tree.map(lambda v_, g: ADAM_B2 * v_ + (1 - ADAM_B2) * g * g,
                     v, grads)
    w = jax.tree.map(lambda w_, m_, v_: w_ - alpha * m_
                     / (jnp.sqrt(v_) + ADAM_EPS), w, m, v)
    return w, m, v


@jax.jit
def leaf_norms(tree):
    """L2 norm of every leaf; block leaves give one norm per layer."""
    def norm(x, stacked):
        axes = tuple(range(1, x.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    out = {k: norm(x, False) for k, x in tree.items() if k != "blocks"}
    out["blocks"] = {k: norm(x, True) for k, x in tree["blocks"].items()}
    return out


@jax.jit
def leaf_delta_norms(tree, base):
    return leaf_norms(jax.tree.map(lambda a, b: a - b, tree, base))


def train_steps(w, base_fn, batches, *, n_heads: int, eps: float, lr: float,
                row_block: int, precision: str = "float32") -> dict:
    """Follow `len(batches)` optimizer steps from weights `w` (stacked
    layout, consumed; `base_fn()` makes the same weights again, for the
    change at the end). Each batch is (ids, labels) of int32 (B, T); its
    gradient is accumulated over blocks of `row_block` rows so that the
    logits of a whole batch never sit on the device together, and the
    second moment waits on the host meanwhile: weights, both moments,
    the sum and one block's gradient do not fit a 16 GB chip together
    at 0.67 B parameters. Returns each step's loss, the per-leaf norms
    of the first step's gradient, and the per-leaf norms of the
    parameters' change over all steps."""
    m = v_host = None
    losses, grad_norms = [], None
    for step, (ids, labels) in enumerate(batches):
        B = ids.shape[0]
        if B % row_block:
            raise ValueError(f"{B} rows do not split into {row_block}s")
        grads, loss = jax.tree.map(jnp.zeros_like, w), 0.0
        for r in range(0, B, row_block):
            l, g = loss_and_grad(w, ids[r:r + row_block],
                                 labels[r:r + row_block], n_heads=n_heads,
                                 eps=eps, precision=precision)
            grads = accumulate(grads, g, row_block / B)
            loss = loss + l * (row_block / B)
            del g
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = jax.device_get(leaf_norms(grads))
            m = jax.tree.map(jnp.zeros_like, w)
            v = jax.tree.map(jnp.zeros_like, w)
        else:
            v = jax.device_put(v_host)
        w, m, v = adam_step(w, m, v, grads, jnp.asarray(step, jnp.int32),
                            jnp.asarray(lr, jnp.float32))
        del grads
        v_host = jax.device_get(v)
        del v
    delta_norms = jax.device_get(leaf_delta_norms(w, base_fn()))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}
