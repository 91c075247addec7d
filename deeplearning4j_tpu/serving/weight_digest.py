"""The digest of a served tree of weights, reduced where the leaves lie.

`DecodeEngine._build` stamps KV hand-offs, prefix-cache pages and
directory entries with `weight_version(leaves)`: 16 hex digits that two
cooperating replicas of one release compute alike from equal weights and
that a change to a leaf's dtype, shape or bits changes, short of a
collision in the 128 bits a leaf folds to or the 64 the digest keeps.
It is an integrity check between replicas that trust one another, not a
MAC: the fold has no key, so someone who chooses the weights can search
for a collision.

Each leaf is folded ON THE DEVICE (or devices: a sharded leaf folds to
the same integers) by one fused reduction into `WORDS` `uint32` words,

    word_w = sum_i (h(x_i) + c_w) * m_w(i)        (mod 2^32)

over the leaf's elements `x_i` bitcast to unsigned integers of their own
width, `h` a fixed bijection of `uint32` that is not linear (two
multiplies between three xor-shifts, so a flipped high bit of `x_i`
moves low bits of `h` too and the same flip in two elements does not
cancel), `i` the element's row-major index and `m_w(i)` an odd
multiplier mixed from `i` differently for every word. `h` is one to one
and an odd multiplier is invertible mod 2^32, so a change to one element
changes every word; the index mix makes a swap of two elements, or a
transposition, change them. Integer sums wrap and are exact in any
order: the words are the same on CPU and TPU, under any sharding and any
tiling. The host receives `4 * WORDS` bytes a leaf and feeds blake2b
each leaf's dtype, shape and words, in `tree_leaves` order.
"""
from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_U32 = jnp.uint32
# c_w, and the odd multiplier of each word's second mixing round
_OFFSET = (0x9E3779B9, 0x7F4A7C15, 0xF39CC060, 0x5CEDC834)
_ROUND = (0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F, 0x165667B1)
WORDS = len(_OFFSET)  # 128 bits a leaf; the digest keeps 64 of the tree
_SPREAD = 0x2C1B3C6D  # odd: the index mix's first round, shared by the words
_AVALANCHE = (0x7FEB352D, 0x846CA68B)  # odd: `h`'s two multiplies
# a dtype's unsigned integer of its own width; 64 bits as two halves
_UINT_OF_BITS = {2: jnp.uint2, 4: jnp.uint4, 8: jnp.uint8, 16: jnp.uint16,
                 32: jnp.uint32, 64: jnp.uint32}


class WeightDigestError(ValueError):
    """A leaf the fold refuses: 2^32 or more 32-bit units (the index is
    `uint32`), or a dtype with no unsigned integer of its own width."""


def _part_bits(dtype) -> int:
    """Bits of what is bitcast: a complex leaf folds as its two parts."""
    bits = jax.dtypes.itemsize_bits(dtype)
    return bits // 2 if jnp.issubdtype(dtype, jnp.complexfloating) else bits


def _refuse_unfoldable(leaf) -> None:
    try:
        bits = _part_bits(leaf.dtype)
    except ValueError:  # an extended dtype (a PRNG key): no bits to read
        bits = None
    if leaf.dtype != jnp.bool_ and bits not in _UINT_OF_BITS:
        raise WeightDigestError(
            f"no unsigned integer to bitcast a {leaf.dtype} leaf to")
    units = max(1, jax.dtypes.itemsize_bits(leaf.dtype) // 32)
    if math.prod(leaf.shape) * units >= 1 << 32:
        raise WeightDigestError(
            f"a {leaf.dtype} leaf of shape {tuple(leaf.shape)} has 2**32 or "
            f"more 32-bit units: its index would wrap in uint32")


@jax.jit  # one trace a (shape, dtype, sharding) a process, whatever builds
def fold_leaf(x):
    """`(WORDS,) uint32` of one leaf that `_refuse_unfoldable` let pass:
    one variadic reduction whose operands XLA computes element by element
    as it reads the leaf, so no widened copy of the leaf is ever written.
    `bool` folds as 0 / 1, a complex leaf as its real and imaginary parts
    and an 8-byte part as its two 32-bit halves, each along a trailing
    axis."""
    if x.dtype == jnp.bool_:
        u = x.astype(jnp.uint8)
    else:
        if jnp.issubdtype(x.dtype, jnp.complexfloating):
            x = jnp.stack([x.real, x.imag], -1)
        u = lax.bitcast_convert_type(x, _UINT_OF_BITS[_part_bits(x.dtype)])
    # row-major, from 1 (0 would mix to the multiplier 1 in every word)
    index, stride = _U32(1), 1
    for axis in reversed(range(u.ndim)):
        # a stride passes 2**32 only beside an axis of length 0
        index = index + lax.broadcasted_iota(_U32, u.shape, axis) \
            * _U32(stride & 0xFFFFFFFF)
        stride *= u.shape[axis]
    # every step of either mix is a bijection of uint32 (odd multiplier,
    # xor-shift): no two indices share a multiplier before the `| 1`,
    # and no two values of an element share an `h`
    spread = index * _U32(_SPREAD)
    spread = spread ^ (spread >> _U32(15))
    h = u.astype(_U32)
    h = (h ^ (h >> _U32(16))) * _U32(_AVALANCHE[0])
    h = (h ^ (h >> _U32(15))) * _U32(_AVALANCHE[1])
    h = h ^ (h >> _U32(16))
    terms = []
    for c, r in zip(_OFFSET, _ROUND):
        m = spread * _U32(r)
        m = (m ^ (m >> _U32(13))) | _U32(1)
        terms.append((h + _U32(c)) * m)
    words = lax.reduce(
        tuple(terms), (_U32(0),) * WORDS,
        lambda a, b: tuple(p + q for p, q in zip(a, b)),
        tuple(range(u.ndim)))
    return jnp.stack(words)


def weight_version(leaves) -> tuple[str, int]:
    """`(digest, host_bytes)` of `leaves` in order: 16 hex digits of
    blake2b over each leaf's dtype, shape and folded words, and the bytes
    that crossed to the host for it. A leaf the fold cannot index or
    bitcast raises `WeightDigestError` before anything is dispatched."""
    for leaf in leaves:
        _refuse_unfoldable(leaf)
    words = jax.device_get([fold_leaf(leaf) for leaf in leaves])
    digest = hashlib.blake2b(digest_size=8)
    for leaf, w in zip(leaves, words):
        digest.update(str(leaf.dtype).encode())
        digest.update(str(tuple(leaf.shape)).encode())
        digest.update(np.asarray(w, dtype="<u4").tobytes())
    return digest.hexdigest(), sum(int(w.nbytes) for w in words)


# ---------------------------------------------------- the known answer
# `weight_version` of `known_answer_tree()`: the fold is integer
# arithmetic, so every backend reads these 16 digits (`chip_smoke.py
# serve` checks the chip's; `tests/test_weight_digest.py` pins the CPU's)
KNOWN_ANSWER = "683d885f08f9068f"


def known_answer_tree() -> dict:
    """A small fixed tree of every kind of leaf the digest tells apart:
    f32, bf16, int8, bool, a 0-d leaf and an empty one, all values exact
    in their dtype."""
    def ramp(n, mod, by):  # whole numbers over a power of two
        return ((np.arange(n) * 37) % mod - mod // 2) / by

    return {
        "embed": jnp.asarray(ramp(35, 101, 8.0).reshape(5, 7), jnp.float32),
        "w": jnp.asarray(ramp(60, 31, 4.0).reshape(3, 4, 5), jnp.bfloat16),
        "q": jnp.asarray(ramp(9, 251, 1).astype(np.int8)),
        "mask": jnp.asarray(np.arange(12).reshape(4, 3) % 3 == 0),
        "scale": jnp.asarray(0.375, jnp.float32),
        "empty": jnp.zeros((0, 3), jnp.float32)}
