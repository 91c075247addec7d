"""The digest of the served weights (`serving/weight_digest.py`): folded
on the device into a few `uint32` words a leaf, pinned by a golden
value, held to a plain numpy loop, sensitive to every bit, position,
dtype and shape, equal under any sharding, and bound into
`DecodeEngine._build` with nothing of a leaf but those words reaching
the host."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deeplearning4j_tpu as dl4j
from deeplearning4j_tpu.models.transformer import gpt_configuration
from deeplearning4j_tpu.serving import DecodeEngine
from deeplearning4j_tpu.serving import weight_digest as wd

ENGINE = dict(n_slots=2, max_len=32, prompt_buckets=(8,))
MASK = (1 << 32) - 1


def _version(*leaves) -> str:
    return wd.weight_version([jnp.asarray(x) for x in leaves])[0]


def _loop_fold(a: np.ndarray) -> list:
    """The fold as a plain numpy loop over the flat unsigned elements,
    in Python-width integers masked to 32 bits."""
    bits = jax.dtypes.itemsize_bits(a.dtype)
    if a.dtype == np.bool_:
        u = a.astype(np.uint8)
    elif bits < 8:  # ml_dtypes keeps a sub-byte element in a byte
        u = a.astype(np.int8).view(np.uint8) & ((1 << bits) - 1)
    else:  # a complex leaf is its parts in turn; 8 bytes are two halves
        width = min(a.dtype.itemsize // (2 if a.dtype.kind == "c" else 1), 4)
        u = np.ascontiguousarray(a).reshape(-1).view(f"<u{width}")
    u = u.reshape(-1).astype(np.uint64)
    h = ((u ^ (u >> 16)) * wd._AVALANCHE[0]) & MASK
    h = ((h ^ (h >> 15)) * wd._AVALANCHE[1]) & MASK
    h ^= h >> 16
    i = np.arange(1, u.size + 1, dtype=np.uint64)
    spread = (i * wd._SPREAD) & MASK
    spread ^= spread >> 15
    words = []
    for c, r in zip(wd._OFFSET, wd._ROUND):
        m = (spread * r) & MASK
        m = (m ^ (m >> 13)) | 1
        words.append(int(((((h + c) & MASK) * m) & MASK).sum() & MASK))
    return words


def _ramp(shape, dtype, by=4.0):
    n = int(np.prod(shape))
    return (((np.arange(n) * 37) % 61 - 30) / by).reshape(shape) \
        .astype(dtype)


# ------------------------------------------------------ the pinned value


def test_the_golden_tree_reads_the_pinned_version():
    leaves = jax.tree_util.tree_leaves(wd.known_answer_tree())
    assert [str(x.dtype) for x in leaves] == [
        "float32", "float32", "bool", "int8", "float32", "bfloat16"]
    version, crossed = wd.weight_version(leaves)
    assert version == wd.KNOWN_ANSWER == "683d885f08f9068f"
    assert crossed == 4 * wd.WORDS * len(leaves)


@pytest.mark.parametrize("a", [
    _ramp((5, 7), np.float32), _ramp((3, 4, 5), jnp.bfloat16),
    _ramp((9,), np.int8, 1), _ramp((4, 3), np.float32) > 0,
    _ramp((), np.float32), _ramp((0, 3), np.float32),
    _ramp((2, 3), np.float16), _ramp((4, 2), np.float64),
    _ramp((3,), np.int64, 1) << 40, _ramp((6,), np.uint32, 1),
    _ramp((2, 2, 2, 2), jnp.float8_e4m3fn, 1),
    _ramp((3, 5), np.float32) * np.complex64(1 - 2j),
    _ramp((4,), np.float64) * (3 + 0.5j),
    (_ramp((16,), np.int8, 1) % 8).astype(jnp.int4),
    (_ramp((7, 3), np.int8, 1) % 16).astype(jnp.uint4)],
    ids=lambda a: f"{a.dtype}{list(a.shape)}")
def test_the_fold_is_the_plain_loop(a):
    words = np.asarray(wd.fold_leaf(jnp.asarray(a)))
    assert words.dtype == np.uint32 and words.shape == (wd.WORDS,)
    assert words.tolist() == _loop_fold(a)


# ---------------------------------------------------------- sensitivity


def _flip(a: np.ndarray, at, bit: int = 0) -> np.ndarray:
    """`a` with `bit` flipped in the element, or each of the elements,
    `at`."""
    b = a.copy()
    flat = b.reshape(-1).view(f"u{a.dtype.itemsize}")
    flat[np.asarray(at)] ^= np.array(1 << bit, flat.dtype)
    return b


def _swapped(a: np.ndarray, i: int, j: int) -> np.ndarray:
    b = a.copy().reshape(-1)
    b[[i, j]] = b[[j, i]]
    return b.reshape(a.shape)


F32, BF16, I8 = (_ramp((6, 10), np.float32), _ramp((6, 10), jnp.bfloat16),
                 _ramp((6, 10), np.int8, 1))
NAN_A, NAN_B = (np.array([0x7FC00000, 0], np.uint32).view(np.float32),
                np.array([0x7FC00001, 0], np.uint32).view(np.float32))

DIFFERENT = {
    **{f"{a.dtype}-bit-flipped-at-{where}": ((a,), (_flip(a, at),))
       for a in (F32, BF16, I8)
       for where, at in (("first", 0), ("middle", 31), ("last", 59))},
    "the-top-bit-flipped": ((F32,), (_flip(F32, 17, 31),)),
    # what a sum that is linear in the elements cannot tell: 2^31 times
    # an odd multiplier is 2^31, so an even number of sign flips cancels
    "two-sign-bits-flipped": ((F32,), (_flip(F32, [3, 41], 31),)),
    "a-leaf-negated": ((F32,), (-F32,)),
    "a-bf16-leaf-negated": ((BF16,), (-BF16,)),
    "the-high-half-of-f64-negated": ((F32.astype(np.float64),),
                                     (-F32.astype(np.float64),)),
    "bit-30-flipped-in-two": ((F32,), (_flip(F32, [0, 59], 30),)),
    "bit-30-flipped-in-four": ((F32,), (_flip(F32, [5, 6, 30, 31], 30),)),
    "bit-29-flipped-in-eight": ((F32,), (_flip(F32, range(8, 16), 29),)),
    "the-top-byte-bit-flipped-in-two": ((I8,), (_flip(I8, [1, 2], 7),)),
    "two-elements-swapped": ((F32,), (_swapped(F32, 3, 41),)),
    "neighbours-swapped": ((BF16,), (_swapped(BF16, 20, 21),)),
    "two-equal-shaped-leaves-swapped": ((F32, F32 * 2), (F32 * 2, F32)),
    "the-same-bits-under-another-dtype": ((F32,), (F32.view(np.int32),)),
    "the-same-bits-in-halves": ((F32,), (F32.view(np.float16),)),
    "reshaped": ((F32,), (F32.reshape(10, 6),)),
    "flattened": ((F32,), (F32.reshape(-1),)),
    "transposed": ((F32[:, :6],), (np.ascontiguousarray(F32[:, :6].T),)),
    "a-leaf-split-in-two": ((F32,), (F32[:3], F32[3:])),
    "an-empty-leaf-more": ((F32,), (F32, F32[:0])),
    "minus-zero": ((np.zeros(4, np.float32),),
                   (np.array([0.0, -0.0, 0.0, 0.0], np.float32),)),
    "two-nan-payloads": ((NAN_A,), (NAN_B,)),
    "a-bool-flipped": ((F32 > 0,), (_flip(F32 > 0, 7),)),
    "zeros-of-another-length": ((np.zeros(8, np.int8),),
                                (np.zeros(9, np.int8),)),
}


@pytest.mark.parametrize("case", DIFFERENT)
def test_a_difference_the_digest_must_tell(case):
    one, other = DIFFERENT[case]
    assert _version(*one) != _version(*other)
    assert _version(*one) == _version(*(x.copy() for x in one))


def test_one_changed_element_changes_every_word():
    a = _ramp((64, 33), np.float32)
    base = np.asarray(wd.fold_leaf(jnp.asarray(a)))
    for at in (0, 1, 1000, a.size - 1):
        for bit in (0, 15, 31):
            got = np.asarray(wd.fold_leaf(jnp.asarray(_flip(a, at, bit))))
            assert np.all(got != base)


@pytest.mark.parametrize("bit", (31, 30, 24, 16))
def test_a_high_bit_flipped_in_two_elements_reaches_every_words_low_half(bit):
    """An element's term is not linear in it: under `sum (x + c) * m`
    two flips of bit 31 added `2 * 2**31 = 0` to every word, and flips of
    bit `b` could move no bit below `b`."""
    a = _ramp((64, 33), np.float32)
    base = np.asarray(wd.fold_leaf(jnp.asarray(a)))
    for at in ([0, 1], [7, 1000], [a.size - 2, a.size - 1]):
        got = np.asarray(wd.fold_leaf(jnp.asarray(_flip(a, at, bit))))
        assert np.all((got - base) & 0xFFFF)


@pytest.mark.parametrize("spec", [P("x"), P(None, "x"), P("x", "y"),
                                  P(("x", "y"), None)], ids=str)
def test_a_sharded_leaf_folds_to_the_same_integers(spec):
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("x", "y"))
    a = _ramp((16, 24), jnp.bfloat16)
    sharded = jax.device_put(a, NamedSharding(mesh, spec))
    assert len(sharded.sharding.device_set) > 1
    assert np.asarray(wd.fold_leaf(sharded)).tolist() == _loop_fold(a)


@pytest.mark.parametrize("leaf,why", [
    (jax.random.key(0), "no unsigned integer"),
    (jax.ShapeDtypeStruct((1 << 16, 1 << 16), jnp.int8), "2\\*\\*32"),
    (jax.ShapeDtypeStruct((1 << 31,), jnp.float64), "2\\*\\*32"),
    (jax.ShapeDtypeStruct((1 << 30,), jnp.complex128), "2\\*\\*32")],
    ids=("a-prng-key", "2**32-elements", "2**32-halves", "2**32-quarters"))
def test_a_leaf_the_fold_cannot_index_or_bitcast_is_refused_typed(leaf, why):
    before = wd.fold_leaf._cache_size()
    with pytest.raises(wd.WeightDigestError, match=why):
        wd.weight_version([jnp.zeros(3), leaf])
    assert wd.fold_leaf._cache_size() == before  # nothing was dispatched
    assert issubclass(wd.WeightDigestError, ValueError)


@pytest.mark.parametrize("shape,dtype", [
    ((512, 1024), jnp.float32), ((8, 200, 300), jnp.bfloat16),
    ((4096, 333), jnp.int8)], ids=("f32", "bf16-3d", "int8-off-grid"))
def test_the_fold_writes_no_copy_of_the_leaf(shape, dtype):
    leaf = jax.ShapeDtypeStruct(shape, dtype)
    memory = wd.fold_leaf.lower(leaf).compile().memory_analysis()
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    assert memory.argument_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < nbytes // 100
    assert memory.output_size_in_bytes <= 64


def test_a_shape_is_traced_once_a_process():
    a = jnp.asarray(_ramp((7, 11), np.float32))
    wd.fold_leaf(a)
    before = wd.fold_leaf._cache_size()
    wd.weight_version([a, a + 1, jnp.asarray(_ramp((7, 11), np.float32))])
    assert wd.fold_leaf._cache_size() == before


# ------------------------------------------------- bound into the engine


def _gpt_net(seed=12345):
    net = dl4j.MultiLayerNetwork(
        gpt_configuration(seed=seed, vocab_size=48, d_model=32, n_heads=2,
                          n_layers=2, max_length=64))
    net.init()
    return net


def _leaves(net):
    return jax.tree_util.tree_leaves(net._params)


def _built(net):
    eng = DecodeEngine(net, **ENGINE)
    try:
        return eng._weight_version, eng.stats()["build"]
    finally:
        eng.shutdown()


@pytest.fixture(scope="module")
def net():
    return _gpt_net()


@pytest.fixture(scope="module")
def built(net):
    return _built(net)


def test_the_engines_version_is_the_digest_of_its_nets_leaves(net, built):
    version, build = built
    assert version == wd.weight_version(_leaves(net))[0]
    assert len(version) == 16 and int(version, 16) >= 0
    assert 0 < build["weight_hash_host_bytes"] <= 64 * len(_leaves(net))
    assert build["weight_hash_host_bytes"] \
        == 4 * wd.WORDS * len(_leaves(net)) < build["weight_hash_bytes"]


def test_two_engines_over_equal_weights_agree(net, built):
    assert _built(net.clone())[0] == built[0]
    assert _built(_gpt_net())[0] == built[0]


@pytest.mark.parametrize("which", (0, -1), ids=("first-leaf", "last-leaf"))
def test_one_changed_element_changes_the_engines_version(net, built, which):
    other = net.clone()
    leaves, tree = jax.tree_util.tree_flatten(other._params)
    leaf = leaves[which]
    at = (0,) * leaf.ndim
    # one unit in the last place above what was there
    leaves[which] = leaf.at[at].set(
        jax.lax.nextafter(leaf[at], jnp.asarray(jnp.inf, leaf.dtype)))
    other._params = jax.tree_util.tree_unflatten(tree, leaves)
    assert _built(other)[0] != built[0]


def test_a_swap_changes_the_version_and_the_swap_back_restores_it(net):
    other = _gpt_net(seed=2)
    eng = DecodeEngine(net, **ENGINE)
    try:
        first, one = eng._weight_version, eng.stats()["build"]
        eng.drain_and_swap(other)
        second, two = eng._weight_version, eng.stats()["build"]
        eng.drain_and_swap(net)
        third, three = eng._weight_version, eng.stats()["build"]
    finally:
        eng.shutdown()
    assert first == third != second
    assert second == wd.weight_version(_leaves(other))[0]
    assert (one["builds"], two["builds"], three["builds"]) == (1, 2, 3)
    # summed over builds: a second build doubles both counters
    for key in ("weight_hash_host_bytes", "weight_hash_bytes"):
        assert (two[key], three[key]) == (2 * one[key], 3 * one[key])


def test_a_build_leaves_no_host_copy_on_the_nets_leaves():
    net = _gpt_net(seed=3)
    # what a pull to the host leaves behind, where the pull copies (on
    # the CPU one device's array is read in place; a sharded one is not)
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    pulled = jax.device_put(np.arange(8.0), NamedSharding(mesh, P("x")))
    np.asarray(pulled)
    assert pulled._npy_value is not None
    assert all(x._npy_value is None for x in _leaves(net))
    _built(net)
    assert all(x._npy_value is None for x in _leaves(net))
    sharded = jax.device_put(_ramp((16, 8), np.float32),
                             NamedSharding(mesh, P("x")))
    wd.weight_version([sharded])
    assert sharded._npy_value is None
