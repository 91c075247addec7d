"""The engine's resident weights: f32 masters served in bf16 are cast
ONCE, when `DecodeEngine._build` runs (construction, a rebuilding
weight swap), and handed to every program as an argument. No serving
program converts a weight or takes an f32 block weight.

Two halves. Parity at the precision the 1.3B serve cell runs (f32
parameters, `compute_dtype=bfloat16`), which no other engine test
builds: greedy tokens through the engine equal `generate()`'s. And the
mechanism itself: the lowered programs, the two counters, the swap."""
import re

import chip_smoke
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (
    GPTPlan,
    generate,
    gpt_configuration,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import DecodeEngine, ModelServer
from deeplearning4j_tpu.util.checkpoint_store import CheckpointStore
from deeplearning4j_tpu.util.serialization import write_model

VOCAB = 48


def _gpt_net(seed: int = 12345, compute_dtype=jnp.bfloat16, **kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_layers", 2)
    kw.setdefault("max_length", 128)
    net = MultiLayerNetwork(gpt_configuration(seed=seed, **kw),
                            compute_dtype=compute_dtype)
    net.init()
    return net


@pytest.fixture(scope="module")
def net():
    return _gpt_net()


@pytest.fixture(scope="module")
def draft():
    return _gpt_net(seed=999)


def _prompts(n, t0, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, VOCAB, (n, t0)).astype(np.int32)


# ------------------------------------------- parity, f32 masters in bf16

_PARITY = {
    "bucketed-prefill-step": dict(t0=5, gen=dict(decode_chunk=1)),
    "bucketed-prefill-chunk4": dict(t0=5, gen=dict(decode_chunk=4)),
    "chunked-prefill-step": dict(
        t0=20, gen=dict(decode_chunk=1, page_size=8, prefill_chunk=8)),
    "chunked-prefill-chunk4": dict(
        t0=20, gen=dict(decode_chunk=4, page_size=8, prefill_chunk=8)),
    "speculative-self": dict(
        t0=5, gen=dict(speculative={"draft": "self", "k": 3})),
    "speculative-draft": dict(
        t0=5, gen=dict(speculative={"draft": "other", "k": 3})),
    "speculative-chunked-prefill": dict(
        t0=20, gen=dict(page_size=8, prefill_chunk=8,
                        speculative={"draft": "other", "k": 2})),
}


@pytest.mark.parametrize("case", sorted(_PARITY))
def test_bf16_served_f32_masters_match_generate(net, draft, case):
    """Greedy tokens of an f32-parameter, bf16-compute net through the
    engine equal whole-batch `generate()`'s, four requests through two
    slots (slot reuse, admission in flight). The speculative verifier
    scores k+1 tokens a slot in one product where `generate()` scores
    one, and at bf16 the two round a near-tie apart (the parent does
    too): there the streams agree as far as the model is decided."""
    t0, gen = _PARITY[case]["t0"], dict(_PARITY[case]["gen"])
    spec = gen.get("speculative")
    if spec and spec["draft"] == "other":
        gen["speculative"] = dict(spec, draft=draft)
    prompts = _prompts(4, t0, seed=31)
    expected = generate(net, prompts, 9, temperature=0.0)
    eng = DecodeEngine(net, n_slots=2, max_len=48, prompt_buckets=(8,),
                       **gen)
    try:
        reqs = [eng.submit(p, 9) for p in prompts]
        got = [np.asarray(r.result(timeout=120.0)) for r in reqs]
        if spec:
            agreed = chip_smoke._agreement(net, prompts, got, expected,
                                           "the verifier and generate()")
            assert min(agreed["common_prefix_tokens"]) >= 1
        else:
            np.testing.assert_array_equal(got, expected)
        st = eng.stats()
        assert st["failures"] == 0 and st["weight_casts"] == 1
        if t0 > 8:
            assert st["prefill_chunks"] >= 3 * len(prompts)
        if spec:
            assert st["speculative"]["verify_steps"] >= 3
    finally:
        eng.shutdown()


# ------------------------------------------------ the lowered programs


def _master_shapes(net):
    """StableHLO type strings of the f32 embedding and block matrices."""
    plan = GPTPlan(net)
    return {"x".join(map(str, w.shape)) + "xf32"
            for i in (plan.emb_i, *plan.block_is)
            for w in jax.tree_util.tree_leaves(net._params[i])
            if w.ndim >= 2}


def _master_uses(text: str, shapes) -> dict:
    """In a lowered (StableHLO) module: `args`, f32 weight-shaped
    arguments of `main`; `converts`, converts of an f32 weight-shaped
    operand."""
    main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S)
    args = sum(main.group(1).count(f"tensor<{s}>") for s in shapes)
    converts = sum(
        1 for line in text.splitlines() if "stablehlo.convert" in line
        and any(f"(tensor<{s}>) ->" in line for s in shapes))
    return {"args": args, "converts": converts}


def _recorded(obj, attr, seen):
    """Wrap a compiled program so that its first call's argument shapes
    are kept: what the scheduler passes, not what a test would guess."""
    fn = getattr(obj, attr)

    def call(*args):
        seen.setdefault(attr, (fn, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)))
        return fn(*args)

    setattr(obj, attr, call)


def _drive(eng, n_tokens=9):
    """A bucketed and a chunked prefill, single steps and fused chunks."""
    for p in (_prompts(1, 5, seed=3)[0], _prompts(1, 20, seed=4)[0]):
        eng.generate(p, n_tokens)


@pytest.fixture(scope="module")
def lowered(net, draft):
    """name -> lowered text of every serving program, as dispatched."""
    seen = {}
    gen = dict(n_slots=2, max_len=48, prompt_buckets=(8,), page_size=8,
               prefill_chunk=8)
    for chunk, attrs in ((4, ("_decode_chunked", "_prefill",
                              "_prefill_chunk_fn")),
                         (1, ("_decode_step",))):
        eng = DecodeEngine(net, decode_chunk=chunk, **gen)
        try:
            for attr in attrs:
                _recorded(eng, attr, seen)
            _drive(eng)
        finally:
            eng.shutdown()
    eng = DecodeEngine(net, speculative={"draft": draft, "k": 2}, **gen)
    try:
        for attr in ("_verify", "_propose", "_draft_prefill",
                     "_draft_prefill_chunk"):
            _recorded(eng._spec, attr, seen)
        _drive(eng)
    finally:
        eng.shutdown()
    return {attr: fn.lower(*args).as_text()
            for attr, (fn, args) in seen.items()}


@pytest.mark.parametrize("program", [
    "_decode_step", "_decode_chunked", "_prefill", "_prefill_chunk_fn",
    "_verify", "_propose", "_draft_prefill", "_draft_prefill_chunk"])
def test_no_serving_program_converts_or_takes_a_master_weight(
        net, lowered, program):
    uses = _master_uses(lowered[program], _master_shapes(net))
    assert uses == {"args": 0, "converts": 0}, (program, uses)
    # the weights it does take are the compute dtype's
    assert "x96xbf16>" in lowered[program]


def test_the_check_sees_a_program_that_casts(net):
    """The control: `generate()`'s own prefill casts per call by
    construction, takes the masters and converts them."""
    plan = GPTPlan(net)

    def casts(params, ids):
        bp = plan.cast_blocks(params)
        x = bp[plan.emb_i]["W"][ids]
        return x @ bp[plan.block_is[0]]["Wqkv"]

    text = jax.jit(casts).lower(net._params,
                                jnp.zeros((2,), jnp.int32)).as_text()
    uses = _master_uses(text, _master_shapes(net))
    assert uses["args"] == 2 and uses["converts"] == 2, uses


# ------------------------------------------------- counters and the swap


def _resident_bytes(net) -> int:
    """Embedding and blocks at two bytes an element."""
    plan = GPTPlan(net)
    return sum(2 * w.size for i in (plan.emb_i, *plan.block_is)
               for w in jax.tree_util.tree_leaves(net._params[i]))


def test_cast_once_a_build_and_again_on_a_rebuilding_swap(tmp_path, net):
    """1 after construction; 2 after `ModelServer.reload` to other
    weights, whose tokens equal a fresh engine's on them; unchanged by
    the swap that keeps the pools (same net: the engine serves a
    snapshot of the tree it was built on)."""
    new_net = _gpt_net(seed=2)
    store = CheckpointStore(tmp_path)
    store.save(1, lambda tmp: write_model(new_net, tmp, atomic=False))
    gen = {"n_slots": 2, "max_len": 48, "prompt_buckets": (8,)}
    prompt = _prompts(1, 5, seed=23)[0]
    srv = ModelServer(net, auto_canary=False, generation=dict(gen))
    try:
        engine = srv._ensure_engine()
        st = engine.stats()
        assert st["weight_casts"] == 1 and st["swaps"] == 0
        assert st["weights_resident_bytes"] == _resident_bytes(net)
        plan = engine._plan
        assert engine._weights[plan.block_is[0]]["Wqkv"].dtype \
            == jnp.bfloat16
        # head and trailing norm: the net's own arrays, not copies
        assert engine._weights[plan.out_i]["W"] \
            is net._params[plan.out_i]["W"]
        srv.generate(prompt, 4)

        held = engine._weights
        engine.drain_and_swap(engine._net)  # keeps pools and weights
        st = engine.stats()
        assert st["swaps"] == 1 and st["weight_casts"] == 1
        assert engine._weights is held

        srv.reload(store)
        st = srv.stats()["generation"]
        assert st["swaps"] == 2 and st["weight_casts"] == 2
        assert st["weights_resident_bytes"] == _resident_bytes(new_net)
        assert engine._weights is not held
        got = srv.generate(prompt, 9)
    finally:
        srv.shutdown()
    # a stopped engine holds the net's own tree and nothing beside it
    assert engine._weights is None
    assert engine.stats()["weights_resident_bytes"] == 0
    fresh = DecodeEngine(new_net, **gen)
    try:
        np.testing.assert_array_equal(got, fresh.generate(prompt, 9))
    finally:
        fresh.shutdown()
    np.testing.assert_array_equal(
        got, generate(new_net, prompt[None], 9, temperature=0.0)[0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_equal_dtypes_serve_the_nets_own_tree(dtype):
    """Parameter and compute dtypes equal (every net the other engine
    tests build; the granite cell): nothing is cast, nothing is held,
    and the dispatches pass the net's own tree."""
    dt = jnp.dtype(dtype)
    conf = gpt_configuration(vocab_size=VOCAB, d_model=32, n_heads=2,
                             n_layers=2, max_length=64)
    net = MultiLayerNetwork(conf, dtype=dt,
                            compute_dtype=dt if dtype == "bfloat16"
                            else None)
    net.init()
    eng = DecodeEngine(net, n_slots=2, max_len=32, prompt_buckets=(8,),
                       speculative={"draft": "self", "k": 2})
    try:
        assert eng._weights is net._params
        assert eng._spec._weights is net._params
        eng.generate(_prompts(1, 5)[0], 4)
        st = eng.stats()
        assert st["weight_casts"] == 0
        assert st["weights_resident_bytes"] == 0
        eng.drain_and_swap(_gpt_net(seed=3, compute_dtype=None))
        assert eng.stats()["weight_casts"] == 0
    finally:
        eng.shutdown()


def test_a_tied_head_keeps_one_table_in_the_param_dtype():
    """A head tied to the embedding reads the table in the parameter
    dtype: the resident tree keeps the net's own table and casts the
    blocks alone."""
    from deeplearning4j_tpu.models.transformer import (
        hybrid_moe_configuration,
    )

    net = MultiLayerNetwork(hybrid_moe_configuration(
        vocab_size=64, d_model=64, layer_types=("mamba", "attention"),
        n_heads=4, n_kv_heads=2, attention_multiplier=0.1, mamba_heads=8,
        mamba_head_dim=16, mamba_state=16, mamba_chunk=8, n_experts=8,
        top_k=2, expert_width=32, shared_width=48, experts_held=(0, 4),
        embedding_multiplier=1.0, residual_multiplier=0.22,
        logits_scaling=16.0), compute_dtype=jnp.bfloat16)
    net.init()
    plan = GPTPlan(net)
    w = plan.resident_weights(net._params)
    assert w[plan.emb_i] is net._params[plan.emb_i]
    leaves = jax.tree_util.tree_leaves([w[i] for i in plan.block_is])
    assert leaves and all(x.dtype == jnp.bfloat16 for x in leaves)
    eng = DecodeEngine(net, n_slots=2, max_len=32, page_size=8,
                       prompt_buckets=(8,))
    try:
        toks = eng.generate(np.arange(5, dtype=np.int32), 6)
        st = eng.stats()
        assert len(toks) == 6 and st["failures"] == 0
        assert st["weight_casts"] == 1
        assert st["weights_resident_bytes"] == sum(2 * x.size
                                                   for x in leaves)
    finally:
        eng.shutdown()
