"""The seams of the decode engine's four parts:

- `serving.decode_programs.build_programs` runs with no `DecodeEngine`
  and no thread, and its programs compute what the engine's do;
- the modules the engine stands on never import it back;
- `block_state`'s `env` carries numbers and the tp plan, no function;
- the scheduler reaches the hand-off plane at one place an iteration.
"""
import ast
import inspect
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import GPTPlan, gpt_configuration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import block_state, decode_programs
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

REPO = Path(__file__).resolve().parents[1]
SERVING = REPO / "deeplearning4j_tpu" / "serving"
S, PAGE, POOL, L = 2, 8, 12, 32


def _dense_net():
    net = MultiLayerNetwork(gpt_configuration(
        seed=7, vocab_size=53, d_model=32, n_heads=2, n_layers=2,
        max_length=L))
    net.init()
    return net


def _composed_net():
    from perfbench.families import granite_hybrid as fam

    cfg = json.loads((REPO / "perfbench/configs/granite-4.0-h-small.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=32, shared_intermediate_size=48,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               mamba_chunk_size=8, num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               num_local_experts=4, num_experts_per_tok=2, vocab_size=53,
               attention_multiplier=0.1)
    cfg["deployment"] = dict(num_local_experts_published=8,
                             experts_held_first=0)
    sz = fam.sizes(cfg)
    net = fam.build_net(sz, training=True, dtype=jnp.float32)
    fam.install(net, jax.tree.map(lambda a: a.astype(jnp.float32),
                                  fam.make_weights(5, sz)))
    return net


def _prefill_then_step(programs, states, weights):
    """One prompt into slot 0 through `prefill`, then one `decode_step`
    of both slots with only slot 0 active; every output as numpy."""
    caches = [st.alloc() for st in states]
    table = jnp.zeros((S, L // PAGE), jnp.int32).at[0, :2].set(
        jnp.asarray([3, 5], jnp.int32))
    ids = np.zeros((1, 8), np.int32)
    ids[0, :6] = [4, 9, 2, 30, 11, 7]
    kp, kdec = jax.random.split(jax.random.PRNGKey(0))
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(S)])
    zero = jnp.zeros((S,), jnp.int32)
    out = programs.prefill(
        weights, caches, jnp.asarray(ids), jnp.asarray(6, jnp.int32),
        jnp.asarray(0, jnp.int32), jnp.asarray([3], jnp.int32), zero, zero,
        keys, jnp.zeros((S,), jnp.float32), kp, kdec,
        jnp.asarray(0.0, jnp.float32))
    caches, tok, pos, keys, temps, tok0, ok0 = out[:7]
    step = programs.decode_step(weights, caches, table, tok, pos, keys,
                                temps, jnp.asarray([True, False]))
    return jax.tree_util.tree_map(np.asarray, (tok0, ok0, step))


@pytest.mark.parametrize("make_net", [_dense_net, _composed_net],
                         ids=["dense", "composed"])
def test_the_builder_needs_no_engine_and_computes_what_the_engines_do(
        make_net):
    net = make_net()
    plan = GPTPlan(net)
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=S, page=PAGE, pool_pages=POOL, cdt=plan.cdt, kv_quant=None,
        tp_shard=None, tp_axis=None))
    programs = decode_programs.build_programs(
        plan, states, n_slots=S, page=PAGE, L_logical=L, decode_chunk=2,
        top_k=0, logprobs=0, tp=None, donate=False)
    assert sorted(vars(programs)) == [
        "decode_chunked", "decode_step", "prefill", "prefill_chunk_fn"]
    mine = _prefill_then_step(programs, states,
                              plan.resident_weights(net._params))
    tok0, ok0, step = mine
    assert bool(ok0) and bool(step[4][0])
    assert int(step[2][0]) == 7 and int(step[2][1]) == 0  # slot 1 stood

    eng = DecodeEngine(net, n_slots=S, max_len=L, page_size=PAGE,
                       pool_pages=POOL, prompt_buckets=(8,),
                       prefill_chunk=16, decode_chunk=2)
    try:
        theirs = _prefill_then_step(
            SimpleNamespace(prefill=eng._prefill,
                            decode_step=eng._decode_step),
            eng._states, eng._weights)
    finally:
        eng.shutdown(1.0)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_array_equal(a, b)


def test_the_programs_keep_the_names_a_device_trace_shows():
    """`perfbench/harness/readers.py` finds `jit_<name>` in the trace."""
    net = _dense_net()
    plan = GPTPlan(net)
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=S, page=PAGE, pool_pages=POOL, cdt=plan.cdt, kv_quant=None,
        tp_shard=None, tp_axis=None))
    programs = decode_programs.build_programs(
        plan, states, n_slots=S, page=PAGE, L_logical=L, decode_chunk=2,
        top_k=0, logprobs=0, tp=None, donate=False)
    for name, fn in vars(programs).items():
        assert fn.__name__ == name
        assert fn.__wrapped__.__name__ == name


def _imported_modules(path: Path) -> set:
    """Every module a file imports, at top level or inside a function."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module)
            out.update(f"{node.module}.{a.name}" for a in node.names)
    return out


@pytest.mark.parametrize("module", [
    "decode_programs", "page_pool", "kv_handoff", "block_state",
    "speculative"])
def test_what_the_engine_stands_on_never_imports_it_back(module):
    src = SERVING / f"{module}.py"
    up = [m for m in _imported_modules(src) if "decode_engine" in m]
    assert not up, f"{module}.py imports {up}"
    assert "DecodeEngine(" not in src.read_text()


def test_the_engine_imports_its_three_parts():
    mods = _imported_modules(SERVING / "decode_engine.py")
    for part in ("decode_programs", "page_pool", "kv_handoff"):
        assert any(m.endswith(part) or m.endswith(part + ".PagePool")
                   or m.endswith(part + ".HandoffPlane")
                   for m in mods), part


def test_block_states_env_carries_no_function():
    eng = DecodeEngine(_dense_net(), n_slots=S, max_len=L, page_size=PAGE)
    try:
        env = vars(eng._states[0].env)
    finally:
        eng.shutdown(1.0)
    assert set(env) == {"n_slots", "page", "pool_pages", "cdt", "kv_quant",
                        "tp_shard", "tp_axis"}
    assert not [k for k, v in env.items()
                if callable(v) and not isinstance(v, (type, np.dtype))]


def test_the_scheduler_reaches_the_plane_at_one_place_an_iteration():
    src = inspect.getsource(DecodeEngine._schedule)
    assert src.count("self._plane.step()") == 1
    # inside the iteration: nothing else of the plane
    assert src.count("self._plane.") - src.count("self._plane.fail_all") == 1
    for gone in ("_step_migrations", "_serve_prefix_exports",
                 "_sweep_leases", "_export_slot", "_free_request_pages_locked",
                 "_release_lease_locked"):
        assert not hasattr(DecodeEngine, gone), gone
    options = [p for p in inspect.signature(DecodeEngine.__init__)
               .parameters.values() if p.kind is p.KEYWORD_ONLY]
    assert len(options) == 25
    assert "prefill_chunk_budget" not in {p.name for p in options}


def test_sampling_helpers_greedy_finite_screen_and_logprobs():
    logits = jnp.asarray([[0.1, 2.0, -1.0, 0.5],
                          [3.0, jnp.nan, 0.0, 1.0],
                          [0.0, 0.0, 5.0, 1.0]], jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])
    tok, new_keys = decode_programs.sample_slots(
        jnp.nan_to_num(logits), keys, jnp.zeros((3,)), 0)
    np.testing.assert_array_equal(tok, [1, 0, 2])    # temps <= 0: argmax
    assert not np.array_equal(np.asarray(new_keys), np.asarray(keys))
    hot, _ = decode_programs.sample_slots(
        jnp.nan_to_num(logits), keys, jnp.full((3,), 1e-4), 1)
    np.testing.assert_array_equal(hot, [1, 0, 2])    # top-1 leaves one
    ok = decode_programs.logits_ok(logits, jnp.asarray([True, True, True]))
    np.testing.assert_array_equal(ok, [True, False, True])
    idle = decode_programs.logits_ok(logits,
                                     jnp.asarray([True, False, True]))
    np.testing.assert_array_equal(idle, [True, True, True])
    chosen, top_v, top_i = decode_programs.token_logprobs(
        logits[::2], jnp.asarray([1, 2]), 2)
    np.testing.assert_array_equal(top_i, [[1, 3], [2, 3]])
    np.testing.assert_allclose(chosen, top_v[:, 0], rtol=1e-6)
    assert (np.diff(np.asarray(top_v), axis=1) <= 0).all()
    assert (np.asarray(top_v) <= 0).all()
