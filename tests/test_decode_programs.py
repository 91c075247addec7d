"""The seams of the decode engine's four parts:

- `serving.decode_programs.build_programs` runs with no `DecodeEngine`
  and no thread, and its programs compute what the engine's do;
- the modules the engine stands on never import it back;
- `block_state`'s `env` carries numbers and the tp plan, no function;
- the scheduler reaches the hand-off plane at one place an iteration;
- the scheduler and the programs name no cache kind and no router: what a
  kind keeps, counts, refuses and copies is `block_state`'s to say, and
  `stats()` is what it was before that moved.
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
                        "tp_shard", "tp_axis", "ring_pages"}  # PR 49
    assert env["ring_pages"] == 0
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


@pytest.mark.parametrize("module", ["decode_engine", "decode_programs"])
def test_the_scheduler_and_the_programs_name_no_kind_and_no_router(module):
    """Read with `ast`: comments and docstrings do not count. `"kv"`
    stays: it is the key of the `quantize` option."""
    named = []
    for node in ast.walk(ast.parse((SERVING / f"{module}.py").read_text())):
        if isinstance(node, ast.Constant) \
                and node.value in ("recurrent", "latent", "ks", "vs"):
            named.append((node.lineno, repr(node.value)))
        elif isinstance(node, ast.Attribute) and (
                node.attr == "kind"
                or node.attr.startswith(("moe_", "_moe_"))
                or node.attr in ("_n_held", "_n_zero", "_routed_ffns",
                                 "_sorted_rows", "_recurrent",
                                 "_blocks_by_kind", "moe_held",
                                 "moe_zero_experts")):
            named.append((node.lineno, "." + node.attr))
    assert not named, f"{module}.py names {named}"


def test_the_phase_clock_keeps_no_routers_verdict():
    from deeplearning4j_tpu.serving import observability

    phases = observability.SchedulerPhases()
    assert not hasattr(phases, "prefill_sorted_n")
    assert "prefill_sorted_n" not in phases.counters()
    eng = DecodeEngine(_dense_net(), n_slots=S, max_len=L, page_size=PAGE)
    try:
        loop = eng.stats()["loop"]
    finally:
        eng.shutdown(1.0)
    assert loop["prefill_sorted_n"] == 0
    assert set(phases.counters()) | {"prefill_sorted_n"} == set(loop)


def test_a_kind_made_here_is_refused_for_what_it_declares(monkeypatch):
    """A fifth kind edits `block_state.py` only: here, not even that."""
    class PagesOfATest(block_state.KVPages):
        refuses = {"prefix_cache": "pages made in a test"}

    monkeypatch.setitem(block_state._KINDS, "kv", PagesOfATest)
    net = _dense_net()
    with pytest.raises(
            block_state.RecurrentStateUnsupported,
            match=r"^not supported for this network's blocks yet: "
                  r"prefix_cache \(a hit needs pages made in a test at the "
                  r"shared boundary; only K/V pages are kept\)$"):
        DecodeEngine(net, n_slots=S, max_len=L, page_size=PAGE,
                     prefix_cache=True)
    eng = DecodeEngine(net, n_slots=S, max_len=L, page_size=PAGE,
                       quantize={"kv": "int8"})
    try:
        assert {type(st) for st in eng._states} == {PagesOfATest}
        st = eng.stats()
    finally:
        eng.shutdown(1.0)
    assert st["kv_blocks"] == 2 and st["kv_quant_bits"] == 8


# `stats()` of the parent commit (PR 44) after one 6-token prompt and 6
# tokens, as `{type name: keys}`: the same for a dense, a hybrid routed, a
# latent and a stateless-block net, whatever moved behind `block_state`;
# PR 49 added the window kind's keys (`WINDOW_STATS`, `WINDOW_LOOP`), all
# zero on these nets but `kv_positions_*`
WINDOW_STATS = """window_blocks window_bytes_per_slot window_pages_in_use
    window_pages_in_use_peak window_ring_pages""".split()
WINDOW_LOOP = ["kv_positions_attended", "kv_positions_context"]
PARENT_STATS = {
    "int": """active_slots admitted cluster_prefix_hit_tokens decode_steps
        failures handoff_leases handoffs_aborted handoffs_committed
        handoffs_expired handoffs_unfetched kv_blocks kv_bytes_per_token
        kv_quant_bits kv_transfer_bytes latent_blocks latent_bytes_per_token
        max_len max_queued_pages migrations_in migrations_out
        moe_experts_held moe_experts_hit moe_experts_read moe_held_choices
        moe_routed moe_rows_local moe_steps moe_zero_choices n_slots
        page_size pages_in_use pages_in_use_peak pool_pages preemptions
        prefill_chunk prefill_chunks prefills prefix_exports
        prefix_fetch_bytes prefix_fetch_fallbacks prefix_fetches queued
        queued_page_demand recurrent_blocks served shed_deadline
        shed_out_of_pages shed_overload shed_page_quota shed_quota
        shed_unavailable slo_sheds state_bytes_per_slot state_resets
        stateless_blocks submitted swaps tokens_generated tp_degree
        tp_kv_bytes_per_token_per_shard weight_casts
        weights_resident_bytes""".split() + WINDOW_STATS,
    "float": """cluster_prefix_hit_tokens_pct page_fragmentation_pct
        prefix_fetch_ms queue_wait_s slot_occupancy_pct""".split(),
    "list": ["prompt_buckets"],
    "dict": ["build", "compile", "loop", "tenants"],
}
PARENT_NESTED = {
    "build": {
        "int": """build.plan_n build.state_n build.weight_hash_n
            build.weights_n builds weight_hash_bytes
            weight_hash_host_bytes""".split(),
        "float": """build.plan_s build.state_s build.weight_hash_s
            build.weights_s""".split()},
    "compile": {
        "int": """backend_n cache_hits cache_load_n cache_misses lower_n
            trace_n""".split(),
        "float": "backend_s cache_load_s lower_s trace_s".split(),
        "dict": ["by_fun"]},
    "loop": {
        "int": """admit_n ahead_n decode.deliver_n decode.dispatch_n
            decode.wait_n drained_n housekeeping_n iterations kv_pages_table
            kv_pages_walked overshoot_tokens prefill.deliver_n
            prefill.dispatch_n prefill.wait_n prefill_sorted_n sink_n
            spans_dropped wait-work_n""".split() + WINDOW_LOOP,
        "float": """admit_s decode.deliver_s decode.dispatch_s decode.wait_s
            housekeeping_s prefill.deliver_s prefill.dispatch_s
            prefill.wait_s sink_s wait-work_s""".split()},
    "tenants": {},
}
# and, of the keys that moved, the parent's numbers
_DENSE = dict(
    kv_quant_bits=32, kv_bytes_per_token=512, state_bytes_per_slot=0,
    state_resets=0, recurrent_blocks=0, kv_blocks=2, stateless_blocks=0,
    latent_blocks=0, latent_bytes_per_token=0, moe_routed=0,
    moe_held_choices=0, moe_experts_hit=0, moe_experts_read=0, moe_steps=0,
    moe_zero_choices=0, moe_rows_local=0, moe_experts_held=0,
    tp_kv_bytes_per_token_per_shard=512)
PARENT_NUMBERS = {
    "dense": _DENSE,
    "routed": dict(
        _DENSE, kv_bytes_per_token=256, state_bytes_per_slot=20224,
        state_resets=1, recurrent_blocks=2, kv_blocks=1, moe_routed=30,
        moe_held_choices=23, moe_experts_hit=23, moe_experts_read=23,
        moe_steps=5, moe_rows_local=15, moe_experts_held=12,
        tp_kv_bytes_per_token_per_shard=256),
    "latent": dict(
        _DENSE, kv_bytes_per_token=0, kv_blocks=0, latent_blocks=4,
        latent_bytes_per_token=320, moe_routed=30, moe_held_choices=17,
        moe_experts_hit=17, moe_experts_read=17, moe_steps=5,
        moe_zero_choices=13, moe_rows_local=10, moe_experts_held=16,
        tp_kv_bytes_per_token_per_shard=0),
    "stateless": dict(
        _DENSE, state_bytes_per_slot=11264, state_resets=1,
        recurrent_blocks=2, kv_blocks=1, stateless_blocks=2, moe_routed=20,
        moe_held_choices=20, moe_experts_hit=20, moe_experts_read=20,
        moe_steps=5, moe_rows_local=10, moe_experts_held=16),
}


def _by_type(d: dict) -> dict:
    out = {}
    for k, v in d.items():
        out.setdefault(type(v).__name__, set()).add(k)
    return out


def _sets(by_type: dict) -> dict:
    return {t: set(keys) for t, keys in by_type.items()}


def _latent_net():
    import test_longcat_flash as longcat

    return longcat._build(longcat._config())[3]


def _stateless_net():
    import test_nemotron_h as nemotron

    return nemotron._build(nemotron._config())[3]


@pytest.mark.parametrize("name,make_net", [
    ("dense", _dense_net), ("routed", _composed_net),
    ("latent", _latent_net), ("stateless", _stateless_net)])
def test_stats_are_the_parents_keys_types_and_numbers(name, make_net):
    from deeplearning4j_tpu.serving import observability

    eng = DecodeEngine(make_net(), n_slots=S, max_len=L, page_size=PAGE,
                       pool_pages=POOL, prompt_buckets=(8, 16),
                       prefill_chunk=16, decode_chunk=4)
    try:
        eng.generate(np.asarray([4, 9, 2, 30, 11, 7], np.int32), n_tokens=6,
                     timeout=300.0)
        st = eng.stats()
    finally:
        eng.shutdown(2.0)
    assert _by_type(st) == _sets(PARENT_STATS)
    for key, want in PARENT_NESTED.items():
        assert _by_type(st[key]) == _sets(want), key
    assert observability.DECODE_ENGINE_STATS_KEYS <= set(st)
    assert {k: st[k] for k in PARENT_NUMBERS[name]} == PARENT_NUMBERS[name]
    assert [st[k] for k in WINDOW_STATS] == [0] * len(WINDOW_STATS)
    loop = st["loop"]
    assert loop["kv_positions_attended"] == loop["kv_positions_context"]


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
