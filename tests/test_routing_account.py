"""`block_state.RoutingAccount`: a plan's routed-expert counts, from
the lists a decode step's blocks append to, through the tuple the step
returns, to the eight `moe_*` keys of `stats()`.

Held to the arithmetic the engine did itself before the account: what
`packed` -> `add` -> `counters()` reports of a step, or of a chunk of
steps, equals the eight numbers worked out directly from the routers'
gates, an inactive slot masked out; and the totals outlive a swap to a
plan that holds another number of experts, while the facts follow it.
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_deepseek_v2 as dsv2
import test_hybrid_decoder as granite
import test_longcat_flash as longcat
import test_nemotron_h as nemotron

from deeplearning4j_tpu.models.transformer import GPTPlan
from deeplearning4j_tpu.parallel import experts
from deeplearning4j_tpu.serving import block_state, decode_programs
from deeplearning4j_tpu.serving.decode_engine import DecodeEngine

S, PAGE, POOL, L, CHUNK = 3, 8, 12, 32, 4
ACTIVE = np.asarray([True, False, True])


def _granite():
    """Top-2 of 8 softmax-routed experts + a shared one, 4 held."""
    cfg = granite._config()
    sz = granite.fam.sizes(cfg)
    net = granite.fam.build_net(sz, training=True, dtype=jnp.float32)
    granite.fam.install(net, jax.tree.map(
        lambda a: a.astype(jnp.float32), granite.fam.make_weights(5, sz)))
    return net


NETS = {
    "granite": _granite,
    # sigmoid-routed ungated relu^2 experts in blocks of their own, top-2
    # of 8 published with experts 4-7 held
    "nemotron": lambda: nemotron._build(nemotron._config(
        n_routed_experts=4, deployment=dict(
            n_routed_experts_published=8, experts_held_first=4)))[3],
    # 8 real + 4 zero-compute experts on a shortcut, top-3
    "longcat": lambda: longcat._build(longcat._config())[3],
    # 16 experts in 4 groups of which a row reaches 2, top-3
    "dsv2": lambda: dsv2._build(dsv2._config())[3],
}


@pytest.fixture(scope="module", params=sorted(NETS))
def built(request):
    """(plan, programs, resident weights, caches and registers after one
    prompt in each of the S slots)."""
    net = NETS[request.param]()
    plan = GPTPlan(net)
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=S, page=PAGE, pool_pages=POOL, cdt=plan.cdt, kv_quant=None,
        tp_shard=None, tp_axis=None))
    programs = decode_programs.build_programs(
        plan, states, n_slots=S, page=PAGE, L_logical=L, decode_chunk=CHUNK,
        top_k=0, logprobs=0, tp=None, donate=False)
    weights = plan.resident_weights(net._params)
    caches = [st.alloc() for st in states]
    table = np.zeros((S, L // PAGE), np.int32)
    tok = pos = jnp.zeros((S,), jnp.int32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(S)])
    temps = jnp.zeros((S,), jnp.float32)
    for slot in range(S):
        table[slot, :2] = [1 + 2 * slot, 2 + 2 * slot]
        ids = np.zeros((1, 8), np.int32)
        ids[0, :6] = np.random.default_rng(slot).integers(0, 97, 6)
        kp, kdec = jax.random.split(jax.random.PRNGKey(slot))
        caches, tok, pos, keys, temps, _, ok = programs.prefill(
            weights, caches, jnp.asarray(ids), jnp.asarray(6, jnp.int32),
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(table[slot, :1]), tok, pos, keys, temps, kp, kdec,
            jnp.asarray(0.0, jnp.float32))[:7]
        assert bool(ok)
    return plan, programs, (weights, caches, jnp.asarray(table), tok, pos,
                            keys, temps, jnp.asarray(ACTIVE))


def _from_the_gates(plan, gates, n_steps):
    """The eight numbers, straight from every routed block's gates over
    all its outputs `(S, E)`, in the order the blocks ran."""
    ffns = block_state.routed_ffns(plan)
    assert len(gates) == n_steps * len(ffns)
    want = dict.fromkeys(
        ("moe_routed", "moe_held_choices", "moe_experts_hit",
         "moe_experts_read", "moe_steps", "moe_zero_choices",
         "moe_rows_local"), 0)
    for j, g in enumerate(gates):
        ffn = ffns[j % len(ffns)]
        first, held = ffn.held
        chose = (jnp.asarray(g)[:, first:first + held] != 0) \
            & ACTIVE[:, None]
        want["moe_held_choices"] += int(chose.sum())
        want["moe_experts_hit"] += int(jnp.any(chose, axis=0).sum())
        # the grouped product reads the held experts a live row chose
        want["moe_experts_read"] += int(jnp.any(chose, axis=0).sum())
        want["moe_rows_local"] += int(jnp.any(chose, axis=1).sum())
        if ffn.n_zero_experts:
            zero = jnp.asarray(g)[:, g.shape[1] - ffn.n_zero_experts:]
            want["moe_zero_choices"] += int(
                ((zero != 0) & ACTIVE[:, None]).sum())
        want["moe_routed"] += int(ACTIVE.sum()) * ffn.top_k
    want["moe_steps"] = n_steps
    want["moe_experts_held"] = sum(ffn.held[1] for ffn in ffns)
    return want


@pytest.mark.parametrize("program,n_steps", [("decode_step", 1),
                                             ("decode_chunked", CHUNK)])
def test_packed_add_counters_equal_the_gates_own_arithmetic(
        built, program, n_steps, monkeypatch):
    plan, programs, args = built
    gates = []
    routed_gates = experts.routed_gates

    def recording(logits, *a, **kw):
        out = routed_gates(logits, *a, **kw)
        gates.append(np.asarray(out))
        return out

    monkeypatch.setattr(experts, "routed_gates", recording)
    # eagerly, the scan as a Python loop: the gates are numbers
    with jax.disable_jit():
        out = getattr(programs, program)(*args)
    monkeypatch.undo()
    account = block_state.RoutingAccount(plan)
    assert all(v == 0 for k, v in account.counters().items()
               if k != "moe_experts_held")
    account.add(jax.device_get(out[-1]), int(ACTIVE.sum()))
    want = _from_the_gates(plan, gates, n_steps)
    assert account.counters() == want
    assert want["moe_held_choices"] > 0 and want["moe_rows_local"] > 0
    assert all(type(v) is int for v in account.counters().values())
    # the compiled program packs what the eager one did
    jitted = getattr(programs, program)(*args)
    again = block_state.RoutingAccount(plan)
    again.add(jax.device_get(jitted[-1]), int(ACTIVE.sum()))
    assert again.counters() == want


def test_a_net_that_routes_nowhere_packs_nothing_and_counts_zeros():
    from test_decode_programs import _dense_net

    account = block_state.RoutingAccount(GPTPlan(_dense_net()))
    d = SimpleNamespace(**account.step_fields(jnp.asarray([True])))
    assert d.count_mask is None and account.packed(d) == ()
    assert account.counters() == dict.fromkeys(
        ("moe_routed", "moe_held_choices", "moe_experts_hit",
         "moe_experts_read", "moe_steps", "moe_zero_choices",
         "moe_rows_local", "moe_experts_held"), 0)
    account.count_prefill(384)
    assert account.prefill_sorted_n == 0


def test_blocks_that_hold_different_numbers_of_experts_are_refused():
    net = NETS["nemotron"]()
    plan = GPTPlan(net)
    routed = block_state.routed_ffns(plan)
    assert len(routed) == 2 and routed[0] is routed[1]
    import dataclasses

    other = dataclasses.replace(routed[0], experts_held=(0, 2))
    for i in plan.block_is:
        if getattr(plan.layers[i], "ffn", None) is routed[0]:
            plan.layers[i].ffn = other
            break
    with pytest.raises(ValueError, match="different numbers of experts"):
        block_state.RoutingAccount(plan)


def test_a_swap_keeps_the_totals_and_takes_the_new_plans_facts():
    """Nemotron's toy with all 8 experts held, then with experts 4-7:
    the counts of the first stay in `stats()` and grow under the second,
    whose `moe_experts_held` is its own."""
    whole = nemotron._build(nemotron._config())[3]
    half = NETS["nemotron"]()
    prompt = np.random.default_rng(3).integers(0, 97, 9).astype(np.int32)
    eng = DecodeEngine(whole, n_slots=2, max_len=L, page_size=PAGE,
                       prompt_buckets=(16,), prefill_chunk=L,
                       decode_chunk=1)
    try:
        eng.generate(prompt, n_tokens=5, timeout=300.0)
        before = eng.stats()
        assert before["moe_experts_held"] == 2 * 8
        assert before["moe_steps"] == 4
        assert before["moe_held_choices"] == before["moe_routed"] == 4 * 2 * 2
        eng.drain_and_swap(half, timeout=300.0)
        swapped = eng.stats()
        assert swapped["moe_experts_held"] == 2 * 4
        moved = [k for k in before if k.startswith("moe_")
                 and k != "moe_experts_held"]
        assert len(moved) == 7
        assert {k: swapped[k] for k in moved} == {k: before[k] for k in moved}
        assert swapped["loop"]["prefill_sorted_n"] \
            == before["loop"]["prefill_sorted_n"]
        eng.generate(prompt, n_tokens=5, timeout=300.0)
        after = eng.stats()
        assert after["moe_steps"] == 8 and after["moe_routed"] == 2 * 16
        assert before["moe_held_choices"] < after["moe_held_choices"] \
            < after["moe_routed"]
    finally:
        eng.shutdown(2.0)
