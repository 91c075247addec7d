"""Kernels of the serving and training paths compiled at real widths for a TPU v5e that
is described, not attached (libtpu's compiler runs in the sandbox):
Mosaic refuses here what it would refuse on the chip (tiling, VMEM), at
no chip time. Nothing runs, so nothing here says anything about results
or speed. The topology is described inside a fixture, never at import
(one process a worker may load libtpu; see the on-chip-measurement
guide), and all such tests live in this one file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", (64, 512), ids=("decode-64-slots",
                                                "prefill-512-tokens"))
def test_grouped_expert_kernel_compiles_at_the_published_widths(one_chip,
                                                                rows):
    """granite-4.0-h-small's routed experts as one chip holds them: 36
    experts of 4096 x 768, bfloat16."""
    from deeplearning4j_tpu.ops.pallas_moe_experts import moe_experts

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the suite runs with x64 on (conftest); the chip's processes do not,
    # and Mosaic takes 32-bit block indices only (the hit-first walk's
    # prefetched indices are int32 either way)
    with jax.enable_x64(False):
        compiled = moe_experts.lower(
            S((rows, 4096)), S((rows, 36), jnp.float32),
            S((36, 4096, 768)), S((36, 4096, 768)),
            S((36, 768, 4096)), S((36,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", (64, 512), ids=("decode-64-slots",
                                                "prefill-512-tokens"))
def test_ungated_expert_kernel_compiles_off_the_lane_grid(one_chip, rows):
    """NVIDIA-Nemotron-3-Nano-30B-A3B's routed experts as one chip holds
    them: 64 ungated relu^2 experts of 2688 x 1856 (14.5 x 128), both
    matrices held (1856, 2688), bfloat16. Nothing of a stack's size is
    made beside it: a (2688, 1856) block compiles too, behind a
    lane-padded copy of the whole stack every call."""
    from deeplearning4j_tpu.ops.pallas_moe_experts import moe_experts

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = moe_experts.lower(
            S((rows, 2688)), S((rows, 64), jnp.float32), None,
            S((64, 1856, 2688)), S((64, 1856, 2688)),
            S((64,), jnp.bool_), act="relu2").compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("d,f,E,act,tiles,tf", [
    (6144, 2048, 16, "gated_silu", 24, 1024),
    (2688, 1856, 64, "relu2", 88, 1856),
], ids=("longcat-in-tiles-of-f", "nemotron-relu2"))
def test_sorted_expert_kernel_compiles_at_the_published_widths(
        one_chip, monkeypatch, d, f, E, act, tiles, tf):
    """The sorted product's two other shapes: LongCat-Flash-Chat's
    experts (6144 x 2048, 75.5 MB: over the VMEM ceiling whole) in two
    tiles of their width under a second grid axis, at the rows a
    1,024-token prefill's static size gives; nemotron's ungated relu^2
    experts (2688 x 1856, both matrices held (1856, 2688)) whole, at a
    512-token prefill's worst case. Float32 rows out."""
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    monkeypatch.setattr(pme, "_vmem_limit", lambda: 112 << 20)

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pme.f_tile(pme.SORTED_ROWS, d, f, jnp.bfloat16, act) == tf
    M, up = tiles * pme.SORTED_ROWS, (E, f, d) if act == "relu2" \
        else (E, d, f)
    args = (S((M, d)), S((M, 1), jnp.float32), S((tiles,), jnp.int32),
            S((1,), jnp.int32), None if act == "relu2" else S(up), S(up),
            S((E, f, d)))
    with jax.enable_x64(False):
        lowered = pme.moe_experts_sorted.lower(*args, act=act)
        compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert lowered.out_info.dtype == jnp.float32
    assert lowered.out_info.shape == (M, d)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def _shapes(one_chip):
    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    return S


def test_gated_delta_step_kernel_compiles_at_the_published_widths(one_chip):
    """Olmo-Hybrid-7B's linear layers: 64 slots, 30 heads of 96 x 192,
    the state `(64, 96, 5760)` float32 updated in place."""
    from deeplearning4j_tpu.ops.pallas_delta_step import gdn_step

    S = _shapes(one_chip)
    f32 = jnp.float32
    with jax.enable_x64(False):
        compiled = jax.jit(gdn_step.__wrapped__, donate_argnums=(0,)).lower(
            S((64, 96, 5760), f32), S((64, 30, 96), f32),
            S((64, 30, 96), f32), S((64, 30, 192)), S((64, 30), f32),
            S((64, 30), f32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    stats = compiled.memory_analysis()
    # the state goes in and comes out through one buffer, unpadded, and
    # the kernel keeps nothing of its size beside it
    assert stats.alias_size_in_bytes == 64 * 96 * 5760 * 4
    assert stats.temp_size_in_bytes < 1 << 20


def test_channel_gated_delta_step_kernel_compiles_at_the_published_widths(
        one_chip):
    """Ling-3.0-flash's linear layers: 128 slots, 32 heads of 128 x 128
    with a decay a key channel, the state `(128, 128, 4096)` float32
    (whole lane tiles, one head a group) updated in place; q, k and the
    decay go in as `(1, 32, 128)` row blocks and are turned into columns
    in the kernel (a 32-row tile padded to 128 rows and transposed)."""
    from deeplearning4j_tpu.ops.pallas_delta_step import kda_step

    S = _shapes(one_chip)
    f32 = jnp.float32
    with jax.enable_x64(False):
        compiled = jax.jit(kda_step.__wrapped__, donate_argnums=(0,)).lower(
            S((128, 128, 4096), f32), S((128, 32, 128), f32),
            S((128, 32, 128), f32), S((128, 32, 128)),
            S((128, 32, 128), f32), S((128, 32), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _call_operands(text)[1] == [
        "f32[128,32,128]{2,1,0}"] * 3 + [
        "bf16[128,32,128]{2,1,0}", "f32[128,32]{1,0}",
        "f32[128,128,4096]{2,1,0}"]
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == 128 * 128 * 4096 * 4
    assert stats.temp_size_in_bytes < 8 << 20


def _call_operands(text: str) -> tuple:
    """(the HLO lines that define the operands of the program's ONE
    Pallas call, the layouts the call constrains them to)."""
    import re

    (call,) = (line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line)
    names = re.findall(r"%[\w.\-]+", re.search(
        r"custom-call\((.*?)\), custom_call_target", call).group(1))
    defs = {line.split(" = ")[0].strip().removeprefix("ROOT "): line
            for line in text.splitlines() if " = " in line}
    layouts = re.search(r"operand_layout_constraints=\{(.*?\})\}",
                        call).group(1)
    return [defs[n] for n in names], re.findall(r"\w+\[[\d,]*\]\{[\d,]*\}",
                                                layouts)


def test_a_channel_gated_mixer_step_prepares_nothing_for_the_kernel(
        one_chip, monkeypatch):
    """ONE `ChannelGatedDeltaMixer.step` at Ling-3.0-flash's widths (d
    2560, 32 heads of 128 x 128, 128 slots) under a scan of 4, as a
    decode chunk runs it, with the kernel steered on: what XLA leaves
    around the call. Before the kernel took head rows a layer paid three
    `(32, 128) -> (128, 32)` transposes a slot into a lane-padded
    `f32[128,32,128]{1,2,0}`, three transposing fusions and two
    concatenates (`pad_maximum_fusion`) every step (ISSUE 47's count);
    none of them may come back."""
    import re

    import chip_smoke
    from deeplearning4j_tpu.nn.conf.decoder_block import (
        ChannelGatedDeltaMixer,
    )
    from deeplearning4j_tpu.ops import pallas_delta_step

    monkeypatch.setattr(pallas_delta_step, "_platform_supported",
                        lambda: True)
    monkeypatch.setattr(pallas_delta_step, "_probe_verdict",
                        lambda *a, **k: True)
    S = _shapes(one_chip)
    mixer = ChannelGatedDeltaMixer(n_heads=32, key_dim=128, value_dim=128)
    d, slots = 2560, 128
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda: mixer.init_params(
            jax.random.PRNGKey(0), d, jnp.bfloat16,
            lambda key, shape, *fans: jnp.zeros(shape, jnp.bfloat16))))
    state, tail = (S(*sd) for sd in mixer.state_shapes(slots, jnp.bfloat16))

    def chunk(params, x, h, tail, active):
        def body(carry, _):
            x, h, tail = carry
            y, h, tail = mixer.step(params, x, h, tail, active)
            return (x + y, h, tail), None
        return jax.lax.scan(body, (x, h, tail), None, length=4)[0]

    with jax.enable_x64(False):
        text = jax.jit(chunk, donate_argnums=(2, 3)).lower(
            params, S((slots, d)), state, tail,
            S((slots,), jnp.bool_)).compile().as_text()
    operands, layouts = _call_operands(text)
    assert layouts[:3] == ["f32[128,32,128]{2,1,0}"] * 3
    assert not re.search(r"f32\[128,32,128\]\{1,2,0", text)
    assert "pad_maximum_fusion" not in text
    made_by = [re.search(r'op_name="([^"]*)"', line) for line in operands]
    made_by = [m.group(1).rsplit("/", 1)[-1] for m in made_by if m]
    assert made_by and not {"concatenate", "transpose"} & set(made_by)
    # the state is carried through the chunk in one buffer, in place
    assert "f32[128,128,4096]{2,1,0" in operands[-1]
    assert chip_smoke.pool_layout_copies(text, {"f32[128,128,4096]"}) == 0


@pytest.mark.parametrize("B,H,T,kernels", [
    (4, 12, 2048, 2), (1, 1, 57344, 2), (1, 1, 58368, 3)],
    ids=("cgpt590m-t2048", "longest-fused", "first-split"))
def test_flash_attention_backward_compiles_as_one_kernel(one_chip, B, H, T,
                                                         kernels):
    """The train cell's attention layer, forward and backward, in blocks
    of 1,024 at head size 128, bfloat16: the forward and ONE backward
    kernel, dQ of a (batch, head) whole in VMEM; so at the longest
    sequence whose dQ the fused form may hold, and the two-kernel split
    one block past it."""
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=1024,
                                       block_k=1024).astype(jnp.float32))

    x = jax.ShapeDtypeStruct((B, T, H, 128), jnp.bfloat16,
                             sharding=one_chip)
    with jax.enable_x64(False):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == kernels


@pytest.mark.parametrize("slots,H,Hkv,pool", [
    (32, 16, 16, 136), (64, 30, 30, 576), (64, 32, 8, 576)],
    ids=("cgpt1.3b-16-heads", "olmo-30-heads", "granite-8-of-32"))
def test_paged_attention_kernel_compiles_at_the_published_widths(
        one_chip, slots, H, Hkv, pool):
    """The page walk at the three serve cells' head counts, head size
    128, pages of 128, under a page table 16 wide: pools left in HBM,
    two page buffers a pool in VMEM, a loop of dynamic length."""
    from deeplearning4j_tpu.ops.pallas_paged_attention import (
        paged_attention,
    )

    S = _shapes(one_chip)
    i32 = jnp.int32
    with jax.enable_x64(False):
        compiled = paged_attention.lower(
            S((slots, 1, H, 128)), S((pool + 1, Hkv, 128, 128)),
            S((pool + 1, Hkv, 128, 128)), S((slots, 16), i32),
            S((slots,), i32), active=S((slots,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pools are read where they lie: nothing of their size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_decode_step_lowers_the_attend_kernel_once(one_chip, monkeypatch):
    """A toy of four attention blocks (head size and page 128, so that
    Mosaic lowers it) through `build_programs`: the lowered text of
    `decode_step` and of `decode_chunked` holds the attend kernel's
    `tpu_custom_call` in ONE private function, called once a block.
    Lowering is paid on every start, compile cache or not, and a kernel
    traced inline is lowered once a layer (PERF.md, PR 26 and PR 33)."""
    import re
    from types import SimpleNamespace

    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.ops import (
        pallas_paged_attention,
        pallas_paged_kv_write,
    )
    from deeplearning4j_tpu.serving import block_state, decode_programs
    from perfbench.families import gpt_dense as fam

    for mod in (pallas_paged_attention, pallas_paged_kv_write):
        monkeypatch.setattr(mod, "_platform_supported", lambda: True)
        monkeypatch.setattr(mod, "_probe_verdict", lambda *a, **k: True)
    sz = fam.sizes({"n_embd": 256, "n_head": 2, "n_inner": 1024,
                    "n_layer": 4, "vocab_size": 512, "n_positions": 2048,
                    "layer_norm_epsilon": 1e-5})
    S = _shapes(one_chip)
    f32, i32 = jnp.float32, jnp.int32
    shapes = fam._leaf_shapes(sz)
    tree = {n: S(shapes[n], f32) for n in fam.TOP_LEAVES}
    tree["blocks"] = [{n: S(shapes[n], f32) for n in fam.BLOCK_LEAVES}
                      for _ in range(sz["L"])]
    net = fam.build_net(sz, training=False)
    net._params = fam.to_program(tree)
    plan = GPTPlan(net)
    n_slots, page = 8, 128
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=n_slots, page=page, pool_pages=32, cdt=plan.cdt,
        kv_quant=None, tp_shard=None, tp_axis=None))
    with jax.enable_x64(False):
        weights = jax.tree_util.tree_map(
            lambda a: S(a.shape, a.dtype),
            jax.eval_shape(plan.resident_weights, net._params))
        programs = decode_programs.build_programs(
            plan, states, n_slots=n_slots, page=page, L_logical=2048,
            decode_chunk=4, top_k=0, logprobs=0, tp=None, donate=True)
        caches = [tuple(S(a.shape, a.dtype) for a in jax.eval_shape(st.alloc))
                  for st in states]
        args = (weights, caches, S((n_slots, 2048 // page), i32),
                S((n_slots,), i32), S((n_slots,), i32),
                S((n_slots, 2), jnp.uint32), S((n_slots,), f32),
                S((n_slots,), jnp.bool_))
        texts = [getattr(programs, name).lower(*args).as_text()
                 for name in ("decode_step", "decode_chunked")]
    for text in texts:
        # the write and the attend: one body each, whatever the depth
        assert text.count("tpu_custom_call") == 2
        bodies = re.findall(
            r"func\.func private @(\w+)\([^\n]*\n(?:(?!func\.func).)*?"
            r"tpu_custom_call", text, re.S)
        assert sorted(bodies) == ["paged_attention", "paged_kv_write"]
        assert len(re.findall(r"call @paged_attention\(", text)) == 4
        assert len(re.findall(r"call @paged_kv_write\(", text)) == 4


def test_decode_step_compiles_at_the_published_widths(one_chip,
                                                      monkeypatch):
    """One block of each kind of Olmo-Hybrid-7B at its published widths
    (64 slots, 576 pages of 128, 30 K/V heads of 128) through
    `build_programs`, with the three kernel families of the decode path
    steered on as they are on the chip (the dispatch asks
    `jax.default_backend()`, which is the CPU here, and the probes need
    a chip to run): the gated delta step, the paged write and the paged
    attention at a head count that is no multiple of 8. No state- or
    pool-shaped copy is left in the program."""
    import json
    from pathlib import Path
    from types import SimpleNamespace

    import chip_smoke
    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.ops import (
        pallas_delta_step,
        pallas_paged_attention,
        pallas_paged_kv_write,
    )
    from deeplearning4j_tpu.serving import block_state, decode_programs
    from perfbench.families import olmo_hybrid as fam

    for mod in (pallas_delta_step, pallas_paged_attention,
                pallas_paged_kv_write):
        monkeypatch.setattr(mod, "_platform_supported", lambda: True)
        monkeypatch.setattr(mod, "_probe_verdict", lambda *a, **k: True)
    cfg = json.loads((Path(__file__).resolve().parents[1]
                      / "perfbench/configs/olmo-hybrid-7b.json").read_text())
    cfg.update(num_hidden_layers=2,
               layer_types=["linear_attention", "full_attention"])
    sz = fam.sizes(cfg)
    S = _shapes(one_chip)
    shapes = fam._leaf_shapes(sz)
    tree = {n: S(shapes[n]) for n in fam.TOP_LEAVES}
    tree["layers"] = [{n: S(shapes[n])
                       for n in fam.MIXER_LEAVES[kind] + fam.FFN_LEAVES}
                      for kind in sz["layer_types"]]
    net = fam.build_net(sz, training=False)
    net._params = fam.to_program(tree)
    plan = GPTPlan(net)
    n_slots, page = 64, 128
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=n_slots, page=page, pool_pages=576, cdt=plan.cdt,
        kv_quant=None, tp_shard=None, tp_axis=None))
    with jax.enable_x64(False):
        programs = decode_programs.build_programs(
            plan, states, n_slots=n_slots, page=page, L_logical=2048,
            decode_chunk=4, top_k=0, logprobs=0, tp=None, donate=True)
        caches = [tuple(S(a.shape, a.dtype) for a in jax.eval_shape(st.alloc))
                  for st in states]
        i32, f32 = jnp.int32, jnp.float32
        text = programs.decode_step.lower(
            net._params, caches, S((n_slots, 2048 // page), i32),
            S((n_slots,), i32), S((n_slots,), i32),
            S((n_slots, 2), jnp.uint32), S((n_slots,), f32),
            S((n_slots,), jnp.bool_)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "gdn_step" in text
    kept = {"f32[64,96,5760]", "bf16[577,30,128,128]"}
    assert chip_smoke.pool_layout_copies(text, kept) == 0


def test_single_sub_layer_decode_step_compiles_at_the_published_widths(
        one_chip, monkeypatch):
    """One block of each kind of NVIDIA-Nemotron-3-Nano-30B-A3B at its
    published widths (64 slots, 576 pages of 128; a Mamba-2 step over 64
    heads in 8 B/C groups, 32 query heads over 2 K/V heads of 128 on a
    hidden size of 2688, 64 held experts 1856 wide) through
    `build_programs`, the three kernel families of its decode path
    steered on as they are on the chip. The grouped state update stays
    one fusion that reads and writes the state once, and no state- or
    pool-shaped copy is left in the program."""
    import re
    from types import SimpleNamespace

    import chip_smoke
    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.ops import (
        pallas_moe_experts,
        pallas_paged_attention,
        pallas_paged_kv_write,
    )
    from deeplearning4j_tpu.serving import block_state, decode_programs
    from perfbench.families import nemotron_h as fam

    for mod in (pallas_moe_experts, pallas_paged_attention,
                pallas_paged_kv_write):
        monkeypatch.setattr(mod, "_platform_supported", lambda: True)
        monkeypatch.setattr(mod, "_probe_verdict", lambda *a, **k: True)
    monkeypatch.setattr(pallas_moe_experts, "_vmem_limit",
                        lambda: 112 << 20)
    sz = fam.sizes(chip_smoke.SUBLAYER)
    S = _shapes(one_chip)
    shapes = fam._leaf_shapes(sz)
    tree = {n: S(shapes[n]) for n in fam.TOP_LEAVES}
    tree["layers"] = [
        {n: S(shapes[n], jnp.float32 if n in fam.FLOAT32_LEAVES
              else jnp.bfloat16) for n in fam.LAYER_LEAVES[kind]}
        for kind in sz["pattern"]]
    net = fam.build_net(sz, training=False)
    net._params = fam.to_program(tree)
    plan = GPTPlan(net)
    assert plan.state_kinds() == ["recurrent", "kv", "none"]
    assert plan.kv_geometry() == [(2, 128)]
    n_slots, page = 64, 128
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=n_slots, page=page, pool_pages=576, cdt=plan.cdt,
        kv_quant=None, tp_shard=None, tp_axis=None))
    with jax.enable_x64(False):
        programs = decode_programs.build_programs(
            plan, states, n_slots=n_slots, page=page, L_logical=2048,
            decode_chunk=4, top_k=0, logprobs=0, tp=None, donate=True)
        caches = [tuple(S(a.shape, a.dtype) for a in jax.eval_shape(st.alloc))
                  for st in states]
        assert caches[2] == ()
        i32, f32 = jnp.int32, jnp.float32
        text = programs.decode_step.lower(
            net._params, caches, S((n_slots, 2048 // page), i32),
            S((n_slots,), i32), S((n_slots,), i32),
            S((n_slots, 2), jnp.uint32), S((n_slots,), f32),
            S((n_slots,), jnp.bool_)).compile().as_text()
        # a 512-token prompt: the rule sends its rows to the experts
        # sorted (top-6 of 128 scored, 64 held), the decode step's walk
        prefill = programs.prefill.lower(
            net._params, caches, S((1, 512), i32), S((), i32), S((), i32),
            S((512 // page,), i32), S((n_slots,), i32), S((n_slots,), i32),
            S((n_slots, 2), jnp.uint32), S((n_slots,), f32),
            S((2,), jnp.uint32), S((2,), jnp.uint32),
            S((), f32)).compile().as_text()
    assert "moe_experts_sorted" in prefill
    assert text.count("tpu_custom_call") == 3
    assert "moe_experts" in text and "moe_experts_sorted" not in text
    kept = {"f32[64,64,64,128]", "bf16[577,2,128,128]"}
    assert chip_smoke.pool_layout_copies(text, kept) == 0
    sweeps = re.findall(r"^\s*%[\w.\-]+ = \(f32\[64,64,64\]\{.*, "
                        r"f32\[64,64,64,128\]\{.*\) fusion\(", text, re.M)
    assert len(sweeps) == 1


@pytest.mark.parametrize("shape,dtype", [
    ((64, 1856, 2688), jnp.bfloat16), ((131072, 2688), jnp.bfloat16),
    ((50257, 2048), jnp.float32), ((2048,), jnp.float32)],
    ids=("nemotron-expert-stack", "nemotron-embedding", "cgpt-f32-head",
         "a-bias"))
def test_weight_fold_reads_a_leaf_once_and_writes_no_copy(one_chip, shape,
                                                          dtype):
    """The largest leaves any cell serves, folded for the digest: one
    fusion takes the leaf, and the program's temporaries are a tile or
    two, not a widened copy (olmo's cell stands at 91% of HBM)."""
    import re

    from deeplearning4j_tpu.serving.weight_digest import fold_leaf

    leaf = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with jax.enable_x64(False):
        compiled = fold_leaf.lower(leaf).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= leaf.size * leaf.dtype.itemsize
    assert memory.temp_size_in_bytes < 1 << 20
    assert memory.output_size_in_bytes <= 512  # four words, one tile
    takes_the_leaf = re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = .* (?:fusion|reduce|copy|convert)"
        r"\([^)]*%x\.\d+", compiled.as_text(), re.M)
    assert len(takes_the_leaf) == 1 and " fusion(" in takes_the_leaf[0]


def test_latent_kernels_compile_at_the_published_widths(one_chip,
                                                        monkeypatch):
    """LongCat-Flash-Chat's latent attention as one chip serves it: 128
    slots, 64 heads' absorbed queries over a pool of 2560 pages of 128
    positions x (512 + 64), the pool's one-position write, and its
    routed experts (16 of 6144 x 2048: 75.5 MB each, over the VMEM
    ceiling whole) in tiles of their width."""
    from deeplearning4j_tpu.ops import pallas_mla_attend as mla
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    monkeypatch.setattr(mla, "_vmem_limit", lambda: 112 << 20)
    monkeypatch.setattr(pme, "_vmem_limit", lambda: 112 << 20)
    S = _shapes(one_chip)
    i32 = jnp.int32
    with jax.enable_x64(False):
        attend = mla.mla_attend.lower(
            S((128, 64, 576)), S((2561, 576, 128)), S((128, 32), i32),
            S((128,), i32), S((128,), jnp.bool_), kv_rank=512,
            sm_scale=192 ** -0.5).compile()
        write = mla.latent_write.lower(
            S((2561, 576, 128)), S((128, 576)), S((128,), i32),
            S((128,), i32)).compile()
        experts = pme.moe_experts.lower(
            S((128, 6144)), S((128, 16), jnp.float32), S((16, 6144, 2048)),
            S((16, 6144, 2048)), S((16, 2048, 6144)),
            S((16,), jnp.bool_)).compile()
    for compiled in (attend, write, experts):
        assert "tpu_custom_call" in compiled.as_text()
    # the walk takes eight pages an iteration here: two block buffers of
    # 576 x 1024 where the one-page walk had two pages
    assert mla.block_pages(128, 576, 64, jnp.bfloat16) == 8
    assert mla.attend_key(jnp.bfloat16, 64, 576, 512, 128)[-1] == "block8"
    # the pool stays where it is: nothing of its size is made beside it
    assert attend.memory_analysis().temp_size_in_bytes < 1 << 20
    assert pme.f_tile(128, 6144, 2048, jnp.bfloat16) == 1024


def _latent_programs(one_chip, monkeypatch, fam, cfg, layers, pool_pages,
                     max_len, float32=(), n_slots=128):
    """A latent family's net (or, since PR 51, granite's) at `cfg`'s
    widths, described and not drawn (`layers(sz)`: each layer's leaf
    names; `float32`: those not held in bfloat16), through
    `build_programs` for `n_slots` slots and pages of 128 with its
    kernel families steered on as they are on the chip.
    Returns the plan, the programs, their (parameters, caches), the
    decode programs' other operands and, for a prompt bucket, a
    prefill's; lower inside `jax.enable_x64(False)`."""
    from types import SimpleNamespace

    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.ops import (
        pallas_attention, pallas_delta_step, pallas_mla_attend,
        pallas_moe_experts, pallas_paged_attention, pallas_paged_kv_write,
    )
    from deeplearning4j_tpu.serving import block_state, decode_programs

    for mod in (pallas_mla_attend, pallas_moe_experts, pallas_delta_step):
        monkeypatch.setattr(mod, "_platform_supported", lambda: True)
        monkeypatch.setattr(mod, "_probe_verdict", lambda *a, **k: True)
        monkeypatch.setattr(mod, "_vmem_limit", lambda: 112 << 20)
    # a net with K/V blocks (no latent family has one)
    for mod in (pallas_attention, pallas_paged_attention,
                pallas_paged_kv_write):
        monkeypatch.setattr(mod, "_platform_supported", lambda: True)
        monkeypatch.setattr(mod, "_probe_verdict", lambda *a, **k: True)
    sz = fam.sizes(cfg)
    S = _shapes(one_chip)
    shapes = fam._leaf_shapes(sz)
    tree = {n: S(shapes[n]) for n in fam.TOP_LEAVES}
    tree["layers"] = [
        {n: S(shapes[n], jnp.float32 if n in float32 else jnp.bfloat16)
         for n in names} for names in layers(sz)]
    net = fam.build_net(sz, training=False)
    net._params = [{k: S(v.shape, v.dtype) for k, v in p.items()}
                   for p in fam.to_program(tree)]
    plan = GPTPlan(net)
    page = 128
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=n_slots, page=page, pool_pages=pool_pages, cdt=plan.cdt,
        kv_quant=None, tp_shard=None, tp_axis=None))
    i32, f32 = jnp.int32, jnp.float32
    with jax.enable_x64(False):
        programs = decode_programs.build_programs(
            plan, states, n_slots=n_slots, page=page, L_logical=max_len,
            decode_chunk=4, top_k=0, logprobs=0, tp=None, donate=True)
        caches = [jax.tree.map(lambda a: S(a.shape, a.dtype),
                               jax.eval_shape(st.alloc)) for st in states]
    slot_args = (S((n_slots,), i32), S((n_slots,), i32),
                 S((n_slots, 2), jnp.uint32), S((n_slots,), f32))
    decode_args = (S((n_slots, max_len // page), i32), *slot_args,
                   S((n_slots,), jnp.bool_))
    prefill_args = lambda T: (
        S((1, T), i32), S((), i32), S((), i32), S((T // page,), i32),
        *slot_args, S((2,), jnp.uint32), S((2,), jnp.uint32), S((), f32))
    return plan, programs, (net._params, caches), decode_args, prefill_args


def _assert_no_weight_is_relaid(text, params):
    """No instruction of the compiled program `text` copies, transposes
    or re-tiles a leaf of `params` of 1 MB or more."""
    import chip_smoke

    ops = chip_smoke.weight_layout_ops(text, chip_smoke._hlo_shapes(
        a for a in jax.tree.leaves(params) if a.ndim >= 2))
    assert ops == []


@pytest.mark.parametrize("program", ("decode_step", "decode_chunked"))
def test_shortcut_layer_decode_step_compiles_at_the_published_widths(
        one_chip, monkeypatch, program):
    """One layer of LongCat-Flash-Chat at its published widths (128
    slots, 2560 pages of 128) through `build_programs`, its three kernel
    families steered on as they are on the chip: two latent writes, two
    paged latent attentions and the tiled grouped experts in one step,
    no pool-shaped copy left in the program and no weight re-laid, in
    the step alone and in the chunk of four that serves."""
    import chip_smoke
    from perfbench.families import longcat_flash as fam

    plan, programs, held, decode_args, prefill_args = _latent_programs(
        one_chip, monkeypatch, fam, chip_smoke.LATENT,
        lambda sz: [fam.LAYER_LEAVES], 2560, 4096, fam.FLOAT32_LEAVES)
    assert plan.state_kinds() == [("latent", "latent")]
    assert plan.latent_geometry() == [(512, 64)] * 2
    assert plan.kv_geometry() == []
    with jax.enable_x64(False):
        text = getattr(programs, program).lower(
            *held, *decode_args).compile().as_text()
    assert text.count("tpu_custom_call") == 5
    for name in ("mla_attend", "latent_write", "moe_experts"):
        assert name in text
    assert "moe_experts_sorted" not in text
    assert chip_smoke.pool_layout_copies(text, {"bf16[2561,576,128]"}) == 0
    _assert_no_weight_is_relaid(text, held[0])
    if program == "decode_chunked":
        return
    # a 512-token prompt: 1 choice in 48 falls on the 16 held of
    # the router's 768, so its rows go sorted at a third of the worst
    # case's size, the walk the other branch of a conditional
    with jax.enable_x64(False):
        prefill = programs.prefill.lower(
            *held, *prefill_args(512)).compile().as_text()
    assert "moe_experts_sorted" in prefill and "conditional" in prefill
    assert f"f32[{24 * 128},6144]" in prefill


def test_latent_kernels_compile_at_128_heads(one_chip, monkeypatch):
    """DeepSeek-V2's latent attention as one chip serves it: 128 slots,
    128 heads' absorbed queries under the YaRN temperature over a pool
    of 8448 pages of 128 positions x (512 + 64) through a table 96 pages
    wide (`max_len` 12,288), and its routed experts (20 of 5120 x 1536:
    47.2 MB each): whole at a decode step's 128 rows, in two tiles of
    their width at a prefill's 512-row tiles."""
    from deeplearning4j_tpu.ops import pallas_mla_attend as mla
    from deeplearning4j_tpu.ops import pallas_moe_experts as pme

    monkeypatch.setattr(mla, "_vmem_limit", lambda: 112 << 20)
    monkeypatch.setattr(pme, "_vmem_limit", lambda: 112 << 20)
    S = _shapes(one_chip)
    i32 = jnp.int32
    with jax.enable_x64(False):
        attend = mla.mla_attend.lower(
            S((128, 128, 576)), S((8449, 576, 128)), S((128, 96), i32),
            S((128,), i32), S((128,), jnp.bool_), kv_rank=512,
            sm_scale=0.114722).compile()
        experts = [pme.moe_experts.lower(
            S((rows, 5120)), S((rows, 20), jnp.float32), S((20, 5120, 1536)),
            S((20, 5120, 1536)), S((20, 1536, 5120)),
            S((20,), jnp.bool_)).compile() for rows in (128, 4096)]
        # a 4,096-token prefill's choices sorted by expert: 6 a token in
        # whole tiles of 128 rows and a tile an expert to spare
        M = (4096 * 6 // pme.SORTED_ROWS + 20) * pme.SORTED_ROWS
        experts.append(pme.moe_experts_sorted.lower(
            S((M, 5120)), S((M, 1), jnp.float32),
            S((M // pme.SORTED_ROWS,), i32), S((1,), i32),
            S((20, 5120, 1536)), S((20, 5120, 1536)),
            S((20, 1536, 5120))).compile())
        # a 4,096-token prompt's own attention, heads first
        experts.append(mla.mla_prefill.lower(
            S((128, 4096, 128)), S((128, 4096, 64)), S((128, 4096, 128)),
            S((4096, 64)), S((128, 4096, 128)), S((1,), i32),
            sm_scale=0.114722).compile())
    for compiled in (attend, *experts):
        assert "tpu_custom_call" in compiled.as_text()
    assert mla.attend_key(jnp.bfloat16, 128, 576, 512, 128) \
        == ("bfloat16", 128, 576, 512, 128, "block8")
    # the pool (1.25 GB) stays where it is; what is made beside it is the
    # queries' and the outputs' size
    assert attend.memory_analysis().temp_size_in_bytes < 32 << 20
    assert pme.vmem_bytes_estimate(128, 5120, 1536, jnp.bfloat16) \
        < 112 << 20
    assert pme.f_tile(128, 5120, 1536, jnp.bfloat16) == 1536
    assert pme.f_tile(512, 5120, 1536, jnp.bfloat16) == 768


@pytest.mark.parametrize("family,program", [
    ("deepseek_v2", "decode_step"), ("deepseek_v2", "decode_chunked"),
    ("ling_flash", "decode_chunked")])
def test_one_sub_layer_latent_net_compiles_at_the_published_widths(
        one_chip, monkeypatch, family, program):
    """DeepSeek-V2's leading dense layer and one routed layer at their
    published widths (128 slots, 8448 pages of 128, rows of 96 pages)
    through `build_programs`, the kernel families steered on as they are
    on the chip: a decode step of two latent writes, two paged latent
    attentions and the grouped experts with no pool-shaped copy and no
    weight re-laid, alone and in the chunk of four that serves, and a
    4,096-token prefill whose attention goes through the `mla_prefill`
    kernel and whose experts take their rows sorted: no
    (128, 4096, 4096) array, under 1.5 GB of temporaries. And
    Ling-3.0-flash's pair of layers (a delta-rule layer, then latent
    attention at 32 heads with full-rank queries and the head gate):
    the chunk re-lays no weight either."""
    import importlib

    import chip_smoke

    fam = importlib.import_module(f"perfbench.families.{family}")
    described, kinds, pool, calls = {
        "deepseek_v2": ((chip_smoke.LATENT_H128,
                         lambda sz: (fam.DENSE_LEAVES, fam.MOE_LEAVES),
                         8448, 12288),
                        ["latent", "latent"], "bf16[8449,576,128]", 5),
        "ling_flash": ((chip_smoke.LATENT_KDA,
                        lambda sz: [fam.layer_leaves(sz, i)
                                    for i in range(sz["L"])],
                        2560, 4096, ("rb",)),
                       ["recurrent", "latent"], "bf16[2561,576,128]", 4),
    }[family]
    plan, programs, held, decode_args, prefill_args = _latent_programs(
        one_chip, monkeypatch, fam, *described)
    assert plan.state_kinds() == kinds
    assert plan.latent_geometry() == [(512, 64)] * kinds.count("latent")
    with jax.enable_x64(False):
        step = getattr(programs, program).lower(
            *held, *decode_args).compile().as_text()
    assert step.count("tpu_custom_call") == calls
    for name in ("mla_attend", "latent_write", "moe_experts"):
        assert name in step
    assert chip_smoke.pool_layout_copies(step, {pool}) == 0
    assert "moe_experts_sorted" not in step and "mla_prefill" not in step
    _assert_no_weight_is_relaid(step, held[0])
    if (family, program) != ("deepseek_v2", "decode_step"):
        return
    with jax.enable_x64(False):
        prefill = programs.prefill.lower(
            *held, *prefill_args(4096)).compile()
    text = prefill.as_text()
    assert "[128,4096,4096]" not in text
    for name in ("mla_prefill", "moe_experts_sorted"):
        assert name in text
    assert prefill.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("window,table,pool", [(4096, 33, 48 * 33),
                                               (None, 96, 3168)],
                         ids=("ring-of-33", "row-of-96"))
def test_paged_attention_compiles_at_128_heads_over_8(one_chip, window,
                                                      table, pool):
    """Command A+'s decode attention: 48 slots, 128 query heads over 8
    K/V heads of 128 (16 to a group), pages of 128; a window layer's
    walk over its ring of 33 entries and the full layer's over its row
    of 96: the pools left where they lie."""
    from deeplearning4j_tpu.ops.pallas_paged_attention import (
        paged_attention,
    )

    S = _shapes(one_chip)
    i32 = jnp.int32
    with jax.enable_x64(False):
        compiled = paged_attention.lower(
            S((48, 1, 128, 128)), S((pool + 1, 8, 128, 128)),
            S((pool + 1, 8, 128, 128)), S((48, table), i32), S((48,), i32),
            active=S((48,), jnp.bool_), window=window).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("window", (4096, None), ids=("window", "full"))
def test_flash_forward_reads_grouped_heads_at_4096(one_chip, window):
    """A 4,096-token prompt's attention at 128 query heads over 8 K/V
    heads: one kernel, the K/V slabs read by group (no array of the
    repeated keys' size, 134 MB, and none of the scores', 8.6 GB)."""
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    S = _shapes(one_chip)
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                window=window)).lower(
            S((1, 4096, 128, 128)), S((1, 4096, 8, 128)),
            S((1, 4096, 8, 128))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "[128,4096,4096]" not in text
    # the transposes into and out of slabs: q and o, 134 MB each, K and V
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 135e6


def _window_programs(one_chip, monkeypatch, layers: int):
    """Command A+'s net at the cell's widths and engine settings (48
    slots, pages of 128, 3,168 pages, rows of 96, a ring of 33),
    described and not drawn, its kernel families steered on as they are
    on the chip."""
    import json
    from pathlib import Path
    from types import SimpleNamespace

    from deeplearning4j_tpu.models.transformer import GPTPlan
    from deeplearning4j_tpu.ops import (
        pallas_attention, pallas_moe_experts, pallas_paged_attention,
        pallas_paged_kv_write,
    )
    from deeplearning4j_tpu.serving import block_state, decode_programs
    from perfbench.families import cohere2_moe as fam

    for mod in (pallas_attention, pallas_moe_experts, pallas_paged_attention,
                pallas_paged_kv_write):
        monkeypatch.setattr(mod, "_platform_supported", lambda: True)
        monkeypatch.setattr(mod, "_probe_verdict", lambda *a, **k: True)
        monkeypatch.setattr(mod, "_vmem_limit", lambda: 112 << 20)
    cfg = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                      / "configs" / "command-a-plus-05-2026.json")
                     .read_text())
    # a period of two, so that two layers hold one of each kind
    cfg.update(num_hidden_layers=layers, layer_switch=2,
               layer_types=["sliding_attention", "full_attention"][:layers])
    sz = fam.sizes(cfg)
    S = _shapes(one_chip)
    shapes = fam._leaf_shapes(sz)
    tree = {n: S(shapes[n]) for n in fam.TOP_LEAVES}
    tree["layers"] = [
        {n: S(shapes[n], jnp.float32 if n == "rb" else jnp.bfloat16)
         for n in fam.LAYER_LEAVES} for _ in range(layers)]
    net = fam.build_net(sz, training=False)
    net._params = [{k: S(v.shape, v.dtype) for k, v in p.items()}
                   for p in fam.to_program(tree)]
    plan = GPTPlan(net)
    n_slots, page, pool_pages, max_len = 48, 128, 3168, 12288
    ring = block_state.ring_pages(plan, page, 128)
    states = block_state.block_states(plan, SimpleNamespace(
        n_slots=n_slots, page=page, pool_pages=pool_pages, cdt=plan.cdt,
        kv_quant=None, tp_shard=None, tp_axis=None, ring_pages=ring))
    i32, f32 = jnp.int32, jnp.float32
    with jax.enable_x64(False):
        programs = decode_programs.build_programs(
            plan, states, n_slots=n_slots, page=page, L_logical=max_len,
            decode_chunk=4, top_k=0, logprobs=0, tp=None, donate=True,
            ring_pages=ring)
        caches = [jax.tree.map(lambda a: S(a.shape, a.dtype),
                               jax.eval_shape(st.alloc)) for st in states]
    slot_args = (S((n_slots,), i32), S((n_slots,), i32),
                 S((n_slots, 2), jnp.uint32), S((n_slots,), f32))
    decode_args = ((S((n_slots, max_len // page), i32),
                    S((n_slots, ring), i32)), *slot_args,
                   S((n_slots,), jnp.bool_))
    prefill_args = lambda T: (
        S((1, T), i32), S((), i32), S((), i32),
        (S((T // page,), i32), S((T // page,), i32)), *slot_args,
        S((2,), jnp.uint32), S((2,), jnp.uint32), S((), f32))
    return plan, ring, programs, (net._params, caches), decode_args, \
        prefill_args


@pytest.mark.parametrize("program", ("decode_step", "decode_chunked"))
def test_window_net_decode_step_compiles_at_the_published_widths(
        one_chip, monkeypatch, program):
    """A window layer and a full layer of Command A+ at their published
    widths through `build_programs`, two classes of page: two in-place
    K/V writes, the windowed and the whole-context paged attention and
    two grouped expert products tiled over `f` in one step, no pool of
    either class copied and no weight re-laid, in the step alone and in
    the chunk of four that serves."""
    import chip_smoke

    plan, ring, programs, held, decode_args, _ = _window_programs(
        one_chip, monkeypatch, 2)
    assert plan.state_kinds() == ["window", "kv"] and ring == 33
    with jax.enable_x64(False):
        text = getattr(programs, program).lower(
            *held, *decode_args).compile().as_text()
    assert text.count("tpu_custom_call") == 6
    for name in ("paged_attention", "paged_kv_write", "moe_experts"):
        assert name in text
    pools = {f"bf16[{48 * 33 + 1},8,128,128]", "bf16[3169,8,128,128]"}
    assert chip_smoke.pool_layout_copies(text, pools) == 0
    _assert_no_weight_is_relaid(text, held[0])


def test_window_net_prefill_at_4096_makes_no_array_of_scores(one_chip,
                                                             monkeypatch):
    """A 4,096-token prompt through both layers: the flash forward with
    a window and without, grouped, the experts' rows sorted; no (128,
    4096, 4096) array, temporaries under 1.5 GB."""
    plan, ring, programs, held, _, prefill_args = _window_programs(
        one_chip, monkeypatch, 2)
    with jax.enable_x64(False):
        prefill = programs.prefill.lower(
            *held, *prefill_args(4096)).compile()
    text = prefill.as_text()
    assert "[128,4096,4096]" not in text
    assert text.count("tpu_custom_call") == 4
    assert "moe_experts_sorted" in text
    assert prefill.memory_analysis().temp_size_in_bytes < 1.5e9


# The three programs' serialized executables, summed, as the tree BEFORE
# PR 51 compiled them here (the routers' choices by `lax.top_k` and a
# scatter), and the largest prompt bucket each cell's traffic warms.
ROUTED_NETS = {
    "granite": (512, 38_594_496),
    "ling": (1024, 70_334_293),
    "longcat": (1024, 63_088_586),
}


@pytest.mark.parametrize("net", sorted(ROUTED_NETS))
def test_routed_net_chooses_without_a_sort_and_loads_no_more(
        one_chip, monkeypatch, net):
    """A granite-, a Ling- and a LongCat-shaped routed net at the
    published widths: the compiled `decode_step`, `decode_chunked` and
    largest prefill bucket hold no `sort` under `moe.route` /
    `moe.groups` (`parallel.experts.chosen_mask` chooses by rank or by
    rounds, by the shape), and the three serialized executables
    together, what a warm start reads and loads, are no larger than the
    parent's were. A form of the choice that bloats the programs fails
    here and not at the driver (PERF.md section 7, what PR 50 taught)."""
    import chip_smoke

    import json
    from pathlib import Path

    from perfbench.families import granite_hybrid, ling_flash, longcat_flash

    bucket, parent_bytes = ROUTED_NETS[net]
    if net == "granite":
        # a Mamba-2 layer and an attention layer, 36 of the 72 experts
        # held, 64 slots and 576 pages as the cell has them
        cfg = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                          / "configs/granite-4.0-h-small.json").read_text())
        cfg.update(num_hidden_layers=2, layer_types=["mamba", "attention"],
                   vocab_size=256)
        fam, described = granite_hybrid, (
            cfg, lambda sz: [granite_hybrid.MIXER_LEAVES[kind]
                             + granite_hybrid.FFN_LEAVES
                             for kind in sz["layer_types"]], 576, 2048, (), 64)
    elif net == "ling":
        fam, described = ling_flash, (
            chip_smoke.LATENT_KDA,
            lambda sz: [ling_flash.layer_leaves(sz, i)
                        for i in range(sz["L"])], 2560, 4096, ("rb",))
    else:
        fam, described = longcat_flash, (
            chip_smoke.LATENT, lambda sz: [longcat_flash.LAYER_LEAVES],
            2560, 4096, longcat_flash.FLOAT32_LEAVES)
    _, programs, held, decode_args, prefill_args = _latent_programs(
        one_chip, monkeypatch, fam, *described)
    total = 0
    with jax.enable_x64(False):
        for program, args in ((programs.decode_step, decode_args),
                              (programs.decode_chunked, decode_args),
                              (programs.prefill, prefill_args(bucket))):
            compiled = program.lower(*held, *args).compile()
            text = compiled.as_text()
            assert "moe.route" in text
            assert chip_smoke.route_sorts(text) == 0
            total += len(compiled.runtime_executable().serialize())
    assert total <= parent_bytes, (net, total)
