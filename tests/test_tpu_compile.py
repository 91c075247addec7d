"""Kernels of the serving path compiled at real widths for a TPU v5e that
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
    # and Mosaic takes 32-bit block indices only
    with jax.enable_x64(False):
        compiled = moe_experts.lower(
            S((rows, 4096)), S((rows, 36), jnp.float32),
            S((36, 4096, 768)), S((36, 4096, 768)),
            S((36, 768, 4096))).compile()
    assert "tpu_custom_call" in compiled.as_text()
