"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

Sharding/parallelism tests run against a virtual 8-device CPU topology
(`xla_force_host_platform_device_count=8`) — the reference's analogous trick
is running Spark tests with `setMaster("local[N]")` in-JVM
(`BaseSparkTest.java:89-90`): validate the distributed path without a
cluster. fp64 is enabled for gradient checks (reference forces DOUBLE in
`GradientCheckTests.java:46-48`).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", True)

# The suite compiles the same HLO over and over — every test that builds
# its own engine/net at the same shapes repays an identical XLA compile —
# and so does the next run. Keyed by HLO hash, so a hit can never change
# numerics. Subprocess drills spawn their own interpreters; the replica
# child follows the same rule (`util/compile_cache`).
from deeplearning4j_tpu.util.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


@pytest.fixture(scope="session")
def tp_mesh2():
    """The serving tensor-parallel tp=2 mesh over the forced host
    devices, built ONCE per session: `serving.tp_engine.tp_mesh` caches
    per process, so every `tp`-marked test (and any engine built with
    parallel={"tp": 2}) shares one mesh instead of re-paying mesh
    construction + XLA device queries per test — the tier-1 wall-time
    bound for the TP matrix."""
    from deeplearning4j_tpu.serving.tp_engine import tp_mesh

    return tp_mesh(2)


@pytest.fixture(autouse=True)
def _reap_replica_orphans():
    """Orphan-process hygiene for `multiprocess` drills: any replica
    subprocess a test (or its crashed supervisor) left behind is
    SIGKILLed after the test, so one failing chaos drill cannot leak
    interpreter processes into the rest of the tier-1 run. Free when
    the remote-replica module was never imported."""
    yield
    import sys

    mod = sys.modules.get("deeplearning4j_tpu.serving.remote_replica")
    if mod is not None:
        mod.reap_orphans()
