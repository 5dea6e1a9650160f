"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts — block shapes off the
(8, 128) tiling, more fast memory than a kernel may hold — so the advance
kernel is compiled here for v5e at every shape the engine and the
benchmarks bring, and ``jit(simulate)`` at the paper's Figure 9/10 size.
Nothing runs: a passing compile says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a worker that cannot must
skip these tests rather than collect a different set.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import scenarios, simulate
from repro.kernels import ops
from repro.kernels.vm_update import advance_sweep_pallas, kernel_plan

pytestmark = pytest.mark.tier1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-device compile can be written to the persistent cache
    but not read back; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (B, C): fused B=1 at Figure 9/10's 500 tasks; the B=256 batch-major
# benchmark stack; a two-phase row past the tile cap; one 8192-row campaign
# chunk of the 8-task Figure 4 grid (its [B] dt vector in SMEM)
SHAPES = [(1, 500), (256, 8), (32, 300_000), (8192, 8)]


@pytest.mark.parametrize("b,c", SHAPES)
def test_advance_kernel_compiles_for_v5e(one_chip, b, c):
    block = ops.advance_block(c)
    row = (c,) if b == 1 else (b, c)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape(row), shape(row), shape(row, jnp.bool_), shape(row[:-1]))
    compiled = advance_sweep_pallas.lower(
        *args, block=block, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    plan = kernel_plan(b, c, block)
    assert plan["variant"] == ("two_phase" if c > ops._MAX_BLOCK else "fused")


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_fig9_10_simulate_compiles_for_v5e(one_chip, monkeypatch, impl):
    # this host's backend is the CPU, so the kernel router would pick
    # interpret mode; the described chip compiles Mosaic
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    scn = scenarios.fig9_10_scenario(0).replace(sweep_impl=impl)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        scn,
    )
    compiled = jax.jit(simulate).lower(shapes).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (impl == "pallas")
