"""Chip-less compiles for TPU v5e: the main path's programs at real size.

The TPU compiler is installed next to the CPU backend, and it compiles for
a chip that is described, not attached. What it refuses here (a program
that does not fit, a sharding it cannot partition) would otherwise cost a
chip run to find. Nothing runs, so these say nothing about results or time;
`python chip_smoke.py` on the chip does.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

HBM_BYTES = 16e9          # one v5e chip
FLAGSHIP_BATCH = 4096     # bench.B_TPU, chip_smoke.py's one-chip sweep
SHARDED_BATCH = 16384     # chip_smoke.py --four-chips


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


@pytest.fixture(scope="module")
def flagship():
    import bench
    # "auto" would resolve against this process's CPU backend and compile
    # the scatter lowering; the chip runs the one-hot form
    return bench._make_runtime(emission_write="onehot")


def _shapes(rt, batch: int, sharding):
    """The batched state's shapes, placed on `sharding` (a described
    device holds no array, so the compile gets shapes, not values)."""
    state = rt.init_batch(np.arange(batch))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        state)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)


def test_flagship_step_fits_one_chip(topo, no_persistent_cache, flagship):
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = _shapes(flagship, FLAGSHIP_BATCH, one_chip)
    compiled = jax.jit(jax.vmap(flagship._step)).lower(shapes).compile()
    used = _device_bytes(compiled)
    assert 0 < used < HBM_BYTES / 8, used


@pytest.fixture(scope="module")
def sharded_runner(topo, no_persistent_cache, flagship):
    """The flagship's fused runner compiled over four chips (each holds
    FLAGSHIP_BATCH lanes, the one-chip sweep's shapes)."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("seeds",))
    shapes = _shapes(flagship, SHARDED_BATCH,
                     NamedSharding(mesh, P("seeds")))
    n_chunks = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=NamedSharding(mesh, P()))
    return flagship._fused_runner.lower(shapes, n_chunks, 512).compile()


def test_fused_runner_shards_over_four_chips(sharded_runner):
    compiled = sharded_runner
    used = _device_bytes(compiled)   # per device
    assert 0 < used < HBM_BYTES / 8, used
    hlo = compiled.as_text()
    # lanes never talk: the only collective is the halt test's all-reduce
    assert "all-gather" not in hlo and "all-to-all" not in hlo
    # the step's phase scopes survive the TPU compiler's passes as op
    # metadata, so a chip profile's ops map back to their phases
    from madsim_tpu.obs.scopes import op_scopes
    assert set(op_scopes(hlo).values()) == {
        "", "step.pick", "step.supervisor", "step.handler", "step.emit",
        "step.check"}


def test_emission_write_has_no_convolution(sharded_runner):
    """The one-hot lowering writes the event table with selects. An s32
    one-hot product there lowers to a convolution, which pins the payload
    table with the event rows minor and turns the pick's payload read into
    a reduce across lanes."""
    from madsim_tpu.obs.scopes import op_scopes
    hlo = sharded_runner.as_text()
    scopes = op_scopes(hlo)
    convs = [line.split("=", 1)[0].split()[-1] for line in hlo.splitlines()
             if " convolution(" in line]
    assert [c for c in convs if scopes[c] == "step.emit"] == []
