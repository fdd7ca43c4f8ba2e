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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

HBM_BYTES = 16e9          # one v5e chip
FLAGSHIP_BATCH = 4096     # the v5e sweep batch, chip_smoke.py's one-chip sweep
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
    from madsim_tpu.models.workloads import flagship_raft
    # "auto" would resolve against this process's CPU backend and compile
    # the scatter lowering; the chip runs the one-hot form
    return flagship_raft(emission_write="onehot")


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


_TYPED = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s+=\s+(\w+)\[([\d,]*)\]"
                    r"(\{[^}\s]*\})?\s+([\w-]+)\(([^)]*)\)")


def _instructions(hlo: str) -> dict:
    """{name: (dtype, dims, layout, opcode, operand names, line)} for
    every array-valued instruction of a compiled module's text."""
    out = {}
    for line in hlo.splitlines():
        m = _TYPED.match(line)
        if m is not None:
            name, dtype, dims, layout, opcode, args = m.groups()
            dims = tuple(int(d) for d in dims.split(",") if d)
            operands = [a.strip() for a in args.split(",")
                        if a.strip().startswith("%")]
            out[name] = (dtype, dims, layout or "", opcode, operands, line)
    return out


def test_emission_write_has_no_convolution(sharded_runner, flagship):
    """The one-hot lowering writes the event table with selects. An s32
    one-hot product there lowers to a convolution, which pins the payload
    table with the event rows minor and turns the pick's payload read into
    a reduce across lanes: no s32 convolution in `step.emit`, and no
    `s32[..., C, payload_words]` value with the event rows minor."""
    from madsim_tpu.obs.scopes import op_scopes
    hlo = sharded_runner.as_text()
    scopes = op_scopes(hlo)
    instrs = _instructions(hlo)
    C, P = flagship.cfg.event_capacity, flagship.cfg.payload_words
    s32_convs = [n for n, (dt, _, _, op, args, _) in instrs.items()
                 if op == "convolution" and scopes[n] == "step.emit"
                 and "s32" in [dt] + [instrs[a][0] for a in args]]
    assert s32_convs == []
    c_minor = [n for n, (dt, dims, layout, _, _, _) in instrs.items()
               if dt == "s32" and dims[-2:] == (C, P)
               and layout.startswith("{1,2,0")]
    assert c_minor == []


def test_prefix_count_is_a_bf16_matmul(sharded_runner, flagship):
    """The pick's tie-break and the emit's free-slot rank count a prefix
    over the C event rows (`ops/select.prefix_count`). A cumsum there
    lowers to an O(C²) reduce-window; the product of a 0/1 row with the
    [C, C] triangular ones constant runs on the MXU instead. The only
    convolutions in those phases are that product, and no reduce-window
    spans the event rows."""
    from madsim_tpu.obs.scopes import op_scopes
    hlo = sharded_runner.as_text()
    scopes = op_scopes(hlo)
    instrs = _instructions(hlo)
    C = flagship.cfg.event_capacity
    phases = ("step.pick", "step.emit")
    convs = {n: v for n, v in instrs.items()
             if v[3] == "convolution" and scopes[n] in phases}
    assert {scopes[n] for n in convs} == set(phases)
    tri = ("bf16", (C, C))
    for name, (dtype, dims, _, _, args, _) in convs.items():
        assert dtype == "f32" and dims[-1] == C, name
        kinds = [instrs[a][:2] for a in args]
        assert len(kinds) == 2 and tri in kinds, (name, kinds)
        kinds.remove(tri)
        (row_dtype, row_dims), = kinds
        assert row_dtype in ("bf16", "pred") and row_dims[-1] == C, kinds
    # the [C, C] operand is the one constant, moved but never computed
    square = [v for v in instrs.values() if v[:2] == tri]
    assert "constant" in {v[3] for v in square}
    assert {v[3] for v in square} <= {
        "constant", "parameter", "get-tuple-element", "bitcast", "copy",
        "copy-done", "fusion"}
    assert all("calls=%bitcast_fusion" in v[5] for v in square
               if v[3] == "fusion")
    windows = [re.search(r"window=\{size=([\dx]+)", v[5]).group(1)
               for n, v in instrs.items()
               if v[3] == "reduce-window" and scopes[n] in phases]
    assert [w for w in windows if str(C) in w.split("x")] == []
