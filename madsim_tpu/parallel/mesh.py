"""Scale-out: shard the seed batch over a device mesh.

The reference scales schedule exploration by running more `cargo test`
processes (SURVEY.md §5 "long-context"); its real-mode comm backends are
TCP/UCX/eRPC (std/net/). The TPU-native equivalent (SURVEY.md §2.9):
trajectories are independent, so the seed batch is pure data parallelism —
shard it over ICI with `jax.sharding`, and the only cross-chip traffic is
reductions (all-halted tests, first-crash argmin, stat sums), which XLA
lowers to psum/all-reduce over the mesh. Multi-host scale-out uses the same
spec over a DCN-spanning mesh via `jax.distributed.initialize()`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEED_AXIS = "seeds"


def seed_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices, named 'seeds'."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (SEED_AXIS,))


def shard_batch(state, mesh: Mesh):
    """Place a batched SimState so the leading [seed_batch] axis is sharded
    across the mesh; all other dims replicated. jit calls then run SPMD with
    no per-step communication (trajectories never talk to each other)."""
    sharding = NamedSharding(mesh, P(SEED_AXIS))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), state)


def first_crash_seed(state, seeds) -> jax.Array:
    """Index of the lowest-index crashed trajectory, or -1. Under a sharded
    batch this is a cross-chip min-reduction riding ICI."""
    seeds = jnp.asarray(seeds)
    big = jnp.iinfo(jnp.int32).max
    lowest = jnp.min(jnp.where(state.crashed, jnp.arange(seeds.shape[0]),
                               big))
    return jnp.where(lowest == big, -1, lowest)
