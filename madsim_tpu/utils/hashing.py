"""Per-trajectory state fingerprints.

madsim's nondeterminism detector logs `hash(rng_byte ^ time)` at every RNG
draw and compares across two same-seed runs (rand.rs:72-96,
runtime/mod.rs:144-187). Because our whole cluster state is one pytree of
tensors, the equivalent check is cheaper and stronger: fold every state leaf
into a 32-bit fingerprint per trajectory and compare across replays — any
divergence anywhere in the state is caught, not just RNG draw order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# NumPy scalars, not jnp: a jnp constant at import would initialize a JAX
# backend, and on a chip host the import alone would claim the chip
FNV_OFFSET = np.uint32(2166136261)
FNV_PRIME = np.uint32(16777619)


def _leaf_words(a: jax.Array) -> jax.Array:
    """View a leaf as a flat uint32 vector (value-stable encoding)."""
    if a.dtype == jnp.float32:
        w = lax.bitcast_convert_type(a, jnp.uint32)
    elif a.dtype in (jnp.uint32,):
        w = a
    else:
        w = a.astype(jnp.int32).astype(jnp.uint32)
    return w.reshape(-1)


from ..core.state import TRACE_FIELDS

# The recorder is an observation lever, not a replay domain: two lanes
# running identical trajectories must fingerprint equal whether or not
# one of them was sampled into the ring — otherwise partial
# `trace_lanes` sampling would split every trajectory class in
# `summarize()['distinct_outcomes']` and a sampled sweep's fingerprints
# would never match a replay's.
_OBSERVATION_FIELDS = frozenset(TRACE_FIELDS)


def fingerprint(state) -> jax.Array:
    """uint32 fingerprint of one trajectory's full state pytree —
    excluding the flight-recorder (observation-only) fields.

    vmap this for a batched state. Deterministic given identical values and
    identical pytree structure/shapes.
    """
    if hasattr(state, "trace_pos"):     # SimState: drop the recorder
        state = {k: getattr(state, k)
                 for k in type(state).__dataclass_fields__
                 if k not in _OBSERVATION_FIELDS}
    leaves = jax.tree.leaves(state)
    h = FNV_OFFSET
    for i, leaf in enumerate(leaves):
        w = _leaf_words(jnp.asarray(leaf))
        mix = jnp.arange(w.shape[0], dtype=jnp.uint32) * jnp.uint32(
            2654435761) + jnp.uint32(2 * i + 1)
        lh = jnp.sum(w * mix, dtype=jnp.uint32) if w.shape[0] else jnp.uint32(0)
        h = (h ^ lh) * FNV_PRIME
    return h


# ONE process-level jitted batched fingerprint, shared by every Runtime
# and by find_divergence: jax.jit caches by FUNCTION IDENTITY first, so
# the old per-call `jax.jit(jax.vmap(fingerprint))` retraced on every
# invocation — a compile per fingerprints() call. A module-level jit
# retraces only per state structure/shape (which is the granularity
# executables genuinely differ at).
_BATCH_FP = jax.jit(jax.vmap(fingerprint))


def batch_fingerprints(state) -> jax.Array:
    """uint32[B] fingerprints of a batched state (device array; callers
    np.asarray it). Shared compiled entry across all Runtimes."""
    return _BATCH_FP(state)
