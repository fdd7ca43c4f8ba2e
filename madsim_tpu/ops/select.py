"""Vectorized selection primitives for the event engine.

These are the TPU-native equivalents of madsim's two scheduler data
structures: the random-pop ready queue (madsim/src/sim/utils/mpsc.rs:75-85 —
`try_recv_random` picks a uniformly random element with the global RNG) and
the binary-heap timer (madsim/src/sim/time/mod.rs:41-56 — pop earliest
deadline). Both become masked reductions over the fixed-shape event table:
argmin for the next deadline, a masked categorical draw for the tie-break.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def prefix_count(mask):
    """`jnp.cumsum(mask.astype(int32))` over the last axis of a bool mask.

    On TPU a cumsum lowers to a reduce-window as wide as the axis: O(C²)
    adds on the VPU. For C > 32 the count is instead a 0/1 bf16 row times
    a [C, C] upper-triangular ones matrix, accumulated in f32 on the MXU;
    under vmap that is one [B, C] x [C, C] product. Every product is 0 or
    1 and every sum an integer below 2**24, so the result is exact and
    equal to the cumsum bit for bit. The form is chosen by the static
    shape alone: masks of 32 or fewer slots (the threshold JAX itself uses
    between its two cumsum forms on GPU) keep the cumsum, where a matmul's
    fixed cost exceeds the O(C²) work.
    """
    c = mask.shape[-1]
    if c <= 32:
        return jnp.cumsum(mask.astype(jnp.int32), axis=-1)
    tri = np.triu(np.ones((c, c), jnp.bfloat16))
    return jnp.dot(mask.astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def masked_choice(key, mask):
    """Pick a uniformly random index among True entries of `mask`.

    Returns (idx:int32, valid:bool). idx is 0 when no entry is set (callers
    must gate on `valid`). Deterministic given `key` — this is the replayable
    analog of mpsc.rs:75 `try_recv_random`. The draw r is matched against
    the mask's prefix count (`prefix_count`): the chosen index is the first
    whose count reaches r + 1.
    """
    cnt = mask.astype(jnp.int32).sum()
    r = jax.random.randint(key, (), 0, jnp.maximum(cnt, 1), dtype=jnp.int32)
    cum = prefix_count(mask)
    idx = jnp.argmax(cum == r + 1).astype(jnp.int32)
    return idx, cnt > 0


def min_deadline(deadlines, eligible, inf):
    """Earliest eligible deadline and its tie mask.

    Returns (dmin:int32, at_min:bool[T], any_eligible:bool).
    """
    masked = jnp.where(eligible, deadlines, inf)
    dmin = masked.min()
    any_eligible = dmin < inf
    at_min = eligible & (deadlines == dmin)
    return dmin, at_min, any_eligible


def take1(vec, idx):
    """`vec[idx]` for a 1-D `vec` and any-shape integer `idx`, via a one-hot
    masked sum. On TPU, a gather whose index operand has many elements costs
    ~10ns PER ELEMENT (measured: the [N,N,L] invariant gather was 78% of the
    whole Raft step); the one-hot compare+select+reduce stays on the VPU and
    is bandwidth-trivial for the small tables this engine uses. Out-of-range
    indices must be pre-clipped (they select nothing and return 0).
    """
    n = vec.shape[0]
    oh = idx[..., None] == jnp.arange(n, dtype=jnp.int32)
    if vec.dtype == jnp.bool_:
        return (oh & vec).any(-1)
    return jnp.where(oh, vec, jnp.zeros((), vec.dtype)).sum(-1)


def row_onehot(n, idx):
    """bool[n] with True at `idx` (the building block of take_row/put_row;
    use it directly when composing custom one-hot updates so the TPU
    gather-avoidance semantics live in one place)."""
    return jnp.arange(n, dtype=jnp.int32) == idx


def take_row(mat, idx):
    """`mat[idx]` for mat[R, ...] and a SCALAR traced idx, via one-hot
    (same TPU rationale as take1; under vmap the scalar is per-lane)."""
    oh = row_onehot(mat.shape[0], idx).reshape(
        (mat.shape[0],) + (1,) * (mat.ndim - 1))
    if mat.dtype == jnp.bool_:
        return (oh & mat).any(0)
    return jnp.where(oh, mat, jnp.zeros((), mat.dtype)).sum(0)


def put_row(mat, idx, val, mask=True):
    """`mat.at[idx].set(val)` where `mask` holds, via one-hot select.
    `val` broadcasts against one row; out-of-range idx writes nothing."""
    oh = row_onehot(mat.shape[0], idx).reshape(
        (mat.shape[0],) + (1,) * (mat.ndim - 1))
    return jnp.where(oh & mask, val, mat)


def first_k_free(free_mask, k: int, scatter: bool = False):
    """Indices of the first k free slots (stable by index).

    Returns (slots:int32[k], ok:bool[k]) where ok[j] is False when fewer than
    j+1 slots are free; not-ok rows return slot 0 (callers gate on ok).

    Both lowerings rank the free slots by their prefix count
    (`prefix_count`) and give identical results (the emission_write knob,
    types.py): the default rank-match is O(kC) compares — cheap on the
    TPU VPU, but the k*C product is quadratic in cluster width when k ~ n
    and C = 16n (DESIGN §5 width tax); `scatter=True` writes each free
    slot's index into its rank row instead — one O(C) scatter, the
    CPU-friendly form.
    """
    pos = prefix_count(free_mask)
    if scatter:
        C = free_mask.shape[0]
        rank = pos - 1
        dst = jnp.where(free_mask & (rank < k), rank, k)   # k = dropped
        slots = jnp.zeros((k,), jnp.int32).at[dst].set(
            jnp.arange(C, dtype=jnp.int32), mode="drop")
    else:
        targets = jnp.arange(1, k + 1, dtype=jnp.int32)
        eq = (pos[None, :] == targets[:, None]) & free_mask[None, :]
        slots = jnp.argmax(eq, axis=1).astype(jnp.int32)
    ok = jnp.arange(1, k + 1, dtype=jnp.int32) \
        <= (pos[-1] if pos.shape[0] else 0)
    return slots, ok
