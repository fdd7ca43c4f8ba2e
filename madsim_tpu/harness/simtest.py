"""Chaos-test harness: the `#[madsim::test]` analog.

The reference macro expands every test into a seed loop driven by env vars —
MADSIM_TEST_SEED, MADSIM_TEST_NUM, MADSIM_TEST_TIME_LIMIT,
MADSIM_TEST_CHECK_DETERMINISM — and prints a `MADSIM_TEST_SEED={seed}` repro
line plus a config hash on failure (madsim-macros/src/lib.rs:120-206). Here
the seed loop IS the batch axis: MADSIM_TEST_NUM seeds run as one vmapped
program, and the repro line points at the first crashed trajectory, which can
then be replayed alone with `Runtime.run_single` for a full event trace.
"""

from __future__ import annotations

import functools
import hashlib
import os
from typing import Callable

import numpy as np

from ..core import types as T
from ..runtime.runtime import Runtime

_CODE_NAMES = {
    T.CRASH_DEADLOCK: "DEADLOCK (no runnable event — 'task will block forever')",
    T.CRASH_TIME_LIMIT: "TIME_LIMIT exceeded",
    T.CRASH_INVARIANT: "INVARIANT violated",
}


class SimFailure(AssertionError):
    def __init__(self, seed, code, node, cfg_hash, msg=""):
        self.seed, self.code, self.node = int(seed), int(code), int(node)
        name = _CODE_NAMES.get(self.code, f"user crash code {self.code}")
        super().__init__(
            f"simulation failed: {name} at node {self.node}. {msg}\n"
            f"reproduce with: MADSIM_TEST_SEED={self.seed} "
            f"(MADSIM_CONFIG_HASH={cfg_hash})")


class DetSanFailure(AssertionError):
    """The determinism sanitizer (detsan=True) found a seed whose final
    state depends on WHICH LANE it ran in — a violation of the lane-
    independence half of DESIGN §4 (seed i in any batch == seed i
    alone). The lint pass (analyze/lint.py) catches the static causes;
    this is the net for everything it can't see."""

    def __init__(self, diffs: list, seeds, cfg_hash: str):
        self.diffs = diffs
        first = diffs[0]
        lane = first["lanes"][0] if first["lanes"] else 0
        seeds = np.asarray(seeds).reshape(-1)
        self.seed = int(seeds[lane])
        leaves = ", ".join(d["leaf"] for d in diffs[:8])
        # unlike SimFailure, a single-seed repro line would be a lie
        # here: the finding is that the seed's trajectory depended on
        # its LANE PLACEMENT, so only re-creating the exact batch
        # (base + count, the @simtest seed layout) reproduces it
        super().__init__(
            f"determinism sanitizer: {len(diffs)} state leaf(s) differ "
            f"between identity and permuted lane placement.\n"
            f"  first: leaf {first['leaf']}, {first['n_lanes']} lane(s), "
            f"first lane {lane} (seed {self.seed})\n"
            f"  differing leaves: {leaves}\n"
            f"reproduce the exact batch with: "
            f"MADSIM_TEST_SEED={int(seeds[0])} "
            f"MADSIM_TEST_NUM={len(seeds)} MADSIM_TEST_DETSAN=1 "
            f"(MADSIM_CONFIG_HASH={cfg_hash}; the differing seed alone "
            f"may pass — lane placement is the variable under test)")


def _env_int(name, default):
    v = os.environ.get(name)
    return int(v) if v else default


def apply_net_override(state, net, cfg=None):
    """Apply a NetConfig onto a (batched) state's DYNAMIC network knobs —
    loss and latency live in state, so MADSIM_TEST_CONFIG can reshape the
    fault model without recompiling (the TOML-injection contract of
    macros lib.rs:146-151).

    op_jitter_max's BOUND is dynamic too, but the jitter fold itself is
    compiled in only when the build's SimConfig enabled it (step.py §4:
    a jitterless build pays zero draws) — pass `cfg` to catch the
    silent no-op of overriding jitter onto a jitterless build."""
    import jax.numpy as jnp
    if net is None:
        return state
    if cfg is not None and net.op_jitter_max > 0 \
            and cfg.net.op_jitter_max == 0:
        raise ValueError(
            "op_jitter_max override needs a build with jitter enabled: "
            "construct SimConfig(net=NetConfig(op_jitter_max>0)) — the "
            "fold is static (step.py §4); only its bound is dynamic")
    return state.replace(
        loss=jnp.full_like(state.loss, net.packet_loss_rate),
        lat_lo=jnp.full_like(state.lat_lo, net.send_latency_min),
        lat_hi=jnp.full_like(state.lat_hi, net.send_latency_max),
        jitter=jnp.full_like(state.jitter, net.op_jitter_max))


def env_net_override():
    """NetConfig from the MADSIM_TEST_CONFIG env var (a TOML file path),
    or None."""
    path = os.environ.get("MADSIM_TEST_CONFIG")
    if not path:
        return None
    with open(path) as f:
        return T.NetConfig.from_toml(f.read())


def effective_config_hash(rt: Runtime, net_override=None,
                          time_limit_override=None) -> str:
    """Repro hash covering the static config and any runtime overrides —
    the printed hash must identify the config that actually ran
    (the config.rs:27-31 contract)."""
    h = rt.cfg.hash()
    if net_override is None and not time_limit_override:
        return h
    blob = f"{h}|{net_override}|{time_limit_override}".encode()
    return hashlib.sha256(blob).hexdigest()[:8]


def detsan_perm(B: int) -> np.ndarray:
    """The sanitizer's deterministic lane permutation: a Knuth-hash
    shuffle (a real permutation for any B), falling back to reversal if
    the hash order happens to be the identity — for B > 1 the permuted
    run always places at least one seed in a different lane."""
    keys = (np.arange(B, dtype=np.uint64) * np.uint64(2654435761)
            + np.uint64(0x9E3779B9)) & np.uint64(0xFFFFFFFF)
    perm = np.argsort(keys, kind="stable").astype(np.int64)
    if B > 1 and bool((perm == np.arange(B)).all()):
        perm = np.arange(B - 1, -1, -1, dtype=np.int64)
    return perm


def diff_states(a, b, align=None) -> list[dict]:
    """Leaf-for-leaf diff of two batched states (the detsan comparator).
    `align` re-indexes `b`'s batch axis first (the inverse of the lane
    permutation, so lane i compares against the lane that ran seed i).
    Returns one dict per differing leaf: {leaf, n_lanes, lanes} with
    `lanes` the first few differing lane indices. NaN == NaN (a NaN
    that reproduces as the same NaN is deterministic)."""
    import jax
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    diffs: list[dict] = []
    for (path, xa), (_, xb) in zip(la, lb):
        va, vb = np.asarray(xa), np.asarray(xb)
        if align is not None:
            vb = vb[np.asarray(align)]
        if va.size == 0:
            continue
        neq = va != vb
        if va.dtype.kind == "f":
            neq &= ~(np.isnan(va) & np.isnan(vb))
        if not neq.any():
            continue
        lanes = np.nonzero(neq.reshape(neq.shape[0], -1).any(axis=1))[0]
        diffs.append(dict(leaf=jax.tree_util.keystr(path),
                          n_lanes=int(len(lanes)),
                          lanes=lanes[:8].tolist()))
    return diffs


def detsan_check(rt: Runtime, seeds, max_steps: int, chunk: int = 512, *,
                 net_override=None, time_limit_override=None,
                 fused: bool = True, perm=None, baseline_state=None,
                 raise_on_diff: bool = True) -> dict:
    """The determinism sanitizer: run the seed batch twice — once in
    given order, once under a permuted LANE PLACEMENT — un-permute, and
    diff the final states leaf-for-leaf. Lane independence (DESIGN §4:
    seed i in any batch == seed i alone) makes the two runs bitwise
    equal for any program inside the determinism discipline; whatever
    the static lint pass cannot see (a host value baked per-trace, a
    cross-lane leak through an extension, a placement-sensitive
    collective) shows up here as a named leaf + lane + seed.

    Both runs use the same runner (`fused` selects which) and the same
    executable, so the sanitizer's cost is one extra sweep plus a host
    diff (≤2x one sweep). When
    `baseline_state` is given (run_seeds already ran the batch), only
    the permuted sweep is paid. With no baseline both sweeps are
    DISPATCHED before either is forced: JAX async dispatch overlaps
    them where the backend allows.

    Returns {ok, batch, leaves, diffs, perm}; raises `DetSanFailure`
    on a diff unless `raise_on_diff=False`."""
    import jax
    seeds = np.asarray(seeds, np.uint32).reshape(-1)
    B = seeds.shape[0]
    perm = detsan_perm(B) if perm is None else np.asarray(perm, np.int64)
    if sorted(perm.tolist()) != list(range(B)):
        raise ValueError(f"perm is not a permutation of range({B})")

    def _run(sds):
        init = apply_net_override(rt.init_batch(sds), net_override,
                                  cfg=rt.cfg)
        if time_limit_override:
            init = rt.set_time_limit(init, time_limit_override)
        if fused:
            return rt.run_fused(init, max_steps, chunk)
        s, _ = rt.run(init, max_steps, chunk=chunk)
        return s

    if baseline_state is None:
        a = _run(seeds)
        b = _run(seeds[perm])
    else:
        a = baseline_state
        b = _run(seeds[perm])
    diffs = diff_states(a, b, align=np.argsort(perm))
    if diffs and raise_on_diff:
        raise DetSanFailure(diffs, seeds, effective_config_hash(
            rt, net_override, time_limit_override))
    return dict(ok=not diffs, batch=int(B),
                leaves=len(jax.tree_util.tree_leaves(a)),
                diffs=diffs, perm=perm.tolist())


def run_seeds(rt: Runtime, seeds, max_steps: int, chunk: int = 512,
              net_override=None, time_limit_override=None,
              detsan: bool = False):
    """Run a seed batch to completion; raise SimFailure on the first crashed
    seed (lowest index). Returns the final batched state.

    detsan=True (or MADSIM_TEST_DETSAN=1) additionally replays the batch
    under a permuted lane placement and diffs final states leaf-for-leaf
    (`detsan_check`) — DetSanFailure outranks SimFailure, because a
    crash report from a nondeterministic run is not a repro."""
    # cross-process compile tier: JAX_COMPILATION_CACHE_DIR, else the
    # checkout's .jax_cache, so cold harness processes reuse warm
    # executables
    from ..compile.persistent import enable_persistent_cache
    enable_persistent_cache()
    init = apply_net_override(rt.init_batch(np.asarray(seeds, np.uint32)),
                              net_override, cfg=rt.cfg)
    if time_limit_override:
        init = rt.set_time_limit(init, time_limit_override)
    cfg_hash = effective_config_hash(rt, net_override, time_limit_override)
    state, _ = rt.run(init, max_steps, chunk=chunk)
    if detsan or os.environ.get("MADSIM_TEST_DETSAN"):
        detsan_check(rt, seeds, max_steps, chunk,
                     net_override=net_override,
                     time_limit_override=time_limit_override,
                     fused=False, baseline_state=state)
    crashed = np.asarray(state.crashed)
    if crashed.any():
        i = int(np.argmax(crashed))
        msg = f"({int(crashed.sum())}/{len(seeds)} seeds crashed)"
        if os.environ.get("MADSIM_TEST_MINIMIZE"):
            # opt-in ddmin of the chaos script (one compiled run per
            # candidate row). Overrides aren't threaded into the
            # minimizer's replays, so under MADSIM_TEST_CONFIG the crash
            # may not reproduce — report that rather than fail the report
            try:
                from .minimize import minimize_scenario
                minimal, info = minimize_scenario(rt, int(seeds[i]),
                                                  max_steps, chunk)
                msg += (f"\nminimal chaos script ({info['kept']} of "
                        f"{info['kept'] + info['dropped']} rows, "
                        f"{info['runs']} runs):\n{minimal.describe()}")
            except Exception as e:  # noqa: BLE001 - repro line still stands
                msg += f"\n(minimization unavailable: {e})"
        raise SimFailure(
            seeds[i], np.asarray(state.crash_code)[i],
            np.asarray(state.crash_node)[i], cfg_hash, msg=msg)
    oops = np.asarray(state.oops)
    if (oops != 0).any():
        i = int(np.argmax(oops != 0))
        raise SimFailure(
            seeds[i], 0, -1, cfg_hash,
            msg=f"capacity overflow (oops bits {int(oops[i])}) — raise "
                f"event_capacity")
    return state


def simtest(num_seeds: int = 16, max_steps: int = 20_000,
            seed: int | None = None, check_determinism: bool = False,
            chunk: int = 512, detsan: bool = False):
    """Decorator: the wrapped function builds and returns a Runtime (or
    (Runtime, check_fn) where check_fn(final_state) does extra asserts).

    Env knobs (same contract as the reference macro,
    madsim-macros/src/lib.rs:120-206):
      MADSIM_TEST_SEED               base seed (default: stable per-test hash)
      MADSIM_TEST_NUM                number of seeds (the batch axis!)
      MADSIM_TEST_TIME_LIMIT         virtual-time limit in SECONDS (overrides
                                     cfg.time_limit without recompiling — the
                                     limit is dynamic state, lib.rs:157-159)
      MADSIM_TEST_CHECK_DETERMINISM  also run seed twice and compare state
      MADSIM_TEST_DETSAN             determinism sanitizer: replay the whole
                                     batch under permuted lane placement and
                                     diff leaf-for-leaf (detsan_check) —
                                     catches lane-placement dependence the
                                     same-lane replay check above cannot
    """

    def deco(fn: Callable):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if seed is not None:
                default_seed = seed
            else:  # stable across interpreter runs (hash() is randomized)
                digest = hashlib.sha256(fn.__qualname__.encode()).hexdigest()
                default_seed = int(digest[:8], 16) % (2**31)
            base = _env_int("MADSIM_TEST_SEED", default_seed)
            n = _env_int("MADSIM_TEST_NUM", num_seeds)
            limit_s = _env_int("MADSIM_TEST_TIME_LIMIT", 0)
            out = fn(*args, **kwargs)
            rt, check_fn = out if isinstance(out, tuple) else (out, None)
            seeds = np.arange(base, base + n, dtype=np.uint32)
            override = env_net_override()
            state = run_seeds(rt, seeds, max_steps, chunk,
                              net_override=override,
                              time_limit_override=(T.sec(limit_s)
                                                   if limit_s else None),
                              detsan=detsan)
            if check_fn is not None:
                check_fn(state)
            if check_determinism or os.environ.get(
                    "MADSIM_TEST_CHECK_DETERMINISM"):
                assert rt.check_determinism(base, max_steps,
                                            net_override=override), (
                    f"nondeterminism detected for seed {base} "
                    f"(MADSIM_CONFIG_HASH="
                    f"{effective_config_hash(rt, override)})")
            return state
        return wrapper
    return deco
