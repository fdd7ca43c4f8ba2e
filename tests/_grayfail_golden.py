"""Shared harness for the r17 bit-identical-when-disabled contract.

The gray-failure fault plane (r17) added engine machinery — one-way
partition cuts, per-node clock skew, slow-disk emission delay, torn-write
kill flush — that is DYNAMIC: always compiled in, masked to identity at
the zero defaults. The contract is that a scenario using none of the new
ops produces trajectories BIT-IDENTICAL to r16, leaf for leaf, chunked
and fused.

"Identical to r16" is enforced against captured truth, not a slogan:
`scripts/capture_golden.py` ran these exact workloads AT r16 HEAD (before
any r17 engine change landed) and froze per-leaf sha256 digests into
`tests/data/golden_r16_leaves.json`; `tests/test_grayfail.py` re-runs
them on the current tree and compares digest-for-digest. Every r16 leaf
must still exist and hash identically — new r17 leaves are allowed (they
are exactly what the simconfig-v5 signature bump gates), but no r16 leaf
may move by a single bit.

Keep the builders here frozen: they define what the golden file means.
"""

from __future__ import annotations

import hashlib
import json
import os

import jax
import numpy as np

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "golden_r16_leaves.json")

# run parameters are part of the frozen definition
RUNS = dict(
    pingpong=dict(seeds=64, max_steps=4000, chunk=256),
    wal_kv=dict(seeds=32, max_steps=30_000, chunk=512),
)


def build_pingpong():
    """The saturating pingpong chaos workload (`workloads.saturating`'s
    regime), with the recorder compiled in so ring columns are covered
    too."""
    from madsim_tpu import NetConfig, Runtime, Scenario, SimConfig, ms, sec
    from madsim_tpu.models.pingpong import PingPong, state_spec
    sc = Scenario()
    sc.at(ms(40)).kill_random()
    sc.at(ms(400)).restart_random()
    cfg = SimConfig(n_nodes=4, time_limit=sec(5), trace_cap=64,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(1)))
    return Runtime(cfg, [PingPong(4, target=6)], state_spec(), scenario=sc)


def build_wal_kv():
    """The WAL-KV kill/restart chaos matrix (tests/test_fs.py's shape):
    stable storage, persist masks, recovery — the fs-layer workload."""
    from madsim_tpu import Scenario, ms
    from madsim_tpu.models.wal_kv import SERVER, make_wal_kv_runtime
    sc = Scenario()
    for t in range(4):
        sc.at(ms(250) + ms(400) * t).kill(SERVER)
        sc.at(ms(250) + ms(400) * t + ms(120)).restart(SERVER)
    return make_wal_kv_runtime(n_clients=2, n_ops=12, wal_cap=8,
                               sync_wal=True, scenario=sc)


BUILDERS = dict(pingpong=build_pingpong, wal_kv=build_wal_kv)


def leaf_digests(state) -> dict:
    """{leaf path: sha256(shape|dtype|bytes)} over a batched final state."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        a = np.asarray(leaf)
        h = hashlib.sha256()
        h.update(f"{a.shape}|{a.dtype}|".encode())
        h.update(np.ascontiguousarray(a).tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()
    return out


_RUN_CACHE: dict = {}


def run_workload(name: str) -> dict:
    """-> {"run": digests, "run_fused": digests} for one frozen workload.

    Memoized per process: the r17 (vs r16 truth) and r19 (vs r18 truth)
    equivalence suites compare the SAME current-tree digests against
    different captured goldens, so one pytest session pays for each
    workload exactly once."""
    if name in _RUN_CACHE:
        return _RUN_CACHE[name]
    p = RUNS[name]
    rt = BUILDERS[name]()
    seeds = np.arange(p["seeds"], dtype=np.uint32)
    chunked, _ = rt.run(rt.init_batch(seeds), p["max_steps"], p["chunk"])
    fused = rt.run_fused(rt.init_batch(seeds), p["max_steps"], p["chunk"])
    out = {"run": leaf_digests(chunked), "run_fused": leaf_digests(fused)}
    _RUN_CACHE[name] = out
    return out


def capture(path: str = GOLDEN_PATH) -> dict:
    """Freeze this tree's digests into `path`. Where `path` already holds
    a golden, only the leaves it names are re-hashed: a re-freeze (a new
    toolchain moving every digest) keeps the era's leaf set, so each
    test's list of leaves added since stays meaningful."""
    doc = {name: run_workload(name) for name in BUILDERS}
    if os.path.exists(path):
        old = load_golden(path)
        doc = {w: {r: {k: d[k] for k in old[w][r]} for r, d in runs.items()}
               for w, runs in doc.items()}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return doc


def load_golden(path: str = GOLDEN_PATH) -> dict:
    with open(path) as f:
        return json.load(f)
