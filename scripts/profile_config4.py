"""Ablation profile of the config-4 (KV-on-Raft) step — where does the 8x go?

BASELINE_rows_r04.jsonl: config 4 runs at ~7.2k seed-ev/s vs ~58k for
configs 2/3 — an ~8x per-event cost that BASELINE.md attributed in passing
to "per-event digest-chain invariant + apply loop" without evidence. This
script measures it: build the config-4 runtime with one cost component
removed at a time and compare steady-state step rates on JAX's default
device, which the output names (the ratios are what matter; the
reference's criterion benches play the same role, madsim/benches/rpc.rs).

Usage: python scripts/profile_config4.py [--batch 512] [--steps 512] [--out f]

Each variant compiles its own step program; rates are measured on a second
run() call so compile time is excluded. All lanes stay live for the whole
window (fresh states, no compaction), so rate = steps_fired / wall.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build(invariant="full", event_capacity=128, log_capacity=48,
          payload_words=12, apply_per_event=2, halt=True):
    """The config-4 runtime (baseline_configs.config4 shapes), with knobs."""
    import jax.numpy as jnp
    from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
    from madsim_tpu.core.types import SimConfig
    from madsim_tpu.runtime.runtime import Runtime
    from madsim_tpu.models import raft as R
    from madsim_tpu.models.raft_kv import (KV_FIELDS, KvClient, RaftKv,
                                           all_clients_done, kv_persist_spec,
                                           kv_state_spec)
    n_raft, n_clients, n_keys, n_ops = 5, 3, 3, 6
    n = n_raft + n_clients
    sc = Scenario()
    for t in range(3):
        sc.at(ms(700 + 900 * t)).kill_random(among=range(5))
        sc.at(ms(1200 + 900 * t)).restart_random(among=range(5))
    cfg = SimConfig(n_nodes=n, event_capacity=event_capacity,
                    payload_words=payload_words, time_limit=sec(8),
                    net=NetConfig(packet_loss_rate=0.05))
    peer_mask = np.asarray([True] * n_raft + [False] * n_clients)
    if invariant == "full":
        inv = R.raft_invariant(n, log_capacity, KV_FIELDS, peer_mask)
    elif invariant == "cheap":
        # leaders-per-term + commit<=len only: drops the digest-chain
        # prefix-agreement machinery (cumsum + [N,N,L+1] one-hot evaluate)
        eye = jnp.eye(n, dtype=bool)
        peer = jnp.asarray(peer_mask)

        def inv(state):
            ns = state.node_state
            leader = (ns["role"] == R.LEADER) & peer
            same_term = ns["term"][:, None] == ns["term"][None, :]
            two = (leader[:, None] & leader[None, :] & same_term & ~eye).any()
            ec = jnp.maximum(jnp.where(peer, ns["commit"], 0),
                             jnp.where(peer, ns["snap_len"], 0))
            gt = (ec > jnp.where(peer, ns["log_len"], 0)).any()
            return two | gt, jnp.where(two, R.CRASH_TWO_LEADERS,
                                       R.CRASH_COMMIT_GT_LOG)
    else:
        inv = None
    prog_raft = RaftKv(n, log_capacity, n_keys=n_keys, n_peers=n_raft,
                       apply_per_event=apply_per_event)
    prog_client = KvClient(n_raft, n_keys, n_ops)
    return Runtime(
        cfg, [prog_raft, prog_client],
        kv_state_spec(n, log_capacity, n_ops, n_keys, n_clients),
        node_prog=np.asarray([0] * n_raft + [1] * n_clients, np.int32),
        scenario=sc, invariant=inv, persist=kv_persist_spec(),
        halt_when=(all_clients_done(n_raft, n_ops) if halt else None))


VARIANTS = [
    # name, build kwargs — each removes/shrinks ONE component vs "full"
    ("full", {}),
    ("inv=cheap", dict(invariant="cheap")),
    ("inv=none", dict(invariant=None)),
    ("halt_when=none", dict(halt=False)),
    ("apply_per_event=1", dict(apply_per_event=1)),
    ("event_capacity=96", dict(event_capacity=96)),
    ("log_capacity=16", dict(log_capacity=16)),
    ("payload_words=11", dict(payload_words=11)),
    # the config-2 shape, for the cross-config anchor
    ("inv=none,C=96,L=16", dict(invariant=None, event_capacity=96,
                                log_capacity=16)),
]

# right-sizing candidates (run with --variants rightsize): the ablation
# found L the dominant axis; these measure the capacity floor config 4 can
# actually run at (log must fit n_clients*n_ops + election no-ops = 22+,
# ev_peak audit gates C)
RIGHTSIZE = [
    ("full", {}),
    ("L=32", dict(log_capacity=32)),
    ("C=96", dict(event_capacity=96)),
    ("L=32,C=96", dict(log_capacity=32, event_capacity=96)),
    ("L=32,C=96,B=1024", dict(log_capacity=32, event_capacity=96,
                              batch=1024)),
]

# host-chunk batch sweep (run with --variants batch): the 100k BASELINE row
# ran B=4096 chunks; per-lane state is ~20KB so 4096 lanes = ~80MB working
# set vs ~10MB at 512 — on CPU the cache footprint sets the rate
BATCH = [
    ("B=512", dict(batch=512)),
    ("B=1024", dict(batch=1024)),
    ("B=2048", dict(batch=2048)),
    ("B=4096", dict(batch=4096)),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--steps", type=int, default=512)
    ap.add_argument("--variants", default="ablate",
                    choices=["ablate", "rightsize", "batch"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import jax
    steps = args.steps
    table = {"ablate": VARIANTS, "rightsize": RIGHTSIZE,
             "batch": BATCH}[args.variants]
    rows = []
    for name, kw in table:
        kw = dict(kw)
        B = kw.pop("batch", args.batch)
        rt = build(**kw)
        seeds = np.arange(B)
        t0 = time.perf_counter()
        rt.run(rt.init_batch(seeds), steps, chunk=steps)     # compile+warm
        compile_s = time.perf_counter() - t0
        st0 = rt.init_batch(seeds)
        t0 = time.perf_counter()
        st, _ = rt.run(st0, steps, chunk=steps)
        fired = int(np.asarray(st.steps).sum())
        dt = time.perf_counter() - t0
        row = dict(variant=name, batch=B,
                   seed_events_per_sec=round(fired / dt, 1),
                   steps_fired=fired, wall_s=round(dt, 3),
                   compile_s=round(compile_s - dt, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)
    base = rows[0]["seed_events_per_sec"]
    for r in rows:
        r["speedup_vs_full"] = round(r["seed_events_per_sec"] / base, 3)
    out = dict(metric="config4_ablation",
               platform=jax.devices()[0].platform, variants=args.variants,
               steps=steps, rows=rows)
    print(json.dumps({r["variant"]: r["speedup_vs_full"] for r in rows}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
