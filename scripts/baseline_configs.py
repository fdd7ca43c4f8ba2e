"""Measure BASELINE.md's configs 0-4 and emit one JSON row per config.

The reference publishes no numbers (BASELINE.md: bench infrastructure
only), so the CPU-reference column is its *execution model* reproduced
here — one seed advancing sequentially (the `cargo test` loop analog,
task.rs:110-124) — and the batched column is this engine on JAX's
default device. Each row names its platform; a row measured on the CPU
is a CPU number, never a chip number.

Usage:
    python scripts/baseline_configs.py [--config N] [--scale F] [--out f]

--scale shrinks seed counts for smoke runs (e.g. 0.01); the committed
artifact must be produced at scale 1.0.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _plat():
    import jax
    return jax.devices()[0].platform


def _pingpong_rt():
    from madsim_tpu import Runtime, SimConfig, sec
    from madsim_tpu.models.pingpong import PingPong, state_spec
    cfg = SimConfig(n_nodes=3, time_limit=sec(30), event_capacity=32)
    return Runtime(cfg, [PingPong(3, target=20)], state_spec())


def config0(scale):
    """Single-seed 3-node ping-pong on the CPU sim runtime, plus the
    determinism check — the per-seed baseline every other row divides."""
    rt = _pingpong_rt()
    assert rt.check_determinism(seed=7, max_steps=4000)
    state, _ = rt.run(rt.init_single(3), 512)   # warm
    reps = max(1, int(20 * scale))
    t0 = time.perf_counter()
    ev = 0
    for s in range(reps):
        st, _ = rt.run(rt.init_single(s), 4000)
        ev += int(np.asarray(st.steps).sum())
    dt = time.perf_counter() - t0
    return dict(config=0, platform=_plat(), seeds=reps,
                events_per_sec=round(ev / dt, 1), determinism_check=True,
                wall_s=round(dt, 2))


def config1(scale):
    """1k-seed batched 3-node ping-pong on one device."""
    rt = _pingpong_rt()
    B = max(8, int(1024 * scale))
    seeds = np.arange(B)
    rt.run(rt.init_batch(seeds), 512)           # warm/compile
    t0 = time.perf_counter()
    st, _ = rt.run(rt.init_batch(seeds), 4000)
    dt = time.perf_counter() - t0
    assert bool(st.halted.all()) and not bool(np.asarray(st.crashed).any())
    ev = int(np.asarray(st.steps).sum())
    return dict(config=1, platform=_plat(), seeds=B,
                seed_events_per_sec=round(ev / dt, 1), wall_s=round(dt, 2))


def config2(scale):
    """MadRaft 5-node leader election under random partition, 10k seeds."""
    from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
    from madsim_tpu.models import raft as R
    from madsim_tpu.models.raft import make_raft_runtime
    sc = Scenario()
    for t in range(4):
        sc.at(ms(400 + 800 * t)).partition([t % 5, (t + 1) % 5])
        sc.at(ms(800 + 800 * t)).heal()
    cfg = SimConfig(n_nodes=5, event_capacity=96, time_limit=sec(4),
                    net=NetConfig())
    rt = make_raft_runtime(5, log_capacity=16, n_cmds=0, scenario=sc,
                           cfg=cfg)
    B = max(64, int(10_000 * scale))
    total_ev = 0
    elected = 0
    t0 = time.perf_counter()
    for lo in range(0, B, 4096):
        seeds = np.arange(lo, min(lo + 4096, B))
        st, _ = rt.run(rt.init_batch(seeds), 12_000)
        assert not bool(np.asarray(st.crashed).any())
        total_ev += int(np.asarray(st.steps).sum())
        role = np.asarray(st.node_state["role"])
        elected += int(((role == R.LEADER).sum(axis=1) >= 1).sum())
    dt = time.perf_counter() - t0
    return dict(config=2, platform=_plat(), seeds=B,
                seed_events_per_sec=round(total_ev / dt, 1),
                elected_fraction=round(elected / B, 4), wall_s=round(dt, 2))


def config3(scale):
    """tonic-style RPC service under packet loss + kill/restart, 50k
    seeds — the @rpc service stack (net/service.py) under chaos."""
    import jax.numpy as jnp
    from madsim_tpu import Runtime, Scenario, SimConfig, NetConfig, sec, ms
    from madsim_tpu.models.rpc_echo import (EchoClient, EchoServer,
                                            server_state_spec)
    sc = Scenario()
    sc.at(ms(300)).kill(0)
    sc.at(ms(700)).restart(0)
    cfg = SimConfig(n_nodes=3, event_capacity=48, time_limit=sec(6),
                    net=NetConfig(packet_loss_rate=0.1))
    rt = Runtime(cfg, [EchoServer(), EchoClient(target=10,
                                                timeout=ms(60))],
                 server_state_spec(), node_prog=[0, 1, 1], scenario=sc)
    B = max(64, int(50_000 * scale))
    total_ev = 0
    t0 = time.perf_counter()
    for lo in range(0, B, 8192):
        seeds = np.arange(lo, min(lo + 8192, B))
        st, _ = rt.run(rt.init_batch(seeds), 20_000)
        assert not bool(np.asarray(st.crashed).any())
        total_ev += int(np.asarray(st.steps).sum())
    dt = time.perf_counter() - t0
    return dict(config=3, platform=_plat(), seeds=B,
                seed_events_per_sec=round(total_ev / dt, 1),
                wall_s=round(dt, 2))


def config4_runtime():
    """Config 4's raft_kv deployment: 5 Raft nodes, 3 clients, 3 keys,
    6 ops each, 3 kill/restart cycles, 5% loss (chip_smoke.py runs it
    too)."""
    from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
    from madsim_tpu.models.raft_kv import make_kv_runtime
    sc = Scenario()
    for t in range(3):
        sc.at(ms(700 + 900 * t)).kill_random(among=range(5))
        sc.at(ms(1200 + 900 * t)).restart_random(among=range(5))
    cfg = SimConfig(n_nodes=8, event_capacity=96, payload_words=12,
                    time_limit=sec(8),
                    net=NetConfig(packet_loss_rate=0.05))
    return make_kv_runtime(n_raft=5, n_clients=3, n_keys=3, n_ops=6,
                           log_capacity=32, scenario=sc, cfg=cfg)


def config4(scale):
    """Full MadRaft log replication + linearizability fuzz, 100k seeds,
    early-exit compaction (run_compacting) — the north-star workload.
    Every chunk's client histories run through the linearizability
    checker (native C++, Python fallback beyond 57 ops/key).

    Shapes are right-sized from the r5 ablation (DESIGN.md "Where config
    4's 8x went"): log_capacity 48->32 and event_capacity
    128->96 measured 2.0x per-event on CPU at identical workload semantics
    (same nodes/ops/chaos/checker; 32 >= the 22-entry no-compaction floor
    asserted by make_kv_runtime, and any overflow crashes loudly via oops).
    The host chunk is platform-dependent: per-lane state is ~15KB, so CPU
    runs 512-lane chunks (cache-resident) while TPU keeps 4096."""
    from madsim_tpu.models.raft_kv import extract_histories
    from madsim_tpu.native import check_kv_history
    rt = config4_runtime()
    B = max(256, int(100_000 * scale))
    # both chunk axes are platform-dependent: CPU favors small cache-
    # resident lane chunks + frequent compaction checks; TPU keeps the r4
    # geometry (4096 lanes, 2048-step scans) — fewer device->host syncs,
    # and the r5 CPU measurements must not silently change the TPU config
    on_tpu = _plat() == "tpu"
    chunk_lanes = 4096 if on_tpu else 512
    chunk_steps = 2048 if on_tpu else 512
    total_ev = 0
    checked = 0
    check_s = 0.0
    t0 = time.perf_counter()
    for lo in range(0, B, chunk_lanes):
        seeds = np.arange(lo, min(lo + chunk_lanes, B))
        st = rt.run_compacting(rt.init_batch(seeds), 60_000,
                               chunk=chunk_steps)
        assert not bool(np.asarray(st.crashed).any()), \
            f"crash at seed {seeds[np.argmax(np.asarray(st.crashed))]}"
        # the right-sized event_capacity must never overflow silently —
        # dropped emissions are protocol-legal loss, but the measured row
        # has to represent the configured fault model, nothing more
        assert not bool((np.asarray(st.oops) != 0).any()), \
            "oops set (event/time overflow) — capacity too small"
        total_ev += int(np.asarray(st.steps).sum())
        tc = time.perf_counter()
        for h in extract_histories(st, 5, 3):
            assert check_kv_history(h), "non-linearizable history"
            checked += 1
        check_s += time.perf_counter() - tc
        print(f"config4: {min(lo + chunk_lanes, B)}/{B} seeds done",
              file=sys.stderr)
    dt = time.perf_counter() - t0
    # engine rate excludes the host-side checker time (measured
    # separately as check_wall_s) so the figure is comparable to the
    # no-checking configs 0-3; wall_s is the full fuzz+check wall
    return dict(config=4, platform=_plat(), seeds=B,
                seed_events_per_sec=round(total_ev / (dt - check_s), 1),
                histories_checked=checked, all_linearizable=True,
                check_wall_s=round(check_s, 1), wall_s=round(dt, 2),
                compaction=f"run_compacting(chunk={chunk_steps}) x "
                           f"{chunk_lanes}-lane host chunks")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=None)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    fns = [config0, config1, config2, config3, config4]
    todo = fns if args.config is None else [fns[args.config]]
    rows = []
    for fn in todo:
        row = fn(args.scale)
        row["cmd"] = (f"python scripts/baseline_configs.py "
                      f"--config {row['config']} --scale {args.scale}")
        rows.append(row)
        print(json.dumps(row))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"metric": "baseline_configs", "scale": args.scale,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
