"""Raft snapshot fuzz: the log window is far smaller than the workload, so
trajectories only survive through compaction + InstallSnapshot — and a
node that slept through most of the run recovers via snapshot transfer.

    python examples/snapshot_fuzz.py [num_seeds]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
from madsim_tpu.harness.simtest import run_seeds
from madsim_tpu.models.raft import make_raft_runtime


def main():
    n_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 4_096
    cmds, log_cap = 40, 12           # 40 proposals through a 12-entry window
    cfg = SimConfig(n_nodes=5, event_capacity=256, time_limit=sec(12),
                    net=NetConfig(packet_loss_rate=0.05))
    sc = Scenario()
    sc.at(ms(400)).kill(0)           # node 0 misses almost everything
    sc.at(sec(5)).restart(0)         # ...and can only catch up by snapshot
    for t in range(3):
        sc.at(ms(900 + 900 * t)).kill_random(among=range(1, 5))
        sc.at(ms(1400 + 900 * t)).restart_random(among=range(1, 5))

    rt = make_raft_runtime(5, log_capacity=log_cap, n_cmds=cmds,
                           compact_threshold=4, scenario=sc, cfg=cfg)
    state = run_seeds(rt, np.arange(n_seeds), max_steps=40_000, chunk=1024)

    ns = state.node_state
    snap = np.asarray(ns["snap_len"])
    commit = np.asarray(ns["commit"])
    print(f"seeds: {n_seeds}")
    print(f"commit (min/median/max over seeds, cluster max): "
          f"{commit.max(1).min()} / {int(np.median(commit.max(1)))} / "
          f"{commit.max(1).max()}")
    print(f"snapshots: every live node compacted in "
          f"{(snap.max(1) > 0).mean() * 100:.1f}% of seeds; "
          f"node 0 recovered via InstallSnapshot in "
          f"{(snap[:, 0] > 0).mean() * 100:.1f}%")
    print(f"log window never exceeded {log_cap} entries; "
          f"safety checked after every event (digest chain below the "
          f"snapshot boundary)")


if __name__ == "__main__":
    main()
