"""ShardKV demo: watch shards migrate between Raft groups, live.

    python examples/shard_migration.py [num_seeds]

Fuzzes a full sharded-KV deployment — a raft-replicated config service,
two kv Raft groups, and clients — while the controller keeps moving
shards between groups. Per-lane report: how many configurations
committed, where every shard ended up, and whether each lane's client
history stayed linearizable across the migrations (checked with the
native C++ checker).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
from madsim_tpu.harness.simtest import run_seeds
from madsim_tpu.models.shard_kv import (
    extract_histories, grp_of, make_shard_runtime)
from madsim_tpu.native import check_kv_history

RC, RG, G, NC, S = 3, 3, 2, 2, 4
CLIENTS_BASE = RC + G * RG


def main():
    n_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    sc = Scenario()
    for t in range(3):  # chaos on the servers while shards move
        sc.at(ms(1200 + 1500 * t)).kill_random(among=range(CLIENTS_BASE))
        sc.at(ms(1900 + 1500 * t)).restart_random(among=range(CLIENTS_BASE))
    cfg = SimConfig(n_nodes=CLIENTS_BASE + NC, event_capacity=160,
                    payload_words=12, time_limit=sec(60),
                    net=NetConfig(packet_loss_rate=0.05,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(10)))
    rt = make_shard_runtime(n_groups=G, rg=RG, rc=RC, n_clients=NC,
                            n_ops=6, max_cfg=4, scenario=sc, cfg=cfg)
    state = run_seeds(rt, np.arange(n_seeds), max_steps=120_000)

    ns = {k: np.asarray(v) for k, v in state.node_state.items()}
    hists = extract_histories(state, CLIENTS_BASE, NC)
    for b in range(n_seeds):
        cfg_n = int(ns["cfg_n"][b, :RC].max())
        ctrl = int(ns["cfg_n"][b, :RC].argmax())   # a controller that's
        asn = int(ns["cfg_hist"][b, ctrl, cfg_n])  # fully caught up
        owners = [int(grp_of(asn, s)) for s in range(S)]
        done = ns["c_opn"][b, CLIENTS_BASE:]
        lin = check_kv_history(hists[b])
        print(f"seed {b:3d}: configs={cfg_n} shard->group={owners} "
              f"client_ops={list(done)} linearizable={lin}")
        assert lin
    print(f"\nall {n_seeds} lanes linearizable across live migrations")


if __name__ == "__main__":
    main()
