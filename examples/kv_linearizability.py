"""Linearizability fuzzing: KV-on-Raft under chaos, histories checked by
the native Wing-Gong checker.

    python examples/kv_linearizability.py [num_seeds]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
from madsim_tpu.harness.simtest import run_seeds
from madsim_tpu.models.raft_kv import extract_histories, make_kv_runtime
from madsim_tpu.native import check_kv_history


def main():
    n_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    n_raft, n_clients = 5, 3
    cfg = SimConfig(n_nodes=n_raft + n_clients, event_capacity=384,
                    payload_words=12, time_limit=sec(8),
                    net=NetConfig(packet_loss_rate=0.08))
    sc = Scenario()
    for t in range(4):
        sc.at(ms(700 + 800 * t)).kill_random(among=range(n_raft))
        sc.at(ms(1200 + 800 * t)).restart_random(among=range(n_raft))
    sc.at(sec(2)).partition([0, 1])
    sc.at(sec(3)).heal()

    rt = make_kv_runtime(n_raft, n_clients, n_keys=3, n_ops=8,
                         log_capacity=48, scenario=sc, cfg=cfg)
    state = run_seeds(rt, np.arange(n_seeds), max_steps=60_000, chunk=1024)
    hists = extract_histories(state, n_raft, n_clients)
    ok = sum(check_kv_history(h) for h in hists)
    completed = sum(int((h["resp"] >= 0).sum()) for h in hists)
    pending = sum(int((h["resp"] < 0).sum()) for h in hists)
    print(f"{n_seeds} seeds: {ok}/{n_seeds} histories linearizable, "
          f"{completed} ops completed, {pending} pending at halt")
    if ok != n_seeds:
        bad = next(i for i, h in enumerate(hists) if not check_kv_history(h))
        print(f"NON-LINEARIZABLE history at seed {bad}: {hists[bad]}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
