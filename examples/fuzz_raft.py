"""Headline demo: fuzz 10k MadRaft-style clusters under chaos in one go.

    python examples/fuzz_raft.py [num_seeds]

Prints a fleet report; on any invariant violation prints the repro line
and replays the failing seed with a full event trace.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
from madsim_tpu.harness.simtest import SimFailure, run_seeds
from madsim_tpu.models.raft import make_raft_runtime
from madsim_tpu.parallel.stats import summarize
from madsim_tpu.runtime.trace import print_trace


def main():
    n_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
    cfg = SimConfig(n_nodes=5, event_capacity=128, time_limit=sec(6),
                    net=NetConfig(packet_loss_rate=0.05))
    sc = Scenario()
    for t in range(4):
        sc.at(ms(800 + 900 * t)).kill_random()
        sc.at(ms(1300 + 900 * t)).restart_random()
    sc.at(sec(2)).partition([0, 1])
    sc.at(sec(3)).heal()

    rt = make_raft_runtime(5, log_capacity=16, n_cmds=6, scenario=sc, cfg=cfg)
    seeds = np.arange(n_seeds)
    try:
        state = run_seeds(rt, seeds, max_steps=30_000, chunk=1024)
    except SimFailure as e:
        print(e)
        print(f"\n--- replaying seed {e.seed} ---")
        _, events = rt.run_single(e.seed, max_steps=30_000)
        print_trace(events, 0, limit=200)
        raise SystemExit(1)

    rep = summarize(rt, state, seeds)
    print("fleet report:")
    for k, v in rep.items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
