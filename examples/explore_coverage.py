"""Coverage-driven fuzzing: sweep seed batches until the schedule space
dries up, harvesting every distinct failure on the way.

    python examples/explore_coverage.py [batch] [max_rounds]

The reference's lever is a fixed seed count (MADSIM_TEST_NUM, macros
lib.rs:152-167); here each round's distinct-schedule yield is measured
(SimState.sched_hash), so the sweep stops when more seeds stop buying
new interleavings — and a buggy protocol's crashes are collected per
code with their first repro seed instead of aborting the hunt.

Demo workload: WAL-KV with the durability sync REMOVED under power-fail
chaos — the oracle (an acked write must never be un-written) has real
violations to find.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import ProgressObserver, Scenario, ms
from madsim_tpu.models import wal_kv
from madsim_tpu.models.wal_kv import make_wal_kv_runtime
from madsim_tpu.parallel.explore import explore


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    max_rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    sc = Scenario()
    for t in range(6):
        sc.at(ms(150) + ms(250) * t).kill(0)
        sc.at(ms(210) + ms(250) * t).restart(0)
    rt = make_wal_kv_runtime(n_clients=2, n_ops=12, wal_cap=64,
                             sync_wal=False, scenario=sc)

    # live per-round coverage growth on stderr while the sweep runs
    # (obs/progress.py; swap in JsonlObserver to persist the records)
    out = explore(rt, max_steps=60_000, batch=batch, max_rounds=max_rounds,
                  observer=ProgressObserver())
    print(f"seeds run           : {out['seeds_run']}")
    print(f"distinct schedules  : {out['distinct_schedules']}")
    print(f"new per round       : {out['new_per_round']}")
    print(f"saturated           : {out['saturated']}")
    print(f"crashed trajectories: {out['crashes']}")
    for code, seed in out["crash_first_seed_by_code"].items():
        name = ("LOST_WRITE" if code == wal_kv.CRASH_LOST_WRITE
                else f"code {code}")
        print(f"  {name}: repro with MADSIM_TEST_SEED={seed}")


if __name__ == "__main__":
    main()
