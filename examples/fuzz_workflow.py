"""The full fuzzing workflow on a real seeded bug, end to end:

    coverage-driven explore  ->  crash harvest  ->  chaos-script ddmin
    ->  faithful repro report  ->  single-seed replay

    python examples/fuzz_workflow.py

Target: two-phase commit with `early_decide_quorum=2` — the classic
protocol bug (coordinator decides before all votes arrive), which chaos
turns into observable atomicity violations. The reference's workflow
for this is "run N seeds, print the failing seed" (MADSIM_TEST_NUM +
the repro line); here the sweep is coverage-metered, every distinct
crash code is harvested with its first seed, the chaos script shrinks
to its load-bearing rows, and the seed replays alone for inspection.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import Scenario, explore, minimize_scenario, ms
from madsim_tpu.models import two_phase_commit as tpc
from madsim_tpu.models.two_phase_commit import make_tpc_runtime

CODE_NAMES = {tpc.CRASH_DIVERGED: "DIVERGED (commit here, abort there)",
              tpc.CRASH_NO_VOTE_COMMIT: "COMMIT against a NO vote"}


def main():
    from madsim_tpu import NetConfig, SimConfig, sec

    # 15% loss is what actually triggers the bug (a dropped NO vote +
    # quorum-2 decide); the kill/restart rows are red herrings the
    # minimizer should expose as noise
    cfg = SimConfig(n_nodes=5, event_capacity=192, time_limit=sec(30),
                    net=NetConfig(packet_loss_rate=0.15))
    sc = Scenario()
    for t in range(3):
        sc.at(ms(200 + 400 * t)).kill_random(among=range(1, 5))
        sc.at(ms(400 + 400 * t)).restart_random(among=range(1, 5))
    rt = make_tpc_runtime(5, 6, scenario=sc, cfg=cfg,
                          early_decide_quorum=2, p_yes=0.6)

    print("== explore: coverage-metered sweep, crashes harvested ==")
    out = explore(rt, max_steps=40_000, batch=64, max_rounds=4)
    print(f"seeds run {out['seeds_run']}, distinct schedules "
          f"{out['distinct_schedules']}, crashes {out['crashes']}")
    if not out["crash_first_seed_by_code"]:
        print("no crashes found (unexpected for the seeded bug)")
        sys.exit(1)

    for code, seed in sorted(out["crash_first_seed_by_code"].items()):
        print(f"\n== crash {CODE_NAMES.get(code, code)}: first seed "
              f"{seed} ==")
        minimal, info = minimize_scenario(rt, seed, max_steps=40_000)
        print(f"chaos script shrank {info['kept'] + info['dropped']} -> "
              f"{info['kept']} rows ({info['runs']} candidate runs):")
        print(minimal.describe())
        # the shrunken script still reproduces, single lane
        rt.set_scenario(minimal)
        st, _ = rt.run(rt.init_single(seed), 40_000, collect_events=False)
        ok = bool(np.asarray(st.crashed).any())
        print(f"single-seed replay under minimal script: "
              f"{'reproduces' if ok else 'LOST THE BUG'}")
        rt.set_scenario(sc)
        if not ok:
            sys.exit(1)
    print("\nworkflow complete")


if __name__ == "__main__":
    main()
