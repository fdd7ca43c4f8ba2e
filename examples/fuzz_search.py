"""Coverage-guided schedule fuzzing vs blind seed sweeps, side by side.

    python examples/fuzz_search.py [rounds] [batch]

Runs the same chaos workload two ways at the same device budget: blind
`explore()` (fresh seeds, fixed fault script — it saturates) and the
coverage-guided `fuzz()` (corpus + on-device mutation of fault times,
targets, latencies, and PCT tie-break nudges — it keeps finding new
interleavings). Prints both coverage curves and, if the fuzzer found
crashes, the minimized fault script of each repro.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from madsim_tpu import ProgressObserver, explore, fuzz  # noqa: E402

# fixed-latency chaos: the schedule space seeds alone can reach is small,
# so the blind sweep goes dry — the regime where searching the knob space
# (instead of sampling seeds) pays; one shared definition with the search
# tests
from madsim_tpu.models.workloads import saturating  # noqa: E402


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    kw = dict(max_steps=1500, batch=batch, max_rounds=rounds,
              dry_rounds=rounds + 1, chunk=256)

    print(f"blind explore(): {rounds} rounds x {batch} seeds")
    blind = explore(saturating(), observer=ProgressObserver(), **kw)

    print(f"\nfuzz(): same budget, coverage-guided")
    res = fuzz(saturating(), observer=ProgressObserver(),
               minimize=True, **kw)

    print(f"\n  blind:  {blind['distinct_schedules']:>5} distinct "
          f"schedules  {blind['new_per_round']}")
    print(f"  fuzzer: {res['distinct_schedules']:>5} distinct "
          f"schedules  {res['new_per_round']}")
    print(f"  corpus: {res['corpus_size']} entries; operator use: "
          f"{res['mutation_ops']}")
    for code, rep in res["crash_repros"].items():
        print(f"\n  crash code {code}: seed {rep['seed']} "
              f"(round {rep['round']}) — fault script:")
        print(rep["script"])
        mini = res.get("minimized", {}).get(code)
        if mini and "script" in mini:
            print(f"  minimized to {mini['kept']} rows "
                  f"({mini['runs']} batched dispatches):")
            print(mini["script"])


if __name__ == "__main__":
    main()
