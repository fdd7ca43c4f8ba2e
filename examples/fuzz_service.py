"""An always-on fuzzing service: N workers, one durable corpus dir.

    python examples/fuzz_service.py CORPUS_DIR [workers] [rounds] [shards]

The CI-farm shape (ROADMAP "production traffic"): every invocation
RESUMES the campaign in CORPUS_DIR — worker processes pick up at their
persisted round counts, merge each other's coverage at round syncs, and
dedup crashes into shared causal-fingerprint buckets. Kill it however
you like (Ctrl-C, SIGKILL, power loss): nothing past the last round sync
is lost, and the next invocation converges to the run that was never
killed. Run it again with a larger `rounds` to keep an existing campaign
growing. `shards` > 1 makes every worker a mesh-sharded campaign of
that width (DESIGN §15 — worker processes force their own virtual CPU
mesh; on real chips pin one worker per host and let the mesh span its
devices): processes x shards compose, all namespaces stay disjoint.

Prints live campaign stats while the workers run, then the merged
report: coverage, per-worker rounds, and one line per deduped crash
bucket with its durable (seed, knobs) repro handle.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from madsim_tpu import ProgressObserver, campaign_report, run_campaign  # noqa: E402

# the crash-rich wal_kv matrix (lost unsynced writes under kill/restart
# chaos): one shared definition with the campaign and search tests
FACTORY = "madsim_tpu.models.workloads:crashrich"
FACTORY_KWARGS = dict(kind="wal_kv", trace_cap=64, sketch_slots=4)


def main():
    if len(sys.argv) < 2:
        print(__doc__)
        sys.exit(2)
    corpus_dir = sys.argv[1]
    workers = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    rounds = int(sys.argv[3]) if len(sys.argv) > 3 else 4
    shards = int(sys.argv[4]) if len(sys.argv) > 4 else 1

    print(f"campaign: {workers} workers x {shards} shard(s) x {rounds} "
          f"rounds (campaign total) -> {corpus_dir}")
    try:
        rep = run_campaign(
            FACTORY, corpus_dir, workers=workers, max_rounds=rounds,
            max_steps=4096, batch=48, chunk=512, shards=shards,
            factory_kwargs=FACTORY_KWARGS, observer=ProgressObserver(),
            poll_s=1.0)
    except KeyboardInterrupt:
        print("\ninterrupted — campaign state is durable; rerun to resume")
        if not os.path.exists(os.path.join(corpus_dir, "MANIFEST.json")):
            # interrupted before any worker created the store
            sys.exit(0)
        rep = campaign_report(corpus_dir)

    print(f"\n  coverage: {rep['coverage_keys']} distinct schedules "
          f"({rep['corpus_entries']} corpus entries, "
          f"{rep['schedules_per_sec']}/s)")
    for w, d in sorted(rep["workers_detail"].items()):
        print(f"  worker {w}: {d['rounds_done']} rounds, "
              f"{d['corpus_entries']} live entries, {d['wall_s']}s")
    print(f"  crash buckets: {rep['buckets_merged']} "
          f"({rep['crash_observations']} observations deduped)")
    for b in rep["bucket_detail"]:
        mini = " [minimized]" if b["minimized"] else ""
        print(f"    {b['key']}  code {b['crash_code']}  "
              f"x{b['observations']}  repro seed {b['repro']['seed']} "
              f"(worker {b['repro']['worker_id']}, "
              f"round {b['repro']['round']}){mini}")
    print("\nrerun the same command (or with more rounds) to resume; "
          "replay a bucket with madsim_tpu.replay_bucket(rt, dir, key)")


if __name__ == "__main__":
    main()
