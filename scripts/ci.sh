#!/bin/bash
# One-command CI — the analog of the reference's test-std/test-sim matrix
# (ci.yml:57-86 runs the same workspace suite against the simulator AND
# real tokio; here the sim tier is the vectorized engine and the realworld
# tier drives real sockets/wall-clock through the same Programs).
#
# Usage: scripts/ci.sh [fast|full] [--compile-smoke]
#   fast (default)  sim tier minus the long chaos sweeps, then the
#                   realworld tier serially (wall-clock pacing breaks
#                   under CPU contention — see pytest.ini). Green in a few
#                   minutes warm-cached on a 1-core box. With
#                   --compile-smoke, also asserts the shared step-program
#                   cache (two structurally-equal configs -> 1 compile).
#   full            everything: whole suite, a MADSIM_TEST_CHECK_DETERMINISM
#                   re-run of @simtest workloads (the reference's
#                   determinism-check-by-replay mode, macros lib.rs:160-186),
#                   and the 8-device virtual-mesh multichip dryrun.
set -euo pipefail
cd "$(dirname "$0")/.."
tier=${1:-fast}

# Persistent compile cache (DESIGN §10): JAX_COMPILATION_CACHE_DIR when
# set, else the checkout's .jax_cache (compile/persistent.py), shared by
# both lanes, so a cold CI process reuses warm XLA executables. Content-
# keyed — it can only skip the compile stage, never change results. Use
# the SAME 5s floor tests/conftest.py applies (ROADMAP r12/r16:
# small deserialized executables can corrupt on first invocation — the
# floor keeps them out of the cache). Without this, the bench smokes
# below run floor-less (jax's default floor is 1s) and re-seed the
# shared cache with exactly the small high-traffic executables the
# pytest floor exists to exclude — the suite then deserializes them and
# the r16-era masked-digest flake returns.
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="${JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS:-5}"
# pytest prints the compile-counter summary at suite end (tests/conftest.py)
export MADSIM_COMPILE_SUMMARY="${MADSIM_COMPILE_SUMMARY:-1}"

case "$tier" in
  fast)
    python -m pytest tests/ -q -m "not realworld and not slow"
    python -m pytest tests/ -q -m "realworld and not slow"
    # seconds-scale fused-runner smoke: run_fused must stay bitwise-equal
    # to the chunked runner and the pipelined explore() must round-trip
    python bench.py --fused-smoke
    # observability smoke: a tiny traced fused sweep must yield a readable
    # ring that exports as valid Chrome-trace JSON, and the exporter's
    # event counts must agree with the engine's own fired counts
    python bench.py --obs-smoke
    # schedule-fuzzer smoke: a small coverage-guided campaign must beat
    # blind explore() on the saturating workload, exercise the mutation
    # operators, and enumerate PCT tie-break policies
    python bench.py --search-smoke
    # causal-lineage smoke: lineage/sketch compiled in but masked off
    # must not perturb trajectories, a fuzzer-harvested crash must
    # explain itself (parent chain + Perfetto flow arrows), and the
    # divergence profile must come back from the on-device sketches
    python bench.py --causal-smoke
    # persistent-campaign smoke: two concurrent worker processes must
    # merge into one corpus dir with deduped causal-fingerprint crash
    # buckets, a SIGKILLed campaign must resume to exactly the
    # uninterrupted run, and a structurally different runtime must be
    # rejected by the store's version/signature contract
    python bench.py --campaign-smoke
    # mesh-sharded-campaign smoke: a 1-shard sharded campaign must write
    # a byte-identical durable store to the unsharded fuzzer, a 2-shard
    # CPU-mesh campaign must merge both shard namespaces (coverage
    # superset, foreign entries delivered, consensus tally serialized),
    # and a split 2-shard campaign must resume equal to the
    # uninterrupted control with the verify_resume guard armed
    python bench.py --shard-smoke
    # sim-profiler smoke: on-device counters must match a host-replayed
    # reference on a seeded chaos run, profiling on/off/masked must be
    # bit-identical leaf-for-leaf, Perfetto counter tracks must export
    # next to the instants, and fuzz rounds must report per-operator
    # coverage yield summing to each round's admissions
    python bench.py --prof-smoke
    # SLO latency-plane smoke: the on-device e2e histograms must equal a
    # host parent-walk of the flight-recorder ring (root-inheritance
    # rule end to end), the plane on/masked/compiled-out must be
    # bit-identical, slo_invariant must crash deterministically with
    # CRASH_SLO and replay by seed, and the Perfetto export must carry
    # the rolling per-node e2e-p99 track
    python bench.py --lat-smoke
    # windowed-telemetry smoke: every lane's device series must equal a
    # host replay of the flight-recorder ring bucketed by the window
    # rule, the plane on/masked/compiled-out must be bit-identical, the
    # recovery oracle must stay green on the healed flagship and crash
    # CRASH_RECOVERY deterministically (seed-replayable) on the
    # unhealed one, the Perfetto export must carry true sim-time
    # counter tracks, and a burst-guided fuzz campaign must open a
    # CRASH_RECOVERY bucket whose (seed, knobs) handle replays red
    python bench.py --series-smoke
    # attribution-plane smoke: the device's per-(lane, node) tail
    # counters must equal a host parent-walk of the flight-recorder
    # ring on every component (count/queue-wait/net/hops), the plane
    # on/compiled-out must be bit-identical, a pause/resume workload
    # must telescope host request spans exactly (wait + transit == e2e)
    # with the dominant-node fold matching the device bottleneck
    # histogram, explain_latency must be deterministic on re-run, and
    # the Perfetto export must carry request duration spans iff the
    # plane is on
    python bench.py --span-smoke
    # gray-failure smoke: a one-way cut must be observed asymmetrically
    # by gossip, skewed lease expiry on the Percolator-lite flagship
    # must crash the snapshot oracle and reproduce on seed replay, and
    # a torn-write fuzz campaign must open causal-fingerprint crash
    # buckets with replayable (seed, knobs) handles
    python bench.py --grayfail-smoke
    # connection-fault smoke: OP_RESET_PEER must tear conn/stream state
    # on BOTH sides (vs the kill's deliberate half-open survivor), the
    # minipg exactly-once flagship must survive the reset+dup storm with
    # incarnation guards on AND crash fingerprint-exact-replayably with
    # them compiled to the pre-r19 behavior, and a dup-storm fuzz
    # campaign must open causal buckets whose handles replay red
    python bench.py --conn-smoke
    # campaign-triage smoke: a 2-worker campaign must snapshot
    # byte-stably into the triage/ history, a planted bucket must diff
    # as exactly one `new` entry with its torn_write recipe
    # attribution (both attribution dimensions summing to their
    # totals), the standing HTML dashboard must render, and the
    # repro-health audit must record a verdict via replay_bucket
    python bench.py --triage-smoke
    # time-travel smoke: a crash recorded with a wrapped 4-slot ring
    # must replay from a harvested checkpoint to a complete
    # (truncated=False) causal chain, bit-stably twice, staying
    # bucket-compatible with the live truncated observation; and the
    # divergence microscope must name the same first divergent
    # dispatch on a re-run of the same lane pair
    python bench.py --tt-smoke
    # lineage-driven-fault-injection smoke: green-support extraction on
    # a seeded rpc_echo lane must match an inline host parent-walk
    # reference, every synthesized targeted vector must stay on the
    # knob plane (time-guarded rows only, pool-confined targets,
    # in-bounds values), and one targeted round must replay
    # bit-identically from its (seed, knobs) handle
    python bench.py --ldfi-smoke
    # regression gate (OSS-Fuzz-style): every committed crash bucket in
    # tests/data/regression_corpus must still reproduce (run-twice
    # verified) and the top-energy corpus slice must still land on its
    # recorded schedule hashes
    python bench.py --regression-smoke
    # DetSan smoke: the repo-wide determinism lint gate must be clean,
    # a seeded schedule race must confirm via the forced-commute PCT
    # nudge with a replayable (seed, knobs, nudge) repro and dedupe
    # into one bucket, and the detsan double-run sanitizer must pass on
    # a clean runtime while its differ catches a planted divergence
    python bench.py --analyze-smoke
    if [[ "${2:-}" == "--compile-smoke" ]]; then
      # shared step-program cache smoke: two structurally-equal configs
      # must cost exactly one retrace and stay bitwise-equal to a
      # fresh-compile control
      python bench.py --compile-smoke
    fi
    ;;
  full)
    python -m pytest tests/ -q
    # determinism re-run: every @simtest-decorated workload runs its base
    # seed twice and bit-compares full state
    MADSIM_TEST_CHECK_DETERMINISM=1 python -m pytest -q \
        tests/test_raft.py tests/test_rpc_echo.py tests/test_gossip.py
    # multi-chip sharding compiles + executes on a virtual 8-device mesh
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
    # seconds-scale bench self-tests: the measurement paths (incl. the
    # native baseline twin and the shared-compile cache) must not rot —
    # the reference's ci.yml runs its criterion benches the same way
    python bench.py --smoke
    python bench.py --compile-smoke
    ;;
  *)
    echo "usage: scripts/ci.sh [fast|full] [--compile-smoke]" >&2
    exit 2
    ;;
esac
echo "ci $tier: OK"
