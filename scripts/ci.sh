#!/bin/bash
# One-command CI — the analog of the reference's test-std/test-sim matrix
# (ci.yml:57-86 runs the same workspace suite against the simulator AND
# real tokio; here the sim tier is the vectorized engine and the realworld
# tier drives real sockets/wall-clock through the same Programs).
#
# Usage: scripts/ci.sh [fast|full]
#   fast (default)  sim tier minus the long chaos sweeps, then the
#                   realworld tier serially (wall-clock pacing breaks
#                   under CPU contention — see pytest.ini). Green in a few
#                   minutes warm-cached on a 1-core box.
#   full            everything: whole suite (the `slow` tests included), a
#                   MADSIM_TEST_CHECK_DETERMINISM re-run of @simtest
#                   workloads (the reference's determinism-check-by-replay
#                   mode, macros lib.rs:160-186), and the 8-device
#                   virtual-mesh multichip dryrun.
set -euo pipefail
cd "$(dirname "$0")/.."
tier=${1:-fast}

# Persistent compile cache (DESIGN §10): JAX_COMPILATION_CACHE_DIR when
# set, else the checkout's .jax_cache (compile/persistent.py), shared by
# both lanes, so a cold CI process reuses warm XLA executables. Content-
# keyed — it can only skip the compile stage, never change results. Use
# the SAME 5s floor tests/conftest.py applies (ROADMAP r12/r16:
# small deserialized executables can corrupt on first invocation — the
# floor keeps them out of the cache), so every process started here
# seeds the shared cache under the suite's rule.
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="${JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS:-5}"
# pytest prints the compile-counter summary at suite end (tests/conftest.py)
export MADSIM_COMPILE_SUMMARY="${MADSIM_COMPILE_SUMMARY:-1}"

case "$tier" in
  fast)
    python -m pytest tests/ -q -m "not realworld and not slow"
    python -m pytest tests/ -q -m "realworld and not slow"
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
    ;;
  *)
    echo "usage: scripts/ci.sh [fast|full]" >&2
    exit 2
    ;;
esac
echo "ci $tier: OK"
