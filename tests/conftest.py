"""Test env: the CPU backend with a virtual 8-device mesh, set before any
test imports jax.

Tests run on the host CPU; the program runs on the chip through
`python chip_smoke.py` (see README). Sharding correctness is checked on
the virtual mesh here and, on real chips, by `chip_smoke.py --four-chips`.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from madsim_tpu.compile.persistent import enable_persistent_cache  # noqa: E402

# Persistent compilation cache (DESIGN §10): most suite wall-clock is XLA
# recompiles of near-identical step programs. Two tiers cut it: the
# process-level PROGRAM_CACHE (madsim_tpu/compile) shares executables
# across Runtime constructions WITHIN the run, and this on-disk cache
# reuses them ACROSS runs, at JAX_COMPILATION_CACHE_DIR or else the
# checkout's .jax_cache.
#
# Floor RAISED from 1.0s (r16): this jaxlib's deserialized-executable
# first-invocation corruption (ROADMAP r12 open item) reproduces WITHOUT
# concurrency at ~1/5 per fresh process on small fused runners read from
# this cache (a masked lane-gate came back all-False — repro in the r16
# notes; the r15 profiler masked tests flake the same way standalone).
# Small programs recompile in ~a second anyway — keeping only compiles
# ≥5s persistent removes the high-traffic deserializations from the
# corruption surface while the expensive flagship executables (the
# reason this cache exists) stay cached. Retire with the r12 item when
# the toolchain moves.
enable_persistent_cache(min_compile_secs=5.0)


def pytest_sessionfinish(session, exitstatus):
    """Print the compile-counter summary at suite end (scripts/ci.sh sets
    MADSIM_COMPILE_SUMMARY=1): how many retraces the suite paid, by
    runner label, plus program-cache hit rates and jax stage seconds."""
    if not os.environ.get("MADSIM_COMPILE_SUMMARY"):
        return
    try:
        from madsim_tpu.compile.cache import COMPILE_LOG
        print(f"\n{COMPILE_LOG.summary()}")
    except Exception:  # noqa: BLE001 - reporting must never fail the suite
        pass
