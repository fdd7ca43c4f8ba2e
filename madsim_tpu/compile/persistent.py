"""The cross-process compile tier: JAX's persistent compilation cache.

`PROGRAM_CACHE` amortizes compiles within a process; this wires the
on-disk tier so COLD processes (a fresh CI lane, a new harness run) reuse
warm XLA artifacts. JAX keys persistent entries by the serialized HLO +
compile options, so the structural/dynamic split upstream matters here
too: with dynamic knobs as traced operands, a sweep over time limits or
fault models maps onto ONE on-disk artifact.

Contract (DESIGN §10): the cache stores post-optimization executables
keyed by program content — it can never change results, only skip the
XLA compile stage (traces still run, so `COMPILE_LOG.note_trace` counts
are unaffected). Safe to share between lanes of one workspace; do not
share a directory across incompatible jaxlib versions (jax already keys
the version in, stale entries are simply missed).
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: a fixed path, so every process of this checkout
# (tests, chip_smoke.py, campaign workers) finds what another compiled; a
# directory that moves (a mkdtemp, a corpus dir) never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def cache_dir() -> str:
    """The one place the persistent cache lives: JAX_COMPILATION_CACHE_DIR
    when it is set, else `DEFAULT_CACHE_DIR`."""
    return os.path.abspath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                           or DEFAULT_CACHE_DIR)


def enable_persistent_cache(min_compile_secs: float | None = None) -> str:
    """Point jax at `cache_dir()`; idempotent. Returns the directory.

    `min_compile_secs` skips persisting trivial programs whose disk
    round-trip would cost more than the compile. Only an explicit value
    is applied: without one, jax's own setting stands (1 s, or
    JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS), so the repeated calls
    from run_seeds/fuzz/run_fused never clobber a floor a caller chose."""
    import jax
    d = cache_dir()
    os.makedirs(d, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    if min_compile_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_secs))
    return d
