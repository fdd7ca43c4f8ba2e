"""Compilation layer: compile each structural shape once, not once per Runtime.

Execution is fast (the fused runner, DESIGN §5); *getting to execution*
was not: every `Runtime` construction used to own private `jax.jit`
closures, so jit's function-identity cache could never share a step
program across Runtime instances — `explore()` studies, `harness/simtest`
tests, and sweep harnesses all paid a fresh trace+compile for programs
that were structurally identical to ones already built (the Podracer
lesson, PAPERS.md: an accelerator-resident loop only wins when program
construction is amortized; veScale makes the same point about keeping the
compiled-program cache hot across logically-distinct runs).

Three tiers, from hot to cold:

  * `signature.py`  — what "structurally identical" means: the
    shape/lowering-affecting slice of `SimConfig`
    (`SimConfig.structural_signature()`) plus a deep freeze of programs,
    state spec, invariant/halt_when closures, persist mask, and
    extensions. Dynamic knobs (time limit, loss, latency, jitter bound,
    `trace_cap` within its power-of-two bucket) are traced operands in
    `SimState` and never key a compile.
  * `cache.py`      — `PROGRAM_CACHE`, the process-level cache of jitted
    runners keyed on (structural signature, runner kind, backend), and
    `COMPILE_LOG`, the compile counter / stage-timing log that observers
    (`obs.metrics.SweepObserver.on_compile`) and CI summaries read.
  * `persistent.py` — the cross-process tier: wires JAX's persistent
    compilation cache (`jax_compilation_cache_dir`) so cold CI processes
    reuse warm on-disk executables.
"""

from .cache import COMPILE_LOG, PROGRAM_CACHE, ProgramCache
from .persistent import enable_persistent_cache
from .signature import (freeze, next_pow2, program_signature,
                        runtime_signature)

__all__ = [
    "COMPILE_LOG", "PROGRAM_CACHE", "ProgramCache",
    "enable_persistent_cache",
    "freeze", "next_pow2", "program_signature", "runtime_signature",
]
