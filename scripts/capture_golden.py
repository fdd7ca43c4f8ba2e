"""Freeze per-leaf golden digests for an equivalence contract.

Run this ONLY at an engine state whose trajectories are the truth being
gated. Each fault-plane PR captures its own harness module at the HEAD
it gates against:

    # r17 contract (captured at r16 HEAD, before the gray-failure plane)
    JAX_PLATFORMS=cpu python scripts/capture_golden.py _grayfail_golden

    # r19 contract (captured at r18 HEAD, before the connection-fault plane)
    JAX_PLATFORMS=cpu python scripts/capture_golden.py _connfault_golden

    # r21 contract (captured at r20 HEAD, before the windowed-telemetry plane)
    JAX_PLATFORMS=cpu python scripts/capture_golden.py _series_golden

    # r23 contract (captured at r22 HEAD, before the attribution plane;
    # tests/data/golden_r22_trace.json — the span-off Chrome-trace
    # byte-identity golden — was captured at the same point)
    JAX_PLATFORMS=cpu python scripts/capture_golden.py _span_golden

Re-running a capture after the gated engine change landed would
overwrite the evidence with whatever the current tree produces — the
test would then prove nothing. The one legitimate re-run is a RE-FREEZE:
the toolchain moved every digest while the code did not (PR 21: jax
0.9.0 turned `jax_threefry_partitionable` on, moving every key-derived
leaf). Check the contract on the tree first — plane off vs every plane
compiled in but masked, leaf for leaf — then re-run all four captures
at one tree. An existing golden keeps its leaf set; only the hashes
move.
"""

import importlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

module = sys.argv[1] if len(sys.argv) > 1 else "_grayfail_golden"
g = importlib.import_module(module)

doc = g.capture()
n = sum(len(v) for w in doc.values() for v in w.values())
print(f"captured {n} leaf digests -> {g.GOLDEN_PATH}")
