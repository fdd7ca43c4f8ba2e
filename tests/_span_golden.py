"""Shared harness for the r23 bit-identical-when-disabled contract.

The critical-path attribution plane (r23) added engine machinery — the
per-row accumulated span columns (`ev_span`), the tail-attribution
counters (`sa_tail`, `sa_bottleneck`), the `tr_qw` ring column, the
`sp_on` lane gate — that is compiled out at the default
`span_attr=False` and masked to identity when compiled in but no lane
records. The contract is that a workload never enabling the plane
produces trajectories BIT-IDENTICAL to r22, leaf for leaf, chunked and
fused.

Same frozen workload builders as the r17/r19/r21 harnesses
(_grayfail_golden — the canonical engine-equivalence workloads); digests
were captured AT r22 HEAD by scripts/capture_golden.py into
tests/data/golden_r22_leaves.json, before any r23 engine change landed.
Every r22 leaf must still exist and hash identically — the only new
leaves the r23 plane may add are the span plane's own
(`.sp_on` and the zero-size span columns the simconfig-v8 signature
gates).
"""

from __future__ import annotations

import os

import _grayfail_golden as _g

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "golden_r22_leaves.json")

# the frozen definition is shared with the r17/r19/r21 harnesses — one
# set of engine workloads, four captured truths (r16, r18, r20, r22)
RUNS = _g.RUNS
BUILDERS = _g.BUILDERS
leaf_digests = _g.leaf_digests
run_workload = _g.run_workload


TRACE_PATH = os.path.join(os.path.dirname(__file__), "data",
                          "golden_r22_trace.json")


def export_span_off_trace(path: str) -> None:
    """Lane 0 of the frozen pingpong workload (span never on), exported
    as Chrome-trace JSON: the bytes golden_r22_trace.json holds."""
    import numpy as np
    from madsim_tpu.obs.trace import export_chrome_trace
    rt = BUILDERS["pingpong"]()
    run = RUNS["pingpong"]
    st, _ = rt.run(rt.init_batch(np.arange(run["seeds"], dtype=np.uint32)),
                   run["max_steps"], run["chunk"])
    export_chrome_trace(path, state=st, lane=0)


def capture(path: str = GOLDEN_PATH) -> dict:
    export_span_off_trace(TRACE_PATH)
    return _g.capture(path)


def load_golden(path: str = GOLDEN_PATH) -> dict:
    with open(path) as f:
        import json
        return json.load(f)
