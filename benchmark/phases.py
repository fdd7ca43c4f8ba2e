"""The program's own phases (on the device) and stages (on the host), as the
per-layer metrics under metrics/ read them.

Step phases: the step program runs its phases under `step.*` named scopes
(madsim_tpu/obs/scopes.py). A device trace names only instructions
(`%fusion.764`), so `Runtime.fused_op_scopes` maps each instruction of the
cell's `run_fused` program to its phase, compiling that program again from
its shapes (the window's own executable, found in JAX's caches). The map
is built once per process, after the window, in the traced run only. A
program without that method gives no map, and the phase metrics read
nothing.

Search stages: each `fuzz_round` record carries `host_s`, the host seconds
of each stage of the search loop since the previous record
(search/fuzz.py). Records without it give nothing.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness as H

_maps: dict[tuple, dict[str, str] | None] = {}


def op_scopes(run) -> dict[str, str] | None:
    """{instruction: phase or ""} of the cell's fused program, or None."""
    cfg, t = run["config"], run["traffic"]
    key = (cfg["name"], t["batch"], t["chunk"])
    if key not in _maps:
        rt = H.load_module("configs", cfg["name"]).build(cfg)
        scopes = getattr(rt, "fused_op_scopes", None)
        _maps[key] = scopes(t["batch"], t["chunk"]) if scopes else None
    return _maps[key]


def ns_per_event(run, phase: str) -> float | None:
    """Device-0 self nanoseconds per traced event of the ops in `phase`;
    `phase=""` gives the rest of device busy time, outside every phase (the
    while loops' own time, copies, the loop predicate, time between ops),
    so the phases and the rest sum to busy ns per event."""
    t = run["trace"]
    events = (run["traced_counts"] or {}).get("events", 0)
    if t is None or events <= 0:
        return None
    scopes = op_scopes(run)
    if scopes is None:
        return None
    by: dict[str, float] = {}
    for op, sec in t["devices"][0]["ops"].items():
        p = scopes.get(op, "")
        by[p] = by.get(p, 0.0) + sec
    if phase:
        seconds = by.get(phase, 0.0)
    else:
        seconds = t["busy_s"] - sum(s for p, s in by.items() if p)
    return seconds * 1e9 / events


def round_median(run, of) -> float | None:
    """Median over the window's fuzz rounds of `of(host_s)`, leaving out
    the round in which the harness wrote its trace out (as
    round_s_p95.schedules does); None where no record carries host_s."""
    a, b = run["trace_stop"]
    values, prev = [], 0.0
    for r in run["records"].rounds:
        if "host_s" not in r:
            return None
        if r["round"] == 1:
            prev = 0.0
        gap = r["wall_s"] - prev
        prev = r["wall_s"]
        if not (r["t_host"] - gap < b and r["t_host"] > a):
            values.append(of(r["host_s"]))
    return float(np.median(values)) if values else None
