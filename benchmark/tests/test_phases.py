"""benchmark/phases.py: the step-phase and search-stage readers, on a
trace reduction and fuzz records made up around the real program's map.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_phases.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import harness as H  # noqa: E402
from benchmark import phases  # noqa: E402

PHASES = ("pick", "supervisor", "handler", "emit", "check")


def sweep_run(ops: dict, busy_s: float, events: int) -> dict:
    cfg = H.load_json("benchmark/configs/madraft5.json")
    return dict(config=cfg, traffic=dict(batch=8, chunk=16),
                traced_counts=dict(events=events),
                trace=dict(busy_s=busy_s, devices=[dict(ops=ops)]))


def read(name: str, run):
    return H.load_module("metrics", name).read(run)


def test_phase_metrics_split_busy_time():
    run = sweep_run({}, 1.0, 1000)
    scopes = phases.op_scopes(run)
    pick = {}
    for op, p in scopes.items():
        pick.setdefault(p, op)
    assert set(pick) == {"", *(f"step.{p}" for p in PHASES)}
    ops = {pick[f"step.{p}"]: 0.1 * (i + 1) for i, p in enumerate(PHASES)}
    ops[pick[""]] = 0.05                       # a while loop's own time
    ops["%not_in_this_program.1"] = 0.01       # another program's op
    run["trace"]["devices"][0]["ops"] = ops
    got = {p: read(f"{p}_ns_per_event.events", run) for p in PHASES}
    for i, p in enumerate(PHASES):
        assert got[p] == pytest.approx(0.1 * (i + 1) * 1e9 / 1000)
    rest = read("unscoped_ns_per_event.events", run)
    assert rest == pytest.approx((1.0 - 1.5) * 1e9 / 1000)
    assert sum(got.values()) + rest == pytest.approx(
        read("device_ns_per_event.events", run))


def test_phase_metrics_silent_without_the_program_map(monkeypatch):
    from madsim_tpu.runtime.runtime import Runtime
    monkeypatch.delattr(Runtime, "fused_op_scopes")
    monkeypatch.setattr(phases, "_maps", {})
    run = sweep_run({"%fusion.1": 0.5}, 1.0, 1000)
    for p in PHASES + ("unscoped",):
        assert read(f"{p}_ns_per_event.events", run) is None
    run["trace"] = None
    assert read("pick_ns_per_event.events", run) is None


class Records:
    def __init__(self, rounds):
        self.rounds = rounds


def fuzz_run(host_s, stop=(0.0, 0.0)):
    """Two campaigns of three rounds, one second apart."""
    rounds, t = [], 100.0
    for c in range(2):
        for r in range(3):
            t += 1.0
            rec = dict(round=r + 1, wall_s=float(r + 1), t_host=t)
            if host_s is not None:
                rec["host_s"] = dict(host_s(c * 3 + r))
            rounds.append(rec)
    return dict(records=Records(rounds), trace_stop=stop)


def stages(i):
    return dict(schedule=0.01 * i, mutate=0.02, dispatch=0.03, wait=0.5 + i,
                fetch=0.04, admit=0.1, crashes=0.0, dedup=0.05, record=0.01,
                sync=0.0)


def test_stage_medians():
    run = fuzz_run(stages)
    # host: 0.25 + 0.01 i, i = 0..5
    assert read("search_host_s.schedules", run) == pytest.approx(0.275)
    assert read("corpus_s.schedules", run) == pytest.approx(0.1 + 0.025)
    assert read("device_wait_s.schedules", run) == pytest.approx(3.0)
    # the round in which the trace was written out is left out: the
    # stop falls inside the second campaign's first round
    run = fuzz_run(stages, stop=(103.5, 103.6))
    assert read("device_wait_s.schedules", run) == pytest.approx(2.5)


def test_stage_metrics_silent_without_host_s():
    run = fuzz_run(None)
    for name in ("search_host_s", "corpus_s", "device_wait_s"):
        assert read(f"{name}.schedules", run) is None
