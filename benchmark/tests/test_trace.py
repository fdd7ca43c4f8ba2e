"""benchmark/trace.py on synthetic events and on a small recorded trace.

The recorded trace (data/small.xplane.pb) is a v5e chip's profile of two
units of the madraft5 sweep at 64 lanes x 32 steps, with the harness's
spans and markers (recorded by a traced run on the chip, PR 22).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_trace.py -q
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace as T  # noqa: E402

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def test_short_names_and_self_times():
    assert T.short("%fusion.12 = s32[4]{0} fusion(s32[4]{0} %a)") == \
        "%fusion.12"
    ops = [("w", 0, 100), ("a", 10, 20), ("b", 20, 50), ("c", 30, 40),
           ("d", 100, 110)]
    assert dict(T.self_times(ops)) == pytest.approx(
        {"w": 60e-9, "a": 10e-9, "b": 20e-9, "c": 10e-9, "d": 10e-9})


def test_union_merges_overlaps_and_touching():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        [0, 4], [5, 7], [10, 11]]


def test_busy_idle_ops_collectives_and_gaps():
    devices = {
        D0: {T.OP_LINE: [("%while.1", 10, 40), ("%fusion.1", 12, 20),
                         ("%fusion.2", 25, 40),          # inside the while
                         ("%all-reduce.3", 60, 70), ("%fusion.1", 90, 130)],
             T.MODULE_LINE: [("jit_run", 8, 41)],
             T.ASYNC_LINE: [("%all-gather-start.4", 65, 75)]},
        D1: {T.OP_LINE: [("%fusion.1", 0, 50)]},
    }
    spans = [("trace_start", 0, 0), ("trace_stop", 100, 100),
             ("run_fused", 5, 45), ("harvest", 40, 62),
             ("init_batch", 62, 95)]
    r = T.summarize(devices, spans)
    d0, d1 = r["devices"]
    assert r["window_s"] == pytest.approx(100e-9)
    # busy on device 0: [8,41) [60,70) [90,100]
    assert d0["busy_s"] == pytest.approx((33 + 10 + 10) * 1e-9)
    assert d1["busy_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(51.5e-9)
    # self time: the while's 30 less its children's 8 + 15
    assert d0["ops"]["%while.1"] == pytest.approx(7e-9)
    assert d0["ops"]["%fusion.1"] == pytest.approx((8 + 10) * 1e-9)
    assert d0["ops"]["%fusion.2"] == pytest.approx(15e-9)
    # all-reduce [60,70) and the async all-gather [65,75): union 15
    assert d0["collective_s"] == pytest.approx(15e-9)
    assert d1["collective_s"] == 0
    assert r["device_ops"][0][0] == "%fusion.1"
    # gaps on device 0: [70,90) in init_batch, [41,60) mostly in harvest
    # and the end of run_fused, [0,8) in run_fused (the longest first)
    assert [n for n, _ in r["idle_gaps"]] == ["init_batch",
                                              "harvest+run_fused",
                                              "run_fused"]
    assert [s * 1e9 for _, s in r["idle_gaps"]] == pytest.approx(
        [20, 19, 8])


def test_label_names_the_innermost_spans():
    spans = [("unit", 0, 100), ("run", 10, 30), ("check", 30, 60)]
    assert T.label(spans, 12, 20) == "run"
    assert T.label(spans, 25, 50) == "check+run"
    assert T.label(spans, 70, 90) == "unit"
    assert T.label(spans, 200, 300) == "no span"


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_trace():
    devices, spans = T.read(SMALL)
    assert list(devices) == [D0] and len(devices[D0][T.OP_LINE]) > 1000
    names = {n for n, _, _ in spans}
    assert {"trace_start", "trace_stop", "init_batch", "run_fused",
            "harvest"} <= names
    r = T.reduce(SMALL)
    t0 = [s for n, s, _ in spans if n == "trace_start"][0]
    t1 = [s for n, s, _ in spans if n == "trace_stop"][0]
    # busy time by a sweep line that counts open events
    marks = []
    for _, s, e in devices[D0][T.OP_LINE] + devices[D0][T.MODULE_LINE]:
        lo, hi = max(s, t0), min(e, t1)
        if hi > lo:
            marks += [(lo, 1), (hi, -1)]
    covered, depth, last = 0, 0, None
    for t, d in sorted(marks, key=lambda m: (m[0], -m[1])):
        if depth > 0:
            covered += t - last
        depth, last = depth + d, t
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(covered / 1e9, rel=1e-9)
    # self times add up to the op line's busy time, nothing counted twice
    ops_busy = sum(e - s for s, e in T.union(
        [(max(s, t0), min(e, t1)) for _, s, e in devices[D0][T.OP_LINE]
         if e > t0 and s < t1])) / 1e9
    assert sum(r["devices"][0]["ops"].values()) == pytest.approx(ops_busy,
                                                                 rel=1e-6)
    assert r["devices"][0]["collective_s"] == 0      # one chip
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert {p for n, _ in r["idle_gaps"] for p in n.split("+")} <= \
        names | {"no span"}
