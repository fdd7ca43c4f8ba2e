"""The reduction from a profiler trace (xplane) to device metrics.

`reduce(path, t0_ns, t1_ns)` reads the `.xplane.pb` that
`jax.profiler.trace` wrote and returns, for the window [t0_ns, t1_ns] on the
trace's own clock:

  devices      one row per device plane: busy seconds (the union of the
               intervals in which a program or an operation ran), each
               op's self seconds by name (a `while` holds its body's ops:
               only the time no child covers is its own), collective
               seconds (sync and async collective ops, their union)
  busy_s       busy seconds averaged over the devices
  window_s     the window's length
  device_ops   the ten operations that took the most device time (device 0)
  idle_gaps    the ten longest idle gaps on device 0, each named by the
               innermost harness spans (`bench.*` TraceAnnotation) the host
               was in during the gap, the one covering most first

The window runs from the harness's `bench.trace_start` marker to its
`bench.trace_stop` marker when the caller passes no bounds.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the lines of a device plane: one event per operation run (nested: a
# while's event holds its body's), one per program run, and the spans of
# asynchronous operations (copies, async collectives) in flight
OP_LINE, MODULE_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|alltoall", re.I)
SPAN_PREFIX = "bench."


def find(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane under {trace_dir}, found "
                           f"{files}")
    return files[0]


def union(intervals):
    """Sorted, merged copy of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip3(events, t0, t1):
    """(name, start, end) events cut to [t0, t1]."""
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def short(op: str) -> str:
    """`%fusion.12 = s32[...] fusion(...)` -> `%fusion.12`."""
    return op.split(" = ", 1)[0]


def self_times(ops):
    """[(name, start, end)] of one line, nested events allowed ->
    [(name, self seconds)]: an event's time less its direct children's."""
    out, stack = [], []          # stack of [name, start, end, child_ns]

    def close(ev):
        out.append((ev[0], (ev[2] - ev[1] - ev[3]) / 1e9))
    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def read(path: str):
    """(device planes {name: {line: [(op, start, end)]}}, host spans
    [(name, start, end)]) from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [(short(n), s, e) for n, s, e in _events(line)]
                for line in plane.lines
                if line.name in (OP_LINE, MODULE_LINE, ASYNC_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        spans.append((name[len(SPAN_PREFIX):], s, e))
    return devices, spans


def reduce(path: str, t0_ns: float | None = None,
           t1_ns: float | None = None) -> dict:
    return summarize(*read(path), t0_ns, t1_ns)


def summarize(devices: dict, spans: list, t0_ns: float | None = None,
              t1_ns: float | None = None) -> dict:
    """`reduce` on events already read: {device plane: {line: [(op, start,
    end)]}} and host spans [(name, start, end)], times in ns."""
    if t0_ns is None:
        marks = {n: s for n, s, e in spans if n.startswith("trace_")}
        if len(marks) != 2:
            raise RuntimeError(f"trace lacks its start/stop markers: {marks}")
        t0_ns, t1_ns = marks["trace_start"], marks["trace_stop"]
    window = (t1_ns - t0_ns) / 1e9
    rows = []
    for name in sorted(devices, key=lambda n: int(n.rsplit(":", 1)[1])):
        lines = {k: clip3(v, t0_ns, t1_ns) for k, v in devices[name].items()}
        ops = lines.get(OP_LINE, [])
        busy = union([(s, e) for _, s, e in ops + lines.get(MODULE_LINE,
                                                          [])])
        by_op: dict[str, float] = {}
        for o, sec in self_times(ops):
            by_op[o] = by_op.get(o, 0.0) + sec
        coll = [(s, e) for o, s, e in ops + lines.get(ASYNC_LINE, [])
                if COLLECTIVE.search(o)]
        rows.append(dict(
            device=name, busy_s=sum(e - s for s, e in busy) / 1e9,
            ops=by_op, collective_s=sum(e - s for s, e in union(coll)) / 1e9,
            busy=busy))
    if not rows:
        raise RuntimeError("trace holds no device plane with operations")
    gaps = []
    d0 = rows[0]["busy"]
    edges = [t0_ns] + [x for iv in d0 for x in iv] + [t1_ns]
    inner = [(n, s, e) for n, s, e in spans if not n.startswith("trace_")]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append((label(inner, s, e), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    top = sorted(rows[0]["ops"].items(), key=lambda kv: -kv[1])[:10]
    for r in rows:
        del r["busy"]
    return dict(devices=rows, window_s=window,
                busy_s=sum(r["busy_s"] for r in rows) / len(rows),
                device_ops=[[n, s] for n, s in top],
                idle_gaps=[[n, s] for n, s in gaps[:10]])


def label(spans, s, e) -> str:
    """What the host was doing in [s, e]: the innermost spans overlapping
    it (those holding no other overlapping span), by how much of it each
    covers, joined by '+'; "no span" if none overlaps."""
    over = [(n, a, b, min(b, e) - max(a, s)) for n, a, b in spans
            if min(b, e) > max(a, s)]
    inner = [x for x in over
             if not any(y is not x and x[1] <= y[1] and y[2] <= x[2]
                        and (y[1], y[2]) != (x[1], x[2]) for y in over)]
    inner.sort(key=lambda x: -x[3])
    return "+".join(dict.fromkeys(x[0] for x in inner)) or "no span"
