"""The benchmark's shared plumbing: cells from BENCHMARK.json, files found by
name, seeds, host spans and the projection of a state kept for the check.

Nothing here knows a configuration, a traffic mix or a metric: each lives in
a file of its own (configs/, traffic/, drivers/, checks/, metrics/), found by
the name BENCHMARK.json gives it.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def cell(workload: str) -> dict:
    """Everything one cell needs: its BENCHMARK.json entry, its
    configuration's file, its traffic file, and the metrics it reports."""
    bench = load_json("BENCHMARK.json")
    w = {x["name"]: x for x in bench["workloads"]}.get(workload)
    if w is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return workload in m.get("workloads", [workload])

    return dict(
        workload=w, config=load_json(conf["file"]),
        config_name=conf["name"],
        traffic=load_json(os.path.join("benchmark", "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def lane_base(seed: int) -> int:
    """The first lane seed of a run: a 32-bit draw from `--seed`, so runs
    with different seeds start at unrelated points of the uint32 seed
    space, and the same seed always gives the same lanes."""
    return int(np.random.SeedSequence(int(seed)).generate_state(
        1, np.uint32)[0])


def lane_seeds(base: int, start: int, count: int) -> np.ndarray:
    """`count` consecutive uint32 lane seeds from base + start, wrapping."""
    lo = (int(base) + int(start)) % (1 << 32)
    return (np.arange(count, dtype=np.uint64) + np.uint64(lo)).astype(
        np.uint32)


class Spans:
    """Host spans around the calls into each layer. Each is a
    `jax.profiler.TraceAnnotation` too, so a traced run finds it in the
    trace's host plane on the device's clock; the host-clock totals are
    kept here for metrics that read spans."""

    def __init__(self):
        self.total: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        dt = time.perf_counter() - t0
        self.total[name] = self.total.get(name, 0.0) + dt


class Tracer:
    """A profiler trace over the window's first `seconds`: started with the
    window, stopped at the first sync point of the program (`tick`) after
    that many seconds, so a trace holds whole units or rounds and stays a
    size a run can read back. Markers on the host plane bound it."""

    def __init__(self, path: str | None, seconds: float):
        self.path, self.seconds = path, seconds
        self.on = False
        self.counts: dict | None = None
        self.stop = (0.0, 0.0)       # host clock span of stop_trace

    def start(self):
        if self.path is None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self.on = True
        self.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.trace_start"):
            pass

    def tick(self, counts: dict, force: bool = False):
        if not self.on or (time.perf_counter() - self.t0 < self.seconds
                           and not force):
            return
        import jax
        with jax.profiler.TraceAnnotation("bench.trace_stop"):
            pass
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop = (t, time.perf_counter())
        self.on = False
        self.counts = dict(counts)

    def paused(self) -> float:
        """Seconds the window spent writing the trace out: not the
        program's time, so the window leaves them out."""
        return self.stop[1] - self.stop[0]


# leaves every lane keeps for the check, beside the configuration's own
LANE_LEAVES = ("crashed", "crash_code", "oops", "halted", "steps",
               "sched_hash")


def projection(state, node_leaves) -> dict:
    """The device arrays the after-window check reads, and nothing else:
    keeping these (not the whole state) lets a window hold every unit's
    answer without holding every unit's state."""
    out = {k: getattr(state, k) for k in LANE_LEAVES}
    out.update({f"ns.{k}": state.node_state[k] for k in node_leaves})
    return out


def fetch(projections: list[dict]) -> dict:
    """Host copies of a window's projections, units stacked on the lane
    axis."""
    if not projections:
        return {}
    return {k: np.concatenate([np.asarray(p[k]) for p in projections])
            for k in projections[0]}


def differs(a: dict, b: dict) -> np.ndarray:
    """bool[lanes]: lanes on which any leaf of two host projections of the
    same lanes differs."""
    out = np.zeros(len(a["steps"]), bool)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        out |= (x != y).reshape(len(out), -1).any(axis=1)
    return out


def run_checks(cfg: dict, lanes: dict, extra: dict | None = None) -> dict:
    """The configuration's reference checks over every lane kept: a dict
    of name -> bool[lanes] failure flags."""
    ns = {k[3:]: v for k, v in lanes.items() if k.startswith("ns.")}
    ns.update(extra or {})
    flags = dict(crashed=lanes["crashed"].astype(bool),
                 oops=lanes["oops"] != 0)
    for name in cfg["checks"]:
        flags.update(load_module("checks", name).check(cfg, ns))
    return flags


def node_leaves(cfg: dict) -> list[str]:
    out: list[str] = []
    for name in cfg["checks"]:
        for leaf in load_module("checks", name).leaves(cfg):
            if leaf not in out:
                out.append(leaf)
    return out
