"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in BENCHMARK.json names a configuration (configs/<name>.json
and the builder configs/<name>.py beside it) and a traffic mix
(traffic/<mix>.json, whose `driver` names drivers/<driver>.py). Set-up builds
the runtime and runs one warm unit of the cell's own shapes; the window then
repeats whole units until `--seconds` have passed and closes at the end of
the unit in progress. With `--trace 1` a profiler trace covers the window's
first unit(s) (the traffic's `trace_seconds`), and the metrics are the cell's
per-layer ones (metrics/<name>.py each).

After the window the driver's output checks run against the plain
references under checks/; each number is printed beside its limit on
standard error and under `checks`, last in the result line. A run on
anything but a TPU, or on fewer chips than the cell asks for, exits
non-zero and prints no result.
"""

from __future__ import annotations

import os
import time


def _process_age() -> float:
    """Seconds since this process started (Linux /proc)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


T_START = time.perf_counter() - _process_age()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import harness as H  # noqa: E402


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (platform "
                         f"{info['platform']!r}); no result")
    if info["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"sees {info['count']}; no result")
    return info


def compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR points), every program
    kept, so only a checkout's first run compiles."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class Ctx:
    """What a driver gets: the cell's files, its seed range, its spans."""

    def __init__(self, cell: dict, seed: int, control: bool):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.seed = seed
        self.base = H.lane_base(seed)
        self.spans = H.Spans()
        self.control = control
        self.tick = lambda: None      # the run's trace stops at a tick

    def build(self):
        mod = H.load_module("configs", self.cell["config_name"])
        return mod.build(self.config, control=self.control)


class Compiles:
    """Counts backend compiles (a cache hit loads, it does not compile)."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        self.on = False

        def hear(event, **kw):
            if self.on and event == "/jax/compilation_cache/cache_misses":
                self.n += 1
        mon.register_event_listener(hear)


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv=None, require_tpu: bool = True,
         overrides: dict | None = None) -> dict:
    """`require_tpu=False` and `overrides` (traffic keys replaced, for small
    shapes) are for the tests under benchmark/tests, never the CLI."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="run the configuration's control fault instead "
                         "(the check must fail it); never set by the "
                         "benchmark's own runs")
    args = ap.parse_args(argv)
    cell = H.cell(args.workload)
    cell["traffic"].update(overrides or {})
    chips = int(cell["workload"]["chips"])
    device = device_info(chips, require_tpu)
    import jax
    used = jax.devices()[:chips]
    cache = compile_cache()
    compiles = Compiles()
    ctx = Ctx(cell, args.seed, bool(args.control))
    driver = H.load_module("drivers", cell["traffic"]["driver"]).Driver(ctx)
    with ctx.spans("setup"):
        driver.setup()
    setup_s = time.perf_counter() - T_START
    say(f"{args.workload} seed={args.seed} set-up {setup_s:.3f} s, "
        f"cache {cache}")

    trace_dir = os.path.join(ROOT, ".bench_trace", f"run{os.getpid()}")
    if args.trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = H.Tracer(trace_dir if args.trace else None,
                      float(cell["traffic"]["trace_seconds"]))
    ctx.tick = lambda: tracer.tick(driver.counts)
    ctx.spans.total.clear()
    compiles.on = True
    units = 0
    t0 = time.perf_counter()
    tracer.start()
    unit_s = []
    while True:
        t1 = time.perf_counter()
        with ctx.spans("unit"):
            driver.unit(units)
        unit_s.append(time.perf_counter() - t1)
        units += 1
        ctx.tick()
        if time.perf_counter() - t0 - tracer.paused() >= args.seconds:
            break
    elapsed = time.perf_counter() - t0 - tracer.paused()
    compiles.on = False
    tracer.tick(driver.counts, force=True)
    peak = memory_peak(used)
    say(f"window {elapsed:.3f} s, {units} units "
        f"({' '.join(f'{x:.3f}' for x in unit_s)} s), {compiles.n} compiles "
        f"inside it, counts {driver.counts}")

    run = dict(elapsed=elapsed, setup_s=setup_s, units=units,
               counts=dict(driver.counts), traced_counts=tracer.counts,
               trace_stop=tracer.stop,
               spans=dict(ctx.spans.total),
               records=getattr(driver, "records", None),
               config=ctx.config, traffic=ctx.traffic, trace=None,
               device_kind=device["kind"])
    result = dict(correct=None, attempted=0, failed=0, metrics={},
                  device=dict(platform=device["platform"],
                              kind=device["kind"], count=device["count"],
                              memory_peak_bytes=peak))
    if args.trace:
        from benchmark import trace as T
        red = T.reduce(T.find(trace_dir))
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        run["trace"] = red
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = dict(device_ops=red["device_ops"],
                                   idle_gaps=red["idle_gaps"])
    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    for m in wanted:
        value = H.load_module("metrics", m["name"]).read(run)
        if value is not None:
            result["metrics"][m["name"]] = dict(value=value, unit=m["unit"])

    flags = driver.verify(np.random.default_rng(args.seed))
    lanes = None
    failed = None
    checks = {}
    for name, f in flags.items():
        if isinstance(f, np.ndarray):
            lanes = len(f)
            failed = f.copy() if failed is None else failed | f
            checks[name] = dict(value=int(f.sum()), limit=0)
        else:
            checks[name] = dict(value=int(f), limit=0)
    result["attempted"] = int(lanes or 0)
    result["failed"] = int(failed.sum()) if failed is not None else 0
    result["correct"] = bool(lanes) and all(
        c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
