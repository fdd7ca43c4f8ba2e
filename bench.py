"""Benchmark: MadRaft-style fuzz throughput, batched-TPU vs single-seed CPU.

North star (BASELINE.md): simulated schedules/sec (seeds x events/s) on a
5-node Raft cluster under chaos (kill/restart + partition/heal + packet
loss). The reference publishes no numbers (BASELINE.md: its benches are CI
infrastructure only) and its Rust toolchain is not in this image, so the
baseline is the reference's *execution model* reproduced here: one seed
advancing sequentially on one CPU core (the `cargo test` loop analog —
jit-compiled, so this baseline is if anything generous). vs_baseline is
batched-TPU seed-events/s over single-seed-CPU events/s.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
"""

import functools
import json
import os
import subprocess
import sys
import time

import numpy as np

B_TPU = 4096        # seed batch on the TPU chip
WARM = 128          # warmup steps (includes compile)
STEPS = 1024        # timed steps
CPU_STEPS = 512     # timed steps for the single-seed CPU baseline


def _make_runtime(table_dtype: str = "int32", n_nodes: int = 5,
                  log_capacity: int = 32, payload_words: int = 8,
                  event_capacity: int | None = None,
                  emission_write: str = "auto"):
    from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
    from madsim_tpu.models.raft import make_raft_runtime

    n = n_nodes
    # event_capacity sized from measured occupancy (state.ev_peak): n=5
    # peaks at 75 rows, n=15 at 135, n=25 at 216 over 4096-step chaos
    # runs — ~9n, linear because randomized election timeouts stagger RV
    # broadcasts (the O(n^2) simultaneous-candidates storm doesn't
    # materialize). 16n gives ~1.8x headroom; the bench's oops assert
    # turns any overflow into a loud failure, not UB.
    if event_capacity is None:
        event_capacity = max(96, 16 * n)
    cfg = SimConfig(n_nodes=n, event_capacity=event_capacity,
                    time_limit=sec(600), payload_words=payload_words,
                    net=NetConfig(packet_loss_rate=0.05),
                    table_dtype=table_dtype, emission_write=emission_write)
    sc = Scenario()
    for t in range(8):  # rolling chaos, one cycle per simulated second
        sc.at(sec(1 + t)).kill_random()
        sc.at(sec(1 + t) + ms(400)).restart_random()
        sc.at(sec(1 + t) + ms(600)).partition([t % n, (t + 1) % n])
        sc.at(sec(1 + t) + ms(900)).heal()
    return make_raft_runtime(n, log_capacity=log_capacity, n_cmds=24,
                             scenario=sc, cfg=cfg)


def _events_per_sec(batch: int, steps: int, warm: int, make=None) -> float:
    import jax
    rt = (make or _make_runtime)()
    state = rt.init_batch(np.arange(batch))
    runner = rt._run_chunk[False]
    # warmup with the SAME static chunk length as the timed region, so the
    # timed region measures execution, not a recompile
    state, _ = runner(state, steps)
    jax.block_until_ready(state.now)
    t0 = time.perf_counter()
    state, _ = runner(state, steps)
    jax.block_until_ready(state.now)
    dt = time.perf_counter() - t0
    live = float(np.asarray(~state.halted).mean())
    assert not bool(np.asarray(state.crashed).any()), "bench workload crashed"
    assert not bool((np.asarray(state.oops) != 0).any()), \
        "event table overflowed — raise event_capacity"
    assert live > 0.9, f"bench lanes went idle (live={live:.2f})"
    return batch * steps / dt


def _native_baseline_eps(seeds: int = 200, events_per_seed: int = 4096):
    """The second baseline denominator: native/simloop.cpp — a tight C++
    discrete-event loop (heap + random tie-break + RNG loss/latency draws)
    of the SAME flagship workload, one seed at a time on one core (the
    task.rs:110-124 execution model, minus Rust async machinery). Measures
    the chaos-heavy first `events_per_seed` events per seed — the same
    event range the batched side is timed on. Returns None without a C++
    toolchain; sanity-checks that the workload actually elects and commits
    so a silently-broken twin can't set the denominator."""
    from madsim_tpu.native import native_baseline_run
    if native_baseline_run(0, 64) is None:
        return None
    tot_ev, tot_wall, commits, elections = 0, 0.0, 0, 0
    for seed in range(seeds):
        r = native_baseline_run(seed, events_per_seed)
        tot_ev += r["events"]
        tot_wall += r["wall_s"]
        commits = max(commits, r["max_commit"])
        elections += r["elections"]
    assert commits > 0 and elections >= seeds, \
        f"native twin not exercising the workload ({commits=}, {elections=})"
    return dict(events_per_sec=tot_ev / tot_wall, seeds=seeds,
                events_per_seed=events_per_seed, max_commit=commits)


def _force_cpu_inprocess():
    """Run THIS process on the host CPU backend: the CI smokes and the
    host-side modes, whose numbers are CPU numbers and say so. Must run
    before anything in this process touches a JAX device."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def _cpu_env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _require_chip(label: str) -> dict:
    """The device modes measure the chip and nothing else: raise when JAX's
    default device is not a TPU (there is no CPU fallback). Returns the
    device as every result names it."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"{label}: JAX found no TPU ({dev}); this mode "
                         "measures the chip — run it there "
                         "(tests and smokes run on the CPU)")
    print(f"{label}: device {json.dumps(dev)}", file=sys.stderr)
    return dev


def _cpu_baseline_eps() -> float:
    """The reference's execution model: one seed at a time on the host
    CPU backend, in this process — no child process, so nothing competes
    for the chip. Scatter emission writes, the CPU's own lowering."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        return _events_per_sec(1, CPU_STEPS, WARM, make=functools.partial(
            _make_runtime, emission_write="scatter"))


def _sweep_mode():
    """--sweep: batch x event_capacity tuning sweep on the default
    platform (short timed segments). Prints one JSON line per config;
    use it on the chip to pick B_TPU / event_capacity."""
    _require_chip("--sweep")
    import jax
    from madsim_tpu import Scenario, SimConfig, NetConfig, ms as _ms, sec
    from madsim_tpu.models.raft import make_raft_runtime

    steps = 256
    # 80 rides the measured ev_peak of 75 (DESIGN §5b) — the sweep on
    # chip decides whether the tighter table clears overflow-free
    for C in (80, 96, 128):
        cfg = SimConfig(n_nodes=5, event_capacity=C, time_limit=sec(600),
                        net=NetConfig(packet_loss_rate=0.05))
        sc = Scenario()
        for t in range(8):
            sc.at(sec(1 + t)).kill_random()
            sc.at(sec(1 + t) + _ms(400)).restart_random()
            sc.at(sec(1 + t) + _ms(600)).partition([t % 5, (t + 1) % 5])
            sc.at(sec(1 + t) + _ms(900)).heal()
        rt = make_raft_runtime(5, log_capacity=32, n_cmds=24, scenario=sc,
                               cfg=cfg)
        runner = rt._run_chunk[False]
        for B in (2048, 4096, 8192, 16384):
            state = rt.init_batch(np.arange(B))
            state, _ = runner(state, steps)      # warm (same chunk length)
            jax.block_until_ready(state.now)
            state = rt.init_batch(np.arange(B))
            t0 = time.perf_counter()
            state, _ = runner(state, steps)
            jax.block_until_ready(state.now)
            eps = B * steps / (time.perf_counter() - t0)
            print(json.dumps({"metric": "sweep", "batch": B, "capacity": C,
                              "seed_events_per_sec": round(eps, 1)}))


_MULTIHOST_WORKER = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address=sys.argv[2],
                           num_processes=2, process_id=pid)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import numpy as np
from bench import _make_runtime
from madsim_tpu.parallel.distributed import host_seed_slice, shard_global

B_GLOBAL, STEPS = 1024, 256
rt = _make_runtime()
runner = rt._run_chunk[False]
state = shard_global(rt, host_seed_slice(B_GLOBAL))
state, _ = runner(state, STEPS)                      # warm/compile
jax.block_until_ready(state.now)
state = shard_global(rt, host_seed_slice(B_GLOBAL))
# barrier so both processes time the same region
jax.block_until_ready(jax.jit(lambda s: s.halted.any())(state))
t0 = time.perf_counter()
state, _ = runner(state, STEPS)
halted_any = bool(jax.jit(lambda s: s.halted.any())(state))  # DCN reduction
dt = time.perf_counter() - t0
print(f"RESULT pid={pid} wall={dt:.4f} halted_any={halted_any}", flush=True)
"""


def _shardkv_mode(emit=True):
    """--shardkv: batched throughput of the multi-group ShardKV model
    (config service + 2 kv raft groups + clients, live shard migration)
    on the default platform. A second per-workload datapoint beyond the
    flagship Raft chaos bench — heavier per event (4 programs, 11 nodes,
    migration machinery), so absolute seed-events/s is expected below the
    flagship's."""
    _require_chip("--shardkv")
    from madsim_tpu.core.types import SimConfig, NetConfig, ms, sec
    from madsim_tpu.models.shard_kv import make_shard_runtime

    B, steps = 1024, 512

    def make():
        # n_ops sized so client work outlasts warm+timed chunks (one
        # event per step per lane; each op costs ~10 events), log sized
        # to hold it all, virtual time uncapped for the bench horizon —
        # the shared timing helper asserts no crash/overflow/idling
        cfg = SimConfig(n_nodes=11, event_capacity=160, payload_words=12,
                        time_limit=sec(600),
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(10)))
        return make_shard_runtime(n_groups=2, rg=3, rc=3, n_clients=2,
                                  n_ops=64, max_cfg=8, log_capacity=192,
                                  cfg=cfg)

    eps = _events_per_sec(B, steps, WARM, make=make)
    out = {
        "metric": "shardkv_migration_seed_events_per_sec",
        "value": round(eps, 1),
        "unit": "seed*events/s (2 kv groups + config group, live shard "
                "migration)",
        "batch": B,
    }
    if emit:
        print(json.dumps(out))
    return out


def _minipg_mode(emit=True):
    """--minipg: batched throughput of the minipg session protocol
    (startup/auth handshake + pipelined transactions) over the full sim
    TCP stack (conn lifecycle + reliable streams). Stream machinery makes
    each protocol step cost several events, so absolute seed-events/s
    lands well below the flagship's."""
    _require_chip("--minipg")
    from madsim_tpu.core.types import SimConfig, NetConfig, ms, sec
    from madsim_tpu.models.minipg import make_minipg_runtime

    B, steps = 2048, 512

    def make():
        # n_txns sized so client work outlasts warm+timed chunks; the
        # shared timing helper asserts no crash/overflow/idling
        cfg = SimConfig(n_nodes=3, event_capacity=96, payload_words=8,
                        time_limit=sec(600),
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(8)))
        return make_minipg_runtime(n_clients=2, n_txns=64, cfg=cfg)

    eps = _events_per_sec(B, steps, WARM, make=make)
    out = {
        "metric": "minipg_sessions_seed_events_per_sec",
        "value": round(eps, 1),
        "unit": "seed*events/s (pg-style sessions over sim TCP streams)",
        "batch": B,
    }
    if emit:
        print(json.dumps(out))
    return out


def _ministream_mode(emit=True):
    """--ministream: batched throughput of the streaming-dataflow model
    (epoch barriers, upstream replay, exactly-once commits) under loss +
    mapper chaos — the fourth per-workload datapoint."""
    _require_chip("--ministream")
    from madsim_tpu import Scenario, ms
    from madsim_tpu.models.ministream import (MAP_A, MAP_B,
                                              make_ministream_runtime)

    B, steps = 2048, 512

    def make():
        sc = Scenario()
        for t in range(3):
            sc.at(ms(300 + 700 * t)).kill_random(among=(MAP_A, MAP_B))
            sc.at(ms(600 + 700 * t)).restart_random(among=(MAP_A, MAP_B))
        return make_ministream_runtime(k=8, epochs=64, scenario=sc)

    eps = _events_per_sec(B, steps, WARM, make=make)
    out = {
        "metric": "ministream_barrier_seed_events_per_sec",
        "value": round(eps, 1),
        "unit": "seed*events/s (epoch barriers + exactly-once commits "
                "under mapper chaos)",
        "batch": B,
    }
    if emit:
        print(json.dumps(out))
    return out


def _all_mode():
    """--all: one combined JSON with every workload's batched number on
    the current default platform (flagship raft chaos, shardkv migration,
    minipg sessions, ministream barriers) on the chip."""
    dev = _require_chip("--all")
    combined = {"metric": "bench_all", "device": dev, "workloads": {}}
    for name, fn in (
            ("madraft_fuzz", lambda: {"value": round(
                _events_per_sec(B_TPU, STEPS, WARM), 1), "batch": B_TPU}),
            ("shardkv_migration", lambda: _shardkv_mode(emit=False)),
            ("minipg_sessions", lambda: _minipg_mode(emit=False)),
            ("ministream_barriers", lambda: _ministream_mode(emit=False))):
        combined["workloads"][name] = fn()
        print(f"--all: {name} done", file=sys.stderr)
    print(json.dumps(combined))


def _sched_ab_mode():
    """--sched-ab: A/B the value-invisible engine lowering knobs on the
    flagship workload, same platform/batch: int16 vs int32 table columns
    and one-hot vs scatter emission writes (both bit-identical in
    results — pure bandwidth/lowering levers, DESIGN §5). The flag name
    predates the r5 removal of the fused Pallas scheduler (cut: three
    rounds with no on-hardware justification and a roofline that says a
    select-only kernel cannot pay; the watcher chain still invokes this
    mode by the old name, and on-chip rows for THESE knobs are the data
    the next TPU session wants)."""
    _require_chip("--sched-ab")
    import jax
    platform = jax.devices()[0].platform
    out = {"metric": "engine_knob_ab", "platform": platform, "batch": B_TPU,
           "variants": {}}
    for emw in ("onehot", "scatter"):
        for dtype in ("int32", "int16"):
            name = f"{emw}/{dtype}"
            try:
                eps = _events_per_sec(
                    B_TPU, STEPS, WARM,
                    make=lambda: _make_runtime(table_dtype=dtype,
                                               emission_write=emw))
                out["variants"][name] = round(eps, 1)
                print(f"--sched-ab: {name} {eps:,.0f} seed-events/s",
                      file=sys.stderr)
            except Exception as e:  # noqa: BLE001 - partial evidence > none
                out["variants"][name] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))


def _make_light_runtime(n_nodes=2, loss=0.0, trace_cap=0, sketch_slots=0,
                        profile=False, latency_hist=0, series_windows=0,
                        span_attr=False):
    """A deliberately tiny workload (2-node ping-pong, C=16, P=2, stats
    off) for the fused A/B: per-step device compute is small, so the
    per-chunk host round-trip the chunked runner pays
    (`bool(halted.all())` + dispatch) is VISIBLE in the measurement
    instead of vanishing under model compute. The target is unreachable,
    so lanes never halt and both runners execute exactly the same step
    count. The same smallness makes it the worst case for the flight
    recorder's relative overhead (--mode obs_ab): the ring write is a
    fixed per-step cost, so a tiny step magnifies it."""
    from madsim_tpu import Runtime, SimConfig, NetConfig, ms, sec
    from madsim_tpu.core.types import EV_MSG
    from madsim_tpu.models.pingpong import PingPong, state_spec
    cfg = SimConfig(n_nodes=n_nodes, event_capacity=16, payload_words=2,
                    time_limit=sec(590), collect_stats=False,
                    trace_cap=trace_cap, sketch_slots=sketch_slots,
                    profile=profile, latency_hist=latency_hist,
                    series_windows=series_windows,
                    # span_attr rides the latency plane's complete_kinds
                    # below — callers pass latency_hist>0 alongside it
                    span_attr=span_attr,
                    # ping deliveries as completions so the lat_ab
                    # variants pay the e2e fold, not just the sojourn
                    complete_kinds=(((EV_MSG, 1),) if latency_hist
                                    else ()),
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [PingPong(n_nodes, target=1 << 30)], state_spec())


def _fused_ab_mode():
    """--mode fused_ab: A/B the host/device boundary disciplines on one
    workload — chunked `run()` (a device→host sync per chunk) vs fused
    `run_fused` (one XLA dispatch, on-device halt predicate) vs the
    pipelined fused `explore()` (round r+1 dispatched before round r's
    harvest). Sweeps chunk granularity: at fine granularity (fast
    early-exit response) the chunked runner pays max_steps/chunk
    round-trips and fused pays zero — that gap is the measurement. At
    coarse granularity the two converge, which the matrix shows honestly.
    Writes BENCH_fused_ab_<platform>.json next to this file."""
    _require_chip("--fused-ab")
    import jax
    platform = jax.devices()[0].platform
    steps, reps = 1024, 3
    out = {"metric": "fused_ab", "platform": platform, "steps": steps,
           "reps": reps,
           "note": ("tiny 2-node workload so the per-chunk host sync is "
                    "visible against device compute; lanes never halt, "
                    "so both runners execute identical step counts; "
                    "min-of-reps per cell. chunk = halt-check "
                    "granularity: at chunk 1-2 (fast early-exit "
                    "response) the chunked runner pays steps/chunk host "
                    "round-trips and fused pays zero; at coarse chunk "
                    "the two converge on CPU where compute dominates"),
           "configs": [], "explore": {}}
    best = 0.0
    for B, chunks in ((512, (1, 2, 8, 64)), (1024, (1, 2))):
        rt = _make_light_runtime()
        seeds = np.arange(B)
        for chunk in chunks:
            # warm both paths at this exact static chunk length
            rt.run(rt.init_batch(seeds), 2 * chunk, chunk)
            jax.block_until_ready(
                rt.run_fused(rt.init_batch(seeds), 2 * chunk, chunk).now)
            dt_chunked, dt_fused = [], []
            for _ in range(reps):
                state = rt.init_batch(seeds)
                jax.block_until_ready(state.now)
                t0 = time.perf_counter()
                final, _ = rt.run(state, steps, chunk)
                jax.block_until_ready(final.now)
                dt_chunked.append(time.perf_counter() - t0)
                assert not bool(np.asarray(final.halted).any()), \
                    "A/B lanes must stay live"

                state = rt.init_batch(seeds)
                jax.block_until_ready(state.now)
                t0 = time.perf_counter()
                final = rt.run_fused(state, steps, chunk)
                jax.block_until_ready(final.now)
                dt_fused.append(time.perf_counter() - t0)

            ev, dc, df = B * steps, min(dt_chunked), min(dt_fused)
            row = {"batch": B, "chunk": chunk,
                   "chunked_events_per_sec": round(ev / dc, 1),
                   "fused_events_per_sec": round(ev / df, 1),
                   "fused_vs_chunked": round(dc / df, 3)}
            out["configs"].append(row)
            best = max(best, row["fused_vs_chunked"])
            print(f"--fused-ab: B={B} chunk={chunk} "
                  f"chunked {ev/dc:,.0f} ev/s, fused {ev/df:,.0f} ev/s "
                  f"({dc/df:.2f}x)", file=sys.stderr)
    out["fused_vs_chunked_best_at_batch_ge_512"] = round(best, 3)

    # pipelined explore: same rounds of device work on both sides
    # (dry_rounds > max_rounds disables the dry-stop, and the workload
    # has loss-driven schedule diversity so rounds never go dry anyway)
    from madsim_tpu.parallel.explore import explore
    ex_kw = dict(max_steps=1024, batch=512, max_rounds=6, dry_rounds=7,
                 chunk=64)
    rt = _make_light_runtime(n_nodes=4, loss=0.05)
    # warm BOTH runners + the coverage-digest jit before any timed region
    explore(rt, pipeline=False, fused=False, **dict(ex_kw, max_rounds=1))
    explore(rt, pipeline=False, fused=True, **dict(ex_kw, max_rounds=1))
    ev = ex_kw["max_rounds"] * ex_kw["batch"] * ex_kw["max_steps"]
    variants = {}
    for name, kw in (("serial_chunked", dict(pipeline=False, fused=False)),
                     ("serial_fused", dict(pipeline=False, fused=True)),
                     ("pipelined_fused", dict(pipeline=True, fused=True))):
        t0 = time.perf_counter()
        res = explore(rt, **ex_kw, **kw)
        dt = time.perf_counter() - t0
        assert res["rounds"] == ex_kw["max_rounds"], res
        variants[name] = round(ev / dt, 1)
        print(f"--fused-ab: explore/{name} {ev/dt:,.0f} ev/s",
              file=sys.stderr)
    if variants.get("serial_chunked"):
        variants["pipelined_vs_serial_chunked"] = round(
            variants["pipelined_fused"] / variants["serial_chunked"], 3)
    variants["note"] = (
        "pipelining overlaps host dedup with device compute; on a 1-core "
        "CPU host there is nothing to overlap with, so parity (within "
        "single-rep noise) is the expected result here — the overlap win "
        "needs a real accelerator, where device compute proceeds while "
        "the host dedups")
    out["explore"] = variants

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_fused_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _obs_ab_mode():
    """--mode obs_ab: flight-recorder overhead A/B on the fused runner
    (the path the ring exists for — a while_loop sweep had no other way
    to come back with traces). Four builds of the same tiny workload,
    identical trajectories by construction (the ring write consumes no
    randomness):

      off          trace_cap=0 — recorder compiled out (baseline)
      ring_masked  trace_cap=64 compiled in, NO lanes sampled — the cost
                   of carrying the ring state + masked-off writes
      ring_8       trace_cap=64, 8 of B lanes sampled — the intended
                   production shape (record a handful of lanes at full
                   sweep scale)
      ring_all     trace_cap=64, every lane samples — the ceiling

    The acceptance bar is overhead_off-lane <= 5% at B=512: enabling the
    recorder build without sampling must be ~free, so runtimes can ship
    with trace_cap > 0 and flip lanes on per-sweep. min-of-reps per
    cell; writes BENCH_obs_ab_<platform>.json next to this file."""
    _require_chip("--obs-ab")
    import jax
    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 9
    variants = (("off", 0, None), ("ring_masked", 64, []),
                ("ring_8", 64, list(range(8))), ("ring_all", 64, None))
    out = {"metric": "obs_ab", "platform": platform, "batch": B,
           "steps": steps, "chunk": chunk, "reps": reps, "trace_cap": 64,
           "note": ("tiny 2-node workload = worst case for relative ring "
                    "overhead (fixed per-step write vs tiny step); fused "
                    "runner, lanes never halt, so every variant executes "
                    "identical step counts; reps are INTERLEAVED "
                    "round-robin so slow machine drift hits every variant "
                    "equally, min-of-reps per variant. The three ring "
                    "builds execute identical compute (a masked write "
                    "runs whether the mask is on or off), so spread "
                    "among them is the noise floor of the measurement"),
           "variants": {}}
    seeds = np.arange(B)
    # one Runtime per distinct trace_cap: the three ring variants differ
    # only in the init_batch sampling mask (a runtime argument), so they
    # share one compiled fused program — the warmup pays two compiles
    # (cap=0, cap=64), not four
    by_cap = {cap: _make_light_runtime(trace_cap=cap)
              for cap in {c for _, c, _ in variants}}
    rts, kws = {}, {}
    for name, cap, lanes in variants:
        rts[name] = by_cap[cap]
        kws[name] = ({} if cap == 0 or lanes is None
                     else {"trace_lanes": lanes})
    for cap, rt in by_cap.items():
        jax.block_until_ready(
            rt.run_fused(rt.init_batch(seeds), steps, chunk).now)
    best = {name: float("inf") for name, _, _ in variants}
    for _ in range(reps):
        for name, _, _ in variants:
            state = rts[name].init_batch(seeds, **kws[name])
            jax.block_until_ready(state.now)
            t0 = time.perf_counter()
            final = rts[name].run_fused(state, steps, chunk)
            jax.block_until_ready(final.now)
            best[name] = min(best[name], time.perf_counter() - t0)
    eps = {name: B * steps / b for name, b in best.items()}
    for name, _, _ in variants:
        out["variants"][name] = round(eps[name], 1)
        print(f"--obs-ab: {name} {eps[name]:,.0f} seed-events/s",
              file=sys.stderr)
    for name in ("ring_masked", "ring_8", "ring_all"):
        out[f"overhead_{name}"] = round(eps["off"] / eps[name] - 1, 4)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_obs_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _make_saturating_runtime(target=6, trace_cap=0, sketch_slots=0):
    """A chaos workload whose schedule space SEEDS ALONE exhaust quickly
    (fixed latency, no loss, random kill/restart): the regime where blind
    explore() goes dry and the fuzzer's knob mutations are the only way to
    keep coverage growing. The flagship Raft chaos workload is the other
    regime — randomized election timeouts put every seed on a distinct
    schedule, so blind sampling is already at the per-lane ceiling there
    and the A/B shows parity (the hash cannot count past one distinct
    schedule per lane). The single definition of this regime — the search
    tests and examples/fuzz_search.py import it rather than re-declare."""
    from madsim_tpu import Runtime, Scenario, SimConfig, NetConfig, ms, sec
    from madsim_tpu.models.pingpong import PingPong, state_spec
    sc = Scenario()
    sc.at(ms(40)).kill_random()
    sc.at(ms(400)).restart_random()
    cfg = SimConfig(n_nodes=4, time_limit=sec(5),
                    trace_cap=trace_cap, sketch_slots=sketch_slots,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(1)))
    return Runtime(cfg, [PingPong(4, target=target)], state_spec(),
                   scenario=sc)


def _make_crashrich_runtime(kind="wal_kv", trace_cap=0, sketch_slots=0,
                            profile=False):
    """Crash-RICH flagship targets for --mode search_ab / --causal-smoke
    (ROADMAP r9 open item): green Raft's randomized election timeouts
    saturate the schedule ceiling but rarely crash, so its
    crash-codes-per-device-second was a near-zero metric. These two do
    crash under their chaos matrices, making that rate meaningful:

      wal_kv  sync_wal=False under a kill/restart matrix on the server —
              unsynced WAL writes are REALLY lost across each crash, so
              acked-then-lost updates trip the client's read-your-writes
              checks (the fs.py power-fail contract doing its job)
      chain   chain replication with random replica kills/restarts —
              lease expiry, stale-chain reads and re-replication races
              trip the chain invariant
    """
    from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
    sc = Scenario()
    if kind == "wal_kv":
        from madsim_tpu.models.wal_kv import make_wal_kv_runtime
        for t in range(6):
            sc.at(ms(150) + ms(250) * t).kill(0)
            sc.at(ms(210) + ms(250) * t).restart(0)
        cfg = SimConfig(n_nodes=3, event_capacity=256, payload_words=8,
                        time_limit=sec(10), trace_cap=trace_cap,
                        sketch_slots=sketch_slots, profile=profile,
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(8)))
        return make_wal_kv_runtime(n_clients=2, n_ops=12, wal_cap=64,
                                   sync_wal=False, scenario=sc, cfg=cfg)
    assert kind == "chain", kind
    from madsim_tpu.models.chain import make_chain_runtime
    replicas = (1, 2, 3)              # nodes: 0 master, 1-3 replicas
    for t in range(4):
        sc.at(ms(200) + ms(400) * t).kill_random(among=replicas)
        sc.at(ms(330) + ms(400) * t).restart_random(among=replicas)
    cfg = SimConfig(n_nodes=6, event_capacity=384, payload_words=12,
                    time_limit=sec(10), trace_cap=trace_cap,
                    sketch_slots=sketch_slots, profile=profile,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(8)))
    return make_chain_runtime(n_replicas=3, n_clients=2, n_ops=10,
                              scenario=sc, cfg=cfg)


def _make_racy_runtime(trace_cap=256, sketch_slots=0):
    """The RACE-rich flagship mutant for --analyze-smoke /
    tests/test_analyze.py (one canonical definition, same convention as
    the crashrich/saturating workloads): the crash-rich wal_kv matrix
    (sync_wal=False under server kill/restart) with FIXED send latency.
    Randomized latency spreads message arrivals across distinct ticks,
    so the scheduler rarely faces a tie and the PCT nudge has nothing
    to commute; pinning min==max makes concurrent client requests land
    on the server at the SAME virtual instant — exactly the unordered
    same-node dispatch pairs analyze/races.py hunts, in a workload
    whose outcome (which unsynced write is lost) genuinely depends on
    their order."""
    from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
    from madsim_tpu.models.wal_kv import make_wal_kv_runtime
    sc = Scenario()
    for t in range(6):
        sc.at(ms(150) + ms(250) * t).kill(0)
        sc.at(ms(210) + ms(250) * t).restart(0)
    cfg = SimConfig(n_nodes=3, event_capacity=256, payload_words=8,
                    time_limit=sec(10), trace_cap=trace_cap,
                    sketch_slots=sketch_slots,
                    net=NetConfig(send_latency_min=ms(2),
                                  send_latency_max=ms(2)))
    return make_wal_kv_runtime(n_clients=2, n_ops=12, wal_cap=64,
                               sync_wal=False, scenario=sc, cfg=cfg)


def _make_grayfail_runtime(recipe="mix", trace_cap=128, n_ops=12):
    """The gray-failure flagship targets (r17, DESIGN §18): Percolator-
    lite (models/percolator.py) under the chaos recipes whose fault
    shapes its snapshot-isolation oracle is built to catch. One
    canonical definition — --grayfail-smoke, --regression-smoke, the
    search_ab grayfail regime, and tests/test_grayfail.py import it.

      mix    all four families composed on one knob plane (the fuzz
             regime: asym cut, two drifting clocks, a slow disk, a torn
             kill — every row/value/direction mutable); group-commit
             (sync_commits=False) so kills are crash-rich
      skew   fast clocks on both shards + fat latency — skewed lease
             expiry rolls back live locks (CRASH_SNAPSHOT)
      asym   inbound one-way cut to shard 1 — lazy secondary commits
             vanish while everything else flows
      disk   slow disk on shard 0 — commit acks outrun the client
             timeout, rollback races the committed primary
      torn   torn-write kill of shard 1 under group commit — recovery
             sees a partially-written final record
    """
    from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
    from madsim_tpu.models.percolator import make_percolator_runtime
    from madsim_tpu.runtime import chaos
    sc = Scenario()
    sync = True
    if recipe == "mix":
        sync = False
        sc.at(ms(5)).set_latency(ms(8), ms(25))
        sc = chaos.clock_drift(ms(20), 400, node=0, until=ms(900), sc=sc)
        sc = chaos.clock_drift(ms(30), -350, node=1, until=ms(900), sc=sc)
        sc = chaos.asymmetric_partition(ms(150), [1], ms(250),
                                        direction=1, sc=sc)
        sc = chaos.slow_disk(ms(350), ms(20), ms(600), node=0, sc=sc)
        sc = chaos.torn_write_kill(ms(650), 1, down=ms(120), sc=sc)
    elif recipe == "skew":
        sc.at(ms(5)).set_latency(ms(15), ms(35))
        sc = chaos.clock_drift(ms(10), 480, node=0, sc=sc)
        sc = chaos.clock_drift(ms(10), 480, node=1, sc=sc)
    elif recipe == "asym":
        sc = chaos.asymmetric_partition(ms(150), [1], ms(300),
                                        direction=1, sc=sc)
    elif recipe == "disk":
        sc = chaos.slow_disk(ms(100), ms(20), ms(700), node=0, sc=sc)
    else:
        assert recipe == "torn", recipe
        sync = False
        sc = chaos.torn_write_kill(ms(150), 1, down=ms(100), sc=sc)
    cfg = SimConfig(n_nodes=5, event_capacity=256, payload_words=8,
                    time_limit=sec(10), trace_cap=trace_cap,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(8)))
    return make_percolator_runtime(n_clients=3, n_ops=n_ops,
                                   sync_commits=sync, scenario=sc, cfg=cfg)


def _make_connfault_runtime(recipe="mix", trace_cap=128, n_txns=6,
                            guard=None):
    """The connection-fault flagship targets (r19, DESIGN §20): minipg —
    pipelined exactly-once transactions over the full conn+stream stack —
    under the chaos recipes whose fault shapes its client-side oracles
    catch. One canonical definition — --conn-smoke, --regression-smoke,
    the search_ab connfault regime, and tests/test_connfault.py import
    it.

      mix    reset storm on the server + dup storms on every node (the
             fuzz regime: every row/target/rate mutable).
             GUARDS OFF by default here — minipg with incarnation guards
             is designed to survive this regime, so the crash-rich
             search target is the pre-r19 transport (the honest control
             that proves the guard; pass guard=True for the green side)
      reset  conn_reset_storm alone (guards on — the recovery regime)
      dup    retransmit_storm alone (guards on — transport dedup regime)
      half   half_open_churn: kill/restart leaves survivors half-open,
             a trailing reset-peer pulse finally tears both sides
    """
    from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
    from madsim_tpu.models.minipg import make_minipg_runtime
    from madsim_tpu.runtime import chaos
    sc = Scenario()
    if guard is None:
        guard = recipe != "mix"
    if recipe == "mix":
        # no latency-fattening row: a fatter floor drains the windows by
        # the reset instants and the stale-segment overlap vanishes (the
        # latency knobs stay mutable through latency_perturb regardless)
        for n in range(3):
            sc.at(ms(8)).set_dup(n, 0.35)
        sc = chaos.conn_reset_storm(rounds=5, first=ms(30), period=ms(60),
                                    node=0, sc=sc)
        sc = chaos.retransmit_storm(ms(400), 0.5, ms(900), node=0, sc=sc)
    elif recipe == "reset":
        sc = chaos.conn_reset_storm(rounds=5, first=ms(30), period=ms(60),
                                    node=0, sc=sc)
    elif recipe == "dup":
        for n in range(3):
            sc = chaos.retransmit_storm(ms(5), 0.4, ms(800), node=n, sc=sc)
    else:
        assert recipe == "half", recipe
        sc = chaos.half_open_churn(0, rounds=2, first=ms(60),
                                   period=ms(400), down=ms(100), sc=sc)
    cfg = SimConfig(n_nodes=3, event_capacity=192, payload_words=8,
                    time_limit=sec(10), trace_cap=trace_cap,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(8)))
    return make_minipg_runtime(n_clients=2, n_txns=n_txns, scenario=sc,
                               cfg=cfg, epoch_guard=guard)


def _make_recovery_runtime(recipe="heal", invariant=None, target=400):
    """The recovery-oracle flagship targets (r21, DESIGN §22): rpc_echo
    with the latency + series planes on, under fault scripts whose
    timeline shape `harness.recovery_invariant` judges. One canonical
    definition — --series-smoke, the series_ab burst-energy A/B, and
    tests/test_series.py import it.

      heal    clog the server at 1.2s, unclog at 2.6s — the cure is
              OP_UNCLOG (SRF_HEAL, which does NOT restart the recovery
              clock), so the post-heal windows are GENUINELY judged and
              green. The fuzz regime: mutants that move the unclog out
              of the timeline, fatten the recovered floor, or re-clog
              late fail to return to envelope -> CRASH_RECOVERY
      noheal  fatten the network at 1.2s (set_latency: SRF_NET) and
              never recover — every judged window past the grace
              period stays degraded, the oracle fires deterministically
              at the first judged window boundary

    Window arithmetic the recipes lean on: window_len=625ms x W=8
    covers the 5s timeline (time_limit 6s; the tail clamps into w7).
    The fault lands in w1, so within=4 starts judging at w5 — past the
    heal recipe's recovery spike in w4 (pent-up retries complete with
    e2e ~= the clog span; root_kinds can't re-mint while the server is
    dark). target=400 echoes/client keeps lanes alive past w7's
    completion (5s) and halts them before the 6s limit, so green lanes
    judge w5-w7 non-vacuously."""
    from madsim_tpu import (NetConfig, Runtime, Scenario, SimConfig, ms,
                            sec)
    from madsim_tpu.core.types import EV_MSG
    from madsim_tpu.models.rpc_echo import TAG_ECHO, make_echo_runtime
    from madsim_tpu.net import rpc
    rtag = rpc.reply_tag(TAG_ECHO)
    sc = Scenario()
    if recipe == "heal":
        sc.at(ms(1200)).clog_node(0)
        sc.at(ms(2600)).unclog_node(0)
    else:
        assert recipe == "noheal", recipe
        sc.at(ms(1200)).set_latency(ms(30), ms(60))
    cfg = SimConfig(n_nodes=4, event_capacity=64, time_limit=sec(6),
                    latency_hist=24, trace_cap=512,
                    series_windows=8, window_len=ms(625),
                    complete_kinds=((EV_MSG, rtag),),
                    root_kinds=((EV_MSG, rtag),),
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(8)))
    rt = make_echo_runtime(n_nodes=4, target=target, scenario=sc, cfg=cfg)
    if invariant is not None:
        rt = Runtime(cfg, rt.programs, rt.state_spec,
                     node_prog=rt.node_prog, scenario=sc,
                     invariant=invariant, halt_when=rt._halt_when)
    return rt


def _search_ab_mode():
    """--mode search_ab: coverage-guided fuzzer vs blind explore() at
    EQUAL device-dispatch budget (same rounds x batch x max_steps), on
    both regimes:

      saturating   fixed-latency chaos — blind seed sampling exhausts the
                   fixed script's schedule space in one round; the fuzzer
                   keeps growing coverage by mutating the script itself
                   (times/targets/dups), the network knobs, and the PCT
                   tie-break policy. The fuzzer's distinct-schedule count
                   must beat blind's STRICTLY here.
      flagship     the 5-node Raft chaos fuzz at B=512 — randomized
                   election timeouts put every seed on a distinct
                   schedule, so BOTH sides sit at the per-lane ceiling
                   (parity is the honest expectation; the artifact
                   records it) and the comparison is rate + crash codes.
      crashrich_*  (r10) wal_kv lost-write and chain lease chaos matrices
                   (_make_crashrich_runtime) — flagship protocols that DO
                   crash under their chaos, so crash_codes_per_device_sec
                   is a meaningful fuzzer metric (the r9 open item; green
                   Raft's crash rate was near-zero by design).

    Reports distinct schedules and distinct crash codes per device-second
    for each side. Writes BENCH_search_ab_<platform>.json.

    `--shards N` (r13) grows a mesh axis: the fuzzer side runs the
    mesh-sharded campaign driver (search/shard.py) over N devices at
    batch/N lanes per shard — total budget stays equal to blind's. This
    variant forces the virtual CPU mesh up front, so its numbers are CPU
    numbers (its output says platform "cpu"); batch must divide by N."""
    regime_filter = None
    if "--regime" in sys.argv:
        regime_filter = sys.argv[sys.argv.index("--regime") + 1]
        known = ("saturating", "flagship_raft_chaos", "crashrich_wal_kv",
                 "crashrich_chain", "grayfail", "connfault")
        if not any(n == regime_filter or n.startswith(regime_filter)
                   for n in known):
            # a typo must not run zero regimes, write no artifact, and
            # exit green
            sys.exit(f"unknown --regime {regime_filter!r} "
                     f"(known: {list(known)} or a prefix)")

    def want(name):
        return (regime_filter is None or name == regime_filter
                or name.startswith(regime_filter))

    shards = 1
    if "--shards" in sys.argv:
        shards = int(sys.argv[sys.argv.index("--shards") + 1])
    if shards > 1:
        # the mesh must exist before jax's backend initializes; this
        # forces the host platform (the CPU-mesh variant of the mode)
        _force_cpu_mesh_bench(shards)
    else:
        _require_chip("--search-ab")
    import jax
    from madsim_tpu import explore, fuzz, fuzz_sharded
    platform = jax.devices()[0].platform
    out = {"metric": "search_ab", "platform": platform, "shards": shards,
           "note": ("equal budget = same rounds x batch x max_steps per "
                    "side. In the saturating regime blind explore() goes "
                    "dry after round 0 and the fuzzer must beat it "
                    "STRICTLY; on the flagship, randomized election "
                    "timeouts already put every seed on a distinct "
                    "schedule, so both sides sit at the per-lane ceiling "
                    "(distinct == seeds_run) and parity is the honest "
                    "expectation — the fuzzer's job there is matching the "
                    "ceiling while also searching crash space. Fuzzer "
                    "wall includes mutation+corpus host work, which a "
                    "1-core CPU host cannot overlap with device compute "
                    "(the pipelined loop overlaps it on a real "
                    "accelerator)"),
           "regimes": {}}

    def ab(name, make, rounds, batch, steps, chunk):
        row = {"rounds": rounds, "batch": batch, "max_steps": steps}
        if shards > 1:
            assert batch % shards == 0, (batch, shards)

        def run_fuzzer(rt):
            if shards == 1:
                return fuzz(rt, max_steps=steps, batch=batch,
                            max_rounds=rounds, dry_rounds=rounds + 1,
                            chunk=chunk)
            return fuzz_sharded(rt, max_steps=steps,
                                batch=batch // shards, shards=shards,
                                max_rounds=rounds, dry_rounds=rounds + 1,
                                chunk=chunk)

        # warm both sides' executables outside the timed region
        warm = make()
        explore(warm, max_steps=steps, batch=batch, max_rounds=1,
                dry_rounds=2, chunk=chunk)
        if shards == 1:
            fuzz(warm, max_steps=steps, batch=batch, max_rounds=2,
                 dry_rounds=3, chunk=chunk)
        else:
            fuzz_sharded(warm, max_steps=steps, batch=batch // shards,
                         shards=shards, max_rounds=2, dry_rounds=3,
                         chunk=chunk)
        for side, run in (
                ("blind", lambda rt: explore(
                    rt, max_steps=steps, batch=batch, max_rounds=rounds,
                    dry_rounds=rounds + 1, chunk=chunk)),
                ("fuzzer", run_fuzzer)):
            rt = make()
            t0 = time.perf_counter()
            res = run(rt)
            dt = time.perf_counter() - t0
            # fuzz() restricts crash_first_seed_by_code to seed-alone
            # handles (bootstrap lanes); crash_repros has every code
            codes = res.get("crash_repros",
                            res["crash_first_seed_by_code"])
            row[side] = {
                "distinct_schedules": res["distinct_schedules"],
                "distinct_crash_codes": len(codes),
                "wall_s": round(dt, 2),
                "schedules_per_device_sec": round(
                    res["distinct_schedules"] / dt, 1),
                # meaningful on the crash-rich regimes (the r9 open
                # item); near-zero on green Raft by design
                "crash_codes_per_device_sec": round(len(codes) / dt, 3),
                "new_per_round": res["new_per_round"],
            }
            print(f"--search-ab: {name}/{side} "
                  f"{res['distinct_schedules']} schedules, "
                  f"{len(codes)} crash codes, "
                  f"{dt:.1f}s", file=sys.stderr)
        row["fuzzer_vs_blind_schedules"] = round(
            row["fuzzer"]["distinct_schedules"]
            / max(row["blind"]["distinct_schedules"], 1), 2)
        out["regimes"][name] = row

    if want("saturating"):
        ab("saturating", _make_saturating_runtime,
           rounds=6, batch=128, steps=1500, chunk=256)
    big = platform != "cpu"
    if want("flagship_raft_chaos"):
        ab("flagship_raft_chaos", _make_runtime,
           rounds=3, batch=512 if big else 256,
           steps=1024 if big else 512, chunk=256)
    # crash-RICH flagships (the r9 open item): wal_kv lost-write and
    # chain lease/ordering crashes make crash_codes_per_device_sec a
    # real comparison instead of green Raft's near-zero
    for kind, steps_cr in (("wal_kv", 4096), ("chain", 3072)):
        if want(f"crashrich_{kind}"):
            ab(f"crashrich_{kind}",
               functools.partial(_make_crashrich_runtime, kind),
               rounds=3, batch=128 if big else 64, steps=steps_cr,
               chunk=512)
    if want("grayfail"):
        # the r17 gray-failure regime: fuzzer vs blind on the
        # Percolator-lite flagship under the composed fault mix. The
        # fuzzer side runs DURABLY (a throwaway corpus dir) so crashes
        # dedup into causal-fingerprint buckets — buckets per
        # device-second is the regime's headline; blind explore() has
        # no bucket machinery, so its distinct CRASH CODES stand in as
        # the (coarser) lower bound, noted in the artifact.
        import shutil
        import tempfile
        rounds_g, batch_g, steps_g = 4, 128 if big else 96, 20_000
        row = {"rounds": rounds_g, "batch": batch_g, "max_steps": steps_g,
               "note": ("fuzzer side is a durable campaign: crashes "
                        "dedup by causal fingerprint into buckets; "
                        "blind has no bucket machinery — its "
                        "distinct_crash_codes is the coarser stand-in")}
        warm = _make_grayfail_runtime("mix")
        explore(warm, max_steps=steps_g, batch=batch_g, max_rounds=1,
                dry_rounds=2, chunk=512)
        fuzz(warm, max_steps=steps_g, batch=batch_g, max_rounds=2,
             dry_rounds=3, chunk=512)
        rt_b = _make_grayfail_runtime("mix")
        t0 = time.perf_counter()
        res_b = explore(rt_b, max_steps=steps_g, batch=batch_g,
                        max_rounds=rounds_g, dry_rounds=rounds_g + 1,
                        chunk=512)
        dt_b = time.perf_counter() - t0
        row["blind"] = {
            "distinct_schedules": res_b["distinct_schedules"],
            "distinct_crash_codes": len(res_b["crash_first_seed_by_code"]),
            "wall_s": round(dt_b, 2),
            "schedules_per_device_sec": round(
                res_b["distinct_schedules"] / dt_b, 1)}
        tmp = tempfile.mkdtemp(prefix="grayfail_ab_")
        try:
            rt_f = _make_grayfail_runtime("mix")
            t0 = time.perf_counter()
            res_f = fuzz(rt_f, max_steps=steps_g, batch=batch_g,
                         max_rounds=rounds_g, dry_rounds=rounds_g + 1,
                         chunk=512, corpus_dir=tmp)
            dt_f = time.perf_counter() - t0
            row["fuzzer"] = {
                "distinct_schedules": res_f["distinct_schedules"],
                "distinct_crash_codes": len(res_f["crash_repros"]),
                "crash_buckets": res_f["buckets_total"],
                "wall_s": round(dt_f, 2),
                "schedules_per_device_sec": round(
                    res_f["distinct_schedules"] / dt_f, 1),
                "crash_buckets_per_device_sec": round(
                    res_f["buckets_total"] / dt_f, 3),
                "mutation_yield": res_f["mutation_yield"]}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        row["fuzzer_vs_blind_schedules"] = round(
            row["fuzzer"]["distinct_schedules"]
            / max(row["blind"]["distinct_schedules"], 1), 2)
        out["regimes"]["grayfail"] = row
        print(f"--search-ab: grayfail fuzzer "
              f"{row['fuzzer']['distinct_schedules']} schedules / "
              f"{row['fuzzer']['crash_buckets']} buckets vs blind "
              f"{row['blind']['distinct_schedules']}", file=sys.stderr)
        gpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             f"BENCH_grayfail_ab_{platform}.json")
        with open(gpath, "w") as f:
            json.dump(dict({"metric": "grayfail_ab",
                            "platform": platform, "grayfail": row},
                           measured_at=time.strftime("%F %T")), f,
                      indent=1)
    if want("connfault"):
        # the r19 connection-fault regime: fuzzer vs blind on the minipg
        # exactly-once flagship under the composed reset+dup storm with
        # the incarnation guards compiled to the pre-r19 behavior (the
        # crash-rich control — the guarded build is designed to survive
        # this recipe, which tests/test_connfault.py asserts separately).
        # Same protocol as the grayfail regime: the fuzzer side runs
        # DURABLY so crashes dedup into causal-fingerprint buckets.
        import shutil
        import tempfile
        rounds_c, batch_c, steps_c = 4, 128 if big else 96, 30_000
        row = {"rounds": rounds_c, "batch": batch_c,
               "max_steps": steps_c,
               "note": ("minipg with epoch guards OFF (pre-r19 "
                        "transport) under conn_reset_storm + "
                        "retransmit_storm; fuzzer side is a durable "
                        "campaign — crashes dedup by causal fingerprint "
                        "into buckets; blind's distinct_crash_codes is "
                        "the coarser stand-in")}
        warm = _make_connfault_runtime("mix")
        explore(warm, max_steps=steps_c, batch=batch_c, max_rounds=1,
                dry_rounds=2, chunk=512)
        fuzz(warm, max_steps=steps_c, batch=batch_c, max_rounds=2,
             dry_rounds=3, chunk=512)
        rt_b = _make_connfault_runtime("mix")
        t0 = time.perf_counter()
        res_b = explore(rt_b, max_steps=steps_c, batch=batch_c,
                        max_rounds=rounds_c, dry_rounds=rounds_c + 1,
                        chunk=512)
        dt_b = time.perf_counter() - t0
        row["blind"] = {
            "distinct_schedules": res_b["distinct_schedules"],
            "distinct_crash_codes": len(res_b["crash_first_seed_by_code"]),
            "wall_s": round(dt_b, 2),
            "schedules_per_device_sec": round(
                res_b["distinct_schedules"] / dt_b, 1)}
        tmp = tempfile.mkdtemp(prefix="connfault_ab_")
        try:
            rt_f = _make_connfault_runtime("mix")
            t0 = time.perf_counter()
            res_f = fuzz(rt_f, max_steps=steps_c, batch=batch_c,
                         max_rounds=rounds_c, dry_rounds=rounds_c + 1,
                         chunk=512, corpus_dir=tmp)
            dt_f = time.perf_counter() - t0
            row["fuzzer"] = {
                "distinct_schedules": res_f["distinct_schedules"],
                "distinct_crash_codes": len(res_f["crash_repros"]),
                "crash_buckets": res_f["buckets_total"],
                "wall_s": round(dt_f, 2),
                "schedules_per_device_sec": round(
                    res_f["distinct_schedules"] / dt_f, 1),
                "crash_buckets_per_device_sec": round(
                    res_f["buckets_total"] / dt_f, 3),
                "mutation_yield": res_f["mutation_yield"]}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        row["fuzzer_vs_blind_schedules"] = round(
            row["fuzzer"]["distinct_schedules"]
            / max(row["blind"]["distinct_schedules"], 1), 2)
        out["regimes"]["connfault"] = row
        print(f"--search-ab: connfault fuzzer "
              f"{row['fuzzer']['distinct_schedules']} schedules / "
              f"{row['fuzzer']['crash_buckets']} buckets vs blind "
              f"{row['blind']['distinct_schedules']}", file=sys.stderr)
        cpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             f"BENCH_connfault_ab_{platform}.json")
        with open(cpath, "w") as f:
            json.dump(dict({"metric": "connfault_ab",
                            "platform": platform, "connfault": row},
                           measured_at=time.strftime("%F %T")), f,
                      indent=1)
    if "saturating" in out["regimes"]:
        sat = out["regimes"]["saturating"]
        out["fuzzer_beats_blind_on_saturating"] = (
            sat["fuzzer"]["distinct_schedules"]
            > sat["blind"]["distinct_schedules"])
    if regime_filter is None:
        # a filtered run must not clobber the full-matrix artifact
        suffix = f"_shards{shards}" if shards > 1 else ""
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"BENCH_search_ab_{platform}{suffix}.json")
        with open(path, "w") as f:
            json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                      indent=1)
    print(json.dumps(out))


def _search_smoke_mode():
    """--search-smoke: seconds-scale fuzzer self-test for CI (wired into
    scripts/ci.sh fast): a small campaign must beat blind explore() on the
    saturating workload, exercise several mutation operators, keep every
    knob in bounds (the engine's own oops/crash checks would trip
    otherwise), and a PCT sweep must enumerate more than one tie-break
    policy. Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import numpy as np
    from madsim_tpu import explore, fuzz, pct_sweep
    t0 = time.perf_counter()
    rounds, batch, steps = 4, 64, 1200
    blind = explore(_make_saturating_runtime(), max_steps=steps,
                    batch=batch, max_rounds=rounds, dry_rounds=rounds + 1,
                    chunk=256)
    res = fuzz(_make_saturating_runtime(), max_steps=steps, batch=batch,
               max_rounds=rounds, dry_rounds=rounds + 1, chunk=256)
    assert res["distinct_schedules"] > blind["distinct_schedules"], (
        res["distinct_schedules"], blind["distinct_schedules"])
    used = [k for k, v in res["mutation_ops"].items() if v > 0]
    assert len(used) >= 3, res["mutation_ops"]
    ps = pct_sweep(_make_saturating_runtime(), seed=3,
                   nudges=np.arange(32), max_steps=steps, chunk=256)
    assert ps["distinct_schedules"] > 1, ps["distinct_schedules"]
    print(json.dumps({
        "metric": "search_smoke", "platform": "cpu", "ok": True,
        "fuzzer_schedules": res["distinct_schedules"],
        "blind_schedules": blind["distinct_schedules"],
        "mutation_ops_used": len(used),
        "pct_distinct": ps["distinct_schedules"],
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _ldfi_smoke_mode():
    """--ldfi-smoke: seconds-scale lineage-driven-fault-injection
    self-test for CI (wired into scripts/ci.sh fast):

      1. support extraction on a seeded rpc_echo lane must match an
         INLINE host-side parent-walk reference (independent code path
         over the same ring records);
      2. every synthesized targeted vector must stay on the knob plane:
         rows re-aimed only where the time guard allows, targets pool-
         confined (or NODE_RANDOM), values inside the row's [lo, hi];
      3. one targeted round must replay bit-identically from its
         (seed, knobs) handle — two independent apply+run dispatches,
         identical fingerprints and crash verdicts.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import numpy as np
    from madsim_tpu import SimConfig, sec, ms
    from madsim_tpu.core.types import EV_MSG, EV_TIMER, NODE_RANDOM
    from madsim_tpu.models.rpc_echo import make_echo_runtime
    from madsim_tpu.obs import ring_records
    from madsim_tpu.obs.support import (extract_support,
                                        support_from_records)
    from madsim_tpu.runtime import chaos
    from madsim_tpu.runtime.scenario import Scenario
    from madsim_tpu.search import LdfiConfig, fuzz
    from madsim_tpu.search.ldfi import SupportPool, synthesize
    from madsim_tpu.search.mutate import KnobPlan
    t0 = time.perf_counter()

    sc = Scenario()
    sc = chaos.asymmetric_partition(ms(400), [1], ms(300), sc=sc)
    sc = chaos.conn_reset_storm(rounds=2, first=ms(300), period=ms(450),
                                node=2, sc=sc)
    sc = chaos.clock_drift(ms(200), 128, node=1, until=ms(900), sc=sc)
    sc = chaos.retransmit_storm(ms(250), 0.3, ms(800), node=1, sc=sc)
    cfg = SimConfig(n_nodes=4, event_capacity=256, time_limit=sec(20),
                    trace_cap=64)
    rt = make_echo_runtime(n_nodes=4, target=4, cfg=cfg, scenario=sc)
    state, _ = rt.run(rt.init_batch(np.arange(8, dtype=np.uint32)),
                      4000, 256)
    assert not np.asarray(state.crashed)[0], "smoke lane went red"

    # 1. extraction vs an inline parent-walk reference
    sup = extract_support(state, 0)
    assert sup is not None and not sup["truncated"]
    recs = ring_records(state, 0)
    by_step = {int(s): i for i, s in enumerate(recs["step"])}
    i = len(recs["step"]) - 1            # default witness: last dispatch
    ref_msgs, ref_timers = [], []
    while True:
        kind = int(recs["kind"][i])
        if kind == EV_MSG:
            ref_msgs.append((int(recs["src"][i]), int(recs["node"][i]),
                             int(recs["now"][i])))
        elif kind == EV_TIMER:
            ref_timers.append((int(recs["node"][i]),
                               int(recs["now"][i])))
        parent = int(recs["parent"][i])
        if parent < 0 or parent not in by_step:
            break
        i = by_step[parent]
    ref_msgs.reverse()
    ref_timers.reverse()
    assert sup["msg_edges"] == ref_msgs, (sup["msg_edges"], ref_msgs)
    assert sup["timer_edges"] == ref_timers
    ref2 = support_from_records(recs)
    assert ref2["msg_edges"] == ref_msgs and ref2["depth"] == sup["depth"]

    # 2. synthesized rows stay on the knob plane
    plan = KnobPlan.from_runtime(rt, dup_slots=2)
    pool = SupportPool()
    for lane in range(4):
        s = extract_support(state, lane)
        if s is not None:
            pool.add(s)
    assert len(pool) >= 2, "too few green supports pooled"
    vecs = synthesize(plan, pool, 8)
    assert vecs, "plan with 4 fault families synthesized nothing"
    base = plan.base_knobs()
    n_aimed = 0
    for kn in vecs:
        changed = [r for r in range(plan.R)
                   if (kn["row_time"][r] != base["row_time"][r]
                       or kn["row_node"][r] != base["row_node"][r]
                       or kn["row_val"][r] != base["row_val"][r]
                       or kn["row_flag"][r] != base["row_flag"][r]
                       or kn["row_on"][r] != base["row_on"][r])]
        assert changed, "synthesized vector with zero cuts escaped"
        n_aimed += len(changed)
        for r in changed:
            assert plan.time_ok[r], f"row {r} re-aimed past its guard"
            node = int(kn["row_node"][r])
            assert node == NODE_RANDOM or (
                0 <= node < plan.N and plan.pool_ok[r, node + 1]), \
                f"row {r} target {node} escaped its pool"
            v = int(kn["row_val"][r])
            assert plan.val_lo[r] <= v <= plan.val_hi[r], \
                (r, v, plan.val_lo[r], plan.val_hi[r])

    # 3. a targeted round replays bit-identically from (seed, knobs)
    seed, kn = 5, vecs[0]
    runs = []
    for _ in range(2):
        st = plan.apply(
            rt.init_batch(np.asarray([seed], np.uint32)),
            KnobPlan.stack([kn]))
        fin = rt.run_fused(st, 4000, 256)
        runs.append((int(rt.fingerprints(fin)[0]),
                     bool(np.asarray(fin.crashed)[0]),
                     int(np.asarray(fin.crash_code)[0])))
    assert runs[0] == runs[1], runs

    # and the integrated arm runs end-to-end with honest accounting
    res = fuzz(rt, max_steps=4000, batch=16, max_rounds=3, dry_rounds=4,
               chunk=256, ldfi=LdfiConfig(lanes=4, frac=0.25))
    assert res["targeted"]["supports"] >= 1
    assert res["targeted"]["lanes_run"] >= 1
    print(json.dumps({
        "metric": "ldfi_smoke", "platform": "cpu", "ok": True,
        "support_depth": sup["depth"],
        "pooled_supports": len(pool),
        "synthesized_vectors": len(vecs), "rows_aimed": n_aimed,
        "targeted_lanes_run": res["targeted"]["lanes_run"],
        "targeted_admitted": res["targeted"]["admitted"],
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _make_aimed_asym_runtime():
    """The ldfi_ab 'aimed' regime: Percolator-lite with the asym cut's
    rows compiled in but parked at t=6s — AFTER the workload quiesces,
    so the base scenario is GREEN and the fault rows are raw material.
    Blind havoc must drift the cut (and its heal) into the right
    ~100ms commit window by luck; the lineage arm re-aims them at
    extracted support edges (and pins the seed whose timing it
    learned). The regime where 'aim, don't spray' is the whole game."""
    from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
    from madsim_tpu.models.percolator import make_percolator_runtime
    from madsim_tpu.runtime import chaos
    sc = Scenario()
    sc = chaos.asymmetric_partition(ms(6000), [1], ms(300), direction=1,
                                    sc=sc)
    cfg = SimConfig(n_nodes=5, event_capacity=256, payload_words=8,
                    time_limit=sec(10), trace_cap=128,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(8)))
    return make_percolator_runtime(n_clients=3, n_ops=12,
                                   sync_commits=True, scenario=sc,
                                   cfg=cfg)


def _ldfi_ab_mode():
    """--mode ldfi_ab: targeted (lineage-synthesized) vs blind
    (fault_perturb havoc) fault search at EQUAL budget (same rounds x
    batch x max_steps), in three fault regimes:

      grayfail   Percolator-lite under the composed gray-failure mix
      connfault  minipg (guards off) under the reset+dup storm mix
      aimed      Percolator-lite, GREEN base, asym cut rows parked
                 past quiesce (_make_aimed_asym_runtime)

    The headline is SCHEDULES-TO-FIRST-BUCKET: how many schedules each
    arm burned before its first causal-fingerprint crash bucket opened
    ((first bucket's round + 1) x batch — lanes in one round are
    concurrent, so the round that found it charges its whole batch).
    Both arms run durable campaigns (throwaway corpus dirs) so buckets
    dedup identically; the targeted arm additionally reports its
    admission yield and bucket origins. An honest null result (targeted
    not faster) is recorded in the regime's note rather than hidden.
    Writes BENCH_ldfi_ab_<platform>.json. CPU-forced: the comparison is
    about search QUALITY per schedule, not device throughput."""
    _force_cpu_inprocess()
    import shutil
    import tempfile
    from madsim_tpu.search import LdfiConfig, fuzz
    from madsim_tpu.service.store import CorpusStore
    platform = "cpu"
    out = {"metric": "ldfi_ab", "platform": platform,
           "note": ("equal budget = same rounds x batch x max_steps per "
                    "arm; schedules_to_first_bucket = (first bucket's "
                    "round + 1) x batch, None when an arm opened no "
                    "bucket. The targeted arm spends ldfi.frac of each "
                    "post-bootstrap round on lineage-synthesized "
                    "vectors; everything else stays havoc"),
           "regimes": {}}

    def arm(make, rounds, batch, steps, chunk, ldfi):
        tmp = tempfile.mkdtemp(prefix="ldfi_ab_")
        try:
            rt = make()
            t0 = time.perf_counter()
            res = fuzz(rt, max_steps=steps, batch=batch,
                       max_rounds=rounds, dry_rounds=rounds + 1,
                       chunk=chunk, corpus_dir=tmp, ldfi=ldfi)
            dt = time.perf_counter() - t0
            store = CorpusStore(tmp, create=False)
            bucket_rounds = []
            origins = {}
            for key in store.bucket_keys():
                rec = store.load_bucket(key)
                bucket_rounds.append(int(rec["repro"]["round"]))
                o = rec.get("origin", "havoc")
                origins[o] = origins.get(o, 0) + 1
            first = ((min(bucket_rounds) + 1) * batch
                     if bucket_rounds else None)
            row = {
                "schedules_to_first_bucket": first,
                "buckets": len(bucket_rounds),
                "distinct_schedules": res["distinct_schedules"],
                "crashes": res["crashes"],
                "wall_s": round(dt, 2)}
            if ldfi is not None:
                row["targeted"] = res["targeted"]
                row["bucket_origins"] = origins
            return row
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def ab(name, make, rounds, batch, steps, chunk, ldfi=None):
        # warm both arms' executables outside the timed region
        fuzz(make(), max_steps=steps, batch=batch, max_rounds=2,
             dry_rounds=3, chunk=chunk)
        row = {"rounds": rounds, "batch": batch, "max_steps": steps}
        row["blind"] = arm(make, rounds, batch, steps, chunk, None)
        row["targeted"] = arm(
            make, rounds, batch, steps, chunk,
            ldfi or LdfiConfig(frac=0.25, lanes=8, max_cuts=2))
        fb, ft = (row["blind"]["schedules_to_first_bucket"],
                  row["targeted"]["schedules_to_first_bucket"])
        if ft is not None and (fb is None or ft < fb):
            row["verdict"] = "targeted_first"
        elif ft == fb:
            row["verdict"] = ("both_null" if ft is None else "tie")
            row["note"] = ("honest null result: targeted did not reach "
                           "a bucket in fewer schedules at this budget")
            t_orig = row["targeted"].get("bucket_origins", {}).get(
                "targeted", 0)
            if ft is not None and (t_orig
                                   or row["targeted"]["buckets"]
                                   > row["blind"]["buckets"]):
                row["note"] += (
                    f" — but the targeted arm opened "
                    f"{row['targeted']['buckets']} distinct buckets vs "
                    f"blind's {row['blind']['buckets']}, {t_orig} of "
                    f"them from targeted-origin lanes")
        else:
            row["verdict"] = "blind_first"
            row["note"] = ("honest null result: blind reached its first "
                           "bucket in fewer schedules at this budget")
        out["regimes"][name] = row
        print(f"--ldfi-ab: {name} first-bucket blind={fb} "
              f"targeted={ft} ({row['verdict']})", file=sys.stderr)

    ab("grayfail", functools.partial(_make_grayfail_runtime, "mix"),
       rounds=4, batch=96, steps=20_000, chunk=512)
    ab("connfault", functools.partial(_make_connfault_runtime, "mix"),
       rounds=4, batch=96, steps=24_000, chunk=512)
    # green-base regime: the fault rows start parked past quiesce, so
    # every crash is a MUTATED fault — replay-upgraded supports, a
    # bigger targeted slice, and more rounds at finer batch resolution
    ab("aimed", _make_aimed_asym_runtime,
       rounds=8, batch=24, steps=20_000, chunk=512,
       ldfi=LdfiConfig(frac=0.5, lanes=6, max_cuts=2, replay=True))
    out["targeted_first_somewhere"] = any(
        r.get("verdict") == "targeted_first"
        for r in out["regimes"].values())
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_ldfi_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _grayfail_smoke_mode():
    """--grayfail-smoke: seconds-scale gray-failure-plane self-test for
    CI (scripts/ci.sh fast):

      1. a ONE-WAY cut is observed asymmetrically by gossip — the same
         group with the direction flag flipped either starves the
         cluster of node 0's rumors or lets them all through;
      2. skewed lease expiry on the Percolator-lite flagship crashes
         the snapshot-isolation oracle AND reproduces on seed replay
         (same crash code, same fingerprint, single-lane);
      3. a small durable fuzz campaign on the torn-write recipe opens
         >= 1 causal-fingerprint crash bucket whose (seed, knobs)
         handle replays red via replay_bucket.
    """
    _force_cpu_inprocess()
    import shutil
    import tempfile
    import numpy as np
    from madsim_tpu import (Scenario, SimConfig, fuzz, ms, replay_bucket,
                            sec)
    from madsim_tpu.models.gossip import make_gossip_runtime
    from madsim_tpu.models.percolator import CRASH_SNAPSHOT
    t0 = time.perf_counter()

    # 1. gossip sees the cut asymmetrically
    def gossip_have(direction):
        sc = Scenario()
        sc.at(0).partition_oneway([0], direction=direction)
        cfg = SimConfig(n_nodes=6, event_capacity=192, time_limit=sec(2))
        rt = make_gossip_runtime(n_nodes=6, scenario=sc, cfg=cfg)
        fin = rt.run_fused(rt.init_batch(np.arange(8, dtype=np.uint32)),
                           6_000, 256)
        return np.asarray(fin.node_state["have"])
    have_out = gossip_have(0)      # node 0's sends vanish
    have_in = gossip_have(1)       # node 0 hears nothing, sends fine
    full = (1 << 4) - 1
    assert (have_out[:, 1:] == 0).all(), \
        "outbound cut: rumors must never leave node 0"
    assert (have_in == full).all(), \
        "inbound cut: dissemination must be unaffected"

    # 2. skewed lease expiry crashes the SI oracle and replays by seed
    rt = _make_grayfail_runtime("skew")
    fin = rt.run_fused(rt.init_batch(np.arange(192, dtype=np.uint32)),
                       80_000, 512)
    codes = np.asarray(fin.crash_code)
    lanes = np.nonzero(codes == CRASH_SNAPSHOT)[0]
    assert lanes.size > 0, "skew recipe found no CRASH_SNAPSHOT lane"
    lane = int(lanes[0])
    fp_batch = int(rt.fingerprints(fin)[lane])
    rt2 = _make_grayfail_runtime("skew")
    rep = rt2.run_fused(rt2.init_batch(np.asarray([lane], np.uint32)),
                        80_000, 512)
    assert int(np.asarray(rep.crash_code)[0]) == CRASH_SNAPSHOT
    assert int(rt2.fingerprints(rep)[0]) == fp_batch, \
        "seed replay diverged from the batch lane"

    # 3. torn-write crash buckets by causal fingerprint, replayable
    tmp = tempfile.mkdtemp(prefix="grayfail_smoke_")
    try:
        rt3 = _make_grayfail_runtime("torn")
        res = fuzz(rt3, max_steps=40_000, batch=64, max_rounds=3,
                   dry_rounds=4, chunk=512, corpus_dir=tmp)
        assert res["buckets_total"] >= 1, res
        for key in res["buckets_opened"] or []:
            crashed, code, _ = replay_bucket(rt3, tmp, key, 40_000)
            assert crashed, (key, code)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({
        "metric": "grayfail_smoke", "platform": "cpu", "ok": True,
        "skew_crash_lanes": int(lanes.size),
        "torn_buckets": res["buckets_total"],
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _conn_smoke_mode():
    """--conn-smoke: seconds-scale connection-fault-plane self-test for
    CI (scripts/ci.sh fast):

      1. OP_RESET_PEER is observed on BOTH sides — a connected pair's
         conn state drops to CLOSED at both endpoints and both
         incarnation epochs bump (the reset_node parity; a plain kill
         leaves the survivor's half-open state, asserted as the
         contrast);
      2. incarnation REJECTION reproduces on single-lane seed replay —
         a guards-off reset+dup storm lane that crashed replays
         fingerprint-exact by seed, and the guards-ON build completes
         the same storm (both directions of the flagship contract);
      3. a small durable fuzz campaign on the guards-off mix opens >= 1
         causal-fingerprint crash bucket whose (seed, knobs) handle
         replays red via replay_bucket.
    """
    _force_cpu_inprocess()
    import shutil
    import tempfile
    import numpy as np
    from madsim_tpu import Scenario, fuzz, ms, replay_bucket
    from madsim_tpu.models.minipg import make_minipg_runtime
    t0 = time.perf_counter()

    # 1. both-sides teardown vs the kill's deliberate half-open — halt
    # right after the fault so the sample precedes watchdog recovery
    def final_conn(reset: bool):
        sc = Scenario()
        if reset:
            sc.at(ms(400)).reset_peer(0)
        else:
            sc.at(ms(400)).kill(0)
        sc.at(ms(401)).halt()
        rt = make_minipg_runtime(n_clients=2, n_txns=50, scenario=sc)
        fin = rt.run_fused(rt.init_batch(np.arange(8, dtype=np.uint32)),
                           20_000, 512)
        cn = np.asarray(fin.node_state["cn_state"])
        ep = np.asarray(fin.node_state["cn_epoch"])
        return cn, ep
    cn_r, ep_r = final_conn(True)
    assert (cn_r[:, 0, 1:] == 0).all(), "server side must read CLOSED"
    assert (cn_r[:, 1:, 0] == 0).all(), "client sides must read CLOSED"
    assert (ep_r[:, 0, 1:] >= 1).all() and (ep_r[:, 1:, 0] >= 1).all(), \
        "both sides' incarnation epochs must bump"
    cn_k, _ = final_conn(False)
    assert (cn_k[:, 1:, 0] == 2).any(), \
        "a kill must leave some survivor half-open (ESTABLISHED)"

    # 2. flagship both directions + fingerprint-exact red replay
    rt_g = _make_connfault_runtime("mix", guard=True)
    fin_g = rt_g.run_fused(
        rt_g.init_batch(np.arange(48, dtype=np.uint32)), 120_000, 512)
    done = np.asarray(fin_g.node_state["c_done"])[:, 1:]
    assert bool(done.all()) and not np.asarray(fin_g.crashed).any(), \
        "guards-on flagship must survive the storm"
    rt_r = _make_connfault_runtime("mix")
    fin_r = rt_r.run_fused(
        rt_r.init_batch(np.arange(48, dtype=np.uint32)), 120_000, 512)
    lanes = np.nonzero(np.asarray(fin_r.crashed))[0]
    assert lanes.size > 0, "guards-off storm found no crash lane"
    lane = int(lanes[0])
    code = int(np.asarray(fin_r.crash_code)[lane])
    fp_batch = int(rt_r.fingerprints(fin_r)[lane])
    rt_r2 = _make_connfault_runtime("mix")
    rep = rt_r2.run_fused(
        rt_r2.init_batch(np.asarray([lane], np.uint32)), 120_000, 512)
    assert int(np.asarray(rep.crash_code)[0]) == code
    assert int(rt_r2.fingerprints(rep)[0]) == fp_batch, \
        "seed replay diverged from the batch lane"

    # 3. dup-storm fuzz buckets by causal fingerprint, replayable red
    tmp = tempfile.mkdtemp(prefix="conn_smoke_")
    try:
        rt3 = _make_connfault_runtime("mix")
        res = fuzz(rt3, max_steps=30_000, batch=64, max_rounds=3,
                   dry_rounds=4, chunk=512, corpus_dir=tmp)
        assert res["buckets_total"] >= 1, res
        for key in res["buckets_opened"] or []:
            crashed, bcode, _ = replay_bucket(rt3, tmp, key, 30_000)
            assert crashed, (key, bcode)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({
        "metric": "conn_smoke", "platform": "cpu", "ok": True,
        "red_lanes": int(lanes.size), "red_code": code,
        "buckets": res["buckets_total"],
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _triage_smoke_mode():
    """--triage-smoke: seconds-scale campaign-triage-plane self-test
    for CI (scripts/ci.sh fast):

      1. a short 2-worker campaign on the torn-write recipe runs into
         one corpus dir (workers write triage/ROWS.json on open);
      2. snapshot twice — byte-identical bodies, self-diff EMPTY;
      3. mutate the store (open exactly one planted bucket), snapshot
         again — the diff reports EXACTLY that bucket as `new`, with
         the torn_write recipe attribution its knob vector encodes,
         and both attribution dimensions still sum to their totals;
      4. render the standing HTML dashboard (structure asserted) and
         the `service.report --against prev` terminal diff;
      5. audit one bucket through replay_bucket(verify=True) — the
         repro-health ledger records a verdict without aborting.
    """
    _force_cpu_inprocess()
    import shutil
    import subprocess
    import tempfile
    from madsim_tpu import KnobPlan
    from madsim_tpu.obs.causal import causal_fingerprint
    from madsim_tpu.obs.dashboard import render_html
    from madsim_tpu.runtime.scenario import RECIPE_FAMILIES
    from madsim_tpu.service import (CorpusStore, CrashBuckets,
                                    audit_buckets, run_campaign,
                                    triage_diff, triage_snapshot)
    from madsim_tpu.service.triage import snapshot_path
    t0 = time.perf_counter()
    factory = "bench:_make_grayfail_runtime"
    fkw = dict(recipe="torn")           # shares executables with
    steps = 40_000                      # --grayfail-smoke's campaign
    kw = dict(max_steps=steps, batch=64, max_rounds=2, chunk=512)
    root = tempfile.mkdtemp(prefix="madsim_triage_smoke_")
    env = _cpu_env()
    try:
        d = os.path.join(root, "campaign")
        rep = run_campaign(factory, d, workers=2, factory_kwargs=fkw,
                           env=env, **kw)
        for w, res in rep["worker_results"].items():
            assert res["returncode"] == 0, (w, res)
        store = CorpusStore(d, create=False)
        assert store.load_triage_rows() is not None, \
            "workers must write triage/ROWS.json on open"
        n1, s1 = triage_snapshot(store)
        n2, s2 = triage_snapshot(store)
        with open(snapshot_path(store, n1), "rb") as f1, \
                open(snapshot_path(store, n2), "rb") as f2:
            assert f1.read() == f2.read(), \
                "same store must snapshot byte-identically"
        assert triage_diff(s1, s2)["empty"], "self-diff must be empty"

        # mutate: open exactly one new bucket (distinct causal chain,
        # the campaign plan's own base knob vector = torn recipe)
        rt = _make_grayfail_runtime(**fkw)
        plan = KnobPlan.from_runtime(rt)     # dup_slots=2, the default
        chain = [dict(step=i, now=i * 10, kind=1, node=0, src=0,
                      tag=4321 + i, parent=i - 1, lamport=i + 1)
                 for i in range(3)]
        fp = causal_fingerprint(dict(
            chain=chain, truncated=False, root_external=True,
            crashed=True, crash_code=997, crash_node=0, lane=0,
            dropped=0))
        key, opened = CrashBuckets(store).observe(
            fp, seed=424242, knobs=plan.base_knobs(), round_no=5,
            worker_id=0, chain=chain)     # observe() logs the line too
        assert opened
        n3, s3 = triage_snapshot(store)
        diff = triage_diff(s2, s3)
        assert diff["buckets"]["new"] == [key], diff["buckets"]
        assert not diff["buckets"]["stale"], diff["buckets"]
        assert s3["buckets"][key]["recipe"] == "torn_write", \
            s3["buckets"][key]
        a = s3["attribution"]
        assert sum(a["recipe_coverage"].values()) \
            == s3["store"]["coverage_total"]
        assert sum(a["recipe_buckets"].values()) \
            == s3["store"]["buckets_total"]
        assert set(a["recipe_coverage"]) == set(RECIPE_FAMILIES) | {"base"}

        # dashboard + terminal report
        html = render_html(s3, diff)
        html_path = os.path.join(root, "dash.html")
        with open(html_path, "w") as f:
            f.write(html)
        assert "triage-root" in html and "<svg" in html \
            and key[:16] in html and 'class="badge new"' in html
        out = subprocess.run(
            [sys.executable, "-m", "madsim_tpu.service.report", d,
             "--against", "prev"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        assert out.returncode == 0, out.stderr[-800:]
        assert "1 new" in out.stdout, out.stdout

        # repro-health audit: one rotation step, verdict recorded
        audit = audit_buckets(rt, store, max_steps=steps, budget=1,
                              chunk=512)
        assert len(audit["audited"]) == 1
        verdict = audit["audited"][0]
        assert verdict["status"] in ("pass", "fail", "flaky"), verdict
        _n4, s4 = triage_snapshot(store)
        assert s4["audit"][verdict["bucket"]]["status"] \
            == verdict["status"]
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({
        "metric": "triage_smoke", "platform": "cpu", "ok": True,
        "buckets": s3["store"]["buckets_total"],
        "coverage": s3["store"]["coverage_total"],
        "new_bucket": key, "audit": verdict["status"],
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _regression_smoke_mode():
    """--regression-smoke: the durable corpus as a REGRESSION SUITE
    (OSS-Fuzz-style, r17): tests/data/regression_corpus/ holds committed
    campaign dirs — known crash buckets + the corpus that found them.
    Every bucket must still reproduce (replay_bucket with the run-twice
    verify guard), and the top-energy corpus slice must still land on
    its recorded schedule hashes — a silent engine change that rewires
    replay shows up here before it ships."""
    _force_cpu_inprocess()
    import importlib
    import numpy as np
    from madsim_tpu import KnobPlan, replay_bucket
    from madsim_tpu.parallel import stats
    from madsim_tpu.service.store import CorpusStore, store_signature
    t0 = time.perf_counter()
    base = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "data", "regression_corpus")
    names = sorted(n for n in os.listdir(base)
                   if os.path.isdir(os.path.join(base, n)))
    assert names, f"no regression corpus committed under {base}"
    checked = dict(buckets=0, entries=0)
    for name in names:
        d = os.path.join(base, name)
        with open(os.path.join(d, "REGRESSION.json")) as f:
            man = json.load(f)
        mod, fn = man["factory"].split(":")
        rt = getattr(importlib.import_module(mod), fn)(
            **man.get("factory_kwargs", {}))
        dup = int(man.get("dup_slots", 2))
        steps = int(man["max_steps"])
        plan = KnobPlan.from_runtime(rt, dup_slots=dup)
        # signature check: a structurally different engine refuses the
        # dir instead of replaying knobs onto the wrong rows
        store = CorpusStore(d, signature=store_signature(rt, plan),
                            create=False)
        keys = store.bucket_keys()
        missing = set(man["buckets"]) - set(keys)
        assert not missing, f"{name}: recorded buckets missing: {missing}"
        for key in keys:
            crashed, code, _ = replay_bucket(rt, d, key, steps,
                                             dup_slots=dup, verify=True)
            assert crashed, (f"{name}/{key}: bucket no longer "
                             f"reproduces (code={code})")
            checked["buckets"] += 1
        # top-energy corpus slice: recorded (seed, knobs) -> recorded
        # sched_hash, bit-for-bit
        ws = store.load_worker_state(0)
        order = sorted(ws.get("order", []), key=lambda e: -e[1])[:8]
        for eid, _en in order:
            ent = store.load_entry(store._entry_name(int(eid)))
            state = plan.apply(
                rt.init_batch(np.asarray([ent["seed"]], np.uint32)),
                KnobPlan.stack([ent["knobs"]]))
            fin = rt.run_fused(state, steps, 512)
            got = int(stats.sched_hash_u64(fin)[0])
            assert got == ent["hash"], (
                f"{name}: entry {eid} replayed to schedule {got:#x}, "
                f"recorded {ent['hash']:#x}")
            checked["entries"] += 1
    print(json.dumps({
        "metric": "regression_smoke", "platform": "cpu", "ok": True,
        "campaigns": len(names), **checked,
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _campaign_mode():
    """--mode campaign: persistent multi-process fuzzing campaign A/B
    (service/campaign.py) at 1 vs 2 workers, EQUAL per-worker budget
    (same rounds x batch x max_steps each), on the crash-rich wal_kv
    matrix. Workers are CPU subprocesses sharing a corpus dir and the r8
    persistent compile cache; rates use the workers' own fuzz wall
    (max across workers — they overlap), with driver uptime (startup +
    compile included) reported alongside. Writes BENCH_campaign_cpu.json:
    schedules/s and buckets/min per worker count, plus the cross-process
    dedup evidence (crash observations vs merged buckets)."""
    _force_cpu_inprocess()
    import shutil
    import tempfile
    from madsim_tpu.service import run_campaign
    factory = "bench:_make_crashrich_runtime"
    fkw = dict(kind="wal_kv", trace_cap=64, sketch_slots=4)
    kw = dict(max_steps=4096, batch=48, max_rounds=3, chunk=512)
    out = {"metric": "campaign", "platform": "cpu",
           "workload": dict(factory=factory, **fkw, **kw),
           "note": ("equal PER-WORKER budget: the 2-worker campaign "
                    "explores twice the schedules; linear scaling in "
                    "worker_wall-relative schedules/s is the merge-by-"
                    "construction claim (coverage dedup costs no locks). "
                    "buckets_merged counts bugs after the read-side "
                    "suffix merge — crash_observations above it is the "
                    "cross-process dedup doing its job. CPU numbers, "
                    "not a device measurement"),
           "runs": {}}
    root = tempfile.mkdtemp(prefix="madsim_campaign_bench_")
    env = _cpu_env()
    try:
        # warm the shared compile cache so neither measured run eats the
        # one-time cold compile
        run_campaign(factory, os.path.join(root, "warm"), workers=1,
                     factory_kwargs=fkw, env=env,
                     **dict(kw, max_rounds=1))
        for n in (1, 2):
            d = os.path.join(root, f"w{n}")
            t0 = time.perf_counter()
            rep = run_campaign(factory, d, workers=n,
                               factory_kwargs=fkw, env=env, **kw)
            for w, res in rep["worker_results"].items():
                # a dead worker would silently shrink the measured side
                # into a wrong "no scaling" artifact — fail loudly
                assert res["returncode"] == 0, (n, w, res)
            out["runs"][f"workers_{n}"] = {
                "coverage_keys": rep["coverage_keys"],
                "corpus_entries": rep["corpus_entries"],
                "buckets_merged": rep["buckets_merged"],
                "crash_observations": rep["crash_observations"],
                "schedules_per_sec": rep["schedules_per_sec"],
                "buckets_per_min": rep["buckets_per_min"],
                "worker_wall_s": rep["worker_wall_s"],
                "driver_uptime_s": round(time.perf_counter() - t0, 1),
            }
            print(f"--campaign: {n} worker(s): "
                  f"{rep['coverage_keys']} coverage keys, "
                  f"{rep['buckets_merged']} buckets, "
                  f"{rep['schedules_per_sec']}/s", file=sys.stderr)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    r1, r2 = out["runs"]["workers_1"], out["runs"]["workers_2"]
    out["coverage_scaling_2x"] = round(
        r2["coverage_keys"] / max(r1["coverage_keys"], 1), 2)
    out["schedules_per_sec_scaling_2x"] = round(
        r2["schedules_per_sec"] / max(r1["schedules_per_sec"], 1e-9), 2)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_campaign_cpu.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _campaign_smoke_mode():
    """--campaign-smoke: seconds-scale persistent-campaign self-test for
    CI (wired into scripts/ci.sh fast). Three contracts, with CPU-forced
    subprocess workers sharing the persistent compile cache:

      merge       two CONCURRENT workers on one corpus dir -> merged
                  corpus carries both id namespaces, and the crash
                  harvests dedup into shared causal-fingerprint buckets
                  (at least one bucket observed by both processes;
                  observations strictly exceed merged buckets)
      durability  SIGKILL a 1-worker campaign mid-run, resume it from
                  the corpus dir, and the final coverage keys, entry
                  files, and bucket set EQUAL an uninterrupted control
                  run with the same seeds (the acceptance proof)
      reject      the dir refuses a structurally different runtime
                  (version/signature contract)
    """
    _force_cpu_inprocess()
    import shutil
    import signal as _signal
    import subprocess as _sp
    import tempfile
    from madsim_tpu.service import (CorpusStore, StoreMismatch,
                                    campaign_report, run_campaign,
                                    spawn_worker, worker_cmd)
    t0 = time.perf_counter()
    factory = "bench:_make_crashrich_runtime"
    fkw = dict(kind="wal_kv", trace_cap=64, sketch_slots=4)
    kw = dict(max_steps=4096, batch=16, max_rounds=2, chunk=512)
    root = tempfile.mkdtemp(prefix="madsim_campaign_smoke_")
    env = _cpu_env()
    try:
        # -- merge + dedup across two concurrent processes --------------
        d1 = os.path.join(root, "merge")
        rep = run_campaign(factory, d1, workers=2, factory_kwargs=fkw,
                           env=env, poll_s=1.0, **kw)
        for w, res in rep["worker_results"].items():
            assert res["returncode"] == 0, (w, res)
        store = CorpusStore(d1, create=False)
        namespaces = {n.split("-")[0] for n in store.entry_names()}
        assert namespaces == {"w0000", "w0001"}, namespaces
        # entries can transiently exceed coverage when two workers admit
        # one hash before their next merge sync — never the reverse
        assert 0 < rep["coverage_keys"] <= rep["corpus_entries"]
        assert rep["buckets_merged"] >= 1, rep
        assert rep["crash_observations"] > rep["buckets_merged"], rep
        by_bucket = {}
        for line in store.bucket_log():
            by_bucket.setdefault(line["bucket"], set()).add(
                line["worker_id"])
        assert any(len(ws) == 2 for ws in by_bucket.values()), (
            "no bucket was observed by both workers", by_bucket)
        # -- durability: SIGKILL mid-campaign, resume, compare ----------
        dk = os.path.join(root, "kill")
        dc = os.path.join(root, "ctrl")
        kwk = dict(kw, max_rounds=3)
        p = spawn_worker(dk, 0, factory, factory_kwargs=fkw, env=env,
                         **kwk)
        state_path = os.path.join(dk, "state", "w0000.json")
        deadline = time.time() + 300
        while time.time() < deadline:
            if os.path.exists(state_path):
                break
            if p.poll() is not None:
                raise AssertionError("worker exited before first sync")
            time.sleep(0.2)
        else:
            raise AssertionError("no sync within 300s")
        p.send_signal(_signal.SIGKILL)
        p.wait()
        killed_at = json.load(open(state_path))["rounds_done"]
        # resume to the campaign total; control runs uninterrupted
        for d in (dk, dc):
            _sp.run(worker_cmd(d, 0, factory, factory_kwargs=fkw, **kwk),
                    env=env, check=True, stdout=_sp.DEVNULL)
        sk, sc_ = CorpusStore(dk, create=False), CorpusStore(
            dc, create=False)
        assert sk.coverage_keys() == sc_.coverage_keys()
        assert sk.entry_names() == sc_.entry_names()
        assert sk.bucket_keys() == sc_.bucket_keys()
        assert json.load(open(state_path))["rounds_done"] == 3
        # -- signature reject -------------------------------------------
        from madsim_tpu.search.mutate import KnobPlan
        from madsim_tpu.service import store_signature
        other = _make_crashrich_runtime("chain", trace_cap=64)
        try:
            CorpusStore(d1, signature=store_signature(
                other, KnobPlan.from_runtime(other)))
            raise AssertionError("structurally different runtime was "
                                 "not rejected")
        except StoreMismatch:
            pass
        print(json.dumps({
            "metric": "campaign_smoke", "platform": "cpu", "ok": True,
            "merged_coverage": rep["coverage_keys"],
            "buckets_merged": rep["buckets_merged"],
            "crash_observations": rep["crash_observations"],
            "killed_at_round": killed_at,
            "resume_matches_uninterrupted": True,
            "wall_s": round(time.perf_counter() - t0, 1)}))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _force_cpu_mesh_bench(n: int):
    """Force the host platform with >= n virtual devices for the shard
    modes — the repo driver's recipe (__graft_entry__._force_cpu_mesh),
    which must run before anything initializes the XLA backend."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from __graft_entry__ import _force_cpu_mesh
    return _force_cpu_mesh(n)


def _shard_mode():
    """--mode shard: mesh-sharded campaign scaling (search/shard.py) on
    an honest CPU mesh — schedules-explored-per-second at EQUAL
    PER-SHARD budget (same rounds x per-shard batch x max_steps each) as
    the mesh grows 1 -> 2 -> 4 -> 8 virtual devices, on the crash-rich
    wal_kv matrix. Each shard is one more device running the same
    per-shard campaign; the wall should stay ~flat while explored
    lanes (and on this workload, distinct coverage) scale with the mesh.
    Also asserts the acceptance bit: a 1-shard sharded campaign writes a
    BYTE-IDENTICAL durable store to the unsharded fuzzer (entry files,
    coverage keys, scheduler order+energies). Writes
    BENCH_shard_cpu.json. On-chip numbers ride the ROADMAP TPU wishlist
    (`--mode search_ab --shards N`)."""
    import shutil
    import tempfile
    shards_axis = (1, 2, 4, 8)
    _force_cpu_mesh_bench(max(shards_axis))
    from madsim_tpu import fuzz, fuzz_sharded
    from madsim_tpu.service import CorpusStore
    rounds, batch, steps, chunk = 3, 48, 4096, 512
    make = functools.partial(_make_crashrich_runtime, "wal_kv")
    out = {"metric": "shard_scale", "platform": "cpu",
           "workload": "crashrich_wal_kv",
           "budget": {"rounds": rounds, "batch_per_shard": batch,
                      "max_steps": steps},
           "note": ("equal PER-SHARD budget: every shard runs the same "
                    "rounds x batch x max_steps; scaling is "
                    "schedules-explored-per-second (lanes dispatched / "
                    "wall — each lane is one schedule sample) on a "
                    "virtual CPU mesh, where device partitions execute "
                    "on host threads. distinct_per_sec rides along: "
                    "wal_kv's randomized arrivals keep most lanes on "
                    "distinct schedules, so coverage scales too."),
           "shards": {}}
    for S in shards_axis:
        # warm this mesh width's executables (sharded layouts compile
        # per width) outside the timed region — 2 rounds so the masked
        # havoc dispatch (first used in round 1) is warm too
        fuzz_sharded(make(), max_steps=steps, batch=batch, shards=S,
                     max_rounds=2, dry_rounds=3, chunk=chunk)
        rt = make()
        t0 = time.perf_counter()
        res = fuzz_sharded(rt, max_steps=steps, batch=batch, shards=S,
                           max_rounds=rounds, dry_rounds=rounds + 1,
                           chunk=chunk)
        dt = time.perf_counter() - t0
        out["shards"][S] = {
            "lanes_run": res["seeds_run"],
            "distinct_schedules": res["distinct_schedules"],
            "wall_s": round(dt, 2),
            "schedules_explored_per_sec": round(res["seeds_run"] / dt, 1),
            "distinct_per_sec": round(res["distinct_schedules"] / dt, 1),
            "corpus_size": res["corpus_size"],
        }
        print(f"--shard: {S} shard(s): {res['seeds_run']} lanes in "
              f"{dt:.1f}s = {res['seeds_run'] / dt:,.0f} sched/s, "
              f"{res['distinct_schedules']} distinct", file=sys.stderr)
    e1 = out["shards"][1]["schedules_explored_per_sec"]
    for S in shards_axis[1:]:
        out[f"scaling_1_to_{S}"] = round(
            out["shards"][S]["schedules_explored_per_sec"] / e1, 2)
    # the acceptance bit: 1-shard sharded == unsharded fuzzer, down to
    # store bytes
    root = tempfile.mkdtemp(prefix="madsim_shard_bench_")
    try:
        kw = dict(max_steps=1500, batch=16, max_rounds=2, dry_rounds=9,
                  chunk=256)
        da, db = os.path.join(root, "a"), os.path.join(root, "b")
        fuzz(make(), corpus_dir=da, **kw)
        fuzz_sharded(make(), shards=1, corpus_dir=db, **kw)
        sa = CorpusStore(da, create=False)
        sb = CorpusStore(db, create=False)
        names = sa.entry_names()
        assert names == sb.entry_names(), "entry sets differ"
        assert sa.coverage_keys() == sb.coverage_keys()
        assert all(
            open(os.path.join(da, "entries", n), "rb").read()
            == open(os.path.join(db, "entries", n), "rb").read()
            for n in names), "entry files not byte-identical"
        wa = sa.load_worker_state(0)
        gb = sb.load_shard_group_state(0)["shard_states"][0]
        assert wa["order"] == gb["order"], "scheduler order/energies differ"
        out["one_shard_bit_identical"] = True
    finally:
        shutil.rmtree(root, ignore_errors=True)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_shard_cpu.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _shard_smoke_mode():
    """--shard-smoke: seconds-scale mesh-sharded-campaign self-test for
    CI (scripts/ci.sh fast), on a 2-shard virtual CPU mesh:

      equivalence  a 1-shard sharded campaign must write a byte-
                   identical durable store to the unsharded fuzzer
                   (entry files, coverage keys, scheduler order)
      merge        a 2-shard campaign's merged coverage must be a
                   superset of every shard's own, entries must land in
                   both shard namespaces, and the consensus tally must
                   serialize
      durability   a 2-shard campaign split across two calls must end
                   equal to the uninterrupted control (entries +
                   coverage + group state), with the run-twice
                   verify_resume guard armed on the resumed call
    """
    import shutil
    import tempfile
    _force_cpu_mesh_bench(2)
    t0 = time.perf_counter()
    from madsim_tpu import fuzz, fuzz_sharded
    from madsim_tpu.search.shard import shard_worker_id
    from madsim_tpu.service import CorpusStore
    root = tempfile.mkdtemp(prefix="madsim_shard_smoke_")
    try:
        kw = dict(max_steps=400, batch=16, max_rounds=3, dry_rounds=9,
                  chunk=128)
        # -- 1-shard bit-identity ---------------------------------------
        da, db = os.path.join(root, "a"), os.path.join(root, "b")
        fuzz(_make_saturating_runtime(), corpus_dir=da, **kw)
        r1 = fuzz_sharded(_make_saturating_runtime(), shards=1,
                          corpus_dir=db, **kw)
        sa = CorpusStore(da, create=False)
        sb = CorpusStore(db, create=False)
        names = sa.entry_names()
        assert names == sb.entry_names()
        assert sa.coverage_keys() == sb.coverage_keys()
        assert all(
            open(os.path.join(da, "entries", n), "rb").read()
            == open(os.path.join(db, "entries", n), "rb").read()
            for n in names), "1-shard store not byte-identical to fuzz()"
        assert (sa.load_worker_state(0)["order"]
                == sb.load_shard_group_state(0)["shard_states"][0]["order"])
        # -- 2-shard merge ----------------------------------------------
        r2 = fuzz_sharded(_make_saturating_runtime(sketch_slots=8),
                          shards=2, **kw)
        assert r2["shards"] == 2
        for row in r2["per_shard"]:
            # merged coverage is a superset of each shard's own view
            assert row["coverage"] <= r2["distinct_schedules"]
            assert row["worker_id"] == shard_worker_id(0, row["shard"], 2)
        # -- 2-shard split == continuous, verify_resume armed -----------
        dc, dd = os.path.join(root, "c"), os.path.join(root, "d")
        kw2 = dict(kw, shards=2)
        fuzz_sharded(_make_saturating_runtime(), corpus_dir=dc,
                     **dict(kw2, max_rounds=2))
        rs = fuzz_sharded(_make_saturating_runtime(), corpus_dir=dc,
                          verify_resume=True, **dict(kw2, max_rounds=4))
        rc = fuzz_sharded(_make_saturating_runtime(), corpus_dir=dd,
                          **dict(kw2, max_rounds=4))
        sc_ = CorpusStore(dc, create=False)
        sd = CorpusStore(dd, create=False)
        assert rs["rounds_done_total"] == 4 and rc["rounds_done_total"] == 4
        assert sc_.entry_names() == sd.entry_names()
        assert sc_.coverage_keys() == sd.coverage_keys()
        gc_ = sc_.load_shard_group_state(0)
        gd = sd.load_shard_group_state(0)
        assert [s["order"] for s in gc_["shard_states"]] \
            == [s["order"] for s in gd["shard_states"]]
        assert gc_["tally"] == gd["tally"]
        # namespaced entries from BOTH shards present, and each shard's
        # LIVE corpus holds foreign-namespace entries — the cross-shard
        # merge actually delivered, not just co-located files
        ws = {n.split("-")[0] for n in sc_.entry_names()}
        assert ws == {"w0000", "w0001"}, ws
        from madsim_tpu.search.corpus import split_entry_id
        for s, st in enumerate(gc_["shard_states"]):
            owners = {split_entry_id(int(eid))[0] for eid, _ in st["order"]}
            assert owners == {0, 1}, (s, owners)
        print(json.dumps({
            "metric": "shard_smoke", "platform": "cpu", "ok": True,
            "one_shard_entries": len(names),
            "one_shard_bit_identical": True,
            "two_shard_distinct": r2["distinct_schedules"],
            "two_shard_per_shard": [row["coverage"]
                                    for row in r2["per_shard"]],
            "split_equals_continuous": True,
            "verify_resume_armed": True,
            "wall_s": round(time.perf_counter() - t0, 1)}))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _make_raft_compile_matrix_runtime(time_limit, loss, lat_hi,
                                      share: bool):
    """One cell of the compile_ab matrix: the flagship Raft step program
    with a small log, varying ONLY dynamic knobs (time limit, loss,
    latency) so every cell shares one structural signature."""
    from madsim_tpu import Runtime, Scenario, SimConfig, NetConfig, ms, sec
    from madsim_tpu.models.raft import (Raft, persist_spec, raft_invariant,
                                        state_spec)
    cfg = SimConfig(n_nodes=5, event_capacity=128, time_limit=time_limit,
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=lat_hi))
    sc = Scenario()
    sc.at(sec(1)).kill_random()
    sc.at(sec(1) + ms(400)).restart_random()
    return Runtime(cfg, [Raft(5, 8, 4, 0)], state_spec(5, 8), scenario=sc,
                   invariant=raft_invariant(5, 8), persist=persist_spec(),
                   share_programs=share)


def _compile_ab_mode():
    """--mode compile_ab: cold-vs-shared compile A/B on the host CPU
    (a compile-count A/B, not a device measurement). A 6-config matrix of the
    flagship Raft step program sharing ONE structural signature (cells
    differ only in dynamic knobs: time limit, loss, latency) is driven
    two ways:

      per_runtime  share_programs=False — every Runtime owns private jits
                   (the pre-cache world): 6 traces, 6 XLA compiles
      shared       share_programs=True through a cleared PROGRAM_CACHE:
                   cell 1 compiles, cells 2-6 reuse the executable

    Each cell's trace+compile cost is measured as (first call) - (warm
    call) on the chunked runner at B=512; the JAX persistent compile
    cache is disabled so the control is genuinely cold. Also records the
    AOT trace/lower/compile stage split for one cell (compile/timing.py)
    and the COMPILE_LOG/PROGRAM_CACHE counters. Writes
    BENCH_compile_ab_<platform>.json next to this file."""
    _force_cpu_inprocess()
    import jax
    from madsim_tpu import sec as _sec, ms as _ms
    from madsim_tpu.compile.cache import COMPILE_LOG, PROGRAM_CACHE
    from madsim_tpu.compile.timing import timed_stages
    # honest cold control: no on-disk reuse
    jax.config.update("jax_enable_compilation_cache", False)
    platform = jax.devices()[0].platform
    B, chunk = 512, 256
    matrix = [(_sec(2 + i % 3), 0.01 * i, _ms(4 + i)) for i in range(6)]
    seeds = np.arange(B)

    def cell_cost(rt):
        runner = rt._run_chunk[False]
        state = rt.init_batch(seeds)
        t0 = time.perf_counter()
        state, _ = runner(state, chunk)
        jax.block_until_ready(state.now)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, _ = runner(state, chunk)
        jax.block_until_ready(state.now)
        warm = time.perf_counter() - t0
        return max(first - warm, 0.0), warm

    out = {"metric": "compile_ab", "platform": platform, "batch": B,
           "chunk": chunk, "configs": len(matrix),
           "note": ("6-config flagship-Raft matrix, one structural "
                    "signature, dynamic knobs only; trace+compile per "
                    "cell = first-call minus warm-call wall on the "
                    "chunked runner; persistent compile cache disabled "
                    "for the control")}
    results = {}
    for name, share in (("per_runtime", False), ("shared", True)):
        PROGRAM_CACHE.clear()
        t_trace0 = COMPILE_LOG.snapshot()["traces_total"]
        per = []
        for (tl, loss, lat) in matrix:
            rt = _make_raft_compile_matrix_runtime(tl, loss, lat, share)
            tc, warm = cell_cost(rt)
            per.append(round(tc, 3))
        results[name] = {
            "per_config_trace_compile_s": per,
            "total_trace_compile_s": round(sum(per), 3),
            "traces": COMPILE_LOG.snapshot()["traces_total"] - t_trace0,
        }
        print(f"--compile-ab: {name} total trace+compile "
              f"{sum(per):.1f}s over {len(per)} configs "
              f"({results[name]['traces']} traces)", file=sys.stderr)
    out.update(results)
    out["reduction_x"] = round(
        results["per_runtime"]["total_trace_compile_s"]
        / max(results["shared"]["total_trace_compile_s"], 1e-9), 2)
    # AOT stage split for one cell (fresh private jit, so nothing cached)
    rt = _make_raft_compile_matrix_runtime(*matrix[0], share=False)
    stages = timed_stages(rt._compile_chunk(False), rt.init_batch(seeds),
                          chunk)
    out["stages_one_config"] = {
        k: round(v, 3) for k, v in stages.items()
        if k != "compiled" and v is not None}
    out["compile_log"] = COMPILE_LOG.snapshot()
    out["compile_events"] = COMPILE_LOG.recent(16)
    out["program_cache"] = PROGRAM_CACHE.stats()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_compile_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _compile_smoke_mode():
    """--compile-smoke: seconds-scale compile-cache self-test for CI
    (scripts/ci.sh fast --compile-smoke): two structurally-equal configs
    (dynamic knobs differ) must resolve to the SAME chunk-runner object,
    cost exactly ONE retrace between them, and produce results bitwise
    equal to a fresh-compile (share_programs=False) control. Runs on the
    host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    from madsim_tpu import Runtime
    from madsim_tpu.compile.cache import COMPILE_LOG, PROGRAM_CACHE
    from madsim_tpu.models.pingpong import PingPong, state_spec
    t0 = time.perf_counter()
    seeds = np.arange(64)
    before = COMPILE_LOG.snapshot()["traces"].get("chunk_runner", 0)
    rt1 = _make_light_runtime()
    from madsim_tpu import SimConfig, NetConfig, ms, sec
    cfg2 = SimConfig(n_nodes=2, event_capacity=16, payload_words=2,
                     time_limit=sec(123), collect_stats=False,
                     net=NetConfig(packet_loss_rate=0.02,
                                   send_latency_min=ms(1),
                                   send_latency_max=ms(4)))
    rt2 = Runtime(cfg2, [PingPong(2, target=1 << 30)], state_spec())
    assert rt1._sig == rt2._sig, "structural signatures must match"
    assert rt1._run_chunk[False] is rt2._run_chunk[False], \
        "structurally-equal configs must share one chunk runner"
    s1, _ = rt1.run(rt1.init_batch(seeds), 192, 64)
    s2, _ = rt2.run(rt2.init_batch(seeds), 192, 64)
    traces = COMPILE_LOG.snapshot()["traces"].get("chunk_runner",
                                                  0) - before
    assert traces == 1, f"expected exactly 1 retrace for the pair, got " \
        f"{traces}"
    ctrl = Runtime(cfg2, [PingPong(2, target=1 << 30)], state_spec(),
                   share_programs=False)
    sc, _ = ctrl.run(ctrl.init_batch(seeds), 192, 64)
    assert (ctrl.fingerprints(sc) == rt2.fingerprints(s2)).all(), \
        "shared-executable run diverged from fresh compile"
    print(json.dumps({
        "metric": "compile_smoke", "platform": "cpu", "ok": True,
        "traces_for_pair": traces,
        "cache": PROGRAM_CACHE.describe(),
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _obs_smoke_mode():
    """--obs-smoke: seconds-scale observability self-test for CI (wired
    into scripts/ci.sh fast): a tiny traced sweep through the FUSED
    runner must come back with a readable ring that exports as valid
    Chrome-trace JSON, the collect_events exporter must agree with the
    engine's own fired counts, and the sweep observer must see the run.
    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import json as _json
    import tempfile
    from madsim_tpu.obs import (JsonlObserver, export_chrome_trace,
                                ring_records)
    t0 = time.perf_counter()
    rt = _make_light_runtime(trace_cap=32)
    seeds = np.arange(16)
    fused = rt.run_fused(rt.init_batch(seeds, trace_lanes=[0, 5]), 192, 64)
    # ring-enabled fused sweep must stay bitwise-equal to the chunked
    # runner: fingerprints cover the non-trace state (the recorder is
    # excluded from them by design), the ring columns compare directly
    chunked, _ = rt.run(rt.init_batch(seeds, trace_lanes=[0, 5]), 192, 64)
    assert (rt.fingerprints(fused) == rt.fingerprints(chunked)).all(), \
        "traced fused runner diverged from chunked run()"
    from madsim_tpu.core.state import TRACE_FIELDS
    for f in TRACE_FIELDS:
        assert (np.asarray(getattr(fused, f))
                == np.asarray(getattr(chunked, f))).all(), f
    recs = ring_records(fused, lane=5)
    assert recs["total"] > 0 and len(recs["now"]) == min(recs["total"], 32)
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "ring.json")
        n = export_chrome_trace(p, state=fused, lane=5)
        with open(p) as f:
            doc = _json.load(f)          # must be valid JSON
        assert n == len([e for e in doc["traceEvents"] if e["ph"] == "i"])
        obs = JsonlObserver(os.path.join(d, "sweep.jsonl"))
        state, events = rt.run(rt.init_batch(seeds), 192, 64,
                               collect_events=True, observer=obs)
        obs.close()
        assert [r["kind"] for r in obs.records][-1] == "done"
        p2 = os.path.join(d, "events.json")
        n2 = export_chrome_trace(p2, events=events, b=3)
        fired = int(np.asarray(events["fired"])[:, 3].sum())
        assert n2 == fired, (n2, fired)
    print(_json.dumps({
        "metric": "obs_smoke", "platform": "cpu", "ok": True,
        "ring_events": int(n), "exported_events": int(n2),
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _prof_ab_mode():
    """--mode prof_ab: sim-profiler counter-plane overhead A/B on the
    fused runner, the r7 obs_ab protocol exactly (worst-case tiny step,
    interleaved min-of-9 reps so machine drift hits every variant
    equally). Three builds, identical trajectories by construction (the
    counter writes consume no randomness):

      off          profile=False — counters compiled out (baseline; the
                   acceptance bar puts this within noise)
      prof_masked  profile=True compiled in, NO lanes counted — the
                   cost of carrying the counter columns + masked
                   saturating writes; the ship-with-it shape, bar ≤3%
                   at B=512
      prof_on      profile=True, every lane counts — the ceiling

    Writes BENCH_prof_ab_<platform>.json next to this file."""
    _require_chip("--prof-ab")
    import jax
    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 9
    variants = (("off", False, None), ("prof_masked", True, []),
                ("prof_on", True, None))
    out = {"metric": "prof_ab", "platform": platform, "batch": B,
           "steps": steps, "chunk": chunk, "reps": reps,
           "note": ("tiny 2-node workload = worst case for relative "
                    "counter overhead (fixed per-step writes vs tiny "
                    "step); fused runner, lanes never halt, identical "
                    "step counts per variant; reps interleaved "
                    "round-robin, min-of-reps. prof_masked and prof_on "
                    "execute identical compute (masked writes run "
                    "either way) — spread between them is the noise "
                    "floor. Bars: prof_masked <= 3%, off-vs-off "
                    "baseline within noise by construction"),
           "variants": {}}
    seeds = np.arange(B)
    by_prof = {p: _make_light_runtime(profile=p)
               for p in {p for _, p, _ in variants}}
    rts, kws = {}, {}
    for name, prof, lanes in variants:
        rts[name] = by_prof[prof]
        kws[name] = ({} if not prof or lanes is None
                     else {"profile_lanes": lanes})
    for rt in by_prof.values():
        jax.block_until_ready(
            rt.run_fused(rt.init_batch(seeds), steps, chunk).now)
    best = {name: float("inf") for name, _, _ in variants}
    for _ in range(reps):
        for name, _, _ in variants:
            state = rts[name].init_batch(seeds, **kws[name])
            jax.block_until_ready(state.now)
            t0 = time.perf_counter()
            final = rts[name].run_fused(state, steps, chunk)
            jax.block_until_ready(final.now)
            best[name] = min(best[name], time.perf_counter() - t0)
    eps = {name: B * steps / b for name, b in best.items()}
    for name, _, _ in variants:
        out["variants"][name] = round(eps[name], 1)
        print(f"--prof-ab: {name} {eps[name]:,.0f} seed-events/s",
              file=sys.stderr)
    for name in ("prof_masked", "prof_on"):
        out[f"overhead_{name}"] = round(eps["off"] / eps[name] - 1, 4)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_prof_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _prof_smoke_mode():
    """--prof-smoke: seconds-scale profiler self-test for CI (wired into
    scripts/ci.sh fast):

      1. on a seeded chaos run (crash-rich wal_kv, FIXED kill targets)
         the on-device counters must match a host-replayed reference
         computed from the collect_events stream — per-(node, kind)
         dispatch counts and per-node busy time exactly, and the
         kill/restart counters must see the scenario's injections;
      2. profiling must be free of trajectory influence: fingerprints
         equal across profile on/off, and fused == chunked on every
         counter column;
      3. the Perfetto counter tracks must export as valid JSON with
         queue_depth/busy%/cov_divergence tracks alongside the instants;
      4. a small fuzz campaign's rounds must report per-operator
         coverage yield that sums to each round's admissions.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import json as _json
    import tempfile
    from madsim_tpu.core.state import N_EV_KINDS, TRACE_FIELDS
    from madsim_tpu.obs import export_profile_trace, profile_summary
    t0 = time.perf_counter()
    seeds = np.arange(24, dtype=np.uint32)

    # 1+2: counters vs host replay, bit-identity, fused == chunked
    rt = _make_crashrich_runtime("wal_kv", trace_cap=64, sketch_slots=8,
                                 profile=True)
    rt_off = _make_crashrich_runtime("wal_kv", trace_cap=64,
                                     sketch_slots=8)
    chunked, events = rt.run(rt.init_batch(seeds), 4096, 512,
                             collect_events=True)
    fused = rt.run_fused(rt.init_batch(seeds), 4096, 512)
    off, _ = rt_off.run(rt_off.init_batch(seeds), 4096, 512)
    assert (rt.fingerprints(chunked) == rt.fingerprints(fused)).all()
    assert (rt.fingerprints(chunked) == rt_off.fingerprints(off)).all(), \
        "profiling perturbed the trajectory"
    for f in TRACE_FIELDS:
        assert (np.asarray(getattr(chunked, f))
                == np.asarray(getattr(fused, f))).all(), f
    fired = np.asarray(events["fired"])
    now_s = np.asarray(events["now"])
    kind_s = np.asarray(events["kind"])
    node_s = np.asarray(events["node"])
    disp = np.asarray(chunked.pf_dispatch)
    busy = np.asarray(chunked.pf_busy)
    N = rt.cfg.n_nodes
    for b in range(len(seeds)):
        idx = np.nonzero(fired[:, b])[0]
        ref_disp = np.zeros((N, N_EV_KINDS), np.int64)
        ref_busy = np.zeros(N, np.int64)
        prev = 0
        for i in idx:
            nd, kd, nw = int(node_s[i, b]), int(kind_s[i, b]), \
                int(now_s[i, b])
            ref_disp[nd, kd] += 1
            ref_busy[nd] += nw - prev
            prev = nw
        assert (disp[b] == ref_disp).all(), (b, disp[b], ref_disp)
        assert (busy[b] == ref_busy).all(), (b, busy[b], ref_busy)
    kills = np.asarray(chunked.pf_kill)
    assert (kills[:, 0] >= 1).all(), "scheduled kills of node 0 not seen"
    assert int(np.asarray(chunked.pf_qmax).max()) > 0
    summ = profile_summary(chunked)
    assert summ["dispatches"] == int(disp.sum())

    # 3: Perfetto counter tracks next to the instants
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "prof.json")
        n_inst = export_profile_trace(p, fused, lane=0)
        with open(p) as f:
            doc = _json.load(f)
        counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
        names = {e["name"] for e in counters}
        assert "queue_depth" in names and "cov_divergence" in names
        assert any(nm.startswith("busy_pct:") for nm in names), names
        assert n_inst == len([e for e in doc["traceEvents"]
                              if e.get("ph") == "i"]) > 0

    # 4: operator-yield attribution sums to admissions, every round
    import io
    from madsim_tpu.obs import JsonlObserver
    from madsim_tpu.search.fuzz import fuzz
    srt = _make_saturating_runtime()
    obs = JsonlObserver(io.StringIO())
    res = fuzz(srt, max_steps=400, batch=32, max_rounds=4, dry_rounds=9,
               chunk=128, rng_seed=0, observer=obs)
    rounds = [r for r in obs.records if r.get("kind") == "fuzz_round"]
    assert rounds, "no fuzz rounds observed"
    for rec in rounds:
        assert sum(rec["op_yield"].values()) == rec["admitted"], rec
    assert sum(res["mutation_yield"].values()) \
        == sum(r["admitted"] for r in rounds)
    mutated_yield = sum(v for k, v in res["mutation_yield"].items()
                        if k != "base")
    assert res["corpus_energy"]["entries"] == res["corpus_size"]
    print(_json.dumps({
        "metric": "prof_smoke", "platform": "cpu", "ok": True,
        "lanes_checked": int(len(seeds)),
        "dispatches": int(disp.sum()),
        "kills_seen": int(kills[:, 0].sum()),
        "counter_tracks": sorted(names),
        "admitted_total": int(sum(r["admitted"] for r in rounds)),
        "mutant_yield": int(mutated_yield),
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _lat_ab_mode():
    """--mode lat_ab: SLO latency-plane overhead A/B on the fused
    runner — the obs_ab/prof_ab protocol exactly (worst-case tiny step,
    interleaved min-of-9 reps). Three builds, identical trajectories by
    construction (the histogram writes consume no randomness):

      off         latency_hist=0 — plane compiled out (baseline)
      lat_masked  latency_hist=24 + completions compiled in, NO lanes
                  recorded — the cost of carrying the histogram columns,
                  the ev_root_t broadcast, and the masked saturating
                  folds; the ship-with-it shape, bar ≤3% at B=512
      lat_on      every lane records (the ceiling)

    Writes BENCH_lat_ab_<platform>.json next to this file."""
    _require_chip("--lat-ab")
    import jax
    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 9
    variants = (("off", 0, None), ("lat_masked", 24, []),
                ("lat_on", 24, None))
    out = {"metric": "lat_ab", "platform": platform, "batch": B,
           "steps": steps, "chunk": chunk, "reps": reps,
           "note": ("tiny 2-node workload = worst case for relative "
                    "latency-plane overhead (fixed per-step folds + the "
                    "ev_root_t emission broadcast vs tiny step); fused "
                    "runner, lanes never halt, identical step counts "
                    "per variant; reps interleaved round-robin, "
                    "min-of-reps. lat_masked and lat_on execute "
                    "identical compute (masked folds run either way) — "
                    "spread between them is the noise floor. Bar: "
                    "lat_masked <= 3% MODULO this host's cross-run "
                    "envelope — as with causal_ab (DESIGN §12), "
                    "repeated runs here have measured the SAME variant "
                    "pair from +3.6% to -1.2%, so single-run numbers "
                    "cannot resolve 3% on this CPU; the honest claim "
                    "is overhead within that envelope, and the "
                    "masked-vs-on spread (identical compute) bounds "
                    "the floor"),
           "variants": {}}
    seeds = np.arange(B)
    by_lat = {lat: _make_light_runtime(latency_hist=lat)
              for lat in {lat for _, lat, _ in variants}}
    rts, kws = {}, {}
    for name, lat, lanes in variants:
        rts[name] = by_lat[lat]
        kws[name] = ({} if not lat or lanes is None
                     else {"latency_lanes": lanes})
    for rt in by_lat.values():
        jax.block_until_ready(
            rt.run_fused(rt.init_batch(seeds), steps, chunk).now)
    best = {name: float("inf") for name, _, _ in variants}
    for _ in range(reps):
        for name, _, _ in variants:
            state = rts[name].init_batch(seeds, **kws[name])
            jax.block_until_ready(state.now)
            t0 = time.perf_counter()
            final = rts[name].run_fused(state, steps, chunk)
            jax.block_until_ready(final.now)
            best[name] = min(best[name], time.perf_counter() - t0)
    eps = {name: B * steps / b for name, b in best.items()}
    for name, _, _ in variants:
        out["variants"][name] = round(eps[name], 1)
        print(f"--lat-ab: {name} {eps[name]:,.0f} seed-events/s",
              file=sys.stderr)
    for name in ("lat_masked", "lat_on"):
        out[f"overhead_{name}"] = round(eps["off"] / eps[name] - 1, 4)
    # lat_masked and lat_on run the SAME executable on different lh_on
    # values (identical compute — masked folds execute either way), so
    # their pooled best is the honest program cost vs off — the
    # causal_ab precedent (DESIGN §12) for hosts whose per-variant
    # spread exceeds the bar being measured
    pooled = max(eps["lat_masked"], eps["lat_on"])
    out["overhead_lat_program"] = round(eps["off"] / pooled - 1, 4)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_lat_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _lat_smoke_mode():
    """--lat-smoke: seconds-scale latency-plane self-test for CI (wired
    into scripts/ci.sh fast):

      1. on a direct-reply rpc_echo workload the digest's merged e2e
         histogram must EQUAL a host walk of the flight-recorder ring
         (tr_lat records every completion's latency; full-size ring =
         complete history), and the ring latencies must match a
         parent-walk reconstruction (now(completion) − now(root)) —
         the root-inheritance rule, checked end to end;
      2. the plane must be free of trajectory influence: fingerprints
         equal across on/compiled-out, fused == chunked on every
         latency column;
      3. the SLO invariant roundtrip: a runtime with
         slo_invariant(p99_le=) crashes with CRASH_SLO, twice
         identically, and the (seed, knobs-free) repro replays;
      4. the Perfetto export must carry a rolling e2e_p99 counter track
         next to the instants.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import json as _json
    import tempfile
    from madsim_tpu import (CRASH_SLO, NetConfig, Scenario, SimConfig,
                            ms, sec, slo_invariant)
    from madsim_tpu.core.state import TRACE_FIELDS
    from madsim_tpu.core.types import EV_MSG
    from madsim_tpu.models.rpc_echo import TAG_ECHO, make_echo_runtime
    from madsim_tpu.net import rpc
    from madsim_tpu.obs import export_profile_trace, ring_records
    from madsim_tpu.parallel.stats import latency_counters
    t0 = time.perf_counter()
    seeds = np.arange(16, dtype=np.uint32)
    rtag = rpc.reply_tag(TAG_ECHO)

    def make(lat, invariant=None):
        sc = Scenario()
        sc.at(ms(300)).kill(0)
        sc.at(ms(420)).restart(0)
        cfg = SimConfig(
            n_nodes=4, event_capacity=64, time_limit=sec(5),
            latency_hist=24 if lat else 0, trace_cap=512 if lat else 0,
            # reply delivery completes a call AND roots the next one
            complete_kinds=(((EV_MSG, rtag),) if lat else ()),
            root_kinds=(((EV_MSG, rtag),) if lat else ()),
            net=NetConfig(send_latency_min=ms(1), send_latency_max=ms(8)))
        rt = make_echo_runtime(n_nodes=4, target=8, scenario=sc, cfg=cfg)
        if invariant is not None:
            from madsim_tpu import Runtime
            rt = Runtime(cfg, rt.programs, rt.state_spec,
                         node_prog=rt.node_prog, scenario=sc,
                         invariant=invariant, halt_when=rt._halt_when)
        return rt

    # 1+2: digest == ring == parent-walk reference; bit-identity
    rt = make(lat=True)
    rt_off = make(lat=False)
    chunked, _ = rt.run(rt.init_batch(seeds), 2048, 256)
    fused = rt.run_fused(rt.init_batch(seeds), 2048, 256)
    off, _ = rt_off.run(rt_off.init_batch(seeds), 2048, 256)
    assert (rt.fingerprints(chunked) == rt.fingerprints(fused)).all()
    assert (rt.fingerprints(chunked) == rt_off.fingerprints(off)).all(), \
        "latency plane perturbed the trajectory"
    for f in TRACE_FIELDS:
        assert (np.asarray(getattr(chunked, f))
                == np.asarray(getattr(fused, f))).all(), f
    c = latency_counters(chunked)
    e2e = c["e2e_hist"].sum(0)
    checked = 0
    for b in range(len(seeds)):
        recs = ring_records(chunked, b)
        assert recs["dropped"] == 0, "ring must hold the whole history"
        lat = np.asarray(recs["lat"])
        done = lat >= 0
        # parent-walk reference: completion.now − root.now, roots =
        # external or root-kind dispatches (here: reply deliveries)
        step_at = {int(s): i for i, s in enumerate(recs["step"])}
        for i in np.nonzero(done)[0]:
            j, root_now = int(i), None
            while True:
                p = int(recs["parent"][j])
                if p < 0 or p not in step_at:
                    root_now = int(recs["now"][j])   # external root
                    break
                jp = step_at[p]
                if (int(recs["kind"][jp]) == EV_MSG
                        and int(recs["tag"][jp]) == rtag):
                    # parent was a completion→root re-mint
                    root_now = int(recs["now"][jp])
                    break
                j = jp
            want = int(recs["now"][i]) - root_now
            assert int(lat[i]) == want, (b, int(i), int(lat[i]), want)
            checked += 1
        # ring → histogram: bucket the ring's latencies and compare
        ref = np.zeros(len(e2e), np.int64)
        for v in lat[done]:
            bkt = 0 if v == 0 else min(int(v).bit_length(), len(e2e) - 1)
            ref[bkt] += 1
        per_lane = np.asarray(chunked.lh_e2e)[b].sum(0)
        assert (per_lane == ref).all(), (b, per_lane, ref)
    assert checked > 0 and int(e2e.sum()) > 0
    # the digest's MERGE is exactly the sum of the per-lane histograms
    assert (np.asarray(c["e2e_hist"])
            == np.asarray(chunked.lh_e2e).sum(0)).all()

    # 3: SLO invariant roundtrip — deterministic CRASH_SLO + replay
    rt_slo = make(lat=True,
                  invariant=slo_invariant(p99_le=ms(1), min_count=4))
    s1 = rt_slo.run_fused(rt_slo.init_batch(seeds), 2048, 256)
    s2 = rt_slo.run_fused(rt_slo.init_batch(seeds), 2048, 256)
    codes = np.asarray(s1.crash_code)
    assert (codes == CRASH_SLO).all(), codes
    assert (np.asarray(s2.crash_code) == codes).all()
    assert (rt_slo.fingerprints(s1) == rt_slo.fingerprints(s2)).all()
    single, _ = rt_slo.run_single(int(seeds[3]), 2048, 256)
    assert int(np.asarray(single.crash_code)[0]) == CRASH_SLO

    # 4: Perfetto rolling-p99 track
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "lat.json")
        n_inst = export_profile_trace(p, fused, lane=0)
        with open(p) as f:
            doc = _json.load(f)
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "C"}
        assert any(nm.startswith("e2e_p99:") for nm in names), names
        assert n_inst > 0
    print(_json.dumps({
        "metric": "lat_smoke", "platform": "cpu", "ok": True,
        "lanes_checked": int(len(seeds)),
        "completions": int(e2e.sum()),
        "parent_walks_checked": int(checked),
        "e2e_p99_us": c["e2e_p99"],
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _series_ab_mode():
    """--mode series_ab: windowed-telemetry-plane overhead A/B on the
    fused runner — the obs_ab/lat_ab protocol exactly (worst-case tiny
    step, interleaved min-of-9 reps). Three builds, identical
    trajectories by construction (the window writes consume no
    randomness):

      off            series_windows=0 — plane compiled out (baseline)
      series_masked  series_windows=8 compiled in, NO lanes recording —
                     the cost of carrying the sr_* columns and the
                     masked one-hot window folds; the ship-with-it
                     shape, bar <= 3% at B=512
      series_on      every lane records (the ceiling)

    Also A/Bs burst-guided corpus energy (Corpus.burst_bonus, fed by
    stats.lane_burst's deepest-transient-spike signal) against uniform
    energy at EQUAL budget on the heal-bearing recovery flagship — the
    regime where the interesting mutants are the ones that spike
    deepest before (failing to) recover — reporting each side's
    distinct-schedule coverage and whether the campaign opened a
    CRASH_RECOVERY bucket whose (seed, knobs) handle replays red.
    Writes BENCH_series_ab_<platform>.json next to this file."""
    _require_chip("--series-ab")
    import jax
    from madsim_tpu import CRASH_RECOVERY, fuzz, ms, recovery_invariant
    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 9
    variants = (("off", 0, None), ("series_masked", 8, []),
                ("series_on", 8, None))
    out = {"metric": "series_ab", "platform": platform, "batch": B,
           "steps": steps, "chunk": chunk, "reps": reps,
           "note": ("tiny 2-node workload = worst case for relative "
                    "series-plane overhead (fixed per-step window folds "
                    "vs tiny step); fused runner, lanes never halt, "
                    "identical step counts per variant; reps "
                    "interleaved round-robin, min-of-reps. "
                    "series_masked and series_on execute identical "
                    "compute (masked folds run either way) — spread "
                    "between them is the noise floor. Bar: "
                    "series_masked <= 3% MODULO this host's cross-run "
                    "envelope (the causal_ab/lat_ab caveat, DESIGN "
                    "§12): single-run numbers cannot resolve 3% on a "
                    "shared CPU; read overhead_series_program (pooled "
                    "best over the identical-compute builds)"),
           "variants": {}}
    seeds = np.arange(B)
    by_w = {w: _make_light_runtime(series_windows=w)
            for w in {w for _, w, _ in variants}}
    rts, kws = {}, {}
    for name, w, lanes in variants:
        rts[name] = by_w[w]
        kws[name] = ({} if not w or lanes is None
                     else {"series_lanes": lanes})
    for rt in by_w.values():
        jax.block_until_ready(
            rt.run_fused(rt.init_batch(seeds), steps, chunk).now)
    best = {name: float("inf") for name, _, _ in variants}
    for _ in range(reps):
        for name, _, _ in variants:
            state = rts[name].init_batch(seeds, **kws[name])
            jax.block_until_ready(state.now)
            t0 = time.perf_counter()
            final = rts[name].run_fused(state, steps, chunk)
            jax.block_until_ready(final.now)
            best[name] = min(best[name], time.perf_counter() - t0)
    eps = {name: B * steps / b for name, b in best.items()}
    for name, _, _ in variants:
        out["variants"][name] = round(eps[name], 1)
        print(f"--series-ab: {name} {eps[name]:,.0f} seed-events/s",
              file=sys.stderr)
    for name in ("series_masked", "series_on"):
        out[f"overhead_{name}"] = round(eps["off"] / eps[name] - 1, 4)
    # series_masked and series_on run the SAME executable on different
    # sr_on values (masked folds execute either way), so their pooled
    # best is the honest program cost vs off — the causal_ab precedent
    # (DESIGN §12) for hosts whose per-variant spread exceeds the bar
    pooled = max(eps["series_masked"], eps["series_on"])
    out["overhead_series_program"] = round(eps["off"] / pooled - 1, 4)

    # burst-guided vs uniform corpus energy at equal budget on the
    # heal-bearing recovery flagship: the burst signal (deepest
    # per-window p99 spike) concentrates mutation budget on the lanes
    # that degrade hardest — exactly the neighborhood of the
    # failed-recovery mutants the oracle crashes
    inv = recovery_invariant(p99_le=ms(20), within=4, min_count=8)
    be = {"rounds": 5, "batch": 64, "max_steps": 40000}
    warm = _make_recovery_runtime("heal", invariant=inv)
    fuzz(warm, max_steps=40000, batch=64, max_rounds=2, dry_rounds=3,
         chunk=512)
    for side, bonus in (("uniform", 0.0), ("burst", 1.0)):
        rt = _make_recovery_runtime("heal", invariant=inv)
        t0 = time.perf_counter()
        res = fuzz(rt, max_steps=40000, batch=64, max_rounds=5,
                   dry_rounds=6, chunk=512, burst_bonus=bonus)
        rep = res["crash_repros"].get(CRASH_RECOVERY)
        side_out = {"distinct_schedules": res["distinct_schedules"],
                    "recovery_bucket": rep is not None,
                    "wall_s": round(time.perf_counter() - t0, 2)}
        if rep is not None:
            from madsim_tpu.search.mutate import apply_repro_knobs
            st = rt.init_batch(np.asarray([rep["seed"]], np.uint32))
            st, _ = apply_repro_knobs(rt, st, rep["knobs"])
            fin = rt.run_fused(st, 60000, 512)
            side_out["recovery_repro"] = {
                "seed": rep["seed"], "round": rep["round"],
                "replay_code": int(np.asarray(fin.crash_code)[0])}
        be[side] = side_out
        print(f"--series-ab: energy/{side} "
              f"{res['distinct_schedules']} schedules, recovery bucket: "
              f"{side_out['recovery_bucket']}", file=sys.stderr)
    be["burst_vs_uniform"] = round(
        be["burst"]["distinct_schedules"]
        / max(be["uniform"]["distinct_schedules"], 1), 3)
    out["burst_energy"] = be
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_series_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _series_smoke_mode():
    """--series-smoke: seconds-scale windowed-telemetry self-test for CI
    (wired into scripts/ci.sh fast):

      1. on a direct-reply rpc_echo workload whose full-size ring holds
         the complete history, every lane's device series must EQUAL a
         host replay of the ring bucketed by the window rule
         (min(now // window_len, W-1)): per-(window, node) dispatches,
         per-window completions, and the per-window latency histograms;
      2. the plane must be free of trajectory influence: fingerprints
         equal across on/masked/compiled-out, fused == chunked on every
         trace column, masked lanes accumulate nothing;
      3. the batch-merged series digest must be the exact sum/max of
         the recording lanes' columns, and drop to zero lanes when all
         are masked;
      4. the recovery-oracle roundtrip on the canonical flagship
         (_make_recovery_runtime): the healed recipe stays green with
         its post-heal windows genuinely judged, the unhealed recipe
         crashes CRASH_RECOVERY twice identically with equal
         fingerprints, and the single-lane seed replay crashes red too;
      5. the Perfetto export must carry TRUE sim-time counter tracks
         (queue_depth / e2e_p99 / fault at window-start timestamps);
      6. a burst-guided fuzz campaign over the heal-bearing recipe must
         open a CRASH_RECOVERY bucket whose (seed, knobs) handle
         replays red.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import json as _json
    import tempfile
    from madsim_tpu import (CRASH_RECOVERY, NetConfig, SimConfig, fuzz,
                            ms, sec, recovery_invariant)
    from madsim_tpu.core.state import TRACE_FIELDS
    from madsim_tpu.core.types import EV_MSG
    from madsim_tpu.models.rpc_echo import TAG_ECHO, make_echo_runtime
    from madsim_tpu.net import rpc
    from madsim_tpu.obs import (export_profile_trace, format_series,
                                ring_records, series_summary)
    from madsim_tpu.parallel.stats import series_counters
    t0 = time.perf_counter()
    rtag = rpc.reply_tag(TAG_ECHO)
    seeds = np.arange(8, dtype=np.uint32)

    def make_small(windows):
        cfg = SimConfig(n_nodes=4, event_capacity=64, time_limit=sec(3),
                        latency_hist=24 if windows else 0,
                        trace_cap=2048 if windows else 0,
                        series_windows=windows, window_len=ms(150),
                        complete_kinds=((EV_MSG, rtag),) if windows
                        else (),
                        root_kinds=((EV_MSG, rtag),) if windows else (),
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(8)))
        return make_echo_runtime(n_nodes=4, target=40, cfg=cfg)

    # 1+2: device series == host ring replay; bit-identity on/masked/off
    rt = make_small(4)
    rt_off = make_small(0)
    chunked, _ = rt.run(rt.init_batch(seeds), 8192, 512)
    fused = rt.run_fused(rt.init_batch(seeds), 8192, 512)
    masked = rt.run_fused(rt.init_batch(seeds, series_lanes=[]),
                          8192, 512)
    off, _ = rt_off.run(rt_off.init_batch(seeds), 8192, 512)
    assert (rt.fingerprints(chunked) == rt.fingerprints(fused)).all()
    assert (rt.fingerprints(chunked) == rt.fingerprints(masked)).all()
    assert (rt.fingerprints(chunked) == rt_off.fingerprints(off)).all(), \
        "series plane perturbed the trajectory"
    for f in TRACE_FIELDS:
        assert (np.asarray(getattr(chunked, f))
                == np.asarray(getattr(fused, f))).all(), f
    for f in ("sr_dispatch", "sr_busy", "sr_qhw", "sr_drop", "sr_dup",
              "sr_complete", "sr_slo_miss", "sr_lat", "sr_fault"):
        assert not np.asarray(getattr(masked, f)).any(), f
    W, wl = 4, ms(150)
    disp = np.asarray(chunked.sr_dispatch)     # [B, W, N]
    comp = np.asarray(chunked.sr_complete)     # [B, W]
    slat = np.asarray(chunked.sr_lat)          # [B, W, LB]
    replayed = 0
    for b in range(len(seeds)):
        recs = ring_records(chunked, b)
        assert recs["dropped"] == 0, "ring must hold the whole history"
        w_of = np.minimum(np.asarray(recs["now"]) // wl, W - 1)
        ref_d = np.zeros(disp.shape[1:], np.int64)
        for w, n in zip(w_of, np.asarray(recs["node"])):
            ref_d[int(w), int(n)] += 1
        assert (disp[b] == ref_d).all(), (b, disp[b], ref_d)
        lat = np.asarray(recs["lat"])
        done = lat >= 0
        ref_c = np.zeros(W, np.int64)
        ref_l = np.zeros(slat.shape[1:], np.int64)
        for w, v in zip(w_of[done], lat[done]):
            ref_c[int(w)] += 1
            bkt = (0 if v == 0
                   else min(int(v).bit_length(), slat.shape[2] - 1))
            ref_l[int(w), bkt] += 1
        assert (comp[b] == ref_c).all(), (b, comp[b], ref_c)
        assert (slat[b] == ref_l).all(), b
        replayed += int(done.sum())
    assert replayed > 0

    # 3: batch merge == sum/max over recording lanes; masked drops out
    c = series_counters(chunked)
    assert c is not None and c["lanes"] == len(seeds)
    assert (np.asarray(c["dispatch"]) == disp.sum(0)).all()
    assert (np.asarray(c["complete"]) == comp.sum(0)).all()
    assert (np.asarray(c["qhw"])
            == np.asarray(chunked.sr_qhw).max(0)).all()
    cm = series_counters(masked)
    assert cm["lanes"] == 0 and not np.asarray(cm["dispatch"]).any()
    table = format_series(series_summary(chunked))
    assert "p99_us" in table

    # 4: recovery-oracle roundtrip on the canonical flagship
    inv = recovery_invariant(p99_le=ms(20), within=4, min_count=8)
    rt_green = _make_recovery_runtime("heal", invariant=inv)
    g1 = rt_green.run_fused(rt_green.init_batch(seeds), 60000, 512)
    assert (np.asarray(g1.crash_code) == 0).all(), \
        np.asarray(g1.crash_code)
    # green lanes outlive the full window timeline — the post-heal
    # windows were genuinely judged, not skipped
    assert (np.asarray(g1.now) >= 8 * ms(625)).all()
    from madsim_tpu.core.types import SRF_HEAL, SRF_PARTITION
    fw = np.asarray(g1.sr_fault)[0]
    assert fw[1] & SRF_PARTITION and fw[4] & SRF_HEAL, fw
    rt_red = _make_recovery_runtime("noheal", invariant=inv)
    r1 = rt_red.run_fused(rt_red.init_batch(seeds), 60000, 512)
    r2 = rt_red.run_fused(rt_red.init_batch(seeds), 60000, 512)
    codes = np.asarray(r1.crash_code)
    assert (codes == CRASH_RECOVERY).all(), codes
    assert (np.asarray(r2.crash_code) == codes).all()
    assert (rt_red.fingerprints(r1) == rt_red.fingerprints(r2)).all()
    single, _ = rt_red.run_single(int(seeds[3]), 60000, 512)
    assert int(np.asarray(single.crash_code)[0]) == CRASH_RECOVERY

    # 5: true sim-time counter tracks next to the instants
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "series.json")
        export_profile_trace(p, g1, lane=0)
        with open(p) as f:
            doc = _json.load(f)
        cevs = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
        names = {e["name"] for e in cevs}
        assert {"queue_depth", "e2e_p99", "fault"} <= names, names
        qd = [e["ts"] for e in cevs if e["name"] == "queue_depth"]
        assert qd == sorted(qd) and qd[1] - qd[0] == ms(625), qd[:3]

    # 6: burst-guided fuzz opens a CRASH_RECOVERY bucket, replays red
    rt_fz = _make_recovery_runtime("heal", invariant=inv)
    res = fuzz(rt_fz, max_steps=40000, batch=64, max_rounds=3,
               dry_rounds=4, chunk=512, burst_bonus=1.0)
    rep = res["crash_repros"].get(CRASH_RECOVERY)
    assert rep is not None, sorted(res["crash_repros"])
    from madsim_tpu.search.mutate import apply_repro_knobs
    st = rt_fz.init_batch(np.asarray([rep["seed"]], np.uint32))
    st, _ = apply_repro_knobs(rt_fz, st, rep["knobs"])
    fin = rt_fz.run_fused(st, 60000, 512)
    assert int(np.asarray(fin.crash_code)[0]) == CRASH_RECOVERY
    print(_json.dumps({
        "metric": "series_smoke", "platform": "cpu", "ok": True,
        "lanes_checked": int(len(seeds)),
        "ring_replayed_completions": int(replayed),
        "recovery_repro": {"seed": rep["seed"], "round": rep["round"]},
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _causal_ab_mode():
    """--mode causal_ab: causal-lineage + prefix-sketch overhead A/B on
    the fused runner, same protocol as obs_ab (interleaved min-of-reps
    on the worst-case tiny step). Four builds, identical trajectories by
    construction (lineage/sketch consume no randomness):

      off             trace_cap=0, sketch_slots=0 — everything compiled
                      out (the r9 baseline)
      lineage_masked  trace_cap=64 + sketch_slots=16 compiled in, NO
                      lanes sampled — the cost of the lineage column
                      writes, the Lamport update, the sketch fold, and
                      the masked-off ring write
      lineage_8       same build, 8 of B lanes sampled (production shape)
      lineage_all     every lane samples (the ceiling)

    The acceptance bar is overhead_lineage_masked <= 3% at B=512:
    shipping with lineage compiled in and flipping lanes on per-sweep
    must be ~free. Also A/Bs divergence-aware corpus energy
    (Corpus.div_bonus, fed by the sketch) against sched_hash-only
    energy at equal budget on the saturating regime — the fuzzer side
    must match or beat. Writes BENCH_causal_ab_<platform>.json."""
    _require_chip("--causal-ab")
    import jax
    from madsim_tpu import fuzz
    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 15
    variants = (("off", 0, None), ("lineage_masked", 64, []),
                ("lineage_8", 64, list(range(8))), ("lineage_all", 64, None))
    out = {"metric": "causal_ab", "platform": platform, "batch": B,
           "steps": steps, "chunk": chunk, "reps": reps, "trace_cap": 64,
           "sketch_slots": 16,
           "note": ("tiny 2-node workload = worst case for relative "
                    "lineage overhead (fixed per-step cost vs tiny "
                    "step); fused runner, lanes never halt, so every "
                    "variant executes identical step counts; reps "
                    "INTERLEAVED round-robin, min-of-reps per variant. "
                    "The three lineage builds execute identical compute "
                    "(masked writes run either way), so spread among "
                    "them is the noise floor. READ "
                    "overhead_lineage_program (pooled best over the "
                    "three identical-compute builds), not any single "
                    "variant: on the shared CPU host this was measured "
                    "on, identical-compute variants spread up to 8 "
                    "points across runs, the same source measured "
                    "139k-167k eps in different processes, and a "
                    "control build doing STRICTLY MORE work than `off` "
                    "(r7 ring written, lineage leaves removed) measured "
                    "5.7% FASTER than `off` in an interleaved run - "
                    "XLA CPU executable quality under buffer-layout "
                    "changes dominates the lineage arithmetic, which "
                    "phase-isolation could not distinguish from zero"),
           "variants": {}}
    seeds = np.arange(B)
    by_cap = {cap: _make_light_runtime(trace_cap=cap,
                                       sketch_slots=16 if cap else 0)
              for cap in {c for _, c, _ in variants}}
    rts, kws = {}, {}
    for name, cap, lanes in variants:
        rts[name] = by_cap[cap]
        kws[name] = ({} if cap == 0 or lanes is None
                     else {"trace_lanes": lanes})
    for cap, rt in by_cap.items():
        jax.block_until_ready(
            rt.run_fused(rt.init_batch(seeds), steps, chunk).now)
    best = {name: float("inf") for name, _, _ in variants}
    for _ in range(reps):
        for name, _, _ in variants:
            state = rts[name].init_batch(seeds, **kws[name])
            jax.block_until_ready(state.now)
            t0 = time.perf_counter()
            final = rts[name].run_fused(state, steps, chunk)
            jax.block_until_ready(final.now)
            best[name] = min(best[name], time.perf_counter() - t0)
    eps = {name: B * steps / b for name, b in best.items()}
    for name, _, _ in variants:
        out["variants"][name] = round(eps[name], 1)
        print(f"--causal-ab: {name} {eps[name]:,.0f} seed-events/s",
              file=sys.stderr)
    for name in ("lineage_masked", "lineage_8", "lineage_all"):
        out[f"overhead_{name}"] = round(eps["off"] / eps[name] - 1, 4)
    # the headline number: the three lineage variants run ONE executable
    # (same cfg; trace_lanes only changes the trace_on DATA, and masked
    # writes execute either way), so their pooled best time is the best
    # estimate of that program's cost — 3x the samples of any one
    # variant's min. Per-variant spread above is the measurement noise
    # floor, not a masked-vs-sampled cost difference.
    lineage_best = min(best[n]
                       for n in ("lineage_masked", "lineage_8",
                                 "lineage_all"))
    out["overhead_lineage_program"] = round(
        lineage_best / best["off"] - 1, 4)
    print(f"--causal-ab: lineage program overhead (pooled) "
          f"{out['overhead_lineage_program']:+.2%}", file=sys.stderr)

    # divergence-aware corpus energy vs sched_hash-only, equal budget on
    # the saturating regime (the workload where energy scheduling
    # matters — blind sampling is dry after round 0 there)
    de = {"rounds": 5, "batch": 128, "max_steps": 1500}
    warm = _make_saturating_runtime(sketch_slots=16)
    fuzz(warm, max_steps=1500, batch=128, max_rounds=2, dry_rounds=3,
         chunk=256)
    for side, bonus in (("hash_only", 0.0), ("divergence", 1.0)):
        rt = _make_saturating_runtime(sketch_slots=16)
        t0 = time.perf_counter()
        res = fuzz(rt, max_steps=1500, batch=128, max_rounds=5,
                   dry_rounds=6, chunk=256, div_bonus=bonus)
        de[side] = {"distinct_schedules": res["distinct_schedules"],
                    "wall_s": round(time.perf_counter() - t0, 2),
                    "new_per_round": res["new_per_round"]}
        print(f"--causal-ab: energy/{side} "
              f"{res['distinct_schedules']} schedules", file=sys.stderr)
    de["divergence_vs_hash_only"] = round(
        de["divergence"]["distinct_schedules"]
        / max(de["hash_only"]["distinct_schedules"], 1), 3)
    out["divergence_energy"] = de
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_causal_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _causal_smoke_mode():
    """--causal-smoke: seconds-scale causal-lineage self-test for CI
    (wired into scripts/ci.sh fast):

      1. lineage + sketch compiled in but masked off must leave every
         non-trace leaf bit-identical to the compiled-out build, across
         the chunked AND fused runners (the r10 never-perturb contract);
      2. a fuzzer-harvested crash on the crash-rich wal_kv matrix must
         replay from its (seed, knobs) handle and explain itself: a
         non-empty parent chain ending at the crash dispatch, and a
         Perfetto export of that lane containing flow arrows;
      3. summarize() must report the first_divergence profile from the
         on-device sketches.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import json as _json
    import tempfile
    from madsim_tpu import explain_crash, fuzz, summarize
    from madsim_tpu.core.state import TRACE_FIELDS
    from madsim_tpu.obs import export_chrome_trace
    from madsim_tpu.search.mutate import KnobPlan
    t0 = time.perf_counter()

    # 1. never-perturb: off vs compiled-in-masked-off, both runners
    seeds = np.arange(16)
    rt_off = _make_light_runtime(n_nodes=4, loss=0.05)
    rt_on = _make_light_runtime(n_nodes=4, loss=0.05, trace_cap=32,
                                sketch_slots=8)
    for runner in ("run", "run_fused"):
        if runner == "run":
            a, _ = rt_off.run(rt_off.init_batch(seeds), 192, 64)
            b, _ = rt_on.run(rt_on.init_batch(seeds, trace_lanes=[]),
                             192, 64)
        else:
            a = rt_off.run_fused(rt_off.init_batch(seeds), 192, 64)
            b = rt_on.run_fused(rt_on.init_batch(seeds, trace_lanes=[]),
                                192, 64)
        assert (rt_off.fingerprints(a) == rt_on.fingerprints(b)).all(), \
            f"lineage/sketch build perturbed the trajectory ({runner})"
        for f in type(a).__dataclass_fields__:
            if f in TRACE_FIELDS or f in ("node_state", "ext"):
                continue
            assert (np.asarray(getattr(a, f))
                    == np.asarray(getattr(b, f))).all(), (runner, f)

    # 2. fuzzer-harvested crash -> replay -> explain -> flow arrows
    rt = _make_crashrich_runtime("wal_kv", trace_cap=64, sketch_slots=8)
    res = fuzz(rt, max_steps=4096, batch=48, max_rounds=2, dry_rounds=3,
               chunk=512)
    assert res["crash_repros"], "crash-rich matrix produced no crash"
    code, rep = sorted(res["crash_repros"].items())[0]
    plan = KnobPlan.from_runtime(rt)
    st = plan.apply(rt.init_batch(np.asarray([rep["seed"]], np.uint32)),
                    KnobPlan.stack([rep["knobs"]]))
    final = rt.run_fused(st, 4096, 512)
    assert bool(np.asarray(final.crashed)[0]), "repro did not replay"
    exp = explain_crash(final, 0)
    assert exp["chain"], "empty causal chain"
    assert exp["chain"][-1]["step"] == int(np.asarray(final.steps)[0]) - 1, \
        "chain does not end at the crash dispatch"
    assert exp["crash_code"] == code
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "crash.json")
        export_chrome_trace(p, state=final, lane=0)
        with open(p) as fh:
            doc = _json.load(fh)
        flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
        assert flows, "no flow arrows in the crash lane's export"
        starts = {e["id"] for e in flows if e["ph"] == "s"}
        ends = {e["id"] for e in flows if e["ph"] == "f"}
        assert starts == ends, "unpaired flow arrows"

    # 3. divergence telemetry off the sketches
    sweep = rt.run_fused(rt.init_batch(np.arange(32, dtype=np.uint32)),
                         4096, 512)
    prof = summarize(rt, sweep)["first_divergence"]
    assert prof is not None and prof["diverged"] > 0, prof
    print(_json.dumps({
        "metric": "causal_smoke", "platform": "cpu", "ok": True,
        "crash_code": int(code), "chain_len": len(exp["chain"]),
        "chain_truncated": exp["truncated"], "flow_events": len(flows),
        "first_divergence_p50": prof.get("p50"),
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _detsan_ab_mode():
    """--mode detsan_ab: determinism-sanitizer overhead A/B at B=512
    (harness/simtest.detsan_check vs one plain sweep; the ISSUE-8 /
    DESIGN §14 contract is <= ~2x wall — the sanitizer is two full
    sweeps through ONE shared executable plus a host-side leaf diff,
    and both sweeps are dispatched before either is forced, so any
    backend-side overlap lands below 2x). Interleaved min-of-reps, same
    protocol as obs_ab; writes BENCH_detsan_ab_<platform>.json."""
    _require_chip("--detsan-ab")
    import jax
    from madsim_tpu.harness.simtest import detsan_check
    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 5
    rt = _make_light_runtime(n_nodes=2)
    seeds = np.arange(B)
    # warmup: compiles the one fused program both sides share
    jax.block_until_ready(
        rt.run_fused(rt.init_batch(seeds), steps, chunk).now)
    best = {"run": float("inf"), "detsan": float("inf")}
    for _ in range(reps):
        t0 = time.perf_counter()
        final = rt.run_fused(rt.init_batch(seeds), steps, chunk)
        jax.block_until_ready(final.now)
        best["run"] = min(best["run"], time.perf_counter() - t0)
        t0 = time.perf_counter()
        # raises DetSanFailure on any diff — a flagged clean runtime
        # fails the bench loudly rather than publishing a wrong number
        rep = detsan_check(rt, seeds, steps, chunk, fused=True)
        best["detsan"] = min(best["detsan"], time.perf_counter() - t0)
    overhead = best["detsan"] / best["run"]
    out = {
        "metric": "detsan_ab", "platform": platform, "batch": B,
        "steps": steps, "chunk": chunk, "reps": reps,
        "wall_run_s": round(best["run"], 4),
        "wall_detsan_s": round(best["detsan"], 4),
        "overhead_detsan": round(overhead, 3),
        "vs_double_run": round(best["detsan"] / (2 * best["run"]), 3),
        "leaves_compared": rep["leaves"],
        "note": ("detsan = identity sweep + permuted-lane sweep (one "
                 "shared executable, both dispatched before either is "
                 "forced) + leaf-for-leaf host diff; overhead_detsan is "
                 "wall vs ONE plain fused sweep — the <=2x sanitizer "
                 "contract of DESIGN §14; vs_double_run isolates the "
                 "diff+dispatch overhead above the two sweeps "
                 "themselves (1.0 = free)"),
    }
    print(f"--detsan-ab: run {best['run']:.3f}s detsan "
          f"{best['detsan']:.3f}s overhead {overhead:.2f}x",
          file=sys.stderr)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_detsan_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _analyze_smoke_mode():
    """--analyze-smoke: seconds-scale DetSan self-test for CI (wired
    into scripts/ci.sh fast):

      1. the lint gate: a planted-hazard source must trip every AST
         rule (positive control — a silently toothless linter passes
         any repo), and the repo-wide gate over madsim_tpu/ + examples/
         must be CLEAN (the `python -m madsim_tpu.analyze` contract);
      2. a confirmed-race roundtrip on the race-rich wal_kv mutant:
         candidates from the happens-before rings, forced-commute
         confirmation via the PCT nudge, a (seed, knobs, nudge) repro
         that REPLAYS to the confirming lane's exact fingerprint, and
         bucket dedup (a rescan must not open new buckets);
      3. a detsan double-run over a clean runtime must pass, and the
         leaf differ must catch a planted single-lane perturbation.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import tempfile
    from madsim_tpu.analyze.lint import active, lint_paths, lint_source
    from madsim_tpu.analyze.races import replay_race, scan_races
    from madsim_tpu.harness.simtest import detsan_check, diff_states
    from madsim_tpu.search.mutate import KnobPlan
    from madsim_tpu.service.buckets import CrashBuckets
    from madsim_tpu.service.store import CorpusStore, store_signature
    t0 = time.perf_counter()

    # 1. lint: positive control, then the repo gate
    planted = (
        "import time, random\n"
        "import numpy as np\n"
        "from madsim_tpu.core.api import Program\n"
        "class Bad(Program):\n"
        "    def on_timer(self, ctx, tag, payload):\n"
        "        t = time.time()\n"
        "        r = np.random.rand()\n"
        "        for x in {1, 2}: pass\n"
        "        import jax\n"
        "        jax.pure_callback(int, None)\n")
    rules = {f.rule for f in active(lint_source(planted, "planted.py"))}
    assert {"host-time", "host-random", "unordered-iter",
            "host-callback"} <= rules, rules
    here = os.path.dirname(os.path.abspath(__file__))
    gate = active(lint_paths([os.path.join(here, "madsim_tpu"),
                              os.path.join(here, "examples")]))
    assert not gate, "repo lint gate dirty:\n" + "\n".join(
        f.format() for f in gate)

    # 2. race roundtrip on the canonical race-rich mutant
    rt = _make_racy_runtime(trace_cap=256)
    plan = KnobPlan.from_runtime(rt)
    seeds = np.arange(32, dtype=np.uint32)
    with tempfile.TemporaryDirectory() as d:
        store = CorpusStore(d, signature=store_signature(rt, plan))
        buckets = CrashBuckets(store)
        res = scan_races(rt, seeds, 20_000, buckets=buckets,
                         max_confirm=4)
        assert res["confirmed"], f"no confirmed race: {res}"
        conf = res["confirmed"][0]
        rep = replay_race(rt, conf["repro"])
        assert rep["fingerprint"] == conf["diff"]["fingerprint"][1], \
            "(seed, knobs, nudge) repro did not replay"
        n_buckets = len(store.bucket_keys())
        res2 = scan_races(rt, seeds, 20_000, buckets=buckets,
                          max_confirm=4)
        assert len(store.bucket_keys()) == n_buckets, \
            "rescan split one race into new buckets"
        repro_rec = store.load_bucket(res["bucket_keys"][0])["repro"]
        assert "nudge" in repro_rec, repro_rec

    # 3. detsan: clean pass + planted-diff catch
    rt2 = _make_light_runtime(n_nodes=4, loss=0.05)
    drep = detsan_check(rt2, np.arange(32), 512, 128)
    assert drep["ok"], drep
    a = rt2.run_fused(rt2.init_batch(np.arange(8)), 256, 64)
    b = a.replace(now=a.now.at[3].add(1))       # the planted violation
    diffs = diff_states(a, b, align=np.arange(8))
    assert diffs and diffs[0]["lanes"] == [3], diffs
    print(json.dumps({
        "metric": "analyze_smoke", "platform": "cpu", "ok": True,
        "lint_rules_tripped": sorted(rules),
        "race_candidates": res["candidates"],
        "races_confirmed": len(res["confirmed"]),
        "race_nudge": conf["nudge"],
        "buckets": n_buckets,
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _fused_smoke_mode():
    """--fused-smoke: seconds-scale fused-runner self-test for CI (wired
    into scripts/ci.sh): tiny shapes through run_fused + the chunked
    runner, asserting bitwise fingerprint equality and a live fused
    explore() round-trip. Runs on the host CPU: a CI check, not a
    device measurement."""
    _force_cpu_inprocess()
    from madsim_tpu.parallel.explore import explore
    t0 = time.perf_counter()
    rt = _make_light_runtime(n_nodes=2)
    seeds = np.arange(64)
    chunked, _ = rt.run(rt.init_batch(seeds), 256, 64)
    fused = rt.run_fused(rt.init_batch(seeds), 256, 64)
    assert (rt.fingerprints(chunked) == rt.fingerprints(fused)).all(), \
        "fused runner diverged from chunked run()"
    res = explore(_make_light_runtime(n_nodes=4, loss=0.05), max_steps=256,
                  batch=64, max_rounds=2, dry_rounds=3, chunk=64,
                  pipeline=True, fused=True)
    assert res["rounds"] == 2 and res["distinct_schedules"] > 0, res
    print(json.dumps({
        "metric": "fused_smoke", "platform": "cpu", "ok": True,
        "distinct_schedules": res["distinct_schedules"],
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _smoke_mode():
    """--smoke: seconds-scale bench self-test for CI (`ci.sh full`). The
    reference runs its criterion benches as a CI job (madsim/benches/
    rpc.rs:11-53, ci.yml bench job) so bench code cannot rot unnoticed;
    this is that guard for bench.py — tiny shapes through the real
    measurement helpers (including their liveness/no-crash/no-overflow
    asserts) plus the native baseline twin. Runs on the host CPU: a CI
    check, not a device measurement."""
    _force_cpu_inprocess()
    t0 = time.perf_counter()
    eps = _events_per_sec(64, 128, 32)
    native = _native_baseline_eps(seeds=8, events_per_seed=2048)
    print(json.dumps({
        "metric": "bench_smoke", "platform": "cpu",
        "cpu_flagship_seed_events_per_sec": round(eps, 1),
        "native_baseline_events_per_sec":
            round(native["events_per_sec"], 1) if native else None,
        "wall_s": round(time.perf_counter() - t0, 1)}))


def _realworld_mode():
    """--realworld: events/sec of the real-world twin on loopback — the
    eager-vs-compiled dispatch A/B (RealRuntime(compiled=)). Independent
    of the TPU: this measures the production-twin path, where the
    reference's compiled Rust sets the bar."""
    # the twin runs on the host next to its sockets — never the
    # accelerator (per-op dispatch to a device would measure transfer
    # latency, not the twin)
    _force_cpu_inprocess()
    from madsim_tpu import SimConfig
    from madsim_tpu.core.types import ms, sec
    from madsim_tpu.models.rpc_echo import (EchoClient, EchoServer,
                                            server_state_spec)
    from madsim_tpu.real.runtime import RealRuntime

    DUR = 6.0
    out = {"metric": "realworld_dispatch_events_per_sec", "platform": "cpu",
           "note": ("asyncio loop + UDP on 1 core bounds all modes — see "
                    "PARITY §2.2 scope paragraph; batched amortizes the "
                    "jit call but not the per-slot XLA work or the "
                    "per-event socket/timer costs"),
           "workloads": {}}
    # two workload shapes x three dispatch modes. pingpong (1 client) has
    # queue depth 1 — batching can't help there by construction; fanout
    # (16 concurrent clients) is where the drain amortizes.
    shapes = {"pingpong": 1, "fanout": 16}
    modes = {"eager": {}, "compiled": {"compiled": True},
             "batched": {"batch_drain": 64}}
    variant_idx = 0
    for wname, n_cli in shapes.items():
        variants = {}
        for mname, kw in modes.items():
            # ports advance exactly once per variant regardless of how
            # far construction/run got (a mid-run failure must not make
            # the next variant reuse sockets or skip a block)
            port = 19900 + 20 * variant_idx
            variant_idx += 1
            try:
                # a target the run can never finish: throughput-bound,
                # not workload-bound (each client issues back-to-back)
                rt = RealRuntime(
                    SimConfig(n_nodes=1 + n_cli, time_limit=sec(600)),
                    [EchoServer(), EchoClient(target=1_000_000,
                                              timeout=ms(500))],
                    server_state_spec(), node_prog=[0] + [1] * n_cli,
                    base_port=port, **kw)
                if kw.get("batch_drain"):
                    rt.drain_delay = 0.002   # coalesce for drain depth
                rt.run(duration=DUR)
                assert not rt.crashed, rt.crashed  # a crash is not a datum
                served = int(rt.states()[0]["served"])
                acked = sum(int(s["acked"]) for s in rt.states()[1:])
                eps = (served + acked) / DUR
                variants[mname] = round(eps, 1)
                print(f"--realworld: {wname}/{mname} {eps:,.0f} "
                      f"handler-events/s (served={served})",
                      file=sys.stderr)
            except Exception as e:  # noqa: BLE001 - partial evidence > none
                variants[mname] = f"{type(e).__name__}: {e}"
        if isinstance(variants.get("eager"), float):
            for m in ("compiled", "batched"):
                if isinstance(variants.get(m), float):
                    variants[f"{m}_speedup_vs_eager"] = round(
                        variants[m] / max(variants["eager"], 1e-9), 2)
        out["workloads"][wname] = variants
    print(json.dumps(out))


def _multihost_mode():
    """--multihost: run the flagship workload sharded over TWO real
    jax.distributed processes (loopback coordinator, CPU devices) and
    report aggregate seed-events/s. This drives the actual DCN code path
    (global array assembly + cross-process reductions) end-to-end; on a
    single-core host the two processes share the core, so the number
    demonstrates the path, not a speedup."""
    import socket
    import tempfile

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    f = tempfile.NamedTemporaryFile("w", suffix=".py", delete=False,
                                    dir=os.path.dirname(
                                        os.path.abspath(__file__)))
    f.write(_MULTIHOST_WORKER)
    f.close()
    try:
        procs = [subprocess.Popen(
            [sys.executable, f.name, str(i), coord],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_cpu_env()) for i in range(2)]
        outs = [p.communicate(timeout=900)[0] for p in procs]
    finally:
        os.unlink(f.name)
    results = [l for o in outs for l in o.splitlines()
               if l.startswith("RESULT")]
    if len(results) != 2:
        raise RuntimeError("multihost worker failed: "
                           + " | ".join(o[-500:] for o in outs))
    walls = [float(r.split("wall=")[1].split()[0]) for r in results]
    eps = 1024 * 256 / max(walls)
    print(json.dumps({
        "metric": "madraft_fuzz_multihost_seed_events_per_sec",
        "platform": "cpu",
        "value": round(eps, 1),
        "unit": "seed*events/s (2 processes x 2 devices, loopback DCN)",
        "processes": 2,
    }))


def _scaling_mode():
    """--scaling: run the sharded path at every mesh size on the virtual
    8-device CPU mesh and report per-config seed-events/s.

    Virtual devices share one host's cores, so this is NOT a speedup
    measurement — it is executable evidence that the SPMD program runs at
    every mesh width (the real-chip expectation is near-linear: lanes are
    independent, so the step body has no cross-device collectives at all;
    ICI traffic only appears in explicit reductions like first_crash_seed).
    """
    from __graft_entry__ import _force_cpu_mesh
    jax = _force_cpu_mesh(8)
    from madsim_tpu.parallel.mesh import seed_mesh, shard_batch
    rt = _make_runtime()
    B, steps = 2048, 256
    rows = []
    for nd in (1, 2, 4, 8):
        devices = [d for d in jax.devices() if d.platform == "cpu"][:nd]
        mesh = seed_mesh(devices)
        runner = rt._run_chunk[False]
        state = shard_batch(rt.init_batch(np.arange(B)), mesh)
        state, _ = runner(state, steps)          # warm/compile
        jax.block_until_ready(state.now)
        state = shard_batch(rt.init_batch(np.arange(B)), mesh)
        t0 = time.perf_counter()
        state, _ = runner(state, steps)
        jax.block_until_ready(state.now)
        eps = B * steps / (time.perf_counter() - t0)
        rows.append({"devices": nd, "seed_events_per_sec": round(eps, 1)})
        print(f"  {nd} device(s): {eps:,.0f} seed-events/s", file=sys.stderr)
    print(json.dumps({
        "metric": "spmd_compile_check_cpu_mesh", "platform": "cpu",
        "note": ("virtual devices on a 1-core host: proves the SPMD "
                 "program compiles and executes at every mesh width; "
                 "NOT scaling evidence — no ICI, no real parallelism"),
        "batch": B, "rows": rows}))


def _shape_sweep_mode():
    """--shape-sweep: throughput vs workload shape on the flagship Raft
    chaos fuzz — one axis varied at a time from the base shape (n=5,
    L=32, P=8, C=96). This measures where DESIGN §5's [batch, C(,P)]
    bandwidth wall and the per-peer emission count (a Raft heartbeat
    stages npeers send slots EVERY step) actually bite."""
    _require_chip("--shape-sweep")
    import jax
    platform = jax.devices()[0].platform
    big = platform != "cpu"
    B = B_TPU if big else 512
    steps = STEPS if big else 256
    warm = WARM if big else 64
    points = ([("base", {})]
              + [(f"n_nodes={n}", {"n_nodes": n}) for n in (15, 25, 64)]
              + [(f"log_capacity={L}", {"log_capacity": L})
                 for L in (16, 64)]
              + [(f"payload_words={P}", {"payload_words": P})
                 for P in (16,)])
    # report the ACTUAL base shape from the runtime under test, not a
    # copy of its defaults that could drift
    base_rt = _make_runtime()
    base = dict(n_nodes=base_rt.cfg.n_nodes,
                log_capacity=int(base_rt.programs[0].L),
                payload_words=base_rt.cfg.payload_words,
                event_capacity=base_rt.cfg.event_capacity)
    out = {"metric": "shape_sweep", "platform": platform, "batch": B,
           "base": base, "points": {}}
    for name, kw in points:
        try:
            eps = _events_per_sec(B, steps, warm,
                                  make=lambda: _make_runtime(**kw))
            out["points"][name] = round(eps, 1)
            print(f"--shape-sweep: {name} {eps:,.0f} seed-events/s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - partial evidence > none
            out["points"][name] = f"{type(e).__name__}: {e}"
            print(f"--shape-sweep: {name} FAILED {e!r}", file=sys.stderr)
    print(json.dumps(out))


def _tt_smoke_mode():
    """--tt-smoke: seconds-scale time-travel-replay self-test for CI
    (wired into scripts/ci.sh fast):

      1. a crash recorded with a 4-slot ring (wrapped, chain truncated)
         must replay from a harvested checkpoint to a COMPLETE
         (`truncated=False`) causal chain, bit-stably twice, with the
         live truncated chain a suffix of it and the fingerprints
         bucket-compatible (deepest-common-suffix);
      2. checkpoint fidelity: a lane re-seeded from a harvest must
         finish fingerprint-identical to the uninterrupted run on the
         fused runner, and the replayed window trace must export;
      3. the divergence microscope must name the SAME first divergent
         dispatch on a re-run of the same pair.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import os as _os
    import tempfile as _tempfile

    import numpy as _np

    from madsim_tpu import CheckpointLog, divergence_report, explain_crash
    from madsim_tpu import seed_batch_from
    from madsim_tpu.obs.causal import causal_fingerprint, fingerprints_match

    rt = _make_crashrich_runtime("wal_kv", trace_cap=4)
    seeds = _np.arange(24, dtype=_np.uint32)

    # uninterrupted control (fused) vs harvested run (chunked): the
    # r20 zero-cost/equivalence contract — harvesting must not perturb
    control = rt.run_fused(rt.init_batch(seeds), 30_000, 16)
    cfp = rt.fingerprints(control)
    log = CheckpointLog()
    state, _ = rt.run(rt.init_batch(seeds), 30_000, 16,
                      ckpt_every=32, ckpt_log=log)
    assert (rt.fingerprints(state) == cfp).all(), \
        "harvesting perturbed the trajectories"
    print(f"--tt-smoke: harvested {len(log)} checkpoints, trajectories "
          "bit-identical to the unharvested fused run", file=sys.stderr)

    # fidelity: re-seed a crashed lane's mid-flight checkpoint and
    # finish — fingerprint-identical to the uninterrupted lane
    crashed = _np.nonzero(_np.asarray(state.crashed))[0]
    steps = _np.asarray(state.steps)
    assert len(crashed), "tt smoke workload found no crash"
    lane = int(crashed[0])
    ck = log.nearest(lane)
    assert ck is not None and ck.steps > 0
    child = rt.run_fused(seed_batch_from(ck, 2), 30_000, 16)
    assert (rt.fingerprints(child) == cfp[lane]).all(), \
        "checkpoint continuation diverged from the parent lane"
    print(f"--tt-smoke: lane {lane} re-seeded from step {ck.steps} "
          "continues fingerprint-identical", file=sys.stderr)

    # the time-travel chain: live truncated -> replayed complete,
    # bit-stable twice, bucket-compatible with the live observation
    lane = next((int(l) for l in crashed
                 if explain_crash(state, int(l))["truncated"]
                 and steps[l] > 40), None)
    assert lane is not None, "no wrap-truncated crash chain to replay"
    live = explain_crash(state, lane)
    tdir = _tempfile.mkdtemp(prefix="tt_smoke_")
    tpath = _os.path.join(tdir, "window.trace.json")
    full = explain_crash(state, lane, replay=True, rt=rt, ckpts=log,
                         export_trace=tpath)
    full2 = explain_crash(state, lane, replay=True, rt=rt, ckpts=log)
    assert not full["truncated"] and full["replayed"], full.keys()
    assert full["chain"] == full2["chain"], \
        "time-travel chain not bit-stable across replays"
    assert full["chain"][-len(live["chain"]):] == live["chain"], \
        "live truncated chain is not a suffix of the replayed chain"
    assert fingerprints_match(causal_fingerprint(full),
                              causal_fingerprint(live)), \
        "replayed-complete chain left its truncated sibling's bucket"
    assert _os.path.getsize(tpath) > 0
    print(f"--tt-smoke: lane {lane} chain {len(live['chain'])} records "
          f"truncated -> {len(full['chain'])} records complete "
          f"(replayed from step {full['from_step']}; window trace "
          "exported)", file=sys.stderr)

    # divergence microscope: deterministic first divergent dispatch
    r1 = divergence_report(rt, 3, 5, max_steps=20_000, chunk=512)
    r2 = divergence_report(rt, 3, 5, max_steps=20_000, chunk=512)
    assert r1["diverged"] and r1["first"] is not None
    assert r1["first"] == r2["first"], \
        "divergence microscope named a different dispatch on re-run"
    f = r1["first"]
    print(f"--tt-smoke: microscope names first divergent dispatch "
          f"step={f['step']} a=(node {f['a']['node']} kind "
          f"{f['a']['kind']}) b=(node {f['b']['node']} kind "
          f"{f['b']['kind']}) [bound={r1['bound']}], stable on re-run",
          file=sys.stderr)
    print(json.dumps({"metric": "tt_smoke", "ok": True,
                      "checkpoints": len(log),
                      "chain_live": len(live["chain"]),
                      "chain_full": len(full["chain"]),
                      "first_divergent_step": f["step"]}))


def _tt_ab_mode():
    """--mode tt_ab: the two costs of the time-travel plane, measured.

    (a) HARVEST OVERHEAD — the obs_ab protocol on the chunked runner
        (the path whose existing syncs the harvest rides): B=512 tiny
        workload, `ckpt_every` on vs off, interleaved min-of-reps. The
        bar is <=3%: periodic owned host copies at chunk boundaries
        must be noise next to the sweep itself.
    (b) WINDOW REPLAY vs FROM-SCRATCH — on a LONG trajectory, recover
        a complete crash chain (i) by window replay from the last
        harvested checkpoint and (ii) by re-running from t=0 with a
        full-size ring. Window replay must be strictly cheaper —
        that's the point of checkpoints.

    Writes BENCH_tt_ab_<platform>.json next to this file."""
    _require_chip("--tt-ab")
    import jax

    import numpy as _np

    from madsim_tpu import CheckpointLog

    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 9
    rt = _make_light_runtime(trace_cap=0)
    seeds = _np.arange(B)
    out = {"metric": "tt_ab", "platform": platform, "batch": B,
           "steps": steps, "chunk": chunk, "reps": reps,
           "note": ("(a) obs_ab protocol on the CHUNKED runner — the "
                    "harvest rides its existing per-chunk syncs; "
                    "ckpt_every=1024 at 2048 steps = 2 mid-flight "
                    "harvests + the entry snapshot, each an owned host "
                    "copy of the full B=512 batch. (b) wall-clock of "
                    "re-executing the final 4096-dispatch window of a "
                    "16k-dispatch trajectory under a full-fidelity "
                    "ring: from the last harvested checkpoint (ring "
                    "sized to the window) vs from t=0 (ring sized to "
                    "the whole trajectory); both land on the identical "
                    "fingerprint, speedup ~ target/(target-ckpt) minus "
                    "fixed derive/seed costs.")}

    def run_once(ck):
        state = rt.init_batch(seeds)
        jax.block_until_ready(state.now)
        t0 = time.perf_counter()
        fin, _ = rt.run(state, steps, chunk,
                        **({"ckpt_every": 1024,
                            "ckpt_log": CheckpointLog()} if ck else {}))
        jax.block_until_ready(fin.now)
        return time.perf_counter() - t0

    run_once(False)          # warm the executable
    best = {"off": float("inf"), "ckpt": float("inf")}
    for _ in range(reps):
        best["off"] = min(best["off"], run_once(False))
        best["ckpt"] = min(best["ckpt"], run_once(True))
    eps = {k: B * steps / v for k, v in best.items()}
    out["harvest"] = {k: round(v, 1) for k, v in eps.items()}
    out["overhead_ckpt"] = round(eps["off"] / eps["ckpt"] - 1, 4)
    print(f"--tt-ab: harvest overhead {out['overhead_ckpt']:+.2%} "
          f"(off {eps['off']:,.0f} vs ckpt {eps['ckpt']:,.0f} "
          "seed-events/s)", file=sys.stderr)

    # (b) window replay vs from-scratch on a LONG trajectory: lanes
    # that never halt, 16k dispatches, harvested every 4096; the
    # window of interest is the last 4096-dispatch stretch. Replaying
    # THAT window from the nearest checkpoint (ring sized to the
    # window) must beat re-executing all 16k from t=0 with a
    # full-trajectory ring — the whole point of harvesting.
    from madsim_tpu import replay_window
    from madsim_tpu.obs.timetravel import init_checkpoint

    target, every = 16_384, 4096
    log = CheckpointLog()
    state, _ = rt.run(rt.init_batch(_np.arange(8)), target, chunk,
                      ckpt_every=every, ckpt_log=log)
    ck = log.nearest(0, step=target - 1)
    ck0 = init_checkpoint(rt, 0)
    # warm both derived executables (distinct ring buckets), check
    # the two paths land on the identical mid-flight state
    a = replay_window(rt, ck, until_step=target, chunk=chunk)
    b = replay_window(rt, ck0, until_step=target, chunk=chunk)
    assert a["fingerprint"] == b["fingerprint"], \
        "window and from-scratch replays disagree"
    t_win = t_scratch = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        replay_window(rt, ck, until_step=target, chunk=chunk)
        t_win = min(t_win, time.perf_counter() - t0)
        t0 = time.perf_counter()
        replay_window(rt, ck0, until_step=target, chunk=chunk)
        t_scratch = min(t_scratch, time.perf_counter() - t0)
    out["replay"] = dict(
        target_step=target, ckpt_step=int(ck.steps),
        window_s=round(t_win, 4), from_scratch_s=round(t_scratch, 4),
        speedup=round(t_scratch / t_win, 2))
    print(f"--tt-ab: window replay {t_win*1e3:.1f}ms from step "
          f"{ck.steps} vs from-scratch {t_scratch*1e3:.1f}ms to step "
          f"{target} — {out['replay']['speedup']}x", file=sys.stderr)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_tt_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _span_ab_mode():
    """--mode span_ab: attribution-plane overhead A/B on the fused
    runner — the series_ab protocol exactly (worst-case tiny step,
    interleaved min-of-9 reps). Three builds, identical trajectories by
    construction (the span carry and tail folds consume no randomness):

      off          span_attr=False — plane compiled out; latency plane
                   on in every variant so the delta is the SPAN cost,
                   not span+latency
      span_masked  span_attr=True compiled in, NO lanes attributing —
                   the cost of carrying ev_span through the pop/dispatch
                   path and the masked tail folds; the ship-with-it
                   shape, bar <= 3% at B=512
      span_on      every lane attributes (the ceiling)

    Writes BENCH_span_ab_<platform>.json next to this file."""
    _require_chip("--span-ab")
    import jax
    platform = jax.devices()[0].platform
    B, steps, chunk, reps = 512, 2048, 256, 9
    variants = (("off", False, None), ("span_masked", True, []),
                ("span_on", True, None))
    out = {"metric": "span_ab", "platform": platform, "batch": B,
           "steps": steps, "chunk": chunk, "reps": reps,
           "note": ("tiny 2-node workload = worst case for relative "
                    "span-plane overhead (fixed per-step ev_span carry "
                    "+ fold vs tiny step); latency plane ON in all "
                    "three builds so the delta isolates span_attr; "
                    "fused runner, lanes never halt, identical step "
                    "counts per variant; reps interleaved round-robin, "
                    "min-of-reps. span_masked and span_on execute "
                    "identical compute (masked folds run either way) — "
                    "spread between them is the noise floor. Bar: "
                    "span_masked <= 3% MODULO this host's cross-run "
                    "envelope (the causal_ab/lat_ab caveat, DESIGN "
                    "§12); read overhead_span_program (pooled best "
                    "over the identical-compute builds)"),
           "variants": {}}
    seeds = np.arange(B)
    by_sp = {sp: _make_light_runtime(latency_hist=24, span_attr=sp)
             for sp in {sp for _, sp, _ in variants}}
    rts, kws = {}, {}
    for name, sp, lanes in variants:
        rts[name] = by_sp[sp]
        kws[name] = ({} if not sp or lanes is None
                     else {"span_lanes": lanes})
    for rt in by_sp.values():
        jax.block_until_ready(
            rt.run_fused(rt.init_batch(seeds), steps, chunk).now)
    best = {name: float("inf") for name, _, _ in variants}
    for _ in range(reps):
        for name, _, _ in variants:
            state = rts[name].init_batch(seeds, **kws[name])
            jax.block_until_ready(state.now)
            t0 = time.perf_counter()
            final = rts[name].run_fused(state, steps, chunk)
            jax.block_until_ready(final.now)
            best[name] = min(best[name], time.perf_counter() - t0)
    eps = {name: B * steps / b for name, b in best.items()}
    for name, _, _ in variants:
        out["variants"][name] = round(eps[name], 1)
        print(f"--span-ab: {name} {eps[name]:,.0f} seed-events/s",
              file=sys.stderr)
    for name in ("span_masked", "span_on"):
        out[f"overhead_{name}"] = round(eps["off"] / eps[name] - 1, 4)
    # span_masked and span_on run the SAME executable on different
    # sp_on values (masked folds execute either way), so their pooled
    # best is the honest program cost vs off — the causal_ab precedent
    # (DESIGN §12) for hosts whose per-variant spread exceeds the bar
    pooled = max(eps["span_masked"], eps["span_on"])
    out["overhead_span_program"] = round(eps["off"] / pooled - 1, 4)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_span_ab_{platform}.json")
    with open(path, "w") as f:
        json.dump(dict(out, measured_at=time.strftime("%F %T")), f,
                  indent=1)
    print(json.dumps(out))


def _span_smoke_mode():
    """--span-smoke: seconds-scale attribution-plane self-test for CI
    (wired into scripts/ci.sh fast):

      1. on a chaos rpc_echo workload (kill/restart mid-run, re-mint
         roots) the device's per-(lane, node) sa_tail counters must
         EQUAL a host parent-walk of the flight-recorder ring on every
         component — tail count vs lh_slo_miss, queue-wait, net, hops —
         and every tail completion must name exactly one bottleneck
         node (sa_bottleneck);
      2. the plane must be free of trajectory influence: fingerprints
         equal across span-on/compiled-out, fused == chunked on every
         trace column;
      3. on a pause/resume pingpong (parked deadlines -> NONZERO
         queue-wait, the component chaos-free EDF never exercises) the
         obs.request_spans decomposition must telescope exactly
         (wait + transit == e2e per chain) and its tail totals and
         dominant-node fold must match sa_tail / sa_bottleneck;
      4. explain_latency must name the lane's slowest request
         identically on re-run, and the Perfetto export must carry the
         ph="b"/"e" request duration spans exactly when span_attr is on.

    Runs on the host CPU: a CI check, not a device measurement."""
    _force_cpu_inprocess()
    import json as _json
    import tempfile
    from madsim_tpu import (NetConfig, Runtime, Scenario, SimConfig, ms,
                            sec)
    from madsim_tpu.core.state import TRACE_FIELDS
    from madsim_tpu.core.types import EV_MSG
    from madsim_tpu.models.pingpong import PingPong, state_spec
    from madsim_tpu.models.rpc_echo import TAG_ECHO, make_echo_runtime
    from madsim_tpu.net import rpc
    from madsim_tpu.obs import (explain_latency, export_profile_trace,
                                format_span, request_spans, ring_records)
    t0 = time.perf_counter()
    rtag = rpc.reply_tag(TAG_ECHO)
    SLO = ms(8)
    seeds = np.arange(8, dtype=np.uint32)

    def make_echo(span):
        sc = Scenario()
        sc.at(ms(300)).kill(0)
        sc.at(ms(420)).restart(0)
        cfg = SimConfig(
            n_nodes=4, event_capacity=64, time_limit=sec(5),
            latency_hist=24, trace_cap=512,
            complete_kinds=((EV_MSG, rtag),),
            root_kinds=((EV_MSG, rtag),),
            slo_target=SLO, span_attr=span,
            net=NetConfig(send_latency_min=ms(1), send_latency_max=ms(8)))
        return make_echo_runtime(n_nodes=4, target=8, scenario=sc,
                                 cfg=cfg)

    # 1+2: device fold == host parent-walk; bit-identity
    rt_on, rt_off = make_echo(True), make_echo(False)
    on, _ = rt_on.run(rt_on.init_batch(seeds), 2048, 256)
    off, _ = rt_off.run(rt_off.init_batch(seeds), 2048, 256)
    fused = rt_on.run_fused(rt_on.init_batch(seeds), 2048, 256)
    assert (rt_on.fingerprints(on) == rt_off.fingerprints(off)).all(), \
        "span plane perturbed the trajectory"
    assert (rt_on.fingerprints(on) == rt_on.fingerprints(fused)).all()
    for f in TRACE_FIELDS:
        assert (np.asarray(getattr(on, f))
                == np.asarray(getattr(fused, f))).all(), f
    sa = np.asarray(on.sa_tail)
    sb = np.asarray(on.sa_bottleneck)
    assert (sa[:, :, 0] == np.asarray(on.lh_slo_miss)).all(), \
        "tail count must equal lh_slo_miss per node"
    assert sb.sum() == sa[:, :, 0].sum(), \
        "every tail completion names one dominant node"
    walked = 0
    for b in range(len(seeds)):
        recs = ring_records(on, b)
        assert recs["dropped"] == 0, "ring must hold the whole history"
        lat = np.asarray(recs["lat"])
        qw = np.asarray(recs["qw"])
        step_at = {int(s): i for i, s in enumerate(recs["step"])}
        hq = hn = hh = 0
        for i in np.nonzero(lat >= 0)[0]:
            if lat[i] <= SLO:
                continue            # only tails attribute
            # parent-walk to the root (reply deliveries are root_kinds):
            # sum each hop's queue-wait, count hops; the remainder of
            # e2e is transit — the telescoping identity. An externally
            # minted element IS the root (core/step.py root rule): its
            # own wait belongs to no request, so it is not counted.
            j, q, hops = int(i), 0, 0
            while True:
                p = int(recs["parent"][j])
                if p < 0 or p not in step_at:
                    break           # j is the external root
                q += int(qw[j])
                hops += 1
                jp = step_at[p]
                if (int(recs["kind"][jp]) == EV_MSG
                        and int(recs["tag"][jp]) == rtag):
                    break           # completion -> root re-mint
                j = jp
            hq += q
            hn += int(lat[i]) - q
            hh += hops
            walked += 1
        assert (hq, hn, hh) == (sa[b, :, 1].sum(), sa[b, :, 2].sum(),
                                sa[b, :, 3].sum()), b
    tails = int(sa[:, :, 0].sum())
    assert walked == tails > 0

    # 3: nonzero queue-wait + host span decomposition vs device
    sc = Scenario()
    sc.at(ms(30)).pause(1)
    sc.at(ms(90)).resume(1)
    cfg = SimConfig(n_nodes=3, time_limit=sec(5), latency_hist=24,
                    trace_cap=1024, complete_kinds=((EV_MSG, 1),),
                    slo_target=ms(6), span_attr=True,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    rt_pp = Runtime(cfg, [PingPong(3, target=40)], state_spec(),
                    scenario=sc)
    pp, _ = rt_pp.run(rt_pp.init_batch(seeds), 400, 100)
    sa_pp = np.asarray(pp.sa_tail)
    assert sa_pp[:, :, 1].sum() > 0, \
        "pause/resume must produce nonzero queue-wait"
    for b in range(len(seeds)):
        spans = request_spans(pp, b, slo_target=ms(6))
        for sp in spans:
            if not sp["truncated"]:
                assert (sp["wait_us"] + sp["transit_us"]
                        == sp["lat_us"]), sp
        tl = [sp for sp in spans if sp["tail"] and not sp["truncated"]]
        assert sum(sp["wait_us"] for sp in tl) == sa_pp[b, :, 1].sum()
        assert sum(sp["transit_us"] for sp in tl) == sa_pp[b, :, 2].sum()
        assert sum(len(sp["hops"]) for sp in tl) == sa_pp[b, :, 3].sum()
        bn = np.zeros(3, np.int64)
        for sp in tl:
            bn[sp["dominant"]["node"]] += 1
        assert (bn == np.asarray(pp.sa_bottleneck)[b]).all(), b

    # 4: deterministic explain + Perfetto request spans iff span_attr
    e1 = explain_latency(pp, 2, rt=rt_pp)
    e2 = explain_latency(pp, 2, rt=rt_pp)
    assert e1 == e2, "explain_latency must be deterministic on re-run"
    lat2 = np.asarray(ring_records(pp, 2)["lat"])
    assert e1["lat_us"] == int(lat2[lat2 >= 0].max())
    assert format_span(e1)
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "span.json")
        export_profile_trace(p, pp, lane=2)
        with open(p) as f:
            phs = {e.get("ph") for e in _json.load(f)["traceEvents"]}
        assert {"b", "e"} <= phs, phs
        export_profile_trace(p, off, lane=0)
        with open(p) as f:
            phs_off = {e.get("ph") for e in _json.load(f)["traceEvents"]}
        assert "b" not in phs_off, "span-off export must not grow spans"
    print(_json.dumps({
        "metric": "span_smoke", "platform": "cpu", "ok": True,
        "lanes_checked": int(len(seeds)), "tails": tails,
        "parent_walks_checked": walked,
        "qwait_us": int(sa_pp[:, :, 1].sum()),
        "bottleneck_by_node": sb.sum(0).tolist(),
        "wall_s": round(time.perf_counter() - t0, 1)}))


def main():
    # `--mode X` is accepted as an alias for `--X` (dashes for
    # underscores), so `bench.py --mode fused_ab` and `bench.py
    # --fused-ab` are the same invocation; an unknown mode errors out
    # instead of silently falling through to the full flagship bench
    if "--mode" in sys.argv:
        i = sys.argv.index("--mode")
        if i + 1 >= len(sys.argv):
            sys.exit("usage: bench.py --mode <name>")
        flag = "--" + sys.argv[i + 1].replace("_", "-")
        known = {"--fused-ab", "--fused-smoke", "--smoke", "--multihost",
                 "--shape-sweep", "--sweep", "--shardkv", "--minipg",
                 "--ministream", "--all", "--sched-ab", "--realworld",
                 "--scaling", "--cpu-baseline", "--native-baseline",
                 "--obs-ab", "--obs-smoke", "--compile-ab",
                 "--compile-smoke", "--search-ab", "--search-smoke",
                 "--causal-ab", "--causal-smoke", "--campaign",
                 "--campaign-smoke", "--analyze-smoke", "--detsan-ab",
                 "--shard", "--shard-smoke", "--prof-ab", "--prof-smoke",
                 "--lat-ab", "--lat-smoke", "--series-ab",
                 "--series-smoke", "--span-ab", "--span-smoke",
                 "--grayfail-smoke",
                 "--regression-smoke", "--triage-smoke", "--conn-smoke",
                 "--tt-ab", "--tt-smoke", "--ldfi-ab", "--ldfi-smoke"}
        if flag not in known:
            sys.exit(f"unknown mode {sys.argv[i + 1]!r} "
                     f"(known: {sorted(m[2:] for m in known)})")
        sys.argv.append(flag)
    if "--tt-smoke" in sys.argv:
        _tt_smoke_mode()
        return
    if "--tt-ab" in sys.argv:
        _tt_ab_mode()
        return
    if "--analyze-smoke" in sys.argv:
        _analyze_smoke_mode()
        return
    if "--ldfi-smoke" in sys.argv:
        _ldfi_smoke_mode()
        return
    if "--ldfi-ab" in sys.argv:
        _ldfi_ab_mode()
        return
    if "--grayfail-smoke" in sys.argv:
        _grayfail_smoke_mode()
        return
    if "--conn-smoke" in sys.argv:
        _conn_smoke_mode()
        return
    if "--regression-smoke" in sys.argv:
        _regression_smoke_mode()
        return
    if "--triage-smoke" in sys.argv:
        _triage_smoke_mode()
        return
    if "--prof-ab" in sys.argv:
        _prof_ab_mode()
        return
    if "--prof-smoke" in sys.argv:
        _prof_smoke_mode()
        return
    if "--span-ab" in sys.argv:
        _span_ab_mode()
        return
    if "--span-smoke" in sys.argv:
        _span_smoke_mode()
        return
    if "--series-ab" in sys.argv:
        _series_ab_mode()
        return
    if "--series-smoke" in sys.argv:
        _series_smoke_mode()
        return
    if "--lat-ab" in sys.argv:
        _lat_ab_mode()
        return
    if "--lat-smoke" in sys.argv:
        _lat_smoke_mode()
        return
    if "--detsan-ab" in sys.argv:
        _detsan_ab_mode()
        return
    if "--shard-smoke" in sys.argv:
        _shard_smoke_mode()
        return
    if "--shard" in sys.argv:
        _shard_mode()
        return
    if "--campaign-smoke" in sys.argv:
        _campaign_smoke_mode()
        return
    if "--campaign" in sys.argv:
        _campaign_mode()
        return
    if "--causal-ab" in sys.argv:
        _causal_ab_mode()
        return
    if "--causal-smoke" in sys.argv:
        _causal_smoke_mode()
        return
    if "--search-ab" in sys.argv:
        _search_ab_mode()
        return
    if "--search-smoke" in sys.argv:
        _search_smoke_mode()
        return
    if "--compile-ab" in sys.argv:
        _compile_ab_mode()
        return
    if "--compile-smoke" in sys.argv:
        _compile_smoke_mode()
        return
    if "--obs-ab" in sys.argv:
        _obs_ab_mode()
        return
    if "--obs-smoke" in sys.argv:
        _obs_smoke_mode()
        return
    if "--fused-ab" in sys.argv:
        _fused_ab_mode()
        return
    if "--fused-smoke" in sys.argv:
        _fused_smoke_mode()
        return
    if "--smoke" in sys.argv:
        _smoke_mode()
        return
    if "--multihost" in sys.argv:
        _multihost_mode()
        return
    if "--shape-sweep" in sys.argv:
        _shape_sweep_mode()
        return
    if "--sweep" in sys.argv:
        _sweep_mode()
        return
    if "--shardkv" in sys.argv:
        _shardkv_mode()
        return
    if "--minipg" in sys.argv:
        _minipg_mode()
        return
    if "--ministream" in sys.argv:
        _ministream_mode()
        return
    if "--all" in sys.argv:
        _all_mode()
        return
    if "--sched-ab" in sys.argv:
        _sched_ab_mode()
        return
    if "--realworld" in sys.argv:
        _realworld_mode()
        return
    if "--scaling" in sys.argv:
        _scaling_mode()
        return
    if "--cpu-baseline" in sys.argv:
        # single-seed sequential loop on CPU: the reference execution model
        _force_cpu_inprocess()
        print(_cpu_baseline_eps())
        return
    if "--native-baseline" in sys.argv:
        _force_cpu_inprocess()
        print(json.dumps(_native_baseline_eps() or {"error": "no toolchain"}))
        return

    dev = _require_chip("bench")
    cpu_eps = _cpu_baseline_eps()
    print(f"cpu single-seed baseline: {cpu_eps:,.0f} events/s",
          file=sys.stderr)
    native = _native_baseline_eps()
    if native:
        print(f"native single-seed baseline: "
              f"{native['events_per_sec']:,.0f} events/s", file=sys.stderr)
    batched_eps = _events_per_sec(B_TPU, STEPS, WARM)
    result = {
        "metric": "madraft_fuzz_seed_events_per_sec",
        "value": round(batched_eps, 1),
        "unit": "seed*events/s (5-node Raft, chaos scenario)",
        "vs_baseline": round(batched_eps / cpu_eps, 2),
        "device": dev,
    }
    if native:
        # second denominator (BASELINE.md §native): a tight C++ DES of the
        # SAME workload, single seed — an UPPER bound on the reference's
        # per-seed rate (no async-runtime/serialization overhead, and none
        # of the engine's per-event invariant/schedule-hash work)
        result["native_baseline_eps"] = round(native["events_per_sec"], 1)
        result["vs_native_baseline"] = round(
            batched_eps / native["events_per_sec"], 3)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
