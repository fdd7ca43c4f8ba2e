"""Drive madsim_tpu's main path once on a TPU chip, and fail loudly if it can't.

    python chip_smoke.py               # one chip: the phases below
    python chip_smoke.py --four-chips  # four chips: sharded sweep + fuzz only

One chip, in one process, in this order:
  device       platform, device_kind and count; anything but a TPU exits
               non-zero (there is no CPU fallback)
  flagship     workloads.flagship_raft's 5-node Raft chaos sweep (kill/restart,
               partition/heal, 5% loss) at B=4096 seeds x 4096 steps through
               Runtime.run_fused: no crash, no event-table overflow, lanes live
  parity       the first 64 seeds again on the host CPU backend: fingerprints
               equal lane for lane (a seed found on the chip replays anywhere)
  linearizable BASELINE config 4's raft_kv deployment, 4096 seeds run to halt
               through run_compacting; every history passes check_kv_history
  find-replay  2 rounds of search.fuzz at batch 1024 on the crash-rich wal_kv
               target; the first crash bucket's seed reproduces its code in
               run_single on the chip and on the CPU

--four-chips runs the flagship at B=16384 through run_fused_sharded over a
4-device seed mesh, the same seeds on one chip for reference (fingerprints
equal lane for lane), and one round of fuzz_sharded(shards=4).

Timings are printed as information only. The last stdout line is the verdict:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Any failed check raises, so the script exits non-zero and prints no verdict.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

FLAGSHIP_BATCH = 4096      # the v5e sweep batch
FLAGSHIP_STEPS = 4096
CHUNK = 512
PARITY_LANES = 64
KV_SEEDS = 4096
KV_STEPS = 60_000
KV_CHUNK = 2048            # config 4's on-chip scan length
FUZZ_BATCH = 1024
FUZZ_ROUNDS = 2
FUZZ_STEPS = 60_000
SHARDED_BATCH = 16384
SHARDED_FUZZ_BATCH = 1024  # per shard


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def device_phase(want: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say(f"device {json.dumps(info)}")
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{info['platform']!r}); refusing to run elsewhere")
    if info["count"] < want:
        raise SystemExit(f"chip_smoke: needs {want} chips, JAX sees "
                         f"{info['count']}")
    from madsim_tpu.compile.persistent import enable_persistent_cache
    say(f"compile cache {enable_persistent_cache()}")
    return info


def check_sweep(state, what: str) -> None:
    """The flagship must run clean: no lane crashed, the event table never
    overflowed, and (time limit 600 s) over 90% of lanes are still live."""
    crashed = np.asarray(state.crashed)
    assert not crashed.any(), \
        f"{what}: {int(crashed.sum())} lanes crashed, first lane " \
        f"{int(np.argmax(crashed))}"
    oops = np.asarray(state.oops)
    assert not (oops != 0).any(), \
        f"{what}: event table overflowed in {int((oops != 0).sum())} lanes"
    live = float(np.asarray(~state.halted).mean())
    assert live > 0.9, f"{what}: lanes went idle (live share {live})"


def timed_sweep(rt, seeds, steps: int, run) -> tuple:
    import jax
    t0 = time.perf_counter()
    state = run(rt.init_batch(seeds), steps)
    jax.block_until_ready(state.now)
    return state, time.perf_counter() - t0


def flagship_phase():
    from madsim_tpu.models.workloads import flagship_raft
    rt = flagship_raft()
    seeds = np.arange(FLAGSHIP_BATCH)

    def run(s, n):
        return rt.run_fused(s, n, chunk=CHUNK)

    _, cold = timed_sweep(rt, seeds, FLAGSHIP_STEPS, run)
    state, warm = timed_sweep(rt, seeds, FLAGSHIP_STEPS, run)
    check_sweep(state, "flagship")
    say(f"flagship B={FLAGSHIP_BATCH} steps={FLAGSHIP_STEPS}: cold_s={cold} "
        f"warm_s={warm} compile_s~{cold - warm} (information only)")
    return rt, rt.fingerprints(state)


def on_cpu(fn):
    """Run `fn()` with the host CPU backend as JAX's default device."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        return fn()


def parity_phase(rt, chip_fps) -> None:
    t0 = time.perf_counter()

    def run():
        st = rt.run_fused(rt.init_batch(np.arange(PARITY_LANES)),
                          FLAGSHIP_STEPS, chunk=CHUNK)
        assert {d.platform for d in st.now.devices()} == {"cpu"}
        return rt.fingerprints(st)

    cpu_fps = on_cpu(run)
    bad = np.nonzero(cpu_fps != chip_fps[:PARITY_LANES])[0]
    assert bad.size == 0, \
        f"parity: chip and CPU fingerprints differ in lanes {bad.tolist()}"
    say(f"parity: {PARITY_LANES}/{PARITY_LANES} lanes equal chip vs cpu, "
        f"wall_s={time.perf_counter() - t0}")


def linearizable_phase() -> None:
    from madsim_tpu.models.raft_kv import extract_histories
    from madsim_tpu.models.workloads import raft_kv_config4
    from madsim_tpu.native import check_kv_history

    rt = raft_kv_config4()
    t0 = time.perf_counter()
    st = rt.run_compacting(rt.init_batch(np.arange(KV_SEEDS)), KV_STEPS,
                           chunk=KV_CHUNK)
    run_s = time.perf_counter() - t0
    assert np.asarray(st.halted).all(), "raft_kv: lanes still live"
    assert not np.asarray(st.crashed).any(), "raft_kv: a lane crashed"
    assert not (np.asarray(st.oops) != 0).any(), "raft_kv: overflow"
    t0 = time.perf_counter()
    hists = extract_histories(st, 5, 3)
    bad = [i for i, h in enumerate(hists) if not check_kv_history(h)]
    assert len(hists) == KV_SEEDS and not bad, \
        f"raft_kv: non-linearizable histories at seeds {bad[:10]}"
    say(f"linearizable: {len(hists)}/{KV_SEEDS} histories pass, "
        f"run_s={run_s} (compiles included) "
        f"check_s={time.perf_counter() - t0}")


def replay_code(rt, seed: int) -> int:
    st, _ = rt.run_single(seed, FUZZ_STEPS, collect_events=False)
    return int(np.asarray(st.crash_code)[0])


def find_replay_phase() -> None:
    from madsim_tpu.models.workloads import crashrich
    from madsim_tpu.search.fuzz import fuzz

    rt = crashrich("wal_kv")
    t0 = time.perf_counter()
    out = fuzz(rt, FUZZ_STEPS, batch=FUZZ_BATCH, max_rounds=FUZZ_ROUNDS,
               dry_rounds=FUZZ_ROUNDS)
    fuzz_s = time.perf_counter() - t0
    buckets = out["crash_first_seed_by_code"]
    assert buckets, f"fuzz: no crash bucket in {out['seeds_run']} seeds"
    code, seed = min(buckets.items())
    t0 = time.perf_counter()
    chip = replay_code(rt, seed)
    cpu = on_cpu(lambda: replay_code(rt, seed))
    assert chip == code, f"replay: seed {seed} gave {chip} on chip, " \
                         f"bucket says {code}"
    assert cpu == code, f"replay: seed {seed} gave {cpu} on cpu, " \
                        f"bucket says {code}"
    say(f"find-replay: {out['seeds_run']} seeds, {len(buckets)} crash "
        f"buckets, {out['crashes']} crashing lanes, fuzz_s={fuzz_s} "
        f"(compiles included); seed {seed} reproduces code {code} on chip "
        f"and cpu, replay_s={time.perf_counter() - t0}")


def four_chip_phase() -> None:
    import jax
    from madsim_tpu.models.workloads import flagship_raft
    from madsim_tpu.parallel.mesh import seed_mesh
    from madsim_tpu.search.shard import fuzz_sharded

    devs = jax.devices()[:4]
    rt = flagship_raft()
    seeds = np.arange(SHARDED_BATCH)
    mesh = seed_mesh(devs)

    def sharded(s, n):
        return rt.run_fused_sharded(s, n, chunk=CHUNK, mesh=mesh)

    _, cold = timed_sweep(rt, seeds, FLAGSHIP_STEPS, sharded)
    state, warm = timed_sweep(rt, seeds, FLAGSHIP_STEPS, sharded)
    spread = len(state.now.sharding.device_set)
    assert spread == 4, f"sharded: state spans {spread} devices, not 4"
    check_sweep(state, "sharded")
    fps = rt.fingerprints(state)
    say(f"sharded B={SHARDED_BATCH} over {spread} chips: cold_s={cold} "
        f"warm_s={warm} (information only)")

    with jax.default_device(devs[0]):
        ref, ref_s = timed_sweep(
            rt, seeds, FLAGSHIP_STEPS,
            lambda s, n: rt.run_fused(s, n, chunk=CHUNK))
    assert len(ref.now.sharding.device_set) == 1
    bad = np.nonzero(rt.fingerprints(ref) != fps)[0]
    assert bad.size == 0, \
        f"sharded vs one chip: {bad.size} lanes differ, first {bad[:10]}"
    say(f"one-chip reference: {SHARDED_BATCH}/{SHARDED_BATCH} lanes equal, "
        f"wall_s={ref_s} (compile included)")

    t0 = time.perf_counter()
    out = fuzz_sharded(rt, FLAGSHIP_STEPS, batch=SHARDED_FUZZ_BATCH,
                       shards=4, devices=devs, max_rounds=1, dry_rounds=1)
    assert out["shards"] == 4 and out["rounds"] == 1, out
    assert out["seeds_run"] == 4 * SHARDED_FUZZ_BATCH, out["seeds_run"]
    assert out["crashes"] == 0, f"fuzz_sharded: {out['crashes']} crashes"
    say(f"fuzz_sharded: 1 round, {out['seeds_run']} seeds over 4 shards, "
        f"{out['distinct_schedules']} distinct schedules, no crash, "
        f"wall_s={time.perf_counter() - t0} (compiles included)")


def timed(name: str, phase, *args):
    t0 = time.perf_counter()
    out = phase(*args)
    say(f"phase {name} done in {time.perf_counter() - t0} s")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded checks on a 4-chip host")
    args = ap.parse_args(argv)
    info = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        timed("four-chips", four_chip_phase)
    else:
        rt, fps = timed("flagship", flagship_phase)
        timed("parity", parity_phase, rt, fps)
        timed("linearizable", linearizable_phase)
        timed("find-replay", find_replay_phase)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
