"""Open-loop shard_kv: measured tail latency under fuzzable traffic.

The seed of the ROADMAP's big-world flagship ("planet-scale shard_kv
under million-client open traffic, p99 invariants read off the profiler
digest"), at demo scale: an OPEN-loop client population drives a sharded
KV cluster, and the new SLO latency plane (SimConfig.latency_hist,
DESIGN §17) reports p50/p99/p999 end-to-end request latency straight off
the on-device histograms — zero extra host round-trips, fused runner.

Open-loop means arrivals don't wait for completions: each client NODE is
booted by a scenario row at a Poisson-ish arrival time (`Scenario.boot`
— spare event-table rows ARE the client generator), then issues its ops.
Because scenario row TIMES live on the fuzzer's knob plane
(search/mutate.py time_nudge), the traffic shape itself is mutable: run
with `fuzz` and the campaign hunts arrival patterns that amplify the
tail, with `lat_bonus` steering admissions toward high-p99 lanes and an
SLO invariant turning misses into first-class crash findings.

Latency semantics here (the DESIGN §17 chain-correctness rule):
  root_kinds     = ((EV_TIMER, T_NEW),): each new-request timer MINTS a
                   fresh root, so retries/config-chasing of one op stay
                   under that op's root
  complete_kinds = ((EV_MSG, CMD),): the command ARRIVING at a shard
                   server completes the measured leg. shard_kv replies
                   ride the raft APPLY (a replication-ack dispatch whose
                   causal chain descends from the server's boot, not the
                   request), so the client→group request path — routing,
                   wrong-group redirects, retries under chaos — is the
                   chain-correct leg; every retry arrival re-measures
                   cumulatively from the op's root, so the histogram's
                   tail IS time-to-reach-the-group. Direct-reply servers
                   (wal_kv, rpc_echo) can complete on the reply itself.

Usage:
  python examples/open_loop_kv.py [batch] [fuzz]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from madsim_tpu import (NetConfig, Scenario, SimConfig,  # noqa: E402
                        format_latency, latency_summary, ms, sec,
                        slo_invariant, summarize)
from madsim_tpu.core.types import EV_MSG, EV_TIMER  # noqa: E402
from madsim_tpu.models.shard_kv import (CMD, T_NEW,  # noqa: E402
                                        make_shard_runtime)

RC, RG, G, CLIENTS = 3, 3, 2, 4
N_OPS = 2
SLO_US = ms(400)        # miss-counter target for the report
SLO_CRASH_US = ms(800)  # fuzz: a lane whose own p99 passes this CRASHES —
                        # above the baseline tail, so the fuzzer must find
                        # traffic/chaos shapes that amplify it


def make_open_loop_runtime(arrival_seed: int = 0, mean_gap=ms(120),
                           invariant=None):
    """The open-loop cluster: servers boot at t=0, each client node
    boots at a Poisson-ish arrival drawn host-side (fixed seed — the
    arrival SCHEDULE is scenario data, so every lane shares it and the
    fuzzer mutates it via the knob plane; per-lane jitter comes from
    the simulation's own randomness)."""
    n = RC + G * RG + CLIENTS
    arrivals_rng = np.random.default_rng(arrival_seed)
    sc = Scenario()
    # arrivals start once the groups have had time to elect/configure,
    # so e2e measures request service, not the cluster's cold start
    t = sec(2)
    for c in range(CLIENTS):
        t += int(arrivals_rng.exponential(mean_gap))
        sc.at(t).boot(RC + G * RG + c)
    # a little server chaos so the tail has something to amplify —
    # random kills are fuzzer-retargetable (NODE_RANDOM + pool knobs)
    servers = tuple(range(RC, RC + G * RG))
    sc.at(sec(3)).kill_random(among=servers)
    sc.at(sec(3) + ms(600)).restart_random(among=servers)
    cfg = SimConfig(
        n_nodes=n, event_capacity=192, payload_words=12,
        time_limit=sec(30),
        latency_hist=24,
        complete_kinds=((EV_MSG, CMD),),
        root_kinds=((EV_TIMER, T_NEW),),
        slo_target=SLO_US,
        net=NetConfig(send_latency_min=ms(1), send_latency_max=ms(10)))
    return make_shard_runtime(n_groups=G, rg=RG, rc=RC, n_clients=CLIENTS,
                              n_ops=N_OPS, max_cfg=4, scenario=sc, cfg=cfg,
                              extra_invariant=invariant)


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    do_fuzz = "fuzz" in sys.argv[1:]
    rt = make_open_loop_runtime()
    print(f"open-loop shard_kv: {rt.cfg.n_nodes} nodes "
          f"({G} groups x {RG} + {RC} ctrl + {CLIENTS} clients), "
          f"B={batch}, SLO p99 <= {SLO_US}us")
    seeds = np.arange(batch, dtype=np.uint32)
    final = rt.run_fused(rt.init_batch(seeds), 60_000, 1024)
    rep = summarize(rt, final, seeds)
    lat = rep["latency"]
    print(f"halted {rep['halted']}/{rep['batch']}  "
          f"crashed {rep['crashed']}  "
          f"distinct schedules {rep['distinct_schedules']}")
    print(format_latency(latency_summary(final)))
    if lat["e2e_p99"] > SLO_US:
        print(f"!! p99 {lat['e2e_p99']}us exceeds the {SLO_US}us SLO")
    if not do_fuzz:
        return
    # hunt tail amplification: the corpus pays extra energy for
    # admissions whose lanes sit at the round's worst p99, and the SLO
    # invariant turns a p99 regression into a crash code (CRASH_SLO)
    # with a (seed, knobs) repro, bucketable like any safety bug
    from madsim_tpu import ProgressObserver, fuzz
    rt_slo = make_open_loop_runtime(
        invariant=slo_invariant(p99_le=SLO_CRASH_US, min_count=4))
    res = fuzz(rt_slo, max_steps=60_000, batch=max(batch // 2, 16),
               max_rounds=6, dry_rounds=3, chunk=1024,
               lat_bonus=1.0, observer=ProgressObserver())
    print(f"fuzz: {res['distinct_schedules']} distinct schedules, "
          f"crash codes {sorted(res['crash_repros'])}")
    for code, rep_h in res["crash_repros"].items():
        print(f"  code {code}: seed {rep_h['seed']} (round "
              f"{rep_h['round']})")


if __name__ == "__main__":
    main()
