"""Canned chaos workloads: the runtimes the tests, examples, campaign
workers and `chip_smoke.py` share, one definition each.

Every factory builds a fresh `Runtime` and imports its model lazily, so
importing this module initializes no JAX backend. A campaign worker names
a factory by string, e.g.
`--factory madsim_tpu.models.workloads:crashrich`.

  flagship_raft    5-node Raft under rolling kill/restart/partition chaos
                   with 5% loss (the B=4096 sweep workload of
                   `chip_smoke.py`)
  light            tiny 2-node ping-pong whose lanes never halt
  saturating       ping-pong whose seed space exhausts quickly
  crashrich        crash-rich wal_kv or chain under a chaos matrix
  racy             wal_kv with fixed latency: same-instant races
  grayfail         Percolator-lite under the gray-failure recipes
  connfault        minipg under the connection-fault recipes
  recovery         rpc_echo with the latency and series planes on, for
                   the recovery oracle
  raft_kv_config4  BASELINE.md config 4's raft_kv deployment
"""


def flagship_raft(table_dtype: str = "int32", n_nodes: int = 5,
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
    # materialize). 16n gives ~1.8x headroom; the table's oops flag
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


def light(n_nodes=2, loss=0.0, trace_cap=0, sketch_slots=0,
          profile=False, latency_hist=0, series_windows=0,
          span_attr=False):
    """A deliberately tiny workload (2-node ping-pong, C=16, P=2, stats
    off): per-step compute is small, so per-step plane costs and the
    chunked runner's per-chunk host round-trip are not hidden under
    model compute. The target is unreachable, so lanes never halt and
    every runner executes exactly the same step count."""
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
                    # ping deliveries as completions, so a latency
                    # build pays the e2e fold, not just the sojourn
                    complete_kinds=(((EV_MSG, 1),) if latency_hist
                                    else ()),
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [PingPong(n_nodes, target=1 << 30)], state_spec())


def saturating(target=6, trace_cap=0, sketch_slots=0):
    """A chaos workload whose schedule space SEEDS ALONE exhaust quickly
    (fixed latency, no loss, random kill/restart): the regime where blind
    explore() goes dry and the fuzzer's knob mutations are the only way to
    keep coverage growing. The flagship Raft chaos workload is the other
    regime — randomized election timeouts put every seed on a distinct
    schedule, so blind sampling is already at the per-lane ceiling there
    and fuzz and blind sampling tie (the hash cannot count past one
    distinct schedule per lane)."""
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


def crashrich(kind="wal_kv", trace_cap=0, sketch_slots=0,
              profile=False):
    """Crash-RICH flagship targets: green Raft's randomized election
    timeouts saturate the schedule ceiling but rarely crash. These two
    do crash under their chaos matrices:

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


def racy(trace_cap=256, sketch_slots=0):
    """The RACE-rich flagship mutant: the crash-rich wal_kv matrix
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


def grayfail(recipe="mix", trace_cap=128, n_ops=12):
    """The gray-failure flagship targets (r17, DESIGN §18): Percolator-
    lite (models/percolator.py) under the chaos recipes whose fault
    shapes its snapshot-isolation oracle is built to catch; the
    committed `grayfail_mix` regression corpus was frozen from "mix".

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


def connfault(recipe="mix", trace_cap=128, n_txns=6,
              guard=None):
    """The connection-fault flagship targets (r19, DESIGN §20): minipg —
    pipelined exactly-once transactions over the full conn+stream stack —
    under the chaos recipes whose fault shapes its client-side oracles
    catch; the committed `connfault_mix` regression corpus was frozen
    from "mix".

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


def recovery(recipe="heal", invariant=None, target=400):
    """The recovery-oracle flagship targets (r21, DESIGN §22): rpc_echo
    with the latency + series planes on, under fault scripts whose
    timeline shape `harness.recovery_invariant` judges.

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


def raft_kv_config4():
    """Config 4's raft_kv deployment (BASELINE.md): 5 Raft nodes, 3
    clients, 3 keys, 6 ops each, 3 kill/restart cycles, 5% loss."""
    from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
    from madsim_tpu.models.raft_kv import make_kv_runtime
    sc = Scenario()
    for t in range(3):
        sc.at(ms(700 + 900 * t)).kill_random(among=range(5))
        sc.at(ms(1200 + 900 * t)).restart_random(among=range(5))
    cfg = SimConfig(n_nodes=8, event_capacity=96, payload_words=12,
                    time_limit=sec(8),
                    net=NetConfig(packet_loss_rate=0.05))
    return make_kv_runtime(n_raft=5, n_clients=3, n_keys=3, n_ops=6,
                           log_capacity=32, scenario=sc, cfg=cfg)
