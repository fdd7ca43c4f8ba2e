"""The simulation state pytree.

One SimState value is an ENTIRE simulated cluster for one trajectory (seed):
virtual clock, PRNG key, the event table (timers + in-flight messages +
scheduled supervisor ops), per-node liveness and user protocol state, and the
network fault matrix. The reference spreads this across GlobalRng
(rand.rs:48), TimeRuntime (time/mod.rs), the executor's task queue (task.rs),
Network {clogged_node, clogged_link, config, stat} (network.rs:20-29), and
per-node mailboxes (net/mod.rs:368-411); here it is one fixed-shape pytree so
that `vmap` batches thousands of clusters and `jit` compiles one XLA program
that advances them all in lockstep.

There are no mailboxes: madsim needs them because a receiver task may not yet
be awaiting a tag when a message lands (net/mod.rs:368-411). In the
state-machine model, delivery *is* the invocation of `on_message`, so the
event table subsumes the mailbox.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from . import types as T

# SimState fields owned by the flight recorder (cfg.trace_cap), the
# causal-lineage layer (r10 — rides the same gate), the
# prefix-coverage sketch (cfg.sketch_slots), the sim-profiler
# counter plane (cfg.profile, r15 — the pf_* columns + the tr_qlen
# ring column), the SLO latency plane (cfg.latency_hist, r16 —
# the lh_* histograms, the ev_root_t root-birth-time column, and the
# tr_lat ring column), and the windowed telemetry plane
# (cfg.series_windows, r21 — the sr_* per-window series and the
# dynamic window_len operand). One schema constant so every consumer follows it
# automatically: excluded from fingerprints (utils/hashing —
# observation only, never a replay domain), read by obs/rings.py (the
# tr_* columns) and obs/profiler.py (the pf_* columns), compared
# explicitly in the fused-vs-chunked equivalence tests (test_obs,
# test_causal, test_profiler and the other planes). trace_cap is the
# DYNAMIC capacity operand (columns are sized to the power-of-two
# bucket, cfg.trace_cap_bucket — DESIGN §10); sketch_every is the
# DYNAMIC fold period for the structurally sized cov_sketch column
# (DESIGN §12).
TRACE_FIELDS = ("trace_on", "trace_pos", "trace_cap", "tr_now", "tr_step",
                "tr_kind", "tr_node", "tr_src", "tr_tag",
                "tr_parent", "tr_lamport", "tr_qlen", "tr_lat", "tr_qw",
                "ev_prov", "lamport",
                "cov_sketch", "sketch_every",
                "pf_on", "pf_dispatch", "pf_busy", "pf_kill", "pf_restart",
                "pf_qmax", "pf_drop", "pf_delay",
                "lh_on", "ev_root_t", "lh_sojourn", "lh_e2e",
                "lh_slo_miss", "slo_target",
                "sr_on", "window_len", "sr_dispatch", "sr_busy", "sr_qhw",
                "sr_drop", "sr_dup", "sr_complete", "sr_slo_miss",
                "sr_lat", "sr_fault",
                "sp_on", "ev_span", "sa_tail", "sa_bottleneck",
                "hash_base")
# hash_base rides TRACE_FIELDS for the fingerprint-exclusion contract
# only: it is a CONSTANT pure function of the lane's seed (never
# written after init), so folding it into fingerprints would make two
# seeds with identical trajectories fingerprint differently — breaking
# distinct_outcomes. Unlike the recorder columns it IS consumed by the
# replay domain when a model opts in (Ctx.hash_key), but the seed that
# fingerprints already imply determines it completely.

# pf_dispatch's kind axis: one column per event kind (EV_FREE's column
# exists so t_kind values index directly but is never written — only
# valid dispatches count, and a valid dispatch is never EV_FREE).
# Derived from the enum so a new kind widens the counter automatically
N_EV_KINDS = T.EV_SUPER + 1

# ev_span's word axis (the r23 critical-path attribution plane): the
# per-row carried span vector, broadcast to every emission of a dispatch
# exactly like the ev_prov provenance pair. All words describe the
# row's CHAIN as of its enqueue; the dispatch that pops the row folds in
# its own queue-wait and incoming-edge transit before re-broadcasting.
SP_QWAIT = 0     # accumulated queue-wait ticks since the chain's root
SP_NET = 1       # accumulated network/disk transit ticks since the root
SP_HOPS = 2      # hop index: dispatches since the root (root row = 0)
SP_DOM_NODE = 3  # node owning the DOMINANT segment so far (-1 = none)
SP_DOM_MAG = 4   # that segment's magnitude (transit + wait ticks)
SP_EMIT_T = 5    # the emitting dispatch's virtual time (-1 = external)
SPAN_WORDS = 6

# sa_tail's component axis: per-completion-node tail attribution
SA_COUNT = 0     # tail completions (e2e > slo_target) at this node
SA_QWAIT = 1     # their accumulated queue-wait ticks
SA_NET = 2       # their accumulated network/disk transit ticks
SA_HOPS = 3      # their accumulated hop counts
SA_COMPONENTS = 4


@struct.dataclass
class SimState:
    # --- clock & rng & lifecycle -----------------------------------------
    now: jax.Array          # int32 ticks — virtual clock (ClockHandle analog)
    key: jax.Array          # uint32[2] — trajectory PRNG (GlobalRng analog)
    hash_base: jax.Array    # uint32[2] — the lane's UNCONSUMED seed key
                            # (seed_key(seed), frozen at init while `key`
                            # splits away): the root of the per-node
                            # deterministic HASH-SEED streams (r18,
                            # madsim collections.rs parity). Ctx.hash_key
                            # derives fold_in(fold_in(this, DOMAIN),
                            # node) — a pure (seed, node) function, so
                            # model-level hash iteration order is
                            # schedule-stable and can't couple nodes.
                            # Never written after init; excluded from
                            # fingerprints (see TRACE_FIELDS note).
    halted: jax.Array       # bool — simulation finished (normally or crashed)
    crashed: jax.Array      # bool — an invariant/assertion failed
    crash_code: jax.Array   # int32 — which invariant (user >0, engine <0)
    crash_node: jax.Array   # int32 — node implicated, -1 if n/a
    oops: jax.Array         # int32 bitmask — capacity overflows
    steps: jax.Array        # int32 — events dispatched so far
    sched_hash: jax.Array   # uint32[2] — running hash of the dispatch
                            # sequence (kind/node/src/tag of every event, in
                            # order). Two trajectories with different
                            # interleavings get different hashes even when
                            # they converge to the same terminal state — the
                            # schedule-coverage metric proper, vs the
                            # terminal-fingerprint proxy (task.rs:572-596
                            # asserts N seeds -> N schedules; this is the
                            # batched measurement of that property).
                            # Two independent 32-bit lanes = 64 effective
                            # bits: at the 100k-seed fuzz scale a single
                            # 32-bit lane's birthday collisions (~n²/2³³)
                            # would undercount distinct_schedules and stop
                            # explore()'s dry-round loop early. Combine with
                            # parallel/stats.sched_hash_u64 for analysis.
    tlimit: jax.Array       # int32 ticks — virtual-time limit; DYNAMIC (like
                            # loss/latency) so set_time_limit / the
                            # MADSIM_TEST_TIME_LIMIT env knob need no recompile

    # --- event table [C] --------------------------------------------------
    t_deadline: jax.Array   # int32[C] — fire time (T_INF when slot free)
    t_kind: jax.Array       # int32[C] — EV_FREE/MSG/TIMER/SUPER
    t_node: jax.Array       # int32[C] — destination node
    t_src: jax.Array        # int32[C] — source node (msgs) / link src (super)
    t_tag: jax.Array        # int32[C] — msg tag / timer tag / super opcode
    t_payload: jax.Array    # int32[C, P]

    # --- causal lineage (r10; compiled in iff cfg.trace_cap > 0) ----------
    # A provenance matrix for the pending rows above, plus one Lamport
    # clock per node — together they let the ring carry (parent_dispatch,
    # lamport) for every dispatched event, so a crash explains itself by
    # walking parent edges backward (obs/causal.py) even after the ring
    # wrapped. Observation only: no randomness consumed, excluded from
    # fingerprints, zero-size when the recorder is compiled out. One
    # [C, 2] matrix, not two [C] columns: the step then pays ONE extra
    # emission write and ONE extra dispatch gather (the t_payload shape;
    # on the CPU, half the cost of two columns).
    ev_prov: jax.Array      # int32[C, 2] — per pending row:
                            # [0] dispatch index of the step that
                            #     enqueued it; -1 = external (scenario
                            #     row, node boot, host-injected op)
                            # [1] the Lamport timestamp it carries
                            #     (sender's clock at enqueue — the
                            #     "message timestamp" of the Lamport rule)
    lamport: jax.Array      # int32[N] — per-node Lamport clock:
                            # max(own, carried) + 1 at every dispatch

    # --- nodes ------------------------------------------------------------
    alive: jax.Array        # bool[N]
    paused: jax.Array       # bool[N]
    node_state: Any         # user pytree, leaves with leading [N] axis

    # --- network fault matrix (NetSim analog) ----------------------------
    clog_node: jax.Array    # bool[N] — NetSim::clog_node
    clog_link: jax.Array    # bool[N, N] — NetSim::clog_link (src, dst);
                            # OP_PARTITION_ONEWAY (r17) ORs directional
                            # cuts into it — asymmetric partitions are
                            # just an asymmetric matrix
    loss: jax.Array         # float32 — packet_loss_rate
    lat_lo: jax.Array       # int32 ticks — send_latency range
    lat_hi: jax.Array       # int32 ticks
    jitter: jax.Array       # int32 ticks — per-op micro-jitter bound
                            # (NetConfig.op_jitter_max; net/mod.rs:151-156)

    # --- gray-failure fault plane (r17; DESIGN §18) ------------------------
    # All three are DYNAMIC replay-domain state (they change trajectories,
    # so they ride in fingerprints and checkpoints — simconfig-v5 rejects
    # pre-r17 snapshots): always compiled in, exact identity at the zero
    # defaults (the bit-identical-to-r16 contract tests/test_grayfail.py
    # holds against captured golden digests). Set by scenario ops
    # (OP_SET_SKEW / OP_SET_DISK), mutated by the fuzzer's fault_perturb
    # havoc operator through the scenario rows.
    skew: jax.Array         # int32[N] — per-node clock-RATE skew in
                            # 1/1024ths: node n's local clock reads
                            # now + (now·skew[n])>>10 (handlers observe it
                            # as ctx.now) and its timer delays shrink or
                            # stretch inversely — a fast clock fires
                            # timeouts early in global time, the
                            # lease-expiry/timeout-ordering gray failure.
                            # Exact integer arithmetic (no float log/mul):
                            # deterministic, identity at 0.
    disk_lat: jax.Array     # int32[N] — slow-disk emission delay in ticks:
                            # every send latency and timer deadline the
                            # node emits is pushed this much later (an
                            # fsync-stalled event loop emits late). 0 = no
                            # fault.
    torn: jax.Array         # bool[N] — torn-write-on-kill mode: a KILL of
                            # this node flushes a random prefix of each
                            # fs file's unsynced tail to the durable view
                            # before process memory dies, so recovery can
                            # observe a partially-written final record
                            # (fs-layer state schemas only; inert
                            # otherwise). The tear draw rides a key split
                            # the step already made, so enabling it never
                            # shifts the PRNG stream of anything else.

    # --- connection-fault plane (r19; DESIGN §20) --------------------------
    dup_rate: jax.Array     # int32[N] — per-node duplicate-delivery rate
                            # in PARTS PER MILLION (the OP_SET_LOSS
                            # encoding), set by OP_SET_DUP and capped at
                            # DUP_RATE_CAP: a dispatched MESSAGE at the
                            # node is re-armed for one more delivery with
                            # this probability instead of being freed —
                            # byte-identical payload, later deadline, and
                            # it may duplicate again (the retransmit-storm
                            # regime). The decision/delay draws ride keys
                            # FOLDED off the already-consumed scheduler
                            # key, so the zero default consumes nothing
                            # from any stream — bit-identical to r18
                            # (tests/test_connfault.py holds it against
                            # golden digests captured at r18 HEAD).
                            # Replay-domain state like skew/disk_lat:
                            # rides in fingerprints and checkpoints
                            # (simconfig-v6 rejects pre-r19 snapshots).

    # --- schedule search (search/pct.py) ----------------------------------
    prio_nudge: jax.Array   # int32 — PCT-style priority-perturbation point.
                            # 0 (the default) leaves the scheduler's random
                            # tie-break untouched and is BIT-IDENTICAL to a
                            # build without the hook; any nonzero value
                            # replaces the tie-break among earliest-deadline
                            # slots with a deterministic priority order keyed
                            # on (nudge, slot identity) — one nudge = one
                            # tie-breaking policy, so a fuzzer sweeps
                            # scheduler decisions as a DYNAMIC knob (no
                            # recompile, step.py §1). Part of the replay
                            # domain: it changes trajectories, so it rides
                            # in fingerprints, unlike the trace ring.

    # --- stats (NetSim::stat analog, network.rs:82-85) --------------------
    msg_sent: jax.Array
    msg_delivered: jax.Array
    msg_dropped: jax.Array
    ev_peak: jax.Array      # int32 — high-water mark of occupied event rows
                            # (capacity-tuning aid: size event_capacity to
                            # the workload instead of guessing)

    # --- flight-recorder ring (obs/rings.py; cfg.trace_cap) ---------------
    # A fixed-capacity ring of the last trace_cap dispatched events for
    # this lane, written inside the step — ring state RIDES IN SimState,
    # so it survives `lax.while_loop` and the fused runner yields traces.
    # trace_cap == 0 gives zero-size columns (compiled out). Columns are
    # always int32: like the collect_events record schema, table_dtype
    # must not leak into what observers read.
    trace_on: jax.Array     # bool — lane-sampling gate (init_batch sets it;
                            # lets a B=4096 sweep record e.g. 8 lanes)
    trace_pos: jax.Array    # int32 — events recorded so far (monotonic;
                            # the write slot is trace_pos % trace_cap, so
                            # pos > cap means the ring wrapped)
    trace_cap: jax.Array    # int32 — LOGICAL ring capacity (dynamic:
                            # cfg.trace_cap; the columns below are sized
                            # to its power-of-two bucket so sweeping
                            # trace_cap never recompiles — rows past
                            # trace_cap are simply never written)
    tr_now: jax.Array       # int32[bucket] — virtual time of the event
    tr_step: jax.Array      # int32[bucket] — step index (cross-ref with
                            # collect_events row order / state_at)
    tr_kind: jax.Array      # int32[bucket]
    tr_node: jax.Array      # int32[bucket]
    tr_src: jax.Array       # int32[bucket]
    tr_tag: jax.Array       # int32[bucket]
    tr_parent: jax.Array    # int32[bucket] — the dispatched event's
                            # ev_parent (the happens-before edge; -1 =
                            # external) — recorded per event, so the
                            # causal chain survives ring wrap up to the
                            # oldest surviving record
    tr_lamport: jax.Array   # int32[bucket] — the acting node's Lamport
                            # clock AFTER this dispatch
    tr_qlen: jax.Array      # int32[bucket] — event-table occupancy at
                            # this dispatch (rows pending INCLUDING the
                            # row being dispatched) — the queue-depth
                            # counter-track source (obs/profiler.py).
                            # Compiled in only when BOTH the ring and
                            # the profiler are (cfg.trace_cap > 0 and
                            # cfg.profile); zero-size otherwise, and
                            # ring readers skip zero-size columns
    tr_lat: jax.Array       # int32[bucket] — the dispatch's END-TO-END
                            # request latency when it was a completion
                            # (cfg.complete_kinds), -1 otherwise — the
                            # rolling-p99 counter-track source
                            # (obs/profiler.py). Compiled in only when
                            # BOTH the ring and the latency plane are
                            # (cfg.trace_cap > 0 and cfg.latency_hist);
                            # same skip contract as tr_qlen

    # --- prefix-coverage sketch (cfg.sketch_slots; obs/causal.py) ---------
    # Slot j holds the running sched_hash (lanes XOR-folded) after this
    # lane's (j+1)*sketch_every-th dispatch: two lanes' sketches first
    # differ at the slot whose schedule prefix first diverged — the
    # per-lane divergence depth parallel/stats.divergence_profile and
    # the corpus's early-divergence energy bonus read, with zero host
    # round-trips during the run. 0 means "checkpoint not reached".
    cov_sketch: jax.Array   # uint32[sketch_slots]
    sketch_every: jax.Array  # int32 — DYNAMIC fold period (cfg.sketch_every)

    # --- sim-profiler counter plane (cfg.profile; obs/profiler.py) --------
    # Per-lane, on-device counters written through the step's existing
    # one-hot dispatch machinery — where the simulated cluster spends
    # its effort, resident in SimState so a fused while_loop sweep
    # comes back with per-node utilization at zero new host
    # round-trips. Observation only (TRACE_FIELDS): no randomness
    # consumed, excluded from fingerprints, zero-size [N]/[N, K]
    # columns when compiled out (cfg.profile=False). All counters
    # SATURATE at int32 max — a long campaign reads "pegged", never a
    # wrapped negative (DESIGN §16).
    pf_on: jax.Array        # bool — lane gate (init_batch(profile_lanes=))
    pf_dispatch: jax.Array  # int32[N, N_EV_KINDS] — dispatches by
                            # (acting node, event kind); supervisor ops
                            # count at the node _apply_super RESOLVED
                            # (the Lamport-rule node), not the
                            # NODE_RANDOM placeholder
    pf_busy: jax.Array      # int32[N] — busy virtual time: each
                            # dispatch's now-delta attributed to its
                            # acting node (sums to final `now` over
                            # nodes when every step advanced the clock)
    pf_kill: jax.Array      # int32[N] — effective KILL/RESTART ops at
                            # this node (crash injections landed)
    pf_restart: jax.Array   # int32[N] — effective INIT/RESTART boots
    pf_qmax: jax.Array      # int32 — event-table occupancy high-water
                            # mark as seen at dispatch + emission time
                            # (capacity tuning; unlike ev_peak this
                            # also counts the pre-pop dispatch row and
                            # rides the profile gate, not collect_stats)
    pf_drop: jax.Array      # int32 — messages lost: send-side
                            # clog/loss + deliveries to dead nodes
    pf_delay: jax.Array     # int32 — total latency ticks added to
                            # delivered sends (mean delay =
                            # pf_delay / delivered sends)

    # --- SLO latency plane (cfg.latency_hist; obs/profiler.py) ------------
    # Log2-bucketed request-latency histograms that live ON the device
    # (DESIGN §17): bucket j counts latencies in [2^(j-1), 2^j) ticks
    # (bucket 0 = zero). Written through the step's one-hot dispatch
    # machinery like the pf_* counters; SATURATING; observation only
    # (TRACE_FIELDS — no randomness, no non-latency state, excluded
    # from fingerprints; zero-size when compiled out).
    lh_on: jax.Array        # bool — lane gate (init_batch(latency_lanes=))
    ev_root_t: jax.Array    # int32[C] — per pending row: virtual birth
                            # time of the row's causal ROOT request;
                            # -1 = external/unset (scenario rows, boots,
                            # host injections) — minted as the dispatch
                            # `now` at dispatch time and inherited by
                            # every emission of that dispatch (the r10
                            # provenance broadcast, carrying a time)
    lh_sojourn: jax.Array   # int32[N, B] — queue-wait per dispatch
                            # (now − dispatched row's deadline) at the
                            # acting node, log2-bucketed
    lh_e2e: jax.Array       # int32[N, B] — end-to-end latency
                            # (now − root birth time) of dispatches of
                            # cfg.complete_kinds, at the completion node
    lh_slo_miss: jax.Array  # int32[N] — completions with e2e latency
                            # > slo_target (when slo_target > 0)
    slo_target: jax.Array   # int32 ticks — DYNAMIC per-lane SLO target
                            # (cfg.slo_target seeds it; retune/fuzz
                            # without recompile, like tlimit)

    # --- windowed telemetry plane (cfg.series_windows; obs/series.py) -----
    # Per-lane sim-time metric SERIES resident in SimState (DESIGN §22):
    # window w covers virtual ticks [w*window_len, (w+1)*window_len),
    # events past W*window_len clamp into the last window. Written
    # through the step's one-hot dispatch machinery like the pf_*/lh_*
    # planes; SATURATING; observation only (TRACE_FIELDS — no
    # randomness, no non-series state, excluded from fingerprints;
    # zero-size when compiled out). Answers WHEN, not just how much:
    # a brownout during a partition window, a queue that spikes and
    # drains, a system that never recovers after heal.
    sr_on: jax.Array        # bool — lane gate (init_batch(series_lanes=))
    window_len: jax.Array   # int32 ticks per window — DYNAMIC operand
                            # (cfg.window_len seeds it; retune without
                            # recompile via Runtime.set_window_len)
    sr_dispatch: jax.Array  # int32[W, N] — dispatches by (window,
                            # acting node); supervisor ops count at the
                            # node _apply_super resolved (the pf_dispatch
                            # attribution rule)
    sr_busy: jax.Array      # int32[W, N] — busy virtual ticks by
                            # (window, acting node): each dispatch's
                            # now-delta lands in the window it ended in
    sr_qhw: jax.Array       # int32[W] — event-table occupancy
                            # high-water inside the window (dispatch +
                            # emission time, the pf_qmax rule per window)
    sr_drop: jax.Array      # int32[W] — messages lost in the window
                            # (send-side clog/loss + dead-node delivery)
    sr_dup: jax.Array       # int32[W] — duplicate re-arms fired in the
                            # window (the r19 dup-storm axis over time)
    sr_complete: jax.Array  # int32[W] — completions (cfg.complete_kinds)
                            # dispatched in the window; stays zero when
                            # the latency plane is off
    sr_slo_miss: jax.Array  # int32[W] — completions over slo_target in
                            # the window
    sr_lat: jax.Array       # int32[W, B] — per-window e2e log2
                            # histograms (the per-window p99 source for
                            # the recovery oracle and the sim-time
                            # counter tracks). Compiled in only when
                            # BOTH this plane and cfg.latency_hist are;
                            # zero-size otherwise
    sr_fault: jax.Array     # int32[W] — SRF_* bitmask of fault classes
                            # that landed in the window (OR-accumulated,
                            # never saturates) — the recovery oracle's
                            # "last disturbed window" axis

    # --- critical-path attribution plane (cfg.span_attr; obs/spans.py) ----
    # WHERE the tail comes from (DESIGN §24): every pending row carries
    # its chain's accumulated span vector (the ev_prov/ev_root_t
    # broadcast-select, carrying SPAN_WORDS words instead of one), and a
    # completion over the dynamic slo_target folds it into per-node
    # tail-attribution counters through the one-hot machinery.
    # Observation only (TRACE_FIELDS): no randomness, no non-span state,
    # excluded from fingerprints; zero-size when compiled out
    # (cfg.span_attr=False). Counters SATURATE at int32 max (§16).
    sp_on: jax.Array        # bool — lane gate (init_batch(span_lanes=))
    ev_span: jax.Array      # int32[C, SPAN_WORDS] — per pending row: the
                            # chain's accumulated queue-wait / transit /
                            # hops, dominant (node, magnitude), and the
                            # emitting dispatch's virtual time (see the
                            # SP_* word index above); external rows are
                            # [0, 0, 0, -1, 0, -1]
    sa_tail: jax.Array      # int32[N, SA_COMPONENTS] — per COMPLETION
                            # node: count / queue-wait / transit / hops
                            # of tail completions (e2e > slo_target);
                            # queue + transit of a completion sum to its
                            # e2e latency exactly (the telescoping rule,
                            # DESIGN §24) — the invariant the host
                            # parent-walk cross-check holds device-vs-ring
    sa_bottleneck: jax.Array  # int32[N] — how often node n owned a tail
                            # completion's DOMINANT segment (largest
                            # wait+transit hop) — the bottleneck histogram
    tr_qw: jax.Array        # int32[bucket] — the dispatch's OWN
                            # queue-wait (now − the popped row's
                            # deadline): the ring column that lets a host
                            # parent-walk split every hop into wait vs
                            # transit (obs/spans.py). Compiled in only
                            # when BOTH the ring and the span plane are
                            # (cfg.trace_cap > 0 and cfg.span_attr);
                            # same skip contract as tr_qlen/tr_lat

    # --- extension state (plugin framework analog, plugin.rs) -------------
    ext: Any                # dict: extension name -> its state subtree


def init_state(cfg: T.SimConfig, key: jax.Array, node_state: Any,
               ext_state: Any = None) -> SimState:
    """Fresh state for one trajectory. `node_state` must already carry the
    leading [N] axis (Runtime stacks the per-node spec)."""
    C, P, N = cfg.event_capacity, cfg.payload_words, cfg.n_nodes
    i32 = jnp.int32
    # narrow columns (cfg.table_dtype): same values, half the bytes —
    # t_tag/t_deadline/t_payload stay int32 (29-bit tags, time, data)
    ti = jnp.int16 if cfg.table_dtype == "int16" else jnp.int32
    return SimState(
        now=jnp.asarray(0, i32),
        key=key,
        # an OWNED copy, never the same buffer: runners donate the state,
        # and two pytree leaves aliasing one buffer break donation
        hash_base=jnp.array(key, copy=True),
        halted=jnp.asarray(False),
        crashed=jnp.asarray(False),
        crash_code=jnp.asarray(T.CRASH_NONE, i32),
        crash_node=jnp.asarray(-1, i32),
        oops=jnp.asarray(0, i32),
        steps=jnp.asarray(0, i32),
        # lane 0: FNV-1a 32 offset basis; lane 1: low half of the FNV-1a 64
        # offset basis (any distinct odd-ish seed works — the lanes only
        # need independent trajectories)
        sched_hash=jnp.asarray([2166136261, 0x84222325], jnp.uint32),
        tlimit=jnp.asarray(cfg.time_limit, i32),
        t_deadline=jnp.full((C,), T.T_INF, i32),
        t_kind=jnp.zeros((C,), ti),
        t_node=jnp.zeros((C,), ti),
        t_src=jnp.zeros((C,), ti),
        t_tag=jnp.zeros((C,), i32),
        t_payload=jnp.zeros((C, P), i32),
        # lineage rides the recorder gate (zero-size when compiled out);
        # template/scenario rows are external: parent -1, carried clock 0
        ev_prov=jnp.tile(jnp.asarray([[-1, 0]], i32),
                         (C if cfg.trace_cap > 0 else 0, 1)),
        lamport=jnp.zeros((N if cfg.trace_cap > 0 else 0,), i32),
        alive=jnp.zeros((N,), bool),
        paused=jnp.zeros((N,), bool),
        node_state=node_state,
        clog_node=jnp.zeros((N,), bool),
        clog_link=jnp.zeros((N, N), bool),
        loss=jnp.asarray(cfg.net.packet_loss_rate, jnp.float32),
        lat_lo=jnp.asarray(cfg.net.send_latency_min, i32),
        lat_hi=jnp.asarray(cfg.net.send_latency_max, i32),
        jitter=jnp.asarray(cfg.net.op_jitter_max, i32),
        skew=jnp.zeros((N,), i32),
        disk_lat=jnp.zeros((N,), i32),
        torn=jnp.zeros((N,), bool),
        dup_rate=jnp.zeros((N,), i32),
        prio_nudge=jnp.asarray(0, i32),
        msg_sent=jnp.asarray(0, i32),
        msg_delivered=jnp.asarray(0, i32),
        msg_dropped=jnp.asarray(0, i32),
        ev_peak=jnp.asarray(0, i32),
        # recorder default: every lane samples (when the ring is compiled
        # in at all); init_batch(trace_lanes=...) narrows the mask.
        # Columns are bucket-sized; trace_cap is the dynamic capacity.
        trace_on=jnp.asarray(cfg.trace_cap > 0),
        trace_pos=jnp.asarray(0, i32),
        trace_cap=jnp.asarray(cfg.trace_cap, i32),
        tr_now=jnp.zeros((cfg.trace_cap_bucket,), i32),
        tr_step=jnp.zeros((cfg.trace_cap_bucket,), i32),
        tr_kind=jnp.zeros((cfg.trace_cap_bucket,), i32),
        tr_node=jnp.zeros((cfg.trace_cap_bucket,), i32),
        tr_src=jnp.zeros((cfg.trace_cap_bucket,), i32),
        tr_tag=jnp.zeros((cfg.trace_cap_bucket,), i32),
        tr_parent=jnp.zeros((cfg.trace_cap_bucket,), i32),
        tr_lamport=jnp.zeros((cfg.trace_cap_bucket,), i32),
        # the queue-depth ring column needs both gates (see field docs)
        tr_qlen=jnp.zeros((cfg.trace_cap_bucket if cfg.profile else 0,),
                          i32),
        # the e2e-latency ring column likewise (ring AND latency plane)
        tr_lat=jnp.full((cfg.trace_cap_bucket if cfg.latency_hist > 0
                         else 0,), -1, i32),
        cov_sketch=jnp.zeros((cfg.sketch_slots,), jnp.uint32),
        sketch_every=jnp.asarray(cfg.sketch_every, i32),
        # profiler default: every lane counts (when the plane is
        # compiled in at all); init_batch(profile_lanes=...) narrows.
        # Vector columns are zero-size when compiled out, scalars stay
        # (never written then — same shape discipline as trace_pos)
        pf_on=jnp.asarray(cfg.profile),
        pf_dispatch=jnp.zeros((N if cfg.profile else 0, N_EV_KINDS), i32),
        pf_busy=jnp.zeros((N if cfg.profile else 0,), i32),
        pf_kill=jnp.zeros((N if cfg.profile else 0,), i32),
        pf_restart=jnp.zeros((N if cfg.profile else 0,), i32),
        pf_qmax=jnp.asarray(0, i32),
        pf_drop=jnp.asarray(0, i32),
        pf_delay=jnp.asarray(0, i32),
        # latency-plane default: every lane records (when compiled in);
        # init_batch(latency_lanes=...) narrows. Same zero-size shape
        # discipline as the pf_* columns; ev_root_t starts all-external
        lh_on=jnp.asarray(cfg.latency_hist > 0),
        ev_root_t=jnp.full((C if cfg.latency_hist > 0 else 0,), -1, i32),
        lh_sojourn=jnp.zeros((N if cfg.latency_hist > 0 else 0,
                              cfg.latency_hist), i32),
        lh_e2e=jnp.zeros((N if cfg.latency_hist > 0 else 0,
                          cfg.latency_hist), i32),
        lh_slo_miss=jnp.zeros((N if cfg.latency_hist > 0 else 0,), i32),
        slo_target=jnp.asarray(cfg.slo_target, i32),
        # windowed-telemetry default: every lane records (when compiled
        # in); init_batch(series_lanes=...) narrows. Zero-size [W]/[W, .]
        # columns at series_windows == 0; window_len stays a scalar
        # operand either way (never read then — the trace_pos shape
        # discipline). sr_lat needs BOTH gates, like tr_lat.
        sr_on=jnp.asarray(cfg.series_windows > 0),
        window_len=jnp.asarray(cfg.window_len, i32),
        sr_dispatch=jnp.zeros((cfg.series_windows, N), i32),
        sr_busy=jnp.zeros((cfg.series_windows, N), i32),
        sr_qhw=jnp.zeros((cfg.series_windows,), i32),
        sr_drop=jnp.zeros((cfg.series_windows,), i32),
        sr_dup=jnp.zeros((cfg.series_windows,), i32),
        sr_complete=jnp.zeros((cfg.series_windows,), i32),
        sr_slo_miss=jnp.zeros((cfg.series_windows,), i32),
        sr_lat=jnp.zeros((cfg.series_windows if cfg.latency_hist > 0
                          else 0, cfg.latency_hist), i32),
        sr_fault=jnp.zeros((cfg.series_windows,), i32),
        # span-attribution default: every lane attributes (when compiled
        # in); init_batch(span_lanes=...) narrows. Rows start external
        # ([0,0,0,-1,0,-1] — nothing accumulated, no dominant segment,
        # no emitter); tr_qw needs BOTH gates, like tr_qlen/tr_lat.
        sp_on=jnp.asarray(cfg.span_attr),
        ev_span=jnp.tile(jnp.asarray([[0, 0, 0, -1, 0, -1]], i32),
                         (C if cfg.span_attr else 0, 1)),
        sa_tail=jnp.zeros((N if cfg.span_attr else 0, SA_COMPONENTS), i32),
        sa_bottleneck=jnp.zeros((N if cfg.span_attr else 0,), i32),
        tr_qw=jnp.zeros((cfg.trace_cap_bucket if cfg.span_attr else 0,),
                        i32),
        ext=ext_state if ext_state is not None else {},
    )


# ---------------------------------------------------------------------------
# Lane checkpoints (r20, DESIGN §21): checkpoint ONE lane of a batched
# state — gather its leaves into an owned host copy — and broadcast it
# back into a fresh batch later. The snapshot/fork primitive: a lane
# seeded back with unchanged knobs/nudge continues leaf-for-leaf
# bit-identical to the parent lane (the step is a pure function of
# state), and a batch of B clones forked with fresh nudges/knob deltas
# amortizes the shared prefix (the Podracer branching-rollout shape).
# ---------------------------------------------------------------------------

# Observation planes a checkpoint may be re-seeded into a runtime with a
# DIFFERENT observability build than it was captured under (window
# replay upgrades the ring/profiler/latency plane mid-trajectory).
# Each plane adapts as a UNIT: when every leaf of the plane matches the
# target runtime's shapes/dtypes the checkpoint values are preserved
# verbatim (the bit-identical-continuation case); when any leaf differs
# the whole plane is re-initialized from the target runtime's template
# (fresh empty ring, external provenance, zeroed counters) — legal
# because the planes are observation-only (TRACE_FIELDS, DESIGN §9):
# they never feed the replay domain, so the trajectory and its
# fingerprint are unchanged either way. hash_base is the one
# TRACE_FIELDS member outside the planes: it IS consumed by the replay
# domain (ctx.hash_key) and is always carried over.
_CKPT_PLANES = {
    "ring": ("trace_on", "trace_pos", "trace_cap", "tr_now", "tr_step",
             "tr_kind", "tr_node", "tr_src", "tr_tag", "tr_parent",
             "tr_lamport", "tr_qlen", "tr_lat", "tr_qw"),
    "lineage": ("ev_prov", "lamport"),
    "sketch": ("cov_sketch", "sketch_every"),
    "profile": ("pf_on", "pf_dispatch", "pf_busy", "pf_kill", "pf_restart",
                "pf_qmax", "pf_drop", "pf_delay"),
    "latency": ("lh_on", "ev_root_t", "lh_sojourn", "lh_e2e",
                "lh_slo_miss", "slo_target"),
    "series": ("sr_on", "window_len", "sr_dispatch", "sr_busy", "sr_qhw",
               "sr_drop", "sr_dup", "sr_complete", "sr_slo_miss",
               "sr_lat", "sr_fault"),
    "span": ("sp_on", "ev_span", "sa_tail", "sa_bottleneck"),
}

# the WORLD slice of a structural signature: the fields two runtimes
# must agree on for a checkpoint's replay state to continue bit-
# identically — shapes of the replay-domain leaves (n_nodes,
# event_capacity, payload_words, table_dtype), the stats gate
# (collect_stats changes msg_* trajectories), and the jitter gate (a
# distinct replay domain). The OBSERVABILITY fields (trace bucket,
# sketch_slots, profile, latency_hist, complete/root kinds) and the
# emission_write lowering are deliberately excluded: differing there is
# the point of window replay. Indexes into the simconfig-v8 tuple
# (types.SimConfig.structural_signature — v7/v8 appended
# series_windows/span_attr at the END, so these indices still name the
# same world fields); the version string at [0] keeps the indexing
# honest across future signature revisions, and a pre-r23 (v7)
# checkpoint/store rejects on it automatically.
_SIG_WORLD_IDX = (0, 1, 2, 3, 4, 6, 9)

_LANE_CKPT_FORMAT = "madsim-lane-ckpt-r20"


class CheckpointMismatch(ValueError):
    """A LaneCheckpoint does not fit the target runtime's world shape
    (the StoreMismatch analog for checkpoints): different cluster
    size/event capacity/table dtype/model state schema, or a pre-r20
    checkpoint file without the versioned lane-checkpoint header."""


def _world_slice(sig) -> tuple:
    sig = tuple(sig)
    return tuple(sig[i] for i in _SIG_WORLD_IDX if i < len(sig))


def checkpoint_lane(batch_state: SimState, lane: int,
                    signature=None) -> "LaneCheckpoint":
    """Snapshot ONE lane of a batched SimState: one gather per leaf,
    then an owned host copy (the r8 donation discipline — the returned
    checkpoint outlives later donated runs of the batch's buffers).

    `signature` (the capturing runtime's `cfg.structural_signature()`)
    rides along for the save/load contract and the world-shape check in
    `seed_batch_from(rt=...)`; None skips the signature check (leaf
    shape/dtype validation still applies)."""
    leaf0 = jax.tree.leaves(batch_state)[0]
    if np.ndim(leaf0) < 1:
        raise ValueError("checkpoint_lane takes a BATCHED state "
                         "(leading lane axis); got an unbatched pytree")
    from ..utils.hostcopy import owned_host_copy
    lane = int(lane)
    lane_state = owned_host_copy(
        jax.tree.map(lambda a: a[lane], batch_state))
    return LaneCheckpoint(state=lane_state,
                          steps=int(np.asarray(lane_state.steps)),
                          signature=(tuple(signature)
                                     if signature is not None else None))


@dataclasses.dataclass
class LaneCheckpoint:
    """One lane's full simulation state, host-owned — everything the
    step function needs to continue the trajectory (clock, key, event
    table, node state, fault matrices, knobs/nudge) plus whatever
    observation-plane state the capturing build carried.

    `steps` is the lane's dispatch count at capture; `signature` the
    capturing runtime's structural signature (None when captured
    without one)."""

    state: Any
    steps: int
    signature: tuple | None = None

    # -- durable form (MIGRATION r20: versioned like the corpus store) --
    def save(self, path: str) -> None:
        """Write the checkpoint as an .npz with a versioned header —
        format marker, structural signature, step count, treedef — so
        `load` can reject mismatches cleanly instead of replaying a
        foreign world. Pre-r20 batch snapshots (runtime/checkpoint.py)
        carry no header and are rejected by `load`."""
        leaves, treedef = jax.tree.flatten(self.state)
        np.savez_compressed(
            path,
            __lane_ckpt__=np.frombuffer(
                _LANE_CKPT_FORMAT.encode(), dtype=np.uint8),
            __signature__=np.frombuffer(
                repr(self.signature).encode(), dtype=np.uint8),
            __steps__=np.asarray(int(self.steps), np.int64),
            __treedef__=np.frombuffer(
                repr(treedef).encode(), dtype=np.uint8),
            **{f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)})

    @staticmethod
    def load(path: str, rt=None, like: SimState | None = None
             ) -> "LaneCheckpoint":
        """Read a checkpoint written by `save`. Pass the runtime it will
        be seeded into (`rt`, preferred — supplies both the pytree
        structure and the structural signature for the world-shape
        check) or a bare single-lane `like` state for structure only.

        Rejections are CLEAN and typed: a file without the r20 header
        (e.g. a pre-r20 `runtime.checkpoint.save` batch snapshot) or
        with a mismatched format version raises CheckpointMismatch, as
        does a stored signature whose WORLD slice disagrees with `rt`'s
        (observability fields may differ — that is window replay's
        upgrade path, resolved leaf-by-leaf in `seed_batch_from`)."""
        import ast
        if rt is not None and like is None:
            like = rt._template
        if like is None:
            raise ValueError("LaneCheckpoint.load needs rt= or like= "
                             "to supply the pytree structure")
        with np.load(path) as z:
            if "__lane_ckpt__" not in z.files:
                raise CheckpointMismatch(
                    f"{path}: no lane-checkpoint header — a pre-r20 "
                    "snapshot (runtime.checkpoint.save batch format?) "
                    "cannot be loaded as a LaneCheckpoint")
            fmt = bytes(z["__lane_ckpt__"]).decode()
            if fmt != _LANE_CKPT_FORMAT:
                raise CheckpointMismatch(
                    f"{path}: lane-checkpoint format {fmt!r} != "
                    f"{_LANE_CKPT_FORMAT!r}")
            sig = ast.literal_eval(bytes(z["__signature__"]).decode())
            steps = int(z["__steps__"])
            # the signature is the authoritative world contract — check
            # it BEFORE leaf counting so a foreign world is named as
            # such, not as a leaf-count coincidence
            if (rt is not None and sig is not None
                    and _world_slice(sig)
                    != _world_slice(rt.cfg.structural_signature())):
                raise CheckpointMismatch(
                    f"{path}: checkpoint world signature "
                    f"{_world_slice(sig)} != runtime's "
                    f"{_world_slice(rt.cfg.structural_signature())}")
            leaves_like, treedef = jax.tree.flatten(like)
            n = len([k for k in z.files if k.startswith("leaf_")])
            if n != len(leaves_like):
                raise CheckpointMismatch(
                    f"{path}: checkpoint has {n} leaves, target expects "
                    f"{len(leaves_like)} — different world/model?")
            state = jax.tree.unflatten(
                treedef, [z[f"leaf_{i}"] for i in range(n)])
        return LaneCheckpoint(state=state, steps=steps, signature=sig)


def _tree_spec_equal(a, b) -> bool:
    if jax.tree.structure(a) != jax.tree.structure(b):
        return False
    return all(np.shape(x) == np.shape(y)
               and np.asarray(x).dtype == np.asarray(y).dtype
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def seed_batch_from(ckpt: LaneCheckpoint, batch: int, rt=None,
                    reset_planes: tuple = ()) -> SimState:
    """Broadcast a lane checkpoint into a fresh [batch]-lane SimState:
    every lane a clone of the checkpointed lane, mid-trajectory. With
    unchanged knobs/nudge each lane continues leaf-for-leaf
    bit-identical to the parent (the fidelity contract,
    tests/test_timetravel.py); perturb lanes afterwards
    (`with_prio_nudge`, `KnobPlan.apply`) to FORK the trajectory — the
    prefix-fork primitive.

    rt=None broadcasts the checkpoint verbatim (the caller promises a
    structurally identical runtime). With `rt`, the checkpoint is
    validated against — and adapted to — that runtime: every
    replay-domain leaf must match shape/dtype exactly
    (CheckpointMismatch otherwise — a different world NEVER silently
    produces garbage), while observation planes whose compiled shape
    differs are re-initialized from the runtime's template (the
    observability-UPGRADE path: replay a checkpoint captured ring-off
    under a big ring/profiler/latency build; same trajectory, DESIGN
    §21). `reset_planes` names planes to re-initialize even when their
    shapes match (e.g. ("ring",) for a window replay that must start
    from an empty ring)."""
    unknown = set(reset_planes) - set(_CKPT_PLANES)
    if unknown:
        raise ValueError(f"unknown reset_planes {sorted(unknown)} — "
                         f"valid planes: {sorted(_CKPT_PLANES)}")
    if reset_planes and rt is None:
        # fresh plane values come from the runtime's template — without
        # it the reset would be a silent no-op (the clones would carry
        # the parent's ring/counters into the "fresh" window)
        raise ValueError("reset_planes needs rt= (the reset re-"
                         "initializes planes from the runtime template)")
    src = ckpt.state
    if rt is None:
        merged = src
    else:
        if ckpt.signature is not None:
            want = _world_slice(rt.cfg.structural_signature())
            got = _world_slice(ckpt.signature)
            if got != want:
                raise CheckpointMismatch(
                    f"checkpoint world signature {got} != runtime's "
                    f"{want} — different cluster/world shape")
        tpl = rt._template
        plane_of = {f: p for p, fs in _CKPT_PLANES.items() for f in fs}
        fresh = {p: (p in reset_planes
                     or not _tree_spec_equal(
                         {f: getattr(src, f) for f in fs},
                         {f: getattr(tpl, f) for f in fs}))
                 for p, fs in _CKPT_PLANES.items()}
        vals = {}
        for f in type(src).__dataclass_fields__:
            s_v, t_v = getattr(src, f), getattr(tpl, f)
            plane = plane_of.get(f)
            if plane is not None:
                vals[f] = t_v if fresh[plane] else s_v
                continue
            # replay-domain leaf (hash_base included — consumed by
            # ctx.hash_key): must fit the target world exactly
            if not _tree_spec_equal(s_v, t_v):
                raise CheckpointMismatch(
                    f"checkpoint leaf {f!r} does not fit the target "
                    f"runtime (shape/dtype/structure mismatch) — "
                    f"different world or model schema")
            vals[f] = s_v
        merged = type(src)(**vals)
    B = int(batch)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(jnp.asarray(a),
                                   (B,) + jnp.asarray(a).shape), merged)


