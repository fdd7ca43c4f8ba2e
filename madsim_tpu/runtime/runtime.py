"""Runtime: the batched supervisor — madsim::runtime::Runtime, vectorized.

The reference Runtime owns RNG + executor + simulators and drives one seed to
completion on one thread (runtime/mod.rs:39-187). This Runtime compiles the
step engine once and drives a whole `[seed_batch]` of clusters through it in
fixed-size scan chunks, syncing to the host only between chunks (to test
"all halted" and to let host code inspect/fault-inject). Chunked scanning is
the host/device boundary discipline: supervisor logic lives in the scenario
table *inside* the trace; the Python loop only orchestrates jitted calls.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..compile.cache import COMPILE_LOG, PROGRAM_CACHE
from ..compile.persistent import enable_persistent_cache
from ..compile.signature import runtime_signature
from ..core import prng
from ..core import types as T
from ..core.api import Program
from ..core.state import SimState, init_state
from ..core.step import make_step
from ..utils.hashing import batch_fingerprints
from ..utils.hostcopy import owned_host_copy
from .scenario import Scenario


def _halted_count(state) -> int | None:
    """Halted-lane count for observer records; None when the batch spans
    non-addressable shards (multi-process sharding), where fetching the
    [B] lane would raise — the replicated-scalar `halted.all()` sync the
    runners rely on still works there, so observers degrade gracefully
    instead of killing the sweep."""
    h = state.halted
    if not getattr(h, "is_fully_addressable", True):
        return None
    return int(np.asarray(h).sum())


class Runtime:
    """Batched simulation runtime.

    Args:
      cfg: static SimConfig.
      programs: node programs (state machines).
      state_spec: one node's default protocol-state pytree (no node axis).
      node_prog: node -> program index (default: all nodes run programs[0]).
      scenario: scheduled supervisor ops; a HALT at cfg.time_limit is
        appended automatically if the scenario has none (set_time_limit
        analog, runtime/mod.rs:175-177).
      invariant: optional global safety check f(state) -> (bad, code).
      share_programs: resolve this Runtime's jitted runners through the
        process-level `compile.PROGRAM_CACHE` (keyed on the structural
        signature — see compile/signature.py), so structurally-identical
        Runtimes share one trace+compile per (batch shape, chunk length).
        False restores private per-instance jits (the fresh-compile
        control used by the cache-correctness tests).
    """

    def __init__(self, cfg: T.SimConfig, programs: Sequence[Program],
                 state_spec: Any, node_prog=None,
                 scenario: Scenario | None = None,
                 invariant: Callable | None = None,
                 persist: Any = None,
                 halt_when: Callable | None = None,
                 extensions: Sequence = (),
                 share_programs: bool = True,
                 lint: bool | str = False):
        self.cfg = cfg
        self.programs = list(programs)
        self.state_spec = state_spec
        self.node_prog = np.asarray(
            node_prog if node_prog is not None
            else np.zeros(cfg.n_nodes, np.int32), np.int32)
        self.invariant = invariant
        self.extensions = list(extensions)
        self._halt_when = halt_when
        self._persist = persist      # kept for derived() re-construction
        if lint:
            # the DetSan construction gate (analyze/lint.py, DESIGN §14):
            # lint=True raises on active findings BEFORE anything traces,
            # lint="warn" prints them and proceeds. Off by default — the
            # repo-wide `python -m madsim_tpu.analyze` gate covers source
            # statically; this flag adds the closure checks only live
            # objects allow (sig-degrade, mutable captures).
            from ..analyze.lint import (DeterminismLintError, active,
                                        lint_runtime)
            bad = active(lint_runtime(self))
            if bad and lint != "warn":
                raise DeterminismLintError(bad)
            for f in bad:
                print(f"detsan warn: {f.format()}")
        self._step = make_step(cfg, self.programs, self.node_prog,
                               self.state_spec, invariant, persist=persist,
                               halt_when=halt_when,
                               extensions=self.extensions)
        # structural signature: programs/specs/invariants are frozen into
        # the key AT CONSTRUCTION — mutating a program object afterwards
        # was already unsupported (the first run bakes the trace); with
        # sharing it would alias another Runtime's executable, so the
        # freeze formalizes the contract
        self._sig = (runtime_signature(cfg, self.programs, self.node_prog,
                                       self.state_spec, invariant, persist,
                                       halt_when, self.extensions)
                     if share_programs else None)
        self.set_scenario(scenario)

    def _shared(self, kind, build):
        """Resolve a jitted runner: through the process-level ProgramCache
        when sharing is on (a hit means another structurally-identical
        Runtime already built — and possibly compiled — it), else build
        privately."""
        if self._sig is None:
            return build()
        return PROGRAM_CACHE.get((self._sig, kind), build)

    def set_scenario(self, scenario: Scenario | None) -> None:
        """Swap the scheduled supervisor script WITHOUT recompiling.

        A scenario is initial-state DATA (event-table rows pre-loaded by
        `_build_template`), not part of the compiled step program — so
        replacing it never retraces. Copies the rows (the auto-HALT must
        never mutate a caller's object that might be shared across
        Runtimes with different time limits) and re-applies the auto-HALT
        at cfg.time_limit when the script has none. `harness.minimize`
        uses this to ddmin failing chaos scripts."""
        new = Scenario()
        if scenario is not None:
            new.rows = list(scenario.rows)
        if not new.has_halt():
            new.at(self.cfg.time_limit).halt()
        # build first, assign together: a capacity-overflow ValueError
        # must not leave rt.scenario describing a script the template
        # doesn't encode
        old = getattr(self, "scenario", None)
        self.scenario = new
        try:
            self._template = self._build_template()
        except Exception:
            self.scenario = old
            raise

    def derived(self, **overrides) -> "Runtime":
        """A Runtime over the SAME world — programs, state spec,
        node->program map, scenario, invariants, persistence mask,
        extensions — with config fields replaced. The
        observability-upgrade constructor window replay rides
        (obs/timetravel.py, DESIGN §21): derive a big-ring/profiled
        build of a runtime whose live sweep ran lean, replay a lane
        checkpoint through it, get the identical trajectory with more
        instrumentation. Replay-domain overrides (n_nodes, time_limit,
        jitter gate, ...) are legal too but produce a DIFFERENT replay
        domain — checkpoints then reject via the world-signature check.
        Shares the process program cache, so structurally-equal derived
        runtimes cost zero new compiles."""
        return Runtime(dataclasses.replace(self.cfg, **overrides),
                       self.programs, self.state_spec,
                       node_prog=self.node_prog, scenario=self.scenario,
                       invariant=self.invariant, persist=self._persist,
                       halt_when=self._halt_when,
                       extensions=self.extensions,
                       share_programs=self._sig is not None)

    def _ckpt_setup(self, ckpt_every, ckpt_log):
        """Shared ckpt_every/ckpt_log normalization for run()/run_fused:
        returns (ckpt_every, ckpt_log) or (None, None) when harvesting
        is off. The log is also stashed as `self.last_ckpt_log` so the
        sugar form `run(..., ckpt_every=K)` (no explicit log) still
        hands the harvest back."""
        if ckpt_every is None and ckpt_log is None:
            return None, None
        from ..obs.timetravel import CheckpointLog
        if ckpt_log is None:
            ckpt_log = CheckpointLog(every=ckpt_every)
        if ckpt_every is None:
            ckpt_every = ckpt_log.every
        if not ckpt_every or int(ckpt_every) <= 0:
            raise ValueError("ckpt_every must be a positive step count "
                             "(or pass a CheckpointLog with .every set)")
        ckpt_log.signature = self.cfg.structural_signature()
        self.last_ckpt_log = ckpt_log
        return int(ckpt_every), ckpt_log

    # ------------------------------------------------------------------
    def _build_template(self) -> SimState:
        """One-trajectory initial state with the event table pre-loaded:
        an OP_INIT row per node at t=0 (node boot) + all scenario rows."""
        cfg = self.cfg
        rows = self.scenario.build(cfg)
        n_init = cfg.n_nodes
        n_rows = n_init + rows["time"].shape[0]
        if n_rows > cfg.event_capacity:
            raise ValueError(
                f"scenario ({n_rows} rows) exceeds event_capacity "
                f"({cfg.event_capacity})")
        node_state = jax.tree.map(
            lambda a: jnp.broadcast_to(jnp.asarray(a),
                                       (cfg.n_nodes,) + jnp.asarray(a).shape),
            self.state_spec)
        from ..core.extension import build_ext_state
        s = init_state(cfg, prng.seed_key(0), node_state,
                       build_ext_state(cfg, self.extensions))

        C, Pw = cfg.event_capacity, cfg.payload_words
        deadline = np.full(C, T.T_INF, np.int32)
        kind = np.zeros(C, np.int32)
        node = np.zeros(C, np.int32)
        src = np.zeros(C, np.int32)
        tag = np.zeros(C, np.int32)
        payload = np.zeros((C, Pw), np.int32)
        # node boots at t=0 — except nodes with a scheduled Scenario.boot
        # (the create_node analog), which come up at their scheduled time
        deferred = {r.node for r in self.scenario.rows
                    if r.op == T.OP_INIT and r.node != T.NODE_RANDOM}
        deadline[:n_init] = 0
        kind[:n_init] = T.EV_SUPER
        node[:n_init] = np.arange(n_init)
        tag[:n_init] = T.OP_INIT
        for d in deferred:
            deadline[d] = T.T_INF
            kind[d] = 0
            tag[d] = 0
        # scenario ops
        r = rows["time"].shape[0]
        deadline[n_init:n_rows] = rows["time"]
        kind[n_init:n_rows] = T.EV_SUPER
        node[n_init:n_rows] = rows["node"]
        src[n_init:n_rows] = rows["src"]
        tag[n_init:n_rows] = rows["op"]
        payload[n_init:n_rows] = rows["payload"]
        return s.replace(
            t_deadline=jnp.asarray(deadline),
            t_kind=jnp.asarray(kind, s.t_kind.dtype),       # table_dtype
            t_node=jnp.asarray(node, s.t_node.dtype),
            t_src=jnp.asarray(src, s.t_src.dtype),
            t_tag=jnp.asarray(tag), t_payload=jnp.asarray(payload))

    # ------------------------------------------------------------------
    @staticmethod
    def _lane_mask(lanes, B: int, what: str) -> np.ndarray:
        """Normalize a lane selection (int index array or bool[B] mask)
        into a bool[B] mask — shared by the trace_lanes and
        profile_lanes sampling knobs."""
        lanes = np.asarray(lanes)
        if lanes.dtype == bool:
            if lanes.shape != (B,):
                raise ValueError(
                    f"bool {what} mask shape {lanes.shape} != "
                    f"batch ({B},)")
            return lanes
        mask = np.zeros(B, bool)
        mask[lanes.astype(np.int64)] = True
        return mask

    def init_batch(self, seeds, trace_lanes=None,
                   profile_lanes=None, latency_lanes=None,
                   series_lanes=None, span_lanes=None) -> SimState:
        """Initial batched state for an array of seeds (replay-by-seed:
        the same seed always reproduces the same trajectory, the
        MADSIM_TEST_SEED contract of macros lib.rs:141-145).

        trace_lanes: which LANES the flight-recorder ring records when
        cfg.trace_cap > 0 (None = all; an int index array or a bool[B]
        mask narrows it — the lane-sampling knob that lets a B=4096
        sweep record 8 lanes instead of paying ring bandwidth on all of
        them). Lanes, not seeds: sampling is a property of this batch's
        layout, and obs/rings.py readers take lane indices too.

        profile_lanes: which lanes the sim-profiler counter plane counts
        when cfg.profile (None = all; same index/bool-mask forms). The
        masked-off build is the ship-with-it shape: profile=True
        compiled in, lanes flipped on only for the sweeps being
        profiled.

        latency_lanes: which lanes the SLO latency plane histograms
        when cfg.latency_hist > 0 (None = all; same forms). NOTE: the
        root-time column ev_root_t is maintained on every lane
        regardless — only the histogram folds are gated — so flipping a
        lane on mid-campaign needs no warm-up. A runtime whose `invariant=` is
        harness.slo_invariant should keep every lane on: a masked lane
        never folds, so its SLO can never fire.

        series_lanes: which lanes the windowed telemetry plane records
        when cfg.series_windows > 0 (None = all; same forms). A runtime
        whose `invariant=` is harness.recovery_invariant should keep
        every lane on — a masked lane's windows never fill, so its recovery
        oracle can never fire (the slo_invariant rule).

        span_lanes: which lanes the critical-path attribution plane
        attributes when cfg.span_attr (None = all; same forms). Like
        ev_root_t, the carried ev_span column is maintained on every
        lane regardless — only the sa_* counter folds are gated — so
        flipping a lane on mid-campaign needs no warm-up.
        """
        seeds = jnp.atleast_1d(jnp.asarray(seeds, jnp.uint32))
        keys = jax.vmap(prng.seed_key)(seeds)
        batched = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (seeds.shape[0],) + a.shape),
            self._template)
        # hash_base keeps the UNCONSUMED seed key frozen beside the
        # splitting trajectory key — the (seed, node) hash-stream root.
        # An owned copy: aliasing keys' buffer would break donation
        batched = batched.replace(key=keys,
                                  hash_base=jnp.array(keys, copy=True))
        if trace_lanes is not None:
            if self.cfg.trace_cap == 0:
                raise ValueError(
                    "trace_lanes given but cfg.trace_cap == 0 — the ring "
                    "is compiled out; set SimConfig(trace_cap=...) > 0")
            mask = self._lane_mask(trace_lanes, int(seeds.shape[0]),
                                   "trace_lanes")
            batched = batched.replace(trace_on=jnp.asarray(mask))
        if profile_lanes is not None:
            if not self.cfg.profile:
                raise ValueError(
                    "profile_lanes given but cfg.profile is False — the "
                    "counter plane is compiled out; set "
                    "SimConfig(profile=True)")
            mask = self._lane_mask(profile_lanes, int(seeds.shape[0]),
                                   "profile_lanes")
            batched = batched.replace(pf_on=jnp.asarray(mask))
        if latency_lanes is not None:
            if self.cfg.latency_hist == 0:
                raise ValueError(
                    "latency_lanes given but cfg.latency_hist == 0 — the "
                    "latency plane is compiled out; set "
                    "SimConfig(latency_hist=...) > 0")
            mask = self._lane_mask(latency_lanes, int(seeds.shape[0]),
                                   "latency_lanes")
            batched = batched.replace(lh_on=jnp.asarray(mask))
        if series_lanes is not None:
            if self.cfg.series_windows == 0:
                raise ValueError(
                    "series_lanes given but cfg.series_windows == 0 — the "
                    "windowed telemetry plane is compiled out; set "
                    "SimConfig(series_windows=...) > 0")
            mask = self._lane_mask(series_lanes, int(seeds.shape[0]),
                                   "series_lanes")
            batched = batched.replace(sr_on=jnp.asarray(mask))
        if span_lanes is not None:
            if not self.cfg.span_attr:
                raise ValueError(
                    "span_lanes given but cfg.span_attr is False — the "
                    "attribution plane is compiled out; set "
                    "SimConfig(span_attr=True)")
            mask = self._lane_mask(span_lanes, int(seeds.shape[0]),
                                   "span_lanes")
            batched = batched.replace(sp_on=jnp.asarray(mask))
        return batched

    def init_single(self, seed: int) -> SimState:
        return self.init_batch(jnp.asarray([seed], jnp.uint32))

    # ------------------------------------------------------------------
    @functools.cached_property
    def _run_chunk(self):
        return {c: self._shared(("chunk", c),
                                functools.partial(self._compile_chunk, c))
                for c in (True, False)}

    def _compile_chunk(self, collect_events: bool):
        # scan over steps of the vmapped step: one XLA program advances the
        # whole batch chunk_len times
        vstep = jax.vmap(self._step)

        def run(state: SimState, chunk_len: int):
            # traced-Python side effect: fires once per retrace, i.e. per
            # fresh executable (modulo persistent-cache compile skips) —
            # the compile counter CI prints and tests assert on
            COMPILE_LOG.note_trace("chunk_runner", collect=collect_events,
                                   chunk=chunk_len,
                                   batch=int(state.halted.shape[0]))

            def body(s, _):
                s, rec = vstep(s)
                return s, (rec if collect_events else 0)
            return jax.lax.scan(body, state, length=chunk_len)

        return jax.jit(run, static_argnums=1, donate_argnums=0)

    @functools.cached_property
    def _fused_runner(self):
        """Whole-sweep-in-one-dispatch runner: a jitted `lax.while_loop`
        whose body is the same vmapped-scan chunk as `_run_chunk` and whose
        predicate — `(chunks_done < n_chunks) & ~halted.all()` — evaluates
        ON-DEVICE. The chunked `run()` pays a device→host round-trip per
        chunk for `bool(state.halted.all())`; here the whole sweep is one
        XLA dispatch with donated buffers, so the host thread returns
        immediately (async dispatch) and the device never idles between
        chunks. Under a sharded batch the predicate's `all()` lowers to a
        cross-chip all-reduce — no host involvement there either.

        `n_chunks` is a traced operand (no recompile per sweep length);
        `chunk_len` is static (scan length must be)."""
        return self._shared("fused", self._compile_fused)

    def _compile_fused(self):
        vstep = jax.vmap(self._step)

        def run(state: SimState, n_chunks, chunk_len: int):
            COMPILE_LOG.note_trace("fused_runner", chunk=chunk_len,
                                   batch=int(state.halted.shape[0]))

            def chunk_body(s, _):
                s, _ = vstep(s)
                return s, None

            def cond(carry):
                i, s = carry
                return (i < n_chunks) & ~s.halted.all()

            def body(carry):
                i, s = carry
                s, _ = jax.lax.scan(chunk_body, s, length=chunk_len)
                return i + 1, s

            _, final = jax.lax.while_loop(
                cond, body, (jnp.asarray(0, jnp.int32), state))
            return final

        return jax.jit(run, static_argnums=2, donate_argnums=0)

    def fused_op_scopes(self, batch: int, chunk: int = 512) -> dict[str, str]:
        """Which step phase each instruction of the compiled `run_fused`
        program belongs to: {instruction name as a device profile shows
        it (`%fusion.764`): innermost `step.*` phase (obs/scopes.py), or
        "" for none (the while loops, copies, the loop predicate)}.

        Use it to name the phases in a profile of your own model's
        `run_fused(init_batch(seeds), steps, chunk)` at `batch` lanes: sum
        each op's device time under its phase. It compiles the program for
        the default device from abstract shapes, so nothing runs; where
        the program was already compiled with the persistent cache on,
        the compile is a cache hit."""
        from ..obs.scopes import op_scopes
        enable_persistent_cache()
        state = jax.eval_shape(self.init_batch, np.zeros(batch, np.uint32))
        n_chunks = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = self._fused_runner.lower(state, n_chunks, chunk).compile()
        return op_scopes(compiled.as_text())

    def run_fused(self, state: SimState, max_steps: int,
                  chunk: int = 512,
                  ckpt_every: int | None = None, ckpt_log=None) -> SimState:
        """`run()` without the per-chunk host sync: advance until every
        trajectory halts or ~max_steps events each (rounded up to a chunk
        multiple), as ONE XLA dispatch (see `_fused_runner`).

        Bitwise-equivalent to `run(state, max_steps, chunk)`: the loop
        applies the identical vmapped-scan chunk body under the identical
        continue condition, so final states (and fingerprints) match the
        chunked runner exactly (tests/test_fused.py asserts this).

        Trade-offs vs `run()`: no `collect_events` (a while_loop cannot
        stack per-step records; use `run()`/`run_single` for the full
        stream) and no between-chunk host inspection (use `run()` for
        interactive `inject`/`kill` supervision). The fused path is NOT
        blind, though: with `cfg.trace_cap > 0` the flight-recorder ring
        rides in SimState through the while_loop, so the last trace_cap
        events of every sampled lane come back with the final state
        (obs/rings.py reads them; obs/trace.py exports Perfetto JSON).
        Input buffers are DONATED — do not reuse `state` after calling.
        Works on sharded, non-addressable batches (it is pure SPMD),
        unlike `run_compacting`.

        ckpt_every / ckpt_log (r20, DESIGN §21): when set, the sweep is
        segmented into fused dispatches of ~ckpt_every steps each and a
        per-lane checkpoint (owned host copy of the batch) is harvested
        at each segment boundary — the boundary IS the sync the harvest
        needs, so checkpointing adds exactly the syncs it is paid for
        and the default (off) keeps the single-dispatch shape
        untouched. Trajectories are bit-identical either way: segments
        re-enter the same fused executable and frozen lanes are
        identity (tests/test_timetravel.py holds it).
        """
        enable_persistent_cache()
        n_chunks = -(-max_steps // chunk)
        ckpt_every, ckpt_log = self._ckpt_setup(ckpt_every, ckpt_log)
        if ckpt_every is None:
            return self._fused_runner(state,
                                      jnp.asarray(n_chunks, jnp.int32),
                                      chunk)
        seg = max(1, -(-ckpt_every // chunk))     # chunks per segment
        ckpt_log.harvest(state, steps_done=0)     # entry = zeroth ckpt
        total = 0
        while total < n_chunks:
            m = min(seg, n_chunks - total)
            state = self._fused_runner(state, jnp.asarray(m, jnp.int32),
                                       chunk)
            total += m
            if bool(state.halted.all()):
                break
            if total < n_chunks:   # a post-final harvest is dead weight
                ckpt_log.harvest(state, steps_done=total * chunk)
        return state

    def run_fused_sharded(self, state: SimState, max_steps: int,
                          chunk: int = 512, mesh=None) -> SimState:
        """Lane→shard plumbing (r13): place `state`'s leading [B] lane
        axis over a device mesh and drive it with the fused runner as
        ONE SPMD dispatch. Lanes never talk to each other, so the only
        cross-shard traffic is the while_loop predicate's `halted.all()`
        — an all-reduce per chunk riding ICI (or host threads on a
        virtual CPU mesh), no host round-trips.

        Unlike `parallel.distributed.run_fused_sharded` (which builds
        the batch FROM seeds and handles multi-process assembly), this
        takes an already-built batched state — the entry point the
        sharded fuzz driver needs, where knob mutation has already been
        applied to the init state before it shards. `mesh` defaults to
        a 1-D 'seeds' mesh over every local device; B must divide the
        mesh size. A 1-device mesh is the bitwise-degenerate case: the
        sharded executable computes exactly the unsharded values
        (tests/test_shard.py holds the whole-campaign version of that).
        Input buffers are donated, like `run_fused`."""
        from ..parallel.mesh import seed_mesh, shard_batch
        if mesh is None:
            mesh = seed_mesh()
        return self.run_fused(shard_batch(state, mesh), max_steps, chunk)

    def run(self, state: SimState, max_steps: int, chunk: int = 512,
            collect_events: bool = False, observer=None,
            ckpt_every: int | None = None, ckpt_log=None):
        """Advance until every trajectory halts or ~max_steps events each
        (rounded up to a chunk multiple). Returns (state, events|None).

        Overshoot contract (`collect_events=True`): chunks are always run
        in full and the loop continues while ANY lane is live, so a lane
        that halts early keeps emitting records for every remaining chunk
        of the sweep (not just its own chunk's tail — a lane halting in
        chunk 1 of 8 gets ~7 chunks of frozen records). Those records
        carry `fired=False` — trace consumers must filter on `fired`,
        never on step count (tests/test_fused.py asserts the frozen-lane
        tail is present and `fired=False`).

        observer: optional obs.metrics.SweepObserver — gets an `on_chunk`
        record at every chunk boundary (lanes halted, dispatched
        lane-steps/s wall-clock) and an `on_done` at the end. The hooks
        ride the host sync each chunk ALREADY pays for the
        `halted.all()` test — no new sync points; the only extra cost is
        transferring the [B] halted lane at a boundary the host was
        blocked on anyway.

        ckpt_every / ckpt_log (r20, DESIGN §21): harvest periodic
        per-lane checkpoints — an owned host copy of the whole batch —
        into an `obs.timetravel.CheckpointLog` at the first chunk sync
        on or past each multiple of `ckpt_every` steps. Harvests ride
        the per-chunk host sync this runner already pays (no new sync
        points, the §9 rule); off (the default) costs literally
        nothing. Pass an explicit log to accumulate across runs, or
        just `ckpt_every=K` — the auto-created log is also stashed as
        `self.last_ckpt_log`. Any lane's checkpoint re-seeds via
        `core.state.seed_batch_from` / `obs.timetravel.replay_window`.
        """
        ckpt_every, ckpt_log = self._ckpt_setup(ckpt_every, ckpt_log)
        if ckpt_every is not None:
            # the ENTRY state is the zeroth checkpoint: with it in the
            # log, some checkpoint always precedes any causal root, so
            # time_travel_explain's truncated=False guarantee holds
            # unconditionally (ring capacity allowing). Costs one host
            # copy of a state the host just built — no device sync.
            ckpt_log.harvest(state, steps_done=0)
        next_harvest = ckpt_every
        # always run full chunks: halted trajectories are frozen by the
        # live-mask gating inside the step, so overshooting max_steps is free
        # and avoids a second XLA compile for a partial tail chunk
        runner = self._run_chunk[collect_events]
        events = [] if collect_events else None
        B = state.halted.shape[0]
        done = 0
        k = 0
        t0 = time.perf_counter()
        t_prev = t0
        while done < max_steps:
            state, recs = runner(state, chunk)
            done += chunk
            k += 1
            if collect_events:
                # np.asarray (zero-copy where possible) is safe here:
                # records are runner OUTPUTS and are never donated —
                # only the threaded state is — and the view's base
                # reference keeps the buffer alive. The owned-copy rule
                # (utils/hostcopy) applies to stashes of soon-to-be-
                # donated state, like run_compacting's.
                events.append(jax.tree.map(np.asarray, recs))
            all_halted = bool(state.halted.all())
            if (ckpt_every is not None and done >= next_harvest
                    and not all_halted and done < max_steps):
                # at the sync the halted.all() test just paid; an owned
                # host copy (utils/hostcopy) — the next runner() call
                # donates these buffers. An all-halted batch — or the
                # sweep's final state (done >= max_steps) — is an end
                # state, not a restart point, so it is never harvested
                # (run_fused applies the same post-final rule).
                ckpt_log.harvest(state, steps_done=done)
                next_harvest = done + ckpt_every
            if observer is not None:
                t_now = time.perf_counter()
                observer.on_chunk(dict(
                    kind="chunk", chunk=k, steps_done=done, batch=B,
                    lanes_halted=_halted_count(state),
                    wall_s=t_now - t0,
                    lane_steps_per_sec=B * chunk / max(t_now - t_prev, 1e-9)))
                t_prev = t_now
            if all_halted:
                break
        if observer is not None:
            wall = time.perf_counter() - t0
            rec = dict(
                kind="done", steps_done=done, batch=B, chunks=k,
                lanes_halted=_halted_count(state),
                wall_s=wall,
                lane_steps_per_sec=B * done / max(wall, 1e-9))
            if self.cfg.latency_hist > 0 and getattr(
                    state.halted, "is_fully_addressable", True):
                # the sweep's tail-latency rollup rides the final sync
                # the observer already pays (O(buckets) transfer);
                # skipped on non-addressable multi-process batches,
                # like lanes_halted
                from ..parallel.stats import latency_brief
                lb = latency_brief(state)
                if lb is not None:
                    rec.update(lat_p50=lb["e2e_p50"],
                               lat_p99=lb["e2e_p99"],
                               slo_miss=lb["slo_miss"])
            observer.on_done(rec)
        if collect_events and events:
            events = jax.tree.map(
                lambda *xs: np.concatenate(xs, axis=0), *events)
        return state, events

    def run_compacting(self, state: SimState, max_steps: int,
                       chunk: int = 512, compact_when: float = 0.5,
                       min_batch: int = 256, observer=None):
        """Like run(), but with divergent-trajectory early-exit compaction
        (BASELINE.md config 4): when more than `compact_when` of the lanes
        have halted, stash them host-side and re-pack the survivors into a
        smaller batch (padded to a power of two so at most log2(B) distinct
        XLA programs compile). With long-tailed workloads most lanes finish
        early; without compaction they occupy device lanes doing nothing.

        Returns the full-batch final state in the ORIGINAL lane order.

        Single-process only: compaction re-packs lanes through host numpy,
        which requires every shard to be addressable from this process.
        Under multi-process sharding (parallel/distributed.py) run() works
        unchanged — frozen lanes are already ~free there — or compact each
        host's local slice before assembling the global batch.

        observer: optional obs.metrics.SweepObserver — `on_chunk` per
        chunk, `on_compact` at every re-pack (from/to batch widths), and
        `on_done` at the end; hooks ride the per-chunk host sync this
        runner already pays (it transfers the full halted lane anyway).
        """
        leaf = jax.tree.leaves(state)[0]
        if (hasattr(leaf, "is_fully_addressable")
                and not leaf.is_fully_addressable):
            raise ValueError(
                "run_compacting gathers lanes host-side and needs a fully "
                "addressable (single-process) batch; under multi-process "
                "sharding use run(), or compact per-host slices before "
                "assembly")
        # re-packs pass through the host: a batch sharded over several
        # devices is put back on its own shardings, not on device 0
        sharding = getattr(leaf, "sharding", None)
        shardings = (jax.tree.map(lambda a: a.sharding, state)
                     if sharding is not None and len(sharding.device_set) > 1
                     else None)

        def place(host_state):
            if shardings is None:
                return jax.tree.map(jnp.asarray, host_state)
            return jax.tree.map(jax.device_put, host_state, shardings)

        runner = self._run_chunk[False]
        B = int(np.asarray(state.halted).shape[0])
        orig_idx = np.arange(B)
        stash: list[tuple[np.ndarray, Any]] = []  # (orig indices, host copy)
        done = 0
        k = 0
        repacks = 0
        stashed_total = 0
        t0 = time.perf_counter()
        t_prev = t0
        while done < max_steps:
            state, _ = runner(state, chunk)
            done += chunk
            k += 1
            halted = np.asarray(state.halted)
            n = halted.shape[0]
            if observer is not None:
                t_now = time.perf_counter()
                # same convention as run(): lanes_halted is a fraction
                # OF `batch` (the current, post-compaction width);
                # stashed lanes are reported separately so global
                # progress is lanes_halted + stashed_total of the
                # original batch, and a h/batch progress bar never
                # exceeds 100%
                observer.on_chunk(dict(
                    kind="chunk", chunk=k, steps_done=done, batch=n,
                    lanes_halted=int(halted.sum()),
                    stashed_total=stashed_total,
                    wall_s=t_now - t0,
                    lane_steps_per_sec=n * chunk / max(t_now - t_prev,
                                                       1e-9)))
                t_prev = t_now
            if halted.all():
                break
            live = int((~halted).sum())
            if n > min_batch and live / n < (1 - compact_when):
                # pad the live set with halted lanes up to a power of two
                # (frozen lanes are ~free); stash the rest host-side
                target = max(min_batch, 1 << int(np.ceil(np.log2(live))))
                if target < n:
                    live_idx = np.nonzero(~halted)[0]
                    pad_idx = np.nonzero(halted)[0][:target - live]
                    keep = np.concatenate([live_idx, pad_idx])
                    drop = np.setdiff1d(np.arange(n), keep)
                    # OWNED copies, not np.asarray views: the next
                    # runner() call DONATES the state buffers — a
                    # stashed view would read recycled memory (the PR-2
                    # warm-compile-cache bug class; utils/hostcopy.py
                    # documents it)
                    host = owned_host_copy(state)
                    stash.append((orig_idx[drop],
                                  jax.tree.map(lambda a: a[drop], host)))
                    state = place(jax.tree.map(lambda a: a[keep], host))
                    orig_idx = orig_idx[keep]
                    repacks += 1
                    stashed_total += len(drop)
                    if observer is not None:
                        observer.on_compact(dict(
                            kind="compact", steps_done=done,
                            from_batch=n, to_batch=target,
                            stashed=len(drop), stashed_total=stashed_total,
                            wall_s=time.perf_counter() - t0))
        if observer is not None:
            wall = time.perf_counter() - t0
            # done is batch-global: every stashed lane is halted by
            # construction, so halted-in-final + stashed is of B
            observer.on_done(dict(
                kind="done", steps_done=done, batch=B, chunks=k,
                repacks=repacks,
                lanes_halted=int(np.asarray(state.halted).sum())
                + stashed_total,
                stashed_total=stashed_total,
                wall_s=wall))
        # merge: stashed lanes + final state, back in original order
        # (owned copies for the same donation-aliasing reason as above)
        final_host = owned_host_copy(state)
        parts = stash + [(orig_idx, final_host)]
        order = np.concatenate([p[0] for p in parts])
        inv = np.argsort(order)

        def merge(*leaves):
            return np.concatenate(leaves, axis=0)[inv]

        return place(jax.tree.map(merge, *[p[1] for p in parts]))

    def run_single(self, seed: int, max_steps: int, chunk: int = 512,
                   collect_events: bool = True):
        """Debug path: one seed, optionally with the event trace — the
        single-seed replay used to debug a failing seed (the env_logger +
        MADSIM_TEST_SEED repro analog)."""
        state = self.init_single(seed)
        return self.run(state, max_steps, chunk, collect_events)

    def state_at(self, seed: int, step: int):
        """Time travel: the exact state after `step` events of `seed`.

        Decomposes `step` into power-of-two chunks so at most log2(step)
        distinct chunk lengths ever compile (each cached per Runtime) —
        an arbitrary step count never costs an arbitrary-length compile.
        Pair with `find_divergence` / `run_single(collect_events=True)`:
        localize a step, then inspect the full cluster state right there.
        The one exact-step loop, shared with the r20 replay plane
        (`obs.timetravel.advance_exact` — this call is the uncapped
        single-lane case).
        """
        from ..obs.timetravel import advance_exact
        return advance_exact(self, self.init_single(seed), step,
                             chunk=1 << 30)

    # ------------------------------------------------------------------
    # Imperative supervisor surface (Handle::kill/... runtime/mod.rs:200-256)
    # for host-driven scenarios: injects a supervisor op into every
    # trajectory's event table at its current virtual time; it dispatches on
    # the next step. Prefer Scenario for anything that can be pre-scripted
    # (it stays entirely on-device); this is for interactive control between
    # run() chunks.
    @functools.cached_property
    def _inject(self):
        return self._shared("inject", self._compile_inject)

    def _compile_inject(self):
        from ..core import types as Ty
        from ..ops.select import first_k_free

        cfg = self.cfg

        def one(state, op, node, src, payload):
            free = state.t_kind == Ty.EV_FREE
            slots, ok = first_k_free(free, 1)
            slot, ok = slots[0], ok[0]
            w = ok & ~state.halted
            lineage = {}
            if cfg.trace_cap > 0:
                # host-injected ops are EXTERNAL causes (parent -1,
                # carried clock 0) — without this the reused slot would
                # keep a stale parent from its previous occupant
                lineage = dict(
                    ev_prov=state.ev_prov.at[slot].set(
                        jnp.where(w, jnp.asarray([-1, 0], jnp.int32),
                                  state.ev_prov[slot])))
            if cfg.latency_hist > 0:
                # same external-cause contract for the latency plane:
                # the injected op's root time (-1 = unset) is minted at
                # its own dispatch, not inherited from the slot's
                # previous occupant
                lineage["ev_root_t"] = state.ev_root_t.at[slot].set(
                    jnp.where(w, jnp.asarray(-1, jnp.int32),
                              state.ev_root_t[slot]))
            if cfg.span_attr:
                # and for the span plane: an injected op starts a fresh
                # chain — nothing accumulated, no dominant segment, no
                # emitter stamp
                lineage["ev_span"] = state.ev_span.at[slot].set(
                    jnp.where(w,
                              jnp.asarray([0, 0, 0, -1, 0, -1], jnp.int32),
                              state.ev_span[slot]))
            return state.replace(
                **lineage,
                t_deadline=state.t_deadline.at[slot].set(
                    jnp.where(w, state.now, state.t_deadline[slot])),
                t_kind=state.t_kind.at[slot].set(
                    jnp.where(w, Ty.EV_SUPER,
                              state.t_kind[slot]).astype(state.t_kind.dtype)),
                t_node=state.t_node.at[slot].set(
                    jnp.where(w, node,
                              state.t_node[slot]).astype(state.t_node.dtype)),
                t_src=state.t_src.at[slot].set(
                    jnp.where(w, src,
                              state.t_src[slot]).astype(state.t_src.dtype)),
                t_tag=state.t_tag.at[slot].set(
                    jnp.where(w, op, state.t_tag[slot])),
                t_payload=state.t_payload.at[slot].set(
                    jnp.where(w, payload, state.t_payload[slot])),
                oops=state.oops | jnp.where(~ok & ~state.halted,
                                            Ty.OOPS_EVENT_OVERFLOW, 0),
            )

        return jax.jit(jax.vmap(one, in_axes=(0, None, None, None, None)))

    def inject(self, state: SimState, op: int, node: int = 0, src: int = 0,
               payload=()) -> SimState:
        pw = np.zeros(self.cfg.payload_words, np.int32)
        pw[:len(payload)] = payload
        return self._inject(state, jnp.asarray(op, jnp.int32),
                            jnp.asarray(node, jnp.int32),
                            jnp.asarray(src, jnp.int32), jnp.asarray(pw))

    def kill(self, state, node):
        return self.inject(state, T.OP_KILL, node)

    def restart(self, state, node):
        return self.inject(state, T.OP_RESTART, node)

    def pause(self, state, node):
        return self.inject(state, T.OP_PAUSE, node)

    def resume(self, state, node):
        return self.inject(state, T.OP_RESUME, node)

    def clog_link(self, state, src, dst):
        return self.inject(state, T.OP_CLOG_LINK, dst, src)

    def heal(self, state):
        return self.inject(state, T.OP_HEAL)

    def set_time_limit(self, state: SimState, limit: int) -> SimState:
        """Move the virtual-time limit of every trajectory (the
        runtime/mod.rs:175-177 set_time_limit analog). The limit is dynamic
        state, so no recompile: both the hard-stop check and the auto-HALT
        scenario row (identified by sitting exactly at the current limit)
        are rewritten in place."""
        limit = jnp.asarray(limit, jnp.int32)
        auto = ((state.t_kind == T.EV_SUPER) & (state.t_tag == T.OP_HALT)
                & (state.t_deadline == jnp.expand_dims(state.tlimit, -1)))
        return state.replace(
            tlimit=jnp.full_like(state.tlimit, limit),
            t_deadline=jnp.where(auto, limit, state.t_deadline))

    def set_slo_target(self, state: SimState, target: int) -> SimState:
        """Retune every trajectory's SLO target (ticks; 0 disables the
        miss counter) — slo_target is dynamic state like tlimit, so no
        recompile. Requires the latency plane compiled in
        (cfg.latency_hist > 0): a target with no histograms to miss
        against would silently count nothing."""
        if self.cfg.latency_hist == 0:
            raise ValueError(
                "set_slo_target needs cfg.latency_hist > 0 — the latency "
                "plane is compiled out")
        return state.replace(
            slo_target=jnp.full_like(state.slo_target, int(target)))

    def set_window_len(self, state: SimState, ticks: int) -> SimState:
        """Retune every trajectory's series window length (virtual ticks
        per window) — window_len is dynamic state like slo_target, so no
        recompile (the r8 structural/dynamic discipline: the window
        COUNT shapes the program, the window LENGTH rides as an
        operand). Requires the windowed telemetry plane compiled in
        (cfg.series_windows > 0). Retuning MID-RUN re-buckets only
        future dispatches — already-folded windows keep their old
        boundaries — so retune between sweeps, not inside one, unless
        a mixed axis is what you want."""
        if self.cfg.series_windows == 0:
            raise ValueError(
                "set_window_len needs cfg.series_windows > 0 — the "
                "windowed telemetry plane is compiled out")
        if int(ticks) < 1:
            raise ValueError("window_len must be >= 1 tick")
        return state.replace(
            window_len=jnp.full_like(state.window_len, int(ticks)))

    # ------------------------------------------------------------------
    def fingerprints(self, state: SimState) -> np.ndarray:
        """uint32 fingerprint per trajectory (determinism checks). Uses
        the ONE process-level jitted fingerprint (utils/hashing): the old
        per-call `jax.jit(jax.vmap(...))` retraced on every invocation."""
        return np.asarray(batch_fingerprints(state))

    def check_determinism(self, seed: int, max_steps: int,
                          net_override=None) -> bool:
        """Run the same seed twice and bitwise-compare final state — the
        enable_determinism_check analog (runtime/mod.rs:144-187), but over
        the full state rather than the RNG draw log. `net_override` (a
        NetConfig) is applied to both replays so the check validates the
        same fault model the test actually ran."""
        from ..harness.simtest import apply_net_override

        def once():
            s = apply_net_override(self.init_single(seed), net_override,
                                   cfg=self.cfg)
            s, _ = self.run(s, max_steps, collect_events=False)
            return s

        return bool((self.fingerprints(once())
                     == self.fingerprints(once())).all())
