"""The event engine: one jitted, vmappable `step(state) -> (state, record)`.

This is the TPU-native replacement for madsim's hot loop
(Executor::block_on, task.rs:110-124):

    reference (one seed, one thread)          this engine (B seeds, lockstep)
    ---------------------------------         --------------------------------
    pop random ready task (mpsc.rs:75)        masked categorical over earliest-
                                              deadline ties (ops/select.py)
    poll future, may send/sleep               dispatch handler; effects are
                                              fixed-shape emission records
    TimeRuntime::advance (time/mod.rs:41)     now = max(now, earliest deadline)
    message = timer cb (net/mod.rs:301)       message = event-table row
    Handle::kill/clog (runtime/mod.rs:214)    supervisor op = event-table row

Every branch executes for every trajectory each step (vmap turns `cond` into
`select`); masks decide what commits. That is the SIMD price of advancing
thousands of seeds in lockstep, and it is why handlers must be small tensor
programs.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import Phases
from ..ops import select as sel
from . import prng
from . import types as T
from .api import Ctx, Program
from . import state as ST
from .state import N_EV_KINDS, SimState


def _where_tree(mask, new, old):
    return jax.tree.map(lambda a, b: jnp.where(mask, a, b), new, old)


_I32_MAX = np.int32(2**31 - 1)


def _sat_add(a, d):
    """a + d for nonnegative int32 `d`, SATURATING at int32 max instead
    of wrapping — the profiler counter discipline (DESIGN §16): a pegged
    counter reads as pegged, never as a wrapped negative. The wrapped
    sum on the saturating branch is computed but never selected."""
    return jnp.where(a > _I32_MAX - d, _I32_MAX, a + d)


def _drift(t, sk):
    """(t * sk) >> 10 in exact int32-safe pieces — the clock-skew fold
    (DESIGN §18). `t` is a nonnegative tick count (now, or a timer
    delay), `sk` a per-1024 rate deviation bounded by ±SKEW_CAP (512),
    so (t>>10) ≤ 2^21 times 512 and (t&1023)*512 both stay far inside
    int32. Exact integer arithmetic — no float rounding to leak
    nondeterminism across backends — and identically 0 at sk == 0 (the
    bit-identical-when-disabled contract)."""
    return (t >> 10) * sk + (((t & 1023) * sk) >> 10)


# node-state slice/scatter via one-hot over the [N] axis: a traced node
# index would lower to a per-lane gather/scatter under vmap, which TPU
# executes at ~10ns per element (DESIGN.md §5) — for the log-shaped leaves
# that alone was several ms/step
def _slice_node(tree, node):
    return jax.tree.map(lambda a: sel.take_row(a, node), tree)


def _scatter_node(tree, node, new, mask):
    return jax.tree.map(
        lambda full, val: sel.put_row(full, node, val, mask), tree, new)


EMPTY_SEND = lambda P: dict(
    m=jnp.asarray(False), dst=jnp.asarray(0, jnp.int32),
    tag=jnp.asarray(0, jnp.int32), payload=jnp.zeros((P,), jnp.int32))
EMPTY_TIMER = lambda P: dict(
    m=jnp.asarray(False), delay=jnp.asarray(0, jnp.int32),
    tag=jnp.asarray(0, jnp.int32), payload=jnp.zeros((P,), jnp.int32))
EMPTY_CANCEL = lambda: dict(
    m=jnp.asarray(False), tag=jnp.asarray(0, jnp.int32))


def make_step(
    cfg: T.SimConfig,
    programs: Sequence[Program],
    node_prog: np.ndarray,
    state_spec: Any,
    invariant: Callable[[SimState], tuple[jax.Array, jax.Array]] | None = None,
    persist: Any = None,
    halt_when: Callable[[SimState], jax.Array] | None = None,
    extensions: Sequence = (),
) -> Callable[[SimState], tuple[SimState, dict[str, jax.Array]]]:
    """Build the per-trajectory step function.

    Args:
      cfg: static SimConfig.
      programs: node programs; node i runs programs[node_prog[i]].
      node_prog: int array [N] mapping node -> program index (static).
      state_spec: one node's default user-state pytree (no N axis).
      invariant: optional global safety check `f(state) -> (bad, code)`
        evaluated after every dispatch (e.g. Raft election safety). This is
        strictly stronger than the reference, where the supervisor can only
        observe at its own wakeups.
      persist: optional pytree of bools matching state_spec: True leaves are
        STABLE STORAGE — they survive kill/restart (the FsSim analog,
        fs.rs:66-122: files outlive the process; everything else is process
        memory and resets on boot). None = all volatile.
      halt_when: optional global success condition `f(state) -> bool`; when
        True the trajectory halts cleanly (the "supervisor future returned"
        analog of Runtime::block_on resolving).
    """
    node_prog = np.asarray(node_prog, np.int32)
    assert node_prog.shape == (cfg.n_nodes,)
    assert node_prog.min() >= 0 and node_prog.max() < len(programs)
    node_prog_j = jnp.asarray(node_prog)
    P = cfg.payload_words
    # emission-write lowering (types.py): values identical either way;
    # resolved once at trace time so the whole step compiles one form
    em_scatter = cfg.emission_write == "scatter" or (
        cfg.emission_write == "auto" and jax.default_backend() == "cpu")
    spec_default = jax.tree.map(lambda a: jnp.asarray(a), state_spec)
    if persist is None:
        persist_mask = jax.tree.map(lambda a: False, spec_default)
    else:
        persist_mask = persist
        assert (jax.tree.structure(persist_mask)
                == jax.tree.structure(spec_default)), \
            "persist mask must match state_spec structure"

    def phased_step(s: SimState, ph: Phases):
        ph.to("step.pick")
        live = ~s.halted  # frozen trajectories no-op via mask gating (the
        # vmap-friendly alternative to freezing with a whole-tree select)
        key, k_sched, k_super, k_handler, k_net = prng.split(s.key, 5)
        key = jnp.where(live, key, s.key)

        # ---- 1. pick next event: earliest eligible deadline, random tie-break
        occupied = s.t_kind != T.EV_FREE
        tnode = jnp.clip(s.t_node, 0, cfg.n_nodes - 1)
        # one-hot instead of alive[tnode]/paused[tnode]: a [C]-index gather
        # costs ~10ns/element on TPU (it was the 2nd-hottest op in the
        # profiled Raft step); the [C, N] compare+reduce is ~free
        parked_nodes = s.alive & s.paused
        parked = (sel.take1(parked_nodes, tnode)
                  & (s.t_kind != T.EV_SUPER))  # paused nodes park their events
        eligible = occupied & ~parked
        dmin, at_min, any_ev = sel.min_deadline(s.t_deadline, eligible,
                                                T.T_INF)
        idx, picked = sel.masked_choice(k_sched, at_min)
        u32 = jnp.uint32

        # ---- PCT-style priority perturbation (search/pct.py) -------------
        # When the per-lane `prio_nudge` operand is nonzero, the uniform
        # tie-break above is REPLACED by a deterministic priority argmax
        # over the earliest-deadline candidates: each slot's priority is a
        # hash of (nudge, slot identity), so one nudge value = one
        # tie-breaking policy, and sweeping nudges enumerates scheduler
        # decisions the way PCT sweeps priority assignments. Contract:
        #  - nudge == 0 is bit-identical to the hook's absence (the
        #    `where` keeps the masked_choice pick, and k_sched was already
        #    consumed either way, so the PRNG stream never shifts);
        #  - nudge is DYNAMIC state — mutating it never recompiles.
        prio = (s.t_tag.astype(u32) * u32(0x9E3779B1)
                ^ s.t_node.astype(u32) * u32(0x85EBCA77)
                ^ jnp.arange(cfg.event_capacity,
                             dtype=jnp.int32).astype(u32) * u32(0xC2B2AE3D)
                ^ s.prio_nudge.astype(u32) * u32(0x27D4EB2F))
        prio = (prio ^ (prio >> 15)) * u32(0x2C1B3C6D)
        # `| 1` floors candidate priorities above the masked-out 0, so the
        # argmax can only land on an at_min slot whenever one exists
        nudged = jnp.argmax(jnp.where(at_min, prio | u32(1),
                                      u32(0))).astype(jnp.int32)
        idx = jnp.where(s.prio_nudge != 0, nudged, idx)
        valid = picked & any_ev & live

        # ---- sim-profiler inputs (cfg.profile; obs/profiler.py) ----------
        # Captured here, written in one block after the emission phase:
        # queue depth at dispatch (pre-pop, so the dispatched row counts)
        # and the clock advance this dispatch buys. Pure reductions over
        # already-computed values — no randomness, no non-pf state. The
        # windowed telemetry plane (cfg.series_windows, r21) shares both
        # captures — same values, same transparency contract.
        if cfg.profile or cfg.series_windows > 0:
            occ_disp = occupied.sum(dtype=jnp.int32)

        ev_kind = jnp.where(valid, sel.take1(s.t_kind, idx), T.EV_FREE)
        ev_node_raw = sel.take1(s.t_node, idx)  # may be NODE_RANDOM (super)
        ev_node = jnp.clip(ev_node_raw, 0, cfg.n_nodes - 1)
        ev_src = sel.take1(s.t_src, idx)
        ev_tag = sel.take1(s.t_tag, idx)
        ev_payload = sel.take_row(s.t_payload, idx)

        # ---- causal lineage (cfg.trace_cap gate; obs/causal.py) ----------
        # The dispatched row's provenance: which dispatch enqueued it
        # (-1 = external) and the Lamport timestamp it carried. The
        # Lamport-rule clock advance happens below, after _apply_super
        # resolves NODE_RANDOM targets. Pure selects over the lineage
        # columns: no randomness consumed, no non-lineage state touched,
        # so trajectories are bit-identical with the recorder compiled
        # out (the r7 ring discipline).
        if cfg.trace_cap > 0:
            disp_idx = s.steps              # this dispatch's index (the
            # value tr_step records for it: steps increments by `valid`
            # below, so the ring's `s.steps - 1` equals this)
            prov = sel.take_row(s.ev_prov, idx)          # [parent, carried]
            ev_parent = jnp.where(valid, prov[0], jnp.asarray(-1,
                                                             jnp.int32))

        # schedule-coverage hash: fold the dispatched event's identity into
        # a running FNV-style mix. Pure VPU arithmetic, consumes no
        # randomness, so it cannot perturb replay; distinct interleavings
        # yield distinct hashes even when terminal states coincide.
        # two independent lanes (64 effective bits — see state.py): same
        # event fields, different multiplier assignment per lane, different
        # FNV-style folding primes
        ev_mix = jnp.stack([
            (ev_kind.astype(u32) * u32(0x9E3779B1)
             ^ ev_node.astype(u32) * u32(0x85EBCA77)
             ^ ev_src.astype(u32) * u32(0xC2B2AE3D)
             ^ ev_tag.astype(u32) * u32(0x27D4EB2F)),
            (ev_kind.astype(u32) * u32(0x27D4EB2F)
             ^ ev_node.astype(u32) * u32(0xC2B2AE3D)
             ^ ev_src.astype(u32) * u32(0x9E3779B1)
             ^ ev_tag.astype(u32) * u32(0x85EBCA77)),
        ])
        fold = jnp.asarray([16777619, 0x85EBCA6B], u32)  # both odd
        sched_hash = jnp.where(valid, (s.sched_hash ^ ev_mix) * fold,
                               s.sched_hash)

        # ---- duplicate-delivery fault (r19; DESIGN §20) ------------------
        # A dispatched MESSAGE may be delivered AGAIN: with the acting
        # node's per-million dup rate (OP_SET_DUP), the popped row is
        # re-armed at a fresh latency draw instead of being freed —
        # byte-identical payload/provenance/root, later deadline, and the
        # duplicate can duplicate again (the retransmit-storm regime).
        # Both draws ride keys FOLDED off k_sched, which the tie-break
        # already consumed, so the zero-rate default consumes nothing
        # from any other stream — trajectories stay bit-identical to r18
        # (the golden-digest contract, tests/test_connfault.py).
        dup_p = (sel.take1(s.dup_rate, ev_node).astype(jnp.float32)
                 * jnp.float32(1e-6))
        k_dupf = jax.random.fold_in(k_sched, 0x44555031)
        dup_fire = (valid & (ev_kind == T.EV_MSG)
                    & prng.bernoulli(k_dupf, dup_p))

        # pop the slot; clock never runs backward (resumed nodes' past-due
        # events fire "now", the park/unpark analog of task.rs:134-137)
        now = jnp.where(valid, jnp.maximum(s.now, dmin), s.now)
        if cfg.profile or cfg.series_windows > 0:
            now_delta = now - s.now          # >= 0; 0 when not valid

        # ---- SLO latency plane inputs (cfg.latency_hist; DESIGN §17) -----
        # Read BEFORE the pop/emission phase: the popped slot may be
        # reclaimed by this very dispatch's emissions, which overwrite
        # ev_root_t. Root rule: a row whose root is unset (-1 — scenario
        # rows, node boots, host injections: external causes) MINTS its
        # root at dispatch (`now`); everything it emits inherits it.
        # Sojourn = now − the dispatched row's deadline (all
        # earliest-deadline ties share dmin) — the queue-wait this row
        # paid to contention/parking. Pure selects, no randomness.
        if cfg.latency_hist > 0:
            root_raw = sel.take1(s.ev_root_t, idx)
            inherit = valid & (root_raw >= 0)
            # the root this dispatch MEASURES against (completion fold):
            # always the inherited one, so a (complete AND root) kind —
            # e.g. a reply delivery that also starts the next sequential
            # call — measures the finished request before restarting
            root_measured = jnp.where(inherit, root_raw, now)
            if cfg.root_kinds:
                # model-declared request STARTS re-mint the root even on
                # an inherited chain (the closed-loop client's new-
                # request timer; see types.py root_kinds)
                is_root_kind = functools.reduce(
                    jnp.logical_or,
                    [(ev_kind == k) & (ev_tag == t)
                     for k, t in cfg.root_kinds])
                inherit = inherit & ~is_root_kind
            # the root this dispatch's EMISSIONS inherit (post-mint)
            ev_root = jnp.where(inherit, root_raw, now)
            lat_sojourn = jnp.maximum(jnp.where(valid, now - dmin, 0), 0)
        if cfg.span_attr:
            # ---- span-attribution carried reads (r23; DESIGN §24) --------
            # Pre-pop like ev_root_t (the popped slot may be reclaimed by
            # this dispatch's own emissions). The carried vector follows
            # the root's inherit/measure split: the completion fold
            # measures the INHERITED chain (pre-re-mint), emissions carry
            # the post-mint one. A row minting its root starts a fresh
            # chain — nothing accumulated, no dominant segment. The
            # incoming edge's transit is recoverable at dispatch with no
            # per-emission storage: deadline − the emitter's stamped
            # `now` (SP_EMIT_T) = the latency + disk delay the emission
            # imposed (a dup re-arm moves the deadline, so the duplicate
            # delivery honestly measures to ITS deadline). Pure selects,
            # no randomness.
            inherit_sp = valid & (root_raw >= 0)
            span_raw = sel.take_row(s.ev_span, idx)        # [SPAN_WORDS]
            in_sq = jnp.where(inherit_sp, span_raw[ST.SP_QWAIT], 0)
            in_sn = jnp.where(inherit_sp, span_raw[ST.SP_NET], 0)
            in_sh = jnp.where(inherit_sp, span_raw[ST.SP_HOPS], 0)
            in_dnode = jnp.where(inherit_sp, span_raw[ST.SP_DOM_NODE], -1)
            in_dmag = jnp.where(inherit_sp, span_raw[ST.SP_DOM_MAG], 0)
            in_emit = jnp.where(inherit_sp, span_raw[ST.SP_EMIT_T], -1)
            net_seg = jnp.where(inherit_sp & (in_emit >= 0),
                                jnp.maximum(dmin - in_emit, 0), 0)
        # strict >: the scenario's HALT op sits at exactly time_limit, and
        # same-deadline ties may dispatch before it without being late
        time_over = now > s.tlimit
        # the duplicate's redelivery instant: a fresh network-latency draw
        # past the dispatch (never the same tick — the copy is a distinct
        # future delivery, like the reference's re-sent datagram)
        k_dupd = jax.random.fold_in(k_sched, 0x44555032)
        redeliver = now + jnp.maximum(
            prng.randint(k_dupd, s.lat_lo, s.lat_hi), 1)
        s = s.replace(
            key=key,
            now=now,
            sched_hash=sched_hash,
            # a duplicating dispatch keeps its row (kind/node/src/tag/
            # payload — and ev_prov/ev_root_t, which the pop never
            # touches) and only moves the deadline; everything else frees
            t_kind=sel.put_row(s.t_kind, idx,
                               jnp.asarray(T.EV_FREE, s.t_kind.dtype),
                               valid & ~dup_fire),
            t_deadline=sel.put_row(s.t_deadline, idx,
                                   jnp.where(dup_fire, redeliver,
                                             jnp.asarray(T.T_INF,
                                                         jnp.int32)),
                                   valid),
        )

        # ---- 2. supervisor op (Handle::kill/restart/... as events) ---------
        ph.to("step.supervisor")
        is_super = valid & (ev_kind == T.EV_SUPER)
        op = jnp.where(is_super, ev_tag, 0)
        ext_keys = prng.split(k_super, 1 + max(len(extensions), 1))
        s, init_node, reset_target, reset_mask = _apply_super(
            cfg, spec_default, persist_mask, s, op, ev_node_raw, ev_src,
            ev_payload, ext_keys[0])
        # extension custom ops + node-reset hooks (plugin.rs analog).
        # Extensions get the RESOLVED target so NODE_RANDOM scheduled ops
        # work for custom opcodes exactly like for built-ins.
        if extensions:
            new_ext = dict(s.ext)
            for i, e in enumerate(extensions):
                sub = new_ext[e.name]
                sub = e.on_op(cfg, sub, op, reset_target, ev_src, ev_payload,
                              ext_keys[1 + i])
                sub = e.reset_node(cfg, sub, reset_target, reset_mask)
                new_ext[e.name] = sub
            s = s.replace(ext=new_ext)

        # Lamport rule at the node the dispatch actually ACTED on:
        # clock = max(own, carried) + 1. For supervisor ops the scheduled
        # row may say NODE_RANDOM (ev_node clips it to 0), but a
        # kill/restart is an event AT the node _apply_super resolved —
        # so the clock advances there, not at the clipped placeholder.
        if cfg.trace_cap > 0:
            lam_node = jnp.where(is_super, reset_target, ev_node)
            ev_lamport = jnp.maximum(sel.take1(s.lamport, lam_node),
                                     prov[1]) + 1
            s = s.replace(lamport=sel.put_row(s.lamport, lam_node,
                                              ev_lamport, valid))

        # ---- span-attribution accumulation (cfg.span_attr; DESIGN §24) ---
        # Fold THIS dispatch's hop into the chain it inherited: its own
        # queue-wait (lat_sojourn) into the wait accumulator, the
        # incoming edge's transit (net_seg) into the transit accumulator,
        # and the hop's total cost against the dominant segment, owned by
        # the ACTING node (the pf_busy attribution rule). The measured
        # accumulators telescope: wait + transit of a completion equals
        # now − root EXACTLY (every hop contributes (deadline − emit) +
        # (dispatch − deadline) = dispatch − emit, and emit stamps chain
        # from the root's own `now`) — the invariant the host parent-walk
        # cross-check and the sa_tail fold both stand on. A dispatch
        # minting a fresh root measures zero (it IS the root).
        if cfg.span_attr:
            act_sp = jnp.where(is_super, reset_target, ev_node)
            meas_sq = jnp.where(inherit_sp, in_sq + lat_sojourn, 0)
            meas_sn = jnp.where(inherit_sp, in_sn + net_seg, 0)
            meas_sh = in_sh
            seg_sp = net_seg + lat_sojourn          # this hop's cost
            dom_up = inherit_sp & (seg_sp > in_dmag)
            meas_dnode = jnp.where(dom_up, act_sp, in_dnode)
            meas_dmag = jnp.where(dom_up, seg_sp, in_dmag)
            # what this dispatch's EMISSIONS carry (post-mint, like
            # ev_root): a re-minted root restarts the chain at zero; the
            # child's hop index is this dispatch's plus one; every
            # emission is stamped with this dispatch's `now`
            span_new = jnp.stack([
                jnp.where(inherit, meas_sq, 0),
                jnp.where(inherit, meas_sn, 0),
                jnp.where(inherit, meas_sh, 0) + 1,
                jnp.where(inherit, meas_dnode, -1),
                jnp.where(inherit, meas_dmag, 0),
                now])                               # [SPAN_WORDS]

        # ---- 3. protocol handler dispatch ---------------------------------
        ph.to("step.handler")
        node_ok = (sel.take1(s.alive, ev_node)
                   & ~sel.take1(s.paused, ev_node))
        is_msg = valid & (ev_kind == T.EV_MSG) & node_ok
        is_timer = valid & (ev_kind == T.EV_TIMER) & node_ok
        is_init = init_node >= 0
        dropped = valid & (ev_kind == T.EV_MSG) & ~node_ok
        h_node = jnp.where(is_init, jnp.clip(init_node, 0, cfg.n_nodes - 1),
                           ev_node)
        base_slice = _slice_node(s.node_state, h_node)

        # ---- gray-failure fault plane reads (r17; DESIGN §18) ------------
        # The acting node's clock-rate skew and disk-stall delay. Handlers
        # observe the node's LOCAL clock (now + drift) as ctx.now — a
        # skewed node timestamps its messages wrong, which is the whole
        # point; its timer delays stretch inversely below, and every
        # emission leaves disk_lat late. All exact-identity at the zero
        # defaults, no randomness consumed.
        sk_h = sel.take1(s.skew, h_node)
        h_now = s.now + _drift(s.now, sk_h)
        dlat_h = sel.take1(s.disk_lat, h_node)

        combos = []  # (mask, ctx) pairs; masks are mutually exclusive
        h_prog = sel.take1(node_prog_j, h_node)
        for p_idx, prog in enumerate(programs):
            pmask = h_prog == p_idx
            for hkind, run in (
                (is_init, lambda c: prog.init(c)),
                (is_msg, lambda c: prog.on_message(c, ev_src, ev_tag,
                                                   ev_payload)),
                (is_timer, lambda c: prog.on_timer(c, ev_tag, ev_payload)),
            ):
                ctx = Ctx(cfg, h_node, h_now, k_handler, base_slice,
                          hash_base=s.hash_base)
                run(ctx)
                combos.append((hkind & pmask, ctx))

        # merge combo results (masks are mutually exclusive by construction)
        any_h = functools.reduce(jnp.logical_or, [m for m, _ in combos])
        new_slice = base_slice
        crash = jnp.asarray(False)
        crash_code = jnp.asarray(0, jnp.int32)
        halt_req = jnp.asarray(False)
        n_sends = max((len(c._sends) for _, c in combos), default=0)
        n_timers = max((len(c._timers) for _, c in combos), default=0)
        n_cancels = max((len(c._cancels) for _, c in combos), default=0)
        sends = [EMPTY_SEND(P) for _ in range(n_sends)]
        timers = [EMPTY_TIMER(P) for _ in range(n_timers)]
        cancels = [EMPTY_CANCEL() for _ in range(n_cancels)]
        for m, ctx in combos:
            new_slice = _where_tree(m, ctx.state, new_slice)
            crash = crash | (m & ctx._crash)
            crash_code = jnp.where(m & ctx._crash, ctx._crash_code, crash_code)
            halt_req = halt_req | (m & ctx._halt)
            for j, e in enumerate(ctx._sends):
                e = dict(e, m=e["m"] & m)
                sends[j] = _where_tree(m, e, sends[j])
            for j, e in enumerate(ctx._timers):
                e = dict(e, m=e["m"] & m)
                timers[j] = _where_tree(m, e, timers[j])
            for j, e in enumerate(ctx._cancels):
                e = dict(e, m=e["m"] & m)
                cancels[j] = _where_tree(m, e, cancels[j])

        s = s.replace(
            node_state=_scatter_node(s.node_state, h_node, new_slice, any_h))

        # timer cancellation first: freed rows are reusable by this same
        # handler's emissions below (Sleep::reset / abort analog)
        for e in cancels:
            hit = (e["m"] & (s.t_kind == T.EV_TIMER)
                   & (s.t_node == h_node) & (s.t_tag == e["tag"]))
            s = s.replace(
                t_kind=jnp.where(hit, T.EV_FREE, s.t_kind),
                t_deadline=jnp.where(hit, T.T_INF, s.t_deadline))

        # ---- 4. materialize emissions into the event table ----------------
        ph.to("step.emit")
        # All emissions are staged per table column and written in one
        # pass per column (slots are distinct by construction): an
        # [E]-index scatter or a chain of E selects, never E separate
        # dynamic-index updates.
        E = n_sends + n_timers
        sent = delivered_drop = jnp.asarray(0, jnp.int32)
        overflow = jnp.asarray(False)
        high_water = jnp.asarray(0, jnp.int32)
        delay_acc = jnp.asarray(0, jnp.int32)   # cfg.profile: latency sum
        if E > 0:
            free = s.t_kind == T.EV_FREE
            occupied_now = (~free).sum(dtype=jnp.int32)
            slots, slot_ok = sel.first_k_free(free, E, scatter=em_scatter)
            # per-send: loss + latency keys; per-emission (send AND
            # timer): one micro-jitter key (net/mod.rs:151-156 — the
            # reference random-delays EVERY network op). STATICALLY
            # gated: the draws cost a key-split + randint per emission
            # on the dominant phase, so a build with op_jitter_max == 0
            # compiles none of it; when enabled, the BOUND (state.
            # jitter) stays dynamic and tunes without recompile.
            # Enabled/disabled are distinct replay domains (the config
            # hash covers the field); apply_net_override refuses to set
            # a nonzero bound on a jitterless build.
            use_jitter = cfg.net.op_jitter_max > 0
            net_keys = prng.split(
                k_net, 2 * max(n_sends, 1) + (E if use_jitter else 0))
            jit_keys = net_keys[2 * max(n_sends, 1):]

            def jitter_draw(key):
                return (prng.randint(key, 0, s.jitter) if use_jitter
                        else jnp.asarray(0, jnp.int32))
            em_write, em_deadline, em_kind = [], [], []
            em_node, em_tag, em_payload = [], [], []
            src_clog = sel.take1(s.clog_node, h_node)
            src_links = sel.take_row(s.clog_link, h_node)    # [N]

            for j, e in enumerate(sends):
                dst = jnp.clip(e["dst"], 0, cfg.n_nodes - 1)
                # network fault model: clog + loss + latency
                # (network.rs:222-229)
                clogged = (src_clog | sel.take1(s.clog_node, dst)
                           | sel.take1(src_links, dst))
                lost = prng.bernoulli(net_keys[2 * j], s.loss)
                latency = (prng.randint(net_keys[2 * j + 1], s.lat_lo,
                                        s.lat_hi)
                           + jitter_draw(jit_keys[j] if use_jitter
                                         else None))
                ok = e["m"] & ~clogged & ~lost
                sent = sent + e["m"].astype(jnp.int32)
                delivered_drop = delivered_drop + (e["m"] & ~ok).astype(
                    jnp.int32)
                if cfg.profile:
                    # latency actually imposed on delivered sends (the
                    # profiler's delay counter; dropped sends impose no
                    # delay — they impose a drop)
                    delay_acc = delay_acc + jnp.where(ok, latency, 0)
                write = ok & slot_ok[j]
                overflow = overflow | (ok & ~slot_ok[j])
                em_write.append(write)
                # slow-disk fault: a stalled node's replies leave late
                # (dlat_h == 0 on healthy nodes — exact identity)
                em_deadline.append(s.now + latency + dlat_h)
                em_kind.append(jnp.asarray(T.EV_MSG, jnp.int32))
                em_node.append(dst)
                em_tag.append(e["tag"])
                em_payload.append(e["payload"])

            for j, e in enumerate(timers):
                write = e["m"] & slot_ok[n_sends + j]
                overflow = overflow | (e["m"] & ~slot_ok[n_sends + j])
                em_write.append(write)
                # clock-skew stretch: a delay is measured on the node's
                # LOCAL clock, so a fast clock (skew > 0) fires it
                # earlier in global time — d_eff = d − (d·skew)>>10,
                # identity at skew 0; the slow-disk delay then pushes
                # the deadline back like every other emission
                d_eff = jnp.maximum(e["delay"]
                                    - _drift(e["delay"], sk_h), 0)
                em_deadline.append(s.now + d_eff + dlat_h
                                   + jitter_draw(
                                       jit_keys[n_sends + j]
                                       if use_jitter else None))
                em_kind.append(jnp.asarray(T.EV_TIMER, jnp.int32))
                em_node.append(h_node)
                em_tag.append(e["tag"])
                em_payload.append(e["payload"])

            w = jnp.stack(em_write)                      # [E] bool
            high_water = occupied_now + w.sum(dtype=jnp.int32)
            if em_scatter:
                # O(E) scatter per column: real slots are distinct by
                # construction; masked-off emissions target DISTINCT
                # out-of-range rows (C + j) so `unique_indices` holds and
                # mode="drop" discards them
                slots_eff = jnp.where(
                    w, slots,
                    cfg.event_capacity + jnp.arange(E, dtype=jnp.int32))

                def put(col, vals):
                    v = jnp.stack(vals)                  # [E] or [E, P]
                    return col.at[slots_eff].set(
                        v.astype(col.dtype), mode="drop",
                        unique_indices=True)
            else:
                # a chain of E selects per column, one per emission, instead
                # of an [E]-index scatter (serializes on TPU, ~10ns/element).
                # Real slots are distinct by construction and masked-off
                # emissions target row C, which matches no column, so the
                # chain writes each value exactly once, in any order. The
                # selects keep every column lanes-minor: the [E, C] one-hot
                # product this replaced lowered, for the s32 payload, to a
                # convolution that pinned the payload table C-minor and
                # made the pick's payload read a cross-lane reduce (DESIGN
                # §5). The [E, C] compares are what the scatter form above
                # avoids on CPU (width tax).
                slots_eff = jnp.where(
                    w, slots, jnp.asarray(cfg.event_capacity, jnp.int32))
                rows = jnp.arange(cfg.event_capacity, dtype=jnp.int32)
                # read only by the plane writes below; XLA drops it where
                # none is compiled
                written = (slots_eff[:, None] == rows).any(0)  # [C]

                def put(col, vals):
                    for j, v in enumerate(vals):
                        hit = slots_eff[j] == rows             # [C]
                        # cast, not promote: staged values are int32 but the
                        # column may be a narrow (table_dtype) dtype
                        col = jnp.where(hit if col.ndim == 1 else hit[:, None],
                                        jnp.asarray(v, col.dtype), col)
                    return col

            s = s.replace(
                t_deadline=put(s.t_deadline, em_deadline),
                t_kind=put(s.t_kind, em_kind),
                t_node=put(s.t_node, em_node),
                t_src=put(s.t_src, [h_node] * E),
                t_tag=put(s.t_tag, em_tag),
                t_payload=put(s.t_payload, em_payload),
            )
            if cfg.trace_cap > 0:
                # provenance of every emitted row: enqueued by THIS
                # dispatch, carrying the acting node's post-dispatch
                # clock (the Lamport message timestamp). Every emission
                # of a dispatch writes the SAME pair, so each lowering
                # reuses its own machinery — the scatter path's
                # drop-mode slots_eff, the one-hot path's existing [C]
                # `written` mask (never rebuilt; --mode causal_ab
                # bounds the whole lineage build's cost)
                prov_new = jnp.stack([disp_idx, ev_lamport])
                if em_scatter:
                    s = s.replace(ev_prov=s.ev_prov.at[slots_eff].set(
                        jnp.broadcast_to(prov_new, (E, 2)),
                        mode="drop", unique_indices=True))
                else:
                    s = s.replace(ev_prov=jnp.where(
                        written[:, None], prov_new[None, :], s.ev_prov))
            if cfg.latency_hist > 0:
                # root-birth-time inheritance: every row this dispatch
                # emits carries the dispatch's own root — the same
                # one-broadcast-per-dispatch shape as ev_prov above,
                # riding the identical slots_eff / written machinery
                if em_scatter:
                    s = s.replace(ev_root_t=s.ev_root_t.at[slots_eff].set(
                        jnp.broadcast_to(ev_root, (E,)),
                        mode="drop", unique_indices=True))
                else:
                    s = s.replace(ev_root_t=jnp.where(
                        written, ev_root, s.ev_root_t))
            if cfg.span_attr:
                # carried span vector: every row this dispatch emits
                # inherits the chain THROUGH this dispatch (its own
                # queue-wait and incoming transit folded in above) — one
                # [SPAN_WORDS] broadcast per dispatch riding the same
                # slots_eff / written machinery as ev_prov/ev_root_t
                if em_scatter:
                    s = s.replace(ev_span=s.ev_span.at[slots_eff].set(
                        jnp.broadcast_to(span_new, (E, ST.SPAN_WORDS)),
                        mode="drop", unique_indices=True))
                else:
                    s = s.replace(ev_span=jnp.where(
                        written[:, None], span_new[None, :], s.ev_span))

        # oops/steps are correctness-bearing and always tracked; the stat
        # counters honor cfg.collect_stats (Stat is optional in the
        # reference too — NetSim::stat is a query, not a requirement)
        if cfg.collect_stats:
            s = s.replace(
                msg_sent=s.msg_sent + sent,
                msg_delivered=s.msg_delivered + is_msg.astype(jnp.int32),
                msg_dropped=s.msg_dropped + delivered_drop
                + dropped.astype(jnp.int32),
                ev_peak=jnp.maximum(s.ev_peak, high_water),
            )
        s = s.replace(
            oops=s.oops | jnp.where(overflow, T.OOPS_EVENT_OVERFLOW, 0)
            | jnp.where(s.now > T.T_INF - 64 * T.TICKS_PER_SEC,
                        T.OOPS_TIME_OVERFLOW, 0),
            steps=s.steps + valid.astype(jnp.int32),
        )

        # ---- sim-profiler counter plane (cfg.profile; DESIGN §16) --------
        # One block of saturating one-hot increments over values the step
        # already computed: per-(node, kind) dispatch counts and per-node
        # busy time at the ACTING node (for supervisor ops the node
        # _apply_super resolved — the Lamport-rule node), effective
        # kill/boot counts at the reset target, occupancy high-water,
        # drop and delay totals. No randomness consumed, no non-pf state
        # touched: trajectories are bit-identical across the knob, and
        # the pf_* columns ride TRACE_FIELDS out of fingerprints.
        if cfg.profile:
            ph.to("step.profile")
            rec_p = valid & s.pf_on
            act_node = jnp.where(is_super, reset_target, ev_node)
            ohP = sel.row_onehot(cfg.n_nodes, act_node)      # [N]
            k_oh = (jnp.arange(N_EV_KINDS, dtype=jnp.int32)
                    == ev_kind)                              # [K]
            was_kill = reset_mask & ((op == T.OP_KILL)
                                     | (op == T.OP_RESTART))
            was_boot = reset_mask & ((op == T.OP_INIT)
                                     | (op == T.OP_RESTART))
            s = s.replace(
                pf_dispatch=_sat_add(
                    s.pf_dispatch,
                    (ohP[:, None] & k_oh[None, :] & rec_p)
                    .astype(jnp.int32)),
                pf_busy=_sat_add(s.pf_busy,
                                 jnp.where(ohP & rec_p, now_delta, 0)),
                pf_kill=_sat_add(s.pf_kill,
                                 (ohP & was_kill & rec_p)
                                 .astype(jnp.int32)),
                pf_restart=_sat_add(s.pf_restart,
                                    (ohP & was_boot & rec_p)
                                    .astype(jnp.int32)),
                pf_qmax=jnp.where(
                    rec_p,
                    jnp.maximum(s.pf_qmax,
                                jnp.maximum(occ_disp, high_water)),
                    s.pf_qmax),
                pf_drop=_sat_add(s.pf_drop, jnp.where(
                    rec_p, delivered_drop + dropped.astype(jnp.int32), 0)),
                pf_delay=_sat_add(s.pf_delay,
                                  jnp.where(rec_p, delay_acc, 0)),
            )

        # ---- SLO latency plane (cfg.latency_hist; DESIGN §17) ------------
        # Fold this dispatch's queue-wait — and, on completion kinds, its
        # end-to-end request latency — into the per-node log2 histograms.
        # Bucketing is EXACT integer arithmetic: bucket(d) counts the
        # thresholds 2^j <= d, so d in [2^(j-1), 2^j) lands in bucket j
        # and d == 0 in bucket 0 (a float log2 would misbucket near
        # power-of-two boundaries). One [N]x[B] one-hot saturating write
        # per histogram; no randomness, no non-latency state — the same
        # transparency contract as the pf_* counters, and the fold runs
        # BEFORE the end-condition checks so an `invariant=` (e.g.
        # harness.slo_invariant) sees this dispatch's completion.
        lat_e2e = None
        if cfg.latency_hist > 0:
            ph.to("step.latency")
            LB = cfg.latency_hist
            rec_l = valid & s.lh_on
            thr = jnp.asarray([1 << j for j in range(LB - 1)], jnp.int32)

            def bucket_oh(d):     # [LB] one-hot of d's log2 bucket
                b = (d >= thr).sum(dtype=jnp.int32)
                return jnp.arange(LB, dtype=jnp.int32) == b

            # sojourn at the ACTING node (supervisor ops: the resolved
            # target — same attribution rule as pf_busy)
            act_l = jnp.where(is_super, reset_target, ev_node)
            oh_act = sel.row_onehot(cfg.n_nodes, act_l)       # [N]
            s = s.replace(lh_sojourn=_sat_add(
                s.lh_sojourn,
                (oh_act[:, None] & bucket_oh(lat_sojourn)[None, :]
                 & rec_l).astype(jnp.int32)))
            if cfg.complete_kinds:
                is_complete = valid & functools.reduce(
                    jnp.logical_or,
                    [(ev_kind == k) & (ev_tag == t)
                     for k, t in cfg.complete_kinds])
                lat_e2e = jnp.maximum(now - root_measured, 0)
                lat_e2e_raw = lat_e2e    # pre-sentinel value: the series
                # plane below folds the completion's latency per WINDOW
                oh_cpl = sel.row_onehot(cfg.n_nodes, ev_node)  # [N]
                done_l = is_complete & s.lh_on
                miss = (done_l & (s.slo_target > 0)
                        & (lat_e2e > s.slo_target))
                s = s.replace(
                    lh_e2e=_sat_add(
                        s.lh_e2e,
                        (oh_cpl[:, None] & bucket_oh(lat_e2e)[None, :]
                         & done_l).astype(jnp.int32)),
                    lh_slo_miss=_sat_add(
                        s.lh_slo_miss,
                        (oh_cpl & miss).astype(jnp.int32)))
                # the ring's per-dispatch latency value (tr_lat):
                # completions record e2e, everything else -1
                lat_e2e = jnp.where(is_complete, lat_e2e,
                                    jnp.asarray(-1, jnp.int32))

        # ---- span-attribution fold (cfg.span_attr; DESIGN §24) -----------
        # Only TAIL completions attribute (e2e over the dynamic
        # slo_target — the lh_slo_miss gate, on this plane's own lane
        # mask): the healthy majority would drown the tail's signal.
        # One [N, SA_COMPONENTS] saturating masked add at the completion
        # node plus one [N] one-hot increment at the dominant segment's
        # owner. No randomness, no non-span state — the pf_*/lh_*
        # transparency contract.
        if cfg.span_attr:
            ph.to("step.span")
            tail_sp = (is_complete & s.sp_on & (s.slo_target > 0)
                       & (lat_e2e_raw > s.slo_target))
            comp_vals = jnp.stack([jnp.asarray(1, jnp.int32), meas_sq,
                                   meas_sn, meas_sh])  # [SA_COMPONENTS]
            oh_dom = (sel.row_onehot(
                cfg.n_nodes, jnp.clip(meas_dnode, 0, cfg.n_nodes - 1))
                & tail_sp & (meas_dnode >= 0))
            s = s.replace(
                sa_tail=_sat_add(
                    s.sa_tail,
                    jnp.where(oh_cpl[:, None] & tail_sp,
                              comp_vals[None, :], 0)),
                sa_bottleneck=_sat_add(s.sa_bottleneck,
                                       oh_dom.astype(jnp.int32)))

        # ---- prefix-coverage sketch (cfg.sketch_slots; DESIGN §12) -------
        # Fold the running sched_hash into slot j = steps/every - 1 at
        # every sketch_every-th dispatch: slot j then witnesses the whole
        # (j+1)*every-step prefix, so the first slot where two lanes'
        # sketches differ bounds their first schedule divergence — depth
        # telemetry that never leaves the device mid-run. One [slots]
        # one-hot select per step; `every` is a dynamic operand
        # (s.sketch_every), only the slot COUNT shapes the program.
        if cfg.sketch_slots > 0:
            ph.to("step.sketch")
            period = jnp.maximum(s.sketch_every, 1)
            ck = s.steps // period
            at_ck = (valid & (s.steps == ck * period) & (ck >= 1)
                     & (ck <= cfg.sketch_slots))
            oh_ck = sel.row_onehot(
                cfg.sketch_slots,
                jnp.clip(ck - 1, 0, cfg.sketch_slots - 1)) & at_ck
            s = s.replace(cov_sketch=jnp.where(
                oh_ck, s.sched_hash[0] ^ s.sched_hash[1], s.cov_sketch))

        # ---- windowed telemetry plane (cfg.series_windows; DESIGN §22) ---
        # Fold this dispatch into its sim-time WINDOW: the dispatch's
        # post-advance `now` picks window min(now // window_len, W-1) —
        # a dispatch exactly ON a boundary opens the next window, events
        # past W*window_len clamp into the last one. window_len is a
        # DYNAMIC operand (retune without recompile, the trace_cap/
        # sketch_every discipline); only the window COUNT shapes the
        # program. One [W] one-hot (and one [W, N] outer product for the
        # per-node series) of saturating writes over values the step
        # already computed — no randomness, no non-series state, so
        # trajectories are bit-identical across the knob and the sr_*
        # columns ride TRACE_FIELDS out of fingerprints. Runs BEFORE the
        # end-condition checks so an `invariant=` (e.g.
        # harness.recovery_invariant) sees this dispatch's window.
        if cfg.series_windows > 0:
            ph.to("step.series")
            SW = cfg.series_windows
            rec_s = valid & s.sr_on
            w_idx = jnp.minimum(now // jnp.maximum(s.window_len, 1),
                                SW - 1)
            oh_w = sel.row_onehot(SW, w_idx)                  # [W]
            # acting-node attribution: the _apply_super-resolved target
            # for supervisor ops (the pf_dispatch/pf_busy rule)
            act_s = jnp.where(is_super, reset_target, ev_node)
            oh_ns = sel.row_onehot(cfg.n_nodes, act_s)        # [N]
            cell = oh_w[:, None] & oh_ns[None, :] & rec_s     # [W, N]
            # fault-marker word: which fault classes landed in this
            # window (SRF_* bits, types.py). Kill/boot bits require the
            # op to have been EFFECTIVE (reset_mask); matrix/knob ops
            # mark on dispatch. OR-accumulated — bits, not counts.
            eff_kill = reset_mask & ((op == T.OP_KILL)
                                     | (op == T.OP_RESTART))
            eff_boot = reset_mask & ((op == T.OP_INIT)
                                     | (op == T.OP_RESTART))

            def opin(*ops):
                return is_super & functools.reduce(
                    jnp.logical_or, [op == o for o in ops])

            f_bits = (
                jnp.where(eff_kill, T.SRF_KILL, 0)
                | jnp.where(eff_boot, T.SRF_BOOT, 0)
                | jnp.where(opin(T.OP_CLOG_NODE, T.OP_CLOG_LINK,
                                 T.OP_PARTITION, T.OP_PARTITION_ONEWAY),
                            T.SRF_PARTITION, 0)
                | jnp.where(opin(T.OP_HEAL, T.OP_UNCLOG_NODE,
                                 T.OP_UNCLOG_LINK), T.SRF_HEAL, 0)
                | jnp.where(opin(T.OP_SET_LOSS, T.OP_SET_LATENCY),
                            T.SRF_NET, 0)
                | jnp.where(opin(T.OP_SET_SKEW, T.OP_SET_DISK),
                            T.SRF_GRAY, 0)
                | jnp.where(opin(T.OP_RESET_PEER, T.OP_SET_DUP),
                            T.SRF_CONN, 0))
            s = s.replace(
                sr_dispatch=_sat_add(s.sr_dispatch,
                                     cell.astype(jnp.int32)),
                sr_busy=_sat_add(s.sr_busy,
                                 jnp.where(cell, now_delta, 0)),
                # per-window occupancy high-water: max, never saturates
                sr_qhw=jnp.where(
                    oh_w & rec_s,
                    jnp.maximum(s.sr_qhw,
                                jnp.maximum(occ_disp, high_water)),
                    s.sr_qhw),
                sr_drop=_sat_add(s.sr_drop, jnp.where(
                    oh_w & rec_s,
                    delivered_drop + dropped.astype(jnp.int32), 0)),
                sr_dup=_sat_add(s.sr_dup,
                                (oh_w & rec_s & dup_fire)
                                .astype(jnp.int32)),
                sr_fault=s.sr_fault | jnp.where(oh_w & rec_s, f_bits, 0),
            )
            if cfg.latency_hist > 0 and cfg.complete_kinds:
                # per-window completion/miss counts + e2e histogram —
                # the same fold as the lh_* plane, bucketed by WINDOW
                # instead of node, gated on THIS plane's lane mask
                done_s = is_complete & s.sr_on
                miss_s = (done_s & (s.slo_target > 0)
                          & (lat_e2e_raw > s.slo_target))
                s = s.replace(
                    sr_complete=_sat_add(s.sr_complete,
                                         (oh_w & done_s)
                                         .astype(jnp.int32)),
                    sr_slo_miss=_sat_add(s.sr_slo_miss,
                                         (oh_w & miss_s)
                                         .astype(jnp.int32)),
                    sr_lat=_sat_add(
                        s.sr_lat,
                        (oh_w[:, None] & bucket_oh(lat_e2e_raw)[None, :]
                         & done_s).astype(jnp.int32)))

        # ---- 5. end conditions -------------------------------------------
        ph.to("step.check")
        # deadlock: nothing can ever run again (madsim task.rs:116 panic)
        crash = crash | ((~any_ev | time_over) & live)
        crash_code = jnp.where(
            ~any_ev & live, T.CRASH_DEADLOCK,
            jnp.where(time_over & live & (crash_code == 0),
                      T.CRASH_TIME_LIMIT, crash_code))
        halted_now = halt_req | (is_super & (op == T.OP_HALT))
        if halt_when is not None:
            # global success condition (the root-future-ready analog): e.g.
            # "all clients acked" — has whole-cluster visibility
            halted_now = halted_now | (halt_when(s) & live)

        if invariant is not None:
            bad, code = invariant(s)
            bad = bad & live
            first = bad & ~crash
            crash_code = jnp.where(first, code, crash_code)
            crash = crash | bad

        s = s.replace(
            crashed=s.crashed | crash,
            crash_code=jnp.where(crash & (s.crash_code == 0), crash_code,
                                 s.crash_code),
            crash_node=jnp.where(crash & (s.crash_node < 0), h_node,
                                 s.crash_node),
            halted=s.halted | halted_now | crash,
        )

        # records always int32: table_dtype is an internal bandwidth
        # lever and must not leak into the trace schema
        record = dict(
            now=s.now, kind=ev_kind.astype(jnp.int32),
            node=ev_node.astype(jnp.int32), src=ev_src.astype(jnp.int32),
            tag=ev_tag.astype(jnp.int32), payload=ev_payload,
            fired=valid,
        )

        # ---- flight-recorder ring (cfg.trace_cap; obs/rings.py) ----------
        # The same record, written into a per-lane ring that lives in
        # SimState — so it survives `lax.while_loop` and the fused runner
        # is no longer blind. Only FIRED events of SAMPLED lanes write
        # (the ring never holds frozen-lane records, unlike the
        # collect_events stream, whose consumers must filter on `fired`).
        # One one-hot row write per column, no randomness consumed: all
        # non-trace state stays bit-identical across trace_cap settings.
        if cfg.trace_cap > 0:
            ph.to("step.ring")
            rec_w = record["fired"] & s.trace_on
            # DYNAMIC capacity (s.trace_cap), bucket-sized columns: the
            # compiled program depends only on cfg.trace_cap_bucket, so
            # sweeping trace_cap within a bucket shares one executable;
            # slots stay < trace_cap, so rows past it are never written
            # and ring contents are bit-identical to an unbucketed build
            slot = jnp.mod(s.trace_pos, s.trace_cap)
            # one shared one-hot row mask for all six columns (the
            # columns are [bucket] vectors, so put_row's per-call reshape
            # is unnecessary); the recorder's whole per-step cost is six
            # [bucket] selects + one masked increment
            oh = sel.row_onehot(cfg.trace_cap_bucket, slot) & rec_w

            def ringput(col, v):
                return jnp.where(oh, v.astype(col.dtype), col)

            # queue-depth ring column: only when the profiler is also
            # compiled in (its counter-track source; zero-size otherwise)
            extra_cols = (dict(tr_qlen=ringput(s.tr_qlen, occ_disp))
                          if cfg.profile else {})
            if cfg.latency_hist > 0:
                # e2e-latency ring column (rolling-p99 track source):
                # completions record their latency, everything else -1
                extra_cols["tr_lat"] = ringput(
                    s.tr_lat,
                    lat_e2e if lat_e2e is not None
                    else jnp.asarray(-1, jnp.int32))
            if cfg.span_attr:
                # queue-wait ring column: the dispatch's own sojourn, so
                # a host parent-walk splits every hop into wait vs
                # transit (obs/spans.py explain_latency)
                extra_cols["tr_qw"] = ringput(s.tr_qw, lat_sojourn)
            s = s.replace(
                **extra_cols,
                tr_now=ringput(s.tr_now, record["now"]),
                tr_step=ringput(s.tr_step, s.steps - 1),
                tr_kind=ringput(s.tr_kind, record["kind"]),
                tr_node=ringput(s.tr_node, record["node"]),
                tr_src=ringput(s.tr_src, record["src"]),
                tr_tag=ringput(s.tr_tag, record["tag"]),
                # the lineage pair: each recorded event carries its
                # happens-before parent and post-dispatch Lamport clock,
                # so causal chains survive ring wrap (obs/causal.py)
                tr_parent=ringput(s.tr_parent, ev_parent),
                tr_lamport=ringput(s.tr_lamport, ev_lamport),
                trace_pos=s.trace_pos + rec_w.astype(jnp.int32),
            )
        return s, record

    def live_step(s: SimState):
        # each phase under its own jax.named_scope (obs/scopes.py): op
        # metadata only, so a device profile sums by phase and the
        # program stays the same; extension hooks run after the phases
        with Phases() as ph:
            s, record = phased_step(s, ph)
        if extensions:
            new_ext = dict(s.ext)
            for e in extensions:
                new_ext[e.name] = e.on_event(cfg, new_ext[e.name], s, record)
            s = s.replace(ext=new_ext)
        return s, record

    return live_step


def _apply_super(cfg, spec_default, persist_mask, s: SimState, op, node, src,
                 payload, key):
    """Apply one supervisor opcode as masked state edits.

    Returns (state, init_node) where init_node >= 0 requests the program
    `init` handler to run on that node this step (OP_INIT / OP_RESTART —
    the NodeBuilder::init respawn of runtime/mod.rs:287-295).
    """
    k_t, k_tear = prng.split(key)
    N = cfg.n_nodes

    # resolve NODE_RANDOM targets (fuzzing): each op draws from the pool of
    # nodes it can meaningfully act on — kill/pause/clog a random alive node,
    # restart a random dead one, resume a random paused one, unclog a random
    # clogged one. A nonzero payload restricts candidates to a bitmask
    # (31 nodes/word, same packing as OP_PARTITION) so e.g. chaos kills
    # target servers but not client/harness nodes, for any
    # N <= 31 * payload_words. Only the words node ids can actually pack
    # into count as "a pool was given" — the r17 value-carrying ops
    # (OP_SET_SKEW / OP_SET_DISK) put their values in the TAIL payload
    # words, past the pool segment, so value and pool coexist.
    want_alive = (op == T.OP_KILL) | (op == T.OP_PAUSE) | (op == T.OP_CLOG_NODE)
    pool = jnp.where(want_alive, s.alive,
                     jnp.where(op == T.OP_RESTART, ~s.alive,
                               jnp.where(op == T.OP_RESUME, s.paused,
                                         jnp.where(op == T.OP_UNCLOG_NODE,
                                                   s.clog_node,
                                                   jnp.ones((N,), bool)))))
    ids = jnp.arange(N, dtype=jnp.int32)
    pool_words = sel.take1(payload, ids // 31)    # one-hot: vector-index
    in_pool = ((pool_words >> (ids % 31)) & 1) == 1     # gathers serialize
    n_pool_words = min(cfg.payload_words, (N + 30) // 31)   # static
    pool = pool & jnp.where((payload[:n_pool_words] != 0).any(), in_pool,
                            jnp.ones((N,), bool))
    rnd, rnd_ok = sel.masked_choice(k_t, pool)
    is_random = node == T.NODE_RANDOM
    target = jnp.clip(jnp.where(is_random, rnd, node), 0, N - 1)
    effective = ~is_random | rnd_ok  # no eligible random target -> no-op
    src_c = jnp.clip(src, 0, N - 1)

    def when(cond):
        return cond & effective

    kill = when((op == T.OP_KILL) | (op == T.OP_RESTART))
    boot = when((op == T.OP_INIT) | (op == T.OP_RESTART))

    # KILL: drop the node's queued events — its tasks die (task.rs:170-182)
    # and its sockets close so undelivered messages vanish (network.rs:113-118)
    clear = kill & (s.t_node == target) & (
        (s.t_kind == T.EV_MSG) | (s.t_kind == T.EV_TIMER))
    t_kind = jnp.where(clear, T.EV_FREE, s.t_kind)
    t_deadline = jnp.where(clear, T.T_INF, s.t_deadline)

    # all per-node edits below are one-hot selects, not .at[target] scatters
    # (a traced scatter index serializes per lane on TPU — DESIGN.md §5)
    ohT = sel.row_onehot(N, target)                         # [N]
    alive = jnp.where(ohT & kill & ~boot, False,
                      jnp.where(ohT & boot, True, s.alive))
    paused = jnp.where(ohT & (kill | boot | when(op == T.OP_RESUME)), False,
                       jnp.where(ohT & when(op == T.OP_PAUSE), True,
                                 s.paused))

    # torn-write kill flush (r17, DESIGN §18): when the target runs in
    # torn mode, a KILL first flushes a RANDOM PREFIX of each fs file's
    # unsynced tail [dlen, mlen) into the durable view — the disk got
    # part of the final record before power died, instead of clean
    # old-or-new. Synced words (< dlen) are never touched, so a synced
    # record can never tear; a cut can land mid-record, which is the
    # point. Compiled only for fs-layer state schemas (the fs.py leaf
    # quartet); the draw uses a key split this function already made,
    # so enabling torn mode never shifts anyone else's PRNG stream.
    ns = s.node_state
    if isinstance(ns, dict) and {"fs_mem", "fs_mlen", "fs_disk",
                                 "fs_dlen"} <= set(ns.keys()):
        # only a LIVE node's power-fail tears: the kill half of an
        # OP_RESTART aimed at an already-dead node is a no-op process-
        # wise, and re-drawing a tear over the corpse's stale unsynced
        # tail would flush words the original power-fail never did
        tearing = kill & sel.take1(s.torn & s.alive, target)
        mem_t = sel.take_row(ns["fs_mem"], target)      # [F, S]
        mlen_t = sel.take_row(ns["fs_mlen"], target)    # [F]
        disk_t = sel.take_row(ns["fs_disk"], target)
        dlen_t = sel.take_row(ns["fs_dlen"], target)
        F, S = mem_t.shape
        gap = jnp.maximum(mlen_t - dlen_t, 0)
        draw = jax.random.randint(k_tear, (F,), 0, jnp.int32(2**30),
                                  dtype=jnp.int32)
        cut = dlen_t + draw % (gap + 1)                 # in [dlen, mlen]
        ws = jnp.arange(S, dtype=jnp.int32)
        flushed = ((ws[None, :] >= dlen_t[:, None])
                   & (ws[None, :] < cut[:, None]))
        ns = dict(
            ns,
            fs_disk=sel.put_row(ns["fs_disk"], target,
                                jnp.where(flushed, mem_t, disk_t),
                                tearing),
            fs_dlen=sel.put_row(ns["fs_dlen"], target,
                                jnp.maximum(dlen_t, cut), tearing))

    # connection-fault tear (r19, DESIGN §20): OP_RESET_PEER kills every
    # live conn/stream touching the target, on BOTH sides — the
    # NetSim::reset_node parity a kill deliberately lacks (the survivor
    # keeps half-open state; only a reset tears streams down). For any
    # state schema carrying the conn/stream leaf quartets: cn_state rows
    # AND columns of the target drop to CLOSED, the stream rings/counters
    # touching it wipe, and both sides' incarnation epochs bump — the RST
    # notification, applied atomically to both endpoints, so segments and
    # RSTs still in flight from the torn incarnation are STALE to the
    # successor connection (net/stream.py drop-on-less rule). Masked
    # edits only; inert for schemas without the leaves, and a no-op mask
    # costs the same selects the other per-node ops already pay.
    rp = when(op == T.OP_RESET_PEER)
    if isinstance(ns, dict):
        touched = (ohT[:, None] | ohT[None, :]) & rp        # [N, N]

        def _cut(col, zero):
            m = touched.reshape(touched.shape
                                + (1,) * (col.ndim - 2))
            return jnp.where(m, zero, col)

        if {"cn_state", "cn_epoch"} <= set(ns.keys()):
            ns = dict(ns,
                      cn_state=_cut(ns["cn_state"], 0),
                      cn_epoch=ns["cn_epoch"]
                      + touched.astype(jnp.int32))
        if {"sx_seq", "sx_base", "sx_val", "sr_next", "sr_val",
                "sr_have", "st_epoch"} <= set(ns.keys()):
            ns = dict(ns,
                      st_epoch=ns["st_epoch"] + touched.astype(jnp.int32),
                      sx_seq=_cut(ns["sx_seq"], 0),
                      sx_base=_cut(ns["sx_base"], 0),
                      sr_next=_cut(ns["sr_next"], 0),
                      sx_val=_cut(ns["sx_val"], 0),
                      sr_val=_cut(ns["sr_val"], 0),
                      sr_have=_cut(ns["sr_have"], False))

    # node boot/restart resets protocol state to the spec default — process
    # memory does not survive a crash. Leaves marked persistent are stable
    # storage (the FsSim analog) and DO survive.
    node_state = jax.tree.map(
        lambda full, dflt, keep: full if keep
        else sel.put_row(full, target, dflt, boot),
        ns, spec_default, persist_mask)

    clog_node = jnp.where(ohT & when(op == T.OP_CLOG_NODE), True,
                          jnp.where(ohT & when(op == T.OP_UNCLOG_NODE),
                                    False, s.clog_node))
    oh_link = sel.row_onehot(N, src_c)[:, None] & ohT[None, :]
    clog_link = jnp.where(oh_link & when(op == T.OP_CLOG_LINK), True,
                          jnp.where(oh_link & when(op == T.OP_UNCLOG_LINK),
                                    False, s.clog_link))

    # whole-matrix ops: OP_PARTITION replaces the link matrix with the cut
    # A <-> not-A (payload packs membership 31 nodes/word); OP_HEAL clears
    # everything. OP_PARTITION_ONEWAY (r17) ORs a DIRECTIONAL cut into the
    # matrix instead — src bit 0 picks the direction (0: A's sends to
    # not-A vanish while A still hears; 1: the reverse) — so one-way cuts
    # compose with each other and with clog_link, and only HEAL clears
    # them (madsim disconnect2 parity).
    words = sel.take1(payload, ids // 31)     # one-hot: vector-index
    in_a = ((words >> (ids % 31)) & 1).astype(bool)       # gathers serialize
    cut = in_a[:, None] != in_a[None, :]
    clog_link = jnp.where(when(op == T.OP_PARTITION), cut, clog_link)
    a_out = in_a[:, None] & ~in_a[None, :]          # [src, dst]: A -> not-A
    cut_dir = jnp.where((src & 1) == 1, a_out.T, a_out)
    clog_link = jnp.where(when(op == T.OP_PARTITION_ONEWAY),
                          clog_link | cut_dir, clog_link)
    clog_link = jnp.where(when(op == T.OP_HEAL),
                          jnp.zeros_like(clog_link), clog_link)
    clog_node = jnp.where(when(op == T.OP_HEAL),
                          jnp.zeros_like(clog_node), clog_node)

    loss = jnp.where(when(op == T.OP_SET_LOSS),
                     payload[0].astype(jnp.float32) / 1e6, s.loss)
    lat_lo = jnp.where(when(op == T.OP_SET_LATENCY), payload[0], s.lat_lo)
    lat_hi = jnp.where(when(op == T.OP_SET_LATENCY),
                       jnp.maximum(payload[1], payload[0]), s.lat_hi)

    # gray-failure per-node knobs (r17): values ride the TAIL payload
    # words (the leading words may hold a NODE_RANDOM pool), bounded at
    # application — a scenario/mutant can explore, never corrupt
    P = cfg.payload_words
    ohSk = ohT & when(op == T.OP_SET_SKEW)
    skew = jnp.where(ohSk, jnp.clip(payload[P - 1], -T.SKEW_CAP,
                                    T.SKEW_CAP), s.skew)
    ohDk = ohT & when(op == T.OP_SET_DISK)
    disk_lat = jnp.where(ohDk, jnp.clip(payload[P - 1], 0, T.DISK_LAT_CAP),
                         s.disk_lat)
    torn = jnp.where(ohDk, payload[P - 2] != 0, s.torn)
    ohDup = ohT & when(op == T.OP_SET_DUP)
    dup_rate = jnp.where(ohDup,
                         jnp.clip(payload[P - 1], 0, T.DUP_RATE_CAP),
                         s.dup_rate)

    init_node = jnp.where(boot, target, jnp.asarray(-1, jnp.int32))
    s = s.replace(t_kind=t_kind, t_deadline=t_deadline, alive=alive,
                  paused=paused, node_state=node_state, clog_node=clog_node,
                  clog_link=clog_link, loss=loss, lat_lo=lat_lo,
                  lat_hi=lat_hi, skew=skew, disk_lat=disk_lat, torn=torn,
                  dup_rate=dup_rate)
    return s, init_node, target, (kill | boot)
