"""Real-world runtime: the production twin of the simulator.

madsim's signature property is compile-time world-switching — the same
application source runs inside the simulator or against real tokio/TCP with
zero changes (madsim/src/lib.rs:15-24 selects `mod sim` vs `mod std`;
std/net/tcp.rs is the real Endpoint). The analog here: the SAME `Program`
subclasses (state machines over jnp ops, which execute eagerly on concrete
arrays) run either vectorized under jit (runtime/runtime.py) or against real
wall-clock time and real sockets via this asyncio runtime. Protocol code
is written once; the world is chosen at Runtime-construction time.

Transports are pluggable (real/transport.py — the std/net/mod.rs seam):
"udp", "tcp", and the in-memory "local" backend ship; new ones register
without editing this file.

Wire format: little-endian int32s [tag, src_node, payload[P]] — the
tag-matched datagram model of the reference's real TCP backend
(std/net/tcp.rs frames [len][tag][payload]), minus streams (UDP fits the
sim's message semantics; loss/reorder are real-network properties here).

Durability: with `data_dir` set, persist-marked state leaves are spilled
to disk after every event (write-fsync-rename, so a kill -9 of the whole
OS process can never observe a torn file) and reloaded on node start —
the std/fs.rs twin (fs.rs:1-60 backs sim files with real ones). Because
fs.py keeps page-cache and disk-view as SEPARATE leaves and only sync_all
copies cache->disk, spilling the persist leaves (the disk views) after
each event makes on-disk state exactly "stable storage as of the last
sync": unsynced writes die with the process, synced ones survive it.
"""

from __future__ import annotations

import os
import struct
import time
from typing import Any, Sequence

import asyncio

import jax.numpy as jnp
import numpy as np

from ..core import prng
from ..core import types as T
from ..core.api import Ctx, Program
from .transport import TRANSPORTS


class _Staged:
    """Ctx-shaped view over a compiled handler's returned effects."""

    __slots__ = ("state", "_sends", "_timers", "_cancels", "_crash",
                 "_crash_code", "_halt")

    def __init__(self, state, sends, timers, cancels, crash, crash_code,
                 halt):
        self.state = state
        self._sends, self._timers, self._cancels = sends, timers, cancels
        self._crash, self._crash_code, self._halt = crash, crash_code, halt


class RealNode:
    def __init__(self, node_id: int, state):
        self.id = node_id
        self.state = state
        self.alive = False
        self.paused = False
        self.parked: list = []         # events deferred while paused
        self.timers: list[tuple[int, asyncio.TimerHandle]] = []  # (tag, h)


class RealRuntime:
    """Run programs against real time + real sockets on 127.0.0.1.

    API mirrors the simulator Runtime's supervisor surface
    (kill/restart/pause/resume — runtime/mod.rs:200-256) but every operation
    is a real effect: sockets close, wall-clock timers cancel.
    """

    def __init__(self, cfg: T.SimConfig, programs: Sequence[Program],
                 state_spec: Any, node_prog=None, base_port: int = 19200,
                 seed: int = 0, transport: str = "udp",
                 persist: Any = None, loss: float = 0.0,
                 data_dir: str | None = None, compiled: bool = False,
                 batch_drain: int = 0):
        assert transport in TRANSPORTS, \
            f"unknown transport {transport!r}; registered: " \
            f"{sorted(TRANSPORTS)}"
        self.transport = transport
        self.cfg = cfg
        self.programs = list(programs)
        self.node_prog = list(node_prog if node_prog is not None
                              else [0] * cfg.n_nodes)
        self.spec = state_spec
        self.base_port = base_port
        # persist: same pytree-of-bools as the simulator Runtime — leaves
        # marked True survive restart() (the std/fs.rs stable-storage twin:
        # process memory dies, "disk" doesn't). With data_dir they also
        # survive death of this whole OS process.
        self.persist = persist
        self.data_dir = data_dir
        if data_dir is not None:
            assert persist is not None, "data_dir requires a persist spec"
            os.makedirs(data_dir, exist_ok=True)
        # loss: drop this fraction of outgoing datagrams — loopback is
        # near-lossless, so injected loss is how real-world tests exercise
        # retry paths with real sockets
        self.loss = float(loss)
        import random as _random
        self._loss_rng = _random.Random(seed)
        self.key = prng.seed_key(seed)
        # the frozen seed key beside the splitting draw key: the real
        # twin's Ctx.hash_key root (same (seed, node) derivation as the
        # simulator's SimState.hash_base, so a model's hash streams are
        # bit-identical across the two worlds)
        self.hash_base = prng.seed_key(seed)
        self.nodes = [RealNode(i, self._boot_state(i))
                      for i in range(cfg.n_nodes)]
        self.t0 = time.monotonic()
        self.crashed: list[tuple[int, int]] = []   # (node, code)
        self._halted = asyncio.Event()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._net = TRANSPORTS[transport](cfg.n_nodes, base_port,
                                          self._on_packet)
        # compiled dispatch: jit each (program, handler-kind) once and run
        # events through XLA instead of eager op dispatch — measured
        # 3.4x on the echo workload (~0.9ms vs ~3.2ms per handler event
        # on a 1-core CPU box; remaining cost is jit-call overhead + host
        # sync + asyncio, not the ops) — toward
        # the real-mode performance the reference gets from Rust. Opt-in: the
        # first event of each combo pays its compile, which short demo
        # runs may not amortize. Programs are trace-safe by construction
        # (they run under vmap+jit in the simulator), so behavior is
        # identical; effects come back as staged pytrees with concrete
        # masks and the apply loop below is unchanged.
        self.compiled = bool(compiled)
        self._compiled_fns: dict[tuple[int, str], Any] = {}
        # batched drain: queue incoming events and run up to `batch_drain`
        # of them through ONE jitted lax.scan per drain instead of one
        # XLA round-trip per event, amortizing the per-call dispatch
        # overhead. Measured scope (PARITY §2.2): this helps
        # concurrency-heavy workloads but does NOT make the twin
        # perf-grade — per-slot XLA work and per-event asyncio/socket
        # costs remain; the simulator is the throughput path by design.
        # Semantics match per-event dispatch except that (a) all events
        # of one drain observe the same `now`, and (b) effects escape
        # after the whole drain's state updates (and persist spills) are
        # done — a strictly stronger durability order. 0 disables.
        assert batch_drain >= 0
        self.batch_drain = int(batch_drain)
        if self.batch_drain:
            self.compiled = True       # drains ARE compiled dispatch
        # coalescing window (seconds): deferring the drain this long lets
        # more deliveries queue behind it, deepening the batch — the
        # latency/throughput trade of any interrupt-coalescing NIC.
        # 0 drains on the next loop pass (minimum latency). ADAPTIVE:
        # the delay is only paid while drains actually observe depth
        # (last drain >= 2 events) — on depth-1 traffic coalescing buys
        # nothing and the delay would throttle a closed loop to
        # ~1/delay events/s (the measured 0.74x-eager ping-pong trap),
        # so the window self-disables until depth reappears.
        self.drain_delay = 0.0
        self._last_drain_depth = 0
        self._queue: list = []
        self._drain_scheduled = False
        self._drain_fn = None
        # [N]-stacked authoritative node state while draining; None when
        # per-node states were mutated outside the drain (boot/restart)
        self._stacked = None

    # ------------------------------------------------------------------
    def _fresh_state(self):
        return {k: jnp.asarray(v) for k, v in self.spec.items()} \
            if isinstance(self.spec, dict) else \
            __import__("jax").tree.map(lambda a: jnp.asarray(a), self.spec)

    def _boot_state(self, i: int):
        fresh = self._fresh_state()
        if self.data_dir is None:
            return fresh
        return self._load_persist(i, fresh)

    def now(self) -> int:
        """Virtual-time API, real clock: ticks (us) since runtime start."""
        return int((time.monotonic() - self.t0) * T.TICKS_PER_SEC)

    def _next_key(self):
        self.key, k = prng.split(self.key)
        return k

    # -- on-disk stable storage (std/fs.rs twin) ------------------------
    def _disk_path(self, i: int) -> str:
        return os.path.join(self.data_dir, f"node{i}.npz")

    def _persist_items(self, state):
        """(key, array) for every persist-marked leaf, stable order."""
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(state)
        keep = jax.tree_util.tree_leaves(self.persist)
        assert len(keep) == len(leaves), "persist spec shape mismatch"
        return [(f"leaf{ix}", lf) for ix, (lf, k)
                in enumerate(zip(leaves, keep)) if k], treedef

    def _save_persist(self, i: int):
        import io
        items, _ = self._persist_items(self.nodes[i].state)
        vals = [np.asarray(v) for _, v in items]
        # most events never touch stable storage (fs.py's disk views only
        # change on sync_all/set_len): skip the serialize+fsync when the
        # persist leaves are bit-identical to what's already on disk —
        # a cheap host compare instead of an fsync per dispatched event
        prev = getattr(self, "_persist_cache", {}).get(i)
        if prev is not None and len(prev) == len(vals) and all(
                np.array_equal(a, b) for a, b in zip(prev, vals)):
            return
        buf = io.BytesIO()
        np.savez(buf, **{k: v for (k, _), v in zip(items, vals)})
        tmp = self._disk_path(i) + ".tmp"
        with open(tmp, "wb") as f:
            f.write(buf.getvalue())
            f.flush()
            os.fsync(f.fileno())      # the sync in sync_all made durable
        os.replace(tmp, self._disk_path(i))   # atomic: never a torn file
        # fsync the directory too: os.replace makes the rename atomic in
        # the namespace, but only a dir fsync makes it durable across
        # whole-OS power loss (process kill -9 alone doesn't need this).
        # Cheap here because no-op spills are already skipped above.
        dfd = os.open(self.data_dir, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        if not hasattr(self, "_persist_cache"):
            self._persist_cache = {}
        self._persist_cache[i] = vals

    def _load_persist(self, i: int, fresh):
        import jax
        path = self._disk_path(i)
        if not os.path.exists(path):
            return fresh
        with np.load(path) as z:
            saved = dict(z)
        leaves, treedef = jax.tree_util.tree_flatten(fresh)
        keep = jax.tree_util.tree_leaves(self.persist)
        out = [jnp.asarray(saved[f"leaf{ix}"])
               if k and f"leaf{ix}" in saved else lf
               for ix, (lf, k) in enumerate(zip(leaves, keep))]
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- lifecycle (Handle analog) -------------------------------------
    async def start_node(self, i: int):
        await self._net.start_node(i)
        self.nodes[i].alive = True
        self._dispatch(i, "init")

    def kill(self, i: int):
        n = self.nodes[i]
        n.alive = False
        n.paused = False
        n.parked.clear()
        for _, h in n.timers:
            h.cancel()
        n.timers.clear()
        # purge the node's queued drain events too: a killed process's
        # pending events must never fire — without this, a kill+restart
        # inside the coalescing window would run old-incarnation events
        # against the new incarnation's recovered state
        self._queue = [ev for ev in self._queue if ev[0] != i]
        self._net.close_node(i)

    async def restart(self, i: int):
        self.kill(i)
        old = self.nodes[i].state
        fresh = self._fresh_state()                # process memory is lost
        if self.data_dir is not None:
            # stable storage IS the disk file — reload it, exactly what a
            # new process would see after kill -9
            fresh = self._load_persist(i, fresh)
        elif self.persist is not None:             # in-process stable store
            import jax
            fresh = jax.tree.map(
                lambda f, o, keep: o if keep else f, fresh, old,
                self.persist)
        self.nodes[i].state = fresh
        self._stacked = None            # per-node write: restack on drain
        await self.start_node(i)

    def pause(self, i: int):
        self.nodes[i].paused = True

    def resume(self, i: int):
        n = self.nodes[i]
        n.paused = False
        parked, n.parked = n.parked, []
        for kind, args in parked:
            self._dispatch(i, kind, *args)

    # -- event plumbing -------------------------------------------------
    def _on_packet(self, node: int, data: bytes):
        P = self.cfg.payload_words
        # drop malformed/foreign frames like a corrupt datagram: a reused
        # port (or any third-party transport) can hand us bytes that are
        # not an 8+4P frame, and a struct.error here would unwind the
        # transport's reader loop instead of one packet
        if len(data) != 8 + 4 * P:
            return
        tag, src, *payload = struct.unpack(f"<ii{P}i", data)
        self._dispatch(node, "message", src, tag,
                       jnp.asarray(payload, jnp.int32))

    def _get_compiled(self, p_idx: int, kind: str):
        """jit of one (program, handler-kind): (state, node, now, key,
        src, tag, payload) -> (state', sends, timers, cancels, crash,
        crash_code, halt). Effect lists have static length per trace, so
        they return as pytrees of concrete arrays; the apply loop below
        consumes them exactly like an eager Ctx."""
        fn = self._compiled_fns.get((p_idx, kind))
        if fn is None:
            import jax
            prog = self.programs[p_idx]
            cfg = self.cfg

            hash_base = self.hash_base

            def run(state, node, now, key, src, tag, payload):
                ctx = Ctx(cfg, node, now, key, state, hash_base=hash_base)
                self._invoke(prog, ctx, kind, src, tag, payload)
                return (ctx.state, ctx._sends, ctx._timers, ctx._cancels,
                        ctx._crash, ctx._crash_code, ctx._halt)

            fn = jax.jit(run)
            self._compiled_fns[(p_idx, kind)] = fn
        return fn

    @staticmethod
    def _invoke(prog, ctx, kind, src, tag, payload):
        """The one handler-kind dispatch, shared by the compiled and
        eager paths so they can never diverge."""
        if kind == "init":
            prog.init(ctx)
        elif kind == "message":
            prog.on_message(ctx, src, tag, payload)
        else:
            prog.on_timer(ctx, tag, payload)

    def _warm_compiled(self):
        """Compile every (program-in-use, kind) combo up front — XLA
        compiles are seconds-long and would otherwise run synchronously
        inside the event loop on each combo's FIRST event, firing every
        node's timers late in a burst mid-protocol. Dummy inputs on the
        fresh state template; handlers are pure, results discarded; the
        fixed key leaves the runtime's real key stream untouched."""
        P = self.cfg.payload_words
        dummy = (self._fresh_state(), jnp.asarray(0, jnp.int32),
                 jnp.asarray(0, jnp.int32), prng.seed_key(0xC0FFEE),
                 jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                 jnp.zeros((P,), jnp.int32))
        for p_idx in sorted(set(self.node_prog)):
            for kind in ("init", "message", "timer"):
                self._get_compiled(p_idx, kind)(*dummy)

    def _warm_drain(self):
        """Compile the batched drain for every slot bucket up front (same
        rationale as _warm_compiled; jit caches one program per bucket
        shape, so no mid-protocol compile stall on the first deep/shallow
        queue)."""
        import jax
        P = self.cfg.payload_words
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[n.state for n in self.nodes])
        fn = self._get_drain_fn()
        done = set()
        for m in self._BUCKETS + (self.batch_drain,):
            K = self._bucket(m)
            # K=1 is unreachable since the depth-1 bypass (a one-event
            # drain runs per-event compiled dispatch) — don't pay its
            # compile at startup
            if K in done or K == 1:
                continue
            done.add(K)
            out = fn(stacked, jnp.asarray(0, jnp.int32),
                     jnp.zeros((K,), bool), jnp.zeros((K,), jnp.int32),
                     jnp.zeros((K,), jnp.int32), jnp.zeros((K,), jnp.int32),
                     jnp.zeros((K,), jnp.int32), jnp.zeros((K, P),
                                                           jnp.int32),
                     jnp.stack([prng.seed_key(0)] * K))
            jax.block_until_ready(out[0])

    def _dispatch(self, node: int, kind: str, *args):
        n = self.nodes[node]
        if not n.alive:
            return
        if n.paused:
            n.parked.append((kind, args))
            return
        if self.batch_drain:
            P = self.cfg.payload_words
            if kind == "init":
                src, tag, pl = 0, 0, jnp.zeros((P,), jnp.int32)
            elif kind == "message":
                src, tag, pl = args[0], args[1], args[2]
            else:
                src, tag, pl = 0, args[0], args[1]
            self._queue.append((node, {"init": 0, "message": 1,
                                       "timer": 2}[kind],
                                int(src), int(tag), pl))
            if not self._drain_scheduled and self._loop is not None:
                self._drain_scheduled = True
                if self.drain_delay > 0 and self._last_drain_depth >= 2:
                    self._loop.call_later(self.drain_delay, self._drain)
                else:
                    self._loop.call_soon(self._drain)
            return
        p_idx = self.node_prog[node]
        node_j = jnp.asarray(node, jnp.int32)
        now_j = jnp.asarray(self.now(), jnp.int32)
        if self.compiled:
            P = self.cfg.payload_words
            if kind == "init":
                src, tag, pl = 0, 0, jnp.zeros((P,), jnp.int32)
            elif kind == "message":
                src, tag, pl = args[0], args[1], args[2]
            else:
                src, tag, pl = 0, args[0], args[1]
            self._run_compiled_event(n, kind, src, tag, pl)
            return
        prog = self.programs[p_idx]
        ctx = Ctx(self.cfg, node_j, now_j, self._next_key(), n.state,
                  hash_base=self.hash_base)
        if kind == "init":
            src, tag, pl = None, None, None
        elif kind == "message":
            src = jnp.asarray(args[0], jnp.int32)
            tag, pl = jnp.asarray(args[1], jnp.int32), args[2]
        else:
            src = None
            tag, pl = jnp.asarray(args[0], jnp.int32), args[1]
        self._invoke(prog, ctx, kind, src, tag, pl)
        self._apply(n, ctx)

    def _run_compiled_event(self, n: RealNode, kind: str, src, tag, pl):
        """Per-event compiled dispatch tail — the ONE incantation shared
        by _dispatch's compiled branch and _drain's depth-1 bypass, so
        the two paths can never diverge (same rationale as _invoke)."""
        out = self._get_compiled(self.node_prog[n.id], kind)(
            n.state, jnp.asarray(n.id, jnp.int32),
            jnp.asarray(self.now(), jnp.int32), self._next_key(),
            jnp.asarray(src, jnp.int32), jnp.asarray(tag, jnp.int32),
            jnp.asarray(pl, jnp.int32))
        self._apply(n, _Staged(*out))

    # -- batched drain ---------------------------------------------------
    def _get_drain_fn(self):
        """One jitted lax.scan over `batch_drain` event slots. The body is
        the real-world re-telling of the sim step's dispatch phase
        (core/step.py §3): every (program x handler-kind) combo executes
        for every slot, masks decide which commits — here the "batch"
        axis is queued real events instead of seeds, and effects return
        as [K]-stacked staged pytrees for the host to apply in order."""
        if self._drain_fn is not None:
            return self._drain_fn
        import jax
        from jax import lax
        from ..core.step import (EMPTY_CANCEL, EMPTY_SEND, EMPTY_TIMER,
                                 _scatter_node, _slice_node, _where_tree)
        cfg = self.cfg
        programs = self.programs
        node_prog_j = jnp.asarray(self.node_prog, jnp.int32)
        P = cfg.payload_words
        hash_base = self.hash_base
        def body(carry, xs):
            stacked, now = carry
            valid, node, kindc, src, tag, pl, key = xs
            base = _slice_node(stacked, node)
            combos = []
            p_of_node = jnp.sum(
                jnp.where(jnp.arange(cfg.n_nodes) == node, node_prog_j, 0))
            for p_idx, prog in enumerate(programs):
                pmask = p_of_node == p_idx
                for code, run in (
                        (0, lambda c: prog.init(c)),
                        (1, lambda c: prog.on_message(c, src, tag, pl)),
                        (2, lambda c: prog.on_timer(c, tag, pl))):
                    ctx = Ctx(cfg, node, now, key, base,
                              hash_base=hash_base)
                    run(ctx)
                    combos.append((valid & pmask & (kindc == code), ctx))
            any_h = jnp.asarray(False)
            new_slice = base
            crash = jnp.asarray(False)
            crash_code = jnp.asarray(0, jnp.int32)
            halt = jnp.asarray(False)
            ns = max((len(c._sends) for _, c in combos), default=0)
            nt = max((len(c._timers) for _, c in combos), default=0)
            nc = max((len(c._cancels) for _, c in combos), default=0)
            sends = [EMPTY_SEND(P) for _ in range(ns)]
            timers = [EMPTY_TIMER(P) for _ in range(nt)]
            cancels = [EMPTY_CANCEL() for _ in range(nc)]
            for m, ctx in combos:
                any_h = any_h | m
                new_slice = _where_tree(m, ctx.state, new_slice)
                crash = crash | (m & ctx._crash)
                crash_code = jnp.where(m & ctx._crash, ctx._crash_code,
                                       crash_code)
                halt = halt | (m & ctx._halt)
                for j, e in enumerate(ctx._sends):
                    sends[j] = _where_tree(m, dict(e, m=e["m"] & m),
                                           sends[j])
                for j, e in enumerate(ctx._timers):
                    timers[j] = _where_tree(m, dict(e, m=e["m"] & m),
                                            timers[j])
                for j, e in enumerate(ctx._cancels):
                    cancels[j] = _where_tree(m, dict(e, m=e["m"] & m),
                                             cancels[j])
            stacked = _scatter_node(stacked, node, new_slice, any_h)
            return (stacked, now), (sends, timers, cancels, crash,
                                    crash_code, halt)

        def drain(stacked, now, valid, node, kindc, src, tag, pl, keys):
            (stacked, _), eff = lax.scan(
                body, (stacked, now),
                (valid, node, kindc, src, tag, pl, keys))
            return stacked, eff

        self._drain_fn = jax.jit(drain)
        return self._drain_fn

    _BUCKETS = (1, 4, 16, 64, 256)

    def _bucket(self, m: int) -> int:
        """Smallest slot-count bucket holding m events (jit re-specializes
        per shape, so each bucket compiles once — warmed up front — and a
        shallow queue never pays the full-depth scan)."""
        for k in self._BUCKETS:
            if m <= k or k >= self.batch_drain:
                return min(k, self.batch_drain)
        return self.batch_drain

    def _get_stacked(self):
        if self._stacked is None:
            import jax
            self._stacked = jax.tree.map(
                lambda *xs: jnp.stack(xs), *[n.state for n in self.nodes])
        return self._stacked

    def _drain(self):
        self._drain_scheduled = False
        if not self._queue or self._loop is None:
            return
        K = self.batch_drain
        take, self._queue = self._queue[:K], self._queue[K:]
        if self._queue:                 # more than one drain's worth
            self._drain_scheduled = True
            self._loop.call_soon(self._drain)
        events = []
        for ev in take:
            n = self.nodes[ev[0]]
            if not n.alive:             # killed after enqueue: drop
                continue
            if n.paused:                # paused after enqueue: park
                kind = ("init", "message", "timer")[ev[1]]
                args = (() if ev[1] == 0 else
                        (ev[2], ev[3], ev[4]) if ev[1] == 1 else
                        (ev[3], ev[4]))
                n.parked.append((kind, args))
                continue
            events.append(ev)
        # adaptive coalescing signal: LIVE depth only — dead/parked
        # events didn't run, so counting them would keep the delay
        # engaged on traffic that observes no real depth
        self._last_drain_depth = len(events)
        if not events:
            return
        if len(events) == 1:
            # depth-1 guard rail: a one-event scan amortizes nothing and
            # pays the stacked-state round-trip (measured 0.64x eager on
            # the depth-1 ping-pong shape, on the CPU) — run the
            # event through per-event compiled dispatch instead. Key draw
            # order is identical (one key per event in both modes).
            node, kc, src, tag, pl = events[0]
            self._stacked = None        # per-node write: restack on drain
            self._run_compiled_event(self.nodes[node],
                                     ("init", "message", "timer")[kc],
                                     src, tag, pl)
            return
        import jax
        m = len(events)
        B = self._bucket(m)
        P = self.cfg.payload_words
        valid = np.zeros(B, bool)
        valid[:m] = True
        nodei = np.zeros(B, np.int32)
        kindc = np.zeros(B, np.int32)
        srca = np.zeros(B, np.int32)
        taga = np.zeros(B, np.int32)
        pla = np.zeros((B, P), np.int32)
        keys = [prng.seed_key(0)] * B
        for j, (node, kc, src, tag, pl) in enumerate(events):
            nodei[j], kindc[j], srca[j], taga[j] = node, kc, src, tag
            pla[j] = np.asarray(pl, np.int32)
            keys[j] = self._next_key()  # same draw order as per-event mode
        stacked, eff = self._get_drain_fn()(
            self._get_stacked(), jnp.asarray(self.now(), jnp.int32),
            jnp.asarray(valid), jnp.asarray(nodei), jnp.asarray(kindc),
            jnp.asarray(srca), jnp.asarray(taga), jnp.asarray(pla),
            jnp.stack(keys))
        self._stacked = stacked         # stays authoritative across drains
        sends, timers, cancels, crash, crash_code, halt = eff
        # sync ONLY touched per-node rows (others are bit-identical); spill
        # stable storage for all touched nodes BEFORE any send escapes —
        # the per-event durability order, at drain granularity
        touched = sorted({node for node, *_ in events})
        for i in touched:
            self.nodes[i].state = jax.tree.map(lambda a: a[i], stacked)
            if self.data_dir is not None:
                self._save_persist(i)
        # host-apply effects in queue order (sends, then cancels, then
        # timers per event — exactly _apply's order)
        sends = jax.tree.map(np.asarray, sends)
        timers = jax.tree.map(np.asarray, timers)
        cancels = jax.tree.map(np.asarray, cancels)
        crash, crash_code, halt = (np.asarray(crash),
                                   np.asarray(crash_code), np.asarray(halt))
        for j, (node, *_rest) in enumerate(events):
            n = self.nodes[node]
            row = _Staged(
                n.state,
                [{k: v[j] for k, v in e.items()} for e in sends],
                [{k: v[j] for k, v in e.items()} for e in timers],
                [{k: v[j] for k, v in e.items()} for e in cancels],
                bool(crash[j]), int(crash_code[j]), bool(halt[j]))
            self._apply_effects(n, row)

    def _apply(self, n: RealNode, ctx: Ctx):
        n.state = ctx.state
        if self.data_dir is not None:
            # spill stable storage BEFORE effects escape: an ack that
            # promises durability must not be sent while the synced bytes
            # exist only in this process's memory
            self._save_persist(n.id)
        self._apply_effects(n, ctx)

    def _apply_effects(self, n: RealNode, ctx):
        """Apply a handler's staged effects (the shared tail of per-event
        _apply and the batched drain; state/persist are already settled
        by the caller)."""
        P = self.cfg.payload_words
        for e in ctx._sends:
            if not bool(e["m"]):
                continue
            dst = int(e["dst"])
            if not (0 <= dst < self.cfg.n_nodes) or not n.alive:
                continue
            if self.loss and self._loss_rng.random() < self.loss:
                continue  # injected packet loss (real networks drop; loopback won't)
            pkt = struct.pack(f"<ii{P}i", int(e["tag"]), n.id,
                              *np.asarray(e["payload"], np.int32))
            # real send: straight to the peer; latency, loss, and
            # reordering are whatever the real backend does
            self._net.send(n.id, dst, pkt)
        cancelled_tags = set()
        for e in ctx._cancels:
            if not bool(e["m"]):
                continue
            # Sleep::reset/abort analog: wall-clock timers really cancel.
            # Also purge matching timer events parked by pause() — their
            # handles are already spent, but the event must not fire at
            # resume (narrows the inherent wall-clock-vs-virtual-schedule
            # divergence; exact schedule equivalence across worlds is
            # not a goal — the real world has no tie-break scheduler).
            t = int(e["tag"])
            for tag_i, h in n.timers:
                if tag_i == t:
                    h.cancel()
            n.timers = [(tg, h) for tg, h in n.timers if tg != t]
            n.parked = [(kind, args) for kind, args in n.parked
                        if not (kind == "timer" and int(args[0]) == t)]
            cancelled_tags.add(t)
        # batched mode: also purge matching timer firings already
        # sitting in the drain queue (a handle that fired during the
        # coalescing window), mirroring per-event semantics where
        # the cancel lands before the call_later fires. Events of
        # the SAME drain are inherently concurrent — a cancel
        # cannot retract a firing that ran earlier in its own scan;
        # the call-id payload idiom covers that residual window.
        # ONE filter pass for all of this handler's cancels: a per-cancel
        # rebuild would be O(cancels x queue_len) per drain.
        if self.batch_drain and cancelled_tags:
            self._queue = [ev for ev in self._queue
                           if not (ev[0] == n.id and ev[1] == 2
                                   and int(ev[3]) in cancelled_tags)]
        for e in ctx._timers:
            if not bool(e["m"]):
                continue
            delay = int(e["delay"]) / T.TICKS_PER_SEC
            tag = jnp.asarray(int(e["tag"]), jnp.int32)
            payload = e["payload"]
            entry = []

            def fire(n=n, tag=tag, payload=payload, entry=entry):
                # self-prune: spent handles must not accumulate (a
                # periodic timer would otherwise grow the list per fire)
                if entry and entry[0] in n.timers:
                    n.timers.remove(entry[0])
                self._dispatch(n.id, "timer", tag, payload)

            h = self._loop.call_later(delay, fire)
            entry.append((int(e["tag"]), h))
            n.timers.append(entry[0])
        if bool(ctx._crash):
            self.crashed.append((n.id, int(ctx._crash_code)))
            self._halted.set()
        if bool(ctx._halt):
            self._halted.set()

    # -- entry point ----------------------------------------------------
    async def start(self, nodes: Sequence[int] | None = None):
        """Begin real-time execution on the CURRENT event loop: bind the
        loop (timers dispatch via call_later), zero the clock origin, and
        start the given nodes (default: all).

        The public entry for custom supervisor scripts — tests and demos
        await this, then drive kill/restart/pause between awaits (the
        block_on-a-supervisor-future shape, runtime/mod.rs:119) — and for
        single-node boots like recovery inspection (start just the
        server, read its recovered state)."""
        if self.batch_drain:
            self._warm_drain()         # before sockets/timers exist
            self._warm_compiled()      # depth-1 drains bypass to per-event
        elif self.compiled:
            self._warm_compiled()
        self._loop = asyncio.get_running_loop()
        self.t0 = time.monotonic()
        for i in (range(self.cfg.n_nodes) if nodes is None else nodes):
            await self.start_node(i)
        if self._queue and not self._drain_scheduled:
            # events enqueued before the loop was bound (e.g. init
            # dispatched by an external start_node call)
            self._drain_scheduled = True
            self._loop.call_soon(self._drain)

    async def _main(self, duration: float):
        await self.start()
        try:
            await asyncio.wait_for(self._halted.wait(), timeout=duration)
        except asyncio.TimeoutError:
            pass
        for i in range(self.cfg.n_nodes):
            self.kill(i)

    def run(self, duration: float = 2.0):
        """Block until a program halts/crashes or `duration` seconds pass.
        The `#[madsim::main]` real-mode analog (macros lib.rs:46-78)."""
        asyncio.run(self._main(duration))
        return self

    def states(self):
        return [n.state for n in self.nodes]
