"""minipg — a postgres-shaped session protocol over the sim TCP stack.

The reference's strongest ecosystem claim is that an UNMODIFIED complex
client protocol (tokio-postgres: startup/auth handshake, pipelined queries,
transactions) runs over its simulated sockets
(madsim-tokio-postgres/src/socket.rs:6-13 swaps the socket; everything
above is untouched). This model is that claim rebuilt natively: one
protocol state machine with

  * a multi-phase session handshake: STARTUP -> salted-challenge AUTH ->
    READY (wrong credentials draw ERROR + connection reset),
  * PIPELINED queries: the client issues a whole transaction's statements
    without awaiting responses; the server answers strictly in order,
  * TRANSACTIONS: BEGIN / SET / GET (read-your-writes through the txn
    buffer) / COMMIT / ROLLBACK, with exactly-once commits across
    reconnect-and-retry (txn ids dedup against the last committed id),

running over the full sim TCP stack — conn.py lifecycle (SYN/SYN-ACK/RST)
+ stream.py reliable ordered framing — under kill/loss chaos, AND over
real asyncio sockets (real/runtime.py) with the SAME code: the dual-world
contract, proven by tests/test_minipg.py + tests/test_real_runtime.py.

Client-side oracles (ctx.crash_if): response statuses per pipeline
position, read-your-writes inside transactions, committed-state visibility
after COMMIT, rollback invisibility — so a run completing IS the
correctness assertion.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.api import Ctx, Program
from ..core.types import ms
from ..net import conn, stream

# wire frames (ride reliable stream items): [mtype, a, b, c, d]
M_STARTUP, M_AUTHREQ, M_AUTH, M_READY, M_ERROR, M_QUERY, M_RESULT = \
    1, 2, 3, 4, 5, 6, 7
FRAME_WORDS = 5
PROTO_VER = 3          # a nod to the postgres v3 protocol

# query ops
OP_BEGIN, OP_SET, OP_GET, OP_COMMIT, OP_ROLLBACK = 1, 2, 3, 4, 5
# result statuses
ST_OK, ST_VAL, ST_ERR, ST_DUP = 1, 2, 3, 4

# session phases (server, per client)
S_NONE, S_AWAIT_AUTH, S_READY = 0, 1, 2

CRASH_BAD_STATUS = 401
CRASH_TXN_READ = 402
CRASH_VISIBILITY = 403

SERVER = 0
TXN_BUF = 4            # statements a transaction may buffer
RING = 12              # server response backpressure ring, per client
OPS_PER_TXN = 6        # BEGIN, SET, GET, SET, COMMIT|ROLLBACK, verify-GET

AUTH_MIX = 1540483477  # odd multiplier for the toy digest


def password_for(user):
    """The shared secret both sides derive (a stand-in for a password
    file); tests break it deliberately to exercise the refusal path."""
    return user * 7 + 13


def auth_digest(user, password, salt):
    return (user * AUTH_MIX + password) ^ salt


def pg_state_spec(n_nodes: int, n_keys: int, window: int = 8):
    z = jnp.asarray(0, jnp.int32)
    N = n_nodes
    return dict(
        **conn.conn_state(N),
        **stream.stream_state(N, window=window, item_words=FRAME_WORDS),
        # server: sessions
        sess=jnp.zeros((N,), jnp.int32),
        salt=jnp.zeros((N,), jnp.int32),
        susr=jnp.zeros((N,), jnp.int32),
        # server: transactions
        txn=jnp.zeros((N,), jnp.int32),
        tb_key=jnp.zeros((N, TXN_BUF), jnp.int32),
        tb_val=jnp.zeros((N, TXN_BUF), jnp.int32),
        tb_n=jnp.zeros((N,), jnp.int32),
        # server: durable storage (persist mask) — the database survives
        # power-fail; sessions and open transactions do not
        kv=jnp.zeros((n_keys,), jnp.int32),
        ltid=jnp.zeros((N,), jnp.int32),
        # server: in-order response ring (backpressure, never drop)
        rb=jnp.zeros((N, RING, FRAME_WORDS), jnp.int32),
        rb_w=jnp.zeros((N,), jnp.int32),
        rb_r=jnp.zeros((N,), jnp.int32),
        # client
        c_phase=z, c_salt=z, c_tid=jnp.asarray(1, jnp.int32),
        c_sq=z, c_rid=z, c_dup0=z,
        c_exp=jnp.zeros((2,), jnp.int32),
        c_prog=z, c_done=z, c_rej=z,
    )


def pg_persist_spec(spec):
    """Only the database (kv) and commit-dedup table (ltid) are durable."""
    return {k: k in ("kv", "ltid") for k in spec}


class PgServer(Program):
    def __init__(self, n_nodes: int, n_keys: int, tick=ms(10),
                 epoch_guard: bool = True):
        self.n = n_nodes
        self.K = n_keys
        self.tick = tick
        # r19 incarnation guard (net/conn.py, net/stream.py): True is the
        # sound default; False compiles the pre-r19 accept-everything
        # transport — the honest red control tests/test_connfault.py and
        # the connfault_mix regression corpus use to PROVE the guard is
        # what makes exactly-once survive connection churn
        self.guard = epoch_guard

    # ---- response ring (strict output order + backpressure) -------------
    def _rpush(self, st, src, words, when):
        w = st["rb_w"][src]
        slot = w % RING
        ok = jnp.asarray(when) & (w - st["rb_r"][src] < RING)
        frame = jnp.stack([jnp.asarray(x, jnp.int32) for x in words])
        st["rb"] = st["rb"].at[src, slot].set(
            jnp.where(ok, frame, st["rb"][src, slot]))
        st["rb_w"] = st["rb_w"].at[src].set(w + ok)

    def _drain(self, ctx, st):
        for c in range(1, self.n):
            for _ in range(2):     # ≤2 frames per client per event
                has = st["rb_r"][c] < st["rb_w"][c]
                slot = st["rb_r"][c] % RING
                ok = stream.send(ctx, st, c, st["rb"][c, slot], when=has)
                st["rb_r"] = st["rb_r"].at[c].set(st["rb_r"][c] + ok)

    # ---- one protocol frame ---------------------------------------------
    def _frame(self, ctx: Ctx, st, src, f, when):
        from ..utils.maskutil import needed
        mtype, a, b, c, d = f[0], f[1], f[2], f[3], f[4]
        zero = jnp.asarray(0, jnp.int32)

        # STARTUP: fresh session — void any open txn and pending output,
        # challenge with a salt
        su = when & (mtype == M_STARTUP)
        if needed(su):
            st["sess"] = st["sess"].at[src].set(
                jnp.where(su, S_AWAIT_AUTH, st["sess"][src]))
            st["susr"] = st["susr"].at[src].set(
                jnp.where(su, b, st["susr"][src]))
            st["txn"] = st["txn"].at[src].set(
                jnp.where(su, 0, st["txn"][src]))
            st["salt"] = st["salt"].at[src].set(
                jnp.where(su, ctx.randint(1, 2**30 - 1), st["salt"][src]))
            self._rpush(st, src,
                        [M_AUTHREQ, st["salt"][src], zero, zero, zero], su)

        # AUTH: verify the salted digest
        au = when & (mtype == M_AUTH) & (st["sess"][src] == S_AWAIT_AUTH)
        if needed(au):
            good = a == auth_digest(st["susr"][src],
                                    password_for(st["susr"][src]),
                                    st["salt"][src])
            st["sess"] = st["sess"].at[src].set(
                jnp.where(au & good, S_READY, st["sess"][src]))
            self._rpush(st, src, [M_READY, zero, zero, zero, zero],
                        au & good)
            # bad credentials: best-effort ERROR, then reset the connection
            stream.send(ctx, st, src, [M_ERROR, 1, 0, 0, 0],
                        when=au & ~good)
            conn.reset(ctx, st, src, when=au & ~good)
            st["sess"] = st["sess"].at[src].set(
                jnp.where(au & ~good, S_NONE, st["sess"][src]))

        # QUERY: the pipelined statement machine
        q = when & (mtype == M_QUERY) & (st["sess"][src] == S_READY)
        if not needed(q):
            return
        qid, op, key, val = a, b, jnp.clip(c, 0, self.K - 1), d
        open_ = st["txn"][src] == 1

        beg = q & (op == OP_BEGIN)
        dup = beg & (c <= st["ltid"][src])      # txn id already committed
        st["txn"] = st["txn"].at[src].set(
            jnp.where(beg & ~dup, 1, st["txn"][src]))
        st["tb_n"] = st["tb_n"].at[src].set(
            jnp.where(beg & ~dup, 0, st["tb_n"][src]))

        sets = q & (op == OP_SET) & open_
        room = st["tb_n"][src] < TXN_BUF
        wslot = jnp.clip(st["tb_n"][src], 0, TXN_BUF - 1)
        st["tb_key"] = st["tb_key"].at[src, wslot].set(
            jnp.where(sets & room, key, st["tb_key"][src, wslot]))
        st["tb_val"] = st["tb_val"].at[src, wslot].set(
            jnp.where(sets & room, val, st["tb_val"][src, wslot]))
        st["tb_n"] = st["tb_n"].at[src].set(st["tb_n"][src] + (sets & room))

        # GET reads through the txn buffer (read-your-writes), else storage
        get = q & (op == OP_GET)
        js = jnp.arange(TXN_BUF, dtype=jnp.int32)
        m = (st["tb_key"][src] == key) & (js < st["tb_n"][src]) & open_
        lastb = jnp.max(jnp.where(m, js + 1, 0))
        read = jnp.where(lastb > 0,
                         st["tb_val"][src, jnp.clip(lastb - 1, 0,
                                                    TXN_BUF - 1)],
                         st["kv"][key])

        com = q & (op == OP_COMMIT)
        cdup = com & ~open_ & (c <= st["ltid"][src])
        apply_ = com & open_
        for j in range(TXN_BUF):        # ordered buffer replay
            aj = apply_ & (j < st["tb_n"][src])
            kj = jnp.clip(st["tb_key"][src, j], 0, self.K - 1)
            st["kv"] = st["kv"].at[kj].set(
                jnp.where(aj, st["tb_val"][src, j], st["kv"][kj]))
        st["ltid"] = st["ltid"].at[src].set(
            jnp.where(apply_, jnp.maximum(st["ltid"][src], c),
                      st["ltid"][src]))
        st["txn"] = st["txn"].at[src].set(
            jnp.where(com, 0, st["txn"][src]))

        rol = q & (op == OP_ROLLBACK)
        st["txn"] = st["txn"].at[src].set(jnp.where(rol, 0, st["txn"][src]))
        st["tb_n"] = st["tb_n"].at[src].set(
            jnp.where(com | rol, 0, st["tb_n"][src]))

        status = jnp.where(
            beg, jnp.where(dup, ST_DUP, ST_OK),
            jnp.where(sets, jnp.where(room, ST_OK, ST_ERR),
                      jnp.where(get, ST_VAL,
                                jnp.where(com,
                                          jnp.where(apply_, ST_OK,
                                                    jnp.where(cdup, ST_DUP,
                                                              ST_ERR)),
                                          jnp.where(rol, ST_OK, ST_ERR)))))
        # a SET outside a txn is autocommit-disabled here: explicit ERR
        status = jnp.where(q & (op == OP_SET) & ~open_, ST_ERR, status)
        self._rpush(st, src, [M_RESULT, qid, status,
                              jnp.where(get, read, zero), zero], q)

    # ---- lifecycle -------------------------------------------------------
    def init(self, ctx: Ctx):
        st = dict(ctx.state)
        conn.listen(ctx, st)
        ctx.set_timer(self.tick, 1, [0])
        ctx.state = st

    def on_timer(self, ctx: Ctx, tag, payload):
        st = dict(ctx.state)
        self._drain(ctx, st)
        for c in range(1, self.n):
            stream.retransmit(ctx, st, c, when=True)
        ctx.set_timer(self.tick, 1, [0])
        ctx.state = st

    def on_message(self, ctx: Ctx, src, tag, payload):
        st = dict(ctx.state)
        from ..utils.maskutil import needed
        accept, _, rst = conn.on_message(ctx, st, src, tag, payload,
                                         epoch_guard=self.guard)
        # a (re)connecting or resetting peer voids its session and
        # pending output — new connection, new world (the conn layer
        # already rebased the stream fabric onto the negotiated
        # incarnation, r19)
        fresh = accept | rst
        if needed(fresh):
            for k in ("rb_w", "rb_r", "sess", "txn", "tb_n"):
                st[k] = st[k].at[src].set(jnp.where(fresh, 0, st[k][src]))

        vals, mask = stream.on_message(ctx, st, src, tag, payload,
                                       epoch_guard=self.guard)
        for i in stream.delivered_slots(mask):
            self._frame(ctx, st, src, vals[i], mask[i])
        self._drain(ctx, st)
        ctx.state = st


class PgClient(Program):
    """Runs n_txns pipelined transactions, verifying every response; txn
    ids make retried commits exactly-once. wrong_password exercises the
    auth-refusal path (expects ERROR/RST, never READY)."""

    def __init__(self, n_txns: int = 4, tick=ms(8), stall=ms(250),
                 wrong_password: bool = False, epoch_guard: bool = True):
        self.T = n_txns
        self.tick = tick
        self.stall = stall
        self.wrong = wrong_password
        self.guard = epoch_guard

    def _keys(self, ctx):
        base = (ctx.node - 1) * 2
        return base, base + 1

    def _val(self, ctx, tid):
        return ctx.node * 10000 + tid * 10

    def _is_commit(self, tid):
        return tid % 2 == 1

    def init(self, ctx: Ctx):
        st = dict(ctx.state)
        st["c_prog"] = ctx.now
        ctx.set_timer(ctx.randint(0, self.tick), 1, [0])
        ctx.state = st

    def _reset_session(self, ctx, st, when):
        from ..utils.maskutil import needed
        if not needed(when):
            return
        conn.reset(ctx, st, SERVER, when=when)
        stream.reset_peer(st, SERVER, when=when)
        st["c_phase"] = jnp.where(when, 0, st["c_phase"])
        for k in ("c_sq", "c_rid", "c_dup0"):
            st[k] = jnp.where(when, 0, st[k])

    def on_timer(self, ctx: Ctx, tag, payload):
        st = dict(ctx.state)
        done = st["c_done"] == 1

        # stall watchdog: tear the session down, re-handshake, re-run the
        # CURRENT txn (same tid — the server dedups a re-commit)
        stalled = ~done & (ctx.now - st["c_prog"] > self.stall)
        self._reset_session(ctx, st, stalled)
        st["c_prog"] = jnp.where(stalled, ctx.now, st["c_prog"])

        # phase 0: connect, then STARTUP
        est = conn.is_established(st, SERVER)
        conn.connect(ctx, st, SERVER, when=~done & (st["c_phase"] == 0)
                     & ~est)
        ok = stream.send(ctx, st, SERVER,
                         [M_STARTUP, PROTO_VER, ctx.node, 0, 0],
                         when=~done & (st["c_phase"] == 0) & est)
        st["c_phase"] = jnp.where(ok, 1, st["c_phase"])
        st["c_prog"] = jnp.where(ok, ctx.now, st["c_prog"])

        # phase 3: issue the pipelined statements of the current txn —
        # never waiting for a response before the next statement
        from ..utils.maskutil import needed
        tid = st["c_tid"]
        k0, k1 = self._keys(ctx)
        v = self._val(ctx, tid)
        commit = self._is_commit(tid)
        sq = st["c_sq"]
        issuing = ~done & (st["c_phase"] == 3) & (sq < OPS_PER_TXN) & (
            tid <= self.T)
        if needed(issuing):
            op = jnp.where(
                sq == 0, OP_BEGIN,
                jnp.where((sq == 1) | (sq == 3), OP_SET,
                          jnp.where(sq == 2, OP_GET,
                                    jnp.where(sq == 4,
                                              jnp.where(commit, OP_COMMIT,
                                                        OP_ROLLBACK),
                                              OP_GET))))
            key = jnp.where((sq == 0) | (sq == 4), tid,
                            jnp.where(sq == 3, k1, k0))
            val = jnp.where(sq == 1, v, jnp.where(sq == 3, v + 1000, 0))
            qid = tid * 8 + sq
            sent = stream.send(ctx, st, SERVER,
                               [M_QUERY, qid, op, key, val], when=issuing)
            st["c_sq"] = st["c_sq"] + sent
            st["c_prog"] = jnp.where(sent, ctx.now, st["c_prog"])

        stream.retransmit(ctx, st, SERVER, when=~done)
        ctx.set_timer(self.tick, 1, [0], when=True)
        ctx.state = st

    def _result(self, ctx: Ctx, st, f, when):
        from ..utils.maskutil import needed
        mtype, a, b, c = f[0], f[1], f[2], f[3]

        # handshake frames
        hs = when & ((mtype == M_AUTHREQ) | (mtype == M_READY)
                     | (mtype == M_ERROR))
        if needed(hs):
            ar = when & (mtype == M_AUTHREQ) & (st["c_phase"] == 1)
            pw = password_for(ctx.node) + (1 if self.wrong else 0)
            dig = auth_digest(ctx.node, pw, a)
            ok = stream.send(ctx, st, SERVER, [M_AUTH, dig, 0, 0, 0],
                             when=ar)
            st["c_phase"] = jnp.where(ok, 2, st["c_phase"])
            rdy = when & (mtype == M_READY) & (st["c_phase"] == 2)
            st["c_phase"] = jnp.where(rdy, 3, st["c_phase"])
            # the refusal oracle: with bad credentials READY must never come
            if self.wrong:
                ctx.crash_if(rdy, CRASH_BAD_STATUS)
            err = when & (mtype == M_ERROR)
            st["c_rej"] = jnp.where(err, 1, st["c_rej"])
            st["c_done"] = jnp.where(err & self.wrong, 1, st["c_done"])
            st["c_prog"] = jnp.where(ar | rdy | err, ctx.now, st["c_prog"])

        if not needed(when & (mtype == M_RESULT)):
            return
        # pipelined results, strictly in order: c_rid is the position
        tid = st["c_tid"]
        v = self._val(ctx, tid)
        commit = self._is_commit(tid)
        res = (when & (mtype == M_RESULT) & (st["c_phase"] == 3)
               & (st["c_done"] == 0) & (a == tid * 8 + st["c_rid"]))
        pos = st["c_rid"]
        dup0 = st["c_dup0"] == 1

        p0 = res & (pos == 0)
        ctx.crash_if(p0 & (b != ST_OK) & (b != ST_DUP), CRASH_BAD_STATUS)
        st["c_dup0"] = jnp.where(p0 & (b == ST_DUP), 1, st["c_dup0"])

        pset = res & ((pos == 1) | (pos == 3)) & ~dup0
        ctx.crash_if(pset & (b != ST_OK), CRASH_BAD_STATUS)

        # read-your-writes inside the txn
        p2 = res & (pos == 2) & ~dup0
        ctx.crash_if(p2 & ((b != ST_VAL) | (c != v)), CRASH_TXN_READ)

        p4 = res & (pos == 4)
        if True:  # commit/rollback status check
            ctx.crash_if(p4 & commit & (b != ST_OK) & (b != ST_DUP),
                         CRASH_BAD_STATUS)
            ctx.crash_if(p4 & ~commit & ~dup0 & (b != ST_OK),
                         CRASH_BAD_STATUS)
        # commit visibility: remember what the database must now hold
        landed = p4 & commit & ((b == ST_OK) | (b == ST_DUP))
        st["c_exp"] = jnp.where(landed,
                                jnp.stack([v, v + 1000]), st["c_exp"])

        # the out-of-txn verify GET must see exactly the committed state
        p5 = res & (pos == 5)
        ctx.crash_if(p5 & ((b != ST_VAL) | (c != st["c_exp"][0])),
                     CRASH_VISIBILITY)

        st["c_rid"] = st["c_rid"] + res
        st["c_prog"] = jnp.where(res, ctx.now, st["c_prog"])

        # txn complete -> next txn (or done)
        fin = res & (st["c_rid"] >= OPS_PER_TXN)
        st["c_tid"] = st["c_tid"] + fin
        for k in ("c_sq", "c_rid", "c_dup0"):
            st[k] = jnp.where(fin, 0, st[k])
        st["c_done"] = jnp.where(fin & (st["c_tid"] > self.T), 1,
                                 st["c_done"])

    def on_message(self, ctx: Ctx, src, tag, payload):
        st = dict(ctx.state)
        _, _, rst = conn.on_message(ctx, st, src, tag, payload,
                                    epoch_guard=self.guard)
        # server reset (or refusal): back to square one, unless we're the
        # wrong-password client, for whom RST is the expected outcome
        if self.wrong:
            st["c_rej"] = jnp.where(rst, 1, st["c_rej"])
            st["c_done"] = jnp.where(rst, 1, st["c_done"])
        else:
            self._reset_session(ctx, st,
                                rst & (st["c_done"] == 0))
        vals, mask = stream.on_message(ctx, st, src, tag, payload,
                                       epoch_guard=self.guard)
        for i in stream.delivered_slots(mask):
            self._result(ctx, st, vals[i], mask[i] & (src == SERVER))
        ctx.state = st


def clients_done(n_nodes: int):
    def check(state):
        return (state.node_state["c_done"][1:n_nodes] == 1).all()
    return check


def make_minipg_runtime(n_clients=2, n_txns=4, scenario=None, cfg=None,
                        wrong_password=False, epoch_guard=True):
    from ..core.types import NetConfig, SimConfig, sec
    from ..runtime.runtime import Runtime
    n = 1 + n_clients
    n_keys = 2 * n_clients
    if cfg is None:
        cfg = SimConfig(n_nodes=n, event_capacity=64, payload_words=8,
                        time_limit=sec(10),
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(8)))
    spec = pg_state_spec(n, n_keys)
    server = PgServer(n, n_keys, epoch_guard=epoch_guard)
    client = PgClient(n_txns, wrong_password=wrong_password,
                      epoch_guard=epoch_guard)
    node_prog = np.asarray([0] + [1] * n_clients, np.int32)
    return Runtime(cfg, [server, client], spec, node_prog=node_prog,
                   scenario=scenario, persist=pg_persist_spec(spec),
                   halt_when=clients_done(n))
