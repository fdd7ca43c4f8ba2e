"""The connection-fault plane (r19): TCP-grade transport faults, peer
incarnations, and the exactly-once flagship under connection churn.

Load-bearing properties: (1) with every new fault at its zero default,
trajectories are BIT-IDENTICAL to r18 — enforced against per-leaf golden
digests captured at r18 HEAD (tests/_connfault_golden.py), chunked and
fused; (2) OP_RESET_PEER tears conn/stream state touching the target on
BOTH sides and bumps both incarnation epochs, where a kill deliberately
leaves the survivor half-open; (3) OP_SET_DUP redelivers dispatched
messages at the knob-plane rate, deterministically per seed; (4) the
incarnation guards reject stale RSTs, stale segments, and stale ACKs,
adopt missed resets, and make a post-reset retransmit timer a no-op —
each with the pre-r19 behavior compilable as the red control; (5) the
new ops round-trip through describe()/parse(); (6) the KnobPlan picks
the new dimensions up bounded with zero warm-campaign recompiles;
(7) minipg is green on the no-fault baseline AND under the reset+dup
storm with guards on, and measurably red with guards compiled to the
pre-r19 behavior; (8) pre-r19 checkpoints are rejected loudly
(simconfig-v6).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import (NODE_RANDOM, Ctx, KnobPlan, NetConfig, Program,
                        Runtime, Scenario, SimConfig, ms, sec)
from madsim_tpu.core import prng, types as T
from madsim_tpu.net import conn, stream

import _connfault_golden as golden


# ---------------------------------------------------------------------------
# 1. bit-identical-when-disabled, against r18 captured truth
# ---------------------------------------------------------------------------

class TestEquivalenceR18:
    @pytest.mark.parametrize("workload", sorted(golden.BUILDERS))
    def test_leaf_for_leaf_vs_r18_golden(self, workload):
        # scripts/capture_golden.py froze these digests AT r18 HEAD,
        # before any r19 engine change: every r18 leaf must still hash
        # identically, chunked and fused. The ONLY new leaf the plane
        # may add is dup_rate (gated by simconfig-v6); in particular the
        # dup decision/delay draws must consume nothing at rate 0 —
        # they ride keys folded off the already-consumed scheduler key.
        gold = golden.load_golden()[workload]
        got = golden.run_workload(workload)
        for runner in ("run", "run_fused"):
            missing = [k for k in gold[runner] if k not in got[runner]]
            assert not missing, (runner, missing)
            diff = [k for k in gold[runner]
                    if gold[runner][k] != got[runner][k]]
            assert not diff, (runner, diff)
            assert set(got[runner]) - set(gold[runner]) \
                == {".dup_rate",
                    ".sr_on", ".window_len", ".sr_dispatch", ".sr_busy",
                    ".sr_qhw", ".sr_drop", ".sr_dup", ".sr_complete",
                    ".sr_slo_miss", ".sr_lat", ".sr_fault",
                    ".sp_on", ".ev_span", ".sa_tail",
                    ".sa_bottleneck", ".tr_qw"}, \
                (runner, set(got[runner]) - set(gold[runner]))


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------

class _CountPing(Program):
    """Node 0 sends ONE message to node 1 at ms(2); node 1 counts every
    delivery — the duplicate-delivery plane's measurement bench."""

    def init(self, ctx: Ctx):
        ctx.set_timer(ms(2), 1, [0], when=ctx.node == 0)

    def on_timer(self, ctx: Ctx, tag, payload):
        ctx.send(1, 7, [0])

    def on_message(self, ctx: Ctx, src, tag, payload):
        st = dict(ctx.state)
        st["seen"] = st["seen"] + 1
        ctx.state = st


_COUNT_SPEC = dict(seen=jnp.asarray(0, jnp.int32))


def _count_rt(scenario=None, tlimit=sec(1)):
    cfg = SimConfig(n_nodes=2, time_limit=tlimit,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [_CountPing()], _COUNT_SPEC, scenario=scenario)


def _unit_ctx(n=2, payload_words=8):
    cfg = SimConfig(n_nodes=n, payload_words=payload_words)
    return Ctx(cfg, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
               prng.seed_key(7), {})


# ---------------------------------------------------------------------------
# 2. OP_SET_DUP — duplicate delivery at the datagram layer
# ---------------------------------------------------------------------------

class TestDupStorm:
    def _seen(self, rate):
        sc = Scenario()
        if rate:
            sc.at(500).set_dup(1, rate)
        rt = _count_rt(sc)
        fin = rt.run_fused(rt.init_batch(np.arange(64, dtype=np.uint32)),
                           4_000, 256)
        return np.asarray(fin.node_state["seen"])[:, 1]

    def test_zero_rate_is_exactly_once(self):
        assert (self._seen(0.0) == 1).all()

    def test_storm_redelivers_byte_identical_payload(self):
        seen = self._seen(0.8)
        assert (seen >= 1).all()
        assert (seen >= 2).any(), "a 0.8 dup rate must redeliver somewhere"
        # geometric storm: some lane should chain more than one copy
        assert seen.max() >= 3

    def test_rate_clipped_at_apply(self):
        sc = Scenario()
        sc.at(500).set_dup(1, 5.0)          # way past the cap
        rt = _count_rt(sc)
        st = rt.state_at(0, 4)
        assert int(np.asarray(st.dup_rate)[0][1]) == T.DUP_RATE_CAP

    def test_dup_replay_deterministic(self):
        sc = Scenario()
        sc.at(500).set_dup_random(0.7, among=[0, 1])
        rt = _count_rt(sc)
        assert rt.check_determinism(11, 4_000)


# ---------------------------------------------------------------------------
# 3. OP_RESET_PEER — both-sides teardown vs the kill's half-open
# ---------------------------------------------------------------------------

class TestResetPeer:
    def _final(self, reset: bool):
        from madsim_tpu.models.minipg import make_minipg_runtime
        sc = Scenario()
        if reset:
            sc.at(ms(400)).reset_peer(0)
        else:
            sc.at(ms(400)).kill(0)
        sc.at(ms(401)).halt()      # sample before watchdog recovery
        rt = make_minipg_runtime(n_clients=2, n_txns=50, scenario=sc)
        return rt.run_fused(rt.init_batch(np.arange(8, dtype=np.uint32)),
                            20_000, 512)

    def test_reset_tears_both_sides_and_bumps_epochs(self):
        fin = self._final(True)
        cn = np.asarray(fin.node_state["cn_state"])
        ep = np.asarray(fin.node_state["cn_epoch"])
        sx = np.asarray(fin.node_state["sx_seq"])
        assert (cn[:, 0, 1:] == conn.CLOSED).all()
        assert (cn[:, 1:, 0] == conn.CLOSED).all()
        assert (ep[:, 0, 1:] >= 1).all() and (ep[:, 1:, 0] >= 1).all()
        # stream sequence space RESTARTED on every touched pairing: the
        # server (quiescent between reset and halt) reads exactly 0; a
        # client may already have pushed the first send of the fresh
        # incarnation into the sampling window, so "restarted" there
        # means at most one post-wipe send — against the dozens of
        # frames 50 pipelined txns had in flight before the tear
        assert (sx[:, 0, 1:] == 0).all()
        assert (sx[:, 1:, 0] <= 1).all()

    def test_kill_leaves_survivors_half_open(self):
        fin = self._final(False)
        cn = np.asarray(fin.node_state["cn_state"])
        # the killed server's own rows reset at restart; the SURVIVORS
        # keep ESTABLISHED state toward the corpse — the half-open
        # regime only a reset clears (conn.py's documented contract)
        assert (cn[:, 1:, 0] == conn.ESTABLISHED).any()

    def test_inert_without_conn_state(self):
        # a model with no conn/stream leaves: the op resolves, dispatches
        # and does nothing — no crash, no oops
        sc = Scenario()
        sc.at(500).reset_peer_random()
        rt = _count_rt(sc)
        fin = rt.run_fused(rt.init_batch(np.arange(8, dtype=np.uint32)),
                           4_000, 256)
        assert not np.asarray(fin.crashed).any()
        assert (np.asarray(fin.node_state["seen"])[:, 1] == 1).all()


# ---------------------------------------------------------------------------
# 4. incarnation guards (unit level, both worlds' eager path)
# ---------------------------------------------------------------------------

def _established_pair():
    st = dict(**conn.conn_state(2), **stream.stream_state(2, window=4))
    st["cn_state"] = st["cn_state"].at[1].set(conn.ESTABLISHED)
    st["cn_epoch"] = st["cn_epoch"].at[1].set(3)
    st["st_epoch"] = st["st_epoch"].at[1].set(3)
    return st


class TestIncarnationGuards:
    def test_stale_rst_rejected(self):
        # satellite fix: a delayed RST from a pre-reset incarnation must
        # NOT close the successor connection
        ctx = _unit_ctx()
        st = _established_pair()
        conn.on_message(ctx, st, 1, conn.TAG_RST, jnp.asarray([2] + [0] * 7))
        assert int(st["cn_state"][1]) == conn.ESTABLISHED
        assert int(st["cn_epoch"][1]) == 3
        # the CURRENT incarnation's RST does tear it down (and bumps)
        _, _, rst = conn.on_message(ctx, st, 1, conn.TAG_RST,
                                    jnp.asarray([3] + [0] * 7))
        assert bool(rst) and int(st["cn_state"][1]) == conn.CLOSED
        assert int(st["cn_epoch"][1]) == 4

    def test_stale_rst_closes_without_guard(self):
        # the pre-r19 red control: ANY RST closes an ESTABLISHED conn
        ctx = _unit_ctx()
        st = _established_pair()
        _, _, rst = conn.on_message(ctx, st, 1, conn.TAG_RST,
                                    jnp.asarray([2] + [0] * 7),
                                    epoch_guard=False)
        assert bool(rst) and int(st["cn_state"][1]) == conn.CLOSED

    def test_stale_segment_dropped_fresh_adopted(self):
        ctx = _unit_ctx()
        st = _established_pair()

        def data(seq, ep, val):
            return jnp.asarray([seq, ep, val] + [0] * 5)

        # stale epoch: no buffer, no delivery, no ack, no window motion
        vals, mask = stream.on_message(ctx, st, 1, stream.TAG_DATA,
                                       data(0, 2, 41))
        assert not bool(mask.any())
        assert int(st["sr_next"][1]) == 0 and len(ctx._sends) == 0
        # current epoch: delivered + acked
        vals, mask = stream.on_message(ctx, st, 1, stream.TAG_DATA,
                                       data(0, 3, 42))
        assert bool(mask[0]) and int(vals[0]) == 42
        assert int(st["sr_next"][1]) == 1 and len(ctx._sends) == 1
        # NEWER epoch (a reset this side missed): adopt — wipe, jump,
        # deliver into the fresh window
        vals, mask = stream.on_message(ctx, st, 1, stream.TAG_DATA,
                                       data(0, 5, 43))
        assert bool(mask[0]) and int(vals[0]) == 43
        assert int(st["st_epoch"][1]) == 5 and int(st["sr_next"][1]) == 1

    def test_stale_segment_accepted_without_guard(self):
        ctx = _unit_ctx()
        st = _established_pair()
        vals, mask = stream.on_message(ctx, st, 1, stream.TAG_DATA,
                                       jnp.asarray([0, 2, 666] + [0] * 5),
                                       epoch_guard=False)
        # pre-r19: the stale segment lands in the fresh window — exactly
        # the corruption the flagship's red direction measures
        assert bool(mask[0]) and int(vals[0]) == 666

    def test_stale_ack_cannot_slide_window(self):
        ctx = _unit_ctx()
        st = _established_pair()
        stream.send(ctx, st, 1, 10)
        stream.send(ctx, st, 1, 11)
        assert int(st["sx_seq"][1]) == 2
        stream.on_message(ctx, st, 1, stream.TAG_ACK,
                          jnp.asarray([2, 2] + [0] * 6))   # stale epoch
        assert int(st["sx_base"][1]) == 0
        stream.on_message(ctx, st, 1, stream.TAG_ACK,
                          jnp.asarray([2, 3] + [0] * 6))   # current
        assert int(st["sx_base"][1]) == 2

    def test_retransmit_after_reset_is_noop(self):
        # satellite fix: a retransmit timer armed before reset_peer tore
        # the fabric must send NOTHING for the new incarnation
        ctx = _unit_ctx()
        st = _established_pair()
        stream.send(ctx, st, 1, 10)
        stream.send(ctx, st, 1, 11)
        n_before = len(ctx._sends)
        stream.reset_peer(st, 1)
        assert int(st["st_epoch"][1]) == 4
        stream.retransmit(ctx, st, 1, when=True)
        assert len(ctx._sends) == n_before, \
            "stale retransmit injected segments after reset_peer"
        # and frames the NEW incarnation does send stamp the new epoch
        stream.send(ctx, st, 1, 12)
        assert int(ctx._sends[-1]["payload"][1]) == 4

    def test_frames_stamp_current_epoch(self):
        ctx = _unit_ctx()
        st = _established_pair()
        stream.send(ctx, st, 1, 99)
        sent = ctx._sends[-1]["payload"]
        assert int(sent[0]) == 0 and int(sent[1]) == 3

    def test_duplicate_syn_does_not_reopen_window(self):
        # review finding (r19): a network-DUPLICATED SYN of the current
        # generation — exactly what OP_SET_DUP produces — must be a
        # true no-op: re-wiping the fabric at the same epoch would
        # reopen the receive window and deliver already-delivered
        # same-epoch segments AGAIN, breaking exactly-once with the
        # guards ON
        ctx = _unit_ctx()
        st = dict(**conn.conn_state(2), **stream.stream_state(2, window=4))
        conn.listen(ctx, st)
        syn = jnp.asarray([3] + [0] * 7)
        conn.on_message(ctx, st, 1, conn.TAG_SYN, syn)
        assert int(st["st_epoch"][1]) == 3
        data = jnp.asarray([0, 3, 42] + [0] * 5)
        vals, mask = stream.on_message(ctx, st, 1, stream.TAG_DATA, data)
        assert bool(mask[0]) and int(st["sr_next"][1]) == 1
        # the dup-storm redelivers the SYN: same epoch, no wipe
        conn.on_message(ctx, st, 1, conn.TAG_SYN, syn)
        assert int(st["sr_next"][1]) == 1, "duplicate SYN reopened window"
        # the peer's Go-Back-N retransmit of seq 0 must NOT deliver again
        vals, mask = stream.on_message(ctx, st, 1, stream.TAG_DATA, data)
        assert not bool(mask.any()), "same-epoch segment delivered twice"

    def test_handshake_negotiates_past_torn_generation(self):
        # listener side: a SYN proposing epoch 5 against a local counter
        # of 3 accepts at 5 and echoes it; the stream fabric re-bases
        ctx = _unit_ctx()
        st = _established_pair()
        conn.listen(ctx, st)
        accept, _, _ = conn.on_message(ctx, st, 1, conn.TAG_SYN,
                                       jnp.asarray([5] + [0] * 7))
        assert bool(accept)
        assert int(st["cn_epoch"][1]) == 5
        assert int(st["st_epoch"][1]) == 5
        syn_ack = ctx._sends[-1]
        assert int(syn_ack["tag"]) == conn.TAG_SYN_ACK
        assert int(syn_ack["payload"][0]) == 5


# ---------------------------------------------------------------------------
# 5. scenario round-trip (the script re-entry contract)
# ---------------------------------------------------------------------------

class TestScenarioRoundTrip:
    def test_describe_parse_identity_full_op_table(self):
        cfg = SimConfig(n_nodes=4, payload_words=8, time_limit=sec(2))
        sc = Scenario()
        sc.at(ms(1)).reset_peer(2)
        sc.at(ms(2)).reset_peer_random(among=[0, 1])
        sc.at(ms(3)).set_dup(1, 0.25)
        sc.at(ms(4)).set_dup_random(0.5, among=[2, 3])
        sc.at(ms(5)).set_skew(2, -300)
        sc.at(ms(6)).set_disk(1, ms(7), torn=True)
        sc.at(ms(7)).kill_random(among=[1, 2])
        sc.at(ms(8)).partition_oneway([0, 1], direction=1)
        sc.at(ms(9)).set_loss(0.1)
        sc.at(ms(10)).heal()
        sc.at(ms(11)).halt()
        text = sc.describe()
        re = Scenario.parse(text)
        assert re.describe() == text
        b1, b2 = sc.build(cfg), re.build(cfg)
        for k in b1:
            np.testing.assert_array_equal(b1[k], b2[k])

    def test_to_scenario_mutants_still_parse(self):
        import jax
        from madsim_tpu.models.workloads import connfault
        rt = connfault("mix", trace_cap=0)
        plan = KnobPlan.from_runtime(rt)
        text = plan.to_scenario(plan.base_knobs()).describe()
        assert "set_dup" in text and "reset_peer" in text
        assert Scenario.parse(text).describe() == text
        out, _, _ = plan.mutate(plan.base_batch(8), jax.random.PRNGKey(2),
                                havoc=8)
        for i in range(8):
            t2 = plan.to_scenario(KnobPlan.lane(out, i)).describe()
            assert Scenario.parse(t2).describe() == t2

    def test_recipe_class_is_conn_fault(self):
        from madsim_tpu.runtime import chaos
        from madsim_tpu.runtime.scenario import row_recipe_class
        assert row_recipe_class(T.OP_RESET_PEER) == "conn_fault"
        assert row_recipe_class(T.OP_SET_DUP) == "conn_fault"
        sc = chaos.retransmit_storm(ms(5), 0.3, ms(500), node=0)
        sc = chaos.slow_disk(ms(10), ms(5), ms(400), node=0, sc=sc)
        # conn_fault outranks the gray families by precedence
        assert sc.recipe_class() == "conn_fault"


# ---------------------------------------------------------------------------
# 6. fuzzer knob plane
# ---------------------------------------------------------------------------

class TestKnobPlan:
    def test_bounds_and_pools(self):
        import jax
        from madsim_tpu.models.workloads import connfault
        rt = connfault("mix", trace_cap=0)
        plan = KnobPlan.from_runtime(rt)
        dup_rows = plan.base["op"] == T.OP_SET_DUP
        rp_rows = plan.base["op"] == T.OP_RESET_PEER
        assert dup_rows.sum() >= 3 and rp_rows.sum() >= 5
        assert plan.val_ok[dup_rows].all()
        assert (plan.val_hi[dup_rows] == T.DUP_RATE_CAP).all()
        assert plan.node_ok[rp_rows].all()
        out, hist, _ = plan.mutate(plan.base_batch(64),
                                   jax.random.PRNGKey(0), havoc=6)
        rv = np.asarray(out["row_val"])
        assert (rv[:, plan.val_ok] >= plan.val_lo[plan.val_ok]).all()
        assert (rv[:, plan.val_ok] <= plan.val_hi[plan.val_ok]).all()
        assert int(hist[-1]) > 0, "fault_perturb never applied"

    def test_apply_clips_hand_edited_rate(self):
        from madsim_tpu.models.workloads import connfault
        rt = connfault("mix", trace_cap=0)
        plan = KnobPlan.from_runtime(rt)
        kn = plan.base_knobs()
        kn["row_val"] = np.full(plan.R, 10**9, np.int32)
        state = plan.apply(rt.init_batch(np.arange(2, dtype=np.uint32)),
                           KnobPlan.stack([kn] * 2))
        pay = np.asarray(state.t_payload)[0]
        P = rt.cfg.payload_words
        rows = slice(plan.n_init, plan.n_init + plan.R)
        dup_rows = plan.base["op"] == T.OP_SET_DUP
        assert (pay[rows, P - 1][dup_rows] <= T.DUP_RATE_CAP).all()

    def test_warm_campaign_never_recompiles(self):
        # the TestCompileDiscipline pattern over the NEW knob rows: a
        # warm fuzz campaign whose scenario carries reset_peer/set_dup
        # rows must add ZERO traces — mutation stays operand traffic
        from madsim_tpu import fuzz
        from madsim_tpu.compile.cache import COMPILE_LOG
        from madsim_tpu.models.workloads import connfault
        kw = dict(max_steps=2_000, batch=16, max_rounds=3, dry_rounds=4,
                  chunk=256)
        fuzz(connfault("mix", trace_cap=0), **kw)
        before = COMPILE_LOG.snapshot()["traces_total"]
        fuzz(connfault("mix", trace_cap=0), **kw)
        after = COMPILE_LOG.snapshot()["traces_total"]
        assert after == before, COMPILE_LOG.recent(8)


# ---------------------------------------------------------------------------
# 7. the exactly-once flagship under connection churn
# ---------------------------------------------------------------------------

class TestFlagship:
    def test_green_no_fault_baseline(self):
        from madsim_tpu.models.minipg import make_minipg_runtime
        rt = make_minipg_runtime(n_clients=2, n_txns=4)
        fin = rt.run_fused(
            rt.init_batch(np.arange(48, dtype=np.uint32)), 60_000, 512)
        done = np.asarray(fin.node_state["c_done"])[:, 1:]
        assert (done == 1).all()
        assert not np.asarray(fin.crashed).any()

    def test_green_under_churn_with_guards(self):
        from madsim_tpu.models.workloads import connfault
        rt = connfault("mix", guard=True)
        fin = rt.run_fused(
            rt.init_batch(np.arange(48, dtype=np.uint32)), 120_000, 512)
        done = np.asarray(fin.node_state["c_done"])[:, 1:]
        assert (done == 1).all()
        assert not np.asarray(fin.crashed).any()

    def test_red_without_guards(self):
        from madsim_tpu.models.workloads import connfault
        rt = connfault("mix")      # guards OFF
        fin = rt.run_fused(
            rt.init_batch(np.arange(48, dtype=np.uint32)), 120_000, 512)
        crashed = np.asarray(fin.crashed)
        assert crashed.any(), \
            "pre-r19 transport must corrupt under the reset+dup storm"
        # the observed failure is stale-segment corruption surfacing
        # through the client's own oracles, not an engine artifact
        codes = np.asarray(fin.crash_code)[crashed]
        assert (codes > 0).any(), codes

    @pytest.mark.slow
    def test_red_lane_replays_by_seed(self):
        from madsim_tpu.models.workloads import connfault
        rt = connfault("mix")                          # guards OFF
        fin = rt.run_fused(
            rt.init_batch(np.arange(48, dtype=np.uint32)), 120_000, 512)
        lane = int(np.nonzero(np.asarray(fin.crashed))[0][0])
        rep = rt.run_fused(rt.init_batch(np.asarray([lane], np.uint32)),
                           120_000, 512)
        assert int(np.asarray(rep.crash_code)[0]) \
            == int(np.asarray(fin.crash_code)[lane])
        assert int(rt.fingerprints(rep)[0]) \
            == int(rt.fingerprints(fin)[lane])

    @pytest.mark.slow
    def test_red_opens_replaying_causal_bucket(self):
        import shutil
        import tempfile
        from madsim_tpu.models.workloads import connfault
        from madsim_tpu import fuzz, replay_bucket
        tmp = tempfile.mkdtemp(prefix="connfault_bucket_")
        try:
            rt = connfault("mix")
            res = fuzz(rt, max_steps=30_000, batch=64, max_rounds=3,
                       dry_rounds=4, chunk=512, corpus_dir=tmp)
            assert res["buckets_total"] >= 1, res
            opened = res["buckets_opened"]
            assert opened
            crashed, code, _ = replay_bucket(rt, tmp, opened[0], 30_000)
            assert crashed, (opened[0], code)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    @pytest.mark.slow
    def test_recovery_recipes_green_with_guards(self):
        from madsim_tpu.models.workloads import connfault
        for recipe in ("reset", "dup", "half"):
            rt = connfault(recipe)
            fin = rt.run_fused(
                rt.init_batch(np.arange(48, dtype=np.uint32)),
                120_000, 512)
            done = np.asarray(fin.node_state["c_done"])[:, 1:]
            assert (done == 1).all(), recipe
            assert not np.asarray(fin.crashed).any(), recipe


# ---------------------------------------------------------------------------
# 8. migration: pre-r19 checkpoints are rejected
# ---------------------------------------------------------------------------

class TestCheckpointMigration:
    def test_pre_r19_checkpoint_rejected_by_leaf_count(self, tmp_path):
        from madsim_tpu.runtime import checkpoint
        rt = _count_rt()
        st = rt.init_batch(np.arange(2))
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, st)
        with np.load(p) as z:
            leaves = {k: z[k] for k in z.files}
        n = len([k for k in leaves if k.startswith("leaf_")])
        stripped = {k: v for k, v in leaves.items()
                    if not k.startswith("leaf_")}
        for i in range(n - 1):       # drop one leaf: the r19 dup_rate
            stripped[f"leaf_{i}"] = leaves[f"leaf_{i}"]
        p2 = str(tmp_path / "old.npz")
        np.savez_compressed(p2, **stripped)
        with pytest.raises(ValueError, match="leaves"):
            checkpoint.load(p2, st)

    def test_signature_is_current(self):
        # v6 (r19) was bumped to v7 by the r21 windowed-telemetry
        # plane and to v8 by the r23 attribution plane —
        # test_spans.py owns the authoritative assertion
        cfg = SimConfig(n_nodes=2)
        assert cfg.structural_signature()[0] == "simconfig-v8"
