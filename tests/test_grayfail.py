"""The gray-failure fault plane (r17): asymmetric partitions, per-node
clock skew, slow-disk/torn-write faults, and the Percolator-lite
flagship they break.

Load-bearing properties: (1) with every new fault at its zero default,
trajectories are BIT-IDENTICAL to r16 — enforced against per-leaf golden
digests captured at r16 HEAD (tests/_grayfail_golden.py), chunked and
fused; (2) a one-way cut is directional and composes (two opposite cuts
= a full partition; only HEAL clears them); (3) skew is a deterministic
clock-RATE lever — observed `ctx.now` drifts, timer delays stretch
inversely, replay is exact; (4) slow-disk delays every emission of the
node, torn-write kills flush a random PREFIX of the unsynced tail
(synced words never tear); (5) the new ops round-trip through
describe()/parse() — the script re-entry contract; (6) the KnobPlan
picks the new dimensions up bounded (skew clipped, values bounded per
row, direction one bit, pools still confine targets); (7) Percolator-
lite is green with no faults and each gray recipe flips its
snapshot-isolation oracle red; (8) pre-r17 checkpoints are rejected
loudly (simconfig-v5).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import (NODE_RANDOM, Ctx, KnobPlan, NetConfig, Program,
                        Runtime, Scenario, SimConfig, ms, sec)
from madsim_tpu.core import types as T
from madsim_tpu.harness.simtest import SimFailure, run_seeds

import _grayfail_golden as golden


# ---------------------------------------------------------------------------
# 1. bit-identical-when-disabled, against r16 captured truth
# ---------------------------------------------------------------------------

class TestEquivalenceR16:
    @pytest.mark.parametrize("workload", sorted(golden.BUILDERS))
    def test_leaf_for_leaf_vs_r16_golden(self, workload):
        # scripts/capture_golden.py froze these digests AT r16 HEAD,
        # before any r17 engine change: every r16 leaf must still hash
        # identically, chunked and fused. New leaves are allowed only by
        # name: r17's gray-failure plane (skew/disk_lat/torn, gated by
        # simconfig-v5), r18's hash_base (the frozen seed key — a
        # constant that consumes nothing, which is exactly why every
        # OTHER leaf must still match r16 bit for bit), r19's
        # dup_rate (connection-fault plane, simconfig-v6 — its own
        # golden gate lives in tests/test_connfault.py vs r18 truth),
        # r21's windowed-telemetry plane (sr_*/window_len,
        # simconfig-v7 — zero-size columns here since series_windows=0;
        # its own golden gate lives in tests/test_series.py vs r20
        # truth), and r23's attribution plane (sp_on/ev_span/sa_*/tr_qw,
        # simconfig-v8 — zero-size here since span_attr is off; its own
        # golden gate lives in tests/test_spans.py vs r22 truth).
        gold = golden.load_golden()[workload]
        got = golden.run_workload(workload)
        for runner in ("run", "run_fused"):
            missing = [k for k in gold[runner] if k not in got[runner]]
            assert not missing, (runner, missing)
            diff = [k for k in gold[runner]
                    if gold[runner][k] != got[runner][k]]
            assert not diff, (runner, diff)
            new = set(got[runner]) - set(gold[runner])
            assert new == {".skew", ".disk_lat", ".torn",
                           ".hash_base", ".dup_rate",
                           ".sr_on", ".window_len", ".sr_dispatch",
                           ".sr_busy", ".sr_qhw", ".sr_drop", ".sr_dup",
                           ".sr_complete", ".sr_slo_miss", ".sr_lat",
                           ".sr_fault",
                           ".sp_on", ".ev_span", ".sa_tail",
                           ".sa_bottleneck", ".tr_qw"}, new


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------

_PROBE_SPEC = dict(seen=jnp.asarray(0, jnp.int32),
                   fires=jnp.asarray(0, jnp.int32))


class _TimerProbe(Program):
    """Every node re-arms a fixed-delay timer and records the observed
    ctx.now of its last firing — the skew plane's measurement bench."""

    def __init__(self, period=ms(100), fires=8):
        self.period = period
        self.max_fires = fires

    def init(self, ctx: Ctx):
        ctx.set_timer(self.period, 1, [0])

    def on_timer(self, ctx: Ctx, tag, payload):
        st = dict(ctx.state)
        st["seen"] = ctx.now
        st["fires"] = st["fires"] + 1
        ctx.set_timer(self.period, 1, [0], when=st["fires"] < self.max_fires)
        ctx.state = st


class _EchoProbe(Program):
    """Node 0 messages node 1 at boot; receivers record arrival time —
    the slow-disk plane's measurement bench."""

    def init(self, ctx: Ctx):
        ctx.send(1, 1, [0], when=ctx.node == 0)

    def on_message(self, ctx: Ctx, src, tag, payload):
        st = dict(ctx.state)
        st["seen"] = ctx.now
        ctx.state = st


def _probe_rt(prog, n=2, scenario=None, lat=ms(1), tlimit=sec(5)):
    cfg = SimConfig(n_nodes=n, time_limit=tlimit,
                    net=NetConfig(send_latency_min=lat,
                                  send_latency_max=lat))
    return Runtime(cfg, [prog], _PROBE_SPEC, scenario=scenario)


# ---------------------------------------------------------------------------
# 2. one-way partitions
# ---------------------------------------------------------------------------

class TestOneWayPartition:
    def _final_clog(self, sc, steps=60):
        rt = _probe_rt(_TimerProbe(fires=100), n=4, scenario=sc)
        return np.asarray(rt.state_at(0, steps).clog_link)[0]

    def test_directional_and_composes(self):
        sc = Scenario()
        sc.at(ms(10)).partition_oneway([0, 1], direction=0)
        cl = self._final_clog(sc)
        # A -> not-A cut, nothing else: rows 0/1 to cols 2/3 only
        want = np.zeros((4, 4), bool)
        want[np.ix_([0, 1], [2, 3])] = True
        np.testing.assert_array_equal(cl, want)
        # the reverse direction is the transpose
        sc = Scenario()
        sc.at(ms(10)).partition_oneway([0, 1], direction=1)
        np.testing.assert_array_equal(self._final_clog(sc), want.T)
        # two opposite one-way cuts COMPOSE into the full partition
        sc = Scenario()
        sc.at(ms(10)).partition_oneway([0, 1], direction=0)
        sc.at(ms(20)).partition_oneway([0, 1], direction=1)
        np.testing.assert_array_equal(self._final_clog(sc), want | want.T)

    @pytest.mark.parametrize("direction,rumors_leave", [(0, False),
                                                        (1, True)])
    def test_gossip_sees_the_cut_from_one_side(self, direction,
                                               rumors_leave):
        # a protocol-level view of the cut around node 0: outbound
        # (direction 0) no rumor ever leaves node 0; inbound (1) node 0
        # hears nothing but its sends flow, so dissemination completes
        from madsim_tpu.models.gossip import make_gossip_runtime
        sc = Scenario()
        sc.at(0).partition_oneway([0], direction=direction)
        cfg = SimConfig(n_nodes=6, event_capacity=192, time_limit=sec(2))
        rt = make_gossip_runtime(n_nodes=6, n_rumors=4, scenario=sc,
                                 cfg=cfg)
        fin = rt.run_fused(rt.init_batch(np.arange(8, dtype=np.uint32)),
                           6_000, 256)
        have = np.asarray(fin.node_state["have"])     # rumor bitmask
        if rumors_leave:
            assert (have == (1 << 4) - 1).all()       # all four, everywhere
        else:
            assert (have[:, 1:] == 0).all()

    def test_heal_clears_oneway_cuts(self):
        sc = Scenario()
        sc.at(ms(10)).partition_oneway([0, 1], direction=0)
        sc.at(ms(30)).heal()
        assert not self._final_clog(sc, steps=120).any()

    def test_oneway_cut_drops_only_cut_direction(self):
        # node 0's send to 1 vanishes under an outbound cut of {0}, but
        # 1's sends still arrive at 0 (echo both ways)
        class Both(Program):
            def init(self, ctx):
                ctx.send(1 - ctx.node, 1, [0])

            def on_message(self, ctx, src, tag, payload):
                st = dict(ctx.state)
                st["seen"] = 1 + ctx.now
                ctx.state = st

        sc = Scenario()
        sc.at(0).partition_oneway([0], direction=0)
        rt = _probe_rt(Both(), n=2, scenario=sc, tlimit=sec(1))
        fin = rt.run_fused(rt.init_batch(np.arange(8)), 200, 64)
        seen = np.asarray(fin.node_state["seen"])
        # whether the cut fires before the boots is a t=0 tie-break, so
        # assert per lane: node 1 hearing from 0 implies the cut came
        # too late for that lane — but node 0 must ALWAYS hear node 1
        assert (seen[:, 0] > 0).all(), "inbound direction must stay alive"
        assert (seen[:, 1] == 0).any(), "outbound cut must drop sends"


# ---------------------------------------------------------------------------
# 3. clock skew
# ---------------------------------------------------------------------------

class TestClockSkew:
    def test_timer_stretch_and_observed_drift(self):
        sc = Scenario()
        sc.at(0).set_skew(1, 512)        # node 1's clock runs 1.5x
        rt = _probe_rt(_TimerProbe(), scenario=sc)
        st = rt.state_at(3, 40)
        fires = np.asarray(st.node_state["fires"])[0]
        seen = np.asarray(st.node_state["seen"])[0]
        assert fires.tolist() == [8, 8]
        # node 0: 8 unstretched 100ms periods, observed = global
        assert seen[0] == 8 * ms(100)
        # node 1: its timers fire EARLIER in global time (d_eff = 50ms
        # once the skew op landed) and it OBSERVES a 1.5x clock. The
        # t=0 tie-break decides whether the FIRST period was stretched,
        # so its last fire lands at global 400ms (all 8 stretched) or
        # 450ms (first one full) — observed through the 1.5x clock:
        assert seen[1] < seen[0]
        assert int(seen[1]) in (600_000, 675_000)

    def test_skew_value_clipped_at_apply(self):
        sc = Scenario()
        sc.at(0).set_skew(0, 10_000)     # way past SKEW_CAP
        rt = _probe_rt(_TimerProbe(), scenario=sc)
        st = rt.state_at(0, 4)
        assert int(np.asarray(st.skew)[0][0]) == T.SKEW_CAP

    def test_skew_replay_deterministic(self):
        sc = Scenario()
        sc.at(ms(5)).set_skew_random(300, among=[0, 1])
        sc.at(ms(400)).set_skew_random(0, among=[0, 1])
        rt = _probe_rt(_TimerProbe(), scenario=sc)
        assert rt.check_determinism(9, 2_000)


# ---------------------------------------------------------------------------
# 4. disk faults
# ---------------------------------------------------------------------------

class TestDiskFaults:
    def test_slow_disk_delays_emissions(self):
        # arm the disk fault at boot via a deferred send: node 0 pings
        # at init; with set_disk(0) racing the boot at t=0 the delta is
        # either the full disk latency or 0 — inject at a quiet instant
        # instead: scenario op at t=0, probe send re-armed at ms(50)
        class LatePing(Program):
            def init(self, ctx):
                ctx.set_timer(ms(50), 1, [0], when=ctx.node == 0)

            def on_timer(self, ctx, tag, payload):
                ctx.send(1, 2, [0])

            def on_message(self, ctx, src, tag, payload):
                st = dict(ctx.state)
                st["seen"] = ctx.now
                ctx.state = st

        def arrival(disk_lat):
            sc = Scenario()
            if disk_lat:
                sc.at(ms(1)).set_disk(0, disk_lat)
            rt = _probe_rt(LatePing(), scenario=sc, tlimit=sec(1))
            st = rt.state_at(1, 20)
            return int(np.asarray(st.node_state["seen"])[0][1])

        base = arrival(0)
        slow = arrival(ms(40))
        assert slow - base == ms(40)

    def test_torn_kill_flushes_random_prefix(self):
        # wal_kv with sync_wal=False: nothing is ever synced, so a
        # CLEAN kill leaves dlen == 0 everywhere; a TORN kill flushes a
        # random prefix of the unsynced tail — including mid-record
        # (odd) cuts, the partially-written final record
        from madsim_tpu.models.wal_kv import SERVER, make_wal_kv_runtime

        def final_dlen(torn):
            sc = Scenario()
            sc.at(500).set_disk(SERVER, 0, torn=torn)
            sc.at(ms(60)).kill(SERVER)
            sc.at(ms(120)).restart(SERVER)
            rt = make_wal_kv_runtime(n_clients=2, n_ops=8, wal_cap=64,
                                     sync_wal=False, scenario=sc)
            fin = rt.run_fused(
                rt.init_batch(np.arange(64, dtype=np.uint32)),
                40_000, 512)
            return np.asarray(fin.node_state["fs_dlen"])[:, SERVER, 0]

        clean = final_dlen(False)
        assert (clean == 0).all()
        torn = final_dlen(True)
        assert (torn > 0).any(), "torn kill must flush some prefix"
        assert (torn % 2 == 1).any(), "some cuts must land mid-record"

    def test_disk_value_clipped_and_pooled(self):
        sc = Scenario()
        sc.at(0).set_disk_random(10 * T.DISK_LAT_CAP, among=[1])
        rt = _probe_rt(_TimerProbe(), scenario=sc)
        st = rt.state_at(0, 4)
        dl = np.asarray(st.disk_lat)[0]
        assert dl[0] == 0 and dl[1] == T.DISK_LAT_CAP


# ---------------------------------------------------------------------------
# 5. scenario round-trip (the script re-entry contract)
# ---------------------------------------------------------------------------

class TestScenarioRoundTrip:
    def test_describe_parse_identity_all_ops(self):
        cfg = SimConfig(n_nodes=4, payload_words=8, time_limit=sec(2))
        sc = Scenario()
        sc.at(ms(1)).kill_random(among=[1, 2])
        sc.at(ms(2)).partition_oneway([0, 1], direction=1)
        sc.at(ms(3)).set_skew(2, -300)
        sc.at(ms(4)).set_skew_random(128, among=[0, 3])
        sc.at(ms(5)).set_disk(1, ms(7), torn=True)
        sc.at(ms(6)).set_disk_random(0, among=[1])
        sc.at(ms(7)).set_loss(0.1)
        sc.at(ms(8)).set_latency(ms(1), ms(9))
        sc.at(ms(9)).clog_link(1, 2)
        sc.at(ms(10)).partition([2, 3])
        sc.at(ms(11)).heal()
        sc.at(ms(12)).boot(3)
        sc.at(ms(13)).restart_random()
        sc.at(ms(14)).pause(2)
        sc.at(ms(15)).clog_node_random()
        sc.at(ms(16)).halt()
        text = sc.describe()
        re = Scenario.parse(text)
        # text-level identity AND row-level identity: the re-entered
        # script must ENCODE the identical event-table rows
        assert re.describe() == text
        b1, b2 = sc.build(cfg), re.build(cfg)
        for k in b1:
            np.testing.assert_array_equal(b1[k], b2[k])

    def test_to_scenario_random_value_rows_round_trip(self):
        # KnobPlan.to_scenario bakes values into the FULL payload (no
        # payload_tail); describe() must not bit-decode them as phantom
        # pool members, and the script must still re-enter (the review
        # finding: a skew of -300 at word P-1 read as dozens of pool ids)
        import jax
        from madsim_tpu.models.percolator import make_percolator_runtime
        sc = Scenario()
        sc.at(ms(5)).set_skew_random(-300, among=[0, 1])
        sc.at(ms(6)).set_disk_random(ms(9), torn=True, among=[1])
        rt = make_percolator_runtime(scenario=sc)
        plan = KnobPlan.from_runtime(rt)
        kn = plan.base_knobs()
        text = plan.to_scenario(kn).describe()
        assert "random among [0, 1] skew=-300" in text
        assert "random among [1] lat=9000us torn=1" in text
        re = Scenario.parse(text)
        assert re.describe() == text
        # and a mutated vector still parses (values move, pools don't)
        out, _, _ = plan.mutate(plan.base_batch(8), jax.random.PRNGKey(2),
                                havoc=8)
        for i in range(8):
            t2 = plan.to_scenario(KnobPlan.lane(out, i)).describe()
            assert Scenario.parse(t2).describe() == t2

    def test_value_overlapping_pool_segment_refused(self):
        # N > 31 with a tight payload: the tail value word would land
        # inside the pool segment and bit-decode as phantom members —
        # build() refuses instead of mistargeting
        cfg = SimConfig(n_nodes=40, payload_words=2, time_limit=sec(2))
        sc = Scenario()
        sc.at(ms(1)).set_skew_random(100, among=[1])
        with pytest.raises(ValueError, match="pool segment"):
            sc.build(cfg)

    def test_value_and_pool_coexist_in_payload(self):
        cfg = SimConfig(n_nodes=4, payload_words=8, time_limit=sec(2))
        sc = Scenario()
        sc.at(ms(1)).set_skew_random(-77, among=[1, 3])
        rows = sc.build(cfg)
        assert rows["payload"][0, 0] == (1 << 1) | (1 << 3)   # pool head
        assert rows["payload"][0, 7] == -77                   # value tail

    def test_tail_overflow_raises(self):
        cfg = SimConfig(n_nodes=2, payload_words=1, time_limit=sec(2))
        sc = Scenario()
        sc.at(ms(1)).set_disk(0, ms(5), torn=True)   # needs 2 tail words
        with pytest.raises(ValueError, match="payload words"):
            sc.build(cfg)


# ---------------------------------------------------------------------------
# 6. fuzzer knob plane
# ---------------------------------------------------------------------------

def _gray_rt():
    from madsim_tpu.models.workloads import grayfail
    return grayfail("mix", trace_cap=0, n_ops=8)


class TestKnobPlan:
    def test_guards_and_bounds(self):
        import jax
        rt = _gray_rt()
        plan = KnobPlan.from_runtime(rt)
        assert plan.val_ok.sum() >= 6          # skew x4 + disk x4 rows
        assert plan.dir_ok.sum() == 1
        assert plan.torn_ok.sum() >= 2
        # base knobs read the encoded values back
        kb = plan.base_knobs()
        assert (np.abs(kb["row_val"][plan.val_ok])
                <= np.maximum(T.SKEW_CAP, T.DISK_LAT_CAP)).all()
        # mutants stay in bounds and the new operator actually runs
        out, hist, _ = plan.mutate(plan.base_batch(64),
                                   jax.random.PRNGKey(0), havoc=6)
        assert int(hist[-1]) > 0, "fault_perturb never applied"
        rv = np.asarray(out["row_val"])
        assert (rv[:, plan.val_ok] >= plan.val_lo[plan.val_ok]).all()
        assert (rv[:, plan.val_ok] <= plan.val_hi[plan.val_ok]).all()
        assert set(np.asarray(out["row_flag"]).ravel().tolist()) <= {0, 1}

    def test_apply_clips_hand_edited_values(self):
        rt = _gray_rt()
        plan = KnobPlan.from_runtime(rt)
        kn = plan.base_knobs()
        kn["row_val"] = np.full(plan.R, 10**9, np.int32)   # way out
        kn["row_flag"] = np.full(plan.R, 7, np.int32)      # not a bit
        state = plan.apply(rt.init_batch(np.arange(2, dtype=np.uint32)),
                           KnobPlan.stack([kn] * 2))
        pay = np.asarray(state.t_payload)[0]
        P = rt.cfg.payload_words
        rows = slice(plan.n_init, plan.n_init + plan.R)
        vals = pay[rows, P - 1][plan.val_ok]
        assert (vals <= plan.val_hi[plan.val_ok]).all()
        src = np.asarray(state.t_src)[0][rows][plan.dir_ok]
        assert set(src.tolist()) <= {0, 1}

    def test_pool_confinement_still_holds(self):
        # an out-of-pool target on a pool-restricted fault row snaps
        # back to NODE_RANDOM (the r9 contract, extended to the new ops)
        from madsim_tpu.models.percolator import make_percolator_runtime
        sc = Scenario()
        sc.at(ms(5)).set_skew_random(200, among=[0, 1])
        rt = make_percolator_runtime(scenario=sc)
        plan = KnobPlan.from_runtime(rt)
        kn = plan.base_knobs()
        r = int(np.nonzero(plan.base["op"] == T.OP_SET_SKEW)[0][0])
        kn["row_node"] = kn["row_node"].copy()
        kn["row_node"][r] = 3                  # a client — out of pool
        state = plan.apply(rt.init_batch(np.arange(1, dtype=np.uint32)),
                           KnobPlan.stack([kn]))
        tnode = np.asarray(state.t_node)[0][plan.n_init + r]
        assert tnode == NODE_RANDOM


# ---------------------------------------------------------------------------
# 7. the Percolator-lite flagship
# ---------------------------------------------------------------------------

class TestPercolator:
    def test_green_without_faults(self):
        from madsim_tpu.models.percolator import make_percolator_runtime
        rt = make_percolator_runtime()
        state = run_seeds(rt, np.arange(24), max_steps=60_000)
        done = np.asarray(state.node_state["c_done"])[:, 2:]
        assert (done == 1).all()

    def test_slow_disk_recipe_fractures_snapshots(self):
        from madsim_tpu.models.percolator import (CRASH_SNAPSHOT,
                                                  make_percolator_runtime)
        from madsim_tpu.runtime import chaos
        sc = chaos.slow_disk(ms(100), ms(20), ms(700), node=0)
        rt = make_percolator_runtime(n_ops=12, scenario=sc)
        with pytest.raises(SimFailure) as ei:
            run_seeds(rt, np.arange(32), max_steps=80_000)
        assert ei.value.code == CRASH_SNAPSHOT

    @pytest.mark.slow
    def test_skew_crash_replays_by_seed(self):
        # skewed lease expiry crashes the snapshot oracle, and the red
        # lane's seed replays alone to the same code and fingerprint
        from madsim_tpu.models.percolator import CRASH_SNAPSHOT
        from madsim_tpu.models.workloads import grayfail
        rt = grayfail("skew")
        fin = rt.run_fused(rt.init_batch(np.arange(192, dtype=np.uint32)),
                           80_000, 512)
        lanes = np.nonzero(np.asarray(fin.crash_code) == CRASH_SNAPSHOT)[0]
        assert lanes.size > 0
        lane = int(lanes[0])
        rep = rt.run_fused(rt.init_batch(np.asarray([lane], np.uint32)),
                           80_000, 512)
        assert int(np.asarray(rep.crash_code)[0]) == CRASH_SNAPSHOT
        assert int(rt.fingerprints(rep)[0]) \
            == int(rt.fingerprints(fin)[lane])

    @pytest.mark.slow
    def test_torn_campaign_opens_replaying_buckets(self, tmp_path):
        from madsim_tpu import fuzz, replay_bucket
        from madsim_tpu.models.workloads import grayfail
        rt = grayfail("torn")
        d = str(tmp_path / "torn")
        res = fuzz(rt, max_steps=40_000, batch=64, max_rounds=3,
                   dry_rounds=4, chunk=512, corpus_dir=d)
        assert res["buckets_total"] >= 1, res
        for key in res["buckets_opened"]:
            crashed, code, _ = replay_bucket(rt, d, key, 40_000)
            assert crashed, (key, code)

    @pytest.mark.slow
    def test_every_gray_recipe_goes_red(self):
        from madsim_tpu.models.percolator import CRASH_SNAPSHOT
        from madsim_tpu.models.workloads import grayfail
        for recipe in ("skew", "asym", "disk", "torn"):
            rt = grayfail(recipe, trace_cap=0)
            fin = rt.run_fused(
                rt.init_batch(np.arange(192, dtype=np.uint32)),
                80_000, 512)
            codes = np.asarray(fin.crash_code)
            assert (codes == CRASH_SNAPSHOT).any(), recipe


# ---------------------------------------------------------------------------
# 8. migration: pre-r17 checkpoints are rejected
# ---------------------------------------------------------------------------

class TestCheckpointMigration:
    def test_pre_r17_checkpoint_rejected_by_leaf_count(self, tmp_path):
        # the MIGRATION r17 contract: a pre-r17 checkpoint (no skew/
        # disk_lat/torn leaves — 3 fewer) fails load() loudly on the
        # leaf count, not by silent misalignment
        from madsim_tpu.runtime import checkpoint
        rt = _probe_rt(_TimerProbe())
        st = rt.init_batch(np.arange(2))
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, st)
        with np.load(p) as z:
            leaves = {k: z[k] for k in z.files}
        n = len([k for k in leaves if k.startswith("leaf_")])
        stripped = {k: v for k, v in leaves.items()
                    if not k.startswith("leaf_")}
        for i in range(n - 3):
            stripped[f"leaf_{i}"] = leaves[f"leaf_{i}"]
        p2 = str(tmp_path / "old.npz")
        np.savez_compressed(p2, **stripped)
        with pytest.raises(ValueError, match="leaves"):
            checkpoint.load(p2, st)

    def test_signature_is_current(self):
        # r17 introduced v5; the r19 connection-fault plane bumped it to
        # v6, the r21 windowed-telemetry plane to v7, and the r23
        # attribution plane to v8 — test_spans.py owns the
        # authoritative version assertion
        cfg = SimConfig(n_nodes=2)
        assert cfg.structural_signature()[0] == "simconfig-v8"
