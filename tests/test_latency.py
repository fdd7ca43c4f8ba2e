"""SLO latency plane (DESIGN §17): histograms as pure observers, SLO as
a crash code.

The load-bearing properties: (1) the plane is an observation lever —
trajectories are bit-identical leaf-for-leaf with it on, off, compiled
out, or lane-masked, and the lh_*/ev_root_t columns are excluded from
fingerprints; (2) the sojourn histogram equals a host replay of the
step's own rule (now − earliest eligible deadline) and the e2e
histogram equals a parent-walk of the flight-recorder ring (the
root-inheritance rule, end to end); (3) buckets SATURATE; (4) quantile
estimates are exact bucket-CDF lower bounds; (5) `slo_invariant` fires
deterministically with CRASH_SLO, replays by seed, and buckets next to
ordinary crashes; (6) the fuzzer's lat_bonus scales admission energy
and fuzz rounds carry the latency fields; (7) pre-r16 checkpoints are
rejected loudly.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import (CRASH_SLO, JsonlObserver, NetConfig, Runtime,
                        Scenario, SimConfig, ms, sec, slo_invariant,
                        summarize)
from madsim_tpu.core.state import TRACE_FIELDS
from madsim_tpu.core.types import EV_MSG, EV_SUPER, EV_TIMER
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.obs import (format_latency, latency_histogram_rows,
                            latency_summary, ring_records)
from madsim_tpu.parallel.stats import (lane_e2e_p99, latency_bucket_edges,
                                       latency_counters, latency_digest)

I32_MAX = 2**31 - 1
TAG_PING = 1        # pingpong's ping message tag (models/pingpong.py)


def _pingpong_rt(lat=24, target=6, n_nodes=2, scenario=None, loss=0.0,
                 trace_cap=0, complete=True, slo_target=0, invariant=None,
                 root_kinds=()):
    cfg = SimConfig(n_nodes=n_nodes, time_limit=sec(5), latency_hist=lat,
                    trace_cap=trace_cap,
                    complete_kinds=(((EV_MSG, TAG_PING),)
                                    if lat and complete else ()),
                    root_kinds=root_kinds if lat else (),
                    slo_target=slo_target,
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [PingPong(n_nodes, target=target)], state_spec(),
                   scenario=scenario, invariant=invariant)


def _echo_sweep():
    """A direct request/reply rpc_echo sweep whose 512-slot ring holds
    every dispatch: reply deliveries complete a call and root the next.
    Returns (runtime, reply tag, final state)."""
    from madsim_tpu.models.rpc_echo import TAG_ECHO, make_echo_runtime
    from madsim_tpu.net import rpc
    rtag = rpc.reply_tag(TAG_ECHO)
    cfg = SimConfig(n_nodes=3, event_capacity=64, time_limit=sec(5),
                    latency_hist=24, trace_cap=512,
                    complete_kinds=((EV_MSG, rtag),),
                    root_kinds=((EV_MSG, rtag),),
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(8)))
    rt = make_echo_runtime(n_nodes=3, target=6, cfg=cfg)
    return rt, rtag, rt.run_fused(rt.init_batch(np.arange(6)), 1024, 256)


def _nonlatency_state(state) -> dict:
    out = {}
    for name in type(state).__dataclass_fields__:
        if name in TRACE_FIELDS or name in ("node_state", "ext"):
            continue
        out[name] = np.asarray(getattr(state, name))
    for i, leaf in enumerate(jax.tree.leaves(state.node_state)):
        out[f"node_state_{i}"] = np.asarray(leaf)
    return out


class TestLatencyPlane:
    def test_latency_never_perturbs_trajectory(self):
        seeds = np.arange(16, dtype=np.uint32)
        rt0 = _pingpong_rt(lat=0)
        base, _ = rt0.run(rt0.init_batch(seeds), 256, 64)
        ref = _nonlatency_state(base)
        for lanes in (None, [0, 3], []):
            rt = _pingpong_rt(lat=24)
            st, _ = rt.run(rt.init_batch(seeds, latency_lanes=lanes),
                           256, 64)
            got = _nonlatency_state(st)
            assert ref.keys() == got.keys()
            for k in ref:
                assert (ref[k] == got[k]).all(), f"lanes={lanes}: {k}"
            assert (rt0.fingerprints(base) == rt.fingerprints(st)).all()

    def test_fused_equals_chunked_on_latency_columns(self):
        rt = _pingpong_rt(lat=24, target=40, trace_cap=32)
        seeds = np.arange(8, dtype=np.uint32)
        chunked, _ = rt.run(rt.init_batch(seeds), 256, 64)
        fused = rt.run_fused(rt.init_batch(seeds), 256, 64)
        for f in TRACE_FIELDS:
            assert (np.asarray(getattr(chunked, f))
                    == np.asarray(getattr(fused, f))).all(), f
        assert int(np.asarray(fused.lh_e2e).sum()) > 0

    def test_partial_lanes_cannot_split_outcomes(self):
        seeds = np.arange(8, dtype=np.uint32)
        rt = _pingpong_rt(lat=24)
        sampled, _ = rt.run(rt.init_batch(seeds, latency_lanes=[0, 1]),
                            256, 64)
        allon, _ = rt.run(rt.init_batch(seeds), 256, 64)
        assert (rt.fingerprints(sampled) == rt.fingerprints(allon)).all()
        assert (summarize(rt, sampled, seeds)["distinct_outcomes"]
                == summarize(rt, allon, seeds)["distinct_outcomes"])

    def test_masked_lanes_record_nothing(self):
        rt = _pingpong_rt(lat=24, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(4), latency_lanes=[2]),
                          128, 64)
        for f in ("lh_sojourn", "lh_e2e", "lh_slo_miss"):
            v = np.asarray(getattr(st, f))
            assert v[[0, 1, 3]].sum() == 0, f
        assert np.asarray(st.lh_e2e)[2].sum() > 0

    def test_latency_lanes_requires_compiled_plane(self):
        rt = _pingpong_rt(lat=0)
        with pytest.raises(ValueError, match="latency"):
            rt.init_batch(np.arange(4), latency_lanes=[0])

    def test_sojourn_matches_host_replay(self):
        # the step's own rule, replayed on the host: before each step,
        # compute the earliest ELIGIBLE deadline from the pre-state
        # table (all earliest ties share it, so the random tie-break
        # doesn't matter); sojourn = post-now − that deadline. Node-
        # summed per lane — attribution is covered by the e2e walk.
        from madsim_tpu.utils.hostcopy import owned_host_copy
        rt = _pingpong_rt(lat=24, target=40, n_nodes=3)
        B = 4
        state = rt.init_batch(np.arange(B, dtype=np.uint32))
        LB = rt.cfg.latency_hist
        ref = np.zeros((B, LB), np.int64)
        for _ in range(120):
            pre = {k: owned_host_copy(getattr(state, k))
                   for k in ("t_deadline", "t_kind", "t_node", "alive",
                             "paused", "halted", "steps", "now")}
            state, _ = rt.run(state, 1, 1)
            post_now = np.asarray(state.now)
            post_steps = np.asarray(state.steps)
            for b in range(B):
                if pre["halted"][b] or post_steps[b] == pre["steps"][b]:
                    continue        # frozen or no dispatch
                kind = pre["t_kind"][b].astype(np.int64)
                node = np.clip(pre["t_node"][b].astype(np.int64), 0,
                               rt.cfg.n_nodes - 1)
                parked = (pre["alive"][b][node] & pre["paused"][b][node]
                          & (kind != EV_SUPER))
                elig = (kind != 0) & ~parked
                dmin = int(pre["t_deadline"][b][elig].min())
                soj = max(int(post_now[b]) - dmin, 0)
                bkt = (0 if soj == 0
                       else min(int(soj).bit_length(), LB - 1))
                ref[b, bkt] += 1
            if bool(np.asarray(state.halted).all()):
                break
        got = np.asarray(state.lh_sojourn).sum(axis=1)     # [B, LB]
        assert (got == ref).all(), (got, ref)
        assert ref.sum() > 0

    def test_e2e_matches_ring_parent_walk(self):
        # root-inheritance end to end on a direct request/reply chain:
        # every ring completion's tr_lat equals now(completion) −
        # now(its chain's root), roots being external dispatches
        rt, rtag, st = _echo_sweep()
        checked = 0
        for b in range(6):
            recs = ring_records(st, b)
            assert recs["dropped"] == 0
            lat = np.asarray(recs["lat"])
            step_at = {int(s): i for i, s in enumerate(recs["step"])}
            for i in np.nonzero(lat >= 0)[0]:
                j = int(i)
                while True:
                    p = int(recs["parent"][j])
                    if p < 0 or p not in step_at:
                        root_now = int(recs["now"][j])
                        break
                    jp = step_at[p]
                    if (int(recs["kind"][jp]) == EV_MSG
                            and int(recs["tag"][jp]) == rtag):
                        root_now = int(recs["now"][jp])
                        break
                    j = jp
                assert int(lat[i]) == int(recs["now"][i]) - root_now
                checked += 1
        assert checked > 0

    def test_e2e_histogram_equals_ring_bucketing(self):
        # ring -> histogram: each lane's ring latencies, bucketed by the
        # histogram rule, are that lane's device e2e histogram, and the
        # digest's batch merge is the exact sum over lanes
        _, _, st = _echo_sweep()
        lh = np.asarray(st.lh_e2e)                     # [B, N, LB]
        LB = lh.shape[2]
        for b in range(lh.shape[0]):
            recs = ring_records(st, b)
            assert recs["dropped"] == 0
            lat = np.asarray(recs["lat"])
            ref = np.zeros(LB, np.int64)
            for v in lat[lat >= 0]:
                ref[0 if v == 0 else min(int(v).bit_length(), LB - 1)] += 1
            assert (lh[b].sum(0) == ref).all(), (b, lh[b].sum(0), ref)
        assert lh.sum() > 0
        assert (np.asarray(latency_counters(st)["e2e_hist"])
                == lh.sum(0)).all()

    def test_scenario_row_mints_root_at_dispatch(self):
        # deferred boots are external causes that mint roots at THEIR
        # dispatch time: with the whole world arriving at ms(500),
        # every chain's root is >= ms(500), so no measured latency can
        # exceed the time since boot — if roots were the absolute
        # clock's zero, completions near `now` would violate the bound
        sc = Scenario()
        sc.at(ms(500)).boot(0)
        sc.at(ms(500)).boot(1)
        rt = _pingpong_rt(lat=24, target=40, scenario=sc, trace_cap=512)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 256, 64)
        recs = ring_records(st, 0)
        lat = np.asarray(recs["lat"])
        done = lat >= 0
        assert done.any()
        now_at = np.asarray(recs["now"])[done]
        assert (now_at >= ms(500)).all()
        assert (lat[done] <= now_at - ms(500)).all(), \
            "a latency exceeded time-since-boot: root not minted at " \
            "the scenario row's dispatch"

    def test_buckets_saturate_no_wraparound(self):
        rt = _pingpong_rt(lat=24, target=40)
        st = rt.init_batch(np.arange(4))
        st = st.replace(
            lh_sojourn=jnp.full_like(st.lh_sojourn, I32_MAX),
            lh_e2e=jnp.full_like(st.lh_e2e, I32_MAX - 1),
            lh_slo_miss=jnp.full_like(st.lh_slo_miss, I32_MAX))
        final = rt.run_fused(st, 256, 64)
        for f in ("lh_sojourn", "lh_e2e", "lh_slo_miss"):
            v = np.asarray(getattr(final, f))
            assert (v >= 0).all() and (v <= I32_MAX).all(), f
        assert (np.asarray(final.lh_sojourn) == I32_MAX).all()

    def test_slo_target_is_dynamic(self):
        # same executable, different targets: miss counts move, nothing
        # else does (slo_target is observation-side state)
        rt = _pingpong_rt(lat=24, target=40)
        base = rt.run_fused(rt.init_batch(np.arange(4)), 256, 64)
        assert int(np.asarray(base.lh_slo_miss).sum()) == 0   # target 0
        st = rt.set_slo_target(rt.init_batch(np.arange(4)), 1)
        tight = rt.run_fused(st, 256, 64)
        assert int(np.asarray(tight.lh_slo_miss).sum()) > 0
        assert (rt.fingerprints(base) == rt.fingerprints(tight)).all()
        assert (np.asarray(base.lh_e2e)
                == np.asarray(tight.lh_e2e)).all()
        rt0 = _pingpong_rt(lat=0)
        with pytest.raises(ValueError, match="latency"):
            rt0.set_slo_target(rt0.init_batch(np.arange(2)), 5)


class TestFlagshipEquivalence:
    """Leaf-for-leaf equivalence with the plane on/off/compiled-out over
    the flagships — wal_kv fast, raft/shard_kv slow (the r7/r15
    pattern)."""

    def _assert_transparent(self, make_rt, seeds, steps, chunk):
        rt_on = make_rt(True)
        rt_off = make_rt(False)
        on, _ = rt_on.run(rt_on.init_batch(seeds), steps, chunk)
        off, _ = rt_off.run(rt_off.init_batch(seeds), steps, chunk)
        fused = rt_on.run_fused(rt_on.init_batch(seeds), steps, chunk)
        ref = _nonlatency_state(off)
        got = _nonlatency_state(on)
        assert ref.keys() == got.keys()
        for k in ref:
            assert (ref[k] == got[k]).all(), k
        assert (rt_on.fingerprints(on) == rt_off.fingerprints(off)).all()
        for f in TRACE_FIELDS:
            assert (np.asarray(getattr(on, f))
                    == np.asarray(getattr(fused, f))).all(), f
        return on

    def test_wal_kv_latency_transparent(self):
        from madsim_tpu.models.wal_kv import M_ACK, make_wal_kv_runtime

        def make(lat):
            sc = Scenario()
            for t in range(6):
                sc.at(ms(150) + ms(250) * t).kill(0)
                sc.at(ms(210) + ms(250) * t).restart(0)
            cfg = SimConfig(n_nodes=3, event_capacity=256, payload_words=8,
                            time_limit=sec(10),
                            latency_hist=20 if lat else 0,
                            complete_kinds=(((EV_MSG, M_ACK),)
                                            if lat else ()),
                            net=NetConfig(send_latency_min=ms(1),
                                          send_latency_max=ms(8)))
            return make_wal_kv_runtime(n_clients=2, n_ops=8, wal_cap=64,
                                       sync_wal=False, scenario=sc, cfg=cfg)

        on = self._assert_transparent(
            make, np.arange(16, dtype=np.uint32), 2048, 512)
        assert int(np.asarray(on.lh_e2e).sum()) > 0

    @pytest.mark.slow
    def test_raft_latency_transparent(self):
        from madsim_tpu.models.raft import make_raft_runtime

        def make(lat):
            cfg = SimConfig(n_nodes=5, event_capacity=128,
                            time_limit=sec(3),
                            latency_hist=20 if lat else 0,
                            complete_kinds=(((EV_MSG, 1),) if lat else ()),
                            net=NetConfig(packet_loss_rate=0.05,
                                          send_latency_min=ms(1),
                                          send_latency_max=ms(10)))
            sc = Scenario()
            sc.at(sec(1)).kill_random()
            sc.at(sec(1) + ms(400)).restart_random()
            return make_raft_runtime(5, 8, n_cmds=4, scenario=sc, cfg=cfg)

        self._assert_transparent(
            make, np.arange(64, dtype=np.uint32), 1500, 256)

    @pytest.mark.slow
    def test_shard_kv_latency_transparent(self):
        from madsim_tpu.models.shard_kv import CMD, T_NEW, \
            make_shard_runtime

        def make(lat):
            cfg = SimConfig(n_nodes=11, event_capacity=160,
                            payload_words=12, time_limit=sec(60),
                            latency_hist=24 if lat else 0,
                            complete_kinds=(((EV_MSG, CMD),)
                                            if lat else ()),
                            root_kinds=(((EV_TIMER, T_NEW),)
                                        if lat else ()),
                            net=NetConfig(send_latency_min=ms(1),
                                          send_latency_max=ms(10)))
            return make_shard_runtime(n_groups=2, rg=3, rc=3, n_clients=2,
                                      n_ops=4, max_cfg=4, cfg=cfg)

        on = self._assert_transparent(
            make, np.arange(64, dtype=np.uint32), 4096, 512)
        assert int(np.asarray(on.lh_e2e).sum()) > 0


class TestDigestAndReport:
    def test_digest_compiled_out_is_none(self):
        rt = _pingpong_rt(lat=0)
        st, _ = rt.run(rt.init_batch(np.arange(2)), 128, 64)
        assert latency_digest(st) is None
        assert latency_counters(st) is None
        assert latency_summary(st) is None
        assert lane_e2e_p99(st) is None
        assert summarize(rt, st)["latency"] is None
        assert "compiled out" in format_latency(None)

    def test_quantiles_are_bucket_cdf_lower_bounds(self):
        # crafted histogram: 100 samples in bucket 3 ([4, 8)), 1 sample
        # in bucket 10 ([512, 1024)) — p50/p90 read edge 4, p999 reads
        # edge 512; exact, deterministic
        rt = _pingpong_rt(lat=24, target=40)
        st = rt.init_batch(np.arange(2))
        lh = np.zeros(np.asarray(st.lh_e2e).shape, np.int32)
        lh[:, 0, 3] = 100
        lh[:, 0, 10] = 1
        st = st.replace(lh_e2e=jnp.asarray(lh))
        c = latency_counters(st)
        assert c["e2e_p50"] == 4 and c["e2e_p90"] == 4
        assert c["e2e_p999"] == 512
        assert (np.asarray(lane_e2e_p99(st)) == 4).all()
        edges = latency_bucket_edges(24)
        assert edges[0] == 0 and edges[1] == 1 and edges[3] == 4
        rows = latency_histogram_rows(st)
        assert {r["bucket"] for r in rows} == {3, 10}

    def test_summary_masking_and_render(self):
        rt = _pingpong_rt(lat=24, target=40, slo_target=1)
        st = rt.run_fused(rt.init_batch(np.arange(8),
                                        latency_lanes=[1, 4]), 256, 64)
        c = latency_counters(st)
        assert c["lanes"] == 2
        per_lane = np.asarray(st.lh_e2e).sum((1, 2))
        assert c["e2e_hist"].sum() == per_lane[[1, 4]].sum()
        s = latency_summary(st)
        assert s["completions"] == int(per_lane[[1, 4]].sum())
        assert s["slo_miss"] == s["completions"]       # target 1 tick
        txt = format_latency(s, node_names=["ping", "pong"])
        assert "ping" in txt and "slo_miss" in txt
        rep = summarize(rt, st, np.arange(8))
        assert rep["latency"]["lanes"] == 2
        assert rep["latency"]["slo_miss"] == s["slo_miss"]

    def test_all_masked_batch_reads_zero(self):
        rt = _pingpong_rt(lat=24, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(4), latency_lanes=[]),
                          128, 64)
        c = latency_counters(st)
        assert c["lanes"] == 0
        assert c["e2e_hist"].sum() == 0 and c["e2e_p99"] == 0

    def test_lat_ring_column_needs_both_gates(self):
        rt = _pingpong_rt(lat=0, target=40, trace_cap=16)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 128, 64)
        assert "lat" not in ring_records(st, 0)
        rt2 = _pingpong_rt(lat=24, target=40, trace_cap=16)
        st2 = rt2.run_fused(rt2.init_batch(np.arange(2)), 128, 64)
        recs = ring_records(st2, 0)
        assert "lat" in recs and (np.asarray(recs["lat"]) >= -1).all()
        assert (np.asarray(recs["lat"]) >= 0).any()

    def test_rolling_p99_counter_track(self):
        from madsim_tpu.obs import counter_track_events
        rt = _pingpong_rt(lat=24, target=40, trace_cap=64)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 192, 64)
        evs = counter_track_events(st, lane=0)
        p99s = [e for e in evs if e["name"].startswith("e2e_p99:")]
        assert p99s and all(e["args"]["p99_us"] >= 0 for e in p99s)


class TestSloInvariant:
    def test_fires_deterministically_with_crash_slo(self):
        rt = _pingpong_rt(lat=24, target=40,
                          invariant=slo_invariant(p99_le=1, min_count=4))
        a = rt.run_fused(rt.init_batch(np.arange(8)), 256, 64)
        b = rt.run_fused(rt.init_batch(np.arange(8)), 256, 64)
        assert (np.asarray(a.crash_code) == CRASH_SLO).all()
        assert (np.asarray(a.crash_code) == np.asarray(b.crash_code)).all()
        assert (np.asarray(a.steps) == np.asarray(b.steps)).all()
        assert (rt.fingerprints(a) == rt.fingerprints(b)).all()
        # seed replay reproduces the SLO crash (the repro contract)
        single, _ = rt.run_single(3, 256, 64)
        assert int(np.asarray(single.crash_code)[0]) == CRASH_SLO

    def test_min_count_gates_firing(self):
        rt = _pingpong_rt(lat=24, target=6,
                          invariant=slo_invariant(p99_le=1,
                                                  min_count=10**6))
        st = rt.run_fused(rt.init_batch(np.arange(4)), 256, 64)
        assert not np.asarray(st.crashed).any()

    def test_arg_validation(self):
        with pytest.raises(ValueError, match="p99_le"):
            slo_invariant()
        with pytest.raises(ValueError, match="q must"):
            slo_invariant(q="p42", target=5)

    def test_raises_on_compiled_out_plane(self):
        rt = _pingpong_rt(lat=0, invariant=slo_invariant(p99_le=1))
        with pytest.raises(ValueError, match="latency plane"):
            rt.run(rt.init_batch(np.arange(2)), 64, 64)

    def test_slo_crash_buckets_next_to_crashes(self, tmp_path):
        # SLO-as-crash inherits the triage stack: a durable fuzz on an
        # SLO-violating runtime must open a causal-fingerprint bucket
        # whose crash_code is CRASH_SLO, like any safety bug
        from madsim_tpu.search.fuzz import fuzz
        from madsim_tpu.service.store import CorpusStore
        sc = Scenario()
        sc.at(ms(40)).kill_random()
        sc.at(ms(400)).restart_random()
        rt = _pingpong_rt(lat=24, target=40, scenario=sc, trace_cap=64,
                          n_nodes=4,
                          invariant=slo_invariant(p99_le=1, min_count=4))
        d = str(tmp_path / "c")
        res = fuzz(rt, max_steps=300, batch=8, max_rounds=2, dry_rounds=9,
                   chunk=128, corpus_dir=d)
        assert CRASH_SLO in res["crash_repros"]
        store = CorpusStore(d, create=False)
        codes = {store.load_bucket(k)["crash_code"]
                 for k in store.bucket_keys()}
        assert CRASH_SLO in codes


class TestLatBonusAndRecords:
    def test_corpus_lat_bonus_scales_admission_energy(self):
        from madsim_tpu.models.workloads import saturating
        from madsim_tpu.search.corpus import Corpus
        from madsim_tpu.search.mutate import KnobPlan
        rt = saturating()
        plan = KnobPlan.from_runtime(rt)
        c = Corpus(plan, lat_bonus=1.0)
        kb = plan.base_batch(2)
        c.observe(kb, np.arange(2), np.asarray([1, 2], np.uint64),
                  np.zeros(2, bool), np.zeros(2, np.int64),
                  np.full(2, -1, np.int64), 0,
                  lat_p99=np.asarray([100, 1000], np.int32))
        by_hash = {e["hash"]: e["energy"] for e in c.entries}
        assert by_hash[2] == pytest.approx(2.0)    # worst tail: x(1+1)
        assert by_hash[1] == pytest.approx(1.1)    # 100/1000 relative
        # latency-blind corpus ignores the signal entirely
        c0 = Corpus(plan, lat_bonus=0.0)
        c0.observe(kb, np.arange(2), np.asarray([1, 2], np.uint64),
                   np.zeros(2, bool), np.zeros(2, np.int64),
                   np.full(2, -1, np.int64), 0,
                   lat_p99=np.asarray([100, 1000], np.int32))
        assert all(e["energy"] == 1.0 for e in c0.entries)

    def test_fuzz_rounds_carry_latency_fields(self):
        sc = Scenario()
        sc.at(ms(40)).kill_random()
        sc.at(ms(400)).restart_random()
        rt = _pingpong_rt(lat=24, target=6, scenario=sc, n_nodes=4)
        from madsim_tpu.search.fuzz import fuzz
        obs = JsonlObserver(io.StringIO())
        fuzz(rt, max_steps=300, batch=8, max_rounds=3, dry_rounds=9,
             chunk=128, lat_bonus=1.0, observer=obs)
        rounds = [r for r in obs.records if r.get("kind") == "fuzz_round"]
        assert rounds
        for rec in rounds:
            assert "lat_p99" in rec and "slo_miss" in rec
            assert rec["lat_p99"] >= 0
        # a plane-free runtime emits rounds WITHOUT the fields
        rt0 = _pingpong_rt(lat=0, target=6, scenario=sc, n_nodes=4)
        obs0 = JsonlObserver(io.StringIO())
        fuzz(rt0, max_steps=300, batch=8, max_rounds=2, dry_rounds=9,
             chunk=128, observer=obs0)
        r0 = [r for r in obs0.records if r.get("kind") == "fuzz_round"]
        assert r0 and all("lat_p99" not in r for r in r0)

    def test_sweep_done_record_carries_latency(self):
        rt = _pingpong_rt(lat=24, target=40, slo_target=1)
        obs = JsonlObserver(io.StringIO())
        rt.run(rt.init_batch(np.arange(4)), 128, 64, observer=obs)
        done = [r for r in obs.records if r["kind"] == "done"][-1]
        assert done["lat_p99"] >= 0 and done["slo_miss"] > 0

    def test_timeline_p99_curve(self, tmp_path):
        from madsim_tpu.service.campaign import campaign_timeline
        from madsim_tpu.service.store import CorpusStore
        d = str(tmp_path / "c")
        store = CorpusStore(d, signature=["sig"])
        store.append_metrics(0, dict(t=1000.0, rounds_done=1, coverage=3,
                                     wall_s=1.0, lat_p99=250_000,
                                     slo_miss=2))
        store.append_metrics(0, dict(t=1002.0, rounds_done=2, coverage=5,
                                     wall_s=2.0, lat_p99=310_000,
                                     slo_miss=4))
        tl = campaign_timeline(store)
        assert tl["p99_curve"] == [[0.0, 250_000], [2.0, 310_000]]
        # rows without the field contribute nothing (pre-r16 dirs)
        store.append_metrics(1, dict(t=1003.0, rounds_done=1, coverage=6,
                                     wall_s=1.0))
        assert len(campaign_timeline(store)["p99_curve"]) == 2


class TestCheckpointMigration:
    def test_pre_r16_checkpoint_rejected_by_leaf_count(self, tmp_path):
        # the MIGRATION r16 contract: a pre-r16 checkpoint (no lh_*/
        # ev_root_t/slo_target/tr_lat leaves — 7 fewer) fails load()
        # loudly on the leaf count, not by silent misalignment
        from madsim_tpu.runtime import checkpoint
        rt = _pingpong_rt(lat=24)
        st = rt.init_batch(np.arange(2))
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, st)
        with np.load(p) as z:
            leaves = {k: z[k] for k in z.files}
        n = len([k for k in leaves if k.startswith("leaf_")])
        stripped = {k: v for k, v in leaves.items()
                    if not k.startswith("leaf_")}
        for i in range(n - 7):
            stripped[f"leaf_{i}"] = leaves[f"leaf_{i}"]
        p2 = str(tmp_path / "old.npz")
        np.savez_compressed(p2, **stripped)
        with pytest.raises(ValueError, match="leaves"):
            checkpoint.load(p2, st)
