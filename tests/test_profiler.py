"""Sim-profiler counter plane (DESIGN §16): counters as pure observers.

The load-bearing properties: (1) profiling is an observation lever —
trajectories are bit-identical leaf-for-leaf with the plane on, off,
compiled out, or lane-masked, and the pf_* columns are excluded from
fingerprints so partial profiling can never split `distinct_outcomes`;
(2) counters SATURATE at int32 max, never wrap; (3) the counters agree
with a host-replayed reference computed from the collect_events stream;
(4) fuzzer yield attribution sums to admissions; (5) the durable
campaign timeline folds with no gaps and no double-counted rounds, and
stale workers are flagged.
"""

import io
import json
import os

import jax
import numpy as np
import pytest

from madsim_tpu import (JsonlObserver, NetConfig, Runtime, Scenario,
                        SimConfig, ms, sec, summarize)
from madsim_tpu.core.state import N_EV_KINDS, TRACE_FIELDS
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.obs import (counter_track_events, export_profile_trace,
                            format_profile, profile_summary)
from madsim_tpu.parallel.stats import profile_counters, profile_digest

I32_MAX = 2**31 - 1


def _pingpong_rt(profile=True, target=6, n_nodes=2, scenario=None,
                 loss=0.0, trace_cap=0, sketch_slots=0):
    cfg = SimConfig(n_nodes=n_nodes, time_limit=sec(5), profile=profile,
                    trace_cap=trace_cap, sketch_slots=sketch_slots,
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [PingPong(n_nodes, target=target)], state_spec(),
                   scenario=scenario)


def _nonprofile_state(state) -> dict:
    out = {}
    for name in type(state).__dataclass_fields__:
        if name in TRACE_FIELDS or name in ("node_state", "ext"):
            continue
        out[name] = np.asarray(getattr(state, name))
    for i, leaf in enumerate(jax.tree.leaves(state.node_state)):
        out[f"node_state_{i}"] = np.asarray(leaf)
    return out


class TestCounterPlane:
    def test_profile_never_perturbs_trajectory(self):
        # same workload, plane compiled out vs on vs lane-masked: every
        # non-observation field bit-identical (profile is an observation
        # lever, not a replay domain)
        seeds = np.arange(16, dtype=np.uint32)
        rt0 = _pingpong_rt(profile=False)
        base, _ = rt0.run(rt0.init_batch(seeds), 256, 64)
        ref = _nonprofile_state(base)
        for lanes in (None, [0, 3], []):
            rt = _pingpong_rt(profile=True)
            st, _ = rt.run(rt.init_batch(seeds, profile_lanes=lanes),
                           256, 64)
            got = _nonprofile_state(st)
            assert ref.keys() == got.keys()
            for k in ref:
                assert (ref[k] == got[k]).all(), f"lanes={lanes}: {k}"
            assert (rt0.fingerprints(base) == rt.fingerprints(st)).all()

    def test_fused_equals_chunked_on_counters(self):
        rt = _pingpong_rt(profile=True, target=40)
        seeds = np.arange(8, dtype=np.uint32)
        chunked, _ = rt.run(rt.init_batch(seeds), 256, 64)
        fused = rt.run_fused(rt.init_batch(seeds), 256, 64)
        for f in TRACE_FIELDS:
            assert (np.asarray(getattr(chunked, f))
                    == np.asarray(getattr(fused, f))).all(), f

    def test_partial_lanes_cannot_split_outcomes(self):
        # fingerprint exclusion: profiling half the lanes must leave
        # distinct_outcomes a trajectory metric
        seeds = np.arange(8, dtype=np.uint32)
        rt = _pingpong_rt(profile=True)
        sampled, _ = rt.run(rt.init_batch(seeds, profile_lanes=[0, 1]),
                            256, 64)
        allon, _ = rt.run(rt.init_batch(seeds), 256, 64)
        assert (rt.fingerprints(sampled) == rt.fingerprints(allon)).all()
        assert (summarize(rt, sampled, seeds)["distinct_outcomes"]
                == summarize(rt, allon, seeds)["distinct_outcomes"])

    def test_masked_lanes_count_nothing(self):
        rt = _pingpong_rt(profile=True, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(4), profile_lanes=[2]),
                          128, 64)
        disp = np.asarray(st.pf_dispatch)
        assert disp[2].sum() > 0
        assert disp[[0, 1, 3]].sum() == 0
        assert np.asarray(st.pf_busy)[[0, 1, 3]].sum() == 0
        assert (np.asarray(st.pf_qmax)[[0, 1, 3]] == 0).all()

    def test_profile_lanes_requires_compiled_plane(self):
        rt = _pingpong_rt(profile=False)
        with pytest.raises(ValueError, match="profile"):
            rt.init_batch(np.arange(4), profile_lanes=[0])

    def test_dispatch_counts_and_busy_match_host_replay(self):
        # the seeded-reference contract: counters equal what a host
        # walk of the collect_events stream computes (fixed kill
        # targets so super attribution is record-visible)
        sc = Scenario()
        sc.at(ms(6)).kill(1)
        sc.at(ms(9)).restart(1)
        rt = _pingpong_rt(profile=True, target=12, scenario=sc)
        state, events = rt.run(rt.init_batch(np.arange(4)), 512, 128,
                               collect_events=True)
        fired = np.asarray(events["fired"])
        kind = np.asarray(events["kind"])
        node = np.asarray(events["node"])
        now = np.asarray(events["now"])
        disp = np.asarray(state.pf_dispatch)
        busy = np.asarray(state.pf_busy)
        for b in range(4):
            idx = np.nonzero(fired[:, b])[0]
            ref_d = np.zeros((2, N_EV_KINDS), np.int64)
            ref_b = np.zeros(2, np.int64)
            prev = 0
            for i in idx:
                ref_d[int(node[i, b]), int(kind[i, b])] += 1
                ref_b[int(node[i, b])] += int(now[i, b]) - prev
                prev = int(now[i, b])
            assert (disp[b] == ref_d).all(), b
            assert (busy[b] == ref_b).all(), b
        # the scheduled kill/restart landed on node 1, every lane
        assert (np.asarray(state.pf_kill)[:, 1] == 2).all()  # kill+restart
        assert (np.asarray(state.pf_restart)[:, 1] == 2).all()  # boot+restart

    def test_busy_sums_to_now(self):
        rt = _pingpong_rt(profile=True, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(8)), 256, 64)
        assert (np.asarray(st.pf_busy).sum(-1) == np.asarray(st.now)).all()

    def test_qmax_positive_and_bounded(self):
        rt = _pingpong_rt(profile=True, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(8)), 256, 64)
        q = np.asarray(st.pf_qmax)
        assert (q > 0).all()
        assert (q <= rt.cfg.event_capacity).all()

    def test_counters_saturate_no_wraparound(self):
        # plant counters at the brink: further increments must peg at
        # int32 max, never wrap negative
        import jax.numpy as jnp
        rt = _pingpong_rt(profile=True, target=40)
        st = rt.init_batch(np.arange(4))
        st = st.replace(
            pf_delay=jnp.full_like(st.pf_delay, I32_MAX - 3),
            pf_busy=jnp.full_like(st.pf_busy, I32_MAX - 1),
            pf_dispatch=jnp.full_like(st.pf_dispatch, I32_MAX))
        final = rt.run_fused(st, 256, 64)
        for f in ("pf_delay", "pf_busy", "pf_dispatch"):
            v = np.asarray(getattr(final, f))
            assert (v >= 0).all(), f
            assert (v <= I32_MAX).all(), f
        assert (np.asarray(final.pf_delay) == I32_MAX).all()
        assert (np.asarray(final.pf_busy) == I32_MAX).all()
        assert (np.asarray(final.pf_dispatch) == I32_MAX).all()

    def test_drops_counted_on_lossy_net(self):
        rt = _pingpong_rt(profile=True, target=1 << 30, loss=0.3)
        st = rt.run_fused(rt.init_batch(np.arange(8)), 256, 64)
        assert int(np.asarray(st.pf_drop).sum()) > 0
        assert int(np.asarray(st.pf_delay).sum()) > 0


class TestFlagshipEquivalence:
    """Leaf-for-leaf equivalence with profiling on/off/compiled-out over
    the flagships — the r7 ring pattern: the fast lane holds pingpong
    (above) plus wal_kv here; the full raft/wal_kv/shard_kv matrix is
    `slow`."""

    def _assert_profile_transparent(self, make_rt, seeds, steps, chunk):
        rt_on = make_rt(True)
        rt_off = make_rt(False)
        on, _ = rt_on.run(rt_on.init_batch(seeds), steps, chunk)
        off, _ = rt_off.run(rt_off.init_batch(seeds), steps, chunk)
        fused = rt_on.run_fused(rt_on.init_batch(seeds), steps, chunk)
        ref = _nonprofile_state(off)
        got = _nonprofile_state(on)
        assert ref.keys() == got.keys()
        for k in ref:
            assert (ref[k] == got[k]).all(), k
        assert (rt_on.fingerprints(on) == rt_off.fingerprints(off)).all()
        for f in TRACE_FIELDS:
            assert (np.asarray(getattr(on, f))
                    == np.asarray(getattr(fused, f))).all(), f
        return on

    def test_wal_kv_profile_transparent(self):
        from madsim_tpu.models.wal_kv import make_wal_kv_runtime

        def make(profile):
            sc = Scenario()
            for t in range(6):
                sc.at(ms(150) + ms(250) * t).kill(0)
                sc.at(ms(210) + ms(250) * t).restart(0)
            cfg = SimConfig(n_nodes=3, event_capacity=256, payload_words=8,
                            time_limit=sec(10), profile=profile,
                            net=NetConfig(send_latency_min=ms(1),
                                          send_latency_max=ms(8)))
            return make_wal_kv_runtime(n_clients=2, n_ops=8, wal_cap=64,
                                       sync_wal=False, scenario=sc, cfg=cfg)

        on = self._assert_profile_transparent(
            make, np.arange(16, dtype=np.uint32), 2048, 512)
        # the chaos matrix's kills landed and were counted at node 0
        assert int(np.asarray(on.pf_kill)[:, 0].sum()) > 0

    @pytest.mark.slow
    def test_raft_profile_transparent(self):
        from madsim_tpu.models.raft import make_raft_runtime

        def make(profile):
            cfg = SimConfig(n_nodes=5, event_capacity=128,
                            time_limit=sec(3), profile=profile,
                            net=NetConfig(packet_loss_rate=0.05,
                                          send_latency_min=ms(1),
                                          send_latency_max=ms(10)))
            sc = Scenario()
            sc.at(sec(1)).kill_random()
            sc.at(sec(1) + ms(400)).restart_random()
            return make_raft_runtime(5, 8, n_cmds=4, scenario=sc, cfg=cfg)

        self._assert_profile_transparent(
            make, np.arange(64, dtype=np.uint32), 1500, 256)

    @pytest.mark.slow
    def test_shard_kv_profile_transparent(self):
        from madsim_tpu.models.shard_kv import make_shard_runtime

        def make(profile):
            cfg = SimConfig(n_nodes=11, event_capacity=160,
                            payload_words=12, time_limit=sec(60),
                            profile=profile,
                            net=NetConfig(send_latency_min=ms(1),
                                          send_latency_max=ms(10)))
            return make_shard_runtime(n_groups=2, rg=3, rc=3, n_clients=2,
                                      n_ops=4, max_cfg=4, cfg=cfg)

        self._assert_profile_transparent(
            make, np.arange(64, dtype=np.uint32), 4096, 512)


class TestDigestAndReport:
    def test_digest_compiled_out_is_none(self):
        rt = _pingpong_rt(profile=False)
        st, _ = rt.run(rt.init_batch(np.arange(2)), 128, 64)
        assert profile_digest(st) is None
        assert profile_counters(st) is None
        assert profile_summary(st) is None
        assert summarize(rt, st)["profile"] is None
        assert "compiled out" in format_profile(None)

    def test_summary_sums_and_masking(self):
        rt = _pingpong_rt(profile=True, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(8),
                                        profile_lanes=[1, 4]), 256, 64)
        c = profile_counters(st)
        assert c["lanes"] == 2
        steps = np.asarray(st.steps)
        assert c["dispatch"].sum() == steps[[1, 4]].sum()
        # per-lane percentiles cover only the profiled lanes
        assert c["steps_max"] == steps[[1, 4]].max()
        assert c["now_sum"] == np.asarray(st.now)[[1, 4]].sum()
        s = profile_summary(st)
        assert s["dispatches"] == int(steps[[1, 4]].sum())
        assert abs(sum(s["busy_pct"]) - 100.0) < 1.0
        txt = format_profile(s, node_names=["ping", "pong"])
        assert "ping" in txt and str(s["dispatches"]) in txt
        rep = summarize(rt, st, np.arange(8))
        assert rep["profile"]["lanes"] == 2

    def test_batch_sums_do_not_wrap_int32(self):
        # the digest's batch sums must stay exact past 2^31: 64 lanes
        # of pegged counters sum to 64*IMAX — a plain int32 reduction
        # would wrap negative (the reading the saturating per-lane
        # counters exist to prevent)
        import jax.numpy as jnp
        rt = _pingpong_rt(profile=True)
        st = rt.init_batch(np.arange(64))
        st = st.replace(pf_busy=jnp.full_like(st.pf_busy, I32_MAX),
                        pf_delay=jnp.full_like(st.pf_delay, I32_MAX))
        c = profile_counters(st)
        assert (c["busy"] == 64 * I32_MAX).all()
        assert c["delay"] == 64 * I32_MAX > 2**31

    def test_all_masked_batch_reports_zero_percentiles(self):
        # the ship-with-it masked shape: no profiled lanes must read as
        # zeros, not as the int32-max sort sentinel
        rt = _pingpong_rt(profile=True, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(8), profile_lanes=[]),
                          256, 64)
        c = profile_counters(st)
        assert c["lanes"] == 0
        assert c["qmax_p50"] == c["qmax_max"] == 0
        assert c["steps_max"] == 0 and c["now_max"] == 0
        assert c["dispatch"].sum() == 0

    def test_counter_tracks_and_export(self, tmp_path):
        rt = _pingpong_rt(profile=True, target=40, trace_cap=32,
                          sketch_slots=4)
        st = rt.run_fused(rt.init_batch(np.arange(4)), 192, 64)
        evs = counter_track_events(st, lane=0)
        names = {e["name"] for e in evs}
        assert "queue_depth" in names
        assert any(n.startswith("busy_pct:") for n in names)
        assert "cov_divergence" in names
        depths = [e["args"]["depth"] for e in evs
                  if e["name"] == "queue_depth"]
        assert depths and all(0 < d <= rt.cfg.event_capacity
                              for d in depths)
        p = str(tmp_path / "prof.json")
        n = export_profile_trace(p, st, lane=0, node_names=["a", "b"])
        with open(p) as f:
            doc = json.load(f)
        assert n == len([e for e in doc["traceEvents"]
                         if e.get("ph") == "i"]) > 0
        assert [e for e in doc["traceEvents"] if e.get("ph") == "C"]

    def test_qlen_column_needs_both_gates(self):
        from madsim_tpu.obs import ring_records
        rt = _pingpong_rt(profile=False, target=40, trace_cap=16)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 128, 64)
        assert "qlen" not in ring_records(st, 0)
        rt2 = _pingpong_rt(profile=True, target=40, trace_cap=16)
        st2 = rt2.run_fused(rt2.init_batch(np.arange(2)), 128, 64)
        recs = ring_records(st2, 0)
        assert "qlen" in recs and (recs["qlen"] > 0).all()


class TestYieldAttribution:
    def test_mutate_returns_last_op(self):
        from madsim_tpu.models.workloads import saturating
        from madsim_tpu.search.mutate import N_MUT_OPS, KnobPlan
        rt = saturating()
        plan = KnobPlan.from_runtime(rt, dup_slots=2)
        _, hist, last = plan.mutate(plan.base_batch(16),
                                    jax.random.PRNGKey(0), havoc=4)
        last = np.asarray(last)
        assert last.shape == (16,)
        assert ((last >= -1) & (last < N_MUT_OPS)).all()
        assert (last >= 0).any()        # some operator landed somewhere
        _, z_hist, z_last = plan.mutate(plan.base_batch(4),
                                        jax.random.PRNGKey(0), havoc=0)
        assert (np.asarray(z_last) == -1).all()
        assert np.asarray(z_hist).sum() == 0

    def test_mutate_masked_clears_attribution(self):
        from madsim_tpu.models.workloads import saturating
        from madsim_tpu.search.mutate import KnobPlan
        rt = saturating()
        plan = KnobPlan.from_runtime(rt, dup_slots=2)
        mask = np.zeros(16, bool)
        mask[8:] = True
        _, _, last = plan.mutate_masked(plan.base_batch(16),
                                        jax.random.PRNGKey(0), mask,
                                        havoc=4)
        last = np.asarray(last)
        assert (last[:8] == -1).all()
        assert (last[8:] >= 0).any()

    def test_round_yield_sums_to_admissions(self):
        from madsim_tpu.models.workloads import saturating
        from madsim_tpu.search.fuzz import fuzz
        rt = saturating()
        obs = JsonlObserver(io.StringIO())
        res = fuzz(rt, max_steps=400, batch=32, max_rounds=4,
                   dry_rounds=9, chunk=128, rng_seed=0, observer=obs)
        rounds = [r for r in obs.records if r.get("kind") == "fuzz_round"]
        assert rounds
        for rec in rounds:
            assert sum(rec["op_yield"].values()) == rec["admitted"]
            assert rec["corpus_energy"]["entries"] == rec["corpus_size"]
        assert (sum(res["mutation_yield"].values())
                == sum(r["admitted"] for r in rounds))
        assert res["corpus_energy"]["entries"] == res["corpus_size"]

    def test_corpus_energy_summary(self):
        from madsim_tpu.models.workloads import saturating
        from madsim_tpu.search.corpus import Corpus
        from madsim_tpu.search.mutate import KnobPlan
        rt = saturating()
        plan = KnobPlan.from_runtime(rt)
        c = Corpus(plan)
        assert c.energy_summary() == dict(entries=0)
        kb = plan.base_batch(3)
        c.observe(kb, np.arange(3), np.asarray([1, 2, 3], np.uint64),
                  np.asarray([False, True, False]),
                  np.asarray([0, 5, 0], np.int64),
                  np.full(3, -1, np.int64), 0)
        es = c.energy_summary()
        assert es["entries"] == 3 and es["crash_entries"] == 1
        assert es["max"] >= es["p50"] >= 0


class TestCampaignTimeline:
    def _fuzz_kw(self):
        return dict(max_steps=400, batch=16, dry_rounds=9, chunk=128,
                    rng_seed=0)

    def test_killed_and_resumed_timeline_no_gaps_no_dups(self, tmp_path):
        # the acceptance shape, in-process: a campaign interrupted at
        # round 2 and resumed to 4 (the kill+resume contract: a resumed
        # run re-derives the interrupted round identically) plus a
        # second worker — the folded timeline must be gapless and
        # dedup'd per worker
        from madsim_tpu.models.workloads import saturating
        from madsim_tpu.search.fuzz import fuzz
        from madsim_tpu.service.campaign import (campaign_report,
                                                 campaign_timeline)
        from madsim_tpu.service.store import CorpusStore
        d = str(tmp_path / "c")
        rt = saturating()
        # warm every executable OUTSIDE the measured campaign: the
        # staleness flag compares real wall gaps, and a cold compile
        # landing inside one worker's run but not another's (e.g. the
        # first suite run after a structural-signature bump) would skew
        # age-vs-cadence into a flake
        fuzz(rt, corpus_dir=str(tmp_path / "warm"), worker_id=0,
             max_rounds=2, **self._fuzz_kw())
        fuzz(rt, corpus_dir=d, worker_id=0, max_rounds=2,
             **self._fuzz_kw())
        fuzz(rt, corpus_dir=d, worker_id=0, max_rounds=4,
             **self._fuzz_kw())
        fuzz(rt, corpus_dir=d, worker_id=1, max_rounds=3, base_seed=7,
             **self._fuzz_kw())
        store = CorpusStore(d, create=False)
        tl = campaign_timeline(store)
        for w, want in (("w0000", [1, 2, 3, 4]), ("w0001", [1, 2, 3])):
            rd = [r["rounds_done"] for r in tl["timeline"]
                  if r["worker"] == w]
            assert rd == want, (w, rd)
        cov = [c for _, c in tl["coverage_curve"]]
        assert cov == sorted(cov) and cov[-1] > 0
        assert tl["rate_curve"]
        # health check with headroom: these "workers" ran SEQUENTIALLY
        # in one process, so worker 0's age at the campaign's newest row
        # is worker 1's whole run — harness serialization, not campaign
        # dynamics. The default 3x-cadence window is calibrated for
        # concurrent workers (test_stale_worker_flagged covers the
        # positive case synthetically); here a suite-load wobble of
        # ~100ms must not read as a dead worker.
        tl10 = campaign_timeline(store, stale_after=10.0)
        assert not any(h["stale"] for h in tl10["workers_health"].values())
        rep = campaign_report(d, stale_after=10.0)
        assert rep["stale_workers"] == []
        assert rep["coverage_curve"] == tl["coverage_curve"]
        # per-round op_yield survives the resume in the worker state
        ws = store.load_worker_state(0)
        assert sum(ws["op_yield"]) > 0

    def test_duplicate_rows_dedup_keep_last(self, tmp_path):
        from madsim_tpu.service.campaign import campaign_timeline
        from madsim_tpu.service.store import CorpusStore, store_signature
        d = str(tmp_path / "c")
        store = CorpusStore(d, signature=["sig"])
        t0 = 1000.0
        store.append_metrics(0, dict(t=t0, rounds_done=1, coverage=3,
                                     wall_s=1.0))
        # the kill-between-append-and-commit shape: same round
        # re-appended on resume — the LAST occurrence wins
        store.append_metrics(0, dict(t=t0 + 1, rounds_done=1, coverage=3,
                                     wall_s=1.0))
        store.append_metrics(0, dict(t=t0 + 2, rounds_done=2, coverage=5,
                                     wall_s=2.0))
        tl = campaign_timeline(store)
        rows = [r for r in tl["timeline"] if r["worker"] == "w0000"]
        assert [r["rounds_done"] for r in rows] == [1, 2]
        assert rows[0]["t"] == t0 + 1

    def test_rate_curve_uses_campaign_wall_not_row_wall(self, tmp_path):
        # a young worker's first sync (tiny own wall) must not spike the
        # schedules/s curve against the campaign-global coverage — the
        # denominator is the max over workers' walls so far, the
        # campaign_stats rule over time
        from madsim_tpu.service.campaign import campaign_timeline
        from madsim_tpu.service.store import CorpusStore
        d = str(tmp_path / "c")
        store = CorpusStore(d, signature=["sig"])
        store.append_metrics(0, dict(t=1000.0, rounds_done=1,
                                     coverage=10000, wall_s=100.0))
        store.append_metrics(1, dict(t=1001.0, rounds_done=1,
                                     coverage=10, wall_s=1.0))
        tl = campaign_timeline(store)
        assert tl["rate_curve"][0][1] == 100.0          # 10000 / 100
        assert tl["rate_curve"][1][1] == 100.0          # not 10000 / 1

    def test_stale_worker_flagged(self, tmp_path):
        from madsim_tpu.service.campaign import campaign_timeline
        from madsim_tpu.service.store import CorpusStore
        d = str(tmp_path / "c")
        store = CorpusStore(d, signature=["sig"])
        t0 = 1000.0
        for r in range(4):      # healthy cadence: a row every 2s
            store.append_metrics(0, dict(t=t0 + 2 * r, rounds_done=r + 1,
                                         coverage=r, wall_s=r + 1.0))
        # worker 1 stopped syncing long before the campaign's last
        # activity (> 3x its own 2s cadence)
        store.append_metrics(1, dict(t=t0 - 100, rounds_done=1,
                                     coverage=1, wall_s=1.0))
        store.append_metrics(1, dict(t=t0 - 98, rounds_done=2,
                                     coverage=2, wall_s=2.0))
        tl = campaign_timeline(store)
        assert tl["workers_health"]["w0001"]["stale"] is True
        assert tl["workers_health"]["w0000"]["stale"] is False

    def test_jsonl_observer_fsync(self, tmp_path):
        p = str(tmp_path / "log.jsonl")
        obs = JsonlObserver(p, fsync=True)
        obs.on_round(dict(kind="fuzz_round", round=1))
        obs.close()
        with open(p) as f:
            assert json.loads(f.readline())["round"] == 1
        with pytest.raises(io.UnsupportedOperation):
            JsonlObserver(io.StringIO(), fsync=True)


class TestCheckpointMigration:
    def test_pre_r15_checkpoint_rejected_by_leaf_count(self, tmp_path):
        # the MIGRATION r15 contract: a pre-r15 checkpoint (no pf_* or
        # tr_qlen leaves — 9 fewer) fails load() loudly on the leaf
        # count, not by silent misalignment
        from madsim_tpu.runtime import checkpoint
        rt = _pingpong_rt(profile=True)
        st = rt.init_batch(np.arange(2))
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, st)
        with np.load(p) as z:
            leaves = {k: z[k] for k in z.files}
        n = len([k for k in leaves if k.startswith("leaf_")])
        stripped = {k: v for k, v in leaves.items()
                    if not k.startswith("leaf_")}
        for i in range(n - 9):      # a pre-r15 file simply has fewer
            stripped[f"leaf_{i}"] = leaves[f"leaf_{i}"]
        p2 = str(tmp_path / "old.npz")
        np.savez_compressed(p2, **stripped)
        with pytest.raises(ValueError, match="leaves"):
            checkpoint.load(p2, st)
