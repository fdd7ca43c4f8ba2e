"""Windowed telemetry plane (r21, DESIGN §22): sim-time metric series
as pure observers, failing-to-heal as a crash code.

The load-bearing properties: (1) the plane is an observation lever —
trajectories are bit-identical leaf-for-leaf with it on, off, compiled
out, or lane-masked, and the sr_*/window_len columns ride TRACE_FIELDS
out of fingerprints (golden gate vs r20 captured truth); (2) the window
rule is exact — a dispatch at post-advance `now` lands in
min(now // window_len, W-1), a boundary dispatch opens the NEXT window,
overflow clamps into the last window, windows never wrap; (3) counters
SATURATE; (4) window_len is a DYNAMIC operand — retuning re-buckets
without recompiling or perturbing trajectories; (5) the batch digest
(`series_counters`) is an exact masked merge of the recording lanes;
(6) `recovery_invariant` judges only complete windows past the grace
period after the LAST disruptive fault, heals don't restart the clock,
and it fires deterministically with CRASH_RECOVERY; (7) the fuzzer's
burst_bonus scales admission energy by the deepest TRANSIENT spike;
(8) pre-r21 checkpoints are rejected loudly (v7 then; simconfig-v8
since the r23 attribution plane).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import (CRASH_RECOVERY, NetConfig, Runtime, Scenario,
                        SimConfig, format_series, lane_series, ms,
                        recovery_invariant, sec, series_summary, summarize)
from madsim_tpu.core import types as T
from madsim_tpu.core.state import TRACE_FIELDS
from madsim_tpu.core.types import EV_MSG
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.obs import (counter_track_events, fault_names,
                            ring_records, series_counter_track_events)
from madsim_tpu.parallel.stats import (lane_burst, series_counters,
                                       series_digest)

import _series_golden as golden

I32_MAX = 2**31 - 1
TAG_PING = 1        # pingpong's ping message tag (models/pingpong.py)

# the 11 leaves the r21 plane added (MIGRATION r21)
SR_LEAVES = ("sr_on", "window_len", "sr_dispatch", "sr_busy", "sr_qhw",
             "sr_drop", "sr_dup", "sr_complete", "sr_slo_miss",
             "sr_lat", "sr_fault")


def _pingpong_rt(windows=0, window_len=None, target=6, n_nodes=2,
                 scenario=None, lat=0, trace_cap=0, invariant=None):
    kw = {}
    if window_len is not None:
        kw["window_len"] = window_len
    cfg = SimConfig(n_nodes=n_nodes, time_limit=sec(5),
                    series_windows=windows,
                    latency_hist=lat, trace_cap=trace_cap,
                    complete_kinds=(((EV_MSG, TAG_PING),) if lat else ()),
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(4)),
                    **kw)
    return Runtime(cfg, [PingPong(n_nodes, target=target)], state_spec(),
                   scenario=scenario, invariant=invariant)


def _nonseries_state(state) -> dict:
    out = {}
    for name in type(state).__dataclass_fields__:
        if name in TRACE_FIELDS or name in ("node_state", "ext"):
            continue
        out[name] = np.asarray(getattr(state, name))
    for i, leaf in enumerate(jax.tree.leaves(state.node_state)):
        out[f"node_state_{i}"] = np.asarray(leaf)
    return out


# ---------------------------------------------------------------------------
# 1. bit-identical-when-disabled, against r20 captured truth
# ---------------------------------------------------------------------------

class TestEquivalenceR20:
    @pytest.mark.parametrize("workload", sorted(golden.BUILDERS))
    def test_leaf_for_leaf_vs_r20_golden(self, workload):
        # scripts/capture_golden.py froze these digests AT r20 HEAD,
        # before any r21 engine change: every r20 leaf must still hash
        # identically, chunked and fused; the ONLY new leaves are the
        # series plane's own (zero-size sr_* columns here — the frozen
        # workloads never set series_windows)
        gold = golden.load_golden()[workload]
        got = golden.run_workload(workload)
        for runner in ("run", "run_fused"):
            missing = [k for k in gold[runner] if k not in got[runner]]
            assert not missing, (runner, missing)
            diff = [k for k in gold[runner]
                    if gold[runner][k] != got[runner][k]]
            assert not diff, (runner, diff)
            new = set(got[runner]) - set(gold[runner])
            # the r23 attribution plane's leaves ride along (zero-size
            # here — the frozen workloads never set span_attr; their
            # own golden gate lives in tests/test_spans.py)
            span = {".sp_on", ".ev_span", ".sa_tail", ".sa_bottleneck",
                    ".tr_qw"}
            assert new == {"." + n for n in SR_LEAVES} | span, new


# ---------------------------------------------------------------------------
# 2. the observation-lever contract on live runs
# ---------------------------------------------------------------------------

class TestSeriesPlane:
    def test_series_never_perturbs_trajectory(self):
        seeds = np.arange(16, dtype=np.uint32)
        rt0 = _pingpong_rt(windows=0)
        base, _ = rt0.run(rt0.init_batch(seeds), 256, 64)
        ref = _nonseries_state(base)
        for lanes in (None, [0, 3], []):
            rt = _pingpong_rt(windows=8)
            st, _ = rt.run(rt.init_batch(seeds, series_lanes=lanes),
                           256, 64)
            got = _nonseries_state(st)
            assert ref.keys() == got.keys()
            for k in ref:
                assert (ref[k] == got[k]).all(), f"lanes={lanes}: {k}"
            assert (rt0.fingerprints(base) == rt.fingerprints(st)).all()

    def test_fused_equals_chunked_on_series_columns(self):
        rt = _pingpong_rt(windows=8, window_len=ms(25), target=40,
                          lat=24, trace_cap=32)
        seeds = np.arange(8, dtype=np.uint32)
        chunked, _ = rt.run(rt.init_batch(seeds), 256, 64)
        fused = rt.run_fused(rt.init_batch(seeds), 256, 64)
        for f in TRACE_FIELDS:
            assert (np.asarray(getattr(chunked, f))
                    == np.asarray(getattr(fused, f))).all(), f
        assert int(np.asarray(fused.sr_dispatch).sum()) > 0

    def test_partial_lanes_cannot_split_outcomes(self):
        seeds = np.arange(8, dtype=np.uint32)
        rt = _pingpong_rt(windows=8)
        sampled, _ = rt.run(rt.init_batch(seeds, series_lanes=[0, 1]),
                            256, 64)
        allon, _ = rt.run(rt.init_batch(seeds), 256, 64)
        assert (rt.fingerprints(sampled) == rt.fingerprints(allon)).all()
        assert (summarize(rt, sampled, seeds)["distinct_outcomes"]
                == summarize(rt, allon, seeds)["distinct_outcomes"])

    def test_masked_lanes_record_nothing(self):
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=40)
        st = rt.run_fused(rt.init_batch(np.arange(4), series_lanes=[2]),
                          256, 64)
        disp = np.asarray(st.sr_dispatch)
        assert disp[[0, 1, 3]].sum() == 0
        assert disp[2].sum() > 0
        # lane_series refuses to render a masked lane as a healthy
        # flatline — None means "not recorded"
        assert lane_series(st, 0) is None
        assert lane_series(st, 2) is not None

    def test_series_lanes_requires_compiled_plane(self):
        rt = _pingpong_rt(windows=0)
        with pytest.raises(ValueError, match="series"):
            rt.init_batch(np.arange(4), series_lanes=[0])

    def test_signature_and_window_len_is_not_structural(self):
        # v7 here at r21; the r23 attribution plane bumped it to v8 —
        # test_spans.py owns the authoritative version assertion
        cfg = SimConfig(n_nodes=2)
        assert cfg.structural_signature()[0] == "simconfig-v8"
        # the window COUNT shapes the program; the window LENGTH is an
        # operand (the r8 structural/dynamic discipline)
        a = SimConfig(n_nodes=2, series_windows=8)
        b = SimConfig(n_nodes=2, series_windows=4)
        c = SimConfig(n_nodes=2, series_windows=8, window_len=ms(10))
        assert a.structural_signature() != b.structural_signature()
        assert a.structural_signature() == c.structural_signature()

    def test_device_series_equals_ring_replay(self):
        # the host-replay contract on a live run: bucket every ring
        # record by the window rule and the per-(window, node) dispatch
        # counts, per-window completions and window latency histograms
        # must equal the device sr_* columns bit for bit
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=60,
                          lat=24, trace_cap=2048)
        W, wl, LB = 4, ms(25), 24
        st = rt.run_fused(rt.init_batch(np.arange(2)), 1024, 256)
        for b in range(2):
            recs = ring_records(st, b)
            assert recs["dropped"] == 0
            ref_d = np.zeros((W, rt.cfg.n_nodes), np.int64)
            ref_c = np.zeros(W, np.int64)
            ref_l = np.zeros((W, LB), np.int64)
            lat = np.asarray(recs["lat"])
            for i in range(len(recs["now"])):
                w = min(int(recs["now"][i]) // wl, W - 1)
                ref_d[w, int(recs["node"][i])] += 1
                if lat[i] >= 0:
                    ref_c[w] += 1
                    v = int(lat[i])
                    bkt = 0 if v == 0 else min(v.bit_length(), LB - 1)
                    ref_l[w, bkt] += 1
            assert (np.asarray(st.sr_dispatch[b]) == ref_d).all()
            assert (np.asarray(st.sr_complete[b]) == ref_c).all()
            assert (np.asarray(st.sr_lat[b]) == ref_l).all()
            assert ref_c.sum() > 0

    def test_boundary_dispatch_opens_next_window(self):
        # a scenario row dispatches at exactly its at() time; at
        # now == window_len the window rule reads min(wl // wl, W-1)
        # = 1 — the boundary belongs to the NEXT window. unclog on an
        # unclogged link is a pure marker (SRF_HEAL, no disruption).
        sc = Scenario()
        sc.at(ms(25)).unclog_node(0)
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=60,
                          scenario=sc)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 1024, 256)
        f = np.asarray(st.sr_fault)
        assert (f[:, 1] & T.SRF_HEAL != 0).all()
        # window 0 keeps only its own markers (the t=0 boots)
        assert (f[:, 0] & T.SRF_HEAL == 0).all()
        assert (f[:, 0] & T.SRF_BOOT != 0).all()

    def test_overflow_clamps_into_last_window(self):
        # windows never wrap: an event past W * window_len lands in the
        # LAST window (min(3, W-1) = 1 here), never evicts window 0
        sc = Scenario()
        sc.at(ms(90)).unclog_node(0)
        rt = _pingpong_rt(windows=2, window_len=ms(25), target=60,
                          scenario=sc)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 1024, 256)
        f = np.asarray(st.sr_fault)
        assert (f[:, 1] & T.SRF_HEAL != 0).all()
        assert (f[:, 0] & T.SRF_HEAL == 0).all()
        ls = lane_series(st, 0)
        assert ls["touched"] == 2 and ls["windows"] == 2

    def test_counters_saturate_no_wraparound(self):
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=40, lat=24)
        st = rt.init_batch(np.arange(4))
        st = st.replace(
            sr_dispatch=jnp.full_like(st.sr_dispatch, I32_MAX),
            sr_busy=jnp.full_like(st.sr_busy, I32_MAX - 1),
            sr_qhw=jnp.full_like(st.sr_qhw, I32_MAX),
            sr_drop=jnp.full_like(st.sr_drop, I32_MAX),
            sr_dup=jnp.full_like(st.sr_dup, I32_MAX),
            sr_complete=jnp.full_like(st.sr_complete, I32_MAX),
            sr_slo_miss=jnp.full_like(st.sr_slo_miss, I32_MAX),
            sr_lat=jnp.full_like(st.sr_lat, I32_MAX - 1))
        final = rt.run_fused(st, 256, 64)
        for f in ("sr_dispatch", "sr_busy", "sr_qhw", "sr_drop", "sr_dup",
                  "sr_complete", "sr_slo_miss", "sr_lat"):
            v = np.asarray(getattr(final, f))
            assert (v >= 0).all() and (v <= I32_MAX).all(), f
        assert (np.asarray(final.sr_dispatch) == I32_MAX).all()

    def test_window_len_is_dynamic(self):
        # same executable, different bucketing: totals and trajectories
        # identical, only the window axis moves
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=40)
        base = rt.run_fused(rt.init_batch(np.arange(4)), 256, 64)
        spread = np.asarray(base.sr_dispatch).sum(-1)     # [B, W]
        assert (spread[:, 1:].sum(-1) > 0).all()          # multi-window
        wide = rt.set_window_len(rt.init_batch(np.arange(4)), sec(30))
        wide = rt.run_fused(wide, 256, 64)
        coarse = np.asarray(wide.sr_dispatch).sum(-1)
        assert (coarse[:, 1:] == 0).all()                 # all in w0
        assert (coarse.sum(-1) == spread.sum(-1)).all()
        assert (rt.fingerprints(base) == rt.fingerprints(wide)).all()
        rt0 = _pingpong_rt(windows=0)
        with pytest.raises(ValueError, match="series"):
            rt0.set_window_len(rt0.init_batch(np.arange(2)), ms(10))
        with pytest.raises(ValueError, match="window_len"):
            rt.set_window_len(rt.init_batch(np.arange(2)), 0)


# ---------------------------------------------------------------------------
# 3. digest, report, counter tracks
# ---------------------------------------------------------------------------

class TestDigestAndReport:
    def test_compiled_out_is_none(self):
        rt = _pingpong_rt(windows=0)
        st, _ = rt.run(rt.init_batch(np.arange(2)), 128, 64)
        assert series_digest(st) is None
        assert series_counters(st) is None
        assert series_summary(st) is None
        assert lane_series(st) is None
        assert lane_burst(st) is None
        assert summarize(rt, st)["series"] is None
        assert "compiled out" in format_series(None)
        assert series_counter_track_events(st) == []

    def test_counters_merge_exactly_over_recording_lanes(self):
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=40, lat=24)
        st = rt.run_fused(rt.init_batch(np.arange(8),
                                        series_lanes=[1, 4]), 256, 64)
        c = series_counters(st)
        assert c["lanes"] == 2 and c["window_len"] == ms(25)
        disp = np.asarray(st.sr_dispatch).astype(np.int64)
        assert (c["dispatch"] == disp[[1, 4]].sum(0)).all()
        assert c["qhw"] == np.asarray(st.sr_qhw)[[1, 4]].max(0).tolist()
        comp = np.asarray(st.sr_complete).astype(np.int64)
        assert c["complete"] == comp[[1, 4]].sum(0).tolist()
        # all-masked batch reads zero, not garbage
        st0 = rt.run_fused(rt.init_batch(np.arange(4), series_lanes=[]),
                           128, 64)
        c0 = series_counters(st0)
        assert c0["lanes"] == 0 and c0["dispatch"].sum() == 0

    def test_window_p99_is_bucket_cdf_lower_bound(self):
        # crafted window histograms: window 0 holds 100 samples in
        # bucket 3 ([4, 8)) and 1 in bucket 10 ([512, 1024)) — p99
        # reads edge 4; window 1 holds 7 in bucket 10 — edge 512;
        # untouched windows read 0. Exact, deterministic.
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=40, lat=24)
        st = rt.init_batch(np.arange(2))
        sl = np.zeros(np.asarray(st.sr_lat).shape, np.int32)
        sl[:, 0, 3] = 100
        sl[:, 0, 10] = 1
        sl[:, 1, 10] = 7
        st = st.replace(sr_lat=jnp.asarray(sl))
        c = series_counters(st)
        assert c["e2e_p99_by_window"] == [4, 512, 0, 0]
        ls = lane_series(st, 0)
        assert ls["e2e_p99"].tolist() == [4, 512, 0, 0]

    def test_summary_rows_and_render(self):
        sc = Scenario()
        sc.at(ms(30)).unclog_node(0)
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=60,
                          lat=24, scenario=sc)
        st = rt.run_fused(rt.init_batch(np.arange(4)), 1024, 256)
        s = series_summary(st)
        assert s["windows"] == 4 and len(s["rows"]) == 4
        assert [r["t0_us"] for r in s["rows"]] == [0, ms(25), ms(50),
                                                   ms(75)]
        assert s["rows"][0]["faults"] == ["boot"]    # the t=0 boots
        assert s["rows"][1]["faults"] == ["heal"]
        assert sum(r["dispatches"] for r in s["rows"]) > 0
        txt = format_series(s)
        assert "p99_us" in txt and "heal" in txt
        rep = summarize(rt, st, np.arange(4))["series"]
        assert rep["windows"] == 4 and rep["dispatch_peak"] > 0
        assert rep["fault_windows"] == [0, 1]
        assert fault_names(T.SRF_PARTITION | T.SRF_HEAL) == ["partition",
                                                             "heal"]

    def test_counter_tracks_ride_true_sim_time(self):
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=60,
                          lat=24, trace_cap=64)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 1024, 256)
        evs = counter_track_events(st, lane=0)   # prefers the series
        names = {e["name"] for e in evs}
        assert {"queue_depth", "e2e_p99", "fault"} <= names
        qd = sorted(e["ts"] for e in evs if e["name"] == "queue_depth")
        assert qd[0] == 0 and qd[1] - qd[0] == ms(25)
        # masked lane -> [] and the caller falls back to the ring path
        stm = rt.run_fused(rt.init_batch(np.arange(2), series_lanes=[1]),
                           1024, 256)
        assert series_counter_track_events(stm, lane=0) == []
        fb = {e["name"] for e in counter_track_events(stm, lane=0)}
        assert "queue_depth" not in fb
        assert any(n.startswith("e2e_p99:") for n in fb)

    def test_counter_tracks_on_series_only_build(self):
        # ring compiled out entirely: the series tracks stand on their
        # own instead of raising the ring's "compiled out" ValueError
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=60, lat=24)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 1024, 256)
        names = {e["name"] for e in counter_track_events(st, lane=0)}
        assert {"queue_depth", "e2e_p99", "fault"} <= names
        # both planes out -> still the honest ring error
        rt0 = _pingpong_rt()
        st0 = rt0.run_fused(rt0.init_batch(np.arange(2)), 256, 256)
        with pytest.raises(ValueError, match="compiled out"):
            counter_track_events(st0, lane=0)

    def test_dashboard_sim_time_sparklines(self):
        from madsim_tpu.obs.dashboard import (render_html,
                                              series_sparklines_html)
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=60, lat=24)
        st = rt.run_fused(rt.init_batch(np.arange(2)), 1024, 256)
        s = series_summary(st)
        html = series_sparklines_html(s)
        assert "<svg" in html and "Sim-time telemetry" in html
        assert "4 windows" in html and "25000us" in html
        assert "Dispatches / window" in html
        assert "e2e p99 / window" in html       # latency build only
        assert "boot" in html                   # w0 fault-marker footnote
        assert series_sparklines_html(None) == ""
        # render_html includes the section iff the snapshot carries it
        attr = {k: {"base": 1} for k in
                ("recipe_coverage", "recipe_buckets",
                 "operator_coverage", "operator_buckets")}
        cur = {"store": {}, "curves": {}, "attribution": attr,
               "buckets": {}}
        assert "Sim-time telemetry" in render_html(dict(cur, series=s),
                                                   None)
        assert "Sim-time telemetry" not in render_html(cur, None)


# ---------------------------------------------------------------------------
# 4. the recovery oracle
# ---------------------------------------------------------------------------

class TestRecoveryInvariant:
    def _oracle_rt(self, **kw):
        return _pingpong_rt(windows=4, window_len=ms(100), target=40,
                            lat=24, invariant=recovery_invariant(**kw))

    def _prime(self, rt, fault_w=0, qhw=(0, 0, 0, 0), heal_w=None):
        # craft a lane history: now deep enough that all 4 windows are
        # complete, a disruptive marker in fault_w, optional heal
        # marker, per-window queue high-waters — then step once so the
        # oracle judges it
        st = rt.init_batch(np.arange(4))
        f = np.zeros(np.asarray(st.sr_fault).shape, np.int32)
        f[:, fault_w] = T.SRF_PARTITION
        if heal_w is not None:
            f[:, heal_w] |= T.SRF_HEAL
        q = np.broadcast_to(np.asarray(qhw, np.int32),
                            np.asarray(st.sr_qhw).shape)
        st = st.replace(sr_fault=jnp.asarray(f), sr_qhw=jnp.asarray(q),
                        now=jnp.full_like(st.now, ms(450)))
        out, _ = rt.run(st, 1, 1)
        return out

    def test_arg_validation(self):
        with pytest.raises(ValueError, match="p99_le"):
            recovery_invariant()
        with pytest.raises(ValueError, match="within"):
            recovery_invariant(qhw_le=5, within=0)

    def test_raises_on_compiled_out_plane(self):
        rt = _pingpong_rt(windows=0,
                          invariant=recovery_invariant(qhw_le=5))
        with pytest.raises(ValueError, match="series_windows"):
            rt.run(rt.init_batch(np.arange(2)), 64, 64)

    def test_p99_form_needs_latency_plane(self):
        rt = _pingpong_rt(windows=4,
                          invariant=recovery_invariant(p99_le=ms(1)))
        with pytest.raises(ValueError, match="latency plane"):
            rt.run(rt.init_batch(np.arange(2)), 64, 64)

    def test_judges_only_past_grace_and_fires_with_crash_recovery(self):
        rt = self._oracle_rt(qhw_le=8, within=2)
        # fault in w0, queue still deep in w3 (a judged window): red
        red = self._prime(rt, fault_w=0, qhw=(50, 50, 50, 50))
        assert (np.asarray(red.crash_code) == CRASH_RECOVERY).all()
        # deep queue only INSIDE the grace windows (w0-w1): tolerated
        green = self._prime(rt, fault_w=0, qhw=(50, 50, 3, 3))
        assert not np.asarray(green.crashed).any()

    def test_heal_does_not_restart_the_clock(self):
        # the cure is not the disease: a heal marker after the fault
        # leaves judging anchored at the DISRUPTIVE window, so a
        # still-deep queue in w3 fires even with a heal in w2
        rt = self._oracle_rt(qhw_le=8, within=2)
        st = self._prime(rt, fault_w=0, qhw=(50, 50, 3, 50), heal_w=2)
        assert (np.asarray(st.crash_code) == CRASH_RECOVERY).all()

    def test_fault_too_late_leaves_nothing_to_judge(self):
        rt = self._oracle_rt(qhw_le=8, within=2)
        st = self._prime(rt, fault_w=3, qhw=(50, 50, 50, 50))
        assert not np.asarray(st.crashed).any()

    def test_no_fault_never_fires(self):
        # the oracle judges recovery, not steady state: a fault-free
        # run is green even with an unattainable envelope
        rt = self._oracle_rt(qhw_le=0, within=1)
        st = rt.run_fused(rt.init_batch(np.arange(4)), 256, 64)
        assert not np.asarray(st.crashed).any()

    @pytest.mark.slow
    def test_flagship_green_red_and_seed_replay(self):
        # the canonical recovery flagship (models/workloads.recovery):
        # a clogged-then-unclogged echo cluster recovers inside the
        # grace windows (green); the unhealed latency fault keeps p99
        # pinned past them (red, CRASH_RECOVERY), and the crash replays
        # fingerprint-exact by seed — the repro contract
        from madsim_tpu.models.workloads import recovery
        inv = recovery_invariant(p99_le=ms(20), within=4, min_count=8)
        seeds = np.arange(8, dtype=np.uint32)
        rt_g = recovery("heal", invariant=inv)
        g = rt_g.run_fused(rt_g.init_batch(seeds), 40000, 2048)
        assert not np.asarray(g.crashed).any()
        # green lanes outlive the 8 x 625 ms timeline: the post-heal
        # windows were judged, not skipped
        assert (np.asarray(g.now) >= 8 * ms(625)).all()
        f = np.asarray(g.sr_fault)
        assert (f[:, 1] & T.SRF_PARTITION != 0).all()
        assert (f[:, 4] & T.SRF_HEAL != 0).all()
        rt_r = recovery("noheal", invariant=inv)
        a = rt_r.run_fused(rt_r.init_batch(seeds), 40000, 2048)
        b = rt_r.run_fused(rt_r.init_batch(seeds), 40000, 2048)
        assert (np.asarray(a.crash_code) == CRASH_RECOVERY).all()
        assert (rt_r.fingerprints(a) == rt_r.fingerprints(b)).all()
        single, _ = rt_r.run_single(int(seeds[3]), 40000, 2048)
        assert int(np.asarray(single.crash_code)[0]) == CRASH_RECOVERY


# ---------------------------------------------------------------------------
# 5. burst-guided fuzzing
# ---------------------------------------------------------------------------

class TestBurstBonus:
    def test_corpus_burst_bonus_scales_admission_energy(self):
        from madsim_tpu.models.workloads import saturating
        from madsim_tpu.search.corpus import Corpus
        from madsim_tpu.search.mutate import KnobPlan
        rt = saturating()
        plan = KnobPlan.from_runtime(rt)
        c = Corpus(plan, burst_bonus=1.0)
        kb = plan.base_batch(2)
        c.observe(kb, np.arange(2), np.asarray([1, 2], np.uint64),
                  np.zeros(2, bool), np.zeros(2, np.int64),
                  np.full(2, -1, np.int64), 0,
                  burst=np.asarray([100, 1000], np.int32))
        by_hash = {e["hash"]: e["energy"] for e in c.entries}
        assert by_hash[2] == pytest.approx(2.0)    # worst spike: x(1+1)
        assert by_hash[1] == pytest.approx(1.1)    # 100/1000 relative
        # burst-blind corpus ignores the signal entirely
        c0 = Corpus(plan, burst_bonus=0.0)
        c0.observe(kb, np.arange(2), np.asarray([1, 2], np.uint64),
                   np.zeros(2, bool), np.zeros(2, np.int64),
                   np.full(2, -1, np.int64), 0,
                   burst=np.asarray([100, 1000], np.int32))
        assert all(e["energy"] == 1.0 for e in c0.entries)

    @pytest.mark.slow
    def test_burst_fuzz_opens_a_replaying_recovery_crash(self):
        # mutants of the healed recovery flagship that move the unclog,
        # fatten the floor or re-clog late fail to return to envelope;
        # a burst-guided campaign finds one, and its (seed, knobs)
        # handle replays red
        from madsim_tpu import fuzz
        from madsim_tpu.models.workloads import recovery
        from madsim_tpu.search.mutate import apply_repro_knobs
        inv = recovery_invariant(p99_le=ms(20), within=4, min_count=8)
        rt = recovery("heal", invariant=inv)
        res = fuzz(rt, max_steps=40000, batch=64, max_rounds=3,
                   dry_rounds=4, chunk=512, burst_bonus=1.0)
        rep = res["crash_repros"].get(CRASH_RECOVERY)
        assert rep is not None, sorted(res["crash_repros"])
        st = rt.init_batch(np.asarray([rep["seed"]], np.uint32))
        st, _ = apply_repro_knobs(rt, st, rep["knobs"])
        fin = rt.run_fused(st, 60000, 512)
        assert int(np.asarray(fin.crash_code)[0]) == CRASH_RECOVERY

    def test_lane_burst_reads_deepest_transient_spike(self):
        # lane 0's spike lives in window 0 (p99 edge 4), lane 1's in
        # window 1 (edge 512): the per-lane metric keeps windows
        # separate and maxes over them — the signal an aggregate p99
        # would dilute
        rt = _pingpong_rt(windows=4, window_len=ms(25), target=40, lat=24)
        st = rt.init_batch(np.arange(2))
        sl = np.zeros(np.asarray(st.sr_lat).shape, np.int32)
        sl[0, 0, 3] = 100
        sl[1, 1, 10] = 100
        st = st.replace(sr_lat=jnp.asarray(sl))
        assert lane_burst(st).tolist() == [4, 512]
        # latency-less builds fall back to the queue high-water
        rt0 = _pingpong_rt(windows=2, window_len=ms(25), target=40)
        st0 = rt0.init_batch(np.arange(2))
        st0 = st0.replace(sr_qhw=jnp.asarray([[5, 2], [1, 9]], jnp.int32))
        assert lane_burst(st0).tolist() == [5, 9]


# ---------------------------------------------------------------------------
# 6. checkpoint migration
# ---------------------------------------------------------------------------

class TestCheckpointMigration:
    def test_pre_r21_checkpoint_rejected_by_leaf_count(self, tmp_path):
        # the MIGRATION r21 contract: a pre-r21 checkpoint (no sr_*/
        # window_len leaves — 11 fewer) fails load() loudly on the leaf
        # count, not by silent misalignment
        from madsim_tpu.runtime import checkpoint
        rt = _pingpong_rt(windows=4, lat=24)
        st = rt.init_batch(np.arange(2))
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, st)
        with np.load(p) as z:
            leaves = {k: z[k] for k in z.files}
        n = len([k for k in leaves if k.startswith("leaf_")])
        stripped = {k: v for k, v in leaves.items()
                    if not k.startswith("leaf_")}
        for i in range(n - len(SR_LEAVES)):
            stripped[f"leaf_{i}"] = leaves[f"leaf_{i}"]
        p2 = str(tmp_path / "old.npz")
        np.savez_compressed(p2, **stripped)
        with pytest.raises(ValueError, match="leaves"):
            checkpoint.load(p2, st)
