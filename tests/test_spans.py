"""Critical-path attribution plane (r23, DESIGN §24): where a tail
request's time went, answered identically on device and host.

The load-bearing properties: (1) the plane is an observation lever —
span-on/compiled-out trajectories are bit-identical leaf-for-leaf
against the captured r22 truth, chunked and fused, and the
sp_on/ev_span/sa_*/tr_qw leaves are excluded from fingerprints; (2) the
device's per-(lane, node) `sa_tail` fold — tail count, queue-wait, net,
hops — EQUALS a host parent-walk of the flight-recorder ring, and every
tail completion names exactly one `sa_bottleneck` node, agreeing with
the host's first-strict-max dominant rule; (3) host request spans
TELESCOPE: Σ wait + Σ transit == the ring's e2e latency, exactly;
(4) `explain_latency` names the same request on re-run and recovers
wrap-truncated chains by r20 window replay; (5) the Chrome-trace export
grows `ph:"b"/"e"` request duration spans exactly when the plane is on
— a span-off document is byte-identical to the frozen r22 capture;
(6) pre-r23 checkpoints are rejected loudly (simconfig-v8).
"""

import json
import os

import numpy as np
import pytest

from madsim_tpu import (CheckpointLog, NetConfig, Runtime, Scenario,
                        SimConfig, explain_latency, export_chrome_trace,
                        format_span, ms, request_spans, ring_records, sec,
                        summarize)
from madsim_tpu.core.state import TRACE_FIELDS
from madsim_tpu.core.types import EV_MSG
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.models.rpc_echo import TAG_ECHO, make_echo_runtime
from madsim_tpu.net import rpc
from madsim_tpu.obs.spans import request_span
from madsim_tpu.parallel.stats import (attribution_brief,
                                       attribution_counters)

import _span_golden as golden

# the 5 leaves the r23 plane added (MIGRATION r23)
SPAN_LEAVES = ("sp_on", "ev_span", "sa_tail", "sa_bottleneck", "tr_qw")

RTAG = rpc.reply_tag(TAG_ECHO)
SLO = ms(8)
SEEDS = np.arange(8, dtype=np.uint32)


def _echo_rt(span):
    """Chaos rpc_echo: kill/restart mid-run, reply deliveries both
    complete a call and re-mint the next request's root."""
    sc = Scenario()
    sc.at(ms(300)).kill(0)
    sc.at(ms(420)).restart(0)
    cfg = SimConfig(
        n_nodes=4, event_capacity=64, time_limit=sec(5),
        latency_hist=24, trace_cap=512,
        complete_kinds=((EV_MSG, RTAG),),
        root_kinds=((EV_MSG, RTAG),),
        slo_target=SLO, span_attr=span,
        net=NetConfig(send_latency_min=ms(1), send_latency_max=ms(8)))
    return make_echo_runtime(n_nodes=4, target=8, scenario=sc, cfg=cfg)


def _pp_rt(trace_cap=1024):
    """Pause/resume pingpong: parked deadlines produce NONZERO
    queue-wait — the span component a chaos-free EDF never exercises."""
    sc = Scenario()
    sc.at(ms(30)).pause(1)
    sc.at(ms(90)).resume(1)
    cfg = SimConfig(n_nodes=3, time_limit=sec(5), latency_hist=24,
                    trace_cap=trace_cap, complete_kinds=((EV_MSG, 1),),
                    slo_target=ms(6), span_attr=True,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [PingPong(3, target=40)], state_spec(),
                   scenario=sc)


@pytest.fixture(scope="module")
def echo_states():
    rt_on, rt_off = _echo_rt(True), _echo_rt(False)
    on, _ = rt_on.run(rt_on.init_batch(SEEDS), 2048, 256)
    off, _ = rt_off.run(rt_off.init_batch(SEEDS), 2048, 256)
    fused = rt_on.run_fused(rt_on.init_batch(SEEDS), 2048, 256)
    return rt_on, rt_off, on, off, fused


@pytest.fixture(scope="module")
def pp_state():
    rt = _pp_rt()
    st, _ = rt.run(rt.init_batch(SEEDS), 400, 100)
    return rt, st


# ---------------------------------------------------------------------------
# 1. bit-identical-when-disabled, against r22 captured truth
# ---------------------------------------------------------------------------

class TestEquivalenceR22:
    @pytest.mark.parametrize("workload", sorted(golden.BUILDERS))
    def test_leaf_for_leaf_vs_r22_golden(self, workload):
        # scripts/capture_golden.py froze these digests AT r22 HEAD,
        # before any r23 engine change: every r22 leaf must still hash
        # identically, chunked and fused; the ONLY new leaves are the
        # attribution plane's own (zero-size here — the frozen
        # workloads never set span_attr)
        gold = golden.load_golden()[workload]
        got = golden.run_workload(workload)
        for runner in ("run", "run_fused"):
            missing = [k for k in gold[runner] if k not in got[runner]]
            assert not missing, (runner, missing)
            diff = [k for k in gold[runner]
                    if gold[runner][k] != got[runner][k]]
            assert not diff, (runner, diff)
            new = set(got[runner]) - set(gold[runner])
            assert new == {"." + n for n in SPAN_LEAVES}, new


# ---------------------------------------------------------------------------
# 2. the observation-lever contract on live runs
# ---------------------------------------------------------------------------

class TestSpanPlane:
    def test_span_never_perturbs_trajectory(self, echo_states):
        rt_on, rt_off, on, off, fused = echo_states
        assert (rt_on.fingerprints(on) == rt_off.fingerprints(off)).all()
        assert (rt_on.fingerprints(on) == rt_on.fingerprints(fused)).all()
        for f in TRACE_FIELDS:
            assert (np.asarray(getattr(on, f))
                    == np.asarray(getattr(fused, f))).all(), f

    def test_masked_lanes_accumulate_nothing(self, echo_states):
        rt_on, _, on, _, _ = echo_states
        masked = rt_on.run_fused(
            rt_on.init_batch(SEEDS, span_lanes=[0, 3]), 2048, 256)
        assert (rt_on.fingerprints(masked) == rt_on.fingerprints(on)).all()
        sa = np.asarray(masked.sa_tail)
        sb = np.asarray(masked.sa_bottleneck)
        rec = np.zeros(len(SEEDS), bool)
        rec[[0, 3]] = True
        assert (sa[~rec] == 0).all() and (sb[~rec] == 0).all()
        assert (sa[rec] == np.asarray(on.sa_tail)[rec]).all()
        assert (sb[rec] == np.asarray(on.sa_bottleneck)[rec]).all()

    def test_span_lanes_requires_compiled_plane(self, echo_states):
        _, rt_off, _, _, _ = echo_states
        with pytest.raises(ValueError, match="span"):
            rt_off.init_batch(SEEDS, span_lanes=[0])

    def test_span_attr_requires_latency_plane(self):
        with pytest.raises(AssertionError, match="span_attr"):
            SimConfig(n_nodes=2, span_attr=True)

    def test_signature_is_v8_and_span_attr_is_structural(self):
        # r23's bump — this file owns the authoritative assertion
        cfg = SimConfig(n_nodes=2)
        assert cfg.structural_signature()[0] == "simconfig-v8"
        a = SimConfig(n_nodes=2, latency_hist=24,
                      complete_kinds=((EV_MSG, 1),), span_attr=True)
        b = SimConfig(n_nodes=2, latency_hist=24,
                      complete_kinds=((EV_MSG, 1),))
        assert a.structural_signature() != b.structural_signature()


# ---------------------------------------------------------------------------
# 3. device fold == host parent-walk, component for component
# ---------------------------------------------------------------------------

class TestDeviceHostAgreement:
    def test_tail_count_and_bottleneck_close(self, echo_states):
        rt_on, _, on, _, _ = echo_states
        sa = np.asarray(on.sa_tail)
        # the SA_COUNT component IS the latency plane's slo-miss
        # counter, per node — one fold, two consumers
        assert (sa[:, :, 0] == np.asarray(on.lh_slo_miss)).all()
        assert sa[:, :, 0].sum() > 0, "workload produced no tails"
        # every tail completion names exactly one dominant node
        assert np.asarray(on.sa_bottleneck).sum() == sa[:, :, 0].sum()

    def test_device_attribution_equals_host_walk(self, echo_states):
        rt_on, _, on, _, _ = echo_states
        sa = np.asarray(on.sa_tail)
        walked = 0
        for b in range(len(SEEDS)):
            recs = ring_records(on, b)
            assert recs["dropped"] == 0, "ring must hold the history"
            lat = np.asarray(recs["lat"])
            qw = np.asarray(recs["qw"])
            step_at = {int(s): i for i, s in enumerate(recs["step"])}
            hq = hn = hh = 0
            for i in np.nonzero(lat >= 0)[0]:
                if lat[i] <= SLO:
                    continue            # only tails attribute
                # parent-walk to the root: sum each hop's queue-wait,
                # count hops; the remainder of e2e is transit. An
                # externally minted element IS the root (core/step.py
                # root rule) — its own wait belongs to no request.
                j, q, hops = int(i), 0, 0
                while True:
                    p = int(recs["parent"][j])
                    if p < 0 or p not in step_at:
                        break           # j is the external root
                    q += int(qw[j])
                    hops += 1
                    jp = step_at[p]
                    if (int(recs["kind"][jp]) == EV_MSG
                            and int(recs["tag"][jp]) == RTAG):
                        break           # completion -> root re-mint
                    j = jp
                hq += q
                hn += int(lat[i]) - q
                hh += hops
                walked += 1
            assert (hq, hn, hh) == (sa[b, :, 1].sum(), sa[b, :, 2].sum(),
                                    sa[b, :, 3].sum()), b
        assert walked == sa[:, :, 0].sum() > 0

    def test_spans_telescope_and_match_device(self, pp_state):
        rt, st = pp_state
        sa = np.asarray(st.sa_tail)
        assert sa[:, :, 1].sum() > 0, \
            "pause/resume must produce nonzero queue-wait"
        for b in range(len(SEEDS)):
            spans = request_spans(st, b, slo_target=ms(6))
            assert spans
            for sp in spans:
                if not sp["truncated"]:
                    assert (sp["wait_us"] + sp["transit_us"]
                            == sp["lat_us"]), sp
            tl = [s for s in spans if s["tail"] and not s["truncated"]]
            assert sum(s["wait_us"] for s in tl) == sa[b, :, 1].sum()
            assert sum(s["transit_us"] for s in tl) == sa[b, :, 2].sum()
            assert sum(len(s["hops"]) for s in tl) == sa[b, :, 3].sum()
            # the host's first-strict-max dominant fold == the device's
            # bottleneck histogram, node for node
            bn = np.zeros(3, np.int64)
            for s in tl:
                bn[s["dominant"]["node"]] += 1
            assert (bn == np.asarray(st.sa_bottleneck)[b]).all(), b

    def test_spans_raise_without_plane(self, echo_states):
        _, _, _, off, _ = echo_states
        with pytest.raises(ValueError, match="span_attr"):
            request_spans(off, 0)


# ---------------------------------------------------------------------------
# 4. explain_latency: deterministic naming, replay recovery
# ---------------------------------------------------------------------------

class TestExplainLatency:
    def test_names_slowest_deterministically(self, pp_state):
        rt, st = pp_state
        e1 = explain_latency(st, 2, rt=rt)
        e2 = explain_latency(st, 2, rt=rt)
        assert e1 == e2
        lat = np.asarray(ring_records(st, 2)["lat"])
        assert e1["lat_us"] == int(lat[lat >= 0].max())
        assert e1["slo_target"] == ms(6) and e1["slo_miss"]
        assert not e1["truncated"] and not e1["replayed"]
        assert format_span(e1)

    def test_rank_walks_down_the_tail(self, pp_state):
        rt, st = pp_state
        lats = [explain_latency(st, 2, rank=r, rt=rt)["lat_us"]
                for r in range(3)]
        assert lats == sorted(lats, reverse=True)
        with pytest.raises(ValueError, match="rank"):
            explain_latency(st, 2, rank=10_000, rt=rt)

    def test_replay_recovers_wrapped_chain(self, tmp_path):
        # a 16-slot ring wraps long before the pingpong chains root
        # (no root_kinds -> chains reach the t=0 external mint), so the
        # live answer is a truncated suffix; window replay from the
        # harvested checkpoint log must recover the FULL chain and
        # agree with a full-size-ring control, hop for hop
        rt = _pp_rt(trace_cap=16)
        log = CheckpointLog()
        st, _ = rt.run(rt.init_batch(SEEDS), 400, 100,
                       ckpt_every=64, ckpt_log=log)
        live = explain_latency(st, 1, rt=rt)
        assert live["truncated"], "specimen must wrap"
        trace = str(tmp_path / "replayed.json")
        rec = explain_latency(st, 1, rt=rt, replay=True, ckpts=log,
                              export_trace=trace)
        assert rec["replayed"] and not rec["truncated"]
        assert rec["step"] == live["step"]
        assert rec["lat_us"] == live["lat_us"]
        assert rec["wait_us"] + rec["transit_us"] == rec["lat_us"]
        assert os.path.exists(rec["trace_path"])

        rt_big, big = _pp_rt(trace_cap=2048), None
        big, _ = rt_big.run(rt_big.init_batch(SEEDS), 400, 100)
        ctrl = request_span(ring_records(big, 1), rec["step"])
        assert not ctrl["truncated"]
        assert len(rec["hops"]) == len(ctrl["hops"])
        assert rec["wait_us"] == ctrl["wait_us"]
        assert rec["transit_us"] == ctrl["transit_us"]
        assert rec["dominant"] == ctrl["dominant"]
        assert rec["root"]["step"] == ctrl["root"]["step"]

    def test_replay_without_runtime_raises(self, tmp_path):
        rt = _pp_rt(trace_cap=16)
        st, _ = rt.run(rt.init_batch(SEEDS), 400, 100)
        with pytest.raises(ValueError, match="rt="):
            explain_latency(st, 1, replay=True)


# ---------------------------------------------------------------------------
# 5. the host rollups: stats triple, summarize, trace export
# ---------------------------------------------------------------------------

class TestRollups:
    def test_attribution_counters_and_brief(self, echo_states):
        rt_on, _, on, _, _ = echo_states
        c = attribution_counters(on)
        sa = np.asarray(on.sa_tail).astype(np.int64)
        assert (c["tail"] == sa.sum(0)).all()
        assert c["bottleneck"] == np.asarray(on.sa_bottleneck) \
            .sum(0).tolist()
        assert c["slo_target"] == SLO
        brief = attribution_brief(on)
        assert brief["tails"] == int(sa[:, :, 0].sum())
        assert brief["qwait_us"] + brief["net_us"] > 0
        assert 0.0 <= brief["wait_share"] <= 1.0
        s = summarize(rt_on, on, SEEDS)
        assert s["attribution"]["tails"] == brief["tails"]
        assert s["latency"]["slo_target"] == SLO

    def test_rollups_none_when_compiled_out(self, echo_states):
        _, rt_off, _, off, _ = echo_states
        assert attribution_brief(off) is None
        assert summarize(rt_off, off, SEEDS)["attribution"] is None

    def test_trace_grows_request_spans_iff_on(self, pp_state, tmp_path,
                                              echo_states):
        _, st = pp_state
        _, _, _, off, _ = echo_states
        p = str(tmp_path / "t.json")
        export_chrome_trace(p, state=st, lane=2)
        with open(p) as f:
            doc = json.load(f)["traceEvents"]
        spans = [e for e in doc if e.get("ph") in ("b", "e")]
        assert spans and len(spans) % 2 == 0
        lat = np.asarray(ring_records(st, 2)["lat"])
        assert len(spans) == 2 * int((lat >= 0).sum())
        b0 = next(e for e in doc if e.get("ph") == "b")
        assert b0["cat"] == "request" and b0["args"]["lat_us"] >= 0
        export_chrome_trace(p, state=off, lane=0)
        with open(p) as f:
            phs = {e.get("ph") for e in json.load(f)["traceEvents"]}
        assert "b" not in phs and "e" not in phs

    def test_span_off_trace_is_byte_identical_to_r22(self, tmp_path):
        # the frozen pingpong golden workload (span never on), exported
        # into data/golden_r22_trace.json (frozen at r22, re-frozen at
        # PR 21 under jax 0.9.0): the export path must reproduce it byte
        # for byte
        p = str(tmp_path / "pp.json")
        golden.export_span_off_trace(p)
        with open(p, "rb") as a, open(golden.TRACE_PATH, "rb") as g:
            assert a.read() == g.read(), \
                "span-off export must stay byte-identical to r22"


# ---------------------------------------------------------------------------
# 6. pre-r23 checkpoints are rejected loudly
# ---------------------------------------------------------------------------

class TestCheckpointGate:
    def test_pre_r23_checkpoint_rejected(self, tmp_path):
        # a pre-r23 batch checkpoint (no span leaves — 5 fewer) fails
        # load() loudly on the leaf count, not by silent misalignment
        from madsim_tpu.runtime import checkpoint
        rt = _pp_rt()
        st = rt.init_batch(np.arange(2))
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, st)
        with np.load(p) as z:
            leaves = {k: z[k] for k in z.files}
        n = len([k for k in leaves if k.startswith("leaf_")])
        stripped = {k: v for k, v in leaves.items()
                    if not k.startswith("leaf_")}
        for i in range(n - len(SPAN_LEAVES)):
            stripped[f"leaf_{i}"] = leaves[f"leaf_{i}"]
        p2 = str(tmp_path / "old.npz")
        np.savez_compressed(p2, **stripped)
        with pytest.raises(ValueError, match="leaves"):
            checkpoint.load(p2, st)
