"""Causal lineage layer (r10): happens-before edges, Lamport clocks,
crash explanation, prefix-coverage divergence telemetry.

Load-bearing properties (DESIGN §12):
(1) lineage + sketch are OBSERVERS — every non-trace leaf is
bit-identical whether they are compiled out, compiled in but unsampled,
or fully sampling, across the chunked AND fused runners (the fast-lane
single-config check lives here; the raft/wal_kv/shard_kv chaos-matrix
equivalence rides the `slow` lane in test_obs, whose ring-equivalence
sweeps now carry the lineage columns and a compiled-in sketch too);
(2) parent edges are DISPATCH INDICES, meaningful across ring wrap —
a chain truncates honestly instead of mis-resolving;
(3) Lamport clocks respect the happens-before order along any chain;
(4) the sketch folds the schedule prefix so divergence DEPTH is
readable per lane without any mid-run host traffic.
"""

import io
import json

import numpy as np
import pytest

from madsim_tpu import (NetConfig, Runtime, Scenario, SimConfig,
                        divergence_profile, explain_crash, fuzz, ms, sec,
                        summarize)
from madsim_tpu.core import types as T
from madsim_tpu.core.state import TRACE_FIELDS as _TRACE_FIELDS
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.obs import (causal_fingerprint, code_fingerprint,
                            export_chrome_trace, fingerprints_match,
                            happens_before, ring_records,
                            sketch_divergence, to_chrome_events)
from madsim_tpu.parallel.stats import first_divergence_slots
from madsim_tpu.search.corpus import Corpus
from madsim_tpu.search.mutate import KnobPlan


def _pingpong_rt(trace_cap=0, sketch_slots=0, sketch_every=64, target=3,
                 n_nodes=2, scenario=None, loss=0.0):
    cfg = SimConfig(n_nodes=n_nodes, time_limit=sec(5), trace_cap=trace_cap,
                    sketch_slots=sketch_slots, sketch_every=sketch_every,
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [PingPong(n_nodes, target=target)], state_spec(),
                   scenario=scenario)


def _crashrich_wal_kv(trace_cap=0, sketch_slots=0):
    """The crash-rich wal_kv chaos matrix (`models/workloads`)."""
    from madsim_tpu.models.workloads import crashrich
    return crashrich("wal_kv", trace_cap=trace_cap,
                     sketch_slots=sketch_slots)


def _nontrace_state(state) -> dict:
    out = {}
    for name in type(state).__dataclass_fields__:
        if name in _TRACE_FIELDS or name in ("node_state", "ext"):
            continue
        out[name] = np.asarray(getattr(state, name))
    for i, leaf in enumerate(__import__("jax").tree.leaves(state.node_state)):
        out[f"node_state_{i}"] = np.asarray(leaf)
    return out


class TestNeverPerturbs:
    """The fast-lane r10 equivalence: lineage + sketch columns never
    perturb the trajectory, leaf for leaf, on both runners."""

    def test_lineage_and_sketch_never_perturb(self):
        seeds = np.arange(16, dtype=np.uint32)
        rt0 = _pingpong_rt()
        base, _ = rt0.run(rt0.init_batch(seeds), 256, 64)
        ref = _nontrace_state(base)
        for cap, sk, lanes in ((8, 0, None), (8, 8, []), (8, 8, [0, 3]),
                               (0, 8, None)):
            rt = _pingpong_rt(trace_cap=cap, sketch_slots=sk,
                              sketch_every=16)
            kw = {} if cap == 0 or lanes is None else dict(
                trace_lanes=lanes)
            st, _ = rt.run(rt.init_batch(seeds, **kw), 256, 64)
            got = _nontrace_state(st)
            assert ref.keys() == got.keys()
            for k in ref:
                assert (ref[k] == got[k]).all(), \
                    f"cap={cap} sketch={sk} lanes={lanes} perturbed {k}"

    def test_fused_equals_chunked_with_lineage_and_sketch(self):
        rt = _pingpong_rt(trace_cap=8, sketch_slots=8, sketch_every=16,
                          target=40)
        seeds = np.arange(8, dtype=np.uint32)
        chunked, _ = rt.run(rt.init_batch(seeds), 256, 64)
        fused = rt.run_fused(rt.init_batch(seeds), 256, 64)
        assert (rt.fingerprints(chunked) == rt.fingerprints(fused)).all()
        for f in _TRACE_FIELDS:
            assert (np.asarray(getattr(chunked, f))
                    == np.asarray(getattr(fused, f))).all(), f

    def test_fingerprints_ignore_lineage_and_sketch(self):
        seeds = np.arange(8, dtype=np.uint32)
        on = _pingpong_rt(trace_cap=8, sketch_slots=4)
        off = _pingpong_rt()
        a, _ = on.run(on.init_batch(seeds), 256, 64)
        b, _ = off.run(off.init_batch(seeds), 256, 64)
        assert (on.fingerprints(a) == off.fingerprints(b)).all()


class TestLineage:
    def test_parent_edges_resolve_and_precede(self):
        rt = _pingpong_rt(trace_cap=128, target=12)
        st = rt.run_fused(rt.init_batch(np.arange(2, dtype=np.uint32)),
                          256, 64)
        recs = ring_records(st, lane=0)
        assert "parent" in recs and "lamport" in recs
        edges = happens_before(recs)
        assert edges, "no resolvable happens-before edges"
        for p, c in edges:
            assert p < c, (p, c)
        # nothing dropped (cap > steps), so every non-external parent
        # resolves: the ring IS the full happens-before DAG here
        steps = set(recs["step"].tolist())
        for par, s in zip(recs["parent"], recs["step"]):
            assert par == -1 or int(par) in steps, (par, s)
        # the t=0 boots are external causes
        assert int(recs["parent"][0]) == -1

    def test_lamport_clocks_respect_happens_before(self):
        rt = _pingpong_rt(trace_cap=128, target=12)
        st = rt.run_fused(rt.init_batch(np.arange(2, dtype=np.uint32)),
                          256, 64)
        recs = ring_records(st, lane=1)
        by_step = {int(s): i for i, s in enumerate(recs["step"])}
        for p, c in happens_before(recs):
            assert (recs["lamport"][by_step[p]]
                    < recs["lamport"][by_step[c]]), (p, c)

    def test_explain_crash_chain_ends_at_crash_dispatch(self):
        rt = _crashrich_wal_kv(trace_cap=128)
        seeds = np.arange(24, dtype=np.uint32)
        st = rt.run_fused(rt.init_batch(seeds), 4096, 512)
        crashed = np.nonzero(np.asarray(st.crashed))[0]
        assert crashed.size, "crash-rich matrix produced no crash"
        lane = int(crashed[0])
        exp = explain_crash(st, lane)
        assert exp["crashed"] and exp["chain"]
        assert exp["crash_code"] == int(np.asarray(st.crash_code)[lane])
        assert (exp["chain"][-1]["step"]
                == int(np.asarray(st.steps)[lane]) - 1)
        # chain is causally ordered and linked: each record's parent is
        # the previous record's step
        steps = [c["step"] for c in exp["chain"]]
        assert steps == sorted(steps)
        for prev, cur in zip(exp["chain"], exp["chain"][1:]):
            assert cur["parent"] == prev["step"]
        lams = [c["lamport"] for c in exp["chain"]]
        assert lams == sorted(lams) and len(set(lams)) == len(lams)
        assert exp["truncated"] or exp["root_external"]

    def test_chain_truncates_after_wrap(self):
        # tiny ring on a long run: the walk must stop at the wrap
        # horizon and SAY so, not resolve a parent to a wrong record
        rt = _pingpong_rt(trace_cap=4, target=40)
        st = rt.run_fused(rt.init_batch(np.arange(1, dtype=np.uint32)),
                          512, 64)
        recs = ring_records(st, lane=0)
        assert recs["dropped"] > 0
        exp = explain_crash(st, 0)
        assert exp["chain"]
        assert len(exp["chain"]) <= 4
        assert exp["truncated"] or exp["root_external"]

    def test_wrap_preserves_lineage_tail(self):
        # the small ring's surviving records must equal the tail of a
        # big ring's — parent/lamport included (dispatch indices, not
        # slot indices, so wrap cannot skew them)
        seeds = np.arange(2, dtype=np.uint32)
        small = _pingpong_rt(trace_cap=4, target=40)
        big = _pingpong_rt(trace_cap=128, target=40)
        ss = small.run_fused(small.init_batch(seeds), 256, 64)
        sb = big.run_fused(big.init_batch(seeds), 256, 64)
        rs, rb = ring_records(ss, 0), ring_records(sb, 0)
        n = len(rs["now"])
        for col in ("step", "parent", "lamport", "now", "tag"):
            assert (rs[col] == rb[col][-n:]).all(), col

    def test_explain_crash_requires_lineage(self):
        rt = _pingpong_rt(trace_cap=0)
        st, _ = rt.run(rt.init_batch(np.arange(2)), 128, 64)
        with pytest.raises(ValueError, match="compiled out"):
            explain_crash(st, 0)

    def test_injected_op_is_external(self):
        rt = _pingpong_rt(trace_cap=64, target=40)
        st = rt.init_batch(np.arange(1, dtype=np.uint32))
        st, _ = rt.run(st, 64, 32)
        st = rt.kill(st, 1)
        st, _ = rt.run(st, 64, 32)
        recs = ring_records(st, 0)
        kills = np.nonzero((recs["kind"] == T.EV_SUPER)
                           & (recs["tag"] == T.OP_KILL))[0]
        assert kills.size, "injected kill never dispatched"
        assert (recs["parent"][kills] == -1).all()


class TestCausalFingerprint:
    """(r11) crash-dedup fingerprints over explain_crash chains: lane-
    and wrap-invariant, matched by deepest common suffix so a chain
    truncated at different ring-wrap points stays ONE bucket."""

    def _exp(self, toks, code=301, node=2, truncated=False,
             root_external=True, step0=0, now_scale=10):
        chain = [dict(step=step0 + i, now=(step0 + i) * now_scale,
                      kind=k, node=n, src=s, tag=t,
                      parent=step0 + i - 1, lamport=i + 1)
                 for i, (k, n, s, t) in enumerate(toks)]
        return dict(chain=chain, truncated=truncated,
                    root_external=root_external, crashed=True,
                    crash_code=code, crash_node=node, lane=0, dropped=0)

    TOKS = [(1, 0, 0, 5), (2, 1, 0, 7), (2, 0, 1, 7), (3, 1, 1, 2),
            (2, 2, 1, 7)]

    def test_lane_invariant(self):
        # same causal content at different steps/times/lane: same key
        a = causal_fingerprint(self._exp(self.TOKS))
        b = causal_fingerprint(self._exp(self.TOKS, step0=500,
                                         now_scale=77))
        assert a["key"] == b["key"]

    def test_content_sensitive(self):
        a = causal_fingerprint(self._exp(self.TOKS))
        other = [*self.TOKS[:-1], (3, 0, 1, 2)]   # different crash node
        assert a["key"] != causal_fingerprint(self._exp(other))["key"]
        assert a["key"] != causal_fingerprint(
            self._exp(self.TOKS, code=302))["key"]

    def test_wrap_points_do_not_split_buckets(self):
        """The satellite contract: one bug truncated at DIFFERENT wrap
        points matches via the deepest common suffix."""
        full = causal_fingerprint(self._exp(self.TOKS))
        cuts = [causal_fingerprint(self._exp(
            self.TOKS[k:], truncated=True, root_external=False))
            for k in (1, 2, 3)]
        for cut in cuts:
            assert fingerprints_match(full, cut)
            assert fingerprints_match(cut, full)
        for a in cuts:
            for b in cuts:
                assert fingerprints_match(a, b)

    def test_different_bugs_do_not_merge(self):
        a = causal_fingerprint(self._exp(self.TOKS))
        # two COMPLETE chains of different length are different bugs
        # even though one's tokens are the other's suffix
        b = causal_fingerprint(self._exp(self.TOKS[1:]))
        assert a["complete"] and b["complete"]
        assert not fingerprints_match(a, b)
        # a CUT chain longer than a complete chain cannot be it either
        short_full = causal_fingerprint(self._exp(self.TOKS[3:]))
        long_cut = causal_fingerprint(self._exp(
            self.TOKS[1:], truncated=True, root_external=False))
        assert not fingerprints_match(short_full, long_cut)
        # ... nor a cut chain of EQUAL depth: a cut chain always hides
        # at least one more record than it shows, so a same-bug cut
        # observation is strictly shorter than the complete history
        equal_cut = causal_fingerprint(self._exp(
            self.TOKS[3:], truncated=True, root_external=False))
        assert not fingerprints_match(short_full, equal_cut)
        assert not fingerprints_match(equal_cut, short_full)
        # and different suffix content never matches
        other = [*self.TOKS[:-1], (3, 3, 1, 2)]
        assert not fingerprints_match(a, causal_fingerprint(self._exp(
            other, truncated=True, root_external=False)))

    def test_depth_cap_bounds_resolution(self):
        deep_a = [(1, 0, 0, 9)] * 4 + self.TOKS
        deep_b = [(1, 1, 1, 3)] * 4 + self.TOKS
        a = causal_fingerprint(self._exp(deep_a), depth=5)
        b = causal_fingerprint(self._exp(deep_b), depth=5)
        assert a["key"] == b["key"]       # differ only past the horizon
        assert not a["complete"] and a["depth"] == 5

    def test_code_fingerprint_fallback(self):
        fp = code_fingerprint(301, 2)
        assert fp["kind"] == "code" and fp["depth"] == 0
        assert fingerprints_match(fp, code_fingerprint(301, 2))
        assert not fingerprints_match(fp, code_fingerprint(302, 2))
        # code fingerprints never suffix-match causal ones
        assert not fingerprints_match(
            fp, causal_fingerprint(self._exp(self.TOKS)))

    def test_empty_chain_rejected(self):
        with pytest.raises(ValueError, match="empty"):
            causal_fingerprint(dict(chain=[], truncated=False,
                                    root_external=False, crash_code=1,
                                    crash_node=0))

    def test_ring_wrap_stability_on_real_rings(self):
        """Ground the synthetic contract in the engine: the same
        trajectory recorded through a 4-slot ring (wrapped, truncated
        chain) and a 128-slot ring (full chain) fingerprints into the
        same bucket."""
        seeds = np.arange(2, dtype=np.uint32)
        small = _pingpong_rt(trace_cap=4, target=40)
        big = _pingpong_rt(trace_cap=128, target=40)
        ss = small.run_fused(small.init_batch(seeds), 256, 64)
        sb = big.run_fused(big.init_batch(seeds), 256, 64)
        for lane in range(2):
            es, eb = explain_crash(ss, lane), explain_crash(sb, lane)
            assert len(es["chain"]) <= len(eb["chain"])
            fs = causal_fingerprint(es)
            fb = causal_fingerprint(eb)
            assert fingerprints_match(fs, fb), (lane, fs, fb)


class TestSketch:
    def test_sketch_slots_fill_in_order(self):
        rt = _pingpong_rt(trace_cap=0, sketch_slots=4, sketch_every=8,
                          target=40)
        st = rt.run_fused(rt.init_batch(np.arange(2, dtype=np.uint32)),
                          256, 64)
        sk = np.asarray(st.cov_sketch)
        steps = np.asarray(st.steps)
        for lane in range(2):
            filled = min(int(steps[lane]) // 8, 4)
            assert (sk[lane, :filled] != 0).all()
            assert (sk[lane, filled:] == 0).all()

    def test_identical_seeds_identical_sketches(self):
        rt = _pingpong_rt(sketch_slots=4, sketch_every=8, loss=0.2,
                          n_nodes=4, target=6)
        st = rt.run_fused(rt.init_batch(np.asarray([7, 7, 9], np.uint32)),
                          512, 64)
        sk = np.asarray(st.cov_sketch)
        assert (sk[0] == sk[1]).all()
        assert (sk[2] != sk[0]).any()
        d = sketch_divergence(st, 0, 1)
        assert d["slot"] == d["slots"]          # never diverged
        assert sketch_divergence(st, 0, 2)["slot"] < d["slots"]

    def test_first_divergence_slots_math(self):
        sk = np.array([[1, 2, 3],
                       [1, 2, 3],
                       [1, 9, 9],
                       [8, 8, 8]], np.uint32)
        first = first_divergence_slots(sk)
        # consensus prefix is [1, 2, 3] (modal per slot)
        assert first.tolist() == [3, 3, 1, 0]

    def test_divergence_profile_in_summarize(self):
        rt = _pingpong_rt(sketch_slots=8, sketch_every=8, loss=0.2,
                          n_nodes=4, target=6)
        seeds = np.arange(16, dtype=np.uint32)
        st = rt.run_fused(rt.init_batch(seeds), 512, 64)
        rep = summarize(rt, st, seeds=seeds)
        prof = rep["first_divergence"]
        assert prof is not None and prof["diverged"] > 0
        assert prof["every"] == 8 and prof["slots"] == 8
        assert prof["p10"] <= prof["p50"] <= prof["p90"]
        assert rep["first_divergence"] == divergence_profile(st)
        # compiled-out build reports None, not a fake zero profile
        rt0 = _pingpong_rt(n_nodes=4, target=6)
        st0, _ = rt0.run(rt0.init_batch(seeds), 512, 64)
        assert summarize(rt0, st0)["first_divergence"] is None


class TestDivergenceEnergy:
    def _plan(self):
        sc = Scenario()
        sc.at(ms(40)).kill_random()
        sc.at(ms(400)).restart_random()
        rt = _pingpong_rt(n_nodes=4, target=6, scenario=sc,
                          sketch_slots=4)
        return KnobPlan.from_runtime(rt)

    def test_early_divergence_boosts_admission_energy(self):
        plan = self._plan()
        corpus = Corpus(plan, rng=np.random.default_rng(0), div_bonus=1.0)
        knobs = KnobPlan.stack([plan.base_knobs() for _ in range(3)])
        sketches = np.array([[1, 2, 3, 4],      # consensus
                             [1, 2, 9, 9],      # diverges at slot 2
                             [7, 7, 7, 7]],     # diverges at slot 0
                            np.uint32)
        corpus.observe(knobs, seeds=np.arange(3),
                       hashes_u64=np.arange(10, 13, dtype=np.uint64),
                       crashed=np.zeros(3, bool), codes=np.zeros(3),
                       parent_ids=np.full(3, -1), round_no=0,
                       sketches=sketches)
        e = {en["div_slot"]: en["energy"] for en in corpus.entries}
        assert e[0] > e[2] > e[4]               # earlier split = hotter
        assert e[4] == 1.0                      # consensus lane: no bonus

    def test_div_bonus_zero_is_hash_only(self):
        plan = self._plan()
        corpus = Corpus(plan, rng=np.random.default_rng(0), div_bonus=0.0)
        knobs = KnobPlan.stack([plan.base_knobs() for _ in range(2)])
        corpus.observe(knobs, seeds=np.arange(2),
                       hashes_u64=np.arange(2, dtype=np.uint64),
                       crashed=np.zeros(2, bool), codes=np.zeros(2),
                       parent_ids=np.full(2, -1), round_no=0,
                       sketches=np.array([[1, 2], [3, 4]], np.uint32))
        assert all(en["energy"] == 1.0 for en in corpus.entries)

    def test_fuzz_threads_sketches_into_corpus(self):
        sc = Scenario()
        sc.at(ms(40)).kill_random()
        sc.at(ms(400)).restart_random()
        rt = _pingpong_rt(n_nodes=4, target=6, scenario=sc,
                          sketch_slots=4, sketch_every=16)
        from madsim_tpu.obs import JsonlObserver
        obs = JsonlObserver(io.StringIO())
        corpus = Corpus(KnobPlan.from_runtime(rt),
                        rng=np.random.default_rng(0))
        fuzz(rt, max_steps=512, batch=16, max_rounds=2, dry_rounds=3,
             chunk=128, corpus=corpus, observer=obs)
        assert any(e["div_slot"] is not None for e in corpus.entries)
        rounds = [r for r in obs.records if r["kind"] == "fuzz_round"]
        assert all("div_slot_p50" in r for r in rounds)


class TestFlowExport:
    def test_flow_events_golden(self):
        # hand-built lineage ring -> exact JSON: three dispatches where
        # step 5 (a boot, external) enqueued 6, and 6 enqueued 7
        recs = dict(now=np.array([100, 300, 900]),
                    step=np.array([5, 6, 7]),
                    kind=np.array([T.EV_SUPER, T.EV_MSG, T.EV_TIMER]),
                    node=np.array([0, 1, 1]),
                    src=np.array([0, 0, 1]),
                    tag=np.array([T.OP_INIT, 7, 3]),
                    parent=np.array([-1, 5, 6]),
                    lamport=np.array([1, 2, 3]))
        evs = to_chrome_events(recs)
        assert evs == [
            {"name": "SUPER:INIT", "ph": "i", "s": "t", "ts": 100,
             "pid": 0, "tid": 0,
             "args": {"src": 0, "tag": T.OP_INIT, "step": 5,
                      "lamport": 1, "parent": -1}},
            {"name": "MSG:tag7", "ph": "i", "s": "t", "ts": 300,
             "pid": 0, "tid": 1,
             "args": {"src": 0, "tag": 7, "step": 6, "lamport": 2,
                      "parent": 5}},
            {"name": "TIMER:tag3", "ph": "i", "s": "t", "ts": 900,
             "pid": 0, "tid": 1,
             "args": {"src": 1, "tag": 3, "step": 7, "lamport": 3,
                      "parent": 6}},
            {"name": "causal", "cat": "causal", "id": 6, "pid": 0,
             "ph": "s", "ts": 100, "tid": 0},
            {"name": "causal", "cat": "causal", "id": 6, "pid": 0,
             "ph": "f", "bp": "e", "ts": 300, "tid": 1},
            {"name": "causal", "cat": "causal", "id": 7, "pid": 0,
             "ph": "s", "ts": 300, "tid": 1},
            {"name": "causal", "cat": "causal", "id": 7, "pid": 0,
             "ph": "f", "bp": "e", "ts": 900, "tid": 1},
        ]

    def test_ring_export_contains_paired_flows(self, tmp_path):
        rt = _pingpong_rt(trace_cap=128, target=12)
        st = rt.run_fused(rt.init_batch(np.arange(2, dtype=np.uint32)),
                          256, 64)
        p = str(tmp_path / "t.json")
        n = export_chrome_trace(p, state=st, lane=0)
        with open(p) as f:
            doc = json.load(f)
        inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert n == len(inst)                  # flows not counted
        for e in inst:
            assert {"step", "lamport", "parent"} <= set(e["args"])
        flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
        assert flows
        starts = {e["id"] for e in flows if e["ph"] == "s"}
        ends = {e["id"] for e in flows if e["ph"] == "f"}
        assert starts == ends
        # flow count matches the resolvable happens-before edges
        assert len(flows) == 2 * len(happens_before(ring_records(st, 0)))

    def test_stream_export_carries_step_only(self):
        # collect_events records have no lineage columns; their args
        # carry the dispatch index (k-th fired record IS dispatch k)
        rt = _pingpong_rt(target=3)
        _, events = rt.run(rt.init_batch(np.arange(2)), 256, 64,
                           collect_events=True)
        evs = to_chrome_events(events, b=0)
        assert [e["args"]["step"] for e in evs] == list(range(len(evs)))
        assert all("parent" not in e["args"] for e in evs)
        assert all(e["ph"] == "i" for e in evs)


@pytest.mark.slow
class TestChaosMatrixEquivalence:
    """The full-matrix r10 never-perturb contract: flagship chaos
    workloads with lineage + sketch compiled in but masked off are
    leaf-for-leaf identical to the compiled-out build, on the chunked
    AND fused runners (the fast lane keeps the single-config pingpong
    check; this is the raft/wal_kv analog of test_obs's ring sweeps)."""

    def _assert_off_on_equal(self, make_rt, seeds, max_steps, chunk):
        rt0 = make_rt(0, 0)
        rt1 = make_rt(16, 8)
        ref, _ = rt0.run(rt0.init_batch(seeds), max_steps, chunk)
        for runner in ("run", "run_fused"):
            if runner == "run":
                st, _ = rt1.run(rt1.init_batch(seeds, trace_lanes=[]),
                                max_steps, chunk)
            else:
                st = rt1.run_fused(rt1.init_batch(seeds, trace_lanes=[]),
                                   max_steps, chunk)
            a, b = _nontrace_state(ref), _nontrace_state(st)
            assert a.keys() == b.keys()
            for k in a:
                assert (a[k] == b[k]).all(), (runner, k)

    def test_raft_chaos_matrix(self):
        from madsim_tpu.models.raft import make_raft_runtime

        def make(cap, sk):
            cfg = SimConfig(n_nodes=5, event_capacity=128,
                            time_limit=sec(3), trace_cap=cap,
                            sketch_slots=sk,
                            net=NetConfig(packet_loss_rate=0.05,
                                          send_latency_min=ms(1),
                                          send_latency_max=ms(10)))
            sc = Scenario()
            sc.at(sec(1)).kill_random()
            sc.at(sec(1) + ms(400)).restart_random()
            return make_raft_runtime(5, 8, n_cmds=4, scenario=sc, cfg=cfg)

        self._assert_off_on_equal(make, np.arange(64, dtype=np.uint32),
                                  1500, 256)

    def test_wal_kv_chaos_matrix(self):
        def make(cap, sk):
            return _crashrich_wal_kv(trace_cap=cap, sketch_slots=sk)

        self._assert_off_on_equal(make, np.arange(64, dtype=np.uint32),
                                  4096, 512)
