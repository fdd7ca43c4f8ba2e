"""Lineage-driven fault injection (r22, DESIGN §23): support extraction
over synthetic happens-before graphs, the hitting-set pool, knob-plane
synthesis bounds, and the fuzz-arm contracts (additive store schema,
zero retraces on warm caches)."""

import json
import os

import numpy as np
import pytest

from madsim_tpu.core import types as T
from madsim_tpu.harness.witness import success_witness
from madsim_tpu.obs.causal import walk_lineage
from madsim_tpu.obs.support import support_from_records
from madsim_tpu.search.ldfi import SupportPool, synthesize
from madsim_tpu.search.mutate import KnobPlan


def _recs(rows):
    """Synthetic ring_records dict: one row per record, ring order."""
    keys = ("step", "now", "kind", "node", "src", "tag", "parent",
            "lamport")
    return {k: np.asarray([r.get(k, 0) for r in rows], np.int64)
            for k in keys}


def _msg(step, parent, src, dst, now, tag=1):
    return dict(step=step, now=now, kind=T.EV_MSG, node=dst, src=src,
                tag=tag, parent=parent)


def _timer(step, parent, node, now, tag=2):
    return dict(step=step, now=now, kind=T.EV_TIMER, node=node, src=-1,
                tag=tag, parent=parent)


class TestWalkLineage:
    def test_chain_walks_to_external_root(self):
        recs = _recs([_msg(0, -1, 0, 1, 10), _msg(1, 0, 1, 0, 20),
                      _msg(2, 1, 0, 1, 30), _msg(3, 2, 1, 0, 40)])
        walk = walk_lineage(recs)
        assert [c["step"] for c in walk["chain"]] == [0, 1, 2, 3]
        assert walk["root_external"] and not walk["truncated"]

    def test_diamond_follows_single_parent_path(self):
        # A -> {B, C}, C -> D: the lineage walk from D is D, C, A —
        # B happened, but D did not causally depend on it
        recs = _recs([_msg(0, -1, 0, 1, 10),   # A
                      _msg(1, 0, 1, 2, 20),    # B (off-path)
                      _msg(2, 0, 1, 3, 25),    # C
                      _msg(3, 2, 3, 0, 40)])   # D
        walk = walk_lineage(recs, from_step=3)
        assert [c["step"] for c in walk["chain"]] == [0, 2, 3]
        assert walk["root_external"]

    def test_wrap_truncation_is_honest(self):
        # the oldest surviving record's parent was overwritten by wrap:
        # the walk stops there and says so (r11 suffix contract)
        recs = _recs([_msg(5, 2, 0, 1, 50), _msg(6, 5, 1, 0, 60),
                      _msg(7, 6, 0, 1, 70)])
        walk = walk_lineage(recs, from_step=7)
        assert [c["step"] for c in walk["chain"]] == [5, 6, 7]
        assert walk["truncated"] and not walk["root_external"]

    def test_bad_from_step_and_empty_ring_raise(self):
        recs = _recs([_msg(0, -1, 0, 1, 10)])
        with pytest.raises(ValueError):
            walk_lineage(recs, from_step=99)
        with pytest.raises(ValueError):
            walk_lineage(_recs([]))


class TestWitnessAndSupport:
    def test_default_witness_is_last_dispatch(self):
        recs = _recs([_msg(0, -1, 0, 1, 10), _msg(1, 0, 1, 0, 20)])
        sup = support_from_records(recs)
        assert sup["witness_step"] == 1
        assert sup["msg_edges"] == [(0, 1, 10), (1, 0, 20)]
        assert sup["depth"] == 2 and sup["root_external"]

    def test_witness_filters_kind_tag_node(self):
        recs = _recs([_msg(0, -1, 0, 1, 10, tag=7),
                      _timer(1, 0, 1, 30, tag=9),
                      _msg(2, 1, 1, 2, 40, tag=7),
                      _msg(3, 2, 2, 1, 50, tag=8)])
        w = success_witness(kinds=(T.EV_MSG,), tags=(7,), node=2)
        sup = support_from_records(recs, w)
        # last match is step 2 (the tag-8 record fails the tag filter)
        assert sup["witness_step"] == 2
        assert sup["msg_edges"] == [(0, 1, 10), (1, 2, 40)]
        assert sup["timer_edges"] == [(1, 30)]

    def test_unmatched_witness_returns_none(self):
        recs = _recs([_msg(0, -1, 0, 1, 10)])
        assert support_from_records(
            recs, success_witness(kinds=(T.EV_SUPER,))) is None
        assert support_from_records(_recs([])) is None

    def test_wrap_truncated_flag_propagates(self):
        recs = _recs([_msg(5, 2, 0, 1, 50), _msg(6, 5, 1, 0, 60)])
        sup = support_from_records(recs)
        assert sup["truncated"] and not sup["root_external"]
        pool = SupportPool()
        assert pool.add(sup)
        assert pool.truncated == 1


class TestSupportPool:
    def _sup(self, msg=(), timer=(), truncated=False):
        return dict(msg_edges=list(msg), timer_edges=list(timer),
                    depth=len(msg) + len(timer), witness_step=0,
                    truncated=truncated, root_external=not truncated)

    def test_external_sends_are_not_candidates(self):
        pool = SupportPool()
        # only an external (src < 0) edge: nothing cuttable
        assert not pool.add(self._sup(msg=[(-1, 2, 10)]))
        assert len(pool) == 0

    def test_ranked_is_a_greedy_hitting_set(self):
        pool = SupportPool()
        a, b, c, d = (0, 1, 5), (1, 2, 6), (2, 0, 7), (0, 2, 8)
        pool.add(self._sup(msg=[a, b]))
        pool.add(self._sup(msg=[a, c]))
        pool.add(self._sup(msg=[d]))
        ranked = pool.ranked(8)
        keys = [r["key"] for r in ranked]
        # a hits 2 uncovered supports -> first; d covers the last
        # uncovered one -> second; b/c pad by (-hits, key) order
        assert keys[0] == ("msg", 0, 1)
        assert keys[1] == ("msg", 0, 2)
        assert set(keys[2:]) == {("msg", 1, 2), ("msg", 2, 0)}
        assert ranked[0]["hits"] == 2 and ranked[0]["times"] == [5, 5]

    def test_merge_pools_across_shards(self):
        p1, p2 = SupportPool(), SupportPool()
        p1.add(self._sup(msg=[(0, 1, 5)]))
        p2.add(self._sup(msg=[(0, 1, 9)], truncated=True))
        p2.add(self._sup(timer=[(2, 7)]))
        p1.merge(p2)
        assert len(p1) == 3 and p1.truncated == 1
        assert p1.times[("msg", 0, 1)] == [5, 9]
        assert ("timer", 2, -1) in p1.times


def _echo_ldfi_rt(trace_cap=64, target=3):
    """rpc_echo under a 4-family chaos script: every synthesis-relevant
    fault op (oneway / reset / skew / dup) has a mutable row."""
    from madsim_tpu import SimConfig, sec, ms
    from madsim_tpu.models.rpc_echo import make_echo_runtime
    from madsim_tpu.runtime import chaos
    from madsim_tpu.runtime.scenario import Scenario
    sc = Scenario()
    sc = chaos.asymmetric_partition(ms(400), [1], ms(300), sc=sc)
    sc = chaos.conn_reset_storm(rounds=2, first=ms(300), period=ms(450),
                                node=2, sc=sc)
    sc = chaos.clock_drift(ms(200), 128, node=1, until=ms(900), sc=sc)
    sc = chaos.retransmit_storm(ms(250), 0.3, ms(800), node=1, sc=sc)
    cfg = SimConfig(n_nodes=4, event_capacity=256, time_limit=sec(20),
                    trace_cap=trace_cap)
    return make_echo_runtime(n_nodes=4, target=target, cfg=cfg,
                             scenario=sc)


class TestLiveRing:
    """Extraction and targeted replay on a real run of the four-family
    echo workload (the classes around it feed hand-built records)."""

    @pytest.fixture(scope="class")
    def green(self):
        rt = _echo_ldfi_rt()
        state, _ = rt.run(rt.init_batch(np.arange(8, dtype=np.uint32)),
                          3000, 256)
        assert not np.asarray(state.crashed)[0], "lane 0 went red"
        return rt, state

    def test_extraction_matches_inline_parent_walk(self, green):
        from madsim_tpu.obs import ring_records
        from madsim_tpu.obs.support import extract_support
        _, state = green
        sup = extract_support(state, 0)
        assert sup is not None and not sup["truncated"]
        # an independent walk over the same ring: parent links back from
        # the last dispatch (the default witness) to the external root
        recs = ring_records(state, 0)
        by_step = {int(s): i for i, s in enumerate(recs["step"])}
        i = len(recs["step"]) - 1
        msgs, timers = [], []
        while True:
            kind = int(recs["kind"][i])
            if kind == T.EV_MSG:
                msgs.append((int(recs["src"][i]), int(recs["node"][i]),
                             int(recs["now"][i])))
            elif kind == T.EV_TIMER:
                timers.append((int(recs["node"][i]), int(recs["now"][i])))
            parent = int(recs["parent"][i])
            if parent < 0 or parent not in by_step:
                break
            i = by_step[parent]
        assert msgs, "the witness chain carries no message edge"
        assert sup["msg_edges"] == msgs[::-1]
        assert sup["timer_edges"] == timers[::-1]
        assert support_from_records(recs)["depth"] == sup["depth"]

    def test_targeted_vector_replays_bit_identically(self, green):
        from madsim_tpu.obs.support import extract_support
        rt, state = green
        plan = KnobPlan.from_runtime(rt, dup_slots=2)
        pool = SupportPool()
        for lane in range(4):
            sup = extract_support(state, lane)
            if sup is not None:
                pool.add(sup)
        assert len(pool) >= 2
        vecs = synthesize(plan, pool, 8)
        assert vecs
        runs = []
        for _ in range(2):
            st = plan.apply(rt.init_batch(np.asarray([5], np.uint32)),
                            KnobPlan.stack([vecs[0]]))
            fin = rt.run_fused(st, 3000, 256)
            runs.append((int(rt.fingerprints(fin)[0]),
                         bool(np.asarray(fin.crashed)[0]),
                         int(np.asarray(fin.crash_code)[0])))
        assert runs[0] == runs[1]


class TestSynthesize:
    def _pool(self):
        pool = SupportPool()
        pool.add(dict(msg_edges=[(1, 0, 5000), (0, 1, 9000)],
                      timer_edges=[(2, 4000)], depth=3, witness_step=9,
                      truncated=False, root_external=True))
        pool.add(dict(msg_edges=[(1, 0, 7000)], timer_edges=[],
                      depth=1, witness_step=5, truncated=False,
                      root_external=True))
        return pool

    def test_vectors_stay_on_the_knob_plane(self):
        plan = KnobPlan.from_runtime(_echo_ldfi_rt(), dup_slots=2)
        vecs = synthesize(plan, self._pool(), 6)
        assert vecs
        base = plan.base_knobs()
        for kn in vecs:
            changed = [r for r in range(plan.R)
                       if any(kn[f][r] != base[f][r]
                              for f in ("row_time", "row_node", "row_val",
                                        "row_flag", "row_on"))]
            assert changed
            for r in changed:
                assert plan.time_ok[r]
                node = int(kn["row_node"][r])
                assert node == T.NODE_RANDOM or (
                    0 <= node < plan.N and plan.pool_ok[r, node + 1])
                assert plan.val_lo[r] <= int(kn["row_val"][r]) \
                    <= plan.val_hi[r]
                assert bool(kn["row_on"][r])

    def test_oneway_direction_tracks_group_mask(self):
        # scenario group A = {1}: an edge 1 -> 0 leaves the group, so
        # the cut keeps direction 0 (A's outbound sends vanish); the
        # row fires `lead` before the observed instant
        plan = KnobPlan.from_runtime(_echo_ldfi_rt(), dup_slots=2)
        pool = SupportPool()
        pool.add(dict(msg_edges=[(1, 0, 5000)], timer_edges=[], depth=1,
                      witness_step=3, truncated=False,
                      root_external=True))
        vecs = synthesize(plan, pool, 1, max_cuts=1, lead=1000)
        assert len(vecs) == 1
        ops = np.asarray(plan.base["op"])
        rows = [r for r in range(plan.R)
                if vecs[0]["row_time"][r] == 4000
                and ops[r] == T.OP_PARTITION_ONEWAY]
        assert rows and int(vecs[0]["row_flag"][rows[0]]) == 0

    def test_oneway_cut_drags_its_heal_with_duration(self):
        # the scenario's asymmetric_partition cuts at 400ms and heals
        # at 700ms; re-aiming the cut to t=4000 must re-aim the paired
        # OP_HEAL to 4000 + the base 300ms delta — a permanent cut
        # makes protocols abort cleanly instead of exposing torn state
        plan = KnobPlan.from_runtime(_echo_ldfi_rt(), dup_slots=2)
        pool = SupportPool()
        pool.add(dict(msg_edges=[(1, 0, 5000)], timer_edges=[], depth=1,
                      witness_step=3, truncated=False,
                      root_external=True))
        vecs = synthesize(plan, pool, 1, max_cuts=1, lead=1000)
        assert len(vecs) == 1
        ops = np.asarray(plan.base["op"])
        times = np.asarray(plan.base["time"])
        heal = [r for r in range(plan.R) if ops[r] == T.OP_HEAL]
        assert len(heal) == 1
        cut = [r for r in range(plan.R)
               if ops[r] == T.OP_PARTITION_ONEWAY
               and vecs[0]["row_time"][r] == 4000]
        assert cut
        delta = int(times[heal[0]]) - int(times[cut[0]])
        assert int(vecs[0]["row_time"][heal[0]]) == 4000 + delta
        assert bool(vecs[0]["row_on"][heal[0]])

    def test_synthesize_pins_the_support_seed(self):
        # edge instants are seed-specific: vectors carry the green seed
        # their first cut was timed against so the driver can replay
        # THAT trajectory with the cut injected
        plan = KnobPlan.from_runtime(_echo_ldfi_rt(), dup_slots=2)
        pool = SupportPool()
        pool.add(dict(msg_edges=[(1, 0, 5000)], timer_edges=[], depth=1,
                      witness_step=3, truncated=False,
                      root_external=True), seed=42)
        vecs, seeds = synthesize(plan, pool, 2, max_cuts=1,
                                 with_seeds=True)
        assert vecs and all(s == 42 for s in seeds)
        # an un-seeded pool yields None pins (driver keeps fresh seeds)
        anon = SupportPool()
        anon.add(dict(msg_edges=[(1, 0, 5000)], timer_edges=[], depth=1,
                      witness_step=3, truncated=False,
                      root_external=True))
        vecs2, seeds2 = synthesize(plan, anon, 1, max_cuts=1,
                                   with_seeds=True)
        assert vecs2 and seeds2 == [None]
        # merge keeps first-seen pins (the sharded pool contract)
        pool.merge(anon)
        assert pool.seed_of[(("msg", 1, 0), 5000)] == 42

    def test_deterministic_and_empty_cases(self):
        plan = KnobPlan.from_runtime(_echo_ldfi_rt(), dup_slots=2)
        a = synthesize(plan, self._pool(), 4)
        b = synthesize(plan, self._pool(), 4)
        assert len(a) == len(b)
        for ka, kb in zip(a, b):
            for f in ka:
                assert (np.asarray(ka[f]) == np.asarray(kb[f])).all(), f
        assert synthesize(plan, SupportPool(), 4) == []
        # a plan with no fault rows cannot express any cut
        from madsim_tpu import SimConfig, sec
        from madsim_tpu.models.rpc_echo import make_echo_runtime
        bare = make_echo_runtime(
            n_nodes=4, target=3,
            cfg=SimConfig(n_nodes=4, event_capacity=256,
                          time_limit=sec(20), trace_cap=64))
        assert synthesize(KnobPlan.from_runtime(bare, dup_slots=2),
                          self._pool(), 4) == []


class TestFuzzArmContracts:
    def test_ldfi_none_store_schema_untouched(self, tmp_path):
        # the additive contract: without ldfi, no entry carries an
        # origin member and no worker state carries targeted_yield —
        # the store bytes are the pre-r22 schema exactly
        from madsim_tpu.search import fuzz
        from madsim_tpu.service.store import CorpusStore
        rt = _echo_ldfi_rt()
        fuzz(rt, max_steps=3000, batch=12, max_rounds=2, dry_rounds=3,
             chunk=256, corpus_dir=str(tmp_path))
        store = CorpusStore(str(tmp_path), create=False)
        names = store.entry_names()
        assert names
        for name in names:
            assert "origin" not in store.load_entry(name)
        sdir = os.path.join(str(tmp_path), "state")
        for f in os.listdir(sdir):
            with open(os.path.join(sdir, f)) as fh:
                assert "targeted_yield" not in json.load(fh)

    def test_targeted_arm_accounting_and_entry_origin(self, tmp_path):
        from madsim_tpu.search import LdfiConfig, fuzz
        from madsim_tpu.service.store import CorpusStore
        rt = _echo_ldfi_rt()
        res = fuzz(rt, max_steps=3000, batch=12, max_rounds=3,
                   dry_rounds=4, chunk=256, corpus_dir=str(tmp_path),
                   ldfi=LdfiConfig(lanes=4, frac=0.25))
        t = res["targeted"]
        assert t["supports"] >= 1
        assert t["lanes_run"] >= 1
        assert 0 <= t["admitted"] <= t["lanes_run"]
        store = CorpusStore(str(tmp_path), create=False)
        origins = [store.load_entry(n).get("origin")
                   for n in store.entry_names()]
        assert origins.count("targeted") == t["admitted"]
        # the cumulative admission ledger survives in the worker state
        sdir = os.path.join(str(tmp_path), "state")
        states = [json.load(open(os.path.join(sdir, f)))
                  for f in os.listdir(sdir)]
        assert any(s.get("targeted_yield") == t["admitted"]
                   for s in states)

    def test_ldfi_needs_the_flight_recorder(self):
        from madsim_tpu.search import LdfiConfig, fuzz
        with pytest.raises(ValueError, match="flight recorder"):
            fuzz(_echo_ldfi_rt(trace_cap=0), max_steps=500, batch=8,
                 max_rounds=1, dry_rounds=2, chunk=256,
                 ldfi=LdfiConfig())

    def test_warm_targeted_campaign_never_recompiles(self):
        # the acceptance gate: a targeted round is mask + host splice +
        # the SAME module-level mutate/apply/run programs — a warm-cache
        # ldfi campaign adds ZERO compile traces
        from madsim_tpu.compile.cache import COMPILE_LOG
        from madsim_tpu.search import LdfiConfig, fuzz
        kw = dict(max_steps=3000, batch=12, max_rounds=3, dry_rounds=4,
                  chunk=256, ldfi=LdfiConfig(lanes=4, frac=0.25))
        fuzz(_echo_ldfi_rt(), **kw)              # warm
        before = COMPILE_LOG.snapshot()["traces_total"]
        res = fuzz(_echo_ldfi_rt(), **kw)        # fresh Runtime + plan
        after = COMPILE_LOG.snapshot()["traces_total"]
        assert after == before, COMPILE_LOG.recent(8)
        assert res["targeted"]["lanes_run"] >= 1
