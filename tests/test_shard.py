"""Mesh-sharded fuzzing campaigns (r13, search/shard.py + DESIGN §15).

Load-bearing contracts:
(1) a 1-shard sharded campaign is BIT-IDENTICAL to the unsharded fuzzer —
down to store bytes (entry files, coverage keys, scheduler order and
energies, buckets) — over the saturating, crash-rich wal_kv, and
flagship raft workloads;
(2) an N-shard campaign's merged coverage is a superset of every shard's
own view, and the cross-shard merge actually DELIVERS (each shard's live
corpus holds foreign-namespace entries; the consensus tally folds every
shard's deltas exactly once);
(3) shard namespaces are worker ids: worker_id*shards+s, disjoint seed
spaces, group state committed in one atomic write, split == continuous
on resume;
(4) the r13 run-twice verify guards (fuzz/fuzz_sharded verify_resume,
replay_bucket verify) contain a corrupted first invocation and raise on
real nondeterminism;
(5) the supervisor pass rotates round targets, counts dead-worker
restarts, and prunes cold entries without forgetting coverage.

The suite runs on the conftest-forced 8-device virtual CPU mesh.
"""

import io
import json
import os

import numpy as np
import pytest

from madsim_tpu import fuzz, fuzz_sharded
from madsim_tpu.obs.progress import ProgressObserver
from madsim_tpu.parallel import stats
from madsim_tpu.search.corpus import (Corpus, merge_consensus,
                                      split_entry_id)
from madsim_tpu.search.mutate import KnobPlan
from madsim_tpu.search.shard import shard_worker_id
from madsim_tpu.service import (CorpusStore, prune_cold_entries,
                                replay_bucket, supervise_campaign)


def _saturating_rt(**kw):
    from madsim_tpu.models.workloads import saturating
    return saturating(**kw)


def _crashrich_rt(trace_cap=128):
    from madsim_tpu.models.workloads import crashrich
    return crashrich("wal_kv", trace_cap=trace_cap)


KW = dict(max_steps=400, batch=16, max_rounds=3, dry_rounds=9, chunk=128)


def _store_bytes(d):
    s = CorpusStore(d, create=False)
    return {n: open(os.path.join(d, "entries", n), "rb").read()
            for n in s.entry_names()}


def _assert_stores_equal(da, db, sharded_side="b"):
    """fuzz() store vs fuzz_sharded(shards=1) store: byte-equal entries,
    equal coverage, equal scheduler order/energies/rng."""
    sa, sb = CorpusStore(da, create=False), CorpusStore(db, create=False)
    assert sa.entry_names() == sb.entry_names()
    assert sa.coverage_keys() == sb.coverage_keys()
    assert _store_bytes(da) == _store_bytes(db)
    wa = sa.load_worker_state(0)
    gb = sb.load_shard_group_state(0)
    assert gb["shards"] == 1
    sh = gb["shard_states"][0]
    for k in ("next_counter", "order", "crash_codes", "sketch_counts",
              "rng_state"):
        assert wa[k] == sh[k], k
    assert wa["rounds_done"] == gb["rounds_done"]
    assert sorted(sa.bucket_keys()) == sorted(sb.bucket_keys())


class TestOneShardBitIdentity:
    def test_saturating(self, tmp_path):
        da, db = str(tmp_path / "a"), str(tmp_path / "b")
        r1 = fuzz(_saturating_rt(), corpus_dir=da, **KW)
        r2 = fuzz_sharded(_saturating_rt(), shards=1, corpus_dir=db, **KW)
        assert r1["distinct_schedules"] == r2["distinct_schedules"]
        assert r1["new_per_round"] == r2["new_per_round"]
        assert r1["crashes"] == r2["crashes"]
        assert r1["mutation_ops"] == r2["mutation_ops"]
        assert r1["crash_first_seed_by_code"] == r2["crash_first_seed_by_code"]
        _assert_stores_equal(da, db)

    def test_crashrich_wal_kv(self, tmp_path):
        kw = dict(max_steps=1500, batch=8, max_rounds=2, dry_rounds=9,
                  chunk=256)
        da, db = str(tmp_path / "a"), str(tmp_path / "b")
        r1 = fuzz(_crashrich_rt(), corpus_dir=da, **kw)
        r2 = fuzz_sharded(_crashrich_rt(), shards=1, corpus_dir=db, **kw)
        assert r1["crashes"] == r2["crashes"] > 0
        assert sorted(r1["crash_repros"]) == sorted(r2["crash_repros"])
        _assert_stores_equal(da, db)

    @pytest.mark.slow
    def test_flagship_raft(self, tmp_path):
        from madsim_tpu.models.workloads import flagship_raft
        kw = dict(max_steps=512, batch=8, max_rounds=2, dry_rounds=9,
                  chunk=256)
        da, db = str(tmp_path / "a"), str(tmp_path / "b")
        r1 = fuzz(flagship_raft(), corpus_dir=da, **kw)
        r2 = fuzz_sharded(flagship_raft(), shards=1, corpus_dir=db, **kw)
        assert r1["distinct_schedules"] == r2["distinct_schedules"]
        _assert_stores_equal(da, db)

    def test_in_memory_results_match(self):
        r1 = fuzz(_saturating_rt(), **KW)
        r2 = fuzz_sharded(_saturating_rt(), shards=1, **KW)
        assert r1["distinct_schedules"] == r2["distinct_schedules"]
        assert r1["new_per_round"] == r2["new_per_round"]
        assert r1["mutation_ops"] == r2["mutation_ops"]
        assert r2["shards"] == 1


class TestShardMerge:
    def test_merged_coverage_superset_of_each_shard(self, tmp_path):
        d = str(tmp_path / "c")
        res = fuzz_sharded(_saturating_rt(sketch_slots=8), shards=2,
                           corpus_dir=d, **KW)
        assert res["shards"] == 2
        for row in res["per_shard"]:
            assert row["coverage"] <= res["distinct_schedules"]
            # the documented result row schema
            for k in ("shard", "worker_id", "corpus_size", "coverage",
                      "crashes", "seeds_run"):
                assert k in row
            assert row["seeds_run"] == res["rounds"] * KW["batch"]
        # the campaign union really is the union of the shard views
        assert res["distinct_schedules"] <= sum(
            row["coverage"] for row in res["per_shard"])
        # every shard's LIVE corpus received the other's entries
        g = CorpusStore(d, create=False).load_shard_group_state(0)
        for s, st in enumerate(g["shard_states"]):
            owners = {split_entry_id(int(e))[0] for e, _ in st["order"]}
            assert owners == {0, 1}, (s, owners)

    def test_four_shard_namespaces_and_entries(self, tmp_path):
        d = str(tmp_path / "c")
        res = fuzz_sharded(_saturating_rt(), shards=4, corpus_dir=d,
                           worker_id=1, **KW)
        # shard s of worker 1 at 4 shards owns namespace 4+s
        assert [row["worker_id"] for row in res["per_shard"]] == [4, 5, 6, 7]
        store = CorpusStore(d, create=False)
        owners = {split_entry_id(
            CorpusStore._parse_entry_name(n))[0]
            for n in store.entry_names()}
        assert owners == {4, 5, 6, 7}
        # group state is keyed by the BASE worker id, one file
        assert store.shard_group_ids() == [1]
        assert store.load_shard_group_state(1)["shards"] == 4

    def test_shard_worker_id_mapping(self):
        assert shard_worker_id(0, 0, 1) == 0          # the identity case
        assert shard_worker_id(3, 0, 1) == 3
        assert shard_worker_id(0, 2, 4) == 2
        assert shard_worker_id(2, 1, 4) == 9
        # groups are disjoint
        ids = {shard_worker_id(w, s, 4) for w in range(3) for s in range(4)}
        assert len(ids) == 12

    def test_disjoint_seed_spaces(self):
        from madsim_tpu.search.fuzz import WORKER_SEED_STRIDE
        res = fuzz_sharded(_saturating_rt(), shards=2,
                           **dict(KW, max_rounds=1))
        # base knob bootstrap crashes record real seeds from each
        # shard's stride-separated space
        for row in res["per_shard"]:
            assert row["worker_id"] in (0, 1)
        assert WORKER_SEED_STRIDE * 1 < 2**32


class TestConsensus:
    def test_allreduce_matches_host_rule(self):
        rng = np.random.default_rng(0)
        sk = rng.integers(0, 5, size=(64, 7)).astype(np.uint32)
        modal = stats.consensus_allreduce(sk)
        # the host rule: per-slot modal, ties to the smallest value
        expect = np.zeros(7, np.uint32)
        for j in range(7):
            vals, counts = np.unique(sk[:, j], return_counts=True)
            expect[j] = vals[np.argmax(counts)]
        assert (modal == expect).all()
        # and first_divergence_slots agrees with its own default
        assert (stats.first_divergence_slots(sk, consensus=modal)
                == stats.first_divergence_slots(sk)).all()

    def test_merge_consensus_counts_each_fold_once(self):
        plan = KnobPlan.from_runtime(_saturating_rt(sketch_slots=4))
        cs = [Corpus(plan, worker_id=w) for w in range(2)]
        for c in cs:
            c.track_admissions = True
        sk0 = np.zeros((4, 3), np.uint32)          # 4 lanes of value 0
        sk1 = np.ones((6, 3), np.uint32)           # 6 lanes of value 1
        cs[0]._fold_sketches(sk0)
        cs[1]._fold_sketches(sk1)
        tally = merge_consensus(cs, None)
        assert tally[0] == {0: 4, 1: 6}
        # both corpora hold the merged view; a second merge with no new
        # folds must NOT double-count the shared history
        assert cs[0]._slot_counts[0] == {0: 4, 1: 6}
        tally = merge_consensus(cs, tally)
        assert tally[0] == {0: 4, 1: 6}
        # new folds enter exactly once
        cs[0]._fold_sketches(np.full((3, 3), 1, np.uint32))
        tally = merge_consensus(cs, tally)
        assert tally[0] == {0: 4, 1: 9}
        assert cs[1]._slot_counts[0] == {0: 4, 1: 9}
        # consensus flips to the hotter value on every shard
        assert int(cs[0].consensus_sketch()[0]) == 1

    def test_single_corpus_merge_is_value_noop(self):
        plan = KnobPlan.from_runtime(_saturating_rt(sketch_slots=4))
        c = Corpus(plan)
        c.track_admissions = True
        c._fold_sketches(np.arange(12, dtype=np.uint32).reshape(4, 3) % 3)
        before = [dict(s) for s in c._slot_counts]
        merge_consensus([c], None)
        assert c._slot_counts == before


class TestShardedResume:
    def test_split_equals_continuous_two_shards(self, tmp_path):
        dc, dd = str(tmp_path / "c"), str(tmp_path / "d")
        kw = dict(KW, shards=2)
        fuzz_sharded(_saturating_rt(), corpus_dir=dc,
                     **dict(kw, max_rounds=2))
        rs = fuzz_sharded(_saturating_rt(), corpus_dir=dc,
                          **dict(kw, max_rounds=4))
        rc = fuzz_sharded(_saturating_rt(), corpus_dir=dd,
                          **dict(kw, max_rounds=4))
        assert rs["rounds"] == 2 and rs["rounds_done_total"] == 4
        assert rc["rounds"] == 4
        assert _store_bytes(dc) == _store_bytes(dd)
        gc_ = CorpusStore(dc, create=False).load_shard_group_state(0)
        gd = CorpusStore(dd, create=False).load_shard_group_state(0)
        assert [s["order"] for s in gc_["shard_states"]] \
            == [s["order"] for s in gd["shard_states"]]
        assert [s["rng_state"] for s in gc_["shard_states"]] \
            == [s["rng_state"] for s in gd["shard_states"]]
        assert gc_["tally"] == gd["tally"]
        # finished campaign: a further call is a durable no-op
        r3 = fuzz_sharded(_saturating_rt(), corpus_dir=dc,
                          **dict(kw, max_rounds=4))
        assert r3["rounds"] == 0

    def test_resume_rejects_different_shard_count(self, tmp_path):
        from madsim_tpu.service import StoreMismatch
        d = str(tmp_path / "c")
        fuzz_sharded(_saturating_rt(), shards=2, corpus_dir=d,
                     **dict(KW, max_rounds=2))
        with pytest.raises(StoreMismatch):
            fuzz_sharded(_saturating_rt(), shards=4, corpus_dir=d,
                         **dict(KW, max_rounds=4))

    def test_namespace_collision_guard(self, tmp_path):
        """The worker_id*shards+s mapping numerically overlaps plain
        worker ids — mixing owners of one namespace on one dir must be
        refused at open, in both directions, before any entry file
        could collide."""
        from madsim_tpu.service import StoreMismatch
        d = str(tmp_path / "c")
        # group 0 at 2 shards owns namespaces 0 and 1 ...
        fuzz_sharded(_saturating_rt(), shards=2, corpus_dir=d,
                     **dict(KW, max_rounds=1))
        with pytest.raises(StoreMismatch, match="owned by"):
            fuzz(_saturating_rt(), corpus_dir=d, worker_id=1,
                 **dict(KW, max_rounds=1))
        # ... and a plain worker blocks a group that would claim it
        d2 = str(tmp_path / "d")
        fuzz(_saturating_rt(), corpus_dir=d2, worker_id=1,
             **dict(KW, max_rounds=1))
        with pytest.raises(StoreMismatch, match="owned by"):
            fuzz_sharded(_saturating_rt(), shards=2, worker_id=0,
                         corpus_dir=d2, **dict(KW, max_rounds=1))
        # disjoint namespaces still compose (worker 1 at 2 shards owns
        # 2 and 3 — fine next to group 0's 0 and 1)
        fuzz_sharded(_saturating_rt(), shards=2, worker_id=1,
                     corpus_dir=d, **dict(KW, max_rounds=1))


class _FlakyRuntime:
    """Delegates to a real Runtime, but corrupts the FIRST `run_fused`
    result (sched_hash xored, crash lanes cleared) — the shape of the
    persistent-cache first-invocation transient (ROADMAP r12)."""

    def __init__(self, rt, corrupt_calls=1):
        self._rt = rt
        self._left = corrupt_calls
        self.calls = 0

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def run_fused(self, state, max_steps, chunk=512):
        import jax.numpy as jnp
        out = self._rt.run_fused(state, max_steps, chunk)
        self.calls += 1
        if self._left > 0:
            self._left -= 1
            out = out.replace(
                sched_hash=out.sched_hash ^ jnp.uint32(0xBAD),
                crashed=jnp.zeros_like(out.crashed),
                crash_code=jnp.zeros_like(out.crash_code))
        return out


class TestVerifyGuards:
    def test_verify_resume_contains_corrupted_first_invocation(
            self, tmp_path):
        dc, dd = str(tmp_path / "c"), str(tmp_path / "d")
        fuzz(_saturating_rt(), corpus_dir=dc, **dict(KW, max_rounds=2))
        flaky = _FlakyRuntime(_saturating_rt())
        fuzz(flaky, corpus_dir=dc, verify_resume=True,
             **dict(KW, max_rounds=4))
        fuzz(_saturating_rt(), corpus_dir=dd, **dict(KW, max_rounds=4))
        assert flaky.calls >= 3      # round + at least one re-dispatch
        assert _store_bytes(dc) == _store_bytes(dd)

    def test_without_verify_corruption_forks_the_campaign(self, tmp_path):
        dc, dd = str(tmp_path / "c"), str(tmp_path / "d")
        fuzz(_saturating_rt(), corpus_dir=dc, **dict(KW, max_rounds=2))
        fuzz(_FlakyRuntime(_saturating_rt()), corpus_dir=dc,
             verify_resume=False, **dict(KW, max_rounds=4))
        fuzz(_saturating_rt(), corpus_dir=dd, **dict(KW, max_rounds=4))
        assert _store_bytes(dc) != _store_bytes(dd)

    def test_verify_resume_sharded(self, tmp_path):
        dc, dd = str(tmp_path / "c"), str(tmp_path / "d")
        kw = dict(KW, shards=2)
        fuzz_sharded(_saturating_rt(), corpus_dir=dc,
                     **dict(kw, max_rounds=2))
        flaky = _FlakyRuntime(_saturating_rt())
        fuzz_sharded(flaky, corpus_dir=dc, verify_resume=True,
                     **dict(kw, max_rounds=4))
        fuzz_sharded(_saturating_rt(), corpus_dir=dd,
                     **dict(kw, max_rounds=4))
        assert _store_bytes(dc) == _store_bytes(dd)

    def test_verify_raises_on_real_nondeterminism(self, tmp_path):
        dc = str(tmp_path / "c")
        fuzz(_saturating_rt(), corpus_dir=dc, **dict(KW, max_rounds=2))
        # corrupting every invocation differently is real nondeterminism
        class _Chaos(_FlakyRuntime):
            def run_fused(self, state, max_steps, chunk=512):
                import jax.numpy as jnp
                out = self._rt.run_fused(state, max_steps, chunk)
                self.calls += 1
                return out.replace(
                    sched_hash=out.sched_hash ^ jnp.uint32(self.calls))
        with pytest.raises(RuntimeError, match="deterministic"):
            fuzz(_Chaos(_saturating_rt()), corpus_dir=dc,
                 verify_resume=True, **dict(KW, max_rounds=4))

    def test_replay_bucket_verify(self, tmp_path):
        d = str(tmp_path / "c")
        kw = dict(max_steps=1500, batch=8, max_rounds=2, dry_rounds=9,
                  chunk=256)
        res = fuzz(_crashrich_rt(), corpus_dir=d, **kw)
        assert res["buckets_total"] > 0
        key = CorpusStore(d, create=False).bucket_keys()[0]
        plain = replay_bucket(_crashrich_rt(), d, key, max_steps=1500,
                              chunk=256, verify=False)
        verified = replay_bucket(_crashrich_rt(), d, key, max_steps=1500,
                                 chunk=256, verify=True)
        assert plain[:2] == verified[:2]
        assert verified[0] is True    # the bucket's crash reproduces
        # a corrupted first invocation is contained under verify
        flaky = _FlakyRuntime(_crashrich_rt())
        crashed, code, _ = replay_bucket(flaky, d, key, max_steps=1500,
                                         chunk=256, verify=True)
        assert (crashed, code) == plain[:2]
        assert flaky.calls >= 3


class TestSupervisor:
    def _mk_store_with_states(self, tmp_path):
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        from madsim_tpu.service import store_signature
        d = str(tmp_path / "c")
        store = CorpusStore(d, signature=store_signature(rt, plan))
        from madsim_tpu.service.store import _atomic_json
        _atomic_json(store.worker_state_path(0), dict(
            worker_id=0, rounds_done=2, dry=0, wall_s=1.0, op_hist=[],
            next_counter=5, rng_state={}, crash_codes=[],
            sketch_counts=None,
            order=[[i, e] for i, e in
                   enumerate([5.0, 0.05, 2.0, 0.01, 0.06, 3.0])]))
        _atomic_json(store.shard_group_path(1), dict(
            worker_id=1, shards=2, rounds_done=2, dry=0, wall_s=1.0,
            op_hist=[], tally=None, shard_states=[
                dict(worker_id=2, next_counter=1, rng_state={},
                     crash_codes=[], sketch_counts=None,
                     order=[[9, 0.01], [10, 4.0], [11, 0.02], [12, 0.3],
                            [13, 0.01]]),
                dict(worker_id=3, next_counter=0, rng_state={},
                     crash_codes=[], sketch_counts=None,
                     order=[[20, 0.01]])]))
        return d, store

    def test_prune_cold_entries(self, tmp_path):
        d, store = self._mk_store_with_states(tmp_path)
        out = prune_cold_entries(d, below=0.1, keep_min=2)
        ws = store.load_worker_state(0)
        # cold rows dropped, hot rows kept, order preserved
        assert [e for _, e in ws["order"]] == [5.0, 2.0, 3.0]
        gs = store.load_shard_group_state(1)
        assert [e for _, e in gs["shard_states"][0]["order"]] \
            == [4.0, 0.3]
        # keep_min floor: a tiny corpus is never pruned below it
        assert gs["shard_states"][1]["order"] == [[20, 0.01]]
        assert out["pruned"] == 3 + 3
        # everything else in the states is untouched
        assert ws["next_counter"] == 5 and gs["shards"] == 2

    def test_supervise_campaign_rotates_restarts_prunes(self, tmp_path):
        d, store = self._mk_store_with_states(tmp_path)
        calls = []

        def fake_segment(factory, corpus_dir, **kw):
            calls.append(kw["max_rounds"])
            dead = {"0": {"returncode": 137, "result": None}} \
                if len(calls) == 1 else {}
            return dict(rounds_done=2 * len(calls), coverage_keys=7,
                        buckets=1,
                        worker_results={"1": {"returncode": 0},
                                        **dead})

        out = supervise_campaign(
            "madsim_tpu.models.workloads:saturating", d, workers=2,
            segments=3,
            rounds_per_segment=4, max_steps=100,
            run_segment=fake_segment)
        assert calls == [4, 8, 12]            # the rotation
        assert out["restarts"] == 1           # the SIGKILLed worker
        # default keep_min=4 protects the hottest rows: worker 0 loses
        # its 2 cold unprotected rows, group shard 0 loses 1, the
        # 1-entry shard is floored — 3 pruned on the first boundary,
        # nothing left on the second
        assert out["pruned"] == 3
        assert [s["max_rounds"] for s in out["segments"]] == [4, 8, 12]
        assert out["segments"][0]["dead_workers"] == [0]
        assert out["report"]["kind"] == "campaign"


class TestShardObservability:
    def test_round_records_carry_per_shard_rows(self):
        recs = []

        class Rec:
            def on_round(self, r):
                recs.append(r)

            def on_done(self, r):
                pass

        fuzz_sharded(_saturating_rt(), shards=2, observer=Rec(),
                     **dict(KW, max_rounds=2))
        assert recs and all(r["kind"] == "fuzz_round" for r in recs)
        for r in recs:
            assert r["shards"] == 2
            assert "new_crash_codes" in r     # the fuzz_round schema
            assert len(r["per_shard"]) == 2
            for row in r["per_shard"]:
                for k in ("shard", "worker_id", "corpus_size", "coverage",
                          "new", "crashes", "seeds_run"):
                    assert k in row

    def test_progress_observer_renders_shard_rows(self):
        buf = io.StringIO()
        obs = ProgressObserver(stream=buf, min_interval=0.0)
        obs.on_round(dict(
            kind="fuzz_round", round=1, batch=16, shards=2, seeds_run=32,
            new_schedules=5, distinct_total=5, crashes=0, corpus_size=5,
            dry_rounds=0, wall_s=1.0,
            per_shard=[dict(shard=0, worker_id=0, corpus_size=3,
                            coverage=3, new=3, crashes=0, seeds_run=16),
                       dict(shard=1, worker_id=1, corpus_size=2,
                            coverage=2, new=2, crashes=0, seeds_run=16)]))
        text = buf.getvalue()
        assert "x2 shards" in text
        assert "shard 0 (w0)" in text and "shard 1 (w1)" in text
        obs.on_round(dict(kind="supervisor", segment=0, max_rounds=4,
                          dead_workers=[1], restarts=1, pruned=3))
        assert "supervisor seg 0" in buf.getvalue()


class TestRunFusedSharded:
    def test_method_matches_unsharded(self):
        rt = _saturating_rt()
        seeds = np.arange(16, dtype=np.uint32)
        a = rt.run_fused(rt.init_batch(seeds), 400, 128)
        b = rt.run_fused_sharded(rt.init_batch(seeds), 400, 128)
        np.testing.assert_array_equal(np.asarray(a.sched_hash),
                                      np.asarray(b.sched_hash))
        np.testing.assert_array_equal(rt.fingerprints(a),
                                      rt.fingerprints(b))
