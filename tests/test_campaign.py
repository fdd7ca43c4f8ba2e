"""Persistent fuzzing campaigns (r11): durable corpus store, causal-
fingerprint crash buckets, resumable multi-process service.

Load-bearing contracts (DESIGN §13):
(1) save -> load -> resume is BIT-IDENTICAL: a restored corpus schedules
the same parents and derives the same mutants leaf-for-leaf, and a
split fuzz campaign ends byte-equal to an uninterrupted one;
(2) the store REJECTS mismatches loudly (format version, structural
signature) instead of merging unreplayable entries;
(3) a kill at any instant leaves a loadable store (write-then-rename:
tmp leftovers ignored, half-synced own entries quarantined until the
re-run rewrites them);
(4) entry ids are worker-namespaced — collision-free across processes,
so by-id parent rewards/evictions stay sound under merge;
(5) crash buckets dedup by causal fingerprint across workers, and a
bucket's (seed, knobs) handle replays its crash.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from madsim_tpu import fuzz
from madsim_tpu.obs.causal import causal_fingerprint
from madsim_tpu.search.corpus import Corpus, split_entry_id
from madsim_tpu.search.fuzz import WORKER_SEED_STRIDE
from madsim_tpu.search.mutate import N_MUT_OPS, KnobPlan
from madsim_tpu.service import (CorpusStore, CrashBuckets, StoreMismatch,
                                campaign_report, merged_buckets,
                                replay_bucket, run_campaign,
                                store_signature, worker_cmd)
from madsim_tpu.service.store import CORPUS_VERSION

CRASHRICH = "madsim_tpu.models.workloads:crashrich"   # the worker factory


def _saturating_rt(trace_cap=16, sketch_slots=4):
    from madsim_tpu.models.workloads import saturating
    return saturating(trace_cap=trace_cap, sketch_slots=sketch_slots)


def _crashrich_rt():
    # trace_cap/batch/steps chosen to SHARE executables with
    # test_causal's fast-lane wal_kv runs (one compile, two files)
    from madsim_tpu.models.workloads import crashrich
    return crashrich("wal_kv", trace_cap=128)


def _mk_store(tmp_path, rt, plan, name="corpus"):
    return CorpusStore(str(tmp_path / name),
                       signature=store_signature(rt, plan))


def _observe_round(corpus, plan, n=8, hash0=100, round_no=0, crashed=None,
                   sketches=None):
    knobs = KnobPlan.stack([plan.base_knobs() for _ in range(n)])
    corpus.observe(
        knobs, seeds=np.arange(n), crashed=(crashed if crashed is not None
                                            else np.zeros(n, bool)),
        hashes_u64=np.arange(hash0, hash0 + n, dtype=np.uint64),
        codes=np.full(n, 7), parent_ids=np.full(n, -1),
        round_no=round_no, sketches=sketches)


class TestStoreRoundTrip:
    def test_next_round_mutants_bit_identical(self, tmp_path):
        """The satellite contract: save -> load -> the next round's
        parent draws AND derived mutants are leaf-for-leaf identical."""
        import jax
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        c1 = Corpus(plan, rng=np.random.default_rng(7))
        c1.track_evictions = True
        sk = np.arange(24, dtype=np.uint32).reshape(8, 3) % 5
        _observe_round(c1, plan, round_no=0, sketches=sk,
                       crashed=np.asarray([1, 0, 0, 0, 0, 0, 0, 1], bool))
        _observe_round(c1, plan, n=4, hash0=300, round_no=1)
        store = _mk_store(tmp_path, rt, plan)
        store.sync(c1, 0, rounds_done=2, dry=0,
                   op_hist=np.zeros(N_MUT_OPS, np.int64), wall_s=1.0)
        c2 = CorpusStore(str(tmp_path / "corpus"),
                         signature=store_signature(rt, plan)
                         ).load_corpus(plan, worker_id=0, rng_seed=7)
        assert [e["id"] for e in c2.entries] == [e["id"] for e in c1.entries]
        assert [e["energy"] for e in c2.entries] \
            == [e["energy"] for e in c1.entries]
        assert c2.coverage_keys() == c1.coverage_keys()
        assert c2.crash_codes == c1.crash_codes
        assert c2._slot_counts == c1._slot_counts
        assert (c2.consensus_sketch() == c1.consensus_sketch()).all()
        p1, i1 = c1.schedule(16)
        p2, i2 = c2.schedule(16)
        assert (i1 == i2).all()
        for k in p1:
            assert (np.asarray(p1[k]) == np.asarray(p2[k])).all(), k
        key = jax.random.PRNGKey(3)
        m1, h1, _ = plan.mutate(p1, key)
        m2, h2, _ = plan.mutate(p2, key)
        assert (np.asarray(h1) == np.asarray(h2)).all()
        for k in m1:
            assert (np.asarray(m1[k]) == np.asarray(m2[k])).all(), k

    def test_split_fuzz_equals_continuous(self, tmp_path):
        """The durability proof, in-process: interrupt a campaign at the
        round boundary, resume it, and the store ends byte-equal to an
        uninterrupted run (coverage keys, entry files, ids, knobs)."""
        kw = dict(max_steps=400, batch=16, dry_rounds=9, chunk=128)
        da, db = str(tmp_path / "a"), str(tmp_path / "b")
        fuzz(_saturating_rt(), max_rounds=2, corpus_dir=da, **kw)
        ra = fuzz(_saturating_rt(), max_rounds=4, corpus_dir=da, **kw)
        rb = fuzz(_saturating_rt(), max_rounds=4, corpus_dir=db, **kw)
        assert ra["rounds"] == 2 and ra["rounds_done_total"] == 4
        assert rb["rounds"] == 4
        assert ra["distinct_schedules"] == rb["distinct_schedules"]
        sa = CorpusStore(da, create=False)
        sb = CorpusStore(db, create=False)
        assert sa.coverage_keys() == sb.coverage_keys()
        assert sa.entry_names() == sb.entry_names()
        for n in sa.entry_names():
            ea, eb = sa.load_entry(n), sb.load_entry(n)
            assert ea["hash"] == eb["hash"] and ea["id"] == eb["id"]
            for k in ea["knobs"]:
                assert (np.asarray(ea["knobs"][k])
                        == np.asarray(eb["knobs"][k])).all(), (n, k)
        # a third call on the finished campaign is a durable no-op
        r3 = fuzz(_saturating_rt(), max_rounds=4, corpus_dir=da, **kw)
        assert r3["rounds"] == 0
        assert r3["distinct_schedules"] == ra["distinct_schedules"]


class TestStoreContracts:
    def _store(self, tmp_path):
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        return _mk_store(tmp_path, rt, plan), rt, plan

    def test_version_mismatch_rejects(self, tmp_path):
        store, rt, plan = self._store(tmp_path)
        p = os.path.join(store.dir, "MANIFEST.json")
        man = json.load(open(p))
        man["version"] = CORPUS_VERSION + 1
        json.dump(man, open(p, "w"))
        with pytest.raises(StoreMismatch, match="version"):
            CorpusStore(store.dir, signature=store_signature(rt, plan))

    def test_signature_mismatch_rejects(self, tmp_path):
        store, rt, plan = self._store(tmp_path)
        other = _crashrich_rt()
        with pytest.raises(StoreMismatch, match="structurally different"):
            CorpusStore(store.dir, signature=store_signature(
                other, KnobPlan.from_runtime(other)))

    def test_not_a_corpus_dir_rejects(self, tmp_path):
        d = tmp_path / "x"
        d.mkdir()
        json.dump({"format": "something-else"},
                  open(d / "MANIFEST.json", "w"))
        with pytest.raises(StoreMismatch, match="not a corpus"):
            CorpusStore(str(d))

    def test_missing_dir_without_create(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            CorpusStore(str(tmp_path / "nope"), create=False)

    def test_kill_mid_write_leaves_loadable_store(self, tmp_path):
        """The atomic-rename contract: a writer killed mid-write leaves
        only `.tmp-` siblings, which every reader ignores."""
        store, rt, plan = self._store(tmp_path)
        c = Corpus(plan, rng=np.random.default_rng(0))
        c.track_evictions = True
        _observe_round(c, plan)
        store.sync(c, 0, rounds_done=1, dry=0,
                   op_hist=np.zeros(N_MUT_OPS), wall_s=0.5)
        # simulate kills mid-write of every file class
        for rel in ("entries/w0000-000000000099.npz.tmp-777",
                    "state/w0000.json.tmp-777",
                    "buckets/deadbeef.json.tmp-777",
                    "MANIFEST.json.tmp-777"):
            with open(os.path.join(store.dir, rel), "w") as f:
                f.write("torn half-write garbage")
        s2 = CorpusStore(store.dir, signature=store_signature(rt, plan))
        c2 = s2.load_corpus(plan, worker_id=0, rng_seed=0)
        assert c2.coverage_keys() == c.coverage_keys()
        assert s2.bucket_keys() == []
        assert len(s2.entry_names()) == len(c.entries)

    def test_half_synced_own_entries_quarantined(self, tmp_path):
        """A kill DURING sync (entry files renamed, state json not yet):
        own-namespace entries at/past the persisted counter are ignored
        on load — the interrupted round re-runs and rewrites them —
        so the resumed corpus equals the uninterrupted one."""
        store, rt, plan = self._store(tmp_path)
        c = Corpus(plan, rng=np.random.default_rng(0))
        c.track_evictions = True
        _observe_round(c, plan)          # counters 0..7, next_counter=8
        store.sync(c, 0, rounds_done=1, dry=0,
                   op_hist=np.zeros(N_MUT_OPS), wall_s=0.5)
        orphan = dict(c.entries[0], id=(0 << 40) | 42, hash=999_999)
        store.write_entry(orphan)        # counter 42 >= next_counter 8
        c2 = CorpusStore(store.dir, signature=store_signature(rt, plan)
                         ).load_corpus(plan, worker_id=0, rng_seed=0)
        assert 999_999 not in c2.coverage_keys()
        assert all(e["id"] != orphan["id"] for e in c2.entries)

    def test_evicted_coverage_survives_resume(self, tmp_path):
        """Eviction never forgets: a hash admitted then evicted between
        syncs still blocks re-admission after a resume."""
        store, rt, plan = self._store(tmp_path)
        c = Corpus(plan, rng=np.random.default_rng(0), max_entries=4)
        c.track_evictions = True
        _observe_round(c, plan, n=8)     # 8 admissions into 4 slots
        assert len(c.entries) == 4 and len(c.coverage_keys()) == 8
        store.sync(c, 0, rounds_done=1, dry=0,
                   op_hist=np.zeros(N_MUT_OPS), wall_s=0.5)
        c2 = CorpusStore(store.dir, signature=store_signature(rt, plan)
                         ).load_corpus(plan, worker_id=0, rng_seed=0,
                                       max_entries=4)
        assert c2.coverage_keys() == c.coverage_keys()
        assert len(c2.entries) == 4


class TestWorkerNamespacing:
    def test_durable_fuzz_rejects_mismatched_corpus_namespace(self,
                                                              tmp_path):
        """A passed-in corpus minting ids outside fuzz's worker_id would
        persist a worker state pointing at entry files sync never
        writes — reject before touching the dir."""
        rt = _saturating_rt()
        corpus = Corpus(KnobPlan.from_runtime(rt),
                        rng=np.random.default_rng(0), worker_id=0)
        with pytest.raises(ValueError, match="worker_id"):
            fuzz(rt, max_steps=200, batch=8, max_rounds=1, chunk=64,
                 corpus=corpus, corpus_dir=str(tmp_path / "c"),
                 worker_id=3)

    def test_ids_collision_free_across_workers(self):
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        c0 = Corpus(plan, rng=np.random.default_rng(0), worker_id=0)
        c3 = Corpus(plan, rng=np.random.default_rng(0), worker_id=3)
        _observe_round(c0, plan)
        _observe_round(c3, plan)
        ids0 = {e["id"] for e in c0.entries}
        ids3 = {e["id"] for e in c3.entries}
        assert not ids0 & ids3
        for eid in ids3:
            w, cnt = split_entry_id(eid)
            assert w == 3 and 0 <= cnt < 8
        # same-hash entries dedupe on merge, ids stay foreign
        merged = sum(c0.admit_foreign(e) for e in c3.entries)
        assert merged == 0               # identical hashes: nothing new

    def test_merge_foreign_rewards_stay_sound(self, tmp_path):
        """The r9 by-id reward contract under merge: a lane bred from a
        FOREIGN parent rewards exactly that merged entry — or nobody
        after its eviction — never a colliding local id."""
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        store = _mk_store(tmp_path, rt, plan)
        c0 = Corpus(plan, rng=np.random.default_rng(0), worker_id=0)
        c0.track_evictions = True
        _observe_round(c0, plan, hash0=100)
        store.sync(c0, 0, rounds_done=1, dry=0,
                   op_hist=np.zeros(N_MUT_OPS), wall_s=0.1)
        c1 = store.load_corpus(plan, worker_id=1, rng_seed=1)
        assert len(c1.entries) == 8      # all of w0's merged in
        foreign = c1.entries[0]
        assert split_entry_id(foreign["id"])[0] == 0
        e0 = foreign["energy"]
        knobs = KnobPlan.stack([plan.base_knobs() for _ in range(2)])
        c1.observe(knobs, seeds=np.arange(2),
                   hashes_u64=np.asarray([900, 901], np.uint64),
                   crashed=np.zeros(2, bool), codes=np.zeros(2),
                   parent_ids=np.asarray([foreign["id"], -1]),
                   round_no=1)
        assert foreign["energy"] > e0 * 0.9  # rewarded (net of decay)
        new_ids = {e["id"] for e in c1.entries} - {e["id"] for e in
                                                   c0.entries}
        assert all(split_entry_id(i)[0] == 1 for i in new_ids)


class TestCrashBuckets:
    def _exp(self, toks, code=301, node=2, truncated=False,
             root_external=True):
        chain = [dict(step=i, now=i * 10, kind=k, node=n, src=s, tag=t,
                      parent=i - 1, lamport=i + 1)
                 for i, (k, n, s, t) in enumerate(toks)]
        return dict(chain=chain, truncated=truncated,
                    root_external=root_external, crashed=True,
                    crash_code=code, crash_node=node, lane=0, dropped=0)

    def test_bucket_files_and_repro_roundtrip(self, tmp_path):
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        store = _mk_store(tmp_path, rt, plan)
        bk = CrashBuckets(store)
        toks = [(1, 0, 0, 5), (2, 1, 0, 7), (2, 0, 1, 7)]
        knobs = plan.base_knobs()
        key, opened = bk.observe(
            causal_fingerprint(self._exp(toks)), seed=11, knobs=knobs,
            round_no=0, worker_id=0,
            chain=self._exp(toks)["chain"])
        assert opened and store.bucket_keys() == [key]
        rec = store.load_bucket(key)
        assert rec["crash_code"] == 301
        assert len(rec["chain"]) == 3
        seed, kn = store.load_bucket_repro(key)
        assert seed == 11
        for k in knobs:
            assert (np.asarray(kn[k]) == np.asarray(knobs[k])).all(), k

    def test_wrap_truncated_rebucket_dedups(self, tmp_path):
        """One bug, observed complete and then wrap-truncated at two
        different depths: one bucket, three observations."""
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        store = _mk_store(tmp_path, rt, plan)
        bk = CrashBuckets(store)
        toks = [(1, 0, 0, 5), (2, 1, 0, 7), (2, 0, 1, 7), (3, 1, 1, 2)]
        full = causal_fingerprint(self._exp(toks))
        cut3 = causal_fingerprint(self._exp(
            toks[1:], truncated=True, root_external=False))
        cut2 = causal_fingerprint(self._exp(
            toks[2:], truncated=True, root_external=False))
        k0, o0 = bk.observe(full, seed=1, knobs=plan.base_knobs(),
                            round_no=0, worker_id=0)
        k1, o1 = bk.observe(cut3, seed=2, knobs=plan.base_knobs(),
                            round_no=1, worker_id=1)
        k2, o2 = bk.observe(cut2, seed=3, knobs=plan.base_knobs(),
                            round_no=2, worker_id=0)
        assert o0 and not o1 and not o2
        assert k0 == k1 == k2
        assert len(store.bucket_keys()) == 1
        m = merged_buckets(store)
        assert len(m) == 1 and m[0]["observations"] == 3

    def test_merged_buckets_repairs_concurrent_open_race(self, tmp_path):
        """Two workers opening buckets for one bug at different wrap
        depths in the same instant (no refresh between): the read-side
        merge folds them."""
        rt = _saturating_rt()
        plan = KnobPlan.from_runtime(rt)
        store = _mk_store(tmp_path, rt, plan)
        toks = [(1, 0, 0, 5), (2, 1, 0, 7), (2, 0, 1, 7)]
        full = causal_fingerprint(self._exp(toks))
        cut = causal_fingerprint(self._exp(
            toks[1:], truncated=True, root_external=False))
        # two writers, neither saw the other's bucket before writing
        CrashBuckets(store).observe(full, seed=1, knobs=None,
                                    round_no=0, worker_id=0)
        rec = dict(key=cut["key"], fingerprint=cut, crash_code=301,
                   crash_node=2, chain=[],
                   repro=dict(seed=2, round=0, worker_id=1))
        store.write_bucket(cut["key"], rec)
        assert len(store.bucket_keys()) == 2
        m = merged_buckets(store)
        assert len(m) == 1
        assert set(m[0]["members"]) == {full["key"], cut["key"]}
        # deepest chain is canonical
        assert m[0]["key"] == full["key"]


class TestCampaignDedup:
    def test_two_workers_share_buckets(self, tmp_path):
        """Cross-process dedup, deterministically: worker 1 replays
        worker 0's seed space (base_seed offset cancels the worker
        stride), so both observe the SAME crashes — one bucket set, two
        observations each, and zero duplicate corpus entries."""
        d = str(tmp_path / "camp")
        kw = dict(max_steps=4096, batch=24, max_rounds=1, dry_rounds=3,
                  chunk=512)
        r0 = fuzz(_crashrich_rt(), corpus_dir=d, worker_id=0, **kw)
        assert r0["crashes"] > 0 and r0["buckets_total"] >= 1
        r1 = fuzz(_crashrich_rt(), corpus_dir=d, worker_id=1,
                  base_seed=-WORKER_SEED_STRIDE, **kw)
        store = CorpusStore(d, create=False)
        # same seeds -> same coverage: worker 1 admits nothing new
        assert {split_entry_id(store.load_entry(n)["id"])[0]
                for n in store.entry_names()} == {0}
        assert r1["distinct_schedules"] == r0["distinct_schedules"]
        # ... and the same crashes: same buckets, doubled observations
        assert r1["buckets_total"] == r0["buckets_total"]
        assert not r1["buckets_opened"]
        log = store.bucket_log()
        assert {li["worker_id"] for li in log} == {0, 1}
        per_bucket = {}
        for li in log:
            per_bucket.setdefault(li["bucket"], []).append(li["worker_id"])
        for key, ws in per_bucket.items():
            assert sorted(ws) == [0, 1], (key, ws)
        rep = campaign_report(d)
        assert rep["buckets_merged"] == len(store.bucket_keys())
        # a durable campaign writes its row table for triage on open
        assert store.load_triage_rows() is not None


class TestOneProcessPerChip:
    """A chip belongs to one process: run_campaign must never start a
    second JAX worker on an accelerator host (it would hang on the chip
    lock), and must decide so without initializing JAX itself."""

    def test_refuses_two_workers_on_an_accelerator_host(self, tmp_path,
                                                        monkeypatch):
        from madsim_tpu.service import campaign
        monkeypatch.setattr(campaign, "_accelerator_installed",
                            lambda: True)
        monkeypatch.setattr(campaign, "spawn_worker", None)  # never reached
        with pytest.raises(ValueError, match="shards="):
            campaign.run_campaign(CRASHRICH,
                                  str(tmp_path), workers=2, max_steps=64,
                                  env={"PATH": os.environ["PATH"]})

    def test_host_pinned_or_single_workers_pass(self, monkeypatch):
        from madsim_tpu.service import campaign
        monkeypatch.setattr(campaign, "_accelerator_installed",
                            lambda: True)
        campaign.check_one_process_per_chip(4, {"JAX_PLATFORMS": "cpu"})
        campaign.check_one_process_per_chip(1, {})

    def test_refuses_when_this_process_holds_the_chip(self, monkeypatch):
        import types
        from madsim_tpu.service import campaign
        monkeypatch.setitem(sys.modules, "jax._src.xla_bridge",
                            types.SimpleNamespace(_backends={"tpu": 0}))
        with pytest.raises(RuntimeError, match="chip lock"):
            campaign.check_one_process_per_chip(1, {})


@pytest.mark.slow
class TestCampaignProcesses:
    """The real multi-process contracts (subprocess workers pay a jax
    import + compile each)."""

    def _env(self):
        return dict(os.environ, JAX_PLATFORMS="cpu")

    def _cmd(self, d, worker, rounds):
        return worker_cmd(
            d, worker, CRASHRICH,
            factory_kwargs=dict(kind="wal_kv", trace_cap=64,
                                sketch_slots=4),
            max_steps=4096, batch=16, max_rounds=rounds, chunk=512)

    def test_sigkill_resume_equals_uninterrupted(self, tmp_path):
        dk, dc = str(tmp_path / "kill"), str(tmp_path / "ctrl")
        p = subprocess.Popen(self._cmd(dk, 0, 3), env=self._env(),
                             stdout=subprocess.DEVNULL)
        state = os.path.join(dk, "state", "w0000.json")
        deadline = time.time() + 300
        while time.time() < deadline and not os.path.exists(state):
            assert p.poll() is None, "worker died before first sync"
            time.sleep(0.2)
        assert os.path.exists(state), "no sync within 300s"
        p.send_signal(signal.SIGKILL)
        p.wait()
        assert json.load(open(state))["rounds_done"] < 3
        for d in (dk, dc):
            subprocess.run(self._cmd(d, 0, 3), env=self._env(),
                           check=True, stdout=subprocess.DEVNULL)
        sk = CorpusStore(dk, create=False)
        sc = CorpusStore(dc, create=False)
        assert sk.coverage_keys() == sc.coverage_keys()
        assert sk.entry_names() == sc.entry_names()
        assert sk.bucket_keys() == sc.bucket_keys()

    def test_two_concurrent_workers_merge_and_dedup(self, tmp_path):
        d = str(tmp_path / "merge")
        rep = run_campaign(
            CRASHRICH, d, workers=2, env=self._env(), poll_s=1.0,
            factory_kwargs=dict(kind="wal_kv", trace_cap=64,
                                sketch_slots=4),
            max_steps=4096, batch=16, max_rounds=2, chunk=512)
        for w, res in rep["worker_results"].items():
            assert res["returncode"] == 0, (w, res)
        store = CorpusStore(d, create=False)
        assert {n.split("-")[0] for n in store.entry_names()} \
            == {"w0000", "w0001"}
        # entries can transiently exceed coverage when both workers admit
        # one hash before their next merge sync — never the reverse
        assert 0 < rep["coverage_keys"] <= rep["corpus_entries"]
        assert rep["buckets_merged"] >= 1
        assert rep["crash_observations"] > rep["buckets_merged"]
        seen_by = {}
        for line in store.bucket_log():
            seen_by.setdefault(line["bucket"], set()).add(line["worker_id"])
        assert any(len(ws) == 2 for ws in seen_by.values()), seen_by

    def test_replay_bucket_reproduces_crash(self, tmp_path):
        d = str(tmp_path / "camp")
        res = fuzz(_crashrich_rt(), max_steps=4096, batch=24,
                   max_rounds=1, dry_rounds=3, chunk=512, corpus_dir=d,
                   worker_id=0)
        assert res["buckets_total"] >= 1
        store = CorpusStore(d, create=False)
        key = store.bucket_keys()[0]
        crashed, code, exp = replay_bucket(_crashrich_rt(), d, key,
                                           max_steps=4096, chunk=512)
        assert crashed
        assert code == store.load_bucket(key)["crash_code"]
        assert exp is not None and exp["chain"]
