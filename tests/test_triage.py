"""Campaign triage plane (r18): snapshots, diffs, attribution, audits.

Load-bearing contracts (DESIGN §19):
(1) snapshot IDENTITY — the snapshot body is a pure function of the
store's durable contents: same store -> byte-identical bytes, no
wall-clock fields, and triage_diff(s, s) is provably empty;
(2) bucket LIFECYCLE — a planted bucket classifies `new`, a removed or
newly-quiet one `stale`, a quiet-then-reobserved one `regressed`;
(3) attribution ACCOUNTING — per-recipe and per-operator attributions
each sum EXACTLY to their totals over the frozen grayfail_mix
regression corpus, with unattributable rows in an explicit `base`
class (zero silent leakage);
(4) the repro-health audit records a planted failing handle as `fail`
(and a broken one as `flaky`) WITHOUT aborting the sweep;
(5) the satellite fixes hold: bucket observations dedup by
(fingerprint, worker, round), and a finished campaign's last-syncing
worker is never flagged stale;
(6) per-node hasher seeding (the r18 robustness satellite): a node's
hash stream is a pure (seed, node) function — schedule-independent,
node-decoupled, and consuming it never moves any other draw.
"""

import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import (NetConfig, Program, Runtime, Scenario, SimConfig,
                        fuzz, ms)
from madsim_tpu.core import prng
from madsim_tpu.obs.causal import causal_fingerprint
from madsim_tpu.obs.dashboard import render_html, sparkline_svg
from madsim_tpu.runtime.scenario import (RECIPE_FAMILIES, classify_recipe,
                                         row_recipe_class)
from madsim_tpu.search.corpus import YIELD_NAMES
from madsim_tpu.search.mutate import KnobPlan
from madsim_tpu.service import (CorpusStore, CrashBuckets, audit_buckets,
                                campaign_stats, campaign_timeline,
                                merged_buckets, store_signature,
                                triage_diff, triage_snapshot)
from madsim_tpu.service.triage import (BASE_CLASS, classify_knobs,
                                       list_snapshots, load_audit,
                                       load_snapshot, snapshot_path)

FROZEN = os.path.join(os.path.dirname(__file__), "data",
                      "regression_corpus", "grayfail_mix")


@pytest.fixture()
def frozen(tmp_path):
    """A writable copy of the committed grayfail_mix campaign (the
    frozen store itself must stay byte-pristine — triage writes a
    triage/ subdir into the store)."""
    dst = tmp_path / "grayfail_mix"
    shutil.copytree(FROZEN, dst)
    return CorpusStore(str(dst), create=False)


@pytest.fixture(scope="module")
def grayfail_plan():
    """The frozen campaign's KnobPlan (REGRESSION.json: factory mix,
    dup_slots 2) — row-table source for attribution. Construction only;
    nothing compiles."""
    from madsim_tpu.models.workloads import grayfail
    rt = grayfail("mix")
    return KnobPlan.from_runtime(rt, dup_slots=2)


def _snap_bytes(store, n):
    with open(snapshot_path(store, n), "rb") as f:
        return f.read()


def _plant_bucket(store, knobs, *, code=999, seed=12345, round_no=9,
                  worker_id=0, tok=77):
    """Open a bucket with a deliberately DISTINCT causal fingerprint
    (unique token chain) + a real knobs npz + one observation line —
    the diff's planted `new` bucket."""
    chain = [dict(step=i, now=i * 10, kind=1, node=0, src=0,
                  tag=tok + i, parent=i - 1, lamport=i + 1)
             for i in range(3)]
    fp = causal_fingerprint(dict(
        chain=chain, truncated=False, root_external=True, crashed=True,
        crash_code=code, crash_node=0, lane=0, dropped=0))
    bk = CrashBuckets(store)
    key, opened = bk.observe(fp, seed=seed, knobs=knobs,
                             round_no=round_no, worker_id=worker_id,
                             chain=chain)
    assert opened
    return key


# ---------------------------------------------------------------------------
# (1) snapshot identity
# ---------------------------------------------------------------------------

class TestSnapshotIdentity:
    def test_same_store_twice_byte_identical(self, frozen, grayfail_plan):
        frozen.write_triage_rows(grayfail_plan)
        n1, _ = triage_snapshot(frozen)
        n2, _ = triage_snapshot(frozen)
        assert n2 == n1 + 1
        b1, b2 = _snap_bytes(frozen, n1), _snap_bytes(frozen, n2)
        assert b1 == b2
        # and a FRESH handle over the same dir (cold caches) agrees
        n3, _ = triage_snapshot(CorpusStore(frozen.dir, create=False))
        assert _snap_bytes(frozen, n3) == b1

    def test_no_wallclock_fields(self, frozen):
        _n, body = triage_snapshot(frozen)
        blob = json.dumps(body)
        assert "created_at" not in blob and "measured_at" not in blob

    def test_self_diff_is_empty(self, frozen):
        _n1, s1 = triage_snapshot(frozen)
        _n2, s2 = triage_snapshot(frozen)
        d = triage_diff(s1, s2)
        assert d["empty"]
        assert not any(d["buckets"].values())
        assert d["coverage"] == dict(added=0, removed=0)
        assert not any(d["attribution"].values())
        assert not d["workers"] and not d["audit"] and not d["p99"]
        # literal self-diff too
        assert triage_diff(s1, s1)["empty"]

    def test_history_numbers_monotonic(self, frozen):
        ns = [triage_snapshot(frozen)[0] for _ in range(3)]
        assert ns == sorted(ns)
        assert list_snapshots(frozen)[-3:] == ns
        assert load_snapshot(frozen, "last")["store"]["entries"] == 256
        assert load_snapshot(frozen, "prev") == load_snapshot(frozen,
                                                              ns[-2])


# ---------------------------------------------------------------------------
# (2) bucket lifecycle
# ---------------------------------------------------------------------------

class TestLifecycle:
    def test_planted_bucket_new_and_removed_stale(self, frozen,
                                                  grayfail_plan):
        frozen.write_triage_rows(grayfail_plan)
        _n, before = triage_snapshot(frozen)
        key = _plant_bucket(
            frozen, grayfail_plan.base_knobs())   # observe() logs too
        _n, after = triage_snapshot(frozen)
        d = triage_diff(before, after)
        assert d["buckets"]["new"] == [key]
        assert not d["empty"]
        # the planted bucket classifies by its knob vector, not "other"
        assert after["buckets"][key]["recipe"] in RECIPE_FAMILIES
        # removed -> stale (diff the other way)
        d_rev = triage_diff(after, before)
        assert key in d_rev["buckets"]["stale"]
        assert d_rev["buckets"]["new"] == []

    def _mini(self, max_round, buckets):
        return dict(
            store=dict(max_round=max_round, entries=0, coverage_total=0,
                       buckets_total=len(buckets),
                       crash_observations=0, workers={}),
            coverage=dict(keys=[]), buckets=buckets,
            attribution={}, workers_health={}, audit={},
            quiet_rounds=2)

    def _b(self, obs, last_round, key="k1"):
        return dict(crash_code=1, crash_node=0, members=[key],
                    observations=obs, first_round=0, last_round=last_round,
                    workers=[0], recipe="none", op="base",
                    repro=dict(seed=0, round=0, worker_id=0),
                    minimized=False)

    def test_quiet_then_reobserved_is_regressed(self):
        prev = self._mini(10, {"k1": self._b(3, 2)})   # quiet: 10-2 >= 2
        cur = self._mini(12, {"k1": self._b(4, 12)})
        d = triage_diff(prev, cur)
        assert d["buckets"]["regressed"] == ["k1"]
        assert d["buckets"]["grew"] == []

    def test_active_and_growing_is_grew(self):
        prev = self._mini(3, {"k1": self._b(3, 2)})    # active at prev
        cur = self._mini(5, {"k1": self._b(4, 5)})
        d = triage_diff(prev, cur)
        assert d["buckets"]["grew"] == ["k1"]
        assert d["buckets"]["regressed"] == []

    def test_newly_quiet_is_stale(self):
        prev = self._mini(2, {"k1": self._b(3, 2)})    # active at prev
        cur = self._mini(9, {"k1": self._b(3, 2)})     # quiet at cur
        d = triage_diff(prev, cur)
        assert d["buckets"]["stale"] == ["k1"]
        # still quiet on both sides -> no lifecycle event
        d2 = triage_diff(cur, cur)
        assert d2["empty"]

    def test_canonical_reelection_not_new_plus_stale(self):
        """A deeper member arriving can re-elect a merged bucket's
        canonical key; member overlap must keep it ONE bug."""
        prev = self._mini(3, {"k1": self._b(2, 3)})
        deeper = self._b(3, 4, key="k2")
        deeper["members"] = ["k2", "k1"]
        cur = self._mini(4, {"k2": deeper})
        d = triage_diff(prev, cur)
        assert d["buckets"]["new"] == []
        assert d["buckets"]["stale"] == []
        assert d["buckets"]["grew"] == ["k2"]


# ---------------------------------------------------------------------------
# (3) attribution accounting (the frozen regression corpus)
# ---------------------------------------------------------------------------

class TestAttribution:
    def test_sums_exact_on_frozen_corpus(self, frozen, grayfail_plan):
        frozen.write_triage_rows(grayfail_plan)
        _n, s = triage_snapshot(frozen)
        a = s["attribution"]
        assert a["rows_known"]
        # recipe side: every DISTINCT coverage key exactly once
        assert sum(a["recipe_coverage"].values()) \
            == s["store"]["coverage_total"] == 256
        # operator side: every committed admission exactly once
        assert sum(a["operator_coverage"].values()) \
            == s["store"]["entries"] == 256
        # bucket side: every merged bucket exactly once, both dims
        assert sum(a["recipe_buckets"].values()) \
            == s["store"]["buckets_total"] == 4
        assert sum(a["operator_buckets"].values()) == 4
        # no silent classes: only the declared families/operators
        assert set(a["recipe_coverage"]) \
            == set(RECIPE_FAMILIES) | {BASE_CLASS}
        assert set(a["operator_coverage"]) == set(YIELD_NAMES)
        # the mix campaign's gray rows dominate; nothing leaked to base
        assert a["recipe_coverage"][BASE_CLASS] == 0
        assert a["recipe_coverage"]["torn_write"] > 0

    def test_without_rows_everything_is_explicit_base(self, frozen):
        _n, s = triage_snapshot(frozen)           # no ROWS.json written
        a = s["attribution"]
        assert not a["rows_known"]
        assert a["recipe_coverage"][BASE_CLASS] \
            == s["store"]["coverage_total"]
        assert sum(a["recipe_coverage"].values()) \
            == s["store"]["coverage_total"]
        # operator attribution rides op_yield and still works rowless
        assert sum(a["operator_coverage"].values()) == 256

    def test_classifier_respects_knob_state(self, grayfail_plan):
        plan = grayfail_plan
        rows = dict(
            op=[int(x) for x in np.asarray(plan.base["op"])],
            drop_ok=[bool(x) for x in plan.drop_ok],
            torn_ok=[bool(x) for x in plan.torn_ok],
            base_torn=[int(x) for x in
                       np.asarray(plan.base["payload"])[:, -2] & 1])
        kn = plan.base_knobs()
        base_fam = classify_knobs(rows, kn)
        assert base_fam == "torn_write"          # the mix recipe's head
        # flipping the torn flag off every disk row demotes to the next
        # family present
        kn2 = {k: np.array(v) for k, v in kn.items()}
        kn2["row_flag"] = np.where(plan.torn_ok, 0, kn2["row_flag"])
        fam2 = classify_knobs(rows, kn2)
        assert fam2 == "slow_disk"
        # dropping EVERY droppable row leaves only pinned rows -> none
        kn3 = {k: np.array(v) for k, v in kn.items()}
        kn3["row_on"] = ~np.asarray(plan.drop_ok)
        assert classify_knobs(rows, kn3) == "none"
        # no row table -> explicit base
        assert classify_knobs(None, kn) == BASE_CLASS

    def test_row_and_scenario_classifiers(self):
        from madsim_tpu.core import types as T
        assert row_recipe_class(T.OP_SET_DISK, torn=True) == "torn_write"
        assert row_recipe_class(T.OP_SET_DISK) == "slow_disk"
        assert row_recipe_class(T.OP_SET_SKEW) == "clock_skew"
        assert row_recipe_class(T.OP_PARTITION_ONEWAY) == "asym_partition"
        assert row_recipe_class(T.OP_SET_LOSS) == "loss_latency"
        assert row_recipe_class(T.OP_KILL) == "none"
        assert classify_recipe(["none", "clock_skew",
                                "slow_disk"]) == "slow_disk"
        assert classify_recipe([]) == "none"
        from madsim_tpu.runtime import chaos
        sc = chaos.torn_write_kill(ms(10), 1, down=ms(5))
        assert sc.recipe_class() == "torn_write"
        sc2 = chaos.clock_drift(ms(10), 300, node=0)
        assert sc2.recipe_class() == "clock_skew"
        sc3 = Scenario()
        sc3.at(ms(1)).kill(0)
        sc3.at(ms(2)).halt()
        assert sc3.recipe_class() == "none"


# ---------------------------------------------------------------------------
# (4) repro-health audit
# ---------------------------------------------------------------------------

def _crashrich_rt():
    from madsim_tpu.models.workloads import crashrich
    return crashrich("wal_kv", trace_cap=128)


class TestAudit:
    def test_fail_and_flaky_recorded_without_abort(self, tmp_path):
        rt = _crashrich_rt()
        d = str(tmp_path / "campaign")
        res = fuzz(rt, max_steps=3000, batch=16, max_rounds=2,
                   dry_rounds=8, chunk=512, corpus_dir=d, worker_id=0,
                   rng_seed=0)
        assert res["buckets_total"] >= 1, "crashrich campaign found none"
        store = CorpusStore(d, create=False)
        plan = KnobPlan.from_runtime(rt)
        # planted FAILING handle: every droppable chaos row disabled —
        # the replay runs the clean protocol and cannot crash
        benign = plan.base_knobs()
        benign["row_on"] = np.where(plan.drop_ok, False, True)
        fail_key = _plant_bucket(store, benign, code=901, tok=501)
        # planted BROKEN handle: bucket json without its knobs npz
        flaky_key = _plant_bucket(store, benign, code=902, tok=601)
        os.unlink(store.bucket_path(flaky_key, ".npz"))
        out = audit_buckets(rt, store, max_steps=3000, chunk=512,
                            budget=len(store.bucket_keys()))
        by_key = {a["bucket"]: a["status"] for a in out["audited"]}
        assert by_key[fail_key] == "fail"
        assert by_key[flaky_key] == "flaky"
        # the real bucket(s) still replay red — and the sweep finished
        real = [k for k in by_key if k not in (fail_key, flaky_key)]
        assert real and all(by_key[k] == "pass" for k in real)
        # verdicts fold into the next snapshot
        _n, snap = triage_snapshot(store)
        assert snap["audit"][fail_key]["status"] == "fail"
        assert snap["audit"][flaky_key]["status"] == "flaky"

    def test_rotation_cursor_advances(self, tmp_path):
        rt = _crashrich_rt()
        d = str(tmp_path / "c2")
        fuzz(rt, max_steps=3000, batch=16, max_rounds=2, dry_rounds=8,
             chunk=512, corpus_dir=d, worker_id=0, rng_seed=0)
        store = CorpusStore(d, create=False)
        plan = KnobPlan.from_runtime(rt)
        _plant_bucket(store, plan.base_knobs(), code=903, tok=701)
        keys = store.bucket_keys()
        assert len(keys) >= 2
        first = audit_buckets(rt, store, max_steps=3000, chunk=512,
                              budget=1)
        second = audit_buckets(rt, store, max_steps=3000, chunk=512,
                               budget=1)
        assert first["audited"][0]["bucket"] \
            != second["audited"][0]["bucket"]
        # the cursor is the last audited KEY (insertion-stable: a new
        # bucket sorting below it can't make the rotation re-audit)
        assert load_audit(store)["cursor_key"] \
            == second["audited"][0]["bucket"]


REGRESSION_ROOT = os.path.dirname(FROZEN)
REGRESSION_CAMPAIGNS = sorted(
    n for n in os.listdir(REGRESSION_ROOT)
    if os.path.isdir(os.path.join(REGRESSION_ROOT, n)))


class TestRegressionCorpus:
    """The committed campaign dirs as a regression suite (OSS-Fuzz
    style): every recorded crash bucket still reproduces under the
    run-twice verify guard, and the top-energy corpus entries still land
    on their recorded schedule hashes bit for bit — an engine change
    that rewires replay fails here. REGRESSION.json names the factory
    and the replay budget; the store's structural signature refuses a
    runtime that would replay the knobs onto the wrong rows.

    The stores were frozen under jax's earlier threefry key derivation
    (`jax_threefry_partitionable=False`; jax 0.9.0 defaults it to True).
    A (seed, knobs) handle names a trajectory only under the key
    derivation it was recorded with, and the store signature does not
    record it, so the replay pins that mode."""

    TOP_ENTRIES = 8
    CHUNK = 64      # fine enough that the recorded step budget binds

    @pytest.mark.parametrize("name", REGRESSION_CAMPAIGNS)
    def test_buckets_reproduce_and_entries_hit_recorded_hashes(
            self, name, tmp_path):
        import jax
        d = str(tmp_path / name)        # replay never touches the fixture
        shutil.copytree(os.path.join(REGRESSION_ROOT, name), d)
        with jax.threefry_partitionable(False):
            self._replay(name, d)

    def _replay(self, name, d):
        from madsim_tpu import replay_bucket
        from madsim_tpu.parallel.stats import sched_hash_u64
        from madsim_tpu.service.worker import resolve_factory
        with open(os.path.join(d, "REGRESSION.json")) as f:
            man = json.load(f)
        rt = resolve_factory(man["factory"])(**man["factory_kwargs"])
        dup, steps = int(man["dup_slots"]), int(man["max_steps"])
        plan = KnobPlan.from_runtime(rt, dup_slots=dup)
        store = CorpusStore(d, signature=store_signature(rt, plan),
                            create=False)
        keys = store.bucket_keys()
        assert set(man["buckets"]) <= set(keys), "recorded bucket missing"
        for key in keys:
            crashed, code, _ = replay_bucket(rt, d, key, steps,
                                             chunk=self.CHUNK,
                                             dup_slots=dup, verify=True)
            assert crashed, f"{name}/{key} no longer reproduces ({code})"
        order = sorted(store.load_worker_state(0)["order"],
                       key=lambda e: -e[1])[:self.TOP_ENTRIES]
        assert order
        for eid, _energy in order:
            ent = store.load_entry(store._entry_name(int(eid)))
            state = plan.apply(
                rt.init_batch(np.asarray([ent["seed"]], np.uint32)),
                KnobPlan.stack([ent["knobs"]]))
            fin = rt.run_fused(state, steps, self.CHUNK)
            got = int(sched_hash_u64(fin)[0])
            assert got == ent["hash"], \
                f"{name}: entry {eid} ran schedule {got:#x}, " \
                f"recorded {ent['hash']:#x}"


# ---------------------------------------------------------------------------
# (5) satellite fixes
# ---------------------------------------------------------------------------

class TestSatelliteFixes:
    def test_bucket_observations_deduped(self, frozen):
        line = dict(kind="crash", bucket="245503b450c447fe",
                    fp_key="245503b450c447fe", crash_code=501, seed=6,
                    round=0, worker_id=0, opened=False)
        base_obs = {m["key"]: m["observations"]
                    for m in merged_buckets(frozen)}
        base_stats = campaign_stats(frozen.dir, store=frozen)
        # a killed worker's resumed round re-appends IDENTICAL lines
        for _ in range(3):
            frozen.append_bucket_log(line)
        obs = {m["key"]: m["observations"] for m in merged_buckets(frozen)}
        assert obs == base_obs                       # replay never counts
        stats = campaign_stats(frozen.dir, store=frozen)
        assert stats["crash_observations"] \
            == base_stats["crash_observations"]
        # a DIFFERENT round of the same worker still counts
        frozen.append_bucket_log(dict(line, round=7))
        obs2 = {m["key"]: m["observations"]
                for m in merged_buckets(frozen)}
        assert obs2["245503b450c447fe"] \
            == base_obs["245503b450c447fe"] + 1
        # and so does another worker in the same round
        frozen.append_bucket_log(dict(line, worker_id=3))
        obs3 = {m["key"]: m["observations"]
                for m in merged_buckets(frozen)}
        assert obs3["245503b450c447fe"] \
            == base_obs["245503b450c447fe"] + 2

    def test_finished_campaign_worker_not_stale(self, tmp_path):
        rt_dir = str(tmp_path / "tl")
        store = CorpusStore(rt_dir, signature=["sig"])
        for i in range(4):
            store.append_metrics(0, dict(t=100.0 + 10 * i, worker=0,
                                         rounds_done=i + 1, coverage=i,
                                         wall_s=1.0 * i))
        # long after the campaign finished, from a wall-clock `now`:
        # the single worker IS the newest activity -> healthy
        tl = campaign_timeline(store, now=99999.0)
        h = tl["workers_health"]["w0000"]
        assert not h["stale"]
        assert h["age_s"] > 0                  # age still reports vs now

    def test_worker_behind_campaign_activity_is_stale(self, tmp_path):
        rt_dir = str(tmp_path / "tl2")
        store = CorpusStore(rt_dir, signature=["sig"])
        for i in range(4):
            store.append_metrics(0, dict(t=100.0 + 10 * i, worker=0,
                                         rounds_done=i + 1, coverage=i))
        for i in range(40):
            store.append_metrics(1, dict(t=100.0 + 10 * i, worker=1,
                                         rounds_done=i + 1, coverage=i))
        tl = campaign_timeline(store)
        assert tl["workers_health"]["w0000"]["stale"]
        assert not tl["workers_health"]["w0001"]["stale"]


class TestSuperviseHooks:
    def test_segments_accrete_diffable_history(self, frozen,
                                               grayfail_plan, capsys):
        """supervise_campaign snapshots between segments: a 3-segment
        run leaves a monotonically growing triage/ history whose last
        pair `service.report --against prev` diffs — re-reading raw
        entry files at most once per snapshot (the cached-classification
        contract rides the long-lived store handle supervise holds)."""
        from madsim_tpu.service import supervise_campaign
        frozen.write_triage_rows(grayfail_plan)
        loads = {"n": 0}
        orig = CorpusStore.load_entry

        def counting(self, name):
            loads["n"] += 1
            return orig(self, name)

        def fake_segment(factory, corpus_dir, **kw):
            return dict(rounds_done=4, coverage_keys=256, buckets=4,
                        worker_results={})

        recs = []

        class Rec:
            def on_round(self, r):
                recs.append(r)

        CorpusStore.load_entry = counting
        try:
            out = supervise_campaign(
                "madsim_tpu.models.workloads:grayfail", frozen.dir,
                workers=1,
                segments=3, rounds_per_segment=4, max_steps=100,
                run_segment=fake_segment, observer=Rec())
            n_supervise = loads["n"]
            # marginal snapshot cost on a long-lived handle: the first
            # walk classifies every immutable entry file once, the
            # second re-reads NONE (O(new files), like the poll loop)
            handle = CorpusStore(frozen.dir, create=False)
            triage_snapshot(handle)
            first = loads["n"] - n_supervise
            triage_snapshot(handle)
            assert loads["n"] - n_supervise == first
        finally:
            CorpusStore.load_entry = orig
        snaps = [s["snapshot"] for s in out["segments"]]
        assert snaps == sorted(snaps) and None not in snaps
        assert list_snapshots(handle)[:3] == snaps
        # across the whole 3-segment supervise run the snapshots read
        # each entry file at most once (the final campaign_report's own
        # coverage scan on its fresh handle accounts for the second 256)
        assert n_supervise <= 2 * 256 + len(frozen.bucket_keys())
        # unchanged store between segments -> triage records say so
        triage_recs = [r for r in recs if r.get("kind") == "triage"]
        assert len(triage_recs) == 3
        assert all(r.get("empty") for r in triage_recs[1:])
        # and the CLI diffs the last pair without error
        from madsim_tpu.service.report import main
        assert main([frozen.dir, "--against", "prev"]) == 0
        assert "EMPTY" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# dashboard + report (structure, not pixels)
# ---------------------------------------------------------------------------

class TestDashboard:
    def test_golden_html_structure(self, frozen, grayfail_plan):
        frozen.write_triage_rows(grayfail_plan)
        _n, s1 = triage_snapshot(frozen)
        key = _plant_bucket(frozen, grayfail_plan.base_knobs())
        _n, s2 = triage_snapshot(frozen)
        d = triage_diff(s1, s2)
        html = render_html(s2, d)
        # structural smoke: root class, sparkline svg, attribution
        # panels, bucket rows with lifecycle + audit badges, repro line
        assert "triage-root" in html and "<svg" in html
        assert "Coverage by recipe" in html
        assert "Buckets by operator" in html
        assert key[:16] in html
        assert 'class="badge new"' in html
        assert "seed=12345" in html
        assert "torn_write" in html
        # every value/label wears text ink: no series-colored text
        assert 'color: var(--series-1)' not in html
        # dark mode is selected, not inverted
        assert "prefers-color-scheme: dark" in html

    def test_sparkline_shapes(self):
        assert "&mdash;" in sparkline_svg([])
        svg = sparkline_svg([[0, 1], [10, 5], [20, 3]], unit="us")
        assert svg.count("<title>") == 3        # per-point hover
        assert 'stroke-width="2"' in svg        # the line spec
        assert 'r="4"' in svg                   # end dot >= 8px diameter

    def test_report_cli_roundtrip(self, frozen, grayfail_plan, capsys):
        from madsim_tpu.service.report import main
        frozen.write_triage_rows(grayfail_plan)
        triage_snapshot(frozen)
        _plant_bucket(frozen, grayfail_plan.base_knobs())
        out_html = os.path.join(frozen.dir, "dash.html")
        rc = main([frozen.dir, "--snapshot", "--against", "prev",
                   "--html", out_html])
        assert rc == 0
        text = capsys.readouterr().out
        assert "1 new" in text
        assert "recipe coverage" in text
        assert os.path.exists(out_html)


# ---------------------------------------------------------------------------
# (6) per-node deterministic hasher seeding
# ---------------------------------------------------------------------------

class _HashProbe(Program):
    """Records each node's first hash-stream draw (and a plain randint
    beside it) into node_state at boot."""

    def __init__(self, use_hash: bool = True):
        self.use_hash = use_hash

    def init(self, ctx):
        st = dict(ctx.state)
        if self.use_hash:
            st["hseed"] = ctx.hash_randint(0, 2**20)
            st["hseed2"] = ctx.hash_randint(0, 2**20, stream=1)
        st["plain"] = ctx.randint(0, 2**20)
        ctx.state = st

    def on_timer(self, ctx, tag, payload):
        pass


def _probe_rt(n=4, use_hash=True, extra_chaos=False):
    sc = Scenario()
    if extra_chaos:
        # schedule reshaping: node 3 boots at ms(1) — AFTER the t=0
        # group, which also grew an extra supervisor op — so its init
        # dispatches at step 4 instead of somewhere in steps 0..3, with
        # a guaranteed-different per-step handler key
        sc.at(0).set_loss(0.1)
        sc.at(ms(1)).boot(3)
    sc.at(ms(5)).halt()
    spec = dict(hseed=jnp.asarray(0, jnp.int32),
                hseed2=jnp.asarray(0, jnp.int32),
                plain=jnp.asarray(0, jnp.int32))
    cfg = SimConfig(n_nodes=n, event_capacity=32, payload_words=2,
                    time_limit=ms(10))
    return Runtime(cfg, [_HashProbe(use_hash)], spec, scenario=sc)


class TestHasherSeeding:
    def test_stream_is_pure_seed_node_function(self):
        rt = _probe_rt()
        st = rt.run_fused(rt.init_batch(np.asarray([3, 9], np.uint32)),
                          200, 64)
        hs = np.asarray(st.node_state["hseed"])      # [B, N]
        hs2 = np.asarray(st.node_state["hseed2"])
        for b, seed in enumerate((3, 9)):
            for node in range(4):
                want = int(prng.randint(
                    prng.node_hash_key(seed, node), 0, 2**20))
                assert int(hs[b, node]) == want, (b, node)
                want2 = int(prng.randint(
                    prng.node_hash_key(seed, node, stream=1), 0, 2**20))
                assert int(hs2[b, node]) == want2
        # decoupled: distinct across nodes and seeds
        assert len({int(x) for x in hs.reshape(-1)}) == hs.size
        assert len({int(x) for x in hs2.reshape(-1)}) == hs2.size

    def test_schedule_independent_where_rand_key_is_not(self):
        """The whole point: a different schedule (chaos reordering
        dispatches) moves ctx.randint draws but NOT the hash stream."""
        seeds = np.asarray([5], np.uint32)
        a = _probe_rt(extra_chaos=False)
        b = _probe_rt(extra_chaos=True)
        sa = a.run_fused(a.init_batch(seeds), 200, 64)
        sb = b.run_fused(b.init_batch(seeds), 200, 64)
        ha = np.asarray(sa.node_state["hseed"])[0]
        hb = np.asarray(sb.node_state["hseed"])[0]
        assert (ha == hb).all(), "hash stream coupled to the schedule"
        # control: the PLAIN per-event draws DO move when the boot
        # steps shift — that coupling is exactly what hash_key removes
        pa = np.asarray(sa.node_state["plain"])[0]
        pb = np.asarray(sb.node_state["plain"])[0]
        assert (pa != pb).any()

    def test_consuming_hash_stream_moves_nothing(self):
        """Bit-identity for everyone else: a model that drains the hash
        stream draws the same plain randint as one that never touches
        it (the stream consumes nothing from the trajectory key)."""
        seeds = np.asarray([11, 12], np.uint32)
        with_h = _probe_rt(use_hash=True)
        without = _probe_rt(use_hash=False)
        sw = with_h.run_fused(with_h.init_batch(seeds), 200, 64)
        so = without.run_fused(without.init_batch(seeds), 200, 64)
        assert (np.asarray(sw.node_state["plain"])
                == np.asarray(so.node_state["plain"])).all()
        # trajectories identical outside the probe's own record
        assert (np.asarray(sw.sched_hash) == np.asarray(so.sched_hash)).all()
        assert int(np.asarray(sw.now)[0]) == int(np.asarray(so.now)[0])

    def test_hash_base_leaf_is_frozen_seed_key(self):
        rt = _probe_rt()
        st = rt.init_batch(np.asarray([7], np.uint32))
        assert (np.asarray(st.hash_base)[0]
                == np.asarray(prng.seed_key(7))).all()
        fin = rt.run_fused(st, 200, 64)
        assert (np.asarray(fin.hash_base)[0]
                == np.asarray(prng.seed_key(7))).all()   # never written
        assert (np.asarray(fin.key)[0]
                != np.asarray(prng.seed_key(7))).any()   # key split away

    def test_ctx_without_base_raises(self):
        from madsim_tpu.core.api import Ctx
        from madsim_tpu.core.types import SimConfig as _SC
        ctx = Ctx(_SC(n_nodes=2, event_capacity=8, payload_words=2,
                      time_limit=100), 0, 0, prng.seed_key(0), {})
        with pytest.raises(ValueError, match="hash base"):
            ctx.hash_key()
