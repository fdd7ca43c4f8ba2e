"""Time-travel replay plane (r20, DESIGN §21).

Load-bearing contracts:
(1) CHECKPOINT FIDELITY — `seed_batch_from(checkpoint_lane(...))`
continues leaf-for-leaf bit-identical (fingerprint, crash verdict,
every leaf including the observation planes) to the uninterrupted
parent lane, on the chunked AND fused runners; harvesting itself
(`run(ckpt_every=...)`) never perturbs trajectories.
(2) UPGRADE SOUNDNESS — a checkpoint re-seeded into a runtime with
MORE observability compiled in (ring/profiler/latency, any combo)
preserves fingerprints and crash verdicts; a DIFFERENT world shape
raises CheckpointMismatch (StoreMismatch-style), never garbage.
(3) TIME TRAVEL — a crash recorded with a wrapped 4-slot ring replays
from a harvested checkpoint to a complete (`truncated=False`) chain,
bit-stable across replays, whose fingerprint stays bucket-compatible
with the live truncated observation (deepest-common-suffix), and the
bucket record upgrades to the complete chain.
(4) MICROSCOPE — `divergence_report` names the same first divergent
dispatch on every re-run of the same pair.
"""

import os

import jax
import numpy as np
import pytest

from madsim_tpu import (CheckpointLog, CheckpointMismatch, LaneCheckpoint,
                        checkpoint_lane, divergence_report, explain_crash,
                        fuzz, replay_bucket, replay_window, seed_batch_from)
from madsim_tpu.obs.causal import (causal_fingerprint, fingerprints_match,
                                   sketch_divergence)
from madsim_tpu.obs.timetravel import (ReplayDivergence, advance_exact,
                                       full_chain_replay)


def _crashrich_rt(trace_cap=128):
    # trace_cap=128 SHARES executables with test_campaign/test_causal's
    # wal_kv runs (the r8 one-compile rule); trace_cap=4 is the
    # wrapped-ring specimen
    from madsim_tpu.models.workloads import crashrich
    return crashrich("wal_kv", trace_cap=trace_cap)


def _saturating_rt(**kw):
    from madsim_tpu.models.workloads import saturating
    return saturating(**kw)


def _lane_tree(state, lane):
    return jax.tree.map(lambda a: np.asarray(a)[lane], state)


def _assert_lanes_equal(a, b):
    """Leaf-for-leaf bitwise equality of two single-lane pytrees, with
    the first offending leaf named."""
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), \
            f"leaf {jax.tree_util.keystr(path)} diverged"


# ---------------------------------------------------------------------------
# (1) checkpoint fidelity
# ---------------------------------------------------------------------------

class TestCheckpointFidelity:
    def test_harvest_never_perturbs_and_child_continues_bitwise(self):
        rt = _crashrich_rt()
        seeds = np.arange(12, dtype=np.uint32)
        parent, _ = rt.run(rt.init_batch(seeds), 30_000, 16)
        pfp = rt.fingerprints(parent)

        log = CheckpointLog()
        harvested, _ = rt.run(rt.init_batch(seeds), 30_000, 16,
                              ckpt_every=32, ckpt_log=log)
        # zero perturbation: harvesting is pure observation
        assert (rt.fingerprints(harvested) == pfp).all()
        assert len(log) > 2          # entry + >=2 mid-flight

        # pick a lane with a real mid-flight checkpoint
        steps = np.asarray(harvested.steps)
        lane = int(np.argmax(steps))
        ck = log.nearest(lane)
        assert 0 < ck.steps < int(steps[lane])

        # continue on BOTH runners: fingerprint, crash verdict, and
        # every leaf (observation planes included) match the parent
        child_f = rt.run_fused(seed_batch_from(ck, 3), 30_000, 16)
        assert (rt.fingerprints(child_f) == pfp[lane]).all()
        child_c, _ = rt.run(seed_batch_from(ck, 2), 30_000, 16)
        assert (rt.fingerprints(child_c) == pfp[lane]).all()
        for child in (child_f, child_c):
            assert (np.asarray(child.crashed)
                    == np.asarray(parent.crashed)[lane]).all()
            assert (np.asarray(child.crash_code)
                    == np.asarray(parent.crash_code)[lane]).all()
        _assert_lanes_equal(_lane_tree(parent, lane),
                            _lane_tree(child_f, 0))
        _assert_lanes_equal(_lane_tree(parent, lane),
                            _lane_tree(child_c, 1))

    def test_fused_harvest_matches_single_dispatch(self):
        rt = _crashrich_rt()
        seeds = np.arange(8, dtype=np.uint32)
        control = rt.run_fused(rt.init_batch(seeds), 30_000, 16)
        log = CheckpointLog()
        seg = rt.run_fused(rt.init_batch(seeds), 30_000, 16,
                           ckpt_every=32, ckpt_log=log)
        assert (rt.fingerprints(seg) == rt.fingerprints(control)).all()
        assert len(log) >= 2
        assert rt.last_ckpt_log is log

    def test_advance_exact_counts_dispatches(self):
        rt = _saturating_rt(trace_cap=16, sketch_slots=4)
        st = advance_exact(rt, rt.init_batch(np.arange(4)), 11, chunk=4)
        assert (np.asarray(st.steps) == 11).all()

    def test_checkpoint_lane_rejects_unbatched(self):
        rt = _saturating_rt(trace_cap=16, sketch_slots=4)
        with pytest.raises(ValueError, match="BATCHED"):
            checkpoint_lane(rt._template, 0)


# ---------------------------------------------------------------------------
# (durable form) save/load — the MIGRATION r20 versioned contract
# ---------------------------------------------------------------------------

class TestSaveLoad:
    def _ckpt(self, rt):
        st = advance_exact(rt, rt.init_batch(np.arange(4)), 8, chunk=4)
        return checkpoint_lane(st, 1,
                               signature=rt.cfg.structural_signature())

    def test_roundtrip_continues_identically(self, tmp_path):
        rt = _saturating_rt(trace_cap=16, sketch_slots=4)
        parent = rt.run_fused(rt.init_batch(np.arange(4)), 64, 4)
        ck = self._ckpt(rt)
        p = str(tmp_path / "lane.npz")
        ck.save(p)
        ck2 = LaneCheckpoint.load(p, rt)
        assert ck2.steps == ck.steps == 8
        assert ck2.signature == rt.cfg.structural_signature()
        child = rt.run_fused(seed_batch_from(ck2, 1, rt=rt), 64, 4)
        assert (rt.fingerprints(child)[0]
                == rt.fingerprints(parent)[1])

    def test_pre_r20_batch_snapshot_rejected_cleanly(self, tmp_path):
        from madsim_tpu.runtime import checkpoint as batch_ckpt
        rt = _saturating_rt(trace_cap=16, sketch_slots=4)
        p = str(tmp_path / "batch.npz")
        batch_ckpt.save(p, rt.init_batch(np.arange(2)))
        with pytest.raises(CheckpointMismatch, match="pre-r20"):
            LaneCheckpoint.load(p, rt)

    def test_world_signature_checked_at_load(self, tmp_path):
        rt = _saturating_rt(trace_cap=16, sketch_slots=4)
        p = str(tmp_path / "lane.npz")
        self._ckpt(rt).save(p)
        other = _crashrich_rt()              # different world entirely
        with pytest.raises(CheckpointMismatch, match="world signature"):
            LaneCheckpoint.load(p, other)

    def test_observability_difference_loads_fine(self, tmp_path):
        # same WORLD, different observability build: load succeeds (the
        # upgrade is seed_batch_from's job, not a rejection)
        rt = _saturating_rt(trace_cap=16, sketch_slots=4)
        p = str(tmp_path / "lane.npz")
        self._ckpt(rt).save(p)
        up = rt.derived(trace_cap=64, profile=True)
        ck = LaneCheckpoint.load(p, up)
        child = up.run_fused(seed_batch_from(ck, 1, rt=up), 64, 4)
        parent = rt.run_fused(rt.init_batch(np.arange(4)), 64, 4)
        assert (up.fingerprints(child)[0] == rt.fingerprints(parent)[1])


# ---------------------------------------------------------------------------
# (2) observability-upgrade matrix + world mismatch
# ---------------------------------------------------------------------------

class TestUpgradeMatrix:
    def test_every_gate_combo_preserves_fingerprint(self):
        """The satellite contract: seed_batch_from into a runtime with
        MORE observability compiled in — every combo of trace_cap,
        profile, latency_hist on/off — preserves fingerprints and the
        crash verdict of the continuation."""
        rt = _saturating_rt()        # all planes off: the lean build
        seeds = np.arange(4)
        parent = rt.run_fused(rt.init_batch(seeds), 64, 4)
        want = int(rt.fingerprints(parent)[2])
        st = advance_exact(rt, rt.init_batch(seeds), 8, chunk=4)
        ck = checkpoint_lane(st, 2,
                             signature=rt.cfg.structural_signature())
        for tc in (0, 16):
            for prof in (False, True):
                for lat in (0, 8):
                    up = rt.derived(trace_cap=tc, profile=prof,
                                    latency_hist=lat)
                    child = up.run_fused(
                        seed_batch_from(ck, 1, rt=up), 64, 4)
                    got = int(up.fingerprints(child)[0])
                    assert got == want, (tc, prof, lat)
                    assert (bool(np.asarray(child.crashed)[0])
                            == bool(np.asarray(parent.crashed)[2]))

    def test_upgraded_ring_records_the_window(self):
        rt = _saturating_rt()
        st = advance_exact(rt, rt.init_batch(np.arange(2)), 8, chunk=4)
        ck = checkpoint_lane(st, 0)
        up = rt.derived(trace_cap=64)
        child = up.run_fused(seed_batch_from(ck, 1, rt=up), 64, 4)
        from madsim_tpu.obs.rings import ring_records
        recs = ring_records(child, 0)
        # the fresh ring starts AT the checkpoint: first record is
        # dispatch 8, nothing dropped, window fully held
        assert int(np.asarray(recs["step"])[0]) == 8
        assert recs["dropped"] == 0

    def test_different_world_raises_not_garbage(self):
        rt = _saturating_rt()
        st = advance_exact(rt, rt.init_batch(np.arange(2)), 8, chunk=4)
        ck = checkpoint_lane(st, 0,
                             signature=rt.cfg.structural_signature())
        other = _crashrich_rt()
        with pytest.raises(CheckpointMismatch):
            seed_batch_from(ck, 1, rt=other)
        # and leaf-level mismatch is caught even WITHOUT a signature
        ck_unsigned = checkpoint_lane(st, 0)
        with pytest.raises(CheckpointMismatch):
            seed_batch_from(ck_unsigned, 1, rt=other)


# ---------------------------------------------------------------------------
# (3) time travel: window replay, complete chains, bucket compatibility
# ---------------------------------------------------------------------------

def _truncated_crash(rt, seeds, log):
    state, _ = rt.run(rt.init_batch(seeds), 30_000, 16,
                      ckpt_every=32, ckpt_log=log)
    steps = np.asarray(state.steps)
    for lane in np.nonzero(np.asarray(state.crashed))[0]:
        exp = explain_crash(state, int(lane))
        if exp["truncated"] and steps[lane] > 40:
            return state, int(lane), exp
    raise AssertionError("workload produced no wrap-truncated crash")


class TestTimeTravelExplain:
    def test_replay_recovers_complete_chain_bucket_compatible(self,
                                                              tmp_path):
        rt = _crashrich_rt(trace_cap=4)      # ring wraps immediately
        log = CheckpointLog()
        state, lane, live = _truncated_crash(
            rt, np.arange(24, dtype=np.uint32), log)
        tpath = str(tmp_path / "window.trace.json")
        full = explain_crash(state, lane, replay=True, rt=rt, ckpts=log,
                             export_trace=tpath)
        again = explain_crash(state, lane, replay=True, rt=rt, ckpts=log)
        assert full["replayed"] and not full["truncated"]
        assert full["chain"] == again["chain"]       # bit-stable
        assert len(full["chain"]) > len(live["chain"])
        # the live truncated chain is a faithful SUFFIX of the full one
        assert full["chain"][-len(live["chain"]):] == live["chain"]
        # completeness honesty: the replayed-complete chain merges into
        # the bucket its truncated sibling opened
        assert fingerprints_match(causal_fingerprint(full),
                                  causal_fingerprint(live))
        assert os.path.getsize(tpath) > 0
        # crash verdict carried through the replay equivalence check
        assert full["crash_code"] == live["crash_code"]

    def test_complete_live_chain_skips_replay(self):
        rt = _crashrich_rt(trace_cap=128)    # big ring: chains complete
        log = CheckpointLog()
        state, _ = rt.run(rt.init_batch(np.arange(8, dtype=np.uint32)),
                          30_000, 16, ckpt_every=32, ckpt_log=log)
        lane = int(np.nonzero(np.asarray(state.crashed))[0][0])
        live = explain_crash(state, lane)
        if live["truncated"]:
            pytest.skip("128-slot ring unexpectedly wrapped")
        out = explain_crash(state, lane, replay=True, rt=rt, ckpts=log)
        assert out["replayed"] is False
        assert out["chain"] == live["chain"]

    def test_no_checkpoints_is_a_clean_error(self):
        rt = _crashrich_rt(trace_cap=4)
        state = rt.run_fused(
            rt.init_batch(np.arange(4, dtype=np.uint32)), 30_000, 512)
        lane = int(np.nonzero(np.asarray(state.crashed))[0][0])
        with pytest.raises(ValueError, match="checkpoint"):
            explain_crash(state, lane, replay=True, rt=rt,
                          ckpts=CheckpointLog())

    def test_bucket_record_upgrades_to_complete_chain(self, tmp_path):
        """Satellite: a replayed-complete observation lands in the
        bucket its truncated sibling opened, and the bucket record is
        UPGRADED to the complete chain (repro handle unchanged)."""
        from madsim_tpu.search.mutate import KnobPlan
        from madsim_tpu.service.buckets import CrashBuckets
        from madsim_tpu.service.store import CorpusStore, store_signature
        rt = _crashrich_rt(trace_cap=4)
        log = CheckpointLog()
        state, lane, live = _truncated_crash(
            rt, np.arange(24, dtype=np.uint32), log)
        store = CorpusStore(str(tmp_path / "c"),
                            signature=store_signature(
                                rt, KnobPlan.from_runtime(rt)))
        buckets = CrashBuckets(store)
        key, opened = buckets.observe_lane(
            state, lane, seed=int(lane), knobs=None, round_no=0,
            worker_id=0)
        assert opened
        rec0 = store.load_bucket(key)
        assert rec0["chain_truncated"] is True
        assert len(rec0["chain"]) == len(live["chain"])

        full = explain_crash(state, lane, replay=True, rt=rt, ckpts=log)
        key2, opened2 = buckets.observe(
            causal_fingerprint(full), seed=int(lane), knobs=None,
            round_no=1, worker_id=0, chain=full["chain"],
            chain_truncated=full["truncated"])
        assert key2 == key and not opened2   # merged, not a second bug
        rec1 = store.load_bucket(key)
        assert rec1["chain_truncated"] is False
        assert len(rec1["chain"]) == len(full["chain"])
        assert rec1["repro"] == rec0["repro"]    # canonical handle kept

    def test_replay_bucket_full_chain_and_triage_links(self, tmp_path):
        """Satellite: replay_bucket(full_chain=True) recovers the
        complete chain + window trace; audit_buckets records chain
        completeness; snapshot/report rows link both."""
        from madsim_tpu import audit_buckets, triage_snapshot
        from madsim_tpu.service import CorpusStore
        from madsim_tpu.service.report import render_text
        d = str(tmp_path / "camp")
        rt = _crashrich_rt(trace_cap=4)
        res = fuzz(rt, max_steps=4096, batch=24, max_rounds=1,
                   dry_rounds=3, chunk=512, corpus_dir=d, worker_id=0)
        assert res["buckets_total"] >= 1
        store = CorpusStore(d, create=False)
        key = store.bucket_keys()[0]
        assert store.load_bucket(key).get("chain_truncated") is True
        crashed, _code, exp = replay_bucket(
            rt, d, key, 4096, chunk=512, full_chain=True,
            window_trace=True)
        assert crashed and exp is not None and not exp["truncated"]
        rec = store.load_bucket(key)
        assert rec["chain_truncated"] is False
        assert len(rec["chain"]) == len(exp["chain"])
        assert os.path.exists(
            store.bucket_path(key, ".window.trace.json"))
        aud = audit_buckets(rt, store, max_steps=4096, budget=2)
        row = next(r for r in aud["audited"] if r["bucket"] == key)
        assert row["chain_complete"] is True
        _n, snap = triage_snapshot(store)
        bk = snap["buckets"][key]
        assert bk["chain_complete"] and bk["window_trace"]
        text = render_text(snap)
        assert "full+tr" in text and ".window.trace.json" in text

    def test_live_lane_replays_to_exact_step(self):
        # a lane the sweep left RUNNING (hit max_steps live) replays to
        # exactly its live dispatch count — not to halt, which would
        # honestly diverge the fingerprint and raise ReplayDivergence
        from madsim_tpu.models.workloads import light
        rt = light(trace_cap=4)     # never halts, tiny ring
        log = CheckpointLog()
        state, _ = rt.run(rt.init_batch(np.arange(2)), 2048, 256,
                          ckpt_every=512, ckpt_log=log)
        assert not bool(np.asarray(state.halted)[0])
        live = explain_crash(state, 0)
        assert live["truncated"]                  # 4-slot ring wrapped
        full = explain_crash(state, 0, replay=True, rt=rt, ckpts=log)
        assert full["replayed"] and not full["truncated"]
        assert full["chain"][-len(live["chain"]):] == live["chain"]

    def test_log_signature_is_per_snapshot(self):
        # a log accumulated across DIFFERENT runtimes keeps each
        # snapshot's own world signature — a later run's _ckpt_setup
        # stamp must not retroactively re-badge earlier harvests
        rt1 = _saturating_rt(trace_cap=16, sketch_slots=4)
        rt2 = _crashrich_rt()
        log = CheckpointLog()
        rt1.run(rt1.init_batch(np.arange(2)), 64, 4,
                ckpt_every=8, ckpt_log=log)
        n1 = len(log)
        rt2.run(rt2.init_batch(np.arange(2, dtype=np.uint32)), 256, 16,
                ckpt_every=32, ckpt_log=log)
        assert len(log) > n1 and log.signature == \
            rt2.cfg.structural_signature()
        oldest = log.checkpoints(0)[-1]           # an rt1-era snapshot
        assert oldest.signature == rt1.cfg.structural_signature()
        with pytest.raises(CheckpointMismatch):
            seed_batch_from(oldest, 1, rt=rt2)

    def test_replay_window_expect_mismatch_raises(self):
        rt = _saturating_rt()
        st = advance_exact(rt, rt.init_batch(np.arange(2)), 8, chunk=4)
        ck = checkpoint_lane(st, 0)
        with pytest.raises(ReplayDivergence, match="fingerprint"):
            replay_window(rt, ck, max_steps=64, chunk=4,
                          expect=dict(fingerprint=-1))

    def test_full_chain_replay_from_handle(self):
        # t=0 is always a checkpoint when the (seed) handle is known
        rt = _crashrich_rt(trace_cap=4)
        state = rt.run_fused(
            rt.init_batch(np.arange(8, dtype=np.uint32)), 30_000, 512)
        lane = int(np.nonzero(np.asarray(state.crashed))[0][0])
        rep = full_chain_replay(
            rt, seed=int(lane),
            expect=dict(fingerprint=int(rt.fingerprints(state)[lane]),
                        crashed=bool(np.asarray(state.crashed)[lane]),
                        crash_code=int(np.asarray(state.crash_code)[lane])),
            trace_cap=int(np.asarray(state.steps)[lane]) + 1)
        assert not rep["explain"]["truncated"]
        assert rep["explain"]["replayed_from_step"] == 0


# ---------------------------------------------------------------------------
# (4) divergence microscope + the sketch bound fix
# ---------------------------------------------------------------------------

class TestDivergenceMicroscope:
    def test_sketch_divergence_names_its_bound(self):
        # sketch_every=4: this workload halts near step 17, so the
        # default 64-dispatch fold period would never fill a slot
        rt = _saturating_rt(trace_cap=16,
                            sketch_slots=4).derived(sketch_every=4)
        st = rt.run_fused(
            rt.init_batch(np.asarray([7, 7, 9], np.uint32)), 64, 4)
        same = sketch_divergence(st, 0, 1)
        assert same["bound"] == "exhausted" and same["slot"] == same["slots"]
        diff = sketch_divergence(st, 0, 2)
        assert diff["bound"] == "sketch-slot"
        assert diff["slot"] < diff["slots"]

    def test_microscope_names_stable_first_dispatch(self):
        rt = _crashrich_rt(trace_cap=4)
        r1 = divergence_report(rt, 3, 5, max_steps=20_000, chunk=512)
        r2 = divergence_report(rt, 3, 5, max_steps=20_000, chunk=512)
        assert r1["diverged"]
        f = r1["first"]
        assert f is not None and f == r2["first"]
        assert f["kind"] in ("dispatch", "halt")
        if f["kind"] == "dispatch":
            # the tie that flipped: both sides' records at one step,
            # with genuinely different dispatch tokens
            assert f["a"]["step"] == f["b"]["step"] == f["step"]
            tok = ("kind", "node", "src", "tag")
            assert tuple(f["a"][k] for k in tok) != \
                tuple(f["b"][k] for k in tok)
        assert r1["suffix_a"] and r1["suffix_b"]

    def test_microscope_identical_lanes_report_no_divergence(self):
        rt = _crashrich_rt(trace_cap=4)
        r = divergence_report(rt, 3, 3, max_steps=20_000, chunk=512)
        assert r["diverged"] is False
        assert r["probe"]["bound"] == "exhausted"

    def test_microscope_two_track_trace(self, tmp_path):
        import json
        rt = _crashrich_rt(trace_cap=4)
        p = str(tmp_path / "pair.trace.json")
        r = divergence_report(rt, 3, 5, max_steps=20_000, chunk=512,
                              export_trace=p)
        assert r["trace_path"] == p
        with open(p) as f:
            doc = json.load(f)
        pids = {e.get("pid") for e in doc["traceEvents"]}
        assert pids == {0, 1}
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        assert names == {"lane_a", "lane_b"}
        # flow binding is global by (cat, id): the two lanes' flow ids
        # must be disjoint or the viewer draws cross-lane arrows
        ids = [{e["id"] for e in doc["traceEvents"]
                if e.get("pid") == p and "id" in e} for p in (0, 1)]
        assert ids[0] and ids[1] and not (ids[0] & ids[1])

    def test_microscope_requires_a_difference(self):
        rt = _crashrich_rt(trace_cap=4)
        with pytest.raises(ValueError, match="diverge"):
            divergence_report(rt, 3)


# ---------------------------------------------------------------------------
# flagship fidelity matrix (slow lane): raft / wal_kv / percolator /
# minipg, run AND run_fused — the acceptance bar's named foursome
# ---------------------------------------------------------------------------

def _raft_rt():
    from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
    from madsim_tpu.models.raft import make_raft_runtime
    cfg = SimConfig(n_nodes=5, event_capacity=128, time_limit=sec(3),
                    net=NetConfig(packet_loss_rate=0.05,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(10)))
    sc = Scenario()
    sc.at(sec(1)).kill_random()
    sc.at(sec(1) + ms(400)).restart_random()
    return make_raft_runtime(5, 8, n_cmds=4, scenario=sc, cfg=cfg)


def _percolator_rt():
    from madsim_tpu import ms
    from madsim_tpu.models.percolator import make_percolator_runtime
    from madsim_tpu.runtime.chaos import slow_disk
    return make_percolator_runtime(
        scenario=slow_disk(ms(100), ms(20), ms(700), node=0))


def _minipg_rt():
    from madsim_tpu.models.minipg import make_minipg_runtime
    return make_minipg_runtime(n_clients=2, n_txns=4)


@pytest.mark.slow
class TestFlagshipFidelity:
    @pytest.mark.parametrize("make,max_steps,chunk,every", [
        (_raft_rt, 20_000, 512, 2048),
        (lambda: _crashrich_rt(trace_cap=0), 30_000, 16, 64),
        (_percolator_rt, 60_000, 256, 1024),
        (_minipg_rt, 60_000, 256, 1024),
    ], ids=["raft", "wal_kv", "percolator", "minipg"])
    def test_checkpoint_continues_bit_identical(self, make, max_steps,
                                                chunk, every):
        rt = make()
        seeds = np.arange(6, dtype=np.uint32)
        parent, _ = rt.run(rt.init_batch(seeds), max_steps, chunk)
        pfp = rt.fingerprints(parent)
        log = CheckpointLog()
        harvested, _ = rt.run(rt.init_batch(seeds), max_steps, chunk,
                              ckpt_every=every, ckpt_log=log)
        assert (rt.fingerprints(harvested) == pfp).all()
        lane = int(np.argmax(np.asarray(harvested.steps)))
        ck = log.nearest(lane)
        assert ck is not None
        child_f = rt.run_fused(seed_batch_from(ck, 2), max_steps, chunk)
        child_c, _ = rt.run(seed_batch_from(ck, 2), max_steps, chunk)
        for child in (child_f, child_c):
            assert (rt.fingerprints(child) == pfp[lane]).all()
            assert (np.asarray(child.crashed)
                    == np.asarray(parent.crashed)[lane]).all()
            assert (np.asarray(child.crash_code)
                    == np.asarray(parent.crash_code)[lane]).all()
        _assert_lanes_equal(_lane_tree(parent, lane),
                            _lane_tree(child_c, 0))


@pytest.mark.slow
class TestRaceFullChain:
    def test_confirmed_race_attaches_complete_chain(self):
        from madsim_tpu.analyze.races import confirm_race, find_races
        from madsim_tpu.models.workloads import racy
        rt = racy(trace_cap=256)
        seeds = np.arange(32, dtype=np.uint32)
        state = rt.run_fused(rt.init_batch(seeds), 20_000, 512)
        lanes = np.nonzero(np.asarray(state.crashed))[0]
        assert len(lanes)
        confirmed = None
        for cand in find_races(state, int(lanes[0]), max_pairs=4):
            conf = confirm_race(rt, int(seeds[lanes[0]]), cand,
                                max_steps=20_000, full_chain=True)
            if conf["status"] == "confirmed":
                confirmed = conf
                break
        if confirmed is None:
            pytest.skip("no candidate confirmed in this window")
        if confirmed["diff"]["commuted"]["crashed"]:
            assert confirmed["chain"], confirmed.keys()
            assert "chain_complete" in confirmed
