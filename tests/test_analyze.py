"""DetSan (r12): determinism linter, happens-before schedule-race
detector with forced-commute confirmation, and the detsan double-run
sanitizer.

Load-bearing properties (DESIGN §14):
(1) every lint rule FIRES on a planted hazard and HONORS its
`# detsan: ok(<rule>)` suppression — a toothless linter passes any
repo, so the positive controls are the real test;
(2) the rules apply only to TRACED scopes — host driver code may use
clocks and RNG freely (flagging it would bury real findings);
(3) the repo's own models/examples pass the gate (satellite 1);
(4) a seeded schedule race in the wal_kv mutant is detected from the
rings, confirmed by forcing the commuted tie-break order via the PCT
nudge, carries a (seed, knobs, nudge) repro that REPLAYS to the
confirming lane's exact fingerprint, and dedupes into ONE bucket;
(5) detsan: identity vs permuted lane placement is leaf-for-leaf
bit-identical for clean runtimes (raft/wal_kv fast, shard_kv slow),
and the differ pins a planted divergence to its leaf + lane + seed;
(6) identity-token signature degradation is no longer silent: it emits
a COMPILE_LOG record naming qualname + cell (satellite 2).
"""

import os
import threading

import numpy as np
import pytest

from madsim_tpu import DetSanFailure, Program, detsan_check, run_seeds
from madsim_tpu.analyze.lint import (DeterminismLintError, active,
                                     lint_callable, lint_paths,
                                     lint_program, lint_runtime,
                                     lint_source)
from madsim_tpu.analyze.races import (confirm_race, find_races,
                                      replay_race, scan_races)
from madsim_tpu.harness.simtest import detsan_perm, diff_states
from madsim_tpu.obs.causal import fingerprints_match, race_fingerprint

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = (
    "import time, random, os, uuid, secrets\n"
    "import numpy as np\n"
    "from madsim_tpu.core.api import Program\n")


def _rules(src, suppressed_too=False):
    fs = lint_source(_PRELUDE + src, "t.py")
    return {f.rule for f in (fs if suppressed_too else active(fs))}


# ---------------------------------------------------------------------------
# lint rules: positive + suppressed, one per rule
# ---------------------------------------------------------------------------


class TestLintRules:
    def test_host_time_positive(self):
        src = ("class P(Program):\n"
               "    def on_timer(self, ctx, tag, payload):\n"
               "        t = time.time()\n")
        assert _rules(src) == {"host-time"}

    def test_host_time_suppressed(self):
        src = ("class P(Program):\n"
               "    def on_timer(self, ctx, tag, payload):\n"
               "        t = time.time()  # detsan: ok(host-time)\n")
        assert _rules(src) == set()
        assert _rules(src, suppressed_too=True) == {"host-time"}

    def test_host_random_positive(self):
        src = ("class P(Program):\n"
               "    def on_message(self, ctx, src_, tag, payload):\n"
               "        a = random.random()\n"
               "        b = np.random.rand()\n"
               "        c = os.urandom(4)\n"
               "        d = uuid.uuid4()\n"
               "        e = secrets.token_bytes(4)\n")
        fs = active(lint_source(_PRELUDE + src, "t.py"))
        assert {f.rule for f in fs} == {"host-random"}
        assert len(fs) == 5

    def test_host_random_suppressed_line_above(self):
        src = ("class P(Program):\n"
               "    def on_message(self, ctx, src_, tag, payload):\n"
               "        # detsan: ok(host-random)\n"
               "        a = random.random()\n")
        assert _rules(src) == set()

    def test_jax_random_not_flagged(self):
        src = ("import jax\n"
               "class P(Program):\n"
               "    def init(self, ctx):\n"
               "        k = jax.random.split(ctx.rand_key())\n")
        assert _rules(src) == set()

    def test_unordered_iter_positive(self):
        src = ("class P(Program):\n"
               "    def init(self, ctx):\n"
               "        for x in {1, 2, 3}:\n"
               "            pass\n"
               "        ys = [k for k in vars(self)]\n"
               "        for k in set(ys).keys() if False else set(ys):\n"
               "            pass\n")
        assert _rules(src) == {"unordered-iter"}

    def test_unordered_iter_suppressed(self):
        src = ("class P(Program):\n"
               "    def init(self, ctx):\n"
               "        for x in {1, 2}:  # detsan: ok(unordered-iter)\n"
               "            pass\n")
        assert _rules(src) == set()

    def test_dict_iteration_not_flagged(self):
        # py3.7+ dicts iterate in insertion order — deterministic
        src = ("class P(Program):\n"
               "    def init(self, ctx):\n"
               "        st = dict(ctx.state)\n"
               "        for k in st:\n"
               "            pass\n")
        assert _rules(src) == set()

    def test_host_callback_positive(self):
        src = ("import jax\n"
               "class P(Program):\n"
               "    def on_timer(self, ctx, tag, payload):\n"
               "        jax.pure_callback(int, None)\n")
        assert _rules(src) == {"host-callback"}

    def test_host_callback_suppressed(self):
        src = ("import jax\n"
               "class P(Program):\n"
               "    def on_timer(self, ctx, tag, payload):\n"
               "        jax.pure_callback(int, None)"
               "  # detsan: ok(host-callback)\n")
        assert _rules(src) == set()

    def test_star_suppression(self):
        src = ("class P(Program):\n"
               "    def init(self, ctx):\n"
               "        t = time.time()  # detsan: ok(*)\n")
        assert _rules(src) == set()

    def test_parse_error_is_a_finding(self):
        fs = lint_source("def broken(:\n", "t.py")
        assert [f.rule for f in fs] == ["parse-error"]


class TestLintScoping:
    def test_host_driver_code_not_flagged(self):
        src = ("def host_driver():\n"
               "    time.sleep(1)\n"
               "    return random.random()\n")
        assert _rules(src) == set()

    def test_invariant_kwarg_scopes(self):
        src = ("def make(n):\n"
               "    return Runtime(None, [], {},\n"
               "                   invariant=my_inv_factory(n),\n"
               "                   halt_when=lambda s: time.monotonic())\n"
               "def my_inv_factory(n):\n"
               "    def invariant(state):\n"
               "        return random.random() < 0.5, 0\n"
               "    return invariant\n")
        assert _rules(src) == {"host-random", "host-time"}

    def test_invariant_factory_reached_without_call_site(self):
        # raft.py defines raft_invariant; raft_kv constructs with
        # R.raft_invariant(...) from ANOTHER file — the factory's own
        # module must still lint the closure
        src = ("def chain_invariant(n):\n"
               "    def invariant(state):\n"
               "        return time.time() > 0, 0\n"
               "    return invariant\n")
        assert _rules(src) == {"host-time"}

    def test_cross_module_model_inheritance(self):
        src = ("from madsim_tpu.models import raft as R\n"
               "class CfgRaft(R.Raft):\n"
               "    def on_timer(self, ctx, tag, payload):\n"
               "        t = time.time()\n")
        assert _rules(src) == {"host-time"}

    def test_repo_gate_clean(self):
        # satellite 1: the repo-wide `python -m madsim_tpu.analyze` gate
        bad = active(lint_paths([os.path.join(_REPO, "madsim_tpu"),
                                 os.path.join(_REPO, "examples")]))
        assert not bad, "\n".join(f.format() for f in bad)


# ---------------------------------------------------------------------------
# runtime-side rules: closures, Program attributes, the lint= flag
# ---------------------------------------------------------------------------


def _make_bad_time_program():
    import time as _time

    class BadClock(Program):
        def on_timer(self, ctx, tag, payload):
            t = _time.time()
            return t

    return BadClock()


class TestRuntimeLint:
    def test_mutable_capture_closure(self):
        log = []

        def inv(state):
            log.append(1)
            return False, 0

        fs = lint_callable(inv, name="inv")
        assert "mutable-capture" in {f.rule for f in active(fs)}

    def test_mutable_capture_program_attribute(self):
        class P(Program):
            def __init__(self):
                self.table = [1, 2, 3]

        fs = lint_program(P())
        assert "mutable-capture" in {f.rule for f in active(fs)}

    def test_sig_degrade_closure(self):
        lock = threading.Lock()      # freeze() -> identity token

        def inv(state):
            return bool(lock), 0

        fs = lint_callable(inv, name="inv")
        assert "sig-degrade" in {f.rule for f in active(fs)}

    def test_clean_flagships(self):
        from madsim_tpu.models.raft import make_raft_runtime
        from madsim_tpu.models.wal_kv import make_wal_kv_runtime
        for rt in (make_raft_runtime(3, 8), make_wal_kv_runtime()):
            assert active(lint_runtime(rt)) == []

    def test_lint_flag_raises_and_warn_passes(self, capsys):
        from madsim_tpu.models.pingpong import state_spec
        from madsim_tpu import Runtime, SimConfig, sec
        cfg = SimConfig(n_nodes=2, event_capacity=64, time_limit=sec(1))
        prog = _make_bad_time_program()
        with pytest.raises(DeterminismLintError) as ei:
            Runtime(cfg, [prog], state_spec(), lint=True)
        assert "host-time" in str(ei.value)
        Runtime(cfg, [prog], state_spec(), lint="warn")   # must construct
        assert "detsan warn" in capsys.readouterr().out

    def test_degrade_warning_emitted(self):
        # satellite 2: identity-token degradation is a named COMPILE_LOG
        # record (qualname + cell), fanned out to on_compile observers
        from madsim_tpu import Runtime, SimConfig, SweepObserver, sec
        from madsim_tpu.compile.cache import COMPILE_LOG
        from madsim_tpu.models.pingpong import PingPong, state_spec

        lock = threading.Lock()

        def degraded_invariant(state):
            _ = lock
            return state.now < 0, 0

        class Catch(SweepObserver):
            def __init__(self):
                self.recs = []

            def on_compile(self, rec):
                if rec.get("label") == "signature_degrade":
                    self.recs.append(rec)

        obs = Catch()
        COMPILE_LOG.attach(obs)
        try:
            cfg = SimConfig(n_nodes=2, event_capacity=64,
                            time_limit=sec(1))
            Runtime(cfg, [PingPong(2)], state_spec(),
                    invariant=degraded_invariant)
        finally:
            COMPILE_LOG.detach(obs)
        assert obs.recs, "no signature_degrade record emitted"
        rec = obs.recs[0]
        assert rec["cell"] == "lock"
        assert "degraded_invariant" in rec["owner"]
        assert "signature degrade" in COMPILE_LOG.summary()


# ---------------------------------------------------------------------------
# schedule races: detect from rings, confirm by forced commute, bucket
# ---------------------------------------------------------------------------


def _racy_rt(trace_cap=256):
    """The race-rich wal_kv mutant (`models/workloads.racy`)."""
    from madsim_tpu.models.workloads import racy
    return racy(trace_cap=trace_cap)


class TestRaces:
    def test_race_fingerprint_symmetric_dedup(self):
        a = dict(step=5, now=100, kind=1, node=0, src=1, tag=7,
                 parent=2, lamport=3)
        b = dict(step=6, now=100, kind=1, node=0, src=2, tag=7,
                 parent=2, lamport=3)
        cand_ab = dict(lane=0, node=0, now=100, a=a, b=b)
        cand_ba = dict(lane=3, node=0, now=900, a=b, b=a)
        fp1, fp2 = race_fingerprint(cand_ab), race_fingerprint(cand_ba)
        assert fp1["key"] == fp2["key"]          # order-normalized
        assert fp1["kind"] == "race"
        assert fingerprints_match(fp1, fp2)
        other = race_fingerprint(dict(cand_ab, node=1))
        assert other["key"] != fp1["key"]
        assert not fingerprints_match(fp1, other)

    def test_seeded_race_confirms_and_replays(self, tmp_path):
        from madsim_tpu.search.mutate import KnobPlan
        from madsim_tpu.service.buckets import CrashBuckets
        from madsim_tpu.service.store import CorpusStore, store_signature
        rt = _racy_rt()
        plan = KnobPlan.from_runtime(rt)
        store = CorpusStore(str(tmp_path / "c"),
                            signature=store_signature(rt, plan))
        buckets = CrashBuckets(store)
        seeds = np.arange(32, dtype=np.uint32)
        res = scan_races(rt, seeds, 20_000, buckets=buckets,
                         max_confirm=2)
        assert res["candidates"] >= 1
        assert res["confirmed"], res
        conf = res["confirmed"][0]
        assert conf["status"] == "confirmed" and conf["nudge"] != 0
        # the (seed, knobs, nudge) repro replays ALONE to the confirming
        # lane's exact fingerprint (lane independence, DESIGN §4)
        rep = replay_race(rt, conf["repro"])
        assert rep["fingerprint"] == conf["diff"]["fingerprint"][1]
        # bucketed as a first-class finding with the nudge in the handle
        rec = store.load_bucket(res["bucket_keys"][0])
        assert rec["fingerprint"]["kind"] == "race"
        assert rec["repro"]["nudge"] == conf["nudge"]
        # dedup: rescanning the same seeds opens no new buckets
        n0 = len(store.bucket_keys())
        scan_races(rt, seeds, 20_000, buckets=buckets, max_confirm=2)
        assert len(store.bucket_keys()) == n0

    def test_candidates_are_unordered_same_instant_pairs(self):
        rt = _racy_rt()
        seeds = np.arange(16, dtype=np.uint32)
        state = rt.run_fused(rt.init_batch(seeds), 20_000, 512)
        lanes = np.nonzero(np.asarray(state.crashed))[0]
        assert len(lanes), "race-rich mutant produced no crash"
        cands = find_races(state, int(lanes[0]))
        for c in cands:
            assert c["a"]["now"] == c["b"]["now"] == c["now"]
            assert c["a"]["node"] == c["b"]["node"] == c["node"]
            # b must not descend from a (the detector's HB contract)
            assert c["b"]["parent"] != c["a"]["step"]

    def test_confirm_baseline_uses_mutant_nudge(self):
        # a fuzz mutant may carry its own tie-break policy: the baseline
        # lane must replay THAT policy (not 0), and the sweep must not
        # waste a lane on a baseline clone
        from madsim_tpu.search.mutate import KnobPlan
        rt = _racy_rt()
        plan = KnobPlan.from_runtime(rt)
        knobs = plan.base_knobs()
        knobs["prio_nudge"] = np.int32(5)
        state = rt.run_fused(rt.init_batch(np.arange(8, dtype=np.uint32)),
                             20_000, 512)
        lanes = np.nonzero(np.asarray(state.crashed))[0]
        cand = find_races(state, int(lanes[0]))[0]
        conf = confirm_race(rt, 1, cand, knobs=knobs, plan=plan,
                            nudges=np.asarray([5, 6]), max_steps=20_000)
        assert conf["swept"] == [6]          # 5 == baseline, dropped
        assert conf["baseline"] is not None

    def test_confirm_requires_commuted_order(self):
        # a candidate whose tokens never co-occur in any nudged lane is
        # inconclusive, not confirmed — no false positives from
        # fingerprint drift alone
        rt = _racy_rt()
        seeds = np.arange(8, dtype=np.uint32)
        state = rt.run_fused(rt.init_batch(seeds), 20_000, 512)
        lanes = np.nonzero(np.asarray(state.crashed))[0]
        cands = find_races(state, int(lanes[0]))
        fake = dict(cands[0])
        fake["a"] = dict(cands[0]["a"], kind=99, tag=12345)   # no such event
        conf = confirm_race(rt, int(seeds[lanes[0]]), fake,
                            nudges=np.arange(1, 5), max_steps=20_000)
        assert conf["status"] == "inconclusive"


# ---------------------------------------------------------------------------
# detsan: permuted-lane double run
# ---------------------------------------------------------------------------


class TestDetSan:
    def test_perm_is_a_real_permutation(self):
        for B in (1, 2, 3, 16, 512):
            p = detsan_perm(B)
            assert sorted(p.tolist()) == list(range(B))
            if B > 1:
                assert (p != np.arange(B)).any()

    def test_raft_equivalence(self):
        from madsim_tpu.models.raft import make_raft_runtime
        rep = detsan_check(make_raft_runtime(3, 8), np.arange(24), 2048,
                           chunk=256)
        assert rep["ok"] and rep["diffs"] == []

    def test_wal_kv_equivalence(self):
        from madsim_tpu.models.wal_kv import make_wal_kv_runtime
        rep = detsan_check(make_wal_kv_runtime(), np.arange(24), 2048,
                           chunk=256)
        assert rep["ok"] and rep["diffs"] == []

    @pytest.mark.slow
    def test_shard_kv_equivalence(self):
        from madsim_tpu.models.shard_kv import make_shard_runtime
        rep = detsan_check(make_shard_runtime(), np.arange(16), 8192,
                           chunk=512)
        assert rep["ok"] and rep["diffs"] == []

    def test_planted_diff_is_pinned_to_leaf_lane_seed(self):
        from madsim_tpu.models.workloads import light
        rt = light(n_nodes=2)
        seeds = np.arange(8)
        a = rt.run_fused(rt.init_batch(seeds), 256, 64)
        bad = a.replace(now=a.now.at[3].add(1))
        diffs = diff_states(a, bad, align=np.arange(8))
        assert len(diffs) == 1
        assert "now" in diffs[0]["leaf"] and diffs[0]["lanes"] == [3]
        # end to end: a baseline that disagrees with the permuted replay
        # raises with the seed of the differing lane
        with pytest.raises(DetSanFailure) as ei:
            detsan_check(rt, seeds, 256, 64, baseline_state=bad)
        assert ei.value.seed == 3
        assert "MADSIM_TEST_DETSAN" in str(ei.value)

    def test_run_seeds_detsan_flag_and_env(self):
        from madsim_tpu.models.workloads import light
        rt = light(n_nodes=2)
        state = run_seeds(rt, np.arange(8), 256, chunk=64, detsan=True)
        assert np.asarray(state.now).shape == (8,)   # ran + sanitized
        os.environ["MADSIM_TEST_DETSAN"] = "1"
        try:
            run_seeds(rt, np.arange(8), 256, chunk=64)
        finally:
            del os.environ["MADSIM_TEST_DETSAN"]
