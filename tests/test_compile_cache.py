"""Shared step-program cache: signature soundness, cross-Runtime
executable reuse, trace_cap bucketing, and warm-cache correctness.

The load-bearing property is the compile-domain / replay-domain split
(DESIGN §10): configs differing only in DYNAMIC knobs (time limit, loss,
latency, jitter bound, exact trace_cap within its power-of-two bucket)
must share ONE executable — asserted with the compile counter — and a
warm-cache run must be bitwise-equal to a fresh-compile control (state,
fingerprints, ring columns). Anything less would make the cache a replay
domain, which DESIGN §4 forbids.
"""

import os

import numpy as np
import pytest

from madsim_tpu import Runtime, Scenario, SimConfig, NetConfig, ms, sec
from madsim_tpu.compile.cache import COMPILE_LOG, PROGRAM_CACHE
from madsim_tpu.compile.signature import (freeze, next_pow2,
                                          runtime_signature)
from madsim_tpu.core.state import TRACE_FIELDS
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.obs import ring_records
from madsim_tpu.utils.hostcopy import owned_host_copy

# distinctive structural shape (payload_words=3) so compile-counter
# deltas cannot be polluted by entries other test files already primed
def _pp(time_limit=sec(5), loss=0.0, lat_hi=ms(4), trace_cap=0,
        target=6, share=True):
    cfg = SimConfig(n_nodes=2, event_capacity=16, payload_words=3,
                    time_limit=time_limit, trace_cap=trace_cap,
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=lat_hi))
    return Runtime(cfg, [PingPong(2, target=target)], state_spec(),
                   share_programs=share)


def _chunk_traces():
    return COMPILE_LOG.snapshot()["traces"].get("chunk_runner", 0)


def _assert_states_equal(a, b, what=""):
    """Bitwise leaf-by-leaf comparison of two final states — INCLUDING
    the recorder columns (the warm-cache contract covers observation
    state too)."""
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        assert (np.asarray(x) == np.asarray(y)).all(), f"{what} leaf {i}"


class TestStructuralSignature:
    def test_dynamic_knobs_do_not_key_compiles(self):
        a = SimConfig(n_nodes=3, time_limit=sec(1),
                      net=NetConfig(packet_loss_rate=0.0))
        b = SimConfig(n_nodes=3, time_limit=sec(9),
                      net=NetConfig(packet_loss_rate=0.3,
                                    send_latency_min=ms(2),
                                    send_latency_max=ms(50)))
        assert a.structural_signature() == b.structural_signature()
        # ...but they ARE distinct replay domains: the repro hash differs
        assert a.hash() != b.hash()

    @pytest.mark.parametrize("kw", [
        dict(event_capacity=256), dict(payload_words=4),
        dict(table_dtype="int16"), dict(emission_write="onehot"),
        dict(collect_stats=False), dict(trace_cap=8),
        dict(net=NetConfig(op_jitter_max=3)),   # the static jitter GATE
        dict(latency_hist=16),                  # the r16 latency plane
        dict(latency_hist=16, complete_kinds=((1, 7),)),
        dict(latency_hist=16, root_kinds=((2, 4),)),
    ])
    def test_structural_fields_key_compiles(self, kw):
        base = SimConfig(n_nodes=3)
        assert (SimConfig(n_nodes=3, **kw).structural_signature()
                != base.structural_signature())

    def test_jitter_value_is_dynamic_once_enabled(self):
        a = SimConfig(n_nodes=3, net=NetConfig(op_jitter_max=3))
        b = SimConfig(n_nodes=3, net=NetConfig(op_jitter_max=7))
        assert a.structural_signature() == b.structural_signature()

    def test_slo_target_is_dynamic(self):
        # the SLO target rides SimState (retune/fuzz without recompile)
        a = SimConfig(n_nodes=3, latency_hist=16, slo_target=100)
        b = SimConfig(n_nodes=3, latency_hist=16, slo_target=9000)
        assert a.structural_signature() == b.structural_signature()
        assert a.hash() != b.hash()     # hash() covers every field

    def test_trace_cap_buckets(self):
        assert next_pow2(0) == 0 and next_pow2(1) == 1
        assert next_pow2(17) == 32 and next_pow2(32) == 32
        sigs = {SimConfig(n_nodes=2, trace_cap=c).structural_signature()
                for c in range(17, 33)}
        assert len(sigs) == 1          # one executable for the whole sweep
        assert (SimConfig(n_nodes=2, trace_cap=33).structural_signature()
                not in sigs)
        for c in range(17, 33):
            assert SimConfig(n_nodes=2, trace_cap=c).trace_cap_bucket == 32


class TestRuntimeSignature:
    def test_same_construction_shares(self):
        assert _pp(sec(5), 0.0)._sig == _pp(sec(8), 0.2)._sig

    def test_program_params_key_compiles(self):
        # target is baked into the handler trace
        assert _pp(target=6)._sig != _pp(target=7)._sig

    def test_factory_closures_freeze_by_value(self):
        # the flagship factories build invariant/halt_when CLOSURES per
        # call; freezing by (code, defaults, cells) makes two identical
        # constructions equal — this is what makes sharing reach the
        # real models, not just bare Programs
        from madsim_tpu.models.raft import make_raft_runtime
        a = make_raft_runtime(5, 8, n_cmds=4)
        b = make_raft_runtime(5, 8, n_cmds=4)
        c = make_raft_runtime(5, 16, n_cmds=4)
        assert a._sig == b._sig
        assert a._sig != c._sig

    def test_kwonly_defaults_key_the_freeze(self):
        # keyword-only defaults bake into the trace exactly like
        # positional ones — two closures differing only there must NOT
        # freeze equal (a false hit would run the wrong invariant)
        def mk(k):
            def inv(s, *, thresh=k):
                return thresh
            return inv
        assert freeze(mk(1)) != freeze(mk(2))
        assert freeze(mk(3)) == freeze(mk(3))

    def test_module_globals_key_the_freeze(self):
        # CPython compares code objects by VALUE: byte-identical source
        # in two modules yields EQUAL code objects even when the module
        # globals they read differ — the freeze must fold those bindings
        # in (a false hit would run the wrong invariant silently)
        import types as _t
        src = "THRESH = %d\ndef inv(s):\n    return s > THRESH\n"
        m1, m2, m3 = (_t.ModuleType(f"_sigmod{i}") for i in range(3))
        exec(src % 5, m1.__dict__)
        exec(src % 9, m2.__dict__)
        exec(src % 5, m3.__dict__)
        assert m1.inv.__code__ == m2.inv.__code__   # the trap
        assert freeze(m1.inv) != freeze(m2.inv)     # the fix
        assert freeze(m1.inv) == freeze(m3.inv)     # same binding shares

    def test_recursive_function_freezes_stably(self):
        # a recursive function's own global binding is a reference
        # cycle; it must encode as a stable marker, not an identity
        # token (which would silently disable sharing for the module)
        import types as _t
        src = ("def fact(n):\n"
               "    return 1 if n <= 1 else n * fact(n - 1)\n")
        m1, m2 = _t.ModuleType("_sigr1"), _t.ModuleType("_sigr2")
        exec(src, m1.__dict__)
        exec(src, m2.__dict__)
        assert freeze(m1.fact) == freeze(m1.fact)
        assert freeze(m1.fact) == freeze(m2.fact)

    def test_unknown_objects_never_false_hit(self):
        class Opaque:
            __slots__ = ()              # no attribute dict to freeze
        x, y = Opaque(), Opaque()
        # soundness: opaque values NEVER compare equal across objects
        # (losing sharing is acceptable; a false cache hit is not)
        assert freeze(x) != freeze(y)

    def test_unknown_with_attrs_is_stable_per_object(self):
        # an object whose attributes cannot freeze gets an identity
        # token stashed on it — the SAME object keeps one signature
        class Weird:
            def __init__(self):
                self.gen = (i for i in range(3))   # unfreezable attr
        w = Weird()
        assert freeze(w) == freeze(w)
        assert freeze(w) != freeze(Weird())


class TestSharedExecutables:
    def test_chunk_runner_shared_one_trace_bitwise_equal(self):
        seeds = np.arange(48)
        rt1 = _pp(sec(5), 0.0)
        rt2 = _pp(sec(7), 0.1)          # dynamic knobs only
        assert rt1._run_chunk[False] is rt2._run_chunk[False]
        before = _chunk_traces()
        s1, _ = rt1.run(rt1.init_batch(seeds), 192, 64)
        s2, _ = rt2.run(rt2.init_batch(seeds), 192, 64)
        assert _chunk_traces() - before <= 1   # one retrace for the pair
        # warm-cache run == fresh-compile control, bitwise
        ctrl = _pp(sec(7), 0.1, share=False)
        sc, _ = ctrl.run(ctrl.init_batch(seeds), 192, 64)
        assert (ctrl.fingerprints(sc) == rt2.fingerprints(s2)).all()
        _assert_states_equal(sc, s2, "chunk")

    def test_fused_runner_shared_bitwise_equal(self):
        seeds = np.arange(48)
        rt1 = _pp(sec(5), 0.05)
        rt2 = _pp(sec(6), 0.15)
        assert rt1._fused_runner is rt2._fused_runner
        f1 = rt1.run_fused(rt1.init_batch(seeds), 192, 64)
        f2 = rt2.run_fused(rt2.init_batch(seeds), 192, 64)
        ctrl = _pp(sec(6), 0.15, share=False)
        fc = ctrl.run_fused(ctrl.init_batch(seeds), 192, 64)
        assert (ctrl.fingerprints(fc) == rt2.fingerprints(f2)).all()
        _assert_states_equal(fc, f2, "fused")
        del f1

    def test_dynamic_knob_sweep_costs_one_trace(self):
        # the explore()/harness shape: N configs, one structure — the
        # whole sweep must pay one chunk-runner retrace (same B/chunk)
        seeds = np.arange(16)
        rts = [_pp(sec(2 + i), 0.02 * i) for i in range(4)]
        rts[0].run(rts[0].init_batch(seeds), 64, 32)   # prime
        before = _chunk_traces()
        for rt in rts[1:]:
            rt.run(rt.init_batch(seeds), 64, 32)
        assert _chunk_traces() == before   # all warm

    def test_inject_shared(self):
        rt1, rt2 = _pp(sec(5)), _pp(sec(9))
        assert rt1._inject is rt2._inject

    def test_share_programs_false_is_private(self):
        rt1 = _pp(sec(5), share=False)
        rt2 = _pp(sec(5), share=False)
        assert rt1._run_chunk[False] is not rt2._run_chunk[False]


class TestTraceCapBucketing:
    def _traced(self, cap, share=True):
        return _pp(sec(50), 0.0, trace_cap=cap, target=1 << 30,
                   share=share)

    def test_caps_in_one_bucket_share_executable(self):
        rt24, rt32 = self._traced(24), self._traced(32)
        assert rt24._sig == rt32._sig
        assert rt24._run_chunk[False] is rt32._run_chunk[False]

    def test_ring_bit_identical_vs_unbucketed(self):
        # cap=32 IS its own bucket — the compiled program is exactly what
        # an unbucketed build would produce — so the bucketed cap=24
        # ring must equal the chronological tail-24 of the cap=32 ring,
        # and all non-trace state must match bitwise
        seeds = np.arange(8)
        rt24, rt32 = self._traced(24), self._traced(32)
        s24, _ = rt24.run(rt24.init_batch(seeds), 256, 64)
        s32, _ = rt32.run(rt32.init_batch(seeds), 256, 64)
        for lane in (0, 3):
            r24 = ring_records(s24, lane=lane)
            r32 = ring_records(s32, lane=lane)
            assert r24["total"] == r32["total"] > 32
            assert r24["dropped"] == r24["total"] - 24
            for k in ("now", "step", "kind", "node", "src", "tag"):
                assert (r24[k] == r32[k][-24:]).all(), (lane, k)
        for f in type(s24).__dataclass_fields__:
            if f in TRACE_FIELDS or f in ("node_state", "ext"):
                continue
            assert (np.asarray(getattr(s24, f))
                    == np.asarray(getattr(s32, f))).all(), f
        assert (rt24.fingerprints(s24) == rt32.fingerprints(s32)).all()

    def test_bucketed_ring_matches_fresh_compile_control(self):
        seeds = np.arange(8)
        rt = self._traced(24)
        ctrl = self._traced(24, share=False)
        s, _ = rt.run(rt.init_batch(seeds), 256, 64)
        sc, _ = ctrl.run(ctrl.init_batch(seeds), 256, 64)
        _assert_states_equal(sc, s, "ring")


class TestWarmCacheCompacting:
    """The hostcopy satellite: stashed lanes must be OWNED copies, so a
    warm-cache double run (second run reuses executables whose buffer
    lifetimes differ from the fresh-compile path) returns identical,
    uncorrupted results."""

    def _halting(self, share=True):
        # loss staggers per-lane completion so compaction actually fires
        # (measured: ~70% of lanes halt around chunk 4 of 16-step chunks)
        return _pp(sec(30), 0.3, target=16, share=share)

    def test_forced_warm_cache_double_run(self):
        from madsim_tpu.obs import SweepObserver

        class CompactCount(SweepObserver):
            n = 0

            def on_compact(self, rec):
                CompactCount.n += 1

        seeds = np.arange(64)
        rt = self._halting()
        ref, _ = rt.run(rt.init_batch(seeds), 4096, 16)
        fp_ref = rt.fingerprints(ref)
        kw = dict(chunk=16, compact_when=0.25, min_batch=8)
        c1 = rt.run_compacting(rt.init_batch(seeds), 4096,
                               observer=CompactCount(), **kw)
        # second run: every executable now comes from the warm cache
        c2 = rt.run_compacting(rt.init_batch(seeds), 4096,
                               observer=CompactCount(), **kw)
        assert CompactCount.n >= 2, "compaction never fired — vacuous test"
        assert (rt.fingerprints(c1) == fp_ref).all()
        assert (rt.fingerprints(c2) == fp_ref).all()
        _assert_states_equal(c1, c2, "double-run")

    def test_owned_host_copy_owns(self):
        import jax.numpy as jnp
        src = {"a": jnp.arange(8), "b": np.arange(4.0)}
        out = owned_host_copy(src)
        assert out["a"].flags.owndata and out["b"].flags.owndata
        out["a"][0] = 99    # owned: writable, no aliasing with the source
        assert int(np.asarray(src["a"])[0]) == 0


class TestPersistentCacheWiring:
    """One resolution everywhere (compile/persistent.py): the env var
    when set, else the checkout's fixed .jax_cache — never None, never a
    directory that moves between processes."""

    @pytest.fixture
    def restore_cache_dir(self):
        import jax
        prior = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prior)

    def test_env_var_wins(self, tmp_path, monkeypatch, restore_cache_dir):
        import jax
        from madsim_tpu.compile.persistent import enable_persistent_cache
        d = str(tmp_path / "cc")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        assert enable_persistent_cache() == d
        assert jax.config.jax_compilation_cache_dir == d

    def test_default_is_the_checkout(self, monkeypatch, restore_cache_dir):
        import tempfile

        import jax
        from madsim_tpu.compile.persistent import enable_persistent_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        d = enable_persistent_cache()
        assert d == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
        assert not d.startswith(tempfile.gettempdir())

    def test_campaign_worker_resolves_the_same(self, tmp_path):
        # a worker spawned the way run_campaign spawns it, on a fresh
        # temp corpus dir, lands on the checkout cache (its factory
        # reports the cache dir the worker configured, then exits)
        from madsim_tpu.service.campaign import spawn_worker
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        (tmp_path / "probe_factory.py").write_text(
            "import sys\n"
            "def make():\n"
            "    import jax\n"
            "    print(jax.config.jax_compilation_cache_dir)\n"
            "    sys.exit(0)\n")
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join([str(tmp_path), root]))
        p = spawn_worker(str(tmp_path / "corpus"), 0, "probe_factory:make",
                         env=env, max_steps=1)
        out, _ = p.communicate(timeout=120)
        assert p.returncode == 0
        assert out.strip() == os.path.join(root, ".jax_cache")


@pytest.mark.slow
class TestCacheMatrixFlagships:
    """The full warm-vs-fresh matrix (ISSUE satellite): raft / wal_kv /
    shard_kv at 64 seeds through all three runners plus
    run_fused_sharded — warm-cache executables bitwise-equal to
    fresh-compile controls. Chaos- and compile-heavy; ci.sh full runs it.
    """

    def _pair(self, build):
        """(prime+warm runtime, fresh-compile control) for one factory."""
        prime = build()     # populates the cache
        warm = build()      # same signature: every runner is a cache hit
        assert prime._sig == warm._sig
        assert prime._run_chunk[False] is warm._run_chunk[False]
        ctrl = build()
        ctrl._sig = None            # private jits: the fresh-compile arm
        for attr in ("_run_chunk", "_fused_runner", "_inject"):
            ctrl.__dict__.pop(attr, None)
        return prime, warm, ctrl

    def _check(self, build, max_steps, chunk, expect_crash=False):
        seeds = np.arange(64, dtype=np.uint32)
        prime, warm, ctrl = self._pair(build)
        # prime the shared executables once
        prime.run(prime.init_batch(seeds), max_steps, chunk)
        for runner in ("run", "run_fused", "run_compacting", "sharded"):
            if runner == "run":
                w, _ = warm.run(warm.init_batch(seeds), max_steps, chunk)
                c, _ = ctrl.run(ctrl.init_batch(seeds), max_steps, chunk)
            elif runner == "run_fused":
                w = warm.run_fused(warm.init_batch(seeds), max_steps,
                                   chunk)
                c = ctrl.run_fused(ctrl.init_batch(seeds), max_steps,
                                   chunk)
            elif runner == "run_compacting":
                w = warm.run_compacting(warm.init_batch(seeds), max_steps,
                                        chunk=chunk, min_batch=8)
                c = ctrl.run_compacting(ctrl.init_batch(seeds), max_steps,
                                        chunk=chunk, min_batch=8)
            else:
                from madsim_tpu.parallel.distributed import \
                    run_fused_sharded
                w = run_fused_sharded(warm, seeds, max_steps, chunk)
                c = run_fused_sharded(ctrl, seeds, max_steps, chunk)
            assert (warm.fingerprints(w) == ctrl.fingerprints(c)).all(), \
                runner
            _assert_states_equal(c, w, runner)
        if expect_crash:
            assert np.asarray(w.crashed).any()

    def test_raft(self):
        from madsim_tpu.models.raft import make_raft_runtime

        def build():
            cfg = SimConfig(n_nodes=5, event_capacity=128,
                            time_limit=sec(3),
                            net=NetConfig(packet_loss_rate=0.05,
                                          send_latency_min=ms(1),
                                          send_latency_max=ms(10)))
            sc = Scenario()
            sc.at(sec(1)).kill_random()
            sc.at(sec(1) + ms(400)).restart_random()
            return make_raft_runtime(5, 8, n_cmds=4, scenario=sc, cfg=cfg)

        self._check(build, 1500, 256)

    def test_wal_kv_mid_sweep_crash(self):
        from madsim_tpu.models.wal_kv import make_wal_kv_runtime

        def build():
            sc = Scenario()
            for t in range(6):
                sc.at(ms(150) + ms(250) * t).kill(0)
                sc.at(ms(210) + ms(250) * t).restart(0)
            return make_wal_kv_runtime(n_clients=2, n_ops=12, wal_cap=64,
                                       sync_wal=False, scenario=sc)

        self._check(build, 4096, 512, expect_crash=True)

    def test_shard_kv(self):
        from madsim_tpu.models.shard_kv import make_shard_runtime

        def build():
            cfg = SimConfig(n_nodes=11, event_capacity=160,
                            payload_words=12, time_limit=sec(60),
                            net=NetConfig(send_latency_min=ms(1),
                                          send_latency_max=ms(10)))
            return make_shard_runtime(n_groups=2, rg=3, rc=3, n_clients=2,
                                      n_ops=8, max_cfg=8, log_capacity=48,
                                      cfg=cfg)

        self._check(build, 3000, 512)
