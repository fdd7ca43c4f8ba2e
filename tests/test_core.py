"""Core engine tests: selection primitives, state init, basic stepping.

Mirrors the reference's inline unit-test strategy (SURVEY.md §4): every test
builds a fresh runtime and drives virtual time; a whole "cluster" runs in
one process with no real sleeping.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import Program, Runtime, Scenario, SimConfig, NetConfig, ms
from madsim_tpu.core import types as T
from madsim_tpu.core import prng
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.ops import select as sel


class TestSelectOps:
    def test_masked_choice_uniform(self):
        mask = jnp.asarray([False, True, False, True, True])
        hits = set()
        for s in range(40):
            idx, valid = sel.masked_choice(prng.seed_key(s), mask)
            assert bool(valid)
            assert int(idx) in (1, 3, 4)
            hits.add(int(idx))
        assert hits == {1, 3, 4}  # all eligible slots reachable

    def test_masked_choice_empty(self):
        idx, valid = sel.masked_choice(prng.seed_key(0), jnp.zeros(4, bool))
        assert not bool(valid)

    def test_min_deadline(self):
        d = jnp.asarray([5, 3, 3, 9], jnp.int32)
        elig = jnp.asarray([True, True, True, False])
        dmin, at_min, any_e = sel.min_deadline(d, elig, T.T_INF)
        assert int(dmin) == 3
        assert list(np.asarray(at_min)) == [False, True, True, False]
        assert bool(any_e)

    def test_min_deadline_none(self):
        d = jnp.full(4, T.T_INF, jnp.int32)
        _, _, any_e = sel.min_deadline(d, jnp.zeros(4, bool), T.T_INF)
        assert not bool(any_e)

    def test_first_k_free(self):
        free = jnp.asarray([False, True, False, True, True])
        slots, ok = sel.first_k_free(free, 4)
        assert list(np.asarray(slots)) == [1, 3, 4, 0]
        assert list(np.asarray(ok)) == [True, True, True, False]

    def test_take1_matches_gather(self):
        vec = jnp.asarray([10, 20, 30, 40], jnp.int32)
        # scalar, vector, and matrix index shapes; int and bool vecs
        assert int(sel.take1(vec, jnp.asarray(2))) == 30
        idx = jnp.asarray([[0, 3], [1, 1]], jnp.int32)
        assert np.asarray(sel.take1(vec, idx)).tolist() == [[10, 40],
                                                            [20, 20]]
        bvec = jnp.asarray([True, False, True, False])
        assert np.asarray(sel.take1(bvec, idx)).tolist() == [[True, False],
                                                             [False, False]]

    def test_take_row_put_row(self):
        mat = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
        assert np.asarray(sel.take_row(mat, jnp.asarray(1))).tolist() == \
            [4, 5, 6, 7]
        bmat = mat > 5
        assert np.asarray(sel.take_row(bmat, jnp.asarray(2))).tolist() == \
            [True, True, True, True]
        # put_row: row write, broadcasting scalar val, mask=False no-op
        out = sel.put_row(mat, jnp.asarray(2), jnp.asarray(-1, jnp.int32))
        assert np.asarray(out).tolist() == [[0, 1, 2, 3], [4, 5, 6, 7],
                                            [-1, -1, -1, -1]]
        row = jnp.asarray([9, 9, 9, 9], jnp.int32)
        noop = sel.put_row(mat, jnp.asarray(0), row, mask=jnp.asarray(False))
        assert (np.asarray(noop) == np.asarray(mat)).all()
        # 1-D mats (the Raft log columns) and masked scalar write
        vec = jnp.asarray([1, 2, 3], jnp.int32)
        out = sel.put_row(vec, jnp.asarray(1), jnp.asarray(7, jnp.int32),
                          mask=jnp.asarray(True))
        assert np.asarray(out).tolist() == [1, 7, 3]
        # under vmap (per-lane scalar index — the engine's actual use)
        idxs = jnp.asarray([0, 2], jnp.int32)
        rows = jax.vmap(lambda i: sel.take_row(mat, i))(idxs)
        assert np.asarray(rows).tolist() == [[0, 1, 2, 3], [8, 9, 10, 11]]



PREFIX_WIDTHS = [1, 5, 16, 32, 33, 96, 256, 384]


def _lane_masks(c: int, kind: str, lanes: int = 64):
    """bool[lanes, c]: random rows of varied density, or all set, or none."""
    if kind == "all":
        return np.ones((lanes, c), bool)
    if kind == "none":
        return np.zeros((lanes, c), bool)
    rng = np.random.default_rng(c)
    return rng.random((lanes, c)) < rng.random((lanes, 1))


class TestPrefixCount:
    """`prefix_count` is a matmul above 32 slots and a cumsum at or below;
    both must equal the cumsum bit for bit, and so must every pick and
    free-slot rank built on it."""

    @pytest.mark.parametrize("kind", ["random", "all", "none"])
    @pytest.mark.parametrize("c", PREFIX_WIDTHS)
    def test_equals_cumsum_under_vmap(self, c, kind):
        mask = jnp.asarray(_lane_masks(c, kind))
        got = jax.jit(jax.vmap(sel.prefix_count))(mask)
        want = np.cumsum(np.asarray(mask), axis=-1, dtype=np.int32)
        assert got.dtype == jnp.int32
        assert (np.asarray(got) == want).all()

    @pytest.mark.parametrize("c", PREFIX_WIDTHS)
    def test_masked_choice_matches_cumsum_pick(self, c):
        def cumsum_pick(key, mask):
            m = mask.astype(jnp.int32)
            cnt = m.sum()
            r = jax.random.randint(key, (), 0, jnp.maximum(cnt, 1),
                                   dtype=jnp.int32)
            return jnp.argmax(jnp.cumsum(m) == r + 1).astype(jnp.int32), \
                cnt > 0

        masks = jnp.asarray(np.concatenate(
            [_lane_masks(c, k, 32) for k in ("random", "all", "none")]))
        keys = jax.vmap(prng.seed_key)(jnp.arange(masks.shape[0]))
        idx, ok = jax.jit(jax.vmap(sel.masked_choice))(keys, masks)
        ref_idx, ref_ok = jax.jit(jax.vmap(cumsum_pick))(keys, masks)
        assert (np.asarray(ok) == np.asarray(ref_ok)).all()
        assert (np.asarray(idx) == np.asarray(ref_idx)).all()

    @pytest.mark.parametrize("scatter", [False, True])
    @pytest.mark.parametrize("k", [1, 4, 8])
    @pytest.mark.parametrize("c", [5, 32, 33, 96, 384])
    def test_first_k_free_matches_reference(self, c, k, scatter):
        free = np.concatenate(
            [_lane_masks(c, kind, 32) for kind in ("random", "all", "none")])
        slots, ok = jax.jit(jax.vmap(
            lambda f: sel.first_k_free(f, k, scatter=scatter)))(
                jnp.asarray(free))
        for lane, row in enumerate(free):
            idx = np.flatnonzero(row)[:k]
            want = np.zeros(k, np.int32)
            want[:idx.size] = idx
            assert np.asarray(slots)[lane].tolist() == want.tolist()
            assert np.asarray(ok)[lane].tolist() == \
                (np.arange(k) < idx.size).tolist()

def _pingpong_rt(n_nodes=3, target=5, **cfg_kw):
    cfg = SimConfig(n_nodes=n_nodes, time_limit=T.sec(30), **cfg_kw)
    return Runtime(cfg, [PingPong(n_nodes, target=target)], state_spec())


class TestPingPong:
    def test_single_seed_completes(self):
        rt = _pingpong_rt()
        state, _ = rt.run(rt.init_single(42), max_steps=4000)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())
        st = state.node_state
        assert int(np.asarray(st["acked"])[0, 0]) >= 5
        # pongs came from peers
        assert int(np.asarray(st["pings_got"])[0, 1:].sum()) >= 5

    def test_batch_completes(self):
        rt = _pingpong_rt()
        state, _ = rt.run(rt.init_batch(np.arange(32)), max_steps=4000)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())
        acked = np.asarray(state.node_state["acked"])[:, 0]
        assert (acked >= 5).all()

    def test_virtual_time_advances(self):
        rt = _pingpong_rt()
        state, _ = rt.run(rt.init_single(7), max_steps=4000)
        # 5 round trips at >= 2ms each must take >= 10ms of virtual time
        assert int(np.asarray(state.now)[0]) >= ms(10)

    def test_packet_loss_still_completes(self):
        # retry timers must mask 30% loss (config.rs packet_loss_rate knob)
        rt = _pingpong_rt(net=NetConfig(packet_loss_rate=0.3))
        state, _ = rt.run(rt.init_batch(np.arange(16)), max_steps=20_000)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())
        assert int(np.asarray(state.msg_dropped).sum()) > 0

    def test_determinism_same_seed(self):
        rt = _pingpong_rt()
        assert rt.check_determinism(seed=123, max_steps=4000)

    def test_schedule_diversity_across_seeds(self):
        # the task.rs:572-596 property: distinct seeds -> distinct schedules
        rt = _pingpong_rt()
        state, _ = rt.run(rt.init_batch(np.arange(10)), max_steps=4000)
        fps = rt.fingerprints(state)
        assert len(set(fps.tolist())) >= 8

    def test_batch_consistent_with_single(self):
        # seed i in a batch == seed i alone (replay-by-seed survives vmap)
        rt = _pingpong_rt()
        sb, _ = rt.run(rt.init_batch(np.asarray([5, 6, 7])), max_steps=4000)
        s6, _ = rt.run(rt.init_single(6), max_steps=4000)
        assert rt.fingerprints(sb)[1] == rt.fingerprints(s6)[0]


class TestLifecycleFaults:
    def test_deadlock_detected(self):
        class Idle(Program):
            pass

        cfg = SimConfig(n_nodes=1, time_limit=T.sec(1))
        sc = Scenario()  # auto-halt at 1s; but Idle schedules nothing, so
        # after boot there is nothing runnable until the halt op -> halts fine
        rt = Runtime(cfg, [Idle()], dict(x=jnp.asarray(0, jnp.int32)),
                     scenario=sc)
        state, _ = rt.run(rt.init_single(0), max_steps=100)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())  # HALT op keeps it live

    def test_kill_breaks_pingpong_and_restart_recovers(self):
        n, target = 3, 50
        cfg = SimConfig(n_nodes=n, time_limit=T.sec(60))
        sc = Scenario()
        # kill both responders early, restart them later; pinger's retry
        # timer must carry it through (Handle::kill/restart semantics)
        sc.at(ms(5)).kill(1)
        sc.at(ms(5)).kill(2)
        sc.at(T.sec(2)).restart(1)
        sc.at(T.sec(2)).restart(2)
        rt = Runtime(cfg, [PingPong(n, target=target)], state_spec(),
                     scenario=sc)
        state, _ = rt.run(rt.init_batch(np.arange(8)), max_steps=40_000)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())
        # progress stalled during the dead window => finish time > 2s
        assert (np.asarray(state.now) > T.sec(2)).all()

    def test_partition_stalls_heal_recovers(self):
        n = 3
        cfg = SimConfig(n_nodes=n, time_limit=T.sec(60))
        sc = Scenario()
        sc.at(ms(2)).partition([0])      # isolate the pinger
        sc.at(T.sec(3)).heal()
        rt = Runtime(cfg, [PingPong(n, target=20)], state_spec(),
                     scenario=sc)
        state, _ = rt.run(rt.init_batch(np.arange(8)), max_steps=40_000)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())
        assert (np.asarray(state.now) > T.sec(3)).all()
        assert int(np.asarray(state.msg_dropped).sum()) > 0

    def test_pause_parks_events_resume_replays(self):
        n = 3
        cfg = SimConfig(n_nodes=n, time_limit=T.sec(60))
        sc = Scenario()
        sc.at(ms(2)).pause(0)
        sc.at(T.sec(5)).resume(0)
        rt = Runtime(cfg, [PingPong(n, target=10)], state_spec(),
                     scenario=sc)
        state, _ = rt.run(rt.init_single(3), max_steps=40_000)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())
        assert int(np.asarray(state.now)[0]) >= T.sec(5)  # parked until resume


class TestHarnessOops:
    def test_event_overflow_flagged(self):
        class Bomb(Program):
            def init(self, ctx):
                ctx.set_timer(1, 1)

            def on_timer(self, ctx, tag, payload):
                for _ in range(4):
                    ctx.set_timer(1, 1)  # exponential timer growth

        cfg = SimConfig(n_nodes=1, event_capacity=16, time_limit=T.sec(1))
        rt = Runtime(cfg, [Bomb()], dict(x=jnp.asarray(0, jnp.int32)))
        state, _ = rt.run(rt.init_single(0), max_steps=200)
        assert int(np.asarray(state.oops)[0]) & T.OOPS_EVENT_OVERFLOW


class TestRandomTargets:
    def test_kill_random_varies_victim_across_seeds(self):
        # regression: NODE_RANDOM must survive to the supervisor (a clip once
        # collapsed it to node 0, degenerating all random faults)
        from madsim_tpu import Scenario
        from madsim_tpu.core.types import sec as _sec
        n = 4
        sc = Scenario()
        sc.at(ms(5)).kill_random()
        cfg = SimConfig(n_nodes=n, time_limit=_sec(1))
        rt = Runtime(cfg, [PingPong(n, target=3)], state_spec(), scenario=sc)
        state, _ = rt.run(rt.init_batch(np.arange(64)), max_steps=4000)
        dead = np.asarray(~state.alive)
        assert (dead.sum(axis=1) == 1).all()        # exactly one victim
        victims = dead.argmax(axis=1)
        assert len(set(victims.tolist())) >= 3      # victims vary by seed

    def test_pool_beyond_31_nodes(self):
        # pools pack 31 nodes/word across ALL payload words (VERDICT r2
        # next #6): a 36-node cluster with the candidate pool entirely in
        # word 1 must kill only pool members, varying by seed
        from madsim_tpu import Scenario
        from madsim_tpu.core.types import sec as _sec
        n = 36
        sc = Scenario()
        sc.at(ms(5)).kill_random(among=range(32, 36))
        cfg = SimConfig(n_nodes=n, time_limit=_sec(1))
        rt = Runtime(cfg, [PingPong(n, target=2)], state_spec(), scenario=sc)
        state, _ = rt.run(rt.init_batch(np.arange(48)), max_steps=3000)
        dead = np.asarray(~state.alive)
        assert (dead.sum(axis=1) == 1).all()        # exactly one victim
        victims = dead.argmax(axis=1)
        assert set(victims.tolist()) <= set(range(32, 36))  # pool respected
        assert len(set(victims.tolist())) >= 2      # still random within it


class CancelDemo(Program):
    """Arms a long SLOW timer, then (optionally) cancels it shortly
    after — the Sleep::reset / abort analog, red/green testable."""

    SLOW, DO_CANCEL = 1, 2

    def __init__(self, do_cancel: bool):
        self.do_cancel = do_cancel

    def init(self, ctx):
        ctx.set_timer(ms(500), self.SLOW, when=ctx.node == 0)
        ctx.set_timer(ms(10), self.DO_CANCEL, when=ctx.node == 0)

    def on_timer(self, ctx, tag, payload):
        st = dict(ctx.state)
        st["fired"] = st["fired"] + (tag == self.SLOW)
        ctx.cancel_timer(self.SLOW,
                         when=(tag == self.DO_CANCEL) & self.do_cancel)
        ctx.state = st


class TestCancelTimer:
    def _run(self, do_cancel):
        cfg = SimConfig(n_nodes=1, time_limit=T.sec(1))
        rt = Runtime(cfg, [CancelDemo(do_cancel)],
                     dict(fired=jnp.asarray(0, jnp.int32)))
        state, _ = rt.run(rt.init_batch(np.arange(16)), max_steps=500)
        assert bool(state.halted.all()) and not bool(state.crashed.any())
        assert rt.check_determinism(seed=4, max_steps=500)
        return np.asarray(state.node_state["fired"])

    def test_cancelled_timer_never_fires(self):
        assert (self._run(do_cancel=True) == 0).all()

    def test_uncancelled_timer_fires(self):
        # the control: without the cancel the same program fires
        assert (self._run(do_cancel=False) == 1).all()


class TestNarrowTableColumns:
    def test_int16_columns_bit_identical_to_int32(self):
        # table_dtype is a pure bandwidth lever: t_kind/t_node/t_src in
        # int16 must yield BIT-IDENTICAL trajectories (values unchanged,
        # fingerprints cover every leaf's values)
        from madsim_tpu import Scenario
        from madsim_tpu.core.types import sec as _sec
        from madsim_tpu.utils.hashing import fingerprint

        def run(dtype):
            n = 4
            sc = Scenario()
            sc.at(ms(5)).kill_random()
            sc.at(ms(300)).restart_random()
            cfg = SimConfig(n_nodes=n, time_limit=_sec(2),
                            net=NetConfig(packet_loss_rate=0.1),
                            table_dtype=dtype)
            rt = Runtime(cfg, [PingPong(n, target=4, retry=ms(20))],
                         state_spec(), scenario=sc)
            state, _ = rt.run(rt.init_batch(np.arange(64)), max_steps=4000)
            assert bool(state.halted.all())
            return np.asarray(jax.vmap(fingerprint)(state))

        np.testing.assert_array_equal(run("int32"), run("int16"))


class TestContinuationIdiom:
    """A handler is atomic here (a deliberate transform of madsim's
    poll-level interleaving, DESIGN.md §3); `ctx.defer` splits a
    multi-phase handler into same-deadline continuations so its phases
    interleave with other nodes' events again. The schedule-coverage
    metric must MEASURE that widening across a seed batch."""

    START, DONE, PH = 1, 2, 1

    def _spec(self):
        z = jnp.asarray(0, jnp.int32)
        return dict(phase=z, acc=z, done=z)

    def _summarize(self, prog, n=4, seeds=64):
        from madsim_tpu.core.types import sec as _sec
        from madsim_tpu.parallel.stats import summarize
        # constant latency: deliveries land at identical deadlines, so the
        # same-deadline random tie-break is the ONLY schedule freedom and
        # the metric isolates exactly what defer() adds
        cfg = SimConfig(n_nodes=n, time_limit=_sec(5),
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(1)))
        rt = Runtime(cfg, [prog], self._spec())
        state, _ = rt.run(rt.init_batch(np.arange(seeds)), max_steps=4000)
        assert bool(state.halted.all()) and not bool(state.crashed.any())
        return summarize(rt, state), state

    def test_defer_widens_schedule_coverage(self):
        n = 4
        outer = self

        class Base(Program):
            def init(self, ctx):
                for d in range(1, n):
                    ctx.send(d, outer.START, when=ctx.node == 0)

        class Atomic(Base):
            # three work phases inside ONE handler: invisible to the
            # scheduler, so the only explored orderings are arrival orders
            def on_message(self, ctx, src, tag, payload):
                st = dict(ctx.state)
                is_start = tag == outer.START
                st["acc"] = st["acc"] + 3 * is_start
                ctx.send(0, outer.DONE, when=is_start)
                if_done = tag == outer.DONE
                done = st["done"] + if_done
                st["done"] = jnp.where(if_done, done, st["done"])
                ctx.halt_if(if_done & (st["done"] >= n - 1))
                ctx.state = st

            def on_timer(self, ctx, tag, payload):
                pass

        class Split(Base):
            # same work, each phase deferred: continuations land in the
            # event table and the random tie-break interleaves them with
            # the other workers' phases
            def on_message(self, ctx, src, tag, payload):
                st = dict(ctx.state)
                is_start = tag == outer.START
                st["phase"] = jnp.where(is_start, 1, st["phase"])
                ctx.defer(outer.PH, when=is_start)
                if_done = tag == outer.DONE
                done = st["done"] + if_done
                st["done"] = jnp.where(if_done, done, st["done"])
                ctx.halt_if(if_done & (st["done"] >= n - 1))
                ctx.state = st

            def on_timer(self, ctx, tag, payload):
                st = dict(ctx.state)
                fire = tag == outer.PH
                st["acc"] = st["acc"] + fire
                more = fire & (st["phase"] < 3)
                st["phase"] = st["phase"] + fire
                ctx.defer(outer.PH, when=more)
                ctx.send(0, outer.DONE, when=fire & ~more)
                ctx.state = st

        atomic, ast = self._summarize(Atomic())
        split, sst = self._summarize(Split())
        # identical work done...
        assert (np.asarray(ast.node_state["acc"])[:, 1:]
                == np.asarray(sst.node_state["acc"])[:, 1:]).all()
        # ...but the split version explores strictly more interleavings
        assert split["distinct_schedules"] > atomic["distinct_schedules"], \
            (split["distinct_schedules"], atomic["distinct_schedules"])


class TestPayloadStructs:
    def test_layout_pack_unpack_roundtrip(self):
        import jax.numpy as jnp
        from madsim_tpu.utils.structs import Layout
        L = Layout("term", "prev", "commit")
        assert (L.term, L.prev, L.commit, L.width) == (0, 1, 2, 3)
        words = L.pack(term=7, commit=9)
        assert [int(w) for w in words] == [7, 0, 9]
        payload = jnp.asarray([7, 0, 9, 0], jnp.int32)
        got = L.unpack(payload)
        assert int(got["term"]) == 7 and int(got["commit"]) == 9

    def test_float_bitcast_lossless(self):
        import numpy as np
        from madsim_tpu.utils.structs import f32_to_word, word_to_f32
        vals = np.asarray([0.0, 1.5, -3.25e-7, 1e30], np.float32)
        back = np.asarray(word_to_f32(f32_to_word(vals)))
        np.testing.assert_array_equal(back, vals)


class TestChaosRecipes:
    def test_recipes_compose_and_run(self):
        import numpy as np
        from madsim_tpu import SimConfig, NetConfig, ms, sec
        from madsim_tpu.harness.simtest import run_seeds
        from madsim_tpu.models.raft import make_raft_runtime
        from madsim_tpu.runtime import chaos

        sc = chaos.madraft_churn(servers=range(5), rounds=3)
        sc = chaos.flaky_network(at=ms(500), loss=0.15, until=sec(2), sc=sc)
        cfg = SimConfig(n_nodes=5, event_capacity=256, time_limit=sec(6),
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(10)))
        rt = make_raft_runtime(5, 16, n_cmds=6, scenario=sc, cfg=cfg)
        state = run_seeds(rt, np.arange(6), max_steps=30_000)
        assert bool(state.halted.all())
        # the loss window actually dropped packets somewhere in the batch
        assert int(np.asarray(state.msg_dropped).sum()) > 0
