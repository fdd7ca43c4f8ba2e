"""The emission_write lowering knob (types.py) must be value-invisible:
"onehot" and "scatter" are two XLA lowerings of the SAME table write, so
trajectories, fingerprints, and schedule hashes must be BIT-IDENTICAL
across them — this knob must never become a replay domain. The cheap
form differentially pins the fast form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import NetConfig, Runtime, Scenario, SimConfig, ms, sec
from madsim_tpu.core import types as T
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.models.raft import make_raft_runtime
from madsim_tpu.ops import select as sel


class TestFirstKFreeLowerings:
    @pytest.mark.parametrize("k", [1, 3, 8])
    def test_scatter_matches_rank_match(self, k):
        rng = np.random.default_rng(7)
        for _ in range(32):
            free = jnp.asarray(rng.random(24) < rng.random())
            s_a, ok_a = sel.first_k_free(free, k, scatter=False)
            s_b, ok_b = sel.first_k_free(free, k, scatter=True)
            assert (np.asarray(ok_a) == np.asarray(ok_b)).all()
            # not-ok rows are gated by callers; compare only the real ones
            m = np.asarray(ok_a)
            assert (np.asarray(s_a)[m] == np.asarray(s_b)[m]).all()

    def test_all_free_and_none_free(self):
        for free in (jnp.ones(16, bool), jnp.zeros(16, bool)):
            s_a, ok_a = sel.first_k_free(free, 4, scatter=False)
            s_b, ok_b = sel.first_k_free(free, 4, scatter=True)
            assert (np.asarray(ok_a) == np.asarray(ok_b)).all()
            m = np.asarray(ok_a)
            assert (np.asarray(s_a)[m] == np.asarray(s_b)[m]).all()


def _rt(emission_write, event_capacity=96, **cfg_kw):
    sc = Scenario()
    sc.at(ms(300)).kill_random()
    sc.at(ms(700)).restart_random()
    sc.at(ms(900)).partition([0, 1])
    sc.at(ms(1300)).heal()
    cfg = SimConfig(n_nodes=5, event_capacity=event_capacity,
                    time_limit=sec(30),
                    net=NetConfig(packet_loss_rate=0.05),
                    emission_write=emission_write, **cfg_kw)
    return make_raft_runtime(5, log_capacity=16, n_cmds=6, scenario=sc,
                             cfg=cfg)


class TestEndToEndBitIdentical:
    def test_chaos_raft_state_identical_across_lowerings(self):
        seeds = np.arange(8)
        final = {}
        for mode in ("onehot", "scatter"):
            rt = _rt(mode)
            st, _ = rt.run(rt.init_batch(seeds), 768)
            final[mode] = jax.tree.map(np.asarray, st)
        a, b = final["onehot"], final["scatter"]
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert la.dtype == lb.dtype
            assert (la == lb).all()
        # the knob must not leak into replay identity: schedule hashes
        # agree too
        assert (np.asarray(a.sched_hash) == np.asarray(b.sched_hash)).all()


def _planes_rt(emission_write):
    """Pingpong with the flight recorder, latency and span planes compiled
    in: the plane writes that read the one-hot form's `written` mask
    (ev_prov, ev_root_t, ev_span). A pause parks deadlines, so spans carry
    nonzero queue-wait."""
    sc = Scenario()
    sc.at(ms(30)).pause(1)
    sc.at(ms(90)).resume(1)
    cfg = SimConfig(n_nodes=3, time_limit=sec(5), trace_cap=64,
                    latency_hist=24, complete_kinds=((T.EV_MSG, 1),),
                    slo_target=ms(6), span_attr=True,
                    net=NetConfig(send_latency_min=ms(1),
                                  send_latency_max=ms(4)),
                    emission_write=emission_write)
    return Runtime(cfg, [PingPong(3, target=40)], state_spec(),
                   scenario=sc)


def _int16(st):
    # narrow columns stay narrow under either lowering
    assert st.t_kind.dtype == np.int16 and st.t_node.dtype == np.int16


def _overflowed(st):
    # the table is too small for the churn: emissions were dropped
    assert (st.oops & T.OOPS_EVENT_OVERFLOW).any()


def _planes_written(st):
    # every plane column the emission write feeds was written
    assert (st.ev_prov != 0).any()
    assert (st.ev_root_t != 0).any()
    assert (st.ev_span != 0).any()


CASES = {
    "int16": (lambda m: _rt(m, table_dtype="int16"), 768, _int16),
    "overflow": (lambda m: _rt(m, event_capacity=16), 768, _overflowed),
    "planes": (_planes_rt, 512, _planes_written),
}


@pytest.mark.parametrize("case", list(CASES))
def test_lowerings_bit_identical(case):
    """Trajectories, every leaf with its dtype, and schedule hashes agree
    across the two lowerings where the write narrows a column, drops
    emissions on overflow, and feeds the plane columns."""
    build, steps, exercised = CASES[case]
    final = {}
    for mode in ("onehot", "scatter"):
        rt = build(mode)
        st, _ = rt.run(rt.init_batch(np.arange(8)), steps)
        final[mode] = jax.tree.map(np.asarray, st)
    a, b = final["onehot"], final["scatter"]
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert la.dtype == lb.dtype
        assert (la == lb).all()
    assert (a.sched_hash == b.sched_hash).all()
    exercised(a)
