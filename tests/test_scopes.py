"""Step-phase scopes (obs/scopes.py, core/step.py) and the fuzz loop's stage
spans (obs/metrics.py Stages, search/fuzz.py).

The scopes are op metadata only: the golden and replay tests hold the
trajectories bit for bit; these hold that the metadata is there and that
the map from a compiled program back to it reads it right.
"""

from __future__ import annotations

import numpy as np
import pytest

from madsim_tpu import NetConfig, Runtime, Scenario, SimConfig, ms, sec
from madsim_tpu.core.types import EV_MSG
from madsim_tpu.obs.metrics import Stages
from madsim_tpu.obs.scopes import STEP_PHASES, Phases, op_scopes, scope_of

CORE = {"step.pick", "step.supervisor", "step.handler", "step.emit",
        "step.check"}

HLO = """\
HloModule jit_run, is_scheduled=true

%fused_computation.7 (param_0: s32[4]) -> s32[4] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %add.3 = s32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(run)/while/body/closed_call/vmap(step.emit)/add" stack_frame_id=4}
}

%body.2 (p: (s32[], s32[4])) -> (s32[], s32[4]) {
  %p = (s32[], s32[4]{0}) parameter(0)
  %gte.1 = s32[4]{0} get-tuple-element(%p), index=1
  %select_reduce_fusion.41 = s32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(run)/while/body/closed_call/vmap(step.pick)/jit(cumsum)/masked_choice/reduce_sum" stack_frame_id=2}
  %fusion.9 = s32[4]{0} fusion(%select_reduce_fusion.41), kind=kLoop, calls=%fused_computation.7
  %copy-done = s32[4]{0} copy-done(%gte.1)
  ROOT %tuple.1 = (s32[], s32[4]{0}) tuple(%gte.1, %fusion.9)
}

ENTRY %main.5 (a: s32[4]) -> s32[4] {
  %a = s32[4]{0} parameter(0), metadata={op_name="state"}
  %while.204 = (s32[], s32[4]{0}) while(%a), condition=%cond.1, body=%body.2, metadata={op_name="jit(run)/while" stack_frame_id=1}
  ROOT %gte.9 = s32[4]{0} get-tuple-element(%while.204), index=1
}
"""


def test_op_scopes_parses_fusions_whiles_and_unscoped_ops():
    m = op_scopes(HLO)
    # a fusion counts under its own metadata, the innermost step.* scope
    assert m["%select_reduce_fusion.41"] == "step.pick"
    assert m["%add.3"] == "step.emit"
    # no metadata of its own: no phase
    assert m["%fusion.9"] == ""
    # the loop itself, async copies and parameters belong to no phase
    assert m["%while.204"] == ""
    assert m["%copy-done"] == ""
    assert m["%a"] == "" and m["%gte.9"] == ""
    assert scope_of("step.picky/vmap(step.check)/add") == "step.check"


def test_phases_open_one_scope_at_a_time():
    import jax
    import jax.numpy as jnp

    def f(x):
        with Phases() as ph:
            ph.to("step.pick")
            y = x * 3
            ph.to("step.emit")
            y = jnp.sin(y)
        return y + 1
    text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    assert set(op_scopes(text).values()) == {"", "step.pick", "step.emit"}
    with pytest.raises(ValueError):
        with Phases() as ph:
            ph.to("step.nope")


def _raft_like_madraft5():
    """madraft5's shapes (5 servers, 96 event rows, 8 payload words, a
    32-entry log) and fault kinds, at a small batch."""
    from madsim_tpu.models.raft import make_raft_runtime
    sc = Scenario()
    sc.at(ms(700)).kill(0)
    sc.at(ms(1400)).clog_node(4)
    sc.at(ms(2100)).restart(0)
    sc.at(ms(2800)).unclog_node(4)
    cfg = SimConfig(n_nodes=5, event_capacity=96, time_limit=sec(4),
                    payload_words=8,
                    net=NetConfig(packet_loss_rate=0.1, send_latency_min=0,
                                  send_latency_max=ms(26)))
    return make_raft_runtime(5, log_capacity=32, n_cmds=24, scenario=sc,
                             cfg=cfg)


def test_fused_op_scopes_names_every_core_phase():
    rt = _raft_like_madraft5()
    m = rt.fused_op_scopes(8, 16)
    assert CORE <= set(m.values())
    assert "" in set(m.values())                 # the while loops
    assert all(k.startswith("%") for k in m)
    assert not set(m.values()) - CORE - {""}     # no plane compiled in
    # the map is of the program run_fused runs: a second call after a run
    # reads the same program
    rt.run_fused(rt.init_batch(np.arange(8)), 32, chunk=16)
    assert rt.fused_op_scopes(8, 16) == m


PLANES = {
    "step.profile": dict(profile=True),
    "step.latency": dict(latency_hist=8),
    "step.span": dict(latency_hist=8, complete_kinds=((EV_MSG, 1),),
                      slo_target=ms(6), span_attr=True),
    "step.sketch": dict(sketch_slots=4),
    "step.series": dict(series_windows=4),
    "step.ring": dict(trace_cap=16),
}


@pytest.mark.parametrize("scope", list(PLANES))
def test_plane_build_maps_its_scope(scope):
    from madsim_tpu.models.pingpong import PingPong, state_spec
    cfg = SimConfig(n_nodes=3, time_limit=sec(2), **PLANES[scope])
    rt = Runtime(cfg, [PingPong(3, target=10)], state_spec())
    got = set(rt.fused_op_scopes(4, 8).values())
    assert scope in got
    assert CORE <= got
    # a plane compiled out leaves no scope behind
    others = set(PLANES) - {scope} - ({"step.latency"}
                                      if scope == "step.span" else set())
    assert not got & others
    assert got <= set(STEP_PHASES) | {""}


def test_stages_time_and_reset():
    st = Stages("madsim.test", ("a", "b"))
    with st("a"):
        pass
    with st("a"):
        pass
    got = st.take()
    assert set(got) == {"a", "b"} and got["a"] > 0 and got["b"] == 0.0
    assert st.take() == {"a": 0.0, "b": 0.0}
    with pytest.raises(ValueError):
        with st("c"):
            pass


@pytest.mark.parametrize("durable", [False, True], ids=["memory", "store"])
def test_fuzz_rounds_carry_host_stage_seconds(durable, tmp_path):
    from madsim_tpu.obs import SweepObserver
    from madsim_tpu.search.fuzz import STAGES, fuzz
    from madsim_tpu.models.workloads import saturating

    class Rec(SweepObserver):
        def __init__(self):
            self.rounds = []

        def on_round(self, rec):
            self.rounds.append(rec)

    obs = Rec()
    fuzz(saturating(target=6), max_steps=600, batch=16,
         max_rounds=3, dry_rounds=5, chunk=128, observer=obs,
         corpus_dir=str(tmp_path / "corpus") if durable else None)
    assert len(obs.rounds) == 3
    prev = 0.0
    for rec in obs.rounds:
        host = rec["host_s"]
        assert set(host) == set(STAGES)
        assert all(v >= 0.0 for v in host.values())
        gap = rec["wall_s"] - prev
        prev = rec["wall_s"]
        assert sum(host.values()) <= gap
        assert host["wait"] > 0 and host["fetch"] > 0
        assert host["admit"] > 0 and host["record"] > 0
        # a durable campaign's store syncs after each round's record, so
        # the next record counts it
        assert (host["sync"] > 0) == (durable and rec["round"] > 1)
    total = {k: sum(r["host_s"][k] for r in obs.rounds) for k in STAGES}
    # every round was dispatched; rounds after the bootstrap schedule
    # parents from the corpus and mutate them
    assert total["dispatch"] > 0 and total["schedule"] > 0
    assert total["mutate"] > 0 and total["dedup"] > 0
