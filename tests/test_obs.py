"""Flight recorder + observability layer: ring semantics, exporters,
sweep observers.

The load-bearing properties, per the observability discipline (DESIGN.md):
(1) the ring is an OBSERVER — all non-trace state must be bit-identical
whether the ring is compiled out, compiled in, or sampling; (2) the ring
survives `lax.while_loop`, so `run_fused` sweeps yield traces bitwise
equal to the chunked runner's; (3) exporters honor the overshoot
contract — frozen-lane `fired=False` records never reach a trace.
"""

import io
import json

import jax
import numpy as np
import pytest

from madsim_tpu import (JsonlObserver, NetConfig, ProgressObserver, Runtime,
                        Scenario, SimConfig, explore, ms, sec, summarize)
from madsim_tpu.core import types as T
from madsim_tpu.core.state import TRACE_FIELDS as _TRACE_FIELDS
from madsim_tpu.obs import (export_chrome_trace, ring_records, sampled_lanes,
                            to_chrome_events)
from madsim_tpu.obs.metrics import TeeObserver
from madsim_tpu.models.pingpong import PingPong, state_spec


def _pingpong_rt(trace_cap=0, target=3, n_nodes=2, scenario=None, loss=0.0):
    cfg = SimConfig(n_nodes=n_nodes, time_limit=sec(5), trace_cap=trace_cap,
                    net=NetConfig(packet_loss_rate=loss,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(4)))
    return Runtime(cfg, [PingPong(n_nodes, target=target)], state_spec(),
                   scenario=scenario)


def _nontrace_state(state) -> dict:
    out = {}
    for name in type(state).__dataclass_fields__:
        if name in _TRACE_FIELDS or name in ("node_state", "ext"):
            continue
        out[name] = np.asarray(getattr(state, name))
    for i, leaf in enumerate(jax.tree.leaves(state.node_state)):
        out[f"node_state_{i}"] = np.asarray(leaf)
    return out


class TestRing:
    def test_wraparound_at_capacity(self):
        # far more events than ring rows: the ring must hold exactly the
        # LAST cap events in chronological order and report the drop
        rt = _pingpong_rt(trace_cap=4, target=40)
        state, events = rt.run(rt.init_batch(np.arange(2)), 512, 64,
                               collect_events=True)
        recs = ring_records(state, lane=1)
        steps = int(np.asarray(state.steps)[1])
        assert recs["total"] == steps > 4          # every event counted
        assert recs["dropped"] == steps - 4
        assert len(recs["now"]) == 4
        # chronological and exactly the tail of the collect_events stream
        fired = np.asarray(events["fired"])[:, 1]
        idx = np.nonzero(fired)[0][-4:]
        for col in ("now", "kind", "node", "src", "tag"):
            assert (recs[col] == np.asarray(events[col])[idx, 1]).all(), col
        assert (np.diff(recs["step"]) == 1).all()
        assert (np.diff(recs["now"]) >= 0).all()

    def test_ring_not_wrapped_holds_everything(self):
        rt = _pingpong_rt(trace_cap=64, target=3)
        state, events = rt.run(rt.init_batch(np.arange(2)), 256, 64,
                               collect_events=True)
        recs = ring_records(state, lane=0)
        steps = int(np.asarray(state.steps)[0])
        assert recs["total"] == steps and recs["dropped"] == 0
        fired = np.asarray(events["fired"])[:, 0]
        assert (recs["now"] == np.asarray(events["now"])[fired, 0]).all()

    def test_lane_sampling_mask(self):
        rt = _pingpong_rt(trace_cap=8, target=40)
        state = rt.run_fused(rt.init_batch(np.arange(8),
                                           trace_lanes=[2, 5]), 128, 64)
        pos = np.asarray(state.trace_pos)
        assert (pos[[2, 5]] > 0).all()
        assert (pos[[0, 1, 3, 4, 6, 7]] == 0).all()
        assert sampled_lanes(state).tolist() == [2, 5]
        with pytest.raises(ValueError, match="not sampled"):
            ring_records(state, lane=0)

    def test_bool_mask_form(self):
        rt = _pingpong_rt(trace_cap=8, target=40)
        mask = np.zeros(4, bool)
        mask[1] = True
        state = rt.run_fused(rt.init_batch(np.arange(4), trace_lanes=mask),
                             128, 64)
        assert sampled_lanes(state).tolist() == [1]

    def test_trace_lanes_requires_compiled_ring(self):
        rt = _pingpong_rt(trace_cap=0)
        with pytest.raises(ValueError, match="trace_cap"):
            rt.init_batch(np.arange(4), trace_lanes=[0])

    def test_ring_compiled_out_raises_on_read(self):
        rt = _pingpong_rt(trace_cap=0)
        state, _ = rt.run(rt.init_batch(np.arange(2)), 128, 64)
        with pytest.raises(ValueError, match="compiled out"):
            ring_records(state, lane=0)


class TestRingEquivalence:
    """run_fused with trace_cap > 0 bitwise-equal to chunked run() on all
    state (ring included), and the ring itself an observer that never
    perturbs the trajectory. The raft/wal_kv/shard_kv chaos sweeps are
    `slow` (r7 durations triage); the fast lane keeps the pingpong
    perturbation and fused-equality checks."""

    def _assert_fused_equals_chunked(self, rt, seeds, max_steps, chunk):
        chunked, _ = rt.run(rt.init_batch(seeds), max_steps, chunk)
        fused = rt.run_fused(rt.init_batch(seeds), max_steps, chunk)
        # fingerprints cover all non-trace state (the recorder is
        # excluded by design — utils/hashing); the ring columns are
        # compared explicitly so the fused runner must reproduce the
        # recorder's contents exactly too, not just the trajectory
        assert (rt.fingerprints(chunked) == rt.fingerprints(fused)).all()
        for f in _TRACE_FIELDS:
            assert (np.asarray(getattr(chunked, f))
                    == np.asarray(getattr(fused, f))).all(), f
        return fused

    @pytest.mark.slow
    def test_raft_fused_equals_chunked_with_ring(self):
        from madsim_tpu.models.raft import make_raft_runtime
        cfg = SimConfig(n_nodes=5, event_capacity=128, time_limit=sec(3),
                        trace_cap=16,
                        net=NetConfig(packet_loss_rate=0.05,
                                      send_latency_min=ms(1),
                                      send_latency_max=ms(10)))
        sc = Scenario()
        sc.at(sec(1)).kill_random()
        sc.at(sec(1) + ms(400)).restart_random()
        rt = make_raft_runtime(5, 8, n_cmds=4, scenario=sc, cfg=cfg)
        fused = self._assert_fused_equals_chunked(
            rt, np.arange(64, dtype=np.uint32), 1500, 256)
        assert (np.asarray(fused.trace_pos) > 0).all()

    @pytest.mark.slow
    def test_wal_kv_fused_equals_chunked_with_ring(self):
        # mid-sweep crashes: crashed lanes freeze their rings exactly
        # where the chunked runner froze them
        from madsim_tpu.models.wal_kv import make_wal_kv_runtime
        sc = Scenario()
        for t in range(6):
            sc.at(ms(150) + ms(250) * t).kill(0)
            sc.at(ms(210) + ms(250) * t).restart(0)
        # the factory's default cfg with the recorder switched on
        cfg = SimConfig(n_nodes=3, event_capacity=256, payload_words=8,
                        time_limit=sec(10), trace_cap=16,
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(8)))
        rt = make_wal_kv_runtime(n_clients=2, n_ops=12, wal_cap=64,
                                 sync_wal=False, scenario=sc, cfg=cfg)
        fused = self._assert_fused_equals_chunked(
            rt, np.arange(64, dtype=np.uint32), 4096, 512)
        crashed = np.asarray(fused.crashed)
        assert crashed.any() and not crashed.all()

    @pytest.mark.slow
    def test_shard_kv_fused_equals_chunked_with_ring(self):
        from madsim_tpu.models.shard_kv import make_shard_runtime
        cfg = SimConfig(n_nodes=11, event_capacity=160, payload_words=12,
                        time_limit=sec(60), trace_cap=16,
                        net=NetConfig(send_latency_min=ms(1),
                                      send_latency_max=ms(10)))
        rt = make_shard_runtime(n_groups=2, rg=3, rc=3, n_clients=2,
                                n_ops=4, max_cfg=4, cfg=cfg)
        self._assert_fused_equals_chunked(
            rt, np.arange(64, dtype=np.uint32), 4096, 512)

    def test_pingpong_fused_equals_chunked_with_sampled_ring(self):
        # the fast lane's fused-equality check: two sampled lanes, a ring
        # that wraps, and every ring column reproduced by the fused
        # runner; a sampled lane reads back min(total, cap) records
        rt = _pingpong_rt(trace_cap=32, target=1 << 30)
        seeds = np.arange(16)
        chunked, _ = rt.run(rt.init_batch(seeds, trace_lanes=[0, 5]),
                            192, 64)
        fused = rt.run_fused(rt.init_batch(seeds, trace_lanes=[0, 5]),
                             192, 64)
        assert (rt.fingerprints(fused) == rt.fingerprints(chunked)).all()
        for f in _TRACE_FIELDS:
            assert (np.asarray(getattr(fused, f))
                    == np.asarray(getattr(chunked, f))).all(), f
        recs = ring_records(fused, lane=5)
        assert recs["total"] > 32
        assert len(recs["now"]) == min(recs["total"], 32)

    def test_fingerprints_ignore_sampling_mask(self):
        # partial lane sampling must not split fingerprints: the same
        # seeds with different trace_lanes masks (and a cap=0 build)
        # fingerprint identically, so distinct_outcomes stays a
        # trajectory metric, not a which-lanes-were-sampled metric
        seeds = np.arange(8, dtype=np.uint32)
        rt = _pingpong_rt(trace_cap=8)
        sampled, _ = rt.run(rt.init_batch(seeds, trace_lanes=[0]), 256, 64)
        allon, _ = rt.run(rt.init_batch(seeds), 256, 64)
        assert (rt.fingerprints(sampled) == rt.fingerprints(allon)).all()
        rt0 = _pingpong_rt(trace_cap=0)
        off, _ = rt0.run(rt0.init_batch(seeds), 256, 64)
        assert (rt0.fingerprints(off) == rt.fingerprints(sampled)).all()

    def test_ring_never_perturbs_trajectory(self):
        # same workload, ring compiled out vs compiled in vs sampling:
        # every non-trace field bit-identical (trace_cap is an
        # observation lever, not a replay domain)
        seeds = np.arange(16, dtype=np.uint32)
        base, _ = _pingpong_rt(trace_cap=0).run(
            _pingpong_rt(trace_cap=0).init_batch(seeds), 256, 64)
        ref = _nontrace_state(base)
        for cap, lanes in ((8, None), (8, [0, 3]), (64, [])):
            rt = _pingpong_rt(trace_cap=cap)
            st, _ = rt.run(rt.init_batch(seeds, trace_lanes=lanes), 256, 64)
            got = _nontrace_state(st)
            assert ref.keys() == got.keys()
            for k in ref:
                assert (ref[k] == got[k]).all(), \
                    f"trace_cap={cap} lanes={lanes} perturbed {k}"


class TestChromeExport:
    def _kill_restart_rt(self, **kw):
        sc = Scenario()
        sc.at(ms(6)).kill(1)
        sc.at(ms(9)).restart(1)
        return _pingpong_rt(scenario=sc, target=12, **kw)

    def test_event_count_equals_fired_count(self, tmp_path):
        rt = self._kill_restart_rt()
        state, events = rt.run_single(7, 512, chunk=128)
        p = str(tmp_path / "t.json")
        n = export_chrome_trace(p, events=events)
        with open(p) as f:
            doc = json.load(f)                     # valid JSON
        inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        fired = int(np.asarray(events["fired"])[:, 0].sum())
        assert n == len(inst) == fired == int(np.asarray(state.steps)[0])

    def test_frozen_lane_records_excluded(self):
        # overshoot: lanes halt at different steps but every lane's chunk
        # tail keeps emitting fired=False records — none may export
        rt = _pingpong_rt(target=3)
        state, events = rt.run(rt.init_batch(np.arange(4)), 4096, 256,
                               collect_events=True)
        assert np.asarray(events["fired"]).shape[0] \
            > int(np.asarray(state.steps).max())
        for lane in range(4):
            evs = to_chrome_events(events, b=lane)
            assert len(evs) == int(np.asarray(state.steps)[lane])

    def test_kill_restart_render_on_right_node_track(self, tmp_path):
        rt = self._kill_restart_rt()
        _, events = rt.run_single(3, 512, chunk=128)
        p = str(tmp_path / "t.json")
        export_chrome_trace(p, events=events, node_names=["ping", "pong"])
        with open(p) as f:
            doc = json.load(f)
        kills = [e for e in doc["traceEvents"] if e["name"] == "SUPER:KILL"]
        restarts = [e for e in doc["traceEvents"]
                    if e["name"] == "SUPER:RESTART"]
        assert kills and restarts
        assert all(e["tid"] == 1 and e["ph"] == "i" for e in kills + restarts)
        assert kills[0]["ts"] == T.ms(6) and restarts[0]["ts"] == T.ms(9)
        names = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert names[1] == "pong"

    def test_ring_export_matches_collect_events_export(self, tmp_path):
        # cap big enough that nothing dropped: the fused sweep's ring
        # must export the identical event list as the chunked
        # collect_events stream for the same seed
        rt = self._kill_restart_rt(trace_cap=128)
        seeds = np.arange(2, dtype=np.uint32)
        _, events = rt.run(rt.init_batch(seeds), 512, 128,
                           collect_events=True)
        fused = rt.run_fused(rt.init_batch(seeds), 512, 128)
        from_events = to_chrome_events(events, b=1)
        from_ring = to_chrome_events(ring_records(fused, lane=1))
        # the ring source carries MORE than the stream: lineage args
        # (lamport/parent, r10) on each instant plus causal flow arrows
        # appended after them. The shared contract is the dispatch
        # timeline itself — instants must match field-for-field once the
        # ring-only lineage args are set aside.
        ring_instants = [dict(e, args={k: v for k, v in e["args"].items()
                                       if k not in ("lamport", "parent")})
                         for e in from_ring if e["ph"] == "i"]
        assert ring_instants == from_events
        # and every instant the ring exports DOES carry the lineage pair
        assert all({"lamport", "parent"} <= e["args"].keys()
                   for e in from_ring if e["ph"] == "i")

    def test_golden_roundtrip(self, tmp_path):
        # hand-built record stream -> exact expected JSON document
        events = dict(
            fired=np.array([[True], [True], [True], [False]]),
            now=np.array([[0], [1000], [2500], [2500]]),
            kind=np.array([[T.EV_SUPER], [T.EV_MSG], [T.EV_TIMER],
                           [T.EV_MSG]]),
            node=np.array([[0], [1], [1], [0]]),
            src=np.array([[0], [0], [1], [1]]),
            tag=np.array([[T.OP_INIT], [7], [3], [9]]),
        )
        p = str(tmp_path / "golden.json")
        n = export_chrome_trace(p, events=events)
        assert n == 3                              # fired=False dropped
        with open(p) as f:
            doc = json.load(f)
        assert doc == {
            "traceEvents": [
                {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": "node0"}},
                {"name": "thread_name", "ph": "M", "pid": 0, "tid": 1,
                 "args": {"name": "node1"}},
                # args.step (r10): the dispatch index — a stream's k-th
                # fired record IS dispatch k, so Perfetto queries can
                # join the timeline against explain_crash chains and
                # divergence reports
                {"name": "SUPER:INIT", "ph": "i", "s": "t", "ts": 0,
                 "pid": 0, "tid": 0,
                 "args": {"src": 0, "tag": T.OP_INIT, "step": 0}},
                {"name": "MSG:tag7", "ph": "i", "s": "t", "ts": 1000,
                 "pid": 0, "tid": 1, "args": {"src": 0, "tag": 7, "step": 1}},
                {"name": "TIMER:tag3", "ph": "i", "s": "t", "ts": 2500,
                 "pid": 0, "tid": 1, "args": {"src": 1, "tag": 3, "step": 2}},
            ],
            "displayTimeUnit": "ms",
        }

    def test_exactly_one_source(self):
        with pytest.raises(ValueError, match="exactly one"):
            export_chrome_trace("/tmp/x.json")


class TestSweepObservers:
    def test_run_observer_sees_chunks_and_done(self):
        rt = _pingpong_rt(target=3)
        buf = io.StringIO()
        with JsonlObserver(buf) as obs:
            state, _ = rt.run(rt.init_batch(np.arange(8)), 1024, 128,
                              observer=obs)
        recs = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert recs == obs.records
        assert [r["kind"] for r in recs][-1] == "done"
        chunks = [r for r in recs if r["kind"] == "chunk"]
        assert chunks and chunks[0]["batch"] == 8
        assert (np.diff([c["steps_done"] for c in chunks]) == 128).all()
        done = recs[-1]
        assert done["lanes_halted"] == 8
        assert done["lane_steps_per_sec"] > 0

    def test_explore_observer_matches_result(self):
        rt = _pingpong_rt(target=3, loss=0.1, n_nodes=4)
        buf = io.StringIO()
        with JsonlObserver(buf) as obs:
            res = explore(rt, max_steps=1024, batch=16, max_rounds=4,
                          dry_rounds=2, observer=obs)
        rounds = [r for r in obs.records if r["kind"] == "round"]
        assert len(rounds) == res["rounds"]
        assert [r["new_schedules"] for r in rounds] == res["new_per_round"]
        assert rounds[-1]["distinct_total"] == res["distinct_schedules"]
        assert obs.records[-1]["kind"] == "done"
        assert obs.records[-1]["distinct_total"] == res["distinct_schedules"]

    def test_compacting_observer_sees_repack(self):
        # loss-driven retries spread halt steps across lanes (measured
        # 31..61 over this batch) and a fine chunk catches the spread
        # mid-flight, so the re-pack actually triggers; tiny min_batch
        cfg = SimConfig(n_nodes=2, time_limit=sec(60),
                        net=NetConfig(packet_loss_rate=0.3,
                                      send_latency_min=ms(1),
                                      send_latency_max=ms(40)))
        rt = Runtime(cfg, [PingPong(2, target=6)], state_spec())
        seeds = np.arange(64, dtype=np.uint32)
        ref, _ = rt.run(rt.init_batch(seeds), 8192, 16)
        obs = JsonlObserver(io.StringIO())
        final = rt.run_compacting(rt.init_batch(seeds), 8192, 16,
                                  compact_when=0.3, min_batch=8,
                                  observer=obs)
        assert (rt.fingerprints(final) == rt.fingerprints(ref)).all()
        compacts = [r for r in obs.records if r["kind"] == "compact"]
        assert compacts, "workload never triggered a re-pack"
        assert all(c["to_batch"] < c["from_batch"] for c in compacts)
        assert obs.records[-1]["kind"] == "done"
        assert obs.records[-1]["repacks"] == len(compacts)
        assert obs.records[-1]["lanes_halted"] == 64

    def test_progress_and_tee(self):
        rt = _pingpong_rt(target=3)
        out = io.StringIO()
        jl = JsonlObserver(io.StringIO())
        prog = ProgressObserver(stream=out, min_interval=0.0)
        rt.run(rt.init_batch(np.arange(8)), 512, 128,
               observer=TeeObserver(jl, prog))
        assert "halted 8/8" in out.getvalue()
        assert jl.records[-1]["kind"] == "done"


def _fake_state(cap, pos, on=True, batch=None):
    """Synthetic ring state: slot values encode (event index + 1) * 10 so
    unwrap order is checkable without running the engine. Exercises
    rings.py's host-side math at zero compile cost."""
    from types import SimpleNamespace
    if cap > 0:
        vals = np.zeros(cap, np.int32)
        for e in range(pos):            # replay the writer's slot rule
            vals[e % cap] = (e + 1) * 10
    else:
        vals = np.zeros(0, np.int32)
    cols = {f"tr_{k}": vals.copy() for k in
            ("now", "step", "kind", "node", "src", "tag")}
    st = SimpleNamespace(trace_pos=np.int32(pos), trace_on=np.bool_(on),
                         trace_cap=np.int32(cap),   # the dynamic capacity
                         **cols)                    # operand (DESIGN §10)
    if batch is not None:
        for k, v in vars(st).items():
            setattr(st, k, np.stack([np.asarray(v)] * batch))
    return st


class TestRingUnwrapMath:
    def test_empty_ring(self):
        recs = ring_records(_fake_state(4, 0))
        assert recs["total"] == 0 and recs["dropped"] == 0
        assert len(recs["now"]) == 0

    def test_partial_fill_is_prefix(self):
        recs = ring_records(_fake_state(4, 3))
        assert recs["now"].tolist() == [10, 20, 30]
        assert recs["dropped"] == 0

    def test_exactly_full_no_wrap(self):
        recs = ring_records(_fake_state(4, 4))
        assert recs["now"].tolist() == [10, 20, 30, 40]
        assert recs["dropped"] == 0

    def test_wrap_by_one(self):
        recs = ring_records(_fake_state(4, 5))
        assert recs["now"].tolist() == [20, 30, 40, 50]
        assert recs["dropped"] == 1

    def test_wrap_to_slot_zero_boundary(self):
        # pos a multiple of cap after wrapping: oldest is at slot 0 again
        recs = ring_records(_fake_state(4, 8))
        assert recs["now"].tolist() == [50, 60, 70, 80]
        assert recs["dropped"] == 4

    def test_batched_lane_select(self):
        recs = ring_records(_fake_state(4, 5, batch=3), lane=2)
        assert recs["now"].tolist() == [20, 30, 40, 50]

    def test_unsampled_lane_raises(self):
        with pytest.raises(ValueError, match="not sampled"):
            ring_records(_fake_state(4, 0, on=False))

    def test_chrome_events_from_ring_dict(self):
        # a ring_records dict feeds the exporter without a fired column
        evs = to_chrome_events(dict(
            now=np.array([5, 9]), kind=np.array([T.EV_MSG, T.EV_TIMER]),
            node=np.array([1, 0]), src=np.array([0, 0]),
            tag=np.array([7, 2])))
        assert [e["ts"] for e in evs] == [5, 9]
        assert evs[0]["name"] == "MSG:tag7"
        assert evs[1]["name"] == "TIMER:tag2"


class TestObserverPlumbing:
    def test_jsonl_rounds_floats_and_appends(self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        with JsonlObserver(p) as obs:
            obs.on_chunk(dict(kind="chunk", wall_s=1.23456))
        with JsonlObserver(p) as obs:       # append, not truncate
            obs.on_done(dict(kind="done", wall_s=2.0))
        recs = [json.loads(l) for l in open(p)]
        assert [r["kind"] for r in recs] == ["chunk", "done"]
        assert recs[0]["wall_s"] == 1.235

    def test_tee_fans_out_every_hook(self):
        seen = []

        class Probe(JsonlObserver):
            def __init__(self, name):
                super().__init__(io.StringIO())
                self.name = name

            def _emit(self, rec):
                seen.append((self.name, rec["kind"]))

            on_chunk = on_compact = on_round = on_done = _emit

        tee = TeeObserver(Probe("a"), Probe("b"))
        tee.on_chunk(dict(kind="chunk"))
        tee.on_compact(dict(kind="compact"))
        tee.on_round(dict(kind="round"))
        tee.on_done(dict(kind="done"))
        assert seen == [("a", "chunk"), ("b", "chunk"),
                        ("a", "compact"), ("b", "compact"),
                        ("a", "round"), ("b", "round"),
                        ("a", "done"), ("b", "done")]

    def test_progress_rate_formatting(self):
        from madsim_tpu.obs.progress import _rate
        assert _rate(512.0) == "512"
        assert _rate(2_500.0) == "2.5k"
        assert _rate(3_400_000.0) == "3.4M"
        assert _rate(1.2e9) == "1.2G"

    def test_base_observer_is_noop(self):
        from madsim_tpu import SweepObserver
        obs = SweepObserver()
        obs.on_chunk({})
        obs.on_compact({})
        obs.on_round({})
        obs.on_done({})


class TestSummarizeLabels:
    def test_labels_are_explicit(self):
        rt = _pingpong_rt(target=3)
        seeds = np.arange(100, 108, dtype=np.uint32)
        state, _ = rt.run(rt.init_batch(seeds), 512, 128)
        assert summarize(rt, state)["seed_labels"] == "lane_index"
        rep = summarize(rt, state, seeds=seeds)
        assert rep["seed_labels"] == "seed"
