"""Linearizability sweep: batches of fresh seeds run to halt through
`Runtime.run_compacting`, every history through the program's checker.

Traffic keys: batch, max_steps, chunk, compact_when, min_batch.

A unit is one batch: `init_batch`, `run_compacting` until every lane halts
(the re-pack onto narrower batches included), then `extract_histories` and
`native.check_kv_history` on every history. Its events are the steps the
lanes dispatched; its verified seeds are the lanes whose history the
program's checker passed. After the window every lane goes through the
configuration's reference checks, its own linearizability search among
them, and the program's verdicts are compared with the reference's.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness as H


class Recorder:
    """A SweepObserver (duck-typed) that keeps run_compacting's records."""

    def __init__(self):
        self.chunks: list[dict] = []
        self.compacts: list[dict] = []

    def on_chunk(self, rec):
        self.chunks.append(rec)

    def on_compact(self, rec):
        self.compacts.append(rec)

    def on_done(self, rec):
        pass


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic
        self.B = self.t["batch"]
        self.counts = dict(events=0, verified=0)
        self.kept: list[dict] = []
        self.verdicts: list[np.ndarray] = []
        self.records = Recorder()
        self.leaves = H.node_leaves(ctx.config)

    def setup(self):
        self.rt = self.ctx.build()
        # every width a re-pack can reach: powers of two below the batch
        # down to min_batch, one chunk each, so no unit compiles (the warm
        # unit below runs the full width)
        w = self.B // 2
        while w >= self.t["min_batch"]:
            st = self.rt.init_batch(H.lane_seeds(self.ctx.base, 0, w))
            st = self.rt.run_compacting(st, self.t["chunk"],
                                        chunk=self.t["chunk"])
            st.steps.block_until_ready()
            w //= 2
        self._unit(-1, Recorder())
        self.kept.clear()
        self.verdicts.clear()
        self.counts.update(events=0, verified=0)

    def _unit(self, u: int, rec):
        from madsim_tpu.models.raft_kv import extract_histories
        from madsim_tpu.native import check_kv_history
        sp, kv = self.ctx.spans, self.ctx.config["kv"]
        seeds = H.lane_seeds(self.ctx.base, self.B * (u + 1), self.B)
        with sp("init_batch"):
            st = self.rt.init_batch(seeds)
        with sp("run_compacting"):
            st = self.rt.run_compacting(
                st, self.t["max_steps"], chunk=self.t["chunk"],
                compact_when=self.t["compact_when"],
                min_batch=self.t["min_batch"], observer=rec)
            st.steps.block_until_ready()
        with sp("check"):
            hists = extract_histories(st, kv["n_raft"], kv["n_clients"])
            ok = np.array([check_kv_history(h) for h in hists], bool)
        steps = np.asarray(st.steps)
        self.counts["events"] += int(steps.astype(np.int64).sum())
        self.counts["verified"] += int(ok.sum())
        self.kept.append(H.projection(st, self.leaves))
        self.verdicts.append(ok)

    def unit(self, u: int):
        self._unit(u, self.records)

    def verify(self, rng: np.random.Generator):
        lanes = H.fetch(self.kept)
        flags = H.run_checks(self.ctx.config, lanes,
                             dict(verdicts=np.concatenate(self.verdicts)))
        flags["unfinished"] = ~lanes["halted"].astype(bool)
        return flags
