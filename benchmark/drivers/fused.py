"""Bare seed sweep: `Runtime.run_fused` over fresh seeds, one call a unit.

Traffic keys: batch (lanes per call), steps (per call), chunk (scan length),
replay_lanes (lanes replayed on the host CPU after the window).

A unit is one call: `init_batch` of `batch` fresh seeds, `run_fused` for
`steps` steps, then the harvest of each lane's step count, which is the
sync that ends the unit. The unit's events are the steps its lanes
dispatched. After the window every lane of every unit goes through the
configuration's reference checks, and `replay_lanes` lanes drawn from the
seed run again on the host CPU, where every leaf the check keeps has to
come out as the chip's did.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness as H


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.B, self.steps, self.chunk = t["batch"], t["steps"], t["chunk"]
        self.per_call = -(-self.steps // self.chunk) * self.chunk
        self.counts = dict(events=0)
        self.kept: list[dict] = []
        self.seeds: list[np.ndarray] = []
        self.leaves = H.node_leaves(ctx.config)

    def setup(self):
        self.rt = self.ctx.build()
        self._unit(-1)            # warm: the very shapes every unit uses
        self.kept.clear()
        self.seeds.clear()
        self.counts["events"] = 0

    def _unit(self, u: int):
        sp = self.ctx.spans
        seeds = H.lane_seeds(self.ctx.base, self.B * (u + 1), self.B)
        with sp("init_batch"):
            st = self.rt.init_batch(seeds)
        with sp("run_fused"):
            st = self.rt.run_fused(st, self.steps, chunk=self.chunk)
            st.steps.block_until_ready()
        with sp("harvest"):
            steps = np.asarray(st.steps)
        self.counts["events"] += int(steps.astype(np.int64).sum())
        self.kept.append(H.projection(st, self.leaves))
        self.seeds.append(seeds)

    def unit(self, u: int):
        self._unit(u)

    def verify(self, rng: np.random.Generator):
        lanes = H.fetch(self.kept)
        flags = H.run_checks(self.ctx.config, lanes)
        steps = lanes["steps"]
        halted = lanes["halted"].astype(bool)
        # a live lane dispatched exactly one event per step it was given
        flags["progress"] = ~halted & (steps != self.per_call)
        seeds = np.concatenate(self.seeds)
        pick = np.sort(rng.choice(len(seeds), self.ctx.traffic[
            "replay_lanes"], replace=False))
        replay = np.zeros(len(seeds), bool)
        replay[pick] = H.differs(self._cpu_replay(seeds[pick]),
                                 {k: v[pick] for k, v in lanes.items()})
        flags["replay_differs"] = replay
        return flags

    def _cpu_replay(self, seeds: np.ndarray) -> dict:
        """The same seeds, run again on the host CPU backend: the leaves
        the check keeps."""
        import jax
        with self.ctx.spans("cpu_replay"), \
                jax.default_device(jax.devices("cpu")[0]):
            st = self.rt.run_fused(self.rt.init_batch(seeds), self.steps,
                                   chunk=self.chunk)
            return H.fetch([H.projection(st, self.leaves)])
