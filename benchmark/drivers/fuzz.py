"""Fuzz campaigns: `search.fuzz.fuzz` on one chip, back to back with fresh
seeds.

Traffic keys: batch (lanes per round), steps (per round), chunk, rounds
(per campaign), pipeline, replay_lanes (corpus entries replayed on the chip
after the window, at most `batch`), cpu_replay_lanes (of those, the ones
run again on the host CPU).

A unit is one campaign of `rounds` rounds, in memory (no corpus dir), given
a corpus built as `fuzz` builds its own, so the driver can read the
(seed, knobs, sched_hash) of every schedule it kept. Its schedules are the
distinct schedules the campaign reports, deduplicated by `sched_hash` as
the fuzzer does. Nothing is added to the timed path.

After the window, `replay_lanes` corpus entries drawn from the seed run
again on the chip from their (seed, knobs) pair through `KnobPlan.apply`
and `run_fused`, the window's own programs at its batch. Each has to come
back with the schedule hash the campaign counted, pass the configuration's
reference checks, and have run every step it was given; `cpu_replay_lanes`
of them run once more on the host CPU, where every leaf the check keeps has
to come out as the chip's did.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness as H


class Recorder:
    """A SweepObserver (duck-typed) that keeps the fuzz_round records; each
    round's harvest is a sync point where a traced run may stop its trace."""

    def __init__(self, tick=lambda: None):
        self.rounds: list[dict] = []
        self.tick = tick

    def on_round(self, rec):
        import time
        self.rounds.append(dict(rec, t_host=time.perf_counter()))
        self.tick()

    def on_done(self, rec):
        pass


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic
        self.per_call = -(-self.t["steps"] // self.t["chunk"]) * self.t[
            "chunk"]
        self.counts = dict(schedules=0)
        self.corpora: list = []
        self.records = Recorder(lambda: ctx.tick())
        self.leaves = H.node_leaves(ctx.config)

    def setup(self):
        from madsim_tpu.search.mutate import KnobPlan
        self.rt = self.ctx.build()
        self.plan = KnobPlan.from_runtime(self.rt)
        # three rounds reach every program a campaign runs: the pipeline
        # launches round 1 before round 0's harvest fills the corpus, so
        # round 2 is the first to schedule and mutate
        self._unit(-1, Recorder(), rounds=3)
        self.corpora.clear()
        self.counts["schedules"] = 0

    def _unit(self, u: int, rec, rounds: int | None = None):
        from madsim_tpu.search.corpus import Corpus
        from madsim_tpu.search.fuzz import fuzz
        t = self.t
        rounds = rounds or t["rounds"]
        base_seed = (self.ctx.base + (u + 1) * t["rounds"] * t["batch"]) \
            % (1 << 32)
        rng_seed = (self.ctx.base ^ ((u + 1) * 0x9E3779B1)) % (1 << 32)
        kw = dict(batch=t["batch"], max_rounds=rounds, dry_rounds=rounds,
                  base_seed=base_seed, chunk=t["chunk"],
                  pipeline=t["pipeline"], rng_seed=rng_seed, observer=rec)
        # fuzz's own default corpus, built here so that its entries stay
        # readable after the campaign
        corpus = Corpus(self.plan, rng=np.random.default_rng(rng_seed),
                        div_bonus=1.0)
        with self.ctx.spans("campaign"):
            out = fuzz(self.rt, t["steps"], corpus=corpus, **kw)
        self.counts["schedules"] += int(out["distinct_schedules"])
        self.corpora.append(corpus)

    def unit(self, u: int):
        self._unit(u, self.records)

    def entries(self) -> list[dict]:
        return [e for c in self.corpora for e in c.entries]

    def replay(self, entries: list[dict]) -> dict:
        """(seed, knobs) pairs run again through the campaign's programs:
        the leaves the check keeps, as host arrays."""
        from madsim_tpu.search.mutate import KnobPlan
        seeds = np.array([e["seed"] for e in entries], np.uint32)
        knobs = KnobPlan.stack([e["knobs"] for e in entries])
        st = self.plan.apply(self.rt.init_batch(seeds), knobs)
        st = self.rt.run_fused(st, self.t["steps"], chunk=self.t["chunk"])
        return H.fetch([H.projection(st, self.leaves)])

    def verify(self, rng: np.random.Generator):
        import jax
        kept = self.entries()
        if not kept:
            return dict(no_schedules=np.ones(1, bool))
        n = min(self.t["replay_lanes"], len(kept))
        pick = [kept[int(i)] for i in np.sort(rng.choice(len(kept), n,
                                                          replace=False))]
        if n < self.t["batch"]:
            # the window's batch, so the replay runs the window's programs
            pick = pick + [pick[-1]] * (self.t["batch"] - n)
        with self.ctx.spans("replay"):
            lanes = {k: v[:n] for k, v in self.replay(pick).items()}
        flags = H.run_checks(self.ctx.config, lanes)
        halted = lanes["halted"].astype(bool)
        flags["progress"] = ~halted & (lanes["steps"] != self.per_call)
        h = lanes["sched_hash"].astype(np.uint64)
        h = (h[:, 0] << np.uint64(32)) | h[:, 1]
        want = np.array([e["hash"] for e in pick[:n]], np.uint64)
        flags["hash_differs"] = h != want
        m = min(self.t["cpu_replay_lanes"], n)
        sub = np.sort(rng.choice(n, m, replace=False))
        with self.ctx.spans("cpu_replay"), \
                jax.default_device(jax.devices("cpu")[0]):
            cpu = self.replay([pick[int(i)] for i in sub])
        replay = np.zeros(n, bool)
        replay[sub] = H.differs(cpu, {k: v[sub] for k, v in lanes.items()})
        flags["replay_differs"] = replay
        return flags
