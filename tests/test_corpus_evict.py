"""Corpus admission against a plain reference of its eviction rule.

`Corpus.observe` keeps a round's energies in a float64 array and picks
each eviction with `np.argmin` over it. The reference below keeps the
rule in its plainest form: decay and parent reward on the entry dicts,
and each eviction a scan of every entry's energy. Driven through the same
rounds, the two must agree exactly after every round: slot order, ids,
hashes, float energies, `_by_id`, `evicted_unsynced`, and the ids the
next `schedule()` draws."""

import numpy as np
import pytest

from madsim_tpu import Corpus, KnobPlan, Runtime, Scenario, SimConfig, fuzz
from madsim_tpu import ms, sec
from madsim_tpu.models.pingpong import PingPong, state_spec


class _Plan:
    """The one thing `Corpus` asks of its plan here: base knobs."""

    def base_knobs(self):
        return {"row_t": np.zeros(3, np.int32)}


class _Reference:
    """The eviction rule as a list scan over entry dicts."""

    def __init__(self, max_entries, decay, rng_seed, fresh_frac=0.125,
                 reward=1.5, energy_cap=8.0):
        self.max_entries = max_entries
        self.decay = decay
        self.rng = np.random.default_rng(rng_seed)
        self.fresh_frac = fresh_frac
        self.reward = reward
        self.energy_cap = energy_cap
        self.entries, self.by_id, self.evicted = [], {}, []
        self.seen, self.crash_codes = set(), set()
        self.next_id = 0
        # what the rounds exercised, for the cases' own checks
        self.seen_cases = dict(fill_mid_round=0, floor_ties=0,
                               parent_evicted_same_round=0, crashed=0,
                               duplicates=0, evictions=0)

    def observe(self, kb, seeds, hashes, crashed, codes, parent_ids,
                round_no):
        for e in self.entries:
            e["energy"] = max(0.05, e["energy"] * self.decay)
        at_start = set(self.by_id)
        appended = evicted = 0
        for i in range(len(seeds)):
            h = int(hashes[i])
            hit = bool(crashed[i])
            if hit:
                self.crash_codes.add(int(codes[i]))
            if h in self.seen:
                self.seen_cases["duplicates"] += 1
                continue
            self.seen.add(h)
            entry = dict(id=self.next_id, hash=h, seed=int(seeds[i]),
                         knobs=KnobPlan.lane(kb, i),
                         energy=min(self.energy_cap, 3.0 if hit else 1.0),
                         round=int(round_no), div_slot=None,
                         crash_code=int(codes[i]) if hit else 0)
            self.next_id += 1
            self.seen_cases["crashed"] += hit
            self.by_id[entry["id"]] = entry
            if len(self.entries) < self.max_entries:
                self.entries.append(entry)
                appended += 1
            else:
                scan = [e["energy"] for e in self.entries]
                j = int(np.argmin(scan))
                self.seen_cases["floor_ties"] += (
                    scan[j] == 0.05 and scan.count(0.05) > 1)
                del self.by_id[self.entries[j]["id"]]
                self.evicted.append(self.entries[j])
                self.entries[j] = entry
                evicted += 1
            pid = int(parent_ids[i])
            parent = self.by_id.get(pid)
            if parent is not None:
                parent["energy"] = min(self.energy_cap,
                                       parent["energy"] * self.reward)
            elif pid in at_start:
                self.seen_cases["parent_evicted_same_round"] += 1
        self.seen_cases["fill_mid_round"] += appended > 0 and evicted > 0
        self.seen_cases["evictions"] += evicted

    def schedule(self, batch):
        ids = np.full(batch, -1, np.int64)
        if self.entries:
            en = np.asarray([e["energy"] for e in self.entries])
            pick = self.rng.choice(len(self.entries), size=batch,
                                   p=en / en.sum())
            mutate = self.rng.random(batch) >= self.fresh_frac
            for i in range(batch):
                if mutate[i]:
                    ids[i] = self.entries[int(pick[i])]["id"]
        return ids


def _same_entry(a, b):
    for k in ("id", "hash", "seed", "round", "div_slot", "crash_code"):
        assert a[k] == b[k], k
    assert type(a["energy"]) is float and type(b["energy"]) is float
    assert a["energy"].hex() == b["energy"].hex()
    assert a["knobs"].keys() == b["knobs"].keys()
    for k in a["knobs"]:
        np.testing.assert_array_equal(a["knobs"][k], b["knobs"][k])


def _assert_same(c, ref):
    assert len(c.entries) == len(ref.entries)
    for a, b in zip(c.entries, ref.entries):
        _same_entry(a, b)
    assert set(c._by_id) == set(ref.by_id)
    for j, e in enumerate(c.entries):
        assert c._by_id[e["id"]] is e, j
    assert len(c.evicted_unsynced) == len(ref.evicted)
    for a, b in zip(c.evicted_unsynced, ref.evicted):
        _same_entry(a, b)
    assert c._seen == ref.seen and c.crash_codes == ref.crash_codes


# name: (max_entries, batch, rounds, decay, hash pool, crash share,
#        quiet rounds before the first admission, what must be exercised)
CASES = {
    "fills_mid_round": (40, 16, 6, 0.97, 10_000, 0.0, 0, "fill_mid_round"),
    "floor_ties": (12, 16, 8, 0.5, 10_000, 0.0, 6, "floor_ties"),
    "parent_evicted_same_round": (8, 32, 8, 0.9, 10_000, 0.0, 0,
                                  "parent_evicted_same_round"),
    "crashed_lanes": (16, 24, 6, 0.97, 10_000, 0.4, 0, "crashed"),
    "duplicate_hashes": (16, 32, 8, 0.97, 48, 0.1, 0, "duplicates"),
    "max_entries_1": (1, 8, 6, 0.97, 10_000, 0.2, 0, "evictions"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_observe_matches_list_scan_rule(case):
    (max_entries, batch, rounds, decay, pool, crash_p, quiet,
     witness) = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case) + 2_400_000_001)
    c = Corpus(_Plan(), rng=np.random.default_rng(7),
               max_entries=max_entries, decay=decay)
    c.track_evictions = True
    ref = _Reference(max_entries, decay, rng_seed=7)
    # round 0 seeds both corpora; the quiet rounds then only decay (every
    # hash already seen), so the energies pile up on the 0.05 floor
    first = rng.choice(pool, size=batch, replace=False)
    parent_ids = np.full(batch, -1, np.int64)
    for r in range(rounds):
        if 1 <= r <= quiet:
            hashes = first.copy()
        else:
            hashes = rng.integers(0, pool, size=batch)
            if r == 0:
                hashes = first
        crashed = rng.random(batch) < crash_p
        codes = np.where(crashed, rng.integers(1, 4, size=batch), 0)
        # parent ids: the scheduled ones, a few stale and unknown ids
        pids = parent_ids.copy()
        pids[rng.random(batch) < 0.1] = rng.integers(0, ref.next_id + 5)
        seeds = np.arange(batch) + r * batch
        kb = {"row_t": rng.integers(0, 1000, size=(batch, 3))}
        c.observe(kb, seeds, hashes.astype(np.uint64), crashed, codes,
                  pids, r)
        ref.observe(kb, seeds, hashes, crashed, codes, pids, r)
        _assert_same(c, ref)
        _, parent_ids = c.schedule(batch)
        np.testing.assert_array_equal(parent_ids, ref.schedule(batch))
    assert ref.seen_cases[witness] > 0, ref.seen_cases


def test_observe_counts_evictions():
    c = Corpus(_Plan(), rng=np.random.default_rng(0), max_entries=4)
    kb = {"row_t": np.zeros((3, 3), np.int64)}

    def observe(hashes, r):
        return c.observe(kb, np.arange(3), np.asarray(hashes, np.uint64),
                         np.zeros(3, bool), np.zeros(3, int),
                         np.full(3, -1), r)

    st = observe([1, 2, 3], 0)
    assert (st["new"], st["evicted"], st["size"]) == (3, 0, 3)
    st = observe([4, 5, 6], 1)        # one fills the corpus, two evict
    assert (st["new"], st["evicted"], st["size"]) == (3, 2, 4)
    st = observe([6, 7, 7], 2)        # duplicates admit and evict nothing
    assert (st["new"], st["evicted"], st["size"]) == (1, 1, 4)


def test_fuzz_round_records_carry_evicted():
    sc = Scenario()
    sc.at(ms(40)).kill_random()
    sc.at(ms(300)).restart_random()
    rt = Runtime(SimConfig(n_nodes=3, time_limit=sec(2)),
                 [PingPong(3, target=4)], state_spec(), scenario=sc)

    class Rec:
        def __init__(self):
            self.records = []

        def on_round(self, rec):
            self.records.append(rec)

        def on_done(self, rec):
            pass

    obs = Rec()
    cap = 6
    corpus = Corpus(KnobPlan.from_runtime(rt), rng=np.random.default_rng(0),
                    max_entries=cap)
    fuzz(rt, max_steps=400, batch=16, max_rounds=3, dry_rounds=4,
         chunk=128, corpus=corpus, observer=obs)
    rounds = [r for r in obs.records if r["kind"] == "fuzz_round"]
    assert len(rounds) == 3
    size = 0
    for rec in rounds:
        # admissions past the room the corpus had at the round's start
        assert rec["evicted"] == max(0, rec["admitted"] - (cap - size))
        size = rec["corpus_size"]
    assert sum(rec["evicted"] for rec in rounds) > 0
