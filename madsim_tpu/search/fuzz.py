"""The coverage-guided fuzz loop: mutate -> run -> evaluate, pipelined.

`explore()` (parallel/explore.py) samples the schedule space blindly —
fresh seeds, one fixed fault script. This driver SEARCHES it: every round
schedules parents from the corpus (energy-weighted), derives a batch of
mutants on device (search/mutate.py — zero recompiles, knobs are traced
operands), runs them as one fused dispatch, and admits lanes that reached
never-seen `sched_hash` coverage back into the corpus. Loop-until-dry,
exactly like explore(): the sweep stops when `dry_rounds` consecutive
rounds add no new schedule.

Pipelining (the Podracer discipline, PAPERS.md, same shape as explore()):
round r+1's mutate+init+run is DISPATCHED before the host blocks on round
r's harvest, so corpus bookkeeping overlaps device compute. The price is
one round of corpus staleness — round r+1's parents are scheduled from
the corpus as of round r-1 — which only delays (never loses) coverage
feedback; `pipeline=False` restores the fully-serial AFL loop.

Crashes are harvested, never aborted on: every distinct crash code keeps
its first full repro handle — (seed, knob vector) — because a mutated
lane's behavior is NOT reproducible from the seed alone. `minimize=True`
auto-shrinks each repro's fault rows through `harness.minimize`
(batched ddmin, knob domain — no slot-layout verification gap).

Durable campaigns (r11, `corpus_dir=`): the corpus, the cross-round
consensus sketch, and every crash repro live in a `service.CorpusStore`
directory, synced at round boundaries. A killed campaign resumes from
its last sync and — because everything between syncs is re-derived from
(restored rng state, restored corpus, deterministic seeds) — converges
to exactly the run that was never killed. Crashed lanes are additionally
deduped into causal-fingerprint buckets (service/buckets.py). The price
of the durability contract is that the speculative pipeline is disabled
(round r+1's parents must be scheduled AFTER round r's sync point, or
the persisted rng state could not replay the schedule draw); campaign
throughput instead comes from multiple worker processes sharing the dir
(service/campaign.py — the Podracer split: many cheap actors, one
durable store).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from ..compile.persistent import enable_persistent_cache
from ..parallel import stats
from .corpus import Corpus, YIELD_NAMES
from .mutate import N_MUT_OPS, OP_NAMES, KnobPlan

# seed-space stride between workers sharing a corpus dir: worker w's round
# r runs seeds [base + w*STRIDE + r*batch, ...) mod 2^32. Campaigns stay
# collision-free while rounds*batch < STRIDE (2^26 ≈ 67M seeds per worker)
# and worker_id < 64 per base_seed (the uint32 seed space holds 64
# strides; the 2^23-worker ID namespace is a separate, wider contract —
# shard bigger fleets across base_seeds).
WORKER_SEED_STRIDE = 1 << 26


def _lat_fields(lat_brief: dict) -> dict:
    """The latency slice of a fuzz-round / done / metrics record
    (obs/metrics.py schema) — ONE definition, so the round records and
    the durable timeline rows can't silently diverge (the
    apply_repro_knobs precedent). `search.shard` imports it too."""
    return dict(lat_p50=lat_brief["e2e_p50"],
                lat_p99=lat_brief["e2e_p99"],
                slo_miss=lat_brief["slo_miss"],
                slo_target=lat_brief.get("slo_target", 0))


def _env_verify_resume() -> bool:
    """Default for the run-twice resume guard when the caller passed
    None: MADSIM_FUZZ_VERIFY_RESUME=1 turns it on fleet-wide (CI and
    the campaign smokes set it) without touching call sites."""
    import os
    return os.environ.get("MADSIM_FUZZ_VERIFY_RESUME", "") not in ("", "0")


def fuzz(rt, max_steps: int, batch: int = 512, max_rounds: int = 16,
         dry_rounds: int = 3, base_seed: int = 0, chunk: int = 512,
         pipeline: bool = True, fused: bool = True, dup_slots: int = 2,
         havoc: int = 3, fresh_frac: float = 0.125, rng_seed: int = 0,
         observer=None, minimize: bool = False, corpus: Corpus | None = None,
         div_bonus: float | None = None, lat_bonus: float | None = None,
         burst_bonus: float | None = None,
         corpus_dir: str | None = None,
         worker_id: int = 0, sync_every: int = 1,
         verify_resume: bool | None = None, ldfi=None):
    """Coverage-guided schedule fuzzing over `rt`'s dynamic fault knobs.

    Round 0 is a blind bootstrap (base knobs, fresh seeds — one explore()
    round) that seeds the corpus; rounds 1.. run mutants. Every lane gets
    a FRESH seed (seed randomness and knob search compose: the knob vector
    moves the fault model, the seed moves the tie-breaks/timeouts within
    it), so a repro is always the (seed, knobs) pair.

    Args beyond explore()'s: dup_slots (spare event rows for the
    row-duplicate operator), havoc (stacked mutations per lane), fresh_frac
    (exploration floor of unmutated lanes per round), rng_seed (corpus
    scheduling + mutation randomness — the whole campaign is replayable),
    minimize (auto-shrink each crash repro's fault rows), corpus (pass a
    prior campaign's corpus to continue it), div_bonus (early-divergence
    admission-energy bonus when the runtime compiles the prefix sketch
    in, cfg.sketch_slots > 0 — see search/corpus.py; 0 restores
    sched_hash-only energy, a sketchless build is always hash-only
    regardless, and None keeps the corpus's setting — the default 1.0
    for a fresh corpus, whatever a passed-in `corpus` was built with),
    lat_bonus (OPT-IN tail-latency admission bonus when the runtime
    compiles the latency plane in, cfg.latency_hist > 0 — admissions
    whose lane's own e2e p99 sits at the round's worst tail get up to
    x(1+lat_bonus) energy, so the fuzzer hunts TAIL AMPLIFICATION; the
    default None/0.0 keeps energy latency-blind, same None-keeps-
    corpus-setting contract as div_bonus), burst_bonus (OPT-IN
    transient-spike admission bonus when the runtime compiles the
    windowed series plane in, cfg.series_windows > 0 — admissions are
    scored by each lane's DEEPEST per-window spike
    (parallel.stats.lane_burst: worst per-window p99, or queue
    high-water without the latency plane), so a mutant that digs one
    deep transient hole outscores one that is merely uniformly slow —
    the admission shape that feeds `recovery_invariant` campaigns;
    same None-keeps-corpus-setting contract).

    Durable-campaign args (corpus_dir is the switch):
      corpus_dir   a service.CorpusStore directory (created on first
                   use, signature-checked on reopen). `max_rounds`
                   becomes the CAMPAIGN total: a resumed call runs only
                   the remaining rounds and returns immediately once
                   rounds_done >= max_rounds or the persisted dry count
                   saturated. With corpus_dir set, `distinct_schedules`
                   reports the campaign's cumulative coverage as seen by
                   this worker (resumes and cross-worker merges fold in).
      worker_id    this process's namespace: entry ids, seed space
                   (WORKER_SEED_STRIDE apart), and state/log file names.
                   Give every concurrent worker on one dir a distinct id.
      sync_every   rounds between durability points (1 = every round).
                   A SIGKILL loses at most the work since the last sync,
                   and the resumed run re-derives it bit-identically.
      verify_resume  run-twice guard (r13, knob-gated; None reads
                   MADSIM_FUZZ_VERIFY_RESUME, default off) on the FIRST
                   round after a resume — exactly the deserialized-
                   executable invocation where this jaxlib's persistent
                   compile cache can return a deterministic-but-wrong
                   result under load (ROADMAP r12 note). The round's
                   (seeds, knobs) batch is re-dispatched until two
                   consecutive invocations agree on (hashes, crashed,
                   codes, sketches), mirroring analyze.replay_race's
                   contract; three distinct results raise. Resume
                   equality is replay-authoritative — a corrupted first
                   invocation would fork the campaign from the run that
                   was never killed.

    ldfi (r22, DESIGN §23): a `search.ldfi.LdfiConfig` turns on the
    lineage-driven arm — green lanes' success supports are extracted
    from their rings (`obs/support.py`, needs cfg.trace_cap > 0; the
    witness is `ldfi.witness`), pooled across lanes, and each round
    after bootstrap gives the LAST `ldfi.frac` of its batch to
    synthesized targeted vectors (ordinary knob rows — apply/minimize/
    replay/buckets all work unchanged) while the rest stays havoc.
    Targeted lanes are a distinct corpus arm: admitted entries carry
    `origin="targeted"` (additive store field), bucket records an
    `origin`, round records and worker state a `targeted_yield`
    counter. The speculative pipeline is disabled (round r+1's
    synthesis needs round r's rings — the durable-store rationale);
    ldfi=None is the pre-r22 fuzzer bit for bit, stores included.

    observer: obs.metrics.SweepObserver — `on_round` records of kind
    "fuzz_round" (explore's round schema + corpus_size/new_crash_codes),
    `on_done` with the final result; hooks ride the harvest the loop
    already blocks on.

    Returns a dict — explore()'s schema (seeds_run/rounds/
    distinct_schedules/new_per_round/saturated/crashes/
    crash_first_seed_by_code — that key keeps explore()'s contract of
    SEED-ALONE repro handles, so it only records crashes from unmutated
    bootstrap lanes; a crash first seen on a mutated lane appears only in
    crash_repros, whose (seed, knobs) pair is its real handle) plus:
      crash_repros      {code: {seed, round, knobs, script}} full handles
      corpus_size       corpus entries at the end
      mutation_ops      {operator name: times applied}
      minimized         {code: minimize_knobs info} when minimize=True
      targeted          (ldfi runs only) {supports, truncated_supports,
                        lanes_run, admitted} — the lineage arm's ledger
    """
    enable_persistent_cache()
    plan = KnobPlan.from_runtime(rt, dup_slots=dup_slots)
    pool = None
    targeted_total = 0
    targeted_yield_total = 0
    if ldfi is not None:
        if rt.cfg.trace_cap <= 0:
            raise ValueError(
                "fuzz(ldfi=...) needs the flight recorder compiled in "
                "(cfg.trace_cap > 0): support extraction walks lineage "
                "rings — there is nothing to aim without them")
        from ..obs.support import extract_support
        from .ldfi import SupportPool, synthesize
        pool = SupportPool()
    op_hist = np.zeros(N_MUT_OPS, np.int64)
    # cumulative coverage-YIELD attribution (vs op_hist's application
    # counts): admissions credited to the admitted lane's last applied
    # operator, "+1" slot = base/untouched lanes (search/corpus.py)
    yield_hist = np.zeros(N_MUT_OPS + 1, np.int64)
    if verify_resume is None:
        verify_resume = _env_verify_resume()
    store = buckets = None
    round_start = 0
    dry = 0
    wall_prior = 0.0
    if corpus_dir is not None:
        from ..service.buckets import CrashBuckets
        from ..service.store import CorpusStore, store_signature
        store = CorpusStore(corpus_dir,
                            signature=store_signature(rt, plan))
        # the r13 shard↔worker mapping numerically overlaps plain
        # worker ids — refuse a namespace a shard GROUP's state already
        # claims (see CorpusStore.claimed_namespaces / DESIGN §15)
        owner = store.claimed_namespaces().get(worker_id)
        if owner is not None and owner != f"worker w{worker_id}":
            from ..service.store import StoreMismatch
            raise StoreMismatch(
                f"worker namespace {worker_id} is already owned by "
                f"{owner} in this corpus dir — a mesh-sharded group's "
                "shards occupy worker_id*shards+s; pick a worker_id "
                "outside every group's range (DESIGN §15)")
        buckets = CrashBuckets(store)
        # the triage plane's read side needs the scenario row table to
        # attribute coverage/buckets to recipe families without a
        # Runtime (service/triage.py); write-once, identical bytes
        # from every worker
        store.write_triage_rows(plan)
        if corpus is None:
            corpus = store.load_corpus(
                plan, worker_id=worker_id, rng_seed=rng_seed,
                fresh_frac=fresh_frac,
                div_bonus=1.0 if div_bonus is None else div_bonus,
                lat_bonus=0.0 if lat_bonus is None else lat_bonus,
                burst_bonus=0.0 if burst_bonus is None else burst_bonus)
        else:
            if corpus.worker_id != worker_id:
                # a mismatched namespace would persist a worker state
                # whose entry order points at files sync never writes —
                # an unresumable store; fail before touching the dir
                raise ValueError(
                    f"corpus.worker_id={corpus.worker_id} != "
                    f"fuzz(worker_id={worker_id}): a durable campaign's "
                    "corpus must mint ids in its worker's namespace "
                    "(build it with Corpus(..., worker_id=) or let "
                    "fuzz load it from the store)")
            corpus.track_evictions = True
        ws = store.load_worker_state(worker_id)
        round_start = int(ws.get("rounds_done", 0))
        dry = int(ws.get("dry", 0))
        wall_prior = float(ws.get("wall_s", 0.0))
        if ws.get("op_hist"):
            op_hist[:] = np.asarray(ws["op_hist"], np.int64)
        if ws.get("op_yield"):
            yield_hist[:] = np.asarray(ws["op_yield"], np.int64)
        if ws.get("targeted_yield") is not None and ldfi is not None:
            # the support pool itself is NOT persisted — a resumed ldfi
            # campaign re-harvests green supports (cheap, a few host
            # walks); only the cumulative admission ledger survives
            targeted_yield_total = int(ws["targeted_yield"])
    if corpus is None:
        corpus = Corpus(plan, rng=np.random.default_rng(rng_seed),
                        fresh_frac=fresh_frac,
                        div_bonus=1.0 if div_bonus is None else div_bonus,
                        lat_bonus=0.0 if lat_bonus is None else lat_bonus,
                        burst_bonus=(0.0 if burst_bonus is None
                                     else burst_bonus))
    else:
        # an explicit div_bonus/lat_bonus/burst_bonus must win over a
        # passed-in corpus's setting — silently keeping the old value
        # would skew any with-vs-without energy comparison run through
        # these args
        if div_bonus is not None:
            corpus.div_bonus = float(div_bonus)
        if lat_bonus is not None:
            corpus.lat_bonus = float(lat_bonus)
        if burst_bonus is not None:
            corpus.burst_bonus = float(burst_bonus)
    master = jax.random.PRNGKey(np.uint32(rng_seed ^ 0x5EED5EED))

    def launch(r):
        """Schedule + mutate + dispatch one round without blocking on
        results (run_fused and the knob kernels are all async)."""
        # explicit mod-2^32 arithmetic: a large worker_id/base_seed wraps
        # deterministically on every numpy instead of overflowing arange
        lane0 = (base_seed + worker_id * WORKER_SEED_STRIDE
                 + r * batch) % (1 << 32)
        seeds = (np.arange(batch, dtype=np.uint64)
                 + np.uint64(lane0)).astype(np.uint32)
        targeted = np.zeros(batch, bool)
        if r == 0 or len(corpus) == 0:
            knobs_dev = {k: v for k, v in plan.base_batch(batch).items()}
            ids = np.full(batch, -1, np.int64)
            hist = None
            last_op = np.full(batch, -1, np.int64)
        else:
            parents, ids = corpus.schedule(batch)
            key = jax.random.fold_in(master, np.uint32(r))
            tvecs, tseeds = [], []
            if pool is not None and len(pool):
                tvecs, tseeds = synthesize(
                    plan, pool, min(batch, max(1, int(batch * ldfi.frac))),
                    max_cuts=ldfi.max_cuts, lead=ldfi.lead,
                    rank_cap=ldfi.rank_cap, with_seeds=True)
            if tvecs:
                # the lineage arm: targeted vectors ride the LAST T
                # lanes. The masked mutate (the shard driver's kernel —
                # module-level jit, traced once per shape) leaves those
                # lanes' parents untouched so the havoc histogram and
                # last-op attribution count ONLY real mutants; the
                # synthesized rows then overwrite them host-side and
                # plan.apply bounds-checks them like any mutant — zero
                # new compiled programs for a targeted round
                tn = len(tvecs)
                mask = np.ones(batch, bool)
                mask[batch - tn:] = False
                knobs_dev, hist, last_op = plan.mutate_masked(
                    parents, key, mask, havoc=havoc)
                knobs_host = {k: np.asarray(v).copy()
                              for k, v in knobs_dev.items()}
                tb = KnobPlan.stack(tvecs)
                for k in knobs_host:
                    knobs_host[k][batch - tn:] = tb[k]
                knobs_dev = knobs_host
                ids = ids.copy()
                ids[batch - tn:] = -1     # no havoc parent to reward
                targeted[batch - tn:] = True
                # pin each targeted lane to the green seed its cut was
                # aimed at: edge instants are seed-specific, so the cut
                # only lands inside the trajectory it was extracted from
                for j, ts_seed in enumerate(tseeds):
                    if ts_seed is not None:
                        seeds[batch - tn + j] = np.uint32(ts_seed)
            else:
                knobs_dev, hist, last_op = plan.mutate(parents, key,
                                                       havoc=havoc)
        state = plan.apply(rt.init_batch(seeds), knobs_dev)
        if fused:
            state = rt.run_fused(state, max_steps, chunk)
        else:
            state, _ = rt.run(state, max_steps, chunk)
        return seeds, ids, knobs_dev, hist, last_op, targeted, state

    def harvest(launched):
        """Block on one round. Transfers the [B] hash/crash lanes plus
        the knob batch (kilobytes — the corpus needs per-lane
        attribution, unlike explore()'s O(distinct) digest) and, when
        the build compiles the prefix sketch in, the [B, S] sketch
        batch (also kilobytes — the divergence-depth signal)."""
        seeds, ids, knobs_dev, hist, last_op, targeted, state = launched
        knobs_host = {k: np.asarray(v) for k, v in knobs_dev.items()}
        hashes = stats.sched_hash_u64(state)
        sk = np.asarray(state.cov_sketch)
        sketches = sk if sk.ndim == 2 and sk.shape[1] > 0 else None
        # tail-latency signal (r16): per-lane e2e p99 for corpus energy
        # + the round's merged brief for telemetry — None on builds
        # without the latency plane (one [B] + one O(buckets)
        # transfer); the brief only when something will consume it
        lat_p99 = stats.lane_e2e_p99(state)
        lat_brief = (stats.latency_brief(state)
                     if lat_p99 is not None
                     and (observer is not None or store is not None)
                     else None)
        # transient-spike signal (r21): per-lane deepest per-window
        # spike for corpus energy — None on builds without the series
        # plane (one [B] transfer)
        burst = stats.lane_burst(state)
        if hist is not None:
            op_hist[:] += np.asarray(hist)
        return (seeds, ids, knobs_host, hashes,
                np.asarray(state.crashed), np.asarray(state.crash_code),
                hist is not None, np.asarray(last_op), sketches, state,
                lat_p99, lat_brief, burst, targeted)

    def verified(harvested):
        """The run-twice resume guard (verify_resume): re-dispatch the
        SAME (seeds, knobs) batch — the knob batch is never donated —
        until two consecutive invocations agree on the authoritative
        outputs (utils.verify.agree_twice: contains the persistent-
        cache first-invocation corruption, raises on real
        nondeterminism)."""
        from ..utils.verify import agree_twice

        def key_of(h):
            hashes, crashed, codes, sketches, lat_p99, burst = \
                h[3], h[4], h[5], h[8], h[10], h[12]
            return (hashes.tobytes(), crashed.tobytes(), codes.tobytes(),
                    None if sketches is None else sketches.tobytes(),
                    None if lat_p99 is None else lat_p99.tobytes(),
                    None if burst is None else burst.tobytes())

        def again(prev):
            seeds, ids, knobs_host = prev[0], prev[1], prev[2]
            mutated, last_op = prev[6], prev[7]
            state = plan.apply(rt.init_batch(seeds), knobs_host)
            if fused:
                state = rt.run_fused(state, max_steps, chunk)
            else:
                state, _ = rt.run(state, max_steps, chunk)
            return harvest((seeds, ids, knobs_host,
                            None if not mutated else
                            np.zeros(N_MUT_OPS, np.int64), last_op,
                            prev[13], state))

        return agree_twice(harvested, again, key_of,
                           what="first post-resume campaign round")

    # under a durable store, `seen` starts at the campaign's cumulative
    # coverage (this worker's view) so dry-detection and the distinct
    # count continue across resumes instead of restarting from zero
    seen: set[int] = corpus.coverage_keys() if store is not None else set()
    crashes: dict[int, int] = {}
    repros: dict[int, dict] = {}
    opened_buckets: list[str] = []
    n_crashed = 0
    new_per_round: list[int] = []
    rounds = 0
    # the speculative pipeline schedules round r+1's parents BEFORE round
    # r's harvest; a durable campaign must schedule AFTER the sync point
    # (or the persisted rng state couldn't replay the draw), so the store
    # forces the serial loop — multi-worker campaigns restore the overlap
    speculate = pipeline and fused and store is None and ldfi is None
    t0 = time.perf_counter()
    pending = (launch(round_start)
               if round_start < max_rounds and dry < dry_rounds else None)
    verify_round = (round_start if verify_resume and store is not None
                    and round_start > 0 else None)
    for r in range(round_start, max_rounds):
        if pending is None:
            break
        nxt = (launch(r + 1) if speculate and r + 1 < max_rounds else None)
        harvested = harvest(pending)
        if r == verify_round:
            harvested = verified(harvested)
        (seeds, ids, knobs_host, hashes, crashed, codes, mutated,
         last_op, sketches, state, lat_p99, lat_brief, burst,
         targeted) = harvested
        rounds += 1
        cstats = corpus.observe(knobs_host, seeds, hashes, crashed, codes,
                                ids, r, sketches=sketches,
                                last_op=last_op, lat_p99=lat_p99,
                                burst=burst,
                                origin=targeted if ldfi is not None
                                else None)
        yield_hist[:] += cstats["op_yield"]
        if ldfi is not None:
            targeted_total += int(targeted.sum())
            targeted_yield_total += int(cstats.get("targeted_yield", 0))
            if len(pool) < ldfi.lanes:
                # harvest green supports: UNMUTATED lanes (bootstrap or
                # havoc no-ops; last_op < 0, not targeted) that did not
                # crash — the undisturbed trajectories whose success
                # support is worth cutting. Bounded: the pool stops
                # growing at ldfi.lanes supports, so the per-lane host
                # walks are a one-time cost, not a per-round tax
                for i in range(len(seeds)):
                    if len(pool) >= ldfi.lanes:
                        break
                    if (bool(crashed[i]) or int(last_op[i]) >= 0
                            or bool(targeted[i])):
                        continue
                    sup = extract_support(
                        state, int(i), witness=ldfi.witness,
                        replay=ldfi.replay, rt=rt, seed=int(seeds[i]),
                        knobs=KnobPlan.lane(knobs_host, int(i)))
                    if sup is not None:
                        pool.add(sup, seed=int(seeds[i]))
        for i in np.nonzero(crashed)[0]:
            c = int(codes[i])
            if not mutated:     # seed-alone handles: bootstrap lanes only
                crashes.setdefault(c, int(seeds[i]))
            if c not in repros:
                kn = KnobPlan.lane(knobs_host, int(i))
                repros[c] = dict(seed=int(seeds[i]), round=r, knobs=kn,
                                 script=plan.to_scenario(kn).describe())
        if buckets is not None and crashed.any():
            # dedup crashes into causal-fingerprint buckets: one
            # representative lane per distinct (crash code, origin) per
            # round keeps the host-side explain work bounded (the chain
            # walk is O(trace_cap) per lane; codes, not lanes, are the
            # cheap first partition — the fingerprint then splits bugs
            # sharing a code across rounds). The origin axis matters:
            # targeted lanes ride the batch TAIL, so a code-only dedup
            # would always hand representation to an earlier havoc lane
            # and the targeted arm could never open a bucket it earned
            coded: set[tuple] = set()
            for i in np.nonzero(crashed)[0]:
                c = (int(codes[i]),
                     bool(targeted[int(i)]) if ldfi is not None else False)
                if c in coded:
                    continue
                coded.add(c)
                key, opened = buckets.observe_lane(
                    state, int(i), seed=int(seeds[i]),
                    knobs=KnobPlan.lane(knobs_host, int(i)),
                    round_no=r, worker_id=worker_id,
                    last_op=int(last_op[int(i)]),
                    origin=(("targeted" if targeted[int(i)] else "havoc")
                            if ldfi is not None else None))
                if opened:
                    opened_buckets.append(key)
        n_crashed += int(crashed.sum())
        fresh = set(hashes.tolist()) - seen
        seen |= fresh
        new_per_round.append(len(fresh))
        dry = dry + 1 if not fresh else 0
        if observer is not None:
            rec = dict(
                kind="fuzz_round", round=rounds, batch=batch,
                seeds_run=rounds * batch, new_schedules=len(fresh),
                distinct_total=len(seen), crashes=n_crashed,
                corpus_size=cstats["size"],
                new_crash_codes=cstats["new_crash_codes"],
                # coverage-yield attribution (r15): the round's
                # admissions credited to the operator that produced
                # each admitted mutant (sums to `admitted`; "base" =
                # untouched lanes), plus where the corpus's mutation
                # budget sits — the fuzzer-effectiveness half of the
                # profiler plane
                admitted=cstats["new"],
                op_yield={YIELD_NAMES[i]: int(cstats["op_yield"][i])
                          for i in range(len(YIELD_NAMES))},
                corpus_energy=corpus.energy_summary(),
                dry_rounds=dry, wall_s=time.perf_counter() - t0)
            if ldfi is not None:
                # the lineage arm's round ledger: lanes given to
                # targeted vectors, their admissions (the slice of
                # `admitted` that was aimed, not sprayed), and the
                # support pool's size/honesty
                rec.update(targeted=int(targeted.sum()),
                           targeted_yield=int(
                               cstats.get("targeted_yield", 0)),
                           support_pool=len(pool))
            if lat_brief is not None:
                # the round's tail (obs/metrics.py schema): merged e2e
                # p50/p99 estimates + SLO misses for this round's batch
                rec.update(_lat_fields(lat_brief))
            if buckets is not None:
                rec["buckets_opened"] = len(opened_buckets)
            if sketches is not None:
                # divergence depth of this round's mutants (median
                # first-divergence slot vs the consensus prefix): how
                # early the round's schedule rewiring bit, off the
                # sketch transfer the corpus already paid for
                rec["div_slot_p50"] = int(np.median(
                    stats.first_divergence_slots(sketches)))
            observer.on_round(rec)
        if store is not None and (
                (r + 1 - round_start) % sync_every == 0
                or dry >= dry_rounds or r + 1 == max_rounds):
            # the durability point: after observe/buckets, BEFORE the
            # next round's schedule draw — a resume restores the rng
            # state saved here and replays that draw identically.
            # The campaign-timeline row goes FIRST: a kill between the
            # two re-runs the round and re-appends an identical row
            # (deduped by rounds_done in campaign_timeline), so the
            # durable timeline has no gaps and no double counts
            wall_now = wall_prior + time.perf_counter() - t0
            mrow = dict(
                t=time.time(), worker=worker_id, rounds_done=r + 1,
                coverage=len(seen), seeds_run=(r + 1) * batch,
                crashes=n_crashed, corpus_size=len(corpus),
                dry=dry, wall_s=round(wall_now, 3),
                op_yield=[int(x) for x in yield_hist])
            if ldfi is not None:
                mrow["targeted_yield"] = targeted_yield_total
            if lat_brief is not None:
                # the durable p99 timeline (campaign_report folds the
                # rows into a p99_curve): this sync's round-batch tail
                mrow.update(_lat_fields(lat_brief))
            store.append_metrics(worker_id, mrow)
            store.sync(corpus, worker_id, rounds_done=r + 1, dry=dry,
                       op_hist=op_hist, op_yield=yield_hist,
                       wall_s=wall_now,
                       targeted_yield=(targeted_yield_total
                                       if ldfi is not None else None))
        if dry >= dry_rounds:
            break
        pending = nxt if nxt is not None else (
            launch(r + 1) if r + 1 < max_rounds else None)

    result = dict(
        seeds_run=rounds * batch,
        rounds=rounds,
        distinct_schedules=len(seen),
        new_per_round=new_per_round,
        saturated=dry >= dry_rounds,
        crash_first_seed_by_code=crashes,
        crashes=n_crashed,
        crash_repros=repros,
        corpus_size=len(corpus),
        mutation_ops={OP_NAMES[i]: int(op_hist[i])
                      for i in range(N_MUT_OPS)},
        # campaign-cumulative coverage yield by operator (the
        # effectiveness view op_hist's application counts cannot give:
        # an operator that runs constantly but never buys coverage
        # shows up here as 0)
        mutation_yield={YIELD_NAMES[i]: int(yield_hist[i])
                        for i in range(len(YIELD_NAMES))},
        corpus_energy=corpus.energy_summary(),
    )
    if ldfi is not None:
        result["targeted"] = dict(
            supports=len(pool), truncated_supports=pool.truncated,
            lanes_run=targeted_total, admitted=targeted_yield_total)
    if store is not None:
        result.update(
            corpus_dir=store.dir,
            rounds_done_total=round_start + rounds,
            buckets_opened=opened_buckets,
            buckets_total=len(store.bucket_keys()))
    if minimize and repros:
        from ..harness.minimize import minimize_knobs
        result["minimized"] = {}
        for c, rep in repros.items():
            try:
                minimal, info = minimize_knobs(rt, plan, rep["knobs"],
                                               rep["seed"], max_steps,
                                               chunk)
                result["minimized"][c] = dict(info, knobs=minimal)
            except Exception as e:  # noqa: BLE001 - repro handle still stands
                result["minimized"][c] = dict(error=f"{type(e).__name__}: {e}")
        if buckets is not None:
            # attach the shrunk fault script to the buckets this run
            # opened (matched by crash code — the repro/minimize tables
            # are code-keyed): the bucket's canonical (seed, knobs) repro
            # stays untouched, the minimal script is reporting
            for key in buckets.new_keys:
                rec_b = store.load_bucket(key)
                mini = result["minimized"].get(int(rec_b["crash_code"]))
                if mini and "script" in mini:
                    rec_b["minimized"] = {
                        k: v for k, v in mini.items() if k != "knobs"}
                    store.write_bucket(key, rec_b)
    if observer is not None:
        observer.on_done(dict(
            kind="done", distinct_total=len(seen),
            wall_s=time.perf_counter() - t0,
            **{k: v for k, v in result.items()
               if k not in ("crash_repros", "minimized")}))
    return result
