"""(Re)generate the committed regression corpus (tests/data/
regression_corpus/) — the OSS-Fuzz-style gate that
tests/test_triage.py::TestRegressionCorpus replays.

Runs a small DETERMINISTIC durable fuzz campaign per flagship regime and
freezes the resulting corpus dir (entries + causal-fingerprint crash
buckets + worker state) plus a REGRESSION.json sidecar naming the
runtime factory and replay budget. Re-run this ONLY when the store
signature legitimately moves (a new knob dimension, a structural change
to a flagship) — the whole point of the gate is that buckets keep
reproducing across unrelated changes. (Last re-frozen at r23: the
simconfig-v8 bump — the attribution plane's structural span_attr gate —
rejects pre-r23 corpus dirs with StoreMismatch, so both campaigns were
regenerated; the trajectories themselves are bit-identical to the r21
freeze, per the golden-equivalence gates. The r21 freeze did the same
for the v7 windowed-telemetry bump.)

    JAX_PLATFORMS=cpu python scripts/make_regression_corpus.py [name ...]
"""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from madsim_tpu import fuzz  # noqa: E402
from madsim_tpu.service.worker import resolve_factory  # noqa: E402
from madsim_tpu.service.store import CorpusStore  # noqa: E402

BASE = os.path.join(REPO, "tests", "data", "regression_corpus")

CAMPAIGNS = {
    # the r17 gray-failure flagship: Percolator-lite under the composed
    # fault mix (asym cut, drifting clocks, slow disk, torn kill)
    "grayfail_mix": dict(
        factory="madsim_tpu.models.workloads:grayfail",
        factory_kwargs=dict(recipe="mix"),
        max_steps=30_000, batch=64, max_rounds=4, rng_seed=1),
    # the r19 connection-fault flagship: minipg exactly-once transactions
    # with incarnation guards compiled to the pre-r19 behavior, under the
    # reset+dup storm (the honest red control — these buckets ARE the
    # stale-segment corruptions the guard exists to prevent)
    "connfault_mix": dict(
        factory="madsim_tpu.models.workloads:connfault",
        factory_kwargs=dict(recipe="mix"),
        max_steps=30_000, batch=64, max_rounds=4, rng_seed=1),
}

names = sys.argv[1:] or sorted(CAMPAIGNS)
for name in names:
    spec = CAMPAIGNS[name]
    dest = os.path.join(BASE, name)
    shutil.rmtree(dest, ignore_errors=True)
    rt = resolve_factory(spec["factory"])(**spec["factory_kwargs"])
    res = fuzz(rt, max_steps=spec["max_steps"], batch=spec["batch"],
               max_rounds=spec["max_rounds"],
               dry_rounds=spec["max_rounds"] + 1,
               chunk=512, corpus_dir=dest, rng_seed=spec["rng_seed"])
    store = CorpusStore(dest, create=False)
    keys = store.bucket_keys()
    assert keys, f"{name}: campaign found no crash buckets to gate on"
    # freeze the store MINIMAL: the triage/ subdir (ROWS.json, snapshots)
    # is derived state the r18+ fuzz writes on open — the committed
    # fixture stays rowless so tests/test_triage.py can exercise the
    # rows-unknown attribution fallback against it, and triage output
    # never bloats the repo
    shutil.rmtree(os.path.join(dest, "triage"), ignore_errors=True)
    with open(os.path.join(dest, "REGRESSION.json"), "w") as f:
        json.dump(dict(
            factory=spec["factory"],
            factory_kwargs=spec["factory_kwargs"],
            dup_slots=2,
            max_steps=spec["max_steps"],
            buckets=keys,
            note=("frozen by scripts/make_regression_corpus.py; replayed "
                  "by tests/test_triage.py::TestRegressionCorpus"),
        ), f, indent=1)
    print(f"{dest}: {len(store.entry_names())} entries, "
          f"{len(keys)} buckets: {keys}")
