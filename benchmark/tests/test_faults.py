"""The output check has to fail a broken timed path.

Each test skips the harness's look for a chip and drives the rest of a run
at a small size on the CPU, with the path under the harness broken, and
sees `correct` come out false. The faults are the ones each cell can have:
a step that returns its state unchanged, half of the batch left out, and an
answer altered where it is produced. The configuration's control (a 2-of-5
quorum) fails too, and a sound run passes. Run as a program on the chip
(below), it plants the first two faults under a cell at the cell's own
size and runs the control on three seeds (PERF.md).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

# each case: a cell of BENCHMARK.json and its traffic cut to a small size
SMALL = {
    "madraft5.sweep": dict(batch=32, steps=512, replay_lanes=2),
    "madraft5.fuzz": dict(batch=32, steps=512, rounds=2, replay_lanes=32,
                          cpu_replay_lanes=2),
}


def go(case, seed=2_200_000_123, control=0, small=True, seconds=0.5):
    return run.main(["--workload", case, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", "0", "--control",
                     str(control)], require_tpu=not small,
                    overrides=SMALL[case] if small else None)


@pytest.fixture
def runtime():
    from madsim_tpu.runtime.runtime import Runtime
    return Runtime


def unchanged(Runtime, monkeypatch):
    monkeypatch.setattr(Runtime, "run_fused",
                        lambda self, state, *a, **k: state)


def half(Runtime, monkeypatch):
    """Only the first half of the lanes runs; the rest come back as they
    went in."""
    import jax
    fused = Runtime.run_fused

    def run_half(self, state, *a, **k):
        n = state.halted.shape[0] // 2
        rest = jax.tree.map(lambda x: np.asarray(x)[n:], state)
        done = fused(self, jax.tree.map(lambda x: x[:n], state), *a, **k)
        return jax.tree.map(
            lambda d, r: jax.numpy.concatenate([d, r]), done, rest)
    monkeypatch.setattr(Runtime, "run_fused", run_half)


def altered(Runtime, monkeypatch):
    """An answer changed where it is produced: node 0's first log entry,
    a committed command in most lanes."""
    fused = Runtime.run_fused

    def run_altered(self, state, *a, **k):
        out = fused(self, state, *a, **k)
        ns = dict(out.node_state)
        ns["log_cmd"] = ns["log_cmd"].at[:, 0, 0].add(1)
        return out.replace(node_state=ns)
    monkeypatch.setattr(Runtime, "run_fused", run_altered)


CASES = [(w, f) for w in SMALL for f in (unchanged, half, altered)]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__}" for w, f in CASES])
def test_fault_fails_the_check(workload, fault, runtime, monkeypatch):
    fault(runtime, monkeypatch)
    out = go(workload)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", list(SMALL))
def test_control_fails_the_check(workload):
    out = go(workload, control=1)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", list(SMALL))
def test_sound_run_passes(workload):
    out = go(workload)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


if __name__ == "__main__":
    # on the chip, `python3 benchmark/tests/test_faults.py <cell>`: the
    # first two faults planted under the cell at its own size, then the
    # control on three seeds, short windows, all in one process (it holds
    # the chip)
    from madsim_tpu.runtime.runtime import Runtime
    cell, seed = sys.argv[1], 2_230_000_000
    for f in (unchanged, half, None, None, None):
        mp = pytest.MonkeyPatch()
        if f is not None:
            f(Runtime, mp)
        seed += 1
        out = go(cell, seed=seed, small=False, seconds=5,
                 control=int(f is None))
        mp.undo()
        bad = {k: c["value"] for k, c in out["checks"].items() if c["value"]}
        print(f"{'control' if f is None else f.__name__} {cell} seed={seed} "
              f"correct={out['correct']} attempted={out['attempted']} "
              f"failed={out['failed']} nonzero={bad}", flush=True)
