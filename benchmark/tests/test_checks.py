"""The plain references under benchmark/checks on hand-made answers.

    python -m pytest benchmark/tests/test_checks.py -q
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import load_module  # noqa: E402

lin = load_module("checks", "kv_linearizable")
raft = load_module("checks", "raft_safety")
PUT, GET = lin.PUT, lin.GET

# (kind, val, inv, resp) rows of one register, and the verdict
REGISTER = [
    ("empty", [], True),
    ("read of the initial 0", [(GET, 0, 0, 5)], True),
    ("write then read", [(PUT, 3, 0, 5), (GET, 3, 6, 9)], True),
    ("stale read after a write", [(PUT, 3, 0, 5), (GET, 0, 6, 9)], False),
    ("read overlapping the write may see either",
     [(PUT, 3, 0, 10), (GET, 0, 2, 4), (GET, 3, 5, 7)], True),
    ("reads out of order", [(PUT, 3, 0, 10), (GET, 3, 2, 4),
                            (GET, 0, 5, 7)], False),
    ("unanswered write may have happened", [(PUT, 4, 0, -1),
                                            (GET, 4, 5, 7)], True),
    ("unanswered write may not have happened", [(PUT, 4, 0, -1),
                                                (GET, 0, 5, 7)], True),
    ("a value nobody wrote", [(PUT, 4, 0, 3), (GET, 5, 5, 7)], False),
    ("unanswered read constrains nothing", [(PUT, 4, 0, 3),
                                            (GET, 9, 5, -1)], True),
    ("lost write", [(PUT, 1, 0, 2), (PUT, 2, 3, 4), (GET, 1, 5, 6)], False),
]


@pytest.mark.parametrize("ops,ok", [(o, k) for _, o, k in REGISTER],
                         ids=[n for n, _, _ in REGISTER])
def test_register(ops, ok):
    cols = list(zip(*ops)) if ops else [[], [], [], []]
    assert lin.register_ok(*cols) is ok


def test_history_splits_by_key_and_skips_unstarted():
    op = np.array([PUT, GET, PUT, GET, GET])
    key = np.array([0, 0, 1, 1, 1])
    val = np.array([1, 1, 2, 0, 7])
    inv = np.array([0, 3, 0, 3, -1])        # the last op never started
    resp = np.array([2, 4, 2, 4, -1])
    assert not lin.history_ok(op, key, val, inv, resp)   # key 1: stale
    val[3] = 2
    assert lin.history_ok(op, key, val, inv, resp)


def lanes(**over):
    """Two lanes of a 3-peer cluster; lane 0 is sound."""
    L = 4
    ns = dict(role=np.array([[2, 0, 0]] * 2), term=np.array([[2, 2, 1]] * 2),
              log_term=np.ones((2, 3, L), int), log_cmd=np.arange(
                  2 * 3 * L).reshape(2, 3, L) % L,
              log_len=np.full((2, 3), 3), commit=np.full((2, 3), 2),
              snap_len=np.zeros((2, 3), int))
    for k, (lane, idx, v) in over.items():
        ns[k] = ns[k].copy()
        ns[k][(lane,) + idx] = v
    return ns


CFG = {"raft": {"peers": 3, "fields": ["cmd"]}}
SAFETY = [
    ("sound", {}, None),
    ("two leaders in term 2", dict(role=(1, (1,), 2)), "two_leaders"),
    ("leaders of different terms", dict(role=(1, (2,), 2)), None),
    ("committed entry differs", dict(log_cmd=(1, (2, 1), 9)),
     "log_mismatch"),
    ("uncommitted entry differs", dict(log_cmd=(1, (2, 2), 9)), None),
    ("commit past the log", dict(commit=(1, (0,), 4)), "commit_past_log"),
    ("compacted log", dict(snap_len=(1, (1,), 1)), "log_compacted"),
]


@pytest.mark.parametrize("over,flag", [(o, f) for _, o, f in SAFETY],
                         ids=[n for n, _, _ in SAFETY])
def test_raft_safety(over, flag):
    out = raft.check(CFG, lanes(**over))
    for name, f in out.items():
        assert not f[0], name
        assert bool(f[1]) is (name == flag), name
