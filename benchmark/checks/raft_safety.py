"""Plain reference: Raft's safety guarantees, read off each lane's final state.

Imports nothing of madsim_tpu. It takes the node-state arrays a run ended
with and checks, lane by lane, what the Raft paper (Figure 3) promises:

  two_leaders     Election Safety: no two peers lead in the same term.
  log_mismatch    State Machine Safety / Log Matching: any two peers agree,
                  entry for entry (term and every entry field), on the
                  prefix both have committed.
  commit_past_log a peer's commit index never passes its own log.

The configurations this reference serves never compact their logs, so a
non-zero `snap_len` is itself reported (`log_compacted`): the prefix check
below would not be sound for a slid window.
"""

from __future__ import annotations

import numpy as np

LEADER = 2


def leaves(cfg: dict) -> list[str]:
    """The node-state leaves this check reads."""
    fields = cfg["raft"]["fields"]
    return (["role", "term", "log_term", "log_len", "commit", "snap_len"]
            + [f"log_{f}" for f in fields])


def check(cfg: dict, ns: dict) -> dict[str, np.ndarray]:
    """Per-lane violation flags, each bool[lanes], for node-state arrays
    `ns[leaf]` shaped [lanes, nodes, ...]."""
    peers = int(cfg["raft"]["peers"])
    fields = cfg["raft"]["fields"]
    role = ns["role"][:, :peers]
    term = ns["term"][:, :peers]
    commit = ns["commit"][:, :peers].astype(np.int64)
    log_len = ns["log_len"][:, :peers].astype(np.int64)
    cols = [ns["log_term"][:, :peers]] + [ns[f"log_{f}"][:, :peers]
                                          for f in fields]
    L = cols[0].shape[-1]
    lanes = role.shape[0]
    two_leaders = np.zeros(lanes, bool)
    mismatch = np.zeros(lanes, bool)
    pos = np.arange(L)[None, :]
    for i in range(peers):
        for j in range(i + 1, peers):
            both_lead = (role[:, i] == LEADER) & (role[:, j] == LEADER)
            two_leaders |= both_lead & (term[:, i] == term[:, j])
            upto = np.minimum(np.minimum(commit[:, i], commit[:, j]), L)
            in_prefix = pos < upto[:, None]
            differ = np.zeros((lanes, L), bool)
            for c in cols:
                differ |= c[:, i] != c[:, j]
            mismatch |= (differ & in_prefix).any(axis=1)
    return dict(
        two_leaders=two_leaders,
        log_mismatch=mismatch,
        commit_past_log=(commit > log_len).any(axis=1),
        log_compacted=(ns["snap_len"][:, :peers] != 0).any(axis=1),
    )
