"""Plain reference: every client history of a KV run is linearizable.

Imports nothing of madsim_tpu. It reads the clients' recorded histories
straight from the final node-state arrays (`h_op`, `h_key`, `h_val`,
`h_inv`, `h_resp`, shaped [lanes, nodes, ops]) and decides linearizability
with its own search, per key (registers compose: a history is
linearizable iff each key's sub-history is). A register starts at 0; PUT
writes `val`; GET returns `val`. An op with `resp < 0` was never answered:
it may have taken effect at any point after its invocation, or never.

  nonlinearizable  the reference rejects the lane's history
  verdict_disagree the program's own checker said otherwise
"""

from __future__ import annotations

import numpy as np

PUT, GET = 1, 2
LEAVES = ["h_op", "h_key", "h_val", "h_inv", "h_resp"]


def leaves(cfg: dict) -> list[str]:
    return list(LEAVES)


def register_ok(kind, val, inv, resp) -> bool:
    """Wing & Gong search with memo on (ops left, register value)."""
    # an unanswered GET constrains nothing: it may simply never have run
    keep = [i for i in range(len(kind)) if not (kind[i] == GET and resp[i] < 0)]
    kind = [int(kind[i]) for i in keep]
    val = [int(val[i]) for i in keep]
    inv = [int(inv[i]) for i in keep]
    done = [int(resp[i]) for i in keep]
    n = len(kind)
    if n == 0:
        return True
    never = max(max(done), max(inv)) + 1
    end = [d if d >= 0 else never for d in done]
    answered = sum(1 << i for i in range(n) if done[i] >= 0)
    failed: set[tuple[int, int]] = set()

    def search(left: int, value: int) -> bool:
        if left & answered == 0:
            return True          # what is left was never answered: drop it
        if (left, value) in failed:
            return False
        first_end = min(end[i] for i in range(n) if left >> i & 1)
        for i in range(n):
            if not left >> i & 1 or inv[i] > first_end:
                continue
            rest = left & ~(1 << i)
            if kind[i] == PUT:
                if search(rest, val[i]):
                    return True
            elif val[i] == value and search(rest, value):
                return True
            if done[i] < 0 and search(rest, value):
                return True      # an unanswered PUT that never took effect
        failed.add((left, value))
        return False

    return search((1 << n) - 1, 0)


def history_ok(op, key, val, inv, resp) -> bool:
    started = inv >= 0
    op, key, val, inv, resp = (a[started] for a in (op, key, val, inv, resp))
    for k in np.unique(key):
        m = key == k
        if not register_ok(op[m], val[m], inv[m], resp[m]):
            return False
    return True


def check(cfg: dict, ns: dict) -> dict[str, np.ndarray]:
    """Per-lane flags; `ns` holds the history leaves, and `verdicts` (the
    program's own per-lane checker results) when the traffic recorded
    them."""
    lo = int(cfg["kv"]["n_raft"])
    hi = lo + int(cfg["kv"]["n_clients"])
    h = {k: ns[k][:, lo:hi].reshape(ns[k].shape[0], -1) for k in LEAVES}
    ok = np.array([history_ok(*(h[k][b] for k in LEAVES))
                   for b in range(h["h_op"].shape[0])], bool)
    out = dict(nonlinearizable=~ok)
    if "verdicts" in ns:
        out["verdict_disagree"] = ns["verdicts"].astype(bool) != ok
    return out
