"""95th percentile of the fuzz rounds' wall seconds: the gaps between
consecutive `fuzz_round` records of each campaign (the first round's gap
runs from the campaign's start). A traced run leaves out the one round in
which the harness wrote its trace out."""

import numpy as np


def read(run):
    a, b = run["trace_stop"]
    gaps, prev = [], 0.0
    for r in run["records"].rounds:
        if r["round"] == 1:
            prev = 0.0
        gap = r["wall_s"] - prev
        prev = r["wall_s"]
        if not (r["t_host"] - gap < b and r["t_host"] > a):
            gaps.append(gap)
    if not gaps:
        return None
    return float(np.percentile(gaps, 95))
