"""Median over the window's fuzz rounds of the host's own seconds in a round:
the sum of the record's `host_s` stages less `wait`. A round takes about
this plus device_wait_s.schedules; where the wait is near 0 the host sets
the pace. The round in which the trace was written out is left out
(benchmark/phases.py)."""

from benchmark.phases import round_median


def read(run):
    return round_median(run, lambda s: sum(s.values()) - s["wait"])
