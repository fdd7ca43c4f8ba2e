"""Median over the window's fuzz rounds of the seconds the search loop waited
on the round's device result: the `wait` stage of the record's `host_s`. The
round in which the trace was written out is left out (benchmark/phases.py)."""

from benchmark.phases import round_median


def read(run):
    return round_median(run, lambda s: s["wait"])
