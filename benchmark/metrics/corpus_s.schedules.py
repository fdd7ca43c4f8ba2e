"""Median over the window's fuzz rounds of the corpus layer's host seconds
(search/corpus.py): the `schedule` and `admit` stages of the record's
`host_s`. The round in which the trace was written out is left out
(benchmark/phases.py)."""

from benchmark.phases import round_median


def read(run):
    return round_median(run, lambda s: s["schedule"] + s["admit"])
