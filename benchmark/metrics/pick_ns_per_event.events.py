"""Device nanoseconds per simulated event in the step's `step.pick` scope
(phase 1: the earliest eligible event, its tie-break (PCT, lineage,
duplicate delivery, the schedule hash) and the pop): the traced window's
device-0 self time of the ops the program maps to it (benchmark/phases.py)."""

from benchmark.phases import ns_per_event


def read(run):
    return ns_per_event(run, "step.pick")
