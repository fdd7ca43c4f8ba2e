"""Device nanoseconds per simulated event in the step's `step.emit` scope
(phase 4: the emissions written into the event table, and the stat
counters): the traced window's device-0 self time of the ops the program
maps to it (benchmark/phases.py)."""

from benchmark.phases import ns_per_event


def read(run):
    return ns_per_event(run, "step.emit")
