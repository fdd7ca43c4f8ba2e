"""Device nanoseconds per simulated event in the step's `step.supervisor` scope
(phase 2: the supervisor op, the Lamport clock and span accumulation): the
traced window's device-0 self time of the ops the program maps to it
(benchmark/phases.py)."""

from benchmark.phases import ns_per_event


def read(run):
    return ns_per_event(run, "step.supervisor")
