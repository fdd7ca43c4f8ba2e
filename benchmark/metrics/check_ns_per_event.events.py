"""Device nanoseconds per simulated event in the step's `step.check` scope
(phase 5: the end conditions, the model's invariant and halt_when): the
traced window's device-0 self time of the ops the program maps to it
(benchmark/phases.py)."""

from benchmark.phases import ns_per_event


def read(run):
    return ns_per_event(run, "step.check")
