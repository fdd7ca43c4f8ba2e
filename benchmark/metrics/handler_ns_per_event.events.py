"""Device nanoseconds per simulated event in the step's `step.handler` scope
(phase 3: the protocol handler, the gray-failure reads and timer cancels):
the traced window's device-0 self time of the ops the program maps to it
(benchmark/phases.py)."""

from benchmark.phases import ns_per_event


def read(run):
    return ns_per_event(run, "step.handler")
