"""Device nanoseconds per simulated event outside every `step.*` scope: busy
time less the phases' ops (the while loops' own time, copies, the loop
predicate, time between ops), so the phase metrics and this one sum to
device_ns_per_event.events (benchmark/phases.py)."""

from benchmark.phases import ns_per_event


def read(run):
    return ns_per_event(run, "")
