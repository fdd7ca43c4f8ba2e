"""Device busy nanoseconds per simulated event in the traced window: the
step program's cost per event, whatever the host does around it."""


def read(run):
    t = run["trace"]
    ev = (run["traced_counts"] or {}).get("events", 0)
    if t is None or ev <= 0 or t["busy_s"] <= 0:
        return None
    return t["busy_s"] * 1e9 / ev
