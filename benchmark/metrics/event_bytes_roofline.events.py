"""Share of the event table's bandwidth roofline: each event's row is read
once and written once in any implementation, so the least time the chip
could take is events x 2 x row bytes over peak HBM bandwidth. Row bytes
are (5 + payload_words) x 4, from the configuration, whatever type the
program keeps the table in. Over the device's busy time."""

from benchmark.peaks import peak


def read(run):
    t = run["trace"]
    ev = (run["traced_counts"] or {}).get("events", 0)
    if t is None or ev <= 0 or t["busy_s"] <= 0:
        return None
    row = (5 + run["config"]["payload_words"]) * 4
    least = ev * 2 * row / peak(run["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least / t["busy_s"]
