"""Share of the traced slice (host clock, profiler start to stop) in which
no operation ran on the device: 1 minus the union of the device's operation
intervals over the slice, averaged over the chips."""

from bench import readings


def read(rec):
    busy = readings.busy_seconds(rec)
    if busy is None or not rec.get("slice"):
        return None
    window = rec["slice"]["t1"] - rec["slice"]["t0"]
    return readings.share(window - busy, window)
