"""Share of the decode step's device time spent in collectives (all-reduce,
all-gather and their kin) inside that program, averaged over the chips."""

from bench import readings
from bench import trace as trace_lib


def read(rec):
    t = readings.decode_seconds(rec)
    if not t:
        return None
    name = rec["modules"]["decode"]
    devs = readings.devices(rec)
    coll = sum(trace_lib.collective_seconds(d, name) for d in devs) / len(devs)
    return readings.share(coll, t)
