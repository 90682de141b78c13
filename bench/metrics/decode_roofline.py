"""Roofline share of the decode step: the least time the chips could take
for the slice's steps (per step, the larger of FLOPs over peak FLOP/s and
least bytes over HBM bandwidth; at these sizes the bytes bound it) over the
steps' device time. Least bytes: the weights once, the K/V rows of the
active slots' cached positions, the new K/V rows and embedding rows."""

from bench import readings
from bench.counts import decode_bytes, decode_flops, least_seconds


def read(rec):
    t = readings.decode_seconds(rec)
    if not t:
        return None
    least = sum(least_seconds(decode_flops(rec["arch"], c),
                              decode_bytes(rec["arch"], c), rec["chips"],
                              rec["peak"])[0]
                for c in readings.slice_steps(rec))
    return readings.share(least, t)
