"""The whole decode step's share of the chips' peak: FLOPs of the slice's
steps over their active slots at live context lengths, over the steps'
device time times the chips' peak."""

from bench import readings
from bench.counts import decode_flops


def read(rec):
    t = readings.decode_seconds(rec)
    if not t:
        return None
    flops = sum(decode_flops(rec["arch"], c) for c in readings.slice_steps(rec))
    return readings.share(flops, t * readings.peak_flops(rec))
