"""Useful prefill FLOPs of the slice's prefill dispatches (valid tokens
only, padding excluded) over their device time times the chips' peak."""

from bench import readings
from bench.counts import prefill_flops


def read(rec):
    pre = readings.slice_prefills(rec)
    t = readings.program_seconds(rec, "prefill", len(pre))
    if not t:
        return None
    flops = sum(prefill_flops(rec["arch"], valid) for valid in pre)
    return readings.share(flops, t * readings.peak_flops(rec))
