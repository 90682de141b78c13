"""95th percentile of time to first token, over every request due in the
window: the host time at which the ``step()`` that produced the request's
first token returned, minus the time the request was due (open loop)."""

from bench import readings


def read(rec):
    if rec["loop"] != "open":
        return None
    v = readings.p95(readings.ttft_s(rec))
    return None if v is None else 1e3 * v
