"""95th percentile over every gap between consecutive output tokens of
every request in the window. A token's time is when the ``step()`` that
produced it returned: that step ends in the engine's one host transfer.
The first two tokens of a request come from the same step (prefill, then
the first decode step), so each request adds one gap of zero."""

from bench import readings


def read(rec):
    v = readings.p95(readings.token_gaps_s(rec))
    return None if v is None else 1e3 * v
