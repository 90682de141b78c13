"""95th percentile of the wait from a request's due time to the start of
the engine step that prefilled it (host clock), over the requests due
before the traced slice opened, so that starting the profiler does not
stall them. A request never admitted waits until the end of the run."""

from bench import readings


def read(rec):
    if rec["loop"] != "open":
        return None
    cut = rec["slice"]["t0"] if rec.get("slice") else rec["window"]["t1"]
    waits = []
    for r in rec["requests"]:
        if r["due"] is None or r["due"] >= cut:
            continue
        start = r["admit_start"] if r["admit_start"] is not None \
            else rec["window"]["last"]
        waits.append(start - r["due"])
    v = readings.p95(waits)
    return None if v is None else 1e3 * v
