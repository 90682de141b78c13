"""Device time of the fused decode-step program, per step of the slice."""

from bench import readings


def read(rec):
    steps = readings.slice_steps(rec)
    t = readings.decode_seconds(rec)
    return 1e3 * t / len(steps) if t else None
