"""Output tokens produced in the window, over the window's seconds."""

from bench import readings


def read(rec):
    n = readings.tokens_in_window(rec)
    return n / rec["window"]["seconds"] if n else None
