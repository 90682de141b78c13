"""Seconds from process start to the window's first request: model and
weight creation, warm-up of the cell's shapes and any compilation."""


def read(rec):
    return rec["setup_s"]
