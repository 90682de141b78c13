"""Arithmetic the metric readers share: percentiles of the host-clock
record, and the device time of the slice's programs from the reduced trace.

A reader returns ``None`` where its cell has nothing for it to read; the
harness then leaves the metric out."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from bench import trace as trace_lib


def p95(values) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def ttft_s(rec: dict) -> List[float]:
    """First token minus due, for every request due in the window; one that
    never got a first token counts from its due time to the end of the run."""
    out = []
    for r in rec["requests"]:
        if r["due"] is None:
            continue
        first = r["token_times"][0] if r["token_times"] else rec["window"]["last"]
        out.append(first - r["due"])
    return out


def token_gaps_s(rec: dict) -> List[float]:
    """Every gap between consecutive output tokens of a request whose later
    token came inside the window."""
    t1 = rec["window"]["t1"]
    out = []
    for r in rec["requests"]:
        t = r["token_times"]
        out.extend(b - a for a, b in zip(t, t[1:]) if b <= t1)
    return out


def tokens_in_window(rec: dict) -> int:
    t0, t1 = rec["window"]["t0"], rec["window"]["t1"]
    return sum(1 for r in rec["requests"] for t in r["token_times"]
               if t0 <= t <= t1)


# -- the traced slice ---------------------------------------------------------

def devices(rec: dict):
    tr = rec.get("trace")
    return list(tr.devices.values()) if tr is not None else []


def program_seconds(rec: dict, which: str, expect: int) -> Optional[float]:
    """Device seconds of the slice's executions of the ``which`` program
    (``prefill`` or ``decode``), averaged over the devices. Each device must
    show exactly the ``expect`` executions the harness dispatched in the
    slice; anything else means the trace and the record disagree."""
    devs = devices(rec)
    if not devs or expect == 0:
        return None
    name = rec["modules"][which]
    total = 0.0
    for d in devs:
        ex = trace_lib.executions(d, name)
        if len(ex) != expect:
            raise ValueError(
                f"trace holds {len(ex)} executions of {name} on a device, "
                f"the slice dispatched {expect}")
        total += sum(b - a for a, b in ex)
    return total / len(devs)


def slice_prefills(rec: dict) -> list:
    return rec["slice"]["prefills"] if rec.get("slice") else []


def slice_steps(rec: dict) -> list:
    return rec["slice"]["decode_steps"] if rec.get("slice") else []


def decode_seconds(rec: dict) -> Optional[float]:
    return program_seconds(rec, "decode", len(slice_steps(rec)))


def share(part: float, whole: float) -> Optional[float]:
    return 100.0 * part / whole if whole and part > 0 else None


def busy_seconds(rec: dict) -> Optional[float]:
    devs = devices(rec)
    if not devs:
        return None
    return sum(trace_lib.busy_seconds(d) for d in devs) / len(devs)


def peak_flops(rec: dict) -> float:
    return rec["chips"] * rec["peak"]["bf16_flops_per_s"]

