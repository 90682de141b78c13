#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's server (``BENCHMARK.json`` names its configuration and
traffic), warms up, drives the measured window, checks what it produced
against the plain reference and prints one JSON object as the last line of
standard output. With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of a slice of the window. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 1


def validate(result: dict, listed, trace: bool) -> list:
    """Every listed metric present, finite and above 0; shares at most
    100%. Returns the faults."""
    faults = []
    for m in listed:
        got = result["metrics"].get(m["name"])
        if got is None:
            faults.append(f"{m['name']} missing")
            continue
        v = got["value"]
        if not (isinstance(v, (int, float)) and math.isfinite(v) and v > 0):
            faults.append(f"{m['name']} = {v}")
        elif m["unit"] == "%" and v > 100.0:
            faults.append(f"{m['name']} = {v} above 100%")
    if trace:
        d = result["device"]
        if not (0 < d.get("busy_s", 0) <= d.get("window_s", 0)):
            faults.append(f"busy_s {d.get('busy_s')} window_s "
                          f"{d.get('window_s')}")
    return faults


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program under {ROOT / 'src'}: run from a checkout "
                    "of the repository")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    man = harness.manifest(ROOT)
    try:
        wl, conf, _ = harness.cell(args.workload, man, ROOT)
    except KeyError as e:
        return fail(str(e))

    import jax
    harness.configure_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU found (JAX platform {devices[0].platform!r}); "
                    "this benchmark measures the chip and has no fallback")
    if len(devices) < wl["chips"]:
        return fail(f"{args.workload} needs {wl['chips']} chips, found "
                    f"{len(devices)}")

    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), man=man, t_start=T_START)
    result, info = out["result"], out["info"]
    print(f"info: {json.dumps(info)}", flush=True)
    kind = "per_layer" if args.trace else "end_to_end"
    faults = validate(result, harness.metrics_of(args.workload, man, kind),
                      bool(args.trace))
    if faults:
        print(json.dumps({k: result.get(k) for k in
                          ("metrics", "device", "breakdown")}),
              file=sys.stderr)
        return fail("metrics at fault: " + "; ".join(faults))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
