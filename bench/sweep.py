#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the highest rate
the server sustains without a growing backlog.

    python3 bench/sweep.py --workload smollm-chat --rates 6,9,12,15 --seconds 15

Builds and warms the cell once, then drives one window per rate (nothing after it),
and prints per rate: requests, TTFT p50/p95, token-gap p95, output tokens/s,
the backlog at the window's end (due but not yet prefilled) and how TTFT
grew from the first third of the window to the last. The cell's traffic
file then states 0.8 x the knee as a number; the benchmark's own runs never
search for a rate.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def summary(rec: dict) -> dict:
    import numpy as np
    from bench import readings
    t0, t1 = rec["window"]["t0"], rec["window"]["t1"]
    reqs = [r for r in rec["requests"] if r["due"] is not None]
    ttft = np.asarray(readings.ttft_s(rec)) * 1e3
    third = max(1, len(ttft) // 3)
    backlog = sum(1 for r in reqs
                  if r["admit_start"] is None or r["admit_start"] > t1)
    return {
        "requests": len(reqs),
        "ttft_p50_ms": float(np.percentile(ttft, 50)),
        "ttft_p95_ms": readings.p95(ttft),
        "tpot_p95_ms": 1e3 * readings.p95(readings.token_gaps_s(rec)),
        "output_tok_per_s": readings.tokens_in_window(rec) / (t1 - t0),
        "backlog_at_end": backlog,
        "ttft_growth": float(ttft[-third:].mean() / ttft[:third].mean()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    import jax
    harness.configure_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    _, conf, traffic = harness.cell(args.workload, harness.manifest(ROOT))
    sess = harness.Session(conf, traffic, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        sess.traffic = dict(traffic, rate_per_s=rate)
        rec = sess.window(args.seed, args.seconds, None, "stop").record()
        print(json.dumps({"rate_per_s": rate, **summary(rec)}), flush=True)
        sess.empty()
    return 0


if __name__ == "__main__":
    sys.exit(main())
