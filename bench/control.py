#!/usr/bin/env python3
"""Readings the comparison's limits are set from, on the chip, at the
cell's own sizes and load, in one process:

    python3 bench/control.py --workload smollm-chat --seeds 1,2,3 --seconds 12

For each seed: weights drawn from it, one window of the cell's traffic
through the served path (an open loop's drained, so that its longest
requests finish), then the reference over a sample of the finished requests
as a run draws it. Prints per seed the widest logit gap of the served tokens (the
program's reading) and, for each lower precision, the widest gap of the
tokens that precision puts first (the control's reading). The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--modes", default="int8,fp8")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    import jax
    harness.configure_cache()
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    _, conf, traffic = harness.cell(args.workload, harness.manifest(ROOT))
    seeds = [int(s) for s in args.seeds.split(",")]
    modes = ("f32",) + tuple(m for m in args.modes.split(",") if m)
    # an open loop's server is emptied and reused with the next seed's
    # weights; a closed loop keeps a backlog that would take minutes to
    # empty, so each seed gets a server of its own and its window is read
    # as a run reads it (the requests it finished)
    reuse = traffic["loop"] == "open"
    sess = harness.Session(conf, traffic, seeds[0]) if reuse else None
    chk = conf["check"]
    out = []
    for i, seed in enumerate(seeds):
        if reuse and i:
            sess.reseed(seed)
        elif not reuse:
            sess = harness.Session(conf, traffic, seed)
        t = time.perf_counter()
        win = sess.window(seed, args.seconds, None,
                          "drain" if reuse else "settle")
        if reuse:
            sess.params = None
        else:
            sess.close()
        sample = harness.sample_finished(win.tracks, seed,
                                         chk["compared_tokens"],
                                         chk["reference_tokens"])
        t_ref = time.perf_counter()
        gaps = harness.compare(conf["arch"], seed, sample, modes)
        row = {"seed": seed, "requests": len(sample),
               "tokens": int(gaps["f32"].size),
               "unfinished": sum(1 for tr in win.tracks if not tr.done),
               "no_first_token": sum(1 for tr in win.tracks
                                     if tr.attempted and not tr.times),
               "window_s": t_ref - t, "reference_s": time.perf_counter() - t_ref}
        for m, g in gaps.items():
            row[f"gap_{m}"] = float(g.max())
            row[f"gap_{m}_p99"] = float(sorted(g)[int(0.99 * (len(g) - 1))])
        print(json.dumps(row), flush=True)
        out.append(row)
    harness.OUT.mkdir(parents=True, exist_ok=True)
    (harness.OUT / f"control.{args.workload}.json").write_text(
        json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
