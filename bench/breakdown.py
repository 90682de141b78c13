#!/usr/bin/env python3
"""Where a traced slice's time goes, by the engine's own names.

    python3 bench/breakdown.py --workload <name> --seed <n> [--seconds 51]

Builds and warms the cell's server as ``bench/run.py`` does, drives one
window with the same traced slice, and prints one JSON object as the last
line of standard output:

- ``decode_scopes``: device self-seconds of the decode program's ops over
  the slice, averaged over the chips, by the named scope of each op's HLO
  instruction (``attention``, ``kv_update``, ``mlp``, ``lm_head``,
  ``sample``; ``unscoped`` for none, ``missing`` for an op the compiled
  program does not name);
- ``idle_gaps``: device idle by the innermost host span, the engine's
  ``lm.step.*`` phases among them;
- ``host_step_ms``: mean over the slice's ``lm.step`` spans of their length
  less the ``lm.step.wait_*`` spans inside them, the host time per step in
  which the engine is not waiting on the chip;
- ``admit_wait_p95_ms``: p95 of the engine's dispatch stamp minus arrival,
  over the requests due before the slice (open loop; one never dispatched
  waits to the end of the run);
- ``decode_attention_ms``: ``decode_scopes["attention"]`` per decode step;
- ``admit_device_ms``: as ``bench/metrics/admit_device_ms.py`` reads it;
- ``bench_step_ms``: mean length of the harness's ``bench.step`` spans in
  the slice, the engine step as the harness's loop sees it.

A quantity the program has no span, scope or stamp for reads ``null``. No
correctness check is made: ``bench/run.py`` makes it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness, readings  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

STEP, WAIT = "lm.step", "lm.step.wait_"     # an engine step; a block on it
# the named scopes of the decode program (models/transformer.py,
# serving/engine.py)
SCOPES = ("attention", "kv_update", "mlp", "lm_head", "sample")
_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def engine_spans(path: str) -> List[tuple]:
    """The engine's ``lm.step*`` spans in a profiler trace, as (name,
    start, end) in seconds on the profiler's clock, in time order."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
           for plane in pd.planes if not plane.name.startswith("/device:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(STEP)]
    return sorted(out, key=lambda h: h[1])


def host_step_seconds(host: List[tuple]) -> List[float]:
    """For each ``lm.step`` span, its length less that of the
    ``lm.step.wait_*`` spans inside it."""
    waits = [h for h in host if h[0].startswith(WAIT)]
    return [(b - a) - sum(w[2] - w[1] for w in waits
                          if a <= w[1] and w[2] <= b)
            for name, a, b in host if name == STEP]


def scope_table(hlo_text: str) -> Dict[str, str]:
    """Each instruction of a compiled program's HLO text, by name, mapped
    to the innermost of ``SCOPES`` in its ``op_name`` metadata, or to
    ``unscoped``."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        parts = meta.group(1).split("/") if meta else []
        table[m.group(1)] = next((p for p in reversed(parts) if p in SCOPES),
                                 "unscoped")
    return table


def scope_seconds(trace: trace_lib.Trace, module: str,
                  table: Dict[str, str]) -> Dict[str, float]:
    """Self time of ``module``'s operations by the scope ``table`` gives
    their instruction, averaged over the devices."""
    tot = {s: 0.0 for s in SCOPES + ("unscoped", "missing")}
    for dev in trace.devices.values():
        for o, t in zip(dev.ops, trace_lib.self_seconds(dev.ops)):
            if o[3] == module:
                tot[table.get(o[0], "missing")] += t
    k = max(1, len(trace.devices))
    return {s: t / k for s, t in tot.items()}


def _ms(seconds: List[float]) -> Optional[float]:
    return 1e3 * float(np.mean(seconds)) if seconds else None


def breakdown(cell_name: str, seed: int, seconds: float, *,
              man: Optional[dict] = None, conf: Optional[dict] = None,
              traffic: Optional[dict] = None,
              t_start: Optional[float] = None,
              out_dir: Path = harness.OUT,
              session: Optional[harness.Session] = None) -> dict:
    """One traced window of the cell, reduced by the engine's names.
    ``conf``, ``traffic`` and ``session`` as in ``harness.run``."""
    import jax
    man = man or harness.manifest()
    _, conf0, traffic0 = harness.cell(cell_name, man)
    conf, traffic = conf or conf0, traffic or traffic0
    if session is None:
        sess = harness.Session(conf, traffic, seed, t_start)
    else:
        sess = session
        sess.reseed(seed)
    out_dir.mkdir(parents=True, exist_ok=True)
    trace_dir = out_dir / f"breakdown.{cell_name}.{seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = sess.window(seed, seconds, trace_dir)
    rec = win.record()

    # the warmed decode program, lowered and compiled again (the
    # compilation cache holds it): the named scope of each instruction
    s = sess.server
    table = scope_table(s._decode_fused.lower(
        sess.params, s.cache, s.lengths, s.cur_tokens, s.active_mask,
        s.gen_counts, s.max_new, jax.random.PRNGKey(0)).compile().as_text())
    path = trace_lib.latest_xplane(str(trace_dir))
    tr = trace_lib.load(path)
    tr = trace_lib.Trace(tr.devices, sorted(tr.host + engine_spans(path),
                                            key=lambda h: h[1]))
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec.update(trace=tr, modules=sess.modules)

    steps = readings.slice_steps(rec)
    scopes = scope_seconds(tr, sess.modules["decode"], table)
    sl = rec["slice"]
    waits = [(t.obj.dispatch_time if t.obj.dispatch_time is not None
              else rec["window"]["last"]) - t.due
             for t in win.tracks if t.due is not None and t.due < sl["t0"]]
    stamped = any(t.obj.dispatch_time is not None for t in win.tracks)
    admit_wait = readings.p95(waits) if stamped else None
    bench_steps = [b - a for name, a, b in tr.host if name == "bench.step"]
    dec = readings.decode_seconds(rec)
    out = {
        "workload": cell_name, "seed": seed, "modules": sess.modules,
        "slice": {"prefills": len(readings.slice_prefills(rec)),
                  "decode_steps": len(steps),
                  "seconds": sl["t1"] - sl["t0"]},
        "decode_step_ms": 1e3 * dec / len(steps) if dec else None,
        "decode_scopes": scopes,
        "idle_gaps": trace_lib.idle_gaps(tr, n=16),
        "host_step_ms": _ms(host_step_seconds(tr.host)),
        "admit_wait_p95_ms": (1e3 * admit_wait if admit_wait is not None
                              else None),
        "decode_attention_ms": (1e3 * scopes["attention"] / len(steps)
                                if steps and scopes["attention"] > 0
                                else None),
        "admit_device_ms": harness.reader("admit_device_ms")(rec),
        "bench_step_ms": _ms(bench_steps),
    }
    if session is None:
        sess.close()
    else:
        session.empty()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    import jax
    man = harness.manifest(ROOT)
    wl, _, _ = harness.cell(args.workload, man, ROOT)
    harness.configure_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"bench/breakdown.py: {args.workload} needs {wl['chips']} "
              f"TPU chips, found {len(devices)} {devices[0].platform}",
              file=sys.stderr)
        return 1
    out = breakdown(args.workload, args.seed, args.seconds, man=man,
                    t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
