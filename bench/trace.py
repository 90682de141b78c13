"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metric
readers need: per device, the executions of each XLA module and the device
operations with their intervals; on the host, the harness's own spans.

Two layouts are read. On a TPU each chip is a plane ``/device:TPU:<n>``
whose line ``XLA Modules`` holds one event per program execution, named
``<module>(<fingerprint>)``, and whose line ``XLA Ops`` holds the
operations, each named by its HLO instruction. On the CPU backend operations run on
host threads and carry the stats ``hlo_module``, ``run_id`` and
``device_ordinal``; one execution of a module is then the set of its
operations that share a ``run_id``.

Times are in seconds on the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

HOST_PREFIX = "bench."
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all", re.I)


@dataclass
class Device:
    # (module name, start, end) per execution, in time order
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    # (op name, start, end, module name or None)
    ops: List[Tuple[str, float, float, Optional[str]]] = field(
        default_factory=list)


@dataclass
class Trace:
    devices: Dict[str, Device]
    host: List[Tuple[str, float, float]]     # harness spans ("bench.*")


def module_base(name: str) -> str:
    """``jit_fused(123)`` -> ``jit_fused``: the module name an execution
    event carries, without the program id the profiler appends."""
    return name.split("(", 1)[0].strip()


def op_base(name: str) -> str:
    """``%fusion.156 = s32[4]{0} fusion(...)`` -> ``fusion.156``: a TPU
    trace names each operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def latest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Device] = defaultdict(Device)
    host: List[Tuple[str, float, float]] = []
    cpu_runs: Dict[tuple, List[float]] = {}
    planes = list(pd.planes)
    chips = [p for p in planes if p.name.startswith("/device:")
             and any(ln.name == "XLA Modules" for ln in p.lines)]
    for plane in chips:
        dev = devices[plane.name]
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    dev.modules.append((module_base(ev.name),
                                        ev.start_ns * 1e-9, ev.end_ns * 1e-9))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    dev.ops.append((op_base(ev.name), ev.start_ns * 1e-9,
                                    ev.end_ns * 1e-9, None))
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    host.append((ev.name, ev.start_ns * 1e-9,
                                 ev.end_ns * 1e-9))
                    continue
                if chips:
                    continue
                st = _stats(ev)
                mod = st.get("hlo_module")
                if mod is None or "hlo_op" not in st:
                    continue
                name = f"/cpu:{st.get('device_ordinal', 0)}"
                t0, t1 = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                devices[name].ops.append((ev.name, t0, t1, mod))
                key = (name, mod, st.get("run_id"))
                span = cpu_runs.setdefault(key, [t0, t1])
                span[0], span[1] = min(span[0], t0), max(span[1], t1)
    for (name, mod, _), (t0, t1) in cpu_runs.items():
        devices[name].modules.append((mod, t0, t1))
    for dev in devices.values():
        dev.modules.sort(key=lambda m: m[1])
        dev.ops.sort(key=lambda o: o[1])
        _attribute_ops(dev)
    host.sort(key=lambda h: h[1])
    return Trace(dict(devices), host)


def _attribute_ops(dev: Device) -> None:
    """Give each op without a module the module execution that holds it."""
    mods = dev.modules
    j = 0
    out = []
    for name, t0, t1, mod in dev.ops:
        if mod is None:
            while j < len(mods) and mods[j][2] < t0:
                j += 1
            if j < len(mods) and mods[j][1] <= t0 and t1 <= mods[j][2] + 1e-9:
                mod = mods[j][0]
        out.append((name, t0, t1, mod))
    dev.ops = out


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(dev: Device) -> float:
    return sum(b - a for a, b in union([(o[1], o[2]) for o in dev.ops]))


def executions(dev: Device, module: str) -> List[Tuple[float, float]]:
    return [(a, b) for m, a, b in dev.modules if m == module]


def collective_seconds(dev: Device, module: str) -> float:
    """Device time of the collective operations inside ``module``."""
    return sum(b - a for a, b in union(
        [(o[1], o[2]) for o in dev.ops
         if o[3] == module and _COLLECTIVE.search(o[0])]))


def collective_names(trace: Trace) -> List[str]:
    return sorted({o[0] for d in trace.devices.values() for o in d.ops
                   if _COLLECTIVE.search(o[0])})


def self_seconds(ops) -> List[float]:
    """Each op's duration less that of the ops nested inside it (a TPU
    trace puts a loop's body ops inside the loop op's interval)."""
    own = [o[2] - o[1] for o in ops]
    stack: List[tuple] = []                    # (end, index), innermost last
    for i, (_, a, b, _) in enumerate(ops):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack and b <= stack[-1][0]:
            own[stack[-1][1]] -= b - a
        stack.append((b, i))
    return own


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """Device operations that took most time, by self time summed over
    their name and averaged over the devices."""
    tot: Dict[str, float] = defaultdict(float)
    for dev in trace.devices.values():
        for o, t in zip(dev.ops, self_seconds(dev.ops)):
            tot[o[0]] += t
    k = max(1, len(trace.devices))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, s / k] for name, s in best]


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """Device idle time inside the harness's spans, by the innermost span
    the host was in at the gap's middle, summed and averaged over devices;
    the largest ``n``."""
    if not trace.host:
        return []
    lo = min(h[1] for h in trace.host)
    hi = max(h[2] for h in trace.host)
    tot: Dict[str, float] = defaultdict(float)
    for dev in trace.devices.values():
        busy = union([(o[1], o[2]) for o in dev.ops])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            inside = [h for h in trace.host if h[1] <= mid <= h[2]]
            name = (min(inside, key=lambda h: h[2] - h[1])[0]
                    if inside else "outside harness spans")
            tot[name] += b - a
    k = max(1, len(trace.devices))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, s / k] for name, s in best]
