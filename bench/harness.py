"""One benchmark run: build the cell's server, warm up its shapes, drive the
measured window through ``LMServer.submit`` / ``LMServer.step``, check what
the window produced against the plain reference, and reduce it all to the
record the metric readers take.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own (``bench/configs``, ``bench/traffic``, ``bench/metrics``),
found by the name ``BENCHMARK.json`` gives it.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import traffic as traffic_lib

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / "bench_out"                  # run files and traces (ignored by git)
CACHE = ROOT / ".bench_cache" / "jax"     # persistent compilation cache

TRACE_START_FRACTION = 0.6   # the traced slice opens this far into the window
TRACE_DECODE_STEPS = 32      # ... and closes once it holds this many steps
TRACE_MAX_SECONDS = 20.0     # ... and at least one prefill, or after this
SETTLE_SECONDS = 60.0        # after the window, for late first tokens
DRAIN_SECONDS = 600.0        # after the window, to empty a reused server


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

def manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(name: str, man: dict, root: Path = ROOT):
    """(workload entry, configuration, traffic) of cell ``name``."""
    wl = {w["name"]: w for w in man["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf_entry = {c["name"]: c for c in man["configs"]}[wl["config"]]
    conf = json.loads((root / conf_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    return wl, conf, traffic


def metrics_of(cell_name: str, man: dict, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that cell reports."""
    if kind == "end_to_end":
        return [m for m in man["end_to_end"]
                if cell_name in m.get("workloads", [cell_name])]
    e2e = {m["name"] for m in metrics_of(cell_name, man, "end_to_end")}
    return [m for m in man["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in e2e]


def reader(name: str, root: Path = ROOT) -> Callable[[dict], Optional[float]]:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str, table: Optional[dict] = None) -> dict:
    table = table or json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def weight_seed(seed: int) -> int:
    """The seed the weights are drawn from (a PRNG key holds 32 bits)."""
    return seed % (2 ** 31)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def configure_cache(path: Path = CACHE) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever ``JAX_COMPILATION_CACHE_DIR`` says: the path is part of the
    cache's key, and the checkout is all a run may write besides its
    temporary directories."""
    import jax
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def model_config(conf: dict):
    from repro.configs.registry import get_config
    return dataclasses.replace(get_config(conf["registry"]), **conf["arch"])


def build(conf: dict, seed: int):
    """(model, params, server) of the cell, on the mesh its configuration
    states."""
    from repro.distributed.sharding import serve_rules
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import build_server
    mesh = make_local_mesh(*conf["mesh"])
    return build_server(
        model_config(conf), mesh, serve_rules(multi_pod=False),
        seed=weight_seed(seed), slots=conf["slots"],
        max_len=conf["max_len"], temperature=0.0)


def instrument(server) -> None:
    """Host spans around the engine's two phases, for the trace."""
    server._admit = _annotate("bench.engine_admit", server._admit)
    server._decode_once = _annotate("bench.engine_decode",
                                    server._decode_once)


def shapes(server, traffic: dict):
    """Batch rungs and prompt-length rungs this cell's traffic can reach."""
    from repro.core.batching import bucket
    lo, hi = traffic_lib.prompt_bounds(traffic)
    rungs = sorted({bucket(n, ladder=server.length_ladder)
                    for n in range(lo, hi + 1)})
    batches = sorted({bucket(n, cap=server.slots)
                      for n in range(1, server.slots + 1)})
    return batches, rungs


def warm(server, params, traffic: dict) -> Dict[str, str]:
    """Compile and run once every program the window can dispatch: each
    prefill shape of the cell's traffic, and the admission path (first-token
    sampling, batched scatter, slot-state update) of every batch rung
    through the server's own ``step``. Returns the XLA module names of the
    prefill and decode programs, read from the programs themselves."""
    import jax
    import jax.numpy as jnp
    batches, rungs = shapes(server, traffic)
    for nb in batches:
        for plen in rungs:
            out = server._prefill_jit(nb, plen, True)(
                params, jnp.zeros((nb, plen), jnp.int32),
                jnp.full((nb,), plen, jnp.int32))
            jax.block_until_ready(out)
            del out
    # every batch rung through the real admission path, with one-token
    # requests of the shortest prompt. First full batches, so that the
    # admission controller (one more per full batch) opens up to every
    # slot and the slot state takes the layout the decode step gives it;
    # then one batch of each rung, in that layout.
    lo = traffic_lib.prompt_bounds(traffic)[0]

    def batch(k: int) -> bool:
        for _ in range(k):
            server.submit(np.zeros((lo,), np.int32), max_new_tokens=1)
        before = server.prefill_dispatches
        while server.pending:
            server.step(params)
        return server.prefill_dispatches == before + 1

    for _ in range(2 * server.slots):
        if server.admission.max_batch_size >= server.slots:
            break
        batch(server.admission.max_batch_size)
    missed = [nb for nb in batches if not batch(nb)]
    if missed:
        print(f"warm-up: batch rungs {missed} not admitted whole; the "
              "window may compile", file=sys.stderr)
    jax.block_until_ready(server.cache)
    nb, plen = batches[0], rungs[0]
    prefill = server._prefill_jit(nb, plen, True).lower(
        params, jnp.zeros((nb, plen), jnp.int32),
        jnp.full((nb,), plen, jnp.int32))
    decode = server._decode_fused.lower(
        params, server.cache, server.lengths, server.cur_tokens,
        server.active_mask, server.gen_counts, server.max_new,
        jax.random.PRNGKey(0))
    return {"prefill": _module_name(prefill), "decode": _module_name(decode)}


def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]     # "module @jit_fn attributes"
    return head.split("@", 1)[1].split()[0]


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Track:
    req: traffic_lib.Req
    due: Optional[float]         # absolute due time (open loop)
    submitted: float
    obj: Any = None              # the engine's Request
    seen: int = 0
    times: List[float] = dataclasses.field(default_factory=list)
    admit_start: Optional[float] = None
    attempted: bool = True

    @property
    def done(self) -> bool:
        return bool(self.obj.done)


class CompileCounter:
    """Counts XLA compilations while ``active``."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.events: Dict[str, int] = {}
        mon.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, _secs, **_kw):
        if self.active and name.endswith("backend_compile_duration"):
            self.events[name] = self.events.get(name, 0) + 1


def _annotate(name: str, fn):
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


class Window:
    """Drives one window of ``seconds``, and what follows it."""

    def __init__(self, server, params, traffic: dict, seed: int,
                 seconds: float, vocab: int, trace_dir: Optional[Path]):
        import jax
        self.jax = jax
        self.server, self.params = server, params
        self.traffic, self.seconds = traffic, seconds
        self.loop = traffic["loop"]
        self.trace_dir = trace_dir
        if self.loop == "open":
            self.pending = traffic_lib.open_loop(traffic, seed, seconds, vocab)
            self.stream = None
        else:
            self.pending = []
            self.stream = traffic_lib.closed_loop(traffic, seed, vocab)
        self.tracks: List[Track] = []
        self.inflight: List[Track] = []
        self.slice: Optional[dict] = None
        self._slicing = False

    # -- requests ---------------------------------------------------------
    def _submit(self, req, due: Optional[float], now: float) -> None:
        s = self.server
        with self.jax.profiler.TraceAnnotation("bench.submit"):
            s.submit(req.prompt, max_new_tokens=req.max_new,
                     now=due if due is not None else now)
        tr = Track(req, due, now, obj=s._queue[-1])
        self.tracks.append(tr)
        self.inflight.append(tr)

    def _arrivals(self, now: float) -> None:
        if self.loop == "open":
            while self.pending and self.t0 + self.pending[0].due <= now:
                req = self.pending.pop(0)
                self._submit(req, self.t0 + req.due, now)
        else:
            queued = sum(1 for t in self.inflight if t.seen == 0)
            for _ in range(self.traffic["waiting"] - queued):
                self._submit(next(self.stream), None, now)

    # -- one engine step ----------------------------------------------------
    def _step(self) -> None:
        clock = time.perf_counter
        t_s = clock()
        with self.jax.profiler.TraceAnnotation("bench.step"):
            self.server.step(self.params)
        t_e = clock()
        with self.jax.profiler.TraceAnnotation("bench.record"):
            admitted, contexts, keep = [], [], []
            for tr in self.inflight:
                n = len(tr.obj.tokens)
                if n > tr.seen:
                    if tr.seen == 0:
                        tr.admit_start = t_s
                        admitted.append(len(tr.req.prompt))
                    tr.times.extend([t_e] * (n - tr.seen))
                    tr.seen = n
                    contexts.append(len(tr.req.prompt) + n - 1)
                if not tr.obj.done:
                    keep.append(tr)
            self.inflight = keep
            if self._slicing:
                if admitted:
                    self.slice["prefills"].append(admitted)
                if contexts:
                    self.slice["decode_steps"].append(contexts)

    # -- the traced slice ---------------------------------------------------
    def _quiet(self) -> None:
        """Wait until every device has finished the last step: the host
        has waited for one device's copy of the step's tokens only."""
        s = self.server
        self.jax.block_until_ready((s.cache, s.lengths, s.cur_tokens,
                                    s.active_mask, s.gen_counts))

    def _slice_open(self) -> None:
        self._quiet()
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(str(self.trace_dir),
                                      profiler_options=opts)
        self.slice = {"t0": time.perf_counter(), "prefills": [],
                      "decode_steps": []}
        self._slicing = True

    def _slice_close(self) -> None:
        self._quiet()
        self.slice["t1"] = time.perf_counter()
        self.jax.profiler.stop_trace()
        self._slicing = False

    def _slice_due(self, now: float) -> None:
        if self.trace_dir is None:
            return
        if self.slice is None:
            if now >= self.t0 + TRACE_START_FRACTION * self.seconds:
                self._slice_open()
            return
        if self._slicing:
            sl = self.slice
            full = (len(sl["prefills"]) >= 1
                    and len(sl["decode_steps"]) >= TRACE_DECODE_STEPS)
            if full or now - sl["t0"] >= TRACE_MAX_SECONDS:
                self._slice_close()

    # -- the run ----------------------------------------------------------
    def run(self, counter: Optional[CompileCounter] = None,
            after: str = "settle") -> dict:
        """Drive the window, then ``after`` it: ``"settle"`` steps on (no
        new requests) until every request of the window has its first
        token, ``"drain"`` until the server is empty, ``"stop"`` not at
        all. In a closed loop the backlog still queued when the window
        closes was never attempted."""
        clock = time.perf_counter
        self.t0 = clock()
        self.t_end = self.t0 + self.seconds
        if counter:
            counter.active = True
        while True:
            now = clock()
            if now >= self.t_end:
                break
            self._arrivals(now)
            self._slice_due(now)
            if self.server.pending:
                self._step()
            elif self.pending:
                with self.jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(self.t0 + self.pending[0].due,
                                            self.t_end) - clock()))
            else:
                time.sleep(max(0.0, min(1e-3, self.t_end - clock())))
        if counter:
            counter.active = False
        for tr in self.tracks:
            tr.attempted = self.loop == "open" or tr.seen > 0
        if after == "drain":
            limit, left = clock() + DRAIN_SECONDS, lambda: self.inflight
        elif after == "settle":
            limit = clock() + SETTLE_SECONDS
            left = lambda: [t for t in self.inflight
                            if t.attempted and t.seen == 0]
        else:
            limit, left = clock(), lambda: ()
        while left() and clock() < limit:
            self._slice_due(clock())
            self._step()
        if self._slicing:
            self._slice_close()
        self.t_last = clock()
        return self.record()

    def record(self) -> dict:
        reqs = []
        for tr in self.tracks:
            if not tr.attempted:
                continue
            reqs.append({
                "due": tr.due, "submitted": tr.submitted,
                "prompt_len": len(tr.req.prompt), "max_new": tr.req.max_new,
                "admit_start": tr.admit_start,
                "token_times": list(tr.times),
                "done": tr.done,
            })
        return {"loop": self.loop,
                "window": {"t0": self.t0, "t1": self.t_end,
                           "seconds": self.seconds, "last": self.t_last},
                "requests": reqs, "slice": self.slice}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample_finished(tracks: List[Track], seed: int, want_tokens: int,
                    max_total: int) -> List[Track]:
    """The finished request with the longest sequence, then others in an
    order drawn from ``seed``, until ``want_tokens`` served tokens are in or
    the reference's budget of ``max_total`` positions would be passed."""
    done = [t for t in tracks if t.done and t.obj.tokens]
    if not done:
        return []
    size = lambda t: len(t.req.prompt) + len(t.obj.tokens)
    longest = max(done, key=size)
    rest = [t for t in done if t is not longest]
    order = np.random.default_rng(seed + 1).permutation(len(rest))
    out, served, total = [longest], len(longest.obj.tokens), size(longest)
    for i in order:
        if served >= want_tokens:
            break
        t = rest[i]
        if total + size(t) > max_total:
            continue
        out.append(t)
        served += len(t.obj.tokens)
        total += size(t)
    return out


def compare(arch: dict, seed: int, sample: List[Track],
            modes=("f32",)) -> Dict[str, np.ndarray]:
    """Teacher-force the reference over each sampled prompt and its served
    tokens. For mode ``f32`` the result is the gap by which each served
    token's logit lies below the reference's best; for a lower-precision
    mode it is the gap, in the float32 reference, of the token that mode
    puts first at the same position."""
    import jax.numpy as jnp
    from bench.reference import model as ref
    weights = ref.make_weights(arch, weight_seed(seed))
    seqs, rows, served = [], [], []
    for t in sample:
        toks = np.asarray(t.obj.tokens, np.int32)
        L = len(t.req.prompt)
        seqs.append(np.concatenate([t.req.prompt, toks]))
        rows.append(np.arange(L - 1, L - 1 + len(toks)))
        served.append(toks)
    served = jnp.asarray(np.concatenate(served))
    base = ref.logits_at(arch, weights, seqs, rows, "f32")
    out = {}
    for mode in modes:
        if mode == "f32":
            out[mode] = np.asarray(ref.gap_of(base, served))
        else:
            low = ref.logits_at(arch, weights, seqs, rows, mode)
            out[mode] = np.asarray(ref.gap_of(base, jnp.argmax(low, -1)))
            del low
    return out


# ---------------------------------------------------------------------------
# one whole run
# ---------------------------------------------------------------------------

class Session:
    """The cell's model, weights and server, built and warmed once. The
    benchmark runs one window on it; the control and the tests run one per
    seed, each with weights drawn from that seed."""

    def __init__(self, conf: dict, traffic: dict, seed: int,
                 t_start: Optional[float] = None):
        import jax
        t_start = time.perf_counter() if t_start is None else t_start
        self.conf, self.traffic = conf, traffic
        self.counter = CompileCounter()
        self.model, self.params, self.server = build(conf, seed)
        instrument(self.server)
        self.modules = warm(self.server, self.params, traffic)
        jax.block_until_ready(self.params)
        self.setup_s = time.perf_counter() - t_start

    def reseed(self, seed: int) -> None:
        """The same model's weights, drawn from another seed."""
        from repro.launch.serve import init_params
        self.params = None
        self.params = init_params(self.model, self.server.mesh,
                                  self.server.rules, weight_seed(seed))

    def window(self, seed: int, seconds: float, trace_dir: Optional[Path],
               after: str = "settle") -> "Window":
        win = Window(self.server, self.params, self.traffic, seed, seconds,
                     self.conf["arch"]["vocab_size"], trace_dir)
        self.counter.events = {}
        win.run(self.counter, after)
        win.params = win.server = None
        return win

    def empty(self) -> None:
        """Finish whatever an earlier window left in the server."""
        while self.server.pending:
            self.server.step(self.params)

    def close(self) -> None:
        """Drop the program's state, so that the reference has the chip."""
        self.model = self.params = self.server = None
        gc.collect()


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        man: Optional[dict] = None, conf: Optional[dict] = None,
        traffic: Optional[dict] = None, peak_table: Optional[dict] = None,
        t_start: Optional[float] = None, out_dir: Path = OUT,
        session: Optional[Session] = None) -> dict:
    """Run one cell and return the result line's object and what else the
    run learned. ``conf`` and ``traffic`` replace the cell's files, and a
    ``session`` is reused with weights from ``seed`` (the tests do both)."""
    import jax
    man = man or manifest()
    _, conf0, traffic0 = cell(cell_name, man)
    conf, traffic = conf or conf0, traffic or traffic0
    devices = jax.devices()[:conf["chips"]]
    kind = devices[0].device_kind
    peak = peaks(kind, peak_table)
    if session is None:
        sess = Session(conf, traffic, seed, t_start)
    else:
        sess = session
        sess.reseed(seed)
    setup_s, names = sess.setup_s, sess.modules

    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{cell_name}.{seed}.{int(trace)}"
    trace_dir = out_dir / f"trace.{tag}" if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = sess.window(seed, seconds, trace_dir)
    rec = win.record()
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    tracks = win.tracks
    del win
    if session is None:
        sess.close()

    rec.update(setup_s=setup_s, chips=conf["chips"], arch=conf["arch"],
               peak=peak, modules=names)
    breakdown, collectives = None, None
    if trace:
        from bench import trace as trace_lib
        tr = trace_lib.load(trace_lib.latest_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec["trace"] = tr
        breakdown = {"device_ops": trace_lib.top_ops(tr),
                     "idle_gaps": trace_lib.idle_gaps(tr)}
        collectives = trace_lib.collective_names(tr)[:20]

    # correctness: the window's finished requests against the reference
    chk = conf["check"]
    sample = sample_finished(tracks, seed, chk["compared_tokens"],
                             chk["reference_tokens"])
    gaps = (compare(conf["arch"], seed, sample)["f32"] if sample
            else np.zeros(0))
    due = rec["requests"]
    failed = sum(1 for r in due if not r["token_times"])
    checks = {
        "logit_gap": {"value": float(gaps.max()) if gaps.size else None,
                      "limit": chk["logit_gap"]},
        "compared_tokens": {"value": int(gaps.size),
                            "limit": chk["compared_tokens"]},
        "no_first_token": {"value": failed, "limit": 0},
    }
    correct = (gaps.size >= chk["compared_tokens"]
               and float(gaps.max()) <= chk["logit_gap"] and failed == 0)

    kind_name = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(cell_name, man, kind_name):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        from bench import readings
        device["busy_s"] = readings.busy_seconds(rec)
        device["window_s"] = rec["slice"]["t1"] - rec["slice"]["t0"]
    late = np.asarray([t.submitted - t.due for t in tracks
                       if t.due is not None]) * 1e3
    extra = {
        "generator_late_ms": ({"p50": float(np.percentile(late, 50)),
                               "p99": float(np.percentile(late, 99))}
                              if late.size else None),
        "window_compiles": dict(sess.counter.events),
        "modules": names,
        "collectives": collectives,
        "sample": {"requests": len(sample), "tokens": int(gaps.size)},
        "slice": ({"prefills": len(rec["slice"]["prefills"]),
                   "decode_steps": len(rec["slice"]["decode_steps"])}
                  if rec.get("slice") else None),
    }
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    (out_dir / f"run.{tag}.json").write_text(json.dumps(
        {"result": result, "info": extra}, indent=1, default=str))
    if session is not None:
        session.empty()
    return {"result": result, "info": extra}

