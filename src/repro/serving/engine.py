"""LM serving engine: continuous batching with Clipper admission control.

Requests (token prompts) enter an AIMD-governed admission queue (paper §4.3
applied to prefill); admitted prompts are prefilled in bucket-padded batches
and parked in decode *slots*; every engine step advances all active slots by
one token through a single jitted decode step (continuous batching). Slot
caches live in one donated buffer, so decode never reallocates.

Device-resident hot path (DESIGN.md §11): the *fused* decode step folds
sampling, per-slot length advance, EOS/max-token done-masking, and the
next-token feedback into the one jitted function — the host sees exactly one
compact ``[tokens ‖ done]`` transfer per step (O(1) in slots, down from the
O(slots) per-step syncs of the reference loop, kept here as ``fused=False``
for parity tests and before/after benchmarks). Admission likewise writes
the whole prefilled batch into the donated slot cache with one jitted
program that updates only the admitted rows, in place, and prompts are
padded up a geometric *length ladder*
(``core.batching.prompt_length_ladder``) so distinct prefill compilations
are bounded by the ladder, not by the workload's distinct prompt lengths —
and mixed-length traces no longer head-of-line block behind same-length
grouping.

This is deliberately the same architecture a TPU pod would run — the jitted
prefill/decode functions come from launch/steps.py-style builders with the
production shardings; here they execute on the local mesh.

Telemetry note: in wall-clock mode the first dispatch of each shape bucket
includes XLA compilation in its measured service time — a real cold-start
the tail percentiles deliberately keep. Calibrated-simulation mode
(``service_model`` + ``VirtualClock``) has no such transient."""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.batching import AIMDController, bucket, prompt_length_ladder
from repro.core import metrics as M
from repro.core.metrics import MetricsRegistry
from repro.distributed.sharding import (ShardingContext, params_shardings,
                                        sharding_context)
from repro.models.api import Model
from repro.models.common import traced_attention_backend
from repro.serving.sampler import sample

# Calibrated-simulation hook (DESIGN.md §8): maps ("prefill", batch, tokens)
# or ("decode", batch, 1) to modeled service seconds, where batch is the
# *executed* shape (padded prefill bucket; all decode slots) — the shapes the
# wall-clock engine actually pays for. With one installed, the engine
# advances its (advanceable) clock by modeled time instead of measuring
# wall-clock — deterministic, byte-identical telemetry from a seed.
ServiceModel = Callable[[str, int, int], float]


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_time: float
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    slot: Optional[int] = None
    finish_time: Optional[float] = None
    # phase boundaries on the engine clock, stamped with or without a
    # tracer: latency attribution partitions the SLO budget along them and
    # engine_report() reads TTFT and inter-token gaps from them
    dispatch_time: Optional[float] = None     # left the queue for prefill
    prefill_end: Optional[float] = None       # prefill done, decode begins
    # one stamp per token: the engine clock right after the host transfer
    # that delivered it
    token_times: List[float] = field(default_factory=list)
    # span tracing (repro.obs): the request's root span
    trace: Optional[Any] = None
    # injected per-request failure (repro.faults.RequestFaults): the tokens
    # exist but the answer is unusable — downstream policy (LMCascade)
    # escalates failed drafts and degrades failed verifies
    failed: bool = False


def make_fused_decode_fn(model: Model, mesh, rules, *, temperature: float,
                         eos: int, max_len: int):
    """Build the fused device-resident decode step (the engine's hot loop).

    Signature: ``(params, cache, lengths, cur, active, gen, max_new, key)
    -> (packed, cache, lengths, cur, active, gen)`` where ``packed`` is the
    single per-step host transfer ``concat([tokens, done])`` ([2*slots]
    int32) and everything else stays on device. Done semantics mirror the
    reference loop token-for-token: a slot finishes when its sampled token
    is EOS, its generated count reaches ``max_new``, or its advanced context
    length reaches ``max_len - 1``."""

    def fused(params, cache, lengths, cur, active, gen, max_new, key):
        with sharding_context(mesh, rules):
            logits, cache = model.decode_step(params, cache, cur, lengths)
        with jax.named_scope("sample"):
            toks = sample(logits, key, temperature=temperature)
            act = active.astype(jnp.int32)
            new_len = lengths + act
            new_cur = jnp.where(active[:, None], toks[:, None], cur)
            new_gen = gen + act
            done = active & ((toks == eos) | (new_gen >= max_new)
                             | (new_len >= max_len - 1))
            packed = jnp.concatenate([toks.astype(jnp.int32),
                                      done.astype(jnp.int32)])
        return packed, cache, new_len, new_cur, active & ~done, new_gen

    return fused


def admit_scatter(cache, pcache, dst, n):
    """Write the first ``n`` rows of a prefilled batch into their slots of
    the (donated) slot cache, in place.

    ``dst``: [nb] int32 — destination slot of each row of ``pcache``; rows
    ``n..nb-1`` pad the batch rung and are never written (the loop runs over
    the traced ``n``, since a clamped out-of-range start would land on the
    last slot). Each admitted row is one dynamic-update-slice per leaf, so
    an admission reads and writes its own rows and no other slot's bytes.
    Leaves are [B] (lengths), or layer-stacked [L, B, ...] with an optional
    shorter dim-2 (e.g. encoder memory) padded up to the destination."""

    def leaf(dv, sv, i, s):
        if dv.ndim == 1:
            return lax.dynamic_update_slice(
                dv, lax.dynamic_slice(sv, (i,), (1,)).astype(dv.dtype), (s,))
        row = lax.dynamic_slice_in_dim(sv, i, 1, axis=1)
        if row.ndim > 2 and row.shape[2] < dv.shape[2]:
            pad = dv.shape[2] - row.shape[2]
            row = jnp.pad(row, [(0, 0), (0, 0), (0, pad)]
                          + [(0, 0)] * (row.ndim - 3))
        start = (0, s) + (0,) * (dv.ndim - 2)
        return lax.dynamic_update_slice(dv, row.astype(dv.dtype), start)

    def body(i, c):
        return jax.tree.map(lambda dv, sv: leaf(dv, sv, i, dst[i]), c, pcache)

    return lax.fori_loop(0, n, body, cache)


def admit_state(lengths, cur, active, gen, max_new, slot_mask, src_idx,
                vlens, firsts, maxnews):
    """Batched slot-state update at admission (device-resident mirror of the
    per-request bookkeeping): one dispatch for the whole admitted batch."""
    new_len = jnp.where(slot_mask, vlens[src_idx], lengths)
    new_cur = jnp.where(slot_mask[:, None], firsts[src_idx][:, None], cur)
    new_gen = jnp.where(slot_mask, 1, gen)
    new_maxn = jnp.where(slot_mask, maxnews[src_idx], max_new)
    return new_len, new_cur, active | slot_mask, new_gen, new_maxn


class LMServer:
    """Continuous-batching server for one Model."""

    def __init__(self, model: Model, mesh, rules, *, slots: int = 8,
                 max_len: int = 256, slo: float = 0.5,
                 temperature: float = 0.0, eos_token: int = -1,
                 seed: int = 0, clock: Callable[[], float] = time.perf_counter,
                 metrics: Optional[MetricsRegistry] = None,
                 service_model: Optional[ServiceModel] = None,
                 model_id: str = "lm", admission_control=None,
                 fused: bool = True, prefill_slo_frac: float = 0.5,
                 pad_prompts: Optional[bool] = None,
                 on_finish: Optional[Callable[["Request"], None]] = None,
                 tracer=None, faults=None, audit=None):
        self.model = model
        self.mesh = mesh
        self.rules = rules
        self.slots = slots
        self.max_len = max_len
        self.temperature = temperature
        self.eos = eos_token
        self.slo = slo
        self.clock = clock
        self.service_model = service_model
        if service_model is not None and not hasattr(clock, "advance"):
            # modeled service times with a wall clock would mix timelines:
            # service_s modeled, latencies/throughput wall-clock
            raise ValueError(
                "service_model requires an advanceable clock "
                "(e.g. metrics.VirtualClock) so the whole report shares "
                "one timeline")
        self.model_id = model_id
        self.metrics = metrics if metrics is not None else MetricsRegistry(slo)
        # span tracing (repro.obs, DESIGN.md §13): None = tracing off
        self.tracer = tracer
        # control-plane decision audit (repro.obs.audit, DESIGN.md §15):
        # admission sheds record their backlog/wait evidence here. None = off
        self.audit = audit
        # probe state for repro.obs.timeseries windowed rates
        self._ts_prev: Dict[str, float] = {}
        # SLO-aware admission control (repro.cluster.admission): consulted
        # per submit; rejected requests are shed before they touch the
        # queue. Distinct from ``self.admission``, the AIMD *batch-size*
        # controller that governs prefill admission below.
        self.admission_control = admission_control
        self.shed = 0
        # cascade hook (repro.pipeline.cascade): invoked once per request at
        # completion, after the engine's own bookkeeping — a draft engine's
        # callback decides whether to escalate to a verify engine
        self.on_finish = on_finish
        # per-request fault injection (repro.faults.RequestFaults): a pure
        # seeded hash of the request id decides transient failures, so a
        # faulted LM run stays byte-identical per seed. None = off.
        self.faults = faults
        # prefill-only service time gets its own latency budget — a fraction
        # of the request SLO — rather than the full SLO, which would bias
        # max_batch high (prefill is only the first leg of a request)
        self.prefill_slo_frac = prefill_slo_frac
        self.admission = AIMDController(slo * prefill_slo_frac, additive=1,
                                        init=1, max_batch=slots)
        self.fused = fused
        # prompt-length ladder (only meaningful on the fused path; the
        # reference path reproduces the PR-3 same-length grouping exactly)
        if pad_prompts is None:
            pad_prompts = fused and bool(model.extras.get("prompt_pad"))
        self.pad_prompts = pad_prompts
        self._pad_cap = min(max_len,
                            int(model.extras.get("prompt_pad_cap", max_len)))
        self.length_ladder = prompt_length_ladder(self._pad_cap)
        self.rng = jax.random.PRNGKey(seed)
        self._queue: List[Request] = []
        self._active: Dict[int, Request] = {}      # slot -> request
        self._next_id = 0
        self.completed: Dict[int, Request] = {}
        # hot-path instrumentation (bench_serving reads these)
        self.decode_steps = 0
        self.decode_host_syncs = 0
        self.prefill_dispatches = 0
        # slot-cache rows admission wrote: one per admitted request, never
        # the whole slot count per dispatch
        self.admit_rows = 0
        # prefill dispatches per ladder rung (padded prompt length) — which
        # rungs the workload actually exercises (repro.obs.timeseries)
        self.rung_dispatches: Dict[int, int] = {}

        # the slot cache is created in place, sharded by the model's cache
        # axes (kv heads over the model axis), never gathered on one device
        cache_sh = params_shardings(model.cache_axes(slots, max_len),
                                    ShardingContext(mesh, rules))
        self.cache = jax.jit(lambda: model.init_cache(slots, max_len),
                             out_shardings=cache_sh)()
        # decode-attention implementation the decode step traced (set on the
        # first decode dispatch; engine_report)
        self.attention_backend: Optional[str] = None
        self.lengths = jnp.zeros((slots,), jnp.int32)
        self.cur_tokens = jnp.zeros((slots, 1), jnp.int32)
        self.active_mask = jnp.zeros((slots,), jnp.bool_)
        self.gen_counts = jnp.zeros((slots,), jnp.int32)
        self.max_new = jnp.zeros((slots,), jnp.int32)

        if fused:
            self._decode_fused = jax.jit(
                make_fused_decode_fn(model, mesh, rules,
                                     temperature=temperature, eos=eos_token,
                                     max_len=max_len),
                donate_argnums=(1, 2, 3, 4, 5))
            self._scatter_jit = jax.jit(admit_scatter, donate_argnums=(0,))
            self._admit_state_jit = jax.jit(admit_state,
                                            donate_argnums=(0, 1, 2, 3, 4))
        else:
            def decode_fn(params, cache, tokens, lengths, key):
                with sharding_context(mesh, rules):
                    logits, cache = model.decode_step(params, cache, tokens,
                                                      lengths)
                toks = sample(logits, key, temperature=temperature)
                return toks, cache

            self._decode = jax.jit(decode_fn, donate_argnums=(1,))
        self._prefill_cache: Dict[Any, Any] = {}   # shape key -> jitted prefill

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               now: Optional[float] = None) -> int:
        """Enqueue a prompt. ``now`` (when given) must be on the same
        timeline as this server's ``clock`` — completion telemetry computes
        ``finish - arrival`` with ``clock()``, so a foreign timestamp (e.g.
        0.0 against the default wall clock) yields garbage latencies."""
        rid = self._next_id
        self._next_id += 1
        at = self.clock() if now is None else now
        self.metrics.inc(M.QUERIES_SUBMITTED)
        self.metrics.mark(at)
        trace = None
        if self.tracer is not None:
            # root span: the request's whole lifecycle; budget = full SLO
            trace = self.tracer.start_trace(
                "request", "lm", at, budget_s=self.slo,
                attrs={"rid": rid, "prompt_len": int(len(prompt)),
                       "max_new": max_new_tokens})
        if (self.admission_control is not None
                and not self.admission_control.admit_lm(self, at)):
            self.metrics.inc(M.QUERIES_SHED)
            self.shed += 1
            if self.tracer is not None:
                self.tracer.event(trace, "shed", "lm.admission", at)
                self.tracer.end_trace(trace, at, status="shed")
            return rid              # shed — never queued, never completes
        req = Request(rid, np.asarray(prompt, np.int32), max_new_tokens, at)
        req.trace = trace
        self._queue.append(req)
        return rid

    def est_request_service(self) -> float:
        """Observed engine-seconds per completed request — the backlog-drain
        estimate admission control consumes. Zero until the first completion
        (admit everything while there is no signal)."""
        done = self.metrics.counter(M.QUERIES_COMPLETED)
        h = self.metrics.hist(M.SERVICE, model=self.model_id)
        if not done or h is None:
            return 0.0
        return h.total / done

    def _service_time(self, kind: str, batch: int, tokens: int,
                      t0: float) -> float:
        """Measured wall-clock, or modeled time (advancing the injected
        clock) in calibrated-simulation mode."""
        if self.service_model is None:
            return self.clock() - t0
        dt = self.service_model(kind, batch, tokens)
        self.clock.advance(dt)      # ctor guarantees the clock is advanceable
        return dt

    @property
    def prefill_compiles(self) -> int:
        """Distinct compiled prefill shapes so far — with the length ladder
        this is bounded by (batch rungs × ladder rungs), not by the number
        of distinct prompt lengths in the trace."""
        return len(self._prefill_cache)

    def _prefill_jit(self, b: int, plen: int, padded: bool):
        key = (b, plen, padded)
        if key not in self._prefill_cache:
            if self.tracer is not None:
                # compile events mark the cold-start tail wall-clock mode
                # pays per new (batch, length) shape (module docstring)
                self.tracer.global_event(
                    "compile", "engine.prefill", self.clock(),
                    attrs={"batch": b, "prompt_len": plen, "padded": padded})
            if padded:
                def fn(params, tokens, lengths):
                    with sharding_context(self.mesh, self.rules):
                        return self.model.prefill(
                            params, {"tokens": tokens, "lengths": lengths},
                            max_len=self.max_len)
            else:
                def fn(params, tokens):
                    with sharding_context(self.mesh, self.rules):
                        return self.model.prefill(params, {"tokens": tokens},
                                                  max_len=self.max_len)
            self._prefill_cache[key] = jax.jit(fn)
        return self._prefill_cache[key]

    # ------------------------------------------------------------------
    def _take_batch(self, n: int):
        """Dequeue up to ``n`` requests for one prefill dispatch; returns
        ``(batch, padded)``.

        Ladder mode (``padded=True``): the FIFO prefix whose prompts fit
        the pad cap — mixed lengths ride together (no same-length
        head-of-line blocking). Fallback (reference mode, moe, or an
        over-cap head prompt): the PR-3 same-length group around the
        queue head."""
        if self.pad_prompts and len(self._queue[0].prompt) <= self._pad_cap:
            batch: List[Request] = []
            while (self._queue and len(batch) < n
                   and len(self._queue[0].prompt) <= self._pad_cap):
                batch.append(self._queue.pop(0))
            return batch, True
        # same-length group (prefill has no per-sample prompt masking here;
        # grouping by length avoids junk-token attention)
        plen = len(self._queue[0].prompt)
        batch = []
        for r in list(self._queue):
            if len(r.prompt) == plen and len(batch) < n:
                batch.append(r)
                self._queue.remove(r)
        return batch, False

    def _admit(self, params) -> None:
        free = [s for s in range(self.slots) if s not in self._active]
        if not free or not self._queue:
            return
        with TraceAnnotation("lm.step.admit"):
            self._admit_batch(params, free)

    def _admit_batch(self, params, free: List[int]) -> None:
        """Prefill one batch from the queue head and park it in ``free``
        slots. The ``lm.step.*`` spans mark its phases on the profiler's
        clock; those named ``wait_*`` are where the host blocks on the
        device."""
        with TraceAnnotation("lm.step.batch"):
            n = min(len(free), len(self._queue),
                    self.admission.max_batch_size)
            batch, padded = self._take_batch(n)
            n = len(batch)
            if n == 0:
                return
            self.metrics.observe(M.QUEUE_DEPTH, n + len(self._queue))
            if padded:
                plen = bucket(max(len(r.prompt) for r in batch),
                              ladder=self.length_ladder)
            else:
                plen = len(batch[0].prompt)
            nb = bucket(n, cap=self.slots)
            toks = np.zeros((nb, plen), np.int32)
            vlens = np.full((nb,), plen, np.int32)
            for i, r in enumerate(batch):
                L = len(r.prompt)
                toks[i, :L] = r.prompt
                vlens[i] = L
        t0 = self.clock()
        with TraceAnnotation("lm.step.prefill"):
            if padded:
                logits, pcache = self._prefill_jit(nb, plen, True)(
                    params, jnp.asarray(toks), jnp.asarray(vlens))
            else:
                logits, pcache = self._prefill_jit(nb, plen, False)(
                    params, jnp.asarray(toks))
        with TraceAnnotation("lm.step.wait_prefill"):
            jax.block_until_ready(logits)
        self.prefill_dispatches += 1
        self.rung_dispatches[int(plen)] = (
            self.rung_dispatches.get(int(plen), 0) + 1)
        # the service model is charged the *executed* shape (padded bucket),
        # matching what wall-clock mode measures for the same workload
        dt = self._service_time("prefill", nb, plen, t0)
        self.admission.record(n, dt)
        self.metrics.inc(M.QUERIES_SUBMITTED, n, model=self.model_id)
        self._observe_batch(n, dt)
        self.metrics.mark(self.clock())
        for r in batch:
            r.dispatch_time = t0
            r.prefill_end = t0 + dt
            if self.tracer is not None and r.trace is not None:
                # queue span: arrival -> dispatch; prefill span: the batch's
                # service interval, budgeted at the prefill share of the SLO
                self.tracer.add_span(r.trace, "queue", "lm.queue",
                                     r.arrival_time, t0)
                self.tracer.add_span(
                    r.trace, "prefill", "lm.prefill", t0, t0 + dt,
                    budget_s=self.slo * self.prefill_slo_frac,
                    attrs={"batch": n, "padded_len": int(plen)})
        self.rng, k = jax.random.split(self.rng)
        with TraceAnnotation("lm.step.wait_first_token"):
            first = sample(logits, k, temperature=self.temperature)
            first_np = np.asarray(first)
        now = self.clock()
        with TraceAnnotation("lm.step.scatter"):
            for i, r in enumerate(batch):
                r.slot = free[i]
                r.tokens.append(int(first_np[i]))
                r.token_times.append(now)
                self._active[free[i]] = r
            if self.fused:
                # one jitted scatter + one jitted state update for the whole
                # admitted batch; the scatter writes the n admitted rows into
                # the donated cache in place and leaves every other slot be
                slot_mask = np.zeros((self.slots,), bool)
                src_idx = np.zeros((self.slots,), np.int32)
                dst = np.full((nb,), self.slots, np.int32)
                maxnews = np.zeros((nb,), np.int32)
                for i, r in enumerate(batch):
                    slot_mask[free[i]] = True
                    src_idx[free[i]] = i
                    dst[i] = free[i]
                    maxnews[i] = r.max_new_tokens
                slot_mask = jnp.asarray(slot_mask)
                src_idx = jnp.asarray(src_idx)
                self.cache = self._scatter_jit(self.cache, pcache,
                                               jnp.asarray(dst), np.int32(n))
                self.admit_rows += n
                (self.lengths, self.cur_tokens, self.active_mask,
                 self.gen_counts, self.max_new) = self._admit_state_jit(
                    self.lengths, self.cur_tokens, self.active_mask,
                    self.gen_counts, self.max_new, slot_mask, src_idx,
                    jnp.asarray(vlens), first.astype(jnp.int32),
                    jnp.asarray(maxnews))
            else:
                # reference path: per-request scatter, per-slot host
                # bookkeeping
                for i in range(n):
                    s = free[i]
                    self.cache = _scatter_cache(self.cache, pcache, i, s)
                    self.admit_rows += 1
                    self.lengths = self.lengths.at[s].set(int(vlens[i]))
                    self.cur_tokens = self.cur_tokens.at[s, 0].set(
                        int(first_np[i]))

    def _decode_once(self, params) -> None:
        if not self._active:
            return
        with TraceAnnotation("lm.step.decode"):
            if self.fused:
                self._decode_once_fused(params)
            else:
                self._decode_once_reference(params)

    def _decode_once_fused(self, params) -> None:
        t0 = self.clock()
        with TraceAnnotation("lm.step.dispatch"):
            self.rng, k = jax.random.split(self.rng)
            args = (params, self.cache, self.lengths, self.cur_tokens,
                    self.active_mask, self.gen_counts, self.max_new, k)
            self._note_backend(self._decode_fused, args)
            (packed, self.cache, self.lengths, self.cur_tokens,
             self.active_mask, self.gen_counts) = self._decode_fused(*args)
        with TraceAnnotation("lm.step.wait_decode"):
            out = np.asarray(packed)        # the ONE host transfer per step
        # executed shape: the jitted decode computes every slot each step
        # regardless of how many are active, like the wall-clock engine
        dt = self._service_time("decode", self.slots, 1, t0)
        now = self.clock()
        self.decode_host_syncs += 1
        toks, done = out[:self.slots], out[self.slots:].astype(bool)
        # decode steps dominate LM serving work — they count as dispatched
        # batches alongside prefill, so the report reflects the whole run
        self._observe_batch(len(self._active), dt)
        self.decode_steps += 1
        with TraceAnnotation("lm.step.tokens"):
            for s, r in list(self._active.items()):
                r.tokens.append(int(toks[s]))
                r.token_times.append(now)
                if done[s]:
                    self._finish(s, r)

    def _decode_once_reference(self, params) -> None:
        """PR-3 hot path, kept verbatim as the parity/benchmark baseline:
        per-slot ``int()`` pulls and per-slot ``.at[].set`` feedback — the
        O(slots) host round-trips the fused step eliminates."""
        t0 = self.clock()
        self.rng, k = jax.random.split(self.rng)
        args = (params, self.cache, self.cur_tokens, self.lengths, k)
        self._note_backend(self._decode, args)
        toks, self.cache = self._decode(*args)
        toks = np.asarray(toks)
        dt = self._service_time("decode", self.slots, 1, t0)
        now = self.clock()
        self.decode_host_syncs += 1
        self._observe_batch(len(self._active), dt)
        self.decode_steps += 1
        self.lengths = self.lengths + jnp.asarray(
            [1 if s in self._active else 0 for s in range(self.slots)],
            jnp.int32)
        for s, r in list(self._active.items()):
            t = int(toks[s])
            r.tokens.append(t)
            r.token_times.append(now)
            self.cur_tokens = self.cur_tokens.at[s, 0].set(t)
            cur_len = int(self.lengths[s])
            self.decode_host_syncs += 1     # per-slot device read
            if (t == self.eos or len(r.tokens) >= r.max_new_tokens
                    or cur_len >= self.max_len - 1):
                self._finish(s, r)

    def _note_backend(self, step, args) -> None:
        """Record which attention implementation the decode step traced
        (once: jit reuses this trace for the dispatch that follows)."""
        if self.attention_backend is None:
            self.attention_backend = traced_attention_backend(
                step.trace(*args).jaxpr)

    def _finish(self, s: int, r: Request) -> None:
        r.done = True
        if self.faults is not None and self.faults.failed(r.request_id):
            r.failed = True
            self.metrics.inc_both(M.FAULTS_TRANSIENT, model=self.model_id)
            self.metrics.inc_both(M.MODEL_FAILURES, model=self.model_id)
            if self.tracer is not None and r.trace is not None:
                self.tracer.event(r.trace, "fault.request_failed",
                                  "lm.fault", self.clock())
        r.finish_time = self.clock()
        self.completed[r.request_id] = r
        del self._active[s]
        if self.tracer is not None and r.trace is not None:
            # decode span: per-step work aggregated into one interval from
            # prefill end to completion; the attribution is an exact
            # partition queue + prefill + decode == end-to-end latency
            self.tracer.add_span(
                r.trace, "decode", "lm.decode", r.prefill_end, r.finish_time,
                budget_s=self.slo * (1.0 - self.prefill_slo_frac),
                attrs={"tokens": len(r.tokens)})
            latency = r.finish_time - r.arrival_time
            attribution = None
            if latency > 0:
                attribution = {
                    "lm.queue": r.dispatch_time - r.arrival_time,
                    "lm.prefill": r.prefill_end - r.dispatch_time,
                    "lm.decode": r.finish_time - r.prefill_end,
                }
            self.tracer.end_trace(r.trace, r.finish_time,
                                  attribution=attribution,
                                  attrs={"tokens": len(r.tokens)})
        # tagged per-model so multi-model cluster reports can separate LM
        # completions from frontend ones
        self.metrics.inc_both(M.QUERIES_COMPLETED, model=self.model_id)
        self.metrics.observe_latency(r.finish_time - r.arrival_time,
                                     model=self.model_id)
        self.metrics.mark(r.finish_time)
        if self.on_finish is not None:
            self.on_finish(r)

    def _observe_batch(self, size: int, service: float) -> None:
        """One dispatched batch (prefill or decode) into the shared schema —
        both dispatch paths must stay in lockstep."""
        self.metrics.inc_both(M.BATCHES, model=self.model_id)
        self.metrics.observe_both(M.BATCH_SIZE, size, model=self.model_id)
        self.metrics.observe_both(M.SERVICE, service, model=self.model_id)

    @property
    def pending(self) -> bool:
        """True while any request is queued or decoding — the public drive
        predicate (ScenarioRunner and external loops use this, not the
        private queue/slot state)."""
        return bool(self._queue or self._active)

    def timeseries_probe(self, now: float, dt: float) -> Dict[str, float]:
        """FleetSampler probe: slot occupancy, queue depth, AIMD prefill
        budget, shed/throughput rates, and per-rung dispatch rates
        (repro.obs.timeseries, DESIGN.md §15). Read-only on the engine."""
        mid = self.model_id

        def rate(key: str, cur: float) -> float:
            prev = self._ts_prev.get(key, 0.0)
            self._ts_prev[key] = cur
            return (cur - prev) / dt

        out = {
            f"lm.slots_active.{mid}": float(len(self._active)),
            f"lm.slots_free.{mid}": float(self.slots - len(self._active)),
            f"lm.queue_depth.{mid}": float(len(self._queue)),
            f"lm.aimd_budget.{mid}": float(self.admission.max_batch_size),
            f"lm.est_service.{mid}": self.est_request_service(),
            # model-scoped (not the frontend's global names): a cascade
            # samples two engines into one document without collisions
            f"lm.lambda.{mid}": rate(
                "submitted", self.metrics.counter(M.QUERIES_SUBMITTED)),
            f"lm.throughput.{mid}": rate(
                "completed", self.metrics.counter(M.QUERIES_COMPLETED)),
            f"lm.shed_rate.{mid}": rate(
                "shed", self.metrics.counter(M.QUERIES_SHED)),
            f"lm.decode_steps.{mid}": rate("decode", self.decode_steps),
            f"lm.prefill_dispatches.{mid}": rate(
                "prefill", self.prefill_dispatches),
        }
        for plen, n in sorted(self.rung_dispatches.items()):
            out[f"lm.rung_dispatches.{plen}.{mid}"] = rate(
                f"rung.{plen}", n)
        return out

    def step(self, params) -> None:
        with TraceAnnotation("lm.step"):
            self._admit(params)
            self._decode_once(params)

    def run(self, params, *, max_steps: int = 10_000) -> None:
        steps = 0
        while self.pending and steps < max_steps:
            self.step(params)
            steps += 1

    @property
    def stats(self) -> Dict[str, Any]:
        return {
            "completed": len(self.completed),
            "shed": self.shed,
            "admission_max_batch": self.admission.max_batch_size,
            "decode_steps": self.decode_steps,
            "decode_host_syncs": self.decode_host_syncs,
            "host_syncs_per_decode_step": (
                self.decode_host_syncs / self.decode_steps
                if self.decode_steps else 0.0),
            "prefill_compiles": self.prefill_compiles,
            "prefill_dispatches": self.prefill_dispatches,
            "admit_rows": self.admit_rows,
        }

    def engine_report(self) -> Dict[str, Any]:
        """Engine-level observability counters (DESIGN.md §11 hot path):
        where XLA compiles happened, how chatty the decode loop is with the
        host, and which attention backend the decode step traced with; and,
        from the completed requests' token stamps, time to first token
        (first token minus arrival) and the gaps between tokens."""
        done = list(self.completed.values())
        ttft = [r.token_times[0] - r.arrival_time for r in done
                if r.token_times]
        tpot = [b - a for r in done
                for a, b in zip(r.token_times, r.token_times[1:])]
        return {
            "ttft_s": _percentiles(ttft),
            "tpot_s": _percentiles(tpot),
            "fused": self.fused,
            # what the decode step traced, not the requested switch; None
            # until the first decode step
            "attention_backend": self.attention_backend,
            "prefill": {
                "dispatches": self.prefill_dispatches,
                "compiled_shapes": self.prefill_compiles,
                # ladder rungs actually compiled: [batch, prompt_len, padded]
                "shapes": [list(k) for k in sorted(self._prefill_cache)],
                # dispatches per (padded) prompt length — rung utilization
                "rung_dispatches": {str(k): v for k, v in
                                    sorted(self.rung_dispatches.items())},
            },
            "decode": {
                "steps": self.decode_steps,
                "host_syncs": self.decode_host_syncs,
                "host_syncs_per_step": (
                    self.decode_host_syncs / self.decode_steps
                    if self.decode_steps else 0.0),
            },
        }

    def report(self) -> Dict[str, Any]:
        """Canonical telemetry report (metrics.py schema, shared with the
        Clipper frontend), plus the engine-level ``engine`` section; with a
        tracer attached it also gains ``latency_attribution`` and a
        ``trace`` summary (same contract as ``Clipper.report``)."""
        rep = self.metrics.report("lmserver")
        rep["engine"] = self.engine_report()
        if self.tracer is not None:
            rep["latency_attribution"] = self.tracer.attribution_report()
            rep["trace"] = self.tracer.summary()
        return rep

    def report_json(self, **extra: Any) -> str:
        rep = self.report()
        rep.update(extra)
        return json.dumps(rep, sort_keys=True, indent=2)


def _percentiles(values: List[float]) -> Dict[str, Any]:
    if not values:
        return {"p50": None, "p95": None, "count": 0}
    p50, p95 = np.percentile(np.asarray(values, np.float64), [50, 95])
    return {"p50": float(p50), "p95": float(p95), "count": len(values)}


def _scatter_cache(cache, pcache, src: int, dst: int):
    """Copy request ``src`` of a prefill cache into slot ``dst`` (reference
    per-request path; the fused engine uses :func:`admit_scatter`)."""
    out = {}
    for k, v in cache.items():
        pv = pcache[k]
        if isinstance(v, tuple):
            out[k] = tuple(_scatter_leaf(a, b, src, dst) for a, b in zip(v, pv))
        else:
            out[k] = _scatter_leaf(v, pv, src, dst)
    return out


def _scatter_leaf(dst_arr, src_arr, src: int, dst: int):
    if dst_arr.ndim == 1:                   # lengths [B]
        return dst_arr.at[dst].set(src_arr[src])
    # layer-stacked [L, B, ...]: batch is dim 1
    sl = src_arr[:, src]
    if dst_arr.ndim > 2:
        pad = dst_arr.shape[2] - sl.shape[1]
        if pad > 0:
            sl = jnp.pad(sl, [(0, 0), (0, pad)] + [(0, 0)] * (sl.ndim - 2))
    return dst_arr.at[:, dst].set(sl.astype(dst_arr.dtype))
