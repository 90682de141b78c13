"""Device-resident serving hot path (DESIGN.md §11): fused decode step vs
the reference per-slot loop, batched cache scatter vs per-request scatter,
prompt-length ladder exactness, and Pallas-vs-jnp decode attention parity
through the backend switch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHITECTURES, reduced_config
from repro.core.batching import bucket, prompt_length_ladder
from repro.core.metrics import VirtualClock
from repro.distributed.sharding import serve_rules
from repro.launch.mesh import make_local_mesh
from repro.models.api import build_model
from repro.models.common import (attention_decode, attention_decode_auto,
                                 get_attention_backend,
                                 set_attention_backend)
from repro.serving.engine import (LMServer, _scatter_cache, admit_scatter,
                                  make_fused_decode_fn)
from repro.serving.sampler import sample

FAMILIES = {
    "dense": "smollm-360m",
    "ssm": "xlstm-125m",
    "hybrid": "hymba-1.5b",
    "encdec": "seamless-m4t-medium",
}


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


@pytest.fixture(scope="module")
def built(mesh):
    out = {}
    for fam, name in FAMILIES.items():
        cfg = reduced_config(ARCHITECTURES[name])
        model = build_model(cfg, mesh, serve_rules(False))
        params = model.init(jax.random.PRNGKey(0))
        out[fam] = (cfg, model, params)
    return out


def _sim_server(model, mesh, seed=0, **kw):
    clock = VirtualClock()

    def service_model(kind, batch, tokens):
        return 0.004 + 5e-5 * batch * tokens if kind == "prefill" \
            else 0.001 + 5e-5 * batch

    return LMServer(model, mesh, serve_rules(False), max_len=64,
                    clock=clock, service_model=service_model, seed=seed,
                    **kw)


# ---------------------------------------------------------------------------
# fused vs reference: byte-identical token streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_fused_matches_reference_byte_identical(built, mesh, temperature):
    """Acceptance: the fused decode step produces byte-identical token
    streams to the reference engine for a fixed seed in calibrated-sim
    mode (same-length prompts, so admission batching is identical)."""
    cfg, model, params = built["dense"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(7)]
    streams = {}
    for fused in (True, False):
        srv = _sim_server(model, mesh, slots=4, fused=fused,
                          temperature=temperature)
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        srv.run(params)
        streams[fused] = [srv.completed[r].tokens for r in rids]
    assert streams[True] == streams[False]


@pytest.mark.parametrize("fam", ["dense", "ssm", "hybrid"])
def test_fused_mixed_lengths_matches_reference_greedy(built, mesh, fam):
    """Mixed-length traces: the ladder pads prompts while the reference
    engine same-length-groups them — batching differs, but greedy decode is
    per-sample deterministic and padded prefill is exact, so per-request
    token streams must agree across the two engines."""
    cfg, model, params = built[fam]
    rng = np.random.default_rng(1)
    lens = [4, 9, 4, 13, 6]
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in lens]
    streams = {}
    for fused in (True, False):
        srv = _sim_server(model, mesh, slots=4, fused=fused)
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        srv.run(params)
        streams[fused] = [srv.completed[r].tokens for r in rids]
    assert streams[True] == streams[False]
    if fam != "ssm":        # attention families pad under the ladder
        srv = _sim_server(model, mesh, slots=4, fused=True)
        assert srv.pad_prompts


def test_fused_host_syncs_O1_reference_O_slots(built, mesh):
    """The hot-path contract: one host transfer per fused decode step; the
    reference loop pays 1 + one per active slot."""
    cfg, model, params = built["dense"]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(6)]
    stats = {}
    for fused in (True, False):
        srv = _sim_server(model, mesh, slots=4, fused=fused)
        for p in prompts:
            srv.submit(p, max_new_tokens=6)
        srv.run(params)
        stats[fused] = srv.stats
    assert stats[True]["host_syncs_per_decode_step"] == 1.0
    assert stats[False]["host_syncs_per_decode_step"] > 1.5


# ---------------------------------------------------------------------------
# fused step builder: token-for-token parity across all four families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_fused_step_token_parity_all_families(built, mesh, fam):
    """Drive make_fused_decode_fn directly against a reference loop that
    reproduces the per-slot Python bookkeeping, from the same prefilled
    cache — tokens and done transitions must match step for step."""
    cfg, model, params = built[fam]
    rules = serve_rules(False)
    rng = np.random.default_rng(3)
    slots, max_len, plen, max_new = 3, 32, 6, 5
    toks = rng.integers(0, cfg.vocab_size, (2, plen)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(2, 8, cfg.d_model)) * 0.02, jnp.float32)
    logits, pcache = model.prefill(params, batch, max_len=max_len)
    first = np.asarray(sample(logits, jax.random.PRNGKey(9)))

    def scattered():
        cache = model.init_cache(slots, max_len)
        return admit_scatter(cache, pcache, jnp.asarray([0, 1], jnp.int32),
                             2)

    lengths0 = jnp.asarray([plen, plen, 0], jnp.int32)
    cur0 = jnp.asarray([[first[0]], [first[1]], [0]], jnp.int32)

    # fused path
    fused = jax.jit(make_fused_decode_fn(
        model, mesh, rules, temperature=0.0, eos=-1, max_len=max_len))
    cache = scattered()
    lengths, cur = lengths0, cur0
    active = jnp.asarray([True, True, False])
    gen = jnp.asarray([1, 1, 0], jnp.int32)
    maxn = jnp.asarray([max_new, max_new, 0], jnp.int32)
    key = jax.random.PRNGKey(4)
    fused_toks, fused_done = [], []
    for _ in range(max_new):
        key, k = jax.random.split(key)
        packed, cache, lengths, cur, active, gen = fused(
            params, cache, lengths, cur, active, gen, maxn, k)
        out = np.asarray(packed)
        fused_toks.append(out[:slots].tolist())
        fused_done.append(out[slots:].astype(bool).tolist())

    # reference loop (PR-3 semantics)
    cache = scattered()
    lengths, cur = lengths0, cur0
    live = {0: 1, 1: 1}                     # slot -> generated count
    key = jax.random.PRNGKey(4)
    ref_toks, ref_done = [], []
    for _ in range(max_new):
        key, k = jax.random.split(key)
        logits, cache = model.decode_step(params, cache, cur, lengths)
        t = np.asarray(sample(logits, k, temperature=0.0))
        lengths = lengths + jnp.asarray(
            [1 if s in live else 0 for s in range(slots)], jnp.int32)
        step_done = [False] * slots
        for s in list(live):
            live[s] += 1
            cur = cur.at[s, 0].set(int(t[s]))
            if live[s] >= max_new or int(lengths[s]) >= max_len - 1:
                step_done[s] = True
                del live[s]
        ref_toks.append(t.tolist())
        ref_done.append(step_done)

    # only active slots carry meaningful tokens
    for ft, rt, fd, rd in zip(fused_toks, ref_toks, fused_done, ref_done):
        assert fd == rd
        for s in (0, 1):
            assert ft[s] == rt[s]


# ---------------------------------------------------------------------------
# batched scatter == per-request reference scatter
# ---------------------------------------------------------------------------

def _gather_select(cache, pcache, slot_mask, src_idx):
    """The batched scatter as it was first written: gather every slot's
    source row, then a masked select over the whole cache (the oracle)."""

    def leaf(dv, sv):
        if dv.ndim == 1:
            return jnp.where(slot_mask, sv[src_idx].astype(dv.dtype), dv)
        sl = jnp.take(sv, src_idx, axis=1)
        if sl.ndim > 2 and sl.shape[2] < dv.shape[2]:
            pad = dv.shape[2] - sl.shape[2]
            sl = jnp.pad(sl, [(0, 0), (0, 0), (0, pad)]
                         + [(0, 0)] * (sl.ndim - 3))
        m = slot_mask.reshape((1, slot_mask.shape[0]) + (1,) * (sl.ndim - 2))
        return jnp.where(m, sl.astype(dv.dtype), dv)

    return jax.tree.map(leaf, cache, pcache)


def _filled_cache(model, slots, max_len, seed):
    """A slot cache of non-zero random values: a slot the scatter leaves
    alone is told apart from one it zeroes."""
    rng = np.random.default_rng(seed)

    def fill(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.asarray(rng.integers(1, 1000, a.shape), a.dtype)
        return jnp.asarray(rng.normal(size=a.shape) + 3.0, a.dtype)

    return jax.tree.map(fill, model.init_cache(slots, max_len))


# (prefill rows nb, admitted n, destination slot of each admitted row);
# rows n..nb-1 pad the rung and aim at slot index ``slots``, which a clamped
# dynamic-update-slice would turn into the last slot
SCATTER_CASES = {
    "full_rung": (2, 2, [2, 0]),
    "pad_rows": (4, 2, [1, 2]),
    "last_slot": (2, 1, [3]),
}


@pytest.mark.parametrize("case", sorted(SCATTER_CASES))
@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_batched_scatter_matches_reference(built, mesh, fam, case):
    """The in-place row scatter writes exactly what the gather-select
    wrote, bit for bit, into the admitted slots, and leaves every other
    slot (pad rows' targets included) as it was."""
    cfg, model, params = built[fam]
    nb, n, dsts = SCATTER_CASES[case]
    rng = np.random.default_rng(4)
    slots, max_len, plen = 4, 32, 6
    toks = rng.integers(0, cfg.vocab_size, (nb, plen)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(nb, 8, cfg.d_model)) * 0.02, jnp.float32)
    _, pcache = model.prefill(params, batch, max_len=max_len)

    slot_mask = np.zeros((slots,), bool)
    src_idx = np.zeros((slots,), np.int32)
    dst = np.full((nb,), slots, np.int32)
    for i, s in enumerate(dsts):
        slot_mask[s], src_idx[s], dst[i] = True, i, s
    before = _filled_cache(model, slots, max_len, 7)
    oracle = _gather_select(before, pcache, jnp.asarray(slot_mask),
                            jnp.asarray(src_idx))
    ref = before
    for i, s in enumerate(dsts):
        ref = _scatter_cache(ref, pcache, i, s)
    got = jax.jit(admit_scatter, donate_argnums=(0,))(
        _filled_cache(model, slots, max_len, 7), pcache, jnp.asarray(dst),
        np.int32(n))
    for ol, rl, gl, bl in zip(jax.tree.leaves(oracle), jax.tree.leaves(ref),
                              jax.tree.leaves(got), jax.tree.leaves(before)):
        assert gl.dtype == ol.dtype and gl.shape == ol.shape
        g = np.asarray(gl, np.float32)
        np.testing.assert_array_equal(np.asarray(ol, np.float32), g)
        np.testing.assert_array_equal(np.asarray(rl, np.float32), g)
        kept = [s for s in range(slots) if s not in dsts]
        b = np.asarray(bl, np.float32)
        axis = 1 if bl.ndim > 1 else 0
        np.testing.assert_array_equal(np.take(b, kept, axis=axis),
                                      np.take(g, kept, axis=axis))


def test_reused_slot_matches_reference(built, mesh):
    """A long request finishes; a shorter one lands in the slot it freed
    while another slot still decodes. Greedy tokens equal the reference
    engine's, and admission wrote one cache row per admitted request."""
    cfg, model, params = built["dense"]
    rng = np.random.default_rng(8)
    long_p, live_p, short_p = (rng.integers(0, cfg.vocab_size, size=k)
                               for k in (40, 8, 5))
    streams = {}
    for fused in (True, False):
        srv = _sim_server(model, mesh, slots=2, fused=fused)
        a = srv.submit(long_p, max_new_tokens=3)
        b = srv.submit(live_p, max_new_tokens=16)
        while a not in srv.completed:
            srv.step(params)
        assert b not in srv.completed
        freed = srv.completed[a].slot
        c = srv.submit(short_p, max_new_tokens=5)
        srv.step(params)
        assert srv._active[freed].request_id == c
        assert len(srv._active) == 2
        srv.run(params)
        assert srv.stats["admit_rows"] == 3
        streams[fused] = [srv.completed[r].tokens for r in (a, b, c)]
    assert streams[True] == streams[False]
    assert [len(t) for t in streams[True]] == [3, 16, 5]


# ---------------------------------------------------------------------------
# prompt-length ladder: padded prefill is exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_padded_prefill_matches_exact(built, mesh, fam):
    """Right-padding a prompt up the ladder with ``batch["lengths"]`` must
    reproduce the exact-length prefill bit-for-bit: logits, cache lengths,
    and the next decode step."""
    cfg, model, params = built[fam]
    rng = np.random.default_rng(5)
    L, Lb = 5, 8
    toks = rng.integers(0, cfg.vocab_size, (2, L)).astype(np.int32)
    padded = np.zeros((2, Lb), np.int32)
    padded[:, :L] = toks
    be = {"tokens": jnp.asarray(toks)}
    bp = {"tokens": jnp.asarray(padded),
          "lengths": jnp.asarray([L, L], jnp.int32)}
    if cfg.family == "encdec":
        fr = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)) * 0.02,
                         jnp.float32)
        be["frames"] = fr
        bp["frames"] = fr
    le, ce = model.prefill(params, be, max_len=32)
    lp, cp = model.prefill(params, bp, max_len=32)
    np.testing.assert_array_equal(np.asarray(le, np.float32),
                                  np.asarray(lp, np.float32))
    np.testing.assert_array_equal(np.asarray(ce["lengths"]),
                                  np.asarray(cp["lengths"]))
    t = jnp.argmax(le, -1).astype(jnp.int32)[:, None]
    l2e, _ = model.decode_step(params, ce, t, ce["lengths"])
    l2p, _ = model.decode_step(params, cp, t, cp["lengths"])
    np.testing.assert_array_equal(np.asarray(l2e, np.float32),
                                  np.asarray(l2p, np.float32))


def test_prompt_length_ladder_shape():
    lad = prompt_length_ladder(64)
    assert lad[-1] == 64 and lad[0] == 8
    assert all(b >= 2 * a for a, b in zip(lad, lad[1:]))
    assert bucket(5, ladder=lad) == 8
    assert bucket(9, ladder=lad) == 16
    assert bucket(64, ladder=lad) == 64
    assert bucket(100, ladder=lad) == 100      # above cap: exact, no pad
    assert prompt_length_ladder(6) == (6,)


def test_prefill_compiles_bounded_by_ladder(built, mesh):
    """Distinct prefill compilations track ladder rungs, not distinct
    prompt lengths: 6 different lengths land in 2 (batch, rung) shapes."""
    cfg, model, params = built["dense"]
    rng = np.random.default_rng(6)
    srv = _sim_server(model, mesh, slots=2, fused=True)
    for n in (3, 4, 5, 9, 11, 13):
        srv.submit(rng.integers(0, cfg.vocab_size, size=n), max_new_tokens=2)
    srv.run(params)
    assert len(srv.completed) == 6
    # bound: batch rungs {1,2} x ladder rungs {8,16} under this trace
    assert srv.prefill_compiles <= 4
    # reference engine compiles one shape per distinct length
    srv_ref = _sim_server(model, mesh, slots=2, fused=False)
    for n in (3, 4, 5, 9, 11, 13):
        srv_ref.submit(rng.integers(0, cfg.vocab_size, size=n),
                       max_new_tokens=2)
    srv_ref.run(params)
    assert srv_ref.prefill_compiles >= 6


# ---------------------------------------------------------------------------
# Pallas decode attention through the backend switch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 64])
def test_pallas_backend_parity_including_zero_lengths(window):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 64)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(3, 256, 2, 64)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(3, 256, 2, 64)), jnp.float32)
    lengths = jnp.asarray([0, 17, 256], jnp.int32)     # incl. empty row
    ref = attention_decode(q, kc, vc, lengths, window=window)
    prev = set_attention_backend("pallas")
    try:
        got = attention_decode_auto(q, kc, vc, lengths, window=window)
    finally:
        set_attention_backend(prev)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # the zero-length row attends to nothing on both paths
    np.testing.assert_array_equal(np.asarray(ref[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(got[0]), 0.0)


def test_pallas_backend_serving_stream_matches_jnp(built, mesh):
    """End-to-end: the same trace served with the Pallas decode-attention
    backend yields the same greedy token streams as the jnp path."""
    cfg, model, params = built["dense"]
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(3)]
    streams = {}
    for backend in ("jnp", "pallas"):
        prev = set_attention_backend(backend)
        try:
            srv = _sim_server(model, mesh, slots=2, fused=True)
            rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
            srv.run(params)
        finally:
            set_attention_backend(prev)
        streams[backend] = [srv.completed[r].tokens for r in rids]
    assert streams["pallas"] == streams["jnp"]
    assert get_attention_backend() == "jnp"


@pytest.mark.parametrize("backend,max_len,traced", [
    ("jnp", 64, "jnp"),
    ("pallas", 64, "pallas"),
    # no legal k tile divides a 600-row cache: off the TPU the request for
    # the kernel runs jnp, and the report says so
    ("pallas", 600, "jnp"),
])
def test_engine_report_names_the_traced_backend(built, mesh, backend,
                                                max_len, traced):
    cfg, model, params = built["dense"]
    prev = set_attention_backend(backend)
    try:
        srv = LMServer(model, mesh, serve_rules(False), slots=2,
                       max_len=max_len, clock=VirtualClock(),
                       service_model=lambda kind, b, t: 0.001)
        assert srv.engine_report()["attention_backend"] is None
        srv.submit(np.arange(8) % cfg.vocab_size, max_new_tokens=3)
        srv.run(params)
    finally:
        set_attention_backend(prev)
    assert len(srv.completed) == 1
    assert srv.engine_report()["attention_backend"] == traced


def _refused_decode_inputs(smax):
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 64)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(2, smax, 2, 64)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(2, smax, 2, 64)), jnp.float32)
    return q, kc, vc, jnp.asarray([5, smax], jnp.int32)


REFUSED = [(600, 0, None), (256, 0, 0.5), (256, jnp.int32(16), None)]
REFUSED_IDS = ["no-k-tile", "custom-scale", "traced-window"]


@pytest.mark.parametrize("smax,window,scale", REFUSED, ids=REFUSED_IDS)
def test_pallas_gate_raises_on_tpu(monkeypatch, smax, window, scale):
    """A Pallas request the shape gate refuses raises on a TPU rather than
    quietly running the jnp path."""
    from repro.models import common
    monkeypatch.setattr(common, "_on_tpu", lambda: True)
    prev = set_attention_backend("pallas")
    try:
        with pytest.raises(ValueError, match="cannot serve"):
            attention_decode_auto(*_refused_decode_inputs(smax),
                                  window=window, scale=scale)
    finally:
        set_attention_backend(prev)


@pytest.mark.parametrize("smax,window,scale", REFUSED, ids=REFUSED_IDS)
def test_pallas_gate_runs_jnp_off_tpu(smax, window, scale):
    args = _refused_decode_inputs(smax)
    ref = attention_decode(*args, window=window, scale=scale)
    prev = set_attention_backend("pallas")
    try:
        got = attention_decode_auto(*args, window=window, scale=scale)
    finally:
        set_attention_backend(prev)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# satellites: prefill AIMD budget, per-model completion telemetry
# ---------------------------------------------------------------------------

def test_prefill_aimd_budget_is_slo_fraction(built, mesh):
    cfg, model, params = built["dense"]
    srv = _sim_server(model, mesh, slots=2, slo=0.4, prefill_slo_frac=0.25)
    assert srv.admission.slo == pytest.approx(0.1)
    assert srv.slo == pytest.approx(0.4)


def test_per_model_completions_tagged(built, mesh):
    cfg, model, params = built["dense"]
    srv = _sim_server(model, mesh, slots=2, model_id="lm-a")
    rng = np.random.default_rng(9)
    for _ in range(5):
        srv.submit(rng.integers(0, cfg.vocab_size, size=8), max_new_tokens=3)
    srv.run(params)
    rep = srv.report()
    pm = rep["per_model"]["lm-a"]
    assert pm["completed"] == 5
    assert pm["latency_s"]["count"] == 5
    # the global series still carries every completion (dual emission)
    assert rep["queries"]["completed"] == 5
    assert rep["latency_s"]["count"] == 5
