"""LM serving engine: continuous batching, admission control, completion,
and what it records of itself: program names, phase spans, request stamps."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHITECTURES, reduced_config
from repro.distributed.sharding import serve_rules
from repro.models.api import build_model
from repro.serving.engine import LMServer
from repro.launch.mesh import make_local_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


@pytest.fixture(scope="module")
def served(mesh):
    cfg = reduced_config(ARCHITECTURES["smollm-360m"])
    model = build_model(cfg, mesh, serve_rules(False))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_all_requests_complete(served, mesh):
    cfg, model, params = served
    srv = LMServer(model, mesh, serve_rules(False), slots=4, max_len=64)
    rng = np.random.default_rng(0)
    rids = [srv.submit(rng.integers(0, cfg.vocab_size, size=8),
                       max_new_tokens=5) for _ in range(9)]
    srv.run(params)
    assert len(srv.completed) == 9
    for rid in rids:
        assert len(srv.completed[rid].tokens) == 5


def test_greedy_decode_deterministic(served, mesh):
    cfg, model, params = served
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, size=8)
    outs = []
    for _ in range(2):
        srv = LMServer(model, mesh, serve_rules(False), slots=2, max_len=64,
                       temperature=0.0)
        rid = srv.submit(prompt, max_new_tokens=6)
        srv.run(params)
        outs.append(srv.completed[rid].tokens)
    assert outs[0] == outs[1]


def test_continuous_batching_mixes_requests(served, mesh):
    """Late-arriving requests join while earlier ones still decode."""
    cfg, model, params = served
    srv = LMServer(model, mesh, serve_rules(False), slots=4, max_len=64)
    rng = np.random.default_rng(2)
    first = srv.submit(rng.integers(0, cfg.vocab_size, size=8),
                       max_new_tokens=12)
    srv.step(params)    # admit + decode once
    late = srv.submit(rng.integers(0, cfg.vocab_size, size=8),
                      max_new_tokens=3)
    srv.run(params)
    assert srv.completed[late].tokens and srv.completed[first].tokens
    assert len(srv.completed[first].tokens) == 12


def test_varied_prompt_lengths(served, mesh):
    cfg, model, params = served
    srv = LMServer(model, mesh, serve_rules(False), slots=4, max_len=64)
    rng = np.random.default_rng(3)
    rids = [srv.submit(rng.integers(0, cfg.vocab_size, size=n),
                       max_new_tokens=4)
            for n in (4, 8, 4, 16)]
    srv.run(params)
    assert len(srv.completed) == 4


def test_admission_programs_carry_their_names(served, mesh):
    """The XLA modules a profiler trace shows for admission's two programs
    are named after them."""
    cfg, model, params = served
    srv = LMServer(model, mesh, serve_rules(False), slots=4, max_len=64)
    pcache = jax.eval_shape(lambda: model.init_cache(2, 16))
    mask = jnp.zeros((4,), bool)
    idx = jnp.zeros((4,), jnp.int32)
    rows = jnp.zeros((2,), jnp.int32)
    scatter = srv._scatter_jit.lower(srv.cache, pcache, rows, np.int32(2))
    state = srv._admit_state_jit.lower(
        srv.lengths, srv.cur_tokens, srv.active_mask, srv.gen_counts,
        srv.max_new, mask, idx, rows, rows, rows)
    assert "@jit_admit_scatter " in scatter.as_text().split("\n", 1)[0]
    assert "@jit_admit_state " in state.as_text().split("\n", 1)[0]


def _engine_spans(log_dir):
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.end_ns) for plane in pd.planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("lm.step")]


def test_profiled_step_nests_the_engine_phases(served, mesh, tmp_path):
    """Under the profiler a step that admits and decodes shows lm.step
    around lm.step.admit and lm.step.decode, each around the spans in which
    it waits on the device."""
    cfg, model, params = served
    srv = LMServer(model, mesh, serve_rules(False), slots=4, max_len=64)
    rng = np.random.default_rng(4)
    srv.submit(rng.integers(0, cfg.vocab_size, size=8), max_new_tokens=4)
    srv.step(params)                 # compile outside the trace
    srv.submit(rng.integers(0, cfg.vocab_size, size=8), max_new_tokens=4)
    with jax.profiler.trace(str(tmp_path)):
        srv.step(params)
    spans = _engine_spans(str(tmp_path))

    def one(name):
        found = [s for s in spans if s[0] == name]
        assert len(found) == 1, (name, spans)
        return found[0]

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    step, admit, decode = one("lm.step"), one("lm.step.admit"), \
        one("lm.step.decode")
    assert inside(admit, step) and inside(decode, step)
    assert admit[2] <= decode[1]
    for name in ("lm.step.batch", "lm.step.prefill", "lm.step.wait_prefill",
                 "lm.step.wait_first_token", "lm.step.scatter"):
        assert inside(one(name), admit), name
    for name in ("lm.step.dispatch", "lm.step.wait_decode",
                 "lm.step.tokens"):
        assert inside(one(name), decode), name
    assert {s[0] for s in spans if s[0].startswith("lm.step.wait_")} == {
        "lm.step.wait_prefill", "lm.step.wait_first_token",
        "lm.step.wait_decode"}


@pytest.mark.parametrize("fused", [True, False])
def test_request_stamps_without_a_tracer(served, mesh, fused):
    """With no tracer each request still carries its dispatch, prefill end
    and one stamp per token, in order; engine_report()'s TTFT and
    inter-token gaps are read from those stamps."""
    cfg, model, params = served
    srv = LMServer(model, mesh, serve_rules(False), slots=4, max_len=64,
                   fused=fused)
    assert srv.tracer is None
    rng = np.random.default_rng(5)
    for n in (4, 8, 4, 16, 8, 6):
        srv.submit(rng.integers(0, cfg.vocab_size, size=n),
                   max_new_tokens=int(rng.integers(2, 7)))
    srv.run(params)
    done = list(srv.completed.values())
    assert len(done) == 6
    for r in done:
        assert r.arrival_time <= r.dispatch_time <= r.prefill_end
        assert len(r.token_times) == len(r.tokens)
        assert r.prefill_end <= r.token_times[0]
        assert np.all(np.diff(r.token_times) >= 0)
        assert r.token_times[-1] <= r.finish_time
    rep = srv.engine_report()
    ttft = [r.token_times[0] - r.arrival_time for r in done]
    gaps = [b - a for r in done for a, b in zip(r.token_times,
                                                r.token_times[1:])]
    for key, vals in (("ttft_s", ttft), ("tpot_s", gaps)):
        assert rep[key]["count"] == len(vals)
        assert rep[key]["p50"] == pytest.approx(np.percentile(vals, 50))
        assert rep[key]["p95"] == pytest.approx(np.percentile(vals, 95))
    assert not hasattr(done[0], "prefill_time")
