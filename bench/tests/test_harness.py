"""The harness driven through the real ``LMServer`` programs at a tiny size
on the CPU: every metric each cell lists appears in every run, the trace
reduction finds the programs by the names they carry, and the comparison
passes sound runs and fails broken ones."""

import json

import numpy as np
import pytest

from bench import harness, readings
from bench import trace as trace_lib
from bench.tests import tiny

CELL = "smollm-chat"
SEEDS = [3, 17, 2147483659, 4000000007] + list(range(100, 108))


@pytest.fixture(scope="module")
def chat():
    man, conf, traffic = tiny.cell(CELL)
    sess = harness.Session(conf, traffic, SEEDS[0])
    return man, conf, traffic, sess


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("seed", SEEDS)
def test_every_listed_metric_in_every_run(chat, tmp_path, seed, trace):
    man, conf, traffic, sess = chat
    out = harness.run(CELL, seed, tiny.SECONDS, bool(trace), man=man,
                      conf=conf, traffic=traffic, peak_table=tiny.PEAKS,
                      out_dir=tmp_path, session=sess)
    res = out["result"]
    listed = harness.metrics_of(CELL, man,
                                "per_layer" if trace else "end_to_end")
    assert listed
    assert tiny.valid(res, listed) == []
    assert set(res["metrics"]) == {m["name"] for m in listed}
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert out["info"]["window_compiles"] == {}
    if trace:
        d = res["device"]
        assert 0 < d["busy_s"] <= d["window_s"]
        assert res["breakdown"]["device_ops"]
        assert out["info"]["slice"]["prefills"] >= 1
    assert list(res)[-1] == "checks"
    json.dumps(res, allow_nan=False)


def test_trace_reduction_finds_the_programs(chat, tmp_path):
    """A profiler trace of a real window: the prefill and decode programs
    are found under the module names read from them at warm-up, as often
    as the harness dispatched them in the slice."""
    man, conf, traffic, sess = chat
    assert sess.modules == {"prefill": "jit_fn", "decode": "jit_fused"}
    sess.reseed(5)
    win = sess.window(5, tiny.SECONDS, tmp_path / "trace")
    rec = win.record()
    tr = trace_lib.load(trace_lib.latest_xplane(str(tmp_path / "trace")))
    (dev,) = tr.devices.values()
    pre = trace_lib.executions(dev, "jit_fn")
    dec = trace_lib.executions(dev, "jit_fused")
    assert len(pre) == len(rec["slice"]["prefills"]) >= 1
    assert len(dec) == len(rec["slice"]["decode_steps"]) >= 1
    assert all(b > a for a, b in pre + dec)
    assert {h[0] for h in tr.host} >= {"bench.step", "bench.engine_decode"}
    rec.update(trace=tr, modules=sess.modules)
    assert readings.decode_seconds(rec) > 0
    rec["slice"]["decode_steps"].append([1])
    with pytest.raises(ValueError, match="executions of jit_fused"):
        readings.decode_seconds(rec)


def test_union_and_idle_gaps():
    dev = trace_lib.Device(ops=[("a", 0.0, 1.0, None), ("b", 0.5, 2.0, None),
                                ("c", 3.0, 4.0, None)])
    assert trace_lib.union([(o[1], o[2]) for o in dev.ops]) == [(0.0, 2.0),
                                                                (3.0, 4.0)]
    assert trace_lib.busy_seconds(dev) == 3.0
    tr = trace_lib.Trace({"d": dev}, [("bench.step", 0.0, 6.0),
                                       ("bench.record", 2.2, 2.8)])
    # gaps [2, 3] (middle inside bench.record) and [4, 6]
    assert trace_lib.idle_gaps(tr) == [["bench.step", 2.0],
                                       ["bench.record", 1.0]]
    assert trace_lib.module_base("jit_fused(123)") == "jit_fused"


def test_traffic_is_the_same_work_in_another_order():
    _, _, traffic = tiny.cell(CELL)
    from bench import traffic as tl
    a = tl.open_loop(traffic, 1, 10.0, 256)
    b = tl.open_loop(traffic, 2, 10.0, 256)
    again = tl.open_loop(traffic, 1, 10.0, 256)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in again]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert len(a) == 80 and a[0].due == 0.0 and a[-1].due < 10.0
    gaps = np.diff([r.due for r in a])
    assert np.isclose(sorted(np.diff([r.due for r in b]))[-1], gaps.max())


# -- the comparison fails a broken timed path ----------------------------------

def _token_altered(monkeypatch):
    """Each token the engine samples is the next id after its choice."""
    import repro.serving.engine as engine
    real = engine.sample

    def altered(logits, key, **kw):
        return (real(logits, key, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine, "sample", altered)


def _state_unchanged(monkeypatch):
    """The decode step hands back the slot cache and lengths it was given:
    no K/V row is kept and no position advances (it still emits its token
    and counts it, so requests end)."""
    import repro.serving.engine as engine
    real = engine.make_fused_decode_fn

    def made(*a, **k):
        fused = real(*a, **k)

        def stuck(params, cache, lengths, cur, active, gen, max_new, key):
            packed, _, _, cur2, act, gen2 = fused(params, cache, lengths, cur,
                                                  active, gen, max_new, key)
            return packed, cache, lengths, cur2, act, gen2
        return stuck
    monkeypatch.setattr(engine, "make_fused_decode_fn", made)


def _exchange_left_out(monkeypatch):
    """The feed-forward's sum over chips left out: the result is what the
    first of four chips holds before the all-reduce."""
    import jax
    import repro.models.transformer as transformer

    def first_quarter(p, x):
        q = p["wg"].shape[-1] // 4
        h = jax.nn.silu(x @ p["wg"][:, :q]) * (x @ p["wi"][:, :q])
        return h @ p["wo"][:q]
    monkeypatch.setattr(transformer, "glu_apply", first_quarter)


@pytest.mark.parametrize("cell_name,fault", [
    ("smollm-chat", _token_altered),
    ("smollm-chat", _state_unchanged),
    ("granite-code-tp4", _token_altered),
    ("granite-code-tp4", _state_unchanged),
    ("granite-code-tp4", _exchange_left_out),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, tmp_path,
                                            cell_name, fault):
    fault(monkeypatch)
    man, conf, traffic = tiny.cell(cell_name)
    # longer answers and a larger sample, so that a fault has positions to
    # show at
    traffic["output_len"].update(median=40, min=24, max=64)
    conf["check"]["compared_tokens"] = 200
    out = harness.run(cell_name, 11, tiny.SECONDS, False, man=man, conf=conf,
                      traffic=traffic, peak_table=tiny.PEAKS,
                      out_dir=tmp_path)
    res = out["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > conf["check"]["logit_gap"]
    assert res["checks"]["compared_tokens"]["value"] >= 200


@pytest.mark.parametrize("cell_name", ["smollm-chat", "granite-code-tp4"])
def test_the_control_is_not_correct(tmp_path, cell_name):
    """The reference in the configuration's control precision, put in the
    program's place on the tokens a real window served, reads a gap above
    the limit; the program itself reads one below it."""
    man, conf, traffic = tiny.cell(cell_name, num_layers=8, d_model=256,
                                   num_heads=8, num_kv_heads=4, head_dim=32,
                                   d_ff=512, vocab_size=4096)
    traffic["output_len"].update(median=40, min=24, max=64)
    sess = harness.Session(conf, traffic, 23)
    win = sess.window(23, tiny.SECONDS, None, "drain")
    chk = conf["check"]
    sample = harness.sample_finished(win.tracks, 23, 200,
                                     chk["reference_tokens"])
    gaps = harness.compare(conf["arch"], 23, sample, ("f32", chk["control"]))
    assert gaps["f32"].size >= 200
    assert gaps["f32"].max() <= chk["logit_gap"]
    assert gaps[chk["control"]].max() > chk["logit_gap"]


def test_self_time_takes_nested_ops_out_of_their_parent():
    ops = [("while", 0.0, 10.0, None), ("copy", 1.0, 4.0, None),
           ("fusion", 2.0, 3.0, None), ("dot", 5.0, 6.0, None),
           ("after", 11.0, 12.0, None)]
    assert trace_lib.self_seconds(ops) == [6.0, 2.0, 1.0, 1.0, 1.0]
    tr = trace_lib.Trace({"d": trace_lib.Device(ops=ops)}, [])
    assert trace_lib.top_ops(tr, 2) == [["while", 6.0], ["copy", 2.0]]
