"""The breakdown by the engine's own names, driven through the real
``LMServer`` programs at a tiny size on the CPU: the decode program's ops
fall into their named scopes, device idle is named by the engine's phases,
and the engine's token stamps agree with the harness's own times."""

import numpy as np
import pytest

from bench import breakdown, harness
from bench import trace as trace_lib
from bench.tests import tiny

CELL = "smollm-chat"


@pytest.fixture(scope="module")
def chat():
    man, conf, traffic = tiny.cell(CELL)
    return man, conf, traffic, harness.Session(conf, traffic, 7)


def test_breakdown_reads_the_engine_phases(chat, tmp_path):
    man, conf, traffic, sess = chat
    out = breakdown.breakdown(CELL, 9, tiny.SECONDS, man=man, conf=conf,
                              traffic=traffic, out_dir=tmp_path, session=sess)
    scopes = out["decode_scopes"]
    assert scopes["attention"] > 0 and scopes["kv_update"] > 0
    assert scopes["mlp"] > 0 and scopes["lm_head"] > 0
    assert scopes["missing"] == 0
    named = {name for name, _ in out["idle_gaps"]}
    assert {"lm.step.dispatch", "lm.step.wait_decode"} <= named
    for key in ("host_step_ms", "admit_wait_p95_ms", "decode_attention_ms",
                "admit_device_ms", "bench_step_ms", "decode_step_ms"):
        assert out[key] is not None and out[key] > 0, key
    assert out["host_step_ms"] < out["bench_step_ms"]
    assert out["slice"]["decode_steps"] >= 1


def test_token_stamps_precede_the_harness_times(chat):
    """One engine stamp per token, in order, none later than the return of
    the step that the harness saw deliver it."""
    man, conf, traffic, sess = chat
    sess.reseed(13)
    win = sess.window(13, tiny.SECONDS, None)
    seen = [t for t in win.tracks if t.seen]
    assert seen
    for t in seen:
        stamps = t.obj.token_times
        assert len(stamps) == len(t.obj.tokens) >= t.seen
        assert np.all(np.diff(stamps) >= 0)
        assert all(a <= b for a, b in zip(stamps, t.times))
        assert t.due <= t.obj.dispatch_time <= stamps[0]
    sess.empty()


def test_scope_table_reads_op_name_metadata():
    hlo = "\n".join([
        "HloModule jit_fused, is_scheduled=true",
        '  %fusion.3 = bf16[4]{0} fusion(%p), kind=kLoop, metadata={'
        'op_name="jit(fused)/while/body/closed_call/attention/exp"}',
        '  ROOT %copy.7 = f32[2]{0} copy(%x), metadata={op_name="jit(fused)'
        '/while/body/kv_update/vmap(vmap())/scatter" source_line=3}',
        "  %bitcast_dynamic-update-slice_fusion.4 = f32[2]{0} fusion(%y)",
        '  dot.9 = f32[2]{0} dot(%a, %b), metadata={op_name="jit(fused)/'
        'lm_head/dot_general"}',
    ])
    assert breakdown.scope_table(hlo) == {
        "fusion.3": "attention", "copy.7": "kv_update",
        "bitcast_dynamic-update-slice_fusion.4": "unscoped",
        "dot.9": "lm_head"}
    dev = trace_lib.Device(ops=[("while", 0.0, 10.0, "m"),
                                ("fusion.3", 1.0, 4.0, "m"),
                                ("copy.7", 5.0, 6.0, "m"),
                                ("other", 11.0, 12.0, "m"),
                                ("dot.9", 13.0, 14.0, "n")])
    got = breakdown.scope_seconds(trace_lib.Trace({"d": dev}, []), "m",
                                  breakdown.scope_table(hlo))
    assert got["attention"] == 3.0 and got["kv_update"] == 1.0
    assert got["missing"] == 7.0 and got["lm_head"] == 0.0


def test_host_step_leaves_out_the_waits():
    host = [("bench.step", 0.0, 10.0), ("lm.step", 0.5, 9.5),
            ("lm.step.admit", 0.6, 4.0), ("lm.step.wait_prefill", 1.0, 3.0),
            ("lm.step.decode", 4.0, 9.0), ("lm.step.wait_decode", 5.0, 8.5),
            ("lm.step", 10.0, 11.0), ("lm.step.wait_decode", 12.0, 13.0)]
    assert breakdown.host_step_seconds(host) == [3.5, 1.0]


def test_admit_device_ms_counts_each_admission_once():
    read = harness.reader("admit_device_ms")
    dev = trace_lib.Device(modules=[("jit_admit_scatter", 0.0, 0.004),
                                    ("jit_admit_state", 0.004, 0.005),
                                    ("jit_fused", 0.005, 0.08)])
    rec = {"trace": trace_lib.Trace({"d": dev}, []),
           "slice": {"prefills": [[16]], "decode_steps": [[17]]}}
    assert read(rec) == pytest.approx(5.0)
    rec["slice"]["prefills"].append([20])
    with pytest.raises(ValueError, match="executions of jit_admit_scatter"):
        read(rec)
    rec["slice"]["prefills"] = []
    assert read(rec) is None
