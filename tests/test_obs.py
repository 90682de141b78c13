"""Span tracing + deadline-budget attribution (DESIGN.md §13).

The tracer's contract is the same determinism bar as the metrics layer:
under the virtual clock, two runs of the same seed produce byte-identical
``repro.trace/v1`` span logs, per-query attributions partition end-to-end
latency exactly, and child spans nest within their parents. Sampling is
head-based and a pure function of (seed, trace_id). These tests exercise
the contract on all three stacks plus the export and validation CLIs.
"""

import dataclasses
import json

import pytest

from repro.metrics.validate import (validate_document, validate_report,
                                    validate_trace)
from repro.obs import Tracer
from repro.obs.export import chrome_trace
from repro.obs.tracer import Span, SpanLog, sample_decision
from repro.workloads.scenario import Scenario, ScenarioRunner

_FE = dict(rate=200.0, duration=0.2, seed=11)
_LM = dict(duration=0.05, rate=200.0, lm_requests=5, slots=2,
           prompt_len=4, max_new_tokens=2, seed=11)


def _run_traced(stack, **kw):
    sc = Scenario("t", **kw)
    tr = Tracer(sample_rate=1.0, seed=sc.seed)
    rep = ScenarioRunner(sc, tracer=tr).run(stack)
    return rep, tr


def _run_pipeline_traced(shape="cascade"):
    from repro.pipeline.scenario import pipeline_scenario, run_pipeline
    sc = dataclasses.replace(pipeline_scenario("pipeline"),
                             duration=0.2, rate=40.0, seed=11)
    tr = Tracer(sample_rate=1.0, seed=sc.seed)
    rep = run_pipeline(sc, shape, tracer=tr)
    return rep, tr


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_deterministic_and_calibrated():
    ids = range(1, 4001)
    picks = {t for t in ids if sample_decision(7, t, 0.3)}
    assert picks == {t for t in ids if sample_decision(7, t, 0.3)}
    assert 0.2 < len(picks) / 4000 < 0.4            # calibrated to the rate
    assert picks != {t for t in ids if sample_decision(8, t, 0.3)}
    assert all(sample_decision(7, t, 1.0) for t in ids)
    assert not any(sample_decision(7, t, 0.0) for t in ids)


def test_unsampled_traces_consume_ids_and_propagate_none():
    tr = Tracer(sample_rate=0.0, seed=0)
    root = tr.start_trace("query", "frontend", 0.0)
    assert root is None
    # every downstream call tolerates the None root silently
    assert tr.start_span(root, "queue", "frontend.queue", 0.0) is None
    tr.end_span(None, 1.0)
    tr.event(root, "hit", "frontend.cache", 0.5)
    tr.end_trace(root, 1.0, attribution={"frontend.queue": 1.0})
    assert tr.traces == 1 and tr.sampled == 0
    assert len(tr.spans()) == 0
    assert tr.attribution_report()["queries"] == 0


def test_sampled_subset_identical_across_runs():
    def subset():
        tr = Tracer(sample_rate=0.5, seed=3)
        kept = []
        for i in range(200):
            root = tr.start_trace("query", "frontend", float(i))
            if root is not None:
                kept.append(root.trace_id)
                tr.end_trace(root, i + 1.0)
        return kept
    a, b = subset(), subset()
    assert a == b
    assert 0 < len(a) < 200


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------

def test_spanlog_ring_bounds_memory_and_counts_dropped():
    log = SpanLog(capacity=8)
    for i in range(20):
        log.append(Span(i, 1, None, f"s{i}", "c", float(i), end=float(i)))
    assert len(log) == 8
    assert log.total == 20
    assert log.dropped == 12
    assert [s.name for s in log.spans()] == [f"s{i}" for i in range(12, 20)]


def test_tracer_reports_drops_in_summary_and_document():
    tr = Tracer(sample_rate=1.0, seed=0, capacity=4)
    for i in range(10):
        root = tr.start_trace("query", "frontend", float(i))
        tr.end_trace(root, i + 0.5)
    doc = tr.to_dict()
    assert doc["dropped"] == 6 and len(doc["spans"]) == 4
    assert doc["spans_total"] == 10


# ---------------------------------------------------------------------------
# determinism: byte-identical span logs per seed, all three stacks
# ---------------------------------------------------------------------------

def test_frontend_trace_byte_identical_per_seed():
    _, t1 = _run_traced("frontend", **_FE)
    _, t2 = _run_traced("frontend", **_FE)
    assert t1.to_json() == t2.to_json()
    assert len(t1.spans()) > 0


def test_lmserver_trace_byte_identical_per_seed():
    _, t1 = _run_traced("lmserver", **_LM)
    _, t2 = _run_traced("lmserver", **_LM)
    assert t1.to_json() == t2.to_json()
    assert len(t1.spans()) > 0


def test_pipeline_trace_byte_identical_per_seed():
    _, t1 = _run_pipeline_traced()
    _, t2 = _run_pipeline_traced()
    assert t1.to_json() == t2.to_json()
    assert len(t1.spans()) > 0


# ---------------------------------------------------------------------------
# attribution: exact partition of end-to-end latency
# ---------------------------------------------------------------------------

def _roots(tr, name):
    return [s for s in tr.spans()
            if s.parent_id is None and s.kind == "span" and s.name == name]


@pytest.mark.parametrize("stack,root,kw", [
    ("frontend", "query", _FE),
    ("lmserver", "request", _LM),
])
def test_per_query_attribution_partitions_latency(stack, root, kw):
    rep, tr = _run_traced(stack, **kw)
    roots = _roots(tr, root)
    attributed = [r for r in roots if (r.attrs or {}).get("attribution")]
    assert attributed, "expected at least one attributed query"
    for r in attributed:
        total = sum(r.attrs["attribution"].values())
        assert total == pytest.approx(r.end - r.start, abs=1e-9)
    att = rep["latency_attribution"]
    assert att["queries"] == len(attributed)
    fracs = [c["fraction"] for c in att["components"].values()]
    assert sum(fracs) == pytest.approx(1.0, abs=1e-6)
    assert all(f >= 0 for f in fracs)


def test_pipeline_attribution_covers_stages_and_sums_to_one():
    rep, tr = _run_pipeline_traced()
    att = rep["latency_attribution"]
    assert att["queries"] > 0
    assert any(c.startswith("pipeline.stage.") for c in att["components"])
    assert sum(c["fraction"] for c in att["components"].values()) \
        == pytest.approx(1.0, abs=1e-6)
    for r in _roots(tr, "pipeline"):
        a = (r.attrs or {}).get("attribution")
        if a:
            assert sum(a.values()) == pytest.approx(r.end - r.start, abs=1e-9)


# ---------------------------------------------------------------------------
# span structure
# ---------------------------------------------------------------------------

def test_child_spans_nest_within_parent_bounds():
    for _, tr in (_run_traced("frontend", **_FE),
                  _run_traced("lmserver", **_LM)):
        doc = tr.to_dict()
        assert validate_trace(doc) == []           # includes nesting checks
        by_id = {s["span_id"]: s for s in doc["spans"]}
        checked = 0
        for s in doc["spans"]:
            p = by_id.get(s["parent_id"])
            if p is None:
                continue
            assert s["start"] >= p["start"] - 1e-9
            assert s["end"] <= p["end"] + 1e-9
            checked += 1
        assert checked > 0


def test_budget_annotations_present_on_roots_and_stages():
    _, tr = _run_traced("frontend", **_FE)
    assert all(r.budget_s is not None for r in _roots(tr, "query"))
    rep, tp = _run_pipeline_traced()
    stages = [s for s in tp.spans() if s.component == "pipeline.stage"]
    assert stages and all(s.budget_s is not None and s.budget_s > 0
                          for s in stages)
    # planner shares: each stage budget is bounded by the pipeline SLO
    slo = rep["slo"]["target_s"]
    assert all(s.budget_s <= slo + 1e-9 for s in stages)


def test_tracing_off_by_default_adds_no_report_sections():
    rep = ScenarioRunner(Scenario("t", **_FE)).run("frontend")
    assert "latency_attribution" not in rep
    assert "trace" not in rep


def test_lm_report_always_carries_engine_section():
    rep = ScenarioRunner(Scenario("t", **_LM)).run("lmserver")
    eng = rep["engine"]
    assert set(eng) == {"fused", "attention_backend", "prefill", "decode",
                        "ttft_s", "tpot_s"}
    assert eng["ttft_s"]["count"] >= 1 and eng["ttft_s"]["p95"] >= 0
    assert eng["tpot_s"]["count"] >= 1
    assert eng["prefill"]["dispatches"] >= 1
    assert eng["prefill"]["compiled_shapes"] == len(eng["prefill"]["shapes"])
    assert eng["decode"]["steps"] >= 1
    assert eng["decode"]["host_syncs_per_step"] is not None


# ---------------------------------------------------------------------------
# export + validation
# ---------------------------------------------------------------------------

def test_chrome_export_structure_and_determinism():
    _, tr = _run_traced("frontend", **_FE)
    doc = tr.to_dict()
    ct = chrome_trace(doc)
    evs = [e for e in ct["traceEvents"] if e["ph"] != "M"]
    assert evs
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
    spans = {s["span_id"]: s for s in doc["spans"]}
    # microsecond conversion is exact for one known span
    s = next(iter(spans.values()))
    assert any(abs(e["ts"] - s["start"] * 1e6) < 1e-6 for e in evs)
    assert json.dumps(chrome_trace(doc), sort_keys=True) \
        == json.dumps(chrome_trace(doc), sort_keys=True)


def test_chrome_export_rejects_wrong_schema():
    with pytest.raises(ValueError):
        chrome_trace({"schema": "repro.metrics/v1", "spans": []})


def test_validator_accepts_real_reports_and_traces():
    rep, tr = _run_traced("frontend", **_FE)
    assert validate_report(rep) == []
    assert validate_trace(tr.to_dict()) == []
    assert validate_document(rep) == []
    assert validate_document({"schema": "nope"}) != []


def test_validator_flags_schema_violations():
    rep, tr = _run_traced("frontend", **_FE)
    bad = dict(rep)
    bad["duration_s"] = 0
    bad["throughput_qps"] = 12.0       # must be null on a degenerate span
    assert any("throughput_qps" in e for e in validate_report(bad))
    doc = tr.to_dict()
    doc["spans"] = [dict(doc["spans"][0], start=5.0, end=1.0)]
    assert any("end" in e for e in validate_trace(doc))
    att = {"queries": 2, "total_latency_s": 1.0,
           "components": {"a": {"seconds": 0.7, "fraction": 0.7}}}
    assert any("sum" in e for e in validate_trace(
        {**tr.to_dict(), "attribution": att}))


def test_validate_cli_roundtrip(tmp_path):
    from repro.metrics.validate import main
    rep, tr = _run_traced("frontend", **_FE)
    rp = tmp_path / "report.json"
    tp = tmp_path / "trace.json"
    rp.write_text(json.dumps(rep))
    tp.write_text(tr.to_json())
    assert main([str(rp), str(tp)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": "nope"}))
    assert main([str(bad)]) == 1


def test_export_cli_roundtrip(tmp_path):
    from repro.obs.export import main
    _, tr = _run_traced("frontend", **_FE)
    src = tmp_path / "trace.json"
    out = tmp_path / "chrome.json"
    src.write_text(tr.to_json())
    assert main([str(src), "-o", str(out)]) == 0
    ct = json.loads(out.read_text())
    assert ct["traceEvents"]
    assert ct["otherData"]["schema"] == "repro.trace/v1"
