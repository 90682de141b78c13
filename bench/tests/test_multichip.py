"""The four-chip cell at a tiny size over four virtual CPU devices, for a
dozen seeds in both trace modes: the sharded decode step, its collectives
found in the trace, every listed metric present."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
CELL = "granite-code-tp4"
SEEDS = [3, 17, 2147483659, 4000000007] + list(range(100, 108))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(ROOT), str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.multichip",
         str(tmp_path_factory.mktemp("multichip"))] + [str(s) for s in SEEDS],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return {(r["seed"], r["trace"]): r for r in
            map(json.loads, proc.stdout.strip().splitlines())}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("seed", SEEDS)
def test_four_chip_cell_lists_every_metric(runs, seed, trace):
    man = harness.manifest(ROOT)
    r = runs[(seed, trace)]
    res = r["result"]
    listed = harness.metrics_of(CELL, man, "per_layer" if trace else
                                "end_to_end")
    assert tiny.valid(res, listed) == []
    assert set(res["metrics"]) == {m["name"] for m in listed}
    assert res["device"]["count"] == 4
    assert res["correct"], res["checks"]
    assert r["info"]["window_compiles"] == {}
    if trace:
        assert "collective_share" in res["metrics"]
