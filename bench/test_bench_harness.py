"""Whole runs of small cells on the CPU, with the look for a chip skipped:
a sound program comes out correct, a broken one does not."""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

import faults
import harness
import spec
from smallcells import small

SEED = 2 ** 33 + 17


def run(cell, patch=None):
    return harness.run_cell(cell, seed=SEED, seconds=1.0, trace=False,
                            require_chip=False, patch_engine=patch)


@pytest.mark.parametrize("workload", ["gcn-reddit-rsc", "sage-reddit-rsc",
                                      "gcn-reddit-exact"])
def test_sound_run_is_correct(workload):
    r = run(small(workload))
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"epoch_s", "test_acc", "setup_s"}
    assert all(m["value"] > 0 and math.isfinite(m["value"])
               for m in r["metrics"].values())
    assert r["device"]["count"] == 1
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("workload,fault", [
    *[("gcn-reddit-rsc", f) for f in faults.EXACT_FAULTS],
    ("sage-reddit-rsc", "rescaled_sample"),
    *[("gcn-reddit-exact", f) for f in faults.EXACT_FAULTS]])
def test_broken_step_is_not_correct(workload, fault):
    r = run(small(workload), faults.FAULTS[fault])
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_report_puts_checks_last(capsys):
    result = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
              "device": {}, "checks": {"loss_gap": {"value": 1e-6,
                                                    "limit": 1e-3}}}
    harness.report(result)
    out, err = capsys.readouterr()
    assert json.loads(out.splitlines()[-1]) == result
    assert err.splitlines()[-1] == "loss_gap 1e-06 limit 0.001"


def test_no_chip_means_no_result(capsys):
    assert harness.main(["--workload", "gcn-reddit-rsc", "--seed", "1",
                         "--seconds", "1"]) == 3
    assert "correct" not in capsys.readouterr().out


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "gcn-reddit-rsc", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout
