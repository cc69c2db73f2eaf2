"""Each model's file, ``bench/models/<model>.py``: the references of the
benchmark's cells read as they did before the files took over the layer
stacks, and GCNII, which no cell runs yet, is checked through the whole
harness."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

import compare
import faults
import graphgen
import harness
import spec
import work
from smallcells import small

SEED = 2 ** 33 + 17
RECORDED = json.loads((Path(__file__).resolve().parent / "testdata"
                       / "reference_small.json").read_text())["cells"]
COUNTS = {"rsc_steps": 160, "exact_steps": 40, "evals": 20}
FULL = {"nodes": 23296, "nnz": 2344628, "hidden": 256, "classes": 41,
        "feat_dim": 602}
REL = 1e-6


def _close(got: dict, want: dict, keys=None) -> None:
    """Per-leaf norms within ``REL`` of the larger of the leaf's recorded
    norm and the median leaf's."""
    keys = list(want) if keys is None else keys
    assert set(got) == set(want)
    floor = float(np.median([want[k] for k in keys]))
    for k in keys:
        assert abs(got[k] - want[k]) <= REL * max(want[k], floor), k


@pytest.mark.parametrize("workload", sorted(RECORDED))
def test_reference_and_counts_did_not_move(workload):
    want = RECORDED[workload]
    cell = small(workload)
    graph = graphgen.generate(cell.config, SEED)
    ref = harness.reference_run(cell, graph, harness.program_seed(SEED))
    r = harness.refresh_step(cell.traffic)
    assert ref["losses"] == pytest.approx(want["losses"], rel=REL, abs=0)
    for t in (0, r):
        _close(compare._norms(ref["grads"][t]), want["grad_norms"][str(t)])
    g0 = want["grad_norms"]["0"]
    moved = [k for k, v in g0.items()
             if v >= compare.STILL * float(np.median(list(g0.values())))]
    _close(compare._norms(ref["params"][compare.UPDATE_STEPS]),
           want["param_norms"], moved)
    _close(compare._change_norms(ref), want["change_norms"], moved)
    peak = work.peaks_for("TPU v5 lite")
    small_shape = work.shape_of(cell.config, graph)
    for size, shape in (("small", small_shape),
                        ("full", dict(small_shape, **FULL))):
        assert want["counts"][size] == {
            "spmm_least_seconds": work.spmm_least_seconds(
                shape, COUNTS, cell.traffic["budget"], peak),
            "model_flops": work.model_flops(shape),
            "eval_flops": work.model_flops(shape, train=False)}


def gcnii_cell(workload: str) -> spec.Cell:
    """``gcn-reddit``'s configuration as GCNII with 4 propagation layers,
    cut as the small cells are, under ``gcn-reddit-rsc``'s limits."""
    cell = small(workload, model="gcnii", n_layers=4)
    cell.limits = spec.load_cell("gcn-reddit-rsc").limits
    return cell


def run(cell, patch=None):
    return harness.run_cell(cell, seed=SEED, seconds=1.0, trace=False,
                            require_chip=False, patch_engine=patch)


@pytest.mark.parametrize("workload", ["gcn-reddit-rsc", "gcn-reddit-exact"])
def test_gcnii_sound_run_is_correct(workload):
    r = run(gcnii_cell(workload))
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(m["value"] > 0 and math.isfinite(m["value"])
               for m in r["metrics"].values())


@pytest.mark.parametrize("workload", ["gcn-reddit-rsc", "gcn-reddit-exact"])
@pytest.mark.parametrize("fault", faults.EXACT_FAULTS)
def test_gcnii_broken_step_is_not_correct(workload, fault):
    r = run(gcnii_cell(workload), faults.FAULTS[fault])
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_gcnii_work_counts_follow_its_layers():
    s = dict(FULL, model="gcnii", n_layers=4)
    assert work.spmm_widths(s) == ([256] * 4, [256] * 4)
    n, nnz = FULL["nodes"], FULL["nnz"]
    dense = 2 * n * (602 * 256 + 4 * 256 * 256 + 256 * 41)
    spmm = 2 * nnz * 256 * 4
    dx = dense - 2 * n * 602 * 256
    assert work.model_flops(s, train=False) == dense + spmm
    assert work.model_flops(s) == dense + spmm + dense + dx + spmm


def test_gcnii_reference_takes_model_args():
    cell = small("gcn-reddit-exact", model="gcnii", n_layers=4)
    cell.config["nodes"] = 512
    graph = graphgen.generate(cell.config, SEED)
    pseed = harness.program_seed(SEED)
    default = harness.reference_run(cell, graph, pseed)["losses"]
    cell.config["model_args"] = {"alpha": 0.1, "lam": 0.5}
    assert harness.reference_run(cell, graph, pseed)["losses"] == default
    cell.config["model_args"] = {"alpha": 0.2, "lam": 1.0}
    assert harness.reference_run(cell, graph, pseed)["losses"][0] != \
        default[0]
