import json
import shutil

import numpy as np
import pytest

import compare
import graphgen
import reference
import spec
import work

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_by_name(workload):
    from repro.models.gnn import MODELS
    cell = spec.load_cell(workload)
    assert (spec.BENCH / "models" / f"{cell.config['model']}.py").is_file()
    assert cell.config["model"] in MODELS
    assert cell.traffic["mode"] == "full_batch"
    assert cell.limits and set(cell.limits) <= set(compare.NUMBERS)
    assert {m["name"] for m in cell.end_to_end} >= {"epoch_s", "setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_every_configuration_has_a_cell_and_a_file_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]


def test_per_layer_metrics_move_a_metric_every_listed_cell_reports():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A configuration, a traffic mix, a metric and a cell added as files
    and BENCHMARK.json entries, with no code changed."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((spec.BENCH / "configs" / "gcn-reddit.json").read_text())
    cfg.update(name="gcnii-reddit", model="gcnii")
    (tmp_path / "bench" / "configs" / "gcnii-reddit.json").write_text(
        json.dumps(cfg))
    traffic = json.loads(
        (spec.BENCH / "traffic" / "full-rsc.json").read_text())
    traffic["budget"] = 0.3
    (tmp_path / "bench" / "traffic" / "full-rsc-0.3.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "gcnii-reddit-rsc0.3.json").write_text(
        json.dumps({"loss_gap": 1, "grad_gap": 1, "update_gap": 1}))
    (tmp_path / "bench" / "metrics" / "refresh_count.py").write_text(
        "def read(ctx):\n    return ctx.counts['rsc_steps'] / 10\n")
    bench["configs"].append({"name": "gcnii-reddit", "source": "x",
                             "file": "bench/configs/gcnii-reddit.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "gcnii-reddit-rsc0.3",
                               "config": "gcnii-reddit",
                               "traffic": "full-rsc-0.3", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "refresh_count", "unit": "1",
                               "better": "lower", "source": "program_span",
                               "layer": "planner", "moves": "epoch_s",
                               "workloads": ["gcnii-reddit-rsc0.3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("gcnii-reddit-rsc0.3", root=tmp_path)
    assert cell.config["model"] == "gcnii"
    assert cell.traffic["budget"] == 0.3
    assert [m["name"] for m in cell.per_layer] == ["refresh_count"]
    read = spec.metric_reader("refresh_count", root=tmp_path)
    assert read(type("C", (), {"counts": {"rsc_steps": 40}})) == 4.0
    # the model's own file gives the reference and the work counts
    cell.config.update(nodes=512, classes=4, feat_dim=8, hidden=16)
    graph = graphgen.generate(cell.config, seed=3)
    prob = reference.problem(graph, "gcnii", cell.config["block"])
    cfg = {k: cell.config[k] for k in
           ("hidden", "n_layers", "batchnorm", "dropout", "lr")}
    out = reference.run(prob, dict(cfg, classes=4, model_args={}), 3, 1)
    assert len(out["losses"]) == 1 and np.isfinite(out["losses"][0])
    assert "proj_in" in out["params"][0]
    shape = work.shape_of(cell.config, graph)
    assert work.model_flops(shape) > work.model_flops(shape, train=False) > 0


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
