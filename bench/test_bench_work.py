import pytest

import work

GCN = {"model": "gcn", "n_layers": 3, "hidden": 256, "classes": 41,
       "feat_dim": 602, "nodes": 1000, "nnz": 20000}
SAGE = dict(GCN, model="graphsage")
PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e9}


def test_spmm_work_counts_nonzeros_not_tiles():
    flops, nbytes = work.spmm_work(nnz=20000, n=1000, d=256)
    assert flops == 2 * 20000 * 256
    assert nbytes == 20000 * 8 + 2 * 1000 * 256 * 4
    f2, b2 = work.spmm_work(nnz=20000, n=1000, d=256, frac=0.1)
    assert f2 == pytest.approx(0.1 * flops)
    assert b2 == pytest.approx(0.1 * nbytes)


def test_spmm_widths_follow_the_model():
    assert work.spmm_widths(GCN) == ([256, 256, 41], [256, 256, 41])
    assert work.spmm_widths(SAGE) == ([602, 256, 256], [256, 256])


def test_least_time_of_a_window():
    one = lambda d, frac=1.0: work.least_seconds(
        *work.spmm_work(20000, 1000, d, frac), PEAK)
    counts = {"rsc_steps": 2, "exact_steps": 1, "evals": 1}
    fwd = one(256) + one(256) + one(41)
    bwd_rsc = one(256, 0.1) + one(256, 0.1) + one(41, 0.1)
    want = 2 * (fwd + bwd_rsc) + 1 * (2 * fwd) + 1 * fwd
    assert work.spmm_least_seconds(GCN, counts, 0.1, PEAK) == \
        pytest.approx(want)


def test_model_flops():
    n, nnz = 1000, 20000
    dense = 2 * n * (602 * 256 + 256 * 256 + 256 * 41)
    spmm = 2 * nnz * (256 + 256 + 41)
    assert work.model_flops(GCN, train=False) == dense + spmm
    dx = dense - 2 * n * 602 * 256
    assert work.model_flops(GCN) == dense + spmm + dense + dx + spmm
    s_dense = 2 * dense
    s_fwd = 2 * nnz * (602 + 256 + 256)
    s_bwd = 2 * nnz * (256 + 256)
    s_dx = s_dense - 2 * 2 * n * 602 * 256
    assert work.model_flops(SAGE) == s_dense + s_fwd + s_dense + s_dx + s_bwd
    counts = {"rsc_steps": 3, "exact_steps": 2, "evals": 1}
    assert work.window_model_flops(GCN, counts) == \
        5 * work.model_flops(GCN) + work.model_flops(GCN, train=False)


def test_shape_counts_the_propagation_matrix():
    import graphgen
    cfg = {"nodes": 300, "classes": 4, "avg_degree": 10.0, "feat_dim": 8,
           "feature_noise": 1.0, "label_rate": 0.6, "topology_seed": 1, "n_layers": 3,
           "hidden": 16}
    g = graphgen.generate(cfg, seed=2)
    edges = 2 * graphgen.target_edges(300, 10.0)
    gcn = work.shape_of(dict(cfg, model="gcn"), g)
    sage = work.shape_of(dict(cfg, model="graphsage"), g)
    assert gcn["nnz"] == edges + 300       # self-loops
    assert sage["nnz"] == edges
    assert gcn["feat_dim"] == 8 and gcn["classes"] == 4


def test_peaks_are_read_from_the_table_and_unknown_devices_refused():
    assert work.peaks_for("TPU v5 lite") == {
        "flops_per_s": 1.97e14, "bytes_per_s": 8.19e11, "hbm_bytes": 1.6e10}
    with pytest.raises(work.UnknownDevice):
        work.peaks_for("cpu")
