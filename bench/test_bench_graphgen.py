import numpy as np

import graphgen

SMALL = {"nodes": 2000, "classes": 41, "avg_degree": 49.82262571630073,
         "feat_dim": 8, "feature_noise": 1.0, "label_rate": 0.6586,
         "topology_seed": 3}


def test_reaches_the_target_edge_count_it_states():
    g = graphgen.generate(SMALL, seed=5)
    want = graphgen.target_edges(SMALL["nodes"], SMALL["avg_degree"])
    assert g["target_edges"] == want == 49822
    assert g["rows"].size == 2 * want
    key = g["rows"].astype(np.int64) * SMALL["nodes"] + g["cols"]
    assert np.unique(key).size == key.size          # no duplicate edges
    assert not np.any(g["rows"] == g["cols"])       # no self-loops
    back = g["cols"].astype(np.int64) * SMALL["nodes"] + g["rows"]
    assert np.array_equal(np.sort(key), np.sort(back))   # undirected


def test_seed_draws_node_data_and_topology_stays():
    a = graphgen.generate(SMALL, seed=5)
    b = graphgen.generate(SMALL, seed=5)
    c = graphgen.generate(SMALL, seed=2 ** 33 + 5)
    for k in ("rows", "cols", "labels"):
        assert np.array_equal(a[k], c[k])
    for k in ("features", "train_mask", "test_mask"):
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["features"], c["features"])
    assert not np.array_equal(a["train_mask"], c["train_mask"])
    assert not np.any(a["train_mask"] & a["test_mask"])
