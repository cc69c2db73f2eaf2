"""Reddit-shaped synthetic graphs for the benchmark.

A degree-corrected stochastic block model with power-law degree
propensities, the same family as the program's ``graphs/synthetic.py``,
with one correction: edges are drawn in rounds until the graph holds
exactly the number of distinct undirected edges it states
(``target_edges``). The program's generator draws a fixed 2.2x surplus
once, keeps about a seventh of it (cross-cluster pairs are accepted with
probability 1/8), and so reaches only about a third of its own target.

The topology (cluster of each node, degree propensities, edges) comes from
the configuration's ``topology_seed``; the run's ``--seed`` draws the node
features and the train/val/test split. So every seed runs the same tiles
and the same compiled programs, with different data on them.

Pure numpy: the plain reference reads the same arrays the program gets.
"""
from __future__ import annotations

import numpy as np


def target_edges(nodes: int, avg_degree: float) -> int:
    """Distinct undirected edges of a graph with this average degree."""
    return int(nodes * avg_degree / 2)


def topology(nodes: int, classes: int, avg_degree: float, seed: int, *,
             p_in_out_ratio: float = 8.0, powerlaw: float = 1.6,
             round_size: int | None = None):
    """Cluster ids ``z`` and the directed edge list ``(rows, cols)``.

    Returns both directions of each of exactly ``target_edges`` distinct
    undirected edges, no self-loops, sorted by ``(row, col)``.
    """
    rng = np.random.default_rng(seed)
    z = rng.integers(0, classes, size=nodes)
    theta = rng.pareto(powerlaw, size=nodes) + 1.0
    p = theta / theta.sum()
    want = target_edges(nodes, avg_degree)
    round_size = round_size or max(2 * want, 1024)
    keys = np.zeros(0, np.int64)
    while keys.size < want:
        u = rng.choice(nodes, size=round_size, p=p)
        v = rng.choice(nodes, size=round_size, p=p)
        keep_prob = np.where(z[u] == z[v], 1.0, 1.0 / p_in_out_ratio)
        keep = (rng.random(round_size) < keep_prob) & (u != v)
        lo = np.minimum(u[keep], v[keep]).astype(np.int64)
        hi = np.maximum(u[keep], v[keep]).astype(np.int64)
        drawn = np.concatenate([keys, lo * nodes + hi])
        # first occurrence of each edge, in draw order
        _, first = np.unique(drawn, return_index=True)
        keys = drawn[np.sort(first)]
    keys = keys[:want]
    lo, hi = keys // nodes, keys % nodes
    rows = np.concatenate([lo, hi])
    cols = np.concatenate([hi, lo])
    order = np.lexsort((cols, rows))
    return z, rows[order], cols[order]


def node_data(z: np.ndarray, classes: int, feat_dim: int, label_rate: float,
              seed: int, *, noise: float = 1.0, val_rate: float = 0.1):
    """Features (noisy cluster centroids), labels and split masks."""
    rng = np.random.default_rng(seed)
    n = z.shape[0]
    centroids = rng.standard_normal((classes, feat_dim)).astype(np.float32)
    feats = centroids[z] + noise * rng.standard_normal(
        (n, feat_dim)).astype(np.float32)
    order = rng.permutation(n)
    n_train, n_val = int(label_rate * n), int(val_rate * n)
    masks = []
    for lo, hi in ((0, n_train), (n_train, n_train + n_val),
                   (n_train + n_val, n)):
        m = np.zeros(n, bool)
        m[order[lo:hi]] = True
        masks.append(m)
    return feats, z.astype(np.int64), masks[0], masks[1], masks[2]


def generate(graph: dict, seed: int) -> dict:
    """The graph a configuration describes (``nodes``, ``classes``,
    ``avg_degree``, ``feat_dim``, ``feature_noise``, ``label_rate``,
    ``topology_seed``), with the node data of ``seed``."""
    z, rows, cols = topology(graph["nodes"], graph["classes"],
                             graph["avg_degree"], graph["topology_seed"])
    feats, labels, tr, va, te = node_data(
        z, graph["classes"], graph["feat_dim"], graph["label_rate"], seed,
        noise=graph["feature_noise"])
    return {"nodes": graph["nodes"], "classes": graph["classes"],
            "rows": rows, "cols": cols, "features": feats, "labels": labels,
            "train_mask": tr, "val_mask": va, "test_mask": te,
            "target_edges": target_edges(graph["nodes"],
                                         graph["avg_degree"])}
