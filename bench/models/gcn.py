"""GCN (Kipf & Welling 2017), as the reference and the work counts see it.

Layer l: ``H' = Ã · (dropout(H) W + b)`` with
``Ã = D̃^-1/2 (A + I) D̃^-1/2``; batch norm (where the configuration sets
``batchnorm``) and ReLU follow every layer but the last.

Every function takes the configuration's sizes ``s``: ``feat_dim``,
``hidden``, ``classes``, ``n_layers``, ``batchnorm``, ``model_args``.
"""
import jax
import numpy as np


def normalize(rows, cols, deg):
    """(rows, cols, values) of the propagation matrix, from the edge list
    and each node's degree."""
    n = deg.shape[0]
    loop = np.arange(n)
    rows, cols = np.concatenate([rows, loop]), np.concatenate([cols, loop])
    dt = deg.astype(np.float64) + 1.0
    return rows, cols, 1.0 / np.sqrt(dt[rows] * dt[cols])


def layer_widths(s):
    """Input and output width of each layer: the features, ``hidden``
    between layers, the classes."""
    return [s["feat_dim"]] + [s["hidden"]] * (s["n_layers"] - 1) \
        + [s["classes"]]


def init(s, key, p):
    """The parameter tree, with the program's key paths: ``p.dense(key,
    d_in, d_out)`` and ``p.bn(d)`` make one dense map and one batch norm."""
    d, n = layer_widths(s), s["n_layers"]
    keys = jax.random.split(key, n)
    return {"lin": [p.dense(keys[l], d[l], d[l + 1]) for l in range(n)],
            "bn": [p.bn(d[l + 1]) if s["batchnorm"] and l < n - 1 else None
                   for l in range(n)]}


def forward(s, params, x, f):
    """Logits: ``f.dropout(h)``, ``f.spmm(l)(h)``, ``f.dot(a, b)`` and
    ``f.bn(p, h)`` are the reference's operations."""
    n = len(params["lin"])
    h = x
    for l in range(n):
        p = params["lin"][l]
        h = f.spmm(l)(f.dot(f.dropout(h), p["w"]) + p["b"])
        if l < n - 1:
            if params["bn"][l] is not None:
                h = f.bn(params["bn"][l], h)
            h = jax.nn.relu(h)
    return h


def sampled_layers(s):
    """Layers whose backward SpMM RSC samples: every one."""
    return list(range(s["n_layers"]))


def spmm_widths(s):
    """Widths of one step's forward SpMMs and backward SpMMs: GCN
    propagates each layer's output, and every weight gradient needs the
    backward SpMM."""
    fwd = layer_widths(s)[1:]
    return fwd, list(fwd)


def dense_maps(s):
    """(d_in, d_out) of each layer's dense maps; the first layer's input
    carries no gradient."""
    d = layer_widths(s)
    return [[(d[l], d[l + 1])] for l in range(len(d) - 1)]
