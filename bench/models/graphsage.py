"""GraphSAGE with the mean aggregator (Hamilton et al. 2017), as the
reference and the work counts see it.

Layer l: ``H' = H Ws + bs + (D^-1 A H) Wn + bn`` on ``H = dropout(H)``;
batch norm (where the configuration sets ``batchnorm``) and ReLU follow
every layer but the last. The functions' arguments are as in ``gcn.py``.
"""
import jax

import spec

layer_widths = spec.model_module("gcn").layer_widths


def normalize(rows, cols, deg):
    """(rows, cols, values) of ``D^-1 A``."""
    return rows, cols, 1.0 / deg[rows].astype("float64")


def init(s, key, p):
    d, n = layer_widths(s), s["n_layers"]
    keys = jax.random.split(key, 2 * n)
    return {"self": [p.dense(keys[2 * l], d[l], d[l + 1]) for l in range(n)],
            "neigh": [p.dense(keys[2 * l + 1], d[l], d[l + 1])
                      for l in range(n)],
            "bn": [p.bn(d[l + 1]) if s["batchnorm"] and l < n - 1 else None
                   for l in range(n)]}


def forward(s, params, x, f):
    n = len(params["self"])
    h = x
    for l in range(n):
        sp, nb = params["self"][l], params["neigh"][l]
        h = f.dropout(h)
        h = (f.dot(h, sp["w"]) + sp["b"] + f.dot(f.spmm(l)(h), nb["w"])
             + nb["b"])
        if l < n - 1:
            if params["bn"][l] is not None:
                h = f.bn(params["bn"][l], h)
            h = jax.nn.relu(h)
    return h


def sampled_layers(s):
    """Layers whose backward SpMM RSC samples: all but the first, whose
    input (the features) carries no gradient."""
    return list(range(1, s["n_layers"]))


def spmm_widths(s):
    """GraphSAGE propagates each layer's input; the first layer's input
    (the features) carries no gradient, so it has no backward SpMM."""
    fwd = layer_widths(s)[:-1]
    return fwd, fwd[1:]


def dense_maps(s):
    d = layer_widths(s)
    return [[(d[l], d[l + 1])] * 2 for l in range(len(d) - 1)]
