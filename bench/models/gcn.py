"""GCN (Kipf & Welling 2017), as the reference and the work counts see it.

Layer l: ``H' = Ã · (dropout(H) W + b)`` with
``Ã = D̃^-1/2 (A + I) D̃^-1/2``; batch norm and ReLU follow every layer
but the last (in ``reference.py``).
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


def init(key, dims, dense):
    keys = jax.random.split(key, len(dims) - 1)
    return {"lin": [dense(keys[l], dims[l], dims[l + 1])
                    for l in range(len(dims) - 1)]}


def layer(params, l, h, spmm, dot):
    p = params["lin"][l]
    return spmm(dot(h, p["w"]) + p["b"])


def sampled_layers(n_layers):
    """Layers whose backward SpMM RSC samples: every one."""
    return list(range(n_layers))


def spmm_widths(dims):
    """Widths of one step's forward SpMMs and backward SpMMs: GCN
    propagates each layer's output, and every weight gradient needs the
    backward SpMM."""
    fwd = list(dims[1:])
    return fwd, list(fwd)


def dense_maps(dims):
    """(d_in, d_out) of each layer's dense maps."""
    return [[(dims[l], dims[l + 1])] for l in range(len(dims) - 1)]
