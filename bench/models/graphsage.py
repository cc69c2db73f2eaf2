"""GraphSAGE with the mean aggregator (Hamilton et al. 2017), as the
reference and the work counts see it.

Layer l: ``H' = H Ws + bs + (D^-1 A H) Wn + bn`` on ``H = dropout(H)``;
batch norm and ReLU follow every layer but the last (in ``reference.py``).
"""
import jax


def normalize(rows, cols, deg):
    """(rows, cols, values) of ``D^-1 A``."""
    return rows, cols, 1.0 / deg[rows].astype("float64")


def init(key, dims, dense):
    n = len(dims) - 1
    keys = jax.random.split(key, 2 * n)
    return {"self": [dense(keys[2 * l], dims[l], dims[l + 1])
                     for l in range(n)],
            "neigh": [dense(keys[2 * l + 1], dims[l], dims[l + 1])
                      for l in range(n)]}


def layer(params, l, h, spmm, dot):
    s, nb = params["self"][l], params["neigh"][l]
    return dot(h, s["w"]) + s["b"] + dot(spmm(h), nb["w"]) + nb["b"]


def sampled_layers(n_layers):
    """Layers whose backward SpMM RSC samples: all but the first, whose
    input (the features) carries no gradient."""
    return list(range(1, n_layers))


def spmm_widths(dims):
    """GraphSAGE propagates each layer's input; the first layer's input
    (the features) carries no gradient, so it has no backward SpMM."""
    fwd = list(dims[:-1])
    return fwd, fwd[1:]


def dense_maps(dims):
    return [[(dims[l], dims[l + 1])] * 2 for l in range(len(dims) - 1)]
