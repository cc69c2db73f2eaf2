"""GCNII (Chen et al. 2020, arXiv 2007.02133), as the reference and the
work counts see it.

``H⁰ = ReLU(dropout(X) W_in + b_in)``; layer l = 0 .. L-1, on
``H = dropout(H^l)``:

    P  = Ã H,   Ã = D̃^-1/2 (A + I) D̃^-1/2 (GCN's propagation matrix)
    T  = (1 - α) P + α H⁰
    H^{l+1} = ReLU(BN((1 - β_l) T + β_l (T W^l + b^l))),
    β_l = log(λ / (l + 1) + 1);

logits ``dropout(H^L) W_out + b_out``. ``n_layers`` is L, the number of
propagation layers; α and λ are the configuration's ``model_args``
(``alpha``, ``lam``), by default 0.1 and 0.5 as in the program.

Where the program departs from the paper, this follows the program:
``W^l`` has a bias ``b^l``, and each propagation layer has a batch norm
where the configuration sets ``batchnorm``. Dropout, which the equations
leave out, sits where the program puts it: on the features, on each
layer's input (not on the ``H⁰`` mixed back in) and before the output
projection, each drawing the next key in that order.
"""
import math

import jax

import spec

normalize = spec.model_module("gcn").normalize


def _args(s):
    a = s.get("model_args", {})
    return a.get("alpha", 0.1), a.get("lam", 0.5)


def init(s, key, p):
    n, d = s["n_layers"], s["hidden"]
    keys = jax.random.split(key, n + 2)
    return {"proj_in": p.dense(keys[0], s["feat_dim"], d),
            "w": [p.dense(keys[l + 1], d, d) for l in range(n)],
            "bn": [p.bn(d) if s["batchnorm"] else None for _ in range(n)],
            "proj_out": p.dense(keys[-1], d, s["classes"])}


def forward(s, params, x, f):
    alpha, lam = _args(s)
    p = params["proj_in"]
    h0 = jax.nn.relu(f.dot(f.dropout(x), p["w"]) + p["b"])
    h = h0
    for l in range(len(params["w"])):
        prop = f.spmm(l)(f.dropout(h))
        beta = math.log(lam / (l + 1) + 1.0)
        t = (1.0 - alpha) * prop + alpha * h0
        w = params["w"][l]
        h = (1.0 - beta) * t + beta * (f.dot(t, w["w"]) + w["b"])
        if params["bn"][l] is not None:
            h = f.bn(params["bn"][l], h)
        h = jax.nn.relu(h)
    p = params["proj_out"]
    return f.dot(f.dropout(h), p["w"]) + p["b"]


def sampled_layers(s):
    """Layers whose backward SpMM RSC samples: every one (``H⁰``, the
    first layer's input, carries the projection's gradient)."""
    return list(range(s["n_layers"]))


def spmm_widths(s):
    """Every layer propagates a hidden-wide ``H`` forward and its gradient
    backward."""
    fwd = [s["hidden"]] * s["n_layers"]
    return fwd, list(fwd)


def dense_maps(s):
    """The input projection (its input, the features, carries no
    gradient), one ``W^l`` per layer, the output projection."""
    d = s["hidden"]
    return ([[(s["feat_dim"], d)]] + [[(d, d)]] * s["n_layers"]
            + [[(d, s["classes"])]])
