"""GraphSAGE with MEAN aggregator (paper App. A.3, full-batch).

Layer l:  H^{l+1} = ReLU(H^l W₁ + SpMM_MEAN(A, H^l) W₂)

SpMM_MEAN = SpMM with D⁻¹A values (mean_normalize) — same kernel.
The first layer's backward SpMM does not exist (A, X carry no gradient),
so RSC registers plans only for layers 1..L-1 (paper Figs. 7/8 note).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import common as C


def spmm_names(n_layers: int) -> list[str]:
    return [f"sage/spmm{l}" for l in range(1, n_layers)]


def spmm_dims(n_layers: int, hidden: int, n_classes: int) -> dict[str, int]:
    # operand of backward SpMM at layer l is ∇M^{l} with dim of H^{l} (input)
    return {f"sage/spmm{l}": hidden for l in range(1, n_layers)}


def tap_shapes(n_layers: int, n_pad: int, hidden: int,
               n_classes: int) -> dict[str, tuple[int, int]]:
    return {f"sage/spmm{l}": (n_pad, hidden) for l in range(1, n_layers)}


def uses_mean_agg() -> bool:
    return True


def init(key, d_in: int, hidden: int, n_classes: int, n_layers: int,
         batchnorm: bool) -> dict:
    keys = jax.random.split(key, 2 * n_layers)
    params = {"self": [], "neigh": [], "bn": []}
    dims = [d_in] + [hidden] * (n_layers - 1) + [n_classes]
    for l in range(n_layers):
        params["self"].append(C.dense_init(keys[2 * l], dims[l], dims[l + 1]))
        params["neigh"].append(
            C.dense_init(keys[2 * l + 1], dims[l], dims[l + 1]))
        params["bn"].append(C.batchnorm_init(dims[l + 1])
                            if (batchnorm and l < n_layers - 1) else None)
    return params


# ---------------------- streaming-inference hooks --------------------------
# (protocol in models/gnn/common.py; orchestration in repro/infer/stream.py)

def infer_n_layers(params) -> int:
    return len(params["self"])


def infer_spmm_dims(params, feat_dim: int) -> list[int]:
    # layer l's SpMM_MEAN consumes H^l itself: dim = layer input width
    return [feat_dim] + [p["w"].shape[1]
                         for p in params["self"][:-1]]


def infer_init(params, feats):
    return np.asarray(feats, np.float32), None


def infer_pre(params, l: int):
    return None         # SpMM input is H^l itself


def infer_post(params, l: int, m, h, ctx, valid, bn_stats=None):
    hp = (C.np_dense(params["self"][l], h)
          + C.np_dense(params["neigh"][l], m)).astype(np.float32)
    if l == len(params["self"]) - 1:
        return hp, None
    if params["bn"][l] is not None:
        hp, bn_stats = C.np_batchnorm(params["bn"][l], hp, valid, bn_stats)
    return np.maximum(hp, 0.0).astype(np.float32), bn_stats


def infer_out(params, h, ctx):
    return h


def apply(params, ops: C.GraphOperands, taps: dict, plans: dict | None,
          *, dropout_rate: float = 0.5, train: bool = True,
          key=None, backend: str = "jnp") -> jax.Array:
    plans = plans or {}
    n_layers = len(params["self"])
    h = ops.features
    valid = jnp.arange(h.shape[0]) < ops.n_valid
    for l in range(n_layers):
        if train and dropout_rate > 0:
            key, sub = jax.random.split(key)
            h = C.dropout(h, dropout_rate, sub, train)
        name = f"sage/spmm{l}"
        # Tap fused as the epilogue residual (the neigh dense layer sits
        # between the SpMM and the activation, so ReLU stays outside).
        m = C.spmm_op(ops.am, ops.amt, h, plans.get(name), backend,
                      name=name, residual=taps.get(name))
        hp = C.dense(params["self"][l], h) + C.dense(params["neigh"][l], m)
        if l < n_layers - 1:
            if params["bn"][l] is not None:
                hp = C.batchnorm(params["bn"][l], hp, valid)
            h = jax.nn.relu(hp)
        else:
            h = hp
    return h
