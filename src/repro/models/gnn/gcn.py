"""GCN (Kipf & Welling 2017) — paper Eq. 1, full-batch.

Layer l:  H^{l+1} = ReLU(SpMM(Ã, MatMul(H^l, Θ^l)))
RSC replaces the backward SpMM per layer with its sampled version.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import common as C


def spmm_names(n_layers: int) -> list[str]:
    return [f"gcn/spmm{l}" for l in range(n_layers)]


def spmm_dims(n_layers: int, hidden: int, n_classes: int) -> dict[str, int]:
    return {f"gcn/spmm{l}": (hidden if l < n_layers - 1 else n_classes)
            for l in range(n_layers)}


def tap_shapes(n_layers: int, n_pad: int, hidden: int,
               n_classes: int) -> dict[str, tuple[int, int]]:
    return {f"gcn/spmm{l}": (n_pad, hidden if l < n_layers - 1 else n_classes)
            for l in range(n_layers)}


def uses_mean_agg() -> bool:
    return False


def init(key, d_in: int, hidden: int, n_classes: int, n_layers: int,
         batchnorm: bool) -> dict:
    keys = jax.random.split(key, n_layers)
    params = {"lin": [], "bn": []}
    dims = [d_in] + [hidden] * (n_layers - 1) + [n_classes]
    for l in range(n_layers):
        params["lin"].append(C.dense_init(keys[l], dims[l], dims[l + 1]))
        params["bn"].append(C.batchnorm_init(dims[l + 1])
                            if (batchnorm and l < n_layers - 1) else None)
    return params


# ---------------------- streaming-inference hooks --------------------------
# (protocol in models/gnn/common.py; orchestration in repro/infer/stream.py)

def infer_n_layers(params) -> int:
    return len(params["lin"])


def infer_spmm_dims(params, feat_dim: int) -> list[int]:
    # layer l's SpMM consumes dense(lin[l], h): dim = lin[l] output width
    return [p["w"].shape[1] for p in params["lin"]]


def infer_init(params, feats):
    return np.asarray(feats, np.float32), None


def infer_pre(params, l: int):
    # (pure_fn, pre_params): params stay ARGUMENTS of the jitted layer fn
    # so repeated evals with fresh params never retrace (common.py contract)
    def fn(p, h):
        return h @ p["w"] + p["b"]
    return fn, params["lin"][l]


def infer_post(params, l: int, p, h, ctx, valid, bn_stats=None):
    if l == len(params["lin"]) - 1:
        return p, None
    if params["bn"][l] is not None:
        p, bn_stats = C.np_batchnorm(params["bn"][l], p, valid, bn_stats)
    return np.maximum(p, 0.0).astype(np.float32), bn_stats


def infer_out(params, h, ctx):
    return h


def apply(params, ops: C.GraphOperands, taps: dict, plans: dict | None,
          *, dropout_rate: float = 0.5, train: bool = True,
          key=None, backend: str = "jnp") -> jax.Array:
    plans = plans or {}
    n_layers = len(params["lin"])
    h = ops.features
    valid = jnp.arange(h.shape[0]) < ops.n_valid
    for l in range(n_layers):
        if train and dropout_rate > 0:
            key, sub = jax.random.split(key)
            h = C.dropout(h, dropout_rate, sub, train)
        j = C.dense(params["lin"][l], h)
        name = f"gcn/spmm{l}"
        # Fused epilogue: the tap rides as the residual term, and ReLU
        # fuses into the SpMM whenever nothing (batchnorm) sits between.
        fuse_relu = l < n_layers - 1 and params["bn"][l] is None
        hp = C.spmm_op(ops.a, ops.at, j, plans.get(name), backend,
                       name=name, residual=taps.get(name), relu=fuse_relu)
        if l < n_layers - 1 and params["bn"][l] is not None:
            hp = jax.nn.relu(C.batchnorm(params["bn"][l], hp, valid))
        h = hp
    return h
