"""GCNII (Chen et al. 2020) — the paper's deep model (full-batch).

Layer l: H^{l+1} = ReLU( ((1−α)·SpMM(Ã,H^l) + α·H⁰) ((1−β_l)I + β_l W^l) ),
β_l = log(λ/l + 1). Initial/final dense projections, dropout per paper.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import common as C


def spmm_names(n_layers: int) -> list[str]:
    return [f"gcnii/spmm{l}" for l in range(n_layers)]


def spmm_dims(n_layers: int, hidden: int, n_classes: int) -> dict[str, int]:
    return {f"gcnii/spmm{l}": hidden for l in range(n_layers)}


def tap_shapes(n_layers: int, n_pad: int, hidden: int,
               n_classes: int) -> dict[str, tuple[int, int]]:
    return {f"gcnii/spmm{l}": (n_pad, hidden) for l in range(n_layers)}


def uses_mean_agg() -> bool:
    return False


def init(key, d_in: int, hidden: int, n_classes: int, n_layers: int,
         batchnorm: bool) -> dict:
    keys = jax.random.split(key, n_layers + 2)
    params = {
        "proj_in": C.dense_init(keys[0], d_in, hidden),
        "w": [C.dense_init(keys[l + 1], hidden, hidden)
              for l in range(n_layers)],
        "bn": [C.batchnorm_init(hidden) if batchnorm else None
               for _ in range(n_layers)],
        "proj_out": C.dense_init(keys[-1], hidden, n_classes),
    }
    return params


# ---------------------- streaming-inference hooks --------------------------
# (protocol in models/gnn/common.py; orchestration in repro/infer/stream.py.
# alpha/lam must match the defaults of ``apply`` — eval uses them too.)

def infer_n_layers(params) -> int:
    return len(params["w"])


def infer_spmm_dims(params, feat_dim: int) -> list[int]:
    hidden = params["proj_in"]["w"].shape[1]
    return [hidden] * len(params["w"])


def infer_init(params, feats):
    h0 = np.maximum(
        C.np_dense(params["proj_in"], np.asarray(feats, np.float32)),
        0.0).astype(np.float32)
    return h0, h0


def infer_pre(params, l: int):
    return None         # SpMM input is H^l itself


def infer_post(params, l: int, p, h, ctx, valid, bn_stats=None,
               alpha: float = 0.1, lam: float = 0.5):
    beta = math.log(lam / (l + 1) + 1.0)
    ht = (1.0 - alpha) * p + alpha * ctx
    hp = ((1.0 - beta) * ht
          + beta * C.np_dense(params["w"][l], ht)).astype(np.float32)
    if params["bn"][l] is not None:
        hp, bn_stats = C.np_batchnorm(params["bn"][l], hp, valid, bn_stats)
    return np.maximum(hp, 0.0).astype(np.float32), bn_stats


def infer_out(params, h, ctx):
    return C.np_dense(params["proj_out"], h).astype(np.float32)


def apply(params, ops: C.GraphOperands, taps: dict, plans: dict | None,
          *, dropout_rate: float = 0.5, train: bool = True,
          key=None, backend: str = "jnp", alpha: float = 0.1,
          lam: float = 0.5) -> jax.Array:
    plans = plans or {}
    n_layers = len(params["w"])
    valid = jnp.arange(ops.features.shape[0]) < ops.n_valid

    if train and dropout_rate > 0:
        key, sub = jax.random.split(key)
        x = C.dropout(ops.features, dropout_rate, sub, train)
    else:
        x = ops.features
    h0 = jax.nn.relu(C.dense(params["proj_in"], x))
    h = h0
    for l in range(n_layers):
        if train and dropout_rate > 0:
            key, sub = jax.random.split(key)
            h = C.dropout(h, dropout_rate, sub, train)
        name = f"gcnii/spmm{l}"
        # Tap fused as the epilogue residual (ReLU can't fuse here: the
        # (1−β)I + βW mix sits between the SpMM and the activation).
        p = C.spmm_op(ops.a, ops.at, h, plans.get(name), backend,
                      name=name, residual=taps.get(name))
        beta = math.log(lam / (l + 1) + 1.0)
        ht = (1.0 - alpha) * p + alpha * h0
        hp = (1.0 - beta) * ht + beta * C.dense(params["w"][l], ht)
        if params["bn"][l] is not None:
            hp = C.batchnorm(params["bn"][l], hp, valid)
        h = jax.nn.relu(hp)
    if train and dropout_rate > 0:
        key, sub = jax.random.split(key)
        h = C.dropout(h, dropout_rate, sub, train)
    return C.dense(params["proj_out"], h)
