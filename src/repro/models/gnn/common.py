"""Shared GNN plumbing: operands, layers, taps.

The TAP mechanism: every SpMM output gets a zero-valued additive ``tap``
array. ``jax.grad`` w.r.t. the taps yields exactly the backward operand
∇H^{(l+1)} of each sparse op — the quantity Eq. 4a scores need — without
instrumenting autodiff internals. The train step reduces taps' gradients to
row norms inside the same jit (the full (N, d) arrays never leave device).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import SamplePlan, full_plan
from repro.core.rsc_spmm import exact_spmm, rsc_spmm
from repro.graphs.synthetic import GraphData
from repro.sparse.bcoo import BlockCOO, BlockMeta, csr_to_bcoo, \
    degree_sort_permutation
from repro.sparse.topology import mean_normalize, sym_normalize


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["a", "at", "am", "amt", "features", "labels", "train_mask",
                 "val_mask", "test_mask", "n_valid", "loss_w"],
    meta_fields=["num_classes", "multilabel"],
)
@dataclasses.dataclass(frozen=True)
class GraphOperands:
    """Device-resident graph operands (padded to block multiples).

    ``n_valid`` is pytree DATA (not static metadata) so subgraphs padded to a
    shared bucket shape but with different real node counts hit the same jit
    cache entry — the property the minibatch pipeline's shape bucketing
    relies on.

    ``loss_w`` (optional, GraphSAINT pools) is the per-node 1/λ_v loss
    normalization weight; ``None`` (full batch, disjoint pools) means
    uniform weights and leaves the loss untouched.
    """

    a: BlockCOO          # sym-normalized Ã (GCN/GCNII propagation)
    at: BlockCOO         # Ãᵀ
    am: BlockCOO         # mean-normalized D⁻¹A (GraphSAGE, App. A.3)
    amt: BlockCOO        # (D⁻¹A)ᵀ
    features: jax.Array  # (N_pad, d_in)
    labels: jax.Array    # (N_pad,) int32 or (N_pad, C) f32
    train_mask: jax.Array
    val_mask: jax.Array
    test_mask: jax.Array
    n_valid: int | jax.Array   # real (un-padded) node count
    num_classes: int
    multilabel: bool
    loss_w: jax.Array | None = None  # (N_pad,) f32 or None (uniform)


@dataclasses.dataclass(frozen=True)
class OperandMeta:
    """Host metadata of the operands: the backward ones for the PlanCache,
    all four for the kernel's grouping gauges."""

    at_meta: BlockMeta
    amt_meta: BlockMeta
    a_fro: float
    am_fro: float
    a_meta: BlockMeta
    am_meta: BlockMeta


def degree_sorted_arrays(adj, feats, labels, tr, va, te):
    """Relabel nodes by descending degree; permuted copies + the perm."""
    perm = degree_sort_permutation(adj)
    return (adj.permute(perm), feats[perm], labels[perm],
            tr[perm], va[perm], te[perm], perm)


def pad_node_arrays(n_pad: int, feats, labels, tr, va, te,
                    multilabel: bool):
    """Pad per-node host arrays to ``n_pad`` rows (labels in device dtype:
    f32 one-hots for multilabel, int32 class ids otherwise)."""
    pad = n_pad - feats.shape[0]

    def padf(x, fill=0):
        width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return np.pad(x, width, constant_values=fill)

    labels_p = (padf(labels).astype(np.float32) if multilabel
                else padf(labels).astype(np.int32))
    return (padf(feats).astype(np.float32), labels_p,
            padf(tr).astype(bool), padf(va).astype(bool),
            padf(te).astype(bool))


def build_operands(
    g: GraphData, bm: int = 128, bk: int = 128, degree_sort: bool = True,
) -> tuple[GraphOperands, OperandMeta]:
    adj = g.adj
    feats, labels = g.features, g.labels
    tr, va, te = g.train_mask, g.val_mask, g.test_mask
    if degree_sort:
        adj, feats, labels, tr, va, te, _ = degree_sorted_arrays(
            adj, feats, labels, tr, va, te)

    a_csr = sym_normalize(adj)
    am_csr = mean_normalize(adj)
    a, a_meta = csr_to_bcoo(a_csr, bm, bk)
    at, at_meta = csr_to_bcoo(a_csr.transpose(), bm, bk)
    am, am_meta = csr_to_bcoo(am_csr, bm, bk)
    amt, amt_meta = csr_to_bcoo(am_csr.transpose(), bm, bk)

    feats_p, labels_p, tr_p, va_p, te_p = pad_node_arrays(
        a.n_rows, feats, labels, tr, va, te, g.multilabel)
    ops = GraphOperands(
        a=a, at=at, am=am, amt=amt,
        features=jnp.asarray(feats_p),
        labels=jnp.asarray(labels_p),
        train_mask=jnp.asarray(tr_p),
        val_mask=jnp.asarray(va_p),
        test_mask=jnp.asarray(te_p),
        n_valid=g.n,
        num_classes=g.num_classes,
        multilabel=g.multilabel,
    )
    meta = OperandMeta(
        at_meta=at_meta, amt_meta=amt_meta,
        a_fro=float(np.sqrt(np.sum(a_csr.val.astype(np.float64) ** 2))),
        am_fro=float(np.sqrt(np.sum(am_csr.val.astype(np.float64) ** 2))),
        a_meta=a_meta, am_meta=am_meta,
    )
    return ops, meta


def spmm_op(a: BlockCOO, at: BlockCOO, h: jax.Array,
            plan: SamplePlan | None, backend: str, *, name: str,
            bias: jax.Array | None = None,
            residual: jax.Array | None = None,
            relu: bool = False) -> jax.Array:
    """Dispatch: RSC (sampled backward) if a plan is supplied, exact else.

    ``bias``/``residual``/``relu`` ride the SpMM's fused epilogue
    (``out = relu(spmm + bias + residual)``) so GCN-style layers skip one
    full HBM round-trip per SpMM; gradients flow through the epilogue
    exactly (see ``core.rsc_spmm``). The gradient TAP of each SpMM output
    is fused as the ``residual`` term — algebraically identical to the
    post-hoc ``+ tap``.

    ``name`` (the op's plan-cache name, ``gcn/spmm0``) scopes the call at
    trace time: its ops, forward and backward, carry it in their location.
    """
    with jax.named_scope(name):
        if plan is None:
            return exact_spmm(a, at, h, backend, bias=bias,
                              residual=residual, relu=relu)
        return rsc_spmm(a, at, plan, h, backend, bias=bias,
                        residual=residual, relu=relu)


# ------------------------------ nn primitives ------------------------------

def dense_init(key, d_in, d_out, scale=None):
    scale = scale if scale is not None else float(np.sqrt(2.0 / d_in))
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale,
            "b": jnp.zeros((d_out,), jnp.float32)}


def dense(p, x):
    return x @ p["w"] + p["b"]


def batchnorm_init(d):
    return {"g": jnp.ones((d,), jnp.float32),
            "b": jnp.zeros((d,), jnp.float32)}


def batchnorm(p, x, mask):
    """BatchNorm over valid nodes (full-batch graph training)."""
    m = mask.astype(jnp.float32)[:, None]
    cnt = jnp.maximum(jnp.sum(m), 1.0)
    mu = jnp.sum(x * m, axis=0) / cnt
    var = jnp.sum(((x - mu) ** 2) * m, axis=0) / cnt
    return ((x - mu) / jnp.sqrt(var + 1e-5)) * p["g"] + p["b"]


def dropout(x, rate, key, train):
    if not train or rate == 0.0:
        return x
    # The mask's bits come from XLA's RngBitGenerator, seeded by ``key``.
    # A threefry mask gets fused into the neighbouring matmuls and their
    # gradients, and the TPU compiler then spends most of a minute on each
    # train step at Reddit's widths.
    rbg = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key), 2), impl="rbg")
    keep = jax.random.bernoulli(rbg, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


# ------------------------- streaming-inference hooks -----------------------
#
# The streaming full-graph inference engine (``repro.infer.stream``) runs
# each layer's SpMM for all nodes one row-partition at a time, with the
# activations resident on HOST. Every model module implements the hook
# protocol below; the row-wise (non-SpMM) math runs on host numpy so only
# the SpMM and the optional pre-map ever touch the device:
#
#   infer_n_layers(params) -> int          number of SpMM layers
#   infer_spmm_dims(params, feat_dim)      dense-operand dim of each SpMM
#   infer_init(params, feats) -> (h, ctx)  host setup; ctx e.g. GCNII's H⁰
#   infer_pre(params, l) -> (fn, p) | None row-wise device map applied to
#                                          the gathered SpMM input as
#                                          ``fn(p, h)`` (None = identity;
#                                          fn pure/jittable, ``p`` rides as
#                                          a jit argument so fresh params
#                                          never retrace)
#   infer_post(params, l, p, h, ctx, valid, bn_stats)
#       -> (h_next, bn_stats)              row-wise host combine of the SpMM
#                                          output ``p`` with the layer input
#                                          ``h``; ``bn_stats=None`` computes
#                                          fresh batch statistics (full
#                                          pass), a stats tuple applies them
#                                          FROZEN (incremental row-subset
#                                          recompute in the serving path)
#   infer_out(params, h, ctx) -> logits    row-wise host final projection
#
# ``np_dense`` / ``np_batchnorm`` are the host mirrors of ``dense`` /
# ``batchnorm`` the hooks build on.

def np_dense(p, x: np.ndarray) -> np.ndarray:
    return x @ np.asarray(p["w"]) + np.asarray(p["b"])


def np_batchnorm(p, x: np.ndarray, valid: np.ndarray,
                 stats: tuple | None = None):
    """Host mirror of :func:`batchnorm`.

    ``stats=None`` computes (mu, var) over valid rows and returns them so
    callers can freeze them; a provided tuple is applied as-is (row-wise,
    enabling subset recompute).
    """
    if stats is None:
        m = valid.astype(np.float32)[:, None]
        cnt = max(float(m.sum()), 1.0)
        mu = (x * m).sum(axis=0) / cnt
        var = (((x - mu) ** 2) * m).sum(axis=0) / cnt
        stats = (mu, var)
    mu, var = stats
    out = ((x - mu) / np.sqrt(var + 1e-5)) * np.asarray(p["g"]) \
        + np.asarray(p["b"])
    return out.astype(np.float32), stats
