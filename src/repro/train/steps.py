"""Shared GNN train/eval step builders.

Every training configuration — the full-batch loop, the minibatch pipeline
and the mesh-sharded data-parallel engine — jits step functions built here,
so results differ only by the data fed in, never by the step math.

The layering is gradients-first: :func:`make_gnn_grads` builds the pure
loss/grad functions, :func:`make_gnn_steps` composes them with the optimizer
into single-device steps, and :func:`make_dp_gnn_steps` wraps the same grad
functions in a ``shard_map`` over a ``("data",)`` mesh — each device runs
its own subgraph shard, gradients are all-reduced (``pmean``) across the
axis, optionally through the int8 error-feedback compressor
(``distributed/compression.py``), and the optimizer update happens once on
the replicated mean gradient. Per-shard gradient row norms (the Eq. 4a
inputs) come back stacked along the device axis so each shard's plan cache
refreshes from its *own* gradients.

The step functions are shape-polymorphic over the operands: tap arrays (the
gradient-capture trick, models/gnn/common.py) take their row count from
``ops.features`` at trace time, so one builder serves every shape bucket of
a subgraph pool and jit recompiles once per bucket.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.sampling import row_norms
from repro.distributed.compression import ErrorFeedbackCompressor
from repro.train.optimizer import apply_updates


def gnn_loss(logits: jax.Array, ops) -> jax.Array:
    """Masked mean cross-entropy (softmax) or sigmoid BCE (multilabel).

    When the operands carry per-node loss weights (``ops.loss_w`` — the
    GraphSAINT 1/λ_v bias correction for overlapping subgraph pools), the
    mean is weight-normalized: ``Σ w·L / Σ w`` over valid train nodes — a
    self-normalized importance estimator that reduces exactly to the plain
    mean when the weights are uniform (disjoint pools, full batch).
    """
    valid = jnp.arange(logits.shape[0]) < ops.n_valid
    m = (ops.train_mask & valid).astype(jnp.float32)
    loss_w = getattr(ops, "loss_w", None)
    if loss_w is not None:
        m = m * loss_w
    if ops.multilabel:
        ls = jax.nn.log_sigmoid(logits)
        lns = jax.nn.log_sigmoid(-logits)
        per = -(ops.labels * ls + (1 - ops.labels) * lns).sum(-1)
    else:
        logp = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.take_along_axis(
            logp, ops.labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)


def make_gnn_grads(module, dims: dict[str, int], rsc_names,
                   *, dropout: float, backend: str):
    """Build the pure gradient functions every step flavor shares.

    Returns ``(rsc_grads, exact_grads, eval_logits)``:

    * ``rsc_grads(params, ops, plans, key) -> (loss, grads, norms)`` where
      ``norms[name]`` are the per-node ∇H row norms of each sampled SpMM
      (via the tap trick) that the planner's Eq. 4a scores consume;
    * ``exact_grads(params, ops, key) -> (loss, grads)``;
    * ``eval_logits(params, ops) -> logits``.
    """
    rsc_names = tuple(rsc_names)

    def rsc_grads(params, ops, plans, key):
        n_pad = ops.features.shape[0]
        taps = {k: jnp.zeros((n_pad, dims[k]), jnp.float32)
                for k in rsc_names}

        def loss_fn(p, t):
            logits = module.apply(
                p, ops, t, plans, dropout_rate=dropout,
                train=True, key=key, backend=backend)
            return gnn_loss(logits, ops)

        lv, (gp, gt) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(params, taps)
        norms = {k: row_norms(g) for k, g in gt.items()}
        return lv, gp, norms

    def exact_grads(params, ops, key):
        def loss_fn(p):
            logits = module.apply(
                p, ops, {}, None, dropout_rate=dropout,
                train=True, key=key, backend=backend)
            return gnn_loss(logits, ops)

        lv, gp = jax.value_and_grad(loss_fn)(params)
        return lv, gp

    def eval_logits(params, ops):
        return module.apply(params, ops, {}, None, dropout_rate=0.0,
                            train=False, key=None, backend=backend)

    return rsc_grads, exact_grads, eval_logits


def make_gnn_steps(module, opt, dims: dict[str, int], rsc_names,
                   *, dropout: float, backend: str):
    """Build (rsc_step, exact_step, eval_logits) for a GNN module.

    dims: hidden dim of each RSC op's dense operand (module.spmm_dims).
    rsc_names: the ops whose backward SpMM is sampled (module.spmm_names).
    The returned functions are un-jitted; callers own the jit wrappers.
    """
    rsc_grads, exact_grads, eval_logits = make_gnn_grads(
        module, dims, rsc_names, dropout=dropout, backend=backend)

    def rsc_step(params, opt_state, ops, plans, key):
        lv, gp, norms = rsc_grads(params, ops, plans, key)
        upd, opt_state = opt.update(gp, opt_state, params)
        params = apply_updates(params, upd)
        return params, opt_state, lv, norms

    def exact_step(params, opt_state, ops, key):
        lv, gp = exact_grads(params, ops, key)
        upd, opt_state = opt.update(gp, opt_state, params)
        params = apply_updates(params, upd)
        return params, opt_state, lv

    return rsc_step, exact_step, eval_logits


# ---------------------------------------------------------------------------
# Data-parallel steps: one subgraph shard per device, pmean'd gradients.
# ---------------------------------------------------------------------------

def _squeeze_shard(tree):
    """Drop the per-device leading axis shard_map leaves carry."""
    return jax.tree.map(lambda x: x[0], tree)


def _stack_shard(tree):
    """Re-add the per-device leading axis for P('data') outputs."""
    return jax.tree.map(lambda x: x[None], tree)


def _bucketed_pmean(grads, axis: str, n_buckets: int):
    """All-reduce the gradient pytree as ``n_buckets`` flat buckets.

    Leaves are flattened in tree order and split at even cumulative-size
    boundaries; each bucket concatenates to ONE flat f32 vector and issues
    ONE ``pmean``. Backward-pass/communication overlap follows: the last
    gradients a backward pass produces are the FIRST layers' (reverse-mode
    order), so with per-bucket collectives XLA's scheduler can launch the
    all-reduce of already-finished buckets while the backward tail is
    still computing — one monolithic reduce (or one barrier-like
    ``tree.map`` of per-leaf reduces the compiler chooses to fuse) cannot
    start until every gradient exists.

    Trajectory identity with the per-leaf path is exact, not approximate:
    ``pmean`` is an elementwise mean over devices, so mean-then-split ==
    split-then-mean bit-for-bit (all-f32 accumulation both ways). The
    compressed path keeps identity because quantization happens PER LEAF
    before bucketing — int8 block codes never straddle a bucket boundary.
    """
    leaves, treedef = jax.tree.flatten(grads)
    if len(leaves) <= 1:
        return jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
    n_buckets = max(1, min(n_buckets, len(leaves)))
    sizes = [l.size for l in leaves]
    total = sum(sizes)
    buckets: list[list[int]] = []
    cur: list[int] = []
    acc = 0
    for i, s in enumerate(sizes):
        cur.append(i)
        acc += s
        if (len(buckets) < n_buckets - 1
                and acc * n_buckets >= total * (len(buckets) + 1)):
            buckets.append(cur)
            cur = []
    if cur:
        buckets.append(cur)
    out: list = [None] * len(leaves)
    for idx in buckets:
        flat = jnp.concatenate(
            [leaves[i].reshape(-1).astype(jnp.float32) for i in idx])
        red = jax.lax.pmean(flat, axis)
        off = 0
        for i in idx:
            out[i] = (red[off: off + sizes[i]]
                      .reshape(leaves[i].shape).astype(leaves[i].dtype))
            off += sizes[i]
    return jax.tree.unflatten(treedef, out)


def make_dp_gnn_steps(module, opt, dims: dict[str, int], rsc_names,
                      *, dropout: float, backend: str, mesh,
                      axis: str = "data", compress_block: int = 128,
                      overlap_allreduce: bool = False,
                      overlap_buckets: int = 4):
    """Build data-parallel (rsc_step, exact_step, eval_logits).

    The returned steps take operand/plan/key pytrees STACKED along a leading
    device axis (one subgraph per device) plus the error-feedback state:

        rsc_step(params, opt_state, err, ops, plans, keys, compress)
            -> (params, opt_state, loss, norms, err)
        exact_step(params, opt_state, err, ops, keys, compress)
            -> (params, opt_state, loss, err)

    ``compress`` is a python bool baked into the trace (two cache entries):
    when True each device quantizes its local gradient (plus carried error)
    to int8 per-block codes before the all-reduce and keeps the quantization
    residual in ``err`` — the EF21-style compressed all-reduce. The paper's
    §3.3.2 switch-back applies to the compressor too: the engine calls the
    ``compress=False`` variant for the exact tail, passing an EMPTY ``err``
    pytree (the carried error is frozen host-side, not leaked into the
    updates, and the uncompressed trace never pays for the state).

    ``norms`` come back stacked ``(n_devices, n_pad)`` so per-shard plan
    caches refresh from their own shard's gradients. The loss is the pmean
    over shards. ``eval_logits`` is the plain single-device evaluator —
    pooled evaluation streams subgraphs through one device.

    ``overlap_allreduce`` swaps the per-leaf ``pmean`` for
    :func:`_bucketed_pmean` over ``overlap_buckets`` buckets — the
    all-reduce of finished buckets overlaps the backward tail, with a
    bit-identical trajectory (see that docstring for why identity is
    exact, compressed or not).
    """
    rsc_grads, exact_grads, eval_logits = make_gnn_grads(
        module, dims, rsc_names, dropout=dropout, backend=backend)
    ef = ErrorFeedbackCompressor(block=compress_block)

    def _reduce(grads, err, compress: bool):
        if compress:
            grads, err = ef.compress(grads, err)
        if overlap_allreduce:
            grads = _bucketed_pmean(grads, axis, overlap_buckets)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
        return grads, err

    def _apply(params, opt_state, grads):
        upd, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, upd), opt_state

    def rsc_step(params, opt_state, err, ops, plans, keys, compress: bool):
        def body(params, err_s, ops_s, plans_s, key_s):
            lv, gp, norms = rsc_grads(
                params, _squeeze_shard(ops_s), _squeeze_shard(plans_s),
                key_s[0])
            gp, err_l = _reduce(gp, _squeeze_shard(err_s), compress)
            return (jax.lax.pmean(lv, axis), gp,
                    _stack_shard(norms), _stack_shard(err_l))

        sharded = shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(), P(), P(axis), P(axis)),
            check_vma=False)
        lv, grads, norms, err = sharded(params, err, ops, plans, keys)
        params, opt_state = _apply(params, opt_state, grads)
        return params, opt_state, lv, norms, err

    def exact_step(params, opt_state, err, ops, keys, compress: bool):
        def body(params, err_s, ops_s, key_s):
            lv, gp = exact_grads(params, _squeeze_shard(ops_s), key_s[0])
            gp, err_l = _reduce(gp, _squeeze_shard(err_s), compress)
            return jax.lax.pmean(lv, axis), gp, _stack_shard(err_l)

        sharded = shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis)),
            out_specs=(P(), P(), P(axis)),
            check_vma=False)
        lv, grads, err = sharded(params, err, ops, keys)
        params, opt_state = _apply(params, opt_state, grads)
        return params, opt_state, lv, err

    return rsc_step, exact_step, eval_logits


def init_error_feedback(params, n_devices: int):
    """Zero EF accumulators, one per device (stacked leading axis)."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_devices,) + p.shape, jnp.float32), params)
