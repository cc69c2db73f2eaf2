"""Plain reference of full-batch GNN training steps, RSC included.

Straightforward ``jax.numpy`` and numpy from the published equations, with
nothing of the program imported and nothing it made taken: the reference
builds its own propagation matrix from the benchmark's edge list, draws its
own weights and dropout masks from the seed, picks its own column-row
pairs and runs its own Adam.

* the model is its file, ``bench/models/<model>.py``: the propagation
  matrix (``normalize``), the parameter tree (``init``), the layer stack
  (``forward``: where dropout, SpMM, dense maps, batch norm and ReLU go)
  and which layers RSC samples (``sampled_layers``), all from the
  configuration's sizes (``SIZES``); what every model shares is here;
* masked mean softmax cross-entropy over the training nodes;
* Adam (Kingma & Ba 2015), no weight decay;
* RSC (Liu et al. 2022, Sec. 3): the forward SpMM is exact; the backward
  SpMM of each sampled layer keeps only the column-row pairs of the kept
  node blocks, unscaled. Every ``refresh_every`` steps, from the previous
  step's gradient row norms, each block scores the sum over its nodes of
  ``|P_i,:| * |dH_i,:|``, and the greedy allocation of Algorithm 1 drops,
  ``step_frac`` of a layer's blocks at a time, from the layer whose
  normalized dropped score grows least, until the backward cost
  (tiles times width) fits ``budget`` of the exact cost. Until the first
  refresh every pair is kept.

Where the program fixes a convention the equations leave open, the
reference follows it, so that one seed gives one computation on both
sides: nodes are relabelled by descending degree (stable, ties by id),
rows are padded to a multiple of the block, pairs are chosen and costed in
blocks of ``block`` nodes, weights are He-normal with zero biases, BN
statistics run over the real nodes, and each dropout mask is a Bernoulli
draw from the ``rbg`` generator keyed by a threefry key of its own
(split once per step from ``PRNGKey(seed + 1)``, then once per dropout,
in the order the model's ``forward`` draws them).

The SpMM is a gather and a segment sum over the edge list, exact in f32;
dense products run at ``highest`` precision. ``dtype=jnp.bfloat16`` gives
the control: every array and every operation in bfloat16.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

import spec

B1, B2, EPS = 0.9, 0.999, 1e-8
# the configuration's sizes a model's file reads, besides ``feat_dim``
SIZES = ("hidden", "classes", "n_layers", "batchnorm", "model_args")


def problem(graph: dict, model: str, block: int) -> dict:
    """Degree-sorted, normalized, padded inputs and the block statistics
    of the backward operand (host numpy)."""
    n = graph["nodes"]
    rows, cols = graph["rows"], graph["cols"]
    deg = np.bincount(rows, minlength=n)
    perm = np.argsort(-deg, kind="stable")
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    r, c, vals = spec.model_module(model).normalize(
        inv[rows], inv[cols], deg[perm])
    n_pad = -(-n // block) * block
    nb = n_pad // block

    def pad(x):
        return np.concatenate(
            [x, np.zeros((n_pad - n,) + x.shape[1:], x.dtype)])

    # The backward SpMM multiplies by P^T: its column i is P's row i, and
    # its column block b holds one tile per distinct column block of P's
    # rows in block b.
    row_norm = np.sqrt(np.bincount(r, weights=vals * vals, minlength=n_pad))
    pairs = np.unique((r // block) * nb + c // block)
    return {"n": n, "n_pad": n_pad, "model": model, "block": block,
            "rows": r.astype(np.int32), "cols": c.astype(np.int32),
            "vals": vals.astype(np.float32),
            "x": pad(graph["features"][perm]),
            "labels": pad(graph["labels"][perm]).astype(np.int32),
            "train": pad(graph["train_mask"][perm]),
            "pair_norm": row_norm, "block_tiles": np.bincount(
                pairs // nb, minlength=nb).astype(np.float64),
            "fro": float(np.sqrt(np.sum(vals.astype(np.float64) ** 2)))}


def init_params(mod, key, sizes: dict) -> dict:
    """The model's parameter tree: He-normal weights, zero biases, batch
    norms at unit scale and zero shift."""
    def dense(k, i, o):
        return {"w": jax.random.normal(k, (i, o), jnp.float32)
                * float(np.sqrt(2.0 / i)),
                "b": jnp.zeros((o,), jnp.float32)}

    def bn(d):
        return {"g": jnp.ones((d,), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32)}

    return mod.init(sizes, key, types.SimpleNamespace(dense=dense, bn=bn))


def _dropout(h, rate, key):
    rbg = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key), 2), impl="rbg")
    keep = jax.random.bernoulli(rbg, 1.0 - rate, h.shape)
    return jnp.where(keep, h / (1.0 - rate), 0.0).astype(h.dtype)


def _batchnorm(p, x, valid):
    m = valid.astype(x.dtype)[:, None]
    cnt = jnp.maximum(jnp.sum(m), 1.0)
    mu = jnp.sum(x * m, axis=0) / cnt
    var = jnp.sum(((x - mu) ** 2) * m, axis=0) / cnt
    return ((x - mu) / jnp.sqrt(var + 1e-5)) * p["g"] + p["b"]


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def device_data(prob: dict, dtype) -> dict:
    """The reference's inputs on the device (passed to the jitted loss as
    arguments, never closed over as constants)."""
    valid = np.arange(prob["n_pad"]) < prob["n"]
    return {"rows": jnp.asarray(prob["rows"]),
            "cols": jnp.asarray(prob["cols"]),
            "vals": jnp.asarray(prob["vals"], dtype),
            "x": jnp.asarray(prob["x"], dtype),
            "labels": jnp.asarray(prob["labels"]),
            "valid": jnp.asarray(valid),
            "train": jnp.asarray(prob["train"] & valid)}


def make_loss(mod, sizes: dict, n_pad: int, dropout: float):
    """``loss(params, taps, keeps, key, data)``.

    The model's ``forward`` runs the layer stack on the reference's
    operations. ``dropout(h)`` draws the next key split from ``key``.
    ``spmm(l)`` is layer ``l``'s propagation; where RSC samples the layer,
    as the ``j``-th of ``sampled_layers``, ``taps[j]`` is added to its
    output, so that its gradient is that output's gradient, and its
    backward keeps the rows that ``keeps[j]`` (1 or 0 per node) marks.
    """
    layers = mod.sampled_layers(sizes)

    def loss(params, taps, keeps, key, data):
        def spmm(h):
            return jax.ops.segment_sum(
                data["vals"][:, None] * h[data["cols"]], data["rows"],
                num_segments=n_pad)

        def sampled(j):
            def f(h):
                y = spmm(h)
                z = keeps[j][:, None] * y   # its backward: P^T (keep * dY)
                return jax.lax.stop_gradient(y - z) + z + taps[j]
            return f

        def drop(h):
            nonlocal key
            key, sub = jax.random.split(key)
            return _dropout(h, dropout, sub)

        ops = types.SimpleNamespace(
            dropout=drop, dot=_dot,
            spmm=lambda l: sampled(layers.index(l)) if l in layers else spmm,
            bn=lambda p, h: _batchnorm(p, h, data["valid"]))
        h = mod.forward(sizes, params, data["x"], ops)
        logp = jax.nn.log_softmax(h, axis=-1)
        per = -jnp.take_along_axis(
            logp, data["labels"][:, None], axis=-1)[:, 0]
        m = data["train"].astype(h.dtype)
        return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)

    return loss


def allocate(prob: dict, widths: list, grad_norms: list, budget: float,
             step_frac: float) -> list:
    """Algorithm 1 over node blocks: which blocks each sampled layer
    keeps, from each layer's gradient row norms."""
    nb = prob["n_pad"] // prob["block"]
    tiles = prob["block_tiles"]
    orders, values, costs, steps = [], [], [], []
    for d, g in zip(widths, grad_norms):
        g = np.asarray(g, np.float64)
        score = (prob["pair_norm"] * g).reshape(nb, -1).sum(axis=1)
        order = np.argsort(score, kind="stable")
        norm = max(prob["fro"] * float(np.sqrt(np.sum(g * g))), 1e-30)
        orders.append(order)
        values.append(np.concatenate([[0.0], np.cumsum(score[order] / norm)]))
        costs.append(np.concatenate([[0.0], np.cumsum(tiles[order] * d)]))
        steps.append(max(1, int(round(step_frac * nb))))
    cost = sum(c[-1] for c in costs)
    cap = budget * cost
    dropped = [0] * len(widths)
    while cost > cap:
        best, best_inc = -1, np.inf
        for j in range(len(widths)):
            new = min(dropped[j] + steps[j], nb)
            inc = values[j][new] - values[j][dropped[j]]
            if new > dropped[j] and inc < best_inc:
                best, best_inc = j, inc
        if best < 0:
            break
        new = min(dropped[best] + steps[best], nb)
        cost -= costs[best][new] - costs[best][dropped[best]]
        dropped[best] = new
    keeps = []
    for order, n_drop in zip(orders, dropped):
        keep = np.ones(nb, bool)
        keep[order[:n_drop]] = False
        keeps.append(keep)
    return keeps


def _adam(params, grads, m, v, t, lr):
    b1c, b2c = 1.0 - B1 ** t, 1.0 - B2 ** t

    def upd(p, g, mi, vi):
        mi = B1 * mi + (1 - B1) * g
        vi = B2 * vi + (1 - B2) * g * g
        step = -lr * (mi / b1c) / (jnp.sqrt(vi / b2c) + EPS)
        return (p + step).astype(p.dtype), mi, vi

    out = jax.tree.map(upd, params, grads, m, v)
    pick = [jax.tree.map(lambda o: o[i], out,
                         is_leaf=lambda o: isinstance(o, tuple))
            for i in range(3)]
    return pick


def run(prob: dict, cfg: dict, seed: int, steps: int, *, grad_steps=(0,),
        param_steps=(0,), dtype=jnp.float32) -> dict:
    """``steps`` training steps from the seed's initial weights.

    ``cfg`` holds the model's sizes (``SIZES``), ``dropout``, ``lr`` and,
    for RSC, ``rsc``, ``budget``, ``step_frac``, ``refresh_every`` and
    ``rsc_steps`` (the steps before switch-back). Returns the loss of each
    step, the gradient of each step in ``grad_steps`` and the weights
    before each step in ``param_steps``, all as float32 numpy pytrees.
    """
    mod = spec.model_module(prob["model"])
    sizes = {"feat_dim": prob["x"].shape[1],
             **{k: cfg[k] for k in SIZES}}
    params = init_params(mod, jax.random.PRNGKey(seed), sizes)
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    layers = mod.sampled_layers(sizes)
    widths = [mod.spmm_widths(sizes)[0][l] for l in layers]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    data = device_data(prob, dtype)
    vg = jax.jit(jax.value_and_grad(
        make_loss(mod, sizes, prob["n_pad"], cfg["dropout"]),
        argnums=(0, 1)))
    update = jax.jit(_adam, static_argnums=(5,))
    taps = [jnp.zeros((prob["n_pad"], d), dtype) for d in widths]
    every = np.ones(prob["n_pad"], np.float32)
    keeps = [jnp.asarray(every, dtype) for _ in layers]
    key = jax.random.PRNGKey(seed + 1)
    f32 = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32),
                                    tree)
    out = {"losses": [], "grads": {}, "params": {}}
    norms = None
    for t in range(steps):
        if t in param_steps:
            out["params"][t] = f32(params)
        rsc = cfg.get("rsc", False) and t < cfg["rsc_steps"]
        if rsc and norms is not None and t % cfg["refresh_every"] == 0:
            kept = allocate(prob, widths, norms, cfg["budget"],
                            cfg["step_frac"])
            keeps = [jnp.asarray(np.repeat(k, prob["block"]), dtype)
                     for k in kept]
        use = keeps if rsc else [jnp.asarray(every, dtype) for _ in layers]
        key, sub = jax.random.split(key)
        lv, (g, gt) = vg(params, taps, use, sub, data)
        out["losses"].append(float(lv))
        if rsc and (t + 1) % cfg["refresh_every"] == 0:
            norms = [np.sqrt(np.sum(np.asarray(x, np.float64) ** 2, axis=1))
                     for x in gt]
        if t in grad_steps:
            out["grads"][t] = f32(g)
        params, m, v = update(params, g, m, v, jnp.float32(t + 1),
                              cfg["lr"])
    if steps in param_steps:
        out["params"][steps] = f32(params)
    return out
