"""Faults planted under the timed path, to show the check catches them.

Each is a ``patch_engine`` for :func:`harness.run_cell`: it wraps the
engine's runner, its model or its planner so that every step the window
(and the warm-up) drives is broken in one way.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def state_unchanged(engine) -> None:
    """Every step computes its loss but returns the state it was given."""
    r = engine.runner
    rsc, exact = r.rsc_step, r.exact_step

    def rsc_step(params, opt_state, ops, plans, key, compress=False):
        _, _, lv, norms = rsc(params, opt_state, ops, plans, key, compress)
        return params, opt_state, lv, norms

    def exact_step(params, opt_state, ops, key, compress=False):
        _, _, lv = exact(params, opt_state, ops, key, compress)
        return params, opt_state, lv

    r.rsc_step, r.exact_step = rsc_step, exact_step


def half_batch(engine) -> None:
    """Every step sees every other training node: the loss is the mean
    over the half that is left."""
    import jax.numpy as jnp

    r = engine.runner
    rsc, exact = r.rsc_step, r.exact_step
    halved = {}

    def halve(ops):
        if "ops" not in halved:
            m = np.asarray(ops.train_mask).copy()
            m[np.flatnonzero(m)[1::2]] = False
            halved["ops"] = dataclasses.replace(ops,
                                                train_mask=jnp.asarray(m))
        return halved["ops"]

    def rsc_step(params, opt_state, ops, plans, key, compress=False):
        return rsc(params, opt_state, halve(ops), plans, key, compress)

    def exact_step(params, opt_state, ops, key, compress=False):
        return exact(params, opt_state, halve(ops), key, compress)

    r.rsc_step, r.exact_step = rsc_step, exact_step


def rescaled_sample(engine) -> None:
    """Every sampled backward SpMM's output is scaled by ``1 / budget``,
    the rescale of randomized sampling that RSC's deterministic top-k
    must not apply."""
    import jax
    import types

    from repro.models.gnn import common
    from repro.train.engine import SingleDeviceRunner

    cfg, mod = engine.cfg, engine.module
    scale = 1.0 / cfg.budget
    spmm_op = common.spmm_op

    def scaled(a, at, h, plan, backend, **kw):
        if plan is not None:
            fixed = jax.lax.stop_gradient(h)
            h = fixed + scale * (h - fixed)
        return spmm_op(a, at, h, plan, backend, **kw)

    def apply(*args, **kw):
        # runs while the step is traced: only this engine's steps see it
        common.spmm_op = scaled
        try:
            return mod.apply(*args, **kw)
        finally:
            common.spmm_op = spmm_op

    faulty = types.SimpleNamespace(**{k: getattr(mod, k) for k in dir(mod)
                                      if not k.startswith("__")})
    faulty.apply = apply
    engine.runner = SingleDeviceRunner(
        faulty, engine.opt,
        mod.spmm_dims(cfg.n_layers, cfg.hidden, engine.n_classes),
        mod.spmm_names(cfg.n_layers), dropout=cfg.dropout,
        backend=cfg.backend)


def lowest_blocks(engine) -> None:
    """Each plan refresh keeps, in each layer, as many column blocks as
    the allocator chose, but those with the lowest scores."""
    from repro.core.plan import build_plan

    cache = engine.planner.cache
    refresh = cache.refresh

    def inverted(grad_row_norms):
        alloc = refresh(grad_row_norms)
        for e, k in zip(cache.ops.values(), alloc.k):
            keep = np.zeros(e.last_scores.shape[0], bool)
            keep[np.argsort(e.last_scores, kind="stable")[:k]] = True
            e.plan = build_plan(e.meta, keep, e.at.n_row_blocks,
                                e.at.s_total, bucket=cache._bucket(e.at))
        return alloc

    cache.refresh = inverted


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "rescaled_sample": rescaled_sample, "lowest_blocks": lowest_blocks}
# the faults a cell without RSC can have
EXACT_FAULTS = ("state_unchanged", "half_batch")
