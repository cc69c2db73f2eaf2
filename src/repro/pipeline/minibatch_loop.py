"""Minibatch GraphSAINT training as configurations of the unified Engine.

The loop mechanics (switch-back schedule, step dispatch, metrics,
checkpointing) live in :mod:`repro.train.engine`; this module supplies the
pooled pieces:

* :class:`PooledSource` — prefetched subgraph-pool batches (one subgraph
  per step, shape-bucketed, double-buffered host→device upload);
* :class:`PooledPlanner` — the per-subgraph :class:`PlanCachePool` adapter
  (paper §3.3.1 footnote 1: caches per sampled subgraph, own clocks);
* :func:`pooled_evaluate` — pooled evaluation with node-multiplicity
  dedup: logits of nodes shared by overlapping random-walk subgraphs are
  averaged in parent-graph id space and every node is scored exactly once
  (for disjoint ``ldg`` pools this is identical to the old path);
* :func:`minibatch_engine` — the factory wiring pool, planner and (for
  ``dp > 1``) the mesh-sharded source + data-parallel runner together;
* :class:`MinibatchTrainer` — the historical API, now a thin shell.

The switch-back schedule (§3.3.2) runs on the GLOBAL step counter
(epochs × steps-per-epoch): the last (1−rsc_fraction) of all minibatch
steps are exact, mirroring the full-batch loop's tail. With gradient
compression enabled, the switch-back applies to the compressor as well —
the exact tail all-reduces uncompressed f32 gradients.

One epoch = one pass over the pool in a seeded random order. With the
``ldg`` partitioner the parts are disjoint and cover the graph, so an epoch
touches every training node exactly once, like classic minibatch SGD.
"""
from __future__ import annotations

import dataclasses
import logging
from collections import OrderedDict

import numpy as np

from repro import obs
from repro.core.schedule import RSCSchedule
from repro.graphs.synthetic import GraphData
from repro.models.gnn import MODELS
from repro.pipeline.partition import PoolConfig, SubgraphPool, build_pool
from repro.pipeline.plan_pool import PlanCachePool
from repro.pipeline.prefetch import Prefetcher
from repro.train.engine import Engine, TrainConfig


@dataclasses.dataclass
class MinibatchConfig(TrainConfig):
    """TrainConfig + pool/prefetch/data-parallel knobs.

    ``epochs`` = passes over the pool. ``dp > 1`` shards the pool across a
    ``("data",)`` mesh of that many devices and all-reduces gradients each
    step; multi-bucket pools work under DP via bucket-grouped stacking
    (every bucket must split evenly across shards — each step stacks one
    SAME-bucket subgraph per device). ``compress_grads`` routes the
    all-reduce through the int8 error-feedback compressor.
    """

    n_subgraphs: int = 8
    method: str = "random_walk"      # or "ldg"
    roots: int = 200
    walk_length: int = 4
    n_buckets: int = 2
    prefetch: bool = True
    prefetch_depth: int = 2
    resident: int = 0                # device-resident subgraph cache size
    autotune: bool = True            # sweep SpMM tile configs per bucket
    saint_norm: bool = True          # GraphSAINT λ/α bias correction
    # Data-parallel
    dp: int = 0                      # 0/1 = single device; N = shards
    compress_grads: bool = False     # int8 EF compression on the all-reduce
    compress_block: int = 128
    overlap_allreduce: bool = False  # per-bucket pmean over grad buckets
    overlap_buckets: int = 4


def tune_buckets(pool: SubgraphPool, cfg, dims: dict[str, int],
                 n_classes: int) -> dict[str, object]:
    """One autotuner sweep per (bucket shape × dim × plan length).

    Forward SpMMs run the bucket's exact plan (``s_pad`` tiles); sampled
    backward SpMMs run bucketed plans of ``plan_pad`` entries — both
    signatures get tuned so trace-time lookups always hit. Runs BEFORE the
    step functions trace; dispatch reads the tuned configs from the
    process-wide autotune cache at trace time, and every subgraph of a
    bucket shares the bucket's signature, so the decision is made exactly
    once per bucket (and persists across processes via the JSON cache).
    """
    from repro.kernels import autotune

    backend = cfg.backend
    # feat_dim covers layer-0 SpMMs over raw features (GraphSAGE).
    dim_set = sorted({cfg.hidden, n_classes, pool.feat_dim,
                      *dims.values()})
    tuned: dict[str, object] = {}
    for b in pool.buckets:
        for d in dim_set:
            for s_pad in {b.s_pad, b.plan_pad}:
                sig = autotune.signature(
                    backend, bm=cfg.block, bk=cfg.block, d=d,
                    s_pad=s_pad, n_row_blocks=b.n_blocks,
                    n_col_blocks=b.n_blocks)
                if sig not in tuned:
                    tuned[sig] = autotune.get_or_tune(
                        backend, bm=cfg.block, bk=cfg.block, d=d,
                        s_pad=s_pad, n_row_blocks=b.n_blocks,
                        n_col_blocks=b.n_blocks)
    return tuned


def pooled_evaluate(pool: SubgraphPool, eval_fn, mfn, params, *,
                    prefetch: bool = True, depth: int = 2,
                    resident: int = 0,
                    cache: OrderedDict | None = None) -> tuple[float, float]:
    """Pooled evaluation deduplicated by node multiplicity.

    Logits are accumulated in parent-graph id space — a node appearing in
    several overlapping subgraphs contributes the MEAN of its per-subgraph
    logits and is scored exactly once, so the metric is computed over the
    set of covered nodes, not the multiset of appearances. For disjoint
    ``ldg`` pools every node appears once and this equals the old
    per-subgraph weighting exactly.
    """
    sum_logits: np.ndarray | None = None
    counts = np.zeros(pool.n_nodes, dtype=np.float32)
    fetch = Prefetcher(pool, range(len(pool)), depth=depth,
                       enabled=prefetch, resident=resident, cache=cache)
    for sid, ops in fetch:
        sub = pool.subgraphs[sid]
        logits = np.asarray(eval_fn(params, ops))[: sub.n_valid]
        if sum_logits is None:
            sum_logits = np.zeros((pool.n_nodes, logits.shape[1]),
                                  dtype=np.float64)
        # parent ids are unique within one subgraph → plain fancy-index add
        sum_logits[sub.nodes] += logits
        counts[sub.nodes] += 1.0
    seen = counts > 0
    mean_logits = (sum_logits
                   / np.maximum(counts, 1.0)[:, None]).astype(np.float32)
    val = mfn(mean_logits, pool.node_labels, pool.node_val_mask & seen)
    test = mfn(mean_logits, pool.node_labels, pool.node_test_mask & seen)
    return val, test


class PooledPlanner:
    """Engine planner adapter over the per-subgraph PlanCachePool."""

    def __init__(self, pool: SubgraphPool, names, dims, *,
                 budget_frac: float, step_frac: float, strategy: str,
                 refresh_every: int):
        self.pool = pool
        self.plan_pool = PlanCachePool(
            pool, names, dims, budget_frac=budget_frac,
            step_frac=step_frac, strategy=strategy,
            refresh_every=refresh_every)

    def plans_for(self, tag, step: int, schedule: RSCSchedule):
        return self.plan_pool.plans_for(self.pool.subgraphs[int(tag)])

    def record(self, tag, norms) -> None:
        self.plan_pool.record_norms(
            int(tag), {k: np.asarray(v) for k, v in norms.items()})

    def flops_fraction(self) -> float:
        return self.plan_pool.flops_fraction()

    def hit_rate(self) -> float | None:
        return self.plan_pool.stats.hit_rate

    def stats(self):
        return self.plan_pool.stats

    def k_latest(self):
        return None

    def publish(self, registry) -> None:
        self.plan_pool.publish(registry)

    def probe_entries(self):
        return self.plan_pool.probe_entries()

    def state_dict(self):
        return self.plan_pool.state_dict()

    def load_state_dict(self, state) -> None:
        self.plan_pool.load_state_dict(state)


class PooledSource:
    """Prefetched subgraph-pool batches: one subgraph per step."""

    def __init__(self, pool: SubgraphPool, cfg: MinibatchConfig):
        self.pool = pool
        self.cfg = cfg
        self.steps_per_epoch = len(pool)
        self.num_classes = pool.num_classes
        self.feat_dim = pool.feat_dim
        self.n_buckets = len(pool.buckets)
        self._order_rng = np.random.default_rng(cfg.seed)
        # Resident device-operand LRU shared by train epochs and eval
        # sweeps (None => stream every visit).
        self._device_cache = OrderedDict() if cfg.resident > 0 else None

    def warmup(self, cfg, dims, n_classes) -> None:
        tune_buckets(self.pool, cfg, dims, n_classes)

    def batches(self, epoch: int, skip: int = 0):
        cfg = self.cfg
        # The full permutation is ALWAYS drawn (the RNG stream must advance
        # identically whether or not a resume skips a prefix); ``skip``
        # only trims what is uploaded and yielded.
        order = self._order_rng.permutation(len(self.pool))[skip:]
        fetch = Prefetcher(
            self.pool, order,
            depth=cfg.prefetch_depth, enabled=cfg.prefetch,
            resident=cfg.resident, cache=self._device_cache)
        for sid, ops in fetch:
            yield int(sid), ops

    def state_dict(self):
        return {"order_rng": self._order_rng.bit_generator.state}

    def load_state_dict(self, state) -> None:
        if state is not None:
            self._order_rng.bit_generator.state = state["order_rng"]

    def evaluate(self, eval_fn, mfn, params) -> tuple[float, float]:
        cfg = self.cfg
        return pooled_evaluate(
            self.pool, eval_fn, mfn, params,
            prefetch=cfg.prefetch, depth=cfg.prefetch_depth,
            resident=cfg.resident, cache=self._device_cache)


def _build_default_pool(cfg: MinibatchConfig, graph: GraphData,
                        n_buckets: int) -> SubgraphPool:
    return build_pool(
        graph,
        PoolConfig(n_subgraphs=cfg.n_subgraphs, method=cfg.method,
                   roots=cfg.roots, walk_length=cfg.walk_length,
                   n_buckets=n_buckets, block=cfg.block,
                   degree_sort=cfg.degree_sort, seed=cfg.seed,
                   saint_norm=cfg.saint_norm),
        mean_agg=MODELS[cfg.model].uses_mean_agg())


def minibatch_engine(cfg: MinibatchConfig, graph: GraphData | None = None,
                     pool: SubgraphPool | None = None,
                     mesh=None) -> Engine:
    """Assemble the minibatch Engine: pooled or mesh-sharded.

    ``cfg.dp > 1`` builds/validates a single-bucket pool, shards it over a
    ``("data",)`` mesh (``mesh`` arg, or a fresh one over the first ``dp``
    local devices) and installs the data-parallel runner with per-shard
    plan caches. Otherwise this is the classic single-device pipeline.
    """
    module = MODELS[cfg.model]
    dp = int(cfg.dp or 0)
    if pool is None:
        if graph is None:
            raise ValueError("need a graph or a prebuilt pool")
        pool = _build_default_pool(cfg, graph, n_buckets=cfg.n_buckets)
        # Bucket-grouped stacking needs every bucket to split evenly
        # across shards; if this pool's bucket sizes don't, rebuild
        # single-bucket rather than fail (prebuilt pools must comply).
        # A pool size not divisible by dp is a USER error no rebuild can
        # fix — leave it to surface downstream with its own message.
        if dp > 1 and cfg.n_buckets > 1 and len(pool) % dp == 0:
            from repro.pipeline.sharding import shard_pool_ids
            try:
                shard_pool_ids(pool, dp)
            except ValueError:
                pool = _build_default_pool(cfg, graph, n_buckets=1)
    if module.uses_mean_agg() != pool.mean_agg:
        raise ValueError(
            f"pool built with mean_agg={pool.mean_agg} but model "
            f"{cfg.model!r} needs mean_agg={module.uses_mean_agg()}")

    # GraphSAINT λ/α correction status, logged ONCE at startup: whether
    # the pool carries 1/λ_v loss weights (and α-normalized operands) is
    # invisible later and silently biases sampled-pool training when off.
    corrected = pool.subgraphs[0].loss_w is not None
    logging.getLogger("repro.obs").info(
        "GraphSAINT λ/α bias correction %s (pool method=%s, "
        "saint_norm=%s)",
        "ACTIVE" if corrected else "OFF",
        cfg.method, getattr(cfg, "saint_norm", None))
    obs.get_tracer().instant("saint_correction", active=corrected,
                             method=cfg.method)
    obs.get_registry().gauge("saint.correction_active", float(corrected))

    names = module.spmm_names(cfg.n_layers)
    dims = module.spmm_dims(cfg.n_layers, cfg.hidden, pool.num_classes)
    refresh = cfg.refresh_every if cfg.caching else 1

    if dp > 1:
        from repro.launch.mesh import make_dp_mesh
        from repro.pipeline.sharding import (ShardedPlanner,
                                             ShardedPoolSource)
        mesh = mesh if mesh is not None else make_dp_mesh(dp)
        source = ShardedPoolSource(pool, cfg, mesh)
        planner = ShardedPlanner(
            pool, source.shards, names, dims,
            budget_frac=cfg.budget, step_frac=cfg.step_frac,
            strategy=cfg.strategy, refresh_every=refresh,
            mesh=mesh) if cfg.rsc else None
        return Engine(cfg, source, planner=planner, mesh=mesh,
                      compress_grads=cfg.compress_grads,
                      compress_block=cfg.compress_block,
                      overlap_allreduce=cfg.overlap_allreduce,
                      overlap_buckets=cfg.overlap_buckets, graph=graph)

    source = PooledSource(pool, cfg)
    planner = PooledPlanner(
        pool, names, dims, budget_frac=cfg.budget,
        step_frac=cfg.step_frac, strategy=cfg.strategy,
        refresh_every=refresh) if cfg.rsc else None
    return Engine(cfg, source, planner=planner, graph=graph)


class MinibatchTrainer:
    """GraphSAINT-style minibatch trainer over a bucketed subgraph pool.

    A named configuration of :class:`repro.train.engine.Engine`; kept for
    API compatibility (tests, examples, benchmarks construct it directly).
    """

    def __init__(self, cfg: MinibatchConfig, graph: GraphData | None = None,
                 pool: SubgraphPool | None = None, mesh=None):
        self.cfg = cfg
        self.engine: Engine = minibatch_engine(cfg, graph, pool, mesh)
        self.pool: SubgraphPool = self.engine.source.pool
        self.module = MODELS[cfg.model]

    @property
    def params(self):
        return self.engine.params

    @property
    def plan_pool(self):
        planner = self.engine.planner
        return getattr(planner, "plan_pool", None)

    @property
    def schedule(self):
        return self.engine.schedule

    @property
    def history(self):
        return self.engine.history

    def train(self, epochs: int | None = None, eval_every: int = 5,
              verbose: bool = False) -> dict:
        return self.engine.train(epochs=epochs, eval_every=eval_every,
                                 verbose=verbose)

    def evaluate(self, mfn=None) -> tuple[float, float]:
        return self.engine.evaluate(mfn)

    def compile_counts(self) -> dict[str, int | None]:
        return self.engine.runner.compile_counts()
