"""Unified RSC training engine: one loop skeleton, pluggable data sources.

Full-batch, minibatch (prefetched subgraph pool) and data-parallel
(mesh-sharded subgraph pool) training used to be separate hand-rolled
drivers; they are now configurations of one :class:`Engine` that owns

* the :class:`~repro.core.schedule.RSCSchedule` (switch-back §3.3.2 on the
  global step counter),
* the plan caches and their refresh clocks (§3.3.1) behind a
  :class:`Planner` adapter,
* the SpMM autotune warmup (delegated to the source, which knows its
  shape buckets),
* metrics/history bookkeeping and optional checkpointing,
* the jitted step functions behind a :class:`Runner` adapter — single
  device, or ``shard_map`` over a ``("data",)`` mesh with pmean'd
  gradients and optional int8 error-feedback compression.

A **data source** yields ``(tag, operands)`` batches per epoch — the tag
identifies the plan-cache identity (``None`` for the full graph, a subgraph
id for a pool, a tuple of per-shard ids for a sharded pool) — and knows how
to evaluate. A **planner** maps tags to RSC sampling plans and absorbs the
gradient row norms each step reports. A **runner** executes one optimizer
step. The engine never needs to know which flavor it is driving.

Concrete pooled/sharded sources live in ``repro/pipeline`` (they depend on
the pool machinery); the full-graph source lives here.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro import obs
from repro.core.cache import PlanCache
from repro.obs import context as trace_context
from repro.core.schedule import RSCSchedule
from repro.obs.sentinel import CompileSentinel, jit_compiles  # noqa: F401
                                          # (jit_compiles re-exported: it
                                          # lived here before repro.obs)
from repro.graphs.synthetic import GraphData
from repro.kernels.ops import publish_grouping, require_tpu
from repro.models.gnn import MODELS
from repro.models.gnn.common import build_operands
from repro.sparse.bcoo import host_row_ptr
from repro.train.metrics import metric_fn
from repro.train.optimizer import Adam
from repro.train.steps import (init_error_feedback, make_dp_gnn_steps,
                               make_gnn_steps)


@dataclasses.dataclass
class TrainConfig:
    model: str = "gcn"
    n_layers: int = 3
    hidden: int = 256
    dropout: float = 0.5
    batchnorm: bool = True
    lr: float = 0.01
    weight_decay: float = 0.0
    epochs: int = 400
    seed: int = 0
    metric: str = "accuracy"
    # RSC
    rsc: bool = False
    budget: float = 0.1
    step_frac: float = 0.02
    refresh_every: int = 10
    allocate_every: int = 10
    rsc_fraction: float = 0.8
    caching: bool = True         # False ⇒ refresh every step (Table 4 ablation)
    switching: bool = True       # False ⇒ rsc for 100% of epochs
    strategy: str = "greedy"     # "uniform" for Fig. 6 baseline
    backend: str = "jnp"
    block: int = 128             # bm == bk
    degree_sort: bool = True
    # Evaluation: "auto" keeps the source's evaluator (dense full-graph /
    # pooled dedup); "stream" swaps in exact streaming full-graph inference
    # (repro/infer) — under minibatch training this makes the reported
    # accuracy an exact full-graph measurement instead of a pool estimate.
    eval_mode: str = "auto"
    stream_partitions: int = 0       # 0 = size by stream_budget_mb
    stream_budget_mb: float = 256.0
    stream_resident_mb: float = 0.0  # >0: device partition LRU budget
    stream_overlap: bool = False     # double-buffer partition uploads
    # Checkpointing (optional): save (params, opt_state) every N global
    # steps to ckpt_dir. Engine.restore() resumes STEP-EXACTLY when the
    # checkpoint carries engine state (planner clocks, pool cursor, RNG
    # key), and falls back to a warm start otherwise.
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    # Observability: the engine always records through the process-wide
    # repro.obs bundle (no-op unless obs.configure() enabled it).
    # ``strict_compiles`` arms the retrace sentinel to HARD-FAIL when a
    # step function compiles more often than the one-compile-per-bucket
    # invariant allows (tests/CI; production runs just get the counters).
    strict_compiles: bool = False
    # ``strict_budget`` does the same for the approximation ledger's
    # conservation invariant: any allocator run whose achieved cost
    # exceeds its budget raises BudgetError at the next epoch boundary
    # (expected to fire only under strategy="uniform", which the paper's
    # Fig. 6 shows violates the budget by construction).
    strict_budget: bool = False
    # Online error probes (obs.probe): every ``probe_every`` epochs run a
    # cheap exact-vs-sampled comparison on ``probe_rows`` row blocks per
    # RSC op with a ``probe_dim``-wide Gaussian probe matrix; estimates
    # land in the ledger time series + registry gauges. 0 disables.
    probe_every: int = 1
    probe_rows: int = 8
    probe_dim: int = 8


# ---------------------------------------------------------------------------
# Planners: map batch tags to sampling plans, absorb gradient row norms.
# ---------------------------------------------------------------------------

class NullPlanner:
    """RSC off: no plans, no stats."""

    def plans_for(self, tag, step: int, schedule: RSCSchedule):
        raise RuntimeError("NullPlanner has no plans (rsc disabled)")

    def record(self, tag, norms) -> None:
        pass

    def flops_fraction(self) -> float:
        return 1.0

    def hit_rate(self) -> float | None:
        return None

    def stats(self):
        return None

    def k_latest(self):
        return None

    def publish(self, registry) -> None:
        pass

    def probe_entries(self):
        """(name, at, meta, plan, d) tuples for the error probes."""
        return []

    def state_dict(self):
        return None

    def load_state_dict(self, state) -> None:
        pass


class FullGraphPlanner:
    """One :class:`PlanCache` refreshed on the global schedule clock from
    the previous step's gradient row norms (exactly the full-batch loop's
    §3.3.1 behavior)."""

    def __init__(self, cfg: TrainConfig, module, at, meta, fro: float,
                 n_classes: int):
        self.cache = PlanCache(budget_frac=cfg.budget,
                               step_frac=cfg.step_frac,
                               strategy=cfg.strategy)
        self.backend = cfg.backend
        names = module.spmm_names(cfg.n_layers)
        dims = module.spmm_dims(cfg.n_layers, cfg.hidden, n_classes)
        for n in names:
            self.cache.register(n, at, meta, dims[n], fro)
        self._publish_grouping()
        self._last_norms: dict[str, np.ndarray] | None = None
        self._refresh_norms: dict[str, np.ndarray] | None = None

    def _publish_grouping(self) -> None:
        for n, e in self.cache.ops.items():
            publish_grouping(e.row_ptr, e.at, e.d, layer=n,
                             op="spmm_bwd_sampled", backend=self.backend)

    def plans_for(self, tag, step: int, schedule: RSCSchedule):
        if self._last_norms is not None and schedule.refresh_due(step):
            self.cache.refresh(self._last_norms)
            self._publish_grouping()
            self._refresh_norms = self._last_norms
        return self.cache.plans()

    def record(self, tag, norms) -> None:
        self._last_norms = {k: np.asarray(v) for k, v in norms.items()}

    def flops_fraction(self) -> float:
        return self.cache.flops_fraction()

    def hit_rate(self) -> float | None:
        return None

    def stats(self):
        return self.cache.stats

    def k_latest(self):
        kh = self.cache.stats.k_history
        return kh[-1] if kh else None

    def publish(self, registry) -> None:
        """Plan-cache clock stats → registry gauges (epoch-end dump)."""
        s = self.cache.stats
        registry.gauge("plan_cache.refreshes", s.refreshes)
        registry.gauge("plan_cache.allocations", s.allocations)
        registry.gauge("plan_cache.host_seconds", s.host_seconds)
        registry.gauge("rsc.flops_fraction", self.flops_fraction())
        k = self.k_latest()
        if k is not None:
            vals = list(k.values()) if isinstance(k, dict) else k
            registry.gauge("rsc.k_latest", float(np.sum(vals)))

    def probe_entries(self):
        return [(n, e.at, e.meta, e.plan, e.d)
                for n, e in self.cache.ops.items()]

    def state_dict(self):
        """Everything a resumed run needs to rebuild the current plans:
        the allocator is a pure function of its latest refresh norms, so
        replaying them reproduces the plans exactly."""
        return {"last_norms": self._last_norms,
                "refresh_norms": self._refresh_norms,
                "refreshes": self.cache.stats.refreshes}

    def load_state_dict(self, state) -> None:
        if state is None:
            return
        if state.get("refresh_norms") is not None:
            self.cache.refresh(state["refresh_norms"])
            self._publish_grouping()
            self._refresh_norms = state["refresh_norms"]
        self.cache.stats.refreshes = state.get("refreshes",
                                               self.cache.stats.refreshes)
        self._last_norms = state.get("last_norms")


# ---------------------------------------------------------------------------
# Runners: execute one optimizer step (single device / data parallel).
# ---------------------------------------------------------------------------

class SingleDeviceRunner:
    """Jitted single-device steps shared by full-batch and minibatch."""

    supports_compression = False

    def __init__(self, module, opt, dims, names, *, dropout: float,
                 backend: str):
        rsc_step, exact_step, eval_logits = make_gnn_steps(
            module, opt, dims, names, dropout=dropout, backend=backend)
        self._rsc = jax.jit(rsc_step)
        self._exact = jax.jit(exact_step)
        self._eval = jax.jit(eval_logits)

    def rsc_step(self, params, opt_state, ops, plans, key,
                 compress: bool = False):
        return self._rsc(params, opt_state, ops, plans, key)

    def exact_step(self, params, opt_state, ops, key,
                   compress: bool = False):
        return self._exact(params, opt_state, ops, key)

    def eval_logits(self, params, ops):
        return self._eval(params, ops)

    def compile_counts(self) -> dict[str, int | None]:
        return {"rsc": jit_compiles(self._rsc),
                "exact": jit_compiles(self._exact),
                "eval": jit_compiles(self._eval)}

    def state_dict(self):
        return None

    def load_state_dict(self, state) -> None:
        pass


class DataParallelRunner:
    """``shard_map`` steps over a ``("data",)`` mesh: one subgraph shard per
    device, gradients pmean'd across the axis — optionally through the int8
    error-feedback compressor. Holds the per-device EF accumulators;
    evaluation stays single-device (pooled eval streams subgraphs).
    """

    supports_compression = True

    def __init__(self, module, opt, dims, names, *, dropout: float,
                 backend: str, mesh, axis: str = "data",
                 compress_block: int = 128,
                 overlap_allreduce: bool = False,
                 overlap_buckets: int = 4):
        from functools import partial

        rsc_step, exact_step, eval_logits = make_dp_gnn_steps(
            module, opt, dims, names, dropout=dropout, backend=backend,
            mesh=mesh, axis=axis, compress_block=compress_block,
            overlap_allreduce=overlap_allreduce,
            overlap_buckets=overlap_buckets)
        self.mesh = mesh
        self.axis = axis
        self.n_devices = int(mesh.shape[axis])
        self._rsc = {c: jax.jit(partial(rsc_step, compress=c))
                     for c in (False, True)}
        self._exact = {c: jax.jit(partial(exact_step, compress=c))
                       for c in (False, True)}
        self._eval = jax.jit(eval_logits)
        # Error-feedback accumulators cost n_devices × params f32: allocate
        # lazily on the first compressed step. Uncompressed traces thread an
        # EMPTY pytree instead, so they never pay memory or pass-through.
        self._err = None

    def _err_state(self, params, compress: bool):
        if not compress:
            return {}
        if self._err is None:
            self._err = init_error_feedback(params, self.n_devices)
        return self._err

    def rsc_step(self, params, opt_state, ops, plans, key, compress: bool):
        compress = bool(compress)
        keys = jax.random.split(key, self.n_devices)
        params, opt_state, lv, norms, err = self._rsc[compress](
            params, opt_state, self._err_state(params, compress),
            ops, plans, keys)
        if compress:
            self._err = err
        return params, opt_state, lv, norms

    def exact_step(self, params, opt_state, ops, key, compress: bool):
        compress = bool(compress)
        keys = jax.random.split(key, self.n_devices)
        params, opt_state, lv, err = self._exact[compress](
            params, opt_state, self._err_state(params, compress),
            ops, keys)
        if compress:
            self._err = err
        return params, opt_state, lv

    def eval_logits(self, params, ops):
        return self._eval(params, ops)

    def compile_counts(self) -> dict[str, int | None]:
        def tot(d):
            ns = [jit_compiles(f) for f in d.values()]
            return None if all(n is None for n in ns) \
                else sum(n or 0 for n in ns)
        return {"rsc": tot(self._rsc), "exact": tot(self._exact),
                "eval": jit_compiles(self._eval)}

    def state_dict(self):
        """Error-feedback accumulators (compressed all-reduce state)."""
        if self._err is None:
            return None
        return jax.tree.map(np.asarray, self._err)

    def load_state_dict(self, state) -> None:
        if state is not None:
            import jax.numpy as jnp
            self._err = jax.tree.map(jnp.asarray, state)


# ---------------------------------------------------------------------------
# Full-graph data source (pooled/sharded sources live in repro.pipeline).
# ---------------------------------------------------------------------------

class FullGraphSource:
    """The whole graph as one resident batch, every step."""

    n_buckets = 1
    steps_per_epoch = 1

    def __init__(self, graph: GraphData, cfg: TrainConfig, module):
        self.ops, self.meta = build_operands(
            graph, bm=cfg.block, bk=cfg.block,
            degree_sort=cfg.degree_sort)
        self.num_classes = graph.num_classes
        self.feat_dim = graph.features.shape[1]
        self.mean_agg = module.uses_mean_agg()
        fwd = "am" if self.mean_agg else "a"
        dims = module.spmm_dims(cfg.n_layers, cfg.hidden, self.num_classes)
        for n in module.spmm_names(cfg.n_layers):
            for op, key in (("spmm_fwd", fwd), ("spmm_bwd_exact", fwd + "t")):
                a = getattr(self.ops, key)
                rows = getattr(self.meta, key + "_meta").row_ids
                publish_grouping(host_row_ptr(rows, a.n_row_blocks), a,
                                 dims[n], layer=n, op=op,
                                 backend=cfg.backend)

    def planner_operand(self):
        """(at, meta, fro) of the backward operand the planner scores."""
        if self.mean_agg:
            return self.ops.amt, self.meta.amt_meta, self.meta.am_fro
        return self.ops.at, self.meta.at_meta, self.meta.a_fro

    def warmup(self, cfg, dims, n_classes) -> None:
        pass

    def batches(self, epoch: int, skip: int = 0):
        if skip == 0:
            yield None, self.ops

    def state_dict(self):
        return None

    def load_state_dict(self, state) -> None:
        pass

    def evaluate(self, eval_fn, mfn, params) -> tuple[float, float]:
        tracer = obs.get_tracer()
        with tracer.span("eval.logits"):
            logits = np.asarray(eval_fn(params, self.ops))
        with tracer.span("eval.metric"):
            labels = np.asarray(self.ops.labels)
            valid = np.arange(logits.shape[0]) < self.ops.n_valid
            val = mfn(logits, labels, np.asarray(self.ops.val_mask) & valid)
            test = mfn(logits, labels,
                       np.asarray(self.ops.test_mask) & valid)
        return val, test


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

class Engine:
    """One training loop for every RSC configuration.

    The caller assembles a source and (optionally) a planner; the engine
    builds params/optimizer/schedule/runner, owns the step loop, the
    switch-back clock, metrics and checkpointing. ``mesh`` switches the
    runner to data-parallel ``shard_map`` execution — the source must then
    yield device-stacked operand batches (see
    ``repro.pipeline.sharding.ShardedPoolSource``).
    """

    def __init__(self, cfg: TrainConfig, source, *, planner=None,
                 mesh=None, compress_grads: bool = False,
                 compress_block: int = 128,
                 overlap_allreduce: bool = False,
                 overlap_buckets: int = 4, graph=None):
        require_tpu(cfg.backend)
        self.cfg = cfg
        self.source = source
        self.module = MODELS[cfg.model]
        self.planner = planner if planner is not None else NullPlanner()
        self.compress_grads = compress_grads
        self.n_classes = source.num_classes

        key = jax.random.PRNGKey(cfg.seed)
        self.params = self.module.init(
            key, source.feat_dim, cfg.hidden, self.n_classes, cfg.n_layers,
            cfg.batchnorm)
        self.opt = Adam(lr=cfg.lr, weight_decay=cfg.weight_decay)
        self.opt_state = self.opt.init(self.params)

        rsc_frac = cfg.rsc_fraction if cfg.switching else 1.0
        refresh = cfg.refresh_every if cfg.caching else 1
        self.schedule = RSCSchedule(
            total_steps=cfg.epochs * source.steps_per_epoch,
            rsc_fraction=rsc_frac,
            refresh_every=refresh, allocate_every=refresh)

        names = self.module.spmm_names(cfg.n_layers)
        dims = self.module.spmm_dims(cfg.n_layers, cfg.hidden,
                                     self.n_classes)
        # Autotune warmup happens BEFORE the steps trace: dispatch reads
        # the tuned tile configs from the process-wide cache at trace time.
        if getattr(cfg, "autotune", False):
            source.warmup(cfg, dims, self.n_classes)

        if mesh is not None:
            # Commit params/opt state replicated on the mesh up front:
            # otherwise the first step sees uncommitted inputs, the second
            # sees its own committed outputs, and jit retraces once.
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(mesh, PartitionSpec())
            self.params = jax.device_put(self.params, rep)
            self.opt_state = jax.device_put(self.opt_state, rep)
            self.runner = DataParallelRunner(
                self.module, self.opt, dims, names,
                dropout=cfg.dropout, backend=cfg.backend, mesh=mesh,
                compress_block=compress_block,
                overlap_allreduce=overlap_allreduce,
                overlap_buckets=overlap_buckets)
        else:
            self.runner = SingleDeviceRunner(
                self.module, self.opt, dims, names,
                dropout=cfg.dropout, backend=cfg.backend)

        # Retrace sentinel: the step functions must compile once per shape
        # bucket (pooled plans share a fixed per-bucket plan_pad). The
        # full-batch RSC step is exempt from a hard limit — its plan
        # lengths re-bucket on the s_pad quantization grid, which is a
        # bounded-but-unpredictable handful of recompiles by design.
        self.obs = obs.get_obs()
        # Approximation ledger: per-layer hidden dims + tile shape give it
        # the FLOPs/bytes cost model; everything else arrives as events.
        self.ledger = self.obs.ledger
        self.ledger.set_dims(dims, bm=cfg.block, bk=cfg.block)
        nb = source.n_buckets
        mult = 2 if (mesh is not None and compress_grads) else 1
        rsc_limit = (None if isinstance(self.planner, FullGraphPlanner)
                     else nb * mult)
        self.sentinel = CompileSentinel(registry=self.obs.registry,
                                        hard_fail=cfg.strict_compiles)
        counts = self.runner.compile_counts
        self.sentinel.watch("step.rsc", lambda: counts()["rsc"],
                            limit=rsc_limit)
        self.sentinel.watch("step.exact", lambda: counts()["exact"],
                            limit=nb * mult)
        self.sentinel.watch("step.eval", lambda: counts()["eval"],
                            limit=nb)

        # Streaming full-graph evaluator (repro/infer): exact accuracy
        # even when the source's own evaluator only covers pooled nodes.
        self.stream_eval = None
        if cfg.eval_mode == "stream":
            if graph is None:
                raise ValueError('eval_mode="stream" needs the full graph '
                                 "(pass graph= to the engine factory)")
            from repro.infer.stream import StreamConfig, StreamEvaluator
            self.stream_eval = StreamEvaluator(
                graph, cfg.model,
                StreamConfig(
                    block=cfg.block,
                    n_partitions=cfg.stream_partitions or None,
                    memory_budget_mb=(None if cfg.stream_partitions
                                      else cfg.stream_budget_mb),
                    backend=cfg.backend,
                    degree_sort=cfg.degree_sort,
                    resident_mb=cfg.stream_resident_mb or None,
                    overlap=cfg.stream_overlap))
            # One compile per (layer, mode) — checked against the total
            # once the lazily-built StreamingInference exists.
            se = self.stream_eval
            self.sentinel.watch(
                "stream_eval.layers",
                lambda: (None if se.si is None
                         else max(se.si.compile_counts().values(),
                                  default=0)),
                limit=1)

        self.ckpt = None
        self._ckpt_base = 0   # step offset after restore(): saved step
                              # numbers keep increasing across warm-starts
                              # so the checkpointer's keep-k GC never
                              # prefers a stale pre-restore snapshot
        self._resume = None   # aux dict of an exact restore, one-shot
        if cfg.ckpt_dir:
            from repro.checkpoint.checkpointer import Checkpointer
            self.ckpt = Checkpointer(cfg.ckpt_dir)

        self.history: dict[str, list] = {
            "loss": [], "val": [], "test": [], "step_time": [],
            "mode": [], "k": [], "sub_id": [], "compress": []}

    # ------------------------------------------------------------------
    def _capture_state(self, epoch: int, batch_idx: int, gstep: int, key,
                       best: tuple[float, float]) -> dict:
        """Engine state alongside a (params, opt_state) snapshot: enough
        to make restore step-exact (planner clocks + refresh norms, pool
        cursor via the epoch-start source RNG state, the live PRNG key)."""
        return {
            "gstep": gstep, "epoch": epoch, "batch_idx": batch_idx,
            "key": np.asarray(key), "best": best,
            "source": self._epoch_src_state,
            "planner": self.planner.state_dict(),
            "runner": self.runner.state_dict(),
        }

    def restore(self, step: int | None = None) -> int | None:
        """Restore (params, opt_state) from a checkpoint.

        When the checkpoint carries engine state (saved by this engine's
        own ``train`` loop), the restore is STEP-EXACT: the next ``train``
        call continues mid-epoch with the saved RNG key, pool cursor and
        plan-cache clocks, reproducing the uninterrupted trajectory.
        Without aux state this degrades to the old warm start. Returns the
        checkpoint step, or None if there is none.
        """
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return None
        step, (self.params, self.opt_state) = self.ckpt.restore(
            (self.params, self.opt_state), step=step)
        aux = self.ckpt.load_aux(step)
        if aux is not None:
            self.planner.load_state_dict(aux.get("planner"))
            self.runner.load_state_dict(aux.get("runner"))
            self.source.load_state_dict(aux.get("source"))
            self._resume = aux
            self._ckpt_base = step - aux["gstep"]
        else:
            self._ckpt_base = step
        return step

    # ------------------------------------------------------------------
    def train(self, epochs: int | None = None, eval_every: int = 10,
              verbose: bool = False) -> dict:
        cfg = self.cfg
        epochs = epochs if epochs is not None else cfg.epochs
        total = epochs * self.source.steps_per_epoch
        if total != self.schedule.total_steps:
            # keep the switch-back fraction relative to the run actually
            # executed, not the configured one
            self.schedule = dataclasses.replace(
                self.schedule, total_steps=total)
        key = jax.random.PRNGKey(cfg.seed + 1)
        mfn = metric_fn(cfg.metric)
        best_val, best_test = -1.0, -1.0
        gstep = 0
        start_epoch, skip = 0, 0
        self._epoch_src_state = None
        if self._resume is not None:
            # Step-exact continuation from restore(): re-enter the saved
            # epoch at the saved batch cursor with the saved PRNG key. The
            # source re-draws its epoch permutation from the restored
            # epoch-start RNG state, so the skipped prefix is exactly the
            # prefix the pre-checkpoint run consumed.
            r, self._resume = self._resume, None
            start_epoch, skip = r["epoch"], r["batch_idx"]
            gstep = r["gstep"]
            key = jax.numpy.asarray(r["key"])
            best_val, best_test = r["best"]

        reg, tracer = self.obs.registry, self.obs.tracer
        ledger = self.ledger
        for epoch in range(start_epoch, epochs):
            ledger.set_epoch(epoch)
            self._epoch_src_state = self.source.state_dict()
            batch_it = enumerate(self.source.batches(epoch, skip=skip),
                                 start=skip)
            while True:
                # Sample/fetch time: blocking on the source iterator is the
                # prefetcher-starved time (~0 when the upload thread keeps
                # up, the whole upload latency when it does not).
                t_fetch = time.perf_counter()
                if tracer.enabled:
                    trace_context.take_pending()   # drop any stale baton
                try:
                    bidx, (tag, ops) = next(batch_it)
                except StopIteration:
                    break
                # The prefetcher leaves the batch's trace context as this
                # thread's pending handoff just before yielding; adopting
                # it here links the step span to the upload span that
                # produced its operands — one trace across both threads.
                step_ctx = (trace_context.take_pending()
                            if tracer.enabled else None)
                reg.observe("engine.sample_ms",
                            (time.perf_counter() - t_fetch) * 1e3)
                with tracer.span("rng"):
                    key, sub = jax.random.split(key)
                approx = self.schedule.use_rsc(gstep)
                use_rsc = cfg.rsc and approx
                compress = (self.compress_grads
                            and self.runner.supports_compression
                            and (approx if cfg.switching else True))
                mode = "rsc" if use_rsc else "exact"
                t0 = time.perf_counter()
                with tracer.span_in(step_ctx, "step", step=gstep,
                                    epoch=epoch, mode=mode):
                    if use_rsc:
                        with tracer.span("plan"):
                            plans = self.planner.plans_for(
                                tag, gstep, self.schedule)
                        with tracer.span("device_step", mode=mode):
                            with tracer.span("dispatch"):
                                self.params, self.opt_state, lv, norms = \
                                    self.runner.rsc_step(
                                        self.params, self.opt_state,
                                        ops, plans, sub, compress)
                            with tracer.span("wait"):
                                jax.block_until_ready(lv)
                        # The gradient row norms come to the host here.
                        with tracer.span("norms"):
                            self.planner.record(tag, norms)
                        if ledger.enabled:
                            # np.asarray on n_active forces a host sync on
                            # device-stacked DP plans — only when the
                            # ledger is actually recording.
                            ledger.note_step(mode="rsc", tiles_by_op={
                                n: int(np.sum(np.asarray(p.n_active)))
                                for n, p in plans.items()})
                        # Sampled every 16th step: the gauges are last-
                        # write-wins anyway, and reading them forces a
                        # device→host sync per op that would otherwise
                        # tax EVERY step (~2-5% on small steps).
                        if reg.enabled and gstep % 16 == 0:
                            self._record_rsc_gauges(reg, plans, norms)
                    else:
                        with tracer.span("device_step", mode=mode):
                            with tracer.span("dispatch"):
                                self.params, self.opt_state, lv = \
                                    self.runner.exact_step(
                                        self.params, self.opt_state,
                                        ops, sub, compress)
                            with tracer.span("wait"):
                                jax.block_until_ready(lv)
                        if ledger.enabled:
                            ledger.note_step(mode="exact")
                    dt = time.perf_counter() - t0
                reg.observe("engine.step_ms", dt * 1e3, mode=mode)
                reg.counter("engine.steps", mode=mode)

                with tracer.span("loss"):
                    self.history["loss"].append(float(lv))
                self.history["step_time"].append(dt)
                self.history["mode"].append("rsc" if use_rsc else "exact")
                self.history["compress"].append(bool(compress))
                if tag is not None:
                    self.history["sub_id"].append(
                        tag if isinstance(tag, int) else tuple(tag))
                if use_rsc:
                    k = self.planner.k_latest()
                    if k is not None:
                        self.history["k"].append(k)
                gstep += 1
                if (self.ckpt is not None and cfg.ckpt_every > 0
                        and gstep % cfg.ckpt_every == 0):
                    self.ckpt.save(
                        self._ckpt_base + gstep,
                        (self.params, self.opt_state),
                        aux=self._capture_state(epoch, bidx + 1, gstep, key,
                                                (best_val, best_test)))
            skip = 0
            with tracer.span("epoch_end"):
                if self.obs.enabled:
                    # Fold the planner's plan-cache statistics into the
                    # registry each epoch (summary()/per-shard stats used
                    # to be write-only), and enforce/record compile counts.
                    self.planner.publish(reg)
                if (cfg.rsc and cfg.probe_every > 0
                        and epoch % cfg.probe_every == 0
                        and (reg.enabled or ledger.enabled)):
                    self._run_probes(epoch, reg)
                if ledger.enabled:
                    ledger.end_epoch(epoch, reg)
                ledger.check(f"epoch {epoch}", hard_fail=cfg.strict_budget)
                self.sentinel.check(f"epoch {epoch}")

            if epoch % eval_every == 0 or epoch == epochs - 1:
                with tracer.span("eval", epoch=epoch), \
                        reg.timer("engine.eval_ms"):
                    val, test = self.evaluate(mfn)
                reg.gauge("engine.val_metric", val)
                reg.gauge("engine.test_metric", test)
                self.history["val"].append((epoch, val))
                self.history["test"].append((epoch, test))
                if val > best_val:
                    best_val, best_test = val, test
                if verbose:
                    # the resumed tail of a finished run has no new steps
                    loss_s = (f"{self.history['loss'][-1]:.4f} "
                              if self.history["loss"] else "---- ")
                    mode_s = (self.history["mode"][-1]
                              if self.history["mode"] else "none")
                    print(f"epoch {epoch:4d} loss {loss_s}"
                          f"val {val:.4f} test {test:.4f} mode={mode_s}")

        if self.ckpt is not None:
            # Final snapshot represented as "last epoch fully consumed":
            # resuming it replays the last epoch's (empty) batch tail, so
            # the source RNG stream stays aligned if training continues.
            self.ckpt.save(
                self._ckpt_base + gstep, (self.params, self.opt_state),
                aux=self._capture_state(
                    max(epochs - 1, 0), self.source.steps_per_epoch, gstep,
                    key, (best_val, best_test)))
            self.ckpt.wait()

        compiles = self.sentinel.check("end of training")
        return {
            "best_val": best_val,
            "best_test": best_test,
            "sentinel": compiles,
            "history": self.history,
            "cache_stats": self.planner.stats(),
            "plan_hit_rate": self.planner.hit_rate(),
            "flops_fraction": (self.planner.flops_fraction()
                               if cfg.rsc else 1.0),
            "compiles": self.runner.compile_counts(),
            "n_buckets": self.source.n_buckets,
            "ledger": (self.ledger.summary()
                       if self.ledger.enabled else None),
        }

    # ------------------------------------------------------------------
    def _run_probes(self, epoch: int, reg) -> None:
        """Epoch-end exact-vs-sampled error probes on every RSC op.

        Pure numpy (obs.probe) against the planner's live plans — no jit,
        so probes never show up in the compile sentinel or the steady-step
        timings. Results feed both the ledger time series and the
        per-layer registry gauges the exposition endpoint serves.
        """
        from repro.obs.probe import probe_plan_error
        cfg = self.cfg
        entries = self.planner.probe_entries()
        if not entries:
            return
        with self.obs.tracer.span("probe", epoch=epoch):
            for name, at, meta, plan, d in entries:
                if plan is None:
                    continue
                res = probe_plan_error(
                    np.asarray(at.blocks), meta, plan,
                    bm=at.bm, bk=at.bk, n_cols=at.n_col_blocks * at.bk,
                    op=name, n_rows=cfg.probe_rows,
                    d_probe=cfg.probe_dim, seed=cfg.seed + epoch)
                if res is None:
                    continue
                self.ledger.note_probe(name, rel_error=res.mean,
                                       ci_lo=res.ci_lo, ci_hi=res.ci_hi,
                                       n_rows=res.n_rows)
                if reg.enabled:
                    reg.gauge("rsc.probe.rel_error", res.mean, layer=name)
                    reg.gauge("rsc.probe.ci_lo", res.ci_lo, layer=name)
                    reg.gauge("rsc.probe.ci_hi", res.ci_hi, layer=name)

    @staticmethod
    def _record_rsc_gauges(reg, plans, norms) -> None:
        """Per-layer sampled fraction + gradient-row-norm gauges.

        ``plans`` maps op name → SamplePlan (possibly device-stacked under
        DP); ``norms`` maps op name → ∇H row norms the planner scores with
        (the sampling residual signal). Means only — these are trend
        gauges, not exact accounting.
        """
        for name, p in plans.items():
            n_active = float(np.mean(np.asarray(p.n_active)))
            reg.gauge("rsc.sampled_frac",
                      n_active / max(int(p.s_pad), 1), op=name)
        for name, v in norms.items():
            reg.gauge("rsc.grad_row_norm",
                      float(np.mean(np.asarray(v))), op=name)

    def evaluate(self, mfn=None) -> tuple[float, float]:
        mfn = mfn or metric_fn(self.cfg.metric)
        if self.stream_eval is not None:
            return self.stream_eval.evaluate(self.params, mfn)
        return self.source.evaluate(self.runner.eval_logits, mfn,
                                    self.params)


def full_batch_engine(cfg: TrainConfig, graph: GraphData) -> Engine:
    """The full-batch trainer as an Engine configuration."""
    module = MODELS[cfg.model]
    source = FullGraphSource(graph, cfg, module)
    planner = None
    if cfg.rsc:
        at, meta, fro = source.planner_operand()
        planner = FullGraphPlanner(cfg, module, at, meta, fro,
                                   source.num_classes)
    return Engine(cfg, source, planner=planner, graph=graph)
