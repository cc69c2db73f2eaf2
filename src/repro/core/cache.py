"""Plan cache (paper §3.3.1): sample every R steps, reuse in between.

The cache owns, per backward sparse op (= per layer):

* the host BlockMeta of the Ãᵀ operand,
* the most recent SamplePlan (device arrays),
* refresh logic: rerun allocator (Alg. 1) + rebuild plans every R steps
  from the latest ∇H row norms the training step reported.

Because slicing is metadata-only in block-COO (DESIGN.md §2), a refresh
costs O(S) int32 host work — the paper's motivation for caching (GPU CSR
re-slicing) is even stronger here: refreshes stay entirely off the device
critical path.

``s_pad`` bucketing: plan lengths quantize to multiples of
``ceil(s_total · bucket_frac)`` so a changing allocation re-jits the train
step at most ~1/bucket_frac times per layer over the whole run.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro import obs
from repro.core.allocator import (Allocation, LayerSpec, greedy_allocate,
                                  uniform_allocate)
from repro.core.plan import SamplePlan, host_plan
from repro.core.sampling import block_scores, topk_overlap_auc
from repro.sparse.bcoo import BlockCOO, BlockMeta


@dataclasses.dataclass
class OpEntry:
    name: str
    at: BlockCOO            # backward operand Ãᵀ (device)
    meta: BlockMeta         # host planner metadata of Ãᵀ
    d: int                  # hidden dim of this op's dense operand
    a_fro: float            # ‖Ã‖_F (Eq. 4a denominator, static half)
    plan: SamplePlan | None = None
    row_ptr: np.ndarray | None = None   # host copy of plan.row_ptr
    last_scores: np.ndarray | None = None

    def set_plan(self, plan: SamplePlan) -> None:
        """Upload a host-built plan, keeping its row pointers on the host."""
        self.plan = plan.to_device()
        self.row_ptr = plan.row_ptr


@dataclasses.dataclass
class CacheStats:
    refreshes: int = 0
    allocations: int = 0
    host_seconds: float = 0.0
    k_history: list = dataclasses.field(default_factory=list)
    auc_history: list = dataclasses.field(default_factory=list)

    def summary(self) -> dict:
        """JSON-ready snapshot (per-cache / per-shard reporting)."""
        return {
            "refreshes": self.refreshes,
            "allocations": self.allocations,
            "host_seconds": round(self.host_seconds, 4),
            "mean_auc": (float(np.mean(self.auc_history))
                         if self.auc_history else None),
        }


class PlanCache:
    """Owns sampling plans for every RSC op in a model."""

    def __init__(
        self,
        budget_frac: float,
        step_frac: float = 0.02,
        bucket_frac: float = 1 / 16,
        strategy: str = "greedy",   # or "uniform" (Fig. 6 baseline)
        plan_pad: int | None = None,
        label: str = "",            # diagnostics: which shard/subgraph
    ):
        self.budget_frac = budget_frac
        self.step_frac = step_frac
        self.bucket_frac = bucket_frac
        self.strategy = strategy
        self.label = label
        # Fixed absolute plan length. When set, every plan this cache builds
        # (full and sampled) pads to exactly ``plan_pad`` entries, so ALL
        # plans of a shape bucket share one jit signature and the minibatch
        # train step compiles once per bucket instead of once per allocation.
        self.plan_pad = plan_pad
        self.ops: dict[str, OpEntry] = {}
        self.stats = CacheStats()

    def _bucket(self, at) -> int:
        if self.plan_pad is not None:
            return self.plan_pad
        return max(1, int(np.ceil(at.s_total * self.bucket_frac)))

    def register(self, name: str, at: BlockCOO, meta: BlockMeta, d: int,
                 a_fro: float) -> None:
        """``at`` may be a device BlockCOO or a host mirror — only its
        static shape attributes (and never its tiles) are read here."""
        entry = OpEntry(name=name, at=at, meta=meta, d=d, a_fro=a_fro)
        # Start exact (full plan) until the first refresh has gradient info.
        bucket = self.plan_pad if self.plan_pad is not None else 1
        entry.set_plan(host_plan(meta, None, at.n_row_blocks, at.s_total,
                                 bucket=bucket))
        self.ops[name] = entry

    def plans(self) -> dict[str, SamplePlan]:
        return {k: v.plan for k, v in self.ops.items()}

    def refresh(self, grad_row_norms: dict[str, np.ndarray]) -> Allocation:
        """Re-run allocator + rebuild all plans from fresh ∇H row norms.

        grad_row_norms[name]: (n_rows_of_∇H,) — ‖∇H^{(l+1)}_{i,:}‖₂ per node.
        """
        tracer = obs.get_tracer()
        with tracer.span("plan.refresh"):
            return self._refresh(grad_row_norms, tracer)

    def _refresh(self, grad_row_norms, tracer) -> Allocation:
        t0 = time.perf_counter()
        names = list(self.ops.keys())
        layers = []
        for n in names:
            e = self.ops[n]
            g = grad_row_norms[n].astype(np.float64)
            scores = block_scores(e.meta.col_norm, g[: e.meta.col_norm.shape[0]],
                                  e.at.bk, e.at.n_col_blocks)
            gfro = float(np.sqrt(np.sum(g * g)))
            layers.append(LayerSpec(scores=scores,
                                    tiles=e.meta.col_block_tiles,
                                    d=e.d,
                                    norm=e.a_fro * max(gfro, 1e-30)))
        alloc_fn = greedy_allocate if self.strategy == "greedy" \
            else uniform_allocate
        if self.strategy == "greedy":
            alloc = alloc_fn(layers, self.budget_frac, self.step_frac)
        else:
            alloc = alloc_fn(layers, self.budget_frac)

        # Plan construction and its upload to the device.
        with tracer.span("plan.build"):
            for n, spec, keep in zip(names, layers, alloc.keep):
                e = self.ops[n]
                e.set_plan(host_plan(e.meta, keep, e.at.n_row_blocks,
                                     e.at.s_total, bucket=self._bucket(e.at)))
                if e.last_scores is not None:
                    self.stats.auc_history.append(
                        topk_overlap_auc(e.last_scores, keep))
                e.last_scores = spec.scores
        self.stats.refreshes += 1
        self.stats.allocations += 1
        self.stats.k_history.append(alloc.k.copy())
        self.stats.host_seconds += time.perf_counter() - t0
        # Approximation ledger: every allocator run is an accountable
        # budget event — the conservation invariant (cost ≤ budget) is
        # enforced HERE, where the greedy guarantee holds, not on raw
        # steps (bootstrap plans are exact by design).
        obs.get_ledger().note_allocation(
            scope=self.label or "full", strategy=self.strategy,
            cost=float(alloc.cost), budget=float(alloc.budget),
            k=alloc.k)
        return alloc

    def flops_fraction(self) -> float:
        """Achieved backward-SpMM FLOPs vs exact (diagnostics / Table 2)."""
        num = sum(e.plan.n_active * e.d for e in self.ops.values())
        den = sum(e.at.s_total * e.d for e in self.ops.values())
        return num / max(den, 1)
