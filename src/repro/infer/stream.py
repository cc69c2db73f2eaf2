"""Partitioned layer-wise streaming inference (exact full-graph forward).

Training-time evaluation of pooled/minibatch runs only ever scores nodes
the subgraph pool happens to sample; this engine computes the EXACT
full-graph forward pass in bounded device memory instead. Layer ℓ is
computed for *all* nodes one row-partition at a time — the standard
layer-wise trick of GraphSAINT/Cluster-GCN-style systems — with the
activations resident on HOST (numpy) between layers:

* the normalized propagation operand is tiled once
  (``sparse.bcoo.csr_to_bcoo_host``) and its row blocks are split into
  partitions by a device-memory budget
  (``pipeline.partition.contiguous_block_partition``) or by tile
  connectivity (``pipeline.partition.ldg_block_partition``);
* each partition uploads only its own tiles plus the dense rows of the
  column blocks those tiles actually reference (a column GATHER — the
  partition never sees the full activation matrix), runs the SpMM through
  the autotuned ``core.rsc_spmm.spmm_apply`` path (streaming jnp or the
  row-segmented Pallas kernel), and writes its output rows back to the
  host store;
* all partitions share one padded static shape per mode, so the jitted
  per-layer functions compile once per layer, not once per partition;
* row-wise math (dense mixes, batchnorm, activations — the model's
  ``infer_pre``/``infer_post``/``infer_out`` hooks, see
  ``models/gnn/common.py``) runs on host; batch statistics are computed
  over the full graph exactly like the training-time evaluator.

``sample_budget`` enables the RSC-SAMPLED variant: each partition keeps
only its top-scoring column blocks (static Eq. 3 column norms) covering
that fraction of its tiles, shrinking both the gather and the SpMM — the
paper's accuracy/latency trade-off extended to inference.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.plan import SamplePlan
from repro.obs import context as trace_context
from repro.obs.sentinel import jit_compiles
from repro.core.rsc_spmm import spmm_apply
from repro.graphs.synthetic import GraphData
from repro.models.gnn import MODELS
from repro.models.gnn.common import degree_sorted_arrays, pad_node_arrays
from repro.sparse.bcoo import HostBlockCOO, csr_to_bcoo_host, host_row_ptr
from repro.sparse.csr import CSR
from repro.sparse.topology import mean_normalize, sym_normalize


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Knobs of the streaming engine.

    ``memory_budget_mb`` bounds the estimated device bytes of one
    partition (tiles + gathered columns + output rows); ``n_partitions``
    overrides it with an explicit even split. ``sample_budget`` < 1
    switches to RSC-sampled column gathers. ``store_layers`` keeps every
    layer's activations (and frozen batchnorm statistics) on host — the
    serving frontend needs them for incremental recompute.

    ``resident_mb`` enables the device-resident partition LRU: a
    partition's STATIC operands (tiles + id lists + row_ptr — everything
    the layer loop would otherwise re-upload every layer of every
    forward) stay on device up to that byte budget, evicted
    least-recently-used. ``overlap`` double-buffers the per-partition
    upload (activation gather + ``device_put``) against the previous
    partition's device SpMM, reusing the ``pipeline.prefetch`` pattern.
    Both default off — the exact PR-4 execution path.
    """

    block: int = 64                    # bm == bk of the tiled operand
    n_partitions: int | None = None
    memory_budget_mb: float | None = 256.0
    partition_method: str = "contiguous"   # or "ldg" (tile connectivity)
    backend: str = "jnp"
    sample_budget: float | None = None     # None / >=1 → exact
    degree_sort: bool = True
    autotune: bool = False                 # sweep SpMM tiles up front
    store_layers: bool = False
    resident_mb: float | None = None       # device partition LRU budget
    overlap: bool = False                  # double-buffer uploads


class _DeviceLRU:
    """Budget-aware LRU of device-resident partition operands.

    Values are the ``device_put`` STATIC operand tuples of one partition
    (tiles, sel, row_ids, col_ids, row_ptr) keyed by ``(mode, part)``; the
    activation slab is never cached (it changes every layer). Hot
    partitions therefore stop paying the tile re-upload on every layer of
    every forward — the dominant host→device traffic of streaming
    inference when the graph fits. Eviction keeps ``resident_bytes``
    under ``budget_bytes`` (the newest entry always survives, even
    oversized: evicting it would just re-upload next layer). Counters and
    gauges (``stream.lru_*``) publish through ``repro.obs``; plain-int
    stats stay readable on the object when obs is disabled. Thread-safe:
    the overlap prefetch thread and the main loop share it (uploads run
    outside the lock; a racing duplicate upload is harmless — last insert
    wins).
    """

    def __init__(self, budget_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.resident_bytes = 0
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._bytes: dict[tuple, int] = {}
        self._lock = threading.Lock()

    def get(self, key: tuple, build):
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                obs.get_registry().counter("stream.lru_hits")
                self._publish()
                return ent
        val = build()   # slow upload outside the lock
        nbytes = int(sum(x.nbytes for x in val))
        reg = obs.get_registry()
        with self._lock:
            self.misses += 1
            reg.counter("stream.lru_misses")
            if key not in self._entries:
                self._entries[key] = val
                self._bytes[key] = nbytes
                self.resident_bytes += nbytes
            self._entries.move_to_end(key)
            while (self.resident_bytes > self.budget_bytes
                   and len(self._entries) > 1):
                old, _ = self._entries.popitem(last=False)
                self.resident_bytes -= self._bytes.pop(old)
                self.evictions += 1
                reg.counter("stream.lru_evictions")
            self._publish()
        return val

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes.clear()
            self.resident_bytes = 0
            self._publish()

    def invalidate(self, keys) -> None:
        """Drop specific entries (dirty-bounded operand updates evict only
        the partitions whose tiles changed)."""
        with self._lock:
            for key in keys:
                if key in self._entries:
                    del self._entries[key]
                    self.resident_bytes -= self._bytes.pop(key)
            self._publish()

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def _publish(self) -> None:
        reg = obs.get_registry()
        reg.gauge("stream.lru_resident_bytes", self.resident_bytes)
        reg.gauge("stream.lru_hit_rate", self.hit_rate())


@dataclasses.dataclass
class _Partition:
    """Device-ready operands of one row-partition (host arrays)."""

    rbs: np.ndarray          # global row-block ids, sorted
    blocks: np.ndarray       # (s_pad + 1, bm, bk) tiles + zero sentinel
    sel: np.ndarray          # (s_pad,) int32, sentinel == s_pad
    row_ids: np.ndarray      # (s_pad,) int32 LOCAL row blocks
    col_ids: np.ndarray      # (s_pad,) int32 LOCAL gather blocks
    row_ptr: np.ndarray      # (nb_pad + 1,) int32
    gather_rows: np.ndarray  # (g_pad * bk,) int64 host rows to gather
    out_rows: np.ndarray     # (len(rbs) * bm,) int64 host rows written
    n_rows: int              # real output rows (== len(rbs) * bm)
    n_active: int            # real tiles
    n_gather: int            # real gathered column blocks


class StreamingInference:
    """Exact (or RSC-sampled) layer-wise full-graph forward in partitions.

    Node order is the operand order (degree-sorted when configured);
    ``nodes[i]`` maps local row ``i`` back to the original graph id and
    ``pos`` is the inverse. ``forward`` may be called repeatedly with new
    params (periodic eval during training): the jitted layer functions are
    cached by shape, never by parameter values.
    """

    def __init__(self, graph: GraphData, model, params,
                 cfg: StreamConfig = StreamConfig()):
        from repro.kernels.ops import require_tpu
        require_tpu(cfg.backend)
        self.module = MODELS[model] if isinstance(model, str) else model
        self.cfg = cfg
        self.params = params

        adj, feats, labels = graph.adj, graph.features, graph.labels
        tr, va, te = graph.train_mask, graph.val_mask, graph.test_mask
        perm = np.arange(graph.n, dtype=np.int64)
        if cfg.degree_sort:
            adj, feats, labels, tr, va, te, perm = degree_sorted_arrays(
                adj, feats, labels, tr, va, te)
        self.nodes = perm                          # local row -> original id
        self.pos = np.empty_like(perm)             # original id -> local row
        self.pos[perm] = np.arange(perm.shape[0])
        self.n_valid = graph.n
        self.num_classes = graph.num_classes
        self.multilabel = graph.multilabel
        self._mean_agg = self.module.uses_mean_agg()
        self.lru = (_DeviceLRU(int(cfg.resident_mb * 2 ** 20))
                    if cfg.resident_mb else None)

        self._set_operand(adj)
        n_pad = self.host.n_rows
        (self.features, self.labels, self.train_mask, self.val_mask,
         self.test_mask) = pad_node_arrays(n_pad, feats, labels, tr, va, te,
                                           graph.multilabel)
        self.valid = np.arange(n_pad) < self.n_valid

        self._dims = list(self.module.infer_spmm_dims(
            params, feats.shape[1]))
        self.n_layers = self.module.infer_n_layers(params)
        self._layer_fns: dict = {}
        self._parts: dict[str, list[_Partition]] = {}
        self._pads: dict[str, tuple[int, int, int]] = {}
        self._build_partitions()
        if cfg.autotune:
            self._warmup_autotune()

        # Populated by a store_layers forward (serving / incremental).
        self.layer_store: list[np.ndarray] | None = None
        self.ctx_store = None
        self.bn_stats: dict[int, tuple | None] = {}
        self.logits: np.ndarray | None = None

    # ------------------------------------------------------------ operand
    def _set_operand(self, adj: CSR) -> None:
        """(Re)build the normalized tiled operand from a raw adjacency."""
        normalize = mean_normalize if self._mean_agg else sym_normalize
        a_csr = normalize(adj)
        self.adj = adj
        self.host, self.meta = csr_to_bcoo_host(
            a_csr, self.cfg.block, self.cfg.block)

    def rebuild_operand(self, adj: CSR) -> None:
        """Swap in an updated adjacency (serving edge updates). Re-tiles
        the operand and re-plans the partitions; jit caches survive as
        long as the padded shapes do."""
        old_pads = dict(self._pads)
        self._set_operand(adj)
        if self.lru is not None:
            self.lru.clear()   # cached tiles belong to the old operand
        self._build_partitions()
        for mode, pads in self._pads.items():
            if old_pads.get(mode) != pads:
                self._layer_fns = {k: v for k, v in self._layer_fns.items()
                                   if k[1] != mode}

    def update_operand(self, adj: CSR, dirty_rows: np.ndarray) -> dict:
        """Dirty-bounded operand refresh: re-tile ONLY the row blocks whose
        normalized rows changed (``sparse.bcoo.retile_rows``) and rebuild
        ONLY the partitions containing them, keeping every other
        partition's device-ready operands — and the compiled layer
        functions — untouched.

        ``dirty_rows`` are the LOCAL rows whose Ã row differs between the
        old and new adjacency (edge endpoints plus their old∪new neighbors
        under degree renormalization). If a touched partition no longer
        fits the padded shapes every partition shares (tile growth past
        ``s_pad``), the method falls back to a full partition re-plan —
        counted in the returned stats, never silent. Normalization itself
        stays O(nnz) vectorized numpy; the scatter into tiles, the
        dominant host cost, is bounded by the dirty rows' nnz.
        """
        from repro.sparse.bcoo import retile_rows

        normalize = mean_normalize if self._mean_agg else sym_normalize
        a_csr = normalize(adj)
        dirty_rows = np.asarray(dirty_rows, dtype=np.int64)
        rbs = np.unique(dirty_rows // self.host.bm)
        self.host, self.meta = retile_rows(self.host, self.meta, a_csr,
                                           dirty_rows)
        self.adj = adj
        touched = [i for i, ids in enumerate(self._partition_id_list)
                   if np.intersect1d(ids, rbs, assume_unique=True).size]
        stats = {"dirty_row_blocks": int(rbs.size),
                 "partitions_touched": len(touched),
                 "partitions_rebuilt": 0, "fallback": False}
        for mode in list(self._parts):
            sampled = mode == "sampled"
            nb_pad, s_pad, g_pad = self._pads[mode]
            for i in touched:
                ids = self._partition_id_list[i]
                raw = self._raw_partition(ids, sampled)
                if (ids.shape[0] > nb_pad
                        or raw[0].shape[0] + nb_pad > s_pad
                        or raw[3].shape[0] > g_pad):
                    # grown past the shared padded shapes: full re-plan
                    # (keeps compiled fns for modes whose pads survive)
                    old_pads = dict(self._pads)
                    self._build_partitions()
                    for m2, pads in self._pads.items():
                        if old_pads.get(m2) != pads:
                            self._layer_fns = {
                                k: v for k, v in self._layer_fns.items()
                                if k[1] != m2}
                    if self.lru is not None:
                        self.lru.clear()
                    stats["fallback"] = True
                    stats["partitions_rebuilt"] = sum(
                        len(p) for p in self._parts.values())
                    obs.get_registry().counter("stream.update_fallbacks")
                    return stats
                self._parts[mode][i] = self._build_one(ids, raw, nb_pad,
                                                       s_pad, g_pad)
                stats["partitions_rebuilt"] += 1
        if self.lru is not None:
            self.lru.invalidate([(m, i) for m in self._parts
                                 for i in touched])
        return stats

    # --------------------------------------------------------- partitions
    def _partition_ids(self) -> list[np.ndarray]:
        from repro.pipeline.partition import (contiguous_block_partition,
                                              ldg_block_partition)
        cfg = self.cfg
        hb = self.host
        if cfg.partition_method == "ldg":
            if not cfg.n_partitions:
                raise ValueError(
                    'partition_method="ldg" groups a FIXED number of '
                    "partitions by tile connectivity; set n_partitions "
                    "(the byte budget only drives the contiguous splitter)")
            return ldg_block_partition(
                self.host.row_ids, self.host.col_ids,
                hb.n_row_blocks, cfg.n_partitions)
        if cfg.partition_method != "contiguous":
            raise ValueError(
                f"unknown partition_method {cfg.partition_method!r}")
        budget = (int(cfg.memory_budget_mb * 2 ** 20)
                  if cfg.memory_budget_mb else None)
        return contiguous_block_partition(
            hb.row_ptr, bm=hb.bm, bk=hb.bk,
            d=max(self._dims) if self._dims else hb.bk,
            n_parts=cfg.n_partitions, budget_bytes=budget)

    def _tiles_of(self, rbs: np.ndarray) -> np.ndarray:
        """Indices (into the tile lists) of all tiles of the row blocks."""
        ptr = self.host.row_ptr
        starts, ends = ptr[rbs].astype(np.int64), ptr[rbs + 1].astype(np.int64)
        counts = ends - starts
        total = int(counts.sum())
        if total == 0:
            return np.empty(0, dtype=np.int64)
        offs = np.repeat(np.cumsum(counts) - counts, counts)
        return np.repeat(starts, counts) + (np.arange(total) - offs)

    def _sampled_keep(self, idx: np.ndarray) -> np.ndarray:
        """Tile mask keeping the top-norm column blocks covering
        ``sample_budget`` of this partition's tiles (static Eq. 3 half)."""
        budget = float(self.cfg.sample_budget)
        cb = self.host.col_ids[idx]
        uniq, cnt = np.unique(cb, return_counts=True)
        order = np.argsort(-self.meta.col_block_norm[uniq], kind="stable")
        cum = np.cumsum(cnt[order])
        k = int(np.searchsorted(cum, budget * cum[-1])) + 1
        return np.isin(cb, uniq[order[:k]])

    def _raw_partition(self, rbs: np.ndarray, sampled: bool):
        """Unpadded (sel, local rows, global cols, uniq col blocks)."""
        idx = self._tiles_of(rbs)
        if sampled and idx.size:
            idx = idx[self._sampled_keep(idx)]
        ptr = self.host.row_ptr
        counts = (ptr[rbs + 1] - ptr[rbs]).astype(np.int64)
        if sampled:
            rows_g = self.host.row_ids[idx].astype(np.int64)
            local = np.searchsorted(rbs, rows_g)
        else:
            local = np.repeat(np.arange(rbs.shape[0]), counts)
        cols_g = self.host.col_ids[idx].astype(np.int64)
        uniq = np.unique(cols_g)
        return idx, local, cols_g, uniq

    def _build_one(self, rbs: np.ndarray, raw, nb_pad: int, s_pad: int,
                   g_pad: int) -> _Partition:
        bm, bk = self.host.bm, self.host.bk
        idx, local, cols_g, uniq = raw
        k = idx.shape[0]
        sentinel = s_pad

        sel = np.arange(k, dtype=np.int32)
        rows = local.astype(np.int32)
        cols = np.searchsorted(uniq, cols_g).astype(np.int32)
        # One sentinel entry per local row block with no tiles (covers
        # sampled-away rows and nb_pad padding rows): the kernel's
        # initialize-on-row-change accumulation needs every row present.
        present = np.zeros(nb_pad, dtype=bool)
        present[rows] = True
        missing = np.nonzero(~present)[0].astype(np.int32)
        if missing.size:
            sel = np.concatenate([sel,
                                  np.full(missing.shape, sentinel, np.int32)])
            rows = np.concatenate([rows, missing])
            cols = np.concatenate([cols, np.zeros(missing.shape, np.int32)])
        order = np.argsort(rows, kind="stable")
        sel, rows, cols = sel[order], rows[order], cols[order]
        pad = s_pad - sel.shape[0]
        if pad < 0:
            raise ValueError(f"s_pad {s_pad} < {sel.shape[0]} entries")
        if pad:
            last = rows[-1] if rows.size else 0
            sel = np.concatenate([sel, np.full(pad, sentinel, np.int32)])
            rows = np.concatenate([rows, np.full(pad, last, np.int32)])
            cols = np.concatenate([cols, np.zeros(pad, np.int32)])

        blocks = np.zeros((s_pad + 1, bm, bk), dtype=np.float32)
        blocks[:k] = self.host.blocks[idx]

        gather = np.zeros(g_pad * bk, dtype=np.int64)
        g = uniq.shape[0]
        if g:
            gather[: g * bk] = (uniq[:, None] * bk
                                + np.arange(bk)[None, :]).reshape(-1)
        out_rows = (rbs[:, None] * bm + np.arange(bm)[None, :]).reshape(-1)
        return _Partition(
            rbs=rbs, blocks=blocks, sel=sel, row_ids=rows, col_ids=cols,
            row_ptr=host_row_ptr(rows, nb_pad), gather_rows=gather,
            out_rows=out_rows, n_rows=rbs.shape[0] * bm,
            n_active=k, n_gather=g)

    def _build_mode(self, ids: list[np.ndarray], sampled: bool,
                    mode: str) -> None:
        raws = [self._raw_partition(rbs, sampled) for rbs in ids]
        nb_pad = max(rbs.shape[0] for rbs in ids)
        s_pad = max(1, max(r[0].shape[0] + nb_pad for r in raws))
        g_pad = max(1, max(r[3].shape[0] for r in raws))
        self._pads[mode] = (nb_pad, s_pad, g_pad)
        self._parts[mode] = [self._build_one(rbs, raw, nb_pad, s_pad, g_pad)
                             for rbs, raw in zip(ids, raws)]

    def _build_partitions(self) -> None:
        ids = self._partition_ids()
        self._partition_id_list = ids
        self._build_mode(ids, sampled=False, mode="exact")
        sb = self.cfg.sample_budget
        if sb is not None and sb < 1.0:
            self._build_mode(ids, sampled=True, mode="sampled")

    @property
    def n_partitions(self) -> int:
        return len(self._parts["exact"])

    # -------------------------------------------------------------- spmm

    def _warmup_autotune(self) -> None:
        from repro.kernels import autotune
        backend = self.cfg.backend
        bm = bk = self.cfg.block
        for mode, (nb_pad, s_pad, g_pad) in self._pads.items():
            for d in sorted(set(self._dims)):
                autotune.get_or_tune(
                    backend, bm=bm, bk=bk, d=d, s_pad=s_pad,
                    n_row_blocks=nb_pad, n_col_blocks=g_pad)

    def _layer_fn(self, l: int, mode: str, pre):
        """Jitted (pre →) SpMM for one layer at one mode's padded shape.

        ``pre`` is ``(pure_fn, pre_params)`` or None; ``pre_params`` stays
        an ARGUMENT of the jitted function so repeated evals with fresh
        params reuse the compiled code (nothing is baked in as a
        constant)."""
        key = (l, mode)
        cached = self._layer_fns.get(key)
        if cached is not None:
            return cached
        nb_pad, s_pad, g_pad = self._pads[mode]
        bm, bk = self.host.bm, self.host.bk
        backend = self.cfg.backend
        pre_fn = pre[0] if pre is not None else None

        def fn(blocks, sel, rows, cols, rptr, n_active, h, pre_params):
            if pre_fn is not None:
                h = pre_fn(pre_params, h)
            plan = SamplePlan(sel=sel, row_ids=rows, col_ids=cols,
                              n_active=n_active, s_pad=s_pad, row_ptr=rptr)
            return spmm_apply(blocks, plan, h, nb_pad, bm, bk, backend)

        jitted = jax.jit(fn)
        self._layer_fns[key] = jitted
        return jitted

    def compile_counts(self) -> dict[str, int]:
        """Compiles per cached layer function — the streaming invariant is
        ONE per ``(layer, mode)`` key, watched by the engine's sentinel."""
        return {f"layer{l}/{mode}": (jit_compiles(fn) or 0)
                for (l, mode), fn in self._layer_fns.items()}

    def _statics(self, mode: str, i: int | None, p: _Partition):
        """The partition's static device operands, through the resident
        LRU when enabled. Ad-hoc partitions (``recompute_rows`` chunks,
        ``i is None``) never enter the cache — their operands are
        one-shot."""
        def build():
            return jax.block_until_ready(jax.device_put(
                (p.blocks, p.sel, p.row_ids, p.col_ids, p.row_ptr)))
        if self.lru is not None and i is not None:
            return self.lru.get((mode, i), build)
        return build()

    def _spmm_layer(self, l: int, h: np.ndarray, pre, mode: str,
                    parts: list[_Partition] | None = None,
                    d_out: int | None = None) -> np.ndarray:
        """SpMM(operand, pre(h)) for all rows covered by ``parts``."""
        adhoc = parts is not None
        parts = parts if adhoc else self._parts[mode]
        fn = self._layer_fn(l, mode, pre)
        bundle = obs.get_obs()
        pre_params = pre[1] if pre is not None else {}
        out = None

        if self.cfg.overlap and not adhoc:
            iterator = self._overlapped(fn, l, mode, parts, h, pre_params)
        else:
            def _serial():
                for i, p in enumerate(parts):
                    key_i = None if adhoc else i
                    if bundle.enabled or self.lru is not None:
                        yield p, self._timed_partition(
                            bundle, fn, l, mode, i, p, h, pre_params, key_i)
                    else:
                        slab = np.ascontiguousarray(h[p.gather_rows])
                        yield p, fn(p.blocks, p.sel, p.row_ids, p.col_ids,
                                    p.row_ptr,
                                    jnp.asarray(p.n_active, jnp.int32),
                                    slab, pre_params)
            iterator = _serial()
        for p, res in iterator:
            res = np.asarray(res)
            if out is None:
                out = np.zeros((self.host.n_rows, res.shape[1]), np.float32)
            out[p.out_rows] = res[: p.n_rows]
        return out

    def _overlapped(self, fn, l: int, mode: str, parts, h: np.ndarray,
                    pre_params):
        """Double-buffered partition loop: a prefetch thread gathers the
        activation slab and ``device_put``s partition i+1's operands
        (statics through the LRU when enabled) while the main thread runs
        partition i's SpMM — the ``pipeline.prefetch`` pattern pointed at
        inference partitions instead of pool subgraphs."""
        from repro.pipeline.prefetch import Prefetcher

        def fetch(i):
            p = parts[i]
            statics = self._statics(mode, i, p)
            slab = jax.device_put(np.ascontiguousarray(h[p.gather_rows]))
            return statics + (jax.block_until_ready(slab),)

        pf = Prefetcher(None, range(len(parts)), fetch=fetch, enabled=True)
        tracer = obs.get_tracer()
        for i, ups in pf:
            p = parts[i]
            # Adopt the prefetcher's handoff baton: the partition's compute
            # span joins the same trace as its upload span (and, when this
            # rebuild runs under the serving applier, the originating
            # update_edges call).
            ictx = trace_context.take_pending() if tracer.enabled else None
            with tracer.span_in(ictx, "stream_partition", layer=l,
                                mode=mode, part=i):
                res = fn(*ups[:5], jnp.asarray(p.n_active, jnp.int32),
                         ups[5], pre_params)
            yield p, res

    def _timed_partition(self, bundle, fn, l: int, mode: str, i: int,
                         p: _Partition, h: np.ndarray, pre_params,
                         key_i: int | None = None):
        """Instrumented partition step: splits host gather + host→device
        upload from device compute (explicit ``device_put`` + blocking —
        the un-instrumented path lets jit overlap them, so this split only
        runs when observability or the resident LRU is on; with the LRU,
        the 'upload' phase is a cache read on hot partitions)."""
        reg, tracer = bundle.registry, bundle.tracer
        with tracer.span("stream_partition", layer=l, mode=mode, part=i):
            t0 = time.perf_counter()
            slab = np.ascontiguousarray(h[p.gather_rows])
            statics = self._statics(mode, key_i, p)
            slab_d = jax.block_until_ready(jax.device_put(slab))
            t1 = time.perf_counter()
            res = jax.block_until_ready(
                fn(*statics, jnp.asarray(p.n_active, jnp.int32), slab_d,
                   pre_params))
            t2 = time.perf_counter()
        reg.observe("stream.upload_ms", (t1 - t0) * 1e3,
                    layer=str(l), mode=mode)
        reg.observe("stream.compute_ms", (t2 - t1) * 1e3,
                    layer=str(l), mode=mode)
        return res

    # ------------------------------------------------------------ forward
    def forward(self, params=None, *, sampled: bool | None = None,
                store: bool | None = None) -> np.ndarray:
        """Full-graph logits (padded, operand row order).

        ``sampled`` defaults to whether the config carries a
        ``sample_budget``; ``store`` defaults to ``cfg.store_layers`` and
        retains per-layer activations + frozen batchnorm statistics for
        the serving/incremental path.
        """
        params = params if params is not None else self.params
        sampled = ("sampled" in self._parts) if sampled is None else sampled
        if sampled and "sampled" not in self._parts:
            raise ValueError("sampled forward requested but the config "
                             "has no sample_budget < 1")
        mode = "sampled" if sampled else "exact"
        store = self.cfg.store_layers if store is None else store
        module = self.module

        tracer = obs.get_tracer()
        h, ctx = module.infer_init(params, self.features)
        layers = [h.copy()] if store else None
        bn_stats: dict[int, tuple | None] = {}
        for l in range(self.n_layers):
            with tracer.span("stream_layer", layer=l, mode=mode):
                pre = module.infer_pre(params, l)
                p_out = self._spmm_layer(l, h, pre, mode)
                h, st = module.infer_post(params, l, p_out, h, ctx,
                                          self.valid, None)
            bn_stats[l] = st
            if store:
                layers.append(h.copy())
        logits = np.asarray(module.infer_out(params, h, ctx),
                            dtype=np.float32)
        if store:
            self.layer_store = layers
            self.ctx_store = (np.asarray(ctx, np.float32)
                              if ctx is not None else None)
            self.bn_stats = bn_stats
            self.logits = logits
            self.params = params
        return logits

    # ----------------------------------------------- incremental recompute
    def _chunk_blocks(self, rbs: np.ndarray, mode: str) -> list[np.ndarray]:
        """Split an arbitrary row-block set into groups that fit the
        mode's padded shapes (reusing the compiled layer functions)."""
        nb_pad, s_pad, g_pad = self._pads[mode]
        ptr = self.host.row_ptr
        chunks, cur, tiles, cols = [], [], 0, set()
        for r in rbs:
            t = int(ptr[r + 1] - ptr[r])
            c = set(self.host.col_ids[ptr[r]: ptr[r + 1]].tolist())
            if cur and (len(cur) + 1 > nb_pad
                        or tiles + t + nb_pad > s_pad
                        or len(cols | c) > g_pad):
                chunks.append(np.asarray(cur, np.int64))
                cur, tiles, cols = [], 0, set()
            cur.append(int(r))
            tiles += t
            cols |= c
        if cur:
            chunks.append(np.asarray(cur, np.int64))
        return chunks

    def recompute_rows(self, dirty_per_layer: list[np.ndarray],
                       params=None, mode: str = "exact") -> None:
        """Recompute stored activations/logits for the dirty node sets.

        ``dirty_per_layer[l]`` are the LOCAL rows whose H^{l+1} changed
        (monotone growing with l, ≤L-hop BFS — see ``infer.serve``).
        Batchnorm statistics are applied FROZEN from the last full pass,
        the standard serving-time semantics. Only dirty node rows are
        written back, so clean rows stay bit-identical. ``mode="sampled"``
        recomputes with the RSC-sampled column gathers (sampled serving
        replicas: the stores were built by a sampled forward).
        """
        if self.layer_store is None:
            raise RuntimeError("no stored activations: run "
                               "forward(store=True) first")
        if mode not in self._parts:
            raise ValueError(f"no {mode!r} partitions built")
        params = params if params is not None else self.params
        module = self.module
        bm = self.host.bm
        for l in range(self.n_layers):
            dirty = np.asarray(dirty_per_layer[l], dtype=np.int64)
            if dirty.size == 0:
                continue
            rbs = np.unique(dirty // bm)
            h = self.layer_store[l]
            pre = module.infer_pre(params, l)
            parts = []
            for chunk in self._chunk_blocks(rbs, mode):
                raw = self._raw_partition(chunk, sampled=(mode == "sampled"))
                nb_pad, s_pad, g_pad = self._pads[mode]
                parts.append(self._build_one(chunk, raw, nb_pad, s_pad,
                                             g_pad))
            p_out = self._spmm_layer(l, h, pre, mode, parts=parts)
            ctx_rows = (self.ctx_store[dirty]
                        if self.ctx_store is not None else None)
            h_new, _ = module.infer_post(
                params, l, p_out[dirty], h[dirty], ctx_rows,
                self.valid[dirty], self.bn_stats.get(l))
            self.layer_store[l + 1][dirty] = h_new
        final = np.asarray(dirty_per_layer[self.n_layers - 1],
                           dtype=np.int64)
        if final.size:
            ctx_rows = (self.ctx_store[final]
                        if self.ctx_store is not None else None)
            self.logits[final] = np.asarray(module.infer_out(
                params, self.layer_store[self.n_layers][final], ctx_rows),
                dtype=np.float32)


class StreamEvaluator:
    """Engine-facing adapter: streaming eval with the training metric.

    Built lazily — the tiled operand and partitions are constructed on the
    first evaluation call (params are needed for the layer dims), then
    reused for every periodic eval of the run.
    """

    def __init__(self, graph: GraphData, model: str,
                 cfg: StreamConfig = StreamConfig()):
        self.graph = graph
        self.model = model
        self.cfg = cfg
        self.si: StreamingInference | None = None
        self.seconds = 0.0
        self.evals = 0

    def evaluate(self, params, mfn) -> tuple[float, float]:
        t0 = time.perf_counter()
        params = jax.device_get(params)
        if self.si is None:
            self.si = StreamingInference(self.graph, self.model, params,
                                         self.cfg)
        logits = self.si.forward(params, store=False)
        si = self.si
        val = mfn(logits, si.labels, si.val_mask & si.valid)
        test = mfn(logits, si.labels, si.test_mask & si.valid)
        dt = time.perf_counter() - t0
        self.seconds += dt
        self.evals += 1
        obs.get_registry().observe("stream.eval_ms", dt * 1e3)
        return val, test
