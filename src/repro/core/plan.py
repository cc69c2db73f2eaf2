"""SamplePlan: the metadata-only representation of a sampled sparse operand.

A plan selects a subset of a BlockCOO's tiles (by index into ``blocks``),
sorted by row block, padded to a bucketed static length with entries pointing
at the sentinel zero tile. Every row block appears at least once (sentinel
entries for otherwise-empty rows) so the Pallas kernel's
initialize-on-row-change accumulation covers the whole output.

Slicing the sparse matrix (paper Fig. 5 — the expensive CSR rebuild) is here
an O(S) int32 rewrite; tile data never moves.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import numpy as np

from repro.sparse.bcoo import BlockMeta, host_row_ptr


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["sel", "row_ids", "col_ids", "n_active", "row_ptr"],
    meta_fields=["s_pad"],
)
@dataclasses.dataclass(frozen=True)
class SamplePlan:
    """Index-list view of a (possibly sampled) BlockCOO operand.

    ``n_active`` is host bookkeeping but registered as pytree DATA, not
    static metadata: plans with equal ``s_pad`` and different allocations
    must hit the same jit cache entry (one compile per shape bucket).

    ``row_ptr`` is the CSR-of-tiles pointer array of the sorted id lists:
    tiles of output row block ``r`` occupy ``sel[row_ptr[r]:row_ptr[r+1]]``.
    It drives the row-segmented Pallas kernel (one grid step per output
    tile); the streaming jnp fallback scans the flat id lists and ignores
    it. Plans built before the field existed may carry ``None``; the
    kernel recovers it on device via :func:`plan_row_ptr`.
    """

    sel: jax.Array      # (s_pad,) int32 — tile index into blocks; sentinel = s_total
    row_ids: jax.Array  # (s_pad,) int32 — sorted ascending
    col_ids: jax.Array  # (s_pad,) int32
    n_active: int       # real (non-sentinel) tiles — bookkeeping/FLOPs
    s_pad: int          # static grid length
    row_ptr: jax.Array | None = None  # (n_row_blocks + 1,) int32 or None

    def flops(self, bm: int, bk: int, d: int) -> int:
        """FLOPs of SpMM under this plan (Eq. 4b cost, block units)."""
        return 2 * self.n_active * bm * bk * d

    def to_device(self) -> "SamplePlan":
        """The same plan with its id lists uploaded."""
        put = jax.numpy.asarray
        return dataclasses.replace(
            self, sel=put(self.sel), row_ids=put(self.row_ids),
            col_ids=put(self.col_ids),
            row_ptr=None if self.row_ptr is None else put(self.row_ptr))

    def bytes_moved(self, bm: int, bk: int, d: int) -> int:
        """f32 bytes an SpMM under this plan streams per call: each active
        tile plus the (bk, d) dense slab it gathers (ledger cost model —
        output writes are plan-independent and excluded)."""
        return self.n_active * (bm * bk + bk * d) * 4


def plan_row_ptr(row_ids: jax.Array, n_row_blocks: int) -> jax.Array:
    """Recover the tiles-per-row-block pointer array from sorted row ids.

    Works under jit (device searchsorted); ``build_plan`` precomputes the
    same thing on host so hot paths never pay for it.
    """
    return jax.numpy.searchsorted(
        row_ids, jax.numpy.arange(n_row_blocks + 1, dtype=row_ids.dtype),
        side="left").astype(jax.numpy.int32)


def build_plan(
    meta: BlockMeta,
    keep_col_blocks: np.ndarray | None,
    n_row_blocks: int,
    sentinel: int,
    bucket: int = 1,
) -> SamplePlan:
    """Build a plan keeping tiles whose column block is in ``keep_col_blocks``.

    keep_col_blocks: bool (n_col_blocks,) or None for the full/exact plan.
    sentinel: index of the zero tile (== s_total).
    bucket: pad s_pad up to a multiple of this (bounds recompilation count).
    """
    return host_plan(meta, keep_col_blocks, n_row_blocks, sentinel,
                     bucket).to_device()


def host_plan(
    meta: BlockMeta,
    keep_col_blocks: np.ndarray | None,
    n_row_blocks: int,
    sentinel: int,
    bucket: int = 1,
) -> SamplePlan:
    """:func:`build_plan` with its id lists left as host numpy arrays."""
    s_total = meta.row_ids.shape[0]
    if keep_col_blocks is None:
        keep_tile = np.ones(s_total, dtype=bool)
    else:
        keep_tile = keep_col_blocks[meta.col_ids]

    sel = np.nonzero(keep_tile)[0].astype(np.int32)
    rows = meta.row_ids[sel]
    cols = meta.col_ids[sel]

    # Guarantee every row block appears: add one sentinel entry per missing
    # row so the kernel zero-initializes that output tile.
    present = np.zeros(n_row_blocks, dtype=bool)
    present[rows] = True
    missing = np.nonzero(~present)[0].astype(np.int32)
    if missing.size:
        sel = np.concatenate([sel, np.full(missing.shape, sentinel, np.int32)])
        rows = np.concatenate([rows, missing])
        cols = np.concatenate([cols, np.zeros(missing.shape, np.int32)])

    order = np.argsort(rows, kind="stable")
    sel, rows, cols = sel[order], rows[order], cols[order]

    n_active = int(sel.shape[0])
    s_pad = _ceil_to(max(n_active, 1), max(bucket, 1))
    pad = s_pad - n_active
    if pad:
        last_row = rows[-1] if n_active else 0
        sel = np.concatenate([sel, np.full(pad, sentinel, np.int32)])
        rows = np.concatenate([rows, np.full(pad, last_row, np.int32)])
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])

    return SamplePlan(
        sel=sel, row_ids=rows, col_ids=cols, s_pad=s_pad,
        n_active=int(np.count_nonzero(keep_tile)),
        row_ptr=host_row_ptr(rows, n_row_blocks),
    )


def full_plan(meta: BlockMeta, n_row_blocks: int, sentinel: int,
              bucket: int = 1) -> SamplePlan:
    """The exact (un-sampled) plan."""
    return build_plan(meta, None, n_row_blocks, sentinel, bucket=bucket)
