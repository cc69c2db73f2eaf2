"""Pallas TPU kernel: ROW-SEGMENTED block-COO SpMM with a fused epilogue.

    out[r·bm:(r+1)·bm, j·bd:(j+1)·bd] = epilogue(
        Σ_{s ∈ [row_ptr[r], row_ptr[r+1])}
            blocks[sel[s]] @ h[col_ids[s]·bk:(col_ids[s]+1)·bk, j·bd:(j+1)·bd])

Grid: ``(n_row_blocks, d_tiles)`` — ONE grid step per output tile. The body
walks that row block's tile segment (bounds from the scalar-prefetched
CSR-of-tiles ``row_ptr``) in GROUPS of ``k`` tiles. A group's ``k``
(bm, bk) value tiles and ``k`` (bk, bd) dense slabs are fetched HBM→VMEM
into one buffer slot, and the next group's fetch is in flight while the
current group is in the MXU, so up to ``2k`` tiles are in flight. A full
group contracts over ``k·bk`` and updates the f32 VMEM accumulator once;
a segment's tail of fewer than ``k`` tiles fetches only those tiles and
adds them one dot at a time. Per tile, one 128-deep dot and one
read-modify-write of the accumulator behind a single outstanding fetch
set the pace of a one-tile walk, not HBM; grouping amortizes both. The
output tile is written EXACTLY ONCE.

Fused epilogue (optional, all static flags at trace time):

    y = acc (+ bias[j·bd:(j+1)·bd]) (+ residual[r·bm:(r+1)·bm, j·bd:(j+1)·bd])
    out = max(y, 0) if relu else y

so a GCN-style layer (SpMM → +tap → ReLU) retires in one kernel launch with
no extra HBM round-trip for the activation.

Sentinel convention (unchanged): padding entries have ``sel == s_total``
(an all-zero tile), so any sentinel inside a row segment accumulates
nothing. Row blocks with an EMPTY segment (``row_ptr[r] == row_ptr[r+1]``)
come out as ``epilogue(0)`` — the row-segmented schedule no longer needs
the every-row-appears plan invariant, though plans still maintain it for
the flat reference path.

VMEM working set per grid step (:func:`working_set`): two group slots of
``k`` tiles and ``k`` slabs, the f32 accumulator, and the pipelined
(double-buffered) output and residual blocks. :func:`group_size` picks
``k`` from the shapes alone: the largest of :data:`GROUP_SIZES` whose
working set fits :data:`VMEM_SHARE`, else 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Candidate tiles per group, largest first.
GROUP_SIZES = (8, 4, 2)
#: Bytes of VMEM the grouped working set may take: well inside the 16 MiB
#: that Mosaic scopes for a kernel by default on a v5e. There, time per
#: tile stops falling once a group holds about 0.75-1 MiB (k = 8 / 4 / 2
#: at d = 128 / 256 / 640 with 128x128 f32 tiles); the k this share
#: allows lies within 1.2% of the fastest k at each of those widths.
VMEM_SHARE = 6 * 2 ** 20


def working_set(bm: int, bk: int, bd: int, itemsize: int, k: int) -> int:
    """VMEM bytes one grid step holds at ``k`` tiles per group."""
    slots = 2 * k * (bm * bk + bk * bd) * itemsize
    acc = bm * bd * 4
    out_and_residual = 2 * 2 * bm * bd * itemsize
    return slots + acc + out_and_residual


def group_size(bm: int, bk: int, bd: int, itemsize: int) -> int:
    """Tiles per group: the largest candidate whose working set fits."""
    for k in GROUP_SIZES:
        if working_set(bm, bk, bd, itemsize, k) <= VMEM_SHARE:
            return k
    return 1


def grouped_share(row_ptr, k: int) -> float:
    """Share of a plan's tiles that run in full groups of ``k``.

    ``row_ptr`` is the host CSR-of-tiles pointer array the kernel walks;
    a segment of ``n`` tiles runs ``(n // k) · k`` of them in full groups.
    """
    lens = np.diff(np.asarray(row_ptr, dtype=np.int64))
    total = int(lens.sum())
    return float((lens // k * k).sum() / total) if total else 0.0


@functools.partial(
    jax.jit,
    static_argnames=("n_row_blocks", "bm", "bk", "bd", "relu", "group",
                     "interpret"),
)
def bcoo_spmm(
    blocks: jax.Array,    # (S_total+1, bm, bk) — +1 zero sentinel
    sel: jax.Array,       # (s_pad,) int32
    row_ids: jax.Array,   # (s_pad,) int32, sorted ascending
    col_ids: jax.Array,   # (s_pad,) int32
    h: jax.Array,         # (n_cols, d)
    *,
    n_row_blocks: int,
    bm: int,
    bk: int,
    bd: int = 512,
    row_ptr: jax.Array | None = None,   # (n_row_blocks+1,) int32
    bias: jax.Array | None = None,      # (d,) — fused epilogue
    residual: jax.Array | None = None,  # (n_row_blocks*bm, d)
    relu: bool = False,
    group: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``group`` pins the tiles per group (tests); ``None`` takes
    :func:`group_size` of the call's shapes."""
    n_cols, d = h.shape
    assert n_cols % bk == 0, (n_cols, bk)
    bd = min(bd, d)
    assert d % bd == 0, (d, bd)
    d_tiles = d // bd
    if row_ptr is None:
        # Host-built plans carry row_ptr; recover it on device otherwise.
        from repro.core.plan import plan_row_ptr
        row_ptr = plan_row_ptr(row_ids, n_row_blocks)
    itemsize = max(jnp.dtype(blocks.dtype).itemsize,
                   jnp.dtype(h.dtype).itemsize)
    k = group if group is not None else group_size(bm, bk, bd, itemsize)

    hb = h.reshape(n_cols // bk, bk, d)
    has_bias = bias is not None
    has_residual = residual is not None

    def body(sel_ref, col_ref, rptr_ref, *refs):
        # refs: blocks, hb [, bias][, residual], out, scratches...
        blocks_ref, hb_ref = refs[0], refs[1]
        i_ref = 2
        bias_ref = refs[i_ref] if has_bias else None
        i_ref += has_bias
        res_ref = refs[i_ref] if has_residual else None
        i_ref += has_residual
        out_ref, acc_ref, tile_ref, slab_ref, sems = refs[i_ref:i_ref + 5]

        r = pl.program_id(0)
        j = pl.program_id(1)
        lo = rptr_ref[r]
        hi = rptr_ref[r + 1]
        n_groups = (hi - lo + k - 1) // k

        def copies(s, slot, i):
            # All of a slot's fetches signal its two semaphores; each wait
            # below takes one fetch's bytes off them.
            return (
                pltpu.make_async_copy(
                    blocks_ref.at[sel_ref[s]], tile_ref.at[slot, i],
                    sems.at[slot, 0]),
                pltpu.make_async_copy(
                    hb_ref.at[col_ref[s], :, pl.ds(j * bd, bd)],
                    slab_ref.at[slot, i], sems.at[slot, 1]),
            )

        def each_fetch(g, slot, act):
            # Only the tiles the segment holds: a tail group fetches fewer.
            s0 = lo + g * k

            def one(i, carry):
                for c in copies(s0 + i, slot, i):
                    act(c)
                return carry

            jax.lax.fori_loop(0, jnp.minimum(k, hi - s0), one, 0)

        def dot(slot, i):
            return jnp.dot(tile_ref[slot, i], slab_ref[slot, i],
                           preferred_element_type=jnp.float32)

        @pl.when(n_groups > 0)
        def _first_fetch():
            each_fetch(0, 0, lambda c: c.start())

        def step(g, carry):
            slot = jax.lax.rem(g, 2)

            @pl.when(g + 1 < n_groups)
            def _prefetch_next():
                each_fetch(g + 1, 1 - slot, lambda c: c.start())

            each_fetch(g, slot, lambda c: c.wait())
            n_here = hi - (lo + g * k)

            @pl.when(n_here >= k)
            def _full_group():
                part = dot(slot, 0)
                for i in range(1, k):
                    part += dot(slot, i)
                acc_ref[...] += part

            @pl.when(n_here < k)
            def _tail():
                def one(i, c):
                    acc_ref[...] += dot(slot, i)
                    return c

                jax.lax.fori_loop(0, n_here, one, 0)
            return carry

        acc_ref[...] = jnp.zeros_like(acc_ref)
        jax.lax.fori_loop(0, n_groups, step, 0)

        y = acc_ref[...]
        if has_bias:
            y = y + bias_ref[...].astype(jnp.float32)
        if has_residual:
            y = y + res_ref[...].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        out_ref[...] = y.astype(out_ref.dtype)

    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),   # blocks stay in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # hb stays in HBM
    ]
    args = [blocks, hb]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bd), lambda r, j, *_: (0, j)))
        args.append(bias.reshape(1, d))
    if has_residual:
        in_specs.append(pl.BlockSpec((bm, bd), lambda r, j, *_: (r, j)))
        args.append(residual)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_row_blocks, d_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bd), lambda r, j, *_: (r, j)),
        scratch_shapes=[
            pltpu.VMEM((bm, bd), jnp.float32),           # accumulator
            pltpu.VMEM((2, k, bm, bk), blocks.dtype),    # tile group slots
            pltpu.VMEM((2, k, bk, bd), h.dtype),         # slab group slots
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )

    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bm, d), h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="bcoo_spmm",
    )(sel, col_ids, row_ptr, *args)
