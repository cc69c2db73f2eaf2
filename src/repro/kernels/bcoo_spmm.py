"""Pallas TPU kernel: ROW-SEGMENTED block-COO SpMM with a fused epilogue.

    out[r·bm:(r+1)·bm, j·bd:(j+1)·bd] = epilogue(
        Σ_{s ∈ [row_ptr[r], row_ptr[r+1])}
            blocks[sel[s]] @ h[col_ids[s]·bk:(col_ids[s]+1)·bk, j·bd:(j+1)·bd])

Grid: ``(n_row_blocks, d_tiles)`` — ONE grid step per output tile. The body
walks that row block's tile segment (bounds from the scalar-prefetched
CSR-of-tiles ``row_ptr``) with double-buffered manual DMA: while tile ``s``
is in the MXU, tile ``s+1``'s (bm, bk) value tile and (bk, bd) dense slab
are already in flight HBM→VMEM. The f32 accumulator lives in VMEM scratch
and the output tile is written EXACTLY ONCE — unlike the flat
``(d_tiles, s_pad)`` schedule this replaces, which re-read and re-flushed
the output ref on every row change and issued one grid step per tile id.

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

VMEM working set per grid step: 2·bm·bk (tile slots) + 2·bk·bd (slab
slots) + bm·bd f32 (acc) ≤ ~1.3 MB at the (128, 128, 512) defaults —
comfortably inside the ~16 MB VMEM budget; bm=bk=128 aligns the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@functools.partial(
    jax.jit,
    static_argnames=("n_row_blocks", "bm", "bk", "bd", "relu", "interpret"),
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
    interpret: bool = False,
) -> jax.Array:
    n_cols, d = h.shape
    assert n_cols % bk == 0, (n_cols, bk)
    bd = min(bd, d)
    assert d % bd == 0, (d, bd)
    d_tiles = d // bd
    if row_ptr is None:
        # Host-built plans carry row_ptr; recover it on device otherwise.
        from repro.core.plan import plan_row_ptr
        row_ptr = plan_row_ptr(row_ids, n_row_blocks)

    hb = h.reshape(n_cols // bk, bk, d)
    has_bias = bias is not None
    has_residual = residual is not None

    def body(sel_ref, col_ref, rptr_ref, *refs):
        # refs: blocks, hb [, bias][, residual], out, scratches...
        blocks_ref, hb_ref = refs[0], refs[1]
        k = 2
        bias_ref = refs[k] if has_bias else None
        k += has_bias
        res_ref = refs[k] if has_residual else None
        k += has_residual
        out_ref, acc_ref, tile_ref, slab_ref, sems = refs[k:k + 5]

        r = pl.program_id(0)
        j = pl.program_id(1)
        lo = rptr_ref[r]
        hi = rptr_ref[r + 1]

        def copies(s, slot):
            return (
                pltpu.make_async_copy(
                    blocks_ref.at[sel_ref[s]], tile_ref.at[slot],
                    sems.at[slot, 0]),
                pltpu.make_async_copy(
                    hb_ref.at[col_ref[s], :, pl.ds(j * bd, bd)],
                    slab_ref.at[slot], sems.at[slot, 1]),
            )

        @pl.when(lo < hi)
        def _first_fetch():
            for c in copies(lo, 0):
                c.start()

        def step(s, _):
            slot = jax.lax.rem(s - lo, 2)

            @pl.when(s + 1 < hi)
            def _prefetch_next():
                for c in copies(s + 1, 1 - slot):
                    c.start()

            for c in copies(s, slot):
                c.wait()
            acc_ref[...] += jnp.dot(
                tile_ref[slot], slab_ref[slot],
                preferred_element_type=jnp.float32)
            return _

        acc_ref[...] = jnp.zeros_like(acc_ref)
        jax.lax.fori_loop(lo, hi, step, 0)

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
            pltpu.VMEM((bm, bd), jnp.float32),          # accumulator
            pltpu.VMEM((2, bm, bk), blocks.dtype),      # tile double-buffer
            pltpu.VMEM((2, bk, bd), h.dtype),           # slab double-buffer
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
    )(sel, col_ids, row_ptr, *args)
