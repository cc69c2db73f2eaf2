"""Pallas TPU kernel: block-gathered matmul for dense RSC (rsc_matmul bwd).

    out = Σ_t  X[idx[t]·bk : (idx[t]+1)·bk, :]ᵀ @ G[idx[t]·bk : (idx[t]+1)·bk, :]

i.e. approx(XᵀG) over the top-k selected 128-row token blocks (Adelman-style
column-row sampling at MXU-aligned block granularity). The selected block
list ``idx`` is scalar-prefetched and drives the X/G BlockSpec index maps,
so no gathered copy of X/G is ever materialized in HBM.

Grid: (m_tiles, q_tiles, k_sel) with the reduction axis (selected blocks)
fastest → the (bm, bq) f32 accumulator stays resident in VMEM and flushes
once per output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@functools.partial(
    jax.jit, static_argnames=("bk", "bm", "bq", "interpret", "transpose_lhs"))
def gather_matmul(
    x: jax.Array,          # (n, m) — token-major
    g: jax.Array,          # (n, q)
    idx: jax.Array,        # (k_sel,) int32 selected token-block ids (sorted)
    *,
    bk: int = 128,
    bm: int = 256,
    bq: int = 256,
    transpose_lhs: bool = True,
    interpret: bool = False,
) -> jax.Array:
    assert transpose_lhs, "only the XᵀG form is used by rsc_matmul"
    n, m = x.shape
    _, q = g.shape
    assert n % bk == 0, (n, bk)
    bm = min(bm, m)
    bq = min(bq, q)
    assert m % bm == 0 and q % bq == 0, (m, bm, q, bq)
    k_sel = idx.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm, q // bq, k_sel),
        in_specs=[
            # X slab: rows idx[t]·bk.., cols i·bm..
            pl.BlockSpec((bk, bm), lambda i, j, t, idx: (idx[t], i)),
            # G slab: rows idx[t]·bk.., cols j·bq..
            pl.BlockSpec((bk, bq), lambda i, j, t, idx: (idx[t], j)),
        ],
        out_specs=pl.BlockSpec((bm, bq), lambda i, j, t, idx: (i, j)),
    )

    def body(idx_ref, x_ref, g_ref, out_ref):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += jnp.dot(
            x_ref[...].T, g_ref[...], preferred_element_type=out_ref.dtype)

    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, q), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx, x, g).astype(x.dtype)
