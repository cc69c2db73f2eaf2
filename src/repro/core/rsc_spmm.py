"""rsc_spmm: exact forward SpMM, top-k-sampled backward SpMM (paper §3.1).

Forward:  H_pre = SpMM(Ã, J)                       — exact (Prop. 3.1 requires it)
Backward: ∇J    = SpMM_sampled(Ãᵀ, ∇H_pre; plan)   — only the plan's tiles

Both directions run the same block-COO apply (`spmm_apply`), either the
STREAMING pure-JAX path (`spmm_stream`, a chunked ``lax.scan`` over the tile
list — CPU training / oracle) or the row-segmented Pallas kernel
(`repro.kernels.ops.bcoo_spmm`) selected by ``backend``. The old
``segment_sum`` schedule survives only as the test oracle
(`repro.kernels.ref.bcoo_spmm_ref`): it materializes the full
``(s_pad, bm, d)`` partial-product tensor, which blows the cache for every
sampled plan size, while ``spmm_stream`` keeps the live intermediate at
``(chunk, bm, d)`` and scatter-adds into a donated accumulator.

Fused epilogue: both paths accept ``bias`` / ``residual`` / ``relu`` and
apply ``out = relu(spmm + bias + residual)`` in the same kernel launch
(Pallas) or fused XLA computation (jnp) — the custom VJPs below propagate
gradients through the epilogue (ReLU mask from the exact forward output,
``∂bias = Σ_rows``, ``∂residual = masked cotangent``) before the sampled
backward SpMM.

Bias note (paper §3.1.2): the approximation sits strictly behind the ReLU
mask computed from exact pre-activations, so gradients stay unbiased when
the sampler is; deterministic top-k is unbiased under the zero-centered
assumption of Adelman et al.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import SamplePlan
from repro.sparse.bcoo import BlockCOO, host_row_ptr


def _zero_cot(tree):
    """Cotangents for non-differentiable operands (float0 for ints)."""
    def z(x):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.integer):
            return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)
        return jnp.zeros_like(x)
    return jax.tree.map(z, tree)


def exact_plan(a: BlockCOO) -> SamplePlan:
    """The identity plan of a BlockCOO: its own sorted id lists."""
    return SamplePlan(sel=jnp.arange(a.s_total, dtype=jnp.int32),
                      row_ids=a.row_ids, col_ids=a.col_ids,
                      s_pad=a.s_total, n_active=a.s_total,
                      row_ptr=a.row_ptr)


def spmm_stream(
    blocks: jax.Array,      # (S+1, bm, bk) tiles incl. trailing zero sentinel
    sel: jax.Array,         # (s_pad,) int32
    row_ids: jax.Array,     # (s_pad,) int32, sorted ascending
    col_ids: jax.Array,     # (s_pad,) int32
    h: jax.Array,           # (n_cols, d)
    *,
    n_row_blocks: int,
    bm: int,
    bk: int,
    chunk: int = 32,
) -> jax.Array:
    """Streaming jnp SpMM: ``lax.scan`` over ``chunk``-tile slices.

    Each scan step gathers ``(chunk, bm, bk)`` tiles and ``(chunk, bk, d)``
    dense slabs, contracts them, and scatter-adds into the carried
    ``(n_row_blocks, bm, d)`` accumulator — the ``(s_pad, bm, d)`` tensor of
    the old schedule is never materialized. Tail padding points at the zero
    sentinel tile with row index ``n_row_blocks`` (dropped by the scatter).
    """
    d = h.shape[-1]
    s_pad = sel.shape[0]
    chunk = max(1, min(chunk, s_pad))
    hb = h.reshape(-1, bk, d)
    n_chunks = -(-s_pad // chunk)
    pad = n_chunks * chunk - s_pad
    if pad:
        sentinel = blocks.shape[0] - 1
        sel = jnp.concatenate(
            [sel, jnp.full((pad,), sentinel, sel.dtype)])
        row_ids = jnp.concatenate(
            [row_ids, jnp.full((pad,), n_row_blocks, row_ids.dtype)])
        col_ids = jnp.concatenate([col_ids, jnp.zeros((pad,), col_ids.dtype)])

    def step(acc, xs):
        sl, rw, cl = xs
        part = jnp.einsum("sij,sjd->sid", blocks[sl], hb[cl],
                          preferred_element_type=jnp.float32)
        return acc.at[rw].add(part, mode="drop"), None

    acc = jnp.zeros((n_row_blocks, bm, d), jnp.float32)
    acc, _ = jax.lax.scan(step, acc, (sel.reshape(n_chunks, chunk),
                                      row_ids.reshape(n_chunks, chunk),
                                      col_ids.reshape(n_chunks, chunk)))
    return acc.reshape(n_row_blocks * bm, d).astype(h.dtype)


def spmm_apply(
    blocks: jax.Array,      # (S+1, bm, bk) tiles incl. sentinel
    plan: SamplePlan,
    h: jax.Array,           # (n_cols, d)
    n_row_blocks: int,
    bm: int,
    bk: int,
    backend: str = "jnp",
    *,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    relu: bool = False,
    chunk: int | None = None,
) -> jax.Array:
    """out[r] = epilogue(Σ_{tiles (r,c) in plan} blocks[sel] @ h[c·bk:...]).

    Epilogue contract (identical on every backend):
    ``out = max(acc + bias + residual, 0) if relu else acc + bias + residual``.
    Tuning knobs (Pallas ``bd``, streaming ``chunk``) resolve through
    :mod:`repro.kernels.autotune` when not given explicitly.

    Backends: ``"stream"`` (alias ``"jnp"``, the chunked-scan fallback),
    ``"pallas"`` (row-segmented kernel, compiled for a TPU; JAX refuses it
    on other backends) / ``"pallas_interpret"`` (the same kernel in the
    Pallas interpreter),
    ``"dense"`` (scatter-into-dense + one matmul,
    :mod:`repro.kernels.dense_spmm`), and ``"auto"`` — a trace-time read of
    the per-signature backend decision cached by
    :func:`repro.kernels.autotune.get_or_tune_auto` (never sweeps; the
    heuristic default is the streaming path).
    """
    if backend == "auto":
        from repro import obs
        from repro.kernels import autotune
        sig = autotune.signature(
            "auto", bm=bm, bk=bk, d=h.shape[-1], s_pad=plan.s_pad,
            n_row_blocks=n_row_blocks,
            n_col_blocks=h.shape[0] // bk)
        cfg = autotune.lookup(sig, d=h.shape[-1])
        backend = cfg.backend
        obs.get_ledger().note_backend(sig, backend)
        if chunk is None:
            chunk = cfg.chunk
    if backend == "pallas" or backend == "pallas_interpret":
        from repro.kernels import ops as kops
        return kops.bcoo_spmm(
            blocks, plan.sel, plan.row_ids, plan.col_ids, h,
            n_row_blocks=n_row_blocks, bm=bm, bk=bk,
            row_ptr=plan.row_ptr, bias=bias, residual=residual, relu=relu,
            interpret=(backend == "pallas_interpret"),
        )
    if backend == "dense":
        from repro.kernels.dense_spmm import dense_spmm
        return dense_spmm(
            blocks, plan.sel, plan.row_ids, plan.col_ids, h,
            n_row_blocks=n_row_blocks, bm=bm, bk=bk,
            bias=bias, residual=residual, relu=relu)
    if backend not in ("jnp", "stream"):
        raise ValueError(f"unknown SpMM backend {backend!r}")
    if chunk is None:
        from repro.kernels import autotune
        chunk = autotune.lookup(autotune.signature(
            "jnp", bm=bm, bk=bk, d=h.shape[-1], s_pad=plan.s_pad,
            n_row_blocks=n_row_blocks,
            n_col_blocks=h.shape[0] // bk)).chunk
    out = spmm_stream(blocks, plan.sel, plan.row_ids, plan.col_ids, h,
                      n_row_blocks=n_row_blocks, bm=bm, bk=bk, chunk=chunk)
    if bias is not None:
        out = out + bias
    if residual is not None:
        out = out + residual
    if relu:
        out = jnp.maximum(out, 0.0)
    return out


def _exact_fwd(a: BlockCOO, h: jax.Array, backend: str,
               bias=None, residual=None, relu=False) -> jax.Array:
    return spmm_apply(a.blocks, exact_plan(a), h, a.n_row_blocks, a.bm, a.bk,
                      backend, bias=bias, residual=residual, relu=relu)


# cfg = (backend, relu, has_bias, has_residual) — static dispatch tuple.
@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rsc_spmm(cfg, a, at, bwd_plan, h, bias, residual):
    backend, relu, _, _ = cfg
    return _exact_fwd(a, h, backend, bias, residual, relu)


def _rsc_fwd(cfg, a, at, bwd_plan, h, bias, residual):
    backend, relu, _, _ = cfg
    out = _exact_fwd(a, h, backend, bias, residual, relu)
    # relu'(x) = 1 ⟺ x > 0 ⟺ max(x, 0) > 0: the mask recomputes exactly
    # from the fused output, so the pre-activation never needs saving.
    mask = (out > 0) if relu else None
    return out, (a, at, bwd_plan, mask)


def _rsc_bwd(cfg, res, g):
    backend, relu, has_bias, has_residual = cfg
    a, at, bwd_plan, mask = res
    gp = jnp.where(mask, g, 0) if relu else g
    # ∇J = SpMM_sampled(Ãᵀ, ∇H_pre): only the tiles the plan kept.
    dh = spmm_apply(at.blocks, bwd_plan, gp, at.n_row_blocks, at.bm, at.bk,
                    backend)
    dbias = jnp.sum(gp, axis=0) if has_bias else None
    dres = gp if has_residual else None
    return (_zero_cot(a), _zero_cot(at), _zero_cot(bwd_plan), dh, dbias, dres)


_rsc_spmm.defvjp(_rsc_fwd, _rsc_bwd)


def rsc_spmm(a: BlockCOO, at: BlockCOO, bwd_plan: SamplePlan,
             h: jax.Array, backend: str = "jnp", *,
             bias: jax.Array | None = None,
             residual: jax.Array | None = None,
             relu: bool = False) -> jax.Array:
    """SpMM(a, h) (+ fused epilogue) with sampled VJP through ``at``.

    ``a`` carries its own full plan implicitly (its sorted id lists are the
    exact plan); ``at`` is the pre-transposed operand for the backward op.
    The epilogue is differentiated exactly; only the SpMM against ``at``
    is sampled (under ``bwd_plan``).
    """
    cfg = (backend, relu, bias is not None, residual is not None)
    return _rsc_spmm(cfg, a, at, bwd_plan, h, bias, residual)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exact_spmm(cfg, a, at, h, bias, residual):
    backend, relu, _, _ = cfg
    return _exact_fwd(a, h, backend, bias, residual, relu)


def _eb_fwd(cfg, a, at, h, bias, residual):
    backend, relu, _, _ = cfg
    out = _exact_fwd(a, h, backend, bias, residual, relu)
    mask = (out > 0) if relu else None
    return out, (a, at, mask)


def _eb_bwd(cfg, res, g):
    backend, relu, has_bias, has_residual = cfg
    a, at, mask = res
    gp = jnp.where(mask, g, 0) if relu else g
    dh = _exact_fwd(at, gp, backend)
    dbias = jnp.sum(gp, axis=0) if has_bias else None
    dres = gp if has_residual else None
    return (_zero_cot(a), _zero_cot(at), dh, dbias, dres)


_exact_spmm.defvjp(_eb_fwd, _eb_bwd)


def exact_spmm(a: BlockCOO, at: BlockCOO, h: jax.Array,
               backend: str = "jnp", *,
               bias: jax.Array | None = None,
               residual: jax.Array | None = None,
               relu: bool = False) -> jax.Array:
    """Exact SpMM (+ fused epilogue) with exact VJP — the no-RSC baseline.

    Implemented as a custom_vjp as well so forward/backward both route
    through the same block-COO apply (fair Table 2/3 comparisons).
    ``at`` must be the pre-transposed operand (built at setup time —
    transposition cannot happen under jit).
    """
    cfg = (backend, relu, bias is not None, residual is not None)
    return _exact_spmm(cfg, a, at, h, bias, residual)


def transpose_bcoo(a: BlockCOO) -> BlockCOO:
    """Ãᵀ in BlockCOO form: transpose tiles, swap (row, col), re-sort."""
    rows = np.asarray(a.row_ids)
    cols = np.asarray(a.col_ids)
    order = np.lexsort((rows, cols))
    blocks = jnp.concatenate(
        [jnp.swapaxes(a.blocks[: a.s_total][order], 1, 2),
         jnp.zeros((1, a.bk, a.bm), a.blocks.dtype)], axis=0)
    return BlockCOO(
        blocks=blocks,
        row_ids=jnp.asarray(cols[order]),
        col_ids=jnp.asarray(rows[order]),
        bm=a.bk, bk=a.bm,
        n_rows=a.n_cols, n_cols=a.n_rows,
        n_row_blocks=a.n_col_blocks, n_col_blocks=a.n_row_blocks,
        s_total=a.s_total,
        row_ptr=jnp.asarray(host_row_ptr(cols[order], a.n_col_blocks)),
    )
