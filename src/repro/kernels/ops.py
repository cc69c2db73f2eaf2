"""Jit'd public wrappers for the Pallas kernels.

The kernels compile to Mosaic on a TPU. ``interpret=True`` runs the same
kernel bodies in the Pallas interpreter, which is how tests exercise them
on a CPU; it is never chosen implicitly. ``repro.core`` ops name the
lowering with an explicit ``backend`` string (``"pallas"`` or
``"pallas_interpret"``), and :func:`require_tpu` turns a ``"pallas"``
request on a host without a TPU into an error instead of a silent switch.

SpMM dispatch consults :mod:`repro.kernels.autotune`: when ``bd`` is not
given explicitly, the per-signature config cache supplies the tuned dense
column tile (or a heuristic default if the signature was never swept).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import autotune
from repro.kernels.autotune import LANE, padded_width
from repro.kernels.bcoo_spmm import bcoo_spmm as _bcoo_spmm_pallas
from repro.kernels.bcoo_spmm import group_size, grouped_share
from repro.kernels.gather_matmul import gather_matmul as _gather_matmul_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_backend() -> str:
    """Pallas on TPU; pure-jnp reference path elsewhere."""
    return "pallas" if on_tpu() else "jnp"


def require_tpu(backend: str) -> None:
    """Refuse a compiled-kernel backend where no TPU is attached."""
    if backend == "pallas" and not on_tpu():
        raise RuntimeError(
            f"backend 'pallas' compiles the SpMM kernel for a TPU, but JAX's "
            f"default backend is {jax.default_backend()!r}; pass "
            "'pallas_interpret' to run the kernel in the Pallas interpreter")


def publish_grouping(row_ptr, operand, d: int, *, layer: str, op: str,
                     backend: str) -> None:
    """Report how often the kernel's tile groups engage for one call.

    ``row_ptr`` is the host CSR-of-tiles pointer array of ``operand`` (a
    block-COO operand, device or host) or of a plan over it, which
    :func:`bcoo_spmm` walks at feature width ``d``. Sets the registry
    gauges ``spmm.grouped_share{layer,op}`` (share of its tiles that run
    in full groups) and ``spmm.group_k{layer,op}`` (tiles per group) for
    the column tile dispatch serves that call. Other backends never run
    the kernel and report nothing.
    """
    reg = obs.get_registry()
    if not reg.enabled or backend not in ("pallas", "pallas_interpret"):
        return
    row_ptr = np.asarray(row_ptr)
    dp = padded_width(d)
    sig = autotune.signature(
        backend, bm=operand.bm, bk=operand.bk, d=d, s_pad=int(row_ptr[-1]),
        n_row_blocks=row_ptr.shape[0] - 1,
        n_col_blocks=operand.n_col_blocks)
    k = group_size(operand.bm, operand.bk,
                   min(autotune.served_bd(sig, dp), dp),
                   operand.blocks.dtype.itemsize)
    reg.gauge("spmm.grouped_share", grouped_share(row_ptr, k),
              layer=layer, op=op)
    reg.gauge("spmm.group_k", k, layer=layer, op=op)


def bcoo_spmm(blocks, sel, row_ids, col_ids, h, *, n_row_blocks, bm, bk,
              bd: int | None = None, row_ptr=None, bias=None, residual=None,
              relu: bool = False, interpret: bool = False):
    """Block-COO SpMM at any feature width ``d``.

    Columns of ``h``, ``bias`` and ``residual`` are zero-padded up to
    :func:`padded_width` (the kernel's column slabs are LANE-aligned), the
    kernel runs at the padded width, and the output is sliced back to
    ``d``. Zero columns stay zero through the epilogue, so the slice is
    exact.
    """
    d = h.shape[-1]
    dp = padded_width(d)
    if bd is None:
        backend = "pallas_interpret" if interpret else "pallas"
        sig = autotune.signature(
            backend, bm=bm, bk=bk, d=d, s_pad=sel.shape[0],
            n_row_blocks=n_row_blocks, n_col_blocks=h.shape[0] // bk)
        bd = autotune.lookup(sig, d=dp).bd
        obs.get_ledger().note_backend(sig, backend)
    bd = min(bd, dp)
    if bd % LANE or dp % bd:
        raise ValueError(
            f"column tile bd={bd} must be a multiple of {LANE} that divides "
            f"the padded width {dp} (d={d})")
    if dp != d:
        pad = [(0, 0), (0, dp - d)]
        h = jnp.pad(h, pad)
        if bias is not None:
            bias = jnp.pad(bias, pad[1:])
        if residual is not None:
            residual = jnp.pad(residual, pad)
    out = _bcoo_spmm_pallas(
        blocks, sel, row_ids, col_ids, h,
        n_row_blocks=n_row_blocks, bm=bm, bk=bk, bd=bd, row_ptr=row_ptr,
        bias=bias, residual=residual, relu=relu, interpret=interpret)
    return out[:, :d] if dp != d else out


def gather_matmul(x, g, idx, *, bk: int = 128, transpose_lhs: bool = True,
                  interpret: bool = False):
    return _gather_matmul_pallas(
        x, g, idx, bk=bk, transpose_lhs=transpose_lhs, interpret=interpret)


def flash_attention(q, k, v, *, q_offset=0, causal=True, window=None,
                    interpret: bool = False):
    from repro.kernels.flash_attention import flash_attention_fwd
    return flash_attention_fwd(q, k, v, q_offset=q_offset, causal=causal,
                               window=window, interpret=interpret)
