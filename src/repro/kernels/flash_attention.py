"""Pallas TPU kernel: flash attention (forward) with GQA and windowing.

Production TPU path for prefill/decode attention (training keeps the
rematerialized jnp flash — it needs autodiff). The (bq × bk) logits tile
lives entirely in VMEM; HBM traffic is exactly q+k+v reads and o writes —
this is the fix for the memory-term blow-up the roofline attributes to the
jnp flash's materialized f32 score tensors (EXPERIMENTS.md §Perf H5).

Grid: (b·nq, tq_blocks, kv_blocks) — kv fastest so the (bq, hd) f32
accumulator and (bq,) m/l stats stay resident; the GQA kv head for q head
``h`` is ``h // (nq // nkv)``, computed inside the k/v index maps (no
repeated-KV materialization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention_fwd(
    q: jax.Array,           # (b, tq, nq, hd)
    k: jax.Array,           # (b, tk, nkv, hd)
    v: jax.Array,           # (b, tk, nkv, hd)
    *,
    q_offset: int | jax.Array = 0,   # absolute position of q[0]
    causal: bool = True,
    window: int | None = None,
    bq: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, tq, nq, hd = q.shape
    tk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    bq = min(bq, tq)
    bk = min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0, (tq, bq, tk, bk)

    scale = hd ** -0.5
    # (B, t, hd) head-major layouts
    qm = q.transpose(0, 2, 1, 3).reshape(b * nq, tq, hd)
    km = k.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hd)
    vm = v.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hd)
    q_off = jnp.asarray(q_offset, jnp.int32).reshape(1)

    def kv_head(h):
        return (h // nq) * nkv + (h % nq) // g

    grid = (b * nq, tq // bq, tk // bk)

    def body(qoff_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        j = pl.program_id(2)
        nj = pl.num_programs(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qb = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        kb = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32)

        i = pl.program_id(1)
        qpos = qoff_ref[0] + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(j == nj - 1)
        def _finish():
            o_ref[0] = (acc_ref[...] /
                        jnp.maximum(l_ref[...], 1e-30)[:, None]
                        ).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j, qo: (h, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda h, i, j, qo: (kv_head(h), j, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda h, i, j, qo: (kv_head(h), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j, qo: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * nq, tq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_off, qm, km, vm)
    return out.reshape(b, nq, tq, hd).transpose(0, 2, 1, 3)
