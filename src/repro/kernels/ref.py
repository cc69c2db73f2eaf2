"""Pure-jnp oracles for every Pallas kernel (tested with assert_allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bcoo_spmm_ref(
    blocks: jax.Array,   # (S+1, bm, bk)
    sel: jax.Array,      # (s_pad,)
    row_ids: jax.Array,  # (s_pad,)
    col_ids: jax.Array,  # (s_pad,)
    h: jax.Array,        # (n_cols, d)
    *,
    n_row_blocks: int,
    bm: int,
    bk: int,
) -> jax.Array:
    d = h.shape[-1]
    hb = h.reshape(-1, bk, d)
    tiles = blocks[sel]                                  # (s_pad, bm, bk)
    gathered = hb[col_ids]                               # (s_pad, bk, d)
    # f32 accumulation at least; float64 operands stay float64.
    part = jnp.einsum("sij,sjd->sid", tiles, gathered,
                      preferred_element_type=jnp.promote_types(
                          h.dtype, jnp.float32))
    out = jax.ops.segment_sum(part, row_ids, num_segments=n_row_blocks)
    return out.reshape(n_row_blocks * bm, d).astype(h.dtype)


def gather_matmul_ref(
    x: jax.Array,      # (n, m)
    g: jax.Array,      # (n, q)
    idx: jax.Array,    # (k_sel,)
    *,
    bk: int,
) -> jax.Array:
    n, m = x.shape
    xb = x.reshape(n // bk, bk, m)
    gb = g.reshape(n // bk, bk, -1)
    return jnp.einsum("kbm,kbq->mq", xb[idx], gb[idx],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def flash_attention_ref(q, k, v, *, q_offset=0, causal=True, window=None):
    """Dense-softmax oracle for the flash kernel."""
    b, tq, nq, hd = q.shape
    tk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    kk = jnp.repeat(k.astype(jnp.float32), g, axis=2)
    vv = jnp.repeat(v.astype(jnp.float32), g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * hd ** -0.5, kk)
    qpos = q_offset + jnp.arange(tq)[:, None]
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv).astype(q.dtype)
