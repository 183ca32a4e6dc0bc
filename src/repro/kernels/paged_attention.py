"""Paged decode attention — Pallas TPU kernel (serving hot spot).

One query token per sequence attends over a *paged* KV pool through a block
table (vLLM-style indirection). The block table and sequence lengths are
scalar-prefetched (SMEM) so each grid step's page id feeds the BlockSpec
index_map — the kernel walks physical pages, not virtual positions. This is
the access path MaxMem's tiering manages: the pool rows it reads are exactly
the "pages" whose heat the central manager tracks.

Grid: (B, nkv, n_pages_per_seq); the page dimension is innermost with VMEM
accumulators, online softmax over pages. GQA: q is viewed [B, nkv, g, dh];
each (b, kv-head) cell processes its g query heads as one (g x dh) block
(g x page MXU matmuls).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    tables_ref,  # SMEM [B, n_p] int32 (scalar prefetch)
    lens_ref,  # SMEM [B] int32 (scalar prefetch)
    q_ref,  # [1, 1, g, dh]
    k_ref,  # [1, page, 1, dh] — row tables[b, p] of the pool
    v_ref,
    o_ref,  # [1, 1, g, dh]
    acc_ref,  # VMEM [g, dh] f32
    m_ref,  # VMEM [g, 1] f32
    l_ref,  # VMEM [g, 1] f32
    *,
    sm_scale: float,
    page: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = lens_ref[b]
    page_id = tables_ref[b, p]
    n_valid = jnp.clip(seq_len - p * page, 0, page)
    run = jnp.logical_and(n_valid > 0, page_id >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [g, dh]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [page, dh]
        v = v_ref[0, :, 0, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [g, page]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = pos < n_valid
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        pr = jnp.exp(s - m_new[:, None])
        pr = jnp.where(mask, pr, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + pr.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(p == n_p - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jax.Array,  # [B, nh, dh]
    k_pages: jax.Array,  # [P, page, nkv, dh]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, n_p] int32; -1 entries skipped
    seq_lens: jax.Array,  # [B] int32
    *,
    interpret: bool,
) -> jax.Array:
    B, nh, dh = q.shape
    P, page, nkv, _ = k_pages.shape
    n_p = block_tables.shape[1]
    assert nh % nkv == 0
    g = nh // nkv
    qg = q.reshape(B, nkv, g, dh)

    kernel = functools.partial(_paged_kernel, sm_scale=1.0 / math.sqrt(dh), page=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nkv, n_p),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b, h, p, tables, lens: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, page, 1, dh),
                lambda b, h, p, tables, lens: (jnp.maximum(tables[b, p], 0), 0, h, 0),
            ),
            pl.BlockSpec(
                (1, page, 1, dh),
                lambda b, h, p, tables, lens: (jnp.maximum(tables[b, p], 0), 0, h, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda b, h, p, tables, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, g, dh), q.dtype),
        interpret=interpret,
    )(block_tables, seq_lens, qg, k_pages, v_pages)
    return out.reshape(B, nh, dh)
