"""Paged decode attention over a latent pool — Pallas TPU kernel (the serving
decode step's attention).

Multi-head latent attention decodes in MQA form: every head's absorbed query
``[nh, W]`` attends over one shared row per token, its keys all ``W`` lanes of
the row and its values the first ``v_width``. The rows live in a paged pool
``[L, n_slots, page, W]`` that stays in HBM (``memory_space=ANY``), reached
through each lane's slot table (vLLM-style indirection): the kernel reads
pool rows in place, the pages whose heat MaxMem tracks, and never builds a
``[B, T, W]`` copy of the context.

The layer, the slot tables (flattened) and the lengths are scalar-prefetched
(SMEM). One invocation walks the lanes in order. For each it copies blocks of
``block_pages`` pages into VMEM, one DMA per page, double-buffered: the next
block, or the next lane's first, is in flight while the current one is
computed. A lane stops at its own length, so no DMA is issued past its last
page, and a table entry of -1 below the length is never read: its tokens are
masked.

Scores and the online softmax are float32 over inputs in the query's dtype
(the pool's rows are cast to it). ``new``, the row of the token being decoded
(not yet in the pool), is the softmax's first element, so every lane attends
to at least one token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
F32 = jnp.float32


def _kernel(layer_ref, tables_ref, lens_ref, q_ref, new_ref, pool_hbm, o_ref, buf, sem, *,
            sm_scale: float, v_width: int, block_pages: int, n_p: int):
    B, nh, W = q_ref.shape
    page = buf.shape[2]
    T = block_pages * page
    layer = layer_ref[0]
    # every row a masked token meets is finite: pool rows, or these zeros
    buf[...] = jnp.zeros_like(buf)

    def n_blocks(b):
        return (lens_ref[b] + T - 1) // T

    def for_pages(b, i, half, op):
        """``op`` ("start" or "wait") on the copy of every page of block ``i``
        of lane ``b`` that is read; returns the block's bit mask of -1 pages.
        Unrolled: a loop over the pages made the DMA issue half again as slow
        on the v5e."""
        first = i * block_pages
        count = (lens_ref[b] + page - 1) // page - first
        bad = jnp.int32(0)
        for j in range(block_pages):
            slot = tables_ref[b * n_p + jnp.minimum(first + j, n_p - 1)]
            read = j < count

            @pl.when(read & (slot >= 0))
            def _():
                copy = pltpu.make_async_copy(pool_hbm.at[layer, slot], buf.at[half, j],
                                             sem.at[half])
                getattr(copy, op)()

            bad = bad | jnp.where(read & (slot < 0), jnp.left_shift(jnp.int32(1), j), 0)
        return bad

    def lane(b, carry):
        half, pending = carry
        nb = n_blocks(b)
        nxt_lane = jnp.minimum(b + 1, B - 1)
        prefetch_next_lane = (b + 1 < B) & (n_blocks(nxt_lane) > 0)

        @pl.when((nb > 0) & (pending == 0))
        def _():
            for_pages(b, 0, half, "start")

        q = q_ref[b]  # [nh, W]
        new = new_ref[b].astype(q.dtype)  # [1, W]: the softmax's first element
        m = (q.astype(F32) * new.astype(F32)).sum(-1, keepdims=True) * sm_scale
        l = jnp.ones((nh, 1), F32)
        acc = jnp.broadcast_to(new[:, :v_width].astype(F32), (nh, v_width))

        def block(i, c):
            m, l, acc, half = c
            nxt = 1 - half

            @pl.when(i + 1 < nb)
            def _():
                for_pages(b, i + 1, nxt, "start")

            @pl.when((i + 1 == nb) & prefetch_next_lane)
            def _():
                for_pages(nxt_lane, 0, nxt, "start")

            bad = for_pages(b, i, half, "wait")
            kv = buf[half].reshape(T, W).astype(q.dtype)
            s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * sm_scale  # [nh, T]
            tok = lax.broadcasted_iota(jnp.int32, (1, T), 1)
            valid = (i * T + tok < lens_ref[b]) & (
                (jnp.right_shift(bad, tok // page) & 1) == 0)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)  # 0 where masked: m_new is at least the new token's score
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            acc = alpha * acc + lax.dot_general(
                p.astype(kv.dtype), kv[:, :v_width], (((1,), (0,)), ((), ())),
                preferred_element_type=F32)
            return m_new, l, acc, nxt

        _, l, acc, half = lax.fori_loop(0, nb, block, (m, l, acc, half))
        o_ref[b] = acc / l
        return half, ((nb > 0) & prefetch_next_lane).astype(jnp.int32)

    lax.fori_loop(0, B, lane, (jnp.int32(0), jnp.int32(0)))


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "v_width", "block_pages", "interpret"))
def paged_attention(
    q: jax.Array,  # [B, nh, W] absorbed queries
    pool: jax.Array,  # [L, n_slots, page, W], read in place
    layer: jax.Array,  # [] int32: the pool's layer to read
    tables: jax.Array,  # [B, n_p] int32 slots (-1 = no page)
    lens: jax.Array,  # [B] int32 tokens of each lane in the pool (0 = idle)
    new: jax.Array,  # [B, W]: each lane's token not yet in the pool
    *,
    sm_scale: float,
    v_width: int,
    block_pages: int = 32,
    interpret: bool,
) -> jax.Array:
    """Attention of each lane's query over ``new`` and the first ``lens[b]``
    tokens of its pages. Returns [B, nh, v_width] float32."""
    B, nh, W = q.shape
    n_p = tables.shape[1]
    page = pool.shape[2]
    assert pool.shape[3] == W and v_width <= W and 0 < block_pages <= 32
    lens = jnp.clip(lens, 0, n_p * page).astype(jnp.int32)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    kernel = functools.partial(_kernel, sm_scale=sm_scale, v_width=v_width,
                               block_pages=block_pages, n_p=n_p)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole((B, nh, W)), whole((B, 1, W)), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole((B, nh, v_width)),
            scratch_shapes=[pltpu.VMEM((2, block_pages, page, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nh, v_width), F32),
        interpret=interpret,
        name="paged_attention",
    )(layer, tables.reshape(-1).astype(jnp.int32), lens, q, new.reshape(B, 1, W), pool)
