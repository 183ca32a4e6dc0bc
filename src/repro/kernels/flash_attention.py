"""Causal GQA flash attention — Pallas TPU kernel (train/prefill hot spot).

Canonical 4D-grid online-softmax flash: grid = (B, nh, nq, nk) with the kv
dimension innermost ("arbitrary" semantics); accumulators live in VMEM
scratch and persist across the kv iterations of one (b, h, i) cell.

Block shapes are the VMEM tiling: q (q_blk x dh), k/v (kv_blk x dh) with
dh in {64, 128} — MXU-aligned (128 lanes). GQA maps q head h to kv head
h // (nh // nkv) inside the index_map (no KV expansion in memory).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, 1, q_blk, dh]
    k_ref,  # [1, 1, kv_blk, dh]
    v_ref,
    o_ref,  # [1, 1, q_blk, dh]
    acc_ref,  # VMEM scratch [q_blk, dh] f32
    m_ref,  # [q_blk, 1] f32
    l_ref,  # [q_blk, 1] f32
    *,
    sm_scale: float,
    causal: bool,
    sliding_window: int,
    q_blk: int,
    kv_blk: int,
    kv_len: int,
    q_offset: int,
):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # suffix alignment: queries are the last Sq positions of the kv stream
    q_pos = q_offset + i * q_blk + jax.lax.broadcasted_iota(
        jnp.int32, (q_blk, kv_blk), 0
    )
    k_pos = j * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)

    run = jnp.asarray(True)
    if causal:
        # skip blocks entirely in the future (saves ~half the FLOPs)
        run = jnp.logical_and(run, j * kv_blk <= q_offset + i * q_blk + q_blk - 1)
    if sliding_window:
        # skip blocks entirely older than the window
        run = jnp.logical_and(
            run, (j + 1) * kv_blk - 1 >= q_offset + i * q_blk - sliding_window + 1
        )

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [q_blk, kv_blk]
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if sliding_window:
            mask &= k_pos > q_pos - sliding_window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "q_blk", "kv_blk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, nh, Sq, dh]
    k: jax.Array,  # [B, nkv, Skv, dh]
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: int = 0,
    q_blk: int = 256,
    kv_blk: int = 256,
    interpret: bool,
) -> jax.Array:
    B, nh, Sq, dh = q.shape
    nkv, Skv = k.shape[1], k.shape[2]
    assert nh % nkv == 0
    g = nh // nkv
    q_blk = min(q_blk, Sq)
    kv_blk = min(kv_blk, Skv)
    pq, pk = (-Sq) % q_blk, (-Skv) % kv_blk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk_blocks = (Sq + pq) // q_blk, (Skv + pk) // kv_blk

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=1.0 / math.sqrt(dh),
        causal=causal,
        sliding_window=sliding_window,
        q_blk=q_blk,
        kv_blk=kv_blk,
        kv_len=Skv,
        q_offset=Skv - Sq,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, nh, nq, nk_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_blk, dh), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, kv_blk, dh), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nh, Sq + pq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, dh), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
