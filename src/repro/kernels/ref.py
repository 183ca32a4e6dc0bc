"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each ``<name>_ref`` mirrors the corresponding kernel's contract exactly;
tests sweep shapes/dtypes and assert_allclose kernel-vs-ref.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(
    q: jax.Array,  # [B, nh, Sq, dh]
    k: jax.Array,  # [B, nkv, Skv, dh]
    v: jax.Array,  # [B, nkv, Skv, dh]
    *,
    causal: bool = True,
    sliding_window: int = 0,
) -> jax.Array:
    B, nh, Sq, dh = q.shape
    nkv, Skv = k.shape[1], k.shape[2]
    g = nh // nkv
    if nkv != nh:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(dh)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos + (Skv - Sq)  # allows Sq<Skv (suffix alignment)
    if sliding_window:
        mask &= kpos > qpos + (Skv - Sq) - sliding_window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def paged_attention_ref(
    q: jax.Array,  # [B, nh, W] one query row per head and lane
    pool: jax.Array,  # [L, n_slots, page, W] latent rows
    layer: int,
    tables: jax.Array,  # [B, n_p] int32 slots (-1 = no page)
    lens: jax.Array,  # [B] int32 valid tokens per lane
    new: jax.Array | None = None,  # [B, W] one more token per lane
    *,
    sm_scale: float,
    v_width: int,
) -> jax.Array:
    """Keys are all W lanes of a row, values its first ``v_width``; returns
    [B, nh, v_width] float32 (0 for a lane with no token)."""
    B, nh, W = q.shape
    n_p, page = tables.shape[1], pool.shape[2]
    ctx = pool[layer][jnp.maximum(tables, 0)].reshape(B, n_p * page, W)
    valid = jnp.arange(n_p * page)[None, :] < lens[:, None]
    valid &= jnp.repeat(tables >= 0, page, axis=1)
    if new is not None:
        ctx = jnp.concatenate([ctx, new[:, None].astype(ctx.dtype)], axis=1)
        valid = jnp.concatenate([valid, jnp.ones((B, 1), bool)], axis=1)
    ctx = jnp.where(valid[..., None], ctx, 0).astype(q.dtype)  # rows past the length: unread
    s = jnp.einsum("bhw,btw->bht", q, ctx, preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # rows with zero valid keys
    return jnp.einsum("bht,btv->bhv", p.astype(q.dtype), ctx[..., :v_width],
                      preferred_element_type=jnp.float32)


def hot_bins_ref(
    page_ids: jax.Array,  # [N] int32 sampled page ids; <0 entries ignored
    counts_in: jax.Array,  # [P] int32 existing (cooled) counters
    num_bins: int,
) -> tuple[jax.Array, jax.Array]:
    """Returns (counts_out [P] i32, bins [P] i32)."""
    P = counts_in.shape[0]
    valid = page_ids >= 0
    ids = jnp.where(valid, page_ids, P)
    hist = jnp.zeros((P + 1,), jnp.int32).at[ids].add(1)[:P]
    counts = counts_in + hist
    fl = jnp.where(
        counts > 0,
        31 - jax.lax.clz(jnp.maximum(counts, 1)),
        -1,
    )
    bins = jnp.clip(fl + 1, 0, num_bins - 1).astype(jnp.int32)
    return counts, bins


def page_copy_ref(
    src_pool: jax.Array,  # [Ps, page_elems]
    dst_pool: jax.Array,  # [Pd, page_elems]
    src_ids: jax.Array,  # [M] int32 rows of src_pool
    dst_ids: jax.Array,  # [M] int32 rows of dst_pool
) -> jax.Array:
    """dst_pool with dst_pool[dst_ids[i]] = src_pool[src_ids[i]].

    Contract (shared with the kernel): ids are in-range; padding entries must
    point at a reserved trash row, not -1.
    """
    return dst_pool.at[dst_ids].set(src_pool[src_ids])


def page_move_ref(
    pool: jax.Array, src_ids: jax.Array, dst_ids: jax.Array
) -> jax.Array:
    """Gather semantics: every read sees the PRE-plan pool. Plans must not
    read a row the same plan writes (the MaxMem executor guarantees this:
    promote sources are owned slow slots, demote destinations are unowned
    slow slots — disjoint; write-after-read on freed fast slots is safe)."""
    return pool.at[dst_ids].set(pool[src_ids])
