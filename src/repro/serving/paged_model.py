"""Jitted batched decode over the tiered paged KV cache.

Grouped-query attention, per layer and step:
  1. project q/k/v for the new token; write k/v into the current page slot
  2. update the page's Quest summaries (key min/max)
  3. score all pages of each sequence with the Quest upper bound
         score(p) = sum_h sum_d max(q_hd * kmax_pd, q_hd * kmin_pd)
     and select the top-``quest_pages`` pages (current page force-included)
  4. gather ONLY the selected pages and run masked decode attention
  5. emit the selected logical page ids -> per-page access counts

Multi-head latent attention (``cfg.is_mla``) takes the exact path whatever
``quest_pages`` says (the latent pool keeps no Quest summaries): attention
is absorbed (``models/deepseek.py``) and the paged kernel
(``kernels/paged_attention.py``) reads every valid page of each lane in
place from the pool, up to the lane's own length, with the new token's
latent folded into the same softmax; every valid page is reported touched
once a layer. After the layer scan one scatter writes every layer's new
latent into its page slot.

:func:`paged_prefill` is the prefill of one prompt for either layout, its
cache rows in the pools' order (``ModelAPI.paged_prefill``).

The per-page access counts are the PEBS-analogue stream MaxMem samples: with
top-k selection, page touches are heat-skewed, which is exactly what makes
tiering profitable (hot pages earn fast-tier residency).
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kvcache.paged import put_tokens
from repro.models import deepseek
from repro.models import layers as L
from repro.models.model import get_model
from repro.models.moe import moe_mlp
from repro.models.transformer import lm_head_weight

NEG_INF = -1e30


class PagedPools(NamedTuple):
    k: jax.Array  # [L, n_slots, page, nkv, dh]
    v: jax.Array
    kmax: jax.Array  # [L, n_slots, nkv, dh] f32
    kmin: jax.Array


@partial(jax.jit, static_argnames=("cfg",))
def paged_prefill(params, tokens: jax.Array, last: jax.Array, cfg):
    """tokens [1, S] (a prompt padded at its end) -> (logits [1, V] f32 at
    position ``last``, the rows of each pool [L, 1, S, *row], routed ids
    [L_moe, S, k] or None)."""
    return get_model(cfg).paged_prefill(params, tokens, last)


@partial(jax.jit, static_argnames=("cfg", "quest_pages", "num_logical_pages"),
         donate_argnames=("pools",))
def paged_decode_step(
    params,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] int32 (index of the token being generated)
    slot_tables: jax.Array,  # [B, n_p] int32 physical slots (-1 = no page)
    logical_tables: jax.Array,  # [B, n_p] int32 logical page ids (-1 = none)
    active: jax.Array,  # [B] bool
    pools,  # (k, v, kmax, kmin), or (latent,) for MLA; donated
    num_logical_pages: int = 0,
    cfg=None,
    quest_pages: int = 4,
):
    """Returns (logits [B, V], pools', access_counts [P_logical] i32); an
    MLA model adds the routed expert ids of its MoE layers, [L_moe, B, k]."""
    if cfg.is_mla:
        return _mla_decode(params, tokens, positions, slot_tables, logical_tables, active,
                           pools, num_logical_pages, cfg)
    pools = PagedPools(*pools)
    B = tokens.shape[0]
    page = pools.k.shape[2]
    n_p = slot_tables.shape[1]
    nkv, dh, nh = cfg.num_kv_heads, cfg.d_head, cfg.num_heads
    g = nh // nkv

    x = params["embed"][tokens[:, None]].astype(cfg.cdtype)  # [B, 1, d]
    pos_b = positions  # [B]
    cur_p = pos_b // page
    cur_off = pos_b % page
    cur_slot = jnp.take_along_axis(slot_tables, cur_p[:, None], axis=1)[:, 0]
    cur_slot = jnp.maximum(cur_slot, 0)
    # Inactive lanes must not write: their clamped cur_slot would be row 0,
    # silently corrupting whatever page physically lives there (KV bytes AND
    # Quest summaries). Route their writes out of bounds so the scatter
    # drops them.
    n_slots = pools.k.shape[1]
    write_slot = jnp.where(active, cur_slot, n_slots)
    seq_lens = jnp.where(active, pos_b + 1, 0)

    k_sel_n = min(quest_pages, n_p)
    P_logical = num_logical_pages

    def layer_fn(carry, xs):
        x, counts = carry
        lp, kp, vp, kmx, kmn = xs  # per-layer pools
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], h, cfg)  # q [B,1,nh,dh]
        rope_pos = pos_b[:, None]
        q = L.apply_rope(q, rope_pos, cfg.rope_theta)
        k = L.apply_rope(k, rope_pos, cfg.rope_theta)

        # ---- write new token into its page slot (idle lanes dropped) -----
        kp = kp.at[write_slot, cur_off].set(k[:, 0].astype(kp.dtype), mode="drop")
        vp = vp.at[write_slot, cur_off].set(v[:, 0].astype(vp.dtype), mode="drop")
        kmx = kmx.at[write_slot].max(k[:, 0].astype(jnp.float32), mode="drop")
        kmn = kmn.at[write_slot].min(k[:, 0].astype(jnp.float32), mode="drop")

        # ---- Quest page scores -------------------------------------------
        st = jnp.maximum(slot_tables, 0)
        kmx_t = kmx[st]  # [B, n_p, nkv, dh]
        kmn_t = kmn[st]
        qg = q.reshape(B, nkv, g, dh).astype(jnp.float32)
        hi = jnp.einsum("bngd,bpnd->bpng", qg, kmx_t)
        lo = jnp.einsum("bngd,bpnd->bpng", qg, kmn_t)
        score = jnp.maximum(hi, lo).sum(axis=(2, 3))  # [B, n_p]
        valid_page = (slot_tables >= 0) & (
            jnp.arange(n_p)[None, :] * page < seq_lens[:, None]
        )
        score = jnp.where(valid_page, score, NEG_INF)
        # force-include the current page
        score = jnp.where(
            jnp.arange(n_p)[None, :] == cur_p[:, None], jnp.inf, score
        )
        _, sel = jax.lax.top_k(score, k_sel_n)  # [B, k_sel] table positions

        # ---- gather selected pages + attention ---------------------------
        sel_slots = jnp.take_along_axis(st, sel, axis=1)  # [B, k_sel]
        k_sel = kp[sel_slots]  # [B, k_sel, page, nkv, dh]
        v_sel = vp[sel_slots]
        tok_pos = sel[:, :, None] * page + jnp.arange(page)[None, None, :]
        tok_valid = (tok_pos < seq_lens[:, None, None]) & jnp.take_along_axis(
            valid_page | (jnp.arange(n_p)[None, :] == cur_p[:, None]), sel, axis=1
        )[:, :, None]
        kk = k_sel.reshape(B, k_sel_n * page, nkv, dh)
        vv = v_sel.reshape(B, k_sel_n * page, nkv, dh)
        mask = tok_valid.reshape(B, k_sel_n * page)
        s = jnp.einsum(
            "bngd,bknd->bngk", q.reshape(B, nkv, g, dh), kk,
            preferred_element_type=jnp.float32,
        ) / math.sqrt(dh)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        p_att = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum(
            "bngk,bknd->bngd", p_att.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32,
        ).reshape(B, 1, nh * dh).astype(x.dtype)
        x = x + o @ lp["attn"]["w_o"]

        h2 = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if cfg.is_moe:
            m, _ = moe_mlp(lp["moe"], h2, cfg)
        else:
            m = L.mlp(lp["mlp"], h2, cfg)
        x = x + m

        # ---- access accounting (selected logical pages) -------------------
        sel_logical = jnp.take_along_axis(logical_tables, sel, axis=1)  # [B,k]
        ok = (sel_logical >= 0) & active[:, None]
        idx = jnp.where(ok, sel_logical, P_logical)
        counts = counts.at[idx.reshape(-1)].add(1, mode="drop")
        return (x, counts), (kp, vp, kmx, kmn)

    counts0 = jnp.zeros((int(P_logical) + 1,), jnp.int32)
    (x, counts), new_pools = jax.lax.scan(
        layer_fn,
        (x, counts0),
        (params["layers"], pools.k, pools.v, pools.kmax, pools.kmin),
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ lm_head_weight(params, cfg)).astype(jnp.float32)
    # an inactive lane's attention gathers whatever page physically sits at
    # row 0 — layout-dependent garbage. Zero those rows so the step output
    # is a pure function of logical state (the reuse-parity tests rely on
    # this, and callers never consume dead-lane logits anyway).
    logits = jnp.where(active[:, None], logits, 0.0)
    return logits, PagedPools(*new_pools), counts[:-1]


def _mla_decode(params, tokens, positions, slot_tables, logical_tables, active, pools,
                num_logical_pages, cfg):
    (lat_pool,) = pools  # [L, n_slots, page, latent_dim]
    B, n_p = slot_tables.shape
    n_slots, page, width = lat_pool.shape[1:]
    cur_p, cur_off = positions // page, positions % page
    cur_slot = jnp.take_along_axis(slot_tables, cur_p[:, None], axis=1)[:, 0]
    # idle lanes write out of range: the scatter drops them
    write_slot = jnp.where(active, jnp.maximum(cur_slot, 0), n_slots)
    lens = jnp.where(active, positions, 0)  # tokens already in the pool

    def body(x, inp):
        lp, layer = inp

        def attend(p, q_nope, q_rope, new):  # this token joins the pool's pages
            with jax.named_scope("mla.attend"):
                q = deepseek.absorbed_query(p, q_nope, q_rope, width, cfg)
                new = jnp.pad(new.astype(lat_pool.dtype), ((0, 0), (0, width - new.shape[-1])))
                o_lat = ops.paged_attention(q, lat_pool, layer, slot_tables, lens, new,
                                            sm_scale=deepseek.mla_scale(cfg),
                                            v_width=cfg.kv_lora_rank)
                return deepseek.absorbed_output(p, o_lat, cfg)

        x, new, ids = deepseek.decode_layer(lp, x, positions, attend, cfg)
        return x, (new, ids)

    k0, L = cfg.first_k_dense_replace, cfg.num_layers
    x = params["embed"][tokens].astype(cfg.cdtype)
    x, (lat0, _) = jax.lax.scan(body, x, (params["dense_layers"], jnp.arange(k0)))
    x, (lat1, ids) = jax.lax.scan(body, x, (params["moe_layers"], jnp.arange(k0, L)))
    with jax.named_scope("mla.latent_write"):
        lat_pool = put_tokens(lat_pool, write_slot, cur_off, jnp.concatenate([lat0, lat1]))
    # every valid page of an active sequence is read in every layer
    touched = (slot_tables >= 0) & (jnp.arange(n_p)[None, :] <= cur_p[:, None]) & active[:, None]
    idx = jnp.where(touched, logical_tables, num_logical_pages)
    counts = jnp.zeros((num_logical_pages + 1,), jnp.int32).at[idx.reshape(-1)].add(L, mode="drop")
    logits = jnp.where(active[:, None], deepseek.head(params, x, cfg), 0.0)
    return logits, (lat_pool,), counts[:-1], ids
