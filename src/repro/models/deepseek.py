"""DeepSeek-V3 decoder (Moonlight-16B-A3B): multi-head latent attention, a
leading dense layer, and sigmoid-routed MoE over the experts this chip holds.

Written to the published description (HF ``modeling_deepseek_v3``):

* MLA with ``q_lora_rank`` null: ``q = W_q h`` -> ``[nh, qk_nope + qk_rope]``;
  ``W_kva h`` -> ``c_kv`` (``kv_lora_rank``, then ``kv_a_layernorm``) and one
  ``k_rope`` shared by every head. RoPE on the rope dims de-interleaves
  (even dims, then odd) and rotates halves; the softmax scale is
  ``1/sqrt(qk_nope + qk_rope)``. A token's cache entry is the latent
  ``c_kv || k_rope`` (``latent_dim``), shared by all heads.
* Full-sequence attention (prefill, training) expands the latent with
  ``kv_b_proj`` into ``k_nope`` and ``v``; one-token decode is absorbed:
  ``q_lat = q_nope W_UK``, scores ``q_lat c_kv + q_rope k_rope`` over the
  latents, output ``(sum p c_kv) W_UV``, then ``o_proj``.
* Router: ``s = sigmoid(h W_g)`` in float32; the choice is the top-k of
  ``s + e_score_correction_bias`` (n_group = topk_group = 1); the weights are
  ``s`` at the chosen experts over their sum (+1e-20), times
  ``routed_scaling_factor``. The first ``first_k_dense_replace`` layers are a
  dense SwiGLU; the shared experts are one SwiGLU of width
  ``num_shared_experts * moe_d_ff``.

Departures, each deliberate:

* Expert share: a layer holds experts ``[expert_rank * H, +H)`` with
  ``H = cfg.held_experts``, routes over all ``num_experts``, and adds only its
  own experts' weighted outputs (plus the shared experts). Pairs routed to
  experts held elsewhere add nothing; no exchange with other chips is stood
  in for. With ``experts_held`` 0 every expert is held and the layer is the
  published one.
* Dropless: every routed pair to a held expert is computed. The held experts
  run as one batched matmul over all tokens, each token's output weighted by
  its combine weight (zero where it did not choose the expert): no capacity,
  no dropped pairs. A prefill so computes every held expert for every token,
  about 8 / 0.75 times the routed pairs' work, where the published model
  sorts or groups the pairs (a departure kept for now: a sorted, tiled loop
  over the pairs did not finish on the v5e, and the reason is not known yet).
* No multi-token-prediction layers (``num_nextn_predict_layers`` 0) and no
  rope scaling (the config has none). The training loss has no balance loss:
  ``noaux_tc`` balances through the bias, which is a parameter here.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.transformer import chunked_ce_loss, lm_head_weight

Params = Dict[str, Any]
F32 = jnp.float32


class LatentCache(NamedTuple):
    """Dense (contiguous) decode cache: one latent per token and layer."""

    lat: jax.Array  # [L, B, S_max, latent_dim]
    pos: jax.Array  # [] int32


# --------------------------------------------------------------------------- init
def init_mla(rng, cfg) -> Params:
    d, nh, c = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 4)
    dt = cfg.pdtype
    return {
        "w_q": L.dense_init(ks[0], d, nh * (dn + dr), dt),
        "w_kva": L.dense_init(ks[1], d, c + dr, dt),
        "kv_norm": jnp.ones((c,), dt),
        "w_kvb": L.dense_init(ks[2], c, nh * (dn + dv), dt),
        "w_o": L.dense_init(ks[3], nh * dv, d, dt),
    }


def _swiglu_init(rng, d, ff, dt) -> Params:
    ks = jax.random.split(rng, 3)
    return {"w_gate": L.dense_init(ks[0], d, ff, dt), "w_up": L.dense_init(ks[1], d, ff, dt),
            "w_down": L.dense_init(ks[2], ff, d, dt)}


def init_moe(rng, cfg) -> Params:
    """Router over all ``num_experts``; routed weights of the held experts only."""
    d, ff, H = cfg.d_model, cfg.moe_d_ff, cfg.held_experts
    ks = jax.random.split(rng, 6)
    dt = cfg.pdtype
    return {
        "router": L.dense_init(ks[0], d, cfg.num_experts, dt),
        # seeded, not zero, so the biased choice differs from the unbiased one
        "bias": jax.random.normal(ks[1], (cfg.num_experts,), F32) * 0.05,
        "w_gate": (jax.random.normal(ks[2], (H, d, ff), F32) / math.sqrt(d)).astype(dt),
        "w_up": (jax.random.normal(ks[3], (H, d, ff), F32) / math.sqrt(d)).astype(dt),
        "w_down": (jax.random.normal(ks[4], (H, ff, d), F32) / math.sqrt(ff)).astype(dt),
        "shared": _swiglu_init(ks[5], d, cfg.num_shared_experts * ff, dt),
    }


def _init_block(rng, cfg, moe: bool) -> Params:
    ks = jax.random.split(rng, 2)
    d = cfg.d_model
    p = {"attn_norm": jnp.ones((d,), cfg.pdtype), "attn": init_mla(ks[0], cfg),
         "mlp_norm": jnp.ones((d,), cfg.pdtype)}
    if moe:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = _swiglu_init(ks[1], d, cfg.dense_d_ff, cfg.pdtype)
    return p


def init_params(rng, cfg) -> Params:
    if (cfg.scoring_func, cfg.topk_method, cfg.q_lora_rank) != ("sigmoid", "noaux_tc", 0):
        raise ValueError("this block implements sigmoid noaux_tc routing and q_lora_rank null")
    k0 = cfg.first_k_dense_replace
    ks = jax.random.split(rng, 5)
    p: Params = {
        "embed": L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, cfg.pdtype),
        "dense_layers": jax.vmap(lambda k: _init_block(k, cfg, False))(jax.random.split(ks[1], k0)),
        "moe_layers": jax.vmap(lambda k: _init_block(k, cfg, True))(
            jax.random.split(ks[2], cfg.num_layers - k0)),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[3], cfg.d_model, cfg.vocab_size, cfg.pdtype)
    return p


# --------------------------------------------------------------------------- MLA
def rope_mla(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """DeepSeek's RoPE: de-interleave the rope dims (even, then odd), then
    rotate halves. x: [..., S, heads, dr]; positions: [..., S]."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return L.apply_rope(x, positions, theta)


def mla_project(p: Params, h: jax.Array, positions: jax.Array, cfg):
    """h: [B, S, d] -> (q_nope [B,S,nh,dn], q_rope [B,S,nh,dr] roped,
    latent [B,S,latent_dim]: normed c_kv || roped k_rope)."""
    B, S, _ = h.shape
    nh, c = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla.project"):
        q = (h @ p["w_q"]).reshape(B, S, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], rope_mla(q[..., dn:], positions, cfg.rope_theta)
        kva = h @ p["w_kva"]
        c_kv = L.rms_norm(kva[..., :c], p["kv_norm"], cfg.norm_eps)
        k_rope = rope_mla(kva[..., None, c:], positions, cfg.rope_theta)[..., 0, :]
        return q_nope, q_rope, jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype)], axis=-1)


def mla_attend_full(p: Params, q_nope, q_rope, latent, cfg) -> jax.Array:
    """Causal attention over the whole sequence, the latent expanded by
    ``kv_b_proj``. Returns [B, S, d]."""
    B, S, nh, dn = q_nope.shape
    c, dv = cfg.kv_lora_rank, cfg.v_head_dim
    with jax.named_scope("mla.attend"):
        kv = (latent[..., :c] @ p["w_kvb"]).reshape(B, S, nh, dn + dv)
        k_rope = jnp.broadcast_to(latent[..., None, c:], (B, S, nh, latent.shape[-1] - c))
        q = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], axis=-1)
        k = jnp.concatenate([kv[..., :dn], k_rope.astype(kv.dtype)], axis=-1)
        o = L.blocked_attention(q, k, kv[..., dn:], causal=True)
        return o.reshape(B, S, nh * dv) @ p["w_o"]


def absorbed_query(p: Params, q_nope, q_rope, width: int, cfg) -> jax.Array:
    """One query per row in latent form, ``q_nope W_UK || q_rope``, zero-padded
    to ``width`` lanes: [B, nh, width] in ``cfg.cdtype``."""
    B, nh, dn = q_nope.shape
    c, dv = cfg.kv_lora_rank, cfg.v_head_dim
    w = p["w_kvb"].reshape(c, nh, dn + dv)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w[..., :dn], preferred_element_type=F32)
    pad = jnp.zeros((B, nh, width - c - q_rope.shape[-1]), F32)
    return jnp.concatenate([q_lat, q_rope.astype(F32), pad], axis=-1).astype(cfg.cdtype)


def absorbed_output(p: Params, o_lat, cfg) -> jax.Array:
    """Attention-weighted ``c_kv`` [B, nh, kv_lora_rank] through ``W_UV`` and
    ``o_proj``: [B, d]."""
    B, nh, c = o_lat.shape
    dn, dv, dt = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.cdtype
    w = p["w_kvb"].reshape(c, nh, dn + dv)
    o = jnp.einsum("bhc,chv->bhv", o_lat.astype(dt), w[..., dn:], preferred_element_type=F32)
    return o.reshape(B, nh * dv).astype(dt) @ p["w_o"]


def mla_scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def mla_attend_latent(p: Params, q_nope, q_rope, lat, mask, cfg) -> jax.Array:
    """Absorbed attention of one query per row over cached latents.

    q_nope [B,nh,dn], q_rope [B,nh,dr], lat [B,T,latent_dim] (any storage
    dtype, computed in ``cfg.cdtype``; lanes past ``latent_dim`` are
    ignored), mask [B,T]. Returns [B, d]."""
    c, dt = cfg.kv_lora_rank, cfg.cdtype
    with jax.named_scope("mla.attend"):
        q = absorbed_query(p, q_nope, q_rope, lat.shape[-1], cfg)
        lat = lat.astype(dt)
        s = jnp.einsum("bhc,btc->bht", q, lat, preferred_element_type=F32) * mla_scale(cfg)
        s = jnp.where(mask[:, None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bht,btc->bhc", pr.astype(dt), lat, preferred_element_type=F32)
        return absorbed_output(p, o_lat[..., :c], cfg)


# --------------------------------------------------------------------------- MoE
def route(p: Params, x: jax.Array, cfg):
    """x: [T, d] -> (ids [T, k] int32, weights [T, k] f32)."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x.astype(F32), p["router"].astype(F32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(s + p["bias"], cfg.moe_top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        if cfg.norm_topk_prob:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return ids.astype(jnp.int32), w * cfg.routed_scaling_factor


def _swiglu(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def held_moe(p: Params, x: jax.Array, cfg):
    """x: [T, d] -> (out [T, d], routed ids [T, k]). Dropless over the held
    experts (module docstring)."""
    ids, w = route(p, x, cfg)
    H = p["w_gate"].shape[0]
    lo = cfg.expert_rank * H if cfg.experts_held else 0
    with jax.named_scope("moe.experts"):
        hit = (ids - lo)[:, :, None] == jnp.arange(H)[None, None, :]  # [T, k, H]
        comb = jnp.where(hit, w[:, :, None], 0.0).sum(axis=1)  # [T, H]
        g = jnp.einsum("td,hdf->thf", x, p["w_gate"])
        u = jnp.einsum("td,hdf->thf", x, p["w_up"])
        a = jax.nn.silu(g) * u * comb[:, :, None].astype(x.dtype)
        y = jnp.einsum("thf,hfd->td", a, p["w_down"])
    with jax.named_scope("moe.shared"):
        y = y + _swiglu(p["shared"], x)
    return y, ids


def mlp_block(lp: Params, h: jax.Array, cfg):
    """The block's feed-forward on [B, S, d]: (out, ids [B*S, k] or None)."""
    B, S, d = h.shape
    if "moe" in lp:
        y, ids = held_moe(lp["moe"], h.reshape(B * S, d), cfg)
        return y.reshape(B, S, d), ids
    return _swiglu(lp["mlp"], h), None


# --------------------------------------------------------------------------- forward
def _block_full(lp: Params, x: jax.Array, positions: jax.Array, cfg):
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q_nope, q_rope, lat = mla_project(lp["attn"], h, positions, cfg)
    x = x + mla_attend_full(lp["attn"], q_nope, q_rope, lat, cfg)
    m, ids = mlp_block(lp, L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps), cfg)
    return x + m, lat, ids


def forward(params: Params, tokens: jax.Array, cfg, remat: bool = False):
    """tokens [B, S] -> (hidden [B,S,d] before the final norm, latents
    [L,B,S,latent_dim], routed ids [L_moe, B*S, k])."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"][tokens].astype(cfg.cdtype)

    def body(x, lp):
        x, lat, ids = _block_full(lp, x, positions, cfg)
        return x, (lat, ids)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (lat0, _) = jax.lax.scan(body, x, params["dense_layers"])
    x, (lat1, ids) = jax.lax.scan(body, x, params["moe_layers"])
    return x, jnp.concatenate([lat0, lat1]), ids


def head(params: Params, h: jax.Array, cfg) -> jax.Array:
    with jax.named_scope("lm_head"):
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return (h @ lm_head_weight(params, cfg)).astype(F32)


def prefill(params: Params, tokens: jax.Array, last, cfg):
    """The forward pass of prompts [B, S], padded at their end (causal
    attention keeps the padding out of every real position).

    Returns (logits [B, V] f32 at position ``last``, the cache rows
    ``(latents [L, B, S, latent_dim],)``, routed ids [L_moe, B * S, k])."""
    h, lat, ids = forward(params, tokens, cfg)
    logits = head(params, jax.lax.dynamic_index_in_dim(h, last, axis=1, keepdims=False), cfg)
    return logits, (lat,), ids


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg, *, remat: str = "block"):
    tokens, labels = batch["tokens"], batch["labels"]
    h, _, _ = forward(params, tokens, cfg, remat=remat != "none")
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    tot, cnt = chunked_ce_loss(h, lm_head_weight(params, cfg), labels, cfg)
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss, {"ce": loss, "aux": jnp.zeros((), F32), "tokens": cnt}


# --------------------------------------------------------------------------- dense decode
def init_cache(cfg, batch: int, max_len: int, dtype=None) -> LatentCache:
    return LatentCache(lat=jnp.zeros((cfg.num_layers, batch, max_len, cfg.latent_dim),
                                     dtype or cfg.cdtype),
                       pos=jnp.zeros((), jnp.int32))


def prefill_cache(params: Params, tokens: jax.Array, cfg, max_len: int):
    """:func:`prefill` into a contiguous cache: (last logits [B, 1, V],
    LatentCache of ``max_len`` positions)."""
    S = tokens.shape[1]
    logits, (lat,), _ = prefill(params, tokens, S - 1, cfg)
    lat = jnp.pad(lat, ((0, 0), (0, 0), (0, max_len - S), (0, 0))).astype(cfg.cdtype)
    return logits[:, None], LatentCache(lat=lat, pos=jnp.asarray(S, jnp.int32))


def decode_layer(lp: Params, x: jax.Array, positions: jax.Array, attend, cfg):
    """One layer for one new token per row. x [B, d]; ``attend(p, q_nope,
    q_rope, new)`` is the attention output [B, d] of each row's query over
    its context and this token's latent ``new`` [B, latent_dim]. Returns (x,
    new latent, ids)."""
    h = L.rms_norm(x[:, None], lp["attn_norm"], cfg.norm_eps)
    q_nope, q_rope, lat = mla_project(lp["attn"], h, positions[:, None], cfg)
    x = x + attend(lp["attn"], q_nope[:, 0], q_rope[:, 0], lat[:, 0])
    m, ids = mlp_block(lp, L.rms_norm(x[:, None], lp["mlp_norm"], cfg.norm_eps), cfg)
    return x + m[:, 0], lat[:, 0], ids


def decode_step(params: Params, token: jax.Array, cache: LatentCache, cfg):
    """One decode step over a contiguous latent cache: (logits [B, V], cache)."""
    B = token.shape[0]
    pos = cache.pos
    positions = jnp.full((B,), pos, jnp.int32)
    mask = jnp.broadcast_to(jnp.arange(cache.lat.shape[2]) <= pos, (B, cache.lat.shape[2]))
    x = params["embed"][token].astype(cfg.cdtype)

    def put(ctx, new):
        return jax.lax.dynamic_update_slice_in_dim(ctx, new[:, None].astype(ctx.dtype), pos, 1)

    def body(x, inp):
        lp, lat_l = inp

        def attend(p, q_nope, q_rope, new):
            return mla_attend_latent(p, q_nope, q_rope, put(lat_l, new), mask, cfg)

        x, new, _ = decode_layer(lp, x, positions, attend, cfg)
        return x, put(lat_l, new)

    k0 = cfg.first_k_dense_replace
    x, lat0 = jax.lax.scan(body, x, (params["dense_layers"], cache.lat[:k0]))
    x, lat1 = jax.lax.scan(body, x, (params["moe_layers"], cache.lat[k0:]))
    return head(params, x, cfg), LatentCache(lat=jnp.concatenate([lat0, lat1]), pos=pos + 1)
