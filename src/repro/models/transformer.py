"""Decoder-only transformer assembly (dense / MoE / VLM backbones).

Layers are stacked along a leading axis and iterated with ``lax.scan`` so the
HLO stays O(1) in depth (fast compiles at 64 layers, small dry-run graphs).
Per-layer remat (``jax.checkpoint``) wraps the scan body.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch.partitioning import shard
from repro.models import layers as L
from repro.models import tuning
from repro.models.moe import init_moe, moe_mlp

Params = Dict[str, Any]

REMAT_POLICIES = {
    "none": None,  # no remat
    "block": "recompute_all",  # recompute everything within a layer
    "dots": "dots_saveable",
}


class KVCache(NamedTuple):
    """Dense (contiguous) decode cache. k/v: [L, B, S_max, nkv, dh]."""

    k: jax.Array
    v: jax.Array
    pos: jax.Array  # [] int32 — tokens already in cache


# --------------------------------------------------------------------------- init
def init_block(rng, cfg) -> Params:
    ks = jax.random.split(rng, 3)
    d = cfg.d_model
    p: Params = {
        "attn_norm": jnp.ones((d,), cfg.pdtype),
        "attn": L.init_attention(ks[0], cfg),
        "mlp_norm": jnp.ones((d,), cfg.pdtype),
    }
    if cfg.is_moe:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg)
    return p


def init_params(rng, cfg) -> Params:
    ks = jax.random.split(rng, 4)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)
    p: Params = {
        "embed": L.embed_init(ks[1], cfg.vocab_size, cfg.d_model, cfg.pdtype),
        "layers": jax.vmap(lambda k: init_block(k, cfg))(layer_keys),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[2], cfg.d_model, cfg.vocab_size, cfg.pdtype)
    return p


# --------------------------------------------------------------------------- block
def _attn_full(lp: Params, x: jax.Array, cfg, positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence attention (train / prefill). Returns (out, k, v)."""
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = L.qkv_project(lp["attn"], h, cfg)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    o = L.blocked_attention(
        q, k, v, causal=True, sliding_window=cfg.sliding_window,
        q_block=tuning.FLAGS.q_block, kv_block=tuning.FLAGS.kv_block,
    )
    o = o.reshape(*x.shape[:2], -1) @ lp["attn"]["w_o"]
    return o, k, v


def block_full(lp: Params, x: jax.Array, cfg, positions: jax.Array):
    """One decoder layer over a full sequence. Returns (x, aux, (k, v))."""
    o, k, v = _attn_full(lp, x, cfg, positions)
    x = x + o
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.is_moe:
        m, aux = moe_mlp(lp["moe"], h, cfg)
    else:
        m, aux = L.mlp(lp["mlp"], h, cfg), jnp.zeros((), jnp.float32)
    if tuning.FLAGS.seq_parallel_activations and not cfg.is_moe:
        # Megatron-style sequence parallelism: the residual stream is
        # model-axis sharded between layers; XLA inserts the ag/rs pair.
        h2 = shard(x + m, "batch", "seq_sp", None)
    else:
        h2 = shard(x + m, "batch", "seq", None)
    return h2, aux, (k, v)


def block_decode(lp: Params, x: jax.Array, cfg, k_cache, v_cache, pos):
    """One decoder layer for a single new token.

    x: [B, 1, d]; k_cache/v_cache: [B, S, nkv, dh]; pos: [] int32.
    Returns (x, k_cache, v_cache).
    """
    B = x.shape[0]
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = L.qkv_project(lp["attn"], h, cfg)
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), pos, 1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), pos, 1)
    o = L.decode_attention(
        q, k_cache, v_cache, pos + 1, sliding_window=cfg.sliding_window
    )
    x = x + o.reshape(B, 1, -1) @ lp["attn"]["w_o"]
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.is_moe:
        m, _ = moe_mlp(lp["moe"], h, cfg)
    else:
        m = L.mlp(lp["mlp"], h, cfg)
    return x + m, k_cache, v_cache


# --------------------------------------------------------------------------- forward
def embed_tokens(params: Params, tokens: jax.Array, cfg) -> jax.Array:
    x = params["embed"][tokens].astype(cfg.cdtype)
    return shard(x, "batch", "seq", None)


def forward_hidden(
    params: Params,
    x: jax.Array,
    cfg,
    positions: jax.Array,
    *,
    remat: str = "block",
    collect_kv: bool = False,
):
    """Run the layer stack. x: [B, S, d]. Returns (hidden, aux, kv|None)."""

    def body(carry, lp):
        h, aux = carry
        h, a, kv = block_full(lp, h, cfg, positions)
        ys = kv if collect_kv else None
        return (h, aux + a), ys

    if remat != "none":
        policy = None
        if remat == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    (h, aux), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, aux, kv


def lm_head_weight(params: Params, cfg) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T  # [d, V]
    return params["lm_head"]


def chunked_ce_loss(
    hidden: jax.Array,  # [B, S, d]
    head: jax.Array,  # [d, V]
    labels: jax.Array,  # [B, S] int32, -1 = ignore
    cfg,
    chunk: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy scanned over sequence chunks: peak memory is
    [B, chunk, V] logits instead of [B, S, V]. Returns (sum_loss, n_valid)."""
    B, S, d = hidden.shape
    V = head.shape[1]
    if chunk <= 0:
        # target <= ~64 MB fp32 logits per chunk (pre-sharding)
        chunk = max(16, min(S, int(64e6 / max(B * V * 4, 1)) or 16))
        chunk = max(16, 1 << (chunk.bit_length() - 1))
        chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (S + pad) // chunk
    hc = hidden.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

    def step(carry, inp):
        tot, cnt = carry
        h, lab = inp  # [B, chunk, d], [B, chunk]
        ldt = jnp.bfloat16 if tuning.FLAGS.loss_logits_bf16 else jnp.float32
        logits = (h @ head).astype(ldt)  # [B, chunk, V]
        logits = shard(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        lab_c = jnp.clip(lab, 0, V - 1)
        ll = jnp.take_along_axis(logits, lab_c[..., None], axis=-1)[..., 0].astype(jnp.float32)
        valid = (lab >= 0).astype(jnp.float32)
        tot = tot + ((lse - ll) * valid).sum()
        cnt = cnt + valid.sum()
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return tot, cnt


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg, *, remat: str = "block"):
    """Next-token LM loss. batch: tokens [B, S], labels [B, S] (-1 ignore)."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed_tokens(params, tokens, cfg)
    h, aux, _ = forward_hidden(params, x, cfg, positions, remat=remat)
    tot, cnt = chunked_ce_loss(h, lm_head_weight(params, cfg), labels, cfg)
    loss = tot / jnp.maximum(cnt, 1.0)
    metrics = {"ce": loss, "aux": aux, "tokens": cnt}
    return loss + aux, metrics


# --------------------------------------------------------------------------- decode
def init_kv_cache(cfg, batch: int, max_len: int, dtype=None) -> KVCache:
    dt = dtype or cfg.cdtype
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.d_head)
    return KVCache(
        k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt), pos=jnp.zeros((), jnp.int32)
    )


def shard_kv_cache(cache: KVCache) -> KVCache:
    return KVCache(
        k=shard(cache.k, None, "batch", "kv_seq", "kv_heads", None),
        v=shard(cache.v, None, "batch", "kv_seq", "kv_heads", None),
        pos=cache.pos,
    )


def prefill(params: Params, tokens: jax.Array, cfg, max_len: int):
    """Process a full prompt; returns (last_logits, KVCache of size max_len)."""
    S = tokens.shape[1]
    logits, (k, v), _ = paged_prefill(params, tokens, S - 1, cfg)
    pad = max_len - S
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = shard_kv_cache(KVCache(k=k, v=v, pos=jnp.asarray(S, jnp.int32)))
    return logits[:, None], cache


def paged_prefill(params: Params, tokens: jax.Array, last, cfg):
    """The forward pass of prompts [B, S] for a paged cache: (logits [B, V]
    f32 at position ``last``, the cache rows ``(k, v)`` [L, B, S, nkv, dh],
    no routed ids). Padding past ``last`` stays out of every real position
    of a dense model (causal attention); a capacity-routed MoE layer counts
    it against its capacity."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed_tokens(params, tokens, cfg)
    h, _, (k, v) = forward_hidden(params, x, cfg, positions, remat="none", collect_kv=True)
    h = jax.lax.dynamic_index_in_dim(h, last, axis=1, keepdims=False)
    logits = (h @ lm_head_weight(params, cfg)).astype(jnp.float32)
    return logits, (k.astype(cfg.cdtype), v.astype(cfg.cdtype)), None


def _block_decode_deferred(lp, x, cfg, k_cache, v_cache, pos):
    """block_decode that does NOT mutate the cache: attention runs over the
    existing ``pos`` tokens (read-only) and the current token's key/value are
    merged into the softmax exactly; returns the new (k, v) for a post-scan
    batched commit."""
    B = x.shape[0]
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = L.qkv_project(lp["attn"], h, cfg)
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    nkv, dh = cfg.num_kv_heads, cfg.d_head
    g = cfg.num_heads // nkv
    acc, m, l = L.decode_attention_stats(
        q, k_cache, v_cache, pos, sliding_window=cfg.sliding_window
    )
    # merge the current token: score q·k_new, value v_new
    qg = q.reshape(B, 1, nkv, g, dh)
    s_new = jnp.einsum(
        "bqngd,bqnd->bngq", qg, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(dh, jnp.float32))  # [B,nkv,g,1]
    m2 = jnp.maximum(m, s_new)
    w_c = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m2))
    w_n = jnp.exp(s_new - m2)
    acc2 = acc * w_c[..., None] + w_n[..., None] * v.astype(jnp.float32).reshape(
        B, 1, nkv, 1, dh
    ).transpose(0, 2, 3, 1, 4)
    l2 = l * w_c + w_n
    o = (acc2 / jnp.maximum(l2[..., None], 1e-30)).astype(x.dtype)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, 1, cfg.num_heads * dh)
    x = x + o @ lp["attn"]["w_o"]
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.is_moe:
        mo, _ = moe_mlp(lp["moe"], h, cfg)
    else:
        mo = L.mlp(lp["mlp"], h, cfg)
    return x + mo, k, v


def decode_step(params: Params, token: jax.Array, cache: KVCache, cfg):
    """One decode step. token: [B] int32. Returns (logits [B, V], cache)."""
    B = token.shape[0]
    x = embed_tokens(params, token[:, None], cfg)
    pos = cache.pos

    if tuning.FLAGS.decode_deferred_commit:
        def body(h, inp):
            lp, kc, vc = inp
            h, k_new, v_new = _block_decode_deferred(lp, h, cfg, kc, vc, pos)
            return h, (k_new.astype(kc.dtype), v_new.astype(vc.dtype))

        h, (k_tok, v_tok) = jax.lax.scan(
            body, x, (params["layers"], cache.k, cache.v)
        )
        # one small commit for ALL layers: [L, B, 1, nkv, dh] at seq pos
        k_all = jax.lax.dynamic_update_slice(cache.k, k_tok, (0, 0, pos, 0, 0))
        v_all = jax.lax.dynamic_update_slice(cache.v, v_tok, (0, 0, pos, 0, 0))
        new_cache = shard_kv_cache(KVCache(k=k_all, v=v_all, pos=pos + 1))
    else:
        def body(h, inp):
            lp, kc, vc = inp
            h, kc, vc = block_decode(lp, h, cfg, kc, vc, pos)
            return h, (kc, vc)

        h, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"], cache.k, cache.v))
        new_cache = shard_kv_cache(KVCache(k=k_new, v=v_new, pos=pos + 1))
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = (h[:, 0] @ lm_head_weight(params, cfg)).astype(jnp.float32)
    logits = shard(logits, "batch", "vocab")
    return logits, new_cache
