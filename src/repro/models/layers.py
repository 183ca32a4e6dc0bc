"""Shared model building blocks (pure JAX, functional, param dicts).

Conventions:
  - params are nested dicts of jnp arrays
  - activations flow as [batch, seq, d_model] in ``cfg.compute_dtype``
  - reductions (norms, softmax) accumulate in fp32
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import tuning

Params = Dict[str, Any]


# --------------------------------------------------------------------------- init
def dense_init(rng, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(rng, vocab: int, dim: int, dtype):
    return (jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if tuning.FLAGS.norm_bf16_apply and dt != jnp.float32:
        # fp32 only for the reduction; the [B,S,1] scale applies in bf16 so
        # the full-width tensors (and their cotangents -> TP collectives)
        # stay at 2 bytes. §Perf knob.
        scale = jax.lax.rsqrt(var + eps).astype(dt)
        return (x * scale) * weight
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------- rope
def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, d_head]; positions: [..., seq] (int)."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)  # [d_head/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., seq, 1, d/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- mlp
def init_mlp(rng, cfg) -> Params:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    dt = cfg.pdtype
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], d, ff, dt),
            "w_up": dense_init(ks[1], d, ff, dt),
            "w_down": dense_init(ks[2], ff, d, dt),
        }
    return {
        "w_up": dense_init(ks[0], d, ff, dt),
        "w_down": dense_init(ks[1], ff, d, dt),
    }


def mlp(params: Params, x: jax.Array, cfg) -> jax.Array:
    act = cfg.activation
    if act == "swiglu":
        g = x @ params["w_gate"]
        u = x @ params["w_up"]
        h = jax.nn.silu(g) * u
    elif act == "geglu":
        g = x @ params["w_gate"]
        u = x @ params["w_up"]
        h = jax.nn.gelu(g) * u
    elif act == "squared_relu":
        h = jnp.square(jax.nn.relu(x @ params["w_up"]))
    elif act == "gelu":
        h = jax.nn.gelu(x @ params["w_up"])
    else:
        raise ValueError(f"unknown activation {act}")
    return h @ params["w_down"]


# --------------------------------------------------------------------------- attention
def init_attention(rng, cfg, d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    dh, nh, nkv = cfg.d_head, cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(rng, 4)
    dt = cfg.pdtype
    p: Params = {
        "w_q": dense_init(ks[0], d, nh * dh, dt),
        "w_k": dense_init(ks[1], d, nkv * dh, dt),
        "w_v": dense_init(ks[2], d, nkv * dh, dt),
        "w_o": dense_init(ks[3], nh * dh, d, dt),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((nh * dh,), dt)
        p["b_k"] = jnp.zeros((nkv * dh,), dt)
        p["b_v"] = jnp.zeros((nkv * dh,), dt)
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def qkv_project(params: Params, x: jax.Array, cfg):
    """x: [B, S, d] -> q [B, S, nh, dh], k/v [B, S, nkv, dh]."""
    B, S, _ = x.shape
    q = x @ params["w_q"]
    k = x @ params["w_k"]
    v = x @ params["w_v"]
    if "b_q" in params:
        q = q + params["b_q"]
        k = k + params["b_k"]
        v = v + params["b_v"]
    q = q.reshape(B, S, cfg.num_heads, cfg.d_head)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.d_head)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.d_head)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    return q, k, v


def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_block: int = 512,
    kv_block: int = 1024,
    sliding_window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    """Memory-efficient (flash-style) attention in pure JAX.

    q: [B, Sq, nh, dh]; k: [B, Skv, nkv, dh]; v: [B, Skv, nkv, dv] with
    nh % nkv == 0 (dv may differ from dh: MLA's v is narrower than q·k).
    Online-softmax over kv blocks via lax.scan, so peak score memory is
    [B, nh, q_block, kv_block] rather than [B, nh, Sq, Skv].
    Returns [B, Sq, nh, dv].
    """
    B, Sq, nh, dh = q.shape
    Skv, nkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(dh)

    # GQA-expand KV to full heads: keeps the head dim uniform so TP sharding
    # (heads -> "model") stays aligned. On real TPU the Pallas flash kernel
    # dedups the reads; here the expansion is a cheap broadcast.
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    pq = (-Sq) % q_block
    pk = (-Skv) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // q_block, (Skv + pk) // kv_block

    qb = q.reshape(B, nq, q_block, nh, dh).transpose(0, 3, 1, 2, 4)  # [B,h,nq,qb,dh]
    kb = k.reshape(B, nk, kv_block, nh, dh).transpose(1, 0, 3, 2, 4)  # [nk,B,h,kb,dh]
    vb = v.reshape(B, nk, kv_block, nh, dv).transpose(1, 0, 3, 2, 4)

    q_pos = q_offset + jnp.arange(nq * q_block).reshape(nq, q_block)
    kv_pos = jnp.arange(nk * kv_block).reshape(nk, kv_block)
    kv_valid = (jnp.arange(nk * kv_block) < Skv).reshape(nk, kv_block)

    # §Perf knob: dtype of the materialized score/probability tensors.
    # fp32 = paper-faithful baseline; bf16 halves the dominant HBM traffic
    # of the XLA attention path (the Pallas kernel keeps them in VMEM).
    sdt = jnp.float32 if tuning.FLAGS.attn_score_f32 else jnp.bfloat16

    def kv_step(carry, inputs):
        acc, m, l = carry  # acc [B,h,nq,qb,dh], m/l [B,h,nq,qb]
        k_j, v_j, kpos_j, kvalid_j = inputs  # [B,h,kb,dh], [kb], [kb]
        s = jnp.einsum(
            "bhqtd,bhkd->bhqtk", qb, k_j, preferred_element_type=sdt
        ) * jnp.asarray(scale, sdt)  # [B,h,nq,qb,kb]
        mask = jnp.broadcast_to(kvalid_j[None, None, :], (nq, q_block, kv_block))
        if causal:
            mask = mask & (kpos_j[None, None, :] <= q_pos[:, :, None])
        if sliding_window:
            mask = mask & (kpos_j[None, None, :] > q_pos[:, :, None] - sliding_window)
        neg = jnp.asarray(-jnp.inf, sdt)
        s = jnp.where(mask[None, None], s, neg)
        m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)  # fully-masked rows
        # one materialized p tensor in sdt: (sub, exp, where) fuse into it
        p = jnp.where(
            mask[None, None],
            jnp.exp(s - m_safe[..., None].astype(sdt)),
            jnp.asarray(0.0, sdt),
        )
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + p.sum(axis=-1).astype(jnp.float32)
        acc = acc * corr[..., None].astype(sdt) + jnp.einsum(
            "bhqtk,bhkd->bhqtd", p.astype(v_j.dtype), v_j,
            preferred_element_type=sdt,
        )
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((B, nh, nq, q_block, dv), sdt)
    m0 = jnp.full((B, nh, nq, q_block), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, nh, nq, q_block), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), (kb, vb, kv_pos, kv_valid))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    out = out.transpose(0, 2, 3, 1, 4).reshape(B, nq * q_block, nh, dv)
    return out[:, :Sq].astype(q.dtype)


def decode_attention_stats(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    length: jax.Array | int,
    *,
    sliding_window: int = 0,
):
    """decode_attention returning (out_unnormalized, m, l) online-softmax
    stats so callers can merge additional keys exactly (deferred cache
    commit, §Perf). out = acc / l recovers the normalized result."""
    B, S, nkv, dh = k_cache.shape
    nh = q.shape[2]
    group = nh // nkv
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, 1, nkv, group, dh)
    s = jnp.einsum(
        "bqngd,bknd->bngqk", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(S)
    mask = pos[None, :] < jnp.asarray(length).reshape(-1, 1)
    if sliding_window:
        mask = mask & (pos[None, :] >= jnp.asarray(length).reshape(-1, 1) - sliding_window)
    s = jnp.where(mask[:, None, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1)  # [B,nkv,g,1]
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask[:, None, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum(
        "bngqk,bknd->bngqd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )  # [B,nkv,g,1,dh] unnormalized
    return acc, m, l


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    length: jax.Array | int,
    *,
    sliding_window: int = 0,
) -> jax.Array:
    """Single-token decode attention.

    q: [B, 1, nh, dh]; k_cache/v_cache: [B, S, nkv, dh]; length: current
    context length (static or traced scalar). Returns [B, 1, nh, dh].
    """
    B, S, nkv, dh = k_cache.shape
    nh = q.shape[2]
    group = nh // nkv
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, 1, nkv, group, dh)
    # q [B,1,nkv,g,dh] x k [B,S,nkv,dh] -> [B,nkv,g,1,S]
    s = jnp.einsum(
        "bqngd,bknd->bngqk", qg, k_cache, preferred_element_type=jnp.float32
    )
    s = s * scale
    pos = jnp.arange(S)
    mask = pos[None, :] < jnp.asarray(length).reshape(-1, 1)  # [B or 1, S]
    if sliding_window:
        mask = mask & (pos[None, :] >= jnp.asarray(length).reshape(-1, 1) - sliding_window)
    s = jnp.where(mask[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bngqk,bknd->bngqd",
        p.astype(v_cache.dtype),
        v_cache,
        preferred_element_type=jnp.float32,
    )  # [B,nkv,g,1,dh]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, 1, nh, dh).astype(q.dtype)
