"""Mixture-of-Experts block: top-k routing + capacity grouped matmul.

Dispatch strategy (TPU-native): instead of GShard's [T, E, C] one-hot einsum
(memory-hostile at Big-Data batch sizes) we compute per-assignment slots with a
one-hot cumsum rank, scatter tokens into an [E, C, d] buffer, and run the
expert FFNs as one batched einsum. With experts sharded over the "model" mesh
axis this lowers to an all-to-all-style resharding + per-device grouped GEMM.

Dropped tokens (beyond capacity) fall through via the residual connection,
standard for capacity-factor routing. An auxiliary load-balance loss follows
Switch/GShard.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.launch.partitioning import shard
from repro.models import tuning
from repro.models.layers import dense_init

Params = Dict[str, Any]


def _padded_experts(cfg) -> int:
    return max(cfg.num_experts, cfg.expert_pad_to or 0)


def init_moe(rng, cfg) -> Params:
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    Ep = _padded_experts(cfg)  # weight arrays padded for even EP sharding
    ks = jax.random.split(rng, 5)
    dt = cfg.pdtype
    scale = 1.0 / math.sqrt(d)
    p: Params = {
        "router": dense_init(ks[0], d, E, jnp.float32, scale=scale),
        "w_gate": (jax.random.normal(ks[1], (Ep, d, ff), jnp.float32) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (Ep, d, ff), jnp.float32) * scale).astype(dt),
        "w_down": (
            jax.random.normal(ks[3], (Ep, ff, d), jnp.float32) / math.sqrt(ff)
        ).astype(dt),
    }
    if cfg.num_shared_experts:
        sf = cfg.num_shared_experts * ff
        sks = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(sks[0], d, sf, dt),
            "w_up": dense_init(sks[1], d, sf, dt),
            "w_down": dense_init(sks[2], sf, d, dt),
        }
    return p


def _capacity(tokens: int, cfg) -> int:
    cf = tuning.FLAGS.capacity_factor or cfg.capacity_factor
    cap = int(math.ceil(tokens * cfg.moe_top_k / cfg.num_experts * cf))
    # keep lane-aligned for TPU
    return max(8, ((cap + 7) // 8) * 8)


def moe_mlp(params: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    if tuning.FLAGS.moe_shardmap:
        from repro.launch import partitioning as _pt

        ctx = _pt._current()
        if ctx is not None:
            mesh, rules = ctx
            return moe_mlp_shardmap(params, x, cfg, mesh, rules)
    B, S, d = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.moe_top_k
    Ep = _padded_experts(cfg)
    C = _capacity(T, cfg)
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ params["router"]).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_ids = jax.lax.top_k(probs, k)  # [T, k]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)  # [E]
    assign = jax.nn.one_hot(gate_ids[:, 0], E, dtype=jnp.float32)  # top-1 fraction
    ce = assign.mean(axis=0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_weight

    # ----- slot computation: rank within expert via one-hot cumsum ---------
    flat_ids = gate_ids.reshape(T * k)  # assignment order: token-major
    oh = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)  # [TK, E]
    pos_in_expert = jnp.cumsum(oh, axis=0) - 1  # rank of each assignment
    rank = jnp.take_along_axis(pos_in_expert, flat_ids[:, None], axis=1)[:, 0]  # [TK]
    valid = rank < C
    rank_c = jnp.minimum(rank, C - 1)

    # ----- dispatch: masked scatter-add into [E, C, d] ----------------------
    # (add of masked values: valid assignments own unique (e, c) slots, so no
    # collisions; dropped assignments contribute zero. Keeps the [E, C, d]
    # layout intact so the "experts" sharding annotation survives.)
    token_idx = jnp.repeat(jnp.arange(T), k)
    contrib = xf[token_idx] * valid[:, None].astype(x.dtype)
    xe = jnp.zeros((Ep, C, d), x.dtype).at[flat_ids, rank_c].add(contrib)
    if tuning.FLAGS.moe_explicit_a2a:
        # scatter stays token-local (C over data), then one explicit
        # resharding to expert-parallel layout = the dispatch all-to-all
        xe = shard(xe, None, "a2a_cap", None)
        xe = shard(xe, "experts", None, None)
    else:
        xe = shard(xe, "experts_buf", "expert_cap", None)

    # ----- expert FFN: batched grouped GEMM ---------------------------------
    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])  # [E, C, d]
    if tuning.FLAGS.moe_explicit_a2a:
        ye = shard(ye, "experts", None, None)
        ye = shard(ye, None, "a2a_cap", None)  # combine all-to-all back
    else:
        ye = shard(ye, "experts_buf", "expert_cap", None)

    # ----- combine: gather back, weight, sum over k --------------------------
    per_assign = ye[flat_ids, rank_c] * (
        gate_w.reshape(T * k, 1) * valid[:, None]
    ).astype(ye.dtype)
    out = per_assign.reshape(T, k, d).sum(axis=1)

    if cfg.num_shared_experts:
        sp = params["shared"]
        gs = xf @ sp["w_gate"]
        us = xf @ sp["w_up"]
        out = out + (jax.nn.silu(gs) * us) @ sp["w_down"]

    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Token-motion-free expert parallelism (§Perf, beyond-paper optimization).
#
# Dry-run attribution finding: with pjit-annotation dispatch the partitioner
# materializes/reshards the GLOBAL [E, C, d] buffer (O(T·d) f32 wire bytes
# per layer). But activations are REPLICATED over the "model" axis in this
# framework's layout — each device already holds all the tokens of its data
# shard AND a slice of the experts. So dispatch can be 100% local:
#
#   each device: route local tokens -> local buffer for ITS experts only
#                -> grouped GEMM -> partial token outputs
#   one psum over "model" combines the partials (T_local · d bytes).
#
# Token dropping becomes per-(device, expert) instead of global (same
# expected drop rate, different tail pattern — documented in EXPERIMENTS).
# ---------------------------------------------------------------------------
def moe_mlp_shardmap(
    params: Params, x: jax.Array, cfg, mesh, rules
) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    Ep = _padded_experts(cfg)
    dp_axes = rules.get("batch") or ()
    dp_axes = (dp_axes,) if isinstance(dp_axes, str) else tuple(dp_axes)
    model_ax = "model"
    m_size = mesh.shape[model_ax]
    ep_sharded = Ep % m_size == 0
    E_local = Ep // m_size if ep_sharded else Ep
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    T_local = (B // dp_size if B % dp_size == 0 else B) * S
    cf = tuning.FLAGS.capacity_factor or cfg.capacity_factor
    C_dev = max(8, int(math.ceil(T_local * k / E * cf / 8.0)) * 8)

    bspec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    x_spec = P(bspec, None, None)
    w_spec = P(model_ax if ep_sharded else None, None, None)
    sf = cfg.num_shared_experts * cfg.moe_d_ff
    shared_ff_sharded = ep_sharded and cfg.num_shared_experts and sf % m_size == 0
    sg_spec = P(None, model_ax) if shared_ff_sharded else P(None, None)
    sd_spec = P(model_ax, None) if shared_ff_sharded else P(None, None)

    def local_fn(xl, router, wg, wu, wd, sg, su, sd):
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xf = xl.reshape(Tl, d)
        logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_ids = jax.lax.top_k(probs, k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jax.nn.one_hot(gate_ids[:, 0], E, dtype=jnp.float32).mean(axis=0)
        aux_l = E * jnp.sum(me * ce) * cfg.router_aux_weight
        if dp_axes:
            aux_l = jax.lax.pmean(aux_l, dp_axes)

        # local ranks across ALL experts (local compute, no wire traffic)
        flat_ids = gate_ids.reshape(Tl * k)
        oh = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
        rank = jnp.take_along_axis(
            jnp.cumsum(oh, axis=0) - 1, flat_ids[:, None], axis=1
        )[:, 0]
        # keep only assignments to THIS device's expert slice
        e_lo = (jax.lax.axis_index(model_ax) * E_local) if ep_sharded else 0
        local_e = flat_ids - e_lo
        mine = (local_e >= 0) & (local_e < wg.shape[0]) & (rank < C_dev)
        le = jnp.clip(local_e, 0, wg.shape[0] - 1)
        rc = jnp.minimum(rank, C_dev - 1)
        token_idx = jnp.repeat(jnp.arange(Tl), k)
        contrib = xf[token_idx] * mine[:, None].astype(xl.dtype)
        xe = jnp.zeros((wg.shape[0], C_dev, d), xl.dtype).at[le, rc].add(contrib)

        g = jnp.einsum("ecd,edf->ecf", xe, wg)
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, wd)

        per = ye[le, rc] * (gate_w.reshape(Tl * k, 1) * mine[:, None]).astype(ye.dtype)
        out = per.reshape(Tl, k, d).sum(axis=1)
        if cfg.num_shared_experts and shared_ff_sharded:
            # shared experts ff-sharded over the SAME axis: partial sums ride
            # the same psum as the routed experts (one collective total)
            out = out + (jax.nn.silu(xf @ sg) * (xf @ su)) @ sd
        if ep_sharded:
            out = jax.lax.psum(out, model_ax)  # the ONLY cross-model traffic
        if cfg.num_shared_experts and not shared_ff_sharded:
            out = out + (jax.nn.silu(xf @ sg) * (xf @ su)) @ sd
        return out.reshape(Bl, Sl, d), aux_l

    sp = params.get("shared")
    sg = sp["w_gate"] if sp else jnp.zeros((d, 0), x.dtype)
    su = sp["w_up"] if sp else jnp.zeros((d, 0), x.dtype)
    sd = sp["w_down"] if sp else jnp.zeros((0, d), x.dtype)
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            x_spec, P(None, None), w_spec, w_spec, w_spec,
            sg_spec, sg_spec, sd_spec,
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(
        x, params["router"],
        params["w_gate"], params["w_up"], params["w_down"],
        sg, su, sd,
    )
    return out, aux
