"""Plain forward pass of Moonlight-16B-A3B (the DeepSeek-V3 block), the
reference the serving cells of ``moonlight_*`` are compared with.

Written from the published description (HF ``modeling_deepseek_v3`` with
the model's ``config.json``), in plain ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no pages, no
batching, no kernels, and nothing imported from the program under test. One
sequence at a time, its whole causal forward pass, attention computed in
query blocks so that 8,192 tokens fit; the sequence is padded at its end to
a multiple of ``pad_to`` tokens (causal attention keeps the padding out of
every real position), so a few compiled programs serve every length.

* Attention is multi-head latent attention in its plain form: ``q = h W_q``
  (q_lora_rank null), ``W_kva h`` -> ``c_kv`` (RMS-normed) and ``k_pe``;
  ``kv_b_proj`` expands ``c_kv`` to each head's ``k_nope`` and ``v``; RoPE
  (``rope_theta``) on the rope dims after de-interleaving them, as that file
  does; scale ``1/sqrt(qk_nope + qk_rope)``.
* The first ``first_k_dense_replace`` layers have a dense SwiGLU; the others
  an MoE layer: ``s = sigmoid(h W_g)``, the top-k of ``s + bias``, weights
  ``s`` there over their sum (+1e-20) times ``routed_scaling_factor``, plus
  the shared experts.
* The same expert share as the program: this chip holds experts
  ``[expert_rank * experts_held, +experts_held)`` and adds only theirs; pairs
  routed to the others add nothing (``experts_held`` 0: all are held).

Routing follows the run where the choice is a near tie: given the run's
routed ids, where the reference's margin (its k-th biased score less its
(k+1)-th) is at most ``eps`` and the sets differ, the reference takes the
run's experts (with its own scores as weights); where the margin exceeds
``eps`` and the sets differ, it keeps its own and counts a flip.

Weights are a dict of arrays of any float dtype: ``embed`` [V, d], ``lm_head``
[d, V], ``final_norm`` [d]; ``dense`` and ``moe``, each a dict of arrays
stacked over its layers: ``attn_norm``, ``w_q``, ``w_kva``, ``kv_norm``,
``w_kvb``, ``w_o``, ``mlp_norm`` and ``w_gate``, ``w_up``, ``w_down`` (the
dense SwiGLU, or the held experts' [H, d, f] / [H, f, d]); ``moe`` adds
``router`` [d, E], ``bias`` [E] and ``shared_gate``, ``shared_up``,
``shared_down``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


class Config(NamedTuple):
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    experts_held: int
    expert_rank: int


def config_of(doc: Dict) -> Config:
    """The reference's settings from a configuration file (the published
    ``config.json`` keys, plus the share: ``num_experts`` held here and
    ``expert_rank``)."""
    held = doc.get("num_experts", 0)
    return Config(doc["num_attention_heads"], doc["kv_lora_rank"], doc["qk_nope_head_dim"],
                  doc["qk_rope_head_dim"], doc["v_head_dim"], float(doc["rms_norm_eps"]),
                  float(doc["rope_theta"]), doc["num_experts_per_tok"],
                  float(doc["routed_scaling_factor"]), bool(doc["norm_topk_prob"]),
                  0 if held == doc["n_routed_experts"] else held, doc.get("expert_rank", 0))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, pos, theta):
    """x [S, h, dr]: de-interleave, then ``x cos + rotate_half(x) sin``."""
    S, h, dr = x.shape
    x = x.reshape(S, h, dr // 2, 2).swapaxes(-1, -2).reshape(S, h, dr)
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr)
    emb = jnp.concatenate([pos[:, None] * inv] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., dr // 2:], x[..., : dr // 2]], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _attention(w, h, cfg: Config, q_block: int):
    S = h.shape[0]
    nh, c = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pos = jnp.arange(S, dtype=F32)
    q = (h @ w["w_q"].astype(F32)).reshape(S, nh, dn + dr)
    kva = h @ w["w_kva"].astype(F32)
    c_kv = _rms(kva[:, :c], w["kv_norm"], cfg.rms_norm_eps)
    k_pe = _rope(kva[:, None, c:], pos, cfg.rope_theta)
    kv = (c_kv @ w["w_kvb"].astype(F32)).reshape(S, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg.rope_theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, nh, dr))], axis=-1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        causal = jnp.arange(S)[None, :] <= (i * q_block + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(S // q_block)).reshape(S, nh * dv)
    return o @ w["w_o"].astype(F32)


def _swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g.astype(F32)) * (x @ u.astype(F32))) @ d.astype(F32)


def _moe(w, h, run_ids, valid, eps, cfg: Config):
    k = cfg.num_experts_per_tok
    s = jax.nn.sigmoid(h @ w["router"].astype(F32))
    vals, top = jax.lax.top_k(s + w["bias"].astype(F32), k + 1)
    ref, margin = top[:, :k], vals[:, k - 1] - vals[:, k]
    same = (jnp.sort(ref, axis=-1) == jnp.sort(run_ids, axis=-1)).all(-1)
    given = (run_ids >= 0).all(-1)
    follow = given & ~same & (margin <= eps)
    flips = (given & ~same & (margin > eps) & valid).sum()
    chosen = jnp.where(follow[:, None], run_ids, ref)
    wt = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.norm_topk_prob:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    wt = wt * cfg.routed_scaling_factor
    H = w["w_gate"].shape[0]
    lo = cfg.expert_rank * H if cfg.experts_held else 0
    comb = jnp.where(chosen[:, :, None] == lo + jnp.arange(H), wt[:, :, None], 0.0).sum(1)
    g = jnp.einsum("td,edf->tef", h, w["w_gate"].astype(F32))
    u = jnp.einsum("td,edf->tef", h, w["w_up"].astype(F32))
    y = jnp.einsum("tef,efd->td", jax.nn.silu(g) * u * comb[:, :, None], w["w_down"].astype(F32))
    y = y + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    return y, flips, (follow & valid).sum()


@partial(jax.jit, static_argnames=("cfg", "q_block"))
def _hidden(weights, tokens, run_ids, n_valid, eps, cfg: Config, q_block: int):
    eps_ = cfg.rms_norm_eps
    valid = jnp.arange(tokens.shape[0]) < n_valid
    x = weights["embed"][tokens].astype(F32)

    def attn(w, x):
        return x + _attention(w, _rms(x, w["attn_norm"], eps_), cfg, q_block)

    def dense(x, w):
        x = attn(w, x)
        return x + _swiglu(_rms(x, w["mlp_norm"], eps_), w["w_gate"], w["w_up"], w["w_down"]), None

    def moe(carry, inp):
        x, flips, followed = carry
        w, ids = inp
        x = attn(w, x)
        y, f, fo = _moe(w, _rms(x, w["mlp_norm"], eps_), ids, valid, eps, cfg)
        return (x + y, flips + f, followed + fo), None

    x, _ = jax.lax.scan(dense, x, weights["dense"])
    zero = jnp.zeros((), jnp.int32)
    (x, flips, followed), _ = jax.lax.scan(moe, (x, zero, zero), (weights["moe"], run_ids))
    return x, flips, followed


@jax.jit
def _logits(weights, h, eps):
    return _rms(h, weights["final_norm"], eps) @ weights["lm_head"].astype(F32)


def forward(weights, cfg: Config, tokens: Sequence[int], positions: Sequence[int],
            run_ids: Optional[np.ndarray] = None, eps: float = 0.0, pad_to: int = 1024,
            q_block: int = 512) -> Dict:
    """The reference over ``tokens`` (one sequence). Returns ``logits``
    (f32[len(positions), V], numpy) at ``positions``, and ``route_flips`` and
    ``route_followed`` over the real tokens when ``run_ids`` ([L_moe, S, k],
    the run's routed ids) is given."""
    S = len(tokens)
    S_pad = -(-S // pad_to) * pad_to
    toks = np.zeros(S_pad, np.int32)
    toks[:S] = tokens
    n_moe = weights["moe"]["router"].shape[0]
    ids = np.full((n_moe, S_pad, cfg.num_experts_per_tok), -1, np.int32)
    if run_ids is not None:
        ids[:, :S] = run_ids
    with jax.default_matmul_precision("highest"):
        h, flips, followed = _hidden(weights, jnp.asarray(toks), jnp.asarray(ids),
                                     jnp.asarray(S, jnp.int32), jnp.asarray(eps, F32),
                                     cfg=cfg, q_block=min(q_block, S_pad))
        n = len(positions)  # padded to a power of two, so a few programs serve every count
        at = np.full(1 << max(n - 1, 0).bit_length(), positions[-1], np.int32)
        at[:n] = positions
        logits = _logits(weights, h[jnp.asarray(at)], jnp.asarray(cfg.rms_norm_eps, F32))
    return {"logits": np.asarray(logits)[:n], "route_flips": int(flips),
            "route_followed": int(followed)}
