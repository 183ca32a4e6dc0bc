"""Moonlight-16B-A3B (the DeepSeek-V3 block) at smoke widths on the CPU:
latent attention, the sigmoid router, the held-expert MoE and the latent
KV pool, against the plain reference ``bench/reference/moonlight.py``.

Weights are seeded random float32 (the smoke config's dtype); every
tolerance below is float32 rounding of the same sums taken in another order,
far below what a wrong mechanism moves (a dropped expert or a wrong RoPE
changes logits by a tenth of their RMS or more)."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.drivers.serving_engine import reference_weights  # noqa: E402
from bench.reference import moonlight as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.manager import CentralManager  # noqa: E402
from repro.core.types import TIER_FAST, MigrationPlan  # noqa: E402
from repro.kvcache.paged import TieredPagedKV  # noqa: E402
from repro.models import deepseek  # noqa: E402
from repro.models.model import get_model  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

SMOKE = get_config("moonlight-16b-a3b").smoke()
TOL = 1e-4  # float32: |served - reference| over the reference's logit RMS


def ref_config(cfg) -> ref.Config:
    return ref.Config(cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, cfg.norm_eps, cfg.rope_theta, cfg.moe_top_k,
                      cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.experts_held,
                      cfg.expert_rank)


def rel_err(got, want):
    want = np.asarray(want, np.float64)
    return float((np.abs(np.asarray(got) - want).max(-1) / np.sqrt((want ** 2).mean(-1))).max())


@pytest.fixture(scope="module")
def shared():
    cfg = dataclasses.replace(SMOKE, experts_held=4, expert_rank=1)
    return cfg, jax.jit(get_model(cfg).init)(jax.random.PRNGKey(3))


def test_prefill_then_paged_decode_matches_reference(shared):
    """A prompt prefilled into latent pages, then decoded through them across
    three page boundaries, with the request's pages migrated through
    ``page_move`` mid-decode: every served logit row against the reference's
    full forward over the same tokens, and the same routed experts."""
    cfg, params = shared
    page, n_fast, n_slow = 4, 4, 28
    mgr = CentralManager(num_pages=n_fast + n_slow, fast_capacity=n_fast, migration_budget=8,
                         max_tenants=2, sample_period=1, exact_sampling=True)
    kv = TieredPagedKV(cfg, n_fast, n_slow, page_tokens=page)
    eng = ServingEngine(cfg, params, mgr, kv, max_batch=2, pages_per_seq=6, quest_pages=6,
                        epoch_steps=1000, prompt_bucket=8, keep_logits_every=1)
    eng.add_tenant("a", 0.1)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 6).astype(np.int32)
    eng.submit("a", prompt, max_new_tokens=13)  # decodes positions 6..17: boundaries 8, 12, 16
    moved = 0
    for step in range(12):
        eng.step()
        if step == 4:
            req = eng.lanes[0]
            ids = np.asarray(req.pages, np.int32)
            fast = eng.kv.tier_of_pages(ids) == TIER_FAST
            plan = MigrationPlan(promote=jnp.asarray(ids[~fast]), demote=jnp.asarray(ids[fast]))
            moved = eng.kv.migrate(plan, mgr)
    assert moved > 0 and len(eng.finished) == 1
    req = eng.finished[0]
    positions = sorted(req.logits)
    assert positions == list(range(5, 18))
    tokens = np.concatenate([prompt, np.asarray(req.generated[:-1], np.int32)])
    out = ref.forward(reference_weights(params), ref_config(cfg), tokens, positions,
                      run_ids=req.route_ids, eps=0.0, pad_to=8)
    assert rel_err(np.stack([req.logits[p] for p in positions]), out["logits"]) < TOL
    assert out["route_flips"] == 0 and req.route_ids.shape == (cfg.num_layers - 1, 18, cfg.moe_top_k)


def test_absorbed_decode_equals_expanded_attention(shared):
    cfg, params = shared
    p = jax.tree.map(lambda a: a[0], params["moe_layers"])["attn"]
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 11, cfg.d_model))

    @jax.jit
    def both(p, h):
        q_nope, q_rope, lat = deepseek.mla_project(p, h, jnp.arange(11)[None], cfg)
        full = deepseek.mla_attend_full(p, q_nope, q_rope, lat, cfg)[0, -1]
        padded = jnp.pad(lat, ((0, 0), (0, 0), (0, 40)))  # lanes past latent_dim are ignored
        return full, deepseek.mla_attend_latent(p, q_nope[:, -1], q_rope[:, -1], padded,
                                                jnp.ones((1, 11), bool), cfg)[0]

    full, absorbed = both(p, h)
    np.testing.assert_allclose(absorbed, full, rtol=1e-5, atol=1e-5)


def test_router_choice_and_weights_by_hand():
    cfg = dataclasses.replace(SMOKE, routed_scaling_factor=2.446)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(40, cfg.d_model)).astype(np.float32)
    w = rng.normal(size=(cfg.d_model, cfg.num_experts)).astype(np.float32) / 8
    bias = rng.normal(size=cfg.num_experts).astype(np.float32) * 0.3
    ids, wt = deepseek.route({"router": jnp.asarray(w), "bias": jnp.asarray(bias)}, jnp.asarray(x), cfg)
    s = 1 / (1 + np.exp(-(x.astype(np.float64) @ w)))
    want = np.argsort(-(s + bias), axis=1, kind="stable")[:, : cfg.moe_top_k]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), 1), np.sort(want, 1))
    assert (np.sort(want, 1) != np.sort(np.argsort(-s, 1)[:, : cfg.moe_top_k], 1)).any(), \
        "the bias never changed a choice: the test would not see it ignored"
    sw = np.take_along_axis(s, np.asarray(ids), 1)
    np.testing.assert_allclose(wt, sw / (sw.sum(1, keepdims=True) + 1e-20) * 2.446, rtol=1e-5)


def _moe_params(cfg, key):
    return deepseek.init_moe(jax.random.PRNGKey(key), cfg)


def _by_hand(p, x, ids, wt, lo):
    """Each token's held experts, one at a time, plus the shared experts."""
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    g, u, d = (np.asarray(p[k], np.float64) for k in ("w_gate", "w_up", "w_down"))
    out = np.zeros_like(x, np.float64)
    for t in range(x.shape[0]):
        for e, a in zip(np.asarray(ids[t]), np.asarray(wt[t])):
            j = e - lo
            if 0 <= j < g.shape[0]:
                out[t] += a * (silu(x[t] @ g[j]) * (x[t] @ u[j])) @ d[j]
    sp = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    return out + (silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]


def test_no_pair_dropped_under_skewed_routing():
    """Every token routed to the same held experts: all pairs are computed."""
    cfg = dataclasses.replace(SMOKE, experts_held=8, expert_rank=0)
    p = _moe_params(cfg, 7)
    p["bias"] = p["bias"].at[:cfg.moe_top_k].add(10.0)  # experts 0..k-1 win every token
    x = np.random.default_rng(2).normal(size=(64, cfg.d_model)).astype(np.float32)
    y, ids = jax.jit(deepseek.held_moe, static_argnums=2)(p, jnp.asarray(x), cfg)
    assert (np.sort(np.asarray(ids), 1) == np.arange(cfg.moe_top_k)).all()
    _, wt = deepseek.route(p, jnp.asarray(x), cfg)
    np.testing.assert_allclose(y, _by_hand(p, x, ids, wt, 0), rtol=1e-4, atol=1e-4)


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of one MoE layer, the shared experts counted once,
    add up to the layer with every expert held."""
    full_cfg = dataclasses.replace(SMOKE, experts_held=0)
    p = _moe_params(full_cfg, 11)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(32, full_cfg.d_model)), jnp.float32)
    moe = jax.jit(deepseek.held_moe, static_argnums=2)
    uncut, ids = moe(p, x, full_cfg)
    shared_out = deepseek._swiglu(p["shared"], x)
    H = full_cfg.num_experts // 8
    total = shared_out
    for r in range(8):
        cfg = dataclasses.replace(full_cfg, experts_held=H, expert_rank=r)
        share = dict(p, **{k: p[k][r * H:(r + 1) * H] for k in ("w_gate", "w_up", "w_down")})
        y, ids_r = moe(share, x, cfg)
        np.testing.assert_array_equal(ids_r, ids)  # every chip routes over all experts alike
        total = total + (y - shared_out)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


def test_latent_pool_scrub_reuse_and_move(shared):
    cfg, _ = shared
    mgr = CentralManager(num_pages=16, fast_capacity=4, migration_budget=8, max_tenants=2,
                         sample_period=1, exact_sampling=True)
    kv = TieredPagedKV(cfg, 4, 12, page_tokens=4)
    assert len(kv.pools) == 1 and kv.pools[0].shape[-1] == 128  # 56 latents, one lane tile
    assert kv.page_bytes() == cfg.num_layers * 4 * 128 * 4
    h = mgr.register(0.1)
    pages = mgr.allocate(h, 6)
    rng = np.random.default_rng(0)
    lat = rng.normal(size=(cfg.num_layers, 1, 24, cfg.latent_dim)).astype(np.float32)
    kv.write_tokens((jnp.asarray(lat),), pages[None].astype(np.int32), length=21)
    got = np.concatenate([kv.read_page(p)[0] for p in pages], axis=1)  # [L, 24, 128]
    np.testing.assert_array_equal(got[:, :21, : cfg.latent_dim], lat[:, 0, :21])
    assert not got[:, 21:].any() and not got[:, :, cfg.latent_dim:].any()
    before = {int(p): kv.read_page(p)[0] for p in pages}
    fast = kv.tier_of_pages(pages) == TIER_FAST
    plan = MigrationPlan(promote=jnp.asarray(pages[~fast], jnp.int32),
                         demote=jnp.asarray(pages[fast], jnp.int32))
    assert kv.migrate(plan, mgr) > 0
    for p in pages:
        np.testing.assert_array_equal(kv.read_page(p)[0], before[int(p)])
    kv.free_pages(pages)
    mgr.free(h, pages)
    assert not np.asarray(kv.pools[0]).any(), "a freed page's latents must be scrubbed"
    again = mgr.allocate(h, 2)
    new = rng.normal(size=(cfg.num_layers, 1, 5, cfg.latent_dim)).astype(np.float32)
    kv.write_tokens((jnp.asarray(new),), again[None].astype(np.int32))
    got = np.concatenate([kv.read_page(p)[0] for p in again], axis=1)
    np.testing.assert_array_equal(got[:, :5, : cfg.latent_dim], new[:, 0])
    assert not got[:, 5:].any()


def test_param_count_is_the_published_models():
    cfg = get_config("moonlight-16b-a3b")
    assert abs(cfg.param_count() - 15.96e9) < 0.01e9
    assert 2.8e9 < cfg.active_param_count() < 3.0e9  # "A3B"
    held = dataclasses.replace(cfg, experts_held=8).held_param_count()
    assert abs(held - 3.365e9) < 0.01e9  # one chip's share under EP8


def test_decode_pages_read_counts_each_active_lanes_pooled_pages(shared):
    """``decode_pages_read`` is, over decode steps, the pages the latent kernel
    reads in a layer: each active lane's ``ceil(position / page)``. A request
    of prompt S and n new tokens decodes at positions S .. S + n - 2 (its
    first token comes from the prefill), whichever lane and step it gets."""
    cfg, params = shared
    page = 4
    mgr = CentralManager(num_pages=40, fast_capacity=8, migration_budget=8, max_tenants=2,
                         sample_period=1, exact_sampling=True)
    kv = TieredPagedKV(cfg, 8, 32, page_tokens=page)
    eng = ServingEngine(cfg, params, mgr, kv, max_batch=3, pages_per_seq=6, quest_pages=6,
                        epoch_steps=1000, prompt_bucket=8)
    eng.add_tenant("a", 0.1)
    rng = np.random.default_rng(3)
    specs = [(5, 7), (9, 3), (4, 10), (8, 5)]  # the fourth waits for a lane
    for S, n in specs:
        eng.submit("a", rng.integers(0, cfg.vocab_size, S).astype(np.int32), max_new_tokens=n)
    while len(eng.finished) < len(specs):
        eng.step()
    want = sum(-(-pos // page) for S, n in specs for pos in range(S, S + n - 1))
    assert eng.counters()["decode_pages_read"] == want
    assert eng.decode_steps < sum(n - 1 for _, n in specs)  # lanes decoded side by side
