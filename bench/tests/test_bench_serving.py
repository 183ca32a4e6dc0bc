"""The serving driver (``bench/drivers/serving_engine.py``) on a CPU-sized
cut of ``moonlight_ep8``: a sound run is correct under the committed limits,
and a run whose KV page is altered after a move, or a reference that holds
the wrong experts, is not."""

import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout and src/ on the path)
from bench import check
from bench.drivers import serving_engine as drv
from bench.generator import build_schedule, load_json
from repro.kvcache.paged import TieredPagedKV

CELL = "moonlight_ep8.mixed_long"
SEED = 2**33 + 5


def tiny_config() -> dict:
    """moonlight_ep8 at toy widths (float32), 3 layers, 2 of 16 experts held;
    a 256-page pool, 4-token pages, 4 lanes."""
    cfg = load_json("configs", "moonlight_ep8")
    cfg.update(hidden_size=64, intermediate_size=96, kv_lora_rank=32, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=256,
               n_routed_experts=16, num_experts=2, expert_rank=1, num_experts_per_tok=4,
               torch_dtype="float32")
    cfg["manager"] = dict(cfg["manager"], num_pages=256, fast_capacity=48, migration_budget=8,
                          queue_size=16)
    cfg["serving"] = dict(cfg["serving"], page_tokens=4, max_batch=4, pages_per_seq=32,
                          quest_pages=32, epoch_steps=4, prompt_bucket=32)
    cfg["audit"] = dict(cfg["audit"], finished=6, longest_live=1, moved_live=1, every=8, pad_to=32)
    for t in cfg["tenants"]:
        t["pages"] = 128
    return cfg


def tiny_mix() -> dict:
    mix = load_json("traffic", "mixed_long")
    spec = lambda med, lo, hi: {"median": med, "sigma": 0.8, "min": lo, "max": hi}  # noqa: E731
    return dict(mix, warmup_epochs=2, quantiles=8, rate_per_s=50.0, requests={
        "chat": {"share": 0.6, "prompt": spec(20, 6, 48), "output": spec(10, 4, 24)},
        "docs": {"share": 0.4, "prompt": spec(48, 24, 64), "output": spec(16, 8, 24)}})


def serve(seconds: float = 2.0, audit_all: bool = False):
    cfg = tiny_config()
    if audit_all:  # every finished and every running request
        cfg["audit"].update(finished=1000, longest_live=1000, moved_live=1000)
    sched = build_schedule(cfg, tiny_mix(), SEED)
    c = drv.Cell(sched)
    for _ in range(sched.warmup_epochs):
        c.step()
    win = drv.run_window(c, seconds)
    return sched, win, c.record()


def verdict(sched, rec):
    numbers = drv.compare(sched, rec)
    limits = check.load_limits(CELL, drv.CHECKS)
    return check.verdict(numbers, limits), numbers


@pytest.fixture(scope="module")
def sound():
    return serve()


def test_sound_run_is_correct(sound):
    sched, win, rec = sound
    ok, n = verdict(sched, rec)
    assert ok, n
    assert win["moved_pages"] > 0 and win["compiles"] == 0
    assert n["compared_requests"] == 8 and n["compared_running"] == 2, n
    assert n["logit_err"] < 1e-4  # float32 on both sides


def test_reference_with_the_wrong_expert_share_is_not(sound):
    sched, _, rec = sound
    moe = dict(rec["weights"]["moe"])
    for k in ("w_gate", "w_up", "w_down"):  # each held slot gets its neighbour's expert
        moe[k] = jnp.roll(moe[k], 1, axis=1)
    ok, n = verdict(sched, dict(rec, weights=dict(rec["weights"], moe=moe)))
    assert not ok and n["logit_err"] > 0.1, n


def test_latent_page_altered_after_a_move_is_not(monkeypatch):
    migrate = TieredPagedKV.migrate
    altered = []

    def alter(self, plan, manager):
        moved = migrate(self, plan, manager)
        if moved and not altered:
            ids = np.concatenate([np.asarray(plan.promote), np.asarray(plan.demote)])
            page = int(ids[ids >= 0][0])
            slot = int(self.slot_of[page])
            self.pools = (self.pools[0].at[:, slot].add(1.0),)
            altered.append(page)
        return moved

    monkeypatch.setattr(TieredPagedKV, "migrate", alter)
    sched, _, rec = serve(audit_all=True)  # the altered page's request among them
    ok, n = verdict(sched, rec)
    assert altered and not ok, n
