"""Multi-tenant tiered-KV serving driver (the paper's scenario, end to end).

    PYTHONPATH=src python -m repro.launch.serve            # published widths
    PYTHONPATH=src python -m repro.launch.serve --smoke    # toy widths (CPU)

Builds the model (``--arch``, qwen2.5-3b by default, at its published widths
unless ``--smoke``; weights are random, made from ``--seed``; a model with
latent attention, ``moonlight-16b-a3b``, holds ``--experts-held`` of each MoE
layer's experts, by default 8 of 64: one chip's share of expert-parallel
serving over eight, since all 64 do not fit one chip), a queue-mode
MaxMem central manager over a fast and a slow slot range of the paged KV
cache, registers a latency-sensitive (``ls``) and a best-effort (``be``)
tenant, and runs continuous-batching decode with Quest page selection until
every request finished, printing per-tenant FMMR and the pages moved each
epoch — Figure 4 of the paper, live on the real serving stack instead of the
simulator. The ``be`` requests are admitted first, so their prompts take the
fast slots and the ``ls`` tenant's pages have to be migrated in.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Callable, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.core.manager import CentralManager
from repro.core.types import TIER_FAST
from repro.kvcache.paged import TieredPagedKV
from repro.models.model import get_model
from repro.serving.engine import ServingEngine

# published widths on one chip: ~0.6 GB of bf16 KV for qwen2.5-3b
FULL = dict(fast_pages=256, slow_pages=768, page_tokens=16, lanes=8,
            requests=8, prompt_tokens=512, new_tokens=32, quest_pages=4,
            epoch_steps=4, queue_size=256)
SMOKE = dict(fast_pages=8, slow_pages=120, page_tokens=4, lanes=2,
             requests=2, prompt_tokens=16, new_tokens=60, quest_pages=3,
             epoch_steps=4, queue_size=32)


# experts held a MoE layer by a latent-attention model (full widths, --smoke)
EXPERTS_HELD = (8, 2)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--experts-held", type=int, default=None,
                    help=f"latent-attention MoE models: experts held a layer "
                         f"(default {EXPERTS_HELD[0]}, {EXPERTS_HELD[1]} with --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="toy widths and a small cache (CPU runs)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ls-target", type=float, default=0.1)
    for k in ("fast_pages", "slow_pages", "page_tokens", "quest_pages", "new_tokens"):
        ap.add_argument("--" + k.replace("_", "-"), type=int, default=None,
                        help=f"default {FULL[k]} ({SMOKE[k]} with --smoke)")
    args = ap.parse_args(argv)
    for k, v in (SMOKE if args.smoke else FULL).items():
        if getattr(args, k, None) is None:
            setattr(args, k, v)
    return args


def load_model(args: argparse.Namespace):
    """(cfg, params): the architecture at published widths (or its smoke
    cut), parameters drawn from ``args.seed`` in one compiled program."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if cfg.is_mla:
        held = args.experts_held if args.experts_held is not None else EXPERTS_HELD[args.smoke]
        cfg = dataclasses.replace(cfg, experts_held=held)
    params = jax.jit(get_model(cfg).init)(jax.random.PRNGKey(args.seed))
    return cfg, params


def build_engine(cfg, params, args: argparse.Namespace) -> ServingEngine:
    """Queue-mode manager + tiered KV cache + engine with the two tenants."""
    n_pages = args.fast_pages + args.slow_pages
    manager = CentralManager(
        num_pages=n_pages,
        fast_capacity=args.fast_pages,
        migration_budget=max(args.fast_pages // 8, 8),
        max_tenants=4,
        sample_period=1,
        exact_sampling=True,
        queue_size=args.queue_size,
        seed=args.seed,
    )
    kv = TieredPagedKV(cfg, args.fast_pages, args.slow_pages,
                       page_tokens=args.page_tokens)
    pages_per_seq = -(-(args.prompt_tokens + args.new_tokens) // args.page_tokens)
    eng = ServingEngine(
        cfg, params, manager, kv,
        max_batch=args.lanes, pages_per_seq=pages_per_seq,
        quest_pages=args.quest_pages, epoch_steps=args.epoch_steps,
        seed=args.seed,
    )
    eng.add_tenant("ls", t_miss=args.ls_target)
    eng.add_tenant("be", t_miss=1.0)
    return eng


def submit_requests(eng: ServingEngine, args: argparse.Namespace) -> None:
    """``args.requests`` prompts of random tokens, the ``be`` half first."""
    rng = np.random.default_rng(args.seed)
    n_be = (args.requests + 1) // 2
    for i in range(args.requests):
        prompt = rng.integers(1, eng.cfg.vocab_size, args.prompt_tokens)
        eng.submit("be" if i < n_be else "ls", prompt, max_new_tokens=args.new_tokens)


def run_to_completion(
    eng: ServingEngine,
    on_step: Optional[Callable[[ServingEngine], None]] = None,
    log=print,
) -> None:
    """Step until the queue and every lane are empty; one line per epoch."""
    log(f"{'step':>5} {'LS fmmr':>8} {'BE fmmr':>8} {'LS fast':>8} "
        f"{'BE fast':>8} {'moved':>6}")
    while eng.queue or any(r is not None for r in eng.lanes):
        eng.step()
        if on_step is not None:
            on_step(eng)
        if eng._epoch_log and eng._epoch_log[-1]["step"] == eng.step_count:
            e = eng._epoch_log[-1]
            owner, tier = eng.manager.owners(), eng.manager.tiers()
            fast = {
                n: int(((owner == int(h)) & (tier == TIER_FAST)).sum())
                for n, h in eng.tenant_handles.items()
            }
            log(f"{e['step']:>5} {e['fmmr'].get('ls', 0):>8.3f} "
                f"{e['fmmr'].get('be', 0):>8.3f} {fast['ls']:>8} "
                f"{fast['be']:>8} {e['moved']:>6}")


def main(argv=None) -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    args = parse_args(argv)
    cfg, params = load_model(args)
    eng = build_engine(cfg, params, args)
    submit_requests(eng, args)
    run_to_completion(eng)
    for t in ("ls", "be"):
        pct = eng.latency_percentiles(t)
        if pct:
            print(f"{t}: modeled page-read p50={pct['p50'] * 1e6:.1f}us "
                  f"p99={pct['p99'] * 1e6:.1f}us mean={pct['mean'] * 1e6:.1f}us")
    print(f"migrated pages total: {eng._migrated_pages}")
    print(f"completed requests: {len(eng.finished)}")


if __name__ == "__main__":
    main()
