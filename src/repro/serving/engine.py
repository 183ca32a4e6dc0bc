"""Multi-tenant serving engine over the tiered paged KV cache.

Continuous batching: requests from multiple tenants (each with its own MaxMem
``t_miss`` target) share one fixed decode batch. Every step:

  1. admit queued requests into free batch lanes (dense prefill -> pages);
     a request whose pages cannot be allocated yet exerts *backpressure*
     (it waits in FIFO order) without head-of-line blocking smaller
     requests behind it
  2. one batched paged-decode step (Quest top-k page selection)
  3. report the selected-page access stream to the central manager
  4. on page-boundary crossings, first-touch allocate new pages
  5. every ``epoch_steps`` decode steps: run the MaxMem epoch. With a
     queue-mode manager (``queue_size > 0``) the epoch's DRAINED batch is
     committed to the KV pools (commit-on-completion: selections still in
     flight move no bytes); an instant-apply manager executes the whole
     plan immediately. Either way the Pallas ``page_move`` data plane does
     the actual copies.
  6. finished sequences free their pages back to the tiered pool AND scrub
     their KV slots (zero content, ±inf Quest summaries) so a reused page
     never folds against a prior owner's stale summaries

A step-latency model (HBM vs host-DMA page reads) attributes per-tenant
decode latency so QoS benchmarks can measure p50/p99 per tenant.

Prefill runs the model's ``paged_prefill`` (``serving/paged_model.py``) on
the prompt padded to a multiple of ``prompt_bucket`` tokens (1: unpadded;
with a larger bucket a few compiled programs serve every length, and
:meth:`ServingEngine.warm` compiles them) and writes its cache rows into the
request's pages in one device scatter. The model decides the cache's layout:
grouped-query attention's ``(k, v)`` pools with Quest summaries, or one
latent pool that multi-head latent attention decodes exactly over every
page. A model that returns routed expert ids (DeepSeek-V3's held-expert
MoE) has them counted: ``held_pairs`` is the routed pairs that hit an expert
this chip holds.

Each step is a ``jax.profiler.TraceAnnotation`` span ``serve.<step>``:
``admit``, ``prefill``, ``decode``, ``record_access``, ``epoch`` and
``finish``. Counters: ``tokens_decoded``, ``prompt_tokens_prefilled``,
``held_pairs``, ``kv_pages_moved``, ``admission_blocked`` and
``decode_pages_read`` (:meth:`counters`): the pages latent attention's paged
kernel reads in one layer, summed over decode steps (each active lane's
pooled tokens in whole pages; 0 where Quest selects the pages).
With ``keep_logits_every`` > 0 every request keeps its logits at the
prefill's last position, at every that-many-th decode position after it and
at its last position (``Request.logits``), and, for an MLA model, its routed
ids at every position (``Request.route_ids``): the record an audit against
a reference reads.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.manager import CentralManager, TenantHandle
from repro.core.types import TIER_FAST
from repro.kvcache.paged import TieredPagedKV
from repro.serving.paged_model import paged_decode_step, paged_prefill

span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    rid: int
    tenant: str
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int
    # runtime
    generated: List[int] = dataclasses.field(default_factory=list)
    lane: int = -1
    pages: List[int] = dataclasses.field(default_factory=list)
    submit_step: int = 0
    admit_step: int = -1
    finish_step: int = -1
    # kept with keep_logits_every > 0: position -> f32[V] logits, and routed
    # ids [L_moe, positions, k] in position order (MLA)
    logits: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    routes: List[np.ndarray] = dataclasses.field(default_factory=list)
    tail: Optional[tuple] = None  # (position, logits) of the latest decode step
    pages_moved: int = 0  # its pages that changed tier while it held them

    @property
    def route_ids(self) -> Optional[np.ndarray]:
        return np.concatenate(self.routes, axis=1) if self.routes else None

    @property
    def queue_delay_steps(self) -> int:
        """Decode steps spent waiting for admission (backpressure)."""
        return max(self.admit_step - self.submit_step, 0)


@dataclasses.dataclass
class StepLatency:
    fast_pages: int
    slow_pages: int
    seconds: float


class ServingEngine:
    def __init__(
        self,
        cfg,
        params,
        manager: CentralManager,
        kv: TieredPagedKV,
        *,
        max_batch: int = 8,
        pages_per_seq: int = 16,
        quest_pages: int = 4,
        epoch_steps: int = 8,
        fast_page_s: float = 1e-6,
        slow_page_s: float = 20e-6,
        seed: int = 0,
        prompt_bucket: int = 1,
        keep_logits_every: int = 0,
    ):
        self.cfg = cfg
        self.params = params
        self.manager = manager
        self.kv = kv
        self.prompt_bucket = prompt_bucket
        self.keep_logits_every = keep_logits_every
        self.held_lo = cfg.expert_rank * cfg.held_experts if cfg.experts_held else 0
        self.max_batch = max_batch
        self.n_p = pages_per_seq
        self.quest_pages = quest_pages
        self.epoch_steps = epoch_steps
        self.fast_page_s = fast_page_s
        self.slow_page_s = slow_page_s

        self.tenant_handles: Dict[str, TenantHandle] = {}
        self.queue: Deque[Request] = deque()
        self.lanes: List[Optional[Request]] = [None] * max_batch
        self.tables = np.full((max_batch, pages_per_seq), -1, np.int32)
        self.positions = np.zeros(max_batch, np.int32)
        self.step_count = 0
        self._rid = 0
        self._latencies: Dict[str, List[float]] = {}
        self._migrated_pages = 0
        self.admission_blocked = 0  # allocation-failure backpressure events
        self.tokens_decoded = 0
        self.prompt_tokens_prefilled = 0
        self.held_pairs = 0
        self.decode_steps = 0
        self.decode_context_tokens = 0  # sum over decode steps of active context lengths
        self.decode_pages_read = 0  # pages the latent kernel reads a layer, over decode steps
        self.held_expert_reads = 0  # distinct (layer, held expert) a decode step's tokens hit
        self.prefill_sq_tokens = 0  # sum over prefills of prompt length squared
        self._epoch_log: List[dict] = []
        self.finished: List[Request] = []
        self.last_logits: Optional[np.ndarray] = None  # [B, V] of last step

    # ------------------------------------------------------------- tenants
    def add_tenant(self, name: str, t_miss: float) -> None:
        self.tenant_handles[name] = self.manager.register(t_miss)
        self._latencies[name] = []

    def set_target(self, name: str, t_miss: float) -> None:
        self.manager.set_target(self.tenant_handles[name], t_miss)

    # ------------------------------------------------------------- requests
    def submit(self, tenant: str, prompt: np.ndarray, max_new_tokens: int) -> int:
        prompt = np.asarray(prompt, np.int32)
        max_tokens = self.n_p * self.kv.page
        if len(prompt) > max_tokens:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the per-sequence "
                f"page table: pages_per_seq={self.n_p} x page={self.kv.page} "
                f"= {max_tokens} tokens"
            )
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        self._rid += 1
        self.queue.append(
            Request(
                rid=self._rid,
                tenant=tenant,
                prompt=prompt,
                max_new_tokens=max_new_tokens,
                submit_step=self.step_count,
            )
        )
        return self._rid

    # ------------------------------------------------------------- admission
    def _admit(self) -> None:
        with span("serve.admit"):
            self._admit_queued()

    def _admit_queued(self) -> None:
        free_lanes = [i for i, r in enumerate(self.lanes) if r is None]
        blocked: List[Request] = []
        while free_lanes and self.queue:
            req = self.queue.popleft()
            S = len(req.prompt)
            h = self.tenant_handles[req.tenant]
            n_pages = (S + self.kv.page - 1) // self.kv.page
            try:
                pages = self.manager.allocate(h, n_pages)
            except MemoryError:
                # backpressure: the request keeps waiting (FIFO order is
                # preserved below) but does NOT head-of-line block smaller
                # requests behind it from taking this lane
                self.admission_blocked += 1
                blocked.append(req)
                continue
            lane = free_lanes.pop(0)
            req.pages = list(map(int, pages))
            req.lane = lane
            req.admit_step = self.step_count
            self.lanes[lane] = req
            self.tables[lane, :] = -1
            self.tables[lane, :n_pages] = req.pages
            with span("serve.prefill"):
                logits = self._prefill_into_pages(req)
            # prefill accesses: every page of the prompt touched once
            counts = np.zeros(self.manager.num_pages, np.int64)
            counts[req.pages] += 1
            with span("serve.record_access"):
                self.manager.record_access(counts)
            if self.keep_logits_every:
                req.logits[S - 1] = logits
            req.generated.append(int(np.argmax(logits)))
            self.positions[lane] = S  # next token index to write
            self.prompt_tokens_prefilled += S
            self.prefill_sq_tokens += S * S
        for req in reversed(blocked):
            self.queue.appendleft(req)

    def _bucket(self, S: int) -> int:
        b = self.prompt_bucket
        return -(-S // b) * b

    def _prefill_into_pages(self, req: Request) -> np.ndarray:
        """Forward of the padded prompt, its first ``S`` cache rows
        scattered into the request's pages; returns the f32 logits at the
        last position."""
        S = len(req.prompt)
        toks = np.zeros((1, self._bucket(S)), np.int32)
        toks[0, :S] = req.prompt
        logits, rows, ids = paged_prefill(self.params, jnp.asarray(toks),
                                          jnp.asarray(np.int32(S - 1)), cfg=self.cfg)
        self.kv.write_tokens(rows, np.asarray([req.pages], np.int32), length=S)
        if ids is not None:
            ids = np.asarray(ids)[:, :S]  # [L_moe, S, k]
            self.held_pairs += int(self._held(ids).sum())
            if self.keep_logits_every:
                req.routes.append(ids.astype(np.int16))
        return np.asarray(logits[0], np.float32)

    def _held(self, ids: np.ndarray) -> np.ndarray:
        local = ids - self.held_lo
        return (local >= 0) & (local < self.cfg.held_experts)

    def warm(self, max_prompt: int, max_moves: int) -> None:
        """Compile every prefill bucket up to ``max_prompt`` tokens and the
        page scrubs and moves up to ``max_moves`` pages, writing nothing."""
        for S in range(self.prompt_bucket, self._bucket(max_prompt) + 1, self.prompt_bucket):
            _, rows, _ = paged_prefill(self.params, jnp.zeros((1, S), jnp.int32),
                                       jnp.asarray(np.int32(0)), cfg=self.cfg)
            self.kv.write_tokens(rows, np.full((1, self.n_p), -1, np.int32))
        self.kv.warm(max(max_moves, self.n_p))

    def counters(self) -> Dict[str, int]:
        return {"tokens_decoded": self.tokens_decoded,
                "prompt_tokens_prefilled": self.prompt_tokens_prefilled,
                "held_pairs": self.held_pairs, "kv_pages_moved": self._migrated_pages,
                "admission_blocked": self.admission_blocked,
                "decode_pages_read": self.decode_pages_read}

    # ------------------------------------------------------------- stepping
    def _ensure_page(self, lane: int) -> bool:
        """Allocate the page for the position about to be written."""
        req = self.lanes[lane]
        p_idx = int(self.positions[lane]) // self.kv.page
        if p_idx >= self.n_p:
            return False  # out of table space: finish the request
        if self.tables[lane, p_idx] >= 0:
            return True
        h = self.tenant_handles[req.tenant]
        try:
            pages = self.manager.allocate(h, 1)
        except MemoryError:
            return False
        self.tables[lane, p_idx] = int(pages[0])
        req.pages.append(int(pages[0]))
        return True

    def step(self) -> Dict[str, StepLatency]:
        self._admit()
        active_mask = np.array([r is not None for r in self.lanes])
        if not active_mask.any():
            self.step_count += 1
            return {}
        for lane, req in enumerate(self.lanes):
            if req is not None and not self._ensure_page(lane):
                self._finish(lane)
                active_mask[lane] = False
        if not active_mask.any():
            self.step_count += 1
            return {}

        tokens = np.array(
            [
                (r.generated[-1] if r is not None and r.generated else 0)
                for r in self.lanes
            ],
            np.int32,
        )
        slot_tables = np.where(self.tables >= 0, self.kv.slot_of[np.maximum(self.tables, 0)], -1)
        with span("serve.decode"):
            out = paged_decode_step(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(self.positions),
                jnp.asarray(slot_tables.astype(np.int32)),
                jnp.asarray(self.tables),
                jnp.asarray(active_mask),
                self.kv.pools,
                num_logical_pages=self.manager.num_pages,
                cfg=self.cfg,
                quest_pages=self.quest_pages,
            )
            logits, pools, counts = out[:3]
            self.kv.pools = tuple(pools)
            counts_np = np.asarray(counts, np.int64)
            self.last_logits = np.asarray(logits)
            routes = np.asarray(out[3]) if len(out) > 3 else None  # [L_moe, B, k]
        with span("serve.record_access"):
            self.manager.record_access(counts_np)
        self.decode_steps += 1
        self.decode_context_tokens += int((self.positions + 1)[active_mask].sum())
        if self.cfg.is_mla:  # the paged kernel stops at each lane's last pooled page
            self.decode_pages_read += int((-(-self.positions // self.kv.page))[active_mask].sum())
        if routes is not None:
            held = self._held(routes) & active_mask[None, :, None]
            self.held_pairs += int(held.sum())
            self.held_expert_reads += sum(
                len(np.unique(routes[l][held[l]])) for l in range(routes.shape[0]))

        # ---- latency attribution: page tiers touched this step -------------
        lat: Dict[str, StepLatency] = {}
        touched = np.flatnonzero(counts_np > 0)
        owner = self.manager.owners()
        for name, h in self.tenant_handles.items():
            mine = touched[(owner[touched] == int(h))] if len(touched) else touched
            nf = int((self.manager.tier_of(mine) == TIER_FAST).sum()) if len(mine) else 0
            ns = len(mine) - nf
            sec = nf * self.fast_page_s + ns * self.slow_page_s
            if len(mine):
                lat[name] = StepLatency(fast_pages=nf, slow_pages=ns, seconds=sec)
                self._latencies[name].append(sec)

        # ---- token bookkeeping ---------------------------------------------
        greedy = np.argmax(self.last_logits, axis=-1)
        every = self.keep_logits_every
        for lane, req in enumerate(self.lanes):
            if req is None or not active_mask[lane]:
                continue
            pos = int(self.positions[lane])
            req.generated.append(int(greedy[lane]))
            self.tokens_decoded += 1
            last = len(req.generated) >= req.max_new_tokens
            if every:
                if routes is not None:
                    req.routes.append(routes[:, lane, None].astype(np.int16))
                if (pos - len(req.prompt) + 1) % every == 0:
                    req.logits[pos] = self.last_logits[lane].copy()
                req.tail = (pos, self.last_logits[lane])
            self.positions[lane] += 1
            if last:
                self._finish(lane)

        self.step_count += 1
        # ---- MaxMem epoch ----------------------------------------------------
        if self.step_count % self.epoch_steps == 0:
            with span("serve.epoch"):
                self._epoch()
        return lat

    def _epoch(self) -> None:
        res = self.manager.run_epoch()
        if res.stats.queue is not None:
            # queue mode: only the DRAINED batch moves bytes this epoch
            # (commit-on-completion); enqueued selections still in
            # flight keep serving from their source tier
            q = res.stats.queue
            ids = (np.asarray(q.drained_promote_ids), np.asarray(q.drained_demote_ids))
            moved = self.kv.apply_drained(*ids, self.manager)
        else:
            ids = (np.asarray(res.plan.promote), np.asarray(res.plan.demote))
            moved = self.kv.migrate(res.plan, self.manager)
        self._migrated_pages += moved
        flat = np.concatenate([a.ravel() for a in ids])
        changed = set(flat[flat >= 0].tolist())
        for req in self.lanes:
            if req is not None and changed:
                req.pages_moved += len(changed.intersection(req.pages))
        self._epoch_log.append(
            {
                "step": self.step_count,
                "moved": moved,
                "queue_depth": res.queue_depth,
                "fmmr": {
                    n: float(self.manager.fmmr_of(h))
                    for n, h in self.tenant_handles.items()
                },
            }
        )

    def _finish(self, lane: int) -> None:
        with span("serve.finish"):
            self._release(lane)

    def _release(self, lane: int) -> None:
        req = self.lanes[lane]
        if req.tail is not None:  # its last position, however the request ends
            pos, row = req.tail
            req.logits[pos] = row.copy()
            req.tail = None
        req.finish_step = self.step_count
        h = self.tenant_handles[req.tenant]
        if req.pages:
            # scrub the KV slots BEFORE releasing the ids: a freed page's
            # slot must hold zero content and ±inf Quest summaries so the
            # next owner starts from a fresh page (free/reuse invariant)
            self.kv.free_pages(req.pages)
            self.manager.free(h, np.asarray(req.pages, np.int32))
        self.tables[lane, :] = -1
        self.positions[lane] = 0
        self.lanes[lane] = None
        self.finished.append(req)

    def run(self, n_steps: int) -> None:
        for _ in range(n_steps):
            self.step()

    # ------------------------------------------------------------- telemetry
    @property
    def migrated_bytes(self) -> int:
        """Bytes physically moved across the tier boundary so far."""
        return self._migrated_pages * self.kv.page_bytes()

    def latency_percentiles(self, tenant: str):
        xs = np.asarray(self._latencies.get(tenant, []))
        if len(xs) == 0:
            return {}
        return {
            "p50": float(np.percentile(xs, 50)),
            "p90": float(np.percentile(xs, 90)),
            "p99": float(np.percentile(xs, 99)),
            "mean": float(xs.mean()),
        }
