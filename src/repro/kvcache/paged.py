"""Tiered paged KV cache.

A *logical page* (what MaxMem tracks and migrates) is a block of
``page_tokens`` consecutive tokens of one sequence, spanning ALL layers and
both K and V — for yi-6b with 16-token pages that is ~0.5 MB, i.e. exactly a
huge-page-sized migration unit (DESIGN.md §2).

Physically, pools are [L, n_slots, page, nkv, dh] for K and V, in the
model's compute dtype. Slots [0, n_fast) are the fast tier and slots
[n_fast, n_slots) the slow tier; today both are slot ranges of one HBM
array, and a slow tier in host memory is future work. ``slot_of`` maps
logical page id -> physical slot; migration copies slot contents across the
boundary and rewrites the mapping — block tables hold logical ids and never
change.

Page heat summaries (Quest-style per-page key min/max) ride along for the
top-k page selector in the serving engine.

Free/reuse invariant (DESIGN.md §8): the slot of an unallocated logical page
always holds zeroed K/V content and reset (±inf) Quest summaries. Two paths
maintain it: :meth:`TieredPagedKV.free_pages` scrubs slots when a sequence
finishes, and :meth:`TieredPagedKV.migrate` re-scrubs the vacated source
rows its swaps hand to free holders (``page_move`` has gather semantics, so
a swapped-out row otherwise retains a stale copy of the migrated page).
Without the invariant, a reused page's ``write_tokens`` folds max/min
against the PREVIOUS owner's summaries, corrupting Quest top-k selection.
"""
from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.manager import CentralManager
from repro.core.types import TIER_FAST, TIER_SLOW, MigrationPlan
from repro.kernels import ops


@partial(jax.jit, donate_argnums=(0,))
def _move_slots(pools, src, dst):
    """Move slot ``src[i]`` to slot ``dst[i]`` in every layer of every
    [L, n_slots, *row] pool, in place: one ``page_move`` per pool over its
    [L * n_slots, *row] row view (row id = layer * n_slots + slot)."""
    out = []
    for pool in pools:
        L, n = pool.shape[:2]
        base = (jnp.arange(L, dtype=jnp.int32) * n)[:, None]
        rows = pool.reshape(L * n, *pool.shape[2:])
        rows = ops.page_move(rows, (base + src).reshape(-1), (base + dst).reshape(-1))
        out.append(rows.reshape(pool.shape))
    return tuple(out)


class TieredPagedKV:
    def __init__(
        self,
        cfg,
        n_fast_slots: int,
        n_slow_slots: int,
        page_tokens: int = 16,
    ):
        self.cfg = cfg
        self.page = page_tokens
        self.n_fast = n_fast_slots
        self.n_slots = n_fast_slots + n_slow_slots
        L, nkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.d_head
        self.k_pool = jnp.zeros((L, self.n_slots, page_tokens, nkv, dh), cfg.cdtype)
        self.v_pool = jnp.zeros((L, self.n_slots, page_tokens, nkv, dh), cfg.cdtype)
        # Quest summaries (per layer): elementwise min/max of keys in the page
        self.k_max = jnp.full((L, self.n_slots, nkv, dh), -jnp.inf, jnp.float32)
        self.k_min = jnp.full((L, self.n_slots, nkv, dh), jnp.inf, jnp.float32)
        # logical page id -> physical slot. Identity at boot: manager hands
        # out page ids with tier semantics (id < n_fast iff fast at alloc).
        self.slot_of = np.arange(self.n_slots, dtype=np.int32)
        self._slot_owner = np.full(self.n_slots, -1, np.int32)  # logical page or -1

    # ------------------------------------------------------------ mapping
    def slots_for(self, logical_pages: np.ndarray) -> np.ndarray:
        return self.slot_of[np.asarray(logical_pages)]

    def page_bytes(self) -> int:
        L, nkv, dh = self.cfg.num_layers, self.cfg.num_kv_heads, self.cfg.d_head
        return L * 2 * self.page * nkv * dh * self.k_pool.dtype.itemsize

    # ------------------------------------------------------------ writes
    def write_tokens(
        self,
        layer_kv: Tuple[jax.Array, jax.Array],  # k,v: [L, B, T, nkv, dh]
        logical_pages: np.ndarray,  # [B, n_pages_of_write] logical ids
        start_pos: int,
    ) -> None:
        """Scatter T tokens (from prefill) into pages. Host-side loop over
        pages — prefill writes are not the steady-state hot path."""
        k, v = layer_kv
        L, B, T, nkv, dh = k.shape
        p = self.page
        for b in range(B):
            for j in range((start_pos + T + p - 1) // p):
                lo = max(j * p - start_pos, 0)
                hi = min((j + 1) * p - start_pos, T)
                if hi <= lo:
                    continue
                slot = int(self.slot_of[int(logical_pages[b, j])])
                off = (start_pos + lo) % p
                kb = k[:, b, lo:hi]
                vb = v[:, b, lo:hi]
                self.k_pool = jax.lax.dynamic_update_slice(
                    self.k_pool, kb[:, None].astype(self.k_pool.dtype), (0, slot, off, 0, 0)
                )
                self.v_pool = jax.lax.dynamic_update_slice(
                    self.v_pool, vb[:, None].astype(self.v_pool.dtype), (0, slot, off, 0, 0)
                )
                kmax = jnp.maximum(self.k_max[:, slot], kb.max(axis=1).astype(jnp.float32))
                kmin = jnp.minimum(self.k_min[:, slot], kb.min(axis=1).astype(jnp.float32))
                self.k_max = self.k_max.at[:, slot].set(kmax)
                self.k_min = self.k_min.at[:, slot].set(kmin)

    def _scrub_slots(self, slots: np.ndarray) -> None:
        """Reset the given physical slots to the free-slot state: zero K/V
        content, ±inf Quest summaries (one fused device update per pool)."""
        if len(slots) == 0:
            return
        s = jnp.asarray(np.asarray(slots, np.int32))
        self.k_pool = self.k_pool.at[:, s].set(0)
        self.v_pool = self.v_pool.at[:, s].set(0)
        self.k_max = self.k_max.at[:, s].set(-jnp.inf)
        self.k_min = self.k_min.at[:, s].set(jnp.inf)

    def free_pages(self, logical_pages) -> None:
        """Scrub the slots of freed logical pages (call BEFORE or after the
        manager's ``free`` — the slot mapping is engine-owned either way).

        Without this, a reused page's ``write_tokens`` does maximum/minimum
        against the previous owner's stale Quest summaries — corrupting
        top-k page selection — and its pool slot leaks the prior sequence's
        KV bytes. The reuse round-trip test locks decode on a reused cache
        bit-equal to a fresh one."""
        ids = np.asarray(logical_pages, np.int32)
        if ids.size == 0:
            return
        self._scrub_slots(self.slot_of[ids])

    # ------------------------------------------------------------ migration
    def apply_drained(self, promote_ids, demote_ids, manager: CentralManager) -> int:
        """Commit a drained queue batch (commit-on-completion): the manager's
        queue tick already flipped the tier metadata of exactly these pages,
        so the KV pool moves the same ids. -1-padded id lists as emitted in
        ``QueueStats.drained_promote_ids`` / ``drained_demote_ids``."""
        return self.migrate(
            MigrationPlan(
                promote=jnp.asarray(np.asarray(promote_ids, np.int32).ravel()),
                demote=jnp.asarray(np.asarray(demote_ids, np.int32).ravel()),
            ),
            manager,
        )

    def migrate(self, plan: MigrationPlan, manager: CentralManager) -> int:
        """Execute a MaxMem plan: move page data across the tier boundary and
        rewrite slot_of. Demotions first (they free fast slots). Returns the
        number of pages moved."""
        promote = np.asarray(plan.promote)
        demote = np.asarray(plan.demote)
        promote = promote[promote >= 0]
        demote = demote[demote >= 0]
        if len(promote) == 0 and len(demote) == 0:
            return 0

        # slot_of is a permutation: "free" slots are those whose logical
        # holder is unallocated in the manager. Moving a page swaps its
        # mapping with such a holder (whose slot content is garbage).
        owner = np.asarray(manager.pages.owner)
        inv = np.empty_like(self.slot_of)
        inv[self.slot_of] = np.arange(self.n_slots, dtype=np.int32)
        free_fast = [s for s in range(self.n_fast) if owner[inv[s]] < 0]
        free_slow = [s for s in range(self.n_fast, self.n_slots) if owner[inv[s]] < 0]

        moves_src: List[int] = []
        moves_dst: List[int] = []

        def _swap(pg: int, dst: int):
            src = int(self.slot_of[pg])
            holder = int(inv[dst])  # unallocated logical page holding dst
            self.slot_of[pg] = dst
            self.slot_of[holder] = src
            inv[dst] = pg
            inv[src] = holder
            moves_src.append(src)
            moves_dst.append(dst)
            return src

        for pg in demote:
            if int(self.slot_of[pg]) >= self.n_fast:
                continue  # already slow (idempotent)
            if not free_slow:
                break
            freed = _swap(int(pg), free_slow.pop())
            free_fast.append(freed)
        for pg in promote:
            if int(self.slot_of[pg]) < self.n_fast:
                continue
            if not free_fast:
                break  # plan over-eager for the slots actually available
            freed = _swap(int(pg), free_fast.pop())
            free_slow.append(freed)
        if not moves_src:
            return 0

        # pad to a power of two so plan sizes reuse a few compiled programs;
        # the pad repeats move 0, whose duplicate gathers read the same
        # pre-plan row and write the same bytes
        m = len(moves_src)
        pad = (1 << (m - 1).bit_length()) - m
        self.k_pool, self.v_pool, self.k_max, self.k_min = _move_slots(
            (self.k_pool, self.v_pool, self.k_max, self.k_min),
            jnp.asarray(moves_src + moves_src[:1] * pad, jnp.int32),
            jnp.asarray(moves_dst + moves_dst[:1] * pad, jnp.int32),
        )
        # page_move is a gather: a swapped-out source row keeps a stale COPY
        # of the migrated page's data. Any such row now held by a free
        # logical page must be re-scrubbed or the free/reuse invariant
        # breaks the moment a migration swaps with a free holder.
        freed_rows = np.asarray(
            [r for r in moves_src if owner[inv[r]] < 0], np.int32
        )
        self._scrub_slots(freed_rows)
        return len(moves_src)

    # ------------------------------------------------------------ telemetry
    def tier_of_pages(self, logical_pages: np.ndarray) -> np.ndarray:
        return np.where(self.slots_for(logical_pages) < self.n_fast, TIER_FAST, TIER_SLOW)

    def read_page(self, logical_page: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host copy of one logical page's (k, v) contents — [L, page, nkv,
        dh] each, independent of where the page physically lives. The
        migration-integrity tests read pages back across a migrate() and
        assert bit-equality."""
        slot = int(self.slot_of[int(logical_page)])
        return np.asarray(self.k_pool[:, slot]), np.asarray(self.v_pool[:, slot])
