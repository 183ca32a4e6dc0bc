"""Tiered paged KV cache.

A *logical page* (what MaxMem tracks and migrates) is a block of
``page_tokens`` consecutive tokens of one sequence, spanning ALL layers and
both K and V — for yi-6b with 16-token pages that is ~0.5 MB, i.e. exactly a
huge-page-sized migration unit (DESIGN.md §2).

Physically a cache is a tuple of ``[L, n_slots, *row]`` pools:

* grouped-query attention keeps ``(k, v)``, each row ``[page, nkv, dh]`` in
  the compute dtype, plus the Quest summaries ``(kmax, kmin)``, per-page
  key max/min rows ``[nkv, dh]`` in float32 for the top-k page selector;
* multi-head latent attention (``cfg.is_mla``) keeps one latent pool, rows
  ``[page, latent_dim]``: each token's ``c_kv || k_rope``, shared by every
  head, and no summaries (its decode attends to every page exactly). Each
  latent is padded with zeros to whole 128-lane tiles (576 -> 640), the
  row the TPU lays out without moving the slot axis (``lane_width``).

Slots [0, n_fast) are the fast tier and slots [n_fast, n_slots) the slow
tier; today both are slot ranges of one HBM array, and a slow tier in host
memory is future work. ``slot_of`` maps logical page id -> physical slot;
migration copies slot contents across the boundary in every pool and
rewrites the mapping — block tables hold logical ids and never change.

Free/reuse invariant (DESIGN.md §8): the slot of an unallocated logical page
always holds zeroed content and reset (±inf) Quest summaries. Two paths
maintain it: :meth:`TieredPagedKV.free_pages` scrubs slots when a sequence
finishes, and :meth:`TieredPagedKV.migrate` re-scrubs the vacated source
rows its swaps hand to free holders (``page_move`` has gather semantics, so
a swapped-out row otherwise retains a stale copy of the migrated page).
Without the invariant, a reused page's ``write_tokens`` folds max/min
against the PREVIOUS owner's summaries, corrupting Quest top-k selection.

Writes, scrubs and moves are each one jitted device update over every pool
(the pools donated), with index lists padded to a power of two so a few
compiled programs serve every size; :meth:`TieredPagedKV.warm` compiles them.
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


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def lane_width(n: int) -> int:
    """A row of ``n`` elements padded to the TPU's 128-lane tile. A pool
    whose minor dimension is not a multiple of it is laid out on the device
    with the slots innermost, and every gather or scatter by slot then
    copies the whole pool."""
    return -(-n // 128) * 128


def put_rows(pool, slots, rows, op: str = "set"):
    """``pool[l, slots[t]] <op>= rows[l, t]`` for every layer, in place: one
    scatter over the pool's ``[L * n_slots, ...]`` row view (a scatter into
    the 4-D or 5-D pool by two index arrays is not done in place on the
    TPU). Out-of-range slots are dropped; rows narrower than the pool's last
    dimension are padded with zeros."""
    L, n = pool.shape[:2]
    idx = jnp.arange(L, dtype=jnp.int32)[:, None] * n + slots[None, :]
    idx = jnp.where((slots < n)[None, :], idx, L * n).reshape(-1)
    rows = rows.astype(pool.dtype)
    pad = pool.shape[-1] - rows.shape[-1]
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])
    flat = pool.reshape(L * n, *pool.shape[2:])
    at = flat.at[idx]
    vals = rows.reshape(idx.shape[0], *flat.shape[1:])
    out = {"set": at.set, "max": at.max, "min": at.min}[op](vals, mode="drop")
    return out.reshape(pool.shape)


def put_tokens(pool, slots, offs, rows):
    """Write token ``t``'s row ``rows[l, t]`` at page offset ``offs[t]`` of
    slot ``slots[t]`` in every layer of a ``[L, n_slots, page, ...]`` pool."""
    L, n, page = pool.shape[:3]
    flat = pool.reshape(L, n * page, *pool.shape[3:])
    tok = jnp.where(slots < n, slots * page + offs, n * page)
    return put_rows(flat, tok, rows).reshape(pool.shape)


@partial(jax.jit, static_argnames=("n_content",), donate_argnums=(0,))
def _scatter_tokens(pools, rows, slots, offs, n_content: int):
    """Write token rows into pages: ``rows[i]`` is ``[L, T, *tail]`` for
    content pool ``i``; token ``t`` goes to (``slots[t]``, ``offs[t]``), and a
    slot out of range drops it. Summary pools after the content ones fold
    the first pool's rows (keys) in by max and min."""
    with jax.named_scope("mla.latent_write"):
        out = [put_tokens(p, slots, offs, r) for p, r in zip(pools[:n_content], rows)]
    if len(pools) > n_content:
        k = rows[0].astype(jnp.float32)
        out.append(put_rows(pools[n_content], slots, k, "max"))
        out.append(put_rows(pools[n_content + 1], slots, k, "min"))
    return tuple(out)


@partial(jax.jit, static_argnames=("values",), donate_argnums=(0,))
def _scrub(pools, slots, values):
    """Reset the given slots of every pool to its free value (out-of-range
    slots, the padding, are dropped)."""
    return tuple(put_rows(p, slots, jnp.full((p.shape[0], slots.shape[0], *p.shape[2:]), v, p.dtype))
                 for p, v in zip(pools, values))


class TieredPagedKV:
    def __init__(
        self,
        cfg,
        n_fast_slots: int,
        n_slow_slots: int,
        page_tokens: int = 16,
        dtype=None,
    ):
        """``dtype`` stores the content pools in another dtype than the
        model's compute dtype (a lower-precision control); decode computes
        in the compute dtype either way."""
        self.cfg = cfg
        self.page = page_tokens
        self.n_fast = n_fast_slots
        self.n_slots = n_fast_slots + n_slow_slots
        L, n, dt = cfg.num_layers, self.n_slots, dtype or cfg.cdtype
        if cfg.is_mla:  # latents padded to whole lane tiles; the pad stays zero
            self.pools = (jnp.zeros((L, n, page_tokens, lane_width(cfg.latent_dim)), dt),)
            self._free = (0.0,)
        else:
            nkv, dh = cfg.num_kv_heads, cfg.d_head
            self.pools = (
                jnp.zeros((L, n, page_tokens, nkv, dh), dt),
                jnp.zeros((L, n, page_tokens, nkv, dh), dt),
                # Quest summaries (per layer): elementwise min/max of keys in the page
                jnp.full((L, n, nkv, dh), -jnp.inf, jnp.float32),
                jnp.full((L, n, nkv, dh), jnp.inf, jnp.float32),
            )
            self._free = (0.0, 0.0, -float("inf"), float("inf"))
        self.n_content = 1 if cfg.is_mla else 2
        # logical page id -> physical slot. Identity at boot: manager hands
        # out page ids with tier semantics (id < n_fast iff fast at alloc).
        self.slot_of = np.arange(self.n_slots, dtype=np.int32)

    # the grouped-query pools by name
    k_pool = property(lambda self: self.pools[0])
    v_pool = property(lambda self: self.pools[1])
    k_max = property(lambda self: self.pools[2])
    k_min = property(lambda self: self.pools[3])

    # ------------------------------------------------------------ mapping
    def slots_for(self, logical_pages: np.ndarray) -> np.ndarray:
        return self.slot_of[np.asarray(logical_pages)]

    def page_bytes(self) -> int:
        """Bytes of one logical page's content (summaries not counted)."""
        return sum(int(np.prod(p.shape[2:])) * p.dtype.itemsize * p.shape[0]
                   for p in self.pools[: self.n_content])

    # ------------------------------------------------------------ writes
    def write_tokens(
        self,
        rows,  # per content pool [L, B, T, *tail]: (k, v) or (latent,)
        logical_pages: np.ndarray,  # [B, n_pages_of_write] logical ids
        start_pos: int = 0,
        length: int | None = None,
    ) -> None:
        """Scatter the first ``length`` (default all T) tokens of each
        sequence, from position ``start_pos`` on, into their pages: one
        device update for every pool. Tokens past ``length`` (a padded
        prompt's tail) are not written."""
        L, B, T = rows[0].shape[:3]
        p = self.page
        t = np.arange(T)
        pos = start_pos + t
        tables = np.asarray(logical_pages).reshape(B, -1)
        pages = tables[:, np.minimum(pos // p, tables.shape[1] - 1)]  # [B, T]
        keep = (t < (T if length is None else length))[None, :] & (pages >= 0)
        slots = np.where(keep, self.slot_of[np.maximum(pages, 0)], self.n_slots)
        offs = np.broadcast_to(pos % p, (B, T))
        flat = tuple(r.reshape(L, B * T, *r.shape[3:]) for r in rows)
        self.pools = _scatter_tokens(
            self.pools, flat, jnp.asarray(slots.reshape(-1).astype(np.int32)),
            jnp.asarray(offs.reshape(-1).astype(np.int32)), n_content=self.n_content)

    def _scrub_slots(self, slots: np.ndarray) -> None:
        """Reset the given physical slots to the free-slot state: zero
        content, ±inf Quest summaries (one device update for every pool)."""
        if len(slots) == 0:
            return
        s = np.full(_pow2(len(slots)), self.n_slots, np.int32)
        s[: len(slots)] = slots
        self.pools = _scrub(self.pools, jnp.asarray(s), self._free)

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

    def warm(self, max_pages: int) -> None:
        """Compile the scrub and move programs for every padded size up to
        ``max_pages`` pages, without changing any slot's contents."""
        n = 1
        while True:
            self.pools = _scrub(self.pools, jnp.full(n, self.n_slots, jnp.int32), self._free)
            same = jnp.zeros(n, jnp.int32)  # slot 0 onto itself
            self.pools = _move_slots(self.pools, same, same)
            if n >= max_pages:
                break
            n *= 2
        jax.block_until_ready(self.pools)

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
        free = np.flatnonzero(owner[inv] < 0)
        free_fast = free[free < self.n_fast].tolist()
        free_slow = free[free >= self.n_fast].tolist()

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
        pad = _pow2(len(moves_src)) - len(moves_src)
        self.pools = _move_slots(
            self.pools,
            jnp.asarray(np.asarray(moves_src + moves_src[:1] * pad, np.int32)),
            jnp.asarray(np.asarray(moves_dst + moves_dst[:1] * pad, np.int32)),
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

    def read_page(self, logical_page: int) -> Tuple[np.ndarray, ...]:
        """Host copy of one logical page's content, one array per content
        pool (``(k, v)``, each [L, page, nkv, dh]; or ``(latent,)``),
        independent of where the page physically lives. The
        migration-integrity tests read pages back across a migrate() and
        assert bit-equality."""
        slot = int(self.slot_of[int(logical_page)])
        return tuple(np.asarray(p[:, slot]) for p in self.pools[: self.n_content])
