"""Pool-backed page data plane: the Pallas-kernel-driven DMA analogue.

``PagePool`` holds actual page *contents* in one device pool whose rows are
physical frames: rows ``[0, F)`` are fast-tier frames, ``[F, F + P)`` slow
frames, and the last row is the reserved trash row that pads fixed-size
plans (the convention ``kernels/page_copy.py`` documents). A host-side frame
table maps page id -> frame; the control plane (allocate/free) is host
bookkeeping, while every data movement goes through the Pallas kernels:

  * migrations  — ONE :func:`repro.kernels.ops.page_move` call per
    drained batch: demote entries first (their vacated fast frames are
    legally reused as promote destinations — the kernel gathers every
    source row before it writes any), then promotes, padded to a fixed
    plan size with trash-row self-copies so plan shapes never retrace;
  * bulk writes — tenant data is staged host-side and DMA'd into frames
    with :func:`repro.kernels.ops.page_copy` (staging pool -> page
    pool), again trash-padded to the fixed plan size.

``CentralManager(data_plane_elems=...)`` owns a pool and feeds it the
drained id lists from each epoch's queue tick (or the instant-apply plan),
so simulated placements and actual page bytes can never diverge — which is
what the data-integrity tests assert.

``on_allocate``, ``on_free``, ``write_pages`` and ``execute`` each sit in one
``maxmem.pool.<method>`` profiler span per call (see core/manager.py).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import TIER_FAST
from repro.kernels import ops


class PagePool:
    def __init__(
        self,
        num_pages: int,
        fast_capacity: int,
        row_elems: int = 128,
        dtype=jnp.float32,
        plan_slots: int = 64,
    ):
        self.num_pages = num_pages
        self.fast_capacity = fast_capacity
        self.row_elems = row_elems
        self.plan_slots = plan_slots
        self.trash = fast_capacity + num_pages  # reserved last row
        self.pool = jnp.zeros((self.trash + 1, row_elems), dtype)
        self.frame = np.full(num_pages, -1, np.int64)  # page -> frame row
        # LIFO free lists; fast frames are scarce, slow frames can hold all
        self._free_fast = list(range(fast_capacity - 1, -1, -1))
        self._free_slow = list(range(self.trash - 1, fast_capacity - 1, -1))
        self.moved_pages = 0  # cumulative pages DMA'd by migrations
        # Fault injection (core/faults.py). With an injector attached each
        # page move runs through its bounded-retry loop; moves that exhaust
        # the budget are abandoned — the page keeps its source-tier frame
        # (commit-on-completion fallback: degraded, never corrupt) and its
        # id lands in ``last_failed`` so the manager can revert the
        # already-flipped tier metadata.
        self.fault_injector = None
        self.last_failed = (np.empty(0, np.int64), np.empty(0, np.int64))

    def set_fault_injector(self, injector) -> None:
        """Attach (or with ``None`` detach) a ``FaultInjector``."""
        self.fault_injector = injector

    # ------------------------------------------------------------ control
    @partial(jax.profiler.annotate_function, name="maxmem.pool.on_allocate")
    def on_allocate(self, page_ids: Sequence[int], tiers: Sequence[int]) -> None:
        """Assign a frame (in the page's tier) to each newly allocated page."""
        for p, t in zip(np.asarray(page_ids), np.asarray(tiers)):
            free = self._free_fast if t == TIER_FAST else self._free_slow
            self.frame[p] = free.pop()

    @partial(jax.profiler.annotate_function, name="maxmem.pool.on_free")
    def on_free(self, page_ids: Sequence[int]) -> None:
        for p in np.asarray(page_ids):
            f = int(self.frame[p])
            if f < 0:
                continue
            (self._free_fast if f < self.fast_capacity else self._free_slow).append(f)
            self.frame[p] = -1

    # --------------------------------------------------------------- data
    @partial(jax.profiler.annotate_function, name="maxmem.pool.write_pages")
    def write_pages(self, page_ids: Sequence[int], rows: np.ndarray) -> None:
        """DMA tenant data into page frames (staging -> pool, page_copy).

        At most two staging chunks are on the device at once: each chunk is
        uploaded while the previous one's copy runs, and the call returns
        once the last copy has landed. Left unpaced, the uploads of an
        arrival pile up on the device as far as the host runs ahead of the
        transfers (six 2 MB chunks at once in one run of the paper's box),
        so the device's peak memory would depend on timing."""
        ids = np.asarray(page_ids, np.int64)
        rows = np.asarray(rows)
        M = self.plan_slots
        for lo in range(0, len(ids), M):
            chunk = ids[lo : lo + M]
            staging = np.zeros((M, self.row_elems), rows.dtype)
            staging[: len(chunk)] = rows[lo : lo + len(chunk)]
            src = np.arange(M, dtype=np.int32)
            dst = np.full(M, self.trash, np.int32)
            dst[: len(chunk)] = self.frame[chunk]
            args = (jnp.asarray(staging, self.pool.dtype), jnp.asarray(src), jnp.asarray(dst))
            self.pool.block_until_ready()  # the previous chunk's copy, and its staging, are done
            self.pool = ops.page_copy(args[0], self.pool, args[1], args[2])
        self.pool.block_until_ready()

    def read_page(self, page_id: int) -> np.ndarray:
        f = int(self.frame[page_id])
        assert f >= 0, f"page {page_id} has no frame"
        return np.asarray(self.pool[f])

    # ---------------------------------------------------------- migration
    @partial(jax.profiler.annotate_function, name="maxmem.pool.execute")
    def execute(self, demote_ids, promote_ids) -> int:
        """Move drained pages across tiers; returns pages moved.

        ``demote_ids``/``promote_ids`` are -1-padded id lists (the queue
        tick's drained lists, or an instant-mode plan's sides). Demotes are
        planned first so their vacated fast frames can serve as promote
        destinations within the same ``page_move`` call — the kernel gathers
        every source row before it writes any (the write-after-read contract
        ``tests/test_kernels.py`` locks).
        """
        dem = np.asarray(demote_ids).ravel()
        pro = np.asarray(promote_ids).ravel()
        dem = dem[dem >= 0]
        pro = pro[pro >= 0]
        fi = self.fault_injector
        failed_dem, failed_pro = [], []
        src, dst = [], []
        for p in dem:
            if fi is not None and int(self.frame[p]) >= self.fast_capacity:
                # already physically slow: an earlier promote of this page
                # failed, and the policy has now demoted it again — the
                # "move" is already satisfied, no DMA needed
                continue
            if fi is not None and not fi.attempt_move():
                # abandoned after the retry budget: the page keeps its fast
                # frame, so this batch's promotes have one fewer slot
                failed_dem.append(int(p))
                continue
            f = int(self.frame[p])
            src.append(f)
            dst.append(self._free_slow.pop())
            self.frame[p] = dst[-1]
            self._free_fast.append(f)  # reusable by this batch's promotes
        freed_slow = []
        for p in pro:
            if fi is not None:
                if int(self.frame[p]) < self.fast_capacity:
                    # already physically fast (an earlier failed demote
                    # kept its frame): nothing to move
                    continue
                if not self._free_fast:
                    # a failed demote kept its frame: refuse rather than
                    # oversubscribe the fast tier
                    fi.no_frame += 1
                    failed_pro.append(int(p))
                    continue
                if not fi.attempt_move():
                    failed_pro.append(int(p))
                    continue
            f = int(self.frame[p])
            src.append(f)
            dst.append(self._free_fast.pop())
            self.frame[p] = dst[-1]
            freed_slow.append(f)  # released only after the sweep: a demote
            # destination must never alias a row this sweep still reads
        self.last_failed = (
            np.asarray(failed_dem, np.int64),
            np.asarray(failed_pro, np.int64),
        )
        n = len(src)
        M = self.plan_slots
        for lo in range(0, n, M):
            s = np.full(M, self.trash, np.int32)
            d = np.full(M, self.trash, np.int32)
            s[: len(src[lo : lo + M])] = src[lo : lo + M]
            d[: len(dst[lo : lo + M])] = dst[lo : lo + M]
            self.pool = ops.page_move(self.pool, jnp.asarray(s), jnp.asarray(d))
        self._free_slow.extend(freed_slow)
        self.moved_pages += n
        return n

    # ------------------------------------------------------------- checks
    def check(self, tier: Optional[np.ndarray] = None) -> None:
        """Frame-table invariants (tests): frames are a bijection onto used
        rows, fast frames exactly back fast-tier pages, free lists disjoint."""
        used = self.frame[self.frame >= 0]
        assert len(np.unique(used)) == len(used), "frame table not injective"
        assert self.trash not in used, "trash row assigned to a page"
        free = self._free_fast + self._free_slow
        assert not set(free) & set(used.tolist()), "free list overlaps used"
        assert len(set(free)) == len(free), "duplicate free frames"
        assert len(free) + len(used) == self.trash, "frames leaked"
        if tier is not None:
            fast_pages = np.flatnonzero(np.asarray(tier) == TIER_FAST)
            backed = self.frame[fast_pages]
            assert (backed >= 0).all(), "fast page without a frame"
            assert (backed < self.fast_capacity).all(), "fast page on slow frame"
