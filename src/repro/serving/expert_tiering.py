"""MoE expert-weight tiering — MaxMem's second Big-Data object (DESIGN §2).

A *page* here is one (layer, expert) weight block (w_gate+w_up+w_down,
~17.3 MB for moonlight-16b-a3b's 2,048 x 1,408 experts in bf16) living in
pooled storage: fast slots = HBM-resident, slow slots = host memory. Routing skew (top-k gating concentrates traffic on
few experts) is the heat signal: each decode/prefill step's routed expert ids
feed the central manager exactly like KV-page touches, and the policy
migrates hot experts into the fast pool with the Pallas page_move kernel.

The jitted forward gathers each layer's expert weights from the pools by
physical slot (``moe_layer_from_pools``), so migrations change real data
placement, not just bookkeeping.
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.manager import CentralManager
from repro.core.types import MigrationPlan
from repro.kernels import ops


class ExpertPools(NamedTuple):
    w_gate: jax.Array  # [n_slots, d, ff]
    w_up: jax.Array  # [n_slots, d, ff]
    w_down: jax.Array  # [n_slots, ff, d]


class ExpertTierManager:
    """Tiered storage + QoS manager for one MoE model's expert weights.

    Logical page id = layer * E + expert. The MODEL is the tenant (one
    t_miss per model; multiple colocated models can each register one)."""

    def __init__(self, cfg, n_fast_slots: int, t_miss: float = 0.1,
                 migration_budget: int = 8, epoch_steps: int = 8):
        self.cfg = cfg
        L, E = cfg.num_layers, cfg.num_experts
        self.n_pages = L * E
        self.n_fast = n_fast_slots
        self.n_slots = self.n_pages  # 1:1 slots (a permutation), like kvcache
        assert n_fast_slots <= self.n_slots
        self.manager = CentralManager(
            num_pages=self.n_pages,
            fast_capacity=n_fast_slots,
            migration_budget=migration_budget,
            max_tenants=2,
            sample_period=1,
            exact_sampling=True,
        )
        self.tenant = self.manager.register(t_miss=t_miss)
        self.manager.allocate(self.tenant, self.n_pages)
        self.slot_of = np.arange(self.n_slots, dtype=np.int32)
        self.epoch_steps = epoch_steps
        self._step = 0
        self.pools: ExpertPools | None = None
        # plan entries that could not be executed because the 1:1 slot
        # layout pairs every promotion with a demotion: an odd plan's
        # remainder is counted here instead of being silently dropped
        self.unpaired_promotes = 0
        self.unpaired_demotes = 0

    # ------------------------------------------------------------- pools
    def build_pools(self, params) -> ExpertPools:
        """Pack stacked MoE weights [L, E, ...] into pooled [L*E, ...]."""
        moe = params["layers"]["moe"]
        L, E = self.cfg.num_layers, self.cfg.num_experts
        Ep = moe["w_gate"].shape[1]

        def pack(w):  # [L, Ep, a, b] -> rows for the REAL experts only
            return w[:, :E].reshape(L * E, *w.shape[2:])

        self.pools = ExpertPools(
            w_gate=pack(moe["w_gate"]),
            w_up=pack(moe["w_up"]),
            w_down=pack(moe["w_down"]),
        )
        return self.pools

    def slot_table(self) -> jax.Array:
        """[L, E] physical slot of each (layer, expert)."""
        L, E = self.cfg.num_layers, self.cfg.num_experts
        return jnp.asarray(self.slot_of.reshape(L, E))

    # ------------------------------------------------------------- accounting
    def record_routing(self, expert_counts: np.ndarray) -> None:
        """expert_counts: [L, E] routed-assignment counts from the step."""
        self.manager.record_access(np.asarray(expert_counts, np.int64).reshape(-1))
        self._step += 1

    def maybe_epoch(self) -> int:
        """Run a policy epoch every epoch_steps; returns pages migrated."""
        if self._step % self.epoch_steps != 0 or self._step == 0:
            return 0
        res = self.manager.run_epoch()
        return self._migrate(res.plan)

    # ------------------------------------------------------------- migration
    def _migrate(self, plan: MigrationPlan) -> int:
        promote = np.asarray(plan.promote)
        demote = np.asarray(plan.demote)
        promote = promote[promote >= 0]
        demote = demote[demote >= 0]
        if len(promote) == 0 and len(demote) == 0:
            return 0
        # every page is allocated (1:1 slots): migrations are PAIRED SWAPS of
        # a promoted page with a demoted page. page_move has gather semantics
        # (all reads see the pre-plan pool), so the swap src=[a,b]/dst=[b,a]
        # is exact with no temp slot.
        src: List[int] = []
        dst: List[int] = []
        promote = [int(p) for p in promote if int(self.slot_of[p]) >= self.n_fast]
        demote = [int(p) for p in demote if int(self.slot_of[p]) < self.n_fast]
        # zip truncates to the shorter side: the unpaired remainder cannot
        # move (no partner slot in a full 1:1 layout) — count it so the
        # telemetry shows the plan was wider than the swaps executed; the
        # policy re-selects still-hot leftovers next epoch
        self.unpaired_promotes += max(len(promote) - len(demote), 0)
        self.unpaired_demotes += max(len(demote) - len(promote), 0)
        for pg_up, pg_down in zip(promote, demote):
            s_up = int(self.slot_of[pg_up])  # slow slot
            s_down = int(self.slot_of[pg_down])  # fast slot
            src.extend([s_up, s_down])
            dst.extend([s_down, s_up])
            self.slot_of[pg_up], self.slot_of[pg_down] = s_down, s_up
        if not src:
            return 0
        sidx = jnp.asarray(src, jnp.int32)
        didx = jnp.asarray(dst, jnp.int32)
        self.pools = ExpertPools(
            *(ops.page_move(w, sidx, didx) for w in self.pools)
        )
        return len(src)

    # ------------------------------------------------------------- telemetry
    def fast_resident(self, layer: int, expert: int) -> bool:
        return int(self.slot_of[layer * self.cfg.num_experts + expert]) < self.n_fast

    def fmmr(self) -> float:
        return self.manager.fmmr_of(self.tenant)

    def fast_share_of_traffic(self, expert_counts: np.ndarray) -> float:
        """Fraction of routed traffic hitting fast-resident experts."""
        flat = np.asarray(expert_counts, np.float64).reshape(-1)
        fast = self.slot_of < self.n_fast
        tot = flat.sum()
        return float(flat[fast].sum() / tot) if tot else 0.0


# --------------------------------------------------------------------------
# Pool-consuming MoE forward (jitted): gathers each layer's expert weights by
# physical slot, so placement changes flow through real compute.
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("cfg",))
def moe_layer_from_pools(
    pools: ExpertPools,
    slots_l: jax.Array,  # [E] physical slots for this layer's experts
    router: jax.Array,  # [d, E]
    x: jax.Array,  # [T, d]
    cfg=None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out [T, d], expert_counts [E])."""
    T, d = x.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    logits = (x.astype(jnp.float32) @ router)
    gate_w, gate_ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    wg = pools.w_gate[slots_l]  # [E, d, ff] gathered by PHYSICAL slot
    wu = pools.w_up[slots_l]
    wd = pools.w_down[slots_l]

    # small-T dense-per-token dispatch (serving decode batch sizes)
    def per_assignment(tok, e, w):
        g = tok @ wg[e]
        u = tok @ wu[e]
        return ((jax.nn.silu(g) * u) @ wd[e]) * w

    out = jnp.zeros((T, d), x.dtype)
    for j in range(k):
        o = jax.vmap(per_assignment)(x, gate_ids[:, j], gate_w[:, j])
        out = out + o.astype(x.dtype)
    counts = jnp.zeros((E,), jnp.int32).at[gate_ids.reshape(-1)].add(1)
    return out, counts
